"""The window-and-summary page format (``page_format="eva"``) of
``PagedKVCacheManager`` on the CPU: two chains a sequence in one pool
(the window's pages, released at every window's end; one summary row a
page the window chain fills), the arithmetic admission books by, the
table a step hands the kernel, invariants and the sanitizer over both
chains, and each refusal by name. Pages of 8, a window of 64: a window's
8 summary rows fill one page."""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from paddle_tpu.incubate.nn import PagedKVCacheManager  # noqa: E402
from paddle_tpu.incubate.nn.paged_cache import (  # noqa: E402
    HostKVSwapSpace,
)

P, W, HEADS, D = 8, 64, 2, 16


def pool(num_pages=64, **kw):
    kw.setdefault("sanitizer", "strict")
    return PagedKVCacheManager(num_pages, P, HEADS, D, dtype=jnp.float32,
                               page_format="eva", window_tokens=W, **kw)


def book(c, seq_ids, counts, n_pad=64):
    """One step's booking as the adapter asks for it."""
    return c.book_step(seq_ids, counts, len(seq_ids), 16, n_pad)


def used(c):
    return c.num_pages - c.num_free_pages


# -- arithmetic ------------------------------------------------------------
@pytest.mark.parametrize("n, at_most, held, width", [
    (0, 0, 0, 0), (1, 2, 1, 1), (8, 2, 2, 1), (9, 3, 3, 2),
    (64, 9, 9, 8), (65, 10, 2, 2), (72, 10, 3, 2), (128, 10, 10, 9),
    (129, 11, 3, 3), (200, 12, 5, 4), (640, 18, 18, 17),
])
def test_pages_of_a_sequence(n, at_most, held, width):
    """``pages_for``: one window's pages and a summary page a 64 tokens
    at most, what admission reserves; ``pages_held``: what the sequence
    holds at n; ``table_pages``: its table row, the earlier windows'
    summary pages (whole pages) then the window's."""
    c = pool(sanitizer="off")
    assert (c.pages_for(n), c.pages_held(n), c.table_pages(n)) == \
        (at_most, held, width)
    assert c.pages_held(n) <= c.pages_for(n)


@pytest.mark.parametrize("page_format", ["kv", "latent"])
def test_one_chain_formats_hold_a_page_a_page_of_tokens(page_format):
    c = PagedKVCacheManager(8, 16, 1, 32, page_format=page_format)
    for n in (0, 1, 16, 17, 100):
        want = -(-n // 16)
        assert c.pages_for(n) == c.pages_held(n) == c.table_pages(n) == want
    c.alloc("a")
    assert c.chunk_room("a") is None


def test_the_published_geometry():
    """2,048 / 16: a row at 10,240 bytes holds at most 128 + 40 pages."""
    c = PagedKVCacheManager(8, 16, 1, 8, page_format="eva",
                            window_tokens=2048, sanitizer="off")
    assert c.pages_for(10240) == 168 and c.window_pages == 128
    assert c.table_pages(10240) == 8 * 4 + 128
    assert c.pages_held(2049) == 1 + 8


@pytest.mark.parametrize("kw, word", [
    (dict(page_format="kv", window_tokens=64), "window_tokens"),
    (dict(page_format="eva"), "window_tokens"),
    (dict(page_format="eva", window_tokens=96), "page_size^2"),
    (dict(page_format="eva", window_tokens=64, kv_dtype="int8"),
     "float pages"),
    (dict(page_format="eva", window_tokens=64, mp_size=2), "mp_size"),
])
def test_constructor_refuses_by_name(kw, word):
    with pytest.raises(ValueError, match=word.replace("^", r"\^")):
        PagedKVCacheManager(16, P, HEADS, D, **kw)


# -- two chains ------------------------------------------------------------
def test_booking_rolls_and_books_summary_rows():
    """Feed one sequence 200 tokens in uneven steps that end at every
    window's end: after each step the pages in use are ``pages_held(n)``,
    never more than ``pages_for(n)``; a page's summary row is booked in
    the step that fills it; the window chain goes back whole at a roll."""
    c = pool()
    c.alloc("a")
    n, rows = 0, 0
    for step in (5, 3, 40, 16, 1, 7, 56, 30, 34, 8):
        t = book(c, ["a"], [step])
        n += step
        rows += t.counts["summaries_written"]
        assert t.counts["fed"] == step
        assert c.seq_len("a") == n and used(c) == c.pages_held(n)
        assert used(c) <= c.pages_for(n)
        assert rows == n // P == c._lens[c._summary_key("a")]
        assert len(c.seq_summary_pages("a")) == -(-rows // P)
        assert c.seq_page_count("a") == used(c)
        assert c.pages_window + c.pages_summary == used(c)
        c.assert_ref_invariants()
        c.sanitizer_crosscheck()
    assert n == 200 and c.sanitizer_stats["violations"] == 0
    assert c.sanitizer_stats["by_op"]["roll"] == 3
    c.free("a")
    assert used(c) == 0 and not c._tables and not c._lens
    c.assert_ref_invariants()


def test_the_table_a_step_hands_the_kernel():
    """A row in window w: [the summary pages of windows 0..w-1 ; the
    window's pages], its length a visible summary row and a window token
    each; the slot plan and the summary plan beside it."""
    c = pool()
    c.alloc("a"), c.alloc("b")
    book(c, ["a", "b"], [64, 10])
    book(c, ["a"], [64])
    t = book(c, ["a", "b"], [5, 1])
    tbl, lens = t.host_tbl, t.host_lens
    a_sum, a_win = c.seq_summary_pages("a"), c.seq_pages("a")
    assert len(a_sum) == 2 and len(a_win) == 1
    assert list(tbl[0, :3]) == a_sum + a_win
    assert lens[0] == 2 * (W // P) + 5              # 16 summaries, 5 tokens
    assert list(tbl[1, :2]) == c.seq_pages("b") and lens[1] == 11
    # every fed token pairs with the rows before it and itself
    assert t.counts == {"fed": 6, "kv_rows": 21 + 11,
                        "pairs": 17 + 18 + 19 + 20 + 21 + 11,
                        "summaries_written": 0}
    rows, slots, sums = (np.asarray(a) for a in t)
    assert rows.shape == (2, 16 + 2) and slots.shape == (2, 64)
    assert sums.shape == (3, 64 // P + 2)
    assert (sums[1] == c.num_pages).all()           # nothing filled: drops
    # the step that fills a page names it and its summary row
    t = book(c, ["a"], [3])
    sums = np.asarray(t[2])
    new = c.seq_summary_pages("a")[-1]              # row 16: a third page
    assert list(sums[:, 0]) == [a_win[0], new, 0] and new not in a_sum
    assert (sums[1, 1:] == c.num_pages).all()


def test_layers_share_the_first_pools_tables():
    """Pools of one adapter are driven in lockstep: the second layer's
    booking equals the first's, so it builds and uploads nothing."""
    a, b = pool(), pool()
    for c in (a, b):
        c.alloc("s")
    for step in (60, 4, 9):
        first = a.book_step(["s"], [step], 1, 16, 64)
        again = b.book_step(["s"], [step], 1, 16, 64, like=first)
        assert again is first
    b.free("s"), b.alloc("s")                       # out of step
    b.book_step(["s"], [3], 1, 16, 64)
    first = a.book_step(["s"], [3], 1, 16, 64)
    assert b.book_step(["s"], [3], 1, 16, 64, like=first) is not first


def test_a_chunk_that_would_straddle_a_boundary():
    c = pool()
    c.alloc("a")
    book(c, ["a"], [60])
    assert c.chunk_room("a") == 4
    with pytest.raises(ValueError, match="straddle the window boundary"):
        book(c, ["a"], [5])
    assert c.seq_len("a") == 60 and used(c) == c.pages_held(60)
    book(c, ["a"], [4])
    assert c.chunk_room("a") == W
    c.assert_ref_invariants()


def test_an_exhausted_pool_raises_with_nothing_written():
    """The capacity check comes before any mutation and counts the pages
    a roll gives back: a pool of exactly one window and its summary page
    takes the 65th token, a pool short of one page refuses the 9th."""
    c = pool(num_pages=9)
    c.alloc("a")
    book(c, ["a"], [64])
    assert c.num_free_pages == 0
    book(c, ["a"], [1])                             # rolls, then draws
    assert used(c) == 2
    tight = pool(num_pages=1)
    tight.alloc("a")
    book(tight, ["a"], [7])
    with pytest.raises(RuntimeError, match="pool exhausted"):
        book(tight, ["a"], [2])
    assert tight.seq_len("a") == 7 and used(tight) == 1
    tight.assert_ref_invariants()


def test_truncate_inside_the_window_and_across_a_roll():
    c = pool()
    c.alloc("a")
    book(c, ["a"], [64])
    book(c, ["a"], [30])                            # 94: 3 + 1 pages of w1
    c.truncate("a", 70)                             # speculative rollback
    assert c.seq_len("a") == 70 and used(c) == c.pages_held(70)
    assert c._lens[c._summary_key("a")] == 70 // P
    c.assert_ref_invariants()
    with pytest.raises(ValueError, match="across a roll.*page_format='eva'"):
        c.truncate("a", 64)
    with pytest.raises(ValueError, match="across a roll"):
        c.truncate("a", 10)
    book(c, ["a"], [10])                            # rows 8, 9 booked anew
    assert used(c) == c.pages_held(80)
    assert c.sanitizer_stats["violations"] == 0


@pytest.mark.parametrize("op, call", [
    ("attach", lambda c: c.attach("b", [0], 8)),
    ("swap_out", lambda c: c.swap_out("a", HostKVSwapSpace(1 << 20))),
    ("swap_in", lambda c: c.swap_in("a", HostKVSwapSpace(1 << 20))),
    ("export_seq",
     lambda c: HostKVSwapSpace(1 << 20).export_seq("a", [c])),
    ("import_seq",
     lambda c: HostKVSwapSpace(1 << 20).import_seq("a", [b""], [c])),
    ("append", lambda c: c.append("a", jnp.zeros((HEADS, D)),
                                  jnp.zeros((HEADS, D)))),
    ("append_batch", lambda c: c.append_batch(
        ["a"], jnp.zeros((1, HEADS, D)), jnp.zeros((1, HEADS, D)))),
    ("append_ragged", lambda c: c.append_ragged(
        ["a"], [1], jnp.zeros((1, HEADS, D)), jnp.zeros((1, HEADS, D)))),
    ("attend", lambda c: c.attend(jnp.zeros((1, HEADS, D)), ["a"])),
    ("attend_ragged", lambda c: c.attend_ragged(
        jnp.zeros((1, 1, HEADS, D)), ["a"], [1])),
    ("dense_kv", lambda c: c.dense_kv(["a"])),
])
def test_refused_by_name(op, call):
    """What takes a sequence for one chain as long as its tokens."""
    c = pool(sanitizer="off")
    c.alloc("a")
    with pytest.raises(ValueError,
                       match=f"{op}: not available for page_format='eva'"):
        call(c)
    assert c.seq_len("a") == 0 and used(c) == 0


def test_layer_step_wants_the_summary_operands_of_its_format():
    kv = PagedKVCacheManager(8, P, HEADS, D, dtype=jnp.float32)
    with pytest.raises(ValueError, match="summary=.*page_format='eva'"):
        kv.layer_step(None, None, None, (None, None), (None, None), 1e-5,
                      summary=(None, None))
    with pytest.raises(ValueError, match="summary=.*page_format='eva'"):
        pool().layer_step(None, None, None, (None, None),
                          (None, None, None), 1e-5)


def test_the_sanitizer_sees_a_roll_that_keeps_its_pages(monkeypatch):
    """The shadow heap follows the window chain through a roll: a pool
    that forgot to release it is caught at the next cross-check."""
    c = pool()
    c.alloc("a")
    book(c, ["a"], [64])

    def forgetful(seq_id):
        c._san.event("roll", seq=seq_id,
                     pages=[int(p) for p in c._tables[seq_id]])
        c._tables[seq_id].clear()                   # no reference dropped

    monkeypatch.setattr(c, "_roll", forgetful)
    with pytest.raises(Exception, match="refcount|leak|free"):
        book(c, ["a"], [1])
        c.sanitizer_crosscheck()


def test_the_sanitizer_checks_both_parts_of_a_table(monkeypatch):
    """A table whose summary part names another page is a stale table."""
    c = pool()
    c.alloc("a")
    book(c, ["a"], [64])
    real = c._table_row

    def wrong(seq_id):
        pages, n = real(seq_id)
        return ([pages[0] + 1] + pages[1:], n) if len(pages) > 1 \
            else (pages, n)

    monkeypatch.setattr(c, "_table_row", wrong)
    with pytest.raises(Exception, match="page-table|stale"):
        book(c, ["a"], [3])
