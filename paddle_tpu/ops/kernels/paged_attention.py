"""Paged KV-cache attention — one unified ragged Pallas TPU kernel.

Upstream analogs: paddle/fluid/operators/fused/fused_multi_transformer
_op.cu's cache-KV decode path and the block-attention kernels the
reference's serving stacks use (PagedAttention). Design follows the
TPU paged-attention recipe ("Ragged Paged Attention" — see PAPERS.md):

* the KV cache lives in HBM as fixed-size pages
  ``(num_pages, page_size, kv_heads, head_dim)``;
* a per-sequence ``page_table (B, max_pages)`` maps logical pages to
  physical ones; ``seq_lens (B,)`` bounds the ragged KV lengths and a
  per-row ``q_lens (B,)`` bounds the ragged QUERY lengths — 1 for
  decode rows, n for prefill chunks, k+1 for speculative VERIFY rows
  (a draft window riding right-aligned like any other chunk; the
  caller samples per-position logits via
  :func:`packed_position_index`), so one kernel handles a mixed
  packed batch uniformly (:func:`paged_ragged_attention`);
* the kernel grid is (row, block of 16 whole pages), run in order; the
  page table and both length vectors ride scalar prefetch, the pools
  stay in HBM as they are held, and a live step copies the NEXT live
  step's K and V pages into the other half of a VMEM landing buffer
  while it computes its own — each page once for every head, only the
  pages the row has; blocks past a row's length, below its window or
  of a padding row copy and multiply nothing;
* inside a step the query heads of a KV group are multiplied together
  against their KV head's keys — rows (token, head-in-group), in tiles,
  so a right-aligned row's leading padding is skipped by tile — with
  online softmax (m, l, acc) in VMEM scratch across the page axis
  (row i's last q_lens[i] rows are its newest tokens; padded leading
  rows return exact zeros);
* a decode row whose KV heads have one query row each (group 1: MHA)
  is not fed to the MXU head by head: its token is multiplied with the
  pages where they landed, (slot, kv head, dim), on the vector unit,
  chosen by ``_ragged_tiling`` from the static shapes and, beside a
  prompt chunk, by the kernel from ``q_lens``.

GQA never replicates KV in HBM or in VMEM. Int8 pages dequantize in
VMEM right after the page DMA (per-page per-head scale sidecars ride
scalar prefetch). Off-TPU (tests) the same kernel runs in pallas
interpret mode against a dense reference. :func:`paged_attention`,
the (B, H, D) decode entry, is the T=1 shape of the same call.

Latent pages (:func:`latent_ragged_attention`, MLA): one
``(num_pages, page_size, latent_dim)`` pool holds a token's compressed
K/V once for every head; the absorbed kernel reads it through the same
page table, lengths and right-aligned rows.

The layer program (:func:`paged_ragged_layer_step`): once the
attention path is ONE program, a dense decoder layer folds into it —
``rms_norm``, qkv projection + RoPE + the K/V page scatter, the kernel,
o_proj, the residual, ``rms_norm``, the gated MLP and its residual are
one compiled program with the layer's weights as operands (every layer
shares the program of a shape) and the pools donated on the chip, so a
serving layer step is a single dispatch.

Dispatch caching: eager callers (the serving step loop, tests) hit a
shape-keyed LRU of ``jax.jit``-ted entry points, so stepping the same
shapes never re-traces the pallas call — the historical per-call
build cost was pure trace/compile overhead. The kernel keys ONE
cache for every row kind. Callers already under an outer trace
(``to_static``) inline the identical lowering; the surrounding program
owns compilation and caching there.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...framework import telemetry
from . import on_tpu  # defined before the package imports its kernels
from .rms_norm import rms_norm
from .rope import apply_rotary_emb

NEG_INF = -1e30


def paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                    sm_scale=None, interpret=None, window=0,
                    k_scales=None, v_scales=None):
    """Decode attend over a paged KV cache — one token per sequence.

    q: (B, H, D); k_pages/v_pages: (NP, P, KVH, D);
    page_table: (B, max_pages) int32 physical-page ids;
    seq_lens: (B,) int32. ``window`` > 0 keeps only the last
    ``window`` keys (Mistral sliding attention; out-of-window pages
    are skipped entirely). Returns (B, H, D).

    Quantized pages: pass int8 k_pages/v_pages plus per-page, per-head
    scale sidecars k_scales/v_scales (NP, KVH) f32 — the pages DMA as
    int8 (half the HBM traffic) and dequantize in VMEM inside the
    kernel, scales riding scalar prefetch.

    This is the T=1 shape wrapper over :func:`paged_ragged_attention`:
    a decode batch is a packed batch whose rows all have ``q_len`` 1,
    and compiles the same program as one.
    """
    b = q.shape[0]
    out = paged_ragged_attention(
        q[:, None], k_pages, v_pages, page_table, seq_lens,
        q_lens=jnp.ones((b,), jnp.int32), sm_scale=sm_scale,
        interpret=interpret, window=window, k_scales=k_scales,
        v_scales=v_scales)
    return out[:, 0]


def paged_attention_reference(q, k_pages, v_pages, page_table,
                              seq_lens, sm_scale=None, window=0,
                              k_scales=None, v_scales=None):
    """Dense float32 decode reference for tests."""
    import numpy as np

    b, h, d = q.shape
    npages, page_size, kvh, _ = k_pages.shape
    group = h // kvh
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    qn = np.asarray(q, np.float32)
    kn = np.asarray(k_pages, np.float32)
    vn = np.asarray(v_pages, np.float32)
    if k_scales is not None:
        kn = kn * np.asarray(k_scales, np.float32)[:, None, :, None]
        vn = vn * np.asarray(v_scales, np.float32)[:, None, :, None]
    tbl = np.asarray(page_table)
    lens = np.asarray(seq_lens)
    out = np.zeros((b, h, d), np.float32)
    for i in range(b):
        L = int(lens[i])
        n_used = -(-L // page_size) if L else 0
        ks = np.concatenate(
            [kn[tbl[i, p]] for p in range(n_used)], axis=0
        )[:L] if n_used else np.zeros((0, kvh, d), np.float32)
        vs = np.concatenate(
            [vn[tbl[i, p]] for p in range(n_used)], axis=0
        )[:L] if n_used else np.zeros((0, kvh, d), np.float32)
        if window and L > window:
            ks, vs = ks[L - window:], vs[L - window:]
        for j in range(h):
            kj = ks[:, j // group]
            vj = vs[:, j // group]
            s = kj @ qn[i, j] * scale
            p = np.exp(s - s.max()) if L else s
            p = p / p.sum() if L else p
            out[i, j] = p @ vj if L else 0.0
    return out


def paged_ragged_attention_reference(q, k_pages, v_pages, page_table,
                                     seq_lens, q_lens=None,
                                     sm_scale=None, window=0,
                                     k_scales=None, v_scales=None, block=0):
    """Dense float32 reference for the unified ragged kernel: q is
    (B, T, H, D) right-aligned (row i's last q_lens[i] rows are real;
    padded leading rows return exact zeros). ``q_lens=None`` treats
    every row as real. ``block`` > 0: a query sees its own aligned block
    of ``block`` positions whole (as far as the sequence goes) and every
    block before it. Returns (B, T, H, D) float32."""
    import numpy as np

    b, t, h, d = q.shape
    npages, page_size, kvh, _ = k_pages.shape
    group = h // kvh
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    qn = np.asarray(q, np.float32)
    kn = np.asarray(k_pages, np.float32)
    vn = np.asarray(v_pages, np.float32)
    if k_scales is not None:
        kn = kn * np.asarray(k_scales, np.float32)[:, None, :, None]
        vn = vn * np.asarray(v_scales, np.float32)[:, None, :, None]
    tbl = np.asarray(page_table)
    lens = np.asarray(seq_lens)
    ql = np.full((b,), t) if q_lens is None else np.asarray(q_lens)
    out = np.zeros((b, t, h, d), np.float32)
    for i in range(b):
        L = int(lens[i])
        if not L:
            continue
        n_used = -(-L // page_size)
        ks = np.concatenate(
            [kn[tbl[i, p]] for p in range(n_used)], axis=0)[:L]
        vs = np.concatenate(
            [vn[tbl[i, p]] for p in range(n_used)], axis=0)[:L]
        for r in range(t - int(ql[i]), t):
            qpos = L - t + r
            lo = max(0, qpos - window + 1) if window else 0
            hi = min(qpos | (block - 1), L - 1) if block else qpos
            for j in range(h):
                kj = ks[lo:hi + 1, j // group]
                vj = vs[lo:hi + 1, j // group]
                s = kj @ qn[i, r, j] * scale
                p = np.exp(s - s.max())
                p /= p.sum()
                out[i, r, j] = p @ vj
    return out


RAGGED_PAGES_PER_STEP = 16       # K and V pages DMA'd a grid step
RAGGED_ROW_TILE = 64             # (token, head-in-group) rows a matmul
RAGGED_FEW_ROWS = 1              # query rows a KV head of the few-row form
RAGGED_FEW_PAGES = 4             # pages a turn of the few-row form's loop


def _ragged_tiling(t, group, max_pages, quant=False):
    """(pages a step, page blocks a row, query row tile, few) of the
    unified kernel, from the static shapes alone. A KV head's query rows
    are ``t * group`` (token, head-in-group) pairs; they are multiplied in
    tiles so that a right-aligned row's leading padding is skipped by
    tile. A row count the tile does not divide is one whole tile.

    ``few``: whether a decode row (``q_len`` 1) is attended in the
    pool's own layout instead (:func:`_few_row_pages`): where it has at
    most ``RAGGED_FEW_ROWS`` query rows a KV head. Such a row feeds the
    MXU's stationary side a head's keys for one row pushed through them
    and pays by KV head, not by byte: on the vector unit a live step
    takes 5.9 us against 25.5 at 32 KV heads x group 1 (v5e; PERF.md
    section 6, PR 35). At 8 x 4 the form is faster too (4.9 us against
    6.2) and stays off: a program that holds both forms costs the host
    more to trace, and the Mistral cell, which builds a dozen programs
    inside its window, lost a fifth of its tokens a second to that. Int8
    pages dequantise on the way to the head-major copy and keep the
    tiles."""
    ppb = min(RAGGED_PAGES_PER_STEP, max_pages)
    m = t * group
    tm = m
    if m > RAGGED_ROW_TILE:
        # a dynamic row slice of bf16 queries starts on a packed tile
        for c in range(RAGGED_ROW_TILE, 15, -16):
            if m % c == 0:
                tm = c
                break
    few = not quant and group <= RAGGED_FEW_ROWS
    return ppb, -(-max_pages // ppb), tm, few


def _ragged_grid_steps(b, max_pages):
    """Grid steps of one call: rows x page blocks (the ``kernel.ragged``
    span's ``grid_steps``)."""
    return b * _ragged_tiling(1, 1, max_pages)[1]


def ragged_few_rows(q_lens, t, group, quant=False):
    """Rows of one call that take the few-row form, from the host's
    ``q_lens`` (a step's real rows; padding rows carry 0) and the static
    shapes: the ``kernel.ragged`` span's ``few_row_rows``."""
    few = _ragged_tiling(t, group, 1, quant)[3]
    return sum(n == 1 for n in q_lens) if few else 0


def _ragged_kernel(scale, page_size, ppb, n_steps, n_rows, t, group, kvh,
                   tm, window, quant, few, block, tbl_ref, lens_ref,
                   qlens_ref, *refs):
    """THE unified kernel, grid (row b, page block p), steps in order. A
    live step holds the block's K pages and V pages — at most ``ppb``,
    only those the row has — in VMEM in the pool's own layout
    (page_size, kv heads, head_dim), each copied from HBM once for every
    head while the block before it was computed (the copies of a step
    are issued by the live step before it, across rows too). The pages
    are multiplied in one of two forms, by what the row is:

    * tiles: the pages are laid head-major and, KV head by KV head, the
      block's keys (ppb * page_size, head_dim) are multiplied with the
      ``t * group`` (token, head-in-group) query rows of the head's
      group in tiles of ``tm``, from the tile that holds the row's first
      real token: the last q_lens[b] tokens are real, the rest is the
      right-alignment's padding and returns exact zeros;
    * few rows (``few`` and ``q_lens[b] <= 1``: a decode row at a small
      group): the row's one real token is attended where the pages
      landed, every KV head at once on the vector unit
      (:func:`_few_row_pages`); at ``t`` = 1 every row is such a row and
      the kernel holds no head-major buffer.

    Causal; ``window`` > 0 bands the mask (0 <= qpos - kpos < window);
    ``block`` > 0 (a power of two) lets a query see its own aligned
    block of positions whole, as far as the row's length: causal across
    blocks, bidirectional inside one (the few-row form's one token is
    the row's last, and sees every key either way).
    ``quant``: int8 pages dequantised on the way by the scalar-prefetched
    per-page, per-head scale sidecars. Online softmax state (m, l, acc)
    stays in VMEM across the page axis, float32. Blocks beyond the row's
    length, below every real token's window, or of a padding row copy
    and multiply nothing."""
    refs = list(refs)
    if quant:
        k_scale_ref = refs.pop(0)
        v_scale_ref = refs.pop(0)
    all_few = few and t == 1
    q_ref = None if all_few else refs.pop(0)
    qf_ref = refs.pop(0) if few else None
    k_hbm, v_hbm, o_ref, k_in, v_in, sems, slot_ref = refs[:7]
    refs = refs[7:]
    if not all_few:
        k_buf, v_buf, m_ref, l_ref, acc_ref = refs[:5]
        refs = refs[5:]
    few_state = refs                       # (m, l, acc) of the few rows
    b = pl.program_id(0)
    p = pl.program_id(1)
    seq_len = lens_ref[b]
    q_len = qlens_ref[b]
    m_rows = t * group
    n_tiles = m_rows // tm
    span = ppb * page_size
    log_g = group.bit_length() - 1 if group & (group - 1) == 0 else None

    def div(a, n):
        # of non-negative scalars: ``//`` on a traced integer lowers
        # through sign(), several milliseconds of lowering each
        return jax.lax.div(a, jnp.int32(n))

    def token_of(row):
        return jax.lax.shift_right_logical(row, log_g) \
            if log_g is not None else div(row, group)

    def either(tiles, few_rows):
        """Run the form the row takes."""
        if all_few:
            return few_rows()
        if few:
            pl.when(q_len <= 1)(few_rows)
            return pl.when(q_len > 1)(tiles)
        return tiles()

    def blocks(row):
        """(first, last) live block of a row that holds tokens."""
        n = lens_ref[row]
        last = div(jnp.maximum(n, 1) - 1, span)
        if not window:
            return 0, last
        # the lowest real token sits at n - q_lens[row]
        floor = div(jnp.maximum(n - qlens_ref[row] - window + 1, 0),
                    span)
        return jnp.minimum(floor, last), last

    def copies(row, blk, slot, j):
        pg = tbl_ref[row, blk * ppb + j]
        return (pltpu.make_async_copy(k_hbm.at[pg], k_in.at[slot, j],
                                      sems.at[slot, 0]),
                pltpu.make_async_copy(v_hbm.at[pg], v_in.at[slot, j],
                                      sems.at[slot, 1]))

    def pages_here(row, blk):
        # the pages of the block the row has tokens in
        return jnp.minimum(
            ppb, div(lens_ref[row] + page_size - 1, page_size) - blk * ppb)

    def each_page(row, blk, slot, act):
        def body(j, c):
            for cp in copies(row, blk, slot, j):
                act(cp)
            return c
        jax.lax.fori_loop(0, pages_here(row, blk), body, 0)

    def start(row, blk, slot):
        each_page(row, blk, slot, lambda cp: cp.start())

    @pl.when((b == 0) & (p == 0))
    def _():
        slot_ref[0] = 0
        # a page a block does not copy keeps what the buffer held:
        # masked, but it goes through the multiply (0 x NaN)
        k_in[...] = jnp.zeros(k_in.shape, k_in.dtype)
        v_in[...] = jnp.zeros(v_in.shape, v_in.dtype)

    def init_tiles():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def init_few():
        for ref, x in zip(few_state, (NEG_INF, 0.0, 0.0)):
            ref[...] = jnp.full(ref.shape, x, jnp.float32)

    @pl.when(p == 0)
    def _():
        either(init_tiles, init_few)

    first, last = blocks(b)

    @pl.when((seq_len > 0) & (p >= first) & (p <= last))
    def _():
        slot = slot_ref[0]
        # the live step before this one issued this block's copies,
        # unless there was none: the call's first, or after padding rows
        before = jnp.maximum(b - 1, 0)

        @pl.when((p == first) & ((b == 0) | (lens_ref[before] == 0)))
        def _():
            start(b, p, slot)

        # the next live step's: this row's next block, else the first
        # of the row after it, if that row holds tokens
        after = jnp.minimum(b + 1, n_rows - 1)
        inside = p < last

        @pl.when(inside | ((b + 1 < n_rows) & (lens_ref[after] > 0)))
        def _():
            start(jnp.where(inside, b, after),
                  jnp.where(inside, p + 1, blocks(after)[0]), 1 - slot)

        each_page(b, p, slot, lambda cp: cp.wait())
        slot_ref[0] = 1 - slot

        def gather(pages_in, buf, scale_ref):
            # (ppb, P, KVH, D); beside the few-row form the landing
            # buffers may hold a last turn's pages more
            x = pages_in[slot, :ppb] if few else pages_in[slot]
            if not quant:
                x = x.reshape(span, kvh, x.shape[-1])
                for g in range(kvh):
                    buf[g] = x[:, g, :]
                return
            x = x.astype(jnp.float32)
            for j in range(ppb):
                pg = tbl_ref[b, jnp.minimum(p * ppb + j,
                                            tbl_ref.shape[1] - 1)]
                for g in range(kvh):
                    buf[g, j * page_size:(j + 1) * page_size, :] = \
                        x[j, :, g, :] * scale_ref[pg * kvh + g]

        def head(g, c):
            k = k_buf[g]                                 # (span, D)
            v = v_buf[g]

            def tile(i, c):
                rows = slice(None) if n_tiles == 1 else \
                    pl.ds(pl.multiple_of(i * tm, tm), tm)
                q = q_ref[0, g, rows, :]                 # (tm, D)
                if quant:
                    q = q.astype(jnp.float32)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                kpos = p * span + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                tok = token_of(i * tm + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 0))
                # token r is absolute position seq_len - t + r
                qpos = seq_len - t + tok
                if block:
                    keep = (kpos <= (qpos | (block - 1))) \
                        & (kpos < seq_len) & (tok >= t - q_len)
                else:
                    keep = (kpos <= qpos) & (tok >= t - q_len)
                if window:
                    keep = keep & (qpos - kpos < window)
                s = jnp.where(keep, s, NEG_INF)
                m_prev = m_ref[g, rows, :][:, :1]
                l_prev = l_ref[g, rows, :][:, :1]
                m_cur = jnp.maximum(m_prev,
                                    jnp.max(s, -1, keepdims=True))
                corr = jnp.exp(m_prev - m_cur)
                pv = jnp.where(keep, jnp.exp(s - m_cur), 0.0)
                l_ref[g, rows, :] = jnp.broadcast_to(
                    corr * l_prev + jnp.sum(pv, -1, keepdims=True),
                    (tm, l_ref.shape[-1]))
                acc_ref[g, rows, :] = acc_ref[g, rows, :] * corr \
                    + jax.lax.dot_general(
                        pv.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                m_ref[g, rows, :] = jnp.broadcast_to(
                    m_cur, (tm, m_ref.shape[-1]))
                return c

            if n_tiles == 1:
                return tile(0, c)
            # tiles below the first real token's are padding: skipped
            return jax.lax.fori_loop(
                div((t - q_len) * group, tm), n_tiles, tile, c)

        def tiles():
            gather(k_in, k_buf, k_scale_ref if quant else None)
            gather(v_in, v_buf, v_scale_ref if quant else None)
            jax.lax.fori_loop(0, kvh, head, 0)

        def few_rows():
            # the row's one token sits at seq_len - 1: the block's pages
            # from its window's floor to the row's last
            floor = div(jnp.maximum(seq_len - window, 0), page_size) \
                - p * ppb if window else 0
            _few_row_pages(
                scale, window, seq_len, p * span, jnp.maximum(floor, 0),
                pages_here(b, p), qf_ref, k_in.at[slot], v_in.at[slot],
                few_state)

        either(tiles, few_rows)

    @pl.when(p == n_steps - 1)
    def _():
        def few_out():
            _, l_few, acc_few = few_state
            return jnp.where(
                q_len > 0,
                acc_few[...] / jnp.maximum(l_few[...], 1e-30), 0.0)

        if all_few:
            o_ref[0] = few_out().astype(o_ref.dtype)
            return
        if few:
            # the few rows' token joins the tiles' state, head by head,
            # at the row block's end, and leaves by the tiles' way out
            @pl.when(q_len <= 1)
            def _():
                out = few_out()                  # (group, KVH, D)
                for g in range(kvh):
                    acc_ref[g, m_rows - group:, :] = out[:, g, :]
                l_ref[:, m_rows - group:, :] = jnp.ones(
                    (kvh, group, l_ref.shape[-1]), jnp.float32)

        out = acc_ref[...] / jnp.maximum(l_ref[...][:, :, :1], 1e-30)
        tok = token_of(jax.lax.broadcasted_iota(jnp.int32, out.shape, 1))
        o_ref[0] = jnp.where(tok >= t - q_len, out, 0.0).astype(
            o_ref.dtype)


def _few_row_pages(scale, window, seq_len, base, first, pages, q_ref, k_in,
                   v_in, state):
    """The few-row form of a live step: the ONE real token of the row
    (position ``seq_len - 1``, ``group`` query rows a KV head) against
    the block's pages where they landed, ``k_in`` / ``v_in`` (pages, P,
    KVH, D): pages ``first`` up to ``pages``, in whole turns of
    ``RAGGED_FEW_PAGES`` counted from the block's start (what a turn
    holds beside them is masked). Nothing is
    laid head-major and nothing is fed to the MXU head by head: the
    query (group, KVH, D) has the layout of one slot of a page, so
    ``k * q`` is elementwise with the slots on the leading axis; the
    products (exact in float32 for bf16 operands) are summed over the
    lanes in float32, the score comes back along the lanes, which is the
    form the weighted sum over ``v`` wants, and softmax state ``state`` =
    (m, l, acc), each (group, KVH, D) float32 with m and l along the
    lanes, runs over the slots on the leading axis: vector adds. The
    probabilities are cast to the values' dtype before the weighted sum,
    as the tiles' are. ``base``: the block's first position."""
    m_ref, l_ref, acc_ref = state
    group, kvh, d = acc_ref.shape
    page_size = k_in.shape[1]
    pc = RAGGED_FEW_PAGES
    n = pc * page_size
    q = q_ref[0].astype(jnp.float32)                     # (group, KVH, D)

    def turn(c, carry):
        pages = pl.ds(c * pc, pc)
        k = k_in[pages].astype(jnp.float32).reshape(n, kvh, d)
        v = v_in[pages].reshape(n, kvh, d)
        kpos = base + c * n + jax.lax.broadcasted_iota(
            jnp.int32, (n, kvh, d), 0)
        keep = kpos < seq_len
        if window:
            keep = keep & (kpos >= seq_len - window)
        out = []
        for gi, (m_prev, l_prev, acc) in enumerate(carry):
            s = jnp.sum(k * q[gi], -1, keepdims=True) * scale
            s = jnp.where(keep, jnp.broadcast_to(s, (n, kvh, d)), NEG_INF)
            m_cur = jnp.maximum(m_prev, jnp.max(s, 0))
            corr = jnp.exp(m_prev - m_cur)
            pv = jnp.where(keep, jnp.exp(s - m_cur), 0.0)
            out.append((
                m_cur, corr * l_prev + jnp.sum(pv, 0),
                acc * corr + jnp.sum(
                    pv.astype(v.dtype).astype(jnp.float32)
                    * v.astype(jnp.float32), 0)))
        return tuple(out)

    done = jax.lax.fori_loop(
        jax.lax.div(first, jnp.int32(pc)),
        jax.lax.div(pages + (pc - 1), jnp.int32(pc)), turn,
        tuple((m_ref[gi], l_ref[gi], acc_ref[gi]) for gi in range(group)))
    for gi, (m_cur, l_cur, acc) in enumerate(done):
        m_ref[gi], l_ref[gi], acc_ref[gi] = m_cur, l_cur, acc


def paged_ragged_attention(q, k_pages, v_pages, page_table, seq_lens,
                           q_lens=None, sm_scale=None, interpret=None,
                           window=0, k_scales=None, v_scales=None,
                           counts=None, block=0):
    """The unified ragged paged-attention entry (PAPERS.md: Ragged
    Paged Attention) — ONE kernel for decode rows and prefill chunks.

    q: (B, T, H, D) — each row's newest tokens RIGHT-ALIGNED, whose
    K/V have already been appended to the pages; seq_lens counts them.
    ``q_lens`` (B,) marks how many TRAILING rows of each sequence are
    real new tokens: 1 for a decode row, n for an n-token prefill
    chunk; the padded leading rows return exact zeros. Without q_lens
    every row is treated as real (positions follow seq_len) and short
    rows must be masked by the caller. Returns (B, T, H, D). Int8
    pages: pass k_scales/v_scales (NP, KVH) as in
    :func:`paged_attention`. ``counts``: what the caller knows of the
    call on the host (``few_row_rows``), attributes of the
    ``kernel.ragged`` span. ``block`` (static, 0 = causal): a power of
    two; a query sees its own aligned block of that many positions whole
    and every block before it (generation by diffusion over blocks).
    """
    b, t, h, d = q.shape
    npages, page_size, kvh, _ = k_pages.shape
    max_pages = page_table.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    quant = k_scales is not None
    if quant != (v_scales is not None):
        raise ValueError(
            "paged_ragged_attention: pass both k_scales and v_scales "
            "or neither")

    if interpret is None:
        interpret = not on_tpu()

    ragged = q_lens is not None
    scalar_args = [page_table.astype(jnp.int32),
                   seq_lens.astype(jnp.int32)]
    if ragged:
        scalar_args.append(jnp.asarray(q_lens).astype(jnp.int32))
    if quant:
        scalar_args += [k_scales.astype(jnp.float32).reshape(-1),
                        v_scales.astype(jnp.float32).reshape(-1)]
    cfg = (b, t, h, d, npages, page_size, kvh, max_pages,
           float(scale), int(window or 0), quant, ragged,
           bool(interpret), _block_of(block))
    args = (q, k_pages, v_pages, *scalar_args)
    if any(isinstance(x, jax.core.Tracer) for x in args):
        return _build_ragged_call(*cfg)(*args)
    with telemetry.span("kernel.ragged", rows=b, t=t,
                        max_pages=max_pages,
                        grid_steps=_ragged_grid_steps(b, max_pages),
                        **(counts or {})):
        return _jitted_ragged_call(cfg)(*args)


def _block_of(block):
    """``block`` as the kernel takes it: 0, or a power of two."""
    block = int(block or 0)
    if block & (block - 1):
        raise ValueError(
            f"ragged attention: block={block} has to be a power of two "
            "(the mask is kpos <= qpos | (block - 1))")
    return block


def _build_ragged_call(b, t, h, d, npages, page_size, kvh, max_pages,
                       scale, window, quant, ragged, interpret, block=0):
    """The unified ragged pallas dispatch as a pure function of the
    static config: returns ``run(q, k_pages, v_pages, *scalar_args)``.
    Traced callers inline it; eager callers go through
    :func:`_jitted_ragged_call`'s cached ``jax.jit`` of the same body,
    so a serving loop stepping the same shapes never re-traces the
    kernel. The pools stay in HBM as they
    are held, (NP, P, KVH, D), and the kernel copies the pages it wants
    by the scalar-prefetched table (on the chip any other view of the
    pool, the lane-merged (NP, P, KVH * D) one included, is a copy of
    all of it; a BlockSpec a page costs every program that holds the
    call a tenth of a second of tracing and lowering more)."""
    group = h // kvh
    m = t * group
    ppb, n_steps, tm, few = _ragged_tiling(t, group, max_pages, quant)
    all_few = few and t == 1
    span = ppb * page_size

    def q_map(b_, p_, *pref):
        return (b_, 0, 0, 0)

    def run(q, k_pages, v_pages, tbl, lens, *scalar_args):
        if not ragged:
            scalar_args = (jnp.full((b,), t, jnp.int32), *scalar_args)
        kv_dtype = jnp.float32 if quant else k_pages.dtype
        pool = pl.BlockSpec(memory_space=pl.ANY)
        # the few-row form takes its pages RAGGED_FEW_PAGES a turn,
        # whatever the table's width: a row's sums have one order
        pages = -(-ppb // RAGGED_FEW_PAGES) * RAGGED_FEW_PAGES \
            if few else ppb
        landing = pltpu.VMEM((2, pages, page_size, kvh, d), k_pages.dtype)
        rows, few_row = (kvh, m, d), (group, kvh, d)
        # (B, T, H, D) -> (B, KVH, T * group, D): a KV head's query
        # rows together, ordered (token, head-in-group); the few-row
        # form reads a row's last token as (B, group, KVH, D), a slot
        # of a page a query row
        qs = [] if all_few else [jnp.transpose(
            q.reshape(b, t, kvh, group, d),
            (0, 2, 1, 3, 4)).reshape(b, *rows)]
        if few:
            qs.append(jnp.transpose(
                q[:, t - 1].reshape(b, kvh, group, d), (0, 2, 1, 3)))
        shape_out = few_row if all_few else rows
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + (2 if quant else 0),
            grid=(b, n_steps),
            in_specs=[pl.BlockSpec((1, *x.shape[1:]), q_map) for x in qs]
            + [pool, pool],
            out_specs=pl.BlockSpec((1, *shape_out), q_map),
            scratch_shapes=[
                landing, landing,
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ] + ([] if all_few else [
                pltpu.VMEM((kvh, span, d), kv_dtype),
                pltpu.VMEM((kvh, span, d), kv_dtype),
                pltpu.VMEM((kvh, m, 128), jnp.float32),
                pltpu.VMEM((kvh, m, 128), jnp.float32),
                pltpu.VMEM((kvh, m, d), jnp.float32),
            ]) + [pltpu.VMEM(few_row, jnp.float32)] * (3 if few else 0),
        )
        out = pl.pallas_call(
            functools.partial(
                _ragged_kernel, scale, page_size, ppb, n_steps, b, t,
                group, kvh, tm, window, quant, few, block),
            name="ragged_paged_attention",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, *shape_out), q.dtype),
            interpret=interpret,
            # in order: a step issues the next live step's page copies
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=96 * 1024 * 1024,
            ) if not interpret else None,
        )(tbl, lens, *scalar_args, *qs, k_pages, v_pages)
        if all_few:
            return jnp.transpose(out, (0, 2, 1, 3)).reshape(b, t, h, d)
        return jnp.transpose(out.reshape(b, kvh, t, group, d),
                             (0, 2, 1, 3, 4)).reshape(b, t, h, d)

    return run


@functools.lru_cache(maxsize=512)
def _jitted_ragged_call(cfg):
    """ONE shape-keyed dispatch cache for every row kind — decode
    (T=1), prefill, and mixed ragged batches share it, so warm serving
    never splits compile work per row kind and compiled programs are
    shared across pool instances."""
    return jax.jit(_build_ragged_call(*cfg))


def pool_pages(k, v, phi, mu, scale):
    """One pooled (key, value) row a finished chunk: ``k``/``v`` (n, P,
    H, D), a chunk's P rotated keys and its values; ``phi``/``mu`` (H, D)
    learned a layer. a_j = softmax_j(scale * phi . k_j); the pooled key
    is sum_j a_j k_j + mu, the pooled value sum_j a_j v_j. Float32,
    elementwise (no matmul rounds the weights). Returns float32 (n, H,
    D) twice."""
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    a = jax.nn.softmax(
        jnp.sum(kf * phi.astype(jnp.float32), -1) * scale, axis=1)[..., None]
    return (jnp.sum(a * kf, 1) + mu.astype(jnp.float32), jnp.sum(a * vf, 1))


def head_rms(x, gain, eps):
    """RMSNorm over a head's numbers (x [..., heads, head_dim], one gain
    vector a layer), in float32, rounded once to the stream's type."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def _build_layer_call(n_pad, e, nh, kvh, hd, npages, page_size, b_pad,
                      t_pad, max_pages, scale, window, has_bias, eps,
                      interpret, unit_offset=False, n_sum=0, block=0,
                      qk_norm=False, router=None):
    """One decoder layer of the packed serving step as ONE program:
    ``rms_norm`` -> qkv projection + RoPE + this chunk's K/V page scatter
    -> the ragged kernel over the right-aligned rows -> scatter back +
    ``o_proj`` -> residual -> ``rms_norm`` -> ``down(silu(gate) * up)``
    -> residual. The layer's weights are OPERANDS, so every layer of a
    model shares the program of a shape.

    Operands (all arrays; statics live in the cfg key — every operand is
    padded to the BUCKETED shapes, so neither the layer nor the step's
    real token count re-keys the dispatch cache):

    * ``k_pages/v_pages`` first (the donated pair on the chip), then
      ``x`` (n_pad, e), the packed residual stream;
    * ``ln1``, ``wq/wk/wv`` (e, nh*hd / kvh*hd), ``wo`` (nh*hd, e)
      ([in, out] paddle layout), the q/k/v biases when ``has_bias``,
      ``ln2``, ``wg/wu`` (e, f), ``wd`` (f, e);
    * ``cos/sin`` (S, hd) RoPE tables;
    * the step's index operands, merged so that few arrays cross to the
      device: ``tok`` (5, n_pad), a packed token's id (unused here),
      absolute position and ``mr/mc/mflat``, the scatter of the
      kernel's output back to the packed axis (padding entries gather
      slot (0, 0) and drop on an out-of-bounds ``mflat``); ``gm``
      (b_pad, t_pad), the flat-index gather map right-aligning each
      row's tokens; ``slots`` (2, n_pad), physical page / in-page slot
      per written token (PADDING entries carry an out-of-bounds page id
      and the scatter runs mode="drop", so they write nothing);
      ``rows`` (b_pad, max_pages + 2), a row's page table, then its
      ``seq_len`` and ``q_len`` as in :func:`paged_ragged_attention`;
    * two static switches, both off for a Llama/Mistral layer, whose
      program they leave as it was. ``unit_offset``: the norms' gains
      are stored less one (x / rms(x) * (1 + g), the sum in float32).
      ``n_sum`` > 0, the summary epilogue of a window-and-summary pool
      (three more operands: ``phi``/``mu`` (kvh, hd) and ``sums`` (3,
      n_sum), for every page this step's scatter fills the page, then
      the page and slot of its summary row; padding drops): the filled
      pages are gathered, pooled (:func:`pool_pages`) and scattered
      into the same pools before the kernel reads them;
    * three more, off likewise. ``block``: the kernel's block-causal
      mask (:func:`paged_ragged_attention`). ``qk_norm`` (two more
      operands, ``gq``/``gk`` (hd,)): an RMSNorm over each query and key
      head's numbers before RoPE. ``router`` = (top_k, norm_topk,
      scoring): the second static body of the feed-forward, the
      drop-free routed experts (``dropless_moe``, a pure function of
      arrays): ``wg/wu`` are (E, e, f) and ``wd`` (E, f, e) stacks, one
      more operand ``wr`` (e, E) is the router, the packed axis's padding
      (``mflat`` out of bounds) is routed nowhere, and the per-expert
      assignment counts (E,) come back beside ``x``.

    Returns ``(x_out (n_pad, e), new_k_pages, new_v_pages)`` — the caller
    (the pool, which owns page state) commits the returned pages. The
    mathematics is the eager layer's: the same ``rms_norm`` kernel and
    epsilon, ``jnp.matmul`` on the operands' own dtype (``F.linear``),
    activations in the stream's dtype.
    """
    attend = _build_ragged_call(
        b_pad, t_pad, nh, hd, npages, page_size, kvh, max_pages,
        scale, window, False, True, interpret, block)
    if router is not None:
        from ...incubate.distributed.models.moe.dropless import dropless_moe

    def run(k_pages, v_pages, x, ln1, wq, wk, wv, wo, *rest):
        rest = list(rest)
        bq = bk = bv = None
        if has_bias:
            bq, bk, bv = rest[:3]
            rest = rest[3:]
        ln2, wg, wu, wd, cos, sin, tok, gm, slots, rows, *rest = rest
        eva = [rest.pop(0) for _ in range(3 if n_sum else 0)]
        gq, gk = (rest.pop(0), rest.pop(0)) if qk_norm else (None, None)
        _, pos, mr, mc, mflat = tok
        (pg, of), tbl = slots, rows[:, :max_pages]
        lens, q_lens = rows[:, max_pages], rows[:, max_pages + 1]
        if unit_offset:
            ln1, ln2 = (1.0 + g.astype(jnp.float32) for g in (ln1, ln2))
        h = rms_norm(x, ln1, eps)
        xq = jnp.matmul(h, wq)
        xk = jnp.matmul(h, wk)
        xv = jnp.matmul(h, wv)
        if has_bias:
            xq, xk, xv = xq + bq, xk + bk, xv + bv
        qh = xq.reshape(1, n_pad, nh, hd)
        kh = xk.reshape(1, n_pad, kvh, hd)
        vh = xv.reshape(n_pad, kvh, hd)
        if qk_norm:
            qh, kh = head_rms(qh, gq, eps), head_rms(kh, gk, eps)
        qh = apply_rotary_emb(qh, cos, sin, position_ids=pos)[0]
        kh = apply_rotary_emb(kh, cos, sin, position_ids=pos)[0]
        # land this chunk's K/V in the pages (the pool computed the
        # slot plan; padding rows carry out-of-bounds page ids and drop)
        kp = k_pages.at[pg, of].set(
            kh.astype(k_pages.dtype), mode="drop")
        vp = v_pages.at[pg, of].set(
            vh.astype(v_pages.dtype), mode="drop")
        if n_sum:
            phi, mu, (src, s_pg, s_of) = eva
            kt, vt = pool_pages(kp[src], vp[src], phi, mu, scale)
            kp = kp.at[s_pg, s_of].set(kt.astype(kp.dtype), mode="drop")
            vp = vp.at[s_pg, s_of].set(vt.astype(vp.dtype), mode="drop")
        qm = qh[gm]                        # (b_pad, t_pad, nh, hd)
        out = attend(qm, kp, vp, tbl, lens, q_lens)
        # scatter back to the packed axis (padding entries target the
        # out-of-bounds slot n_pad: drop) + o_proj
        attn = jnp.zeros((n_pad, nh, hd), qh.dtype)
        attn = attn.at[mflat].set(out[mr, mc], mode="drop")
        x = x + jnp.matmul(attn.reshape(n_pad, nh * hd), wo)
        h2 = rms_norm(x, ln2, eps)
        if router is not None:
            top_k, norm_topk, scoring = router
            y, sizes = dropless_moe(
                h2, rest[0], None, wg, wu, wd, None, top_k,
                norm_topk=norm_topk, valid=mflat < n_pad, scoring=scoring)
            return x + y, kp, vp, sizes
        y = jnp.matmul(
            jax.nn.silu(jnp.matmul(h2, wg)) * jnp.matmul(h2, wu), wd)
        return x + y, kp, vp

    run.__name__ = "ragged_layer_step"      # jit(<name>) in the traces
    return run


@functools.lru_cache(maxsize=256)
def _jitted_layer_step(cfg, donate, mesh):
    """The layer program of a shape. Pools donated on the chip: the pool
    holds the only reference and commits what comes back. ``mesh`` keys
    the entry only (the norm kernel wraps itself per mesh)."""
    del mesh
    return jax.jit(_build_layer_call(*cfg),
                   donate_argnums=(0, 1) if donate else ())


# --------------------------------------------------------------------------
# latent (MLA) pages: one [num_pages, page_size, D] array whose row is the
# key of every query head and, in its leading ``value_dim`` numbers, the
# value. The absorbed ragged kernel.
# --------------------------------------------------------------------------
LATENT_PAGES_PER_STEP = 16       # pages DMA'd and multiplied a grid step
LATENT_ROW_TILE = 256            # (token, head) rows a matmul of the kernel


def latent_ragged_attention_reference(q, pages, page_table, seq_lens,
                                      q_lens, value_dim, sm_scale=None):
    """``jax.numpy`` float32 reference of the latent ragged kernel: q
    (B, T, H, D) right-aligned as in :func:`paged_ragged_attention`,
    pages (NP, P, D). Row i's last q_lens[i] tokens attend causally over
    its seq_lens[i] cached rows; every head scores against the whole
    cached row and takes its leading ``value_dim`` numbers as the value.
    Returns (B, T, H, value_dim) float32, padded leading rows zero."""
    b, t, h, d = q.shape
    _, page_size, _ = pages.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    max_pages = page_table.shape[1]
    kv = pages.astype(jnp.float32)[page_table].reshape(
        b, max_pages * page_size, d)
    s = jnp.einsum("bthd,bkd->bthk", q.astype(jnp.float32), kv,
                   precision=jax.lax.Precision.HIGHEST) * scale
    kpos = jnp.arange(max_pages * page_size)[None, None, :]
    tok = jnp.arange(t)[None, :, None]
    qpos = seq_lens[:, None, None] - t + tok
    keep = (kpos <= qpos) & (kpos < seq_lens[:, None, None]) \
        & (tok >= t - q_lens[:, None, None])
    s = jnp.where(keep[:, :, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, -1)
    out = jnp.einsum("bthk,bkv->bthv", p, kv[..., :value_dim],
                     precision=jax.lax.Precision.HIGHEST)
    real = jnp.any(keep, -1)[:, :, None, None]
    return jnp.where(real, out, 0.0)


def _latent_kernel(scale, page_size, ppb, n_steps, t, h, dv, tm,
                   tbl_ref, lens_ref, qlens_ref, q_ref, *refs):
    """Grid (row b, page block p). A step holds ``ppb`` pages of row b
    in VMEM, each read once, and multiplies them with the row's real
    (token, head) query rows in tiles of ``tm``: scores against the whole
    cached row, the value its leading ``dv`` numbers. Online softmax
    state (m, l, acc) stays in VMEM across the page axis, float32."""
    page_refs = refs[:ppb]
    o_ref, m_ref, l_ref, acc_ref = refs[ppb:]
    del tbl_ref
    b = pl.program_id(0)
    p = pl.program_id(1)
    seq_len = lens_ref[b]
    q_len = qlens_ref[b]
    n_tiles = (t * h) // tm
    # rows below (t - q_len) * h are the right-alignment's padding
    first = ((t - q_len) * h) // tm
    span = ppb * page_size
    log_h = h.bit_length() - 1 if h & (h - 1) == 0 else None

    def token_of(row):
        return jax.lax.shift_right_logical(row, log_h) \
            if log_h is not None else row // h

    def rows(i):
        return pl.ds(pl.multiple_of(i * tm, tm), tm)

    @pl.when(p == 0)
    def _():
        def init(i, c):
            m_ref[rows(i), :] = jnp.full((tm, m_ref.shape[1]), NEG_INF,
                                         jnp.float32)
            l_ref[rows(i), :] = jnp.zeros((tm, l_ref.shape[1]),
                                          jnp.float32)
            acc_ref[rows(i), :] = jnp.zeros((tm, dv), jnp.float32)
            return c
        jax.lax.fori_loop(first, n_tiles, init, 0)

    @pl.when(p * span < seq_len)
    def _():
        k = jnp.concatenate([r[0] for r in page_refs], 0) \
            if ppb > 1 else page_refs[0][0]            # (span, D)
        v = k[:, :dv]

        def tile(i, c):
            q = q_ref[0, rows(i), :]                     # (tm, D)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            kpos = p * span + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            tok = token_of(i * tm + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0))
            qpos = seq_len - t + tok
            keep = (kpos <= qpos) & (kpos < seq_len) & (tok >= t - q_len)
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_ref[rows(i), :][:, :1]
            l_prev = l_ref[rows(i), :][:, :1]
            m_cur = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            corr = jnp.exp(m_prev - m_cur)
            pv = jnp.where(keep, jnp.exp(s - m_cur), 0.0)
            l_ref[rows(i), :] = jnp.broadcast_to(
                corr * l_prev + jnp.sum(pv, -1, keepdims=True),
                (tm, l_ref.shape[1]))
            acc_ref[rows(i), :] = acc_ref[rows(i), :] * corr \
                + jax.lax.dot_general(
                    pv.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            m_ref[rows(i), :] = jnp.broadcast_to(
                m_cur, (tm, m_ref.shape[1]))
            return c
        jax.lax.fori_loop(first, n_tiles, tile, 0)

    @pl.when(p == n_steps - 1)
    def _():
        def zero(i, c):
            o_ref[0, rows(i), :] = jnp.zeros((tm, dv), o_ref.dtype)
            return c
        jax.lax.fori_loop(0, first, zero, 0)

        def done(i, c):
            tok = token_of(i * tm + jax.lax.broadcasted_iota(
                jnp.int32, (tm, dv), 0))
            out = acc_ref[rows(i), :] / jnp.maximum(
                l_ref[rows(i), :][:, :1], 1e-30)
            o_ref[0, rows(i), :] = jnp.where(
                tok >= t - q_len, out, 0.0).astype(o_ref.dtype)
            return c
        jax.lax.fori_loop(first, n_tiles, done, 0)


def _build_latent_call(b, t, h, d, dv, npages, page_size, max_pages,
                       scale, interpret):
    """The latent ragged pallas dispatch as a pure function of its
    static config: run(q (B, T, H, D), pages (NP, P, D), page_table,
    seq_lens, q_lens) -> (B, T, H, dv)."""
    from jax.experimental.pallas import tpu as pltpu

    ppb = min(LATENT_PAGES_PER_STEP, max_pages)
    if max_pages % ppb:
        raise ValueError(
            f"latent_ragged_attention: the page table's width "
            f"{max_pages} is no multiple of {ppb} pages a step")
    n_steps = max_pages // ppb
    m = t * h
    tm = min(LATENT_ROW_TILE, m)
    if m % tm:
        raise ValueError(
            f"latent_ragged_attention: {t} tokens x {h} heads is no "
            f"multiple of the row tile {tm}")

    def q_map(b_, p_, *pref):
        return (b_, 0, 0)

    def page_map(j):
        return lambda b_, p_, tbl, *pref: (tbl[b_, p_ * ppb + j], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_steps),
        in_specs=[pl.BlockSpec((1, m, d), q_map)] + [
            pl.BlockSpec((1, page_size, d), page_map(j))
            for j in range(ppb)],
        out_specs=pl.BlockSpec((1, m, dv), q_map),
        scratch_shapes=[
            pltpu.VMEM((m, 128), jnp.float32),
            pltpu.VMEM((m, 128), jnp.float32),
            pltpu.VMEM((m, dv), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _latent_kernel, scale, page_size, ppb, n_steps, t, h, dv, tm)

    def run(q, pages, tbl, lens, q_lens):
        out = pl.pallas_call(
            kernel,
            name="latent_ragged_attention",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, m, dv), q.dtype),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=96 * 1024 * 1024,
            ) if not interpret else None,
        )(tbl, lens, q_lens, q.reshape(b, m, d), *([pages] * ppb))
        return out.reshape(b, t, h, dv)

    return run


@functools.lru_cache(maxsize=512)
def _jitted_latent_call(cfg):
    return jax.jit(_build_latent_call(*cfg))


def latent_ragged_attention(q, pages, page_table, seq_lens, q_lens,
                            value_dim, sm_scale=None, interpret=None):
    """Absorbed latent attention over pages, decode rows and prompt
    chunks in one call: q (B, T, H, D) right-aligned like
    :func:`paged_ragged_attention` (the rows' latent rows already in the
    pages; seq_lens counts them), pages (NP, P, D) with D the cached row
    (latent | rope key). Each latent page of a row is read once and used
    as the key (all D) and the value (the first ``value_dim``) of all H
    query heads. Returns (B, T, H, value_dim); padded rows exact zeros."""
    b, t, h, d = q.shape
    npages, page_size, _ = pages.shape
    max_pages = page_table.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = not on_tpu()
    cfg = (b, t, h, d, int(value_dim), npages, page_size, max_pages,
           float(scale), bool(interpret))
    args = (q, pages, page_table.astype(jnp.int32),
            seq_lens.astype(jnp.int32),
            jnp.asarray(q_lens).astype(jnp.int32))
    if any(isinstance(x, jax.core.Tracer) for x in args):
        return _build_latent_call(*cfg)(*args)
    with telemetry.span("kernel.latent_ragged", rows=b, t=t,
                        max_pages=max_pages):
        return _jitted_latent_call(cfg)(*args)


@functools.lru_cache(maxsize=256)
def _jitted_latent_step(cfg, donate):
    """One program: this chunk's rows into the pages (entries whose page
    id is out of bounds are padding and drop), the queries gathered
    right-aligned, the latent ragged kernel. Pages donated on the chip:
    the pool holds the only reference and commits what comes back."""
    attend = _build_latent_call(*cfg)

    def run(q, toks, pg, of, gm, pages, tbl, lens, q_lens):
        pages = pages.at[pg, of].set(toks.astype(pages.dtype), mode="drop")
        return attend(q[gm], pages, tbl, lens, q_lens), pages

    run.__name__ = "latent_ragged_step"
    return jax.jit(run, donate_argnums=(5,) if donate else ())


def latent_ragged_step(q, toks, pg, of, gm, pages, page_table, seq_lens,
                       q_lens, value_dim, sm_scale=None, interpret=None):
    """The packed attention step of a latent pool (see
    ``PagedKVCacheManager.latent_ragged_step``): q (n_pad, H, D), toks
    (n_pad, D), pg/of (n_pad,) the write plan padded with out-of-bounds
    page ids, gm (rows_pad, T) the right-align gather. Returns (out
    (rows_pad, T, H, value_dim), new pages)."""
    _, h, d = q.shape
    npages, page_size, _ = pages.shape
    b, t = gm.shape
    max_pages = page_table.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = not on_tpu()
    cfg = (b, t, h, d, int(value_dim), npages, page_size, max_pages,
           float(scale), bool(interpret))
    with telemetry.span("kernel.latent_ragged", rows=b, t=t,
                        max_pages=max_pages, fused=1):
        return _jitted_latent_step(cfg, on_tpu())(
            q, toks, jnp.asarray(pg, jnp.int32), jnp.asarray(of, jnp.int32),
            jnp.asarray(gm, jnp.int32), pages,
            page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
            jnp.asarray(q_lens).astype(jnp.int32))


def pad_plan_np(a, n, fill):
    """Pad a 1-D int32 plan operand of a step program to ``n`` entries
    with ``fill``, on the host — the single place the programs'
    out-of-bounds drop-entry contract is encoded (fill = the packed
    length for a scatter back to the packed axis, ``num_pages`` for a
    page plan)."""
    a = np.asarray(a, np.int32)      # trace-lint: ok(a host plan, no tracer)
    short = n - a.shape[0]
    if short > 0:
        a = np.concatenate([a, np.full((short,), fill, np.int32)])
    return a


def pad_plan_i32(a, n, fill):
    """:func:`pad_plan_np` on the device: padded on the host, crossing
    once — a device-side concatenate is one program per (length,
    padding) pair, and the packed length changes every step."""
    return jnp.asarray(pad_plan_np(a, n, fill))


def packed_position_index(starts, counts, rows):
    """Flat packed-axis indices of EVERY position of the listed rows,
    in row order — the multi-row sampling epilogue's gather plan.

    The unified ragged step computes the head over each row's LAST
    packed position only (one sampled token per row). Speculative
    VERIFY rows need the logits of all ``counts[i]`` positions (the
    per-position greedy acceptance compares the target's argmax at
    window slot j against draft proposal j), so the epilogue gathers
    ``starts[i] .. starts[i] + counts[i] - 1`` for each verify row
    and runs norm + lm-head over that concatenation — host-built like
    the right-align plan, so it adds no compiled attend program (the
    acceptance bound of ISSUE 19: spec rows reuse the existing bucketed
    kernel family)."""
    return jnp.asarray(packed_position_index_np(starts, counts, rows))


def packed_position_index_np(starts, counts, rows):
    """:func:`packed_position_index` on the host (int32 numpy), for a
    caller that uploads its step's index operands together."""
    return np.concatenate([
        np.arange(int(starts[i]), int(starts[i]) + int(counts[i]),
                  dtype=np.int32) for i in rows])


def upload_plan(*arrays):
    """A step's host-built int32 index operands onto the device in ONE
    transfer call, in order."""
    return jax.device_put(tuple(
        np.ascontiguousarray(a, np.int32) for a in arrays))


def paged_ragged_layer_step(k_pages, v_pages, x, weights, rope, index,
                            eps, sm_scale=None, window=0,
                            interpret=None, unit_offset=False,
                            summary=None, counts=None, block=0,
                            qk_norm=None, router=None):
    """One decoder layer of the packed serving step, one dispatch (see
    :func:`_build_layer_call` for the operand contract). ``weights`` =
    (ln1, wq, wk, wv, wo, biases, ln2, wg, wu, wd) with ``biases``
    ``None`` or the (bq, bk, bv) triple; ``rope`` = (cos, sin);
    ``index`` = (tok, gm, slots, rows): int32 device arrays of the
    bucketed shapes, built once a step and shared by every layer's
    call. Float KV pages only — int8 calibration is a host-driven wave
    replay the program cannot express.

    Returns ``(x_out, new_k_pages, new_v_pages)``; on the chip the pools
    handed in are DONATED: the page-pool owner holds the only reference
    and commits the returned arrays. ``unit_offset`` and ``summary`` =
    (phi, mu, sums) are the program's two static switches; ``counts``
    (a window-and-summary step's exact ``fed`` / ``pairs`` / ``kv_rows`` /
    ``summaries_written``, from the pool's table, and the step's
    ``few_row_rows``) ride the ``kernel.ragged`` span as attributes.
    Three more switches, each off unless given: ``block`` (the kernel's
    block-causal mask), ``qk_norm`` = (gq, gk), the per-head q/k norm
    gains, and ``router`` = (wr, top_k, norm_topk, scoring) of a routed
    feed-forward whose ``wg/wu/wd`` are the experts' stacks; with it the
    per-expert assignment counts come back as a fourth result.
    """
    from ...distributed.mesh import global_mesh

    ln1, wq, wk, wv, wo, biases, ln2, wg, wu, wd = weights
    cos, sin = rope
    _, gm, _, rows = index
    n_pad, e = x.shape
    hd = cos.shape[1]
    nh = wq.shape[1] // hd
    kvh = wk.shape[1] // hd
    npages, page_size, _, _ = k_pages.shape
    b_pad, t_pad = gm.shape
    max_pages = rows.shape[1] - 2
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)
    if interpret is None:
        interpret = not on_tpu()
    has_bias = biases is not None
    cfg = (n_pad, e, nh, kvh, hd, npages, page_size,
           b_pad, t_pad, max_pages, float(scale), int(window or 0),
           has_bias, float(eps), bool(interpret), bool(unit_offset),
           summary[2].shape[1] if summary else 0, _block_of(block),
           qk_norm is not None, tuple(router[1:]) if router else None)
    with telemetry.span("kernel.ragged", rows=b_pad, t=t_pad,
                        max_pages=max_pages, fused=1,
                        grid_steps=_ragged_grid_steps(b_pad, max_pages),
                        **({"block": cfg[-3]} if block else {}),
                        **(counts or {})):
        return _jitted_layer_step(cfg, on_tpu(), global_mesh())(
            k_pages, v_pages, x, ln1, wq, wk, wv, wo,
            *(biases if has_bias else ()), ln2, wg, wu, wd, cos, sin,
            *index, *(summary or ()), *(qk_norm or ()),
            *(router[:1] if router else ()))
