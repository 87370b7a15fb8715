"""The latent page format and the absorbed ragged kernel on the CPU: the
kernel (interpret mode) against its ``*_reference`` and against expanded
attention; prefill in chunks then decode through the latent pool against
the reference's full forward; the scheduler over the adapter; what a
latent pool shares with a K/V pool and what it refuses by name."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.framework import telemetry  # noqa: E402
from paddle_tpu.incubate.nn import PagedKVCacheManager  # noqa: E402
from paddle_tpu.incubate.nn.paged_cache import HostKVSwapSpace  # noqa: E402
from paddle_tpu.inference import (  # noqa: E402
    BatchScheduler, PagedXing4Adapter, Request)
from paddle_tpu.ops.kernels import (  # noqa: E402
    latent_ragged_attention, latent_ragged_attention_reference)
from xing4_tiny_config import tiny_config  # noqa: E402

from benchmarks.lib import common  # noqa: E402

SEED = 1


@pytest.fixture(scope="module", autouse=True)
def _interpret():
    prev = paddle.get_flags("FLAGS_pallas_interpret")
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    yield
    paddle.set_flags(prev)


def _case(b, t, h, d, mp, lens, q_lens, npages=80, page=16, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(0, 1, (b, t, h, d)), jnp.float32)
    pages = jnp.asarray(rng.normal(0, 1, (npages, page, d)), jnp.float32)
    tbl = jnp.asarray(rng.permutation(npages)[:b * mp].reshape(b, mp),
                      jnp.int32)
    return (q, pages, tbl, jnp.asarray(lens, jnp.int32),
            jnp.asarray(q_lens, jnp.int32))


@pytest.mark.parametrize("t,mp,lens,q_lens", [
    (1, 4, [5, 64, 33, 0], [1, 1, 1, 0]),            # decode rows
    (8, 4, [5, 64, 33, 0], [5, 1, 8, 0]),            # chunks and decode
    (16, 32, [500, 17, 300, 512], [16, 1, 3, 9]),    # two steps of pages
    (128, 2, [32, 20, 128, 1], [32, 1, 128, 1]),     # two row tiles
])
def test_kernel_equals_its_reference(t, mp, lens, q_lens):
    npages = max(80, 4 * mp)
    args = _case(4, t, 4, 40, mp, lens, q_lens, npages=npages)
    got = latent_ragged_attention(*args, 32)
    want = latent_ragged_attention_reference(*args, 32)
    assert got.shape == (4, t, 4, 32)
    assert float(jnp.abs(got - want).max()) < 2e-5
    # padded leading rows and padding sequences are exact zeros
    for i, n in enumerate(q_lens):
        assert not np.asarray(got[i, :t - n]).any()


def test_absorbed_kernel_equals_expanded_attention():
    """q_lat = q_nope W_UK^T against the cached latent, P c_kv through
    W_UV: the same numbers as expanding k_nope and v from the latent."""
    rng = np.random.default_rng(2)
    h, r, dr, dn, dv, n = 4, 32, 8, 16, 16, 40
    c_kv = rng.normal(0, 1, (n, r)).astype(np.float32)
    k_r = rng.normal(0, 1, (n, dr)).astype(np.float32)
    w_kvb = rng.normal(0, 0.3, (r, h, dn + dv)).astype(np.float32)
    q_n = rng.normal(0, 1, (h, dn)).astype(np.float32)
    q_r = rng.normal(0, 1, (h, dr)).astype(np.float32)
    scale = 0.2
    kv = np.einsum("nr,rhd->nhd", c_kv, w_kvb)
    s = (np.einsum("hd,nhd->hn", q_n, kv[..., :dn])
         + np.einsum("hd,nd->hn", q_r, k_r)) * scale
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("hn,nhd->hd", p, kv[..., dn:])
    pages = np.zeros((8, 16, r + dr), np.float32)
    pages.reshape(-1, r + dr)[:n] = np.concatenate([c_kv, k_r], -1)
    q_abs = np.concatenate(
        [np.einsum("hd,rhd->hr", q_n, w_kvb[..., :dn]), q_r], -1)
    o_lat = latent_ragged_attention(
        jnp.asarray(q_abs)[None, None], jnp.asarray(pages),
        jnp.arange(4, dtype=jnp.int32)[None], jnp.asarray([n], jnp.int32),
        jnp.asarray([1], jnp.int32), r, sm_scale=scale)[0, 0]
    got = np.einsum("hr,rhd->hd", np.asarray(o_lat), w_kvb[..., dn:])
    assert np.abs(got - want).max() < 1e-5


@pytest.fixture(scope="module")
def served():
    cfg = tiny_config(num_nextn_predict_layers=0)
    fam = common.load_family(cfg)
    model, _ = common.build_model(fam, cfg, SEED)
    for p in model.parameters():
        p._data = p._data.astype(jnp.float32)
    model.eval()
    return cfg, fam, model


def test_chunked_prefill_then_decode_equals_the_full_forward(served):
    """Rows of unequal length in one call, a chunk resumed mid-prompt
    beside a decode row, then decode steps: the logits of every row's
    last token against the reference's teacher-forced forward. float32
    on both sides: the absorbed form reorders the sums, no more."""
    cfg, fam, model = served
    ad = fam.serving(model, cfg)
    assert all(c.latent and c.v_pages is None for c in ad.caches)
    rng = np.random.default_rng(5)
    ids = rng.integers(1, cfg["vocab_size"], (2, 40)).astype(np.int32)
    v = cfg["vocab_size"]
    gather = np.broadcast_to(np.arange(v), (2, 40, v)).astype(np.int32)
    _, _, want = fam.serve_logits(cfg, SEED, ids, gather)
    ad.alloc("a"), ad.alloc("b")
    lg = ad.prefill_chunk([ids[0, :17].tolist(), ids[1, :5].tolist()],
                          ["a", "b"], [0, 0], pad_to=32).numpy()
    assert lg.shape == (2, v)
    assert np.abs(lg[0] - want[0, 16]).max() < 1e-5
    assert np.abs(lg[1] - want[1, 4]).max() < 1e-5
    lg = ad.prefill_chunk([ids[0, 17:30].tolist(), ids[1, 5:6].tolist()],
                          ["a", "b"], [17, 5], pad_to=16).numpy()
    assert np.abs(lg[0] - want[0, 29]).max() < 1e-5
    assert np.abs(lg[1] - want[1, 5]).max() < 1e-5
    for t in range(30, 40):
        lg = ad.decode_token([ids[0, t], ids[1, t - 24]], ["a", "b"]).numpy()
    assert np.abs(lg[0] - want[0, 39]).max() < 1e-5
    assert np.abs(lg[1] - want[1, 15]).max() < 1e-5
    assert ad.caches[0].seq_len("a") == 40 and ad.compile_count == 3
    with pytest.raises(ValueError, match="start_positions"):
        ad.prefill_chunk([[1]], ["a"], [3])
    ad.free("a"), ad.free("b")
    assert all(c.num_free_pages == c.num_pages for c in ad.caches)


def test_warm_builds_the_steady_steps_programs(served):
    """After ``warm`` a decode step of the warmed row count and a step
    with a prompt chunk beside it build nothing, whatever the contexts'
    page-table width."""
    cfg, fam, model = served
    ad = fam.serving(model, cfg)
    # the scheduler's set-up: its 4 rows, its buckets, the flags' chunk
    BatchScheduler(ad, **cfg["program"]["scheduler"]).warm()
    builds = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: builds.append(ev) if ev.endswith(
            "backend_compile_duration") else None)
    seqs = ["a", "b", "c", "d"]
    for s in seqs:
        ad.alloc(s)
    ad.prefill_chunk([[3] * 20, [4] * 30, [5] * 7, [6] * 7], seqs,
                     pad_to=128)
    n0 = len(builds)
    one = [[1], [2], [3], [4]]                # the scheduler's decode step
    ad.prefill_chunk(one, seqs, pad_to=8).numpy()
    ad.prefill_chunk([[7], [8], [9], [1] * 40], seqs, pad_to=128).numpy()
    ad.prefill_chunk(one, seqs, pad_to=8).numpy()     # "d" is past 2 pages
    assert len(builds) == n0, builds[n0:]


def test_scheduler_serves_greedy_tokens_and_a_steady_step_builds_nothing(
        served):
    cfg, fam, model = served
    ad = fam.serving(model, cfg)
    sched = BatchScheduler(ad, **cfg["program"]["scheduler"])
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg["vocab_size"], n).tolist()
               for n in (9, 21, 14)]
    for i, p in enumerate(prompts):
        sched.submit(Request(f"r{i}", p, max_new_tokens=12))
    builds = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: builds.append(ev) if ev.endswith(
            "backend_compile_duration") else None)
    n_before = None
    for step in range(200):
        if not (sched.num_active or sched.num_queued):
            break
        if step == 8:                  # all three rows decoding by now
            n_before = len(builds)
        sched.step()
        if step == 11:
            assert len(builds) == n_before, "a steady decode step built"
    for i, p in enumerate(prompts):
        got = sched.result(f"r{i}").generated_ids
        # greedy: one teacher-forced forward over prompt + answer
        seq = np.asarray([list(p) + got[:-1]])
        lg = model(paddle.to_tensor(seq)).numpy()[0, len(p) - 1:]
        assert lg.argmax(-1).tolist() == got and len(got) == 12


def test_expert_counts_reach_the_registry_with_the_pull(served):
    cfg, fam, model = served
    prev = paddle.get_flags("FLAGS_telemetry")
    paddle.set_flags({"FLAGS_telemetry": "metrics"})
    telemetry.reset()
    try:
        ad = fam.serving(model, cfg)
        ad.alloc("a")
        out = ad.prefill_chunk([[3, 4, 5, 6, 7]], ["a"], [0], pad_to=8)
        reg = telemetry.registry()
        assert "moe" not in reg.snapshot()
        out.numpy()                              # the pull carries them
        c = reg.snapshot()["moe"]
        moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
        assert c["calls"] == moe_layers
        # 5 real tokens x top-2: the 3 padding tokens are in no group
        assert c["assignments"] == moe_layers * 5 * 2
        assert 2 * moe_layers <= c["experts_touched"] \
            <= moe_layers * cfg["n_routed_experts"]
        assert c["expert_tokens_max"] >= moe_layers * 2
    finally:
        paddle.set_flags(prev)
        telemetry.reset()


def test_latent_pool_shares_the_booking_and_sizes_by_its_format():
    kv = PagedKVCacheManager(8, 16, 2, 24, dtype=jnp.float32)
    lat = PagedKVCacheManager(8, 16, 1, 40, dtype=jnp.float32,
                              page_format="latent", sanitizer="strict")
    assert lat.k_pages.shape == (8, 16, 40) and lat.v_pages is None
    assert lat.page_nbytes == 16 * 40 * 4 and lat.pool_nbytes == 8 * 2560
    assert kv.page_nbytes == 16 * 2 * 24 * 4 * 2
    assert PagedKVCacheManager.page_bytes(
        16, 1, 576, page_format="latent") == 16 * 1152
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(0, 1, (24, 2, 40)), jnp.float32)
    toks = jnp.asarray(rng.normal(0, 1, (24, 40)), jnp.float32)
    gm = np.zeros((1, 32), np.int32)
    gm[0, 12:] = np.arange(20)
    lat.alloc("s")
    out = lat.latent_ragged_step(q, toks, ["s"], [20], jnp.asarray(gm), 32,
                                 rows_pad=1, max_pages=2)
    assert out.shape == [1, 32, 2, 32] and lat.seq_len("s") == 20
    flat = np.asarray(lat.k_pages)[lat.seq_pages("s")].reshape(-1, 40)
    assert np.array_equal(flat[:20], np.asarray(toks[:20]))
    assert not flat[20:].any()                   # padding rows land nowhere
    # prefix attach and copy-on-write on the one array
    chain = lat.seq_pages("s")
    lat.attach("t", chain, 20)
    assert lat.num_shared_pages == 2 and lat.pending_cow("t")
    lat.latent_ragged_step(q[:8], toks[:8] + 1, ["t"], [1],
                           jnp.zeros((1, 1), jnp.int32), 32, rows_pad=1,
                           max_pages=2)
    assert lat.cow_forks == 1 and lat.seq_pages("t")[0] == chain[0]
    forked = np.asarray(lat.k_pages)[lat.seq_pages("t")[1]]
    assert np.array_equal(forked[:4], np.asarray(toks[16:20]))
    assert np.array_equal(forked[4], np.asarray(toks[0] + 1))
    assert np.array_equal(np.asarray(lat.k_pages)[chain[1]][:4],
                          np.asarray(toks[16:20]))
    lat.free("s"), lat.free("t")
    assert lat.num_free_pages == 8
    lat.assert_ref_invariants()
    with pytest.raises(ValueError, match="needs page_format='latent'"):
        kv.alloc("s")
        kv.latent_ragged_step(q[:, :, :24], toks[:, :24], ["s"], [20],
                              jnp.asarray(gm), 16)
    for bad in (dict(kv_heads=2), dict(mp_size=2), dict(kv_dtype="int8")):
        args = dict(kv_heads=1, mp_size=1)
        args.update(bad)
        with pytest.raises(ValueError, match="latent"):
            PagedKVCacheManager(8, 16, head_dim=40, page_format="latent",
                                **args)


K = jnp.zeros((1, 1, 40), jnp.float32)


@pytest.mark.parametrize("op,call", [
    ("append", lambda p, sp: p.append("s", K[0], K[0])),
    ("append_batch", lambda p, sp: p.append_batch(["s"], K, K)),
    ("append_ragged", lambda p, sp: p.append_ragged(["s"], [1], K, K)),
    ("attend", lambda p, sp: p.attend(K, ["s"])),
    ("attend_ragged", lambda p, sp: p.attend_ragged(K[None], ["s"], [1])),
    ("layer_step", lambda p, sp: p.layer_step(
        K[0], (K,) * 10, (K, K), (K, K), (K, K), 1e-6)),
    ("dense_kv", lambda p, sp: p.dense_kv(["s"])),
    ("swap_out", lambda p, sp: p.swap_out("s", sp)),
    ("swap_in", lambda p, sp: p.swap_in("s", sp)),
    ("export_seq", lambda p, sp: sp.export_seq("s", [p])),
    ("import_seq", lambda p, sp: sp.import_seq("s", [b""], [p])),
])
def test_kv_only_operations_refuse_a_latent_pool_by_name(op, call):
    pool = PagedKVCacheManager(4, 16, 1, 40, dtype=jnp.float32,
                               page_format="latent")
    pool.alloc("s")
    with pytest.raises(ValueError) as e:
        call(pool, HostKVSwapSpace(1 << 20))
    assert op in str(e.value) and "page_format='latent'" in str(e.value)
    assert pool.seq_len("s") == 0 and pool.num_free_pages == 4
