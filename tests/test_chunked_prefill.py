"""Chunked prefill + mixed prefill/decode ragged batching (ISSUE 5).

The acceptance matrix: the chunked scheduler must produce GREEDY-
IDENTICAL outputs to the token-per-step path across chunk budgets
{1, page_size, odd, > prompt}, parameterized over kv_dtype
{float32, int8} and prefix-cache on/off — plus a mid-page cached-
prefix resume, a speculative-mode run, the ragged pool append's
atomicity/COW contract, the packed-shape bucket helper, and the
ragged prefill kernel's q_lens masking.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.incubate.nn import PagedKVCacheManager
from paddle_tpu.inference import (
    BatchScheduler,
    PagedLlamaAdapter,
    Request,
    bucket_packed_tokens,
)
from paddle_tpu.inference.serving import _parse_buckets
from paddle_tpu.models import LlamaForCausalLM, llama_tiny

PAGE = 4


def _tiny_cfg(**kw):
    kw.setdefault("hidden_size", 64)
    kw.setdefault("intermediate_size", 128)
    kw.setdefault("num_hidden_layers", 1)
    kw.setdefault("num_attention_heads", 2)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("max_position_embeddings", 128)
    return llama_tiny(**kw)


@pytest.fixture(scope="module")
def model():
    paddle.seed(17)
    return LlamaForCausalLM(_tiny_cfg())


_RNG = np.random.RandomState(0)
PROMPTS = {
    "a": _RNG.randint(1, 500, 11).tolist(),
    "b": _RNG.randint(1, 500, 3).tolist(),
    "c": _RNG.randint(1, 500, 7).tolist(),
}
N_NEW = {"a": 4, "b": 5, "c": 3}


def _serve(model, chunked, kv=None, prefix=False, budget=8,
           buckets=None):
    adapter = PagedLlamaAdapter(model, num_pages=96, page_size=PAGE,
                                max_length=128, kv_cache_dtype=kv)
    sched = BatchScheduler(
        adapter, max_batch_size=4, prefix_cache=prefix,
        chunked_prefill=chunked, prefill_chunk_tokens=budget,
        serving_buckets=buckets)
    for rid, p in PROMPTS.items():
        sched.submit(Request(rid, list(p), max_new_tokens=N_NEW[rid]))
    done = sched.run_until_complete()
    stats = sched.page_pool_stats()
    if not prefix:  # the radix tree deliberately retains pages
        assert stats["free_pages"] == stats["total_pages"], stats
    return {k: v.generated_ids for k, v in done.items()}, sched, adapter


_BASE = {}


def _baseline(model, kv):
    """Token-per-step oracle, once per kv dtype."""
    if kv not in _BASE:
        _BASE[kv] = _serve(model, chunked=False, kv=kv)[0]
    return _BASE[kv]


_slow = pytest.mark.slow


class TestGreedyIdentical:
    # chunk budgets: degenerate 1, exactly one page, odd (straddles
    # page boundaries), and larger than every prompt (whole-prompt
    # prefill in one call). The fast tier runs a representative slice
    # (odd fp32, page int8); the full budget x dtype matrix rides the
    # slow tier to respect the tier-1 wall-clock budget.
    @pytest.mark.parametrize("kv,budget", [
        (None, 5),
        ("int8", PAGE),
        pytest.param(None, 1, marks=_slow),
        pytest.param(None, PAGE, marks=_slow),
        pytest.param(None, 64, marks=_slow),
        pytest.param("int8", 1, marks=_slow),
        pytest.param("int8", 5, marks=_slow),
        pytest.param("int8", 64, marks=_slow),
    ])
    def test_matches_token_per_step(self, model, kv, budget):
        got, sched, adapter = _serve(model, chunked=True, kv=kv,
                                     budget=budget)
        assert got == _baseline(model, kv), (kv, budget)
        cs = sched.chunk_stats
        assert cs["prefill_tokens"] == sum(map(len, PROMPTS.values()))
        # every compiled ragged shape is a configured bucket
        buckets = set(sched.serving_buckets)
        assert adapter._dispatch_shapes <= buckets
        assert adapter.compile_count <= len(buckets)

    @pytest.mark.parametrize("kv,budget", [
        (None, 5),
        pytest.param(None, PAGE, marks=_slow),
        pytest.param("int8", PAGE, marks=_slow),
        pytest.param("int8", 5, marks=_slow),
    ])
    def test_matches_with_prefix_cache(self, model, kv, budget):
        got, sched, _ = _serve(model, chunked=True, kv=kv,
                               prefix=True, budget=budget)
        assert got == _baseline(model, kv), (kv, budget)

    def test_step_stats_and_utilization(self, model):
        adapter = PagedLlamaAdapter(model, num_pages=96,
                                    page_size=PAGE, max_length=128)
        sched = BatchScheduler(adapter, max_batch_size=4,
                               chunked_prefill=True,
                               prefill_chunk_tokens=8)
        for rid, p in PROMPTS.items():
            sched.submit(Request(rid, list(p),
                                 max_new_tokens=N_NEW[rid]))
        ev = sched.step()
        assert ev["prefill_tokens"] == 8  # the budget, split across rows
        assert ev["decode_tokens"] == 0
        assert 0 < ev["chunk_utilization"] <= 1.0
        assert ev["compile_count"] >= 1
        sched.run_until_complete()
        # steady-state compile count bounded by the bucket set
        assert adapter.compile_count <= len(sched.serving_buckets)

    def test_chunked_auto_detected_and_forcible(self, model):
        adapter = PagedLlamaAdapter(model, num_pages=32,
                                    page_size=PAGE, max_length=128)
        assert BatchScheduler(adapter).chunked_prefill  # auto-on

        class DecodeOnly:
            caches = adapter.caches

            def decode_token(self, toks, sids):
                raise NotImplementedError

        assert not BatchScheduler(DecodeOnly()).chunked_prefill
        with pytest.raises(ValueError, match="prefill_chunk"):
            BatchScheduler(DecodeOnly(), chunked_prefill=True)


class TestPrefixResume:
    def test_mid_page_cached_prefix_resume(self, model):
        """A prefix hit that ends MID-PAGE: the chunked resume's first
        append lands in a shared partial page, forks it copy-on-write,
        and the outputs still match the token-per-step path."""
        rng = np.random.RandomState(7)
        shared = rng.randint(1, 500, 10).tolist()  # 2.5 pages of 4
        tails = {f"r{i}": rng.randint(1, 500, 3 + i).tolist()
                 for i in range(3)}

        def run(chunked):
            adapter = PagedLlamaAdapter(model, num_pages=96,
                                        page_size=PAGE, max_length=128)
            sched = BatchScheduler(adapter, max_batch_size=4,
                                   prefix_cache=True,
                                   chunked_prefill=chunked,
                                   prefill_chunk_tokens=8)
            out = {}
            for wave in (0, 1):
                for rid, t in tails.items():
                    sched.submit(Request(f"{rid}w{wave}", shared + t,
                                         max_new_tokens=3))
                done = sched.run_until_complete()
                for k, v in done.items():
                    out[k] = v.generated_ids
            return out, sched

        base, _ = run(False)
        got, sched = run(True)
        assert got == base
        ps = sched.prefix_stats
        assert ps["hit_tokens"] > 0
        # the hits genuinely resumed mid-page
        assert ps["hit_tokens"] % PAGE != 0
        assert sched.page_pool_stats()["cow_forks"] > 0

    def test_page_aligned_lookup(self, model):
        """prefix_align=page_size rounds hits down to full pages: the
        resume never pays the shared-tail COW fork."""
        rng = np.random.RandomState(7)
        shared = rng.randint(1, 500, 10).tolist()
        adapter = PagedLlamaAdapter(model, num_pages=96,
                                    page_size=PAGE, max_length=128)
        sched = BatchScheduler(adapter, max_batch_size=4,
                               prefix_cache=True,
                               prefill_chunk_tokens=8,
                               prefix_align=PAGE)
        for wave in (0, 1):
            sched.submit(Request(f"w{wave}", shared + [7, 8, 9],
                                 max_new_tokens=2))
            sched.run_until_complete()
        assert sched.prefix_stats["hit_tokens"] > 0
        assert sched.prefix_stats["hit_tokens"] % PAGE == 0

    def test_match_align_trims_chains(self):
        from paddle_tpu.inference import RadixPrefixCache

        pool = PagedKVCacheManager(16, PAGE, 1, 2, dtype=jnp.float32)
        pool.alloc("s")
        toks = list(range(10))
        for _ in toks:
            pool.append("s", np.zeros((1, 2), "float32"),
                        np.zeros((1, 2), "float32"))
        tree = RadixPrefixCache([pool])
        tree.insert(toks, [pool.seq_pages("s")])
        full = tree.match(toks)
        assert full.length == 10 and len(full.chains[0]) == 3
        aligned = tree.match(toks, align=PAGE)
        assert aligned.length == 8
        assert len(aligned.chains[0]) == 2  # partial tail page dropped
        assert aligned.chains[0] == full.chains[0][:2]
        pool.free("s")


@_slow  # ~1 min: two full schedulers + draft/target adapter pairs
class TestSpeculativeChunked:
    def test_spec_prompt_phase_chunked_token_identical(self):
        cfg = _tiny_cfg(num_hidden_layers=2)
        paddle.seed(0)
        target = LlamaForCausalLM(cfg)
        paddle.seed(1)
        draft = LlamaForCausalLM(_tiny_cfg(num_hidden_layers=1))
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, 500, n).tolist() for n in (5, 9, 3)]

        def run(spec, chunked):
            ad = PagedLlamaAdapter(target, num_pages=256,
                                   page_size=PAGE)
            kw = {}
            if spec:
                kw = dict(draft_model=PagedLlamaAdapter(
                    draft, num_pages=256, page_size=PAGE), draft_k=3)
            sched = BatchScheduler(ad, max_batch_size=4,
                                   chunked_prefill=chunked,
                                   prefill_chunk_tokens=8, **kw)
            for i, p in enumerate(prompts):
                sched.submit(Request(f"r{i}", list(p),
                                     max_new_tokens=10))
            done = sched.run_until_complete()
            return ({k: v.generated_ids for k, v in done.items()},
                    sched)

        plain, _ = run(False, False)
        got, sched = run(True, True)
        assert plain == got
        assert sched.spec_stats["rounds"] > 0
        # the prompt phase really ran chunked on both adapters
        assert sched.chunk_stats["chunk_calls"] > 0
        assert sched.chunk_stats["prefill_tokens"] == \
            sum(len(p) for p in prompts)


class TestRaggedAppend:
    def _pool(self, kv=None, num_pages=16):
        return PagedKVCacheManager(num_pages, PAGE, 2, 8,
                                   dtype=jnp.float32, kv_dtype=kv)

    def test_matches_sequential_appends_fp32(self):
        rng = np.random.RandomState(4)
        a, b = self._pool(), self._pool()
        for mgr in (a, b):
            mgr.alloc("x")
            mgr.alloc("y")
        counts = [5, 3]
        ks = rng.randn(sum(counts), 2, 8).astype("float32")
        vs = rng.randn(sum(counts), 2, 8).astype("float32")
        a.append_ragged(["x", "y"], counts, ks, vs)
        off = 0
        for s, c in zip(["x", "y"], counts):
            for j in range(c):
                b.append(s, ks[off + j], vs[off + j])
            off += c
        np.testing.assert_array_equal(np.asarray(a.k_pages),
                                      np.asarray(b.k_pages))
        np.testing.assert_array_equal(np.asarray(a.v_pages),
                                      np.asarray(b.v_pages))
        assert a.seq_len("x") == 5 and a.seq_len("y") == 3

    def test_int8_bitwise_matches_sequential(self):
        # the quantized ragged write replays per-token calibration
        # order (wave = one token per chunk), so the stored int8
        # bytes AND scale sidecars are bit-identical to sequential
        # appends — what keeps chunked int8 greedy-identical
        rng = np.random.RandomState(5)
        a, b = self._pool("int8"), self._pool("int8")
        for mgr in (a, b):
            mgr.alloc("x")
            mgr.alloc("y")
        counts = [6, 3]
        ks = rng.randn(sum(counts), 2, 8).astype("float32")
        vs = rng.randn(sum(counts), 2, 8).astype("float32")
        a.append_ragged(["x", "y"], counts, ks, vs)
        off = 0
        for s, c in zip(["x", "y"], counts):
            for j in range(c):
                b.append(s, ks[off + j], vs[off + j])
            off += c
        for mgr in (a, b):
            mgr.assert_ref_invariants()
        np.testing.assert_array_equal(np.asarray(a.k_pages),
                                      np.asarray(b.k_pages))
        np.testing.assert_array_equal(np.asarray(a.v_pages),
                                      np.asarray(b.v_pages))
        np.testing.assert_array_equal(np.asarray(a.k_scales),
                                      np.asarray(b.k_scales))
        np.testing.assert_array_equal(np.asarray(a.v_scales),
                                      np.asarray(b.v_scales))

    @pytest.mark.parametrize("kv", [None, "int8"])
    def test_capacity_precheck_is_atomic(self, kv):
        pool = self._pool(kv, num_pages=2)
        pool.alloc("s")
        toks = np.zeros((12, 2, 8), "float32")
        with pytest.raises(RuntimeError, match="exhausted"):
            pool.append_ragged(["s"], [12], toks, toks)  # needs 3 pages
        # nothing mutated: lens and free list untouched
        assert pool.seq_len("s") == 0
        assert pool.num_free_pages == 2
        pool.assert_ref_invariants()

    def test_cow_fork_counts_in_precheck_and_preserves_shared(self):
        pool = self._pool(num_pages=8)
        pool.alloc("w")
        rng = np.random.RandomState(1)
        ks = rng.randn(6, 2, 8).astype("float32")
        pool.append_ragged(["w"], [6], ks, ks)
        chain = pool.seq_pages("w")
        pool.incref(chain)  # a tree-style second owner
        before = np.asarray(pool.k_pages[chain[-1]]).copy()
        # mid-page resume on the shared tail: must fork, not overwrite
        assert pool.pending_cow("w")
        assert pool.ragged_pages_needed(["w"], [3]) == 2  # fork + new
        more = rng.randn(3, 2, 8).astype("float32")
        pool.append_ragged(["w"], [3], more, more)
        np.testing.assert_array_equal(
            np.asarray(pool.k_pages[chain[-1]]), before)
        assert pool.seq_pages("w")[-2] != chain[-1]
        assert pool.cow_forks == 1
        pool.assert_ref_invariants()


class TestBucketHelper:
    def test_rounds_up_to_configured_bucket(self):
        buckets = _parse_buckets("8,16,64")
        assert bucket_packed_tokens(1, buckets) == 8
        assert bucket_packed_tokens(8, buckets) == 8
        assert bucket_packed_tokens(9, buckets) == 16
        assert bucket_packed_tokens(17, buckets) == 64

    def test_beyond_largest_bucket_next_pow2(self):
        buckets = _parse_buckets("8,16")
        assert bucket_packed_tokens(17, buckets) == 32
        assert bucket_packed_tokens(100, buckets) == 128

    def test_flag_default_and_validation(self):
        assert bucket_packed_tokens(3) >= 3  # FLAGS_serving_buckets
        with pytest.raises(ValueError):
            bucket_packed_tokens(0)
        with pytest.raises(ValueError):
            _parse_buckets("")


class TestRaggedPrefillKernel:
    def test_q_lens_masks_padded_rows(self):
        from paddle_tpu.ops.kernels import paged_ragged_attention

        rng = np.random.RandomState(3)
        np_, p, kvh, d, h = 8, 4, 2, 8, 2
        kp = rng.randn(np_, p, kvh, d).astype("float32")
        vp = rng.randn(np_, p, kvh, d).astype("float32")
        tbl = np.asarray([[0, 1, 2], [3, 4, 5]], np.int32)
        lens = np.asarray([9, 6], np.int32)
        t = 4
        q = rng.randn(2, t, h, d).astype("float32")
        q_lens = np.asarray([4, 2], np.int32)
        out = np.asarray(paged_ragged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tbl), jnp.asarray(lens),
            q_lens=jnp.asarray(q_lens)))
        # padded leading rows are exact zeros
        np.testing.assert_array_equal(out[1, :2], 0.0)
        # real rows match the unmasked kernel at matching alignment
        full = np.asarray(paged_ragged_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tbl), jnp.asarray(lens)))
        np.testing.assert_allclose(out[0], full[0], rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(out[1, 2:], full[1, 2:],
                                   rtol=1e-5, atol=1e-5)
