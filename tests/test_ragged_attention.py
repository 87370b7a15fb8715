"""Unified ragged paged-attention kernel (ISSUE 13, ROADMAP item 2).

One Pallas kernel serves every packed row kind — single-token decode
rows and multi-token prefill chunks alike carry their own q_lens and
ride right-aligned through ONE program per packed config, replacing
the decode/prefill kernel pair. The acceptance matrix here: kernel
parity vs the dense reference for decode-only / prefill-only / mixed
batches x kv {float32, int8} x window on/off, the (B, H, D) decode
entries as the T=1 shape of the same call, the one place the pool
builds a step's tables, warm LRU-dispatch reuse across pool instances,
the layer program (norm, qkv + RoPE + page scatter, the kernel, o_proj,
norm, MLP: one dispatch over the pool's pages, the weights operands),
which body an adapter runs from what it can observe, the programmed
step against the eager body and the ``decode_token`` oracle, the step's
tables built and uploaded once, end-to-end greedy identity of both
bodies with the token-per-step scheduler x prefix on/off, and the
attend program count bound (one program per config).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.incubate.nn import PagedKVCacheManager
from paddle_tpu.inference import (
    BatchScheduler,
    PagedLlamaAdapter,
    Request,
)
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.ops.kernels.paged_attention import (
    _jitted_ragged_call,
    paged_attention,
    paged_attention_reference,
    paged_ragged_attention,
    paged_ragged_attention_reference,
)

PAGE = 4
_slow = pytest.mark.slow


def _pages(rng, NP, P, KVH, D, quant=False):
    if quant:
        kp = rng.randint(-127, 128, (NP, P, KVH, D)).astype(np.int8)
        vp = rng.randint(-127, 128, (NP, P, KVH, D)).astype(np.int8)
        ks = rng.rand(NP, KVH).astype("float32") * 0.1 + 1e-3
        vs = rng.rand(NP, KVH).astype("float32") * 0.1 + 1e-3
        return (jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(ks), jnp.asarray(vs))
    kp = jnp.asarray(rng.randn(NP, P, KVH, D), jnp.float32)
    vp = jnp.asarray(rng.randn(NP, P, KVH, D), jnp.float32)
    return kp, vp, None, None


class TestUnifiedKernelParity:
    """paged_ragged_attention vs the dense reference over the full
    row-kind matrix — the tentpole's correctness core."""

    def _run(self, lens, q_lens, T, quant=False, window=0, H=4,
             KVH=2, D=32, seed=0, MAXP=None, dtype=jnp.float32,
             tol=2e-4):
        rng = np.random.RandomState(seed)
        B = len(lens)
        P = PAGE
        MAXP = MAXP or max(-(-max(lens) // P), 1)
        NP = B * MAXP + 4
        kp, vp, ks, vs = _pages(rng, NP, P, KVH, D, quant)
        if not quant:
            kp, vp = kp.astype(dtype), vp.astype(dtype)
        tbl = jnp.asarray(
            rng.permutation(NP)[:B * MAXP].reshape(B, MAXP), jnp.int32)
        ln = jnp.asarray(lens, jnp.int32)
        ql = jnp.asarray(q_lens, jnp.int32)
        q = jnp.asarray(rng.randn(B, T, H, D), dtype)
        out = paged_ragged_attention(
            q, kp, vp, tbl, ln, q_lens=ql, window=window,
            k_scales=ks, v_scales=vs)
        ref = paged_ragged_attention_reference(
            q, kp, vp, tbl, ln, q_lens=ql, window=window,
            k_scales=ks, v_scales=vs)
        assert out.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                                   atol=tol, rtol=tol)
        return np.asarray(out, np.float32)

    @pytest.mark.parametrize("quant", [False, True])
    def test_decode_only_rows(self, quant):
        # every row q_lens=1 at T=1: the decode shape through the
        # unified kernel
        self._run(lens=(9, 17, 4), q_lens=(1, 1, 1), T=1, quant=quant)

    @pytest.mark.parametrize("quant", [False, True])
    def test_prefill_only_rows(self, quant):
        self._run(lens=(11, 7), q_lens=(4, 3), T=4, quant=quant)

    @pytest.mark.parametrize("quant", [False, True])
    def test_mixed_decode_and_prefill_rows(self, quant):
        # the chunked-serving shape: decode rows (q_lens=1) and
        # prefill chunks share one call, right-aligned
        out = self._run(lens=(13, 9, 6, 21), q_lens=(1, 4, 2, 1),
                        T=4, quant=quant)
        # padded leading rows are exact zeros
        np.testing.assert_array_equal(out[0, :3], 0.0)
        np.testing.assert_array_equal(out[2, :2], 0.0)

    @pytest.mark.parametrize("window", [3, PAGE, 7])
    def test_windowed_mixed_rows(self, window):
        self._run(lens=(13, 9, 21), q_lens=(1, 3, 2), T=4,
                  window=window)

    @_slow
    @pytest.mark.parametrize("quant", [False, True])
    @pytest.mark.parametrize("window", [0, 5])
    def test_full_matrix_gqa(self, quant, window):
        self._run(lens=(19, 8, 26, 5), q_lens=(1, 3, 4, 2), T=4,
                  quant=quant, window=window, H=8, KVH=2, seed=3)

    def test_padding_rows_inert(self):
        # a seq_len=0 padding row (the bucketed dispatch's filler)
        # returns exact zeros without poisoning the softmax state
        out = self._run(lens=(9, 0), q_lens=(2, 1), T=2)
        np.testing.assert_array_equal(out[1], 0.0)

    # -- the (row, page block) grid of ISSUE 31: a step is 16 whole
    # pages (64 tokens at this page size), a KV head's query rows are
    # (token, head-in-group) pairs in tiles of 64

    @pytest.mark.parametrize("maxp", [1, 2, 3, 24, 32])
    def test_table_widths_around_the_pages_a_step(self, maxp):
        # widths that are no multiple of the 16 pages a step (the last
        # block names columns past the table: clamped, masked) and one
        # that is; the longest row fills the table to its last token
        full = maxp * PAGE
        lens = (full, max(full // 2, 1), 1)
        self._run(lens=lens, q_lens=(1, 1, 1), T=1, MAXP=maxp)

    @pytest.mark.parametrize("tail", [1, PAGE - 1, PAGE + 1])
    def test_row_ends_inside_a_blocks_first_pages(self, tail):
        # 64 tokens a block: the second block holds `tail` tokens
        self._run(lens=(64 + tail, 64, 128 + tail),
                  q_lens=(1, 2, 2), T=2, MAXP=40)

    @pytest.mark.parametrize("quant", [False, True])
    def test_padding_rows_between_real_ones(self, quant):
        out = self._run(lens=(70, 0, 9, 0, 0, 131),
                        q_lens=(1, 0, 3, 1, 0, 4), T=4, quant=quant,
                        MAXP=33)
        for r in (1, 3, 4):
            np.testing.assert_array_equal(out[r], 0.0)

    @pytest.mark.parametrize("group", [1, 4, 8])
    def test_chunk_and_decode_rows_of_64_tokens(self, group):
        # t = 64 with q_lens 1, 17 and 64 in one call: the decode rows
        # of a step that carries a prompt chunk. A group's 64 * group
        # query rows go tile by tile from the first real token's tile
        out = self._run(lens=(130, 19, 70, 0), q_lens=(1, 17, 64, 0),
                        T=64, H=2 * group, KVH=2, MAXP=40)
        np.testing.assert_array_equal(out[0, :63], 0.0)
        np.testing.assert_array_equal(out[1, :47], 0.0)
        np.testing.assert_array_equal(out[3], 0.0)

    @pytest.mark.parametrize("window", [50, 64, 70, 129])
    def test_window_floor_inside_a_page_block(self, window):
        # rows of 130-200 tokens under a band of 50-129: the floor of
        # the lowest real token falls inside block 0, 1 or 2, on a
        # block's edge (64) and inside a page (50, 70, 129)
        self._run(lens=(130, 200, 66, 141), q_lens=(1, 4, 2, 3), T=4,
                  window=window, H=8, KVH=2, MAXP=50)

    @pytest.mark.parametrize("group,window", [(1, 0), (4, 0), (8, 0),
                                              (4, 37)])
    def test_int8_pages_over_several_blocks(self, group, window):
        # every page of a block dequantises by its own scale
        self._run(lens=(150, 64, 65, 7), q_lens=(1, 5, 2, 1), T=5,
                  quant=True, window=window, H=2 * group, KVH=2,
                  MAXP=38)

    @pytest.mark.parametrize("window", [0, 70])
    def test_bf16_pages_and_queries(self, window):
        # the serving dtype: bf16 operands, float32 accumulation and
        # softmax state, the probabilities cast to the value dtype
        self._run(lens=(130, 19, 0, 70), q_lens=(1, 17, 0, 64), T=64,
                  window=window, H=8, KVH=2, MAXP=40, dtype=jnp.bfloat16,
                  tol=2e-2)

    @pytest.mark.parametrize("t,group,want", [
        (1, 4, 4), (64, 4, 64), (64, 1, 64), (16, 4, 64), (5, 8, 40),
        (24, 4, 48), (7, 4, 28), (40, 2, 16), (64, 8, 64)])
    def test_row_tile_divides_a_groups_rows(self, t, group, want):
        from paddle_tpu.ops.kernels.paged_attention import \
            _ragged_tiling

        ppb, blocks, tm, _ = _ragged_tiling(t, group, 24)
        assert (ppb, blocks, tm) == (16, 2, want)
        assert (t * group) % tm == 0


class TestFewRowForm:
    """ISSUE 35: a decode row at group 1 is attended in the pool's own
    (slot, kv head, dim) layout, no head-major copy and no matmul a head;
    at t = 1 every row, beside a prompt chunk the rows the kernel finds
    with ``q_len`` 1. Against the dense reference, as the tiles are."""
    _run = TestUnifiedKernelParity._run

    def _few(self, lens, q_lens, T, KVH=4, group=1, **kw):
        from paddle_tpu.ops.kernels.paged_attention import \
            _ragged_tiling

        assert _ragged_tiling(T, group, kw.get("MAXP") or 1)[3]
        return self._run(lens=lens, q_lens=q_lens, T=T, H=KVH * group,
                         KVH=KVH, **kw)

    @pytest.fixture
    def few_rows_4(self, monkeypatch):
        """The form at a group of up to 4, as the chip probe of PR 35 ran
        it (faster a step at 8 x 4 too, and off for what a program that
        holds both forms costs to trace: PERF.md section 6): one constant
        away, so it stays tested."""
        import importlib

        pa = importlib.import_module(
            "paddle_tpu.ops.kernels.paged_attention")
        monkeypatch.setattr(pa, "RAGGED_FEW_ROWS", 4)
        pa._jitted_ragged_call.cache_clear()
        yield
        pa._jitted_ragged_call.cache_clear()

    @pytest.mark.parametrize("window", [0, 70])
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                           (jnp.bfloat16, 2e-2)])
    @pytest.mark.parametrize("kvh", [4, 32])
    def test_decode_rows_at_group_1(self, kvh, dtype, tol, window):
        # rows that end on a block's edge (64 tokens), one token past
        # it, inside the third block and at their first token; a band of
        # 70 floors inside a page of the block before the row's last
        self._few(lens=(130, 65, 1, 64, 200), q_lens=(1,) * 5, T=1,
                  KVH=kvh, window=window, MAXP=50, dtype=dtype, tol=tol)

    @pytest.mark.parametrize("maxp", [1, 2, 24, 32])
    def test_table_widths_around_the_pages_a_step(self, maxp):
        full = maxp * PAGE
        # under four pages the landing buffers hold four (a turn of the
        # loop), the table's width aside
        self._few(lens=(full, max(full // 2, 1), 1), q_lens=(1, 1, 1),
                  T=1, MAXP=maxp)

    @pytest.mark.parametrize("tail", [1, PAGE - 1, PAGE + 1])
    def test_row_ends_inside_a_blocks_first_pages(self, tail):
        self._few(lens=(64 + tail, 64, 128 + tail), q_lens=(1, 1, 1),
                  T=1, MAXP=40)

    @pytest.mark.parametrize("window", [0, 50])
    def test_padding_rows_between_real_ones(self, window):
        # a padding row carries length 0; a row of length 0 that claims
        # a token, and a row that holds tokens and claims none
        out = self._few(lens=(70, 0, 9, 0, 33, 131),
                        q_lens=(1, 0, 1, 1, 0, 1), T=1, window=window,
                        MAXP=33)
        for r in (1, 3, 4):
            np.testing.assert_array_equal(out[r], 0.0)

    @pytest.mark.parametrize("group", [2, 4])
    @pytest.mark.parametrize("window", [0, 70])
    def test_decode_rows_of_a_small_group(self, few_rows_4, group, window):
        # a KV head's few query rows, one after the other against the
        # same pages: Mistral's 8 x 4 at the test's widths
        self._few(lens=(130, 65, 1, 64, 200), q_lens=(1,) * 5, T=1,
                  KVH=2, group=group, window=window, MAXP=50)

    @pytest.mark.parametrize("group", [1, 4])
    @pytest.mark.parametrize("window", [0, 70])
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                           (jnp.bfloat16, 2e-2)])
    def test_decode_rows_beside_a_chunk_row(self, few_rows_4, dtype, tol,
                                            window, group):
        # t = 64: both forms in one call, the chunk's row between decode
        # rows, so a few-row step issues a tiled step's copies and the
        # other way round
        out = self._few(lens=(130, 19, 70, 0, 200, 5),
                        q_lens=(1, 1, 64, 0, 1, 3), T=64, window=window,
                        group=group, MAXP=50, dtype=dtype, tol=tol)
        for r in (0, 1, 4):
            np.testing.assert_array_equal(out[r, :63], 0.0)
            assert np.abs(out[r, 63]).max() > 0
        np.testing.assert_array_equal(out[3], 0.0)

    @pytest.mark.parametrize("t,group,quant,want", [
        (1, 1, False, True),        # EvaByte's decode step: 32 x 1
        (512, 1, False, True),      # its prompt step: chosen a row
        (1, 4, False, False),       # Mistral's 8 x 4: the tiles (faster
        (64, 4, False, False),      # a step in PR 35's probe, off for
        (1, 2, False, False),       # what two forms cost to trace)
        (1, 8, False, False),
        (1, 1, True, False), (64, 1, True, False)])     # int8: never
    def test_the_form_follows_the_static_shapes(self, t, group, quant,
                                                want):
        from paddle_tpu.ops.kernels.paged_attention import \
            _ragged_tiling

        for max_pages in (1, 128, 256):
            assert _ragged_tiling(t, group, max_pages, quant)[3] == want

    @pytest.mark.parametrize("t", [1, 32])
    def test_t1_holds_no_head_major_scratch(self, t):
        # the kernel's operands: at t = 1 the landing buffers and the
        # few rows' state alone; beside a chunk both forms' state
        from paddle_tpu.ops.kernels.paged_attention import \
            _build_ragged_call

        b, kvh, d, npg, mp = 3, 4, 32, 64, 40
        S = jax.ShapeDtypeStruct
        run = _build_ragged_call(b, t, kvh, d, npg, PAGE, kvh, mp,
                                 d ** -0.5, 0, False, True, True)
        pool = S((npg, PAGE, kvh, d), jnp.float32)
        jaxpr = jax.make_jaxpr(run)(
            S((b, t, kvh, d), jnp.float32), pool, pool,
            S((b, mp), jnp.int32), S((b,), jnp.int32),
            S((b,), jnp.int32)).jaxpr
        (call,) = [e for e in _eqns(jaxpr)
                   if e.primitive.name == "pallas_call"]
        shapes = [tuple(v.aval.shape) for v in call.params["jaxpr"].invars]
        head_major = (kvh, 16 * PAGE, d)
        assert shapes.count((2, 16, PAGE, kvh, d)) == 2
        assert shapes.count((1, kvh, d)) == 3              # m, l, acc
        # a row's last token, a slot of a page a query row: in, and at
        # t = 1 out
        assert shapes.count((1, 1, kvh, d)) == 1 + (t == 1)
        assert shapes.count(head_major) == (0 if t == 1 else 2)


def _eqns(jaxpr):
    """Every equation of a program, nested programs opened; a pallas
    call is one equation (its kernel is not entered)."""
    for e in jaxpr.eqns:
        subs = [] if e.primitive.name == "pallas_call" else [
            v for v in e.params.values()
            if hasattr(v, "eqns") or hasattr(v, "jaxpr")]
        for sub in subs:
            yield from _eqns(getattr(sub, "jaxpr", sub))
        if not subs:
            yield e


class TestGridStructure:
    """ISSUE 31, so that the faults of the (row, head, page) grid cannot
    come back: at the shapes of ``mistral-7b-serve.decode-closed32`` (32
    rows, 4,096 pages of 16, a table 64 wide) the pool reaches the kernel
    as it is held, and a call is rows x page blocks grid steps."""
    B, T, H, KVH, D, NP, P, MP = 32, 1, 32, 8, 128, 4096, 16, 64
    POOL = NP * P * KVH * D

    def _ragged(self):
        from paddle_tpu.ops.kernels.paged_attention import \
            _build_ragged_call

        bf, i32 = jnp.bfloat16, jnp.int32
        S = jax.ShapeDtypeStruct
        run = _build_ragged_call(
            self.B, self.T, self.H, self.D, self.NP, self.P, self.KVH,
            self.MP, self.D ** -0.5, 4096, False, True, False)
        pool = S((self.NP, self.P, self.KVH, self.D), bf)
        return jax.make_jaxpr(run)(
            S((self.B, self.T, self.H, self.D), bf), pool, pool,
            S((self.B, self.MP), i32), S((self.B,), i32),
            S((self.B,), i32)).jaxpr

    def _layer(self):
        from paddle_tpu.ops.kernels.paged_attention import \
            _build_layer_call

        bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
        S = jax.ShapeDtypeStruct
        n, e, f = 32, 4096, 14336
        run = _build_layer_call(
            n, e, self.H, self.KVH, self.D, self.NP, self.P, self.B,
            self.T, self.MP, self.D ** -0.5, 4096, False, 1e-5, False)
        pool = S((self.NP, self.P, self.KVH, self.D), bf)
        return jax.make_jaxpr(run)(
            pool, pool, S((n, e), bf), S((e,), bf),
            S((e, self.H * self.D), bf),
            S((e, self.KVH * self.D), bf), S((e, self.KVH * self.D), bf),
            S((self.H * self.D, e), bf), S((e,), bf), S((e, f), bf),
            S((e, f), bf), S((f, e), bf), S((32768, self.D), f32),
            S((32768, self.D), f32), S((5, n), i32),
            S((self.B, self.T), i32), S((2, n), i32),
            S((self.B, self.MP + 2), i32)).jaxpr

    @pytest.mark.parametrize("which,allowed", [
        ("_ragged", set()), ("_layer", {"scatter"})])
    def test_no_pool_sized_copy_outside_the_kernel(self, which, allowed):
        # the old wrapper transposed both pools on every call (134 MB
        # each at these shapes); the layer program's page write is the
        # one operation that may return a pool
        made = {e.primitive.name for e in _eqns(getattr(self, which)())
                if e.primitive.name != "pallas_call"
                and any(v.aval.size == self.POOL for v in e.outvars)}
        assert made == allowed

    @pytest.mark.parametrize("which", ["_ragged", "_layer"])
    def test_grid_is_rows_by_page_blocks(self, which):
        from paddle_tpu.ops.kernels.paged_attention import (
            RAGGED_PAGES_PER_STEP, _ragged_grid_steps)

        (call,) = [e for e in _eqns(getattr(self, which)())
                   if e.primitive.name == "pallas_call"]
        grid = tuple(call.params["grid_mapping"].grid)
        assert grid == (self.B, -(-self.MP // RAGGED_PAGES_PER_STEP))
        assert grid[0] * grid[1] == _ragged_grid_steps(self.B, self.MP)
        # both pools go in whole, as they are held: the kernel copies
        # the pages it wants itself
        pools = [v for v in call.invars
                 if tuple(v.aval.shape) == (self.NP, self.P, self.KVH,
                                            self.D)]
        assert len(pools) == 2

    @pytest.mark.parametrize("rows,t,maxp", [(3, 1, 5), (2, 4, 24)])
    def test_span_reports_the_grid_steps(self, rows, t, maxp):
        from paddle_tpu.framework import telemetry
        from paddle_tpu.ops.kernels.paged_attention import \
            _ragged_grid_steps

        rng = np.random.RandomState(0)
        kp, vp, _, _ = _pages(rng, 8, PAGE, 2, 32)
        q = jnp.asarray(rng.randn(rows, t, 4, 32), jnp.float32)
        tbl = jnp.zeros((rows, maxp), jnp.int32)
        ln = jnp.full((rows,), 3, jnp.int32)
        paddle.set_flags({"telemetry": "trace"})
        try:
            paged_ragged_attention(q, kp, vp, tbl, ln,
                                   q_lens=jnp.ones((rows,), jnp.int32))
        finally:
            paddle.set_flags({"telemetry": "off"})
        span = [s for s in telemetry.peek_tracer().spans()
                if s.name == "kernel.ragged"][-1]
        assert span.attrs["grid_steps"] == _ragged_grid_steps(rows, maxp)
        assert span.attrs["grid_steps"] == rows * -(-maxp // min(16, maxp))


class TestDecodeWrapper:
    """``paged_attention(q (B, H, D), ...)`` is the T=1 shape of the
    ragged call: against the dense DECODE reference, over what the
    dedicated decode kernel's tests used to cover."""

    @pytest.mark.parametrize("quant", [False, True],
                             ids=["float", "int8"])
    @pytest.mark.parametrize("window", [0, 6])
    def test_t1_wrapper_matches_decode_reference(self, window, quant):
        rng = np.random.RandomState(0)
        B, H, KVH, D, NP, P, MAXP = 3, 4, 2, 32, 12, 8, 3
        q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
        kp, vp, ks, vs = _pages(rng, NP, P, KVH, D, quant)
        tbl = jnp.asarray(
            rng.permutation(NP)[:B * MAXP].reshape(B, MAXP), jnp.int32)
        lens = jnp.asarray([20, 9, 1], jnp.int32)
        out = paged_attention(q, kp, vp, tbl, lens, window=window,
                              k_scales=ks, v_scales=vs)
        ref = paged_attention_reference(q, kp, vp, tbl, lens,
                                        window=window, k_scales=ks,
                                        v_scales=vs)
        assert out.shape == (B, H, D)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-4,
                                   rtol=2e-4)
        # the same program as a packed batch of q_len-1 rows, bitwise
        rag = paged_ragged_attention(
            q[:, None], kp, vp, tbl, lens,
            q_lens=jnp.ones((B,), jnp.int32), window=window,
            k_scales=ks, v_scales=vs)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(rag[:, 0]))


def _filled_pool(kv=None, seed=2, lens=(6, 9, 1), **kw):
    rng = np.random.RandomState(seed)
    pool = PagedKVCacheManager(32, PAGE, 2, 8, dtype=jnp.float32,
                               kv_dtype=kv, **kw)
    for i, n in enumerate(lens):
        sid = f"s{i}"
        pool.alloc(sid)
        for _ in range(n):
            pool.append(sid, rng.randn(2, 8).astype("float32"),
                        rng.randn(2, 8).astype("float32"))
    return pool, rng


class TestPoolAttend:
    @pytest.mark.parametrize("kv", [None, "int8"],
                             ids=["float", "int8"])
    def test_attend_is_attend_ragged_at_t1(self, kv):
        pool, rng = _filled_pool(kv=kv)
        sids = ["s0", "s1", "s2"]
        q = jnp.asarray(rng.randn(3, 2, 8), jnp.float32)
        out = pool.attend(q, sids, window=5)
        rag = pool.attend_ragged(q[:, None], sids, [1, 1, 1], window=5)
        assert out.shape == [3, 2, 8]
        np.testing.assert_array_equal(out.numpy(), rag.numpy()[:, 0])
        # and the kernel's own (B, H, D) entry over the pool's arrays
        ks = pool.k_scales if pool.quantized else None
        vs = pool.v_scales if pool.quantized else None
        direct = paged_attention(
            q, pool.k_pages, pool.v_pages, pool.page_table(sids),
            pool.seq_lens(sids), window=5, k_scales=ks, v_scales=vs)
        np.testing.assert_array_equal(out.numpy(), np.asarray(direct))


def _latent_pool(**kw):
    pool = PagedKVCacheManager(8, 16, 1, 40, dtype=jnp.float32,
                               page_format="latent", **kw)
    pool.alloc("s0")
    return pool


def _call_attend_ragged(**kw):
    pool, rng = _filled_pool(**kw)
    q = jnp.asarray(rng.randn(4, 2, 2, 8), jnp.float32)
    return pool, lambda: pool.attend_ragged(
        q, ["s0", "s1"], [2, 1], rows_pad=4, max_pages=4)


def _layer_weights(rng, E, NH, KVH, HD, F=24):
    """(ln1, wq, wk, wv, wo, None, ln2, wg, wu, wd) of one tiny layer."""
    def w(*sh):
        return jnp.asarray(rng.randn(*sh) * 0.1, jnp.float32)

    def gain():
        return jnp.asarray(1 + 0.1 * rng.randn(E), jnp.float32)

    return (gain(), w(E, NH * HD), w(E, KVH * HD), w(E, KVH * HD),
            w(NH * HD, E), None, gain(), w(E, F), w(E, F), w(F, E))


def _call_layer_step(**kw):
    from paddle_tpu.ops.kernels.paged_attention import upload_plan
    from paddle_tpu.ops.kernels.rope import build_rope_cache

    pool, rng = _filled_pool(**kw)
    E, NH, KVH, HD, n_pad = 16, 2, 2, 8, 8
    gm = np.zeros((2, 4), np.int32)
    gm[0, 2:] = [0, 1]
    gm[1, 3:] = [2]
    pos = np.zeros(n_pad, np.int32)
    pos[:3] = [6, 7, 9]
    tok = [[0] * n_pad, pos, [0, 0, 1] + [0] * 5, [2, 3, 3] + [0] * 5,
           [0, 1, 2] + [n_pad] * 5]   # id, position, mr, mc, mflat
    plan = upload_plan(tok, gm)
    x = jnp.asarray(rng.randn(n_pad, E), jnp.float32)
    weights = _layer_weights(rng, E, NH, KVH, HD)

    def call():
        tables = pool.book_step(["s0", "s1"], [2, 1], 2, 4, n_pad)
        return pool.layer_step(x, weights, build_rope_cache(64, HD),
                               plan, tables, 1e-6)

    return pool, call


def _call_latent_step(**kw):
    pool = _latent_pool(**kw)
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(8, 2, 40), jnp.float32)
    toks = jnp.asarray(rng.randn(8, 40), jnp.float32)
    gm = np.zeros((1, 8), np.int32)
    gm[0, 3:] = np.arange(5)
    return pool, lambda: pool.latent_ragged_step(
        q, toks, ["s0"], [5], jnp.asarray(gm), 32, rows_pad=1,
        max_pages=2)


_TABLE_CALLERS = {"attend_ragged": _call_attend_ragged,
                  "layer_step": _call_layer_step,
                  "latent_ragged_step": _call_latent_step}


class TestStepTables:
    """The pool builds what a step's kernel reads beside the pages —
    page table, lens, q_lens, the padded slot plan — in ONE function,
    under the one ``pool.table`` span site."""

    def test_padding_rows_carry_length_0_and_q_len_0(self):
        pool, _ = _filled_pool()
        tbl, lens, ql = pool._step_tables(["s0", "s1"], [2, 1], 4, 4)
        assert tbl.shape == (4, 4) and tbl.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(lens), [6, 9, 0, 0])
        np.testing.assert_array_equal(np.asarray(ql), [2, 1, 0, 0])
        assert ql.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(tbl)[2:], 0)
        assert list(np.asarray(tbl)[1, :3]) == pool.seq_pages("s1")

    @pytest.mark.parametrize("max_pages,want", [(None, 3), (2, 3),
                                                (8, 8)])
    def test_width_is_max_of_max_pages_and_longest_chain(self, max_pages,
                                                         want):
        pool, _ = _filled_pool()          # s1: 9 tokens = 3 pages of 4
        tbl, _, _ = pool._step_tables(["s0", "s1"], [1, 1], None,
                                      max_pages)
        assert tbl.shape == (2, want)

    def test_rows_pad_below_the_row_count_is_raised_to_it(self):
        pool, _ = _filled_pool()
        tbl, lens, ql = pool._step_tables(["s0", "s1", "s2"], [1, 1, 1],
                                          2, 4)
        assert tbl.shape[0] == lens.shape[0] == ql.shape[0] == 3

    def test_padded_slots_carry_page_id_num_pages(self):
        pool, _ = _filled_pool()
        out = pool._step_tables(["s0"], [2], 1, 4,
                                slots=([5, 5], [2, 3]), n_pad=8)
        assert len(out) == 5
        pg, of = np.asarray(out[3]), np.asarray(out[4])
        np.testing.assert_array_equal(pg, [5, 5] + [pool.num_pages] * 6)
        np.testing.assert_array_equal(of, [2, 3] + [0] * 6)
        assert pg.dtype == of.dtype == np.int32
        # without a plan there is nothing to pad
        assert len(pool._step_tables(["s0"], [2], 1, 4)) == 3

    @pytest.mark.parametrize("caller", sorted(_TABLE_CALLERS))
    def test_caller_emits_one_table_span_with_rows_and_bytes(self, caller):
        pool, call = _TABLE_CALLERS[caller]()
        spans = _spans_of(call)
        table = [s for s in spans if s.name == "pool.table"]
        assert len(table) == 1
        rows = 1 if caller == "latent_ragged_step" else 2
        assert table[0].attrs["rows"] == rows
        assert table[0].attrs["bytes"] > 0
        (step,) = [s for s in spans if s.name == "pool.fused_step"]
        assert step.attrs["op"] == caller

    @pytest.mark.parametrize("caller", sorted(_TABLE_CALLERS))
    def test_sanitizer_checks_the_table_once_a_call(self, caller):
        pool, call = _TABLE_CALLERS[caller](sanitizer="strict")
        before = pool.sanitizer_stats["by_op"].get("page-table", 0)
        call()
        stats = pool.sanitizer_stats
        assert stats["by_op"]["page-table"] == before + 1
        assert stats["violations"] == 0

    def test_one_builder_one_span_site(self):
        import inspect

        from paddle_tpu.incubate.nn import paged_cache

        src = inspect.getsource(paged_cache)
        assert src.count('span("pool.table")') == 1
        # the layer program's tables come from book_step (once a step,
        # shared by the layers' pools); layer_step itself builds none
        for caller in ("attend_ragged", "book_step",
                       "latent_ragged_step"):
            body = inspect.getsource(
                getattr(PagedKVCacheManager, caller))
            assert body.count("self._step_tables(") == 1, caller
            assert "_padded_kernel_inputs" not in body, caller
        body = inspect.getsource(PagedKVCacheManager.layer_step)
        assert "_step_tables" not in body and "_upload" not in body


class TestPoolAttendRagged:
    def test_warm_dispatch_reuse_across_pools(self):
        # satellite: the unified kernel keys ONE shape-keyed LRU —
        # a second pool instance at the same shapes reuses the
        # compiled entry instead of re-tracing
        pool_a, rng = _filled_pool(seed=3)
        q = jnp.asarray(rng.randn(4, 2, 2, 8), jnp.float32)
        pool_a.attend_ragged(q, ["s0", "s1"], [2, 1], rows_pad=4,
                             max_pages=4)
        info0 = _jitted_ragged_call.cache_info()
        pool_b, _ = _filled_pool(seed=4)
        pool_b.attend_ragged(q, ["s0", "s1"], [2, 1], rows_pad=4,
                             max_pages=4)
        info1 = _jitted_ragged_call.cache_info()
        assert info1.currsize == info0.currsize
        assert info1.hits == info0.hits + 1

    def test_single_cache_serves_decode_and_prefill_kinds(self):
        # no per-row-kind cache split: a decode-shaped (T=1) call and
        # a prefill-shaped call both land in _jitted_ragged_call
        pool, rng = _filled_pool(seed=5)
        size0 = _jitted_ragged_call.cache_info().currsize
        q1 = jnp.asarray(rng.randn(2, 1, 2, 8), jnp.float32)
        pool.attend_ragged(q1, ["s0", "s1"], [1, 1], max_pages=4)
        qT = jnp.asarray(rng.randn(2, 4, 2, 8), jnp.float32)
        pool.attend_ragged(qT, ["s0", "s1"], [3, 4], max_pages=4)
        assert _jitted_ragged_call.cache_info().currsize >= size0 + 1


def _np_plan(counts, n_pad, t_pad, b_pad):
    """(gm, mr, mc, mflat) of packed rows, padded to n_pad (numpy)."""
    from paddle_tpu.inference.paged_common import right_align_plan_np
    from paddle_tpu.ops.kernels.paged_attention import pad_plan_np

    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    gm, mr, mc, mflat = right_align_plan_np(
        range(len(counts)), starts, counts, t_pad, b_pad)
    return (gm, pad_plan_np(mr, n_pad, 0), pad_plan_np(mc, n_pad, 0),
            pad_plan_np(mflat, n_pad, n_pad))


class TestLayerStep:
    """The layer program: norm, qkv + RoPE + page scatter, the ragged
    kernel, o_proj, residual, norm, MLP, residual as ONE dispatch over
    the pool's pages — numerically the op-by-op layer over the unfused
    pool path, leaving identical page state behind; the layer's weights
    are operands."""

    E, NH, KVH, HD = 16, 2, 2, 8

    def _setup(self, seed=7):
        from paddle_tpu.ops.kernels.rope import build_rope_cache

        rng = np.random.RandomState(seed)
        pool_f = PagedKVCacheManager(16, PAGE, self.KVH, self.HD,
                                     dtype=jnp.float32)
        pool_u = PagedKVCacheManager(16, PAGE, self.KVH, self.HD,
                                     dtype=jnp.float32)
        lens = (5, 1)
        for pool in (pool_f, pool_u):
            for i, n in enumerate(lens):
                sid = f"s{i}"
                pool.alloc(sid)
                for _ in range(n):
                    rs = np.random.RandomState(100 + i)
                    pool.append(
                        sid, rs.randn(self.KVH, self.HD).astype("float32"),
                        rs.randn(self.KVH, self.HD).astype("float32"))
        weights = _layer_weights(rng, self.E, self.NH, self.KVH, self.HD)
        return (rng, pool_f, pool_u, lens, weights,
                build_rope_cache(64, self.HD))

    def _step(self, pool, x, weights, rope, counts, positions,
              n_pad=8, t_pad=4, b_pad=2):
        from paddle_tpu.ops.kernels.paged_attention import upload_plan

        pos = np.zeros(n_pad, np.int32)
        pos[:sum(counts)] = positions
        gm, mr, mc, mflat = _np_plan(counts, n_pad, t_pad, b_pad)
        plan = upload_plan([0 * pos, pos, mr, mc, mflat], gm)
        tables = pool.book_step(["s0", "s1"], counts, b_pad, 4, n_pad)
        return pool.layer_step(x, weights, rope, plan, tables, 1e-6)

    def test_program_matches_the_ops_and_pages_identical(self):
        from paddle_tpu.framework.core import Tensor
        from paddle_tpu.ops.kernels.rms_norm import rms_norm
        from paddle_tpu.ops.kernels.rope import apply_rotary_emb

        rng, pool_f, pool_u, lens, weights, (cos, sin) = self._setup()
        ln1, wq, wk, wv, wo, _, ln2, wg, wu, wd = weights
        E, NH, KVH, HD = self.E, self.NH, self.KVH, self.HD
        sids = ["s0", "s1"]
        counts = [3, 1]            # one prefill chunk + one decode row
        n_real, n_pad, t_pad, b_pad = 4, 8, 4, 2
        x = jnp.asarray(rng.randn(n_pad, E), jnp.float32)
        pos = np.zeros(n_pad, np.int32)
        pos[:4] = [5, 6, 7, 1]
        y = self._step(pool_f, x, weights, (cos, sin), counts,
                       [5, 6, 7, 1])

        # the same layer op by op over the unfused pool path
        gm, mr, mc, mflat = _np_plan(counts, n_real, t_pad, b_pad)
        h = rms_norm(x, ln1, 1e-6)
        xq = (h @ wq).reshape(1, n_pad, NH, HD)
        xk = (h @ wk).reshape(1, n_pad, KVH, HD)
        vh = (h @ wv).reshape(n_pad, KVH, HD)
        qh = apply_rotary_emb(xq, cos, sin,
                              position_ids=jnp.asarray(pos))[0]
        kh = apply_rotary_emb(xk, cos, sin,
                              position_ids=jnp.asarray(pos))[0]
        pool_u.append_ragged(sids, counts, kh[:n_real], vh[:n_real])
        out = pool_u.attend_ragged(
            Tensor(qh[jnp.asarray(gm)]), sids, counts,
            rows_pad=b_pad, max_pages=4)
        attn = jnp.zeros((n_pad, NH, HD), jnp.float32)
        attn = attn.at[mflat].set(out._data[mr, mc])
        x1 = x + attn.reshape(n_pad, NH * HD) @ wo
        h2 = rms_norm(x1, ln2, 1e-6)
        y_ref = x1 + (jax.nn.silu(h2 @ wg) * (h2 @ wu)) @ wd

        # padding rows of the packed axis carry no attention output but
        # still run the dense work: the whole (n_pad, E) block agrees
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=2e-6)
        # page payloads: the program computes K/V in-graph, so XLA's
        # fusion may differ from the eager path by float ulps —
        # allclose, while the BOOKKEEPING (tables, lens) is exact
        np.testing.assert_allclose(np.asarray(pool_f.k_pages),
                                   np.asarray(pool_u.k_pages),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(pool_f.v_pages),
                                   np.asarray(pool_u.v_pages),
                                   atol=1e-6)
        for s in sids:
            assert pool_f.seq_pages(s) == pool_u.seq_pages(s)
        assert pool_f.seq_len("s0") == lens[0] + 3
        assert pool_f.seq_len("s1") == lens[1] + 1

    def test_one_program_for_every_layer_and_real_token_count(self):
        # the dispatch cache keys only BUCKETED shapes and no weight: a
        # second step with another layer's weights and another real
        # token count but the same padded config reuses the compiled
        # program instead of re-tracing (the padded plans'
        # out-of-bounds entries drop)
        from paddle_tpu.ops.kernels.paged_attention import \
            _jitted_layer_step

        rng, pool, _, _, weights, rope = self._setup(seed=11)
        other = _layer_weights(rng, self.E, self.NH, self.KVH, self.HD)
        x = jnp.asarray(rng.randn(8, self.E), jnp.float32)
        y0 = self._step(pool, x, weights, rope, [3, 1], [5, 6, 7, 1])
        info0 = _jitted_layer_step.cache_info()
        out = []
        built = _builds_during(lambda: out.append(self._step(
            pool, x, other, rope, [2, 1], [8, 9, 2])))
        info1 = _jitted_layer_step.cache_info()
        assert info1.currsize == info0.currsize
        assert info1.hits == info0.hits + 1
        assert built == []          # neither traced, lowered nor built
        assert not np.allclose(np.asarray(y0), np.asarray(out[0]))

    def test_int8_pool_refuses_the_program(self):
        pool = PagedKVCacheManager(8, PAGE, 2, 8, dtype=jnp.float32,
                                   kv_dtype="int8")
        pool.alloc("s")
        with pytest.raises(ValueError, match="int8"):
            pool.layer_step(jnp.zeros((4, 16)), (None,) * 10,
                            (None, None), (None,) * 2, (None,) * 2, 1e-6)

    def test_plan_of_another_length_is_refused_before_the_dispatch(self):
        rng, pool, _, _, weights, rope = self._setup(seed=13)
        x = jnp.asarray(rng.randn(16, self.E), jnp.float32)
        pages = pool.k_pages
        with pytest.raises(ValueError, match="padded to the packed"):
            self._step(pool, x, weights, rope, [3, 1], [5, 6, 7, 1])
        assert pool.k_pages is pages


def _spans_of(call):
    """``call()`` with spans live, in a ring of its own; the spans it
    leaves."""
    from paddle_tpu.framework import telemetry

    telemetry.reset()
    paddle.set_flags({"telemetry": "trace"})
    try:
        call()
        return telemetry.peek_tracer().spans()
    finally:
        paddle.set_flags({"telemetry": "off"})
        telemetry.reset()


def _builds_during(call):
    """The ``xla.trace`` / ``xla.lower`` / ``xla.build`` ranges that
    ``call()`` leaves in the span ring: [(name, fun)]."""
    return [(s.name, s.attrs.get("fun")) for s in _spans_of(call)
            if s.name.startswith("xla.")]


# ---------------------------------------------------------------------------
# end-to-end: both bodies of the chunked step against the token-per-step
# scheduler


def _tiny_cfg(**kw):
    kw.setdefault("hidden_size", 64)
    kw.setdefault("intermediate_size", 128)
    kw.setdefault("num_hidden_layers", 1)
    kw.setdefault("num_attention_heads", 2)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("max_position_embeddings", 128)
    return llama_tiny(**kw)


def _fresh_model(seed=17, **kw):
    paddle.seed(seed)
    return LlamaForCausalLM(_tiny_cfg(**kw))


@pytest.fixture(scope="module")
def model():
    return _fresh_model()


_RNG = np.random.RandomState(0)
PROMPTS = {
    "a": _RNG.randint(1, 500, 11).tolist(),
    "b": _RNG.randint(1, 500, 3).tolist(),
    "c": _RNG.randint(1, 500, 7).tolist(),
}
N_NEW = {"a": 4, "b": 5, "c": 3}


def _serve(model, kv=None, prefix=False, budget=8, chunked=True,
           weight_dtype=None, waves=None):
    """Serve PROMPTS (twice over a prefix cache) and return (generated
    ids, scheduler, adapter). ``chunked=False`` is the token-per-step
    scheduler over ``decode_token`` — the oracle of both chunked
    bodies. ``weight_dtype`` quantizes ``model`` in place: hand it a
    :func:`_fresh_model`."""
    adapter = PagedLlamaAdapter(model, num_pages=96, page_size=PAGE,
                                max_length=128, kv_cache_dtype=kv,
                                weight_dtype=weight_dtype)
    kw = dict(prefill_chunk_tokens=budget) if chunked else {}
    sched = BatchScheduler(adapter, max_batch_size=4,
                           prefix_cache=prefix, chunked_prefill=chunked,
                           **kw)
    out = {}
    for wave in range(waves or (2 if prefix else 1)):
        for rid, p in PROMPTS.items():
            sched.submit(Request(f"{rid}w{wave}", list(p),
                                 max_new_tokens=N_NEW[rid]))
        done = sched.run_until_complete()
        for k, v in done.items():
            out[k] = v.generated_ids
    return out, sched, adapter


def _kinds(adapter):
    return {k for k, *_ in adapter._kernel_shapes}


class TestFusionChoice:
    """Which body ``prefill_chunk`` runs follows from what the adapter
    can observe of its pages, projections, norms and feed-forward,
    nothing else: a dense float model runs one program a layer, anything
    else the same plan op by op."""

    @pytest.mark.parametrize("case,want", [
        ("float", "ragged_fused"),
        ("qkv_bias", "ragged_fused"),
        ("int8_kv", "ragged"),
        ("int8_weights", "ragged"),
        ("partial_qkv_bias", "ragged"),
        ("routed_experts", "ragged"),
        ("mlp_bias", "ragged"),
        ("o_proj_bias", "ragged"),
        ("other_norm", "ragged"),
    ])
    def test_body_follows_eligibility(self, case, want):
        from paddle_tpu.nn import LayerNorm

        kw = {}
        if case in ("partial_qkv_bias", "qkv_bias"):
            kw["attention_bias"] = True
        if case == "routed_experts":
            kw.update(num_local_experts=4, num_experts_per_tok=2)
        m = _fresh_model(**kw)
        layer = m.model.layers[0]
        if case == "partial_qkv_bias":
            layer.self_attn.k_proj.bias = None
        for name, proj in (("mlp_bias", layer.mlp.gate_proj
                            if case == "mlp_bias" else None),
                           ("o_proj_bias", layer.self_attn.o_proj)):
            if case == name:
                proj.bias = proj.create_parameter(
                    [proj.weight.shape[1]], is_bias=True)
        if case == "other_norm":
            layer.post_attention_layernorm = LayerNorm(
                m.config.hidden_size)
        ad = PagedLlamaAdapter(
            m, num_pages=16, page_size=PAGE, max_length=64,
            kv_cache_dtype="int8" if case == "int8_kv" else None,
            weight_dtype="int8" if case == "int8_weights" else None)
        program = want == "ragged_fused"
        assert ad._fusion_eligible() == program
        for sid in "ab":
            ad.alloc(sid)
        out = []
        spans = _spans_of(lambda: out.append(ad.prefill_chunk(
            [[5, 6, 7], [9]], ["a", "b"], [0, 0], pad_to=8)))
        logits = out[0]
        assert logits.shape == [2, m.config.vocab_size]
        assert np.isfinite(logits.numpy()).all()
        assert _kinds(ad) == {want}
        assert ad.caches[0].seq_len("a") == 3
        # the counter: every layer span says how it ran, and the share
        # engaged is layer_programs / attend_calls
        layers = [s for s in spans if s.name == "model.layer"]
        assert [s.attrs["program"] for s in layers] == [int(program)]
        assert ad.chunk_stats["attend_calls"] == 1
        assert ad.chunk_stats["layer_programs"] == int(program)
        eager_only = {s.name for s in spans} & {"model.norm", "model.mlp"}
        assert eager_only == (set() if program
                              else {"model.norm", "model.mlp"})


    @pytest.mark.parametrize("case,kw,adkw,want", [
        ("group_1", {}, {}, [0, 2, 1]),
        ("group_2", {"num_attention_heads": 4}, {}, [0, 0, 0]),
        ("int8_pages", {}, {"kv_cache_dtype": "int8"}, [0, 0, 0])],
        ids=["group_1", "group_2", "int8_pages"])
    def test_few_row_rows_ride_the_span_and_the_stats(self, case, kw,
                                                      adkw, want):
        # how often the kernel's few-row form engages, from the step's
        # own counts and the static shapes: a chunk step, a decode step,
        # a chunk beside a decode row; both bodies say it
        m = _fresh_model(num_hidden_layers=2, **kw)
        ad = PagedLlamaAdapter(m, num_pages=16, page_size=PAGE,
                               max_length=64, **adkw)
        for sid in "ab":
            ad.alloc(sid)
        feeds = [([[5, 6, 7], [9, 3]], 8), ([[4], [8]], 2),
                 ([[4, 5], [8]], 4)]

        def run():
            for toks, pad_to in feeds:
                ad.prefill_chunk(
                    toks, ["a", "b"],
                    [ad.caches[0].seq_len(s) for s in "ab"], pad_to=pad_to)

        calls = [s.attrs["few_row_rows"] for s in _spans_of(run)
                 if s.name == "kernel.ragged"]
        assert calls == [n for n in want for _ in range(2)]  # two layers
        assert ad.chunk_stats["few_row_rows"] == 2 * sum(want)
        assert ad.chunk_stats["attend_calls"] == 2 * len(feeds)


def _three(model, **kw):
    """Three adapters over one model: the programmed body, the eager
    body of the same plan, and one for the ``decode_token`` oracle."""
    ads = [PagedLlamaAdapter(model, num_pages=48, page_size=PAGE,
                             max_length=128, **kw) for _ in range(3)]
    assert ads[0]._fusion_eligible()
    ads[1]._fused_ok = False              # the same plan, op by op
    return ads


class TestProgrammedStep:
    """The programmed body of ``prefill_chunk`` (one compiled program a
    layer, embed and head programs, index operands uploaded once)
    against the eager body and against ``decode_token``."""

    # (case, model options): mixed decode and chunk rows throughout
    CASES = [("plain", {}),
             ("window_inside_context", {"sliding_window": 6}),
             ("qkv_bias", {"attention_bias": True}),
             ("tied_head", {"tie_word_embeddings": True})]

    @pytest.mark.parametrize("case,kw", CASES,
                             ids=[c for c, _ in CASES])
    def test_logits_match_eager_body_and_oracle(self, case, kw):
        m = _fresh_model(seed=31, num_hidden_layers=2, **kw)
        prog, eager, oracle = _three(m)
        rng = np.random.RandomState(5)
        sids = ["a", "b", "c"]
        feeds = [  # (tokens a row, pad_to): prompt chunks, then mixed
            ([9, 2, 5], 16), ([3, 1, 4], 8), ([1, 1, 6], 8),
            ([1, 1, 1], 4)]
        for ad in (prog, eager, oracle):
            for sid in sids:
                ad.alloc(sid)
        for counts, pad_to in feeds:
            toks = [rng.randint(1, 500, c).tolist() for c in counts]
            starts = [prog.caches[0].seq_len(s) for s in sids]
            got = prog.prefill_chunk(toks, sids, starts,
                                     pad_to=pad_to).numpy()
            want = eager.prefill_chunk(toks, sids, starts,
                                       pad_to=pad_to).numpy()
            np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
            # the oracle: the same tokens one at a time
            for j in range(max(counts)):
                rows = [i for i, c in enumerate(counts) if j < c]
                lg = oracle.decode_token(
                    [toks[i][j] for i in rows],
                    [sids[i] for i in rows]).numpy()
                for r, i in enumerate(rows):
                    if j == counts[i] - 1:
                        np.testing.assert_allclose(
                            got[i], lg[r], atol=5e-5, rtol=1e-4)
        assert prog.chunk_stats["layer_programs"] == \
            prog.chunk_stats["attend_calls"] == 2 * len(feeds)
        assert eager.chunk_stats["layer_programs"] == 0
        # the pages both bodies leave behind agree, layer by layer
        for cp, ce in zip(prog.caches, eager.caches):
            np.testing.assert_allclose(
                np.asarray(cp.k_pages), np.asarray(ce.k_pages),
                atol=1e-5)
            assert [cp.seq_pages(s) for s in sids] == \
                [ce.seq_pages(s) for s in sids]

    def test_verify_rows_get_every_position(self):
        m = _fresh_model(seed=37, num_hidden_layers=2)
        prog, eager, _ = _three(m)
        sids = ["a", "b", "c"]
        toks = [[5, 6, 7, 8], [9], [3, 4, 2]]
        outs = []
        for ad in (prog, eager):
            for sid in sids:
                ad.alloc(sid)
            ad.prefill_chunk([[1, 2], [3], [4, 5, 6]], sids, pad_to=8)
            last, full = ad.prefill_chunk(toks, sids, pad_to=8,
                                          logits_rows=[2, 0])
            assert last.shape == [3, 512] and full.shape == [7, 512]
            outs.append((last.numpy(), full.numpy()))
        for got, want in zip(*outs):
            np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
        last, full = outs[0]
        # rows in list order: row 2's three positions, then row 0's four
        np.testing.assert_allclose(full[2], last[2], atol=1e-6)
        np.testing.assert_allclose(full[6], last[0], atol=1e-6)

    def test_tables_built_and_uploaded_once_a_step(self, monkeypatch):
        from paddle_tpu.incubate.nn import paged_cache
        from paddle_tpu.inference import paged_llama

        layers = 3
        m = _fresh_model(seed=41, num_hidden_layers=layers)
        ad = PagedLlamaAdapter(m, num_pages=48, page_size=PAGE,
                               max_length=128)
        for sid in "ab":
            ad.alloc(sid)
        ad.prefill_chunk([[5, 6, 7], [9]], ["a", "b"], pad_to=8)
        uploads = []
        for mod in (paged_cache, paged_llama):
            real = mod._upload

            def counted(*arrays, _real=real, _mod=mod.__name__):
                uploads.append((_mod.rsplit(".", 1)[1],
                                [np.shape(a) for a in arrays]))
                return _real(*arrays)

            monkeypatch.setattr(mod, "_upload", counted)
        spans = _spans_of(lambda: ad.prefill_chunk(
            [[1, 2], [3]], ["a", "b"], pad_to=4))
        # one upload of the adapter's operands (a token's id, position,
        # mr, mc, mflat as one array; gm; the last rows) and one of the
        # pool's (table | lens | q_lens as one array; the padded slot
        # plan): each index operand crosses once
        assert uploads == [("paged_llama", [(5, 4), (2, 2), (2,)]),
                           ("paged_cache", [(2, 4), (2, 4)])]
        names = [s.name for s in spans]
        assert names.count("pool.table") == 1
        assert names.count("pool.book") == layers
        assert names.count("model.layer") == layers
        assert names.count("kernel.ragged") == layers
        assert [s.attrs["op"] for s in spans
                if s.name == "pool.fused_step"] == ["layer_step"] * layers
        # every pool booked before the first layer ran
        order = [n for n in names if n in ("pool.book", "model.layer")]
        assert order == ["pool.book"] * layers + ["model.layer"] * layers
        # every layer's pool holds the same tables and lengths
        first = ad.caches[0]
        for pool in ad.caches[1:]:
            for sid in "ab":
                assert pool.seq_pages(sid) == first.seq_pages(sid)
                assert pool.seq_len(sid) == first.seq_len(sid)
        assert first.seq_len("a") == 5 and first.seq_len("b") == 2

    def test_a_pool_that_differs_builds_its_own_tables(self):
        # pools of one adapter are driven in lockstep; one that is not
        # (another page chain for the same rows) is not handed the
        # first pool's table
        m = _fresh_model(seed=43, num_hidden_layers=2)
        ad = PagedLlamaAdapter(m, num_pages=48, page_size=PAGE,
                               max_length=128)
        ref = PagedLlamaAdapter(m, num_pages=48, page_size=PAGE,
                                max_length=128)
        ad.caches[1].alloc("x")
        ad.caches[1].append("x", np.zeros((2, 32), "float32"),
                            np.zeros((2, 32), "float32"))
        for a in (ad, ref):
            for sid in "ab":
                a.alloc(sid)
        spans = _spans_of(lambda: ad.prefill_chunk(
            [[5, 6, 7], [9]], ["a", "b"], pad_to=8))
        assert [s.name for s in spans].count("pool.table") == 2
        assert ad.caches[1].seq_pages("a") != ad.caches[0].seq_pages("a")
        got = ad.prefill_chunk([[1], [2]], ["a", "b"], pad_to=2).numpy()
        ref.prefill_chunk([[5, 6, 7], [9]], ["a", "b"], pad_to=8)
        want = ref.prefill_chunk([[1], [2]], ["a", "b"], pad_to=2).numpy()
        np.testing.assert_allclose(got, want, atol=1e-6)

    def test_sanitizer_checks_the_shared_table_in_every_pool(self):
        m = _fresh_model(seed=47, num_hidden_layers=3)
        ad = PagedLlamaAdapter(m, num_pages=48, page_size=PAGE,
                               max_length=128, sanitizer="strict")
        for sid in "ab":
            ad.alloc(sid)
        before = [c.sanitizer_stats["by_op"].get("page-table", 0)
                  for c in ad.caches]
        ad.prefill_chunk([[5, 6, 7], [9]], ["a", "b"], pad_to=8)
        for c, n in zip(ad.caches, before):
            assert c.sanitizer_stats["by_op"]["page-table"] == n + 1
            assert c.sanitizer_stats["violations"] == 0

    def test_exhausted_pool_raises_before_any_page_array_is_replaced(
            self):
        m = _fresh_model(seed=53, num_hidden_layers=3)
        ad = PagedLlamaAdapter(m, num_pages=4, page_size=PAGE,
                               max_length=64)
        for sid in "ab":
            ad.alloc(sid)
        ad.prefill_chunk([[5, 6, 7], [9]], ["a", "b"], pad_to=4)
        # the LAST layer's pool has no page left for the rows' growth
        last = ad.caches[-1]
        last.alloc("hog")
        for _ in range(2 * PAGE):
            last.append("hog", np.zeros((2, 32), "float32"),
                        np.zeros((2, 32), "float32"))
        assert last.num_free_pages == 0
        arrays = [(c.k_pages, c.v_pages) for c in ad.caches]
        with pytest.raises(RuntimeError, match="exhausted"):
            ad.prefill_chunk([[1, 2], [3, 4, 5, 6]], ["a", "b"],
                             pad_to=8)
        assert all(c.k_pages is k and c.v_pages is v
                   for c, (k, v) in zip(ad.caches, arrays))
        assert last.seq_len("a") == 3 and last.seq_len("b") == 1

    def test_one_program_a_shape_serves_every_layer(self):
        layers = 3
        m = _fresh_model(seed=59, num_hidden_layers=layers,
                         hidden_size=32, intermediate_size=96)
        ad = PagedLlamaAdapter(m, num_pages=48, page_size=PAGE,
                               max_length=128)
        for sid in "ab":
            ad.alloc(sid)

        def step(a, b):
            return lambda: ad.prefill_chunk([a, b], ["a", "b"], pad_to=8)

        step([5, 6, 7], [9])()
        # a new shape (rows of 4 tokens, tables 2 pages wide)
        first = _builds_during(step([1, 2, 3], [4]))
        traced = [fun for name, fun in first if name == "xla.trace"]
        assert traced.count("ragged_layer_step") == 1     # not `layers`
        # another step of the same buckets, other real token counts:
        # nothing is traced, lowered or built, in any layer
        assert _builds_during(step([1, 2], [3, 4, 5])) == []
        assert ad.chunk_stats["layer_programs"] == 3 * layers


class TestEndToEndGreedyIdentity:
    """The chunked scheduler's greedy outputs must be token-identical
    to the token-per-step scheduler's, through the fused body (float
    pages, plain projections) and through the unfused one (int8 pages,
    or int8 weights over float pages)."""

    @pytest.mark.parametrize("kv,prefix", [
        (None, False),
        ("int8", False),
        pytest.param(None, True, marks=_slow),
        pytest.param("int8", True, marks=_slow),
    ])
    def test_modes_agree(self, model, kv, prefix):
        waves = 2 if prefix else 1
        base, _, _ = _serve(model, kv=kv, chunked=False, waves=waves)
        got, _, ad = _serve(model, kv=kv, prefix=prefix)
        assert got == base, (kv, prefix)
        # ONE attend program per packed config
        assert _kinds(ad) == ({"ragged"} if kv else {"ragged_fused"})
        if kv is None:
            # the unfused body over float pages: int8 projections
            base_w, _, _ = _serve(_fresh_model(), weight_dtype="int8",
                                  chunked=False, waves=waves)
            got_w, _, ad_w = _serve(_fresh_model(), weight_dtype="int8",
                                    prefix=prefix)
            assert got_w == base_w, prefix
            assert _kinds(ad_w) == {"ragged"}

    def test_fuses_fp_and_declines_int8(self, model):
        _, _, ad_fp = _serve(model)
        assert _kinds(ad_fp) == {"ragged_fused"}
        _, _, ad_i8 = _serve(model, kv="int8")
        assert _kinds(ad_i8) == {"ragged"}

    def test_attend_program_count_bounded_by_buckets(self, model):
        got, sched, adapter = _serve(model)
        assert got == _serve(model, chunked=False)[0]
        # satellite acceptance: one attend program per packed config
        # keeps the compiled-program count within the bucket ladder
        assert adapter.compile_count <= len(sched.serving_buckets)
        assert adapter.attend_program_count <= \
            len(sched.serving_buckets)
        # one attend kernel KIND per dispatch bucket, never a pair
        assert all(len(kinds) == 1 for kinds in
                   adapter.attend_kinds_by_bucket.values()), \
            adapter.attend_kinds_by_bucket

    def test_fused_program_count_includes_packed_bucket(self, model):
        # two packed buckets sharing (b_pad, t_pad, mp_pad) compile
        # two REAL layer programs — the dense work is bucket-shaped —
        # and the accounting must not collapse them
        # (review find: the cfg keys n_pad, the shape tuple must too)
        from paddle_tpu.ops.kernels.paged_attention import (
            _jitted_layer_step,
        )

        ad = PagedLlamaAdapter(model, num_pages=32, page_size=16,
                               max_length=128)
        for s in "abcd":
            ad.alloc(s)
        rng = np.random.RandomState(3)

        def toks(n):
            return rng.randint(1, 400, n).tolist()

        miss0 = _jitted_layer_step.cache_info().misses
        ad.prefill_chunk([toks(5), toks(1), toks(1), toks(1)],
                         list("abcd"), [0, 0, 0, 0], pad_to=8)
        ad.prefill_chunk([toks(5), toks(2), toks(2), toks(2)],
                         list("abcd"), [5, 1, 1, 1], pad_to=16)
        compiled = _jitted_layer_step.cache_info().misses - miss0
        assert ad.attend_program_count == compiled == 2, (
            ad.attend_program_count, compiled, ad._kernel_shapes)
        for s in "abcd":
            ad.free(s)

    def test_step_event_reports_attend_programs(self, model):
        adapter = PagedLlamaAdapter(model, num_pages=96,
                                    page_size=PAGE, max_length=128)
        sched = BatchScheduler(adapter, max_batch_size=4,
                               chunked_prefill=True,
                               prefill_chunk_tokens=8)
        for rid, p in PROMPTS.items():
            sched.submit(Request(rid, list(p),
                                 max_new_tokens=N_NEW[rid]))
        ev = sched.step()
        assert ev["attend_programs"] == adapter.attend_program_count
        assert ev["attend_programs"] >= 1

    def test_qkv_bias_model_fuses_and_agrees(self):
        # Qwen2-style q/k/v biases ride the fused prologue
        bmodel = _fresh_model(seed=29, attention_bias=True)
        base, _, _ = _serve(bmodel, chunked=False)
        got, _, ad = _serve(bmodel)
        assert got == base
        assert _kinds(ad) == {"ragged_fused"}

    @_slow
    def test_windowed_model_modes_agree(self):
        wmodel = _fresh_model(seed=23, sliding_window=6)
        base, _, _ = _serve(wmodel, chunked=False)
        got, _, ad = _serve(wmodel)
        assert got == base
        assert _kinds(ad) == {"ragged_fused"}


@pytest.mark.parametrize("name", ["ragged_attention", "spec_decode"])
def test_removed_flag_is_unknown(name):
    with pytest.raises(Exception, match="unknown flag"):
        paddle.set_flags({name: "off"})
