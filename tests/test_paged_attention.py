"""Paged KV-cache decode attention kernel (upstream analogs: the
block/paged attention path of fused_multi_transformer serving kernels).
Runs the Pallas kernel in interpret mode on CPU vs a dense reference."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.kernels import (
    paged_attention,
    paged_attention_reference,
)


def _case(B=2, H=4, KVH=4, D=64, NP=8, P=16, MAXP=3, lens=(40, 17),
          dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, H, D), dtype)
    kp = jnp.asarray(rng.randn(NP, P, KVH, D), dtype)
    vp = jnp.asarray(rng.randn(NP, P, KVH, D), dtype)
    tbl = jnp.asarray(
        rng.permutation(NP)[:B * MAXP].reshape(B, MAXP), jnp.int32)
    ln = jnp.asarray(lens, jnp.int32)
    return q, kp, vp, tbl, ln


class TestPagedAttention:
    def test_matches_reference(self):
        q, kp, vp, tbl, lens = _case()
        out = paged_attention(q, kp, vp, tbl, lens)
        ref = paged_attention_reference(q, kp, vp, tbl, lens)
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4)

    def test_gqa_heads(self):
        q, kp, vp, tbl, lens = _case(H=8, KVH=2)
        out = paged_attention(q, kp, vp, tbl, lens)
        ref = paged_attention_reference(q, kp, vp, tbl, lens)
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4)

    def test_ragged_lengths_page_misaligned(self):
        # lengths not multiples of the page size, incl. a 1-token lane
        q, kp, vp, tbl, lens = _case(lens=(33, 1))
        out = paged_attention(q, kp, vp, tbl, lens)
        ref = paged_attention_reference(q, kp, vp, tbl, lens)
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4)

    def test_bfloat16(self):
        q, kp, vp, tbl, lens = _case(dtype=jnp.bfloat16)
        out = paged_attention(q, kp, vp, tbl, lens)
        ref = paged_attention_reference(
            q.astype(jnp.float32), kp.astype(jnp.float32),
            vp.astype(jnp.float32), tbl, lens)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), ref, atol=3e-2, rtol=3e-2)

    @pytest.mark.parametrize("window", [8, 16, 24, 100])
    def test_sliding_window_matches_reference(self, window):
        # window crossing page boundaries (P=16): 8 (within last
        # page), 16 (exactly one page), 24 (page-misaligned), 100
        # (wider than every lane -> full attention)
        q, kp, vp, tbl, lens = _case(lens=(40, 17))
        out = paged_attention(q, kp, vp, tbl, lens, window=window)
        ref = paged_attention_reference(q, kp, vp, tbl, lens,
                                        window=window)
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4)
        full = paged_attention_reference(q, kp, vp, tbl, lens)
        if window < int(min(np.asarray(lens))):
            assert not np.allclose(np.asarray(out), full, atol=1e-4)

    def test_under_jit(self):
        q, kp, vp, tbl, lens = _case()
        f = jax.jit(lambda *a: paged_attention(*a, interpret=True))
        out = f(q, kp, vp, tbl, lens)
        ref = paged_attention_reference(q, kp, vp, tbl, lens)
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4)


class TestPagedKVCacheManager:
    def _dense(self, qi, ks, vs, H, KVH, D):
        import math

        scale = 1 / math.sqrt(D)
        ks = np.stack(ks)
        vs = np.stack(vs)
        res = np.zeros((H, D), "float32")
        for h in range(H):
            kh = ks[:, h // (H // KVH)]
            vh = vs[:, h // (H // KVH)]
            s = kh @ qi[h] * scale
            p = np.exp(s - s.max())
            p /= p.sum()
            res[h] = p @ vh
        return res

    def test_continuous_batching_decode(self):
        import paddle_tpu as paddle
        from paddle_tpu.incubate.nn import PagedKVCacheManager

        rng = np.random.RandomState(0)
        KVH, D, H = 2, 64, 4
        mgr = PagedKVCacheManager(16, 4, KVH, D, dtype=jnp.float32)
        mgr.alloc("a")
        mgr.alloc("b")
        store = {"a": ([], []), "b": ([], [])}
        for sid, n in (("a", 9), ("b", 3)):
            for _ in range(n):
                k = rng.randn(KVH, D).astype("float32")
                v = rng.randn(KVH, D).astype("float32")
                mgr.append(sid, k, v)
                store[sid][0].append(k)
                store[sid][1].append(v)
        q = paddle.to_tensor(rng.randn(2, H, D).astype("float32"))
        out = mgr.attend(q, ["a", "b"])
        for i, sid in enumerate(("a", "b")):
            ref = self._dense(q.numpy()[i], *store[sid], H, KVH, D)
            np.testing.assert_allclose(
                out.numpy()[i], ref, atol=1e-4)

    def test_page_recycling_and_exhaustion(self):
        from paddle_tpu.incubate.nn import PagedKVCacheManager

        mgr = PagedKVCacheManager(2, 2, 1, 8, dtype=jnp.float32)
        mgr.alloc("s")
        k = np.zeros((1, 8), "float32")
        for _ in range(4):
            mgr.append("s", k, k)  # fills both pages
        with pytest.raises(RuntimeError):
            mgr.append("s", k, k)
        mgr.free("s")
        mgr.alloc("t")
        mgr.append("t", k, k)  # pool usable again
        assert mgr.seq_len("t") == 1


class TestPagedPrefill:
    def _ref(self, q, kp, vp, tbl, lens, P, H, KVH, D, T, window=0):
        import math

        B = q.shape[0]
        res = np.zeros((B, T, H, D), np.float32)
        scale = 1 / math.sqrt(D)
        for b in range(B):
            L = int(lens[b])
            n_used = -(-L // P)
            ks = np.concatenate(
                [np.asarray(kp)[tbl[b, p]] for p in range(n_used)],
                0)[:L]
            vs = np.concatenate(
                [np.asarray(vp)[tbl[b, p]] for p in range(n_used)],
                0)[:L]
            for r in range(T):
                qpos = L - T + r
                lo = max(0, qpos - window + 1) if window else 0
                for h in range(H):
                    kh = ks[lo:qpos + 1, h // (H // KVH)]
                    vh = vs[lo:qpos + 1, h // (H // KVH)]
                    s = kh @ np.asarray(q)[b, r, h] * scale
                    pr = np.exp(s - s.max())
                    pr /= pr.sum()
                    res[b, r, h] = pr @ vh
        return res

    def test_causal_ragged_prefill(self):
        import importlib

        pa = importlib.import_module(
            "paddle_tpu.ops.kernels.paged_attention")
        rng = np.random.RandomState(0)
        B, T, H, KVH, D = 2, 4, 4, 2, 32
        NP, P, MAXP = 10, 8, 4
        kp = jnp.asarray(rng.randn(NP, P, KVH, D), jnp.float32)
        vp = jnp.asarray(rng.randn(NP, P, KVH, D), jnp.float32)
        tbl = jnp.asarray(
            rng.permutation(NP)[:B * MAXP].reshape(B, MAXP),
            jnp.int32)
        lens = jnp.asarray([27, 12], jnp.int32)
        q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
        out = pa.paged_ragged_attention(q, kp, vp, tbl, lens)
        ref = self._ref(q, kp, vp, tbl, lens, P, H, KVH, D, T)
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4)

    @pytest.mark.parametrize("window", [5, 8, 11, 64])
    def test_windowed_prefill_matches_reference(self, window):
        # window below/at/above the page size (P=8) and wider than
        # every lane; lens page-misaligned, one lane shorter than T
        # would be masked by the caller so both lens exceed T here
        import importlib

        pa = importlib.import_module(
            "paddle_tpu.ops.kernels.paged_attention")
        rng = np.random.RandomState(7)
        B, T, H, KVH, D = 2, 4, 4, 2, 32
        NP, P, MAXP = 10, 8, 4
        kp = jnp.asarray(rng.randn(NP, P, KVH, D), jnp.float32)
        vp = jnp.asarray(rng.randn(NP, P, KVH, D), jnp.float32)
        tbl = jnp.asarray(
            rng.permutation(NP)[:B * MAXP].reshape(B, MAXP),
            jnp.int32)
        lens = jnp.asarray([27, 12], jnp.int32)
        q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
        out = pa.paged_ragged_attention(q, kp, vp, tbl, lens,
                                        window=window)
        ref = self._ref(q, kp, vp, tbl, lens, P, H, KVH, D, T,
                        window=window)
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4)

    def test_prefill_agrees_with_decode_on_last_token(self):
        import importlib

        pa = importlib.import_module(
            "paddle_tpu.ops.kernels.paged_attention")
        rng = np.random.RandomState(1)
        B, T, H, KVH, D = 2, 3, 4, 4, 32
        NP, P, MAXP = 8, 8, 3
        kp = jnp.asarray(rng.randn(NP, P, KVH, D), jnp.float32)
        vp = jnp.asarray(rng.randn(NP, P, KVH, D), jnp.float32)
        tbl = jnp.asarray(
            rng.permutation(NP)[:B * MAXP].reshape(B, MAXP),
            jnp.int32)
        lens = jnp.asarray([20, 9], jnp.int32)
        q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
        pre = pa.paged_ragged_attention(q, kp, vp, tbl, lens)
        dec = pa.paged_attention(q[:, -1], kp, vp, tbl, lens)
        np.testing.assert_allclose(
            np.asarray(pre[:, -1]), np.asarray(dec), atol=1e-5)
