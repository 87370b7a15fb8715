"""Blocked-ragged (varlen) FlashAttention for TPU.

Upstream analog: the varlen path of
paddle/phi/kernels/gpu/flash_attn_kernel.cu (flash_attn_varlen), which
the reference exposes as flash_attn_unpadded over cu_seqlens-packed
batches. TPU-first design (not a port):

* sequences are packed along one token axis; per-token segment ids and
  local positions are computed once in XLA (O(T)) and fed to the kernel
  as int32 metadata, so the kernel stays static-shape;
* the forward kernel is the online-softmax blocked kernel with a
  segment-equality mask folded into each tile;
* per-block segment min/max and local-position extrema ride the scalar
  prefetch channel (SMEM — same machinery as paged_attention): a
  (q_block, k_block) tile whose segment ranges cannot intersect (or is
  entirely above the causal diagonal inside a single segment) is
  skipped before any MXU work, so cost approaches O(sum_i s_i^2)
  instead of O(T^2);
* dedicated dq and dk/dv backward kernels share the same mask +
  block-skip logic via a custom VJP (autodiff cannot differentiate
  through pallas_call on TPU).

The segment-masked XLA path in nn/functional/flash_attention.py remains
the oracle and the fallback for non-tileable shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .flash_attention import NEG_INF, _prec, _interpret

_LANE = 128


def _block_run(causal, qsmin, qsmax, qlmax, ksmin, ksmax, klmin):
    """Whether a (q_block, k_block) tile can contain any unmasked
    entry, from per-block segment/position extrema (SMEM scalars)."""
    run = jnp.logical_and(ksmin <= qsmax, ksmax >= qsmin)
    if causal:
        single = jnp.logical_and(
            jnp.logical_and(qsmin == qsmax, ksmin == ksmax),
            qsmin == ksmin,
        )
        above = jnp.logical_and(single, qlmax < klmin)
        run = jnp.logical_and(run, jnp.logical_not(above))
    return run


def _tile_mask(causal, qseg, qloc, kseg, kloc):
    """(Bq, Bk) bool mask from q-side column vectors (Bq, 1) and k-side
    row vectors (1, Bk)."""
    mask = qseg == kseg
    if causal:
        mask = jnp.logical_and(mask, qloc >= kloc)
    return mask


def _varlen_fwd_kernel(scale, causal, block_q, block_k, nk,
                       qsmin_ref, qsmax_ref, qlmax_ref,
                       ksmin_ref, ksmax_ref, klmin_ref,
                       qseg_ref, qloc_ref, kseg_ref, kloc_ref,
                       q_ref, k_ref, v_ref, o_ref, lse_ref,
                       acc_ref, m_ref, l_ref):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    run = _block_run(
        causal, qsmin_ref[qi], qsmax_ref[qi], qlmax_ref[qi],
        ksmin_ref[ki], ksmax_ref[ki], klmin_ref[ki],
    )

    @pl.when(run)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(),
        ) * scale  # (Bq, Bk)
        mask = _tile_mask(
            causal, qseg_ref[:, :1], qloc_ref[:, :1],
            kseg_ref[:1, :], kloc_ref[:1, :],
        )
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        # fully-masked rows: m stays NEG_INF, p == exp(0) == 1 there —
        # zero them so they contribute nothing (out stays 0)
        p = jnp.where(mask, p, 0.0)
        l_cur = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(),
        )
        m_ref[:] = jnp.broadcast_to(m_cur, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_cur, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(
            (m_ref[:, :1] + jnp.log(safe_l)), lse_ref.shape[1:]
        )


def _varlen_bwd_dkdv_kernel(scale, causal, block_q, block_k, group, nq,
                            qsmin_ref, qsmax_ref, qlmax_ref,
                            ksmin_ref, ksmax_ref, klmin_ref,
                            qseg_ref, qloc_ref, kseg_ref, kloc_ref,
                            q_ref, do_ref, lse_ref, delta_ref,
                            k_ref, v_ref, dk_ref, dv_ref,
                            dk_acc, dv_acc):
    ki = pl.program_id(1)
    gi = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(jnp.logical_and(gi == 0, qi == 0))
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = _block_run(
        causal, qsmin_ref[qi], qsmax_ref[qi], qlmax_ref[qi],
        ksmin_ref[ki], ksmax_ref[ki], klmin_ref[ki],
    )

    @pl.when(run)
    def _():
        q = q_ref[0]
        do = do_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(),
        ) * scale
        mask = _tile_mask(
            causal, qseg_ref[:, :1], qloc_ref[:, :1],
            kseg_ref[:1, :], kloc_ref[:1, :],
        )
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(),
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(),
        )
        ds = p * (dp - delta) * scale
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(),
        )

    @pl.when(jnp.logical_and(gi == group - 1, qi == nq - 1))
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _varlen_bwd_dq_kernel(scale, causal, block_q, block_k, nk,
                          qsmin_ref, qsmax_ref, qlmax_ref,
                          ksmin_ref, ksmax_ref, klmin_ref,
                          qseg_ref, qloc_ref, kseg_ref, kloc_ref,
                          q_ref, do_ref, lse_ref, delta_ref,
                          k_ref, v_ref, dq_ref, dq_acc):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = _block_run(
        causal, qsmin_ref[qi], qsmax_ref[qi], qlmax_ref[qi],
        ksmin_ref[ki], ksmax_ref[ki], klmin_ref[ki],
    )

    @pl.when(run)
    def _():
        q = q_ref[0]
        do = do_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(),
        ) * scale
        mask = _tile_mask(
            causal, qseg_ref[:, :1], qloc_ref[:, :1],
            kseg_ref[:1, :], kloc_ref[:1, :],
        )
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(),
        )
        ds = p * (dp - delta) * scale
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(),
        )

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _block_extrema(seg, loc, block):
    """Per-block (min seg, max seg, and the causal-relevant loc
    extremum) — scalar-prefetch operands."""
    n = seg.shape[0] // block
    seg2 = seg.reshape(n, block)
    loc2 = loc.reshape(n, block)
    return seg2.min(1), seg2.max(1), loc2.min(1), loc2.max(1)


def _meta_cols(seg, loc):
    """(T,) int32 -> (T, 8) column-broadcast (TPU minor-dim tiling)."""
    return (
        jnp.broadcast_to(seg[:, None], (seg.shape[0], 8)),
        jnp.broadcast_to(loc[:, None], (loc.shape[0], 8)),
    )


def _meta_rows(seg, loc):
    """(Tk,) int32 -> (8, Tk) row-broadcast."""
    return (
        jnp.broadcast_to(seg[None, :], (8, seg.shape[0])),
        jnp.broadcast_to(loc[None, :], (8, loc.shape[0])),
    )


def _varlen_fwd_pallas(qh, kh, vh, qseg, qloc, kseg, kloc,
                       causal, scale, block_q, block_k,
                       interpret=False):
    """qh: (H, T, D); kh/vh: (Hkv, Tk, D); qseg/qloc: (T,) int32;
    kseg/kloc: (Tk,) int32. Returns (out (H,T,D), lse (H,T))."""
    from jax.experimental.pallas import tpu as pltpu

    h, t, d = qh.shape
    hkv, tk, _ = kh.shape
    group = h // hkv
    block_q = min(block_q, t)
    block_k = min(block_k, tk)
    nq = t // block_q
    nk = tk // block_k

    qsmin, qsmax, _, qlmax = _block_extrema(qseg, qloc, block_q)
    ksmin, ksmax, klmin, _ = _block_extrema(kseg, kloc, block_k)
    qseg8, qloc8 = _meta_cols(qseg, qloc)
    kseg8, kloc8 = _meta_rows(kseg, kloc)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(h, nq, nk),
        in_specs=[
            pl.BlockSpec((block_q, 8), lambda hh, i, j, *_: (i, 0)),
            pl.BlockSpec((block_q, 8), lambda hh, i, j, *_: (i, 0)),
            pl.BlockSpec((8, block_k), lambda hh, i, j, *_: (0, j)),
            pl.BlockSpec((8, block_k), lambda hh, i, j, *_: (0, j)),
            pl.BlockSpec((1, block_q, d), lambda hh, i, j, *_: (hh, i, 0)),
            pl.BlockSpec(
                (1, block_k, d), lambda hh, i, j, *_: (hh // group, j, 0)
            ),
            pl.BlockSpec(
                (1, block_k, d), lambda hh, i, j, *_: (hh // group, j, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda hh, i, j, *_: (hh, i, 0)),
            pl.BlockSpec((1, block_q, 8), lambda hh, i, j, *_: (hh, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(
            _varlen_fwd_kernel, scale, causal, block_q, block_k, nk
        ),
        name="flash_varlen_fwd",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((h, t, d), qh.dtype),
            jax.ShapeDtypeStruct((h, t, 8), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ) if not interpret else None,
    )(
        qsmin, qsmax, qlmax, ksmin, ksmax, klmin,
        qseg8, qloc8, kseg8, kloc8, qh, kh, vh,
    )
    return out, lse[..., 0]


def _varlen_bwd_pallas(qh, kh, vh, out, lse, do, qseg, qloc, kseg, kloc,
                       causal, scale, block_q, block_k,
                       interpret=False):
    from jax.experimental.pallas import tpu as pltpu

    h, t, d = qh.shape
    hkv, tk, _ = kh.shape
    group = h // hkv
    block_q = min(block_q, t)
    block_k = min(block_k, tk)
    nq = t // block_q
    nk = tk // block_k

    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # (H, T)
    lse8 = jnp.broadcast_to(lse[..., None], (h, t, 8))
    delta8 = jnp.broadcast_to(delta[..., None], (h, t, 8))

    qsmin, qsmax, _, qlmax = _block_extrema(qseg, qloc, block_q)
    ksmin, ksmax, klmin, _ = _block_extrema(kseg, kloc, block_k)
    qseg8, qloc8 = _meta_cols(qseg, qloc)
    kseg8, kloc8 = _meta_rows(kseg, kloc)

    # dk/dv: grid (Hkv, nk, group, nq); q-side blocks walk the inner loop
    qspec = pl.BlockSpec(
        (block_q, 8), lambda hk, ki, g, qi, *_: (qi, 0)
    )
    kspec = pl.BlockSpec(
        (8, block_k), lambda hk, ki, g, qi, *_: (0, ki)
    )
    qdat = pl.BlockSpec(
        (1, block_q, d), lambda hk, ki, g, qi, *_: (hk * group + g, qi, 0)
    )
    qrow = pl.BlockSpec(
        (1, block_q, 8), lambda hk, ki, g, qi, *_: (hk * group + g, qi, 0)
    )
    kvdat = pl.BlockSpec(
        (1, block_k, d), lambda hk, ki, g, qi, *_: (hk, ki, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(hkv, nk, group, nq),
        in_specs=[qspec, qspec, kspec, kspec,
                  qdat, qdat, qrow, qrow, kvdat, kvdat],
        out_specs=[kvdat, kvdat],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
    )
    dk, dv = pl.pallas_call(
        functools.partial(
            _varlen_bwd_dkdv_kernel, scale, causal,
            block_q, block_k, group, nq,
        ),
        name="flash_varlen_bwd_dkv",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((hkv, tk, d), kh.dtype),
            jax.ShapeDtypeStruct((hkv, tk, d), vh.dtype),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "arbitrary", "arbitrary"
            )
        ) if not interpret else None,
    )(
        qsmin, qsmax, qlmax, ksmin, ksmax, klmin,
        qseg8, qloc8, kseg8, kloc8,
        qh, do, lse8, delta8, kh, vh,
    )

    # dq: grid (H, nq, nk)
    qspec2 = pl.BlockSpec((block_q, 8), lambda hh, i, j, *_: (i, 0))
    kspec2 = pl.BlockSpec((8, block_k), lambda hh, i, j, *_: (0, j))
    qdat2 = pl.BlockSpec((1, block_q, d), lambda hh, i, j, *_: (hh, i, 0))
    qrow2 = pl.BlockSpec((1, block_q, 8), lambda hh, i, j, *_: (hh, i, 0))
    kvdat2 = pl.BlockSpec(
        (1, block_k, d), lambda hh, i, j, *_: (hh // group, j, 0)
    )
    grid_spec2 = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(h, nq, nk),
        in_specs=[qspec2, qspec2, kspec2, kspec2,
                  qdat2, qdat2, qrow2, qrow2, kvdat2, kvdat2],
        out_specs=qdat2,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
    )
    dq = pl.pallas_call(
        functools.partial(
            _varlen_bwd_dq_kernel, scale, causal, block_q, block_k, nk
        ),
        name="flash_varlen_bwd_dq",
        grid_spec=grid_spec2,
        out_shape=jax.ShapeDtypeStruct((h, t, d), qh.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ) if not interpret else None,
    )(
        qsmin, qsmax, qlmax, ksmin, ksmax, klmin,
        qseg8, qloc8, kseg8, kloc8,
        qh, do, lse8, delta8, kh, vh,
    )
    return dq, dk, dv


def _pad_d(arrs, d):
    target = -(-d // _LANE) * _LANE
    if target == d:
        return arrs
    return tuple(
        jnp.pad(a, ((0, 0), (0, 0), (0, target - d))) for a in arrs
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _varlen_core(qh, kh, vh, qseg, qloc, kseg, kloc,
                 causal, scale, block_q, block_k):
    out, _ = _varlen_fwd_pallas(
        qh, kh, vh, qseg, qloc, kseg, kloc,
        causal, scale, block_q, block_k, interpret=_interpret(),
    )
    return out


def _varlen_core_fwd(qh, kh, vh, qseg, qloc, kseg, kloc,
                     causal, scale, block_q, block_k):
    out, lse = _varlen_fwd_pallas(
        qh, kh, vh, qseg, qloc, kseg, kloc,
        causal, scale, block_q, block_k, interpret=_interpret(),
    )
    return out, (qh, kh, vh, out, lse, qseg, qloc, kseg, kloc)


def _varlen_core_bwd(causal, scale, block_q, block_k, res, do):
    qh, kh, vh, out, lse, qseg, qloc, kseg, kloc = res
    dq, dk, dv = _varlen_bwd_pallas(
        qh, kh, vh, out, lse, do, qseg, qloc, kseg, kloc,
        causal, scale, block_q, block_k, interpret=_interpret(),
    )
    zero_i = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (dq, dk, dv,
            zero_i(qseg), zero_i(qloc), zero_i(kseg), zero_i(kloc))


_varlen_core.defvjp(_varlen_core_fwd, _varlen_core_bwd)


def _segments(cu, total):
    """Per-token segment id + local position from cu_seqlens."""
    cu = cu.astype(jnp.int32)
    pos = jnp.arange(total, dtype=jnp.int32)
    seg = jnp.searchsorted(cu[1:], pos, side="right").astype(jnp.int32)
    loc = pos - cu[seg]
    return seg, loc


def varlen_ok(total_q, total_k, block_q, block_k):
    from . import use_pallas

    bq = min(block_q, total_q)
    bk = min(block_k, total_k)
    return (
        (use_pallas() or _interpret())
        and total_q % bq == 0 and total_k % bk == 0
        and total_q >= 8 and total_k >= 8
    )


def varlen_attention(q, k, v, cu_seqlens_q, cu_seqlens_k, causal, scale,
                     block_q=512, block_k=512):
    """Packed varlen attention via the blocked-ragged Pallas kernel.

    q: (total_q, H, D); k/v: (total_k, Hkv, D); cu_seqlens_*: (B+1,)
    int32. Returns (total_q, H, D). Tokens outside any segment
    (padding beyond cu[-1]) produce zeros only if masked by callers —
    standard packing has total == cu[-1].
    """
    tq, h, d = q.shape
    tk, hkv, _ = k.shape
    qseg, qloc = _segments(cu_seqlens_q, tq)
    kseg, kloc = _segments(cu_seqlens_k, tk)
    qh = jnp.swapaxes(q, 0, 1)
    kh = jnp.swapaxes(k, 0, 1)
    vh = jnp.swapaxes(v, 0, 1)
    (qh,) = _pad_d((qh,), d)
    kh, vh = _pad_d((kh, vh), d)
    out = _varlen_core(
        qh, kh, vh, qseg, qloc, kseg, kloc,
        bool(causal), float(scale), int(block_q), int(block_k),
    )
    if out.shape[-1] != d:
        out = out[..., :d]
    return jnp.swapaxes(out, 0, 1)
