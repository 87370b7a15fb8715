"""FlashAttention for TPU — Pallas kernel + chunked XLA fallback.

Upstream analog: paddle/phi/kernels/gpu/flash_attn_kernel.cu (which wraps
the CUDA flashattn library). This is a from-scratch TPU design:

* forward: online-softmax blocked kernel. Grid (batch*heads, q_blocks,
  k_blocks); K-loop is the innermost ("arbitrary") grid dim so the fp32
  accumulator, running max m and running sum l live in VMEM scratch
  across K iterations. QK^T and PV ride the MXU with fp32 accumulate.
* backward: two dedicated Pallas kernels (matching the reference's
  flash_attn_bwd in paddle/phi/kernels/gpu/flash_attn_kernel.cu):
  a dk/dv kernel with grid (batch*kv_heads, k_blocks, [group,] q_blocks)
  accumulating into VMEM scratch across the inner q loop, and a dq
  kernel with grid (batch*heads, q_blocks, k_blocks) accumulating dq
  across the inner k loop. delta = sum(do*o) is precomputed in XLA.
  A chunked `lax.scan` XLA fallback covers non-tileable shapes.
* GQA/MQA: kv-head = q-head // group resolved in the BlockSpec index
  map — no KV repetition in HBM.

Layout convention matches the reference API: [batch, seq, heads, head_dim].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
_LANE = 128


def _prec():
    """MXU dot precision for the flash kernels. DEFAULT keeps native
    bf16x bf16->fp32 single-pass MXU throughput (the flash-attention
    convention); the FLAGS_flash_precision_highest escape hatch forces
    multi-pass fp32-emulated multiplies for debugging numerics."""
    from ...framework.flags import flag

    try:
        if flag("flash_precision_highest"):
            return jax.lax.Precision.HIGHEST
    except KeyError:
        pass
    return jax.lax.Precision.DEFAULT


def _flash_fwd_kernel(scale, causal, window, offset, block_q, block_k,
                      nk,
                      q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref):
    # offset = sk - sq: causal condition is q_idx + offset >= k_idx;
    # window > 0 additionally requires q_idx + offset - k_idx < window
    # (Mistral band) — whole out-of-band k blocks are skipped
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    run = True
    if causal:
        run = ki * block_k <= qi * block_q + block_q - 1 + offset
        if window:
            run = jnp.logical_and(
                run,
                ki * block_k + block_k - 1
                >= qi * block_q + offset - window + 1)

    @pl.when(run if causal else ki >= 0)
    def _():
        # dots ride the MXU on the native dtype (single pass for bf16)
        # with fp32 accumulation; softmax math stays fp32
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(),
        ) * scale  # (Bq, Bk)
        if causal:
            q_idx = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_idx = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            keep = q_idx + offset >= k_idx
            if window:
                keep = keep & (q_idx + offset - k_idx < window)
            s = jnp.where(keep, s, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
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
        # lse is (Bq,) logically; stored broadcast over an 8-lane minor
        # dim to satisfy TPU tiling (block minor dim == array minor dim)
        lse_ref[0] = jnp.broadcast_to(
            (m_ref[:, :1] + jnp.log(safe_l)), lse_ref.shape[1:]
        )


def _flash_fwd_pallas(q, k, v, causal, scale, block_q, block_k,
                      interpret=False, window=0):
    """q: (BH, Sq, D); k/v: (BHkv, Sk, D). Returns (out, lse)."""
    bh, sq, d = q.shape
    bhkv, sk, _ = k.shape
    group = bh // bhkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)

    kernel = functools.partial(
        _flash_fwd_kernel, scale, causal, int(window or 0), sk - sq,
        block_q, block_k, nk
    )
    from jax.experimental.pallas import tpu as pltpu

    params = dict(interpret=True) if interpret else dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    )
    scratch = [
        pltpu.VMEM((block_q, d), jnp.float32),
        pltpu.VMEM((block_q, _LANE), jnp.float32),
        pltpu.VMEM((block_q, _LANE), jnp.float32),
    ]

    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, j: (h // group, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, j: (h // group, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_q, 8), lambda h, i, j: (h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 8), jnp.float32),
        ],
        scratch_shapes=scratch,
        **params,
    )(q, k, v)
    return out, lse[..., 0]


def _flash_fwd_ref(q, k, v, causal, scale, window=0):
    """XLA reference forward (full S² — used off-TPU / small shapes)."""
    bh, sq, d = q.shape
    bhkv, sk, _ = k.shape
    if bhkv != bh:
        rep = bh // bhkv
        k = jnp.repeat(k, rep, axis=0)
        v = jnp.repeat(v, rep, axis=0)
    s = jnp.einsum(
        "bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window:
            diff = (jnp.arange(sq)[:, None] + (sk - sq)
                    - jnp.arange(sk)[None, :])
            mask = mask & (diff < window)
        s = jnp.where(mask[None], s, NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype), lse


def _flash_bwd_dkdv_kernel(scale, causal, window, offset, block_q,
                           block_k, group, nq,
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

    run = True
    if causal:
        # any q row in this block attends to any k col in this block?
        run = qi * block_q + block_q - 1 + offset >= ki * block_k
        if window:
            run = jnp.logical_and(
                run,
                qi * block_q + offset
                <= ki * block_k + block_k - 1 + window - 1)

    @pl.when(run if causal else qi >= 0)
    def _():
        # native-dtype MXU dots, fp32 accumulate; p/ds cast back to the
        # input dtype before their dots (flash-attn convention)
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
        ) * scale  # (Bq, Bk)
        p = jnp.exp(s - lse)
        if causal:
            q_idx = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_idx = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            keep = q_idx + offset >= k_idx
            if window:
                keep = keep & (q_idx + offset - k_idx < window)
            p = jnp.where(keep, p, 0.0)
        # dv += p^T do
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(),
        )
        # dp = do v^T ; ds = p * (dp - delta) * scale
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(),
        )
        ds = p * (dp - delta) * scale
        # dk += ds^T q
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(),
        )

    @pl.when(jnp.logical_and(gi == group - 1, qi == nq - 1))
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(scale, causal, window, offset, block_q,
                         block_k, nk,
                         q_ref, do_ref, lse_ref, delta_ref,
                         k_ref, v_ref, dq_ref, dq_acc):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = True
    if causal:
        run = ki * block_k <= qi * block_q + block_q - 1 + offset
        if window:
            run = jnp.logical_and(
                run,
                ki * block_k + block_k - 1
                >= qi * block_q + offset - window + 1)

    @pl.when(run if causal else ki >= 0)
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
        p = jnp.exp(s - lse)
        if causal:
            q_idx = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_idx = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            keep = q_idx + offset >= k_idx
            if window:
                keep = keep & (q_idx + offset - k_idx < window)
            p = jnp.where(keep, p, 0.0)
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


def _flash_bwd_pallas(q, k, v, out, lse, do, causal, scale,
                      block_q, block_k, dlse=None, interpret=False,
                      window=0):
    """Pallas dq/dk/dv. q/do: (BH, Sq, D); k/v: (BHkv, Sk, D);
    lse: (BH, Sq) fp32. Returns (dq, dk, dv) in input dtypes."""
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = q.shape
    bhkv, sk, _ = k.shape
    group = bh // bhkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    offset = sk - sq

    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # (BH, Sq)
    if dlse is not None:
        # d(lse)/ds = p, so ds += p*dlse — folded in as delta -= dlse
        delta = delta - dlse
    # column-broadcast over an 8-lane minor dim (TPU tiling; see fwd lse)
    lse8 = jnp.broadcast_to(lse[..., None], (bh, sq, 8))
    delta8 = jnp.broadcast_to(delta[..., None], (bh, sq, 8))

    qspec = pl.BlockSpec(
        (1, block_q, d), lambda hk, ki, g, qi: (hk * group + g, qi, 0)
    )
    rowspec = pl.BlockSpec(
        (1, block_q, 8), lambda hk, ki, g, qi: (hk * group + g, qi, 0)
    )
    kvspec = pl.BlockSpec((1, block_k, d), lambda hk, ki, g, qi: (hk, ki, 0))

    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkdv_kernel, scale, causal, int(window or 0),
            offset, block_q, block_k, group, nq,
        ),
        name="flash_bwd_dkv",
        grid=(bhkv, nk, group, nq),
        in_specs=[qspec, qspec, rowspec, rowspec, kvspec, kvspec],
        out_specs=[kvspec, kvspec],
        out_shape=[
            jax.ShapeDtypeStruct((bhkv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bhkv, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        **(dict(interpret=True) if interpret else dict(
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=(
                    "parallel", "parallel", "arbitrary", "arbitrary"
                )
            )
        )),
    )(q, do, lse8, delta8, k, v)

    qspec2 = pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0))
    rowspec2 = pl.BlockSpec((1, block_q, 8), lambda h, i, j: (h, i, 0))
    kvspec2 = pl.BlockSpec(
        (1, block_k, d), lambda h, i, j: (h // group, j, 0)
    )
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, scale, causal, int(window or 0),
            offset, block_q, block_k, nk,
        ),
        name="flash_bwd_dq",
        grid=(bh, nq, nk),
        in_specs=[qspec2, qspec2, rowspec2, rowspec2, kvspec2, kvspec2],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        **(dict(interpret=True) if interpret else dict(
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")
            )
        )),
    )(q, do, lse8, delta8, k, v)
    return dq, dk, dv


def _flash_bwd_chunked(q, k, v, out, lse, do, causal, scale, block_k,
                       dlse=None, window=0):
    """Blocked recompute backward over K blocks (lax.scan).

    ``dlse`` (BH, Sq) is the optional cotangent of the logsumexp output
    (needed when lse feeds the ring-attention combine): since
    dlse/ds = softmax(s) = p, it adds ``p * dlse`` to ds."""
    bh, sq, d = q.shape
    bhkv, sk, _ = k.shape
    group = bh // bhkv
    if group != 1:
        k_full = jnp.repeat(k, group, axis=0)
        v_full = jnp.repeat(v, group, axis=0)
    else:
        k_full, v_full = k, v

    block_k = min(block_k, sk)
    nk = sk // block_k if sk % block_k == 0 else 1
    if sk % block_k != 0:
        block_k = sk
        nk = 1

    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    outf = out.astype(jnp.float32)
    delta = jnp.sum(dof * outf, axis=-1)  # (BH, Sq)

    k_blocks = k_full.astype(jnp.float32).reshape(bh, nk, block_k, d)
    v_blocks = v_full.astype(jnp.float32).reshape(bh, nk, block_k, d)
    k_blocks = jnp.moveaxis(k_blocks, 1, 0)  # (nk, BH, Bk, D)
    v_blocks = jnp.moveaxis(v_blocks, 1, 0)

    q_pos = jnp.arange(sq)

    def body(dq_acc, blk):
        k_b, v_b, ki = blk
        s = jnp.einsum("bqd,bkd->bqk", qf, k_b) * scale
        if causal:
            k_pos = ki * block_k + jnp.arange(block_k)
            diff = q_pos[:, None] + (sk - sq) - k_pos[None, :]
            mask = diff >= 0
            if window:
                mask = mask & (diff < window)
            s = jnp.where(mask[None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])
        dv_b = jnp.einsum("bqk,bqd->bkd", p, dof)
        dp = jnp.einsum("bqd,bkd->bqk", dof, v_b)
        ds = p * (dp - delta[..., None])
        if dlse is not None:
            ds = ds + p * dlse[..., None]
        ds = ds * scale
        dq_acc = dq_acc + jnp.einsum("bqk,bkd->bqd", ds, k_b)
        dk_b = jnp.einsum("bqk,bqd->bkd", ds, qf)
        return dq_acc, (dk_b, dv_b)

    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        body, jnp.zeros_like(qf),
        (k_blocks, v_blocks, jnp.arange(nk)),
    )
    dk = jnp.moveaxis(dk_blocks, 0, 1).reshape(bh, sk, d)
    dv = jnp.moveaxis(dv_blocks, 0, 1).reshape(bh, sk, d)
    if group != 1:
        dk = dk.reshape(bhkv, group, sk, d).sum(1)
        dv = dv.reshape(bhkv, group, sk, d).sum(1)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _interpret():
    from . import interpret_mode

    return interpret_mode()


def _pallas_ok(q, k, block_q, block_k):
    from . import use_pallas

    bh, sq, d = q.shape
    sk = k.shape[1]
    # head dims that aren't lane-multiples (e.g. 64 — GPT-3 1.3B) are
    # zero-padded to 128 before the kernel (_pad_head_dim): zeros are
    # inert in QK^T and PV, so results are exact. Cost: the d-dim
    # matmuls run at 128/d of their useful FLOPs — still far better
    # than the O(S^2)-memory XLA fallback at training lengths.
    return (
        (use_pallas() or _interpret())
        and sq % min(block_q, sq) == 0
        and sk % min(block_k, sk) == 0
        and sq >= 8 and sk >= 8
    )


def _pad_head_dim(arrs, d):
    """Zero-pad the trailing head dim to the 128-lane multiple."""
    target = -(-d // _LANE) * _LANE
    if target == d:
        return arrs
    return tuple(
        jnp.pad(a, ((0, 0), (0, 0), (0, target - d))) for a in arrs
    )


def _flash_bwd_dispatch(q, k, v, out, lse, do, causal, scale,
                        block_q, block_k, dlse=None, window=0):
    from ...framework.flags import flag

    from . import record_dispatch

    ok = flag("use_pallas_flash_bwd") and _pallas_ok(q, k, block_q, block_k)
    record_dispatch("flash_bwd", ok)
    if ok:
        d = q.shape[-1]
        qp, outp, dop = _pad_head_dim((q, out, do), d)
        kp, vp = _pad_head_dim((k, v), d)
        dq, dk, dv = _flash_bwd_pallas(
            qp, kp, vp, outp, lse, dop, causal, scale, block_q, block_k,
            dlse=dlse, interpret=_interpret(), window=window,
        )
        if dq.shape[-1] != d:
            dq, dk, dv = dq[..., :d], dk[..., :d], dv[..., :d]
        return dq, dk, dv
    return _flash_bwd_chunked(
        q, k, v, out, lse, do, causal, scale, block_k, dlse=dlse,
        window=window,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, causal, scale, block_q, block_k, window=0):
    out, _ = _flash_fwd_dispatch(q, k, v, causal, scale, block_q,
                                 block_k, window)
    return out


def _flash_fwd_dispatch(q, k, v, causal, scale, block_q, block_k,
                        window=0):
    from . import record_dispatch

    ok = _pallas_ok(q, k, block_q, block_k)
    record_dispatch("flash_fwd", ok)
    if ok:
        d = q.shape[-1]
        (qp,) = _pad_head_dim((q,), d)
        kp, vp = _pad_head_dim((k, v), d)
        out, lse = _flash_fwd_pallas(
            qp, kp, vp, causal, scale, block_q, block_k,
            interpret=_interpret(), window=window,
        )
        if out.shape[-1] != d:
            out = out[..., :d]
        return out, lse
    return _flash_fwd_ref(q, k, v, causal, scale, window=window)


def _flash_core_fwd(q, k, v, causal, scale, block_q, block_k,
                    window=0):
    out, lse = _flash_fwd_dispatch(q, k, v, causal, scale, block_q,
                                   block_k, window)
    return out, (q, k, v, out, lse)


def _flash_core_bwd(causal, scale, block_q, block_k, window, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd_dispatch(
        q, k, v, out, lse, do, causal, scale, block_q, block_k,
        window=window,
    )
    return dq, dk, dv


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_core_lse(q, k, v, causal, scale, block_q, block_k):
    """Differentiable (out, lse) pair — the unit ring attention scans:
    the online-combine consumes both, so lse carries a real cotangent."""
    return _flash_fwd_dispatch(q, k, v, causal, scale, block_q, block_k)


def _flash_core_lse_fwd(q, k, v, causal, scale, block_q, block_k):
    out, lse = _flash_fwd_dispatch(
        q, k, v, causal, scale, block_q, block_k
    )
    return (out, lse), (q, k, v, out, lse)


def _flash_core_lse_bwd(causal, scale, block_q, block_k, res, cts):
    q, k, v, out, lse = res
    do, dlse = cts
    dq, dk, dv = _flash_bwd_dispatch(
        q, k, v, out, lse, do, causal, scale, block_q, block_k, dlse=dlse
    )
    return dq, dk, dv


_flash_core_lse.defvjp(_flash_core_lse_fwd, _flash_core_lse_bwd)


def flash_attention(q, k, v, causal=False, sm_scale=None,
                    block_q=512, block_k=512, window=0):
    """q,k,v: [B, S, H, D] (reference layout). Returns [B, Sq, H, D].
    ``window`` > 0 (requires causal): sliding-window band
    0 <= q_pos - k_pos < window with out-of-band blocks skipped."""
    if window and not causal:
        raise ValueError("flash_attention: window requires causal=True")
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)

    def local(q, k, v):
        b, sq, h, _ = q.shape
        hkv = k.shape[2]
        sk = k.shape[1]
        q3 = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
        k3 = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
        v3 = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
        out = _flash_core(q3, k3, v3, bool(causal), float(scale),
                          int(block_q), int(block_k), int(window or 0))
        return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)

    # on a mesh: batch over dp, heads over mp, where they divide
    from jax.sharding import PartitionSpec as P

    from ...distributed.mesh import dividing_axis
    from . import _per_shard

    spec = P(dividing_axis("dp", q.shape[0]), None,
             dividing_axis("mp", q.shape[2], k.shape[2]), None)
    return _per_shard(local, (spec,) * 3, spec)(q, k, v)


def flash_attention_with_lse(q, k, v, causal=False, sm_scale=None,
                             block_q=512, block_k=512):
    """Like flash_attention but also returns logsumexp [B, H, S]
    (needed by ring attention to combine partial results)."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    sk = k.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    q3 = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    k3 = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    v3 = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    # _flash_core_lse (not the raw dispatch): differentiating the public
    # API must hit the custom VJP — autodiff straight through pallas_call
    # would fail on TPU.
    out, lse = _flash_core_lse(
        q3, k3, v3, bool(causal), float(scale), int(block_q), int(block_k)
    )
    return (
        out.reshape(b, h, sq, d).transpose(0, 2, 1, 3),
        lse.reshape(b, h, sq),
    )
