"""Fused RMSNorm / LayerNorm Pallas TPU kernels.

Upstream analog: paddle/phi/kernels/gpu/rms_norm_kernel.cu (block-per-row
Welford/rsqrt fused normalize+scale). TPU design: rows are tiled into
(block_rows, hidden) VMEM blocks; stats in fp32 on the VPU; one pass.
Backward is XLA (it fuses fine — the win is the fwd fusion on the hot
decode/train path).

Dispatch caching: an eager caller (the serving step's 17 norms a step,
eager ``generate()``, tests) with concrete arrays on the Pallas branch
runs ONE cached ``jax.jit`` program per shape (``kernels.eager_call``,
wrapped outside the ``custom_vjp``), so a repeated shape is neither
traced nor lowered through Mosaic again — an eager ``pl.pallas_call``
is a fresh closure per call that no cache of JAX's recognises. Callers
already under an outer trace (``to_static``, ``jax.grad``, ``shard_map``)
inline the identical lowering; the surrounding program owns compilation
and caching there. The XLA reference branch stays op by op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None


def _choose_block_rows(n_rows, hidden, itemsize):
    """Row block of a (block_rows, hidden) VMEM tile, ≲ 2 MB: the whole
    array when it is that small (a block equal to the array is always
    legal), else a multiple of the sublane tile (8) — rows that do not
    divide are padded by ``_row_tiled_call``, never cut to one-row
    blocks (the TPU lowering refuses a block whose last two dims are
    neither (8, 128)-divisible nor the array's)."""
    target = (2 * 1024 * 1024) // max(hidden * itemsize, 1)
    br = max(8, min(256, target))
    if n_rows <= br:
        return n_rows
    while n_rows % br and br > 8:
        br //= 2
    return br


def _row_tiled_call(kernel, x2d, vecs, interpret, name):
    """Run a row-wise ``kernel(x_ref, *vec_refs, o_ref)`` over (n, h)
    in row blocks; ``vecs`` are (h,) operands every block sees whole.
    ``name`` is the kernel's name in a device trace."""
    n, h = x2d.shape
    br = _choose_block_rows(n, h, x2d.dtype.itemsize)
    n_pad = -(-n // br) * br
    if n_pad != n:
        x2d = jnp.pad(x2d, ((0, n_pad - n), (0, 0)))
    out = pl.pallas_call(
        kernel,
        name=name,
        out_shape=jax.ShapeDtypeStruct((n_pad, h), x2d.dtype),
        grid=(n_pad // br,),
        in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0))]
        + [pl.BlockSpec((h,), lambda i: (0,))] * len(vecs),
        out_specs=pl.BlockSpec((br, h), lambda i: (i, 0)),
        interpret=interpret,
    )(x2d, *vecs)
    return out[:n] if n_pad != n else out


def _rms_kernel(eps, has_w, x_ref, *refs):
    if has_w:
        w_ref, o_ref = refs
    else:
        (o_ref,) = refs
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    if has_w:
        y = y * w_ref[:].astype(jnp.float32)
    o_ref[:] = y.astype(o_ref.dtype)


def _rms_pallas(x2d, w, eps, interpret=False):
    return _row_tiled_call(
        functools.partial(_rms_kernel, eps, w is not None), x2d,
        [] if w is None else [w], interpret, "rms_norm")


def _rows_per_shard(local, x, vecs):
    """Run the row-wise ``local(x, *vecs)``; on a mesh, batch over dp
    where it divides, everything else replicated."""
    from jax.sharding import PartitionSpec as P

    from ...distributed.mesh import dividing_axis
    from . import _per_shard

    dp = dividing_axis("dp", x.shape[0]) if x.ndim > 1 else None
    xs = P(dp, *[None] * (x.ndim - 1))
    return _per_shard(local, (xs,) + (P(None),) * len(vecs), xs)(x, *vecs)


def _rms_ref(x, w, eps):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    return y.astype(x.dtype)


def _pallas_ok(x):
    from . import interpret_mode, use_pallas

    return (use_pallas() or interpret_mode()) and x.shape[-1] % 128 == 0


def _dispatch(kernel, core, eps, *arrays):
    """The entry of a norm kernel: count the call, then run ``core``
    inlined (the reference branch, or a caller already under an outer
    trace) or as the cached eager program."""
    from . import eager_call, record_dispatch

    ok = _pallas_ok(arrays[0])
    record_dispatch(kernel, ok)
    if not ok or any(isinstance(a, jax.core.Tracer) for a in arrays):
        return core(*arrays, eps)
    return eager_call(kernel, core, (eps,), *arrays)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_norm_core(x, w, eps):
    from . import interpret_mode

    if _pallas_ok(x):
        def local(x, *w):
            out = _rms_pallas(x.reshape(-1, x.shape[-1]),
                              w[0] if w else None, eps,
                              interpret=interpret_mode())
            return out.reshape(x.shape)

        return _rows_per_shard(local, x, [] if w is None else [w])
    return _rms_ref(x, w, eps)


def _rms_fwd(x, w, eps):
    return _rms_norm_core(x, w, eps), (x, w)


def _rms_bwd(eps, res, g):
    x, w = res

    def ref(x_, w_):
        return (
            _rms_ref(x_, w_, eps).astype(jnp.float32)
            if w_ is not None
            else _rms_ref(x_, None, eps).astype(jnp.float32)
        )

    if w is None:
        _, vjp = jax.vjp(lambda a: _rms_ref(a, None, eps), x)
        (dx,) = vjp(g)
        return dx, None
    _, vjp = jax.vjp(lambda a, ww: _rms_ref(a, ww, eps), x, w)
    dx, dw = vjp(g)
    return dx, dw


_rms_norm_core.defvjp(_rms_fwd, _rms_bwd)


def rms_norm(x, weight=None, eps=1e-6):
    """rms_norm over the last axis. x: [..., H], weight: [H] or None."""
    return _dispatch("rms_norm", _rms_norm_core, float(eps), x, weight)


def _ln_kernel(eps, has_w, has_b, x_ref, *refs):
    idx = 0
    w_ref = b_ref = None
    refs = list(refs)
    o_ref = refs.pop()
    if has_w:
        w_ref = refs[idx]
        idx += 1
    if has_b:
        b_ref = refs[idx]
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    if has_w:
        y = y * w_ref[:].astype(jnp.float32)
    if has_b:
        y = y + b_ref[:].astype(jnp.float32)
    o_ref[:] = y.astype(o_ref.dtype)


def _ln_ref(x, weight, bias, eps):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _layer_norm_core(x, weight, bias, eps):
    from . import interpret_mode

    h = x.shape[-1]
    if not _pallas_ok(x):
        return _ln_ref(x, weight, bias, eps)

    def local(x, *vecs):
        out = _row_tiled_call(
            functools.partial(_ln_kernel, eps, weight is not None,
                              bias is not None),
            x.reshape(-1, h), vecs, interpret_mode(), "layer_norm")
        return out.reshape(x.shape)

    return _rows_per_shard(local, x,
                           [a for a in (weight, bias) if a is not None])


def _ln_fwd(x, weight, bias, eps):
    return _layer_norm_core(x, weight, bias, eps), (x, weight, bias)


def _ln_bwd(eps, res, g):
    x, weight, bias = res
    diff = [x] + [a for a in (weight, bias) if a is not None]

    def f(*aa):
        it = iter(aa)
        xx = next(it)
        ww = next(it) if weight is not None else None
        bb = next(it) if bias is not None else None
        return _ln_ref(xx, ww, bb, eps)

    _, vjp = jax.vjp(f, *diff)
    grads = list(vjp(g))
    dx = grads.pop(0)
    dw = grads.pop(0) if weight is not None else None
    db = grads.pop(0) if bias is not None else None
    return dx, dw, db


_layer_norm_core.defvjp(_ln_fwd, _ln_bwd)


def layer_norm_fused(x, weight=None, bias=None, eps=1e-5):
    """Pallas fused layer_norm over the last axis (fwd); XLA autodiff
    bwd via the reference formula (pallas_call itself has no transpose
    rule, so reverse-mode MUST go through the custom VJP)."""
    return _dispatch("layer_norm_fused", _layer_norm_core, float(eps),
                     x, weight, bias)
