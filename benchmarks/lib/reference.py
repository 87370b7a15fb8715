"""What the plain reference of every family is made of: float32
``jax.numpy``, matmuls at ``highest`` precision, no kernels, no cache, no
batching tricks: the matmul with its int8 control, RMSNorm, the rotary
embedding in the rotate-half form, grouped-query causal attention with a
window, AdamW as published, and the loop that follows a family's loss
through its first optimizer steps. The blocks themselves, ``serve_logits``
and ``lm_loss`` are the family's (families/<family>.py). Nothing here
imports the program, and the weights come from the seed (weights.py).

``mode="int8"`` is the control: the same arithmetic with the operands of
every matmul that goes through ``mm``, forward and backward, rounded to
int8 levels (per token for activations and gradients, per output column
for weights), the precision one step below the bfloat16 the configurations
state.

Memory: ``attention`` runs one group of heads at a time.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

HI = jax.lax.Precision.HIGHEST


def _q8(x, axis):
    """Round to 255 symmetric levels along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


@jax.custom_vjp
def _mm_int8(a, b):
    """int8 training as a later PR would be tempted to do it: the forward
    matmul and both backward matmuls take int8 operands (rows of the
    activations and of the incoming gradient, columns of the weights)."""
    return jnp.matmul(_q8(a, -1), _q8(b, 0), precision=HI)


def _mm_int8_fwd(a, b):
    return _mm_int8(a, b), (a, b)


def _mm_int8_bwd(res, dy):
    a, b = res
    dq = _q8(dy, -1)
    da = jnp.matmul(dq, _q8(b, 0).T, precision=HI)
    a2, d2 = a.reshape(-1, a.shape[-1]), dq.reshape(-1, dq.shape[-1])
    db = jnp.matmul(_q8(a2, -1).T, d2, precision=HI)
    return da, db


_mm_int8.defvjp(_mm_int8_fwd, _mm_int8_bwd)


def mm(a, b, mode):
    """a [..., K] @ b [K, N] in float32."""
    if mode == "int8":
        return _mm_int8(a, b)
    return jnp.matmul(a, b, precision=HI)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x [B, S, heads, D]; pos [S] or [B, S]; rotate-half convention."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[..., None] * inv          # [..., S, D/2]
    ang = jnp.concatenate([ang, ang], -1)
    if ang.ndim == 2:
        ang = ang[None]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(q, k, v, window):
    """q [B, S, nh, D], k/v [B, S, nkv, D] -> [B, S, nh, D]. Causal, key j
    seen from i where 0 <= i - j < window (window 0: all). One (sequence,
    KV head) at a time so the scores never exceed [group, S, S]."""
    b, s, nh, d = q.shape
    nkv = k.shape[2]
    g = nh // nkv
    qg = q.reshape(b, s, nkv, g, d).transpose(0, 2, 3, 1, 4)   # b nkv g s d
    kg = k.transpose(0, 2, 1, 3)                               # b nkv s d
    vg = v.transpose(0, 2, 1, 3)
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    mask = j <= i
    if window:
        mask = mask & (i - j < window)

    @jax.checkpoint
    def one(args):
        qq, kk, vv = args                                       # g s d, s d
        sc = jnp.einsum("gsd,td->gst", qq, kk, precision=HI) / np.sqrt(d)
        sc = jnp.where(mask[None], sc, -1e30)
        p = jax.nn.softmax(sc, -1)
        return jnp.einsum("gst,td->gsd", p, vv, precision=HI)

    flat = (qg.reshape(b * nkv, g, s, d), kg.reshape(b * nkv, s, d),
            vg.reshape(b * nkv, s, d))
    out = jax.lax.map(one, flat)                                # bn g s d
    return out.reshape(b, nkv, g, s, d).transpose(0, 3, 1, 2, 4).reshape(
        b, s, nh, d)


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def adamw_leaf(p, g, m, v, t, hp):
    """Decoupled AdamW, bias-corrected, as published (Loshchilov & Hutter):
    decay first, then the Adam move. ``t`` is the 1-based step."""
    b1, b2 = hp["beta1"], hp["beta2"]
    p = p * (1.0 - hp["learning_rate"] * hp["weight_decay"])
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - b1 ** t)
    vh = v / (1 - b2 ** t)
    return p - hp["learning_rate"] * mh / (jnp.sqrt(vh) + hp["epsilon"]), m, v


def tree_norms(tree, paths):
    """{leaf path: l2 norm} of the leaves at ``paths``."""
    return {p: jnp.sqrt(jnp.sum(jnp.square(
        W.get_leaf(tree, p).astype(jnp.float32)))) for p in paths}


def train_reference(family, cfg, hp, seed, batches, mode="f32", rows=None,
                    frozen=False):
    """Follow ``len(batches)`` optimizer steps of the family's ``lm_loss``
    from the seed's weights. Returns {"loss": [..], "grad1": {leaf: norm of
    the first gradient}, "delta": {leaf: norm of (params after the steps -
    initial params)}}. ``rows`` and ``frozen`` plant faults for the tests: a
    part of the batch left out; a step that returns its state unchanged."""
    leaves = family.leaves(cfg)
    paths = list(leaves)
    spec = W.spec(leaves, family.LEAF_NAMES, cfg["initializer_range"])

    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, ids: family.lm_loss(p, ids, cfg, mode, rows)))
    hpd = {k: float(hp[k]) for k in
           ("learning_rate", "beta1", "beta2", "epsilon", "weight_decay")}

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def update(p, g, m, v, t):
        return adamw_leaf(p, g, m, v, t, hpd)

    get = W.get_leaf

    def put(tree, path, val):
        layer, name = W.split(path)
        (tree if layer < 0 else tree["layers"][layer])[name] = val

    params = f32(W.make_all(spec, seed))
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    m, v = zeros(params), zeros(params)
    out = {"loss": [], "grad1": None, "delta": None}
    for t, ids in enumerate(batches, 1):
        loss, grads = grad_fn(params, jnp.asarray(ids, jnp.int32))
        out["loss"].append(float(loss))
        if t == 1:
            out["grad1"] = {k: float(x)
                            for k, x in tree_norms(grads, paths).items()}
        if frozen:
            del grads
            continue
        for path in paths:
            p2, m2, v2 = update(get(params, path), get(grads, path),
                                get(m, path), get(v, path), float(t))
            put(params, path, p2)
            put(m, path, m2)
            put(v, path, v2)
            put(grads, path, None)
        del grads
    del m, v
    # the change, leaf by leaf, against the seed's weights made anew
    init = W.make_all(spec, seed)
    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a - b.astype(jnp.float32)))))
    out["delta"] = {path: float(diff(get(params, path), get(init, path)))
                    for path in paths}
    return out
