"""Plain reference of the decoder the cells run: float32 ``jax.numpy``,
matmuls at ``highest`` precision, no kernels, no cache, no batching tricks.
It follows the published Mistral/Llama block (pre-norm RMSNorm, rotary
embedding in the rotate-half form, grouped-query causal attention with a
sliding window, SwiGLU, untied head). It imports nothing of the program
and makes its own weights from the seed (weights.py).

``mode="int8"`` is the control: the same arithmetic with the operands of
every projection, MLP and head matmul, forward and backward, rounded to
int8 levels (per token for activations and gradients, per output column
for weights), the precision one step below the bfloat16 the configurations
state.

Memory: serving runs layer by layer over a block of sequences; training
rematerialises each layer and runs attention one group of heads at a time.
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


def layer(x, lw, pos, cfg, mode):
    """One decoder block. x [B, S, H] float32."""
    b, s, _ = x.shape
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = rms(x, lw["ln1"], eps)
    q = rope(mm(h, lw["q"], mode).reshape(b, s, nh, d), pos, theta)
    k = rope(mm(h, lw["k"], mode).reshape(b, s, nkv, d), pos, theta)
    v = mm(h, lw["v"], mode).reshape(b, s, nkv, d)
    a = attention(q, k, v, int(cfg.get("sliding_window") or 0))
    x = x + mm(a.reshape(b, s, nh * d), lw["o"], mode)
    h = rms(x, lw["ln2"], eps)
    return x + mm(jax.nn.silu(mm(h, lw["gate"], mode))
                  * mm(h, lw["up"], mode), lw["down"], mode)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _static_cfg(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


# --------------------------------------------------------------------------
# serving: teacher-forced logits over prompt + served tokens
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _serve_fns(cfg_key, mode):
    cfg = dict(cfg_key)

    @jax.jit
    def embed(emb, ids):
        return emb.astype(jnp.float32)[ids]

    @jax.jit
    def one_layer(x, lw):
        pos = jnp.arange(x.shape[1])
        return layer(x, _f32(lw), pos, cfg, mode)

    @jax.jit
    def final(x, norm, head, gather):
        """best logit, argmax and the logits of ``gather`` [B, S, G] at
        every position, one sequence at a time (the [S, V] logits of a
        block never all exist at once)."""
        norm, head = norm.astype(jnp.float32), head.astype(jnp.float32)

        def one(args):
            xs, gs = args
            lg = mm(rms(xs, norm, cfg["rms_norm_eps"]), head, mode)  # S V
            return (lg.max(-1), lg.argmax(-1).astype(jnp.int32),
                    jnp.take_along_axis(lg, gs, -1))
        return jax.lax.map(one, (x, gather))
    return embed, one_layer, final


def serve_logits(cfg, seed, ids, gather, mode="f32"):
    """ids [B, S] int32 (padded on the right; causal, so padding never
    reaches a real position); gather [B, S, G] token ids whose logits are
    wanted at each position. Returns numpy (best [B,S], argmax [B,S],
    gathered [B,S,G]) of the logits that predict position s+1."""
    embed, one_layer, final = _serve_fns(_static_cfg(cfg), mode)
    top = W.make_top(cfg, seed)
    x = embed(top["embed"], jnp.asarray(ids, jnp.int32))
    for li in range(cfg["num_hidden_layers"]):
        x = one_layer(x, W.make_layer(cfg, seed, li))
    best, arg, got = final(x, top["norm"], top["head"],
                           jnp.asarray(gather, jnp.int32))
    return np.asarray(best), np.asarray(arg), np.asarray(got)


# --------------------------------------------------------------------------
# training: loss, gradients and AdamW on float32 copies of the bf16 weights
# --------------------------------------------------------------------------
def lm_loss(params, ids, cfg, mode="f32", rows=None):
    """Mean next-token cross-entropy: logits[:, :-1] predict ids[:, 1:].
    ``rows`` keeps only those sequences (the half-batch fault)."""
    if rows is not None:
        ids = ids[jnp.asarray(rows)]
    pos = jnp.arange(ids.shape[1])
    x = params["embed"][ids]
    for lw in params["layers"]:
        x = jax.checkpoint(
            lambda x_, lw_: layer(x_, lw_, pos, cfg, mode))(x, lw)

    @jax.checkpoint
    def seq_loss(args):
        xs, ys = args
        lg = mm(rms(xs[:-1], params["norm"], cfg["rms_norm_eps"]),
                params["head"], mode)
        lse = jax.nn.logsumexp(lg, -1)
        return jnp.sum(lse - jnp.take_along_axis(lg, ys[1:, None], -1)[:, 0])

    tot = jnp.sum(jax.lax.map(seq_loss, (x, ids)))
    return tot / (ids.shape[0] * (ids.shape[1] - 1))


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


def tree_norms(tree):
    """{leaf path: l2 norm} with weights.py's leaf paths."""
    out = {n: jnp.sqrt(jnp.sum(jnp.square(tree[n].astype(jnp.float32))))
           for n in W.TOP_LEAVES}
    for i, lw in enumerate(tree["layers"]):
        for n in W.LAYER_LEAVES:
            out[f"layers.{i}.{n}"] = jnp.sqrt(
                jnp.sum(jnp.square(lw[n].astype(jnp.float32))))
    return out


def train_reference(cfg, hp, seed, batches, mode="f32", rows=None,
                    frozen=False):
    """Follow ``len(batches)`` optimizer steps from the seed's weights.
    Returns {"loss": [..], "grad1": {leaf: norm of the first gradient},
    "delta": {leaf: norm of (params after the steps - initial params)}}.
    ``rows`` and ``frozen`` plant faults for the tests: a part of the batch
    left out; a step that returns its state unchanged."""
    ckey = _static_cfg(cfg)
    cfgd = dict(ckey)

    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, ids: lm_loss(p, ids, cfgd, mode, rows)))
    hpd = {k: float(hp[k]) for k in
           ("learning_rate", "beta1", "beta2", "epsilon", "weight_decay")}

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def update(p, g, m, v, t):
        return adamw_leaf(p, g, m, v, t, hpd)

    def leaves(tree):
        return [(None, n) for n in W.TOP_LEAVES] + [
            (i, n) for i in range(len(tree["layers"]))
            for n in W.LAYER_LEAVES]

    def get(tree, i, n):
        return tree[n] if i is None else tree["layers"][i][n]

    def put(tree, i, n, val):
        if i is None:
            tree[n] = val
        else:
            tree["layers"][i][n] = val

    params = _f32(W.make_all(cfg, seed))
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    m, v = zeros(params), zeros(params)
    out = {"loss": [], "grad1": None, "delta": None}
    for t, ids in enumerate(batches, 1):
        loss, grads = grad_fn(params, jnp.asarray(ids, jnp.int32))
        out["loss"].append(float(loss))
        if t == 1:
            out["grad1"] = {k: float(x) for k, x in tree_norms(grads).items()}
        if frozen:
            del grads
            continue
        for i, n in leaves(params):
            p2, m2, v2 = update(get(params, i, n), get(grads, i, n),
                                get(m, i, n), get(v, i, n), float(t))
            put(params, i, n, p2)
            put(m, i, n, m2)
            put(v, i, n, v2)
            put(grads, i, n, None)
        del grads
    del m, v
    # the change, leaf by leaf, against the seed's weights made anew
    init = W.make_all(cfg, seed)
    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a - b.astype(jnp.float32)))))
    out["delta"] = {}
    for i, n in leaves(params):
        path = n if i is None else f"layers.{i}.{n}"
        out["delta"][path] = float(diff(get(params, i, n), get(init, i, n)))
    return out
