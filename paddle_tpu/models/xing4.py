"""Xing4.0: a latent-attention (MLA), sparse-expert decoder whose residual
path is ``hc_mult`` streams mixed by manifold-constrained hyper-connections
(mHC), with a multi-token-prediction module.

Upstream ecosystem analog: PaddleNLP's DeepSeek-V3 modelling (MLA, the
``noaux_tc`` sigmoid router with a shared expert, MTP); the residual path
follows "mHC: Manifold-Constrained Hyper-Connections". Layer by layer
(all norms RMSNorm):

* **Residual state** X [n, C] a token (n = ``hc_mult``). At each of a
  layer's two sites (attention, feed-forward), with the site's own phi, b,
  alpha: ``x~ = RMSNorm(vec(X))`` without gain; ``Hpre = sigmoid(a_pre
  x~ phi_pre + b_pre)`` [n], ``Hpost = 2 sigmoid(a_post x~ phi_post +
  b_post)`` [n], ``Hres = Sinkhorn(exp(clamp(a_res mat(x~ phi_res) +
  b_res)))`` [n, n] (``hc_sinkhorn_iters`` rounds of row then column
  normalisation, ``hc_eps`` in the denominators). The site computes ``y =
  F(norm(Hpre X))`` on ONE stream and ``X <- Hres X + Hpost^T y``.
  Coefficients in float32. The embedding is copied into the n streams; a
  last ``Hpre``-style read reduces them before the final norm.
* **Attention** (MLA): low-rank query (``q_lora_rank``) and one shared
  latent ``c_kv`` (``kv_lora_rank``) with one shared rotary key
  (``qk_rope_head_dim``, YaRN, rotate-half form). ``mla_query`` /
  ``mla_latent`` give the ABSORBED operands the latent page pool serves
  from (the cache holds ``[c_kv | k_rope]`` a token); ``mla_expanded`` is
  the plain form the model's own forward uses.
* **Feed-forward**: ``first_k_dense_replace`` leading SwiGLU layers, then
  ``DroplessMoE`` (sigmoid router, top-k, shared expert, no capacity).
* **MTP**: ``h' = [norm(h_t) ; norm(Emb(id_{t+1}))] W``, one expert layer,
  the shared head.

Every function of the mathematics is a pure ``jax.numpy`` function of
arrays, so that the serving adapter (inference/paged_xing4.py) compiles
one program per site and shape over the same code the layers run.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from ..framework.core import Tensor, apply_op
from ..incubate.distributed.models.moe.dropless import (
    DroplessMoE, dropless_moe, swiglu)
from ..nn import initializer as I
from ..nn.layer.layers import Layer

__all__ = ["Xing4Config", "Xing4ForCausalLM", "Xing4Model",
           "Xing4DecoderLayer", "Xing4MTP", "xing4_29b_a4b", "xing4_tiny",
           "yarn_rope_tables", "mla_softmax_scale", "sinkhorn",
           "hc_coefficients", "hc_pre", "hc_post", "hc_reduce"]


@dataclass
class Xing4Config:
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 2
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    norm_topk_prob: bool = True
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    hc_alpha_init: float = 0.01
    num_nextn_predict_layers: int = 1
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: dict = field(default_factory=lambda: {
        "type": "yarn", "factor": 64, "original_max_position_embeddings":
        4096, "beta_fast": 32, "beta_slow": 1, "mscale": 1,
        "mscale_all_dim": 1})
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    dtype: str = "float32"

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self):
        """Numbers a token and layer in the cache: c_kv and the rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def is_moe_layer(self, i):
        return i >= self.first_k_dense_replace


def xing4_29b_a4b(**kw) -> Xing4Config:
    """The published sizes of XingChen-AGI/Xing4.0-29B-A4B."""
    return Xing4Config(**kw)


def xing4_tiny(**kw) -> Xing4Config:
    """Every mechanism at a size the CPU tests run in seconds."""
    base = dict(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=3,
        first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=24,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
        hc_mult=4, num_nextn_predict_layers=1,
        max_position_embeddings=512,
        rope_scaling={"type": "yarn", "factor": 4,
                      "original_max_position_embeddings": 64,
                      "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                      "mscale_all_dim": 1})
    base.update(kw)
    return Xing4Config(**base)


# --------------------------------------------------------------------------
# the mathematics, as pure functions of arrays
# --------------------------------------------------------------------------
def rms(x, w, eps):
    """RMSNorm in float32, back in x's type; ``w`` None: no gain."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    return y.astype(x.dtype)


def _yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def mla_softmax_scale(cfg) -> float:
    """qk_head_dim^-0.5, times YaRN's mscale(factor, mscale_all_dim)^2."""
    rs = cfg.rope_scaling or {}
    m = _yarn_mscale(rs.get("factor", 1), rs.get("mscale_all_dim", 0)) \
        if rs.get("mscale_all_dim") else 1.0
    return cfg.qk_head_dim ** -0.5 * m * m


def yarn_rope_tables(cfg, length):
    """(cos, sin) [length, qk_rope_head_dim] float32 in the rotate-half
    form: YaRN's blend of the interpolated and the original frequencies
    between the correction dims of beta_fast and beta_slow, the tables
    scaled by mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    d, base = cfg.qk_rope_head_dim, float(cfg.rope_theta)
    rs = cfg.rope_scaling or {}
    i = jnp.arange(0, d, 2, dtype=jnp.float32)
    extra = 1.0 / base ** (i / d)
    attn = 1.0
    if rs.get("type") == "yarn":
        factor = float(rs["factor"])
        orig = rs["original_max_position_embeddings"]

        def corr(rot):
            return d * math.log(orig / (rot * 2 * math.pi)) \
                / (2 * math.log(base))

        lo = max(math.floor(corr(rs["beta_fast"])), 0)
        hi = min(math.ceil(corr(rs["beta_slow"])), d - 1)
        ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - lo)
                        / max(hi - lo, 1e-3), 0.0, 1.0)
        inv = extra / factor * ramp + extra * (1.0 - ramp)
        attn = _yarn_mscale(factor, rs.get("mscale", 1)) \
            / _yarn_mscale(factor, rs.get("mscale_all_dim", 0))
    else:
        inv = extra
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang) * attn, jnp.sin(ang) * attn


def rope_at(x, cos, sin, pos):
    """x [..., N, heads, d] or [..., N, d] rotated at positions pos [N]
    (rotate-half), in float32, back in x's type."""
    c, s = cos[pos], sin[pos]
    if x.ndim == c.ndim + 1:
        c, s = c[..., None, :], s[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, -1)
    return (x32 * c + jnp.concatenate([-x2, x1], -1) * s).astype(x.dtype)


def sinkhorn(m, iters, eps):
    """Rows then columns of a positive [..., n, n] normalised ``iters``
    times, ``eps`` in every denominator: doubly stochastic in the limit."""
    def body(_, a):
        a = a / (jnp.sum(a, -1, keepdims=True) + eps)
        return a / (jnp.sum(a, -2, keepdims=True) + eps)
    return jax.lax.fori_loop(0, iters, body, m)


def _hc_projection(xs, phi, cfg):
    """x~ phi in float32: the state xs [N, n, C] flattened, normed without
    a gain over all n x C numbers, times a site's phi [nC, m]."""
    flat = rms(xs.reshape(xs.shape[0], -1).astype(jnp.float32), None,
               cfg.rms_norm_eps)
    return jnp.matmul(flat, phi.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def hc_coefficients(xs, phi, b, alpha, cfg):
    """The three coefficient sets of one site from the state xs [N, n, C]:
    (Hpre [N, n], Hpost [N, n], Hres [N, n, n]), float32. phi [nC, 2n +
    n^2], b [2n + n^2], alpha [3] (pre, post, res)."""
    with jax.named_scope("mhc"):
        n = xs.shape[-2]
        t = _hc_projection(xs, phi, cfg)
        a, b = alpha.astype(jnp.float32), b.astype(jnp.float32)
        pre = jax.nn.sigmoid(a[0] * t[:, :n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * t[:, n:2 * n] + b[n:2 * n])
        res = (a[2] * t[:, 2 * n:] + b[2 * n:]).reshape(-1, n, n)
        res = jnp.exp(jnp.clip(res, cfg.mhc_h_res_clamp_min,
                               cfg.mhc_h_res_clamp_max))
        return pre, post, sinkhorn(res, cfg.hc_sinkhorn_iters, cfg.hc_eps)


def hc_pre(xs, phi, b, alpha, norm_w, cfg):
    """A site's read: (the one stream F sees, normed, [N, C]; Hpost; Hres)."""
    with jax.named_scope("mhc"):
        pre, post, res = hc_coefficients(xs, phi, b, alpha, cfg)
        h = jnp.einsum("ni,nic->nc", pre, xs.astype(jnp.float32))
        return rms(h, norm_w, cfg.rms_norm_eps).astype(xs.dtype), post, res


def hc_post(xs, y, post, res):
    """A site's write: X <- Hres X + Hpost^T y, in float32."""
    with jax.named_scope("mhc"):
        out = jnp.einsum("nij,njc->nic", res, xs.astype(jnp.float32)) \
            + post[:, :, None] * y.astype(jnp.float32)[:, None, :]
        return out.astype(xs.dtype)


def hc_reduce(xs, phi, b, alpha, cfg):
    """The last read: the streams to one, [N, C]. phi [nC, n], b [n],
    alpha [1]."""
    with jax.named_scope("mhc"):
        pre = jax.nn.sigmoid(
            alpha.astype(jnp.float32)[0] * _hc_projection(xs, phi, cfg)
            + b.astype(jnp.float32))
        return jnp.einsum("ni,nic->nc", pre,
                          xs.astype(jnp.float32)).astype(xs.dtype)


def mla_query(h, w_qa, qa_norm, w_qb, cfg):
    """h [N, C] -> (q_nope [N, H, nope], q_rope [N, H, rope]) unrotated."""
    nh = cfg.num_attention_heads
    cq = rms(jnp.matmul(h, w_qa), qa_norm, cfg.rms_norm_eps)
    q = jnp.matmul(cq, w_qb).reshape(h.shape[0], nh, cfg.qk_head_dim)
    return q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]


def mla_latent(h, w_kva, kva_norm, cos, sin, pos, cfg):
    """h [N, C] -> what the cache holds of each token, [N, kv_lora_rank +
    rope]: the normed latent and the rotated shared rope key."""
    r = cfg.kv_lora_rank
    kv = jnp.matmul(h, w_kva)
    c = rms(kv[:, :r], kva_norm, cfg.rms_norm_eps)
    return jnp.concatenate([c, rope_at(kv[:, r:], cos, sin, pos)], -1)


def mla_absorbed_query(h, w_qa, qa_norm, w_qb, w_kvb, cos, sin, pos, cfg):
    """The query of the absorbed form, [N, H, kv_lora_rank + rope]:
    q_nope W_UK^T beside the rotated rope part, so that its dot product
    with a cached ``[c_kv | k_rope]`` row is the expanded score."""
    nh, r = cfg.num_attention_heads, cfg.kv_lora_rank
    qn, qr = mla_query(h, w_qa, qa_norm, w_qb, cfg)
    w_uk = w_kvb.reshape(r, nh, -1)[:, :, :cfg.qk_nope_head_dim]
    q_lat = jnp.einsum("nhd,rhd->nhr", qn, w_uk,
                       preferred_element_type=jnp.float32).astype(h.dtype)
    return jnp.concatenate([q_lat, rope_at(qr, cos, sin, pos)], -1)


def mla_output(o_lat, w_kvb, w_o, cfg):
    """o_lat [N, H, kv_lora_rank] -> (o_lat W_UV) W_o, [N, C]."""
    nh, r = cfg.num_attention_heads, cfg.kv_lora_rank
    w_uv = w_kvb.reshape(r, nh, -1)[:, :, cfg.qk_nope_head_dim:]
    o = jnp.einsum("nhr,rhd->nhd", o_lat, w_uv,
                   preferred_element_type=jnp.float32).astype(o_lat.dtype)
    return jnp.matmul(o.reshape(o.shape[0], -1), w_o)


def mla_expanded(h, pos, attn_w, cos, sin, cfg):
    """Plain causal MLA over whole sequences h [B, S, C], pos [S]:
    k_nope and v expanded from the latent, float32 softmax."""
    w_qa, qa_norm, w_qb, w_kva, kva_norm, w_kvb, w_o = attn_w
    b, s, c = h.shape
    nh, r, dn = cfg.num_attention_heads, cfg.kv_lora_rank, \
        cfg.qk_nope_head_dim
    flat = h.reshape(b * s, c)
    posf = jnp.tile(pos, b)
    qn, qr = mla_query(flat, w_qa, qa_norm, w_qb, cfg)
    qr = rope_at(qr, cos, sin, posf)
    lat = mla_latent(flat, w_kva, kva_norm, cos, sin, posf, cfg)
    kv = jnp.matmul(lat[:, :r], w_kvb).reshape(b, s, nh, -1)
    kn, v = kv[..., :dn], kv[..., dn:]
    kr = lat[:, r:].reshape(b, s, -1)
    qn, qr = qn.reshape(b, s, nh, -1), qr.reshape(b, s, nh, -1)
    f32 = jnp.float32
    sc = (jnp.einsum("bshd,bthd->bhst", qn.astype(f32), kn.astype(f32))
          + jnp.einsum("bshd,btd->bhst", qr.astype(f32), kr.astype(f32))) \
        * mla_softmax_scale(cfg)
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(mask, sc, -1e30), -1)
    o = jnp.einsum("bhst,bthd->bshd", p, v.astype(f32)).astype(h.dtype)
    return jnp.matmul(o.reshape(b, s, -1), w_o)


def layer_forward(xs, pos, w, cos, sin, cfg):
    """One decoder layer on the state xs [B, S, n, C]; ``w`` is the dict
    ``Xing4DecoderLayer.arrays()`` gives. Returns (xs, expert counts or
    None)."""
    b, s, n, c = xs.shape
    flat = xs.reshape(b * s, n, c)
    h, post, res = hc_pre(flat, *w["attn_hc"], w["ln1"], cfg)
    y = mla_expanded(h.reshape(b, s, c), pos, w["attn"], cos, sin, cfg)
    flat = hc_post(flat, y.reshape(b * s, c), post, res)
    h, post, res = hc_pre(flat, *w["ffn_hc"], w["ln2"], cfg)
    if "moe" in w:
        y, sizes = dropless_moe(
            h, *w["moe"], w["shared"], cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.norm_topk_prob)
    else:
        y, sizes = swiglu(h, *w["mlp"]), None
    return hc_post(flat, y, post, res).reshape(b, s, n, c), sizes


# --------------------------------------------------------------------------
# the layers
# --------------------------------------------------------------------------
def _raw(p):
    return p._data


class _Weight(Layer):
    """One matrix or gain under the published parameter name
    ``<path>.weight``."""

    def __init__(self, shape, init, dtype):
        super().__init__(dtype=dtype)
        self.weight = self.create_parameter(shape, default_initializer=init)


class HyperConnection(Layer):
    """phi, b and alpha of one mHC site (``width`` = 2n + n^2) or of the
    final read (``width`` = n, one alpha)."""

    def __init__(self, cfg, final=False):
        super().__init__(dtype=cfg.dtype)
        n = cfg.hc_mult
        width = n if final else 2 * n + n * n
        self.phi = self.create_parameter(
            [n * cfg.hidden_size, width],
            default_initializer=I.Normal(0.0, cfg.initializer_range))
        self.b = self.create_parameter(
            [width], default_initializer=I.Constant(0.0))
        self.alpha = self.create_parameter(
            [1 if final else 3],
            default_initializer=I.Constant(cfg.hc_alpha_init))

    def arrays(self, get=_raw):
        return (get(self.phi), get(self.b), get(self.alpha))


class Xing4Attention(Layer):
    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        c, nh = cfg.hidden_size, cfg.num_attention_heads
        init, one = I.Normal(0.0, cfg.initializer_range), I.Constant(1.0)
        mk = lambda shape, i=init: _Weight(shape, i, cfg.dtype)  # noqa: E731
        self.q_a_proj = mk([c, cfg.q_lora_rank])
        self.q_a_layernorm = mk([cfg.q_lora_rank], one)
        self.q_b_proj = mk([cfg.q_lora_rank, nh * cfg.qk_head_dim])
        self.kv_a_proj_with_mqa = mk([c, cfg.latent_dim])
        self.kv_a_layernorm = mk([cfg.kv_lora_rank], one)
        self.kv_b_proj = mk([cfg.kv_lora_rank, nh * (
            cfg.qk_nope_head_dim + cfg.v_head_dim)])
        self.o_proj = mk([nh * cfg.v_head_dim, c])

    def arrays(self, get=_raw):
        return tuple(get(m.weight) for m in (
            self.q_a_proj, self.q_a_layernorm, self.q_b_proj,
            self.kv_a_proj_with_mqa, self.kv_a_layernorm, self.kv_b_proj,
            self.o_proj))


class Xing4MLP(Layer):
    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        c, f = cfg.hidden_size, cfg.intermediate_size
        init = I.Normal(0.0, cfg.initializer_range)
        self.gate_proj = _Weight([c, f], init, cfg.dtype)
        self.up_proj = _Weight([c, f], init, cfg.dtype)
        self.down_proj = _Weight([f, c], init, cfg.dtype)

    def arrays(self, get=_raw):
        return (get(self.gate_proj.weight), get(self.up_proj.weight),
                get(self.down_proj.weight))


class Xing4DecoderLayer(Layer):
    """Two mHC sites around MLA and a dense or routed feed-forward."""

    def __init__(self, cfg, layer_idx):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        one = I.Constant(1.0)
        self.attn_hc = HyperConnection(cfg)
        self.input_layernorm = _Weight([cfg.hidden_size], one, cfg.dtype)
        self.self_attn = Xing4Attention(cfg)
        self.ffn_hc = HyperConnection(cfg)
        self.post_attention_layernorm = _Weight(
            [cfg.hidden_size], one, cfg.dtype)
        self.is_moe = cfg.is_moe_layer(layer_idx)
        if self.is_moe:
            self.mlp = DroplessMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                num_shared=cfg.n_shared_experts,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_topk_prob=cfg.norm_topk_prob,
                init_std=cfg.initializer_range, dtype=cfg.dtype)
        else:
            self.mlp = Xing4MLP(cfg)

    def arrays(self, get=_raw):
        """The layer's arrays, grouped as ``layer_forward`` and the
        serving adapter take them; ``get`` maps a parameter to the array
        that stands for it (its own, or a traced one)."""
        w = {"attn_hc": self.attn_hc.arrays(get),
             "ln1": get(self.input_layernorm.weight),
             "attn": self.self_attn.arrays(get),
             "ffn_hc": self.ffn_hc.arrays(get),
             "ln2": get(self.post_attention_layernorm.weight)}
        if self.is_moe:
            w["moe"] = tuple(get(p) for p in self.mlp.routed_params())
            w["shared"] = tuple(get(p) for p in self.mlp.shared_params()) \
                or None
        else:
            w["mlp"] = self.mlp.arrays(get)
        return w

    def forward(self, xs, pos, cos, sin):
        """xs [B, S, n, C] Tensor -> the same. One differentiable op over
        the layer's parameters."""
        params = [p for _, p in self.named_parameters()]
        cfg, layer = self.cfg, self

        def f(x, *raw):
            given = {id(p): r for p, r in zip(params, raw)}
            w = layer.arrays(lambda p: given[id(p)])
            return layer_forward(x, pos, w, cos, sin, cfg)[0]

        return apply_op("xing4_layer", f, xs, *params)


class Xing4Model(Layer):
    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        init = I.Normal(0.0, cfg.initializer_range)
        self.embed_tokens = _Weight(
            [cfg.vocab_size, cfg.hidden_size], init, cfg.dtype)
        self.layers = []
        for i in range(cfg.num_hidden_layers):
            layer = Xing4DecoderLayer(cfg, i)
            self.add_sublayer(f"layers.{i}", layer)
            self.layers.append(layer)
        self.final_hc = HyperConnection(cfg, final=True)
        self.norm = _Weight([cfg.hidden_size], I.Constant(1.0), cfg.dtype)
        self._rope = None

    def rope(self, length=None):
        """YaRN tables up to ``length`` (all declared positions when
        None), built once for the longest length asked for."""
        length = int(length or self.cfg.max_position_embeddings)
        if self._rope is None or self._rope[0].shape[0] < length:
            self._rope = yarn_rope_tables(self.cfg, length)
        return self._rope

    def streams(self, input_ids, pos):
        """ids [B, S] -> the n-stream state after every layer, a Tensor
        [B, S, n, C]."""
        cfg = self.cfg
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        cos, sin = self.rope(int(ids.shape[1]))
        n = cfg.hc_mult
        xs = apply_op(
            "xing4_embed",
            lambda e: jnp.repeat(e[ids][:, :, None, :], n, 2),
            self.embed_tokens.weight)
        for layer in self.layers:
            xs = layer(xs, pos, cos, sin)
        return xs

    def forward(self, input_ids):
        """ids [B, S] -> the reduced hidden state BEFORE the final norm
        [B, S, C] (what the MTP module takes)."""
        cfg = self.cfg
        s = int(input_ids.shape[1])
        xs = self.streams(input_ids, jnp.arange(s))

        def f(x, phi, b, alpha):
            bb, ss, n, c = x.shape
            return hc_reduce(x.reshape(bb * ss, n, c), phi, b, alpha,
                             cfg).reshape(bb, ss, c)

        fh = self.final_hc
        return apply_op("xing4_reduce", f, xs, fh.phi, fh.b, fh.alpha)


class Xing4MTP(Layer):
    """One multi-token-prediction module: the main model's reduced state
    at t and the embedding of token t+1, each normed, concatenated and
    projected to C; copied into n streams, one expert layer, the streams
    reduced by the module's own read. Embedding, final norm and head are
    the main model's."""

    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        c, one = cfg.hidden_size, I.Constant(1.0)
        self.enorm = _Weight([c], one, cfg.dtype)
        self.hnorm = _Weight([c], one, cfg.dtype)
        self.eh_proj = _Weight(
            [2 * c, c], I.Normal(0.0, cfg.initializer_range), cfg.dtype)
        self.layer = Xing4DecoderLayer(cfg, cfg.first_k_dense_replace)
        self.final_hc = HyperConnection(cfg, final=True)

    def forward(self, hidden, next_ids, embed_weight, cos, sin):
        """hidden [B, S, C] (pre-norm, at t), next_ids [B, S] (token
        t+1) -> the module's reduced state [B, S, C], pre-norm."""
        cfg = self.cfg
        ids = next_ids._data if isinstance(next_ids, Tensor) \
            else jnp.asarray(next_ids)
        eps, n = cfg.rms_norm_eps, cfg.hc_mult

        def f(h, emb, en, hn, w):
            e = rms(emb[ids], en, eps)
            x = jnp.matmul(jnp.concatenate([rms(h, hn, eps), e], -1), w)
            return jnp.repeat(x[:, :, None, :], n, 2)

        xs = apply_op("xing4_mtp_in", f, hidden, embed_weight,
                      self.enorm.weight, self.hnorm.weight,
                      self.eh_proj.weight)
        xs = self.layer(xs, jnp.arange(ids.shape[1]), cos, sin)

        def g(x, phi, b, alpha):
            bb, ss, nn, c = x.shape
            return hc_reduce(x.reshape(bb * ss, nn, c), phi, b, alpha,
                             cfg).reshape(bb, ss, c)

        fh = self.final_hc
        return apply_op("xing4_reduce", g, xs, fh.phi, fh.b, fh.alpha)


class Xing4ForCausalLM(Layer):
    def __init__(self, cfg):
        super().__init__(dtype=cfg.dtype)
        self.config = cfg
        self.model = Xing4Model(cfg)
        self.lm_head = _Weight(
            [cfg.hidden_size, cfg.vocab_size],
            I.Normal(0.0, cfg.initializer_range), cfg.dtype)
        self.mtp = []
        for k in range(cfg.num_nextn_predict_layers):
            m = Xing4MTP(cfg)
            self.add_sublayer(f"mtp.{k}", m)
            self.mtp.append(m)

    def head(self, hidden):
        """Final norm and output head over a reduced state [..., C]."""
        eps = self.config.rms_norm_eps
        return apply_op(
            "xing4_head",
            lambda h, g, w: jnp.matmul(rms(h, g, eps), w),
            hidden, self.model.norm.weight, self.lm_head.weight)

    def forward(self, input_ids):
        """ids [B, S] -> logits [B, S, V]."""
        return self.head(self.model(input_ids))

    def mtp_logits(self, input_ids, k=0):
        """Logits [B, S-1, V] of module ``k``: position t, fed the main
        model's state at t and token t+1, predicts token t+2."""
        hidden = self.model(input_ids)
        s = int(input_ids.shape[1])
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        cos, sin = self.model.rope(s)
        h = apply_op("xing4_slice", lambda a: a[:, :-1], hidden)
        out = self.mtp[k](h, ids[:, 1:], self.model.embed_tokens.weight,
                          cos, sin)
        return self.head(out)
