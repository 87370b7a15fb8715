"""EvaByte: a byte-level decoder whose attention reads the current window
exactly and everything behind it through one pooled row a chunk (EVA,
"Efficient Attention via Control Variates", in the simplified learned form
the published model ships), with ``num_pred_heads`` next-byte heads.

Upstream ecosystem analog: PaddleNLP's Llama modelling; the dense layer IS
Llama's (pre-norm RMSNorm, rotary embedding in the rotate-half form,
SwiGLU, untied head) but for three things. Per head (d = ``head_dim``, s =
d^-1/2; t a position, W = ``window_size``, C = ``chunk_size``; windows
and chunks are aligned blocks):

* **Norm** ``n = x / rms(x) * (1 + g)`` (``norm_add_unit_offset``: the
  gain is stored less one).
* **Chunk summaries.** Once chunk c's C tokens exist, with ``phi`` and
  ``mu`` learned, [heads, d] a layer: ``a_j = softmax_j(s phi . k_(cC+j))``
  over the chunk's rotated keys; the pooled key ``k~_c = sum_j a_j k_j +
  mu``, the pooled value ``v~_c = sum_j a_j v_j``
  (ops/kernels/paged_attention.pool_pages).
* **Attention of t** in window w = t // W: ONE softmax, float32 scores,
  over the exact keys ``w W .. t`` and the summaries of every chunk of
  every earlier window (``c < w W / C``).
* **Head**: ``num_pred_heads`` x ``vocab_size`` logits a position, laid
  out [head, vocabulary], float32 (``fp32_logits``); head 0 predicts byte
  t + 1, head i byte t + 1 + i.

So a served sequence holds its current window's K/V rows and one pooled
row a finished chunk, not a row a token: ``PagedLlamaAdapter`` reads
``window_size`` / ``chunk_size`` off this config and serves the model
from a ``page_format="eva"`` pool (incubate/nn/paged_cache.py). The
parameter paths are Llama's (``model.layers.<i>.self_attn.q_proj.weight``
...), with ``self_attn.phi`` / ``self_attn.mu`` beside them; the forward
here is the full-sequence masked form of the same equations.

Recalled from the paper and the released modelling code, not re-read: the
exact form of ``a_j`` (no ``-|k|^2 / 2`` term), ``mu`` added to the pooled
key and not to the value, aligned windows, the summary taken after the
rotary embedding, head 0 = next byte, the head's [pred_head, vocab]
layout.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..framework.core import Tensor, apply_op
from ..nn import initializer as I
from ..nn.layer.layers import Layer, LayerList, LazyGuard
from ..nn.layer.norm import RMSNorm
from ..nn.param_attr import ParamAttr
from ..ops.kernels.paged_attention import pool_pages
from ..ops.kernels.rope import apply_rotary_emb, build_rope_cache
from .llama import LlamaMLP

__all__ = ["EvaByteConfig", "EvaByteForCausalLM", "EvaByteModel",
           "EvaByteRMSNorm", "evabyte_6_5b", "evabyte_tiny",
           "eva_attention"]


@dataclass
class EvaByteConfig:
    vocab_size: int = 320
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    num_pred_heads: int = 8
    window_size: int = 2048
    chunk_size: int = 16
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-5
    rope_theta: float = 100000.0
    init_std: float = 0.01275
    attention_class: str = "eva"
    norm_add_unit_offset: bool = True
    fp32_logits: bool = True
    fp32_skip_add: bool = True
    mixedp_attn: bool = True
    tie_word_embeddings: bool = False
    dtype: str = "float32"

    def __post_init__(self):
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                "EvaByte is multi-head: a pooled row is a head's own "
                f"(num_key_value_heads={self.num_key_value_heads}, "
                f"num_attention_heads={self.num_attention_heads})")
        if self.window_size % self.chunk_size:
            raise ValueError(
                f"window_size={self.window_size} holds whole chunks of "
                f"chunk_size={self.chunk_size}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def evabyte_6_5b(**kw) -> EvaByteConfig:
    """The published EvaByte (6.5 B parameters)."""
    return EvaByteConfig(**kw)


def evabyte_tiny(**kw) -> EvaByteConfig:
    """Every mechanism at a size the CPU tests run in seconds: a window
    of 64 in chunks of 8, so that a few hundred tokens cross several
    windows and a window's 8 summary rows fill one page of 8 (the pool
    shows summaries a whole page at a time: window_size a multiple of
    chunk_size squared, as the published 2048 / 16 is)."""
    base = dict(vocab_size=64, hidden_size=128, intermediate_size=192,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=4, num_pred_heads=8, window_size=64,
                chunk_size=8, max_position_embeddings=512)
    base.update(kw)
    return EvaByteConfig(**base)


def eva_attention(q, k, v, phi, mu, window, chunk):
    """The full-sequence masked form. q, k, v [B, S, H, D], rotated; phi,
    mu [H, D]. One float32 softmax a query over the exact keys of its own
    window (causal) and the pooled row of every whole chunk of every
    earlier window. Returns float32 [B, S, H, D]."""
    b, s, h, d = q.shape
    scale = d ** -0.5
    hi = jax.lax.Precision.HIGHEST
    qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))
    pos = jnp.arange(s)
    exact = (pos[None, :] <= pos[:, None]) \
        & (pos[None, :] // window == pos[:, None] // window)
    sc = jnp.einsum("bihd,bjhd->bhij", qf, kf, precision=hi) * scale
    sc = jnp.where(exact[None, None], sc, -jnp.inf)
    nc = s // chunk
    if nc:
        kt, vt = pool_pages(
            kf[:, :nc * chunk].reshape(b * nc, chunk, h, d),
            vf[:, :nc * chunk].reshape(b * nc, chunk, h, d), phi, mu, scale)
        kt, vt = kt.reshape(b, nc, h, d), vt.reshape(b, nc, h, d)
        seen = (jnp.arange(nc)[None, :] * chunk) // window \
            < pos[:, None] // window
        ss = jnp.einsum("bihd,bchd->bhic", qf, kt, precision=hi) * scale
        sc = jnp.concatenate(
            [sc, jnp.where(seen[None, None], ss, -jnp.inf)], -1)
        vf = jnp.concatenate([vf, vt], 1)
    p = jax.nn.softmax(sc, -1)
    return jnp.einsum("bhij,bjhd->bihd", p, vf, precision=hi)


class EvaByteRMSNorm(RMSNorm):
    """``x / rms(x) * (1 + g)``: the gain is stored less one."""

    unit_offset = True

    def __init__(self, hidden_size, epsilon):
        super().__init__(hidden_size, epsilon=epsilon, weight_attr=ParamAttr(
            initializer=I.Constant(0.0)))

    def forward(self, x):
        eps = self._epsilon

        def f(a, g):
            af = a.astype(jnp.float32)
            y = af * jax.lax.rsqrt(jnp.mean(af * af, -1, keepdims=True) + eps)
            return (y * (1.0 + g.astype(jnp.float32))).astype(a.dtype)

        return apply_op("evabyte_rms_norm", f, x, self.weight)


class _Linear(Layer):
    """A plain ``y = x W`` ([in, out], no bias) under the published
    parameter name ``<path>.weight``."""

    def __init__(self, n_in, n_out, std):
        super().__init__()
        self.weight = self.create_parameter(
            [n_in, n_out], default_initializer=I.Normal(0.0, std))
        self.bias = None

    def forward(self, x):
        return apply_op("evabyte_linear", jnp.matmul, x, self.weight)


class EvaByteMLP(LlamaMLP):
    """Llama's SwiGLU over plain linears."""

    def __init__(self, cfg):
        Layer.__init__(self)
        h, f = cfg.hidden_size, cfg.intermediate_size
        self.gate_proj = _Linear(h, f, cfg.init_std)
        self.up_proj = _Linear(h, f, cfg.init_std)
        self.down_proj = _Linear(f, h, cfg.init_std)


class EvaByteAttention(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        h, nh, d = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim
        self.q_proj = _Linear(h, nh * d, cfg.init_std)
        self.k_proj = _Linear(h, nh * d, cfg.init_std)
        self.v_proj = _Linear(h, nh * d, cfg.init_std)
        self.o_proj = _Linear(nh * d, h, cfg.init_std)
        # the pooling query and the pooled key's offset, a head
        self.phi = self.create_parameter(
            [nh, d], default_initializer=I.Normal(0.0, 1.0))
        self.mu = self.create_parameter(
            [nh, d], default_initializer=I.Normal(0.0, cfg.init_std))

    def forward(self, x, cos, sin):
        cfg = self.config
        nh, d = cfg.num_attention_heads, cfg.head_dim

        def f(xr, wq, wk, wv, wo, phi, mu):
            b, s, _ = xr.shape
            q = apply_rotary_emb(
                jnp.matmul(xr, wq).reshape(b, s, nh, d), cos, sin)
            k = apply_rotary_emb(
                jnp.matmul(xr, wk).reshape(b, s, nh, d), cos, sin)
            v = jnp.matmul(xr, wv).reshape(b, s, nh, d)
            o = eva_attention(q, k, v, phi, mu, cfg.window_size,
                              cfg.chunk_size)
            return jnp.matmul(o.astype(xr.dtype).reshape(b, s, nh * d), wo)

        return apply_op(
            "evabyte_attention", f, x, self.q_proj.weight,
            self.k_proj.weight, self.v_proj.weight, self.o_proj.weight,
            self.phi, self.mu)


def _skip_add(x, y):
    """``fp32_skip_add``: a residual sum is formed in float32 and rounded
    once to the stream's type."""
    return apply_op(
        "evabyte_skip_add",
        lambda a, b: (a.astype(jnp.float32)
                      + b.astype(jnp.float32)).astype(a.dtype), x, y)


class EvaByteDecoderLayer(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.input_layernorm = EvaByteRMSNorm(cfg.hidden_size,
                                              cfg.rms_norm_eps)
        self.self_attn = EvaByteAttention(cfg)
        self.post_attention_layernorm = EvaByteRMSNorm(cfg.hidden_size,
                                                       cfg.rms_norm_eps)
        self.mlp = EvaByteMLP(cfg)

    def forward(self, x, cos, sin):
        x = _skip_add(x, self.self_attn(self.input_layernorm(x), cos, sin))
        return _skip_add(x, self.mlp(self.post_attention_layernorm(x)))


class EvaByteModel(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        self.embed_tokens = _Linear(cfg.vocab_size, cfg.hidden_size,
                                    cfg.init_std)
        self.layers = LayerList([EvaByteDecoderLayer(cfg)
                                 for _ in range(cfg.num_hidden_layers)])
        self.norm = EvaByteRMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def forward(self, input_ids):
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        cos, sin = build_rope_cache(
            int(ids.shape[1]), self.config.head_dim,
            base=self.config.rope_theta, dtype=jnp.float32)
        x = apply_op("evabyte_embed", lambda w: jnp.take(w, ids, axis=0),
                     self.embed_tokens.weight)
        for layer in self.layers:
            x = layer(x, cos, sin)
        return self.norm(x)


class EvaByteForCausalLM(Layer):
    """ids [B, S] -> float32 logits [B, S, num_pred_heads, vocab_size]."""

    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        self.model = EvaByteModel(cfg)
        self.lm_head = _Linear(cfg.hidden_size,
                               cfg.num_pred_heads * cfg.vocab_size,
                               cfg.init_std)
        if cfg.dtype not in ("float32", None):
            if LazyGuard._depth:
                # shapes and types only: the caller hands every
                # parameter its array, in the type it is served in
                for p in self.parameters():
                    p._data = jax.ShapeDtypeStruct(p._data.shape,
                                                   jnp.dtype(cfg.dtype))
            else:
                self.astype(cfg.dtype)

    def _head(self, h):
        cfg = self.config

        def f(a, w):
            lg = jnp.matmul(a, w, preferred_element_type=jnp.float32) \
                if cfg.fp32_logits else jnp.matmul(a, w)
            return lg.reshape(*a.shape[:-1], cfg.num_pred_heads,
                              cfg.vocab_size)

        return apply_op("evabyte_head", f, h, self.lm_head.weight)

    def forward(self, input_ids):
        return self._head(self.model(input_ids))
