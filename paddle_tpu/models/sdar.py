"""SDAR-MoE: a Qwen3-MoE-shaped decoder that generates by diffusion over
blocks (SDAR, arXiv:2510.06303; ``model_type`` ``sdar_moe``).

Upstream ecosystem analog: PaddleNLP's Qwen3-MoE modelling. The layer is
Llama's (pre-norm RMSNorm, rotary embedding in the rotate-half form, GQA,
untied head) but for three things (x a token's state; B = ``block_length``;
b(t) = t // B):

* **q/k norms.** ``q_h = rope(rms_128(W_q,h n) * g_q)``, ``k_j =
  rope(rms_128(W_k,j n) * g_k)``: an RMSNorm over each head's
  ``head_dim`` numbers, one gain vector a layer each, before the rotary
  embedding. ``head_dim`` is the config's own (128), not hidden / heads.
* **Routed experts in every layer.** ``r = softmax(W_r m)`` over all
  ``num_experts`` in float32, the top ``num_experts_per_tok``, their
  scores divided by their sum (``norm_topk_prob``); ``z = y + sum_i w_i
  W_d,i(silu(W_g,i m) * W_u,i m)``. No shared expert, no capacity
  (``DroplessMoE(num_shared=0, scoring="softmax")``).
* **Attention is causal across blocks and bidirectional inside one.** A
  clean pass (prefill, commit) lets t see every m with b(m) <= b(t);
  position t's logits score the token AT t (no shift).

Generation (``BatchScheduler`` over ``PagedLlamaAdapter``, which reads
``block_length`` / ``mask_token_id`` off this config): a block at a time,
its ids start as ``mask_token_id`` (behind the prompt's tail, if the
prompt ends inside it), up to ``denoising_steps`` passes over the block's
B ids (nothing committed) unmask the best-scored positions, the pass
that fixes the last one delivers the block's B tokens together, and one
clean pass over its final tokens commits the block's K/V: the server
(inference/serving.py) folds that pass into the next block's first. The parameter paths are Llama's (``model.layers.<i>.self_attn.
q_proj.weight`` ...), with ``self_attn.q_norm.weight`` / ``k_norm.weight``
and ``mlp.gate_weight`` / ``mlp.experts_{gate,up,down}`` beside them; the
forward here is the full-sequence clean pass.

Recalled from the paper, the model card and the released modelling code,
not re-read: ``block_length`` 4, ``mask_token_id`` 151669, the per-head
q/k norms, no logit shift, the prompt's tail sharing the first generated
block, the commit pass.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..framework.core import Tensor, apply_op
from ..incubate.distributed.models.moe.dropless import DroplessMoE
from ..nn.layer.layers import Layer, LayerList, LazyGuard
from ..nn.layer.norm import RMSNorm
from ..ops.kernels.paged_attention import head_rms
from ..ops.kernels.rope import apply_rotary_emb, build_rope_cache
from .evabyte import _Linear

__all__ = ["SDARMoeConfig", "SDARMoeForCausalLM", "SDARMoeModel",
           "sdar_30b_a3b", "sdar_tiny", "block_causal_attention"]


@dataclass
class SDARMoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 6144          # the dense width: no layer uses it
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    block_length: int = 4
    mask_token_id: int = 151669
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        self.mlp_only_layers = tuple(self.mlp_only_layers)
        if self.decoder_sparse_step != 1 or self.mlp_only_layers:
            raise ValueError(
                "SDAR-MoE routes every layer (decoder_sparse_step="
                f"{self.decoder_sparse_step}, mlp_only_layers="
                f"{list(self.mlp_only_layers)})")
        b = self.block_length
        if b < 1 or b & (b - 1):
            raise ValueError(f"block_length={b} has to be a power of two")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask_token_id={self.mask_token_id} is not in "
                             f"the vocabulary of {self.vocab_size}")


def sdar_30b_a3b(**kw) -> SDARMoeConfig:
    """The published SDAR-30B-A3B-Chat."""
    return SDARMoeConfig(**kw)


def sdar_tiny(**kw) -> SDARMoeConfig:
    """Every mechanism at a size the CPU tests run in seconds."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                num_experts=8, num_experts_per_tok=2,
                max_position_embeddings=512, mask_token_id=255,
                initializer_range=0.08)
    base.update(kw)
    return SDARMoeConfig(**base)


def block_causal_attention(q, k, v, block):
    """The clean pass, full sequence. q [B, S, H, D], k, v [B, S, KVH, D],
    rotated; position t sees every m with m // block <= t // block. One
    float32 softmax a query. Returns float32 [B, S, H, D]."""
    b, s, h, d = q.shape
    kvh = k.shape[2]
    hi = jax.lax.Precision.HIGHEST
    qf = q.astype(jnp.float32).reshape(b, s, kvh, h // kvh, d)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    blk = jnp.arange(s) // block
    sc = jnp.einsum("bigrd,bjgd->bgrij", qf, kf, precision=hi) * d ** -0.5
    sc = jnp.where(blk[None, :] <= blk[:, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, -1)
    return jnp.einsum("bgrij,bjgd->bigrd", p, vf,
                      precision=hi).reshape(b, s, h, d)


class SDARAttention(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        h, d, std = cfg.hidden_size, cfg.head_dim, cfg.initializer_range
        nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
        self.q_proj = _Linear(h, nh * d, std)
        self.k_proj = _Linear(h, nkv * d, std)
        self.v_proj = _Linear(h, nkv * d, std)
        self.o_proj = _Linear(nh * d, h, std)
        self.q_norm = RMSNorm(d, epsilon=cfg.rms_norm_eps)
        self.k_norm = RMSNorm(d, epsilon=cfg.rms_norm_eps)

    def forward(self, x, cos, sin):
        cfg = self.config
        nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        eps = cfg.rms_norm_eps

        def f(xr, wq, wk, wv, wo, gq, gk):
            b, s, _ = xr.shape
            q = head_rms(jnp.matmul(xr, wq).reshape(b, s, nh, d), gq, eps)
            k = head_rms(jnp.matmul(xr, wk).reshape(b, s, nkv, d), gk, eps)
            v = jnp.matmul(xr, wv).reshape(b, s, nkv, d)
            o = block_causal_attention(
                apply_rotary_emb(q, cos, sin), apply_rotary_emb(k, cos, sin),
                v, cfg.block_length)
            return jnp.matmul(o.astype(xr.dtype).reshape(b, s, nh * d), wo)

        return apply_op(
            "sdar_attention", f, x, self.q_proj.weight, self.k_proj.weight,
            self.v_proj.weight, self.o_proj.weight, self.q_norm.weight,
            self.k_norm.weight)


class SDARDecoderLayer(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.input_layernorm = RMSNorm(cfg.hidden_size,
                                       epsilon=cfg.rms_norm_eps)
        self.self_attn = SDARAttention(cfg)
        self.post_attention_layernorm = RMSNorm(cfg.hidden_size,
                                                epsilon=cfg.rms_norm_eps)
        self.mlp = DroplessMoE(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok, num_shared=0,
            norm_topk_prob=cfg.norm_topk_prob,
            init_std=cfg.initializer_range, scoring="softmax")

    def forward(self, x, cos, sin):
        x = x + self.self_attn(self.input_layernorm(x), cos, sin)
        return x + self.mlp(self.post_attention_layernorm(x))


class SDARMoeModel(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        self.embed_tokens = _Linear(cfg.vocab_size, cfg.hidden_size,
                                    cfg.initializer_range)
        self.layers = LayerList([SDARDecoderLayer(cfg)
                                 for _ in range(cfg.num_hidden_layers)])
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids):
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        cos, sin = build_rope_cache(
            int(ids.shape[1]), self.config.head_dim,
            base=self.config.rope_theta, dtype=jnp.float32)
        x = apply_op("sdar_embed", lambda w: jnp.take(w, ids, axis=0),
                     self.embed_tokens.weight)
        for layer in self.layers:
            x = layer(x, cos, sin)
        return self.norm(x)


class SDARMoeForCausalLM(Layer):
    """ids [B, S] -> logits [B, S, vocab_size] of the clean pass: position
    t's logits score the token at t."""

    def __init__(self, cfg):
        super().__init__()
        self.config = cfg
        self.model = SDARMoeModel(cfg)
        self.lm_head = _Linear(cfg.hidden_size, cfg.vocab_size,
                               cfg.initializer_range)
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
        return self.lm_head(h)

    def forward(self, input_ids):
        return self._head(self.model(input_ids))
