"""Llama-2 model family — the flagship TP (mp=8) acceptance config.

Architecture parity with the reference ecosystem's Llama implementation
(RMSNorm pre-norm, rotary position embedding, grouped-query attention,
SwiGLU MLP, untied lm head), built on this framework's tensor-parallel
layers (paddle_tpu/distributed/fleet/layers/mpu/mp_layers.py — the
analog of upstream python/paddle/distributed/fleet/layers/mpu/
mp_layers.py Column/RowParallelLinear + VocabParallelEmbedding).

TPU-native notes:

* Parameters are GLOBAL arrays with mp-axis shardings; GSPMD
  materializes the Megatron collective pattern (identity-fwd /
  allreduce-bwd around column, allreduce-fwd after row) and fuses it
  with the matmuls onto the MXU.
* Attention runs the Pallas flash-attention kernel (causal), heads
  sharded over mp; with sep_degree > 1 the sequence dimension of
  activations is sharded over the "sep" axis (context parallelism —
  ring attention lives in distributed/fleet/utils/
  sequence_parallel_utils.py).
* The decoder layer is a single-tensor-signature Layer so it stacks
  into the compiled 1F1B pipeline schedule (pp_layers._StackedBody).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from ..distributed.fleet.layers.mpu.mp_layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ..distributed.fleet.layers.mpu.mp_ops import shard_constraint
from ..distributed.mesh import axis_degree
from ..framework.core import apply_op
from ..nn import functional as F
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..ops.kernels.rope import apply_rotary_emb, build_rope_cache


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    sequence_parallel: bool = False  # Megatron-SP over the mp axis
    # context parallelism over the sep axis when sep_degree>1:
    # "ring" (ppermute KV rotation) or "ulysses" (all_to_all head swap)
    context_parallel: str = "ring"
    recompute: bool = False
    # "full" replays the whole layer in backward; "selective"/
    # "core_attn" keep matmul outputs and replay only the cheap glue
    # (upstream recompute_granularity — fleet/recompute)
    recompute_granularity: str = "full"
    # chunked fused linear+CE loss head: never materializes the [T, V]
    # logits (ops/kernels/fused_loss.py). At mp>1 the vocab-parallel
    # variant engages (shard-local lse + mp-collective combine);
    # forward returns (None, loss) when engaged.
    fused_head_loss: bool = False
    # Qwen2-style bias on q/k/v projections (o_proj stays bias-free)
    attention_bias: bool = False
    # Mistral-style sliding-window attention: 0 = full causal; w > 0
    # keeps keys j with 0 <= i - j < w (HF semantics)
    sliding_window: int = 0
    # Mixtral-style sparse-MoE MLP: num_local_experts > 0 replaces the
    # dense SwiGLU MLP with a top-k routed expert mixture (MixtralGate:
    # softmax top-k renormalized over the selected experts + the HF
    # load-balancing aux loss, weighted by router_aux_loss_coef)
    num_local_experts: int = 0
    num_experts_per_tok: int = 2
    router_aux_loss_coef: float = 0.02
    moe_capacity_factor: float = 2.0
    dtype: str = "float32"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def num_params(self) -> int:
        """Total parameter count (for MFU math in bench.py)."""
        h, i, v = self.hidden_size, self.intermediate_size, self.vocab_size
        kvh = self.num_key_value_heads * self.head_dim
        if self.num_local_experts > 0:
            e = self.num_local_experts
            # stacked SwiGLU experts (E, h, 2i) + (E, i, h) + biases,
            # plus the router weight [h, E]
            mlp = e * (h * 2 * i + 2 * i + i * h + h) + h * e
        else:
            mlp = 3 * h * i               # gate up down
        per_layer = (
            h * h + 2 * h * kvh + h * h  # q k v o
            + mlp
            + 2 * h                       # two rms norms
        )
        if self.attention_bias:
            per_layer += h + 2 * kvh      # q k v biases (no o bias)
        emb = v * h * (1 if self.tie_word_embeddings else 2)
        return per_layer * self.num_hidden_layers + emb + h


def llama2_7b(**kw) -> LlamaConfig:
    return LlamaConfig(**kw)


def llama2_13b(**kw) -> LlamaConfig:
    return LlamaConfig(
        hidden_size=5120, intermediate_size=13824, num_hidden_layers=40,
        num_attention_heads=40, num_key_value_heads=40, **kw,
    )


def llama3_8b(**kw) -> LlamaConfig:
    """Llama-3-8B: GQA 32:8, 128k vocab, rope theta 500k."""
    kw.setdefault("vocab_size", 128256)
    kw.setdefault("hidden_size", 4096)
    kw.setdefault("intermediate_size", 14336)
    kw.setdefault("num_hidden_layers", 32)
    kw.setdefault("num_attention_heads", 32)
    kw.setdefault("num_key_value_heads", 8)
    kw.setdefault("max_position_embeddings", 8192)
    kw.setdefault("rope_theta", 500000.0)
    return LlamaConfig(**kw)


def llama3_70b(**kw) -> LlamaConfig:
    kw.setdefault("vocab_size", 128256)
    kw.setdefault("hidden_size", 8192)
    kw.setdefault("intermediate_size", 28672)
    kw.setdefault("num_hidden_layers", 80)
    kw.setdefault("num_attention_heads", 64)
    kw.setdefault("num_key_value_heads", 8)
    kw.setdefault("max_position_embeddings", 8192)
    kw.setdefault("rope_theta", 500000.0)
    return LlamaConfig(**kw)


def qwen2_7b(**kw) -> LlamaConfig:
    """Qwen2-7B: llama trunk + q/k/v bias, GQA 28:4, 152k vocab."""
    kw.setdefault("vocab_size", 152064)
    kw.setdefault("hidden_size", 3584)
    kw.setdefault("intermediate_size", 18944)
    kw.setdefault("num_hidden_layers", 28)
    kw.setdefault("num_attention_heads", 28)
    kw.setdefault("num_key_value_heads", 4)
    kw.setdefault("max_position_embeddings", 32768)
    kw.setdefault("rope_theta", 1000000.0)
    kw.setdefault("attention_bias", True)
    kw.setdefault("rms_norm_eps", 1e-6)
    return LlamaConfig(**kw)


def qwen2_0_5b(**kw) -> LlamaConfig:
    """Qwen2-0.5B (tied embeddings, GQA 14:2)."""
    kw.setdefault("vocab_size", 151936)
    kw.setdefault("hidden_size", 896)
    kw.setdefault("intermediate_size", 4864)
    kw.setdefault("num_hidden_layers", 24)
    kw.setdefault("num_attention_heads", 14)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("max_position_embeddings", 32768)
    kw.setdefault("rope_theta", 1000000.0)
    kw.setdefault("attention_bias", True)
    kw.setdefault("tie_word_embeddings", True)
    kw.setdefault("rms_norm_eps", 1e-6)
    return LlamaConfig(**kw)


def mistral_7b(**kw) -> LlamaConfig:
    """Mistral-7B-v0.1: llama trunk + 4096-token sliding window,
    GQA 32:8."""
    kw.setdefault("vocab_size", 32000)
    kw.setdefault("hidden_size", 4096)
    kw.setdefault("intermediate_size", 14336)
    kw.setdefault("num_hidden_layers", 32)
    kw.setdefault("num_attention_heads", 32)
    kw.setdefault("num_key_value_heads", 8)
    kw.setdefault("max_position_embeddings", 32768)
    kw.setdefault("sliding_window", 4096)
    return LlamaConfig(**kw)


_warned_moe_recompute_llama = False


def mixtral_8x7b(**kw) -> LlamaConfig:
    """Mixtral-8x7B: Mistral trunk + 8-expert top-2 sparse MoE MLP.

    Capacity caveat (vs HF): experts here dispatch with a FIXED
    per-expert capacity (``moe_capacity_factor``, default 2.0 —
    static shapes for the TPU batched-expert matmul), while HF's
    MixtralSparseMoeBlock gathers dynamically and processes every
    routed token. Under heavily skewed routing, tokens past an
    expert's capacity are DROPPED from that expert's contribution
    (the residual path still carries them), so logits can diverge
    from HF even with identical weights. Raise ``moe_capacity_factor``
    toward ``num_local_experts / num_experts_per_tok`` for exact-coverage
    dispatch at the cost of padding FLOPs. See docs/ARCHITECTURE.md
    ("MoE capacity")."""
    kw.setdefault("vocab_size", 32000)
    kw.setdefault("hidden_size", 4096)
    kw.setdefault("intermediate_size", 14336)
    kw.setdefault("num_hidden_layers", 32)
    kw.setdefault("num_attention_heads", 32)
    kw.setdefault("num_key_value_heads", 8)
    kw.setdefault("max_position_embeddings", 32768)
    kw.setdefault("rope_theta", 1000000.0)
    kw.setdefault("num_local_experts", 8)
    kw.setdefault("num_experts_per_tok", 2)
    return LlamaConfig(**kw)


def mixtral_tiny(**kw) -> LlamaConfig:
    """Test-scale Mixtral topology (4 experts, top-2)."""
    kw.setdefault("vocab_size", 512)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("intermediate_size", 128)
    kw.setdefault("num_hidden_layers", 2)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("max_position_embeddings", 256)
    kw.setdefault("num_local_experts", 4)
    kw.setdefault("num_experts_per_tok", 2)
    return LlamaConfig(**kw)


def llama_headline(**kw) -> LlamaConfig:
    """The single-chip headline-bench config (~470M params): shared by
    bench.py, tools/exp_mfu.py, and tools/roofline.py so the benchmark
    and its analysis tools can never desynchronize."""
    kw.setdefault("vocab_size", 32000)
    kw.setdefault("hidden_size", 1536)
    kw.setdefault("intermediate_size", 4224)
    kw.setdefault("num_hidden_layers", 14)
    kw.setdefault("num_attention_heads", 12)
    kw.setdefault("num_key_value_heads", 12)
    kw.setdefault("max_position_embeddings", 2048)
    kw.setdefault("tie_word_embeddings", True)
    # chunked fused CE head: ~4GB less HBM traffic per step at this
    # vocab/batch (tests/test_fused_loss.py pins trajectory parity)
    kw.setdefault("fused_head_loss", True)
    return LlamaConfig(**kw)


def llama_tiny(**kw) -> LlamaConfig:
    """Small config for tests / compile checks (GQA 4:2 exercised)."""
    kw.setdefault("vocab_size", 512)
    kw.setdefault("hidden_size", 128)
    kw.setdefault("intermediate_size", 256)
    kw.setdefault("num_hidden_layers", 2)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("max_position_embeddings", 256)
    return LlamaConfig(**kw)


def _seq_spec(sequence_parallel=False):
    """Activation PartitionSpec [B, S, H] honoring dp/sep axes. With
    Megatron-SP (sequence_parallel=True) the sequence dim is also
    sharded over mp between the matmul regions — GSPMD then places the
    reference's allgather-fwd/reduce-scatter-bwd pattern
    (sequence_parallel_utils.py) at the TP-layer boundaries."""
    if sequence_parallel and axis_degree("mp") > 1:
        seq = ("sep", "mp") if axis_degree("sep") > 1 else "mp"
    else:
        seq = "sep" if axis_degree("sep") > 1 else None
    return ("dp", seq, None)


def _constrain_act(x, sequence_parallel=False):
    if (
        axis_degree("dp") > 1 or axis_degree("sep") > 1
        or (sequence_parallel and axis_degree("mp") > 1)
    ):
        return shard_constraint(x, *_seq_spec(sequence_parallel))
    return x


class LlamaMLP(Layer):
    """SwiGLU: down(silu(gate(x)) * up(x)); gate/up column-split over mp,
    down row-split (the Megatron pair — one allreduce per MLP)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.gate_proj = ColumnParallelLinear(
            config.hidden_size, config.intermediate_size,
            has_bias=False, gather_output=False,
        )
        self.up_proj = ColumnParallelLinear(
            config.hidden_size, config.intermediate_size,
            has_bias=False, gather_output=False,
        )
        self.down_proj = RowParallelLinear(
            config.intermediate_size, config.hidden_size,
            has_bias=False, input_is_parallel=True,
        )

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaAttention(Layer):
    """GQA attention: q/k/v column-split over mp (heads sharded), o
    row-split; rotary embedding fused elementwise; Pallas flash kernel."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim
        kv_out = self.num_kv_heads * self.head_dim
        qkv_bias = config.attention_bias  # Qwen2: bias on q/k/v only
        self.q_proj = ColumnParallelLinear(
            config.hidden_size, config.hidden_size,
            has_bias=qkv_bias, gather_output=False,
        )
        self.k_proj = ColumnParallelLinear(
            config.hidden_size, kv_out, has_bias=qkv_bias,
            gather_output=False,
        )
        self.v_proj = ColumnParallelLinear(
            config.hidden_size, kv_out, has_bias=qkv_bias,
            gather_output=False,
        )
        self.o_proj = RowParallelLinear(
            config.hidden_size, config.hidden_size,
            has_bias=False, input_is_parallel=True,
        )

    def forward(self, x):
        cfg = self.config
        b, s = x.shape[0], x.shape[1]
        q = self.q_proj(x)
        k = self.k_proj(x)
        v = self.v_proj(x)

        nh, nkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        theta = cfg.rope_theta
        # the flash kernel resolves kv_head = q_head // group in its
        # BlockSpec index map — no KV repetition in HBM. Repeat only
        # when the kv heads don't divide over the mp axis.
        mp = axis_degree("mp")
        rep = nh // nkv if (mp > 1 and nkv % mp != 0) else 1

        def attn(qr, kr, vr):
            qh = qr.reshape(b, s, nh, hd)
            kh = kr.reshape(b, s, nkv, hd)
            vh = vr.reshape(b, s, nkv, hd)
            cos, sin = build_rope_cache(s, hd, base=theta, dtype=jnp.float32)
            qh = apply_rotary_emb(qh, cos, sin)
            kh = apply_rotary_emb(kh, cos, sin)
            if rep > 1:
                kh = jnp.repeat(kh, rep, axis=2)
                vh = jnp.repeat(vh, rep, axis=2)
            return qh, kh, vh

        q, k, v = apply_op("llama_qkv_rope", attn, q, k, v, n_outs=3)
        return self._attend(q, k, v, b, s)

    def _attend(self, q, k, v, b, s):
        cfg = self.config
        nh, hd = self.num_heads, self.head_dim
        mp = axis_degree("mp")
        sep = axis_degree("sep")
        if mp > 1:
            seq_ax = "sep" if sep > 1 else None
            spec = ("dp", seq_ax, "mp", None)
            q = shard_constraint(q, *spec)
            k = shard_constraint(k, *spec)
            v = shard_constraint(v, *spec)
        w = int(cfg.sliding_window or 0)
        if sep > 1:
            if w and w < s:
                # at w >= s the window is inert (full causal), which
                # the CP kernels already implement
                raise NotImplementedError(
                    "sliding_window attention narrower than the "
                    "sequence is not implemented under sep (context-"
                    "parallel) sharding; use sep_degree=1 or "
                    "sliding_window=0"
                )
            from ..distributed.fleet.utils.context_parallel import (
                ring_flash_attention,
                ulysses_flash_attention,
            )

            if cfg.context_parallel == "ulysses":
                cp = ulysses_flash_attention
            elif cfg.context_parallel == "ring":
                cp = ring_flash_attention
            else:
                raise ValueError(
                    "context_parallel must be 'ring' or 'ulysses', got "
                    f"{cfg.context_parallel!r}"
                )
            out = cp(q, k, v, causal=True)
        else:
            # windowed flash: the Pallas kernels band the mask AND skip
            # out-of-band blocks, so long-context Mistral training is
            # O(S*w), not O(S^2); w >= s makes the band inert (plain
            # causal flash)
            out, _ = F.flash_attention(
                q, k, v, causal=True,
                window=w if (w and w < s) else 0)
        out = apply_op(
            "merge_heads", lambda o: o.reshape(b, s, nh * hd), out
        )
        return self.o_proj(out)

    def decode_step(self, x, cache_k, cache_v, pos):
        """KV-cache incremental attention (the decode side of the
        reference's fused_multi_transformer_op.cu: static-shape cache
        slots updated in place, masked attention over the prefix).

        x: [B, S, H] new tokens occupying positions [pos, pos+S);
        cache_k/v: [B, S_max, KVH, D]; pos: scalar int32 Tensor (traced
        — one compiled step serves every position). Returns
        (out, new_cache_k, new_cache_v)."""
        import jax

        cfg = self.config
        b, s = x.shape[0], x.shape[1]
        nh, nkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        theta = cfg.rope_theta

        q = self.q_proj(x)
        k = self.k_proj(x)
        v = self.v_proj(x)

        def f(qr, kr, vr, ck, cv, p):
            smax = ck.shape[1]
            qh = qr.reshape(b, s, nh, hd)
            kh = kr.reshape(b, s, nkv, hd)
            vh = vr.reshape(b, s, nkv, hd)
            cos, sin = build_rope_cache(
                smax, hd, base=theta, dtype=jnp.float32
            )
            positions = p + jnp.arange(s, dtype=jnp.int32)
            qh = apply_rotary_emb(qh, cos, sin, position_ids=positions)
            kh = apply_rotary_emb(kh, cos, sin, position_ids=positions)
            ck = jax.lax.dynamic_update_slice(
                ck, kh.astype(ck.dtype), (0, p, 0, 0)
            )
            cv = jax.lax.dynamic_update_slice(
                cv, vh.astype(cv.dtype), (0, p, 0, 0)
            )
            kk, vv = ck, cv
            if nkv != nh:
                kk = jnp.repeat(kk, nh // nkv, axis=2)
                vv = jnp.repeat(vv, nh // nkv, axis=2)
            scale = 1.0 / (hd ** 0.5)
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk",
                qh.astype(jnp.float32), kk.astype(jnp.float32),
            ) * scale
            kpos = jnp.arange(smax, dtype=jnp.int32)
            mask = kpos[None, :] <= positions[:, None]  # (S, Smax)
            w = int(cfg.sliding_window or 0)
            if w:
                mask = mask & (kpos[None, :] > positions[:, None] - w)
            scores = jnp.where(mask[None, None], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum(
                "bhqk,bkhd->bqhd", probs, vv.astype(jnp.float32)
            ).astype(qr.dtype)
            return out.reshape(b, s, nh * hd), ck, cv

        out, nk, nv = apply_op(
            "llama_decode_attn", f, q, k, v, cache_k, cache_v, pos,
            n_outs=3,
        )
        return self.o_proj(out), nk, nv


class LlamaSparseMoeBlock(Layer):
    """Mixtral-style sparse-MoE MLP (upstream ecosystem analog:
    MixtralSparseMoeBlock). TPU-first: stacked (E, d, 2f)/(E, f, d)
    SwiGLU experts batched over the MXU with capacity-based dispatch
    (the incubate MoELayer machinery, ep-shardable), routed by
    MixtralGate — softmax top-k renormalized over the selected
    experts, HF load-balancing aux loss on ``self.gate.loss``.

    NOT token-exact vs HF under skewed routing: capacity-based
    dispatch (``config.moe_capacity_factor``) drops tokens past an
    expert's fixed capacity, where HF's dynamic gather processes all
    of them — see the :func:`mixtral_8x7b` docstring for the full
    caveat and the capacity knob that recovers exact coverage. The
    layer that drops nothing at any imbalance is
    ``incubate.distributed.models.moe.DroplessMoE`` (tokens sorted by
    expert, a grouped matmul with the group sizes as device data; the
    feed-forward of ``models/xing4.py`` and ``models/sdar.py``). It has
    two routers, the sigmoid ``noaux_tc`` one and the softmax-over-all-
    experts top-k one that Mixtral's is; this block keeps ``MoELayer``
    for its expert-parallel dispatch and its aux loss, which
    ``DroplessMoE`` does not have yet."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        from ..incubate.distributed.models.moe import MoELayer

        self.moe = MoELayer(
            config.hidden_size,
            num_experts=config.num_local_experts,
            d_hidden=config.intermediate_size,
            gate="mixtral",
            top_k=config.num_experts_per_tok,
            capacity_factor=config.moe_capacity_factor,
            activation="swiglu",
        )
        self.gate = self.moe.gate  # aux-loss surface (gate.get_loss())

    def forward(self, x):
        return self.moe(x)


class LlamaDecoderLayer(Layer):
    """Pre-norm block; single-tensor signature → pipeline-stackable."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self._sp = config.sequence_parallel
        self.input_layernorm = RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps
        )
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps
        )
        self.is_moe = config.num_local_experts > 0
        self.mlp = (LlamaSparseMoeBlock(config) if self.is_moe
                    else LlamaMLP(config))

    def forward(self, x):
        x = _constrain_act(x, self._sp)
        h = x + self.self_attn(self.input_layernorm(x))
        out = h + self.mlp(self.post_attention_layernorm(h))
        return _constrain_act(out, self._sp)

    def decode_step(self, x, cache_k, cache_v, pos):
        attn_out, nk, nv = self.self_attn.decode_step(
            self.input_layernorm(x), cache_k, cache_v, pos
        )
        h = x + attn_out
        out = h + self.mlp(self.post_attention_layernorm(h))
        return out, nk, nv

    def moe_loss(self):
        if getattr(self, "is_moe", False) and \
                self.mlp.gate.loss is not None:
            return self.mlp.gate.get_loss()
        return None


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size
        )
        from ..nn.layer.layers import LayerList

        self.layers = LayerList(
            [LlamaDecoderLayer(config)
             for _ in range(config.num_hidden_layers)]
        )
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        h = self.embed_tokens(input_ids)
        h = _constrain_act(h, self.config.sequence_parallel)
        if self.config.recompute:
            from ..distributed.fleet.recompute import recompute

            for l in self.layers:
                h = recompute(
                    l, h, granularity=self.config.recompute_granularity)
        else:
            for l in self.layers:
                h = l(h)
        return self.norm(h)

    def decode_step(self, input_ids, caches, pos):
        h = self.embed_tokens(input_ids)
        new_caches = []
        for l, (ck, cv) in zip(self.layers, caches):
            h, nk, nv = l.decode_step(h, ck, cv, pos)
            new_caches.append((nk, nv))
        return self.norm(h), new_caches


class LlamaForCausalLM(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.model = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size,
                has_bias=False, gather_output=False,
            )
        if config.dtype not in ("float32", None):
            self.astype(config.dtype)

    def forward(self, input_ids, labels=None):
        h = self.model(input_ids)
        if labels is not None and self._fused_loss_active(labels):
            from ..incubate.nn.functional import fused_linear_cross_entropy

            tied = self.lm_head is None
            w = (self.model.embed_tokens.weight if tied
                 else self.lm_head.weight)  # [V,H] tied / [H,V] linear
            # the chunked kernel never builds [T, V] logits, so there
            # are no logits to return
            if axis_degree("mp") > 1:
                # vocab-parallel path: keep the full S (the SP seq
                # sharding needs S % mp == 0 — slicing to S-1 would
                # break it); shift by PADDING labels instead:
                # labels_next[:, t] = labels[:, t+1], ignore at S-1
                ii = -100
                lab_s = apply_op(
                    "shift_labels_pad",
                    lambda a: jnp.concatenate(
                        [a[:, 1:],
                         jnp.full((a.shape[0], 1), ii, a.dtype)], axis=1),
                    labels, differentiable=False)
                return None, self._with_moe_aux(
                    fused_linear_cross_entropy(
                        h, w, lab_s, ignore_index=ii,
                        transpose_w=not tied))
            # single-replica head: logits[:, :-1] predicts labels[:, 1:]
            h_s = apply_op("shift_hidden", lambda a: a[:, :-1], h)
            lab_s = apply_op("shift_labels", lambda a: a[:, 1:], labels,
                             differentiable=False)
            return None, self._with_moe_aux(fused_linear_cross_entropy(
                h_s, w, lab_s, transpose_w=not tied))
        logits = self._head(h)
        if labels is None:
            return logits
        loss = self._with_moe_aux(
            LlamaPretrainingCriterion()(logits, labels))
        return logits, loss

    def _with_moe_aux(self, loss):
        """Add the routers' load-balance aux losses (Mixtral
        router_aux_loss_coef). Under recompute the gate's side-channel
        tensor is a leaked tracer inside jax.checkpoint and cannot be
        collected — same limitation as the GPT-MoE path; routing still
        trains through the combine weights."""
        if self.config.num_local_experts == 0:
            return loss
        from .moe_common import add_moe_aux_loss

        return add_moe_aux_loss(
            loss, self.model.layers, self.config.router_aux_loss_coef,
            recompute=self.config.recompute, family="mixtral")

    def _fused_loss_active(self, labels=None):
        # mp==1: the single-replica chunked kernel. mp>1: the vocab-
        # parallel kernel (shard-local chunked lse + mp-collective
        # combine) — engages when seq and vocab divide the mp degree,
        # else the unfused criterion's collective path applies.
        if not self.config.fused_head_loss:
            return False
        mp = axis_degree("mp")
        if mp == 1:
            return True
        if labels is None:
            return False
        s = labels.shape[-1]
        return s % mp == 0 and self.config.vocab_size % mp == 0

    # -- decode / serving --------------------------------------------------

    def _head(self, h):
        if self.lm_head is not None:
            return self.lm_head(h)
        return _tied_logits(h, self.model.embed_tokens.weight)

    def init_cache(self, batch_size, max_length, dtype=None):
        """Allocate static-shape KV cache slots (one (k, v) pair per
        layer): [B, max_length, KVH, D]."""
        from ..framework.core import Tensor

        cfg = self.config
        if dtype is None:
            dtype = self.model.embed_tokens.weight._data.dtype
        shape = (batch_size, max_length, cfg.num_key_value_heads,
                 cfg.head_dim)
        return [
            (Tensor(jnp.zeros(shape, dtype)), Tensor(jnp.zeros(shape, dtype)))
            for _ in range(cfg.num_hidden_layers)
        ]

    def decode_step(self, input_ids, caches, pos):
        """One incremental step: logits for the new tokens + updated
        caches. `pos` is a scalar int32 Tensor so a single compiled
        step serves all positions."""
        h, new_caches = self.model.decode_step(input_ids, caches, pos)
        return self._head(h), new_caches

    def generate(self, input_ids, max_new_tokens=32, use_jit=False,
                 **kwargs):
        """Decode over the KV cache. Greedy by default; sampling
        (do_sample/temperature/top_k/top_p/repetition_penalty/
        eos_token_id) and beam search (num_beams) via
        :mod:`.generation`. Returns [B, S0+max_new]."""
        from .generation import generate as _generate

        return _generate(self, input_ids, max_new_tokens=max_new_tokens,
                         use_jit=use_jit, **kwargs)


class LlamaPretrainingCriterion(Layer):
    """Next-token mean CE: predicts labels[:, t+1] from logits[:, t]
    (labels == input_ids, shifted internally). Logits may arrive
    vocab-sharded over mp — log_softmax's reduction over that dim
    becomes the mp collective (the reference's
    c_softmax_with_cross_entropy)."""

    def __init__(self, ignore_index: int = -100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, logits, labels):
        logits, labels = _shift_for_next_token(logits, labels)
        # reduction='mean' normalizes by the count of non-ignored
        # tokens, so padded positions don't deflate the loss
        return F.cross_entropy(
            logits, labels, reduction="mean",
            ignore_index=self.ignore_index,
        )


def _shift_for_next_token(logits, labels):
    """logits[:, :-1] predicts labels[:, 1:]."""
    logits = apply_op("shift_logits", lambda l: l[:, :-1], logits)
    labels = apply_op(
        "shift_labels", lambda l: l[:, 1:], labels, differentiable=False
    )
    return logits, labels


# -- pipeline form ----------------------------------------------------------


def llama_pipeline_model(config: LlamaConfig, **pp_kwargs):
    """PipelineLayer with [embed | N×decoder | norm(+head)] segmentation
    — the decoder run stacks onto the pp axis (compiled 1F1B schedule).
    With tie_word_embeddings the head is a SharedLayerDesc occurrence of
    the embedding (one tensor; the reference's shared-embedding grad
    allreduce becomes ordinary accumulation — pp_layers.py)."""
    if config.num_local_experts > 0:
        import warnings

        warnings.warn(
            "llama_pipeline_model with Mixtral MoE: the router "
            "load-balance aux loss stays inside the compiled stage "
            "and is NOT added to the pipeline loss (same caveat as "
            "gpt_pipeline_model); routing still trains through the "
            "combine weights")
    from ..distributed.fleet.meta_parallel.parallel_layers.pp_layers import (
        LayerDesc,
        PipelineLayer,
        SharedLayerDesc,
    )

    body = [
        LayerDesc(LlamaDecoderLayer, config)
        for _ in range(config.num_hidden_layers)
    ]
    if config.tie_word_embeddings:
        descs = [
            SharedLayerDesc(
                "llama_embed", _LlamaEmbedding, None, "embed_tokens",
                config.vocab_size, config.hidden_size,
            ),
            *body,
            LayerDesc(_LlamaNorm, config),
            SharedLayerDesc(
                "llama_embed", _LlamaEmbedding, _tied_head_forward,
                "embed_tokens", config.vocab_size, config.hidden_size,
            ),
        ]
    else:
        descs = [
            LayerDesc(
                _LlamaEmbedding, config.vocab_size, config.hidden_size
            ),
            *body,
            LayerDesc(_LlamaHead, config),
        ]
    pp_kwargs.setdefault(
        "loss_fn", LlamaPretrainingCriterion()
    )
    if config.recompute:
        pp_kwargs.setdefault("recompute_interval", 1)
    return PipelineLayer(descs, **pp_kwargs)


def _tied_logits(h, w):
    return apply_op("tied_lm_head", lambda a, b: a @ b.T, h, w)


def _tied_head_forward(embed_layer, h):
    return _tied_logits(h, embed_layer.embed_tokens.weight)


class _LlamaEmbedding(Layer):
    def __init__(self, vocab_size, hidden_size):
        super().__init__()
        self.embed_tokens = VocabParallelEmbedding(vocab_size, hidden_size)

    def forward(self, input_ids):
        return _constrain_act(self.embed_tokens(input_ids))


class _LlamaNorm(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, h):
        return self.norm(h)


class _LlamaHead(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size,
            has_bias=False, gather_output=False,
        )

    def forward(self, h):
        return self.lm_head(self.norm(h))
