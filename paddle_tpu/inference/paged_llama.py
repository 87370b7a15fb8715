"""Paged-cache serving adapter for LlamaForCausalLM.

Upstream analog: PaddleNLP's serving of fused_multi_transformer —
a trained model served with a paged (block) KV cache instead of the
dense per-request cache. This adapter exposes a trained
``LlamaForCausalLM`` through the BatchScheduler model protocol
(``alloc`` / ``free`` / ``decode_token`` / ``caches``): every decode
step is ONE paged-attention Pallas kernel call per layer over the
whole ragged batch, with pages shared from a fixed pool.

The adapter reuses the model's own weights/layers (no copy): embed →
per layer (rms_norm → qkv → RoPE at each sequence's own position →
paged append + attend → o_proj → mlp) → final norm → lm head.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from ..framework import telemetry
from ..framework.core import Tensor, no_grad
from ..incubate.nn import PagedKVCacheManager
from ..ops.kernels.paged_attention import pad_plan_i32 as _pad_plan
from ..ops.kernels.rope import apply_rotary_emb, build_rope_cache
from ..tensor.manipulation import reshape
from .paged_common import (
    PagedAdapterBase, logits_epilogue, plan_packed_rows,
    pow2 as _pow2, right_align_plan as _right_align_plan,
)

__all__ = ["PagedLlamaAdapter"]


class PagedLlamaAdapter(PagedAdapterBase):
    """Serve a LlamaForCausalLM from a paged KV pool.

    ``num_pages`` x ``page_size`` tokens per layer; ``max_length``
    bounds RoPE positions. Works with the BatchScheduler or driven
    directly via decode_token.

    Quantized serving knobs (docs/QUANTIZATION.md):

    * ``kv_cache_dtype="int8"`` — pages store int8 with per-page,
      per-head scale sidecars; dequant fuses into the paged-attention
      kernel. Halves page bytes, so the same HBM budget holds ~2x the
      sequences.
    * ``weight_dtype="int8"|"int4"`` — runs
      quantization.quantize_for_serving over the wrapped model IN
      PLACE at adapter construction (the serving analog of
      quantize-on-checkpoint-load): attention/MLP linears swap to
      WeightOnlyLinear. The report lands on ``self.quant_report``.
    * ``page_pool_bytes`` — size the pool by HBM budget instead of
      page count: ``num_pages`` becomes
      ``page_pool_bytes // (layers * page_nbytes)``, so switching
      kv_cache_dtype at a FIXED byte budget changes capacity, not
      spend.
    * ``sanitizer`` — per-adapter override of ``FLAGS_page_sanitizer``
      (``"off"``/``"warn"``/``"strict"``): every per-layer pool gets
      the lifecycle shadow heap + event journal of
      incubate/nn/page_sanitizer.py.
    """

    def __init__(self, model, num_pages=256, page_size=16,
                 max_length=None, dtype=None, kv_cache_dtype=None,
                 weight_dtype=None, page_pool_bytes=None,
                 sanitizer=None):
        self.model = model
        cfg = model.config
        self.cfg = cfg
        # Mistral-style sliding window rides through the paged decode
        # kernel's banded mask (out-of-window pages skipped)
        self._window = int(getattr(cfg, "sliding_window", 0) or 0)
        self.weight_dtype = weight_dtype
        self.quant_report = None
        if weight_dtype is not None:
            from ..quantization import quantize_for_serving

            self.quant_report = quantize_for_serving(
                model, weight_dtype=weight_dtype)
        if dtype is None:
            dtype = model.model.embed_tokens.weight._data.dtype
        self.kv_cache_dtype = kv_cache_dtype
        self.max_length = int(max_length or cfg.max_position_embeddings)

        def make_cache(n):
            return PagedKVCacheManager(
                n, page_size, cfg.num_key_value_heads,
                cfg.head_dim, dtype=dtype, kv_dtype=kv_cache_dtype,
                sanitizer=sanitizer,
            )

        if page_pool_bytes is not None:
            per_page = PagedKVCacheManager.page_bytes(
                page_size, cfg.num_key_value_heads, cfg.head_dim,
                dtype=dtype, kv_dtype=kv_cache_dtype)
            num_pages = int(page_pool_bytes) // (
                cfg.num_hidden_layers * per_page)
            if num_pages < 1:
                raise ValueError(
                    f"page_pool_bytes={page_pool_bytes} cannot hold "
                    f"one page per layer "
                    f"({cfg.num_hidden_layers} x {per_page} bytes)")
        self.caches = [
            make_cache(num_pages)
            for _ in range(cfg.num_hidden_layers)
        ]
        self._cos, self._sin = build_rope_cache(
            self.max_length, cfg.head_dim, base=cfg.rope_theta,
            dtype=jnp.float32,
        )
        self._init_dispatch_accounting()
        self._fused_ok = None

    def _fusion_eligible(self) -> bool:
        """The fusion gate, computed once per adapter: the
        fused prologue/epilogue consumes raw [in, out] projection
        weights and writes fp pages, so every layer's q/k/v/o
        projection must be a plain (non-distributed, non-weight-
        quantized) linear and the KV pool must be float — int8 page
        calibration is a host-driven per-token wave replay. Ineligible
        adapters keep the unified attend, just unfused."""
        if self._fused_ok is None:
            ok = not self.caches[0].quantized \
                and self.weight_dtype is None
            if ok:
                for layer in self.model.model.layers:
                    att = layer.self_attn
                    projs = (att.q_proj, att.k_proj, att.v_proj,
                             att.o_proj)
                    for proj in projs:
                        w = getattr(proj, "weight", None)
                        if (w is None
                                or getattr(w, "is_distributed", False)
                                or getattr(getattr(w, "_data", None),
                                           "ndim", 0) != 2):
                            ok = False
                            break
                    has = [getattr(p, "bias", None) is not None
                           for p in projs[:3]]
                    if any(has) and not all(has):
                        ok = False
                    if getattr(att.o_proj, "bias", None) is not None:
                        ok = False  # epilogue models bias-free o_proj
                    if not ok:
                        break
            self._fused_ok = ok
        return self._fused_ok

    def decode_token(self, token_ids, seq_ids):
        """One token per listed sequence; returns logits (B, vocab)."""
        cfg = self.cfg
        b = len(seq_ids)
        nh, nkv, hd = (cfg.num_attention_heads,
                       cfg.num_key_value_heads, cfg.head_dim)
        # this token's position in each sequence = tokens already cached
        lens = [self.caches[0].seq_len(s) for s in seq_ids]
        over = [s for s, n in zip(seq_ids, lens) if n >= self.max_length]
        if over:
            # jnp.take would silently clamp the RoPE position, rotating
            # every later token with the wrong phase — fail loudly
            raise ValueError(
                f"sequences {over} reached max_length="
                f"{self.max_length}; positions beyond it cannot be "
                "rotary-encoded"
            )
        pos = jnp.asarray(lens, jnp.int32)[:, None]  # (B, 1)

        with no_grad():
            ids = Tensor(np.asarray(token_ids, "int64")[:, None])
            x = self.model.model.embed_tokens(ids)[:, 0]  # (B, H)
            for li, layer in enumerate(self.model.model.layers):
                xi = layer.input_layernorm(x)
                q = layer.self_attn.q_proj(xi)
                k = layer.self_attn.k_proj(xi)
                v = layer.self_attn.v_proj(xi)
                qh = q._data.reshape(b, 1, nh, hd)
                kh = k._data.reshape(b, 1, nkv, hd)
                vh = v._data.reshape(b, 1, nkv, hd)
                qh = apply_rotary_emb(
                    qh, self._cos, self._sin, position_ids=pos)
                kh = apply_rotary_emb(
                    kh, self._cos, self._sin, position_ids=pos)
                self.caches[li].append_batch(
                    seq_ids, kh[:, 0], vh[:, 0])
                attn = self.caches[li].attend(
                    Tensor(qh[:, 0]), seq_ids,
                    window=self._window)  # (B, nh, hd)
                attn_flat = reshape(attn, [b, nh * hd])
                x = x + layer.self_attn.o_proj(attn_flat)
                x = x + layer.mlp(layer.post_attention_layernorm(x))
            h = self.model.model.norm(x)
            return self.model._head(h)


def _prefill_chunk(self, token_ids, seq_ids, start_positions=None,
                   pad_to=None, logits_rows=None):
    """One ragged mixed prefill/decode step (the Ragged Paged
    Attention shape — see PAPERS.md): row i appends the
    ``len(token_ids[i])`` tokens of ``token_ids[i]`` to sequence
    ``seq_ids[i]`` and the call returns the logits of every row's
    LAST token, (B, vocab) — single-token rows are exactly
    ``decode_token`` rows, multi-token rows are prefill chunks
    resuming at ``start_positions[i]`` (validated against the cache;
    mid-prompt resume and mid-page cached-prefix resume both work).

    ``logits_rows`` (ISSUE 19, speculative VERIFY rows): a list of
    row indices whose PER-POSITION logits the caller needs — the
    greedy verify step compares the target argmax at every window
    slot against the draft proposal there. The return value becomes
    ``(last_logits, full_logits)`` where ``full_logits`` is the
    ``(sum(counts[i] for i in logits_rows), vocab)`` concatenation
    of the listed rows' positions in list order (split host-side by
    the known counts). The multi-row sampling epilogue is a gather
    (ops/kernels/paged_attention.packed_position_index) + norm +
    lm-head over the packed activations the step already computed —
    eager like the chunk body, so verify rows add NO compiled attend
    program beyond the existing bucketed ragged family.

    All dense compute (embed / qkv / o_proj / mlp / norms) runs over
    ONE flat packed token axis padded to ``pad_to`` (the scheduler
    buckets it — serving.bucket_packed_tokens — so steady-state
    serving compiles one program per bucket, not per packed length).
    Attention is ONE ragged kernel call per layer for the whole mixed
    batch: every row — single-token decode rows and multi-token chunks
    alike — rides the unified ragged kernel right-aligned with its own
    q_lens (fused int8-KV dequant included), padded to power-of-two
    row/length/page-table shapes so the kernel programs are
    shape-stable. Where :meth:`_fusion_eligible` (fp pages + plain
    projection weights) the whole layer attention step fuses
    FlashFuser-style: qkv + RoPE + page scatter as the kernel's
    prologue, o_proj as its epilogue (``cache.fused_ragged_step``);
    otherwise the same plan runs unfused (``cache.append_ragged`` +
    ``cache.attend_ragged``)."""
    cfg = self.cfg
    with telemetry.span("model.plan") as plan_span:
        rows = plan_packed_rows(self.caches[0], token_ids, seq_ids,
                                start_positions, pad_to, self.max_length)
        b, counts, starts = rows.b, rows.counts, rows.starts
        flat, pos_np = rows.flat, rows.pos_np
        n_real, pad_to, mp_pad = rows.n_real, rows.pad_to, rows.mp_pad
        nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim)
        pos = jnp.asarray(pos_np)[None, :]             # (1, N)
        self._count_packed_step(rows)

        # gather/scatter plan (host-built once, shared by every layer):
        # ONE right-aligned ragged block for EVERY row — decode rows are
        # q_lens=1 rows of the same kernel call (the Ragged Paged
        # Attention shape), so each packed config compiles ONE attend
        # program
        t_pad = _pow2(max(counts))
        b_pad = _pow2(b)
        gm, mr, mc, m_flat = _right_align_plan(
            range(b), starts, counts, t_pad, b_pad)
        fuse = self._fusion_eligible()
        # the fused program embeds the packed dense prologue/epilogue,
        # so its REAL dispatch key includes the packed bucket (pad_to)
        # — the pure attend program's does not
        shape = ("ragged_fused", b_pad, t_pad, mp_pad, pad_to) \
            if fuse else ("ragged", b_pad, t_pad, mp_pad)
        self._count_kernel_shape(pad_to, shape)
        pos_flat = jnp.asarray(pos_np)
        if fuse:
            # loop-invariant across layers: pad the scatter plan to
            # the bucket ONCE (out-of-bounds fills drop in the fused
            # program's scatters) instead of once per layer
            mr = _pad_plan(mr, pad_to, 0)
            mc = _pad_plan(mc, pad_to, 0)
            m_flat = _pad_plan(m_flat, pad_to, pad_to)
        ids = Tensor(flat[:, None])
        if plan_span is not None:
            up = [ids._data, pos, pos_flat, gm, mr, mc, m_flat]
            plan_span.attrs.update(rows=b, packed=n_real, pad_to=pad_to,
                            bytes=sum(int(a.nbytes) for a in up))

    span = telemetry.span
    with no_grad():
        with span("model.embed"):
            x = self.model.model.embed_tokens(ids)[:, 0]     # (N, H)
        for li, layer in enumerate(self.model.model.layers):
            with span("model.layer", li=li):
                cache = self.caches[li]
                with span("model.norm"):
                    xi = layer.input_layernorm(x)
                if fuse:
                    # FlashFuser path: qkv + RoPE + page scatter fold
                    # into the ragged kernel's prologue and o_proj
                    # into its epilogue — one program, one dispatch
                    # per layer
                    att = layer.self_attn
                    biases = None
                    if att.q_proj.bias is not None:
                        biases = (att.q_proj.bias._data,
                                  att.k_proj.bias._data,
                                  att.v_proj.bias._data)
                    self.chunk_stats["attend_calls"] += 1
                    y = cache.fused_ragged_step(
                        xi,
                        (att.q_proj.weight._data,
                         att.k_proj.weight._data,
                         att.v_proj.weight._data,
                         att.o_proj.weight._data, biases),
                        (self._cos, self._sin), pos_flat, seq_ids,
                        counts, gm, (mr, mc, m_flat), rows_pad=b_pad,
                        max_pages=mp_pad, window=self._window)
                    x = x + y
                else:
                    q = layer.self_attn.q_proj(xi)
                    k = layer.self_attn.k_proj(xi)
                    v = layer.self_attn.v_proj(xi)
                    qh = q._data.reshape(1, pad_to, nh, hd)
                    kh = k._data.reshape(1, pad_to, nkv, hd)
                    vh = v._data.reshape(1, pad_to, nkv, hd)
                    qh = apply_rotary_emb(
                        qh, self._cos, self._sin, position_ids=pos)[0]
                    kh = apply_rotary_emb(
                        kh, self._cos, self._sin, position_ids=pos)[0]
                    vh = vh[0]
                    cache.append_ragged(
                        seq_ids, counts, kh[:n_real], vh[:n_real])
                    qm = qh[gm]              # (b_pad, t_pad, nh, hd)
                    self.chunk_stats["attend_calls"] += 1
                    out = cache.attend_ragged(
                        Tensor(qm), seq_ids, counts,
                        rows_pad=b_pad, max_pages=mp_pad,
                        window=self._window)
                    attn = jnp.zeros((pad_to, nh, hd), qh.dtype)
                    attn = attn.at[m_flat].set(out._data[mr, mc])
                    attn_flat = Tensor(attn.reshape(pad_to, nh * hd))
                    x = x + layer.self_attn.o_proj(attn_flat)
                with span("model.norm"):
                    h2 = layer.post_attention_layernorm(x)
                with span("model.mlp"):
                    x = x + layer.mlp(h2)
        with span("model.head"):
            # multi-row sampling epilogue: per-position logits for
            # the listed (verify) rows, concatenated in list order
            return logits_epilogue(
                x._data, rows,
                lambda xr: self.model._head(
                    self.model.model.norm(Tensor(xr))), logits_rows)


PagedLlamaAdapter.prefill_chunk = _prefill_chunk
del _prefill_chunk
