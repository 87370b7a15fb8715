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

A packed step (``prefill_chunk``) has one plan and two ways to dispatch
it, told apart by what the adapter can observe (``_fusion_eligible``):
a dense float model runs ONE compiled program a layer over the pools'
raw arrays (``PagedKVCacheManager.layer_step``), with embed and head as
programs too and every index operand of the step built in numpy and
uploaded once; anything else (int8 pages or weights, routed experts,
sharded or biased projections) runs the same plan op by op.

A model that declares ``window_size`` / ``chunk_size`` (EvaByte,
models/evabyte.py: exact keys for the current aligned window, one pooled
row a finished chunk behind it) is served by the same adapter from a
``page_format="eva"`` pool. Its dense layer is Llama's but for four
things the adapter reads off the model: the norms' unit offset, MHA, the
summary epilogue of the layer program, and the table a step hands the
kernel (the pool's). It runs the programmed body only, offers ``warm``,
and returns head 0's logits of the ``num_pred_heads`` it computes.

A model that declares ``block_length`` / ``mask_token_id`` (SDAR-MoE,
models/sdar.py: generation by diffusion over blocks) is served from plain
``"kv"`` pages by the same programs with three more things read off the
model: per-head q/k norm gains, a routed feed-forward (``DroplessMoE``:
the layer program's second static body; the per-expert counts come back
beside the stream and cross with the step's pull) and the block length,
handed to the kernel as its block-causal mask. ``prefill_chunk(...,
choose_rows=)`` lists rows of one block, or of two (a finished block whose
K/V the pass writes, and the open one behind it), runs the head over
every position of each listed row's LAST block ON THE DEVICE and returns,
a position, (best token, its logit, logsumexp): the MASK id's logit is
left out, and 3 numbers cross where 151,936 would. The programmed body
only; ``warm`` as above.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..framework import telemetry
from ..framework.core import Tensor, no_grad
from ..incubate.nn import PagedKVCacheManager
from ..incubate.nn.paged_cache import StepTables
from ..nn.layer.norm import RMSNorm
from ..ops.kernels.paged_attention import (
    packed_position_index_np as _position_index,
    pad_plan_np as _pad_plan,
    ragged_few_rows,
    upload_plan as _upload,
)
from ..ops.kernels.rms_norm import rms_norm as _rms_norm
from ..ops.kernels.rope import apply_rotary_emb, build_rope_cache
from ..tensor.manipulation import reshape
from .paged_common import (
    PagedAdapterBase, logits_epilogue, plan_packed_rows,
    pow2 as _pow2, right_align_plan_np as _right_align_plan, step_logits,
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
        # a window-and-summary model (EvaByte): aligned windows read
        # exactly, one pooled row a finished chunk behind them; the
        # chunk is the page, so the layer program pools whole pages
        self._eva = int(getattr(cfg, "chunk_size", 0) or 0)
        # a model that generates by diffusion over blocks (SDAR): a row
        # feeds a whole block a pass, so every length the pool holds is
        # whole blocks and the kernel's mask is block-causal
        self.block_length = int(getattr(cfg, "block_length", 0) or 0)
        self.mask_token_id = getattr(cfg, "mask_token_id", None)
        if self.block_length and (page_size % self.block_length
                                  or kv_cache_dtype or weight_dtype):
            raise ValueError(
                f"a block-diffusion model (block_length="
                f"{self.block_length}) is served from float pages that "
                f"hold whole blocks, with float weights (page_size="
                f"{page_size} kv_cache_dtype={kv_cache_dtype!r} "
                f"weight_dtype={weight_dtype!r})")
        if self._eva and (self._eva != page_size or kv_cache_dtype
                          or weight_dtype or page_pool_bytes):
            raise ValueError(
                f"a window-and-summary model (chunk_size={self._eva}) is "
                "served from page_format='eva' pages of page_size = "
                "chunk_size, float pages and weights, sized by num_pages "
                f"(page_size={page_size} kv_cache_dtype={kv_cache_dtype!r}"
                f" weight_dtype={weight_dtype!r} "
                f"page_pool_bytes={page_pool_bytes!r})")
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
                sanitizer=sanitizer, **(dict(
                    page_format="eva", window_tokens=cfg.window_size)
                    if self._eva else {}),
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
        self._programs = None
        # the row shapes of a window-and-summary step: rows and a
        # multi-token row's tokens are padded up to what ``warm`` was
        # told, the table to a window's pages at least, so that the
        # steady steps run the programs set-up built
        self._rows_pad = self._chunk_pad = self._table_pad = 1
        self.pred_logits = None
        if self.block_length and not self._fusion_eligible():
            raise ValueError(
                "a block-diffusion model is served by the layer program "
                "alone (the op-by-op body has no q/k norms, no block mask "
                "and no device-side choice): every layer plain float "
                "linears, RMSNorm gains of one epsilon, a DroplessMoE "
                "without a shared expert or a selection bias, a plain "
                "embedding and head")
        if self._eva and not self._fusion_eligible():
            raise ValueError(
                "a window-and-summary model is served by the layer "
                "program alone (page_format='eva' pages are written by "
                "layer_step): every layer plain float linears, "
                "RMSNorm gains of one kind, a plain embedding and head")

    def _fusion_eligible(self) -> bool:
        """The gate of the programmed body, computed once per adapter
        from what it can observe: the layer program takes raw [in, out]
        float weights as operands and writes float pages, so the KV
        pool must be float (int8 page calibration is a host-driven
        per-token wave replay) and every layer one of plain parts —
        q/k/v/o plain (non-distributed, non-weight-quantized) linears,
        bias-free but for an all-or-none q/k/v bias, a feed-forward that
        is a ``LlamaMLP`` of such linears or a ``DroplessMoE`` of plain
        float stacks with no shared expert and no selection bias (the
        program's second static body), ``RMSNorm`` norms of one epsilon,
        per-head q/k norms in every layer or in none — with a plain
        embedding and head. Anything else keeps the same plan, op by
        op."""
        if self._fused_ok is None:
            self._fused_ok = not self.caches[0].quantized \
                and self.weight_dtype is None and self._plain_dense()
        return self._fused_ok

    def _plain_dense(self) -> bool:
        from ..incubate.distributed.models.moe import DroplessMoE
        from ..models.llama import LlamaMLP

        def plain(w, ndim=2):
            data = getattr(w, "_data", None)
            return (w is not None
                    and not getattr(w, "is_distributed", False)
                    and getattr(data, "ndim", 0) == ndim
                    and jnp.issubdtype(data.dtype, jnp.floating))

        def linear(proj, bias_ok=False):
            return plain(getattr(proj, "weight", None)) and (
                bias_ok or getattr(proj, "bias", None) is None)

        def feed_forward(mlp):
            if isinstance(mlp, LlamaMLP):
                return all(linear(p) for p in (
                    mlp.gate_proj, mlp.up_proj, mlp.down_proj))
            return (isinstance(mlp, DroplessMoE) and not mlp.has_shared
                    and mlp.e_score_correction_bias is None
                    and mlp.routed_scaling_factor == 1.0
                    and plain(mlp.gate_weight) and all(
                        plain(w, 3) for w in mlp.routed_params()[1:]))

        core = self.model.model
        norms, head_norms = [core.norm], []
        for layer in core.layers:
            att, mlp = layer.self_attn, layer.mlp
            qkv = (att.q_proj, att.k_proj, att.v_proj)
            has = [getattr(p, "bias", None) is not None for p in qkv]
            if not (all(linear(p, bias_ok=True) for p in qkv)
                    and (all(has) or not any(has))
                    and all(plain(p.bias, 1) for p in qkv if has[0])
                    and linear(att.o_proj)   # bias-free epilogue
                    and feed_forward(mlp)):
                return False
            norms += [layer.input_layernorm, layer.post_attention_layernorm]
            head_norms += [n for n in (getattr(att, "q_norm", None),
                                       getattr(att, "k_norm", None))
                           if n is not None]
        if len(head_norms) not in (0, 2 * len(core.layers)):
            return False
        norms += head_norms
        head = self.model.lm_head
        return (all(isinstance(n, RMSNorm) and plain(n.weight, 1)
                    for n in norms)
                and len({(float(n._epsilon), self._unit_offset(n))
                         for n in norms}) == 1
                and plain(core.embed_tokens.weight)
                and (head is None or linear(head)))

    @staticmethod
    def _unit_offset(norm) -> bool:
        """The norm stores its gain less one (x / rms(x) * (1 + g))."""
        return bool(getattr(norm, "unit_offset", False))

    def _step_programs(self):
        """(embed, head, eps) of the programmed body: the two programs
        beside the layers', each ``jax.jit`` of a pure function of arrays
        (jit keys on their bucketed shapes), and the norms' epsilon."""
        if self._programs is None:
            eps = float(self.model.model.norm._epsilon)
            tied = self.model.lm_head is None
            offset = self._unit_offset(self.model.model.norm)
            f32 = bool(getattr(self.cfg, "fp32_logits", False))
            heads = int(getattr(self.cfg, "num_pred_heads", 1))

            def embed(emb, tok):
                return jnp.take(emb, tok[0], axis=0)

            def head(x, idx, norm_w, head_w):
                if offset:
                    norm_w = 1.0 + norm_w.astype(jnp.float32)
                h = _rms_norm(x[idx], norm_w, eps)
                if tied:
                    return h @ head_w.T
                lg = jnp.matmul(h, head_w, preferred_element_type=(
                    jnp.float32 if f32 else None))
                if heads == 1:
                    return lg
                # [head, vocabulary]: head 0, what a sampler sees,
                # beside all of them
                lg = lg.reshape(lg.shape[0], heads, -1)
                return lg[:, 0], lg

            mask_id = self.mask_token_id

            def choose(x, idx, norm_w, head_w):
                """A position: (best token, its logit, logsumexp) of
                the float32 logits with the MASK id's left out."""
                lg = jnp.matmul(_rms_norm(x[idx], norm_w, eps), head_w,
                                preferred_element_type=jnp.float32)
                lg = lg.at[:, mask_id].set(-jnp.inf)
                return jnp.stack([
                    jnp.argmax(lg, -1).astype(jnp.float32), jnp.max(lg, -1),
                    jax.nn.logsumexp(lg, -1)], -1)

            embed.__name__, head.__name__ = "llama_embed", "llama_head"
            choose.__name__ = "llama_choose"
            self._programs = jax.jit(embed), jax.jit(head), eps
            if self.block_length:
                self._programs += (jax.jit(choose),)
        return self._programs

    def decode_token(self, token_ids, seq_ids):
        """One token per listed sequence; returns logits (B, vocab)."""
        if self._eva:
            return self.prefill_chunk([[int(t)] for t in token_ids],
                                      seq_ids, pad_to=_pow2(len(seq_ids)))
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

    def prefill_chunk(self, token_ids, seq_ids, start_positions=None,
                      pad_to=None, logits_rows=None, choose_rows=None):
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
        lm-head over the packed activations the step already computed,
        so verify rows add NO compiled attend program beyond the existing
        bucketed ragged family.

        ``choose_rows`` (a block-diffusion model only): a list of row
        indices, each a row of one or two blocks of ``block_length``
        tokens; every position of a listed row's LAST block is chosen ON
        THE DEVICE (a first block is a finished one, fed for its K/V).
        The call then returns one float32 array [len(choose_rows) *
        block_length, 3], a position (best token, its logit, logsumexp),
        the MASK id's logit left out, in place of any logits; what else
        rides the step's pull (the routed layers' per-expert counts)
        comes with it.

        All dense compute (embed / qkv / o_proj / mlp / norms) runs over
        ONE flat packed token axis padded to ``pad_to`` (the scheduler
        buckets it — serving.bucket_packed_tokens — so steady-state
        serving compiles one program per bucket, not per packed length).
        Attention is ONE ragged kernel call per layer for the whole mixed
        batch: every row — single-token decode rows and multi-token chunks
        alike — rides the unified ragged kernel right-aligned with its own
        q_lens (fused int8-KV dequant included), padded to power-of-two
        row/length/page-table shapes so the kernel programs are
        shape-stable. Where :meth:`_fusion_eligible` (a dense float model
        over float pages) a layer is ONE compiled program
        (``cache.layer_step``: norm, qkv + RoPE + page scatter, the
        kernel, o_proj, residual, norm, MLP, residual; the layer's weights
        are operands, so the layers share the program of a shape), embed
        and head are programs too, and the step's index operands cross to
        the device once; otherwise the same plan runs op by op
        (``cache.append_ragged`` + ``cache.attend_ragged``)."""
        with telemetry.span("model.plan") as plan_span:
            rows = plan_packed_rows(self.caches[0], token_ids, seq_ids,
                                    start_positions, pad_to, self.max_length)
            b, counts, pad_to = rows.b, rows.counts, rows.pad_to
            self._count_packed_step(rows)

            # gather/scatter plan (host-built once, shared by every layer):
            # ONE right-aligned ragged block for EVERY row — decode rows are
            # q_lens=1 rows of the same kernel call (the Ragged Paged
            # Attention shape), so each packed config compiles ONE attend
            # program
            t_pad = _pow2(max(counts))
            b_pad = _pow2(b)
            if self._eva:
                b_pad = max(b_pad, self._rows_pad)
                t_pad = 1 if t_pad == 1 else max(t_pad, self._chunk_pad)
                rows.mp_pad = max(rows.mp_pad, self.caches[0].window_pages)
            blk = self.block_length
            if blk:
                self._check_blocks(rows, seq_ids, choose_rows)
                b_pad = max(b_pad, self._rows_pad)
                # a closed list: a block where every row feeds one, two
                # where some row feeds two, the chunk's pad beyond
                t_pad = max(t_pad, blk) if t_pad <= 2 * blk \
                    else max(t_pad, self._chunk_pad)
                rows.mp_pad = max(rows.mp_pad, self._table_pad)
            gm, mr, mc, m_flat = _right_align_plan(
                range(b), rows.starts, counts, t_pad, b_pad)
            fuse = self._fusion_eligible()
            # the layer program embeds the packed dense work, so its REAL
            # dispatch key includes the packed bucket (pad_to) — the pure
            # attend program's does not
            shape = ("ragged_fused", b_pad, t_pad, rows.mp_pad, pad_to) \
                if fuse else ("ragged", b_pad, t_pad, rows.mp_pad)
            self._count_kernel_shape(pad_to, shape)
            if fuse:
                # every index operand bucket-shaped (out-of-bounds fills
                # drop in the program's scatters), the per-token ones
                # merged into one array, and all across in one call
                tok = np.stack([rows.flat, rows.pos_np,
                                _pad_plan(mr, pad_to, 0),
                                _pad_plan(mc, pad_to, 0),
                                _pad_plan(m_flat, pad_to, pad_to)])
                # (the head of a window-and-summary model runs on the
                # padded row count: slot 0 again, cut on the host)
                host = [tok, gm, _pad_plan(rows.last_idx, b_pad, 0)
                        if self._eva or blk else rows.last_idx]
                if choose_rows is not None:
                    # the head runs over rows x the positions of a row's
                    # last block whatever the step holds (slot 0 again,
                    # cut on the host)
                    host[2] = _pad_plan(_position_index(
                        rows.last_idx + 1 - blk, [blk] * b, choose_rows)
                        if choose_rows else (), b_pad * blk, 0)
                if logits_rows is not None:
                    host.append(_position_index(rows.starts, counts,
                                                logits_rows))
                up = _upload(*host)
            else:
                up = [jnp.asarray(a) for a in (
                    rows.flat[:, None], rows.pos_np, gm, mr, mc, m_flat)]
            if plan_span is not None:
                plan_span.attrs.update(
                    rows=b, packed=rows.n_real, pad_to=pad_to,
                    bytes=sum(int(a.nbytes) for a in up))
        with no_grad():
            if fuse:
                return self._run_programs(rows, seq_ids, up, b_pad,
                                          choose_rows)
            return self._run_eager(rows, seq_ids, up, b_pad, logits_rows)

    def _check_blocks(self, rows, seq_ids, choose_rows):
        """A block-diffusion step's rows: every row resumes on a block
        boundary (a prompt chunk ends on one, a generated block is fed
        whole), and a row whose positions are chosen on the device is one
        block, or two: a finished block and the open one."""
        blk = self.block_length
        off = [s for s, n in zip(seq_ids, rows.lens0) if n % blk]
        if off:
            raise ValueError(
                f"prefill_chunk: sequences {off} do not resume on a "
                f"boundary of block_length={blk}")
        bad = [i for i in choose_rows or ()
               if rows.counts[i] not in (blk, 2 * blk)]
        if bad:
            raise ValueError(
                f"prefill_chunk: choose_rows {bad} are not rows of one or "
                f"two blocks of block_length={blk} tokens (counts "
                f"{[rows.counts[i] for i in bad]})")

    def chunk_room(self, seq_id):
        """Tokens one step may feed the sequence (the scheduler clamps a
        prompt chunk to it): up to its window's end for a
        window-and-summary model, else no bound (None)."""
        return self.caches[0].chunk_room(seq_id)

    def warm(self, rows, packed, chunk_tokens):
        """Build, before the first request, the programs of the steady
        steps of a window-and-summary or a block-diffusion model
        (``BatchScheduler.warm`` calls it with its batch size, packed
        widths and chunk size): ``rows`` sequences decoding (a token a
        row; a block, or two in the pass that carries a finished one),
        alone or beside prompt chunks of at most ``chunk_tokens`` tokens,
        at each packed width (those it is told of and the doublings
        between them) that rows of so many tokens can fill, and each
        table width up to ``max_length``. From here on every step pads
        its rows to ``rows`` and a multi-token row to ``chunk_tokens``,
        so the list is short and closed. Each program runs once on
        zeros over the pools themselves: every slot and summary plan
        entry is out of bounds (nothing is written, no token is routed)
        and every length 0.
        A Llama/Mistral model returns at once: its steps' shapes follow
        the traffic (ROADMAP Queue 1 #3)."""
        blk = self.block_length
        if not (self._eva or blk):
            return
        self._rows_pad = b_pad = _pow2(rows)
        self._chunk_pad = _pow2(max(2, int(chunk_tokens)))
        embed, head, eps, *choose = self._step_programs()
        core, pool = self.model.model, self.caches[0]
        z, i32 = np.zeros, np.int32
        top = _pow2(pool.table_pages(self.max_length))
        if blk:
            # tables of 64 pages (1,024 tokens) at least: a width costs
            # set-up a program for every (packed width, pad) below, a
            # shorter row's dead block of pages the kernel a skipped grid
            # step
            self._table_pad = min(64, top)
        mps, mp = [], pool.window_pages if self._eva else self._table_pad
        while mp <= top:
            mps.append(mp)
            mp *= 2
        layer = core.layers[0]
        # a short last chunk packs to a width between the two it is
        # told of: the doublings between them (the shipped buckets' and
        # the usual ones'; another width compiles when it is first met)
        packed = {int(p) for p in packed}
        n = min(packed)
        while n < max(packed):
            n *= 2
            packed.add(n)
        packed = sorted(packed)
        # a decode row's tokens: one, a block, or two blocks
        t_pads = sorted({blk or 1, 2 * blk or 1, self._chunk_pad})
        for below, n_pad in zip([0] + packed, packed):
            self._dispatch_shapes.add(n_pad)
            tok = z((5, n_pad), i32)
            tok[4] = n_pad
            slots = z((2, n_pad), i32)
            slots[0] = pool.num_pages
            sums = z((3, n_pad // pool.page_size + b_pad), i32)
            sums[1] = pool.num_pages
            for t_pad in t_pads:
                if rows * t_pad <= below:
                    continue      # rows this short pack to a lesser width
                for mp in mps:
                    self._count_kernel_shape(
                        n_pad, ("ragged_fused", b_pad, t_pad, mp, n_pad))
                    tok_d, gm, slots_d, rows_d, sums_d = _upload(
                        tok, z((b_pad, t_pad), i32), slots,
                        z((b_pad, mp + 2), i32), sums)
                    x = embed(core.embed_tokens.weight._data, tok_d)
                    x = pool.layer_step(
                        x, self._layer_weights(layer),
                        (self._cos, self._sin), (tok_d, gm),
                        StepTables((rows_d, slots_d, sums_d) if self._eva
                                   else (rows_d, slots_d)), eps,
                        **self._layer_switches(layer))
                    if isinstance(x, tuple):
                        x = x[0]
            head_w = (core.norm.weight._data, self.model.lm_head.weight._data)
            out = choose[0](x, jnp.zeros((b_pad * blk,), jnp.int32),
                            *head_w) if blk else \
                head(x, jnp.zeros((b_pad,), jnp.int32), *head_w)
        jax.block_until_ready(out)

    def _few_row_rows(self, rows, t_pad):
        """The rows of this step that the ragged kernel attends in its
        few-row form, a layer's call: from the step's own counts and
        the static shapes, nothing read back."""
        cfg = self.cfg
        return ragged_few_rows(
            rows.counts, t_pad,
            cfg.num_attention_heads // cfg.num_key_value_heads,
            self.caches[0].quantized)

    def _run_programs(self, rows, seq_ids, up, b_pad, choose_rows=None):
        """The programmed body of a packed step: one dispatch for the
        embedding, one a layer, one for the head (two with verify rows,
        whose positions are the fourth operand of ``up``)."""
        span = telemetry.span
        embed, head, eps, *choose = self._step_programs()
        core, caches = self.model.model, self.caches
        tok, gm, last, *verify = up
        # every layer's pool books its own slots BEFORE the first layer
        # runs (an exhausted pool raises with every page array untouched);
        # the tables are built and uploaded by the first and shared
        tables = []
        for cache in caches:
            tables.append(cache.book_step(
                seq_ids, rows.counts, b_pad, rows.mp_pad, rows.pad_to,
                like=tables[-1] if tables else None))
        if self.block_length:
            self._note_block_counts(tables, rows)
        plan, rope = (tok, gm), (self._cos, self._sin)
        few = self._few_row_rows(rows, gm.shape[1])
        sizes = []                 # a routed layer's per-expert counts
        with span("model.embed") as sp:
            if sp is not None:
                sp.attrs["key"] = self._program_key("embed", rows, gm)
            x = embed(core.embed_tokens.weight._data, tok)      # (N, H)
        for li, layer in enumerate(core.layers):
            with span("model.layer", li=li, program=1) as sp:
                if sp is not None:
                    sp.attrs["key"] = self._program_key(
                        "layer", rows, gm, layer)
                self.chunk_stats["attend_calls"] += 1
                self.chunk_stats["layer_programs"] += 1
                self.chunk_stats["few_row_rows"] += few
                x = caches[li].layer_step(
                    x, self._layer_weights(layer), rope, plan, tables[li],
                    eps, window=self._window, few_row_rows=few,
                    **self._layer_switches(layer))
                if isinstance(x, tuple):
                    x, n = x
                    sizes.append(n)
        with span("model.head") as sp:
            if sp is not None:
                sp.attrs["key"] = self._program_key(
                    "head" if choose_rows is None else "choose", rows, gm,
                    last=last)
            head_w = (core.norm.weight._data,
                      (core.embed_tokens if self.model.lm_head is None
                       else self.model.lm_head).weight._data)
            extra = (jnp.stack(sizes), self._note_counts) if sizes \
                else (None, None)
            if choose_rows is not None:
                # the choice of every position of the listed rows, made
                # on the device: 3 numbers a position cross, not a row
                # of the vocabulary
                return step_logits(
                    choose[0](x, last, *head_w),
                    len(choose_rows) * self.block_length, *extra)
            logits = head(x, last, *head_w)
            if self.block_length:
                return step_logits(logits, rows.b, *extra)
            if self._eva:
                # every prediction head is computed; a sampler sees
                # head 0 of the rows that are real (cut on the host)
                logits, self.pred_logits = logits
                return step_logits(logits, rows.b)
            logits = Tensor(logits)
            if not verify:
                return logits
            # multi-row sampling epilogue: per-position logits for
            # the listed (verify) rows, concatenated in list order
            return logits, Tensor(head(x, verify[0], *head_w))

    def _program_key(self, part, rows, gm, layer=None, last=None):
        """What chooses the compiled program that a dispatch span of a
        programmed step runs, as a short string: the span's ``key``,
        which the ``xla.*`` ranges of a build under it copy. The embed
        program is chosen by the packed width, the head's by it and the
        rows it reads (``last``), a layer's by packed width, rows, row
        length, table width and its static switches. Built only where
        the span is live."""
        if part == "embed":
            return f"embed n{rows.pad_to}"
        if layer is None:
            return f"{part} n{rows.pad_to} r{last.shape[0]}"
        b_pad, t_pad = gm.shape
        switches = "".join(f" {k}" for k in self._layer_switches(layer))
        window = f" w{self._window}" if self._window else ""
        return (f"layer n{rows.pad_to} r{b_pad} t{t_pad} p{rows.mp_pad}"
                f"{window}{switches}")

    def _note_block_counts(self, tables, rows):
        """What a block-diffusion step's kernel call reads and computes,
        exact from the step's table (the ``kernel.ragged`` span's ``fed``
        / ``pairs`` / ``kv_rows``): a fed token is paired with every row
        up to the end of its own block."""
        blk = self.block_length
        fed = np.asarray(rows.counts, np.int64)
        seen = fed + np.asarray(rows.lens0, np.int64)
        # a row of q tokens ending at L (both whole blocks): each of its
        # q / blk blocks pairs blk tokens with the rows up to its end
        n = fed // blk
        pairs = blk * (n * (seen - fed) + blk * n * (n + 1) // 2)
        # a last partial chunk (a prompt scored, never continued) sees
        # up to the row's end
        part = fed % blk
        pairs += part * seen
        tables[0].counts = {"fed": int(fed.sum()), "pairs": int(pairs.sum()),
                            "kv_rows": int(seen.sum())}

    @staticmethod
    def _layer_weights(layer):
        """A layer's raw arrays as the layer program takes them; the
        feed-forward's three are a ``LlamaMLP``'s matrices or a routed
        layer's expert stacks."""
        att, mlp = layer.self_attn, layer.mlp
        biases = None
        if att.q_proj.bias is not None:
            biases = (att.q_proj.bias._data, att.k_proj.bias._data,
                      att.v_proj.bias._data)
        ffn = (mlp.gate_proj, mlp.up_proj, mlp.down_proj) \
            if hasattr(mlp, "gate_proj") else None
        return (layer.input_layernorm.weight._data,
                att.q_proj.weight._data, att.k_proj.weight._data,
                att.v_proj.weight._data, att.o_proj.weight._data,
                biases, layer.post_attention_layernorm.weight._data,
                *((p.weight._data for p in ffn) if ffn else
                  (w._data for w in mlp.routed_params()[1:])))

    def _layer_switches(self, layer):
        """The layer program's static switches, off for Llama/Mistral:
        the norms' unit offset and the summary epilogue's (phi, mu); the
        kernel's block-causal mask, the per-head q/k norm gains, the
        routed feed-forward's router."""
        att, mlp, out = layer.self_attn, layer.mlp, {}
        if self._eva:
            out.update(unit_offset=self._unit_offset(layer.input_layernorm),
                       summary=(att.phi._data, att.mu._data))
        if self.block_length:
            out["block"] = self.block_length
        if getattr(att, "q_norm", None) is not None:
            out["qk_norm"] = (att.q_norm.weight._data,
                              att.k_norm.weight._data)
        if not hasattr(mlp, "gate_proj"):
            out["router"] = (mlp.gate_weight._data, mlp.top_k,
                             mlp.norm_topk_prob, mlp.scoring)
        return out

    def _run_eager(self, rows, seq_ids, up, b_pad, logits_rows):
        """The op-by-op body of a packed step (int8 pages or weights,
        routed experts, sharded or biased projections)."""
        cfg, span = self.cfg, telemetry.span
        counts, n_real, pad_to = rows.counts, rows.n_real, rows.pad_to
        nh, nkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim)
        ids, pos, gm, mr, mc, m_flat = up
        ids, pos = Tensor(ids), pos[None, :]                       # (1, N)
        few = self._few_row_rows(rows, gm.shape[1])
        with span("model.embed"):
            x = self.model.model.embed_tokens(ids)[:, 0]     # (N, H)
        for li, layer in enumerate(self.model.model.layers):
            with span("model.layer", li=li, program=0):
                cache = self.caches[li]
                with span("model.norm"):
                    xi = layer.input_layernorm(x)
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
                self.chunk_stats["few_row_rows"] += few
                out = cache.attend_ragged(
                    Tensor(qm), seq_ids, counts,
                    rows_pad=b_pad, max_pages=rows.mp_pad,
                    window=self._window, few_row_rows=few)
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
