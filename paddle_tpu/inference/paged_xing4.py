"""Latent-page serving adapter for Xing4ForCausalLM (models/xing4.py).

The second anatomy behind ``BatchScheduler``: where ``PagedLlamaAdapter``
caches per-head K and V, this one caches ONE row a token and layer (the
normed latent beside the rotated shared rope key) in a
``PagedKVCacheManager(page_format="latent")`` and attends in the absorbed
form: ``q_lat = q_nope W_UK^T`` scores against the cached row, the
kernel's output ``P c_kv`` goes through ``W_UV`` and ``W_o``
(ops/kernels/paged_attention.latent_ragged_attention). The residual state
is ``hc_mult`` streams a token, mixed at each site by the mHC coefficients,
and the feed-forward of the later layers is the drop-free routed-experts
layer.

Everything between two kernels of a layer runs as ONE compiled program a
site and shape (``jax.jit`` keys on the bucketed shapes the row plan
gives), not op by op: ``model.hc`` (a site's read or write), ``model.mla``
(the projections to the absorbed operands; the output projections),
``model.moe`` (router and sort; combine and shared expert) around
``kernel.moe_gmm`` (the three grouped matmuls), and the pool's
``latent_ragged_step`` (page write + ``kernel.latent_ragged``). A steady
step builds nothing.

Per-expert token counts stay on the device through the step and cross to
the host with the logits pull (``StepLogits.numpy``): registry counters
``moe.assignments``, ``moe.experts_touched``, ``moe.expert_tokens_max``,
``moe.expert_tokens_mean``, ``moe.calls`` where the registry is on, and
the same numbers on a ``moe.counts`` span where spans are live.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..framework import telemetry
from ..framework.core import Tensor
from ..incubate.distributed.models.moe import dropless as _moe
from ..incubate.nn import PagedKVCacheManager
from ..models import xing4 as _x
from ..ops.kernels.paged_attention import latent_ragged_step as _latent_step
from ..ops.kernels.paged_attention import pad_plan_i32 as _pad_plan
from .paged_common import (
    PagedAdapterBase, StepLogits, logits_epilogue, plan_packed_rows, pow2,
    right_align_plan_np, step_logits as _step_logits,
)

__all__ = ["PagedXing4Adapter", "StepLogits"]

# a step with a multi-token row pads its rows to at least this many tokens
# (FLAGS_prefill_chunk_tokens' default), so that the latent step's programs
# are of two row widths (decode; chunk) and not one a power of two; the
# kernel skips a row's padding
CHUNK_ROW_PAD = 64


class PagedXing4Adapter(PagedAdapterBase):
    """Serve a Xing4ForCausalLM from latent pages.

    ``num_pages`` x ``page_size`` tokens a layer, ``latent_dim`` numbers
    a token; ``max_length`` bounds the rotary positions (default: what the
    pool can hold of one sequence, at most the model's declared
    positions). ``sanitizer`` as in ``PagedLlamaAdapter``."""

    def __init__(self, model, num_pages=256, page_size=16,
                 max_length=None, dtype=None, sanitizer=None):
        self.model = model
        self.cfg = cfg = model.config
        if dtype is None:
            dtype = model.model.embed_tokens.weight._data.dtype
        self.max_length = int(max_length or min(
            cfg.max_position_embeddings, num_pages * page_size))
        self.caches = [
            PagedKVCacheManager(
                num_pages, page_size, 1, cfg.latent_dim, dtype=dtype,
                sanitizer=sanitizer, page_format="latent")
            for _ in range(cfg.num_hidden_layers)]
        self._cos, self._sin = _x.yarn_rope_tables(cfg, self.max_length)
        self._scale = _x.mla_softmax_scale(cfg)
        self._init_dispatch_accounting()
        self._programs = self._build_programs()

    def _build_programs(self):
        """The compiled pieces of a step, each ``jax.jit`` of a pure
        function of arrays (jit keys on their bucketed shapes)."""
        cfg = self.cfg
        n, k = cfg.hc_mult, cfg.num_experts_per_tok
        n_exp = cfg.n_routed_experts

        def embed(emb, ids):
            return jnp.repeat(emb[ids][:, None, :], n, 1)

        def hc_pre(xs, phi, b, alpha, norm_w):
            return _x.hc_pre(xs, phi, b, alpha, norm_w, cfg)

        def mla_in(h, w_qa, qa_norm, w_qb, w_kva, kva_norm, w_kvb, cos,
                   sin, pos):
            q = _x.mla_absorbed_query(h, w_qa, qa_norm, w_qb, w_kvb, cos,
                                      sin, pos, cfg)
            return q, _x.mla_latent(h, w_kva, kva_norm, cos, sin, pos, cfg)

        def mla_out(out, mr, mc, mflat, w_kvb, w_o):
            o_lat = jnp.zeros((mflat.shape[0],) + out.shape[2:], out.dtype)
            o_lat = o_lat.at[mflat].set(out[mr, mc], mode="drop")
            return _x.mla_output(o_lat, w_kvb, w_o, cfg)

        def moe_route(h, w_router, bias, valid):
            idx, w = _moe.route(h, w_router, bias, k,
                                cfg.routed_scaling_factor,
                                cfg.norm_topk_prob)
            order, sizes = _moe.sort_by_expert(idx, n_exp, valid)
            return h[order // k], order, sizes, w

        def moe_combine(ys, order, w, h, *shared):
            y = _moe.combine(ys, order, w, h.shape[0])
            if shared:
                y = y + _moe.swiglu(h, *shared).astype(jnp.float32)
            return y.astype(h.dtype)

        def head(xs, phi, b, alpha, norm_w, head_w):
            h = _x.hc_reduce(xs, phi, b, alpha, cfg)
            return jnp.matmul(_x.rms(h, norm_w, cfg.rms_norm_eps), head_w,
                              preferred_element_type=jnp.float32)

        progs = {"embed": embed, "hc_pre": hc_pre, "hc_post": _x.hc_post,
                 "mla_in": mla_in, "mla_out": mla_out,
                 "moe_route": moe_route, "moe_gmm": _moe.experts_ffn,
                 "moe_combine": moe_combine, "mlp": _moe.swiglu,
                 "head": head}
        out = {}
        for name, fn in progs.items():
            fn.__name__ = "xing4_" + name       # jit(<name>) in the traces
            out[name] = jax.jit(fn)
        return out

    @staticmethod
    def _row_pad(longest):
        return 1 if longest == 1 else max(CHUNK_ROW_PAD, pow2(longest))

    @staticmethod
    def _program_key(part, rows, *shape):
        """What chooses the compiled programs that a dispatch span of
        the step runs, as a short string: the span's ``key``, which the
        ``xla.*`` ranges of a build under it copy. The packed width;
        then a layer's rows, row length, table width and feed-forward
        (routed or dense), the head's rows, the grouped matmul's
        assignments. Built only where the span is live."""
        if part == "layer":
            b_pad, t_pad, routed = shape
            return (f"layer n{rows.pad_to} r{b_pad} t{t_pad} "
                    f"p{rows.mp_pad} {'moe' if routed else 'dense'}")
        if part == "moe_gmm":
            return f"moe_gmm a{shape[0]}"
        if part == "head":
            return f"head n{rows.pad_to} r{shape[0]}"
        return f"{part} n{rows.pad_to}"

    # -- set-up ------------------------------------------------------------
    def warm(self, rows, packed, chunk_tokens):
        """Build, before the first request, the programs of the steady
        steps (``BatchScheduler.warm`` calls it with its own batch size,
        buckets and chunk size): ``rows`` sequences decoding (``rows`` padded to a power of
        two), alone or beside prompt chunks of at most ``chunk_tokens``
        tokens, for each packed width in ``packed`` and every page-table
        width up to ``max_length``. Runs each program once on zeros (the
        attention over a scratch array of the pool's shape, every length
        0), so the same ``jax.jit`` entries serve the requests."""
        cfg, P = self.cfg, self._programs
        core, pool = self.model.model, self.caches[0]
        b_pad, z = pow2(rows), jnp.zeros
        i32 = jnp.int32
        t_pads = sorted({1, self._row_pad(max(2, int(chunk_tokens)))})
        mp_pads, mp = [], 1
        while mp <= pow2(-(-self.max_length // pool.page_size)):
            mp_pads.append(mp)
            mp *= 2
        scratch = jnp.zeros_like(pool.k_pages)
        kinds, n_moe = {}, 0         # one dense and one expert layer do
        for layer in core.layers:
            w = layer.arrays()
            kinds.setdefault("moe" in w, w)
            n_moe += "moe" in w
        for n_pad in sorted({int(p) for p in packed}):
            ids, pos = z((n_pad,), i32), z((n_pad,), i32)
            valid = z((n_pad,), jnp.bool_)
            xs = P["embed"](core.embed_tokens.weight._data, ids)
            for w in kinds.values():
                h, post, res = P["hc_pre"](xs, *w["attn_hc"], w["ln1"])
                q, lat = P["mla_in"](h, *w["attn"][:6], self._cos,
                                     self._sin, pos)
                for t_pad in t_pads:
                    gm = z((b_pad, t_pad), i32)
                    for mp in mp_pads:
                        out, scratch = _latent_step(
                            q, lat, jnp.full((n_pad,), pool.num_pages, i32),
                            ids, gm, scratch, z((b_pad, mp), i32),
                            z((b_pad,), i32), z((b_pad,), i32),
                            cfg.kv_lora_rank, sm_scale=self._scale)
                    y = P["mla_out"](out, ids, ids,
                                     jnp.full((n_pad,), n_pad, i32),
                                     w["attn"][5], w["attn"][6])
                xs2 = P["hc_post"](xs, y, post, res)
                if "moe" in w:
                    w_r, bias, wg, wu, wd = w["moe"]
                    x_s, order, sizes, wt = P["moe_route"](h, w_r, bias,
                                                           valid)
                    ys = P["moe_gmm"](x_s, sizes, wg, wu, wd)
                    y = P["moe_combine"](ys, order, wt, h,
                                         *(w["shared"] or ()))
                    jnp.stack([sizes] * n_moe)
                else:
                    y = P["mlp"](h, *w["mlp"])
            fh = core.final_hc
            P["head"](xs2[z((b_pad,), i32)], fh.phi._data, fh.b._data,
                      fh.alpha._data, core.norm.weight._data,
                      self.model.lm_head.weight._data)
        jax.block_until_ready(scratch)

    # -- the step ----------------------------------------------------------
    def decode_token(self, token_ids, seq_ids):
        """One token per listed sequence; returns logits (B, vocab)."""
        return self.prefill_chunk([[int(t)] for t in token_ids], seq_ids,
                                  pad_to=pow2(len(seq_ids)))

    def prefill_chunk(self, token_ids, seq_ids, start_positions=None,
                      pad_to=None, logits_rows=None):
        """One ragged mixed prefill/decode step, as
        ``PagedLlamaAdapter.prefill_chunk``: row i appends
        ``token_ids[i]`` to sequence ``seq_ids[i]``; returns the float32
        logits of every row's LAST token, (B, vocab) (``logits_rows``:
        also every position of the listed rows). All dense work runs over
        one packed token axis padded to ``pad_to``; attention is one
        latent ragged call a layer for the whole batch."""
        cfg, span, P = self.cfg, telemetry.span, self._programs
        with span("model.plan") as plan_span:
            rows = plan_packed_rows(self.caches[0], token_ids, seq_ids,
                                    start_positions, pad_to,
                                    self.max_length)
            self._count_packed_step(rows)
            n_pad, b = rows.pad_to, rows.b
            t_pad, b_pad = self._row_pad(max(rows.counts)), pow2(b)
            gm, mr, mc, mflat = right_align_plan_np(
                range(b), rows.starts, rows.counts, t_pad, b_pad)
            gm = jnp.asarray(gm)
            self._count_kernel_shape(
                n_pad, ("latent_ragged", b_pad, t_pad, rows.mp_pad, n_pad))
            # padding entries gather slot (0, 0) and drop on an
            # out-of-bounds packed slot: every operand bucket-shaped
            mr = _pad_plan(mr, n_pad, 0)
            mc = _pad_plan(mc, n_pad, 0)
            mflat = _pad_plan(mflat, n_pad, n_pad)
            ids = jnp.asarray(rows.flat.astype(np.int32))
            pos = jnp.asarray(rows.pos_np)
            valid = jnp.asarray(np.arange(n_pad) < rows.n_real)
            if plan_span is not None:
                plan_span.attrs.update(
                    rows=b, packed=rows.n_real, pad_to=n_pad,
                    bytes=sum(int(a.nbytes) for a in (
                        ids, pos, valid, gm, mr, mc, mflat)))
        cos, sin = self._cos, self._sin
        core = self.model.model
        counts = []
        with span("model.embed") as sp:
            if sp is not None:
                sp.attrs["key"] = self._program_key("embed", rows)
            xs = P["embed"](core.embed_tokens.weight._data, ids)
        for li, layer in enumerate(core.layers):
            with span("model.layer", li=li) as sp:
                w = layer.arrays()
                if sp is not None:
                    sp.attrs["key"] = self._program_key(
                        "layer", rows, b_pad, t_pad, "moe" in w)
                with span("model.hc", li=li, site="attn"):
                    h, post, res = P["hc_pre"](xs, *w["attn_hc"], w["ln1"])
                with span("model.mla", li=li):
                    q, lat = P["mla_in"](h, *w["attn"][:6], cos, sin, pos)
                    self.chunk_stats["attend_calls"] += 1
                    out = self.caches[li].latent_ragged_step(
                        q, lat, seq_ids, rows.counts, gm,
                        cfg.kv_lora_rank, rows_pad=b_pad,
                        max_pages=rows.mp_pad, sm_scale=self._scale)
                    y = P["mla_out"](out._data, mr, mc, mflat,
                                     w["attn"][5], w["attn"][6])
                with span("model.hc", li=li, site="attn"):
                    xs = P["hc_post"](xs, y, post, res)
                with span("model.hc", li=li, site="ffn"):
                    h, post, res = P["hc_pre"](xs, *w["ffn_hc"], w["ln2"])
                if "moe" in w:
                    with span("model.moe", li=li, tokens=rows.n_real):
                        w_r, bias, wg, wu, wd = w["moe"]
                        x_s, order, sizes, wt = P["moe_route"](
                            h, w_r, bias, valid)
                        with span("kernel.moe_gmm",
                                  assignments=int(x_s.shape[0])) as sp:
                            if sp is not None:
                                sp.attrs["key"] = self._program_key(
                                    "moe_gmm", rows, x_s.shape[0])
                            ys = P["moe_gmm"](x_s, sizes, wg, wu, wd)
                        y = P["moe_combine"](ys, order, wt, h,
                                             *(w["shared"] or ()))
                        counts.append(sizes)
                else:
                    with span("model.mlp"):
                        y = P["mlp"](h, *w["mlp"])
                with span("model.hc", li=li, site="ffn"):
                    xs = P["hc_post"](xs, y, post, res)
        with span("model.head") as sp:
            if sp is not None:
                sp.attrs["key"] = self._program_key("head", rows, b_pad)
            fh = core.final_hc
            head_w = (fh.phi._data, fh.b._data, fh.alpha._data,
                      core.norm.weight._data,
                      self.model.lm_head.weight._data)
            got = logits_epilogue(
                xs, rows, lambda xr: P["head"](xr, *head_w), logits_rows,
                rows_pad=b_pad)
            extra = jnp.stack(counts) if counts else None
            if logits_rows is None:
                return _step_logits(got, b, extra, self._note_counts)
            last, full = got
            return _step_logits(last, b, extra, self._note_counts), \
                Tensor(full)
