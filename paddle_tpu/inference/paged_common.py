"""What every paged serving adapter shares, whatever the model's anatomy:
the scheduler-protocol booking (``alloc`` / ``free`` / prefix attach /
swap) over a list of per-layer pools, the dispatch accounting the
scheduler reports, the packed-step row plan (validation, the flat padded
token axis, positions, the power-of-two right-alignment of rows for the
ragged kernels) and the logits epilogue. ``PagedLlamaAdapter`` (K/V pages,
GQA) and ``PagedXing4Adapter`` (latent pages, MLA) are both built on it.

The adapter contract ``BatchScheduler`` drives (docs/SERVING.md):
``caches`` (one pool a layer, each with ``num_pages`` / ``num_free_pages``
/ ``seq_len`` / the booking calls), ``alloc``, ``free``,
``prefill_chunk(token_ids, seq_ids, start_positions, pad_to=)``,
``decode_token(token_ids, seq_ids)``, ``max_length``, ``compile_count``;
the rest (``attach_prefix``, ``seq_page_chains``, ``swap_out`` /
``swap_in``, ``attend_program_count``) is found by ``getattr``.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..framework import telemetry
from ..framework.core import Tensor
from ..ops.kernels.paged_attention import (
    packed_position_index as _packed_position_index,
)

__all__ = ["PagedAdapterBase", "PackedRows", "StepLogits", "step_logits",
           "pow2",
           "right_align_plan_np", "plan_packed_rows", "logits_epilogue"]


def pow2(n: int) -> int:
    return 1 << (max(int(n), 1) - 1).bit_length()


def right_align_plan_np(row_indices, starts, counts, t_pad, rows_pad):
    """Host-built gather/scatter plan right-aligning each listed
    packed row into a (rows_pad, t_pad) block: returns (gm, mr, mc,
    mflat) — ``gm`` gathers flat packed token indices into the block
    (row r's last counts[i] columns), and ``mr``/``mc``/``mflat``
    map the kernel output back to flat packed slots. int32 numpy
    arrays."""
    gm = np.zeros((rows_pad, t_pad), np.int32)
    rr, cc, ff = [], [], []
    for r, i in enumerate(row_indices):
        c = counts[i]
        st = starts[i]
        gm[r, t_pad - c:] = np.arange(st, st + c)
        for j in range(c):
            rr.append(r)
            cc.append(t_pad - c + j)
            ff.append(st + j)
    return (gm, np.asarray(rr, np.int32), np.asarray(cc, np.int32),
            np.asarray(ff, np.int32))


class PackedRows:
    """One packed step's rows on the host: ``counts`` / ``lens0`` (tokens
    fed and tokens cached, a row), ``flat`` (token ids padded to
    ``pad_to``), ``pos_np`` (each packed token's position), ``starts`` /
    ``last_idx`` (a row's first and last packed slot), ``n_real``,
    ``pad_to`` and ``mp_pad`` (the page table's padded width)."""

    __slots__ = ("b", "counts", "lens0", "flat", "pos_np", "starts",
                 "last_idx", "n_real", "pad_to", "mp_pad")


def plan_packed_rows(cache, token_ids, seq_ids, start_positions, pad_to,
                     max_length) -> PackedRows:
    """Validate a ``prefill_chunk`` call against the first layer's pool
    and lay its rows on one flat token axis padded to ``pad_to``."""
    r = PackedRows()
    r.b = b = len(seq_ids)
    r.counts = counts = [len(t) for t in token_ids]
    if b != len(counts) or b == 0:
        raise ValueError(
            f"prefill_chunk: {len(counts)} token rows for {b} "
            "sequences")
    if min(counts) < 1:
        raise ValueError(
            "prefill_chunk: every row must carry at least one token "
            f"(counts={counts})")
    r.lens0 = lens0 = [cache.seq_len(s) for s in seq_ids]
    if start_positions is not None:
        sp = [int(p) for p in start_positions]
        if sp != lens0:
            raise ValueError(
                f"prefill_chunk: start_positions {sp} disagree with "
                f"the cached lengths {lens0} — a chunk must resume "
                "exactly where the cache left off")
    over = [s for s, n, c in zip(seq_ids, lens0, counts)
            if n + c > max_length]
    if over:
        raise ValueError(
            f"sequences {over} would exceed max_length="
            f"{max_length}; positions beyond it cannot be "
            "rotary-encoded")

    flat = np.concatenate(
        [np.asarray(t, "int64") for t in token_ids])
    r.n_real = n_real = int(flat.shape[0])
    r.pad_to = pad_to = int(pad_to) if pad_to else n_real
    if pad_to < n_real:
        raise ValueError(
            f"prefill_chunk: pad_to={pad_to} below the packed token "
            f"count {n_real}")
    r.flat = np.concatenate(
        [flat, np.zeros(pad_to - n_real, "int64")])
    r.pos_np = pos_np = np.zeros(pad_to, np.int32)
    r.starts = starts = np.zeros(b, np.int64)
    off = 0
    for i, (n, c) in enumerate(zip(lens0, counts)):
        starts[i] = off
        pos_np[off:off + c] = np.arange(n, n + c)
        off += c
    r.last_idx = starts + np.asarray(counts) - 1
    # every layer's cache shares one page size (adapter construction),
    # so the padded page-table width is loop-invariant
    r.mp_pad = pow2(max(
        cache.table_pages(n + c) for n, c in zip(lens0, counts)))
    return r


def logits_epilogue(x, rows: PackedRows, head, logits_rows=None,
                    rows_pad=None):
    """The step's sampling epilogue over packed activations ``x`` (a raw
    array, [N, ...]): ``head(x[last slot of every row])`` -> (B, vocab),
    and for ``logits_rows`` (speculative VERIFY rows) also the logits of
    every position of those rows, concatenated in list order.
    ``rows_pad`` pads the gather to that many rows (slot 0 again), so
    that the head's program is keyed by a bucketed row count; the caller
    reads the first B rows."""
    idx = np.asarray(rows.last_idx, np.int32)
    if rows_pad and rows_pad > idx.shape[0]:
        idx = np.concatenate(
            [idx, np.zeros(rows_pad - idx.shape[0], np.int32)])
    last = head(x[jnp.asarray(idx, jnp.int32)])
    if logits_rows is None:
        return last
    vidx = _packed_position_index(rows.starts, rows.counts, logits_rows)
    return last, head(x[vidx])


class StepLogits(Tensor):
    """A step's logits as an adapter whose head runs on a bucketed row
    count hands them back, with what rides their pull: ``numpy()`` cuts
    the padding rows on the host and, where the step has some
    (``extra``: Xing4's per-layer expert counts), fetches them in the
    same transfer and hands them to ``note``."""

    def numpy(self):
        """The first ``rows`` rows (the head runs on a bucketed row
        count; the padding is cut on the host, where it costs no
        program)."""
        extra, note = self._extra, self._note
        if extra is None:
            return np.asarray(jax.device_get(self._data))[:self._rows]
        logits, counts = jax.device_get((self._data, extra))
        self._extra = None
        note(counts)
        return np.asarray(logits)[:self._rows]


def step_logits(data, rows, extra=None, note=None):
    out = StepLogits(data)
    out._rows, out._extra, out._note = rows, extra, note
    return out


class PagedAdapterBase:
    """Scheduler-protocol booking and dispatch accounting over
    ``self.caches`` (one pool a layer)."""

    def _init_dispatch_accounting(self):
        # chunked-prefill dispatch accounting (docs/SERVING.md):
        # _dispatch_shapes holds the distinct BUCKETED packed token
        # counts prefill_chunk has been fed — each is one compiled
        # ragged program, so len() is the steady-state compile count
        # the scheduler and bench report; _kernel_shapes tracks the
        # (kind, rows, T, max_pages) signatures of the pow2-padded
        # attention sub-calls underneath.
        self._dispatch_shapes = set()
        self._kernel_shapes = set()
        self._bucket_programs = {}   # pad_to -> set of kernel shapes
        # attend_calls counts a step's per-layer attend dispatches,
        # layer_programs those that ran the whole layer as one program,
        # few_row_rows the rows of those calls that the ragged kernel
        # attended in its few-row form
        self.chunk_stats = {"calls": 0, "packed_tokens": 0,
                            "padded_tokens": 0, "attend_calls": 0,
                            "layer_programs": 0, "few_row_rows": 0}
        self._reg = telemetry.registry()

    def _count_packed_step(self, rows: PackedRows):
        self._dispatch_shapes.add(rows.pad_to)
        self.chunk_stats["calls"] += 1
        self.chunk_stats["packed_tokens"] += rows.n_real
        self.chunk_stats["padded_tokens"] += rows.pad_to - rows.n_real

    def _count_kernel_shape(self, pad_to, shape):
        self._kernel_shapes.add(shape)
        self._bucket_programs.setdefault(pad_to, set()).add(shape)

    @property
    def compile_count(self) -> int:
        """Distinct bucketed packed shapes the ragged chunked-prefill
        dispatch has compiled (<= number of configured buckets in
        steady state)."""
        return len(self._dispatch_shapes)

    @property
    def attend_program_count(self) -> int:
        """Distinct paged-attention kernel programs the packed step
        dispatch has compiled: ONE ragged program per packed config,
        whatever kinds of row it mixes."""
        return len(self._kernel_shapes)

    @property
    def attend_kinds_by_bucket(self) -> dict:
        """Per dispatch bucket (pad_to): the distinct attend KERNEL
        KINDS its steps launched — exactly {'ragged'},
        {'ragged_fused'} or {'latent_ragged'} per bucket: one attend
        program per bucket."""
        return {b: sorted({k for k, *_ in shapes})
                for b, shapes in self._bucket_programs.items()}

    # -- expert counts -----------------------------------------------------
    def _note_counts(self, counts):
        """counts [expert layers, E] of one step, on the host."""
        counts = np.asarray(counts)
        if counts.size == 0:
            return
        nums = {
            "calls": int(counts.shape[0]),
            "assignments": int(counts.sum()),
            "experts_touched": int((counts > 0).sum()),
            "expert_tokens_max": int(counts.max(-1).sum()),
            "expert_tokens_mean": float(counts.mean(-1).sum()),
        }
        reg = self._reg
        if reg is not None:
            reg.inc("moe.calls", nums["calls"])
            reg.inc("moe.assignments", nums["assignments"])
            reg.inc("moe.experts_touched", nums["experts_touched"])
            reg.inc("moe.expert_tokens_max", nums["expert_tokens_max"])
            reg.inc("moe.expert_tokens_mean", nums["expert_tokens_mean"])
        with telemetry.span("moe.counts", **nums):
            pass

    # -- scheduler protocol ------------------------------------------------
    def alloc(self, seq_id):
        for c in self.caches:
            c.alloc(seq_id)

    def free(self, seq_id):
        for c in self.caches:
            c.free(seq_id)

    # -- prefix-cache hooks (inference/prefix_cache.py) --------------------
    def attach_prefix(self, seq_id, chains, length):
        """Cached prefill: register ``seq_id`` on shared page chains
        (one per layer) covering its first ``length`` tokens. The
        pages stay shared until the sequence's first write into the
        partial tail page, which the pool forks copy-on-write."""
        if len(chains) != len(self.caches):
            raise ValueError(
                f"{len(chains)} chains for {len(self.caches)} layers")
        for c, chain in zip(self.caches, chains):
            c.attach(seq_id, chain, length)

    def seq_page_chains(self, seq_id):
        """The sequence's physical page chain per layer — what the
        scheduler hands the radix tree at retire."""
        return [c.seq_pages(seq_id) for c in self.caches]

    # -- preemption hooks (tiered KV swap; docs/SERVING.md) ----------------
    def swap_out(self, seq_id, space):
        """Page the sequence out of EVERY layer pool into the shared
        host swap space (scheduler preemption). Returns
        (pages_freed, nbytes_swapped) summed across layers."""
        freed = nbytes = 0
        for c in self.caches:
            fp, nb = c.swap_out(seq_id, space)
            freed += fp
            nbytes += nb
        return freed, nbytes

    def swap_in(self, seq_id, space):
        """Restore a swapped-out sequence into every layer pool
        (bitwise). Returns pages restored from host."""
        return sum(c.swap_in(seq_id, space) for c in self.caches)
