"""Continuous-batching decode scheduler over the paged KV cache.

Upstream analog: the serving role of
paddle/fluid/operators/fused/fused_multi_transformer_op.cu plus the
request batching that PaddleNLP's serving stack layers on top of it.
TPU-native design: the attention per step is ONE paged-attention Pallas
kernel call over the whole active batch (static shapes; ragged context
lengths live in the page table + seq_lens, not in the tensor shapes),
and the scheduler is host-side bookkeeping only.

Token-level continuous batching (Orca-style): every scheduler step
advances each active sequence — sampled tokens for sequences in
decode, prompt tokens for sequences still in prefill — so arrivals
and completions interleave freely without padding the batch to a
common length.

Chunked prefill (Sarathi-style, default when the model implements
``prefill_chunk``): instead of one prompt token per step, each step
packs EVERY active decode row plus up to ``prefill_chunk_tokens``
pending prompt tokens (split across sequences, resuming mid-prompt)
into ONE ragged model call — multi-token rows ride the paged prefill
kernel, single-token rows the decode kernel. The packed token count
is padded up to a bucket from ``FLAGS_serving_buckets``
(:func:`bucket_packed_tokens`) so steady-state serving compiles at
most len(buckets) ragged programs. Decode rows keep advancing one
token per step (latency stays flat) while prefill saturates the chip;
a 432-token prompt costs ceil(432/budget) steps instead of 432.

Generation by diffusion over blocks (a model that declares
``block_length``; models/sdar.py, docs/SERVING.md): a DECODE row's state
is its open block's B ids — MASK at the positions not fixed yet — and,
behind it, the ids of the finished block whose K/V is not in the pages
yet (none for a request's first block). Every step the row feeds its
open block and gets back, a position, the best token and its
probability, chosen on the device; the pass fixes positions by the
``remasking`` rule and the open block's slots are rolled back. The pass
that fixes a block's last position delivers its B tokens together; a
request that ends there retires, and its last block's K/V is never
written. Otherwise the block rides the NEXT block's first pass as one
row of 2B tokens (the kernel's mask lets a later block of a row see an
earlier one whole), which leaves its K/V in the pages: the one clean
pass of the published generator, with no pass of its own. So a row
receives B tokens every ``denoising_steps`` passes at most
(:meth:`BatchScheduler._step_block`).

Admission control: a request is admitted only while (a) the active
batch is below ``max_batch_size`` and (b) the page pool would stay
under the high watermark after reserving the request's worst-case page
need (prompt + max_new_tokens, across every layer's cache). This is
what keeps a burst of long prompts from deadlocking the pool mid-
generation.

Page sanitizer (``FLAGS_page_sanitizer=warn|strict``): every pool the
model serves from mirrors its mutations into a shadow heap
(incubate/nn/page_sanitizer.py), and the scheduler runs an epoch
cross-check every ``FLAGS_page_sanitizer_stride`` steps — shadow vs.
real refcounts/free-list/lens plus, in strict mode,
``assert_ref_invariants()`` on every cache. ``page_pool_stats()``
reports the event/violation counters under ``"sanitizer"``. Off (the
default) costs one attribute check per stride.

Prefix caching (``prefix_cache=True``): a radix tree over token ids
(inference/prefix_cache.py) remembers retired sequences' KV pages. On
admission the prompt is matched against the tree, the matched page
chains are pinned and ATTACHED (shared, refcounted — see
incubate/nn/paged_cache.py), and prefill starts at the first uncached
token; the worst-case reservation shrinks by the full pages the hit
covers, so admission control stays deadlock-free. On retire the
sequence's cached tokens are inserted into the tree instead of dying
with the sequence, and an LRU-by-leaf evictor reclaims unpinned
cached pages whenever admission would otherwise cross the watermark.

Overload survival (docs/SERVING.md "Overload behavior"): capacity
pressure means SLOWER, never FAILED. The submit queue is bounded
(``FLAGS_serving_max_queue`` -> :class:`QueueFullError` backpressure)
and ordered by per-request ``priority`` (FIFO within a priority;
``max_inflight_per_tenant`` caps any one tenant's active share). When
admission cannot reserve pages for a request even after prefix-cache
eviction, the scheduler PREEMPTS strictly-lower-priority victims
(lowest priority, then most pages held, then least progress) instead
of blocking behind them: a victim's private KV pages swap out
BITWISE to the host tier (``HostKVSwapSpace``,
``FLAGS_serving_swap_bytes``; shared prefix pages stay on-device
under swap holds — pins block eviction of shared pages, never the
swap of private ones) and restore bitwise on re-admission, which is
just another packed prompt resume through the ragged chunked-prefill
path. Per-request deadlines (``deadline_s``) abort expired work at
step boundaries into the distinct ``aborted_deadline`` terminal
state, releasing every reservation (queued, active mid-prefill, or
swapped-out alike). Admission failures are counted DISTINCTLY
(``admit_reject_pool`` vs ``admit_evict_then_admit`` vs
``admit_preempt_then_admit`` vs ``admit_reject_queue_full`` vs
``aborted_deadline``) so goodput/SLO attainment stays truthful under
overload — aborted requests count as SLO misses in the goodput
window. A deterministic fault-injection harness
(incubate/nn/fault_injection.py, ``FLAGS_serving_faults``) perturbs
the scheduler at step boundaries only — forced pool exhaustion,
preemption storms, delayed swap-in, simulated step failure with
retry/backoff — and every fault must be absorbed with greedy outputs
bit-identical to an uninjected run.

Telemetry (``FLAGS_telemetry=metrics|trace``; framework/telemetry.py):
the scheduler is the primary producer of the ``serving.*`` registry
namespace — per-request TTFT / TPOT / queue-wait / retire-latency
histograms and token/request counters, surfaced through
:meth:`BatchScheduler.metrics` as ONE namespaced snapshot (pool,
prefix and sanitizer counters fold into the same shape; the legacy
``page_pool_stats()`` keys stay as aliases). While spans are live
(trace mode, a profiler RECORD window, or a ``jax.profiler`` session —
decided at each call, not at construction) every step additionally
records nested wall spans — ``serving.step`` > ``serving.admit`` /
``serving.pack`` / ``serving.prefill_chunk`` (the model call alone) /
``serving.logits_pull`` (the wait for the device and the copy) /
``serving.decode`` / ``serving.retire`` — into the telemetry ring
(Chrome-trace exportable) and, under a session, into the device
trace. Off (the default) allocates nothing and costs one
probe per site; all timing goes through
``telemetry.clock()`` — tools/lint_codebase.py's clock-discipline
rule bans direct ``time.*`` reads in this module.

Performance ledger + flight recorder (ISSUE 12;
framework/perf_ledger.py, framework/flight_recorder.py): under live
metrics the scheduler stamps every ragged model call into
``exec.wall_s.prefill_chunk`` / ``exec.wall_s.decode_token``
histograms, and :meth:`BatchScheduler.metrics` surfaces the ledger's
per-program plan-vs-actual rows under ``"ledger"`` (attained
flops/s, MFU, bytes/s, step-wall share, plan drift). The
``ledger.*`` gauges republish every watchdog stride so the
``plan-drift`` detector stays registry-read-only, and with
``FLAGS_telemetry_incident_dir`` set every watchdog fire (or an
explicit :meth:`BatchScheduler.dump_incident`) writes one atomic
incident bundle capturing the trip's own evidence.

Live ops plane (ISSUE 15; framework/ops_server.py,
docs/OBSERVABILITY.md "Live ops plane"): with
``FLAGS_ops_server_port`` set the scheduler starts the process-wide
read-only debug server (``/metrics``, ``/statusz``, ``/tracez``,
``/planz``, ``/flagz``, ``/incidentz``) and registers its own
``/statusz`` section. Every request carries a serializable
:class:`telemetry.TraceContext` (created at :meth:`submit`, or
adopted via ``Request(trace_ctx=...)``): request-scoped spans
(preempt/swap-in/retire) record under it, the serialized context is
pinned to the request's page chains and rides the swap records, so
one request renders as ONE stitched trace across preemption round
trips, asyncio executor hops, and the future prefill/decode worker
split; TTFT/TPOT observations attach the trace id as an OpenMetrics
exemplar.
"""
from __future__ import annotations

import collections
import warnings
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence

import numpy as np

from ..framework import concurrency as _concurrency
from ..framework import telemetry
from ..framework.flags import flag
from ..framework.telemetry import NULL_SPAN as _NULL

__all__ = ["Request", "BatchScheduler", "RequestState",
           "bucket_packed_tokens", "QueueFullError"]

# scheduler uid sequence: the namespaced serving.compile_count.<uid>
# gauges (two schedulers must never overwrite each other's program
# counts — the old shared gauge was last-writer-wins and stays only
# as an alias)
_SCHED_SEQ = [0]  # concurrency: single-writer


def _pages_for(cache, n) -> int:
    """Pages a sequence of ``n`` tokens holds at most over its life, as
    its pool says (``PagedKVCacheManager.pages_for``: a window-and-summary
    pool holds one window's pages and the summary pages, not one a
    ``page_size`` tokens); a pool that does not say holds one a
    ``page_size`` tokens."""
    ask = getattr(cache, "pages_for", None)
    return ask(n) if ask is not None else -(-int(n) // cache.page_size)


class QueueFullError(RuntimeError):
    """submit() backpressure: the bounded queue
    (``FLAGS_serving_max_queue`` / ``max_queue=``) is at capacity —
    the caller should shed load or retry later (the counted-distinct
    ``serving.admit_reject_queue_full`` signal)."""


def _parse_buckets(spec) -> tuple:
    """Normalize a bucket spec ('8,16,64' / iterable of ints) into a
    sorted tuple of positive ints."""
    if isinstance(spec, str):
        vals = [int(s) for s in spec.replace(" ", "").split(",") if s]
    else:
        vals = [int(v) for v in spec]
    if not vals or min(vals) < 1:
        raise ValueError(f"invalid serving bucket spec {spec!r}")
    return tuple(sorted(set(vals)))


def bucket_packed_tokens(n: int, buckets=None) -> int:
    """Round a packed ragged token count up to the smallest configured
    bucket (FLAGS_serving_buckets by default). Every packed feed the
    scheduler hands the model goes through here — padding to a small
    fixed shape set is what bounds steady-state XLA compiles to
    len(buckets) programs (enforced by tools/lint_codebase.py).
    Counts beyond the largest bucket round up to the next power of
    two, each such shape costing one extra compile."""
    buckets = _parse_buckets(
        flag("serving_buckets") if buckets is None else buckets)
    n = int(n)
    if n < 1:
        raise ValueError(f"cannot bucket a packed count of {n}")
    for b in buckets:
        if n <= b:
            return b
    return 1 << (n - 1).bit_length()


def _accepts_logits_rows(model) -> bool:
    """True when ``model.prefill_chunk`` exposes the per-position
    logits epilogue (``logits_rows=`` keyword) the unified ragged
    speculative step samples verify windows from."""
    fn = getattr(model, "prefill_chunk", None)
    if fn is None:
        return False
    try:
        import inspect

        return "logits_rows" in inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False


class RequestState:
    QUEUED = "queued"
    PREFILL = "prefill"
    DECODE = "decode"
    # preempted: KV paged out to the host tier, awaiting re-admission
    SWAPPED = "swapped"
    FINISHED = "finished"
    # terminal, DISTINCT from finished: the deadline expired before
    # completion and every reservation was released
    ABORTED_DEADLINE = "aborted_deadline"
    # the request was handed off to a decode worker
    # (export_request): its KV page chains left this box over the
    # HostKVSwapSpace wire format — gone locally, live remotely
    MIGRATED = "migrated"


@dataclass
class Request:
    """One generation request.

    ``on_token(request, token_id, is_prompt)`` fires for every token
    the scheduler commits for this request — the streaming-detokenize
    hook (called on the host thread; keep it cheap)."""

    req_id: str
    prompt_ids: List[int]
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    on_token: Optional[Callable] = None
    # overload-survival knobs: admission orders by priority (higher
    # wins; FIFO within), preemption only ever evicts STRICTLY
    # lower-priority victims; tenant feeds max_inflight_per_tenant;
    # deadline_s (seconds from submit) aborts expired work at step
    # boundaries into the aborted_deadline terminal state
    priority: int = 0
    tenant: str = "default"
    deadline_s: Optional[float] = None
    # trace identity (framework/telemetry.py TraceContext): None
    # under FLAGS_telemetry=off; auto-created at submit otherwise,
    # or adopted from an ingress — pass a TraceContext (or its
    # to_wire() string, e.g. extracted from a front-end carrier) and
    # every span/lane event of this request stitches to that trace
    # id, across preemption round trips and worker hops
    trace_ctx: Optional[object] = None
    state: str = RequestState.QUEUED
    generated_ids: List[int] = field(default_factory=list)
    _pos: int = 0  # prompt tokens consumed so far
    _prefix_hit: int = 0  # prompt tokens served from the prefix cache
    _prefix_path: tuple = ()  # pinned radix nodes (unpinned at retire)
    _order: int = 0  # submit sequence number (FIFO within priority)
    _t_deadline: float = 0.0  # absolute clock deadline (0 = none)
    _preemptions: int = 0  # times this request was swapped out
    # telemetry timestamps (telemetry.clock(); 0.0 = never stamped —
    # only written when the scheduler's registry handle is live)
    _t_submit: float = 0.0
    _t_last_tok: float = 0.0
    # per-request SLO measurements (set only under live metrics):
    # TTFT, queue wait, and every inter-token gap — the inputs to
    # SLOConfig.request_meets at retire
    _ttft: Optional[float] = None
    _qwait: Optional[float] = None
    _gaps: Optional[List[float]] = None
    # the open block of a DECODE row over a block-diffusion model
    _block: Optional["_Block"] = None

    @property
    def finished(self) -> bool:
        return self.state == RequestState.FINISHED

    @property
    def terminal(self) -> bool:
        """Finished OR deadline-aborted — the request left the
        scheduler either way (both land in ``result()``)."""
        return self.state in (RequestState.FINISHED,
                              RequestState.ABORTED_DEADLINE)

    def total_tokens(self) -> int:
        return len(self.prompt_ids) + self.max_new_tokens


class _Block:
    """A DECODE row's open block over a block-diffusion model: its B
    current ``ids`` (MASK where ``masked``), how many leading positions
    are the prompt's tail (never masked: the row's own state, a prompt or
    chosen token may equal the MASK id), the denoising passes made, and
    ``behind``: the ids of the finished block before it while its K/V is
    not in the pages (the open block's first pass carries them)."""

    __slots__ = ("ids", "masked", "n_prompt", "passes", "behind")

    def __init__(self, tail, size, mask_id, behind=()):
        self.n_prompt = len(tail)
        self.ids = list(tail) + [mask_id] * (size - len(tail))
        self.masked = [False] * len(tail) + [True] * (size - len(tail))
        self.passes = 0
        self.behind = list(behind)


REMASKING = ("low_confidence_dynamic", "low_confidence_static", "sequential")


class BatchScheduler:
    """Drives a paged decoder model with continuous batching.

    ``model`` must provide the paged-serving protocol:
      * ``alloc(seq_id)`` / ``free(seq_id)`` — per-sequence cache slots
      * ``decode_token(token_ids, seq_ids) -> logits (B, vocab)`` — one
        token per listed sequence through the paged-attention kernel
      * ``caches`` — iterable of PagedKVCacheManager (for the
        admission watermark; one per layer)
    """

    def __init__(self, model, max_batch_size=32, page_watermark=0.95,
                 sampler=None, draft_model=None, draft_k=4,
                 prefix_cache=None, chunked_prefill=None,
                 prefill_chunk_tokens=None, serving_buckets=None,
                 prefix_align=1, slo=None, watchdog=None,
                 max_queue=None, max_inflight_per_tenant=None,
                 preempt=None, swap_bytes=None, fault_injector=None,
                 denoising_steps=4, remasking="low_confidence_dynamic",
                 confidence_threshold=0.9):
        self.model = model
        self.max_batch_size = int(max_batch_size)
        self.page_watermark = float(page_watermark)
        self.sampler = sampler or (lambda logits: int(np.argmax(logits)))
        self._queue = collections.deque()
        self._active = {}
        self._finished = {}
        # chunked prefill (module docstring): None -> auto (on when
        # the model implements prefill_chunk), True/False force.
        # Models that only speak decode_token keep the token-per-step
        # path — also the oracle the chunked tests pin against.
        if chunked_prefill is None:
            chunked_prefill = hasattr(model, "prefill_chunk")
        if chunked_prefill and not hasattr(model, "prefill_chunk"):
            raise ValueError(
                "chunked_prefill=True but the model has no "
                "prefill_chunk(token_ids, seq_ids, start_positions) "
                "entry (see PagedLlamaAdapter)")
        self.chunked_prefill = bool(chunked_prefill)
        self.prefill_chunk_tokens = max(1, int(
            flag("prefill_chunk_tokens")
            if prefill_chunk_tokens is None else prefill_chunk_tokens))
        self.serving_buckets = _parse_buckets(
            serving_buckets if serving_buckets is not None
            else flag("serving_buckets"))
        # capacity apply seam (framework/autotuner.py): knob changes
        # land only BETWEEN steps — apply_capacity_config refuses to
        # run while this is True
        self._in_step = False
        # speculative decoding (ISSUE 19): verify windows ride the
        # ordinary packed prefill_chunk step as (k+1)-token rows, so a
        # decode round is two bucketed ragged programs (draft propose +
        # target verify). Needs the chunked step on both adapters and
        # the per-position logits epilogue on the target.
        if draft_model is not None:
            missing = []
            if not hasattr(draft_model, "prefill_chunk"):
                missing.append("the draft adapter has no prefill_chunk")
            if not hasattr(model, "prefill_chunk"):
                missing.append("the target adapter has no prefill_chunk")
            elif not _accepts_logits_rows(model):
                missing.append(
                    "the target's prefill_chunk takes no logits_rows=")
            elif not self.chunked_prefill:
                missing.append("chunked_prefill=False was given")
            if missing:
                raise ValueError(
                    "speculative decoding packs its verify windows as "
                    "rows of the chunked step: " + "; ".join(missing))
        self.chunk_stats = {
            "steps": 0, "chunk_calls": 0, "prefill_tokens": 0,
            "decode_tokens": 0, "packed_tokens": 0, "padded_tokens": 0,
        }
        # cross-request prefix KV cache (inference/prefix_cache.py):
        # True builds a RadixPrefixCache over the model's own caches;
        # a window-and-summary pool (page_format="eva") keeps two chains
        # a sequence and releases pages behind its window: nothing that
        # takes a sequence for one chain as long as its tokens serves it
        # generation by diffusion over blocks (module docstring): the
        # block length and the MASK id are the model's, the three knobs
        # the model card's command's (its defaults)
        self.block_length = int(getattr(model, "block_length", 0) or 0)
        if self.block_length:
            self._init_block(denoising_steps, remasking,
                             confidence_threshold, prefix_cache,
                             draft_model, preempt)
        eva = any(getattr(c, "eva", False) for c in model.caches)
        if eva and (prefix_cache or draft_model is not None or preempt):
            raise ValueError(
                "page_format='eva' pools serve no prefix cache (no reuse "
                "across a roll), no speculative draft (truncate across a "
                "roll) and no preemption (no swap record of a window and "
                "its summaries): got prefix_cache="
                f"{bool(prefix_cache)} draft_model="
                f"{draft_model is not None} preempt={preempt}")
        # or pass a pre-built instance (shared across schedulers)
        if prefix_cache:
            if prefix_cache is True:
                from .prefix_cache import RadixPrefixCache

                prefix_cache = RadixPrefixCache(list(model.caches))
        else:
            prefix_cache = None
        self.prefix_cache = prefix_cache
        # chunk-aligned prefix lookups (prefix_cache.match(align=...)):
        # align=page_size makes every cached-prefill resume start at a
        # page boundary, trading <= align-1 hit tokens for never
        # paying the shared-tail COW draw the reservation must
        # otherwise hold (docs/SERVING.md). align=1 keeps mid-page
        # resumes (the default; chunked prefill handles both).
        self.prefix_align = max(1, int(prefix_align))
        # (req_id, tree mutation count) -> PrefixMatch: avoids
        # re-walking the tree for a head-of-queue request blocked on
        # admission across steps (see _try_admit)
        self._match_memo = None
        self.prefix_stats = {
            "requests": 0, "request_hits": 0,
            "prompt_tokens": 0, "hit_tokens": 0,
            "inserted_tokens": 0,
        }
        # speculative decoding (upstream: the serving role of
        # fused_multi_transformer's draft-verify deployments): a small
        # draft adapter proposes draft_k tokens per sequence per round;
        # the target verifies the whole window as one row of its
        # packed step. Greedy acceptance — output token-identical to the
        # non-speculative scheduler. Batch>1 is native: per-row
        # acceptance lengths live in the paged caches' per-sequence
        # lens (rejections roll back with cache.truncate).
        self.draft = draft_model
        self.draft_k = int(draft_k)
        if draft_model is not None and sampler is not None:
            raise ValueError(
                "speculative scheduling is greedy-only (a custom "
                "sampler would break the token-identity guarantee); "
                "use models.speculative_generate for sampled "
                "speculative decoding")
        self.spec_stats = {"rounds": 0, "target_calls": 0,
                           "draft_calls": 0, "committed_tokens": 0,
                           "proposed_tokens": 0,
                           "accepted_draft_tokens": 0,
                           "refill_tokens": 0, "draft_discards": 0}
        # overload survival (module docstring "Overload survival"):
        # bounded submit queue + per-tenant in-flight cap + sequence
        # preemption onto the host swap tier + deadline aborts
        self.max_queue = int(flag("serving_max_queue")
                             if max_queue is None else max_queue)
        self.max_inflight_per_tenant = (
            None if max_inflight_per_tenant is None
            else max(1, int(max_inflight_per_tenant)))
        self._submit_seq = 0
        self._swapped = {}  # req_id -> Request (insertion = FIFO)
        # admission fast-path latches: until a nonzero priority (or a
        # deadline) is ever submitted, candidate picking stays the
        # O(1) FIFO head and the per-step deadline sweep is skipped —
        # the defaults cost nothing extra under a deep backlog
        self._plain_fifo = True
        self._deadline_seen = False
        preempt = bool(flag("serving_preempt")
                       if preempt is None else preempt) \
            and not eva and not self.block_length
        swap_bytes = int(flag("serving_swap_bytes")
                         if swap_bytes is None else swap_bytes)
        self.swap_space = None
        if preempt and swap_bytes > 0:
            # a draft adapter keeps its OWN KV pool, which never swaps:
            # the draft KV is disposable — discarded at swap-out and
            # re-prefilled from the committed prefix at swap-in
            from ..incubate.nn.paged_cache import HostKVSwapSpace

            self.swap_space = HostKVSwapSpace(swap_bytes)
        self._preempt_enabled = self.swap_space is not None
        # deterministic fault injection (fault_injection.py): None
        # (the default, empty FLAGS_serving_faults) costs one is-None
        # check per step and imports nothing
        if fault_injector is None:
            spec = str(flag("serving_faults"))
            if spec.strip():
                from ..incubate.nn.fault_injection import FaultInjector

                fault_injector = FaultInjector(spec)
        self._faults = fault_injector
        self._fault_step = 0
        self._consec_fails = 0
        self._resume_at = 0
        self._step_extras = {}
        self._admitted_step = 0
        # page-sanitizer epoch cross-check (page_sanitizer.py): every
        # stride steps, shadow-vs-real on every cache; strict-mode
        # pools also run assert_ref_invariants there
        self._san_stride = max(1, int(flag("page_sanitizer_stride")))
        self._san_steps = 0
        # runtime telemetry (framework/telemetry.py): mode read HERE,
        # like the sanitizer — off holds None handles and every
        # instrumented site below pays one `is None` check. SPANS are
        # the exception: _span/_req_span ask telemetry at call time,
        # so a profiler session that starts later is honoured;
        # _tracer only says whether trace mode was on at construction
        # (request trace contexts, the flight recorder's ring)
        self._metrics = telemetry.registry()
        self._tracer = telemetry.tracer()
        # per-request trace assembly (trace mode / armed profiler
        # window): submit -> admit -> prefill chunks -> tokens ->
        # retire timelines, bounded by FLAGS_telemetry_request_traces
        self._traces = telemetry.request_traces()
        # request-lifecycle accounting (PR 8): step-epoch window
        # anchor, SLO/goodput window, watchdogs, periodic Prometheus
        # export — ALL of it exists only under live metrics (off
        # allocates nothing beyond these None handles).
        # _step_epoch mirrors the REGISTRY-owned monotonic epoch (two
        # schedulers share one stamp); _steps counts THIS scheduler's
        # iterations (throughput + stride accounting)
        self._step_epoch = 0
        self._steps = 0
        self._slo = None
        self._slo_window = None
        self._watchdog = None
        self._export_path = None
        self._t_start = 0.0
        # performance ledger + incident flight recorder (ISSUE 12):
        # both exist only under live metrics — the off path holds
        # None handles and never imports either module
        self._ledger = None
        self._recorder = None
        _SCHED_SEQ[0] += 1
        self._sched_uid = "s%d" % _SCHED_SEQ[0]
        # host-plane concurrency sanitizer (framework/concurrency.py):
        # the submit queue and the active/finished/swapped maps are
        # single-writer BY CONTRACT (the thread driving the step loop
        # also submits); the registered vars turn a second writer
        # thread — the async-engine hazard — into a journaled
        # violation, while scrape-thread reads of the /statusz
        # provider stay unchecked GIL-atomic snapshots. Off mode
        # holds None handles: one `is not None` check per site.
        self._csan = _concurrency.sanitizer()
        if self._csan is None:
            self._cv_queue = None
            self._cv_state = None
        else:
            self._cv_queue = self._csan.shared(
                "serving.%s.queue" % self._sched_uid, owner=self,
                single_writer=True)
            self._cv_state = self._csan.shared(
                "serving.%s.state" % self._sched_uid, owner=self,
                single_writer=True)
        if self._metrics is None:
            if slo is not None or watchdog is not None:
                warnings.warn(
                    "BatchScheduler got an explicit "
                    + " and ".join(
                        n for n, v in (("slo=", slo),
                                       ("watchdog=", watchdog))
                        if v is not None)
                    + " but FLAGS_telemetry is off — no SLO "
                    "accounting or watchdog checks will run (set "
                    "FLAGS_telemetry=metrics|trace)",
                    RuntimeWarning, stacklevel=2)
        else:
            self._t_start = telemetry.clock()
            # join the shared stamp where it stands: trace events
            # recorded before this scheduler's first step must not
            # rewind behind samples other schedulers already stamped
            self._step_epoch = self._metrics.epoch
            self._win = max(1, int(flag("telemetry_window")))
            cfg = slo if slo is not None \
                else telemetry.SLOConfig.from_flag()
            self._slo = cfg if cfg.enabled() else None
            # (epoch, met_all, {slo: met}) per retired request,
            # pruned to the trailing window at publish time, with
            # running met-counts maintained on append/prune so every
            # retire publishes in O(1) instead of re-summing the
            # whole window on the latency-sensitive retire path
            self._slo_window = collections.deque()
            self._slo_met_all = 0
            self._slo_met = collections.Counter()
            wd_mode = str(flag("telemetry_watchdog")).lower()
            if watchdog is not None:
                self._watchdog = watchdog
            elif wd_mode in ("warn", "strict"):
                from ..framework.watchdog import Watchdog

                self._watchdog = Watchdog(self._metrics,
                                          mode=wd_mode,
                                          window=self._win)
            self._wd_stride = max(
                1, int(flag("telemetry_watchdog_stride")))
            self._export_path = \
                str(flag("telemetry_export_path")) or None
            # the per-program performance ledger joins the planner's
            # static cost model with the exec.wall_s.<program> stamps
            # this scheduler (and jit/api.py) records — surfaced via
            # metrics()["ledger"] and the ledger.* gauges the
            # plan-drift watchdog reads
            from ..framework import perf_ledger as _perf_ledger

            self._ledger = _perf_ledger.ledger()
            if str(flag("telemetry_incident_dir")):
                # every watchdog fire writes an atomic incident
                # bundle (chrome lanes, registry snapshot, ledger
                # top-N, sanitizer tail, ...) — see dump_incident()
                self._recorder = telemetry.FlightRecorder(
                    registry=self._metrics, tracer=self._tracer,
                    traces=self._traces, watchdog=self._watchdog,
                    ledger=self._ledger)
            if int(flag("ops_server_port")) > 0:
                # embedded live-ops debug server (framework/
                # ops_server.py): one per process, read-only —
                # /metrics, /statusz, /tracez, /planz, /flagz,
                # /incidentz. Flag 0 (default) never imports the
                # module; the server refuses to exist without a
                # live registry
                from ..framework import ops_server as _ops_server

                srv = _ops_server.maybe_start()
                if srv is not None:
                    srv.add_status_provider(
                        "scheduler." + self._sched_uid,
                        self._statusz_info)

    def _init_block(self, denoising_steps, remasking, confidence_threshold,
                    prefix_cache, draft_model, preempt):
        """The block step's settings, checked: what cannot serve a row
        with an open block is refused by name."""
        if prefix_cache or draft_model is not None or preempt:
            raise ValueError(
                "a block-diffusion model (block_length="
                f"{self.block_length}) serves no prefix cache (a cached "
                "prefix would have to end on a block boundary), no "
                "speculative draft (a row already commits a block a round) "
                "and no preemption (no swap record of an open block): got "
                f"prefix_cache={bool(prefix_cache)} draft_model="
                f"{draft_model is not None} preempt={preempt}")
        if not self.chunked_prefill:
            raise ValueError(
                "a block-diffusion model is served by the packed step "
                "(prefill_chunk) alone: chunked_prefill=False was given")
        if remasking not in REMASKING:
            raise ValueError(f"remasking={remasking!r} is none of "
                             f"{list(REMASKING)}")
        b, t = self.block_length, int(denoising_steps)
        if not 1 <= t <= b:
            raise ValueError(f"denoising_steps={t} is not in 1.."
                             f"block_length={b}")
        if not 0.0 < float(confidence_threshold) <= 1.0:
            raise ValueError(
                f"confidence_threshold={confidence_threshold} is no "
                "probability")
        self.denoising_steps, self.remasking = t, remasking
        self.confidence_threshold = float(confidence_threshold)
        # a pass's share of the block: its ceil-split over the passes
        self._shares = [b // t + (s < b % t) for s in range(t)]
        self.block_stats = {"denoise_passes": 0, "commits_carried": 0,
                            "tokens_unmasked": 0, "blocks_committed": 0}

    # -- set-up ------------------------------------------------------------
    def warm(self):
        """Build, before the first request, the programs of the steady
        steps, where the adapter offers ``warm(rows, packed,
        chunk_tokens)``: every slot decoding, alone or beside one step's
        share of prompt tokens, at the packed widths this scheduler's
        buckets give them. ``ServingEngine`` calls it when it starts; a
        caller that steps the scheduler itself calls it once, or lets
        the first requests meet the compiles."""
        warm = getattr(self.model, "warm", None)
        if warm is None:
            return
        rows, chunk = self.max_batch_size, self.prefill_chunk_tokens
        # a decode row feeds a token; over a block model its open block,
        # and in that block's first pass the finished one behind it too
        fed = rows * (self.block_length or 1)
        most = 2 * fed if self.block_length else fed
        packed = {bucket_packed_tokens(n, self.serving_buckets)
                  for n in (fed, most, most + chunk)}
        with self._span("serving.warm") as sp:
            if sp is not None:
                sp.attrs["key"] = \
                    f"warm r{rows} n{sorted(packed)} c{chunk}"
            warm(rows, packed, chunk)

    # -- pool accounting ---------------------------------------------------
    def _pool(self, model=None):
        caches = list((model or self.model).caches)
        total = sum(c.num_pages for c in caches)
        free = sum(c.num_free_pages for c in caches)
        return total, free

    def _pages_needed(self, req: Request, model=None,
                      hit_tokens=0) -> int:
        need = 0
        # speculative windows transiently overshoot the committed
        # length by up to draft_k+1 tokens before the rollback
        slack = (self.draft_k + 1) if self.draft is not None else 0
        for c in (model or self.model).caches:
            n = _pages_for(c, req.total_tokens() + slack)
            # a prefix-cache hit shares its FULL pages; the hit's
            # partial tail page still costs one draw (the COW fork on
            # the first divergent write), so only full pages reduce
            # the worst-case reservation
            need += max(n - hit_tokens // c.page_size, 0)
        return need

    def page_pool_stats(self):
        total, free = self._pool()
        caches = list(self.model.caches)
        stats = {
            "total_pages": total,
            "free_pages": free,
            "reserved_pages": self._reserved_pages_outstanding(),
            "utilization": 1.0 - free / max(total, 1),
            "shared_pages": sum(
                getattr(c, "num_shared_pages", 0) for c in caches),
            "cow_forks": sum(
                getattr(c, "cow_forks", 0) for c in caches),
            # quantized-serving accounting: page bytes as stored
            # (int8 pages + scale sidecars report their true HBM
            # footprint — the capacity story of docs/QUANTIZATION.md)
            "kv_dtype": sorted({
                getattr(c, "kv_dtype", "unknown") for c in caches}),
            "pool_bytes": sum(
                getattr(c, "pool_nbytes", 0) for c in caches),
            "used_bytes": sum(
                getattr(c, "page_nbytes", 0)
                * (c.num_pages - c.num_free_pages) for c in caches),
        }
        if self.prefix_cache is not None:
            # scheduler-side counters (admission-level) and tree-side
            # counters (lookup-level) share names like hit_tokens but
            # mean different things — keep them in separate blocks
            stats["prefix_cache"] = dict(self.prefix_stats)
            stats["prefix_cache"]["tree"] = self.prefix_cache.summary()
        if self.swap_space is not None:
            stats["swap"] = self.swap_space.summary()
            stats["swap"]["swapped_requests"] = len(self._swapped)
        all_caches = caches + (list(self.draft.caches)
                               if self.draft is not None else [])
        san = [s for s in (getattr(c, "sanitizer_stats", None)
                           for c in all_caches) if s]
        if san:
            stats["sanitizer"] = {
                "mode": san[0]["mode"],
                "events": sum(s["events"] for s in san),
                "violations": sum(s["violations"] for s in san),
                "crosschecks": sum(
                    s["by_op"].get("crosscheck", 0) for s in san),
            }
        return stats

    def metrics(self) -> dict:
        """ONE namespaced telemetry snapshot for the whole serving
        stack — the unified replacement for the three divergent stats
        shapes (``page_pool_stats()`` / ``prefix_stats`` / sanitizer
        counters, all of which keep their old keys as aliases):

        * ``serving`` — TTFT/TPOT/queue-wait/retire histograms (exact
          p50/p90/p99) and token/request counters;
        * ``pool`` — occupancy gauges (refreshed here) + lifetime
          COW-fork/alloc/free counters;
        * ``prefix`` — hit/insert/evict counters + tree-size gauges;
        * ``compile`` / ``collective`` — whatever the compile path and
          the collective-matmul dispatch recorded in this process;
        * ``sanitizer`` — event/violation counters when a sanitizer
          is live;
        * ``sampler`` — over a block-diffusion model only: the note that
          ``sampler=`` is not called (a host callable over one row's
          logits cannot choose among a block's positions; the tokens are
          chosen on the device).

        Plus, since PR 8: self-describing ``serving`` gauges (uptime,
        steps/sec, active/queued/retired request counts), SLO/goodput
        attainment when an :class:`telemetry.SLOConfig` is configured,
        sliding-window percentile views (``"window"`` sub-dict on
        each latency histogram, keyed by step epoch), and — when live
        — ``watchdog`` and ``request_traces`` digests.

        Returns ``{"telemetry": "off"}`` when FLAGS_telemetry was off
        at scheduler construction (nothing was ever recorded)."""
        said = {"sampler": "not called: the tokens of a block-diffusion "
                "model are chosen on the device"} if self.block_length else {}
        if self._metrics is None:
            return {"telemetry": "off", **said}
        m = self._metrics
        stats = self._publish_gauges()
        snap = m.snapshot()
        snap.update(said)
        snap["telemetry"] = ("trace" if self._tracer is not None
                             else "metrics")
        if "sanitizer" in stats:
            snap["sanitizer"] = stats["sanitizer"]
        # sliding-window percentile views, windowed by step epoch —
        # the deterministic "last N steps" read the SLO layer and the
        # admission controller consume (full-history summaries stay)
        lo = self._step_epoch - self._win
        for name in ("ttft_s", "tpot_s", "queue_wait_s",
                     "step_wall_s"):
            w = m.hist_windowed("serving." + name, lo)
            if w is not None and name in snap.get("serving", {}):
                snap["serving"][name]["window"] = w
        if self._slo is not None:
            snap["slo"] = self._slo.to_dict()
        if self._watchdog is not None:
            snap["watchdog"] = self._watchdog.summary()
        if self._traces is not None:
            snap["request_traces"] = self._traces.summary()
        if self._ledger is not None:
            # plan-vs-actual attribution per program (framework/
            # perf_ledger.py): the "ledger" block REPLACES the raw
            # exec.* histograms as the intended read (those stay in
            # the snapshot as the measured source of truth)
            snap["ledger"] = self._ledger.report()
        return snap

    def _statusz_info(self) -> dict:
        """This scheduler's ``/statusz`` section (framework/
        ops_server.py provider contract): population counts, SLO
        window, and the watchdog state — the live operator view."""
        info = {
            "steps": self._steps,
            "active": len(self._active),
            "queued": len(self._queue),
            "swapped": len(self._swapped),
            "retired": len(self._finished),
            "chunked_prefill": self.chunked_prefill,
        }
        if self.draft is not None:
            # accept-rate column (ISSUE 19 satellite): committed /
            # proposed over the scheduler's lifetime, plus the round
            # counters behind it
            ss = self.spec_stats
            proposed = ss["proposed_tokens"]
            rounds = ss["rounds"]
            info["spec"] = {
                "mode": "ragged",
                "rounds": rounds,
                "committed_tokens": ss["committed_tokens"],
                "accept_rate": (
                    round(ss["accepted_draft_tokens"] / proposed, 4)
                    if proposed else None),
                "tokens_per_round": (
                    round(ss["committed_tokens"] / rounds, 3)
                    if rounds else None),
            }
        if self._slo is not None:
            info["slo"] = self._slo.to_dict()
            m = self._metrics
            info["slo_window"] = {
                "goodput": m.gauge_value("serving.goodput"),
                "requests": m.gauge_value(
                    "serving.slo_window_requests"),
            }
        if self._watchdog is not None:
            info["watchdog"] = self._watchdog.summary()
        return info

    def _publish_gauges(self) -> dict:
        """Publish every derived gauge into the registry and return
        the legacy-shape stats dict. ONE source of truth for the
        aggregation: the ``page_pool_stats()`` snapshot computes the
        pool/prefix/sanitizer sums, and the gauges here are those
        same numbers published into the registry (the shapes cannot
        drift)."""
        m = self._metrics
        stats = self.page_pool_stats()
        for key in ("total_pages", "free_pages", "utilization",
                    "shared_pages", "used_bytes"):
            m.gauge("pool." + key, stats[key])
        if any(getattr(c, "eva", False) for c in self.model.caches):
            for chain in ("pages_window", "pages_summary"):
                m.gauge("eva." + chain, sum(
                    getattr(c, chain, 0) for c in self.model.caches))
        peak = sum(getattr(c, "peak_used_pages", 0)
                   for c in self.model.caches)
        m.gauge("pool.peak_utilization",
                peak / max(stats["total_pages"], 1))
        tree = stats.get("prefix_cache", {}).get("tree")
        if tree is not None:
            m.gauge("prefix.cached_tokens", tree["cached_tokens"])
            m.gauge("prefix.cached_pages", tree["cached_pages"])
            m.gauge("prefix.nodes", tree["nodes"])
        san = stats.get("sanitizer")
        if san is not None:
            m.gauge("sanitizer.events", san["events"])
            m.gauge("sanitizer.violations", san["violations"])
        # self-describing serving gauges (ISSUE 8 satellite): the
        # snapshot carries its own uptime/throughput/population so a
        # reader needs no bench context; step()'s counters remain the
        # aliases
        uptime = telemetry.clock() - self._t_start
        m.gauge("serving.uptime_s", uptime)
        m.gauge("serving.steps_per_s",
                self._steps / uptime if uptime > 0 else 0.0)
        m.gauge("serving.step_epoch", self._step_epoch)
        m.gauge("serving.active_requests", len(self._active))
        m.gauge("serving.queued_requests", len(self._queue))
        m.gauge("serving.retired_requests", len(self._finished))
        m.gauge("serving.swapped_requests", len(self._swapped))
        if self.swap_space is not None:
            m.gauge("serving.swap_used_bytes",
                    self.swap_space.used_bytes)
        self._publish_slo_gauges()
        return stats

    def _sanitizer_epoch(self):
        """Every FLAGS_page_sanitizer_stride steps: cross-check each
        cache's shadow heap against the real pool (and, on strict
        pools, run assert_ref_invariants) — the epoch half of the
        page sanitizer. A single counter bump when the sanitizer is
        off."""
        self._san_steps += 1
        if self._san_steps % self._san_stride:
            return
        models = [self.model] + (
            [self.draft] if self.draft is not None else [])
        for m in models:
            for c in m.caches:
                chk = getattr(c, "sanitizer_crosscheck", None)
                if chk is not None:
                    chk()

    # -- request lifecycle -------------------------------------------------
    def submit(self, req: Request) -> str:
        if not req.prompt_ids:
            raise ValueError("empty prompt")
        if req.max_new_tokens < 0:
            raise ValueError("max_new_tokens must be >= 0")
        # context-length bound (models that declare one): rejecting at
        # submit beats a mid-batch crash for every co-batched request
        limit = getattr(self.model, "max_length", None)
        if limit is not None and self.draft is not None:
            # a speculative verify window transiently appends up to
            # draft_k+1 tokens beyond the committed prefix before the
            # rollback — admission must leave that headroom or
            # the verify row raises mid-batch near the end
            limit = limit - (self.draft_k + 1)
        if limit is not None and req.total_tokens() > limit:
            raise ValueError(
                f"request {req.req_id!r} needs {req.total_tokens()} "
                f"positions but the model serves at most {limit}"
            )
        # reject requests that could NEVER be admitted (worst-case page
        # need above the watermark even with an empty pool) instead of
        # letting them block the FIFO queue forever
        need = self._pages_needed(req)
        total, _ = self._pool()
        if need > self.page_watermark * total:
            raise ValueError(
                f"request {req.req_id!r} needs {need} pages worst-case "
                f"but the pool watermark admits at most "
                f"{int(self.page_watermark * total)} of {total}"
            )
        # bounded-queue backpressure: past max_queue waiting requests,
        # shedding load at submit beats unbounded memory growth and a
        # silently exploding queue-wait tail
        if self.max_queue and len(self._queue) >= self.max_queue:
            if self._metrics is not None:
                self._metrics.inc("serving.admit_reject_queue_full")
            raise QueueFullError(
                f"request {req.req_id!r} rejected: submit queue at "
                f"capacity ({self.max_queue}); shed load or retry "
                "(FLAGS_serving_max_queue)")
        if req.deadline_s is not None:
            if req.deadline_s <= 0:
                raise ValueError(
                    f"request {req.req_id!r}: deadline_s must be "
                    f"positive, got {req.deadline_s}")
            req._t_deadline = telemetry.clock() + float(req.deadline_s)
            self._deadline_seen = True
        if req.priority:
            self._plain_fifo = False
        self._submit_seq += 1
        req._order = self._submit_seq
        if self._metrics is not None:
            req._t_submit = telemetry.clock()
        if self._metrics is not None or self._traces is not None \
                or self._tracer is not None:
            # trace identity: adopt an injected context (object or
            # wire string — a front-end/ingress handoff), else start
            # a fresh trace. NEVER under off — the hot path must
            # allocate nothing (the zero-alloc gate covers this)
            ctx = req.trace_ctx
            if isinstance(ctx, str):
                ctx = telemetry.TraceContext.from_wire(ctx)
            if ctx is None:
                ctx = telemetry.TraceContext(
                    tenant=req.tenant, deadline_s=req.deadline_s)
            req.trace_ctx = ctx
        if self._traces is not None:
            payload = {"prompt_tokens": len(req.prompt_ids),
                       "max_new_tokens": req.max_new_tokens}
            if req.trace_ctx is not None:
                payload["trace_id"] = req.trace_ctx.trace_id
            self._traces.begin(
                req.req_id, telemetry.clock(), self._step_epoch,
                **payload)
        if self._cv_queue is not None:
            self._cv_queue.write()
        self._queue.append(req)
        return req.req_id

    def _tenant_full(self, tenant) -> bool:
        """True when the tenant already holds its max in-flight share
        of the active batch (multi-tenant fairness; None = no cap)."""
        if self.max_inflight_per_tenant is None:
            return False
        n = sum(1 for r in self._active.values()
                if r.tenant == tenant)
        return n >= self.max_inflight_per_tenant

    def _pick_queued(self):
        """The admission candidate: highest priority first, FIFO
        within a priority, skipping tenant-capped requests. With
        default priorities and no tenant cap this is exactly the old
        FIFO head — and costs exactly the old O(1), not a scan (a
        deep backlog is precisely when admission runs hottest)."""
        if self._plain_fifo and self.max_inflight_per_tenant is None:
            return self._queue[0] if self._queue else None
        cap = self.max_inflight_per_tenant
        # one O(active) tenant census per scan, not one per queued
        # element — a deep backlog is exactly when this runs hottest
        counts = (collections.Counter(r.tenant
                                      for r in self._active.values())
                  if cap is not None else None)
        best, bk = None, None
        for req in self._queue:
            if counts is not None and counts[req.tenant] >= cap:
                continue
            k = (-req.priority, req._order)
            if best is None or k < bk:
                best, bk = req, k
        return best

    def _pop_queued(self, req):
        """Remove an admitted candidate from the queue (O(1) for the
        head — the plain-FIFO common case)."""
        if self._cv_queue is not None:
            self._cv_queue.write()
        if self._queue and self._queue[0] is req:
            self._queue.popleft()
        else:
            self._queue.remove(req)

    def _try_admit(self):
        hit_tokens_admitted = 0
        if self._faults is not None \
                and self._faults.pool_exhausted(self._fault_step):
            # injected pool exhaustion: admission (and swap-in) sees
            # a full pool; active decode continues untouched
            self._note_fault("exhaust")
            return 0
        head = self._pick_queued()
        self._admit_swapped(None if head is None else head.priority)
        while self._queue and len(self._active) < self.max_batch_size:
            # the head pick is still the right candidate unless the
            # swap-ins above filled its tenant's in-flight share —
            # don't pay a second full queue scan to rediscover it
            if head is not None and not self._tenant_full(head.tenant):
                req = head
            else:
                req = self._pick_queued()
            head = None
            if req is None:
                break  # every queued request is tenant-capped
            hit = None
            if self.prefix_cache is not None:
                # a blocked head-of-queue request would re-walk the
                # tree every step, inflating lookup stats and bumping
                # LRU recency for a request that never got admitted —
                # reuse the previous match while the tree is unchanged
                key = (req.req_id, self.prefix_cache.mutations)
                if self._match_memo is not None \
                        and self._match_memo[0] == key:
                    hit = self._match_memo[1]
                else:
                    # cap the match one token short of the prompt: the
                    # LAST prompt position must run through the model
                    # to produce the logits that sample the first new
                    # token
                    hit = self.prefix_cache.match(
                        req.prompt_ids, limit=len(req.prompt_ids) - 1,
                        align=self.prefix_align)
                    self._match_memo = (key, hit)
                if hit.length:
                    # protect the matched chain from the evictor
                    # until the request retires
                    self.prefix_cache.pin(hit.path)
            hit_len = hit.length if hit is not None else 0
            need = self._pages_needed(req, hit_tokens=hit_len)
            total, free = self._pool()
            # admit only if worst-case reservation keeps the pool under
            # the watermark (reservations of already-active requests
            # are counted; their already-used pages are no longer free,
            # so subtract usage double-counted inside reservations)
            used = total - free
            projected = used + self._reserved_pages_outstanding() + need
            evicted = False
            if (projected > self.page_watermark * total
                    and self.prefix_cache is not None):
                # cached pages count as "used": reclaim unpinned
                # cached chains (LRU leaf first) before refusing
                deficit = int(np.ceil(
                    projected - self.page_watermark * total))
                if self.prefix_cache.evict(deficit):
                    evicted = True
                    total, free = self._pool()
                    used = total - free
                    projected = (used
                                 + self._reserved_pages_outstanding()
                                 + need)
            preempted = False
            if (projected > self.page_watermark * total
                    and self._preempt_enabled):
                # preempt-instead-of-reject: swap strictly-lower-
                # priority victims out to the host tier until the
                # candidate's reservation fits (or no victim remains).
                # Guarded on the victims' reachable releasable pages
                # covering the deficit: swapping a victim out only to
                # learn the candidate STILL doesn't fit buys nothing —
                # next step's idle-capacity swap-in undoes it and the
                # same admission attempt preempts it again, a
                # deterministic host-copy ping-pong until the blocking
                # peer retires
                relief, space_blocked = self._releasable_pages(
                    req.priority)
                if relief >= projected - self.page_watermark * total:
                    while projected > self.page_watermark * total:
                        victim = self._pick_victim(
                            max_priority=req.priority)
                        if victim is None or not self._preempt(
                                victim, reason="admit"):
                            break
                        preempted = True
                        total, free = self._pool()
                        used = total - free
                        projected = (
                            used + self._reserved_pages_outstanding()
                            + need)
                elif space_blocked and self._metrics is not None:
                    # the guard declined because the HOST TIER cannot
                    # hold the victims, not because the pool math
                    # falls short — keep that signal distinct (it
                    # used to be counted by _preempt's own refusal)
                    self._metrics.inc("serving.preempt_swap_full")
            if projected > self.page_watermark * total:
                if hit_len:
                    self.prefix_cache.unpin(hit.path)
                # admission-side failure accounting (ISSUE 8/9): a
                # pool-capacity block is ITS OWN signal — the
                # admission controller must distinguish "the pool is
                # full" from "we made room by evicting cached pages"
                # from "we made room by preempting" (counted below)
                if self._metrics is not None:
                    self._metrics.inc("serving.admit_reject_pool")
                return hit_tokens_admitted
            if self.draft is not None:
                # the draft pool is budgeted too (it may be sized
                # differently): worst-case draft need for every active
                # request + this one must fit under the watermark
                need_d = self._pages_needed(req, self.draft)
                total_d, free_d = self._pool(self.draft)
                used_d = total_d - free_d
                # conservative: the full worst-case draft need of every
                # active request (already-used pages count toward it)
                out_d = sum(self._pages_needed(r, self.draft)
                            for r in self._active.values())
                if max(out_d, used_d) + need_d > \
                        self.page_watermark * total_d:
                    if self._metrics is not None:
                        self._metrics.inc(
                            "serving.admit_reject_draft_pool")
                    return hit_tokens_admitted
            self._pop_queued(req)
            self._match_memo = None
            if hit_len:
                # cached prefill: share the matched chain and start
                # prefill at the first uncached token
                self._attach_prefix(req.req_id, hit.chains, hit_len)
                req._prefix_hit = hit_len
                req._prefix_path = hit.path
                req._pos = hit_len
                hit_tokens_admitted += hit_len
                if req.on_token is not None:
                    # the skipped prompt tokens still stream in order
                    for t in req.prompt_ids[:hit_len]:
                        req.on_token(req, t, True)
            else:
                self.model.alloc(req.req_id)
            if self.prefix_cache is not None:
                self.prefix_stats["requests"] += 1
                self.prefix_stats["prompt_tokens"] += \
                    len(req.prompt_ids)
                self.prefix_stats["hit_tokens"] += hit_len
                if hit_len:
                    self.prefix_stats["request_hits"] += 1
            if self.draft is not None:
                self.draft.alloc(req.req_id)
            # the admitted chains carry the request's trace context
            # from here on (swap records and COW handoffs inherit it)
            self._tag_pool_trace(req)
            req.state = RequestState.PREFILL
            if self._cv_state is not None:
                self._cv_state.write()
            self._active[req.req_id] = req
            self._admitted_step += 1
            if self._metrics is not None:
                req._qwait = telemetry.clock() - req._t_submit
                self._metrics.observe("serving.queue_wait_s",
                                      req._qwait)
                self._metrics.inc("serving.requests_admitted")
                if evicted:
                    self._metrics.inc(
                        "serving.admit_evict_then_admit")
                if preempted:
                    self._metrics.inc(
                        "serving.admit_preempt_then_admit")
            if self._traces is not None:
                self._traces.event(
                    req.req_id, "admit", telemetry.clock(),
                    self._step_epoch, prefix_hit_tokens=hit_len,
                    evicted_for_room=evicted)
        return hit_tokens_admitted

    # -- preemption + tiered KV swap ---------------------------------------
    def _admit_swapped(self, queued_priority=None):
        """Re-admit swapped-out requests (highest priority first,
        FIFO within) while their restore + worst-case growth
        reservation fits under the watermark. A blocked
        highest-priority victim blocks the ones behind it — swapped
        requests must never be starved by smaller late arrivals.
        ``queued_priority`` is the best queued candidate's priority:
        a swapped request of STRICTLY lower priority yields to it
        (restoring first would either steal the last batch slot from
        the higher-priority arrival or be re-preempted right after —
        a wasted host round trip); equal priority resumes first (it
        was admitted once already and its submit order is older)."""
        if not self._swapped:
            return
        if self._faults is not None \
                and self._faults.swap_in_delayed(self._fault_step):
            self._note_fault("delay_swap_in")
            return
        order = sorted(self._swapped.values(),
                       key=lambda r: (-r.priority, r._order))
        for req in order:
            if queued_priority is not None \
                    and req.priority < queued_priority:
                break  # the queue's best outranks this one and the
                #        rest of the (sorted) swapped set
            if len(self._active) >= self.max_batch_size:
                break
            if self._tenant_full(req.tenant):
                continue
            worst = req.total_tokens() + (
                (self.draft_k + 1) if self.draft is not None else 0)
            need = sum(
                c.swap_in_pages_needed(req.req_id, self.swap_space,
                                       worst)
                for c in self.model.caches)
            total, free = self._pool()
            used = total - free
            projected = (used + self._reserved_pages_outstanding()
                         + need)
            if (projected > self.page_watermark * total
                    and self.prefix_cache is not None):
                deficit = int(np.ceil(
                    projected - self.page_watermark * total))
                if self.prefix_cache.evict(deficit):
                    total, free = self._pool()
                    used = total - free
                    projected = (used
                                 + self._reserved_pages_outstanding()
                                 + need)
            if projected > self.page_watermark * total:
                break
            self._swap_in(req)

    def _swap_in(self, req: "Request"):
        """Restore a swapped-out request: bitwise page restore
        through the pool's swap tier, then back into the active set —
        resuming is just another packed prompt/decode row next step
        (the chunked-prefill path needs no special case)."""
        rid = req.req_id
        with self._req_span("serving.swap_in", req, req=rid):
            fn = getattr(self.model, "swap_in", None)
            if fn is not None:
                restored = fn(rid, self.swap_space)
            else:
                restored = sum(c.swap_in(rid, self.swap_space)
                               for c in self.model.caches)
        # the restored chains re-carry the context (pools that
        # round-trip it through their swap records already do; this
        # covers model-level swap hooks and fresh chains)
        self._tag_pool_trace(req)
        if self._cv_state is not None:
            self._cv_state.write()
        del self._swapped[rid]
        if self.draft is not None:
            # fresh (empty) draft chain: the ragged spec step's
            # draft-refill rows re-prefill it from the committed
            # prefix over the next steps (the row verifies again
            # once the draft pool has caught up)
            self.draft.alloc(rid)
        req.state = (RequestState.DECODE if req.generated_ids
                     else RequestState.PREFILL)
        self._active[rid] = req
        self._admitted_step += 1
        self._step_extras["resumed"] = \
            self._step_extras.get("resumed", 0) + 1
        if self._metrics is not None:
            self._metrics.inc("serving.swap_in_requests")
            self._metrics.inc("serving.swap_in_pages", restored)
        if self._traces is not None:
            self._traces.event(
                req.req_id, "admit", telemetry.clock(),
                self._step_epoch, swapped_in=True, pages=restored)

    def _victim_key(self, r):
        """Victim scoring: lowest priority first, then most pages
        held (frees the most room), then least progress (throws away
        the least work), then submit order for determinism. ONE
        definition, shared by the preempt loop's pick and the relief
        guard's walk — if they ordered victims differently the guard
        would mispredict what the loop can actually free."""
        held = sum(c.seq_page_count(r.req_id)
                   for c in self.model.caches)
        return (r.priority, -held, len(r.generated_ids), r._order)

    def _pick_victim(self, max_priority=None):
        """The preemption victim by :meth:`_victim_key`.
        ``max_priority`` restricts to STRICTLY lower priorities (an
        admission candidate may never preempt its own class)."""
        cands = [r for r in self._active.values()
                 if max_priority is None or r.priority < max_priority]
        return min(cands, key=self._victim_key) if cands else None

    def _releasable_pages(self, max_priority):
        """``(pages, space_blocked)``: the projected-demand relief
        preempting the strictly-lower-priority active victims would
        buy — each victim frees its private pages (shared pages stay
        resident under swap holds) AND its remaining worst-case
        reservation leaves the admission projection with it. Victims
        are walked in the preempt loop's own order and stop counting
        at the first whose host copy no longer fits the swap space —
        ``_preempt`` would refuse it there and the loop would break,
        so pages past that point are unreachable relief
        (``space_blocked`` reports that cut so the caller can count
        the decline as a swap-space failure, not a pool reject). The
        admission pass checks the total against its deficit before
        swapping anyone out."""
        space = self.swap_space
        if space is None:
            return 0, False
        victims = sorted(
            (r for r in self._active.values()
             if r.priority < max_priority), key=self._victim_key)
        budget = space.free_bytes
        pages = 0
        for r in victims:
            nbytes = sum(c.swap_out_nbytes(r.req_id)
                         for c in self.model.caches)
            if nbytes > budget:
                return pages, True
            budget -= nbytes
            for c in self.model.caches:
                pages += (c.swap_out_pages(r.req_id)
                          + self._growth_pages(r, c))
        return pages, False

    def _preempt(self, req: "Request", reason: str) -> bool:
        """Swap one active request out to the host tier. Returns
        False (and changes nothing — swap_out is atomic) when the
        swap space cannot hold the victim's private pages."""
        rid = req.req_id
        space = self.swap_space
        if space is None:
            return False
        est = sum(c.swap_out_nbytes(rid) for c in self.model.caches)
        if not space.would_fit(est):
            if self._metrics is not None:
                self._metrics.inc("serving.preempt_swap_full")
            return False
        freed = 0
        nbytes = 0
        with self._req_span("serving.preempt", req, req=rid,
                            reason=reason):
            fn = getattr(self.model, "swap_out", None)
            if fn is not None:
                freed, nbytes = fn(rid, space)
            else:
                for c in self.model.caches:
                    fp, nb = c.swap_out(rid, space)
                    freed += fp
                    nbytes += nb
            if self.draft is not None:
                # the draft KV is disposable — discard it here and
                # let the spec step re-prefill it from the committed
                # prefix after swap-in. The draft pool itself never
                # swaps, so it stays wait-free.
                self.draft.free(rid)
                self.spec_stats["draft_discards"] += 1
        req.state = RequestState.SWAPPED
        req._preemptions += 1
        if self._cv_state is not None:
            self._cv_state.write()
        self._active.pop(rid)
        self._swapped[rid] = req
        self._step_extras["preempted"] = \
            self._step_extras.get("preempted", 0) + 1
        if self._metrics is not None:
            self._metrics.inc("serving.preempt_victims")
            self._metrics.inc("serving.preempt_pages", freed)
            self._metrics.inc("serving.swap_out_bytes", nbytes)
        if self._traces is not None:
            # the PR-8-reserved "evict" request-trace event, live:
            # non-terminal (the request resumes), rendered as an
            # instant marker on the request's chrome lane
            self._traces.event(
                rid, "evict", telemetry.clock(), self._step_epoch,
                reason=reason, pages=freed, bytes=nbytes)
        return True

    # -- disaggregated prefill/decode handoff (inference/disagg.py) --------
    def export_request(self, req_id, mp_shards=1):
        """Hand one prefill-complete active request off to a decode
        worker: swap its page chains out to the host tier BITWISE
        (payload + int8 scale sidecars), serialize them over the
        versioned ``HostKVSwapSpace`` wire format (one payload per
        ``mp`` shard, split on the KV-head axis), and return the
        handoff envelope — request metadata (prompt, committed
        tokens, budget/priority/tenant, remaining deadline, trace
        wire) plus the payloads. The request leaves THIS scheduler
        with state ``migrated`` and a terminal ``handoff`` trace
        event; the receiving scheduler's :meth:`adopt_swapped`
        re-registers it and resumes decode through the standard
        swap-in path, so the streamed output is greedy-identical to
        never having moved. Requires the host swap tier
        (``FLAGS_serving_swap_bytes``); chains still sharing pages
        with the prefix cache cannot travel (``SwapWireError``).
        Must run on the stepping thread."""
        req = self._active.get(req_id)
        if req is None:
            raise KeyError(
                f"export_request({req_id!r}): not an active request")
        space = self.swap_space
        if space is None:
            raise RuntimeError(
                "export_request needs the host swap tier — construct "
                "the scheduler with preempt=True and swap_bytes>0 "
                "(FLAGS_serving_preempt / FLAGS_serving_swap_bytes)")
        if self.draft is not None:
            raise RuntimeError(
                "export_request: speculative scheduling keeps a "
                "draft-model KV pool that cannot travel — hand off "
                "from non-speculative schedulers only")
        if req._pos < len(req.prompt_ids) or not req.generated_ids:
            raise ValueError(
                f"export_request({req_id!r}): prefill incomplete "
                f"({req._pos}/{len(req.prompt_ids)} prompt tokens, "
                f"{len(req.generated_ids)} committed) — decode "
                "workers adopt only prefill-complete chains")
        if self.prefix_cache is not None and req._prefix_path:
            # drop the radix pins; pages STILL shared with the tree
            # after this stay on-device and export_seq refuses them
            self.prefix_cache.unpin(req._prefix_path)
            req._prefix_path = ()
        est = sum(c.swap_out_nbytes(req_id)
                  for c in self.model.caches)
        if not space.would_fit(est):
            from ..incubate.nn.paged_cache import SwapSpaceFull

            raise SwapSpaceFull(
                f"export_request({req_id!r}): the handoff staging "
                f"needs {est} bytes, {space.free_bytes} of "
                f"{space.capacity_bytes} free")
        self._tag_pool_trace(req)
        with self._req_span("serving.handoff_out", req, req=req_id,
                            shards=int(mp_shards)):
            for c in self.model.caches:
                c.swap_out(req_id, space)
            payloads = space.export_seq(
                req_id, list(self.model.caches),
                mp_shards=mp_shards)
        deadline_left = None
        if req._t_deadline:
            deadline_left = max(
                req._t_deadline - telemetry.clock(), 1e-3)
        elif req.deadline_s is not None:
            deadline_left = float(req.deadline_s)
        ctx = req.trace_ctx
        wire = None
        if ctx is not None:
            wire = ctx if isinstance(ctx, str) else ctx.to_wire()
        req.state = RequestState.MIGRATED
        if self._cv_state is not None:
            self._cv_state.write()
        self._active.pop(req_id)
        self._step_extras["migrated"] = \
            self._step_extras.get("migrated", 0) + 1
        wire_bytes = sum(len(p) for p in payloads)
        if self._metrics is not None:
            self._metrics.inc("serving.handoff_out_requests")
            self._metrics.inc("serving.handoff_out_bytes",
                              wire_bytes)
        if self._traces is not None:
            # terminal ON THIS WORKER only: the decode worker's
            # adopt_swapped continues the same trace id
            self._traces.complete(
                req_id, "handoff", telemetry.clock(),
                self._step_epoch, shards=int(mp_shards),
                wire_bytes=wire_bytes,
                generated_tokens=len(req.generated_ids))
        return {
            "req": {
                "req_id": req.req_id,
                "prompt_ids": list(req.prompt_ids),
                "generated_ids": list(req.generated_ids),
                "max_new_tokens": req.max_new_tokens,
                "eos_id": req.eos_id,
                "priority": req.priority,
                "tenant": req.tenant,
                "deadline_s": deadline_left,
                "trace_ctx": wire,
            },
            "payloads": payloads,
        }

    def adopt_swapped(self, req, payloads):
        """Adopt a handed-off request from a prefill worker: restore
        its page-chain payloads into THIS scheduler's host swap tier
        (magic/version/shard-set/geometry validated loudly) and
        register the request as swapped-out — the next step's
        standard ``_admit_swapped``/``_swap_in`` path restores the
        chains bitwise and decode resumes exactly where the prefill
        worker stopped. The trace identity rides the swap records:
        ``swap_space.trace_context(req_id)`` is the decode-worker
        ingress, so the request's decode-side spans stitch under ONE
        trace id across the prefill -> transfer -> decode hop. Must
        run on the stepping thread (the async engine marshals it via
        ``ServingEngine.adopt``)."""
        rid = req.req_id
        if (rid in self._active or rid in self._swapped
                or rid in self._finished
                or any(r.req_id == rid for r in self._queue)):
            raise ValueError(
                f"adopt_swapped({rid!r}): this scheduler already "
                "knows the request id")
        space = self.swap_space
        if space is None:
            raise RuntimeError(
                "adopt_swapped needs the host swap tier — construct "
                "the scheduler with preempt=True and swap_bytes>0 "
                "(FLAGS_serving_preempt / FLAGS_serving_swap_bytes)")
        if self.draft is not None:
            raise RuntimeError(
                "adopt_swapped: speculative scheduling cannot adopt "
                "a foreign chain (the draft pool never saw the "
                "prompt)")
        if not req.generated_ids:
            raise ValueError(
                f"adopt_swapped({rid!r}): no committed token rides "
                "the envelope — only prefill-complete requests hand "
                "off")
        space.import_seq(rid, payloads, list(self.model.caches))
        req._pos = len(req.prompt_ids)
        req.state = RequestState.SWAPPED
        self._submit_seq += 1
        req._order = self._submit_seq
        if req.priority:
            self._plain_fifo = False
        if req.deadline_s is not None:
            req._t_deadline = \
                telemetry.clock() + float(req.deadline_s)
            self._deadline_seen = True
        if req.trace_ctx is None:
            # the decode-worker trace ingress: the identity the
            # swap records carried over the wire
            req.trace_ctx = space.trace_context(rid)
        if self._metrics is not None or self._traces is not None \
                or self._tracer is not None:
            ctx = req.trace_ctx
            if isinstance(ctx, str):
                ctx = telemetry.TraceContext.from_wire(ctx)
            if ctx is None:
                ctx = telemetry.TraceContext(
                    tenant=req.tenant, deadline_s=req.deadline_s)
            req.trace_ctx = ctx
        if self._metrics is not None:
            req._t_submit = telemetry.clock()
            # the NEXT token's inter-token gap starts at adoption —
            # without this the first decode-side TPOT sample would
            # span back to an unset (zero) timestamp
            req._t_last_tok = req._t_submit
            self._metrics.inc("serving.handoff_in_requests")
            self._metrics.inc("serving.handoff_in_bytes",
                              sum(len(p) for p in payloads))
        if self._traces is not None:
            payload = {"adopted": True,
                       "prompt_tokens": len(req.prompt_ids),
                       "generated_tokens": len(req.generated_ids),
                       "max_new_tokens": req.max_new_tokens}
            if req.trace_ctx is not None:
                payload["trace_id"] = req.trace_ctx.trace_id
            self._traces.begin(rid, telemetry.clock(),
                               self._step_epoch, **payload)
        if self._cv_state is not None:
            self._cv_state.write()
        self._swapped[rid] = req
        return rid

    # -- deadlines ---------------------------------------------------------
    def _expire_deadlines(self):
        """Abort every request whose deadline passed — queued, active
        mid-generation, or swapped-out alike — at the step boundary
        (never mid-model-call). One clock read per step; until any
        deadlined request is submitted the sweep is skipped
        entirely."""
        if not self._deadline_seen:
            return
        now = telemetry.clock()

        def gone(req):
            return req._t_deadline and now >= req._t_deadline

        for req in [r for r in self._queue if gone(r)]:
            if self._cv_queue is not None:
                self._cv_queue.write()
            self._queue.remove(req)
            self._abort_deadline(req, "queued")
        for req in [r for r in self._active.values() if gone(r)]:
            self._abort_deadline(req, "active")
        for req in [r for r in self._swapped.values() if gone(r)]:
            self._abort_deadline(req, "swapped")

    def _abort_deadline(self, req: "Request", where: str,
                        reason: str = "deadline"):
        """Terminal deadline abort: release EVERY reservation this
        request holds (pins, pages, swap records), count it
        distinctly, and emit the terminal trace event. Lands in
        ``result()`` with state ``aborted_deadline``. ``reason``
        only relabels the trace event (engine-side cancels reuse
        this path with reason="cancelled"); the counter and SLO
        accounting are identical — a cancel is an abort."""
        rid = req.req_id
        if self.prefix_cache is not None and req._prefix_path:
            self.prefix_cache.unpin(req._prefix_path)
            req._prefix_path = ()
        if self._cv_state is not None:
            self._cv_state.write()
        if where == "active":
            self.model.free(rid)
            if self.draft is not None:
                self.draft.free(rid)
            self._active.pop(rid)
        elif where == "swapped":
            for c in self.model.caches:
                c.swap_discard(rid, self.swap_space)
            del self._swapped[rid]
        req.state = RequestState.ABORTED_DEADLINE
        self._finished[rid] = req
        self._step_extras["aborted"] = \
            self._step_extras.get("aborted", 0) + 1
        if self._metrics is not None:
            self._metrics.inc("serving.aborted_deadline")
            self._slo_note_abort(req)
        if self._traces is not None:
            self._traces.complete(
                rid, "abort", telemetry.clock(), self._step_epoch,
                reason=reason, where=where,
                generated_tokens=len(req.generated_ids))

    def expire_queued_deadlines(self) -> int:
        """Abort *queued* requests whose deadline already passed,
        without waiting for the next step boundary. The async
        engine's pump calls this between steps so a request whose
        ``deadline_s`` lapsed while waiting never burns a prefill
        before aborting (still counted under
        ``serving.aborted_deadline``). Must run on the stepping
        thread — it mutates the single-writer queue/state vars.
        Returns how many requests were aborted."""
        if not self._deadline_seen or not self._queue:
            return 0
        now = telemetry.clock()
        expired = [r for r in self._queue
                   if r._t_deadline and now >= r._t_deadline]
        for req in expired:
            if self._cv_queue is not None:
                self._cv_queue.write()
            self._queue.remove(req)
            self._abort_deadline(req, "queued")
        return len(expired)

    def cancel(self, req_id: str, reason: str = "cancelled") -> bool:
        """Abort one request by id wherever it currently lives —
        queued, active mid-generation, or swapped out — releasing
        every reservation it holds, exactly like a deadline abort
        (same counter, same SLO miss accounting, same terminal
        ``aborted_deadline`` state; the trace event carries
        ``reason``). The async engine routes caller cancellation /
        client disconnect here. Must run on the stepping thread.
        Returns False when the id is unknown or already terminal."""
        for req in self._queue:
            if req.req_id == req_id:
                if self._cv_queue is not None:
                    self._cv_queue.write()
                self._queue.remove(req)
                self._abort_deadline(req, "queued", reason=reason)
                return True
        if req_id in self._active:
            self._abort_deadline(self._active[req_id], "active",
                                 reason=reason)
            return True
        if req_id in self._swapped:
            self._abort_deadline(self._swapped[req_id], "swapped",
                                 reason=reason)
            return True
        return False

    def _slo_note_abort(self, req: "Request"):
        """A deadline abort is an SLO MISS by definition: it enters
        the goodput window with every configured SLO unmet, so
        attainment stays truthful under overload (dropping aborts
        would inflate goodput exactly when it matters most)."""
        if self._slo is None:
            return
        met = {key: False
               for key in self._slo.request_meets(None, None, None)}
        self._slo_window.append((self._step_epoch, False, met))
        self._publish_slo_gauges()

    def _growth_pages(self, req: "Request", c) -> int:
        """Worst-case free-list draws still ahead of ``req`` on cache
        ``c``: pages to reach the worst-case table size, measured
        from the cache's actual state (the freshly sampled token is
        only appended next step, and an attached prefix chain was
        shared rather than drawn), plus one draw when the partial
        tail page is still shared (the pending copy-on-write fork).
        ONE definition, shared by the admission reservation and the
        preemption relief guard."""
        slack = (self.draft_k + 1) if self.draft is not None else 0
        worst = req.total_tokens() + slack
        n = c.seq_len(req.req_id)
        held = getattr(c, "pages_held", None)
        rem = _pages_for(c, worst) - (
            held(n) if held is not None else _pages_for(c, n))
        pcow = getattr(c, "pending_cow", None)
        if pcow is not None and pcow(req.req_id):
            rem += 1
        return max(rem, 0)

    def _reserved_pages_outstanding(self) -> int:
        """Worst-case free-list draws still ahead of the whole active
        set (see :meth:`_growth_pages`)."""
        return sum(self._growth_pages(req, c)
                   for req in self._active.values()
                   for c in self.model.caches)

    def _attach_prefix(self, seq_id, chains, length):
        """Model hook with a caches-level fallback, so any model
        whose ``caches`` are PagedKVCacheManager serves cached
        prefills without opting in."""
        fn = getattr(self.model, "attach_prefix", None)
        if fn is not None:
            fn(seq_id, chains, length)
        else:
            for c, chain in zip(self.model.caches, chains):
                c.attach(seq_id, chain, length)

    def _seq_chains(self, seq_id):
        fn = getattr(self.model, "seq_page_chains", None)
        if fn is not None:
            return fn(seq_id)
        return [c.seq_pages(seq_id) for c in self.model.caches]

    def _span(self, name, **attrs):
        """Span context for a step phase, decided at CALL time by
        :func:`telemetry.span`: live under ``FLAGS_telemetry=trace``,
        a profiler RECORD window or a ``jax.profiler`` session —
        whether it began before or after this scheduler was built —
        and NULL_SPAN otherwise."""
        return telemetry.span(name, **attrs)

    def _pull(self, logits):
        """A model call's logits as a host array, under its own span:
        the wait for the device and the device->host copy, apart from
        the dispatch that ``serving.prefill_chunk`` covers."""
        with self._span("serving.logits_pull") as sp:
            out = np.asarray(
                logits.numpy() if hasattr(logits, "numpy") else logits)
            if sp is not None:
                sp.attrs["bytes"] = int(out.nbytes)
            return out

    def _req_span(self, name, request, **attrs):
        """Request-scoped span: recorded under the request's
        :class:`telemetry.TraceContext`, so its trace id and parent
        link stitch one request's spans across steps, preemption
        round trips, asyncio executor hops, and (via the serialized
        context on the swap records / page chains) a future
        cross-worker handoff. NULL_SPAN when no tracer is live.
        (``request`` is positional-by-convention: the ``req=`` span
        ATTRIBUTE carries the id, like every other span site.)"""
        tr = telemetry.tracer()
        if tr is None:
            return _NULL
        ctx = request.trace_ctx
        if not isinstance(ctx, telemetry.TraceContext):
            # None, or a raw wire string left unparsed because no
            # telemetry was live at submit: plain span
            return tr.span(name, **attrs)
        return telemetry.span_in(tr, ctx, name, **attrs)

    def _tag_pool_trace(self, req):
        """Stamp the request's SERIALIZED TraceContext onto its page
        chains (pool-level ``set_trace_context``): the swap records
        (``HostKVSwapSpace``) and COW chain attaches then carry the
        trace across the prefill/decode worker split of ROADMAP
        item 4 — the receiving worker re-extracts the context from
        the record instead of starting a fresh trace."""
        ctx = req.trace_ctx
        if ctx is None:
            return
        # under FLAGS_telemetry=off an ingress-provided context stays
        # the raw wire string (submit builds nothing) — propagate it
        # as-is: the cross-worker handoff must not depend on THIS
        # box's telemetry mode
        wire = ctx if isinstance(ctx, str) else ctx.to_wire()
        for c in self.model.caches:
            fn = getattr(c, "set_trace_context", None)
            if fn is not None:
                fn(req.req_id, wire)

    def _note_gen_token(self, req: Request):
        """TTFT/TPOT accounting — call right after a GENERATED token
        is appended (prompt tokens never count). The first token
        closes the submit->first-token span (TTFT); later tokens
        record the inter-token gap (TPOT). Speculative rounds commit
        bursts, so their intra-round TPOT is near zero by design —
        that IS the latency the client observes."""
        if self._traces is not None:
            self._traces.event(
                req.req_id, "token", telemetry.clock(),
                self._step_epoch, token=req.generated_ids[-1],
                n=len(req.generated_ids))
        if self._metrics is None:
            return
        self._metrics.inc("serving.generated_tokens")
        now = telemetry.clock()
        # the OpenMetrics exemplar: the trace id that landed in the
        # bucket — /metrics readers can jump from a latency bucket
        # straight to the request trace behind it
        ex = req.trace_ctx.trace_id \
            if req.trace_ctx is not None else None
        if len(req.generated_ids) == 1:
            req._ttft = now - req._t_submit
            self._metrics.observe("serving.ttft_s", req._ttft,
                                  exemplar=ex)
        else:
            gap = now - req._t_last_tok
            self._metrics.observe("serving.tpot_s", gap, exemplar=ex)
            if req._gaps is None:
                req._gaps = []
            req._gaps.append(gap)
        req._t_last_tok = now

    def _retire(self, req: Request):
        # span and histogram gate independently: a tracer armed by a
        # profiler window (metrics off) still gets its retire spans
        t0 = telemetry.clock() if self._metrics is not None else 0.0
        with self._req_span("serving.retire", req, req=req.req_id):
            self._retire_impl(req)
        met = None
        if self._metrics is not None:
            self._metrics.observe("serving.retire_s",
                                  telemetry.clock() - t0)
            self._metrics.inc("serving.requests_finished")
            met = self._slo_note_retire(req)
        if self._traces is not None:
            self._traces.complete(
                req.req_id, "retire", telemetry.clock(),
                self._step_epoch,
                generated_tokens=len(req.generated_ids),
                prefix_hit_tokens=req._prefix_hit,
                slo_met=met)
        # terminal bookkeeping lives HERE, next to the terminal trace
        # emit above — the serving-terminal-trace lint rule holds any
        # function that drops a request to that pairing
        req.state = RequestState.FINISHED
        if self._cv_state is not None:
            self._cv_state.write()
        del self._active[req.req_id]
        self._finished[req.req_id] = req

    def _slo_note_retire(self, req: Request):
        """Per-request SLO verdicts at retire: record the request in
        the goodput window (epoch-keyed) and republish the attainment
        gauges. Returns the per-SLO verdict dict (None when no SLO is
        configured)."""
        if self._slo is None:
            return None
        met = self._slo.request_meets(
            req._ttft,
            telemetry.SLOConfig.p99(req._gaps or []),
            req._qwait)
        ok = all(met.values())
        self._slo_window.append((self._step_epoch, ok, met))
        self._slo_met_all += ok
        for key, v in met.items():
            self._slo_met[key] += v
        self._publish_slo_gauges()
        return met

    def _publish_slo_gauges(self):
        """Prune the goodput window to the trailing step epochs and
        publish serving.goodput + per-SLO attainment — the exact
        numbers the future admission controller gates on. An EMPTY
        window (nothing retired recently) republishes goodput 1.0
        with slo_window_requests 0, so a stale miss never outlives
        its window: consumers weigh the fraction by the population."""
        if self._slo is None:
            return
        lo = self._step_epoch - self._win
        win = self._slo_window
        while win and win[0][0] < lo:
            _, ok, met = win.popleft()
            self._slo_met_all -= ok
            for key, v in met.items():
                self._slo_met[key] -= v
        m = self._metrics
        n = len(win)
        m.gauge("serving.slo_window_requests", n)
        if not win:
            if m.gauge_value("serving.goodput") is not None:
                m.gauge("serving.goodput", 1.0)
                for key in self._slo.request_meets(None, None, None):
                    m.gauge("serving.slo_attain_" + key, 1.0)
            return
        m.gauge("serving.goodput", self._slo_met_all / n)
        for key in win[0][2]:
            m.gauge("serving.slo_attain_" + key,
                    self._slo_met[key] / n)

    def _retire_impl(self, req: Request):
        rid = req.req_id
        if self.prefix_cache is not None:
            # keep the sequence's prefix: insert the cached tokens
            # (everything actually appended — the newest sampled token
            # never was) into the radix tree, which increfs the pages
            # so the free() below only drops THIS sequence's refs
            n = self.model.caches[0].seq_len(rid)
            toks = (req.prompt_ids + req.generated_ids)[:n]
            inserted = self.prefix_cache.insert(
                toks, self._seq_chains(rid))
            self.prefix_stats["inserted_tokens"] += inserted
            if req._prefix_path:
                self.prefix_cache.unpin(req._prefix_path)
                req._prefix_path = ()
        self.model.free(rid)
        if self.draft is not None:
            self.draft.free(rid)

    # -- the step ----------------------------------------------------------
    def apply_capacity_config(self, config: dict) -> dict:
        """Step-boundary capacity apply seam (the scheduler half of
        ``framework.autotuner.apply_config``): retarget the
        scheduler-owned capacity knobs — chunk budget, bucket ladder,
        host swap budget — on a LIVE scheduler. Must run on the
        thread that drives :meth:`step` (single-writer contract; the
        async engine marshals it onto the pump thread) and only
        between steps: calling mid-step raises, because a chunk
        budget that changes under ``_step_impl`` would desynchronize
        the packed feed already being built. Unknown keys are
        ignored; returns the dict of knobs actually changed."""
        if self._in_step:
            raise RuntimeError(
                "apply_capacity_config called mid-step — capacity "
                "knobs may only change at step boundaries (post it "
                "through ServingEngine.apply_config, or call "
                "between step()s)")
        applied = {}
        if "prefill_chunk_tokens" in config:
            v = max(1, int(config["prefill_chunk_tokens"]))
            if v != self.prefill_chunk_tokens:
                self.prefill_chunk_tokens = v
                applied["prefill_chunk_tokens"] = v
        if "serving_buckets" in config:
            bl = _parse_buckets(config["serving_buckets"])
            if bl != self.serving_buckets:
                self.serving_buckets = bl
                applied["serving_buckets"] = ",".join(
                    str(b) for b in bl)
        if "serving_swap_bytes" in config \
                and self.swap_space is not None:
            # never shrink below what is already resident: swapped
            # chains stay valid, the tier just stops admitting more
            v = max(int(config["serving_swap_bytes"]),
                    self.swap_space.used_bytes)
            if v != self.swap_space.capacity_bytes:
                self.swap_space.capacity_bytes = v
                applied["serving_swap_bytes"] = v
        return applied

    def step(self) -> dict:
        """One scheduler iteration: admit, advance the active set,
        retire completions. Returns event counters
        (admitted/advanced/finished plus the prefill/decode token
        split and, under chunked prefill, chunk_utilization and the
        adapter's ragged-dispatch compile count). Under telemetry the
        whole iteration is a ``serving.step`` span and the counters
        also land in the ``serving.*`` registry namespace
        (:meth:`metrics`); every ``FLAGS_telemetry_watchdog_stride``
        steps the gauges refresh, the watchdog detectors run, and
        the Prometheus snapshot (``FLAGS_telemetry_export_path``)
        rewrites."""
        t0 = 0.0
        if self._metrics is not None:
            # advance the epoch FIRST: every observation this step
            # lands (TTFT, gaps, step wall) is stamped with it — the
            # deterministic window key of the SLO/watchdog layer.
            # The registry owns the counter (monotonic, shared), so a
            # second scheduler never rewinds this one's windows
            self._step_epoch = self._metrics.advance_epoch()
            self._steps += 1
            t0 = telemetry.clock()
        elif self._traces is not None:
            # an armed profiler window with FLAGS_telemetry=off still
            # collects request traces — the epoch must advance so the
            # dumped events correlate to steps instead of all
            # stamping 0
            self._step_epoch += 1
        self._in_step = True
        try:
            with self._span("serving.step") as sp:
                ev = self._step_impl()
                if sp is not None:
                    sp.attrs["n"] = self._fault_step
        finally:
            self._in_step = False
        if self._step_extras:
            # per-step overload/fault annotations (preempted /
            # resumed / aborted counts, the active fault kind) ride
            # the event dict of every step shape uniformly
            ev.update(self._step_extras)
        if self._metrics is not None:
            m = self._metrics
            m.inc("serving.steps")
            m.inc("serving.prefill_tokens",
                  ev.get("prefill_tokens", 0))
            m.inc("serving.decode_tokens", ev.get("decode_tokens", 0))
            m.inc("serving.prefix_hit_tokens",
                  ev.get("prefix_hit_tokens", 0))
            m.observe("serving.step_wall_s", telemetry.clock() - t0)
            cc = getattr(self.model, "compile_count", None)
            if cc is not None:
                # the shared gauge is LAST-WRITER-WINS across
                # schedulers (kept as an alias for single-scheduler
                # dashboards); the namespaced per-scheduler gauge is
                # the truthful series
                m.gauge("serving.compile_count", cc)
                m.gauge("serving.compile_count." + self._sched_uid,
                        cc)
            apc = getattr(self.model, "attend_program_count", None)
            if apc is not None:
                # distinct attend kernel programs (ONE per packed
                # config) — same per-scheduler namespacing as
                # compile_count
                m.gauge("serving.attend_programs", apc)
                m.gauge("serving.attend_programs." + self._sched_uid,
                        apc)
            # stride on THIS scheduler's own step count: with two
            # schedulers interleaving, the shared epoch advances by 2
            # per iteration and `epoch % stride` could starve one of
            # them forever
            if self._steps % self._wd_stride == 0:
                self._observability_epoch()
        return ev

    def _observability_epoch(self):
        """The watchdog-stride housekeeping pass: refresh the
        pool/prefix/sanitizer/serving gauges, run the watchdog
        detectors (read-only; evidence like the sanitizer journal
        tail is gathered HERE, through public pool API, and handed
        in), and rewrite the Prometheus export file. The performance
        ledger republishes its plan-vs-actual gauges FIRST, so the
        plan-drift detector judges current ratios; any watchdog fire
        — warn or strict — lands an incident bundle through the
        flight recorder before a strict error propagates."""
        self._publish_gauges()
        if self._ledger is not None:
            self._ledger.publish()
        context = None
        if self._watchdog is not None:
            context = {}
            # THIS scheduler's own adapter program count — the shared
            # serving.compile_count gauge is last-writer-wins across
            # schedulers, so the storm detector needs the per-caller
            # series handed in
            cc = getattr(self.model, "compile_count", None)
            if cc is not None:
                context["compile_count"] = cc
            # evidence for a sanitizer-spike event: the journal tail
            # of the pool that actually recorded the most violations,
            # searched across EVERY cache (draft included) — not just
            # layer 0's
            caches = list(self.model.caches) + (
                list(self.draft.caches)
                if self.draft is not None else [])
            worst, worst_n = None, 0
            for c in caches:
                san = getattr(c, "sanitizer", None)
                if san is None:
                    continue
                n = san.stats().get("violations", 0)
                if n > worst_n:
                    worst, worst_n = san, n
            if worst is not None:
                context["sanitizer_journal_tail"] = worst.tail(16)
            # race-journal evidence: any concurrency-sanitizer
            # activity rides the same incident bundle as the page-
            # sanitizer tail (concurrency_journal.jsonl member)
            if self._csan is not None and self._csan.has_events():
                context["concurrency_journal_tail"] = \
                    self._csan.tail(16)
            try:
                fired = self._watchdog.check(self._step_epoch,
                                             context=context or None)
            except Exception as e:
                # strict mode raises WatchdogError AT the detecting
                # step — capture the evidence bundle first, then let
                # the error propagate (the bundle carries e.events)
                evs = getattr(e, "events", None)
                if evs is not None:
                    self._record_incident(evs, context)
                raise
            if fired:
                self._record_incident(fired, context)
        if self._export_path is not None:
            # a scrape-file failure must never take down serving:
            # warn once and stop trying (the observability layer may
            # not perturb the hot path)
            try:
                telemetry.write_prometheus(self._export_path,
                                           registry=self._metrics)
            except OSError as e:
                warnings.warn(
                    "FLAGS_telemetry_export_path "
                    f"({self._export_path!r}) is unwritable: {e}; "
                    "disabling the periodic Prometheus export",
                    RuntimeWarning)
                self._export_path = None

    def _record_incident(self, events, context):
        """Write one incident bundle for a watchdog trip (no-op
        without a recorder). A bundle-write failure must never take
        down serving — warn once and stop recording, like the
        Prometheus export."""
        if self._recorder is None:
            return
        try:
            self._recorder.record(events, context=context)
        except OSError as e:
            warnings.warn(
                "FLAGS_telemetry_incident_dir is unwritable "
                f"({e}); disabling the incident flight recorder",
                RuntimeWarning)
            self._recorder = None

    def dump_incident(self, reason: str = "manual"):
        """Explicitly capture an incident bundle RIGHT NOW (the
        on-demand half of the flight recorder): current gauges are
        republished first so the bundle reflects this instant, then
        the recorder writes one atomic bundle under
        ``FLAGS_telemetry_incident_dir``. Returns the bundle path,
        or None when no recorder is configured."""
        if self._recorder is None:
            return None
        self._publish_gauges()
        if self._ledger is not None:
            self._ledger.publish()
        return self._recorder.dump_incident(reason=reason)

    def _noop_event(self) -> dict:
        return {"admitted": 0, "advanced": 0, "finished": 0,
                "prefix_hit_tokens": 0, "prefill_tokens": 0,
                "decode_tokens": 0}

    def _note_fault(self, kind: str):
        """Annotate the step event with an active fault kind. Two
        faults can fire on one step (the shipped bench plan lands a
        preempt storm inside a delay_swap_in window) — both must
        survive onto the event, "+"-joined, not last-writer-wins."""
        cur = self._step_extras.get("faulted")
        if cur is None:
            self._step_extras["faulted"] = kind
        elif kind not in cur.split("+"):
            self._step_extras["faulted"] = cur + "+" + kind

    def _fault_gate(self):
        """Simulated step failure with retry/backoff: a ``fail_step``
        fault abandons the attempt BEFORE the model call (no state
        was mutated, so the retry is trivially safe); consecutive
        failures back off exponentially (0, 1, 3, 7, capped 8 skipped
        steps). Returns a no-op event while failing/backing off, None
        to run the step normally."""
        if self._faults is None:
            return None
        step = self._fault_step
        if step < self._resume_at:
            self._note_fault("backoff")
            if self._metrics is not None:
                self._metrics.inc("serving.step_backoff_steps")
            return self._noop_event()
        if self._faults.fail_step(step):
            self._consec_fails += 1
            skip = min(2 ** (self._consec_fails - 1) - 1, 8)
            self._resume_at = step + 1 + skip
            self._note_fault("fail_step")
            if self._metrics is not None:
                self._metrics.inc("serving.step_retries")
            return self._noop_event()
        self._consec_fails = 0
        return None

    def _step_impl(self) -> dict:
        self._step_extras = {}
        self._fault_step += 1
        noop = self._fault_gate()
        if noop is not None:
            return noop
        self._expire_deadlines()
        if self._faults is not None:
            # forced preemption storm: swap out N victims regardless
            # of pool pressure (they must restore bitwise later)
            n = self._faults.forced_preemptions(self._fault_step)
            if n:
                self._note_fault("preempt_storm")
                for _ in range(n):
                    victim = self._pick_victim()
                    if victim is None or not self._preempt(
                            victim, reason="fault"):
                        break
        self._sanitizer_epoch()
        self._admitted_step = 0
        with self._span("serving.admit"):
            hit_tokens = self._try_admit()
            if (self._swapped and self._admitted_step == 0
                    and len(self._active) < self.max_batch_size
                    and not self._step_extras.get("faulted")):
                # the queue's best candidate (which swapped requests
                # of lower priority yielded to) turned out to be
                # blocked this step — hand the idle capacity to the
                # swapped set after all, so a stuck arrival can never
                # freeze already-admitted work out of resuming. NOT
                # on faulted steps: an exhaust/delay window must keep
                # swap-in blocked (and a second consult would double-
                # count the fault in the injector's audit log)
                self._admit_swapped(None)
        # actual admissions + swap-in resumes, NOT the active-set
        # delta: a preempt-then-reject step would otherwise report a
        # NEGATIVE admission count to every event consumer
        admitted = self._admitted_step
        if not self._active:
            return {"admitted": admitted, "advanced": 0, "finished": 0,
                    "prefix_hit_tokens": hit_tokens,
                    "prefill_tokens": 0, "decode_tokens": 0}

        if self.block_length:
            return self._step_block(admitted, hit_tokens)
        if self.draft is not None:
            return self._step_spec_ragged(admitted, hit_tokens)
        if self.chunked_prefill:
            return self._step_chunked(admitted, hit_tokens)

        sids = sorted(self._active)
        feed = []
        n_pre = 0
        for s in sids:
            req = self._active[s]
            if req.state == RequestState.PREFILL:
                feed.append(req.prompt_ids[req._pos])
                n_pre += 1
            else:
                feed.append(req.generated_ids[-1])
        # one serving.decode span covers the model forward AND the
        # sampling/commit loop — the same meaning the chunked path
        # gives it (the documented span schema: retire nests inside)
        with self._span("serving.decode", rows=len(sids),
                        prefill=n_pre):
            # execution stamp for the performance ledger (framework/
            # perf_ledger.py): the model call + its device->host sync
            # is the program wall, the sampling loop below is not
            t_exec = telemetry.clock() if self._metrics is not None \
                else 0.0
            logits = self.model.decode_token(feed, sids)
            logits_np = self._pull(logits)
            if self._metrics is not None:
                self._metrics.observe("exec.wall_s.decode_token",
                                      telemetry.clock() - t_exec)
                self._metrics.inc("exec.count.decode_token")

            finished = 0
            for bi, s in enumerate(sids):
                req = self._active[s]
                if req.state == RequestState.PREFILL:
                    tok = req.prompt_ids[req._pos]
                    req._pos += 1
                    if self._traces is not None:
                        # token-per-step prefill is a 1-token chunk
                        self._traces.event(
                            req.req_id, "prefill_chunk",
                            telemetry.clock(), self._step_epoch,
                            tokens=1, pos=req._pos)
                    if req.on_token is not None:
                        req.on_token(req, tok, True)
                    if req._pos == len(req.prompt_ids):
                        if req.max_new_tokens == 0:
                            # prefill-only (scoring): no sampling
                            self._retire(req)
                            finished += 1
                            continue
                        req.state = RequestState.DECODE
                        # the last prompt position's logits sample the
                        # first generated token
                        first = self.sampler(logits_np[bi])
                        req.generated_ids.append(first)
                        self._note_gen_token(req)
                        if req.on_token is not None:
                            req.on_token(req, first, False)
                        if self._done(req, first):
                            self._retire(req)
                            finished += 1
                    continue
                tok = self.sampler(logits_np[bi])
                req.generated_ids.append(tok)
                self._note_gen_token(req)
                if req.on_token is not None:
                    req.on_token(req, tok, False)
                if self._done(req, tok):
                    self._retire(req)
                    finished += 1
        return {
            "admitted": admitted,
            "advanced": len(sids),
            "finished": finished,
            "prefix_hit_tokens": hit_tokens,
            "prefill_tokens": n_pre,
            "decode_tokens": len(sids) - n_pre,
        }

    def _chunk_feeds(self, sids):
        """Pack one ragged step: EVERY decode row (one token each)
        plus up to ``prefill_chunk_tokens`` pending prompt tokens,
        split across prefilling sequences in id order and resuming
        mid-prompt. Prefill sequences the budget cannot reach this
        step are simply left out (they advance on a later step —
        budget >= 1 guarantees progress). Returns (rows, feeds,
        starts, prefill_tokens, decode_rows)."""
        budget = self.prefill_chunk_tokens
        rows, feeds, starts = [], [], []
        n_pre = n_dec = 0
        # the one hook an adapter answers a chunk's length with: a
        # window-and-summary row ends at its window's end
        room = getattr(self.model, "chunk_room", None)
        for s in sids:
            req = self._active[s]
            if req.state == RequestState.DECODE:
                rows.append(s)
                feeds.append([req.generated_ids[-1]])
                starts.append(self.model.caches[0].seq_len(s))
                n_dec += 1
            elif budget > 0:
                take = min(len(req.prompt_ids) - req._pos, budget)
                if room is not None:
                    take = min(take, room(s) or take)
                budget -= take
                rows.append(s)
                feeds.append(req.prompt_ids[req._pos:req._pos + take])
                starts.append(req._pos)
                n_pre += take
        return rows, feeds, starts, n_pre, n_dec

    def _stream_prompt_chunk(self, req, toks):
        """A chunk of prompt tokens is in the pool: count and stream it."""
        req._pos += len(toks)
        if self._traces is not None:
            self._traces.event(
                req.req_id, "prefill_chunk", telemetry.clock(),
                self._step_epoch, tokens=len(toks), pos=req._pos)
        if req.on_token is not None:
            for t in toks:
                req.on_token(req, t, True)

    def _advance_prefill_row(self, req, toks, logits_row) -> int:
        """Commit one chunk of prompt tokens for a PREFILL row:
        stream them, and when the chunk finishes the prompt either
        retire (prefill-only) or sample the first generated token
        from the chunk's last-position logits — the shared completion
        logic of the chunked step and the speculative prompt phase
        (in spec mode ``self.sampler`` is the greedy argmax default:
        a custom sampler is rejected at construction). Returns 1 if
        the request retired."""
        self._stream_prompt_chunk(req, toks)
        if req._pos < len(req.prompt_ids):
            return 0
        if req.max_new_tokens == 0:
            # prefill-only (scoring): no sampling
            self._retire(req)
            return 1
        req.state = RequestState.DECODE
        first = self.sampler(logits_row)
        req.generated_ids.append(first)
        self._note_gen_token(req)
        if req.on_token is not None:
            req.on_token(req, first, False)
        if self._done(req, first):
            self._retire(req)
            return 1
        return 0

    def _step_chunked(self, admitted, hit_tokens) -> dict:
        """Chunked-prefill scheduler step: one ragged
        ``prefill_chunk`` call advances every decode row by one token
        and every budget-reached prefill row by its whole chunk —
        greedy outputs are token-identical to the token-per-step path
        (pinned in tests/test_chunked_prefill.py)."""
        with self._span("serving.pack") as sp:
            sids = sorted(self._active)
            rows, feeds, starts, n_pre, n_dec = self._chunk_feeds(sids)
            packed = sum(len(f) for f in feeds)
            pad_to = bucket_packed_tokens(packed, self.serving_buckets)
            if sp is not None:
                sp.attrs.update(rows=len(rows), packed=packed,
                                pad_to=pad_to, prefill=n_pre)
        t_exec = telemetry.clock() if self._metrics is not None \
            else 0.0
        with self._span("serving.prefill_chunk", rows=len(rows),
                        packed=packed, pad_to=pad_to, prefill=n_pre,
                        decode=n_dec):
            logits = self.model.prefill_chunk(
                feeds, rows, starts, pad_to=pad_to)
        logits_np = self._pull(logits)
        if self._metrics is not None:
            # execution stamp for the performance ledger: one ragged
            # program invocation per step under the "prefill_chunk"
            # key — register a plan under the same name (bench.py
            # does, for the paged attend program) and the ledger
            # reports its attained bytes/s, MFU and plan drift
            self._metrics.observe("exec.wall_s.prefill_chunk",
                                  telemetry.clock() - t_exec)
            self._metrics.inc("exec.count.prefill_chunk")

        finished = 0
        with self._span("serving.decode", rows=len(rows)):
            for bi, s in enumerate(rows):
                req = self._active[s]
                if req.state == RequestState.PREFILL:
                    finished += self._advance_prefill_row(
                        req, feeds[bi], logits_np[bi])
                    continue
                tok = self.sampler(logits_np[bi])
                req.generated_ids.append(tok)
                self._note_gen_token(req)
                if req.on_token is not None:
                    req.on_token(req, tok, False)
                if self._done(req, tok):
                    self._retire(req)
                    finished += 1

        cs = self.chunk_stats
        cs["steps"] += 1
        cs["chunk_calls"] += 1
        cs["prefill_tokens"] += n_pre
        cs["decode_tokens"] += n_dec
        cs["packed_tokens"] += packed
        cs["padded_tokens"] += pad_to - packed
        return {
            "admitted": admitted,
            "advanced": len(rows),
            "finished": finished,
            "prefix_hit_tokens": hit_tokens,
            "prefill_tokens": n_pre,
            "decode_tokens": n_dec,
            "chunk_utilization": round(packed / pad_to, 4),
            "compile_count": getattr(self.model, "compile_count",
                                     None),
            "attend_programs": getattr(
                self.model, "attend_program_count", None),
        }

    # -- generation by diffusion over blocks ------------------------------
    def _prompt_end(self, req) -> int:
        """The prompt tokens fed by clean passes: its whole blocks (the
        tail shares the first generated block), all of it where nothing
        is generated (a prompt scored)."""
        n = len(req.prompt_ids)
        return n if req.max_new_tokens == 0 else n - n % self.block_length

    def _open_block(self, req, behind=()):
        """The block after what the row holds (the committed tokens and
        ``behind``, the finished block not in the pages yet): the
        prompt's tail, if the prompt ends inside it, and MASK behind."""
        base = self.model.caches[0].seq_len(req.req_id) + len(behind)
        req._block = _Block(req.prompt_ids[base:], self.block_length,
                            self.model.mask_token_id, behind)
        req.state = RequestState.DECODE

    def _block_feeds(self, sids):
        """Pack one block step: EVERY decode row's open block at its
        committed length (B ids; 2B in the pass that carries the finished
        block behind it) plus up to ``prefill_chunk_tokens`` pending
        prompt tokens in whole blocks. Returns (rows, feeds, starts,
        prefill_tokens, indices of the decode rows)."""
        b = self.block_length
        # prompt chunks end on block boundaries: whole blocks a step
        budget = max(b, self.prefill_chunk_tokens
                     - self.prefill_chunk_tokens % b)
        rows, feeds, starts, dec = [], [], [], []
        n_pre = 0
        for s in sids:
            req = self._active[s]
            if req.state == RequestState.PREFILL:
                end = self._prompt_end(req)
                if req._pos < end:
                    if budget <= 0:
                        continue
                    take = min(end - req._pos, budget)
                    budget -= -(-take // b) * b
                    rows.append(s)
                    feeds.append(req.prompt_ids[req._pos:req._pos + take])
                    starts.append(req._pos)
                    n_pre += take
                    continue
                self._open_block(req)   # a prompt shorter than a block
            dec.append(len(rows))
            rows.append(s)
            feeds.append(req._block.behind + req._block.ids)
            starts.append(self.model.caches[0].seq_len(s))
        return rows, feeds, starts, n_pre, dec

    def _unmask(self, blk, choice) -> int:
        """One denoising pass's outcome for one row: fix this pass's
        positions by the ``remasking`` rule with the device's choice
        (``choice`` [B, 3]: best token, its logit, logsumexp). Returns how
        many positions were fixed."""
        live = [p for p in range(self.block_length) if blk.masked[p]]
        n = min(self._shares[blk.passes], len(live))
        logp = choice[:, 1] - choice[:, 2]
        if self.remasking == "sequential":
            picks = live[:n]
        else:
            picks = sorted(live, key=lambda p: -logp[p])[:n]
            if self.remasking == "low_confidence_dynamic":
                sure = [p for p in live
                        if logp[p] > np.log(self.confidence_threshold)]
                if len(sure) >= n:
                    picks = sure
        for p in picks:
            blk.ids[p] = int(choice[p, 0])
            blk.masked[p] = False
        blk.passes += 1
        return len(picks)

    def _roll_back(self, req, n):
        """The open block's slots hold a pass's K/V, not the block's: the
        row's pools go back to ``n`` tokens, its committed length with the
        finished block this pass carried (the next pass writes the open
        block's slots again)."""
        for c in self.model.caches:
            c.truncate(req.req_id, n)

    def _deliver_block(self, req) -> tuple:
        """The outcome of the pass that fixed the block's last position:
        the block's tokens reach the request in position order (the
        prompt's tail as prompt tokens), up to the token that ends it: a
        request that ends here retires and its last block's K/V is never
        written. Otherwise the next block opens with this one behind it.
        Returns (tokens delivered, 1 if it retired)."""
        blk, n = req._block, 0
        for t in blk.ids[:blk.n_prompt]:
            req._pos += 1
            if req.on_token is not None:
                req.on_token(req, t, True)
        for t in blk.ids[blk.n_prompt:]:
            req.generated_ids.append(t)
            self._note_gen_token(req)
            n += 1
            if req.on_token is not None:
                req.on_token(req, t, False)
            if self._done(req, t):
                self._retire(req)
                return n, 1
        self._open_block(req, behind=blk.ids)
        return n, 0

    def _step_block(self, admitted, hit_tokens) -> dict:
        """The block-diffusion scheduler step (module docstring): one
        ragged ``prefill_chunk`` call feeds every decode row's open block
        (behind the finished block whose K/V is still to be written, in
        the open block's first pass) and the step's prompt chunks (whole
        blocks), and returns the device's choice for every position of
        the decode rows' open blocks. Every decode row made a denoising
        pass: this pass's positions are fixed, the open block's slots are
        rolled back (``truncate``; a carried block stays), and a block
        with no MASK left is delivered. ``sampler`` is not called."""
        b = self.block_length
        with self._span("serving.pack") as sp:
            sids = sorted(self._active)
            rows, feeds, starts, n_pre, dec = self._block_feeds(sids)
            packed = sum(len(f) for f in feeds)
            pad_to = bucket_packed_tokens(packed, self.serving_buckets)
            if sp is not None:
                sp.attrs.update(rows=len(rows), packed=packed,
                                pad_to=pad_to, prefill=n_pre)
        t_exec = telemetry.clock() if self._metrics is not None \
            else 0.0
        with self._span("serving.prefill_chunk", rows=len(rows),
                        packed=packed, pad_to=pad_to, prefill=n_pre,
                        decode=packed - n_pre):
            out = self.model.prefill_chunk(
                feeds, rows, starts, pad_to=pad_to, choose_rows=dec)
        choice = self._pull(out).reshape(len(dec), b, 3)
        if self._metrics is not None:
            self._metrics.observe("exec.wall_s.prefill_chunk",
                                  telemetry.clock() - t_exec)
            self._metrics.inc("exec.count.prefill_chunk")

        finished = carried = unmasked = blocks = delivered = 0
        with self._span("serving.block") as sp:
            for k, bi in enumerate(dec):
                req = self._active[rows[bi]]
                blk = req._block
                keep = starts[bi] + len(blk.behind)
                carried += bool(blk.behind)
                blk.behind = []
                unmasked += self._unmask(blk, choice[k])
                self._roll_back(req, keep)
                if not any(blk.masked):
                    n, done = self._deliver_block(req)
                    blocks += 1
                    delivered += n
                    finished += done
            for bi, s in enumerate(rows):
                req = self._active.get(s)
                if req is None or req.state != RequestState.PREFILL:
                    continue
                finished += self._advance_block_prefill(req, feeds[bi])
            if sp is not None:
                # a row's pass counts once, whatever it carried
                sp.attrs.update(
                    rows=len(rows), denoise_rows=len(dec),
                    carried_rows=carried, unmasked=unmasked,
                    delivered=delivered)

        for key, n in (("denoise_passes", len(dec)),
                       ("commits_carried", carried),
                       ("tokens_unmasked", unmasked),
                       ("blocks_committed", blocks)):
            self.block_stats[key] += n
            if self._metrics is not None:
                self._metrics.inc("diffusion." + key, n)
        cs = self.chunk_stats
        cs["steps"] += 1
        cs["chunk_calls"] += 1
        cs["prefill_tokens"] += n_pre
        cs["decode_tokens"] += packed - n_pre
        cs["packed_tokens"] += packed
        cs["padded_tokens"] += pad_to - packed
        return {
            "admitted": admitted,
            "advanced": len(rows),
            "finished": finished,
            "prefix_hit_tokens": hit_tokens,
            "prefill_tokens": n_pre,
            # the tokens the decode rows FED (a block or two a row); what
            # reached the requests is delivered_tokens
            "decode_tokens": packed - n_pre,
            "delivered_tokens": delivered,
            "chunk_utilization": round(packed / pad_to, 4),
            "compile_count": getattr(self.model, "compile_count",
                                     None),
            "attend_programs": getattr(
                self.model, "attend_program_count", None),
        }

    def _advance_block_prefill(self, req, toks) -> int:
        """Commit one chunk of prompt tokens of a block-diffusion row:
        stream them, and when the prompt's whole blocks are in, open the
        first block (or retire a prompt that was only scored). Returns 1
        if the request retired."""
        self._stream_prompt_chunk(req, toks)
        if req._pos < self._prompt_end(req):
            return 0
        if req.max_new_tokens == 0:
            self._retire(req)
            return 1
        self._open_block(req)
        return 0

    def _commit_spec_row(self, s, props_i, preds_i, base_t, base_d):
        """Greedy acceptance for ONE spec-active decode row: commit
        the longest draft-proposal prefix matching the target's
        per-position argmax, plus the target's bonus token, then roll
        BOTH pools back to the committed prefix (everything except
        the newest token, which feeds the next round). ``props_i`` is
        the row's draft_k proposals; ``preds_i`` the target argmax at
        each of the draft_k+1 window positions; ``base_t``/``base_d``
        the target/draft cache lengths before the round. Returns
        ``(committed, retired)``."""
        req = self._active[s]
        k = len(props_i)
        n_acc = 0
        while n_acc < k and props_i[n_acc] == int(preds_i[n_acc]):
            n_acc += 1
            if (req.eos_id is not None
                    and props_i[n_acc - 1] == req.eos_id):
                break
        accepted = list(props_i[:n_acc])
        if (req.eos_id is None or not accepted
                or accepted[-1] != req.eos_id):
            accepted.append(int(preds_i[n_acc]))
        done = False
        committed = 0
        for t in accepted:
            req.generated_ids.append(t)
            self._note_gen_token(req)
            committed += 1
            self.spec_stats["committed_tokens"] += 1
            if req.on_token is not None:
                req.on_token(req, t, False)
            if self._done(req, t):
                done = True
                break
        self.spec_stats["proposed_tokens"] += k
        self.spec_stats["accepted_draft_tokens"] += n_acc
        if self._metrics is not None:
            self._metrics.observe("serving.spec_accept_rate",
                                  (n_acc / k) if k else 0.0)
            self._metrics.inc("serving.spec_committed_tokens",
                              committed)
        if done:
            if self.prefix_cache is not None:
                # retire inserts the chain into the radix tree keyed
                # by the COMMITTED token stream — drop the unverified
                # window tail first so cached KV == committed tokens
                for c in self.model.caches:
                    c.truncate(s, base_t + committed)
            self._retire(req)
            return committed, True
        if self._metrics is not None:
            self._metrics.inc("serving.spec_rollback_tokens",
                              (k + 1) - committed)
        # committed prefix back in the caches: everything except the
        # newest token (fed next round)
        for c in self.model.caches:
            c.truncate(s, base_t + committed)
        for c in self.draft.caches:
            c.truncate(s, base_d + committed)
        return committed, False

    def _step_spec_ragged(self, admitted, hit_tokens) -> dict:
        """The speculative scheduler step (ISSUE 19): one decode
        round is exactly TWO bucketed ragged program families. The
        draft adapter proposes ``draft_k`` tokens through its OWN
        chunked step — call 0 packs every propose row together with
        prompt-mirror chunks and draft-refill rows, calls 1..k feed
        successive proposals (the k-th feed keeps the draft pool at
        committed prefix + window) — then the target verifies EVERY
        window in the ordinary :meth:`prefill_chunk` step: each
        spec-active sequence contributes one right-aligned
        ``draft_k+1``-token row next to the regular prefill-chunk
        rows, and the per-position logits epilogue (``logits_rows=``)
        hands back the window argmax for greedy acceptance. ``cache.truncate`` rolls both pools back past
        the first mismatch (COW/prefix-shared pages survive — page
        sanitizer strict). No per-sequence target forward exists on
        this path.

        Draft-lag rows: after a prefix-cache hit or a swap-in the
        draft pool is behind the committed prefix (its KV was never
        built, or was discarded at swap-out). Such rows pause
        target-side and instead REFILL the draft cache from the
        committed token stream under the chunk budget until it
        catches up — wait-free, no separate prefill pass, and they
        count as advanced so the stall watchdog stays quiet."""
        sids = sorted(self._active)
        t_cache = self.model.caches[0]
        d_cache = self.draft.caches[0]
        k = self.draft_k
        pre, dec, lag = [], [], []
        for s in sids:
            req = self._active[s]
            if req.state == RequestState.PREFILL:
                pre.append(s)
            elif d_cache.seq_len(s) == t_cache.seq_len(s):
                dec.append(s)
            else:
                lag.append(s)
        base_t = {s: t_cache.seq_len(s) for s in dec}
        base_d = {s: d_cache.seq_len(s) for s in dec}
        # target-side chunk plan for the prefill rows (shared budget)
        if pre:
            rows, feeds, starts, n_pre, _ = self._chunk_feeds(pre)
        else:
            rows, feeds, starts, n_pre = [], [], [], 0

        # ---- draft program: propose, mirror, refill — all rows of
        # the draft adapter's own bucketed chunked step
        props = []  # props[j][i] = (j+1)-th proposal for dec[i]
        lag_refilled = 0
        refill_tokens = 0
        t_draft = telemetry.clock() if self._metrics is not None \
            else 0.0
        with self._span("serving.draft_propose", rows=len(dec),
                        refill=len(lag), draft_k=k):
            d_rows, d_feeds, d_starts = [], [], []
            for i, s in enumerate(dec):
                d_rows.append(s)
                d_feeds.append([self._active[s].generated_ids[-1]])
                d_starts.append(base_d[s])
            # refill lagging draft chains from the committed stream
            # (lag rows first — they block verify entirely — then
            # prefix-hit prefill rows whose draft never saw the hit)
            d_budget = self.prefill_chunk_tokens
            for s in lag + [r for r in pre
                            if d_cache.seq_len(r) < t_cache.seq_len(r)]:
                if d_budget <= 0:
                    break
                req = self._active[s]
                d_len = d_cache.seq_len(s)
                gap = t_cache.seq_len(s) - d_len
                take = min(gap, d_budget)
                if take <= 0:
                    continue
                d_budget -= take
                allt = req.prompt_ids + req.generated_ids
                d_rows.append(s)
                d_feeds.append(allt[d_len:d_len + take])
                d_starts.append(d_len)
                refill_tokens += take
                if req.state == RequestState.DECODE:
                    lag_refilled += 1
            # mirror this step's prompt chunks for draft-synced
            # prefill rows (same feed, same start), packed into the
            # same call
            for bi, r in enumerate(rows):
                if d_cache.seq_len(r) == starts[bi]:
                    d_rows.append(r)
                    d_feeds.append(feeds[bi])
                    d_starts.append(starts[bi])
            if d_rows:
                packed0 = sum(len(f) for f in d_feeds)
                pad0 = bucket_packed_tokens(packed0,
                                            self.serving_buckets)
                dl = self.draft.prefill_chunk(
                    d_feeds, d_rows, d_starts, pad_to=pad0)
            if dec:
                dl_np = self._pull(dl)
                cur = [int(np.argmax(dl_np[i]))
                       for i in range(len(dec))]
                props.append(cur)
                pad_j = bucket_packed_tokens(len(dec),
                                             self.serving_buckets)
                for j in range(1, k + 1):
                    dl = self.draft.prefill_chunk(
                        [[c] for c in cur], dec,
                        [base_d[s] + j for s in dec], pad_to=pad_j)
                    if j == k:
                        # k-th proposal fed for pool symmetry with
                        # the window; its logits are never sampled
                        break
                    dl_np = self._pull(dl)
                    cur = [int(np.argmax(dl_np[i]))
                           for i in range(len(dec))]
                    props.append(cur)
        if self._metrics is not None:
            # performance-ledger stamp for the DRAFT program: its
            # share_of_step_wall is the draft overhead the acceptance
            # rate has to pay for (framework/perf_ledger.py)
            self._metrics.observe("exec.wall_s.draft_propose",
                                  telemetry.clock() - t_draft)
            self._metrics.inc("exec.count.draft_propose")
        self.spec_stats["refill_tokens"] += refill_tokens

        # ---- target program: ONE packed ragged step — verify rows
        # (right-aligned k+1-token windows, listed first) next to the
        # ordinary prefill-chunk rows
        t_rows, t_feeds, t_starts = [], [], []
        for i, s in enumerate(dec):
            t_rows.append(s)
            t_feeds.append([self._active[s].generated_ids[-1]]
                           + [props[j][i] for j in range(k)])
            t_starts.append(base_t[s])
        t_rows += rows
        t_feeds += feeds
        t_starts += starts

        finished = 0
        dec_tokens = 0
        preds = last_np = None
        packed = pad_to = 0
        if t_rows:
            packed = sum(len(f) for f in t_feeds)
            pad_to = bucket_packed_tokens(packed, self.serving_buckets)
            t_exec = telemetry.clock() if self._metrics is not None \
                else 0.0
            with self._span("serving.prefill_chunk", rows=len(t_rows),
                            packed=packed, pad_to=pad_to,
                            prefill=n_pre, decode=0, verify=len(dec)):
                out = self.model.prefill_chunk(
                    t_feeds, t_rows, t_starts, pad_to=pad_to,
                    logits_rows=(list(range(len(dec))) if dec
                                 else None))
            if dec:
                last, full = out
                preds = np.argmax(
                    self._pull(full).reshape(len(dec), k + 1, -1),
                    axis=-1)
            else:
                last = out
            last_np = self._pull(last)
            if self._metrics is not None:
                self._metrics.observe("exec.wall_s.prefill_chunk",
                                      telemetry.clock() - t_exec)
                self._metrics.inc("exec.count.prefill_chunk")
            cs = self.chunk_stats
            cs["steps"] += 1
            cs["chunk_calls"] += 1
            cs["prefill_tokens"] += n_pre
            cs["packed_tokens"] += packed
            cs["padded_tokens"] += pad_to - packed
            if dec:
                self.spec_stats["rounds"] += 1
                self.spec_stats["target_calls"] += 1
                self.spec_stats["draft_calls"] += k + 1
                if self._metrics is not None:
                    self._metrics.inc("serving.spec_rounds")

            # accept/commit (and retire/rollback) inside the decode
            # span — same schema as every other scheduler path
            with self._span("serving.decode", rows=len(t_rows),
                            draft_k=k):
                for i, s in enumerate(dec):
                    committed, retired = self._commit_spec_row(
                        s, [props[j][i] for j in range(k)], preds[i],
                        base_t[s], base_d[s])
                    dec_tokens += committed
                    finished += int(retired)
                for bi, r in enumerate(rows):
                    finished += self._advance_prefill_row(
                        self._active[r], feeds[bi],
                        last_np[len(dec) + bi])

        out = {
            "admitted": admitted,
            "advanced": len(t_rows) + lag_refilled,
            "finished": finished,
            "prefix_hit_tokens": hit_tokens,
            "prefill_tokens": n_pre,
            "decode_tokens": dec_tokens,
            "spec_verify_rows": len(dec),
            "compile_count": getattr(self.model, "compile_count",
                                     None),
            "attend_programs": getattr(
                self.model, "attend_program_count", None),
        }
        if t_rows:
            out["chunk_utilization"] = round(packed / pad_to, 4)
        return out

    def _done(self, req: Request, last_tok: int) -> bool:
        if req.eos_id is not None and last_tok == req.eos_id:
            return True
        return len(req.generated_ids) >= req.max_new_tokens

    def run_until_complete(self, max_steps=10_000) -> dict:
        """Drain the queue + active + swapped sets; returns terminal
        requests by id (finished AND deadline-aborted — check
        ``req.state``)."""
        for _ in range(max_steps):
            if not self._queue and not self._active \
                    and not self._swapped:
                break
            ev = self.step()
            if (ev["advanced"] == 0 and ev["admitted"] == 0
                    and (self._queue or self._swapped)
                    and not ev.get("faulted")
                    and not ev.get("aborted")
                    and not ev.get("preempted")):
                # defensive: submit() rejects never-admissible requests
                # and active requests always finish, so this fires only
                # on an accounting bug or external pool interference
                # (injected faults and deadline sweeps are progress in
                # their own right and exempt)
                raise RuntimeError(
                    "scheduler stalled: nothing active yet the queue "
                    "head cannot be admitted; "
                    f"{self.page_pool_stats()}"
                )
        else:
            raise RuntimeError(f"not drained after {max_steps} steps")
        return dict(self._finished)

    # -- introspection -----------------------------------------------------
    @property
    def num_active(self):
        return len(self._active)

    @property
    def num_queued(self):
        return len(self._queue)

    @property
    def num_swapped(self):
        return len(self._swapped)

    @property
    def watchdog(self):
        """The scheduler's Watchdog (or None when telemetry/watchdog
        is off) — read-only; the engine's admission gate polls its
        ``summary()['by_class']`` counts for fresh events."""
        return self._watchdog

    def result(self, req_id: str) -> Request:
        return self._finished[req_id]
