"""FLAGS registry — analog of the reference's exported gflags system
(upstream: paddle/phi/core/flags.cc, paddle/utils/flags.h).

Flags are registered with a type and default, overridable by FLAGS_*
environment variables at import, and by paddle_tpu.set_flags at runtime.
When the native runtime extension (csrc/) is available the registry is
mirrored there; otherwise this pure-Python registry is authoritative.
"""
from __future__ import annotations

import os
from typing import Any, Dict

_REGISTRY: Dict[str, Any] = {}
_META: Dict[str, tuple] = {}  # name -> (type, help)


def _parse(value: str, typ):
    if typ is bool:
        return value.lower() in ("1", "true", "yes", "on")
    return typ(value)


def define_flag(name: str, default, help_str: str = ""):
    typ = type(default)
    env = os.environ.get("FLAGS_" + name)
    _META[name] = (typ, help_str)
    _REGISTRY[name] = _parse(env, typ) if env is not None else default


def get_flags(flags):
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for f in flags:
        key = f[6:] if f.startswith("FLAGS_") else f
        if key not in _REGISTRY:
            raise ValueError(f"unknown flag {f}")
        out[f] = _REGISTRY[key]
    return out


def set_flags(flags: Dict[str, Any]):
    for f, v in flags.items():
        key = f[6:] if f.startswith("FLAGS_") else f
        if key not in _REGISTRY:
            raise ValueError(f"unknown flag {f}")
        typ = _META[key][0]
        _REGISTRY[key] = _parse(v, typ) if isinstance(v, str) else typ(v)
        _on_set(key, _REGISTRY[key])


def _on_set(key, value):
    if key == "check_nan_inf":
        import jax

        jax.config.update("jax_debug_nans", bool(value))


def flag(name: str):
    return _REGISTRY[name]


# -- core flags (subset of the reference's, TPU-meaningful) -----------------
define_flag("check_nan_inf", False,
            "check every op output for nan/inf (jax_debug_nans)")
define_flag("benchmark", False, "benchmark mode: sync after each op")
define_flag("use_pallas_flash_bwd", True,
            "use the dedicated Pallas flash-attention backward kernels "
            "(off -> chunked XLA recompute backward)")
define_flag("use_pallas_kernels", True,
            "use hand-written Pallas TPU kernels where available")
define_flag("allocator_strategy", "auto_growth",
            "kept for API parity; XLA/PJRT owns TPU memory")
define_flag("log_level", 0, "VLOG-style verbosity")
define_flag("cudnn_deterministic", False, "API parity; XLA is deterministic")
define_flag("enable_signal_handler", True,
            "install faulthandler-based crash/TERM stack dumps at init")
define_flag("embedding_deterministic", 0, "API parity")

if os.environ.get("FLAGS_check_nan_inf"):
    _on_set("check_nan_inf", _REGISTRY["check_nan_inf"])
define_flag("flash_precision_highest", False,
            "force fp32-emulated (multi-pass) MXU multiplies in the "
            "Pallas flash-attention kernels; default uses native bf16 "
            "single-pass with fp32 accumulation")
define_flag("pallas_interpret", False,
            "run the Pallas kernels in interpret mode "
            "off-TPU (CI coverage of the kernel path on CPU)")
define_flag("xla_comm_extra_flags", "",
            "space-separated XLA flags propagated to every launched "
            "worker's environment before backend init (deployment "
            "tuning; distributed/comm_flags.py). The latency-hiding "
            "scheduler itself is default-on in current XLA")
define_flag("dy2static_convert_control_flow", True,
            "AST-convert if/while in @to_static functions for traced-"
            "predicate dispatch (upstream: jit/dy2static transformers)")
define_flag("compilation_cache_dir", "",
            "persistent XLA compilation-cache directory (empty -> "
            "<checkout>/.jax_cache; 'off' disables; where "
            "JAX_COMPILATION_CACHE_DIR is set JAX reads that and this "
            "flag sets nothing). Analog of "
            "the reference persisting optimized inference programs "
            "(paddle/fluid/inference/api/analysis_predictor.cc)")
define_flag("jit_lint", "warn",
            "trace-time jaxpr linter over @to_static programs "
            "(framework/analysis.py): 'off' skips analysis entirely, "
            "'warn' logs findings (criticals to the console, the rest "
            "to VLOG(1)), 'strict' raises JitLintError at compile on "
            "any warning/critical finding")
define_flag("jit_lint_suppress", "",
            "comma-separated lint rule ids to suppress globally "
            "(e.g. 'dtype-drift,donation-miss'; see "
            "framework/analysis.RULES for the id list)")
define_flag("jit_plan", "report",
            "static resource planner over @to_static programs "
            "(framework/planner.py): 'off' skips planning entirely "
            "(the module is never imported; zero allocations), "
            "'report' (default) computes each compiled program's "
            "peak-live-HBM / collective-byte plan, attaches it to "
            "the cache entry, emits compile.hbm_peak_bytes and "
            "compile.comm_bytes.<axis> telemetry, and logs planner "
            "findings, 'strict' raises JitPlanError at compile time "
            "on any hbm-over-budget / comm-over-budget / comm-bound-"
            "program / dead-collective finding (suppression shares "
            "the linter's three scopes; docs/ANALYSIS.md)")
define_flag("jit_budget_hbm", 0,
            "per-program peak-live-HBM budget in bytes for the "
            "static resource planner: a compiled program whose "
            "planned peak (linear-scan buffer lifetimes, donation/"
            "alias aware) exceeds this fires hbm-over-budget "
            "(critical; compile fails under FLAGS_jit_plan=strict). "
            "0 (default) disables the gate")
define_flag("jit_budget_comm", 0,
            "per-program per-device collective-traffic budget in "
            "bytes for the static resource planner: a compiled "
            "program whose planned wire bytes (summed over all mesh "
            "axes) exceed this fires comm-over-budget (critical). "
            "0 (default) disables the gate")
define_flag("jit_plan_comm_bound_ratio", 8.0,
            "comm-bound-program threshold for the static resource "
            "planner: a compiled program whose flops-per-comm-byte "
            "ratio falls below this while moving >=4-byte collective "
            "elements is flagged as a quantized-ring candidate "
            "(EQuARX-style quantize-on-the-wire would halve the "
            "bytes; ROADMAP item 3). 0 disables the check")
define_flag("jit_lint_donation_min_bytes", 1 << 20,
            "donation-miss threshold: written-each-step state buffers "
            "at least this large must be donated into the compiled "
            "step (jit/api.py donate_argnums) or the rule fires")
define_flag("jit_lint_flops_threshold", 1e10,
            "unsharded-compute threshold: a single matmul/conv eqn "
            "above this many FLOPs with every operand replicated on a "
            ">1-device mesh fires the rule")
define_flag("collective_matmul", "auto",
            "ring-decomposed collective+matmul for the TP/SP hot path "
            "(ops/kernels/collective_matmul.py): 'off' keeps the plain "
            "blocking all_gather/reduce-scatter chains (bit-identical "
            "lowering), 'on' decomposes wherever structurally possible, "
            "'auto' decomposes only above "
            "FLAGS_collective_matmul_min_bytes — tiny matmuls lose to "
            "ring hop latency (docs/OVERLAP.md; the deployment-tuning "
            "companion of distributed/comm_flags.py)")
define_flag("collective_matmul_min_bytes", 4 << 20,
            "auto-mode decomposition threshold: decompose a dependent "
            "collective+matmul pair only when the blocking collective "
            "would move at least this many bytes; also the trace "
            "linter's overlap-miss threshold (framework/analysis.py) "
            "and the quantize-on-the-wire auto-decline floor "
            "(FLAGS_collective_dtype)")
define_flag("collective_dtype", "off",
            "quantize-on-the-wire dtype for the chunked ring "
            "collectives (ops/kernels/collective_matmul.py): 'off' "
            "(default) ships fp chunks and keeps every ring lowering "
            "bit-identical to the unquantized path (pinned like "
            "FLAGS_collective_matmul=off); 'int8' ships each ring hop "
            "as an EQuARX-style block-scaled int8 payload plus one "
            "f32 scale per wire_block (128) of the trailing dim, with "
            "dequant fused chunk-local before the partial matmul and "
            "the custom-VJP backwards quantizing their cotangent "
            "rings the same way; 'fp8' uses float8_e4m3 where the "
            "jax build supports it (falls back to int8 otherwise). "
            "Applies to the TP/SP collective-matmul rings, the DP "
            "grad-sync ring (mp_ops.grad_allreduce_dispatch) and the "
            "MoE expert all-to-all overlap; auto-declines below "
            "FLAGS_collective_matmul_min_bytes (docs/OVERLAP.md)")
define_flag("prefill_chunk_tokens", 64,
            "chunked-prefill token budget for the paged serving "
            "scheduler (inference/serving.py): each BatchScheduler "
            "step packs every active decode row plus up to this many "
            "pending prompt tokens (split across sequences, resuming "
            "mid-prompt) into ONE ragged model call via "
            "PagedLlamaAdapter.prefill_chunk — Sarathi-style budget "
            "packing keeps decode latency flat while prefill "
            "saturates the chip (docs/SERVING.md)")
define_flag("serving_buckets", "8,16,32,64,128,256",
            "comma-separated packed-token buckets for the chunked-"
            "prefill ragged dispatch: the per-step packed token count "
            "(decode rows + prefill chunk tokens) is padded up to the "
            "smallest bucket >= count (tail masked), so steady-state "
            "serving compiles at most len(buckets) ragged programs "
            "instead of one per distinct packed length. Counts beyond "
            "the largest bucket round up to the next power of two "
            "(each such shape is one extra compile)")
define_flag("page_sanitizer", "off",
            "KV page-pool sanitizer for the paged serving stack "
            "(incubate/nn/page_sanitizer.py): 'off' (default) is "
            "zero-cost — no shadow objects are allocated and every "
            "instrumented pool mutation is a single attribute check; "
            "'warn' mirrors every PagedKVCacheManager mutation into a "
            "shadow heap, validates it (use-after-free via page "
            "generations, double-free, refcount leaks, copy-on-write "
            "violations, stale page-table rows, capacity drift) and "
            "logs violations as RuntimeWarning; 'strict' raises "
            "PageSanitizerError carrying the journal tail, and "
            "BatchScheduler additionally runs "
            "assert_ref_invariants() at the epoch stride "
            "(docs/ANALYSIS.md)")
define_flag("page_sanitizer_journal", 512,
            "bounded event-journal chunk size for the page sanitizer: "
            "the journal keeps a shadow-heap snapshot plus up to this "
            "many typed events, so a dumped journal always replays "
            "(python -m paddle_tpu.incubate.nn.page_sanitizer "
            "--replay <file>) from a sound state regardless of how "
            "long the pool ran")
define_flag("page_sanitizer_stride", 16,
            "epoch cross-check stride for the page sanitizer: every "
            "this many BatchScheduler steps the shadow heap is "
            "compared against the real pool (refcounts, free list, "
            "sequence lens, num_free_pages capacity accounting) and, "
            "in strict mode, assert_ref_invariants() runs on every "
            "cache")
define_flag("concurrency_sanitizer", "off",
            "host-plane concurrency sanitizer (framework/"
            "concurrency.py): 'off' (default) is zero-cost — no "
            "shadow objects are allocated, guarded() hands back a "
            "plain threading.Lock and every instrumented site pays "
            "one attribute check (same tracemalloc-gated discipline "
            "as FLAGS_page_sanitizer=off); 'warn' runs the lockset + "
            "vector-clock happens-before race detector over the "
            "instrumented serving/telemetry modules (unguarded "
            "shared writes, lockset-empty read-write races, "
            "lock-order inversions, blocking acquires on a running "
            "event loop, unsanctioned writer threads) and reports "
            "violations as RuntimeWarning; 'strict' raises "
            "ConcurrencyError carrying the journal tail. The mode is "
            "read when the instrumented object is CONSTRUCTED "
            "(docs/ANALYSIS.md)")
define_flag("concurrency_journal", 512,
            "bounded event-journal chunk size for the concurrency "
            "sanitizer: the journal keeps a state snapshot plus up "
            "to this many typed events (acquire/release/read/write/"
            "spawn), re-snapshotting on overflow, so a dumped "
            "journal always replays (python -m "
            "paddle_tpu.framework.concurrency --replay <file>) from "
            "a sound state regardless of how long the process ran")
define_flag("telemetry", "off",
            "runtime telemetry (framework/telemetry.py): 'off' "
            "(default) allocates NOTHING — no registry, no tracer, "
            "every instrumented site pays one attribute check (same "
            "zero-cost discipline as FLAGS_page_sanitizer=off, gated "
            "at zero tracemalloc blocks in bench.py --serving); "
            "'metrics' activates the process-wide MetricsRegistry "
            "(counters/gauges/histograms: serving TTFT/TPOT/queue-"
            "wait, pool occupancy/COW, prefix hits, compile events, "
            "collective-matmul dispatch — docs/OBSERVABILITY.md); "
            "'trace' additionally records nested wall-clock spans "
            "(admit/prefill-chunk/decode/retire, jit.compile) into a "
            "bounded ring exportable as Chrome trace JSON. The mode "
            "is read when a scheduler/pool/cache is CONSTRUCTED; "
            "spans alone are decided at call time and are also live "
            "while a jax.profiler session or a Profiler RECORD window "
            "collects")
define_flag("telemetry_ring", 262144,
            "span ring-buffer capacity for the telemetry tracer: the "
            "newest this-many finished spans are retained (rollover "
            "drops the oldest and counts it in Tracer.dropped; "
            "exports stay valid Chrome JSON regardless of how long "
            "the process ran). Sized to hold a 40 s profiler session "
            "of the serving path whole: ~250 spans a step at ~1 step/s "
            "today, ~45 a step at 100 steps/s once a step is one "
            "program; the ring allocates per span, ~0.4 KB each")
define_flag("telemetry_samples", 4096,
            "per-histogram raw-sample reservoir for the telemetry "
            "registry: percentile readout (p50/p90/p99) is EXACT "
            "while a histogram has seen at most this many values, "
            "and exact over the newest this-many after that (the "
            "log2 bucket counts always cover everything)")
define_flag("telemetry_request_traces", 256,
            "bounded LRU of COMPLETED per-request traces kept by the "
            "request-trace book (framework/telemetry.py "
            "RequestTraceBook, live in trace mode): each retired "
            "request's submit -> admit -> prefill-chunk -> token -> "
            "retire timeline is retained until this many completed "
            "traces exist, then the oldest is dropped — memory stays "
            "fixed under load. Active (in-flight) traces are never "
            "dropped")
define_flag("telemetry_window", 128,
            "sliding-window size in SCHEDULER STEP EPOCHS (not wall "
            "clock, so windowed views stay deterministic under a fake "
            "clock) for the request-lifecycle observability layer: "
            "windowed percentile views over the latency histograms, "
            "the SLO/goodput attainment window over retired requests, "
            "and the rate window every watchdog detector computes "
            "deltas over (framework/watchdog.py)")
define_flag("telemetry_slo", "",
            "declarative serving SLO spec consumed by BatchScheduler "
            "when FLAGS_telemetry is on: comma-separated "
            "'ttft_p99_s=<s>,tpot_p99_s=<s>,queue_wait_p99_s=<s>' "
            "(any subset; empty disables SLO accounting). A retired "
            "request 'meets' the SLO set when its TTFT, its p99 "
            "inter-token gap, and its queue wait are each within the "
            "configured bounds; serving.goodput is the fraction of "
            "requests retired inside the FLAGS_telemetry_window that "
            "met ALL configured SLOs (per-SLO attainment gauges ride "
            "alongside) — the admission-control signal of ROADMAP "
            "item 1 (docs/OBSERVABILITY.md)")
define_flag("telemetry_watchdog", "off",
            "anomaly watchdogs over the telemetry registry "
            "(framework/watchdog.py): 'off' (default) builds nothing; "
            "'warn' runs the registry-READ-ONLY detector pass every "
            "FLAGS_telemetry_watchdog_stride scheduler steps — "
            "recompile storm, page-pool high-watermark / alloc-free "
            "churn, prefix-cache hit-rate collapse, decode stall, "
            "sanitizer-violation spike, preemption thrash, and plan "
            "drift (the performance ledger's predicted-vs-measured "
            "wall ratio, FLAGS_telemetry_drift_ratio) — appending "
            "structured events "
            "to a bounded log and raising RuntimeWarning; 'strict' "
            "raises WatchdogError at the detecting step instead. "
            "Requires FLAGS_telemetry=metrics|trace (detectors only "
            "read registry state)")
define_flag("telemetry_watchdog_stride", 32,
            "scheduler-step stride of the watchdog detector pass AND "
            "of the periodic FLAGS_telemetry_export_path snapshot "
            "write: every this many BatchScheduler.step() calls the "
            "pool/prefix/sanitizer gauges are refreshed, every "
            "watchdog detector runs, and (when an export path is "
            "set) the Prometheus snapshot is rewritten")
define_flag("telemetry_export_path", "",
            "when non-empty and FLAGS_telemetry is on, the scheduler "
            "rewrites this file with a Prometheus text-format "
            "snapshot of the metrics registry every "
            "FLAGS_telemetry_watchdog_stride steps (atomic tmp+rename "
            "write, so a scraper or the multi-host router never reads "
            "a torn file; the renderer is jax-free — "
            "telemetry.prometheus_text / --export-prom)")
define_flag("telemetry_peak_flops", 1.97e14,
            "device peak flops/s the per-program performance ledger "
            "(framework/perf_ledger.py) judges live MFU against, and "
            "the compute leg of its roofline-predicted per-invocation "
            "wall (the plan-drift denominator). Default is the v5e "
            "bf16 peak (197 TFLOP/s); set it to the deployed chip's "
            "peak, or 0 to drop the MFU column and the compute bound")
define_flag("telemetry_peak_hbm_gbs", 819.0,
            "device HBM bandwidth in GB/s for the performance "
            "ledger's roofline math: the memory leg of the predicted "
            "per-invocation wall and the attained-arithmetic-"
            "intensity column. Default is v5e (819 GB/s); 0 drops "
            "the memory bound")
define_flag("telemetry_drift_ratio", 4.0,
            "plan-drift threshold for the performance ledger and the "
            "plan-drift watchdog class (framework/watchdog.py): a "
            "program whose roofline-predicted lower-bound wall "
            "(planned flops / FLAGS_telemetry_peak_flops vs planned "
            "HBM bytes / FLAGS_telemetry_peak_hbm_gbs) exceeds its "
            "SUSTAINED measured wall (windowed mean over "
            "FLAGS_telemetry_window epochs) by at least this ratio "
            "is running faster than the plan says is possible — the "
            "cost model is off (falsified/stale plan) and the "
            "watchdog fires plan-drift. 0 disables the check")
define_flag("telemetry_incident_dir", "",
            "when non-empty and FLAGS_telemetry is on, the serving "
            "scheduler attaches a telemetry.FlightRecorder and every "
            "watchdog fire (plus explicit dump_incident() calls) "
            "writes one atomic, bounded incident bundle directory "
            "here — chrome trace with request lanes, registry "
            "snapshot, Prometheus text, sanitizer journal tail, "
            "resource-plan summaries, ledger top-N, flags snapshot, "
            "and the watchdog event log — replayable via python -m "
            "paddle_tpu.framework.telemetry --summarize-incident "
            "<bundle>. Empty (default) builds no recorder")
define_flag("ops_server_port", 0,
            "embedded live-ops debug HTTP server "
            "(framework/ops_server.py): 0 (default) builds nothing — "
            "the serving scheduler pays one integer check at "
            "construction; a positive port starts ONE process-wide, "
            "read-only, stdlib-only server on 127.0.0.1:<port> "
            "serving /metrics (byte-identical to "
            "telemetry.prometheus_text), /statusz (build/flags/"
            "uptime + SLO-window and watchdog state), /tracez "
            "(recent spans + chrome/perfetto payload), /planz "
            "(resource plans + perf-ledger plan-vs-actual), /flagz, "
            "and /incidentz (flight-recorder bundle index + "
            "summarize view). Requires FLAGS_telemetry=metrics|trace "
            "— with telemetry off the server refuses to start "
            "(docs/OBSERVABILITY.md)")
define_flag("telemetry_incident_keep", 8,
            "bound on retained incident bundles per "
            "FLAGS_telemetry_incident_dir: when a new bundle would "
            "exceed this many, the oldest bundles are pruned first "
            "(incident storage stays fixed no matter how long the "
            "process watchdogs)")
define_flag("moe_dense_dispatch", False,
            "route MoE tokens via the dense (N,E,C) one-hot "
            "dispatch/combine einsums instead of the sparse index "
            "scatter/gather path (oracle/debug; same semantics)")
define_flag("serving_max_queue", 0,
            "bound on the BatchScheduler submit queue (inference/"
            "serving.py): submit() past this many waiting requests "
            "raises QueueFullError instead of growing the backlog "
            "without limit — the backpressure half of admission "
            "control (docs/SERVING.md 'Overload behavior'). 0 "
            "(default) keeps the queue unbounded")
define_flag("serving_swap_bytes", 256 << 20,
            "host-memory budget for the tiered KV swap space "
            "(incubate/nn/paged_cache.py HostKVSwapSpace): preempted "
            "sequences page their PRIVATE KV pages (payload + int8 "
            "scale sidecars) out to host buffers under this byte cap "
            "and restore them bitwise on re-admission; shared "
            "(prefix) pages stay on-device under an external "
            "reference. 0 disables the swap tier (preemption then "
            "declines and admission blocks, the pre-ISSUE-9 "
            "behavior)")
define_flag("serving_preempt", True,
            "sequence preemption for the serving scheduler "
            "(inference/serving.py): when admission cannot reserve "
            "pages for a request, victims with STRICTLY lower "
            "priority (lowest priority first, then most pages held, "
            "then least progress) are swapped out to the host tier "
            "(FLAGS_serving_swap_bytes) instead of the request being "
            "blocked behind them — capacity pressure means slower, "
            "never failed. Off restores wait-in-queue admission "
            "exactly")
define_flag("serving_faults", "",
            "deterministic fault-injection plan for the serving "
            "scheduler (incubate/nn/fault_injection.py): comma-"
            "separated 'kind@step', 'kind@step+duration', or "
            "'kind@step:param' entries over kinds exhaust / "
            "preempt_storm / delay_swap_in / fail_step, e.g. "
            "'exhaust@10+5,preempt_storm@20:2,fail_step@30+3'. "
            "Faults perturb the scheduler at step boundaries only; "
            "empty (default) constructs no injector and costs one "
            "is-None check per step")
define_flag("serving_fault_seed", 0,
            "seed for FaultInjector.random() plans (the fault-"
            "injection harness's randomized mode: same seed + same "
            "step count -> the identical fault schedule, so every "
            "injected-fault run is replayable)")
define_flag("engine_goodput_low", 0.75,
            "trip threshold for the ServingEngine admission gate "
            "(inference/engine.py): when the live serving.goodput "
            "windowed gauge falls below this fraction (and the SLO "
            "window holds at least FLAGS_engine_min_window "
            "requests), the gate counts a bad signal toward "
            "escalating backpressure (open -> shed -> clamp). Must "
            "be < FLAGS_engine_goodput_high — the gap is the "
            "hysteresis band in which the gate holds state")
define_flag("engine_goodput_high", 0.9,
            "recovery threshold for the ServingEngine admission "
            "gate: goodput at or above this fraction (with no fresh "
            "watchdog events) counts a good signal toward de-"
            "escalating backpressure one level. Goodput between "
            "FLAGS_engine_goodput_low and this value is the "
            "hysteresis band: both trip and recovery streaks freeze "
            "so the gate doesn't flap at a single threshold")
define_flag("engine_min_window", 4,
            "minimum serving.slo_window_requests before the "
            "ServingEngine admission gate trusts the goodput gauge: "
            "with fewer retired requests in the SLO window the "
            "goodput signal is noise (one slow request swings it to "
            "0.0) and the gate ignores it. Watchdog-event signals "
            "are not window-gated")
define_flag("engine_trip_steps", 2,
            "consecutive bad gate evaluations (goodput below "
            "FLAGS_engine_goodput_low, or fresh watchdog events in "
            "the six overload classes) required before the "
            "ServingEngine escalates backpressure one level — the "
            "trip half of the gate's hysteresis")
define_flag("engine_recover_steps", 4,
            "consecutive good gate evaluations (goodput at or above "
            "FLAGS_engine_goodput_high or no SLO signal, and no "
            "fresh watchdog events) required before the "
            "ServingEngine de-escalates backpressure one level — "
            "deliberately larger than FLAGS_engine_trip_steps so "
            "recovery is slower than tripping")
define_flag("engine_gate_stride", 2,
            "the ServingEngine re-evaluates its admission gate "
            "every this-many pump steps: the SLO gauges it reads "
            "are themselves windowed per scheduler step, so "
            "per-step evaluation buys nothing and doubles the "
            "gauge-read overhead on the pump thread")
define_flag("engine_shed_keep_priority", 1,
            "priority floor while the ServingEngine gate is in the "
            "shed state: submissions with request.priority below "
            "this value are rejected with EngineOverloadError "
            "(lowest-priority admissions shed first); at or above "
            "it they are still admitted. The clamp state rejects "
            "all new admissions regardless of priority")
define_flag("engine_idle_wait_s", 0.002,
            "how long the ServingEngine pump thread parks on its "
            "wake event when the scheduler has no queued, active, "
            "or swapped work: long enough to avoid a busy spin, "
            "short enough that a submit landing between the inbox "
            "drain and the wait (which also sets the event) is "
            "picked up immediately")
define_flag("disagg_router_policy", "rr",
            "replica-selection policy for the disaggregated "
            "SessionRouter (inference/disagg.py): 'rr' round-robins "
            "new sessions over the DP replicas; 'least' picks the "
            "replica with the fewest live sessions (better under "
            "skewed session lifetimes, one extra scan per submit)")
define_flag("disagg_mp_shards", 1,
            "KV-head shard count for the disaggregated page-chain "
            "transfer (incubate/nn/paged_cache.py export_seq): a "
            "handed-off chain is split into this many wire payloads "
            "along the KV-head axis — one per mp-mesh shard on the "
            "decode side — so each decode shard imports only the "
            "heads it owns; must divide the pool's KV head count")
define_flag("disagg_prefill_chunk_tokens", 0,
            "chunked-prefill token budget override for PREFILL-role "
            "schedulers in the disaggregated split (inference/"
            "disagg.py): prefill workers run chunk-budget-heavy "
            "steps, so this (when > 0) replaces the single-box "
            "FLAGS_prefill_chunk_tokens on the prefill side only; "
            "0 keeps the single-box value")
define_flag("disagg_prefill_budget_hbm", 0,
            "per-role override of FLAGS_jit_budget_hbm applied by "
            "disagg.apply_role_budgets('prefill'): prefill workers "
            "hold full prompt activations so their peak-live-HBM "
            "budget differs from decode's; 0 leaves the global "
            "budget untouched (strict mode still raises "
            "JitPlanError on breach)")
define_flag("disagg_prefill_budget_comm", 0,
            "per-role override of FLAGS_jit_budget_comm applied by "
            "disagg.apply_role_budgets('prefill'): the prefill "
            "role's per-device collective-traffic budget in bytes; "
            "0 leaves the global budget untouched")
define_flag("disagg_decode_budget_hbm", 0,
            "per-role override of FLAGS_jit_budget_hbm applied by "
            "disagg.apply_role_budgets('decode'): decode workers "
            "are KV-pool-dominated, so their peak-live-HBM budget "
            "differs from prefill's; 0 leaves the global budget "
            "untouched (strict mode still raises JitPlanError on "
            "breach)")
define_flag("disagg_decode_budget_comm", 0,
            "per-role override of FLAGS_jit_budget_comm applied by "
            "disagg.apply_role_budgets('decode'): the decode role's "
            "per-device collective-traffic budget in bytes; 0 "
            "leaves the global budget untouched")
define_flag("autotune", "off",
            "capacity-autotuner mode (framework/autotuner.py): "
            "'off' (hand-picked knobs, the default), 'static' "
            "(planner-scored search only — the best statically "
            "feasible candidate is chosen, nothing is measured "
            "live), 'live' (deploy the static frontier and "
            "hill-climb on the live goodput window with hysteresis "
            "and watchdog quarantine)")
define_flag("autotune_space", "",
            "capacity-autotuner search-space override, a "
            "';'-separated list of knob=alt|alt clauses — e.g. "
            "'chunk=16|32|64;buckets=8,16,32|8,16,32,64,128;"
            "swap=0|268435456;dtype=off|int8;band=0.75:0.9' — "
            "knobs omitted from the spec keep their built-in "
            "alternatives (autotuner.DEFAULT_SPACE); empty uses "
            "the built-in space for every knob")
define_flag("autotune_eval_windows", 3,
            "live goodput windows the capacity autotuner averages "
            "per candidate before scoring it (one window = one "
            "Autotuner.observe() with signal): the hysteresis "
            "half-width — a single noisy window can never adopt or "
            "reject a candidate because the decision waits for the "
            "median of this many")
define_flag("autotune_min_improve", 0.05,
            "relative live-score improvement a challenger "
            "candidate must sustain over the incumbent before the "
            "capacity autotuner adopts it (0.05 = 5% better on the "
            "goodput-window score); challengers inside the dead "
            "band are reverted, so config churn needs a real win")
define_flag("autotune_artifact", "",
            "path the capacity autotuner writes its reproducible "
            "tuned-config JSON artifact to "
            "(TUNED_CONFIG_LAST.json-style: chosen config, the "
            "scored candidate table, quarantine list, and the "
            "flags dict to re-apply it); empty disables the write")
if os.environ.get("FLAGS_flash_pallas_interpret"):
    # pre-rename env alias (was flash-only before covering all kernels)
    _REGISTRY["pallas_interpret"] = True
