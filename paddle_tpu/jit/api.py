"""@to_static — compile an imperative (dygraph) step into one XLA program.

Upstream analog: python/paddle/jit/dy2static/ (ProgramTranslator +
PartialProgramLayer). The reference rewrites Python AST into a static
Program executed by InterpreterCore; on TPU the right mechanism is
trace-and-jit:

* snapshot all mutable framework state (params, buffers, optimizer
  accumulators, RNG) via the state registry;
* run the user's imperative function once under ``jax.jit`` tracing with
  state bound to tracers — the eager Tensor/tape machinery is
  trace-transparent, so ``loss.backward()``/``opt.step()`` trace into
  pure XLA ops (XLA then CSEs the vjp re-traces and fuses the whole
  step, playing the role of CINN);
* the compiled step is (state, args) → (outs, new_state) with state
  buffers donated → in-place param updates in HBM;
* cached by input spec (shape/dtype/tree) like the reference's program
  cache keyed on InputSpec.

Restrictions (same class as the reference's dy2static): no
data-dependent Python control flow on traced values, no .numpy()/.item()
inside the traced function.
"""
from __future__ import annotations

import functools
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import state as _registry
from ..framework import telemetry as _telemetry
from ..framework.core import EagerParamBase, Tensor
from ..framework.flags import flag


_CACHE_WIRED = False

# every constructed StaticFunction, for process-wide lint reporting
# (framework/analysis.py live_lint_summaries + the analysis CLI)
import weakref

_LIVE_STATICS: "weakref.WeakSet[StaticFunction]" = weakref.WeakSet()


def live_static_functions():
    return list(_LIVE_STATICS)


# XLA's build phases as ranges in the span ring, under whatever span is
# open on the thread that built: xla.trace, xla.lower, xla.build (the
# persistent cache's key hash, then a load or a compile and a cache
# write) and, inside a build that the cache served, xla.cache_load.
# Each xla.build says how the cache served it (cache = hit / miss /
# off, from the note the cache's events leave on the building thread)
# and carries the key of the innermost open span that declares one
# (telemetry.Tracer.add_complete). ONE pair of listeners for the
# process, registered at import: jax.monitoring has no cheap way to
# take a listener off again, so they are no-ops while no span is live.
_XLA_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "xla.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "xla.lower",
    "/jax/compilation_cache/cache_retrieval_time_sec": "xla.cache_load",
    "/jax/core/compile/backend_compile_duration": "xla.build",
}
# the cache's events inside a build: a request that uses the cache is a
# miss until a hit says otherwise (JAX's own cache_misses event fires
# only where the compiled program is written, not for every miss)
_CACHE_NOTES = {
    "/jax/compilation_cache/compile_requests_use_cache": "miss",
    "/jax/compilation_cache/cache_hits": "hit",
}
_BUILD_NOTE = threading.local()


def _xla_cache_listener(event, **kw):
    note = _CACHE_NOTES.get(event)
    if note is not None:
        _BUILD_NOTE.cache = note


def _xla_phase_listener(event, secs, **kw):
    name = _XLA_PHASES.get(event)
    if name is None:
        return
    fun = kw.get("fun_name")
    attrs = {} if fun is None else {"fun": str(fun)}
    if name == "xla.build":
        # taken whether or not a span is live: a note must not outlive
        # its build
        attrs["cache"] = getattr(_BUILD_NOTE, "cache", "off")
        _BUILD_NOTE.cache = "off"
    if _telemetry.tracing_on():
        _telemetry.add_complete(
            name, _telemetry.clock() - secs, secs, cat="compile",
            **attrs)


jax.monitoring.register_event_listener(_xla_cache_listener)
jax.monitoring.register_event_duration_secs_listener(
    _xla_phase_listener)


# the one default cache directory: fixed inside the checkout (the path
# is part of the cache key — a directory that moves never hits)
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def ensure_compilation_cache():
    """Enable JAX's persistent compilation cache (idempotent; wired at
    the first to_static compile, jit.load/Predictor, and the first
    PagedKVCacheManager — every BatchScheduler serves from those pools,
    so the serving path's jax.jit programs land in it too). Plays the role of the reference's
    serialized optimized programs (analysis_predictor warm start).
    Where JAX_COMPILATION_CACHE_DIR is set JAX reads it and no
    directory is set here; otherwise FLAGS_compilation_cache_dir
    ('' -> <checkout>/.jax_cache, 'off' -> not wired)."""
    global _CACHE_WIRED
    if _CACHE_WIRED:
        return
    _CACHE_WIRED = True
    conf = flag("compilation_cache_dir")
    if conf == "off":
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = conf or _DEFAULT_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # default threshold is 1s of compile time: big programs (the ones
    # worth persisting) qualify, trivia stays out of the dir
    if os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS") is None:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _tree_flatten(obj):
    return jax.tree_util.tree_flatten(
        obj, is_leaf=lambda x: isinstance(x, Tensor)
    )


def _is_arr(x):
    return isinstance(x, (jax.Array, np.ndarray)) or hasattr(x, "aval")


class StaticFunction:
    def __init__(self, fn, input_spec=None, build_strategy=None,
                 backend=None, full_graph=True, property=False,
                 donate_state=True, lint_suppress=()):
        functools.update_wrapper(self, fn)
        from .dy2static import convert_control_flow

        self._fn = convert_control_flow(fn)
        self._input_spec = input_spec
        self._cache = {}
        self._donate = donate_state
        self._lint_suppress = tuple(lint_suppress)
        _LIVE_STATICS.add(self)

    # flags that change what gets traced (kernel selection, nan checks).
    # Others (allocator_strategy, log_level, ...) are runtime-only: keying
    # on them would force a full retrace/recompile for a no-op change.
    _TRACE_FLAGS = (
        "check_nan_inf", "use_pallas_flash_bwd", "use_pallas_kernels",
        "flash_precision_highest", "pallas_interpret",
        "moe_dense_dispatch",
    )

    def _mode_sig(self):
        # trace-relevant flags are part of the cache key so set_flags()
        # takes effect on the NEXT call via retrace instead of being
        # silently ignored by the cache
        from ..framework.flags import _REGISTRY as _flags

        return (
            tuple(
                sorted((id(l), l.training)
                       for l in _registry.live_layers())
            ),
            tuple((k, _flags[k]) for k in self._TRACE_FLAGS),
        )

    def _prepare(self, args, kwargs):
        """Flatten args, snapshot state, and resolve (or build) the
        cache entry for this (args, state) signature — everything
        __call__ does short of finalizing/executing. Shared with the
        no-execute analysis path (paddle.jit.analyze)."""
        arg_leaves, arg_tree = _tree_flatten((args, kwargs))
        leaf_is_tensor = [isinstance(l, Tensor) for l in arg_leaves]
        tensor_raws = [
            l._data for l in arg_leaves if isinstance(l, Tensor)
        ]
        static_leaves = [
            None if is_t else l
            for l, is_t in zip(arg_leaves, leaf_is_tensor)
        ]
        arg_sg = [
            l.stop_gradient if isinstance(l, Tensor) else None
            for l in arg_leaves
        ]

        def make_key(state):
            return (
                arg_tree,
                tuple(
                    ("arr", tuple(r.shape), str(r.dtype))
                    for r in tensor_raws
                ),
                tuple(repr(s) for s in static_leaves),
                tuple(t._uid for t in state),
                self._mode_sig(),
            )

        state = _registry.snapshot_state_tensors()
        key = make_key(state)
        entry = self._cache.get(key)
        if entry is None:
            # a miss can be spurious: layers/optimizers in cyclic garbage
            # still sit in the weak registries until the GC runs, so the
            # snapshot (and key) depends on collection timing. Collect,
            # re-snapshot, re-check — only a genuinely new (args, state)
            # signature pays a retrace.
            import gc

            gc.collect()
            state = _registry.snapshot_state_tensors()
            key = make_key(state)
            entry = self._cache.get(key)
        if entry is None:
            entry = self._make_entry(
                state, arg_tree, leaf_is_tensor, static_leaves, arg_sg
            )
            self._cache[key] = entry
        return entry, state, tensor_raws

    def _finalized_entries(self):
        return [e for e in self._cache.values() if "jitted" in e]

    def trace_for_analysis(self, *args, **kwargs):
        """Build + finalize (trace, prune — no compile, no execution)
        the cache entry for example args; returns the entry. The
        automatic lint hook is skipped: the caller (paddle.jit.analyze)
        runs its own analysis with its own suppressions and must get a
        report back regardless of FLAGS_jit_lint."""
        entry, state, tensor_raws = self._prepare(args, kwargs)
        if "jitted" not in entry:
            self._finalize_entry(entry, state, tensor_raws, lint=False)
        return entry

    def __call__(self, *args, **kwargs):
        entry, state, tensor_raws = self._prepare(args, kwargs)
        if "jitted" not in entry:
            self._finalize_entry(entry, state, tensor_raws)
        elif flag("jit_lint") == "strict":
            # entry may have been finalized under warn/off (or via
            # trace_for_analysis) before the flag flipped — strict must
            # keep failing on every call, linting now if it never ran
            from ..framework import analysis

            rep = entry.get("lint_report")
            if rep is None:
                try:
                    rep = analysis.lint_static_entry(self, entry)
                    entry["lint_report"] = rep
                except Exception:
                    rep = None
            if rep is not None and rep.blocking():
                raise analysis.JitLintError(rep)
        # per-invocation execution stamp (framework/perf_ledger.py):
        # the handle tuple is attached at finalize ONLY when the
        # registry was live, so the off path pays one dict get + one
        # `is None` check and allocates nothing
        _exec = entry.get("_exec")
        _t_exec = _telemetry.clock() if _exec is not None else 0.0
        rw_raws = [state[i]._data for i in entry["rw_idx"]]
        ro_raws = [state[i]._data for i in entry["ro_idx"]]
        if entry.get("donates"):
            # a buffer aliased into a donated rw slot AND any other
            # reference — an ro/tensor input, another rw slot, or a
            # snapshot state tensor PRUNED from the jaxpr — would be
            # deleted by donation while still referenced. Count every
            # live holder; donate a copy when a buffer has >1.
            # (Aliasing across slots is rare; normal steps only pay
            # the id() sweep.)
            counts = {}
            for t in state:
                k = id(t._data)
                counts[k] = counts.get(k, 0) + 1
            for r in tensor_raws:
                counts[id(r)] = counts.get(id(r), 0) + 1
            rw_raws = [
                jnp.array(r, copy=True) if counts.get(id(r), 0) > 1
                else r
                for r in rw_raws
            ]
        # the entry call: dispatch of the compiled step (and, the
        # first time, its trace/lower/build: xla.* ranges nest here)
        with _telemetry.span("jit.call"):
            out_arrs, changed_state, grad_raws = entry["jitted"](
                rw_raws, ro_raws, tensor_raws
            )
        if _exec is not None:
            # host-observed dispatch wall of the compiled program —
            # the measured half of the performance ledger's
            # plan-vs-actual join (exec.wall_s.<program> histogram +
            # exec.count.<program> counter)
            _reg, _wall_key, _count_key = _exec
            # the keys are the compile-time literals "exec.wall_s."
            # / "exec.count." + program (armed in _finalize_entry),
            # pre-resolved so the hot dispatch path pays no string
            # concat per call:
            # metric-name: ok (pre-resolved exec.* keys)
            _reg.observe(_wall_key, _telemetry.clock() - _t_exec)
            _reg.inc(_count_key)  # metric-name: ok (same keys)
        aux = entry["aux"]

        for i, r in zip(entry["changed_idx"], changed_state):
            state[i]._data = r
        for i, g in zip(aux["grad_idx"], grad_raws):
            t = state[i]
            if t._grad is None:
                t._grad = Tensor(g, stop_gradient=True)
                t._grad.name = t.name + "@GRAD"
            else:
                t._grad._data = g

        # reassemble outputs: array slots get fresh Tensors, static slots
        # their recorded values
        out_leaves = []
        ai = 0
        for kind, val in aux["out_slots"]:
            if kind == "arr":
                out_leaves.append(Tensor(out_arrs[ai]))
                ai += 1
            else:
                out_leaves.append(val)
        return jax.tree_util.tree_unflatten(aux["out_tree"], out_leaves)

    def _make_entry(self, state, arg_tree, leaf_is_tensor, static_leaves,
                    arg_sg):
        fn = self._fn
        aux = {"out_tree": None, "out_slots": None, "grad_idx": []}
        n_state_before = len(state)

        def pure(state_raws, tensor_raws):
            saved = [(t, t._data, t._grad) for t in state]
            for t, r in zip(state, state_raws):
                t._data = r
                t._grad = None
            try:
                it = iter(tensor_raws)
                full_leaves = []
                for is_t, sl, sg in zip(
                    leaf_is_tensor, static_leaves, arg_sg
                ):
                    if is_t:
                        nt = Tensor(next(it))
                        nt.stop_gradient = sg
                        full_leaves.append(nt)
                    else:
                        full_leaves.append(sl)
                args, kwargs = jax.tree_util.tree_unflatten(
                    arg_tree, full_leaves
                )
                outs = fn(*args, **kwargs)

                out_leaves, out_tree = _tree_flatten(outs)
                out_slots, out_arrs = [], []
                for l in out_leaves:
                    if isinstance(l, Tensor):
                        out_slots.append(("arr", None))
                        out_arrs.append(l._data)
                    elif _is_arr(l):
                        out_slots.append(("arr", None))
                        out_arrs.append(l)
                    else:
                        out_slots.append(("static", l))
                grad_idx = [
                    i for i, t in enumerate(state)
                    if isinstance(t, EagerParamBase) and t._grad is not None
                ]
                grad_raws = [state[i]._grad._data for i in grad_idx]
                aux["out_tree"] = out_tree
                aux["out_slots"] = out_slots
                aux["grad_idx"] = grad_idx

                post = _registry.snapshot_state_tensors()
                if len(post) != n_state_before:
                    raise RuntimeError(
                        "to_static: new persistent state was created inside "
                        "the traced function (e.g. a lazily-built layer or "
                        "optimizer accumulator). Build all layers/optimizers "
                        "before the first compiled call."
                    )
                new_state = [t._data for t in state]
                return out_arrs, new_state, grad_raws
            finally:
                for t, d, g in saved:
                    t._data = d
                    t._grad = g

        return {
            "pure": pure, "aux": aux, "n_state": len(state),
            # python-scalar args for the linter's recompilation checks
            # (values only — no object refs pinned)
            "static_meta": [
                (i, type(l).__name__,
                 l if isinstance(l, (int, float, bool)) else None)
                for i, l in enumerate(static_leaves)
                if l is not None and not isinstance(l, str)
            ],
        }

    def _finalize_entry(self, entry, state, tensor_raws, lint=True):
        """Trace ``pure`` once (no compile), then DEAD-STRIP the state:
        the registry snapshot is global, so an unrelated live model's
        params would otherwise ride through every compiled step — extra
        transfers, and (worse) the step's output commits them to
        whatever mesh is active, which changes their sharding and
        forces a full jax retrace on the next call (the r3→r4
        order-dependent cache flake). The pruned jaxpr keeps only
        state inputs the program reads and state outputs that differ
        from their input (real writes); everything else never enters
        the compiled program."""
        import jax.extend.core as jex

        ensure_compilation_cache()
        # telemetry compile event (framework/telemetry.py): one
        # counter bump + wall-time histogram sample + trace span per
        # to_static trace, attributed to the program and its variant
        # count — a recompile storm shows up as a run of jit.compile
        # spans with a climbing variant number. Off costs nothing.
        _reg = _telemetry.registry()
        _tr = _telemetry.tracer()
        _t0 = _telemetry.clock() \
            if (_reg is not None or _tr is not None) else None
        pure, aux = entry["pure"], entry["aux"]
        n_s = entry["n_state"]
        s_structs = [jax.ShapeDtypeStruct(t._data.shape, t._data.dtype)
                     for t in state]
        t_structs = [jax.ShapeDtypeStruct(r.shape, r.dtype)
                     for r in tensor_raws]
        closed = jax.make_jaxpr(pure)(s_structs, t_structs)
        j = closed.jaxpr

        n_out = sum(1 for k, _ in aux["out_slots"] if k == "arr")
        out_arr_vars = list(j.outvars[:n_out])
        state_out = list(j.outvars[n_out:n_out + n_s])
        grad_vars = list(j.outvars[n_out + n_s:])
        state_in = list(j.invars[:n_s])

        changed_idx = [i for i in range(n_s)
                       if state_out[i] is not state_in[i]]
        kept_out = out_arr_vars + [state_out[i] for i in changed_idx] \
            + grad_vars
        used = set()
        for eqn in j.eqns:
            for v in eqn.invars:
                used.add(id(v))
        for v in kept_out:
            used.add(id(v))
        kept_state_idx = [i for i in range(n_s)
                          if id(state_in[i]) in used]
        # Donation splits the kept state: only WRITTEN state (changed
        # outputs exist to alias into) may be donated — donating a
        # read-only input would let XLA alias its buffer into some
        # output and delete the array while state[i]._data still
        # points at it (second call would read a deleted buffer).
        changed_set = set(changed_idx)
        rw_idx = [i for i in kept_state_idx if i in changed_set]
        ro_idx = [i for i in kept_state_idx if i not in changed_set]
        kept_order = {i: pos for pos, i in enumerate(kept_state_idx)}
        kept_in = [state_in[i] for i in kept_state_idx] \
            + list(j.invars[n_s:])
        # debug_info names the ORIGINAL invars/outvars; after the
        # dead-strip their counts differ and Jaxpr.__init__ asserts.
        # It is cosmetic (pretty-printing) — drop it for the pruned
        # program rather than fabricating per-slot names.
        pruned = jex.ClosedJaxpr(
            jex.Jaxpr(j.constvars, kept_in, kept_out, j.eqns, j.effects,
                      debug_info=None),
            closed.consts)
        fn = jex.jaxpr_as_fun(pruned)
        n_changed = len(changed_idx)
        rw_pos = [kept_order[i] for i in rw_idx]
        ro_pos = [kept_order[i] for i in ro_idx]
        n_kept = len(kept_state_idx)

        def runner(rw_state, ro_state, t_raws):
            flat_state = [None] * n_kept
            for p, v in zip(rw_pos, rw_state):
                flat_state[p] = v
            for p, v in zip(ro_pos, ro_state):
                flat_state[p] = v
            flat = fn(*flat_state, *t_raws)
            return (tuple(flat[:n_out]),
                    tuple(flat[n_out:n_out + n_changed]),
                    tuple(flat[n_out + n_changed:]))

        donate = (0,) if (
            self._donate and jax.default_backend() != "cpu"
        ) else ()
        entry["jitted"] = jax.jit(runner, donate_argnums=donate)
        entry["donates"] = bool(donate)
        entry["pruned_jaxpr"] = pruned
        entry["rw_idx"] = rw_idx
        entry["ro_idx"] = ro_idx
        entry["kept_state_idx"] = kept_state_idx
        entry["changed_idx"] = changed_idx
        # pure's closure strongly references every snapshot tensor
        # (zombies included) — drop it now that the jaxpr is the program
        del entry["pure"]

        # context the trace-time linter (framework/analysis.py) needs
        # beyond the jaxpr itself: buffer names/sizes for the donation
        # rule, input shapes for the shape-leak heuristic. Metadata
        # only — the compiled program above is untouched.
        entry["state_meta"] = {
            i: (state[i].name,
                int(np.prod(state[i]._data.shape))
                * state[i]._data.dtype.itemsize)
            for i in kept_state_idx
        }
        entry["t_shapes"] = [tuple(r.shape) for r in tensor_raws]
        entry["donate_intent"] = self._donate

        mode = flag("jit_lint")
        report = None
        if lint and mode != "off":
            from ..framework import analysis

            try:
                report = analysis.lint_static_entry(self, entry)
                entry["lint_report"] = report
            except Exception as e:  # the linter must never break a
                # compile — strict failures are raised below, not here
                from ..framework.log import VLOG

                VLOG(1, "jit_lint: analysis failed: %r", e,
                     module="jit.api")
            if report is not None:
                analysis.emit_report(report, mode)

        # static resource planner (framework/planner.py): per-program
        # HBM footprint + collective-byte plan behind FLAGS_jit_plan.
        # 'off' never imports the module (this flag read is the whole
        # cost); computation failures never break a compile — under
        # strict, any blocking finding (budget overruns AND the
        # warning-severity dead-collective / comm-bound-program rules)
        # raises via emit_plan_report below.
        pmode = flag("jit_plan")
        plan = plan_report = None
        if lint and pmode != "off":
            from ..framework import planner

            try:
                plan, plan_report = planner.plan_static_entry(
                    self, entry)
                entry["resource_plan"] = plan
                entry["plan_report"] = plan_report
            except Exception as e:
                from ..framework.log import VLOG

                VLOG(1, "jit_plan: planning failed: %r", e,
                     module="jit.api")
            if plan_report is not None:
                planner.emit_plan_report(plan_report, pmode)

        if _t0 is not None:
            dur = _telemetry.clock() - _t0
            prog = getattr(self, "__name__", "<static>")
            variants = len(self._finalized_entries())
            lint_counts = report.counts() if report is not None else {}
            if _reg is not None:
                # arm the per-invocation execution stamp for this
                # entry (performance ledger, framework/perf_ledger.py)
                # and hand the ledger the program's resource plan so
                # live walls join the static cost model. Like the
                # telemetry mode itself, read at COMPILE time.
                entry["_exec"] = (_reg,
                                  "exec.wall_s." + str(prog),
                                  "exec.count." + str(prog))
                if plan is not None:
                    from ..framework import perf_ledger as _ledger

                    _ledger.register_plan(str(prog), plan)
                _reg.inc("compile.count")
                # per-program attribution: when the recompile-storm
                # watchdog fires, the by_program counters in its
                # event snapshot name the offender
                _reg.inc("compile.by_program." + str(prog))
                _reg.observe("compile.wall_s", dur)
                if plan is not None:
                    # resource-plan telemetry (framework/planner.py):
                    # planned peak HBM per compile and wire bytes per
                    # mesh axis — the budget dashboards of ROADMAP
                    # items 3-4 read these, not the chip
                    _reg.observe("compile.hbm_peak_bytes",
                                 float(plan.hbm_peak_bytes))
                    for _ax, _nb in plan.comm_bytes_by_axis.items():
                        _reg.inc("compile.comm_bytes." + str(_ax),
                                 int(_nb))
            if _tr is not None:
                _tr.add_complete(
                    "jit.compile", _t0, dur, cat="compile",
                    attrs={"program": prog, "variant": variants,
                           "n_eqns": len(j.eqns),
                           "lint": lint_counts})


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    def decorate(fn):
        if isinstance(fn, StaticFunction):
            return fn
        return StaticFunction(fn, input_spec=input_spec,
                              build_strategy=build_strategy,
                              backend=backend, **kwargs)

    if function is not None:
        return decorate(function)
    return decorate


def analyze(function, *example_args, suppress=(), **example_kwargs):
    """Run the trace-time linter (framework/analysis.py) on a compiled
    function and return an ``AnalysisReport`` — without executing it.

    * ``analyze(static_fn)`` — lint every program variant the
      ``@to_static`` function has already compiled;
    * ``analyze(fn_or_static_fn, *example_args)`` — trace the function
      against the example inputs (array-likes are promoted to Tensors,
      shapes/dtypes are what matter) and lint the resulting program.

    Runs regardless of FLAGS_jit_lint (the flag only governs the
    automatic compile-time hook); ``suppress`` silences rule ids for
    this call."""
    from ..framework import analysis

    sf = function if isinstance(function, StaticFunction) \
        else StaticFunction(function)
    if example_args or example_kwargs:
        def as_tensor(x):
            return Tensor(x) if _is_arr(x) and not isinstance(x, Tensor) \
                else x

        args = tuple(as_tensor(a) for a in example_args)
        kwargs = {k: as_tensor(v) for k, v in example_kwargs.items()}
        entries = [sf.trace_for_analysis(*args, **kwargs)]
    else:
        entries = sf._finalized_entries()
        if not entries:
            raise ValueError(
                "analyze(fn) without example args needs an already-"
                "compiled @to_static function (call it once, or pass "
                "example inputs: analyze(fn, x, y))"
            )
    reports = [analysis.lint_static_entry(sf, e, suppress=suppress)
               for e in entries]
    if len(reports) == 1:
        return reports[0]
    return analysis.AnalysisReport.merge(
        reports, name=reports[0].name + " (%d variants)" % len(reports))


def plan(function, *example_args, **example_kwargs):
    """Run the static resource planner (framework/planner.py) on a
    compiled function and return its ``ResourcePlan`` — without
    executing it.

    * ``plan(static_fn)`` — plan every program variant the
      ``@to_static`` function has already compiled (returns one
      ``ResourcePlan``, or a list when several variants exist);
    * ``plan(fn_or_static_fn, *example_args)`` — trace the function
      against the example inputs (shapes/dtypes are what matter) and
      plan the resulting program.

    Runs regardless of FLAGS_jit_plan (the flag only governs the
    automatic compile-time hook) and never raises on findings — this
    returns the PLAN only; planner findings (and their suppression)
    live on the compile hook and the CLI ``--plan``."""
    from ..framework import planner

    sf = function if isinstance(function, StaticFunction) \
        else StaticFunction(function)
    if example_args or example_kwargs:
        def as_tensor(x):
            return Tensor(x) if _is_arr(x) and not isinstance(x, Tensor) \
                else x

        args = tuple(as_tensor(a) for a in example_args)
        kwargs = {k: as_tensor(v) for k, v in example_kwargs.items()}
        entries = [sf.trace_for_analysis(*args, **kwargs)]
    else:
        entries = sf._finalized_entries()
        if not entries:
            raise ValueError(
                "plan(fn) without example args needs an already-"
                "compiled @to_static function (call it once, or pass "
                "example inputs: plan(fn, x, y))"
            )
    plans = [planner.plan_static_entry(sf, e)[0] for e in entries]
    return plans[0] if len(plans) == 1 else plans


def not_to_static(fn=None):
    return fn


def enable_to_static(flag: bool):
    global _TO_STATIC_ENABLED
    _TO_STATIC_ENABLED = bool(flag)


_TO_STATIC_ENABLED = True


class ignore_module:
    def __init__(self, modules):
        pass
