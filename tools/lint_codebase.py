"""AST self-lint over paddle_tpu/ — the codebase-level companion of the
trace-time jaxpr linter (paddle_tpu/framework/analysis.py).

Checks:

1. traced-path hygiene: modules whose code runs INSIDE jit traces
   (ops/kernels, nn/functional, jit/dy2static.py) must not call
   ``jax.device_get`` / ``np.asarray`` / ``time.time`` — each is a
   host sync that either breaks under tracing or silently forces a
   device->host transfer per step. Waivers:
     * a trailing ``# trace-lint: ok(<reason>)`` comment on the line
       (deliberate eager-only paths);
     * any function whose name ends in ``_reference`` (host-side test
       oracles are not traced).
2. op-table coverage: every public callable in the op namespaces must
   resolve in ops/op_table.py's registry — raw jax/jnp functions
   leaking through a public module surface are flagged, as are ops
   with guessed (undeclared) metadata.
3. host-only hygiene (the prefix-cache subsystem): modules declared
   pure host bookkeeping (inference/prefix_cache.py) must not touch
   jax/jnp at all — device compute or a host<->device sync inside the
   scheduler's admission path stalls every step. The public
   ``paddle_tpu.inference`` surface is also checked for raw jax
   callables leaking through.
4. quantized-page sidecar ownership: the int8 KV pool's per-page
   scale sidecars (``k_scales``/``v_scales`` on PagedKVCacheManager)
   are pool-private calibration state — a serving-layer write that
   bypasses the pool's requantize-on-append / COW-copy paths silently
   corrupts every shared reader of the page. Serving modules
   (paddle_tpu/inference/) may READ them through the pool API but
   must never assign, aug-assign, or ``.at[...]``-update them.
5. serving-bucket discipline: inference/serving.py must never hand
   the model an UNBUCKETED ragged token batch — a packed feed whose
   length varies freely keys a fresh XLA compile per distinct length
   (the recompile-serving-shape hazard the trace linter flags). Any
   function in the scheduler module that calls ``*.prefill_chunk(...)``
   must also call the sanctioned pad-to-bucket helper
   (``bucket_packed_tokens``) in the same scope.
6. pool-mutation audit (the static half of the KV page-pool
   sanitizer, incubate/nn/page_sanitizer.py): the paged pool's state
   — page payloads (``k_pages``/``v_pages``), quantization sidecars
   (``k_scales``/``v_scales``), refcount bookkeeping
   (``_refcnt``/``_free``/``_tables``/``_lens``/``_ext_refs``), and
   the host swap tier's store (``_swap_store``/``_swap_used`` on
   HostKVSwapSpace) — may be written ONLY inside PagedKVCacheManager
   methods (paged_cache.py). Any other inference/incubate module
   assigning, aug-assigning, or ``.at[...]``-updating them bypasses
   the sanitizer's event instrumentation; and the serving consumers
   (inference/serving.py, prefix_cache.py, paged_llama.py) must stay
   on the public audited pool API — calling a pool-private underscore
   method (``_next_slot``/``_release_page``/``_fork_page``/
   ``_swap_put``/...) or touching the private bookkeeping attrs from
   there is an error. Together these guarantee the dynamic
   sanitizer's event coverage statically: there is no
   un-instrumented mutation path (the swap tier included).
6b. serving terminal-trace discipline: any function in
   inference/serving.py that moves a request to a terminal state
   (assigns ``RequestState.FINISHED``/``ABORTED_DEADLINE`` or writes
   ``self._finished[...]``) must call ``self._traces.complete(...)``
   in the same function — the scheduler may never drop a request
   without its terminal request-trace event, so per-request
   timelines stay complete under preemption and deadline aborts.
7. clock discipline (the framework/telemetry.py observability
   contract): the instrumented serving modules
   (inference/serving.py, incubate/nn/paged_cache.py,
   inference/prefix_cache.py) must not read wall clocks directly —
   telemetry spans and ``telemetry.clock()`` are the single timing
   path, so TTFT/TPOT/span accounting can never silently fork from
   an ad-hoc ``time.time()``. framework/telemetry.py itself is also
   held jax-free (HOST_ONLY_FILES): it is imported by host-only
   modules and backs the admission loop's accounting.
8. flag inventory: every flag defined in framework/flags.py must
   carry a non-empty docstring and be mentioned (``FLAGS_<name>``)
   somewhere under docs/ — an env knob nobody can discover from the
   docs is configuration drift waiting to happen. docs/FLAGS.md is
   the catch-all reference that keeps the rule satisfiable for every
   flag; feature pages (SERVING/ANALYSIS/OBSERVABILITY/...) carry
   the load-bearing ones.
9. collective-matmul discipline: ops/kernels/collective_matmul.py is
   jax-only (every body runs inside jit traces under shard_map) — no
   host-side module imports (os/sys/time/numpy/threading/...); and the
   TP/SP layer modules (mpu/mp_layers.py, mpu/mp_ops.py,
   sequence_parallel_utils.py) must route dependent matmul+collective
   pairs through the subsystem (mp_ops.collective_matmul_dispatch)
   instead of hand-rolling new blocking chains: no single function may
   call both a raw lax collective (all_gather/psum/psum_scatter/...)
   and a raw matmul (jnp.matmul/dot_general/F.linear/...).
10. wire-quant ownership: quantize-on-the-wire for ring collectives
   (FLAGS_collective_dtype) is implemented once, in the jax-only
   kernel module — the TP/SP layer modules, the DP grad-sync helper
   (fleet/utils/hybrid_parallel_util.py) and the MoE layer
   (incubate/.../moe_layer.py) must not cast a payload to
   int8/float8 in the same function as a raw collective: a
   hand-rolled wire cast bypasses the block-scale format, the
   custom-VJP cotangent rings, and the planner's exact byte model.

Run: JAX_PLATFORMS=cpu python tools/lint_codebase.py
Wired as a tier-1 test in tests/test_lint_codebase.py.
"""
from __future__ import annotations

import ast
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# modules whose function bodies execute inside jit traces
TRACED_PATH_DIRS = (
    os.path.join("paddle_tpu", "ops", "kernels"),
    os.path.join("paddle_tpu", "nn", "functional"),
)
TRACED_PATH_FILES = (
    os.path.join("paddle_tpu", "jit", "dy2static.py"),
)

# (module-alias head, attribute) pairs forbidden in traced code
_FORBIDDEN = {
    ("jax", "device_get"): "materializes device buffers on host",
    ("np", "asarray"): "host-materializes a traced value "
                       "(use jnp.asarray for in-graph conversion)",
    ("numpy", "asarray"): "host-materializes a traced value "
                          "(use jnp.asarray for in-graph conversion)",
    ("time", "time"): "wall-clock reads trace to a constant "
                      "(and defeat step timing)",
}

_WAIVER_MARK = "# trace-lint: ok"

# modules that must stay PURE host bookkeeping: the prefix-cache
# subsystem runs inside the scheduler's admission loop, where any jax
# import means device compute (or a device sync) per admitted request;
# the telemetry module is imported BY host-only modules and must
# itself never pull jax in (the jax-free contract of
# docs/OBSERVABILITY.md)
HOST_ONLY_FILES = (
    os.path.join("paddle_tpu", "inference", "prefix_cache.py"),
    os.path.join("paddle_tpu", "framework", "telemetry.py"),
    os.path.join("paddle_tpu", "framework", "watchdog.py"),
    os.path.join("paddle_tpu", "framework", "perf_ledger.py"),
    os.path.join("paddle_tpu", "framework", "flight_recorder.py"),
    os.path.join("paddle_tpu", "framework", "ops_server.py"),
    os.path.join("paddle_tpu", "incubate", "nn", "fault_injection.py"),
    os.path.join("paddle_tpu", "framework", "concurrency.py"),
    # the disaggregated router/transfer plane is host orchestration:
    # it serializes host swap buffers and marshals requests between
    # schedulers — a jax import here would put device compute on the
    # session-routing path
    os.path.join("paddle_tpu", "inference", "disagg.py"),
    # the capacity autotuner scores duck-typed plan dicts and fleet
    # snapshots shipped from other hosts — it must stay importable
    # (and runnable) with no accelerator runtime at all
    os.path.join("paddle_tpu", "framework", "autotuner.py"),
)

_HOST_ONLY_BANNED_MODULES = ("jax", "jax.numpy")


def _dotted_head(node):
    """For a Call like np.asarray(x) return ('np', 'asarray')."""
    fn = node.func
    if isinstance(fn, ast.Attribute) and isinstance(fn.value, ast.Name):
        return fn.value.id, fn.attr
    return None


class _TracedPathVisitor(ast.NodeVisitor):
    def __init__(self, relpath, source_lines):
        self.relpath = relpath
        self.lines = source_lines
        self.violations = []
        self._func_stack = []

    def _in_reference_fn(self):
        return any(name.endswith("_reference")
                   for name in self._func_stack)

    def visit_FunctionDef(self, node):
        self._func_stack.append(node.name)
        self.generic_visit(node)
        self._func_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Call(self, node):
        head = _dotted_head(node)
        if head in _FORBIDDEN and not self._in_reference_fn():
            line = self.lines[node.lineno - 1] \
                if node.lineno - 1 < len(self.lines) else ""
            if _WAIVER_MARK not in line:
                self.violations.append(
                    "%s:%d: %s.%s in traced-path module (%s); fix it "
                    "or waive with '%s(<reason>)'"
                    % (self.relpath, node.lineno, head[0], head[1],
                       _FORBIDDEN[head], _WAIVER_MARK))
        self.generic_visit(node)


def lint_file(path, text=None):
    """Traced-path check for one file; returns violation strings."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    v = _TracedPathVisitor(rel, text.splitlines())
    v.visit(tree)
    return v.violations


def check_traced_paths(root=REPO):
    files = []
    for d in TRACED_PATH_DIRS:
        full = os.path.join(root, d)
        for fn in sorted(os.listdir(full)):
            if fn.endswith(".py"):
                files.append(os.path.join(full, fn))
    files += [os.path.join(root, f) for f in TRACED_PATH_FILES]
    out = []
    for path in files:
        out.extend(lint_file(path))
    return out


class _HostOnlyVisitor(ast.NodeVisitor):
    """Flags any jax/jnp import or attribute use in a module declared
    pure host bookkeeping."""

    def __init__(self, relpath, source_lines):
        self.relpath = relpath
        self.lines = source_lines
        self.violations = []

    def _flag(self, lineno, what):
        line = self.lines[lineno - 1] \
            if lineno - 1 < len(self.lines) else ""
        if _WAIVER_MARK not in line:
            self.violations.append(
                "%s:%d: %s in a host-only module (prefix-cache "
                "bookkeeping runs in the scheduler's admission loop; "
                "no device compute or sync allowed); fix it or waive "
                "with '%s(<reason>)'"
                % (self.relpath, lineno, what, _WAIVER_MARK))

    def visit_Import(self, node):
        for alias in node.names:
            head = alias.name.split(".")[0]
            if head == "jax":
                self._flag(node.lineno, "import %s" % alias.name)
        self.generic_visit(node)

    def visit_ImportFrom(self, node):
        mod = node.module or ""
        if mod.split(".")[0] == "jax":
            self._flag(node.lineno, "from %s import ..." % mod)
        self.generic_visit(node)

    def visit_Attribute(self, node):
        if isinstance(node.value, ast.Name) \
                and node.value.id in ("jax", "jnp"):
            self._flag(node.lineno,
                       "%s.%s" % (node.value.id, node.attr))
        self.generic_visit(node)


def lint_host_only_file(path, text=None):
    """Host-only check for one file; returns violation strings."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    v = _HostOnlyVisitor(rel, text.splitlines())
    v.visit(tree)
    return v.violations


def check_host_only(root=REPO):
    out = []
    for f in HOST_ONLY_FILES:
        out.extend(lint_host_only_file(os.path.join(root, f)))
    return out


# clock discipline (the observability contract of framework/
# telemetry.py): the instrumented serving modules must have exactly
# ONE timing path — telemetry spans / telemetry.clock(). A direct
# time.time()/perf_counter() read in the scheduler or the caches is
# ad-hoc timing the telemetry layer cannot see (and time.time is not
# even monotonic), so latency accounting silently forks.
CLOCK_DISCIPLINE_FILES = (
    os.path.join("paddle_tpu", "inference", "serving.py"),
    os.path.join("paddle_tpu", "inference", "prefix_cache.py"),
    os.path.join("paddle_tpu", "incubate", "nn", "paged_cache.py"),
)

# clock attributes of the time module (dotted calls time.X(...))
_CLOCK_ATTRS = frozenset({
    "time", "time_ns", "perf_counter", "perf_counter_ns",
    "monotonic", "monotonic_ns", "process_time", "process_time_ns",
    "thread_time", "thread_time_ns", "clock_gettime",
    "clock_gettime_ns",
})


class _ClockDisciplineVisitor(ast.NodeVisitor):
    """Flags direct wall-clock reads: ``time.<clock>()`` calls and
    ``from time import <clock>`` (which would make the later bare
    call invisible to a call-site check)."""

    def __init__(self, relpath, source_lines):
        self.relpath = relpath
        self.lines = source_lines
        self.violations = []

    def _flag(self, lineno, what):
        line = self.lines[lineno - 1] \
            if lineno - 1 < len(self.lines) else ""
        if _WAIVER_MARK not in line:
            self.violations.append(
                "%s:%d: %s in a telemetry-disciplined serving module "
                "(spans / telemetry.clock() are the SINGLE timing "
                "path — ad-hoc clock reads fork the latency "
                "accounting; framework/telemetry.py); route it "
                "through the telemetry layer or waive with "
                "'%s(<reason>)'"
                % (self.relpath, lineno, what, _WAIVER_MARK))

    def visit_Call(self, node):
        dotted = _dotted_head(node)
        if dotted is not None and dotted[0] == "time" \
                and dotted[1] in _CLOCK_ATTRS:
            self._flag(node.lineno, "time.%s()" % dotted[1])
        self.generic_visit(node)

    def visit_ImportFrom(self, node):
        if (node.module or "") == "time":
            names = sorted(a.name for a in node.names
                           if a.name in _CLOCK_ATTRS or a.name == "*")
            if names:
                self._flag(node.lineno,
                           "from time import %s" % ", ".join(names))
        self.generic_visit(node)


def lint_clock_discipline_file(path, text=None):
    """Clock-discipline check for one file; returns violations."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    v = _ClockDisciplineVisitor(rel, text.splitlines())
    v.visit(tree)
    return v.violations


def check_clock_discipline(root=REPO):
    out = []
    for f in CLOCK_DISCIPLINE_FILES:
        out.extend(lint_clock_discipline_file(os.path.join(root, f)))
    return out


# watchdog read-only discipline (the framework/watchdog.py
# observability contract): detector code may READ the telemetry
# registry (counter / gauge_value / histogram / hist_samples /
# snapshot) but must never mutate it, and must never reach into
# serving/pool state — a detector that writes the metrics it watches
# (or perturbs the pool it diagnoses) produces evidence nobody can
# trust. Evidence that requires pool access (the sanitizer journal
# tail) is gathered by the SCHEDULER through public API and handed
# in via the check() context. The incident flight recorder
# (framework/flight_recorder.py) is held to the SAME read-only
# surface: a recorder that perturbs the metrics it snapshots (or
# reaches into a pool for "better" evidence) corrupts the incident
# bundle it exists to preserve.
WATCHDOG_FILES = (
    os.path.join("paddle_tpu", "framework", "watchdog.py"),
    os.path.join("paddle_tpu", "framework", "flight_recorder.py"),
    # the live-ops debug server is a READ-ONLY surface by the same
    # contract: it renders registry/ledger/bundle state, never
    # mutates it
    os.path.join("paddle_tpu", "framework", "ops_server.py"),
)

# registry mutators (MetricsRegistry write surface) banned in
# detector code
_REGISTRY_MUTATORS = frozenset({
    "inc", "gauge", "observe", "set_epoch", "advance_epoch",
})
# (the visitor itself — _WatchdogReadOnlyVisitor — subclasses the
# pool-mutation visitor and is defined after it, below)


# serving-layer modules barred from writing the quantized-page scale
# sidecars (pool-private state; see paddle_cache's _quant_write)
QUANT_SIDECAR_DIRS = (
    os.path.join("paddle_tpu", "inference"),
)

_SIDECAR_ATTRS = ("k_scales", "v_scales")


class _SidecarWriteVisitor(ast.NodeVisitor):
    """Flags writes to the quantized-page scale sidecars from serving
    code: attribute assignment (x.k_scales = ..., x.k_scales += ...)
    and functional updates (x.k_scales.at[...] — the jnp mutation
    idiom, which is always followed by a rebind)."""

    def __init__(self, relpath, source_lines):
        self.relpath = relpath
        self.lines = source_lines
        self.violations = []

    def _flag(self, lineno, what):
        line = self.lines[lineno - 1] \
            if lineno - 1 < len(self.lines) else ""
        if _WAIVER_MARK not in line:
            self.violations.append(
                "%s:%d: %s — quantized-page scale sidecars are pool-"
                "private (mutate only via the PagedKVCacheManager "
                "append/COW paths); fix it or waive with '%s(<reason>)'"
                % (self.relpath, lineno, what, _WAIVER_MARK))

    def _sidecar_target(self, node):
        return (isinstance(node, ast.Attribute)
                and node.attr in _SIDECAR_ATTRS)

    def visit_Assign(self, node):
        for t in node.targets:
            for sub in ast.walk(t):
                if self._sidecar_target(sub):
                    self._flag(node.lineno,
                               "assignment to .%s" % sub.attr)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        for sub in ast.walk(node.target):
            if self._sidecar_target(sub):
                self._flag(node.lineno,
                           "augmented assignment to .%s" % sub.attr)
        self.generic_visit(node)

    def visit_Attribute(self, node):
        # x.k_scales.at[...] — the functional-update idiom
        if node.attr == "at" and self._sidecar_target(node.value):
            self._flag(node.lineno,
                       ".%s.at[...] update" % node.value.attr)
        self.generic_visit(node)


def lint_quant_sidecar_file(path, text=None):
    """Sidecar-write check for one file; returns violation strings."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    v = _SidecarWriteVisitor(rel, text.splitlines())
    v.visit(tree)
    return v.violations


def check_quant_sidecar_writes(root=REPO):
    out = []
    for d in QUANT_SIDECAR_DIRS:
        full = os.path.join(root, d)
        for fn in sorted(os.listdir(full)):
            if fn.endswith(".py"):
                out.extend(
                    lint_quant_sidecar_file(os.path.join(full, fn)))
    return out


# pool-mutation audit (static half of the page sanitizer): pool state
# writable ONLY inside PagedKVCacheManager (incubate/nn/paged_cache.py)
POOL_MUTATION_DIRS = (
    os.path.join("paddle_tpu", "inference"),
    os.path.join("paddle_tpu", "incubate", "nn"),
)
POOL_MUTATION_EXEMPT = (
    os.path.join("paddle_tpu", "incubate", "nn", "paged_cache.py"),
)

# every attr here is PagedKVCacheManager-private mutable state; the
# tree's own `node.pages` lists are tree state and deliberately NOT in
# this set (the pool's page payloads are k_pages/v_pages). The host
# swap tier's store (_swap_store/_swap_used on HostKVSwapSpace) is
# swap-tier-private by the same contract: writable only through the
# pool's swap_out/swap_in/swap_discard so the sanitizer's swap events
# see every transition
_POOL_STATE_ATTRS = (
    "k_pages", "v_pages", "k_scales", "v_scales",
    "_refcnt", "_free", "_tables", "_lens", "_ext_refs",
    "_swap_store", "_swap_used",
    # sharded-pool geometry (mp-mesh KV-head split): rewriting any of
    # these after construction would silently misroute every wire
    # transfer's head-axis reassembly
    "kv_heads_global", "head_start", "mp_size", "mp_rank",
)
# the refcount-bookkeeping subset: reading these from serving code is
# also an API bypass (the pool exposes num_free_pages/seq_pages/...;
# the swap space exposes used_bytes/free_bytes/num_records/summary)
_POOL_BOOKKEEPING_ATTRS = (
    "_refcnt", "_free", "_tables", "_lens", "_ext_refs",
    "_swap_store", "_swap_used",
)

# serving modules restricted to the PUBLIC audited pool API
POOL_API_FILES = (
    os.path.join("paddle_tpu", "inference", "serving.py"),
    os.path.join("paddle_tpu", "inference", "prefix_cache.py"),
    os.path.join("paddle_tpu", "inference", "paged_llama.py"),
    os.path.join("paddle_tpu", "inference", "disagg.py"),
)

# pool-private methods a serving module must never call (each is an
# un-instrumented mutation or kernel-input path the sanitizer's event
# coverage depends on)
_POOL_PRIVATE_METHODS = (
    "_next_slot", "_release_page", "_alloc_page", "_fork_page",
    "_copy_page", "_quant_write", "_padded_kernel_inputs",
    "_ref_pages", "_drop_refs", "_needs_fork",
    "_swap_put", "_swap_get", "_swap_pop",
)


class _PoolStateWriteVisitor(ast.NodeVisitor):
    """Flags writes to PagedKVCacheManager state from outside the pool
    module: attribute assignment (x.k_pages = ..., x._refcnt[p] = ...,
    x._free += ...) and functional updates (x.k_pages.at[...])."""

    def __init__(self, relpath, source_lines):
        self.relpath = relpath
        self.lines = source_lines
        self.violations = []

    def _flag(self, lineno, what):
        line = self.lines[lineno - 1] \
            if lineno - 1 < len(self.lines) else ""
        if _WAIVER_MARK not in line:
            self.violations.append(
                "%s:%d: %s — PagedKVCacheManager state is pool-"
                "private (mutate only through the audited API in "
                "incubate/nn/paged_cache.py, whose methods the page "
                "sanitizer instruments); fix it or waive with "
                "'%s(<reason>)'"
                % (self.relpath, lineno, what, _WAIVER_MARK))

    def _pool_target(self, node):
        # x.k_pages, x.k_pages[i], x._free[0] ... any write whose
        # innermost attribute is a pool state attr
        while isinstance(node, (ast.Subscript, ast.Starred)):
            node = node.value
        return (isinstance(node, ast.Attribute)
                and node.attr in _POOL_STATE_ATTRS)

    def visit_Assign(self, node):
        for t in node.targets:
            for sub in ast.walk(t):
                if self._pool_target(sub):
                    self._flag(node.lineno,
                               "assignment to .%s"
                               % self._attr_name(sub))
                    break
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        if self._pool_target(node.target):
            self._flag(node.lineno,
                       "augmented assignment to .%s"
                       % self._attr_name(node.target))
        self.generic_visit(node)

    def visit_Attribute(self, node):
        # x.k_pages.at[...] — the jnp functional-update idiom
        if node.attr == "at" and isinstance(node.value, ast.Attribute) \
                and node.value.attr in _POOL_STATE_ATTRS:
            self._flag(node.lineno,
                       ".%s.at[...] update" % node.value.attr)
        self.generic_visit(node)

    def visit_Call(self, node):
        # x._free.pop() / x._tables.update(...) — container mutation
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr in (
                "append", "pop", "extend", "insert", "remove",
                "clear", "update", "setdefault", "popitem") \
                and self._pool_target(fn.value):
            self._flag(node.lineno,
                       ".%s.%s(...) mutation"
                       % (self._attr_name(fn.value), fn.attr))
        self.generic_visit(node)

    @staticmethod
    def _attr_name(node):
        while isinstance(node, (ast.Subscript, ast.Starred)):
            node = node.value
        return node.attr if isinstance(node, ast.Attribute) else "?"


class _PoolPrivateAPIVisitor(ast.NodeVisitor):
    """Flags serving modules stepping off the public pool API: calls
    into pool-private underscore methods and any access to the
    refcount-bookkeeping attrs."""

    def __init__(self, relpath, source_lines):
        self.relpath = relpath
        self.lines = source_lines
        self.violations = []

    def _flag(self, lineno, what):
        line = self.lines[lineno - 1] \
            if lineno - 1 < len(self.lines) else ""
        if _WAIVER_MARK not in line:
            self.violations.append(
                "%s:%d: %s — serving modules may only use the PUBLIC "
                "audited PagedKVCacheManager API (the page sanitizer "
                "instruments exactly those entry points); fix it or "
                "waive with '%s(<reason>)'"
                % (self.relpath, lineno, what, _WAIVER_MARK))

    def visit_Call(self, node):
        fn = node.func
        if isinstance(fn, ast.Attribute) \
                and fn.attr in _POOL_PRIVATE_METHODS:
            self._flag(node.lineno,
                       "call into pool-private .%s()" % fn.attr)
        self.generic_visit(node)

    def visit_Attribute(self, node):
        if node.attr in _POOL_BOOKKEEPING_ATTRS:
            self._flag(node.lineno,
                       "access to pool-private .%s" % node.attr)
        self.generic_visit(node)


def lint_pool_state_file(path, text=None):
    """Pool-state write audit for one file; returns violations."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    v = _PoolStateWriteVisitor(rel, text.splitlines())
    v.visit(tree)
    return v.violations


def lint_pool_api_file(path, text=None):
    """Public-pool-API audit for one serving file; returns
    violations."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    v = _PoolPrivateAPIVisitor(rel, text.splitlines())
    v.visit(tree)
    return v.violations


def check_pool_mutation_audit(root=REPO):
    out = []
    exempt = {os.path.join(root, f) for f in POOL_MUTATION_EXEMPT}
    for d in POOL_MUTATION_DIRS:
        full = os.path.join(root, d)
        for fn in sorted(os.listdir(full)):
            path = os.path.join(full, fn)
            if fn.endswith(".py") and path not in exempt:
                out.extend(lint_pool_state_file(path))
    for f in POOL_API_FILES:
        out.extend(lint_pool_api_file(os.path.join(root, f)))
    return out


# the serving scheduler may never DROP a request silently: any
# function that moves a request to a terminal state (writes
# self._finished[...] or assigns RequestState.FINISHED /
# RequestState.ABORTED_DEADLINE) must emit the terminal request-trace
# event (self._traces.complete(...)) in the SAME function, so every
# retired/aborted request has a complete timeline when tracing is on
SERVING_TERMINAL_FILES = (
    os.path.join("paddle_tpu", "inference", "serving.py"),
)
_TERMINAL_STATES = ("FINISHED", "ABORTED_DEADLINE")


def _fn_drops_request(fn_node):
    """(drops, emits) for one function body: does it move a request
    to a terminal state, and does it call ._traces.complete(...)?"""
    drops = emits = False
    for node in ast.walk(fn_node):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                # self._finished[rid] = req
                if isinstance(t, ast.Subscript) \
                        and isinstance(t.value, ast.Attribute) \
                        and t.value.attr == "_finished":
                    drops = True
                # req.state = RequestState.FINISHED / ABORTED_DEADLINE
                if isinstance(t, ast.Attribute) \
                        and t.attr == "state" \
                        and isinstance(node.value, ast.Attribute) \
                        and isinstance(node.value.value, ast.Name) \
                        and node.value.value.id == "RequestState" \
                        and node.value.attr in _TERMINAL_STATES:
                    drops = True
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "complete" \
                and isinstance(node.func.value, ast.Attribute) \
                and node.func.value.attr == "_traces":
            emits = True
    return drops, emits


def lint_serving_terminal_file(path, text=None):
    """Terminal-trace audit for one scheduler file; returns
    violations."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    lines = text.splitlines()
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
            continue
        drops, emits = _fn_drops_request(node)
        if drops and not emits:
            line = lines[node.lineno - 1] \
                if node.lineno - 1 < len(lines) else ""
            if _WAIVER_MARK not in line:
                out.append(
                    "%s:%d: %s() moves a request to a terminal state "
                    "without calling self._traces.complete(...) — the "
                    "scheduler must never drop a request silently "
                    "(every retired/aborted request needs its "
                    "terminal trace event); fix it or waive with "
                    "'%s(<reason>)'"
                    % (rel, node.lineno, node.name, _WAIVER_MARK))
    return out


def check_serving_terminal_trace(root=REPO):
    out = []
    for f in SERVING_TERMINAL_FILES:
        out.extend(lint_serving_terminal_file(os.path.join(root, f)))
    return out


class _WatchdogReadOnlyVisitor(_PoolStateWriteVisitor):
    """Flags watchdog/detector code stepping off the read-only
    surface: registry mutator calls (obj.inc/gauge/observe/
    set_epoch), pool-private underscore method calls, and — via the
    inherited pool-mutation visitor — any write to
    PagedKVCacheManager state attrs."""

    def _flag(self, lineno, what):
        line = self.lines[lineno - 1] \
            if lineno - 1 < len(self.lines) else ""
        if _WAIVER_MARK not in line:
            self.violations.append(
                "%s:%d: %s — watchdog/detector code is registry-READ-"
                "ONLY (no registry mutation, no serving/pool state "
                "mutation, no pool-private calls; evidence needing "
                "pool access is handed in via check()'s context); "
                "fix it or waive with '%s(<reason>)'"
                % (self.relpath, lineno, what, _WAIVER_MARK))

    def visit_Call(self, node):
        fn = node.func
        if isinstance(fn, ast.Attribute):
            if fn.attr in _REGISTRY_MUTATORS:
                self._flag(node.lineno,
                           "registry mutator call .%s(...)" % fn.attr)
                self.generic_visit(node)
                return
            if fn.attr in _POOL_PRIVATE_METHODS:
                self._flag(node.lineno,
                           "call into pool-private .%s()" % fn.attr)
                self.generic_visit(node)
                return
        # the inherited check (container mutations on pool state)
        super().visit_Call(node)


def lint_watchdog_file(path, text=None):
    """Watchdog read-only audit for one file; returns violations."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    v = _WatchdogReadOnlyVisitor(rel, text.splitlines())
    v.visit(tree)
    return v.violations


def check_watchdog_readonly(root=REPO):
    out = []
    for f in WATCHDOG_FILES:
        out.extend(lint_watchdog_file(os.path.join(root, f)))
    return out


# bundle-atomicity discipline (the incident flight recorder's write
# contract): every file an incident-bundle writer produces must go
# through telemetry's atomic-write helper (atomic_write_text: tmp +
# rename) — a torn half-written evidence file defeats the bundle's
# whole purpose. Operationally: NO direct write/append-mode open()
# calls in the incident-writer modules (reads stay allowed — the
# --summarize-incident replay lives next door), and a dynamic (non-
# literal) mode is flagged too because the linter cannot prove it
# read-only. Directory-level renames (the bundle's own atomicity
# point) are the writer's job and stay allowed.
INCIDENT_WRITER_FILES = (
    os.path.join("paddle_tpu", "framework", "flight_recorder.py"),
)

_WRITE_MODE_CHARS = frozenset("wax+")


class _BundleAtomicityVisitor(ast.NodeVisitor):
    """Flags direct write-mode ``open()`` (and ``io.open``/
    ``os.fdopen``) calls in incident-writer modules."""

    def __init__(self, relpath, source_lines):
        self.relpath = relpath
        self.lines = source_lines
        self.violations = []

    def _flag(self, lineno, what):
        line = self.lines[lineno - 1] \
            if lineno - 1 < len(self.lines) else ""
        if _WAIVER_MARK not in line:
            self.violations.append(
                "%s:%d: %s — incident-bundle writers must go through "
                "telemetry.atomic_write_text (tmp + rename; a torn "
                "half-written evidence file defeats the bundle); fix "
                "it or waive with '%s(<reason>)'"
                % (self.relpath, lineno, what, _WAIVER_MARK))

    def _is_open(self, node):
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id == "open":
            return "open"
        dotted = _dotted_head(node)
        if dotted in (("io", "open"), ("os", "fdopen")):
            return "%s.%s" % dotted
        return None

    def visit_Call(self, node):
        name = self._is_open(node)
        if name is not None:
            mode = None
            if len(node.args) >= 2:
                mode = node.args[1]
            for kw in node.keywords:
                if kw.arg == "mode":
                    mode = kw.value
            if mode is None:
                pass  # default "r": a read, allowed
            elif isinstance(mode, ast.Constant) \
                    and isinstance(mode.value, str):
                if _WRITE_MODE_CHARS & set(mode.value):
                    self._flag(node.lineno,
                               "%s(..., %r)" % (name, mode.value))
            else:
                self._flag(node.lineno,
                           "%s(...) with a dynamic mode (cannot be "
                           "proven read-only)" % name)
        self.generic_visit(node)


def lint_incident_writer_file(path, text=None):
    """Bundle-atomicity check for one file; returns violations."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    v = _BundleAtomicityVisitor(rel, text.splitlines())
    v.visit(tree)
    return v.violations


def check_bundle_atomicity(root=REPO):
    out = []
    for f in INCIDENT_WRITER_FILES:
        out.extend(lint_incident_writer_file(os.path.join(root, f)))
    return out


# the serving scheduler module: every packed ragged feed it hands the
# model must be padded through the bucket helper first (otherwise each
# distinct packed length compiles a fresh XLA program)
SERVING_BUCKET_FILES = (
    os.path.join("paddle_tpu", "inference", "serving.py"),
)

# the model entry that consumes a packed ragged token batch, and the
# sanctioned helper that buckets it
_RAGGED_MODEL_CALLS = frozenset({"prefill_chunk"})
_BUCKET_HELPER_CALLS = frozenset({"bucket_packed_tokens"})


class _ServingBucketVisitor(ast.NodeVisitor):
    """Per innermost function: a ``*.prefill_chunk(...)`` call without
    a ``bucket_packed_tokens`` call in the same scope feeds the model
    a raw packed length — the unbucketed ragged batch the trace
    linter's recompile-serving-shape rule exists to catch at runtime;
    this catches it at review time."""

    def __init__(self, relpath, source_lines):
        self.relpath = relpath
        self.lines = source_lines
        self.violations = []

    def _call_name(self, node):
        fn = node.func
        if isinstance(fn, ast.Attribute):
            return fn.attr
        if isinstance(fn, ast.Name):
            return fn.id
        return None

    def _scoped_calls(self, node):
        stack = list(ast.iter_child_nodes(node))
        while stack:
            sub = stack.pop()
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            if isinstance(sub, ast.Call):
                yield sub
            stack.extend(ast.iter_child_nodes(sub))

    def _check_fn(self, node):
        ragged, bucketed = [], False
        for sub in self._scoped_calls(node):
            name = self._call_name(sub)
            if name in _RAGGED_MODEL_CALLS:
                ragged.append((sub.lineno, name))
            elif name in _BUCKET_HELPER_CALLS:
                bucketed = True
        if ragged and not bucketed:
            lineno, name = min(ragged)
            line = self.lines[lineno - 1] \
                if lineno - 1 < len(self.lines) else ""
            if _WAIVER_MARK not in line:
                self.violations.append(
                    "%s:%d: function %r calls %s without bucketing "
                    "the packed feed (bucket_packed_tokens) — an "
                    "unbucketed ragged token batch compiles one XLA "
                    "program per distinct packed length; pad to a "
                    "FLAGS_serving_buckets bucket or waive with "
                    "'%s(<reason>)'"
                    % (self.relpath, lineno, node.name, name,
                       _WAIVER_MARK))

    def visit_FunctionDef(self, node):
        self._check_fn(node)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef


def lint_serving_bucket_file(path, text=None):
    """Bucketed-ragged-feed check; returns violation strings."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    v = _ServingBucketVisitor(rel, text.splitlines())
    v.visit(tree)
    return v.violations


def check_serving_buckets(root=REPO):
    out = []
    for f in SERVING_BUCKET_FILES:
        out.extend(lint_serving_bucket_file(os.path.join(root, f)))
    return out


# the packed serving step must route attention through the unified
# ragged pool API (ROADMAP item 2: one attend program per packed
# config): a function landing a ragged append must attend through the
# unified entry in the same scope
UNIFIED_ATTENTION_FILES = (
    os.path.join("paddle_tpu", "inference", "serving.py"),
    os.path.join("paddle_tpu", "inference", "paged_llama.py"),
)

_UNIFIED_ATTEND_CALLS = frozenset({"attend_ragged",
                                   "layer_step"})
_PACKED_STEP_MARKERS = frozenset({"append_ragged"})


class _UnifiedAttentionVisitor(ast.NodeVisitor):
    """Per innermost function of the serving layers: a function that
    lands a ragged append (``append_ragged`` — the packed-step marker)
    must route its attention through the unified pool API in the same
    scope — a packed step that appends ragged K/V but attends some
    other way re-splits the one attend program per packed config.
    """

    def __init__(self, relpath, source_lines):
        self.relpath = relpath
        self.lines = source_lines
        self.violations = []

    def _call_name(self, node):
        fn = node.func
        if isinstance(fn, ast.Attribute):
            return fn.attr
        if isinstance(fn, ast.Name):
            return fn.id
        return None

    def _scoped_calls(self, node):
        stack = list(ast.iter_child_nodes(node))
        while stack:
            sub = stack.pop()
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            if isinstance(sub, ast.Call):
                yield sub
            stack.extend(ast.iter_child_nodes(sub))

    def _waived(self, lineno):
        line = self.lines[lineno - 1] \
            if lineno - 1 < len(self.lines) else ""
        return _WAIVER_MARK in line

    def _check_fn(self, node):
        unified = False
        appends = []
        for sub in self._scoped_calls(node):
            name = self._call_name(sub)
            if name in _UNIFIED_ATTEND_CALLS:
                unified = True
            elif name in _PACKED_STEP_MARKERS:
                appends.append(sub.lineno)
        if appends and not unified:
            lineno = min(appends)
            if not self._waived(lineno):
                self.violations.append(
                    "%s:%d: function %r lands a ragged append "
                    "(append_ragged) without attending through the "
                    "unified pool API (attend_ragged/"
                    "layer_step) in the same scope — the "
                    "packed step must compile ONE attend program per "
                    "config; fix it or waive with '%s(<reason>)'"
                    % (self.relpath, lineno, node.name, _WAIVER_MARK))

    def visit_FunctionDef(self, node):
        self._check_fn(node)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef


def lint_unified_attention_file(path, text=None):
    """Unified-attention routing check; returns violation strings."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    v = _UnifiedAttentionVisitor(rel, text.splitlines())
    v.visit(tree)
    return v.violations


def check_unified_attention(root=REPO):
    out = []
    for f in UNIFIED_ATTENTION_FILES:
        out.extend(lint_unified_attention_file(os.path.join(root, f)))
    return out


# modules that must stay pure-jax: collective-matmul ring kernels run
# entirely inside jit traces under shard_map — a host-side import is
# either dead weight or a per-step host sync waiting to happen
JAX_ONLY_FILES = (
    os.path.join("paddle_tpu", "ops", "kernels", "collective_matmul.py"),
)

# allowed top-level imports in a jax-only module (relative, in-package
# imports are always allowed — e.g. the framework flags registry)
_JAX_ONLY_ALLOWED = ("jax", "functools", "math", "typing", "__future__")


class _JaxOnlyImportVisitor(ast.NodeVisitor):
    def __init__(self, relpath, source_lines):
        self.relpath = relpath
        self.lines = source_lines
        self.violations = []

    def _flag(self, lineno, what):
        line = self.lines[lineno - 1] \
            if lineno - 1 < len(self.lines) else ""
        if _WAIVER_MARK not in line:
            self.violations.append(
                "%s:%d: %s in a jax-only kernel module (the collective-"
                "matmul rings run inside jit traces under shard_map; "
                "host-side imports are banned); fix it or waive with "
                "'%s(<reason>)'"
                % (self.relpath, lineno, what, _WAIVER_MARK))

    def visit_Import(self, node):
        for alias in node.names:
            head = alias.name.split(".")[0]
            if head not in _JAX_ONLY_ALLOWED:
                self._flag(node.lineno, "import %s" % alias.name)
        self.generic_visit(node)

    def visit_ImportFrom(self, node):
        if node.level:  # relative (in-package) import
            self.generic_visit(node)
            return
        head = (node.module or "").split(".")[0]
        if head not in _JAX_ONLY_ALLOWED:
            self._flag(node.lineno,
                       "from %s import ..." % (node.module or "?"))
        self.generic_visit(node)


def lint_jax_only_file(path, text=None):
    """Jax-only import check for one file; returns violation strings."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    v = _JaxOnlyImportVisitor(rel, text.splitlines())
    v.visit(tree)
    return v.violations


def check_jax_only(root=REPO):
    out = []
    for f in JAX_ONLY_FILES:
        out.extend(lint_jax_only_file(os.path.join(root, f)))
    return out


# TP/SP modules that must route matmul+collective pairs through the
# collective-matmul subsystem instead of hand-rolling blocking chains
TP_ROUTING_FILES = (
    os.path.join("paddle_tpu", "distributed", "fleet", "layers", "mpu",
                 "mp_layers.py"),
    os.path.join("paddle_tpu", "distributed", "fleet", "layers", "mpu",
                 "mp_ops.py"),
    os.path.join("paddle_tpu", "distributed", "fleet", "utils",
                 "sequence_parallel_utils.py"),
)

_RAW_COLLECTIVE_CALLS = frozenset({
    "all_gather", "psum", "psum_scatter", "ppermute", "all_to_all",
    "pmean",
})
_RAW_MATMUL_CALLS = frozenset({
    "matmul", "dot", "dot_general", "einsum", "tensordot", "linear",
})


class _TPRoutingVisitor(ast.NodeVisitor):
    """Per innermost function: a raw lax collective AND a raw matmul in
    the same body is a hand-rolled blocking pair — it belongs in
    ops/kernels/collective_matmul.py behind
    mp_ops.collective_matmul_dispatch."""

    def __init__(self, relpath, source_lines):
        self.relpath = relpath
        self.lines = source_lines
        self.violations = []

    def _call_name(self, node):
        fn = node.func
        if isinstance(fn, ast.Attribute):
            return fn.attr
        if isinstance(fn, ast.Name):
            return fn.id
        return None

    def _scoped_calls(self, node):
        """Call nodes in node's own scope — nested def/lambda bodies
        are separate scopes (they get their own visit / are VJP-closure
        territory)."""
        stack = list(ast.iter_child_nodes(node))
        while stack:
            sub = stack.pop()
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            if isinstance(sub, ast.Call):
                yield sub
            stack.extend(ast.iter_child_nodes(sub))

    def _check_fn(self, node):
        colls, mms = [], []
        for sub in self._scoped_calls(node):
            name = self._call_name(sub)
            if name in _RAW_COLLECTIVE_CALLS:
                colls.append((sub.lineno, name))
            elif name in _RAW_MATMUL_CALLS:
                mms.append((sub.lineno, name))
        if colls and mms:
            lineno = min(colls + mms)[0]
            line = self.lines[lineno - 1] \
                if lineno - 1 < len(self.lines) else ""
            if _WAIVER_MARK not in line:
                self.violations.append(
                    "%s:%d: function %r pairs a raw collective (%s) "
                    "with a raw matmul (%s) — a hand-rolled blocking "
                    "chain; route it through mp_ops."
                    "collective_matmul_dispatch (ops/kernels/"
                    "collective_matmul.py) or waive with '%s(<reason>)'"
                    % (self.relpath, lineno, node.name,
                       ", ".join(sorted({n for _, n in colls})),
                       ", ".join(sorted({n for _, n in mms})),
                       _WAIVER_MARK))

    def visit_FunctionDef(self, node):
        self._check_fn(node)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef


def lint_tp_routing_file(path, text=None):
    """Matmul+collective pairing check; returns violation strings.

    Walks only direct (non-nested-def) statements of each function, so
    the sanctioned wrappers — a collective in a dedicated VJP closure,
    a matmul in the layer body — don't pair up across scopes."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    v = _TPRoutingVisitor(rel, text.splitlines())
    v.visit(tree)
    return v.violations


def check_tp_routing(root=REPO):
    out = []
    for f in TP_ROUTING_FILES:
        out.extend(lint_tp_routing_file(os.path.join(root, f)))
    return out


# quantize-on-the-wire ownership: the quant/dequant of ring payloads
# (FLAGS_collective_dtype) lives ONLY in the jax-only kernel module —
# a raw int8/fp8 dtype cast next to a raw collective in the TP/SP
# layer modules, the DP grad-sync helper, or the MoE layer is a
# hand-rolled wire quantization that bypasses the block-scale format,
# the custom-VJP cotangent rings, and the planner's exact byte model
WIRE_QUANT_FILES = TP_ROUTING_FILES + (
    os.path.join("paddle_tpu", "distributed", "fleet", "utils",
                 "hybrid_parallel_util.py"),
    os.path.join("paddle_tpu", "incubate", "distributed", "models",
                 "moe", "moe_layer.py"),
)

_WIRE_QUANT_DTYPES = frozenset({
    "int8", "uint8", "float8_e4m3fn", "float8_e4m3", "float8_e5m2",
})


class _WireQuantVisitor(_TPRoutingVisitor):
    """Per innermost function: a raw lax collective AND a quantized
    dtype cast (``.astype('int8')`` / ``.astype(jnp.int8)`` /
    ``convert_element_type(..., int8)``) in the same body is wire
    quantization hand-rolled outside ops/kernels/collective_matmul.py."""

    def _quant_cast(self, node):
        """True when the Call quantize-casts: astype/convert with an
        int8/fp8 dtype argument (literal string, jnp attribute, or
        bare name)."""
        name = self._call_name(node)
        if name not in ("astype", "convert_element_type", "asarray",
                        "array"):
            return False
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            if isinstance(arg, ast.Constant) \
                    and isinstance(arg.value, str) \
                    and arg.value in _WIRE_QUANT_DTYPES:
                return True
            if isinstance(arg, ast.Attribute) \
                    and arg.attr in _WIRE_QUANT_DTYPES:
                return True
            if isinstance(arg, ast.Name) \
                    and arg.id in _WIRE_QUANT_DTYPES:
                return True
        return False

    def _check_fn(self, node):
        colls, casts = [], []
        for sub in self._scoped_calls(node):
            name = self._call_name(sub)
            if name in _RAW_COLLECTIVE_CALLS:
                colls.append((sub.lineno, name))
            if self._quant_cast(sub):
                casts.append((sub.lineno, name))
        if colls and casts:
            lineno = min(casts)[0]
            line = self.lines[lineno - 1] \
                if lineno - 1 < len(self.lines) else ""
            if _WAIVER_MARK not in line:
                self.violations.append(
                    "%s:%d: function %r casts a wire payload to a "
                    "quantized dtype (%s) next to a raw collective "
                    "(%s) — quantize-on-the-wire belongs in "
                    "ops/kernels/collective_matmul.py behind "
                    "FLAGS_collective_dtype (block scales, custom-VJP "
                    "cotangent rings, planner-exact bytes); route the "
                    "pair through the dispatch or waive with "
                    "'%s(<reason>)'"
                    % (self.relpath, lineno, node.name,
                       ", ".join(sorted({n for _, n in casts if n})),
                       ", ".join(sorted({n for _, n in colls})),
                       _WAIVER_MARK))


def lint_wire_quant_file(path, text=None):
    """Wire-quantization ownership check; returns violation strings."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    v = _WireQuantVisitor(rel, text.splitlines())
    v.visit(tree)
    return v.violations


def check_wire_quant(root=REPO):
    out = []
    for f in WIRE_QUANT_FILES:
        out.extend(lint_wire_quant_file(os.path.join(root, f)))
    return out


# flag inventory (the FLAGS registry contract): every flag defined in
# framework/flags.py must carry a non-empty docstring AND be mentioned
# (as FLAGS_<name>) somewhere under docs/ — an undocumented knob is a
# knob nobody can discover, and the docs/FLAGS.md reference exists
# precisely so this check is satisfiable for every flag
# metric-name discipline (ISSUE 15): every metric name emitted into
# the telemetry registry anywhere in the package must (a) be built
# from Prometheus-safe literal parts — lowercase [a-z0-9_.] only, so
# the name survives telemetry._prom_name unchanged modulo the dot
# separator (the round-trip contract of the /metrics endpoint and
# the fleet aggregation CLI), (b) never be an ad-hoc f-string, and
# (c) resolve to a row of the CENTRAL inventory telemetry.SURFACE —
# dynamic segments ("prefix." + var, "%s" templates) match the
# inventory's <placeholder> rows. The SURFACE tuple is parsed from
# telemetry.py's AST, so the check needs no package import. A
# deliberately dynamic emit (pre-resolved keys on a hot path) can
# waive a line (or its preceding comment) with '# metric-name: ok'.
TELEMETRY_SURFACE_FILE = os.path.join(
    "paddle_tpu", "framework", "telemetry.py")
_METRIC_EMIT_METHODS = frozenset({"inc", "observe", "gauge"})
# receiver names that ARE (by repo convention) a MetricsRegistry
# handle — obj.inc/observe/gauge on anything else is not a metric
_METRIC_RECEIVERS = frozenset({
    "m", "reg", "registry", "_reg", "_metrics", "_registry",
})
_METRIC_WAIVER = "# metric-name: ok"
_METRIC_NAME_CHARS = frozenset(
    "abcdefghijklmnopqrstuvwxyz0123456789._")


def surface_metric_names(root=REPO, text=None):
    """The metric names of telemetry.SURFACE, parsed from the module
    SOURCE (ast.literal_eval of the tuple literal — no package
    import), span rows excluded."""
    if text is None:
        with open(os.path.join(root, TELEMETRY_SURFACE_FILE),
                  encoding="utf-8") as f:
            text = f.read()
    tree = ast.parse(text)
    for node in ast.walk(tree):
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
        if any(isinstance(t, ast.Name) and t.id == "SURFACE"
               for t in targets):
            rows = ast.literal_eval(node.value)
            return tuple(name for name, _kind, _desc in rows
                         if not str(name).startswith("span:"))
    raise RuntimeError(
        "telemetry.SURFACE literal not found in %s"
        % TELEMETRY_SURFACE_FILE)


def _metric_name_parts(node, consts):
    """Decompose a metric-name EXPRESSION into literal/dynamic parts
    (None = dynamic). Handles literals, module-constant Names,
    '+'-concatenation, and '%'-templates; returns (parts,
    is_fstring)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value], False
    if isinstance(node, ast.Name):
        lit = consts.get(node.id)
        return ([lit] if lit is not None else [None]), False
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        lparts, lf = _metric_name_parts(node.left, consts)
        rparts, rf = _metric_name_parts(node.right, consts)
        return lparts + rparts, lf or rf
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mod):
        if isinstance(node.left, ast.Constant) \
                and isinstance(node.left.value, str):
            import re

            frags = re.split(
                r"%[#0\- +]?[0-9]*(?:\.[0-9]+)?[sdifeEgGxXr]",
                node.left.value)
            parts = []
            for i, frag in enumerate(frags):
                if i:
                    parts.append(None)
                if frag:
                    parts.append(frag)
            return (parts or [None]), False
        return [None], False
    if isinstance(node, ast.JoinedStr):
        parts = []
        for v in node.values:
            if isinstance(v, ast.Constant) \
                    and isinstance(v.value, str):
                parts.append(v.value)
            else:
                parts.append(None)
        return (parts or [None]), True
    return [None], False


def _metric_matches_surface(parts, surface_names):
    """True when the emitted name pattern resolves to an inventory
    row. Both sides may carry wildcards (the emit's dynamic parts,
    the inventory's ``<placeholder>`` segments), so the match is
    two-way: the emit pattern against a concretized inventory row
    ('ledger.%s.%s' -> 'ledger.mfu.x'), AND the inventory pattern
    against a concretized emit ('exec.wall_s.<program>' matches the
    literal 'exec.wall_s.decode_token')."""
    import re

    emit_rx = re.compile("".join(
        ".+" if p is None else re.escape(p) for p in parts) + "$")
    emit_probe = "".join("x" if p is None else p for p in parts)
    for name in surface_names:
        if emit_rx.match(re.sub(r"<[^>]+>", "x", name)):
            return True
        surf_rx = re.compile(
            re.sub(r"<[^>]+>", ".+",
                   re.escape(name).replace(r"\<", "<")
                   .replace(r"\>", ">")) + "$")
        if surf_rx.match(emit_probe):
            return True
    return False


class _MetricNameVisitor(ast.NodeVisitor):
    """Flags registry emits (`<registry>.inc/observe/gauge(name,...)`)
    whose name is an f-string, fully dynamic, Prometheus-unsafe, or
    unregistered in telemetry.SURFACE."""

    def __init__(self, relpath, source_lines, surface_names):
        self.relpath = relpath
        self.lines = source_lines
        self.surface = surface_names
        self.violations = []
        self.consts = {}

    def visit_Module(self, node):
        # module-level string constants (EXEC_WALL_PREFIX-style name
        # prefixes) resolve into literal parts
        for stmt in node.body:
            if isinstance(stmt, ast.Assign) \
                    and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name) \
                    and isinstance(stmt.value, ast.Constant) \
                    and isinstance(stmt.value.value, str):
                self.consts[stmt.targets[0].id] = stmt.value.value
        self.generic_visit(node)

    def _waived(self, node) -> bool:
        lo = max(node.lineno - 2, 0)  # the line above counts too
        hi = min(getattr(node, "end_lineno", node.lineno),
                 len(self.lines))
        return any(_METRIC_WAIVER in ln
                   for ln in self.lines[lo:hi])

    def _flag(self, node, what):
        self.violations.append(
            "%s:%d: %s — metric names are registered surface: use a "
            "lowercase [a-z0-9_.] literal (head) registered in "
            "telemetry.SURFACE (+ '+ suffix' / '%%s' templates for "
            "dynamic segments), or waive a deliberately pre-resolved "
            "emit with '%s (<reason>)'"
            % (self.relpath, node.lineno, what, _METRIC_WAIVER))

    def visit_Call(self, node):
        fn = node.func
        if isinstance(fn, ast.Attribute) \
                and fn.attr in _METRIC_EMIT_METHODS and node.args:
            recv = fn.value
            rname = recv.id if isinstance(recv, ast.Name) else (
                recv.attr if isinstance(recv, ast.Attribute)
                else None)
            if rname in _METRIC_RECEIVERS:
                self._check_name(node)
        self.generic_visit(node)

    def _check_name(self, node):
        if self._waived(node):
            return
        parts, is_fstring = _metric_name_parts(node.args[0],
                                               self.consts)
        lits = [p for p in parts if p is not None]
        if is_fstring:
            self._flag(node, "ad-hoc f-string metric name")
            return
        if not lits:
            self._flag(node, "fully dynamic metric name (nothing to "
                       "register or round-trip)")
            return
        for lit in lits:
            bad = set(lit) - _METRIC_NAME_CHARS
            if bad:
                self._flag(node, "metric name part %r fails the "
                           "_prom_name round trip (bad chars %s)"
                           % (lit, "".join(sorted(bad))))
                return
        if parts[0] is None:
            self._flag(node, "metric name has a dynamic namespace "
                       "head (the '<ns>.' prefix must be literal)")
            return
        if parts[0][:1].isdigit():
            self._flag(node, "metric name starts with a digit")
            return
        if not _metric_matches_surface(parts, self.surface):
            shown = "".join("<?>" if p is None else p for p in parts)
            self._flag(node, "metric name %r is not registered in "
                       "telemetry.SURFACE" % shown)


def lint_metric_names_file(path, text=None, surface_names=None):
    """Metric-name audit for one file; returns violations."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    if surface_names is None:
        surface_names = surface_metric_names()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    v = _MetricNameVisitor(rel, text.splitlines(), surface_names)
    v.visit(tree)
    return v.violations


def check_metric_names(root=REPO):
    surface = surface_metric_names(root)
    out = []
    base = os.path.join(root, "paddle_tpu")
    for dirpath, dirnames, filenames in os.walk(base):
        dirnames[:] = [d for d in sorted(dirnames)
                       if d != "__pycache__"]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                out.extend(lint_metric_names_file(
                    os.path.join(dirpath, fn),
                    surface_names=surface))
    return out


FLAGS_FILE = os.path.join("paddle_tpu", "framework", "flags.py")
FLAG_DOCS_DIR = "docs"


def _defined_flags(text, relpath=FLAGS_FILE):
    """(name, help_str, lineno) for every top-level define_flag call
    in the flags module source (help_str None = missing arg)."""
    tree = ast.parse(text, filename=relpath)
    out = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "define_flag"):
            continue
        if not node.args or not isinstance(node.args[0], ast.Constant):
            continue
        name = node.args[0].value
        help_str = None
        if len(node.args) >= 3 and isinstance(node.args[2],
                                              ast.Constant):
            help_str = node.args[2].value
        for kw in node.keywords:
            if kw.arg == "help_str" and isinstance(kw.value,
                                                   ast.Constant):
                help_str = kw.value.value
        out.append((name, help_str, node.lineno))
    return out


def lint_flag_inventory(flags_text, docs_text, relpath=FLAGS_FILE):
    """Flag-inventory check over given sources (testable without the
    repo): ``docs_text`` is the concatenated documentation corpus a
    FLAGS_<name> mention must appear in."""
    import re

    out = []
    for name, help_str, lineno in _defined_flags(flags_text, relpath):
        if not (help_str or "").strip():
            out.append(
                "%s:%d: FLAGS_%s has no docstring — every flag needs "
                "a help string explaining what it does and what reads "
                "it (define_flag's third argument)"
                % (relpath, lineno, name))
        # word-boundary match: FLAGS_jit_plan must not be satisfied
        # by a mention of FLAGS_jit_plan_comm_bound_ratio (the repo
        # has many prefix-colliding flag families)
        if not re.search(r"FLAGS_%s\b" % re.escape(name), docs_text):
            out.append(
                "%s:%d: FLAGS_%s is not mentioned anywhere under "
                "docs/ — add it to the flag reference (docs/FLAGS.md) "
                "or the feature's doc page"
                % (relpath, lineno, name))
    return out


def check_flag_inventory(root=REPO):
    with open(os.path.join(root, FLAGS_FILE), encoding="utf-8") as f:
        flags_text = f.read()
    docs_text = []
    docs_dir = os.path.join(root, FLAG_DOCS_DIR)
    for fn in sorted(os.listdir(docs_dir)):
        if fn.endswith(".md"):
            with open(os.path.join(docs_dir, fn),
                      encoding="utf-8") as f:
                docs_text.append(f.read())
    return lint_flag_inventory(flags_text, "\n".join(docs_text))


def check_inference_surface():
    """No raw jax callable may leak through the public
    ``paddle_tpu.inference`` namespace (same leak rule the op
    namespaces get, without requiring op-table registration — the
    serving surface exports classes and factories, not ops)."""
    import importlib
    import inspect

    out = []
    mod = importlib.import_module("paddle_tpu.inference")
    for rawname in getattr(mod, "__all__", dir(mod)):
        if rawname.startswith("_"):
            continue
        fn = getattr(mod, rawname, None)
        if fn is None or not callable(fn) or inspect.isclass(fn):
            continue
        if getattr(fn, "__module__", "").startswith("jax"):
            out.append(
                "paddle_tpu.inference.%s: public serving namespace "
                "leaks a raw jax callable (%s) — wrap it or "
                "underscore-prefix the import"
                % (rawname, getattr(fn, "__module__", "?")))
    return out


def check_op_table():
    """Public callables in the op namespaces must resolve in the
    registry; undeclared (guessed-metadata) registry entries are also
    flagged (same contract the op-suite enforces, surfaced here with
    module + nearest-neighbor hints for new-op authors)."""
    import inspect

    from paddle_tpu.ops import op_table

    op_table._populate()
    out = []
    mods = [
        ("paddle_tpu.tensor.math", ""),
        ("paddle_tpu.tensor.manipulation", ""),
        ("paddle_tpu.tensor.creation", ""),
        ("paddle_tpu.tensor.linalg", ""),
        ("paddle_tpu.tensor.logic", ""),
        ("paddle_tpu.tensor.search", ""),
        ("paddle_tpu.tensor.stat", ""),
        ("paddle_tpu.nn.functional", ""),
        ("paddle_tpu.sparse", "sparse_"),
    ]
    import importlib

    for modname, prefix in mods:
        mod = importlib.import_module(modname)
        for rawname in dir(mod):
            if rawname.startswith("_") or rawname in op_table._NOT_OPS:
                continue
            fn = getattr(mod, rawname)
            if not callable(fn) or inspect.isclass(fn):
                continue
            name = prefix + rawname
            if getattr(fn, "__module__", "").startswith("jax"):
                out.append(
                    "%s.%s: public op namespace leaks a raw jax "
                    "callable (%s) — wrap it or underscore-prefix the "
                    "import" % (modname, rawname,
                                getattr(fn, "__module__", "?")))
                continue
            if op_table.get_op(name) is None:
                near = op_table.nearest_registered(name)
                out.append(
                    "%s.%s: public op missing from op_table registry"
                    "%s" % (modname, rawname,
                            " (nearest: %r)" % near if near else ""))
    for name in op_table.undeclared_ops():
        out.append("op_table: %r carries guessed (dir()-walk) metadata "
                   "— declare it in _DECL_GROUPS or waive it:\n%s"
                   % (name, op_table.describe_ops([name])))
    return out


# concurrency lock discipline (the static half of framework/
# concurrency.py — the runtime race sanitizer is the dynamic half;
# docs/ANALYSIS.md "Concurrency"). Four rules over the concurrency-
# bearing host-plane modules:
#   * concurrency-guarded-by — module-level mutable shared state
#     (rebound via `global`, or mutated in place from function
#     bodies) must declare its guard with a trailing
#     `# guarded-by: <lock>` or waive with
#     `# concurrency: single-writer`;
#   * concurrency-lock-order — the statically-visible lock
#     acquisition order (nested `with <lock>:` blocks) must form a
#     DAG across ALL the checked files — a cycle is a potential
#     deadlock, the AST-level twin of the sanitizer's
#     lock-order-inversion class;
#   * concurrency-blocking-async — no time.sleep, blocking lock
#     acquire, or blocking IO inside `async def` (checked repo-wide:
#     one blocking call stalls every task on the loop — the static
#     twin of blocking-acquire-on-loop);
#   * concurrency-thread-discipline — host-plane modules create
#     threads only through concurrency.spawn_thread (named, daemon,
#     sanitizer-registered) — never raw threading.Thread.

CONCURRENCY_FILES = (
    os.path.join("paddle_tpu", "framework", "telemetry.py"),
    os.path.join("paddle_tpu", "framework", "ops_server.py"),
    os.path.join("paddle_tpu", "framework", "flight_recorder.py"),
    os.path.join("paddle_tpu", "framework", "concurrency.py"),
    os.path.join("paddle_tpu", "inference", "serving.py"),
    os.path.join("paddle_tpu", "incubate", "nn", "paged_cache.py"),
)

# thread creation is checked over the concurrency files plus the rest
# of the host observability plane (concurrency.py itself hosts the
# sanctioned helper and is exempt)
THREAD_DISCIPLINE_FILES = tuple(
    f for f in CONCURRENCY_FILES
    if not f.endswith("concurrency.py")) + (
    os.path.join("paddle_tpu", "framework", "watchdog.py"),
    os.path.join("paddle_tpu", "framework", "perf_ledger.py"),
)

_GUARD_MARKS = ("# guarded-by:", "# concurrency: single-writer")

_MUTABLE_CTORS = {"deque", "Counter", "defaultdict", "OrderedDict",
                  "dict", "list", "set"}
_MUTATOR_ATTRS = {"append", "appendleft", "add", "insert", "extend",
                  "update", "pop", "popleft", "popitem", "remove",
                  "discard", "clear", "setdefault"}


def _is_mutable_value(node):
    if isinstance(node, (ast.Dict, ast.List, ast.Set, ast.ListComp,
                         ast.DictComp, ast.SetComp)):
        return True
    if isinstance(node, ast.Call):
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id in _MUTABLE_CTORS:
            return True
        if isinstance(fn, ast.Attribute) and fn.attr in _MUTABLE_CTORS:
            return True
    return False


def _has_guard_mark(lines, lineno):
    line = lines[lineno - 1] if lineno - 1 < len(lines) else ""
    return any(m in line for m in _GUARD_MARKS) \
        or _WAIVER_MARK in line


class _SharedStateVisitor(ast.NodeVisitor):
    """Collects module-level mutable names and how function bodies
    touch them: `global` rebinding, subscript stores, and mutating
    method calls."""

    def __init__(self):
        self.module_assign = {}   # name -> first top-level def line
        self.module_mutable = {}  # name -> def line (mutable value)
        self.rebound = {}         # name -> lineno of global stmt
        self.mutated = {}         # name -> lineno of in-place write
        self._depth = 0
        self._globals = set()

    def visit_Module(self, node):
        for stmt in node.body:
            targets = []
            if isinstance(stmt, ast.Assign):
                targets = stmt.targets
                value = stmt.value
            elif isinstance(stmt, ast.AnnAssign) and stmt.value:
                targets = [stmt.target]
                value = stmt.value
            else:
                continue
            for t in targets:
                if isinstance(t, ast.Name):
                    self.module_assign.setdefault(t.id, stmt.lineno)
                    if _is_mutable_value(value):
                        self.module_mutable.setdefault(
                            t.id, stmt.lineno)
        self.generic_visit(node)

    def visit_FunctionDef(self, node):
        outer = self._globals
        self._depth += 1
        if self._depth == 1:
            self._globals = set()
        self.generic_visit(node)
        self._depth -= 1
        if self._depth == 0:
            self._globals = outer

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Global(self, node):
        if self._depth:
            self._globals.update(node.names)
        self.generic_visit(node)

    def _note_store(self, target, lineno):
        # <name> = ... under a `global` declaration -> rebinding;
        # <name>[...] = ... -> in-place mutation of module state
        if isinstance(target, ast.Name) and self._depth \
                and target.id in self._globals:
            self.rebound.setdefault(target.id, lineno)
        if isinstance(target, ast.Subscript) \
                and isinstance(target.value, ast.Name) and self._depth:
            self.mutated.setdefault(target.value.id, lineno)

    def visit_Assign(self, node):
        if self._depth:
            for t in node.targets:
                self._note_store(t, node.lineno)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        if self._depth:
            self._note_store(node.target, node.lineno)
        self.generic_visit(node)

    def visit_Call(self, node):
        fn = node.func
        if self._depth and isinstance(fn, ast.Attribute) \
                and isinstance(fn.value, ast.Name) \
                and fn.attr in _MUTATOR_ATTRS:
            self.mutated.setdefault(fn.value.id, node.lineno)
        self.generic_visit(node)


def lint_guarded_by_file(path, text=None):
    """GuardedBy declarations on module-level shared state for one
    file; returns violation strings."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    v = _SharedStateVisitor()
    v.visit(tree)
    lines = text.splitlines()
    out = []
    shared = {}
    for name, lineno in v.rebound.items():
        shared[name] = v.module_assign.get(name, lineno)
    for name, lineno in v.mutated.items():
        if name in v.module_mutable:
            shared.setdefault(name, v.module_mutable[name])
    for name in sorted(shared):
        lineno = shared[name]
        if not _has_guard_mark(lines, lineno):
            out.append(
                "%s:%d: module-level shared attribute %r is mutated "
                "from function bodies but declares no guard — add a "
                "trailing '# guarded-by: <lock>' (and hold that lock "
                "at every write) or waive with "
                "'# concurrency: single-writer' (one writer thread "
                "by contract); the runtime half is "
                "framework/concurrency.py" % (rel, lineno, name))
    return out


def check_guarded_by(root=REPO):
    out = []
    for f in CONCURRENCY_FILES:
        out.extend(lint_guarded_by_file(os.path.join(root, f)))
    return out


def _is_lockish(expr):
    """Name/attribute heuristic for lock objects in `with` items."""
    if isinstance(expr, ast.Attribute):
        n = expr.attr
    elif isinstance(expr, ast.Name):
        n = expr.id
    else:
        return None
    low = n.lower()
    if "lock" in low or low == "_mu" or low.endswith("_mutex"):
        return n
    return None


class _LockOrderVisitor(ast.NodeVisitor):
    """Collects statically-visible acquisition edges: `with A:`
    lexically containing `with B:` (or `with A, B:`) yields edge
    A -> B. Canonical lock names come from `= guarded("name")`
    assignments where resolvable, else <module-stem>.<attr>."""

    def __init__(self, relpath, stem, canon):
        self.relpath = relpath
        self.stem = stem
        self.canon = canon  # raw attr/name -> canonical name
        self.edges = []     # (src, dst, lineno)
        self._held = []

    def _canonical(self, raw):
        return self.canon.get(raw, "%s.%s" % (self.stem, raw))

    def visit_With(self, node):
        acquired = []
        for item in node.items:
            raw = _is_lockish(item.context_expr)
            if raw is not None:
                name = self._canonical(raw)
                for held in self._held + acquired:
                    if held != name:
                        self.edges.append((held, name, node.lineno))
                acquired.append(name)
        self._held.extend(acquired)
        self.generic_visit(node)
        for _ in acquired:
            self._held.pop()

    visit_AsyncWith = visit_With

    def visit_FunctionDef(self, node):
        # a nested def runs later, not under the enclosing `with`
        held, self._held = self._held, []
        self.generic_visit(node)
        self._held = held

    visit_AsyncFunctionDef = visit_FunctionDef


def _lock_canon_map(tree):
    """raw attr/name -> canonical sanitizer lock name, from
    `<target> = [mod.]guarded("name", ...)` assignments."""
    canon = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        call = node.value
        if not isinstance(call, ast.Call):
            continue
        fn = call.func
        fname = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None)
        if fname != "guarded" or not call.args:
            continue
        arg = call.args[0]
        if not (isinstance(arg, ast.Constant)
                and isinstance(arg.value, str)):
            continue
        for t in node.targets:
            if isinstance(t, ast.Attribute):
                canon[t.attr] = arg.value
            elif isinstance(t, ast.Name):
                canon[t.id] = arg.value
    return canon


def _lock_order_edges(path, text=None):
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return [], ["%s: syntax error during lint: %s" % (rel, e)]
    stem = os.path.splitext(os.path.basename(rel))[0]
    v = _LockOrderVisitor(rel, stem, _lock_canon_map(tree))
    v.visit(tree)
    return [(src, dst, rel, lineno) for src, dst, lineno in v.edges], []


def _lock_order_violations(edges):
    """Cycle check over the merged acquisition digraph: an edge
    (u, v) whose reverse is reachable through OTHER edges closes a
    cycle — both orders exist somewhere, a potential deadlock."""
    graph = {}
    for src, dst, rel, lineno in edges:
        graph.setdefault(src, set()).add(dst)
    out = []
    seen_pairs = set()
    for src, dst, rel, lineno in edges:
        # the edge src -> dst closes a cycle iff dst reaches src
        stack, visited = [dst], set()
        found = False
        while stack:
            n = stack.pop()
            if n == src:
                found = True
                break
            if n in visited:
                continue
            visited.add(n)
            stack.extend(graph.get(n, ()))
        key = tuple(sorted((src, dst)))
        if found and key not in seen_pairs:
            seen_pairs.add(key)
            out.append(
                "%s:%d: lock-order inversion: %r is acquired while "
                "holding %r here, but another code path acquires "
                "them in the opposite order — the declared "
                "acquisition order must be a DAG (potential "
                "deadlock; the runtime twin is the sanitizer's "
                "lock-order-inversion class)"
                % (rel, lineno, dst, src))
    return out


def lint_lock_order_file(path, text=None):
    """Per-file lock-order DAG check; returns violation strings."""
    edges, errs = _lock_order_edges(path, text)
    return errs + _lock_order_violations(edges)


def check_lock_order(root=REPO):
    edges, out = [], []
    for f in CONCURRENCY_FILES:
        e, errs = _lock_order_edges(os.path.join(root, f))
        edges.extend(e)
        out.extend(errs)
    out.extend(_lock_order_violations(edges))
    return out


_BLOCKING_IO_CALLS = {
    ("time", "sleep"): "time.sleep",
    ("os", "system"): "os.system",
    ("subprocess", "run"): "subprocess.run",
    ("subprocess", "call"): "subprocess.call",
    ("subprocess", "check_call"): "subprocess.check_call",
    ("subprocess", "check_output"): "subprocess.check_output",
    ("subprocess", "Popen"): "subprocess.Popen",
}


def _acquire_is_nonblocking(node):
    """True when an .acquire(...) call is explicitly non-blocking:
    blocking=False / timeout=0 keywords or a literal False/0 first
    positional."""
    for kw in node.keywords:
        if kw.arg == "blocking" and isinstance(kw.value, ast.Constant) \
                and kw.value.value is False:
            return True
        if kw.arg == "timeout" and isinstance(kw.value, ast.Constant) \
                and kw.value.value == 0:
            return True
    if node.args and isinstance(node.args[0], ast.Constant) \
            and node.args[0].value is False:
        return True
    return False


class _BlockingAsyncVisitor(ast.NodeVisitor):
    """Flags blocking calls lexically inside `async def` bodies."""

    def __init__(self, relpath, source_lines):
        self.relpath = relpath
        self.lines = source_lines
        self.violations = []
        self._async_depth = 0

    def _flag(self, lineno, what):
        line = self.lines[lineno - 1] \
            if lineno - 1 < len(self.lines) else ""
        if _WAIVER_MARK not in line:
            self.violations.append(
                "%s:%d: %s inside `async def` — a blocking call "
                "stalls EVERY task on the event loop (the sanitizer's "
                "blocking-acquire-on-loop class, statically); hop to "
                "an executor, use the async primitive, or waive with "
                "'%s(<reason>)'"
                % (self.relpath, lineno, what, _WAIVER_MARK))

    def visit_AsyncFunctionDef(self, node):
        self._async_depth += 1
        self.generic_visit(node)
        self._async_depth -= 1

    def visit_FunctionDef(self, node):
        # a sync helper DEFINED inside an async def runs wherever it
        # is called — do not blame the enclosing coroutine
        depth, self._async_depth = self._async_depth, 0
        self.generic_visit(node)
        self._async_depth = depth

    def visit_Call(self, node):
        if self._async_depth:
            dotted = _dotted_head(node)
            if dotted in _BLOCKING_IO_CALLS:
                self._flag(node.lineno,
                           "%s()" % _BLOCKING_IO_CALLS[dotted])
            fn = node.func
            if isinstance(fn, ast.Attribute) and fn.attr == "acquire" \
                    and not _acquire_is_nonblocking(node):
                self._flag(node.lineno, "blocking .acquire()")
            if isinstance(fn, ast.Name) and fn.id == "open":
                self._flag(node.lineno, "open() file IO")
        self.generic_visit(node)


def lint_blocking_async_file(path, text=None):
    """Blocking-in-async check for one file; returns violations."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    v = _BlockingAsyncVisitor(rel, text.splitlines())
    v.visit(tree)
    return v.violations


def check_blocking_async(root=REPO):
    """Repo-wide: async defs are rare and every one matters."""
    out = []
    pkg = os.path.join(root, "paddle_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in sorted(dirnames)
                       if d != "__pycache__"]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                out.extend(lint_blocking_async_file(
                    os.path.join(dirpath, fn)))
    return out


class _ThreadDisciplineVisitor(ast.NodeVisitor):
    """Flags raw thread construction: threading.Thread(...) or a
    bare Thread(...) imported from threading."""

    def __init__(self, relpath, source_lines):
        self.relpath = relpath
        self.lines = source_lines
        self.violations = []
        self._thread_aliases = {"Thread"}

    def visit_ImportFrom(self, node):
        if (node.module or "") == "threading":
            for a in node.names:
                if a.name == "Thread":
                    self._thread_aliases.add(a.asname or a.name)
        self.generic_visit(node)

    def _flag(self, lineno, what):
        line = self.lines[lineno - 1] \
            if lineno - 1 < len(self.lines) else ""
        if _WAIVER_MARK not in line:
            self.violations.append(
                "%s:%d: %s in a host-plane module — threads are "
                "created ONLY through concurrency.spawn_thread "
                "(named, daemon, sanitizer-registered with a "
                "parent->child happens-before edge); or waive with "
                "'%s(<reason>)'"
                % (self.relpath, lineno, what, _WAIVER_MARK))

    def visit_Call(self, node):
        fn = node.func
        dotted = _dotted_head(node)
        if dotted is not None and dotted[0] == "threading" \
                and dotted[1] == "Thread":
            self._flag(node.lineno, "raw threading.Thread(...)")
        elif isinstance(fn, ast.Name) \
                and fn.id in self._thread_aliases:
            self._flag(node.lineno, "raw %s(...)" % fn.id)
        self.generic_visit(node)


def lint_thread_discipline_file(path, text=None):
    """Thread-discipline check for one file; returns violations."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    v = _ThreadDisciplineVisitor(rel, text.splitlines())
    v.visit(tree)
    return v.violations


def check_thread_discipline(root=REPO):
    out = []
    for f in THREAD_DISCIPLINE_FILES:
        out.extend(lint_thread_discipline_file(os.path.join(root, f)))
    return out


# the async serving engine's own discipline: the scheduler registers
# its queue/state as SINGLE-WRITER shared vars, so every
# scheduler.step() in engine.py must come from the pump thread's
# functions (def _pump_*) — a step from submit()/a handler/a helper
# is the exact multi-writer hazard the engine exists to prevent
ENGINE_FILE = "paddle_tpu/inference/engine.py"


class _EngineStepVisitor(ast.NodeVisitor):
    """Flags ``<x>.step(...)`` calls outside ``_pump*`` functions."""

    def __init__(self, relpath, source_lines):
        self.relpath = relpath
        self.lines = source_lines
        self.violations = []
        self._func_stack = []

    def _in_pump(self):
        return any(n.startswith("_pump") for n in self._func_stack)

    def visit_FunctionDef(self, node):
        self._func_stack.append(node.name)
        self.generic_visit(node)
        self._func_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Call(self, node):
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr == "step" \
                and not self._in_pump():
            line = self.lines[node.lineno - 1] \
                if node.lineno - 1 < len(self.lines) else ""
            if _WAIVER_MARK not in line:
                self.violations.append(
                    "%s:%d: scheduler.step() outside a _pump* "
                    "function — the scheduler's queue/state are "
                    "single-writer shared vars owned by the pump "
                    "thread; stepping from anywhere else is a "
                    "multi-writer race (marshal an op to the pump "
                    "instead, or waive with '%s(<reason>)')"
                    % (self.relpath, node.lineno, _WAIVER_MARK))
        self.generic_visit(node)


def lint_engine_discipline_file(path, text=None):
    """Engine-discipline check for one file: the step-only-in-pump
    rule plus the thread-discipline and guarded-by rules (the engine
    is a host-plane module but is owned by this composite rule, not
    the CONCURRENCY_FILES lists, so each finding is reported once)."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    v = _EngineStepVisitor(rel, text.splitlines())
    v.visit(tree)
    out = list(v.violations)
    out.extend(lint_thread_discipline_file(path, text))
    out.extend(lint_guarded_by_file(path, text))
    return out


def check_engine_discipline(root=REPO):
    path = os.path.join(root, ENGINE_FILE)
    if not os.path.exists(path):
        return []
    return lint_engine_discipline_file(path)


# disaggregated role discipline: in the role-split modules, code
# whose enclosing scope is prefill-role (a class or function with
# "prefill" in its name) must never call the decode-only restore
# surface — a prefill worker that swaps a chain back IN (or adopts a
# foreign one) collapses the role split and double-materializes the
# KV pages the decode worker is about to import
ROLE_DISCIPLINE_FILES = (
    os.path.join("paddle_tpu", "inference", "disagg.py"),
)

# the decode-only half of the pool/scheduler/engine surface: restore
# and adoption entry points (export_seq/export_request/swap_out stay
# prefill-legal — they are the handoff itself)
_ROLE_DECODE_ONLY = (
    "swap_in", "import_seq", "adopt_swapped", "adopt",
)


class _RoleDisciplineVisitor(ast.NodeVisitor):
    """Flags decode-only API calls from prefill-role scopes."""

    def __init__(self, relpath, source_lines):
        self.relpath = relpath
        self.lines = source_lines
        self.violations = []
        self._scope_stack = []

    def _in_prefill_scope(self):
        return any("prefill" in n.lower() for n in self._scope_stack)

    def _push(self, node):
        self._scope_stack.append(node.name)
        self.generic_visit(node)
        self._scope_stack.pop()

    visit_FunctionDef = _push
    visit_AsyncFunctionDef = _push
    visit_ClassDef = _push

    def visit_Call(self, node):
        fn = node.func
        name = fn.attr if isinstance(fn, ast.Attribute) \
            else (fn.id if isinstance(fn, ast.Name) else None)
        if name in _ROLE_DECODE_ONLY and self._in_prefill_scope():
            line = self.lines[node.lineno - 1] \
                if node.lineno - 1 < len(self.lines) else ""
            if _WAIVER_MARK not in line:
                self.violations.append(
                    "%s:%d: prefill-role scope calls decode-only "
                    ".%s() — the restore/adoption surface belongs to "
                    "the decode role (a prefill worker re-importing "
                    "a chain collapses the role split and double-"
                    "materializes pages); move it to a decode-role "
                    "scope or waive with '%s(<reason>)'"
                    % (self.relpath, node.lineno, name, _WAIVER_MARK))
        self.generic_visit(node)


def lint_role_discipline_file(path, text=None):
    """Role-discipline check for one file; returns violations."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    v = _RoleDisciplineVisitor(rel, text.splitlines())
    v.visit(tree)
    return v.violations


def check_role_discipline(root=REPO):
    out = []
    for f in ROLE_DISCIPLINE_FILES:
        path = os.path.join(root, f)
        if os.path.exists(path):
            out.extend(lint_role_discipline_file(path))
    return out


# capacity knob discipline: the serving-layer modules must never
# mutate the capacity flags (or poke the scheduler's capacity attrs)
# directly — every change funnels through the autotuner apply seam
# (framework/autotuner.py apply_config -> scheduler
# apply_capacity_config -> engine _pump_tune), which is the only
# path that guarantees step-boundary application, flag/attr
# coherence, and the knob-discipline audit trail
# (autotune.applies). A mid-step set_flags("prefill_chunk_tokens")
# would desynchronize the packed feed being built; an ad-hoc
# `sched.serving_buckets = ...` skips the bucket re-parse and the
# boundary guard.
KNOB_DISCIPLINE_FILES = (
    os.path.join("paddle_tpu", "inference", "serving.py"),
    os.path.join("paddle_tpu", "inference", "engine.py"),
    os.path.join("paddle_tpu", "inference", "disagg.py"),
    os.path.join("paddle_tpu", "inference", "paged_llama.py"),
    os.path.join("paddle_tpu", "inference", "prefix_cache.py"),
    os.path.join("paddle_tpu", "framework", "ops_server.py"),
)

# the tuner-owned capacity flags (autotuner.CAPACITY_KNOBS — kept as
# literals here so the linter never imports the package under lint)
_CAPACITY_FLAGS = frozenset({
    "prefill_chunk_tokens", "serving_buckets", "serving_swap_bytes",
    "collective_dtype", "engine_goodput_low", "engine_goodput_high",
})
# scheduler-instance capacity attrs: stores allowed only in the
# sanctioned seam functions below (construction reads the flags;
# apply_capacity_config is the boundary-guarded mutator; the engine
# pump op marshals onto it)
_CAPACITY_ATTRS = frozenset({
    "prefill_chunk_tokens", "serving_buckets",
})
_KNOB_SEAM_FUNCS = frozenset({
    "__init__", "apply_capacity_config", "_pump_tune",
})


class _KnobDisciplineVisitor(ast.NodeVisitor):
    """Flags capacity-flag set_flags() calls and capacity-attr
    stores outside the autotuner apply seam."""

    def __init__(self, relpath, source_lines):
        self.relpath = relpath
        self.lines = source_lines
        self.violations = []
        self._func_stack = []

    def _waived(self, lineno):
        line = self.lines[lineno - 1] \
            if lineno - 1 < len(self.lines) else ""
        return _WAIVER_MARK in line

    def _push(self, node):
        self._func_stack.append(node.name)
        self.generic_visit(node)
        self._func_stack.pop()

    visit_FunctionDef = _push
    visit_AsyncFunctionDef = _push

    def visit_Call(self, node):
        fn = node.func
        name = fn.attr if isinstance(fn, ast.Attribute) \
            else (fn.id if isinstance(fn, ast.Name) else None)
        if name == "set_flags" and node.args:
            d = node.args[0]
            keys = set()
            if isinstance(d, ast.Dict):
                keys = {k.value for k in d.keys
                        if isinstance(k, ast.Constant)
                        and isinstance(k.value, str)}
            bad = sorted(keys & _CAPACITY_FLAGS)
            if bad and not self._waived(node.lineno):
                self.violations.append(
                    "%s:%d: set_flags(%s) mutates capacity knob(s) "
                    "outside the autotuner apply seam — route "
                    "through framework.autotuner.apply_config (or "
                    "ServingEngine.apply_config for a live engine) "
                    "so the change lands at a step boundary, or "
                    "waive with '%s(<reason>)'"
                    % (self.relpath, node.lineno, ", ".join(bad),
                       _WAIVER_MARK))
        self.generic_visit(node)

    def visit_Assign(self, node):
        for tgt in node.targets:
            self._check_store(tgt, node.lineno)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        self._check_store(node.target, node.lineno)
        self.generic_visit(node)

    def _check_store(self, tgt, lineno):
        if not isinstance(tgt, ast.Attribute):
            return
        if tgt.attr not in _CAPACITY_ATTRS:
            return
        if self._func_stack \
                and self._func_stack[-1] in _KNOB_SEAM_FUNCS:
            return
        if self._waived(lineno):
            return
        self.violations.append(
            "%s:%d: direct store to .%s outside the capacity apply "
            "seam (%s) — an ad-hoc capacity poke skips the "
            "step-boundary guard and the bucket re-parse; call "
            "scheduler.apply_capacity_config (via "
            "framework.autotuner.apply_config) instead, or waive "
            "with '%s(<reason>)'"
            % (self.relpath, lineno, tgt.attr,
               "/".join(sorted(_KNOB_SEAM_FUNCS)), _WAIVER_MARK))


def lint_knob_discipline_file(path, text=None):
    """Knob-discipline check for one file; returns violations."""
    if text is None:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    rel = os.path.relpath(path, REPO) if os.path.isabs(path) else path
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        return ["%s: syntax error during lint: %s" % (rel, e)]
    v = _KnobDisciplineVisitor(rel, text.splitlines())
    v.visit(tree)
    return v.violations


def check_knob_discipline(root=REPO):
    out = []
    for f in KNOB_DISCIPLINE_FILES:
        path = os.path.join(root, f)
        if os.path.exists(path):
            out.extend(lint_knob_discipline_file(path))
    return out


# rule inventory: (rule id, one-line summary) for every AST check in
# this linter — merged into `python -m paddle_tpu.framework.analysis
# --rules` alongside the jaxpr rules and the page-sanitizer violation
# classes, so one CLI lists every static check in the repo
RULES = (
    ("traced-path-hygiene",
     "no host syncs (device_get / np.asarray / time.time) in modules "
     "whose code runs inside jit traces"),
    ("op-table-coverage",
     "public op-namespace callables must resolve in the op_table "
     "registry; no raw jax callables leaking through"),
    ("host-only-hygiene",
     "declared host-only modules (prefix_cache.py, framework/"
     "telemetry.py, framework/watchdog.py, framework/perf_ledger.py, "
     "framework/flight_recorder.py) must not touch jax/jnp at all"),
    ("watchdog-read-only",
     "watchdog/detector, incident-recorder AND live-ops-server code "
     "(framework/watchdog.py, framework/flight_recorder.py, "
     "framework/ops_server.py) may only READ the telemetry registry "
     "— no registry mutators (inc/gauge/observe/set_epoch), no "
     "pool-private calls, no pool state writes"),
    ("bundle-atomicity",
     "incident-bundle writers (framework/flight_recorder.py) may not "
     "open files in write/append mode directly — every member goes "
     "through telemetry.atomic_write_text (tmp + rename), so a "
     "reader never sees a torn evidence file"),
    ("clock-discipline",
     "no direct time.time/perf_counter reads in serving.py/"
     "paged_cache.py/prefix_cache.py — telemetry spans/clock() are "
     "the single timing path"),
    ("inference-surface-leak",
     "no raw jax callable through the public paddle_tpu.inference "
     "namespace"),
    ("quant-sidecar-ownership",
     "serving code must never write the int8 KV scale sidecars "
     "(k_scales/v_scales are pool-private calibration state)"),
    ("pool-mutation-audit",
     "PagedKVCacheManager state (k_pages/v_pages/k_scales/v_scales/"
     "_refcnt/_free/_tables/_lens/_ext_refs), the host swap "
     "tier's store (_swap_store/_swap_used) AND the sharded-pool "
     "geometry (kv_heads_global/head_start/mp_size/mp_rank) are "
     "writable only inside the pool module — everything else goes "
     "through the sanitizer-instrumented public API"),
    ("pool-private-api",
     "serving.py/prefix_cache.py/paged_llama.py/disagg.py may only "
     "call the public audited pool API — no pool-private underscore "
     "methods or bookkeeping attrs"),
    ("serving-bucket-discipline",
     "every prefill_chunk feed must be padded via "
     "bucket_packed_tokens (bounded XLA compile count)"),
    ("unified-attention",
     "packed-step attention in serving.py/paged_llama.py routes "
     "through the single attend_ragged/layer_step pool API "
     "(one attend program per packed config): a ragged append's "
     "function must attend unified in-scope"),
    ("serving-terminal-trace",
     "any serving.py function that moves a request to a terminal "
     "state (FINISHED/ABORTED_DEADLINE or a _finished[] write) must "
     "emit the terminal request-trace event (_traces.complete) in "
     "the same function — no request is ever dropped silently"),
    ("flag-inventory",
     "every FLAGS_* defined in framework/flags.py must carry a "
     "non-empty docstring and be mentioned (FLAGS_<name>) somewhere "
     "under docs/ (docs/FLAGS.md is the catch-all reference)"),
    ("jax-only-kernel-imports",
     "collective-matmul kernel module must not import host-side "
     "modules"),
    ("tp-collective-routing",
     "no hand-rolled raw collective + matmul pair in the TP/SP layer "
     "modules — route through collective_matmul_dispatch"),
    ("metric-name-discipline",
     "every metric name emitted into the telemetry registry "
     "(<registry>.inc/observe/gauge) must be a Prometheus-safe "
     "lowercase literal (surviving telemetry._prom_name unchanged "
     "modulo dots) registered in the central telemetry.SURFACE "
     "inventory — no ad-hoc f-string metric names; dynamic "
     "segments match the inventory's <placeholder> rows"),
    ("wire-quant-ownership",
     "no raw int8/fp8 dtype cast next to a raw collective in the "
     "TP/SP layer modules, the DP grad-sync helper, or the MoE layer "
     "— quantize-on-the-wire (FLAGS_collective_dtype) lives only in "
     "ops/kernels/collective_matmul.py (block scales, custom-VJP "
     "cotangent rings, planner-exact wire bytes)"),
    ("concurrency-guarded-by",
     "module-level mutable shared state in the concurrency-bearing "
     "host-plane modules (telemetry.py, ops_server.py, "
     "flight_recorder.py, concurrency.py, serving.py, "
     "paged_cache.py) must declare its guard with a trailing "
     "'# guarded-by: <lock>' or waive with "
     "'# concurrency: single-writer'"),
    ("concurrency-lock-order",
     "the statically-visible lock acquisition order (nested "
     "'with <lock>:' blocks, merged across the concurrency files) "
     "must be a DAG — a cycle is a potential deadlock (the AST twin "
     "of the sanitizer's lock-order-inversion class)"),
    ("concurrency-blocking-async",
     "no time.sleep / blocking .acquire() / blocking IO (open, "
     "os.system, subprocess.*) inside 'async def', repo-wide — one "
     "blocking call stalls every task on the event loop (the static "
     "twin of blocking-acquire-on-loop)"),
    ("concurrency-thread-discipline",
     "host-plane modules create threads only through "
     "concurrency.spawn_thread (named daemon threads, "
     "sanitizer-registered with a parent->child happens-before "
     "edge) — never raw threading.Thread"),
    ("engine-discipline",
     "inference/engine.py: scheduler.step() is called ONLY from "
     "pump-thread functions (def _pump_*) — anywhere else breaks "
     "the scheduler's single-writer contract; plus the thread-"
     "discipline (spawn_thread only) and guarded-by (module state "
     "declares its guard) rules applied to the engine module"),
    ("disagg-role-discipline",
     "in the disaggregated role-split modules (inference/disagg.py) "
     "prefill-role scopes (classes/functions named *prefill*) must "
     "never call the decode-only restore surface (swap_in / "
     "import_seq / adopt_swapped / adopt) — a prefill worker "
     "re-importing a chain collapses the role split"),
    ("knob-discipline",
     "the serving-layer modules must not mutate capacity flags "
     "(set_flags with prefill_chunk_tokens / serving_buckets / "
     "serving_swap_bytes / collective_dtype / engine_goodput_*) or "
     "poke scheduler capacity attrs directly — every change routes "
     "through the autotuner apply seam "
     "(framework/autotuner.py apply_config -> "
     "BatchScheduler.apply_capacity_config, step-boundary only)"),
)


def run_lint(root=REPO, with_op_table=True):
    out = check_traced_paths(root)
    out.extend(check_host_only(root))
    out.extend(check_clock_discipline(root))
    out.extend(check_watchdog_readonly(root))
    out.extend(check_bundle_atomicity(root))
    out.extend(check_quant_sidecar_writes(root))
    out.extend(check_pool_mutation_audit(root))
    out.extend(check_serving_buckets(root))
    out.extend(check_unified_attention(root))
    out.extend(check_serving_terminal_trace(root))
    out.extend(check_flag_inventory(root))
    out.extend(check_metric_names(root))
    out.extend(check_jax_only(root))
    out.extend(check_tp_routing(root))
    out.extend(check_wire_quant(root))
    out.extend(check_guarded_by(root))
    out.extend(check_lock_order(root))
    out.extend(check_blocking_async(root))
    out.extend(check_thread_discipline(root))
    out.extend(check_engine_discipline(root))
    out.extend(check_role_discipline(root))
    out.extend(check_knob_discipline(root))
    if with_op_table:
        out.extend(check_op_table())
        out.extend(check_inference_surface())
    return out


def main():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, REPO)
    violations = run_lint()
    for v in violations:
        print(v)
    print("%d violation(s)" % len(violations))
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
