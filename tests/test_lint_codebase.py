"""Codebase self-lint (tools/lint_codebase.py) wired into the tier-1
gate: traced-path modules must stay free of host-sync calls, and the
public op namespaces must stay covered by the op_table registry."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import lint_codebase  # noqa: E402


class TestSelfLint:
    def test_codebase_clean(self):
        violations = lint_codebase.run_lint()
        assert violations == [], (
            "%d self-lint violation(s):\n%s"
            % (len(violations), "\n".join(violations))
        )

    def test_catches_seeded_host_sync(self):
        bad = (
            "import numpy as np\n"
            "import time\n"
            "import jax\n"
            "def kernel(x):\n"
            "    a = np.asarray(x)\n"
            "    t = time.time()\n"
            "    b = jax.device_get(x)\n"
            "    return a, t, b\n"
        )
        v = lint_codebase.lint_file("fake/kernel.py", text=bad)
        rules = "\n".join(v)
        assert len(v) == 3, v
        assert "np.asarray" in rules
        assert "time.time" in rules
        assert "jax.device_get" in rules

    def test_waiver_comment_suppresses(self):
        text = (
            "import numpy as np\n"
            "def f(x):\n"
            "    return np.asarray(x)  # trace-lint: ok(test waiver)\n"
        )
        assert lint_codebase.lint_file("fake/f.py", text=text) == []

    def test_reference_functions_exempt(self):
        text = (
            "import numpy as np\n"
            "def kernel_reference(x):\n"
            "    return np.asarray(x)\n"
        )
        assert lint_codebase.lint_file("fake/r.py", text=text) == []

    def test_jnp_asarray_not_flagged(self):
        text = (
            "import jax.numpy as jnp\n"
            "def f(x):\n"
            "    return jnp.asarray(x)\n"
        )
        assert lint_codebase.lint_file("fake/j.py", text=text) == []


class TestHostOnlyLint:
    """The prefix-cache subsystem (inference/prefix_cache.py) is
    declared pure host bookkeeping — the lint must catch any jax
    usage creeping into the scheduler's admission path."""

    def test_catches_seeded_jax_usage(self):
        bad = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def match(tokens):\n"
            "    return jnp.asarray(tokens), jax.device_count()\n"
        )
        v = lint_codebase.lint_host_only_file("fake/pc.py", text=bad)
        rules = "\n".join(v)
        assert len(v) == 4, v
        assert "import jax" in rules
        assert "jnp.asarray" in rules
        assert "jax.device_count" in rules

    def test_plain_host_code_clean(self):
        text = (
            "import collections\n"
            "def match(tokens):\n"
            "    return collections.Counter(tokens)\n"
        )
        assert lint_codebase.lint_host_only_file(
            "fake/pc.py", text=text) == []

    def test_waiver_comment_suppresses(self):
        text = (
            "import jax  # trace-lint: ok(test waiver)\n"
        )
        assert lint_codebase.lint_host_only_file(
            "fake/pc.py", text=text) == []

    def test_prefix_cache_module_is_covered(self):
        covered = [os.path.join(REPO, f)
                   for f in lint_codebase.HOST_ONLY_FILES]
        assert any(p.endswith(os.path.join("inference",
                                           "prefix_cache.py"))
                   for p in covered)
        for p in covered:
            assert os.path.exists(p), p

    def test_telemetry_module_is_covered(self):
        # the jax-free-import contract of the telemetry layer: it is
        # imported BY host-only modules and must stay host-only itself
        assert any(
            f.endswith(os.path.join("framework", "telemetry.py"))
            for f in lint_codebase.HOST_ONLY_FILES)

    def test_inference_surface_leak_free(self):
        assert lint_codebase.check_inference_surface() == []


class TestClockDiscipline:
    """Telemetry clock discipline: the instrumented serving modules
    (serving.py / paged_cache.py / prefix_cache.py) must not read
    wall clocks directly — spans / telemetry.clock() are the single
    timing path."""

    def test_seeded_dotted_clock_calls_flagged(self):
        bad = (
            "import time\n"
            "def step(self):\n"
            "    t0 = time.time()\n"
            "    t1 = time.perf_counter()\n"
            "    t2 = time.monotonic()\n"
            "    return t1 - t0, t2\n"
        )
        v = lint_codebase.lint_clock_discipline_file(
            "fake/serving.py", text=bad)
        rules = "\n".join(v)
        assert len(v) == 3, v
        assert "time.time()" in rules
        assert "time.perf_counter()" in rules
        assert "time.monotonic()" in rules
        assert "single timing path" in rules.lower() or \
            "SINGLE timing" in rules

    def test_seeded_from_import_flagged(self):
        bad = (
            "from time import perf_counter\n"
            "def step(self):\n"
            "    return perf_counter()\n"
        )
        v = lint_codebase.lint_clock_discipline_file(
            "fake/serving.py", text=bad)
        assert len(v) == 1, v
        assert "from time import perf_counter" in v[0]

    def test_telemetry_helper_clean(self):
        text = (
            "from ..framework import telemetry\n"
            "import time\n"          # import alone is fine (sleep..)
            "def step(self):\n"
            "    time.sleep(0)\n"    # non-clock time attr is fine
            "    if self._metrics is not None:\n"
            "        t0 = telemetry.clock()\n"
            "    return t0\n"
        )
        assert lint_codebase.lint_clock_discipline_file(
            "fake/serving.py", text=text) == []

    def test_waiver_comment_suppresses(self):
        text = (
            "import time\n"
            "def step(self):\n"
            "    return time.time()  # trace-lint: ok(test waiver)\n"
        )
        assert lint_codebase.lint_clock_discipline_file(
            "fake/serving.py", text=text) == []

    def test_serving_modules_are_covered_and_clean(self):
        files = lint_codebase.CLOCK_DISCIPLINE_FILES
        endings = {os.path.join("inference", "serving.py"),
                   os.path.join("inference", "prefix_cache.py"),
                   os.path.join("nn", "paged_cache.py")}
        for want in endings:
            assert any(f.endswith(want) for f in files), want
        assert lint_codebase.check_clock_discipline() == []


class TestWatchdogReadOnly:
    """Watchdog read-only discipline (ISSUE 8): detector code may
    only READ the telemetry registry — no registry mutators, no
    pool-private calls, no pool state writes."""

    def test_seeded_registry_mutators_flagged(self):
        bad = (
            "def check(self, epoch):\n"
            "    self.registry.inc('serving.steps')\n"
            "    self.registry.gauge('pool.utilization', 1.0)\n"
            "    self.registry.observe('serving.ttft_s', 0.1)\n"
            "    self.registry.set_epoch(epoch)\n"
        )
        v = lint_codebase.lint_watchdog_file(
            "fake/watchdog.py", text=bad)
        rules = "\n".join(v)
        assert len(v) == 4, v
        assert ".inc(...)" in rules
        assert ".gauge(...)" in rules
        assert ".observe(...)" in rules
        assert ".set_epoch(...)" in rules
        assert "READ" in rules

    def test_seeded_pool_private_call_flagged(self):
        bad = (
            "def check(self, epoch, pool):\n"
            "    pool._release_page(3)\n"
            "    return pool._padded_kernel_inputs()\n"
        )
        v = lint_codebase.lint_watchdog_file(
            "fake/watchdog.py", text=bad)
        assert len(v) == 2, v
        assert "pool-private ._release_page()" in v[0]

    def test_seeded_pool_state_write_flagged(self):
        bad = (
            "def check(self, epoch, pool):\n"
            "    pool._refcnt[3] = 0\n"
            "    pool.k_pages = None\n"
            "    pool._lens['s'] += 1\n"
        )
        v = lint_codebase.lint_watchdog_file(
            "fake/watchdog.py", text=bad)
        rules = "\n".join(v)
        assert len(v) == 3, v
        assert "._refcnt" in v[0]
        assert ".k_pages" in v[1]
        assert "._lens" in v[2]
        assert "registry-READ-ONLY" in rules

    def test_reads_and_internal_state_clean(self):
        text = (
            "import collections\n"
            "def check(self, epoch):\n"
            "    n = self.registry.counter('compile.count')\n"
            "    u = self.registry.gauge_value('pool.utilization')\n"
            "    s = self.registry.hist_samples('serving.x')\n"
            "    snap = self.registry.snapshot()\n"
            "    self.events.append({'n': n, 'u': u})\n"
            "    self.counts['x'] = self.counts.get('x', 0) + 1\n"
            "    return s, snap\n"
        )
        assert lint_codebase.lint_watchdog_file(
            "fake/watchdog.py", text=text) == []

    def test_waiver_comment_suppresses(self):
        text = (
            "def check(self, epoch):\n"
            "    self.registry.inc('x')"
            "  # trace-lint: ok(test waiver)\n"
        )
        assert lint_codebase.lint_watchdog_file(
            "fake/watchdog.py", text=text) == []

    def test_watchdog_module_is_covered_and_clean(self):
        assert any(
            f.endswith(os.path.join("framework", "watchdog.py"))
            for f in lint_codebase.WATCHDOG_FILES)
        # the real module passes its own rule AND the host-only rule
        assert lint_codebase.check_watchdog_readonly() == []
        assert any(
            f.endswith(os.path.join("framework", "watchdog.py"))
            for f in lint_codebase.HOST_ONLY_FILES)

    def test_rule_inventory_has_watchdog_rule(self):
        ids = [r for r, _ in lint_codebase.RULES]
        assert "watchdog-read-only" in ids

    def test_flight_recorder_is_covered_by_readonly_rule(self):
        # ISSUE 12: the incident flight recorder is held to the same
        # read-only surface as the detectors whose trips it records
        assert any(
            f.endswith(os.path.join("framework", "flight_recorder.py"))
            for f in lint_codebase.WATCHDOG_FILES)


class TestBundleAtomicity:
    """Bundle-atomicity discipline (ISSUE 12): incident-bundle
    writers must route every file write through telemetry's
    atomic-write helper — no direct write-mode open() calls."""

    def test_seeded_write_mode_open_flagged(self):
        bad = (
            "import json, io, os\n"
            "def write(self, path, obj):\n"
            "    with open(path, 'w') as f:\n"
            "        json.dump(obj, f)\n"
            "    with open(path + '.log', 'a') as f:\n"
            "        f.write('x')\n"
            "    io.open(path, 'w+')\n"
        )
        v = lint_codebase.lint_incident_writer_file(
            "fake/flight_recorder.py", text=bad)
        rules = "\n".join(v)
        assert len(v) == 3, v
        assert "open(..., 'w')" in rules
        assert "open(..., 'a')" in rules
        assert "atomic_write_text" in rules

    def test_seeded_dynamic_mode_flagged(self):
        bad = (
            "def write(self, path, mode):\n"
            "    return open(path, mode)\n"
        )
        v = lint_codebase.lint_incident_writer_file(
            "fake/flight_recorder.py", text=bad)
        assert len(v) == 1, v
        assert "dynamic mode" in v[0]

    def test_reads_allowed(self):
        text = (
            "import json\n"
            "def read(self, path):\n"
            "    with open(path) as f:\n"
            "        return json.load(f)\n"
            "def read2(self, path):\n"
            "    return open(path, 'r', encoding='utf-8').read()\n"
        )
        assert lint_codebase.lint_incident_writer_file(
            "fake/flight_recorder.py", text=text) == []

    def test_waiver_comment_suppresses(self):
        text = (
            "def write(self, path):\n"
            "    open(path, 'w')"
            "  # trace-lint: ok(test waiver)\n"
        )
        assert lint_codebase.lint_incident_writer_file(
            "fake/flight_recorder.py", text=text) == []

    def test_recorder_module_is_covered_and_clean(self):
        assert any(
            f.endswith(os.path.join("framework", "flight_recorder.py"))
            for f in lint_codebase.INCIDENT_WRITER_FILES)
        assert lint_codebase.check_bundle_atomicity() == []

    def test_ledger_and_recorder_are_host_only(self):
        # ISSUE 12: the performance ledger and the flight recorder
        # run inside the scheduler's step loop — jax-free by lint
        for tail in ("perf_ledger.py", "flight_recorder.py"):
            assert any(
                f.endswith(os.path.join("framework", tail))
                for f in lint_codebase.HOST_ONLY_FILES), tail

    def test_rule_inventory_has_bundle_atomicity(self):
        ids = [r for r, _ in lint_codebase.RULES]
        assert "bundle-atomicity" in ids


class TestOpTableMessages:
    """The small-fix satellite: undeclared/waiver failures must name
    the offending module and the nearest registered op."""

    def test_describe_ops_names_module_and_neighbor(self):
        from paddle_tpu.ops.op_table import describe_ops

        msg = describe_ops(["matmull"])  # typo'd op, not registered
        assert "matmull" in msg
        assert "<not in registry>" in msg
        assert "matmul" in msg  # the nearest-neighbor hint

    def test_describe_ops_real_op_names_module(self):
        from paddle_tpu.ops.op_table import describe_ops

        msg = describe_ops(["matmul"])
        assert "tensor.linalg" in msg


class TestQuantSidecarRule:
    """ISSUE-3 satellite: the int8 KV pool's per-page scale sidecars
    (k_scales/v_scales) are pool-private; a serving-layer write
    bypassing the requantize/COW paths must be flagged."""

    def test_seeded_direct_assignment_flagged(self):
        bad = (
            "class S:\n"
            "    def step(self, cache):\n"
            "        cache.k_scales = None\n"
            "        cache.v_scales += 1\n"
        )
        v = lint_codebase.lint_quant_sidecar_file(
            "fake/serving.py", text=bad)
        assert len(v) == 2, v
        assert "k_scales" in v[0] and "v_scales" in v[1]

    def test_seeded_functional_update_flagged(self):
        bad = (
            "def evict(cache, p):\n"
            "    cache.k_scales = cache.k_scales.at[p].set(0.0)\n"
        )
        v = lint_codebase.lint_quant_sidecar_file(
            "fake/serving.py", text=bad)
        # both the rebind and the .at[...] update are caught
        assert len(v) == 2, v
        assert any(".at[...]" in s for s in v)

    def test_reads_allowed(self):
        ok = (
            "def stats(cache):\n"
            "    return cache.k_scales, cache.v_scales.shape\n"
        )
        assert lint_codebase.lint_quant_sidecar_file(
            "fake/serving.py", text=ok) == []

    def test_waiver_comment_suppresses(self):
        text = (
            "def f(cache):\n"
            "    cache.k_scales = 0  # trace-lint: ok(test waiver)\n"
        )
        assert lint_codebase.lint_quant_sidecar_file(
            "fake/serving.py", text=text) == []

    def test_serving_modules_are_covered(self):
        assert lint_codebase.check_quant_sidecar_writes() == []
        dirs = [os.path.join(REPO, d)
                for d in lint_codebase.QUANT_SIDECAR_DIRS]
        assert any(d.endswith("inference") for d in dirs)
        for d in dirs:
            assert os.path.isdir(d), d


class TestServingBucketRule:
    """ISSUE-5 satellite: the serving scheduler must never hand the
    model an unbucketed ragged token batch — every packed feed goes
    through the bucket helper (bucket_packed_tokens) before a
    prefill_chunk call."""

    def test_seeded_unbucketed_feed_flagged(self):
        bad = (
            "class Sched:\n"
            "    def step(self):\n"
            "        feeds, rows, starts = self._pack()\n"
            "        return self.model.prefill_chunk(\n"
            "            feeds, rows, starts)\n"
        )
        v = lint_codebase.lint_serving_bucket_file("fake/serving.py",
                                                   text=bad)
        assert len(v) == 1, v
        assert "bucket_packed_tokens" in v[0]
        assert "prefill_chunk" in v[0]

    def test_bucketed_feed_clean(self):
        ok = (
            "class Sched:\n"
            "    def step(self):\n"
            "        feeds, rows, starts = self._pack()\n"
            "        pad = bucket_packed_tokens(sum(map(len, feeds)),\n"
            "                                   self.buckets)\n"
            "        return self.model.prefill_chunk(\n"
            "            feeds, rows, starts, pad_to=pad)\n"
        )
        assert lint_codebase.lint_serving_bucket_file(
            "fake/serving.py", text=ok) == []

    def test_helper_in_nested_scope_does_not_count(self):
        # the bucket call must be in the SAME scope as the feed — a
        # nested def that never runs cannot sanction the call site
        bad = (
            "class Sched:\n"
            "    def step(self):\n"
            "        def unused():\n"
            "            return bucket_packed_tokens(8)\n"
            "        return self.model.prefill_chunk(f, r, s)\n"
        )
        v = lint_codebase.lint_serving_bucket_file("fake/serving.py",
                                                   text=bad)
        assert len(v) == 1, v

    def test_waiver_comment_suppresses(self):
        bad = (
            "class Sched:\n"
            "    def step(self):\n"
            "        return self.model.prefill_chunk(f, r, s)"
            "  # trace-lint: ok(test waiver)\n"
        )
        assert lint_codebase.lint_serving_bucket_file(
            "fake/serving.py", text=bad) == []

    def test_serving_module_is_covered_and_clean(self):
        covered = [os.path.join(REPO, f)
                   for f in lint_codebase.SERVING_BUCKET_FILES]
        assert any(p.endswith(os.path.join("inference", "serving.py"))
                   for p in covered)
        for p in covered:
            assert os.path.exists(p), p
        assert lint_codebase.check_serving_buckets() == []


class TestCollectiveMatmulDiscipline:
    """ISSUE-4 satellite: the collective-matmul kernel module is
    jax-only, and the TP/SP layer modules must route dependent
    matmul+collective pairs through the subsystem instead of
    hand-rolling new blocking chains."""

    def test_seeded_host_import_flagged(self):
        bad = (
            "import jax\n"
            "import numpy as np\n"
            "import time, os\n"
            "from threading import Lock\n"
            "import functools\n"
        )
        v = lint_codebase.lint_jax_only_file("fake/cm.py", text=bad)
        rules = "\n".join(v)
        assert len(v) == 4, v
        assert "import numpy" in rules
        assert "import time" in rules and "import os" in rules
        assert "from threading import" in rules

    def test_relative_and_jax_imports_allowed(self):
        ok = (
            "from __future__ import annotations\n"
            "import functools\n"
            "import math\n"
            "import jax\n"
            "import jax.numpy as jnp\n"
            "from ...framework.flags import flag\n"
        )
        assert lint_codebase.lint_jax_only_file(
            "fake/cm.py", text=ok) == []

    def test_kernel_module_is_covered(self):
        covered = [os.path.join(REPO, f)
                   for f in lint_codebase.JAX_ONLY_FILES]
        assert any(p.endswith("collective_matmul.py") for p in covered)
        for p in covered:
            assert os.path.exists(p), p
        assert lint_codebase.check_jax_only() == []

    def test_seeded_blocking_pair_flagged(self):
        bad = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def forward(x, w):\n"
            "    g = jax.lax.all_gather(x, 'mp', axis=0, tiled=True)\n"
            "    return jnp.matmul(g, w)\n"
        )
        v = lint_codebase.lint_tp_routing_file("fake/mp.py", text=bad)
        assert len(v) == 1, v
        assert "collective_matmul_dispatch" in v[0]
        assert "all_gather" in v[0] and "matmul" in v[0]

    def test_pair_split_across_scopes_clean(self):
        # the sanctioned structure: collective in a dedicated VJP
        # closure, matmul in the enclosing layer body
        ok = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def forward(x, w):\n"
            "    def gather(v):\n"
            "        return jax.lax.all_gather(v, 'mp', axis=0,\n"
            "                                  tiled=True)\n"
            "    return jnp.matmul(x, w)\n"
        )
        assert lint_codebase.lint_tp_routing_file(
            "fake/mp.py", text=ok) == []

    def test_waiver_comment_suppresses(self):
        bad = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def forward(x, w):\n"
            "    g = jax.lax.all_gather(x, 'mp')"
            "  # trace-lint: ok(test waiver)\n"
            "    return jnp.matmul(g, w)\n"
        )
        assert lint_codebase.lint_tp_routing_file(
            "fake/mp.py", text=bad) == []

    def test_tp_modules_are_covered(self):
        covered = [os.path.join(REPO, f)
                   for f in lint_codebase.TP_ROUTING_FILES]
        names = "\n".join(covered)
        assert "mp_layers.py" in names and "mp_ops.py" in names
        assert "sequence_parallel_utils.py" in names
        for p in covered:
            assert os.path.exists(p), p
        assert lint_codebase.check_tp_routing() == []


class TestPoolMutationAudit:
    """ISSUE-6 static half: PagedKVCacheManager state writes and
    pool-private method calls outside the pool module are lint
    errors — the guarantee that the page sanitizer's instrumented
    entry points are the ONLY mutation paths."""

    def test_seeded_state_writes_flagged(self):
        bad = (
            "def evict(cache, p):\n"
            "    cache._refcnt[p] = 0\n"
            "    cache._free.append(p)\n"
            "    cache.k_pages = cache.k_pages.at[p].set(0)\n"
            "    cache._lens['s'] += 1\n"
        )
        v = lint_codebase.lint_pool_state_file("fake/srv.py", text=bad)
        joined = "\n".join(v)
        assert "_refcnt" in joined
        assert "_free.append" in joined
        assert ".k_pages" in joined and ".at[...]" in joined
        assert "_lens" in joined
        assert len(v) >= 4, v

    def test_container_mutations_flagged(self):
        bad = (
            "def steal(cache):\n"
            "    return cache._free.pop()\n"
        )
        v = lint_codebase.lint_pool_state_file("fake/s.py", text=bad)
        assert len(v) == 1 and "_free.pop" in v[0]

    def test_tree_node_pages_not_flagged(self):
        # the radix tree's OWN node.pages lists are tree state
        ok = (
            "def split(node, lower_pages):\n"
            "    node.pages = lower_pages\n"
            "    node.pages.append([1, 2])\n"
        )
        assert lint_codebase.lint_pool_state_file(
            "fake/tree.py", text=ok) == []

    def test_reads_allowed_in_state_rule(self):
        ok = (
            "def stats(cache):\n"
            "    return len(cache.k_pages), cache.k_scales.sum()\n"
        )
        assert lint_codebase.lint_pool_state_file(
            "fake/r.py", text=ok) == []

    def test_state_write_waiver_suppresses(self):
        text = (
            "def f(cache):\n"
            "    cache._refcnt[0] = 1  # trace-lint: ok(test waiver)\n"
        )
        assert lint_codebase.lint_pool_state_file(
            "fake/w.py", text=text) == []

    def test_seeded_private_calls_flagged(self):
        bad = (
            "def fast_path(cache, sid):\n"
            "    page, off = cache._next_slot(sid)\n"
            "    cache._release_page(page)\n"
            "    return cache._padded_kernel_inputs([sid], 1, None)\n"
        )
        v = lint_codebase.lint_pool_api_file("fake/api.py", text=bad)
        joined = "\n".join(v)
        assert "_next_slot" in joined
        assert "_release_page" in joined
        assert "_padded_kernel_inputs" in joined
        assert len(v) == 3, v

    def test_bookkeeping_reads_flagged_in_api_files(self):
        bad = (
            "def peek(cache):\n"
            "    return cache._refcnt[0], len(cache._tables)\n"
        )
        v = lint_codebase.lint_pool_api_file("fake/p.py", text=bad)
        assert len(v) == 2, v

    def test_public_api_clean(self):
        ok = (
            "def step(cache, sid, k, v):\n"
            "    cache.append_batch([sid], k, v)\n"
            "    cache.attend(k, [sid])\n"
            "    n = cache.num_free_pages\n"
            "    return cache.seq_pages(sid), n\n"
        )
        assert lint_codebase.lint_pool_api_file(
            "fake/ok.py", text=ok) == []

    def test_private_call_waiver_suppresses(self):
        text = (
            "def f(cache, s):\n"
            "    return cache._next_slot(s)"
            "  # trace-lint: ok(test waiver)\n"
        )
        assert lint_codebase.lint_pool_api_file(
            "fake/w2.py", text=text) == []

    def test_audit_covers_serving_stack_and_is_clean(self):
        for f in lint_codebase.POOL_API_FILES:
            assert os.path.exists(os.path.join(REPO, f)), f
        names = "\n".join(lint_codebase.POOL_API_FILES)
        assert "serving.py" in names
        assert "prefix_cache.py" in names
        assert "paged_llama.py" in names
        # the pool module itself is exempt (it IS the audited API)
        assert any("paged_cache.py" in f
                   for f in lint_codebase.POOL_MUTATION_EXEMPT)
        assert lint_codebase.check_pool_mutation_audit() == []

    def test_rule_inventory_has_pool_rules(self):
        ids = [r for r, _ in lint_codebase.RULES]
        assert "pool-mutation-audit" in ids
        assert "pool-private-api" in ids
        assert len(ids) == len(set(ids))


class TestSwapTierAudit:
    """ISSUE-9 extension of the pool-mutation audit: the host swap
    tier's store (HostKVSwapSpace._swap_store/_swap_used) is
    swap-tier-private — writable only inside paged_cache.py — and
    the _swap_put/_swap_get/_swap_pop entry points are pool-private
    methods serving code may never call."""

    def test_seeded_swap_state_writes_flagged(self):
        bad = (
            "def steal(space, key, rec):\n"
            "    space._swap_store[key] = rec\n"
            "    space._swap_used += rec.nbytes\n"
            "    space._swap_store.pop(key)\n"
        )
        v = lint_codebase.lint_pool_state_file("fake/sw.py", text=bad)
        joined = "\n".join(v)
        assert "_swap_store" in joined
        assert "_swap_used" in joined
        assert len(v) == 3, v

    def test_seeded_swap_private_calls_flagged(self):
        bad = (
            "def bypass(space, cache, key):\n"
            "    rec = space._swap_get(key)\n"
            "    space._swap_pop(key)\n"
            "    space._swap_put(key, rec)\n"
        )
        v = lint_codebase.lint_pool_api_file("fake/sb.py", text=bad)
        joined = "\n".join(v)
        assert "_swap_get" in joined
        assert "_swap_pop" in joined
        assert "_swap_put" in joined
        assert len(v) == 3, v

    def test_public_swap_readout_clean(self):
        ok = (
            "def pressure(space):\n"
            "    if not space.would_fit(4096):\n"
            "        return space.summary()\n"
            "    return space.used_bytes, space.free_bytes\n"
        )
        assert lint_codebase.lint_pool_api_file(
            "fake/so.py", text=ok) == []

    def test_swap_tier_in_audited_attrs(self):
        assert "_swap_store" in lint_codebase._POOL_STATE_ATTRS
        assert "_swap_used" in lint_codebase._POOL_STATE_ATTRS
        assert "_swap_put" in lint_codebase._POOL_PRIVATE_METHODS
        # and the live serving stack is clean under the extension
        assert lint_codebase.check_pool_mutation_audit() == []

    def test_fault_injection_is_host_only(self):
        assert any("fault_injection.py" in f
                   for f in lint_codebase.HOST_ONLY_FILES)
        assert lint_codebase.check_host_only() == []


class TestServingTerminalTrace:
    """ISSUE-9: serving.py must never drop a request without its
    terminal trace event — any function that moves a request to a
    terminal state must call self._traces.complete(...) itself."""

    def test_seeded_silent_finish_flagged(self):
        bad = (
            "def _retire(self, req):\n"
            "    req.state = RequestState.FINISHED\n"
            "    del self._active[req.req_id]\n"
        )
        v = lint_codebase.lint_serving_terminal_file(
            "fake/sched.py", text=bad)
        assert len(v) == 1 and "_retire" in v[0], v
        assert "terminal" in v[0]

    def test_seeded_silent_finished_write_flagged(self):
        bad = (
            "def _drop(self, req):\n"
            "    self._finished[req.req_id] = req\n"
        )
        v = lint_codebase.lint_serving_terminal_file(
            "fake/d.py", text=bad)
        assert len(v) == 1 and "_drop" in v[0], v

    def test_seeded_abort_state_flagged(self):
        bad = (
            "def _kill(self, req):\n"
            "    req.state = RequestState.ABORTED_DEADLINE\n"
        )
        v = lint_codebase.lint_serving_terminal_file(
            "fake/k.py", text=bad)
        assert len(v) == 1 and "_kill" in v[0], v

    def test_terminal_with_trace_emit_clean(self):
        ok = (
            "def _retire(self, req):\n"
            "    req.state = RequestState.FINISHED\n"
            "    self._finished[req.req_id] = req\n"
            "    if self._traces is not None:\n"
            "        self._traces.complete(req.req_id, 'retire',\n"
            "                              0.0, 0)\n"
        )
        assert lint_codebase.lint_serving_terminal_file(
            "fake/ok.py", text=ok) == []

    def test_non_terminal_states_clean(self):
        ok = (
            "def _preempt(self, req):\n"
            "    req.state = RequestState.SWAPPED\n"
            "    self._swapped[req.req_id] = req\n"
        )
        assert lint_codebase.lint_serving_terminal_file(
            "fake/p.py", text=ok) == []

    def test_waiver_comment_suppresses(self):
        text = (
            "def _quiet(self, req):  # trace-lint: ok(test waiver)\n"
            "    req.state = RequestState.FINISHED\n"
        )
        assert lint_codebase.lint_serving_terminal_file(
            "fake/w.py", text=text) == []

    def test_scheduler_module_is_covered_and_clean(self):
        assert any("serving.py" in f
                   for f in lint_codebase.SERVING_TERMINAL_FILES)
        assert lint_codebase.check_serving_terminal_trace() == []

    def test_rule_inventory_has_terminal_rule(self):
        ids = [r for r, _ in lint_codebase.RULES]
        assert "serving-terminal-trace" in ids
        assert len(ids) == len(set(ids))


class TestFlagInventory:
    """Every FLAGS_* in framework/flags.py needs a docstring and a
    docs/ mention (docs/FLAGS.md is the catch-all reference) — the
    flag-inventory rule catches undocumented knobs at review time."""

    def test_seeded_missing_docstring_flagged(self):
        bad = (
            "def define_flag(name, default, help_str=''):\n"
            "    pass\n"
            "define_flag('mystery_knob', 0)\n"
        )
        v = lint_codebase.lint_flag_inventory(
            bad, docs_text="FLAGS_mystery_knob is documented here")
        assert len(v) == 1, v
        assert "FLAGS_mystery_knob" in v[0]
        assert "docstring" in v[0]

    def test_seeded_empty_docstring_flagged(self):
        bad = "define_flag('blank_knob', 0, '')\n"
        v = lint_codebase.lint_flag_inventory(
            bad, docs_text="FLAGS_blank_knob")
        assert len(v) == 1 and "docstring" in v[0]

    def test_seeded_missing_docs_mention_flagged(self):
        bad = "define_flag('ghost_knob', 1, 'does a thing')\n"
        v = lint_codebase.lint_flag_inventory(bad, docs_text="")
        assert len(v) == 1, v
        assert "FLAGS_ghost_knob" in v[0]
        assert "docs/" in v[0]

    def test_seeded_both_missing_yields_two(self):
        bad = "define_flag('dark_knob', 1)\n"
        v = lint_codebase.lint_flag_inventory(bad, docs_text="")
        assert len(v) == 2, v

    def test_documented_flag_clean(self):
        ok = (
            "define_flag('fine_knob', 'auto',\n"
            "            'a knob with a real docstring '\n"
            "            'spanning literals')\n"
        )
        v = lint_codebase.lint_flag_inventory(
            ok, docs_text="see FLAGS_fine_knob in docs")
        assert v == []

    def test_keyword_help_str_accepted(self):
        ok = "define_flag('kw_knob', 0, help_str='documented knob')\n"
        assert lint_codebase.lint_flag_inventory(
            ok, docs_text="FLAGS_kw_knob") == []

    def test_prefix_collision_not_vacuous(self):
        # a docs mention of the LONGER flag must not satisfy the
        # shorter prefix flag (FLAGS_jit_plan vs
        # FLAGS_jit_plan_comm_bound_ratio families)
        bad = (
            "define_flag('knob', 0, 'short flag')\n"
            "define_flag('knob_extra_ratio', 0, 'long flag')\n"
        )
        v = lint_codebase.lint_flag_inventory(
            bad, docs_text="only FLAGS_knob_extra_ratio is here")
        assert len(v) == 1, v
        assert "FLAGS_knob " in v[0] or "FLAGS_knob is" in v[0]

    def test_repo_flags_all_documented(self):
        v = lint_codebase.check_flag_inventory()
        assert v == [], "\n".join(v)

    def test_every_planner_flag_in_inventory(self):
        # the ISSUE-10 flags ride the same contract from day one
        with open(os.path.join(
                REPO, lint_codebase.FLAGS_FILE)) as f:
            names = [n for n, _, _ in
                     lint_codebase._defined_flags(f.read())]
        for flag in ("jit_plan", "jit_budget_hbm", "jit_budget_comm",
                     "jit_plan_comm_bound_ratio"):
            assert flag in names

    def test_rule_inventory_has_flag_rule(self):
        ids = [r for r, _ in lint_codebase.RULES]
        assert "flag-inventory" in ids


class TestUnifiedAttention:
    """ISSUE-13 satellite: packed-step attention in the serving
    layers routes through the single attend_ragged/layer_step
    pool API — a ragged append's function must attend through the
    unified entry in the same scope."""

    def test_seeded_ragged_append_without_unified_attend(self):
        bad = (
            "def chunk(cache, sids, counts, kh, vh, q):\n"
            "    cache.append_ragged(sids, counts, kh, vh)\n"
            "    return cache.attend(q, sids)\n"
        )
        v = lint_codebase.lint_unified_attention_file(
            "fake/paged_llama.py", text=bad)
        assert len(v) == 1, v
        assert "append_ragged" in v[0]

    def test_ragged_append_with_unified_attend_clean(self):
        ok = (
            "def chunk(cache, sids, counts, kh, vh, q):\n"
            "    cache.append_ragged(sids, counts, kh, vh)\n"
            "    return cache.attend_ragged(q, sids, counts)\n"
        )
        assert lint_codebase.lint_unified_attention_file(
            "fake/paged_llama.py", text=ok) == []

    def test_layer_step_counts_as_unified(self):
        ok = (
            "def chunk(cache, x, w, sids, counts):\n"
            "    cache.append_ragged(sids, counts, x, x)\n"
            "    return cache.layer_step(x, w, sids, counts)\n"
        )
        assert lint_codebase.lint_unified_attention_file(
            "fake/paged_llama.py", text=ok) == []

    def test_nested_scope_does_not_sanction(self):
        # the unified call must be in the SAME scope as the append —
        # a nested def that never runs cannot sanction the site
        bad = (
            "def chunk(cache, sids, counts, kh, vh):\n"
            "    def unused(q):\n"
            "        return cache.attend_ragged(q, sids, counts)\n"
            "    cache.append_ragged(sids, counts, kh, vh)\n"
        )
        v = lint_codebase.lint_unified_attention_file(
            "fake/paged_llama.py", text=bad)
        assert len(v) == 1, v

    def test_serving_layers_covered_and_clean(self):
        covered = [os.path.join(REPO, f)
                   for f in lint_codebase.UNIFIED_ATTENTION_FILES]
        assert any(p.endswith(os.path.join("inference", "serving.py"))
                   for p in covered)
        assert any(p.endswith(os.path.join("inference",
                                           "paged_llama.py"))
                   for p in covered)
        for p in covered:
            assert os.path.exists(p), p
        assert lint_codebase.check_unified_attention() == []

    def test_rule_inventory_has_unified_attention(self):
        ids = [r for r, _ in lint_codebase.RULES]
        assert "unified-attention" in ids


class TestWireQuantOwnership:
    """ISSUE-14 wire-quant ownership rule: quantize-on-the-wire
    (FLAGS_collective_dtype) lives only in the jax-only kernel module
    — a raw int8/fp8 cast next to a raw collective in the TP/SP,
    grad-sync, or MoE layer modules is a hand-rolled wire quantization
    bypassing the block scales, cotangent rings, and byte model."""

    def test_seeded_quant_cast_around_collective_flagged(self):
        bad = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def sync(grad):\n"
            "    q = grad.astype(jnp.int8)\n"
            "    return jax.lax.psum(q, 'dp')\n"
        )
        v = lint_codebase.lint_wire_quant_file("fake/mp_ops.py",
                                               text=bad)
        assert len(v) == 1, v
        assert "collective_matmul.py" in v[0]
        assert "FLAGS_collective_dtype" in v[0]

    def test_seeded_string_dtype_flagged(self):
        bad = (
            "import jax\n"
            "def hop(x):\n"
            "    y = x.astype('int8')\n"
            "    return jax.lax.ppermute(y, 'mp', [(0, 1)])\n"
        )
        v = lint_codebase.lint_wire_quant_file("fake/moe_layer.py",
                                               text=bad)
        assert len(v) == 1, v

    def test_fp8_cast_flagged(self):
        bad = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def hop(x):\n"
            "    y = x.astype(jnp.float8_e4m3fn)\n"
            "    return jax.lax.all_gather(x, 'mp', axis=0)\n"
        )
        v = lint_codebase.lint_wire_quant_file("fake/mp_layers.py",
                                               text=bad)
        assert len(v) == 1, v

    def test_cast_without_collective_clean(self):
        ok = (
            "import jax.numpy as jnp\n"
            "def pack(w):\n"
            "    return w.astype(jnp.int8)\n"
        )
        assert lint_codebase.lint_wire_quant_file(
            "fake/mp_ops.py", text=ok) == []

    def test_collective_with_fp_cast_clean(self):
        ok = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def combine(x):\n"
            "    y = x.astype(jnp.float32)\n"
            "    return jax.lax.psum(y, 'ep')\n"
        )
        assert lint_codebase.lint_wire_quant_file(
            "fake/moe_layer.py", text=ok) == []

    def test_nested_scope_does_not_pair(self):
        ok = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def layer(x):\n"
            "    def quantize(v):\n"
            "        return v.astype(jnp.int8)\n"
            "    return jax.lax.psum(x, 'dp')\n"
        )
        assert lint_codebase.lint_wire_quant_file(
            "fake/mp_ops.py", text=ok) == []

    def test_waiver_comment_suppresses(self):
        bad = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def sync(grad):\n"
            "    q = grad.astype(jnp.int8)"
            "  # trace-lint: ok(test waiver)\n"
            "    return jax.lax.psum(q, 'dp')\n"
        )
        assert lint_codebase.lint_wire_quant_file(
            "fake/mp_ops.py", text=bad) == []

    def test_wire_quant_modules_covered_and_clean(self):
        covered = [os.path.join(REPO, f)
                   for f in lint_codebase.WIRE_QUANT_FILES]
        names = "\n".join(covered)
        assert "mp_ops.py" in names and "mp_layers.py" in names
        assert "hybrid_parallel_util.py" in names
        assert "moe_layer.py" in names
        for p in covered:
            assert os.path.exists(p), p
        assert lint_codebase.check_wire_quant() == []

    def test_rule_inventory_has_wire_quant(self):
        ids = [r for r, _ in lint_codebase.RULES]
        assert "wire-quant-ownership" in ids


class TestMetricNameDiscipline:
    """Seeded violations + clean patterns for the metric-name rule
    (ISSUE 15): registry emits must use Prometheus-safe literals
    registered in telemetry.SURFACE — no ad-hoc f-string names."""

    SURFACE = ("serving.ttft_s", "serving.steps", "pool.cow_forks",
               "ledger.mfu.<program>", "exec.wall_s.<program>",
               "serving.slo_attain_ttft")

    def lint(self, src):
        return lint_codebase.lint_metric_names_file(
            "paddle_tpu/fake_mod.py", text=src,
            surface_names=self.SURFACE)

    def test_registered_literal_clean(self):
        src = (
            "def f(reg):\n"
            "    reg.inc('serving.steps')\n"
            "    reg.observe('serving.ttft_s', 0.1)\n"
            "    reg.gauge('pool.cow_forks', 2)\n"
        )
        assert self.lint(src) == []

    def test_fstring_name_flagged(self):
        src = (
            "def f(reg, x):\n"
            "    reg.inc(f'serving.{x}')\n"
        )
        v = self.lint(src)
        assert len(v) == 1 and "f-string" in v[0]

    def test_unregistered_name_flagged(self):
        src = (
            "def f(reg):\n"
            "    reg.inc('serving.totally_new_counter')\n"
        )
        v = self.lint(src)
        assert len(v) == 1 and "not registered" in v[0]

    def test_prom_unsafe_chars_flagged(self):
        src = (
            "def f(reg):\n"
            "    reg.inc('serving.Bad-Name')\n"
        )
        v = self.lint(src)
        assert len(v) == 1 and "round trip" in v[0]

    def test_fully_dynamic_flagged_and_waivable(self):
        bad = (
            "def f(reg, key):\n"
            "    reg.observe(key, 0.5)\n"
        )
        v = self.lint(bad)
        assert len(v) == 1 and "fully dynamic" in v[0]
        waived = (
            "def f(reg, key):\n"
            "    # metric-name: ok (pre-resolved hot-path key)\n"
            "    reg.observe(key, 0.5)\n"
        )
        assert self.lint(waived) == []
        inline = (
            "def f(reg, key):\n"
            "    reg.observe(key, 0.5)  # metric-name: ok (test)\n"
        )
        assert self.lint(inline) == []

    def test_dynamic_suffix_matches_placeholder_row(self):
        src = (
            "def f(reg, prog):\n"
            "    reg.gauge('ledger.mfu.' + prog, 0.4)\n"
            "    reg.gauge('serving.slo_attain_' + 'ttft', 1.0)\n"
        )
        assert self.lint(src) == []

    def test_percent_template_matches_placeholder_row(self):
        src = (
            "def f(reg, field, prog):\n"
            "    reg.gauge('ledger.%s.%s' % (field, prog), 0.4)\n"
        )
        assert self.lint(src) == []

    def test_concrete_instantiation_of_placeholder_row(self):
        src = (
            "def f(reg):\n"
            "    reg.observe('exec.wall_s.decode_token', 0.1)\n"
        )
        assert self.lint(src) == []

    def test_module_const_prefix_resolves(self):
        src = (
            "PREFIX = 'exec.wall_s.'\n"
            "def f(reg, prog):\n"
            "    reg.observe(PREFIX + str(prog), 0.1)\n"
        )
        assert self.lint(src) == []

    def test_dynamic_namespace_head_flagged(self):
        src = (
            "def f(reg, ns):\n"
            "    reg.inc(ns + '.steps')\n"
        )
        v = self.lint(src)
        assert len(v) == 1 and "dynamic namespace head" in v[0]

    def test_non_registry_receiver_ignored(self):
        src = (
            "def f(h, counterish):\n"
            "    h.observe(0.5)\n"
            "    counterish.inc('whatever.name')\n"
        )
        assert self.lint(src) == []

    def test_surface_parses_from_real_module(self):
        names = lint_codebase.surface_metric_names()
        assert "serving.ttft_s" in names
        assert "ledger.wire_bytes_quantized_per_s.<program>" in names
        assert not any(n.startswith("span:") for n in names)

    def test_repo_metric_names_clean(self):
        v = lint_codebase.check_metric_names()
        assert v == [], "\n".join(v)

    def test_rule_inventory_has_metric_name_discipline(self):
        assert any(rid == "metric-name-discipline"
                   for rid, _ in lint_codebase.RULES)


class TestConcurrencyGuardedBy:
    """ISSUE-16 lock-discipline rule: module-level mutable shared
    state in the concurrency-bearing host modules must declare its
    guard ('# guarded-by: <lock>') or carry the single-writer
    waiver — the static twin of the runtime sanitizer's
    unguarded-shared-write class."""

    def test_seeded_unmarked_mutable_flagged(self):
        bad = (
            "_CACHE = {}\n"
            "def put(k, v):\n"
            "    _CACHE[k] = v\n"
        )
        v = lint_codebase.lint_guarded_by_file("fake/mod.py",
                                               text=bad)
        assert len(v) == 1, v
        assert "_CACHE" in v[0]
        assert "guarded-by" in v[0]

    def test_seeded_global_rebind_flagged(self):
        bad = (
            "_SERVER = None\n"
            "def start():\n"
            "    global _SERVER\n"
            "    _SERVER = object()\n"
        )
        v = lint_codebase.lint_guarded_by_file("fake/mod.py",
                                               text=bad)
        assert len(v) == 1, v
        assert "_SERVER" in v[0]

    def test_guard_mark_suppresses(self):
        ok = (
            "_CACHE = {}  # guarded-by: mod.state\n"
            "_SEQ = [0]  # concurrency: single-writer\n"
            "def put(k, v):\n"
            "    _CACHE[k] = v\n"
            "    _SEQ[0] += 1\n"
        )
        assert lint_codebase.lint_guarded_by_file(
            "fake/mod.py", text=ok) == []

    def test_untouched_and_local_state_clean(self):
        ok = (
            "_TABLE = {}\n"          # never mutated from a function
            "CONST = 3\n"
            "def f():\n"
            "    local = {}\n"
            "    local['k'] = 1\n"
            "    return _TABLE, CONST\n"
        )
        assert lint_codebase.lint_guarded_by_file(
            "fake/mod.py", text=ok) == []

    def test_mutator_method_call_flagged(self):
        bad = (
            "import collections\n"
            "_RING = collections.deque()\n"
            "def push(x):\n"
            "    _RING.append(x)\n"
        )
        v = lint_codebase.lint_guarded_by_file("fake/mod.py",
                                               text=bad)
        assert len(v) == 1, v
        assert "_RING" in v[0]

    def test_concurrency_files_covered_and_clean(self):
        names = "\n".join(lint_codebase.CONCURRENCY_FILES)
        for stem in ("telemetry.py", "ops_server.py", "serving.py",
                     "concurrency.py", "flight_recorder.py",
                     "paged_cache.py"):
            assert stem in names, stem
        for f in lint_codebase.CONCURRENCY_FILES:
            assert os.path.exists(os.path.join(REPO, f)), f
        assert lint_codebase.check_guarded_by() == []

    def test_rule_inventory_has_guarded_by(self):
        assert any(rid == "concurrency-guarded-by"
                   for rid, _ in lint_codebase.RULES)


class TestConcurrencyLockOrder:
    """Lock acquisition order must be a DAG at AST level — nested
    `with lock:` blocks merged across the concurrency files; a cycle
    is the static twin of lock-order-inversion."""

    def test_seeded_inversion_flagged(self):
        bad = (
            "import threading\n"
            "a_lock = threading.Lock()\n"
            "b_lock = threading.Lock()\n"
            "def p1():\n"
            "    with a_lock:\n"
            "        with b_lock:\n"
            "            pass\n"
            "def p2():\n"
            "    with b_lock:\n"
            "        with a_lock:\n"
            "            pass\n"
        )
        v = lint_codebase.lint_lock_order_file("fake/mod.py",
                                               text=bad)
        assert len(v) == 1, v
        assert "lock-order inversion" in v[0]

    def test_consistent_order_clean(self):
        ok = (
            "import threading\n"
            "a_lock = threading.Lock()\n"
            "b_lock = threading.Lock()\n"
            "def p1():\n"
            "    with a_lock:\n"
            "        with b_lock:\n"
            "            pass\n"
            "def p2():\n"
            "    with a_lock, b_lock:\n"
            "        pass\n"
        )
        assert lint_codebase.lint_lock_order_file(
            "fake/mod.py", text=ok) == []

    def test_guarded_names_canonicalize_across_files(self):
        """Two files binding DIFFERENT attribute names to the same
        guarded('...') locks still merge into one digraph."""
        f1 = (
            "from paddle_tpu.framework import concurrency\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._reg_lock = concurrency.guarded('x.reg')\n"
            "        self._q_lock = concurrency.guarded('x.queue')\n"
            "    def go(self):\n"
            "        with self._reg_lock:\n"
            "            with self._q_lock:\n"
            "                pass\n"
        )
        f2 = (
            "from paddle_tpu.framework import concurrency\n"
            "class B:\n"
            "    def __init__(self):\n"
            "        self._a_lock = concurrency.guarded('x.queue')\n"
            "        self._b_lock = concurrency.guarded('x.reg')\n"
            "    def go(self):\n"
            "        with self._a_lock:\n"
            "            with self._b_lock:\n"
            "                pass\n"
        )
        e1, err1 = lint_codebase._lock_order_edges("fake/one.py",
                                                   text=f1)
        e2, err2 = lint_codebase._lock_order_edges("fake/two.py",
                                                   text=f2)
        assert err1 == [] and err2 == []
        v = lint_codebase._lock_order_violations(e1 + e2)
        assert len(v) == 1, v
        # neither file alone has a cycle
        assert lint_codebase._lock_order_violations(e1) == []
        assert lint_codebase._lock_order_violations(e2) == []

    def test_nested_def_resets_held_set(self):
        ok = (
            "import threading\n"
            "a_lock = threading.Lock()\n"
            "b_lock = threading.Lock()\n"
            "def p1():\n"
            "    with b_lock:\n"
            "        def later():\n"
            "            with a_lock:\n"
            "                pass\n"
            "        return later\n"
            "def p2():\n"
            "    with a_lock:\n"
            "        with b_lock:\n"
            "            pass\n"
        )
        assert lint_codebase.lint_lock_order_file(
            "fake/mod.py", text=ok) == []

    def test_repo_lock_order_clean(self):
        assert lint_codebase.check_lock_order() == []

    def test_rule_inventory_has_lock_order(self):
        assert any(rid == "concurrency-lock-order"
                   for rid, _ in lint_codebase.RULES)


class TestConcurrencyBlockingAsync:
    """No blocking calls lexically inside `async def` — the static
    twin of blocking-acquire-on-loop."""

    def test_seeded_blocking_calls_flagged(self):
        bad = (
            "import time\n"
            "async def pump(lock):\n"
            "    time.sleep(0.1)\n"
            "    lock.acquire()\n"
            "    open('/tmp/x')\n"
        )
        v = lint_codebase.lint_blocking_async_file("fake/mod.py",
                                                   text=bad)
        assert len(v) == 3, v
        joined = "\n".join(v)
        assert "time.sleep" in joined
        assert "acquire" in joined
        assert "open()" in joined

    def test_nonblocking_acquire_clean(self):
        ok = (
            "async def pump(lock):\n"
            "    if lock.acquire(blocking=False):\n"
            "        lock.release()\n"
            "    if lock.acquire(False):\n"
            "        lock.release()\n"
        )
        assert lint_codebase.lint_blocking_async_file(
            "fake/mod.py", text=ok) == []

    def test_sync_helper_nested_in_async_clean(self):
        ok = (
            "import time\n"
            "async def pump(loop):\n"
            "    def worker():\n"
            "        time.sleep(0.1)\n"
            "    await loop.run_in_executor(None, worker)\n"
        )
        assert lint_codebase.lint_blocking_async_file(
            "fake/mod.py", text=ok) == []

    def test_sync_function_blocking_clean(self):
        ok = (
            "import time\n"
            "def pump():\n"
            "    time.sleep(0.1)\n"
        )
        assert lint_codebase.lint_blocking_async_file(
            "fake/mod.py", text=ok) == []

    def test_waiver_suppresses(self):
        ok = (
            "import time\n"
            "async def pump():\n"
            "    time.sleep(0.1)  # trace-lint: ok(test waiver)\n"
        )
        assert lint_codebase.lint_blocking_async_file(
            "fake/mod.py", text=ok) == []

    def test_repo_async_defs_clean(self):
        assert lint_codebase.check_blocking_async() == []

    def test_rule_inventory_has_blocking_async(self):
        assert any(rid == "concurrency-blocking-async"
                   for rid, _ in lint_codebase.RULES)


class TestConcurrencyThreadDiscipline:
    """Host-plane threads are created only through the sanctioned
    concurrency.spawn_thread helper."""

    def test_seeded_raw_thread_flagged(self):
        bad = (
            "import threading\n"
            "def start():\n"
            "    t = threading.Thread(target=print, daemon=True)\n"
            "    t.start()\n"
        )
        v = lint_codebase.lint_thread_discipline_file(
            "fake/mod.py", text=bad)
        assert len(v) == 1, v
        assert "spawn_thread" in v[0]

    def test_seeded_bare_and_aliased_thread_flagged(self):
        bad = (
            "from threading import Thread as T\n"
            "from threading import Thread\n"
            "def start():\n"
            "    Thread(target=print).start()\n"
            "    T(target=print).start()\n"
        )
        v = lint_codebase.lint_thread_discipline_file(
            "fake/mod.py", text=bad)
        assert len(v) == 2, v

    def test_spawn_thread_clean(self):
        ok = (
            "from paddle_tpu.framework import concurrency\n"
            "def start():\n"
            "    return concurrency.spawn_thread('worker', print)\n"
        )
        assert lint_codebase.lint_thread_discipline_file(
            "fake/mod.py", text=ok) == []

    def test_waiver_suppresses(self):
        ok = (
            "import threading\n"
            "def start():\n"
            "    t = threading.Thread(target=print)"
            "  # trace-lint: ok(test waiver)\n"
            "    t.start()\n"
        )
        assert lint_codebase.lint_thread_discipline_file(
            "fake/mod.py", text=ok) == []

    def test_discipline_files_covered_and_clean(self):
        names = "\n".join(lint_codebase.THREAD_DISCIPLINE_FILES)
        assert "ops_server.py" in names
        assert "flight_recorder.py" in names
        assert "concurrency.py" not in names  # hosts the helper
        for f in lint_codebase.THREAD_DISCIPLINE_FILES:
            assert os.path.exists(os.path.join(REPO, f)), f
        assert lint_codebase.check_thread_discipline() == []

    def test_rule_inventory_has_thread_discipline(self):
        assert any(rid == "concurrency-thread-discipline"
                   for rid, _ in lint_codebase.RULES)


class TestEngineDiscipline:
    """Engine-discipline composite rule (ISSUE 17): scheduler.step()
    only from _pump* functions, spawn_thread-only thread creation,
    and guarded-by declarations — applied to inference/engine.py."""

    def test_seeded_step_outside_pump_flagged(self):
        bad = (
            "class Engine:\n"
            "    async def submit(self, req):\n"
            "        self.scheduler.submit(req)\n"
            "        self.scheduler.step()\n"
        )
        v = lint_codebase.lint_engine_discipline_file(
            "fake/engine.py", text=bad)
        assert len(v) == 1, v
        assert "single-writer" in v[0]

    def test_seeded_step_in_nested_helper_flagged(self):
        bad = (
            "def _drive(sched):\n"
            "    def crank():\n"
            "        sched.step()\n"
            "    crank()\n"
        )
        v = lint_codebase.lint_engine_discipline_file(
            "fake/engine.py", text=bad)
        assert len(v) == 1, v

    def test_step_inside_pump_clean(self):
        ok = (
            "class Engine:\n"
            "    def _pump_main(self):\n"
            "        while True:\n"
            "            self.scheduler.step()\n"
            "    def _pump_iteration(self):\n"
            "        def crank():\n"
            "            self.scheduler.step()\n"
            "        crank()\n"
        )
        assert lint_codebase.lint_engine_discipline_file(
            "fake/engine.py", text=ok) == []

    def test_waiver_suppresses_step_rule(self):
        ok = (
            "def drive(sched):\n"
            "    sched.step()  # trace-lint: ok(test harness)\n"
        )
        assert lint_codebase.lint_engine_discipline_file(
            "fake/engine.py", text=ok) == []

    def test_composes_thread_discipline(self):
        bad = (
            "import threading\n"
            "def _pump_main(self):\n"
            "    threading.Thread(target=print).start()\n"
        )
        v = lint_codebase.lint_engine_discipline_file(
            "fake/engine.py", text=bad)
        assert len(v) == 1, v
        assert "spawn_thread" in v[0]

    def test_composes_guarded_by(self):
        bad = (
            "_SEQ = [0]\n"
            "def bump():\n"
            "    _SEQ[0] += 1\n"
        )
        v = lint_codebase.lint_engine_discipline_file(
            "fake/engine.py", text=bad)
        assert len(v) == 1, v
        assert "guarded-by" in v[0]

    def test_engine_file_owned_here_not_by_concurrency_lists(self):
        # the composite rule owns engine.py; the generic lists must
        # not double-report the same findings
        assert lint_codebase.ENGINE_FILE not in \
            lint_codebase.CONCURRENCY_FILES
        assert lint_codebase.ENGINE_FILE not in \
            lint_codebase.THREAD_DISCIPLINE_FILES
        assert os.path.exists(
            os.path.join(REPO, lint_codebase.ENGINE_FILE))
        assert lint_codebase.check_engine_discipline() == []

    def test_rule_inventory_has_engine_discipline(self):
        assert any(rid == "engine-discipline"
                   for rid, _ in lint_codebase.RULES)


class TestRoleDiscipline:
    """Disagg role-discipline rule (ISSUE 18): prefill-role scopes in
    inference/disagg.py must not call the decode-only restore surface
    (swap_in / import_seq / adopt_swapped / adopt)."""

    def test_seeded_prefill_calling_restore_flagged(self):
        bad = (
            "class PrefillWorker:\n"
            "    def run(self, sched, req, space, pools):\n"
            "        sched.adopt_swapped(req, [])\n"
            "        space.import_seq(req.req_id, [], pools)\n"
        )
        v = lint_codebase.lint_role_discipline_file(
            "fake/disagg.py", text=bad)
        assert len(v) == 2, v
        assert all("decode-only" in m for m in v)
        assert ".adopt_swapped()" in v[0]
        assert ".import_seq()" in v[1]

    def test_seeded_prefill_named_function_flagged(self):
        # scope matching is by NAME anywhere on the stack, so a
        # helper nested under a prefill-named function is covered too
        bad = (
            "def run_prefill_leg(pool, space):\n"
            "    def finish(sid):\n"
            "        pool.swap_in(sid, space)\n"
            "    finish('s')\n"
        )
        v = lint_codebase.lint_role_discipline_file(
            "fake/disagg.py", text=bad)
        assert len(v) == 1, v
        assert ".swap_in()" in v[0]

    def test_decode_scope_clean(self):
        ok = (
            "class DecodeWorker:\n"
            "    async def adopt(self, envelope):\n"
            "        return await self.engine.adopt(\n"
            "            envelope, envelope['payloads'])\n"
            "def restore(sched, req, payloads):\n"
            "    sched.adopt_swapped(req, payloads)\n"
        )
        assert lint_codebase.lint_role_discipline_file(
            "fake/disagg.py", text=ok) == []

    def test_waiver_suppresses(self):
        ok = (
            "def prefill_probe(pool, space):\n"
            "    pool.swap_in('s', space)  "
            "# trace-lint: ok(loopback self-test)\n"
        )
        assert lint_codebase.lint_role_discipline_file(
            "fake/disagg.py", text=ok) == []

    def test_disagg_file_covered_and_clean(self):
        rel = os.path.join("paddle_tpu", "inference", "disagg.py")
        assert rel in lint_codebase.ROLE_DISCIPLINE_FILES
        assert rel in lint_codebase.HOST_ONLY_FILES
        assert rel in lint_codebase.POOL_API_FILES
        assert os.path.exists(os.path.join(REPO, rel))
        assert lint_codebase.check_role_discipline() == []

    def test_sharded_pool_state_audited(self):
        # the mp-shard geometry is pool state: writes from outside
        # the pool must be caught by the pool-mutation audit
        for attr in ("kv_heads_global", "head_start",
                     "mp_size", "mp_rank"):
            assert attr in lint_codebase._POOL_STATE_ATTRS

    def test_rule_inventory_has_role_discipline(self):
        assert any(rid == "disagg-role-discipline"
                   for rid, _ in lint_codebase.RULES)


class TestKnobDiscipline:
    """Capacity knob-discipline rule (ISSUE 20): the serving-layer
    modules must not mutate the capacity flags (set_flags) or poke
    the scheduler's capacity attrs outside the autotuner apply seam
    (framework/autotuner.py apply_config ->
    BatchScheduler.apply_capacity_config -> engine _pump_tune)."""

    def test_seeded_capacity_set_flags_flagged(self):
        bad = (
            "from paddle_tpu.framework.flags import set_flags\n"
            "def tighten(sched):\n"
            "    set_flags({'prefill_chunk_tokens': 16,\n"
            "               'serving_buckets': '8,16'})\n"
            "    set_flags({'telemetry': 'off'})\n"
        )
        v = lint_codebase.lint_knob_discipline_file(
            "fake/serving.py", text=bad)
        assert len(v) == 1, v
        assert "prefill_chunk_tokens" in v[0]
        assert "serving_buckets" in v[0]
        assert "apply seam" in v[0]

    def test_seeded_capacity_attr_poke_flagged(self):
        bad = (
            "def shrink(sched):\n"
            "    sched.prefill_chunk_tokens = 8\n"
            "def grow(s):\n"
            "    s.serving_buckets = (8, 16)\n"
        )
        v = lint_codebase.lint_knob_discipline_file(
            "fake/engine.py", text=bad)
        assert len(v) == 2, v
        assert ".prefill_chunk_tokens" in v[0]
        assert ".serving_buckets" in v[1]

    def test_seam_functions_allowed(self):
        ok = (
            "class S:\n"
            "    def __init__(self):\n"
            "        self.prefill_chunk_tokens = 64\n"
            "        self.serving_buckets = (8, 16)\n"
            "    def apply_capacity_config(self, cfg):\n"
            "        self.prefill_chunk_tokens = \\\n"
            "            cfg['prefill_chunk_tokens']\n"
            "        self.serving_buckets = cfg['serving_buckets']\n"
            "class E:\n"
            "    def _pump_tune(self, cfg, fut):\n"
            "        self.scheduler.prefill_chunk_tokens = 1\n"
        )
        assert lint_codebase.lint_knob_discipline_file(
            "fake/serving.py", text=ok) == []

    def test_non_capacity_flags_and_attrs_clean(self):
        ok = (
            "from paddle_tpu.framework.flags import set_flags\n"
            "def f(x):\n"
            "    set_flags({'telemetry': 'metrics'})\n"
            "    x.max_batch_size = 4\n"
        )
        assert lint_codebase.lint_knob_discipline_file(
            "fake/serving.py", text=ok) == []

    def test_waiver_suppresses(self):
        ok = (
            "from paddle_tpu.framework.flags import set_flags\n"
            "def probe(sched):\n"
            "    set_flags({'collective_dtype': 'int8'})  "
            "# trace-lint: ok(loopback probe)\n"
            "    sched.serving_buckets = (8,)  "
            "# trace-lint: ok(loopback probe)\n"
        )
        assert lint_codebase.lint_knob_discipline_file(
            "fake/serving.py", text=ok) == []

    def test_capacity_flag_set_matches_autotuner(self):
        from paddle_tpu.framework import autotuner

        assert set(autotuner.CAPACITY_KNOBS) \
            == set(lint_codebase._CAPACITY_FLAGS)

    def test_serving_layers_covered_and_clean(self):
        for rel in (
                os.path.join("paddle_tpu", "inference",
                             "serving.py"),
                os.path.join("paddle_tpu", "inference", "engine.py"),
                os.path.join("paddle_tpu", "framework",
                             "ops_server.py")):
            assert rel in lint_codebase.KNOB_DISCIPLINE_FILES
        assert os.path.join("paddle_tpu", "framework",
                            "autotuner.py") \
            in lint_codebase.HOST_ONLY_FILES
        assert lint_codebase.check_knob_discipline() == []
        assert ("knob-discipline",
                ) in tuple((r[0],) for r in lint_codebase.RULES)
