"""Trace-time program linter (framework/analysis.py + jit integration).

Each of the 5 rule families gets a SEEDED hazard that must fire:
  1. dtype drift        — forced bf16 -> float32 upcast
  2. donation miss      — large written param with donation disabled
  3. collective hazards — psum over a bogus axis; collective in one
                          cond branch
  4. recompilation      — python scalar arg; weak-typed scalar closure
  5. unsharded compute  — over-threshold matmul with replicated
                          operands on a multi-device mesh

Plus the mode contract: FLAGS_jit_lint=strict raises at compile,
'off' is bit-for-bit inert, and the shipped llama/gpt train steps
report ZERO critical findings under 'warn'.
"""
import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as optim
from paddle_tpu.framework import analysis
from paddle_tpu.framework.flags import _REGISTRY as _FLAGS


@contextlib.contextmanager
def flags(**kw):
    saved = {k: _FLAGS[k] for k in kw}
    paddle.set_flags({"FLAGS_" + k: v for k, v in kw.items()})
    try:
        yield
    finally:
        paddle.set_flags({"FLAGS_" + k: v for k, v in saved.items()})


def _rules(report):
    return {f.rule for f in report.findings}


def _x32(shape=(8, 8)):
    return paddle.to_tensor(np.ones(shape, np.float32))


# ---------------------------------------------------------------------------
# rule family 1: dtype drift
# ---------------------------------------------------------------------------

class TestDtypeDrift:
    def test_forced_upcast_fires(self):
        def step(x):
            return (x.astype("float32") * 2.0).sum()

        xb = _x32().astype("bfloat16")
        rep = paddle.jit.analyze(step, xb)
        assert "dtype-drift" in _rules(rep)
        f = next(f for f in rep.findings if f.rule == "dtype-drift")
        assert f.severity == "warning"
        assert "bfloat16" in f.message and "float32" in f.message

    def test_fp32_program_clean(self):
        rep = paddle.jit.analyze(lambda x: (x * 2.0).sum(), _x32())
        assert "dtype-drift" not in _rules(rep)

    def test_accumulation_allowlist(self):
        # bf16 matmul accumulating to f32 via preferred_element_type is
        # the MXU-native pattern — dot_general is allowlisted
        def step(x):
            r = jax.lax.dot_general(
                x._data, x._data, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return paddle.to_tensor(r).sum()

        rep = paddle.jit.analyze(step, _x32().astype("bfloat16"))
        assert "dtype-drift" not in _rules(rep)

    def test_suppression(self):
        def step(x):
            return (x.astype("float32") * 2.0).sum()

        rep = paddle.jit.analyze(step, _x32().astype("bfloat16"),
                                 suppress=("dtype-drift",))
        assert "dtype-drift" not in _rules(rep)
        assert rep.suppressed.get("dtype-drift", 0) >= 1

    def test_unknown_suppression_id_raises(self):
        with pytest.raises(ValueError, match="unknown lint rule"):
            paddle.jit.analyze(lambda x: x, _x32(),
                               suppress=("not-a-rule",))


# ---------------------------------------------------------------------------
# rule family 2: donation misses
# ---------------------------------------------------------------------------

def _sgd_step(model, opt, donate):
    @paddle.jit.to_static(donate_state=donate)
    def step(x):
        loss = (model(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return step


class TestDonationMiss:
    def test_undonated_large_param_fires(self):
        with flags(jit_lint_donation_min_bytes=1024):
            model = nn.Linear(64, 64)  # weight: 16 KiB
            opt = optim.SGD(0.1, parameters=model.parameters())
            step = _sgd_step(model, opt, donate=False)
            rep = paddle.jit.analyze(step, _x32((4, 64)))
        assert "donation-miss" in _rules(rep)
        f = next(f for f in rep.findings if f.rule == "donation-miss")
        assert "donate_state" in f.suggestion

    def test_cpu_backend_skip_respected(self):
        # donation intent on + cpu backend = the deliberate skip in
        # jit/api.py — not a finding
        with flags(jit_lint_donation_min_bytes=1024):
            model = nn.Linear(64, 64)
            opt = optim.SGD(0.1, parameters=model.parameters())
            step = _sgd_step(model, opt, donate=True)
            rep = paddle.jit.analyze(step, _x32((4, 64)))
        assert "donation-miss" not in _rules(rep)

    def test_byte_threshold(self):
        with flags(jit_lint_donation_min_bytes=1 << 30):  # 1 GiB
            model = nn.Linear(64, 64)
            opt = optim.SGD(0.1, parameters=model.parameters())
            step = _sgd_step(model, opt, donate=False)
            rep = paddle.jit.analyze(step, _x32((4, 64)))
        assert "donation-miss" not in _rules(rep)


# ---------------------------------------------------------------------------
# rule family 3: collective hazards
# ---------------------------------------------------------------------------

def _mp_mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:2]).reshape(2), ("mp",))


class TestCollectiveHazards:
    def test_psum_over_missing_axis_is_critical(self):
        from paddle_tpu.distributed.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = _mp_mesh()
        f = shard_map(lambda x: jax.lax.psum(x, "mp"), mesh=mesh,
                      in_specs=P("mp"), out_specs=P())
        closed = jax.make_jaxpr(f)(jnp.ones((2, 4)))

        # program compiled against a mesh whose axes went stale
        rep = analysis.analyze_jaxpr(closed, mesh_axes={"dp"})
        crit = [f for f in rep.findings if f.rule == "collective-axis"]
        assert crit and crit[0].severity == "critical"
        assert "'mp'" in crit[0].message

        # matching mesh: clean
        rep_ok = analysis.analyze_jaxpr(closed, mesh_axes={"mp"})
        assert "collective-axis" not in _rules(rep_ok)

    def test_collective_in_one_cond_branch_is_critical(self):
        from paddle_tpu.distributed.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = _mp_mesh()

        def body(p, x):
            return jax.lax.cond(
                p, lambda v: jax.lax.psum(v, "mp"), lambda v: v * 1.0, x)

        g = shard_map(body, mesh=mesh, in_specs=(P(), P("mp")),
                      out_specs=P("mp"))
        closed = jax.make_jaxpr(g)(jnp.asarray(True), jnp.ones((2, 4)))
        rep = analysis.analyze_jaxpr(closed, mesh_axes={"mp"})
        crit = [f for f in rep.findings
                if f.rule == "collective-branch"]
        assert crit and crit[0].severity == "critical"
        assert "deadlock" in crit[0].message

    def test_collective_in_all_branches_clean(self):
        from paddle_tpu.distributed.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = _mp_mesh()

        def body(p, x):
            return jax.lax.cond(
                p, lambda v: jax.lax.psum(v * 2.0, "mp"),
                lambda v: jax.lax.psum(v, "mp"), x)

        g = shard_map(body, mesh=mesh, in_specs=(P(), P("mp")),
                      out_specs=P())
        closed = jax.make_jaxpr(g)(jnp.asarray(True), jnp.ones((2, 4)))
        rep = analysis.analyze_jaxpr(closed, mesh_axes={"mp"})
        assert "collective-branch" not in _rules(rep)


# ---------------------------------------------------------------------------
# rule family 4: recompilation hazards
# ---------------------------------------------------------------------------

class TestRecompileHazards:
    def test_python_scalar_arg_fires(self):
        rep = paddle.jit.analyze(lambda x, k: x * k, _x32(), 3.5)
        assert "recompile-static-scalar" in _rules(rep)

    def test_python_int_shape_leak_flagged(self):
        rep = paddle.jit.analyze(
            lambda x, n: x.reshape([n, -1]), _x32((8, 4)), 8)
        f = next(f for f in rep.findings
                 if f.rule == "recompile-static-scalar")
        assert "shape leak" in f.message

    def test_weak_scalar_closure_fires(self):
        c = jnp.asarray(2.5)  # weak-typed f32 scalar

        def step(x):
            return x * paddle.to_tensor(c)

        rep = paddle.jit.analyze(step, _x32())
        assert "recompile-weak-scalar" in _rules(rep)

    def test_tensor_args_clean(self):
        rep = paddle.jit.analyze(lambda x, y: x * y, _x32(), _x32())
        assert "recompile-static-scalar" not in _rules(rep)

    def test_monotone_token_growth_fires_serving_shape(self):
        # the unbucketed-prefill signature: the same compiled function
        # fed strictly longer token batches call after call — one full
        # retrace + compile per prompt length
        sf = paddle.jit.to_static(lambda x: (x * 2.0).sum())
        for n in (8, 12, 16, 20):
            sf(_x32((1, n)))
        rep = paddle.jit.analyze(sf)
        assert "recompile-serving-shape" in _rules(rep)
        f = next(f for f in rep.findings
                 if f.rule == "recompile-serving-shape")
        assert f.severity == "warning"
        assert "8 -> 20" in f.message
        assert "bucket" in f.suggestion

    def test_bucketed_shapes_clean(self):
        # a bucketed caller warming up its power-of-two ladder grows
        # GEOMETRICALLY — that is legitimate, not the signature (and
        # repeats are cache hits that add no entries at all)
        sf = paddle.jit.to_static(lambda x: (x * 2.0).sum())
        for n in (8, 16, 32, 64, 16, 8):
            sf(_x32((1, n)))
        rep = paddle.jit.analyze(sf)
        assert "recompile-serving-shape" not in _rules(rep)

    def test_configured_bucket_ladder_clean_even_non_geometric(self):
        # a NON-geometric bucket set is valid config; warming it up in
        # increasing order must not trip the rule — values that are
        # all members of FLAGS_serving_buckets are the sanctioned
        # ladder by definition
        with flags(serving_buckets="8,16,32,48,64"):
            sf = paddle.jit.to_static(lambda x: (x * 2.0).sum())
            for n in (8, 16, 32, 48, 64):
                sf(_x32((1, n)))
            rep = paddle.jit.analyze(sf)
        assert "recompile-serving-shape" not in _rules(rep)

    def test_few_growing_entries_clean(self):
        # 2-3 growing shapes are normal warmup, not a trend
        sf = paddle.jit.to_static(lambda x: (x * 2.0).sum())
        for n in (8, 16, 32):
            sf(_x32((1, n)))
        rep = paddle.jit.analyze(sf)
        assert "recompile-serving-shape" not in _rules(rep)

    def test_serving_shape_suppression(self):
        sf = paddle.jit.to_static(lambda x: (x * 2.0).sum())
        for n in (8, 12, 16, 20):
            sf(_x32((1, n)))
        rep = paddle.jit.analyze(
            sf, suppress=("recompile-serving-shape",))
        assert "recompile-serving-shape" not in _rules(rep)
        assert rep.suppressed.get("recompile-serving-shape", 0) >= 1


# ---------------------------------------------------------------------------
# rule family 5: oversized unsharded compute
# ---------------------------------------------------------------------------

class TestUnshardedCompute:
    def _big_matmul_jaxpr(self):
        return jax.make_jaxpr(lambda a, b: a @ b)(
            jnp.ones((128, 128)), jnp.ones((128, 128)))

    def test_replicated_matmul_fires(self):
        with flags(jit_lint_flops_threshold=1e6):
            rep = analysis.analyze_jaxpr(
                self._big_matmul_jaxpr(), mesh_axes={"dp"},
                mesh_devices=8)
        assert "unsharded-compute" in _rules(rep)

    def test_single_device_clean(self):
        with flags(jit_lint_flops_threshold=1e6):
            rep = analysis.analyze_jaxpr(
                self._big_matmul_jaxpr(), mesh_axes=set(),
                mesh_devices=1)
        assert "unsharded-compute" not in _rules(rep)

    def test_sharding_constraint_silences(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = _mp_mesh()

        def f(a, b):
            a = jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, P("mp", None)))
            return a @ b

        closed = jax.make_jaxpr(f)(jnp.ones((128, 128)),
                                   jnp.ones((128, 128)))
        with flags(jit_lint_flops_threshold=1e6):
            rep = analysis.analyze_jaxpr(closed, mesh_axes={"mp"},
                                         mesh_devices=8)
        assert "unsharded-compute" not in _rules(rep)

    def test_flops_come_from_op_table_estimator(self):
        from paddle_tpu.ops.op_table import get_op

        est = get_op("matmul").flops
        assert est is not None
        assert est(((128, 128), (128, 128))) == 2 * 128 ** 3


# ---------------------------------------------------------------------------
# modes: off inert / warn / strict; report plumbing
# ---------------------------------------------------------------------------

class TestModes:
    def _drift_fn(self):
        def step(x):
            return (x.astype("float32") * 2.0).sum()

        return step

    def test_strict_raises_at_compile(self):
        xb = _x32().astype("bfloat16")
        with flags(jit_lint="strict"):
            sf = paddle.jit.to_static(self._drift_fn())
            with pytest.raises(analysis.JitLintError) as ei:
                sf(xb)
            assert "dtype-drift" in str(ei.value)

    def test_strict_clean_program_compiles(self):
        with flags(jit_lint="strict"):
            sf = paddle.jit.to_static(lambda x: (x * 2.0).sum())
            out = sf(_x32())
        assert np.isfinite(float(np.asarray(out._data)))

    def test_off_is_inert(self):
        xb = _x32().astype("bfloat16")
        with flags(jit_lint="off"):
            sf_off = paddle.jit.to_static(self._drift_fn())
            out_off = sf_off(xb)
            entries = sf_off._finalized_entries()
            assert entries and all(
                "lint_report" not in e for e in entries)
        with flags(jit_lint="warn"):
            sf_warn = paddle.jit.to_static(self._drift_fn())
            out_warn = sf_warn(xb)
            entries_w = sf_warn._finalized_entries()
            assert entries_w and all(
                "lint_report" in e for e in entries_w)
        # identical program either way: the linter only observes
        assert str(entries[0]["pruned_jaxpr"]) \
            == str(entries_w[0]["pruned_jaxpr"])
        assert float(np.asarray(out_off._data)) \
            == float(np.asarray(out_warn._data))

    def test_warn_attaches_report_and_runs(self):
        xb = _x32().astype("bfloat16")
        with flags(jit_lint="warn"):
            sf = paddle.jit.to_static(self._drift_fn())
            out = sf(xb)
        assert np.isfinite(float(np.asarray(out._data)))
        rep = paddle.jit.analyze(sf)  # post-hoc, from the cache
        assert "dtype-drift" in _rules(rep)

    def test_flag_suppression(self):
        xb = _x32().astype("bfloat16")
        with flags(jit_lint_suppress="dtype-drift"):
            rep = paddle.jit.analyze(self._drift_fn(), xb)
        assert "dtype-drift" not in _rules(rep)
        assert rep.suppressed.get("dtype-drift", 0) >= 1

    def test_report_json_roundtrip(self):
        import json

        rep = paddle.jit.analyze(
            self._drift_fn(), _x32().astype("bfloat16"))
        d = json.loads(rep.to_json())
        assert d["program"] and d["n_eqns"] > 0
        assert d["counts"]["warning"] >= 1
        assert any(f["rule"] == "dtype-drift" for f in d["findings"])

    def test_analyze_without_args_needs_compiled(self):
        sf = paddle.jit.to_static(lambda x: x + 1.0)
        with pytest.raises(ValueError, match="example"):
            paddle.jit.analyze(sf)

    def test_analyze_returns_report_under_strict(self):
        # analyze() runs regardless of FLAGS_jit_lint: the flag only
        # governs the automatic compile-time hook, so even under
        # strict it must RETURN the report, not raise
        xb = _x32().astype("bfloat16")
        with flags(jit_lint="strict"):
            rep = paddle.jit.analyze(self._drift_fn(), xb)
        assert "dtype-drift" in _rules(rep)

    def test_strict_lints_entries_compiled_under_off(self):
        # compiled under off (no lint ran, no report cached), then the
        # flag flips to strict: the next call must lint lazily and fail
        xb = _x32().astype("bfloat16")
        sf = paddle.jit.to_static(self._drift_fn())
        with flags(jit_lint="off"):
            sf(xb)
        with flags(jit_lint="strict"):
            with pytest.raises(analysis.JitLintError):
                sf(xb)

    def test_live_summaries_inert_under_off(self):
        # 'off skips analysis entirely' extends to the bench-artifact
        # path: no rows, no late lint passes
        sf = paddle.jit.to_static(lambda x: (x * 3.0).sum())
        with flags(jit_lint="off"):
            sf(_x32())
            assert analysis.live_lint_summaries() == []


# ---------------------------------------------------------------------------
# end-to-end: the shipped model train steps are lint-clean
# ---------------------------------------------------------------------------

def _train_step_report(model_cls, cfg):
    paddle.seed(0)
    model = model_cls(cfg)
    opt = optim.AdamW(1e-3, parameters=model.parameters())
    opt._create_accumulators()

    @paddle.jit.to_static
    def step(x, y):
        _, loss = model(x, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.RandomState(0)
    x = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (2, 16)).astype("int32"))
    y = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (2, 16)).astype("int64"))
    with flags(jit_lint="warn"):
        loss = step(x, y)
    assert np.isfinite(float(np.asarray(loss._data)))
    return paddle.jit.analyze(step)


class TestEndToEnd:
    def test_llama_train_step_zero_critical(self):
        from paddle_tpu.models import LlamaForCausalLM, llama_tiny

        rep = _train_step_report(LlamaForCausalLM, llama_tiny())
        assert rep.critical() == [], rep

    def test_gpt_train_step_zero_critical(self):
        from paddle_tpu.models import GPTForCausalLM, gpt_tiny

        rep = _train_step_report(GPTForCausalLM, gpt_tiny())
        assert rep.critical() == [], rep


# ---------------------------------------------------------------------------
# CLI + live summaries
# ---------------------------------------------------------------------------

class TestReporting:
    def test_live_lint_summaries(self):
        sf = paddle.jit.to_static(lambda x: (x * 2.0).sum())
        sf(_x32())
        rows = analysis.live_lint_summaries()
        assert rows and all("program" in r and "critical" in r
                            for r in rows)

    def test_cli_json(self, tmp_path):
        import json
        import os
        import subprocess
        import sys

        script = tmp_path / "entry.py"
        script.write_text(
            "import numpy as np\n"
            "import paddle_tpu as paddle\n"
            "@paddle.jit.to_static\n"
            "def step(x):\n"
            "    return (x.astype('float32') * 2.0).sum()\n"
            "xb = paddle.to_tensor(\n"
            "    np.ones((4, 4), np.float32)).astype('bfloat16')\n"
            "step(xb)\n"
        )
        out = tmp_path / "report.json"
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.framework.analysis",
             str(script), "--json", str(out)],
            capture_output=True, text=True, timeout=300, env=env,
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
        )
        assert r.returncode == 0, r.stdout + r.stderr
        payload = json.loads(out.read_text())
        assert payload["version"] == 1
        progs = payload["programs"]
        assert progs and any(
            f["rule"] == "dtype-drift"
            for p in progs for f in p["findings"])


# ---------------------------------------------------------------------------
# rule family 6: overlap-miss (collective-matmul satellite)
# ---------------------------------------------------------------------------

class TestOverlapMiss:
    """A blocking all_gather whose sole consumer is an over-threshold
    dot_general is the dependent pair FLAGS_collective_matmul would
    decompose — the linter must point at it."""

    def _ag_dot_jaxpr(self):
        from paddle_tpu.distributed.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = _mp_mesh()

        def local(xl, wl):
            g = jax.lax.all_gather(xl, "mp", axis=0, tiled=True)
            return jnp.matmul(g, wl)

        f = shard_map(local, mesh=mesh,
                      in_specs=(P("mp", None), P(None, None)),
                      out_specs=P(None, None))
        return jax.make_jaxpr(f)(
            jnp.ones((8, 16), jnp.float32),
            jnp.ones((16, 8), jnp.float32))

    def test_seeded_ag_dot_pair_fires(self):
        with flags(collective_matmul_min_bytes=1):
            rep = analysis.analyze_jaxpr(
                self._ag_dot_jaxpr(), mesh_axes={"mp"})
        f = next(f for f in rep.findings if f.rule == "overlap-miss")
        assert f.severity == "warning"
        assert "collective_matmul" in f.suggestion

    def test_below_threshold_clean(self):
        with flags(collective_matmul_min_bytes=1 << 30):
            rep = analysis.analyze_jaxpr(
                self._ag_dot_jaxpr(), mesh_axes={"mp"})
        assert "overlap-miss" not in _rules(rep)

    def test_decomposed_ring_clean(self):
        # the ring replacement (ppermute chunks, no blocking gather)
        # must NOT fire the rule
        from paddle_tpu.distributed.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.ops.kernels import collective_matmul as cm

        mesh = _mp_mesh()

        def local(xl, wl):
            return cm.all_gather_matmul(
                xl, wl, axis_name="mp", axis_size=2, gather_axis=0)

        f = shard_map(local, mesh=mesh,
                      in_specs=(P("mp", None), P(None, None)),
                      out_specs=P(None, None))
        closed = jax.make_jaxpr(f)(
            jnp.ones((8, 16), jnp.float32),
            jnp.ones((16, 8), jnp.float32))
        with flags(collective_matmul_min_bytes=1):
            rep = analysis.analyze_jaxpr(closed, mesh_axes={"mp"})
        assert "overlap-miss" not in _rules(rep)

    def test_gather_with_second_consumer_clean(self):
        # the gathered value escaping to a second consumer is not the
        # pure dependent pair (decomposition would change live ranges)
        from paddle_tpu.distributed.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = _mp_mesh()

        def local(xl, wl):
            g = jax.lax.all_gather(xl, "mp", axis=0, tiled=True)
            return jnp.matmul(g, wl) + g[:, :8]

        f = shard_map(local, mesh=mesh,
                      in_specs=(P("mp", None), P(None, None)),
                      out_specs=P(None, None))
        closed = jax.make_jaxpr(f)(
            jnp.ones((8, 16), jnp.float32),
            jnp.ones((16, 8), jnp.float32))
        with flags(collective_matmul_min_bytes=1):
            rep = analysis.analyze_jaxpr(closed, mesh_axes={"mp"})
        assert "overlap-miss" not in _rules(rep)

    def test_suppression(self):
        with flags(collective_matmul_min_bytes=1):
            rep = analysis.analyze_jaxpr(
                self._ag_dot_jaxpr(), mesh_axes={"mp"},
                suppress=("overlap-miss",))
        assert "overlap-miss" not in _rules(rep)
        assert rep.suppressed.get("overlap-miss", 0) >= 1
