"""Static resource planner (framework/planner.py + jit integration).

Golden-value coverage of the lifetime pass (donation honored, alias
dedup, weak-const exclusion), the collective byte model (ring ppermute
hops match the chunk schedule exactly, all-reduce factor 2x(ws-1)/ws),
the four planner rules (seeded over-budget / comm-bound /
dead-collective programs caught under FLAGS_jit_plan=strict and
suppressible per scope), the off-mode zero-allocation contract, the
``paddle.jit.plan()`` API, and the CLI ``--plan --json`` round trip.
"""
import contextlib
import tracemalloc

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework import analysis, planner
from paddle_tpu.framework.flags import _REGISTRY as _FLAGS

U = 256 * 256 * 4  # bytes of one (256, 256) float32 buffer


@contextlib.contextmanager
def flags(**kw):
    saved = {k: _FLAGS[k] for k in kw}
    paddle.set_flags({"FLAGS_" + k: v for k, v in kw.items()})
    try:
        yield
    finally:
        paddle.set_flags({"FLAGS_" + k: v for k, v in saved.items()})


def _x32(shape=(8, 8)):
    return paddle.to_tensor(np.ones(shape, np.float32))


def _ones(shape=(256, 256)):
    return jnp.ones(shape, jnp.float32)


def _mp_mesh(n=2):
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n]).reshape(n), ("mp",))


def _rules(report):
    return {f.rule for f in report.findings}


# ---------------------------------------------------------------------------
# golden values: the buffer-lifetime pass
# ---------------------------------------------------------------------------

class TestLifetimeGolden:
    def test_matmul_add_peak(self):
        # c = a @ b; d = c + a: peak is at d's allocation, when a, b,
        # c, d are all simultaneously live = 4 buffers exactly
        closed = jax.make_jaxpr(lambda a, b: (a @ b) + a)(
            _ones(), _ones())
        plan, _ = planner.plan_jaxpr(closed, name="golden")
        assert plan.hbm_peak_bytes == 4 * U
        assert plan.input_bytes == 2 * U
        assert plan.output_bytes == U
        assert plan.transient_peak_bytes == U  # c only; d is an output
        assert plan.const_bytes == 0
        assert plan.flops_total == 2.0 * 256 ** 3
        assert plan.comm_bytes_total == 0
        assert plan.flops_per_comm_byte is None

    def test_donation_alias_elides_state_update(self):
        # s' = s + g with s donated and aliased into its own output
        # slot (the jit/api.py in-place update): the update allocates
        # NOTHING new — peak drops from 3 buffers to 2
        closed = jax.make_jaxpr(lambda s, g: s + g)(_ones(), _ones())
        plain, _ = planner.plan_jaxpr(closed, name="no_donate")
        assert plain.hbm_peak_bytes == 3 * U
        assert plain.output_bytes == U

        donated, _ = planner.plan_jaxpr(
            closed, name="donated", donated_invars=(0,),
            alias_out_to_in={0: 0})
        assert donated.hbm_peak_bytes == 2 * U
        assert donated.donated_bytes == U
        assert donated.input_bytes == U
        assert donated.output_bytes == 0  # no NEW bytes: the alias

    def test_donated_input_freed_at_last_use(self):
        # a is donated and dead after the first eqn: the second
        # allocation reuses its bytes, so peak stays at 3 buffers
        # (a+b live, then b + t + out) instead of 4
        def f(a, b):
            t = a * 2.0
            return t + b

        closed = jax.make_jaxpr(f)(_ones(), _ones())
        plain, _ = planner.plan_jaxpr(closed, name="plain")
        donated, _ = planner.plan_jaxpr(closed, name="donated",
                                        donated_invars=(0,))
        assert plain.hbm_peak_bytes == 4 * U
        assert donated.hbm_peak_bytes == 3 * U

    def test_alias_dedup_and_passthrough(self):
        # (x, y, x): the duplicated passthrough output allocates
        # nothing — output bytes are y alone
        closed = jax.make_jaxpr(lambda x: (x, x * 2.0, x))(_ones())
        plan, _ = planner.plan_jaxpr(closed, name="dedup")
        assert plan.output_bytes == U
        assert plan.hbm_peak_bytes == 2 * U

    def test_weak_const_excluded(self):
        weak = jnp.asarray(2.5)          # weak-typed scalar
        wide = jnp.ones((16, 16), jnp.float32)  # a real const buffer

        closed = jax.make_jaxpr(lambda x: x * weak + wide)(
            jnp.ones((16, 16), jnp.float32))
        plan, _ = planner.plan_jaxpr(closed, name="consts")
        assert plan.weak_consts_excluded == 1
        assert plan.const_bytes == 16 * 16 * 4

    def test_intermediate_freed_at_last_use(self):
        # a long chain keeps only one intermediate live at a time:
        # peak = input + 2 intermediates (the allocate-then-free
        # moment), NOT input + chain length
        def f(x):
            for _ in range(8):
                x = x * 1.5
            return x

        closed = jax.make_jaxpr(f)(_ones())
        plan, _ = planner.plan_jaxpr(closed, name="chain")
        assert plan.hbm_peak_bytes == 3 * U

    def test_to_dict_roundtrip(self):
        import json

        closed = jax.make_jaxpr(lambda a, b: (a @ b) + a)(
            _ones(), _ones())
        plan, _ = planner.plan_jaxpr(closed, name="json")
        d = json.loads(plan.to_json())
        assert d["hbm_peak_bytes"] == 4 * U
        assert d["program"] == "json"
        kinds = {b["kind"] for b in d["largest_buffers"]}
        assert "input" in kinds and "output" in kinds


# ---------------------------------------------------------------------------
# golden values: the collective byte model
# ---------------------------------------------------------------------------

class TestCommGolden:
    def _shmapped(self, body, n_in=1, shape=(8, 8)):
        from paddle_tpu.distributed.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = _mp_mesh()
        f = shard_map(body, mesh=mesh,
                      in_specs=tuple([P("mp", None)] * n_in),
                      out_specs=P("mp", None))
        return jax.make_jaxpr(f)(
            *[jnp.ones(shape, jnp.float32)] * n_in)

    def test_psum_all_reduce_factor(self):
        # ring all-reduce moves 2 x (ws-1)/ws of the operand: local
        # (4, 8) f32 = 128 B on mp2 -> exactly 128 wire bytes
        closed = self._shmapped(lambda x: jax.lax.psum(x, "mp") + x)
        plan, _ = planner.plan_jaxpr(closed, name="psum",
                                     mesh_axis_sizes={"mp": 2})
        assert plan.comm_bytes_by_axis == {"mp": 128}
        c = plan.collectives[0]
        assert c.prim == "psum" and c.axis_size == 2
        assert not c.ring_chunk

    def test_all_gather_output_side(self):
        # gather receives the other ws-1 shards: output (8, 8) f32 =
        # 256 B x 1/2 = 128 wire bytes
        def body(x):
            g = jax.lax.all_gather(x, "mp", axis=0, tiled=True)
            return g[:4] * 1.0

        closed = self._shmapped(body)
        plan, _ = planner.plan_jaxpr(closed, name="ag",
                                     mesh_axis_sizes={"mp": 2})
        assert plan.comm_bytes_by_axis == {"mp": 128}

    def test_ring_chunks_match_chunk_schedule_exactly(self):
        # the PR-4 decomposed ring: ws-1 ppermute hops each moving
        # this device's full x-chunk — the bench asserts the same
        # equality at headline shapes (bench.py tp_overlap arm)
        from paddle_tpu.ops.kernels import collective_matmul as cm

        ws = 2
        rows, k, n = 16, 8, 4

        def body(x, w):
            return cm.all_gather_matmul(
                x, w, axis_name="mp", axis_size=ws, gather_axis=0)

        from paddle_tpu.distributed.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = _mp_mesh(ws)
        f = shard_map(body, mesh=mesh,
                      in_specs=(P("mp", None), P(None, None)),
                      out_specs=P(None, None))
        closed = jax.make_jaxpr(f)(
            jnp.ones((rows, k), jnp.float32),
            jnp.ones((k, n), jnp.float32))
        plan, _ = planner.plan_jaxpr(closed, name="ring",
                                     mesh_axis_sizes={"mp": ws})
        chunk_bytes = (rows // ws) * k * 4
        assert plan.comm_bytes_by_axis == {"mp": (ws - 1) * chunk_bytes}
        assert plan.ring_chunks_by_axis == {"mp": ws - 1}
        assert all(c.ring_chunk for c in plan.collectives)

    def test_size_one_axis_moves_nothing(self):
        # a collective over a degree-1 axis has no wire: it must not
        # leave a zero-byte entry behind (which would make
        # comm_bytes_by_axis truthy with a None flops/comm ratio —
        # print(plan) and the artifact rows crashed on exactly this)
        from paddle_tpu.distributed.mesh import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("mp",))
        f = shard_map(lambda x: jax.lax.psum(x, "mp"), mesh=mesh,
                      in_specs=P("mp", None), out_specs=P(None, None))
        closed = jax.make_jaxpr(f)(jnp.ones((8, 8), jnp.float32))
        plan, _ = planner.plan_jaxpr(closed, name="deg1",
                                     mesh_axis_sizes={"mp": 1})
        assert plan.collectives == []
        assert plan.comm_bytes_by_axis == {}
        assert plan.flops_per_comm_byte is None
        str(plan)  # format() must not raise
        rows_plan = plan.to_dict()
        assert rows_plan["flops_per_comm_byte"] is None

    def test_scan_multiplies_trip_count(self):
        def body(x):
            def step(c, _):
                return jax.lax.psum(c, "mp"), None

            out, _ = jax.lax.scan(step, x, None, length=5)
            return out

        closed = self._shmapped(body)
        plan, _ = planner.plan_jaxpr(closed, name="scan",
                                     mesh_axis_sizes={"mp": 2})
        assert plan.comm_bytes_by_axis == {"mp": 5 * 128}

    def test_flops_per_comm_byte(self):
        from paddle_tpu.distributed.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = _mp_mesh()

        def body(x, w):
            g = jax.lax.all_gather(x, "mp", axis=0, tiled=True)
            return (g @ w)[:4]

        f = shard_map(body, mesh=mesh,
                      in_specs=(P("mp", None), P(None, None)),
                      out_specs=P("mp", None))
        closed = jax.make_jaxpr(f)(
            jnp.ones((8, 8), jnp.float32),
            jnp.ones((8, 4), jnp.float32))
        plan, _ = planner.plan_jaxpr(closed, name="ratio",
                                     mesh_axis_sizes={"mp": 2})
        assert plan.comm_bytes_total == 128  # gather 256 B x 1/2
        assert plan.flops_total == 2.0 * 8 * 8 * 4
        assert plan.flops_per_comm_byte == pytest.approx(512 / 128)


# ---------------------------------------------------------------------------
# the four planner rules
# ---------------------------------------------------------------------------

class TestPlannerRules:
    def test_hbm_over_budget_strict_raises_at_compile(self):
        with flags(jit_plan="strict", jit_budget_hbm=64):
            sf = paddle.jit.to_static(lambda x: (x * 2.0).sum())
            with pytest.raises(planner.JitPlanError) as ei:
                sf(_x32((64, 64)))
            assert "hbm-over-budget" in str(ei.value)
            assert "FLAGS_jit_budget_hbm" in str(ei.value)

    def test_report_mode_never_raises(self):
        with flags(jit_plan="report", jit_budget_hbm=64):
            sf = paddle.jit.to_static(lambda x: (x * 2.0).sum())
            out = sf(_x32((64, 64)))
        assert np.isfinite(float(np.asarray(out._data)))
        entry = sf._finalized_entries()[0]
        rep = entry["plan_report"]
        assert "hbm-over-budget" in _rules(rep)

    def test_budget_zero_disables(self):
        with flags(jit_plan="strict", jit_budget_hbm=0):
            sf = paddle.jit.to_static(lambda x: (x * 2.0).sum())
            sf(_x32((64, 64)))  # must not raise

    def test_global_flag_suppression(self):
        with flags(jit_plan="strict", jit_budget_hbm=64,
                   jit_lint_suppress="hbm-over-budget"):
            sf = paddle.jit.to_static(lambda x: (x * 3.0).sum())
            sf(_x32((64, 64)))  # suppressed: compiles
        entry = sf._finalized_entries()[0]
        assert entry["plan_report"].suppressed.get(
            "hbm-over-budget", 0) >= 1

    def test_per_function_suppression(self):
        with flags(jit_plan="strict", jit_budget_hbm=64):
            sf = paddle.jit.to_static(
                lambda x: (x * 4.0).sum(),
                lint_suppress=("hbm-over-budget",))
            sf(_x32((64, 64)))  # suppressed: compiles

    def test_comm_over_budget(self):
        from paddle_tpu.distributed.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = _mp_mesh()
        f = shard_map(lambda x: jax.lax.psum(x, "mp"), mesh=mesh,
                      in_specs=P("mp", None), out_specs=P(None, None))
        closed = jax.make_jaxpr(f)(jnp.ones((8, 8), jnp.float32))
        with flags(jit_budget_comm=16):
            _, rep = planner.plan_jaxpr(closed, name="comm",
                                        mesh_axis_sizes={"mp": 2})
        assert "comm-over-budget" in _rules(rep)
        f = next(f for f in rep.findings
                 if f.rule == "comm-over-budget")
        assert f.severity == "critical"
        with flags(jit_budget_comm=16):
            with pytest.raises(planner.JitPlanError):
                planner.emit_plan_report(rep, "strict")

    def test_comm_bound_program_fires_on_fp32_collectives(self):
        from paddle_tpu.distributed.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = _mp_mesh()
        # pure communication, no flops: ratio 0 < any threshold
        f = shard_map(lambda x: jax.lax.psum(x, "mp"), mesh=mesh,
                      in_specs=P("mp", None), out_specs=P(None, None))
        closed = jax.make_jaxpr(f)(jnp.ones((8, 8), jnp.float32))
        with flags(jit_plan_comm_bound_ratio=8.0):
            _, rep = planner.plan_jaxpr(closed, name="bound",
                                        mesh_axis_sizes={"mp": 2})
        assert "comm-bound-program" in _rules(rep)
        f = next(f for f in rep.findings
                 if f.rule == "comm-bound-program")
        assert "quantized" in f.message

    def test_comm_bound_quiet_on_bf16_wire(self):
        from paddle_tpu.distributed.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = _mp_mesh()
        f = shard_map(lambda x: jax.lax.psum(x, "mp"), mesh=mesh,
                      in_specs=P("mp", None), out_specs=P(None, None))
        closed = jax.make_jaxpr(f)(jnp.ones((8, 8), jnp.bfloat16))
        with flags(jit_plan_comm_bound_ratio=8.0):
            _, rep = planner.plan_jaxpr(closed, name="bf16",
                                        mesh_axis_sizes={"mp": 2})
        assert "comm-bound-program" not in _rules(rep)

    def test_comm_bound_threshold_zero_disables(self):
        from paddle_tpu.distributed.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = _mp_mesh()
        f = shard_map(lambda x: jax.lax.psum(x, "mp"), mesh=mesh,
                      in_specs=P("mp", None), out_specs=P(None, None))
        closed = jax.make_jaxpr(f)(jnp.ones((8, 8), jnp.float32))
        with flags(jit_plan_comm_bound_ratio=0.0):
            _, rep = planner.plan_jaxpr(closed, name="off",
                                        mesh_axis_sizes={"mp": 2})
        assert "comm-bound-program" not in _rules(rep)

    def _dead_psum_jaxpr(self):
        from paddle_tpu.distributed.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = _mp_mesh()

        def body(x):
            _ = jax.lax.psum(x, "mp")
            return x * 2.0

        f = shard_map(body, mesh=mesh, in_specs=P("mp", None),
                      out_specs=P("mp", None))
        return jax.make_jaxpr(f)(jnp.ones((8, 8), jnp.float32))

    def test_dead_collective_detected(self):
        plan, rep = planner.plan_jaxpr(
            self._dead_psum_jaxpr(), name="dead",
            mesh_axis_sizes={"mp": 2})
        assert plan.dead_collectives and \
            plan.dead_collectives[0][0] == "psum"
        assert "dead-collective" in _rules(rep)
        with pytest.raises(planner.JitPlanError):
            planner.emit_plan_report(rep, "strict")

    def test_dead_collective_suppressible_per_call(self):
        _, rep = planner.plan_jaxpr(
            self._dead_psum_jaxpr(), name="dead",
            mesh_axis_sizes={"mp": 2},
            suppress=("dead-collective", "comm-bound-program"))
        assert "dead-collective" not in _rules(rep)
        assert rep.suppressed.get("dead-collective", 0) >= 1
        planner.emit_plan_report(rep, "strict")  # nothing blocking

    def test_consumed_collective_clean(self):
        from paddle_tpu.distributed.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = _mp_mesh()
        f = shard_map(lambda x: jax.lax.psum(x, "mp") * 2.0,
                      mesh=mesh, in_specs=P("mp", None),
                      out_specs=P(None, None))
        closed = jax.make_jaxpr(f)(jnp.ones((8, 8), jnp.float32))
        plan, rep = planner.plan_jaxpr(closed, name="live",
                                       mesh_axis_sizes={"mp": 2})
        assert plan.dead_collectives == []
        assert "dead-collective" not in _rules(rep)

    def test_planner_rules_in_inventory_group(self):
        inv = analysis.static_check_inventory()
        ids = {r["rule_id"] for r in inv["planner"]}
        assert ids == {"hbm-over-budget", "comm-over-budget",
                       "comm-bound-program", "dead-collective",
                       "wire-savings-miss"}
        jaxpr_ids = {r["rule_id"] for r in inv["jaxpr"]}
        assert not (ids & jaxpr_ids)
        # the comm-bound inventory row documents its dtype-awareness
        row = next(r for r in inv["planner"]
                   if r["rule_id"] == "comm-bound-program")
        assert "quantized" in row["summary"].lower()


# ---------------------------------------------------------------------------
# quantized-wire planning (ISSUE 14): dtype-aware bytes, no false
# comm-bound flag on quantized rings, verify_wire_savings assertion
# ---------------------------------------------------------------------------

class TestQuantizedWirePlanning:
    def _ring_ar_jaxpr(self, wire, n=2, shape=(8, 64)):
        import functools

        from paddle_tpu.ops.kernels import collective_matmul as cm
        from paddle_tpu.distributed.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = _mp_mesh(n)
        f = shard_map(
            functools.partial(cm.ring_all_reduce, axis_name="mp",
                              axis_size=n, wire=wire),
            mesh=mesh, in_specs=P("mp", None),
            out_specs=P("mp", None))
        return jax.make_jaxpr(f)(jnp.ones(shape, jnp.float32))

    def _plan(self, wire, **kw):
        plan, rep = planner.plan_jaxpr(
            self._ring_ar_jaxpr(wire), name="ring_" + wire,
            mesh_axis_sizes={"mp": 2}, **kw)
        return plan, rep

    def test_comm_bound_seeded_both_ways(self):
        # fp wire: a pure-communication ring MUST fire comm-bound —
        # the same ring with its wire quantized MUST NOT (the >=4-byte
        # collectives left are the f32 scale sidecars)
        with flags(jit_plan_comm_bound_ratio=8.0):
            _, rep_fp = self._plan("off")
            plan_q, rep_q = self._plan("int8")
        assert "comm-bound-program" in _rules(rep_fp)
        assert "comm-bound-program" not in _rules(rep_q)
        assert plan_q.comm_bytes_quantized > 0

    def test_quantized_bytes_match_chunk_schedule_exactly(self):
        from paddle_tpu.ops.kernels import collective_matmul as cm

        plan_q, _ = self._plan("int8")
        plan_fp, _ = self._plan("off")
        ws = 2
        n_loc = (8 // ws) * 64          # 256 elements per device
        chunk_elems = n_loc // ws       # 128 per ring chunk
        pay, sc = cm.wire_chunk_bytes((chunk_elems,), "int8")
        # RS: ws-1 hops of (payload + sidecar); AG: (ws-1)/ws of the
        # gathered int8 payload and of the f32 sidecar
        sched = (ws - 1) * (pay + sc) \
            + (n_loc * 1) * (ws - 1) // ws \
            + (ws * sc) * (ws - 1) // ws
        assert plan_q.comm_bytes_total == sched, (
            plan_q.comm_bytes_total, sched)
        # fp reference: ws-1 fp hops + (ws-1)/ws of the fp gather
        sched_fp = (ws - 1) * chunk_elems * 4 \
            + n_loc * 4 * (ws - 1) // ws
        assert plan_fp.comm_bytes_total == sched_fp
        assert plan_q.comm_bytes_quantized == \
            (ws - 1) * pay + n_loc * (ws - 1) // ws

    def test_verify_wire_savings_passes(self):
        plan_q, _ = self._plan("int8")
        plan_fp, _ = self._plan("off")
        with flags(jit_plan="strict"):
            ratio, rep = planner.verify_wire_savings(
                plan_q, plan_fp, max_ratio=0.55)
        assert rep.findings == []
        assert ratio is not None and ratio <= 0.55

    def test_verify_wire_savings_seeded_miss(self):
        plan_q, _ = self._plan("int8")
        plan_fp, _ = self._plan("off")
        with flags(jit_plan="strict"):
            with pytest.raises(planner.JitPlanError):
                planner.verify_wire_savings(
                    plan_q, plan_fp, max_ratio=0.01)
        with flags(jit_plan="report"):
            ratio, rep = planner.verify_wire_savings(
                plan_q, plan_fp, max_ratio=0.01)
        assert "wire-savings-miss" in _rules(rep)

    def test_verify_wire_savings_unquantized_arm_is_a_miss(self):
        # a 'quantized' arm that never quantized (no sub-2-byte
        # traffic) is the purest savings miss
        plan_fp, _ = self._plan("off")
        with flags(jit_plan="report"):
            _, rep = planner.verify_wire_savings(
                plan_fp, plan_fp, max_ratio=0.55)
        assert "wire-savings-miss" in _rules(rep)

    def test_verify_accepts_jaxprs(self):
        with flags(jit_plan="report"):
            ratio, rep = planner.verify_wire_savings(
                self._ring_ar_jaxpr("int8"),
                self._ring_ar_jaxpr("off"),
                mesh_axis_sizes={"mp": 2}, max_ratio=0.55)
        assert rep.findings == []
        assert ratio is not None and ratio <= 0.55

    def test_plan_dict_carries_quantized_bytes(self):
        plan_q, _ = self._plan("int8")
        d = plan_q.to_dict()
        assert d["comm_bytes_quantized"] == plan_q.comm_bytes_quantized
        assert 0 < d["comm_bytes_quantized"] < d["comm_bytes_total"]


# ---------------------------------------------------------------------------
# modes: off is zero-cost, report attaches, plan() API
# ---------------------------------------------------------------------------

class TestModes:
    def test_off_mode_attaches_nothing(self):
        with flags(jit_plan="off"):
            sf = paddle.jit.to_static(lambda x: (x * 2.0).sum())
            sf(_x32())
            entries = sf._finalized_entries()
            assert entries and all(
                "resource_plan" not in e for e in entries)
            assert planner.live_plan_summaries() == []

    def test_report_mode_attaches_plan(self):
        with flags(jit_plan="report"):
            sf = paddle.jit.to_static(lambda x: (x * 2.0).sum())
            sf(_x32())
        entry = sf._finalized_entries()[0]
        plan = entry["resource_plan"]
        assert plan.hbm_peak_bytes > 0
        rows = planner.live_plan_summaries()
        assert any(r["program"] == "<lambda>" and
                   r["hbm_peak_bytes"] == plan.hbm_peak_bytes
                   for r in rows)

    def test_off_mode_allocates_nothing_in_planner(self):
        # the zero-cost-off contract (same discipline as the linter /
        # sanitizer / telemetry): under FLAGS_jit_plan=off a compile
        # attributes LITERALLY zero allocations to planner.py
        with flags(jit_plan="off"):
            sf = paddle.jit.to_static(lambda x: (x * 5.0).sum())
            x = _x32((16, 16))
            tracemalloc.start()
            snap0 = tracemalloc.take_snapshot()
            sf(x)
            snap1 = tracemalloc.take_snapshot()
            tracemalloc.stop()
        filt = [tracemalloc.Filter(True, planner.__file__)]
        blocks = sum(
            s.size for s in snap1.filter_traces(filt).statistics(
                "filename"))
        blocks0 = sum(
            s.size for s in snap0.filter_traces(filt).statistics(
                "filename"))
        assert blocks - blocks0 == 0, (
            "FLAGS_jit_plan=off allocated %d bytes in planner.py"
            % (blocks - blocks0))

    def test_report_mode_does_allocate(self):
        # teeth for the gate above: the same probe sees planner
        # allocations when the mode is on
        with flags(jit_plan="report"):
            sf = paddle.jit.to_static(lambda x: (x * 6.0).sum())
            x = _x32((16, 16))
            tracemalloc.start()
            sf(x)
            snap1 = tracemalloc.take_snapshot()
            tracemalloc.stop()
        filt = [tracemalloc.Filter(True, planner.__file__)]
        assert sum(s.size for s in snap1.filter_traces(
            filt).statistics("filename")) > 0

    def test_plan_api_with_example_args(self):
        plan = paddle.jit.plan(lambda a, b: (a @ b) + a,
                               _x32((256, 256)), _x32((256, 256)))
        assert plan.hbm_peak_bytes == 4 * U
        assert plan.flops_total == 2.0 * 256 ** 3

    def test_plan_api_on_compiled_variants(self):
        sf = paddle.jit.to_static(lambda x: (x * 2.0).sum())
        sf(_x32((4, 4)))
        sf(_x32((8, 8)))
        plans = paddle.jit.plan(sf)
        assert isinstance(plans, list) and len(plans) == 2
        assert {p.input_bytes for p in plans} == {64, 256}

    def test_plan_api_without_args_needs_compiled(self):
        sf = paddle.jit.to_static(lambda x: x + 1.0)
        with pytest.raises(ValueError, match="example"):
            paddle.jit.plan(sf)

    def test_plan_runs_even_under_flag_off(self):
        with flags(jit_plan="off"):
            plan = paddle.jit.plan(lambda x: (x * 2.0).sum(), _x32())
        assert plan.hbm_peak_bytes > 0

    def test_donated_state_step_plan(self):
        # the to_static state-donation layout flows into the plan:
        # on the CPU backend donation is deliberately off (jit/api),
        # so the plan reports the written state as plain inputs
        import paddle_tpu.nn as nn
        import paddle_tpu.optimizer as optim

        paddle.seed(0)
        model = nn.Linear(32, 32)
        opt = optim.SGD(0.1, parameters=model.parameters())

        @paddle.jit.to_static
        def step(x):
            loss = (model(x) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        step(_x32((4, 32)))
        plan = paddle.jit.plan(step)
        param_bytes = sum(
            int(np.prod(p._data.shape)) * p._data.dtype.itemsize
            for p in model.parameters())
        assert plan.hbm_peak_bytes >= plan.input_bytes >= param_bytes
        assert plan.output_bytes > 0
        assert plan.flops_total > 0


# ---------------------------------------------------------------------------
# end-to-end: the shipped model configs plan sanely
# ---------------------------------------------------------------------------

def _train_step_plan(model_cls, cfg):
    import paddle_tpu.optimizer as optim

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
    step(x, y)
    plan = paddle.jit.plan(step)
    param_bytes = sum(
        int(np.prod(p._data.shape)) * p._data.dtype.itemsize
        for p in model.parameters())
    return plan, param_bytes


class TestModelPlans:
    """The shipped example configs produce coherent plans: peak
    covers at least params + optimizer moments + grads (all are
    program inputs/outputs on the cpu backend), outputs carry the
    full updated state, and a single-host trace plans zero comm."""

    def test_llama_train_step(self):
        from paddle_tpu.models import LlamaForCausalLM, llama_tiny

        plan, param_bytes = _train_step_plan(
            LlamaForCausalLM, llama_tiny())
        # params + 2 Adam moments ride as state inputs; grads +
        # updated state as outputs
        assert plan.input_bytes >= 3 * param_bytes
        assert plan.output_bytes >= 2 * param_bytes
        assert plan.hbm_peak_bytes >= plan.input_bytes
        assert plan.flops_total > 0
        assert plan.comm_bytes_total == 0

    def test_gpt_train_step(self):
        from paddle_tpu.models import GPTForCausalLM, gpt_tiny

        plan, param_bytes = _train_step_plan(
            GPTForCausalLM, gpt_tiny())
        assert plan.input_bytes >= 3 * param_bytes
        assert plan.hbm_peak_bytes >= plan.input_bytes

    def test_mixtral_moe_step(self):
        from paddle_tpu.models import LlamaForCausalLM, mixtral_tiny

        plan, param_bytes = _train_step_plan(
            LlamaForCausalLM, mixtral_tiny())
        assert plan.input_bytes >= 3 * param_bytes
        assert plan.hbm_peak_bytes >= plan.input_bytes


# ---------------------------------------------------------------------------
# CLI: --plan --json round trip
# ---------------------------------------------------------------------------

class TestCLI:
    def test_cli_plan_json(self, tmp_path):
        import json
        import os
        import subprocess
        import sys

        script = tmp_path / "entry.py"
        script.write_text(
            "import numpy as np\n"
            "import paddle_tpu as paddle\n"
            "@paddle.jit.to_static\n"
            "def step(a, b):\n"
            "    return (a @ b + a).sum()\n"
            "x = paddle.to_tensor(np.ones((64, 64), np.float32))\n"
            "step(x, x)\n"
        )
        out = tmp_path / "report.json"
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.framework.analysis",
             str(script), "--plan", "--json", str(out)],
            capture_output=True, text=True, timeout=300, env=env,
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
        )
        assert r.returncode == 0, r.stdout + r.stderr
        payload = json.loads(out.read_text())
        plans = payload["plans"]
        assert plans and plans[0]["program"] == "step"
        assert plans[0]["hbm_peak_bytes"] > 0
        assert plans[0]["flops_total"] == 2.0 * 64 ** 3
        assert "findings" in plans[0]
        # the inventory rides every --json payload, planner group in
        assert {"jaxpr", "planner"} <= set(
            payload["static_checks"])
