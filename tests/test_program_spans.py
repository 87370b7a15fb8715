"""The program's spans from the engine to the pool (ISSUE 27).

One switch: ``telemetry.span`` is live under ``FLAGS_telemetry=trace``,
an armed RECORD window, or a ``jax.profiler`` session — read through the
probe that ``paddle_tpu.profiler`` installs, monkeypatched here — and is
decided at call time, so a session that starts after the scheduler was
built is honoured. A tiny Llama goes through ``ServingEngine`` ->
``BatchScheduler`` -> ``PagedLlamaAdapter`` -> the paged pool, and every
step must leave the span tree of docs/OBSERVABILITY.md.
"""
import asyncio
import tracemalloc

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework import telemetry
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.inference import (
    BatchScheduler,
    PagedLlamaAdapter,
    Request,
    ServingEngine,
)
from paddle_tpu.models import LlamaForCausalLM, llama_tiny

LAYERS = 2
_RNG = np.random.RandomState(3)
PROMPTS = {f"r{i}": _RNG.randint(1, 500, n).tolist()
           for i, n in enumerate((9, 4, 6))}
N_NEW = 5

# child -> the parent it must lie inside (the tree of ISSUE 27 §2); a
# step's booking and its one table lie before the first layer where a
# layer runs as one program (ISSUE 33), under the pool's call on the
# op-by-op body
PARENT = {
    "serving.admit": "serving.step",
    "serving.pack": "serving.step",
    "serving.prefill_chunk": "serving.step",
    "serving.logits_pull": "serving.step",
    "serving.decode": "serving.step",
    "model.plan": "serving.prefill_chunk",
    "model.embed": "serving.prefill_chunk",
    "model.layer": "serving.prefill_chunk",
    "model.head": "serving.prefill_chunk",
    "model.norm": "model.layer",
    "model.mlp": "model.layer",
    "pool.fused_step": "model.layer",
    "kernel.ragged": "pool.fused_step",
    "engine.flush": "engine.ops",
}
POOL_PARENT = {"program": "serving.prefill_chunk",
               "eager": "pool.fused_step"}


@pytest.fixture(scope="module")
def model():
    paddle.seed(23)
    return LlamaForCausalLM(llama_tiny(
        hidden_size=64, intermediate_size=128, num_hidden_layers=LAYERS,
        num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=128))


class FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: records what was
    entered, in order."""

    entered = []

    def __init__(self, name, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        FakeAnnotation.entered.append((self.name, dict(self.attrs)))
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def session(monkeypatch):
    """A switchable stand-in for the device profiler's session:
    ``session.on = True`` is what ``jax.profiler.start_trace`` does to
    the probe."""

    class Session:
        on = False

    set_flags({"telemetry": "off"})
    telemetry.reset()
    FakeAnnotation.entered = []
    monkeypatch.setattr(telemetry, "_SESSION_PROBE", lambda: Session.on)
    monkeypatch.setattr(telemetry, "_ANNOTATION", FakeAnnotation)
    yield Session
    Session.on = False
    telemetry.reset()


def _sched(model, body="program"):
    adapter = PagedLlamaAdapter(
        model, num_pages=96, page_size=4, max_length=128,
        kv_cache_dtype="int8" if body == "eager" else None)
    assert adapter._fusion_eligible() == (body == "program")
    return BatchScheduler(adapter, max_batch_size=4,
                          prefill_chunk_tokens=8)


def _serve(sched, on_first_token=None):
    """All prompts through a live engine; {req_id: streamed tokens}."""

    async def main():
        async with ServingEngine(sched) as eng:
            streams = [await eng.submit(Request(
                rid, list(p), max_new_tokens=N_NEW))
                for rid, p in PROMPTS.items()]

            async def drain(s):
                out = []
                async for tok in s:
                    if on_first_token and not out:
                        on_first_token()
                    out.append(tok)
                return out

            toks = await asyncio.gather(*[drain(s) for s in streams])
            return {s.req_id: t for s, t in zip(streams, toks)}

    return asyncio.run(main())


def _traced(model, session, body="program"):
    sched = _sched(model, body)
    session.on = True
    toks = _serve(sched)
    session.on = False
    return toks, telemetry.peek_tracer().spans(), sched


@pytest.fixture
def traced(model, session):
    """One whole run with the session on; (tokens, spans, scheduler)."""
    return _traced(model, session)


def _inside(child, parent, slack=1e-9):
    return parent.t0 - slack <= child.t0 and \
        child.t0 + child.dur <= parent.t0 + parent.dur + slack


def test_probe_is_installed_by_the_profiler_module():
    import paddle_tpu.profiler  # noqa: F401  (import paddle_tpu does it)

    assert telemetry._SESSION_PROBE is \
        jax.profiler.TraceAnnotation.is_enabled
    assert telemetry._ANNOTATION is jax.profiler.TraceAnnotation
    assert telemetry.tracing_on() is False
    assert telemetry.span("x") is telemetry.NULL_SPAN


@pytest.mark.parametrize("body", ["program", "eager"])
def test_every_step_yields_the_tree(model, session, body):
    _, spans, _ = _traced(model, session, body)
    by_id = {s.span_id: s for s in spans}
    steps = [s for s in spans if s.name == "serving.step"]
    assert len(steps) >= N_NEW
    program = body == "program"
    for step in steps:
        kids = [s for s in spans if s.path.startswith("serving.step/")
                and _inside(s, step) and s.tid == step.tid]
        names = {s.name for s in kids}
        assert {"serving.admit", "serving.pack", "serving.prefill_chunk",
                "serving.logits_pull", "serving.decode", "model.plan",
                "model.embed", "model.layer",
                "model.head", "pool.fused_step", "pool.book",
                "pool.table", "kernel.ragged"} <= names
        layers = [s for s in kids if s.name == "model.layer"]
        assert len(layers) == LAYERS
        assert sorted(s.attrs["li"] for s in layers) == list(range(LAYERS))
        assert {s.attrs["program"] for s in layers} == {int(program)}
        # model.norm / model.mlp: the op-by-op body only
        for name, n in (("model.norm", 2 * LAYERS), ("model.mlp", LAYERS)):
            assert sum(s.name == name for s in kids) == \
                (0 if program else n)
        # a programmed step builds its tables once, an eager one a layer
        assert sum(s.name == "pool.table" for s in kids) == \
            (1 if program else LAYERS)
        assert sum(s.name == "pool.book" for s in kids) == LAYERS
    parents = dict(PARENT, **{"pool.book": POOL_PARENT[body],
                              "pool.table": POOL_PARENT[body]})
    for s in spans:
        want = parents.get(s.name)
        if want is None or s.parent_id is None:
            continue
        parent = by_id[s.parent_id]
        assert parent.name == want, (s.path, parent.name)
        assert _inside(s, parent), s.path


def test_self_times_of_a_step_sum_to_the_step(traced):
    _, spans, _ = traced
    steps = [s for s in spans if s.name == "serving.step"]
    for step in steps:
        tree = [s for s in spans if s.tid == step.tid
                and _inside(s, step)]
        total = 0.0
        for s in tree:
            kids = [c for c in tree if c.parent_id == s.span_id]
            total += s.dur - sum(min(c.dur, s.dur) for c in kids)
        assert total == pytest.approx(step.dur, rel=1e-6, abs=2e-6)


def test_prefill_chunk_span_no_longer_holds_the_pull(traced):
    _, spans, _ = traced
    pulls = [s for s in spans if s.name == "serving.logits_pull"]
    chunks = [s for s in spans if s.name == "serving.prefill_chunk"]
    assert len(pulls) == len(chunks) > 0
    for p in pulls:
        assert p.path == "serving.step/serving.logits_pull"
        assert not any(_inside(p, c) for c in chunks)


def test_span_attrs_carry_the_counters(traced):
    _, spans, _ = traced
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    assert all({"rows", "packed", "pad_to"} <= set(s.attrs)
               for s in by["serving.pack"])
    assert all(s.attrs["bytes"] > 0 and s.attrs["rows"] >= 1
               for s in by["pool.table"])
    assert all(s.attrs["bytes"] > 0 for s in by["model.plan"])
    booked = sum(s.attrs["slots"] for s in by["pool.book"])
    fed = sum(len(p) for p in PROMPTS.values()) \
        + len(PROMPTS) * (N_NEW - 1)
    assert booked == LAYERS * fed
    assert all(s.attrs["op"] == "layer_step"
               for s in by["pool.fused_step"])


def test_engine_spans_and_stream_lag(traced):
    toks, spans, _ = traced
    names = {s.name for s in spans}
    assert {"engine.ops", "engine.flush", "engine.stream_lag"} <= names
    lags = [s for s in spans if s.name == "engine.stream_lag"]
    assert sum(s.attrs["n"] for s in lags) == sum(map(len, toks.values()))
    assert {s.attrs["req"] for s in lags} == set(PROMPTS)
    assert all(0 <= s.dur < 5.0 for s in lags)
    pump = {s.tid for s in spans if s.name == "serving.step"}
    assert len(pump) == 1
    # the client's task reads the token on the loop thread, not the pump
    assert all(s.tid not in pump for s in lags)
    for f in (s for s in spans if s.name == "engine.flush"):
        assert f.path == "engine.ops/engine.flush"


def test_engine_idle_is_a_span(model, session):
    sched = _sched(model)
    session.on = True

    async def main():
        async with ServingEngine(sched):
            await asyncio.sleep(0.15)     # nothing submitted: pump parks

    asyncio.run(main())
    session.on = False
    idle = [s for s in telemetry.peek_tracer().spans()
            if s.name == "engine.idle"]
    assert idle and sum(s.dur for s in idle) > 0.05


def test_session_spans_are_written_to_the_device_trace_too(traced):
    _, spans, _ = traced
    ann = [n for n, _ in FakeAnnotation.entered]
    for name in ("serving.step", "model.layer", "pool.table",
                 "kernel.ragged", "engine.ops"):
        assert ann.count(name) == sum(s.name == name for s in spans)
    # a range taken after the fact leaves an end mark with its duration
    marks = [a for n, a in FakeAnnotation.entered
             if n == "engine.stream_lag"]
    assert marks and all("dur_us" in a for a in marks)


def test_probe_turning_true_after_construction_is_honoured(model,
                                                           session):
    sched = _sched(model)               # built with everything off
    assert sched._tracer is None
    assert telemetry.peek_tracer() is None

    def switch_on():
        session.on = True

    _serve(sched, on_first_token=switch_on)
    session.on = False
    spans = telemetry.peek_tracer().spans()
    steps = [s for s in spans if s.name == "serving.step"]
    # the steps before the first token left nothing; the later ones did
    assert 0 < len(steps) < N_NEW + 3
    assert any(s.name == "pool.table" for s in spans)


def test_off_returns_null_span_and_allocates_nothing(model, session):
    sched = _sched(model)
    for rid, p in PROMPTS.items():
        sched.submit(Request(rid, list(p), max_new_tokens=2))
    sched.step()                                  # warm every path
    assert telemetry.span("x", a=1) is telemetry.NULL_SPAN
    assert telemetry.add_complete("x", 0.0, 1.0) is None
    tracemalloc.start()
    snap0 = tracemalloc.take_snapshot()
    sched.run_until_complete()
    snap1 = tracemalloc.take_snapshot()
    tracemalloc.stop()
    filt = [tracemalloc.Filter(True, telemetry.__file__)]
    diff = snap1.filter_traces(filt).compare_to(
        snap0.filter_traces(filt), "filename")
    assert sum(max(d.count_diff, 0) for d in diff) == 0
    assert telemetry.peek_tracer() is None        # the ring stayed empty


def test_greedy_tokens_identical_with_spans_on_and_off(model, session):
    off = _serve(_sched(model))
    assert telemetry.peek_tracer() is None
    session.on = True
    on = _serve(_sched(model))
    session.on = False
    assert on == off
    assert all(len(t) == N_NEW for t in on.values())


def test_xla_phases_are_filed_under_the_open_span(session):
    session.on = True
    with telemetry.span("model.norm") as outer:
        jax.jit(lambda x: x * 3 + 1)(jnp.ones((5,)))
    session.on = False
    spans = telemetry.peek_tracer().spans()
    got = {s.name: s for s in spans if s.name.startswith("xla.")}
    assert set(got) == {"xla.trace", "xla.lower", "xla.build"}
    for s in got.values():
        assert s.parent_id == outer.span_id
        assert s.path == "model.norm/" + s.name
        assert s.dur > 0 and s.t0 + s.dur <= outer.t0 + outer.dur + 1e-6


def test_xla_listener_is_silent_when_off(session):
    jax.jit(lambda x: x * 5 + 2)(jnp.ones((6,)))
    assert telemetry.peek_tracer() is None
    assert FakeAnnotation.entered == []


def test_jit_call_span_wraps_the_to_static_entry(session):
    lin = paddle.nn.Linear(4, 4)

    @paddle.jit.to_static
    def f(x):
        return lin(x)

    x = paddle.ones([2, 4])
    f(x)                                           # compiles, spans off
    session.on = True
    f(x)
    session.on = False
    names = [s.name for s in telemetry.peek_tracer().spans()]
    assert names.count("jit.call") == 1


def test_flag_mode_and_record_window_still_switch_spans_on(session):
    assert telemetry.span("x") is telemetry.NULL_SPAN
    telemetry.arm_tracer()
    try:
        with telemetry.span("a", k=1) as sp:
            assert sp.attrs == {"k": 1}
    finally:
        telemetry.disarm_tracer()
    assert telemetry.span("x") is telemetry.NULL_SPAN
    set_flags({"telemetry": "trace"})
    try:
        with telemetry.span("b"):
            pass
    finally:
        set_flags({"telemetry": "off"})
    assert [s.name for s in telemetry.peek_tracer().spans()] == ["a", "b"]
    # no session in either case: nothing went to the device trace
    assert FakeAnnotation.entered == []


def test_ring_holds_a_session_at_a_hundred_times_todays_steps():
    # 47 steps x 252 ranges today (chip, PR 27); 4,500 steps x 45 once
    # a step is one program: both fit the default ring, a quarter spare
    cap = telemetry.Tracer()._ring.maxlen
    assert cap >= 1.25 * max(47 * 252, 4500 * 45)
    tr = telemetry.Tracer(ring=64)
    for i in range(70):
        tr.add_complete("x", float(i), 0.5)
    assert tr.dropped == 6 and len(tr.spans()) == 64


@pytest.mark.parametrize("build,name", [
    ("ragged", "ragged_paged_attention"),
    ("rms", "rms_norm"),
    ("flash_fwd", "flash_fwd"),
    ("flash_bwd", "flash_bwd_dkv"),
    ("flash_bwd", "flash_bwd_dq"),
])
def test_pallas_kernels_carry_a_stable_name(build, name):
    """The name a roofline reader finds the kernel by in a device
    trace is the pallas_call's ``name=``: it must be in the jaxpr."""
    import importlib

    # the package re-exports functions under its modules' names
    fa, pa, rn = (importlib.import_module("paddle_tpu.ops.kernels." + m)
                  for m in ("flash_attention", "paged_attention",
                            "rms_norm"))

    f32 = jnp.float32
    if build == "ragged":
        fn = pa._build_ragged_call(2, 1, 2, 64, 8, 4, 2, 2, 0.125, 0,
                                   False, True, True)
        args = (jnp.zeros((2, 1, 2, 64), f32),
                jnp.zeros((8, 4, 2, 64), f32),
                jnp.zeros((8, 4, 2, 64), f32),
                jnp.zeros((2, 2), jnp.int32), jnp.ones((2,), jnp.int32),
                jnp.ones((2,), jnp.int32))
    elif build == "rms":
        fn = lambda x, w: rn._rms_pallas(x, w, 1e-6, True)  # noqa: E731
        args = (jnp.ones((8, 128), f32), jnp.ones((128,), f32))
    else:
        q = jnp.ones((2, 128, 64), f32)
        if build == "flash_fwd":
            fn = lambda q, k, v: fa._flash_fwd_pallas(  # noqa: E731
                q, k, v, True, 0.125, 128, 128, interpret=True)
            args = (q, q, q)
        else:
            lse = jnp.zeros((2, 128), f32)
            fn = lambda q, k, v, o, l, do: fa._flash_bwd_pallas(  # noqa
                q, k, v, o, l, do, True, 0.125, 128, 128, interpret=True)
            args = (q, q, q, q, lse, q)
    text = str(jax.make_jaxpr(fn)(*args))
    assert f"name={name}\n" in text or f"name={name} " in text
