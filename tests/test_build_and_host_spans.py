"""What holds the host while the chip is idle (ISSUE 38): every build
named by its phase, its cache outcome and what was built; Python's
collector as a ``py.gc`` range on every thread; the thread's CPU seconds
on every live span; and the five readers of benchmarks/metrics/ that
read them, on a synthetic ring laid on a reduced trace. With spans off
none of it runs: no collector callback, no key string, no CPU clock.
CPU, tiny sizes."""
import gc
import os
import threading
import time

import pytest

import jax
import jax.numpy as jnp
from jax._src import compilation_cache

import paddle_tpu as paddle
from paddle_tpu.framework import telemetry
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.inference import BatchScheduler, PagedLlamaAdapter, Request
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.ops import kernels

from benchmarks.lib import common
from benchmarks.lib import program_spans as P
from benchmarks.tests.test_program_spans import LOOP, PUMP, synthetic

NEW = ("idle_in_cache_load_share.serve", "idle_in_xla_trace_share.serve",
       "programs_built_in_window.serve", "idle_in_gc_share.serve",
       "idle_offcpu_share.serve")
SEVEN = tuple("idle_unattributed_share.serve" if b == "unattributed"
              else f"idle_in_{b}_share.serve" for b in P.SHARES)
MISTRAL = "mistral-7b-serve.decode-closed32"


@pytest.fixture
def live():
    """Spans live through the flag, from a fresh tracer."""
    set_flags({"telemetry": "trace"})
    telemetry.reset()
    yield
    set_flags({"telemetry": "off"})
    telemetry.reset()


@pytest.fixture
def off():
    set_flags({"telemetry": "off"})
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent cache in a directory of its own, every program
    written; the process's own settings come back after."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_enable_compilation_cache")
    saved = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def _built(fn, x, key=None):
    """The xla.* ranges of building ``fn`` at ``x`` under a span that
    declares ``key`` (none if None)."""
    attrs = {} if key is None else {"key": key}
    n0 = len(telemetry.tracer().spans())
    with telemetry.span("model.layer", **attrs):
        jax.jit(fn)(x).block_until_ready()
    return [s for s in telemetry.tracer().spans()[n0:]
            if s.name.startswith("xla.")]


def _inside(child, parent, slack=1e-6):
    return parent.t0 - slack <= child.t0 and \
        child.t0 + child.dur <= parent.t0 + parent.dur + slack


# -- the listener: phase, cache outcome, key ---------------------------------
def test_a_build_misses_then_loads_from_the_persistent_cache(
        live, persistent_cache):
    def f(x):
        return jnp.tanh(x) * 3 - 1

    x = jnp.ones((7, 3))
    first = [s for s in _built(f, x, "f 7x3") if s.name == "xla.build"]
    jax.clear_caches()
    again = _built(f, x, "f 7x3")
    assert [s.attrs["cache"] for s in first
            if s.attrs["fun"] == "jit(f)"] == ["miss"]
    build = [s for s in again if s.name == "xla.build"
             and s.attrs["fun"] == "jit(f)"]
    assert [s.attrs["cache"] for s in build] == ["hit"]
    loads = [s for s in again if s.name == "xla.cache_load"]
    assert loads and any(_inside(s, build[0]) for s in loads)
    # the load is a child of its build by interval, and named as it
    assert all(s.attrs["key"] == "f 7x3" for s in again)


def test_a_build_with_the_cache_disabled_reads_off(live, persistent_cache):
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    got = _built(lambda x: x * 11 + 4, jnp.ones((9,)))
    builds = [s for s in got if s.name == "xla.build"]
    assert builds and {s.attrs["cache"] for s in builds} == {"off"}
    assert not any(s.name == "xla.cache_load" for s in got)


def test_the_key_comes_from_the_innermost_span_that_declares_one(live):
    with telemetry.span("serving.step", key="outer"):
        with telemetry.span("model.layer", key="inner"):
            with telemetry.span("pool.fused_step"):
                jax.jit(lambda x: x * 13 - 2)(jnp.ones((11,)))
    got = [s for s in telemetry.tracer().spans()
           if s.name.startswith("xla.")]
    assert got and {s.attrs["key"] for s in got} == {"inner"}


def test_no_key_where_no_open_span_declares_one(live):
    got = _built(lambda x: x * 17 + 5, jnp.ones((13,)))
    assert got and not any("key" in s.attrs for s in got)
    # only a compile range takes one
    with telemetry.span("a", key="k"):
        s = telemetry.add_complete("other", telemetry.clock(), 0.0)
    assert "key" not in s.attrs


# -- py.gc ---------------------------------------------------------------------
def test_a_collection_is_a_range_under_the_open_span(live):
    with telemetry.span("serving.decode") as outer:
        gc.collect(2)
    got = [s for s in telemetry.tracer().spans() if s.name == "py.gc"
           and s.attrs["gen"] == 2]
    assert got, "a forced full collection leaves a range"
    s = got[-1]
    assert s.path == "serving.decode/py.gc"
    assert s.parent_id == outer.span_id and _inside(s, outer)
    assert s.tid == threading.get_ident() and s.cpu is None
    assert set(s.attrs) == {"gen", "collected", "uncollectable"}


def test_a_collection_on_another_thread_is_filed_on_that_thread(live):
    telemetry.tracer()
    tids = []

    def other():
        tids.append(threading.get_ident())
        gc.collect(2)

    t = threading.Thread(target=other)
    t.start()
    t.join()
    got = [s for s in telemetry.tracer().spans() if s.name == "py.gc"
           and s.tid == tids[0]]
    assert got and got[-1].attrs["gen"] == 2 and got[-1].depth == 0


def test_the_callback_takes_no_lock_and_waits_for_the_next_read(live):
    tr = telemetry.tracer()
    with tr._lock:                    # a collection on a thread holding it
        telemetry._gc_callback("start", {})
        telemetry._gc_callback("stop", {"generation": 1, "collected": 3,
                                        "uncollectable": 0})
        assert len(tr._pending) >= 1
    got = [s for s in tr.spans() if s.name == "py.gc"
           and s.attrs["collected"] == 3]
    assert len(got) == 1 and not tr._pending


def test_the_callback_goes_with_the_tracer_and_records_only_while_live(
        off):
    assert telemetry._gc_callback not in gc.callbacks
    telemetry.arm_tracer()
    try:
        assert gc.callbacks.count(telemetry._gc_callback) == 1
    finally:
        telemetry.disarm_tracer()
    n = len(telemetry.peek_tracer().spans())
    gc.collect(2)                                  # installed, not live
    assert len(telemetry.peek_tracer().spans()) == n
    telemetry.reset()
    assert telemetry._gc_callback not in gc.callbacks


# -- Span.cpu --------------------------------------------------------------------
def test_a_busy_span_reads_its_wall_in_cpu_and_a_sleep_reads_none(live):
    # busy for 30 ms of the thread's own CPU: on an idle machine that is
    # about 30 ms of wall, on a loaded one more (the thread waited)
    with telemetry.span("busy") as busy:
        t = time.thread_time()
        while time.thread_time() - t < 0.03:
            pass
    with telemetry.span("sleep") as sleep:
        time.sleep(0.05)
    assert 0.03 <= busy.cpu <= busy.dur + 1e-3
    assert sleep.dur >= 0.05 and sleep.cpu < 0.005
    assert busy.to_dict()["cpu"] == busy.cpu
    late = telemetry.add_complete("xla.build", telemetry.clock(), 0.01)
    assert late.cpu is None and "cpu" not in late.to_dict()


# -- the scheduler: n, and the off path --------------------------------------
@pytest.fixture(scope="module")
def model():
    paddle.seed(29)
    return LlamaForCausalLM(llama_tiny(
        hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=128))


def _run(model):
    # a pool of its own size: programs no other test of the process built
    sched = BatchScheduler(
        PagedLlamaAdapter(model, num_pages=56, page_size=4, max_length=64),
        max_batch_size=2, prefill_chunk_tokens=8)
    for i, n in enumerate((5, 3)):
        sched.submit(Request(f"r{i}", list(range(1, n + 1)),
                             max_new_tokens=3))
    sched.run_until_complete()
    return sched


def test_steps_count_and_dispatch_spans_name_their_programs(model, live):
    _run(model)
    spans = telemetry.tracer().spans()
    steps = [s.attrs["n"] for s in spans if s.name == "serving.step"]
    assert steps == list(range(1, len(steps) + 1))
    for name, head in (("model.embed", "embed n"), ("model.layer", "layer n"),
                       ("model.head", "head n")):
        keys = [s.attrs["key"] for s in spans if s.name == name]
        assert keys and all(k.startswith(head) for k in keys)
    # a build under a dispatch span names it: n (packed width), r, t, p
    builds = [s for s in spans if s.name == "xla.build"
              and "model.layer/" in s.path]
    assert builds
    for b in builds:
        assert b.attrs["key"].startswith("layer n")
        assert b.attrs["cache"] in ("hit", "miss", "off")


@pytest.mark.parametrize("part,shape,key", [
    ("embed", (), "embed n64"),
    ("layer", (32, 1, True), "layer n64 r32 t1 p16 moe"),
    ("layer", (32, 16, False), "layer n64 r32 t16 p16 dense"),
    ("moe_gmm", (256,), "moe_gmm a256"),
    ("head", (32,), "head n64 r32"),
])
def test_the_latent_adapter_names_its_programs(part, shape, key):
    from types import SimpleNamespace

    from paddle_tpu.inference.paged_xing4 import PagedXing4Adapter

    rows = SimpleNamespace(pad_to=64, mp_pad=16)
    assert PagedXing4Adapter._program_key(part, rows, *shape) == key


def test_off_installs_no_callback_builds_no_key_reads_no_cpu_clock(
        model, off, monkeypatch):
    seen = []

    def recorder(name, value):
        def record(*a, **k):
            seen.append(name)
            return value
        return record

    monkeypatch.setattr(PagedLlamaAdapter, "_program_key",
                        recorder("key", "k"))
    monkeypatch.setattr(kernels, "_eager_key", recorder("eager key", "k"))
    monkeypatch.setattr(telemetry, "_thread_time", recorder("cpu", 0.0))
    paddle.set_flags({"FLAGS_pallas_interpret": True})

    def both():
        _run(model)
        kernels.rms_norm(jnp.ones((2, 128)), jnp.ones((128,)))

    try:
        both()
        assert telemetry.peek_tracer() is None
        assert telemetry._gc_callback not in gc.callbacks
        assert seen == []
        # the same three run once spans are live
        set_flags({"telemetry": "trace"})
        both()
    finally:
        set_flags({"telemetry": "off"})
        paddle.set_flags({"FLAGS_pallas_interpret": False})
    assert set(seen) == {"key", "eager key", "cpu"}


# -- the five readers on a synthetic ring ---------------------------------------
def _ring(spans, extra=()):
    """A tracer holding ``spans`` [(name, t0_ns, t1_ns, tid)] of the
    synthetic window (program clock) plus ``extra`` [(name, t0, t1, tid,
    attrs, cpu)]; every live-span range gets a cpu of half its wall."""
    tr = telemetry.Tracer(ring=1 << 16)
    steps = 0
    for name, a, b, tid, attrs, cpu in \
            [s + ({}, None) for s in spans] + list(extra):
        s = telemetry.Span(name, attrs=dict(attrs))
        s.t0, s.dur, s.tid = a / 1e9, (b - a) / 1e9, tid
        if not name.startswith(("xla.", "py.")):
            s.cpu = 0.5 * s.dur if cpu is None else cpu
        if name == "serving.step":
            steps += 1
            s.attrs["n"] = steps
        tr._ring.append(s)
    return tr


def _with_builds(spans):
    """Each build of the synthetic window split into a cache load inside
    it, named by a key a layer; a collection on the pump inside each
    ``serving.decode`` and one on the loop thread beside it."""
    extra = []
    layer = 0
    for name, a, b, tid in spans:
        if name == "xla.build":
            extra.append(("xla.build", a, b, tid,
                          {"fun": "jit(ragged_layer_step)", "cache": "hit",
                           "key": f"layer n{8 << (layer % 2)}"}, None))
            extra.append(("xla.cache_load", a + (b - a) // 4,
                          b - (b - a) // 4, tid, {}, None))
            layer += 1
        elif name == "serving.decode":
            mid = (a + b) // 2
            extra.append(("py.gc", mid - 200_000, mid + 200_000, PUMP,
                          {"gen": 0, "collected": 0, "uncollectable": 0},
                          None))
            extra.append(("py.gc", mid - 200_000, mid + 200_000, LOOP,
                          {"gen": 0, "collected": 0, "uncollectable": 0},
                          None))
    return [s for s in spans if s[0] != "xla.build"], extra


@pytest.fixture
def laid_ctx(monkeypatch):
    spans, red, n = synthetic()
    base, extra = _with_builds(spans)
    monkeypatch.setattr(telemetry, "_TRACER", _ring(base, extra))
    return {"trace": red, "counters": {"steps": n}}, red, spans


def _read(ctx):
    return {m: common.read_metric(m, ctx) for m in NEW + SEVEN}


def test_the_build_readers_split_the_build_share(laid_ctx, capsys):
    ctx, red, _ = laid_ctx
    got = _read(ctx)
    build = got["idle_in_build_share.serve"]
    load = got["idle_in_cache_load_share.serve"]
    trace = got["idle_in_xla_trace_share.serve"]
    assert 0 < load and 0 < trace and load + trace <= build + 1e-9
    # the load is the middle half of every build, all of it idle here
    laid = ctx["_program_spans"]
    assert load == pytest.approx(
        100 * laid["idle_by_name"]["xla.cache_load"] / laid["window_ns"])
    assert got["programs_built_in_window.serve"] == 2
    err = capsys.readouterr().err
    assert "key='layer n8' cache=hit builds=" in err
    assert "key='layer n16' cache=hit builds=" in err


def test_the_seven_shares_still_add_up_with_collections_nested(laid_ctx):
    ctx, red, _ = laid_ctx
    got = _read(ctx)
    assert sum(got[m] for m in SEVEN) == pytest.approx(
        common.read_metric("device_idle_share.serve", ctx), abs=0.01)
    # the pump's collections moved out of the scheduler's bucket
    laid = ctx["_program_spans"]
    assert laid["idle_by_name"]["py.gc"] > 0
    assert got["idle_unattributed_share.serve"] >= \
        100 * laid["idle_by_name"]["py.gc"] / laid["window_ns"] - 1e-9


def test_the_gc_share_is_the_union_over_threads_in_idle_time(laid_ctx,
                                                              capsys):
    ctx, red, _ = laid_ctx
    share = common.read_metric("idle_in_gc_share.serve", ctx)
    laid = ctx["_program_spans"]
    # two collections a step at the same instant: one union, 0.4 ms a
    # step, all of it idle (serving.decode runs with the device idle)
    n = ctx["counters"]["steps"]
    assert share == pytest.approx(100 * n * 400_000 / laid["window_ns"],
                                  rel=1e-4)
    err = capsys.readouterr().err
    assert "longest idle gap" in err and f"{2 * n} collections" in err


def test_the_offcpu_share_spreads_each_span_off_cpu_over_its_idle(
        laid_ctx):
    ctx, red, spans = laid_ctx
    share = common.read_metric("idle_offcpu_share.serve", ctx)
    device_idle = common.read_metric("device_idle_share.serve", ctx)
    assert 0 < share < device_idle
    # one span alone: a child recorded after the fact is taken as on
    # the CPU throughout; the self time is idle the whole way
    mod = common._load_file("offcpu", os.path.join(
        common.BENCH_DIR, "metrics", "idle_offcpu_share.serve.py"))
    by = mod.offcpu_idle_ns(
        [(0, 100, "a", 60e-9), (20, 40, "xla.build", None),
         (50, 70, "b", 0.0)], [(0, 100)])
    # a: self wall 60, self cpu 60 - 20 (the build) - 0 = 40 -> 20 off
    assert by["a"] == (60, pytest.approx(20.0))
    assert by["b"] == (20, pytest.approx(20.0))    # asleep
    assert by["xla.build"] == (20, 0.0)            # no cpu: not counted


@pytest.mark.parametrize("ticks,off", [(1, 0.0), (0, 10.0)])
def test_the_offcpu_share_sums_a_clock_that_counts_in_ticks(ticks, off):
    """Five busy spans of 2 ns (all idle) under a clock of 10 ns ticks:
    one of them reads a whole tick and four read 0. Summed over the
    name they read on the CPU throughout; with no tick, off it."""
    mod = common._load_file("offcpu", os.path.join(
        common.BENCH_DIR, "metrics", "idle_offcpu_share.serve.py"))
    own = [(10 * k, 10 * k + 2, "a", 10e-9 if k < ticks else 0.0)
           for k in range(5)]
    by = mod.offcpu_idle_ns(own, [(0, 50)])
    assert by["a"] == (10, pytest.approx(off))


def test_an_older_program_reads_none_and_says_why(monkeypatch, capsys):
    spans, red, n = synthetic()
    tr = _ring(spans)
    monkeypatch.setattr(telemetry, "_TRACER", tr)
    monkeypatch.setattr(telemetry, "SURFACE", tuple(
        e for e in telemetry.SURFACE
        if e[0] not in ("span:xla.cache_load", "span:py.gc")))
    monkeypatch.setattr(telemetry.Span, "__slots__", tuple(
        x for x in telemetry.Span.__slots__ if x != "cpu"))
    ctx = {"trace": red, "counters": {"steps": n}}
    got = {m: common.read_metric(m, ctx) for m in NEW}
    assert got["idle_in_cache_load_share.serve"] is None
    assert got["programs_built_in_window.serve"] is None
    assert got["idle_in_gc_share.serve"] is None
    assert got["idle_offcpu_share.serve"] is None
    # the trace and lower ranges are as old as the ring
    assert got["idle_in_xla_trace_share.serve"] > 0
    err = capsys.readouterr().err
    assert "records no xla.cache_load" in err and "names no build" in err
    assert "records no py.gc" in err and "carry no cpu" in err


def test_each_new_metric_has_its_entry_in_every_serving_cell():
    bench = common.load_benchmark()
    names = {m["name"]: m for m in bench["per_layer"]}
    serving = names["idle_in_build_share.serve"]["workloads"]
    for m in NEW:
        assert names[m]["workloads"] == serving
        assert names[m]["source"] == ("program_counter" if m.startswith(
            "programs_") else "device_trace")
    assert names["programs_built_in_window.serve"]["moves"] == "tpot_p95_ms"
    assert MISTRAL in serving and len(serving) == 4
