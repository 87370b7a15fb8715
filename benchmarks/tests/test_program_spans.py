"""The program's spans laid on the trace's clock (lib/program_spans.py):
the pairing of program steps with the harness's ``bench.sched_step``
wrappers on synthetic spans with a known offset and jitter and on the two
wrappers of the recorded trace, its refusals, self time, and the seven
idle shares adding up to the idle share."""
import gzip
import json
import os
import random
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.lib import program_spans as P  # noqa: E402
from benchmarks.lib import trace as T  # noqa: E402

DEV = "/device:TPU:0"
OFFSET = -48_000_000_000_000          # program clock far from the trace's
PUMP, LOOP = 11, 22                   # thread ids


def steps(n, seed=5, step_ns=900_000_000, jitter_ns=30_000_000):
    """n wrappers on the trace's clock: irregular, as real steps are."""
    rng = random.Random(seed)
    out, t = [], 1_000_000_000
    for _ in range(n):
        dur = step_ns + rng.randrange(-jitter_ns, jitter_ns)
        out.append((t, t + dur))
        t += dur + rng.randrange(50_000, 400_000)     # the engine between
    return out


def program_steps(bench, jitter_ns=8_000, seed=9):
    """The program's serving.step inside each wrapper, on its own clock:
    a few microseconds in from both ends, jittered."""
    rng = random.Random(seed)
    return [(s + 5_000 + rng.randrange(jitter_ns) - OFFSET,
             e - 5_000 - rng.randrange(jitter_ns) - OFFSET)
            for s, e in bench]


def test_alignment_finds_a_known_offset_under_jitter():
    bench = steps(45)
    off, pairs, spread = P.align(program_steps(bench), bench)
    assert pairs == [(i, i) for i in range(45)]
    assert abs(off - (OFFSET - 5_000)) <= 8_000
    assert spread <= 8_000


@pytest.mark.parametrize("extra_prog,extra_bench,shift", [
    ((1, 0), (0, 0), -1),     # a program step began before the session
    ((0, 1), (0, 0), 0),      # one was still running when it ended
    ((0, 0), (1, 0), 1),
    ((1, 1), (0, 1), -1),
])
def test_unpaired_steps_at_the_ends_are_allowed(extra_prog, extra_bench,
                                                shift):
    full = steps(14)
    prog_all = program_steps(full)
    bench = full[1 - extra_bench[0]:13 + extra_bench[1]]
    prog = prog_all[1 - extra_prog[0]:13 + extra_prog[1]]
    _, pairs, _ = P.align(prog, bench)
    assert all(j - i == shift for i, j in pairs)
    assert len(pairs) == 12 + min(extra_prog[0], extra_bench[0]) \
        + min(extra_prog[1], extra_bench[1])


def test_alignment_on_the_recorded_wrappers():
    with gzip.open(os.path.join(HERE, "data", "small_trace.json.gz"),
                   "rt") as f:
        d = json.load(f)["serve"]
    red = T.Reduced(d["events"], window=tuple(d["window"]))
    bench = sorted(red.host["sched_step"])
    assert len(bench) == 2
    off, pairs, spread = P.align(program_steps(bench), bench)
    assert pairs == [(0, 0), (1, 1)] and spread <= 8_000
    spans = [("serving.step", a, b, PUMP)
             for a, b in program_steps(bench)]
    inside = sum(1 for s, e in bench
                 if red.window[0] <= (s + e) // 2 <= red.window[1])
    got = P.lay(spans, 0, red, inside)
    # every idle nanosecond of the window is either in a step or in no span
    assert sum(got["idle_by"].values()) == got["idle_ns"]
    assert got["idle_ns"] / got["window_ns"] == pytest.approx(
        red.idle_share, rel=1e-9)


def test_refuses_a_missing_step():
    bench = steps(20)
    prog = program_steps(bench)
    del prog[7]
    with pytest.raises(P.Refused, match="missing"):
        P.align(prog, bench)


def test_refuses_a_span_outside_its_wrapper():
    bench = steps(20)
    prog = program_steps(bench)
    a, b = prog[11]
    prog[11] = (a, b + 400_000)          # ends 0.4 ms after its wrapper
    with pytest.raises(P.Refused, match="outside"):
        P.align(prog, bench)


def test_refuses_steps_too_regular_to_tell_apart():
    bench = [(i * 1_000_000, i * 1_000_000 + 900_000) for i in range(30)]
    prog = [(s + 1_000 - OFFSET, e - 1_000 - OFFSET) for s, e in bench]
    with pytest.raises(P.Refused, match="regular"):
        P.align(prog[1:], bench[:-1])


def ev(name, start, dur, plane=DEV, line="XLA Ops"):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur, "text": ""}


def synthetic(n=6):
    """A window of n steps with the span tree of the program in each, a
    device that is busy for 5 ms in every layer call, and the spans on the
    program's clock. Returns (ring spans, reduced trace)."""
    bench = steps(n, step_ns=100_000_000, jitter_ns=9_000_000)
    events, spans = [], []

    def span(name, a, b, tid=PUMP):
        spans.append((name, a - OFFSET, b - OFFSET, tid))

    prev_end = bench[0][0] - 300_000
    for s, e in bench:
        events.append(ev("bench.sched_step", s, e - s, "/host:CPU", "py"))
        span("engine.ops", prev_end + 10_000, s - 10_000)
        span("engine.flush", prev_end + 20_000, prev_end + 60_000)
        a, b = s + 4_000, e - 4_000
        span("serving.step", a, b)
        span("serving.admit", a + 1_000, a + 50_000)
        span("serving.pack", a + 60_000, a + 90_000)
        c0, c1 = a + 100_000, b - 3_000_000
        span("serving.prefill_chunk", c0, c1)
        span("model.plan", c0 + 1_000, c0 + 400_000)
        width = (c1 - c0 - 1_000_000) // 4
        for li in range(4):
            l0 = c0 + 500_000 + li * width
            span("model.layer", l0, l0 + width - 1_000)
            span("model.norm", l0 + 1_000, l0 + width // 2)
            span("xla.trace", l0 + 2_000, l0 + width // 8)
            span("xla.lower", l0 + width // 8, l0 + width // 4)
            span("xla.build", l0 + width // 4, l0 + width // 2 - 1_000)
            p0 = l0 + width // 2 + 1_000
            span("pool.fused_step", p0, p0 + width // 4)
            span("pool.book", p0 + 1_000, p0 + 50_000)
            span("pool.table", p0 + 60_000, p0 + width // 8)
            span("kernel.ragged", p0 + width // 8 + 1_000,
                 p0 + width // 4 - 1_000)
            events.append(ev("%fusion.1 = f32[4]{0} fusion()",
                             p0 + width // 4, 5_000_000))
        span("serving.logits_pull", c1 + 10_000, c1 + 2_000_000)
        span("serving.decode", c1 + 2_010_000, b - 10_000)
        span("engine.stream_lag", b - 500_000, e + 700_000, LOOP)
        prev_end = e
    lo, hi = bench[0][0] - 100_000, bench[-1][1] + 20_000
    return spans, T.Reduced(events, window=(lo, hi)), n


def test_seven_shares_add_up_to_the_idle_share():
    spans, red, n = synthetic()
    got = P.lay(spans, 0, red, n)
    assert got["steps_in_window"] == n and got["pairs"] == n
    assert sum(got["idle_by"].values()) == got["idle_ns"]
    assert set(got["idle_by"]) == set(P.SHARES)
    ctx = {"trace": red, "counters": {"steps": n}, "_program_spans": got}
    total = sum(P.share(ctx, b) for b in P.SHARES)
    assert total == pytest.approx(100.0 * red.idle_share, abs=1e-9)
    # what was built lands where it was built: the share of the build
    # ranges is about 3/8 of each layer's width, the largest of the seven
    assert max(P.SHARES, key=lambda b: got["idle_by"][b]) == "build"
    assert got["idle_by"]["unattributed"] < 0.01 * got["idle_ns"]
    # spans of another thread take no part in the pump's shares
    assert "engine.stream_lag" not in got["self_ns"]
    assert len(got["lags_ms"]) == n - 1     # the last ends past the window
    assert all(x == pytest.approx(1.204) for x in got["lags_ms"])


def test_self_time_is_the_interval_minus_the_children():
    segs = P.self_segments([(0, 100, "a"), (10, 40, "b"), (20, 30, "c"),
                            (50, 120, "d"),      # overhangs its parent
                            (38, 48, "e")])      # and e overhangs b
    by = {}
    for a, b, n in segs:
        by[n] = by.get(n, 0) + (b - a)
    # each is clipped to the span it began in: e to b's end, d to a's
    assert by == {"a": 20, "b": 18, "c": 10, "e": 2, "d": 50}
    assert sum(by.values()) == 100
    assert all(segs[i][1] <= segs[i + 1][0] for i in range(len(segs) - 1))


@pytest.mark.parametrize("fault,why", [
    ("dropped", "dropped"),
    ("steps", "driver counted"),
    ("threads", "threads"),
])
def test_lay_refuses(fault, why):
    spans, red, n = synthetic()
    dropped = 0
    if fault == "dropped":           # and nothing older than the window
        dropped = 3
        spans = [s for s in spans if s[2] + OFFSET > red.window[0]]
    elif fault == "steps":
        n += 1
    else:
        name, a, b, _ = next(s for s in spans if s[0] == "serving.step")
        spans[spans.index((name, a, b, PUMP))] = (name, a, b, LOOP)
    with pytest.raises(P.Refused, match=why):
        P.lay(spans, dropped, red, n)


def test_a_drop_before_the_window_is_no_refusal():
    spans, red, n = synthetic()
    old = ("engine.idle", red.window[0] - 9_000_000 - OFFSET,
           red.window[0] - 8_000_000 - OFFSET, PUMP)
    got = P.lay([old] + spans, 3, red, n)
    assert got["dropped"] == 3


def test_readers_read_nothing_without_a_ring_or_on_refusal(capsys):
    spans, red, n = synthetic()
    assert P.collect() is None or P.collect()[0] == []   # no ring here
    ctx = {"trace": red, "counters": {"steps": n}}
    assert P.share(ctx, "build") is None
    assert P.stream_lag_p95_ms(ctx) is None
    # fewer than 200 lag samples: the percentile is not given
    ctx = {"_program_spans": P.lay(spans, 0, red, n)}
    assert P.stream_lag_p95_ms(ctx) is None
    assert "p95 wants 200" in capsys.readouterr().err


def test_every_new_metric_has_its_reader_and_entry():
    from benchmarks.lib import common
    bench = common.load_benchmark()
    cell = "mistral-7b-serve.decode-closed32"
    names = {m["name"]: m for m in bench["per_layer"]}
    spans, red, n = synthetic()
    ctx = {"trace": red, "counters": {"steps": n},
           "_program_spans": P.lay(spans, 0, red, n)}
    ctx["_program_spans"]["lags_ms"] = [1.0 + i / 1000 for i in range(400)]
    got = {}
    for b in P.SHARES:
        name = ("idle_unattributed_share.serve" if b == "unattributed"
                else f"idle_in_{b}_share.serve")
        assert names[name]["workloads"] == [cell]
        assert names[name]["source"] == "device_trace"
        got[name] = common.read_metric(name, ctx)
    assert sum(got.values()) == pytest.approx(100 * red.idle_share)
    assert names["stream_lag_p95_ms.serve"]["moves"] == "tpot_p95_ms"
    assert common.read_metric("stream_lag_p95_ms.serve", ctx) == \
        pytest.approx(1.379, abs=1e-3)
