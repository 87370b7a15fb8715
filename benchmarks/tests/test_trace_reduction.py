"""The reduction from a trace to numbers: busy union, idle share, kernel
time by name, idle gaps named by what the host was doing. First on a
hand-made trace whose answers are known exactly, then on a small trace
recorded on the chip (tests/data/small_trace.json.gz, two scheduler steps
of the serving cell and two optimizer steps of the training cell)."""
import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.lib import trace as T  # noqa: E402

DEV = "/device:TPU:0"


def ev(name, start, dur, plane=DEV, line="XLA Ops"):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur, "text": ""}


def hand_made():
    k = ('%runner.1 = bf16[8,64]{1,0} custom-call(bf16[8,64]{1,0} %a), '
         'custom_call_target="tpu_custom_call"')
    return [
        ev("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop", 100, 50),
        ev(k, 120, 10),                       # nested in fusion.1's span
        ev("%while.1 = (s32[]) while((s32[]) %t), body=%b", 200, 100),
        ev("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %q), kind=kLoop", 210, 30),
        ev(k, 250, 40),
        ev("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop", 600, 100),
        ev("%fusion.9 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop", 950, 100),
        ev("bench.sched_step", 90, 320, plane="/host:CPU", line="python3"),
        ev("bench.sched_step", 580, 130, plane="/host:CPU", line="python3"),
    ]


def test_union_and_overlap():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3], [5, 9]]
    assert T._overlap([[0, 10]], [[2, 4], [8, 12]]) == 4


def test_busy_idle_and_kernel_time_exact():
    r = T.Reduced(hand_made(), window=(0, 1000),
                  host_spans={"compiling": [(300, 560)]})
    # busy: [100,150] + [200,300] + [600,700] + [950,1000 cut] = 300 ns
    assert r.busy_s == pytest.approx(300e-9)
    assert r.window_s == pytest.approx(1000e-9)
    assert r.idle_share == pytest.approx(0.7)
    secs, calls = r.kernel_calls(all_of=('"tpu_custom_call"', "[8,64]"))
    assert calls == 2 and secs == pytest.approx(50e-9)
    assert r.kernel_calls(any_of=("no_such_kernel",))[1] == 0
    ops = r.op_seconds()
    # wrappers (fusion.1 around the kernel, the while loop) are not counted
    # beside what runs inside them
    assert ops["%runner.1 custom-call tpu_custom_call bf16[8,64]"] == \
        pytest.approx(50e-9)
    assert not any(k.startswith("%while.1") for k in ops)
    assert ops["%fusion.2 fusion f32[4]"] == pytest.approx(30e-9)


def test_gaps_are_named_by_what_the_host_did():
    r = T.Reduced(hand_made(), window=(0, 1000),
                  host_spans={"compiling": [(300, 560)]})
    gaps = r.gaps()
    assert [round(s * 1e9) for s, _ in gaps] == [300, 250, 100, 50]
    kinds = dict((round(s * 1e9), k) for s, k in gaps)
    assert kinds[300] == "compiling"       # 300..600: 260 of it compiling
    assert kinds[250] == "other"           # 700..950: the host did nothing
    assert kinds[50] == "sched_step"       # 150..200: inside sched.step
    assert kinds[100] == "other"           # 0..100: a tenth covered, no more
    bd = r.breakdown()
    assert bd["idle_gaps"][0] == ["all:other", pytest.approx(350e-9)]
    assert bd["idle_gaps"][1] == ["all:compiling", pytest.approx(300e-9)]
    assert bd["idle_gaps"][3] == ["longest:compiling", pytest.approx(300e-9)]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_window_defaults_to_the_annotation():
    evs = hand_made() + [ev("bench.window", 100, 600, plane="/host:CPU",
                            line="python3")]
    r = T.Reduced(evs)
    assert r.window == (100, 700)
    assert r.busy_s == pytest.approx(250e-9)


def test_no_device_events_reads_nothing():
    r = T.Reduced([ev("bench.sched_step", 0, 10, plane="/host:CPU")],
                  window=(0, 10))
    assert r.idle_share is None and r.gaps() == []


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "data", "small_trace.json.gz"),
                   "rt") as f:
        return json.load(f)


@pytest.mark.parametrize("part", ["serve", "train"])
def test_recorded_trace(recorded, part):
    d = recorded[part]
    r = T.Reduced(d["events"], window=tuple(d["window"]),
                  host_spans={"compiling": [tuple(x) for x in d["compiling"]]})
    want = d["expected"]
    assert r.window_s == pytest.approx(want["window_s"])
    assert r.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 100 * r.idle_share == pytest.approx(want["idle_pct"], rel=1e-9)
    secs, calls = r.kernel_calls(all_of=tuple(d["kernel"]))
    assert calls == want["kernel_calls"]
    assert secs == pytest.approx(want["kernel_s"], rel=1e-9)
    gaps = r.gaps()
    assert gaps[0][1] == want["longest_gap_kind"]
    if part == "serve":      # the chip waits for the host, which compiles
        assert r.idle_share > 0.5
        assert {k for _, k in gaps[:20]} & {"compiling", "sched_step"}
    else:                    # one program a step, nothing between
        assert r.idle_share < 0.05
