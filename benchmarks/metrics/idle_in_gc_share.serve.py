"""Percent of the traced window in which the first chip was idle and
Python's collector was running on ANY thread: the union of the program's
``py.gc`` ranges (framework/telemetry.py; a collection stops every
thread), laid on the trace's clock with the ring's offset, intersected
with the chip's idle gaps. On the pump thread a collection nests inside
whatever span was open, and its self time counts in
``idle_unattributed_share.serve``. Standard error also names the
window's longest idle gap and what covered it: the pump's spans (self
time) and the collections. A program that records no such range (its
``telemetry.SURFACE`` declares no ``py.gc``: an older commit) reads
nothing, and says so."""
import sys

from benchmarks.lib import program_spans
from benchmarks.lib import trace as T

NAME = "idle_in_gc_share.serve"
SPAN = "py.gc"


def _overlap(a, b):
    """Total length of the intersection of two sorted disjoint lists."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        tot += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _longest_gap(gaps, spans, off):
    """What covered the window's longest idle gap, on standard error."""
    lo, hi = max(gaps, key=lambda g: g[1] - g[0])
    on = [(round(s.t0 * 1e9) + off, round((s.t0 + s.dur) * 1e9) + off, s)
          for s in spans]
    pump = {s.tid for _, _, s in on if s.name == program_spans.STEP}
    steps = [s.attrs.get("n") for a, b, s in on
             if s.name == program_spans.STEP and a < hi and b > lo]
    by = {}
    for a, b, name in program_spans.self_segments(
            [(a, b, s.name) for a, b, s in on if s.tid in pump]):
        cut = min(b, hi) - max(a, lo)
        if cut > 0:
            by[name] = by.get(name, 0) + cut
    gcs = [(s.attrs.get("gen"), round((min(b, hi) - max(a, lo)) / 1e6, 3),
            "pump" if s.tid in pump else "other")
           for a, b, s in on if s.name == SPAN and a < hi and b > lo]
    by = {k: round(v / 1e6, 3)
          for k, v in sorted(by.items(), key=lambda kv: -kv[1])}
    print(f"{NAME}: longest idle gap {(hi - lo) / 1e6:.3f} ms in steps "
          f"{steps}; pump self ms {by}; py.gc (gen, ms, thread) {gcs}",
          file=sys.stderr, flush=True)


def read(ctx):
    got = program_spans.laid(ctx)
    if not got or got["window_ns"] <= 0:
        return None
    from paddle_tpu.framework import telemetry

    tr = telemetry.peek_tracer()
    if tr is None or "span:" + SPAN not in {
            n for n, _, _ in getattr(telemetry, "SURFACE", ())}:
        print(f"{NAME}: the program records no {SPAN} range",
              file=sys.stderr, flush=True)
        return None
    red, off = ctx["trace"], got["offset_ns"]
    spans = tr.spans()
    ranges = [(round(s.t0 * 1e9) + off, round((s.t0 + s.dur) * 1e9) + off,
               s.attrs.get("gen")) for s in spans if s.name == SPAN]
    lo, hi = red.window
    inside = [r for r in ranges if lo <= r[1] and r[0] <= hi]
    gaps = program_spans.idle_gaps(red)
    if gaps:
        _longest_gap(gaps, spans, off)
    gens = {g: sum(1 for r in inside if r[2] == g) for g in (0, 1, 2)}
    longest = max((r[1] - r[0] for r in inside), default=0) / 1e6
    print(f"{NAME}: {len(inside)} collections in the window, by gen "
          f"{gens}, longest {longest:.3f} ms", file=sys.stderr, flush=True)
    idle = _overlap(T.union((a, b) for a, b, _ in ranges), gaps)
    return 100.0 * idle / got["window_ns"]
