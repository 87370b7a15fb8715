"""The program's own spans laid on the device trace's clock, and the
traced window's idle time split by what the pump thread was doing.

The program (paddle_tpu/framework/telemetry.py) records its spans in a
ring on ``time.perf_counter``; a profiler session switches them on. The
harness keeps no host event of the trace but its own ``bench.*``
annotations, and its ``bench.sched_step`` encloses each program
``serving.step`` one to one. So the two sequences of steps are paired
by their start-to-start differences, the clock offset is the median of
(bench start - program start), and it is CHECKED before anything is
read: every paired program step lies inside its wrapper to within
``TOL_NS``, the pairs of the window number the driver's step count, and
the ring dropped nothing the window needs. A check that fails gives no
number, and says why on standard error.

A program that has no such ring (an older commit) reads nothing."""
import sys

from . import common
from . import trace as T

TOL_NS = 100_000                 # a program step inside its wrapper
MAX_SHIFT = 3                    # unpaired steps at either end, at most
STEP = "serving.step"
LAG = "engine.stream_lag"
MIN_LAG_SAMPLES = 200            # p95 with ten samples beyond it

# which share a span's self time counts towards; first match wins
BUCKETS = (
    ("logits_pull", ("serving.logits_pull",), ()),
    ("dispatch", ("serving.prefill_chunk", "serving.draft_propose"),
     ("model.", "kernel.")),
    ("engine", (), ("engine.",)),
    ("pool", (), ("pool.",)),
    ("build", (), ("xla.",)),
    ("sched", (), ("serving.",)),
)
SHARES = tuple(b[0] for b in BUCKETS) + ("unattributed",)


class Refused(Exception):
    """The spans cannot be laid on the trace's clock with certainty."""


def bucket_of(name):
    for bucket, exact, prefixes in BUCKETS:
        if name in exact or any(name.startswith(p) for p in prefixes):
            return bucket
    return None


def collect():
    """The program's ring as ([(name, t0_ns, t1_ns, tid)], dropped), on
    the program's clock; None where the program keeps no such ring."""
    try:
        from paddle_tpu.framework import telemetry
    except ImportError:
        return None
    peek = getattr(telemetry, "peek_tracer", None)
    tr = peek() if peek is not None else None
    if tr is None:
        return None
    spans = [(s.name, round(s.t0 * 1e9), round((s.t0 + s.dur) * 1e9),
              s.tid) for s in tr.spans()]
    return spans, int(tr.dropped)


def align(prog_steps, bench_steps, tol_ns=TOL_NS):
    """Pair the program's steps with the harness's wrappers, both as
    sorted [(start_ns, end_ns)] on their own clocks. Returns (offset_ns,
    [(i_prog, j_bench)], spread_ns): trace time = program time + offset;
    spread is the widest distance of a pair's own offset from the median.
    Steps without a partner are allowed at the ends only (a step that was
    running when the session began or ended is in one record and not the
    other). Raises Refused when no pairing, or more than one, passes."""
    n, m = len(prog_steps), len(bench_steps)
    if n < 2 or m < 2:
        raise Refused(f"too few steps to pair: program {n}, trace {m}")
    passed = []
    for k in range(-MAX_SHIFT, MAX_SHIFT + 1):      # bench j = prog i + k
        pairs = [(i, i + k) for i in range(n) if 0 <= i + k < m]
        if len(pairs) < 2 or n - len(pairs) > MAX_SHIFT \
                or m - len(pairs) > MAX_SHIFT:
            continue
        offs = sorted(bench_steps[j][0] - prog_steps[i][0]
                      for i, j in pairs)
        off = offs[len(offs) // 2]
        if all(bench_steps[j][0] - tol_ns <= prog_steps[i][0] + off
               and prog_steps[i][1] + off <= bench_steps[j][1] + tol_ns
               for i, j in pairs):
            passed.append((off, pairs, max(off - offs[0], offs[-1] - off)))
    if not passed:
        raise Refused(
            f"no pairing of {n} program steps with {m} bench.sched_step "
            f"puts every step inside its wrapper to {tol_ns} ns: a step "
            "is missing, or a span lies outside its wrapper")
    if len(passed) > 1:
        raise Refused(f"{len(passed)} pairings pass: steps too regular "
                      "to tell apart")
    return passed[0]


def self_segments(spans):
    """[(t0, t1, name)] of ONE thread -> disjoint [(a, b, name)]: every
    instant goes to the innermost span that covers it, so a span keeps
    its interval minus what its children cover. A child is clipped to
    its parent and to what came before it (ranges recorded after the
    fact can overhang by the clock's jitter)."""
    out, stack = [], []                  # stack of (end, name)
    cur = None

    def close_until(t):
        nonlocal cur
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cur:
                out.append((cur, end, name))
                cur = end

    for t0, t1, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        if cur is None:
            cur = t0
        close_until(t0)
        if stack:
            t1 = min(t1, stack[-1][0])
        t0 = max(t0, cur)
        if t1 <= t0:
            continue
        if stack and t0 > cur:
            out.append((cur, t0, stack[-1][1]))
        cur = t0
        stack.append((t1, name))
    close_until(float("inf"))
    return out


def idle_gaps(red):
    """The first chip's idle intervals inside the window, trace clock."""
    lo, hi = red.window
    if not red.planes or hi <= lo:
        return []
    busy = T.union((max(e["start_ns"], lo),
                    min(e["start_ns"] + e["dur_ns"], hi))
                   for e in red.events if e["plane"] == red.planes[0]
                   and e["start_ns"] < hi
                   and e["start_ns"] + e["dur_ns"] > lo)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def lay(spans, dropped, red, steps_expected):
    """Everything the readers take, from the ring's spans, the reduced
    trace and the driver's step count. Raises Refused."""
    lo, hi = red.window
    prog = sorted((t0, t1, tid) for n, t0, t1, tid in spans if n == STEP)
    bench = sorted(red.host.get("sched_step", []))
    tids = {tid for _, _, tid in prog}
    if len(tids) > 1:
        raise Refused(f"{STEP} on {len(tids)} threads: one pump expected")
    off, pairs, spread = align([(a, b) for a, b, _ in prog], bench)
    inside = sum(1 for _, j in pairs
                 if lo <= (bench[j][0] + bench[j][1]) // 2 <= hi)
    if inside != steps_expected:
        raise Refused(f"{inside} paired steps inside the window, the "
                      f"driver counted {steps_expected}")
    if dropped and spans and min(s[2] for s in spans) + off > lo:
        raise Refused(f"the ring dropped {dropped} spans and its oldest "
                      "is younger than the window")
    pump = tids.pop()
    segs = [(a + off, b + off, n) for a, b, n in self_segments(
        [(t0, t1, n) for n, t0, t1, tid in spans if tid == pump])]
    gaps = idle_gaps(red)
    idle_by = dict.fromkeys(SHARES, 0)
    idle_by_name, self_ns = {}, {}
    j = 0                    # segments and gaps are both in time order
    for a, b, n in segs:
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        self_ns[n] = self_ns.get(n, 0) + (b - a)
        while j < len(gaps) and gaps[j][1] <= a:
            j += 1
        got, k = 0, j
        while k < len(gaps) and gaps[k][0] < b:
            got += min(b, gaps[k][1]) - max(a, gaps[k][0])
            k += 1
        idle_by[bucket_of(n) or "unattributed"] += got
        idle_by_name[n] = idle_by_name.get(n, 0) + got
    idle_total = sum(b - a for a, b in gaps)
    idle_by["unattributed"] += idle_total - sum(idle_by.values())
    lags = [(t1 - t0) / 1e6 for n, t0, t1, _ in spans
            if n == LAG and lo <= t1 + off <= hi]
    return {"offset_ns": off, "offset_spread_ns": spread,
            "pairs": len(pairs), "steps_in_window": inside,
            "dropped": dropped, "spans": len(spans),
            "window_ns": hi - lo, "idle_ns": idle_total,
            "idle_by": idle_by, "idle_by_name": idle_by_name,
            "self_ns": self_ns, "lags_ms": lags}


def laid(ctx):
    """``lay`` for a reader's ctx, once a run (kept in the ctx); None
    where there is nothing to read or the checks refuse."""
    if "_program_spans" not in ctx:
        got, why = None, None
        red, ring = ctx.get("trace"), collect()
        if red is not None and ring is not None:
            try:
                got = lay(ring[0], ring[1], red, ctx["counters"]["steps"])
            except Refused as e:
                why = str(e)
        if why:
            print(f"program_spans: refused: {why}", file=sys.stderr,
                  flush=True)
        elif got:
            by = {k: round(v / 1e6, 3)
                  for k, v in got["idle_by_name"].items()}
            print("program_spans: " + " ".join(
                f"{k}={got[k]}" for k in (
                    "pairs", "steps_in_window", "offset_ns",
                    "offset_spread_ns", "dropped", "spans")), file=sys.stderr)
            print(f"program_spans: idle_ms_by_span={by}", file=sys.stderr,
                  flush=True)
        ctx["_program_spans"] = got
    return ctx["_program_spans"]


def share(ctx, bucket):
    """Percent of the traced window in which the device was idle and the
    pump thread was in the self time of ``bucket``'s spans."""
    got = laid(ctx)
    if not got or got["window_ns"] <= 0:
        return None
    return 100.0 * got["idle_by"][bucket] / got["window_ns"]


def stream_lag_p95_ms(ctx):
    got = laid(ctx)
    if not got:
        return None
    lags = got["lags_ms"]
    if len(lags) < MIN_LAG_SAMPLES:
        print(f"program_spans: {len(lags)} {LAG} samples in the window, "
              f"p95 wants {MIN_LAG_SAMPLES}", file=sys.stderr, flush=True)
        return None
    return common.quantile(lags, 0.95)
