"""Percent of the traced window in which the first chip was idle and the
pump thread, inside its spans, held no CPU: waiting for the GIL, a
transfer, a lock or a sleep rather than running Python or native code.

An ESTIMATE per span. A live span records ``cpu``, the thread's CPU
seconds between its enter and exit (``time.thread_time``), beside its
wall ``dur`` (framework/telemetry.py). Per span of the pump thread: self
wall = its interval minus its children's; self CPU = its ``cpu`` minus
its children's, a child recorded after the fact (``xla.*``, ``py.gc``:
no ``cpu``) taken as on the CPU throughout; self off-CPU = self wall -
self CPU. The span's idle self time counts in the ratio of its self
off-CPU to its self wall, summed over the spans of a name and only then
held to [0, the name's idle self time]: a thread clock that counts in
ticks reads a short span's CPU as 0 or a whole tick, so one span's
reading may lie outside [0, its wall] while the sum is right. One number of CPU
seconds covers a span's whole self time, so where inside it the thread
waited is not known: a wait is spread evenly over the span, over its
idle and its busy instants alike. Standard error lists the spans that
give the most. A program whose spans carry no ``cpu`` (an older commit)
reads nothing, and says so."""
import sys

from benchmarks.lib import program_spans

NAME = "idle_offcpu_share.serve"


def offcpu_idle_ns(own, gaps):
    """[(t0_ns, t1_ns, name, cpu_s or None)] of ONE thread on the
    trace's clock, the idle gaps -> {name: (idle self ns, idle self ns
    off the CPU)}."""
    own = sorted(own, key=lambda s: (s[0], -s[1]))
    n = len(own)
    child_cpu = [0.0] * n
    stack = []
    for i, (a, b, _, _) in enumerate(own):
        while stack and own[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            cpu = own[i][3]
            child_cpu[stack[-1]] += (b - a) / 1e9 if cpu is None else cpu
        stack.append(i)
    wall, idle = [0] * n, [0] * n
    j = 0
    for a, b, i in program_spans.self_segments(
            [(a, b, i) for i, (a, b, _, _) in enumerate(own)]):
        wall[i] += b - a
        while j < len(gaps) and gaps[j][1] <= a:
            j += 1
        k = j
        while k < len(gaps) and gaps[k][0] < b:
            idle[i] += min(b, gaps[k][1]) - max(a, gaps[k][0])
            k += 1
    out = {}
    for i, (_, _, name, cpu) in enumerate(own):
        if not idle[i]:
            continue
        off = 0.0
        if cpu is not None:
            off = idle[i] * (wall[i] - (cpu - child_cpu[i]) * 1e9) / wall[i]
        got = out.get(name, (0, 0.0))
        out[name] = (got[0] + idle[i], got[1] + off)
    return {k: (a, min(max(b, 0.0), a)) for k, (a, b) in out.items()}


def read(ctx):
    got = program_spans.laid(ctx)
    if not got or got["window_ns"] <= 0:
        return None
    from paddle_tpu.framework import telemetry

    tr = telemetry.peek_tracer()
    if tr is None or "cpu" not in getattr(telemetry.Span, "__slots__", ()):
        print(f"{NAME}: the program's spans carry no cpu", file=sys.stderr,
              flush=True)
        return None
    off = got["offset_ns"]
    spans = tr.spans()
    pump = {s.tid for s in spans if s.name == program_spans.STEP}
    by = offcpu_idle_ns(
        [(round(s.t0 * 1e9) + off, round((s.t0 + s.dur) * 1e9) + off,
          s.name, s.cpu) for s in spans if s.tid in pump],
        program_spans.idle_gaps(ctx["trace"]))
    top = {k: (round(v[0] / 1e6, 1), round(v[1] / 1e6, 1)) for k, v in
           sorted(by.items(), key=lambda kv: -kv[1][1])[:10]}
    print(f"{NAME}: idle self ms (all, off the CPU) by span {top}",
          file=sys.stderr, flush=True)
    return 100.0 * sum(v[1] for v in by.values()) / got["window_ns"]
