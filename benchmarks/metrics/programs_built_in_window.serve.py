"""Distinct programs built inside the traced window: the (``fun``,
``key``) pairs of the program's ``xla.build`` ranges that end in it.
``fun`` is the function JAX built, ``key`` the short string of the shapes
and static switches that chose it, copied from the dispatch span the
build ran under (paddle_tpu/jit/api.py, framework/telemetry.py). Every
pair goes to standard error with its cache outcome (``hit``: a load from
the persistent cache; ``miss``: a compile; ``off``), its builds, their
summed seconds and the scheduler steps (``serving.step`` ``n``) that met
them. A steady path reads 0. A program whose builds name no cache outcome
(its ``telemetry.SURFACE`` declares no ``xla.cache_load``: an older
commit) reads nothing, and says so."""
import bisect
import sys

from benchmarks.lib import program_spans

NAME = "programs_built_in_window.serve"


def read(ctx):
    got = program_spans.laid(ctx)
    if not got:
        return None
    from paddle_tpu.framework import telemetry

    tr = telemetry.peek_tracer()
    if tr is None or "span:xla.cache_load" not in {
            n for n, _, _ in getattr(telemetry, "SURFACE", ())}:
        print(f"{NAME}: the program names no build", file=sys.stderr,
              flush=True)
        return None
    lo, hi = ctx["trace"].window
    off = got["offset_ns"]
    spans = tr.spans()
    steps = sorted((s.t0, s.t0 + s.dur, s.attrs.get("n"))
                   for s in spans if s.name == program_spans.STEP)
    starts = [s[0] for s in steps]
    built = {}
    for s in spans:
        if s.name != "xla.build" \
                or not lo <= round((s.t0 + s.dur) * 1e9) + off <= hi:
            continue
        b = built.setdefault((s.attrs.get("fun", ""), s.attrs.get("key", "")),
                             {"cache": set(), "builds": 0, "s": 0.0,
                              "steps": []})
        b["cache"].add(s.attrs.get("cache", "?"))
        b["builds"] += 1
        b["s"] += s.dur
        i = bisect.bisect_right(starts, s.t0) - 1
        if i >= 0 and s.t0 + s.dur <= steps[i][1]:
            b["steps"].append(steps[i][2])
    for (fun, key), b in sorted(built.items(), key=lambda kv: -kv[1]["s"]):
        print(f"{NAME}: fun={fun!r} key={key!r} "
              f"cache={'/'.join(sorted(b['cache']))} builds={b['builds']} "
              f"seconds={b['s']:.4f} steps={b['steps']}", file=sys.stderr)
    sys.stderr.flush()
    return len(built)
