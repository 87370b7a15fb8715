"""Attributes of the program's own spans inside the traced window: what a
per-layer reader takes where the program hands a step's counts to the
host as attributes of a span (the registry is off in a benchmark run;
the spans are live under the profiler's session). The spans are laid on
the trace's clock by ``program_spans`` and read only where its checks
pass. A program with no ring, or with no span of the name (an older
commit), reads nothing."""
from . import program_spans


def in_window(ctx, name):
    """[attrs] of the ring's spans called ``name`` that end inside the
    traced window, in ring order; None where nothing can be read."""
    got = program_spans.laid(ctx)
    if not got:
        return None
    try:
        from paddle_tpu.framework import telemetry
    except ImportError:
        return None
    peek = getattr(telemetry, "peek_tracer", None)
    tr = peek() if peek is not None else None
    if tr is None:
        return None
    lo, hi = ctx["trace"].window
    off = got["offset_ns"]
    return [dict(s.attrs or {}) for s in tr.spans()
            if s.name == name
            and lo <= round((s.t0 + s.dur) * 1e9) + off <= hi]


def total(rows, key):
    """Sum of attribute ``key`` over the spans that carry it."""
    return sum(r[key] for r in rows if key in r)
