"""Percent of the traced window in which the first chip was idle and the
pump thread was in the self time of ``xla.cache_load``: an executable
read from JAX's persistent compilation cache, inside the ``xla.build``
that the cache served (the program's ``jax.monitoring`` listener,
paddle_tpu/jit/api.py). A part of ``idle_in_build_share.serve``; with
``idle_in_xla_trace_share.serve`` beside it, the rest of that share is
the cache key's hash and real compiles. A program that records no such
range (its ``telemetry.SURFACE`` declares none: an older commit) reads
nothing, and says so."""
import sys

from benchmarks.lib import program_spans

NAME = "idle_in_cache_load_share.serve"
SPAN = "xla.cache_load"


def read(ctx):
    got = program_spans.laid(ctx)
    if not got or got["window_ns"] <= 0:
        return None
    from paddle_tpu.framework import telemetry

    if "span:" + SPAN not in {n for n, _, _ in
                              getattr(telemetry, "SURFACE", ())}:
        print(f"{NAME}: the program records no {SPAN} range",
              file=sys.stderr, flush=True)
        return None
    return 100.0 * got["idle_by_name"].get(SPAN, 0) / got["window_ns"]
