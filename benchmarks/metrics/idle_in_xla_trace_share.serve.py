"""Percent of the traced window in which the first chip was idle and the
pump thread was in the self time of ``xla.trace`` or ``xla.lower``: JAX
tracing a function to a jaxpr and lowering it to a module, host Python
that no persistent cache removes. A part of ``idle_in_build_share.serve``;
with ``idle_in_cache_load_share.serve`` beside it, the rest of that share
is the cache key's hash and real compiles."""
from benchmarks.lib import program_spans

SPANS = ("xla.trace", "xla.lower")


def read(ctx):
    got = program_spans.laid(ctx)
    if not got or got["window_ns"] <= 0:
        return None
    idle = sum(got["idle_by_name"].get(n, 0) for n in SPANS)
    return 100.0 * idle / got["window_ns"]
