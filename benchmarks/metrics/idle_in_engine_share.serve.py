"""Percent of the traced window in which the device was idle and the pump
thread was in the engine, outside a scheduler step: the self time of
``engine.idle`` (parked in its wake event), ``engine.ops`` (inbox,
deadlines, retire, gate) and ``engine.flush`` (token marshalling).
The seven ``idle_*`` shares add up to ``device_idle_share.serve``."""
from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.share(ctx, "engine")
