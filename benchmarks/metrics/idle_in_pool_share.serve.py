"""Percent of the traced window in which the device was idle and the pump
thread was in the KV pool: the self time of ``pool.fused_step``,
``pool.book`` (capacity check, forks, slot plan) and ``pool.table`` (page
table and scatter plan built in numpy and uploaded).
The seven ``idle_*`` shares add up to ``device_idle_share.serve``."""
from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.share(ctx, "pool")
