"""Median time between the ends of consecutive optimizer steps, each end
seen by block_until_ready on that step's loss."""
from statistics import median


def read(ctx):
    iv = ctx["counters"].get("step_intervals_ms")
    if not iv:
        return None
    return median(iv)
