"""Percent of the traced window in which the device was idle and no program
span covered the pump thread: a hole in the spans, to be near 0.
The seven ``idle_*`` shares add up to ``device_idle_share.serve``."""
from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.share(ctx, "unattributed")
