"""Percent of the traced window in which the device was idle and the pump
thread was building a program: ``xla.trace``, ``xla.lower`` and
``xla.build`` (a persistent-cache load lands in the last), wherever they
nest.
The seven ``idle_*`` shares add up to ``device_idle_share.serve``."""
from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.share(ctx, "build")
