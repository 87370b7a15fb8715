"""Percent of the traced window in which the device was idle and the pump
thread was in the scheduler's own code: the self time of ``serving.step``,
``serving.admit``, ``serving.pack``, ``serving.decode`` (sampler, commit,
``on_token``) and the request-scoped ``serving.*`` spans.
The seven ``idle_*`` shares add up to ``device_idle_share.serve``."""
from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.share(ctx, "sched")
