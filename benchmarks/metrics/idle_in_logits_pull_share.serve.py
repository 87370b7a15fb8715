"""Percent of the traced window in which the device was idle and the pump
thread was in ``serving.logits_pull``: waiting for the device to finish
the step's last program and copying the logits to the host.
The seven ``idle_*`` shares add up to ``device_idle_share.serve``."""
from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.share(ctx, "logits_pull")
