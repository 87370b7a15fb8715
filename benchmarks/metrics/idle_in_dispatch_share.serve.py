"""Percent of the traced window in which the device was idle and the pump
thread was in eager dispatch, what is left of the model call once builds
and the pool are taken out: the self time of ``serving.prefill_chunk``,
``model.*`` and ``kernel.ragged``.
The seven ``idle_*`` shares add up to ``device_idle_share.serve``."""
from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.share(ctx, "dispatch")
