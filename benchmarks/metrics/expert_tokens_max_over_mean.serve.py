"""Imbalance of the routed experts over the traced window: the fullest
expert's tokens over the mean expert's, summed over every expert-layer
call of the window's steps (1: perfectly even; E / k: every token to the
same experts). Read from the ``moe.counts`` spans, which carry each
step's per-layer expert counts as they reach the host with the logits
pull. A program without them: nothing returned."""
from benchmarks.lib import span_attrs


def read(ctx):
    rows = span_attrs.in_window(ctx, "moe.counts")
    if not rows:
        return None
    mean = span_attrs.total(rows, "expert_tokens_mean")
    if mean <= 0:
        return None
    return span_attrs.total(rows, "expert_tokens_max") / mean
