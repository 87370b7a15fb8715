"""Percent of the traced window's scheduler steps that carried prompt
tokens beside their decode rows (the ``prefill`` attribute of the
program's ``serving.pack`` span): it says which kind of step the 95th
percentile of the gap between tokens is. A program whose spans carry no
such attribute (an older commit): nothing returned."""
from benchmarks.lib import span_attrs


def read(ctx):
    rows = [r for r in span_attrs.in_window(ctx, "serving.pack") or ()
            if "prefill" in r]
    if not rows:
        return None
    return 100.0 * sum(1 for r in rows if r["prefill"] > 0) / len(rows)
