"""Row-passes a delivered token of a block-diffusion model: over the
traced window's scheduler steps, the decode rows that made a pass
(denoising or commit: the ``denoise_rows`` and ``commit_rows`` attributes of
the program's ``serving.block`` span, one a step) over the tokens those
steps delivered (``delivered``). A block of B tokens costs its row T
denoising passes and one commit pass, so (T + 1) / B where every block
takes all its passes: 0.75 at B = 4, T = 2. A program that fixes more
positions a pass, or folds a block's commit into the next block's first
pass, reads lower. A program whose spans carry no such attributes (an
older commit, another family): nothing returned."""
from benchmarks.lib import span_attrs


def read(ctx):
    rows = [r for r in span_attrs.in_window(ctx, "serving.block") or ()
            if "delivered" in r]
    delivered = span_attrs.total(rows, "delivered")
    if not delivered:
        return None
    return (span_attrs.total(rows, "denoise_rows")
            + span_attrs.total(rows, "commit_rows")) / delivered
