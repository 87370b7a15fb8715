"""Percent of the window's device time spent in operations of the mHC
residual path: the sites' coefficient projections, Sinkhorn, the reads
(Hpre X) and the writes (Hres X + Hpost^T y), the copy of the embedding
into the streams and the last read. The device trace's operation events
carry no scope, so the operations are found by what only they touch: the
n-stream state [tokens, n, C], its flattened form [tokens, nC], the
projections [nC, width] and the coefficients [tokens, n, n]. A
configuration with one residual stream, or a trace without such
operations: nothing returned."""


def read(ctx):
    t, cfg = ctx["trace"], ctx["config"]
    n = cfg.get("hc_mult")
    if t is None or not n or not t.busy_s:
        return None
    c = cfg["hidden_size"]
    shapes = r"\[(\d+,%d,%d|\d+,%d|%d,\d+|\d+,%d,%d)\]" % (
        n, c, n * c, n * c, n, n)
    secs, calls = t.kernel_calls(regex=shapes)
    if not calls:
        return None
    return 100.0 * secs / (t.busy_s * len(t.planes))
