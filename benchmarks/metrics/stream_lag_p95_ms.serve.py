"""95th percentile of ``engine.stream_lag`` ranges that end in the traced
window, in ms: from the commit of a flushed batch's first token on the
pump thread to ``TokenStream.__anext__`` handing it to the client. Wants
200 samples (ten beyond the percentile); fewer read nothing."""
from benchmarks.lib import program_spans


def read(ctx):
    return program_spans.stream_lag_p95_ms(ctx)
