"""Model FLOPs of every prompt and decode token the window processed
(2 x matmul parameters a token, the head once a sampled row, attention
over the real context) over window x chips x peak, in percent."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("tokens_fed") or ctx["window_s"] <= 0:
        return None
    ops = ctx["flops"].serve_flops(ctx["config"], c["tokens_fed"],
                                   c["rows_sampled"], c["ctx_fed"])
    return 100.0 * ops / (ctx["window_s"] * ctx["chips"]
                          * ctx["peaks"]["flops_bf16"])
