"""6 x matmul parameters a token plus attention (recompute not counted),
times the tokens of the window's steps, over window x chips x peak."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("steps") or ctx["window_s"] <= 0:
        return None
    ops = ctx["flops"].train_step_flops(ctx["config"], c["batch"],
                                        c["seq_len"]) * c["steps"]
    return 100.0 * ops / (ctx["window_s"] * ctx["chips"]
                          * ctx["peaks"]["flops_bf16"])
