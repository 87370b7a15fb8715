"""Roofline share of the ragged paged-attention kernel: the least time the
chip could take for the window's calls (the real sequence lengths of every
row of every step; HBM-bound on a v5e) over the kernel's time in the
trace. Nothing in the trace that looks like the kernel: nothing returned."""

TARGET = 'custom_call_target="tpu_custom_call"'
NAMES = ("ragged_paged_attention", "paged_ragged", "ragged_attention")


def read(ctx):
    t, c, cfg = ctx["trace"], ctx["counters"], ctx["config"]
    if t is None:
        return None
    # the program gives its kernels no name yet: the ragged kernel is the
    # Mosaic call that returns [rows, query heads, tokens a row, head_dim]
    out = r"= bf16\[\d+,%d,\d+,%d\]\S* custom-call\(" % (
        cfg["num_attention_heads"], cfg["head_dim"])
    secs, calls = t.kernel_calls(all_of=(TARGET,), regex=out)
    named = t.kernel_calls(any_of=NAMES, all_of=(TARGET,))
    if named[1]:
        secs, calls = named
    if not calls or secs <= 0:
        return None
    f = ctx["flops"]
    ops, byts = f.ragged_attention_call(cfg, c["tokens_fed"], c["ctx_fed"],
                                        c["ctx_rows"])
    layers = cfg["num_hidden_layers"]
    least, _ = f.roofline_seconds(ops * layers, byts * layers, ctx["peaks"])
    return 100.0 * least / secs
