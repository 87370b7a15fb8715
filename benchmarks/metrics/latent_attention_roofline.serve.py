"""Roofline share of the latent ragged attention kernel: the least time
the chip could take for the window's calls (every cached latent row of
every sequence once a layer call, the absorbed queries in and the latent
outputs back; HBM-bound on a v5e at these chunks) over the kernel's time
in the trace. A family with no latent kernel, or a trace with no such
kernel: nothing returned."""

TARGET = 'custom_call_target="tpu_custom_call"'
NAMES = ("latent_ragged_attention",)


def read(ctx):
    t, c, cfg, f = ctx["trace"], ctx["counters"], ctx["config"], ctx["flops"]
    call = getattr(f, "latent_attention_call", None)
    if t is None or call is None:
        return None
    secs, calls = t.kernel_calls(any_of=NAMES, all_of=(TARGET,))
    if not calls or secs <= 0:
        return None
    ops, byts = call(cfg, c["tokens_fed"], c["ctx_fed"], c["ctx_rows"])
    layers = cfg["num_hidden_layers"]
    least, _ = f.roofline_seconds(ops * layers, byts * layers, ctx["peaks"])
    return 100.0 * least / secs
