"""Roofline share of the routed experts' grouped matmuls: the least time
the chip could take for the window's expert-layer calls (every touched
expert's three matrices once a call, the sorted activations; HBM-bound on
a v5e at a few tokens an expert) over the device time of the operations
that read the stacked expert weights. The assignments and the experts
touched are the window's own, from the ``moe.counts`` spans (the counts
are accumulated on the device and reach the host with the logits pull).
The program gives the grouped matmul no kernel name (``jax.lax.
ragged_dot`` lowers to XLA operations), so the operations are found by
their operand: the [experts, in, out] weight stacks. A family with no
such call, a program without the spans, a trace without such operations:
nothing returned."""
from benchmarks.lib import span_attrs


def read(ctx):
    t, cfg, f = ctx["trace"], ctx["config"], ctx["flops"]
    call = getattr(f, "moe_matmul_call", None)
    if t is None or call is None:
        return None
    e, c, w = (cfg["n_routed_experts"], cfg["hidden_size"],
               cfg["moe_intermediate_size"])
    stacks = r"\[%d,(%d,%d|%d,%d)\]" % (e, c, w, w, c)
    secs, calls = t.kernel_calls(regex=stacks)
    rows = span_attrs.in_window(ctx, "moe.counts")
    if not calls or secs <= 0 or not rows:
        return None
    ops, byts = call(cfg, span_attrs.total(rows, "assignments"),
                     span_attrs.total(rows, "experts_touched"))
    least, _ = f.roofline_seconds(ops, byts, ctx["peaks"])
    return 100.0 * least / secs
