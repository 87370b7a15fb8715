"""Roofline share of the ragged paged-attention kernel under the
block-causal mask: the least time the chip could take for the window's
calls over the kernel's time in the trace. Each call's exact counts ride
the program's ``kernel.ragged`` span (``block``, ``fed`` tokens, ``pairs``
= the keys each fed token sees, its own block's whole, summed, ``kv_rows``
= the cached rows of every sequence), taken from the table the call was
handed; the least time of a call is its own maximum of compute and memory
time. A family with no such call, a program whose spans carry no such
counts (an older commit), or a trace without the kernel: nothing
returned."""
from benchmarks.lib import span_attrs

TARGET = 'custom_call_target="tpu_custom_call"'
NAMES = ("ragged_paged_attention",)


def read(ctx):
    t, cfg, f = ctx["trace"], ctx["config"], ctx["flops"]
    call = getattr(f, "block_attention_call", None)
    if t is None or call is None:
        return None
    secs, calls = t.kernel_calls(any_of=NAMES, all_of=(TARGET,))
    if not calls or secs <= 0:
        return None
    rows = [r for r in span_attrs.in_window(ctx, "kernel.ragged") or ()
            if "block" in r and "kv_rows" in r]
    if not rows:
        return None
    least = sum(f.roofline_seconds(
        *call(cfg, r["fed"], r["pairs"], r["kv_rows"]), ctx["peaks"])[0]
        for r in rows)
    return 100.0 * least / secs
