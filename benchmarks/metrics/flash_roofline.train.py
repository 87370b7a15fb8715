"""Roofline share of the flash-attention kernels, forward and backward:
the least time for the attention of the window's steps (causal, window
4096; compute-bound on a v5e) over the kernels' time in the trace."""

TARGET = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    t, c = ctx["trace"], ctx["counters"]
    if t is None:
        return None
    cfg = ctx["config"]
    # the program gives its kernels no name yet: the flash kernels are the
    # Mosaic calls that take [batch x heads, sequence, head_dim] operands
    qshape = "[%d,%d,%d]" % (c["batch"] * cfg["num_attention_heads"],
                             c["seq_len"], cfg["head_dim"])
    secs, calls = t.kernel_calls(all_of=(TARGET, qshape))
    named = t.kernel_calls(any_of=("flash",), all_of=(TARGET,))
    if named[1]:
        secs, calls = named
    if not calls or secs <= 0:
        return None
    f = ctx["flops"]
    ops = f.flash_train_flops(ctx["config"], c["batch"], c["seq_len"]) \
        * c["steps"]
    # q, k, v, o and their gradients, once each way: far under the compute
    # bound at these lengths, so the bound is compute's
    return 100.0 * (ops / ctx["peaks"]["flops_bf16"]) / secs
