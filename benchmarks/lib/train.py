"""The training driver: one ``@paddle.jit.to_static`` step (forward, tape
backward, AdamW, clear_grad) built once, driven from the seed through its
first steps in set-up (their losses, first gradient and parameter change
are kept for the comparison) and then handed, the same object, to the
measured window."""
import gc
import time

import numpy as np

from . import common, correct, traffic
from . import weights as W


def _build(family, config, seed):
    """The program's model with the seed's weights, its optimizer and the
    compiled step. Returns (step, params by leaf path, optimizer)."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as optim

    model, params = common.build_model(family, config, seed)
    prog = config["program"]
    o = prog["optimizer"]
    opt = getattr(optim, o["class"])(
        o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        parameters=model.parameters())

    @paddle.jit.to_static
    def train_step(x, y):
        _, loss = model(x, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return train_step, params, opt


def _state_reads(family, config, seed, params, opt):
    """Two reads of the optimizer's state: the norm of every leaf's first
    moment, and the norm of every master weight's distance from the seed's
    weights (made anew, layer by layer)."""
    import jax
    import jax.numpy as jnp

    paths = list(params)
    def l2(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

    @jax.jit
    def norms(leaves):
        return [l2(x) for x in leaves]

    @jax.jit
    def dist(a, b):
        return l2(a.astype(jnp.float32) - b.astype(jnp.float32))

    def moment1():
        acc = opt._accumulators["moment1"]
        vals = norms([acc[params[p]._uid]._data for p in paths])
        return {p: float(v) for p, v in zip(paths, vals)}

    def change():
        # the seed's weights again, as arrays of their own: made inside the
        # same program as the subtraction, XLA keeps them in float32 and the
        # bf16 rounding of the start would read as movement
        mw, out = opt._master_weights, {}
        spec = W.spec(family.leaves(config), family.LEAF_NAMES,
                      config["initializer_range"])
        by_layer = {}
        for p in paths:
            by_layer.setdefault(W.split(p)[0], []).append(p)
        top = W.make_top(spec, seed)      # held while the layers are made
        for layer in sorted(by_layer):
            made = top if layer < 0 else W.make_layer(spec, seed, layer)
            for p in by_layer[layer]:
                out[p] = dist(mw[params[p]._uid]._data,
                              made[W.split(p)[1]])
        return {p: float(out[p]) for p in paths}

    return moment1, change


def run(bench, cell, config, family, mix, seed, seconds, trace, t_proc0,
        device, peaks, break_with=None, trace_dir=None, limits=None):
    """One run of a training cell. ``break_with`` (tests only) takes the
    compiled step and returns the callable that is driven in its place;
    ``limits`` (tests only) stands in for limits/<cell>.json."""
    import jax
    import paddle_tpu as paddle
    from jax.profiler import TraceAnnotation
    from paddle_tpu.jit.api import ensure_compilation_cache

    watch = common.BuildWatch()
    ensure_compilation_cache()
    vocab = config["vocab_size"]
    batch, seq = int(mix["batch"]), int(mix["seq_len"])
    n_check = int(mix["check_steps"])
    beta1 = config["program"]["optimizer"]["beta1"]

    step, params, opt = _build(family, config, seed)
    moment1, change = _state_reads(family, config, seed, params, opt)
    drive = break_with(step) if break_with else step

    def feed(i):
        ids = traffic.train_batch(mix, seed, i, vocab)
        return (paddle.to_tensor(ids), paddle.to_tensor(ids.astype("int64")))

    # set-up: the first steps, through the window's own call and feed
    got = {"loss": [], "grad1": None, "delta": None}
    for i in range(n_check):
        loss = drive(*feed(i))
        got["loss"].append(float(np.asarray(loss._data, np.float32)))
        if i == 0:
            got["grad1"] = {p: v / (1.0 - beta1)
                            for p, v in moment1().items()}
    got["delta"] = change()
    jax.block_until_ready(drive(*feed(n_check))._data)   # a step past them
    common.note(phase="setup", losses=got["loss"], builds=len(watch.builds))

    sync_ns = common.start_trace(trace_dir) if trace else None
    in_flight = int(mix.get("in_flight", 2))
    pending, done_at = [], []
    i = n_check + 1
    t0_ns = time.time_ns()
    t0 = time.perf_counter()
    setup_s = t0 - t_proc0
    with TraceAnnotation("bench.window"):
        while True:
            with TraceAnnotation("bench.feed"):
                x, y = feed(i)
            pending.append(drive(x, y))
            i += 1
            if len(pending) >= in_flight:
                jax.block_until_ready(pending.pop(0)._data)
                done_at.append(time.perf_counter())
                if done_at[-1] - t0 >= seconds:
                    break
        # steps still in flight are finished and counted: the window ends
        # with the last of them
        for last in pending:
            jax.block_until_ready(last._data)
            done_at.append(time.perf_counter())
    t1 = done_at[-1]
    t1_ns = time.time_ns()
    if trace:
        jax.profiler.stop_trace()
    window_s = t1 - t0
    steps = len(done_at)
    builds_in_window = watch.count_between(t0_ns, t1_ns)
    peak = common.memory_peak_bytes()
    common.note(phase="window", steps=steps, window_s=window_s,
                builds_in_window=builds_in_window, setup_s=setup_s)

    # free the program's state, then follow the first steps in the reference
    step = drive = params = opt = moment1 = change = None
    pending = x = y = loss = last = None
    gc.collect()
    from . import reference

    batches = [traffic.train_batch(mix, seed, k, vocab)
               for k in range(n_check)]
    want = reference.train_reference(
        family, config, config["program"]["optimizer"], seed, batches)
    compared = correct.compare_training(
        got, want, limits or common.load_limits(cell["name"]))
    ok = all(v["ok"] for v in compared.values())

    counters = {
        "steps": steps, "tokens": steps * batch * seq,
        "builds_in_window": builds_in_window,
        "step_intervals_ms": [1e3 * (b - a) for a, b in
                              zip([t0] + done_at[:-1], done_at)],
        "batch": batch, "seq_len": seq,
    }
    e2e = {"train_tokens_per_s": counters["tokens"] / window_s,
           "setup_s": setup_s}
    return {"e2e": e2e, "counters": counters, "window_s": window_s,
            "window_ns": (t0_ns, t1_ns), "peak": peak, "compared": compared,
            "correct": ok, "attempted": steps, "failed": 0,
            "build_spans": watch.spans(), "sync_ns": sync_ns}
