#!/usr/bin/env python3
"""Developer's tool, run on the chip: the readings that the limits of
``correct`` are set from (PERF.md gives them).

  train: for each seed, the program's first steps against the reference
         (the lower reading); for the control seeds also the reference in
         int8 and the reference on half of the batch, put in the program's
         place (upper readings).
  serve: for each seed, a short window at the cell's own load, then the
         served tokens against the reference (lower) and the int8
         reference's first tokens at the same positions (upper).

    python3 benchmarks/tests/readings.py <cell> --seeds 1,2,3 [--control 1,2] [--seconds 10]
"""
import argparse
import functools
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")


def train_readings(cell, config, mix, seeds, control):
    import jax
    from benchmarks.lib import common, correct, reference, traffic, train
    from paddle_tpu.jit.api import ensure_compilation_cache
    import paddle_tpu as paddle

    ensure_compilation_cache()
    vocab, n = config["vocab_size"], int(mix["check_steps"])
    hp = config["program"]["optimizer"]
    family = common.load_family(config)
    follow = functools.partial(reference.train_reference, family, config, hp)
    for seed in seeds:
        t0 = time.perf_counter()
        step, params, opt = train._build(family, config, seed)
        moment1, change = train._state_reads(family, config, seed, params,
                                             opt)
        got = {"loss": []}
        for i in range(n):
            ids = traffic.train_batch(mix, seed, i, vocab)
            loss = step(paddle.to_tensor(ids),
                        paddle.to_tensor(ids.astype("int64")))
            got["loss"].append(float(loss._data))
            if i == 0:
                got["grad1"] = {p: v / (1 - hp["beta1"])
                                for p, v in moment1().items()}
        got["delta"] = change()
        t_prog = time.perf_counter() - t0
        step = params = opt = moment1 = change = loss = None
        gc.collect()
        batches = [traffic.train_batch(mix, seed, k, vocab) for k in range(n)]
        t0 = time.perf_counter()
        want = follow(seed, batches)
        t_ref = time.perf_counter() - t0
        nums, info = correct.training_numbers(got, want)
        row = {"seed": seed, "program": nums, "info": info,
               "loss": got["loss"], "ref_loss": want["loss"],
               "t_prog_s": t_prog, "t_ref_s": t_ref,
               "peak": common.memory_peak_bytes()}
        if seed in control:
            t0 = time.perf_counter()
            ctl = follow(seed, batches, mode="int8")
            row["control_int8"], row["control_info"] = \
                correct.training_numbers(ctl, want)
            row["t_control_s"] = time.perf_counter() - t0
            half = follow(seed, batches,
                          rows=list(range(int(mix["batch"]) // 2)))
            row["fault_half_batch"], _ = correct.training_numbers(half, want)
            froz = follow(seed, batches, frozen=True)
            froz["delta"] = {k: 0.0 for k in want["delta"]}
            row["fault_frozen"], _ = correct.training_numbers(froz, want)
            # per-leaf detail of the first seed, to see what swings
            if seed == control[0]:
                row["grad_leaves"] = correct.norm_gaps(got["grad1"],
                                                       want["grad1"])
                row["delta_leaves"] = correct.norm_gaps(got["delta"],
                                                        want["delta"])
                row["delta_leaves_control"] = correct.norm_gaps(
                    ctl["delta"], want["delta"])
                row["ref_grad1"] = want["grad1"]
                row["ref_delta"] = want["delta"]
        print(json.dumps(row), flush=True)


def serve_readings(cell, config, mix, seeds, control, seconds):
    from benchmarks.lib import common, serve

    bench = common.load_benchmark()
    device, peaks = common.device_info(cell["chips"])
    family = common.load_family(config)
    keep = {}
    inner = serve.check

    def spy(config_, family_, mix_, seed_, served, gens, top, limits, **kw):
        keep.update(served=served, gens=gens, top=top)
        return inner(config_, family_, mix_, seed_, served, gens, top, limits,
                     **kw)

    serve.check = spy
    for seed in seeds:
        t0 = time.perf_counter()
        out = serve.run(bench, cell, config, family, mix, seed, seconds,
                        False, time.perf_counter(), device, peaks)
        row = {"seed": seed, "e2e": out["e2e"],
               "program": {k: v["value"] for k, v in out["compared"].items()},
               "steps": out["counters"]["steps"],
               "t_run_s": time.perf_counter() - t0, "peak": out["peak"]}
        if seed in control:
            t0 = time.perf_counter()
            cmp_, info = inner(config, family, mix, seed, keep["served"],
                               keep["gens"], {}, {"served_gap": 1e30},
                               control=True)
            row["control_int8"] = {k: v["value"] for k, v in cmp_.items()}
            row["control_tokens"] = info["tokens_compared"]
            row["t_control_s"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        gc.collect()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    a = ap.parse_args()
    from benchmarks.lib import common

    bench = common.load_benchmark()
    cell, config, mix = common.load_cell(bench, a.cell)
    seeds = [int(s) for s in a.seeds.split(",")]
    control = [int(s) for s in a.control.split(",") if s]
    if mix["driver"] == "train":
        train_readings(cell, config, mix, seeds, control)
    else:
        serve_readings(cell, config, mix, seeds, control, a.seconds)


if __name__ == "__main__":
    main()
