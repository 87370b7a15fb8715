#!/usr/bin/env python3
"""One run of one cell of the benchmark:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic from the files BENCHMARK.json
names, and the architecture from the families/ file that the configuration
names, builds the system under test with weights from the seed, warms it
up (set-up), measures for ``--seconds`` seconds, compares what the timed
path produced with the plain reference, and prints one JSON object as the
last line of standard output. Fails, with no result, when JAX finds no TPU,
fewer chips than the cell asks for, or a device with no published peaks."""
import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)          # the program (paddle_tpu) and `benchmarks`
# Only the first run of a cell in a checkout may compile. The program keeps
# programs that compile in under a second out of the persistent cache unless
# this variable says otherwise (jit/api.ensure_compilation_cache), and its
# eager path builds hundreds of those: give it the cache it is to use.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        raise SystemExit("benchmark: the program (paddle_tpu/) is not in "
                         "this checkout")
    from benchmarks.lib import common

    bench = common.load_benchmark()
    cell, config, mix = common.load_cell(bench, args.workload)
    family = common.load_family(config)
    device, peaks = common.device_info(cell["chips"])
    common.load_limits(cell["name"])         # a cell with no limits fails now

    trace_dir = os.path.join(HERE, ".trace", cell["name"])
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    drivers = {"serve": "benchmarks.lib.serve", "train": "benchmarks.lib.train"}
    import importlib
    driver = importlib.import_module(drivers[mix["driver"]])
    out = driver.run(bench, cell, config, family, mix, args.seed,
                     args.seconds, bool(args.trace), T_PROC0, device, peaks,
                     trace_dir=trace_dir)
    report(bench, cell, config, family, mix, peaks, device, out,
           trace_dir if args.trace else None)


def report(bench, cell, config, family, mix, peaks, device, out, trace_dir):
    from benchmarks.lib import common

    device = dict(device, memory_peak_bytes=out["peak"])
    breakdown = None
    if trace_dir is None:
        names = [m["name"] for m in
                 common.metrics_for(bench, cell["name"], "end_to_end")]
        metrics = {n: out["e2e"].get(n) for n in names}
    else:
        red, _ = common.reduce_trace(trace_dir, out["sync_ns"],
                                     out["window_ns"], out["build_spans"])
        shutil.rmtree(trace_dir, ignore_errors=True)   # little left on disk
        ctx = {"cell": cell, "config": config, "traffic": mix, "peaks": peaks,
               "window_s": out["window_s"], "counters": out["counters"],
               "trace": red, "flops": family, "chips": cell["chips"]}
        metrics = {}
        for m in common.metrics_for(bench, cell["name"], "per_layer"):
            metrics[m["name"]] = common.read_metric(m["name"], ctx)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        breakdown = red.breakdown()
    common.finish(bench, metrics, device, out["compared"],
                  out["correct"], out["attempted"], out["failed"], breakdown)


if __name__ == "__main__":
    main()
