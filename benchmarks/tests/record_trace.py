#!/usr/bin/env python3
"""Developer's tool, run on the chip: one traced run of a cell that keeps
what run.py throws away. Writes under chiprun_out/: a summary of every
plane and line of the raw trace (to see how kernels are named), and the
flattened events that the reduction reads (the source of
tests/data/small_trace.json).

    python3 benchmarks/tests/record_trace.py --workload <cell> --seed <n> --seconds <s>
"""
import glob
import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")


def summarize(trace_dir, out_path):
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    pd = ProfileData.from_file(paths[-1])
    rows = []
    for plane in pd.planes:
        for line in plane.lines:
            evs = list(line.events)
            names = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + e.duration_ns
            top = sorted(names.items(), key=lambda kv: -kv[1])[:40]
            sample = []
            for e in evs[:3]:
                sample.append({"name": e.name, "start_ns": e.start_ns,
                               "dur_ns": e.duration_ns,
                               "stats": {str(k): str(v)[:300]
                                         for k, v in e.stats}})
            rows.append({"plane": plane.name, "line": line.name,
                         "events": len(evs), "top": top, "sample": sample})
    with open(out_path, "w") as f:
        json.dump({"file_bytes": os.path.getsize(paths[-1]), "lines": rows},
                  f, indent=1)


def main():
    from benchmarks import run as R
    from benchmarks.lib import common

    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    inner = common.reduce_trace
    tag = sys.argv[sys.argv.index("--workload") + 1]

    def keep(trace_dir, sync_ns, window_ns, build_spans):
        summarize(trace_dir, os.path.join(out_dir, f"trace_summary_{tag}.json"))
        red, events = inner(trace_dir, sync_ns, window_ns, build_spans)
        with gzip.open(os.path.join(out_dir, f"trace_events_{tag}.json.gz"),
                       "wt") as f:
            json.dump({"window": red.window, "sync_ns": sync_ns,
                       "window_ns": window_ns, "build_spans": build_spans,
                       "events": events}, f)
        return red, events

    common.reduce_trace = keep
    R.main(sys.argv[1:] + ["--trace", "1"])


if __name__ == "__main__":
    main()
