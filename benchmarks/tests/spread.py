#!/usr/bin/env python3
"""Developer's tool: read chiprun_out/sets_<cell>.jsonl and print, for each
end-to-end metric, each set's median and its spread (distance between the
first and third quartile of statistics.quantiles(n=4) over the median)."""
import json
import statistics
import sys


def spread(vals):
    q = statistics.quantiles(vals, n=4)
    return (q[2] - q[0]) / statistics.median(vals)


rows = [json.loads(l) for l in open(sys.argv[1])]
for r in rows:
    if not r["line"].get("correct") or r["rc"]:
        print("NOT CORRECT / failed:", r["set"], r["seed"], r["rc"])
names = sorted({k for r in rows if r["set"] in (1, 2)
                for k in r["line"]["metrics"]})
for n in names:
    out = []
    for s in (1, 2):
        v = [r["line"]["metrics"][n]["value"] for r in rows if r["set"] == s]
        if n == "setup_s":
            v = v[1:] if s == 1 else v     # the first run of a side compiles
        out.append((statistics.median(v), spread(v), min(v), max(v), len(v)))
    print(n, " | ".join("median %.6g spread %.4f [%.6g..%.6g] n=%d" % o
                        for o in out),
          "| 2nd/1st median %.4f" % (out[1][0] / out[0][0]))
print("compared:", {k: max(r["line"]["compared"][k]["value"] for r in rows)
                    for k in rows[0]["line"]["compared"]})
print("peak:", max(r["line"]["device"]["memory_peak_bytes"] for r in rows),
      "wall_s max:", max(r["wall_s"] for r in rows))
for r in rows:
    if r["set"] == "traced":
        d = r["line"]["device"]
        print("traced", r["seed"], {k: round(v["value"], 4) for k, v in
                                    r["line"]["metrics"].items()},
              "busy/window", d.get("busy_s"), d.get("window_s"))
