#!/usr/bin/env python3
"""Developer's tool, run on the chip (PR 30): the readings behind
``assumed.router_margin`` and the ``served_gap`` limit of
``xing4-29b-a4b-serve.decode-closed64`` (PERF.md section 6).

For each seed a short window at the cell's own load, judged as the harness
judges it (the committed margin and limits file); then every compared
position with its selection margin and no mask, saved to
``chiprun_out/xing4_positions_<seed>.npy`` (columns: row, position, the
float32 reference's best logit, its logit of the served token, its logit
of the int8 control's token or nan, the program's logit of the served
token, the margin), so that the share of positions kept, the program's
largest gap and the control's smallest can be read at any margin. For the
``--control`` seeds the int8 reference then stands in the program's place
and goes through ``serve.check`` and the committed limits file: it has to
come out ``ok: false``.

    python3 tools/xing4_readings.py --seeds 1,2,3 [--control 1,2] [--seconds 10]
"""
import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

import numpy as np  # noqa: E402

CELL = "xing4-29b-a4b-serve.decode-closed64"


def blocks(served, gens, mix, seed, serve):
    """The sampled requests as ``serve.check`` lays them out."""
    sample = serve.pick_sample(served, gens, seed, int(mix["check_sample"]))
    seqs = [(rid, served[rid][0]["prompt"], gens[rid][:served[rid][1]])
            for rid in sample]
    s_pad = -(-max(len(p) + len(g) for _, p, g in seqs) // 128) * 128
    ids = np.zeros((len(seqs), s_pad), np.int32)
    for r, (_, p, g) in enumerate(seqs):
        ids[r, :len(p) + len(g)] = p + g
    return seqs, ids


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    a = ap.parse_args()
    from benchmarks.lib import common, serve

    bench = common.load_benchmark()
    cell, config, mix = common.load_cell(bench, CELL)
    device, peaks = common.device_info(cell["chips"])
    family = common.load_family(config)
    limits = common.load_limits(CELL)
    control = {int(s) for s in a.control.split(",") if s}
    keep, inner = {}, serve.check

    def spy(config_, family_, mix_, seed_, served, gens, top, limits_, **kw):
        keep.update(served=served, gens=gens, top=top)   # run() keeps none
        return inner(config_, family_, mix_, seed_, served, gens, top,
                     limits_, **kw)

    serve.check = spy
    os.makedirs("chiprun_out", exist_ok=True)
    for seed in (int(s) for s in a.seeds.split(",")):
        t0 = time.perf_counter()
        out = serve.run(bench, cell, config, family, mix, seed, a.seconds,
                        False, time.perf_counter(), device, peaks)
        row = {"seed": seed, "margin": config["assumed"]["router_margin"],
               "limits": limits, "program": out["compared"],
               "correct": out["correct"], "e2e": out["e2e"],
               "t_run_s": time.perf_counter() - t0}
        seqs, ids = blocks(keep["served"], keep["gens"], mix, seed, serve)
        gather = np.zeros(ids.shape + (1,), np.int32)
        gather[:, :-1, 0] = ids[:, 1:]
        best, _, got, margin = family.position_logits(config, seed, ids,
                                                      gather)
        got8 = np.full_like(got, np.nan)
        if seed in control:
            t0 = time.perf_counter()
            cmp_, info = inner(config, family, mix, seed, keep["served"],
                               keep["gens"], {}, limits, control=True)
            row["control_int8"] = cmp_
            row["control_correct"] = all(v["ok"] for v in cmp_.values())
            row["control_tokens"] = info["tokens_compared"]
            arg8 = family.position_logits(config, seed, ids, gather,
                                          mode="int8")[1]
            got8 = family.position_logits(config, seed, ids,
                                          arg8[..., None])[2]
            row["t_control_s"] = time.perf_counter() - t0
        rows = []
        for r, (rid, p, g) in enumerate(seqs):
            tops = keep["top"].get(rid) or []
            for j in range(len(g)):
                s = len(p) + j - 1           # logits at s predict token s+1
                t = tops[j] if j < len(tops) else None
                rows.append((r, s, best[r, s], got[r, s, 0], got8[r, s, 0],
                             np.nan if t is None else t, margin[r, s]))
        rows = np.asarray(rows, np.float64)
        np.save(f"chiprun_out/xing4_positions_{seed}.npy", rows)
        gap, m = rows[:, 2] - rows[:, 3], rows[:, 6]
        row["positions"] = len(rows)
        row["widest_margin_with_gap_above_0.1"] = \
            float(m[gap > 0.1].max()) if (gap > 0.1).any() else 0.0
        print("READING", json.dumps(row), flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
