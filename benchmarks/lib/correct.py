"""The comparisons that decide ``correct``. Every number compared is a gap
between what the timed path produced and what the plain reference gives for
the same inputs; each has a limit of its own in limits/<cell>.json, set
from readings on the chip (PERF.md gives them)."""
from statistics import median


def _entry(value, limit):
    return {"value": float(value), "limit": float(limit),
            "ok": bool(value <= limit)}


def norm_gaps(got, want):
    """{leaf: |got - want| / max(want of the leaf, want of the median leaf)}:
    the gap between two norms, not the norm of a difference."""
    med = median(want.values())
    return {k: abs(got[k] - want[k]) / max(want[k], med, 1e-30)
            for k in want}


def training_numbers(got, want):
    """loss_gap: worst relative gap of the followed steps' losses.
    grad_gap: worst leaf of the first gradient's norm.
    delta_gap: worst leaf of the parameters' change after the steps, over
    the leaves whose reference gradient is a thousandth of the median
    leaf's or more (the others move under Adam by round-off alone).
    A leaf that the program never moved while the reference did, or moved
    double, reads about 1 here."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(got["loss"], want["loss"]))
    g = norm_gaps(got["grad1"], want["grad1"])
    gmed = median(want["grad1"].values())
    live = [k for k, v in want["grad1"].items() if v >= 1e-3 * gmed]
    d = norm_gaps({k: got["delta"][k] for k in live},
                  {k: want["delta"][k] for k in live})
    worst_g = max(g, key=g.get)
    worst_d = max(d, key=d.get)
    return ({"loss_gap": loss, "grad_gap": g[worst_g],
             "delta_gap": d[worst_d]},
            {"grad_gap_leaf": worst_g, "delta_gap_leaf": worst_d,
             "leaves_left_out": sorted(set(want["grad1"]) - set(live))})


def compare_training(got, want, limits):
    nums, _ = training_numbers(got, want)
    return {k: _entry(v, limits[k]) for k, v in nums.items() if k in limits}


def serving_numbers(served, best, got):
    """served_gap: the widest gap by which a served token's reference logit
    lies below the reference's best at its position. ``served`` is a list
    of (row, position, token); ``best`` [rows, S] and ``got`` [rows, S]
    are the reference's best logit and its logit of the served token."""
    gaps = [float(best[r, s] - got[r, s]) for r, s, _ in served]
    return {"served_gap": max(gaps)}, {"tokens_compared": len(gaps)}


def compare_serving(nums, limits):
    return {k: _entry(v, limits[k]) for k, v in nums.items() if k in limits}
