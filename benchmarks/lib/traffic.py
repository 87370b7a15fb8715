"""One general traffic generator. A traffic mix is a JSON file of
parameters (traffic/<name>.json); this module turns it and ``--seed`` into
requests or batches. Every seed gets the SAME set of sizes and arrival
gaps in another order (stratified quantiles of the stated distributions,
shuffled by the seed), so a seed moves the order of the work and not its
amount. Token ids are uniform over the vocabulary.

Keys of a serving mix:
  driver "serve"; loop "closed" | "open"; clients (closed) or rate_per_s
  and horizon_s (open); prompt_len / output_len {"dist": "lognormal",
  "median", "sigma", "min", "max"} or {"dist": "fixed", "value"};
  rounds (closed: requests made per client); shared_prefix {"groups",
  "len"} (optional: requests of a group start with the same tokens);
  burst {"every", "size"} (open: every n-th gap is followed by size
  arrivals at once); warmup "first_token_all_clients" | {"seconds": s};
  check_sample (requests compared with the reference).
Keys of a training mix:
  driver "train"; batch; seq_len; check_steps.
"""
import json
import math
import os
from statistics import NormalDist

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def _quantile_lengths(spec, n):
    """n lengths at the stratified quantiles (i + 1/2) / n of ``spec``."""
    if spec["dist"] == "fixed":
        return [int(spec["value"])] * n
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    mu, sg = math.log(spec["median"]), float(spec["sigma"])
    out = []
    for i in range(n):
        x = math.exp(mu + sg * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(x), spec["min"]), spec["max"])))
    return out


def _rng(seed, *stream):
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  *stream])


def serve_requests(mix, seed, vocab_size):
    """-> list of dicts {id, prompt, max_new, client | due_s}. Closed loop:
    ``rounds`` rounds of one request per client, each round the same set of
    (prompt, output) sizes in a new order. Open loop: arrivals until
    ``horizon_s`` with the same set of gaps in a new order."""
    loop = mix["loop"]
    if loop == "closed":
        per_round, rounds = int(mix["clients"]), int(mix.get("rounds", 4))
    elif loop == "open":
        per_round = max(1, int(round(mix["rate_per_s"] * mix["horizon_s"])))
        rounds = 1
    else:
        raise ValueError(f"unknown loop kind {loop!r}")
    plens = _quantile_lengths(mix["prompt_len"], per_round)
    olens = _quantile_lengths(mix["output_len"], per_round)
    sp = mix.get("shared_prefix") or {}
    groups, sp_len = int(sp.get("groups", 0)), int(sp.get("len", 0))
    prefixes = [_rng(seed, 7, g).integers(1, vocab_size, sp_len).tolist()
                for g in range(groups)]
    reqs = []
    for r in range(rounds):
        rng = _rng(seed, 1, r)
        pp, oo = rng.permutation(per_round), rng.permutation(per_round)
        for c in range(per_round):
            n = plens[pp[c]]
            prompt = rng.integers(1, vocab_size, n).tolist()
            if groups:
                pre = prefixes[int(rng.integers(groups))][:n - 1]
                prompt[:len(pre)] = pre
            reqs.append({"id": f"r{r}c{c}", "prompt": prompt,
                         "max_new": olens[oo[c]], "client": c, "round": r})
    if loop == "open":
        n = len(reqs)
        # exponential gaps at stratified quantiles, shuffled by the seed
        gaps = [-math.log(1 - (i + 0.5) / n) / mix["rate_per_s"]
                for i in range(n)]
        gaps = _rng(seed, 2).permutation(np.asarray(gaps)).tolist()
        burst = mix.get("burst") or {}
        every, size = int(burst.get("every", 0)), int(burst.get("size", 0))
        t, k = 0.0, 0
        while k < n:
            t += gaps[k]
            reqs[k]["due_s"] = t
            k += 1
            if every and k % every == 0:          # a burst: all at once
                for _ in range(size):
                    if k < n:
                        reqs[k]["due_s"] = t
                        k += 1
        for q in reqs:
            q.pop("client")
    return reqs


def train_batch(mix, seed, step, vocab_size):
    """Token ids [batch, seq_len] int32 of optimizer step ``step`` (0-based):
    fresh uniform ids from the seed, every row different."""
    rng = _rng(seed, 3, step)
    return rng.integers(0, vocab_size, (int(mix["batch"]),
                                        int(mix["seq_len"])), dtype=np.int32)
