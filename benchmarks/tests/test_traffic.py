"""The traffic generator: the same seed gives the same requests, another
seed gives others, and every seed gets the same set of sizes and gaps."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.lib import traffic  # noqa: E402

BIG = 2**31 + 77          # the driver's seeds do not fit 32 signed bits


def _sizes(reqs, r=None):
    return sorted((len(q["prompt"]), q["max_new"]) for q in reqs
                  if r is None or q.get("round") == r)


def test_closed_same_seed_same_requests():
    mix = traffic.load("decode-closed32")
    a = traffic.serve_requests(mix, BIG, 32000)
    b = traffic.serve_requests(mix, BIG, 32000)
    assert a == b
    assert len(a) == mix["clients"] * mix["rounds"]


def test_closed_other_seed_other_order_same_sizes():
    mix = traffic.load("decode-closed32")
    a = traffic.serve_requests(mix, BIG, 32000)
    b = traffic.serve_requests(mix, BIG + 1, 32000)
    assert [q["prompt"] for q in a] != [q["prompt"] for q in b]
    assert [len(q["prompt"]) for q in a] != [len(q["prompt"]) for q in b]
    # every round of every seed holds the same lengths, in another order
    plen = lambda reqs, r: sorted(len(q["prompt"]) for q in reqs  # noqa
                                  if q["round"] == r)
    olen = lambda reqs, r: sorted(q["max_new"] for q in reqs      # noqa
                                  if q["round"] == r)
    for r in range(mix["rounds"]):
        assert plen(a, r) == plen(b, r) == plen(a, 0)
        assert olen(a, r) == olen(b, r) == olen(a, 0)


def test_closed_lengths_follow_the_file():
    mix = traffic.load("decode-closed32")
    reqs = traffic.serve_requests(mix, 5, 32000)
    p = sorted(len(q["prompt"]) for q in reqs if q["round"] == 0)
    o = sorted(q["max_new"] for q in reqs if q["round"] == 0)
    assert p[0] >= 32 and p[-1] <= 256 and 80 <= p[len(p) // 2] <= 115
    assert o[0] >= 256 and o[-1] <= 1024 and 450 <= o[len(o) // 2] <= 580
    assert all(1 <= t < 32000 for q in reqs for t in q["prompt"])
    assert {q["client"] for q in reqs} == set(range(32))


def test_open_loop_arrivals():
    mix = {"driver": "serve", "loop": "open", "rate_per_s": 4.0,
           "horizon_s": 20, "burst": {"every": 8, "size": 3},
           "prompt_len": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                          "min": 16, "max": 4096},
           "output_len": {"dist": "fixed", "value": 64}}
    a = traffic.serve_requests(mix, BIG, 1000)
    b = traffic.serve_requests(mix, BIG, 1000)
    c = traffic.serve_requests(mix, BIG + 5, 1000)
    assert a == b and len(a) == 80
    due = [q["due_s"] for q in a]
    assert due == sorted(due) and due[0] > 0
    assert [q["due_s"] for q in c] != due
    assert _sizes(a) == _sizes(c)
    # bursts: some requests are due at the same instant
    assert len(set(due)) < len(due)
    # the mean rate is the file's, within what the bursts take away
    assert 0.5 * 20 < due[-1] < 1.5 * 20


def test_shared_prefix_groups():
    mix = {"driver": "serve", "loop": "closed", "clients": 8, "rounds": 2,
           "prompt_len": {"dist": "fixed", "value": 48},
           "output_len": {"dist": "fixed", "value": 4},
           "shared_prefix": {"groups": 2, "len": 32}}
    reqs = traffic.serve_requests(mix, 9, 1000)
    heads = {tuple(q["prompt"][:32]) for q in reqs}
    assert len(heads) == 2
    assert len({tuple(q["prompt"]) for q in reqs}) == len(reqs)


def test_train_batches():
    mix = traffic.load("seq4096")
    a = traffic.train_batch(mix, BIG, 0, 32000)
    assert a.shape == (2, 4096) and a.dtype.name == "int32"
    assert (a == traffic.train_batch(mix, BIG, 0, 32000)).all()
    assert (a != traffic.train_batch(mix, BIG, 1, 32000)).any()
    assert (a != traffic.train_batch(mix, BIG + 1, 0, 32000)).any()
    assert (a[0] != a[1]).any() and a.min() >= 0 and a.max() < 32000
