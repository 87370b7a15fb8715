"""The ``evabyte`` family at the harness's seam, its cell's traffic file and
its two readers (PR 34; a file of its own: the files the benchmark already
had are not a model_config PR's to edit). Not collected by tier-1 from
here; ``tests/test_benchmark_families.py`` imports it."""
import gzip
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "tests"))
import tiny  # noqa: E402

tiny.setup_cpu()

from benchmarks.lib import common, span_attrs, traffic  # noqa: E402
from benchmarks.lib import trace as T  # noqa: E402
from benchmarks.lib import weights as W  # noqa: E402
from benchmarks.lib.peaks import peaks_for  # noqa: E402
from evabyte_tiny_config import tiny_config as eva_tiny  # noqa: E402

CELL = "evabyte-6.5b-serve.docs-closed24"
BIG = 2**31 + 77


def published():
    with open(os.path.join(common.BENCH_DIR, "configs",
                           "evabyte-6.5b-serve.json")) as f:
        return json.load(f)


# -- the family at the seam --------------------------------------------------
def test_evabyte_leaves_are_the_same_whoever_makes_them():
    """Every leaf once, the same array from the one call that makes them
    all (the program's) and from the top and layer makers (the
    reference's); gains, phi and mu around one, matrices at init_std."""
    cfg = eva_tiny()
    fam = common.load_family(cfg)
    leaves = fam.leaves(cfg)
    assert {W.split(p)[1] for p in leaves} == set(fam.LEAF_NAMES)
    assert len(set(fam.LEAF_NAMES)) == len(fam.LEAF_NAMES)
    spec = W.spec(leaves, fam.LEAF_NAMES, cfg["initializer_range"])
    tree = W.make_all(spec, BIG)
    parts = {-1: W.make_top(spec, BIG)}
    for path in leaves:
        layer, name = W.split(path)
        if layer not in parts:
            parts[layer] = W.make_layer(spec, BIG, layer)
        a = np.asarray(W.get_leaf(tree, path).astype(np.float32))
        assert np.array_equal(a, np.asarray(
            parts[layer][name].astype(np.float32))), path
        if name in fam.GAINS + ("phi", "mu"):
            assert 0.5 < a.mean() < 1.5, path
        else:
            assert abs(a.std() / cfg["initializer_range"] - 1) < 0.1, path


def test_evabyte_reference_is_causal_and_padding_reaches_nothing():
    """A later byte, and padding on the right, change no earlier logit:
    a chunk that holds them is seen only from later windows."""
    cfg = eva_tiny()
    fam = common.load_family(cfg)
    rng = np.random.default_rng(7)
    ids = rng.integers(1, cfg["vocab_size"], (2, 256)).astype(np.int32)
    gather = np.zeros((2, 256, 1), np.int32)
    gather[:, :-1, 0] = ids[:, 1:]
    best, arg, got = fam.serve_logits(cfg, BIG, ids, gather)
    cut = ids.copy()
    cut[1, 150:] = 0
    best2, arg2, got2 = fam.serve_logits(cfg, BIG, cut, gather)
    assert np.array_equal(best[0], best2[0])
    assert np.allclose(best[1, :150], best2[1, :150], atol=1e-5)
    assert not np.allclose(best[1, 150:], best2[1, 150:], atol=1e-3)
    low, _, _ = fam.serve_logits(cfg, BIG, ids, gather, mode="int8")
    assert 1e-3 < np.abs(low - best).max() < 1.0      # the control differs
    assert (np.take_along_axis(best, np.zeros((2, 1), int), 1) >=
            got[:, :1, 0]).all()


def test_evabyte_summaries_are_seen_from_later_windows_only():
    """Changing one byte of window 0 changes window 0's later logits and,
    through its chunk's pooled row alone, every later window's."""
    cfg = eva_tiny()
    fam = common.load_family(cfg)
    rng = np.random.default_rng(8)
    ids = rng.integers(1, cfg["vocab_size"], (1, 192)).astype(np.int32)
    other = ids.copy()
    other[0, 10] = (ids[0, 10] % (cfg["vocab_size"] - 1)) + 1
    a = fam.pred_logits(cfg, BIG, ids)[0]
    b = fam.pred_logits(cfg, BIG, other)[0]
    assert np.array_equal(a[:10], b[:10])
    assert np.abs(a[10:64] - b[10:64]).max() > 1e-3
    assert np.abs(a[64:] - b[64:]).max() > 1e-4
    assert a.shape == (192, cfg["num_pred_heads"], cfg["vocab_size"])


def test_evabyte_file_states_what_the_contract_asks():
    cfg = published()
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 32}
    assert cfg["num_hidden_layers"] == 8 and cfg["head_dim"] == 128
    for key in ("recalled", "head_dim", "dtype", "rope_form",
                "lm_head_width", "phi_mu_init", "weights"):
        assert key in cfg["assumed"], key
    assert "not re-read" in cfg["assumed"]["recalled"]
    assert cfg["stands_for"].startswith("one pipeline stage of four")
    prog = cfg["program"]
    assert prog["family"] == "evabyte"
    assert prog["pool"] == {"num_pages": 4096, "page_size": 16}
    assert prog["scheduler"] == {
        "max_batch_size": 24, "prefill_chunk_tokens": 480,
        "serving_buckets": "32,64,128,256,512"}
    bench = common.load_benchmark()
    entry = {c["name"]: c for c in bench["configs"]}["evabyte-6.5b-serve"]
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"]


# -- the traffic file --------------------------------------------------------
def test_docs_closed24_lengths_follow_the_file():
    mix = traffic.load("docs-closed24")
    reqs = traffic.serve_requests(mix, BIG, 320)
    assert len(reqs) == 24 * 6
    assert reqs == traffic.serve_requests(mix, BIG, 320)
    p = sorted(len(q["prompt"]) for q in reqs if q["round"] == 0)
    o = sorted(q["max_new"] for q in reqs if q["round"] == 0)
    assert p[0] >= 2048 and p[-1] <= 8192 and 3700 <= p[12] <= 4500
    assert o[0] >= 512 and o[-1] <= 2048 and 950 <= o[12] <= 1100
    assert max(a + b for a, b in zip(p, o)) <= 10240
    assert all(1 <= t < 320 for q in reqs for t in q["prompt"])
    # what a row holds at most, 24 at a time, fits the cell's pool
    worst = sorted((len(q["prompt"]) + q["max_new"] for q in reqs
                    if q["round"] == 0), reverse=True)
    pages = sum(min(128, -(-n // 16)) + -(-n // 256) for n in worst)
    assert pages <= 0.95 * 4096


# -- the readers -------------------------------------------------------------
def recorded(rename):
    """The serving part of the trace recorded on the chip (PR 26: 16
    calls of the then nameless ragged kernel in two scheduler steps);
    ``rename`` gives those calls the kernel's name of today."""
    with gzip.open(os.path.join(HERE, "data", "small_trace.json.gz"),
                   "rt") as f:
        d = json.load(f)["serve"]
    events = d["events"]
    if rename:
        events = [dict(e, name=e["name"].replace(
            "%run.", "%ragged_paged_attention."))
            if all(k in e["name"] + e["text"] for k in d["kernel"]) else e
            for e in events]
    return T.Reduced(events, window=tuple(d["window"])), d["expected"]


def ctx_of(red):
    return {"trace": red, "config": published(), "peaks": peaks_for(
        "TPU v5 lite"), "flops": common.load_family(published()),
        "counters": {}}


def test_eva_attention_roofline_from_known_counts(monkeypatch):
    """16 calls of 24 rows x 1,300 visible rows each: 511 MB a call at
    819 GB/s is 0.62 ms, 10 ms in all, over the 0.1038 s the recorded
    calls took: 9.6 %, under 100."""
    red, want = recorded(rename=True)
    rows = [{"fed": 24, "pairs": 24 * 1300, "kv_rows": 24 * 1300,
             "summaries_written": 1, "rows": 32}] * want["kernel_calls"]
    monkeypatch.setattr(span_attrs, "in_window",
                        lambda ctx, name: rows if name == "kernel.ragged"
                        else None)
    got = common.read_metric("eva_attention_roofline.serve", ctx_of(red))
    byts = 2 * 32 * 128 * 2 * 24 * 1300 + 2 * 32 * 128 * 2 * 24
    assert got == pytest.approx(
        100 * 16 * byts / 819e9 / want["kernel_s"], rel=1e-6)
    assert 5 < got < 100


def test_eva_attention_roofline_reads_nothing_where_there_is_nothing(
        monkeypatch):
    """A trace without the kernel, spans without the counts (an older
    commit), a family without the call: None, and no raise."""
    rows = [{"fed": 24, "pairs": 100, "kv_rows": 100}]
    monkeypatch.setattr(span_attrs, "in_window", lambda ctx, name: rows)
    red, _ = recorded(rename=False)
    read = lambda c: common.read_metric(          # noqa: E731
        "eva_attention_roofline.serve", c)
    assert read(ctx_of(red)) is None
    named, _ = recorded(rename=True)
    monkeypatch.setattr(span_attrs, "in_window",
                        lambda ctx, name: [{"rows": 32, "t": 1}])
    assert read(ctx_of(named)) is None
    monkeypatch.setattr(span_attrs, "in_window", lambda ctx, name: None)
    assert read(ctx_of(named)) is None
    assert read(dict(ctx_of(named), trace=None)) is None
    mistral = dict(ctx_of(named))
    with open(os.path.join(common.BENCH_DIR, "configs",
                           "mistral-7b-serve.json")) as f:
        mistral["flops"] = common.load_family(json.load(f))
    assert read(mistral) is None


def test_prefill_step_share(monkeypatch):
    rows = [{"rows": 24, "packed": 24, "pad_to": 32, "prefill": 0}] * 3 \
        + [{"rows": 25, "packed": 504, "pad_to": 512, "prefill": 480}]
    monkeypatch.setattr(span_attrs, "in_window",
                        lambda ctx, name: rows if name == "serving.pack"
                        else None)
    assert common.read_metric("prefill_step_share.serve", {}) == 25.0
    monkeypatch.setattr(span_attrs, "in_window",
                        lambda ctx, name: [{"rows": 24, "packed": 24}])
    assert common.read_metric("prefill_step_share.serve", {}) is None
    monkeypatch.setattr(span_attrs, "in_window", lambda ctx, name: None)
    assert common.read_metric("prefill_step_share.serve", {}) is None


def test_the_cell_and_its_metrics_are_entered():
    bench = common.load_benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "docs-closed24"
    assert common.load_limits(CELL)["served_gap"] > 0
    e2e = [m["name"] for m in common.metrics_for(bench, CELL, "end_to_end")]
    assert e2e == ["serve_tokens_per_s", "tpot_p95_ms", "setup_s"]
    per = {m["name"]: m for m in common.metrics_for(bench, CELL, "per_layer")}
    assert len(per) == 15 and "ragged_attention_roofline.serve" not in per
    for name, layer, moves in (
            ("eva_attention_roofline.serve", "L1 kernels",
             "serve_tokens_per_s"),
            ("prefill_step_share.serve", "L4 scheduler", "tpot_p95_ms")):
        assert per[name]["workloads"] == [CELL]
        assert (per[name]["layer"], per[name]["moves"]) == (layer, moves)
        assert os.path.isfile(os.path.join(common.BENCH_DIR, "metrics",
                                           name + ".py"))
