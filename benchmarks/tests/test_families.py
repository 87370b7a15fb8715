"""The seam between the harness and an architecture (families/<family>.py).

* The pin: ``data/families_pin.json`` was written by PR 29 with its
  parent's code (commit 1435843, where weights, reference and counts were
  wired to the Llama block inside lib/), on the CPU: the sha256 of every
  leaf, of the serving reference's outputs on a fixed block of ids, and the
  training reference's losses, first gradient and change as float hex. The
  same readings through the ``llama`` family must agree bit for bit.
* The seam is the only place an architecture lives: a renamed copy of the
  family file in another directory runs both rehearsals; lib/ and run.py
  name nothing of a Llama; a configuration without a family says so."""
import hashlib
import json
import os
import re
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import tiny  # noqa: E402

tiny.setup_cpu()

from benchmarks.lib import common, reference, traffic  # noqa: E402
from benchmarks.lib import weights as W  # noqa: E402

SEEDS = (12345, 2**31 + 4321)
with open(os.path.join(HERE, "data", "families_pin.json")) as f:
    PIN = json.load(f)


def sha(a):
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("kind", ("serve", "train"))
def test_pin_leaves(kind, seed):
    cfg = tiny.tiny_config(kind, 2)
    fam = common.load_family(cfg)
    want = PIN["leaves"][f"{kind}:{seed}"]
    assert sorted(fam.leaves(cfg)) == sorted(want)
    spec = W.spec(fam.leaves(cfg), fam.LEAF_NAMES, cfg["initializer_range"])
    tree = W.make_all(spec, seed)
    parts = {-1: W.make_top(spec, seed)}
    for path in fam.leaves(cfg):
        layer, name = W.split(path)
        if layer not in parts:
            parts[layer] = W.make_layer(spec, seed, layer)
        assert sha(W.get_leaf(tree, path)) == want[path], path
        assert sha(parts[layer][name]) == want[path], path


@pytest.mark.parametrize("mode", ("f32", "int8"))
@pytest.mark.parametrize("seed", SEEDS)
def test_pin_serve_logits(seed, mode):
    cfg = tiny.tiny_config("serve", 2)
    rng = np.random.default_rng(7)
    ids = rng.integers(1, cfg["vocab_size"], (2, 128)).astype(np.int32)
    ids[1, 100:] = 0                       # padded on the right
    gather = np.zeros((2, 128, 1), np.int32)
    gather[:, :-1, 0] = ids[:, 1:]
    best, arg, got = common.load_family(cfg).serve_logits(
        cfg, seed, ids, gather, mode=mode)
    assert {"best": sha(best), "argmax": sha(arg), "gathered": sha(got)} \
        == PIN["serve_logits"][f"{seed}:{mode}"]


@pytest.mark.parametrize("seed", SEEDS)
def test_pin_train_reference(seed):
    cfg, mix = tiny.tiny_config("train", 2), tiny.tiny_train_mix()
    batches = [traffic.train_batch(mix, seed, k, cfg["vocab_size"])
               for k in range(mix["check_steps"])]
    out = reference.train_reference(
        common.load_family(cfg), cfg, cfg["program"]["optimizer"], seed,
        batches)
    got = {"loss": [float(x).hex() for x in out["loss"]],
           "grad1": {k: float(v).hex() for k, v in out["grad1"].items()},
           "delta": {k: float(v).hex() for k, v in out["delta"].items()}}
    assert got == PIN["train_reference"][str(seed)]


@pytest.mark.parametrize("kind, layers, want", (
    ("serve", 8, (38581345320960, 98803038289920, 6598680379392,
                  (125408395264, 31703302144), 1875902464)),
    ("train", 2, (11614562058240, 29532597780480, 1649670094848,
                  (125408395264, 31703302144), 567279616))))
def test_pin_counts(kind, layers, want):
    """The parent's lib/flops.py at the cells' own configurations, and the
    readers' way to them: ``ctx["flops"]`` is the cell's family."""
    with open(os.path.join(common.BENCH_DIR, "configs",
                           f"mistral-7b-{kind}.json")) as f:
        cfg = json.load(f)
    assert cfg["num_hidden_layers"] == layers
    fam = common.load_family(cfg)
    assert (fam.serve_flops(cfg, 10016, 10016, 7654321),
            fam.train_step_flops(cfg, 2, 4096),
            fam.flash_train_flops(cfg, 2, 4096),
            fam.ragged_attention_call(cfg, 10016, 7654321, 7700000),
            fam.matmul_params(cfg)) == want
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    assert fam.roofline_seconds(1e12, 3e9, peaks) == (1e12 / 197e12, "compute")
    ctx = {"config": cfg, "flops": fam, "window_s": 40.0, "chips": 1,
           "peaks": peaks, "trace": None,
           "counters": {"tokens_fed": 10016, "rows_sampled": 10016,
                        "ctx_fed": 7654321, "steps": 150, "batch": 2,
                        "seq_len": 4096}}
    assert common.read_metric("step_mfu.serve", ctx) == \
        100.0 * want[0] / (40.0 * 197e12)
    assert common.read_metric("step_mfu.train", ctx) == \
        100.0 * want[1] * 150 / (40.0 * 197e12)


def test_inits_that_draw_nothing():
    """``zeros`` and ``("const", value)`` beside the drawn ``normal`` and
    ``gain``; a layer of another kind gets a program of its own."""
    spec = W.spec({"w": ((4, 3), "normal"),
                   "layers.0.b": ((3,), "zeros"),
                   "layers.1.s": ((2,), ("const", 0.25)),
                   "layers.1.w": ((4, 3), "gain")}, ("w", "b", "s"), 0.02)
    tree = W.make_all(spec, 5, "float32")
    assert np.all(np.asarray(tree["layers"][0]["b"]) == 0)
    assert np.all(np.asarray(tree["layers"][1]["s"]) == 0.25)
    one = W.make_layer(spec, 5, 1, "float32")
    assert np.array_equal(one["w"], tree["layers"][1]["w"])
    assert abs(float(np.mean(one["w"])) - 1.0) < 0.2
    assert float(np.std(np.asarray(tree["w"]))) < 0.1
    with pytest.raises(SystemExit, match="no init 'uniform'"):
        W.make_all(W.spec({"w": ((2,), "uniform")}, ("w",), 0.02), 5)


def test_a_family_is_handed_the_whole_file():
    """Leaves and loss that hang on nested keys of the file (a list of
    layer kinds, a group of numbers): the reference's loop hands the family
    the dict it was given, so its leaves are the program's."""
    import types

    import jax.numpy as jnp

    def leaves(cfg):
        out = {"emb": ((cfg["vocab_size"], 4), "normal")}
        for i, kind in enumerate(cfg["layer_kinds"]):
            out[f"layers.{i}.{kind}"] = ((4,), "gain")
        return out

    def lm_loss(params, ids, cfg, mode, rows):
        x = params["emb"][ids] * cfg["scaling"]["factor"]
        for kind, lw in zip(cfg["layer_kinds"], params["layers"]):
            x = x * lw[kind]
        return jnp.mean(x * x)

    fam = types.SimpleNamespace(LEAF_NAMES=("emb", "a", "b"), leaves=leaves,
                                lm_loss=lm_loss)
    cfg = {"vocab_size": 16, "initializer_range": 0.5,
           "layer_kinds": ["a", "b", "a"], "scaling": {"factor": 2.0},
           "tie": None}
    hp = tiny.tiny_config("train", 1)["program"]["optimizer"]
    ids = np.arange(12, dtype=np.int32).reshape(2, 6)
    out = reference.train_reference(fam, cfg, hp, 3, [ids, ids])
    assert list(out["delta"]) == list(out["grad1"]) == \
        ["emb", "layers.0.a", "layers.1.b", "layers.2.a"]
    assert all(v > 0 for v in out["delta"].values()), out["delta"]


# --------------------------------------------------------------------------
# the seam is the only place an architecture lives
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def other_family(tmp_path_factory):
    """families/llama.py under another name in another directory, two
    leaves renamed and one key taken off the checked list."""
    with open(os.path.join(common.BENCH_DIR, "families", "llama.py")) as f:
        src = f.read()
    for old, new in (('"gate"', '"wg"'), ('"ln2"', '"post"'),
                     ('"rope_theta",', "")):
        assert old in src
        src = src.replace(old, new)
    d = tmp_path_factory.mktemp("families")
    (d / "renamed.py").write_text(src)
    cfg = tiny.tiny_config("serve", 2)
    cfg["program"]["family"] = "renamed"
    fam = common.load_family(cfg, str(d))
    assert "layers.0.wg" in fam.leaves(cfg) and "rope_theta" not in fam.CHECKED
    return fam


@pytest.mark.parametrize("kind", ("serve", "train"))
def test_a_family_file_elsewhere_runs_the_rehearsals(other_family, kind):
    lim = {"served_gap": 0.005} if kind == "serve" else common.load_limits(
        "mistral-7b-train.seq4096")
    out = tiny.run_tiny(kind, 2**31 + 99, 0.5, lim, family=other_family)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0


def test_lib_and_run_name_no_architecture():
    words = re.compile("Llama|q_proj|gate_proj|num_key_value_heads|"
                       "LAYER_LEAVES")
    lib = os.path.join(common.BENCH_DIR, "lib")
    files = [os.path.join(lib, n) for n in sorted(os.listdir(lib))
             if n.endswith(".py")] + [os.path.join(common.BENCH_DIR, "run.py")]
    hits = []
    for path in files:
        with open(path) as f:
            hits += [f"{os.path.relpath(path, common.BENCH_DIR)}:{i}: {ln}"
                     for i, ln in enumerate(f, 1) if words.search(ln)]
    assert not hits, hits


def test_every_configuration_names_a_family_file():
    configs = os.path.join(common.BENCH_DIR, "configs")
    names = sorted(n for n in os.listdir(configs) if n.endswith(".json"))
    assert names
    for n in names:
        with open(os.path.join(configs, n)) as f:
            fam = common.load_family(json.load(f))
        for fn in ("leaves", "program_name", "build", "serve_logits",
                   "lm_loss"):
            assert callable(getattr(fam, fn)), (n, fn)


@pytest.mark.parametrize("program", ({}, {"family": "no-such"}))
def test_a_configuration_must_name_a_family_file(program, tmp_path):
    with pytest.raises(SystemExit) as e:
        common.load_family({"program": program}, str(tmp_path))
    msg = str(e.value)
    assert "\n" not in msg and "program.family" in msg and \
        str(tmp_path) in msg
