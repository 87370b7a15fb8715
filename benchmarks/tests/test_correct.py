"""What decides ``correct``, at a size a test run can hold, on the CPU:

* the timed path of each kind of cell passes its cell's own limits;
* the control (the reference in int8, one precision below the bfloat16
  the configurations state) does not;
* a run whose timed path is broken underneath comes out as not correct,
  once for each fault a cell can have: a step that returns its state
  unchanged, half of the batch left out, a token altered where it is
  produced. (The cells are on one chip: no exchange to leave out.)

The tests skip the harness's look for a chip (run.py's device_info) and
drive the rest of a run through the same drivers."""
import functools
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import tiny  # noqa: E402

tiny.setup_cpu()

from benchmarks.lib import common, correct, reference, serve, traffic  # noqa

TRAIN = "mistral-7b-train.seq4096"
SERVE = "mistral-7b-serve.decode-closed32"
SEED = 2**31 + 4321


def limits(cell):
    """The cell's own limits. The training numbers are relative and hold
    at the test size as they stand. The serving number is a gap between
    logits, which at the test widths (hidden 256, two layers) are a
    quarter the size: there the bf16 path read 0.0 and the int8 control
    0.0097 to 0.0166 over four seeds (CPU, PR 26), so the test holds both
    to 0.005 where the cell holds them to its own limit."""
    if cell == SERVE:
        return {"served_gap": 0.005}
    return common.load_limits(cell)


@pytest.fixture(scope="module")
def train_ref():
    cfg, mix = tiny.tiny_config("train", 1), tiny.tiny_train_mix()
    batches = [traffic.train_batch(mix, SEED, k, cfg["vocab_size"])
               for k in range(mix["check_steps"])]
    hp = cfg["program"]["optimizer"]
    follow = functools.partial(reference.train_reference,
                               common.load_family(cfg), cfg, hp, SEED, batches)
    return follow, follow()


def test_train_timed_path_is_correct():
    out = tiny.run_tiny("train", SEED, 0.5, limits(TRAIN))
    assert out["correct"], out["compared"]
    assert out["counters"]["builds_in_window"] == 0
    assert out["e2e"]["setup_s"] > 0 and out["counters"]["steps"] > 0


def test_train_control_int8_is_not_correct(train_ref):
    follow, want = train_ref
    got = follow(mode="int8")
    cmp_ = correct.compare_training(got, want, limits(TRAIN))
    assert not all(v["ok"] for v in cmp_.values()), cmp_


def test_train_fault_half_batch_reference(train_ref):
    follow, want = train_ref
    got = follow(rows=[0])
    cmp_ = correct.compare_training(got, want, limits(TRAIN))
    assert not all(v["ok"] for v in cmp_.values()), cmp_


def test_train_fault_state_unchanged():
    import paddle_tpu as paddle

    def frozen(step):
        return lambda x, y: paddle.to_tensor(np.float32(6.3))
    out = tiny.run_tiny("train", SEED, 0.3, limits(TRAIN), break_with=frozen)
    assert not out["correct"]
    assert out["compared"]["grad_gap"]["value"] == pytest.approx(1.0)
    assert out["compared"]["delta_gap"]["value"] == pytest.approx(1.0)


def test_train_fault_half_of_the_batch_left_out():
    import paddle_tpu as paddle

    def half(step):
        def drive(x, y):
            return step(paddle.to_tensor(np.asarray(x._data)[:1]),
                        paddle.to_tensor(np.asarray(y._data)[:1]))
        return drive
    out = tiny.run_tiny("train", SEED, 0.3, limits(TRAIN), break_with=half)
    assert not out["correct"], out["compared"]


def test_serve_timed_path_is_correct_and_control_is_not():
    lim = limits(SERVE)
    out = tiny.run_tiny("serve", SEED, 1.0, lim)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["counters"]["tokens_delivered"] > 0
    # the control, at the same prompts and tokens of a fresh short run
    cfg, mix = tiny.tiny_config("serve", 2), tiny.tiny_serve_mix()
    mix["check_sample"] = 4
    reqs = traffic.serve_requests(mix, SEED, cfg["vocab_size"])[:4]
    rng = np.random.default_rng(0)
    served = {q["id"]: (q, 24) for q in reqs}
    gens = {q["id"]: rng.integers(1, cfg["vocab_size"], 24).tolist()
            for q in reqs}
    cmp_, info = serve.check(cfg, common.load_family(cfg), mix, SEED, served,
                             gens, {}, lim, control=True)
    assert not all(v["ok"] for v in cmp_.values()), (cmp_, info)


def test_serve_fault_token_altered_where_it_is_produced():
    def altered(sampler):
        n = [0]

        def bad(logits):
            n[0] += 1
            tok = sampler(logits)
            return (tok + 1) % len(logits) if n[0] % 5 == 0 else tok
        return bad
    out = tiny.run_tiny("serve", SEED, 1.0, limits(SERVE),
                        break_with=altered)
    assert not out["correct"], out["compared"]
