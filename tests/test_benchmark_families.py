"""Tier-1's view of the seam between the benchmark's harness and an
architecture: the pin and seam tests of ``benchmarks/tests/
test_families.py`` (not collected from there by tier-1), and the CPU
rehearsal of ``benchmarks/run.py``'s serving driver with a tiny ``xing4``
and a tiny ``evabyte`` configuration through to ``correct: true``; the
``evabyte`` family's own seam, traffic and reader tests
(``benchmarks/tests/test_evabyte_family.py``) are collected here too."""
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import paddle_tpu as paddle  # noqa: E402

# the imported module switches the Pallas interpreter on as it is imported
# (its rehearsals run the kernels on the CPU): every xdist worker imports
# every test file, so the switch goes back at once and is held only while
# this file's tests run
_PREV = paddle.get_flags("FLAGS_pallas_interpret")
from benchmarks.tests.test_families import *  # noqa: E402,F401,F403
from benchmarks.tests.test_evabyte_family import *  # noqa: E402,F401,F403
from benchmarks.tests import tiny  # noqa: E402
paddle.set_flags(_PREV)

from evabyte_tiny_config import tiny_config as evabyte_tiny  # noqa: E402
from xing4_tiny_config import tiny_config  # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def _interpret():
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    yield
    paddle.set_flags(_PREV)


def test_the_rehearsal_of_a_tiny_xing4_cell_is_correct(tmp_path):
    """The serving driver end to end on the CPU: the family's build under
    LazyGuard, the seed's leaves, BatchScheduler over the latent-page
    adapter behind ServingEngine, a traced window, the reference over the
    sampled requests. The seed's leaves are bfloat16, so the program
    computes in bfloat16 against the float32 reference: at this size the
    program's logit of a served token lies 0.002 from the reference's and
    the served token is the reference's best (measured here, PR 30); a
    serving path that dropped a token of an expert, read a wrong page or
    mixed the streams wrongly reads a gap of order 0.1. Limits 0.02."""
    from benchmarks.lib import common, serve

    config = tiny_config(num_nextn_predict_layers=0, hidden_size=64)
    bench = common.load_benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[
        "xing4-29b-a4b-serve.decode-closed64"]
    fam = common.load_family(config)
    out = serve.run(bench, cell, config, fam, tiny.tiny_serve_mix(),
                    2**31 + 77, 2.0, True, time.perf_counter(),
                    tiny.CPU_DEVICE, tiny.CPU_PEAKS,
                    trace_dir=str(tmp_path),
                    limits={"served_gap": 0.02, "logit_err": 0.02})
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["compared"]) == {"served_gap", "logit_err"}
    c = out["counters"]
    assert c["tokens_delivered"] > 0 and c["pool_pages_used_peak"] > 0
    # the new readers on a CPU trace: nothing of the device to read, and
    # none raises; the counts of the window reach the reader as spans
    red, _ = common.reduce_trace(str(tmp_path), out["sync_ns"],
                                 out["window_ns"], out["build_spans"])
    ctx = {"cell": cell, "config": config, "traffic": {}, "chips": 1,
           "peaks": tiny.CPU_PEAKS, "window_s": out["window_s"],
           "counters": c, "trace": red, "flops": fam}
    for name in ("latent_attention_roofline.serve",
                 "moe_matmul_roofline.serve", "hc_device_share.serve"):
        assert common.read_metric(name, ctx) is None
    ratio = common.read_metric("expert_tokens_max_over_mean.serve", ctx)
    if ratio is not None:           # read only where the spans could be laid
        assert 1.0 <= ratio <= config["n_routed_experts"]
    assert common.read_metric("step_mfu.serve", ctx) > 0


def test_the_rehearsal_of_a_tiny_evabyte_cell_is_correct(tmp_path):
    """The serving driver end to end on the CPU over window-and-summary
    pages: the family's build under LazyGuard, the seed's leaves, the
    gains stored less one, BatchScheduler (warmed: no program is built
    inside the window) over the Llama adapter behind ServingEngine,
    prompts of 70-160 bytes and answers of 20-80 with a window of 64, so
    every request rolls, a traced window whose spans the two new readers
    read, the reference over the sampled requests. The seed's leaves are
    bfloat16 against the float32 reference: the program's logit of a
    served token reads 0.024 from the reference's and the served token
    0.005-0.011 under the reference's best (measured here, PR 34); a path
    that read a wrong page or summary reads a gap of order one. Limits
    0.1."""
    from benchmarks.lib import common, serve
    from paddle_tpu.framework import telemetry

    # the ring of an earlier rehearsal in this process holds another pump
    # thread's steps: the readers lay ONE run's spans on ONE trace
    if telemetry.peek_tracer() is not None:
        telemetry.peek_tracer().clear()
    config = evabyte_tiny()
    bench = common.load_benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[
        "evabyte-6.5b-serve.docs-closed24"]
    fam = common.load_family(config)
    mix = dict(tiny.tiny_serve_mix(), clients=4, rounds=6,
               prompt_len={"dist": "lognormal", "median": 100, "sigma": 0.5,
                           "min": 70, "max": 160},
               output_len={"dist": "lognormal", "median": 40, "sigma": 0.4,
                           "min": 20, "max": 80})
    out = serve.run(bench, cell, config, fam, mix, 2**31 + 77, 2.0, True,
                    time.perf_counter(), tiny.CPU_DEVICE, tiny.CPU_PEAKS,
                    trace_dir=str(tmp_path),
                    limits={"served_gap": 0.1, "logit_err": 0.1})
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["counters"]["builds_in_window"] == 0
    red, _ = common.reduce_trace(str(tmp_path), out["sync_ns"],
                                 out["window_ns"], out["build_spans"])
    ctx = {"cell": cell, "config": config, "traffic": mix,
           "peaks": tiny.CPU_PEAKS, "window_s": out["window_s"],
           "counters": out["counters"], "trace": red, "flops": fam,
           "chips": 1}
    share = common.read_metric("prefill_step_share.serve", ctx)
    assert share is not None and 0 < share < 100
    # the CPU's trace holds no Mosaic kernel: the reader reads nothing
    assert common.read_metric("eva_attention_roofline.serve", ctx) is None
