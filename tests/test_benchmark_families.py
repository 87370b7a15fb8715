"""Tier-1's view of the seam between the benchmark's harness and an
architecture: the pin and seam tests of ``benchmarks/tests/
test_families.py`` (not collected from there by tier-1), and the CPU
rehearsal of ``benchmarks/run.py``'s serving driver with a tiny ``xing4``,
a tiny ``evabyte`` and a tiny ``sdar`` configuration through to
``correct: true`` (and, for ``sdar``, two planted faults through to
``correct: false``); the
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
from sdar_tiny_config import tiny_config as sdar_tiny  # noqa: E402
from xing4_tiny_config import tiny_config  # noqa: E402


# the per-layer metrics ISSUE 38 appended to every serving cell
HOST_METRICS = ("idle_in_cache_load_share.serve",
                "idle_in_xla_trace_share.serve",
                "programs_built_in_window.serve", "idle_in_gc_share.serve",
                "idle_offcpu_share.serve")


def test_the_cell_and_its_metrics_are_entered(monkeypatch):
    """The EvaByte cell's entry as ``benchmarks/tests/
    test_evabyte_family.py`` pins it (that file is the benchmark's and
    stays as it is; its count of the cell's per-layer metrics predates
    the five of ISSUE 38), and the five beside them."""
    from benchmarks.lib import common
    from benchmarks.tests import test_evabyte_family as eva

    listed = common.metrics_for

    def before(bench, cell, kind):
        return [m for m in listed(bench, cell, kind)
                if m["name"] not in HOST_METRICS]

    monkeypatch.setattr(common, "metrics_for", before)
    eva.test_the_cell_and_its_metrics_are_entered()
    names = {m["name"] for m in
             listed(common.load_benchmark(), eva.CELL, "per_layer")}
    assert names >= set(HOST_METRICS)


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


def _sdar_rehearsal(tmp_path, seconds=2.0, trace=True):
    from benchmarks.lib import common, serve
    from paddle_tpu.framework import telemetry

    if telemetry.peek_tracer() is not None:
        telemetry.peek_tracer().clear()
    config = sdar_tiny()
    config["assumed"]["order_margin"] = 0.05
    bench = common.load_benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[
        "sdar-30b-a3b-serve.blocks-closed64"]
    fam = common.load_family(config)
    # (the CPU makes a hundred steps a second here: enough rounds to
    # outlast the window, or the pump idles and it never closes)
    mix = dict(tiny.tiny_serve_mix(), clients=4, rounds=80,
               prompt_len={"dist": "fixed", "value": 24},
               output_len={"dist": "fixed", "value": 16})
    out = serve.run(bench, cell, config, fam, mix, 2**31 + 77, seconds,
                    trace, time.perf_counter(), tiny.CPU_DEVICE,
                    tiny.CPU_PEAKS, trace_dir=str(tmp_path),
                    limits={"served_gap": 1.75})
    return out, cell, config, fam, mix


def test_the_rehearsal_of_a_tiny_sdar_cell_is_correct(tmp_path):
    """The serving driver end to end on the CPU over a block-diffusion
    model: the family's build under LazyGuard, the seed's leaves,
    BatchScheduler (warmed: no program is built inside the window) over
    the Llama adapter with the routed layer program behind ServingEngine,
    prompts of 24 and answers of 16 in blocks of 4 at two passes a block
    (a block's commit rides the next block's first pass),
    a traced window whose spans the new readers read, the reference's
    replay of the sampled requests with the served tokens forced. The
    seed's leaves are bfloat16, so the program computes in bfloat16
    against the float32 reference: its logits lie 0.01-0.03 from the
    reference's and a served token up to 0.06 under the reference's best
    where two logits nearly tie; a block whose unmasking order was a near
    tie (``order_margin`` 0.05) is not judged: at this size a position
    fixed in another pass reads another context. ``served_gap`` is the
    family's sum (``loss_share``: the served tokens' summed loss over the
    loss of a program blind to ties under ``tie_margin``, the denominator
    at least ``tie_floor``): 0.08 here, 0.00-0.08 over five seeds, and
    the two planted faults below 2.7 and 3.7 (measured here, PR 36).
    Limit 1.75, the cell's own; the harness's ``top_logit`` stays None (the
    sampler is never called), so ``logit_err`` is not formed."""
    from benchmarks.lib import common

    out, cell, config, fam, mix = _sdar_rehearsal(tmp_path)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["compared"]) == {"served_gap"}
    assert out["counters"]["builds_in_window"] == 0
    red, _ = common.reduce_trace(str(tmp_path), out["sync_ns"],
                                 out["window_ns"], out["build_spans"])
    ctx = {"cell": cell, "config": config, "traffic": mix,
           "peaks": tiny.CPU_PEAKS, "window_s": out["window_s"],
           "counters": out["counters"], "trace": red, "flops": fam,
           "chips": 1}
    ratio = common.read_metric("block_passes_per_token.serve", ctx)
    if ratio is not None:           # read only where the spans could be laid
        assert 0.4 <= ratio <= 0.6  # T / B = 0.5 but for open blocks
    spread = common.read_metric("expert_tokens_max_over_mean.serve", ctx)
    if spread is not None:
        assert 1.0 <= spread <= config["num_experts"]
    # the CPU's trace holds no Mosaic kernel and no grouped matmul by name
    assert common.read_metric("block_attention_roofline.serve", ctx) is None
    assert common.read_metric("moe_matmul_roofline.serve", ctx) is None
    assert common.read_metric("step_mfu.serve", ctx) > 0


@pytest.mark.parametrize("fault", ("causal_inside_the_block",
                                   "no_commit_pass"))
def test_a_faulty_block_program_is_not_correct(tmp_path, monkeypatch, fault):
    """Two faults a block-diffusion server can have and still emit
    tokens, planted in the program for one rehearsal: a mask that is
    causal inside the block (the kernel handed ``block`` 0), and a
    scheduler that never commits a block (keeps its last denoising
    pass's K/V, written while some of the block was still MASK, and
    opens the next block with nothing behind it to carry: later blocks
    read them). Both read
    ``correct: false`` by ``served_gap`` (2.7 and 3.7 against the sound
    program's 0.08 and the limit of 1.75, measured here, PR 36)."""
    from paddle_tpu.inference import serving
    from paddle_tpu.inference.paged_llama import PagedLlamaAdapter

    if fault == "causal_inside_the_block":
        inner = PagedLlamaAdapter._layer_switches

        def switches(self, layer):
            return dict(inner(self, layer), block=0)

        monkeypatch.setattr(PagedLlamaAdapter, "_layer_switches", switches)
    else:
        inner = serving.BatchScheduler._roll_back
        opened = serving.BatchScheduler._open_block

        def roll_back(self, req, n):
            if any(req._block.masked):   # else the last pass's K/V stay
                inner(self, req, n)

        def open_block(self, req, behind=()):
            opened(self, req)            # and no pass writes the block's

        monkeypatch.setattr(serving.BatchScheduler, "_roll_back", roll_back)
        monkeypatch.setattr(serving.BatchScheduler, "_open_block",
                            open_block)
    out, *_ = _sdar_rehearsal(tmp_path, seconds=1.0, trace=False)
    assert not out["correct"]
    assert not out["compared"]["served_gap"]["ok"], out["compared"]
