#!/usr/bin/env python
"""Llama-2-7B feasibility artifact (VERDICT r3 missing #3 -> SCALE_7B.json).

The north star (BASELINE.json) is Llama-2-7B training at >=45% MFU on a
v5e-256. Everything measured so far is the 454M single-chip proxy; this
tool produces the evidence that the REAL 7B config fits and performs at
the real mesh shape, without 256 chips:

1. analytic per-chip memory + step plan at mesh dp32 x mp8 (the
   scaling-book recipe: TP over the fast axis, ZeRO-1 over dp,
   recompute, gradient accumulation) — every term stated;
2. jaxpr-liveness + trace validation of the ACTUAL fleet mp8 training
   step at full 7B shapes (the model is materialized once on the host
   and the step is traced, never executed — ~95 GB host RAM);
3. an 8-virtual-device CPU-mesh dryrun of the exact topology
   (mp8, MHA 32:32 ratio, grad accumulation) at tiny hidden size,
   asserting convergence;
4. MFU extrapolation from the measured single-chip headline to
   v5e-256 with an explicit ICI collective-overhead model.

Run (detached; writes SCALE_7B.json):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python tools/scale_7b.py [--skip-trace]
"""
import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

GB = 2**30

# v5e chip (How to Scale Your Model numbers)
V5E = {
    "peak_bf16_tflops": 197.0,
    "hbm_gb": 16.0,
    "hbm_gbps": 819.0,
    # one ICI link ~45 GB/s usable each direction; v5e 2D torus,
    # an 8-chip ring along one axis does bidirectional ring collectives
    "ici_ring_gbps": 2 * 45.0,
}


def seven_b_plan(seq=4096, micro_batch=1, accum=4, dp=32, mp=8):
    """Closed-form per-chip budget for llama2-7b on dp32 x mp8 = 256.

    Round-5 plan: the VOCAB-PARALLEL FUSED CHUNKED CE head (shard-local
    online-lse + mp-collective combine — ops/kernels/fused_loss.py
    fused_linear_cross_entropy_vocab_parallel) replaces the materialized
    [t, v/mp] logits path, and SELECTIVE recompute (recompute_granularity
    ="selective": dot outputs saved, only cheap glue + flash replayed)
    replaces full-layer recompute — together they drop the 8/6 remat
    flops charge to ~1.03x while still fitting 16 GB with margin.
    Megatron-SP over the mp axis is on, halving TP collective volume.
    """
    from paddle_tpu.models import llama2_7b

    cfg = llama2_7b(max_position_embeddings=seq, recompute=True,
                    recompute_granularity="selective",
                    sequence_parallel=True, fused_head_loss=True)
    n = cfg.num_params()
    h, i, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    L, s, b = cfg.num_hidden_layers, seq, micro_batch
    t_local = b * s  # tokens resident per chip per micro-step

    # --- per-chip memory (bytes) ---------------------------------------
    # TP shards every matmul weight over mp; ZeRO-1 shards optimizer
    # state (fp32 master + m + v) over the dp axis as well. Activation
    # terms are charged x accum: the framework's in-step unrolled
    # accumulation keeps every micro-batch's saved set live until the
    # single backward.
    m = {
        "params_bf16": 2.0 * n / mp,
        "grads_fp32": 4.0 * n / mp,
        "opt_master_m_v_fp32": 12.0 * n / (mp * dp),
        # per-layer boundary activations (bf16), SEQUENCE-SHARDED over
        # mp with sequence_parallel=True (models/llama.py _constrain_act)
        "saved_boundaries": 2.0 * h * L * t_local / mp * accum,
        # selective recompute saves the DOT OUTPUTS per layer: qkv
        # 3h/mp + o_proj out h (seq-sharded -> /mp) + gate,up 2i/mp +
        # down out h (/mp). Flash attention is a custom_vjp (not a
        # dot_general) so its o/lse are REPLAYED, not saved;
        # norms/rope/silu-prod glue is replayed too.
        "selective_saved_dots": 2.0 * (5 * h + 2 * i) * t_local / mp
        * L * accum,
        # fused vocab-parallel CE: O(t) softmax stats + one fp32
        # [t, chunk] logits block + fp32 dh accumulator (transient)
        "fused_ce_working_set": (4.0 * t_local * 4096
                                 + 4.0 * t_local * h
                                 + 12.0 * t_local),
    }
    per_chip_gb = {k: round(x / GB, 3) for k, x in m.items()}
    per_chip_gb["total"] = round(sum(m.values()) / GB, 3)
    per_chip_gb["fits_16gb"] = per_chip_gb["total"] < V5E["hbm_gb"] * 0.9

    # --- per-chip step time model --------------------------------------
    tokens_per_chip_step = t_local * accum
    model_flops = (6.0 * n + 6.0 * L * h * s) * tokens_per_chip_step
    # selective recompute replays only flash attention (one extra
    # attention fwd = 2*L*h*s per token) and the fused CE backward
    # recomputes the chunk logits (2*h*v per token); the elementwise
    # glue it also replays is bandwidth- not flops-relevant
    hw_flops = model_flops * (
        1.0 + (2.0 * L * h * s + 2.0 * h * v)
        / (6.0 * n + 6.0 * L * h * s))
    t_compute = hw_flops / mp / (V5E["peak_bf16_tflops"] * 1e12)

    # TP+SP collectives (the framework's sequence_parallel=True path,
    # mp_layers + sequence_parallel_utils): per layer per micro-batch,
    # one reduce-scatter + one all-gather around each of the two
    # parallel blocks instead of full allreduces — each moves
    # (mp-1)/mp * bytes per chip, i.e. HALF the allreduce volume.
    ar_bytes = 2.0 * t_local * h
    coll_bytes = 2 * L * accum * ar_bytes * 2 * (mp - 1) / mp / 2.0
    t_ici = coll_bytes / (V5E["ici_ring_gbps"] * 1e9)
    # dp grad sync: ZeRO-1 reduce-scatter + all-gather of 2N bf16 over
    # dp=32 ring, once per step (overlappable with cooldown bwd; count
    # half as exposed)
    dp_bytes = 2.0 * (2.0 * n / mp) * 2 * (dp - 1) / dp
    t_dcn = 0.5 * dp_bytes / (V5E["ici_ring_gbps"] * 1e9)

    t_step = t_compute + t_ici + t_dcn
    mfu = 100.0 * (model_flops / mp) / (
        V5E["peak_bf16_tflops"] * 1e12 * t_step)
    return cfg, {
        "mesh": {"dp": dp, "mp": mp, "chips": dp * mp,
                 "order": "dp outer (DCN-tolerant), mp inner (ICI)"},
        "schedule": {"seq": s, "micro_batch": b,
                     "grad_accum_steps": accum,
                     "global_batch": b * dp * accum,
                     "tokens_per_step_global": b * dp * accum * s,
                     "recompute": "selective (dots saved, glue+flash "
                                  "replayed — recompute_granularity)",
                     "loss_head": "vocab-parallel FUSED chunked CE "
                                  "(shard-local lse + mp collectives; "
                                  "no [t, v/mp] logits materialized)",
                     "sequence_parallel": True,
                     "zero_stage": 1},
        "per_chip_memory_gb": per_chip_gb,
        "per_step_model": {
            "model_tflops_per_chip": round(model_flops / mp / 1e12, 1),
            "t_compute_s": round(t_compute, 4),
            "t_ici_tp_collectives_s": round(t_ici, 4),
            "t_dp_grad_sync_exposed_s": round(t_dcn, 4),
            "t_step_s": round(t_step, 4),
            "projected_mfu_pct": round(mfu, 1),
            "projected_tokens_per_sec_per_chip": round(
                tokens_per_chip_step / t_step, 0),
        },
    }


def trace_7b_mp8(report, seq=4096, micro_batch=1):
    """Materialize the real 7B model under the fleet mp8 mesh (8
    virtual CPU devices) and TRACE its training step — no execution.
    Validates that the exact config builds, shards, and traces, and
    measures the jaxpr-liveness peak of the global program."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as optim
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import LlamaForCausalLM, llama2_7b

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 8}
    fleet.init(is_collective=True, strategy=strategy)
    # the EXACT plan config: SP on, selective recompute, fused
    # vocab-parallel CE head (engages at mp8: 32000 % 8 == 0)
    cfg = llama2_7b(max_position_embeddings=seq, recompute=True,
                    recompute_granularity="selective",
                    sequence_parallel=True, fused_head_loss=True)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.bfloat16()

    # Tracing reads only shapes/dtypes of the optimizer state (the
    # compiled step swaps every state payload for a tracer), so the
    # fp32 master + m + v (~81 GB for 7B) are created as
    # ShapeDtypeStruct payloads instead of real zeros — the host peak
    # stays at the ~27 GB fp32 build transient.
    import jax

    from paddle_tpu.framework.core import Tensor as _T
    from paddle_tpu.optimizer import optimizer as _opt_mod

    def _add_acc(self, name, param, fill_value=0.0, dtype=None):
        if param._uid in self._accumulators[name]:
            return
        import jax.numpy as jnp

        d = dtype or (jnp.float32 if self._use_master(param)
                      else param._data.dtype)
        self._accumulators[name][param._uid] = _T(
            jax.ShapeDtypeStruct(tuple(param.shape), d),
            persistable=True, name=f"{param.name}_{name}_0")

    def _get_master(self, param):
        import jax.numpy as jnp

        if not self._use_master(param):
            return None
        if param._uid not in self._master_weights:
            self._master_weights[param._uid] = _T(
                jax.ShapeDtypeStruct(tuple(param.shape), jnp.float32),
                persistable=True, name=f"{param.name}_fp32_master_0")
        return self._master_weights[param._uid]

    _opt_mod.Optimizer._add_accumulator = _add_acc
    _opt_mod.Optimizer._get_master = _get_master
    opt = optim.AdamW(3e-4, parameters=model.parameters(),
                      multi_precision=True)
    opt._create_accumulators()
    # params too: values are never read under trace — free the bf16
    for t in model.parameters():
        if isinstance(t._data, jax.Array):
            t._data = jax.ShapeDtypeStruct(t._data.shape, t._data.dtype)

    @paddle.jit.to_static
    def step(x, y):
        _, loss = model(x, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randint(
        0, cfg.vocab_size, (micro_batch, seq)).astype("int32"))
    y = paddle.to_tensor(rng.randint(
        0, cfg.vocab_size, (micro_batch, seq)).astype("int64"))

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from roofline import _peak_live_bytes, trace_compiled_step

    jaxpr, state, donated = trace_compiled_step(step, x, y)
    peak, peak_at, n_eqns = _peak_live_bytes(jaxpr, donated)
    state_bytes = sum(
        int(np.prod(t._data.shape)) * t._data.dtype.itemsize
        for t in state)
    sharded = sum(
        1 for t in state
        if getattr(t, "_dist_attr", None) and "mp" in (t._dist_attr or ()))
    report["trace_mp8_full_7b"] = {
        "built": True,
        "n_params": cfg.num_params(),
        "n_state_tensors": len(state),
        "tp_sharded_params": sharded,
        "n_eqns": n_eqns,
        "global_peak_live_gb": round(peak / GB, 2),
        "global_state_gb": round(state_bytes / GB, 2),
        "note": "global (pre-partition) liveness of the traced step; "
                "per-chip residency is the analytic table — GSPMD "
                "divides sharded dims by the mesh axis",
    }
    return report


def tiny_topology_dryrun(report):
    """Exact-topology dryrun in a subprocess: mp8, MHA 32:32 head
    ratio scaled down, 4-step grad accumulation; loss must fall."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
import json
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import paddle_tpu as paddle
import paddle_tpu.optimizer as optim
from paddle_tpu.distributed import fleet
from paddle_tpu.models import LlamaForCausalLM, LlamaConfig

strategy = fleet.DistributedStrategy()
strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 8}
fleet.init(is_collective=True, strategy=strategy)
# llama2-7b topology scaled: MHA (kv == q heads), 8 heads over mp8,
# recompute + fused head loss as in the plan
cfg = LlamaConfig(vocab_size=512, hidden_size=256, intermediate_size=688,
                  num_hidden_layers=2, num_attention_heads=8,
                  num_key_value_heads=8, max_position_embeddings=128,
                  recompute=True, recompute_granularity="selective",
                  sequence_parallel=True, fused_head_loss=True)
paddle.seed(0)
model = LlamaForCausalLM(cfg)
opt = optim.AdamW(1e-3, parameters=model.parameters())
ACCUM = 4

# TPU-idiomatic gradient accumulation: the micro-batch loop unrolls
# INSIDE one compiled step (XLA schedules it; one grad sync per step —
# the plan's accumulate_steps semantics)
@paddle.jit.to_static
def step(xs, ys):
    total = None
    for k in range(ACCUM):
        _, loss = model(xs[:, k], ys[:, k])
        total = loss if total is None else total + loss
    mean = total / ACCUM
    mean.backward()
    opt.step()
    opt.clear_grad()
    return mean

rng = np.random.RandomState(0)
# overfit one fixed accumulated batch: loss must fall monotonically
xs = paddle.to_tensor(
    rng.randint(0, cfg.vocab_size, (1, ACCUM, 64)).astype("int32"))
ys = paddle.to_tensor(
    ((np.asarray(xs._data) + 1) % cfg.vocab_size).astype("int64"))
losses = [float(np.asarray(step(xs, ys)._data)) for _ in range(5)]
print(json.dumps({"losses": [round(l, 4) for l in losses],
                  "converges": losses[-1] < losses[0],
                  "mesh": "mp8 + SP, accum 4 (in-step), selective "
                          "recompute, fused vocab-parallel CE"}))
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=1200)
    try:
        report["tiny_topology_dryrun"] = json.loads(
            r.stdout.strip().splitlines()[-1])
    except Exception:
        report["tiny_topology_dryrun"] = {
            "error": (r.stderr or "no output")[-800:]}
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-trace", action="store_true",
                    help="skip the ~95 GB full-7B materialize+trace")
    ap.add_argument("--seq", type=int, default=4096)
    args = ap.parse_args()

    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")

    cfg, plan = seven_b_plan(seq=args.seq)
    report = {"north_star": "Llama-2-7B, v5e-256, >=45% MFU "
                            "(BASELINE.json)",
              "plan": plan}

    # extrapolation anchor: the measured 454M single-chip headline
    try:
        with open(os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "BENCH_HEADLINE_LAST.json")
                ) as f:
            hl = json.load(f)
        report["measured_anchor"] = {
            "value_mfu_pct": hl["record"]["value"],
            "config": "454M proxy, single v5e chip",
            "git_rev": hl.get("git_rev", "")[:12],
        }
        anchor = hl["record"]["value"]
    except Exception:
        anchor = None
    if anchor is not None:
        proj = plan["per_step_model"]["projected_mfu_pct"]
        # Decomposed extrapolation. The 454M proxy measured `anchor`
        # (46.08%) against a 96.8% roofline ceiling — a 2.1x gap with
        # two distinct causes: (a) XLA auto-remat flops the proxy's
        # recompute=False config forces on a 16 GB chip (bounded by
        # 8/6 = 1.33x), and (b) residual kernel/overhead inefficiency.
        # The r5 7B plan charges only ~1.03x replay flops (selective
        # recompute + fused CE replaced the blanket 8/6), so the
        # anchor's remat contamination must be FACTORED OUT of the
        # efficiency estimate (else remat the plan never pays is
        # double-counted): resid_eff = 0.476 x 1.333 = 0.635. Carrying
        # the WHOLE proxy gap (0.476) is the pessimistic floor; the
        # roofline itself is the ceiling. Larger matmuls (h 4096 vs
        # 1536) push real efficiency further toward the ceiling.
        floor = round(proj * anchor / 96.8, 1)
        resid = round(proj * anchor * (8.0 / 6.0) / 96.8, 1)
        report["extrapolated_mfu_v5e256"] = {
            "roofline_ceiling_pct": proj,
            "anchored_floor_pct": floor,
            "point_estimate_pct": min(resid, proj),
            "method": "floor = roofline x measured proxy efficiency "
                      "(0.476, remat-contaminated); point = roofline "
                      "x remat-free residual efficiency (0.635) — "
                      "valid since the r5 plan's own replay charge "
                      "is ~1.03x, not 8/6",
            "north_star_within_range": floor <= 45.0 <= proj,
            "resolving_experiment": "chip window: run "
                "BENCH_RECOMPUTE=1 python bench.py --only llama to "
                "measure the proxy's efficiency with explicit "
                "recompute (isolates remat from overhead)",
        }

    report = tiny_topology_dryrun(report)
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "SCALE_7B.json")
    if not args.skip_trace:
        report = trace_7b_mp8(report, seq=args.seq)
    else:
        # refresh the cheap sections without discarding a prior
        # (expensive) full-7B trace validation
        try:
            with open(out) as f:
                prev = json.load(f).get("trace_mp8_full_7b")
            if prev:
                report["trace_mp8_full_7b"] = prev
        except Exception:
            pass
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report["plan"]["per_step_model"]))
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
