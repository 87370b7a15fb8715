"""Tiny stand-ins of the cells for CPU rehearsals and tests: the same
drivers, comparisons and report, at sizes a test run can hold. They skip
the harness's look for a chip (run.py's device_info) and nothing else.
No number from here is ever written under a device metric's name."""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

MODEL = dict(vocab_size=512, hidden_size=256, intermediate_size=512,
             num_attention_heads=4, num_key_value_heads=2, sliding_window=64,
             max_position_embeddings=512)

CPU_DEVICE = {"platform": "cpu", "kind": "rehearsal", "count": 1}
CPU_PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9,
             "source": "none: a rehearsal has no peaks"}


def setup_cpu():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import paddle_tpu as paddle
    paddle.set_flags({"FLAGS_pallas_interpret": True})


def tiny_config(kind, layers):
    from benchmarks.lib import common
    with open(os.path.join(common.BENCH_DIR, "configs",
                           f"mistral-7b-{kind}.json")) as f:
        config = json.load(f)
    config.update(MODEL, num_hidden_layers=layers, head_dim=64)
    config["program"]["constructor_args"].update(MODEL,
                                                 num_hidden_layers=layers)
    if kind == "serve":
        config["program"]["pool"] = {"num_pages": 128, "page_size": 16}
        config["program"]["scheduler"] = {"max_batch_size": 4}
    return config


def tiny_serve_mix(loop="closed"):
    mix = {"driver": "serve", "loop": loop, "name": "tiny-" + loop,
           "prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                          "min": 8, "max": 40},
           "output_len": {"dist": "lognormal", "median": 12, "sigma": 0.4,
                          "min": 6, "max": 24},
           "check_sample": 3}
    if loop == "closed":
        mix.update(clients=4, rounds=8, warmup="first_token_all_clients")
    else:
        mix.update(rate_per_s=2.0, horizon_s=30,
                   burst={"every": 5, "size": 2}, warmup={"seconds": 2.0})
    return mix


def tiny_train_mix():
    return {"driver": "train", "name": "tiny-train", "batch": 2,
            "seq_len": 128, "check_steps": 3, "in_flight": 2}


def run_tiny(kind, seed, seconds, limits, break_with=None, trace=False,
             loop="closed", trace_dir=None, family=None):
    """Drive one run of a tiny cell on the CPU; returns the driver's dict.
    ``family`` (a loaded module) stands in for the configuration's own."""
    from benchmarks.lib import common, serve, train

    bench = common.load_benchmark()
    name = {"serve": "mistral-7b-serve.decode-closed32",
            "train": "mistral-7b-train.seq4096"}[kind]
    cell = {w["name"]: w for w in bench["workloads"]}[name]
    config = tiny_config(kind, 2 if kind == "serve" else 1)
    fam = family or common.load_family(config)
    mix = tiny_serve_mix(loop) if kind == "serve" else tiny_train_mix()
    driver = serve if kind == "serve" else train
    return driver.run(bench, cell, config, fam, mix, seed, seconds, trace,
                      time.perf_counter(), CPU_DEVICE, CPU_PEAKS,
                      break_with=break_with, trace_dir=trace_dir,
                      limits=limits)


if __name__ == "__main__":
    setup_cpu()
    kind = sys.argv[1]
    loop = sys.argv[2] if len(sys.argv) > 2 else "closed"
    lim = {"loss_gap": 1e30, "grad_gap": 1e30, "delta_gap": 1e30,
           "served_gap": 1e30, "logit_err": 1e30}
    out = run_tiny(kind, 2**31 + 12345, 3.0, lim, loop=loop)
    out.pop("build_spans")
    out["counters"].pop("step_intervals_ms", None)
    print(json.dumps(out, default=str)[:3000])
