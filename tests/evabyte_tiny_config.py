"""A tiny ``evabyte`` configuration for the CPU tests: the benchmark's own
configuration file with every size cut (tests/test_evabyte.py,
tests/test_eva_pool.py, tests/test_benchmark_families.py). The keys stay
the file's, so the family's leaves, build, reference and counts run exactly
as they do for the cell. A window of 64 in chunks of 8 (pages of 8): a
window's 8 summary rows fill one page, as the published 2048 / 16 fills
eight, and a few hundred tokens cross several windows."""
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SIZES = dict(
    vocab_size=64, hidden_size=128, intermediate_size=192,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
    num_pred_heads=8, window_size=64, chunk_size=8,
    max_position_embeddings=512)


def tiny_config(dtype="float32", **sizes):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "evabyte-6.5b-serve.json")) as f:
        cfg = json.load(f)
    cfg.update(SIZES)
    cfg.update(sizes)
    cfg["head_dim"] = cfg["hidden_size"] // cfg["num_attention_heads"]
    # matrices of a size that gives scores and logits of order one at a
    # hidden size of 128
    cfg["init_std"] = cfg["initializer_range"] = 0.08
    args = {k: cfg[k] for k in SIZES}
    args.update(init_std=cfg["init_std"], dtype=dtype)
    cfg["program"].update(
        constructor="evabyte_tiny", constructor_args=args, dtype=dtype,
        pool={"num_pages": 96, "page_size": 8},
        scheduler={"max_batch_size": 4, "prefill_chunk_tokens": 24,
                   "serving_buckets": "8,16,32"})
    return cfg
