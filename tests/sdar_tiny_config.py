"""A tiny ``sdar`` configuration for the CPU tests: the benchmark's own
configuration file with every size cut (tests/test_sdar.py,
tests/test_benchmark_families.py). The keys stay the file's, so the
family's leaves, build, reference and counts run exactly as they do for
the cell: hidden 64, 4 / 2 heads of 16, 8 experts of 32 top-2, 2 layers,
vocabulary 256, blocks of 4, the MASK id the last of the vocabulary."""
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SIZES = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, num_experts=8,
    num_experts_per_tok=2, max_position_embeddings=512)


def tiny_config(dtype="float32", scheduler=None, **sizes):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sdar-30b-a3b-serve.json")) as f:
        cfg = json.load(f)
    cfg.update(SIZES)
    cfg.update(sizes)
    cfg["n_routed_experts"] = cfg["num_experts"]
    # matrices of a size that gives scores and logits of order one at a
    # hidden size of 64
    cfg["initializer_range"] = 0.08
    cfg["assumed"].update(mask_token_id=cfg["vocab_size"] - 1)
    args = {k: cfg[k] for k in SIZES}
    args.update(initializer_range=0.08, dtype=dtype,
                mask_token_id=cfg["vocab_size"] - 1)
    cfg["program"].update(
        constructor="sdar_tiny", constructor_args=args, dtype=dtype,
        pool={"num_pages": 96, "page_size": 16, "max_length": 256},
        scheduler=dict({"max_batch_size": 4, "prefill_chunk_tokens": 16,
                        "serving_buckets": "16,32", "denoising_steps": 2,
                        "remasking": "low_confidence_static"},
                       **(scheduler or {})))
    return cfg
