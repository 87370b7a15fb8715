"""A tiny ``xing4`` configuration for the CPU tests: the benchmark's own
configuration file with every size cut (tests/test_xing4.py,
tests/test_latent_pool.py, tests/test_benchmark_families.py). The keys
stay the file's, so the family's leaves, build, reference and counts run
exactly as they do for the cell."""
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SIZES = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=3, first_k_dense_replace=1,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=8, num_experts_per_tok=2, max_position_embeddings=512,
    num_nextn_predict_layers=1)


def tiny_config(dtype="float32", **sizes):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "xing4-29b-a4b-serve.json")) as f:
        cfg = json.load(f)
    cfg.update(SIZES)
    cfg.update(sizes)
    # the tests compare every position unless they ask for the margin
    cfg["assumed"] = dict(cfg["assumed"], router_margin=0.0)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"], factor=4,
                               original_max_position_embeddings=64)
    args = {k: cfg[k] for k in SIZES}
    args.update(rope_scaling=cfg["rope_scaling"], dtype=dtype)
    cfg["program"].update(
        constructor="xing4_tiny", constructor_args=args, dtype=dtype,
        pool={"num_pages": 96, "page_size": 16},
        scheduler={"max_batch_size": 4})
    return cfg
