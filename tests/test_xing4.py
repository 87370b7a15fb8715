"""Xing4.0 on the CPU at a tiny size: the model, the drop-free experts
layer, the mHC residual path, the MTP module and ``paddle.LazyGuard``,
each against the plain reference of ``benchmarks/families/xing4.py``
(float32 ``jax.numpy``, nothing of the program) on seeded weights."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import models  # noqa: E402
from paddle_tpu.models import xing4 as X  # noqa: E402
from xing4_tiny_config import tiny_config  # noqa: E402

from benchmarks.lib import common  # noqa: E402

SEED = 1


@pytest.fixture(scope="module", autouse=True)
def _interpret():
    prev = paddle.get_flags("FLAGS_pallas_interpret")
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    yield
    paddle.set_flags(prev)


def built(dtype, seed=SEED, **sizes):
    cfg = tiny_config(dtype=dtype, **sizes)
    fam = common.load_family(cfg)
    model, _ = common.build_model(fam, cfg, seed)
    if dtype == "float32":           # the seed's bf16 leaves, held exactly
        for p in model.parameters():
            p._data = p._data.astype(jnp.float32)
    model.eval()
    return cfg, fam, model


def block(cfg, rows=2, length=48, seed=SEED):
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, cfg["vocab_size"], (rows, length)).astype(np.int32)
    gather = np.zeros((rows, length, 1), np.int32)
    gather[:, :-1, 0] = ids[:, 1:]
    return ids, gather


@pytest.fixture(scope="module")
def f32():
    return built("float32")


def test_forward_equals_reference_in_float32(f32):
    """Same leaves, same arithmetic in float32: only the order of the
    sums differs (expanded attention in blocks against one einsum, the
    experts by scan against a grouped matmul): 1e-5 of logits of 0.7."""
    cfg, fam, model = f32
    ids, gather = block(cfg)
    lg = model(paddle.to_tensor(ids)).numpy()
    best, arg, got = fam.serve_logits(cfg, SEED, ids, gather)
    assert np.abs(lg.max(-1) - best).max() < 1e-5
    assert np.abs(np.take_along_axis(lg, gather, -1) - got).max() < 1e-5
    assert (lg.argmax(-1) == arg).all()


def test_bf16_program_passes_where_int8_fails():
    """The program in bfloat16 against the float32 reference, and the
    reference in int8 against itself, as the root mean square gap of the
    best logit over 96 positions: bfloat16 reads 0.0011-0.0012 over three
    seeds, int8 0.0041-0.0048 (measured here, PR 30); the tolerance lies
    between, so a program computing a precision lower fails."""
    cfg, fam, model = built("bfloat16")
    ids, gather = block(cfg)
    lg = np.asarray(model(paddle.to_tensor(ids))._data.astype(jnp.float32))
    best, _, _ = fam.serve_logits(cfg, SEED, ids, gather)
    low, _, _ = fam.serve_logits(cfg, SEED, ids, gather, mode="int8")
    rms = lambda d: float(np.sqrt(np.mean(d * d)))          # noqa: E731
    tol = 0.0025
    assert rms(lg.max(-1) - best) < tol < rms(low - best)


def test_reference_does_not_judge_a_tight_selection(f32):
    """With ``assumed.router_margin`` the reference reports the gathered
    logit as the best at a position whose top-k selection is closer than
    the margin in some layer (served_gap reads 0 there) and leaves every
    other position as it was; ``position_logits`` gives the margins."""
    cfg, fam, _ = f32
    ids, gather = block(cfg)
    best, arg, got, margin = fam.position_logits(cfg, SEED, ids, gather)
    best1, arg1, got1 = fam.serve_logits(cfg, SEED, ids, gather)
    assert (best1 == best).all() and (arg1 == arg).all() \
        and (got1 == got).all()             # the tiny file's margin is 0
    tau = float(np.median(margin))
    tight = margin < tau
    assert tight.any() and not tight.all()
    cut = dict(cfg, assumed=dict(cfg["assumed"], router_margin=tau))
    best2, arg2, got2 = fam.serve_logits(cut, SEED, ids, gather)
    assert (arg2 == arg).all() and (got2 == got).all()
    assert (best2[~tight] == best[~tight]).all()
    assert (best2[tight] == got[..., 0][tight]).all()


def test_mtp_logits_equal_reference(f32):
    cfg, fam, model = f32
    ids, _ = block(cfg, length=24)
    got = model.mtp_logits(paddle.to_tensor(ids)).numpy()
    gather = np.zeros((2, 23, 1), np.int32)
    gather[:, :-1, 0] = ids[:, 2:]
    best, arg, picked = fam.mtp_logits(cfg, SEED, ids, gather)
    assert got.shape == (2, 23, cfg["vocab_size"])
    assert np.abs(got.max(-1) - best).max() < 1e-5
    assert np.abs(np.take_along_axis(got, gather, -1) - picked).max() < 1e-5
    assert (got.argmax(-1) == arg).all()


def test_sinkhorn_is_doubly_stochastic():
    rng = np.random.default_rng(0)
    m = jnp.exp(jnp.clip(jnp.asarray(
        rng.normal(0, 1, (64, 4, 4)), jnp.float32), -30, 30))
    out = np.asarray(X.sinkhorn(m, 20, 1e-6))
    assert np.abs(out.sum(-1) - 1).max() < 1e-3
    assert np.abs(out.sum(-2) - 1).max() < 1e-3
    assert (out >= 0).all()


def test_hc_site_equals_reference(f32):
    """Hres X + Hpost^T y with order-one coefficients (b and alpha far
    from their initial values, so that a transposed Hres or swapped pre
    and post would show) against the reference's ``site``."""
    cfg, fam, model = f32
    rng = np.random.default_rng(3)
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    xs = jnp.asarray(rng.normal(0, 1, (10, n, c)), jnp.float32)
    phi = jnp.asarray(rng.normal(0, 0.05, (n * c, 2 * n + n * n)),
                      jnp.float32)
    b = jnp.asarray(rng.normal(0, 1, (2 * n + n * n,)), jnp.float32)
    alpha = jnp.asarray([0.7, 0.4, 0.9], jnp.float32)
    gain = jnp.asarray(1 + 0.1 * rng.normal(0, 1, (c,)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.2, (c, c)), jnp.float32)
    pcfg = model.config
    h, post, res = X.hc_pre(xs, phi, b, alpha, gain, pcfg)
    got = X.hc_post(xs, h @ w, post, res)
    want = fam.site(xs[None], lambda hh: (hh @ w, None), phi, b, alpha,
                    gain, cfg, "f32")[0][0]
    assert np.abs(np.asarray(res).sum(-1) - 1).max() < 1e-3
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
    read = X.hc_reduce(xs, phi[:, :n], b[:n], alpha[:1], pcfg)
    want = fam.last_read(xs[None], phi[:, :n], b[:n], alpha[:1], cfg, "f32")
    assert np.abs(np.asarray(read) - np.asarray(want[0])).max() < 1e-4


@pytest.mark.parametrize("picked", [(0, 1), (5, 2)])
def test_routed_experts_drop_nothing_under_a_forced_router(picked):
    """Every token sent to the same two experts (the selection bias
    forces it): the layer equals the per-token sum over exactly those
    experts, whatever the imbalance, and counts every assignment."""
    from paddle_tpu.incubate.distributed.models.moe import DroplessMoE

    paddle.seed(0)
    d, f, e, k, n = 32, 16, 8, 2, 37
    layer = DroplessMoE(d, f, e, k, num_shared=1, routed_scaling_factor=2.0,
                        init_std=0.3)
    bias = np.zeros(e, np.float32)
    bias[list(picked)] = 10.0
    layer.e_score_correction_bias._data = jnp.asarray(bias)
    x = np.random.default_rng(0).normal(0, 1, (n, d)).astype(np.float32)
    y = layer(paddle.to_tensor(x)).numpy()
    sizes = np.asarray(layer.last_group_sizes)
    assert sizes.sum() == n * k and sorted(np.nonzero(sizes)[0]) == \
        sorted(picked) and (sizes[list(picked)] == n).all()
    wr = np.asarray(layer.gate_weight._data)
    s = 1 / (1 + np.exp(-(x @ wr)))[:, list(picked)]
    w = s / s.sum(-1, keepdims=True) * 2.0

    def expert(i):
        g, u, dn = (np.asarray(p._data)[i] for p in (
            layer.experts_gate, layer.experts_up, layer.experts_down))
        a = x @ g
        return (a / (1 + np.exp(-a)) * (x @ u)) @ dn

    a = x @ np.asarray(layer.shared_gate._data)
    want = (a / (1 + np.exp(-a)) * (x @ np.asarray(
        layer.shared_up._data))) @ np.asarray(layer.shared_down._data)
    for j, i in enumerate(picked):
        want = want + w[:, j:j + 1] * expert(i)
    assert np.abs(y - want).max() < 1e-4


def test_lazyguard_allocates_nothing_and_fills_to_the_eager_model():
    cfg = tiny_config()
    fam = common.load_family(cfg)
    lazy = fam.build(cfg)
    assert all(isinstance(p._data, jax.ShapeDtypeStruct)
               for p in lazy.parameters())
    assert lazy.lm_head.weight.shape == [cfg["hidden_size"],
                                         cfg["vocab_size"]]
    paddle.seed(0)
    eager = models.Xing4ForCausalLM(models.xing4_tiny(
        **cfg["program"]["constructor_args"]))
    for (k, p), (k2, q) in zip(lazy.named_parameters(),
                               eager.named_parameters()):
        assert k == k2 and p.shape == q.shape and p.dtype == q.dtype
        p._data = q._data
    ids, _ = block(cfg, length=16)
    lazy.eval(), eager.eval()
    a = lazy(paddle.to_tensor(ids)).numpy()
    assert np.array_equal(a, eager(paddle.to_tensor(ids)).numpy())
    # outside the guard nothing changed
    assert isinstance(paddle.nn.Linear(2, 2).weight._data, jax.Array)


def test_build_refuses_a_size_the_file_does_not_state():
    cfg = tiny_config()
    cfg["kv_lora_rank"] = 48
    with pytest.raises(SystemExit, match="kv_lora_rank"):
        common.load_family(cfg).build(cfg)


def test_counts_of_the_published_cut():
    """The arithmetic of ISSUE 30 from the family's counts, at the cell's
    own configuration."""
    import json

    with open(os.path.join(common.BENCH_DIR, "configs",
                           "xing4-29b-a4b-serve.json")) as f:
        cfg = json.load(f)
    fam = common.load_family(cfg)
    n = sum(int(np.prod(s)) for s, _ in fam.leaves(cfg).values())
    assert abs(2 * n / 1e9 - 8.09) < 0.01          # GB of bfloat16
    assert fam.attention_flops_per_pair(cfg) == 69632
    assert fam.expert_params(cfg) * 2 == 22020096  # bytes of one expert
    ops, byts = fam.moe_matmul_call(cfg, 256, 63)
    assert ops == 6 * 3584 * 1024 * 256
    ops, byts = fam.latent_attention_call(cfg, 64, 64000, 64000)
    assert byts == 1152 * 64000 + 32 * (2 * 512 + 64) * 2 * 64
