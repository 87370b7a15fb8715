"""SDAR-MoE (models/sdar.py) served block by block: the model's clean
forward, the softmax router and the drop-free layer without a shared
expert, the ragged kernel under the block-causal mask, and generation by
diffusion over blocks through ``BatchScheduler`` -> ``PagedLlamaAdapter``,
each against the benchmark's plain reference (benchmarks/families/sdar.py,
nothing of the program imported there) or a plain ``jnp`` expression.

The seed's leaves are bfloat16 numbers; here they are handed to the
program as float32, so program and float32 reference hold the same
weights and agree to rounding: orders and argmaxes are then compared
exactly wherever the reference's own margins are not rounding-thin."""
import contextlib
import importlib
import json
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
from benchmarks.lib import common  # noqa: E402
from benchmarks.lib import reference as R  # noqa: E402
from benchmarks.lib import weights as W  # noqa: E402
from paddle_tpu.inference import BatchScheduler, Request  # noqa: E402
from sdar_tiny_config import tiny_config  # noqa: E402

SEED = 2**31 + 5
B = 4


@pytest.fixture(scope="module", autouse=True)
def _interpret():
    prev = paddle.get_flags("FLAGS_pallas_interpret")
    paddle.set_flags({"FLAGS_pallas_interpret": True})
    yield
    paddle.set_flags(prev)


@pytest.fixture(scope="module")
def fam():
    return common.load_family(tiny_config())


def build(fam, cfg):
    """The program's model with the seed's leaves as float32."""
    model, _ = common.build_model(fam, cfg, SEED)
    for p in model.parameters():
        p._data = p._data.astype(jnp.float32)
    return model.eval()


def ref_leaves(fam, cfg):
    spec = W.spec(fam.leaves(cfg), fam.LEAF_NAMES, cfg["initializer_range"])
    return R.f32(W.make_all(spec, SEED))


# -- the model ---------------------------------------------------------------
def test_clean_forward_is_the_references_clean_pass(fam):
    """ids -> logits of the model's full-sequence forward (causal across
    blocks, bidirectional inside one, no shift) against the family's
    layer, one sequence at a time."""
    cfg = tiny_config()
    model = build(fam, cfg)
    ids = np.random.default_rng(0).integers(1, 255, (2, 22)).astype(np.int32)
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    tree = ref_leaves(fam, cfg)
    with jax.default_matmul_precision("highest"):
        for r in range(2):
            x = tree["embed"][ids[r]]
            for lw in tree["layers"]:
                x, _ = fam.layer(x, lw, jnp.arange(22), cfg, "f32")
            want = R.mm(R.rms(x, tree["norm"], cfg["rms_norm_eps"]),
                        tree["head"], "f32")
            np.testing.assert_allclose(got[r], np.asarray(want), atol=2e-4)


def test_a_token_sees_its_whole_block_and_no_later_one(fam):
    """Changing the last token of a block moves the logits of the block's
    FIRST position (bidirectional inside), changing the first token of
    the NEXT block does not (causal across)."""
    model = build(fam, tiny_config())
    ids = np.random.default_rng(1).integers(1, 255, (1, 12))
    base = np.asarray(model(paddle.to_tensor(ids))._data)
    a, b = ids.copy(), ids.copy()
    a[0, 7] = (a[0, 7] + 1) % 255 + 1
    b[0, 8] = (b[0, 8] + 1) % 255 + 1
    la = np.asarray(model(paddle.to_tensor(a))._data)
    lb = np.asarray(model(paddle.to_tensor(b))._data)
    assert np.abs(la[0, 4] - base[0, 4]).max() > 1e-3
    assert np.abs(lb[0, :8] - base[0, :8]).max() == 0


def test_the_config_refuses_what_the_layer_does_not_have():
    from paddle_tpu.models import SDARMoeConfig, sdar_30b_a3b

    cfg = sdar_30b_a3b()
    assert (cfg.hidden_size, cfg.head_dim, cfg.num_experts,
            cfg.num_experts_per_tok, cfg.vocab_size) == \
        (2048, 128, 128, 8, 151936)
    for kw in ({"mlp_only_layers": [0]}, {"decoder_sparse_step": 2},
               {"block_length": 3}, {"mask_token_id": 151936}):
        with pytest.raises(ValueError):
            SDARMoeConfig(**kw)


# -- the router and the drop-free layer ---------------------------------------
@pytest.mark.parametrize("norm_topk", (True, False))
def test_route_softmax(norm_topk):
    from paddle_tpu.incubate.distributed.models.moe.dropless import route

    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(9, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    idx, wt = route(x, w, None, 3, norm_topk=norm_topk, scoring="softmax")
    r = jax.nn.softmax(jnp.matmul(x, w, precision="highest"), -1)
    want_w, want_i = jax.lax.top_k(r, 3)
    if norm_topk:
        want_w = want_w / want_w.sum(-1, keepdims=True)
    assert np.array_equal(np.asarray(idx), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(wt), np.asarray(want_w), rtol=1e-6)
    with pytest.raises(KeyError):
        route(x, w, None, 3, scoring="tanh")


def test_dropless_moe_without_a_shared_expert_is_the_references(fam):
    from paddle_tpu.incubate.distributed.models.moe import DroplessMoE

    with pytest.raises(ValueError, match="scoring"):
        DroplessMoE(8, 4, 4, 2, scoring="tanh")
    cfg = tiny_config()
    paddle.seed(3)
    moe = DroplessMoE(64, 32, 8, 2, num_shared=0, scoring="softmax",
                      init_std=0.3)
    names = [n for n, _ in moe.named_parameters()]
    assert names == ["gate_weight", "experts_gate", "experts_up",
                     "experts_down"]            # no bias, nothing shared
    x = np.random.default_rng(4).normal(size=(3, 5, 64)).astype(np.float32)
    got = np.asarray(moe(paddle.to_tensor(x))._data)
    lw = {"router": moe.gate_weight._data, "e_gate": moe.experts_gate._data,
          "e_up": moe.experts_up._data, "e_down": moe.experts_down._data}
    with jax.default_matmul_precision("highest"):
        want = fam.experts(jnp.asarray(x.reshape(15, 64)), lw, cfg, "f32")
    np.testing.assert_allclose(got.reshape(15, 64), np.asarray(want),
                               atol=2e-5)
    assert int(moe.last_group_sizes.sum()) == 15 * 2


# -- the kernel ----------------------------------------------------------------
def _kernel_case(rng, lens, q_lens, t, h=4, kvh=2, d=16, page=16, npages=24):
    b = len(lens)
    mp = max(-(-n // page) for n in lens)
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(npages, page, kvh, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(npages, page, kvh, d)), jnp.float32)
    tbl = jnp.asarray(rng.permutation(npages)[:b * mp].reshape(b, mp),
                      jnp.int32)
    return (q, kp, vp, tbl, jnp.asarray(lens, jnp.int32),
            jnp.asarray(q_lens, jnp.int32))


def _dense_block_attention(q, kp, vp, tbl, lens, q_lens, block):
    """A plain expression: row i's token r sits at lens[i] - t + r and sees
    every key up to the end of its own block (as far as the row goes)."""
    b, t, h, d = q.shape
    g = h // kp.shape[2]
    out = np.zeros((b, t, h, d), np.float32)
    for i in range(b):
        n = int(lens[i])
        ks = np.asarray(kp)[np.asarray(tbl[i])].reshape(-1, *kp.shape[2:])[:n]
        vs = np.asarray(vp)[np.asarray(tbl[i])].reshape(-1, *vp.shape[2:])[:n]
        for r in range(t - int(q_lens[i]), t):
            pos = n - t + r
            hi = min((pos // block + 1) * block, n) if block else pos + 1
            for j in range(h):
                s = ks[:hi, j // g] @ np.asarray(q[i, r, j]) / np.sqrt(d)
                p = np.exp(s - s.max())
                out[i, r, j] = (p / p.sum()) @ vs[:hi, j // g]
    return out


@pytest.mark.parametrize("block", (0, 4))
@pytest.mark.parametrize("t,lens,q_lens", [
    (4, [20, 36, 8, 0], [4, 4, 4, 0]),            # decode rows of one block
    (16, [20, 48, 16, 40], [4, 16, 12, 4]),       # beside prompt chunks
    (8, [23, 10], [7, 2]),                        # a row that ends inside one
])
def test_ragged_kernel_and_reference_under_the_block_mask(block, t, lens,
                                                          q_lens):
    pa = importlib.import_module("paddle_tpu.ops.kernels.paged_attention")

    args = _kernel_case(np.random.default_rng(5), lens, q_lens, t)
    q, kp, vp, tbl, ln, ql = args
    want = _dense_block_attention(*args, block)
    ref = pa.paged_ragged_attention_reference(q, kp, vp, tbl, ln, ql,
                                              block=block)
    got = pa.paged_ragged_attention(q, kp, vp, tbl, ln, q_lens=ql,
                                    block=block, interpret=True)
    np.testing.assert_allclose(ref, want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


def test_block_0_is_the_kernel_it_was_and_block_3_is_refused():
    pa = importlib.import_module("paddle_tpu.ops.kernels.paged_attention")

    q, kp, vp, tbl, ln, ql = _kernel_case(np.random.default_rng(6),
                                          [20, 36], [4, 4], 4)
    cfg = (2, 4, 4, 16, 24, 16, 2, 3, 0.25, 0, False, True, True)
    old = jax.make_jaxpr(pa._build_ragged_call(*cfg))(q, kp, vp, tbl, ln, ql)
    new = jax.make_jaxpr(pa._build_ragged_call(*cfg, 0))(
        q, kp, vp, tbl, ln, ql)
    assert str(old) == str(new)
    assert str(old) != str(jax.make_jaxpr(pa._build_ragged_call(*cfg, 4))(
        q, kp, vp, tbl, ln, ql))
    with pytest.raises(ValueError, match="power of two"):
        pa.paged_ragged_attention(q, kp, vp, tbl, ln, q_lens=ql, block=3)


# -- generation ----------------------------------------------------------------
@contextlib.contextmanager
def telemetry_mode(mode):
    """``FLAGS_telemetry`` at ``mode`` over a fresh registry and ring."""
    from paddle_tpu.framework import telemetry

    prev = paddle.get_flags("FLAGS_telemetry")
    paddle.set_flags({"FLAGS_telemetry": mode})
    telemetry.reset()
    try:
        yield telemetry
    finally:
        paddle.set_flags(prev)
        telemetry.reset()


def generate(fam, prompts, max_new, sched_kw=None, model=None, cfg=None,
             eos=None):
    """Run the requests through BatchScheduler; returns (cfg, finished
    requests, the scheduler, what each denoising pass saw: {req: [(block,
    pass, masked before, masked after, choice [B, 3])]}, the rows of every
    model call, the streams)."""
    cfg = cfg or tiny_config(scheduler=sched_kw)
    model = model or build(fam, cfg)
    sched = BatchScheduler(fam.serving(model, cfg),
                           **cfg["program"]["scheduler"])
    passes, calls, inner, call = {}, [], sched._unmask, \
        sched.model.prefill_chunk
    blocks = {}                            # req -> its blocks, in order

    def spy(blk, choice):
        before, n_pass = list(blk.masked), blk.passes
        n = inner(blk, choice)
        rid = next(r.req_id for r in sched._active.values()
                   if r._block is blk)
        mine = blocks.setdefault(rid, [])
        if not any(b is blk for b in mine):
            mine.append(blk)
        passes.setdefault(rid, []).append(
            (len(mine) - 1, n_pass, before, list(blk.masked),
             np.array(choice)))
        return n

    def spy_call(feeds, rows, starts, **kw):
        calls.append([(r, s, len(f)) for f, r, s in zip(feeds, rows, starts)])
        return call(feeds, rows, starts, **kw)

    sched._unmask, sched.model.prefill_chunk = spy, spy_call
    streams = {}
    for i, (p, m) in enumerate(zip(prompts, max_new)):
        sched.submit(Request(
            f"r{i}", list(p), max_new_tokens=m, eos_id=eos,
            on_token=lambda r, t, pr: streams.setdefault(
                r.req_id, []).append((int(t), pr))))
    done = sched.run_until_complete()
    return cfg, done, sched, passes, calls, streams


def check_against_replay(fam, cfg, req, prompt, passes, wide=1e-3):
    """The request's delivered tokens, the order in which its positions
    were fixed and each fixing pass's best logit against the reference's
    replay. Where the reference's own order margin is thinner than
    ``wide`` the block's order may rightly differ (float32 against
    float32 at another summation order) and only its tokens are
    compared."""
    gen = req.generated_ids
    seq = list(prompt) + gen
    tail = req._block.ids[len(seq) % B:] if len(seq) % B else []
    seq = seq + tail                       # the block the program held
    ids = np.asarray([seq], np.int32)
    best, arg, got, info = fam.position_logits(
        cfg, SEED, ids, ids[..., None], prompt_lens=[len(prompt)],
        lens=[len(seq)])
    at = np.arange(len(prompt), len(prompt) + len(gen))
    first = len(prompt) - len(prompt) % B
    # the program's own record: position -> (pass, its best logit)
    mine = {}
    for blk, n_pass, before, after, choice in passes:
        for i in range(B):
            if before[i] and not after[i]:
                mine[first + blk * B + i] = (n_pass, float(choice[i, 1]))
    sure = info["order_margin"][0] >= wide
    for t in at:
        assert int(arg[0, t]) == seq[t] or \
            best[0, t] - got[0, t, 0] < 1e-4, (t, arg[0, t], seq[t])
        if sure[t]:
            assert mine[t][0] == info["passes"][0, t], (t, mine[t])
            assert abs(mine[t][1] - best[0, t]) < 5e-4, (t, mine[t])
    return info


RULES = [("sequential", 4), ("sequential", 2), ("low_confidence_static", 1),
         ("low_confidence_static", 2), ("low_confidence_static", 4),
         ("low_confidence_dynamic", 4), ("low_confidence_dynamic", 2)]


@pytest.mark.parametrize("rule,steps", RULES)
def test_generation_is_the_references_replay(fam, rule, steps):
    """Prompts that end on and inside a block (and one shorter than a
    block), answers that end on and inside one, more requests than slots,
    prompt chunks of 8 beside decode rows: every delivered token is the
    reference's best at its position in the pass that fixed it, the
    positions were fixed in the reference's order, and each fixing pass's
    logit is the reference's."""
    rng = np.random.default_rng(7)
    lens, max_new = (9, 16, 3, 22, 13, 8), (12, 8, 7, 10, 5, 16)
    prompts = [rng.integers(1, 255, n).tolist() for n in lens]
    kw = {"denoising_steps": steps, "remasking": rule,
          "prefill_chunk_tokens": 8, "confidence_threshold": 0.03}
    cfg, done, sched, passes, calls, streams = generate(
        fam, prompts, max_new, kw)
    # a request's blocks: from the one its prompt ends in to its last
    # token's; every one delivered, every one but the last carried by the
    # next one's first pass, and no pass fed a finished block alone
    n_blocks = sum(-(-(len(p) % B + m) // B) for p, m in zip(prompts,
                                                             max_new))
    st = sched.block_stats
    assert (st["blocks_committed"], st["commits_carried"]) == \
        (n_blocks, n_blocks - len(prompts))
    assert "commit_passes" not in st
    assert n_blocks <= st["denoise_passes"] <= steps * n_blocks
    for i, (p, m) in enumerate(zip(prompts, max_new)):
        req = done[f"r{i}"]
        assert len(req.generated_ids) == m
        info = check_against_replay(fam, cfg, req, p, passes[f"r{i}"])
        assert info["passes"].max() < steps
        # the stream: the prompt in order, then the answer, B at a time
        assert streams[f"r{i}"] == [(t, True) for t in p] + \
            [(t, False) for t in req.generated_ids]
    # every row of every call resumes on a block boundary and a chunk ends
    # on one: the budget of 8 is two whole blocks, and a decode row feeds
    # its open block or, with the finished one before it, two
    for call in calls:
        for rid, start, n in call:
            assert start % B == 0 and n % B == 0 and n <= 8, call


def test_static_at_one_pass_fixes_the_whole_block_at_once(fam):
    rng = np.random.default_rng(8)
    cfg, done, sched, passes, calls, _ = generate(
        fam, [rng.integers(1, 255, 8).tolist()], [8],
        {"denoising_steps": 1, "remasking": "low_confidence_static"})
    assert [sum(b) - sum(a) for _, _, b, a, _ in passes["r0"]] == [4, 4]
    st = sched.block_stats
    assert (st["denoise_passes"], st["commits_carried"],
            st["tokens_unmasked"], st["blocks_committed"]) == (2, 1, 8, 2)
    # the prompt, the first block, and the second behind the first
    assert calls == [[("r0", 0, 8)], [("r0", 8, 4)], [("r0", 8, 8)]]


def _scheduler(fam, sched_kw=None):
    cfg = tiny_config(scheduler=sched_kw)
    sched = BatchScheduler(fam.serving(build(fam, cfg), cfg),
                           **cfg["program"]["scheduler"])
    return cfg, sched


@pytest.mark.parametrize("prompt_len", (8, 10))
def test_a_carried_pass_leaves_the_references_clean_kv_in_the_pages(
        fam, prompt_len):
    """After the pass that carries a finished block the pools stand at the
    committed length + B, and what the pages hold up to there, the
    finished block's K/V with it, is the reference's clean pass over the
    row's tokens; the open block's slots were rolled back."""
    cfg, sched = _scheduler(fam)
    prompt = np.random.default_rng(12).integers(1, 255, prompt_len).tolist()
    sched.submit(Request("a", prompt, max_new_tokens=12))
    base = prompt_len - prompt_len % B         # the prompt's whole blocks
    sched.step()                               # the prompt's whole blocks
    while sched.block_stats["commits_carried"] < 1:
        assert all(c.seq_len("a") == base for c in sched.model.caches)
        sched.step()
    req = sched._active["a"]
    gen = req.generated_ids
    assert len(gen) == base + B - prompt_len and req._block.behind == []
    assert [c.seq_len("a") for c in sched.model.caches] == [base + B] * 2
    seq = np.asarray(prompt + gen, np.int32)
    tree = ref_leaves(fam, cfg)
    with jax.default_matmul_precision("highest"):
        x = tree["embed"][seq]
        for lw, cache in zip(tree["layers"], sched.model.caches):
            x, (k, v) = fam.layer(x, lw, jnp.arange(len(seq)), cfg, "f32")
            tbl = np.asarray(cache._tables["a"])
            for pages, want in ((cache.k_pages, k), (cache.v_pages, v)):
                got = np.asarray(pages)[tbl].reshape(-1, *want.shape[1:])
                np.testing.assert_allclose(got[:len(seq)], np.asarray(want),
                                           atol=2e-5)
    # the next step feeds the open block alone, at the new length
    sched.step()
    assert sched.block_stats["commits_carried"] == 1
    assert all(c.seq_len("a") == base + B for c in sched.model.caches)


@pytest.mark.parametrize("prompt_len,max_new,ends,blocks", [
    (8, 8, False, 2),           # the answer ends on a block boundary
    (8, 6, False, 2),           # inside a block
    (10, 5, False, 2),          # a prompt's tail, an end inside a block
    (8, 12, True, 2),           # an EOS inside the second block of three
    (8, 3, False, 1),           # one block: nothing is ever carried
])
def test_a_requests_last_block_is_never_committed(fam, prompt_len, max_new,
                                                  ends, blocks):
    """The pass that fixes the last block's last position delivers it and
    the request retires: no pass follows to write that block's K/V, and
    the carried passes are the blocks but the last."""
    prompt = np.random.default_rng(16).integers(1, 255, prompt_len).tolist()
    eos, want = None, max_new
    if ends:
        _, done, _, _, _, _ = generate(fam, [prompt], [max_new])
        gen = done["r0"].generated_ids
        eos = next(t for t in gen[B:2 * B] if t not in gen[:B])
        want = gen.index(eos) + 1
    with telemetry_mode("metrics"):
        _, done, sched, passes, calls, _ = generate(
            fam, [prompt], [max_new], eos=eos)
        snap = sched.metrics()["diffusion"]
    assert len(done["r0"].generated_ids) == want
    st = sched.block_stats
    assert (st["blocks_committed"], st["commits_carried"]) == \
        (blocks, blocks - 1)
    assert snap["commits_carried"] == blocks - 1
    assert "commit_passes" not in snap
    # the last call is the last block's last denoising pass, and the rows
    # of two blocks are the first passes of the blocks behind the first
    assert len(calls) == 1 + len(passes["r0"])
    assert sum(n == 2 * B for c in calls[1:] for _, _, n in c) == blocks - 1
    assert sched.model.caches[0].num_free_pages == \
        sched.model.caches[0].num_pages


@pytest.mark.parametrize("steps,row_passes,carried", [(1, 3, 2), (4, 12, 2)])
def test_every_pass_carries_at_one_step_and_one_in_four_at_four(
        fam, steps, row_passes, carried):
    """T = 1: a block a pass, every pass but a request's first a row of two
    blocks. T = B: a token a pass, the first of four carries."""
    prompt = np.random.default_rng(13).integers(1, 255, 8).tolist()
    cfg, done, sched, passes, calls, _ = generate(
        fam, [prompt], [12],
        {"denoising_steps": steps, "remasking": "sequential"})
    check_against_replay(fam, cfg, done["r0"], prompt, passes["r0"])
    st = sched.block_stats
    assert (st["denoise_passes"], st["commits_carried"],
            st["blocks_committed"]) == (row_passes, carried, 3)
    fed = [n for c in calls[1:] for _, _, n in c]
    assert fed == [B] * steps + ([2 * B] + [B] * (steps - 1)) * 2


def test_rows_in_different_passes_beside_a_prompt_chunk(fam):
    """One step of a row of two blocks (its open block's first pass), a
    row of one (a later pass) and a prompt chunk of 16: the multi-token
    pad is a block where every row feeds one, two where some row feeds
    two, the chunk's beyond; the packed width is the bucket of what was
    fed; and the ``kernel.ragged`` span's counts are exact, a fed token
    paired with every row up to its own block's end."""
    rng = np.random.default_rng(14)
    prompts = [rng.integers(1, 255, n).tolist() for n in (4, 20, 40)]
    with telemetry_mode("trace") as telemetry:
        cfg, done, sched, passes, calls, _ = generate(fam, prompts,
                                                      [12, 12, 4])
        spans = telemetry.tracer().spans()
    ragged = [s.attrs for s in spans if s.name == "kernel.ragged"][::2]
    packs = [s.attrs for s in spans if s.name == "serving.pack"]
    assert len(ragged) == len(packs) == len(calls)
    mixed = 0
    for call, rg, pk in zip(calls, ragged, packs):
        ns = [n for _, _, n in call]
        top = max(ns)
        assert rg["t"] == (B if top <= B else 2 * B if top <= 2 * B else 16)
        assert (pk["packed"], pk["pad_to"]) == \
            (sum(ns), 16 if sum(ns) <= 16 else 32)
        pairs = sum((p // B + 1) * B for _, start, n in call
                    for p in range(start, start + n))
        assert (rg["fed"], rg["kv_rows"], rg["pairs"]) == \
            (sum(ns), sum(st + n for _, st, n in call), pairs)
        mixed += sorted(ns) == [B, 2 * B, 16]
    assert mixed                          # the step the test is named for
    for i, p in enumerate(prompts):
        check_against_replay(fam, cfg, done[f"r{i}"], p, passes[f"r{i}"])


def test_a_listed_row_is_one_block_or_two(fam):
    cfg = tiny_config()
    ad = fam.serving(build(fam, cfg), cfg)
    ad.alloc("x")
    for feed in ([1] * 12, [1, 2], [1] * 6):
        with pytest.raises(ValueError, match="one or two blocks of "
                                             "block_length=4"):
            ad.prefill_chunk([feed], ["x"], [0], pad_to=16, choose_rows=[0])
    assert ad.caches[0].seq_len("x") == 0          # refused before booking
    out = ad.prefill_chunk([[1] * 8], ["x"], [0], pad_to=16, choose_rows=[0])
    assert np.asarray(out).shape == (B, 3)         # the last block's choice


def test_after_warm_a_generation_of_mixed_rows_builds_nothing(fam):
    """``BatchScheduler.warm`` builds every (packed width, multi-token
    pad, table width) that rows of one block, of two, and a prompt chunk
    beside them can meet: a generation then adds no packed shape, no
    kernel shape and no layer program."""
    pa = importlib.import_module("paddle_tpu.ops.kernels.paged_attention")

    cfg, sched = _scheduler(fam)
    ad = sched.model
    sched.warm()
    assert float(jnp.abs(ad.caches[0].k_pages).max()) == 0.0
    # widths 16 / 32 / 64 (4 rows x 4, x 8, + the chunk of 16), each with
    # the pads rows that short can fill it with, at tables of 16 pages
    assert {(n, t) for _, _, t, _, n in ad._kernel_shapes} == {
        (16, 4), (16, 8), (32, 8), (16, 16), (32, 16), (64, 16)}
    before = (ad.compile_count, ad.attend_program_count,
              pa._jitted_layer_step.cache_info().misses)
    rng = np.random.default_rng(15)
    for i, (n, m) in enumerate(((4, 12), (20, 12), (40, 8), (9, 16),
                                (16, 8), (30, 12))):
        sched.submit(Request(f"r{i}", rng.integers(1, 255, n).tolist(),
                             max_new_tokens=m))
    done = sched.run_until_complete()
    assert all(len(done[f"r{i}"].generated_ids) == m
               for i, m in enumerate((12, 12, 8, 16, 8, 12)))
    assert sched.block_stats["commits_carried"] > 0
    assert (ad.compile_count, ad.attend_program_count,
            pa._jitted_layer_step.cache_info().misses) == before


def test_the_mask_id_is_never_chosen_and_a_prompts_mask_id_stays(fam):
    """The MASK id's logit is left out of the choice: with the program's
    head column of the MASK id at 50 times its size (the largest logit at
    about half the positions) no delivered token equals it, and the
    tokens, orders and logits are still the reference's (which leaves it
    out too, so the column never enters). A
    prompt token that equals the MASK id, in a whole block and in the tail
    that shares the first generated block, is a token: masked-ness is the
    row's own state."""
    cfg = tiny_config()
    model = build(fam, cfg)
    mask = cfg["assumed"]["mask_token_id"]
    head = model.lm_head.weight
    head._data = head._data.at[:, mask].multiply(50.0)
    rng = np.random.default_rng(9)
    prompt = rng.integers(1, 255, 10).tolist()
    prompt[2], prompt[9] = mask, mask
    cfg, done, sched, passes, _, _ = generate(
        fam, [prompt], [9], model=model, cfg=cfg)
    req = done["r0"]
    assert mask not in req.generated_ids
    check_against_replay(fam, cfg, req, prompt, passes["r0"])
    # the first block was opened with the tail in place, MASK id and all
    assert passes["r0"][0][2] == [False, False, True, True]


def test_an_eos_inside_a_block_delivers_up_to_it(fam):
    rng = np.random.default_rng(10)
    prompt = rng.integers(1, 255, 8).tolist()
    _, done, _, _, _, _ = generate(fam, [prompt], [12])
    gen = done["r0"].generated_ids
    eos = gen[5]
    _, done, _, _, _, _ = generate(fam, [prompt], [12], eos=eos)
    cut = gen.index(eos) + 1
    assert done["r0"].generated_ids == gen[:cut]


def test_a_prompt_that_is_only_scored_is_fed_whole(fam):
    cfg = tiny_config()
    sched = BatchScheduler(fam.serving(build(fam, cfg), cfg),
                           **cfg["program"]["scheduler"])
    sched.submit(Request("s", list(range(1, 11)), max_new_tokens=0))
    done = sched.run_until_complete()
    assert done["s"].generated_ids == [] and done["s"]._pos == 10


# -- what the scheduler says and refuses -----------------------------------------
def test_refusals_by_name(fam):
    from paddle_tpu.inference.paged_llama import PagedLlamaAdapter

    cfg = tiny_config()
    model = build(fam, cfg)
    ad = fam.serving(model, cfg)
    for kw, word in (({"prefix_cache": True}, "prefix cache"),
                     ({"draft_model": ad}, "speculative draft"),
                     ({"preempt": True}, "preemption"),
                     ({"chunked_prefill": False}, "chunked_prefill=False"),
                     ({"remasking": "random"}, "remasking="),
                     ({"denoising_steps": 5}, "denoising_steps=5"),
                     ({"denoising_steps": 0}, "denoising_steps=0"),
                     ({"confidence_threshold": 0.0}, "no probability")):
        with pytest.raises(ValueError, match=word):
            BatchScheduler(ad, **kw)
    for kw, word in (({"page_size": 6}, "whole blocks"),
                     ({"kv_cache_dtype": "int8"}, "float pages"),
                     ({"weight_dtype": "int8"}, "float weights")):
        with pytest.raises(ValueError, match=word):
            PagedLlamaAdapter(model, **{"num_pages": 8, "page_size": 16,
                                        **kw})
    # the card's command is the default; the sampler is never called
    sched = BatchScheduler(ad, sampler=lambda lg: 1 / 0)
    assert (sched.denoising_steps, sched.remasking,
            sched.confidence_threshold) == (4, "low_confidence_dynamic", 0.9)
    sched.submit(Request("a", [5, 6, 7, 8, 9], max_new_tokens=4))
    sched.run_until_complete()
    assert "not called" in sched.metrics()["sampler"]
    with pytest.raises(ValueError, match="boundary"):
        ad.alloc("x")
        ad.prefill_chunk([[1, 2]], ["x"], [0], pad_to=8)
        ad.prefill_chunk([[1, 2]], ["x"], [2], pad_to=8)


def test_a_dense_llama_keeps_its_step_and_ignores_the_three_knobs():
    from paddle_tpu.inference.paged_llama import PagedLlamaAdapter
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny

    paddle.seed(0)
    ad = PagedLlamaAdapter(LlamaForCausalLM(llama_tiny()).eval(),
                           num_pages=16, page_size=16)
    sched = BatchScheduler(ad, denoising_steps=2)
    assert sched.block_length == 0 and ad.block_length == 0
    sched.submit(Request("a", [1, 2, 3], max_new_tokens=3))
    assert len(sched.run_until_complete()["a"].generated_ids) == 3
    assert "sampler" not in sched.metrics()


def test_spans_and_counters_of_the_block_step(fam):
    """``serving.block`` a step with the row-passes and what they gave,
    ``kernel.ragged`` with ``block`` and the call's exact counts,
    ``moe.counts`` riding the step's pull, ``serving.logits_pull`` with
    its bytes, the ``diffusion.*`` counters."""
    from paddle_tpu.framework import telemetry

    with telemetry_mode("trace"):
        cfg, sched = _scheduler(fam)
        sched.submit(Request("a", list(range(1, 13)), max_new_tokens=8))
        sched.run_until_complete()
        spans = telemetry.tracer().spans()
        snap = sched.metrics()
    blocks = [s.attrs for s in spans if s.name == "serving.block"]
    # 1 prompt step (12 tokens in one chunk of 16), then 2 blocks of
    # 2 denoising passes, the second block's first carrying the first
    assert [(a["denoise_rows"], a["carried_rows"], a["unmasked"],
             a["delivered"]) for a in blocks] == \
        [(0, 0, 0, 0), (1, 0, 2, 0), (1, 0, 2, 4), (1, 1, 2, 0),
         (1, 0, 2, 4)]
    assert not any("commit_rows" in a for a in blocks)
    ragged = [s.attrs for s in spans if s.name == "kernel.ragged"]
    assert all(a["block"] == B for a in ragged)
    # the layer program's key names its three switches of a block model
    keys = [s.attrs["key"] for s in spans if s.name == "model.layer"]
    assert keys and all(k.startswith("layer n")
                        and k.endswith(" block qk_norm router") for k in keys)
    heads = {s.attrs["key"].split()[0] for s in spans
             if s.name == "model.head"}
    assert heads <= {"choose", "head"} and "choose" in heads
    first = ragged[0]                      # the prompt: 12 tokens, 3 blocks
    assert (first["fed"], first["kv_rows"], first["pairs"]) == \
        (12, 12, 4 * (4 + 8 + 12))
    dec = ragged[2]                        # a layer of the first decode pass
    assert (dec["fed"], dec["kv_rows"], dec["pairs"], dec["t"]) == \
        (4, 16, 64, B)
    two = ragged[6]                        # the pass that carries a block
    assert (two["fed"], two["kv_rows"], two["pairs"], two["t"]) == \
        (8, 20, 4 * (16 + 20), 2 * B)
    pulls = [s.attrs for s in spans if s.name == "serving.logits_pull"]
    assert pulls and all(a["bytes"] == 0 or a["bytes"] == B * 3 * 4
                         for a in pulls)
    counts = [s.attrs for s in spans if s.name == "moe.counts"]
    assert counts and all(a["calls"] == 2 for a in counts)
    # a decode pass routes 4 tokens to 2 experts each in each of 2 layers
    assert counts[1]["assignments"] == 2 * 4 * 2
    assert counts[3]["assignments"] == 2 * 8 * 2     # and 8 where it carries
    d = snap["diffusion"]
    assert (d["denoise_passes"], d["commits_carried"],
            d["tokens_unmasked"], d["blocks_committed"]) == (4, 1, 8, 2)
    assert "commit_passes" not in d
    surface = {n for n, _, _ in telemetry.SURFACE}
    assert surface >= {
        "diffusion.denoise_passes", "diffusion.commits_carried",
        "diffusion.tokens_unmasked", "diffusion.blocks_committed"}
    assert "diffusion.commit_passes" not in surface


def test_transfer_counts_and_choose_of_the_reference(fam):
    assert fam.transfer_counts(4, 4) == [1, 1, 1, 1]
    assert fam.transfer_counts(4, 3) == [2, 1, 1]
    assert fam.transfer_counts(4, 1) == [4]
    lp = np.log(np.array([0.5, 0.1, 0.4, 0.2]))
    m = np.array([True, True, True, False])
    pick = lambda *a: fam.choose(lp, m, *a)[0].tolist()       # noqa: E731
    assert pick(2, "low_confidence_static", 0.9) == [0, 2]
    assert pick(2, "sequential", 0.9) == [0, 1]
    assert pick(1, "low_confidence_dynamic", 0.3) == [0, 2]
    assert pick(1, "low_confidence_dynamic", 0.9) == [0]
    assert fam.choose(lp, m, 2, "low_confidence_static", 0.9)[1] == \
        pytest.approx(np.log(0.4 / 0.1))
    with pytest.raises(SystemExit, match="no remasking rule"):
        fam.choose(lp, m, 1, "random", 0.9)


@pytest.mark.parametrize("floor", (0.01, 1.0))
def test_loss_share_sums_the_tail_of_every_row(fam, floor):
    """``loss_share`` at position t: the judged gaps of all rows from t on,
    over the margins under ``tie_margin`` among them, at least ``floor``;
    against the plain double loop."""
    rng = np.random.default_rng(3)
    gap = rng.random((3, 12)) * (rng.random((3, 12)) < 0.3)
    margin = rng.random((3, 12)) * 0.1
    judged = rng.random((3, 12)) < 0.8
    got = fam.loss_share(gap, margin, judged, 0.05, floor)
    assert got.shape == (12,)
    for t in range(12):
        lost = blind = 0.0
        for r in range(3):
            for u in range(t, 12):
                if judged[r, u]:
                    lost += gap[r, u]
                    blind += margin[r, u] if margin[r, u] < 0.05 else 0.0
        assert got[t] == pytest.approx(lost / max(blind, floor), rel=1e-5)


def test_serve_logits_lays_the_share_where_the_harness_reads_it(fam):
    """The harness reads position t's record at index t - 1 and takes
    ``best - got`` there: that is ``loss_share`` of the replay's own gaps
    and top-two margins at t, the same for every row, and the argmax is
    the replay's at t."""
    cfg = tiny_config()
    cfg["assumed"].update(tie_margin=0.05, tie_floor=0.5)
    ids = np.zeros((2, 128), np.int32)
    ids[:, :20] = np.random.default_rng(11).integers(1, 255, (2, 20))
    gather = np.zeros((2, 128, 1), np.int32)
    gather[:, :-1, 0] = ids[:, 1:]
    best, arg, got = fam.serve_logits(cfg, SEED, ids, gather)
    b, a, g, info = fam.position_logits(cfg, SEED, ids, ids[..., None])
    want = fam.loss_share(b - g[..., 0], b - info["second"], info["judged"],
                          0.05, 0.5)
    assert info["judged"][:, :20].all() and want[19] > 0
    np.testing.assert_allclose((best - got[..., 0])[:, :19],
                               np.broadcast_to(want[1:20], (2, 19)),
                               atol=1e-5)
    np.testing.assert_array_equal(arg[:, :19], a[:, 1:20])
    np.testing.assert_allclose(got[:, :19, 0], g[:, 1:20, 0], atol=1e-6)


def test_the_familys_counts(fam):
    """``serve_flops`` restores the T + 1 passes the harness does not
    count; the kernel's and the grouped matmul's operations and bytes at
    the published widths."""
    with open(os.path.join(common.BENCH_DIR, "configs",
                           "sdar-30b-a3b-serve.json")) as f:
        cfg = json.load(f)
    assert fam.matmul_params(cfg, head=False) == 6 * (
        2 * 2048 * 128 * 36 + 2048 * 128 + 8 * 3 * 2048 * 768)
    per = fam.matmul_params(cfg, head=False)
    assert fam.serve_flops(cfg, 1000, 400, 0) == \
        2 * per * (1000 + 2 * 400) + 2 * 2048 * 151936 * 2 * 400
    assert fam.moe_matmul_call(cfg, 2048, 128) == (
        2 * 3 * 2048 * 768 * 2048,
        3 * 2048 * 768 * 2 * 128 + (2 * 2048 + 3 * 768) * 2 * 2048)
    assert fam.block_attention_call(cfg, 256, 1000, 5000) == (
        4 * 32 * 128 * 1000, 2 * 4 * 128 * 2 * 5000 + 2 * 32 * 128 * 2 * 256)
    leaves = fam.leaves(cfg)
    n = sum(int(np.prod(s)) for s, _ in leaves.values())
    assert n == 4_361_055_744           # 8.72 GB of bfloat16
