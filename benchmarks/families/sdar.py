"""The ``sdar`` family: the published SDAR-MoE decoder (``model_type``
``sdar_moe``; SDAR, arXiv:2510.06303), a Qwen3-MoE-shaped layer that
generates by diffusion over blocks. Everything the benchmark knows of this
architecture is in this file, in four groups: its leaves, the program's
model, the plain reference, the counts.

A configuration names it with ``"program": {"family": "sdar"}``.

The layer (x in R^C a token; B = ``block_length``; b(t) = t // B; all norms
RMSNorm; no bias anywhere):

    n   = x / rms(x) * g1
    q_h = rope(W_q,h n / rms_d(W_q,h n) * g_q)     k_j likewise with g_k
    v_j = W_v,j n          (the q/k norms are over a head's d numbers, one
                            gain vector a layer each; rotate-half rope;
                            head h reads KV head h // group)
    o_t = softmax over the VISIBLE set of t of (q_t . k_m / sqrt(d)) v_m
    y   = x + W_o o
    m   = y / rms(y) * g2
    r   = softmax_E(W_r m) in float32;  I = top-k of r;  w_i = r_i / sum_I r
    z   = y + sum_{i in I} w_i W_d,i(silu(W_g,i m) * W_u,i m)
    logits_t = W_head(z^L_t / rms * g)      (untied; they score the token AT t)

    VISIBLE, clean pass (prefill, commit):  {m : b(m) <= b(t)}
    VISIBLE, denoising pass of block c:     committed K/V of every m with
        b(m) < c, and the K/V of block c's CURRENT ids (some still MASK)

Generation (greedy): blocks are aligned to absolute positions; the whole
blocks of the prompt are clean passes; then, a block at a time, its ids are
the prompt's tail (if the prompt ends inside it) and MASK elsewhere; up to
``denoising_steps`` T passes over the block's ids give every masked position
its best token (the MASK id's logit left out) and that token's probability;
a pass fixes positions by the rule ``remasking`` with the share n_s of B
over T (``transfer_counts``): ``low_confidence_static`` the n_s best-scored
masked positions, ``low_confidence_dynamic`` every masked position whose
probability passes ``confidence_threshold`` where those are n_s or more and
else the n_s best, ``sequential`` the n_s leftmost; when no MASK is left one
clean pass commits the block.

The reference REPLAYS that generation with the served tokens forced
(``position_logits``): it never samples, it scores what was served.
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import reference as R
from benchmarks.lib import weights as W
from benchmarks.lib.flops import roofline_seconds  # noqa: F401

# --------------------------------------------------------------------------
# 1. leaves: the benchmark's own names; a linear weight is [in, out]
#    (y = x @ W), the experts of a layer are three stacked leaves
#    [E, in, out]. Every layer is of one kind.
# --------------------------------------------------------------------------
TOP_LEAVES = ("embed", "norm", "head")
LAYER_LEAVES = ("ln1", "q", "k", "v", "o", "q_norm", "k_norm", "ln2",
                "router", "e_gate", "e_up", "e_down")
LEAF_NAMES = TOP_LEAVES + LAYER_LEAVES


def leaves(cfg):
    """{leaf path: (shape, init)}: matrices normal(0, initializer_range),
    every gain vector 1 + 0.1 normal (the file's ``assumed``)."""
    c, d, f = (cfg["hidden_size"], cfg["head_dim"],
               cfg["moe_intermediate_size"])
    nh, nkv, e = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["num_experts"])
    shapes = {"embed": (cfg["vocab_size"], c), "norm": (c,),
              "head": (c, cfg["vocab_size"]), "ln1": (c,), "q": (c, nh * d),
              "k": (c, nkv * d), "v": (c, nkv * d), "o": (nh * d, c),
              "q_norm": (d,), "k_norm": (d,), "ln2": (c,), "router": (c, e),
              "e_gate": (e, c, f), "e_up": (e, c, f), "e_down": (e, f, c)}

    def leaf(n):
        return shapes[n], ("gain" if len(shapes[n]) == 1 else "normal")

    out = {n: leaf(n) for n in TOP_LEAVES}
    for i in range(cfg["num_hidden_layers"]):
        out.update({f"layers.{i}.{n}": leaf(n) for n in LAYER_LEAVES})
    return out


PROGRAM_NAMES = {
    "embed": "model.embed_tokens.weight", "norm": "model.norm.weight",
    "head": "lm_head.weight",
    "ln1": "model.layers.{i}.input_layernorm.weight",
    "q": "model.layers.{i}.self_attn.q_proj.weight",
    "k": "model.layers.{i}.self_attn.k_proj.weight",
    "v": "model.layers.{i}.self_attn.v_proj.weight",
    "o": "model.layers.{i}.self_attn.o_proj.weight",
    "q_norm": "model.layers.{i}.self_attn.q_norm.weight",
    "k_norm": "model.layers.{i}.self_attn.k_norm.weight",
    "ln2": "model.layers.{i}.post_attention_layernorm.weight",
    "router": "model.layers.{i}.mlp.gate_weight",
    "e_gate": "model.layers.{i}.mlp.experts_gate",
    "e_up": "model.layers.{i}.mlp.experts_up",
    "e_down": "model.layers.{i}.mlp.experts_down",
}


def program_name(path):
    """The program's parameter that holds this leaf."""
    layer, name = W.split(path)
    return PROGRAM_NAMES[name].format(i=layer)


# --------------------------------------------------------------------------
# 2. the program's model
# --------------------------------------------------------------------------
# keys of the file that must equal what the program's constructor built
CHECKED = ("hidden_size", "intermediate_size", "moe_intermediate_size",
           "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
           "head_dim", "num_experts", "num_experts_per_tok", "norm_topk_prob",
           "decoder_sparse_step", "vocab_size", "max_position_embeddings",
           "rope_theta", "rms_norm_eps", "attention_bias",
           "tie_word_embeddings", "initializer_range")
CHECKED_ASSUMED = ("block_length", "mask_token_id")


def build(config):
    """The program's model from the file's constructor and arguments, its
    sizes checked against the file's, built under ``paddle.LazyGuard``:
    shapes and the file's ``dtype`` and no array until
    ``common.build_model`` hands each parameter the seed's leaf."""
    import paddle_tpu as paddle
    from paddle_tpu import models

    prog = config["program"]
    cfg = getattr(models, prog["constructor"])(**prog["constructor_args"])
    for k in CHECKED:
        if getattr(cfg, k) != config[k]:
            raise SystemExit(f"benchmark: the program's {k}="
                             f"{getattr(cfg, k)} is not the file's {config[k]}")
    for k in CHECKED_ASSUMED:
        if getattr(cfg, k) != config["assumed"][k]:
            raise SystemExit(
                f"benchmark: the program's {k}={getattr(cfg, k)} is not the "
                f"file's assumed {config['assumed'][k]}")
    if list(cfg.mlp_only_layers) != config["mlp_only_layers"] or \
            cfg.dtype != prog["dtype"]:
        raise SystemExit("benchmark: the program's mlp_only_layers or dtype "
                         "is not the file's")
    with paddle.LazyGuard():
        return models.SDARMoeForCausalLM(cfg)


def serving(model, config):
    """The adapter that BatchScheduler drives."""
    from paddle_tpu.inference.paged_llama import PagedLlamaAdapter

    pool = {k: v for k, v in config["program"]["pool"].items()
            if not k.endswith("_why")}
    return PagedLlamaAdapter(model, **pool)


# --------------------------------------------------------------------------
# 3. the plain reference: float32 jax.numpy at the highest matmul
#    precision, masks built from positions, nothing of the program.
#    mode="int8" is the control.
# --------------------------------------------------------------------------
def head_rms(x, g, eps):
    """x [..., heads, d]: RMSNorm over a head's d numbers."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def qkv(h, lw, pos, cfg, mode):
    """h [S, C] -> rotated, normed q [S, nh, d], k [S, nkv, d], v."""
    s = h.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    q = head_rms(R.mm(h, lw["q"], mode).reshape(s, nh, d), lw["q_norm"], eps)
    k = head_rms(R.mm(h, lw["k"], mode).reshape(s, nkv, d), lw["k_norm"], eps)
    v = R.mm(h, lw["v"], mode).reshape(s, nkv, d)
    return (R.rope(q[None], pos, theta)[0], R.rope(k[None], pos, theta)[0], v)


def attend(q, k, v, mask):
    """One sequence: q [S, nh, d]; k, v [Sk, nkv, d]; mask [S, Sk] (True:
    visible). One softmax a query, one KV head at a time."""
    s, nh, d = q.shape
    nkv = k.shape[1]
    qg = q.reshape(s, nkv, nh // nkv, d).transpose(1, 2, 0, 3)   # nkv g s d

    def one(args):
        qq, kk, vv = args
        sc = jnp.einsum("gsd,td->gst", qq, kk, precision=R.HI) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask[None], sc, -1e30), -1)
        return jnp.einsum("gst,td->gsd", p, vv, precision=R.HI)

    out = jax.lax.map(one, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(2, 0, 1, 3).reshape(s, nh * d)


def experts(m, lw, cfg, mode):
    """Every token's top-k over ALL experts, one expert at a time (a scan:
    each expert's SwiGLU over every token, weighted by the router's weight
    of the token for it, zero where it was not picked). m [S, C]."""
    k, e = cfg["num_experts_per_tok"], cfg["num_experts"]
    r = jax.nn.softmax(R.mm(m, lw["router"], mode), -1)
    w, idx = jax.lax.top_k(r, k)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    dense = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32)
                    * w[..., None], -2)                          # [S, E]

    def one(acc, args):
        we, g, u, d = args
        y = R.mm(jax.nn.silu(R.mm(m, g, mode)) * R.mm(m, u, mode), d, mode)
        return acc + we[:, None] * y, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(m), (
        dense.T, lw["e_gate"], lw["e_up"], lw["e_down"]))
    return acc


def layer(x, lw, pos, cfg, mode, clean=None):
    """One decoder layer on ONE sequence x [S, C] float32. ``clean`` None:
    the clean pass, every position sees its own block and the blocks before
    it; returns (x, (k, v)). ``clean`` = (k, v) of the clean pass: the
    denoising pass, a query reads the clean K/V of EARLIER blocks and this
    stream's K/V of its OWN block; returns (x, None)."""
    eps, blk = cfg["rms_norm_eps"], pos // cfg["assumed"]["block_length"]
    q, k, v = qkv(R.rms(x, lw["ln1"], eps), lw, pos, cfg, mode)
    if clean is None:
        o = attend(q, k, v, blk[None, :] <= blk[:, None])
    else:
        o = attend(q, jnp.concatenate([clean[0], k]),
                   jnp.concatenate([clean[1], v]),
                   jnp.concatenate([blk[None, :] < blk[:, None],
                                    blk[None, :] == blk[:, None]], 1))
    y = x + R.mm(o, lw["o"], mode)
    z = experts(R.rms(y, lw["ln2"], eps), lw, cfg, mode)
    return y + z, (k, v) if clean is None else None


@functools.lru_cache(maxsize=None)
def _serve_fns(cfg_json, mode):
    cfg = json.loads(cfg_json)              # the whole file, as it was read
    mask_id = int(cfg["assumed"]["mask_token_id"])

    @jax.jit
    def embed(emb, ids):
        return emb.astype(jnp.float32)[ids]

    @jax.jit
    def clean_layer(x, lw):
        pos = jnp.arange(x.shape[1])
        return jax.lax.map(
            lambda xs: layer(xs, R.f32(lw), pos, cfg, mode), x)

    @jax.jit
    def noisy_layer(x, lw, kv):
        pos = jnp.arange(x.shape[1])
        return jax.lax.map(
            lambda a: layer(a[0], R.f32(lw), pos, cfg, mode, a[1])[0],
            (x, kv))

    @jax.jit
    def final(h, norm, head, gather):
        """Per position: the best logit with the MASK id's left out, its
        token, its log-probability (over the same logits), the logits of
        ``gather`` [B, S, G], the second best logit; one sequence at a
        time."""
        norm, head = norm.astype(jnp.float32), head.astype(jnp.float32)

        def one(args):
            hs, gs = args
            lg = R.mm(R.rms(hs, norm, cfg["rms_norm_eps"]), head, mode)
            lg = lg.at[:, mask_id].set(-jnp.inf)
            top, at = jax.lax.top_k(lg, 2)
            return (top[:, 0], at[:, 0].astype(jnp.int32),
                    top[:, 0] - jax.nn.logsumexp(lg, -1),
                    jnp.take_along_axis(lg, gs, -1), top[:, 1])
        return jax.lax.map(one, (h, gather))

    return embed, clean_layer, noisy_layer, final


def transfer_counts(block, steps):
    """How many positions pass s = 0 .. steps - 1 fixes: the ceil-split of
    the block over the passes (the remainder to the first passes)."""
    base, rem = divmod(int(block), int(steps))
    return [base + (s < rem) for s in range(int(steps))]


def choose(logp, masked, n, rule, threshold):
    """The positions ONE block fixes in one pass, by ``rule``: ``logp`` [B]
    the best token's log-probability, ``masked`` [B] bool, ``n`` this
    pass's share. Returns (indices, the gap between the last chosen and
    the first left-out score; inf where the rule compares no scores)."""
    live = np.flatnonzero(masked)
    n = min(int(n), live.size)
    if n == 0:
        return live[:0], np.inf
    if rule == "sequential":
        return live[:n], np.inf
    order = live[np.argsort(-logp[live], kind="stable")]
    gap = logp[order[n - 1]] - logp[order[n]] if n < live.size else np.inf
    if rule == "low_confidence_dynamic":
        # the threshold is a tie too: how near the nearest score lies
        gap = min(gap, np.min(np.abs(logp[live] - np.log(threshold))))
        sure = live[logp[live] > np.log(threshold)]
        if sure.size >= n:
            return sure, gap
    elif rule != "low_confidence_static":
        raise SystemExit(f"benchmark: no remasking rule {rule!r}")
    return np.sort(order[:n]), gap


def generation(cfg):
    """(B, T, rule, threshold, MASK id) of the file: the model's own under
    ``assumed``, the rest as the scheduler was built."""
    sched = cfg["program"]["scheduler"]
    return (int(cfg["assumed"]["block_length"]),
            int(sched.get("denoising_steps", 4)),
            sched.get("remasking", "low_confidence_dynamic"),
            float(sched.get("confidence_threshold", 0.9)),
            int(cfg["assumed"]["mask_token_id"]))


def position_logits(cfg, seed, ids, gather, mode="f32", prompt_lens=None,
                    lens=None):
    """Replay the generation with the served tokens forced. ids [R, S]
    int32 (S a multiple of the block; padded on the right); gather [R, S,
    G] token ids whose logits are wanted AT each position.

    A clean stream over ``ids`` under the block-causal mask gives every
    layer's committed K/V. For pass s = 0 .. T-1 a second stream holds
    each block's ids with the not-yet-fixed positions at MASK, its queries
    reading the clean K/V of earlier blocks and its own block's K/V; the
    reference takes ITS OWN positions by the cell's rule (``choose``),
    records there the best logit, the argmax and the gathered logits, and
    fills in the served token. ``prompt_lens`` [R]: positions below it
    were never masked (a prompt that ends inside a block shares it with
    the first generated tokens); None: every block is replayed as if
    generated (a prompt block's record is never read and reaches no later
    block, whose keys are the clean ones). ``lens`` [R]: what of a row is
    known (default: up to its last non-zero id); a block that reaches
    beyond it cannot be replayed and is not judged.

    Returns numpy, all indexed BY POSITION: best [R, S], argmax [R, S],
    gathered [R, S, G], and a dict: ``judged`` [R, S] bool; ``passes`` [R,
    S] (the pass that fixed the position; -1: never masked or not
    replayed); ``order_margin`` [R, S] (the smallest gap, over the block's
    passes, between the last chosen and the first left-out score);
    ``logp`` [R, S] (the best token's log-probability there); ``second``
    [R, S] (the second best logit there)."""
    B, T, rule, threshold, mask_id = generation(cfg)
    ids = np.asarray(ids, np.int32)
    r, s = ids.shape
    if s % B:
        raise SystemExit(f"benchmark: {s} positions are no whole blocks of "
                         f"{B}")
    if lens is None:
        nz = ids != 0
        lens = np.where(nz.any(1), s - np.argmax(nz[:, ::-1], 1), 0)
    lens = np.asarray(lens)
    plens = np.zeros(r, np.int64) if prompt_lens is None \
        else np.asarray(prompt_lens)
    pos = np.arange(s)
    whole = (pos[None, :] // B + 1) * B <= lens[:, None]   # replayable
    masked = (pos[None, :] >= plens[:, None]) & whole
    out = {"best": np.zeros((r, s), np.float32),
           "arg": np.zeros((r, s), np.int32),
           "got": np.zeros((r, s, np.shape(gather)[-1]), np.float32),
           "logp": np.zeros((r, s), np.float32),
           "second": np.zeros((r, s), np.float32)}
    passes = np.full((r, s), -1, np.int32)
    order_margin = np.full((r, s // B), np.inf, np.float32)
    with jax.default_matmul_precision("highest"):
        embed, clean_layer, noisy_layer, final = _serve_fns(
            json.dumps(cfg, sort_keys=True), mode)
        spec = W.spec(leaves(cfg), LEAF_NAMES, cfg["initializer_range"])
        top = W.make_top(spec, seed)
        n_layers = cfg["num_hidden_layers"]
        x, kvs = embed(top["embed"], jnp.asarray(ids)), []
        for li in range(n_layers):
            x, kv = clean_layer(x, W.make_layer(spec, seed, li))
            kvs.append(kv)
        del x
        gather = jnp.asarray(gather, jnp.int32)
        for step, share in enumerate(transfer_counts(B, T)):
            if not masked.any():
                break
            cur = np.where(masked, mask_id, ids).astype(np.int32)
            x = embed(top["embed"], jnp.asarray(cur))
            for li in range(n_layers):
                x = noisy_layer(x, W.make_layer(spec, seed, li), kvs[li])
            best, arg, logp, got, second = (np.asarray(a) for a in final(
                x, top["norm"], top["head"], gather))
            for i in range(r):
                for c in np.flatnonzero(masked[i].reshape(-1, B).any(1)):
                    sl = slice(c * B, (c + 1) * B)
                    picked, gap = choose(logp[i, sl], masked[i, sl], share,
                                         rule, threshold)
                    order_margin[i, c] = min(order_margin[i, c], gap)
                    at = c * B + picked
                    out["best"][i, at], out["arg"][i, at] = \
                        best[i, at], arg[i, at]
                    out["got"][i, at], out["logp"][i, at] = \
                        got[i, at], logp[i, at]
                    out["second"][i, at] = second[i, at]
                    passes[i, at] = step
                    masked[i, at] = False
    info = {"judged": passes >= 0, "passes": passes,
            "order_margin": np.repeat(order_margin, B, 1),
            "logp": out["logp"], "second": out["second"]}
    return out["best"], out["arg"], out["got"], info


def loss_share(gap, margin, judged, tie_margin, floor):
    """The number the harness takes the maximum of. ``gap``, ``margin``,
    ``judged`` [R, S], BY POSITION; returns [S]. At position t: the summed
    ``gap`` of ALL rows' judged positions from t to the end, over the
    summed ``margin`` of those of them whose ``margin`` is under
    ``tie_margin`` (at least ``floor``, which keeps a tail a few near
    ties long from being read as a whole sample is). A row's generated
    positions are its tail, so from a generated position on the sums hold
    generated positions only **where the rows' prompts are of one length**
    (the harness does not hand the lengths over, and reads no prompt
    position); under prompts of several lengths a longer prompt's random
    tokens, each some 4 under the best, fall into a shorter row's sums:
    that reads as not correct, never as correct."""
    rev = lambda x: np.cumsum(x.sum(0)[::-1], dtype=np.float64)[::-1]  # noqa
    lost = rev(np.where(judged, np.minimum(gap, 1e6), 0.0))
    blind = rev(np.where(judged & (margin < tie_margin), margin, 0.0))
    return (lost / np.maximum(blind, floor)).astype(np.float32)


def serve_logits(cfg, seed, ids, gather, mode="f32"):
    """What the harness compares: it reads the record of position t at
    index t - 1 (``lib/serve.py:check``: "logits at s predict token s +
    1"), so ``position_logits``'s arrays are laid one to the left, and
    ``gather`` [R, S, G], which the harness lays the same way, one to the
    right before the replay.

    **What ``served_gap`` is for this family: a sum, not one position.**
    The harness takes the maximum over the served positions of ``best -
    got``. With random weights a masked position's state is nearly one
    state and its best tokens nearly tie, the top-k of the router nearly
    ties in some layer at most positions, and a swapped expert moves a
    position's logits by up to 0.09, for the bfloat16 program and the int8
    control alike: the maximum over positions reads the rarest event of
    either and does not tell them apart, whatever margins are left out
    (PERF.md section 6 has the sweep, and the run the driver refused).
    What does tell them apart is how OFTEN and by how much in all a served
    token lies under the reference's best. So ``best`` is returned as
    ``got`` (of the first gathered id, the served token) plus
    ``loss_share``: the summed loss of the sample's served tokens from
    that position to the end, over the loss of a program that takes the
    reference's SECOND best token wherever its top two lie closer than
    ``assumed.tie_margin`` (1.0: the served tokens lost as much as such a
    program would). The denominator takes out what swings both sides
    alike, a seed's and a prompt's density of near ties. Every replayed
    position is judged, but (1) a block in which the reference's
    choice of positions was closer than ``assumed.order_margin`` in any
    pass (0 or absent: not used): a position fixed in another pass was
    scored in another context; (2) a block that reaches beyond what is
    known of the row. A note on standard output (``{"phase": "replay",
    ...}``) gives the share judged and the share of near ties."""
    ids = np.asarray(ids, np.int32)
    gather = np.asarray(gather, np.int32)
    shifted = np.zeros_like(gather)
    shifted[:, 1:] = gather[:, :-1]
    best, arg, got, info = position_logits(cfg, seed, ids, shifted, mode)
    a = cfg["assumed"]
    judged = info["judged"] \
        & (info["order_margin"] >= float(a.get("order_margin", 0.0)))
    margin = best - info["second"]
    share = loss_share(best - got[..., 0], margin, judged,
                       float(a["tie_margin"]), float(a["tie_floor"]))
    left = lambda x: np.concatenate(                      # noqa: E731
        [x[:, 1:], np.zeros_like(x[:, :1])], 1)
    replayed = info["passes"] >= 0
    # a note on standard output, before the harness's own of the check
    print(json.dumps({
        "phase": "replay", "mode": mode, "positions": int(replayed.sum()),
        "judged_share": float(judged[replayed].mean()) if replayed.any()
        else 0.0,
        "near_tie_share": float((margin < float(a["tie_margin"]))[
            judged].mean()) if judged.any() else 0.0}), flush=True)
    return left(got[..., 0] + share[None, :]), left(arg), left(got)


def lm_loss(params, ids, cfg, mode="f32", rows=None):
    """The denoising loss at the fully masked state: the clean stream
    over ``ids`` gives the K/V, a second stream holds MASK at every
    position, and the loss is the mean cross-entropy of the token AT each
    position (no shift). No cell trains this family (16 B a parameter
    needs eight chips to a layer); the harness's seam asks every family
    for one, and a training cell would start from it. ``rows`` keeps only
    those sequences."""
    if rows is not None:
        ids = ids[jnp.asarray(rows)]
    pos = jnp.arange(ids.shape[1])
    mask_id = int(cfg["assumed"]["mask_token_id"])

    def one(seq):
        x, xn = params["embed"][seq], params["embed"][
            jnp.full_like(seq, mask_id)]
        for lw in params["layers"]:
            x, kv = jax.checkpoint(
                lambda a, lw_=lw: layer(a, lw_, pos, cfg, mode))(x)
            xn, _ = jax.checkpoint(
                lambda a, kv_, lw_=lw: layer(a, lw_, pos, cfg, mode,
                                             kv_))(xn, kv)
        lg = R.mm(R.rms(xn, params["norm"], cfg["rms_norm_eps"]),
                  params["head"], mode)
        return jnp.sum(jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, seq[:, None], -1)[:, 0])

    return jnp.sum(jax.lax.map(one, ids)) / (ids.shape[0] * ids.shape[1])


# --------------------------------------------------------------------------
# 4. counts: operations and bytes that the algorithm needs, from shapes
#    alone. Recomputed operations are never counted.
# --------------------------------------------------------------------------
def expert_params(cfg):
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def matmul_params(cfg, head=True):
    """Parameters that ONE fed token multiplies: in every layer the four
    projections, the router and ``num_experts_per_tok`` experts; with
    ``head`` the output head. Not the embedding (a lookup), not the gains,
    not the experts the token is not routed to."""
    c, d = cfg["hidden_size"], cfg["head_dim"]
    attn = 2 * c * d * (cfg["num_attention_heads"]
                        + cfg["num_key_value_heads"])
    per = attn + c * cfg["num_experts"] \
        + expert_params(cfg) * cfg["num_experts_per_tok"]
    return cfg["num_hidden_layers"] * per \
        + (c * cfg["vocab_size"] if head else 0)


def attention_flops_per_pair(cfg):
    """QK^T and PV of every query head for one (query, key) pair."""
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"]


def serve_flops(cfg, tokens_fed, rows_sampled, context_of_fed):
    """Forward work of serving. **The harness counts one fed token a
    delivered token** (``lib/serve.py:Recorder``: ``tokens_fed`` and
    ``rows_sampled`` rise by one an ``on_token`` call), but a delivered
    token of a block model was fed in T denoising passes and one commit
    pass. T is fixed in the cell, so the T + 1 passes are restored here:
    the layers' matmuls for ``tokens_fed + T x rows_sampled`` tokens
    (prompt tokens once, each delivered token T + 1 times), the head ``T x
    rows_sampled`` times (every position of a block in each of its
    denoising passes; the commit pass needs none), attention pairs ``(T +
    1) x context_of_fed`` (a prompt token's pairs are counted T + 1 times
    too many, which the in-block pairs a pass also computes, up to B - 1 a
    token, do not make up: at this cell's contexts attention is under a
    hundredth of a step's operations). The program also runs the head over
    the rows of a commit pass: the count reads UNDER what the device did,
    never over."""
    t = generation(cfg)[1]
    fed = tokens_fed + t * rows_sampled
    return (2 * matmul_params(cfg, head=False) * fed
            + 2 * cfg["hidden_size"] * cfg["vocab_size"] * t * rows_sampled
            + attention_flops_per_pair(cfg) * context_of_fed
            * cfg["num_hidden_layers"])


def moe_matmul_call(cfg, assignments, experts_touched, w_bytes=2,
                    act_bytes=2):
    """The three grouped matmuls of one expert layer: 2 x 3 x C x F
    operations an assignment; the bytes of every expert that is touched,
    once, and the sorted activations in, between and out."""
    c, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    ops = 2 * expert_params(cfg) * assignments
    byts = (expert_params(cfg) * w_bytes * experts_touched
            + (2 * c + 3 * f) * act_bytes * assignments)
    return ops, byts


def block_attention_call(cfg, fed, pairs, kv_rows, kv_bytes=2, act_bytes=2):
    """One ragged paged-attention call of one layer under the
    block-causal mask: operations (``pairs``: the sum over fed tokens of
    the keys each sees, its own block's whole), and the bytes it must
    move: the key and the value of every cached row of every sequence
    once (``kv_rows``), the ``fed`` queries in and outputs back."""
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    ops = attention_flops_per_pair(cfg) * pairs
    byts = 2 * nkv * d * kv_bytes * kv_rows + 2 * nh * d * act_bytes * fed
    return ops, byts
