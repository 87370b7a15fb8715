"""The ``evabyte`` family: the published EvaByte decoder, a byte-level
model whose attention reads the current aligned window exactly and every
finished chunk of every earlier window through one pooled row (EVA,
"Efficient Attention via Control Variates", arXiv:2302.04542, in the
simplified learned form the model ships). Pre-norm RMSNorm with a unit
offset, rotary embedding in the rotate-half form, multi-head attention,
SwiGLU, an untied head of ``num_pred_heads`` x ``vocab_size`` logits.
Everything the benchmark knows of this architecture is in this file, in
four groups: its leaves, the program's model, the plain reference, the
counts.

A configuration names it with ``"program": {"family": "evabyte"}``.

The equations (per head, d = head_dim, s = d^-1/2; t a position, W =
window_size, C = chunk_size; windows and chunks are aligned blocks):

* n = x / rms(x) * G, G the gain (the model stores G - 1:
  ``norm_add_unit_offset``); q = rope(n W_q), k = rope(n W_k), v = n W_v.
* chunk c, once its C tokens exist, phi and mu [heads, d] a layer:
  a_j = softmax_j(s phi . k_(cC+j)); k~_c = sum_j a_j k_(cC+j) + mu;
  v~_c = sum_j a_j v_(cC+j).
* attention of t, w = t // W: ONE softmax over the exact keys w W .. t
  and the pooled rows of the chunks c < w W / C; y = x + o W_o;
  z = y + (silu(n' W_g) * n' W_u) W_d, n' the same norm of y.
* logits = norm(z) W_head in R^(num_pred_heads x vocab_size), laid out
  [head, vocabulary]; head 0 predicts byte t + 1 (what a sampler sees,
  and what ``serve_logits`` returns), head i byte t + 1 + i.

What of this is recalled and not re-read is in the configuration's
``assumed``."""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import reference as R
from benchmarks.lib import weights as W
from benchmarks.lib.flops import roofline_seconds  # noqa: F401

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512          # queries scored at once by the reference

# --------------------------------------------------------------------------
# 1. leaves: the benchmark's own names; a linear weight is [in, out]
#    (y = x @ W). A leaf's seeded key folds in its layer and its index here.
# --------------------------------------------------------------------------
TOP_LEAVES = ("embed", "norm", "head")
LAYER_LEAVES = ("ln1", "q", "k", "v", "o", "phi", "mu", "ln2", "gate", "up",
                "down")
LEAF_NAMES = TOP_LEAVES + LAYER_LEAVES
GAINS = ("norm", "ln1", "ln2")


def leaves(cfg):
    """{leaf path: (shape, init)}: the top of the model, then every layer
    by index (all of one kind). Matrices are normal(0, initializer_range);
    norm gains G, phi and mu are 1 + 0.1 normal (phi of order one, so that
    a chunk's softmax is not flat; mu of order one, so that a pooled key
    without it is another key)."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    nh, d = cfg["num_attention_heads"], cfg["head_dim"]
    out_w = cfg["num_pred_heads"] * cfg["vocab_size"]
    shapes = {"embed": (cfg["vocab_size"], h), "norm": (h,),
              "head": (h, out_w), "ln1": (h,), "q": (h, nh * d),
              "k": (h, nh * d), "v": (h, nh * d), "o": (nh * d, h),
              "phi": (nh, d), "mu": (nh, d), "ln2": (h,), "gate": (h, f),
              "up": (h, f), "down": (f, h)}

    def leaf(n):
        return shapes[n], ("gain" if n in GAINS + ("phi", "mu")
                           else "normal")

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
    "phi": "model.layers.{i}.self_attn.phi",
    "mu": "model.layers.{i}.self_attn.mu",
    "ln2": "model.layers.{i}.post_attention_layernorm.weight",
    "gate": "model.layers.{i}.mlp.gate_proj.weight",
    "up": "model.layers.{i}.mlp.up_proj.weight",
    "down": "model.layers.{i}.mlp.down_proj.weight",
}


def program_name(path):
    """The program's parameter that holds this leaf."""
    layer, name = W.split(path)
    return PROGRAM_NAMES[name].format(i=layer)


# --------------------------------------------------------------------------
# 2. the program's model
# --------------------------------------------------------------------------
# keys of the file that must equal what the program's constructor built
CHECKED = ("hidden_size", "intermediate_size", "num_attention_heads",
           "num_key_value_heads", "vocab_size", "num_pred_heads",
           "window_size", "chunk_size", "num_hidden_layers",
           "max_position_embeddings", "rope_theta", "rms_norm_eps",
           "init_std", "norm_add_unit_offset", "fp32_logits",
           "attention_class", "head_dim")


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
    if cfg.dtype != prog["dtype"]:
        raise SystemExit("benchmark: the program's dtype is not the file's")
    with paddle.LazyGuard():
        return models.EvaByteForCausalLM(cfg)


def store_gains(model):
    """A gain leaf is the gain G itself (1 + 0.1 normal, as in every
    family); the model stores a gain less one (``norm_add_unit_offset``).
    Hand each norm its leaf less one: exact in bfloat16, and 1 + (G - 1)
    in the program's float32 is G again."""
    for name, p in model.named_parameters():
        if name.endswith("norm.weight") or name.endswith("layernorm.weight"):
            p._data = p._data - jnp.ones((), p._data.dtype)
    return model


def serving(model, config):
    """The adapter that BatchScheduler drives, over the model with its
    gains stored as it stores them (:func:`store_gains`)."""
    from paddle_tpu.inference.paged_llama import PagedLlamaAdapter

    pool = {k: v for k, v in config["program"]["pool"].items()
            if not k.endswith("_why")}
    return PagedLlamaAdapter(store_gains(model), **pool)


# --------------------------------------------------------------------------
# 3. the plain reference: float32 jax.numpy at the highest matmul
#    precision, masks built from positions, nothing of the program.
#    mode="int8" is the control.
# --------------------------------------------------------------------------
def chunk_summaries(k, v, phi, mu, chunk):
    """k, v [S, nh, d] (k rotated) -> the pooled key and value of every
    whole chunk, [S // chunk, nh, d] each."""
    s, nh, d = k.shape
    nc = s // chunk
    kc = k[:nc * chunk].reshape(nc, chunk, nh, d)
    vc = v[:nc * chunk].reshape(nc, chunk, nh, d)
    a = jax.nn.softmax(jnp.sum(kc * phi, -1) / np.sqrt(d), axis=1)[..., None]
    return jnp.sum(a * kc, 1) + mu, jnp.sum(a * vc, 1)


def eva_attention(q, k, v, phi, mu, window, chunk):
    """One sequence. q, k, v [S, nh, d] -> [S, nh, d]: a query at t sees
    the keys j <= t of its own window exactly and the pooled row of every
    whole chunk of every earlier window, in ONE softmax. Queries go
    ``QUERY_BLOCK`` at a time, so the scores are never more than [nh,
    block, S + S // chunk]."""
    s, nh, d = q.shape
    kt, vt = chunk_summaries(k, v, phi, mu, chunk)
    keys = jnp.concatenate([k, kt], 0)                  # [S + nc, nh, d]
    vals = jnp.concatenate([v, vt], 0)
    j = jnp.arange(s)
    c = jnp.arange(kt.shape[0])
    blk = next(b for b in range(min(QUERY_BLOCK, s), 0, -1) if s % b == 0)

    @jax.checkpoint
    def block(args):
        qq, i = args                                    # [blk, nh, d], [blk]
        sc = jnp.einsum("ihd,jhd->hij", qq, keys, precision=HI) / np.sqrt(d)
        exact = (j[None, :] <= i[:, None]) \
            & (j[None, :] // window == i[:, None] // window)
        pooled = (c[None, :] * chunk) // window < i[:, None] // window
        mask = jnp.concatenate([exact, pooled], 1)
        p = jax.nn.softmax(jnp.where(mask[None], sc, -1e30), -1)
        return jnp.einsum("hij,jhd->ihd", p, vals, precision=HI)

    out = jax.lax.map(block, (q.reshape(s // blk, blk, nh, d),
                              j.reshape(s // blk, blk)))
    return out.reshape(s, nh, d)


def layer(x, lw, pos, cfg, mode):
    """One decoder block of one sequence. x [S, H] float32."""
    s = x.shape[0]
    nh, d = cfg["num_attention_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = R.rms(x, lw["ln1"], eps)
    q = R.rope(R.mm(h, lw["q"], mode).reshape(1, s, nh, d), pos, theta)[0]
    k = R.rope(R.mm(h, lw["k"], mode).reshape(1, s, nh, d), pos, theta)[0]
    v = R.mm(h, lw["v"], mode).reshape(s, nh, d)
    a = eva_attention(q, k, v, lw["phi"], lw["mu"], cfg["window_size"],
                      cfg["chunk_size"])
    x = x + R.mm(a.reshape(s, nh * d), lw["o"], mode)
    h = R.rms(x, lw["ln2"], eps)
    return x + R.mm(jax.nn.silu(R.mm(h, lw["gate"], mode))
                    * R.mm(h, lw["up"], mode), lw["down"], mode)


@functools.lru_cache(maxsize=None)
def _serve_fns(cfg_json, mode):
    cfg = json.loads(cfg_json)              # the whole file, as it was read
    vocab = cfg["vocab_size"]

    @jax.jit
    def embed(emb, ids):
        return emb.astype(jnp.float32)[ids]

    @jax.jit
    def one_layer(x, lw):
        lw = R.f32(lw)
        pos = jnp.arange(x.shape[1])
        return jax.lax.map(lambda xs: layer(xs, lw, pos, cfg, mode), x)

    @jax.jit
    def final(x, norm, head, gather):
        """Every prediction head's logits [B, S, heads, vocab]' best,
        argmax and gathered entries are taken of HEAD 0, one sequence at
        a time; ``every`` also returns all heads' logits (tests)."""
        norm, head = norm.astype(jnp.float32), head.astype(jnp.float32)

        def one(args):
            xs, gs = args
            lg = R.mm(R.rms(xs, norm, cfg["rms_norm_eps"]),
                      head[:, :vocab], mode)
            return (lg.max(-1), lg.argmax(-1).astype(jnp.int32),
                    jnp.take_along_axis(lg, gs, -1))
        return jax.lax.map(one, (x, gather))

    @jax.jit
    def every(x, norm, head):
        lg = R.mm(R.rms(x, norm.astype(jnp.float32), cfg["rms_norm_eps"]),
                  head.astype(jnp.float32), mode)
        return lg.reshape(*x.shape[:-1], cfg["num_pred_heads"], vocab)
    return embed, one_layer, final, every


def _hidden(cfg, seed, ids, mode):
    embed, one_layer, _, _ = _serve_fns(json.dumps(cfg, sort_keys=True), mode)
    spec = W.spec(leaves(cfg), LEAF_NAMES, cfg["initializer_range"])
    top = W.make_top(spec, seed)
    x = embed(top["embed"], jnp.asarray(ids, jnp.int32))
    for li in range(cfg["num_hidden_layers"]):
        x = one_layer(x, W.make_layer(spec, seed, li))
    return x, top


def serve_logits(cfg, seed, ids, gather, mode="f32"):
    """Teacher-forced logits of head 0 (the next byte: what the sampler
    sees), layer by layer over a block of sequences. ids [B, S] int32
    (padded on the right; causal, and a chunk that holds padding is seen
    only from later windows, so padding never reaches a real position);
    gather [B, S, G] token ids whose logits are wanted at each position.
    Returns numpy (best [B,S], argmax [B,S], gathered [B,S,G]) of the
    logits that predict position s+1."""
    x, top = _hidden(cfg, seed, ids, mode)
    final = _serve_fns(json.dumps(cfg, sort_keys=True), mode)[2]
    best, arg, got = final(x, top["norm"], top["head"],
                           jnp.asarray(gather, jnp.int32))
    return np.asarray(best), np.asarray(arg), np.asarray(got)


def pred_logits(cfg, seed, ids, mode="f32"):
    """[B, S, num_pred_heads, vocab_size]: every prediction head's logits
    (head i at position s predicts byte s + 1 + i). Small sizes only."""
    x, top = _hidden(cfg, seed, ids, mode)
    every = _serve_fns(json.dumps(cfg, sort_keys=True), mode)[3]
    return np.asarray(every(x, top["norm"], top["head"]))


def lm_loss(params, ids, cfg, mode="f32", rows=None):
    """Mean next-byte cross-entropy of head 0: logits[:, :-1] predict
    ids[:, 1:]. No cell trains this family (at 16 B a parameter not four
    layers fit the chip); the harness's seam asks every family for one,
    and a training cell would start from it. ``rows`` keeps only those
    sequences."""
    if rows is not None:
        ids = ids[jnp.asarray(rows)]
    pos = jnp.arange(ids.shape[1])
    x = params["embed"][ids]
    for lw in params["layers"]:
        x = jax.vmap(jax.checkpoint(
            lambda xs, lw_=lw: layer(xs, lw_, pos, cfg, mode)))(x)
    lg = R.mm(R.rms(x[:, :-1], params["norm"], cfg["rms_norm_eps"]),
              params["head"][:, :cfg["vocab_size"]], mode)
    lse = jax.nn.logsumexp(lg, -1)
    return jnp.mean(lse - jnp.take_along_axis(
        lg, ids[:, 1:, None], -1)[..., 0])


# --------------------------------------------------------------------------
# 4. counts: operations and bytes that the algorithm needs, from shapes
#    alone. Recomputed operations are never counted.
# --------------------------------------------------------------------------
def matmul_params(cfg, head=True):
    """Parameters that a token multiplies: the projections and the MLP of
    every layer held here and, with ``head``, every prediction head. Not
    the embedding (a lookup), the norm gains, phi or mu."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    n = (4 * h * qd + 3 * h * f) * cfg["num_hidden_layers"]
    return n + (h * cfg["num_pred_heads"] * cfg["vocab_size"] if head else 0)


def attention_flops_per_pair(cfg):
    """QK^T and PV of every query head for one (query, row) pair."""
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"]


def visible(cfg, p):
    """Rows the p-th token of a sequence (p = 1, 2, ...) attends to: its
    window's tokens up to itself and one pooled row for every chunk of
    every earlier window."""
    w, c = cfg["window_size"], cfg["chunk_size"]
    return (p - 1) % w + 1 + (w // c) * ((p - 1) // w)


def serve_flops(cfg, tokens_fed, rows_sampled, context_of_fed):
    """Forward work of serving: every token fed (prompt or decode) goes
    through the layers' matmuls; every prediction head runs once for each
    row that is sampled; attention pairs each fed token with the rows it
    sees. The harness hands over ``context_of_fed``, the sum over fed
    tokens of their POSITIONS, not of the rows they see, so the pairs are
    counted as ``tokens_fed x visible(mean position)``. That term is under
    a tenth of a step's operations at this cell's contexts (a decode
    token: 3.24 GFLOP of matmuls, 16,384 operations a pair x 1,300 rows x
    8 layers = 0.17 GFLOP of attention), and ``visible`` is a sawtooth, so
    the count at the mean position can differ from the mean count by up
    to window_size x (1 - 1 / chunk_size) pairs a token: the whole can err
    by under a tenth as well. The exact pairs of every kernel call are on
    the program's ``kernel.ragged`` spans (``eva_attention_roofline.serve``
    reads them)."""
    mean = max(1, round(context_of_fed / max(tokens_fed, 1)))
    return (2 * matmul_params(cfg, head=False) * tokens_fed
            + 2 * cfg["hidden_size"] * cfg["num_pred_heads"]
            * cfg["vocab_size"] * rows_sampled
            + attention_flops_per_pair(cfg) * visible(cfg, mean) * tokens_fed
            * cfg["num_hidden_layers"])


def eva_attention_call(cfg, fed, pairs, kv_rows, kv_bytes=2, act_bytes=2):
    """One ragged paged-attention call of one layer over a
    window-and-summary table: operations (``pairs``: the sum over fed
    tokens of the rows each sees), and the bytes it must move: the key
    and the value of every row of every sequence once (``kv_rows``: the
    sum over sequences of window tokens and visible pooled rows), the
    ``fed`` queries in and outputs back."""
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    ops = attention_flops_per_pair(cfg) * pairs
    byts = (2 * nkv * d * kv_bytes * kv_rows + 2 * nh * d * act_bytes * fed)
    return ops, byts
