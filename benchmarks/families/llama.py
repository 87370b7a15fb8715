"""The ``llama`` family: the published Mistral/Llama decoder (pre-norm
RMSNorm, rotary embedding in the rotate-half form, grouped-query causal
attention with a sliding window, SwiGLU, untied head). Everything the
benchmark knows of this architecture is in this file, in four groups:
its leaves, the program's model, the plain reference, the counts.

A configuration names it with ``"program": {"family": "llama"}``."""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import reference as R
from benchmarks.lib import weights as W
from benchmarks.lib.flops import causal_pairs, roofline_seconds  # noqa: F401

# --------------------------------------------------------------------------
# 1. leaves: the benchmark's own names; a linear weight is [in, out]
#    (y = x @ W). A leaf's seeded key folds in its layer and its index here.
# --------------------------------------------------------------------------
TOP_LEAVES = ("embed", "norm", "head")
LAYER_LEAVES = ("ln1", "q", "k", "v", "o", "ln2", "gate", "up", "down")
LEAF_NAMES = TOP_LEAVES + LAYER_LEAVES


def leaves(cfg):
    """{leaf path: (shape, init)}: the top of the model, then every layer
    by index (all of one kind here). Matrices are normal(0,
    initializer_range), norm gains 1 + 0.1 normal."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    shapes = {"embed": (v, h), "norm": (h,), "head": (h, v),
              "ln1": (h,), "q": (h, qd), "k": (h, kv), "v": (h, kv),
              "o": (qd, h), "ln2": (h,), "gate": (h, f), "up": (h, f),
              "down": (f, h)}

    def leaf(n):
        return shapes[n], "gain" if len(shapes[n]) == 1 else "normal"

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
           "num_key_value_heads", "vocab_size", "sliding_window",
           "num_hidden_layers", "max_position_embeddings", "rope_theta",
           "rms_norm_eps")


def build(config):
    """The program's model from the file's constructor and arguments, in
    bf16, its sizes checked against the file's. (common.build_model hands
    it the seed's leaves.)"""
    import paddle_tpu as paddle
    from paddle_tpu import models

    prog = config["program"]
    paddle.seed(0)
    cfg = getattr(models, prog["constructor"])(**prog["constructor_args"])
    for k in CHECKED:
        if getattr(cfg, k) != config[k]:
            raise SystemExit(f"benchmark: the program's {k}="
                             f"{getattr(cfg, k)} is not the file's {config[k]}")
    model = models.LlamaForCausalLM(cfg)
    model.bfloat16()
    return model


def serving(model, config):
    """The adapter that BatchScheduler drives."""
    from paddle_tpu.inference.paged_llama import PagedLlamaAdapter

    return PagedLlamaAdapter(model, **config["program"]["pool"])


# --------------------------------------------------------------------------
# 3. the plain reference: float32 jax.numpy on lib/reference.py's parts,
#    nothing of the program. mode="int8" is the control.
# --------------------------------------------------------------------------
def layer(x, lw, pos, cfg, mode):
    """One decoder block. x [B, S, H] float32."""
    b, s, _ = x.shape
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = R.rms(x, lw["ln1"], eps)
    q = R.rope(R.mm(h, lw["q"], mode).reshape(b, s, nh, d), pos, theta)
    k = R.rope(R.mm(h, lw["k"], mode).reshape(b, s, nkv, d), pos, theta)
    v = R.mm(h, lw["v"], mode).reshape(b, s, nkv, d)
    a = R.attention(q, k, v, int(cfg.get("sliding_window") or 0))
    x = x + R.mm(a.reshape(b, s, nh * d), lw["o"], mode)
    h = R.rms(x, lw["ln2"], eps)
    return x + R.mm(jax.nn.silu(R.mm(h, lw["gate"], mode))
                    * R.mm(h, lw["up"], mode), lw["down"], mode)


@functools.lru_cache(maxsize=None)
def _serve_fns(cfg_json, mode):
    cfg = json.loads(cfg_json)              # the whole file, as it was read

    @jax.jit
    def embed(emb, ids):
        return emb.astype(jnp.float32)[ids]

    @jax.jit
    def one_layer(x, lw):
        pos = jnp.arange(x.shape[1])
        return layer(x, R.f32(lw), pos, cfg, mode)

    @jax.jit
    def final(x, norm, head, gather):
        """best logit, argmax and the logits of ``gather`` [B, S, G] at
        every position, one sequence at a time (the [S, V] logits of a
        block never all exist at once)."""
        norm, head = norm.astype(jnp.float32), head.astype(jnp.float32)

        def one(args):
            xs, gs = args
            lg = R.mm(R.rms(xs, norm, cfg["rms_norm_eps"]), head, mode)
            return (lg.max(-1), lg.argmax(-1).astype(jnp.int32),
                    jnp.take_along_axis(lg, gs, -1))
        return jax.lax.map(one, (x, gather))
    return embed, one_layer, final


def serve_logits(cfg, seed, ids, gather, mode="f32"):
    """Teacher-forced logits, layer by layer over a block of sequences.
    ids [B, S] int32 (padded on the right; causal, so padding never
    reaches a real position); gather [B, S, G] token ids whose logits are
    wanted at each position. Returns numpy (best [B,S], argmax [B,S],
    gathered [B,S,G]) of the logits that predict position s+1."""
    embed, one_layer, final = _serve_fns(json.dumps(cfg, sort_keys=True), mode)
    spec = W.spec(leaves(cfg), LEAF_NAMES, cfg["initializer_range"])
    top = W.make_top(spec, seed)
    x = embed(top["embed"], jnp.asarray(ids, jnp.int32))
    for li in range(cfg["num_hidden_layers"]):
        x = one_layer(x, W.make_layer(spec, seed, li))
    best, arg, got = final(x, top["norm"], top["head"],
                           jnp.asarray(gather, jnp.int32))
    return np.asarray(best), np.asarray(arg), np.asarray(got)


def lm_loss(params, ids, cfg, mode="f32", rows=None):
    """Mean next-token cross-entropy: logits[:, :-1] predict ids[:, 1:].
    Each layer is rematerialised. ``rows`` keeps only those sequences
    (the half-batch fault)."""
    if rows is not None:
        ids = ids[jnp.asarray(rows)]
    pos = jnp.arange(ids.shape[1])
    x = params["embed"][ids]
    for lw in params["layers"]:
        x = jax.checkpoint(
            lambda x_, lw_: layer(x_, lw_, pos, cfg, mode))(x, lw)

    @jax.checkpoint
    def seq_loss(args):
        xs, ys = args
        lg = R.mm(R.rms(xs[:-1], params["norm"], cfg["rms_norm_eps"]),
                  params["head"], mode)
        lse = jax.nn.logsumexp(lg, -1)
        return jnp.sum(lse - jnp.take_along_axis(lg, ys[1:, None], -1)[:, 0])

    tot = jnp.sum(jax.lax.map(seq_loss, (x, ids)))
    return tot / (ids.shape[0] * (ids.shape[1] - 1))


# --------------------------------------------------------------------------
# 4. counts: operations and bytes that the algorithm needs, from shapes
#    alone. Recomputed operations are never counted.
# --------------------------------------------------------------------------
def matmul_params(cfg, head=True):
    """Parameters that a token multiplies: the projections and the MLP of
    every layer held here and, with ``head``, the output head. Not the
    embedding (a lookup) and not the norm gains."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    per_layer = h * qd + 2 * h * kv + qd * h + 3 * h * f
    n = per_layer * cfg["num_hidden_layers"]
    return n + (h * cfg["vocab_size"] if head else 0)


def attention_flops_per_pair(cfg):
    """QK^T and PV of every query head for one (query, key) pair."""
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"]


def train_step_flops(cfg, batch, seq_len):
    """Forward and backward of one optimizer step: 6 x matmul parameters a
    token, plus attention (forward 4, backward 8 a pair and head-dim)."""
    return (6 * matmul_params(cfg) * batch * seq_len
            + flash_train_flops(cfg, batch, seq_len))


def flash_train_flops(cfg, batch, seq_len):
    """The attention kernels' own share of a step: forward 4 and backward 8
    multiply-adds x 2 a pair, head and head-dim; the backward's recomputed
    scores are not counted."""
    pairs = batch * causal_pairs(seq_len, cfg.get("sliding_window"))
    return 3 * attention_flops_per_pair(cfg) * pairs * cfg["num_hidden_layers"]


def serve_flops(cfg, tokens_fed, rows_sampled, context_of_fed):
    """Forward work of serving: every token fed (prompt or decode) goes
    through the layers' matmuls; the head runs once for each row that is
    sampled; attention pairs each fed token with its context
    (``context_of_fed``: the sum over fed tokens of the positions they see,
    at most the window)."""
    return (2 * matmul_params(cfg, head=False) * tokens_fed
            + 2 * cfg["hidden_size"] * cfg["vocab_size"] * rows_sampled
            + attention_flops_per_pair(cfg) * context_of_fed
            * cfg["num_hidden_layers"])


def ragged_attention_call(cfg, tokens_fed, context_of_fed, context_of_rows,
                          kv_bytes=2, act_bytes=2):
    """One ragged paged-attention call of one layer: operations, and the
    bytes it must move: every cached key and value of every row once
    (``context_of_rows``: sum over rows of the cached length, after this
    step's append, at most the window), queries in and outputs back."""
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    ops = attention_flops_per_pair(cfg) * context_of_fed
    byts = (2 * nkv * d * kv_bytes * context_of_rows
            + 2 * nh * d * act_bytes * tokens_fed)
    return ops, byts
