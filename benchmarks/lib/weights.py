"""Random weights from ``--seed``, made on the device in the type they
are served or trained in. One jitted call makes every leaf for the
program; the reference makes the same leaves again, layer by layer, from
the same seed, so it takes nothing that the program has touched.

Leaf names are the benchmark's own: ``embed`` [V, H], ``norm`` [H],
``head`` [H, V] and, per layer, ``ln1`` ``q`` ``k`` ``v`` ``o`` ``ln2``
``gate`` ``up`` ``down``; a linear weight is [in, out] (y = x @ W)."""
import functools

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("ln1", "q", "k", "v", "o", "ln2", "gate", "up", "down")
TOP_LEAVES = ("embed", "norm", "head")


def leaf_shapes(cfg):
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    return {"embed": (v, h), "norm": (h,), "head": (h, v),
            "ln1": (h,), "q": (h, qd), "k": (h, kv), "v": (h, kv),
            "o": (qd, h), "ln2": (h,), "gate": (h, f), "up": (h, f),
            "down": (f, h)}


def seed_key(seed):
    """``--seed`` may be a little over 2**31: fold it in 31 bits at a time."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _leaf_key(key, layer, name):
    names = TOP_LEAVES + LAYER_LEAVES
    return jax.random.fold_in(jax.random.fold_in(key, layer + 1),
                              names.index(name))


def _cfg_key(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


@functools.lru_cache(maxsize=None)
def _makers(cfg_key, dtype):
    """(top leaves, one layer's leaves, every leaf) as three jitted
    functions of the key. A leaf's values depend on the key, its layer and
    its name alone, so the three give the same arrays."""
    cfg = dict(cfg_key)
    shapes, std = leaf_shapes(cfg), float(cfg["initializer_range"])

    def leaf(key, layer, name):
        n = jax.random.normal(_leaf_key(key, layer, name), shapes[name],
                              jnp.float32)
        if len(shapes[name]) == 1:              # norm gains: around one
            return (1.0 + 0.1 * n).astype(dtype)
        return (std * n).astype(dtype)

    def top(key):
        return {n: leaf(key, -1, n) for n in TOP_LEAVES}

    def layer(key, li):
        return {n: leaf(key, li, n) for n in LAYER_LEAVES}

    def everything(key):
        return dict(top(key), layers=[
            layer(key, li) for li in range(cfg["num_hidden_layers"])])

    return jax.jit(top), jax.jit(layer), jax.jit(everything)


def make_all(cfg, seed, dtype=jnp.bfloat16):
    """Every leaf in one jitted call (what the program is handed)."""
    return _makers(_cfg_key(cfg), jnp.dtype(dtype))[2](seed_key(seed))


def make_layer(cfg, seed, layer, dtype=jnp.bfloat16):
    return _makers(_cfg_key(cfg), jnp.dtype(dtype))[1](
        seed_key(seed), jnp.int32(layer))


def make_top(cfg, seed, dtype=jnp.bfloat16):
    return _makers(_cfg_key(cfg), jnp.dtype(dtype))[0](seed_key(seed))


# how the benchmark's leaf names map onto the program's parameter names
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


def flat_names(cfg):
    """[(benchmark leaf path, program parameter name)] in a fixed order."""
    out = [(n, PROGRAM_NAMES[n]) for n in TOP_LEAVES]
    for i in range(cfg["num_hidden_layers"]):
        out += [(f"layers.{i}.{n}", PROGRAM_NAMES[n].format(i=i))
                for n in LAYER_LEAVES]
    return out


def get_leaf(tree, path):
    parts = path.split(".")
    if parts[0] == "layers":
        return tree["layers"][int(parts[1])][parts[2]]
    return tree[path]
