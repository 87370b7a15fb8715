"""Random weights from ``--seed``, made on the device in the type they
are served or trained in. One jitted call makes every leaf for the
program; the reference makes the same leaves again, layer by layer, from
the same seed, so it takes nothing that the program has touched.

Which leaves there are is the family's to say (families/<family>.py), and
reaches this file as plain data through ``spec``: the family's
``leaves(cfg)``, ``{path: (shape, init)}`` in a fixed order, a path being
``<name>`` for the top of the model and ``layers.<i>.<name>`` for layer i,
``init`` one of "normal", "gain", "zeros" and ("const", value); its
``LEAF_NAMES``, every name once; and the file's ``initializer_range``. A
drawn leaf's values depend on the key, its layer and its name's place in
``LEAF_NAMES`` alone."""
import functools

import jax
import jax.numpy as jnp


def seed_key(seed):
    """``--seed`` may be a little over 2**31: fold it in 31 bits at a time."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def split(path):
    """(layer, name) of a leaf path; the top of the model is layer -1."""
    if path.startswith("layers."):
        _, i, name = path.split(".", 2)
        return int(i), name
    return -1, path


def spec(leaves, names, std):
    """What the makers need of an architecture, as something a cache can
    key on: ``W.spec(family.leaves(cfg), family.LEAF_NAMES,
    cfg["initializer_range"])``."""
    return (tuple((path, tuple(shape), init if isinstance(init, str)
                   else tuple(init)) for path, (shape, init) in leaves.items()),
            tuple(names), float(std))


@functools.lru_cache(maxsize=None)
def _makers(spec_, dtype):
    """(top leaves, one layer's leaves, every leaf) as jitted functions of
    the key; the three give the same arrays. Layers that hold the same
    leaves share one program, which takes the layer's index as data."""
    leaves, names, std = spec_
    by_layer = {}
    for path, shape, init in leaves:
        li, name = split(path)
        by_layer.setdefault(li, {})[name] = (shape, init)

    def leaf(key, li, name, shape, init):
        if init == "zeros":
            return jnp.zeros(shape, dtype)
        if init[0] == "const":
            return jnp.full(shape, init[1], dtype)
        if init not in ("normal", "gain"):
            raise SystemExit(f"benchmark: leaf {name!r} has no init {init!r}")
        n = jax.random.normal(
            jax.random.fold_in(jax.random.fold_in(key, li + 1),
                               names.index(name)), shape, jnp.float32)
        if init == "gain":                      # norm gains: around one
            return (1.0 + 0.1 * n).astype(dtype)
        return (std * n).astype(dtype)

    def some(kind):
        return lambda key, li: {n: leaf(key, li, n, *kind[n]) for n in kind}

    top = by_layer.pop(-1)
    kinds = [by_layer[li] for li in range(len(by_layer))]

    def everything(key):
        return dict(some(top)(key, -1),
                    layers=[some(k)(key, li) for li, k in enumerate(kinds)])

    programs = {}                     # one a kind of layer, not one a layer
    return (jax.jit(lambda key: some(top)(key, -1)),
            [programs.setdefault(tuple(k.items()), jax.jit(some(k)))
             for k in kinds], jax.jit(everything))


def make_all(spec_, seed, dtype=jnp.bfloat16):
    """Every leaf in one jitted call (what the program is handed):
    {name: leaf, ..., "layers": [{name: leaf}]}."""
    return _makers(spec_, jnp.dtype(dtype))[2](seed_key(seed))


def make_layer(spec_, seed, layer, dtype=jnp.bfloat16):
    return _makers(spec_, jnp.dtype(dtype))[1][layer](
        seed_key(seed), jnp.int32(layer))


def make_top(spec_, seed, dtype=jnp.bfloat16):
    return _makers(spec_, jnp.dtype(dtype))[0](seed_key(seed))


def get_leaf(tree, path):
    layer, name = split(path)
    return tree[name] if layer < 0 else tree["layers"][layer][name]
