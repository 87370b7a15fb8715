"""Random sampling ops (upstream: python/paddle/tensor/random.py).

All draws go through the global counter-based generator
(framework/random.py) so they are reproducible under ``paddle.seed`` and
trace-capturable by the compiled step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import Tensor, _as_tensor, apply_op
from ..framework.dtype import to_np_dtype
from ..framework.random import next_key
from .creation import _shape


def rand(shape, dtype="float32", name=None):
    return uniform(shape, dtype, min=0.0, max=1.0)


def randn(shape, dtype="float32", name=None):
    return standard_normal(shape, dtype)


def standard_normal(shape, dtype="float32", name=None):
    k = next_key()
    return Tensor(jax.random.normal(k, _shape(shape), to_np_dtype(dtype)))


def normal(mean=0.0, std=1.0, shape=None, name=None):
    if isinstance(mean, Tensor) or isinstance(std, Tensor):
        m = mean._data if isinstance(mean, Tensor) else mean
        s = std._data if isinstance(std, Tensor) else std
        shp = jnp.broadcast_shapes(
            jnp.shape(m), jnp.shape(s)
        )
        k = next_key()
        return Tensor(jax.random.normal(k, shp) * s + m)
    shp = _shape(shape) if shape is not None else ()
    k = next_key()
    return Tensor(jax.random.normal(k, shp) * std + mean)


def uniform(shape, dtype="float32", min=-1.0, max=1.0, seed=0, name=None):
    k = next_key() if not seed else jax.random.PRNGKey(seed)
    lo = min.item() if isinstance(min, Tensor) else float(min)
    hi = max.item() if isinstance(max, Tensor) else float(max)
    return Tensor(
        jax.random.uniform(k, _shape(shape), to_np_dtype(dtype), lo, hi)
    )


def uniform_(x, min=-1.0, max=1.0, seed=0, name=None):
    x = _as_tensor(x)
    x.set_value(uniform(x.shape, x.dtype, min, max, seed))
    return x


def randint(low=0, high=None, shape=(1,), dtype="int64", name=None):
    if high is None:
        low, high = 0, low
    k = next_key()
    return Tensor(
        jax.random.randint(k, _shape(shape), int(low), int(high),
                           to_np_dtype(dtype))
    )


def randint_like(x, low=0, high=None, dtype=None, name=None):
    x = _as_tensor(x)
    return randint(low, high, x.shape, dtype or x.dtype)


def randperm(n, dtype="int64", name=None):
    k = next_key()
    return Tensor(jax.random.permutation(k, int(n)).astype(to_np_dtype(dtype)))


def bernoulli(x, name=None):
    x = _as_tensor(x)
    k = next_key()
    return Tensor(
        jax.random.bernoulli(k, np.asarray(x._data, np.float32) if False else x._data.astype(jnp.float32)).astype(x._data.dtype)
    )


def bernoulli_(x, p=0.5, name=None):
    x = _as_tensor(x)
    k = next_key()
    x.set_value(jax.random.bernoulli(k, p, tuple(x.shape)).astype(x._data.dtype))
    return x


def multinomial(x, num_samples=1, replacement=False, name=None):
    x = _as_tensor(x)
    k = next_key()
    probs = x._data / jnp.sum(x._data, axis=-1, keepdims=True)
    logits = jnp.log(jnp.maximum(probs, 1e-30))
    if x.ndim == 1:
        out = jax.random.choice(
            k, x.shape[0], (num_samples,), replace=replacement, p=probs
        )
    else:
        ks = jax.random.split(k, x.shape[0])
        out = jnp.stack([
            jax.random.choice(kk, x.shape[-1], (num_samples,),
                              replace=replacement, p=pp)
            for kk, pp in zip(ks, probs)
        ])
    return Tensor(out.astype(jnp.int64))


def poisson(x, name=None):
    x = _as_tensor(x)
    k = next_key()
    return Tensor(jax.random.poisson(k, x._data).astype(x._data.dtype))


def exponential_(x, lam=1.0, name=None):
    x = _as_tensor(x)
    k = next_key()
    x.set_value(jax.random.exponential(k, tuple(x.shape)) / lam)
    return x


def rand_like(x, dtype=None, name=None):
    x = _as_tensor(x)
    return rand(x.shape, dtype or x.dtype)


def randn_like(x, dtype=None, name=None):
    x = _as_tensor(x)
    return randn(x.shape, dtype or x.dtype)


def normal_(x, mean=0.0, std=1.0, name=None):
    x = _as_tensor(x)
    k = next_key()
    x.set_value(
        jax.random.normal(k, tuple(x.shape), x._data.dtype) * std + mean
    )
    return x


def geometric_(x, probs, name=None):
    """Fill x in-place with Geometric(probs) draws, support {1, 2, ...}
    (upstream Tensor.geometric_): k = ceil(log U / log(1 - p))."""
    x = _as_tensor(x)
    p = _as_tensor(probs)._data if not isinstance(probs, float) else probs
    k = next_key()
    u = jax.random.uniform(
        k, tuple(x.shape), minval=jnp.finfo(jnp.float32).tiny)
    draws = jnp.ceil(jnp.log(u) / jnp.log1p(-p))
    x.set_value(draws.astype(x._data.dtype))
    return x


def binomial(count, prob, name=None):
    """Elementwise binomial draws (upstream paddle.binomial)."""
    from ..framework.random import next_key

    count = _as_tensor(count)
    prob = _as_tensor(prob)
    k = next_key()

    def f(n, p):
        return jax.random.binomial(
            k, n.astype(jnp.float32), p
        ).astype(jnp.int64)

    return apply_op("binomial", f, count, prob, differentiable=False)


def standard_gamma(x, name=None):
    """Gamma(alpha=x, scale=1) draws (upstream standard_gamma)."""
    from ..framework.random import next_key

    x = _as_tensor(x)
    k = next_key()
    return apply_op(
        "standard_gamma",
        lambda a: jax.random.gamma(k, a.astype(jnp.float32)),
        x, differentiable=False,
    )


def log_normal(mean=1.0, std=2.0, shape=None, name=None):
    """Log-normal draws (upstream log_normal)."""
    from ..framework.random import next_key

    k = next_key()
    shp = tuple(int(s) for s in (shape or [1]))
    out = jnp.exp(
        float(mean) + float(std) * jax.random.normal(k, shp)
    )
    return Tensor(out)


def cauchy_(x, loc=0, scale=1, name=None):
    """Fill x in place with Cauchy(loc, scale) draws (upstream
    paddle.Tensor.cauchy_)."""
    from .math import _inplace

    x = _as_tensor(x)
    k = next_key()

    def f(a):
        u = jax.random.uniform(k, a.shape, jnp.float32, 1e-7, 1 - 1e-7)
        v = loc + scale * jnp.tan(jnp.pi * (u - 0.5))
        return v.astype(a.dtype)

    return _inplace(x, apply_op("cauchy", f, x, differentiable=False))
