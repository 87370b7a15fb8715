"""Probability distributions (upstream: python/paddle/distribution/).

TPU-first: every ``sample`` draws through the framework's counter-based
PRNG (``framework.random.next_key``) so sampling stays reproducible and
trace-friendly under ``to_static``; densities are jnp/`jax.scipy.stats`
math that fuses on the VPU, and every method routes through ``apply_op``
so reparameterized samples (``rsample``) carry gradients on the tape.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import Tensor, apply_op, _as_tensor
from ..framework.random import next_key

__all__ = [
    "Distribution", "Normal", "Uniform", "Bernoulli", "Categorical",
    "Beta", "Dirichlet", "Exponential", "Gamma", "Geometric", "Gumbel",
    "Laplace", "LogNormal", "Multinomial", "Poisson", "Cauchy",
    "StudentT", "Independent", "kl_divergence", "register_kl",
]


def _shape_tuple(shape):
    if shape is None:
        return ()
    if isinstance(shape, (list, tuple)):
        return tuple(int(s) for s in shape)
    return (int(shape),)


class Distribution:
    """Base API (upstream: python/paddle/distribution/distribution.py)."""

    def __init__(self, batch_shape=(), event_shape=()):
        self._batch_shape = _shape_tuple(batch_shape)
        self._event_shape = _shape_tuple(event_shape)

    @property
    def batch_shape(self):
        return self._batch_shape

    @property
    def event_shape(self):
        return self._event_shape

    def sample(self, shape=()):
        # default: detached reparameterized draw — distributions with
        # an rsample get sample() for free; discrete ones override
        s = self.rsample(shape)
        s.stop_gradient = True
        return s

    def rsample(self, shape=()):
        raise NotImplementedError

    def log_prob(self, value):
        raise NotImplementedError

    def prob(self, value):
        from ..tensor.math import exp

        return exp(self.log_prob(value))

    def entropy(self):
        raise NotImplementedError

    def kl_divergence(self, other):
        return kl_divergence(self, other)


def _param(v):
    t = _as_tensor(v if not isinstance(v, (int, float))
                   else np.asarray(v, "float32"))
    return t


class Normal(Distribution):
    def __init__(self, loc, scale, name=None):
        self.loc = _param(loc)
        self.scale = _param(scale)
        super().__init__(np.broadcast_shapes(
            tuple(self.loc.shape), tuple(self.scale.shape)))

    @property
    def mean(self):
        return self.loc

    @property
    def variance(self):
        from ..tensor.math import square

        return square(self.scale)

    def rsample(self, shape=()):
        shape = _shape_tuple(shape)
        k = next_key()

        def f(mu, sig):
            out_shape = shape + np.broadcast_shapes(mu.shape, sig.shape)
            eps = jax.random.normal(k, out_shape, jnp.float32)
            return mu + sig * eps

        return apply_op("normal_rsample", f, self.loc, self.scale)

    def log_prob(self, value):
        value = _as_tensor(value)

        def f(v, mu, sig):
            vf = v.astype(jnp.float32)
            return (
                -jnp.square(vf - mu) / (2.0 * jnp.square(sig))
                - jnp.log(sig) - 0.5 * math.log(2.0 * math.pi)
            )

        return apply_op("normal_log_prob", f, value, self.loc, self.scale)

    def entropy(self):
        def f(sig):
            return 0.5 + 0.5 * math.log(2.0 * math.pi) + jnp.log(sig)

        return apply_op("normal_entropy", f, self.scale)


class LogNormal(Normal):
    def rsample(self, shape=()):
        from ..tensor.math import exp

        return exp(super().rsample(shape))

    def log_prob(self, value):
        value = _as_tensor(value)

        def f(v, mu, sig):
            vf = v.astype(jnp.float32)
            lv = jnp.log(vf)
            return (
                -jnp.square(lv - mu) / (2.0 * jnp.square(sig))
                - jnp.log(sig) - lv - 0.5 * math.log(2.0 * math.pi)
            )

        return apply_op("lognormal_log_prob", f, value, self.loc,
                        self.scale)

    def entropy(self):
        def f(mu, sig):
            return mu + 0.5 + 0.5 * math.log(2.0 * math.pi) + jnp.log(sig)

        return apply_op("lognormal_entropy", f, self.loc, self.scale)


class Uniform(Distribution):
    def __init__(self, low, high, name=None):
        self.low = _param(low)
        self.high = _param(high)
        super().__init__(np.broadcast_shapes(
            tuple(self.low.shape), tuple(self.high.shape)))

    def rsample(self, shape=()):
        shape = _shape_tuple(shape)
        k = next_key()

        def f(lo, hi):
            out_shape = shape + np.broadcast_shapes(lo.shape, hi.shape)
            u = jax.random.uniform(k, out_shape, jnp.float32)
            return lo + (hi - lo) * u

        return apply_op("uniform_rsample", f, self.low, self.high)

    def log_prob(self, value):
        value = _as_tensor(value)

        def f(v, lo, hi):
            inside = (v >= lo) & (v < hi)
            return jnp.where(
                inside, -jnp.log(hi - lo), -jnp.inf
            )

        return apply_op("uniform_log_prob", f, value, self.low, self.high)

    def entropy(self):
        return apply_op(
            "uniform_entropy", lambda lo, hi: jnp.log(hi - lo),
            self.low, self.high,
        )


class Bernoulli(Distribution):
    def __init__(self, probs, name=None):
        self.probs = _param(probs)
        super().__init__(tuple(self.probs.shape))

    def sample(self, shape=()):
        shape = _shape_tuple(shape)
        k = next_key()

        def f(p):
            return jax.random.bernoulli(
                k, p, shape + tuple(p.shape)
            ).astype(jnp.float32)

        return apply_op("bernoulli_sample", f, self.probs,
                        differentiable=False)

    def log_prob(self, value):
        value = _as_tensor(value)

        def f(v, p):
            pf = jnp.clip(p, 1e-7, 1.0 - 1e-7)
            vf = v.astype(jnp.float32)
            return vf * jnp.log(pf) + (1.0 - vf) * jnp.log1p(-pf)

        return apply_op("bernoulli_log_prob", f, value, self.probs)

    def entropy(self):
        def f(p):
            pf = jnp.clip(p, 1e-7, 1.0 - 1e-7)
            return -(pf * jnp.log(pf) + (1 - pf) * jnp.log1p(-pf))

        return apply_op("bernoulli_entropy", f, self.probs)


class Categorical(Distribution):
    def __init__(self, logits, name=None):
        self.logits = _param(logits)
        super().__init__(tuple(self.logits.shape)[:-1])

    @property
    def probs(self):
        from ..nn.functional import softmax

        return softmax(self.logits, axis=-1)

    def sample(self, shape=()):
        shape = _shape_tuple(shape)
        k = next_key()

        def f(lg):
            return jax.random.categorical(
                k, lg, axis=-1, shape=shape + tuple(lg.shape[:-1])
            ).astype(jnp.int64)

        return apply_op("categorical_sample", f, self.logits,
                        differentiable=False)

    def log_prob(self, value):
        value = _as_tensor(value)

        def f(v, lg):
            logp = jax.nn.log_softmax(lg, axis=-1)
            return jnp.take_along_axis(
                logp, v.astype(jnp.int32)[..., None], axis=-1
            )[..., 0]

        return apply_op("categorical_log_prob", f, value, self.logits)

    def entropy(self):
        def f(lg):
            logp = jax.nn.log_softmax(lg, axis=-1)
            return -jnp.sum(jnp.exp(logp) * logp, axis=-1)

        return apply_op("categorical_entropy", f, self.logits)


class Multinomial(Distribution):
    def __init__(self, total_count, probs, name=None):
        self.total_count = int(total_count)
        self.probs = _param(probs)
        super().__init__(tuple(self.probs.shape)[:-1],
                         tuple(self.probs.shape)[-1:])

    def sample(self, shape=()):
        shape = _shape_tuple(shape)
        k = next_key()
        n = self.total_count

        def f(p):
            logits = jnp.log(jnp.clip(p, 1e-30, None))
            draws = jax.random.categorical(
                k, logits, axis=-1,
                shape=(n,) + shape + tuple(p.shape[:-1]),
            )
            onehot = jax.nn.one_hot(draws, p.shape[-1])
            return jnp.sum(onehot, axis=0)

        return apply_op("multinomial_sample", f, self.probs,
                        differentiable=False)

    def log_prob(self, value):
        value = _as_tensor(value)

        def f(v, p):
            vf = v.astype(jnp.float32)
            logp = jnp.log(jnp.clip(p, 1e-30, None))
            from jax.scipy.special import gammaln

            return (
                gammaln(jnp.sum(vf, -1) + 1.0)
                - jnp.sum(gammaln(vf + 1.0), -1)
                + jnp.sum(vf * logp, -1)
            )

        return apply_op("multinomial_log_prob", f, value, self.probs)


class Beta(Distribution):
    def __init__(self, alpha, beta, name=None):
        self.alpha = _param(alpha)
        self.beta = _param(beta)
        super().__init__(np.broadcast_shapes(
            tuple(self.alpha.shape), tuple(self.beta.shape)))

    def sample(self, shape=()):
        shape = _shape_tuple(shape)
        k = next_key()

        def f(a, b):
            out = shape + np.broadcast_shapes(a.shape, b.shape)
            return jax.random.beta(k, a, b, out)

        s = apply_op("beta_sample", f, self.alpha, self.beta,
                     differentiable=False)
        return s

    def log_prob(self, value):
        value = _as_tensor(value)

        def f(v, a, b):
            from jax.scipy.stats import beta as sbeta

            return sbeta.logpdf(v.astype(jnp.float32), a, b)

        return apply_op("beta_log_prob", f, value, self.alpha, self.beta)

    def entropy(self):
        def f(a, b):
            from jax.scipy.special import betaln, digamma

            return (
                betaln(a, b) - (a - 1) * digamma(a)
                - (b - 1) * digamma(b)
                + (a + b - 2) * digamma(a + b)
            )

        return apply_op("beta_entropy", f, self.alpha, self.beta)


class Dirichlet(Distribution):
    def __init__(self, concentration, name=None):
        self.concentration = _param(concentration)
        super().__init__(tuple(self.concentration.shape)[:-1],
                         tuple(self.concentration.shape)[-1:])

    def sample(self, shape=()):
        shape = _shape_tuple(shape)
        k = next_key()

        def f(c):
            return jax.random.dirichlet(
                k, c, shape + tuple(c.shape[:-1])
            )

        return apply_op("dirichlet_sample", f, self.concentration,
                        differentiable=False)

    def log_prob(self, value):
        value = _as_tensor(value)

        def f(v, c):
            from jax.scipy.special import gammaln

            vf = v.astype(jnp.float32)
            return (
                jnp.sum((c - 1.0) * jnp.log(vf), -1)
                + gammaln(jnp.sum(c, -1))
                - jnp.sum(gammaln(c), -1)
            )

        return apply_op("dirichlet_log_prob", f, value,
                        self.concentration)

    def entropy(self):
        def f(c):
            from jax.scipy.special import digamma, gammaln

            c0 = jnp.sum(c, -1)
            kdim = c.shape[-1]
            return (
                jnp.sum(gammaln(c), -1) - gammaln(c0)
                + (c0 - kdim) * digamma(c0)
                - jnp.sum((c - 1.0) * digamma(c), -1)
            )

        return apply_op("dirichlet_entropy", f, self.concentration)


class Exponential(Distribution):
    def __init__(self, rate, name=None):
        self.rate = _param(rate)
        super().__init__(tuple(self.rate.shape))

    def rsample(self, shape=()):
        shape = _shape_tuple(shape)
        k = next_key()

        def f(r):
            u = jax.random.exponential(k, shape + tuple(r.shape))
            return u / r

        return apply_op("exponential_rsample", f, self.rate)

    def log_prob(self, value):
        value = _as_tensor(value)
        return apply_op(
            "exponential_log_prob",
            lambda v, r: jnp.log(r) - r * v.astype(jnp.float32),
            value, self.rate,
        )

    def entropy(self):
        return apply_op(
            "exponential_entropy", lambda r: 1.0 - jnp.log(r), self.rate
        )


class Gamma(Distribution):
    def __init__(self, concentration, rate, name=None):
        self.concentration = _param(concentration)
        self.rate = _param(rate)
        super().__init__(np.broadcast_shapes(
            tuple(self.concentration.shape), tuple(self.rate.shape)))

    def sample(self, shape=()):
        shape = _shape_tuple(shape)
        k = next_key()

        def f(c, r):
            out = shape + np.broadcast_shapes(c.shape, r.shape)
            return jax.random.gamma(k, c, out) / r

        return apply_op("gamma_sample", f, self.concentration, self.rate,
                        differentiable=False)

    def log_prob(self, value):
        value = _as_tensor(value)

        def f(v, c, r):
            from jax.scipy.special import gammaln

            vf = v.astype(jnp.float32)
            return (
                c * jnp.log(r) + (c - 1.0) * jnp.log(vf) - r * vf
                - gammaln(c)
            )

        return apply_op("gamma_log_prob", f, value, self.concentration,
                        self.rate)

    def entropy(self):
        def f(c, r):
            from jax.scipy.special import digamma, gammaln

            return c - jnp.log(r) + gammaln(c) + (1.0 - c) * digamma(c)

        return apply_op("gamma_entropy", f, self.concentration, self.rate)


class Geometric(Distribution):
    """P(X=k) = (1-p)^k p, k = 0, 1, ... (failures before success)."""

    def __init__(self, probs, name=None):
        self.probs = _param(probs)
        super().__init__(tuple(self.probs.shape))

    def sample(self, shape=()):
        shape = _shape_tuple(shape)
        k = next_key()

        def f(p):
            u = jax.random.uniform(
                k, shape + tuple(p.shape), jnp.float32, 1e-7, 1.0
            )
            return jnp.floor(jnp.log(u) / jnp.log1p(-p))

        return apply_op("geometric_sample", f, self.probs,
                        differentiable=False)

    def log_prob(self, value):
        value = _as_tensor(value)
        return apply_op(
            "geometric_log_prob",
            lambda v, p: v.astype(jnp.float32) * jnp.log1p(-p)
            + jnp.log(p),
            value, self.probs,
        )

    def entropy(self):
        def f(p):
            q = 1.0 - p
            return -(q * jnp.log(q) + p * jnp.log(p)) / p

        return apply_op("geometric_entropy", f, self.probs)


class Gumbel(Distribution):
    def __init__(self, loc, scale, name=None):
        self.loc = _param(loc)
        self.scale = _param(scale)
        super().__init__(np.broadcast_shapes(
            tuple(self.loc.shape), tuple(self.scale.shape)))

    def rsample(self, shape=()):
        shape = _shape_tuple(shape)
        k = next_key()

        def f(mu, b):
            out = shape + np.broadcast_shapes(mu.shape, b.shape)
            g = jax.random.gumbel(k, out)
            return mu + b * g

        return apply_op("gumbel_rsample", f, self.loc, self.scale)

    def log_prob(self, value):
        value = _as_tensor(value)

        def f(v, mu, b):
            z = (v.astype(jnp.float32) - mu) / b
            return -(z + jnp.exp(-z)) - jnp.log(b)

        return apply_op("gumbel_log_prob", f, value, self.loc, self.scale)

    def entropy(self):
        return apply_op(
            "gumbel_entropy",
            lambda b: jnp.log(b) + 1.0 + np.euler_gamma, self.scale,
        )


class Laplace(Distribution):
    def __init__(self, loc, scale, name=None):
        self.loc = _param(loc)
        self.scale = _param(scale)
        super().__init__(np.broadcast_shapes(
            tuple(self.loc.shape), tuple(self.scale.shape)))

    def rsample(self, shape=()):
        shape = _shape_tuple(shape)
        k = next_key()

        def f(mu, b):
            out = shape + np.broadcast_shapes(mu.shape, b.shape)
            return mu + b * jax.random.laplace(k, out)

        return apply_op("laplace_rsample", f, self.loc, self.scale)

    def log_prob(self, value):
        value = _as_tensor(value)

        def f(v, mu, b):
            return -jnp.abs(v.astype(jnp.float32) - mu) / b \
                - jnp.log(2.0 * b)

        return apply_op("laplace_log_prob", f, value, self.loc,
                        self.scale)

    def entropy(self):
        return apply_op(
            "laplace_entropy", lambda b: 1.0 + jnp.log(2.0 * b),
            self.scale,
        )


class Poisson(Distribution):
    def __init__(self, rate, name=None):
        self.rate = _param(rate)
        super().__init__(tuple(self.rate.shape))

    def sample(self, shape=()):
        shape = _shape_tuple(shape)
        k = next_key()

        def f(r):
            return jax.random.poisson(
                k, r, shape + tuple(r.shape)
            ).astype(jnp.float32)

        return apply_op("poisson_sample", f, self.rate,
                        differentiable=False)

    def log_prob(self, value):
        value = _as_tensor(value)

        def f(v, r):
            from jax.scipy.special import gammaln

            vf = v.astype(jnp.float32)
            return vf * jnp.log(r) - r - gammaln(vf + 1.0)

        return apply_op("poisson_log_prob", f, value, self.rate)


class Cauchy(Distribution):
    def __init__(self, loc, scale, name=None):
        self.loc = _param(loc)
        self.scale = _param(scale)
        super().__init__(np.broadcast_shapes(
            tuple(self.loc.shape), tuple(self.scale.shape)))

    def rsample(self, shape=()):
        shape = _shape_tuple(shape)
        k = next_key()

        def f(mu, g):
            out = shape + np.broadcast_shapes(mu.shape, g.shape)
            return mu + g * jax.random.cauchy(k, out)

        return apply_op("cauchy_rsample", f, self.loc, self.scale)

    def log_prob(self, value):
        value = _as_tensor(value)

        def f(v, mu, g):
            z = (v.astype(jnp.float32) - mu) / g
            return -jnp.log(math.pi * g * (1.0 + z * z))

        return apply_op("cauchy_log_prob", f, value, self.loc, self.scale)

    def entropy(self):
        return apply_op(
            "cauchy_entropy",
            lambda g: jnp.log(4.0 * math.pi * g), self.scale,
        )


class StudentT(Distribution):
    def __init__(self, df, loc=0.0, scale=1.0, name=None):
        self.df = _param(df)
        self.loc = _param(loc)
        self.scale = _param(scale)
        super().__init__(np.broadcast_shapes(
            tuple(self.df.shape), tuple(self.loc.shape),
            tuple(self.scale.shape)))

    def sample(self, shape=()):
        shape = _shape_tuple(shape)
        k = next_key()

        def f(df, mu, sig):
            out = shape + np.broadcast_shapes(
                df.shape, mu.shape, sig.shape
            )
            return mu + sig * jax.random.t(k, df, out)

        return apply_op("studentt_sample", f, self.df, self.loc,
                        self.scale, differentiable=False)

    def log_prob(self, value):
        value = _as_tensor(value)

        def f(v, df, mu, sig):
            from jax.scipy.special import gammaln

            z = (v.astype(jnp.float32) - mu) / sig
            return (
                gammaln((df + 1.0) / 2.0) - gammaln(df / 2.0)
                - 0.5 * jnp.log(df * math.pi) - jnp.log(sig)
                - (df + 1.0) / 2.0 * jnp.log1p(z * z / df)
            )

        return apply_op("studentt_log_prob", f, value, self.df, self.loc,
                        self.scale)


class Independent(Distribution):
    """Reinterpret batch dims as event dims (upstream:
    python/paddle/distribution/independent.py)."""

    def __init__(self, base, reinterpreted_batch_rank):
        self.base = base
        self.rank = int(reinterpreted_batch_rank)
        bs = base.batch_shape
        super().__init__(bs[:len(bs) - self.rank],
                         bs[len(bs) - self.rank:] + base.event_shape)

    def sample(self, shape=()):
        return self.base.sample(shape)

    def rsample(self, shape=()):
        return self.base.rsample(shape)

    def log_prob(self, value):
        lp = self.base.log_prob(value)
        from ..tensor.math import sum as _sum

        axes = list(range(len(lp.shape) - self.rank, len(lp.shape)))
        return _sum(lp, axis=axes)

    def entropy(self):
        ent = self.base.entropy()
        from ..tensor.math import sum as _sum

        axes = list(range(len(ent.shape) - self.rank, len(ent.shape)))
        return _sum(ent, axis=axes)


# -- KL divergence registry -------------------------------------------------
_KL_REGISTRY = {}


def register_kl(p_cls, q_cls):
    def deco(fn):
        _KL_REGISTRY[(p_cls, q_cls)] = fn
        return fn

    return deco


def kl_divergence(p, q):
    for (pc, qc), fn in _KL_REGISTRY.items():
        if isinstance(p, pc) and isinstance(q, qc):
            return fn(p, q)
    raise NotImplementedError(
        f"no KL registered for ({type(p).__name__}, {type(q).__name__})"
    )


@register_kl(Normal, Normal)
def _kl_normal(p, q):
    def f(mu0, s0, mu1, s1):
        var0 = jnp.square(s0)
        var1 = jnp.square(s1)
        return (
            jnp.log(s1 / s0)
            + (var0 + jnp.square(mu0 - mu1)) / (2.0 * var1) - 0.5
        )

    return apply_op("kl_normal", f, p.loc, p.scale, q.loc, q.scale)


@register_kl(Uniform, Uniform)
def _kl_uniform(p, q):
    def f(lo0, hi0, lo1, hi1):
        kl = jnp.log((hi1 - lo1) / (hi0 - lo0))
        outside = (lo0 < lo1) | (hi0 > hi1)
        return jnp.where(outside, jnp.inf, kl)

    return apply_op("kl_uniform", f, p.low, p.high, q.low, q.high)


@register_kl(Bernoulli, Bernoulli)
def _kl_bernoulli(p, q):
    def f(p0, p1):
        a = jnp.clip(p0, 1e-7, 1 - 1e-7)
        b = jnp.clip(p1, 1e-7, 1 - 1e-7)
        return a * jnp.log(a / b) + (1 - a) * jnp.log((1 - a) / (1 - b))

    return apply_op("kl_bernoulli", f, p.probs, q.probs)


@register_kl(Categorical, Categorical)
def _kl_categorical(p, q):
    def f(l0, l1):
        lp = jax.nn.log_softmax(l0, -1)
        lq = jax.nn.log_softmax(l1, -1)
        return jnp.sum(jnp.exp(lp) * (lp - lq), -1)

    return apply_op("kl_categorical", f, p.logits, q.logits)


@register_kl(Beta, Beta)
def _kl_beta(p, q):
    def f(a0, b0, a1, b1):
        from jax.scipy.special import betaln, digamma

        t0 = a0 + b0
        return (
            betaln(a1, b1) - betaln(a0, b0)
            + (a0 - a1) * digamma(a0) + (b0 - b1) * digamma(b0)
            + (a1 - a0 + b1 - b0) * digamma(t0)
        )

    return apply_op("kl_beta", f, p.alpha, p.beta, q.alpha, q.beta)


@register_kl(Dirichlet, Dirichlet)
def _kl_dirichlet(p, q):
    def f(c0, c1):
        from jax.scipy.special import digamma, gammaln

        s0 = jnp.sum(c0, -1)
        return (
            gammaln(s0) - jnp.sum(gammaln(c0), -1)
            - gammaln(jnp.sum(c1, -1)) + jnp.sum(gammaln(c1), -1)
            + jnp.sum(
                (c0 - c1) * (digamma(c0) - digamma(s0)[..., None]), -1
            )
        )

    return apply_op("kl_dirichlet", f, p.concentration, q.concentration)


@register_kl(Exponential, Exponential)
def _kl_exponential(p, q):
    def f(r0, r1):
        return jnp.log(r0 / r1) + r1 / r0 - 1.0

    return apply_op("kl_exponential", f, p.rate, q.rate)


@register_kl(Gamma, Gamma)
def _kl_gamma(p, q):
    def f(c0, r0, c1, r1):
        from jax.scipy.special import digamma, gammaln

        return (
            (c0 - c1) * digamma(c0) - gammaln(c0) + gammaln(c1)
            + c1 * (jnp.log(r0) - jnp.log(r1)) + c0 * (r1 / r0 - 1.0)
        )

    return apply_op("kl_gamma", f, p.concentration, p.rate,
                    q.concentration, q.rate)


@register_kl(Laplace, Laplace)
def _kl_laplace(p, q):
    def f(mu0, b0, mu1, b1):
        d = jnp.abs(mu0 - mu1)
        return (
            jnp.log(b1 / b0)
            + (b0 * jnp.exp(-d / b0) + d) / b1 - 1.0
        )

    return apply_op("kl_laplace", f, p.loc, p.scale, q.loc, q.scale)


class Binomial(Distribution):
    """Binomial(total_count, probs) (upstream: distribution/binomial.py)."""

    def __init__(self, total_count, probs, name=None):
        self.total_count = int(total_count)
        self.probs = _param(probs)
        super().__init__(tuple(self.probs.shape))

    def sample(self, shape=()):
        shape = _shape_tuple(shape)
        k = next_key()
        n = self.total_count

        def f(p):
            out_shape = shape + tuple(p.shape)
            # O(shape) native sampler (upstream uses a dedicated
            # binomial kernel)
            return jax.random.binomial(
                k, n, p, shape=out_shape
            ).astype(jnp.float32)

        return apply_op("binomial_sample", f, self.probs,
                        differentiable=False)

    def log_prob(self, value):
        value = _as_tensor(value)
        n = self.total_count

        def f(v, p):
            from jax.scipy.special import gammaln

            vf = v.astype(jnp.float32)
            pc = jnp.clip(p, 1e-7, 1 - 1e-7)
            return (
                gammaln(n + 1.0) - gammaln(vf + 1.0)
                - gammaln(n - vf + 1.0)
                + vf * jnp.log(pc) + (n - vf) * jnp.log1p(-pc)
            )

        return apply_op("binomial_log_prob", f, value, self.probs)

    @property
    def mean(self):
        from ..tensor.math import scale as _scale

        return _scale(self.probs, float(self.total_count))


class MultivariateNormal(Distribution):
    """MVN with full covariance (upstream: distribution/
    multivariate_normal.py). Sampling goes through the Cholesky factor
    (reparameterized); log_prob solves against it."""

    def __init__(self, loc, covariance_matrix=None, scale_tril=None,
                 name=None):
        self.loc = _param(loc)
        if (covariance_matrix is None) == (scale_tril is None):
            raise ValueError(
                "give exactly one of covariance_matrix / scale_tril"
            )
        if scale_tril is not None:
            self.scale_tril = _param(scale_tril)
        else:
            cov = _param(covariance_matrix)
            self.scale_tril = apply_op(
                "mvn_chol", jnp.linalg.cholesky, cov
            )
        super().__init__(tuple(self.loc.shape)[:-1],
                         tuple(self.loc.shape)[-1:])

    def rsample(self, shape=()):
        shape = _shape_tuple(shape)
        k = next_key()

        def f(mu, L):
            eps = jax.random.normal(
                k, shape + mu.shape, jnp.float32
            )
            return mu + jnp.einsum("...ij,...j->...i", L, eps)

        return apply_op("mvn_rsample", f, self.loc, self.scale_tril)

    def log_prob(self, value):
        value = _as_tensor(value)

        def f(v, mu, L):
            d = mu.shape[-1]
            diff = v.astype(jnp.float32) - mu
            sol = jax.scipy.linalg.solve_triangular(
                L, diff[..., None], lower=True
            )[..., 0]
            maha = jnp.sum(jnp.square(sol), axis=-1)
            logdet = jnp.sum(
                jnp.log(jnp.abs(jnp.diagonal(
                    L, axis1=-2, axis2=-1))), axis=-1
            )
            return (
                -0.5 * maha - logdet
                - 0.5 * d * math.log(2.0 * math.pi)
            )

        return apply_op("mvn_log_prob", f, value, self.loc,
                        self.scale_tril)

    def entropy(self):
        def f(mu, L):
            d = mu.shape[-1]
            logdet = jnp.sum(
                jnp.log(jnp.abs(jnp.diagonal(
                    L, axis1=-2, axis2=-1))), axis=-1
            )
            return 0.5 * d * (1.0 + math.log(2.0 * math.pi)) + logdet

        return apply_op("mvn_entropy", f, self.loc, self.scale_tril)


__all__.extend(["Binomial", "MultivariateNormal"])


class ExponentialFamily(Distribution):
    """Base for exponential-family distributions (upstream
    python/paddle/distribution/exponential_family.py): subclasses
    expose natural parameters + log-normalizer; entropy falls out via
    the Bregman identity (autodiff of the log normalizer)."""

    @property
    def _natural_parameters(self):
        raise NotImplementedError

    def _log_normalizer(self, *natural_params):
        raise NotImplementedError

    @property
    def _mean_carrier_measure(self):
        return 0.0

    def entropy(self):
        """H = A(η) - <η, ∇A(η)> - E[log h(x)] via autodiff of the log
        normalizer (∇A = E[T]); ``_mean_carrier_measure`` is E[log h],
        the torch/paddle convention."""
        nat = [_as_tensor(p) for p in self._natural_parameters]

        def f(*raws):
            raws = [r.astype(jnp.float32) for r in raws]
            # A(η) is elementwise over the batch, so grad-of-sum gives
            # the per-element ∇A; entropy keeps the batch shape
            grads = jax.grad(
                lambda *ps: jnp.sum(self._log_normalizer(*ps)),
                argnums=tuple(range(len(raws))))(*raws)
            a = self._log_normalizer(*raws)
            ent = a - sum(g * r for g, r in zip(grads, raws))
            return ent - self._mean_carrier_measure

        return apply_op("expfam_entropy", f, *nat)


class ContinuousBernoulli(Distribution):
    """Continuous Bernoulli on [0, 1] (upstream
    python/paddle/distribution/continuous_bernoulli.py; Loaiza-Ganem &
    Cunningham 2019). ``probs`` parametrizes the un-normalized density
    p^x (1-p)^(1-x) with the closed-form normalizing constant."""

    def __init__(self, probs, lims=(0.499, 0.501)):
        self.probs = _as_tensor(probs)
        self._lims = lims
        super().__init__(tuple(self.probs.shape), ())

    def _safe_p(self, p):
        lo, hi = self._lims
        # the normalizer has a removable singularity at p=1/2 — clamp
        # to the NEAREST window edge like the reference (p just above
        # 1/2 must stay above it)
        cut = jnp.where(
            (p >= lo) & (p <= hi),
            jnp.where(p < 0.5, lo, hi), p)
        return jnp.clip(cut, 1e-6, 1 - 1e-6)

    def _log_norm(self, p):
        # log C(p); C = 2 atanh(1-2p) / (1-2p) is positive for all
        # p != 1/2 (both factors flip sign together), so the log is
        # taken of the RATIO
        return jnp.log(
            2.0 * jnp.arctanh(1.0 - 2.0 * p) / (1.0 - 2.0 * p))

    @property
    def mean(self):
        def f(pr):
            p = self._safe_p(pr.astype(jnp.float32))
            return p / (2.0 * p - 1.0) \
                + 1.0 / (2.0 * jnp.arctanh(1.0 - 2.0 * p))

        return apply_op("cb_mean", f, self.probs)

    def log_prob(self, value):
        value = _as_tensor(value)

        def f(pr, x):
            p = self._safe_p(pr.astype(jnp.float32))
            x = x.astype(jnp.float32)
            return (x * jnp.log(p) + (1.0 - x) * jnp.log1p(-p)
                    + self._log_norm(p))

        return apply_op("cb_log_prob", f, self.probs, value)

    def rsample(self, shape=()):
        k = next_key()
        shp = _shape_tuple(shape) + tuple(self.probs.shape)

        def f(pr):
            p = self._safe_p(pr.astype(jnp.float32))
            u = jax.random.uniform(
                k, shp, minval=1e-6, maxval=1.0 - 1e-6)
            # inverse CDF: x = [atanh((2p-1)(2u-1)... ] closed form:
            # F^-1(u) = (log(u*(2p-1)/(1-p) + 1) / log(p/(1-p)))
            ratio = jnp.log(p) - jnp.log1p(-p)
            x = jnp.log1p(u * (jnp.exp(ratio) - 1.0)) / ratio
            return jnp.clip(x, 0.0, 1.0)

        return apply_op("cb_rsample", f, self.probs)

    def sample(self, shape=()):
        s = self.rsample(shape)
        s.stop_gradient = True
        return s


__all__.extend(["ExponentialFamily", "ContinuousBernoulli"])

# transforms live in their own module but surface here like the
# reference (paddle.distribution.AffineTransform, ...)
from .transform import (  # noqa: E402,F401
    AbsTransform,
    AffineTransform,
    ChainTransform,
    ExpTransform,
    PowerTransform,
    SigmoidTransform,
    SoftmaxTransform,
    StackTransform,
    TanhTransform,
    Transform,
    TransformedDistribution,
)

__all__.extend([
    "Transform", "AffineTransform", "ExpTransform", "PowerTransform",
    "SigmoidTransform", "TanhTransform", "AbsTransform",
    "ChainTransform", "SoftmaxTransform", "StackTransform",
    "TransformedDistribution",
])
