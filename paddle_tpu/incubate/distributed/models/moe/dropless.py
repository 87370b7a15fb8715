"""Routed experts without a capacity: every assignment of the router is
computed, whatever the imbalance (the DeepSeek-V3 family's expert layer;
upstream ecosystem analog: PaddleNLP's ``DeepseekV2MoE`` with the
``noaux_tc`` gate).

``MoELayer`` dispatches into a fixed ``[E, capacity, d]`` buffer and drops
what overflows it. Here the tokens' assignments are sorted by expert and a
grouped matmul runs over the stacked expert weights with the group sizes
as DEVICE data, so no shape depends on the routing and nothing crosses to
the host inside the layer:

* router in float32, two scores: ``s = sigmoid(x W_r)`` and the top ``k``
  of ``s + bias`` (``e_score_correction_bias``, the ``noaux_tc`` selection
  bias: it picks, it does not weigh), or ``s = softmax(x W_r)`` over all
  experts and its top ``k`` (the Qwen3-MoE family's router, no bias);
  weights ``s`` of the picked, divided by their sum when
  ``norm_topk_prob``, times ``routed_scaling_factor``;
* ``y = sum_i w_i E_i(x) + E_shared(x)``, every expert a SwiGLU; the
  shared expert is there or not (``num_shared`` 0).

The pure functions (``route``, ``sort_by_expert``, ``experts_ffn``,
``combine``) are what the serving adapter compiles one program each of;
``DroplessMoE`` is the layer over them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .....framework.core import apply_op
from .....nn import initializer as I
from .....nn.layer.layers import Layer
from .....ops import kernels
from .....ops.kernels import grouped_matmul as gmm

__all__ = ["DroplessMoE", "route", "sort_by_expert", "grouped_matmul",
           "experts_ffn", "combine", "swiglu", "dropless_moe"]


SCORES = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}


def route(x, w_router, bias, top_k, scale=1.0, norm_topk=True,
          scoring="sigmoid"):
    """x [N, C] -> (expert ids [N, k] int32, weights [N, k] float32).
    Scores in float32, ``scoring`` of ``SCORES``: each expert's sigmoid,
    or the softmax over all experts; ``bias`` (None: none) only enters the
    selection."""
    s = SCORES[scoring](jnp.matmul(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(
        s if bias is None else s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, -1)
    if norm_topk:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale


def sort_by_expert(idx, num_experts, valid=None):
    """The dispatch plan of ``idx`` [N, k]: (order [N*k], group_sizes [E]).
    ``order`` lists the flat assignments (token n, choice j -> n*k + j)
    sorted by expert; assignments of rows where ``valid`` [N] is false (the
    packed axis's padding) sort behind every expert and count in no group,
    so the grouped matmul leaves them out."""
    flat = idx.reshape(-1)
    if valid is not None:
        flat = jnp.where(jnp.repeat(valid, idx.shape[1]), flat, num_experts)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    sizes = jnp.sum(flat[:, None] == jnp.arange(num_experts)[None, :], 0,
                    dtype=jnp.int32)
    return order, sizes


def grouped_matmul(xs, w, group_sizes):
    """xs [A, K] (rows sorted by group) x w [E, K, N] -> [A, N]: row a is
    multiplied by the matrix of its group; rows past the last group give
    zeros; the group sizes are device data. On the chip the Pallas
    kernel ``grouped_matmul`` (interpreted under ``FLAGS_pallas_interpret``
    off it), else ``jax.lax.ragged_dot``; counted as
    ``grouped_matmul:pallas|xla_fallback`` in ``kernel_dispatch_stats()``."""
    pallas = kernels.use_pallas() or kernels.interpret_mode()
    kernels.record_dispatch("grouped_matmul", pallas)
    if pallas:
        return gmm.grouped_matmul_pallas(xs, w, group_sizes,
                                         kernels.interpret_mode())
    return gmm.grouped_matmul_reference(xs, w, group_sizes)


def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(jnp.matmul(x, w_gate)) * jnp.matmul(x, w_up)
    return jnp.matmul(h, w_down)


def experts_ffn(xs, group_sizes, w_gate, w_up, w_down):
    """The routed SwiGLU over sorted rows: three grouped matmuls."""
    with jax.named_scope("moe_gmm"):
        h = jax.nn.silu(grouped_matmul(xs, w_gate, group_sizes)) \
            * grouped_matmul(xs, w_up, group_sizes)
        return grouped_matmul(h, w_down, group_sizes)


def combine(ys, order, weights, n_tokens):
    """Sorted expert outputs ys [A, C] back to tokens: y[n] = sum_j w[n, j]
    * (output of assignment n*k + j), accumulated in float32."""
    k = weights.shape[1]
    inv = jnp.argsort(order).astype(jnp.int32)
    per = ys[inv].reshape(n_tokens, k, ys.shape[-1]).astype(jnp.float32)
    return jnp.sum(per * weights[..., None], 1)


def dropless_moe(x, w_router, bias, w_gate, w_up, w_down, shared, top_k,
                 scale=1.0, norm_topk=True, valid=None, scoring="sigmoid"):
    """The whole layer on [N, C]; ``shared`` is None or the shared
    expert's (gate, up, down). Returns (y [N, C], group_sizes [E])."""
    n = x.shape[0]
    idx, w = route(x, w_router, bias, top_k, scale, norm_topk, scoring)
    order, sizes = sort_by_expert(idx, w_gate.shape[0], valid)
    xs = x[order // top_k]
    ys = experts_ffn(xs, sizes, w_gate, w_up, w_down)
    y = combine(ys, order, w, n)
    if shared is not None:
        y = y + swiglu(x, *shared).astype(jnp.float32)
    return y.astype(x.dtype), sizes


class DroplessMoE(Layer):
    """``num_experts`` routed SwiGLU experts of width ``d_hidden`` as three
    stacked parameters, a router (``scoring`` "sigmoid" with its selection
    bias, or "softmax" over all experts with none), and ``num_shared``
    shared experts (one SwiGLU of width ``num_shared * d_hidden``; 0:
    none). No capacity: no token is dropped at any imbalance.
    ``forward`` takes [..., d_model]; ``last_group_sizes`` holds the
    per-expert assignment counts of the last call (a device array)."""

    def __init__(self, d_model, d_hidden, num_experts, top_k,
                 num_shared=1, routed_scaling_factor=1.0,
                 norm_topk_prob=True, init_std=0.02, dtype="float32",
                 scoring="sigmoid"):
        super().__init__(dtype=dtype)
        if scoring not in SCORES:
            raise ValueError(f"DroplessMoE: scoring={scoring!r} is none of "
                             f"{sorted(SCORES)}")
        self.scoring = scoring
        self.top_k = int(top_k)
        self.num_experts = int(num_experts)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        init = I.Normal(0.0, init_std)
        mk = lambda shape: self.create_parameter(  # noqa: E731
            shape, default_initializer=init)
        self.gate_weight = mk([d_model, num_experts])
        # the selection bias is the sigmoid (noaux_tc) router's
        self.e_score_correction_bias = self.create_parameter(
            [num_experts], default_initializer=I.Constant(0.0)) \
            if scoring == "sigmoid" else None
        self.experts_gate = mk([num_experts, d_model, d_hidden])
        self.experts_up = mk([num_experts, d_model, d_hidden])
        self.experts_down = mk([num_experts, d_hidden, d_model])
        self.has_shared = num_shared > 0
        if self.has_shared:
            ds = d_hidden * num_shared
            self.shared_gate = mk([d_model, ds])
            self.shared_up = mk([d_model, ds])
            self.shared_down = mk([ds, d_model])
        self.last_group_sizes = None

    def routed_params(self):
        """(router, [selection bias,] the three expert stacks)."""
        bias = self.e_score_correction_bias
        return (self.gate_weight, *(() if bias is None else (bias,)),
                self.experts_gate, self.experts_up, self.experts_down)

    def shared_params(self):
        return (self.shared_gate, self.shared_up, self.shared_down) \
            if self.has_shared else ()

    def forward(self, x):
        k, scale, norm = (self.top_k, self.routed_scaling_factor,
                          self.norm_topk_prob)
        has_shared, scoring = self.has_shared, self.scoring
        has_bias = self.e_score_correction_bias is not None

        def f(xr, wr, *rest):
            b, (wg, wu, wd, *sh) = (rest[0], rest[1:]) if has_bias \
                else (None, rest)
            y, sizes = dropless_moe(
                xr.reshape(-1, xr.shape[-1]), wr, b, wg, wu, wd,
                sh if has_shared else None, k, scale, norm,
                scoring=scoring)
            # the counts leave the op as float32: the tape's cotangents
            # are of the outputs' types
            return y.reshape(xr.shape), sizes.astype(jnp.float32)

        y, sizes = apply_op("dropless_moe", f, x, *self.routed_params(),
                            *self.shared_params(), n_outs=2)
        self.last_group_sizes = sizes._data.astype(jnp.int32)
        return y
