"""Grouped matmul of the routed experts as a Pallas TPU kernel.

``xs`` [m, K] holds rows sorted by group, ``w`` [E, K, N] one matrix a
group, ``group_sizes`` [E] the rows of each group as DEVICE data; row r
of the result is row r of ``xs`` times the matrix of its group, and the
rows past the last group are zeros (``jax.lax.ragged_dot``'s contract,
which :func:`grouped_matmul_reference` keeps for the backward pass and
off the chip). Upstream analog: megablox's ``gmm`` (JAX's
``pallas/ops/tpu/megablox``), on whose plan this is built:

* the grid is (N tiles, visits, K tiles); a visit is a (group, row tile)
  pair, listed on the device from the group sizes (scalar prefetch)
  before the call, so an empty group is never visited and an expert
  that no row chose is never read;
* the visits of a group are consecutive, and with the whole K in one
  tile a group's weight tile stays in VMEM across them: each expert's
  matrix is read once a call whatever its rows;
* the rows past the last group are one more group, visited last, whose
  visits store zeros and fetch nothing (their operand tiles are the
  last real visit's, which the pipeline does not copy again);
* a visit accumulates in float32 and stores the rows of its group in
  the result's dtype, the arithmetic of ``ragged_dot(...,
  preferred_element_type=float32).astype(dtype)``.

The tiling is a function of the shapes alone (:func:`gmm_tiling`). The
backward pass is ``jax.vjp`` of the reference (``pallas_call`` has no
transpose rule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from . import _per_shard

ROW_TILE = 128
# the tiles' VMEM (weights, lhs and result double-buffered, the float32
# accumulator) under v5e's 16 MiB default scoped VMEM, with room left
# for what Mosaic holds beside them
VMEM_BUDGET = 12 * 1024 * 1024


def grouped_matmul_reference(xs, w, group_sizes):
    """``jax.lax.ragged_dot`` in float32, cast to ``xs``'s dtype."""
    return jax.lax.ragged_dot(xs, w, group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(xs.dtype)


def _lane_tiles(dim):
    """A dimension's tile sizes, largest first: the whole dimension, then
    every multiple of 128 that divides it."""
    return [dim] + [t for t in range(dim - 128, 0, -128)
                    if t % 128 == 0 and dim % t == 0]


def gmm_tiling(m, k, n, itemsize=2):
    """(tm, tk, tn) of a call of these shapes. ``tm`` 128 rows (the whole
    ``m`` below it): SDAR's and Xing4's mean groups of 4-64 rows fill a
    row tile of 128 at most once, and a wider tile pays for padded rows
    on the MXU (probed on the chip, PERF.md). ``tk`` the whole K where
    it fits, so a group's weight tile is fetched once whatever the
    number of its row tiles; then the widest ``tn`` whose tiles fit
    ``VMEM_BUDGET``. The group count leaves the choice alone: it adds
    one visit a group, whatever the tiles."""
    tm = min(m, ROW_TILE)

    def vmem(tk, tn):
        return (2 * tk * tn * itemsize + 2 * tm * tk * itemsize
                + 2 * tm * tn * itemsize + tm * tn * 4)

    pairs = [(tk, tn) for tk in _lane_tiles(k) for tn in _lane_tiles(n)]
    return (tm,) + next((p for p in pairs if vmem(*p) <= VMEM_BUDGET),
                        pairs[-1])


def _visits(group_sizes, m, tm):
    """The grid's visits on the device: (offsets [G+1], group of each
    visit, row tile of each visit, index of the last visit of a real
    group [1], number of visits), with G = E + 1 groups: the last holds
    the rows past the E real groups."""
    e = group_sizes.shape[0]
    tail = m - jnp.sum(group_sizes)
    sizes = jnp.concatenate([group_sizes, tail[None]]).astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - starts // tm + 1, 0)
    first = jnp.cumsum(tiles) - tiles
    most = -(-m // tm) + e                       # tiles_m + G - 1
    gid = jnp.repeat(jnp.arange(e + 1, dtype=jnp.int32), tiles,
                     total_repeat_length=most)
    mid = starts[gid] // tm + jnp.arange(most, dtype=jnp.int32) - first[gid]
    mid = jnp.clip(mid, 0, -(-m // tm) - 1)
    last = jnp.maximum(jnp.sum(tiles[:e]) - 1, 0)[None]
    return offsets, gid, mid, last, jnp.sum(tiles)


def _gmm_kernel(e, tm, nk, offs, gid, mid, last, x_ref, w_ref, o_ref,
                acc_ref):
    t, ki = pl.program_id(1), pl.program_id(2)
    g = gid[t]

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(g < e)
    def _():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _():
        rows = mid[t] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mine = (rows >= offs[g]) & (rows < offs[g + 1])
        o_ref[...] = jnp.where(mine, acc_ref[...].astype(o_ref.dtype),
                               o_ref[...])


def gmm_pallas(xs, w, group_sizes, tiling=None, interpret=False):
    """The kernel: ``xs`` [m, K] x ``w`` [E, K, N] by ``group_sizes`` [E]
    -> [m, N] in ``xs``'s dtype. ``tiling`` (tm, tk, tn), default
    :func:`gmm_tiling`."""
    m, k = xs.shape
    e, _, n = w.shape
    tm, tk, tn = tiling or gmm_tiling(m, k, n, xs.dtype.itemsize)
    nk, nn = k // tk, -(-n // tn)
    offs, gid, mid, last, visits = _visits(
        group_sizes.astype(jnp.int32), m, tm)

    def operand(t, ki, gid, last):
        # a visit of the rows past the last group reads the tiles of the
        # last real visit: the same block indices, so nothing is copied
        # (where no group has a row, the first expert's)
        tail = gid[t] == e
        return jnp.where(tail, last[0], t), jnp.where(tail, nk - 1, ki)

    def x_map(ni, t, ki, offs, gid, mid, last):
        t, ki = operand(t, ki, gid, last)
        return mid[t], ki

    def w_map(ni, t, ki, offs, gid, mid, last):
        t, ki = operand(t, ki, gid, last)
        return jnp.minimum(gid[t], e - 1), ki, ni

    def o_map(ni, t, ki, offs, gid, mid, last):
        return mid[t], ni

    isz = xs.dtype.itemsize
    cost = pl.CostEstimate(
        flops=2 * m * k * n, transcendentals=0,
        bytes_accessed=e * k * n * w.dtype.itemsize + nn * m * k * isz
        + m * n * isz)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, e, tm, nk),
        name="grouped_matmul",
        out_shape=jax.ShapeDtypeStruct((m, n), xs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(nn, visits, nk),
            in_specs=[pl.BlockSpec((tm, tk), x_map),
                      pl.BlockSpec((None, tk, tn), w_map)],
            out_specs=pl.BlockSpec((tm, tn), o_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=cost,
        interpret=interpret,
    )(offs, gid, mid, last, xs, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul_pallas(xs, w, group_sizes, interpret=False):
    """:func:`gmm_pallas` at its own tiling, differentiable: the backward
    pass is the reference's. Under a multi-device mesh on the chip every
    device runs the whole call on replicated operands."""
    run = functools.partial(gmm_pallas, interpret=interpret)
    return _per_shard(run, (P(),) * 3, P())(xs, w, group_sizes)


def _fwd(xs, w, group_sizes, interpret):
    return (grouped_matmul_pallas(xs, w, group_sizes, interpret),
            (xs, w, group_sizes))


def _bwd(interpret, res, g):
    xs, w, group_sizes = res
    _, vjp = jax.vjp(
        lambda a, b: grouped_matmul_reference(a, b, group_sizes), xs, w)
    return (*vjp(g), None)


grouped_matmul_pallas.defvjp(_fwd, _bwd)
