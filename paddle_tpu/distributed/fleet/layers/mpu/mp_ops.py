"""Tensor-parallel communication primitives
(upstream: python/paddle/distributed/fleet/layers/mpu/mp_ops.py —
_c_identity/_c_split/_c_concat/_mp_allreduce autograd functions).

TPU-native: in the GSPMD context these become sharding constraints —
the partitioner inserts the all-reduce/all-gather exactly where the
reference's hand-written collective ops run (and fuses them into the
surrounding computation). In a manual shard_map context they lower to
explicit lax collectives with matching fwd/bwd semantics.

``collective_matmul_dispatch`` is the single routing point for the
*dependent* collective+matmul pairs these layers emit: behind
FLAGS_collective_matmul it replaces the blocking chain with the
ring-decomposed kernels (ops/kernels/collective_matmul.py), either
directly inside an active manual region or via a partial-manual
shard_map over the mp axis in the GSPMD context. New TP/SP code must
route matmul+collective pairs through it rather than hand-rolling
blocking chains (tools/lint_codebase.py enforces this).
"""
from __future__ import annotations

import functools

import jax
from jax.sharding import NamedSharding, PartitionSpec

from .....framework.core import Tensor, apply_op, _as_tensor
from ....collective import _resolve
from ....mesh import global_mesh, in_manual_context


def shard_constraint(x, *spec):
    """with_sharding_constraint as a taped op (identity semantics)."""
    x = _as_tensor(x)
    m = global_mesh()
    if m is None:
        return x
    sh = NamedSharding(m, PartitionSpec(*spec))
    return apply_op(
        "sharding_constraint",
        lambda a: jax.lax.with_sharding_constraint(a, sh),
        x,
    )


def _axis(group):
    g = _resolve(group)
    return g.axis_names if len(g.axis_names) > 1 else (
        g.axis_names[0] if g.axis_names else None
    )


def _c_identity(tensor, group=None, skip_c_identity_dynamic=False):
    """fwd identity / bwd all-reduce over the mp group."""
    tensor = _as_tensor(tensor)
    g = _resolve(group)
    if g.nranks == 1:
        return tensor
    if in_manual_context(g.axis_names):
        ax = _axis(group)

        @jax.custom_vjp
        def ident(x):
            return x

        ident.defvjp(
            lambda x: (x, None),
            lambda _, ct: (jax.lax.psum(ct, ax),),
        )
        return apply_op("c_identity", ident, tensor)
    # GSPMD: grads of replicated values are reduced by the partitioner
    return tensor


def _mp_allreduce(tensor, op=None, group=None, use_calc_stream=True,
                  use_model_parallel=True):
    """fwd all-reduce / bwd identity over the mp group."""
    tensor = _as_tensor(tensor)
    g = _resolve(group)
    if g.nranks == 1:
        return tensor
    if in_manual_context(g.axis_names):
        ax = _axis(group)

        @jax.custom_vjp
        def allred(x):
            return jax.lax.psum(x, ax)

        allred.defvjp(
            lambda x: (jax.lax.psum(x, ax), None),
            lambda _, ct: (ct,),
        )
        return apply_op("mp_allreduce", allred, tensor)
    # GSPMD: a partial-sum product is materialized reduced automatically;
    # an explicit replicated constraint is the belt-and-braces annotation
    return shard_constraint(tensor)


def _c_split(tensor, group=None):
    """Split the last dim across the mp group (fwd) / all-gather (bwd)."""
    tensor = _as_tensor(tensor)
    g = _resolve(group)
    if g.nranks == 1:
        return tensor
    if in_manual_context(g.axis_names):
        ax = _axis(group)
        n = g.nranks

        @jax.custom_vjp
        def split(x):
            i = jax.lax.axis_index(ax)
            size = x.shape[-1] // n
            return jax.lax.dynamic_slice_in_dim(x, i * size, size, -1)

        def fwd(x):
            return split(x), None

        def bwd(_, ct):
            return (jax.lax.all_gather(ct, ax, axis=ct.ndim - 1, tiled=True),)

        split.defvjp(fwd, bwd)
        return apply_op("c_split", split, tensor)
    return shard_constraint(tensor, *([None] * (tensor.ndim - 1) + ["mp"]))


def _c_concat(tensor, group=None):
    """All-gather the last dim across the mp group (fwd) / split (bwd)."""
    tensor = _as_tensor(tensor)
    g = _resolve(group)
    if g.nranks == 1:
        return tensor
    if in_manual_context(g.axis_names):
        ax = _axis(group)
        n = g.nranks

        @jax.custom_vjp
        def concat(x):
            return jax.lax.all_gather(x, ax, axis=x.ndim - 1, tiled=True)

        def fwd(x):
            return concat(x), None

        def bwd(_, ct):
            i = jax.lax.axis_index(ax)
            size = ct.shape[-1] // n
            return (jax.lax.dynamic_slice_in_dim(ct, i * size, size, -1),)

        concat.defvjp(fwd, bwd)
        return apply_op("c_concat", concat, tensor)
    return shard_constraint(tensor)


# ---------------------------------------------------------------------------
# collective matmul routing (FLAGS_collective_matmul)
# ---------------------------------------------------------------------------

_CM_KINDS = ("ag_mm", "mm_rs", "mm_ar", "mm_ag")

# one jit'd shard_map per (kind, axis, degree, seq-axis, rank, mesh) —
# see the cache note at the build site
_CM_JIT_CACHE: dict = {}


def _rows(t):
    """Row count with the trailing (feature) dim collapsed."""
    return t.size // t.shape[-1]


def _cm_axis(group, axis):
    """Resolve (axis_name, degree) for the decomposition ring from an
    explicit comm group (mp_layers) or a bare axis name (SP utils)."""
    if group is not None or axis is None:
        g = _resolve(group)
        ax = _axis(group)
        return (ax, g.nranks) if isinstance(ax, str) else (None, 1)
    from ....mesh import axis_degree

    return axis, axis_degree(axis)


def collective_matmul_dispatch(kind, x, w, bias=None, group=None,
                               axis=None, seq_axis=0):
    """Route a dependent collective+matmul pair through the ring-
    decomposed subsystem (ops/kernels/collective_matmul.py).

    kinds:
      ag_mm  all_gather(x, seq_axis) @ w      SP/column entry
      mm_rs  psum_scatter(x @ w, seq_axis)    SP/row exit
      mm_ar  psum(x @ w)                      RowParallelLinear
                                              (= mm_rs + all_gather:
                                              the reduce half rides
                                              the ring)
      mm_ag  all_gather(x @ w, -1)            ColumnParallelLinear
                                              gather_output

    Returns the output Tensor (bias included), or None when the policy
    declines — FLAGS_collective_matmul off/auto-below-threshold, degree
    1, or a chunk dim that doesn't divide the ring — in which case the
    caller falls through to its plain blocking chain UNCHANGED (the
    off-path lowering stays bit-identical).
    """
    from .....ops.kernels import collective_matmul as cm

    if kind not in _CM_KINDS:
        return None
    if cm.decompose_mode() == "off":
        cm.record_dispatch(kind, False, "off")
        return None
    ax, ws = _cm_axis(group, axis)
    if ax is None or ws <= 1:
        cm.record_dispatch(kind, False, "degree")
        return None
    x, w = _as_tensor(x), _as_tensor(w)
    if x.ndim < 2 or w.ndim != 2:
        cm.record_dispatch(kind, False, "shape")
        return None
    itemsize = jax.numpy.dtype(x._data.dtype).itemsize
    manual = in_manual_context((ax,))
    if not manual:
        m = global_mesh()
        if m is None or ax not in m.axis_names:
            cm.record_dispatch(kind, False, "no_mesh")
            return None

    rows = _rows(x)
    n_out = int(w.shape[-1])
    if kind == "ag_mm":
        comm = x.size * itemsize * (ws if manual else 1)
    elif kind == "mm_ag":
        comm = rows * n_out * itemsize * (ws if manual else 1)
    else:  # mm_rs / mm_ar: the partial product fed to the reduction
        comm = rows * n_out * itemsize

    if kind == "mm_ar":
        # the reduced output is re-gathered tiled over a leading dim;
        # pick the first one the ring divides
        sa = next((i for i in range(x.ndim - 1)
                   if x.shape[i] % ws == 0), None)
        if sa is None:
            cm.record_dispatch(kind, False, "indivisible")
            return None
    else:
        sa = seq_axis

    if manual:
        ok = {
            "ag_mm": True,
            "mm_rs": x.shape[sa] % ws == 0,
            "mm_ar": True,
            "mm_ag": bias is None,  # out is full-dim; bias is a shard
        }[kind]
    else:
        ok = {
            "ag_mm": x.shape[sa] % ws == 0 and w.shape[1] % ws == 0,
            "mm_rs": x.shape[-1] % ws == 0 and w.shape[0] % ws == 0
            and x.shape[sa] % ws == 0,
            "mm_ar": x.shape[-1] % ws == 0 and w.shape[0] % ws == 0,
            "mm_ag": w.shape[1] % ws == 0,
        }[kind]
    deny = cm.decline_reason(comm, ws, ok)
    if deny is not None:
        cm.record_dispatch(kind, False, deny)
        return None
    cm.record_dispatch(kind, True, chunks=ws)

    # quantize-on-the-wire (FLAGS_collective_dtype): the wire dtype is
    # resolved HERE, at the dispatch decision point, and handed to the
    # kernels as a static argument — the quant/dequant math itself
    # lives only in ops/kernels/collective_matmul.py (enforced by the
    # wire-quant-ownership codebase lint). The savings counters record
    # the TOTAL elements the program's rings move over ICI (every hop
    # of every ring this dispatch emits), so the aggregate stays one
    # currency across kinds.
    if kind == "ag_mm":
        # the x shard rotates: ws-1 hops of the local chunk
        loc = x.size if manual else x.size // ws
        elems, last = (ws - 1) * loc, int(x.shape[-1])
    elif kind == "mm_ag":
        # the weight column-shard rotates
        loc = w.size if manual else w.size // ws
        elems, last = (ws - 1) * loc, n_out
    elif kind == "mm_rs":
        # ws-1 hops of the (rows/ws, n_out) partial-sum carry
        elems, last = (ws - 1) * (rows // ws) * n_out, n_out
    else:  # mm_ar: the carry ring plus the tiled re-gather
        elems, last = 2 * (ws - 1) * (rows // ws) * n_out, n_out
    wire = cm.resolve_wire(comm, last, itemsize)
    if wire != "off":
        cm.record_wire(kind, wire, elems, last, itemsize)

    # ONE local ring per kind, shared by both execution contexts so the
    # lowerings cannot desynchronize. mm_ar/mm_ag take the cotangent
    # convention switch: tape_ct under the manual tape (replicated,
    # complete cotangents), shard_map-transpose semantics otherwise —
    # see the kernel docstrings.
    local = {
        "ag_mm": functools.partial(
            cm.all_gather_matmul, axis_name=ax, axis_size=ws,
            gather_axis=sa, wire=wire),
        "mm_rs": functools.partial(
            cm.matmul_reduce_scatter, axis_name=ax, axis_size=ws,
            scatter_axis=sa, wire=wire),
        "mm_ar": functools.partial(
            cm.matmul_all_reduce, axis_name=ax, axis_size=ws,
            scatter_axis=sa, tape_ct=manual, wire=wire),
        "mm_ag": functools.partial(
            cm.matmul_all_gather, axis_name=ax, axis_size=ws,
            tape_ct=manual, wire=wire),
    }[kind]

    if manual:
        out = apply_op("collective_matmul_" + kind, local, x, w)
        return out if bias is None else out + bias

    from ....mesh import shard_map as _shard_map

    nd = x.ndim
    none = [None] * nd
    x_seq = list(none)
    x_seq[sa] = ax
    x_hid = list(none)
    x_hid[-1] = ax
    out_hid = list(none)
    out_hid[-1] = ax
    in_specs, out_specs = {
        "ag_mm": ((PartitionSpec(*x_seq), PartitionSpec(None, ax)),
                  PartitionSpec(*out_hid)),
        "mm_rs": ((PartitionSpec(*x_hid), PartitionSpec(ax, None)),
                  PartitionSpec(*x_seq)),
        "mm_ar": ((PartitionSpec(*x_hid), PartitionSpec(ax, None)),
                  PartitionSpec(*none)),
        "mm_ag": ((PartitionSpec(*none), PartitionSpec(None, ax)),
                  PartitionSpec(*none)),
    }[kind]
    mesh = global_mesh()

    def sm_fn(xr, wr, local=local, in_specs=in_specs,
              out_specs=out_specs):
        return _shard_map(
            local, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs, axis_names={ax},
        )(xr, wr)

    # Context-sensitive wrapping: inside an enclosing trace
    # (@to_static step) the shard_map must lower DIRECTLY into the
    # surrounding program — a nested pjit makes the outer SPMD
    # partitioner reject the manual axis_index lowering
    # (PartitionId). In eager mode the opposite holds: the legacy
    # shard_map auto path only lowers under a jit, so wrap — cached
    # per routing signature so eager layers reuse the compile instead
    # of paying a retrace per forward.
    if isinstance(x._data, jax.core.Tracer) \
            or isinstance(w._data, jax.core.Tracer):
        global_fn = sm_fn
    else:
        key = (kind, ax, ws, sa, nd, wire, mesh)
        global_fn = _CM_JIT_CACHE.get(key)
        if global_fn is None:
            # evict signatures of dead meshes (rebuilt via
            # build_global_mesh) so retired executables don't pile up
            for k in [k for k in _CM_JIT_CACHE if k[-1] is not mesh]:
                del _CM_JIT_CACHE[k]
            global_fn = _CM_JIT_CACHE[key] = jax.jit(sm_fn)

    out = apply_op("collective_matmul_" + kind, global_fn, x, w)
    return out if bias is None else out + bias


def grad_allreduce_dispatch(tensor, group=None):
    """Route a DP gradient-sync all-reduce through the chunked
    (optionally quantized) ring (ops/kernels/collective_matmul.py
    ring_all_reduce) — the blocking-psum replacement
    fleet/utils/hybrid_parallel_util.fused_allreduce_gradients calls
    before falling back to the plain collective.

    Returns the reduced Tensor (NOT averaged — the caller owns the
    1/world scaling exactly as before), or None when the policy
    declines: FLAGS_collective_matmul off/auto-below-threshold, degree
    1, a grad whose element count the ring does not divide, or a
    non-manual context (under GSPMD the grads of global arrays are
    already reduced in-program — there is no blocking psum to
    replace). The off-path lowering stays bit-identical."""
    from .....ops.kernels import collective_matmul as cm

    if cm.decompose_mode() == "off":
        cm.record_dispatch("dp_ar", False, "off")
        return None
    g = _resolve(group)
    ax = _axis(group)
    ws = g.nranks
    if not isinstance(ax, str) or ws <= 1:
        cm.record_dispatch("dp_ar", False, "degree")
        return None
    if not in_manual_context(g.axis_names):
        cm.record_dispatch("dp_ar", False, "no_mesh")
        return None
    tensor = _as_tensor(tensor)
    itemsize = jax.numpy.dtype(tensor._data.dtype).itemsize
    comm = 2 * tensor.size * itemsize  # allreduce = RS + AG
    divisible = tensor.size % ws == 0
    deny = cm.decline_reason(comm, ws, divisible)
    if deny is not None:
        cm.record_dispatch("dp_ar", False, deny)
        return None
    # the ring chunks are (size/ws,) flat vectors — the scale blocks
    # tile that length
    chunk_len = max(tensor.size // ws, 1)
    wire = cm.resolve_wire(comm, chunk_len, itemsize)
    cm.record_dispatch("dp_ar", True, chunks=ws)
    # RS ships ws-1 chunks of size/ws, the re-gather (ws-1)/ws of the
    # whole grad: 2*(ws-1)*size/ws elements over the wire in total
    cm.record_wire("dp_ar", wire, 2 * (ws - 1) * (tensor.size // ws),
                   chunk_len, itemsize)
    return apply_op(
        "grad_sync_ring",
        functools.partial(cm.ring_all_reduce, axis_name=ax,
                          axis_size=ws, wire=wire),
        tensor)


def split(x, size, operation="linear", axis=0, num_partitions=1,
          gather_out=True, weight_attr=None, bias_attr=None, name=None):
    raise NotImplementedError(
        "paddle.distributed.split: use ColumnParallelLinear / "
        "RowParallelLinear directly"
    )
