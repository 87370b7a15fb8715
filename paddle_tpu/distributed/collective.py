"""Communication groups + collective ops
(upstream: python/paddle/distributed/collective.py, communication/*;
C++ core: paddle/fluid/distributed/collective/process_group_nccl.cc).

A Group is a handle on one or more named mesh axes. Collectives:
* inside a manual (shard_map) region → explicit `lax` collectives over
  the axis names (psum / all_gather / psum_scatter / all_to_all /
  ppermute) — exactly the ops the reference's NCCL calls become on ICI;
* in the GSPMD context → global-array semantics (reduction is part of
  op semantics; all_reduce is identity, all_gather/scatter reshard).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import Tensor, apply_op, _as_tensor
from . import env as _env
from .mesh import axis_degree, global_mesh, in_manual_context


class ReduceOp:
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


class Group:
    """Communication group = named mesh axes (innermost-varying last)."""

    def __init__(self, axis_names, ranks=None, gid=0, name=None):
        if isinstance(axis_names, str):
            axis_names = (axis_names,)
        self.axis_names = tuple(axis_names)
        self.id = gid
        self._name = name or "_".join(self.axis_names) or "world"
        self._ranks = ranks

    @property
    def nranks(self):
        n = 1
        for a in self.axis_names:
            n *= axis_degree(a)
        return max(n, 1)

    world_size = nranks

    @property
    def rank(self):
        return 0  # single-controller; per-device rank exists only in-trace

    @property
    def ranks(self):
        return self._ranks if self._ranks is not None else list(
            range(self.nranks)
        )

    def get_group_rank(self, rank):
        return rank if rank in self.ranks else -1

    @property
    def process_group(self):
        return self

    def __repr__(self):
        return f"Group(axes={self.axis_names}, nranks={self.nranks})"


_GROUPS = {}
_WORLD = None
_next_gid = [1]


def _world_group():
    global _WORLD
    if _WORLD is None:
        m = global_mesh()
        axes = m.axis_names if m is not None else ()
        _WORLD = Group(axes, gid=0, name="world")
    return _WORLD


def _set_world_group(group):
    global _WORLD
    _WORLD = group


def new_group(ranks=None, backend=None, timeout=None, axis_names=None):
    """Create a subgroup. TPU-native: groups are mesh-axis handles; a
    ranks list that matches an axis coordinate pattern maps onto that
    axis (the fleet topology always constructs groups axis-wise)."""
    gid = _next_gid[0]
    _next_gid[0] += 1
    if axis_names is not None:
        g = Group(axis_names, ranks=ranks, gid=gid)
    else:
        g = Group((), ranks=ranks, gid=gid)
    _GROUPS[gid] = g
    return g


def get_group(gid=0):
    if gid == 0:
        return _world_group()
    return _GROUPS.get(gid)


def _resolve(group):
    if group is None:
        return _world_group()
    return group


def is_available():
    return True


def destroy_process_group(group=None):
    global _WORLD
    if group is None:
        _GROUPS.clear()
        _WORLD = None


# --------------------------------------------------------------------------
# collectives
# --------------------------------------------------------------------------


def _inplace(tensor, out):
    tensor._data = out._data
    tensor._grad_node = out._grad_node
    tensor._version += 1
    return tensor


class CollectiveTask:
    """Async-collective handle (upstream: ProcessGroup::Task — event-
    backed). XLA dispatch is already asynchronous; wait() is the hard
    sync (the role of Task::Wait's event block)."""

    def __init__(self, tensor):
        self._tensor = tensor

    def wait(self, timeout=None):
        data = getattr(self._tensor, "_data", None)
        if data is not None and hasattr(data, "block_until_ready"):
            # execution errors (OOM, poisoned buffer) propagate —
            # upstream Task::Wait does the same
            data.block_until_ready()
        return True

    def is_completed(self):
        data = getattr(self._tensor, "_data", None)
        if data is not None and hasattr(data, "is_ready"):
            return bool(data.is_ready())
        return True

    def synchronize(self):
        self.wait()


def _maybe_task(tensor, sync_op):
    """Reference semantics: sync_op=False returns the async Task;
    sync_op=True returns the (in-place updated) tensor."""
    return tensor if sync_op else CollectiveTask(tensor)


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    g = _resolve(group)
    tensor = _as_tensor(tensor)
    if g.nranks == 1 or not g.axis_names:
        return _maybe_task(tensor, sync_op)
    if in_manual_context(g.axis_names):
        ax = g.axis_names if len(g.axis_names) > 1 else g.axis_names[0]
        if op == ReduceOp.SUM:
            fn = lambda x: jax.lax.psum(x, ax)
        elif op == ReduceOp.MAX:
            fn = lambda x: jax.lax.pmax(x, ax)
        elif op == ReduceOp.MIN:
            fn = lambda x: jax.lax.pmin(x, ax)
        elif op == ReduceOp.AVG:
            fn = lambda x: jax.lax.pmean(x, ax)
        else:
            fn = lambda x: jax.lax.psum(x, ax)
        out = apply_op("c_allreduce", fn, tensor)
        _inplace(tensor, out)
        return _maybe_task(tensor, sync_op)
    # GSPMD context: values are global; reduction already implied
    return _maybe_task(tensor, sync_op)


def all_gather(tensor_list, tensor, group=None, sync_op=True, axis=0):
    g = _resolve(group)
    tensor = _as_tensor(tensor)
    if g.nranks == 1 or not g.axis_names:
        if isinstance(tensor_list, list):
            tensor_list.append(tensor.clone())
            return tensor_list
        return tensor
    if in_manual_context(g.axis_names):
        ax = g.axis_names if len(g.axis_names) > 1 else g.axis_names[0]
        out = apply_op(
            "c_allgather",
            lambda x: jax.lax.all_gather(x, ax, axis=0, tiled=False),
            tensor,
        )
        if isinstance(tensor_list, list):
            from ..tensor.manipulation import unbind

            tensor_list.extend(unbind(out, axis=0))
            return tensor_list if sync_op else CollectiveTask(
                tensor_list[-1]
            )
        return _maybe_task(out, sync_op)
    if isinstance(tensor_list, list):
        for _ in range(g.nranks):
            tensor_list.append(tensor.clone())
        return tensor_list
    return tensor


def all_gather_into_tensor(out_tensor, tensor, group=None, sync_op=True):
    g = _resolve(group)
    res = all_gather(None, tensor, group=group)
    if isinstance(res, Tensor) and out_tensor is not None:
        shape = out_tensor.shape
        from ..tensor.manipulation import reshape

        out_tensor.set_value(reshape(res, shape)._data)
        return out_tensor
    return res


def reduce_scatter(tensor, tensor_or_tensor_list, op=ReduceOp.SUM,
                   group=None, sync_op=True):
    g = _resolve(group)
    src = tensor_or_tensor_list
    if isinstance(src, list):
        from ..tensor.manipulation import concat

        src = concat([_as_tensor(t) for t in src], axis=0)
    src = _as_tensor(src)
    if g.nranks == 1 or not g.axis_names:
        tensor.set_value(src._data)
        return _maybe_task(tensor, sync_op)
    if in_manual_context(g.axis_names):
        ax = g.axis_names if len(g.axis_names) > 1 else g.axis_names[0]
        out = apply_op(
            "c_reducescatter",
            lambda x: jax.lax.psum_scatter(x, ax, scatter_dimension=0,
                                           tiled=True),
            src,
        )
        tensor._data = out._data
        tensor._grad_node = out._grad_node
        return _maybe_task(tensor, sync_op)
    tensor.set_value(src._data)
    return _maybe_task(tensor, sync_op)


def broadcast(tensor, src=0, group=None, sync_op=True):
    # single-controller SPMD: one copy of the data exists; broadcast is
    # the identity (startup param sync is inherent)
    return _maybe_task(_as_tensor(tensor), sync_op)


def gather(tensor, gather_list=None, dst=0, group=None, sync_op=True):
    """Gather `tensor` from every rank (upstream:
    python/paddle/distributed/communication/gather.py). Under SPMD the
    compiled region is rank-uniform, so every rank materializes the
    gathered list (a strict superset of the reference's dst-only
    delivery)."""
    g = _resolve(group)
    tensor = _as_tensor(tensor)
    if g.nranks == 1 or not g.axis_names:
        if gather_list is not None:
            gather_list.append(tensor.clone())
            return gather_list
        return tensor
    if in_manual_context(g.axis_names):
        ax = g.axis_names if len(g.axis_names) > 1 else g.axis_names[0]
        out = apply_op(
            "c_gather",
            lambda x: jax.lax.all_gather(x, ax, axis=0, tiled=False),
            tensor,
        )
        if gather_list is not None:
            from ..tensor.manipulation import unbind

            gather_list.extend(unbind(out, axis=0))
            return gather_list if sync_op else CollectiveTask(
                gather_list[-1]
            )
        return _maybe_task(out, sync_op)
    raise RuntimeError(
        "gather across a real group requires a manual (shard_map) "
        "context; in the GSPMD context use sharding annotations instead"
    )


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    """Scatter chunks from rank `src` (upstream:
    python/paddle/distributed/communication/scatter.py): rank i receives
    tensor_list[i] as held by the src rank."""
    g = _resolve(group)
    if g.nranks == 1 or not g.axis_names:
        if tensor_list:
            tensor.set_value(_as_tensor(tensor_list[0])._data)
        return tensor
    if in_manual_context(g.axis_names) and tensor_list:
        if len(g.axis_names) != 1:
            raise RuntimeError("scatter needs a single-axis group")
        ax = g.axis_names[0]
        if len(tensor_list) != g.nranks:
            raise ValueError(
                f"scatter needs {g.nranks} tensors, got {len(tensor_list)}"
            )
        from ..tensor.manipulation import stack

        stacked = stack([_as_tensor(t) for t in tensor_list], axis=0)

        def fn(x):
            # route through the src rank so the data provably originates
            # there, then take this rank's chunk
            gathered = jax.lax.all_gather(x, ax, axis=0, tiled=False)
            idx = jax.lax.axis_index(ax)
            return gathered[src, idx]

        out = apply_op("c_scatter", fn, stacked)
        _inplace(tensor, out)
        return _maybe_task(tensor, sync_op)
    raise RuntimeError(
        "scatter across a real group requires a manual (shard_map) "
        "context and a tensor_list; in the GSPMD context use sharding "
        "annotations instead"
    )


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    return all_reduce(tensor, op, group, sync_op)


def alltoall(out_tensor_list, in_tensor_list, group=None, sync_op=True):
    g = _resolve(group)
    ins = [_as_tensor(t) for t in in_tensor_list]
    if g.nranks == 1 or not g.axis_names:
        out_tensor_list.extend(t.clone() for t in ins)
        return out_tensor_list
    if in_manual_context(g.axis_names):
        from ..tensor.manipulation import concat, split

        ax = g.axis_names if len(g.axis_names) > 1 else g.axis_names[0]
        stacked = concat(ins, axis=0)
        out = apply_op(
            "c_alltoall",
            lambda x: jax.lax.all_to_all(
                x.reshape((g.nranks, -1) + tuple(x.shape[1:])),
                ax, split_axis=0, concat_axis=0, tiled=False,
            ).reshape(x.shape),
            stacked,
        )
        out_tensor_list.extend(split(out, g.nranks, axis=0))
        return out_tensor_list if sync_op else CollectiveTask(
            out_tensor_list[-1]
        )
    raise RuntimeError(
        "alltoall across a real group requires a manual (shard_map) "
        "context (silent clone would be a wrong answer); wrap the "
        "region with mesh.manual_axes or use fleet MoE/sep utilities"
    )


def alltoall_single(out_tensor, in_tensor, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    g = _resolve(group)
    in_tensor = _as_tensor(in_tensor)
    if g.nranks == 1 or not g.axis_names:
        out_tensor.set_value(in_tensor._data)
        return out_tensor
    if in_manual_context(g.axis_names):
        ax = g.axis_names if len(g.axis_names) > 1 else g.axis_names[0]
        n = g.nranks
        out = apply_op(
            "c_alltoall_single",
            lambda x: jax.lax.all_to_all(
                x.reshape((n, x.shape[0] // n) + tuple(x.shape[1:])),
                ax, split_axis=0, concat_axis=0, tiled=False,
            ).reshape(x.shape),
            in_tensor,
        )
        out_tensor._data = out._data
        out_tensor._grad_node = out._grad_node
        return _maybe_task(out_tensor, sync_op)
    raise RuntimeError(
        "alltoall_single across a real group requires a manual "
        "(shard_map) context (silent copy would be a wrong answer)"
    )


def send(tensor, dst=0, group=None, sync_op=True):
    raise RuntimeError(
        "point-to-point send/recv outside a compiled region is not part of "
        "the SPMD model; use batch_isend_irecv (ppermute) inside a manual "
        "region, or the pipeline schedule's built-in p2p"
    )


recv = send


def isend(tensor, dst=0, group=None):
    """Marker for batch_isend_irecv (standalone async p2p has no SPMD
    meaning — see send)."""
    return P2POp(isend, tensor, dst, group)


def irecv(tensor, src=0, group=None):
    return P2POp(irecv, tensor, src, group)


class P2POp:
    """Upstream: python/paddle/distributed/communication/batch_isend_irecv.py
    P2POp(op, tensor, peer, group). Under single-controller SPMD `peer`
    is a rank *offset pattern*: every rank sends to (rank+peer) % n /
    receives from (rank-peer) % n — the translation-invariant pattern
    that covers the reference's pipeline neighbor-exchange usage."""

    def __init__(self, op, tensor, peer, group=None):
        if op not in (isend, irecv):
            raise ValueError("op must be paddle.distributed.isend/irecv")
        self.op = op
        self.tensor = _as_tensor(tensor)
        self.peer = peer
        self.group = group


class _DoneTask:
    def wait(self):
        return True

    def is_completed(self):
        return True


def batch_isend_irecv(p2p_op_list):
    """Execute a batch of p2p ops as `ppermute`s inside a manual region.

    Each isend(shift=s) rotates its tensor by +s along the group axis;
    the positionally matching irecv(shift=s) receives the rotated value
    into its tensor. Requires a manual (shard_map) context — outside one
    there is no per-rank data to exchange."""
    if not p2p_op_list:
        return []
    g = _resolve(p2p_op_list[0].group)
    if g.nranks == 1 or not g.axis_names:
        # world of one: send-to-self
        sends = [o for o in p2p_op_list if o.op is isend]
        recvs = [o for o in p2p_op_list if o.op is irecv]
        for s, r in zip(sends, recvs):
            r.tensor.set_value(s.tensor._data)
        return [_DoneTask()]
    if not in_manual_context(g.axis_names):
        raise RuntimeError(
            "batch_isend_irecv requires a manual (shard_map) context"
        )
    if len(g.axis_names) != 1:
        raise RuntimeError("batch_isend_irecv needs a single-axis group")
    n = g.nranks
    sends = [o for o in p2p_op_list if o.op is isend]
    recvs = [o for o in p2p_op_list if o.op is irecv]
    if len(sends) != len(recvs):
        raise ValueError(
            "batch_isend_irecv needs matching isend/irecv pairs under "
            f"SPMD (got {len(sends)} sends, {len(recvs)} recvs)"
        )
    for s, r in zip(sends, recvs):
        shift = s.peer % n
        if shift != (-r.peer) % n and shift != r.peer % n:
            raise ValueError(
                "paired isend/irecv offsets disagree: send +%d vs recv %d"
                % (s.peer, r.peer)
            )
        perm = [(i, (i + shift) % n) for i in range(n)]
        out = ppermute(s.tensor, perm, group=g)
        _inplace(r.tensor, out)
    return [_DoneTask()]


def barrier(group=None):
    jax.effects_barrier()
    try:
        (jnp.zeros(()) + 0).block_until_ready()
    except Exception:
        pass


def wait(tensor, group=None, use_calc_stream=True):
    """Block until ``tensor``'s producing collective lands (upstream
    paddle.distributed.wait; PJRT's single ordered stream means
    block_until_ready is the whole contract)."""
    t = _as_tensor(tensor)
    try:
        t._data.block_until_ready()
    except Exception:
        pass
    return t


def monitored_barrier(group=None, timeout=None, wait_all_ranks=False):
    """Barrier that raises if peers don't arrive within ``timeout``
    seconds (upstream monitored_barrier over gloo). Uses the launch
    store (the object-collectives rendezvous) for cross-process
    arrival counting; in-process / single-rank it reduces to
    barrier()."""
    from .object_collectives import _proc_info

    st, rank, world = _proc_info()
    if st is not None and world > 1:
        import time as _time

        key = f"__monitored_barrier_{_MONITORED_SEQ[0]}"
        _MONITORED_SEQ[0] += 1
        st.add(key, 1)
        eff_timeout = 300.0 if timeout is None else float(timeout)
        deadline = _time.monotonic() + eff_timeout
        while int(st.get(key) or 0) < world:
            if _time.monotonic() > deadline:
                raise RuntimeError(
                    f"monitored_barrier: rank {rank} timed out after "
                    f"{eff_timeout}s waiting for {world} ranks")
            _time.sleep(0.01)
    barrier(group)


_MONITORED_SEQ = [0]


def stream_all_reduce(*a, **k):
    return all_reduce(*a, **k)


# in-trace p2p primitive used by the pipeline schedule
def ppermute(tensor, perm, group=None):
    g = _resolve(group)
    tensor = _as_tensor(tensor)
    if g.nranks == 1 or not g.axis_names:
        return tensor
    ax = g.axis_names if len(g.axis_names) > 1 else g.axis_names[0]
    return apply_op(
        "c_ppermute", lambda x: jax.lax.ppermute(x, ax, perm), tensor
    )
