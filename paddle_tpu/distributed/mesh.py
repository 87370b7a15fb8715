"""Global device mesh + axis-context management.

This is the TPU-native replacement for the reference's ProcessGroup/
NCCLComm machinery (upstream: paddle/fluid/distributed/collective/
process_group_nccl.cc): a "communication group" is a set of named mesh
axes on the global `jax.sharding.Mesh`; collectives inside compiled
regions are `lax.psum`-family ops over those names, and XLA picks the
ICI algorithms (the role ncclAllReduce ring/tree selection plays).

Two execution contexts:
* GSPMD context (default): arrays are global, shardings are annotations,
  XLA inserts collectives. Eager collectives are identity-on-global-
  array (the reduction is already part of op semantics).
* manual context (inside a framework-managed shard_map, used by the
  pipeline schedule, ring attention, and MoE all_to_all): Tensor._data
  holds the per-device shard and collectives lower to explicit lax ops.
  `_MANUAL_AXES` tracks which axis names are currently manual.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_state = threading.local()


def _manual_axes() -> set:
    if not hasattr(_state, "manual"):
        _state.manual = set()
    return _state.manual


@contextlib.contextmanager
def manual_axes(names):
    s = _manual_axes()
    added = [n for n in names if n not in s]
    s.update(added)
    try:
        yield
    finally:
        for n in added:
            s.discard(n)


def in_manual_context(names) -> bool:
    s = _manual_axes()
    return all(n in s for n in names)


def shard_map(f, mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` manual over ``axis_names`` (all mesh axes when
    None). ``check_vma=False``: the replication checker cannot infer the
    replication the ring collectives and custom_vjp bodies produce."""
    kwargs = {}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False, **kwargs)


def dividing_axis(name, *dims):
    """``name`` when the global mesh splits every one of ``dims`` evenly
    over that axis (degree > 1), else None (replicate)."""
    d = axis_degree(name)
    return name if d > 1 and all(x % d == 0 for x in dims) else None


def kernel_shard_map(f, in_specs, out_specs):
    """Run a Pallas TPU kernel body per shard of the global mesh. Mosaic
    kernels cannot be partitioned automatically: under a multi-device jit
    the TPU lowering refuses them ("wrap the call in a shard_map") unless
    every mesh axis is manual. Returns ``f`` itself where there is nothing
    to wrap — no mesh, one device, or already fully manual."""
    m = global_mesh()
    if m is None or m.size == 1 or in_manual_context(m.axis_names):
        return f
    return shard_map(f, mesh=m, in_specs=in_specs, out_specs=out_specs)


class GlobalMesh:
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.axis_degrees = {}

    def build(self, axis_names: Sequence[str], degrees: Sequence[int],
              devices=None):
        devices = devices if devices is not None else np.array(jax.devices())
        total = int(np.prod(degrees))
        if total > len(devices):
            raise ValueError(
                f"mesh degrees {dict(zip(axis_names, degrees))} need {total} "
                f"devices but only {len(devices)} available"
            )
        devices = np.array(devices[:total]).reshape(tuple(degrees))
        self.mesh = Mesh(devices, tuple(axis_names))
        self.axis_degrees = dict(zip(axis_names, degrees))
        return self.mesh


_GLOBAL = GlobalMesh()


def global_mesh() -> Optional[Mesh]:
    return _GLOBAL.mesh


def build_global_mesh(axis_names, degrees, devices=None):
    return _GLOBAL.build(axis_names, degrees, devices)


def axis_degree(name) -> int:
    return _GLOBAL.axis_degrees.get(name, 1)


def named_sharding(*spec) -> Optional[NamedSharding]:
    m = global_mesh()
    if m is None:
        return None
    return NamedSharding(m, PartitionSpec(*spec))


def active_axis_info() -> dict:
    """Introspection view of the active global mesh for tooling (the
    jit linter's collective-axis checks, framework/analysis.py): axis
    names, per-axis degrees, and total device count."""
    m = global_mesh()
    return {
        "axes": set(m.axis_names) if m is not None else set(),
        "degrees": dict(_GLOBAL.axis_degrees),
        "n_devices": int(m.size) if m is not None else 1,
    }


def reset_mesh():
    _GLOBAL.mesh = None
    _GLOBAL.axis_degrees = {}


@contextlib.contextmanager
def suspend_mesh():
    """Temporarily hide the global mesh (sharding constraints become
    no-ops) — used to trace device-agnostic export artifacts."""
    mesh, degrees = _GLOBAL.mesh, _GLOBAL.axis_degrees
    _GLOBAL.mesh, _GLOBAL.axis_degrees = None, {}
    try:
        yield
    finally:
        _GLOBAL.mesh, _GLOBAL.axis_degrees = mesh, degrees
