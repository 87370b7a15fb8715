"""Distributed checkpoint: sharded, async, topology-resharding
(upstream: python/paddle/distributed/checkpoint/save_state_dict.py,
load_state_dict.py + the auto_parallel dist-checkpoint converter).

Layout (one directory per checkpoint):
    manifest.json   — tensor index: name -> {shape, dtype, chunks:[{
                      index: [[lo,hi],...], file, offset, nbytes}]},
                      plus JSON-able non-tensor leaves
    shard_{p}.bin   — process p's chunk payloads, back-to-back
    meta.pkl        — non-JSON-able leaves (pickle), if any

Design (TPU-native):
* every process writes only the chunks it owns (`addressable_shards`
  whose first replica lives on a local device) — no cross-host gather
  on save; single-controller runs degenerate to one shard file;
* save is asynchronous by default-able: the device->host pull and file
  write run on a background thread. Snapshot consistency is free
  because jax arrays are immutable — the train step replaces
  `Tensor._data` rather than mutating buffers, so the thread's
  references pin the exact step-N values;
* load reshards: chunks are reassembled and re-placed onto the *target*
  tensor's current NamedSharding, so a checkpoint saved on one
  dp×mp×pp×sharding topology loads onto any other (the role of the
  reference's dist_checkpoint converter). Chunked storage keeps
  slice-level partial reads possible for multi-host scale.
"""
from __future__ import annotations

import json
import os
import pickle
import threading

import jax
import numpy as np

from ...framework.core import Tensor

__all__ = [
    "save_state_dict",
    "load_state_dict",
    "AsyncCheckpointHandle",
]

_SEP = "/"


def _flatten(obj, prefix=""):
    """Flatten nested dict/list structure to {path: leaf}."""
    out = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            out.update(_flatten(v, f"{prefix}{_SEP}{k}" if prefix else str(k)))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            out.update(_flatten(v, f"{prefix}{_SEP}{i}" if prefix else str(i)))
    else:
        out[prefix] = obj
    return out


def _np_dtype(name):
    if name == "bfloat16":
        return np.dtype(jax.numpy.bfloat16)
    return np.dtype(name)


def _shard_index(arr, shard):
    """Concrete [[lo,hi],...] bounds of one addressable shard."""
    idx = shard.index
    bounds = []
    for dim, sl in zip(arr.shape, idx):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        bounds.append([start, stop])
    return bounds


def _owned_chunks(arr):
    """The chunks this process must write: for each distinct index, the
    GLOBAL lowest-id device among its replicas owns it; we write only
    the chunks whose owner is one of our addressable devices — so
    replicated tensors are stored exactly once across all hosts."""
    owner_by_index = {}
    try:
        imap = arr.sharding.devices_indices_map(arr.shape)
    except Exception:
        imap = None
    if imap is not None:
        for dev, idx in imap.items():
            bounds = []
            for dim, sl in zip(arr.shape, idx):
                start = 0 if sl.start is None else int(sl.start)
                stop = dim if sl.stop is None else int(sl.stop)
                bounds.append((start, stop))
            key = tuple(bounds)
            dev_id = getattr(dev, "id", 0)
            cur = owner_by_index.get(key)
            if cur is None or dev_id < cur:
                owner_by_index[key] = dev_id
    out = []
    seen = set()
    for sh in arr.addressable_shards:
        key = tuple(map(tuple, _shard_index(arr, sh)))
        dev_id = getattr(sh.device, "id", 0)
        owner = owner_by_index.get(key, dev_id)
        if dev_id == owner and key not in seen:
            seen.add(key)
            out.append((list(map(list, key)), sh))
    return out


class AsyncCheckpointHandle:
    def __init__(self, thread=None, error=None):
        self._thread = thread
        self._error = [error]

    def wait(self):
        if self._thread is not None:
            self._thread.join()
        if self._error[0] is not None:
            raise self._error[0]
        return True

    result = wait

    def done(self):
        return self._thread is None or not self._thread.is_alive()


def save_state_dict(state_dict, path, process_index=None,
                    async_save=False, coordinator_rank=0):
    """Write `state_dict` (nested dict of Tensors / scalars) to `path`.
    Returns an AsyncCheckpointHandle (already complete when
    async_save=False)."""
    flat = _flatten(state_dict)
    proc = process_index
    if proc is None:
        proc = jax.process_index()
    os.makedirs(path, exist_ok=True)

    # snapshot the array refs now (immutability makes this a consistent
    # point-in-time view even while training continues)
    tensor_items = []
    meta_json = {}
    meta_pkl = {}
    for name, leaf in flat.items():
        if isinstance(leaf, Tensor):
            tensor_items.append((name, leaf._data))
        elif hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            tensor_items.append((name, leaf))
        else:
            try:
                json.dumps(leaf)
                meta_json[name] = leaf
            except (TypeError, ValueError):
                meta_pkl[name] = leaf

    try:
        n_procs = jax.process_count()
    except Exception:
        n_procs = 1

    def _write():
        shard_file = f"shard_{proc}.bin"
        manifest = {"format": 1, "process_index": proc,
                    "process_count": n_procs, "tensors": {},
                    "meta": meta_json}
        offset = 0
        with open(os.path.join(path, shard_file), "wb") as f:
            for name, arr in tensor_items:
                entry = {
                    "shape": list(arr.shape),
                    "dtype": str(arr.dtype),
                    "chunks": [],
                }
                for bounds, sh in _owned_chunks(arr):
                    data = np.asarray(sh.data)
                    raw = data.tobytes()
                    entry["chunks"].append({
                        "index": bounds,
                        "file": shard_file,
                        "offset": offset,
                        "nbytes": len(raw),
                    })
                    f.write(raw)
                    offset += len(raw)
                manifest["tensors"][name] = entry
        if meta_pkl and proc == coordinator_rank:
            # single writer — every process holds the same replicated
            # non-tensor leaves, so N concurrent writers would only race
            with open(os.path.join(path, "meta.pkl"), "wb") as f:
                pickle.dump(meta_pkl, f)
        # manifest written last = commit point (partial checkpoints
        # are detectable by its absence)
        man_path = os.path.join(path, f"manifest_{proc}.json")
        with open(man_path, "w") as f:
            json.dump(manifest, f)
        if proc == coordinator_rank:
            # drop manifests from a previous larger-world save into the
            # same directory, so load doesn't merge stale chunk tables
            for fn in os.listdir(path):
                if fn.startswith("manifest_") and fn.endswith(".json"):
                    try:
                        p = int(fn[len("manifest_"):-len(".json")])
                    except ValueError:
                        continue
                    if p >= n_procs:
                        try:
                            os.remove(os.path.join(path, fn))
                        except OSError:
                            pass
            with open(os.path.join(path, "manifest.json"), "w") as f:
                json.dump(manifest, f)

    if not async_save:
        _write()
        return AsyncCheckpointHandle()

    handle = AsyncCheckpointHandle()

    def _run():
        try:
            _write()
        except BaseException as e:  # surfaced on wait()
            handle._error[0] = e

    t = threading.Thread(target=_run, name="ckpt-save", daemon=True)
    handle._thread = t
    t.start()
    return handle


def _read_manifests(path):
    """Merge the per-process manifests of the LAST save (chunks union
    per tensor). The coordinator's manifest.json records
    process_count; only manifest_0..process_count-1 belong to the
    current checkpoint (higher ranks are stale leftovers)."""
    n_procs = None
    top = os.path.join(path, "manifest.json")
    if os.path.exists(top):
        with open(top) as f:
            n_procs = json.load(f).get("process_count")
    manifests = []
    for fn in sorted(os.listdir(path)):
        if fn.startswith("manifest_") and fn.endswith(".json"):
            try:
                p = int(fn[len("manifest_"):-len(".json")])
            except ValueError:
                continue
            if n_procs is not None and p >= n_procs:
                continue
            with open(os.path.join(path, fn)) as f:
                manifests.append(json.load(f))
    if n_procs is not None and len(manifests) < n_procs:
        raise ValueError(
            f"checkpoint at {path} is torn: expected {n_procs} "
            f"process manifests, found {len(manifests)}"
        )
    if not manifests:
        with open(top) as f:
            manifests.append(json.load(f))
    merged = {"tensors": {}, "meta": {}}
    for m in manifests:
        merged["meta"].update(m.get("meta", {}))
        for name, entry in m["tensors"].items():
            tgt = merged["tensors"].setdefault(
                name, {"shape": entry["shape"], "dtype": entry["dtype"],
                       "chunks": []}
            )
            tgt["chunks"].extend(entry["chunks"])
    return merged


def _assemble(path, entry):
    """Reassemble a tensor's global ndarray from its chunks."""
    dtype = _np_dtype(entry["dtype"])
    shape = tuple(entry["shape"])
    out = np.empty(shape, dtype)
    covered = np.zeros(shape, bool) if shape else np.zeros((1,), bool)
    files = {}
    for ch in entry["chunks"]:
        f = files.get(ch["file"])
        if f is None:
            f = open(os.path.join(path, ch["file"]), "rb")
            files[ch["file"]] = f
        f.seek(ch["offset"])
        raw = f.read(ch["nbytes"])
        idx = tuple(slice(lo, hi) for lo, hi in ch["index"])
        sub_shape = tuple(hi - lo for lo, hi in ch["index"])
        out[idx] = np.frombuffer(raw, dtype=dtype).reshape(sub_shape)
        if shape:
            covered[idx] = True
        else:
            covered[0] = True
    for f in files.values():
        f.close()
    if not covered.all():
        # torn checkpoint (e.g. one process died pre-manifest): refuse
        # to resume from uninitialized memory
        raise ValueError(
            "checkpoint chunks do not cover the full tensor "
            f"(shape {shape}); a writer's manifest is likely missing"
        )
    return out


def load_state_dict(state_dict, path, process_index=None):
    """Fill `state_dict`'s tensors in place from the checkpoint at
    `path`, resharding every tensor onto its CURRENT placement (which
    may differ from the topology it was saved under)."""
    merged = _read_manifests(path)
    meta = dict(merged["meta"])
    pkl_path = os.path.join(path, "meta.pkl")
    if os.path.exists(pkl_path):
        with open(pkl_path, "rb") as f:
            meta.update(pickle.load(f))

    flat = _flatten(state_dict)
    missing = []
    for name, leaf in flat.items():
        if isinstance(leaf, Tensor):
            entry = merged["tensors"].get(name)
            if entry is None:
                missing.append(name)
                continue
            arr = _assemble(path, entry)
            target = leaf._data
            if str(arr.dtype) != str(target.dtype):
                arr = arr.astype(_np_dtype(str(target.dtype)))
            sharding = getattr(target, "sharding", None)
            # re-place only onto real (named/multi-device) shardings;
            # plain single-device arrays stay uncommitted so they can
            # keep composing with mesh-placed operands
            if isinstance(sharding, jax.sharding.NamedSharding):
                leaf._data = jax.device_put(arr, sharding)
            else:
                leaf._data = jax.numpy.asarray(arr)
            leaf._version += 1
        elif name in meta:
            _set_nested(state_dict, name.split(_SEP), meta[name])
    if missing:
        raise KeyError(
            f"checkpoint at {path} is missing tensors: {missing[:5]}"
            + ("..." if len(missing) > 5 else "")
        )
    return state_dict


def _set_nested(obj, parts, value):
    for p in parts[:-1]:
        if isinstance(obj, (list, tuple)):
            obj = obj[int(p)]
        else:
            obj = obj[p]
    last = parts[-1]
    if isinstance(obj, (list,)):
        obj[int(last)] = value
    elif isinstance(obj, dict):
        obj[last] = value
