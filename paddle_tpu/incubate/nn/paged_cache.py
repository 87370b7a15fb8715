"""Paged KV-cache manager for continuous-batching decode (upstream
analog: the BlockManager/paged cache machinery behind PaddleNLP's
serving of fused_multi_transformer; kernel side in
ops/kernels/paged_attention.py).

The manager is host-side bookkeeping (page free-list + per-sequence
tables); the cache pages themselves are device arrays updated with
static-shape `dynamic_update_slice` writes, so every op stays
jit-compilable.

Pages are REFERENCE-COUNTED so they can be shared across owners — the
enabler for cross-request prefix caching (inference/prefix_cache.py):

* every page in use carries a refcount; the free list is exactly the
  refcount-zero set;
* ``attach(seq_id, pages, length)`` registers a sequence directly on
  an existing (shared) page chain instead of empty — each chain page
  gains a reference;
* a write into a shared page (refcount > 1) forks it first
  (copy-on-write): the writer gets a private copy, every other owner
  keeps the original bytes;
* ``free``/``truncate`` only drop references; a page returns to the
  pool when its last reference dies;
* ``incref``/``decref`` let a non-sequence owner (the radix prefix
  tree) hold pages alive after the sequence that wrote them retires.

Page formats (``page_format=``): ``"kv"`` (the default) keeps per-head
K and V arrays ``(num_pages, page_size, kv_heads, head_dim)``;
``"latent"`` keeps ONE array ``(num_pages, page_size, head_dim)`` and no
V array: a latent-attention (MLA) layer caches one row a token, the
normed latent beside the rotated shared rope key, and the absorbed kernel
reads that row as the key and its leading ``value_dim`` numbers as the
value (:meth:`PagedKVCacheManager.latent_ragged_step`). Booking, page
tables, reference counts, copy-on-write, prefix attach, the sanitizer and
the ``pool.*`` spans are the same code for both; what moves K/V-shaped
records (the append/attend family, host swap, the page-chain wire, int8
calibration) refuses a latent pool by name. ``"eva"`` (window and
summary, ``window_tokens=``) keeps K and V arrays like ``"kv"`` and TWO
chains a sequence in them: the window chain holds the tokens of the
current aligned window of ``window_tokens`` and is released whole when
the sequence passes a multiple of it (span ``pool.roll``); the summary
chain, booked under a derived id (:meth:`PagedKVCacheManager.
_summary_key`) so that refcounts, invariants and the sanitizer cover it
with the same code, gains one row for every page the window chain fills
(the model's chunk is the page), written by the layer program in the
step that fills the page. The table a step hands the kernel is, for a row
in window w, [the summary pages of windows 0..w-1 ; the window's pages],
its length one a visible summary row and one a window token: causal
attention over it is one softmax over the window's exact keys and every
earlier chunk's pooled key. ``pages_for(n)`` is what admission reserves
(one window's pages and n / page_size^2 summary pages at most, not n /
page_size). Only :meth:`PagedKVCacheManager.layer_step` writes such a
pool; prefix attach, host swap, the page-chain wire, the append/attend
family and ``truncate`` across a roll refuse it by name.

Quantized pages (``kv_dtype="int8"``): pages store int8 with a
per-page, PER-HEAD float32 scale sidecar ``k_scales``/``v_scales``
(num_pages, kv_heads) — half the HBM bytes per token, so the same HBM
budget holds ~2x the sequences. The sidecar rides the same physical
page ids as the payload, so refcount/COW/prefix sharing need no extra
bookkeeping: shared pages share their scale row, and a copy-on-write
fork copies the scale row with the bytes. Appends requantize: a token
whose abs-max exceeds the page's current scale grows the scale and
rescales the already-stored slots (round(q_old * old/new) — bounded
extra rounding, page_size slots at most). Dequant is fused into the
paged-attention kernels (scales ride scalar prefetch). The sidecar is
pool-private state: serving layers must never write
``k_scales``/``v_scales`` directly (enforced by
tools/lint_codebase.py).

Sanitizer (``FLAGS_page_sanitizer`` or the ``sanitizer=`` kwarg;
incubate/nn/page_sanitizer.py): in ``warn``/``strict`` mode every
mutation here — alloc/attach/incref/decref/free/truncate, the
copy-on-write fork, each append flavor, and every page table handed
to a kernel — is mirrored as a typed event into a bounded journal and
validated against a shadow heap with per-page generation counters
(use-after-free, double-free, refcount leaks, COW violations, stale
kernel inputs, capacity drift). ``off`` (the default) allocates no
shadow objects: each instrumented method pays one ``is None`` check.
Tiered swap (``HostKVSwapSpace``): preemption pages a victim
sequence's KV out to HOST buffers and back. ``swap_out`` copies the
sequence's PRIVATE pages (refcount 1 — payload plus, when quantized,
the per-page scale sidecar rows) to host bitwise and releases them;
SHARED pages (a prefix-cache hit, a still-shared COW tail) stay
on-device under an external "swap hold" reference, so pinning blocks
eviction of shared pages but never blocks swapping the private ones.
``swap_in`` draws fresh pages, restores the private bytes bitwise,
takes the sequence references back and drops the holds — the restored
chain is byte-identical to the swapped-out one, so greedy decode
resumes exactly where it stopped. Swap records live ONLY in the
byte-budgeted :class:`HostKVSwapSpace`; every transition is mirrored
into the sanitizer shadow heap (``swap_out``/``swap_in`` events with
generation-tagged kept pages — a hold lost while swapped out surfaces
as use-after-free at swap-in, not as silent KV aliasing).

ALL pool state (``k_pages``/``v_pages``/``k_scales``/``v_scales``,
``_refcnt``/``_free``/``_tables``/``_lens``/``_ext_refs``, and the
swap tier's ``_swap_store``/``_swap_used``) is pool-private —
tools/lint_codebase.py's mutation audit rejects writes or
private-method calls from serving code, so the sanitizer's event
coverage is complete by construction.
"""
from __future__ import annotations

import collections
import itertools
import json
import struct

import numpy as np

import jax
import jax.numpy as jnp

from ...framework import concurrency as _concurrency
from ...framework import telemetry
from ...framework.core import Tensor, apply_op, _as_tensor
from ...framework.flags import flag
from ...ops.kernels.paged_attention import paged_attention as _kernel
from ...ops.kernels.paged_attention import (
    paged_ragged_attention as _ragged_kernel_fn,
)
from ...ops.kernels.paged_attention import (
    paged_ragged_layer_step as _layer_step_fn,
)
from ...ops.kernels.paged_attention import (
    latent_ragged_step as _latent_step_fn,
)
from ...ops.kernels.paged_attention import upload_plan as _upload
from ...ops.kernels.quant import kv_head_scale, quantize_kv

__all__ = ["PagedKVCacheManager", "paged_attention",
           "HostKVSwapSpace", "SwapSpaceFull", "SwapWireError",
           "SWAP_WIRE_MAGIC", "SWAP_WIRE_VERSION"]

_pool_uids = itertools.count()

# page-chain wire format (export_seq/import_seq): every payload leads
# with this magic + a version word so a decode worker running drifted
# code REFUSES the bytes loudly instead of bitwise-corrupting KV.
# Bump SWAP_WIRE_VERSION on ANY layout change (header fields, buffer
# order, shard tagging) — mixed-version fleets must fail at ingress.
SWAP_WIRE_MAGIC = b"PKVC"
SWAP_WIRE_VERSION = 1
_WIRE_HEAD = struct.Struct("<4sII")  # magic, version, header length


class SwapSpaceFull(RuntimeError):
    """The host swap space cannot hold another record under its byte
    budget (FLAGS_serving_swap_bytes) — the caller should pick a
    different victim or fall back to blocking admission."""


class SwapWireError(RuntimeError):
    """A page-chain wire payload failed validation at (de)serialize:
    bad magic, a version mismatch between workers, an incomplete or
    overlapping shard set, or geometry that does not match the
    destination pool. Raised LOUDLY — a silent fallback would restore
    corrupt KV bytes and decode garbage."""


class _SwapRecord:
    """One swapped-out sequence for ONE layer pool: the page chain as
    it stood (``pages``/``kept``/``length``), host copies of the
    private pages' payload (+ int8 scale rows), and the sanitizer
    generations of the kept pages captured at swap-out."""

    __slots__ = ("pages", "kept", "length", "k_host", "v_host",
                 "k_scales_host", "v_scales_host", "gens", "nbytes",
                 "trace_ctx")

    def __init__(self, pages, kept, length, k_host, v_host,
                 k_scales_host, v_scales_host, gens, nbytes,
                 trace_ctx=None):
        self.pages = pages
        self.kept = kept
        self.length = length
        self.k_host = k_host
        self.v_host = v_host
        self.k_scales_host = k_scales_host
        self.v_scales_host = v_scales_host
        self.gens = gens
        self.nbytes = nbytes
        # serialized TraceContext wire (telemetry.TraceContext): the
        # swapped-out sequence's trace identity travels WITH the
        # record, so a restore — on this worker or, once records go
        # over the wire, on a decode worker — resumes the same trace
        self.trace_ctx = trace_ctx


class HostKVSwapSpace:
    """Byte-budgeted host tier for swapped-out KV page chains.

    One space is shared by every layer pool of a model (and budgets
    them jointly); records are keyed by (pool uid, seq id). The store
    itself (``_swap_store``/``_swap_used``) is swap-tier-private
    state, writable only through the pool's ``swap_out`` /
    ``swap_in`` / ``swap_discard`` — the lint pool-mutation audit
    extends to it, so the sanitizer's swap events see every
    transition. Serving code reads the public byte/record accessors
    only."""

    def __init__(self, capacity_bytes):
        self.capacity_bytes = int(capacity_bytes)
        self._swap_store = {}
        self._swap_used = 0
        # lifetime counters (bench/test visibility)
        self.swapped_out_records = 0
        self.swapped_in_records = 0
        self.exported_records = 0
        self.imported_records = 0
        self.peak_used_bytes = 0
        # transfer-plane telemetry (pool.transfer_* counters); None
        # when FLAGS_telemetry=off — each site pays one check
        self._reg = telemetry.registry()
        # concurrency-sanitizer handle (framework/concurrency.py):
        # the store is single-writer by contract — only the thread
        # driving the pools' swap_out/swap_in mutates it, while the
        # ops-server scrape reads summary() as a GIL-atomic snapshot
        _csan = _concurrency.sanitizer()
        self._cv = None if _csan is None else _csan.shared(
            "paged_cache.swap.store", owner=self, single_writer=True)

    # -- public (serving-visible) readout ----------------------------------
    @property
    def used_bytes(self) -> int:
        return self._swap_used

    @property
    def free_bytes(self) -> int:
        return max(self.capacity_bytes - self._swap_used, 0)

    @property
    def num_records(self) -> int:
        return len(self._swap_store)

    def would_fit(self, nbytes: int) -> bool:
        return self._swap_used + int(nbytes) <= self.capacity_bytes

    def holds(self, seq_id) -> bool:
        """True if ANY pool holds a swap record for ``seq_id``."""
        return any(k[1] == seq_id for k in self._swap_store)

    def trace_context(self, seq_id):
        """The swapped-out sequence's serialized TraceContext wire
        (telemetry.TraceContext.to_wire()), read off its swap
        records — what a receiving decode worker extracts to resume
        the request's trace. None when the sequence is not swapped
        here or was never stamped."""
        for k, rec in self._swap_store.items():
            if k[1] == seq_id and rec.trace_ctx is not None:
                return rec.trace_ctx
        return None

    def summary(self) -> dict:
        return {
            "capacity_bytes": self.capacity_bytes,
            "used_bytes": self._swap_used,
            "peak_used_bytes": self.peak_used_bytes,
            "records": len(self._swap_store),
            "swapped_out_records": self.swapped_out_records,
            "swapped_in_records": self.swapped_in_records,
            "exported_records": self.exported_records,
            "imported_records": self.imported_records,
        }

    # -- page-chain wire transfer (disaggregated serving) ------------------
    @staticmethod
    def _wire_np_dtype(name):
        """Numpy dtype for a wire-declared kv dtype name (bfloat16
        resolves through jax's ml_dtypes registration)."""
        try:
            return np.dtype(name)
        except TypeError:
            return np.dtype(getattr(jnp, name))

    def export_seq(self, seq_id, pools, mp_shards=1):
        """Serialize a swapped-out sequence's page chains (one swap
        record per layer pool, in ``pools`` order) into ``mp_shards``
        self-describing byte payloads and DROP the source records —
        the bytes leave this worker. Shard ``r`` carries the
        contiguous KV-head slice ``[r*H/N, (r+1)*H/N)`` of every
        record (payload + int8 scale sidecar rows, bitwise), so each
        payload lands on exactly the ``mp`` shard owning those heads.
        Only fully-PRIVATE chains can travel: a kept (shared) page is
        a prefix-cache/COW reference into THIS worker's pool and
        raises :class:`SwapWireError`. Atomic: validation happens
        before any record is popped."""
        mp_shards = int(mp_shards)
        if mp_shards < 1:
            raise ValueError("export_seq: mp_shards must be >= 1")
        for pool in pools:
            pool._plain_kv("export_seq")
        if not pools:
            raise ValueError("export_seq: no pools given")
        recs = []
        for pool in pools:
            rec = self._swap_get((pool._uid, seq_id))
            if any(rec.kept):
                raise SwapWireError(
                    f"export_seq({seq_id!r}): the chain holds "
                    f"{sum(rec.kept)} shared (kept) page(s) — "
                    "prefix-cache/COW references cannot cross "
                    "workers; hand off only fully-private chains")
            recs.append(rec)
        g = pools[0]
        heads = g.k_pages.shape[2]
        head_dim = g.k_pages.shape[3]
        if heads % mp_shards:
            raise SwapWireError(
                f"export_seq({seq_id!r}): {heads} KV heads do not "
                f"split into {mp_shards} mp shards")
        per = heads // mp_shards
        payloads = []
        for r in range(mp_shards):
            h0, h1 = r * per, (r + 1) * per
            metas, bufs = [], []
            for pool, rec in zip(pools, recs):
                npriv = 0 if rec.k_host is None else len(rec.k_host)
                metas.append({
                    "pages": [int(p) for p in rec.pages],
                    "length": int(rec.length),
                    "npriv": int(npriv),
                    "trace_ctx": rec.trace_ctx,
                    "quantized": bool(pool.quantized),
                })
                if npriv:
                    bufs.append(np.ascontiguousarray(
                        rec.k_host[:, :, h0:h1, :]).tobytes())
                    bufs.append(np.ascontiguousarray(
                        rec.v_host[:, :, h0:h1, :]).tobytes())
                    if pool.quantized:
                        bufs.append(np.ascontiguousarray(
                            rec.k_scales_host[:, h0:h1]).tobytes())
                        bufs.append(np.ascontiguousarray(
                            rec.v_scales_host[:, h0:h1]).tobytes())
            header = json.dumps({
                "seq_id": str(seq_id),
                "shard": {"rank": r, "size": mp_shards,
                          "head_start": int(g.head_start + h0),
                          "heads": int(per)},
                "geometry": {
                    "page_size": int(g.page_size),
                    "head_dim": int(head_dim),
                    "kv_dtype": str(g.kv_dtype),
                    "kv_heads_global": int(g.kv_heads_global),
                    "layers": len(pools),
                },
                "records": metas,
            }, sort_keys=True).encode("utf-8")
            payloads.append(
                _WIRE_HEAD.pack(SWAP_WIRE_MAGIC, SWAP_WIRE_VERSION,
                                len(header))
                + header + b"".join(bufs))
        # validation passed for every layer: the records leave now
        for pool in pools:
            self._swap_pop((pool._uid, seq_id))
        self.exported_records += len(recs)
        if self._reg is not None:
            self._reg.inc("pool.transfer_out_records", len(recs))
            self._reg.inc("pool.transfer_out_bytes",
                          sum(len(p) for p in payloads))
        return payloads

    @staticmethod
    def _parse_wire(payload):
        """Split one wire payload into (header dict, buffer bytes),
        refusing bad magic / version drift LOUDLY."""
        if len(payload) < _WIRE_HEAD.size:
            raise SwapWireError(
                "page-chain payload truncated: %d bytes is shorter "
                "than the %d-byte wire header"
                % (len(payload), _WIRE_HEAD.size))
        magic, version, hlen = _WIRE_HEAD.unpack_from(payload)
        if magic != SWAP_WIRE_MAGIC:
            raise SwapWireError(
                "not a KV page-chain payload: magic %r != %r — "
                "refusing to deserialize (bitwise KV corruption)"
                % (magic, SWAP_WIRE_MAGIC))
        if version != SWAP_WIRE_VERSION:
            raise SwapWireError(
                "page-chain wire version mismatch: payload v%d, this "
                "worker speaks v%d — upgrade the drifted worker; a "
                "silent fallback would restore corrupt KV bytes"
                % (version, SWAP_WIRE_VERSION))
        head_end = _WIRE_HEAD.size + hlen
        try:
            header = json.loads(payload[_WIRE_HEAD.size:head_end])
        except ValueError as e:
            raise SwapWireError(
                "page-chain header is not valid JSON: %s" % e)
        return header, payload[head_end:]

    def import_seq(self, seq_id, payloads, pools):
        """Deserialize a complete mp shard set of page-chain payloads
        (from :meth:`export_seq` on the prefill worker) into THIS
        space, keyed to the destination ``pools`` — afterwards the
        standard ``pool.swap_in`` restore path (and
        :meth:`trace_context`, the decode-worker trace ingress) see
        the sequence exactly as if it had been swapped out locally.
        Each destination pool takes the KV-head range it owns
        (``head_start .. head_start+local``), so full-width and
        mp-sharded decode pools both reassemble from the same shard
        set. Atomic: shard-set completeness, geometry, duplicate keys
        and the byte budget are all validated before any record is
        stored. Returns the host bytes stored."""
        for pool in pools:
            pool._plain_kv("import_seq")
        parsed = sorted((self._parse_wire(p) for p in payloads),
                        key=lambda hp: hp[0]["shard"]["rank"])
        if not parsed:
            raise SwapWireError("import_seq: no payloads given")
        first = parsed[0][0]
        size = int(first["shard"]["size"])
        ranks = [h["shard"]["rank"] for h, _ in parsed]
        if ranks != list(range(size)):
            raise SwapWireError(
                f"import_seq({seq_id!r}): incomplete shard set — got "
                f"ranks {ranks} of a {size}-shard export")
        geo = first["geometry"]
        for h, _ in parsed[1:]:
            if h["geometry"] != geo or h["seq_id"] != first["seq_id"]:
                raise SwapWireError(
                    f"import_seq({seq_id!r}): shard headers disagree "
                    "on sequence/geometry — mixed exports?")
        if len(pools) != int(geo["layers"]):
            raise SwapWireError(
                f"import_seq({seq_id!r}): export carries "
                f"{geo['layers']} layer record(s), destination has "
                f"{len(pools)} pool(s)")
        dt = self._wire_np_dtype(geo["kv_dtype"])
        ps, hd = int(geo["page_size"]), int(geo["head_dim"])
        quant = dt.name == "int8"
        # slice each payload's buffers per record, then reassemble
        # the head axis per destination pool
        shards = []  # [(head_start, heads, [record buffers])]
        for h, buf in parsed:
            sh = h["shard"]
            heads = int(sh["heads"])
            off, per_rec = 0, []
            for meta in h["records"]:
                npriv = int(meta["npriv"])
                nk = npriv * ps * heads * hd * dt.itemsize
                ns = npriv * heads * 4
                need = 2 * nk + (2 * ns if quant else 0)
                if off + need > len(buf):
                    raise SwapWireError(
                        f"import_seq({seq_id!r}): payload truncated "
                        f"mid-record ({len(buf)} bytes, need "
                        f"{off + need})")
                shape = (npriv, ps, heads, hd)
                k = np.frombuffer(buf, dt, npriv * ps * heads * hd,
                                  off).reshape(shape)
                v = np.frombuffer(buf, dt, npriv * ps * heads * hd,
                                  off + nk).reshape(shape)
                off += 2 * nk
                ks = vs = None
                if quant:
                    ks = np.frombuffer(
                        buf, np.float32, npriv * heads,
                        off).reshape(npriv, heads)
                    vs = np.frombuffer(
                        buf, np.float32, npriv * heads,
                        off + ns).reshape(npriv, heads)
                    off += 2 * ns
                per_rec.append((k, v, ks, vs))
            shards.append((int(sh["head_start"]), heads, per_rec))
        pend = []
        total = 0
        for li, pool in enumerate(pools):
            if (pool.page_size != ps
                    or pool.k_pages.shape[3] != hd
                    or pool.kv_dtype != geo["kv_dtype"]
                    or pool.kv_heads_global
                    != int(geo["kv_heads_global"])):
                raise SwapWireError(
                    f"import_seq({seq_id!r}): destination pool "
                    f"{li} geometry (page_size={pool.page_size}, "
                    f"head_dim={pool.k_pages.shape[3]}, "
                    f"kv_dtype={pool.kv_dtype}, kv_heads_global="
                    f"{pool.kv_heads_global}) does not match the "
                    f"export's {geo}")
            key = (pool._uid, seq_id)
            if key in self._swap_store:
                raise SwapWireError(
                    f"import_seq({seq_id!r}): this space already "
                    f"holds a record for pool {li}")
            p0 = pool.head_start
            p1 = p0 + pool.k_pages.shape[2]
            meta = first["records"][li]
            npriv = int(meta["npriv"])
            kparts, vparts, ksparts, vsparts = [], [], [], []
            covered = 0
            for h0, heads, per_rec in shards:
                lo, hi = max(h0, p0), min(h0 + heads, p1)
                if lo >= hi:
                    continue
                k, v, ks, vs = per_rec[li]
                kparts.append(k[:, :, lo - h0:hi - h0, :])
                vparts.append(v[:, :, lo - h0:hi - h0, :])
                if quant:
                    ksparts.append(ks[:, lo - h0:hi - h0])
                    vsparts.append(vs[:, lo - h0:hi - h0])
                covered += hi - lo
            if covered != p1 - p0:
                raise SwapWireError(
                    f"import_seq({seq_id!r}): shard set covers "
                    f"{covered} of the {p1 - p0} KV heads pool {li} "
                    f"owns ([{p0}, {p1}))")
            k_host = v_host = ks_host = vs_host = None
            if npriv:
                k_host = np.ascontiguousarray(
                    np.concatenate(kparts, axis=2))
                v_host = np.ascontiguousarray(
                    np.concatenate(vparts, axis=2))
                if quant:
                    ks_host = np.ascontiguousarray(
                        np.concatenate(ksparts, axis=1))
                    vs_host = np.ascontiguousarray(
                        np.concatenate(vsparts, axis=1))
            rec = _SwapRecord(
                pages=[int(p) for p in meta["pages"]],
                kept=[False] * len(meta["pages"]),
                length=int(meta["length"]), k_host=k_host,
                v_host=v_host, k_scales_host=ks_host,
                v_scales_host=vs_host, gens=None,
                nbytes=npriv * pool.page_nbytes,
                trace_ctx=meta.get("trace_ctx"))
            pend.append((key, rec))
            total += rec.nbytes
        if not self.would_fit(total):
            raise SwapSpaceFull(
                f"import_seq({seq_id!r}): shard set needs {total} "
                f"bytes, {self.free_bytes} of {self.capacity_bytes} "
                "free")
        for key, rec in pend:
            self._swap_put(key, rec)
        self.imported_records += len(pend)
        if self._reg is not None:
            self._reg.inc("pool.transfer_in_records", len(pend))
            self._reg.inc("pool.transfer_in_bytes",
                          sum(len(p) for p in payloads))
        return total

    # -- pool-only entry points (audited like pool-private methods) --------
    def _swap_put(self, key, rec):
        if key in self._swap_store:
            raise ValueError(
                f"swap space already holds a record for {key!r}")
        if self._swap_used + rec.nbytes > self.capacity_bytes:
            raise SwapSpaceFull(
                f"swap space full: record needs {rec.nbytes} bytes, "
                f"{self.free_bytes} of {self.capacity_bytes} free")
        if self._cv is not None:
            self._cv.write()
        self._swap_store[key] = rec
        self._swap_used += rec.nbytes
        self.swapped_out_records += 1
        if self._swap_used > self.peak_used_bytes:
            self.peak_used_bytes = self._swap_used

    def _swap_get(self, key):
        rec = self._swap_store.get(key)
        if rec is None:
            raise KeyError(f"no swap record for {key!r}")
        return rec

    def _swap_pop(self, key):
        """Remove and return a record (swap-in restore or a deadline-
        abort discard — the caller counts which)."""
        rec = self._swap_get(key)
        if self._cv is not None:
            self._cv.write()
        del self._swap_store[key]
        self._swap_used -= rec.nbytes
        return rec


class _SummaryKey(str):
    """The id a sequence's chain of summary rows is booked under
    (:meth:`PagedKVCacheManager._summary_key`)."""


def _few_counts(counts, few_row_rows):
    """A step's span attributes with the caller's ``few_row_rows``."""
    if few_row_rows is None:
        return counts
    return {**(counts or {}), "few_row_rows": few_row_rows}


class StepTables(tuple):
    """What a step's kernel reads beside the pages, on the device:
    ``(tbl, lens, q_lens)``, ``(tbl, lens, q_lens, pg, of)`` or, merged
    for the layer program, ``(rows, slots)``
    (:meth:`PagedKVCacheManager._step_tables`). Beside them the host's
    table and lens (``host_tbl`` / ``host_lens``, what a sanitizer
    checks) and, for a step shared between the pools of the layers,
    what it was ``booked`` from (:meth:`PagedKVCacheManager.book_step`)."""

    host_tbl = host_lens = booked = counts = None


class PagedKVCacheManager:
    """Fixed pool of KV pages shared by many sequences.

    * ``alloc(seq_id)`` registers a sequence;
    * ``attach(seq_id, pages, length)`` registers a sequence on a
      SHARED page chain (prefix-cache hit) — appends past ``length``
      copy-on-write the last page if it is shared;
    * ``append(seq_id)`` returns (physical_page, offset) for the next
      token, growing the sequence's page list from the free list;
    * ``page_table(seq_ids, max_pages)`` / ``seq_lens`` build the
      device-side inputs of the paged attention kernel;
    * ``free(seq_id)`` drops the sequence's references; pages return
      to the pool when their refcount hits zero.
    """

    _KV_DTYPES = {
        "int8": jnp.int8, "bf16": jnp.bfloat16,
        "bfloat16": jnp.bfloat16, "fp32": jnp.float32,
        "float32": jnp.float32, "fp16": jnp.float16,
        "float16": jnp.float16,
    }

    PAGE_FORMATS = ("kv", "latent", "eva")

    def __init__(self, num_pages, page_size, kv_heads, head_dim,
                 dtype=jnp.bfloat16, kv_dtype=None, sanitizer=None,
                 mp_size=1, mp_rank=0, page_format="kv",
                 window_tokens=None):
        # the serving path's jax.jit programs persist like to_static's
        from ...jit.api import ensure_compilation_cache

        ensure_compilation_cache()
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        if page_format not in self.PAGE_FORMATS:
            raise ValueError(
                f"page_format must be one of {self.PAGE_FORMATS}, got "
                f"{page_format!r}")
        self.page_format = page_format
        self.latent = page_format == "latent"
        self.eva = page_format == "eva"
        if self.eva != (window_tokens is not None):
            raise ValueError(
                "window_tokens is the aligned window of page_format="
                f"'eva' and of no other format (page_format="
                f"{page_format!r}, window_tokens={window_tokens!r})")
        if self.eva:
            # a summary row is ONE finished page pooled over its slots,
            # so the model's chunk is the page; whole pages of summary
            # rows become visible a window at a time
            self.window_tokens = int(window_tokens)
            self.window_pages = self.window_tokens // self.page_size
            if self.window_tokens % (self.page_size ** 2) \
                    or int(mp_size) != 1 or kv_dtype == "int8":
                raise ValueError(
                    "page_format='eva': the window holds whole pages of "
                    "summary rows (window_tokens a multiple of "
                    f"page_size^2 = {self.page_size ** 2}), float pages, "
                    f"mp_size=1; got window_tokens={window_tokens} "
                    f"kv_dtype={kv_dtype!r} mp_size={mp_size}")
        if self.latent and (int(kv_heads) != 1 or int(mp_size) != 1):
            raise ValueError(
                "page_format='latent': one latent row a token, shared "
                f"by every query head (kv_heads=1, mp_size=1), got "
                f"kv_heads={kv_heads} mp_size={mp_size}")
        # mp-mesh KV-head sharding (disaggregated serving / tensor
        # parallel): ``kv_heads`` is the GLOBAL head count; a sharded
        # pool stores only the contiguous slice its mp rank owns —
        # the layout the ragged kernel already indexes per head, and
        # what lets a page-chain wire shard land on exactly the pool
        # owning those heads (export_seq/import_seq)
        self.mp_size = int(mp_size)
        self.mp_rank = int(mp_rank)
        if self.mp_size < 1 or not 0 <= self.mp_rank < self.mp_size:
            raise ValueError(
                f"mp_rank {mp_rank} out of range for mp_size "
                f"{mp_size}")
        if int(kv_heads) % self.mp_size:
            raise ValueError(
                f"{kv_heads} KV heads do not shard across an mp "
                f"mesh of {mp_size}")
        self.kv_heads_global = int(kv_heads)
        kv_heads = self.kv_heads_global // self.mp_size
        self.head_start = self.mp_rank * kv_heads
        if kv_dtype is not None:
            if kv_dtype not in self._KV_DTYPES:
                raise ValueError(
                    f"kv_dtype must be one of "
                    f"{sorted(self._KV_DTYPES)}, got {kv_dtype!r}")
            dtype = self._KV_DTYPES[kv_dtype]
        self.kv_dtype = jnp.dtype(dtype).name
        self.quantized = self.kv_dtype == "int8"
        if self.latent:
            if self.quantized:
                raise ValueError(
                    "page_format='latent' has no int8 pages: the scale "
                    "sidecars are per K/V head")
            # ONE array: the row is key and (its head) value at once
            self.k_pages = jnp.zeros(
                (num_pages, page_size, head_dim), dtype)
            self.v_pages = None
        else:
            self.k_pages = jnp.zeros(
                (num_pages, page_size, kv_heads, head_dim), dtype
            )
            self.v_pages = jnp.zeros_like(self.k_pages)
        if self.quantized:
            # per-page, per-head scale sidecars (pool-private: mutate
            # ONLY through the append/COW paths below)
            self.k_scales = jnp.zeros((num_pages, kv_heads),
                                      jnp.float32)
            self.v_scales = jnp.zeros_like(self.k_scales)
        self._free = list(range(num_pages))[::-1]
        self._tables = {}   # seq_id -> [page ids]
        self._lens = {}     # seq_id -> token count
        # stable identity for swap-space keys (layer pools of one
        # model share ONE HostKVSwapSpace; records key on (uid, seq))
        self._uid = next(_pool_uids)
        self._refcnt = [0] * num_pages
        # references held by non-sequence owners (the prefix tree),
        # tracked separately so invariants are checkable without the
        # owner's cooperation
        self._ext_refs = collections.Counter()
        self.cow_forks = 0  # lifetime count of copy-on-write forks
        # high watermark: most pages ever simultaneously in use —
        # pool.peak_utilization in BatchScheduler.metrics(), and the
        # pool-pressure watchdog's capacity-planning evidence
        self.peak_used_pages = 0
        # lifecycle sanitizer (page_sanitizer.py): 'off' is zero-cost
        # by constructing NOTHING — every instrumented method below
        # guards on `self._san is not None` only
        mode = sanitizer if sanitizer is not None \
            else flag("page_sanitizer")
        if mode and mode != "off":
            from .page_sanitizer import PageSanitizer

            self._san = PageSanitizer(self.num_pages, self.page_size,
                                      mode=mode)
        else:
            self._san = None
        # runtime telemetry (framework/telemetry.py): lifetime pool
        # counters under the "pool." namespace; None when
        # FLAGS_telemetry=off — each event site pays one check
        self._reg = telemetry.registry()
        # per-sequence serialized TraceContext wires (the ops-plane
        # propagation contract, docs/OBSERVABILITY.md): stamped by
        # the scheduler at admission (set_trace_context), carried on
        # the swap records across the host tier, and handed over
        # with a COW chain attach — so one request's trace survives
        # preemption round trips and the future prefill/decode
        # worker split. Plain strings only; never device state
        self._trace_ctxs = {}

    def _kv_only(self, op):
        """Refuse, by name, an operation that moves K/V-shaped records
        on a pool that holds latent pages."""
        if self.latent:
            raise ValueError(
                f"{op}: not available for page_format='latent' (one "
                f"[num_pages, page_size, {self.k_pages.shape[-1]}] array, "
                "no V array); latent pools are written and read by "
                "latent_ragged_step")

    def _one_chain(self, op):
        """Refuse, by name, an operation that takes a sequence for ONE
        page chain as long as its tokens on a window-and-summary pool."""
        if self.eva:
            raise ValueError(
                f"{op}: not available for page_format='eva' (a sequence "
                "holds a window chain, released at every window's end, "
                "and a chain of summary rows; both are written by "
                "layer_step alone)")

    def _plain_kv(self, op):
        """Refuse what moves K/V records of a chain as long as its
        sequence: a latent pool has no such record, a window-and-summary
        sequence no such chain."""
        self._kv_only(op)
        self._one_chain(op)

    # -- window-and-summary geometry (page_format="eva") -------------------
    @staticmethod
    def _summary_key(seq_id):
        """The id the summary chain of ``seq_id`` is booked under: a
        chain of its own in ``_tables`` / ``_lens`` (length = summary
        rows) and in the sanitizer's shadow heap, so that refcounts,
        invariants and cross-checks cover both chains with one code."""
        return _SummaryKey(f"{seq_id!r}/summary")

    def pages_for(self, n) -> int:
        """Pages a sequence of ``n`` tokens holds AT MOST over its life
        (what admission reserves): one a ``page_size`` tokens; for
        ``"eva"`` the current window's pages, at most one window's,
        beside one summary page a ``page_size ** 2`` tokens."""
        n = max(int(n), 0)
        pages = -(-n // self.page_size)
        if not self.eva:
            return pages
        return min(self.window_pages, pages) + -(-n // self.page_size ** 2)

    def _window_of(self, n):
        """(w, t, visible summary pages) of an ``"eva"`` sequence of
        ``n`` >= 1 tokens: its newest token is the t-th (1..window) of
        window w, and sees the summary pages of windows 0..w-1."""
        w = (n - 1) // self.window_tokens
        return (w, n - w * self.window_tokens,
                w * (self.window_pages // self.page_size))

    def pages_held(self, n) -> int:
        """Pages a sequence holds WHEN it is ``n`` tokens long."""
        n = max(int(n), 0)
        if not self.eva or not n:
            return -(-n // self.page_size)
        return (-(-self._window_of(n)[1] // self.page_size)
                + -(-(n // self.page_size) // self.page_size))

    def table_pages(self, n) -> int:
        """Width of the page-table row of a sequence of ``n`` tokens:
        for ``"eva"`` the visible summary pages (every chunk of every
        earlier window) and then the window's."""
        n = max(int(n), 0)
        if not self.eva or not n:
            return -(-n // self.page_size)
        _, t, vis = self._window_of(n)
        return vis + -(-t // self.page_size)

    def chunk_room(self, seq_id) -> int:
        """Tokens one step may append to the sequence: for ``"eva"`` up
        to the end of the window its next token falls in (a step's rows
        never straddle a window boundary), else no bound (None)."""
        if not self.eva:
            return None
        return self.window_tokens - self._lens[seq_id] % self.window_tokens

    # -- bookkeeping -------------------------------------------------------
    def alloc(self, seq_id):
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id!r} already allocated")
        for key in self._chain_keys(seq_id):
            if self._san is not None:
                self._san.event("alloc", seq=key)
            self._tables[key] = []
            self._lens[key] = 0

    def _chain_keys(self, seq_id):
        return (seq_id, self._summary_key(seq_id)) if self.eva \
            else (seq_id,)

    def attach(self, seq_id, pages, length, trace_ctx=None):
        """Register ``seq_id`` on an existing page chain covering its
        first ``length`` tokens (a prefix-cache hit, or a page-chain
        handoff from another worker). Every chain page gains a
        reference; the content is shared until this sequence writes
        into the (partial) last page, which forks it. ``trace_ctx``
        (a serialized TraceContext wire string) rides along so the
        chain's trace identity transfers with its ownership."""
        self._one_chain("attach")
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id!r} already allocated")
        need = -(-int(length) // self.page_size) if length else 0
        if len(pages) != need:
            raise ValueError(
                f"attach({seq_id!r}): {length} tokens span {need} "
                f"pages, got a chain of {len(pages)}")
        if self._san is not None:
            # strict mode raises here (with the journal) on a dangling
            # chain, before the pool's own ValueError below
            self._san.event("attach", seq=seq_id,
                            pages=[int(p) for p in pages],
                            length=int(length))
        for p in pages:
            if self._refcnt[p] == 0:
                raise ValueError(
                    f"attach({seq_id!r}): page {p} is on the free "
                    "list (dangling chain)" + self._san_tail())
        self._ref_pages(pages)
        self._tables[seq_id] = list(pages)
        self._lens[seq_id] = int(length)
        if trace_ctx is not None:
            self._trace_ctxs[seq_id] = str(trace_ctx)
        if self._san is not None:
            self._san.verify_pages(pages, self)

    # -- trace-context propagation (framework/telemetry.py) ----------------
    def set_trace_context(self, seq_id, wire) -> None:
        """Pin a SERIALIZED TraceContext (``TraceContext.to_wire()``)
        to a live sequence: it rides the sequence's swap records
        through the host tier and is what a receiving worker
        extracts after a page-chain handoff. Host-only metadata —
        never touches device state."""
        if seq_id not in self._tables:
            raise KeyError(
                f"set_trace_context({seq_id!r}): unknown sequence")
        self._trace_ctxs[seq_id] = str(wire)

    def seq_trace_context(self, seq_id):
        """The sequence's serialized TraceContext wire (None when
        never stamped)."""
        return self._trace_ctxs.get(seq_id)

    def _ref_pages(self, pages):
        """Take one reference per chain page (attach)."""
        for p in pages:
            self._refcnt[p] += 1

    def free(self, seq_id):
        for key in self._chain_keys(seq_id):
            self._free_chain(key)
        self._trace_ctxs.pop(seq_id, None)

    def _free_chain(self, seq_id):
        tbl = self._tables.get(seq_id)
        if self._san is not None:
            # emitted BEFORE the lookup raise: a double-free lands in
            # the journal, strict mode raises with the event tail
            self._san.event(
                "free", seq=seq_id,
                pages=None if tbl is None else [int(p) for p in tbl])
        if tbl is None:
            raise KeyError(
                f"free({seq_id!r}): unknown or already-freed sequence "
                "(double-free would corrupt the page free list)"
                + self._san_tail())
        del self._tables[seq_id]
        self._drop_refs(tbl)
        self._lens.pop(seq_id)
        if self._san is not None:
            self._san.verify_pages(tbl, self)

    def _drop_refs(self, pages):
        """Release a retiring sequence's references (free)."""
        for p in reversed(pages):
            self._release_page(p)

    def _san_tail(self) -> str:
        return ("\n" + self._san.format_tail()
                if self._san is not None else "")

    # -- reference counting ------------------------------------------------
    def incref(self, pages):
        """Add an external (non-sequence) reference to each page —
        used by the prefix tree to keep a retired sequence's prefix
        alive past ``free``."""
        pages = list(pages)
        if self._san is not None:
            self._san.event("incref", pages=[int(p) for p in pages])
        for p in pages:
            if self._refcnt[p] == 0:
                raise ValueError(
                    f"incref: page {p} is free (cannot resurrect)"
                    + self._san_tail())
            self._refcnt[p] += 1
            self._ext_refs[p] += 1
        if self._san is not None:
            self._san.verify_pages(pages, self)

    def decref(self, pages):
        """Drop external references; returns how many pages that
        released back to the pool."""
        pages = list(pages)
        if self._san is not None:
            self._san.event("decref", pages=[int(p) for p in pages])
        freed = 0
        for p in pages:
            if self._ext_refs[p] <= 0:
                raise ValueError(
                    f"decref: page {p} holds no external reference"
                    + self._san_tail())
            self._ext_refs[p] -= 1
            if self._ext_refs[p] == 0:
                del self._ext_refs[p]
            freed += self._release_page(p)
        if self._san is not None:
            self._san.verify_pages(pages, self)
        return freed

    def _release_page(self, p):
        c = self._refcnt[p] - 1
        if c < 0:
            raise AssertionError(f"page {p} refcount underflow")
        self._refcnt[p] = c
        if c == 0:
            self._free.append(p)
            if self._reg is not None:
                self._reg.inc("pool.page_frees")
            return 1
        return 0

    def _alloc_page(self):
        if not self._free:
            raise RuntimeError("KV page pool exhausted")
        p = self._free.pop()
        self._refcnt[p] = 1
        used = self.num_pages - len(self._free)
        if used > self.peak_used_pages:
            self.peak_used_pages = used
        if self._reg is not None:
            self._reg.inc("pool.page_allocs")
        if self.quantized:
            # a fresh page is all-zero: its scale must restart at 0 or
            # the first append would inherit a dead page's calibration
            self.k_scales = self.k_scales.at[p].set(0.0)
            self.v_scales = self.v_scales.at[p].set(0.0)
        return p

    def _fork_page(self, src):
        """Copy-on-write: give the writer a private copy of ``src``
        (which stays intact for its other owners)."""
        dst = self._alloc_page()
        self._copy_page(dst, src)
        self._refcnt[src] -= 1  # src was shared: cannot hit zero here
        self.cow_forks += 1
        if self._reg is not None:
            self._reg.inc("pool.cow_forks")
        return dst

    def _copy_page(self, dst, src):
        self.k_pages = self.k_pages.at[dst].set(self.k_pages[src])
        if self.v_pages is not None:
            self.v_pages = self.v_pages.at[dst].set(self.v_pages[src])
        if self.quantized:
            # the fork COPIES the scale row (the source chain keeps
            # its own); from here the two pages recalibrate
            # independently
            self.k_scales = self.k_scales.at[dst].set(
                self.k_scales[src])
            self.v_scales = self.v_scales.at[dst].set(
                self.v_scales[src])

    def seq_len(self, seq_id):
        return self._lens[seq_id]

    def seq_pages(self, seq_id):
        """The sequence's physical page chain (copy)."""
        return list(self._tables[seq_id])

    def seq_summary_pages(self, seq_id):
        """The sequence's chain of summary pages (copy; ``"eva"``)."""
        return list(self._tables[self._summary_key(seq_id)])

    def seq_page_count(self, seq_id) -> int:
        """Pages the sequence holds (both chains of an ``"eva"``
        sequence), without materializing the chain (victim scoring
        reads this for every active sequence on every pick —
        ``len(seq_pages())`` would copy the table each time)."""
        return sum(len(self._tables[k]) for k in self._chain_keys(seq_id))

    def pending_cow(self, seq_id) -> bool:
        """True if the sequence's next append must fork a shared page
        (admission accounting: that fork draws one page from the
        pool)."""
        tbl = self._tables[seq_id]
        return (bool(tbl) and self._lens[seq_id] % self.page_size != 0
                and self._refcnt[tbl[-1]] > 1)

    def truncate(self, seq_id, n):
        """Roll a sequence back to ``n`` tokens (speculative-decoding
        rejection: stale K/V beyond ``n`` is never attended — the
        kernels mask by seq_len — and pages past ceil(n/P) drop this
        sequence's reference)."""
        cur = self._lens[seq_id]
        if n > cur:
            raise ValueError(
                f"truncate({seq_id!r}, {n}): sequence has only {cur}")
        start = 0
        if self.eva and n < cur:
            start = self._window_of(cur)[0] * self.window_tokens
            if n <= start:
                raise ValueError(
                    f"truncate({seq_id!r}, {n}): not available across a "
                    f"roll for page_format='eva' (the window that held "
                    f"token {n} was released at {start}; only its "
                    "summary rows are left)")
            # the summary rows of the chunks that are whole no longer
            rows = n // self.page_size
            self._truncate_chain(self._summary_key(seq_id), rows,
                                 -(-rows // self.page_size))
        self._truncate_chain(
            seq_id, n, -(-(n - start) // self.page_size) if n else 0)

    def _truncate_chain(self, seq_id, n, keep):
        tbl = self._tables[seq_id]
        dropped = tbl[keep:]
        if self._san is not None:
            self._san.event("truncate", seq=seq_id, n=int(n), keep=keep,
                            dropped=[int(p) for p in dropped])
        while len(tbl) > keep:
            self._release_page(tbl.pop())
        self._lens[seq_id] = n
        if self._san is not None and dropped:
            self._san.verify_pages(dropped, self)

    # -- tiered host swap (preemption; HostKVSwapSpace) --------------------
    def swap_out_pages(self, seq_id) -> int:
        """Device pages a ``swap_out`` of this sequence would FREE
        (its PRIVATE pages only — shared pages stay on-device under a
        hold). Read-only: the scheduler sums this over candidate
        victims to decide whether preemption can close an admission
        deficit at all before swapping anyone out."""
        tbl = self._tables.get(seq_id)
        if tbl is None:
            raise KeyError(f"swap_out_pages({seq_id!r}): unknown "
                           "sequence")
        return sum(1 for p in tbl if self._refcnt[p] == 1)

    def swap_out_nbytes(self, seq_id) -> int:
        """Host bytes a ``swap_out`` of this sequence would store
        (its PRIVATE pages only). Read-only: the scheduler
        budget-checks the swap space with this BEFORE picking a
        victim."""
        return self.swap_out_pages(seq_id) * self.page_nbytes

    def swap_out(self, seq_id, space):
        """Page the sequence out to the host tier: private pages
        (refcount 1) are copied to host buffers BITWISE (payload +
        int8 scale rows) and released back to the pool; shared pages
        (prefix-cache chains, still-shared COW tails) stay on-device
        under an external "swap hold" reference so they can neither
        be freed nor recycled while the sequence is out. Atomic: the
        host copy and the swap-space reservation both happen before
        any bookkeeping mutation, so a full space
        (:class:`SwapSpaceFull`) aborts with the pool untouched.
        Returns ``(pages_freed, nbytes_swapped)``."""
        self._plain_kv("swap_out")
        tbl = self._tables.get(seq_id)
        if tbl is None:
            raise KeyError(f"swap_out({seq_id!r}): unknown sequence")
        length = self._lens[seq_id]
        kept = [self._refcnt[p] > 1 for p in tbl]
        priv = [p for p, k in zip(tbl, kept) if not k]
        shared = [p for p, k in zip(tbl, kept) if k]
        k_host = v_host = ks_host = vs_host = None
        if priv:
            pg = jnp.asarray(priv, jnp.int32)
            k_host = np.asarray(self.k_pages[pg])
            v_host = np.asarray(self.v_pages[pg])
            if self.quantized:
                ks_host = np.asarray(self.k_scales[pg])
                vs_host = np.asarray(self.v_scales[pg])
        gens = (self._san.page_gens(shared)
                if self._san is not None else None)
        rec = _SwapRecord(
            pages=list(tbl), kept=kept, length=length, k_host=k_host,
            v_host=v_host, k_scales_host=ks_host,
            v_scales_host=vs_host, gens=gens,
            nbytes=len(priv) * self.page_nbytes,
            trace_ctx=self._trace_ctxs.get(seq_id))
        space._swap_put((self._uid, seq_id), rec)
        self._trace_ctxs.pop(seq_id, None)
        if self._san is not None:
            self._san.event("swap_out", seq=seq_id,
                            pages=[int(p) for p in tbl],
                            kept=[bool(k) for k in kept],
                            length=int(length))
        # the swap hold: each shared page gains an external reference
        # BEFORE the sequence's own references drop, so its refcount
        # never transits zero
        for p in shared:
            self._refcnt[p] += 1
            self._ext_refs[p] += 1
        del self._tables[seq_id]
        self._lens.pop(seq_id)
        freed = 0
        for p in reversed(tbl):
            freed += self._release_page(p)
        if self._san is not None and tbl:
            self._san.verify_pages(tbl, self)
        if self._reg is not None:
            self._reg.inc("pool.swap_out_pages", freed)
        return freed, rec.nbytes

    def swap_in_pages_needed(self, seq_id, space,
                             worst_tokens=None) -> int:
        """Free-list draws a ``swap_in`` (plus, when ``worst_tokens``
        is given, growing to that worst-case length afterwards) would
        make: one per private page to restore, the remaining growth
        pages past the restored length, and the pending COW fork when
        the restored tail page is shared and mid-page — the admission
        reservation a re-admit must hold."""
        rec = space._swap_get((self._uid, seq_id))
        need = sum(1 for k in rec.kept if not k)
        have = -(-rec.length // self.page_size) if rec.length else 0
        if worst_tokens is not None:
            need += max(
                -(-int(worst_tokens) // self.page_size) - have, 0)
        if rec.kept and rec.kept[-1] and rec.length % self.page_size:
            need += 1
        return need

    def swap_in(self, seq_id, space):
        """Restore a swapped-out sequence: draw fresh pages for the
        private positions and write their host bytes back BITWISE,
        re-take the sequence references on the kept (shared) pages
        and drop their swap holds. The restored chain is
        byte-identical to the swapped-out one (the page IDS of
        private positions change; contents and order do not).
        Atomic: capacity is validated before any mutation. Returns
        the number of pages restored from host."""
        self._plain_kv("swap_in")
        if seq_id in self._tables:
            raise ValueError(
                f"swap_in({seq_id!r}): sequence already allocated")
        key = (self._uid, seq_id)
        rec = space._swap_get(key)
        priv_n = sum(1 for k in rec.kept if not k)
        if priv_n > len(self._free):
            raise RuntimeError(
                f"KV page pool exhausted: swap_in needs {priv_n} "
                f"pages, {len(self._free)} free")
        chain = []
        new_priv = []
        for p, k in zip(rec.pages, rec.kept):
            if k:
                chain.append(p)
            else:
                q = self._alloc_page()
                chain.append(q)
                new_priv.append(q)
        if new_priv:
            pg = jnp.asarray(new_priv, jnp.int32)
            self.k_pages = self.k_pages.at[pg].set(
                jnp.asarray(rec.k_host, self.k_pages.dtype))
            self.v_pages = self.v_pages.at[pg].set(
                jnp.asarray(rec.v_host, self.v_pages.dtype))
            if self.quantized:
                self.k_scales = self.k_scales.at[pg].set(
                    jnp.asarray(rec.k_scales_host, jnp.float32))
                self.v_scales = self.v_scales.at[pg].set(
                    jnp.asarray(rec.v_scales_host, jnp.float32))
        for p, k in zip(rec.pages, rec.kept):
            if k:
                # the sequence reference replaces the swap hold: net
                # refcount unchanged, ownership moves back
                self._ext_refs[p] -= 1
                if self._ext_refs[p] == 0:
                    del self._ext_refs[p]
        self._tables[seq_id] = chain
        self._lens[seq_id] = rec.length
        if self._san is not None:
            self._san.event(
                "swap_in", seq=seq_id,
                pages=[int(p) for p in chain],
                kept=[bool(k) for k in rec.kept],
                length=int(rec.length),
                gens=None if rec.gens is None
                else [int(g) for g in rec.gens],
                pool=self)
        space._swap_pop(key)
        space.swapped_in_records += 1
        if rec.trace_ctx is not None:
            # the restored sequence resumes its own trace
            self._trace_ctxs[seq_id] = rec.trace_ctx
        if self._reg is not None:
            self._reg.inc("pool.swap_in_pages", len(new_priv))
        return len(new_priv)

    def swap_discard(self, seq_id, space):
        """Drop a swap record without restoring it (deadline abort of
        a swapped-out request): releases the swap holds on the kept
        pages through the instrumented ``decref`` path and frees the
        host bytes. Returns the pages released back to the pool."""
        rec = space._swap_pop((self._uid, seq_id))
        shared = [p for p, k in zip(rec.pages, rec.kept) if k]
        freed = self.decref(shared) if shared else 0
        return freed

    @property
    def kv_heads_local(self) -> int:
        """KV heads THIS shard stores (== global / mp_size)."""
        return 1 if self.latent else self.k_pages.shape[2]

    @property
    def num_free_pages(self) -> int:
        return len(self._free)

    @property
    def num_shared_pages(self) -> int:
        """Pages currently owned by more than one reference."""
        return sum(1 for c in self._refcnt if c > 1)

    def assert_ref_invariants(self):
        """Crash loudly if the refcount state is inconsistent:
        per-page refcount == occurrences across sequence tables plus
        external references, and the free list is exactly the
        refcount-zero set (no duplicates)."""
        expect = collections.Counter()
        for tbl in self._tables.values():
            expect.update(tbl)
        expect.update(self._ext_refs)
        for p in range(self.num_pages):
            if self._refcnt[p] != expect.get(p, 0):
                raise AssertionError(
                    f"page {p}: refcount {self._refcnt[p]} != "
                    f"{expect.get(p, 0)} tracked references")
        free_set = set(self._free)
        if len(free_set) != len(self._free):
            raise AssertionError("duplicate pages on the free list")
        zero = {p for p in range(self.num_pages)
                if self._refcnt[p] == 0}
        if free_set != zero:
            raise AssertionError(
                f"free list {sorted(free_set)} != refcount-zero set "
                f"{sorted(zero)}")
        return True

    # -- lifecycle sanitizer surface (page_sanitizer.py) -------------------
    @property
    def sanitizer(self):
        """The pool's PageSanitizer, or None when off."""
        return self._san

    @property
    def sanitizer_stats(self):
        """Event/violation counters, or None when off."""
        return None if self._san is None else self._san.stats()

    def sanitizer_page_gens(self, pages):
        """Current shadow generation of each listed page (None when
        the sanitizer is off). Capture these next to a held chain —
        a later :meth:`sanitizer_check_chain` proves no page was
        recycled underneath the holder."""
        return (None if self._san is None
                else self._san.page_gens(pages))

    def sanitizer_check_chain(self, pages, gens, what="chain"):
        """Validate a generation-tagged chain captured earlier (the
        radix prefix tree checks its node chains on every match)."""
        if self._san is not None and gens is not None:
            self._san.check_chain(pages, gens, what=what)

    def sanitizer_note(self, op, **fields):
        """Journal a context-only event (prefix-cache pin / unpin /
        evict / insert) — diagnosis breadcrumbs, no shadow
        semantics."""
        if self._san is not None:
            self._san.note(op, **fields)

    def sanitizer_crosscheck(self):
        """Epoch cross-check: compare the shadow heap against the real
        pool (refcounts, free list, lens, ``num_free_pages``) and, in
        strict mode, run :meth:`assert_ref_invariants` too — the
        BatchScheduler calls this every FLAGS_page_sanitizer_stride
        steps. Returns the sanitizer stats dict, or None when off."""
        if self._san is None:
            return None
        self._san.crosscheck(self)
        if self._san.mode == "strict":
            try:
                self.assert_ref_invariants()
            except AssertionError as e:
                raise AssertionError(
                    str(e) + "\n" + self._san.format_tail()) from None
        return self._san.stats()

    def _san_check_table(self, seq_ids, tbl, lens):
        tbl, lens = np.asarray(tbl), np.asarray(lens)
        if not self.eva:
            self._san.check_table(seq_ids, tbl, lens)
            return
        # a row is [visible summary pages ; window pages] and its length
        # counts summary rows and window tokens: each part against its
        # own shadow chain (the window a sequence is in, and the summary
        # pages that are not visible yet, from the pool's books)
        wins = [self._window_of(max(self._lens[s], 1)) for s in seq_ids]
        self._san.check_table(
            seq_ids, [tbl[i, vis:] for i, (_, _, vis) in enumerate(wins)],
            [int(lens[i]) + w * (self.window_tokens - self.window_pages)
             for i, (w, _, _) in enumerate(wins)])
        keys = [self._summary_key(s) for s in seq_ids]
        self._san.check_table(
            keys, [list(tbl[i, :vis]) + self._tables[k][vis:]
                   for i, ((_, _, vis), k) in enumerate(zip(wins, keys))],
            [self._lens[k] for k in keys])

    def _needs_fork(self, page) -> bool:
        """A mid-page write must fork when the page is shared."""
        return self._refcnt[page] > 1

    def _next_slot(self, seq_id):
        n = self._lens[seq_id]
        off = n % self.page_size
        tbl = self._tables[seq_id]
        if off == 0:
            tbl.append(self._alloc_page())
        elif self._needs_fork(tbl[-1]):
            # divergent write into a shared page: fork first
            src = tbl[-1]
            tbl[-1] = self._fork_page(src)
            if self._san is not None:
                self._san.event("fork", seq=seq_id, src=int(src),
                                dst=int(tbl[-1]), pool=self)
        return tbl[-1], off

    # -- quantized writes --------------------------------------------------
    def _quant_write(self, pages, offs, k_toks, v_toks):
        """Quantized token write: grow each written page's per-head
        scale to cover the new token (requantizing the already-stored
        slots by round(q * old/new) — exact when the scale is
        unchanged), then store the tokens as int8. ``pages`` holds
        DISTINCT physical ids (each page has exactly one writer — a
        shared page is forked before any write reaches here, and
        append_ragged's wave replay feeds at most one token per
        sequence per call).

        Steady state (scales already cover the token — the common
        decode case once a page has seen a few tokens) writes ONLY the
        token's slot; the full-page requantize gather/scatter runs
        only when a scale actually grows. The host-side branch costs
        one device read per append batch — this pool is host-driven
        bookkeeping by design (see module docstring)."""
        pg = jnp.asarray(pages, jnp.int32)
        of = jnp.asarray(offs, jnp.int32)
        rows = jnp.arange(pg.shape[0])
        for name_p, name_s, toks in (
            ("k_pages", "k_scales", k_toks),
            ("v_pages", "v_scales", v_toks),
        ):
            all_pages = getattr(self, name_p)
            all_scales = getattr(self, name_s)
            tok_s = kv_head_scale(toks, keep_leading=1)   # (B, KVH)
            old_s = all_scales[pg]
            new_s = jnp.maximum(old_s, tok_s)
            if bool(jnp.any(new_s > old_s)):
                ratio = jnp.where(
                    new_s > 0, old_s / jnp.maximum(new_s, 1e-20), 1.0)
                body = jnp.round(
                    all_pages[pg].astype(jnp.float32)
                    * ratio[:, None, :, None]).astype(jnp.int8)
                body = body.at[rows, of].set(quantize_kv(toks, new_s))
                setattr(self, name_p, all_pages.at[pg].set(body))
                setattr(self, name_s, all_scales.at[pg].set(new_s))
            else:
                setattr(self, name_p, all_pages.at[pg, of].set(
                    quantize_kv(toks, old_s)))

    # -- device writes -----------------------------------------------------
    def append(self, seq_id, k_tok, v_tok):
        """Write one token's K/V ((KVH, D) arrays or Tensors) into the
        sequence's next slot."""
        self._plain_kv("append")
        page, off = self._next_slot(seq_id)
        k_tok = k_tok._data if isinstance(k_tok, Tensor) else k_tok
        v_tok = v_tok._data if isinstance(v_tok, Tensor) else v_tok
        if self.quantized:
            self._quant_write([page], [off], k_tok[None], v_tok[None])
        else:
            self.k_pages = jax.lax.dynamic_update_slice(
                self.k_pages,
                k_tok[None, None].astype(self.k_pages.dtype),
                (page, off, 0, 0),
            )
            self.v_pages = jax.lax.dynamic_update_slice(
                self.v_pages,
                v_tok[None, None].astype(self.v_pages.dtype),
                (page, off, 0, 0),
            )
        self._lens[seq_id] += 1
        if self._san is not None:
            self._san.event("append", seq_ids=[seq_id], counts=[1],
                            pages=[int(page)], offs=[int(off)],
                            pool=self)
        return page, off

    def append_batch(self, seq_ids, k_toks, v_toks):
        """Write one token's K/V for EVERY listed sequence in one
        scatter per pages array (the hot serving path: B sequences x
        L layers must not issue B*L separate updates). k_toks/v_toks:
        (B, KVH, D) arrays or Tensors."""
        self._plain_kv("append_batch")
        k_toks = k_toks._data if isinstance(k_toks, Tensor) else k_toks
        v_toks = v_toks._data if isinstance(v_toks, Tensor) else v_toks
        # atomicity: validate capacity BEFORE any bookkeeping mutation,
        # so exhaustion cannot leave some sequences' lens ahead of
        # their actual device writes. A mid-page write into a shared
        # page forks it — that draws a page just like opening a new one
        new_pages_needed = sum(
            1 for s in seq_ids
            if self._lens[s] % self.page_size == 0
            or self.pending_cow(s)
        )
        if new_pages_needed > len(self._free):
            raise RuntimeError(
                f"KV page pool exhausted: batch needs "
                f"{new_pages_needed} new pages, {len(self._free)} free"
            )
        pages = []
        offs = []
        for s in seq_ids:
            page, off = self._next_slot(s)
            self._lens[s] += 1
            pages.append(page)
            offs.append(off)
        if self.quantized:
            self._quant_write(pages, offs, k_toks, v_toks)
        else:
            pg = jnp.asarray(pages, jnp.int32)
            of = jnp.asarray(offs, jnp.int32)
            self.k_pages = self.k_pages.at[pg, of].set(
                k_toks.astype(self.k_pages.dtype))
            self.v_pages = self.v_pages.at[pg, of].set(
                v_toks.astype(self.v_pages.dtype))
        if self._san is not None:
            self._san.event("append_batch", seq_ids=list(seq_ids),
                            counts=[1] * len(pages),
                            pages=[int(p) for p in pages],
                            offs=[int(o) for o in offs], pool=self)

    def ragged_pages_needed(self, seq_ids, counts) -> int:
        """Free-list draws a ragged append of ``counts[i]`` tokens per
        sequence would make: new pages opened past each sequence's
        current tail, plus one draw per sequence whose first write
        lands mid-page on a SHARED page (the copy-on-write fork) —
        the page-granular reservation a chunk boundary must respect."""
        if self.eva:
            return sum(sum(self._eva_growth(s, int(c))[:2])
                       for s, c in zip(seq_ids, counts))
        need = 0
        for s, c in zip(seq_ids, counts):
            if not c:
                continue
            n = self._lens[s]
            have = -(-n // self.page_size) if n else 0
            need += -(-(n + c) // self.page_size) - have
            if self.pending_cow(s):
                need += 1
        return need

    def _eva_growth(self, seq_id, c):
        """(window pages drawn, summary pages drawn, window pages
        released first) by appending ``c`` tokens to a window-and-summary
        sequence: arithmetic alone. A sequence whose length is a
        multiple of the window rolls: its whole window chain goes back
        to the pool before the first new page is drawn."""
        n, P, W = self._lens[seq_id], self.page_size, self.window_tokens
        if not c:
            return 0, 0, 0
        start = n // W * W
        if n + c > start + W:
            raise ValueError(
                f"page_format='eva': {c} tokens appended to {seq_id!r} at "
                f"{n} would straddle the window boundary at {start + W} "
                "(a step's rows end at a window's end: chunk_room)")
        rolled = self.window_pages if n and n == start else 0
        rows0, rows1 = n // P, (n + c) // P
        return (-(-(n + c - start) // P) - -(-(n - start) // P),
                -(-rows1 // P) - -(-rows0 // P), rolled)

    def _eva_slots(self, seq_ids, counts):
        """:meth:`_ragged_slots` of a window-and-summary pool: the same
        contract (atomic capacity precheck, then slot assignment and
        length advance, sanitizer events) over two chains. Rolls come
        first (span ``pool.roll``): a row at a window's end releases its
        window chain. Then every token gets its window slot, and every
        token that FILLS its page books the next row of the summary
        chain: the layer program pools that page into it in this step.
        Returns (pages, offs, (src, dst_page, dst_off)): the K/V write
        plan and the summary plan, row-major."""
        with telemetry.span("pool.book") as sp:
            grow = [self._eva_growth(s, c) for s, c in zip(seq_ids, counts)]
            need = sum(g[0] + g[1] for g in grow)
            released = sum(g[2] for g in grow)
            if need > len(self._free) + released:
                raise RuntimeError(
                    f"KV page pool exhausted: ragged append needs "
                    f"{need} new pages, {len(self._free)} free and "
                    f"{released} released by window roll-over")
            if released:
                rolled = [s for s, g in zip(seq_ids, grow) if g[2]]
                with telemetry.span("pool.roll", rows=len(rolled),
                                    pages=released):
                    for s in rolled:
                        self._roll(s)
            pages, offs, src, dpg, dof, filled = [], [], [], [], [], []
            last = self.page_size - 1
            for s, c in zip(seq_ids, counts):
                key = self._summary_key(s)
                filled.append(-len(src))
                for _ in range(c):
                    page, off = self._next_slot(s)
                    self._lens[s] += 1
                    pages.append(page)
                    offs.append(off)
                    if off == last:
                        spg, sof = self._next_slot(key)
                        self._lens[key] += 1
                        src.append(page)
                        dpg.append(spg)
                        dof.append(sof)
                filled[-1] += len(src)
            if self._reg is not None:
                self._reg.inc("eva.summaries_written", len(src))
            if pages and self._san is not None:
                self._san.event("append_ragged", seq_ids=list(seq_ids),
                                counts=list(counts),
                                pages=[int(p) for p in pages],
                                offs=[int(o) for o in offs], pool=self)
                if src:
                    self._san.event(
                        "append_ragged",
                        seq_ids=[self._summary_key(s) for s in seq_ids],
                        counts=filled,
                        pages=[int(p) for p in dpg],
                        offs=[int(o) for o in dof], pool=self)
            if sp is not None:
                sp.attrs.update(slots=len(pages), pages=need,
                                summary_slots=len(src))
            return pages, offs, (src, dpg, dof)

    def _roll(self, seq_id):
        """Release the window chain of a sequence at a window's end (its
        last page was pooled in the step that filled it)."""
        tbl = self._tables[seq_id]
        if len(tbl) != self.window_pages:
            raise AssertionError(
                f"roll({seq_id!r}): the window chain holds {len(tbl)} "
                f"pages at length {self._lens[seq_id]}, a full window is "
                f"{self.window_pages}")
        pages = list(tbl)
        if self._san is not None:
            self._san.event("roll", seq=seq_id,
                            pages=[int(p) for p in pages])
        tbl.clear()
        self._drop_refs(pages)
        if self._reg is not None:
            self._reg.inc("eva.windows_rolled")
        if self._san is not None:
            self._san.verify_pages(pages, self)

    @property
    def pages_window(self) -> int:
        """Pages in window chains right now (``"eva"``)."""
        return sum(len(t) for k, t in self._tables.items()
                   if not isinstance(k, _SummaryKey))

    @property
    def pages_summary(self) -> int:
        """Pages in summary chains right now (``"eva"``)."""
        return sum(len(t) for k, t in self._tables.items()
                   if isinstance(k, _SummaryKey))

    def _ragged_slots(self, seq_ids, counts):
        """Bookkeeping half of a ragged append: atomic capacity
        precheck (nothing mutates on failure — the validation runs
        BEFORE any bookkeeping, same contract as append_batch), slot
        assignment (COW forks included), length advance, and the
        sanitizer event. Returns the (pages, offs) write plan; the
        device scatter belongs to the caller — :meth:`append_ragged`,
        or the fused program that owns it as its prologue
        (:meth:`layer_step`, :meth:`latent_ragged_step`)."""
        with telemetry.span("pool.book") as sp:
            need = self.ragged_pages_needed(seq_ids, counts)
            if need > len(self._free):
                raise RuntimeError(
                    f"KV page pool exhausted: ragged append needs "
                    f"{need} new pages, {len(self._free)} free")
            pages = []
            offs = []
            for s, c in zip(seq_ids, counts):
                for _ in range(c):
                    page, off = self._next_slot(s)
                    self._lens[s] += 1
                    pages.append(page)
                    offs.append(off)
            if pages and self._san is not None:
                self._san.event("append_ragged", seq_ids=list(seq_ids),
                                counts=list(counts),
                                pages=[int(p) for p in pages],
                                offs=[int(o) for o in offs], pool=self)
            if sp is not None:
                sp.attrs.update(slots=len(pages), pages=need)
            return pages, offs

    def append_ragged(self, seq_ids, counts, k_toks, v_toks):
        """Write ``counts[i]`` consecutive tokens' K/V for EVERY listed
        sequence in one scatter per pages array (the chunked-prefill
        hot path: a mixed batch of multi-token chunks and single-token
        decode rows must not issue one update per token per layer).
        k_toks/v_toks: (sum(counts), KVH, D) arrays or Tensors, rows
        ordered sequence-major (seq_ids[0]'s tokens first)."""
        self._plain_kv("append_ragged")
        with telemetry.span("pool.fused_step", op="append_ragged"):
            k_toks = k_toks._data if isinstance(k_toks, Tensor) else k_toks
            v_toks = v_toks._data if isinstance(v_toks, Tensor) else v_toks
            counts = [int(c) for c in counts]
            if sum(counts) != k_toks.shape[0]:
                raise ValueError(
                    f"append_ragged: counts sum to {sum(counts)} but "
                    f"{k_toks.shape[0]} token rows were passed")
            pages, offs = self._ragged_slots(seq_ids, counts)
            if not pages:
                return
            if self.quantized:
                # replay the per-token calibration ORDER (wave j = the
                # j-th token of every chunk): scale growth requantizes
                # through the same intermediate scales the token-per-step
                # path would use, so chunked-prefill int8 pages are
                # BIT-identical to sequential appends (greedy identity —
                # tests/test_chunked_prefill.py). Same per-token write
                # cost as the legacy path; the chunking win is in the
                # attention/projection dispatch, not the pool write.
                offsets = np.concatenate(
                    [[0], np.cumsum(counts)]).astype(np.int64)
                for j in range(max(counts)):
                    rows = np.asarray([offsets[i] + j
                                       for i, c in enumerate(counts)
                                       if j < c])
                    self._quant_write(
                        [pages[r] for r in rows],
                        [offs[r] for r in rows],
                        k_toks[rows], v_toks[rows])
                return
            pg = jnp.asarray(pages, jnp.int32)
            of = jnp.asarray(offs, jnp.int32)
            self.k_pages = self.k_pages.at[pg, of].set(
                k_toks.astype(self.k_pages.dtype))
            self.v_pages = self.v_pages.at[pg, of].set(
                v_toks.astype(self.v_pages.dtype))

    # -- kernel inputs -----------------------------------------------------
    def page_table(self, seq_ids, max_pages=None):
        tbl, lens = self._padded_kernel_inputs(
            seq_ids, len(seq_ids), max_pages)
        if self._san is not None:
            self._san_check_table(seq_ids, tbl, lens)
        return jnp.asarray(tbl)

    def seq_lens(self, seq_ids):
        return jnp.asarray(
            [self._lens[s] for s in seq_ids], jnp.int32
        )

    def attend(self, q, seq_ids, sm_scale=None, window=0):
        """q: Tensor (B, H, D) — one decode token per listed sequence.
        ``window`` > 0: sliding-window attention over the last
        ``window`` cached tokens (out-of-window pages skipped).
        Quantized pools pass their scale sidecars into the kernel
        (dequant fused after the page DMA). The T=1 shape of
        :meth:`attend_ragged`: every row's ``q_len`` is 1."""
        self._plain_kv("attend")
        out = self.attend_ragged(
            Tensor(_as_tensor(q)._data[:, None]), seq_ids,
            [1] * len(seq_ids), sm_scale=sm_scale, window=window)
        return Tensor(out._data[:, 0])

    def _padded_kernel_inputs(self, seq_ids, rows_pad, max_pages):
        """Page table + lens padded to ``rows_pad`` rows x
        ``max_pages`` columns, numpy. Padding rows carry seq_len 0, which
        the paged kernels treat as inert (no page is valid, output
        exact zeros) — the shape-bucketing enabler for the chunked-
        prefill dispatch."""
        rows_pad = max(int(rows_pad or len(seq_ids)), len(seq_ids))
        chains = [self._table_row(s) for s in seq_ids]
        mp = max((len(pages) for pages, _ in chains), default=1)
        mp = max(int(max_pages or mp), mp, 1)
        tbl = np.zeros((rows_pad, mp), np.int32)
        lens = np.zeros((rows_pad,), np.int32)
        for i, (pages, n) in enumerate(chains):
            tbl[i, :len(pages)] = pages
            lens[i] = n
        return tbl, lens

    def _table_row(self, seq_id):
        """(pages, length) of a sequence as its kernel sees it. ``"eva"``,
        a sequence in window w: the summary pages of every earlier
        window, always whole pages, then the window's pages; the length
        counts one row a visible summary and one a window token. Every
        summary lies before every query, so causal attention over this
        row is ONE softmax over the window's exact keys and the earlier
        windows' summaries."""
        n = self._lens[seq_id]
        if not self.eva or not n:
            return self._tables[seq_id], n
        w, t, vis = self._window_of(n)
        return (self._tables[self._summary_key(seq_id)][:vis]
                + self._tables[seq_id], w * self.window_pages + t)

    def _step_tables(self, seq_ids, q_lens, rows_pad, max_pages,
                     slots=None, n_pad=None, merged=False):
        """What one attend call hands its kernel beside the pages, built
        in one place: the padded page table and lens
        (:meth:`_padded_kernel_inputs`, checked by the sanitizer where
        one is on), ``q_lens`` padded with zeros to the table's rows
        and, where ``slots`` = (pages, offsets) of a booked write plan
        is given, that plan padded to ``n_pad`` entries — page id
        ``num_pages`` is OUT OF BOUNDS, so the step programs'
        ``mode="drop"`` scatters skip the padding and every operand
        stays bucket-shaped. Everything is laid out on the host and
        crosses in one transfer call. Returns a :class:`StepTables`:
        ``(tbl, lens, ql)`` or ``(tbl, lens, ql, pg, of)`` on the device
        — ``merged`` (the layer program's form, fewer arrays to send):
        ``(rows, slots)`` with ``rows`` = table | lens | q_lens column
        by column and ``slots`` = (pg, of) — with the host's table and
        lens beside them; the one ``pool.table`` span site."""
        with telemetry.span("pool.table") as sp:
            tbl, lens = self._padded_kernel_inputs(
                seq_ids, rows_pad, max_pages)
            if self._san is not None:
                self._san_check_table(seq_ids, tbl, lens)
            ql = np.zeros((tbl.shape[0],), np.int32)
            ql[:len(seq_ids)] = q_lens
            host = (tbl, lens, ql)
            if slots is not None:
                pages, offs, *sums = slots
                plan = np.zeros((2, max(int(n_pad), len(pages))), np.int32)
                plan[0, len(pages):] = self.num_pages
                plan[0, :len(pages)] = pages
                plan[1, :len(offs)] = offs
                host += (plan[0], plan[1])
            if merged:
                host = (np.concatenate(
                    [tbl, lens[:, None], ql[:, None]], 1), plan)
                if self.eva:
                    # the summary plan: (page pooled, page and slot of
                    # its row), one a page the step fills, padded like
                    # the slot plan to what a step can fill at most
                    src = sums[0][0]
                    plan = np.zeros((3, plan.shape[1] // self.page_size
                                     + tbl.shape[0]), np.int32)
                    plan[1, len(src):] = self.num_pages
                    plan[:, :len(src)] = sums[0]
                    host += (plan,)
            out = StepTables(_upload(*host))
            out.host_tbl, out.host_lens = tbl, lens
            if self.eva:
                # what the kernel call of this step reads and computes,
                # exact from the table: a fed token is paired with every
                # row before it and itself
                fed, seen = ql.astype(np.int64), lens.astype(np.int64)
                out.counts = {
                    "fed": int(fed.sum()), "kv_rows": int(seen.sum()),
                    "pairs": int((fed * seen - fed * (fed - 1) // 2).sum()),
                    "summaries_written": len(sums[0][0])}
            if sp is not None:
                sp.attrs.update(rows=len(seq_ids), bytes=int(
                    sum(a.nbytes for a in host)))
        return out

    def book_step(self, seq_ids, counts, rows_pad, max_pages, n_pad,
                  like=None):
        """This pool's half of one packed step that every layer's pool
        takes part in: book the rows' slots here (:meth:`_ragged_slots`:
        capacity precheck, COW forks, length advance, sanitizer event)
        and return the step's :class:`StepTables`. ``like``: the tables
        another layer's pool built for the SAME step. Where this pool's
        booking, page chains and lengths are what ``like`` was built
        from (pools of one adapter are driven in lockstep, so always in
        practice) they are shared, checked against this pool's own
        shadow heap where a sanitizer is on, and nothing is built or
        uploaded; else this pool builds its own."""
        counts = [int(c) for c in counts]
        slots = (self._eva_slots if self.eva else self._ragged_slots)(
            seq_ids, counts)
        chains = [self._tables[k] for s in seq_ids
                  for k in self._chain_keys(s)]
        lens = [self._lens[s] for s in seq_ids]
        if like is not None and like.booked == (slots, chains, lens):
            if self._san is not None:
                self._san_check_table(seq_ids, like.host_tbl,
                                      like.host_lens)
            return like
        out = self._step_tables(seq_ids, counts, rows_pad, max_pages,
                                slots=slots, n_pad=n_pad, merged=True)
        out.booked = (slots, chains, lens)
        return out

    def attend_ragged(self, q, seq_ids, q_lens, rows_pad=None,
                      max_pages=None, sm_scale=None, window=0,
                      few_row_rows=None):
        """THE unified packed-step attend (ROADMAP item 2): ``q`` is
        (rows_pad, T, H, D) with row i's last ``q_lens[i]`` rows the
        newest tokens of seq_ids[i] — 1 for decode rows, n for
        prefill chunks (K/V already appended; seq_len counts them).
        Earlier rows and batch-padding rows return exact zeros. One
        ragged kernel call for the whole mixed batch: the single
        attend program per packed config. ``few_row_rows``: the rows
        the caller counted for the kernel's few-row form
        (``ragged_few_rows``), an attribute of the ``kernel.ragged``
        span."""
        self._plain_kv("attend_ragged")
        with telemetry.span("pool.fused_step", op="attend_ragged"):
            q = _as_tensor(q)
            tbl, lens, ql = self._step_tables(
                seq_ids, list(q_lens), rows_pad, max_pages)
            kp, vp = self.k_pages, self.v_pages
            ks = self.k_scales if self.quantized else None
            vs = self.v_scales if self.quantized else None

            def f(qr):
                return _ragged_kernel_fn(
                    qr, kp, vp, tbl, lens, q_lens=ql, sm_scale=sm_scale,
                    window=window, k_scales=ks, v_scales=vs,
                    counts=_few_counts(None, few_row_rows))

            return apply_op("paged_ragged_attend", f, q,
                            differentiable=False)

    def layer_step(self, x, weights, rope, plan, tables, eps,
                   sm_scale=None, window=0, unit_offset=False,
                   summary=None, few_row_rows=None, block=0, qk_norm=None,
                   router=None):
        """One decoder layer of a packed step as ONE compiled program
        over this pool's pages (ops/kernels/paged_attention.
        paged_ragged_layer_step): norm, qkv projection + RoPE + THIS
        chunk's K/V page scatter, the unified ragged kernel, o_proj,
        residual, norm, the gated MLP, residual. The pool owns the page
        mutation: the rows' slots were booked by :meth:`book_step`
        (which returned ``tables``; the forks ran BEFORE the program
        takes the page arrays), the arrays are handed over — donated on
        the chip, this pool holds the only reference — and the
        program's pages are committed before the output is handed back,
        so whatever reads ``k_pages`` next reads them after the commit.

        ``x`` (n_pad, E): the packed residual stream (raw array);
        ``weights`` = (ln1, wq, wk, wv, wo, biases, ln2, wg, wu, wd) raw
        arrays, [in, out] (biases None or (bq, bk, bv)); ``rope`` =
        (cos, sin); ``plan`` = (tok, gm) int32 device arrays: ``tok``
        (5, n_pad), a packed token's id, position and the scatter of
        the kernel's output back to the packed axis (mr, mc, mflat,
        padded with out-of-bounds drop entries), ``gm`` (rows_pad, T)
        the right-aligning gather map; ``tables`` the step's
        :class:`StepTables` of :meth:`book_step`. Returns the layer's
        output stream (n_pad, E), a raw array. Float pools only — int8
        page calibration is a host-driven per-token wave replay the
        program cannot express (callers use append_ragged +
        attend_ragged). ``unit_offset``: the norms' gains are stored
        less one (x / rms(x) * (1 + g)). ``summary`` = (phi, mu), each
        (heads, head_dim), of a ``page_format="eva"`` pool: the program
        pools every page this step fills into the row of the summary
        chain that :meth:`book_step` booked for it. ``few_row_rows``: as
        in :meth:`attend_ragged`. ``block``, ``qk_norm`` = (gq, gk) and
        ``router`` = (wr, top_k, norm_topk, scoring) are the program's
        other switches (the block-causal mask, per-head q/k norms, a
        routed feed-forward over ``weights``' expert stacks); with
        ``router`` the per-expert assignment counts come back beside the
        stream: ``(y, counts)``."""
        self._kv_only("layer_step")
        with telemetry.span("pool.fused_step", op="layer_step"):
            if self.quantized:
                raise ValueError(
                    "layer_step: int8 KV pools calibrate per token on "
                    "the host — use append_ragged + attend_ragged")
            if self.eva != (summary is not None):
                raise ValueError(
                    "layer_step: summary=(phi, mu) goes with "
                    f"page_format='eva' and with no other (this pool "
                    f"holds {self.page_format!r} pages)")
            tok, gm = plan
            rows, slots, *sums = tables
            n_pad = x.shape[0]
            if not tok.shape[1] == slots.shape[1] == n_pad:
                raise ValueError(
                    f"layer_step: the packed operand carries {n_pad} "
                    f"rows, its plan {tok.shape[1]} and {slots.shape[1]}"
                    " (every plan operand is padded to the packed "
                    "length)")
            y, self.k_pages, self.v_pages, *sizes = _layer_step_fn(
                self.k_pages, self.v_pages, x, weights, rope,
                (tok, gm, slots, rows), eps, sm_scale=sm_scale,
                window=window, unit_offset=unit_offset,
                summary=summary and (*summary, *sums),
                counts=_few_counts(tables.counts, few_row_rows),
                block=block, qk_norm=qk_norm, router=router)
            return (y, *sizes) if sizes else y

    def latent_ragged_step(self, q, toks, seq_ids, counts, gather_map,
                           value_dim, rows_pad=None, max_pages=None,
                           sm_scale=None):
        """The packed attention step of a LATENT pool, one compiled
        program per packed config: this chunk's rows land in the pages
        (the program's prologue), then the absorbed ragged kernel
        (ops/kernels/paged_attention.latent_ragged_attention) reads each
        row's pages once, as key and as value.

        ``q`` (n_pad, H, D): the packed absorbed queries; ``toks``
        (n_pad, D): what the cache holds of each packed token (rows past
        ``sum(counts)`` are padding and land nowhere); ``gather_map``
        (rows_pad, T) right-aligns each row's tokens; ``value_dim``: the
        leading numbers of a cached row that are its value. Returns the
        kernel's output (rows_pad, T, H, value_dim) as a Tensor (padded
        leading rows exact zeros). The pool owns the page mutation as in
        :meth:`layer_step`: slots are booked first (capacity
        precheck, COW forks, sanitizer events), the program's pages are
        committed before the output is handed back."""
        with telemetry.span("pool.fused_step", op="latent_ragged_step"):
            if not self.latent:
                raise ValueError(
                    "latent_ragged_step: needs page_format='latent', "
                    f"this pool holds {self.page_format!r} pages")
            q = q._data if isinstance(q, Tensor) else q
            toks = toks._data if isinstance(toks, Tensor) else toks
            counts = [int(c) for c in counts]
            n_pad, n_real = toks.shape[0], sum(counts)
            if n_real > n_pad or q.shape[0] != n_pad:
                raise ValueError(
                    f"latent_ragged_step: counts sum to {n_real}, the "
                    f"packed operands carry {q.shape[0]} queries and "
                    f"{n_pad} rows")
            tbl, lens, ql, pg, of = self._step_tables(
                seq_ids, counts, rows_pad, max_pages,
                slots=self._ragged_slots(seq_ids, counts), n_pad=n_pad)
            out, self.k_pages = _latent_step_fn(
                q, toks, pg, of, gather_map, self.k_pages, tbl, lens, ql,
                value_dim=value_dim, sm_scale=sm_scale)
            return Tensor(out)

    def dense_kv(self, seq_ids):
        """Dense (dequantized) gather of the listed sequences' pages:
        returns (page_table (B, MP), k (B, MP, P, KVH, D),
        v (...)) with k/v in compute dtype — the supported way to
        read quantized pages without touching the scale sidecars."""
        self._plain_kv("dense_kv")
        tbl = self.page_table(seq_ids)
        kd = self.k_pages[tbl]
        vd = self.v_pages[tbl]
        if self.quantized:
            kd = (kd.astype(jnp.float32)
                  * self.k_scales[tbl][:, :, None, :, None])
            vd = (vd.astype(jnp.float32)
                  * self.v_scales[tbl][:, :, None, :, None])
        return tbl, kd, vd

    @staticmethod
    def page_bytes(page_size, kv_heads, head_dim,
                   dtype=jnp.bfloat16, kv_dtype=None,
                   page_format="kv") -> int:
        """HBM bytes one page costs (K + V payload plus, when
        quantized, the scale sidecar rows; a latent page is ONE array
        of ``head_dim`` numbers a token) — pure arithmetic, usable
        for pool sizing BEFORE allocating anything."""
        if kv_dtype is not None:
            dtype = PagedKVCacheManager._KV_DTYPES[kv_dtype]
        dtype = jnp.dtype(dtype)
        if page_format == "latent":
            return page_size * head_dim * dtype.itemsize
        per = page_size * kv_heads * head_dim * dtype.itemsize * 2
        if dtype.name == "int8":
            per += kv_heads * 4 * 2
        return per

    @property
    def page_nbytes(self) -> int:
        return self.page_bytes(
            self.page_size, 1 if self.latent else self.k_pages.shape[2],
            self.k_pages.shape[-1], dtype=self.k_pages.dtype,
            page_format=self.page_format)

    @property
    def pool_nbytes(self) -> int:
        return self.page_nbytes * self.num_pages


def paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                    sm_scale=None, window=0, k_scales=None,
                    v_scales=None, name=None):
    """Functional surface over the Pallas paged decode kernel.
    ``k_scales``/``v_scales`` (NP, KVH): int8 pages with fused
    dequant."""
    q = _as_tensor(q)
    k_pages = _as_tensor(k_pages)
    v_pages = _as_tensor(v_pages)
    page_table = _as_tensor(page_table)
    seq_lens = _as_tensor(seq_lens)
    args = [q, k_pages, v_pages, page_table, seq_lens]
    quant = k_scales is not None
    if quant != (v_scales is not None):
        # mirror the kernel's guard here: dropping one scale silently
        # would attend over raw int8 codes
        raise ValueError(
            "paged_attention: pass both k_scales and v_scales or "
            "neither")
    if quant:
        args += [_as_tensor(k_scales), _as_tensor(v_scales)]

    def f(qr, kp, vp, tbl, ln, *scales):
        ks, vs = scales if quant else (None, None)
        return _kernel(qr, kp, vp, tbl, ln, sm_scale=sm_scale,
                       window=window, k_scales=ks, v_scales=vs)

    return apply_op(
        "paged_attention", f, *args, differentiable=False,
    )
