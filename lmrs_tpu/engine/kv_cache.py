"""Paged KV cache: fixed page pool + host-side page allocator.

The serving-memory design SURVEY.md §7.4 ranks as hard part #1: a fixed-size
page pool in HBM ([n_layers * num_pages, K, page_size, hd] — see PagedKVCache
for the layer-flattened layout rationale) with per-slot page tables, so KV
memory is allocated in O(page) quanta instead of one max_seq_len region per
slot.  Admission control = free pages (the reference's semaphore analog,
SURVEY.md §2.2).

The allocator is deliberately tiny and host-side (free-list); a C++
implementation with the same interface lives in runtime/native (used when
built — see lmrs_tpu.runtime.native) since allocator churn sits on the
scheduler's critical path.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from lmrs_tpu.config import ModelConfig
from lmrs_tpu.testing import faults

logger = logging.getLogger("lmrs.kv_cache")


class OutOfPages(RuntimeError):
    """Page pool exhausted — callers treat this as back-pressure, not error."""


class PageAllocator:
    """Ref-counted free-list page allocator (python reference implementation).

    Page 0 is RESERVED as the null page and never handed out: inactive batch
    rows carry all-zero page tables, and their masked-out dummy writes must
    land somewhere no live sequence owns (the vLLM null-block trick).

    Pages carry a reference count so the prefix cache can share one
    physical page read-only across live sequences (engine/prefix_cache.py):
    ``alloc`` hands out pages at refcount 1, ``incref`` adds a holder, and
    ``free`` is a decref — the page returns to the free list only when the
    last holder releases it.  Freeing a page that is already free raises
    ``ValueError`` instead of silently corrupting the pool (a double-freed
    page on the free list would be handed to two sequences at once).
    """

    RESERVED = 1  # page 0

    def __init__(self, num_pages: int):
        if num_pages <= self.RESERVED:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, self.RESERVED - 1, -1))
        self._refs = [0] * num_pages  # refcount per page (0 == on free list)

    @property
    def free_count(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise OutOfPages(f"need {n} pages, {len(self._free)} free")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def _check(self, pages: list[int], op: str) -> None:
        """Validate a free/incref batch BEFORE any mutation (the native
        allocator's contract): range-check every id, and require each
        page's refcount to cover its multiplicity in the call — so a
        rejected call leaves the pool untouched."""
        need: dict[int, int] = {}
        for p in pages:
            if not self.RESERVED <= p < self.num_pages:
                raise ValueError(f"bad page id {p}")
            need[p] = need.get(p, 0) + 1
        for p, n in need.items():
            if self._refs[p] < n:
                raise ValueError(
                    f"{op} of page {p} with refcount {self._refs[p]} "
                    f"(x{n} in call): double-free / unowned page")

    def incref(self, pages: list[int]) -> None:
        """Add one reference per listed page (prefix-cache sharing).  Only
        live (refcount > 0) pages may gain holders."""
        self._check(pages, "incref")
        for p in pages:
            self._refs[p] += 1

    def refcount(self, page: int) -> int:
        if not 0 <= page < self.num_pages:
            raise ValueError(f"bad page id {page}")
        return self._refs[page]

    def free(self, pages: list[int]) -> None:
        """Release one reference per listed page; pages reaching refcount 0
        return to the free list.  Raises on double-free (see class doc)."""
        self._check(pages, "free")
        for p in pages:
            self._refs[p] -= 1
            if self._refs[p] == 0:
                self._free.append(p)


def make_page_allocator(num_pages: int):
    """Native C++ allocator when built, else the Python free list.

    Both implement the identical contract (parity: tests/test_native.py);
    allocator churn sits on the scheduler's critical path, so the native one
    is preferred.
    """
    try:
        from lmrs_tpu.runtime.native import NativePageAllocator, native_available

        if native_available():
            return NativePageAllocator(num_pages)
    except Exception as e:  # pragma: no cover - fallback path
        logger.debug("native allocator unavailable: %s", e)
    return PageAllocator(num_pages)


@dataclass
class SequencePages:
    """Page table of one active sequence."""

    pages: list[int]
    length: int = 0  # tokens written

    def capacity(self, page_size: int) -> int:
        return len(self.pages) * page_size


class PagedKVCache:
    """Device page pool + per-slot host page tables.

    Layout [L*P, K, page_size, hd] — PAGE-major (round 3): one page's ALL
    kv heads are a contiguous [K, page_size, hd] block, so the ragged
    decode kernel fetches a page with ONE DMA instead of one per head (the
    decode walk measured DMA-issue-bound; docs/PERF.md round 3).  The
    layer axis is FLATTENED into the page axis: layer ``li``'s copy of
    logical page ``p`` is physical page ``li * P + p``.
    That lets the per-layer decode scatter write straight into the full
    carried pool with global page ids — no per-layer slice/update round
    trip, which would otherwise move the whole layer slice every decode
    step (models/transformer.forward_paged).  A slot's logical KV position
    maps to (page_table[pos // ps], pos % ps); tables hold LOGICAL page ids
    (< P) and are globalized per layer inside the forward.

    Under latent attention (``ModelConfig.kv_lora_rank``) there is ONE pool:
    ``k`` is [L*P, 1, page_size, latent_width], a token's row the normed
    latent beside the shared rotary key (models/latent.py), and ``v`` is
    None; every program carries the pair through as it carries K and V.

    Under a sliding window (``ModelConfig.sliding_window``) the pool holds
    TWO KINDS of layer (models/windowed.pool_layout, ``self.window``): the
    full layers as above, ``num_pages`` each, and behind them the window
    layers, each a ring of ``ceil(window / page_size) + 1`` pages for every
    one of ``slots`` slots plus a null page, whatever ``num_pages`` is: a
    window layer's share does not grow with the sequences.  The allocator
    and the page tables are the full layers'; a window layer's table is
    its slot's ring, built inside the forward from the row's slot.
    """

    def __init__(self, model_cfg: ModelConfig, num_pages: int, page_size: int,
                 max_pages_per_slot: int, allocator: PageAllocator | None = None,
                 mesh=None, kv_dtype: str | None = None, slots: int = 0):
        hd = model_cfg.hd
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_pages_per_slot = max_pages_per_slot
        # int8 pools (EngineConfig.kv_quantize): half the bytes per streamed
        # page and double the tokens per HBM GiB; scales are scheduler-owned
        # (ops/quant.py KV section)
        dt = jnp.dtype(kv_dtype) if kv_dtype else jnp.dtype(model_cfg.dtype)
        # L counts CACHE layers: one per (pass, layer) of a looped stack
        # (ModelConfig.cache_layers; n_layers where the stack runs once)
        shape = (model_cfg.cache_layers * num_pages, model_cfg.n_kv_heads,
                 page_size, hd)
        self.latent = bool(model_cfg.kv_lora_rank)
        self.window = None
        if model_cfg.sliding_window:
            if kv_dtype or (mesh is not None and mesh.devices.size > 1):
                raise ValueError(
                    "window KV cache (sliding_window > 0): no int8 pages "
                    "and no mesh of several devices")
            if slots < 1:
                raise ValueError("window KV cache: the rings are sized by "
                                 "the engine's slots")
            from lmrs_tpu.models.windowed import pool_layout

            self.window = pool_layout(model_cfg, page_size, slots, num_pages)
            shape = (self.window["total"], *shape[1:])
        if self.latent:
            if kv_dtype or (mesh is not None and mesh.devices.size > 1):
                raise ValueError(
                    "latent KV cache (kv_lora_rank > 0): no int8 pages and "
                    "no mesh of several devices (the latent row has no "
                    "per-head scales and no kv-head axis to shard)")
            shape = (model_cfg.cache_layers * num_pages, 1, page_size,
                     model_cfg.latent_width)
            pinned = None
            if mesh is not None:  # a replica's one device
                from jax.sharding import NamedSharding, PartitionSpec as P

                pinned = NamedSharding(mesh, P())
            self.k, self.v = jnp.zeros(shape, dt, device=pinned), None
        elif mesh is not None:
            # tensor-parallel serving: pages shard on the kv-head axis,
            # matching the wk/wv head sharding — each shard's attention and
            # page writes stay local, no cross-chip KV traffic.  tp=1 still
            # places on the mesh (replicated): a DP replica's cache must pin
            # to ITS devices, not the process default device.
            from jax.sharding import NamedSharding, PartitionSpec as P

            tp = mesh.shape.get("tp", 1)
            if tp > 1 and model_cfg.n_kv_heads % tp:
                raise ValueError(
                    f"n_kv_heads={model_cfg.n_kv_heads} not divisible by "
                    f"tp={tp}")
            sh = NamedSharding(mesh, P(None, "tp") if tp > 1 else P())
            self.k = jnp.zeros(shape, dt, device=sh)
            self.v = jnp.zeros(shape, dt, device=sh)
        else:
            self.k = jnp.zeros(shape, dt)
            self.v = jnp.zeros(shape, dt)
        self.allocator = allocator or make_page_allocator(num_pages)
        # Page-pressure reclaim hook (engine/prefix_cache.py): when set, an
        # allocation that would exceed the free list first asks the hook to
        # release reclaimable pages (LRU cache eviction).  Keeps the
        # admission/growth deadlock argument intact: cached pages are never
        # pinned — under pressure they drain back into the pool on demand.
        self.reclaim_cb = None
        logger.info(
            "paged KV cache: %d pages x %d tokens (%.1f MiB)",
            num_pages, page_size,
            (1 if self.latent else 2) * np.prod(shape) * dt.itemsize / 2**20,
        )

    def reallocate(self) -> None:
        """Fresh zeroed pools with the same shape/dtype/sharding.  Recovery
        hook for a failed DONATED dispatch chain (the scheduler's run-loop
        recovery): the old buffers may already be consumed, leaving self.k/v unusable.
        Only valid while no sequence is live (content is discarded)."""
        self.k = jnp.zeros(self.k.shape, self.k.dtype, device=self.k.sharding)
        if self.v is not None:
            self.v = jnp.zeros(self.v.shape, self.v.dtype,
                               device=self.v.sharding)

    def _no_export(self, op: str) -> None:
        if self.latent:
            raise NotImplementedError(
                f"latent KV cache: {op} (page export/import: prefix-cache "
                "spill, handoff, migration) is not built for the one-pool "
                "layout")
        if self.window is not None:
            raise NotImplementedError(
                f"window KV cache: {op} (page export/import: prefix-cache "
                "spill, handoff, migration) is not built for the two-kind "
                "pool: a window layer holds a ring a slot, not the "
                "sequence's pages")

    def kind_pages(self) -> tuple[int, int]:
        """(pages the full layers hold, pages the window layers hold), null
        pages left out: the pool's two shares.  (every page, 0) without a
        window."""
        if self.window is None:
            return (self.n_layers * (self.num_pages - 1), 0)
        w = self.window
        return (w["n_full"] * (self.num_pages - 1),
                w["n_win"] * (w["win_pages"] - 1))

    def pages_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def can_admit(self, n_tokens: int) -> bool:
        return self.pages_needed(n_tokens) <= self.allocator.free_count

    def alloc_pages(self, n: int) -> list[int]:
        """``allocator.alloc`` with the reclaim hook applied: under pressure,
        ask the prefix cache to evict before declaring OutOfPages."""
        # injection site: a fired plan forces the back-pressure path even
        # with free pages on hand — every caller must already treat
        # OutOfPages as pressure, not error (tests/test_chaos.py proves it)
        faults.fire("kv_cache.allocate", OutOfPages)
        if n > self.allocator.free_count and self.reclaim_cb is not None:
            self.reclaim_cb(n - self.allocator.free_count)
        return self.allocator.alloc(n)

    def open_sequence(self, n_tokens: int) -> SequencePages:
        """Allocate pages for a sequence expected to reach n_tokens (capped
        at max_pages_per_slot — callers clamp write positions accordingly)."""
        n = min(self.pages_needed(n_tokens), self.max_pages_per_slot)
        return SequencePages(pages=self.alloc_pages(n))

    def grow(self, seq: SequencePages, n_tokens: int) -> None:
        """Ensure capacity for n_tokens, allocating more pages as needed."""
        need = self.pages_needed(n_tokens) - len(seq.pages)
        if need > 0:
            if len(seq.pages) + need > self.max_pages_per_slot:
                raise OutOfPages("sequence exceeds max_pages_per_slot")
            seq.pages.extend(self.alloc_pages(need))

    def close_sequence(self, seq: SequencePages) -> None:
        self.allocator.free(seq.pages)
        seq.pages = []
        seq.length = 0

    def page_table_array(self, seqs: list[SequencePages | None]) -> np.ndarray:
        """[B, max_pages_per_slot] int32 table; unused entries point at page 0
        (masked out by per-row lengths)."""
        out = np.zeros((len(seqs), self.max_pages_per_slot), np.int32)
        for i, s in enumerate(seqs):
            if s is not None:
                out[i, : len(s.pages)] = s.pages
        return out

    # -------------------------------------------- sequence export / import

    @property
    def n_layers(self) -> int:
        return self.k.shape[0] // self.num_pages

    def _phys_ids(self, pages: list[int]) -> np.ndarray:
        """Physical page ids of a logical page set, all layers: layer li's
        copy of logical page p is physical page ``li * P + p`` (the
        layer-flattened pool layout, class doc)."""
        pg = np.asarray(pages, np.int64)
        return (np.arange(self.n_layers)[:, None] * self.num_pages
                + pg[None, :]).reshape(-1)

    def export_sequence(self, seq: SequencePages, length: int) -> dict:
        """Gather a sequence's page set into a host-side payload — the
        transferable unit of the disaggregated prefill→decode handoff
        (serving/handoff.py carries it over the wire).

        The page-major ``[L*P, K, ps, hd]`` layout makes the page set a
        contiguous unit: ONE gather over the flattened layer×page axis
        pulls every layer's copy.  Only the pages covering ``length``
        tokens are exported (a slot grown past the handoff point for
        decode-block capacity exports its prompt prefix only); the final
        page may be partial — ``kv_len`` in the payload masks the tail,
        exactly as per-row lengths do in the decode kernels.  Works for
        bf16 and int8-quantized pools alike (raw dtype bytes travel;
        int8's per-slot scales are scheduler-owned and ride the payload
        separately).  The sequence itself is untouched: the caller keeps
        the pages pinned until the importer acks (scheduler pin class).
        """
        self._no_export("export_sequence")
        faults.fire("handoff.export")
        n = self.pages_needed(max(1, length))
        if n > len(seq.pages):
            raise ValueError(
                f"export of {length} tokens needs {n} pages; sequence "
                f"holds {len(seq.pages)}")
        phys = jnp.asarray(self._phys_ids(seq.pages[:n]))
        L = self.n_layers
        # one batched fetch: each device_get is a blocking host sync, and
        # this runs on the scheduler thread
        k, v = (np.asarray(a)
                for a in jax.device_get((self.k[phys], self.v[phys])))
        kh, ps, hd = self.k.shape[1:]
        return {
            "version": 1,
            "kv_len": int(length),
            "n_pages": n,
            "page_size": self.page_size,
            "n_layers": L,
            "n_kv_heads": int(kh),
            "head_dim": int(hd),
            "dtype": str(self.k.dtype),
            "k": k.reshape(L, n, kh, ps, hd),
            "v": v.reshape(L, n, kh, ps, hd),
        }

    def export_pages(self, pages: list[int]) -> dict:
        """Host capture of an arbitrary page set's contents, all layers —
        the spill tier's device→host path (engine/prefix_cache.py).  Same
        single batched gather over the layer-flattened pool as
        ``export_sequence`` (one host sync), minus the
        sequence framing: the prefix cache's radix node carries the token
        labels, so the payload is just raw page content + dtype."""
        self._no_export("export_pages")
        phys = jnp.asarray(self._phys_ids(pages))
        k, v = (np.asarray(a)
                for a in jax.device_get((self.k[phys], self.v[phys])))
        kh, ps, hd = (int(x) for x in self.k.shape[1:])
        L, n = self.n_layers, len(pages)
        return {
            "k": k.reshape(L, n, kh, ps, hd),
            "v": v.reshape(L, n, kh, ps, hd),
            "dtype": str(self.k.dtype),
        }

    def import_pages(self, pages: list[int], payload: dict,
                     sync: bool = False) -> None:
        """Scatter a spilled payload back into freshly allocated pages —
        the prefetch half of the host-RAM tier.  Issued ASYNCHRONOUSLY by
        default: ``jnp.asarray`` + ``.at[].set`` dispatch without a host
        sync, the device sequences the copy before the next dispatch that
        consumes the pool, and the transfer overlaps the scheduler
        thread's host-side bookkeeping (the packing-prefetch overlap,
        PAPERS.md).  ``sync=True`` blocks until the scatter lands
        (``LMRS_HOST_KV_SYNC`` A/B fallback).  Geometry/dtype mismatches
        raise ``ValueError`` — same rejection discipline as
        ``import_sequence``; the caller re-prefills."""
        self._no_export("import_pages")
        n = len(pages)
        if payload.get("dtype") != str(self.k.dtype):
            raise ValueError(
                f"spill payload dtype {payload.get('dtype')!r} != pool "
                f"{self.k.dtype}")
        kh, ps, hd = (int(x) for x in self.k.shape[1:])
        shape = (self.n_layers, n, kh, ps, hd)
        k = np.asarray(payload["k"])
        v = np.asarray(payload["v"])
        if k.shape != shape or v.shape != shape:
            raise ValueError(
                f"spill payload shape {k.shape} != expected {shape}")
        phys = jnp.asarray(self._phys_ids(pages))
        flat = (self.n_layers * n, kh, ps, hd)
        self.k = self.k.at[phys].set(
            jnp.asarray(k.reshape(flat), self.k.dtype))
        self.v = self.v.at[phys].set(
            jnp.asarray(v.reshape(flat), self.v.dtype))
        if sync:
            jax.block_until_ready((self.k, self.v))

    def page_payload_bytes(self) -> int:
        """Host bytes one spilled page costs (k + v, all layers) — the
        spill tier's budget/fits arithmetic."""
        kh, ps, hd = (int(x) for x in self.k.shape[1:])
        return 2 * self.n_layers * kh * ps * hd * self.k.dtype.itemsize

    def import_sequence(self, payload: dict) -> SequencePages:
        """Scatter an exported page set into freshly allocated local pages
        and return the live sequence (``length`` = the payload's kv_len).

        The destination's free-list state is arbitrary — imported pages
        land wherever the local allocator hands them out; the page table
        indirection makes the physical ids irrelevant to attention.
        Raises ``ValueError`` on an incompatible payload (pool geometry or
        dtype mismatch — a stale ticket from a differently-configured pod
        must be rejected, not silently mis-scattered) and ``OutOfPages``
        under pool pressure (back-pressure: the importer retries, never
        corrupts).  On any failure after allocation the pages are freed —
        a failed import must not leak."""
        self._no_export("import_sequence")
        faults.fire("handoff.import")
        kh, ps, hd = (int(x) for x in self.k.shape[1:])
        want = {"page_size": self.page_size, "n_layers": self.n_layers,
                "n_kv_heads": kh, "head_dim": hd, "dtype": str(self.k.dtype)}
        for key, val in want.items():
            got = payload.get(key)
            if got != val:
                raise ValueError(
                    f"incompatible handoff payload: {key}={got!r}, this "
                    f"pool has {val!r}")
        n = int(payload["n_pages"])
        length = int(payload["kv_len"])
        if not 0 < n <= self.max_pages_per_slot:
            raise ValueError(f"bad handoff page count {n}")
        if self.pages_needed(max(1, length)) != n:
            raise ValueError(
                f"handoff kv_len {length} does not cover {n} pages")
        k = np.asarray(payload["k"])
        v = np.asarray(payload["v"])
        shape = (self.n_layers, n, kh, ps, hd)
        if k.shape != shape or v.shape != shape:
            raise ValueError(
                f"handoff page data shape {k.shape} != expected {shape}")
        pages = self.alloc_pages(n)
        try:
            phys = jnp.asarray(self._phys_ids(pages))
            flat = (self.n_layers * n, kh, ps, hd)
            self.k = self.k.at[phys].set(
                jnp.asarray(k.reshape(flat), self.k.dtype))
            self.v = self.v.at[phys].set(
                jnp.asarray(v.reshape(flat), self.v.dtype))
        except Exception:
            self.allocator.free(pages)
            raise
        return SequencePages(pages=pages, length=length)


def audit_allocator(allocator, num_pages: int,
                    holders: dict[int, int]) -> list[str]:
    """Page-pool invariant audit (the scheduler's ``audit()`` core).

    ``holders`` maps page id -> how many references the CALLER can account
    for (live sequences + prefix-cache retention).  Checks, returning one
    human-readable string per violation (empty list = clean):

    * conservation — every non-reserved page is either free (refcount 0)
      or held (refcount > 0), and the two partitions sum to the pool;
    * refcount balance — each page's allocator refcount equals the
      accounted holder count (a leak shows as refcount > holders == 0; a
      double-free / stray incref as a mismatch);
    * no accounted holder points at a free or reserved page.

    Works against both allocator implementations (Python free-list and the
    native C++ one) through the shared ``free_count``/``refcount`` API.
    """
    violations: list[str] = []
    reserved = getattr(type(allocator), "RESERVED", 1)
    free = allocator.free_count
    held = 0
    for p in range(reserved, num_pages):
        rc = allocator.refcount(p)
        if rc < 0:
            violations.append(f"page {p}: negative refcount {rc}")
            continue
        if rc > 0:
            held += 1
        expected = holders.get(p, 0)
        if rc != expected:
            kind = "leaked" if expected == 0 else "unbalanced"
            violations.append(
                f"page {p}: refcount {rc} but {expected} accounted "
                f"holder(s) ({kind})")
    if free + held != num_pages - reserved:
        violations.append(
            f"page conservation broken: {free} free + {held} held != "
            f"{num_pages - reserved} usable")
    for p in holders:
        if not reserved <= p < num_pages:
            violations.append(f"holder references out-of-range page {p}")
    if allocator.refcount(0) != 0:
        violations.append("reserved null page has a nonzero refcount")
    return violations
