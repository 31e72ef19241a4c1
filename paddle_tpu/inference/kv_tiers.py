"""Tiered KV memory — the host-RAM spill tier behind the paged prefix
cache (ISSUE 14 tentpole, part a).

The HBM page pool (inference/paged_kv.py) is the capacity that actually
bounds a prefix-cache working set: before this module a cold prefix
evicted under page pressure was simply GONE, and the next request of
that tenant re-paid its whole prefill. Host RAM is order-10x HBM on a
serving host, and the paged layout's fixed ``[page_size, Hkv*D]`` tiles
are exactly the unit a capacity tier wants to move — so this module adds
the tier: cold prefix pages demote to pinned host buffers and promote
back on a hit, multiplying effective prefix-cache capacity by
host-RAM/HBM without touching the serving programs.

The staging contract (how a memory tier stays inside the audited
one-fetch/zero-extra-sync serving loop):

* **D2H staging rides the segment fetch.** ``stage()`` dispatches an
  async device gather of the entry's pool rows at a segment boundary
  (jax dispatch — no sync) and queues the futures; the engine's
  ``finish_segment`` folds them into THE single per-segment
  ``device_get`` (one ``allowed_sync`` event, unchanged count), and
  ``complete()`` lands the bytes in the host store. Staging is
  write-through: every insert queues a stage, so cache entries become
  "clean" (HBM + host copies) one segment after they appear.
* **Spill is metadata-only.** Under page pressure a CLEAN entry's HBM
  pages release instantly (the host copy is the data) — the pressure
  valve never needs a synchronous copy, which is what lets
  ``evict_until`` keep its zero-sync shape. An entry evicted before its
  stage materialised falls back to a plain drop (recompute later).
* **Restore is a dispatch.** A hit on a host-tier entry reserves fresh
  HBM pages and uploads the host rows with one scattered
  ``device_put``-class op BEFORE the segment dispatch — async device
  work, no host sync; the segment program reads the pages through the
  page table exactly like any prefix hit. The page-0 trash convention
  guarantees in-flight slots never observe a page mid-transition: only
  cache-held pages with no live-slot references ever spill.
* **Host pages are replica-portable.** A staged entry is plain host
  bytes + tokens, so the fleet directory (inference/fleet.py) can
  IMPORT it into another replica's cache on a steering miss — migration
  instead of recompute, the cross-replica half of the tier.

Accounting: every movement emits a ``tier_transfer`` flight/journal
event (direction = stage | spill | restore | import) with page and byte
counts, broadcasts on ``paged_kv.POOL_HOOKS`` (``tier_*`` events, the
PoolMonitor/CapacityMonitor feed), and restores/imports are billed to
the admitted request (``Request.tier_pages`` / ``tier_bytes``) so the
``analysis.tiers`` pass can enforce bytes-migrated/request <= KV-size.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from typing import Dict, List, Optional

import numpy as np

from ..observability import flight as _flight
from ..observability import metrics as _metrics

__all__ = ["HostTier", "TierMeter", "page_bytes", "flush_tiers",
           "install", "uninstall"]


def page_bytes(pager) -> int:
    """Bytes one pool page holds across EVERY pool plane (K + V, plus
    the per-page scale planes of a quantized pool): the tier-transfer
    unit cost. Computed from the live pool arrays — axis 1 is the page
    axis in all planes — so dtype changes are automatically priced: an
    int8 pool's spill/restore bills the true (¼-ish) bytes instead of
    assuming bf16 (r21 satellite; the SCALING §3n arithmetic reads this
    number)."""
    return sum(int(np.prod([a.shape[0], *a.shape[2:]])) * a.dtype.itemsize
               for a in pager.pool.values())


class HostTier:
    """Pinned host-RAM staging store for spilled prefix-cache pages.

    One per ``PagedPrefixCache`` (the fleet-isolation rule: host bytes
    belong to the cache that staged them; cross-replica movement is an
    explicit ``export``/``import``, never aliasing). All lookup state is
    host-side; the only device contact is the async stage gather and the
    restore upload, both dispatches — the audited sync set is untouched.

    ``capacity_pages`` bounds HOST residency (the 10x tier is still
    finite); LRU entries drop when it overflows."""

    def __init__(self, pager, capacity_pages: int = 4096):
        if capacity_pages < 1:
            raise ValueError(f"capacity_pages must be >= 1, got "
                             f"{capacity_pages}")
        from ..models import require

        require(pager.cfg, "host tier")
        self.pager = pager
        self.capacity_pages = int(capacity_pages)
        # key -> {<plane>: np [L, n, psz, ...] per pool plane ("k"/"v",
        #         plus "ks"/"vs" for quantized pools), "pages": n,
        #         "at": perf_counter} — LRU by insertion/touch order
        self._host: "OrderedDict[bytes, dict]" = OrderedDict()
        # queued D2H stages: [key, n_pages, *per-plane futures]
        self._pending: List[list] = []
        self.pages_host = 0           # host-resident staged pages
        self.stages = 0               # D2H copies completed
        self.spills = 0               # HBM page sets released to host tier
        self.restores = 0             # host -> fresh HBM page uploads
        self.imports = 0              # entries imported from another tier
        self.host_evictions = 0       # host-capacity LRU drops
        self.bytes_to_host = 0
        self.bytes_to_hbm = 0
        self.bytes_imported = 0

    # --- sizing -----------------------------------------------------------
    def page_bytes(self) -> int:
        return page_bytes(self.pager)

    def planes(self) -> tuple:
        """Pool plane names, in pool order — ("k", "v") for an fp pool,
        plus ("ks", "vs") per-page scale planes for a quantized pool
        (r21). Every tier movement carries ALL planes: a restored
        quantized page arrives with its scales or not at all."""
        return tuple(self.pager.pool)

    def has(self, key: bytes) -> bool:
        return key in self._host

    def prewarm_transfers(self, max_pages: int) -> None:
        """Compile the tier-transfer eager programs for every reachable
        page count (r20, ISSUE 15): the stage gather and the restore
        scatter are shape-keyed on the transferred page COUNT, which is
        bounded by the envelope's longest cacheable prefix — executing
        each count once here keeps the zero-post-warmup-compile budget
        intact through spills and restores. State-neutral: the gather
        reads page 0's rows, the scatter writes them back to a copy
        that is immediately dropped."""
        import jax.numpy as jnp

        pool = self.pager.pool
        for n in range(1, max(1, int(max_pages)) + 1):
            idx = jnp.asarray([0] * n, jnp.int32)   # stage()'s exact aval
            for arr in pool.values():
                g = arr[:, idx]
                # upload()'s scatter: host rows arrive as numpy,
                # transferred by jnp.asarray — replicate the aval chain
                # then discard
                _ = arr.at[:, idx].set(jnp.asarray(np.asarray(g)))

    # --- D2H staging (write-through; materialises at the segment fetch) ---
    def stage(self, key: bytes, pages: List[int]) -> None:
        """Queue an async D2H copy of ``pages``'s pool rows. Dispatch
        only — the futures ride the NEXT segment's single event fetch
        (``take_pending``/``complete``). Idempotent per key."""
        if key in self._host or any(p[0] == key for p in self._pending):
            return
        import jax.numpy as jnp

        idx = jnp.asarray(pages, jnp.int32)
        self._pending.append([key, len(pages)] +
                             [a[:, idx] for a in self.pager.pool.values()])

    def cancel(self, key: bytes) -> None:
        """Forget a queued stage (its entry was dropped before the copy
        landed) — the futures are simply released."""
        self._pending = [p for p in self._pending if p[0] != key]

    def take_pending(self) -> List[list]:
        """Hand the queued stage futures to the engine's segment fetch
        (the caller folds them into the ONE audited ``device_get``)."""
        out, self._pending = self._pending, []
        return out

    def complete(self, staged: List[list], host_vals) -> None:
        """Land fetched stage bytes in the host store. ``host_vals`` is
        the materialised per-entry plane tuples matching ``staged`` —
        plain numpy from the segment fetch that carried them."""
        pb = self.page_bytes()
        names = self.planes()
        for st, vals in zip(staged, host_vals):
            key, n = st[0], st[1]
            self._put(key, {p: np.asarray(a) for p, a in zip(names, vals)},
                      n)
            self.stages += 1
            self.bytes_to_host += n * pb
            _metrics.counter("serving.tier.stages").inc()
            _metrics.counter("serving.tier.bytes_to_host").inc(n * pb)
            from .paged_kv import _notify as _pool_notify

            _pool_notify("tier_stage", n, self.pager.allocator)
            _flight.record("tier_transfer", direction="stage", pages=n,
                           bytes=n * pb)

    def flush(self):
        """Materialise queued stages NOW (one labelled allowed sync) —
        for drains/teardown OUTSIDE the audited serve loop; the serve
        loop itself always rides the segment fetch instead."""
        staged = self.take_pending()
        if not staged:
            return
        import jax

        from ..analysis.syncs import allowed_sync

        with allowed_sync("serving.tier_transfer"):
            vals = jax.device_get([s[2:] for s in staged])
        self.complete(staged, vals)

    # --- host store -------------------------------------------------------
    # (module-level flush_tiers below batches SEVERAL tiers' pending
    # stages under one labelled sync — the r23 disagg-coalescing path)
    def _put(self, key: bytes, planes: Dict[str, np.ndarray],
             n: int) -> None:
        old = self._host.pop(key, None)
        if old is not None:
            self.pages_host -= old["pages"]
        self._host[key] = {**planes, "pages": int(n),
                           "at": time.perf_counter()}
        self.pages_host += int(n)
        while self.pages_host > self.capacity_pages and len(self._host) > 1:
            _, dropped = self._host.popitem(last=False)
            self.pages_host -= dropped["pages"]
            self.host_evictions += 1
            _metrics.counter("serving.tier.host_evictions").inc()
        _metrics.gauge("serving.tier.pages_host").set(self.pages_host)

    def get(self, key: bytes) -> Optional[dict]:
        ent = self._host.get(key)
        if ent is not None:
            self._host.move_to_end(key)
        return ent

    def drop(self, key: bytes) -> None:
        self.cancel(key)
        ent = self._host.pop(key, None)
        if ent is not None:
            self.pages_host -= ent["pages"]
            _metrics.gauge("serving.tier.pages_host").set(self.pages_host)

    # --- spill / restore / import accounting ------------------------------
    def note_spill(self, n_pages: int) -> None:
        """A clean entry's HBM pages released (metadata-only: the bytes
        already live here)."""
        self.spills += 1
        _metrics.counter("serving.tier.spills").inc()
        _metrics.counter("serving.tier.pages_spilled").inc(n_pages)
        from .paged_kv import _notify as _pool_notify

        _pool_notify("tier_spill", n_pages, self.pager.allocator)
        _flight.record("tier_transfer", direction="spill", pages=n_pages,
                       bytes=0)

    def upload(self, pages: List[int],
               planes: Dict[str, np.ndarray]) -> None:
        """Scatter host rows into freshly reserved pool pages — async
        dispatch (the H2D restore), issued BEFORE the segment that reads
        them. No host sync. ``planes`` carries every pool plane (scale
        planes included for a quantized pool)."""
        import jax.numpy as jnp

        idx = jnp.asarray(pages, jnp.int32)
        pool = self.pager.pool
        self.pager.pool = {
            p: pool[p].at[:, idx].set(jnp.asarray(planes[p]))
            for p in pool
        }
        n = len(pages)
        pb = self.page_bytes()
        self.restores += 1
        self.bytes_to_hbm += n * pb
        _metrics.counter("serving.tier.restores").inc()
        _metrics.counter("serving.tier.bytes_to_hbm").inc(n * pb)
        from .paged_kv import _notify as _pool_notify

        _pool_notify("tier_restore", n, self.pager.allocator)
        _flight.record("tier_transfer", direction="restore", pages=n,
                       bytes=n * pb)

    def export(self, key: bytes) -> Optional[dict]:
        """Replica-portable view of a staged entry (the fleet
        migration-on-miss source): host bytes only — an entry that
        never finished staging cannot export without a sync, so it
        returns None and the importer recomputes."""
        return self.get(key)

    def note_import(self, key: bytes, planes: Dict[str, np.ndarray],
                    n: int) -> None:
        """Land an entry imported from ANOTHER replica's tier (a host-
        to-host copy — the arrays are copied so the source replica's
        reset can never invalidate them)."""
        self._put(key, {p: np.array(a, copy=True)
                        for p, a in planes.items()}, n)
        pb = self.page_bytes()
        self.imports += 1
        self.bytes_imported += n * pb
        _metrics.counter("serving.tier.imports").inc()
        _metrics.counter("serving.tier.bytes_imported").inc(n * pb)
        from .paged_kv import _notify as _pool_notify

        _pool_notify("tier_import", n, self.pager.allocator)
        _flight.record("tier_transfer", direction="import", pages=n,
                       bytes=n * pb)

    # --- lifecycle / stats ------------------------------------------------
    def reset(self) -> None:
        """Drop all host state and zero counters (warm-run isolation —
        the same hook as ``PagedPrefixCache.reset``)."""
        self._host.clear()
        self._pending = []
        self.pages_host = 0
        self.stages = self.spills = self.restores = self.imports = 0
        self.host_evictions = 0
        self.bytes_to_host = self.bytes_to_hbm = self.bytes_imported = 0

    def stats(self) -> dict:
        return {"capacity_pages": self.capacity_pages,
                "pages_host": self.pages_host,
                "entries_host": len(self._host),
                "pending_stages": len(self._pending),
                "stages": self.stages,
                "spills": self.spills,
                "restores": self.restores,
                "imports": self.imports,
                "host_evictions": self.host_evictions,
                "bytes_to_host": self.bytes_to_host,
                "bytes_to_hbm": self.bytes_to_hbm,
                "bytes_imported": self.bytes_imported,
                "page_bytes": self.page_bytes()}


def flush_tiers(tiers) -> int:
    """Materialise the queued stages of SEVERAL tiers under ONE labelled
    ``serving.tier_transfer`` sync (r23 disagg satellite): when multiple
    requests cross the prefill→decode boundary in the same fleet loop
    turn, each crossing stages its handoff pages on its source replica's
    tier, and this coalesces all of those D2H copies into a single
    ``device_get`` instead of one sync per crossing. Per-tier
    ``complete()`` still lands each tier's bytes in its own host store
    (the per-crossing ledger — counters, journal events, byte billing —
    is untouched; only the SYNC count collapses).

    Returns the number of tiers that actually had pending stages (0 means
    no sync was issued at all)."""
    work = []
    for t in tiers:
        staged = t.take_pending()
        if staged:
            work.append((t, staged))
    if not work:
        return 0
    import jax

    from ..analysis.syncs import allowed_sync

    with allowed_sync("serving.tier_transfer"):
        flat = jax.device_get(
            [[s[2:] for s in staged] for _, staged in work])
    for (t, staged), vals in zip(work, flat):
        t.complete(staged, vals)
    return len(work)


# ---------------------------------------------------------------------------
# Ambient attachment (the gate's --tiers mode): a pure observer on
# POOL_HOOKS + SEGMENT_HOOKS counting tier traffic next to segments —
# host ints only, so attaching it must leave every canonical program's
# budget bit-identical (--tiers on|off, the capacity.install pattern).
# ---------------------------------------------------------------------------


class TierMeter:
    """Process-wide tier-traffic observer: counts ``tier_*`` pool events
    and engine segments. The gate attaches one to prove the tier
    accounting plane is hazard-neutral."""

    def __init__(self):
        self.segments = 0
        self.events: Dict[str, int] = {}
        self.pages: Dict[str, int] = {}

    def on_pool(self, event: str, n: int, alloc) -> None:
        if event.startswith("tier_"):
            self.events[event] = self.events.get(event, 0) + 1
            self.pages[event] = self.pages.get(event, 0) + int(n)

    def on_segment(self, steps: int, new_tokens: int,
                   finished: int) -> None:
        self.segments += 1


_INSTALLED: List[tuple] = []


def install(meter: TierMeter) -> None:
    from . import paged_kv as _pk
    from . import serving as _serving

    for m, _, _ in _INSTALLED:
        if m is meter:
            return
    ph, sh = meter.on_pool, meter.on_segment
    _pk.POOL_HOOKS.append(ph)
    _serving.SEGMENT_HOOKS.append(sh)
    _INSTALLED.append((meter, ph, sh))


def uninstall(meter: Optional[TierMeter] = None) -> None:
    from . import paged_kv as _pk
    from . import serving as _serving

    keep = []
    for m, ph, sh in _INSTALLED:
        if meter is None or m is meter:
            if ph in _pk.POOL_HOOKS:
                _pk.POOL_HOOKS.remove(ph)
            if sh in _serving.SEGMENT_HOOKS:
                _serving.SEGMENT_HOOKS.remove(sh)
        else:
            keep.append((m, ph, sh))
    _INSTALLED[:] = keep
