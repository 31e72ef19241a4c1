"""Shared-prefix KV cache over the paged pool.

Reference counterpart: the prefix/prompt caches in production serving
stacks (vLLM's block-level prefix caching, SGLang's RadixAttention; the
reference's serving engines cache system-prompt KV the same way): when
many requests share a prompt prefix — a system prompt, few-shot
exemplars, a long document — the prefix's KV rows are identical across
requests (greedy prefill is deterministic and rope keys depend only on
absolute position), so prefilling it once and sharing the rows is pure
win over recomputing it per request.

Entries hold PAGE IDS of the engine's pool (``inference/paged_kv.py``): a
hit is a refcount bump followed by a *suffix-only* prefill inside the
segment program; no KV row is copied. Matching is exact-token and
page-aligned, over a flat LRU of entries (entry count is small —
dozens — so an O(entries) host scan beats maintaining a radix tree, and
it naturally credits PARTIAL overlaps: a prompt sharing only the first
64 of a cached 128-row prefix still reuses those 64 rows).

Population is admission-driven: after a segment admits a request cold,
the engine retains the pages that hold its prompt — so the FIRST request
of a shared-prefix burst warms the cache for the rest, with no workload
declaration needed.

Capacity is bounded in pages held; eviction is LRU over entries. All
lookup state is host-side; only the KV rows live on device.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Optional

import numpy as np

from ..observability import flight as _flight
from ..observability import metrics as _metrics
from .paged_kv import _notify as _pool_notify

__all__ = ["PagedPrefixCache", "PagedPrefixMatch", "make_prefix_cache"]


def make_prefix_cache(engine, capacity_tokens: int = 16384,
                      host_tier_pages: int = 0):
    """The ONE prefix cache for ONE engine (r12 fleet isolation): a
    ``PagedPrefixCache`` wrapping ITS pager (page refs must bump the
    allocator the slots actually draw from — sharing a cache across
    engines would retain pages of the wrong pool). The fleet router
    builds one per replica through here (``prefix_caches="auto"``);
    nothing in this module is process-global state, so N engines in one
    process never alias lookup state.

    **Why:** the cache assumes its entries' page ids belong to the
    engine that harvested them; keyed-off-the-engine construction makes
    that assumption structural instead of conventional."""
    host_tier = None
    if host_tier_pages:
        # r19 tiered KV (ISSUE 14): a host-RAM spill tier behind
        # THIS pager — host bytes are keyed to the cache that
        # staged them, so the tier is engine-scoped like the cache
        from .kv_tiers import HostTier

        host_tier = HostTier(engine.pager,
                             capacity_pages=int(host_tier_pages))
    return PagedPrefixCache(engine.pager,
                            capacity_pages=max(
                                1, capacity_tokens
                                // engine.pager.page_size),
                            host_tier=host_tier)


def _common_prefix(a: np.ndarray, b: np.ndarray) -> int:
    n = min(len(a), len(b))
    if n == 0:
        return 0
    neq = np.nonzero(a[:n] != b[:n])[0]
    return n if len(neq) == 0 else int(neq[0])


@dataclass
class _PagedEntry:
    tokens: np.ndarray   # [n] int32, n a multiple of page_size
    pages: list          # physical page ids ([] = host tier only)


@dataclass
class PagedPrefixMatch:
    length: int          # reusable rows (page multiple, < len(prompt))
    pages: list          # the physical pages holding those rows
    # r19 tiered KV (ISSUE 14): where the matched entry's rows live —
    # "hbm" (pool pages only), "clean" (pool pages + staged host copy),
    # "host" (host copy only: ``pages`` is empty and admission must
    # ``restore`` before it can share). ``key`` identifies the entry for
    # the restore call.
    tier: str = "hbm"
    key: bytes = b""


class PagedPrefixCache:
    """Shared-prefix cache over the paged KV pool
    (inference/paged_kv.py): entries hold page IDS, not KV arrays.
    Insertion retains the admitted request's prompt pages (one refcount
    bump per page — the rows are harvested by REFERENCE, the slot and
    the cache literally share physical pages); a hit hands the same page
    ids to the new request's reservation, which retains them again. Zero
    KV rows are copied anywhere in the hit path, and "reuse" is true
    dedup across every live request + the cache (N sharers of a 192-row
    prefix hold its pages ONCE).

    Granularity is whole pages (the page IS the block — sharers must
    never write a shared page, and suffix writes start at the page
    boundary after the hit, so the serving path never needs a COW
    break). Matching is exact-token over a flat LRU; capacity is bounded
    in PAGES held and eviction releases page refs (a page shared with a
    live slot frees only when that slot retires — eviction can't corrupt
    anyone). ``evict_until`` lets the
    admission path reclaim cache-held pages under page pressure before
    deferring a request (the cache must yield to live traffic).

    r19 tiered KV (ISSUE 14): with a ``host_tier``
    (inference/kv_tiers.HostTier) attached, inserts stage their pages
    to host RAM write-through (the async D2H rides the next segment's
    single event fetch), pressure/capacity eviction DEMOTES clean
    entries to the host tier instead of dropping them (metadata-only —
    the host copy is the data), and a hit on a host-tier entry
    ``restore``s: fresh HBM pages + an async upload + the normal
    ref-bump share. ``capacity_pages`` keeps bounding HBM-held pages;
    the host tier has its own bound. Every eviction routes through ONE
    code path (``_evict``) that emits the ``prefix_evict`` flight event
    with a ``reason`` (capacity | pressure | spill | subsumed | reset).
    ``listeners`` broadcast insert/evict/spill/restore transitions —
    the fleet cache directory's feed."""

    def __init__(self, pager, capacity_pages: int = 512, host_tier=None):
        from ..models import require

        require(pager.cfg, "prefix cache")
        self.pager = pager
        self.block = pager.page_size      # alignment rule = the page
        self.capacity_pages = int(capacity_pages)
        self.host_tier = host_tier
        self._entries: "OrderedDict[bytes, _PagedEntry]" = OrderedDict()
        self._pages_held = 0
        self.hits = 0
        self.misses = 0
        self.hit_tokens = 0
        self.evictions = 0
        self.spills = 0                   # demotions to the host tier
        self.restores = 0                 # promotions back to HBM
        # fn(event, key, tokens, tier, n_pages) — host ints/bytes only
        # (zero-sync observer contract); the fleet directory subscribes
        self.listeners: list = []

    # --- tier plumbing (all no-ops without a host tier) -------------------
    def _tier_of(self, key: bytes, ent: _PagedEntry) -> str:
        if not ent.pages:
            return "host"
        if self.host_tier is not None and self.host_tier.has(key):
            return "clean"
        return "hbm"

    def _notify_listeners(self, event: str, key: bytes,
                          ent: _PagedEntry) -> None:
        if self.listeners:
            tier = self._tier_of(key, ent)
            for fn in self.listeners:
                fn(event, key, ent.tokens, tier, len(ent.pages))

    def round_down(self, n: int) -> int:
        return (int(n) // self.block) * self.block

    # --- lookup -----------------------------------------------------------
    def match(self, prompt) -> Optional[PagedPrefixMatch]:
        """Longest whole-page common prefix between ``prompt`` and any
        cached entry — STRICT (at least one token must remain to
        prefill). Returns page ids WITHOUT retaining them: the
        reservation (``PagedKVCache.reserve``) takes the refs, so a
        deferred admission leaves no dangling count."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        cap = self.round_down(len(prompt))
        if cap == len(prompt):
            cap -= self.block
        best_l, best_key = 0, None
        if cap > 0:
            for key, ent in self._entries.items():
                m = self.round_down(min(_common_prefix(prompt, ent.tokens),
                                        cap))
                if m > best_l:
                    best_l, best_key = m, key
        if best_key is None:
            self.misses += 1
            _metrics.counter("serving.prefix_cache.misses").inc()
            return None
        ent = self._entries[best_key]
        self._entries.move_to_end(best_key)
        self.hits += 1
        self.hit_tokens += best_l
        tier = self._tier_of(best_key, ent)
        _metrics.counter("serving.prefix_cache.hits").inc()
        _metrics.counter("serving.prefix_cache.hit_tokens").inc(best_l)
        _flight.record("prefix_hit", rows=best_l,
                       prompt_len=int(len(prompt)),
                       pages=best_l // self.block, tier=tier)
        return PagedPrefixMatch(best_l, ent.pages[:best_l // self.block],
                                tier=tier, key=best_key)

    # --- population -------------------------------------------------------
    def insert(self, tokens, pages) -> None:
        """Insert the prefix ``tokens`` held by the given LIVE pages
        (one page per ``page_size`` tokens, currently referenced by the
        admitted slot). The cache RETAINS them — harvest by reference.
        Covered/subsumed entries are handled like the r7 cache."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        n = len(tokens)
        if n % self.block or n == 0:
            raise ValueError(
                f"prefix length {n} is not a positive multiple of "
                f"page_size {self.block}")
        if len(pages) != n // self.block:
            raise ValueError(f"{len(pages)} pages cannot hold {n} rows "
                             f"at {self.block}/page")
        stale = []
        for key, ent in self._entries.items():
            m = _common_prefix(tokens, ent.tokens)
            if m == n and len(ent.tokens) >= n:
                if ent.pages or (self.host_tier is not None
                                 and self.host_tier.has(key)):
                    self._entries.move_to_end(key)
                    return                  # already covered
                stale.append(key)           # dead host entry: replace
            elif m == len(ent.tokens):
                stale.append(key)           # subsumed by the new entry
        for key in stale:
            self._evict(key, reason="subsumed")
        self.pager.allocator.retain(pages)
        _pool_notify("cache_retain", len(pages), self.pager.allocator)
        key = tokens.tobytes()
        ent = _PagedEntry(tokens, list(pages))
        self._entries[key] = ent
        self._pages_held += len(pages)
        if self.host_tier is not None:
            # write-through staging: the async D2H gather dispatches now
            # and materialises at the NEXT segment's single event fetch,
            # after which this entry is "clean" and pressure eviction
            # demotes it for free instead of dropping it
            self.host_tier.stage(key, ent.pages)
        self._notify_listeners("insert", key, ent)
        self._shrink_to_capacity()
        _metrics.gauge("serving.prefix_cache.pages_held").set(
            self._pages_held)

    def _shrink_to_capacity(self) -> None:
        """HBM-held pages back under ``capacity_pages``: LRU-first,
        spill-preferred (host-tier entries hold zero HBM pages and are
        skipped — they are already out of the bounded resource)."""
        if self._pages_held <= self.capacity_pages:
            return
        for key in list(self._entries):
            if self._pages_held <= self.capacity_pages \
                    or len(self._entries) <= 1:
                break
            if self._entries[key].pages:
                self._evict(key, reason="capacity", count=True)

    def _evict(self, key: bytes, reason: str = "capacity",
               count: bool = False) -> None:
        """THE eviction path (r19 small fix, ISSUE 14): every page
        release routes here and emits one ``prefix_evict`` flight event
        with its ``reason`` — capacity (LRU bound), pressure (the
        admission valve), subsumed (a longer insert), reset (teardown)
        — or demotes to ``spill`` when a host copy exists and the
        reason is reclaim-shaped (the tiered path: the entry survives,
        only its HBM residency ends)."""
        ent = self._entries[key]
        spillable = (self.host_tier is not None and ent.pages
                     and reason in ("capacity", "pressure")
                     and self.host_tier.has(key))
        if spillable:
            self.pager.release_pages(ent.pages)
            _pool_notify("cache_release", len(ent.pages),
                         self.pager.allocator)
            self._pages_held -= len(ent.pages)
            n_pages, ent.pages = len(ent.pages), []
            self.spills += 1
            self.host_tier.note_spill(n_pages)
            _metrics.counter("serving.prefix_cache.spills").inc()
            _flight.record("prefix_evict", pages=n_pages,
                           pages_held=self._pages_held, reason="spill")
            self._notify_listeners("spill", key, ent)
            return
        self._entries.pop(key)
        if ent.pages:
            self.pager.release_pages(ent.pages)
            _pool_notify("cache_release", len(ent.pages),
                         self.pager.allocator)
            self._pages_held -= len(ent.pages)
        if self.host_tier is not None:
            self.host_tier.drop(key)
        if count:
            self.evictions += 1
            _metrics.counter("serving.prefix_cache.evictions").inc()
        _flight.record("prefix_evict", pages=len(ent.pages),
                       pages_held=self._pages_held, reason=reason)
        self._notify_listeners("evict", key, ent)

    def evict_until(self, pages_free: int) -> int:
        """Release LRU entries' HBM pages until the allocator has
        ``pages_free`` free pages (or nothing reclaimable remains). The
        page-pressure valve: admission calls this before deferring a
        request, so cache-held history never starves live traffic. With
        a host tier, clean entries SPILL (the prefix survives in host
        RAM and a later hit restores it) — only unstaged entries are
        truly dropped. Returns entries evicted/spilled.

        Two valve rules (r19 fix — the r18 valve dropped LRU blindly):
        entries whose pages would free NOTHING right now (every page
        still referenced by a live slot) are skipped — destroying them
        cannot help the admission that is stalling, and surviving one
        more segment is exactly what lets their write-through stage
        land so the next pressure event SPILLS them instead; and clean
        entries go first (lossless reclaim before lossy)."""
        n = 0
        alloc = self.pager.allocator
        for lossless in (True, False):
            for key in list(self._entries):
                if alloc.pages_free >= pages_free:
                    return n
                ent = self._entries.get(key)
                if ent is None or not ent.pages:
                    continue              # host tier: no HBM to reclaim
                if not any(alloc.ref(p) == 1 for p in ent.pages):
                    continue              # live-shared: frees nothing
                clean = (self.host_tier is not None
                         and self.host_tier.has(key))
                if lossless != clean:
                    continue
                self._evict(key, reason="pressure", count=True)
                n += 1
        return n

    # --- tier restore / migration (r19, ISSUE 14) -------------------------
    def restore(self, key: bytes, rows: int) -> Optional[list]:
        """Promote a host-tier entry's first ``rows`` back into HBM:
        reserve fresh pages (refcount 1, cache-owned — the same
        ownership a normal insert's retain establishes) and dispatch
        the async upload; the admission's ``reserve(shared=...)`` then
        ref-bumps them exactly like an always-resident hit. A partial
        restore truncates the entry to the restored span (the
        requester's own insert re-grows it). Returns the page list, or
        None when the entry cannot restore (not staged / no room)."""
        ent = self._entries.get(key)
        if ent is None or ent.pages or self.host_tier is None:
            return None
        host = self.host_tier.get(key)
        if host is None:
            return None
        n = min(rows // self.block, host["pages"])
        if n < 1 or n > self.pager.allocator.pages_free:
            return None
        pages = self.pager.allocator.alloc(n)
        _pool_notify("cache_retain", n, self.pager.allocator)
        names = self.host_tier.planes()
        self.host_tier.upload(pages, {p: host[p][:, :n] for p in names})
        if n < len(ent.tokens) // self.block:
            # partial restore truncates the entry (the hitting
            # request's own post-segment insert re-grows it); the host
            # copy re-keys with the truncated tokens so the entry stays
            # clean, and a shorter sibling with the same tokens yields
            del self._entries[key]
            self.host_tier.drop(key)
            ent.tokens = ent.tokens[:n * self.block]
            key = ent.tokens.tobytes()
            if key in self._entries:
                self._evict(key, reason="subsumed")
            self._entries[key] = ent
            self.host_tier._put(key, {p: np.asarray(host[p][:, :n])
                                      for p in names}, n)
        ent.pages = list(pages)
        self._entries.move_to_end(key)
        self._pages_held += n
        self.restores += 1
        _metrics.counter("serving.prefix_cache.restores").inc()
        self._notify_listeners("restore", key, ent)
        self._shrink_to_capacity()
        return list(pages)

    def export_host(self, key: bytes) -> Optional[dict]:
        """Replica-portable bytes for ``key`` (fleet migration-on-miss
        source): the staged host copy + tokens, or None when the entry
        never finished staging (moving it would need a sync)."""
        ent = self._entries.get(key)
        if ent is None or self.host_tier is None:
            return None
        host = self.host_tier.export(key)
        if host is None:
            return None
        n = host["pages"]
        out = {"tokens": ent.tokens[:n * self.block], "pages": n}
        out.update({p: host[p] for p in self.host_tier.planes()})
        return out

    def import_host(self, tokens, planes) -> bool:
        """Land an entry exported from another replica's tier as a
        HOST-tier entry of THIS cache (no HBM pages yet — the next hit
        restores through the normal path). The fleet's migration-on-
        miss: importing host bytes replaces recomputing the prefill.
        ``planes`` maps pool plane name -> host array (every plane of
        the exporter's pool — both replicas of a fleet run the same
        pool dtype, so the plane sets match)."""
        if self.host_tier is None:
            return False
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        n = len(tokens) // self.block
        if n < 1:
            return False
        tokens = tokens[:n * self.block]
        key = tokens.tobytes()
        if key in self._entries:
            return False                  # already present locally
        ent = _PagedEntry(tokens, [])
        self._entries[key] = ent
        self.host_tier.note_import(
            key, {p: np.asarray(a)[:, :n] for p, a in planes.items()}, n)
        self._notify_listeners("insert", key, ent)
        return True

    def clear(self) -> None:
        while self._entries:
            self._evict(next(iter(self._entries)), reason="reset")

    def reset(self) -> None:
        """Release all page refs and zero counters (the scheduler's
        warm-run isolation hook — warmup must not pre-populate measured
        hits; the PAGER keeps its pool and the host tier empties with
        the entries)."""
        self.clear()
        if self.host_tier is not None:
            self.host_tier.reset()
        self.hits = self.misses = self.hit_tokens = self.evictions = 0
        self.spills = self.restores = 0

    # --- stats ------------------------------------------------------------
    @property
    def pages_held(self) -> int:
        return self._pages_held

    def physical_pages_held(self) -> int:
        """DISTINCT physical pages the cache references: entries with a
        common prefix share its pages (the COW dedup), so the ref-count
        sum ``pages_held`` over-counts physical residency exactly when
        dedup is working. The leak audits compare allocator occupancy
        against THIS number (r19 fix: the fleet leak audit previously
        used ``pages_held`` and mis-flagged deduped caches)."""
        return len({p for ent in self._entries.values()
                    for p in ent.pages})

    @property
    def host_pages(self) -> int:
        """Pages resident in the host tier (0 without one) — the other
        half of the r19 tier dimension."""
        return self.host_tier.pages_host if self.host_tier is not None \
            else 0

    def reclaimable_pages(self, tier: str = "hbm") -> int:
        """Pages eviction would actually return to the free list RIGHT
        NOW: cache-held pages not also referenced by a live slot (a
        shared page only frees when its last reference dies, so the
        slot-shared subset is pinned regardless of what the cache
        does). The r18 capacity plane's 'free + reclaimable'
        availability term — host set arithmetic over the pager's
        mirrors.

        r19 tier dimension (ISSUE 14): ``tier="hbm"`` (default) keeps
        the r18 meaning; ``tier="host"`` counts host-resident staged
        pages (all droppable — host RAM is the reclaim, not the pool);
        ``tier="all"`` sums both — the admission-side 'host-tier pages
        count as reclaimable' total."""
        if tier == "host":
            return self.host_pages
        held = {p for ent in self._entries.values() for p in ent.pages}
        live = {p for pages in self.pager.slot_pages for p in pages}
        hbm = len(held - live)
        return hbm + self.host_pages if tier == "all" else hbm

    def spillable_pages(self) -> int:
        """The subset of reclaimable HBM pages whose entries are CLEAN
        (host copy staged): reclaiming them costs zero recompute — the
        capacity plane's lossless-reclaim signal."""
        if self.host_tier is None:
            return 0
        held = set()
        for key, ent in self._entries.items():
            if ent.pages and self.host_tier.has(key):
                held.update(ent.pages)
        live = {p for pages in self.pager.slot_pages for p in pages}
        return len(held - live)

    def stats(self) -> dict:
        out = {"hits": self.hits, "misses": self.misses,
               "hit_tokens": self.hit_tokens,
               "pages_held": self._pages_held,
               "tokens_held": self._pages_held * self.block,
               "entries": len(self._entries),
               "evictions": self.evictions}
        if self.host_tier is not None:
            out.update(spills=self.spills, restores=self.restores,
                       host_pages=self.host_pages,
                       tier=self.host_tier.stats())
        return out
