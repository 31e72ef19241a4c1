"""Paged KV-cache subsystem: page-table allocator + COW prefix sharing.

Reference counterpart: vLLM's PagedAttention block manager and the
Ragged Paged Attention TPU serving design (PAPERS.md #1): instead of one
contiguous ``[slots, max_len]`` block per engine — provisioned for the
WORST-CASE length of every slot — what a sequence keeps on the device
lives in one flat pool of fixed-size pages (axis 1 of every plane is the
page axis) and each slot's sequence is the ordered list of pages its page
table names. **What a page holds is the model's** (``models.family_of``:
its ``init_paged_pool``), one of four today:

* ``llama``: ``page_size`` K rows and V rows of every layer, two planes
  ``[L, num_pages, page_size, Hkv*D]`` (plus scale planes when quantized);
* ``latent_moe``: ``page_size`` latent rows ``[ckv | kr]`` of every layer,
  one plane ``[L, num_pages, page_size, row]``;
* ``power_retention``: ONE SEQUENCE'S RECURRENT STATE in every layer,
  whatever its length: ``{"s": [L, num_pages, Hkv, D, d], "z": [L,
  num_pages, Hkv, D]}`` in float32. Its engine is built with ``page_size =
  max_len``, which makes a page a sequence: ``max_pages`` is 1, the pool is
  ``slots + 1`` pages, ``pages_needed`` is 1 for every request, a slot's
  table is one page id, and ``ensure_writable``'s page-granular copy is a
  snapshot of a state. A freed state page is handed to the next request as
  it is: the model starts from zero at position 0 whatever the page holds.
* ``hybrid_moe``: TWO KINDS OF CACHE for one sequence. Its full-attention
  layers keep ``page_size`` K rows and V rows a page, planes ``{"k", "v"}:
  [L_full, num_pages, page_size, Hkv*D]``, as llama's. Its window layers
  need a sequence's last ``sliding_window`` rows and nothing else, so a
  sequence keeps them in a FIXED PART whose bytes do not depend on its
  length: planes ``{"wk", "wv"}: [L_window, fixed_parts, sliding_window,
  Hkv*D]``, position ``p`` at row ``p mod sliding_window``. A model
  declares the second kind with ``fixed_part_bytes``; this manager then
  holds ``slots + 1`` parts (part 0 the trash part), BINDS PART ``s + 1``
  TO SLOT ``s`` (a sequence needs its part exactly while it has a slot, so
  there is nothing to allocate, to run out of, or to leak: a part is held
  while its slot holds pages) and gives the page table one more column,
  the part's id, which the segment program fills in as it admits. A
  reused part is not cleared: the model masks by position.

Three consequences, each a serving-memory property the contiguous layout
cannot express:

* **The ``max_len`` provisioning wall is gone.** A slot's physical
  footprint is ``ceil(live_rows / page_size)`` pages, allocated at
  admission from the request's KNOWN bound (prompt + max_new_tokens —
  generation length is fixed at admission in this engine, so headroom is
  exact, not an estimate). The pool can be sized to the expected LIVE
  token load, not ``slots x max_len``; admission is gated on *pages
  free* (see ``ServingEngine`` + ``OnlineScheduler``).
* **Prefix sharing is dedup, not copy.** A prefix-cache hit maps the
  SAME physical pages into the new slot's table — one refcount bump per
  page, zero KV row copies. Pages are copy-on-write: sharers
  never write shared pages in the serving path (suffix rows start at a
  page boundary past the shared prefix), and ``cow_break`` materialises
  a private copy for the forking paths (speculative decode, preemption
  resume) that do write history.
* **Harvest/free returns pages, not rows.** Retiring a request releases
  its page refs; pages with live references elsewhere (the prefix
  cache, a sharing slot) survive — eviction and reuse are O(pages), and
  a "freed" prefix stays warm for exactly as long as something
  references it.

Allocation/refcounting is HOST-side (plain lists + a numpy refcount
array — admission already runs on the host between segments); only the
pool and the per-slot page tables live on device. Page 0 is reserved as
the TRASH page: retired slots' in-program writes and table-tail lookups
route there (see ``llama.forward_with_pages``), so a frozen slot can
never scribble on a page the allocator handed to someone else.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..observability import flight as _flight
from ..observability import metrics as _metrics

__all__ = ["PageAllocator", "PagedKVCache", "POOL_HOOKS"]

# Process-wide pool observers (r18, ISSUE 13): ``fn(event, n,
# allocator)`` called after every allocator state change ("alloc" /
# "retain" / "release" from PageAllocator; "cache_retain" /
# "cache_release" forwarded by PagedPrefixCache) — host ints + the
# allocator object only, so a hook can never add a device sync.
# ``observability.capacity.PoolMonitor`` subscribes here (filtering by
# allocator identity — fleet isolation holds for observers too). Empty
# by default: the common case costs one truthiness check per event.
POOL_HOOKS: List = []


def _notify(event: str, n: int, alloc) -> None:
    if POOL_HOOKS:
        for fn in POOL_HOOKS:
            fn(event, n, alloc)


class PageAllocator:
    """Fixed-size-page free list with per-page refcounts.

    Page 0 is reserved (the trash page — never allocated, never freed).
    ``alloc`` hands out pages at refcount 1; ``retain`` bumps (the COW
    share operation); ``release`` drops and returns a page to the free
    list only when its LAST reference dies. ``check`` audits the
    free-list/refcount invariant — the property tests drive randomized
    admit/share/free schedules against it."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError(f"need >= 2 pages (1 is the reserved trash "
                             f"page), got {num_pages}")
        self.num_pages = int(num_pages)
        # LIFO free list: recently-freed pages are re-used first (their
        # pool rows are most likely still resident in any cache level)
        self._free: List[int] = list(range(self.num_pages - 1, 0, -1))
        self._ref = np.zeros(self.num_pages, np.int32)
        self.total_allocated = 0   # cumulative alloc count (bench metric)

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_used(self) -> int:
        return self.num_pages - 1 - len(self._free)

    def ref(self, page: int) -> int:
        return int(self._ref[page])

    def alloc(self, n: int) -> List[int]:
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: need {n}, free {len(self._free)} "
                f"(admission must gate on pages_free)")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        if n:
            self.total_allocated += n
            _metrics.counter("serving.pages.allocated").inc(n)
            _notify("alloc", n, self)
        return pages

    def retain(self, pages: Sequence[int]) -> None:
        """Share ``pages``: one ref bump each (the zero-copy half of
        copy-on-write — a prefix hit is exactly this call)."""
        for p in pages:
            if p == 0 or self._ref[p] <= 0:
                raise RuntimeError(f"retain of unallocated page {p}")
            self._ref[p] += 1
        if len(pages):
            _metrics.counter("serving.pages.cow_shares").inc(len(pages))
            _notify("retain", len(pages), self)

    def release(self, pages: Sequence[int]) -> int:
        """Drop one reference per page; pages reaching refcount 0 return
        to the free list. Returns how many pages actually freed."""
        freed = 0
        for p in pages:
            if p == 0 or self._ref[p] <= 0:
                raise RuntimeError(f"release of unallocated page {p} "
                                   f"(double free?)")
            self._ref[p] -= 1
            if self._ref[p] == 0:
                self._free.append(p)
                freed += 1
        if freed:
            _metrics.counter("serving.pages.freed").inc(freed)
        if len(pages):
            _notify("release", len(pages), self)
        return freed

    def check(self) -> List[str]:
        """Invariant audit: every page is either free (ref 0, on the
        list exactly once) or held (ref > 0, not on the list)."""
        bad = []
        free_set = set(self._free)
        if len(free_set) != len(self._free):
            bad.append("free list holds duplicates")
        if 0 in free_set:
            bad.append("trash page 0 on the free list")
        for p in range(1, self.num_pages):
            r = int(self._ref[p])
            if r < 0:
                bad.append(f"page {p} refcount {r} < 0")
            if r == 0 and p not in free_set:
                bad.append(f"page {p} leaked (ref 0, not free)")
            if r > 0 and p in free_set:
                bad.append(f"page {p} double-booked (ref {r}, on free "
                           f"list)")
        return bad


class PagedKVCache:
    """Device page pool + per-slot page tables over a ``PageAllocator``.

    The serving engine's paged memory: ``pool`` is the model's planes
    (``init_paged_pool``: K/V rows, latent rows, or — with ``page_size =
    max_len`` — a sequence's recurrent state a page; the module's text
    says which) and ``page_table`` the device-side ``[slots, max_pages]``
    int32 map the segment program consumes (both donated through the
    program and updated in place; everything here addresses pages on
    axis 1 and never looks past the page axis, so what a page holds is
    the model's alone; the host keeps ``slot_pages`` mirrors for
    bookkeeping). ``max_pages`` bounds ONE
    slot's virtual length (``max_pages * page_size`` = the engine's
    ``max_len`` contract); ``num_pages`` bounds the POOL — sizing it
    below ``slots * max_pages`` is the whole point (admission degrades
    to pages-free gating instead of provisioning every slot for the
    worst case)."""

    def __init__(self, cfg, slots: int, page_size: int, num_pages: int,
                 max_pages: int, dtype=None, mesh=None, quant=None):
        from ..models import family_of, llama

        self.cfg = cfg
        self.slots = int(slots)
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.max_pages = int(max_pages)
        self.allocator = PageAllocator(self.num_pages)
        self.mesh = mesh
        # quant ('int8' | 'fp8', r21): pages store the narrow dtype and
        # the pool gains per-page fp32 scale planes ("ks"/"vs"). All
        # page BOOKKEEPING here is dtype-oblivious — only the plane set
        # changes, and every page-granular copy below iterates the pool
        # dict instead of naming k/v
        self.quant = quant
        # the planes, their row width and their dtype are the model's; so
        # is a second kind of cache, a fixed part a sequence (the module's
        # text): one a slot and the trash part, its id the table's last
        # column
        from ..analysis.memory import fixed_part_bytes_for

        self.fixed_part_bytes = fixed_part_bytes_for(cfg)
        self.fixed_parts = self.slots + 1 if self.fixed_part_bytes else 0
        kinds = {"fixed_parts": self.fixed_parts} if self.fixed_parts else {}
        self.pool = family_of(cfg).init_paged_pool(
            cfg, self.num_pages, self.page_size, dtype=dtype, quant=quant,
            **kinds)
        self.page_table = jnp.zeros((self.slots, self.table_width),
                                    jnp.int32)
        if mesh is not None:
            # tensor-parallel serving (r12): the pool's flat Hkv*D minor
            # dim shards over 'mp' by whole kv heads
            # (llama.paged_pool_spec — the dim the column-parallel
            # wk/wv projections produce sharded); page
            # TABLES stay replicated int32 indices, so every page-id
            # operation in this class (reserve/install/fork/COW) is
            # untouched — paging is mesh-oblivious by construction
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            self.pool = jax.device_put(
                self.pool, NamedSharding(mesh, llama.paged_pool_spec()))
            self.page_table = jax.device_put(
                self.page_table, NamedSharding(mesh, P()))
        self.slot_pages: List[List[int]] = [[] for _ in range(self.slots)]
        self.cow_breaks = 0
        self.peak_occupancy = 0.0

    # --- sizing -----------------------------------------------------------
    def pages_needed(self, rows: int) -> int:
        return -(-int(rows) // self.page_size)

    @property
    def table_width(self) -> int:
        """Columns of a slot's table row: its ``max_pages`` page ids and,
        where sequences keep a fixed part, that part's id."""
        return self.max_pages + bool(self.fixed_parts)

    @property
    def fixed_parts_held(self) -> int:
        """Fixed parts in use: a slot's part is held while the slot holds
        pages."""
        return sum(map(bool, self.slot_pages)) if self.fixed_parts else 0

    def reserved_bytes(self, rows: int) -> int:
        """What a request spanning ``rows`` rows keeps on the device: its
        pages and, whatever ``rows`` is, its fixed part."""
        from ..analysis.memory import page_bytes_for

        return self.pages_needed(rows) * page_bytes_for(
            self.cfg, self.page_size, self.quant) + self.fixed_part_bytes

    @property
    def pages_free(self) -> int:
        return self.allocator.pages_free

    def occupancy(self) -> float:
        return self.allocator.pages_used / max(1, self.num_pages - 1)

    def _gauges(self) -> None:
        occ = self.occupancy()
        self.peak_occupancy = max(self.peak_occupancy, occ)
        _metrics.gauge("serving.pages_free").set(self.allocator.pages_free)
        _metrics.gauge("serving.page_occupancy").set(occ)

    # --- admission-side page management -----------------------------------
    def reserve(self, rows: int, shared: Sequence[int] = ()):
        """Reserve pages for a request spanning ``rows`` total KV rows,
        the first ``len(shared) * page_size`` of which ride the given
        already-allocated pages (ref-bumped — the COW prefix share).
        Returns (pages, table_row): the full virtual-order page list and
        the int32 ``[max_pages]`` row the segment program installs.
        Raises if the pool can't supply — callers gate on
        ``pages_free`` first."""
        total = self.pages_needed(rows)
        shared = list(shared)
        if len(shared) > total:
            raise ValueError(f"{len(shared)} shared pages exceed the "
                             f"{total} the request spans")
        if total > self.max_pages:
            raise ValueError(f"request spans {total} pages > max_pages "
                             f"{self.max_pages}")
        self.allocator.retain(shared)
        try:
            fresh = self.allocator.alloc(total - len(shared))
        except RuntimeError:
            self.allocator.release(shared)
            raise
        pages = shared + fresh
        row = np.zeros((self.max_pages,), np.int32)
        row[:len(pages)] = pages
        _flight.record("page_alloc", pages=len(fresh),
                       shared=len(shared),
                       free=self.allocator.pages_free)
        self._gauges()
        return pages, row

    def install(self, slot: int, pages: List[int]) -> None:
        """Bind a reserved page list to a slot (host mirror only — the
        device table row was installed in-program at the admit event)."""
        self.slot_pages[slot] = list(pages)

    def free_slot(self, slot: int) -> int:
        """Retire a slot: release its page refs (pages shared with the
        prefix cache or other slots survive). Returns pages freed."""
        pages, self.slot_pages[slot] = self.slot_pages[slot], []
        freed = self.allocator.release(pages)
        self._gauges()
        return freed

    def release_pages(self, pages: Sequence[int]) -> int:
        """Undo a reservation that never reached a slot (segment step
        budget ran out and the request was re-queued)."""
        freed = self.allocator.release(pages)
        self._gauges()
        return freed

    # --- copy-on-write ----------------------------------------------------
    def fork_slot(self, src: int, dst: int) -> None:
        """Map ``src``'s pages into ``dst`` (ref bumps, zero copies) —
        the share half of COW. ``dst`` must be empty."""
        self._pages_only("fork_slot")
        if self.slot_pages[dst]:
            raise RuntimeError(f"fork into occupied slot {dst}")
        pages = list(self.slot_pages[src])
        self.allocator.retain(pages)
        self.slot_pages[dst] = pages
        row = np.zeros((self.max_pages,), np.int32)
        row[:len(pages)] = pages
        self.page_table = self.page_table.at[dst].set(jnp.asarray(row))

    def ensure_writable(self, slot: int, vpage: int) -> int:
        """COW break-on-write: if ``slot``'s virtual page ``vpage`` is
        shared (ref > 1), copy its rows into a fresh private page and
        repoint the table — the one place paging ever copies KV rows.
        Returns the (possibly new) physical page id."""
        self._pages_only("ensure_writable")
        page = self.slot_pages[slot][vpage]
        if self.allocator.ref(page) <= 1:
            return page
        new = self.allocator.alloc(1)[0]
        # every pool plane copies at page granularity (K/V rows AND any
        # quantization scale rows, or a state page's S and z — axis 1 is
        # the page axis in all of them)
        self.pool = {n: a.at[:, new].set(a[:, page])
                     for n, a in self.pool.items()}
        self.allocator.release([page])
        self.slot_pages[slot][vpage] = new
        self.page_table = self.page_table.at[slot, vpage].set(new)
        self.cow_breaks += 1
        _metrics.counter("serving.pages.cow_breaks").inc()
        _flight.record("cow_break", slot=slot, vpage=vpage,
                       shared_page=page, private_page=new)
        self._gauges()
        return new

    def _pages_only(self, what: str) -> None:
        if self.fixed_parts:
            raise RuntimeError(
                f"{what}: a sequence of {type(self.cfg).__name__} keeps a "
                f"fixed part beside its pages, and a part is its slot's "
                f"alone: pages can be shared or copied, a sequence cannot")

    # --- lifecycle --------------------------------------------------------
    def reset(self) -> None:
        """Free every slot's pages and zero the device table (pool rows
        stay allocated — table + refcounts make stale rows invisible,
        the paged analog of ``reset_slots``'s pos masking)."""
        for s in range(self.slots):
            if self.slot_pages[s]:
                self.allocator.release(self.slot_pages[s])
                self.slot_pages[s] = []
        table = jnp.zeros((self.slots, self.table_width), jnp.int32)
        if self.mesh is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            table = jax.device_put(table, NamedSharding(self.mesh, P()))
        self.page_table = table
        self.peak_occupancy = 0.0   # warm-run isolation, like reset_slots
        self.allocator.total_allocated = 0
        self._gauges()

    def leak_report(self, expected_held: int = 0) -> List[str]:
        """Allocator invariant + 'everything returned' audit (tests and
        the serving smoke gate): with no live slots and ``expected_held``
        pages legitimately referenced elsewhere (the prefix cache), all
        other pages must be back on the free list."""
        bad = self.allocator.check()
        held = self.allocator.pages_used
        if held != expected_held:
            bad.append(f"{held} pages held, expected {expected_held}")
        if not expected_held and self.fixed_parts_held:
            bad.append(f"{self.fixed_parts_held} fixed parts held by slots "
                       f"with no live request")
        return bad

    def stats(self) -> Dict[str, float]:
        out = {"num_pages": self.num_pages - 1,  # usable (sans trash)
               "page_size": self.page_size,
               "pages_free": self.allocator.pages_free,
               "pages_used": self.allocator.pages_used,
               "occupancy": round(self.occupancy(), 4),
               "peak_occupancy": round(self.peak_occupancy, 4),
               "cow_breaks": self.cow_breaks}
        if self.fixed_parts:
            out.update(fixed_parts=self.fixed_parts - 1,  # sans trash
                       fixed_parts_held=self.fixed_parts_held,
                       fixed_part_bytes=self.fixed_part_bytes)
        return out
