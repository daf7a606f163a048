"""Block-paged KV-cache bookkeeping: free-list pages + prefix sharing.

The continuous-batching scheduler's capacity unit used to be a cache
ROW (one contiguous ``max_len`` strip per slot), so a 12-token request
reserved the same HBM as a 4096-token one.  This module is the host
side of the paged redesign (the vLLM PagedAttention idea, built on the
repo's own decode stack):

* :class:`PageAllocator` — a free list over ``num_pages`` fixed-size
  cache pages.  A slot owns a *page list* instead of a row; capacity is
  **tokens actually held**, not rows provisioned.  Double-free raises:
  a page returned twice would be handed to two slots at once — the
  aliasing hazard graftlint's ``page-aliasing`` rule exists for.
* :class:`PrefixCache` — refcounted, read-only shared pages keyed by a
  **chained content hash** of page-aligned token prefixes.  Two prompts
  that share their first ``k * page_size`` tokens share the same
  physical K/V pages for them; the shared system prompt at consumer
  traffic is prefilled ONCE and every later request attaches read-only
  (its continuation diverges into freshly-allocated private pages — the
  copy-on-write point — while the shared page bytes stay untouched).
  Pages are released back to the allocator only when the last reader
  has evicted AND the entry is reclaimed under memory pressure
  (:meth:`PrefixCache.evict_for`), so a hot prefix survives between
  requests.

Everything here is host bookkeeping for the single scheduler thread —
no locks, no device arrays.  The device half (page-table gather/scatter
attention) lives in ``nn/attention.py::apply_decode_pages``; see
docs/serving.md for the page lifecycle diagram.

A page id is an index into AXIS 0 of every device pool, and the only
thing this module knows of them.  A pool is ``(num_pages + 1,
page_size, W)``: page, token in page, width — a token's K (or V) of
every KV head side by side, ``W`` padded to whole 128-lane tiles
(``ops.attention.paged_pool_width``; ``paged_pool_dims`` reads a pool's
page size and width).  The extra last page, id ``num_pages``, is the
trash page.  Sharing a prefix page, parking it to the host and
resuming it move whole ``page_size x W`` blocks by their ids.
"""

from __future__ import annotations

import hashlib
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


class PageAllocator:
    """Free-list allocator over ``num_pages`` fixed-size cache pages.

    Page ids are ``0 .. num_pages-1``; id ``num_pages`` is the
    **trash page** — the extra pool row every unallocated page-table
    slot points at, so an in-graph write past a slot's allocation (or
    by an inactive row) lands somewhere harmless instead of clamping
    into a neighbor's page.  The trash page is never allocated and its
    contents are never read at a valid attention position.
    """

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got {num_pages}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        # pop() -> lowest id first, like SlotManager's slot order
        self._free = list(range(num_pages - 1, -1, -1))
        self._live = [False] * num_pages

    @property
    def trash(self) -> int:
        return self.num_pages

    @property
    def capacity_tokens(self) -> int:
        return self.num_pages * self.page_size

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return self.num_pages - len(self._free)

    def pages_for(self, tokens: int) -> int:
        """Pages needed to hold ``tokens`` cache positions."""
        return max(1, -(-int(tokens) // self.page_size))

    def alloc(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` pages all-or-nothing; None when the free list
        is short (the caller decides: evict the prefix cache, hold the
        request back, or shed typed)."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} pages")
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._live[p] = True
        return out

    def free(self, pages: Sequence[int]) -> None:
        """Return pages to the free list.  A double free raises — the
        freed page may already be another slot's (the aliasing bug
        class this subsystem must never have)."""
        for p in pages:
            if not 0 <= p < self.num_pages:
                raise ValueError(f"page id {p} out of range "
                                 f"[0, {self.num_pages})")
            if not self._live[p]:
                raise ValueError(
                    f"double free of page {p}: it is already on the "
                    "free list and may have been re-allocated to a "
                    "live slot — freeing it again would alias two "
                    "slots onto one page")
            self._live[p] = False
            self._free.append(p)


class _PrefixEntry:
    """One shared page at one chain depth: ``key`` is the chained
    content hash of the page-aligned prefix ending at this page."""

    __slots__ = ("key", "page", "parent", "children", "refs", "tick")

    def __init__(self, key: str, page: int, parent: Optional[str]):
        self.key = key
        self.page = page
        self.parent = parent
        self.children = 0
        self.refs = 0
        self.tick = 0


class PrefixCache:
    """Content-hash prefix cache: chain-keyed, refcounted, read-only.

    Keying: page ``i`` of a prompt is addressed by
    ``key_i = sha1(key_{i-1} || tokens[i*ps : (i+1)*ps])`` — the hash
    chain makes a page's identity depend on its ENTIRE prefix, so two
    prompts share page ``i`` iff their first ``(i+1)*ps`` tokens are
    identical.  Only FULL pages are ever shared (a partial page's K/V
    would be extended in place by the reader — a write to a shared
    page); the partial remainder re-prefills into the reader's first
    private page, which is where copy-on-write divergence lands.

    Refcounting: a reader ``acquire()``s every entry on its chain and
    ``release()``s them at evict.  Entries with ``refs == 0`` stay
    cached (that is the point — the next request hits them) until
    :meth:`evict_for` reclaims leaf-first under allocator pressure.
    """

    def __init__(self, page_size: int):
        self.page_size = int(page_size)
        self._entries: Dict[str, _PrefixEntry] = {}
        self._tick = itertools.count(1)
        # census counters (the ledger/metrics figures)
        self.lookup_pages = 0
        self.hit_pages = 0
        self.inserted_pages = 0
        self.evicted_pages = 0

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def held_pages(self) -> int:
        return len(self._entries)

    # -- keying --------------------------------------------------------------

    def chain_keys(self, prompt: np.ndarray) -> List[str]:
        """Chained content-hash key per FULL page of ``prompt``."""
        ps = self.page_size
        toks = np.asarray(prompt, np.int32).reshape(-1)
        keys: List[str] = []
        parent = b""
        for i in range(len(toks) // ps):
            h = hashlib.sha1(parent + toks[i * ps:(i + 1) * ps].tobytes())
            keys.append(h.hexdigest())
            parent = keys[-1].encode("ascii")
        return keys

    # -- read side -----------------------------------------------------------

    def lookup(self, keys: Sequence[str]) -> Tuple[int, List[int]]:
        """Longest cached chain prefix of ``keys``:
        ``(depth, page ids)``.  Counts toward the hit-rate census."""
        depth, pages = 0, []
        for k in keys:
            e = self._entries.get(k)
            if e is None:
                break
            pages.append(e.page)
            depth += 1
        self.lookup_pages += len(keys)
        self.hit_pages += depth
        return depth, pages

    def acquire(self, keys: Sequence[str]) -> None:
        """Attach a reader to every entry on the chain (refcount++)."""
        tick = next(self._tick)
        for k in keys:
            e = self._entries[k]
            e.refs += 1
            e.tick = tick

    def release(self, keys: Sequence[str]) -> None:
        """Detach a reader (refcount--).  Pages stay cached for the
        next hit; only :meth:`evict_for` returns them to the
        allocator."""
        for k in keys:
            e = self._entries.get(k)
            if e is None:        # chain already evicted mid-flight: no
                continue         # reader held it, nothing to release
            if e.refs <= 0:
                raise ValueError(
                    f"release of prefix page {e.page} with no readers "
                    "(refcount underflow)")
            e.refs -= 1

    # -- write side ----------------------------------------------------------

    def insert(self, keys: Sequence[str], pages: Sequence[int],
               depth_known: int) -> None:
        """Publish a prompt's freshly-prefilled full pages.  ``keys``
        is the whole chain; entries ``[0, depth_known)`` already exist
        (the reader found them via :meth:`lookup`); ``pages[i]`` for
        ``i >= depth_known`` transfer OWNERSHIP from the inserting slot
        to the cache — the slot keeps reading them (it must
        ``acquire()`` the chain) but no longer frees them at evict."""
        for i in range(depth_known, len(keys)):
            if keys[i] in self._entries:
                raise ValueError(f"prefix entry at depth {i} already "
                                 "cached — lookup/insert raced")
            parent = keys[i - 1] if i > 0 else None
            self._entries[keys[i]] = _PrefixEntry(keys[i], pages[i],
                                                  parent)
            if parent is not None:
                self._entries[parent].children += 1
            self.inserted_pages += 1

    # -- memory pressure -----------------------------------------------------

    def evict_for(self, n: int, allocator: PageAllocator) -> int:
        """Reclaim up to ``n`` pages from unreferenced leaf entries
        (LRU first), returning them to ``allocator``.  An entry is
        evictable iff no reader holds it AND no longer chain extends
        it; evicting a leaf can make its parent a leaf, so the scan
        repeats until satisfied or nothing is evictable."""
        freed = 0
        while freed < n:
            leaves = [e for e in self._entries.values()
                      if e.refs == 0 and e.children == 0]
            if not leaves:
                break
            leaves.sort(key=lambda e: e.tick)
            for e in leaves:
                del self._entries[e.key]
                if e.parent is not None and e.parent in self._entries:
                    self._entries[e.parent].children -= 1
                allocator.free([e.page])
                self.evicted_pages += 1
                freed += 1
                if freed >= n:
                    break
        return freed

    def stats(self) -> dict:
        return {
            "entries": len(self._entries),
            "lookup_pages": self.lookup_pages,
            "hit_pages": self.hit_pages,
            "hit_rate": (self.hit_pages / self.lookup_pages
                         if self.lookup_pages else 0.0),
            "inserted_pages": self.inserted_pages,
            "evicted_pages": self.evicted_pages,
        }


class HostOffloadTier:
    """Host-RAM parking lot for idle sessions' private KV pages (r20).

    Paged KV makes a sequence a page list, so parking is mechanical: a
    D2H gather of the session's PRIVATE pages (shared prefix pages stay
    on-device, refcount-pinned by the parked session — another reader
    may be attending into them right now) plus a page-table swap to the
    trash page; resume is H2D scatter into freshly-allocated pages plus
    re-attach.  Page contents are position-addressed through the table
    and copied verbatim both ways, so a resumed session is bit-equal to
    one that never parked.

    This class is the host side only — storage and byte accounting.
    The device copies live in the scheduler (it owns the cache arrays
    and the single-threaded page table); everything here is plain
    numpy + dict bookkeeping, called from that one scheduler thread.
    """

    def __init__(self):
        self._parked: Dict[str, tuple] = {}   # sid -> (payload, nbytes)
        # census counters (the mem.offload ledger / run-report figures)
        self.parks = 0
        self.resumes = 0
        self.parked_bytes = 0
        self.peak_parked_bytes = 0

    def __len__(self) -> int:
        return len(self._parked)

    def __contains__(self, sid: str) -> bool:
        return sid in self._parked

    def park(self, sid: str, payload, nbytes: int) -> None:
        """Store ``payload`` (the scheduler's host copy of the
        session's private pages) under ``sid``.  Double-park raises —
        it would leak the first copy and hints the page table was
        swapped twice."""
        if sid in self._parked:
            raise ValueError(f"session {sid!r} is already parked")
        nbytes = int(nbytes)
        self._parked[sid] = (payload, nbytes)
        self.parks += 1
        self.parked_bytes += nbytes
        self.peak_parked_bytes = max(self.peak_parked_bytes,
                                     self.parked_bytes)

    def resume(self, sid: str):
        """Pop and return ``sid``'s parked payload for the H2D
        restore.  Unknown sid raises — resuming a session that was
        never parked (or already resumed) is a lifecycle bug."""
        if sid not in self._parked:
            raise KeyError(f"session {sid!r} is not parked")
        payload, nbytes = self._parked.pop(sid)
        self.resumes += 1
        self.parked_bytes -= nbytes
        return payload

    def drop(self, sid: str) -> int:
        """Discard a parked session's pages (session closed while
        parked); returns the bytes released.  Unknown sid is a no-op
        zero — close is idempotent."""
        if sid not in self._parked:
            return 0
        _, nbytes = self._parked.pop(sid)
        self.parked_bytes -= nbytes
        return nbytes

    def stats(self) -> dict:
        return {
            "parked_sessions": len(self._parked),
            "parks": self.parks,
            "resumes": self.resumes,
            "parked_bytes": self.parked_bytes,
            "peak_parked_bytes": self.peak_parked_bytes,
        }
