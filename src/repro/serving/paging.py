"""Host-side page allocator for the paged KV pool.

The device holds one global ``[num_pages + 1, page_size, ...]`` block pool
per cache leaf; this class owns the host bookkeeping: which physical pages
are free, which slot owns which pages, and the per-slot page tables the
jitted decode step reads each dispatch.

Physical page 0 is a reserved **trap page**: it is never allocated, and
every unassigned page-table entry points at it. The fused decode step
writes the new token's K/V for *every* pool slot (masked slots included —
exactly like the contiguous engine's unconditional scatter), so a slot
whose request finished or was preempted keeps scribbling somewhere until
it is re-admitted; routing those writes into the trap page is what makes
freeing + reusing a victim's pages safe while the victim's slot is still
being dispatched. Trap contents are garbage by design and are only ever
reachable through masked (``>= kv_len``) positions.

Pages are **refcounted** so the radix prefix cache can share them: a page's
refcount is the number of slot page-table entries mapping it plus its
external (radix-tree) references. ``alloc``/``alloc_n`` hand out private
pages (refcount 1); ``map_shared`` maps already-live pages read-only into
another slot's table; ``retain``/``drop`` manage the tree's external refs;
``cow`` repoints one table entry at a fresh private copy (the device-side
page copy is the caller's job). A page returns to the free list exactly
when its refcount hits zero, so ``release`` doubles as rollback for a
partially built mapping.

Allocation is a LIFO free stack (deterministic: benchmark streams and
goldens must not depend on allocator ordering noise). ``check()`` asserts
the structural invariants — refcounts equal mapping + external counts,
free pages have refcount zero, trap never referenced — and is called from
the allocator unit tests and the hypothesis state machine.
"""

from __future__ import annotations

import numpy as np

TRAP_PAGE = 0


class PagePool:
    """Refcounted physical-page allocator behind the paged KV cache."""

    def __init__(self, num_pages: int, page_size: int, slots: int,
                 pages_per_slot: int):
        if num_pages < pages_per_slot:
            raise ValueError(
                f"num_pages={num_pages} cannot hold even one full-length "
                f"request ({pages_per_slot} pages of {page_size}); the "
                f"engine could deadlock on an empty pool")
        self.num_pages = num_pages          # usable (excludes the trap page)
        self.page_size = page_size
        self.pages_per_slot = pages_per_slot
        # physical ids are 1..num_pages; pop() hands out ascending ids first
        self._free = list(range(num_pages, 0, -1))
        self.owned: list[list[int]] = [[] for _ in range(slots)]
        # physical ids of pages this slot maps but does not exclusively own
        # (read-only prefix pages); decode must never write these in place
        self.shared: list[set[int]] = [set() for _ in range(slots)]
        # refcnt[p] = (# table entries mapping p) + ext[p]; index 0 = trap
        self.refcnt = [0] * (num_pages + 1)
        self._ext = [0] * (num_pages + 1)   # radix-tree references
        # device-facing tables; row = slot, entry = physical page (0 = trap)
        self.table = np.full((slots, pages_per_slot), TRAP_PAGE, np.int32)

    # -- allocation ---------------------------------------------------------

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free)

    def alloc(self, slot: int) -> bool:
        """Grow ``slot`` by one private page; False when exhausted."""
        if not self._free:
            return False
        i = len(self.owned[slot])
        if i >= self.pages_per_slot:
            raise RuntimeError(f"slot {slot} already holds its max "
                               f"{self.pages_per_slot} pages")
        page = self._free.pop()
        self.refcnt[page] = 1
        self.owned[slot].append(page)
        self.table[slot, i] = page
        return True

    def alloc_n(self, slot: int, n: int) -> bool:
        """All-or-nothing: grow ``slot`` by ``n`` pages or change nothing."""
        if n > len(self._free) or len(self.owned[slot]) + n \
                > self.pages_per_slot:
            return False
        for _ in range(n):
            self.alloc(slot)
        return True

    def map_shared(self, slot: int, pages: list[int]) -> None:
        """Append already-live ``pages`` read-only to ``slot``'s table.

        The pages keep their existing owners (the radix tree and possibly
        other slots); this only adds mapping refs. Capacity overflow is a
        caller bug (admission sizes the mapping), hence RuntimeError."""
        if len(self.owned[slot]) + len(pages) > self.pages_per_slot:
            raise RuntimeError(f"slot {slot} cannot map {len(pages)} more "
                               f"pages (max {self.pages_per_slot})")
        for page in pages:
            assert page != TRAP_PAGE and self.refcnt[page] >= 1, \
                f"map_shared of dead page {page}"
            i = len(self.owned[slot])
            self.refcnt[page] += 1
            self.owned[slot].append(page)
            self.shared[slot].add(page)
            self.table[slot, i] = page

    def retain(self, page: int) -> None:
        """Add one external (radix-tree) reference to a live page."""
        assert page != TRAP_PAGE and self.refcnt[page] >= 1, \
            f"retain of dead page {page}"
        self._ext[page] += 1
        self.refcnt[page] += 1

    def drop(self, page: int) -> None:
        """Drop one external reference; frees the page at refcount zero."""
        assert self._ext[page] >= 1, f"drop of unretained page {page}"
        self._ext[page] -= 1
        self.refcnt[page] -= 1
        if self.refcnt[page] == 0:
            self._free.append(page)

    def cow(self, slot: int, idx: int) -> tuple[int, int]:
        """Copy-on-write: repoint ``slot``'s table entry ``idx`` (currently
        a shared page) at a fresh private page. Returns ``(src, dst)`` so
        the caller can issue the device page copy. The caller must ensure
        a free page exists (evicting the tree if necessary)."""
        old = self.owned[slot][idx]
        assert old in self.shared[slot], f"cow of private page {old}"
        assert self._free, "cow with no free page (caller must evict first)"
        new = self._free.pop()
        self.refcnt[new] = 1
        self.owned[slot][idx] = new
        self.table[slot, idx] = new
        self.shared[slot].discard(old)
        self.refcnt[old] -= 1
        if self.refcnt[old] == 0:
            self._free.append(old)
        return old, new

    def release(self, slot: int) -> None:
        """Drop every mapping ``slot`` holds; pages whose refcount hits
        zero return to the free list (shared prefix pages survive through
        their tree refs). The table row reverts to trap."""
        while self.owned[slot]:
            page = self.owned[slot].pop()
            self.refcnt[page] -= 1
            if self.refcnt[page] == 0:
                self._free.append(page)
        self.shared[slot].clear()
        self.table[slot, :] = TRAP_PAGE

    # -- chaos hooks --------------------------------------------------------

    def seize_free(self, n: int) -> list[int]:
        """Pull up to ``n`` pages off the free list and pin them with an
        external ref (the chaos harness's page-pool-exhaustion fault).
        Seized pages look exactly like tree-retained pages to every
        invariant, so ``check()`` keeps holding while the hold is live.
        Returns the seized page ids (possibly fewer than ``n``)."""
        pages = []
        for _ in range(min(n, len(self._free))):
            page = self._free.pop()
            self.refcnt[page] = 1
            self._ext[page] = 1
            pages.append(page)
        return pages

    def release_seized(self, pages: list[int]) -> None:
        """End a ``seize_free`` hold: drop the external pins."""
        for page in pages:
            self.drop(page)

    # -- invariants ---------------------------------------------------------

    def check(self) -> None:
        """Structural + refcount invariants; raises AssertionError."""
        all_owned = [p for pages in self.owned for p in pages]
        assert TRAP_PAGE not in all_owned, "trap page allocated"
        assert self.refcnt[TRAP_PAGE] == 0 and self._ext[TRAP_PAGE] == 0, \
            "trap page referenced"
        assert len(self._free) == len(set(self._free)), "free-list duplicate"
        maps = {}                      # page -> number of table mappings
        for slot, pages in enumerate(self.owned):
            assert len(pages) == len(set(pages)), \
                f"slot {slot} maps a page twice"
            assert self.shared[slot] <= set(pages), \
                f"slot {slot} shared set not within owned"
            for p in pages:
                maps[p] = maps.get(p, 0) + 1
            row = self.table[slot]
            assert list(row[:len(pages)]) == pages, "table/owned mismatch"
            assert (row[len(pages):] == TRAP_PAGE).all(), \
                "stale table entry past owned prefix"
        for p in range(1, self.num_pages + 1):
            assert self._ext[p] >= 0, f"negative ext count on page {p}"
            assert self.refcnt[p] == maps.get(p, 0) + self._ext[p], \
                f"refcnt mismatch on page {p}"
            assert (p in set(self._free)) == (self.refcnt[p] == 0), \
                f"free/refcnt disagreement on page {p}"
        for p, n in maps.items():
            if n >= 2:
                # the original allocator may keep the page "private" (it
                # wrote it once during prefill and never writes it again);
                # every later mapper must treat it read-only
                private = sum(1 for slot, pages in enumerate(self.owned)
                              if p in pages and p not in self.shared[slot])
                assert private <= 1, \
                    f"page {p} mapped writable by {private} slots"
        assert len(set(self._free)) \
            + sum(1 for p in range(1, self.num_pages + 1)
                  if self.refcnt[p] > 0) == self.num_pages, \
            "pages leaked or duplicated"
