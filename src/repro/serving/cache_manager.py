"""Cache-management layer of the serving API: one ``alloc / write / grow /
evict / restore`` surface over both KV-cache layouts.

The engine used to special-case "contiguous ``slots x max_seq`` pool" vs
"``PagePool`` + page tables" inline at every call site; the two layouts now
sit behind one interface:

    alloc(slot, n_tokens)   all-or-nothing admission hold for a prompt
    write(cache, kv, slot)  traced prefill scatter (called inside jit)
    grow(slot)              back one more decode write (paged: one page)
    evict(slot)             release the slot's residency
    restore(slot, n_pages)  re-hold for a swap-preempted victim

plus the small queries the engine's dispatch loop needs (``backed``,
``has_free``, ``step_extra``, ``prefill_pages``, ``read``) and per-step
pool statistics. The traced paths dispatch through the registry's unified
``decode_cached`` / ``write_cached`` surface, so a manager works for any
family whose cache layout the registry describes.

``ContiguousCacheManager`` is the trivial implementation (every slot
permanently owns ``max_seq`` rows: alloc/grow always succeed, evict is a
no-op). ``PagedCacheManager`` owns the ``PagePool`` bookkeeping and the
trap-padded page vectors the jitted admission consumes. ``CacheConfig`` is
the declarative form (``paged=None`` auto-selects per family) that the
``Engine`` and the ``LLMEngine`` facade resolve with their own
cfg/slots/max_seq.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.models import registry
from repro.serving.paging import PagePool
from repro.serving.radix import RadixCache


class CacheManager:
    """Interface; see module docstring for the contract."""

    paged: bool = False
    prefix_cache: bool = False

    # -- residency (host side) ----------------------------------------------
    def alloc(self, slot: int, n_tokens: int) -> bool:
        raise NotImplementedError

    def grow(self, slot: int) -> bool:
        raise NotImplementedError

    def evict(self, slot: int) -> None:
        raise NotImplementedError

    def restore(self, slot: int, n_pages: int) -> bool:
        raise NotImplementedError

    def infeasible(self, n_tokens: int) -> Optional[str]:
        """Reason a request of ``n_tokens`` can NEVER be admitted by this
        manager (admission validation + the engine's deadlock watchdog),
        or None when it could fit an otherwise-empty pool."""
        return None

    def clear_tree(self) -> int:
        """Crash recovery: drop every radix-tree reference (the cached KV
        died with the device pool). Returns refs dropped; no-op without a
        prefix cache."""
        return 0

    # -- traced (called inside jit) -----------------------------------------
    def init(self):
        """Fresh device cache tree for this layout."""
        raise NotImplementedError

    def write(self, cache, kv, *, slot=None, pages=None):
        """Scatter one request's prefill cache into the pool."""
        raise NotImplementedError

    def decode(self, params, cache, token, pos, page_table=None,
               write_mask=None, kv_len=None):
        """One fused decode step over the pool (traced). ``write_mask``
        (paged only) routes masked rows' K/V writes to the trap page —
        the speculative-decoding verify program rejects draft positions
        through it. ``kv_len`` (paged only, default ``pos + 1``) bounds
        each slot's attention walk."""
        return registry.decode_cached(params, self.cfg, cache, token, pos,
                                      page_table=page_table,
                                      write_mask=write_mask, kv_len=kv_len)

    def read(self, cache, pages):
        """Gather whole pages back into prefill layout (swap-out)."""
        raise NotImplementedError

    # -- dispatch-loop queries ----------------------------------------------
    def backed(self, slot: int, write_pos: int) -> bool:
        """Is ``write_pos`` already storage-backed for ``slot``?"""
        return True

    @property
    def has_free(self) -> bool:
        return True

    def step_extra(self) -> tuple:
        """Per-dispatch host-owned args for the fused step (page table)."""
        return ()

    def prefill_pages(self, slot: int, n_tokens: int,
                      bucket_len: Optional[int]) -> Optional[np.ndarray]:
        """Physical destinations for a prompt's logical pages (trap-padded
        to the bucket so the jit compile key stays the bucket shape);
        None for the contiguous layout."""
        return None

    def pages_of(self, slot: int) -> Optional[np.ndarray]:
        return None

    def note_step(self, rows_by_slot: dict) -> None:
        """Record one dispatch's occupancy (``{slot: written rows}``) for
        utilization stats."""

    def stats(self) -> dict:
        return {"paged": self.paged}


class ContiguousCacheManager(CacheManager):
    """Every slot permanently owns a ``max_seq`` stripe of the pool — the
    historical layout. Residency management degenerates: admission always
    fits, growth never exhausts, eviction frees nothing."""

    paged = False

    def __init__(self, cfg, slots: int, max_seq: int):
        self.cfg, self.slots, self.max_seq = cfg, slots, max_seq

    def init(self):
        cache, _ = registry.init_cache(self.cfg, self.slots, self.max_seq)
        return cache

    def alloc(self, slot: int, n_tokens: int) -> bool:
        return True

    def grow(self, slot: int) -> bool:
        return True

    def evict(self, slot: int) -> None:
        pass

    def restore(self, slot: int, n_pages: int) -> bool:
        return True

    def write(self, cache, kv, *, slot=None, pages=None):
        return registry.write_cached(self.cfg, cache, kv, slot=slot,
                                     max_seq=self.max_seq)

    def read(self, cache, pages):
        raise NotImplementedError("contiguous slots are never swapped out")


class PagedCacheManager(CacheManager):
    """SGLang/vLLM-style paged layout: a global ``[num_pages + 1,
    page_size, ...]`` block pool (physical page 0 is the trap page) plus
    per-slot page tables. ``num_pages`` below ``slots * max_seq /
    page_size`` oversubscribes: ``alloc``/``grow`` then report exhaustion
    and the engine preempts."""

    paged = True

    def __init__(self, cfg, slots: int, max_seq: int, *,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 prefix_cache: bool = True):
        if not registry.paged_ok(cfg):
            raise ValueError(f"family {cfg.family!r} (window={cfg.window}) "
                             "cannot serve from a paged pool")
        if max_seq % page_size:
            raise ValueError(f"page_size={page_size} must divide "
                             f"max_seq={max_seq} (the gathered logical "
                             "cache must tile exactly)")
        self.cfg, self.slots, self.max_seq = cfg, slots, max_seq
        self.page_size = page_size
        self.pages_per_slot = max_seq // page_size
        if num_pages is None:
            num_pages = slots * self.pages_per_slot   # full subscription
        self.num_pages = num_pages
        self.pool = PagePool(num_pages, page_size, slots,
                             self.pages_per_slot)
        self.prefix_cache = bool(prefix_cache) \
            and registry.prefix_cache_ok(cfg)
        self.tree = RadixCache(page_size) if self.prefix_cache else None
        self._peak = 0
        self._util_sum = 0.0
        self._frag_sum = 0.0
        self._steps = 0
        self._hit_tokens = 0
        self._query_tokens = 0
        self._cow_copies = 0
        self._tree_evictions = 0

    def init(self):
        # +1: physical page 0 is the trap page (see repro.serving.paging)
        cache, _ = registry.init_paged_cache(self.cfg, self.num_pages + 1,
                                             self.page_size)
        return cache

    # -- residency ----------------------------------------------------------
    def _n_pages(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def _reserve(self, slot: int, n: int) -> bool:
        """``alloc_n`` that reclaims radix-tree pages on demand: the tree
        is a cache, so its unpinned leaves are logically free. Keeping
        this inside every allocation path preserves the capacity the
        engine saw before prefix caching existed."""
        if len(self.pool.owned[slot]) + n > self.pool.pages_per_slot:
            return False
        need = n - self.pool.num_free
        if need > 0:
            if self.tree is None:
                return False
            self._tree_evictions += self.tree.evict(need, self.pool)
            if self.pool.num_free < n:
                return False
        return self.pool.alloc_n(slot, n)

    def alloc(self, slot: int, n_tokens: int) -> bool:
        return self._reserve(slot, self._n_pages(n_tokens))

    def grow(self, slot: int) -> bool:
        return self._reserve(slot, 1)

    def evict(self, slot: int) -> None:
        self.pool.release(slot)

    def restore(self, slot: int, n_pages: int) -> bool:
        return self._reserve(slot, n_pages)

    def infeasible(self, n_tokens: int) -> Optional[str]:
        limit = min(self.pool.pages_per_slot, self.num_pages)
        n = self._n_pages(n_tokens)
        if n > limit:
            return (f"prompt needs {n} pages of {self.page_size} but the "
                    f"pool can hold at most {limit} per request")
        return None

    def clear_tree(self) -> int:
        if self.tree is None:
            return 0
        return self.tree.clear(self.pool)

    # -- radix prefix cache -------------------------------------------------
    def admit_prompt(self, slot: int, tokens) -> Optional[dict]:
        """Radix-aware admission hold for a token prompt: map the longest
        cached page-aligned prefix read-only into ``slot``, reserve
        private pages for the rest, and describe what the engine must
        prefill. Returns None (nothing changed) when the pool cannot hold
        the request; otherwise::

            {"n_cached": k,            # tree pages mapped read-only
             "suffix_start": s,        # prefill starts at this position
             "cow": (src, dst) | None} # device page copy to issue first

        A *full*-prompt match would leave the next decode write landing in
        the final shared page, so that page is copy-on-write duplicated up
        front and its tokens re-prefilled (``suffix_start`` backs up one
        page) — the fused decode step then never sees a shared write."""
        n = len(tokens)
        n_total = self._n_pages(n)
        if not self.prefix_cache:
            return {"n_cached": 0, "suffix_start": 0, "cow": None} \
                if self._reserve(slot, n_total) else None
        matched = self.tree.match(tokens)
        k = min(len(matched), n // self.page_size)
        if k == 0:
            if not self._reserve(slot, n_total):
                return None
            self._query_tokens += n
            return {"n_cached": 0, "suffix_start": 0, "cow": None}
        self.pool.map_shared(slot, matched[:k])
        if not self._reserve(slot, n_total - k):
            self.pool.release(slot)       # tree refs keep the pages alive
            return None
        cow = None
        suffix_start = k * self.page_size
        if suffix_start == n:             # whole prompt cached
            if not self.pool.num_free:
                if self.tree.evict(1, self.pool) < 1:
                    self.pool.release(slot)
                    return None
                self._tree_evictions += 1
            cow = self.pool.cow(slot, k - 1)
            self._cow_copies += 1
            suffix_start = (k - 1) * self.page_size
        self._hit_tokens += suffix_start
        self._query_tokens += n
        return {"n_cached": k, "suffix_start": suffix_start, "cow": cow}

    def insert_prompt(self, slot: int, tokens, coverage: int) -> None:
        """Record ``slot``'s already-written full pages in the tree.
        ``coverage`` caps how many positions hold valid KV (a request's
        final emitted token never writes its KV row, and overlap means
        later positions may hold garbage)."""
        if not self.prefix_cache:
            return
        n_full = coverage // self.page_size
        if n_full <= 0:
            return
        self.tree.insert(tokens[:n_full * self.page_size],
                         self.pool.owned[slot][:n_full], self.pool)

    def prefix_page_vec(self, slot: int, suffix_start: int) -> np.ndarray:
        """Fixed-size (pages_per_slot) trap-padded physical page vector of
        the mapped prefix — fixed shape so the suffix-prefill compile key
        stays the suffix bucket only."""
        pages = np.zeros((self.pages_per_slot,), np.int32)
        k0 = suffix_start // self.page_size
        pages[:k0] = self.pool.owned[slot][:k0]
        return pages

    def suffix_pages(self, slot: int, suffix_start: int, n_tokens: int,
                     bucket_len: Optional[int]) -> np.ndarray:
        """Physical destinations for the suffix's logical pages,
        trap-padded to the suffix bucket (cf. ``prefill_pages``)."""
        k0 = suffix_start // self.page_size
        n_real = self._n_pages(n_tokens) - k0
        plen = bucket_len if bucket_len is not None \
            else n_tokens - suffix_start
        pages = np.zeros((max(1, self._n_pages(plen)),), np.int32)
        pages[:n_real] = self.pool.owned[slot][k0:]
        return pages

    # -- traced -------------------------------------------------------------
    def write(self, cache, kv, *, slot=None, pages=None):
        return registry.write_cached(self.cfg, cache, kv, pages=pages,
                                     page_size=self.page_size)

    def read(self, cache, pages):
        return registry.read_pages(self.cfg, cache, pages, self.page_size)

    # -- dispatch-loop queries ----------------------------------------------
    def backed(self, slot: int, write_pos: int) -> bool:
        return write_pos // self.page_size < len(self.pool.owned[slot])

    @property
    def has_free(self) -> bool:
        if self.pool.num_free > 0:
            return True
        return self.tree is not None and self.tree.has_evictable(self.pool)

    def step_extra(self) -> tuple:
        return (self.pool.table,)

    def prefill_pages(self, slot: int, n_tokens: int,
                      bucket_len: Optional[int]) -> np.ndarray:
        n_real = self._n_pages(n_tokens)
        plen = bucket_len if bucket_len is not None else n_tokens
        b_pages = max(1, self._n_pages(plen))
        pages = np.zeros((b_pages,), np.int32)        # bucket tail -> trap
        pages[:n_real] = self.pool.owned[slot]
        return pages

    def pages_of(self, slot: int) -> np.ndarray:
        return np.asarray(self.pool.owned[slot], np.int32)

    def note_step(self, rows_by_slot: dict) -> None:
        in_use = self.pool.pages_in_use
        self._steps += 1
        self._peak = max(self._peak, in_use)
        self._util_sum += in_use / self.num_pages
        # internal fragmentation over *privately written* pages only:
        # read-only shared prefix pages are full by definition and would
        # skew the allocated-but-unwritten ratio low
        ps = self.page_size
        alloc_rows = used = 0
        for slot, rows in rows_by_slot.items():
            shared = self.pool.shared[slot]
            for idx, page in enumerate(self.pool.owned[slot]):
                if page in shared:
                    continue
                alloc_rows += ps
                used += max(0, min(rows - idx * ps, ps))
        if alloc_rows:
            self._frag_sum += 1.0 - min(used, alloc_rows) / alloc_rows

    def stats(self) -> dict:
        steps = max(self._steps, 1)
        out = {
            "paged": True,
            "page_size": self.page_size,
            "num_pages": self.num_pages,
            "peak_pages_in_use": self._peak,
            # time-averaged pool occupancy and internal fragmentation
            # (allocated-but-unwritten rows / allocated private rows)
            "page_util_mean": self._util_sum / steps,
            "page_frag_mean": self._frag_sum / steps,
            "prefix_cache": self.prefix_cache,
        }
        if self.prefix_cache:
            out.update({
                "prefix_hit_tokens": self._hit_tokens,
                "prefix_query_tokens": self._query_tokens,
                "prefix_hit_rate":
                    self._hit_tokens / max(self._query_tokens, 1),
                "cow_copies": self._cow_copies,
                "tree_evictions": self._tree_evictions,
                "tree_pages": self.tree.n_pages,
            })
        return out


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    """Declarative cache-manager choice, resolved against the engine's
    (cfg, slots, max_seq). ``paged=None`` auto-selects: paged when the
    family supports it (``registry.paged_ok``), contiguous otherwise.
    ``num_pages=None`` fully subscribes; fewer oversubscribes.
    ``prefix_cache`` enables radix prefix caching on paged managers whose
    family supports it (``registry.prefix_cache_ok``); elsewhere it is
    silently inert."""
    paged: Optional[bool] = None
    page_size: int = 16
    num_pages: Optional[int] = None
    prefix_cache: bool = True

    def build(self, cfg, slots: int, max_seq: int) -> CacheManager:
        paged = registry.paged_ok(cfg) if self.paged is None else self.paged
        if self.paged and not registry.paged_ok(cfg):
            raise ValueError(f"family {cfg.family!r} (window={cfg.window}) "
                             "cannot serve from a paged pool")
        if paged:
            return PagedCacheManager(cfg, slots, max_seq,
                                     page_size=self.page_size,
                                     num_pages=self.num_pages,
                                     prefix_cache=self.prefix_cache)
        return ContiguousCacheManager(cfg, slots, max_seq)


def make_cache_manager(spec, cfg, slots: int, max_seq: int) -> CacheManager:
    """Resolve ``None`` (auto), a ``CacheConfig``, or a ready instance."""
    if spec is None:
        spec = CacheConfig()
    if isinstance(spec, CacheConfig):
        return spec.build(cfg, slots, max_seq)
    return spec
