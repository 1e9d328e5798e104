"""Host-side paged-KV allocator: block tables, refcounts, prefix cache.

The engine's KV cache is a pool of fixed-size pages (one combined FLAT
{"kv": [L*P, page, 2*Kv, h]} array with K/V interleaved on the head
axis; layer l owns rows [l*P, (l+1)*P) and the forward adds the l*P
offset in-graph — `models/llama.py::init_paged_cache`). This module
owns the *host* bookkeeping in LOGICAL pages 0..P-1 (layer-agnostic):
which pages are free, which are referenced by live slots, and which
hold content-addressed full pages reusable as shared prefixes across
slots (the cross-slot upgrade over round 1's
slot-local prefix cache — ref VERDICT.md item 2; the reference gets
this from vLLM's paged attention + prefix caching, which its operator
orchestrates but never implements: charts/kubeai/values.yaml:39-56).

Design:
- **Page 0 is the trash page** — never allocated. Block-table entries
  default to 0, so padded prefill positions and post-finish decode
  overruns scatter harmlessly into it instead of corrupting live pages.
- **Content addressing** is an exact chain digest: sha256 over
  (parent_digest, page tokens, adapter signature). Exact means a hit
  guarantees identical full context — no hash-collision aliasing.
- **Full pages only** are shared. The first partial page of any
  sequence is always private, so shared pages are never written
  (causally: KV of positions [i*page, (i+1)*page) depends only on
  tokens < (i+1)*page, which the digest pins). No copy-on-write needed.
- **Eviction**: pages with refcount 0 but registered content stay in
  an LRU "cached" set and satisfy future prefix hits; allocation evicts
  the LRU cached page when the free list is empty.
- **Parked pages** (engine/kvstate.py): a preempted or handed-off
  request's pages stay device-resident, pinned by the park entry's own
  reference, so a same-replica restore skips the host->device payload
  upload. Parked pages are RECLAIMABLE, not pressure: they count toward
  available() and are excluded from used() — the occupancy gauge and the
  decode_occupancy autoscaling signal must not read parked state as live
  KV demand (the host blob remains authoritative; a reclaimed park
  degrades to the upload path, and a lost blob degrades to replay).
  Allocation evicts whole park entries LRU after the cached set is dry.

Thread model: called only from the engine scheduler thread.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict


def pages_for(n_tokens: int, page_size: int) -> int:
    """Pages needed to hold n_tokens."""
    return -(-n_tokens // page_size)


class PagePool:
    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        self.num_pages = num_pages
        self.page_size = page_size
        # LIFO free stack (page 0 reserved as trash).
        self._free: list[int] = list(range(num_pages - 1, 0, -1))
        self._ref = [0] * num_pages
        # Content-addressed full pages: digest -> page, page -> digest.
        self._by_digest: dict[bytes, int] = {}
        self._digest_of: dict[int, bytes] = {}
        # refcount-0 pages with registered content, LRU order (oldest
        # first); values unused.
        self._cached: "OrderedDict[int, None]" = OrderedDict()
        # Cumulative cached-page evictions (allocation pressure pushing
        # reusable prefixes out). Plain int — this module stays
        # dependency-free; the engine mirrors it into
        # kubeai_engine_kv_cached_evictions_total from the scheduler
        # loop (same poll discipline as the jit-recompile counter).
        self.evictions = 0
        # Parked page rows (key -> pages, LRU oldest first): each entry
        # pins ONE reference per page, transferred from the slot that
        # parked it. park_evictions counts entries reclaimed under
        # allocation pressure (restore then falls back to the blob).
        self._parked: "OrderedDict[str, list[int]]" = OrderedDict()
        self._parked_pages: dict[int, str] = {}
        self.park_evictions = 0

    # -- capacity ----------------------------------------------------------

    def available(self) -> int:
        """Pages allocatable right now (free + evictable). Parked pages
        count as available only while the park holds their SOLE
        reference — a parked page also claimed as a shared prefix by a
        live slot is real pressure until that slot releases it."""
        return len(self._free) + len(self._cached) + sum(
            1 for p in self._parked_pages if self._ref[p] == 1
        )

    def used(self) -> int:
        return self.num_pages - 1 - self.available()

    def cached_pages(self) -> int:
        return len(self._cached)

    def parked_pages(self) -> int:
        return len(self._parked_pages)

    def is_parked(self, page: int) -> bool:
        return page in self._parked_pages

    def parked_keys(self) -> list[str]:
        return list(self._parked)

    # -- digests -----------------------------------------------------------

    @staticmethod
    def _digest(parent: bytes, tokens: list[int], sig) -> bytes:
        h = hashlib.sha256(parent)
        h.update(repr(sig).encode())
        h.update(b"|")
        h.update(",".join(map(str, tokens)).encode())
        return h.digest()

    def chain_digests(self, token_ids: list[int], sig) -> list[bytes]:
        """Digest per FULL page of token_ids (partial tail excluded)."""
        ps = self.page_size
        out = []
        parent = b""
        for i in range(len(token_ids) // ps):
            parent = self._digest(parent, token_ids[i * ps : (i + 1) * ps], sig)
            out.append(parent)
        return out

    # -- prefix matching ---------------------------------------------------

    def match_prefix(self, token_ids: list[int], sig) -> list[int]:
        """Claim (ref++) the longest chain of resident full pages that
        prefix token_ids, strictly shorter than the prompt (at least one
        token must be prefilled so last-token logits exist). Returns the
        claimed pages in order; reuse tokens = len(result) * page_size."""
        ps = self.page_size
        max_full = (len(token_ids) - 1) // ps  # strict: reuse < len
        claimed: list[int] = []
        parent = b""
        for i in range(max_full):
            parent = self._digest(parent, token_ids[i * ps : (i + 1) * ps], sig)
            page = self._by_digest.get(parent)
            if page is None:
                break
            claimed.append(page)
        for page in claimed:
            self._claim(page)
        return claimed

    def _claim(self, page: int) -> None:
        if self._ref[page] == 0:
            self._cached.pop(page, None)
        self._ref[page] += 1

    # -- allocation --------------------------------------------------------

    def allocate(self, n: int) -> list[int]:
        """Allocate n private pages (ref=1, no content). Raises if the
        pool can't satisfy it — callers must check available() first."""
        if n > self.available():
            raise RuntimeError(f"KV pool exhausted: need {n}, have {self.available()}")
        out = []
        for _ in range(n):
            while not self._free and not self._cached:
                # Free list and cached set are dry: reclaim the LRU park
                # entry whole (its pages release into free/cached; pages
                # a live slot also claims stay referenced). The parked
                # request's host blob still enables restore-by-upload;
                # a lost blob degrades to deterministic replay.
                if not self._parked:
                    raise RuntimeError(
                        "KV pool exhausted mid-allocation (available() raced)"
                    )
                key, pages = self._parked.popitem(last=False)
                for p in pages:
                    self._parked_pages.pop(p, None)
                self.release(pages)
                self.park_evictions += 1
            if self._free:
                page = self._free.pop()
            else:
                # Evict the least-recently-used cached page.
                page, _ = self._cached.popitem(last=False)
                self._unregister(page)
                self.evictions += 1
            self._ref[page] = 1
            out.append(page)
        return out

    def _unregister(self, page: int) -> None:
        d = self._digest_of.pop(page, None)
        if d is not None and self._by_digest.get(d) == page:
            del self._by_digest[d]

    # -- registration ------------------------------------------------------

    def register_chain(self, token_ids: list[int], sig, pages: list[int]) -> list[int]:
        """Content-register the full pages of token_ids held in *pages*
        (the slot's block table, shared prefix included). Already-
        registered pages (shared hits, or double registration) keep
        their existing mapping; a digest that is already mapped to a
        DIFFERENT page keeps the first (the duplicate page stays
        private). Returns the NEWLY registered pages, so a caller whose
        content-write subsequently fails can unregister exactly those."""
        return self.register_pages(self.chain_digests(token_ids, sig), pages)

    def register_pages(self, digests: list[bytes], pages: list[int]) -> list[int]:
        """register_chain's rule for pages whose digests the caller
        holds (a window table holds only part of a chain). Returns the
        newly registered pages."""
        fresh: list[int] = []
        for digest, page in zip(digests, pages):
            if page in self._digest_of:
                continue
            if digest in self._by_digest:
                continue
            self._by_digest[digest] = page
            self._digest_of[page] = digest
            fresh.append(page)
        return fresh

    def claim_pages(self, digests: list[bytes]) -> list[int] | None:
        """Claim (ref++) the resident pages of ALL of *digests*, in
        order; None, and nothing claimed, where one is not resident."""
        pages = [self._by_digest.get(d) for d in digests]
        if any(p is None for p in pages):
            return None
        for page in pages:
            self._claim(page)
        return pages

    def unregister_pages(self, pages: list[int]) -> None:
        """Drop content registration (the pages keep their refcounts)."""
        for page in pages:
            self._unregister(page)

    def refcount(self, page: int) -> int:
        return self._ref[page]

    # -- parking -----------------------------------------------------------

    def park(self, key: str, pages: list[int]) -> None:
        """Pin *pages* under *key*: the caller's reference (one per
        page) transfers to the park entry instead of being released, so
        the page contents survive the slot for a later unpark(). The
        pages may stay content-registered — registered full pages are
        read-only by construction, so concurrent prefix claims are safe."""
        assert key and key not in self._parked, f"duplicate park key {key!r}"
        for p in pages:
            assert self._ref[p] > 0, f"parking unreferenced page {p}"
            assert p not in self._parked_pages, f"page {p} parked twice"
        self._parked[key] = list(pages)
        for p in pages:
            self._parked_pages[p] = key

    def unpark(self, key: str) -> list[int] | None:
        """Take the parked row back (the park's reference transfers to
        the caller). None = the entry was reclaimed under pressure or
        never existed — restore must fall back to the serialized blob."""
        pages = self._parked.pop(key, None)
        if pages is None:
            return None
        for p in pages:
            self._parked_pages.pop(p, None)
        return pages

    def drop_park(self, key: str) -> bool:
        """Release a park entry (TTL expiry, restore consumed the blob
        elsewhere). True if the key was parked."""
        pages = self.unpark(key)
        if pages is None:
            return False
        self.release(pages)
        return True

    # -- release -----------------------------------------------------------

    def release(self, pages: list[int]) -> None:
        """Drop one reference per page. Refcount-0 pages go to the LRU
        cached set if content-registered, else back to the free list."""
        for page in pages:
            self._ref[page] -= 1
            assert self._ref[page] >= 0, f"double release of page {page}"
            if self._ref[page] == 0:
                assert page not in self._parked_pages, (
                    f"page {page} hit refcount 0 while parked — the park's "
                    "pin was released out from under it"
                )
                if page in self._digest_of:
                    self._cached[page] = None
                    self._cached.move_to_end(page)
                else:
                    self._free.append(page)


class WindowPages:
    """The page budget of a family's WINDOW layers (models/smallthinker.py):
    a pool of its own, and for every slot the one contiguous run of
    logical pages that some query of the slot's next call can still see.
    *table* is the engine's view of the slots' window tables ([slots,
    max_pages], one entry a logical page, 0 = no page: a write there goes
    to the trash page); this class is the only writer of it.

    Before every call the engine says where the slot's queries will sit
    (`advance`): pages wholly behind `start - window` are handed back
    (content-registered ones stay findable until the pool needs them),
    pages up to the call's last position are allocated. A slot therefore
    holds at most `cap = (window + chunk) / page + 1` pages whatever its
    length (*chunk*: the widest call the engine makes for one slot,
    `engine/core.py::wide_chunk`), and the pool is sized so that every slot can hold its cap at
    once: a window page is always there when a slot needs it, so
    admission never waits on this pool and nothing is reserved ahead."""

    @staticmethod
    def slot_cap(max_pages: int, window: int, chunk: int, page_size: int) -> int:
        """The most window pages a slot holds: a chunk's queries and the
        window behind the first of them, at the worst alignment."""
        return min(max_pages, (window + chunk) // page_size + 1)

    def __init__(self, table, window: int, chunk: int, page_size: int):
        slots, max_pages = table.shape
        self.table = table
        self.window = window
        self.page_size = page_size
        self.cap = self.slot_cap(max_pages, window, chunk, page_size)
        self.pool = PagePool(slots * self.cap + 1, page_size)
        self.released = 0  # pages handed back behind a window, cumulative
        self._lo = [0] * slots  # the slot holds logical pages [lo, hi)
        self._hi = [0] * slots
        self._limit = [0] * slots  # pages of prompt + budget: nothing is allocated past them
        self._digests: list[list[bytes]] = [[] for _ in range(slots)]
        # Pages `advance` registered for a prompt whose prefill has not
        # succeeded yet (`settle`): a failed one takes exactly these back.
        self._fresh: list[list[int]] = [[] for _ in range(slots)]

    def first_page(self, start: int) -> int:
        """The first logical page a query at position *start* can see."""
        return max(start - self.window + 1, 0) // self.page_size

    def held(self, slot: int) -> int:
        return self._hi[slot] - self._lo[slot]

    def match(self, digests: list[bytes], cuts) -> tuple[int, list[int]]:
        """The first of *cuts* (a prefix's length in pages, longest first)
        whose visible window pages are all resident: (pages of prefix,
        the claimed window pages), (0, []) where there is none."""
        for n in cuts:
            pages = self.pool.claim_pages(digests[self.first_page(n * self.page_size) : n])
            if pages is not None:
                return n, pages
        return 0, []

    def admit(self, slot: int, digests: list[bytes], reuse_pages: int, claimed: list[int], limit: int) -> None:
        """A slot starts behind *reuse_pages* cached pages of which it
        holds the *claimed* last ones; *digests* are its prompt's."""
        self._hi[slot] = reuse_pages
        self._lo[slot] = reuse_pages - len(claimed)
        self._limit[slot] = min(limit, self.table.shape[1])
        self._digests[slot] = digests
        self.table[slot, :] = 0
        self.table[slot, self._lo[slot] : reuse_pages] = claimed

    def advance(self, slot: int, start: int, end: int) -> None:
        """Ready the slot's table for a call whose queries sit at
        positions [start, end)."""
        lo, hi = self._lo[slot], self._hi[slot]
        first = min(self.first_page(start), hi)
        last = min(-(-end // self.page_size), self._limit[slot])
        if first <= lo and last <= hi:
            return  # most decode chunks: no page boundary crossed
        row = self.table[slot]
        if first > lo:
            self.pool.release(row[lo:first].tolist())
            row[lo:first] = 0
            self.released += first - lo
            self._lo[slot] = first
        if last > hi:
            pages = self.pool.allocate(last - hi)
            row[hi:last] = pages
            # A prompt's pages are findable from now on, as the full
            # pool's are from admission: whoever claims one is dispatched
            # behind the call that writes it.
            self._fresh[slot] += self.pool.register_pages(self._digests[slot][hi:last], pages)
            self._hi[slot] = last

    def settle(self, slot: int) -> None:
        """The slot's prefill succeeded: what it registered is content."""
        self._fresh[slot] = []

    def free(self, slot: int, digests: list[bytes] | None = None) -> None:
        """The slot ends: every page goes back. *digests* (the chain of
        everything the slot wrote) registers the whole pages it still
        holds; pages of a prefill that never succeeded are unregistered."""
        lo, hi = self._lo[slot], self._hi[slot]
        pages = self.table[slot, lo:hi].tolist()
        self.pool.unregister_pages(self._fresh[slot])
        self._fresh[slot] = []
        if digests is not None:
            self.pool.register_pages(digests[lo:hi], pages)
        self.pool.release(pages)
        self.table[slot, :] = 0
        self._lo[slot] = self._hi[slot] = self._limit[slot] = 0
        self._digests[slot] = []
