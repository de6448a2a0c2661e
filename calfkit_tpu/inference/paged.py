"""Paged KV-cache management (host side).

Why paging (reference anchor: SURVEY.md §5 long-context — "Ragged Paged
Attention for TPU"; VERDICT r1 weak #4): a dense cache allocates
``[L, B, K, max_seq, hd]`` up front, every slot at the longest sequence any
may reach, whatever it holds.  Paging allocates fixed pools of
``page_size``-token pages and gives each request only the pages its actual
(prompt + requested max_new) footprint needs, so many short streams fit
where few dense rows would.

Pools come BY CACHE KIND (``config.CACHE_KINDS``), because layers differ in
what they keep of a sequence:

- *global* (an attention layer that sees every earlier token; a latent
  model's one latent a token is this kind too): a page a ``page_size`` tokens
  for the row's whole life, ``prompt + max_new`` tokens reserved at
  admission.  :class:`PageAllocator` is this pool's allocator, and the only
  one a model without window layers has.
- *window* (a sliding-window attention layer): a row keeps the last ``W``
  tokens, so it reserves a RING of ``ceil((W + what one dispatch writes
  ahead) / page) + 1`` pages, never more however long it grows: position
  ``p`` lives in ring entry ``(p // page) % ring``, and a row that grows
  writes over, i.e. gives back, what its window has left behind.  At 16k
  tokens under a window of 4,096 a row of a W W W G period holds 16k + 3 x
  4.2k token-layers, not 4 x 16k.  :class:`PagesByKind` pairs the two
  allocators: a row is admitted with both reservations or neither.
- *state* (Mamba-2, Gated DeltaNet) is not paged at all: a fixed array a slot.

Design decisions:

- **Page 0 is the trash page** of every pool.  Never allocated.  Block-table rows start
  as zeros, and consolidation writes the tokens of *inactive* batch rows into page
  0 — a retired slot's stale row can keep "writing" harmlessly even after
  its real pages were reused by another request.
- **Reserve at admission.**  A request's full worst-case footprint
  (``prompt + max_new`` tokens of every global layer, capped by ``max_seq``,
  and its ring of every window layer) is allocated before
  prefill; if a pool can't cover it the request waits in the queue.  No
  mid-flight OOM, no preemption machinery.  (On-demand growth of the global
  pages would pack
  tighter when generations stop early at EOS; noted as future work.)
- The allocator is plain host Python.  It is only touched from the engine's
  scheduler flow (admission on the event loop, retirement on the decode
  thread — never concurrently, same discipline as the slot free-list).

Speculative decoding and pages: a verify wave writes k+1 chunk positions
through the block tables, then acceptance advances each row's length by
only ``accepted + 1`` — the rejected tail's K/V sits in the row's OWN
reserved pages beyond its valid length and is overwritten by the next
wave, so "rollback" is a length update, never a page operation.  Prefix-
cache hashing stays consistent automatically: only FULL PAGES OF THE
PROMPT are ever registered (``chain_hashes`` runs over the prompt alone),
and the chunk's first write lands at ``lens >= prompt_len``, past every
registered page — partially-accepted blocks are always private pages.
Neither is served over window pages yet (a ring entry that is registered or
read again has been written over): the engine refuses speculation and
declines prefix reuse for a model with window layers.
"""

from __future__ import annotations

from collections import OrderedDict

import numpy as np

TRASH_PAGE = 0


class PageAllocator:
    """Fixed pool of KV pages; page 0 reserved as the trash page.  The page
    freed LAST is granted first; with ``oldest_first`` the page that has been
    free LONGEST is, so that what a retired row left in its pages stands for as
    long as the pool has other pages to give."""

    def __init__(self, num_pages: int, oldest_first: bool = False):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        self.num_pages = num_pages
        self._oldest_first = oldest_first
        self._free: list[int] = list(range(num_pages - 1, 0, -1))
        self._held: dict[int, list[int]] = {}  # slot -> pages

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def held_slots(self) -> dict[int, int]:
        """slot -> page count currently reserved (public, for stats/tests)."""
        return {slot: len(pages) for slot, pages in self._held.items()}

    @property
    def in_use(self) -> int:
        return self.num_pages - 1 - len(self._free)

    def fits(self, n: int) -> bool:
        """Could the pool, empty, ever cover ``n`` pages?"""
        return n <= self.num_pages - 1

    def alloc(self, slot: int, n: int) -> list[int] | None:
        """Reserve ``n`` pages for ``slot``; None if the pool can't cover it."""
        if slot in self._held:
            raise ValueError(f"slot {slot} already holds pages")
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        self._held[slot] = pages
        return pages

    def free(self, slot: int) -> None:
        """Return ``slot``'s pages to the pool (idempotent)."""
        self.give_back(self._held.pop(slot, ()))

    def transfer_out(self, slot: int, pages: "list[int]") -> None:
        """Move ``pages`` out of ``slot``'s holding WITHOUT freeing them —
        ownership passes to the prefix cache (so a later ``free(slot)``
        cannot return shared pages to the pool under live readers)."""
        held = self._held.get(slot)
        if held is None:
            return
        moving = set(pages)
        self._held[slot] = [p for p in held if p not in moving]

    def give_back(self, pages: "list[int]") -> None:
        """Return pages to the pool (a slot's; cache-owned ones at a
        prefix-cache eviction)."""
        if self._oldest_first:  # granted from the end: the newly freed wait longest
            self._free[:0] = reversed(pages)
        else:
            self._free.extend(pages)


class PagesByKind:
    """The allocators of a model whose layers keep two kinds of cache: a
    pool of *global* pages (every token) and a pool of *window* pages (a ring
    a row).  ``alloc`` takes ``(n_global, n_window)`` and grants both or
    neither; ``free`` returns both.  Both grant the page that has been free
    LONGEST (``PageAllocator(oldest_first=True)``): a retired row's ring and
    its global pages stand until the pools have gone round, which is what lets
    a check read back what finished rows LEFT BEHIND
    (``InferenceEngine.window_ring`` / ``global_keys``).

    ``num_pages`` and ``free_pages`` count in pages of EQUAL BYTES, a single
    layer's page: a global page is ``weights[0]`` (the global layers) of
    them, a window page ``weights[1]``, so that one ledger
    (``pages_in_use`` / ``pages_total``) can speak for both pools.  (The
    ``+ 1`` keeps the engine's "less the trash page" arithmetic.)"""

    def __init__(self, n_global: int, n_window: int, weights: "tuple[int, int]"):
        self.by_kind = (PageAllocator(n_global, oldest_first=True),
                        PageAllocator(n_window, oldest_first=True))
        self.weights = weights

    def layer_pages(self, n_global: int, n_window: int) -> int:
        return n_global * self.weights[0] + n_window * self.weights[1]

    @property
    def num_pages(self) -> int:
        g, w = self.by_kind
        return self.layer_pages(g.num_pages - 1, w.num_pages - 1) + 1

    @property
    def free_pages(self) -> int:
        g, w = self.by_kind
        return self.layer_pages(g.free_pages, w.free_pages)

    def fits(self, need: "tuple[int, int]") -> bool:
        """Could the pools, empty, ever cover ``need``?"""
        return all(a.fits(n) for n, a in zip(need, self.by_kind))

    def alloc(self, slot: int, need: "tuple[int, int]") -> "tuple[list[int], list[int]] | None":
        g, w = self.by_kind
        if need[0] > g.free_pages or need[1] > w.free_pages:
            return None
        return g.alloc(slot, need[0]), w.alloc(slot, need[1])

    def free(self, slot: int) -> None:
        for allocator in self.by_kind:
            allocator.free(slot)

    @property
    def held_slots(self) -> "dict[int, tuple[int, int]]":
        g, w = (a.held_slots for a in self.by_kind)
        return {slot: (g.get(slot, 0), w.get(slot, 0)) for slot in {*g, *w}}


def chain_hashes(prompt: "list[int]", page_size: int) -> "list[bytes]":
    """Position-dependent content hash per FULL page of the prompt:
    hash_i = H(hash_{i-1} || tokens[i*ps:(i+1)*ps]).  Chaining makes a
    page's identity its entire prefix, so equal pages at different
    positions (or after different histories) never alias."""
    import hashlib

    out: list[bytes] = []
    prev = b""
    for i in range(len(prompt) // page_size):
        h = hashlib.blake2b(digest_size=16)
        h.update(prev)
        # blocking-ok: host token LIST → bytes for hashing, never a
        # device array — nothing syncs
        h.update(np.asarray(
            prompt[i * page_size:(i + 1) * page_size], np.int32
        ).tobytes())
        prev = h.digest()
        out.append(prev)
    return out


class PrefixCache:
    """Automatic prefix caching over the page pool (the vLLM-APC analog,
    sized for agent serving: every run of the same agent re-sends the
    same instruction/history prefix, so its KV pages are recomputed
    per-turn without this).

    Ownership protocol: a landed request's full-prompt pages transfer
    from the allocator to this cache (``PageAllocator.transfer_out``);
    live requests hold references; zero-reference entries sit in an LRU
    and are evicted back to the allocator when admission runs dry.  All
    mutation happens from the engine's scheduler flow (same
    single-writer discipline as the allocator)."""

    def __init__(self) -> None:
        self._entries: dict[bytes, int] = {}      # chain hash -> page
        self._hash_of: dict[int, bytes] = {}
        self._refs: dict[int, int] = {}            # live slot references
        self._lru: "OrderedDict[bytes, None]" = OrderedDict()

    @property
    def size(self) -> int:
        return len(self._entries)

    def lookup(self, hashes: "list[bytes]") -> "list[int]":
        """Longest cached chain prefix → its pages, in sequence order."""
        pages: list[int] = []
        for h in hashes:
            page = self._entries.get(h)
            if page is None:
                break
            pages.append(page)
        return pages

    def acquire(self, pages: "list[int]") -> None:
        for page in pages:
            self._refs[page] += 1
            self._lru.pop(self._hash_of[page], None)

    def release(self, pages: "list[int]") -> None:
        for page in pages:
            self._refs[page] -= 1
            if self._refs[page] <= 0:
                self._lru[self._hash_of[page]] = None

    def register(self, h: bytes, page: int) -> bool:
        """False when the hash is already cached (the caller's duplicate
        page stays private to its slot and frees at retirement)."""
        if h in self._entries:
            return False
        self._entries[h] = page
        self._hash_of[page] = h
        self._refs[page] = 0
        return True

    def evict(
        self, need: int, allocator: PageAllocator, *, ledger=None
    ) -> int:
        """Pop up to ``need`` zero-reference entries (oldest released
        first) back into the allocator's free list.  Evicting a chain's
        middle page strands its suffix entries (unreachable by lookup);
        they drain through this same LRU once released.  ``ledger`` is
        the capacity observatory's per-page hook (ISSUE 19): only the
        cache knows WHICH pages the LRU picked, so attribution must be
        told here, at the reclaim itself."""
        freed = 0
        while freed < need and self._lru:
            h, _ = self._lru.popitem(last=False)
            page = self._entries.pop(h)
            del self._hash_of[page]
            del self._refs[page]
            allocator.give_back([page])
            if ledger is not None:
                ledger.evicted(page)
            freed += 1
        return freed


def pages_needed(total_tokens: int, page_size: int) -> int:
    return -(-total_tokens // page_size)


def table_row(pages: list[int], max_pages: int) -> np.ndarray:
    """A block-table row: allocated page ids, padded with the trash page."""
    row = np.full((max_pages,), TRASH_PAGE, np.int32)
    row[: len(pages)] = pages
    return row
