"""Host-side page allocator for the paged KV cache (vLLM block-table style).

The device side (models/bert.py ``_paged_attend`` + ops/paged_attention.py)
stores K/V in fixed-size pages addressed through a per-slot block table;
this module owns WHICH pages a slot holds. It is deliberately dumb:

- fixed page size, fixed pool, page ids handed out from a free list;
- alloc on admit (the whole worst case — prompt + max_new_tokens — up
  front, so a running request can never starve mid-decode), free on evict;
- refcounted: a page may appear in several block-table rows at once (the
  prefix cache maps one immutable prompt-prefix run into many slots) and
  only returns to the free list when its count reaches zero; writers must
  never touch a page with refcount > 1 — ``cow`` gives them a private copy;
- defrag-free: pages are interchangeable, so freeing returns ids to the
  free list and there is nothing to compact;
- page 0 is RESERVED as the null page: never allocated, idle slots park
  their whole block-table row on it, and entries past a live slot's length
  point at it (reads of those lanes are masked to exact zero by the
  attention math, writes by idle slots land there harmlessly).

All methods are called with the engine's swap lock held (single-threaded
tick loop); the allocator itself takes no locks.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Mapping
from typing import Any

import numpy as np

# Cache-collection keys injected/stripped around jitted calls: the engine's
# resident cache tree holds page POOLS (and, for a family that declares
# them, rings and states) only; block_table/context_len, and slot/chunk_len
# beside a ring or a state, are per-call traced operands.
_TABLE_KEYS = ("block_table", "context_len", "slot", "chunk_len")


@dataclasses.dataclass(frozen=True)
class SlotMemory:
    """One piece of per-sequence memory a model keeps on the device while
    serving, as the model itself declares it (``config.slot_memory()``; a
    family that declares none keeps page pools only, found by their leaves'
    names as before). Three kinds:

    ========  ====================  =======================  ================
    kind      a layer stores        addressed by             on admission
    ========  ====================  =======================  ================
    pages     rows a TOKEN, in a    the slot's block-table   fresh pages from
              pool shared by all    row (``PageAllocator``)  the allocator;
              slots                                          lanes past the
                                                             context masked
    ring      a bounded run of      slot index, position     nothing: a row
              rows a SLOT           modulo the ring          whose position
                                                             would be negative
                                                             is masked
    state     a fixed block a SLOT  slot index               nothing: a step
                                                             at context 0
                                                             starts from zeros
    ========  ====================  =======================  ================

    Only ``pages`` grow with a sequence, so only they are the allocator's:
    rings and states are sized by ``num_slots`` when the engine is built
    and never grow. ``path`` is the cache node that holds the memory;
    ``with_tables`` hands that node its per-call operands. ``readers``
    counts the layers that read it each step (a pool shared down the stack
    is kept once and read by several). The engine's ``kv_bytes_per_token``
    sums ``bytes_per_token``; ``ring_bytes_per_slot`` and
    ``state_bytes_per_slot`` sum ``bytes_per_slot`` by kind."""

    kind: str
    path: tuple
    bytes_per_token: int = 0
    bytes_per_slot: int = 0
    readers: int = 1

    def __post_init__(self):
        if self.kind not in ("pages", "ring", "state"):
            raise ValueError(f"unknown kind of slot memory {self.kind!r}")


class PageAllocator:
    """Free-list allocator over ``num_pages`` fixed-size KV pages.

    ``block_table`` is the [num_slots, pages_per_slot] int32 array handed to
    the device verbatim each tick; row ``slot`` lists that slot's pages in
    token order, null-padded with page 0.
    """

    def __init__(self, num_pages: int, page_size: int, pages_per_slot: int,
                 num_slots: int):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if num_pages < 2:
            raise ValueError(
                f"num_pages must be >= 2 (page 0 is reserved), got {num_pages}"
            )
        if pages_per_slot < 1:
            raise ValueError(
                f"pages_per_slot must be >= 1, got {pages_per_slot}"
            )
        self.num_pages = num_pages
        self.page_size = page_size
        self.pages_per_slot = pages_per_slot
        self.num_slots = num_slots
        # LIFO free list: recently-freed pages are re-handed first, which
        # keeps the working set of hot pages small.
        self._free = list(range(num_pages - 1, 0, -1))
        self._owned: list[list[int]] = [[] for _ in range(num_slots)]
        # Per-page refcount: 0 = free, 1 = sole owner (a slot OR the prefix
        # cache), >1 = shared. Page 0 stays permanently at 0 and is never
        # handed out.
        self._ref = [0] * num_pages
        self.block_table = np.zeros((num_slots, pages_per_slot), np.int32)
        self.peak_used = 0

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_used(self) -> int:
        # excludes the reserved null page
        return (self.num_pages - 1) - len(self._free)

    @property
    def pages_shared(self) -> int:
        """Pages referenced by more than one holder (slots + prefix cache)."""
        return sum(1 for r in self._ref if r > 1)

    def refcount(self, page: int) -> int:
        return self._ref[page]

    def pages_needed(self, total_tokens: int) -> int:
        """Pages covering ``total_tokens`` (prompt + worst-case new)."""
        return -(-max(total_tokens, 1) // self.page_size)

    def pages_reserved(self, total_tokens: int, spec_k: int = 0) -> int:
        """Admission reservation WITH speculative overshoot.

        The reservation formula (pinned by tests/test_spec.py): a spec slot
        reserves ``pages_needed(total_tokens + spec_k)``. Why ``+ spec_k``:
        a verify tick launched one token before the emission cap writes its
        pending token plus k drafts before acceptance is known, so the
        highest position ever SCATTERED is ``(prompt + max_new - 2) + k``
        — i.e. ``total_tokens + spec_k - 1`` last-index, exactly covered.
        Rejected drafts stay in those over-reserved pages as dead lanes
        (masked by ``context_len``, overwritten on reuse): rollback is a
        host-side cursor rewind with zero allocator churn, and
        ``page_exhausted`` can never fire mid-flight for an admitted slot.
        """
        return self.pages_needed(total_tokens + max(spec_k, 0))

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def admit(self, slot: int, n: int) -> None:
        """Give ``slot`` ``n`` pages and fill its block-table row."""
        if self._owned[slot]:
            raise RuntimeError(f"slot {slot} already holds pages")
        if n > self.pages_per_slot:
            raise ValueError(
                f"request needs {n} pages but block-table rows hold "
                f"{self.pages_per_slot}"
            )
        if n > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: need {n}, free {len(self._free)} "
                "(admission must check can_alloc first)"
            )
        pages = [self._pop_free() for _ in range(n)]
        self._owned[slot] = pages
        row = self.block_table[slot]
        row[:] = 0
        row[: len(pages)] = pages
        self.peak_used = max(self.peak_used, self.pages_used)

    def admit_shared(self, slot: int, shared_pages: list[int],
                     n_private: int) -> None:
        """Admit ``slot`` with a prefix-cache hit: map ``shared_pages``
        (already-written pages, refcount bumped — read-only for this slot)
        followed by ``n_private`` fresh pages for the prompt tail + decode."""
        if self._owned[slot]:
            raise RuntimeError(f"slot {slot} already holds pages")
        n = len(shared_pages) + n_private
        if n > self.pages_per_slot:
            raise ValueError(
                f"request needs {n} pages but block-table rows hold "
                f"{self.pages_per_slot}"
            )
        if n_private > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: need {n_private}, free "
                f"{len(self._free)} (admission must check can_alloc first)"
            )
        for p in shared_pages:
            self.acquire(p)
        pages = list(shared_pages)
        pages.extend(self._pop_free() for _ in range(n_private))
        self._owned[slot] = pages
        row = self.block_table[slot]
        row[:] = 0
        row[: len(pages)] = pages
        self.peak_used = max(self.peak_used, self.pages_used)

    def acquire(self, page: int) -> None:
        """Add a reference to an already-allocated page (sharing it)."""
        if page <= 0 or page >= self.num_pages:
            raise ValueError(f"page {page} out of range")
        if self._ref[page] == 0:
            raise RuntimeError(
                f"page {page} is free; acquire only shares live pages"
            )
        self._ref[page] += 1

    def decref(self, page: int) -> bool:
        """Drop one reference; the page frees only at refcount 0.

        Returns True when this call actually freed the page. Double release
        (decref of an already-free page) raises — a freed id may already be
        in another slot's row, so silently continuing would corrupt it.
        """
        if self._ref[page] == 0:
            raise RuntimeError(f"double release of page {page}")
        self._ref[page] -= 1
        if self._ref[page] == 0:
            self._free.append(page)
            return True
        return False

    def cow(self, slot: int, index: int) -> tuple[int, int]:
        """Copy-on-write: repoint ``slot``'s block-table entry ``index`` from
        its current shared page to a fresh private one.

        Host-side bookkeeping only — the caller must copy the page contents
        on device (old page id, new page id are returned for that) BEFORE
        the slot's next write lands. The old page keeps its other holders.
        """
        old = self._owned[slot][index]
        if self._ref[old] <= 1:
            raise RuntimeError(
                f"cow on page {old} with refcount {self._ref[old]}; "
                "exclusively-held pages are written in place"
            )
        if not self._free:
            raise RuntimeError(
                "page pool exhausted: cow needs 1 free page "
                "(admission must reserve the private copy up front)"
            )
        new = self._pop_free()
        self._owned[slot][index] = new
        self.block_table[slot][index] = new
        self._ref[old] -= 1
        self.peak_used = max(self.peak_used, self.pages_used)
        return old, new

    def release(self, slot: int) -> None:
        """Drop ``slot``'s references; pages free only at refcount 0.

        No-op when idle. Reverse order keeps the LIFO free list handing the
        most-recently-freed page first, exactly as before refcounts.
        """
        for page in reversed(self._owned[slot]):
            self.decref(page)
        self._owned[slot] = []
        self.block_table[slot][:] = 0

    def slot_pages(self, slot: int) -> tuple[int, ...]:
        return tuple(self._owned[slot])

    def _pop_free(self) -> int:
        page = self._free.pop()
        assert self._ref[page] == 0, f"free list held live page {page}"
        self._ref[page] = 1
        return page


def with_tables(pools: Mapping[str, Any], block_table: Any,
                context_len: Any, *, memory=(), slot: Any = None,
                chunk_len: Any = None) -> dict[str, Any]:
    """Rebuild a full cache tree from engine-resident ``pools`` by injecting
    ``block_table``/``context_len`` beside every attention layer's page
    pools: the node that holds ``k_pages`` (per-head K/V pools) or
    ``latent_pages`` (a selection group's latent pool; the group's layers
    read its table for their indexer pools too). Used at TRACE level
    inside the jitted programs.

    ``memory`` (a family's declared ``SlotMemory`` entries): nothing is
    guessed from names; each declared node gets ``context_len``, a
    ``pages`` node the block table too, and every node ``slot`` and
    ``chunk_len`` where the step has them (a prefill: which slot its one
    batch row is, how many of its tokens are real)."""
    if memory:
        step = {"context_len": context_len}
        if slot is not None:
            step["slot"] = slot
        if chunk_len is not None:
            step["chunk_len"] = chunk_len

        def declared(node, path, operands):
            if not path:
                return {**node, **operands}
            return {**node, path[0]: declared(node[path[0]], path[1:], operands)}

        out = pools
        for m in memory:
            out = declared(out, m.path, (
                {**step, "block_table": block_table} if m.kind == "pages"
                else step))
        return out

    def walk(node):
        if isinstance(node, Mapping):
            out = {k: walk(v) for k, v in node.items()}
            if "k_pages" in node or "latent_pages" in node:
                out["block_table"] = block_table
                out["context_len"] = context_len
            return out
        return node

    return walk(pools)


def strip_tables(cache: Mapping[str, Any]) -> dict[str, Any]:
    """Inverse of ``with_tables``: drop the per-call table leaves so only
    the page pools persist between calls (they are what donation recycles)."""
    def walk(node):
        if isinstance(node, Mapping):
            return {
                k: walk(v) for k, v in node.items() if k not in _TABLE_KEYS
            }
        return node

    return walk(cache)
