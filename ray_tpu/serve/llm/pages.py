"""Where a sequence's cached tokens live: the serving engine's page
allocator. engine.py asks; nothing here knows whom the engine serves,
how it schedules or what it counts.

One preallocated pool per layer, flattened to (n_pages * page_size,
*trailing) token rows, plus a (slots, pages_per_slot) page table. A
pool's arrays, their trailing shapes and dtypes come from the model's
cache spec, one entry a LAYER (ops/attention.py:kv_cache_spec names the
kinds): indexed by token through the page table or, where a layer keeps
a fixed-size state a SLOT, (slots + 1, *trailing), by slot, which no
page knows of. Pages, page table, lengths and windows cover the paged
layers and do not know which kind they hold. The pools themselves are
the engine's carried, donated state.

A slot reserves the whole pages its prompt + generation budget need at
admission. Full reservation means decode can never hit page exhaustion
mid-stream (no preemption machinery needed).
"""
from __future__ import annotations

from types import MappingProxyType
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.attention import LayerCache


class PageAllocator:
    def __init__(self, spec: Sequence[LayerCache], max_slots: int,
                 max_seq_len: int, page_size: int, pool_tokens: int = 0):
        if page_size <= 0:
            raise ValueError(
                f"kv_page_size must be > 0, got {page_size}: the KV cache "
                "is a page pool and the contiguous per-slot layout is gone")
        self.spec, self.page_size = list(spec), page_size
        # +1 scratch slot, never admitted, so its garbage never decodes
        # (costs one page-table row, not a KV row): padding rows of a
        # batched prefill write through its all-trash page row, and a
        # prefix registration prefills through it.
        self.n_slots = max_slots + 1
        self.scratch_slot = max_slots
        # per-slot gather width: whole pages covering max_seq_len
        self.pages_per_slot = -(-max_seq_len // page_size)
        # the configured budget is honored exactly (rounded up to a
        # page): oversized requests fail fast at submit() instead of
        # silently inflating the pool
        self.n_pages = max(1, -(-(pool_tokens or max_slots * max_seq_len)
                                // page_size))
        self.trash_page = self.n_pages  # extra page: writes by
        # released/padding slots land here and are never read valid
        self.n_flat = (self.n_pages + 1) * page_size

        def row_bytes(by_slot):
            return sum(int(np.prod(t)) * jnp.dtype(d).itemsize
                       for c in self.spec if c.by_slot == by_slot
                       for t, d in zip(c.shapes, c.dtypes))
        self.kv_bytes_per_token = row_bytes(False)
        self.state_bytes_per_slot = row_bytes(True)
        self.n_state_layers = sum(c.by_slot for c in self.spec)
        # the layer whose entry carries the sequences' lengths out of a
        # decode step: the first that pages
        paged = [i for i, c in enumerate(self.spec) if not c.by_slot]
        if not paged:
            raise ValueError(
                "every layer of this model keeps per-slot state and none "
                "pages: the sequences' lengths ride on a paged layer's "
                "entry: not supported")
        self.len_layer = paged[0]
        # the page table lives on the host: every program gets the rows
        # it reads as they stand at its dispatch, which is the order the
        # device runs them in
        self._table = np.full((self.n_slots, self.pages_per_slot),
                              self.trash_page, np.int32)
        # slot -> the length its row restarts from (0, or an adopted
        # prefix's): applied by the next program before it reads
        # lengths, then forgotten
        self._len_edits: Dict[int, int] = {}
        # host mirror of each slot's device length as of the last
        # dispatch, for each slot that holds pages: picks the
        # power-of-2 page window covering the longest sequence at
        # decode-dispatch time. A live read-only view for who asks.
        self._disp_len: Dict[int, int] = {}
        self.dispatched_lengths = MappingProxyType(self._disp_len)
        self._free: List[int] = list(range(self.n_pages))   # a stack
        # slot -> (n_shared_prefix_pages, [all pages in table order])
        self._slot_pages: Dict[int, tuple] = {}
        # prefix -> (its pinned pages, its tokens)
        self._prefixes: Dict[int, Tuple[List[int], int]] = {}
        self.peak = 0           # most pages ever in use

    # ---- what a cache kind cannot do ---------------------------------------
    def refuses(self, what: str) -> Optional[str]:
        """Why this cache cannot `share` a prefix or `roll_back` a
        rejected proposal; None where it can. A prefix of a recurrent
        layer is a snapshot of its state, not pages to share, and a
        rejected proposal would need the state rolled back: neither
        exists (ROADMAP B9)."""
        if not self.n_state_layers:
            return None
        return "this model keeps per-slot recurrent state, " + {
            "share": "whose prefix would be a state snapshot",
            "roll_back": "which cannot be rolled back"}[what] \
            + ": not supported"

    # ---- the device side ---------------------------------------------------
    def new_pools(self) -> list:
        """The zeroed pools, one tuple of arrays a layer: the scratch
        slot takes padding rows' writes in a `by_slot` pool as the
        trash page does in a paged one."""
        return [tuple(jnp.zeros((self.n_slots if c.by_slot else self.n_flat,
                                 *t), d) for t, d in zip(c.shapes, c.dtypes))
                for c in self.spec]

    def entries(self, pools, page_table, lengths, fresh=False, slots=None,
                n_new=None, restart=None) -> list:
        """Per-layer cache entries over the shared pools, as the model's
        cache spec has them: a paged entry (PagedKV, PagedLatent) over
        the call's rows of the page table, or a SlotState over the
        call's `slots` (None: every slot in order) with `n_new` real
        new positions a row, `restart`ing the rows that begin there.
        The gather/scatter happens INSIDE each layer, so only one
        layer's contiguous view is ever live at a time."""
        return [c.entry(*arrays, slots, n_new, restart, fresh=fresh)
                if c.by_slot
                else c.entry(*arrays, page_table, lengths, self.page_size,
                             fresh)
                for c, arrays in zip(self.spec, pools)]

    def copy_page(self, pools, src_page, dst_page) -> list:
        """Copy one page's rows of every pool array in every layer —
        the only device copy prefix adoption pays (its final PARTIAL
        page; full pages are shared by page-table reference)."""
        ps = self.page_size

        def copy(a):
            rows = jax.lax.dynamic_slice_in_dim(a, src_page * ps, ps, axis=0)
            return jax.lax.dynamic_update_slice_in_dim(
                a, rows, dst_page * ps, axis=0)
        return [tuple(copy(a) for a in arrays) for arrays in pools]

    # ---- reserving and releasing -------------------------------------------
    def pages_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def pinned(self) -> int:
        return sum(len(pages) for pages, _ in self._prefixes.values())

    def unservable(self, n_tokens: int, prefix: int = -1,
                   pins: bool = True) -> Optional[str]:
        """Why a sequence of `n_tokens` can NEVER be reserved, or None.
        Pinned prefix pages never return to the pool, so a request
        needing more than (total - pinned [- shared pages it adopts])
        would be held at admission FOREVER and head-of-line-block every
        later request: its stream is errored instead. `pins=False` is
        submit()'s question, on the caller's thread before any queueing:
        the pool's size alone (pins made later it cannot see, and it
        leaves those made earlier to admission)."""
        need = self.pages_needed(n_tokens)
        pinned = self.pinned() if pins else 0
        if prefix >= 0 and pins:
            need -= self._prefixes[prefix][1] // self.page_size
        if need <= self.n_pages - pinned:
            return None
        if not pinned:
            return (f"request needs {need} KV pages; pool has "
                    f"{self.n_pages} total — it could never be admitted")
        return (f"request needs {need} exclusive KV pages but only "
                f"{self.n_pages - pinned} can ever be free ({pinned} "
                "pinned by prefixes)")

    def _alloc(self, n: int) -> Optional[List[int]]:
        if len(self._free) < n:
            return None
        pages = [self._free.pop() for _ in range(n)]
        self.peak = max(self.peak, self.n_pages - len(self._free))
        return pages

    def _set_row(self, slot: int, pages: List[int],
                 length: Optional[int] = None) -> None:
        """Write a slot's page-table row (unused entries -> trash) and,
        where given, the length its sequence restarts from. Both reach
        the device with the next program dispatched, whichever kind,
        before it reads either: programs queued earlier keep the rows
        they were dispatched with and run first."""
        row = self._table[slot]
        row[:] = self.trash_page
        row[:len(pages)] = pages
        if not pages:
            # a row that holds no page holds no key: the decode kernel
            # walks a row's length, so a stale one would cost its pages
            length = 0
        if length is not None:
            self._len_edits[slot] = length

    def reserve(self, slot: int, n_tokens: int,
                prefix: int = -1) -> Optional[tuple]:
        """Reserve the pages `slot` needs for `n_tokens` (prompt + budget):
        (its row's pages in table order, the length its sequence restarts
        from, the one page to copy); None, and nothing changed, while the
        pool lacks them. An adopter of `prefix` shares its full pages by
        page-table reference and restarts behind its tokens; where the
        prefix ends inside a page the copy is (that page, the adopter's
        own first): the caller runs it, and calls `release` if it failed."""
        pinned, restart = self._prefixes[prefix] if prefix >= 0 else ([], 0)
        shared = pinned[:restart // self.page_size]
        own = self._alloc(self.pages_needed(n_tokens) - len(shared))
        if own is None:
            return None
        pages = shared + own
        self._slot_pages[slot] = (len(shared), pages)
        # the slot's device length restarts WITH its new row (the next
        # program applies both before it reads either): a reused slot's
        # stale length would aim inactive decode-steps' garbage writes
        # at an arbitrary position — under a narrowed decode window the
        # clamped scatter could then corrupt the NEW occupant's pages.
        # With length 0, garbage always lands exactly where the next
        # prefill/chunk write goes (overwritten before any read).
        self._set_row(slot, pages, length=restart)
        self._disp_len[slot] = restart
        return pages, restart, ((pinned[len(shared)], own[0])
                                if restart % self.page_size else None)

    def release(self, slot: int) -> None:
        """Return the slot's exclusive pages to the pool (shared prefix
        pages stay pinned) and point its row at the trash page so lagged
        decode writes can't corrupt a reused page: whoever is given
        these pages next is dispatched after this edit, and every
        program dispatched from here on sees the trash row."""
        entry = self._slot_pages.pop(slot, None)
        self._disp_len.pop(slot, None)
        if entry is None:
            return
        n_shared, pages = entry
        self._free.extend(pages[n_shared:])
        self._set_row(slot, [])

    def pin_prefix(self, prefix: int, n_tokens: int) -> bool:
        """Pin pages for a prefix of `n_tokens` (False: the pool lacks
        them) and aim the scratch slot's row at them for the prefill
        that fills them; then `reset_scratch`, or `unpin_prefix` too."""
        pages = self._alloc(self.pages_needed(n_tokens))
        if pages is None:
            return False
        self._set_row(self.scratch_slot, pages)
        self._prefixes[prefix] = (pages, n_tokens)
        return True

    def reset_scratch(self) -> None:
        # scratch row back to all-trash: batch-padding rows write
        # through it and must never touch the pinned prefix pages
        self._set_row(self.scratch_slot, [])

    def unpin_prefix(self, prefix: int) -> None:
        """Free a prefix's pinned pages (safe once no slot shares them)."""
        pages, _ = self._prefixes.pop(prefix, ([], 0))
        self._free.extend(pages)

    # ---- lengths, windows, rows --------------------------------------------
    def set_length(self, slot: int, n: int) -> None:
        """A program that leaves `slot` at `n` tokens was dispatched (or
        a verify step, dispatched as its upper bound, showed `n`)."""
        if slot in self._disp_len:
            self._disp_len[slot] = n

    def advance(self, slots, n: int) -> None:
        """A program appending `n` tokens to each of `slots` was queued."""
        for slot in slots:
            # KeyError here = an admission path forgot to reserve for
            # the slot; fail loudly — a silent 0 default would shrink
            # the window and corrupt KV untraceably
            self._disp_len[slot] += n

    def decode_window(self, new_tokens: int) -> int:
        """Power-of-2 page window covering every slot that holds KV
        (active AND chunk-prefilling — a narrower window would let the
        decode scatter's clamped index corrupt a prefilling slot's
        pages) plus this dispatch's new tokens. 0 = full width. The
        static window buckets keep compile count at O(log2 P) while
        decode cost tracks the longest REAL sequence."""
        need = max(self._disp_len.values(), default=0) + new_tokens
        w = 1 << (self.pages_needed(need) - 1).bit_length()
        return 0 if w >= self.pages_per_slot else w

    def decode_pages(self, window: int, new_tokens: int) -> Tuple[int, int]:
        """The old kernel's grid against what the rows hold: every row
        times the window's pages, and the pages under each slot's
        tokens once this dispatch has written its own."""
        return (self.n_slots * (window or self.pages_per_slot),
                sum(self.pages_needed(n + new_tokens)
                    for n in self._disp_len.values()))

    def take_length_edits(self) -> np.ndarray:
        """(n_slots,) int32 for the front of the next program's `ctl`:
        the pending restart lengths, -1 = leave; forgotten once taken."""
        edits = np.full((self.n_slots,), -1, np.int32)
        for slot, n in self._len_edits.items():
            edits[slot] = n
        self._len_edits.clear()
        return edits

    def rows(self, window: int = 0) -> np.ndarray:
        """The page table as it stands now, cut to the window's columns
        (a view: the caller packs it before anything here changes)."""
        return self._table[:, :window or self.pages_per_slot]

    def rows_shape(self, window: int = 0) -> Tuple[int, int]:
        """The shape of `rows(window)`: how a program finds them in `ctl`."""
        return self.n_slots, window or self.pages_per_slot

    def narrow(self, rows, pages: int):
        """Inside a program: table rows cut to their first `pages` pages
        (0, or no fewer than they hold: as they are)."""
        return rows[:, :pages] if 0 < pages < rows.shape[1] else rows

    def utilization(self) -> float:
        return (self.n_pages - len(self._free)) / max(1, self.n_pages)

    def kv_pages(self) -> Dict[str, int]:
        """The pool as the engine reports it (its `kv_pages`)."""
        free = len(self._free)
        return {"page_size": self.page_size, "total": self.n_pages,
                "free": free, "in_use": self.n_pages - free,
                "pinned_prefix": self.pinned(), "peak_in_use": self.peak}
