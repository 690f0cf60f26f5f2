"""Host-side block-table KV allocator for the paged serving cache.

The contiguous engine pre-allocates every batch row out to ``max_seq``,
so HBM — not compute — caps batch occupancy: a row serving a 40-token
chat holds the same KV footprint as one serving a 4k-token document.
Paged KV (the vLLM block-table idea) breaks the cache into fixed-size
blocks of ``block_size`` tokens; a row owns an ordered *block list* and
grows it as decode advances, so resident bytes track the tokens actually
cached, not the worst case (docs/serving.md "Paged KV").

This module is the HOST half: pure-Python bookkeeping over integer block
ids. The device half lives in `kubedl_tpu.models.llama` (pool layout
``[L, NB, BS, KV, hd]``; gather-view attention and scatter writes over a
``[B, MB]`` block table). The split keeps every policy decision —
refcounts, watermarks, copy-on-write, preemption — unit-testable with no
device in sight.

Invariants the engine relies on:

- **Block 0 is the trash block.** It is never allocated and never freed;
  every unmapped block-table entry points at it, so device writes from
  vacant/overshooting rows land in garbage nobody reads (the paged twin
  of the contiguous path's garbage-beyond-pos contract).
- **Refcounts make sharing safe.** A prefix-cache entry and any number
  of rows may reference the same block; `free` decrements and only
  returns the block to the free list at zero. A block with refs >= 2 is
  *shared* and therefore read-only — the engine copies it
  (`copy-on-write`) before any write can land inside it, which in
  practice means exactly the partial tail block of a grafted prefix:
  full blocks are never written again, so they are shared by reference
  forever at zero copy cost.
- **Watermarks drive admission, with hysteresis.** When the free
  fraction drops below ``low_watermark`` the allocator closes admission;
  it reopens only once frees recover past ``high_watermark``, so
  admission does not flap around one block. The engine sheds (503 +
  Retry-After) while closed and defers admitting queued requests.

Thread safety: one internal lock; the scheduler thread and request
threads (stats) both call in.

**Two kinds of block.** A model whose layers keep different amounts of
context (``models/sparse_window.py``) has a pool a kind, each with its own
allocator, trash block and table, and the invariants above hold for each.
The pool of the layers that keep everything is the one the engine always
had. The pool of the layers that read only the last ``window`` keys is a
:class:`WindowTable`: a row owns the blocks of a RANGE of its positions,
grown at the front as the row advances and released at the back once a
block lies wholly behind the window.

**No block at all.** A model whose every layer keeps a fixed recurrent state
(``models/retention.py``) has no pool; its engine holds a :class:`NoBlocks`,
for which every request is already met.
"""

from __future__ import annotations

import threading
from typing import Dict, Iterable, List, Optional

import numpy as np

#: the reserved write-sink block every unmapped table entry points at
TRASH_BLOCK = 0


class BlockExhausted(Exception):
    """Raised by callers that treat allocation failure as an error (the
    allocator itself returns None — preemption is the engine's policy)."""


class BlockAllocator:
    """Free-list allocator over ``num_blocks`` fixed-size KV blocks.

    ``num_blocks`` INCLUDES the reserved trash block 0, mirroring the
    device pool's leading dimension; ``total`` reports usable blocks.
    """

    #: the fewest blocks a pool has: the trash block and one to hand out
    MIN_BLOCKS = 2

    def __init__(self, num_blocks: int, block_size: int,
                 low_watermark: float = 0.05,
                 high_watermark: float = 0.15) -> None:
        if num_blocks < self.MIN_BLOCKS:
            raise ValueError("need at least one usable block beyond trash")
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        if not 0.0 <= low_watermark <= high_watermark <= 1.0:
            raise ValueError("need 0 <= low <= high <= 1 watermarks")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.low_watermark = float(low_watermark)
        self.high_watermark = float(high_watermark)
        self._lock = threading.Lock()
        # LIFO free list: hot blocks cycle, keeping the working set dense
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self._refs: List[int] = [0] * self.num_blocks
        self._refs[TRASH_BLOCK] = 1  # pinned forever
        self._admitting = True
        self._stats = {"allocs": 0, "frees": 0, "alloc_failures": 0,
                       "cow_copies": 0}

    # -- capacity ----------------------------------------------------------

    @property
    def total(self) -> int:
        """Usable blocks (the trash block is not capacity)."""
        return self.num_blocks - 1

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to cache ``n_tokens`` token positions."""
        return max(0, (int(n_tokens) + self.block_size - 1) // self.block_size)

    @property
    def free_count(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def used_count(self) -> int:
        with self._lock:
            return self.total - len(self._free)

    @property
    def shared_count(self) -> int:
        """Blocks referenced by >= 2 owners (prefix entries + rows)."""
        with self._lock:
            return sum(
                1 for b in range(1, self.num_blocks) if self._refs[b] >= 2
            )

    def free_fraction(self) -> float:
        with self._lock:
            return len(self._free) / max(self.total, 1)

    # -- alloc / free / sharing -------------------------------------------

    def alloc(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` blocks (refcount 1 each) or None if the free
        list cannot cover them — all-or-nothing, so a half-grown row
        never exists. Updates the admission hysteresis either way."""
        n = int(n)
        with self._lock:
            if n > len(self._free):
                self._stats["alloc_failures"] += 1
                return None
            out = [self._free.pop() for _ in range(n)]
            for b in out:
                self._refs[b] = 1
            self._stats["allocs"] += n
            self._update_gate_locked()
            return out

    def incref(self, blocks: Iterable[int]) -> None:
        """Add one reference per block (prefix entry sharing a row's
        blocks, or a graft sharing an entry's)."""
        with self._lock:
            for b in blocks:
                if b == TRASH_BLOCK:
                    continue
                if self._refs[b] <= 0:
                    raise ValueError(f"incref of unallocated block {b}")
                self._refs[b] += 1

    def free(self, blocks: Iterable[int]) -> int:
        """Drop one reference per block; blocks reaching zero return to
        the free list. Returns how many were actually reclaimed."""
        reclaimed = 0
        with self._lock:
            for b in blocks:
                if b == TRASH_BLOCK:
                    continue
                if self._refs[b] <= 0:
                    raise ValueError(f"double free of block {b}")
                self._refs[b] -= 1
                if self._refs[b] == 0:
                    self._free.append(b)
                    reclaimed += 1
            self._stats["frees"] += reclaimed
            self._update_gate_locked()
        return reclaimed

    def refcount(self, block: int) -> int:
        with self._lock:
            return self._refs[block]

    def is_shared(self, block: int) -> bool:
        """True when a write into ``block`` would be visible to another
        owner — the copy-on-write trigger."""
        with self._lock:
            return self._refs[block] >= 2

    def cow(self, block: int) -> Optional[int]:
        """Copy-on-write bookkeeping: allocate a private replacement for
        shared ``block`` and drop this owner's reference to the original.
        The caller owns the DEVICE copy of the payload (the host side
        cannot move bytes). Returns the new block id, or None when no
        block is free. For an unshared block this is a no-op returning
        the block itself — callers can call it unconditionally."""
        with self._lock:
            if block != TRASH_BLOCK and self._refs[block] < 2:
                return block
            if not self._free:
                self._stats["alloc_failures"] += 1
                return None
            new = self._free.pop()
            self._refs[new] = 1
            if block != TRASH_BLOCK:
                self._refs[block] -= 1
                if self._refs[block] == 0:  # last other owner freed it
                    self._free.append(block)
            self._stats["allocs"] += 1
            self._stats["cow_copies"] += 1
            self._update_gate_locked()
            return new

    # -- admission watermarks ---------------------------------------------

    def _update_gate_locked(self) -> None:
        frac = len(self._free) / max(self.total, 1)
        if self._admitting and frac < self.low_watermark:
            self._admitting = False
        elif not self._admitting and frac >= self.high_watermark:
            self._admitting = True

    def admission_open(self) -> bool:
        """Hysteresis gate: False between crossing the low watermark and
        recovering past the high watermark. The engine sheds new requests
        (503 + Retry-After) and defers queued admissions while closed."""
        with self._lock:
            return self._admitting

    # -- introspection -----------------------------------------------------

    def stats(self) -> Dict:
        with self._lock:
            free = len(self._free)
            shared = sum(
                1 for b in range(1, self.num_blocks) if self._refs[b] >= 2
            )
            out = dict(self._stats)
        out.update({
            "total": self.total,
            "free": free,
            "used": self.total - free,
            "shared": shared,
            "block_size": self.block_size,
            "free_fraction": round(free / max(self.total, 1), 4),
            "admission_open": self._admitting,
            "low_watermark": self.low_watermark,
            "high_watermark": self.high_watermark,
        })
        return out


class NoBlocks(BlockAllocator):
    """The allocator of an engine whose rows own no block: a model that is
    recurrent state and nothing else reports ``block_bytes == 0``, and the
    engine then has no pool to hand out. A position needs no block, so
    every reserve is met by the empty list, a trim and a free find nothing
    to give back, admission never closes for want of blocks (rows bound
    it), and ``stats()`` reads 0 of 0."""

    MIN_BLOCKS = 1  # the trash block's index, with no pool behind it

    def __init__(self, block_size: int) -> None:
        super().__init__(1, block_size, low_watermark=0.0, high_watermark=0.0)

    def blocks_for(self, n_tokens: int) -> int:
        return 0


class WindowTable:
    """Rows' blocks in a pool whose layers read only the last ``window``
    keys: the host mirror of the pool's block table, indexed like the full
    pool's by a position's block, and each row's range of live entries.

    A row owns the blocks of logical blocks ``[lo, hi)``; every other entry
    of its table row is the trash block. :meth:`reserve` grows ``hi``,
    :meth:`release_behind` advances ``lo`` past the blocks no query at or
    after a position can see. Blocks here are never shared (a prefix that
    has lost its window blocks cannot be reused), so a free is a return to
    the free list. Not thread safe on its own: the engine calls under its
    lock; the allocator underneath keeps its own."""

    def __init__(self, alloc: BlockAllocator, rows: int, table_blocks: int,
                 window: int) -> None:
        if window % alloc.block_size:
            raise ValueError(
                f"window {window} is not whole blocks of {alloc.block_size}")
        self.alloc = alloc
        self.window = int(window)
        #: uploaded before every dispatch, like the full pool's table
        self.table = np.zeros((rows, table_blocks), np.int32)
        self._lo = [0] * rows
        self._hi = [0] * rows
        self.released = 0

    @staticmethod
    def blocks_per_row(window: int, reach: int, block_size: int,
                       table_blocks: int) -> int:
        """The most blocks a row holds at once: the window, one dispatch's
        ``reach`` of new positions (a prefill chunk, a decode segment), and
        one more where the window's edge lies inside a block."""
        return min(table_blocks, window // block_size + -(-reach // block_size) + 1)

    def held(self, row: int) -> int:
        return self._hi[row] - self._lo[row]

    def reserve(self, row: int, n_tokens: int) -> bool:
        """Grow ``row`` to cover positions ``[.., n_tokens)``; all or
        nothing."""
        need = min(self.alloc.blocks_for(n_tokens), self.table.shape[1])
        hi = self._hi[row]
        if need <= hi:
            return True
        got = self.alloc.alloc(need - hi)
        if got is None:
            return False
        self.table[row, hi:need] = got
        self._hi[row] = need
        return True

    def release_behind(self, row: int, pos: int) -> int:
        """Free the blocks of ``row`` that lie wholly before ``pos - window
        + 1``, the first key a query at ``pos`` sees; returns how many."""
        keep_from = max(0, int(pos) - self.window + 1) // self.alloc.block_size
        lo, keep_from = self._lo[row], min(keep_from, self._hi[row])
        if keep_from <= lo:
            return 0
        self._drop(row, lo, keep_from)
        self._lo[row] = keep_from
        self.released += keep_from - lo
        return keep_from - lo

    def trim(self, row: int, n_tokens: int) -> None:
        """Free the blocks beyond what ``n_tokens`` positions need."""
        keep = max(self._lo[row], self.alloc.blocks_for(n_tokens))
        if keep < self._hi[row]:
            self._drop(row, keep, self._hi[row])
            self._hi[row] = keep

    def free_row(self, row: int) -> None:
        self._drop(row, self._lo[row], self._hi[row])
        self._lo[row] = self._hi[row] = 0

    def _drop(self, row: int, lo: int, hi: int) -> None:
        self.alloc.free(int(b) for b in self.table[row, lo:hi])
        self.table[row, lo:hi] = TRASH_BLOCK

    def stats(self, live_rows: Iterable[int] = ()) -> Dict:
        """The allocator's stats, the blocks released so far, and over
        ``live_rows``: the blocks they hold and the blocks their ranges
        would span had none been released."""
        out = self.alloc.stats()
        live = list(live_rows)
        out.update(
            released=self.released,
            held=sum(self.held(r) for r in live),
            spanned=sum(self._hi[r] for r in live),
        )
        return out
