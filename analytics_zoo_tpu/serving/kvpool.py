"""Host-side KV block-pool allocator + prefix-sharing index (PR 18).

The device side of paged KV lives in ``ops/paged_attention.py`` (pool
buffers + block-table reads) and ``serving/generate.py`` (the compiled
prefill/commit/decode programs).  This module is the HOST side the
scheduler thread drives at admission/free boundaries — plain python, no
device traffic:

- ``BlockPool`` — fixed set of ``block_len``-token block ids with a free
  list and per-block REFCOUNTS.  Block id 0 is the reserved TRASH block:
  table padding and inactive decode rows point at it, so their in-program
  writes land somewhere harmless instead of corrupting live state (the
  device arrays are allocated with ``n_blocks + 1`` rows).  A block frees
  when its last holder (slot or prefix-cache entry) releases it —
  copy-on-write degenerates to pure sharing because SHARED blocks are
  always full prompt-prefix blocks, which are immutable by construction
  (the decode cursor starts past them and never moves backwards).
- ``PrefixIndex`` — full-block prompt prefixes, keyed by their exact
  token bytes (no hash collisions at serving prompt lengths), LRU
  ordered.  ``lookup`` returns the LONGEST registered prefix of a new
  prompt and takes a reference on its blocks for the admitting slot;
  ``register`` parks a freshly-prefetched prompt's full blocks with a
  CACHE hold of their own, so the pages outlive the request that paid
  their prefill.  ``evict`` drops LRU entries (their cache hold) when the
  allocator runs dry — pages still referenced by live slots stay
  resident until those slots free.

Thread contract: scheduler-thread-only, like the rest of the batcher's
host state.
"""

from __future__ import annotations

from collections import OrderedDict, deque
from typing import Dict, List, Optional, Tuple

import numpy as np

TRASH_BLOCK = 0


class BlockPool:
    """``n_blocks`` usable blocks of ``block_len`` tokens (ids 1 ..
    n_blocks; id 0 is the trash block and is never handed out)."""

    def __init__(self, n_blocks: int, block_len: int):
        if n_blocks < 1 or block_len < 1:
            raise ValueError(
                f"need n_blocks >= 1 and block_len >= 1, got "
                f"{n_blocks}/{block_len}")
        self.n_blocks = int(n_blocks)
        self.block_len = int(block_len)
        self._free: deque = deque(range(1, self.n_blocks + 1))
        self._refs: Dict[int, int] = {}

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.n_blocks - len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Claim ``n`` blocks at refcount 1, or None if the pool can't
        cover them (nothing is claimed on failure)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        ids = [self._free.popleft() for _ in range(n)]
        for b in ids:
            self._refs[b] = 1
        return ids

    def addref(self, ids) -> None:
        for b in ids:
            if b not in self._refs:
                raise ValueError(f"addref on unallocated block {b}")
            self._refs[b] += 1

    def release(self, ids) -> int:
        """Drop one reference per id; blocks hitting zero return to the
        free list.  Returns how many blocks actually freed."""
        freed = 0
        for b in ids:
            n = self._refs.get(b)
            if n is None:
                raise ValueError(f"release on unallocated block {b}")
            if n > 1:
                self._refs[b] = n - 1
            else:
                del self._refs[b]
                self._free.append(b)
                freed += 1
        return freed

    def refcount(self, block_id: int) -> int:
        return self._refs.get(block_id, 0)


class PrefixIndex:
    """LRU index of full-block prompt prefixes -> resident pool blocks."""

    def __init__(self, pool: BlockPool, max_entries: int = 256):
        self.pool = pool
        self.max_entries = max(1, int(max_entries))
        # key (prefix token bytes) -> tuple of block ids; LRU order
        self._entries: "OrderedDict[bytes, Tuple[int, ...]]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    @staticmethod
    def _key(tokens: np.ndarray) -> bytes:
        return np.ascontiguousarray(tokens, np.int32).tobytes()

    def lookup(self, tokens: np.ndarray,
               max_blocks: Optional[int] = None) -> Tuple[int, List[int]]:
        """Longest registered full-block prefix of ``tokens`` -> (number
        of shared blocks, their pool ids), with one reference taken per
        block FOR THE CALLER (the admitting slot releases them with the
        rest of its table).  ``max_blocks`` caps the share (admission
        leaves at least one suffix token to prefill, so the request still
        produces first-token logits).  (0, []) on miss."""
        bl = self.pool.block_len
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        k_max = tokens.size // bl
        if max_blocks is not None:
            k_max = min(k_max, int(max_blocks))
        for k in range(k_max, 0, -1):
            ids = self._entries.get(self._key(tokens[:k * bl]))
            if ids is None:
                continue
            self._entries.move_to_end(self._key(tokens[:k * bl]))
            self.pool.addref(ids)
            self.hits += 1
            return k, list(ids)
        self.misses += 1
        return 0, []

    def register(self, tokens: np.ndarray, block_ids) -> bool:
        """Park ``tokens`` (exactly len(block_ids) * block_len of them) ->
        ``block_ids`` with a cache hold on each block.  No-op (False) when
        the prefix is already resident — the duplicate's blocks simply
        stay private to their slot.  Registering past ``max_entries``
        evicts the LRU entry first."""
        bl = self.pool.block_len
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if tokens.size != len(block_ids) * bl:
            raise ValueError(
                f"register: {tokens.size} tokens != {len(block_ids)} "
                f"blocks * block_len {bl}")
        if not block_ids:
            return False
        key = self._key(tokens)
        if key in self._entries:
            self._entries.move_to_end(key)
            return False
        while len(self._entries) >= self.max_entries:
            self._evict_one()
        self.pool.addref(block_ids)
        self._entries[key] = tuple(block_ids)
        return True

    def _evict_one(self) -> int:
        key, ids = self._entries.popitem(last=False)
        self.evictions += 1
        return self.pool.release(ids)

    def evict_for(self, need_blocks: int) -> int:
        """Drop LRU entries until ``need_blocks`` are free in the pool or
        the index is empty.  Returns blocks actually freed (entries whose
        blocks are still held by live slots free nothing NOW — their
        cache hold is dropped, so they free when the slots do)."""
        freed = 0
        while self.pool.free_blocks < need_blocks and self._entries:
            freed += self._evict_one()
        return freed

    def clear(self) -> int:
        """Drop every entry (and its cache hold): the pages' contents are
        gone, e.g. the pool was rebuilt after a failed donated call."""
        freed = 0
        while self._entries:
            freed += self._evict_one()
        return freed

    def stats(self) -> Dict:
        return {"entries": len(self._entries), "hits": self.hits,
                "misses": self.misses, "evictions": self.evictions}
