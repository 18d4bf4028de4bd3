"""Pooled paged storage for device-resident prefix-cache KV segments.

The radix prefix cache historically stored each cached segment as its own
contiguous device array per cache leaf. Two costs follow: (1) hit-seeding
must COPY every matched segment into the decode row (`assemble_row`), a full
HBM round-trip of the prefix bytes plus a per-(segment-shape, takes)
compile-cache zoo; (2) the allocator sees thousands of odd-sized arrays.

`PagedKVPool` replaces that with fixed-size pages inside one pooled buffer
per cache leaf. Segments become `PagedSegment` — a list of page ids — and
hit-seeding gathers the pages straight into the decode row's layout with one
XLA program per row capacity (`gather_pages`: a take over the page axis).
It is plain XLA on every backend: a Pallas gather cannot express the pool
(16-token pages are not a legal TPU block — Mosaic wants the last block
dimension a multiple of 128), while XLA picks the pool's physical layout
itself (page-token axis off the lanes) and gathers whole pages.

Design points (the invariants tests pin):

- **page_tokens == the radix tree's block (MIN_BUCKET, 16).** Match takes
  and `_split` boundaries are always block-aligned (prefix_cache.py), so a
  page never straddles a split: `PagedSegment.split` is a zero-copy
  repartition of the page-id list and never frees or copies a page. A
  *tuned* page size would break that invariant the moment a split landed
  mid-page, so the pool geometry is not a knob.
- **Layout**: a cache leaf `(..., tokens)` is stored as pool pages
  `(num_pages, R, page_tokens)` with `R = prod(leading dims)`; gather
  returns `(..., max_pages * page_tokens)` — exactly the decode row's shape
  with capacity last, zeros past the table's `-1` sentinels (matching the
  zeros `init_cache` seeds the copy path with — bit-identity needs the
  tails equal too).
- **Donated scatter**: `store` writes pages via a jitted
  `pool.at[ids].set(blocks)` with the pool buffer donated, so the pool is
  updated in place instead of doubling its HBM footprint per insert.
  Consequence: the pool must only be touched from the engine loop thread —
  a concurrent reader of the pre-donation buffer would race buffer
  deletion. The engine materializes `PagedSegment`s on the loop before
  handing KV to any off-loop exporter.
- **Lazy sizing**: leaf dtypes/shapes aren't known until the first stored
  segment, so construction takes a byte budget and the first `store` sizes
  `num_pages = budget // page_nbytes`. A budget too small for one page
  disables the pool (every `store` returns None and the engine keeps the
  contiguous copy path — the documented fallback).
"""

from __future__ import annotations

import functools
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["PagedKVPool", "PagedSegment"]


@jax.jit
def gather_pages(pool: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Gather ``pool[table]`` — pool ``(num_pages, R, page_tokens)``, table
    ``(max_pages,) int32`` with ``-1`` marking empty slots — into a contiguous
    ``(R, max_pages*page_tokens)`` row, zeros where ``table < 0`` (matching
    the zeros `init_cache` seeds the copy path's row with)."""
    r_dim = pool.shape[1]
    pages = pool[jnp.maximum(table, 0)]                  # (max_pages, R, PT)
    pages = jnp.where((table >= 0)[:, None, None], pages, 0)
    return jnp.swapaxes(pages, 0, 1).reshape(r_dim, -1)


@functools.partial(jax.jit, donate_argnums=(0,))
def _store_pages(pool: jnp.ndarray, leaf: jnp.ndarray, ids: jnp.ndarray):
    """Scatter ``leaf`` ``(..., n*page_tokens)`` into ``pool`` at ``ids``."""
    _, r_dim, page_tokens = pool.shape
    blocks = leaf.reshape(r_dim, -1, page_tokens).transpose(1, 0, 2)
    return pool.at[ids].set(blocks)


class PagedSegment:
    """A prefix-cache segment held as pages of a :class:`PagedKVPool`.

    Duck-typed against the loose-dict segments the radix tree otherwise
    holds: `nbytes` feeds the tree's byte accounting, `split` backs
    `_split`'s edge cut (zero-copy page repartition), `materialize` produces
    the loose dict for host spill / wire export, and `close` returns the
    pages to the pool when the tree forgets the node.
    """

    __slots__ = ("pool", "pages", "tokens", "_closed")

    def __init__(self, pool: "PagedKVPool", pages: list[int], tokens: int):
        self.pool = pool
        self.pages = pages
        self.tokens = tokens
        self._closed = False

    @property
    def nbytes(self) -> int:
        return len(self.pages) * self.pool.page_nbytes

    def split(self, m: int) -> tuple["PagedSegment", "PagedSegment"]:
        """(first ``m`` slots, rest) — page-list repartition, no copies.
        ``m`` is block-aligned by the radix tree's contract, and
        page_tokens == block, so the boundary is always a page boundary."""
        pt = self.pool.page_tokens
        if m % pt or not 0 < m < self.tokens:
            raise ValueError(f"split at {m} not page-aligned for {self.tokens}")
        cut = m // pt
        upper = PagedSegment(self.pool, self.pages[:cut], m)
        lower = PagedSegment(self.pool, self.pages[cut:], self.tokens - m)
        self._closed = True  # ownership moved to the two halves
        return upper, lower

    def materialize(self) -> dict[str, jnp.ndarray]:
        """The equivalent loose segment: each leaf ``(..., tokens)``."""
        return self.pool.materialize(self.pages, self.tokens)

    def items(self):
        return self.materialize().items()

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self.pool.free(self.pages)


class PagedKVPool:
    """Fixed-page pooled storage for one engine's prefix-cache KV.

    Not thread-safe: store/gather/free must run on the engine loop thread
    (see module docstring — the donated scatter makes this load-bearing).
    """

    def __init__(self, budget_bytes: int, page_tokens: int = 16):
        if page_tokens <= 0:
            raise ValueError("page_tokens must be positive")
        self.page_tokens = int(page_tokens)
        self.budget_bytes = int(budget_bytes)
        self.num_pages = 0
        self.page_nbytes = 0
        self._leaves: dict[str, jnp.ndarray] | None = None
        self._shapes: dict[str, tuple[int, ...]] = {}  # leading dims per leaf
        self._free: list[int] = []

    # -- sizing ----------------------------------------------------------
    def _ensure(self, segment: dict[str, Any]) -> bool:
        """Allocate pool leaves from the first segment's leaf specs. Returns
        False when the budget can't hold even one page (pool disabled)."""
        if self._leaves is not None:
            return self.num_pages > 0
        pt = self.page_tokens
        specs: dict[str, tuple[tuple[int, ...], Any]] = {}
        page_nbytes = 0
        for name, leaf in segment.items():
            shape = tuple(int(d) for d in leaf.shape)
            r_dim = int(np.prod(shape[:-1], dtype=np.int64)) if shape[:-1] else 1
            specs[name] = (shape[:-1], leaf.dtype)
            page_nbytes += r_dim * pt * jnp.dtype(leaf.dtype).itemsize
        self.page_nbytes = page_nbytes
        self.num_pages = max(0, self.budget_bytes // max(1, page_nbytes))
        if self.num_pages <= 0:
            self._leaves = {}
            return False
        self._shapes = {name: lead for name, (lead, _) in specs.items()}
        self._leaves = {
            name: jnp.zeros(
                (self.num_pages, int(np.prod(lead, dtype=np.int64)) if lead else 1, pt),
                dtype=dtype,
            )
            for name, (lead, dtype) in specs.items()
        }
        self._free = list(range(self.num_pages - 1, -1, -1))
        return True

    @property
    def free_pages(self) -> int:
        return len(self._free)

    # -- store / free ----------------------------------------------------
    def store(self, segment: dict[str, Any]) -> list[int] | None:
        """Write a loose segment's pages into the pool; returns the page-id
        list, or None when it doesn't fit (unaligned, pool full, or pool
        disabled) — the caller keeps the loose segment in that case."""
        if not segment or not self._ensure(segment):
            return None
        tokens = int(next(iter(segment.values())).shape[-1])
        if tokens <= 0 or tokens % self.page_tokens:
            return None
        needed = tokens // self.page_tokens
        if needed > len(self._free):
            return None
        if set(segment) != set(self._leaves):
            return None  # leaf structure drifted from the first segment
        ids = [self._free.pop() for _ in range(needed)]
        ids_arr = jnp.asarray(ids, dtype=jnp.int32)
        for name, leaf in segment.items():
            self._leaves[name] = _store_pages(
                self._leaves[name], jnp.asarray(leaf), ids_arr
            )
        return ids

    def free(self, pages: list[int]) -> None:
        self._free.extend(pages)

    # -- gather ----------------------------------------------------------
    def gather_row(self, table: np.ndarray) -> dict[str, jnp.ndarray]:
        """Gather pages into a contiguous row: ``table`` is ``(max_pages,)``
        int32 page ids with ``-1`` marking empty tail slots; each returned
        leaf is ``(..., max_pages*page_tokens)`` with zeros in the tail —
        the decode row's exact layout."""
        table = jnp.asarray(table, dtype=jnp.int32)
        out: dict[str, jnp.ndarray] = {}
        for name, pool in self._leaves.items():
            flat = gather_pages(pool, table)  # (R, max_pages*page_tokens)
            out[name] = flat.reshape(*self._shapes[name], flat.shape[-1])
        return out

    def materialize(self, pages: list[int], tokens: int) -> dict[str, jnp.ndarray]:
        """Loose-dict copy of a page run (host spill / wire export path)."""
        row = self.gather_row(np.asarray(pages, dtype=np.int32))
        return {name: leaf[..., :tokens] for name, leaf in row.items()}
