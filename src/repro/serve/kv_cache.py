"""Paged KV cache: a fixed pool of fixed-size pages + per-request page tables.

The pool is one stacked-leading-layer-dim array per tensor, head-major
inside a page so that one (page, kv head) pair is a contiguous
``[page_size, D]`` tile — the block the paged kernels DMA per grid step
(a TPU block's two minor dimensions must be whole array dimensions or
(8, 128)-aligned, which a per-head slice of a token-major page is not):

    k_pages: [L, P, Hkv, page_size, D]       (int8 payload or bf16)
    k_scale: [L, P, Hkv, page_size]          f32, only when kv_bits < 16

int4 pools pack two nibbles per byte along the head dim (the same "unified
elements" packing the weight path uses), so the payload trailing dim is D//2.

A request owns an ordered list of physical page ids (its *page table*); page
``i`` of the table holds cache positions ``[i*page_size, (i+1)*page_size)``.
Pages are allocated at admission (enough for the prompt), extended one page
at a time as decode crosses a page boundary, and returned when the request
finishes or is preempted.  The free list is LIFO so freed pages are re-used
immediately — fragmentation-free because every page is the same size.

**Sharing.**  Every allocated page carries a refcount so the prefix cache
(``serve/prefix_cache.py``) can map one physical page into many requests'
tables: ``allocate(..., prefix_pages=...)`` adopts already-written pages
read-only, ``fork_page`` copy-on-write-forks a shared page the moment a
request must write into it, and ``free`` only recycles a page when its last
reference drops.  Two hooks connect the pool to a cache layer without the
pool knowing its policy: ``release_hook(page) -> bool`` may retain a
dead page (refcount 0) for future reuse instead of freeing it, and
``reclaim_hook(n) -> list[page]`` surrenders retained pages back when the
free list runs dry — so ``can_allocate`` counts free + reclaimable.

Allocation book-keeping is host-side Python (it runs once per engine step);
the payload arrays live on device and are updated functionally (``.at[]``),
so the jit'd decode step can consume them directly.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_page(pool, src, dst):
    """In-place single-page copy (donation keeps XLA from materializing a
    whole-pool copy for a one-page CoW fork)."""
    return pool.at[:, dst].set(pool[:, src])


@dataclass
class PageCacheStats:
    pages_total: int
    pages_free: int
    high_water: int  # max pages simultaneously in use


class PagedKVCache:
    def __init__(
        self,
        cfg: ArchConfig,
        *,
        num_pages: int,
        page_size: int,
        kv_bits: int = 8,
    ):
        if kv_bits not in (4, 8, 16):
            raise ValueError(f"kv_bits must be 4, 8 or 16, got {kv_bits}")
        self.cfg = cfg
        self.num_pages = num_pages
        self.page_size = page_size
        self.kv_bits = kv_bits
        self.quantized = kv_bits < 16
        n_layers, hkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
        if kv_bits == 4 and hd % 2:
            raise ValueError(f"kv4 packs nibble pairs along head_dim; hd={hd} is odd")
        payload_dtype = jnp.int8 if self.quantized else jnp.dtype(cfg.dtype)
        dk = hd // 2 if kv_bits == 4 else hd  # packed payload trailing dim
        shape = (n_layers, num_pages, hkv, page_size, dk)
        self.k = jnp.zeros(shape, payload_dtype)
        self.v = jnp.zeros(shape, payload_dtype)
        if self.quantized:
            sshape = (n_layers, num_pages, hkv, page_size)
            self.k_scale = jnp.zeros(sshape, jnp.float32)
            self.v_scale = jnp.zeros(sshape, jnp.float32)
        else:
            self.k_scale = None
            self.v_scale = None
        self._free: list[int] = list(range(num_pages - 1, -1, -1))  # LIFO
        self._tables: dict[int, list[int]] = {}
        self._refcount: dict[int, int] = {}  # pages not on the free list
        self._high_water = 0
        # prefix-cache hooks (see module docstring); None = plain pool
        self.release_hook: Optional[Callable[[int], bool]] = None
        self.reclaim_hook: Optional[Callable[[int], list[int]]] = None
        self.reclaimable_fn: Optional[Callable[[], int]] = None

    # ------------------------------------------------------------ bookkeeping
    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_reclaimable(self) -> int:
        """Pages retained by the cache layer that eviction could free."""
        return self.reclaimable_fn() if self.reclaimable_fn else 0

    @property
    def num_allocatable(self) -> int:
        return len(self._free) + self.num_reclaimable

    def can_allocate(self, n_pages: int) -> bool:
        return self.num_allocatable >= n_pages

    def refcount(self, page: int) -> int:
        return self._refcount.get(page, 0)

    def _pop_pages(self, n_pages: int) -> list[int]:
        """Take n fresh pages, evicting retained cache pages if needed."""
        if len(self._free) < n_pages and self.reclaim_hook:
            self._free.extend(self.reclaim_hook(n_pages - len(self._free)))
        if len(self._free) < n_pages:
            raise MemoryError(
                f"need {n_pages} pages, {len(self._free)} free of {self.num_pages}"
            )
        pages = [self._free.pop() for _ in range(n_pages)]
        for p in pages:
            self._refcount[p] = 1
        return pages

    def allocate(
        self, rid: int, n_pages: int, *, prefix_pages: tuple[int, ...] = ()
    ) -> list[int]:
        """Build rid's table: ``prefix_pages`` adopted shared (incref'd, must
        already be live or cache-retained), the remainder fresh from the pool.
        ``n_pages`` is the *total* table length."""
        if rid in self._tables:
            raise KeyError(f"request {rid} already holds pages")
        if len(prefix_pages) > n_pages:
            raise ValueError("prefix_pages longer than the requested table")
        # incref the adopted pages FIRST so a reclaim for the fresh remainder
        # can never evict them out from under this request
        for p in prefix_pages:
            self.incref(p)
        try:
            fresh = self._pop_pages(n_pages - len(prefix_pages))
        except MemoryError:
            for p in prefix_pages:
                self.decref(p)
            raise
        self._tables[rid] = list(prefix_pages) + fresh
        self._note_usage()
        return self._tables[rid]

    def extend(self, rid: int, n_pages: int = 1) -> list[int]:
        pages = self._pop_pages(n_pages)
        self._tables[rid].extend(pages)
        self._note_usage()
        return pages

    def release_retained(self, page: int) -> None:
        """Return a cache-retained page (refcount 0, held out of the free
        list by the release hook) to the free list — the cache layer calls
        this when it drops such a page's entry and nothing else will ever
        free it."""
        if self.refcount(page) != 0:
            raise ValueError(f"page {page} is still referenced")
        self._free.append(page)

    def incref(self, page: int) -> None:
        """Add a reference to a live or cache-retained page.  Retained pages
        (refcount 0, held out of the free list by the release hook) revive to
        refcount 1; the cache layer must un-track them on its side."""
        self._refcount[page] = self._refcount.get(page, 0) + 1
        self._note_usage()

    def decref(self, page: int) -> None:
        n = self._refcount.get(page, 0) - 1
        if n < 0:
            raise ValueError(f"page {page} refcount underflow")
        if n > 0:
            self._refcount[page] = n
            return
        del self._refcount[page]
        # last reference gone: the cache layer may retain the page for
        # future prefix hits; otherwise it returns to the free list
        if self.release_hook is not None and self.release_hook(page):
            return
        self._free.append(page)

    def free(self, rid: int) -> None:
        for page in reversed(self._tables.pop(rid)):
            self.decref(page)

    def truncate(self, rid: int, n_tokens: int) -> list[int]:
        """Shrink rid's table to the pages covering its first ``n_tokens``
        positions, dropping the reference to every tail page (speculative
        rollback: rejected draft tokens may have grown the table past the
        accepted length).  Returns the dropped page ids — shared pages only
        lose this request's reference; a dropped page whose last reference
        this was goes through the normal release hook (so the caller must
        ``PrefixCache.forget_pages`` any page whose *content* the rollback
        invalidated BEFORE truncating, or the cache would retain it)."""
        if n_tokens < 0:
            raise ValueError(f"n_tokens must be >= 0, got {n_tokens}")
        table = self._tables[rid]
        keep = self.pages_for(n_tokens)
        if keep >= len(table):
            return []
        dropped = table[keep:]
        # replace rather than mutate: allocate()/extend() hand out the live
        # table list, so callers may still hold an alias of the old one
        self._tables[rid] = table[:keep]
        for page in reversed(dropped):
            self.decref(page)
        return dropped

    def fork_page(self, rid: int, idx: int) -> int:
        """Copy-on-write: replace slot ``idx`` of rid's table with a private
        copy of the page (payload + scales copied on device), dropping the
        reference to the shared original.  Returns the new page id."""
        old = self._tables[rid][idx]
        (new,) = self._pop_pages(1)
        self.k = _copy_page(self.k, old, new)
        self.v = _copy_page(self.v, old, new)
        if self.quantized:
            self.k_scale = _copy_page(self.k_scale, old, new)
            self.v_scale = _copy_page(self.v_scale, old, new)
        self._tables[rid][idx] = new
        self.decref(old)
        return new

    def table(self, rid: int) -> list[int]:
        return list(self._tables[rid])

    def capacity_tokens(self, rid: int) -> int:
        """Cache positions currently addressable by rid's page table."""
        return len(self._tables[rid]) * self.page_size

    def table_array(self, rids: list[int], width: int) -> np.ndarray:
        """[B, width] int32 page-table matrix, zero-padded (padded entries
        gather page 0; they are masked out by per-row lengths downstream).
        Host-side np so the engine can batch-pad without a device
        round-trip; jit'd consumers convert on entry."""
        out = np.zeros((len(rids), width), np.int32)
        for i, rid in enumerate(rids):
            t = self._tables[rid]
            out[i, : len(t)] = t
        return out

    def stats(self) -> PageCacheStats:
        return PageCacheStats(self.num_pages, len(self._free), self._high_water)

    def _note_usage(self) -> None:
        self._high_water = max(self._high_water, self.num_pages - len(self._free))

    # -------------------------------------------------------------- payloads
    def set_pools(self, k, v, k_scale=None, v_scale=None) -> None:
        """Adopt pool arrays returned by the jitted decode step (which
        scatters each new token into its page in-kernel)."""
        self.k = k
        self.v = v
        if self.quantized:
            self.k_scale = k_scale
            self.v_scale = v_scale

    def write_prompt(self, rid: int, k, v, k_scale=None, v_scale=None) -> None:
        """Scatter a prefilled contiguous cache row into this request's pages.

        k/v: [L, S_pad, Hkv, Dk] token-major, scales [L, S_pad, Hkv], with
        S_pad == len(table) * page_size (the engine prefills with max_len
        rounded up to a page multiple).
        """
        pages = jnp.asarray(self._tables[rid], jnp.int32)
        n, ps = len(self._tables[rid]), self.page_size
        if k.shape[1] != n * ps:
            raise ValueError(f"prompt cache len {k.shape[1]} != {n}*{ps}")

        def scatter(pool, row):  # [L, n*ps, Hkv, ...] -> [L, n, Hkv, ps, ...]
            paged = jnp.swapaxes(row.reshape(row.shape[0], n, ps, *row.shape[2:]), 2, 3)
            return pool.at[:, pages].set(paged.astype(pool.dtype))

        self.k = scatter(self.k, k)
        self.v = scatter(self.v, v)
        if self.quantized:
            self.k_scale = scatter(self.k_scale, k_scale)
            self.v_scale = scatter(self.v_scale, v_scale)

    def write_token(self, rids: list[int], positions: np.ndarray, new_kv) -> None:
        """Write one new token's K/V for a batch of requests.

        positions[i] is the cache position of request rids[i]'s new token;
        new_kv is (k, v[, k_scale, v_scale]) with k/v [L, B, Hkv, Dk] and
        scales [L, B, Hkv].
        """
        page_ids = np.array(
            [self._tables[r][p // self.page_size] for r, p in zip(rids, positions)],
            np.int32,
        )
        self.set_pools(*scatter_tokens(
            (self.k, self.v, self.k_scale, self.v_scale), new_kv,
            jnp.asarray(page_ids), jnp.asarray(positions % self.page_size, jnp.int32),
        ))


def scatter_tokens(pools, new_kv, page_ids, offs, mode=None):
    """Scatter per-token K/V into head-major pools: ``new_kv`` leaves are
    [L, *idx, Hkv(, Dk)] for integer index arrays ``page_ids``/``offs`` of
    shape ``idx``; pools is (k, v, k_scale, v_scale) with None scales for
    kv16, and so is the result.  ``mode="drop"`` discards out-of-range page
    ids (the jitted steps' padding rows)."""
    def scatter(pool, new):
        # pool[:, page, :, off] puts the index dims first: [*idx, L, Hkv, ...]
        new = jnp.moveaxis(new, 0, page_ids.ndim)
        return pool.at[:, page_ids, :, offs].set(new.astype(pool.dtype), mode=mode)

    return tuple(
        None if pool is None else scatter(pool, new)
        for pool, new in zip(pools, tuple(new_kv) + (None,) * (4 - len(new_kv)))
    )
