"""Paged KV cache: a shared block pool + per-request block tables.

The dense decode cache allocates ``max_len`` KV slots per request up front,
so a 32-token chat and a 32k-token document pay the same HBM.  The paged
cache (vLLM-style) splits KV storage into fixed-size *blocks*:

  * every attention layer owns a pool ``k_pool/v_pool (P, Kv, bs, D)`` —
    P blocks of bs positions each, shared by all in-flight requests; a
    block keeps each kv head's (bs, D) slab whole, which is the tile the
    flash-decode kernel loads;
  * each request holds a *block table* row ``tbl (max_blocks,)`` mapping
    its logical block i to a pool block id (-1 = unallocated) and a
    context length ``ctx`` counting KV entries written so far;
  * the host-side :class:`BlockAllocator` hands out pool block ids with a
    free list and per-block refcounts, so completed requests return their
    blocks and ``fork`` can share a finished prefix between requests.

Pools thread through ``transformer.forward``'s layer scan exactly like the
dense caches (stacked over the scanned blocks); the block table and context
lengths are *shared* read-only state passed alongside (``cache['paged']``)
— layers never mutate them, the engine advances ``ctx`` between steps so
every layer stays in sync by construction.

Absolute position p of request b lives at ``(tbl[b, p // bs], :, p % bs)``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax.numpy as jnp
import numpy as np


class PagedCacheError(RuntimeError):
    pass


@dataclasses.dataclass
class BlockAllocator:
    """Host-side pool bookkeeping: free list + refcounts.

    Allocation is all-or-nothing (``allocate`` returns None rather than a
    partial grant) so the scheduler can reserve a request's full footprint
    at admission and never OOM mid-flight.  ``fork`` shares fully-written
    blocks by refcount — a shared block must be treated copy-on-write by
    the caller (the engine copies the partial tail block before a forked
    request appends to it).
    """
    n_blocks: int
    block_size: int

    def __post_init__(self):
        self._free: List[int] = list(range(self.n_blocks - 1, -1, -1))
        self._refs = np.zeros(self.n_blocks, dtype=np.int32)

    @property
    def n_free(self) -> int:
        return len(self._free)

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold n_tokens positions."""
        return -(-max(n_tokens, 0) // self.block_size)

    def allocate(self, n: int) -> Optional[List[int]]:
        """Grant n blocks (refcount 1 each) or None if the pool is short."""
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        self._refs[out] = 1
        return out

    def free(self, blocks: List[int]) -> None:
        for b in blocks:
            if self._refs[b] <= 0:
                raise PagedCacheError(f"double free of block {b}")
            self._refs[b] -= 1
            if self._refs[b] == 0:
                self._free.append(b)

    def fork(self, blocks: List[int]) -> List[int]:
        """Share an existing chain: refcount++ on every block, same ids.

        The forked request reads the shared prefix for free; before it
        *writes* (appends into the last, partially-filled block) the
        caller must replace that block via ``copy_on_write``.
        """
        for b in blocks:
            if self._refs[b] <= 0:
                raise PagedCacheError(f"fork of unallocated block {b}")
            self._refs[b] += 1
        return list(blocks)

    def copy_on_write(self, block: int) -> Optional[int]:
        """Detach one shared block: returns a fresh private block id (the
        caller copies the pool rows device-side), or the same id if the
        block was already private, or None if the pool is exhausted."""
        if self._refs[block] <= 1:
            return block
        fresh = self.allocate(1)
        if fresh is None:
            return None
        self._refs[block] -= 1
        return fresh[0]


def init_paged_pools(cfg, n_blocks: int, block_size: int, dtype,
                     rt=None):
    """Per-layer {k_pool, v_pool} pytree mirroring ``transformer.init_cache``
    (prefix list + stacked scanned blocks) so pools thread through the
    layer scan unchanged.  Every layer must be attention — hybrids keep the
    dense cache path."""
    from repro.models.transformer import _tree_stack, layer_plan

    kv, hd = cfg.kv_heads, cfg.head_dim_
    for i in range(cfg.n_layers):
        if cfg.layer_kind(i) != "attn":
            raise PagedCacheError(
                f"paged cache requires attention-only stacks; layer {i} "
                f"is {cfg.layer_kind(i)!r}")

    def one_layer():
        return {"kv": {
            "k_pool": jnp.zeros((n_blocks, kv, block_size, hd), dtype),
            "v_pool": jnp.zeros((n_blocks, kv, block_size, hd), dtype),
        }}

    prefix, start, period, nb = layer_plan(cfg)
    return {
        "prefix": [one_layer() for _ in prefix],
        "blocks": [_tree_stack([one_layer() for _ in range(nb)])
                   for _ in range(period)] if nb else [],
    }


def init_paged_cache(cfg, n_slots: int, n_blocks: int, block_size: int,
                     max_blocks_per_req: int, dtype, rt=None):
    """Full paged decode cache: pools + shared block-table/ctx state.

    ``tbl (n_slots, max_blocks_per_req)`` int32 (-1 = unallocated);
    ``ctx (n_slots,)`` int32 KV entries written per slot.
    """
    cache = init_paged_pools(cfg, n_blocks, block_size, dtype, rt)
    cache["paged"] = {
        "tbl": jnp.full((n_slots, max_blocks_per_req), -1, jnp.int32),
        "ctx": jnp.zeros((n_slots,), jnp.int32),
    }
    return cache
