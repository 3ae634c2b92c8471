"""Pallas TPU flash-decode: GQA split-K attention over a paged KV cache.

Decode attention is memory-bound — one query token against a long KV
context — so the kernel layout follows flash-decode rather than FA2:

  * grid = (B * Kv, n_splits, blocks_per_split).  Each (request, kv-head)
    pair fans out over ``n_splits`` independent K-splits that scan their
    slice of the block table in parallel grid cells; the minormost axis
    walks the KV *blocks* of one split sequentially, carrying the running
    (m, l, acc) online-softmax state in VMEM scratch (same persistent-
    accumulator pattern as ``flash_attention``'s kv axis).
  * the block table and per-request context lengths ride in as *scalar
    prefetch* operands (``PrefetchScalarGridSpec``): the k/v BlockSpec
    index maps read ``tbl[b, s * bps + j]`` to DMA exactly the pool block
    this grid cell needs — the gather lives in the index map, the kernel
    body never sees a pool-sized tensor.
  * the pools are laid out (P, Kv, bs, D), so one grid cell's K/V block
    (1, 1, bs, D) is a whole (bs, D) slab: the TPU tiles the last two block
    dims to (8, 128), and slicing one kv head out of a (bs, Kv, D) block
    would cut the tiled Kv dim.  For the same reason the per-split m and l
    leave as (G, 1) columns, not as a slice of the splits axis.
  * each split writes its *partial* (acc, m, l); the host-side wrapper
    merges splits with one logsumexp combine (empty splits carry
    m = -inf, l = 0 and vanish).  GQA comes for free: the G query heads
    that share a kv head form the (G, bs) score tile of one grid cell.

Numerics match ``kernels.ref.paged_attention_ref`` to fp32 round-off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(tbl_ref, ctx_ref, q_ref, k_ref, v_ref,
                   o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr,
                   *, scale, block_size, bps, kv_heads):
    b = pl.program_id(0)                  # request * kv_head
    s = pl.program_id(1)                  # K-split
    j = pl.program_id(2)                  # block within the split (seq.)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                 # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)                 # (bs, D)
    v = v_ref[0, 0].astype(jnp.float32)

    sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (G, bs)

    # absolute KV positions of this pool block; everything at or past the
    # request's context length is masked (covers tail blocks of the padded
    # table — their clamped gathers contribute nothing)
    n_valid = ctx_ref[b // kv_heads]
    k_pos = (s * bps + j) * block_size + jax.lax.broadcasted_iota(
        jnp.int32, sc.shape, 1)
    mask = k_pos < n_valid
    sc = jnp.where(mask, sc, NEG_INF)

    m_prev = m_scr[...]                                 # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_scr[...] = m_new
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(p, v)

    @pl.when(j == bps - 1)
    def _finalize():
        # partial (unnormalized) outputs: the wrapper's logsumexp combine
        # across splits does the single global normalization
        o_ref[0, 0] = acc_scr[...].astype(o_ref.dtype)
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]


@functools.partial(jax.jit, static_argnames=("n_splits", "interpret"))
def flash_decode(q, k_pool, v_pool, tbl, ctx, *, n_splits=4,
                 interpret=False):
    """q (B, 1, H, D), pools (P, Kv, bs, D), tbl (B, max_blocks) int32,
    ctx (B,) int32 -> (B, 1, H, D).

    tbl entries < 0 (unallocated) are clamped for the gather; their
    positions are >= ctx so the mask removes them.  Full (non-windowed)
    attention only — the jnp paged path handles sliding windows.
    """
    B, Sq, H, D = q.shape
    P, Kv, bs, _ = k_pool.shape
    assert Sq == 1 and H % Kv == 0, (q.shape, Kv)
    G = H // Kv
    nb = tbl.shape[1]

    splits = min(n_splits, nb)
    bps = -(-nb // splits)                  # blocks per split
    nb_pad = splits * bps
    safe_tbl = jnp.clip(tbl, 0, P - 1)
    if nb_pad != nb:                        # padded tail blocks are masked
        safe_tbl = jnp.pad(safe_tbl, ((0, 0), (0, nb_pad - nb)))

    qg = q.reshape(B, Kv, G, D)             # heads grouped by kv head

    kernel = functools.partial(
        _decode_kernel, scale=D ** -0.5, block_size=bs, bps=bps,
        kv_heads=Kv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * Kv, splits, bps),
        # index maps receive (*grid_indices, *scalar_prefetch_refs)
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, s, j, tbl, ctx, Kv=Kv: (b // Kv, b % Kv,
                                                           0, 0)),
            pl.BlockSpec((1, 1, bs, D),
                         lambda b, s, j, tbl, ctx, Kv=Kv, bps=bps:
                         (tbl[b // Kv, s * bps + j], b % Kv, 0, 0)),
            pl.BlockSpec((1, 1, bs, D),
                         lambda b, s, j, tbl, ctx, Kv=Kv, bps=bps:
                         (tbl[b // Kv, s * bps + j], b % Kv, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, s, j, tbl, ctx: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda b, s, j, tbl, ctx: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda b, s, j, tbl, ctx: (b, s, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B * Kv, splits, G, D), jnp.float32),
            jax.ShapeDtypeStruct((B * Kv, splits, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B * Kv, splits, G, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_decode",
    )(safe_tbl, ctx, qg, k_pool, v_pool)

    # logsumexp merge across splits: empty splits (m=-inf, l=0) vanish
    m, l = m[..., 0], l[..., 0]                          # (B*Kv, S, G)
    m_max = jnp.max(m, axis=1, keepdims=True)            # (B*Kv, 1, G)
    alpha = jnp.exp(m - m_max)                           # (B*Kv, S, G)
    l_tot = jnp.sum(l * alpha, axis=1)                   # (B*Kv, G)
    out = jnp.sum(acc * alpha[..., None], axis=1)        # (B*Kv, G, D)
    out = out / jnp.maximum(l_tot, 1e-30)[..., None]
    return out.reshape(B, 1, H, D).astype(q.dtype)
