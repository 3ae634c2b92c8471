"""Attention: GQA with RoPE/M-RoPE, sliding windows, qk-norm, KV caches.

Two execution paths:
  * dense masked attention for short sequences / decode (1 query token);
  * a blocked online-softmax path (lax.scan over KV chunks inside a scan
    over Q chunks) so that S x S score matrices are never materialized --
    this is what makes 32k-prefill fit in ``memory_analysis`` and it is the
    pure-jnp oracle for the Pallas flash kernel in ``repro.kernels``.

All functions are batch-first: q (B, Sq, H, D), k/v (B, Skv, Kv, D).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import (Runtime, apply_rope, rms_norm_headwise)

NEG_INF = -1e30


def init_attention(cfg, key):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": jax.random.normal(ks[0], (d, h * hd)) * s,
        "wk": jax.random.normal(ks[1], (d, kv * hd)) * s,
        "wv": jax.random.normal(ks[2], (d, kv * hd)) * s,
        "wo": jax.random.normal(ks[3], (h * hd, d)) * ((h * hd) ** -0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,))
        p["bk"] = jnp.zeros((kv * hd,))
        p["bv"] = jnp.zeros((kv * hd,))
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,))
        p["k_norm"] = jnp.ones((hd,))
    return p


# ---------------------------------------------------------------------------
# masking helpers
# ---------------------------------------------------------------------------

def _mask(q_pos, k_pos, window):
    """(..., Sq, Skv) boolean: causal (+ sliding window) visibility."""
    m = k_pos[..., None, :] <= q_pos[..., :, None]
    if window:
        m &= k_pos[..., None, :] > (q_pos[..., :, None] - window)
    return m


# ---------------------------------------------------------------------------
# dense path
# ---------------------------------------------------------------------------

def _attend_dense(q, k, v, q_pos, k_pos, window, scale):
    """q (B,Sq,H,D), k/v (B,Skv,Kv,D); q_pos (Sq,), k_pos (Skv,)."""
    B, Sq, H, D = q.shape
    Kv = k.shape[2]
    G = H // Kv
    qg = q.reshape(B, Sq, Kv, G, D)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * scale
    mask = _mask(q_pos, k_pos, window)                       # (Sq, Skv)
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v)
    return out.reshape(B, Sq, H, D)


# ---------------------------------------------------------------------------
# blocked online-softmax path (flash-style, pure jnp)
# ---------------------------------------------------------------------------

def _attend_blocked(q, k, v, window, scale, q_chunk, kv_chunk):
    """Causal self-attention, q_pos == k_pos == arange(S).

    Scans KV chunks with running (max, denom, acc); scans Q chunks outside.
    Skips fully-masked KV chunks' contribution via masking (the scan itself
    still visits them; XLA removes the FLOPs only on TPU via the Pallas
    kernel -- here correctness + memory are what matter).

    Sequence lengths that are not a multiple of the chunk sizes are padded
    to the next common multiple; the causal mask excludes padded kv
    positions (k_pos > every real q_pos) and padded q rows are sliced off.
    """
    B, S, H, D = q.shape
    Kv = k.shape[2]
    G = H // Kv
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, S)
    mult = math.lcm(q_chunk, kv_chunk)
    Sp = -(-S // mult) * mult
    if Sp != S:
        pad = [(0, 0), (0, Sp - S), (0, 0), (0, 0)]
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    nq, nk = Sp // q_chunk, Sp // kv_chunk

    qg = q.reshape(B, nq, q_chunk, Kv, G, D).transpose(1, 0, 2, 3, 4, 5)
    kc = k.reshape(B, nk, kv_chunk, Kv, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, kv_chunk, Kv, D).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def q_step(_, qi_qblk):
        qi, qblk = qi_qblk                                   # qblk (B,qc,Kv,G,D)
        q_pos = qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, kj_kv):
            m_run, l_run, acc = carry
            kj, kblk, vblk = kj_kv
            k_pos = kj * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qblk, kblk).astype(jnp.float32) * scale
            msk = _mask(q_pos, k_pos, window)                # (qc, kc)
            s = jnp.where(msk[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, s.max(-1))
            alpha = jnp.exp(m_run - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l_run * alpha + p.sum(-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(vblk.dtype), vblk).astype(jnp.float32)
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, Kv, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Kv, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Kv, G, q_chunk, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), kc, vc))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)                     # (B,Kv,G,qc,D)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qg))
    # outs: (nq, B, Kv, G, qc, D) -> (B, Sp, H, D) -> drop padded rows
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sp, H, D)
    return out[:, :S]


def sdpa_causal(q, k, v, window=0, rt: Optional[Runtime] = None):
    """Self-attention where q/k/v cover the same positions 0..S-1."""
    rt = rt or Runtime()
    S = q.shape[1]
    scale = q.shape[-1] ** -0.5
    if rt.attn_impl == "pallas" and S >= 128 and q.shape[-1] % 64 == 0:
        # TPU hot path: Pallas flash kernel (interpret-mode on CPU)
        from repro.kernels import ops as kernel_ops
        return rt.per_shard(
            lambda q, k, v: kernel_ops.attention(q, k, v, window=window),
            q, k, v, heads=True)
    if S <= rt.attn_min_chunked_len:
        pos = jnp.arange(S)
        return _attend_dense(q, k, v, pos, pos, window, scale)
    return _attend_blocked(q, k, v, window, scale, rt.attn_q_chunk, rt.attn_kv_chunk)


def sdpa_decode(q, k_cache, v_cache, k_pos, cur_pos, window=0):
    """One-token decode: q (B,1,H,D) against cache (B,Sc,Kv,D).

    k_pos: (Sc,) absolute position held in each cache slot (-1 = empty);
    cur_pos: scalar position of the query token.
    """
    scale = q.shape[-1] ** -0.5
    B, Sq, H, D = q.shape
    Kv = k_cache.shape[2]
    G = H // Kv
    qg = q.reshape(B, Sq, Kv, G, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache).astype(jnp.float32) * scale
    valid = (k_pos >= 0) & (k_pos <= cur_pos)
    if window:
        valid &= k_pos > (cur_pos - window)
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v_cache)
    return out.reshape(B, Sq, H, D)


# ---------------------------------------------------------------------------
# paged KV cache path (serving: shared block pools + per-request tables)
# ---------------------------------------------------------------------------

def _paged_write(pool, vals, tbl, pos):
    """Scatter vals (B, S, Kv, D) into pool (P, Kv, bs, D) at absolute
    positions pos (B, S) via the block table tbl (B, max_blocks).

    Position p of request b lands at (tbl[b, p // bs], :, p % bs).  Writes
    to unallocated blocks (tbl -1) or past the table are *dropped* — this
    is what makes inactive slots in a fixed-shape decode batch harmless:
    their sentinel positions fall outside any allocated block.
    """
    P, bs = pool.shape[0], pool.shape[2]
    nb = tbl.shape[1]
    blk_log = pos // bs
    blk = jnp.take_along_axis(tbl, jnp.clip(blk_log, 0, nb - 1), axis=1)
    blk = jnp.where((blk < 0) | (blk_log >= nb), P, blk)   # P = out of bounds
    off = pos % bs
    B, S = pos.shape
    return pool.at[blk.reshape(-1), :, off.reshape(-1)].set(
        vals.reshape((B * S,) + vals.shape[2:]).astype(pool.dtype),
        mode="drop")


def _paged_attend(q, k_pool, v_pool, tbl, q_pos, n_valid, window=0):
    """Attention over pool-gathered KV with per-request positions (jnp
    reference path; the Pallas flash-decode kernel replaces it on TPU).

    q (B, Sq, H, D) at absolute positions q_pos (B, Sq); n_valid (B,)
    counts KV entries present per request (the just-written chunk
    included), so both chunked prefill (Sq > 1) and decode (Sq == 1) are
    the same computation.
    """
    P, Kv, bs, D = k_pool.shape
    B, Sq = q_pos.shape
    nb = tbl.shape[1]
    safe = jnp.clip(tbl, 0, P - 1)
    k = k_pool[safe].transpose(0, 1, 3, 2, 4).reshape(B, nb * bs, Kv, D)
    v = v_pool[safe].transpose(0, 1, 3, 2, 4).reshape(B, nb * bs, Kv, D)
    k_pos = jnp.broadcast_to(jnp.arange(nb * bs)[None], (B, nb * bs))
    valid = (k_pos < n_valid[:, None]) & (tbl >= 0).repeat(bs, axis=1)
    mask = valid[:, None, :] & (k_pos[:, None, :] <= q_pos[:, :, None])
    if window:
        mask &= k_pos[:, None, :] > (q_pos[:, :, None] - window)
    G = q.shape[2] // Kv
    qg = q.reshape(B, Sq, Kv, G, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * \
        (D ** -0.5)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v)
    return out.reshape(B, Sq, Kv * G, D)


def _paged_attention_block(cfg, q, k, v, cache, paged, rt: Runtime):
    """Write the new chunk into the layer's pools and attend against the
    request's full paged context.  cache: {'k_pool', 'v_pool'}; paged:
    {'tbl' (B, max_blocks), 'ctx' (B,)} shared across layers (the engine
    advances ctx between steps — layers only read it)."""
    B, S = q.shape[0], q.shape[1]
    tbl, ctx = paged["tbl"], paged["ctx"]
    pos = ctx[:, None] + jnp.arange(S, dtype=jnp.int32)[None]   # (B, S)
    k_pool = _paged_write(cache["k_pool"], k, tbl, pos)
    v_pool = _paged_write(cache["v_pool"], v, tbl, pos)
    n_valid = ctx + S
    if (S == 1 and rt.attn_impl == "pallas" and not cfg.sliding_window
            and cfg.head_dim_ % 8 == 0):
        from repro.kernels import ops as kernel_ops
        out = kernel_ops.paged_decode_attention(q, k_pool, v_pool, tbl,
                                                n_valid)
    else:
        out = _paged_attend(q, k_pool, v_pool, tbl, pos, n_valid,
                            cfg.sliding_window)
    return out, {"k_pool": k_pool, "v_pool": v_pool}


# ---------------------------------------------------------------------------
# full attention block (projections + rope + cache plumbing)
# ---------------------------------------------------------------------------

def _project_qkv(cfg, p, x, rt: Runtime):
    B, S, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim_
    dt = x.dtype
    q = jnp.einsum("bsd,de->bse", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,de->bse", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,de->bse", x, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = q.reshape(B, S, h, hd)
    k = k.reshape(B, S, kv, hd)
    v = v.reshape(B, S, kv, hd)
    if cfg.qk_norm:
        q = rms_norm_headwise(p["q_norm"], q, cfg.norm_eps)
        k = rms_norm_headwise(p["k_norm"], k, cfg.norm_eps)
    return q, k, v


def _cp_attend(q, k, v, window, scale, axis):
    """Manual context parallelism inside a shard_map stage: q/k/v hold this
    rank's contiguous sequence shard; K/V are all-gathered over ``axis``
    (gathered-KV exact attention) and the causal mask is offset by the
    rank's global position."""
    S_loc = q.shape[1]
    k_full = jax.lax.all_gather(k, axis, axis=1, tiled=True)
    v_full = jax.lax.all_gather(v, axis, axis=1, tiled=True)
    idx = jax.lax.axis_index(axis)
    q_pos = idx * S_loc + jnp.arange(S_loc)
    k_pos = jnp.arange(k_full.shape[1])
    return _attend_dense(q, k_full, v_full, q_pos, k_pos, window, scale)


def attention_block(cfg, p, x, rope_ang, rt: Runtime, cache=None,
                    want_cache: bool = False, paged=None):
    """Full attention sublayer.

    Train/prefill: x (B,S,d), cache None -> (out, new_cache | None).
    Decode:        x (B,1,d), cache dict  -> (out, updated cache).
    Paged serving: cache {'k_pool','v_pool'} + paged {'tbl','ctx'} —
                   chunked prefill (S>1) and decode (S==1) both append at
                   the request's ctx and attend over its block chain.
    """
    B, S, _ = x.shape
    if rt.cp_axis and rope_ang is not None:
        # manual CP: x carries only this rank's sequence shard — slice the
        # (full-length, batch-dim-1) rope angles down to its positions
        idx = jax.lax.axis_index(rt.cp_axis)
        rope_ang = jax.lax.dynamic_slice_in_dim(rope_ang, idx * S, S, axis=1)
    q, k, v = _project_qkv(cfg, p, x, rt)
    if rope_ang is not None:
        q = apply_rope(q, rope_ang)
        k = apply_rope(k, rope_ang)
    q = rt.c("heads_q", q)
    k = rt.c("heads_kv", k)
    v = rt.c("heads_kv", v)

    if paged is not None:
        out, new_cache = _paged_attention_block(cfg, q, k, v, cache, paged, rt)
    elif cache is None:
        if rt.cp_axis:
            out = _cp_attend(q, k, v, cfg.sliding_window,
                             q.shape[-1] ** -0.5, rt.cp_axis)
        else:
            out = sdpa_causal(q, k, v, cfg.sliding_window, rt)
        new_cache = None
        if want_cache:
            new_cache = make_kv_cache(cfg, B, S, k.dtype, rt)
            new_cache = prefill_kv_cache(new_cache, k, v, rt)
    elif S > 1:
        # prefill into a pre-allocated decode cache
        out = sdpa_causal(q, k, v, cfg.sliding_window, rt)
        new_cache = prefill_kv_cache(cache, k, v, rt)
    else:
        idx = cache["idx"]                                   # scalar int32
        Sc = cache["k"].shape[1]
        # ring arithmetic: position p lives at slot p % Sc.  For full-attn
        # caches Sc == max seq so this is the identity.
        slot = idx % Sc
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
        k_pos = jax.lax.dynamic_update_slice(
            cache["kpos"], idx[None].astype(cache["kpos"].dtype), (slot,))
        k_cache = rt.c("kv_cache", k_cache)
        v_cache = rt.c("kv_cache", v_cache)
        out = sdpa_decode(q, k_cache, v_cache, k_pos, idx, cfg.sliding_window)
        new_cache = {"k": k_cache, "v": v_cache, "kpos": k_pos, "idx": idx + 1}

    out = out.reshape(B, S, -1)
    out = jnp.einsum("bse,ed->bsd", out, p["wo"].astype(out.dtype))
    return rt.c("act_btd", out), new_cache


def make_kv_cache(cfg, batch, seq_len, dtype, rt: Runtime):
    """Empty cache. SWA archs keep a window-sized ring buffer."""
    size = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
    kv, hd = cfg.kv_heads, cfg.head_dim_
    return {
        "k": rt.c("kv_cache", jnp.zeros((batch, size, kv, hd), dtype)),
        "v": rt.c("kv_cache", jnp.zeros((batch, size, kv, hd), dtype)),
        "kpos": jnp.full((size,), -1, jnp.int32),
        "idx": jnp.zeros((), jnp.int32),
    }


def prefill_kv_cache(cache, k, v, rt: Runtime):
    """Write a full prefix of k/v (B,S,Kv,D) into a fresh cache."""
    S = k.shape[1]
    Sc = cache["k"].shape[1]
    if S >= Sc:          # SWA: keep last Sc positions, ring-consistent layout
        shift = (S - Sc) % Sc
        ks = jnp.roll(k[:, S - Sc:], shift, axis=1)
        vs = jnp.roll(v[:, S - Sc:], shift, axis=1)
        kpos = jnp.roll(jnp.arange(S - Sc, S, dtype=jnp.int32), shift)
        kc = rt.c("kv_cache", ks.astype(cache["k"].dtype))
        vc = rt.c("kv_cache", vs.astype(cache["v"].dtype))
    else:
        kc = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
        kpos = jnp.where(jnp.arange(Sc) < S, jnp.arange(Sc), -1).astype(jnp.int32)
        kc, vc = rt.c("kv_cache", kc), rt.c("kv_cache", vc)
    return {"k": kc, "v": vc, "kpos": kpos,
            "idx": jnp.asarray(S, jnp.int32)}
