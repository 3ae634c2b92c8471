"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Layouts match the kernels: attention is (B, S, H, D) with GQA via
n_kv_heads | n_heads; wkv6 is (B, T, H, N).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def attention_ref(q, k, v, *, causal=True, window=0):
    """q (B,Sq,H,D), k/v (B,Skv,Kv,D) -> (B,Sq,H,D); fp32 softmax."""
    B, Sq, H, D = q.shape
    Kv = k.shape[2]
    G = H // Kv
    qg = q.reshape(B, Sq, Kv, G, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * (D ** -0.5)
    q_pos = jnp.arange(Sq)
    k_pos = jnp.arange(k.shape[1])
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window:
        mask &= k_pos[None, :] > (q_pos[:, None] - window)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def paged_attention_ref(q, k_pool, v_pool, tbl, ctx, *, window=0):
    """Decode attention over a paged KV cache (fp32 softmax oracle).

    q (B, 1, H, D) one query token per request; k_pool/v_pool
    (P, Kv, bs, D) shared block pools; tbl (B, max_blocks) int32 block
    table (-1 = unallocated); ctx (B,) int32 valid KV positions per
    request (the query sits at position ctx[b] - 1).  Position p of
    request b lives at pool slot (tbl[b, p // bs], :, p % bs).
    """
    B, Sq, H, D = q.shape
    P, Kv, bs, _ = k_pool.shape
    G = H // Kv
    nb = tbl.shape[1]
    safe = jnp.clip(tbl, 0, P - 1)

    def gather(pool):                                    # -> (B, Skv, Kv, D)
        return pool[safe].transpose(0, 1, 3, 2, 4).reshape(B, nb * bs, Kv, D)

    k, v = gather(k_pool), gather(v_pool)
    k_pos = jnp.arange(nb * bs)
    valid = (k_pos[None] < ctx[:, None]) & \
        (tbl >= 0).repeat(bs, axis=1)                    # (B, Skv)
    if window:
        valid &= k_pos[None] > (ctx[:, None] - 1 - window)
    qg = q.reshape(B, Sq, Kv, G, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * (D ** -0.5)
    s = jnp.where(valid[:, None, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def rmsnorm_ref(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def wkv6_ref(r, k, v, w, u, state):
    """Sequential RWKV-6 recurrence (fp32).

    r/k/v/w: (B,T,H,N); u: (H,N); state: (B,H,N,N) mapping key-dim -> val-dim.
    """
    r, k, v, w = (a.astype(jnp.float32) for a in (r, k, v, w))
    state = state.astype(jnp.float32)

    def step(S, inp):
        r_t, k_t, v_t, w_t = inp
        kv = jnp.einsum("bhn,bhm->bhnm", k_t, v_t)
        y = jnp.einsum("bhn,bhnm->bhm", r_t, S) \
            + jnp.einsum("bhn,bhn,bhm->bhm", r_t, u[None].astype(jnp.float32) * k_t, v_t)
        return w_t[..., None] * S + kv, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (r, k, v, w))
    state, ys = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(ys, 0, 1), state
