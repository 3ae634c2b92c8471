"""RWKV-6 "Finch" (arXiv:2404.05892): attention-free token mixing with
data-dependent decay.

Time-mix block:
  - ddlerp token shift: inputs for r/k/v/g/w are lerps between x_t and
    x_{t-1} with data-dependent (low-rank) mix coefficients;
  - per-channel decay w_t = exp(-exp(w0 + lora(x))), i.e. data-dependent;
  - WKV: per head (head_dim N) the state S in R^{N x N} evolves as
        S_t = diag(w_t) S_{t-1} + k_t^T v_t
        y_t = r_t (S_{t-1} + (u . k_t)^T v_t)
  - headwise groupnorm, silu(g) gate, output projection.

We provide a chunked parallel form (matmul-heavy, TPU friendly — the same
blocking the Pallas kernel in ``repro.kernels.rwkv6`` uses) and a one-step
recurrent form for decode; a pure sequential scan acts as the test oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import Runtime

TM_RANK = 32   # low-rank dim of the token-shift ddlerp
TD_RANK = 64   # low-rank dim of the decay lora


def init_rwkv_time_mix(cfg, key):
    d = cfg.d_model
    H, N = cfg.rwkv_heads, cfg.rwkv_head_dim
    ks = jax.random.split(key, 12)
    s = d ** -0.5
    # decay bias init ~ -6..-5 => w ~ exp(-exp(-6)) ~ 0.9975 (stable chunks)
    w0 = -6.0 + 2.0 * jax.random.uniform(ks[0], (d,))
    return {
        "maa_x": jnp.zeros((d,)),
        "maa_rkvwg": jnp.zeros((5, d)),
        "tm_w1": jax.random.normal(ks[1], (d, 5 * TM_RANK)) * 1e-2,
        "tm_w2": jax.random.normal(ks[2], (5, TM_RANK, d)) * 1e-2,
        "w0": w0,
        "td_w1": jax.random.normal(ks[3], (d, TD_RANK)) * 1e-2,
        "td_w2": jax.random.normal(ks[4], (TD_RANK, d)) * 1e-2,
        "u": jax.random.normal(ks[5], (H, N)) * 1e-1,
        "wr": jax.random.normal(ks[6], (d, d)) * s,
        "wk": jax.random.normal(ks[7], (d, d)) * s,
        "wv": jax.random.normal(ks[8], (d, d)) * s,
        "wg": jax.random.normal(ks[9], (d, d)) * s,
        "wo": jax.random.normal(ks[10], (d, d)) * s,
        "ln_x": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
    }


def init_rwkv_channel_mix(cfg, key):
    d, dff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "maa_k": jnp.zeros((d,)),
        "maa_r": jnp.zeros((d,)),
        "wk": jax.random.normal(ks[0], (d, dff)) * d ** -0.5,
        "wv": jax.random.normal(ks[1], (dff, d)) * dff ** -0.5,
        "wr": jax.random.normal(ks[2], (d, d)) * d ** -0.5,
    }


# ---------------------------------------------------------------------------
# WKV core
# ---------------------------------------------------------------------------

def wkv_recurrent(r, k, v, w, u, state):
    """Sequential oracle. r/k/v/w (B,T,H,N); u (H,N); state (B,H,N,N)."""
    def step(S, inp):
        r_t, k_t, v_t, w_t = inp                            # (B,H,N)
        kv = jnp.einsum("bhn,bhm->bhnm", k_t, v_t)
        y = jnp.einsum("bhn,bhnm->bhm", r_t, S) \
            + jnp.einsum("bhn,bhn,bhm->bhm", r_t, u[None] * k_t, v_t)
        S = w_t[..., None] * S + kv
        return S, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (r, k, v, w))
    state, ys = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(ys, 0, 1), state


def wkv_chunked(r, k, v, w, u, state, chunk):
    """Chunked parallel WKV (fp32 internals).

    Derivation (per head, per key-channel n):
      cp_t  = prod_{l<=t} w_l  (within chunk; cp_0 = 1)
      y_t   = q'_t S_0 + sum_{j<t} ((q'_t . k'_j)) v_j + ((r_t u) . k_t) v_t
              with q'_t = r_t * cp_{t-1},  k'_j = k_j / cp_j
      S_C   = diag(cp_C) S_0 + sum_j (k_j * cp_C / cp_j)^T v_j
    """
    B, T, H, N = r.shape
    chunk = min(chunk, T)
    Tp = -(-T // chunk) * chunk
    if Tp != T:
        # pad with identity steps: w=1 (no decay), k=0 (no contribution)
        pad = [(0, 0), (0, Tp - T), (0, 0), (0, 0)]
        r, k, v = (jnp.pad(a, pad) for a in (r, k, v))
        w = jnp.pad(w, pad, constant_values=1.0)
    nc = Tp // chunk

    def to_chunks(x):
        return x.reshape(B, nc, chunk, H, N).transpose(1, 0, 2, 3, 4)

    rc, kc, vc, wc = map(to_chunks, (r, k, v, w))

    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), k=-1)  # strict lower

    def chunk_step(S, inp):
        r_, k_, v_, w_ = (a.astype(jnp.float32) for a in inp)    # (B,C,H,N)
        lw = jnp.log(jnp.maximum(w_, 1e-12))
        lc = jnp.cumsum(lw, axis=1)                              # inclusive
        lc_prev = lc - lw                                        # exclusive
        qp = r_ * jnp.exp(lc_prev)
        kp = k_ * jnp.exp(-lc)
        A = jnp.einsum("bchn,bdhn->bhcd", qp, kp) * tri[None, None]
        diag = jnp.einsum("bchn,hn,bchn->bhc", r_, u.astype(jnp.float32), k_)
        y = (jnp.einsum("bhcd,bdhn->bchn", A, v_)
             + diag.transpose(0, 2, 1)[..., None] * v_
             + jnp.einsum("bchn,bhnm->bchm", qp, S))
        lc_tot = lc[:, -1]                                       # (B,H,N)
        k_tail = k_ * jnp.exp(lc_tot[:, None] - lc)
        S = jnp.exp(lc_tot)[..., None] * S \
            + jnp.einsum("bchn,bchm->bhnm", k_tail, v_)
        return S, y.astype(r.dtype)

    state, ys = jax.lax.scan(chunk_step, state.astype(jnp.float32), (rc, kc, vc, wc))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, Tp, H, N)[:, :T]
    return y, state


def wkv_step(r, k, v, w, u, state):
    """One decode step. r/k/v/w (B,H,N); state (B,H,N,N)."""
    y = jnp.einsum("bhn,bhnm->bhm", r, state) \
        + jnp.einsum("bhn,bhn,bhm->bhm", r, u[None] * k, v)
    state = w[..., None] * state + jnp.einsum("bhn,bhm->bhnm", k, v)
    return y, state


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _ddlerp(p, x, x_prev):
    """Data-dependent token-shift lerp -> (xr, xk, xv, xw, xg), each (B,T,d)."""
    xx = x_prev - x
    xxx = x + xx * p["maa_x"]
    B, T, d = x.shape
    lora = jnp.tanh(jnp.einsum("btd,dr->btr", xxx, p["tm_w1"].astype(x.dtype)))
    lora = lora.reshape(B, T, 5, TM_RANK)
    mix = jnp.einsum("btfr,frd->fbtd", lora, p["tm_w2"].astype(x.dtype))
    outs = []
    for i in range(5):
        outs.append(x + xx * (p["maa_rkvwg"][i].astype(x.dtype) + mix[i]))
    return outs


def _shift(x, last):
    """x_{t-1} stream: (B,T,d) shifted right, first slot = `last` (B,d)."""
    return jnp.concatenate([last[:, None], x[:, :-1]], axis=1)


def rwkv_time_mix(cfg, p, x, rt: Runtime, state=None):
    """state: None (train: zeros, returns None) or dict with
    'x_prev' (B,d) and 'wkv' (B,H,N,N) for decode/prefill carry."""
    B, T, d = x.shape
    H, N = cfg.rwkv_heads, cfg.rwkv_head_dim
    last = state["x_prev"] if state is not None else jnp.zeros((B, d), x.dtype)
    S0 = (state["wkv"] if state is not None
          else jnp.zeros((B, H, N, N), jnp.float32))

    xr, xk, xv, xw, xg = _ddlerp(p, x, _shift(x, last))
    dt = x.dtype
    r = jnp.einsum("btd,de->bte", xr, p["wr"].astype(dt)).reshape(B, T, H, N)
    k = jnp.einsum("btd,de->bte", xk, p["wk"].astype(dt)).reshape(B, T, H, N)
    v = jnp.einsum("btd,de->bte", xv, p["wv"].astype(dt)).reshape(B, T, H, N)
    g = jax.nn.silu(jnp.einsum("btd,de->bte", xg, p["wg"].astype(dt)))
    dlora = jnp.einsum("btr,rd->btd",
                       jnp.tanh(jnp.einsum("btd,dr->btr", xw, p["td_w1"].astype(dt))),
                       p["td_w2"].astype(dt))
    w = jnp.exp(-jnp.exp((p["w0"].astype(jnp.float32) + dlora.astype(jnp.float32))
                         )).reshape(B, T, H, N)

    r, k, v = (rt.c("rwkv_heads", a) for a in (r, k, v))
    if T == 1 and state is not None:
        y, S = wkv_step(r[:, 0], k[:, 0], v[:, 0], w[:, 0].astype(jnp.float32),
                        p["u"].astype(jnp.float32), S0)
        y = y[:, None]
    elif (rt.attn_impl == "pallas" and state is None and T >= 64
          and N in (16, 32, 64, 128)):
        # TPU hot path: Pallas chunked WKV kernel (zero initial state)
        from repro.kernels import ops as kernel_ops
        y, S = rt.per_shard(
            lambda r, k, v, w, u: kernel_ops.wkv6(r, k, v, w, u,
                                                  chunk=rt.rwkv_chunk),
            r, k, v, w, p["u"])
    else:
        y, S = wkv_chunked(r, k, v, w, p["u"], S0, rt.rwkv_chunk)

    # headwise groupnorm
    yf = y.reshape(B, T, H, N).astype(jnp.float32)
    mu = yf.mean(-1, keepdims=True)
    var = ((yf - mu) ** 2).mean(-1, keepdims=True)
    yf = (yf - mu) * jax.lax.rsqrt(var + 64e-5)
    yf = yf.reshape(B, T, d) * p["ln_x"]["scale"] + p["ln_x"]["bias"]
    y = yf.astype(dt) * g
    out = jnp.einsum("btd,de->bte", y, p["wo"].astype(dt))

    new_state = None
    if state is not None:
        new_state = {"x_prev": x[:, -1], "wkv": S.astype(jnp.float32)}
    return rt.c("act_btd", out), new_state


def rwkv_channel_mix(cfg, p, x, rt: Runtime, state=None):
    B, T, d = x.shape
    last = state["x_prev"] if state is not None else jnp.zeros((B, d), x.dtype)
    xx = _shift(x, last) - x
    xk = x + xx * p["maa_k"].astype(x.dtype)
    xr = x + xx * p["maa_r"].astype(x.dtype)
    dt = x.dtype
    k = jnp.square(jax.nn.relu(
        rt.c("act_btf", jnp.einsum("btd,df->btf", xk, p["wk"].astype(dt)))))
    kv = jnp.einsum("btf,fd->btd", k, p["wv"].astype(dt))
    r = jax.nn.sigmoid(jnp.einsum("btd,de->bte", xr, p["wr"].astype(dt)))
    new_state = {"x_prev": x[:, -1]} if state is not None else None
    return rt.c("act_btd", r * kv), new_state
