"""Pallas TPU chunked WKV-6 kernel (RWKV-6 data-dependent-decay recurrence).

The recurrence S_t = diag(w_t) S_{t-1} + k_t^T v_t, y_t = r_t (S_{t-1} +
(u*k_t)^T v_t) is evaluated in the chunked-parallel form (see
``repro.models.rwkv6.wkv_chunked``): within a chunk of C tokens everything
is dense matmul on the MXU; the (N, N) per-head state is carried across
chunks in VMEM scratch.

Grid: (B*H, n_chunks) — the chunk axis is minormost and therefore
sequential on a TensorCore, exactly what a linear-recurrence scan needs.
VMEM working set per step: 4 x (C, N) inputs + (C, C) scores + (N, N)
state; with C=64, N=64 in fp32 that is ~100 KB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, sout_ref, s_scr,
                *, chunk, n_chunks):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0].astype(jnp.float32)                    # (C, N)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)                    # (1, N)
    S = s_scr[...]                                      # (N, N)

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = (jj < ii).astype(jnp.float32)                 # strictly lower

    lw = jnp.log(jnp.maximum(w, 1e-12))
    # inclusive cumsum over the chunk as a lower-triangular matmul (Mosaic
    # has no cumsum lowering); full f32 precision keeps the decay exact
    lc = jax.lax.dot(tri + (jj == ii).astype(jnp.float32), lw,
                     precision=jax.lax.Precision.HIGHEST)
    lc_prev = lc - lw
    qp = r * jnp.exp(lc_prev)
    kp = k * jnp.exp(-lc)

    A = jax.lax.dot_general(qp, kp, (((1,), (1,)), ((), ()))) * tri
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)    # (C, 1)
    y = jax.lax.dot(A, v) + diag * v + jax.lax.dot(qp, S)

    lc_tot = lc[-1:, :]                                 # (1, N)
    k_tail = k * jnp.exp(lc_tot - lc)
    s_new = jnp.exp(lc_tot).T * S + jax.lax.dot_general(
        k_tail, v, (((0,), (0,)), ((), ())))
    s_scr[...] = s_new

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _final():
        sout_ref[0] = s_new.astype(sout_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _wkv6(r, k, v, w, u, chunk, interpret):
    return _wkv6_forward(r, k, v, w, u, chunk, interpret)


def _wkv6_fwd_rule(r, k, v, w, u, chunk, interpret):
    return _wkv6_forward(r, k, v, w, u, chunk, interpret), (r, k, v, w, u)


def _wkv6_bwd_rule(chunk, interpret, res, cts):
    # gradient bridge: the WKV backward is not a Pallas kernel yet, so
    # differentiate the jnp chunked-parallel oracle instead — training with
    # Runtime(attn_impl='pallas') stays end-to-end differentiable and the
    # forward still runs on the kernel.
    from repro.models.rwkv6 import wkv_chunked
    r, k, v, w, u = res
    B, _, H, N = r.shape
    s0 = jnp.zeros((B, H, N, N), jnp.float32)
    _, pullback = jax.vjp(
        lambda r, k, v, w, u: wkv_chunked(r, k, v, w, u, s0, chunk),
        r, k, v, w, u)
    return pullback(cts)


_wkv6.defvjp(_wkv6_fwd_rule, _wkv6_bwd_rule)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, *, chunk=64, interpret=False):
    """r/k/v/w (B,T,H,N), u (H,N) -> (y (B,T,H,N), state (B,H,N,N)).

    Zero initial state (the fused-training entry point; decode keeps the
    recurrent step in plain jnp — it is a single (N,N) mat-vec).
    Differentiable: the backward currently replays the jnp chunked oracle
    (see ``_wkv6_bwd_rule``); a fused Pallas WKV backward is future work.
    """
    return _wkv6(r, k, v, w, u, chunk, interpret)


def _wkv6_forward(r, k, v, w, u, chunk, interpret):
    B, T, H, N = r.shape
    chunk = min(chunk, T)
    Tp = -(-T // chunk) * chunk
    if Tp != T:
        pad = [(0, 0), (0, Tp - T), (0, 0), (0, 0)]
        r, k, v = (jnp.pad(a, pad) for a in (r, k, v))
        w = jnp.pad(w, pad, constant_values=1.0)
    nc = Tp // chunk

    def to_bh(a):
        return a.transpose(0, 2, 1, 3).reshape(B * H, Tp, N)

    rb, kb, vb, wb = map(to_bh, (r, k, v, w))
    ub = jnp.broadcast_to(u[None], (B, H, N)).reshape(B * H, 1, N)

    y, s = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk, n_chunks=nc),
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, N), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, N, N), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tp, N), r.dtype),
            jax.ShapeDtypeStruct((B * H, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
        name="wkv6_fwd",
    )(rb, kb, vb, wb, ub)

    y = y.reshape(B, H, Tp, N).transpose(0, 2, 1, 3)[:, :T]
    return y, s.reshape(B, H, N, N)
