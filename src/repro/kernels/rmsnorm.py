"""Pallas TPU fused RMSNorm (forward + backward): one HBM round-trip per
row block.

Rows are tiled (block_rows, d) into VMEM; the mean-square reduction and the
scale multiply fuse in-register (fp32 accumulation regardless of input
dtype).  d is the model dim — a multiple of 128 for every assigned arch,
keeping lanes aligned.

Every block is 2-D so its last two dims tile to the TPU's (8, 128):
``scale`` enters as a (1, d) row and the per-row rstd leaves as an (n, 1)
column (a 1-D (block_rows,) block does not match XLA's 1-D layout).

The forward also emits the per-row rstd = rsqrt(mean(x^2) + eps); the fused
backward reuses it (no second reduction over x) and accumulates the
``scale`` gradient across row blocks in a VMEM-resident output block that
the sequential 1-D grid revisits.  ``rmsnorm`` carries a ``jax.custom_vjp``
so training differentiates through the kernel pair.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, s_ref, o_ref, r_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    y = x * rstd * s_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)
    r_ref[...] = rstd


def _rmsnorm_bwd_kernel(x_ref, s_ref, r_ref, g_ref, dx_ref, ds_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    x = x_ref[...].astype(jnp.float32)                  # (rows, d)
    s = s_ref[...].astype(jnp.float32)                  # (1, d)
    g = g_ref[...].astype(jnp.float32)                  # (rows, d)
    rstd = r_ref[...]                                   # (rows, 1)

    # y = x * rstd * s; with c = mean(g*s*x) the x-gradient is
    # dx = rstd * (g*s - x * rstd^2 * c) — rstd reused from the forward.
    gs = g * s
    c = jnp.mean(gs * x, axis=-1, keepdims=True)
    dx_ref[...] = (rstd * (gs - x * (rstd * rstd) * c)).astype(dx_ref.dtype)
    ds_ref[...] += jnp.sum(g * x * rstd, axis=0, keepdims=True)


def _pad_rows(xf, n, block_rows):
    n_pad = -(-n // block_rows) * block_rows
    if n_pad != n:
        xf = jnp.pad(xf, [(0, n_pad - n), (0, 0)])
    return xf, n_pad


def _rmsnorm_forward(x, scale, eps, block_rows, interpret):
    shape = x.shape
    d = shape[-1]
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    block_rows = min(block_rows, n)
    xf, n_pad = _pad_rows(xf, n, block_rows)

    out, rstd = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(n_pad // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_pad, d), x.dtype),
                   jax.ShapeDtypeStruct((n_pad, 1), jnp.float32)],
        interpret=interpret,
        name="rmsnorm_fwd",
    )(xf, scale.reshape(1, d))
    return out[:n].reshape(shape), (x, scale, rstd)


def _rmsnorm_backward(eps, block_rows, interpret, res, g):
    x, scale, rstd = res                       # rstd already padded (n_pad, 1)
    shape = x.shape
    d = shape[-1]
    xf = x.reshape(-1, d)
    gf = g.reshape(-1, d)
    n = xf.shape[0]
    block_rows = min(block_rows, n)
    xf, n_pad = _pad_rows(xf, n, block_rows)
    gf, _ = _pad_rows(gf, n, block_rows)       # padded rows: x=g=0 -> no-op

    dx, dscale = pl.pallas_call(
        _rmsnorm_bwd_kernel,
        grid=(n_pad // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0)),
                  pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, d), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                   pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_pad, d), x.dtype),
                   jax.ShapeDtypeStruct((1, d), jnp.float32)],
        interpret=interpret,
        name="rmsnorm_bwd",
    )(xf, scale.reshape(1, d), rstd, gf)
    return dx[:n].reshape(shape), dscale.reshape(d).astype(scale.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rmsnorm(x, scale, eps, block_rows, interpret):
    out, _ = _rmsnorm_forward(x, scale, eps, block_rows, interpret)
    return out


def _rmsnorm_fwd_rule(x, scale, eps, block_rows, interpret):
    return _rmsnorm_forward(x, scale, eps, block_rows, interpret)


_rmsnorm.defvjp(_rmsnorm_fwd_rule, _rmsnorm_backward)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, scale, *, eps=1e-6, block_rows=256, interpret=False):
    """x (..., d), scale (d,) -> rmsnorm(x) * scale.  Differentiable via the
    fused Pallas backward (dx + dscale in one pass)."""
    return _rmsnorm(x, scale, eps, block_rows, interpret)
