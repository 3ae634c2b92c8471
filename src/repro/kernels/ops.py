"""Jit'd public wrappers for the Pallas kernels.

``interpret='auto'`` executes the kernel bodies in Python on CPU (the
validation substrate) and compiles them for real on TPU; on a TPU no
argument can select interpret mode.  The backend probe is memoized at
module level so the hot path never re-queries XLA.  Model
code calls these through ``Runtime.attn_impl == 'pallas'`` /
``Runtime.norm_impl == 'pallas'`` — both forward and backward run as Pallas
kernels (``custom_vjp``), so ``jax.grad`` through a train step stays on the
kernel path.
"""
from __future__ import annotations

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.flash_decode import flash_decode as _flash_decode
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm
from repro.kernels.rwkv6 import wkv6 as _wkv6

_IS_TPU = None      # memoized jax.default_backend() == 'tpu' probe


def _interp(interpret):
    global _IS_TPU
    if _IS_TPU is None:
        _IS_TPU = jax.default_backend() == "tpu"
    if _IS_TPU:
        if interpret != "auto" and interpret:
            raise ValueError("Pallas interpret mode requested on a TPU")
        return False
    return True if interpret == "auto" else bool(interpret)


def _dtype_blocks(dtype, f32_val: int) -> int:
    """Dtype-aware block default: sub-4-byte dtypes double the tile.

    TPU tiling is (8, 128) sublanes x lanes at f32 but (16, 128) at bf16
    — half the bytes per element means a 2x-larger block fills the same
    VMEM footprint with half the grid steps.  Not yet tuned on a chip.
    """
    import jax.numpy as jnp
    return f32_val * (2 if jnp.dtype(dtype).itemsize <= 2 else 1)


def attention(q, k, v, *, causal=True, window=0, block_q=None, block_kv=None,
              interpret="auto"):
    if block_q is None:
        block_q = _dtype_blocks(q.dtype, 128)
    if block_kv is None:
        block_kv = _dtype_blocks(q.dtype, 256)
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_kv=block_kv, interpret=_interp(interpret))


def paged_decode_attention(q, k_pool, v_pool, tbl, ctx, *, n_splits=4,
                           interpret="auto"):
    """Flash-decode over a paged KV cache (forward-only; decode has no
    backward).  q (B,1,H,D); pools (P,Kv,bs,D); tbl (B,max_blocks) int32;
    ctx (B,) int32 valid positions per request."""
    return _flash_decode(q, k_pool, v_pool, tbl, ctx, n_splits=n_splits,
                         interpret=_interp(interpret))


# Largest (block_rows, d) rmsnorm block, in elements (512 rows of d 1024).
# The kernels hold f32 copies of a block besides their double-buffered
# operands: the backward at 512 bf16 rows of d 1536 asks for 18.1 MiB of
# the 16 MiB of scoped VMEM a v5e kernel may take, whenever its operands
# sit in HBM.
_RMSNORM_BLOCK_ELEMS = 512 * 1024


def rmsnorm(x, scale, *, eps=1e-6, block_rows=None, interpret="auto"):
    if block_rows is None:
        block_rows = _dtype_blocks(x.dtype, 256)
        d = x.shape[-1]
        while block_rows > 8 and block_rows * d > _RMSNORM_BLOCK_ELEMS:
            block_rows //= 2
    return _rmsnorm(x, scale, eps=eps, block_rows=block_rows,
                    interpret=_interp(interpret))


def wkv6(r, k, v, w, u, *, chunk=64, interpret="auto"):
    return _wkv6(r, k, v, w, u, chunk=chunk, interpret=_interp(interpret))
