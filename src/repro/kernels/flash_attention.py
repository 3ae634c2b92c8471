"""Pallas TPU flash attention (forward + backward): blocked online softmax.

TPU-native design (not a CUDA port, see DESIGN.md §2):
  * forward grid = (batch*kv_heads*q_per_kv, n_q_blocks, n_kv_blocks); the
    minormost kv-block axis executes sequentially on a TensorCore, so the
    running (m, l, acc) state lives in VMEM scratch and is carried across
    kv steps — the TPU analogue of a persistent CTA loop.  The forward also
    emits the per-row logsumexp (lse = m + log l), the only residual the
    backward needs besides q/k/v/o.
  * backward is the standard FA2 two-kernel layout: dq runs q-block-major
    (kv minormost, dq accumulated in VMEM scratch); dk/dv run kv-block-major
    with the (gqa_group, q_block) pair flattened into one sequential axis so
    the dk/dv accumulators also live in scratch and the G query heads that
    share a kv head are reduced on-chip instead of in HBM.  Probabilities
    are recomputed from the saved lse (p = exp(s - lse)) — no S x S tensor
    is ever materialized.
  * BlockSpecs tile q/k/v to (block_q|block_kv, head_dim) VMEM windows;
    block sizes default to 128/256 to keep the MXU's 128-lane shape and a
    working set of ~(2*bq*D + 2*bk*D + bq*bk)*4B well under VMEM.
  * the per-row residuals lse and delta are stored as (N, 1, Sp) rows with
    (1, 1, bq) blocks: the TPU tiles the last two block dims to (8, 128),
    which a (1, bq) block over an (N, Sp) array violates.  The kernels
    transpose the row to the (bq, 1) column the softmax math needs.
  * GQA: q heads are grouped by kv head via index_map arithmetic — no
    repeated K/V in HBM.
  * the value head may be narrower or wider than the q/k head (latent
    attention: q/k 192 = 128 + 64 rotated, v 128), and the softmax scale
    may be given (YaRN's m^2 on top of 1/sqrt(D)); each operand is tiled
    at its own width, so no head is padded.
  * causal + sliding-window masks built from absolute block offsets with
    broadcasted iota (2D, as the TPU requires).

``flash_attention`` carries a ``jax.custom_vjp``, so ``jax.grad`` through it
runs the Pallas backward kernels: the training hot path (fwd + bwd) executes
at kernel speed, which is what makes the cost model's MFU/words-per-second
numbers comparable to measured step times (arXiv 2411.13055 §4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _block_mask(qi, kj, block_q, block_kv, causal, window, seq_len):
    """(block_q, block_kv) visibility for absolute block offsets (qi, kj)."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_kv), 0)
    k_pos = kj * block_kv + jax.lax.broadcasted_iota(jnp.int32,
                                                     (block_q, block_kv), 1)
    mask = k_pos < seq_len
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > (q_pos - window)
    return mask


# ---------------------------------------------------------------------------
# forward kernel (emits o and the logsumexp residual)
# ---------------------------------------------------------------------------

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                  *, scale, block_q, block_kv, n_kv, causal, window, seq_len):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                    # (bq, D)
    k = k_ref[0].astype(jnp.float32)                    # (bk, D)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (bq,bk)

    mask = _block_mask(qi, kj, block_q, block_kv, causal, window, seq_len)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                 # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    # fully-masked rows: make exp(NEG_INF - NEG_INF)=1 contributions vanish
    p = jnp.where(mask, p, 0.0)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_scr[...] = m_new
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(p, v)

    @pl.when(kj == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)
        lse_ref[0] = (m_scr[...] + jnp.log(denom)).T        # (1, bq)


# ---------------------------------------------------------------------------
# backward kernels (FA2 layout: dq q-block-major; dk/dv kv-block-major)
# ---------------------------------------------------------------------------

def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, *, scale, block_q, block_kv, n_kv,
                         causal, window, seq_len):
    """grid (BH, nq, nk): kv minormost, dq accumulated in VMEM scratch."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0].astype(jnp.float32)                    # (bq, D)
    k = k_ref[0].astype(jnp.float32)                    # (bk, D)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)                  # (bq, Dv)
    lse = lse_ref[0].T                                  # (bq, 1)
    delta = delta_ref[0].T                              # (bq, 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    mask = _block_mask(qi, kj, block_q, block_kv, causal, window, seq_len)
    # recompute probabilities from the saved logsumexp; masked entries are
    # zeroed explicitly so padded/fully-masked rows (lse == NEG_INF) vanish
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)        # (bq, bk)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))      # (bq, bk)
    ds = p * (dp - delta)
    dq_scr[...] += jax.lax.dot(ds, k) * scale

    @pl.when(kj == n_kv - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, scale, block_q,
                          block_kv, n_q, n_t, causal, window, seq_len):
    """grid (B*Kv, nk, G*nq): the (gqa group, q block) pair is flattened into
    the minormost sequential axis, so dk/dv accumulate across all G query
    heads sharing this kv head without leaving VMEM."""
    kj = pl.program_id(1)
    t = pl.program_id(2)
    qi = t % n_q

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0].astype(jnp.float32)                    # (bq, D)
    k = k_ref[0].astype(jnp.float32)                    # (bk, D)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)                  # (bq, Dv)
    lse = lse_ref[0].T                                  # (bq, 1)
    delta = delta_ref[0].T                              # (bq, 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    mask = _block_mask(qi, kj, block_q, block_kv, causal, window, seq_len)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)        # (bq, bk)
    dv_scr[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - delta)
    dk_scr[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ()))) * scale

    @pl.when(t == n_t - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# host-side plumbing: padding, GQA grouping, pallas_call wiring
# ---------------------------------------------------------------------------

def _dims(q_shape, k_shape, block_q, block_kv):
    """-> (B, S, H, D, Kv, G, bq, bk, Sp); D is the q/k head width."""
    B, S, H, D = q_shape
    Kv = k_shape[2]
    assert H % Kv == 0, (H, Kv)
    G = H // Kv
    bq = min(block_q, S)
    Sp = -(-S // bq) * bq
    # bk must divide the padded length exactly or tail blocks are dropped
    # (e.g. S=160 with 128/256 blocks); fall back to bq, which always does
    bk = min(block_kv, Sp)
    if Sp % bk:
        bk = bq
    return B, S, H, D, Kv, G, bq, bk, Sp


def _group_q(x, Kv, G, Sp):
    """(B, S, H, D) -> (B*Kv*G, Sp, D), q heads grouped by kv head."""
    B, S, H, D = x.shape
    if Sp != S:
        x = jnp.pad(x, [(0, 0), (0, Sp - S), (0, 0), (0, 0)])
    return x.reshape(B, Sp, Kv, G, D).transpose(0, 2, 3, 1, 4) \
            .reshape(B * Kv * G, Sp, D)


def _ungroup_q(x, B, Kv, G, S):
    """Inverse of _group_q, dropping padded rows: -> (B, S, Kv*G, D)."""
    _, Sp, D = x.shape
    return x.reshape(B, Kv, G, Sp, D).transpose(0, 3, 1, 2, 4) \
            .reshape(B, Sp, Kv * G, D)[:, :S]


def _group(q, k, v, B, Sp, H, Kv, G, D, S):
    """(B, S, H|Kv, D|Dv) -> (B*Kv*G | B*Kv, Sp, D|Dv), q heads grouped by
    kv head."""
    qg = _group_q(q, Kv, G, Sp)
    if Sp != S:
        pad = [(0, 0), (0, Sp - S), (0, 0), (0, 0)]
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    kg = k.transpose(0, 2, 1, 3).reshape(B * Kv, Sp, D)
    vg = v.transpose(0, 2, 1, 3).reshape(B * Kv, Sp, v.shape[-1])
    return qg, kg, vg


def _flash_forward(q, k, v, causal, window, block_q, block_kv, interpret,
                   scale):
    """-> (out (B,S,H,Dv), residuals for the backward)."""
    B, S, H, D, Kv, G, bq, bk, Sp = _dims(q.shape, k.shape, block_q, block_kv)
    Dv = v.shape[-1]
    nq, nk = Sp // bq, Sp // bk
    qg, kg, vg = _group(q, k, v, B, Sp, H, Kv, G, D, S)

    kernel = functools.partial(
        _flash_kernel, scale=_scale(scale, D), block_q=bq, block_kv=bk,
        n_kv=nk, causal=causal, window=window, seq_len=S)

    out, lse = pl.pallas_call(
        kernel,
        grid=(B * Kv * G, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j, G=G: (b // G, j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, i, j, G=G: (b // G, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Kv * G, Sp, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * Kv * G, 1, Sp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(qg, kg, vg)

    # residuals keep the grouped/padded layouts: the backward reuses them
    # directly instead of repeating the pad+transpose relayout of q/k/v
    return _ungroup_q(out, B, Kv, G, S), (qg, kg, vg, out, lse)


def _flash_backward(causal, window, block_q, block_kv, interpret, scale,
                    res, g):
    qg, kg, vg, og, lse = res                  # all grouped+padded by the fwd
    B, S, H, Dv = g.shape
    D = qg.shape[-1]
    Kv = kg.shape[0] // B
    _, _, _, _, _, G, bq, bk, Sp = _dims((B, S, H, D), (B, S, Kv, D),
                                         block_q, block_kv)
    nq, nk = Sp // bq, Sp // bk
    dog = _group_q(g, Kv, G, Sp)
    # delta_i = sum_d do_i * o_i — the rowwise correction term of dsoftmax;
    # O(S*D) elementwise, cheaper as one fused jnp reduce than a kernel pass
    delta = jnp.sum(dog.astype(jnp.float32) * og.astype(jnp.float32),
                    axis=-1)[:, None, :]                # (N, 1, Sp) like lse

    scale = _scale(scale, D)
    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, scale=scale, block_q=bq, block_kv=bk,
        n_kv=nk, causal=causal, window=window, seq_len=S)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(B * Kv * G, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j, G=G: (b // G, j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, i, j, G=G: (b // G, j, 0)),
            pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Kv * G, Sp, D), qg.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(qg, kg, vg, dog, lse, delta)

    n_t = G * nq
    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, scale=scale, block_q=bq, block_kv=bk,
        n_q=nq, n_t=n_t, causal=causal, window=window, seq_len=S)
    # q-side blocks walk (group g, q block i) = (t // nq, t % nq)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(B * Kv, nk, n_t),
        in_specs=[
            pl.BlockSpec((1, bq, D),
                         lambda b, j, t, G=G, nq=nq: (b * G + t // nq, t % nq, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bq, Dv),
                         lambda b, j, t, G=G, nq=nq: (b * G + t // nq, t % nq, 0)),
            pl.BlockSpec((1, 1, bq),
                         lambda b, j, t, G=G, nq=nq: (b * G + t // nq, 0, t % nq)),
            pl.BlockSpec((1, 1, bq),
                         lambda b, j, t, G=G, nq=nq: (b * G + t // nq, 0, t % nq)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, j, t: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Kv, Sp, D), kg.dtype),
            jax.ShapeDtypeStruct((B * Kv, Sp, Dv), vg.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, Dv), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(qg, kg, vg, dog, lse, delta)

    dq = _ungroup_q(dq, B, Kv, G, S)
    dk = dk.reshape(B, Kv, Sp, D).transpose(0, 2, 1, 3)[:, :S]
    dv = dv.reshape(B, Kv, Sp, Dv).transpose(0, 2, 1, 3)[:, :S]
    return dq, dk, dv


def _scale(scale, D):
    """The softmax scale: as given, else 1/sqrt(q/k head width)."""
    return D ** -0.5 if scale is None else scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, block_q, block_kv, interpret, scale):
    out, _ = _flash_forward(q, k, v, causal, window, block_q, block_kv,
                            interpret, scale)
    return out


def _flash_fwd_rule(q, k, v, causal, window, block_q, block_kv, interpret,
                    scale):
    return _flash_forward(q, k, v, causal, window, block_q, block_kv,
                          interpret, scale)


_flash.defvjp(_flash_fwd_rule, _flash_backward)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_kv", "interpret", "scale"))
def flash_attention(q, k, v, *, causal=True, window=0,
                    block_q=128, block_kv=256, interpret=False, scale=None):
    """q (B,S,H,D), k (B,S,Kv,D), v (B,S,Kv,Dv) -> (B,S,H,Dv).
    Self-attention layout; ``scale`` defaults to D ** -0.5.

    Differentiable: ``jax.grad`` runs the Pallas FA2 backward kernels.
    """
    return _flash(q, k, v, causal, window, block_q, block_kv, interpret,
                  scale)
