"""Shared building blocks: norms, positional encodings, FFNs, embeddings.

Everything is a pure function over explicit parameter pytrees (dicts of
jnp arrays); initialization lives next to the apply function.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# runtime knobs (orthogonal to ModelConfig: numerics / impl selection)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Runtime:
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.float32
    grad_dtype: jnp.dtype = jnp.float32  # grad-accumulation/reduce dtype
                                         # (mixed-precision policy; the
                                         # optimizer still updates in f32)
    remat: bool = False                 # checkpoint each scanned layer-block
    attn_q_chunk: int = 1024            # query chunk for blocked attention
    attn_kv_chunk: int = 1024           # kv chunk for blocked attention
    attn_min_chunked_len: int = 2048    # below this, plain masked attention
    rwkv_chunk: int = 64
    mamba_chunk: int = 256
    moe_impl: str = "auto"              # 'dense' | 'dropping' | 'dropless'
                                        # | 'ep' | 'auto'
    moe_groups: int = 1                 # data shards = dispatch groups
    moe_stat_axes: tuple = ()           # mesh axes to psum router load stats
                                        # over (set inside shard_map bodies —
                                        # EP dispatch / pipeline stages — so
                                        # the aux loss sees global counts)
    remat_inner: bool = False           # additionally checkpoint each layer
                                        # inside a scanned block (hybrids)
    gather_params: Optional[Callable] = None
                                        # per-block-iteration FSDP de-gather
                                        # constraint (keeps the all-gather
                                        # inside the layer loop instead of
                                        # letting XLA hoist the whole stack)
    gather_prefetch: bool = False       # double-buffer the per-block gather:
                                        # issue layer l+1's gather at the
                                        # top of layer l's compute so it
                                        # overlaps ('ovl' strategy token)
    attn_impl: str = "jnp"              # 'jnp' | 'pallas' (TPU hot path)
    norm_impl: str = "jnp"              # 'jnp' | 'pallas' (fused rmsnorm VJP)
    constrain: Optional[Callable] = None  # (name, x) -> x sharding constraint
    # pipeline parallelism (schedule over a mesh axis, core/pipeline.py):
    # set by parallel.make_runtime when the plan has a 'pipe' axis
    pipeline_axis: str = ""             # mesh axis name ('' = no pipelining)
    pipeline_microbatches: int = 1      # M microbatches per (GA-)minibatch
    pipeline_mesh: Optional[object] = None   # Mesh the shard_map runs over
    pipeline_batch_axes: tuple = ()     # batch-dim mesh axes inside the pipe
    pipeline_schedule: str = "gpipe"    # 'gpipe' | '1f1b'
    pipeline_tp_axis: str = ""          # model axis to Megatron-compose
                                        # inside the stage (head_tp plans)
    pipeline_cp_axis: str = ""          # model axis to context-compose
                                        # inside the stage (context plans)
    pipeline_param_spec_fn: Optional[Callable] = None
                                        # (tree_path, ndim) -> PartitionSpec
                                        # for stage param leaves (stack dim
                                        # over 'pipe' + inner model/expert
                                        # sharding); None -> stack dim only
    # manual inner-mesh composition, active only inside a pipeline stage
    # body (set on the stage Runtime by transformer._pipeline_blocks):
    tp_reduce_axis: str = ""            # psum mixer/ffn outputs over this
                                        # axis (Megatron-TP inside shard_map)
    cp_axis: str = ""                   # attention gathers KV over this
                                        # axis (manual context parallelism)
    # expert parallelism (sharded all-to-all dispatch, core/expert.py):
    # set by parallel.make_runtime when the plan has an 'expert' axis
    expert_axis: str = ""               # mesh axis of the EP all-to-all
    expert_mesh: Optional[object] = None     # Mesh the EP shard_map runs over
    expert_token_axes: tuple = ()       # mesh axes sharding the token dim
    kernel_shard: Optional[Callable] = None
                                        # (fn, *args, heads) -> fn per device
                                        # shard under the plan's mesh; set by
                                        # parallel.make_runtime (GSPMD cannot
                                        # partition a Pallas TPU kernel)

    def c(self, name: str, x):
        """Apply a named sharding constraint if a parallel plan is active."""
        if self.constrain is None:
            return x
        return self.constrain(name, x)

    def per_shard(self, fn, *args, heads: bool = False):
        """Call a Pallas kernel wrapper ``fn(*args)``: directly without a
        multi-device plan, else on each device's shard of the operands
        (``parallel.make_kernel_sharder``; ``heads`` marks attention
        operands whose dim 2 may shard over the model axis)."""
        if self.kernel_shard is None:
            return fn(*args)
        return self.kernel_shard(fn, *args, heads=heads)


DEFAULT_RUNTIME = Runtime()


# ---------------------------------------------------------------------------
# Megatron-TP reduction (manual shard_map composition)
# ---------------------------------------------------------------------------
# Inside a fully-manual shard_map (a pipeline stage) tensor parallelism
# reduces each sublayer's row-parallel partial output with a *raw*
# jax.lax.psum.  Raw — not a custom "logical" vjp — because jax's
# shard_map machinery differentiates the physical SPMD program: unmentioned
# output axes are implicitly pmean'd, unmentioned input cotangents are
# psummed, and psum transposes to psum, which together make the physical
# gradients equal the logical ones exactly (a hand-rolled identity-backward
# psum breaks that bookkeeping and mis-scales every gradient that crosses
# it).  The column-parallel input side needs no marker at all for the same
# reason.

def tp_reduce_out(x, rt: "Runtime"):
    """Sum a row-parallel sublayer's partial output over the model axis."""
    if not rt.tp_reduce_axis:
        return x
    return jax.lax.psum(x, rt.tp_reduce_axis)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(cfg, key, d=None):
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}
    return {"scale": jnp.ones((d,))}


def apply_norm(p, x, eps, rt: Optional["Runtime"] = None):
    if (rt is not None and rt.norm_impl == "pallas" and "bias" not in p
            and x.shape[-1] % 128 == 0):
        # fused Pallas rmsnorm (custom_vjp: backward is a kernel too);
        # layernorm and non-lane-aligned dims stay on the jnp path
        from repro.kernels import ops as kernel_ops
        return rt.per_shard(lambda x, s: kernel_ops.rmsnorm(x, s, eps=eps),
                            x, p["scale"])
    xf = x.astype(jnp.float32)
    if "bias" in p:  # layernorm
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:            # rmsnorm
        ms = (xf * xf).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_headwise(scale, x, eps):
    """Per-head q/k RMSNorm (Qwen3). x: (..., head_dim)."""
    xf = x.astype(jnp.float32)
    ms = (xf * xf).mean(-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary / positional embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_freqs(dim: int, theta: float, yarn) -> jnp.ndarray:
    """YaRN's inverse frequencies (DeepSeek-V2's ``yarn_find_correction_range``
    and linear ramp): the fastest-turning frequencies keep the plain rope
    value, the slowest are divided by the factor, a ramp between the
    correction dims ``low`` and ``high`` blends the two."""
    extra = rope_freqs(dim, theta)
    inter = extra / yarn.factor

    def correction_dim(rotations):
        return (dim * np.log(yarn.original_max_position
                             / (rotations * 2 * np.pi))) / (2 * np.log(theta))

    low = max(int(np.floor(correction_dim(yarn.beta_fast))), 0)
    high = min(int(np.ceil(correction_dim(yarn.beta_slow))), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return inter * (1.0 - keep) + extra * keep


def rope_angles(positions, head_dim, theta, yarn=None):
    """positions: (..., S) int -> angles (..., S, head_dim//2) fp32."""
    if yarn is not None and yarn.factor > 1:
        inv = yarn_freqs(head_dim, theta, yarn)
    else:
        inv = rope_freqs(head_dim, theta)
    return positions.astype(jnp.float32)[..., None] * inv


def apply_rope(x, angles):
    """x: (B, S, H, D); angles: (B, S, D//2). Rotates pairs (x[2i], x[2i+1])
    laid out as two halves (llama convention)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    # angles: (B, S, d2) -> (B, S, 1, d2) to broadcast over heads
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mrope_angles(position_ids, head_dim, theta, sections):
    """Qwen2-VL M-RoPE. position_ids: (3, B, S) for (t, h, w).

    Returns angles (B, S, head_dim//2) where frequency slots are split into
    three contiguous sections driven by the t/h/w position streams.
    """
    inv = rope_freqs(head_dim, theta)                      # (d2,)
    ang = position_ids.astype(jnp.float32)[..., None] * inv  # (3, B, S, d2)
    d2 = head_dim // 2
    assert sum(sections) == d2, (sections, d2)
    idx = np.zeros((d2,), dtype=np.int32)
    off = 0
    for s_i, sec in enumerate(sections):
        idx[off:off + sec] = s_i
        off += sec
    sel = jnp.asarray(idx)                                 # (d2,)
    # pick, per frequency slot, the angle stream named by `sel`
    return jnp.einsum("sbtd,ds->btd", ang, jax.nn.one_hot(sel, 3, axis=-1))


def sinusoidal_table(max_len: int, d_model: int) -> jnp.ndarray:
    pos = np.arange(max_len)[:, None]
    dim = np.arange(0, d_model, 2)[None, :]
    ang = pos / np.power(10000.0, dim / d_model)
    out = np.zeros((max_len, d_model), dtype=np.float32)
    out[:, 0::2] = np.sin(ang)
    out[:, 1::2] = np.cos(ang)
    return jnp.asarray(out)


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------

def init_embed(cfg, key):
    k1, k2 = jax.random.split(key)
    p = {"tok": jax.random.normal(k1, (cfg.vocab_size, cfg.d_model)) * 0.02}
    if not cfg.tie_embeddings:
        p["lm_head"] = jax.random.normal(k2, (cfg.d_model, cfg.vocab_size)) \
            * (cfg.d_model ** -0.5)
    return p


def embed_tokens(p, tokens, rt: Runtime):
    w = p["tok"].astype(rt.compute_dtype)
    return rt.c("act_btd", jnp.take(w, tokens, axis=0))


def lm_logits(p, h, rt: Runtime):
    if "lm_head" in p:
        w = p["lm_head"].astype(rt.compute_dtype)
    else:
        w = p["tok"].astype(rt.compute_dtype).T
    return rt.c("logits", jnp.einsum("bsd,dv->bsv", h, w))


# ---------------------------------------------------------------------------
# dense FFN (SwiGLU / GELU / relu^2)
# ---------------------------------------------------------------------------

def _act(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "relu2": lambda x: jnp.square(jax.nn.relu(x))}[name]


def init_mlp(cfg, key, d_ff=None):
    d, dff = cfg.d_model, d_ff or (cfg.dense_d_ff or cfg.d_ff)
    ks = jax.random.split(key, 3)
    scale_in, scale_out = d ** -0.5, dff ** -0.5
    p = {"w_up": jax.random.normal(ks[0], (d, dff)) * scale_in,
         "w_down": jax.random.normal(ks[1], (dff, d)) * scale_out}
    if cfg.glu:
        p["w_gate"] = jax.random.normal(ks[2], (d, dff)) * scale_in
    return p


def apply_mlp(cfg, p, x, rt: Runtime):
    act = _act(cfg.act)
    up = rt.c("act_btf", jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype)))
    if "w_gate" in p:
        gate = rt.c("act_btf", jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype)))
        h = act(gate) * up
    else:
        h = act(up)
    return rt.c("act_btd", jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype)))
