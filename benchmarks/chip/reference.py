"""Plain float32 train step of a dense Qwen decoder (``model_type``
qwen2 or qwen3), the yardstick for `correct` in the cells whose
configuration file names ``"reference": "reference"``.

It follows a model's published ``config.json`` (the benchmark's
configuration file) and nothing of the program under test: its own weight
layout and initialisation, a causal softmax attention that materialises the
scores, SwiGLU, RMSNorm, rotary embeddings in the two-halves convention,
tied embeddings and next-token cross entropy; ``reference_base`` adds
global-norm clipping and AdamW.  Every matrix product runs at
``Precision.HIGHEST``; each layer is rematerialised in the backward pass
so that the whole step fits on the chips the timed program ran on.

Weights are a dict in the layout that ``weight_shapes`` spells out (layers
stacked on a leading axis), made by ``init_weights`` from a seed.

Beside the equations: ``program_config``, the one function here that
reads the program (its configuration registry), builds the program's
configuration from the file, and ``program_values`` reads it back in the
file's keys; ``train_flops_per_token`` and ``flash_attention_cost`` are
this model's counts of operations and bytes.
"""
from __future__ import annotations

import dataclasses
import functools
import sys
from typing import Dict

import jax
import jax.numpy as jnp

from chip import reference_base as base
from chip.reference_base import drop_half, make_dot


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

def sizes(config: dict) -> dict:
    """The sizes the reference uses, read from a published config.json."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    mtype = config["model_type"]
    if mtype not in ("qwen2", "qwen3"):
        raise ValueError(f"reference has no layer equations for {mtype!r}")
    if config.get("use_sliding_window"):
        raise ValueError("reference covers full causal attention only")
    if not config.get("tie_word_embeddings"):
        raise ValueError("reference covers tied embeddings only")
    if config.get("hidden_act") != "silu":
        raise ValueError("reference covers SwiGLU (silu) only")
    return dict(
        d=d, h=h, kv=config["num_key_value_heads"],
        hd=config.get("head_dim") or d // h,
        ff=config["intermediate_size"], vocab=config["vocab_size"],
        layers=config["num_hidden_layers"], eps=config["rms_norm_eps"],
        theta=float(config["rope_theta"]),
        std=config["initializer_range"],
        # Qwen2 attention always carries q/k/v biases; Qwen3 has per-head
        # q/k RMSNorm and biases only where attention_bias says so
        qkv_bias=mtype == "qwen2" or bool(config.get("attention_bias")),
        qk_norm=mtype == "qwen3")


def weight_shapes(config: dict) -> Dict:
    """Shapes of the weight dict, layers stacked on axis 0."""
    s = sizes(config)
    d, L, q, kv = s["d"], s["layers"], s["h"] * s["hd"], s["kv"] * s["hd"]
    mixer = {"wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv),
             "wo": (L, q, d)}
    if s["qkv_bias"]:
        mixer.update(bq=(L, q), bk=(L, kv), bv=(L, kv))
    if s["qk_norm"]:
        mixer.update(q_norm=(L, s["hd"]), k_norm=(L, s["hd"]))
    return {
        "embed": {"tok": (s["vocab"], d)},
        "final_norm": {"scale": (d,)},
        "prefix": [],
        "blocks": [{
            "norm1": {"scale": (L, d)}, "norm2": {"scale": (L, d)},
            "mixer": mixer,
            "ffn": {"w_up": (L, d, s["ff"]), "w_gate": (L, d, s["ff"]),
                    "w_down": (L, s["ff"], d)}}],
    }


def init_weights(config: dict, key) -> Dict:
    """Seeded weights: N(0, initializer_range) matrices and embedding,
    unit norm scales, zero biases, all float32."""
    s = sizes(config)
    shapes = weight_shapes(config)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = getattr(path[-1], "key", "")
        if name in ("scale", "q_norm", "k_norm"):
            leaves.append(jnp.ones(shape, jnp.float32))
        elif name in ("bq", "bk", "bv"):
            leaves.append(jnp.zeros(shape, jnp.float32))
        else:
            leaves.append(s["std"] * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32))
    return jax.tree_util.tree_unflatten(tree, leaves)


def program_config(config: dict):
    """The program's ModelConfig for a published config.json, with every
    size and option set as the file states -> (cfg, fields changed from
    the program's registry entry).  Tied head and no experts: the layer
    equations here have no others."""
    from repro.configs import get_config
    base_cfg = get_config(config["registry"])
    s = sizes(config)
    want = dict(n_layers=s["layers"], d_model=s["d"], n_heads=s["h"],
                n_kv_heads=s["kv"], d_ff=s["ff"], vocab_size=s["vocab"],
                norm_eps=s["eps"], rope_theta=s["theta"],
                tie_embeddings=True, qkv_bias=s["qkv_bias"],
                qk_norm=s["qk_norm"], sliding_window=0, act="silu",
                glu=True, norm="rmsnorm", mixer="attn", rope="rope",
                attn_logit_softcap=0.0, pos_embed="none")
    if base_cfg.head_dim_ != s["hd"]:
        want["head_dim"] = s["hd"]
    if base_cfg.moe.n_experts:
        raise ValueError(f"{base_cfg.name} has experts; the reference "
                         "is dense")
    changed = {k: (getattr(base_cfg, k), v) for k, v in want.items()
               if getattr(base_cfg, k) != v}
    return dataclasses.replace(base_cfg, **want), changed


def program_values(cfg) -> dict:
    """The program's configuration read back in config.json's keys."""
    return {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim_, "intermediate_size": cfg.d_ff,
            "vocab_size": cfg.vocab_size, "rms_norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta,
            "tie_word_embeddings": cfg.tie_embeddings}


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (B, S, H, D): rotate the pairs (x[i], x[i + D/2])."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None] * inv          # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(s, dot, h, w):
    B, S, _ = h.shape
    pos = jnp.arange(S)
    mw = w["mixer"]
    x = _rms(h, w["norm1"]["scale"], s["eps"])
    q = dot("bsd,de->bse", x, mw["wq"])
    k = dot("bsd,de->bse", x, mw["wk"])
    v = dot("bsd,de->bse", x, mw["wv"])
    if s["qkv_bias"]:
        q, k, v = q + mw["bq"], k + mw["bk"], v + mw["bv"]
    q = q.reshape(B, S, s["h"], s["hd"])
    k = k.reshape(B, S, s["kv"], s["hd"])
    v = v.reshape(B, S, s["kv"], s["hd"])
    if s["qk_norm"]:
        q = _rms(q, mw["q_norm"], s["eps"])
        k = _rms(k, mw["k_norm"], s["eps"])
    q, k = _rope(q, pos, s["theta"]), _rope(k, pos, s["theta"])
    g = s["h"] // s["kv"]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = dot("bqhd,bkhd->bhqk", q, k) * s["hd"] ** -0.5
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    att = dot("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    h = h + dot("bse,ed->bsd", att.reshape(B, S, -1), mw["wo"])
    fw = w["ffn"]
    x = _rms(h, w["norm2"]["scale"], s["eps"])
    gate = dot("bsd,df->bsf", x, fw["w_gate"])
    up = dot("bsd,df->bsf", x, fw["w_up"])
    return h + dot("bsf,fd->bsd", jax.nn.silu(gate) * up, fw["w_down"])


def loss(config: dict, weights, tokens, labels, dot_dtype=None, fault=None):
    """Mean next-token cross entropy over the batch."""
    s = sizes(config)
    dot = make_dot(dot_dtype)
    tok = weights["embed"]["tok"]
    h = jnp.take(tok, tokens, axis=0)
    layer = jax.checkpoint(functools.partial(_layer, s, dot))
    h, _ = jax.lax.scan(lambda c, w: (layer(c, w), None), h,
                        weights["blocks"][0])
    h = _rms(h, weights["final_norm"]["scale"], s["eps"])
    logits = dot("bsd,vd->bsv", h, tok)
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, labels[..., None], -1)[..., 0]
    if fault == "half_batch":
        nll = drop_half(nll)
    return jnp.mean(nll)


# Reference(config, opt, schedule, shardings=None): this model's jitted
# functions over the checked steps
Reference = functools.partial(base.Reference, sys.modules[__name__])


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------

# ``matmul_params`` is N, the parameters that enter a matrix product once
# per token: the attention and FFN projections of every layer and the
# (tied) output head.  The embedding lookup, norm scales and biases do no
# matrix work and are left out.  A training step needs 6 N FLOPs per token
# (forward 2 N, backward 4 N) plus causal attention's
# 12 L S_eff H hd, S_eff = (S + 1) / 2 keys per query on average.
# Recomputed operations are not counted.

def matmul_params(config: dict) -> int:
    s = sizes(config)
    d, q, kv = s["d"], s["h"] * s["hd"], s["kv"] * s["hd"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * s["ff"]
    return s["layers"] * per_layer + s["vocab"] * d


def attention_flops_per_token(config: dict, seq_len: int) -> float:
    s = sizes(config)
    return 12.0 * s["layers"] * (seq_len + 1) / 2 * s["h"] * s["hd"]


def train_flops_per_token(config: dict, seq_len: int) -> float:
    return 6.0 * matmul_params(config) + attention_flops_per_token(config,
                                                                   seq_len)


def flash_attention_cost(config: dict, seq_len: int, batch: int,
                         itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes one training step's flash-attention kernels
    need, over all layers and the whole batch.

    Each (row, head) sees S (S + 1) / 2 causal query-key pairs; a matrix
    product over them costs 2 hd FLOPs a pair.  The forward takes two
    (Q K^T, P V), the backward five (Q K^T again, dP = dO V^T, dV = P^T dO,
    dQ = dS K, dK = dS^T Q).  Bytes: the forward reads q, k, v and writes
    o and the row log-sum-exp; the backward reads q, k, v, dO, the
    log-sum-exp and the row dot(dO, o), and writes dq, dk, dv.
    """
    s = sizes(config)
    L, H, Kv, D = s["layers"], s["h"], s["kv"], s["hd"]
    pairs = seq_len * (seq_len + 1) / 2
    mm = 2.0 * D * pairs * batch * H
    q = batch * seq_len * H * D * itemsize
    kv = batch * seq_len * Kv * D * itemsize
    row = batch * seq_len * H * 4
    return {"flops": L * 7 * mm,
            "bytes": L * ((q + 2 * kv + q + row)
                          + (q + 2 * kv + q + 2 * row + q + 2 * kv))}
