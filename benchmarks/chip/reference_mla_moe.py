"""Plain float32 train step of a DeepSeek-V2 decoder (``model_type``
deepseek_v2): latent attention and fine-grained experts, the yardstick for
`correct` in the cells whose configuration file names
``"reference": "reference_mla_moe"``.

It follows the published ``config.json`` (the benchmark's configuration
file) and Hugging Face's ``modeling_deepseek.py`` for DeepSeek-V2, and
nothing of the program under test: its own weight layout and
initialisation; latent attention with q straight from the input (no q
compression), keys and values up from an RMS-normed latent, one rotated
key part shared by every head, YaRN rope frequencies and softmax scale,
the rotated dims de-interleaved before the rotation as
``apply_rotary_pos_emb`` does; a causal softmax attention that
materialises the scores a block of queries at a time; dense SwiGLU layers
first, then layers of shared experts and softmax top-k routed experts;
untied embedding and head; next-token cross entropy plus each expert
layer's balance loss.  ``reference_base`` adds global-norm clipping and
AdamW.  Every matrix product runs at ``Precision.HIGHEST``; each layer is
rematerialised in the backward pass, and each block of queries within it,
so that the whole step fits on the chip the timed program ran on.

The expert share: a configuration cut to one chip's share holds
``n_routed_experts`` experts (the first ones) of the ``published`` count
the router scores; the routed part of each layer is what the held experts
give for the tokens routed to them, and the rest is left out, as in the
program.  Each held expert runs on every token, weighted by the token's
router weight for it where it chose it and by nought elsewhere: no
capacity and no dispatch.

Departures from Hugging Face's code: the balance loss's coefficient is
the file's ``assumed`` ``aux_loss_alpha``, and it is DeepSeek's
``seq_aux`` form (each sequence's expert counts and mean probabilities,
averaged over the batch), which at batch 1 is the batch-wise switch form
the program computes; the layers left out by a cut are absent, not on
further chips; weights come from a seed (``init_weights``).

Beside the equations: ``program_config`` builds the program's
configuration from the file (the one function here that reads the
program) and ``program_values`` reads it back in the file's keys;
``train_flops_per_token``, ``flash_attention_cost`` and
``expert_matmul_cost`` are this model's counts of operations and bytes.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import sys
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chip import reference_base as base
from chip.reference_base import drop_half, make_dot

QUERY_BLOCK = 1024          # queries whose scores are materialised at once


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

def sizes(config: dict) -> dict:
    """The sizes the reference uses, read from a published config.json."""
    if config["model_type"] != "deepseek_v2":
        raise ValueError(f"reference has no layer equations for "
                         f"{config['model_type']!r}")
    rules = {"q_lora_rank": None, "attention_bias": False,
             "hidden_act": "silu", "tie_word_embeddings": False,
             "scoring_func": "softmax", "topk_method": "greedy",
             "n_group": 1, "topk_group": 1, "routed_scaling_factor": 1,
             "moe_layer_freq": 1}
    for key, want in rules.items():
        if config.get(key, want) != want:
            raise ValueError(f"reference covers {key} = {want!r} only")
    ys = config["rope_scaling"]
    if ys.get("type") != "yarn":
        raise ValueError("reference covers YaRN rope scaling only")
    published = config.get("published", {})
    return dict(
        d=config["hidden_size"], h=config["num_attention_heads"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        dv=config["v_head_dim"], r=config["kv_lora_rank"],
        ff=config["intermediate_size"], fe=config["moe_intermediate_size"],
        held=config["n_routed_experts"],
        experts=published.get("n_routed_experts", config["n_routed_experts"]),
        k=config["num_experts_per_tok"], shared=config["n_shared_experts"],
        dense=config["first_k_dense_replace"],
        norm_topk=config["norm_topk_prob"],
        vocab=config["vocab_size"], layers=config["num_hidden_layers"],
        eps=config["rms_norm_eps"], theta=float(config["rope_theta"]),
        yarn=dict(factor=float(ys["factor"]),
                  original=ys["original_max_position_embeddings"],
                  beta_fast=float(ys["beta_fast"]),
                  beta_slow=float(ys["beta_slow"]),
                  mscale=float(ys["mscale"]),
                  mscale_all_dim=float(ys["mscale_all_dim"])),
        std=config["assumed"]["initializer_range"],
        alpha=config["assumed"]["aux_loss_alpha"])


def weight_shapes(config: dict) -> Dict:
    """Shapes of the weight dict: the leading dense layers one by one
    under ``prefix``, the expert layers stacked on axis 0 under
    ``blocks``."""
    s = sizes(config)
    d, h, r = s["d"], s["h"], s["r"]

    def layer(*L, moe):
        mixer = {"wq": L + (d, h * (s["nope"] + s["rope"])),
                 "wkv_a": L + (d, r + s["rope"]), "kv_norm": L + (r,),
                 "wkv_b": L + (r, h * (s["nope"] + s["dv"])),
                 "wo": L + (h * s["dv"], d)}
        if moe:
            fs = s["shared"] * s["fe"]
            ffn = {"router": L + (d, s["experts"]),
                   "w_up": L + (s["held"], d, s["fe"]),
                   "w_gate": L + (s["held"], d, s["fe"]),
                   "w_down": L + (s["held"], s["fe"], d),
                   "shared": {"w_up": L + (d, fs), "w_gate": L + (d, fs),
                              "w_down": L + (fs, d)}}
        else:
            ffn = {"w_up": L + (d, s["ff"]), "w_gate": L + (d, s["ff"]),
                   "w_down": L + (s["ff"], d)}
        return {"norm1": {"scale": L + (d,)}, "norm2": {"scale": L + (d,)},
                "mixer": mixer, "ffn": ffn}

    n_moe = s["layers"] - s["dense"]
    return {
        "embed": {"tok": (s["vocab"], d), "lm_head": (d, s["vocab"])},
        "final_norm": {"scale": (d,)},
        "prefix": [layer(moe=False) for _ in range(s["dense"])],
        "blocks": [layer(n_moe, moe=True)] if n_moe else [],
    }


def init_weights(config: dict, key) -> Dict:
    """Seeded weights: N(0, initializer_range) matrices and embedding,
    unit norm scales, all float32."""
    s = sizes(config)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        weight_shapes(config), is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        if getattr(path[-1], "key", "") in ("scale", "kv_norm"):
            leaves.append(jnp.ones(shape, jnp.float32))
        else:
            leaves.append(s["std"] * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32))
    return jax.tree_util.tree_unflatten(tree, leaves)


def program_config(config: dict):
    """The program's ModelConfig for a published config.json, with every
    size and option set as the file states -> (cfg, fields changed from
    the program's registry entry).  Latent attention, an untied head, the
    held experts, and a router as wide as the published expert count."""
    from repro.configs import YarnConfig, get_config
    base_cfg = get_config(config["registry"])
    s = sizes(config)
    y = s["yarn"]
    moe = dataclasses.replace(
        base_cfg.moe, n_experts=s["experts"],
        held_experts=0 if s["held"] == s["experts"] else s["held"],
        held_offset=0, top_k=s["k"], n_shared_experts=s["shared"],
        expert_d_ff=s["fe"], moe_start_layer=s["dense"], moe_every=1,
        aux_loss_coef=s["alpha"], norm_topk_prob=s["norm_topk"])
    want = dict(
        n_layers=s["layers"], d_model=s["d"], n_heads=s["h"],
        n_kv_heads=s["h"], d_ff=s["fe"], dense_d_ff=s["ff"],
        vocab_size=s["vocab"], norm_eps=s["eps"], rope_theta=s["theta"],
        tie_embeddings=False, qkv_bias=False, qk_norm=False,
        sliding_window=0, act="silu", glu=True, norm="rmsnorm",
        mixer="attn", rope="rope", attn_logit_softcap=0.0,
        pos_embed="none", kv_lora_rank=s["r"], qk_nope_head_dim=s["nope"],
        qk_rope_head_dim=s["rope"], v_head_dim=s["dv"],
        yarn=YarnConfig(factor=y["factor"],
                        original_max_position=y["original"],
                        beta_fast=y["beta_fast"], beta_slow=y["beta_slow"],
                        mscale=y["mscale"],
                        mscale_all_dim=y["mscale_all_dim"]),
        moe=moe)
    if y["mscale"] != y["mscale_all_dim"]:
        raise ValueError("the program's latent attention leaves cos/sin "
                         "unscaled: mscale must equal mscale_all_dim")
    if base_cfg.moe.n_experts != s["experts"]:
        raise ValueError(f"{base_cfg.name}'s router scores "
                         f"{base_cfg.moe.n_experts} experts, the source "
                         f"publishes {s['experts']}")
    changed = {k: (getattr(base_cfg, k), v) for k, v in want.items()
               if getattr(base_cfg, k) != v}
    cfg = dataclasses.replace(base_cfg, **want)
    if not (cfg.mla and cfg.moe.n_held == s["held"]
            and cfg.moe.n_experts == s["experts"]):
        raise ValueError("the program's configuration lost the latent "
                         "attention or the expert share")
    return cfg, changed


def program_values(cfg) -> dict:
    """The program's configuration read back in config.json's keys."""
    m, y = cfg.moe, cfg.yarn
    return {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.kv_heads,
            "q_lora_rank": None, "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim,
            "intermediate_size": cfg.dense_d_ff,
            "moe_intermediate_size": m.expert_d_ff,
            "n_routed_experts": m.n_held,
            "num_experts_per_tok": m.top_k,
            "n_shared_experts": m.n_shared_experts,
            "first_k_dense_replace": m.moe_start_layer,
            "moe_layer_freq": m.moe_every,
            "norm_topk_prob": m.norm_topk_prob,
            "vocab_size": cfg.vocab_size, "rms_norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta,
            "rope_scaling": {
                "type": "yarn", "factor": y.factor,
                "original_max_position_embeddings": y.original_max_position,
                "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
                "mscale": y.mscale, "mscale_all_dim": y.mscale_all_dim},
            "tie_word_embeddings": cfg.tie_embeddings}


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope_tables(s, seq_len):
    """cos, sin (S, rope) of DeepseekV2YarnRotaryEmbedding."""
    y, dim, base_ = s["yarn"], s["rope"], s["theta"]
    extra = 1.0 / base_ ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / y["factor"]

    def corr(rotations):
        return (dim * math.log(y["original"] / (rotations * 2 * math.pi))) \
            / (2 * math.log(base_))
    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv_freq = jnp.asarray(inter * (1 - mask) + extra * mask, jnp.float32)
    freqs = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], -1)
    ratio = _yarn_mscale(y["factor"], y["mscale"]) \
        / _yarn_mscale(y["factor"], y["mscale_all_dim"])
    return jnp.cos(emb) * ratio, jnp.sin(emb) * ratio


def _rotate(x, cos, sin):
    """x (B, S, H, D): de-interleave (x[2i] first, x[2i+1] after), then
    x * cos + rotate_half(x) * sin."""
    B, S, H, D = x.shape
    x = x.reshape(B, S, H, D // 2, 2).swapaxes(-1, -2).reshape(B, S, H, D)
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos[None, :, None] + half * sin[None, :, None]


def _attention(s, dot, x, w, cos, sin):
    B, S, _ = x.shape
    h, nope, rope, dv, r = s["h"], s["nope"], s["rope"], s["dv"], s["r"]
    q = dot("bsd,de->bse", x, w["wq"]).reshape(B, S, h, nope + rope)
    ckv = dot("bsd,de->bse", x, w["wkv_a"])
    c = _rms(ckv[..., :r], w["kv_norm"], s["eps"])
    kv = dot("bsr,re->bse", c, w["wkv_b"]).reshape(B, S, h, nope + dv)
    k_pe = _rotate(ckv[..., None, r:], cos, sin)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], cos, sin)],
                        -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (B, S, h, rope))], -1)
    v = kv[..., nope:]
    scale = (nope + rope) ** -0.5 * _yarn_mscale(
        s["yarn"]["factor"], s["yarn"]["mscale_all_dim"]) ** 2
    Q = min(QUERY_BLOCK, S)
    if S % Q:
        raise ValueError(f"sequence {S} is not a multiple of {Q} queries")

    @jax.checkpoint
    def block(args):
        qb, start = args                                     # (B, Q, h, D)
        scores = dot("bqhd,bkhd->bhqk", qb, k) * scale
        causal = jnp.arange(S)[None, :] <= (start + jnp.arange(Q))[:, None]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        return dot("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    qs = q.reshape(B, S // Q, Q, h, nope + rope).swapaxes(0, 1)
    out = jax.lax.map(block, (qs, jnp.arange(0, S, Q)))     # (n, B, Q, h, dv)
    out = out.swapaxes(0, 1).reshape(B, S, h * dv)
    return dot("bse,ed->bsd", out, w["wo"])


def _swiglu(dot, x, w_up, w_gate, w_down):
    gate = dot("...d,df->...f", x, w_gate)
    return dot("...f,fd->...d", jax.nn.silu(gate) * dot("...d,df->...f",
                                                         x, w_up), w_down)


def _experts(s, dot, x, w):
    """Shared experts, the held routed experts and the balance loss.
    x (B, S, d) -> (y, aux)."""
    B, S, d = x.shape
    E, k = s["experts"], s["k"]
    probs = jax.nn.softmax(dot("bsd,de->bse", x, w["router"]), -1)
    top_w, top_i = jax.lax.top_k(probs, k)                  # (B, S, k)
    if s["norm_topk"]:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    chosen = jax.nn.one_hot(top_i, E, dtype=jnp.float32)    # (B, S, k, E)
    # seq_aux: per sequence, E * sum_e (count_e / (S k)) * mean prob_e
    frac = jnp.sum(chosen, (1, 2)) / (S * k)                # (B, E)
    aux = jnp.mean(E * jnp.sum(frac * jnp.mean(probs, 1), -1)) * s["alpha"]
    gate = jnp.sum(chosen * top_w[..., None], 2)[..., :s["held"]]

    def expert(y, e):
        w_up, w_gate, w_down, g = e
        return y + g[..., None] * _swiglu(dot, x, w_up, w_gate, w_down), None
    y, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(x),
                        (w["w_up"], w["w_gate"], w["w_down"],
                         jnp.moveaxis(gate, -1, 0)))
    sw = w["shared"]
    return y + _swiglu(dot, x, sw["w_up"], sw["w_gate"], sw["w_down"]), aux


def _layer(s, dot, moe, cos, sin, h, w):
    """One decoder layer -> (h, balance loss)."""
    h = h + _attention(s, dot, _rms(h, w["norm1"]["scale"], s["eps"]),
                       w["mixer"], cos, sin)
    x = _rms(h, w["norm2"]["scale"], s["eps"])
    fw = w["ffn"]
    if not moe:
        return h + _swiglu(dot, x, fw["w_up"], fw["w_gate"], fw["w_down"]), \
            jnp.zeros((), jnp.float32)
    y, aux = _experts(s, dot, x, fw)
    return h + y, aux


def loss(config: dict, weights, tokens, labels, dot_dtype=None, fault=None):
    """Mean next-token cross entropy plus the expert layers' balance
    losses."""
    s = sizes(config)
    dot = make_dot(dot_dtype)
    cos, sin = _rope_tables(s, tokens.shape[1])
    h = jnp.take(weights["embed"]["tok"], tokens, axis=0)
    aux = jnp.zeros((), jnp.float32)
    dense = jax.checkpoint(functools.partial(_layer, s, dot, False, cos, sin))
    for w in weights["prefix"]:
        h, a = dense(h, w)
        aux = aux + a
    moe = jax.checkpoint(functools.partial(_layer, s, dot, True, cos, sin))
    for stack in weights["blocks"]:
        h, auxes = jax.lax.scan(moe, h, stack)
        aux = aux + jnp.sum(auxes)
    h = _rms(h, weights["final_norm"]["scale"], s["eps"])
    logits = dot("bsd,dv->bsv", h, weights["embed"]["lm_head"])
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, labels[..., None], -1)[..., 0]
    if fault == "half_batch":
        nll = drop_half(nll)
    return jnp.mean(nll) + aux


# Reference(config, opt, schedule, shardings=None): this model's jitted
# functions over the checked steps
Reference = functools.partial(base.Reference, sys.modules[__name__])


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------

# ``matmul_params`` is N, the parameters that enter a matrix product once
# per token: the attention projections of every layer, the dense layers'
# FFN, each expert layer's router and shared experts, the routed experts
# at the load a held share expects, k * held / E experts a token (0.75
# for 8 of 64 at top-6), and the head.  The embedding lookup and norm
# scales do no matrix work and are left out.  A training step needs 6 N
# FLOPs per token plus causal attention's 6 L S_eff H (Dqk + Dv),
# S_eff = (S + 1) / 2 keys per query on average.  Recomputed operations
# are not counted.

def _moe_layers(s) -> int:
    return s["layers"] - s["dense"]


def matmul_params(config: dict) -> float:
    s = sizes(config)
    d, h, r = s["d"], s["h"], s["r"]
    attn = (d * h * (s["nope"] + s["rope"]) + d * (r + s["rope"])
            + r * h * (s["nope"] + s["dv"]) + h * s["dv"] * d)
    expert = 3 * d * s["fe"]
    moe = (d * s["experts"] + s["shared"] * expert
           + s["k"] * s["held"] / s["experts"] * expert)
    return (s["layers"] * attn + s["dense"] * 3 * d * s["ff"]
            + _moe_layers(s) * moe + s["vocab"] * d)


def attention_flops_per_token(config: dict, seq_len: int) -> float:
    s = sizes(config)
    return 6.0 * s["layers"] * (seq_len + 1) / 2 * s["h"] * (
        s["nope"] + s["rope"] + s["dv"])


def train_flops_per_token(config: dict, seq_len: int) -> float:
    return 6.0 * matmul_params(config) + attention_flops_per_token(config,
                                                                   seq_len)


def flash_attention_cost(config: dict, seq_len: int, batch: int,
                         itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes one training step's flash-attention kernels
    need, over all layers and the whole batch; q/k heads Dqk wide, v heads
    Dv.

    Each (row, head) sees S (S + 1) / 2 causal query-key pairs.  The
    forward's Q K^T costs 2 Dqk FLOPs a pair and P V 2 Dv; the backward
    repeats Q K^T and adds dQ = dS K and dK = dS^T Q (2 Dqk each), dP =
    dO V^T and dV = P^T dO (2 Dv each).  Bytes: the forward reads q, k, v
    and writes o and the row log-sum-exp; the backward reads q, k, v, dO,
    the log-sum-exp and the row dot(dO, o), and writes dq, dk, dv.
    """
    s = sizes(config)
    L, H = s["layers"], s["h"]
    Dqk, Dv = s["nope"] + s["rope"], s["dv"]
    pairs = seq_len * (seq_len + 1) / 2
    flops = 2.0 * pairs * batch * H * (4 * Dqk + 3 * Dv)
    rows = batch * seq_len * H
    q, k, v = rows * Dqk * itemsize, rows * Dqk * itemsize, \
        rows * Dv * itemsize
    o, row = rows * Dv * itemsize, rows * 4
    return {"flops": L * flops,
            "bytes": L * ((q + k + v + o + row)
                          + (q + k + v + o + 2 * row + q + k + v))}


def expert_matmul_cost(config: dict, items: float,
                       itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of one training step's grouped matrix products
    over the held experts, for ``items`` routed items computed in all the
    expert layers together (the program's counter).

    Each expert's FFN is three products of an item with a d x fe matrix
    (up, gate, down); each runs forward, and backward twice (the input's
    gradient and the weight's): nine products of 2 d fe FLOPs an item.
    Each reads its items' rows and the held experts' weights and writes
    its items' result rows, all ``itemsize`` bytes wide.
    """
    s = sizes(config)
    d, fe = s["d"], s["fe"]
    weights = _moe_layers(s) * s["held"] * d * fe
    return {"flops": 9 * 2.0 * items * d * fe,
            "bytes": 9 * itemsize * (items * (d + fe) + weights)}
