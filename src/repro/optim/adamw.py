"""AdamW with decoupled weight decay and global-norm gradient clipping.

Optimizer state (m, v) is a pytree congruent with params, so FSDP sharding
rules apply verbatim (ZeRO: optimizer states sharded with the parameters —
this is what makes sharded data parallelism memory-efficient, §2.1 of the
paper).  Moments are kept in fp32 regardless of parameter dtype.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp


# Named scopes of the update, in the ``op_name`` of every compiled
# instruction they cover (see ``models/transformer.py``): the global norm
# and clip factor, then the per-leaf moment and parameter update.
SCOPE_GRAD_CLIP = "grad_clip"
SCOPE_ADAMW = "adamw"
OPTIMIZER_SCOPES = (SCOPE_GRAD_CLIP, SCOPE_ADAMW)


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0


def init_opt_state(params) -> Dict[str, Any]:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def global_norm(tree) -> jnp.ndarray:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def _decay_mask(path) -> bool:
    """No weight decay for norms, biases, 1-d params."""
    leaf = getattr(path[-1], "key", getattr(path[-1], "name", str(path[-1])))
    return leaf not in ("scale", "bias", "b_dt", "conv_b", "w0",
                        "maa_x", "maa_k", "maa_r", "bq", "bk", "bv",
                        "kv_norm")


def adamw_update(cfg: AdamWConfig, params, grads, state, lr_scale=1.0):
    """-> (new_params, new_state, metrics)."""
    with jax.named_scope(SCOPE_GRAD_CLIP):
        gnorm = global_norm(grads)
        clip = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9)) \
            if cfg.grad_clip else 1.0
    step = state["step"] + 1
    t = step.astype(jnp.float32)
    bc1 = 1.0 - cfg.b1 ** t
    bc2 = 1.0 - cfg.b2 ** t
    lr = cfg.lr * lr_scale

    def upd(path, p, g, m, v):
        gf = g.astype(jnp.float32) * clip
        m2 = cfg.b1 * m + (1 - cfg.b1) * gf
        v2 = cfg.b2 * v + (1 - cfg.b2) * gf * gf
        u = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + cfg.eps)
        if cfg.weight_decay and _decay_mask(path) and p.ndim >= 2:
            u = u + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * u).astype(p.dtype), m2, v2

    with jax.named_scope(SCOPE_ADAMW):
        flat = jax.tree_util.tree_map_with_path(
            lambda path, p, g, m, v: upd(path, p, g, m, v),
            params, grads, state["m"], state["v"])
        new_params = jax.tree.map(lambda t3: t3[0], flat,
                                  is_leaf=lambda x: isinstance(x, tuple))
        new_m = jax.tree.map(lambda t3: t3[1], flat,
                             is_leaf=lambda x: isinstance(x, tuple))
        new_v = jax.tree.map(lambda t3: t3[2], flat,
                             is_leaf=lambda x: isinstance(x, tuple))
    return new_params, {"m": new_m, "v": new_v, "step": step}, \
        {"grad_norm": gnorm, "lr": lr}
