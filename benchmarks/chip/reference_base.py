"""What every plain reference shares: seeded keys, the rows it cuts from
the corpus, matrix products in full or rounded precision, AdamW with
global-norm clipping over the checked steps, and the comparison that
decides `correct`.

The model itself (its sizes, weight layout, initialisation and loss) is
in the module its configuration file names under ``"reference"``
(``reference.py`` for the dense Qwen decoders); ``for_config`` finds it.
Such a module gives ``sizes``, ``weight_shapes``, ``init_weights``,
``loss``, ``Reference``, ``program_config`` and ``program_values``, and
the counts ``train_flops_per_token`` and ``flash_attention_cost``.

``dot_dtype`` rounds both operands of every matrix product (and the
cotangents that flow back through them) to a narrower float type, scaled
per tensor: ``float8_e4m3fn`` gives the control that a correct comparison
has to reject.  ``fault='half_batch'`` leaves half the batch out of the
loss: a fault the comparison is shown to catch.
"""
from __future__ import annotations

import functools
import importlib
import os
import re
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# layer weights whose reference gradient is under this share of the median
# leaf's are left out of the update comparison: Adam moves them by
# round-off alone (a key bias under softmax has an exactly-zero gradient)
NEGLIGIBLE_GRAD = 1e-3
_MODULE = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")


def for_config(config: dict):
    """The reference module a configuration file names (``"reference":
    "reference"`` is ``reference.py`` beside this file)."""
    name = config.get("reference", "")
    here = os.path.dirname(os.path.abspath(__file__))
    if not _MODULE.match(name) or not os.path.exists(
            os.path.join(here, name + ".py")):
        raise ValueError(f"the configuration names no reference module "
                         f"beside {os.path.basename(__file__)}: {name!r}")
    return importlib.import_module(f"{__package__}.{name}")


def leaf_name(path) -> str:
    return jax.tree_util.keystr(path)


def seed_key(seed: int):
    """A PRNG key from any non-negative seed below 2**64."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


# ---------------------------------------------------------------------------
# matrix products, full or rounded
# ---------------------------------------------------------------------------

def _round_scaled(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    scale = jax.lax.stop_gradient(scale)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _narrow(x, dtype):
    return _round_scaled(x, dtype)


def _narrow_fwd(x, dtype):
    return _round_scaled(x, dtype), None


def _narrow_bwd(dtype, _, g):
    return (_round_scaled(g, dtype),)


_narrow.defvjp(_narrow_fwd, _narrow_bwd)


def make_dot(dot_dtype: Optional[str] = None):
    """einsum at HIGHEST precision, operands first rounded to ``dot_dtype``."""
    narrow = None if dot_dtype in (None, "float32") else jnp.dtype(dot_dtype)

    def dot(spec, a, b):
        if narrow is not None:
            a, b = _narrow(a, narrow), _narrow(b, narrow)
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    return dot


# ---------------------------------------------------------------------------
# the rows of the checked steps
# ---------------------------------------------------------------------------

def rows(corpus, seq_len: int, batch: int, steps: int):
    """The first ``steps`` batches of a corpus read as a ring of tokens:
    each row is the next ``seq_len + 1`` tokens, its inputs all but the
    last and its targets all but the first. -> [(tokens, targets)]"""
    corpus = np.asarray(corpus, np.int64)
    need = batch * (seq_len + 1)
    out = []
    for i in range(steps):
        grid = corpus[(i * need + np.arange(need)) % len(corpus)].reshape(
            batch, seq_len + 1)
        out.append((grid[:, :-1].astype(np.int32),
                    grid[:, 1:].astype(np.int32)))
    return out


def drop_half(x):
    """The first half of the rows, or of the positions of a single row."""
    B, S = x.shape[:2]
    return x[:B // 2] if B > 1 else x[:, :S // 2]


# ---------------------------------------------------------------------------
# the optimizer and the three checked steps
# ---------------------------------------------------------------------------

def decayed(path, leaf_shape, no_decay) -> bool:
    """AdamW decays a leaf unless it is named in ``no_decay`` or stored
    with fewer than two axes (the job file states the rule)."""
    return getattr(path[-1], "key", "") not in no_decay and len(leaf_shape) >= 2


def lr_scale(step: int, warmup: int, total: int, min_ratio: float = 0.1):
    """Linear warm-up then cosine decay; ``step`` counts from 0."""
    if step < warmup:
        return (step + 1) / max(warmup, 1)
    prog = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return min_ratio + (1 - min_ratio) * 0.5 * (1 + np.cos(np.pi * prog))


def leaf_norms(tree) -> Dict[str, jnp.ndarray]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {leaf_name(p): jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for p, x in flat}


def make_step(model, config: dict, opt: dict, dot_dtype=None, fault=None):
    """-> step(w, m, v, count, lr, tokens, labels) -> (w, m, v, loss,
    per-leaf norms of the clipped gradient), on ``model.loss``."""
    no_decay = tuple(opt["no_decay"])

    def step(w, m, v, count, lr, tokens, labels):
        val, g = jax.value_and_grad(model.loss, argnums=1)(
            config, w, tokens, labels, dot_dtype, fault)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree.leaves(g)))
        clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
        g = jax.tree.map(lambda x: x * clip, g)
        b1, b2 = opt["b1"], opt["b2"]
        bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count

        def upd(path, p, gi, mi, vi):
            mi = b1 * mi + (1 - b1) * gi
            vi = b2 * vi + (1 - b2) * gi * gi
            u = (mi / bc1) / (jnp.sqrt(vi / bc2) + opt["eps"])
            if opt["weight_decay"] and decayed(path, p.shape, no_decay):
                u = u + opt["weight_decay"] * p
            return p - lr * u, mi, vi

        out = jax.tree_util.tree_map_with_path(upd, w, g, m, v)
        pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                      is_leaf=lambda t: isinstance(t, tuple))
        return pick(0), pick(1), pick(2), val, leaf_norms(g)
    return step


class Reference:
    """A reference model's jitted functions for one configuration and job,
    compiled once and run for any seed.

    ``model``: the reference module (``init_weights``, ``loss``);
    ``shardings``: optional (weights, batch) shardings that spread the
    step over several chips.
    """

    def __init__(self, model, config: dict, opt: dict, schedule: dict,
                 shardings=None):
        self.model = model
        self.config, self.opt, self.schedule = config, opt, schedule
        w_sh, self.b_sh = shardings or (None, None)
        self.make = jax.jit(functools.partial(model.init_weights, config),
                            out_shardings=w_sh)
        self.zeros = jax.jit(lambda w: jax.tree.map(jnp.zeros_like, w),
                             out_shardings=w_sh)
        self.change = jax.jit(lambda w, k: leaf_norms(jax.tree.map(
            jnp.subtract, w, model.init_weights(config, k))))
        self.steps = {}

    def _step(self, dot_dtype, fault):
        if (dot_dtype, fault) not in self.steps:
            self.steps[dot_dtype, fault] = jax.jit(
                make_step(self.model, self.config, self.opt, dot_dtype,
                          fault),
                donate_argnums=(0, 1, 2))
        return self.steps[dot_dtype, fault]

    def run(self, seed: int, batches, dot_dtype=None, fault=None) -> dict:
        """The first ``len(batches)`` steps from the seeded weights.

        ``batches``: host (tokens, targets) pairs, as ``rows`` cuts them
        from the corpus the program trained on.  Returns the losses, the per-leaf norms of the first
        clipped gradient and the per-leaf norms of the weights' change
        over all the steps.
        """
        key = seed_key(seed)
        step = self._step(dot_dtype, fault)
        w = self.make(key)
        m, v = self.zeros(w), self.zeros(w)
        losses, first = [], None
        sched = self.schedule
        for i, (tokens, labels) in enumerate(batches):
            if self.b_sh is not None:
                tokens, labels = jax.device_put((tokens, labels), self.b_sh)
            lr = self.opt["lr"] * lr_scale(i, sched["warmup"], sched["total"])
            w, m, v, val, gn = step(w, m, v, jnp.float32(i + 1),
                                    jnp.float32(lr), tokens, labels)
            losses.append(val)
            if first is None:
                first = gn
        del m, v
        change = self.change(w, key)
        return {"losses": [float(x) for x in losses],
                "grad_norms": {k: float(x) for k, x in first.items()},
                "update_norms": {k: float(x) for k, x in change.items()}}


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def _worst(got: dict, want: dict, names):
    """Largest |norm_got - norm_want| over the leaves, each against the
    larger of its own reference norm and the median leaf's."""
    med = float(np.median([want[n] for n in names]))
    gaps = {n: abs(got[n] - want[n]) / max(want[n], med, 1e-30)
            for n in names}
    gaps = {n: g if np.isfinite(g) else float("inf") for n, g in gaps.items()}
    name = max(gaps, key=gaps.get)
    return gaps[name], name


def compare(got: dict, want: dict) -> dict:
    """The three numbers `correct` is decided by, each with where it is
    worst: ``loss_gap`` (nats, worst step), ``grad_norm_gap`` (first
    clipped gradient, worst leaf) and ``update_norm_gap`` (weights' change
    over the checked steps, worst leaf among those the reference moves)."""
    if set(got["grad_norms"]) != set(want["grad_norms"]):
        raise ValueError("program and reference weights differ in layout: "
                         f"{sorted(set(got['grad_norms']) ^ set(want['grad_norms']))}")
    steps = [abs(a - b) for a, b in zip(got["losses"], want["losses"])]
    if len(steps) != len(want["losses"]) or not all(np.isfinite(steps)):
        loss_gap, at = float("inf"), "non-finite"
    else:
        at = int(np.argmax(steps))
        loss_gap, at = steps[at], f"step {at + 1}"
    names = sorted(want["grad_norms"])
    grad_gap, grad_at = _worst(got["grad_norms"], want["grad_norms"], names)
    gmed = float(np.median([want["grad_norms"][n] for n in names]))
    moved = [n for n in names
             if want["grad_norms"][n] >= NEGLIGIBLE_GRAD * gmed]
    upd_gap, upd_at = _worst(got["update_norms"], want["update_norms"], moved)
    return {"loss_gap": (loss_gap, at), "grad_norm_gap": (grad_gap, grad_at),
            "update_norm_gap": (upd_gap, upd_at),
            "left_out": sorted(set(names) - set(moved))}
