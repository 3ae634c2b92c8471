"""Plain float32 train step of a dense decoder, the yardstick for `correct`.

It follows a model's published ``config.json`` (the benchmark's
configuration file) and nothing of the program under test: its own weight
layout and initialisation, a causal softmax attention that materialises the
scores, SwiGLU, RMSNorm, rotary embeddings in the two-halves convention,
tied embeddings, next-token cross entropy, global-norm clipping and AdamW.
Every matrix product runs at ``Precision.HIGHEST``; each layer is
rematerialised in the backward pass so that the whole step fits on the
chips the timed program ran on.

``dot_dtype`` rounds both operands of every matrix product (and the
cotangents that flow back through them) to a narrower float type, scaled
per tensor: ``float8_e4m3fn`` gives the control that a correct comparison
has to reject.  ``fault='half_batch'`` leaves half the batch out of the
loss and ``fault='no_exchange'`` leaves out the exchange of gradients
between chips: two of the faults the comparison is shown to catch.

Weights are a dict in the layout that ``weight_shapes`` spells out (layers
stacked on a leading axis), made by ``init_weights`` from a seed.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# layer weights whose reference gradient is under this share of the median
# leaf's are left out of the update comparison: Adam moves them by
# round-off alone (a key bias under softmax has an exactly-zero gradient)
NEGLIGIBLE_GRAD = 1e-3


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


def leaf_name(path) -> str:
    return jax.tree_util.keystr(path)


def seed_key(seed: int):
    """A PRNG key from any non-negative seed below 2**64."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


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

def local_grads(config: dict, w, tokens, labels, dot_dtype=None):
    """The gradient as data parallelism without its exchange leaves it:
    with one row on each of B chips, chip i keeps only its own row's part
    of the batch-mean gradient, for the block of each weight it holds
    (block i of the weight's largest axis that B divides)."""
    B = tokens.shape[0]

    def keep(a, g, i):
        dims = [d for d in np.argsort(a.shape)[::-1] if a.shape[d] % B == 0]
        if not dims:
            return a + g / B
        idx = jnp.arange(a.shape[dims[0]]) // (a.shape[dims[0]] // B)
        shape = [1] * a.ndim
        shape[dims[0]] = -1
        return jnp.where((idx == i).reshape(shape), g / B, a)

    def row(carry, i):
        total, acc = carry
        t = jax.lax.dynamic_slice_in_dim(tokens, i, 1)
        lab = jax.lax.dynamic_slice_in_dim(labels, i, 1)
        val, g = jax.value_and_grad(loss, argnums=1)(config, w, t, lab,
                                                     dot_dtype)
        return (total + val, jax.tree.map(
            lambda a, gi: keep(a, gi, i), acc, g)), None

    (total, acc), _ = jax.lax.scan(
        row, (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, w)),
        jnp.arange(B))
    return total / B, acc


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


def make_step(config: dict, opt: dict, dot_dtype=None, fault=None):
    """-> step(w, m, v, count, lr, tokens, labels) -> (w, m, v, loss,
    per-leaf norms of the clipped gradient)."""
    no_decay = tuple(opt["no_decay"])

    def step(w, m, v, count, lr, tokens, labels):
        if fault == "no_exchange":
            val, g = local_grads(config, w, tokens, labels, dot_dtype)
        else:
            val, g = jax.value_and_grad(loss, argnums=1)(
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
    """The reference's jitted functions for one configuration and job,
    compiled once and run for any seed.

    ``shardings``: optional (weights, batch) shardings that spread the
    step over several chips.
    """

    def __init__(self, config: dict, opt: dict, schedule: dict,
                 shardings=None):
        self.config, self.opt, self.schedule = config, opt, schedule
        w_sh, self.b_sh = shardings or (None, None)
        self.make = jax.jit(functools.partial(init_weights, config),
                            out_shardings=w_sh)
        self.zeros = jax.jit(lambda w: jax.tree.map(jnp.zeros_like, w),
                             out_shardings=w_sh)
        self.change = jax.jit(lambda w, k: leaf_norms(jax.tree.map(
            jnp.subtract, w, init_weights(config, k))))
        self.steps = {}

    def _step(self, dot_dtype, fault):
        if (dot_dtype, fault) not in self.steps:
            self.steps[dot_dtype, fault] = jax.jit(
                make_step(self.config, self.opt, dot_dtype, fault),
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
