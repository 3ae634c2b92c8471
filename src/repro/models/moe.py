"""Mixture-of-Experts FFN: shared + routed top-k experts.

Three dispatch implementations (and the expert-parallel ones of
``core/expert.py``):
  * ``dense``    — every expert computes every token, combined by router
                   weights.  Exact (no dropping); O(E/k) extra FLOPs.  Used
                   as the numerical oracle and for tiny smoke configs.
  * ``dropping`` — GShard-style fixed-capacity dispatch, but built with an
                   argsort over expert ids instead of a (T, E, C) one-hot
                   tensor, so memory is O(T·k·d + E·C·d).  This is the
                   production path: the (E, C, d) expert buffer shards as
                   (model=experts, data=capacity) and the scatter/gather
                   lowers to the all-to-all-like exchange the paper accounts
                   for in expert-parallel training.
  * ``dropless`` — the items sorted by expert into one buffer, each
                   expert's rows through a grouped matrix product
                   (``kernels.ops.grouped_matmul``): no capacity, nothing
                   dropped, and only the routed rows computed.

A layer may hold a share of the experts (``MoEConfig.held_experts``, from
``held_offset``): the router scores all ``n_experts`` and the layer adds
only what its own experts give for the items routed to them; the shared
experts are computed whole.  The share runs on ``dense`` and ``dropless``.
"""
from __future__ import annotations

import functools
import math
import warnings

import jax
import jax.numpy as jnp

from repro.models.layers import Runtime, _act

# named scopes of the routed part (inside the model's ``blocks`` scope)
SCOPE_DISPATCH = "moe_dispatch"  # router, top-k, sort, permute and combine
SCOPE_EXPERTS = "experts"        # the grouped matrix products


def init_moe(cfg, key):
    m = cfg.moe
    d, f = cfg.d_model, m.expert_d_ff
    ks = jax.random.split(key, 5)
    s_in, s_out = d ** -0.5, f ** -0.5
    p = {
        "router": jax.random.normal(ks[0], (d, m.n_experts)) * s_in,
        "w_up": jax.random.normal(ks[1], (m.n_held, d, f)) * s_in,
        "w_down": jax.random.normal(ks[2], (m.n_held, f, d)) * s_out,
    }
    if cfg.glu:
        p["w_gate"] = jax.random.normal(ks[3], (m.n_held, d, f)) * s_in
    if m.n_shared_experts:
        fs = m.n_shared_experts * f
        kk = jax.random.split(ks[4], 3)
        p["shared"] = {"w_up": jax.random.normal(kk[0], (d, fs)) * s_in,
                       "w_down": jax.random.normal(kk[1], (fs, d)) * (fs ** -0.5)}
        if cfg.glu:
            p["shared"]["w_gate"] = jax.random.normal(kk[2], (d, fs)) * s_in
    return p


def _router(cfg, p, xf, rt: Runtime = None):
    """xf (T, d) -> probs (T, E) fp32, weights/ids (T, k), aux loss.

    Inside a shard_map body (EP dispatch, pipeline stages) ``xf`` is the
    *local* token shard; ``rt.moe_stat_axes`` names the mesh axes to
    psum the load statistics over so the switch-style balance loss is
    computed from global counts — identical on every shard, and equal to
    what the single-device oracle computes on the full batch.
    """
    m = cfg.moe
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, m.top_k)             # (T, k)
    if m.norm_topk_prob:
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True),
                                        1e-9)
    # switch-style load-balance loss.  Every shard holds the same local
    # token count, so the global fractions are the pmean of the local
    # ones — pmean keeps the divisor static (a traced token-count
    # denominator would become a scalar residual, which the shard_map
    # transpose cannot shard over the mesh axes)
    occupancy = jnp.zeros((m.n_experts,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    frac_tokens = occupancy / (xf.shape[0] * m.top_k)
    frac_probs = probs.mean(0)
    axes = tuple(rt.moe_stat_axes) if rt is not None else ()
    if axes:
        frac_tokens = jax.lax.pmean(frac_tokens, axes)
        frac_probs = jax.lax.pmean(frac_probs, axes)
    aux = m.n_experts * jnp.sum(frac_tokens * frac_probs) * m.aux_loss_coef
    return probs, weights, ids, aux


def _expert_ffn(cfg, p, buf, rt: Runtime):
    """buf (E, C, d) -> (E, C, d) through each expert's FFN.

    Under manual Megatron-TP (``rt.tp_reduce_axis`` inside a pipeline
    stage) the expert hidden dim is model-sharded and the partial w_down
    output is psummed by the caller's layer-level ``tp_reduce_out``."""
    act = _act(cfg.act)
    dt = buf.dtype
    up = rt.c("expert_hidden", jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(dt)))
    if "w_gate" in p:
        gate = rt.c("expert_hidden",
                    jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(dt)))
        h = act(gate) * up
    else:
        h = act(up)
    return rt.c("expert_buf", jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dt)))


def _held_counts(ids, m):
    """ids (T, k) -> (n_held,) routed items per held expert, f32."""
    local = ids.reshape(-1) - m.held_offset
    return jax.nn.one_hot(local, m.n_held, dtype=jnp.float32).sum(0)


def _moe_dense(cfg, p, xf, rt: Runtime, route):
    """Oracle: all held experts on all tokens."""
    m = cfg.moe
    _, weights, ids, _ = route
    act = _act(cfg.act)
    dt = xf.dtype
    up = jnp.einsum("td,edf->etf", xf, p["w_up"].astype(dt))
    if "w_gate" in p:
        h = act(jnp.einsum("td,edf->etf", xf, p["w_gate"].astype(dt))) * up
    else:
        h = act(up)
    y_e = jnp.einsum("etf,efd->etd", h, p["w_down"].astype(dt))  # (E, T, d)
    w_full = jnp.zeros((xf.shape[0], m.n_experts), jnp.float32)
    w_full = w_full.at[jnp.arange(xf.shape[0])[:, None], ids].add(weights)
    w_held = w_full[:, m.held_offset:m.held_offset + m.n_held]
    return jnp.einsum("etd,te->td", y_e, w_held.astype(dt))


@jax.custom_vjp
def _routed_take(x, idx, inv_idx):
    """y[i] = x[idx[i]] (idx < 0 -> zero row).

    ``idx`` is an injective partial map and ``inv_idx`` its inverse, so the
    VJP is *also* a gather — no d-wide scatter ever reaches XLA (whose
    scatter lowering materializes huge u32 staging buffers, the dominant
    term in the baseline MoE memory profile; see EXPERIMENTS.md §Perf).
    Rows left out are selected away, not multiplied by 0, so an undefined
    row (a grouped matmul's output past its groups) cannot leak a NaN.
    """
    return jnp.where((idx >= 0)[:, None], x[jnp.maximum(idx, 0)], 0)


def _routed_take_fwd(x, idx, inv_idx):
    return _routed_take(x, idx, inv_idx), (idx, inv_idx, x.shape[0])


def _routed_take_bwd(res, dy):
    idx, inv_idx, n = res
    dx = jnp.where((inv_idx >= 0)[:, None], dy[jnp.maximum(inv_idx, 0)], 0)
    return dx, None, None


_routed_take.defvjp(_routed_take_fwd, _routed_take_bwd)


@jax.custom_vjp
def _take_tokens(x, src, dest):
    """y[r] = x[src[r] // k] (src < 0 -> zero row): token rows copied to the
    rows of their items, k = len(dest) / len(x) items a token.

    ``dest`` maps each item (token-major) to its row (-1 none), so the VJP
    is a gather too: each token sums its items' row gradients.  Nothing
    T*k-by-d is made but the output (a broadcast of x to its items would
    be)."""
    k = dest.shape[0] // x.shape[0]
    return jnp.where((src >= 0)[:, None], x[jnp.maximum(src, 0) // k], 0)


def _take_tokens_fwd(x, src, dest):
    return _take_tokens(x, src, dest), (src, dest, x.shape[0])


def _take_tokens_bwd(res, dy):
    src, dest, T = res
    rows = jnp.where((dest >= 0)[:, None], dy[jnp.maximum(dest, 0)], 0)
    return rows.reshape(T, -1, dy.shape[-1]).sum(1), None, None


_take_tokens.defvjp(_take_tokens_fwd, _take_tokens_bwd)


def _route_capacity(fids, n_experts: int, capacity: int):
    """Index plumbing only (1-wide int ops): slot <-> item maps.

    fids (n_items,) int32 expert ids -> (dest (n_items,), inv (E*C,)):
    ``dest[i]`` is item i's slot in the (E, C) buffer (-1 = dropped),
    ``inv[s]`` the item occupying slot s (-1 = empty).  Shared by the
    grouped-dropping dispatch and the expert-parallel all-to-all path
    (core/expert.py), which routes into its *local* send buffer with the
    same maps.
    """
    n_items = fids.shape[0]
    E, C = n_experts, capacity
    order = jnp.argsort(fids, stable=True)
    sorted_ids = fids[order]
    counts = jnp.zeros((E,), jnp.int32).at[fids].add(1)
    starts = jnp.cumsum(counts) - counts
    pos_sorted = jnp.arange(n_items, dtype=jnp.int32) - starts[sorted_ids]
    keep_sorted = pos_sorted < C
    slot_sorted = sorted_ids * C + jnp.minimum(pos_sorted, C - 1)
    # item -> slot (dropped items -> -1)
    dest = jnp.full((n_items,), -1, jnp.int32).at[order].set(
        jnp.where(keep_sorted, slot_sorted, -1))
    # slot -> item (empty slots -> -1); dropped items scatter out of
    # bounds and are discarded by mode="drop"
    inv = jnp.full((E * C,), -1, jnp.int32).at[
        jnp.where(keep_sorted, slot_sorted, E * C)].set(
        order, mode="drop")
    return dest, inv


def _moe_dropping(cfg, p, xf, rt: Runtime, route):
    """Fixed-capacity dispatch with an explicit *group* dimension.

    Tokens are reshaped to (G, Tg, d) where G = number of data shards
    (``rt.moe_groups``); all routing index math (argsort, positions,
    capacity) is then purely per-group — GSPMD keeps it local to each data
    shard — and the only communication is the (E, G·Cg, d) expert-buffer
    reshard from group-sharded to expert-sharded layout: the expert-parallel
    all-to-all the paper's cost model accounts for.

    The d-wide data movement (items -> expert slots and back) is expressed
    with ``_routed_take``: gathers in both directions, scatter-free.
    """
    m = cfg.moe
    T, d = xf.shape
    k, E = m.top_k, m.n_experts
    _, weights, ids, _ = route

    G = max(1, min(rt.moe_groups, T))
    while T % G:
        G //= 2
    Tg = T // G
    Cg = int(math.ceil(Tg * k * m.capacity_factor / E))
    Cg = max(8, -(-Cg // 8) * 8)                             # pad to 8

    xg = rt.c("moe_group_tokens", xf.reshape(G, Tg, d))
    idg = ids.reshape(G, Tg * k)                             # token-major
    wg = weights.reshape(G, Tg, k)

    dest_g, inv_g = jax.vmap(
        lambda fids: _route_capacity(fids, E, Cg))(idg)      # (G, Tg*k), (G, E*Cg)

    def dispatch_one(x_g, dest, inv):
        # token -> items without a gather (broadcast is scatter-free in bwd)
        x_items = jnp.broadcast_to(x_g[:, None], (Tg, k, d)).reshape(Tg * k, d)
        buf = _routed_take(x_items, inv, dest)               # (E*Cg, d)
        return buf.reshape(E, Cg, d)

    buf_g = jax.vmap(dispatch_one)(xg, dest_g, inv_g)        # (G, E, Cg, d)
    buf = buf_g.transpose(1, 0, 2, 3).reshape(E, G * Cg, d)
    buf = rt.c("expert_buf", buf)                            # all-to-all here

    out = _expert_ffn(cfg, p, buf, rt)                       # (E, G*Cg, d)
    out_g = rt.c("moe_group_buf",
                 out.reshape(E, G, Cg, d).transpose(1, 0, 2, 3))

    def combine_one(out_b, dest, inv, w_g):
        rows = _routed_take(out_b.reshape(E * Cg, d), dest, inv)  # (Tg*k, d)
        return (rows.reshape(Tg, k, d) * w_g[..., None].astype(rows.dtype)
                ).sum(axis=1)

    y = jax.vmap(combine_one)(out_g, dest_g, inv_g, wg)      # (G, Tg, d)
    return y.reshape(T, d)


def _sort_by_expert(ids, m):
    """Index plumbing only (1-wide int ops) for the dropless buffer: the
    T*k items (token-major) sorted by held expert, the items routed to
    experts held elsewhere after them.

    -> (src (N,), dest (N,), sizes (n_held,)): ``src[r]`` is the item in
    buffer row r (-1 past the held items), ``dest[i]`` item i's row (-1
    for an item held elsewhere), ``sizes`` each held expert's rows."""
    local = ids.reshape(-1) - m.held_offset
    N, n = local.shape[0], m.n_held
    group = jnp.where((local >= 0) & (local < n), local, n)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((n + 1,), jnp.int32).at[group].add(1)[:n]
    rows = jnp.arange(N, dtype=jnp.int32)
    src = jnp.where(rows < sizes.sum(), order, -1)
    dest = jnp.zeros((N,), jnp.int32).at[order].set(rows)
    return src, jnp.where(group < n, dest, -1), sizes


def _grouped_ffn(cfg, p, xs, sizes):
    """xs (N, d), rows sorted by held expert with ``sizes`` rows each (the
    rest ignored) -> (N, d) through each row's expert FFN; rows past the
    held items are undefined."""
    from repro.kernels import ops as kernel_ops
    act = _act(cfg.act)
    dt = xs.dtype

    def mm(a, w):
        return kernel_ops.grouped_matmul(a, w.astype(dt), sizes)
    up = mm(xs, p["w_up"])
    h = act(mm(xs, p["w_gate"])) * up if "w_gate" in p else act(up)
    return mm(h, p["w_down"])


def _moe_dropless(cfg, p, xf, rt: Runtime, route):
    """Dropless dispatch over the held experts: every item routed to one of
    them is computed, by a grouped matrix product over a buffer sorted by
    expert; the d-wide moves in and out of it are gathers.  The buffer
    holds all T*k items, the most that can be routed here; rows past the
    held items are never computed."""
    _, weights, ids, _ = route
    stacks = {n: p[n] for n in ("w_up", "w_gate", "w_down") if n in p}
    return _dropless_routed(cfg, stacks, xf, weights, ids)


# The buffer's T*k rows are mostly items held elsewhere (7/8 of them for 8
# of 64 experts at top-6); keeping its activations for the backward would
# hold GBs of them, so the routed part is recomputed there from its inputs
# (the gathers and the grouped products' forward, a few % of the step).
@functools.partial(jax.checkpoint, static_argnums=(0,))
def _dropless_routed(cfg, stacks, xf, weights, ids):
    m = cfg.moe
    T, d = xf.shape
    with jax.named_scope(SCOPE_DISPATCH):
        src, dest, sizes = _sort_by_expert(ids, m)
        xs = _take_tokens(xf, src, dest)
    with jax.named_scope(SCOPE_EXPERTS):
        out = _grouped_ffn(cfg, stacks, xs, sizes)
    with jax.named_scope(SCOPE_DISPATCH):
        rows = _routed_take(out, dest, src)                  # item order
        return (rows.reshape(T, m.top_k, d)
                * weights[..., None].astype(rows.dtype)).sum(axis=1)


def apply_moe(cfg, p, x, rt: Runtime):
    """x (B, S, d) -> (out (B, S, d), aux_loss scalar)."""
    return moe_layer(cfg, p, x, rt)[:2]


def moe_layer(cfg, p, x, rt: Runtime):
    """x (B, S, d) -> (out (B, S, d), aux_loss scalar, load): ``load``
    counts the routed items each held expert computed (n_held,), None on
    the expert-parallel paths, whose routing stays inside their
    shard_map."""
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    m = cfg.moe
    impl = rt.moe_impl
    if impl == "auto":
        impl = "dense" if B * S * m.n_experts <= (1 << 22) else "dropping"
    if m.n_held != m.n_experts and impl not in ("dense", "dropless"):
        raise ValueError(f"a layer holding {m.n_held} of {m.n_experts} "
                         f"experts runs dense or dropless, not {impl!r}")
    load = None
    if impl == "ep":
        # expert-parallel shard_map dispatch.  Token counts that cannot
        # tile every mesh axis (tiny decode batches) are zero-padded up to
        # the shard count and still run the real all-to-all — the plan the
        # planner priced.  Only a genuinely unshardable mesh (experts not
        # divisible over the axis) falls back to GSPMD dropping, loudly:
        # a silent fallback serves a different physical program than the
        # one the strategy ranking chose.
        from repro.core import expert as expert_lib
        if expert_lib.can_shard_tokens(cfg, rt, B * S):
            expert_lib.DISPATCH_STATS["ep_calls"] += 1
            y, aux = expert_lib.moe_expert_parallel(cfg, p, xf, rt)
        elif expert_lib.can_pad_tokens(cfg, rt):
            expert_lib.DISPATCH_STATS["ep_padded_calls"] += 1
            y, aux = expert_lib.moe_expert_parallel_padded(cfg, p, xf, rt)
        else:
            expert_lib.DISPATCH_STATS["ep_fallback_calls"] += 1
            warnings.warn(
                f"EP dispatch unavailable for {B * S} tokens on this mesh "
                f"(experts={cfg.moe.n_experts} do not shard over "
                f"{rt.expert_axis!r}); falling back to GSPMD dropping — "
                "this is a different physical program than the planned "
                "expert-parallel dispatch", stacklevel=2)
            impl = "dropping"
    if impl == "ep_manual":
        # already inside a manual shard_map (pipeline stage body): the
        # all-to-all runs on rt.expert_axis directly, no nested shard_map
        from repro.core import expert as expert_lib
        y, aux = expert_lib.moe_expert_parallel_manual(cfg, p, xf, rt)
    elif impl != "ep":
        with jax.named_scope(SCOPE_DISPATCH):
            route = _router(cfg, p, xf, rt)
            load = _held_counts(route[2], m)
        impls = {"dense": _moe_dense, "dropping": _moe_dropping,
                 "dropless": _moe_dropless}
        if impl not in impls:
            raise ValueError(f"unknown moe_impl {impl!r}")
        y, aux = impls[impl](cfg, p, xf, rt, route), route[3]
    y = y.reshape(B, S, d)
    if "shared" in p:
        sp = p["shared"]
        act = _act(cfg.act)
        dt = x.dtype
        up = jnp.einsum("bsd,df->bsf", x, sp["w_up"].astype(dt))
        if "w_gate" in sp:
            h = act(jnp.einsum("bsd,df->bsf", x, sp["w_gate"].astype(dt))) * up
        else:
            h = act(up)
        y = y + jnp.einsum("bsf,fd->bsd", h, sp["w_down"].astype(dt))
    return rt.c("act_btd", y), aux, load
