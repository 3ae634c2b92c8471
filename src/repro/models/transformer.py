"""Unified decoder-only model over all assigned architecture families.

A model is a stack of layers; each layer = (norm -> mixer -> residual,
norm -> ffn -> residual) where the mixer is attention / RWKV-6 / Mamba and
the ffn is dense MLP / MoE / RWKV channel-mix, both chosen per-layer by the
``ModelConfig`` (hybrids like Jamba interleave).

To keep compiled HLO small at 28-80 layers, layers are executed with
``lax.scan`` over *blocks*: ``layer_plan`` finds the shortest
(prefix, period) decomposition such that layers [start:] repeat a fixed
signature pattern of length ``period``; per-position parameters are stacked
over the ``n_blocks`` repeats and scanned (MaxText-style), with optional
remat per block.  KV/recurrent caches are stacked the same way and threaded
through the scan as xs/ys.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import mamba as mamba_lib
from repro.models import moe as moe_lib
from repro.models import rwkv6 as rwkv_lib
from repro.models.layers import (Runtime, apply_norm, embed_tokens,
                                 init_embed, init_mlp, init_norm, apply_mlp,
                                 lm_logits, mrope_angles, rope_angles,
                                 tp_reduce_out)

# Named scopes of the forward pass and the loss.  Each lands in the
# ``op_name`` of every compiled instruction it covers, and survives
# fusion, so a profile can be split by part: the forward op reads
# ``.../jvp(blocks)/...``, its backward ``.../transpose(jvp(blocks))/...``.
SCOPE_EMBED = "embed"
SCOPE_BLOCKS = "blocks"          # prefix layers, layer scan, pipeline
SCOPE_FINAL_NORM = "final_norm"
SCOPE_LM_HEAD = "lm_head"
SCOPE_XENT_LOSS = "xent_loss"    # cross entropy on the logits
MODEL_SCOPES = (SCOPE_EMBED, SCOPE_BLOCKS, SCOPE_FINAL_NORM,
                SCOPE_LM_HEAD, SCOPE_XENT_LOSS)


# ---------------------------------------------------------------------------
# layer planning
# ---------------------------------------------------------------------------

def _sig(cfg: ModelConfig, i: int) -> Tuple[str, bool]:
    return (cfg.layer_kind(i), cfg.is_moe_layer(i))


def layer_plan(cfg: ModelConfig):
    """-> (prefix_layer_ids, start, period, n_blocks) minimizing unrolled size."""
    L = cfg.n_layers
    sigs = [_sig(cfg, i) for i in range(L)]
    for total in range(1, L + 1):
        for start in range(total):
            period = total - start
            if (L - start) % period:
                continue
            if all(sigs[start + j] == sigs[start + (j % period)]
                   for j in range(L - start)):
                return list(range(start)), start, period, (L - start) // period
    return list(range(L)), L, 1, 0


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(cfg: ModelConfig, i: int, key) -> Dict[str, Any]:
    kind, is_moe = _sig(cfg, i)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {"norm1": init_norm(cfg, k1), "norm2": init_norm(cfg, k2)}
    if kind == "attn" and cfg.mla:
        p["mixer"] = attn_lib.init_mla(cfg, k3)
    elif kind == "attn":
        p["mixer"] = attn_lib.init_attention(cfg, k3)
    elif kind == "rwkv6":
        p["mixer"] = rwkv_lib.init_rwkv_time_mix(cfg, k3)
    elif kind == "mamba":
        p["mixer"] = mamba_lib.init_mamba(cfg, k3)
    else:
        raise ValueError(kind)
    if kind == "rwkv6":
        p["ffn"] = rwkv_lib.init_rwkv_channel_mix(cfg, k4)
    elif is_moe:
        p["ffn"] = moe_lib.init_moe(cfg, k4)
    else:
        p["ffn"] = init_mlp(cfg, k4)
    return p


def _tree_stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    prefix, start, period, n_blocks = layer_plan(cfg)
    keys = jax.random.split(key, cfg.n_layers + 2)
    params = {
        "embed": init_embed(cfg, keys[-1]),
        "final_norm": init_norm(cfg, keys[-2]),
        "prefix": [_init_layer(cfg, i, keys[i]) for i in prefix],
        "blocks": [
            _tree_stack([_init_layer(cfg, start + b * period + pos,
                                     keys[start + b * period + pos])
                         for b in range(n_blocks)])
            for pos in range(period)
        ] if n_blocks else [],
    }
    return params


def param_count_actual(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _init_layer_cache(cfg, i, batch, max_len, dtype, rt: Runtime):
    kind, _ = _sig(cfg, i)
    d = cfg.d_model
    if kind == "attn":
        return {"kv": attn_lib.make_kv_cache(cfg, batch, max_len, dtype, rt)}
    if kind == "rwkv6":
        H, N = cfg.rwkv_heads, cfg.rwkv_head_dim
        return {
            "att": {"x_prev": jnp.zeros((batch, d), dtype),
                    "wkv": rt.c("rwkv_state",
                                jnp.zeros((batch, H, N, N), jnp.float32))},
            "ffn": {"x_prev": jnp.zeros((batch, d), dtype)},
        }
    if kind == "mamba":
        mc = cfg.mamba
        di = mc.expand * d
        return {"conv": jnp.zeros((batch, mc.d_conv - 1, di), dtype),
                "ssm": rt.c("mamba_state",
                            jnp.zeros((batch, di, mc.d_state), jnp.float32))}
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype, rt: Runtime):
    prefix, start, period, n_blocks = layer_plan(cfg)
    return {
        "prefix": [_init_layer_cache(cfg, i, batch, max_len, dtype, rt)
                   for i in prefix],
        "blocks": [
            _tree_stack([_init_layer_cache(cfg, start + b * period + pos,
                                           batch, max_len, dtype, rt)
                         for b in range(n_blocks)])
            for pos in range(period)
        ] if n_blocks else [],
    }


# ---------------------------------------------------------------------------
# layer application
# ---------------------------------------------------------------------------

def _apply_layer(cfg, sig, lp, h, rope_ang, rt: Runtime, cache=None,
                 paged=None):
    """-> (h, new_cache, aux_loss); ``_apply_layer_load`` adds the expert
    load."""
    return _apply_layer_load(cfg, sig, lp, h, rope_ang, rt, cache,
                             paged)[:3]


def _apply_layer_load(cfg, sig, lp, h, rope_ang, rt: Runtime, cache=None,
                      paged=None):
    """-> (h, new_cache, aux_loss, expert_load); ``expert_load`` counts
    the routed items each held expert of an MoE layer computed (None
    elsewhere, and on the expert-parallel paths).

    With ``rt.tp_reduce_axis`` set (Megatron-TP inside a manual pipeline
    stage), the partial mixer/ffn outputs are psummed over the model axis
    — the classic two all-reduces per layer, placed exactly where the
    GSPMD lowering's sharding constraints would induce them.  (The
    column-parallel input side needs no marker: shard_map differentiates
    the physical program, so the psum's transpose and the spec-level
    psum/pmean bookkeeping produce exact gradients.)"""
    kind, is_moe = sig
    aux = jnp.zeros((), jnp.float32)
    load = None

    x = apply_norm(lp["norm1"], h, cfg.norm_eps, rt)
    if kind == "attn" and cfg.mla:
        if cache is not None or paged is not None:
            raise NotImplementedError("latent attention has no KV cache "
                                      "here: training and prefill only")
        mix = attn_lib.mla_block(cfg, lp["mixer"], x, rope_ang, rt)
        new_cache = None
    elif kind == "attn":
        mix, new_mix_cache = attn_lib.attention_block(
            cfg, lp["mixer"], x, rope_ang, rt,
            cache=None if cache is None else cache["kv"], paged=paged)
        new_cache = None if cache is None else {"kv": new_mix_cache}
    elif kind == "rwkv6":
        mix, new_att = rwkv_lib.rwkv_time_mix(
            cfg, lp["mixer"], x, rt,
            state=None if cache is None else cache["att"])
        new_cache = None if cache is None else {"att": new_att}
    else:  # mamba
        mix, new_state = mamba_lib.mamba_block(
            cfg, lp["mixer"], x, rt,
            state=None if cache is None else cache)
        new_cache = new_state
    h = h + tp_reduce_out(mix, rt)

    x = apply_norm(lp["norm2"], h, cfg.norm_eps, rt)
    if kind == "rwkv6":
        ffn, new_ffn = rwkv_lib.rwkv_channel_mix(
            cfg, lp["ffn"], x, rt,
            state=None if cache is None else cache["ffn"])
        if new_cache is not None:
            new_cache["ffn"] = new_ffn
    elif is_moe:
        ffn, aux, load = moe_lib.moe_layer(cfg, lp["ffn"], x, rt)
    else:
        ffn = apply_mlp(cfg, lp["ffn"], x, rt)
    h = h + tp_reduce_out(ffn, rt)
    return h, new_cache, aux, load


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _sinusoidal_from_positions(positions, d_model, dtype):
    """positions (B,S) -> (B,S,d_model) classic sin/cos embedding."""
    half = d_model // 2
    freq = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq
    emb = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    return emb.astype(dtype)


def _embed_inputs(cfg, params, batch, rt: Runtime, positions):
    if "embeds" in batch:
        # audio-frontend stub: precomputed frame embeddings (train/prefill);
        # decode steps feed generated codec *tokens* through the embedding
        h = batch["embeds"].astype(rt.compute_dtype)
    else:
        h = embed_tokens(params["embed"], batch["tokens"], rt)
        if cfg.input_mode == "tokens+vision" and "vision_embeds" in batch:
            v = batch["vision_embeds"].astype(h.dtype)
            # fixed layout: the first V positions of the stream are patches
            v = v[:, :h.shape[1]]
            h = jnp.concatenate([v, h[:, v.shape[1]:]], axis=1)
    if cfg.pos_embed == "sinusoidal":
        h = h + _sinusoidal_from_positions(positions, cfg.d_model, h.dtype)
    return rt.c("act_btd", h)


def _rope_for(cfg, batch, positions):
    hd = cfg.rope_dim
    if cfg.rope == "none":
        return None
    if cfg.rope == "mrope":
        pos_ids = batch.get("position_ids")
        if pos_ids is None:                     # text-only fallback: t=h=w
            pos_ids = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        return mrope_angles(pos_ids, hd, cfg.rope_theta, cfg.mrope_sections)
    return rope_angles(positions, hd, cfg.rope_theta, cfg.yarn)


def forward(cfg: ModelConfig, params, batch, rt: Runtime,
            cache=None) -> Tuple[jnp.ndarray, Optional[Any], jnp.ndarray]:
    """-> (logits, new_cache | None, aux_loss).

    batch: tokens (B,S) [or embeds (B,S,d)], optional position_ids (3,B,S),
    optional pos (scalar absolute offset, decode/continuation).
    """
    logits, new_cache, aux, _ = _forward(cfg, params, batch, rt, cache)
    return logits, new_cache, aux


def _forward(cfg: ModelConfig, params, batch, rt: Runtime, cache=None):
    """``forward`` -> (logits, new_cache, aux_loss, expert_loads): the
    routed items each held expert computed, one (n_held,) row per MoE
    layer that reports them (``_apply_layer_load``), or None."""
    if "embeds" in batch:
        B, S = batch["embeds"].shape[:2]
    else:
        B, S = batch["tokens"].shape
    offset = batch.get("pos", jnp.zeros((), jnp.int32))
    positions = offset + jnp.arange(S, dtype=jnp.int32)[None]
    positions = jnp.broadcast_to(positions, (B, S))

    with jax.named_scope(SCOPE_EMBED):
        h = _embed_inputs(cfg, params, batch, rt, positions)
    rope_ang = _rope_for(cfg, batch, positions)

    prefix, start, period, n_blocks = layer_plan(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    # paged serving state (block table + per-request context lengths) is
    # shared, read-only, across every layer: it rides next to the per-layer
    # pools in the cache dict and is closed over by the scan body rather
    # than threaded through it — the engine advances ctx between steps
    paged = cache.get("paged") if cache is not None else None

    if rt.pipeline_axis and cache is None:
        # GPipe path: the whole layer stack runs under core/pipeline.py's
        # shard_map schedule (embed / final norm / head stay on the plain
        # GSPMD path, replicated over the pipe axis).  Strategy.to_plan
        # only hands out pipeline runtimes for uniform stacks.
        if prefix or period != 1 or not n_blocks:
            raise ValueError(
                "pipeline runtime requires a uniform layer stack "
                "(no prefix, period 1); Strategy.to_plan validates this")
        with jax.named_scope(SCOPE_BLOCKS):
            h, aux_total = _pipeline_blocks(cfg, params, h, rope_ang, rt)
        return _head(cfg, params, h, rt), None, aux_total, None

    new_prefix_caches = []
    loads = []
    for j, i in enumerate(prefix):
        c = None if cache is None else cache["prefix"][j]
        with jax.named_scope(SCOPE_BLOCKS):
            h, nc, aux, load = _apply_layer_load(
                cfg, _sig(cfg, i), params["prefix"][j], h, rope_ang, rt, c,
                paged)
        aux_total += aux
        new_prefix_caches.append(nc)
        if load is not None:
            loads.append(load[None])

    new_block_caches = None
    if n_blocks:
        sigs = [_sig(cfg, start + pos) for pos in range(period)]

        apply = _apply_layer_load
        if rt.remat_inner:
            # cfg, sig and rt are static (hashable frozen dataclasses)
            apply = jax.checkpoint(_apply_layer_load, static_argnums=(0, 1, 5))

        prefetch = rt.gather_prefetch and rt.gather_params is not None

        def block_fn(carry, xs):
            if prefetch:
                # double-buffered gather ('ovl'): the carry holds this
                # iteration's already-gathered slice; xs carries the
                # *next* iteration's shard, whose gather is issued here —
                # before this block's compute — so the collective runs
                # under it instead of serializing ahead of each block
                h_, aux_, lps = carry
                nxt = tuple(rt.gather_params(lp) for lp in xs[:period])
            else:
                h_, aux_ = carry
                lps = xs[:period]
            caches = xs[period:] if cache is not None else [None] * period
            new_caches, block_loads = [], []
            for pos in range(period):
                lp = lps[pos]
                if not prefetch and rt.gather_params is not None:
                    # re-assert the de-gathered (replicated-over-fsdp) layout
                    # on the *per-iteration* slice: the all-gather is loop-
                    # variant and stays inside the scan (per-layer FSDP
                    # gather) instead of being hoisted over the whole stack.
                    lp = rt.gather_params(lp)
                h_, nc, a, load = apply(cfg, sigs[pos], lp, h_,
                                        rope_ang, rt, caches[pos], paged)
                aux_ += a
                new_caches.append(nc)
                if load is not None:
                    block_loads.append(load)
            ys = (tuple(new_caches) if cache is not None else None,
                  jnp.stack(block_loads) if block_loads else None)
            new_carry = (h_, aux_, nxt) if prefetch else (h_, aux_)
            return new_carry, ys

        if rt.remat:
            block_fn = jax.checkpoint(block_fn)

        with jax.named_scope(SCOPE_BLOCKS):
            blocks = tuple(params["blocks"])
            if prefetch:
                # feed each iteration the next slice (rolled stack; the
                # final iteration's wrapped-around gather is dead and DCEs
                # away) and seed the buffer with slice 0's gather
                xs = tuple(jax.tree.map(lambda a: jnp.roll(a, -1, axis=0), b)
                           for b in blocks)
                g0 = tuple(rt.gather_params(jax.tree.map(lambda a: a[0], b))
                           for b in blocks)
                carry0 = (h, aux_total, g0)
            else:
                xs = blocks
                carry0 = (h, aux_total)
            if cache is not None:
                xs = xs + tuple(cache["blocks"])
            out_carry, (ys, block_loads) = jax.lax.scan(block_fn, carry0, xs)
        h, aux_total = out_carry[0], out_carry[1]
        if cache is not None:
            new_block_caches = list(ys)
        if block_loads is not None:      # (n_blocks, per block, n_held)
            loads.append(block_loads.reshape((-1,) + block_loads.shape[2:]))

    logits = _head(cfg, params, h, rt)

    new_cache = None
    if cache is not None:
        new_cache = {"prefix": new_prefix_caches, "blocks": new_block_caches or []}
        if paged is not None:
            new_cache["paged"] = paged
    return (logits, new_cache, aux_total,
            jnp.concatenate(loads) if loads else None)


def _head(cfg: ModelConfig, params, h, rt: Runtime):
    """Final norm and the LM head -> logits."""
    with jax.named_scope(SCOPE_FINAL_NORM):
        h = apply_norm(params["final_norm"], h, cfg.norm_eps, rt)
    with jax.named_scope(SCOPE_LM_HEAD):
        return lm_logits(params["embed"], h, rt)


def pipeline_stage_runtime(rt: Runtime, rows: int) -> Runtime:
    """The stage-body Runtime for a pipeline microbatch of ``rows`` rows.

    The stage body runs inside a fully-manual shard_map: named sharding
    constraints and per-block FSDP gathers are meaningless there; MoE
    router load stats psum over the token-sharding axes for a global aux.
    moe_groups=1: the stage already sees only its device-local token
    slice (the non-pp lowering's per-data-shard dispatch group) —
    keeping the global group count would subdivide it dp times further
    and shrink per-group expert capacity accordingly.  The manual
    tp/cp axes are activated, and EP plans switch to the in-stage
    ``ep_manual`` dispatch (which calls the expert all-to-all directly —
    no nested shard_map)."""
    from repro.core.pipeline import batch_axes_spec

    kept = batch_axes_spec(rt.pipeline_mesh, rt.pipeline_batch_axes, rows)
    tok_axes = kept + ((rt.pipeline_cp_axis,) if rt.pipeline_cp_axis else ())
    moe_impl = rt.moe_impl
    if rt.expert_axis and moe_impl == "ep":
        # the in-stage all-to-all needs the microbatch actually sharded
        # over the expert axis — with replicated tokens the duplicate
        # dispatch rows would overcount the expert grads
        if rt.expert_axis not in kept:
            raise ValueError(
                f"pipeline microbatch of {rows} rows does not shard "
                f"over the {rt.expert_axis!r} mesh axis "
                f"(size {rt.pipeline_mesh.shape[rt.expert_axis]}): the "
                "expert all-to-all inside a pipeline stage needs "
                "expert-sharded tokens — grow global_batch or lower "
                "grad_accum x microbatches")
        moe_impl = "ep_manual"
    return dataclasses.replace(rt, constrain=None, gather_params=None,
                               kernel_shard=None,
                               moe_stat_axes=tok_axes, moe_groups=1,
                               moe_impl=moe_impl,
                               tp_reduce_axis=rt.pipeline_tp_axis,
                               cp_axis=rt.pipeline_cp_axis)


def pipeline_stage_param_specs(rt: Runtime, stage_params):
    """PartitionSpecs for a stage-param pytree via the plan's
    ``pipeline_param_spec_fn`` (stack dim over 'pipe' + inner
    model/expert sharding); None when the runtime carries no spec fn."""
    if rt.pipeline_param_spec_fn is None:
        return None
    return jax.tree_util.tree_map_with_path(
        lambda pth, leaf: rt.pipeline_param_spec_fn(pth, leaf.shape),
        stage_params)


def _pipeline_blocks(cfg: ModelConfig, params, h, rope_ang, rt: Runtime):
    """Apply the full (uniform, stacked) layer stack under the plan's
    pipeline schedule (GPipe or 1F1B): split the batch into M
    microbatches, pipeline them over the mesh 'pipe' axis (stage p owns
    the contiguous layer slice the param sharding already placed there),
    and stitch the outputs back.

    The stage body computes over the *full inner mesh*: head_tp plans keep
    the stage params model-sharded (``rt.pipeline_param_spec_fn``) and run
    Megatron psums inside ``_apply_layer``; context plans shard the
    microbatch sequence over the model axis (attention gathers KV); expert
    plans dispatch MoE layers through ``core/expert.py``'s all-to-all on
    the expert axis.

    Returns (h, aux): the MoE load-balance loss is threaded through the
    schedule alongside each microbatch's activation and averaged over the
    M microbatches — the same per-microbatch averaging grad accumulation
    applies (each microbatch's balance stats are its own, psum-reduced
    across the token-sharding axes so every shard sees global counts)."""
    from repro.core.pipeline import make_pipelined_block_fn, pipeline_apply

    M = rt.pipeline_microbatches
    B = h.shape[0]
    if B % M:
        raise ValueError(
            f"batch {B} does not split into {M} pipeline microbatches "
            "(grad_accum x microbatches must divide the global batch)")
    rt_stage = pipeline_stage_runtime(rt, B // M)
    stage_fn = make_pipelined_block_fn(cfg, rt_stage)
    # training positions are identical across rows -> rope with batch dim 1
    # broadcasts over the (data-sharded) local microbatch inside the stage
    rope_mb = None if rope_ang is None else rope_ang[:1]
    x_mb = h.reshape((M, B // M) + h.shape[1:])
    stage_params = {"layers": params["blocks"][0]}
    pspecs = pipeline_stage_param_specs(rt, stage_params)
    out, aux = pipeline_apply(stage_fn, stage_params, x_mb,
                              rt.pipeline_mesh, rt.pipeline_axis,
                              extras=rope_mb,
                              batch_axes=rt.pipeline_batch_axes,
                              schedule=rt.pipeline_schedule,
                              param_specs=pspecs,
                              seq_axis=rt.pipeline_cp_axis,
                              tp_axis=rt.pipeline_tp_axis)
    return rt.c("act_btd", out.reshape((B,) + out.shape[2:])), aux / M


# ---------------------------------------------------------------------------
# losses / steps
# ---------------------------------------------------------------------------

def loss_fn(cfg: ModelConfig, params, batch, rt: Runtime):
    """Next-token cross entropy; labels < 0 are masked.  Where MoE layers
    report their expert loads, the metrics add ``expert_items`` (routed
    items the held experts computed, all layers) and ``expert_load_max``
    (the most loaded held expert over the mean, averaged over layers)."""
    logits, _, aux, loads = _forward(cfg, params, batch, rt)
    with jax.named_scope(SCOPE_XENT_LOSS):
        labels = batch["labels"]
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        ll = jnp.take_along_axis(
            lf, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        nll = ((lse - ll) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    metrics = {"nll": nll, "aux": aux, "ntok": mask.sum()}
    if loads is not None:
        mean = jnp.maximum(loads.mean(-1), 1e-9)
        metrics.update(expert_items=loads.sum(),
                       expert_load_max=jnp.mean(loads.max(-1) / mean))
    return nll + aux, metrics


def prefill(cfg, params, batch, rt: Runtime, max_len: int):
    """Run the prompt through the model, building a decode cache."""
    if "tokens" in batch:
        B = batch["tokens"].shape[0]
    else:
        B = batch["embeds"].shape[0]
    cache = init_cache(cfg, B, max_len, rt.compute_dtype, rt)
    logits, cache, _ = forward(cfg, params, batch, rt, cache=cache)
    return logits, cache


def decode_step(cfg, params, cache, tokens, pos, rt: Runtime,
                extra: Optional[dict] = None):
    """tokens (B,1); pos scalar absolute position. -> (logits, cache)."""
    batch = {"tokens": tokens, "pos": pos}
    if extra:
        batch.update(extra)
    logits, cache, _ = forward(cfg, params, batch, rt, cache=cache)
    return logits, cache
