"""Parallelization plan: the paper's technique as a first-class object.

The paper's central finding is that the *composition* of sharded data
parallelism (FSDP/HSDP) with model parallelism (tensor / context) determines
throughput at scale, because model parallelism shrinks the FSDP collective
group.  A ``ParallelPlan`` captures one point in that strategy space and
produces:

  * parameter PartitionSpecs (2D: FSDP axis x model axis),
  * named activation constraints consumed by the model code
    (``Runtime.constrain``),
  * batch input specs,

for any of the assigned architectures on any mesh.

Attention strategy selection (see DESIGN.md §4):
  * ``head_tp``  — Megatron-style: Q heads sharded on the model axis
                   (requires n_heads % tp == 0); KV heads sharded too when
                   divisible, else replicated (GQA).
  * ``context``  — sequence sharded on the model axis; K/V all-gathered for
                   exact attention (train/prefill).  Head-count agnostic.
Decode always shards the KV cache along *sequence* (flash-decode over the
mesh); for global_batch < data axis size the cache seq dim is sharded over
both (data, model).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.compat import use_mesh  # noqa: F401  (canonical home:
#                              core/compat.py; re-exported because every
#                              launch/test call site spells par.use_mesh)


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Execution-side mixed-precision policy (dtype names, not jnp dtypes,
    so the plan stays hashable and importable without jax.numpy).

    ``param_dtype`` is the stored-parameter dtype the runtime computes
    from; master parameters always stay f32 (``init_params`` initializes
    f32 and the optimizer updates in f32 — torchtitan's
    ``MixedPrecisionPolicy`` split).  ``compute_dtype`` is the activation/
    matmul dtype, ``grad_dtype`` the grad-accumulation/reduce dtype, and
    ``comm_dtype`` (when set) the wire dtype of the per-layer ZeRO param
    all-gathers — the emulated-fp8-comms path: quantize, gather, and
    dequantize back to ``compute_dtype`` (FSDP2's fp8 all-gather
    extension point).
    """
    name: str
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    grad_dtype: str = "float32"
    comm_dtype: str = ""                 # '' = gather at param_dtype


PRECISION_POLICIES = {
    "f32": PrecisionPolicy("f32"),
    "bf16": PrecisionPolicy("bf16", param_dtype="float32",
                            compute_dtype="bfloat16"),
    "fp8": PrecisionPolicy("fp8", param_dtype="float32",
                           compute_dtype="bfloat16",
                           comm_dtype="float8_e4m3fn"),
}


@dataclasses.dataclass(frozen=True)
class ParallelPlan:
    mesh: Mesh
    dp: Tuple[str, ...]                  # batch-dim axes ('pod','data') or ('data',)
    fsdp: Tuple[str, ...]                # param-shard axes (HSDP: ('data',))
    tp: str                              # model axis name
    attn: str                            # 'head_tp' | 'context'
    kv_tp: bool                          # shard KV heads on model axis
    shape_mode: str = "train"            # train | prefill | decode
    decode_cache_axes: Tuple[str, ...] = ("model",)
    seq_parallel_residuals: bool = True  # Megatron-SP residual stream
    pipe: str = ""                       # pipeline mesh axis ('' = no PP)
    microbatches: int = 1                # pipeline microbatches per minibatch
    pipe_sched: str = "gpipe"            # pipeline schedule: 'gpipe' |
                                         # '1f1b' | '1f1b_i<v>' | 'zb'
    zero_overlap: bool = False           # double-buffered ZeRO gather
                                         # prefetch: issue layer l+1's
                                         # param gather during layer l's
                                         # compute (needs per-block
                                         # gathering, which it implies)
    expert: str = ""                     # expert mesh axis ('' = no EP);
                                         # factored out of the data axis, so
                                         # it also appears in dp/fsdp
    precision: str = "f32"               # PRECISION_POLICIES key

    @property
    def policy(self) -> PrecisionPolicy:
        return PRECISION_POLICIES[self.precision]

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp]

    @property
    def pipe_size(self) -> int:
        return self.mesh.shape[self.pipe] if self.pipe else 1

    @property
    def ep_size(self) -> int:
        return self.mesh.shape[self.expert] if self.expert else 1

    @property
    def fsdp_no_expert(self) -> Tuple[str, ...]:
        """Param-shard axes for tensors already sharded over 'expert'
        (the non-E dims of expert stacks must not reuse the axis)."""
        return tuple(a for a in self.fsdp if a != self.expert)

    def axis_size(self, axes) -> int:
        return int(np.prod([self.mesh.shape[a] for a in axes])) if axes else 1


# The deprecated ``choose_plan`` shim (plan from an already-built mesh) is
# gone: build plans via ``repro.strategy.Strategy(...).to_plan`` — the same
# descriptor feeds the cost model, so planner rankings and SPMD lowerings
# cannot drift apart.


# ---------------------------------------------------------------------------
# spec fitting: drop axes that do not divide the dimension
# ---------------------------------------------------------------------------

def _fit_spec(spec: P, shape, mesh: Mesh) -> P:
    out = []
    for dim, entry in enumerate(spec):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        keep = []
        size = shape[dim]
        for a in axes:
            n = mesh.shape[a]
            if size % n == 0 and size >= n:
                keep.append(a)
                size //= n
            # else: drop axis (dim not divisible)
        out.append(tuple(keep) if len(keep) > 1 else (keep[0] if keep else None))
    return P(*out)


def fitted(plan: ParallelPlan, spec: P, x_or_shape):
    shape = getattr(x_or_shape, "shape", x_or_shape)
    spec = P(*(tuple(spec) + (None,) * (len(shape) - len(spec))))
    return NamedSharding(plan.mesh, _fit_spec(spec, shape, plan.mesh))


# ---------------------------------------------------------------------------
# parameter shardings
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _layer_plan_cached(cfg: ModelConfig):
    # layer_plan is an O(L^3) signature search; _mixer_kind calls it once
    # per parameter leaf of a hybrid model, so cache on the frozen config
    from repro.models.transformer import layer_plan
    return layer_plan(cfg)


def _mixer_kind(cfg: ModelConfig, path) -> str:
    """Mixer kind ('attn' | 'rwkv6' | 'mamba') of the layer owning a leaf.

    Attention and rwkv time-mix share leaf names (wk/wv/wo/wr), so specs
    must discriminate on the layer's kind, not the leaf name.  Pure stacks
    are unambiguous; hybrids recover the layer id from the prefix/blocks
    position in the tree path (each scanned block position holds layers of
    a single kind by construction — see transformer.layer_plan).
    """
    if cfg.mixer == "attn" or cfg.attn_every <= 1:
        return cfg.mixer
    _prefix, start, _period, _n_blocks = _layer_plan_cached(cfg)
    for j, p in enumerate(path[:-1]):
        name = getattr(p, "key", getattr(p, "name", str(p)))
        if name in ("prefix", "blocks"):
            idx = getattr(path[j + 1], "idx", None)
            if idx is None:
                break
            layer = idx if name == "prefix" else start + idx
            return cfg.layer_kind(layer)
    return cfg.mixer


def _param_spec(cfg: ModelConfig, plan: ParallelPlan, path: Tuple[str, ...],
                shape: Tuple[int, ...]) -> P:
    """PartitionSpec for one parameter leaf of ``shape``, identified by its
    tree path.

    Stacked block params have a leading (n_blocks,) dim -> specs are shifted
    right by one (the stack dim is never sharded).

    Placement rule for the fsdp axes: in a plan with no tensor parallelism
    (``plan.tp_size == 1``) an fsdp-sharded matrix carries its shard on the
    leading non-stack dim whenever that dim divides by the fsdp size, so
    ``tok``, attention ``wo``, ``w_down`` and the recurrent mixers' output
    projections are stored row-sharded like ``wq`` and ``w_up``.  The rule
    exists for the gradient exchange: the TPU compiler fuses "all-reduce,
    then keep my rows" into one all-reduce-scatter when the kept slice is
    the leading dim, but on the last dim of a matrix at published widths
    it runs a full all-reduce and each chip discards all but its slice.
    Plans with tensor parallelism keep the layout below, where the model
    axis owns those leading dims.  The rule only moves an fsdp shard; it
    never shards a leaf the layout below leaves whole over the fsdp axes.
    """
    f, m = plan.fsdp, plan.tp
    names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
    leaf = names[-1]
    stacked = "blocks" in names
    # position of the leading stack dim (blocks[i] leaves carry one); a
    # pipeline plan shards it over the pipe axis — contiguous layer groups
    # per stage, exactly the slices core/pipeline.py's shard_map hands out
    pad = 1 if stacked else 0
    stack_entry = plan.pipe if (stacked and plan.pipe) else None
    base_ndim = len(shape) - pad
    fsdp_leads = (bool(f) and plan.tp_size == 1 and base_ndim == 2
                  and shape[pad] % plan.axis_size(f) == 0)

    def spec(*entries):
        entries = entries + (None,) * (base_ndim - len(entries))
        if fsdp_leads and entries[-1] == f:
            entries = (f, None)
        return P(*((stack_entry,) * pad + entries))

    in_attention = "mixer" in names
    vocab_tp = plan.attn == "head_tp"   # context plans keep vocab unsharded

    if leaf == "tok":
        return spec(m if vocab_tp else None, f)
    if leaf == "lm_head":
        return spec(f, m if vocab_tp else None)
    if leaf in ("scale", "bias") or base_ndim == 0:
        return spec()
    if leaf == "router":
        return spec(f, None)
    # MoE expert stacks (E, d, f) / (E, f, d)
    if base_ndim == 3 and leaf in ("w_up", "w_gate", "w_down"):
        if plan.expert:
            # EP: the E dim shards over the 'expert' axis permanently (no
            # gather over it — that is the point of expert parallelism);
            # the d dim ZeRO-shards over the remaining data axes and the
            # hidden dim takes the model axis
            f_ne = plan.fsdp_no_expert or None
            return spec(plan.expert,
                        f_ne if leaf != "w_down" else m,
                        m if leaf != "w_down" else f_ne)
        return spec(m, f if leaf != "w_down" else None,
                    f if leaf == "w_down" else None)
    if in_attention:
        kind = _mixer_kind(cfg, path)
        if kind == "attn":
            head_m = m if plan.attn == "head_tp" else None
            kv_m = m if plan.kv_tp else None
            if leaf == "wq":
                return spec(f, head_m)
            if leaf in ("wk", "wv"):
                return spec(f, kv_m)
            if leaf == "wkv_a":     # latent attention: x -> (latent, k_pe)
                return spec(f, None)
            if leaf == "wkv_b":     # latent -> per-head (k_nope, v)
                return spec(None, head_m)
            if leaf == "wo":
                return spec(head_m, f)
            if leaf == "bq":
                return spec(head_m)
            if leaf in ("bk", "bv"):
                return spec(kv_m)
        elif kind == "rwkv6":
            if leaf in ("wr", "wk", "wv", "wg"):
                return spec(f, m)
            if leaf == "wo":
                return spec(m, f)
            if leaf == "u":
                return spec(m, None)
            if leaf in ("tm_w1", "td_w1"):
                return spec(f, None)
            if leaf == "td_w2":
                return spec(None, f)
            if leaf == "tm_w2":
                return spec(None, None, f)
            if leaf == "maa_x":
                return spec()
            if leaf == "maa_rkvwg":
                return spec(None, None)
            if leaf == "w0":
                return spec()
        elif kind == "mamba":
            if leaf in ("w_x_in", "w_z_in"):
                return spec(f, m)
            if leaf == "conv_w":
                return spec(None, m)
            if leaf in ("conv_b", "b_dt", "D"):
                return spec(m)
            if leaf == "w_x":
                return spec(m, None)
            if leaf == "w_dt":
                return spec(None, m)
            if leaf == "A_log":
                return spec(m, None)
            if leaf == "w_out":
                return spec(m, f)
    # dense / shared-expert / rwkv channel-mix FFN (2D)
    ffn_m = m if plan.attn == "head_tp" else None
    if leaf in ("w_up", "w_gate"):
        return spec(f, ffn_m)
    if leaf == "w_down":
        return spec(ffn_m, f)
    if leaf == "wk":            # rwkv channel-mix key (d, dff)
        return spec(f, ffn_m)
    if leaf == "wv":            # rwkv channel-mix value (dff, d)
        return spec(ffn_m, f)
    if leaf == "wr":
        return spec(f, None)
    if leaf in ("maa_k", "maa_r"):
        return spec()
    return spec()


def param_shardings(cfg: ModelConfig, plan: ParallelPlan, params_shape):
    """Tree of NamedShardings matching ``jax.eval_shape(init_params, ...)``."""
    def one(path, leaf):
        spec = _param_spec(cfg, plan, path, leaf.shape)
        return fitted(plan, spec, leaf.shape)
    return jax.tree_util.tree_map_with_path(one, params_shape)


# ---------------------------------------------------------------------------
# activation constraints (consumed via Runtime.constrain)
# ---------------------------------------------------------------------------

def activation_specs(cfg: ModelConfig, plan: ParallelPlan) -> Dict[str, P]:
    dp, m = plan.dp, plan.tp
    cp = plan.attn == "context"
    decode = plan.shape_mode == "decode"
    seq = m if (cp and not decode) else None
    # Megatron-style sequence parallelism for the residual stream: pure
    # attention architectures keep (B, S, d) activations seq-sharded on the
    # model axis between layers (all-gather at matmul entry, reduce-scatter
    # after wo/w_down — GSPMD inserts these from the constraints).  This is
    # what bounds remat-stored activations per layer boundary.  Recurrent
    # mixers (rwkv/mamba/hybrid) scan along the sequence and keep residuals
    # seq-unsharded; their per-block remat granularity bounds memory instead.
    res_seq = m if (not decode and cfg.mixer == "attn"
                    and plan.seq_parallel_residuals) else seq
    cache_seq = plan.decode_cache_axes
    return {
        # (B, S, d): sequence sharded for context-parallel plans + SP
        "act_btd": P(dp, res_seq, None),
        # (B, S, f): FFN hidden — TP for head plans, seq-sharded for CP
        "act_btf": P(dp, seq, None if cp else m),
        # (B, S, V)
        "logits": P(dp, seq, None if cp else m),
        # (B, S, H, hd)
        "heads_q": P(dp, seq, None if cp else m, None),
        "heads_kv": P(dp, seq, (m if plan.kv_tp else None) if not cp else None,
                      None),
        # decode KV cache (B, Sc, Kv, hd): sequence-sharded flash-decode
        "kv_cache": P(dp if not decode or len(cache_seq) == 1 else None,
                      cache_seq if decode else None, None, None),
        # MoE buffers (E=experts over model, capacity over data)
        "expert_buf": P(m, dp, None),
        "expert_hidden": P(m, dp, None),
        # MoE group-local dispatch tensors (G = data shards)
        "moe_group_tokens": P(dp, None, None),
        "moe_group_buf": P(dp, None, None, None),
        # rwkv
        "rwkv_heads": P(dp, None, m, None),
        "rwkv_state": P(dp, m, None, None),
        # mamba
        "mamba_inner": P(dp, seq, m),
        "mamba_state": P(dp, m, None),
    }


def make_param_gatherer(cfg: ModelConfig, plan: ParallelPlan):
    """Per-layer FSDP de-gather: constraint mapping a (sliced, per-iteration)
    layer-param pytree to its *replicated-over-fsdp* layout (model-axis
    sharding kept).  Applied inside the scan body so the all-gather is
    loop-variant and cannot be hoisted over the whole layer stack.

    When the plan's precision policy sets ``comm_dtype`` (the fp8 policy),
    floating leaves are quantized to that dtype *before* the gather
    constraint and dequantized to ``compute_dtype`` after — the all-gather
    moves fp8 bytes on the wire while compute stays bf16 (FSDP2's fp8
    all-gather extension point; ``convert_element_type`` is differentiable,
    so the backward re-gather takes the same quantized path).
    """
    import jax.numpy as jnp
    gplan = dataclasses.replace(plan, fsdp=())
    pol = plan.policy
    comm_dtype = jnp.dtype(pol.comm_dtype) if pol.comm_dtype else None
    compute_dtype = jnp.dtype(pol.compute_dtype)

    def gather(lp):
        def one(path, leaf):
            spec = _param_spec(cfg, gplan, path, leaf.shape)
            quant = (comm_dtype is not None and
                     jnp.issubdtype(leaf.dtype, jnp.floating))
            if quant:
                leaf = leaf.astype(comm_dtype)
            leaf = jax.lax.with_sharding_constraint(
                leaf, fitted(plan, spec, leaf.shape))
            if quant:
                leaf = leaf.astype(compute_dtype)
            return leaf
        return jax.tree_util.tree_map_with_path(one, lp)

    return gather


class _FakeKey:
    """Synthetic tree-path entries so stage param subtrees (which lack the
    'blocks' prefix of the full param tree) resolve through _param_spec."""

    def __init__(self, key=None, idx=None):
        if key is not None:
            self.key = key
        if idx is not None:
            self.idx = idx


def _normalize_spec(spec: P) -> P:
    out = []
    for e in spec:
        if isinstance(e, tuple):
            e = tuple(a for a in e if a)
            e = None if not e else (e[0] if len(e) == 1 else e)
        out.append(e)
    return P(*out)


def make_stage_param_spec_fn(cfg: ModelConfig, plan: ParallelPlan):
    """(tree_path, shape) -> PartitionSpec for pipeline *stage* param leaves.

    The stage shard_map (``core/pipeline.py``) computes over the full
    inner mesh: the stacked leaves shard their stack dim over the pipe
    axis AND keep their model/expert sharding (the same layout
    ``_param_spec`` assigns, minus the FSDP axes — GSPMD all-gathers those
    at shard_map entry, exactly like the per-layer ZeRO gather on the
    non-pipelined path).  The stage body then runs the Megatron psums /
    expert all-to-all on the still-sharded dims instead of replicating
    the model axis (the pre-schedule-refactor waste).
    """
    gplan = dataclasses.replace(plan, fsdp=())
    prefix = (_FakeKey(key="blocks"), _FakeKey(idx=0))
    head_tp = plan.attn == "head_tp"

    def spec_fn(path, shape):
        sp = _param_spec(cfg, gplan, prefix + tuple(path), shape)
        if not head_tp:
            # context plans keep stage params replicated over the model
            # axis (the sequence is sharded instead); strip the model
            # entries _param_spec assigns for the GSPMD layout
            sp = P(*[None if e == plan.tp else
                     (tuple(a for a in e if a != plan.tp)
                      if isinstance(e, tuple) else e) for e in sp])
        return _normalize_spec(sp)

    return spec_fn


def make_runtime(cfg: ModelConfig, plan: ParallelPlan, shape: ShapeConfig,
                 **overrides):
    """Runtime wired to this plan's activation constraints.

    Context-parallel plans keep q seq-sharded through attention, so the
    blocked-attention path must not scan over the (sharded) query-chunk
    axis: q_chunk = S makes it a single iteration and the KV scan provides
    the memory bound.
    """
    from repro.models.layers import Runtime
    import jax.numpy as jnp
    pol = plan.policy
    kw = dict(
        param_dtype=jnp.dtype(pol.param_dtype),
        compute_dtype=jnp.dtype(pol.compute_dtype),
        grad_dtype=jnp.dtype(pol.grad_dtype),
        remat=shape.mode == "train",
        constrain=make_constrainer(cfg, plan),
        moe_impl=("ep" if plan.expert else "dropping")
        if cfg.moe.n_experts else "auto",
        moe_groups=plan.axis_size(plan.dp),
    )
    if plan.expert:
        # shard_map EP path (core/expert.py): tokens shard over every
        # mesh axis (batch axes + model) so the transpose's psums are
        # exact; the dispatch/combine all-to-all runs over expert_axis
        kw.update(expert_axis=plan.expert,
                  expert_mesh=plan.mesh,
                  expert_token_axes=tuple(plan.dp) + (plan.tp,))
    if plan.pipe and shape.mode != "decode":
        # pipeline path (train / cache-less prefill); decode steps thread a
        # cache and take the sequential scan over the pipe-sharded stack.
        # The stage body composes the full inner mesh: head_tp plans run
        # Megatron psums over the model axis, context plans shard the
        # sequence over it, and MoE layers dispatch over the expert axis.
        model_gt1 = plan.tp_size > 1
        kw.update(pipeline_axis=plan.pipe,
                  pipeline_microbatches=plan.microbatches,
                  pipeline_mesh=plan.mesh,
                  pipeline_batch_axes=tuple(plan.dp),
                  pipeline_schedule=plan.pipe_sched,
                  pipeline_param_spec_fn=make_stage_param_spec_fn(cfg, plan),
                  pipeline_tp_axis=(plan.tp if model_gt1
                                    and plan.attn == "head_tp" else ""),
                  pipeline_cp_axis=(plan.tp if model_gt1
                                    and plan.attn == "context" else ""))
    if plan.attn == "context":
        kw["attn_q_chunk"] = shape.seq_len
    # fp8 comms only exist on the per-layer gather path, so a comm_dtype
    # policy turns it on by default (still overridable); the overlap
    # transform is *defined* on that path (there is no per-layer gather
    # to double-buffer otherwise), so 'ovl' turns it on too
    per_block = overrides.pop("fsdp_gather_per_block",
                              bool(pol.comm_dtype) or plan.zero_overlap)
    if per_block and plan.fsdp:
        kw["gather_params"] = make_param_gatherer(cfg, plan)
        kw["gather_prefetch"] = plan.zero_overlap
    if plan.mesh.size > 1:
        kw["kernel_shard"] = make_kernel_sharder(plan)
    kw.update(overrides)
    return Runtime(**kw)


def make_kernel_sharder(plan: ParallelPlan):
    """Run a Pallas kernel on each device's shard of its operands.

    GSPMD cannot partition a Mosaic (Pallas TPU) kernel, so under a
    multi-device plan every kernel call sits in a shard_map over the plan's
    mesh.  The batch dim (dim 0) shards over the plan's data axes.  For
    attention (``heads=True``) dim 2 also shards over the model axis when
    both query and kv heads are model-sharded, which keeps each GQA group
    on one device.  Every other dim is whole inside the kernel, so
    sequence- or head-sharded operands are gathered at its boundary.
    Operands of another rank than the first (rmsnorm's scale, wkv6's u)
    are replicated.
    """
    from repro.core.compat import shard_map
    head_axis = plan.tp if plan.attn == "head_tp" and plan.kv_tp else None

    def shard(fn, *args, heads=False):
        x = args[0]
        entries = [plan.dp] + [None] * (x.ndim - 1)
        if heads and head_axis:
            entries[2] = head_axis
        spec = _fit_spec(P(*entries), x.shape, plan.mesh)
        in_specs = tuple(spec if a.ndim == x.ndim else P() for a in args)
        return shard_map(fn, plan.mesh, in_specs, spec)(*args)

    return shard


def make_constrainer(cfg: ModelConfig, plan: ParallelPlan):
    specs = activation_specs(cfg, plan)

    def constrain(name: str, x):
        spec = specs.get(name)
        if spec is None:
            return x
        return jax.lax.with_sharding_constraint(x, fitted(plan, spec, x))

    return constrain


# ---------------------------------------------------------------------------
# batch / cache input specs
# ---------------------------------------------------------------------------

def batch_specs(cfg: ModelConfig, plan: ParallelPlan, batch) -> Dict:
    """NamedShardings for a batch pytree (tokens/labels/embeds/...)."""
    dp = plan.dp
    cp_seq = plan.tp if plan.attn == "context" and plan.shape_mode != "decode" else None

    def one(path, leaf):
        names = [getattr(p, "key", str(p)) for p in path]
        leaf_name = names[-1] if names else ""
        nd = len(leaf.shape)
        if leaf_name in ("tokens", "labels"):
            return fitted(plan, P(dp, cp_seq), leaf.shape)
        if leaf_name == "embeds":
            return fitted(plan, P(dp, cp_seq, None), leaf.shape)
        if leaf_name == "vision_embeds":
            return fitted(plan, P(dp, None, None), leaf.shape)
        if leaf_name == "position_ids":
            return fitted(plan, P(None, dp, cp_seq), leaf.shape)
        if nd == 0:
            return fitted(plan, P(), leaf.shape)
        return fitted(plan, P(dp), leaf.shape)

    return jax.tree_util.tree_map_with_path(one, batch)


def cache_shardings(cfg: ModelConfig, plan: ParallelPlan, cache_shape):
    """Shardings for a decode cache pytree (from jax.eval_shape)."""
    specs = activation_specs(cfg, plan)

    def one(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
        leaf_name = names[-1]
        stacked = "blocks" in names
        pad = (None,) if stacked else ()
        nd = len(leaf.shape) - len(pad)
        if leaf_name in ("k", "v"):
            spec = specs["kv_cache"]
        elif leaf_name == "wkv":
            # (B, H, N, N) head-sharded state; 2-D fallback for legacy carries
            spec = specs["rwkv_state"] if nd == 4 else P(plan.dp, plan.tp)
        elif leaf_name == "ssm":
            spec = specs["mamba_state"]
        elif leaf_name == "conv":
            spec = P(plan.dp, None, plan.tp)
        elif leaf_name == "x_prev":
            spec = P(plan.dp, None)
        elif leaf_name in ("kpos", "idx"):
            spec = P()
        else:
            spec = P()
        return fitted(plan, P(*(pad + tuple(spec))), leaf.shape)

    return jax.tree_util.tree_map_with_path(one, cache_shape)
