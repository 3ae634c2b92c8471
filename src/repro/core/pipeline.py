"""Pipeline parallelism: pluggable schedules (GPipe, 1F1B, interleaved
1F1B, zero-bubble) over a mesh axis via shard_map + collective_permute
(ppermute), jax-native (no NCCL p2p emulation).

Each device along the ``pipe`` axis owns one *stage* = a contiguous group
of layers (the stacked layer params are sharded over the pipe axis on
their leading/stack dim, so stage p holds layers [p*L/P, (p+1)*L/P)).  A
minibatch is split into M microbatches that stream through the stages; the
*schedule* decides the per-tick op each stage runs and — crucially — how
many microbatch activations a stage must hold at once:

  * ``gpipe``  — all M forwards first, then (under jax.grad's transposed
    scan) all M backwards.  In-flight activations per stage: M.
  * ``1f1b``   — PipeDream-flush/Megatron one-forward-one-backward: after a
    (P - stage)-deep warmup each stage alternates F and B, so a microbatch's
    stored activation is freed as soon as its backward runs.  In-flight
    activations per stage: min(M, P).
  * ``1f1b_i<v>`` — Megatron *interleaved* 1F1B: each rank holds ``v``
    non-contiguous chunks of the layer stack (virtual stage ``c*P + r``
    on rank r), so every microbatch crosses the ring v times but each
    warmup/drain idle amortizes over vM chunk ticks — bubble
    (P-1)/(vM+P-1) at v× the p2p volume and a deeper warmup window of
    (1/v-sized) chunk activations.
  * ``zb``     — zero-bubble 1F1B (ZB-H1 family): each backward splits
    into a dgrad sub-tick (activation cotangent, frees the stored input)
    and a deferred wgrad sub-tick that fills the drain — bubble
    2(P-1)/(3M+2P-2) < (P-1)/(M+P-1) at 1f1b's activation footprint plus
    a small parameter-gradient stash.

gpipe and 1f1b idle for the same fraction of ticks — ``(P-1)/(M+P-1)``,
exactly the bubble term ``core/costmodel.step_time`` charges — because at
equal per-tick cost 1F1B *reorders* the bubble rather than removing it
(what it buys is the smaller activation footprint, which is why the cost
model's ``mem`` term and therefore ``fits`` is schedule-dependent).  The
interleaved and zero-bubble schedules genuinely shrink the bubble, paying
in p2p volume / warmup depth (interleaved) or sub-tick count and wgrad
stash (zb) — the frontier ``costmodel.step_time`` charges per schedule.

The stage body computes over the *full inner mesh*: activations are
sharded over the batch axes (``x_spec``), stage params over ``axis`` plus
any tensor-/expert-parallel axes named in ``param_specs`` (Megatron-TP
psums and the MoE all-to-all run inside the stage — see
``models/transformer.make_pipelined_block_fn`` / ``core/expert.py``), and
GSPMD all-gathers FSDP-sharded params at entry.

Differentiable: the GPipe path trains through shard_map + ppermute's
transpose rules; every other schedule runs through one table-driven
executor, a ``jax.custom_vjp`` whose backward runs the combined
recompute-forward/backward tick loop of the schedule's table (the primal
stores only the schedule inputs, so per-stage activation residency really
is bounded by the warmup depth).
"""
from __future__ import annotations

import dataclasses
import logging
import re
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.compat import shard_map as _shard_map

logger = logging.getLogger(__name__)

# base schedule families; interleaved schedules are the parametric family
# '1f1b_i<v>' (v >= 2 virtual stages per rank) on top of these
SCHEDULE_NAMES = ("gpipe", "1f1b", "zb")

_INTERLEAVED_RE = re.compile(r"^1f1b_i(\d+)$")


def parse_schedule(sched: str) -> Tuple[str, int]:
    """Split a schedule name into (family, virtual_stages).

    'gpipe' / '1f1b' / 'zb' -> (name, 1); '1f1b_i<v>' -> ('1f1b_i', v)
    with v >= 2 (v == 1 is plain 1f1b — rejected to keep names canonical).
    Raises ValueError for anything else, so every validation site shares
    one grammar."""
    m = _INTERLEAVED_RE.match(sched)
    if m:
        v = int(m.group(1))
        if v < 2:
            raise ValueError(
                f"interleaved schedule {sched!r} needs v >= 2 virtual "
                "stages per rank (v == 1 is plain '1f1b')")
        return "1f1b_i", v
    if sched in SCHEDULE_NAMES:
        return sched, 1
    raise ValueError(f"unknown pipeline schedule {sched!r}; expected one "
                     f"of {SCHEDULE_NAMES} or '1f1b_i<v>' (v >= 2)")


def known_schedule(sched: str) -> bool:
    try:
        parse_schedule(sched)
        return True
    except ValueError:
        return False


def virtual_stages(sched: str) -> int:
    """Virtual stages (param chunks) per pipe rank: v for '1f1b_i<v>',
    1 for every flat schedule."""
    return parse_schedule(sched)[1]


# ---------------------------------------------------------------------------
# analytic terms (pure python — importable by the cost model without tracing)
# ---------------------------------------------------------------------------

def bubble_fraction(n_stages: int, n_microbatches: int,
                    sched: str = "gpipe") -> float:
    """Idle-tick fraction of the schedule.

      * gpipe / 1f1b — (P-1)/(M+P-1): identical at equal per-tick cost
        (1F1B *reorders* the bubble to cap in-flight activations, it does
        not shrink it);
      * 1f1b_i<v>  — (P-1)/(vM+P-1): v virtual stages per rank slice each
        tick v ways, so the same warmup/drain idles amortize over vM work
        ticks (Megatron interleaved);
      * zb         — 2(P-1)/(3M+2P-2): each backward splits into dgrad and
        wgrad sub-ticks (F/B/W all one sub-tick) and the deferred wgrads
        fill the drain; only the 2(P-1) warmup+drain sub-ticks idle, out
        of 3M work sub-ticks per rank (ZB-H1 with a bounded wgrad
        backlog).  Strictly below 1f1b's bubble for every M >= 1.
    """
    family, v = parse_schedule(sched)
    if n_stages <= 1:
        return 0.0
    P_, M = n_stages, n_microbatches
    if family == "1f1b_i":
        return (P_ - 1) / (v * M + P_ - 1)
    if family == "zb":
        return 2 * (P_ - 1) / (3 * M + 2 * P_ - 2)
    return (P_ - 1) / (M + P_ - 1)


def inflight_microbatches(n_stages: int, n_microbatches: int,
                          sched: str = "gpipe") -> int:
    """Peak number of in-flight activations a rank holds awaiting
    backward — the schedule-dependent factor in pipeline activation
    memory.

      * gpipe      — M whole-stage activations;
      * 1f1b / zb  — min(M, P) whole-stage activations (zb's dgrad
        sub-tick frees the activation exactly where 1f1b's combined
        backward does; the deferred wgrad keeps only a param-shaped
        gradient stash, charged separately by the cost model);
      * 1f1b_i<v>  — min(2(P-1) + (v-1)P + 1, vM) *chunk* activations,
        each covering 1/v of the rank's layer slice (the rank-0 warmup
        depth of the interleaved schedule) — divide by v before comparing
        against whole-stage units.
    """
    family, v = parse_schedule(sched)
    P_, M = n_stages, n_microbatches
    if n_stages <= 1:
        return M
    if family == "1f1b_i":
        return min(2 * (P_ - 1) + (v - 1) * P_ + 1, v * M)
    if family in ("1f1b", "zb"):
        return min(M, P_)
    return M


# ---------------------------------------------------------------------------
# batch-axis fitting
# ---------------------------------------------------------------------------

_warned_dropped: set = set()


def batch_axes_spec(mesh, axes: Sequence[str], dim_size: int) -> Tuple[str, ...]:
    """The prefix of ``axes`` that divides ``dim_size`` (fit-or-drop).

    Mirrors ``parallel._fit_spec``: when the microbatch row count cannot
    occupy the data axis (e.g. global_batch 8 split into 8 microbatches of
    1 row), the batch dim is kept replicated and the compute is redundant
    across that axis — correct, just not data-parallel.  Dropping an axis
    is logged (once per (axes, size, mesh-shape) combination) because the
    redundancy is silent in every other signal: the step *runs*, only
    ``dp``-fold slower per token than the plan's mesh suggests.
    """
    keep = []
    size = dim_size
    for a in axes:
        n = mesh.shape[a]
        if n > 1 and size % n == 0 and size >= n:
            keep.append(a)
            size //= n
    dropped = tuple(a for a in axes if a not in keep and mesh.shape[a] > 1)
    if dropped:
        key = (tuple(axes), dim_size,
               tuple((a, int(mesh.shape[a])) for a in axes))
        if key not in _warned_dropped:
            _warned_dropped.add(key)
            logger.warning(
                "pipeline microbatch of %d rows does not occupy batch "
                "mesh axes %s (sizes %s): the microbatch is replicated "
                "and compute is redundant across them — use a larger "
                "global batch or fewer microbatches for true data "
                "parallelism", dim_size, dropped,
                tuple(int(mesh.shape[a]) for a in dropped))
    return tuple(keep)


def _entry(axes: Tuple[str, ...]):
    return axes if len(axes) > 1 else (axes[0] if axes else None)


# ---------------------------------------------------------------------------
# GPipe's forward tick loop (differentiated by autodiff)
# ---------------------------------------------------------------------------

def _make_fwd_body(stage_fn: Callable, axis: str, n_stages: int):
    def per_stage(params_local, xs, extras_local):
        # params_local: (L/P, ...) stage slice; xs: (M, local_mb, ...)
        stage = jax.lax.axis_index(axis)
        M = xs.shape[0]
        mb_shape = xs.shape[1:]
        state = jnp.zeros(mb_shape, xs.dtype)          # activation in flight
        # its running aux loss — carried as shape (1,), never a scalar:
        # scalar shard_map residuals break the jax<=0.4 transpose (they
        # cannot take the residuals' dim-0 sharding)
        aux_state = jnp.zeros((1,), jnp.float32)
        outputs = jnp.zeros_like(xs)
        aux_out = jnp.zeros((M,), jnp.float32)

        def tick(carry, t):
            state, aux_state, outputs, aux_out = carry
            # stage 0 ingests microbatch t (while valid)
            inject = xs[jnp.minimum(t, M - 1)]
            h = jnp.where(stage == 0, inject, state)
            a = jnp.where(stage == 0, 0.0, aux_state)
            h, a_stage = stage_fn(params_local, h, extras_local)
            a = a + a_stage.astype(jnp.float32).reshape((1,))
            # last stage emits microbatch t - (P-1)
            out_slot = t - (n_stages - 1)
            valid = (out_slot >= 0) & (out_slot < M)
            emit = valid & (stage == n_stages - 1)
            outputs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_slice(
                    o, h[None], (jnp.maximum(out_slot, 0),) + (0,) * h.ndim),
                lambda o: o, outputs)
            aux_out = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_slice(
                    o, a, (jnp.maximum(out_slot, 0),)),
                lambda o: o, aux_out)
            # hand activation (+ its aux so far) to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            state = jax.lax.ppermute(h, axis, perm)
            aux_state = jax.lax.ppermute(a, axis, perm)
            return (state, aux_state, outputs, aux_out), None

        (state, aux_state, outputs, aux_out), _ = jax.lax.scan(
            tick, (state, aux_state, outputs, aux_out),
            jnp.arange(M + n_stages - 1))
        # only the last stage's buffer holds real outputs; select+broadcast.
        # aux leaves as the (M,) per-microbatch vector, reduced outside the
        # shard_map — a scalar output that doubles as a backward residual
        # trips jax<=0.4's transpose (scalars cannot take the residuals'
        # dim-0 sharding)
        mask = (stage == n_stages - 1).astype(outputs.dtype)
        outputs = jax.lax.psum(outputs * mask, axis)
        aux_mb = jax.lax.psum(
            aux_out * (stage == n_stages - 1).astype(jnp.float32), axis)
        return outputs, aux_mb

    return per_stage


@dataclasses.dataclass(frozen=True)
class _Specs:
    """Resolved shard_map specs for one pipeline_apply call."""
    x_spec: P
    pspec: object                      # pytree of P over stage_params
    espec: object                      # pytree of P over extras
    kept: Tuple[str, ...]              # batch axes actually sharding the mb
    seq_axis: str                      # context axis sharding the seq dim


def _resolve_specs(stage_params, x, mesh, axis, extras, batch_axes,
                   param_specs, seq_axis) -> _Specs:
    kept = batch_axes_spec(mesh, batch_axes, x.shape[1])
    entries: List = [None, _entry(kept)]
    if seq_axis:
        if x.ndim < 3 or x.shape[2] % mesh.shape[seq_axis]:
            raise ValueError(
                f"context-parallel pipeline needs the sequence dim "
                f"(x.shape={x.shape}) divisible by mesh axis "
                f"{seq_axis!r}={mesh.shape[seq_axis]}")
        entries.append(seq_axis)
    x_spec = P(*entries)
    pspec = (jax.tree.map(lambda _: P(axis), stage_params)
             if param_specs is None else param_specs)
    espec = jax.tree.map(lambda _: P(), extras)
    return _Specs(x_spec, pspec, espec, kept, seq_axis)


def _token_axes(specs: _Specs) -> Tuple[str, ...]:
    """Mesh axes over which the stage body's tokens are sharded (the axes
    whose param-cotangent contributions are distinct and must be summed)."""
    return specs.kept + ((specs.seq_axis,) if specs.seq_axis else ())


def _spec_axes(spec: P) -> Tuple[str, ...]:
    out = []
    for e in spec:
        if e is None:
            continue
        out.extend(e if isinstance(e, tuple) else (e,))
    return tuple(out)


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------

class PipelineSchedule:
    """One pipeline execution schedule: per-tick op tables (for simulation
    and tests), analytic bubble/memory terms, and the executable
    ``apply`` that runs stage_fn over the mesh."""

    name: str = "?"

    # ---- analytic -------------------------------------------------------
    def bubble_fraction(self, n_stages: int, n_microbatches: int) -> float:
        return bubble_fraction(n_stages, n_microbatches, self.name)

    def inflight_microbatches(self, n_stages: int,
                              n_microbatches: int) -> int:
        return inflight_microbatches(n_stages, n_microbatches, self.name)

    # ---- simulation -----------------------------------------------------
    def tick_table(self, n_stages: int, n_microbatches: int
                   ) -> List[List[Tuple[str, int]]]:
        """[tick][stage] -> ('F', j) | ('B', j) | ('idle', -1) covering the
        full fwd+bwd execution.  Host-side python; the executable loops are
        index arithmetic over exactly these tables."""
        raise NotImplementedError

    def simulate(self, n_stages: int, n_microbatches: int) -> Dict:
        """Counted-from-the-table bubble fraction and peak in-flight
        activations — what the analytic formulas must reproduce."""
        table = self.tick_table(n_stages, n_microbatches)
        idle = sum(op == "idle" for row in table for op, _ in row)
        total = len(table) * n_stages
        peak = 0
        inflight = [set() for _ in range(n_stages)]
        for row in table:
            for s, (op, j) in enumerate(row):
                if op == "F":
                    inflight[s].add(j)
                elif op == "B":
                    inflight[s].discard(j)
            peak = max(peak, max(len(f) for f in inflight))
        return {"ticks": len(table), "bubble": idle / total,
                "peak_inflight": peak}

    # ---- execution ------------------------------------------------------
    def apply(self, stage_fn, stage_params, x, mesh, axis, extras,
              batch_axes=(), param_specs=None, seq_axis="", tp_axis=""):
        raise NotImplementedError


class GPipeSchedule(PipelineSchedule):
    """All forwards, then (under autodiff's transposed scan) all
    backwards; M microbatch activations in flight per stage."""

    name = "gpipe"

    def tick_table(self, n_stages, n_microbatches):
        P_, M = n_stages, n_microbatches
        table = []
        for t in range(M + P_ - 1):                       # forward pass
            table.append([("F", t - s) if 0 <= t - s < M else ("idle", -1)
                          for s in range(P_)])
        for u in range(M + P_ - 1):                       # transposed scan
            t = M + P_ - 2 - u
            table.append([("B", t - s) if 0 <= t - s < M else ("idle", -1)
                          for s in range(P_)])
        return table

    def apply(self, stage_fn, stage_params, x, mesh, axis, extras,
              batch_axes=(), param_specs=None, seq_axis="", tp_axis=""):
        n_stages = mesh.shape[axis]
        specs = _resolve_specs(stage_params, x, mesh, axis, extras,
                               batch_axes, param_specs, seq_axis)
        fn = _shard_map(_make_fwd_body(stage_fn, axis, n_stages), mesh,
                        in_specs=(specs.pspec, specs.x_spec, specs.espec),
                        out_specs=(specs.x_spec, P()))
        return fn(stage_params, x, extras)


# ---------------------------------------------------------------------------
# table-driven schedules: 1F1B, interleaved 1F1B and zero-bubble
# ---------------------------------------------------------------------------
# Every schedule that recomputes its forward in the backward runs through
# one executor (``_TableSchedule.apply``).  A schedule only supplies its
# host-side (tick, rank) -> (op, chunk, mb) table: 1F1B's is closed-form,
# the interleaved and zero-bubble ones come from greedy list schedulers
# (their per-rank op order depends on warmup depth and chunk rotation, or
# on three sub-tick kinds).  Static int32 arrays derived from the table
# drive both the primal forward scan and the custom_vjp combined
# recompute/backward scan.

_OP_CODES = {"idle": 0, "F": 1, "B": 2, "W": 3}


def _interleaved_full_table(P_, M, v):
    """Greedy Megatron-order interleaved 1F1B.

    Virtual stage ``sv = c*P + r`` (chunk c of rank r); per-rank op order
    is the Megatron one — forwards in groups of P microbatches,
    chunk-major within the group; backwards the same with chunks
    reversed — after a ``min(2(P-1-r) + (v-1)P + 1, vM)`` warmup.  The
    result achieves exactly T = 2(vM+P-1) ticks and bubble
    (P-1)/(vM+P-1) with peak in-flight chunk activations equal to the
    rank-0 warmup depth."""
    if M % P_:
        raise ValueError(
            f"interleaved 1f1b needs microbatches divisible by stages "
            f"(got M={M}, P={P_}: the chunk rotation assigns microbatches "
            "to ranks in groups of P)")
    S = v * P_
    order_f = [(c, g * P_ + o) for g in range(M // P_)
               for c in range(v) for o in range(P_)]
    order_b = [(c, g * P_ + o) for g in range(M // P_)
               for c in range(v - 1, -1, -1) for o in range(P_)]
    warm = [min(2 * (P_ - r - 1) + (v - 1) * P_ + 1, v * M)
            for r in range(P_)]
    done_f, done_b = {}, {}
    fi = [0] * P_
    bi = [0] * P_
    table = []
    t = 0
    while any(fi[r] < v * M or bi[r] < v * M for r in range(P_)):
        row = []
        for r in range(P_):
            entry = ("idle", 0, 0)
            if fi[r] < warm[r] and bi[r] == 0:
                want = "F"                      # warmup forwards
            elif bi[r] < v * M and (fi[r] >= v * M
                                    or bi[r] <= fi[r] - warm[r]):
                want = "B"                      # steady 1B after warmup
            elif fi[r] < v * M:
                want = "F"
            else:
                want = "B"
            for cand in (want, "B" if want == "F" else "F"):
                if cand == "F" and fi[r] < v * M:
                    c, j = order_f[fi[r]]
                    sv = c * P_ + r
                    if sv == 0 or done_f.get((sv - 1, j), t) < t:
                        entry = ("F", c, j)
                        done_f[(sv, j)] = t
                        fi[r] += 1
                        break
                elif cand == "B" and bi[r] < v * M:
                    c, j = order_b[bi[r]]
                    sv = c * P_ + r
                    ok = (done_b.get((sv + 1, j), t) < t if sv < S - 1
                          else done_f.get((sv, j), t) < t)
                    if ok:
                        entry = ("B", c, j)
                        done_b[(sv, j)] = t
                        bi[r] += 1
                        break
            row.append(entry)
        table.append(row)
        t += 1
        if t > 6 * (v * M + P_):
            raise RuntimeError("interleaved schedule made no progress")
    return table


def _zb_full_table(P_, M):
    """Greedy zero-bubble (ZB-H1-style) table: each backward splits into a
    dgrad sub-tick ('B': activation cotangent, frees the stored input) and
    a deferred wgrad sub-tick ('W': parameter gradient) that fills what
    would otherwise be drain idle time.

    Priority B > W > F keeps the wgrad backlog at <= 1 pending microbatch
    per rank while still reaching T = 3M + 2(P-1) sub-ticks — bubble
    2(P-1)/(3M+2P-2), strictly below 1f1b's (P-1)/(M+P-1) for all M.
    (B > F > W reaches the (P-1)/(3M+P-1) floor but lets the backlog grow
    to M — an O(M) param-gradient stash for a second-order win.)"""
    if M < P_:
        raise ValueError(f"zb needs microbatches >= stages "
                         f"(got M={M} < P={P_})")
    done_f, done_b = {}, {}
    fi = [0] * P_
    bi = [0] * P_
    wi = [0] * P_
    table = []
    t = 0
    while any(fi[r] < M or bi[r] < M or wi[r] < M for r in range(P_)):
        row = []
        for r in range(P_):
            entry = ("idle", 0, 0)
            if bi[r] < M and (done_b.get((r + 1, bi[r]), t) < t
                              if r < P_ - 1
                              else done_f.get((r, bi[r]), t) < t):
                entry = ("B", 0, bi[r])
                done_b[(r, bi[r])] = t
                bi[r] += 1
            elif wi[r] < bi[r]:
                entry = ("W", 0, wi[r])
                wi[r] += 1
            elif fi[r] < M and fi[r] - bi[r] < P_ - r and \
                    (r == 0 or done_f.get((r - 1, fi[r]), t) < t):
                entry = ("F", 0, fi[r])
                done_f[(r, fi[r])] = t
                fi[r] += 1
            row.append(entry)
        table.append(row)
        t += 1
        if t > 6 * (3 * M + 2 * P_):
            raise RuntimeError("zb schedule made no progress")
    return table


def _fwd_only_table(P_, M, v):
    """Forward-only table (the custom_vjp primal): each rank runs its
    Megatron-order forwards as soon as the upstream virtual stage has
    produced the input."""
    S = v * P_
    order_f = [(c, g * P_ + o) for g in range(M // P_)
               for c in range(v) for o in range(P_)] if v > 1 else \
        [(0, j) for j in range(M)]
    done_f = {}
    fi = [0] * P_
    table = []
    t = 0
    while any(fi[r] < v * M for r in range(P_)):
        row = []
        for r in range(P_):
            entry = ("idle", 0, 0)
            if fi[r] < v * M:
                c, j = order_f[fi[r]]
                sv = c * P_ + r
                if sv == 0 or done_f.get((sv - 1, j), t) < t:
                    entry = ("F", c, j)
                    done_f[(sv, j)] = t
                    fi[r] += 1
            row.append(entry)
        table.append(row)
        t += 1
        if t > 6 * (v * M + P_):
            raise RuntimeError("forward table made no progress")
    return table


def _max_overlap(intervals):
    """Peak count of integer-time intervals [a, b] simultaneously alive."""
    events = []
    for a, b in intervals:
        if b >= a:
            events.append((a, 1))
            events.append((b + 1, -1))
    events.sort()
    cur = peak = 0
    for _, d in events:
        cur += d
        peak = max(peak, cur)
    return peak


def _ring_depths(table, P_, M, v):
    """Ring-buffer depths the executable needs for this exact table:
    (act, pend_f, pend_b, wgrad-stash) — the per-(rank, chunk) peak count
    of stored stage inputs (F..B), inbound activations (upstream F..own
    F), inbound cotangents (downstream B..own B) and pending wgrads
    (B..W).  Production/consumption are both j-ascending per chunk, so
    the alive set is a consecutive microbatch window and slot ``j % depth``
    is collision-free."""
    S = v * P_
    tf, tb, tw = {}, {}, {}
    for t, row in enumerate(table):
        for r, (op, c, j) in enumerate(row):
            sv = c * P_ + r
            if op == "F":
                tf[(sv, j)] = t
            elif op == "B":
                tb[(sv, j)] = t
            elif op == "W":
                tw[(sv, j)] = t
    da = df = db = dw = 1
    for sv in range(S):
        if tb:
            da = max(da, _max_overlap(
                [(tf[(sv, j)], tb[(sv, j)] - 1) for j in range(M)]))
        if sv > 0:
            df = max(df, _max_overlap(
                [(tf[(sv - 1, j)], tf[(sv, j)] - 1) for j in range(M)]))
        if tb and sv < S - 1:
            db = max(db, _max_overlap(
                [(tb[(sv + 1, j)], tb[(sv, j)] - 1) for j in range(M)]))
        if tw:
            dw = max(dw, _max_overlap(
                [(tb[(sv, j)], tw[(sv, j)] - 1) for j in range(M)]))
    return da, df, db, dw


def _sched_arrays(table, P_, M, v, df, db):
    """Static int32 (T, P) arrays driving the traced tick loop: per-tick
    op/chunk/microbatch for this rank, virtual-stage-boundary flags, and
    where (if anywhere) to store the values arriving over the two
    ppermute rings this tick (derived from what the *neighbors* ran)."""
    S = v * P_
    T = len(table)

    def zeros():
        return np.zeros((T, P_), np.int32)

    a = {k: zeros() for k in ("op", "c", "j", "sv0", "svl", "sf_on", "sf_c",
                              "sf_slot", "sb_on", "sb_c", "sb_slot")}
    for t, row in enumerate(table):
        for r, (op, c, j) in enumerate(row):
            a["op"][t, r] = _OP_CODES[op]
            if op == "idle":
                continue
            a["c"][t, r] = c
            a["j"][t, r] = j
            sv = c * P_ + r
            a["sv0"][t, r] = int(sv == 0)
            a["svl"][t, r] = int(sv == S - 1)
        for r in range(P_):
            lop, lc, lj = row[(r - 1) % P_]           # fwd ring: left -> r
            if lop == "F":
                sv = lc * P_ + (r - 1) % P_
                if sv < S - 1:
                    a["sf_on"][t, r] = 1
                    a["sf_c"][t, r] = (sv + 1) // P_
                    a["sf_slot"][t, r] = lj % df
            rop, rc, rj = row[(r + 1) % P_]           # bwd ring: right -> r
            if rop == "B":
                sv = rc * P_ + (r + 1) % P_
                if sv > 0:
                    a["sb_on"][t, r] = 1
                    a["sb_c"][t, r] = (sv - 1) // P_
                    a["sb_slot"][t, r] = rj % db
    return {k: jnp.asarray(val) for k, val in a.items()}


class _TableSchedule(PipelineSchedule):
    """The executor of every recomputing schedule (1F1B, interleaved 1F1B,
    zero-bubble).  Subclasses provide the full fwd+bwd table.  The
    ``jax.custom_vjp`` primal stores only the schedule inputs; the
    backward replays microbatch forwards just-in-time, holding per-chunk
    ring buffers sized from the table (``_ring_depths``), the rank's
    layer slice cut into its v chunks, and (zb) a deferred
    parameter-gradient stash written at the dgrad sub-tick and drained at
    the wgrad one."""

    v: int = 1
    has_wgrad: bool = False

    def _full_table(self, n_stages, n_microbatches):
        raise NotImplementedError

    def tick_table(self, n_stages, n_microbatches):
        # (op, chunk*M + mb): unique work-item ids so ``simulate`` counts
        # chunk activations (F adds, B frees — W keeps only a param-shaped
        # stash, not an activation)
        M = n_microbatches
        return [[(op, c * M + j) if op != "idle" else ("idle", -1)
                 for (op, c, j) in row]
                for row in self._full_table(n_stages, n_microbatches)]

    # -- execution --------------------------------------------------------
    def apply(self, stage_fn, stage_params, x, mesh, axis, extras,
              batch_axes=(), param_specs=None, seq_axis="", tp_axis=""):
        n_stages = mesh.shape[axis]
        M = x.shape[0]
        v = self.v
        full_table = self._full_table(n_stages, M)     # validates M vs P
        fwd_table = _fwd_only_table(n_stages, M, v)
        da, df, db, dw = _ring_depths(full_table, n_stages, M, v)
        _, f_df, _, _ = _ring_depths(fwd_table, n_stages, M, v)

        leaves = jax.tree.leaves(stage_params)
        L = leaves[0].shape[0] if leaves else 0
        if L % (n_stages * v):
            raise ValueError(
                f"{L} stacked layers do not split into pipe={n_stages} x "
                f"v={v} virtual-stage chunks ({self.name})")
        nl = L // (n_stages * v)            # layers in one chunk
        if v > 1:
            # re-chunk the stack: rank r's contiguous pipe shard must hold
            # the v non-contiguous slices of virtual stages c*P + r.
            # jnp.take is differentiable and sits outside the custom_vjp,
            # so its transpose un-permutes the param cotangents for free.
            perm = np.array([(c * n_stages + r) * nl + i
                             for r in range(n_stages)
                             for c in range(v)
                             for i in range(nl)], dtype=np.int32)
            stage_params = jax.tree.map(
                lambda a: jnp.take(a, perm, axis=0), stage_params)
        specs = _resolve_specs(stage_params, x, mesh, axis, extras,
                               batch_axes, param_specs, seq_axis)

        def chunk(tree, c):
            # chunk c of the rank's layer slice: rows [c*nl, (c+1)*nl)
            return jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, c * nl, nl), tree)

        def add_to_chunk(tree, g, c):
            return jax.tree.map(
                lambda a, ga: jax.lax.dynamic_update_slice_in_dim(
                    a, jax.lax.dynamic_slice_in_dim(a, c * nl, nl) + ga,
                    c * nl, 0), tree, g)

        def ring_read(buf, c, slot):
            return jax.lax.dynamic_index_in_dim(
                jax.lax.dynamic_index_in_dim(buf, c, 0, keepdims=False),
                slot, 0, keepdims=False)

        def ring_write(buf, val, c, slot):
            return jax.lax.dynamic_update_slice(
                buf, val[None][None], (c, slot) + (0,) * val.ndim)

        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        bwd_perm = [(i, (i - 1) % n_stages) for i in range(n_stages)]

        def fwd_body(params_local, xs, extras_local):
            stage = jax.lax.axis_index(axis)
            mb_shape = xs.shape[1:]
            arrs = _sched_arrays(fwd_table, n_stages, M, v, f_df, 1)

            def tick(carry, tarr):
                pend_h, pend_a, outputs, aux_out = carry
                op = tarr["op"][stage]
                c = tarr["c"][stage]
                j = tarr["j"][stage]
                first = tarr["sv0"][stage].astype(bool)
                last = tarr["svl"][stage].astype(bool)
                slot = jnp.mod(j, f_df)
                h_in = jnp.where(first, xs[j], ring_read(pend_h, c, slot))
                a_in = jnp.where(first, jnp.zeros((1,), jnp.float32),
                                 ring_read(pend_a, c, slot))
                h_out, a_stage = stage_fn(chunk(params_local, c), h_in,
                                          extras_local)
                a_out = a_in + a_stage.astype(jnp.float32).reshape((1,))
                emit = (op == 1) & last
                outputs = jax.lax.cond(
                    emit,
                    lambda o: jax.lax.dynamic_update_slice(
                        o, h_out[None], (j,) + (0,) * h_out.ndim),
                    lambda o: o, outputs)
                aux_out = jax.lax.cond(
                    emit,
                    lambda o: jax.lax.dynamic_update_slice(o, a_out, (j,)),
                    lambda o: o, aux_out)
                h_recv = jax.lax.ppermute(h_out, axis, fwd_perm)
                a_recv = jax.lax.ppermute(a_out, axis, fwd_perm)
                on = tarr["sf_on"][stage].astype(bool)
                dc = tarr["sf_c"][stage]
                ds = tarr["sf_slot"][stage]
                pend_h = jax.lax.cond(
                    on, lambda b: ring_write(b, h_recv, dc, ds),
                    lambda b: b, pend_h)
                pend_a = jax.lax.cond(
                    on, lambda b: ring_write(b, a_recv, dc, ds),
                    lambda b: b, pend_a)
                return (pend_h, pend_a, outputs, aux_out), None

            carry0 = (jnp.zeros((v, f_df) + mb_shape, xs.dtype),
                      jnp.zeros((v, f_df, 1), jnp.float32),
                      jnp.zeros_like(xs), jnp.zeros((M,), jnp.float32))
            (_, _, outputs, aux_out), _ = jax.lax.scan(tick, carry0, arrs)
            mask = (stage == n_stages - 1)
            outputs = jax.lax.psum(
                outputs * mask.astype(outputs.dtype), axis)
            aux_mb = jax.lax.psum(
                aux_out * mask.astype(jnp.float32), axis)
            return outputs, aux_mb

        tok_axes = _token_axes(specs)
        # Megatron-TP cotangent convention inside the manual loop: the
        # stage body contains raw psums, so a replicated value's physical
        # cotangents must SUM across model ranks to the logical one (the
        # "split" convention — see layers.tp_reduce_out).  Injected
        # cotangents (dy, d_aux) are therefore divided by tp, and the
        # final reductions psum back over the model axis.
        tp_div = mesh.shape[tp_axis] if tp_axis else 1
        grad_axes = tok_axes + (
            (tp_axis,) if tp_axis and tp_axis not in tok_axes else ())
        # per-leaf gradient reduction: sum over the axes this leaf is
        # replicated across but whose contributions are distinct (token
        # shards; split model-cotangents under TP).  A leaf already
        # sharded over 'expert'/'model' owns its slice's cotangent.
        p_reduce = jax.tree.map(
            lambda sp: tuple(a for a in grad_axes
                             if a not in _spec_axes(sp)),
            specs.pspec, is_leaf=lambda s: isinstance(s, P))
        # extras feed every stage and every token/head shard
        e_reduce = (axis,) + grad_axes

        def bwd_body(params_local, xs, extras_local, dy, d_aux):
            stage = jax.lax.axis_index(axis)
            mb_shape = xs.shape[1:]
            arrs = _sched_arrays(full_table, n_stages, M, v, df, db)
            zeros_mb = jnp.zeros(mb_shape, xs.dtype)

            def tick(carry, tarr):
                (pend_h, pend_c, act_buf, d_params, d_extras, d_xs,
                 dp_stash) = carry
                op = tarr["op"][stage]
                c = tarr["c"][stage]
                j = tarr["j"][stage]
                first = tarr["sv0"][stage].astype(bool)
                last = tarr["svl"][stage].astype(bool)
                cp = chunk(params_local, c)

                def fwd_op(bufs):
                    pend_h, act_buf = bufs
                    x_in = jnp.where(first, xs[j],
                                     ring_read(pend_h, c, jnp.mod(j, df)))
                    h_out, _ = stage_fn(cp, x_in, extras_local)
                    return h_out, ring_write(act_buf, x_in, c,
                                             jnp.mod(j, da))

                def fwd_or_idle(grads):
                    # A forward touches only the inbound activations and
                    # the stored inputs, so it runs in a cond of its own:
                    # the gradient state passed through the branch that
                    # runs the stage would be copied leaf by leaf each tick.
                    f_pay, act_buf_ = jax.lax.cond(
                        op == _OP_CODES["F"], fwd_op,
                        lambda bufs: (zeros_mb, bufs[1]), (pend_h, act_buf))
                    return (f_pay, zeros_mb, act_buf_) + grads

                def b_op(grads):
                    d_params, d_extras, d_xs, dp_stash = grads
                    h_saved = ring_read(act_buf, c, jnp.mod(j, da))
                    dy_in = jnp.where(last, dy[j] / tp_div,
                                      ring_read(pend_c, c, jnp.mod(j, db)))
                    da_cot = d_aux[j].astype(jnp.float32) / tp_div
                    _, vjp_fn = jax.vjp(stage_fn, cp, h_saved, extras_local)
                    dpc, dh, de = vjp_fn((dy_in, da_cot.reshape(())))
                    if self.has_wgrad:
                        # dgrad sub-tick: defer the param gradient to the
                        # W sub-tick; only the (depth-dw) stash survives
                        dp_stash = jax.tree.map(
                            lambda s, g: jax.lax.dynamic_update_slice(
                                s, g[None],
                                (jnp.mod(j, dw),) + (0,) * g.ndim),
                            dp_stash, dpc)
                    else:
                        d_params = add_to_chunk(d_params, dpc, c)
                    d_extras = jax.tree.map(jnp.add, d_extras, de)
                    upd = jax.lax.dynamic_update_slice(
                        d_xs, dh[None].astype(d_xs.dtype),
                        (j,) + (0,) * dh.ndim)
                    d_xs = jnp.where(first, upd, d_xs)
                    return (zeros_mb, dh, act_buf, d_params, d_extras, d_xs,
                            dp_stash)

                def w_op(grads):
                    d_params, d_extras, d_xs, dp_stash = grads
                    g = jax.tree.map(
                        lambda s: jax.lax.dynamic_index_in_dim(
                            s, jnp.mod(j, dw), 0, keepdims=False), dp_stash)
                    d_params = add_to_chunk(d_params, g, c)
                    return (zeros_mb, zeros_mb, act_buf, d_params, d_extras,
                            d_xs, dp_stash)

                # branch 0 serves idle and F ticks, 1 B, 2 W
                branches = [fwd_or_idle, b_op]
                if self.has_wgrad:
                    branches.append(w_op)
                (f_pay, b_pay, act_buf, d_params, d_extras, d_xs,
                 dp_stash) = jax.lax.switch(
                    jnp.maximum(op - _OP_CODES["F"], 0), branches,
                    (d_params, d_extras, d_xs, dp_stash))
                h_recv = jax.lax.ppermute(f_pay, axis, fwd_perm)
                c_recv = jax.lax.ppermute(b_pay, axis, bwd_perm)
                pend_h = jax.lax.cond(
                    tarr["sf_on"][stage].astype(bool),
                    lambda b: ring_write(b, h_recv, tarr["sf_c"][stage],
                                         tarr["sf_slot"][stage]),
                    lambda b: b, pend_h)
                pend_c = jax.lax.cond(
                    tarr["sb_on"][stage].astype(bool),
                    lambda b: ring_write(b, c_recv, tarr["sb_c"][stage],
                                         tarr["sb_slot"][stage]),
                    lambda b: b, pend_c)
                return (pend_h, pend_c, act_buf, d_params, d_extras,
                        d_xs, dp_stash), None

            dp_stash0 = (jax.tree.map(
                lambda a: jnp.zeros((dw, nl) + a.shape[1:], a.dtype),
                params_local) if self.has_wgrad else None)
            carry0 = (jnp.zeros((v, df) + mb_shape, xs.dtype),
                      jnp.zeros((v, db) + mb_shape, xs.dtype),
                      jnp.zeros((v, da) + mb_shape, xs.dtype),
                      jax.tree.map(jnp.zeros_like, params_local),
                      jax.tree.map(jnp.zeros_like, extras_local),
                      jnp.zeros_like(xs),
                      dp_stash0)
            (_, _, _, d_params, d_extras, d_xs, _), _ = jax.lax.scan(
                tick, carry0, arrs)
            d_params = jax.tree.map(
                lambda g, axes: jax.lax.psum(g, axes) if axes else g,
                d_params, p_reduce)
            d_extras = jax.tree.map(
                lambda g: jax.lax.psum(g, e_reduce), d_extras)
            # only stage 0 wrote d_xs; under TP its per-model-rank values
            # are split cotangents — the psum also recombines those
            d_xs = jax.lax.psum(
                d_xs, (axis,) + ((tp_axis,) if tp_axis else ()))
            return d_params, d_xs, d_extras

        fwd_sm = _shard_map(
            fwd_body, mesh,
            in_specs=(specs.pspec, specs.x_spec, specs.espec),
            out_specs=(specs.x_spec, P()))
        bwd_sm = _shard_map(
            bwd_body, mesh,
            in_specs=(specs.pspec, specs.x_spec, specs.espec,
                      specs.x_spec, P()),
            out_specs=(specs.pspec, specs.x_spec, specs.espec))

        @jax.custom_vjp
        def call(stage_params, x, extras):
            return fwd_sm(stage_params, x, extras)

        def call_fwd(stage_params, x, extras):
            return fwd_sm(stage_params, x, extras), (stage_params, x, extras)

        def call_bwd(res, cots):
            stage_params, x, extras = res
            d_out, d_aux = cots
            return bwd_sm(stage_params, x, extras, d_out, d_aux)

        call.defvjp(call_fwd, call_bwd)
        return call(stage_params, x, extras)


class OneFOneBSchedule(_TableSchedule):
    """1F1B (PipeDream-flush): stage s runs P - s warmup forwards, then
    alternates one-forward-one-backward, then drains.  Per-stage in-flight
    activations <= P instead of M.

    The table is closed-form: stage s forwards microbatch j at tick
    ``s + j`` during warmup (j < P - s) and ``2j + s`` in steady state; it
    backwards j at ``2j + 2P - 1 - s``.  Run by the shared executor, it
    holds min(M, P) stage inputs and one inbound value per ring."""

    name = "1f1b"

    def _full_table(self, n_stages, n_microbatches):
        P_, M = n_stages, n_microbatches
        if M < P_:
            raise ValueError(f"1f1b needs microbatches >= stages "
                             f"(got M={M} < P={P_})")
        table = [[("idle", 0, 0)] * P_ for _ in range(2 * (M + P_ - 1))]
        for s in range(P_):
            for j in range(M):
                table[s + j if j < P_ - s else 2 * j + s][s] = ("F", 0, j)
                table[2 * j + 2 * P_ - 1 - s][s] = ("B", 0, j)
        return table


class InterleavedOneFOneBSchedule(_TableSchedule):
    """Interleaved 1F1B (Megatron virtual stages): each pipe rank holds
    ``v`` non-contiguous chunks of the layer stack (virtual stage
    ``c*P + r`` on rank r), so warmup/drain idles amortize over vM chunk
    ticks — bubble (P-1)/(vM+P-1) — at the price of each microbatch
    crossing the p2p ring v times and a deeper warmup window of chunk
    activations (``inflight_microbatches``, in 1/v-stage units)."""

    has_wgrad = False

    def __init__(self, v: int):
        if v < 2:
            raise ValueError("interleaved 1f1b needs v >= 2 virtual "
                             f"stages per rank (got {v})")
        self.v = v
        self.name = f"1f1b_i{v}"

    def _full_table(self, n_stages, n_microbatches):
        return _interleaved_full_table(n_stages, n_microbatches, self.v)


class ZeroBubbleSchedule(_TableSchedule):
    """Zero-bubble 1F1B (ZB-H1 with a bounded wgrad backlog): the
    backward splits into dgrad ('B', frees the stored input and sends the
    activation cotangent on) and wgrad ('W', drains the deferred
    parameter gradient) sub-ticks; deferred wgrads fill the drain for a
    2(P-1)/(3M+2P-2) bubble at 1f1b's min(M, P) activation footprint
    plus a backlog-deep (usually 1) param-gradient stash."""

    name = "zb"
    v = 1
    has_wgrad = True

    def _full_table(self, n_stages, n_microbatches):
        return _zb_full_table(n_stages, n_microbatches)


SCHEDULES: Dict[str, PipelineSchedule] = {
    "gpipe": GPipeSchedule(),
    "1f1b": OneFOneBSchedule(),
    "1f1b_i2": InterleavedOneFOneBSchedule(2),
    "zb": ZeroBubbleSchedule(),
}


def get_schedule(name: str) -> PipelineSchedule:
    try:
        return SCHEDULES[name]
    except KeyError:
        pass
    family, v = parse_schedule(name)       # raises for unknown names
    assert family == "1f1b_i", name        # base names are all registered
    return InterleavedOneFOneBSchedule(v)


def op_tick_counts(sched: str, n_stages: int,
                   n_microbatches: int) -> Dict[str, int]:
    """Sub-tick census of the schedule's table, summed over ranks:
    forward / dgrad ('B') / wgrad ('W') / idle op counts plus the total
    tick count — the dryrun artifact's per-schedule sub-tick record."""
    table = get_schedule(sched).tick_table(n_stages, n_microbatches)
    out = {"F": 0, "B": 0, "W": 0, "idle": 0}
    for row in table:
        for op, _ in row:
            out[op] += 1
    out["ticks"] = len(table)
    return out


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def pipeline_apply(stage_fn: Callable, stage_params, x_microbatches,
                   mesh, axis: str = "pipe", extras=None,
                   batch_axes: Sequence[str] = (), schedule: str = "gpipe",
                   param_specs=None, seq_axis: str = "", tp_axis: str = ""):
    """Run x through P stages of stage_fn under the named schedule.

    stage_fn: (stage_params_local, h, extras) -> (h, aux), applied by every
      stage on its local slice of the stacked layer params; ``aux`` is a
      float32 scalar per-stage extra loss (the MoE load-balance term) that
      rides along the activation through the schedule.  It must be
      *shard-invariant* across the batch/model axes (the MoE stats are
      psum-reduced inside the router for exactly this reason).
    stage_params: pytree whose leaves have a leading stack dim divisible by
      the pipe axis size (sharded contiguously over ``axis``: stage p gets
      slice [p*L/P, (p+1)*L/P)).
    x_microbatches: (M, mb, ...) microbatched activations; the mb (batch)
      dim is sharded over ``batch_axes`` when divisible, else replicated.
    extras: pytree broadcast to every stage unsharded (e.g. rope angles
      with batch dim 1).
    schedule: 'gpipe' | '1f1b' | '1f1b_i<v>' | 'zb' (see module
      docstring).
    param_specs: optional pytree of PartitionSpecs for stage_params; the
      default shards only the stack dim over ``axis``.  Inner-mesh plans
      pass Megatron-TP / expert-sharded specs so the stage body computes
      over the model/expert axes instead of replicating.
    seq_axis: mesh axis sharding the sequence dim of x inside the stage
      (manual context parallelism; the stage body must gather KV).
    tp_axis: mesh axis the stage body runs Megatron psums over (used to
      reduce extras-cotangents; the psums themselves live in stage_fn).

    Returns ((M, mb, ...) outputs sharded like x, aux summed over
    microbatches and stages — a replicated scalar).
    """
    out, aux_mb = get_schedule(schedule).apply(
        stage_fn, stage_params, x_microbatches, mesh, axis, extras,
        batch_axes=batch_axes, param_specs=param_specs, seq_axis=seq_axis,
        tp_axis=tp_axis)
    return out, aux_mb.sum()


def make_pipelined_block_fn(cfg, rt):
    """stage_fn applying this stage's slice of the stacked layer params.

    ``extras`` carries the rope angles (batch dim 1, broadcast over the
    local microbatch).  The Runtime must have ``constrain=None``: the
    stage body runs inside a fully-manual shard_map where named-sharding
    constraints are meaningless.  Inner-mesh composition is driven by the
    Runtime fields:

      * ``rt.tp_reduce_axis``  — Megatron-TP: the layer code sees a
        head/hidden-local config (the caller shards params over the model
        axis via ``param_specs``) and ``_apply_layer`` psums the mixer/ffn
        outputs over this axis;
      * ``rt.cp_axis``         — manual context parallelism: attention
        gathers KV over this axis and offsets its causal mask;
      * ``rt.moe_impl == 'ep_manual'`` — MoE layers dispatch through
        ``core/expert.py``'s all-to-all on ``rt.expert_axis`` directly
        (we are already inside the manual mesh).

    Returns (h, aux): the per-stage sum of the MoE load-balance losses of
    this stage's layers (zeros for dense stacks), which the schedule
    threads through the ticks.
    """
    from repro.models.transformer import _apply_layer, _sig

    sig = _sig(cfg, 0)
    cfg_stage = cfg
    if rt.tp_reduce_axis:
        # Megatron-TP inside the manual mesh: the stage body sees *local*
        # head/hidden shapes, so hand the layer code a config with local
        # counts (head_dim pinned first — it must not be re-derived from
        # the sliced head count)
        tp = rt.pipeline_mesh.shape[rt.tp_reduce_axis]
        cfg_stage = dataclasses.replace(
            cfg, head_dim=cfg.head_dim_,
            n_heads=cfg.n_heads // tp,
            n_kv_heads=cfg.kv_heads // tp)

    apply = _apply_layer
    if rt.remat:
        apply = jax.checkpoint(_apply_layer, static_argnums=(0, 1, 5))

    def stage_fn(stage_params, h, rope_ang):
        # stage_params: {'layers': pytree stacked (L_per_stage, ...)}
        def body(carry, lp):
            h_, aux_ = carry
            h2, _, a = apply(cfg_stage, sig, lp, h_, rope_ang, rt)
            return (h2, aux_ + a), None
        (h, aux), _ = jax.lax.scan(
            body, (h, jnp.zeros((), jnp.float32)), stage_params["layers"])
        return h, aux

    return stage_fn
