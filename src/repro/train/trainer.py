"""Training loop: jitted sharded train step, gradient accumulation,
metrics, checkpoint hooks.

``make_train_step`` is also what the multi-pod dry-run lowers: it closes
over (cfg, plan, runtime) and maps (params, opt_state, batch) ->
(params, opt_state, metrics) with every input/output sharded per the plan.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import parallel as par
from repro.models import transformer as tfm
from repro.models.layers import Runtime
from repro.optim import AdamWConfig, adamw_update, init_opt_state
from repro.optim.schedule import linear_warmup_cosine
from repro import telemetry as tel


@dataclasses.dataclass
class TrainConfig:
    steps: int = 100
    warmup: int = 10
    log_every: int = 10
    ckpt_every: int = 0
    ckpt_dir: str = ""
    grad_accum: int = 1
    opt: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)
    # resilience: async checkpointing + kill/resume (resilience subsystem)
    ckpt_async: bool = False        # snapshot on-thread, write in background
    ckpt_max_in_flight: int = 2     # bounded queued background writes
    ckpt_keep: int = 0              # gc all but the newest N (0 = keep all)
    resume: bool = False            # restore latest *valid* ckpt_dir state


def make_train_step(cfg: ModelConfig, rt: Runtime, tc: TrainConfig,
                    total_steps: Optional[int] = None):
    """Pure (params, opt_state, batch) -> (params, opt_state, metrics)."""
    total = total_steps or tc.steps

    def train_step(params, opt_state, batch):
        B = batch["labels"].shape[0]
        if B % max(tc.grad_accum, 1):
            raise ValueError(
                f"batch {B} does not split into grad_accum={tc.grad_accum}")
        if rt.pipeline_microbatches > 1 and \
                (B // max(tc.grad_accum, 1)) % rt.pipeline_microbatches:
            # GA slices the batch first; each GA microbatch is then split
            # into M pipeline microbatches — both must compose exactly
            raise ValueError(
                f"batch {B} / grad_accum {tc.grad_accum} does not split "
                f"into {rt.pipeline_microbatches} pipeline microbatches")

        def loss(p):
            return tfm.loss_fn(cfg, p, batch, rt)

        if tc.grad_accum > 1:
            # split the local batch into microbatches along dim 0
            def slice_mb(i):
                return jax.tree.map(
                    lambda x: jax.lax.dynamic_slice_in_dim(
                        x, i * (x.shape[0] // tc.grad_accum),
                        x.shape[0] // tc.grad_accum, 0)
                    if getattr(x, "ndim", 0) > 0 else x, batch)

            def value_grad(mb):
                return jax.value_and_grad(
                    lambda p: tfm.loss_fn(cfg, p, mb, rt),
                    has_aux=True)(params)

            def micro(i, acc):
                g_acc, l_acc, m_acc = acc
                (l, m), g = value_grad(slice_mb(i))
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(a.dtype), g_acc, g)
                m_acc = jax.tree.map(jnp.add, m_acc, m)
                return (g_acc, l_acc + l, m_acc)

            # microbatch 0 runs unrolled: its aux dict gives the fori_loop
            # carry its structure, so the GA path returns the same metrics
            # keys the GA=1 path does instead of discarding them
            (l0, m0), g0 = value_grad(slice_mb(0))
            g0 = jax.tree.map(lambda g: g.astype(rt.grad_dtype), g0)
            grads, lsum, msum = jax.lax.fori_loop(
                1, tc.grad_accum, micro, (g0, l0, m0))
            grads = jax.tree.map(lambda g: g / tc.grad_accum, grads)
            loss_val = lsum / tc.grad_accum
            # token and item counts add across microbatches; everything
            # else is a per-microbatch mean
            metrics: Dict[str, Any] = {
                k: v if k in ("ntok", "expert_items") else v / tc.grad_accum
                for k, v in msum.items()}
        else:
            (loss_val, metrics), grads = jax.value_and_grad(
                loss, has_aux=True)(params)

        lr_scale = linear_warmup_cosine(opt_state["step"], tc.warmup, total)
        params, opt_state, opt_metrics = adamw_update(
            tc.opt, params, grads, opt_state, lr_scale)
        out = {"loss": loss_val, **metrics, **opt_metrics}
        return params, opt_state, out

    return train_step


def jit_train_step(step_fn, pshard, oshard, bshard):
    """The step as ``train_loop`` compiles it: sharded inputs and outputs,
    params and optimizer state donated."""
    return jax.jit(step_fn, in_shardings=(pshard, oshard, bshard),
                   out_shardings=(pshard, oshard, None),
                   donate_argnums=(0, 1))


def place_train_state(cfg: ModelConfig, plan: par.ParallelPlan, params,
                      opt_state, batch):
    """device_put existing (params, opt_state, batch) into the plan's
    shardings -> (params, opt_state, batch, pshard, oshard).

    The equivalence tests and benchmarks all need this exact layout (m/v
    shard like params, scalar step replicated, batch per batch_specs);
    one helper keeps the convention from drifting between call sites.
    Call under ``par.use_mesh(plan.mesh)``.
    """
    pshard = par.param_shardings(cfg, plan, jax.eval_shape(lambda: params))
    oshard = {"m": pshard, "v": pshard,
              "step": par.fitted(plan, par.P(), ())}
    return (jax.device_put(params, pshard),
            jax.device_put(opt_state, oshard),
            jax.device_put(batch, par.batch_specs(cfg, plan, batch)),
            pshard, oshard)


def shard_train_state(cfg: ModelConfig, plan: par.ParallelPlan, key,
                      rt: Runtime):
    """Initialize params + opt state directly into their shardings."""
    def init(k):
        p = tfm.init_params(cfg, k)
        if rt.param_dtype != jnp.float32:
            # storage-dtype policies (e.g. a pure-bf16 Runtime); the bf16
            # mixed-precision policy keeps f32 master params so this is
            # a no-op there
            p = jax.tree.map(
                lambda x: x.astype(rt.param_dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, p)
        return p

    pshapes = jax.eval_shape(init, key)
    pshard = par.param_shardings(cfg, plan, pshapes)

    params = jax.jit(init, out_shardings=pshard)(key)
    oshapes = jax.eval_shape(init_opt_state, pshapes)
    oshard = {"m": pshard, "v": pshard,
              "step": par.fitted(plan, par.P(), ())}
    opt_state = jax.jit(init_opt_state, out_shardings=oshard)(params)
    return params, opt_state, pshard, oshard


def _restore_state(tc: TrainConfig, params, opt_state, pshard, oshard):
    """Resume support: restore (params, opt_state, meta) from the newest
    checkpoint in ``tc.ckpt_dir`` that passes CRC validation, or return
    the freshly initialized state when none exists."""
    from repro import checkpointing as ckpt_lib

    step = ckpt_lib.latest_valid_step(tc.ckpt_dir, verify=True)
    if step is None:
        return params, opt_state, 0, {}
    tree = ckpt_lib.restore_checkpoint(
        tc.ckpt_dir, step, {"params": params, "opt": opt_state},
        shardings={"params": pshard, "opt": oshard})
    meta = ckpt_lib.load_meta(tc.ckpt_dir, step)
    start = int(meta.get("step", step))
    print(f"[resume] restored step {start} from {tc.ckpt_dir}", flush=True)
    return tree["params"], tree["opt"], start, meta


def train_loop(cfg: ModelConfig, plan: par.ParallelPlan, rt: Runtime,
               tc: TrainConfig, batches, key=None,
               hooks: Optional[Callable] = None, fault_plan=None,
               telemetry: tel.Recorder = tel.NULL,
               drift: Optional[tel.DriftMonitor] = None):
    """Full driver: init, jit with shardings, iterate, log, checkpoint.

    ``tc.resume`` restores params/opt_state/PRNG/data position from the
    newest *valid* checkpoint in ``tc.ckpt_dir`` (CRC-verified; corrupt
    or partial saves are skipped), and the resumed run consumes the data
    stream from the restored position — a killed-and-resumed run is
    bit-identical to an uninterrupted one.  ``fault_plan``
    (:class:`repro.resilience.FaultPlan`) injects crashes (raised as
    ``SimulatedFailure`` before the scheduled step runs), straggler
    sleeps, and transient checkpoint-I/O errors (retried once).

    ``telemetry`` records per-step ``train/step`` spans (with
    ``train/dispatch``/``train/data``/``train/ckpt``/``train/wait``
    children) and window gauges (wps, steps/s, goodput fraction,
    measured MFU when ``drift`` carries the flops budget);
    ``drift`` (a :class:`repro.telemetry.DriftMonitor` built from the
    resolved strategy's ``StepReport.decomposition()``) gets one
    measured window per logging window.
    """
    from repro import checkpointing as ckpt_lib

    key = key if key is not None else jax.random.PRNGKey(0)
    with par.use_mesh(plan.mesh):
        params, opt_state, pshard, oshard = shard_train_state(cfg, plan, key, rt)
        start_step = 0
        if tc.resume and tc.ckpt_dir:
            params, opt_state, start_step, meta = _restore_state(
                tc, params, opt_state, pshard, oshard)
            if meta.get("prng") is not None:
                # save() wrote the raw key data; rebuild the key with the
                # same impl so a resumed run draws the bits an
                # uninterrupted one would (previously this was silently
                # dropped and resume re-used the caller's key object)
                kd = jnp.asarray(np.asarray(meta["prng"], dtype=np.uint32))
                if jax.dtypes.issubdtype(key.dtype, jax.dtypes.prng_key):
                    key = jax.random.wrap_key_data(
                        kd, impl=jax.random.key_impl(key))
                else:
                    key = kd
        step_fn = make_train_step(cfg, rt, tc)

        checkpointer = None
        if tc.ckpt_every and tc.ckpt_async:
            checkpointer = ckpt_lib.AsyncCheckpointer(
                tc.ckpt_dir, max_in_flight=tc.ckpt_max_in_flight,
                keep=tc.ckpt_keep)

        def save(step, params, opt_state):
            kd = (jax.random.key_data(key)
                  if jax.dtypes.issubdtype(key.dtype, jax.dtypes.prng_key)
                  else key)
            meta = {"step": step, "batches_consumed": step,
                    "prng": np.asarray(kd).tolist()}
            tree = {"params": params, "opt": opt_state}
            # one retry: the injected checkpoint-I/O faults are transient
            for attempt in range(2):
                try:
                    if fault_plan is not None:
                        fault_plan.ckpt_io_check(step)
                    if checkpointer is not None:
                        checkpointer.save(step, tree, meta=meta)
                    else:
                        ckpt_lib.save_checkpoint(tc.ckpt_dir, step, tree,
                                                 meta=meta)
                        if tc.ckpt_keep:
                            ckpt_lib.gc_checkpoints(tc.ckpt_dir,
                                                    keep=tc.ckpt_keep)
                    return
                except ckpt_lib.CheckpointIOError as e:
                    if attempt:
                        raise
                    print(f"[ckpt] transient I/O error at step {step}, "
                          f"retrying: {e}", flush=True)

        # data-pipeline position: a resumed run must see exactly the
        # batches an uninterrupted run would have seen from this step
        if start_step and hasattr(batches, "at"):
            it = iter(batches.at(start_step))
        else:
            it = iter(batches)
            for _ in range(start_step):
                next(it)
        first = next(it)
        bshard = par.batch_specs(cfg, plan, first)
        jstep = jit_train_step(step_fn, pshard, oshard, bshard)

        history = []
        t0 = time.time()
        t_step_ema = 0.0
        batch = first
        tokens_per_step = int(np.asarray(first["labels"]).size)
        # Straggler injection scales a *measured* step time, so only a
        # fault plan that actually schedules stragglers justifies the
        # every-step host sync; crash/ckpt-io-only plans (and plain
        # runs) sync just on logging windows and keep dispatch async.
        sync_every_step = fault_plan is not None and any(
            e.kind == "straggler" for e in fault_plan.events)
        win_t0 = time.time()
        win_start = start_step
        win_ckpt = win_dispatch = win_wait = win_data = 0.0
        try:
            for step in range(start_step, tc.steps):
              with telemetry.span("train/step", step_num=step):
                if fault_plan is not None:
                    fault_plan.check_crash(step)
                    mult = fault_plan.delay_multiplier(step)
                    if mult > 1.0 and t_step_ema > 0.0:
                        time.sleep((mult - 1.0) * t_step_ema)
                t1 = time.time()
                with telemetry.span("train/dispatch"):
                    params, opt_state, metrics = jstep(params, opt_state,
                                                       batch)
                t2 = time.time()
                win_dispatch += t2 - t1
                if step + 1 < tc.steps:
                    with telemetry.span("train/data"):
                        batch = next(it)
                win_data += time.time() - t2
                if tc.ckpt_every and (step + 1) % tc.ckpt_every == 0:
                    t3 = time.time()
                    with telemetry.span("train/ckpt", step=step + 1):
                        save(step + 1, params, opt_state)
                    win_ckpt += time.time() - t3
                log_now = (step + 1) % tc.log_every == 0 or \
                    step == start_step
                if sync_every_step or log_now:
                    t4 = time.time()
                    with telemetry.span("train/wait"):
                        jax.block_until_ready(metrics["loss"])
                    win_wait += time.time() - t4
                    dt_step = time.time() - t1
                    t_step_ema = dt_step if t_step_ema == 0.0 else \
                        0.7 * t_step_ema + 0.3 * dt_step
                if log_now:
                    m = {k: float(v) for k, v in metrics.items()
                         if getattr(v, "ndim", 0) == 0}
                    dt = time.time() - t0
                    m["steps_per_s"] = (step + 1 - start_step) / dt
                    history.append({"step": step + 1, **m})
                    print(f"step {step+1:5d}  loss {m.get('loss', float('nan')):.4f}"
                          f"  gnorm {m.get('grad_norm', float('nan')):.3f}"
                          f"  {m['steps_per_s']:.2f} it/s", flush=True)
                    n_win = step + 1 - win_start
                    dt_win = time.time() - win_t0
                    if n_win > 0 and dt_win > 0:
                        telemetry.gauge("train/wps",
                                        tokens_per_step * n_win / dt_win)
                        telemetry.gauge("train/steps_per_s",
                                        n_win / dt_win)
                        telemetry.gauge("train/goodput_frac",
                                        max(0.0, 1.0 - win_ckpt / dt_win))
                        if drift is not None:
                            fl = drift.meta.get("model_flops_per_step")
                            peak = drift.meta.get("cluster_peak_flops")
                            if fl and peak:
                                telemetry.gauge(
                                    "train/mfu",
                                    fl / (dt_win / n_win) / peak)
                            drift.observe(
                                {"step": dt_win / n_win,
                                 "dispatch": win_dispatch / n_win,
                                 "wait": win_wait / n_win,
                                 "data": win_data / n_win},
                                n_steps=n_win)
                    win_t0 = time.time()
                    win_start = step + 1
                    win_ckpt = win_dispatch = win_wait = win_data = 0.0
                    if hooks:
                        hooks(step + 1, params, m)
        finally:
            if checkpointer is not None:
                checkpointer.close()
        return params, opt_state, history
