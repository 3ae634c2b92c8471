"""Training job: the program's compiled train step, driven for a window.

Set-up builds one object, the compiled step with its state, and drives it
from the seed through the traffic file's checked steps; the window then
goes on with that same step and state.  The loop keeps ``train_loop``'s
order (dispatch the step, then fetch the next batch) and never waits for
the step it just dispatched: it waits only for the step ``DEPTH`` before,
so that the host cannot run more than ``DEPTH`` steps ahead of the chip
and the window ends within a few steps of its deadline.

The configuration file names its reference module (``"reference"``,
found by ``reference_base.for_config``), which builds the program's
configuration from the file and holds the layer equations, the weights'
layout and initialisation and the model's counts of operations.  The
weights are the benchmark's (the reference's ``init_weights`` in the
program's sharding, one jitted call); the optimizer state, the step, the
plan and the batches are the program's.  After the window the program's
state is freed and the plain reference repeats the checked steps.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import gc
import os
import shutil
import tempfile
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from chip import reference_base as base

DEPTH = 2                 # steps the host may run ahead of the chip
TRACE_SECONDS = 3.0       # the traced part at the end of a --trace 1 window
KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
           "flash_attention_bwd_dkv", "rmsnorm_fwd", "rmsnorm_bwd")


def log(msg: str) -> None:
    print(msg, flush=True)


def _shape_tree(tree):
    return jax.tree.map(lambda x: tuple(x.shape), tree)


def _spread(mesh, shape, axis="x"):
    """Shard a reference array over a 1-D mesh on its largest dividing axis."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = mesh.shape[axis]
    dims = [i for i in np.argsort(shape)[::-1] if shape[i] % n == 0]
    spec = [None] * len(shape)
    if dims:
        spec[dims[0]] = axis
    return NamedSharding(mesh, P(*spec))


class TrainJob:
    """One cell's program objects: plan, compiled step, data and weights."""

    def __init__(self, config: dict, traffic: dict, chips: int,
                 fault: Optional[str] = None):
        from repro import strategy as strategy_lib
        from repro.configs import ShapeConfig
        from repro.core import parallel as par
        from repro.optim import AdamWConfig
        from repro.train.trainer import TrainConfig, make_train_step

        self.config, self.traffic, self.chips = config, traffic, chips
        self.model = base.for_config(config)
        self.cfg, self.changed = self.model.program_config(config)
        self.seq, self.batch = traffic["seq_len"], traffic["global_batch"]
        self.tokens_per_step = self.seq * self.batch
        topo = strategy_lib.host_topology(n_devices=chips)
        shape = ShapeConfig(traffic["name"], self.seq, self.batch, "train")
        strat, _ = strategy_lib.resolve(traffic["strategy"], self.cfg, topo,
                                        shape)
        if strat.grad_accum != traffic["grad_accum"]:
            raise ValueError(f"strategy {traffic['strategy']} accumulates "
                             f"{strat.grad_accum}, the traffic file says "
                             f"{traffic['grad_accum']}")
        self.plan = strat.to_plan(self.cfg, topo, shape)
        self.rt = par.make_runtime(self.cfg, self.plan, shape,
                                   **traffic["runtime"])
        if self.rt.param_dtype != jnp.float32:
            raise ValueError("the train job keeps float32 master weights")
        opt, sched = traffic["optimizer"], traffic["schedule"]
        self.tc = TrainConfig(
            steps=sched["total"], warmup=sched["warmup"],
            grad_accum=traffic["grad_accum"],
            opt=AdamWConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                            eps=opt["eps"], weight_decay=opt["weight_decay"],
                            grad_clip=opt["grad_clip"]))
        self.par = par
        from repro.models import transformer as tfm
        pshapes = jax.eval_shape(lambda k: tfm.init_params(self.cfg, k),
                                 jax.random.PRNGKey(0))
        want = self.model.weight_shapes(config)
        if _shape_tree(pshapes) != jax.tree.map(
                tuple, want, is_leaf=lambda x: isinstance(x, tuple)):
            raise ValueError("the program's parameter layout is not the "
                             "reference's weight layout")
        self.pshard = par.param_shardings(self.cfg, self.plan, pshapes)
        self.oshard = {"m": self.pshard, "v": self.pshard,
                       "step": par.fitted(self.plan, par.P(), ())}
        if fault == "no_exchange" and self.rt.pipeline_axis:
            self.step_fn = no_tp_exchange(make_train_step(self.cfg, self.rt,
                                                          self.tc))
        elif fault == "no_exchange":
            # the step inside a shard_map, where the plan's constraints and
            # kernel sharding do not apply
            local = dataclasses.replace(self.rt, constrain=None,
                                        gather_params=None, kernel_shard=None)
            self.step_fn = no_exchange(make_train_step(self.cfg, local,
                                                       self.tc), self)
        else:
            self.step_fn = plant(make_train_step(self.cfg, self.rt,
                                                 self.tc), fault)
        from repro.optim import init_opt_state
        self.make_weights = jax.jit(
            functools.partial(self.model.init_weights, config),
            out_shardings=self.pshard)
        self.init_opt = jax.jit(init_opt_state, out_shardings=self.oshard)
        self.compiled = None
        self.ref = None
        self.kernels = ()
        self.mesh_shape = dict(self.plan.mesh.shape)

    # -- state -------------------------------------------------------------

    def corpus(self, seed: int) -> np.ndarray:
        """The cell's token corpus: ``corpus_tokens`` Zipf-distributed
        tokens (rank r drawn with weight 1/r over the vocabulary) in one
        draw from the seed."""
        vocab = self.cfg.vocab_size
        weights = 1.0 / np.arange(1, vocab + 1, dtype=np.float64)
        rng = np.random.default_rng(seed)
        return rng.choice(vocab, size=self.traffic["corpus_tokens"],
                          p=weights / weights.sum()).astype(np.uint32)

    def batches(self, corpus: np.ndarray):
        """The program's Batcher over the corpus as a token file
        (``BinTokenSource``), as a job reads its data; the Batcher wraps
        round the file."""
        from repro.data import Batcher, BinTokenSource
        fd, path = tempfile.mkstemp(prefix="bench_corpus_", suffix=".bin")
        with os.fdopen(fd, "wb") as f:
            f.write(corpus.tobytes())
        try:
            source = BinTokenSource(path, dtype=np.uint32)
        finally:
            os.unlink(path)       # the memory map keeps the data
        return iter(Batcher(source, self.seq, self.batch))

    def init_state(self, seed: int):
        """The benchmark's weights in the program's sharding, and the
        program's optimizer state -> (params, opt_state)."""
        with self.par.use_mesh(self.plan.mesh):
            params = self.make_weights(base.seed_key(seed))
            return params, self.init_opt(params)

    def compile(self, params, opt_state, batch):
        from repro.train.trainer import jit_train_step
        par, plan = self.par, self.plan
        with par.use_mesh(plan.mesh):
            bshard = par.batch_specs(self.cfg, plan, batch)
            jstep = jit_train_step(self.step_fn, self.pshard, self.oshard,
                                   bshard)
            self.compiled = jstep.lower(params, opt_state, batch).compile()
        text = self.compiled.as_text()
        self.kernels = tuple(k for k in KERNELS if k in text)
        self.memory = self.compiled.memory_analysis()
        b1 = self.traffic["optimizer"]["b1"]
        # m after one step is (1 - b1) times the clipped gradient
        self.grad_norms = jax.jit(lambda o: base.leaf_norms(
            jax.tree.map(lambda m: m / (1 - b1), o["m"])))
        self.update_norms = jax.jit(lambda p, k: base.leaf_norms(
            jax.tree.map(jnp.subtract, p,
                         self.model.init_weights(self.config, k))))

    # -- the checked steps -------------------------------------------------

    def check_steps(self, seed: int):
        """Set-up: weights, compile, the checked steps through the compiled
        step -> (params, opt_state, next batch, batch iterator, readings,
        corpus)."""
        n = self.traffic["check_steps"]
        key = base.seed_key(seed)
        params, opt_state = self.init_state(seed)
        corpus = self.corpus(seed)
        it = self.batches(corpus)
        batch = next(it)
        if self.compiled is None:
            self.compile(params, opt_state, batch)
        losses, first = [], None
        with self.par.use_mesh(self.plan.mesh):
            for i in range(n):
                params, opt_state, met = self.compiled(params, opt_state,
                                                       batch)
                losses.append(met["loss"])
                if i == 0:
                    first = self.grad_norms(opt_state)
                batch = next(it)
            change = self.update_norms(params, key)
        readings = {
            "losses": [float(x) for x in losses],
            "grad_norms": {k: float(x) for k, x in first.items()},
            "update_norms": {k: float(x) for k, x in change.items()}}
        return params, opt_state, batch, it, readings, corpus

    def reference(self, seed: int, corpus, dot_dtype=None, fault=None):
        """The plain reference over the checked steps' rows, which it cuts
        from the corpus itself, next-token targets included."""
        if self.ref is None:
            shardings = None
            if self.chips > 1:
                from jax.sharding import Mesh, NamedSharding
                from jax.sharding import PartitionSpec as P
                mesh = Mesh(np.array(jax.devices()[:self.chips]), ("x",))
                w_sh = jax.tree.map(
                    lambda s: _spread(mesh, s),
                    self.model.weight_shapes(self.config),
                    is_leaf=lambda x: isinstance(x, tuple))
                rows = P("x") if self.batch % self.chips == 0 else P()
                b_sh = (NamedSharding(mesh, rows), NamedSharding(mesh, rows))
                shardings = (w_sh, b_sh)
            self.ref = self.model.Reference(
                self.config, self.traffic["optimizer"],
                self.traffic["schedule"], shardings)
        rows = base.rows(corpus, self.seq, self.batch,
                         self.traffic["check_steps"])
        return self.ref.run(seed, rows, dot_dtype=dot_dtype, fault=fault)

    # -- the window --------------------------------------------------------

    def window(self, params, opt_state, batch, it, seconds: float,
               trace_dir: Optional[str] = None):
        """Drive the compiled step for ``seconds``; -> record."""
        ann = jax.profiler.TraceAnnotation
        compiles = []

        def on_event(name, *_a, **_k):
            if name.startswith("/jax/core/compile/"):
                compiles.append(name)
        jax.monitoring.register_event_duration_secs_listener(on_event)

        inflight = collections.deque()
        losses, batch_s = [], []
        steps, traced_from, t_tr0 = 0, None, None
        t0 = time.perf_counter()
        deadline = t0 + seconds
        trace_at = deadline - TRACE_SECONDS if trace_dir else None
        try:
            with self.par.use_mesh(self.plan.mesh):
                while True:
                    if trace_at is not None and traced_from is None and \
                            time.perf_counter() >= trace_at:
                        jax.block_until_ready(inflight[-1] if inflight
                                              else params)
                        jax.profiler.start_trace(trace_dir)
                        traced_from, t_tr0 = steps, time.perf_counter()
                    with ann("bench/dispatch"):
                        params, opt_state, met = self.compiled(
                            params, opt_state, batch)
                    steps += 1
                    losses.append(met["loss"])
                    inflight.append(met["loss"])
                    tb = time.perf_counter()
                    with ann("bench/batch"):
                        batch = next(it)
                    batch_s.append(time.perf_counter() - tb)
                    if time.perf_counter() >= deadline:
                        break
                    if len(inflight) > DEPTH:
                        with ann("bench/sync"):
                            inflight.popleft().block_until_ready()
                with ann("bench/sync"):
                    jax.block_until_ready((params, opt_state, met))
                t1 = time.perf_counter()
                if traced_from is not None:
                    jax.profiler.stop_trace()
        finally:
            jax.monitoring.unregister_event_duration_listener(on_event)
        losses = np.asarray([float(x) for x in losses])
        rec = {
            "steps": steps, "window_s": t1 - t0, "t0": t0,
            "tokens": steps * self.tokens_per_step,
            "tokens_per_s_chip": steps * self.tokens_per_step
            / (t1 - t0) / self.chips,
            "failed": int(np.sum(~np.isfinite(losses))),
            "batch_s": batch_s, "compiles_in_window": len(compiles),
            "last_loss": float(losses[-1]),
        }
        if traced_from is not None:
            rec.update(traced_steps=steps - traced_from,
                       traced_window_s=t1 - t_tr0)
        return params, opt_state, rec


def plant(step_fn, fault: Optional[str]):
    """The step with one of the faults the comparison must catch planted in
    it (None: the step as the program makes it)."""
    if fault is None:
        return step_fn
    if fault == "unchanged":
        def step(params, opt_state, batch):
            return params, opt_state, step_fn(params, opt_state, batch)[2]
        return step
    if fault == "half_batch":
        def step(params, opt_state, batch):
            labels = batch["labels"]
            B, S = labels.shape
            mask = jnp.zeros((B, S), bool)
            mask = mask.at[:B // 2].set(True) if B > 1 else \
                mask.at[:, :S // 2].set(True)
            return step_fn(params, opt_state,
                           dict(batch, labels=jnp.where(mask, labels, -1)))
        return step
    if fault == "unshifted_labels":
        def step(params, opt_state, batch):
            return step_fn(params, opt_state,
                           dict(batch, labels=batch["tokens"]))
        return step
    raise ValueError(f"unknown fault {fault!r}")


def no_exchange(step_fn, job):
    """The step with the exchange of gradients between chips left out: each
    chip runs the whole step on its own rows and keeps, of the updated
    state, the block that its shard of the plan holds."""
    from jax.sharding import PartitionSpec as P
    from repro.core.compat import shard_map
    mesh = job.plan.mesh
    specs = (jax.tree.map(lambda s: s.spec, job.pshard),
             jax.tree.map(lambda s: s.spec, job.oshard))

    def own_block(x, spec):
        for dim, axes in enumerate(spec):
            for ax in (axes if isinstance(axes, tuple) else (axes,)):
                if ax is None or mesh.shape[ax] == 1:
                    continue
                size = x.shape[dim] // mesh.shape[ax]
                x = jax.lax.dynamic_slice_in_dim(
                    x, jax.lax.axis_index(ax) * size, size, dim)
        return x

    def body(params, opt_state, batch):
        params, opt_state, met = step_fn(params, opt_state, batch)
        return (jax.tree.map(own_block, params, specs[0]),
                jax.tree.map(own_block, opt_state, specs[1]), met)

    def step(params, opt_state, batch):
        return shard_map(body, mesh, (P(), P(), P(job.plan.dp)),
                         specs + (P(),))(params, opt_state, batch)
    return step


def no_tp_exchange(step_fn):
    """The pipelined step with the exchange between the chips of a stage
    left out: the tensor-parallel all-reduce of each layer's attention and
    FFN output (``tp_reduce_out``) is skipped while the step is traced, so
    each chip adds only its own heads' and hidden units' part to the
    residual stream."""
    from repro.models import transformer as tfm

    def step(params, opt_state, batch):
        reduce_out = tfm.tp_reduce_out
        tfm.tp_reduce_out = lambda x, rt: x
        try:
            return step_fn(params, opt_state, batch)
        finally:
            tfm.tp_reduce_out = reduce_out
    return step


def memory_peak(devices) -> Optional[int]:
    """The allocator's peak bytes in use on the fullest chip; each chip's
    whole memory statistics go to an earlier line."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        log(f"[memory] device {d.id} stats {stats}")
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run(ctx) -> dict:
    """One benchmark run of a training cell; -> the harness's record."""
    job = TrainJob(ctx.config, ctx.traffic, ctx.chips, ctx.fault)
    for k, (was, now) in job.changed.items():
        log(f"[config] {k}: registry {was!r} -> {now!r} (as the config "
            "file states)")
    log(f"[plan] {ctx.traffic['strategy']} mesh {job.mesh_shape} seq "
        f"{job.seq} x batch {job.batch}, {job.tokens_per_step} tokens/step")
    params, opt_state, batch, it, got, corpus = job.check_steps(ctx.seed)
    mem = job.memory
    log(f"[step] kernels {list(job.kernels)}; compiled step: arguments "
        f"{mem.argument_size_in_bytes / 2**30:.3f} GiB, outputs "
        f"{mem.output_size_in_bytes / 2**30:.3f} GiB (aliased "
        f"{mem.alias_size_in_bytes / 2**30:.3f} GiB), temporaries "
        f"{mem.temp_size_in_bytes / 2**30:.3f} GiB, code "
        f"{mem.generated_code_size_in_bytes / 2**30:.3f} GiB")
    log(f"[check] program losses {got['losses']}")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if ctx.trace else None
    try:
        params, opt_state, rec = job.window(params, opt_state, batch, it,
                                            ctx.seconds, trace_dir)
        rec["setup_s"] = rec["t0"] - ctx.t_start
        rec["memory_peak_bytes"] = memory_peak(ctx.devices)
        log(f"[window] {rec['steps']} steps in {rec['window_s']:.3f} s, "
            f"compilations in window {rec['compiles_in_window']}, "
            f"last loss {rec['last_loss']:.4f}")
        log(f"[memory] peak bytes in use on the fullest chip "
            f"{rec['memory_peak_bytes']}")
        del params, opt_state, batch, it
        job.compiled = None
        gc.collect()
        if trace_dir:
            from chip import trace as trace_lib
            path = trace_lib.find_xplane(trace_dir)
            rec["trace"] = trace_lib.reduce(trace_lib.load(path),
                                            n_devices=ctx.chips)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    t_ref = time.perf_counter()
    want = job.reference(ctx.seed, corpus)
    log(f"[check] reference losses {want['losses']} "
        f"({time.perf_counter() - t_ref:.1f} s)")
    rec["compare"] = base.compare(got, want)
    rec["flops_per_token"] = job.model.train_flops_per_token(ctx.config,
                                                             job.seq)
    rec["attention_cost"] = job.model.flash_attention_cost(
        ctx.config, job.seq, job.batch)
    rec["tokens_per_step"] = job.tokens_per_step
    rec["e2e"] = {"train_tokens_per_s": rec["tokens_per_s_chip"],
                  "setup_s": rec["setup_s"]}
    rec["attempted"] = rec["steps"]
    return rec


def calibrate(config: dict, traffic: dict, chips: int, seeds, control):
    """The readings a training cell's limits are set from, one row each:
    for every seed the program's checked steps against the reference
    (``program``, the lower readings); for each seed of ``control`` the
    control, the reference with every matrix product in float8_e4m3fn put
    in the program's place (``control_fp8``), half the batch left out of
    the reference put in its place (``fault_half_batch``) and, on several
    chips, the program with the exchange between chips left out
    (``fault_no_exchange``): the upper readings.  A state left unchanged
    reads 1 by construction and needs no run."""
    job = TrainJob(config, traffic, chips)
    faulty = TrainJob(config, traffic, chips, "no_exchange") \
        if chips > 1 and control else None

    def row(kind, seed, got, want):
        gaps = base.compare(got, want)
        keys = ("loss_gap", "grad_norm_gap", "update_norm_gap")
        return {"kind": kind, "seed": seed,
                **{k: gaps[k][0] for k in keys},
                "at": {k: gaps[k][1] for k in keys},
                "left_out": gaps["left_out"],
                "losses": got["losses"], "ref_losses": want["losses"]}

    for seed in seeds:
        params, opt_state, batch, it, got, corpus = job.check_steps(seed)
        del params, opt_state, batch, it
        want = job.reference(seed, corpus)
        yield row("program", seed, got, want)
        if seed not in control:
            continue
        yield row("control_fp8", seed, job.reference(
            seed, corpus, dot_dtype="float8_e4m3fn"), want)
        yield row("fault_half_batch", seed,
                  job.reference(seed, corpus, fault="half_batch"), want)
        if faulty is not None:
            params, opt_state, batch, it, got, _ = faulty.check_steps(seed)
            del params, opt_state, batch, it
            yield row("fault_no_exchange", seed, got, want)
