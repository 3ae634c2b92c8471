#!/usr/bin/env python3
"""On-chip smoke run: qwen3-0.6b at its published widths, through the
repo's normal entry points, on a TPU.

    python chip_smoke.py               # one chip: kernels, training, serving
    python chip_smoke.py --four-chip   # four chips: sharded training only

One chip runs three phases:

* kernels -- every Pallas kernel against its jnp oracle on a small input
  at qwen3-0.6b's head widths (rwkv6-1.6b's for wkv6);
* train   -- a few AdamW steps of ``train_loop`` under ``fsdp_bf16`` with
  the Pallas kernels, on synthetic data from ``--seed``.  The losses must
  be finite and fall, and the compiled step must contain every expected
  kernel as a ``tpu_custom_call`` (the jnp fallbacks are silent);
* serve   -- the paged ``ServeEngine`` with flash-decode answers a few
  requests, and the Pallas path's first-decode-step logits must match the
  jnp path's.

``--four-chip`` runs the same training steps under ``fsdp_bf16`` on four
chips and under ``fsdp_tp2_pp2_mb4_1f1b_bf16`` (pipeline x tensor), and
compares each, step by step on the same batches, with the one-chip run of
the same shape in this process.  It also checks that the sharded state
really sits on all four devices.

Times printed here are those of a smoke run, not a benchmark.  Any failed
check exits non-zero; so does a host whose JAX finds no TPU.  The last
line of stdout is the JSON contract line and is printed only on success.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.devices import enable_compile_cache  # noqa: E402

ARCH = "qwen3-0.6b"
SEQ_LEN, BATCH, STEPS = 256, 4, 5      # fits 16 GB with f32 master + AdamW
PROMPT_LEN, N_NEW, N_REQUESTS = 128, 32, 4
TRAIN_SPEC = "fsdp_bf16"
FOUR_CHIP_SPECS = ("fsdp_bf16", "fsdp_tp2_pp2_mb4_1f1b_bf16")
TRAIN_KERNELS = {"flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv", "rmsnorm_fwd", "rmsnorm_bwd"}
DECODE_KERNELS = {"flash_decode", "rmsnorm_fwd"}

# Tolerances.  Kernel checks: max abs error against the f32 oracle over
# the oracle's largest |value| (or 1), inputs ~N(0, 0.25).  Serving: the
# Pallas decode step's logits within LOGIT_RTOL of the largest |logit|.
# Sharded training: every layout starts from the same params and sees the
# same batches, but reorders bf16 sums.  For the first STRICT_STEPS steps
# the runs are still at (nearly) the same params, so each step's loss may
# move by LOSS_ATOL and its grad norm by GNORM_RTOL.  After that the params
# drift apart: AdamW divides each gradient entry by its own running RMS,
# which turns rounding noise in small entries into full-size updates, and
# the grad norms drift with them.  Later losses are held to LOSS_BAND, less
# than the loss falls in one step at this learning rate (0.1-0.5 nats), as
# a wrong sharded update would move it.
KERNEL_RTOL = {"float32": 3e-2, "bfloat16": 5e-2}
LOSS_ATOL, GNORM_RTOL, STRICT_STEPS = 2e-2, 2e-2, 2
LOSS_BAND = 0.1
LOGIT_RTOL = 5e-2


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# kernels vs oracles
# ---------------------------------------------------------------------------

def kernel_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))

    def rnd(shape, dtype=jnp.float32):
        return (0.5 * jax.random.normal(next(keys), shape)).astype(dtype)

    def err(a, b):
        b = b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                     / jnp.maximum(1.0, jnp.max(jnp.abs(b))))

    def report(name, dtype, errs):
        worst = max(errs)
        print(f"[kernels] {name:<16} {jnp.dtype(dtype).name:<8} "
              f"max rel err {worst:.3e}", flush=True)
        check(worst <= KERNEL_RTOL[jnp.dtype(dtype).name],
              f"{name} ({jnp.dtype(dtype).name}) off its oracle by {worst}")

    def hi():
        return jax.default_matmul_precision("highest")

    for dt in (jnp.float32, jnp.bfloat16):
        # flash attention fwd + grads, 16/8 heads of 128, S spans 4 blocks
        q, k, v = rnd((1, 1024, 16, 128), dt), rnd((1, 1024, 8, 128), dt), \
            rnd((1, 1024, 8, 128), dt)
        cot = rnd(q.shape, dt)

        def loss(fn, q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32) * cot)

        f_ker = jax.jit(jax.value_and_grad(
            lambda q, k, v: loss(ops.attention, q, k, v), (0, 1, 2)))
        with hi():
            f_ref = jax.jit(jax.value_and_grad(
                lambda q, k, v: loss(ref.attention_ref, *(
                    a.astype(jnp.float32) for a in (q, k, v))), (0, 1, 2)))
            f_out = jax.jit(lambda q, k, v: ref.attention_ref(
                *(a.astype(jnp.float32) for a in (q, k, v))))
            _, g_ref = f_ref(q, k, v)
            o_ref = f_out(q, k, v)
        _, g_ker = f_ker(q, k, v)
        o_ker = jax.jit(ops.attention)(q, k, v)
        report("flash_attention", dt,
               [err(o_ker, o_ref)] + [err(a, b) for a, b in zip(g_ker, g_ref)])

        # rmsnorm fwd + grads at d_model 1024
        x, s, gx = rnd((4, 256, 1024), dt), rnd((1024,)), rnd((4, 256, 1024))
        n_ker = jax.jit(jax.value_and_grad(lambda x, s: jnp.sum(
            ops.rmsnorm(x, s).astype(jnp.float32) * gx), (0, 1)))
        with hi():
            n_ref = jax.jit(jax.value_and_grad(lambda x, s: jnp.sum(
                ref.rmsnorm_ref(x.astype(jnp.float32), s) * gx), (0, 1)))
            (_, (dx_r, ds_r)) = n_ref(x, s)
            y_ref = jax.jit(lambda x, s: ref.rmsnorm_ref(
                x.astype(jnp.float32), s))(x, s)
        _, (dx_k, ds_k) = n_ker(x, s)
        y_ker = jax.jit(ops.rmsnorm)(x, s)
        report("rmsnorm", dt,
               [err(y_ker, y_ref), err(dx_k, dx_r), err(ds_k, ds_r)])

        # flash-decode over a paged pool of 16-token blocks, ragged contexts
        P, nb, bs = 64, 8, 16
        qd = rnd((4, 1, 16, 128), dt)
        kp, vp = rnd((P, 8, bs, 128), dt), rnd((P, 8, bs, 128), dt)
        tbl = jax.random.permutation(next(keys), P)[:4 * nb].reshape(4, nb)
        ctx = jnp.asarray([1, 40, 100, nb * bs], jnp.int32)
        tbl = jnp.where(jnp.arange(nb)[None] < -(-ctx // bs)[:, None],
                        tbl, -1).astype(jnp.int32)
        d_ker = jax.jit(ops.paged_decode_attention)(qd, kp, vp, tbl, ctx)
        with hi():
            d_ref = jax.jit(ref.paged_attention_ref)(
                qd.astype(jnp.float32), kp.astype(jnp.float32),
                vp.astype(jnp.float32), tbl, ctx)
        report("flash_decode", dt, [err(d_ker, d_ref)])

    # wkv6 forward, rwkv6-1.6b's 32 heads of 64, chunk 64 (f32 recurrence)
    r, kk, vv = (rnd((1, 256, 32, 64)) for _ in range(3))
    w = jnp.exp(-jnp.exp(rnd((1, 256, 32, 64)) - 2.5))
    u = rnd((32, 64))
    y_ker, s_ker = jax.jit(ops.wkv6)(r, kk, vv, w, u)
    with hi():
        y_ref, s_ref = jax.jit(ref.wkv6_ref)(r, kk, vv, w, u,
                                             jnp.zeros((1, 32, 64, 64)))
    report("wkv6", jnp.float32, [err(y_ker, y_ref), err(s_ker, s_ref)])


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def train_phase(cfg, spec: str, n_devices: int, seed: int, steps: int,
                seq_len: int = SEQ_LEN, batch: int = BATCH) -> dict:
    """``train_loop`` under ``spec`` on the first ``n_devices`` devices."""
    import jax
    import numpy as np

    from repro import strategy as strategy_lib
    from repro import telemetry as tel
    from repro.configs import ShapeConfig
    from repro.core import parallel as par
    from repro.data import Batcher, SyntheticSource
    from repro.launch.train import runtime_overrides
    from repro.optim import AdamWConfig
    from repro.perf.hlo import pallas_kernels
    from repro.train.trainer import (TrainConfig, jit_train_step,
                                     make_train_step, train_loop)

    tag = f"[train {spec} x{n_devices}]"
    topo = strategy_lib.host_topology(n_devices=n_devices)
    shape = ShapeConfig("smoke", seq_len, batch, "train")
    strat, _ = strategy_lib.resolve(spec, cfg, topo, shape)
    plan = strat.to_plan(cfg, topo, shape)
    rt = par.make_runtime(cfg, plan, shape,
                          **runtime_overrides("pallas", seq_len))
    tc = TrainConfig(steps=steps, warmup=1, log_every=1,
                     grad_accum=strat.grad_accum, opt=AdamWConfig(lr=3e-4))
    print(f"{tag} {topo.hardware} mesh {dict(plan.mesh.shape)} "
          f"seq {seq_len} x batch {batch}", flush=True)

    def batches():
        return Batcher(SyntheticSource(cfg.vocab_size, seed=seed), seq_len,
                       batch)

    rec = tel.Recorder()
    spans = rec.add_sink(tel.InMemorySink())
    params, opt_state, history = train_loop(
        cfg, plan, rt, tc, batches(), key=jax.random.PRNGKey(seed),
        telemetry=rec)
    rec.close()

    losses = [h["loss"] for h in history]
    gnorms = [h["grad_norm"] for h in history]
    check(len(losses) == steps, f"{tag} logged {len(losses)} of {steps}")
    check(all(np.isfinite(losses)) and all(np.isfinite(gnorms)),
          f"{tag} non-finite loss or grad norm: {losses} {gnorms}")
    check(losses[-1] < losses[0], f"{tag} loss did not fall: {losses}")

    # every train/step span ends in block_until_ready (log_every=1); the
    # first one's dispatch traces and compiles the step (or loads it from
    # the persistent compile cache)
    step_s = [e["dur"] for e in spans.by_name("train/step")]
    compile_s = spans.by_name("train/dispatch")[0]["dur"]
    print(f"{tag} losses {[round(x, 4) for x in losses]}", flush=True)
    print(f"{tag} smoke timing, not a benchmark: first dispatch (trace + "
          f"compile) {compile_s:.2f}s, median later step "
          f"{statistics.median(step_s[1:]):.4f}s over {len(step_s) - 1}",
          flush=True)

    # the compiled program must hold every Pallas kernel: the model code
    # falls back to jnp without a word when a shape does not qualify
    with par.use_mesh(plan.mesh):
        first = next(iter(batches()))
        jstep = jit_train_step(
            make_train_step(cfg, rt, tc),
            jax.tree.map(lambda a: a.sharding, params),
            jax.tree.map(lambda a: a.sharding, opt_state),
            par.batch_specs(cfg, plan, first))
        kernels = pallas_kernels(
            jstep.lower(params, opt_state, first).compile().as_text())
    print(f"{tag} compiled kernels {kernels}", flush=True)
    check(TRAIN_KERNELS <= set(kernels),
          f"{tag} kernels missing: {sorted(TRAIN_KERNELS - set(kernels))}")

    # where the state lives: every device holds a share, none holds it all
    per_dev = {}
    total = 0
    for leaf in jax.tree.leaves((params, opt_state)):
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) + \
                sh.data.nbytes
    print(f"{tag} state GiB per device "
          f"{ {d: round(b / 2**30, 3) for d, b in sorted(per_dev.items())} }"
          f" of {total / 2**30:.3f} GiB", flush=True)
    check(len(per_dev) == n_devices and min(per_dev.values()) > 0,
          f"{tag} state sits on {len(per_dev)} of {n_devices} devices")
    if n_devices > 1:
        check(max(per_dev.values()) < total,
              f"{tag} a device holds the whole state: not sharded")
    return {"losses": losses, "grad_norms": gnorms}


def compare_runs(name: str, got: dict, want: dict) -> None:
    for i, (a, b, ga, gb) in enumerate(zip(got["losses"], want["losses"],
                                           got["grad_norms"],
                                           want["grad_norms"])):
        dl, dg = abs(a - b), abs(ga - gb) / max(abs(gb), 1e-6)
        print(f"[compare {name}] step {i + 1} loss {a:.5f} vs {b:.5f} "
              f"(|d| {dl:.2e}) grad norm {ga:.5f} vs {gb:.5f} "
              f"(rel {dg:.2e})", flush=True)
        if i < STRICT_STEPS:
            check(dl <= LOSS_ATOL, f"{name} step {i + 1} loss off by {dl}")
            check(dg <= GNORM_RTOL,
                  f"{name} step {i + 1} grad norm off by {dg}")
        else:
            check(dl <= LOSS_BAND, f"{name} step {i + 1} loss off by {dl}")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def serve_phase(cfg, seed: int, prompt_len: int = PROMPT_LEN,
                n_new: int = N_NEW, n_requests: int = N_REQUESTS) -> None:
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import single_device_runtime
    from repro.models import transformer as tfm
    from repro.perf.hlo import pallas_kernels
    from repro.serve import ServeEngine, init_paged_cache

    key = jax.random.PRNGKey(seed)
    params = tfm.init_params(cfg, key)
    rt_pl = single_device_runtime("pallas")
    rt_np = single_device_runtime("jnp")
    prompts = jax.random.randint(jax.random.fold_in(key, 1),
                                 (n_requests, prompt_len), 0, cfg.vocab_size)

    engine = ServeEngine(cfg, params, rt_pl, max_len=prompt_len + n_new,
                         seed=seed, n_slots=n_requests)
    check(engine.paged_ok, "[serve] the paged engine does not apply")
    for run in ("cold", "warm"):
        t0 = time.perf_counter()
        out = jax.block_until_ready(engine.generate(prompts, n_new, key=key))
        dt = time.perf_counter() - t0
        print(f"[serve] {run}: {n_requests} requests x {n_new} new tokens "
              f"in {dt:.3f}s (smoke timing, not a benchmark; cold includes "
              f"compilation)", flush=True)
    check(out.shape == (n_requests, prompt_len + n_new),
          f"[serve] output shape {out.shape}")
    new = out[:, prompt_len:]
    check(bool(jnp.all((new >= 0) & (new < cfg.vocab_size))),
          "[serve] generated ids outside the vocabulary")
    print(f"[serve] first request's new tokens {new[0, :8].tolist()} ...",
          flush=True)

    # first decode step after the prompts, Pallas vs jnp, on one shared
    # prefilled paged cache (each request owns a contiguous block chain)
    bs = engine.block_size
    nb = -(-(prompt_len + 1) // bs)
    cache = init_paged_cache(cfg, n_requests, n_requests * nb, bs, nb,
                             rt_np.compute_dtype)
    cache["paged"]["tbl"] = jnp.arange(n_requests * nb,
                                       dtype=jnp.int32).reshape(-1, nb)
    zero = jnp.zeros((n_requests, 1), jnp.int32)

    def step(rt, params, cache, tokens, pos):
        logits, cache, _ = tfm.forward(cfg, params,
                                       {"tokens": tokens, "pos": pos}, rt,
                                       cache=cache)
        return logits, cache

    logits, cache = jax.jit(lambda p, c, t, s: step(rt_np, p, c, t, s))(
        params, cache, prompts, zero)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    cache["paged"]["ctx"] = jnp.full((n_requests,), prompt_len, jnp.int32)
    pos = zero + prompt_len
    got = {}
    for name, rt in (("pallas", rt_pl), ("jnp", rt_np)):
        fn = jax.jit(lambda p, c, t, s, rt=rt: step(rt, p, c, t, s)[0])
        compiled = fn.lower(params, cache, tok, pos).compile()
        if name == "pallas":
            kernels = pallas_kernels(compiled.as_text())
            print(f"[serve] decode step kernels {kernels}", flush=True)
            check(DECODE_KERNELS <= set(kernels),
                  f"[serve] decode kernels missing: "
                  f"{sorted(DECODE_KERNELS - set(kernels))}")
        got[name] = compiled(params, cache, tok, pos).astype(jnp.float32)
    scale = float(jnp.max(jnp.abs(got["jnp"])))
    diff = float(jnp.max(jnp.abs(got["pallas"] - got["jnp"])))
    agree = float(jnp.mean(jnp.argmax(got["pallas"], -1)
                           == jnp.argmax(got["jnp"], -1)))
    print(f"[serve] first decode logits pallas vs jnp: max abs diff "
          f"{diff:.3e}, max |logit| {scale:.3e}, argmax agreement "
          f"{agree:.2f}", flush=True)
    check(diff <= LOGIT_RTOL * scale, f"[serve] logits differ by {diff}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="sharded training on four chips vs one chip only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    import jax
    devices = jax.devices()
    d0 = devices[0]
    print(f"[device] platform={d0.platform} device_kind={d0.device_kind} "
          f"count={len(devices)}", flush=True)
    if d0.platform != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 2
    need = 4 if args.four_chip else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.configs import get_config
    cfg = get_config(ARCH)
    print(f"[model] {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads}/{cfg.kv_heads} heads of "
          f"{cfg.head_dim_}, vocab {cfg.vocab_size}, "
          f"{cfg.param_count() / 1e6:.0f}M params", flush=True)
    t0 = time.perf_counter()
    if args.four_chip:
        want = train_phase(cfg, FOUR_CHIP_SPECS[0], 1, args.seed, STEPS)
        for spec in FOUR_CHIP_SPECS:
            got = train_phase(cfg, spec, 4, args.seed, STEPS)
            compare_runs(f"{spec} x4 vs x1", got, want)
    else:
        kernel_phase(args.seed)
        train_phase(cfg, TRAIN_SPEC, 1, args.seed, STEPS)
        serve_phase(cfg, args.seed)
    print(f"[done] all phases passed in {time.perf_counter() - t0:.1f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
