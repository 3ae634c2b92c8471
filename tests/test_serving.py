"""Serving subsystem: paged KV cache, flash-decode kernel, scheduler,
and end-to-end paged-vs-dense engine equivalence.

The load-bearing invariant: the paged continuous-batching engine is a
*memory-layout and scheduling* change, not a numerical one — greedy
decode must produce bit-identical token ids to the dense-cache engine
across block sizes, ragged prompt lengths, and oversubscribed slot
counts, and sampled decode must reproduce exactly under the engine's
(stream, position) key derivation.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.kernels.ops import paged_decode_attention
from repro.kernels.ref import paged_attention_ref
from repro.models import transformer as tfm
from repro.models.layers import Runtime
from repro.serve import BlockAllocator, PagedCacheError, ServeEngine

RT = Runtime()


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced(get_config("qwen3-0.6b"))
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(cfg, key, batch, length):
    return jax.random.randint(key, (batch, length), 0, cfg.vocab_size)


# ---------------------------------------------------------------------------
# block allocator
# ---------------------------------------------------------------------------

def test_allocator_all_or_nothing_and_refcounts():
    a = BlockAllocator(8, 16)
    assert a.n_free == 8
    got = a.allocate(5)
    assert got is not None and len(got) == 5 and a.n_free == 3
    assert a.allocate(4) is None          # short pools allocate nothing
    assert a.n_free == 3
    shared = a.fork(got[:2])              # refcount++, same ids
    assert shared == got[:2] and a.n_free == 3
    a.free(got)                           # forked blocks survive the free
    assert a.n_free == 6
    a.free(shared)
    assert a.n_free == 8
    with pytest.raises(PagedCacheError):
        a.free(shared)                    # double free


def test_allocator_copy_on_write():
    a = BlockAllocator(4, 8)
    blocks = a.allocate(1)
    shared = a.fork(blocks)
    new = a.copy_on_write(shared[0])
    assert new != blocks[0]               # shared -> fresh block
    a.free(blocks)
    sole = a.allocate(1)
    assert a.copy_on_write(sole[0]) == sole[0]   # exclusive -> in place


# ---------------------------------------------------------------------------
# flash-decode kernel vs jnp oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (4, 2), (8, 1)])
def test_flash_decode_matches_oracle(heads, kv_heads):
    key = jax.random.PRNGKey(0)
    B, D, bs, P, nb = 3, 16, 8, 32, 6
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, 1, heads, D))
    k_pool = jax.random.normal(ks[1], (P, kv_heads, bs, D))
    v_pool = jax.random.normal(ks[2], (P, kv_heads, bs, D))
    # ragged contexts, distinct pool blocks per request, tail unallocated
    ctx = jnp.asarray([5, bs * 3, bs * nb], jnp.int32)
    perm = jax.random.permutation(ks[3], P)[:B * nb].reshape(B, nb)
    nalloc = -(-ctx // bs)
    tbl = jnp.where(jnp.arange(nb)[None] < nalloc[:, None], perm, -1)

    ref = paged_attention_ref(q, k_pool, v_pool, tbl, ctx)
    for n_splits in (1, 2, 4):
        out = paged_decode_attention(q, k_pool, v_pool, tbl, ctx,
                                     n_splits=n_splits)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 1e-5, (n_splits, err)


def test_paged_ref_layout_invariance():
    """The paged oracle depends only on the *logical* sequence: permuting
    the physical pool blocks (with the table updated to match) changes
    nothing — the property that makes block reuse sound."""
    key = jax.random.PRNGKey(3)
    B, S, H, Kv, D, bs = 2, 24, 4, 2, 16, 8
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, 1, H, D))
    k = jax.random.normal(ks[1], (B, S, Kv, D))
    v = jax.random.normal(ks[2], (B, S, Kv, D))
    nb = S // bs
    k_pool = k.reshape(B * nb, bs, Kv, D).transpose(0, 2, 1, 3)
    v_pool = v.reshape(B * nb, bs, Kv, D).transpose(0, 2, 1, 3)
    tbl = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    ctx = jnp.full((B,), S, jnp.int32)
    paged = paged_attention_ref(q, k_pool, v_pool, tbl, ctx)
    perm = jax.random.permutation(ks[3], B * nb)
    inv = jnp.argsort(perm)
    paged2 = paged_attention_ref(q, k_pool[inv], v_pool[inv],
                                 perm[tbl.reshape(-1)].reshape(B, nb), ctx)
    assert float(jnp.max(jnp.abs(paged - paged2))) < 1e-6


# ---------------------------------------------------------------------------
# engine equivalence: paged continuous batching vs dense static batch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block_size,prefill_chunk", [(8, 8), (16, 4)])
def test_paged_greedy_bitmatches_dense(small_model, block_size,
                                       prefill_chunk):
    cfg, params = small_model
    eng = ServeEngine(cfg, params, RT, max_len=96, n_slots=4,
                      block_size=block_size, prefill_chunk=prefill_chunk,
                      steps_per_tick=3)
    assert eng.paged_ok
    prompts = _prompts(cfg, jax.random.PRNGKey(1), 4, 13)
    out_p = np.asarray(eng.generate(prompts, 10))
    out_s = np.asarray(eng.generate_static(prompts, 10))
    assert np.array_equal(out_p, out_s)


def test_paged_ragged_oversubscribed_matches_dense(small_model):
    """More requests than slots, ragged prompt lengths: every request's
    greedy continuation must bit-match a dense-cache run of that prompt
    alone — continuous batching must not leak state across slots."""
    cfg, params = small_model
    eng = ServeEngine(cfg, params, RT, max_len=64, n_slots=2, block_size=8,
                      prefill_chunk=8, steps_per_tick=4, n_blocks=18)
    lens = [3, 17, 9, 25, 1]
    n_new = 6
    rids = []
    for i, L in enumerate(lens):
        p = np.asarray(_prompts(cfg, jax.random.PRNGKey(10 + i), 1, L)[0])
        rids.append((eng.submit(p, n_new), p))
    done = eng.run_until_drained(key=jax.random.PRNGKey(3))
    for rid, p in rids:
        ref = np.asarray(
            eng.generate_static(jnp.asarray(p)[None], n_new))[0, len(p):]
        assert np.array_equal(done[rid], ref), (rid, len(p))
    # completed requests freed every block
    assert eng._sched.alloc.n_free == 18
    assert not eng._sched.running and not eng._sched.waiting


def test_paged_sampled_reproducible_and_batch_invariant(small_model):
    """Sampling keys are (stream, position): the same explicit key yields
    identical tokens across calls, and a request's tokens do not depend
    on what else shares the batch."""
    cfg, params = small_model
    eng = ServeEngine(cfg, params, RT, max_len=64, n_slots=4, block_size=8,
                      prefill_chunk=8, steps_per_tick=4)
    key = jax.random.PRNGKey(11)
    prompts = _prompts(cfg, jax.random.PRNGKey(4), 3, 9)
    a = np.asarray(eng.generate(prompts, 8, temperature=0.9, key=key))
    b = np.asarray(eng.generate(prompts, 8, temperature=0.9, key=key))
    assert np.array_equal(a, b)
    # batch invariance: row 0 alone, same stream id and key
    rid = eng.submit(np.asarray(prompts[0]), 8, temperature=0.9, stream=0)
    solo = eng.run_until_drained(key=key)[rid]
    assert np.array_equal(solo, a[0, 9:])


def test_generate_seed_advances_between_calls(small_model):
    """The seed engine reused PRNGKey(0) on every generate() call; now
    repeated sampled calls draw fresh tokens unless a key is pinned."""
    cfg, params = small_model
    eng = ServeEngine(cfg, params, RT, max_len=48, n_slots=2, block_size=8)
    prompts = _prompts(cfg, jax.random.PRNGKey(5), 2, 7)
    c = np.asarray(eng.generate(prompts, 8, temperature=1.0))
    d = np.asarray(eng.generate(prompts, 8, temperature=1.0))
    assert not np.array_equal(c, d)
    # static path too
    e = np.asarray(eng.generate_static(prompts, 8, temperature=1.0))
    f = np.asarray(eng.generate_static(prompts, 8, temperature=1.0))
    assert not np.array_equal(e, f)


# ---------------------------------------------------------------------------
# scheduler invariants
# ---------------------------------------------------------------------------

def _mk_sched(n_slots=2, n_blocks=16, block_size=8, chunk=8):
    from repro.serve import Scheduler
    return Scheduler(n_slots, BlockAllocator(n_blocks, block_size),
                     prefill_chunk=chunk, steps_per_tick=4)


def test_scheduler_fifo_no_starvation():
    """Head-of-line blocking: a big request at the head admits before any
    smaller request behind it, even when the small one would fit now."""
    s = _mk_sched(n_slots=2, n_blocks=10)
    big = s.submit(np.zeros(40, np.int32), 8)       # needs 7 blocks
    small = s.submit(np.zeros(4, np.int32), 4)      # needs 2 blocks
    tiny = s.submit(np.zeros(2, np.int32), 2)
    first = s.admit()
    assert [r.rid for r in first] == [big, small]   # FIFO, both fit
    assert s.alloc.n_free == 1
    assert not s.admit()                            # tiny blocked on blocks
    # completing the big request unblocks the queue head
    req = s.running[[k for k, r in s.running.items() if r.rid == big][0]]
    req.generated = list(range(req.n_new))
    req.prefilled = req.prompt_len
    s.complete(req)
    assert [r.rid for r in s.admit()] == [tiny]


def test_scheduler_completion_frees_blocks_and_slot():
    s = _mk_sched(n_slots=1, n_blocks=8)
    r1 = s.submit(np.zeros(8, np.int32), 3)
    (req,) = s.admit()
    free_before = s.alloc.n_free
    req.prefilled = req.prompt_len
    req.generated = [1, 2, 3]
    assert req.remaining == 0
    s.complete(req)
    # full footprint returned: blocks_for(8 prompt + 3 new + 1) = 2
    assert free_before == 6 and s.alloc.n_free == 8
    assert req.slot == -1 and req.done and s.finished[r1] is req
    # slot reusable immediately
    s.submit(np.zeros(8, np.int32), 3)
    assert len(s.admit()) == 1


def test_scheduler_prefill_oldest_first():
    s = _mk_sched(n_slots=2, n_blocks=32, chunk=4)
    a = s.submit(np.zeros(10, np.int32), 2)
    b = s.submit(np.zeros(10, np.int32), 2)
    s.admit()
    # chunked prefill always feeds the oldest unfinished prompt
    for _ in range(3):                   # 10-token prompt: chunks 4+4+2
        req = s.next_prefill()
        assert req.rid == a
        req.prefilled += min(4, req.prompt_len - req.prefilled)
    assert s.next_prefill().rid == b     # a done -> oldest unfinished is b
    assert [r.rid for r in s.decode_slots()] == [a]


# ---------------------------------------------------------------------------
# request TTL + cancellation (resilience satellite)
# ---------------------------------------------------------------------------

def _mk_timed_sched(clock, n_slots=2, n_blocks=16):
    from repro.serve import Scheduler
    return Scheduler(n_slots, BlockAllocator(n_blocks, 8),
                     prefill_chunk=8, steps_per_tick=4, clock=clock)


def test_scheduler_ttl_expires_running_and_waiting():
    """A passed deadline retires the request wherever it is: a running one
    frees blocks+slot like completion, a waiting one stops blocking the
    queue; both keep partial state and record finish_reason='timeout'."""
    now = [0.0]
    s = _mk_timed_sched(lambda: now[0], n_slots=1, n_blocks=8)
    r1 = s.submit(np.zeros(8, np.int32), 3, ttl_s=5.0)   # will run
    r2 = s.submit(np.zeros(8, np.int32), 3, ttl_s=2.0)   # stuck waiting
    r3 = s.submit(np.zeros(8, np.int32), 3)              # no TTL
    (req1,) = s.admit()
    req1.prefilled = req1.prompt_len
    req1.generated = [7]                                 # partial output
    assert s.expire() == []                              # nothing due yet
    now[0] = 3.0                                         # r2's deadline only
    expired = s.expire()
    assert [(slot, r.rid) for slot, r in expired] == [(-1, r2)]
    assert s.finished[r2].finish_reason == "timeout"
    assert [r.rid for r in s.waiting] == [r3]            # head unblocked
    now[0] = 6.0                                         # r1's deadline
    (slot, req) = s.expire()[0]
    assert (slot, req.rid) == (0, r1)
    assert req.finish_reason == "timeout" and req.slot == -1
    assert req.generated == [7]                          # partial kept
    assert s.alloc.n_free == 8                           # blocks returned
    assert [r.rid for r in s.admit()] == [r3]            # seat reusable


def test_scheduler_cancel_waiting_running_and_unknown():
    now = [0.0]
    s = _mk_timed_sched(lambda: now[0], n_slots=1, n_blocks=8)
    r1 = s.submit(np.zeros(8, np.int32), 3)
    r2 = s.submit(np.zeros(8, np.int32), 3)
    s.admit()
    slot, req = s.cancel(r1)                             # running
    assert slot == 0 and req.finish_reason == "cancelled"
    assert s.alloc.n_free == 8 and not s.running
    assert s.cancel(r2) == (-1, s.finished[r2])          # waiting
    assert s.finished[r2].finish_reason == "cancelled"
    assert s.cancel(r1) is None                          # already finished
    assert s.cancel(999) is None                         # unknown rid


def test_engine_ttl_and_cancel_free_seats_and_drain(small_model):
    """End-to-end: an immediately-expiring request and a cancelled one
    must not wedge run_until_drained or leak blocks; survivors complete
    with full budgets and 'length' finish reason."""
    cfg, params = small_model
    eng = ServeEngine(cfg, params, RT, max_len=64, n_slots=2, block_size=8,
                      prefill_chunk=8, steps_per_tick=4)
    p = np.asarray(_prompts(cfg, jax.random.PRNGKey(21), 3, 9))
    ok = eng.submit(p[0], 5)
    doomed = eng.submit(p[1], 5, ttl_s=1e-9)             # expires first tick
    gone = eng.submit(p[2], 5)
    assert eng.cancel(gone)
    assert not eng.cancel(gone)                          # second time: no-op
    assert not eng.cancel(12345)
    sched = eng._sched
    out = eng.run_until_drained(key=jax.random.PRNGKey(3))
    assert len(out[ok]) == 5
    assert len(out[doomed]) < 5                          # retired early
    assert sched.alloc.n_free == eng.n_blocks            # nothing leaked
    assert not sched.running and not sched.waiting


# ---------------------------------------------------------------------------
# planner decode mode (satellite)
# ---------------------------------------------------------------------------

def test_planner_decode_mode_latency_objective():
    from repro import strategy as sl
    from repro.configs import ShapeConfig
    cfg = get_config("llama2-7b")
    topo = sl.get_topology("pod")
    shape = ShapeConfig("d", 4096, 16, "decode")
    ranked = sl.search(cfg, topo, shape, top=8)
    assert ranked
    best = ranked[0].report
    assert best.latency_p50 > 0 and best.latency_p99 >= best.latency_p50
    # ranked by ascending p50
    p50s = [p.report.latency_p50 for p in ranked]
    assert p50s == sorted(p50s)
    # train shapes keep the throughput default and carry no latency
    tshape = ShapeConfig("t", 4096, 64, "train")
    rt_ = sl.search(cfg, topo, tshape, top=1)
    assert rt_[0].report.latency_p50 == 0.0
    assert rt_[0].score == rt_[0].report.wps
    assert sl.default_objective(shape) == "p50_latency"
    assert sl.default_objective(tshape) == "wps"
