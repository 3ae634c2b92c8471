"""The expert share, dropless dispatch and the latent-attention counts of
DeepSeek-V2-Lite at a small size on the CPU: the shares of a layer's
experts add up to the uncut layer, the dropless grouped matrix product is
the dense oracle under skewed routing and drops nothing, and the
configuration's parameter counts are the parameters' own."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import moe as moe_lib
from repro.models import transformer as tfm
from repro.models.layers import Runtime

KEY = jax.random.PRNGKey(7)
SHARES = 8


def _small(held=0, offset=0, **moe):
    """DeepSeek-V2-Lite's block at a test's widths: 64 experts scored,
    top-6, 2 shared, router weights not renormalised."""
    base = get_config("deepseek-v2-lite")
    return dataclasses.replace(
        base, n_layers=3, d_model=64, n_heads=2, n_kv_heads=2, d_ff=32,
        dense_d_ff=96, vocab_size=128, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=16, v_head_dim=16,
        moe=dataclasses.replace(base.moe, expert_d_ff=32, held_experts=held,
                                held_offset=offset, **moe))


def _share(p, cfg_share):
    """The uncut layer's weights that a share holds."""
    m = cfg_share.moe
    cut = slice(m.held_offset, m.held_offset + m.n_held)
    return {k: (v[cut] if k in ("w_up", "w_gate", "w_down") else v)
            for k, v in p.items()}


def _shared_out(cfg, p, x):
    sp = p["shared"]
    h = jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])
    return h @ sp["w_down"]


@pytest.mark.parametrize("impl", ["dense", "dropless"])
def test_expert_shares_add_up_to_the_uncut_layer(impl):
    """Eight shares of 8 experts each compute their own experts' part; with
    the shared experts, which every share computes alike, counted once,
    they add up to the uncut layer (the dense oracle over all 64)."""
    cfg = _small()
    p = moe_lib.init_moe(cfg, KEY)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model))
    whole, aux_whole = moe_lib.apply_moe(cfg, p, x, Runtime(moe_impl="dense"))
    total, loads = 0.0, []
    for s in range(SHARES):
        cs = _small(held=cfg.moe.n_experts // SHARES,
                    offset=s * cfg.moe.n_experts // SHARES)
        y, aux, load = moe_lib.moe_layer(cs, _share(p, cs), x,
                                         Runtime(moe_impl=impl))
        assert abs(float(aux - aux_whole)) < 1e-7   # the router sees all 64
        total = total + y
        loads.append(load)
    total = total - (SHARES - 1) * _shared_out(cfg, p, x)
    assert float(jnp.max(jnp.abs(total - whole))) < 1e-4
    # every routed item is computed by exactly one share
    assert float(sum(l.sum() for l in loads)) == x.shape[0] * x.shape[1] * 6


def _skewed(cfg, p):
    """Router weights that send most items to three experts of the share."""
    hot = jnp.zeros((cfg.moe.n_experts,)).at[jnp.array([1, 2, 5])].set(4.0)
    return dict(p, router=p["router"] + hot[None, :] / cfg.d_model ** 0.5)


@pytest.mark.parametrize("held", [0, 8])
def test_dropless_matches_dense_under_skewed_routing(held):
    cfg = _small(held=held)
    p = _skewed(cfg, moe_lib.init_moe(cfg, KEY))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, cfg.d_model)) + 1.0

    def loss(impl):
        def f(params, xx):
            y, aux = moe_lib.apply_moe(cfg, params, xx, Runtime(moe_impl=impl))
            return jnp.sum(jnp.sin(y)) + aux, y
        return f

    (_, y_d), g_d = jax.value_and_grad(loss("dense"), (0, 1), has_aux=True)(
        p, x)
    (_, y_l), g_l = jax.value_and_grad(loss("dropless"), (0, 1),
                                       has_aux=True)(p, x)
    assert float(jnp.max(jnp.abs(y_d - y_l))) < 1e-4
    for a, b in zip(jax.tree.leaves(g_d), jax.tree.leaves(g_l)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4
    # skewed: the busiest held expert takes several times the mean, and
    # nothing routed here is dropped
    _, _, load = moe_lib.moe_layer(cfg, p, x, Runtime(moe_impl="dropless"))
    _, _, ids, _ = moe_lib._router(cfg, p, x.reshape(-1, cfg.d_model))
    held_ids = np.asarray(ids) < cfg.moe.n_held
    assert float(load.sum()) == held_ids.sum()
    assert float(load.max()) > 2 * float(load.mean())


def test_dropless_sort_is_a_permutation():
    """Each item routed to a held expert has one buffer row, in its
    expert's group; the others have none."""
    m = _small(held=8, offset=16).moe
    ids = jax.random.randint(KEY, (50, 6), 0, 64)
    src, dest, sizes = moe_lib._sort_by_expert(ids, m)
    flat = np.asarray(ids).reshape(-1)
    held = (flat >= 16) & (flat < 24)
    dest, src, sizes = np.asarray(dest), np.asarray(src), np.asarray(sizes)
    assert sizes.sum() == held.sum()
    assert np.all(dest[~held] == -1)
    assert sorted(dest[held]) == list(range(held.sum()))
    assert np.all(src[dest[held]] == np.nonzero(held)[0])
    assert np.all(src[held.sum():] == -1)
    starts = np.concatenate([[0], np.cumsum(sizes)])
    for e in range(8):
        rows = src[starts[e]:starts[e + 1]]
        assert np.all(flat[rows] == 16 + e)


def test_router_keeps_raw_top_k_weights_without_norm_topk_prob():
    cfg = _small()
    p = moe_lib.init_moe(cfg, KEY)
    xf = jax.random.normal(KEY, (16, cfg.d_model))
    probs, weights, ids, _ = moe_lib._router(cfg, p, xf)
    assert np.allclose(weights, jnp.take_along_axis(probs, ids, -1))
    assert float(weights.sum(-1).max()) < 1.0


def test_param_counts_follow_the_latent_attention_and_the_share():
    """``param_count`` is the parameters' own count; ``active_param_count``
    leaves out, in each expert layer, the held experts a token meets
    less than once on average (k * held / E of them it meets)."""
    cfg = _small(held=8)
    params = tfm.init_params(cfg, KEY)
    assert cfg.param_count() == tfm.param_count_actual(params)
    stack = params["blocks"][0]["ffn"]
    n_moe = stack["w_up"].shape[0]
    per_expert = sum(stack[k][0, 0].size for k in ("w_up", "w_gate",
                                                   "w_down"))
    m = cfg.moe
    idle = m.n_held - m.top_k * m.n_held / m.n_experts
    assert cfg.active_param_count() == round(
        tfm.param_count_actual(params) - n_moe * idle * per_expert)


def test_published_deepseek_v2_lite_counts():
    """15.7B parameters, 2.4B active without the embedding (the model
    card's counts), and the softmax scale with YaRN's m^2."""
    cfg = get_config("deepseek-v2-lite")
    assert abs(cfg.param_count() / 15.7e9 - 1) < 0.01
    active = cfg.active_param_count() - cfg.vocab_size * cfg.d_model
    assert abs(active / 2.4e9 - 1) < 0.03
    assert abs(cfg.attn_scale - 0.114722) < 1e-5
