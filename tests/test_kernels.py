"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles in
``repro.kernels.ref`` (interpret=True executes kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.rwkv6 import wkv6

KEY = jax.random.PRNGKey(42)


@pytest.mark.parametrize("B,S,H,Kv,D", [
    (2, 128, 4, 2, 64),     # GQA
    (1, 256, 4, 4, 64),     # MHA
    (1, 384, 8, 1, 128),    # MQA (granite)
    (2, 96, 6, 2, 64),      # ragged (pad path)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(B, S, H, Kv, D, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Kv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Kv, D), dtype)
    out = flash_attention(q, k, v, block_q=64, block_kv=64, interpret=True)
    expect = ref.attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert out.shape == q.shape and out.dtype == dtype
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - expect.astype(jnp.float32)))) < tol


@pytest.mark.parametrize("window", [32, 64, 100])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    out = flash_attention(q, k, v, window=window, block_q=64, block_kv=64,
                          interpret=True)
    expect = ref.attention_ref(q, k, v, window=window)
    assert float(jnp.max(jnp.abs(out - expect))) < 2e-5


@pytest.mark.parametrize("blocks", [(64, 64), (128, 64), (64, 128), (128, 128)])
def test_flash_attention_block_shapes(blocks):
    bq, bk = blocks
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    out = flash_attention(q, k, v, block_q=bq, block_kv=bk, interpret=True)
    expect = ref.attention_ref(q, k, v)
    assert float(jnp.max(jnp.abs(out - expect))) < 2e-5


# ---------------------------------------------------------------------------
# custom_vjp grad consistency: pallas backward kernels vs jax.grad of the
# jnp oracle (fp32, interpret mode on CPU)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,Kv,D,window", [
    (2, 128, 4, 2, 64, 0),      # GQA causal
    (1, 256, 4, 4, 64, 64),     # sliding window
    (1, 160, 4, 2, 64, 0),      # non-block-multiple S (pad path)
    (1, 128, 8, 1, 64, 0),      # MQA
])
def test_flash_attention_grads(B, S, H, Kv, D, window):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, S, H, D)) * 0.5
    k = jax.random.normal(ks[1], (B, S, Kv, D)) * 0.5
    v = jax.random.normal(ks[2], (B, S, Kv, D)) * 0.5
    cot = jax.random.normal(ks[3], (B, S, H, D))

    def loss_pallas(q, k, v):
        out = flash_attention(q, k, v, window=window, block_q=64,
                              block_kv=64, interpret=True)
        return jnp.sum(out * cot)

    def loss_ref(q, k, v):
        return jnp.sum(ref.attention_ref(q, k, v, window=window) * cot)

    g_pl = jax.grad(loss_pallas, (0, 1, 2))(q, k, v)
    g_rf = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_pl, g_rf):
        err = float(jnp.max(jnp.abs(a - b)))
        assert err < 1e-3, (name, err)


@pytest.mark.parametrize("H,Kv,Dqk,Dv,scale", [
    (4, 4, 192, 128, 0.1147),   # latent attention: q/k 128 + 64, v 128, YaRN
    (4, 2, 128, 128, None),     # one width, default scale: the qwen3 path
])
def test_flash_attention_value_head_and_scale(H, Kv, Dqk, Dv, scale):
    """A value head narrower than q/k's and an explicit softmax scale:
    output and the three gradients agree with dense f32 attention."""
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (1, 256, H, Dqk)) * 0.5
    k = jax.random.normal(ks[1], (1, 256, Kv, Dqk)) * 0.5
    v = jax.random.normal(ks[2], (1, 256, Kv, Dv)) * 0.5
    cot = jax.random.normal(ks[3], (1, 256, H, Dv))

    def pallas(q, k, v):
        return flash_attention(q, k, v, block_q=64, block_kv=128,
                               interpret=True, scale=scale)

    def dense(q, k, v):
        return ref.attention_ref(q, k, v, scale=scale)

    out = pallas(q, k, v)
    assert out.shape == (1, 256, H, Dv)
    assert float(jnp.max(jnp.abs(out - dense(q, k, v)))) < 2e-5
    g_pl = jax.grad(lambda *a: jnp.sum(pallas(*a) * cot), (0, 1, 2))(q, k, v)
    g_rf = jax.grad(lambda *a: jnp.sum(dense(*a) * cot), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_pl, g_rf):
        assert a.shape == b.shape
        err = float(jnp.max(jnp.abs(a - b)))
        assert err < 1e-3, (name, err)


@pytest.mark.parametrize("S", [160, 200, 300])
def test_flash_attention_default_blocks_ragged_s(S):
    """Default 128/256 blocks with 128 < S < 2*block_q: the padded length
    must stay a multiple of both block sizes (regression: tail q-blocks
    were silently dropped, NaN out/grads)."""
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (1, S, 4, 64)) * 0.5
    k = jax.random.normal(ks[1], (1, S, 2, 64)) * 0.5
    v = jax.random.normal(ks[2], (1, S, 2, 64)) * 0.5
    cot = jax.random.normal(ks[3], q.shape)
    out = flash_attention(q, k, v, interpret=True)
    expect = ref.attention_ref(q, k, v)
    assert float(jnp.max(jnp.abs(out - expect))) < 2e-5
    g_pl = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, interpret=True) * cot), (0, 1, 2))(q, k, v)
    g_rf = jax.grad(lambda q, k, v: jnp.sum(
        ref.attention_ref(q, k, v) * cot), (0, 1, 2))(q, k, v)
    for a, b in zip(g_pl, g_rf):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-3


def test_flash_attention_grads_mixed_blocks():
    """bq != bk exercises both backward grids' independent block offsets."""
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (1, 256, 4, 64)) * 0.5
    k = jax.random.normal(ks[1], (1, 256, 2, 64)) * 0.5
    v = jax.random.normal(ks[2], (1, 256, 2, 64)) * 0.5
    cot = jax.random.normal(ks[3], q.shape)
    g_pl = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, block_q=128, block_kv=64, interpret=True) * cot),
        (0, 1, 2))(q, k, v)
    g_rf = jax.grad(lambda q, k, v: jnp.sum(
        ref.attention_ref(q, k, v) * cot), (0, 1, 2))(q, k, v)
    for a, b in zip(g_pl, g_rf):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-3


@pytest.mark.parametrize("shape", [(4, 7, 256), (2, 128, 512), (3, 384)])
def test_rmsnorm_grads(shape):
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], shape)
    scale = jax.random.normal(ks[1], shape[-1:])
    cot = jax.random.normal(ks[2], shape)
    g_pl = jax.grad(lambda x, s: jnp.sum(rmsnorm(
        x, s, block_rows=64, interpret=True) * cot), (0, 1))(x, scale)
    g_rf = jax.grad(lambda x, s: jnp.sum(
        ref.rmsnorm_ref(x, s) * cot), (0, 1))(x, scale)
    for name, a, b in zip(("dx", "dscale"), g_pl, g_rf):
        err = float(jnp.max(jnp.abs(a - b)))
        assert err < 1e-3, (name, err)


@pytest.mark.parametrize("shape", [(4, 7, 256), (2, 128, 512), (3, 384),
                                   (1, 1, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    x = jax.random.normal(KEY, shape, dtype)
    scale = jax.random.normal(KEY, shape[-1:], dtype)
    out = rmsnorm(x, scale, interpret=True)
    expect = ref.rmsnorm_ref(x, scale)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert out.dtype == dtype
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - expect.astype(jnp.float32)))) < tol


@pytest.mark.parametrize("B,T,H,N,chunk", [
    (2, 64, 2, 32, 16),
    (1, 100, 3, 64, 32),    # ragged pad
    (2, 33, 2, 16, 8),
    (1, 128, 1, 64, 64),
])
def test_wkv6_vs_recurrent(B, T, H, N, chunk):
    ks = jax.random.split(KEY, 5)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, N)) * 0.5 for i in range(3))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, T, H, N)) * 0.5 - 2.5))
    u = jax.random.normal(ks[4], (H, N)) * 0.3
    y, s = wkv6(r, k, v, w, u, chunk=chunk, interpret=True)
    yr, sr = ref.wkv6_ref(r, k, v, w, u, jnp.zeros((B, H, N, N)))
    assert float(jnp.max(jnp.abs(y - yr))) < 1e-3
    assert float(jnp.max(jnp.abs(s - sr))) < 1e-3


def test_wkv6_matches_model_chunked_path():
    """The model's jnp chunked WKV and the Pallas kernel agree."""
    from repro.models.rwkv6 import wkv_chunked
    ks = jax.random.split(KEY, 5)
    B, T, H, N = 2, 64, 2, 32
    r, k, v = (jax.random.normal(ks[i], (B, T, H, N)) * 0.5 for i in range(3))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, T, H, N)) * 0.5 - 2.5))
    u = jax.random.normal(ks[4], (H, N)) * 0.3
    y1, s1 = wkv6(r, k, v, w, u, chunk=16, interpret=True)
    y2, s2 = wkv_chunked(r, k, v, w, u, jnp.zeros((B, H, N, N)), 16)
    assert float(jnp.max(jnp.abs(y1 - y2))) < 1e-4
    assert float(jnp.max(jnp.abs(s1 - s2))) < 1e-4
