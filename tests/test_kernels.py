"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.ops import flash_attention, paged_attention

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.fixture(autouse=True)
def float32_matmuls():
    """Kernels and oracles multiply at float32 precision on every backend.

    A TPU's default precision runs a float32 matmul as one bf16 pass (errors
    of ~5e-3), on the kernel's side and the oracle's alike; the float32
    tolerances above hold only at full precision."""
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("b,h,kvh,sq,sk,d", [
    (2, 4, 2, 64, 64, 32),
    (1, 8, 8, 48, 48, 16),     # MHA
    (2, 4, 1, 32, 96, 32),     # MQA, decode-block (sq < sk)
    (1, 6, 2, 64, 64, 64),     # non-pow2 heads (whisper-like grouping)
    (1, 2, 2, 100, 100, 32),   # non-multiple of block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_vs_ref(b, h, kvh, sq, sk, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, kvh, sk, d), dtype)
    v = jax.random.normal(ks[2], (b, kvh, sk, d), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    expected = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("window", [0, 16])
def test_flash_masks(causal, window):
    if window and not causal:
        pytest.skip("window implies causal")
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 4, 64, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 64, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 64, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=16, block_k=16)
    expected = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_flash_kv_len_padding_mask():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 2, 32, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 64, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 64, 16), jnp.float32)
    out = flash_attention(q, k, v, causal=False, kv_len=40,
                          block_q=16, block_k=16)
    expected = ref.flash_attention_ref(q, k, v, causal=False, kv_len=40)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("b,h,kvh,d,page,pps,P", [
    (2, 4, 2, 32, 16, 4, 16),
    (3, 8, 1, 64, 8, 6, 32),    # MQA
    (1, 4, 4, 16, 32, 2, 8),    # MHA
    (4, 12, 2, 32, 16, 3, 24),  # qwen2-vl-like grouping
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_vs_ref(b, h, kvh, d, page, pps, P, dtype, rng):
    q = jnp.asarray(rng.randn(b, h, d), dtype)
    kp = jnp.asarray(rng.randn(kvh, P, page, d), dtype)
    vp = jnp.asarray(rng.randn(kvh, P, page, d), dtype)
    bt = jnp.asarray(rng.choice(P, size=(b, pps)), jnp.int32)
    sl = jnp.asarray(rng.randint(1, pps * page + 1, size=b), jnp.int32)
    out = paged_attention(q, kp, vp, bt, sl)
    expected = ref.paged_attention_ref(q, kp, vp, bt, sl)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_paged_single_token_seq(rng):
    """seq_len=1 edge: only the first slot of the first page is valid."""
    q = jnp.asarray(rng.randn(1, 2, 16), jnp.float32)
    kp = jnp.asarray(rng.randn(2, 4, 8, 16), jnp.float32)
    vp = jnp.asarray(rng.randn(2, 4, 8, 16), jnp.float32)
    bt = jnp.zeros((1, 2), jnp.int32)
    sl = jnp.ones((1,), jnp.int32)
    out = paged_attention(q, kp, vp, bt, sl)
    # attention over one key = that key's value (per kv head)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(vp[:, 0, 0]),
                               atol=1e-5)


def test_ops_shape_checks():
    q = jnp.zeros((1, 4, 8, 16))
    k = jnp.zeros((1, 3, 8, 16))        # 4 % 3 != 0
    with pytest.raises(ValueError):
        ops.flash_attention(q, k, k)
    with pytest.raises(ValueError):
        ops.paged_attention(jnp.zeros((1, 4, 16)), jnp.zeros((3, 2, 8, 16)),
                            jnp.zeros((3, 2, 8, 16)),
                            jnp.zeros((1, 2), jnp.int32),
                            jnp.ones((1,), jnp.int32))


def test_flash_matches_model_attention():
    """The kernel agrees with the model's blockwise_sdpa substrate."""
    from repro.models.attention import blockwise_sdpa
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    b, kvh, g, s, d = 2, 2, 3, 32, 16
    q = jax.random.normal(ks[0], (b, s, kvh, g, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kvh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kvh, d), jnp.float32)
    pos = jnp.arange(s)
    o_model = blockwise_sdpa(q, k, v, q_pos=pos, k_pos=pos, causal=True)
    # kernel layout: [B, H, S, D]
    qk = jnp.moveaxis(q.reshape(b, s, kvh * g, d), 1, 2)
    kk = jnp.moveaxis(k, 1, 2)
    vv = jnp.moveaxis(v, 1, 2)
    o_kernel = flash_attention(qk, kk, vv, causal=True,
                               block_q=16, block_k=16)
    np.testing.assert_allclose(
        np.asarray(jnp.moveaxis(o_kernel, 2, 1).reshape(b, s, kvh * g, d)),
        np.asarray(o_model), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kvh,g", [(2, 6), (8, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pool_decode_attention_vs_sdpa(kvh, g, dtype, rng):
    """One layer of a stacked [L,B,S,KVH,D] pool, each row attending its
    first ``lens`` positions, as ``simple_sdpa`` masks causally at
    ``pos = lens - 1``. Rows end before, on and after a block's edge and
    at S-1; past each length the pool holds large stale values, which must
    not reach the output; a row of length 0 gives a finite output."""
    from repro.kernels.decode_attention import pool_decode_attention
    from repro.models.attention import simple_sdpa
    n_layers, s_len, d, bk, layer = 3, 64, 32, 16, 1
    lens = np.array([0, 1, bk, bk + 1, s_len - 1], np.int32)
    b, h = len(lens), kvh * g
    k = rng.randn(n_layers, b, s_len, kvh, d)
    v = rng.randn(n_layers, b, s_len, kvh, d)
    stale = np.arange(s_len)[None, None, :, None, None] \
        >= lens[None, :, None, None, None]
    k, v = np.where(stale, 50.0, k), np.where(stale, 1e3, v)
    k, v = jnp.asarray(k, dtype), jnp.asarray(v, dtype)
    q = jnp.asarray(rng.randn(b, h, d), dtype)
    out = pool_decode_attention(q, k, v, jnp.int32(layer), jnp.asarray(lens),
                                block=bk, interpret=True)
    pos = np.maximum(lens - 1, 0)
    expected = simple_sdpa(q.reshape(b, 1, kvh, g, d), k[layer], v[layer],
                           q_pos=jnp.asarray(pos)[:, None],
                           k_pos=jnp.arange(s_len), causal=True)[:, 0]
    out = np.asarray(out, np.float32)
    assert out.shape == (b, h, d) and np.isfinite(out).all()
    np.testing.assert_allclose(out[1:], np.asarray(expected, np.float32)[1:],
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_pool_decode_block_len_moves_a_fixed_byte_count():
    """The block follows KVH * D: 256 KiB of K a copy at both cells'
    widths, and a divisor of the cache with whole sublane tiles."""
    from repro.kernels.decode_attention import block_len, blocks_read
    assert block_len(2048, 2, 128, 2) == 512            # qwen2-vl-2b
    assert block_len(2048, 8, 128, 2) == 128            # phi4-mini
    assert block_len(96, 2, 16, 4) == 96
    assert block_len(100, 2, 128, 2) == 100             # no tiled divisor
    assert blocks_read([0, 1, 512, 513, 2047], 512) == 0 + 1 + 1 + 2 + 4
