"""Per-kernel correctness: interpret-mode Pallas vs pure-jnp oracle,
swept over shapes and dtypes (assignment contract (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


TOL = {jnp.float32: dict(rtol=2e-3, atol=2e-3),
       jnp.bfloat16: dict(rtol=6e-2, atol=6e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,K,hd,causal,window", [
    (2, 128, 4, 2, 64, True, 0),
    (1, 256, 4, 4, 64, True, 0),
    (2, 128, 4, 1, 64, True, 64),     # MQA + sliding window
    (1, 96, 2, 2, 32, True, 0),       # non-multiple-of-block seq
    (1, 128, 4, 2, 128, False, 0),    # bidirectional, hd=128
    (1, 64, 8, 2, 16, True, 32),
])
def test_flash_attention_vs_oracle(B, S, H, K, hd, causal, window, dtype,
                                   rng_key):
    ks = jax.random.split(rng_key, 3)
    q = _rand(ks[0], (B, S, H, hd), dtype)
    k = _rand(ks[1], (B, S, K, hd), dtype)
    v = _rand(ks[2], (B, S, K, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              impl="interpret", block_q=64, block_k=64)
    want = ref.mha_reference(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_flash_attention_softcap(rng_key):
    ks = jax.random.split(rng_key, 3)
    q = _rand(ks[0], (1, 64, 2, 32), jnp.float32)
    k = _rand(ks[1], (1, 64, 2, 32), jnp.float32)
    v = _rand(ks[2], (1, 64, 2, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, softcap=30.0, impl="interpret",
                              block_q=32, block_k=32)
    want = ref.mha_reference(q, k, v, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,W,bt,bw", [
    (2, 64, 32, 16, 16),
    (1, 100, 48, 32, 32),      # ragged T and W
    (3, 256, 128, 128, 128),
])
def test_rglru_scan_vs_oracle(B, T, W, bt, bw, dtype, rng_key):
    ks = jax.random.split(rng_key, 3)
    log_a = (-jax.nn.softplus(_rand(ks[0], (B, T, W), jnp.float32))
             ).astype(dtype)
    b = _rand(ks[1], (B, T, W), dtype)
    h0 = _rand(ks[2], (B, W), jnp.float32)
    h, hl = ops.rglru_scan(log_a, b, h0, impl="interpret",
                           block_t=bt, block_w=bw)
    hr, hlr = ref.rglru_scan_reference(log_a, b, h0)
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(hr, np.float32), **TOL[dtype])
    np.testing.assert_allclose(np.asarray(hl), np.asarray(hlr),
                               **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("H,N,bn", [
    (1, 128, 64), (2, 1000, 256), (4, 70000, 8192),
])
def test_consensus_update_vs_oracle(H, N, bn, dtype, rng_key):
    K, M = 3, 5                          # agents, source rows
    ks = jax.random.split(rng_key, 4)
    x = _rand(ks[0], (K, N), dtype)
    src = _rand(ks[1], (M, N), dtype)
    idx = jax.random.randint(ks[2], (K, H), 0, M)
    sig = jax.nn.softmax(jax.random.normal(ks[3], (K, H)), axis=1) * 0.7
    y = ops.consensus_update(x, src, idx, sig, impl="interpret",
                             block_n=bn)
    for k in range(K):
        want = ref.consensus_update_reference(x[k], src[idx[k]], sig[k])
        np.testing.assert_allclose(np.asarray(y[k], np.float32),
                                   np.asarray(want, np.float32),
                                   **TOL[dtype])


@pytest.mark.parametrize("shared_src", [False, True])
def test_consensus_kernels_fold_an_outer_vmap(shared_src, rng_key):
    """vmap over agent blocks (the sharded plan's one-device emulation)
    folds into the kernels' agent axis: same result as the oracle per
    block, whether each block reads its own source or a shared one."""
    B, K, M, N, H = 3, 2, 4, 300, 2
    ks = jax.random.split(rng_key, 4)
    x = _rand(ks[0], (B, K, N), jnp.float32)
    src = _rand(ks[1], (M, N) if shared_src else (B, M, N), jnp.float32)
    idx = jax.random.randint(ks[2], (B, K, H), 0, M)
    sig = jax.random.uniform(ks[3], (B, K, H), maxval=0.4)
    src_axis = None if shared_src else 0
    got, want = (jax.vmap(
        lambda x, s, i, g, impl=impl: ops.consensus_update(
            x, s, i, g, impl=impl, block_n=128),
        in_axes=(0, src_axis, 0, 0))(x, src, idx, sig)
        for impl in ("interpret", "xla"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    q = jnp.round(x * 40).astype(jnp.int8)
    qs = jnp.round(src * 40).astype(jnp.int8)
    s = jnp.full((B, K, -(-N // 64)), 0.025)
    ss = jnp.full(qs.shape[:-1] + (-(-N // 64),), 0.025)
    got, want = (jax.vmap(
        lambda x, q, s, qs, ss, i, g, impl=impl: ops.quant_consensus_update(
            x, q, s, qs, ss, i, g, impl=impl, block_n=128, qblock=64),
        in_axes=(0, 0, 0, src_axis, src_axis, 0, 0))(
        x, q, s, qs, ss, idx, sig) for impl in ("interpret", "xla"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_ops_shape_guards(rng_key):
    q = jnp.zeros((2, 8, 4, 16))
    k = jnp.zeros((2, 8, 3, 16))    # H % K != 0
    with pytest.raises(ValueError):
        ops.flash_attention(q, k, k)
    with pytest.raises(TypeError):
        ops.consensus_update(jnp.zeros((1, 4), jnp.int32),
                             jnp.zeros((1, 4)), jnp.zeros((1, 1), int),
                             jnp.ones((1, 1)))
    with pytest.raises(ValueError):      # one index row per agent
        ops.consensus_update(jnp.zeros((2, 4)), jnp.zeros((3, 4)),
                             jnp.zeros((3, 1), int), jnp.ones((3, 1)))
