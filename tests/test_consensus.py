"""Consensus FL (Eq. 6) semantics + hypothesis property tests on the
mixing-matrix invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import consensus


def _stacked(key, K, shape=(5, 3)):
    return {"w": jax.random.normal(key, (K,) + shape),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (K, 7))}


# ---------------------------------------------------------------------------
# property tests: mixing matrices
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=30)
@given(K=st.integers(3, 12), hops=st.integers(1, 2),
       seed=st.integers(0, 2 ** 16))
def test_paper_weights_row_substochastic(K, hops, seed):
    rng = np.random.default_rng(seed)
    sizes = rng.uniform(0.5, 10.0, K)
    A = consensus.ring_adjacency(K, min(hops, (K - 1) // 2))
    M = np.asarray(consensus.mixing_weights(sizes, A, "paper"))
    assert (M >= 0).all()
    rows = M.sum(axis=1)
    assert (rows <= 1.0 + 1e-5).all()          # self weight >= 0
    assert (np.diag(M) == 0).all()             # σ only on neighbours


@settings(deadline=None, max_examples=30)
@given(K=st.integers(3, 12), seed=st.integers(0, 2 ** 16))
def test_metropolis_doubly_stochastic(K, seed):
    rng = np.random.default_rng(seed)
    sizes = rng.uniform(0.5, 10.0, K)
    A = consensus.ring_adjacency(K, 1)
    M = np.asarray(consensus.mixing_weights(sizes, A, "metropolis"))
    np.testing.assert_allclose(M.sum(axis=0), 1.0, atol=1e-5)
    np.testing.assert_allclose(M.sum(axis=1), 1.0, atol=1e-5)
    np.testing.assert_allclose(M, M.T, atol=1e-6)


@settings(deadline=None, max_examples=20)
@given(K=st.integers(2, 10), seed=st.integers(0, 2 ** 16))
def test_consensus_preserves_fixed_point(K, seed):
    """If all agents agree already, one round changes nothing."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(4, 3)).astype(np.float32)
    stacked = {"w": jnp.asarray(np.stack([x] * K))}
    sizes = rng.uniform(0.5, 5.0, K)
    M = consensus.mixing_weights(sizes, consensus.full_adjacency(K),
                                 "paper")
    out = consensus.consensus_step(stacked, M)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(stacked["w"]), atol=1e-5)


# ---------------------------------------------------------------------------
# convergence
# ---------------------------------------------------------------------------


def test_consensus_converges_ring(rng_key):
    K = 8
    s = _stacked(rng_key, K)
    sizes = np.arange(1.0, K + 1)
    M = consensus.mixing_weights(sizes, consensus.ring_adjacency(K, 1),
                                 "paper")
    e0 = float(consensus.consensus_error(s))
    for _ in range(120):
        s = consensus.consensus_step(s, M)
    assert float(consensus.consensus_error(s)) < 1e-8 * max(e0, 1.0)


def test_literal_eq6_swaps_for_two_agents(rng_key):
    """The literal Eq. (6) reading (zero self-weight) is a pure swap for
    the paper's 2-robot clusters — documented non-convergent corner."""
    s = _stacked(rng_key, 2)
    M = consensus.mixing_weights(
        [1.0, 1.0], consensus.full_adjacency(2), "paper",
        include_self=False)
    out = consensus.consensus_step(s, M)
    np.testing.assert_allclose(np.asarray(out["w"][0]),
                               np.asarray(s["w"][1]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(out["w"][1]),
                               np.asarray(s["w"][0]), atol=1e-6)


def test_metropolis_converges_to_mean(rng_key):
    K = 6
    s = _stacked(rng_key, K)
    mean0 = np.asarray(s["w"]).mean(axis=0)
    M = consensus.mixing_weights(np.ones(K),
                                 consensus.ring_adjacency(K, 1),
                                 "metropolis")
    for _ in range(300):
        s = consensus.consensus_step(s, M)
    np.testing.assert_allclose(np.asarray(s["w"][0]), mean0, atol=1e-4)


def test_cluster_ring_matches_dense_on_cluster_adjacency(rng_key):
    """The distributed cluster-ring path (ppermute collectives, here run
    under vmap-with-axis_name, which shares the shard_map collective
    semantics) must produce the SAME params as the dense consensus_step on
    the cluster adjacency after one round (K=4, cluster_size=2)."""
    from repro.core import topology as topo_lib
    K, cluster = 4, 2
    s = _stacked(rng_key, K)
    sizes = jnp.asarray([1.0, 2.0, 3.0, 4.0])

    ring_out = jax.vmap(
        lambda p, d: consensus.cluster_ring_consensus_step(
            p, d, "agents", cluster_size=cluster),
        axis_name="agents")(s, sizes)

    mix = topo_lib.clusters(K // cluster, cluster).mixing(np.asarray(sizes))
    dense_out = consensus.consensus_step(s, mix)

    for leaf in s:
        np.testing.assert_allclose(np.asarray(ring_out[leaf]),
                                   np.asarray(dense_out[leaf]),
                                   rtol=1e-6, atol=1e-6)


def test_consensus_impl_switch_rejects_unknown(rng_key):
    s = _stacked(rng_key, 4)
    M = consensus.mixing_weights(np.ones(4), consensus.full_adjacency(4),
                                 "paper")
    with pytest.raises(ValueError):
        consensus.consensus_step(s, M, impl="bogus")


def test_kernel_consensus_matches_dense(rng_key):
    """The fused Pallas consensus kernel == one row of consensus_step."""
    from repro.kernels import ops
    K = 4
    s = _stacked(rng_key, K)
    sizes = np.array([1.0, 2.0, 3.0, 4.0])
    M = consensus.mixing_weights(sizes, consensus.full_adjacency(K),
                                 "paper")
    dense = consensus.consensus_step(s, M)
    # agent 0 via the kernel
    flat = jnp.concatenate([s["w"][0].ravel(), s["b"][0].ravel()])
    nb = jnp.stack([jnp.concatenate([s["w"][h].ravel(), s["b"][h].ravel()])
                    for h in range(1, K)])
    out = ops.consensus_update(flat[None], nb,
                               jnp.arange(K - 1)[None],
                               jnp.asarray(M)[0:1, 1:],
                               impl="interpret", block_n=64)[0]
    want = jnp.concatenate([dense["w"][0].ravel(), dense["b"][0].ravel()])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
