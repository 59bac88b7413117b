"""Compressed model-exchange subsystem: codec round-trip error bounds,
exact ``bits()`` accounting, error-feedback residual behaviour
(hypothesis), compressed-consensus convergence (the acceptance tolerance
test: int8 + error feedback reaches the uncompressed consensus mean on
ring/cluster graphs), and Pallas-vs-XLA parity of the fused
dequantize-consensus kernel at K = 256."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import comms
from repro.core import consensus
from repro.core import topology as topo_lib
from repro.kernels import ops


def _tree(key, scale=1.0):
    return {"w": scale * jax.random.normal(key, (6, 5)),
            "b": scale * jax.random.normal(jax.random.fold_in(key, 1), (9,))}


# ---------------------------------------------------------------------------
# round-trip error bounds per codec
# ---------------------------------------------------------------------------


def test_identity_roundtrip_exact(rng_key):
    c = comms.get_codec("none")
    t = _tree(rng_key)
    out = c.decode(c.encode(t))
    for k in t:
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(t[k]))


def test_bf16_roundtrip_bound(rng_key):
    c = comms.get_codec("bf16")
    t = _tree(rng_key)
    out = c.decode(c.encode(t))
    for k in t:
        x = np.asarray(t[k], np.float32)
        # bf16 keeps 8 mantissa bits ⇒ relative error <= 2^-8
        np.testing.assert_allclose(np.asarray(out[k]), x,
                                   atol=2.0 ** -8 * np.abs(x).max())


@pytest.mark.parametrize("bits,qmax", [(8, 127.0), (4, 7.0)])
def test_int_roundtrip_bound(rng_key, bits, qmax):
    c = comms.get_codec(f"int{bits}")
    t = _tree(rng_key)
    out = c.decode(c.encode(t))          # round-to-nearest (no key)
    for k in t:
        x = np.asarray(t[k], np.float32)
        step = np.abs(x).max() / qmax    # per-tensor absmax scale
        assert np.abs(np.asarray(out[k]) - x).max() <= 0.5 * step + 1e-7


def test_int8_stochastic_rounding_unbiased(rng_key):
    """E[decode(encode(x, key))] = x: the quantizer noise is zero-mean."""
    c = comms.get_codec("int8")
    x = {"w": jax.random.uniform(rng_key, (4, 4), minval=-1.0, maxval=1.0)}
    acc = np.zeros((4, 4), np.float32)
    reps = 300
    for i in range(reps):
        wire = c.encode(x, jax.random.fold_in(rng_key, i))
        acc += np.asarray(c.decode(wire)["w"], np.float32)
    step = np.abs(np.asarray(x["w"])).max() / 127.0
    # the empirical mean must be far tighter than one quantization step
    assert np.abs(acc / reps - np.asarray(x["w"])).max() < 0.2 * step


def test_topk_keeps_largest(rng_key):
    c = comms.get_codec("topk:3")
    x = {"w": jnp.asarray([0.1, -5.0, 0.2, 3.0, -0.05, 1.0])}
    out = c.decode(c.encode(x))["w"]
    np.testing.assert_allclose(np.asarray(out),
                               [0.0, -5.0, 0.0, 3.0, 0.0, 1.0], atol=1e-7)


# ---------------------------------------------------------------------------
# block-wise (per-channel) int scales
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits,qmax", [(8, 127.0), (4, 7.0)])
def test_blockwise_scales_tighten_roundtrip_bound(rng_key, bits, qmax):
    """Per-channel absmax scales bound the error by the LOCAL absmax:
    on a tensor mixing a tiny and a huge channel, the per-tensor scale
    drowns the tiny half in one global quantization step while block
    scales keep its relative error; the bound is provably tighter."""
    small = 1e-3 * jax.random.normal(rng_key, (64,))
    big = 1e2 * jax.random.normal(jax.random.fold_in(rng_key, 1), (64,))
    x = {"w": jnp.concatenate([small, big])}
    per_tensor = comms.get_codec(f"int{bits}")
    blockwise = comms.get_codec(f"int{bits}:b64")
    err_t = np.abs(np.asarray(per_tensor.decode(per_tensor.encode(x))["w"])
                   - np.asarray(x["w"]))
    err_b = np.abs(np.asarray(blockwise.decode(blockwise.encode(x))["w"])
                   - np.asarray(x["w"]))
    # each 64-block is bounded by ITS OWN absmax step...
    for sl in (slice(0, 64), slice(64, 128)):
        local_step = np.abs(np.asarray(x["w"][sl])).max() / qmax
        assert err_b[sl].max() <= 0.5 * local_step + 1e-7
    # ...which on the small half is orders of magnitude below the
    # per-tensor bound (and below its realized error)
    global_step = np.abs(np.asarray(x["w"])).max() / qmax
    assert err_b[:64].max() < 1e-3 * global_step
    assert err_b[:64].max() < err_t[:64].max()


def test_blockwise_bits_and_pricing_exact():
    c = comms.get_codec("int8:b16")
    x = {"w": jnp.ones((6, 5)), "b": jnp.ones((9,))}
    # 30 params -> 2 blocks of 16 (padded), 9 params -> 1 block
    assert c.leaf_bits((6, 5)) == 30 * 8 + 2 * 32
    assert c.leaf_bits((9,)) == 9 * 8 + 1 * 32
    assert c.model_bits(x) == 30 * 8 + 2 * 32 + 9 * 8 + 32
    assert c.bits(c.encode(x)) == c.model_bits(x)
    # price_bits includes the (non-negligible) block scales
    assert c.price_bits(39 * 32) == 39 * 8 + 32 * int(np.ceil(39 / 16))
    # spec round-trips, EF wraps, unknown block size form rejected
    assert comms.get_codec("int8:b16").name == "int8:b16"
    assert comms.resolve_codec("int4:b8").name == "int4:b8+ef"
    with pytest.raises(ValueError):
        comms.get_codec("int8:b0")


def test_blockwise_consensus_round_runs(rng_key):
    """Block-scaled wires thread the full compressed consensus path
    (dense impl here; the sparse/sharded paths keep the int8 lanes
    through the fused kernel's qblock support)."""
    K = 8
    s = {"w": jax.random.normal(rng_key, (K, 24))}
    mix = topo_lib.ring(K).mixing()
    want = consensus.consensus_step(s, mix)
    out, state = consensus.consensus_step(s, mix, codec="int8:b8")
    assert state is not None
    step = np.abs(np.asarray(s["w"])).max() / 127.0
    assert np.abs(np.asarray(out["w"])
                  - np.asarray(want["w"])).max() <= 3 * step


# ---------------------------------------------------------------------------
# adaptive codec selection from link quality
# ---------------------------------------------------------------------------


def test_select_codec_thresholds():
    """Cheap links afford wide wires; the graph's bottleneck link picks
    the codec. Paper calibration: SL = 4e6 bit/J (ring -> bf16), UL/DL =
    1.6e6 (star -> int8); an order-of-magnitude degraded edge -> int4."""
    assert comms.select_codec(topo_lib.ring(8)).name == "bf16+ef"
    assert comms.select_codec(topo_lib.star(8)).name == "int8+ef"
    degraded = topo_lib.ring(8).with_edge_efficiency(1e5)
    assert comms.select_codec(degraded).name == "int4+ef"
    # explicit link-quality dict + EF opt-out
    c = comms.select_codec(topo_lib.ring(8), {"SL": 1e6},
                           error_feedback=False)
    assert c.name == "int8"
    # hierarchical mixes SL + UL backhaul: the UL bottleneck decides
    assert comms.select_codec(
        topo_lib.hierarchical(3, 2)).name == "int8+ef"


def test_select_codec_edgeless_graph_returns_none():
    lonely = topo_lib.clusters(2, 1)          # 1-device clusters: no links
    assert comms.select_codec(lonely) is None


def test_link_efficiencies_reports_present_classes():
    effs = comms.link_efficiencies(topo_lib.star(6))
    assert set(effs) == {"UL", "DL"}
    # every edge overridden: the class constant prices NOTHING and must
    # not enter the bottleneck (round_comm_joules uses it only for
    # eff==0 edges) — only the per-edge worst case remains
    effs = comms.link_efficiencies(
        topo_lib.ring(6).with_edge_efficiency(2e5))
    assert set(effs) == {"edge"}
    assert effs["edge"] == pytest.approx(2e5)
    # partial override: both the unset edges' class and the edge min
    topo = topo_lib.ring(6)
    eff = np.where(topo.adjacency, 0.0, 0.0)
    first = tuple(np.argwhere(topo.adjacency)[0])
    eff[first] = 3e6
    effs = comms.link_efficiencies(topo.with_edge_efficiency(eff))
    assert set(effs) == {"SL", "edge"}
    # select_codec follows round_comm_joules: all-overridden cheap edges
    # afford bf16 even when the class constant would have said int8
    fast = topo_lib.ring(6).with_edge_efficiency(3e6)
    assert comms.select_codec(fast, {"SL": 1e6}).name == "bf16+ef"


def test_link_quality_dict_must_cover_present_classes():
    """A quality dict missing a class the graph USES is an error, not a
    silent fall-back to the uncompressed wire."""
    with pytest.raises(ValueError):
        comms.select_codec(topo_lib.star(8), {"SL": 1e6})


# ---------------------------------------------------------------------------
# bits() exactness + static Eq.-(11) pricing
# ---------------------------------------------------------------------------


def test_bits_exactness(rng_key):
    t = _tree(rng_key)                      # 30 + 9 = 39 params, 2 tensors
    expect = {
        "none": 39 * 32,
        "bf16": 39 * 16,
        "int8": 39 * 8 + 2 * 32,            # + one f32 scale per tensor
        "int4": 39 * 4 + 2 * 32,
        "topk:0.1": (3 + 1) * 64,           # ceil-ish: round(.1*30)=3, max(1,round(.1*9))=1
        "topk:4": (4 + 4) * 64,
    }
    for spec, want in expect.items():
        c = comms.get_codec(spec)
        wire = c.encode(t)
        assert c.bits(wire) == want, spec
        assert c.model_bits(t) == want, spec
        # error feedback never changes the wire size
        ef = comms.get_codec(spec + "+ef") if spec != "none" else c
        assert ef.leaf_bits((6, 5)) == c.leaf_bits((6, 5))


def test_price_bits_matches_per_param_rate():
    full = 5.6e6 * 8 * 4 / 4                 # arbitrary b(W)
    assert comms.get_codec("int8").price_bits(full) == full / 4
    assert comms.get_codec("int4").price_bits(full) == full / 8
    assert comms.get_codec("bf16").price_bits(full) == full / 2
    assert comms.get_codec("none").price_bits(full) == full
    # fractional top-k: k·(32+32) bits per param
    assert comms.get_codec("topk:0.05").price_bits(full) \
        == pytest.approx(full / 32 * 0.05 * 64, rel=1e-6)


def test_get_codec_specs():
    assert comms.get_codec(None) is None
    assert comms.get_codec("int8+ef").name == "int8+ef"
    assert comms.get_codec("int8+ef").stateful
    assert comms.resolve_codec("int8").name == "int8+ef"      # EF default
    assert comms.resolve_codec("int8", error_feedback=False).name == "int8"
    assert comms.resolve_codec("none").name == "none"         # never wrapped
    c = comms.get_codec("int4")
    assert comms.get_codec(c) is c
    with pytest.raises(ValueError):
        comms.get_codec("int16")
    with pytest.raises(ValueError):
        comms.ErrorFeedback(comms.get_codec("int8+ef"))


# ---------------------------------------------------------------------------
# error feedback: residuals keep the time-average unbiased
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=10)
@given(seed=st.integers(0, 2 ** 16), bits=st.sampled_from([8, 4]))
def test_error_feedback_residual_convergence(seed, bits):
    """Encoding a CONSTANT model with EF: the running mean of the decoded
    stream converges to the model (residual telescopes the bias away),
    and the residual stays bounded by one quantization step."""
    rng = np.random.default_rng(seed)
    x = {"w": jnp.asarray(rng.normal(size=(5, 4)), jnp.float32)}
    c = comms.get_codec(f"int{bits}+ef")
    qmax = 127.0 if bits == 8 else 7.0
    step = float(np.abs(np.asarray(x["w"])).max()) / qmax
    state, acc, T = None, np.zeros((5, 4), np.float32), 40
    for t in range(T):
        wire, state = c.encode_stateful(x, state)
        acc += np.asarray(c.decode(wire)["w"], np.float32)
        # residual bounded: |r| <= step/2 + slack for the clip boundary
        assert np.abs(np.asarray(state["w"])).max() <= step * 1.5
    err = np.abs(acc / T - np.asarray(x["w"])).max()
    assert err <= step    # time-average error well below one LSB drift·T


def test_error_feedback_beats_plain_topk(rng_key):
    """With aggressive sparsification, EF consensus converges where the
    plain (stateless) codec stalls — the reason EF is the default."""
    K = 8
    s0 = {"w": jax.random.normal(rng_key, (K, 12))}
    mix = 0.4 * np.asarray(topo_lib.ring(K).mixing(kind="metropolis"))
    mean0 = np.asarray(s0["w"]).mean(axis=0)

    def run(codec, error_feedback):
        s, st_, k = dict(s0), None, jax.random.PRNGKey(7)
        for _ in range(300):
            k, sk = jax.random.split(k)
            s, st_ = consensus.consensus_step(
                s, mix, codec=codec, codec_state=st_, key=sk,
                error_feedback=error_feedback)
        return np.abs(np.asarray(s["w"]).mean(axis=0) - mean0).max(), \
            float(consensus.consensus_error(s))

    dev_ef, err_ef = run("topk:0.25", True)
    dev_plain, err_plain = run("topk:0.25", False)
    # EF contracts the residual quantization floor; plain top-k stalls
    assert err_ef < 0.5 * err_plain
    # the CHOCO recentering keeps the population mean EXACT either way
    # (doubly-stochastic σ) — compression error cancels in the sum
    assert dev_ef < 1e-5 and dev_plain < 1e-5


# ---------------------------------------------------------------------------
# compressed consensus — the acceptance tolerance test
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("topo", [topo_lib.ring(8),
                                  topo_lib.clusters(2, 4)])
def test_int8_consensus_reaches_uncompressed_mean(topo):
    """consensus_step(codec="int8") (error feedback on by default) must
    converge to the same consensus mean as the uncompressed step."""
    key = jax.random.PRNGKey(0)
    K = topo.K
    s0 = {"w": jax.random.normal(key, (K, 5, 3)),
          "b": jax.random.normal(jax.random.fold_in(key, 1), (K, 7))}
    mix = topo.mixing(kind="metropolis")

    ref = dict(s0)
    for _ in range(150):
        ref = consensus.consensus_step(ref, mix)

    s, state, k = dict(s0), None, jax.random.PRNGKey(42)
    for _ in range(150):
        k, sk = jax.random.split(k)
        s, state = consensus.consensus_step(s, mix, codec="int8",
                                            codec_state=state, key=sk)
    for leaf in s0:
        want = np.asarray(ref[leaf], np.float32)
        got = np.asarray(s[leaf], np.float32)
        scale = max(np.abs(want).max(), 1.0)
        assert np.abs(got - want).max() <= 2e-2 * scale, leaf
    if topo.is_connected():     # disjoint clusters keep per-cluster means
        assert float(consensus.consensus_error(s)) < 1e-3


def test_compressed_consensus_returns_state_and_none():
    s = {"w": jnp.ones((4, 8))}
    mix = topo_lib.ring(4).mixing()
    out, state = consensus.consensus_step(s, mix, codec="int8")
    assert state is not None and state["w"].shape == (4, 8)
    out2, state2 = consensus.consensus_step(s, mix, codec="int8",
                                            error_feedback=False)
    assert state2 is None
    # uncompressed API unchanged: bare pytree, no tuple
    assert isinstance(consensus.consensus_step(s, mix), dict)


def test_compressed_consensus_identity_codec_matches_uncompressed(rng_key):
    """codec="none" must be the plain Eq.-(6) step exactly (f32 wire)."""
    K = 6
    s = {"w": jax.random.normal(rng_key, (K, 10))}
    mix = topo_lib.ring(K).mixing()
    want = consensus.consensus_step(s, mix)
    got, state = consensus.consensus_step(s, mix, codec="none")
    assert state is None
    np.testing.assert_allclose(np.asarray(got["w"]),
                               np.asarray(want["w"]), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# auto-path density heuristic is codec-aware
# ---------------------------------------------------------------------------


def test_auto_path_accounts_for_codec_payload():
    # ring(256, hops=40): H = 80 > 256//4 = 64 ⇒ dense at f32...
    mix = topo_lib.ring(256, hops=40).mixing()
    assert consensus.auto_path(mix) == "dense"
    # ...but int wires move 4×/8× fewer bytes THROUGH THE GATHER (the
    # fused dequant-consensus kernel consumes int8 lanes directly):
    # H_eff = 20 (int8) / 10 (int4) ⇒ sparse
    assert consensus.auto_path(mix, comms.get_codec("int8")) == "sparse"
    assert consensus.auto_path(mix, comms.get_codec("int8+ef")) == "sparse"
    assert consensus.auto_path(mix, comms.get_codec("int4+ef")) == "sparse"
    # block-wise scales ride the fused kernel too, at 8 + 32/64 wire
    # bits per param
    assert consensus.auto_path(mix, comms.get_codec("int8:b64")) == "sparse"
    # f32 wire: unchanged
    assert consensus.auto_path(mix, comms.get_codec("none")) == "dense"
    # bf16/top-k sparse paths gather DECODED f32 neighbours, so their
    # degree counts at full width — no discount, stays dense
    assert consensus.auto_path(mix, comms.get_codec("bf16")) == "dense"
    assert consensus.auto_path(mix, comms.get_codec("topk:0.05")) == "dense"
    star = topo_lib.star(256).mixing()
    # at int8, h_eff = (K−1)/4 ≤ K/4 ALWAYS: even star's gather moves
    # fewer bytes than the f32 matmul — every graph goes sparse
    assert consensus.auto_path(star, comms.get_codec("int8")) == "sparse"
    # ...except below the calibrated K·degree floor, where the vmapped
    # gather can't amortize its overhead (K=12 ring ran at 0.59× dense
    # in BENCH_consensus_scale): small populations stay dense no matter
    # how light the wire
    small = topo_lib.ring(12, hops=2).mixing()
    assert consensus.auto_path(small) == "dense"
    assert consensus.auto_path(small, comms.get_codec("int8")) == "dense"


# ---------------------------------------------------------------------------
# fused quant-consensus kernel: Pallas vs XLA parity
# ---------------------------------------------------------------------------


def test_quant_consensus_kernel_parity():
    """ops.quant_consensus_update interpret (Pallas body) == XLA oracle."""
    rng = np.random.default_rng(0)
    K, N, H, M = 2, 1000, 3, 4
    x = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    qs = jnp.asarray(rng.integers(-127, 128, (K, N)), jnp.int8)
    ss = jnp.asarray([0.01, 0.02], jnp.float32)
    qn = jnp.asarray(rng.integers(-127, 128, (M, N)), jnp.int8)
    sn = jnp.asarray(rng.uniform(0.005, 0.02, M), jnp.float32)
    idx = jnp.asarray(rng.integers(0, M, (K, H)), jnp.int32)
    sig = jnp.asarray(rng.uniform(0.0, 0.3, (K, H)), jnp.float32)
    a = ops.quant_consensus_update(x, qs, ss, qn, sn, idx, sig, impl="xla")
    b = ops.quant_consensus_update(x, qs, ss, qn, sn, idx, sig,
                                   impl="interpret", block_n=256)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-6, atol=1e-6)


def test_quant_consensus_kernel_guards():
    x = jnp.zeros((1, 8), jnp.float32)
    q = jnp.zeros((1, 8), jnp.int8)
    qn = jnp.zeros((2, 8), jnp.int8)
    s = jnp.ones(2, jnp.float32)
    idx = jnp.zeros((1, 2), jnp.int32)
    with pytest.raises(TypeError):        # wire must be int8
        ops.quant_consensus_update(x, x, jnp.ones(1), qn, s, idx,
                                   jnp.ones((1, 2)))
    with pytest.raises(ValueError):       # mismatched neighbour count
        ops.quant_consensus_update(x, q, jnp.ones(1), qn, s, idx,
                                   jnp.ones((1, 3)))


def test_quant_consensus_parity_at_k256():
    """Full consensus_step parity at K = 256 on a ring: the sparse
    gather + fused Pallas dequant-consensus kernel (interpret mode off
    TPU) must match the dense XLA compressed path."""
    K, N = 256, 96
    key = jax.random.PRNGKey(3)
    s = {"w": jax.random.normal(key, (K, N))}
    mix = topo_lib.ring(K).mixing()
    dense, _ = consensus.consensus_step(s, mix, codec="int8",
                                        impl="xla")
    sparse, _ = consensus.consensus_step(s, mix, codec="int8",
                                         impl="pallas", block_n=N)
    np.testing.assert_allclose(np.asarray(sparse["w"]),
                               np.asarray(dense["w"]),
                               rtol=1e-5, atol=1e-5)


def test_blockwise_quant_consensus_kernel_parity():
    """The fused kernel with per-channel BLOCK-WISE scales (qblock):
    Pallas body (interpret) == XLA oracle == manual decode-then-mix,
    including a tensor length that is not a multiple of the scale block
    or the kernel tile."""
    rng = np.random.default_rng(1)
    K, N, H, B, M = 2, 300, 3, 64, 4      # 300 = 4 full blocks + 44 tail
    nb = -(-N // B)
    x = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    qs = jnp.asarray(rng.integers(-127, 128, (K, N)), jnp.int8)
    ss = jnp.asarray(rng.uniform(0.005, 0.02, (K, nb)), jnp.float32)
    qn = jnp.asarray(rng.integers(-127, 128, (M, N)), jnp.int8)
    sn = jnp.asarray(rng.uniform(0.005, 0.02, (M, nb)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, M, (K, H)), jnp.int32)
    sig = jnp.asarray(rng.uniform(0.0, 0.3, (K, H)), jnp.float32)
    a = ops.quant_consensus_update(x, qs, ss, qn, sn, idx, sig, impl="xla",
                                   qblock=B)
    b = ops.quant_consensus_update(x, qs, ss, qn, sn, idx, sig,
                                   impl="interpret", qblock=B, block_n=128)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-6, atol=1e-6)
    # manual decode (the codec's own blocking) then the plain Eq.-6 mix
    codec = comms.IntCodec(8, block=B)
    like = jax.ShapeDtypeStruct((N,), jnp.float32)
    for k in range(K):
        xhat = codec.decode_leaf({"q": qs[k], "scale": ss[k]}, like)
        nbs = jnp.stack([codec.decode_leaf({"q": qn[j], "scale": sn[j]},
                                           like) for j in idx[k]])
        want = x[k] + jnp.einsum("h,hn->n", sig[k], nbs - xhat[None])
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
    # scale-count guard
    with pytest.raises(ValueError):
        ops.quant_consensus_update(x, qs, ss[:, :-1], qn, sn, idx, sig,
                                   qblock=B)


def test_sharded_blockwise_int8_stays_fused_parity_at_k256():
    """int8:b64 wires on the SHARDED plan: block-scaled int wires stay
    int8 lanes through the all_gather and dequantize INSIDE the fused
    combine (no decode-then-combine), matching the per-agent jnp oracle
    at K = 256 and preserving the population mean exactly under
    doubly-stochastic σ."""
    from repro.core.engine import ConsensusEngine
    from repro.kernels import ref

    K, N, B = 256, 96, 64
    s = {"w": jax.random.normal(jax.random.PRNGKey(3), (K, N))}
    topo = topo_lib.ring(K)
    eng = ConsensusEngine(topo, codec="int8:b64", plan="sharded",
                          num_blocks=8)
    out, state = eng.step(s, eng.init_state(s))
    assert state is not None              # EF residual threads through
    # oracle: EF residual starts at 0 ⇒ the wire is the plain blocked
    # encode; mix every row with the blocked reference kernel
    base = eng.codec.inner
    mix = np.asarray(topo.mixing())
    idx, sg = consensus.sparse_structure(mix)
    xf = jnp.asarray(np.asarray(s["w"], np.float32))
    enc = jax.vmap(lambda m: base.encode_leaf(m, None))(xf)
    want = np.stack([np.asarray(ref.quant_consensus_update_reference(
        xf[k], enc["q"][k], enc["scale"][k], enc["q"][idx[k]],
        enc["scale"][idx[k]], jnp.asarray(sg[k]), qblock=B))
        for k in range(K)])
    np.testing.assert_allclose(np.asarray(out["w"], np.float32), want,
                               rtol=0, atol=1e-5)
    # CHOCO mean exactness survives the blocked wire
    mixm = np.asarray(topo.mixing(kind="metropolis"))
    engm = ConsensusEngine(mixm, codec="int8:b64", plan="sharded",
                           num_blocks=8)
    outm, _ = engm.step(s, engm.init_state(s))
    np.testing.assert_allclose(
        np.asarray(outm["w"], np.float32).mean(axis=0),
        np.asarray(s["w"], np.float32).mean(axis=0), atol=1e-5)
