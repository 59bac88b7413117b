"""Loop-parity contract of the device-resident (chunked lax.scan) round
drivers: scanned vs host-loop produce BIT-IDENTICAL params,
rounds_used/t_i, metric history, and EF codec state — across engine
plans × codecs × chunk sizes, including chunk ∤ max_rounds and a target
hit mid-chunk — plus the engine's ``scan_rounds`` multi-round program
and the traced-sampler / pure_callback fallback machinery."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import federated, maml, scanloop
from repro.core import topology as topo_lib
from repro.core.engine import ConsensusEngine

K = 8


# ---------------------------------------------------------------------------
# toy FL problem: quadratic pull towards sampled targets (deterministic,
# converges fast, and every piece is traceable)
# ---------------------------------------------------------------------------


def _fl_loss(p, b):
    return jnp.mean((p["w"] - b["tgt"]) ** 2)


def _fl_stacked(key):
    return {"w": jax.random.normal(key, (K, 6)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (K, 3))}


def _fl_sampler(key, t):
    return {"tgt": jax.random.normal(key, (K, 3, 1, 6)) * 0.1}


def _target(thr):
    def target(sp):
        m = jnp.mean(jnp.square(sp["w"]))
        return m < thr, m
    return target


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def _run(driver, engine, thr, *, max_rounds=21, **kw):
    return driver(
        _fl_loss, _fl_stacked(jax.random.PRNGKey(1)), _fl_sampler, engine,
        0.3, target_fn=_target(thr), max_rounds=max_rounds,
        key=jax.random.PRNGKey(7), return_state=True, **kw)


# ---------------------------------------------------------------------------
# the parity matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("codec", [None, "int8"])
@pytest.mark.parametrize("plan,plan_kw", [
    ("dense-xla", {}),
    ("sparse-pallas", {}),
    ("sharded", {"num_blocks": 4}),            # the shard_map emulation
])
def test_fl_scan_matches_host_loop(plan, plan_kw, codec):
    """run_fl_until_scan == run_fl_until bit for bit: params, t_i,
    history, EF codec state — across chunk sizes including chunk=32 >
    max_rounds, chunk=4 (divides 21's cover of 24 unevenly), and
    chunk=7 (chunk ∤ max_rounds with the hit mid-chunk)."""
    topo = topo_lib.ring(K)
    eng = ConsensusEngine(topo, codec=codec, plan=plan, **plan_kw)
    # pick a threshold that hits strictly mid-run (rounds_used in
    # (1, max_rounds)) from a preliminary no-target trajectory
    _, _, probe_hist, _ = _run(federated.run_fl_until_scan, eng, -1.0,
                               chunk=32)
    thr = probe_hist[2] * 0.999        # first hit at round 3 of 21
    p_h, t_h, h_h, s_h = _run(federated.run_fl_until, eng, thr)
    assert 1 < t_h < 21                # the hit really is mid-run
    for chunk in (4, 7, 32):
        p_s, t_s, h_s, s_s = _run(federated.run_fl_until_scan, eng, thr,
                                  chunk=chunk)
        assert t_s == t_h, f"chunk={chunk}"
        assert h_s == h_h, f"chunk={chunk}"
        assert _tree_equal(p_s, p_h), f"chunk={chunk}"
        if codec is None:
            assert s_s is None and s_h is None
        else:
            assert _tree_equal(s_s, s_h), f"chunk={chunk}"


def test_fl_scan_never_reached_runs_max_rounds():
    """Unreachable target: every chunking runs exactly max_rounds rounds
    (frozen tail rounds past max_rounds are no-ops) with a full
    history, bit-identical to the host loop."""
    eng = ConsensusEngine(topo_lib.ring(K), plan="sparse-pallas")
    p_h, t_h, h_h, _ = _run(federated.run_fl_until, eng, -1.0,
                            max_rounds=10)
    assert t_h == 10 and len(h_h) == 10
    for chunk in (3, 4, 32):           # 3 ∤ 10, 4 ∤ 10, 32 > 10
        p_s, t_s, h_s, _ = _run(federated.run_fl_until_scan, eng, -1.0,
                                max_rounds=10, chunk=chunk)
        assert (t_s, h_s) == (10, h_h)
        assert _tree_equal(p_s, p_h)


def test_fl_scan_eval_every_matches_host():
    """eval_every > 1: evaluation (and the history grid) happens on the
    same rounds in both drivers, and the scanned t_i lands on an eval
    round exactly like the host loop's."""
    eng = ConsensusEngine(topo_lib.ring(K), codec="int8")
    _, _, probe, _ = _run(federated.run_fl_until_scan, eng, -1.0, chunk=32)
    thr = probe[3] * 0.999
    p_h, t_h, h_h, s_h = _run(federated.run_fl_until, eng, thr,
                              eval_every=2)
    assert t_h % 2 == 0                # hits only surface on eval rounds
    p_s, t_s, h_s, s_s = _run(federated.run_fl_until_scan, eng, thr,
                              eval_every=2, chunk=5)
    assert (t_s, h_s) == (t_h, h_h)
    assert _tree_equal(p_s, p_h) and _tree_equal(s_s, s_h)


def test_fl_scan_freeze_pins_params_after_hit():
    """The lax.cond freeze: params/EF-state at the hit round survive the
    rest of the chunk untouched — running with max_rounds == t_i gives
    the same pytrees as a longer run that froze mid-chunk."""
    eng = ConsensusEngine(topo_lib.ring(K), codec="int8")
    _, _, probe, _ = _run(federated.run_fl_until_scan, eng, -1.0, chunk=32)
    thr = probe[2] * 0.999
    p_long, t_long, _, s_long = _run(federated.run_fl_until_scan, eng, thr,
                                     max_rounds=21, chunk=21)
    p_cut, t_cut, _, s_cut = _run(federated.run_fl_until_scan, eng, thr,
                                  max_rounds=t_long, chunk=t_long)
    assert t_cut == t_long
    assert _tree_equal(p_cut, p_long) and _tree_equal(s_cut, s_long)


def test_fl_scan_host_callback_sampler_fallback():
    """A sampler that concretizes the round index (host numpy RNG) fails
    the traced-contract probe and runs through jax.pure_callback — same
    values, same parity."""
    calls = []

    def np_sampler(key, t):
        t = int(t)                     # host concretization: not traceable
        calls.append(t)
        rng = np.random.default_rng(31 + t)
        return {"tgt": jnp.asarray(
            rng.normal(size=(K, 3, 1, 6)).astype(np.float32) * 0.1)}

    eng = ConsensusEngine(topo_lib.ring(K))
    stacked = _fl_stacked(jax.random.PRNGKey(1))
    kw = dict(target_fn=_target(-1.0), max_rounds=6,
              key=jax.random.PRNGKey(7))
    p_h, t_h, h_h = federated.run_fl_until(
        _fl_loss, stacked, np_sampler, eng, 0.3, **kw)
    p_s, t_s, h_s = federated.run_fl_until_scan(
        _fl_loss, stacked, np_sampler, eng, 0.3, chunk=3, **kw)
    assert (t_s, h_s) == (t_h, h_h)
    assert _tree_equal(p_s, p_h)
    assert calls                       # the callback really ran on host


# ---------------------------------------------------------------------------
# MAML: maml_train_scan vs maml_train
# ---------------------------------------------------------------------------


def _net(p, x):
    return jnp.tanh(x @ p["w1"]) @ p["w2"]


def _maml_loss(p, b):
    return jnp.mean((_net(p, b["x"]) - b["y"]) ** 2)


def _maml_init(key):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (2, 8)) * 0.5,
            "w2": jax.random.normal(k2, (8, 1)) * 0.5}


def _maml_sampler(key, t):
    ks = jax.random.split(key, 2)

    def batch(k):
        x = jax.random.normal(k, (4, 16, 2))
        return {"x": x, "y": jnp.sin(x[..., :1]) * 0.3}

    return batch(ks[0]), batch(ks[1])


@pytest.mark.parametrize("first_order", [True, False])
def test_maml_scan_matches_host_loop(first_order):
    """maml_train_scan == maml_train bit for bit (params AND meta-loss
    history) for first- and second-order meta gradients, across chunk
    sizes including chunk ∤ rounds."""
    p0 = _maml_init(jax.random.PRNGKey(0))
    kw = dict(rounds=7, inner_lr=0.05, outer_lr=0.01,
              first_order=first_order, key=jax.random.PRNGKey(3))
    p_h, h_h = maml.maml_train(_maml_loss, p0, _maml_sampler, **kw)
    assert len(h_h) == 7
    for chunk in (1, 3, 8, 32):
        p_s, h_s = maml.maml_train_scan(_maml_loss, p0, _maml_sampler,
                                        chunk=chunk, **kw)
        assert h_s == h_h, f"chunk={chunk}"
        assert _tree_equal(p_s, p_h), f"chunk={chunk}"


def test_maml_scan_host_callback_sampler_fallback():
    """Non-traceable samplers (int(round) + host RNG) take the
    pure_callback fallback and still reproduce the host loop exactly."""

    def np_sampler(key, t):
        t = int(t)
        rng = np.random.default_rng(100 + t)

        def batch():
            x = rng.normal(size=(4, 16, 2)).astype(np.float32)
            return {"x": x, "y": np.sin(x[..., :1]) * 0.3}

        return batch(), batch()

    p0 = _maml_init(jax.random.PRNGKey(0))
    kw = dict(rounds=5, inner_lr=0.05, outer_lr=0.01,
              key=jax.random.PRNGKey(3))
    p_h, h_h = maml.maml_train(_maml_loss, p0, np_sampler, **kw)
    p_s, h_s = maml.maml_train_scan(_maml_loss, p0, np_sampler, chunk=4,
                                    **kw)
    assert h_s == h_h
    assert _tree_equal(p_s, p_h)


def test_maml_train_callback_still_fires_per_round():
    """The host-loop driver remains the per-round-callback path."""
    seen = []
    p0 = _maml_init(jax.random.PRNGKey(0))
    maml.maml_train(_maml_loss, p0, _maml_sampler, rounds=3,
                    inner_lr=0.05, outer_lr=0.01,
                    key=jax.random.PRNGKey(3),
                    callback=lambda t, p, m: seen.append(
                        (t, float(m["meta_loss"]))))
    assert [t for t, _ in seen] == [0, 1, 2]
    assert all(np.isfinite(l) for _, l in seen)


# ---------------------------------------------------------------------------
# engine.scan_rounds
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("plan,plan_kw", [
    ("dense-xla", {}),
    ("sparse-pallas", {}),
    ("sharded", {"num_blocks": 4}),
    ("distributed", {}),
])
def test_engine_scan_rounds_matches_repeated_step(plan, plan_kw):
    """scan_rounds(keys) == R successive engine.step calls for every
    plan, with the EF codec state threaded through the scan carry."""
    topo = topo_lib.ring(K)
    s = _fl_stacked(jax.random.PRNGKey(2))
    eng = ConsensusEngine(topo, codec="int8", plan=plan, **plan_kw)
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    p_ref, st_ref = s, eng.init_state(s)
    for k in keys:
        p_ref, st_ref = eng.step(p_ref, st_ref, k)
    p_scan, st_scan = jax.jit(
        lambda p, st, ks: eng.scan_rounds(p, st, ks))(
        s, eng.init_state(s), keys)
    for leaf in s:
        np.testing.assert_allclose(
            np.asarray(p_scan[leaf], np.float32),
            np.asarray(p_ref[leaf], np.float32), rtol=0, atol=1e-6,
            err_msg=f"{plan}/{leaf}")
        np.testing.assert_allclose(
            np.asarray(st_scan[leaf], np.float32),
            np.asarray(st_ref[leaf], np.float32), rtol=0, atol=1e-6,
            err_msg=f"{plan}/state/{leaf}")


def test_engine_scan_rounds_keyfree_and_validation():
    eng = ConsensusEngine(topo_lib.ring(K))
    s = _fl_stacked(jax.random.PRNGKey(2))
    p1, st1 = eng.scan_rounds(s, rounds=3)
    p_ref = s
    for _ in range(3):
        p_ref, _ = eng.step(p_ref)
    np.testing.assert_allclose(np.asarray(p1["w"]),
                               np.asarray(p_ref["w"]), rtol=0, atol=1e-6)
    assert st1 is None
    with pytest.raises(ValueError):
        eng.scan_rounds(s)             # neither keys nor rounds


# ---------------------------------------------------------------------------
# scanloop machinery
# ---------------------------------------------------------------------------


def test_traceable_probe_classifies_and_preserves_values():
    traced_fn, traced = scanloop.traceable(
        lambda k, t: jax.random.normal(k, (3,)) + t,
        jax.random.PRNGKey(0), jnp.int32(0))
    assert traced

    def host_fn(k, t):
        return np.float32(int(t)) * np.ones(3, np.float32)

    wrapped, traced = scanloop.traceable(host_fn, jax.random.PRNGKey(0),
                                         jnp.int32(0))
    assert not traced
    out = jax.jit(wrapped)(jax.random.PRNGKey(0), jnp.int32(4))
    np.testing.assert_array_equal(np.asarray(out),
                                  4 * np.ones(3, np.float32))


def test_traceable_routes_constant_output_samplers_to_callback():
    """Impure samplers (stateful iterators, cached host arrays) TRACE
    fine but their outputs are input-independent constants — inside a
    scan the single traced batch would silently replay every round, so
    the probe must route them through pure_callback instead."""
    batches = iter(np.arange(400, dtype=np.float32).reshape(100, 4))

    def it_sampler(key, t):
        return jnp.asarray(next(batches))

    wrapped, traced = scanloop.traceable(it_sampler, jax.random.PRNGKey(0),
                                         jnp.int32(0))
    assert not traced
    # the callback really advances the iterator per call
    a = np.asarray(jax.jit(wrapped)(jax.random.PRNGKey(0), jnp.int32(1)))
    b = np.asarray(jax.jit(wrapped)(jax.random.PRNGKey(0), jnp.int32(2)))
    assert not np.array_equal(a, b)


def test_first_hit():
    assert scanloop.first_hit([False, False, True, True]) == 2
    assert scanloop.first_hit([True]) == 0
    assert scanloop.first_hit([False, False]) is None


def test_traceable_propagates_errors_that_are_not_tracing_errors():
    """Only a tracer-concretization failure means 'use a host callback';
    any other exception is a bug in the sampler and must surface."""
    def broken(k, t):
        raise ValueError("bad batch shape")

    with pytest.raises(ValueError, match="bad batch shape"):
        scanloop.traceable(broken, jax.random.PRNGKey(0), jnp.int32(0))


def test_traceable_logs_its_host_fallback_once(caplog):
    def host_fn(k, t):
        return np.float32(int(t)) * np.ones(2, np.float32)

    with caplog.at_level("WARNING", logger="repro.core.scanloop"):
        for _ in range(2):
            _, traced = scanloop.traceable(host_fn, jax.random.PRNGKey(0),
                                           jnp.int32(0), name="probe-once")
            assert not traced
    hits = [r for r in caplog.records if "probe-once" in r.getMessage()]
    assert len(hits) == 1 and "host callback" in hits[0].getMessage()
