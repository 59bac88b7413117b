"""Tier-1 contract of ``repro.telemetry``: telemetry off / buffered /
streaming produce BIT-IDENTICAL params, t_i, and metric history across
chunk sizes × engine plans; the streamed per-round Eq.-(11) ledger
reconciles EXACTLY (==, not approx) with the post-hoc dropout replay the
orchestrators bill; plus the program-cache stats counters, sinks, and
the JSONL event schema."""
import collections
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry as tl
from repro.core import federated, maml, scanloop
from repro.core import topology as topo_lib
from repro.core.engine import ConsensusEngine

K, D = 6, 8
P_DROP, DROP_SEED = 0.3, 7


# ---------------------------------------------------------------------------
# toy FL problem (traced sampler, deterministic, converges fast)
# ---------------------------------------------------------------------------


def _loss(p, batch):
    pred = batch["x"] @ p["w"] + p["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


def _sample(key, _t):
    ks = jax.random.split(key, K)

    def one(k):
        x = jax.random.normal(k, (4, D))
        return {"x": x, "y": jnp.sum(x, -1, keepdims=True)}

    return jax.vmap(one)(ks)


def _never(_p):
    return jnp.asarray(False), jnp.float32(0.0)


def _stacked():
    p = {"w": jnp.zeros((D, 1)), "b": jnp.zeros((1,))}
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (K,) + x.shape), p)


def _engine(plan, dropout=P_DROP, codec="int8"):
    kw = {"num_blocks": 2} if plan == "sharded" else {}
    graph = (topo_lib.GraphProcess.dropout(dropout, seed=DROP_SEED)
             if dropout else None)
    return ConsensusEngine(topo_lib.ring(K), codec=codec, plan=plan,
                           graph=graph, **kw)


def _run(telemetry, chunk, plan, max_rounds=8, target_fn=_never):
    eng = _engine(plan)
    out = federated.run_fl_until_scan(
        _loss, _stacked(), _sample, eng, 0.1, target_fn=target_fn,
        max_rounds=max_rounds, key=jax.random.PRNGKey(0), chunk=chunk,
        telemetry=telemetry)
    return out, eng


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# bit-parity matrix: mode × chunk × plan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("plan", ["dense-xla", "sparse-pallas",
                                  "sharded", "distributed"])
@pytest.mark.parametrize("chunk", [1, 7, 32])
def test_parity_matrix(plan, chunk):
    (p0, r0, h0), _ = _run(None, chunk, plan)
    buf = tl.Telemetry()
    (p1, r1, h1), _ = _run(buf, chunk, plan)
    stream = tl.Telemetry(mode="streaming", sinks=(tl.MemorySink(),))
    (p2, r2, h2), _ = _run(stream, chunk, plan)

    _assert_trees_equal(p0, p1)
    _assert_trees_equal(p0, p2)
    assert r0 == r1 == r2
    assert h0 == h1 == h2

    # both modes buffer the same live rounds, price the same joules
    eb, es = buf.events(driver="fl"), stream.events(driver="fl")
    assert len(eb) == len(es) == r0
    assert [e["round"] for e in eb] == list(range(r0))
    assert buf.joules() == stream.joules()
    # streaming emitted every live round to the sink, in round order
    assert ([e["round"] for e in stream.sinks[0].events]
            == [e["round"] for e in eb])


def test_midchunk_hit_freezes_frozen_rows_out():
    """Target hit mid-chunk: the frozen tail never reaches events(),
    sinks, or the ledger — live rounds == t_i exactly."""
    def target(stacked):
        p0 = jax.tree.map(lambda x: x[0], stacked)
        m = _loss(p0, {"x": jnp.eye(D), "y": jnp.ones((D, 1))})
        return m < 2.0, m

    buf = tl.Telemetry(sinks=(tl.MemorySink(),))
    (_, r, h), _ = _run(buf, 32, "dense-xla", max_rounds=30,
                        target_fn=target)
    assert 0 < r < 30                      # actually hit, mid-chunk
    live = buf.events(driver="fl")
    assert len(live) == r == len(h)
    assert len(buf.sinks[0].events) == r
    assert [e["reached"] for e in live] == [False] * (r - 1) + [True]
    # frozen padding is in the buffer (live=False) but never billed
    frozen = [e for e in buf.events(live_only=False) if not e["live"]]
    assert frozen and all(e["joules"] == 0.0 for e in frozen)


# ---------------------------------------------------------------------------
# exact ledger reconciliation under dropout
# ---------------------------------------------------------------------------


def test_ledger_reconciles_exactly_with_dropout_replay():
    """telemetry.joules() == the post-hoc host replay of
    topology.dropout × round_comm_joules, bitwise (same float64 pricing
    expression, same summation order) — this is the identity that lets
    the stream replace ``fl_comm_joules_measured``."""
    buf = tl.Telemetry()
    (_, rounds, _), eng = _run(buf, 7, "dense-xla")
    want = sum(
        t.round_comm_joules(buf.energy_params, codec=eng.codec)
        for t in topo_lib.dropout(topo_lib.ring(K), P_DROP,
                                  seed=DROP_SEED, rounds=rounds))
    assert buf.joules() == want            # EXACT, not approx
    # per-class splits are consistent with the total, row by row
    for e in buf.events(driver="fl"):
        assert e["edges"] == e["n_sl"] + e["n_ul"] + e["n_dl"]
        assert e["joules"] == pytest.approx(
            e["joules_sl"] + e["joules_ul"] + e["joules_dl"])


def test_distributed_ledger_reconciles_exactly_with_dropout_replay():
    """Acceptance: with dropout active on the DISTRIBUTED plan — the
    masked ppermute schedule superset — the in-scan (M, K) slot counts
    still bill each surviving directed edge exactly once, so the
    streamed Eq.-(11) joules equal the post-hoc host replay bitwise."""
    buf = tl.Telemetry()
    (_, rounds, _), eng = _run(buf, 7, "distributed")
    assert rounds > 0
    want = sum(
        t.round_comm_joules(buf.energy_params, codec=eng.codec)
        for t in topo_lib.dropout(topo_lib.ring(K), P_DROP,
                                  seed=DROP_SEED, rounds=rounds))
    assert buf.joules() == want            # EXACT, not approx
    for e in buf.events(driver="fl"):
        assert e["edges"] == e["n_sl"] + e["n_ul"] + e["n_dl"]


def test_casestudy_stream_reconciles_with_measured_ledger():
    """CaseStudy threading: per-task streamed joules ==
    ``fl_comm_joules_measured`` (the post-hoc dropout replay) EXACTLY,
    and results are bit-identical to a telemetry-off run."""
    from repro.rl.casestudy import CaseStudy
    key = jax.random.PRNGKey(0)

    tel = tl.Telemetry()
    cs = CaseStudy(dropout_p=0.2, codec="int8", chunk=8, telemetry=tel)
    p = cs.init_params(key)
    _, t_i, h = cs.adapt_task(key, 2, p, max_rounds=4)
    assert tel.joules(task_id=2) == cs.last_adapt_comm_joules
    assert len(tel.events(driver="fl")) == t_i

    ref = CaseStudy(dropout_p=0.2, codec="int8", chunk=8)
    pr = ref.init_params(key)
    out_ref = ref.adapt_task(key, 2, pr, max_rounds=4)
    _, t_ref, h_ref = out_ref
    assert t_i == t_ref and h == h_ref
    assert cs.last_adapt_comm_joules == ref.last_adapt_comm_joules


# ---------------------------------------------------------------------------
# MAML + engine.scan_rounds threading
# ---------------------------------------------------------------------------


def _sample_tasks(key, _t):
    ks = jax.random.split(key, 2)

    def one(k):
        x = jax.random.normal(k, (3, 4, D))
        return {"x": x, "y": jnp.sum(x, -1, keepdims=True)}

    sup = jax.vmap(one)(jax.random.split(ks[0], 2))
    qry = jax.vmap(one)(jax.random.split(ks[1], 2))
    return sup, qry


@pytest.mark.parametrize("mode", ["buffered", "streaming"])
def test_maml_parity_and_events(mode):
    p0 = {"w": jnp.zeros((D, 1)), "b": jnp.zeros((1,))}
    kw = dict(rounds=5, inner_lr=0.1, outer_lr=0.1, chunk=3,
              key=jax.random.PRNGKey(1))
    ref, hist_ref = maml.maml_train_scan(_loss, p0, _sample_tasks, **kw)
    tel = tl.Telemetry(mode=mode, sinks=(tl.MemorySink(),))
    out, hist = maml.maml_train_scan(_loss, p0, _sample_tasks,
                                     telemetry=tel, **kw)
    _assert_trees_equal(ref, out)
    assert hist == hist_ref
    ev = tel.events(driver="maml")
    assert [e["round"] for e in ev] == list(range(5))
    assert [e["meta_loss"] for e in ev] == pytest.approx(hist)
    assert len(tel.sinks[0].events) == 5


def test_scan_rounds_consensus_events():
    eng = ConsensusEngine(topo_lib.ring(K))     # static graph
    p = {"w": jnp.arange(K * 16, dtype=jnp.float32).reshape(K, 16)}
    ref, _ = eng.scan_rounds(p, rounds=4)
    tel = tl.Telemetry()
    out, _ = eng.scan_rounds(p, rounds=4, telemetry=tel)
    _assert_trees_equal(ref, out)
    ev = tel.events(driver="consensus")
    assert [e["round"] for e in ev] == list(range(4))
    # gossip on a connected static ring contracts disagreement
    assert ev[-1]["disagreement"] < ev[0]["disagreement"]
    # static graph: every round bills the full ring
    n_edges = sum(eng.topology.links_per_round().values())
    assert all(e["edges"] == n_edges for e in ev)


# ---------------------------------------------------------------------------
# program-cache stats
# ---------------------------------------------------------------------------


@pytest.fixture
def _isolated_cache(monkeypatch):
    """Fresh cache + counters; the session's real cache/counters are
    untouched (TRACE_COUNTS totals feed the CI trace budget)."""
    monkeypatch.setattr(scanloop, "_program_cache",
                        collections.OrderedDict())
    monkeypatch.setattr(scanloop, "PROGRAM_CACHE_SIZE", 2)
    saved_cs = dict(scanloop.CACHE_STATS)
    saved_tc = dict(scanloop.TRACE_COUNTS)
    scanloop.reset_cache_stats()
    yield
    scanloop.CACHE_STATS.clear()
    scanloop.CACHE_STATS.update(saved_cs)
    scanloop.TRACE_COUNTS.clear()
    scanloop.TRACE_COUNTS.update(saved_tc)


def test_cache_stats_counters(_isolated_cache):
    mk = lambda: (lambda x: x)
    assert scanloop.get_cached_program(("a",)) is None        # miss
    f1 = scanloop.cached_program(("a",), mk)                  # insert
    assert scanloop.get_cached_program(("a",)) is f1          # hit
    scanloop.cached_program(("b",), mk)
    scanloop.cached_program(("c",), mk)                       # evicts "a"
    st = scanloop.cache_stats()
    assert st["hits"] == 1 and st["misses"] == 1
    assert st["inserts"] == 3 and st["evictions"] == 1
    assert st["size"] == 2 and st["capacity"] == 2
    assert scanloop.get_cached_program(("a",)) is None        # LRU victim
    assert scanloop.cached_program(("b",), mk) is not None    # re-hit: no
    assert scanloop.cache_stats()["inserts"] == 3             # new insert

    scanloop.reset_cache_stats()
    st2 = scanloop.cache_stats()
    assert st2["hits"] == st2["misses"] == st2["evictions"] == 0
    assert st2["size"] == 2            # reset clears counters, NOT entries
    assert st2["trace_counts"] == {}


def test_report_exposes_harness_counters():
    tel = tl.Telemetry()
    _run(tel, 4, "dense-xla", max_rounds=4)
    rep = tel.report()
    assert rep["mode"] == "buffered"
    assert rep["live_rounds"] == 4
    assert rep["joules"] == tel.joules()
    pc = rep["program_cache"]
    assert {"hits", "misses", "inserts", "evictions", "size",
            "capacity", "registered_programs",
            "trace_counts"} <= set(pc)
    assert rep["programs"] and all(
        {"name", "cached", "donation_honored"} <= set(p)
        for p in rep["programs"])


# ---------------------------------------------------------------------------
# sinks + schema
# ---------------------------------------------------------------------------


def test_jsonl_sink_schema_roundtrip(tmp_path):
    path = tmp_path / "events.jsonl"
    tel = tl.Telemetry(sinks=(tl.JsonlSink(path),))
    (_, rounds, _), _ = _run(tel, 4, "dense-xla", max_rounds=4)
    tel.close()
    count, errors = tl.validate_jsonl(path)
    assert errors == []
    assert count == rounds == 4
    with open(path) as fh:
        ev = [json.loads(line) for line in fh]
    assert all(e["type"] == "round" and e["driver"] == "fl" for e in ev)
    from repro.telemetry import schema
    assert schema.main([str(path)]) == 0
    assert schema.main([]) == 2


def test_validate_event_rejects_bad_events(tmp_path):
    ok = {"type": "round", "driver": "maml", "round": 0, "live": True,
          "meta_loss": 0.5}
    assert tl.validate_event(ok) == []
    assert tl.validate_event({"type": "round"})          # missing fields
    bad = dict(ok, meta_loss="0.5")
    assert any("meta_loss" in e for e in tl.validate_event(bad))
    assert tl.validate_event({"type": "round", "driver": "nope",
                              "round": 0, "live": True})
    # strict JSON: NaN poisons the file, validator reports it
    path = tmp_path / "bad.jsonl"
    path.write_text('{"type": "round", "driver": "maml", "round": 0, '
                    '"live": true, "meta_loss": NaN}\n')
    _, errors = tl.validate_jsonl(path)
    assert errors


def test_buffer_capacity_drops_oldest():
    buf = tl.MetricBuffer(capacity=3)
    buf.extend({"type": "round", "round": i, "live": True}
               for i in range(5))
    assert len(buf) == 3
    assert buf.dropped == 2
    assert [e["round"] for e in buf.rows()] == [2, 3, 4]


def test_telemetry_mode_validated():
    with pytest.raises(ValueError):
        tl.Telemetry(mode="firehose")


# ---------------------------------------------------------------------------
# per-agent energy attribution (PR 10)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plan,kw", [
    ("dense-xla", {}), ("sparse-pallas", {}),
    ("sharded", {"num_blocks": 2}), ("distributed", {})])
def test_per_agent_attribution_bills_senders_only(plan, kw):
    """The (K,) agent_* rows attribute every surviving wire to its
    SENDER: they sum exactly to the aggregate counts, a sleeping agent
    bills exactly 0.0 J, and the per-plan survival shapes all agree."""
    eng = ConsensusEngine(
        topo_lib.ring(K), codec="int8:b64", plan=plan,
        graph=topo_lib.GraphProcess.dropout(P_DROP, seed=DROP_SEED),
        agents=topo_lib.AgentProcess.bernoulli(0.6, seed=1),
        tau=2, staleness_decay=0.9, **kw)
    rec = tl.RoundRecorder(eng)
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (K, D))}
    rnd = eng.async_round(jnp.int32(3), eng.init_async_state().age)
    row = rec.row(params, rnd.delivered, metric=0.0, reached=False,
                  live=True, active=rnd.act, age=rnd.age)
    ev = rec.event(3, row)
    assert len(ev["agent_joules"]) == K
    for cls in ("sl", "ul", "dl"):
        assert sum(ev[f"agent_{cls}"]) == ev[f"n_{cls}"], cls
    awake = [bool(a) for a in np.asarray(rnd.act)]
    assert not all(awake), "seed must put at least one agent to sleep"
    for k, up in enumerate(awake):
        if not up:
            assert ev["agent_joules"][k] == 0.0
            assert ev["agent_sl"][k] + ev["agent_ul"][k] \
                + ev["agent_dl"][k] == 0
    # the per-agent ledger decomposes the aggregate (tight, not approx:
    # both sides are sums of the same per-class float64 terms)
    assert sum(ev["agent_joules"]) == pytest.approx(ev["joules"], rel=1e-12)


def test_per_agent_static_rows_match_link_classes():
    """Lockstep static rounds: per-sender counts are the topology's
    outgoing-link table, identical across plan representations."""
    link_class = np.asarray(topo_lib.ring(K).link_class)
    expected = (link_class != topo_lib.NONE).sum(axis=0)
    rows = {}
    for plan, kw in (("dense-xla", {}), ("sparse-pallas", {}),
                     ("sharded", {"num_blocks": 2}), ("distributed", {})):
        eng = ConsensusEngine(topo_lib.ring(K), plan=plan, **kw)
        rec = tl.RoundRecorder(eng)
        params = {"w": jnp.ones((K, D), jnp.float32)}
        row = rec.unpack(rec.row(params, None, metric=0.0, reached=False,
                                 live=True))
        total = row["agent_sl"] + row["agent_ul"] + row["agent_dl"]
        rows[plan] = total
        assert (total == expected).all(), (plan, total, expected)
    assert all((v == rows["dense-xla"]).all() for v in rows.values())


# ---------------------------------------------------------------------------
# the packed row: one int32 vector per round, one copy per chunk
# ---------------------------------------------------------------------------

def _bits(x):
    """The raw bits of a float32 array (NaN payloads and -0.0 kept)."""
    return np.asarray(x, np.float32).view(np.int32)


def _f32(bits):
    return np.array(bits, np.uint32).view(np.float32)


#: metric bit patterns: negative, -0.0, -inf, a NaN with a payload and
#: its sign bit set, the smallest negative subnormal
_ODD_METRICS = (-1.5, -0.0, float("-inf"), _f32(0xFFC01234), _f32(0x80000001))


@pytest.mark.parametrize("K", [2, 7])
@pytest.mark.parametrize("plan,kw", [
    ("dense-xla", {}), ("sparse-pallas", {}),
    ("sharded", {"num_blocks": 1}), ("distributed", {})])
def test_packed_rows_round_trip_bit_for_bit(plan, kw, K):
    """Every field of a chunk's live and frozen rows, built on the plan's
    own survival shape, comes back from ``fetch`` and ``unpack`` with
    the bits it was packed with — odd float patterns and NaN
    disagreement included."""
    eng = ConsensusEngine(
        topo_lib.ring(K), codec="int8:b64", plan=plan,
        graph=topo_lib.GraphProcess.dropout(P_DROP, seed=DROP_SEED), **kw)
    rec = tl.RoundRecorder(eng)
    packed_fields = []
    pack = rec._pack
    rec._pack = lambda f: packed_fields.append(
        {k: np.asarray(v) for k, v in f.items()}) or pack(f)
    w = jax.random.normal(jax.random.PRNGKey(K), (K, D))
    rows = []
    for t, metric in enumerate(_ODD_METRICS):
        params = {"w": w.at[0, 0].set(jnp.inf) if t == 2 else w * t}
        rows.append(rec.row(params, eng.round_survival(jnp.int32(t)),
                            metric=metric, reached=t % 2 == 1, live=True))
    rows.append(rec.frozen_row())
    host = rec.fetch(jnp.stack(rows))
    assert len(packed_fields) == len(rows)
    assert np.isnan(host["disagreement"][2])
    assert sum(int(n) for n in host["n_sl"]) > 0, "no link survived"
    for i, want in enumerate(packed_fields):
        for name in tl.ROW_FIELDS:
            got = host[name][i]
            if want[name].dtype == np.float32:
                assert got.dtype == np.float32, name
                assert _bits(got) == _bits(want[name]), (i, name)
            else:
                assert got.dtype == want[name].dtype, name
                np.testing.assert_array_equal(got, want[name], (i, name))
        one = rec.unpack(rows[i])
        assert all(_bits(one[n]) == _bits(host[n][i])
                   for n in ("metric", "disagreement"))
    assert not host["live"][-1] and host["agent_sl"].shape == (6, K)


def test_packed_layout_keeps_odd_bits_and_int_extremes():
    """A hand-built row: negative and NaN disagreement bits, the int32
    extremes, a K-wide agent block — packed and read back exactly."""
    eng = ConsensusEngine(topo_lib.ring(K))
    rec = tl.RoundRecorder(eng)
    lo, hi = np.iinfo(np.int32).min, np.iinfo(np.int32).max
    fields = {"live": np.bool_(True), "reached": np.bool_(False),
              "metric": _f32(0x7F800001), "disagreement": np.float32(-3.25),
              "n_sl": np.int32(lo), "n_ul": np.int32(hi), "n_dl": np.int32(-1),
              "n_active": np.int32(K), "max_age": np.int32(7),
              "agent_sl": np.arange(K, dtype=np.int32) - 3,
              "agent_ul": np.full(K, hi, np.int32),
              "agent_dl": np.full(K, lo, np.int32)}
    packed = rec._pack({k: jnp.asarray(v) for k, v in fields.items()})
    assert packed.shape == (rec.width,) == (9 + 3 * K,)
    assert packed.dtype == jnp.int32
    host = rec.unpack(np.asarray(packed))
    for name, want in fields.items():
        if want.dtype == np.float32:
            assert _bits(host[name]) == _bits(want), name
        else:
            np.testing.assert_array_equal(host[name], want, name)
    with pytest.raises(ValueError, match="packed rows"):
        rec.unpack(np.asarray(packed)[:-1])


class _LeafCounting(tl.Telemetry):
    """Buffered telemetry that notes how many array leaves each chunk's
    stacked rows have when they reach the host."""

    def __init__(self):
        super().__init__()
        self.leaves = []

    def record_rounds(self, recorder, rows, start, driver="fl",
                      extra=None):
        self.leaves.append(len(jax.tree.leaves(rows)))
        return super().record_rounds(recorder, rows, start, driver, extra)


def _drive_casestudy(tel):
    import dataclasses
    from repro.configs import get_arch
    from repro.rl.casestudy import CaseStudy
    cfg = dataclasses.replace(get_arch("paper-dqn"), num_layers=2,
                              d_model=32)
    cs = CaseStudy(cfg=cfg, chunk=2, dropout_p=0.2, telemetry=tel)
    key = jax.random.PRNGKey(0)
    cs.adapt_task(key, 1, cs.init_params(key), max_rounds=4)


def _drive_federated(tel):
    _run(tel, 4, "dense-xla", max_rounds=8)


def _drive_scan_rounds(tel):
    eng = ConsensusEngine(topo_lib.ring(K))
    eng.scan_rounds({"w": jnp.ones((K, D), jnp.float32)}, rounds=4,
                    telemetry=tel)


def _drive_trainer(tel):
    from repro.configs import get_arch, reduced
    from repro.launch.train import train_federated
    cfg = reduced(get_arch("stablelm-3b"), num_layers=1, d_model=32)
    train_federated(cfg, rounds=4, agents=2, tasks=1, local_steps=1,
                    batch=2, seq=16, lr=1e-3, chunk=2, telemetry=tel)


@pytest.mark.parametrize("drive", [_drive_casestudy, _drive_federated,
                                   _drive_scan_rounds, _drive_trainer],
                         ids=["casestudy", "federated", "scan_rounds",
                              "trainer"])
def test_each_driver_hands_over_one_packed_array_per_chunk(drive):
    tel = _LeafCounting()
    drive(tel)
    assert tel.leaves and all(n == 1 for n in tel.leaves), tel.leaves
    rep = tel.report()
    assert rep["fetch_copies"] == rep["fetched_chunks"] == len(tel.leaves)


def test_report_counts_one_copy_per_chunk_after_casestudy_run():
    """A whole chunked process — meta chunks and FL chunks — fetches
    each chunk's ledger rows in exactly one device→host copy."""
    import dataclasses
    from repro.configs import get_arch
    from repro.rl.casestudy import CaseStudy
    cfg = dataclasses.replace(get_arch("paper-dqn"), num_layers=2,
                              d_model=32)
    tel = tl.Telemetry()
    cs = CaseStudy(cfg=cfg, chunk=2, telemetry=tel)
    res = cs.run(jax.random.PRNGKey(3), 2, max_rounds=4)
    rep = tel.report()
    fl_chunks = sum(-(-t // 2) for t in res.rounds_per_task)
    assert rep["fetched_chunks"] == 1 + fl_chunks
    assert rep["fetch_copies"] == rep["fetched_chunks"]
    tel.reset()
    assert tel.report()["fetch_copies"] == tel.report()["fetched_chunks"] == 0
