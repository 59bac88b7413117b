"""The reduction of the program's own spans and scopes
(``bench/program_trace.py``) and its readers, against small traces whose
answers are counted by hand (nanoseconds)."""
import pathlib
import re
import types

import pytest

from bench import harness, program_trace, trace

FIX = pathlib.Path(__file__).parent / "fixtures"
METRICS = harness.BENCH / "metrics"

#: the readers of the program's spans and scopes
READERS = ("driver_ms.mtl", "sync_ms.mtl", "ledger_fetch_ms.mtl",
           "ledger_price_ms.mtl", "episodes_ms.mtl", "maml_step_ms.mtl",
           "local_sgd_ms.mtl", "mix_ms.mtl", "eval_ms.mtl",
           "telemetry_row_ms.mtl")


@pytest.fixture
def nested():
    """One FL chunk's host spans around a device plane with two busy
    stretches; a second plane ran one scoped op."""
    return {
        "host": [
            ("bench.window", 0, 1000),
            ("repro.driver.adapt_task", 100, 600),      # [100, 700]
            ("repro.driver.dispatch", 120, 30),         # [120, 150]
            ("bench.ledger", 200, 300),                 # [200, 500]
            ("repro.telemetry.fetch", 210, 90),         # [210, 300]
            ("repro.telemetry.price", 300, 180),        # [300, 480]
        ],
        "device": {
            "/device:TPU:0": [
                ("while.1", 0, 100, "while.1 op_name=jit(f)/while"),
                ("fusion.1", 10, 40, "fusion.1 "
                 "op_name=jit(f)/while/body/episodes/dot_general"),
                ("fusion.2", 30, 50, "fusion.2 "
                 "op_name=jit(f)/while/body/vmap(episodes)/add"),
                ("fusion.3", 600, 20, "fusion.3 "
                 "op_name=jit(f)/transpose(jvp(maml_step))/mul"),
                ("fusion.4", 650, 50,
                 "fusion.4 op_name=jit(f)/my_episodes_x/add"),
            ],
            "/device:TPU:1": [
                ("fusion.5", 0, 10, "fusion.5 op_name=jit(f)/episodes/sin"),
            ],
        },
    }


def test_self_time_subtracts_nested_spans(nested):
    s = program_trace.span_seconds
    # 600 less dispatch (30) and bench.ledger (300)
    assert s(nested, 0, 1000, "repro.driver.adapt_task") \
        == pytest.approx(270e-9)
    assert s(nested, 0, 1000, ["repro.driver.adapt_task",
                               "repro.driver.dispatch"]) \
        == pytest.approx(300e-9)
    assert s(nested, 0, 1000, "repro.telemetry.fetch") \
        == pytest.approx(90e-9)
    assert s(nested, 0, 1000, "repro.driver.adapt_task",
             self_time=False) == pytest.approx(600e-9)


def test_self_time_is_clipped_to_the_window(nested):
    # adapt_task [150, 650] = 500, dispatch clipped to nothing, the
    # ledger [200, 500] = 300
    assert program_trace.span_seconds(
        nested, 150, 650, "repro.driver.adapt_task") \
        == pytest.approx(200e-9)
    assert program_trace.span_seconds(
        nested, 150, 650, "repro.driver.dispatch") is None


def test_span_absent_reads_none(nested):
    assert program_trace.span_seconds(
        nested, 0, 1000, "repro.driver.bill") is None


def test_scope_time_merges_overlapping_ops(nested):
    # plane 0: [10, 50] and [30, 80] merge to 70, "my_episodes_x" is no
    # episodes op; plane 1: 10. Averaged over the two planes.
    assert program_trace.scope_seconds(nested, 0, 1000, "episodes") \
        == pytest.approx(40e-9)
    assert program_trace.scope_seconds(nested, 0, 1000, "maml_step") \
        == pytest.approx(10e-9)
    assert program_trace.scope_seconds(nested, 0, 1000, "eq6_mix") is None


def test_control_flow_without_op_name_takes_its_ops_scope():
    """A while loop the compiler rebuilt without an op_name is in a
    scope when every op it encloses that has an op_name is; its own
    time (the loop's control) then counts there too."""
    ev = {"host": [], "device": {"/device:TPU:0": [
        ("while.1", 0, 100, "while.1"),                    # all episodes
        ("fusion.1", 10, 10, "fusion.1 op_name=jit(f)/episodes/dot"),
        ("while.2", 20, 30, "while.2"),                    # nested, bare
        ("fusion.2", 25, 5, "fusion.2 op_name=jit(f)/episodes/add"),
        ("copy.1", 60, 5, "copy.1"),                       # bare, no ops
        ("while.3", 200, 100, "while.3"),                  # mixed
        ("fusion.3", 210, 10, "fusion.3 op_name=jit(f)/episodes/dot"),
        ("fusion.4", 250, 10, "fusion.4 op_name=jit(f)/maml_step/mul"),
        ("while.4", 400, 50, "while.4"),                   # nothing named
    ]}}
    sec = program_trace.scope_seconds
    assert sec(ev, 0, 1000, "episodes") == pytest.approx(110e-9)
    assert sec(ev, 0, 1000, "maml_step") == pytest.approx(10e-9)
    assert sec(ev, 0, 50, "episodes") == pytest.approx(50e-9)


def test_program_span_inside_the_ledger_names_the_gap(nested):
    # plane 0 busy [0, 100], [600, 620], [650, 700]: the gap [100, 600]
    # has its midpoint 350 inside repro.telemetry.price, itself inside
    # bench.ledger; [620, 650] lies in adapt_task's own time, [700,
    # 1000] under the window alone
    gaps = dict(trace.idle_gaps(nested, 0, 1000))
    assert gaps == pytest.approx({"repro.telemetry.price": 500e-9,
                                  "repro.driver.adapt_task": 30e-9,
                                  "bench.window": 300e-9})


def _run(events, w0, w1, rounds, workload="no-such-cell"):
    return types.SimpleNamespace(workload=workload, events=events,
                                 window_ns=(w0, w1), traced_rounds=rounds)


def test_readers_give_ms_per_traced_round(nested):
    run = _run(nested, 0, 1000, 2)
    read = {n: harness.load_module(METRICS / f"{n}.py").read(run)
            for n in READERS}
    assert read["driver_ms.mtl"] == pytest.approx(300e-6 / 2)
    assert read["ledger_fetch_ms.mtl"] == pytest.approx(90e-6 / 2)
    assert read["ledger_price_ms.mtl"] == pytest.approx(180e-6 / 2)
    assert read["episodes_ms.mtl"] == pytest.approx(40e-6 / 2)
    assert read["maml_step_ms.mtl"] == pytest.approx(10e-6 / 2)
    for n in ("sync_ms.mtl", "local_sgd_ms.mtl", "mix_ms.mtl",
              "eval_ms.mtl", "telemetry_row_ms.mtl"):
        assert read[n] is None


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_without_program_spans(name):
    """A trace of a program without spans or scopes (the harness's
    spans alone) gives every reader nothing to read."""
    ev = trace.read(str(FIX / "trace_small.json"))
    run = _run(ev, *trace.window(ev), 10)
    assert harness.load_module(METRICS / f"{name}.py").read(run) is None


def test_readers_read_every_span_and_scope():
    from repro.telemetry import spans
    named = set()
    for n in READERS:
        text = (METRICS / f"{n}.py").read_text()
        named |= set(re.findall(r'"(repro\.[a-z_.]+)"', text))
        named |= {s for s in spans.SCOPES if f'"{s}"' in text}
    assert named == set(spans.SPANS) | set(spans.SCOPES)


@pytest.fixture(scope="module")
def chip():
    """32 ms of the MTL cell's traced window as a TPU v5e recorded it:
    the last meta chunk (2 rounds) and task 0's set-up and first FL
    chunk, with the program's spans and each op's ``op_name`` path cut
    after its scope (the window span cut to the slice)."""
    ev = trace.read(str(FIX / "trace_tpu_spans.json"))
    return ev, trace.window(ev)


def test_chip_price_spans_share_the_device_clock(chip):
    """Nothing is queued on the device while the ledger prices a
    chunk's rows: on one clock, the device is idle in those spans."""
    ev, _ = chip
    price = [(s, s + d) for n, s, d in ev["host"]
             if n == "repro.telemetry.price"]
    assert len(price) == 2                      # one per chunk
    busy = sum(trace.busy_s(ev, a, b) for a, b in price)
    assert busy < 0.02 * sum(b - a for a, b in price) / 1e9


def test_chip_gaps_are_named_by_program_spans(chip):
    ev, (w0, w1) = chip
    idle = (w1 - w0) / 1e9 - trace.busy_s(ev, w0, w1)
    gaps = trace.idle_gaps(ev, w0, w1, n=100)
    named = sum(s for n, s in gaps if n.startswith("repro."))
    assert named >= 0.9 * idle


def test_chip_scopes_cover_the_device_time(chip):
    ev, (w0, w1) = chip
    from repro.telemetry import spans
    ops = next(iter(ev["device"].values()))
    inside = [(max(a, w0), min(b, w1)) for s in spans.SCOPES
              for a, b in program_trace._in_scope(
                  ops, program_trace.scope_pattern(s))]
    covered = sum(b - a for a, b in trace._union(inside)) / 1e9
    assert covered >= 0.75 * trace.busy_s(ev, w0, w1)
    for s in spans.SCOPES:
        assert program_trace.scope_seconds(ev, w0, w1, s) > 0


def test_chip_program_spans_account_for_the_ledger(chip):
    ev, (w0, w1) = chip
    ours = program_trace.span_seconds(
        ev, w0, w1, ["repro.telemetry.fetch", "repro.telemetry.price"])
    harness_ledger = program_trace.span_seconds(
        ev, w0, w1, "bench.ledger", self_time=False)
    assert ours == pytest.approx(harness_ledger, rel=0.1)


def test_op_name_is_read_from_event_metadata():
    """A TPU op keeps its op_name in its event metadata's ``tf_op``
    stat, as a string or as a reference to a stat metadata's name."""
    pb = program_trace._xplane_pb2()
    plane = pb.XPlane(name="/device:TPU:0")
    plane.stat_metadata[1].name = "tf_op"
    plane.stat_metadata[2].name = "flops"
    plane.stat_metadata[3].name = "jit(f)/maml_step/mul"
    md = plane.event_metadata[7]
    md.name = "%fusion.1 = f32[2] fusion(...)"
    md.stats.add(metadata_id=2, int64_value=4)
    md.stats.add(metadata_id=1, str_value="jit(f)/episodes/dot")
    plane.event_metadata[8].stats.add(metadata_id=1, ref_value=3)
    plane.event_metadata[9].name = "%while.2 = ..."
    assert program_trace._op_names(plane) == {
        7: "jit(f)/episodes/dot", 8: "jit(f)/maml_step/mul"}


def test_load_keeps_program_and_harness_spans(tmp_path):
    import jax
    with trace.recording(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("repro.driver.sync",
                                              task_id=1):
                jax.numpy.ones(3).block_until_ready()
            with jax.profiler.TraceAnnotation("other.span"):
                pass
    names = [n for n, _, _ in program_trace.load(str(tmp_path))["host"]]
    assert sorted(names) == ["bench.window", "repro.driver.sync"]

