"""The trace reduction against small traces whose answers are counted
by hand (nanoseconds)."""
import pathlib

import pytest

from bench import trace

FIX = pathlib.Path(__file__).parent / "fixtures"


@pytest.fixture
def small():
    return trace.read(str(FIX / "trace_small.json"))


def test_window_is_the_harness_span(small):
    assert trace.window(small) == (90, 420)
    with pytest.raises(ValueError):
        trace.window(small, "bench.missing")


def test_busy_is_the_union_clipped_to_the_window(small):
    # plane 0: [90,170] (fusion.0 clipped, overlapping kernel merged)
    # + [200,300] + [350,400] = 230; plane 1: [100,300] = 200
    assert trace.busy_s(small, 90, 420) == pytest.approx(215e-9)
    assert trace.idle_share(small, 90, 420) == pytest.approx(
        100 * (1 - 215 / 330))


def test_kernel_time_by_name(small):
    assert trace.op_seconds(small, 90, 420, "quant_consensus") \
        == pytest.approx(30e-9 / 2)
    assert trace.op_seconds(small, 90, 420, "no_such_kernel") == 0.0


def test_top_ops_rank_device_time(small):
    top = dict(trace.top_ops(small, 90, 420))
    assert top["fusion.2"] == pytest.approx(300e-9 / 2)
    assert top["fusion.1"] == pytest.approx(100e-9 / 2)
    assert top["fusion.0"] == pytest.approx(20e-9 / 2)
    assert list(top)[0] == "fusion.2"


def test_idle_gaps_named_by_the_innermost_host_span(small):
    gaps = dict(trace.idle_gaps(small, 90, 420))
    # [170,200] under bench.sync, [300,350] under bench.ledger,
    # [400,420] only under the window
    assert gaps == pytest.approx({"bench.ledger": 50e-9,
                                  "bench.sync": 30e-9,
                                  "bench.window": 20e-9})


def test_recorded_chip_trace():
    """The first 3,000 device ops of the MTL cell's traced window, as a
    TPU v5e recorded them (op names shortened from the HLO text, the
    window span cut to the slice): the device plane, the reduction's
    busy time and the scan programs' while loops on top."""
    ev = trace.read(str(FIX / "trace_tpu_mtl.json"))
    w0, w1 = trace.window(ev)
    assert (w1 - w0) / 1e9 == pytest.approx(0.023696852)
    busy = trace.busy_s(ev, w0, w1)
    assert busy == pytest.approx(0.00170082)
    assert trace.idle_share(ev, w0, w1) == pytest.approx(
        100 * (1 - busy / ((w1 - w0) / 1e9)))
    top = trace.top_ops(ev, w0, w1)
    assert top[0][0] == "while.75" and " = " not in top[0][0]
    assert sum(s for _, s in trace.idle_gaps(ev, w0, w1)) \
        == pytest.approx((w1 - w0) / 1e9 - busy)


def test_op_name_drops_the_hlo_text():
    assert trace.op_name("%quant_consensus_update.170 = f32[4,3]{1,0} "
                         "custom-call(...)") == "quant_consensus_update.170"
