"""Each cell's control — its reference computed one precision step
below what the configuration states, put in the program's place —
must come out not correct under the cell's limits. At reduced sizes on
the CPU; on the chip at the cells' own sizes by ``bench/calibrate.py``."""
import json
import pathlib
import time
import types

import jax
import pytest

from bench import harness

FIX = pathlib.Path(__file__).parent / "fixtures"

CELLS = [
    ("dqn-mtl-paper", "casestudy", "paper-dqn", "dqn-tiny.json",
     "mtl-paper.json", {}),
]


@pytest.mark.parametrize("workload,driver,name,config,traffic,over", CELLS,
                         ids=[c[0] for c in CELLS])
def test_control_is_not_correct(workload, driver, name, config, traffic,
                                over):
    drv = harness.load_module(harness.BENCH / "drivers" / f"{driver}.py")
    tr = json.loads((harness.BENCH / "traffic" / traffic).read_text())
    tr.update(over)
    ctx = types.SimpleNamespace(
        workload=workload, config_name=name,
        config=json.loads((FIX / config).read_text()), traffic=tr,
        seed=2 ** 31 + 11, seconds=0.0, devs=jax.devices(),
        clock=harness.Clock(time.perf_counter()),
        compiles=harness.CompileCounter(), trace_dir=None)
    limits = json.loads((harness.BENCH / "limits" / f"{workload}.json")
                        .read_text())["limits"]
    ref = drv.reference_of(ctx)
    control = drv.reference_of(ctx, **drv.CONTROLS["control"])
    got = drv.compare_of(ctx, control, ref)
    # numbers that only the window's finished processes give are not
    # the control's to fail
    limits = {k: v for k, v in limits.items() if k in got}
    correct, _ = harness.judge(got, limits)
    assert correct is False
    same, _ = harness.judge(drv.compare_of(ctx, ref, ref), limits)
    assert same is True
