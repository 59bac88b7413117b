"""Readings that the limits of a cell's comparison are set from.

    python3 bench/calibrate.py --workload <name> --seeds 1 2 3 ... \
        [--control-seeds 1 2 3] [--out readings.jsonl]

For every seed, in one process on the cell's chip: the program's first
steps exactly as a benchmark run drives them (no window), the plain
float32 reference, and the gaps between them — the lower readings. For
every control seed: the reference computed one precision step below
what the configuration states (``control`` of the traffic's driver),
and each fault the cell can have planted in the reference, compared
with the float32 reference in the same way — the upper readings; and,
where the driver has numbers that only whole processes give
(``process_readings``), one process sound and with each program fault
planted.
Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import types

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def context(workload: str, seed: int, devs, skip_device: bool = False):
    bench = harness.load_benchmark()
    cell, cfg_entry, traffic = harness.cell_entries(bench, workload)
    with open(harness.ROOT / cfg_entry["file"]) as f:
        config = json.load(f)
    return types.SimpleNamespace(
        workload=workload, config_name=cell["config"], config=config,
        traffic=traffic, seed=seed, seconds=0.0, devs=devs,
        clock=harness.Clock(time.perf_counter()),
        compiles=harness.CompileCounter(), trace_dir=None)


def readings(ctx, seeds, control_seeds, emit=print):
    """Yield one dict per (seed, variant): variant "program" (lower
    readings), "control" and every "fault:<name>" (upper readings)."""
    driver = harness.load_module(harness.BENCH / "drivers"
                                 / f"{ctx.traffic['driver']}.py")
    for seed in seeds:
        ctx.seed = seed
        prog = driver.first_steps_of(ctx)
        ref = driver.reference_of(ctx)
        emit({"seed": seed, "variant": "program",
              "checks": driver.compare_of(ctx, prog, ref),
              "tie_margin": ref.get("tie_margin")})
        if seed in control_seeds:
            for variant, kw in driver.CONTROLS.items():
                other = driver.reference_of(ctx, **kw)
                emit({"seed": seed, "variant": variant,
                      "checks": driver.compare_of(ctx, other, ref)})
            if hasattr(driver, "process_readings"):
                for variant, n in driver.process_readings(ctx).items():
                    emit({"seed": seed, "variant": variant,
                          "checks": {"t_i_errors": n}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell, _, _ = harness.cell_entries(bench, args.workload)
    try:
        devs = harness.require_chips(cell["chips"])
    except harness.NoDevice as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    harness.enable_cache()
    ctx = context(args.workload, args.seeds[0], devs)
    sink = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()

    try:
        readings(ctx, args.seeds, set(args.control_seeds), emit)
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
