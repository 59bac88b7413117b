"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout. The cell's configuration, traffic mix and
readers are found by the names in BENCHMARK.json (see bench/harness.py).
With ``--trace 0`` the result line carries the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, read from a profiler
trace of the window's first seconds, the harness's host spans and the
counters. Exits non-zero, printing no result, without a TPU or with
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = harness.load_benchmark()
    cell, cfg_entry, traffic = harness.cell_entries(bench, args.workload)
    try:
        devs = harness.require_chips(cell["chips"])
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.enable_cache()
    with open(harness.ROOT / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(harness.BENCH / "limits" / f"{args.workload}.json") as f:
        limits = json.load(f)["limits"]
    trace_dir = None
    if args.trace:
        trace_dir = harness.ROOT / ".bench_trace" / args.workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir = str(trace_dir)
    ctx = types.SimpleNamespace(
        workload=args.workload, config_name=cell["config"], config=config,
        traffic=traffic, seed=args.seed, seconds=args.seconds, devs=devs,
        clock=harness.Clock(T_START), compiles=harness.CompileCounter(),
        trace_dir=trace_dir)
    driver = harness.load_module(harness.BENCH / "drivers"
                                 / f"{traffic['driver']}.py")
    out = driver.run(ctx)
    correct, checks = harness.judge(out["checks"], limits)
    device = out["device"]
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "device": device}
    if args.trace:
        from bench import trace
        events = trace.load(trace_dir)
        w0, w1 = trace.window(events)
        run = types.SimpleNamespace(
            workload=args.workload, config=config, traffic=traffic,
            peaks=harness.peaks(device["kind"]), events=events,
            window_ns=(w0, w1), traced_rounds=out["window"].traced_rounds,
            compiles_in_window=out["window"].compiles, host=out["host"])
        metrics = {}
        for m in harness.metrics_of(bench, args.workload, "per_layer"):
            reader = harness.load_module(harness.BENCH / "metrics"
                                         / f"{m['name']}.py")
            v = reader.read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device = dict(device, busy_s=trace.busy_s(events, w0, w1),
                      window_s=(w1 - w0) / 1e9)
        result["device"] = device
        result["breakdown"] = {
            "device_ops": trace.top_ops(events, w0, w1),
            "idle_gaps": trace.idle_gaps(events, w0, w1)}
    else:
        metrics = {m["name"]: {"value": out["e2e"][m["name"]],
                               "unit": m["unit"]}
                   for m in harness.metrics_of(bench, args.workload,
                                               "end_to_end")
                   if m["name"] in out["e2e"]}
    result["metrics"] = metrics
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
