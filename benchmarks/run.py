"""Benchmark harness entrypoint (assignment deliverable (d)).

One function per paper table/figure + kernel microbenchmarks. Prints
``name,us_per_call,derived`` CSV rows (derived = the quantity the paper's
table reports, e.g. kJ or a ratio; blank when N/A).

Heavy sweeps (Monte-Carlo Fig.4, 512-device dry-runs) run separately
(benchmarks/fig4_tradeoff.py, repro.launch.dryrun) and are READ here if
their JSON results exist; otherwise the paper's published Table II rounds
are used for the energy rows so this entrypoint always completes in
minutes on 1 CPU.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

RESULTS = "benchmarks/results"
ROWS = []


def row(name: str, us_per_call: float, derived: str = ""):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.1f},{derived}")


def _time(fn, *args, reps=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


# ---------------------------------------------------------------------------
# kernel microbenchmarks (XLA oracle path = CPU production path; the Pallas
# interpret path is correctness-only and far slower, so we time a tiny one)
# ---------------------------------------------------------------------------


def bench_kernels():
    from repro.kernels import ops
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, 512, 8, 64), jnp.float32)
    k = jax.random.normal(key, (2, 512, 2, 64), jnp.float32)
    v = jax.random.normal(key, (2, 512, 2, 64), jnp.float32)
    us = _time(lambda: ops.flash_attention(q, k, v, impl="xla"))
    row("kernel.flash_attention.xla.512", us)
    us = _time(lambda: ops.flash_attention(q, k, v, window=128, impl="xla"))
    row("kernel.flash_attention.swa.xla.512", us)
    qs, ks_ = q[:1, :128, :4], k[:1, :128, :2]
    us = _time(lambda: ops.flash_attention(qs, ks_, ks_, impl="interpret",
                                           block_q=64, block_k=64))
    row("kernel.flash_attention.interpret.128", us)

    la = -jax.nn.softplus(jax.random.normal(key, (4, 1024, 256)))
    b = jax.random.normal(key, (4, 1024, 256))
    us = _time(lambda: ops.rglru_scan(la, b, impl="xla"))
    row("kernel.rglru_scan.xla.1024", us)

    x = jax.random.normal(key, (1, 1_000_000))
    nb = jax.random.normal(key, (2, 1_000_000))
    idx = jnp.array([[0, 1]])
    sig = jnp.array([[0.3, 0.3]])
    us = _time(lambda: ops.consensus_update(x, nb, idx, sig, impl="xla"))
    row("kernel.consensus_update.xla.1M", us)


# ---------------------------------------------------------------------------
# core-protocol microbenchmarks
# ---------------------------------------------------------------------------


def bench_protocol():
    from repro.rl.casestudy import CaseStudy
    cs = CaseStudy()
    key = jax.random.PRNGKey(0)
    params = cs.init_params(key)
    us = _time(lambda: cs._meta_round(params, key)[0])
    row("protocol.maml_round.dqn", us)
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (2,) + x.shape), params)
    us = _time(lambda: cs._fl_rounds[0](stacked, key)[0])
    row("protocol.fl_round.dqn", us)


# ---------------------------------------------------------------------------
# paper tables/figures
# ---------------------------------------------------------------------------


def bench_fig3():
    from benchmarks.fig3_energy import report
    from benchmarks.fig4_tradeoff import PAPER_TABLE_II as T2
    path = os.path.join(RESULTS, "fig4.json")
    mr = {}
    if os.path.exists(path):
        with open(path) as f:
            mr = json.load(f)["mean_rounds"]
    r210 = mr.get("210", T2[210])
    r0 = mr.get("0", T2[0])
    src = "measured" if ("210" in mr and "0" in mr) else (
        "partial-measured" if mr else "paper-tableII")
    t0 = time.perf_counter()
    out = report(r210, r0)
    us = (time.perf_counter() - t0) * 1e6
    row(f"fig3.total_maml_kJ.{src}", us, f"{out['total_maml_kJ']:.1f}")
    row(f"fig3.total_fl_only_kJ.{src}", us, f"{out['total_fl_only_kJ']:.1f}")
    row(f"fig3.energy_reduction.{src}", us, f"{out['reduction']:.2f}x")


def bench_fig4():
    from repro.core import energy
    path = os.path.join(RESULTS, "fig4.json")
    if os.path.exists(path):
        with open(path) as f:
            d = json.load(f)
        for regime, r in d["energies"].items():
            row(f"fig4.optimal_t0.{regime}", 0.0, str(r["optimal_t0"]))
        return
    from benchmarks.fig4_tradeoff import PAPER_TABLE_II as T2
    p = energy.paper_calibrated("fig4")
    t0 = time.perf_counter()
    _, _, eb = energy.optimize_split(p, 3, {k: v for k, v in T2.items()
                                            if k})
    us = (time.perf_counter() - t0) * 1e6
    row("fig4.optimal_t0.black_SL500_UL200", us, str(min(eb, key=eb.get)))
    pr = energy.swap_ul_sl(p)
    _, _, er = energy.optimize_split(pr, 3, {k: v for k, v in T2.items()
                                             if k})
    row("fig4.optimal_t0.red_UL500_SL200", us, str(min(er, key=er.get)))


def bench_table2():
    path = os.path.join(RESULTS, "fig4.json")
    if not os.path.exists(path):
        row("table2.rounds_scaledown", 0.0, "pending(fig4 sweep)")
        return
    with open(path) as f:
        mr = json.load(f)["mean_rounds"]
    s0 = sum(mr["0"])
    best = min((k for k in mr if k != "0"), key=lambda k: sum(mr[k]))
    row("table2.rounds_scaledown", 0.0,
        f"{s0 / max(sum(mr[best]), 1e-9):.1f}x@t0={best}")


def bench_roofline():
    path = os.path.join(RESULTS, "roofline.json")
    if not os.path.exists(path):
        single = os.path.join(RESULTS, "dryrun_single_pod.json")
        if os.path.exists(single):
            from benchmarks.roofline import analyze
            with open(single) as f:
                rows_ = [analyze(r) for r in json.load(f)["reports"]]
            with open(path, "w") as f:
                json.dump(rows_, f, indent=1)
        else:
            row("roofline.pairs", 0.0, "pending(dryrun sweep)")
            return
    with open(path) as f:
        rows_ = json.load(f)
    bounds = {}
    for r in rows_:
        bounds[r["bottleneck"]] = bounds.get(r["bottleneck"], 0) + 1
        row(f"roofline.{r['arch']}.{r['shape']}.step_ms", 0.0,
            f"{r['step_ms']:.2f}({r['bottleneck'][:4]})")
    row("roofline.bottleneck_histogram", 0.0,
        ";".join(f"{k}:{v}" for k, v in sorted(bounds.items())))


def main() -> None:
    print("name,us_per_call,derived")
    bench_kernels()
    bench_protocol()
    bench_fig3()
    bench_fig4()
    bench_table2()
    bench_roofline()
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "bench.csv"), "w") as f:
        f.write("name,us_per_call,derived\n")
        for n, u, d in ROWS:
            f.write(f"{n},{u:.1f},{d}\n")


if __name__ == "__main__":
    main()
