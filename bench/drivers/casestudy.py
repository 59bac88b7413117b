"""Driver for traffic mixes that run the paper's multi-task process on
``repro.rl.casestudy.CaseStudy``: ``t0`` MAML meta rounds, then every
task adapted by decentralized FL until its greedy running reward
reaches the target (``CaseStudy.run``, one call per process).

One ``CaseStudy`` is built per run; it compiles its meta and per-task
FL chunk programs once and every process reuses them.

* set-up: the object, then its first steps from the seed — one
  ``meta_train`` of ``check_meta_rounds`` rounds (which covers the meta
  programs of both chunk lengths that ``t0`` needs) and, from its
  result, ``adapt_task`` of every task with its round limit at 1 (one
  chunk of each task's program: its first FL round, the rounds past the
  limit frozen in it, as past a task's target). What these return is
  kept for the check. Then one whole process, which warms the rest.
* window: whole passes over one fixed pool of ``processes`` process
  keys, in an order drawn from the seed, until ``--seconds`` have
  passed; the pass running at the deadline is finished. Every seed
  does the same work (t_i depends on the key). Chunk boundaries are
  stamped through the buffered Eq.-(11) telemetry the case study
  records (``harness.chunk_telemetry``).
* check: ``bench/reference/casestudy.py`` with the Q-network reference
  follows the same first steps from the same keys, at the matrix
  product precision the configuration states; and every process the
  window finished is held to the rule that yields t_i.

Compared numbers (``compare``, ``t_i_errors``):
  meta_loss    each meta round's loss, relative gap (floor: the median);
  meta_change  per leaf ‖W − W0‖ after the meta rounds (gap of norms);
  fl_change    per leaf ‖ȳ − W‖ of a cluster's mean after its first FL
               round (episodes, the 20 clipped local SGD steps, the
               mix), worst task;
  fl_mix       per leaf ‖y − ȳ‖ over a cluster's robots after that
               round, median over the six tasks;
  t_i_errors   tasks of the window's processes whose t_i is not the
               first round whose reported greedy running reward reaches
               the target, or that reported another number of rounds.
A greedy decision within ``tie_margin`` of a tie in Q can go either way
under rounding; each FL number is judged against the closest of the
reference's outcomes of such a round (``Protocol.first_round``).
"""
from __future__ import annotations

import math
import statistics

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness


def arch_config(c: dict):
    import dataclasses
    from repro.configs import get_arch
    cfg = get_arch(c["arch"])
    return dataclasses.replace(cfg, num_layers=c["num_layers"],
                               d_model=c["d_model"], dtype=c["dtype"],
                               param_dtype=c["param_dtype"])


def _host(tree):
    return jax.tree.map(lambda x: np.asarray(x, np.float64), tree)


def _change(a, b):
    """Per-leaf ‖a − b‖ (f64, host)."""
    return [float(np.linalg.norm((x - y).ravel()))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def _mean0(stacked):
    return jax.tree.map(lambda x: x.mean(axis=0), stacked)


def _spread(stacked):
    return [float(np.linalg.norm((x - x.mean(axis=0)).ravel()))
            for x in jax.tree.leaves(stacked)]


def process_keys(n: int, seed: int):
    """The window's processes: one fixed pool of ``n`` process keys for
    every seed (so every run does the same work: t_i depends on the
    key), in an order drawn from the seed."""
    order = np.random.default_rng(seed).permutation(n)
    return [jax.random.fold_in(jax.random.PRNGKey(0), int(i) + 1)
            for i in order]


def check_keys(seed: int):
    base = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
    kmeta, kfl = jax.random.split(base)
    return kmeta, list(jax.random.split(kfl, 6))


def case_study(ctx, tel):
    from repro.rl.casestudy import CaseStudy
    tr = ctx.traffic
    return CaseStudy(cfg=arch_config(ctx.config), chunk=tr["chunk"],
                     plan=tr["plan"], codec=tr["codec"],
                     inner_lr=tr["inner_lr"], outer_lr=tr["outer_lr"],
                     fl_lr=tr["fl_lr"], inner_steps=tr["inner_steps"],
                     fl_local_steps=tr["fl_local_steps"],
                     epsilon=tr["epsilon"], r_target=tr["r_target"],
                     telemetry=tel)


def first_steps(cs, ctx) -> dict:
    """The object's first steps from the seed, as the check reads them."""
    tr = ctx.traffic
    kmeta, kfl = check_keys(ctx.seed)
    w, meta_loss = cs.meta_train(kmeta, tr["check_meta_rounds"])
    got = {"meta_w": _host(w), "meta_loss": list(meta_loss), "fl": []}
    for task in range(6):
        st, used, Rs = cs.adapt_task(kfl[task], task, w, max_rounds=1)
        got["fl"].append({"w": _host(st), "R": list(Rs), "t_i": used})
    return got


def reference_steps(ctx, dtype: str = "float32", store: str = "float32",
                    fault: str | None = None) -> dict:
    from bench.reference import casestudy as ref_cs
    from bench.reference.precision import rounder
    tr = ctx.traffic
    model = harness.load_module(harness.BENCH / "reference"
                                / f"{ctx.config_name}.py")
    proto = ref_cs.Protocol(model, ctx.config, tr, rnd=rounder(dtype),
                            store=rounder(store))
    if fault is not None:
        plant_reference_fault(proto, fault)
    kmeta, kfl = check_keys(ctx.seed)
    w0, w, meta_loss = proto.meta_train(kmeta, tr["check_meta_rounds"])
    got = {"w0": _host(w0), "meta_w": _host(w), "meta_loss": meta_loss,
           "fl": [], "tie_margin": []}
    for task in range(6):
        outcomes, margin = proto.first_round(kfl[task], task, w,
                                             tr["tie_margin"])
        got["fl"].append([{"w": _host(st), "R": R} for st, R in outcomes])
        got["tie_margin"].append(margin)
    return got


def plant_reference_fault(proto, fault: str):
    """The faults a check must catch, planted in the reference."""
    if fault == "half_batch":
        td = proto.td_loss
        proto.td_loss = lambda p, t, b: td(
            p, t, jax.tree.map(lambda x: x[: x.shape[0] // 2], b))
    elif fault == "no_exchange":
        proto.mix = jnp.eye(proto.p["robots"], dtype=jnp.float32)
    elif fault == "reward":
        proto.rewards = proto.rewards.at[:, 1, 2].add(1.0)
    elif fault == "fl_lr_half":
        proto.p = dict(proto.p, fl_lr=proto.p["fl_lr"] / 2)
    elif fault == "fl_one_step":
        proto.p = dict(proto.p, fl_local_steps=1)
    elif fault == "fl_no_clip":
        proto.p = dict(proto.p, fl_clip=float("inf"))
    else:
        raise ValueError(f"unknown fault {fault!r}")
    proto.meta_round = jax.jit(proto._meta_round)
    proto.fl_round = jax.jit(proto._fl_round)


def compare(prog: dict, ref: dict) -> dict:
    """The compared numbers. Each task's FL round is judged against the
    closest of the reference's outcomes of it (more than one where a
    greedy decision lay within ``tie_margin`` of a tie). A reference
    in the program's place (the control, a fault) stands by its round as
    computed."""
    change, mix = [], []
    for p, outcomes in zip(prog["fl"], ref["fl"]):
        if isinstance(p, list):
            p = p[0]
        ch_p = _change(_mean0(p["w"]), prog["meta_w"])
        sp_p = _spread(p["w"])
        best_c, best_m = math.inf, math.inf
        for r in outcomes:
            ch_r = _change(_mean0(r["w"]), ref["meta_w"])
            floor = statistics.median(ch_r)
            best_c = min(best_c, harness.worst_leaf_gap(
                ch_p, ch_r, floor=floor)[0])
            best_m = min(best_m, harness.worst_leaf_gap(
                sp_p, _spread(r["w"]), grad_ref=ch_r, floor=floor)[0])
        change.append(best_c)
        mix.append(best_m)
    w0 = ref["w0"]
    return {
        "meta_loss": harness.rel_gap(
            prog["meta_loss"], ref["meta_loss"],
            floor=statistics.median(abs(x) for x in ref["meta_loss"])),
        "meta_change": harness.worst_leaf_gap(
            _change(prog["meta_w"], w0), _change(ref["meta_w"], w0))[0],
        "fl_change": max(change),
        "fl_mix": statistics.median(mix),
    }


def t_i_errors(results, tr) -> int:
    """Tasks of finished processes whose t_i is not what the rule
    makes of the greedy running rewards the process reported: the
    first round whose reward reaches ``r_target``, or ``max_rounds``;
    and which did not report one reward per round up to it."""
    bad = 0
    for res in results:
        for t_i, hist in zip(res.rounds_per_task, res.fl_histories):
            hit = [i for i, R in enumerate(hist) if R >= tr["r_target"]]
            want = hit[0] + 1 if hit else tr["max_rounds"]
            bad += int(t_i != want or len(hist) != want)
    return bad


def run(ctx) -> dict:
    tr = ctx.traffic
    window = harness.Window(ctx.clock, ctx.seconds, ctx.compiles,
                            ctx.trace_dir, ctx.traffic["trace_seconds"])
    per = {"maml": [0.0, 0], "fl": [0.0, 0]}
    last = [None]

    def on_chunk(driver, start, events):
        t = ctx.clock.now()
        if not window.is_open:
            return
        live = sum(1 for e in events if e.get("live", True))
        per[driver][0] += t - last[0]
        per[driver][1] += live
        window.chunk(live)
        # after the stamp, which may stop the profiler: the trace's
        # write-out is no chunk's host time
        last[0] = ctx.clock.now()

    tel = harness.chunk_telemetry(on_chunk)
    cs = case_study(ctx, tel)
    prog = first_steps(cs, ctx)
    # one whole process more, so that every program and host-side op of
    # the window (later chunk offsets, the per-task key splits) is warm
    cs.run(jax.random.fold_in(jax.random.PRNGKey(0), 0), tr["t0"],
           max_rounds=tr["max_rounds"])
    window.open()
    last[0] = ctx.clock.window_start
    keys = process_keys(tr["processes"], ctx.seed)
    results = []
    while not window.expired():
        for k in keys:
            with harness.span("bench.process"):
                results.append(cs.run(k, tr["t0"],
                                      max_rounds=tr["max_rounds"]))
    processes = len(results)
    end = ctx.clock.now()
    window.close()
    device = harness.device_info(ctx.devs)
    clock = ctx.clock
    span_s = end - clock.window_start
    rounds = clock.rounds()
    chunk_ms = clock.chunk_ms()
    e2e = {"setup_s": clock.setup_s,
           "round_ms": span_s * 1e3 / rounds,
           "mtl_s": span_s / processes,
           "peak_hbm_gb": device["memory_peak_bytes"] / 1e9}
    if len(chunk_ms) >= 200:
        e2e["chunk_ms_p95"] = harness.p95(chunk_ms)
    host = {"meta_round_ms": per["maml"][0] * 1e3 / max(per["maml"][1], 1),
            "fl_round_ms": per["fl"][0] * 1e3 / max(per["fl"][1], 1)}
    del cs, tel
    ref = reference_steps(ctx)
    checks = compare(prog, ref)
    checks["t_i_errors"] = t_i_errors(results, tr)
    return {"e2e": e2e, "checks": checks, "device": device,
            "window": window, "attempted": processes, "failed": 0,
            "host": host}


#: the control and the faults the calibration reads (bench/calibrate.py);
#: a state left unchanged reads 1 on ``meta_change`` by construction
CONTROLS = {"control": {"dtype": "bfloat16", "store": "bfloat16"},
            "fault:half_batch": {"fault": "half_batch"},
            "fault:no_exchange": {"fault": "no_exchange"},
            "fault:reward": {"fault": "reward"},
            "fault:fl_lr_half": {"fault": "fl_lr_half"},
            "fault:fl_one_step": {"fault": "fl_one_step"},
            "fault:fl_no_clip": {"fault": "fl_no_clip"}}


def _late(first_hit):
    return lambda hits: (None if first_hit(hits) is None
                         else first_hit(hits) + 1)


#: faults planted in the program for the number that only finished
#: processes give (``t_i_errors``): t_i one round late, and the reached
#: flag never seen by the host
PROCESS_FAULTS = {"t_i_late": _late, "hit_unseen": lambda f: lambda h: None}


def process_readings(ctx) -> dict:
    """``t_i_errors`` of one whole process of the window's pool (the
    first in the seed's order), sound and with each of
    ``PROCESS_FAULTS`` planted (``bench/calibrate.py``)."""
    from repro.core import scanloop
    tr = ctx.traffic
    cs = ctx.case_study
    key = process_keys(tr["processes"], ctx.seed)[0]

    def one():
        return t_i_errors([cs.run(key, tr["t0"],
                                  max_rounds=tr["max_rounds"])], tr)

    out = {"program": one()}
    orig = scanloop.first_hit
    for name, plant in PROCESS_FAULTS.items():
        scanloop.first_hit = plant(orig)
        try:
            out["fault:" + name] = one()
        finally:
            scanloop.first_hit = orig
    return out


def first_steps_of(ctx):
    if getattr(ctx, "case_study", None) is None:
        ctx.case_study = case_study(ctx, harness.chunk_telemetry(
            lambda *a: None))
    return first_steps(ctx.case_study, ctx)


def reference_of(ctx, **kw):
    return reference_steps(ctx, **kw)


def compare_of(ctx, prog, ref):
    return compare(prog, ref)
