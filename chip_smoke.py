"""Smoke run of the system's main path on a TPU, through the entry points
a user calls, at published model widths with random weights from fixed
seeds. Everything runs in this one process; it starts no other.

Phases (one chip, no arguments):

  (a) casestudy  the paper's MTL case study (``rl/casestudy.CaseStudy``)
                 with the ``paper-dqn`` Q-network and 2-robot clusters:
                 16 meta rounds (``meta_train``), then one task's FL
                 adaptation for up to 16 rounds (``adapt_task``) at
                 chunk=8 — once with ``plan="auto"`` (dense-xla) and once
                 with ``plan="sparse-pallas"`` over the ``int8:b64`` wire,
                 which runs the fused dequant-consensus kernel.
  (b) federated  ``launch.train.train_federated`` on ``xlstm-125m`` at
                 its published width: 4 agents in one task,
                 ``sparse-pallas`` over ``int8:b64``, 2 rounds in one
                 chunk of 2, one local step of batch 1 x 128 tokens.
  (c) parity     one ``sparse-pallas`` round at the ``paper-dqn`` leaf
                 widths (12 agents on a ring), f32 and ``int8:b64``,
                 against the per-agent oracles of ``repro.kernels.ref``.

``--four-chips`` runs only

  (d) mesh       a ``sharded`` round at K=64 and a ``distributed`` round at
                 K=4 (``paper-dqn`` payload, ``int8:b64``) on a 4-position
                 agent mesh, each against the same engine without a mesh
                 on one device; the compiled program must hold the plan's
                 wire collective and the result must stay on 4 devices.

Every phase checks its own result (finite losses and params, no host
callback in any compiled round program, the Pallas kernel present in the
sparse programs, parity within ``PARITY_TOL``) and any failure ends the
run with a non-zero exit. It times nothing: ``bench/run.py`` measures.
The last line of standard output is one JSON object naming the
device.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

#: largest |kernel − oracle| accepted, in parameter units (the payloads'
#: weights are O(0.1); an f32 combine of ≤ 3 terms errs by ~1e-8)
PARITY_TOL = 1e-5
SEED = 0


def say(msg: str):
    print(f"[smoke] {msg}", flush=True)


def check(ok, msg: str):
    if not ok:
        raise RuntimeError(f"smoke check failed: {msg}")


def all_finite(tree) -> bool:
    return all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(tree))


def peak_gb() -> float:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 1e9


def audit_programs(records, *, kernel: bool):
    """No compiled round program holds a host callback; with ``kernel``,
    some program holds the Pallas TPU kernel."""
    from repro.analysis.jaxpr_audit import find_callbacks
    dispatched = [r for r in records if r.abstract_args is not None]
    check(dispatched, "no round program was dispatched")
    lowered = []
    for rec in dispatched:
        traced = rec.jitted.trace(*rec.abstract_args)
        cbs = find_callbacks(traced.jaxpr)
        check(not cbs, f"host callback in {rec.name}: {cbs}")
        lowered.append(traced.lower().as_text())
    if kernel:
        check(any("tpu_custom_call" in t for t in lowered),
              "no Pallas kernel in the compiled round programs")
    return len(dispatched)


def stack_agents(cfg, K: int, seed: int):
    from repro.models import dqn as qmodel
    keys = jax.random.split(jax.random.PRNGKey(seed), K)
    return jax.vmap(lambda k: qmodel.init(k, cfg))(keys)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_casestudy(cfg, *, plan: str, codec, want_plan: str, t0: int = 16,
                    rounds: int = 16, chunk: int = 8):
    from repro.core import scanloop
    from repro.rl.casestudy import CaseStudy
    name = f"casestudy plan={plan} codec={codec}"
    kmeta, kfl = jax.random.split(jax.random.PRNGKey(SEED))
    with scanloop.retained_programs() as records:
        cs = CaseStudy(cfg=cfg, plan=plan, codec=codec, chunk=chunk)
        check(cs.engine.plan.kind == want_plan,
              f"{name} resolved {cs.engine.plan.kind}, expected {want_plan}")
        params, meta_hist = cs.meta_train(kmeta, t0)
        stacked, t_i, rewards = cs.adapt_task(kfl, 0, params,
                                              max_rounds=rounds)
    check(len(meta_hist) == t0 and all(np.isfinite(meta_hist)),
          f"{name}: meta losses {meta_hist}")
    check(rewards and all(np.isfinite(rewards)), f"{name}: rewards {rewards}")
    check(all_finite(params) and all_finite(stacked),
          f"{name}: non-finite params")
    n = audit_programs(records, kernel=want_plan == "sparse-pallas")
    say(f"{name}: plan {cs.engine.plan.kind}, K={cs.engine.K} per task, "
        f"meta loss {meta_hist[0]:.6g} -> {meta_hist[-1]:.6g}, "
        f"t_i {t_i} of {rounds} rounds, reward {rewards[-1]:.6g}, "
        f"{n} programs audited, peak {peak_gb():.3f} GB")


def phase_federated(cfg, *, agents: int = 4, rounds: int = 2,
                    chunk: int = 2, seq: int = 128):
    from repro.core import scanloop
    from repro.launch.train import train_federated
    name = f"federated {cfg.name} K={agents}"
    with scanloop.retained_programs() as records:
        stacked, hist, _ = train_federated(
            cfg, rounds=rounds, agents=agents, tasks=1, local_steps=1,
            batch=1, seq=seq, lr=1e-3, consensus_plan="sparse-pallas",
            codec="int8:b64", chunk=chunk)
    check(len(hist) == rounds and all(np.isfinite(hist)),
          f"{name}: losses {hist}")
    check(all_finite(stacked), f"{name}: non-finite params")
    n_params = sum(x.size for x in jax.tree.leaves(stacked)) // agents
    n = audit_programs(records, kernel=True)
    say(f"{name}: {n_params} params/agent, losses "
        f"{', '.join(f'{l:.6g}' for l in hist)}, "
        f"{n} programs audited, peak {peak_gb():.3f} GB")


def phase_parity(cfg, *, K: int = 12):
    from repro.core import consensus
    from repro.core import topology as topo_lib
    from repro.core.engine import ConsensusEngine
    from repro.kernels import ref
    topo = topo_lib.ring(K)
    stacked = stack_agents(cfg, K, SEED + 1)
    idx, sig = (jnp.asarray(a) for a in
                consensus.sparse_structure(topo.mixing()))
    widths = sorted({x[0].size for x in jax.tree.leaves(stacked)})

    def oracle(params, wire):
        """Per-agent ref oracle of one round, fed the same wire."""
        out = []
        for x in jax.tree.leaves(params):
            x = x.reshape(K, -1)
            if wire is None:
                out.append(jax.vmap(ref.consensus_update_reference)(
                    x, x[idx], sig))
                continue
            enc = jax.vmap(lambda m: wire.encode_leaf(m, None))(x)
            q, s = enc["q"], enc["scale"]
            out.append(jax.vmap(
                lambda *a: ref.quant_consensus_update_reference(
                    *a, qblock=wire.block))(x, q, s, q[idx], s[idx], sig))
        return out

    for codec in (None, "int8:b64"):
        eng = ConsensusEngine(topo, codec=codec, plan="sparse-pallas",
                              error_feedback=False)
        # one program: the round and its oracle share the encode, so the
        # comparison isolates the combine
        both = jax.jit(lambda p, e=eng: (
            [y.reshape(K, -1) for y in jax.tree.leaves(e.step(p)[0])],
            oracle(p, e.codec)))
        with jax.default_matmul_precision("float32"):
            check("tpu_custom_call" in both.lower(stacked).as_text(),
                  f"parity {codec}: no Pallas kernel in the round")
            out, want = both(stacked)
        err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(out, want))
        say(f"parity sparse-pallas {codec or 'f32'} K={K} leaf widths "
            f"{widths}: max |kernel - oracle| = {err:.3e} "
            f"(tolerance {PARITY_TOL:.0e})")
        check(err <= PARITY_TOL, f"parity {codec}: error {err}")


def phase_mesh(cfg):
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.core import topology as topo_lib
    from repro.core.engine import PLAN_AUDIT_EXPECTATIONS, ConsensusEngine
    from repro.launch.mesh import make_agent_mesh
    mesh = make_agent_mesh(4)
    for plan, K in (("sharded", 64), ("distributed", 4)):
        topo = topo_lib.ring(K)
        stacked = stack_agents(cfg, K, SEED + 2)
        on_mesh = ConsensusEngine(topo, codec="int8:b64", mesh=mesh,
                                  plan=plan)
        one_dev = ConsensusEngine(topo, codec="int8:b64", plan=plan,
                                  num_blocks=on_mesh.plan.num_blocks)
        check(on_mesh.plan.num_blocks == (4 if plan == "sharded" else 1),
              f"{plan}: {on_mesh.plan}")
        placed = jax.device_put(
            stacked, NamedSharding(mesh, PartitionSpec("agents")))
        compiled = jax.jit(lambda p: on_mesh.step(p)[0]).lower(
            placed).compile()
        wire = PLAN_AUDIT_EXPECTATIONS[plan]["wire_collective"]
        check(wire in compiled.as_text(), f"{plan}: no {wire} in the HLO")
        got = compiled(placed)
        for leaf in jax.tree.leaves(got):
            check(len(leaf.sharding.device_set) == 4
                  and not leaf.sharding.is_fully_replicated,
                  f"{plan}: output sharding {leaf.sharding}")
        local = jax.device_put(stacked, jax.devices()[0])
        want = jax.jit(lambda p: one_dev.step(p)[0])(local)
        err = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                  for a, b in zip(jax.tree.leaves(got),
                                  jax.tree.leaves(want)))
        say(f"mesh {plan} K={K} int8:b64: {wire} present, output on "
            f"{len(jax.tree.leaves(got)[0].sharding.device_set)} devices, "
            f"max |mesh - one device| = {err:.3e} (tolerance "
            f"{PARITY_TOL:.0e})")
        check(err <= PARITY_TOL, f"{plan}: mesh vs one device error {err}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded/distributed phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2

    from repro.configs import get_arch
    from repro.launch.compile_cache import enable_compile_cache
    say(f"compile cache {enable_compile_cache()}")
    say(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    dqn = get_arch("paper-dqn")
    if args.four_chips:
        phase_mesh(dqn)
    else:
        phase_casestudy(dqn, plan="auto", codec=None, want_plan="dense-xla")
        phase_casestudy(dqn, plan="sparse-pallas", codec="int8:b64",
                        want_plan="sparse-pallas")
        phase_federated(get_arch("xlstm-125m"))
        phase_parity(dqn)
    say("all phases passed")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
