"""Training launcher.

Two modes (the paper's contribution is a first-class feature, not a demo):

* ``standard``  — synchronous data/tensor-parallel training: one jitted
  step, grads averaged over the data axes (GSPMD inserts the all-reduce).
* ``federated`` — the paper's decentralized protocol at LM scale: the
  data axis is a population of AGENTS, each holding its own replica and
  task-conditioned data stream; agents take ``local_steps`` SGD steps per
  round then run one Eq.-(6) consensus mixing step with their cluster
  neighbours (ring over the ICI). No parameter server, no global
  all-reduce — exactly the communication pattern Eqs. (10)–(11) price.

Host execution uses whatever devices exist (tests/examples: 1 CPU);
the production mesh path is exercised by dryrun.py.

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch granite-8b \
        --reduced --steps 20 --mode federated --agents 4 --tasks 2
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, reduced
from repro.core import energy
from repro.core import topology as topo_lib
from repro.core.engine import (PLAN_KINDS, AsyncState, ConsensusEngine,
                               where_active)
from repro.data import TaskTokenDistribution
from repro.launch import compile_cache
from repro.launch import steps as steps_lib
from repro.models import frontend
from repro.models.api import get_model, lm_loss
from repro.optim import adam, apply_updates, clip_by_global_norm


def train_standard(cfg, *, steps: int, batch: int, seq: int, lr: float,
                   log_every: int = 5, seed: int = 0):
    model = get_model(cfg)
    key = jax.random.PRNGKey(seed)
    params = model.init(key, cfg)
    opt = adam(lr)
    opt_state = opt.init(params)
    dist = TaskTokenDistribution(vocab_size=cfg.vocab_size, num_tasks=1)

    def loss_fn(p, batch_d):
        return lm_loss(p, cfg, batch_d["tokens"], batch_d["labels"],
                       embeddings=batch_d.get("frames"), model=model)

    @jax.jit
    def step(params, opt_state, batch_d):
        l, g = jax.value_and_grad(loss_fn)(params, batch_d)
        g, gn = clip_by_global_norm(g, 1.0)
        upd, opt_state = opt.update(g, opt_state, params)
        return apply_updates(params, upd), opt_state, l, gn

    hist = []
    for t in range(steps):
        key, sk = jax.random.split(key)
        toks, labels = dist.sample(sk, 0, batch, seq)
        bd = {"tokens": toks, "labels": labels}
        if cfg.family == "encdec":
            bd["frames"] = frontend.audio_frame_embeddings(sk, cfg, batch)
        t0 = time.time()
        params, opt_state, l, gn = step(params, opt_state, bd)
        hist.append(float(l))
        if t % log_every == 0:
            print(f"step {t:4d}  loss {float(l):.4f}  gnorm {float(gn):.3f}"
                  f"  {time.time() - t0:.2f}s")
    return params, hist


def train_federated(cfg, *, rounds: int, agents: int, tasks: int,
                    local_steps: int, batch: int, seq: int, lr: float,
                    consensus_every: int = 1, seed: int = 0,
                    energy_params=None, consensus_dtype=None,
                    consensus_plan: str = "auto", codec=None, mesh=None,
                    chunk: int = 1, dropout_p: float = 0.0,
                    dropout_seed: int = 0, availability=None,
                    tau=None, staleness_decay: float = 1.0,
                    telemetry=None, metrics_path=None):
    """Clustered federated LM training (the paper's stage-2 at LM scale).

    ``agents`` agents form ``tasks`` clusters (agents/tasks per cluster);
    consensus only mixes within a cluster (per-task Topology) through one
    :class:`repro.core.engine.ConsensusEngine` — ``consensus_plan``
    picks the execution plan ("auto", "dense-xla", "sparse-pallas",
    "sharded", "distributed"; a ``mesh`` with an ``agents`` axis enables
    the multi-position plans). Returns (stacked_params, per_round losses,
    energy J). ``consensus_dtype``: cast exchanged models (e.g. bf16) —
    halves the sidelink bytes of Eq. (11); EXPERIMENTS.md §Perf P3.
    ``codec`` (spec string, :mod:`repro.comms`) supersedes it: the
    exchange runs through the codec (error feedback for lossy ones) and
    the Eq.-(11) estimate prices the codec's wire bits instead of the
    storage dtype. ``codec="auto"`` picks the wire format from the
    graph's bottleneck link efficiency (:func:`repro.comms.select_codec`).
    ``chunk`` compiles that many FL rounds into one ``lax.scan`` program
    (loss history synced per chunk, bit-identical to ``chunk=1`` — the
    per-round host loop); the chunk program donates the stacked params +
    EF-residual buffers where the backend supports donation, so the
    agent population updates in place. ``dropout_p > 0`` attaches a
    :class:`repro.core.topology.GraphProcess` to the engine: every FL
    round mixes over that round's SURVIVING sidelinks, with the masks
    generated in-scan from the folded ``dropout_seed`` key (any maskable
    plan; the modeled Eq.-(11) estimate still prices the full graph —
    an upper bound under fading).

    ``telemetry`` (:class:`repro.telemetry.Telemetry`) records one row
    per round — Eq.-(11) joules by link class over the round's ACTUAL
    surviving links, wire bits, disagreement — synced once per chunk
    (buffered; streaming mode also emits live via
    ``jax.debug.callback``). ``metrics_path`` is the shorthand the
    ``--metrics out.jsonl`` CLI flag uses: a buffered Telemetry with a
    JSONL sink is created (and closed) here, giving a round-by-round
    energy ledger that a dropout run's summed stream reconciles with
    exactly. Loss curves and params are bit-identical with telemetry
    off, buffered, or streaming.
    """
    assert agents % tasks == 0
    per = agents // tasks

    # the population graph (per-task SL clusters) drives the Eq.-(6)
    # mixing weights, the engine plan, AND the Eq.-(11) link pricing
    topo = topo_lib.clusters(tasks, per)
    ep = energy_params or energy.paper_calibrated("fig3")
    if codec is not None:
        from repro import comms
        codec = (comms.select_codec(topo, ep) if codec == "auto"
                 else comms.resolve_codec(codec))
        consensus_dtype = None        # the codec defines the wire format
    graph = (topo_lib.GraphProcess.dropout(dropout_p, seed=dropout_seed)
             if dropout_p > 0 else None)
    # ``availability`` (repro.core.topology.AgentProcess) makes the run
    # ASYNCHRONOUS: every round each agent independently wakes or
    # sleeps, sleeping agents skip local SGD and mixing (their params /
    # EF residuals freeze bitwise), awake receivers mix a neighbour's
    # last-published params staleness-weighted (decay^age, dropped past
    # ``tau`` rounds), and the telemetry ledger bills only wires
    # actually DELIVERED. always_on/tau=None reduces to the lockstep
    # run bit-identically.
    engine = ConsensusEngine(topo, codec=codec, mesh=mesh,
                             plan=consensus_plan, graph=graph,
                             agents=availability, tau=tau,
                             staleness_decay=staleness_decay)
    codec = engine.codec
    is_async = engine.agents is not None

    model = get_model(cfg)
    key = jax.random.PRNGKey(seed)
    params = model.init(key, cfg)
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (agents,) + x.shape), params)
    dist = TaskTokenDistribution(vocab_size=cfg.vocab_size, num_tasks=tasks)
    task_of_agent = jnp.arange(agents, dtype=jnp.int32) // per

    def loss_fn(p, b):
        return lm_loss(p, cfg, b["tokens"], b["labels"], model=model)

    def local(p, b):
        def one(p, bb):
            g = jax.grad(loss_fn)(p, bb)
            g, _ = clip_by_global_norm(g, 1.0)
            return jax.tree.map(
                lambda w, gw: (w.astype(jnp.float32) - lr
                               * gw.astype(jnp.float32)).astype(w.dtype),
                p, g), None
        p, _ = jax.lax.scan(one, p, b)
        return p

    def fl_round(stacked, codec_state, key, t, survival=None,
                 active=None):
        # same split as the pre-codec trainer — codec=None runs keep
        # their exact RNG stream (reproducible loss curves); the codec
        # rounding key is folded out of band
        ks = jax.random.split(key, agents)

        def agent_batches(k, task):
            def sample_one(kk):
                toks, labels = dist.sample_traced(kk, task, batch, seq)
                return {"tokens": toks, "labels": labels}
            return jax.vmap(sample_one)(jax.random.split(k, local_steps))

        batches = jax.vmap(agent_batches)(ks, task_of_agent)
        new = jax.vmap(local)(stacked, batches)
        if active is not None:
            # sleeping agents skip local SGD (bitwise hold)
            new = where_active(active, new, stacked)
        pre = new
        # survival= (telemetry shares one plan-shaped draw with its
        # metrics row) takes precedence over t= inside step — identical
        # ops either way
        if codec is not None:
            old_state = (codec_state if codec_state is not None
                         else engine.init_state(pre))
            new, codec_state = engine.step(
                new, codec_state, jax.random.fold_in(key, agents + 1),
                t=t, survival=survival)
            if active is not None and codec_state is not None:
                # sleeping agents' EF residuals hold too
                codec_state = where_active(active, codec_state, old_state)
        elif consensus_dtype is not None:
            cast = jax.tree.map(
                lambda x: x.astype(consensus_dtype), new)
            mixed, _ = engine.step(cast, t=t, survival=survival)
            new = jax.tree.map(lambda m, n: m.astype(n.dtype), mixed, new)
        else:
            new, _ = engine.step(new, t=t, survival=survival)
        if active is not None:
            # sleeping receivers don't mix
            new = where_active(active, new, pre)
        # mean loss of agent 0's task for logging
        l = loss_fn(jax.tree.map(lambda x: x[0], new),
                    jax.tree.map(lambda x: x[0][0], batches))
        return new, codec_state, l

    # the one compiled round-loop program (chunk=1 == the legacy host
    # loop, one dispatch + sync per round; chunk=N syncs once per chunk;
    # stacked params + EF residuals donated where supported)
    from repro.core import scanloop

    def fl_body(carry, t):
        stacked, codec_state, key, astate = carry
        key, sk = jax.random.split(key)
        if is_async:
            # one availability draw per round, shared between the
            # staleness weights, the per-agent freeze, and the
            # telemetry row (billing only DELIVERED wires)
            ar = engine.async_round(t, astate.age)
            sv, act, sv_row = ar.weights, ar.act, ar.delivered
        else:
            ar, act = None, None
            sv = engine.round_survival(t) if tel is not None else None
            sv_row = sv
        stacked, codec_state, l = fl_round(stacked, codec_state, sk, t,
                                           sv, act)
        if is_async:
            astate = AsyncState(
                astate.clock + ar.act.astype(astate.clock.dtype),
                ar.age)
        if tel is None:
            return (stacked, codec_state, key, astate), l
        row = rec.row(stacked, sv_row, metric=l,
                      reached=jnp.asarray(False), live=jnp.asarray(True),
                      active=act, age=(ar.age if is_async else None))
        if stream_cb is not None:
            jax.debug.callback(stream_cb, t, row, ordered=True)
        return (stacked, codec_state, key, astate), (l, row)

    # astate is None on lockstep runs (an empty pytree through the scan
    # carry) and the engine's AsyncState on async runs — clocks/ages
    # persist ACROSS chunks like the params
    fl_chunk = scanloop.donating_jit(
        lambda s, cs, k, ast, ts: jax.lax.scan(
            fl_body, (s, cs, k, ast), ts),
        donate_argnums=(0, 1))

    n_params = sum(x.size for x in jax.tree.leaves(params))
    n_bytes = sum(x.size * (2 if consensus_dtype is not None
                            else x.dtype.itemsize)
                  for x in jax.tree.leaves(params))
    # with a codec, b(W) is the FULL-PRECISION reference size (32-bit per
    # param) that price_bits discounts — deriving it from the storage
    # itemsize would double-discount bf16-stored models; without a codec
    # the wire IS the storage (or consensus_dtype) bytes
    model_bits = (32.0 * n_params if codec is not None
                  else float(n_bytes) * 8)
    import dataclasses as dc
    ep = dc.replace(ep, model_bits=model_bits,
                    devices_per_cluster=per, B_i=local_steps)
    # one cluster's graph: per·(per−1) directed SL messages per round —
    # NOT the legacy devices_per_cluster × neighbors_per_device constant,
    # which under-priced any cluster larger than 2 robots
    cluster_topo = topo_lib.clusters(1, per)

    from repro import telemetry as telemetry_lib
    tel = telemetry
    own_tel = tel is None and metrics_path is not None
    if own_tel:
        tel = telemetry_lib.Telemetry(
            sinks=(telemetry_lib.JsonlSink(metrics_path),))
    # the recorder bills with THIS run's calibrated ep (wire-format
    # model_bits baked above), over the round's actual surviving links
    rec = tel.recorder_for(engine, ep) if tel is not None else None
    stream_cb = (tel.stream_cb(rec, "fl")
                 if tel is not None and tel.streaming else None)

    codec_state = (codec.init_state(stacked)
                   if codec is not None and codec.stateful else None)
    # own(): fl_chunk donates the stacked/EF carries on donating backends
    stacked = scanloop.own(stacked)
    codec_state = scanloop.own(codec_state)
    astate = engine.init_async_state() if is_async else None
    hist = []
    chunk = max(int(chunk), 1)
    for start in range(0, rounds, chunk):
        n = min(chunk, rounds - start)
        ts = jnp.arange(start, start + n, dtype=jnp.int32)
        (stacked, codec_state, key, astate), ls = fl_chunk(
            stacked, codec_state, key, astate, ts)
        if tel is not None:
            ls, rows = ls
            tel.record_rounds(rec, rows, start, driver="fl")
        for r, l in enumerate(np.asarray(ls), start):   # one sync/chunk
            hist.append(float(l))
            print(f"round {r:3d}  loss {float(l):.4f}")
    # Eq.-(11) priced at the codec's wire size (b(W) · bits ratio)
    E = tasks * energy.fl_energy(ep, rounds, topology=cluster_topo,
                                 codec=codec)
    wire_mb = (codec.price_bits(model_bits) / 8e6 if codec is not None
               else n_bytes / 1e6)
    print(f"estimated FL energy for {rounds} rounds x {tasks} clusters: "
          f"{E / 1e3:.2f} kJ ({wire_mb:.2f} MB per exchange"
          f"{', codec ' + codec.name if codec is not None else ''})")
    if tel is not None:
        n_ev = len(tel.events(driver="fl"))
        print(f"telemetry: {n_ev} round events, measured comm energy "
              f"{tel.joules() / 1e3:.2f} kJ (per-round Eq.-11 ledger)")
        if own_tel:
            tel.close()
    return stacked, hist, E


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mode", choices=["standard", "federated"],
                    default="standard")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--agents", type=int, default=4)
    ap.add_argument("--tasks", type=int, default=2)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--bf16-consensus", action="store_true")
    ap.add_argument("--consensus-plan",
                    choices=["auto"] + list(PLAN_KINDS), default="auto",
                    help="consensus execution plan (repro.core.engine)")
    ap.add_argument("--codec", default=None,
                    help="model-exchange codec spec (bf16, int8, int4, "
                         "int8:b64 block scales, topk:0.05, +ef suffix; "
                         "'auto' picks from link quality; see repro.comms)")
    ap.add_argument("--chunk", type=int, default=1,
                    help="FL rounds per compiled scan program (1 = "
                         "per-round host loop; larger chunks sync once "
                         "per chunk, bit-identical results)")
    ap.add_argument("--dropout-p", type=float, default=0.0,
                    help="per-round sidelink failure probability: each "
                         "FL round mixes over that round's surviving "
                         "links, masks generated in-scan "
                         "(repro.core.topology.GraphProcess)")
    ap.add_argument("--dropout-seed", type=int, default=0)
    ap.add_argument("--availability-p", type=float, default=None,
                    help="per-round agent wake probability: attaches a "
                         "Bernoulli AgentProcess — sleeping agents skip "
                         "local SGD and mixing, receivers mix stale "
                         "neighbour params (repro.core.topology)")
    ap.add_argument("--availability-seed", type=int, default=0)
    ap.add_argument("--tau", type=float, default=None,
                    help="hard staleness bound: wires older than tau "
                         "rounds stop mixing (sigma renormalizes); "
                         "default None = unbounded")
    ap.add_argument("--staleness-decay", type=float, default=1.0,
                    help="per-round age decay of stale-wire mixing "
                         "weight (lambda**age; 1.0 keeps full weight)")
    ap.add_argument("--metrics", default=None, metavar="OUT.JSONL",
                    help="write a per-round telemetry event log (JSONL; "
                         "Eq.-11 joules by link class, wire bits, "
                         "disagreement — see repro.telemetry.schema)")
    args = ap.parse_args()
    compile_cache.enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.mode == "standard":
        train_standard(cfg, steps=args.steps, batch=args.batch,
                       seq=args.seq, lr=args.lr)
    else:
        train_federated(
            cfg, rounds=args.rounds, agents=args.agents, tasks=args.tasks,
            local_steps=args.local_steps, batch=args.batch, seq=args.seq,
            lr=args.lr,
            consensus_dtype=jnp.bfloat16 if args.bf16_consensus else None,
            consensus_plan=args.consensus_plan, codec=args.codec,
            chunk=args.chunk, dropout_p=args.dropout_p,
            dropout_seed=args.dropout_seed,
            availability=(topo_lib.AgentProcess.bernoulli(
                args.availability_p, seed=args.availability_seed)
                if args.availability_p is not None else None),
            tau=args.tau, staleness_decay=args.staleness_decay,
            metrics_path=args.metrics)


if __name__ == "__main__":
    main()
