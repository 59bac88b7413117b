"""The paper's Sect. IV case study, wired end-to-end:

* M = 6 trajectory tasks, 2-robot clusters (ClusterNetwork);
* MAML meta-training on Q = 3 tasks {τ1, τ2, τ6} (Fig. 2(c)) at the
  "data center";
* per-cluster decentralized FL (Eq. 6) adaptation measuring t_i = rounds
  to reach the running-reward target;
* energy accounting with the paper-calibrated constants.

Experience follows the paper's Sect. IV-A budget: each robot gathers ONE
20-motion ε-greedy episode per round (ε = 0.1, b(E_ik) = 20 consecutive
motions) and takes B_i = 20 local SGD minibatch steps on it. The ε-greedy
behaviour is wrapped around the agent's own current Q — this is exactly
why a good meta-initialization cuts t_i: it walks on-trajectory from
round one, while a random init explores blindly. CHUNKS of ``chunk``
protocol rounds (sampling + local SGD + consensus + greedy evaluation,
each) compile into ONE ``lax.scan`` XLA program; the host checks the
per-round reached-target flags once per chunk and recovers the exact t_i
from the in-scan reached mask (a ``lax.cond`` freezes the population
after the hit), which is what makes Monte-Carlo sweeps over t0 tractable
on CPU — O(rounds/chunk) dispatches and syncs instead of O(rounds).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field, replace
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro import comms
from repro.core import energy, maml, scanloop
from repro.core import topology as topo_lib
from repro.core.engine import AsyncState, ConsensusEngine, where_active
from repro.core.multitask import ClusterNetwork
from repro.core.protocol import ProtocolResult
from repro.models import dqn as qmodel
from repro.rl import dqn as dqnrl
from repro.rl import gridworld as gw
from repro.telemetry import spans

META_TASKS = (0, 1, 5)        # {τ1, τ2, τ6} of Fig. 2(c)
R_TARGET = 100.0              # running-reward target (paper: R = 50 in its
                              # own reward units; ours rescale — DESIGN.md §7)


def behaviour_rollout(key, task_id: int, *, steps: int = 20,
                      batch: int = 8):
    """Random-walk behaviour policy (ε = 1), task-dependent rewards only."""
    pos0 = jnp.broadcast_to(jnp.asarray(gw.ENTRY, jnp.int32), (batch, 2))

    def body(pos, k):
        a = jax.random.randint(k, (batch,), 0, gw.NUM_ACTIONS)
        s = gw.one_hot_state(pos)
        new, r = jax.vmap(lambda p, aa: gw.step(p, aa, task_id))(pos, a)
        return new, (s, a, r, gw.one_hot_state(new))

    keys = jax.random.split(key, steps)
    _, (s, a, r, s2) = jax.lax.scan(body, pos0, keys)
    return {"state": s.reshape(-1, gw.NUM_CELLS),
            "action": a.reshape(-1),
            "reward": r.reshape(-1),
            "next_state": s2.reshape(-1, gw.NUM_CELLS)}


def sample_td_batches(key, task_id: int, n_batches: int, *,
                      batch_size: int = 64, episodes: int = 16):
    """(n_batches, batch_size, ...) TD transitions, random behaviour."""
    k1, k2 = jax.random.split(key)
    data = behaviour_rollout(k1, task_id, batch=episodes)
    N = data["state"].shape[0]
    idx = jax.random.randint(k2, (n_batches, batch_size), 0, N)
    return jax.tree.map(lambda x: x[idx], data)


@jax.named_scope("episodes")
def sample_episode_batches(key, params, cfg, task_id: int, n_batches: int,
                           *, batch_size: int = 16, epsilon: float = 0.1,
                           episodes: int = 1):
    """The paper's per-round data: ``episodes`` ε-greedy 20-motion episodes
    collected with the CURRENT Q-network, resampled into B_i minibatches."""
    k1, k2 = jax.random.split(key)
    qfn = lambda s: qmodel.forward(params, cfg, s)[0]
    data = gw.rollout(k1, qfn, task_id, steps=20, epsilon=epsilon,
                      batch=episodes)
    flat = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), data)
    N = flat["state"].shape[0]
    idx = jax.random.randint(k2, (n_batches, batch_size), 0, N)
    return jax.tree.map(lambda x: x[idx], flat)


@jax.named_scope("local_sgd")
def _clipped_sgd_steps(loss_fn, params, batches, lr: float,
                       clip: float = 5.0):
    def one(p, b):
        g = jax.grad(loss_fn)(p, b)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-9))
        p = jax.tree.map(lambda w, gg: w - lr * scale * gg, p, g)
        return p, None

    p, _ = jax.lax.scan(one, params, batches)
    return p


@dataclass
class CaseStudy:
    """Fast, fully-jitted driver for the Fig. 3 / Fig. 4 experiments."""

    cfg: object = None
    inner_lr: float = 0.01
    outer_lr: float = 0.005
    fl_lr: float = 0.01
    inner_steps: int = 5
    fl_local_steps: int = 20       # B_i of Table I
    epsilon: float = 0.1           # Sect. IV-A exploration
    first_order: bool = True
    r_target: float = R_TARGET
    energy_params: object = None
    #: model-exchange codec spec (e.g. "int8", "int4", "topk:0.05") — the
    #: cluster's sidelink messages are sent AND Eq.-(11)-priced in this
    #: wire format (error feedback applied to lossy codecs), so the
    #: Fig.-3 energy comparison reruns at any compression level
    codec: object = None
    #: per-round link-failure probability (fading / contention — the
    #: paper's t_i is then MEASURED under a time-varying graph: each
    #: cluster engine carries a ``GraphProcess.dropout`` whose per-round
    #: survival masks are generated IN-SCAN, and the Eq.-(11) comm term
    #: is billed post hoc — over exactly the rounds used — by replaying
    #: the bit-identical host :func:`repro.core.topology.dropout` stream)
    dropout_p: float = 0.0
    dropout_seed: int = 0
    #: optional :class:`repro.core.topology.AgentProcess` — per-round
    #: per-AGENT availability (duty cycles, heavy-tail stragglers,
    #: arrivals/departures). Each task's cluster engine runs ASYNC with
    #: the process reseeded at ``seed + task_id`` (same fleet
    #: heterogeneity, independent sleep realizations per task):
    #: sleeping robots freeze (no local steps, no wires, codec
    #: residuals hold), neighbours mix their frozen last-published
    #: params at ``staleness_decay ** age`` until ``age > tau``, and
    #: ``last_adapt_comm_joules`` bills only DELIVERED wires by
    #: replaying the bit-identical host availability stream.
    availability: object = None
    #: hard staleness bound τ in rounds (async only; None = ∞)
    tau: object = None
    #: λ ∈ (0, 1]: stale lanes mix at λ^age (1.0 = lockstep-exact)
    staleness_decay: float = 1.0
    #: consensus execution plan for the per-cluster Eq.-(6) engine:
    #: "auto" rides the engine's normal selection (the 2-robot clusters
    #: sit far below the sparse-gather floor, so auto keeps them on
    #: dense-xla), or force any plan — ALL of them, "distributed"
    #: included, support dropout_p > 0 via in-scan per-edge survival
    #: draws (the distributed plan masks slots of its fixed ppermute
    #: schedule superset with a traced σ operand).
    plan: str = "auto"
    #: protocol rounds per compiled program: both stages run inside
    #: chunked ``lax.scan`` programs, so the host syncs (the per-round
    #: reached flags / meta losses) once per CHUNK instead of once per
    #: round — t0 and t_i trajectories are bit-identical to ``chunk=1``
    #: (the per-round host loop), the Monte-Carlo sweeps just stop
    #: paying O(rounds) dispatches. Dropout rounds generate each
    #: round's surviving graph inside the scan from the folded
    #: process key (zero host-side per-round graph prefetch).
    chunk: int = 8
    #: optional :class:`repro.telemetry.Telemetry`: meta rounds land as
    #: ``maml`` events, every task's FL rounds as ``fl`` events tagged
    #: ``task_id`` — one pure metrics row per round rides the scan
    #: outputs (buffered mode; streaming mode also emits each round
    #: live via ``jax.debug.callback`` from programs that are never
    #: cache-admitted). The per-round Eq.-(11) stream prices each
    #: round's ACTUAL surviving links with this case study's
    #: ``energy_params``, so ``telemetry.joules(task_id=i)`` equals the
    #: post-hoc ``last_adapt_comm_joules`` replay EXACTLY under
    #: dropout. t0/t_i/params are bit-identical with telemetry off,
    #: buffered, or streaming.
    telemetry: object = None

    def __post_init__(self):
        self.cfg = self.cfg or get_arch("paper-dqn")
        self.chunk = max(int(self.chunk), 1)
        self.energy_params = (self.energy_params
                              or energy.paper_calibrated("fig3"))
        self.codec = comms.resolve_codec(self.codec)
        cfg = self.cfg
        base_loss = dqnrl.make_loss_fn(cfg)

        def loss_fn(p, batch):
            return dqnrl.td_loss(p, cfg, batch,
                                 target_params=batch["target_params"])

        del base_loss
        self._loss_fn = loss_fn
        self.network = ClusterNetwork(num_tasks=gw.NUM_TASKS,
                                      devices_per_cluster=2,
                                      meta_task_ids=META_TASKS)
        # per-cluster communication graph: single source of truth for the
        # Eq.-(6) mixing below AND the Eq.-(11) pricing in ProtocolResult
        self.cluster_topology = self.network.cluster_topology()

        # ---- jitted meta round (Eqs. 3–5 over the Q tasks) ----------------
        def meta_round(params, key):
            ks = jax.random.split(key, 2 * len(META_TASKS))
            sup, qry = [], []
            for j, tid in enumerate(META_TASKS):
                s = sample_episode_batches(
                    ks[2 * j], params, self.cfg, tid, self.inner_steps,
                    epsilon=self.epsilon)
                q = jax.tree.map(lambda x: x[0], sample_episode_batches(
                    ks[2 * j + 1], params, self.cfg, tid, 1,
                    epsilon=self.epsilon))
                s["target_params"] = jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        x[None], (self.inner_steps,) + x.shape), params)
                q["target_params"] = params
                sup.append(s)
                qry.append(q)
            stack = lambda bs: jax.tree.map(lambda *xs: jnp.stack(xs), *bs)
            return maml.maml_meta_step(
                loss_fn, params, stack(sup), stack(qry),
                inner_lr=self.inner_lr, outer_lr=self.outer_lr,
                inner_steps=self.inner_steps,
                first_order=self.first_order)

        # no donate_argnums: host drivers (benchmarks, tests) replay the
        # SAME params pytree across calls — donation would invalidate it
        meta_round = scanloop.donating_jit(meta_round)
        self._meta_round = meta_round

        # chunked stage-1 driver: `chunk` meta rounds per compiled scan
        # program, key split per round exactly like the host loop (same
        # PRNG stream, bit-identical history), losses synced per chunk
        def meta_body(carry, t):
            p, k = carry
            k, sk = jax.random.split(k)
            p, m = meta_round(p, sk)     # jit-of-jit inlines when traced
            if self.telemetry is not None and self.telemetry.streaming:
                jax.debug.callback(self._meta_stream_cb, t,
                                   m["meta_loss"], m["meta_grad_norm"],
                                   ordered=True)
            return (p, k), m["meta_loss"]

        self._meta_chunk = scanloop.donating_jit(
            lambda p, k, ts: jax.lax.scan(meta_body, (p, k), ts),
            donate_argnums=(0,))

        # ---- jitted FL round per task (Eq. 6 cluster) ---------------------
        # the engine plan is a knob ("auto" rides the normal selection —
        # the 2-robot cluster sits below the sparse-gather floor, so auto
        # resolves to dense-xla); with dropout_p > 0 each task gets its
        # own engine carrying a GraphProcess.dropout seeded at
        # dropout_seed + task_id, so every maskable plan generates that
        # round's surviving graph IN-SCAN (bit-identical to the host
        # topology.dropout stream by the shared fold-in convention)
        C = self.network.devices_per_cluster
        self._engines = {
            tid: ConsensusEngine(
                self.cluster_topology, codec=self.codec, plan=self.plan,
                graph=(topo_lib.GraphProcess.dropout(
                    self.dropout_p, seed=self.dropout_seed + tid)
                    if self.dropout_p > 0 else None),
                agents=self._agent_process(tid), tau=self.tau,
                staleness_decay=self.staleness_decay)
            for tid in range(gw.NUM_TASKS)}
        self.engine = self._engines[0]

        tel = self.telemetry
        if tel is not None:
            # recorders carry THIS case study's billing constants (not
            # the Telemetry default) so the stream reconciles exactly
            # with the post-hoc last_adapt_comm_joules replay
            self._recorders = {
                tid: tel.recorder_for(eng, self.energy_params)
                for tid, eng in self._engines.items()}
            if tel.streaming:
                self._stream_cbs = {
                    tid: tel.stream_cb(self._recorders[tid], "fl",
                                       {"task_id": tid})
                    for tid in self._engines}
                self._meta_stream_cb = tel.maml_stream_cb()

        def fl_round(task_id, stacked_params, codec_state, key, t,
                     survival=None, active=None):
            # split C+1 exactly as pre-codec (codec=None rounds keep
            # their RNG stream); the rounding key is folded out of band
            ks = jax.random.split(key, C + 1)
            target = jax.tree.map(lambda x: x[0], stacked_params)

            def local(p, k):
                b = sample_episode_batches(
                    k, p, self.cfg, task_id, self.fl_local_steps,
                    epsilon=self.epsilon)
                b["target_params"] = jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        x[None], (self.fl_local_steps,) + x.shape), target)
                return _clipped_sgd_steps(loss_fn, p, b, self.fl_lr)

            new = jax.vmap(local)(stacked_params, jnp.stack(ks[:C]))
            if active is not None:
                # sleeping robots skip local SGD (bitwise hold)
                new = where_active(active, new, stacked_params)
            # survival= (telemetry shares one plan-shaped draw with the
            # metrics row) takes precedence over t= inside step;
            # identical ops either way
            mixed, new_state = self._engines[task_id].step(
                new, codec_state,
                None if self.codec is None
                else jax.random.fold_in(key, C + 1),
                t=t, survival=survival)
            if active is not None:
                # sleeping receivers don't mix; residuals hold too
                mixed = where_active(active, mixed, new)
                if new_state is not None:
                    old = (codec_state if codec_state is not None
                           else self._engines[task_id].init_state(new))
                    new_state = where_active(active, new_state, old)
            new, codec_state = mixed, new_state
            p0 = jax.tree.map(lambda x: x[0], new)
            R = dqnrl.evaluate(ks[C], p0, self.cfg, task_id, episodes=4)
            return new, codec_state, R

        self._fl_rounds = {
            tid: scanloop.donating_jit(functools.partial(fl_round, tid))
            for tid in range(gw.NUM_TASKS)}

        # chunked stage-2 driver: `chunk` FL rounds per compiled scan
        # program. Time-varying rounds derive their survival mask from
        # the scanned round index t IN-SCAN (no prefetched mix input),
        # a lax.cond freezes params/EF-state/key once the running
        # reward hits the target, and the per-round reached flags sync
        # to the host once per CHUNK — the exact t_i comes back out of
        # the reached mask, bit-identical to the per-round host loop.
        is_async = self.availability is not None

        def fl_body(task_id, limit, carry, t):
            def live(c):
                st, cs, k, _, ast = c
                k, sk = jax.random.split(k)
                if is_async:
                    # one availability draw per round, shared between
                    # the staleness weights, the per-robot freeze, and
                    # the telemetry row (billing only DELIVERED wires)
                    ar = self._engines[task_id].async_round(t, ast.age)
                    sv, act, sv_row = ar.weights, ar.act, ar.delivered
                else:
                    ar, act = None, None
                    sv = (self._engines[task_id].round_survival(t)
                          if tel is not None else None)
                    sv_row = sv
                st, cs, R = fl_round(task_id, st, cs, sk, t, sv, act)
                if is_async:
                    ast = AsyncState(
                        ast.clock + ar.act.astype(ast.clock.dtype),
                        ar.age)
                hit = R >= self.r_target
                ys = (hit, jnp.asarray(True), R)
                if tel is not None:
                    row = self._recorders[task_id].row(
                        st, sv_row, metric=R, reached=hit,
                        live=jnp.asarray(True), active=act,
                        age=(ar.age if is_async else None))
                    if tel.streaming:
                        jax.debug.callback(self._stream_cbs[task_id], t,
                                           row, ordered=True)
                    ys = ys + (row,)
                return (st, cs, k, hit, ast), ys

            def frozen(c):
                ys = (c[3], jnp.asarray(False), jnp.float32(0))
                if tel is not None:
                    row = self._recorders[task_id].frozen_row()
                    if tel.streaming:
                        jax.debug.callback(self._stream_cbs[task_id], t,
                                           row, ordered=True)
                    ys = ys + (row,)
                return c, ys

            pred = jnp.logical_and(jnp.logical_not(carry[3]), t < limit)
            return jax.lax.cond(pred, live, frozen, carry)

        def fl_chunk(task_id, stacked, codec_state, k, reached, ts,
                     limit, ast):
            # ast is None on lockstep runs (an empty pytree through the
            # scan carry) and the task's AsyncState on async runs —
            # clocks/ages persist ACROSS chunks like the params
            return jax.lax.scan(functools.partial(fl_body, task_id, limit),
                                (stacked, codec_state, k, reached, ast),
                                ts)

        self._fl_chunks = {
            tid: scanloop.donating_jit(functools.partial(fl_chunk, tid),
                                       donate_argnums=(0, 1))
            for tid in range(gw.NUM_TASKS)}

    # -- API ------------------------------------------------------------
    def _agent_process(self, task_id):
        """Per-task availability process: same kind/knobs as
        ``self.availability`` but reseeded at ``seed + task_id``, so each
        task cluster draws an independent (and host-replayable) churn
        stream — mirroring how dropout_seed shifts per task."""
        if self.availability is None:
            return None
        return replace(self.availability,
                       seed=self.availability.seed + task_id)

    def init_params(self, key):
        return qmodel.init(key, self.cfg)

    def meta_train(self, key, t0: int):
        """Stage 1: t0 meta rounds, ``self.chunk`` rounds per compiled
        program, meta-loss history synced once per chunk."""
        with spans.span("driver.meta_train"):
            kinit, kdata = jax.random.split(key)
            # own(): _meta_chunk donates its params carry on donating
            # backends
            params = scanloop.own(self.init_params(kinit))
            hist = []
            for start in range(0, t0, self.chunk):
                n = min(self.chunk, t0 - start)
                with spans.span("driver.dispatch"):
                    ts = jnp.arange(start, start + n, dtype=jnp.int32)
                    (params, kdata), losses = self._meta_chunk(
                        params, kdata, ts)
                with spans.span("driver.sync"):
                    jax.block_until_ready(losses)
                if self.telemetry is not None:
                    self.telemetry.record_maml_rounds(
                        {"meta_loss": losses}, start)
                hist.extend(float(x) for x in np.asarray(losses))
            return params, hist

    def adapt_task(self, key, task_id: int, init_params, *,
                   max_rounds: int = 400):
        """Decentralized FL adaptation of one task; measures t_i. With
        ``dropout_p > 0`` every round mixes over that round's SURVIVING
        links (deterministic in ``dropout_seed`` + task — the masks are
        generated INSIDE the compiled scan from the engine's folded
        graph key, zero host-side per-round prefetch) and the Eq.-(11)
        comm joules of the adaptation are accumulated per sent message
        in ``self.last_adapt_comm_joules``.

        Runs ``self.chunk`` rounds per compiled program: the per-round
        reached flags sync once per chunk and the in-scan freeze keeps
        params/EF-state pinned after the hit. The comm-joules bill is
        computed AFTER t_i is known, by replaying the bit-identical
        host :func:`repro.core.topology.dropout` stream over exactly
        the ``rounds_used`` rounds actually executed — frozen tail
        rounds (target hit mid-chunk, or chunk ∤ max_rounds) bill
        zero."""
        with spans.span("driver.adapt_task", task_id=task_id):
            with spans.span("driver.setup"):
                C = self.network.devices_per_cluster
                # own(): _fl_chunks donate the stacked/EF carries; the
                # broadcast must not alias the caller's init_params on
                # donating backends
                stacked = scanloop.own(jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (C,) + x.shape),
                    init_params))
                codec_state = (
                    self.codec.init_state(stacked)
                    if self.codec is not None and self.codec.stateful
                    else None)
                hist = []
                rounds = max_rounds
                reached = jnp.asarray(False)
                step = self._fl_chunks[task_id]
                limit = jnp.int32(max_rounds)
                eng = self._engines[task_id]
                astate = (eng.init_async_state() if eng.agents is not None
                          else None)
            for start in range(0, max_rounds, self.chunk):
                with spans.span("driver.dispatch"):
                    ts = jnp.arange(start, start + self.chunk,
                                    dtype=jnp.int32)
                    (stacked, codec_state, key, reached, astate), ys = step(
                        stacked, codec_state, key, reached, ts, limit,
                        astate)
                with spans.span("driver.sync"):
                    hits, live_mask, Rs = (np.asarray(y) for y in ys[:3])
                if self.telemetry is not None:
                    self.telemetry.record_rounds(
                        self._recorders[task_id], ys[3], start, driver="fl",
                        extra={"task_id": task_id})
                hist.extend(float(r) for r, v in zip(Rs, live_mask) if v)
                h = scanloop.first_hit(hits)
                if h is not None:
                    rounds = start + h + 1
                    break
            with spans.span("driver.bill"):
                self.last_adapt_comm_joules = self._comm_joules(
                    task_id, rounds)
            return stacked, rounds, hist

    def _comm_joules(self, task_id: int, rounds: int) -> float:
        """Eq.-(11) bill over EXACTLY the ``rounds`` executed rounds:
        static lockstep runs price rounds × the full graph; dropout
        and/or availability runs replay the host streams (bit-identical
        to the in-scan masks by the shared fold-in convention) and price
        each round's DELIVERED wires only — a wire bills iff its link
        survived AND both endpoints were awake, matching
        ``AsyncRound.delivered`` and the telemetry stream exactly
        (left-to-right float64 sum, same expression)."""
        proc = self._agent_process(task_id)
        if self.dropout_p > 0 or proc is not None:
            base = self.cluster_topology
            drops = (topo_lib.dropout(
                base, self.dropout_p,
                seed=self.dropout_seed + task_id, rounds=rounds)
                if self.dropout_p > 0 else [base] * rounds)
            acts = topo_lib.availability_stream(proc, base.K, rounds)
            total = 0.0
            for t_r, a in zip(drops, acts):
                m = (np.asarray(t_r.adjacency)
                     & np.asarray(a)[:, None] & np.asarray(a)[None, :])
                billed = topo_lib.Topology(
                    f"{base.name}~billed", m,
                    np.where(m, np.asarray(base.link_class),
                             topo_lib.NONE))
                total += billed.round_comm_joules(
                    self.energy_params, codec=self.codec)
            return float(total)
        return rounds * float(self.cluster_topology.round_comm_joules(
            self.energy_params, codec=self.codec))

    def run(self, key, t0: int, *, max_rounds: int = 400) -> ProtocolResult:
        with spans.span("driver.process"):
            kmeta, kfl = jax.random.split(key)
            meta_params, meta_hist = self.meta_train(kmeta, t0)
            rounds, hists, comm = [], [], []
            for tid in range(self.network.num_tasks):
                kfl, kt = jax.random.split(kfl)
                _, t_i, h = self.adapt_task(kt, tid, meta_params,
                                            max_rounds=max_rounds)
                rounds.append(t_i)
                hists.append(h)
                comm.append(self.last_adapt_comm_joules)
        return ProtocolResult(
            t0=t0, rounds_per_task=rounds, meta_history=meta_hist,
            fl_histories=hists, energy_params=self.energy_params,
            Q=self.network.Q, cluster_topology=self.cluster_topology,
            codec=self.codec,
            fl_comm_joules_measured=(comm if self.dropout_p > 0 else None))


def run_case_study(key=None, *, t0: int = 210, max_rounds: int = 400,
                   codec=None, dropout_p: float = 0.0,
                   plan: str = "auto"):
    """One Monte-Carlo run of the full Fig. 3 experiment (optionally with
    compressed sidelink exchange + codec-priced Eq.-(11) energy, and/or
    p-probability per-round link failures — on any maskable engine
    ``plan``, not just dense-xla)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    return CaseStudy(codec=codec, dropout_p=dropout_p, plan=plan).run(
        key, t0, max_rounds=max_rounds)
