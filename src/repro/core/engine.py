"""ConsensusEngine — the single entry point for one Eq.-(6) mixing round.

The paper's energy balance (Eqs. 6/11) is evaluated per consensus round,
so the round executor is the hot path of every scaling experiment. This
module turns a ``(Topology, K, codec, mesh)`` description into an
execution **plan** once, at construction, and every caller
(:mod:`repro.core.protocol`, :mod:`repro.core.federated`,
:mod:`repro.rl.casestudy`, :mod:`repro.launch.train`, the scale
benchmark) drives the same ``engine.step(stacked_params, codec_state,
key) -> (params, codec_state)`` — no ``impl=`` strings or per-caller
path wiring.

Plans
-----
* ``dense-xla``     — the reference (K, K) matmul per leaf; also accepts
  a TRACED per-round full mix override via ``step(mix=...)`` (the legacy
  time-varying hook, kept for raw-σ callers).
* ``sparse-pallas`` — batched-over-agents sparse gather through the fused
  Pallas consensus kernels (the bit-identical jnp oracle off TPU);
  O(K·H·N) instead of O(K²·N).
* ``sharded``       — the sparse gather under shard_map over an agent
  axis: each mesh position owns a block of K/num_blocks agents, encodes
  its own block's wires, ``all_gather``s the (K, ·) WIRE (codec bytes,
  not f32), and mixes only its rows. No single program materializes the
  (K, K) stack, which is what lets K = 16384 populations mix on meshes
  of any size (and on one CPU via the vmap-with-axis_name emulation).
* ``distributed``   — one agent per mesh position; neighbour exchange is
  ``jax.lax.ppermute`` rounds from a host-computed permutation schedule,
  and the permuted payload is the CODEC wire: int8/int4 lanes + scales,
  bf16 casts. This makes ``Topology.round_comm_joules(codec=)`` pricing
  truthful on the one path that actually distributes across a mesh —
  an int8 wire ships (and prices) 4× below f32.

Wire formats per path: ``dense-xla`` mixes DECODED f32 models (the wire
is an accounting construct priced by Eq. 11); ``sparse-pallas`` and
``sharded`` gather the int-quantized wire itself through the fused
dequant-consensus kernel — int8/int4 lanes with per-tensor OR
block-wise ``int8:b64`` scales (other codecs decode before the
gather); ``distributed`` permutes the wire payload for every codec.

Time-varying graphs (:class:`repro.core.topology.GraphProcess`)
---------------------------------------------------------------
``ConsensusEngine(topo, graph=GraphProcess.dropout(p, seed))`` resolves
a time-varying graph process ONCE at construction, making per-round
link failures a capability of EVERY plan. Survival is drawn per EDGE:
each directed edge owns a canonical id (symmetric pairs share one, so
a faded channel kills both directions) and round ``t``'s draw is the
pure function ``uniform(fold_in(fold_in(key, t), edge_id)) >= p``
(:func:`repro.core.topology.survival_mask`, the single blessed draw
site — rule R1). Because every edge's fate is independent of HOW the
edges are enumerated, each plan draws survival in its own native
shape — O(#edges) work, never a dense rebuild — via
:meth:`round_survival`:

* ``dense-xla``     — the (K, K) mask; :meth:`masked_mixing` REBUILDS
  the σ matrix on the surviving graph with the engine's mixing kind,
  riding the matmul as a traced operand (dropped links reallocate
  their σ mass; doubly-stochastic kinds stay doubly stochastic on
  every surviving subgraph);
* ``sparse-pallas`` / ``sharded`` — the gather INDICES stay baked from
  the full base graph; survival is drawn straight into the (K, H)
  neighbour-lane table and the per-lane σ is renormalized DIRECTLY on
  the lanes (same values bit for bit as the dense rebuild under the
  default uniform data sizes) and rides the fused (dequant-)consensus
  kernels as a traced operand, so faded lanes carry σ = 0 (exact
  no-ops) — one compiled program for every round and O(K·H) per-round
  work, no (K, K) buffer anywhere (rule H1 holds at K = 4096 WITH
  dropout active);
* ``distributed``   — the ppermute schedule SUPERSET of the base graph
  is resolved once at construction (every surviving graph is a
  subgraph, and each directed edge is carried by exactly one schedule
  slot); survival is drawn straight into the (M, K) schedule table,
  the per-slot σ is renormalized on the survivors and rides the
  permutes as a traced (K, M) operand — faded slots apply σ = 0 while
  the wire still ships the full M permutations (a fixed TDMA-frame-
  like schedule; Eq.-(11) billing counts only the surviving real
  edges). Graphs whose schedule superset exceeds
  :data:`DISTRIBUTED_SCHEDULE_BOUND` slots are refused at
  construction.

Draws are bit-identical to the host
:func:`repro.core.topology.dropout` stream via the shared per-edge
fold-in convention, which is what lets callers bill Eq.-(11) joules
post hoc over exactly the rounds used with ZERO host-side per-round
graph prefetch.

Asynchronous consensus (:class:`repro.core.topology.AgentProcess`)
------------------------------------------------------------------
``ConsensusEngine(topo, agents=AgentProcess.…, tau=τ)`` layers per-AGENT
availability on top of per-LINK survival: each round the engine draws
WHO is awake (:func:`repro.core.topology.availability_mask`, the agent
half of the fold-in convention — duty cycles, heavy-tail stragglers,
arrivals, departures), and the protocol degrades instead of wedging.
Inactive agents freeze — no local compute, no wires, params/codec
residuals/round clocks hold bit-for-bit — while their neighbours keep
mixing the frozen last-published state at staleness-decayed weight
λ^age through the SAME per-plan σ machinery (``masked_mixing`` /
``_lane_sigma`` / ``_schedule_sigma``, which accept float weights),
until the wire age passes the hard bound τ and the lane drops with σ
renormalizing over the survivors. The ``(clock, age)``
:class:`AsyncState` threads through the scan carry
(:meth:`async_step` / :meth:`scan_rounds` / the FL drivers), and
telemetry bills Eq.-(11) only on DELIVERED wires — what active agents
actually sent. Two invariants pin the construction:
``AgentProcess.always_on()`` with τ=∞ reduces to the lockstep engine
bit for bit (stale weights are exactly {0, 1} floats, and IEEE
``1.0·x == x`` / ``0.0·x == +0.0`` make the weighted σ identical to
the bool rebuild), and the in-scan availability draws are bit-parity
with the host :func:`repro.core.topology.availability_stream` replay.

Multi-round programs: :meth:`ConsensusEngine.scan_rounds` runs R rounds
inside one ``lax.scan`` with the codec/EF state in the carry — the
building block of the chunked protocol drivers
(:func:`repro.core.federated.run_fl_until_scan`,
:func:`repro.core.maml.maml_train_scan`), which compile whole stretches
of the round loop into single programs and sync the host once per
chunk.

CHOCO mean-exactness invariant: every compressed plan recenters each
agent's update on its OWN decoded copy — W_k + Σ_h σ_{k,h}(x̂_h − x̂_k) —
so under doubly-stochastic σ the population mean is exactly preserved no
matter how lossy the codec; the error-feedback wrapper (on by default
for lossy codecs) telescopes the per-round quantization error. All four
plans therefore agree with the dense-f32 oracle to within the codec's
round-trip tolerance (tested at K = 256 in ``tests/test_engine.py``).

``plan="auto"`` selection: with no mesh, the payload-aware density
heuristic (:func:`repro.core.consensus.auto_path`) picks dense-xla vs
sparse-pallas; with a mesh carrying the agent axis, one-agent-per-
position meshes take ``distributed`` and everything else ``sharded``
(blocks = mesh axis size).
"""
from __future__ import annotations

import difflib
from dataclasses import dataclass
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import consensus

PLAN_KINDS = ("dense-xla", "sparse-pallas", "sharded", "distributed")
#: plans that accept a per-round survival mask (traced σ operands).
#: Since the per-edge draw convention, ALL of them: the distributed
#: plan keeps its ppermute schedule superset fixed at trace time and
#: masks individual schedule slots via a traced (K, M) σ operand.
MASKABLE_PLANS = ("dense-xla", "sparse-pallas", "sharded", "distributed")

#: largest ppermute-schedule superset a time-varying ``distributed``
#: engine accepts (schedule length ≈ the base graph's max degree — one
#: slot per matching). Every masked round ships all M slots whether or
#: not their edges survived (the superset is the fixed TDMA frame), so
#: a graph needing more slots than this would spend more air time on
#: faded slots than a prefetched-schedule rebuild costs; such graphs
#: are refused at construction.
DISTRIBUTED_SCHEDULE_BOUND = 64

#: per-plan compiled-artifact expectations ``repro.analysis`` keys on.
#: ``kk_buffer``: whether the plan's program may legitimately
#: materialize a (K, K) tensor (the dense σ stack); the sharded and
#: distributed plans exist precisely so it never does, and the HLO
#: auditor (rule H1) fails them if one appears at K ≥ its threshold.
#: ``wire_collective``: which collective carries the codec WIRE on a
#: real mesh — the op whose result bytes rule H2 reconciles against
#: ``codec.bits()`` pricing. ``int_lane_gather``: the plan mixes
#: int-codec wires through a fused gather that must keep int8/int4
#: lanes (the decode-then-combine regression class, rule JX2).
PLAN_AUDIT_EXPECTATIONS = {
    "dense-xla":     {"kk_buffer": True, "wire_collective": None,
                      "int_lane_gather": False},
    "sparse-pallas": {"kk_buffer": False, "wire_collective": None,
                      "int_lane_gather": True},
    "sharded":       {"kk_buffer": False, "wire_collective": "all-gather",
                      "int_lane_gather": True},
    "distributed":   {"kk_buffer": False,
                      "wire_collective": "collective-permute",
                      "int_lane_gather": False},
}


@dataclass(frozen=True)
class ExecutionPlan:
    """A resolved consensus execution strategy (see module docstring)."""

    kind: str
    reason: str
    num_blocks: int = 1
    axis_name: str = "agents"

    def __post_init__(self):
        if self.kind not in PLAN_KINDS:
            close = difflib.get_close_matches(
                str(self.kind), PLAN_KINDS + ("auto",), n=1)
            hint = f"; did you mean {close[0]!r}?" if close else ""
            raise ValueError(f"unknown plan {self.kind!r}; "
                             f"choose from {PLAN_KINDS} or 'auto'{hint}")


class AsyncState(NamedTuple):
    """Per-caller carry of an async (agent-availability) engine:

    * ``clock`` — (K,) int32 per-agent round clocks: how many rounds
      each agent has actually PARTICIPATED in (ticks only while
      active; a straggler's clock lags the global round index);
    * ``age``   — plan-shaped int32 last-received-wire age per lane —
      (K, K) on dense-xla, (K, H) lanes on sparse-pallas/sharded,
      (M, K) schedule slots on distributed — rounds since receiver k
      last got a FRESH wire from that lane's sender (0 after a
      delivery, +1 per round otherwise).

    ``init_async_state`` starts both at zero — the protocol's "all
    agents exchanged initial models at t=0" convention.
    """

    clock: jnp.ndarray
    age: jnp.ndarray


class AsyncRound(NamedTuple):
    """One round's resolved availability facts (``async_round``):
    ``act`` (K,) activity bools; ``weights`` plan-shaped float32
    staleness-scaled σ input (1 fresh, λ^age stale, 0 dropped);
    ``delivered`` plan-shaped bools marking wires ACTUALLY shipped
    this round (what Eq.-(11) bills); ``age`` the post-round wire
    ages (the next carry's ``AsyncState.age``)."""

    act: jnp.ndarray
    weights: jnp.ndarray
    delivered: jnp.ndarray
    age: jnp.ndarray


def where_active(active, new, old):
    """Per-agent freeze/select over K-stacked pytrees: leaf ``[k]``
    takes ``new[k]`` where ``active[k]`` else ``old[k]`` (broadcast over
    trailing axes). An inactive agent's params / codec residuals /
    clocks hold bit-for-bit; an all-True (all-False) mask returns the
    first (second) operand's values exactly, which is what keeps the
    always-on lockstep reduction and the fully-dead-round no-op
    bitwise."""
    act = jnp.asarray(active, bool)

    def sel(n, o):
        a = act.reshape(act.shape + (1,) * (jnp.ndim(n) - 1))
        return jnp.where(a, n, o)

    return jax.tree.map(sel, new, old)


class ConsensusEngine:
    """One Eq.-(6) round behind one entry point (see module docstring).

    Arguments
    ---------
    topology:   a :class:`repro.core.topology.Topology` (preferred — also
                enables :meth:`round_comm_joules`) or a concrete (K, K)
                σ matrix.
    codec:      model-exchange codec spec/Codec (:mod:`repro.comms`);
                lossy codecs get the error-feedback wrapper unless
                ``error_feedback=False``.
    mesh:       optional ``jax.sharding.Mesh`` whose ``axis_name`` axis
                carries agents (one per position ⇒ distributed; blocks
                ⇒ sharded). ``None`` runs every plan in one program
                (sharded/distributed fall back to the vmap-with-
                axis_name emulation, which shares collective semantics).
    plan:       "auto" (default) or one of :data:`PLAN_KINDS`.
    num_blocks: block count for the sharded plan (default: mesh axis
                size, else 1).
    data_sizes / mix_kind / include_self: forwarded to the topology's
                ``mixing`` (uniform paper weights by default) and reused
                to REBUILD the per-round mix on surviving subgraphs when
                a time-varying ``graph`` is attached.
    gamma:      CHOCO consensus step size (damps off-diagonal σ).
    graph:      a :class:`repro.core.topology.GraphProcess` (or None ⇒
                static). Non-static processes turn EVERY plan
                time-varying: each round's edge survival is drawn
                in-scan from the folded process key in the plan's
                native shape — (K, K) mask, (K, H) lanes, or (M, K)
                schedule slots — and the σ is renormalized on the
                survivors (see the module docstring). The
                ``distributed`` plan resolves its ppermute schedule
                superset here, at construction, and refuses graphs
                needing more than :data:`DISTRIBUTED_SCHEDULE_BOUND`
                slots.
    agents:     a :class:`repro.core.topology.AgentProcess` (or None ⇒
                lockstep). Attaching one turns the engine ASYNC: each
                round's per-agent availability is drawn in-scan from
                the same fold-in convention, inactive agents freeze
                (params, codec residuals, round clocks), and mixing
                becomes staleness-weighted — a sleeping neighbour's
                frozen last-published state mixes at weight
                ``staleness_decay ** age`` until ``age > tau``, where
                its lane drops and σ renormalizes (see
                :meth:`async_round`). ``AgentProcess.always_on()``
                with ``tau=None`` reduces to the lockstep engine bit
                for bit.
    tau:        hard staleness bound in rounds (async only): a lane
                whose wire age exceeds τ drops from the mix entirely.
                None ⇒ ∞ (stale lanes never drop); 0 ⇒ only fresh
                wires mix.
    staleness_decay: λ ∈ (0, 1] — stale lanes mix at λ^age. The
                default 1.0 keeps stale weights at exactly 1 (the
                lockstep-exact choice); smaller values fade old wires
                smoothly before the hard τ cut.
    """

    def __init__(self, topology, *, codec=None, mesh=None,
                 plan: str = "auto", axis_name: str = "agents",
                 num_blocks: Optional[int] = None, data_sizes=None,
                 mix_kind: str = "paper", include_self: bool = True,
                 gamma: float = 1.0, error_feedback: bool = True,
                 block_n: Optional[int] = None, graph=None,
                 agents=None, tau=None, staleness_decay: float = 1.0):
        from repro import comms   # deferred: core stays import-light
        from repro.core import topology as topo_lib
        if isinstance(topology, ConsensusEngine):
            raise TypeError(
                f"topology= got an already-built {type(topology).__name__} "
                f"(plan={topology.plan.kind!r}); pass a Topology or mix "
                "matrix, or coerce with ConsensusEngine.wrap(engine)")
        if mix_kind not in consensus.MIX_KINDS:
            # validated here, at construction, so a typo'd kind is
            # refused before any (possibly jitted) round traces it
            raise ValueError(consensus._unknown_kind_msg(mix_kind))
        self.topology = topology if hasattr(topology, "mixing") else None
        self.mix = np.asarray(
            topology.mixing(data_sizes, kind=mix_kind,
                            include_self=include_self)
            if self.topology is not None else topology, np.float32)
        self.K = self.mix.shape[0]
        self.codec = comms.resolve_codec(codec, error_feedback)
        self.mesh = mesh
        self.gamma = float(gamma)
        self.block_n = block_n
        self.mix_kind = mix_kind
        self.include_self = include_self
        self.data_sizes = (None if data_sizes is None
                           else np.asarray(data_sizes, np.float32))
        self.graph = graph if graph is not None else topo_lib.GraphProcess.static()
        if agents is not None and not isinstance(agents,
                                                 topo_lib.AgentProcess):
            raise TypeError(
                f"agents= takes a repro.core.topology.AgentProcess (or "
                f"None), got {agents!r}; build one with "
                "AgentProcess.always_on() / .bernoulli(p_active) / "
                ".straggler(K) / .arrival(t_join) / .departure(t_leave)")
        self.agents = agents
        if agents is not None:
            if self.topology is None:
                raise ValueError(
                    f"agents={agents!r} needs an engine built from a "
                    "Topology, but this one came from a raw mix matrix: "
                    "staleness σ is REBUILT per round from the "
                    "delivered/stale lanes with the engine's mixing "
                    "kind, which cannot faithfully renormalize an "
                    "arbitrary raw mix — construct from a Topology "
                    "(e.g. topology.ring(K)) or drop agents=")
            pk = agents.K
            if pk is not None and pk != self.K:
                raise ValueError(
                    f"agents={agents!r} pins a population of {pk} "
                    f"agents but this engine's topology has K="
                    f"{self.K}; rebuild the process at K={self.K}")
        if tau is not None and agents is None:
            raise ValueError(
                f"tau={tau!r} (the hard staleness bound) only applies "
                "to async engines: pass agents=AgentProcess.… alongside "
                "it, or drop tau= for the lockstep protocol")
        if tau is not None:
            tf = float(tau)
            if np.isnan(tf) or tf < 0:
                raise ValueError(
                    f"tau={tau!r} is not a staleness bound: τ counts "
                    "rounds since the last delivered wire — use "
                    "tau=None (∞: stale lanes never drop), tau=0 "
                    "(only fresh wires mix), or a positive round count")
            tau = None if np.isinf(tf) else tf
        self.tau = tau
        self.staleness_decay = float(staleness_decay)
        if not 0.0 < self.staleness_decay <= 1.0:
            raise ValueError(
                f"staleness_decay={staleness_decay!r} must lie in "
                "(0, 1]: a stale lane mixes at weight λ^age — use "
                "λ=1.0 (no decay, the lockstep-exact default) or a "
                "positive fraction like 0.9")
        self.plan = self._resolve_plan(plan, axis_name, num_blocks)
        self._schedule = None          # distributed ppermute rounds, lazy
        self._masked_struct = None     # (idx, lane-valid) for masked sig
        self._sched_struct = None      # (srcs, real) of the schedule
        self._sched_keep = None        # schedule-kind masks, plan-shaped
        if self.graph.kind != "static":
            if self.topology is None:
                # a raw σ matrix's generating rule is unknown, so the
                # per-round rebuild would silently REPLACE the caller's
                # weights with mixing_weights(kind) on the survivor —
                # refuse rather than diverge
                raise ValueError(
                    f"graph={self.graph!r} (time-varying) needs an "
                    "engine built from a Topology, but this one came "
                    "from a raw mix matrix: each round's σ is REBUILT "
                    "from the surviving graph with the engine's mixing "
                    "kind/data_sizes, which cannot faithfully "
                    "renormalize an arbitrary raw mix — construct from "
                    "a Topology or use GraphProcess.static()")
            # the base adjacency the survival masks apply to
            self._adjacency = np.asarray(self.topology.adjacency, bool)
            self._symmetric = bool(
                (self._adjacency == self._adjacency.T).all())
            if self.graph.kind == "dropout":
                self._graph_key = topo_lib.survival_key(self.graph.seed)
            elif self.graph.masks.shape[1:] != (self.K, self.K):
                raise ValueError(
                    f"schedule masks are {self.graph.masks.shape[1:]}, "
                    f"population is K={self.K}")
        if (self.plan.kind == "distributed"
                and (self.graph.kind != "static"
                     or self.agents is not None)):
            # resolve the ppermute schedule SUPERSET now: every
            # surviving (or delivered) graph is a subgraph of the base
            # graph, so a schedule covering the base graph covers every
            # round — masked slots ride as σ = 0 on a traced operand,
            # no retrace. One slot per matching ⇒ length ≈ max degree.
            self._schedule = consensus.permutation_schedule(
                self.mix, self.gamma)
            if len(self._schedule) > DISTRIBUTED_SCHEDULE_BOUND:
                raise ValueError(
                    f"time-varying/async engines on the distributed "
                    f"plan mask a fixed ppermute schedule superset, "
                    f"and this graph needs {len(self._schedule)} "
                    f"schedule slots (≈ max degree "
                    f"{self.topology.max_degree}) — over the "
                    f"{DISTRIBUTED_SCHEDULE_BOUND}-slot bound "
                    "(DISTRIBUTED_SCHEDULE_BOUND). Use a sparser "
                    "base graph, or the sharded plan (per-lane "
                    "masks, no schedule)")

    # -- plan selection -----------------------------------------------------
    def _resolve_plan(self, plan: str, axis_name: str,
                      num_blocks: Optional[int]) -> ExecutionPlan:
        mesh_axis = consensus._mesh_axis(self.mesh, axis_name)
        if plan == "auto":
            if mesh_axis is not None:
                if mesh_axis == self.K:
                    return ExecutionPlan(
                        "distributed", "mesh holds one agent per "
                        f"'{axis_name}' position", 1, axis_name)
                nb = num_blocks or mesh_axis
                if self.K % nb:
                    # a mesh was given: honour it — fall back to the
                    # largest block count that divides K rather than
                    # silently reverting to a single-program plan
                    nb = next(d for d in range(min(nb, self.K), 0, -1)
                              if self.K % d == 0)
                return ExecutionPlan(
                    "sharded", f"K={self.K} agents in {nb} blocks over "
                    f"the {mesh_axis}-wide '{axis_name}' mesh axis",
                    nb, axis_name)
            base = getattr(self.codec, "inner", self.codec)
            dense = consensus.auto_path(self.mix, codec=base) == "dense"
            return ExecutionPlan(
                "dense-xla" if dense else "sparse-pallas",
                "payload-aware density heuristic "
                f"(max degree vs K={self.K})", 1, axis_name)
        if plan == "sharded":
            nb = num_blocks or mesh_axis or 1
            return ExecutionPlan("sharded", "explicit", nb, axis_name)
        return ExecutionPlan(plan, "explicit", num_blocks or 1, axis_name)

    # -- state --------------------------------------------------------------
    def init_state(self, stacked_params):
        """Initial codec state (stacked EF residuals; None if stateless)."""
        if self.codec is None or not self.codec.stateful:
            return None
        return self.codec.init_state(stacked_params)

    # -- time-varying graphs ------------------------------------------------
    def round_mask(self, t):
        """(K, K) bool edge-survival mask of round ``t`` under this
        engine's :class:`~repro.core.topology.GraphProcess` (None for a
        static graph). ``t`` may be TRACED — this is what the scanned
        drivers call per round INSIDE ``lax.scan``, and by the shared
        fold-in convention the result is bit-identical to round ``t`` of
        the host :func:`repro.core.topology.dropout` stream."""
        from repro.core import topology as topo_lib
        if self.graph.kind == "static":
            return None
        if self.graph.kind == "dropout":
            return topo_lib.survival_mask(
                self._adjacency, self.graph.p, self._graph_key, t,
                symmetric=self._symmetric)
        masks = jnp.asarray(self.graph.masks)          # schedule
        return jnp.asarray(self._adjacency) & masks[
            jnp.asarray(t) % masks.shape[0]]

    def masked_mixing(self, mask):
        """Rebuild the σ matrix on the SURVIVING graph (possibly traced
        mask): the engine's mixing kind / data sizes / include_self are
        re-applied to ``adjacency & mask``, so dropped links reallocate
        their σ mass exactly as ``Topology.mixing`` would on the
        host-materialized survivor (bit-identical — same jnp ops)."""
        sizes = (np.ones(self.K, np.float32) if self.data_sizes is None
                 else self.data_sizes)
        return consensus.mixing_weights(
            sizes, mask, self.mix_kind, include_self=self.include_self)

    def lane_structure(self):
        """(idx, valid) neighbour-lane table of the BASE graph for the
        sparse/sharded plans: idx (K, H) int32 ascending neighbour
        indices (padding lanes index the agent itself), valid (K, H)
        bool marking real lanes. Baked once, lazily, as numpy — the
        cache outlives any one trace, so it must never hold
        tracer-backed arrays."""
        if self._masked_struct is None:
            A = (np.asarray(self.topology.adjacency, bool).copy()
                 if self.topology is not None else self.mix != 0)
            np.fill_diagonal(A, False)
            deg = A.sum(axis=1)
            H = max(int(deg.max()), 1) if self.K else 1
            idx = np.tile(np.arange(self.K, dtype=np.int32)[:, None],
                          (1, H))
            for k in range(self.K):
                nbr = np.flatnonzero(A[k])
                idx[k, :len(nbr)] = nbr
            valid = np.arange(H)[None, :] < deg[:, None]
            self._masked_struct = (idx, valid)
        return self._masked_struct

    def schedule_structure(self):
        """(srcs, real) of the distributed plan's ppermute schedule
        superset: srcs (M, K) int32 — the mesh position each target
        receives from in slot m — and real (M, K) bool marking slots
        that carry an actual base-graph edge (the rest are permutation-
        completion padding, σ = 0 forever). Baked once, lazily, numpy."""
        if self._sched_struct is None:
            if self._schedule is None:
                self._schedule = consensus.permutation_schedule(
                    self.mix, self.gamma)
            M = len(self._schedule)
            srcs = np.zeros((M, self.K), np.int32)
            real = np.zeros((M, self.K), bool)
            for m, (pairs, sig) in enumerate(self._schedule):
                for s, tgt in pairs:
                    srcs[m, tgt] = s
                real[m] = np.asarray(sig) != 0.0
            self._sched_struct = (srcs, real)
        return self._sched_struct

    def round_survival(self, t=None, mask=None):
        """Round ``t``'s edge survival in THIS plan's native shape —
        the in-scan fast path that never materializes (K, K) on the
        non-dense plans: a (K, K) bool mask on dense-xla, surviving-
        lane (K, H) bools on sparse-pallas/sharded, surviving-slot
        (M, K) bools on distributed. ``t`` may be traced; ``mask``
        instead converts an explicit (K, K) survival mask (e.g. a
        host-prefetched :func:`repro.core.topology.dropout` round) to
        the plan shape — bit-identical to the in-scan draw of the same
        round by the shared per-edge fold-in convention. Returns None
        for a static graph with no explicit mask."""
        from repro.core import topology as topo_lib
        kind = self.plan.kind
        if kind == "dense-xla":
            return (jnp.asarray(mask) if mask is not None
                    else self.round_mask(t))
        if mask is None and self.graph.kind == "static":
            return None
        if kind == "distributed":
            srcs, real = self.schedule_structure()
            rows = np.arange(self.K, dtype=np.int32)[None, :]
        else:
            srcs, real = self.lane_structure()      # (idx, valid)
            rows = np.arange(self.K, dtype=np.int32)[:, None]
        if mask is not None:
            keep = jnp.asarray(mask)[rows, srcs]
        elif self.graph.kind == "dropout":
            keep = topo_lib.survival_mask(
                self.K, self.graph.p, self._graph_key, t,
                symmetric=self._symmetric, receivers=rows, senders=srcs)
        else:                                        # schedule masks
            if self._sched_keep is None:
                # pre-gather the (R, K, K) mask stack into the plan
                # shape ONCE (numpy), so the in-scan lookup is a
                # dynamic slice of lanes/slots, never a (K, K) constant
                self._sched_keep = np.asarray(
                    self.graph.masks[:, rows, srcs])
            stack = jnp.asarray(self._sched_keep)
            keep = stack[jnp.asarray(t) % stack.shape[0]]
        return keep & jnp.asarray(real)

    # -- per-agent availability (the async protocol) ------------------------
    def availability(self, t):
        """(K,) activity bools of round ``t`` under this engine's
        :class:`~repro.core.topology.AgentProcess` (all-True when no
        agents= is attached). ``t`` may be traced — drawn in-scan,
        bit-identical to the host
        :func:`repro.core.topology.availability_stream` replay."""
        from repro.core import topology as topo_lib
        return topo_lib.agent_availability(self.agents, self.K, t)

    def _real_edges(self):
        """Plan-shaped bool mask of the REAL base-graph lanes (numpy
        constants baked at trace time): the adjacency on dense-xla,
        lane validity on sparse-pallas/sharded, real schedule slots on
        distributed."""
        kind = self.plan.kind
        if kind == "dense-xla":
            return np.asarray(self.topology.adjacency, bool)
        if kind == "distributed":
            return self.schedule_structure()[1]
        return self.lane_structure()[1]

    def _act_shapes(self, act):
        """Broadcast the (K,) activity vector into this plan's native
        survival shape: ``(act_recv, act_sender)`` per lane — receiver
        rows/sender columns on the (K, K) grid, receiver rows/sender
        lane indices on (K, H), receiver columns/sender schedule
        sources on (M, K)."""
        kind = self.plan.kind
        if kind == "dense-xla":
            return act[:, None], act[None, :]
        if kind == "distributed":
            srcs, _real = self.schedule_structure()
            return act[None, :], act[jnp.asarray(srcs)]
        idx, _valid = self.lane_structure()
        return act[:, None], act[jnp.asarray(idx)]

    def init_async_state(self) -> AsyncState:
        """Zeroed :class:`AsyncState` carry — clocks at 0, every wire
        age 0 ("all agents exchanged initial models at t=0")."""
        if self.agents is None:
            raise ValueError(
                "init_async_state() is the async protocol's carry, but "
                f"this {self.plan.kind!r} engine has agents=None — pass "
                "agents=AgentProcess.bernoulli(p_active) (or another "
                "availability process) at construction")
        shape = np.asarray(self._real_edges()).shape
        return AsyncState(jnp.zeros(self.K, jnp.int32),
                          jnp.zeros(shape, jnp.int32))

    def async_round(self, t, age) -> AsyncRound:
        """Resolve round ``t``'s availability facts against the wire
        ages ``age`` (the :class:`AsyncState` carry): who is awake,
        which wires actually ship, and the staleness-scaled σ input.

        Per lane (receiver k ← sender h), with ``up`` the link survival
        of the engine's graph process (all real lanes, for a static
        graph):

        * DELIVERED (``act[h] & act[k] & up``): a fresh wire ships;
          weight 1, age resets to 0. A lane whose SENDER is awake but
          whose LINK faded drops outright (weight 0) — exactly today's
          lockstep fade semantics, which is what keeps the always-on
          reduction bitwise.
        * STALE (``act[k] & ~act[h]``, real lane): the sender sleeps,
          so the receiver keeps mixing the sender's FROZEN last-
          published params at weight ``staleness_decay ** age`` — a
          stale neighbour is a faded lane with memory — until
          ``age > τ``, where the lane drops and σ renormalizes over
          the survivors. (Optimistic-cache caveat: if the sender's
          last pre-sleep wire itself faded, the cache is the frozen
          params, not the older wire actually received — the engine
          models the cache, not a (K, H, N) wire buffer.)
        * otherwise weight 0 (receiver asleep, or padding lane).

        ``age`` counts rounds since the last delivery and increments
        on every non-delivered lane. With ``AgentProcess.always_on``
        and τ=∞ every real surviving lane is DELIVERED, the weights
        are exactly {0.0, 1.0}, and the staleness σ reproduces the
        lockstep σ bit for bit.
        """
        if self.agents is None:
            raise ValueError(
                "async_round() needs an agents= AgentProcess attached "
                f"at construction, but this {self.plan.kind!r} engine "
                "has agents=None (it runs the lockstep protocol; use "
                "step(t=...) instead)")
        act = self.availability(t)
        act_recv, act_send = self._act_shapes(act)
        real = jnp.asarray(self._real_edges())
        link = self.round_survival(t)   # already ANDed with real lanes
        up = real if link is None else jnp.asarray(link)
        age = jnp.asarray(age, jnp.int32)
        delivered = act_send & act_recv & up
        new_age = jnp.where(delivered, 0, age + 1)
        stale = act_recv & ~act_send & real
        if self.tau is not None:
            stale = stale & (new_age <= self.tau)
        if self.staleness_decay == 1.0:
            stale_w = jnp.float32(1.0)
        else:
            stale_w = (jnp.float32(self.staleness_decay)
                       ** new_age.astype(jnp.float32))
        weights = jnp.where(delivered, jnp.float32(1.0),
                            jnp.where(stale, stale_w, jnp.float32(0.0)))
        return AsyncRound(act, weights, delivered, new_age)

    def async_step(self, stacked_params, codec_state=None, key=None, *,
                   t=None, state: Optional[AsyncState] = None,
                   round_info: Optional[AsyncRound] = None):
        """One async Eq.-(6) round: resolve availability, staleness-mix
        through :meth:`step`, freeze inactive agents' params and codec
        residuals, and advance clocks/ages. Returns ``(params,
        codec_state, AsyncState, AsyncRound)`` — thread the state into
        the next call (start from :meth:`init_async_state`); pass
        ``round_info=`` to reuse facts already drawn (e.g. shared with
        telemetry), else they are drawn from ``t``."""
        if state is None:
            raise ValueError(
                f"async_step at t={t!r} needs state= (the AsyncState "
                "carry, got state=None) — start from "
                "init_async_state() and thread each call's returned "
                "state into the next")
        ar = (round_info if round_info is not None
              else self.async_round(t, state.age))
        p, st = self.step(stacked_params, codec_state, key,
                          survival=ar.weights)
        p = where_active(ar.act, p, stacked_params)
        if st is not None:
            old = (codec_state if codec_state is not None
                   else self.init_state(stacked_params))
            st = where_active(ar.act, st, old)
        new_state = AsyncState(
            state.clock + ar.act.astype(state.clock.dtype), ar.age)
        return p, st, new_state, ar

    def _sizes(self):
        return (np.ones(self.K, np.float32) if self.data_sizes is None
                else self.data_sizes)

    def _lane_sigma(self, survival):
        """(idx, sig_t) structure for the sparse/sharded plans: σ
        renormalized DIRECTLY on the surviving (K, H) lanes — same
        formulas as :func:`repro.core.consensus.mixing_weights` per
        entry, O(K·H) with no dense rebuild. Faded/padding lanes land
        at σ = 0, exact no-ops in the fused kernels. Bit-identical to
        gathering the dense rebuild under uniform data sizes (sums of
        equal addends are association-free).

        ``survival`` may be bool lane keeps (the lockstep protocol) or
        FLOAT per-lane weights in [0, 1] (the async staleness path:
        λ^age on stale lanes, 1 fresh, 0 dropped) — each lane's σ mass
        scales by its weight before renormalizing; {0, 1} floats
        reproduce the bool path bit for bit, and metropolis degrees
        generalize to weighted degrees."""
        idx, _valid = self.lane_structure()
        keep = jnp.asarray(survival)
        sizes = jnp.asarray(self._sizes())
        weighted = jnp.issubdtype(keep.dtype, jnp.floating)
        if weighted:
            keep = keep.astype(jnp.float32)
        if self.mix_kind == "paper":
            w = (keep * sizes[jnp.asarray(idx)] if weighted
                 else jnp.where(keep, sizes[jnp.asarray(idx)], 0.0))
            denom = w.sum(axis=1)
            if self.include_self:
                denom = denom + sizes
            sig = w / jnp.maximum(denom, 1e-12)[:, None]
        elif self.mix_kind == "metropolis":
            deg = (keep.sum(axis=1) if weighted
                   else keep.sum(axis=1).astype(jnp.float32))
            inv = 1.0 / (1.0 + jnp.maximum(deg[:, None],
                                           deg[jnp.asarray(idx)]))
            sig = keep * inv if weighted else jnp.where(keep, inv, 0.0)
        else:
            raise ValueError(consensus._unknown_kind_msg(self.mix_kind))
        return jnp.asarray(idx), sig

    def _schedule_sigma(self, survival):
        """γ-scaled (K, M) schedule σ for the distributed plan,
        renormalized on the surviving (M, K) slots — the traced
        ``sig_override`` operand that replaces the baked full-graph
        ``sig_stack`` without retracing (the ppermute pairs stay
        trace-time structure). Every real directed edge occupies
        exactly one slot, so the per-target sum over slots equals the
        dense rebuild's per-row sum over neighbours. Like
        :meth:`_lane_sigma`, ``survival`` may be bool slot keeps or
        float staleness weights — {0, 1} floats reproduce the bool
        path bit for bit."""
        srcs, _real = self.schedule_structure()
        keep = jnp.asarray(survival)                 # (M, K)
        sizes = jnp.asarray(self._sizes())
        weighted = jnp.issubdtype(keep.dtype, jnp.floating)
        if weighted:
            keep = keep.astype(jnp.float32)
        if self.mix_kind == "paper":
            w = (keep * sizes[jnp.asarray(srcs)] if weighted
                 else jnp.where(keep, sizes[jnp.asarray(srcs)], 0.0))
            denom = w.sum(axis=0)
            if self.include_self:
                denom = denom + sizes
            sig = w / jnp.maximum(denom, 1e-12)[None, :]
        elif self.mix_kind == "metropolis":
            deg = (keep.sum(axis=0) if weighted
                   else keep.sum(axis=0).astype(jnp.float32))
            inv = 1.0 / (1.0 + jnp.maximum(deg[None, :],
                                           deg[jnp.asarray(srcs)]))
            sig = keep * inv if weighted else jnp.where(keep, inv, 0.0)
        else:
            raise ValueError(consensus._unknown_kind_msg(self.mix_kind))
        return (self.gamma * sig).T

    # -- the round ----------------------------------------------------------
    @jax.named_scope("eq6_mix")
    def step(self, stacked_params, codec_state=None, key=None, *, mix=None,
             t=None, mask=None, survival=None):
        """One Eq.-(6) consensus round on agent-stacked params (leading
        axis K). Returns ``(new_stacked_params, new_codec_state)`` for
        EVERY plan and codec (state is None for codec-free rounds).

        ``key`` enables stochastic rounding for quantizing codecs.

        Time-varying graphs: ``t`` (round index, may be traced) draws
        the round's edge survival from the engine's graph process in
        the plan's native shape — the preferred entry point for the
        scanned drivers; ``mask`` passes an explicit (K, K) bool
        survival mask instead (e.g. a host-prefetched
        :func:`topology.dropout` round), converted to the plan shape
        bit-identically; ``survival`` passes a plan-shaped operand a
        caller already drew via :meth:`round_survival` (so one draw can
        be shared with telemetry). All three renormalize σ on the
        surviving edges and run it as a traced operand — dense-xla
        takes the full masked mix, the sparse-pallas/sharded gathers
        take the per-lane σ with faded lanes zeroed (indices stay
        baked), and the distributed plan applies per-slot σ over its
        fixed ppermute schedule superset (faded slots σ = 0).

        ``mix`` overrides the engine's σ matrix wholesale for THIS round
        (may be traced); only the dense-xla plan supports it, every
        other plan bakes the neighbour structure in at trace time.
        """
        kind = self.plan.kind
        if mix is not None and kind != "dense-xla":
            raise ValueError(
                f"per-round mix overrides need the dense-xla plan, not "
                f"{kind!r} (sparse structure is fixed at trace time; "
                "time-varying graphs go through mask=/t= instead)")
        if self.agents is not None and survival is None:
            # deriving survival from t=/mask= here would silently
            # ignore WHO is awake — mixing sleeping agents at full
            # weight and billing wires nobody sent
            raise ValueError(
                f"this engine carries an availability process "
                f"{self.agents!r}: step() needs the staleness-weighted "
                "survival from async_round(t, age).weights passed via "
                "survival= — or drive whole rounds through async_step()"
                " / scan_rounds(), which thread the (clock, age) "
                "AsyncState carry for you")
        if survival is None and (mask is not None or t is not None):
            if mix is not None and mask is not None:
                raise ValueError(
                    f"step() got BOTH mix (shape {jnp.shape(mix)}) and "
                    f"mask (shape {jnp.shape(mask)}) — pass the explicit "
                    "mix= alone, or let mask=/t= rebuild σ from the "
                    "surviving graph")
            survival = self.round_survival(t, mask=mask)
        if survival is None and mix is None and self.graph.kind != "static":
            # silently mixing on the full static graph would measure t_i
            # (and bill Eq.-11) on a never-fading network — fail loudly
            raise ValueError(
                f"this engine carries a time-varying {self.graph!r}: "
                "step() needs the round index (t=) or an explicit "
                "survival mask (mask=); use scan_rounds for whole "
                "round loops")
        structure = None
        sig_override = None
        if survival is not None:
            if mix is not None:
                raise ValueError(
                    f"step() got BOTH mix (shape {jnp.shape(mix)}) and "
                    f"survival (shape {jnp.shape(survival)}) — pass the "
                    "explicit mix= alone, or let survival=/t= rebuild σ "
                    "from the surviving lanes")
            if kind == "dense-xla":
                mix = self.masked_mixing(survival)
            elif kind == "distributed":
                sig_override = self._schedule_sigma(survival)
            else:
                structure = self._lane_sigma(survival)
        mix_ = self.mix if mix is None else mix
        if kind == "dense-xla" or kind == "sparse-pallas":
            impl = "xla" if kind == "dense-xla" else "sparse"
            if self.codec is None:
                return consensus.consensus_step(
                    stacked_params, mix_, impl=impl,
                    block_n=self.block_n, structure=structure), None
            # error_feedback=False: self.codec is ALREADY resolved (the
            # EF default was applied at engine construction) — the step
            # functions must not re-wrap it
            return consensus.consensus_step(
                stacked_params, mix_, impl=impl, block_n=self.block_n,
                codec=self.codec, codec_state=codec_state, key=key,
                gamma=self.gamma, error_feedback=False,
                structure=structure)
        if kind == "sharded":
            return consensus.sharded_consensus_step(
                stacked_params, mix_, num_blocks=self.plan.num_blocks,
                axis_name=self.plan.axis_name, mesh=self.mesh,
                codec=self.codec, codec_state=codec_state, key=key,
                gamma=self.gamma, block_n=self.block_n,
                error_feedback=False, structure=structure)
        if self._schedule is None:
            self._schedule = consensus.permutation_schedule(
                self.mix, self.gamma)
        return consensus.distributed_consensus_step(
            stacked_params, mix_, axis_name=self.plan.axis_name,
            mesh=self.mesh, codec=self.codec, codec_state=codec_state,
            key=key, gamma=self.gamma, schedule=self._schedule,
            error_feedback=False, sig_override=sig_override)

    def scan_rounds(self, stacked_params, codec_state=None, keys=None, *,
                    rounds: Optional[int] = None, t0=0, telemetry=None):
        """Run many Eq.-(6) rounds inside ONE ``jax.lax.scan`` program.

        ``keys``: optional (R, …) stacked PRNG keys, one per round
        (stochastic rounding); without them pass ``rounds=R`` and every
        round runs key-free. The codec / error-feedback state threads
        through the scan carry for all four plans (``codec_state=None``
        initializes stacked zero residuals for stateful codecs), and the
        distributed plan's host-side ppermute permutation schedule is
        resolved HERE, before the scan body is traced, so the loop body
        contains only the collectives. Returns ``(params, codec_state)``
        after R rounds — bit-identical to R successive :meth:`step`
        calls. Trace-time structure (sparse gathers, schedules) is baked
        once per program instead of once per round, which is what the
        chunked drivers (:func:`repro.core.federated.run_fl_until_scan`,
        :func:`repro.core.maml.maml_train_scan`) and the ``rounds_loop``
        benchmark build on.

        Time-varying graphs run device-resident: with a non-static
        :class:`~repro.core.topology.GraphProcess` the rounds are
        numbered ``t0, t0+1, …`` (``t0`` may be traced — chunked callers
        pass each chunk's global offset) and every round's survival mask
        is generated IN-SCAN from the folded process key; no host-side
        per-round graph prefetch, and the masks are bit-identical to the
        host ``topology.dropout`` stream.

        ``telemetry`` (:class:`repro.telemetry.Telemetry`) records one
        row per round (Eq.-(11) joules by link class from the round's
        ACTUAL surviving links, disagreement, wire bits): buffered mode
        stays pure (rows ride the scan outputs, ingested host-side
        right here — so the call must run OUTSIDE any caller jit);
        streaming mode additionally emits each round live via
        ``jax.debug.callback``. Params/state are bit-identical with
        telemetry off, buffered, or streaming: the rows read the round
        state, the mixing consumes the same mask either way.
        """
        if keys is None and rounds is None:
            raise ValueError(
                f"scan_rounds got keys={keys!r} and rounds={rounds!r} — "
                "pass rounds= (a round count) or keys= (one PRNG key "
                "per round, e.g. jax.random.split(key, R))")
        if codec_state is None:
            codec_state = self.init_state(stacked_params)
        if self.plan.kind == "distributed" and self._schedule is None:
            # hoist the host-computed schedule out of the scan body
            self._schedule = consensus.permutation_schedule(
                self.mix, self.gamma)
        is_async = self.agents is not None
        R = (int(rounds) if keys is None
             else jax.tree.leaves(keys)[0].shape[0])
        ts = (t0 + jnp.arange(R, dtype=jnp.int32)
              if (self.graph.kind != "static" or is_async
                  or telemetry is not None)
              else None)
        recorder = (telemetry.recorder_for(self)
                    if telemetry is not None else None)
        stream_cb = (telemetry.stream_cb(recorder, "consensus")
                     if telemetry is not None and telemetry.streaming
                     else None)

        def body(carry, xs):
            t, k = xs
            if is_async:
                p0, st0, ast = carry
                # the round's availability facts are drawn ONCE and
                # shared between the mixing weights, the per-agent
                # freeze, and the telemetry row (which bills only
                # DELIVERED wires)
                ar = self.async_round(t, ast.age)
                p, st = self.step(p0, st0, k, survival=ar.weights)
                p = where_active(ar.act, p, p0)
                if st is not None:
                    st = where_active(ar.act, st, st0)
                ast = AsyncState(
                    ast.clock + ar.act.astype(ast.clock.dtype), ar.age)
                out = (p, st, ast)
                sv_row, act, age = ar.delivered, ar.act, ar.age
            else:
                # telemetry draws the round's survival ONCE — in the
                # plan's native shape, never a dense (K, K) rebuild —
                # and shares it with step() (survival= takes precedence
                # over t=; identical ops, so results match the
                # telemetry-off t= path bit for bit)
                sv = (self.round_survival(t)
                      if telemetry is not None and t is not None
                      else None)
                p, st = self.step(carry[0], carry[1], k, t=t, survival=sv)
                out = (p, st)
                sv_row, act, age = sv, None, None
            row = None
            if telemetry is not None:
                row = recorder.row(p, sv_row, metric=jnp.float32(0.0),
                                   reached=jnp.asarray(False),
                                   live=jnp.asarray(True),
                                   active=act, age=age)
                if stream_cb is not None:
                    jax.debug.callback(stream_cb, t, row, ordered=True)
            return out, row

        carry0 = (stacked_params, codec_state)
        if is_async:
            # NOTE: each scan_rounds call starts a FRESH AsyncState
            # (clocks and ages at zero); callers that chunk a longer
            # round loop thread the state themselves via async_step or
            # the FL drivers, which carry it across chunks
            carry0 = carry0 + (self.init_async_state(),)
        if ts is None and keys is None:
            final, rows = jax.lax.scan(
                lambda c, _x: body(c, (None, None)),
                carry0, None, length=R)
        else:
            final, rows = jax.lax.scan(body, carry0, (ts, keys))
        p, st = final[0], final[1]
        if telemetry is not None:
            telemetry.record_rounds(recorder, rows, t0, driver="consensus")
        return p, st

    # -- Eq.-(11) pricing ---------------------------------------------------
    def round_comm_joules(self, energy_params,
                          model_bits: Optional[float] = None) -> float:
        """Eq.-(11) communication energy of ONE round at THIS engine's
        wire format (delegates to the topology's codec-aware pricing)."""
        if self.topology is None:
            raise ValueError(
                f"this {self.plan.kind!r} engine was built from a raw "
                f"{self.mix.shape} mix matrix, which carries no link "
                "classes to bill; construct it from a Topology (e.g. "
                "topology.ring(K)) to price rounds")
        return self.topology.round_comm_joules(
            energy_params, model_bits=model_bits, codec=self.codec)

    # -- audit metadata -----------------------------------------------------
    def audit_meta(self) -> dict:
        """Resolved facts ``repro.analysis`` keys its checks on: the
        plan kind, its :data:`PLAN_AUDIT_EXPECTATIONS` entry, and the
        wire codec (base codec under the error-feedback wrapper, with
        its int-lane bit width if any). Rule H2 reconciles the compiled
        module's collective bytes against ``codec.model_bits(tree)``;
        the C-layer (``repro.analysis.costmodel``) additionally reads
        ``link_classes`` (the topology's per-class directed message
        counts, ``None`` on raw-mix engines) and ``priced_collectives``
        (which HLO collective kind carries the Eq.-(11)-billed wire
        payload for this plan — every other collective in the compiled
        module must be control plane or allowlisted, rule C3)."""
        base = (getattr(self.codec, "inner", self.codec)
                if self.codec is not None else None)
        meta = dict(PLAN_AUDIT_EXPECTATIONS[self.plan.kind])
        link_classes = (None if self.topology is None else {
            k: v for k, v in self.topology.links_per_round().items()
            if k != "NONE"})
        wire = meta.get("wire_collective")
        meta.update(
            plan=self.plan.kind, K=self.K,
            num_blocks=self.plan.num_blocks,
            axis_name=self.plan.axis_name,
            mesh_axis=(None if self.mesh is None else
                       dict(self.mesh.shape).get(self.plan.axis_name)),
            codec=None if self.codec is None else self.codec.name,
            qbits=getattr(base, "qbits", None),
            link_classes=link_classes,
            priced_collectives=({} if wire is None
                                else {wire: link_classes}),
        )
        return meta

    # -- conveniences -------------------------------------------------------
    @classmethod
    def wrap(cls, obj, **kw) -> "ConsensusEngine":
        """Coerce ``obj`` (engine, Topology, or concrete mix) to an
        engine; extra kwargs only apply when constructing a new one."""
        if isinstance(obj, cls):
            if any(v is not None for v in kw.values()):
                raise ValueError(
                    f"{sorted(k for k, v in kw.items() if v is not None)} "
                    "cannot be re-specified for an existing engine")
            return obj
        return cls(obj, **kw)

    def __repr__(self):
        codec = self.codec.name if self.codec is not None else None
        graph = "" if self.graph.kind == "static" else f", graph={self.graph!r}"
        agents = "" if self.agents is None else (
            f", agents={self.agents!r}, tau="
            f"{'inf' if self.tau is None else self.tau}")
        return (f"ConsensusEngine(K={self.K}, plan={self.plan.kind!r}, "
                f"codec={codec!r}, blocks={self.plan.num_blocks}"
                f"{graph}{agents})")
