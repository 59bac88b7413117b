"""Device-side metric rows and the host-side ring buffer.

The scanned drivers cannot emit anything mid-chunk on the default path —
a chunk is ONE compiled XLA program (see :mod:`repro.core.scanloop`) —
so per-round observability has to ride the scan outputs: each round
appends one fixed-shape ROW to the chunk's stacked ys. A row is ONE
packed ``(F,)`` int32 vector, ``F = 9 + 3K`` columns laid out by
:data:`ROW_LAYOUT` — the flags ``live``/``reached`` as 0/1, ``metric``
and ``disagreement`` as their float32 bits
(``lax.bitcast_convert_type``), the five int32 counts, then the three
(K,) per-agent count vectors — so a chunk's rows are one ``(rounds,
F)`` int32 buffer and reach the host in ONE device→host copy per chunk
(:meth:`RoundRecorder.fetch`), unpacked there into numpy views. The
bitcast loses no bits and the counts stay exact int32, so the priced
stream is bit-for-bit what a dict of typed fields would give. That
keeps the buffered path pure (no callbacks → JX1/JX4-clean and
program-cache-admissible) and bit-parity trivial: the row computation
reads the round's state, it never feeds back into it.

Two halves live here:

* :class:`RoundRecorder` — built per engine; its :meth:`RoundRecorder.row`
  runs INSIDE the trace and records only what must be measured on
  device: exact int32 surviving-link counts per class (from the same
  plan-shaped ``engine.round_survival(t)`` the mixing consumed — never
  a re-draw, never a dense (K, K) rebuild),
  consensus disagreement ‖x_i − x̄‖, the round's eval metric, and
  reached/live flags. Everything derivable on the host — Eq.-(11)
  joules, wire bits — is priced in :meth:`RoundRecorder.finalize` in
  float64 with the LITERAL :meth:`Topology.round_comm_joules
  <repro.core.topology.Topology.round_comm_joules>` expression, so the
  summed stream reconciles EXACTLY (``==``, not ``pytest.approx``) with
  the post-hoc billing replay in :mod:`repro.rl.casestudy`.
* :class:`MetricBuffer` — the host ring buffer the finalized events land
  in; fixed capacity (oldest rounds dropped) or unbounded.
"""
from __future__ import annotations

import collections
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import energy, topology as topo_lib

#: traced per-round row fields, in emission order. ``live`` marks real
#: rounds (False = the frozen lax.cond branch after the target was hit
#: or past max_rounds — zero links, excluded from ledgers and sinks).
#: ``n_active``/``max_age`` are the async (agent-availability) health
#: observables: how many agents participated, and the oldest wire any
#: receiver is still mixing — K and 0 on lockstep rounds.
#: ``agent_sl``/``agent_ul``/``agent_dl`` are the only non-scalar rows:
#: (K,) int32 per-SENDER surviving-wire counts (``link_class[k, h]``
#: classes the h → k message, so the transmitting agent h pays) — the
#: per-agent attribution of the same aggregate ``n_*`` counts, summing
#: exactly to them, and exactly zero for an agent that slept or whose
#: every link died.
#:
#: ``ROW_LAYOUT`` packs them, in this order, into one int32 vector:
#: (field, how it is stored, columns) — ``flag`` 0/1, ``f32`` the
#: float32 bits, ``i32`` as is; ``"K"`` columns = one per agent.
ROW_LAYOUT = (
    ("live", "flag", 1), ("reached", "flag", 1),
    ("metric", "f32", 1), ("disagreement", "f32", 1),
    ("n_sl", "i32", 1), ("n_ul", "i32", 1), ("n_dl", "i32", 1),
    ("n_active", "i32", 1), ("max_age", "i32", 1),
    ("agent_sl", "i32", "K"), ("agent_ul", "i32", "K"),
    ("agent_dl", "i32", "K"),
)
ROW_FIELDS = tuple(name for name, _, _ in ROW_LAYOUT)


def consensus_disagreement(stacked):
    """Mean over agents of ‖x_i − x̄‖ (f32, full flattened tree) — the
    convergence observable of the consensus plans. Traced; runs on the
    POST-mix params so round r reports the disagreement its own mixing
    left behind."""
    leaves = jax.tree.leaves(stacked)
    K = leaves[0].shape[0]
    sq = jnp.zeros((K,), jnp.float32)
    for x in leaves:
        xf = x.astype(jnp.float32).reshape(K, -1)
        d = xf - jnp.mean(xf, axis=0, keepdims=True)
        sq = sq + jnp.sum(d * d, axis=1)
    return jnp.mean(jnp.sqrt(sq))


class RoundRecorder:
    """Per-engine row maker (traced) + event pricer (host, float64).

    Construction bakes the engine's static billing constants the same
    way the post-hoc replay computes them: ``bits`` =
    ``codec.price_bits(p.model_bits)`` (or raw ``model_bits`` uncoded)
    and the class link masks from ``topology.link_class``. Per-edge
    heterogeneous pricing (``edge_efficiency``) is refused — in-scan
    rows carry per-CLASS counts only.
    """

    def __init__(self, engine, energy_params=None):
        topo = getattr(engine, "topology", None)
        if topo is None:
            raise ValueError(
                "telemetry needs an engine built from a Topology (raw "
                "mixing matrices carry no link classes to bill)")
        if topo.edge_efficiency is not None:
            raise NotImplementedError(
                "per-edge efficiencies are priced post-hoc only; in-scan "
                "telemetry rows carry per-class counts")
        self.engine = engine
        self.topology = topo
        self.codec = engine.codec
        self.energy_params = (energy_params
                              or energy.paper_calibrated("fig3"))
        link_class = np.asarray(topo.link_class)
        # the per-class link table in the ENGINE PLAN's native survival
        # shape, so masked-round counts never touch a (K, K) buffer on
        # the plans that avoid one (rule H1 holds with dropout active):
        # (K, K) classes on dense-xla, (K, H) lane classes on
        # sparse-pallas/sharded (padding lanes -> NONE), (M, K)
        # schedule-slot classes on distributed (completion padding ->
        # NONE). Every real directed edge appears exactly once in each
        # representation, so the per-class counts are identical ints.
        if engine.plan.kind == "distributed":
            srcs, real = engine.schedule_structure()
            rows = np.arange(srcs.shape[1])[None, :]
            table = np.where(real, link_class[rows, srcs], topo_lib.NONE)
        elif engine.plan.kind in ("sparse-pallas", "sharded"):
            idx, valid = engine.lane_structure()
            rows = np.arange(idx.shape[0])[:, None]
            table = np.where(valid, link_class[rows, idx], topo_lib.NONE)
        else:
            table = link_class
        self._class_masks = {
            "SL": table == topo_lib.SL,
            "UL": table == topo_lib.UL,
            "DL": table == topo_lib.DL,
        }
        # real lanes in the plan shape — max_age reads only these (the
        # sparse plans' padding lanes and the distributed completion
        # slots never deliver, so their ages grow without meaning)
        self._real_mask = table != topo_lib.NONE
        self._static_counts = {
            "SL": int((link_class == topo_lib.SL).sum()),
            "UL": int((link_class == topo_lib.UL).sum()),
            "DL": int((link_class == topo_lib.DL).sum()),
        }
        # per-SENDER attribution: which agent each table position bills.
        # link_class[k, h] classes the h → k message, so the sender is
        # the second index — column h on dense (K, K), the neighbour
        # table idx[i, h] on the lane plans, the schedule sources
        # srcs[m, k] on distributed. None = dense (axis sum, no scatter).
        if engine.plan.kind == "distributed":
            self._sender_index = np.asarray(srcs)
        elif engine.plan.kind in ("sparse-pallas", "sharded"):
            self._sender_index = np.asarray(idx)
        else:
            self._sender_index = None
        K = topo.K
        self._static_agent_counts = {}
        for name, cls in (("SL", topo_lib.SL), ("UL", topo_lib.UL),
                          ("DL", topo_lib.DL)):
            hit = (table == cls)
            if self._sender_index is None:
                per = hit.sum(axis=0)
            else:
                per = np.zeros((K,), np.int64)
                np.add.at(per, self._sender_index, hit)
            self._static_agent_counts[name] = per.astype(np.int32)
        p = self.energy_params
        bits = p.model_bits
        if self.codec is not None:
            bits = self.codec.price_bits(bits)
        self._priced_bits = float(bits)
        # each field's columns in the packed row; width F = 9 + 3K
        self._columns, at = {}, 0
        for name, _, width in ROW_LAYOUT:
            n = K if width == "K" else 1
            self._columns[name] = slice(at, at + n)
            at += n
        self.width = at

    # -- traced (inside the scan body) ----------------------------------

    def _per_agent(self, hit):
        """(K,) int32 per-SENDER count of the True positions of ``hit``
        (plan-shaped bool). Dense sums the receiver axis; the lane/slot
        plans scatter-add over their baked sender index."""
        if self._sender_index is None:
            return jnp.sum(hit, axis=0, dtype=jnp.int32)
        return jnp.zeros((self.topology.K,), jnp.int32).at[
            jnp.asarray(self._sender_index)].add(
            jnp.asarray(hit, jnp.int32))

    @jax.named_scope("telemetry_row")
    def row(self, stacked, survival, *, metric, reached, live,
            active=None, age=None):
        """One live round's row, packed into one ``(F,)`` int32 vector
        (:data:`ROW_LAYOUT`; :meth:`unpack` reads it back).

        ``survival`` is the PLAN-SHAPED surviving-edge operand the
        round's mixing ACTUALLY used — from
        ``engine.round_survival(t)``: (K, K) on dense-xla, (K, H) lanes
        on sparse-pallas/sharded, (M, K) slots on distributed (``None``
        on static graphs, where the counts are numpy constants folded
        into the program). Counts stay exact int32 in every shape, so
        the priced stream reconciles with the post-hoc replay.

        Async rounds pass ``survival=round.delivered`` (wires ACTUALLY
        shipped — Eq.-(11) bills nothing a sleeping agent didn't send),
        plus ``active=`` (K,) activity bools and ``age=`` the
        plan-shaped wire ages; lockstep rounds leave both None and the
        row reports full participation (``n_active = K, max_age = 0``).
        """
        if survival is None:
            counts = {k: jnp.int32(self._static_counts[k])
                      for k in ("SL", "UL", "DL")}
            agents = {k: jnp.asarray(self._static_agent_counts[k])
                      for k in ("SL", "UL", "DL")}
        else:
            counts, agents = {}, {}
            for k in ("SL", "UL", "DL"):
                hit = survival & jnp.asarray(self._class_masks[k])
                counts[k] = jnp.sum(hit, dtype=jnp.int32)
                agents[k] = self._per_agent(hit)
        n_active = (jnp.int32(self.topology.K) if active is None
                    else jnp.sum(jnp.asarray(active), dtype=jnp.int32))
        max_age = (jnp.int32(0) if age is None
                   else jnp.max(jnp.where(jnp.asarray(self._real_mask),
                                          jnp.asarray(age, jnp.int32),
                                          jnp.int32(0))))
        return self._pack({
            "live": jnp.asarray(live, bool),
            "reached": jnp.asarray(reached, bool),
            "metric": jnp.asarray(metric, jnp.float32),
            "disagreement": consensus_disagreement(stacked),
            "n_sl": counts["SL"], "n_ul": counts["UL"],
            "n_dl": counts["DL"],
            "n_active": n_active, "max_age": max_age,
            "agent_sl": agents["SL"], "agent_ul": agents["UL"],
            "agent_dl": agents["DL"],
        })

    def frozen_row(self):
        """The frozen ``lax.cond`` branch's row: all-zero, ``live`` off —
        pricing and ledgers skip it, so post-hit padding rounds never
        bill."""
        z32 = jnp.int32(0)
        zk = jnp.zeros((self.topology.K,), jnp.int32)
        return self._pack({
            "live": jnp.asarray(False), "reached": jnp.asarray(False),
            "metric": jnp.float32(0.0), "disagreement": jnp.float32(0.0),
            "n_sl": z32, "n_ul": z32, "n_dl": z32,
            "n_active": z32, "max_age": z32,
            "agent_sl": zk, "agent_ul": zk, "agent_dl": zk})

    @staticmethod
    def _pack(fields) -> jax.Array:
        """Row fields → the packed ``(F,)`` int32 row
        (:data:`ROW_LAYOUT`)."""
        parts = []
        for name, kind, _ in ROW_LAYOUT:
            v = fields[name]
            if kind == "f32":
                v = jax.lax.bitcast_convert_type(v, jnp.int32)
            parts.append(jnp.reshape(jnp.asarray(v, jnp.int32), (-1,)))
        return jnp.concatenate(parts)

    # -- host (once per chunk, after the sync) --------------------------

    def price(self, n_sl: int, n_ul: int, n_dl: int) -> dict:
        """Eq.-(11) joules of one round from its surviving per-class
        counts — float64, written as the SAME Python expression
        ``Topology.round_comm_joules`` evaluates (float addition is not
        associative; matching the expression keeps the stream's sum
        bitwise equal to the post-hoc replay)."""
        p = self.energy_params
        bits = self._priced_bits
        sl_cost = energy.sidelink_cost_per_bit(p)
        return {
            "wire_bits": bits * (n_sl + n_ul + n_dl),
            "joules_sl": bits * (n_sl * sl_cost),
            "joules_ul": bits * (n_ul / p.E_UL),
            "joules_dl": bits * (n_dl / p.E_DL),
            "joules": bits * (n_sl * sl_cost
                              + n_ul / p.E_UL + n_dl / p.E_DL),
        }

    def price_agents(self, agent_sl, agent_ul, agent_dl) -> list:
        """Per-agent Eq.-(11) joules from the per-SENDER counts — the
        same literal expression as :meth:`price` per agent, so an agent
        with zero surviving sends bills exactly ``0.0`` (a sleeping
        agent transmits nothing and pays nothing)."""
        p = self.energy_params
        bits = self._priced_bits
        sl_cost = energy.sidelink_cost_per_bit(p)
        return [bits * (int(a_sl) * sl_cost
                        + int(a_ul) / p.E_UL + int(a_dl) / p.E_DL)
                for a_sl, a_ul, a_dl in zip(agent_sl, agent_ul, agent_dl)]

    def fetch(self, rows) -> dict:
        """A chunk's stacked packed rows, ``(rounds, F)`` int32 on the
        device → the host field arrays :meth:`finalize` reads: ONE
        device→host copy, then numpy views (:meth:`unpack`)."""
        return self.unpack(np.asarray(rows))

    def unpack(self, packed) -> dict:
        """Packed row(s), shape ``(..., F)`` → ``{field: array}`` with the
        leading shape kept: flags as bool, ``metric``/``disagreement``
        as float32 (a view of the same bits), counts as int32, and the
        ``agent_*`` fields with a trailing K axis. A device array is
        copied to the host first, in one copy."""
        a = np.asarray(packed)
        if a.dtype != np.int32 or a.shape[-1:] != (self.width,):
            raise ValueError(
                f"packed rows must be int32 (..., {self.width}) for "
                f"K={self.topology.K}, got {a.dtype} {a.shape}; pass "
                f"what RoundRecorder.row / frozen_row of this recorder "
                f"returned")
        host = {}
        for name, kind, width in ROW_LAYOUT:
            v = a[..., self._columns[name]]
            if width != "K":
                v = v[..., 0]
            if kind == "f32":
                v = v.view(np.float32)
            elif kind == "flag":
                v = v != 0
            host[name] = v
        return host

    def finalize(self, host, start: int, driver: str = "fl",
                 extra: Optional[dict] = None):
        """Host rows (:meth:`fetch`, leading axis = rounds) → list of
        host event dicts, one per round, priced in float64."""
        n = host["live"].shape[0]
        base = {"type": "round", "driver": driver,
                "plan": self.engine.plan.kind,
                "topology": self.topology.name, "K": int(self.topology.K)}
        if extra:
            base.update(extra)
        events = []
        for i in range(n):
            e = dict(base)
            e["round"] = int(start) + i
            e["live"] = bool(host["live"][i])
            e["reached"] = bool(host["reached"][i])
            e["metric"] = float(host["metric"][i])
            e["disagreement"] = float(host["disagreement"][i])
            n_sl = int(host["n_sl"][i])
            n_ul = int(host["n_ul"][i])
            n_dl = int(host["n_dl"][i])
            e.update(n_sl=n_sl, n_ul=n_ul, n_dl=n_dl,
                     edges=n_sl + n_ul + n_dl,
                     n_active=int(host["n_active"][i]),
                     max_age=int(host["max_age"][i]))
            e.update(self.price(n_sl, n_ul, n_dl))
            a_sl = [int(v) for v in host["agent_sl"][i]]
            a_ul = [int(v) for v in host["agent_ul"][i]]
            a_dl = [int(v) for v in host["agent_dl"][i]]
            e.update(agent_sl=a_sl, agent_ul=a_ul, agent_dl=a_dl,
                     agent_joules=self.price_agents(a_sl, a_ul, a_dl))
            events.append(e)
        return events

    def event(self, t: int, row, driver: str = "fl",
              extra: Optional[dict] = None) -> dict:
        """One round's event from its packed ``(F,)`` row (the streaming
        callback path)."""
        single = self.unpack(np.asarray(row)[None])
        return self.finalize(single, start=int(t), driver=driver,
                             extra=extra)[0]


class MetricBuffer:
    """Host-side ring buffer of finalized round events. ``capacity``
    bounds retention (oldest rounds dropped first); ``None`` keeps
    everything — the default, since one event is a few hundred bytes."""

    def __init__(self, capacity: Optional[int] = None):
        self.capacity = capacity
        self._events = collections.deque(maxlen=capacity)
        self.dropped = 0            # rounds evicted by the ring

    def append(self, event: dict):
        if (self.capacity is not None
                and len(self._events) == self.capacity):
            self.dropped += 1
        self._events.append(event)

    def extend(self, events):
        for e in events:
            self.append(e)

    def rows(self, live_only: bool = True):
        """Events in round order; ``live_only`` drops the frozen
        padding rounds (the default — they carry no information)."""
        if live_only:
            return [e for e in self._events if e.get("live", True)]
        return list(self._events)

    def __len__(self):
        return len(self._events)

    def clear(self):
        self._events.clear()
        self.dropped = 0
