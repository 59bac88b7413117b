"""repro.telemetry — per-round energy/comms/convergence metrics out of
compiled scan chunks.

The scanned drivers (``federated.run_fl_until_scan``,
``maml.maml_train_scan``, ``engine.scan_rounds``) compile ``chunk``
rounds into one XLA program and sync once per chunk — which is exactly
why nothing used to escape a chunk at round granularity. This package
restores observability without giving that up, in two modes with a
sharp contract:

**buffered** (default) — stays PURE. Each round's metrics ride the scan
outputs as one fixed-shape row (:class:`~repro.telemetry.buffer
.RoundRecorder`), packed into a single int32 vector (flags 0/1, the two
float32 observables as their bits, the per-class and per-agent counts;
column layout :data:`~repro.telemetry.buffer.ROW_LAYOUT`), so a chunk's
rows are one ``(rounds, 9 + 3K)`` int32 buffer that reaches the host in
ONE device→host copy at the chunk boundary. There it is unpacked into
numpy views, priced (Eq.-11 joules by UL/DL/SL class, wire bits) in
float64 and appended to the :class:`~repro.telemetry.buffer.MetricBuffer`
and sinks.
No callbacks enter the trace, so buffered programs remain
program-cache-admissible — they cache under a key extended with
:meth:`Telemetry.trace_signature` — and the JX1/JX4 purity audits hold.
Round results are bit-identical to telemetry-off: rows READ the round
state, they never feed back into it.

**streaming** — opt-in liveness. The same rows are additionally emitted
round-by-round from INSIDE the chunk via ``jax.debug.callback``
(ordered), so sinks see round ``t`` while round ``t+1`` is still on
device. The callback closes over host state, so streaming programs are
impure by construction: the drivers key them OUT of
``scanloop.cached_program`` entirely (built per call, never admitted),
and the JX4 analysis rule proves no cached program ever contains a
``debug_callback``. Params/t_i/history remain bit-identical — the
callback only observes.

Sinks (:mod:`~repro.telemetry.sinks`) are pluggable: in-memory for
tests, JSONL event log (schema-checked by
``python -m repro.telemetry.schema``). ``report()`` counts the
device→host copies the chunk fetches made (``fetch_copies``, one per
chunk: ``fetched_chunks``) and adds the harness counters —
``scanloop.TRACE_COUNTS``, program-cache
hits/misses/evictions, per-``ProgramRecord`` donation flags — so one
call answers both "what did each round cost?" and "did the sweep
recompile or recopy anything?".
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from repro.core import energy
from repro.telemetry.buffer import (MetricBuffer, RoundRecorder,
                                    consensus_disagreement, ROW_FIELDS)
from repro.telemetry.report import harness_report
from repro.telemetry.schema import validate_event, validate_jsonl
from repro.telemetry.sinks import JsonlSink, MemorySink
from repro.telemetry import spans

__all__ = [
    "Telemetry", "MetricBuffer", "RoundRecorder", "ROW_FIELDS",
    "consensus_disagreement", "harness_report",
    "validate_event", "validate_jsonl",
    "MemorySink", "JsonlSink",
]

MODES = ("buffered", "streaming")


class Telemetry:
    """Run-scoped telemetry configuration + collected events.

    One instance is threaded through a driver (or ``MTLProtocol`` /
    ``CaseStudy`` / ``train_federated``); every chunk lands its rounds
    here. ``mode`` picks the contract described in the module docstring;
    ``energy_params`` prices the ledger (defaults to the paper's Fig.-3
    calibration); ``capacity`` bounds the in-memory ring buffer.
    """

    def __init__(self, mode: str = "buffered", sinks=(),
                 energy_params=None, capacity: Optional[int] = None):
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
        self.mode = mode
        self.sinks = tuple(sinks)
        self.energy_params = (energy_params
                              or energy.paper_calibrated("fig3"))
        self.buffer = MetricBuffer(capacity)
        self._recorders: dict = {}      # id(engine) -> (engine, recorder)
        self.fetch_copies = 0           # device→host copies of chunk rows
        self.fetched_chunks = 0

    # -- identity of the traced program ---------------------------------

    @property
    def streaming(self) -> bool:
        return self.mode == "streaming"

    def trace_signature(self) -> tuple:
        """What this instance bakes into a driver's TRACED program —
        part of the ``cached_program`` key for buffered programs (their
        extra row outputs change the jaxpr, so they must not collide
        with telemetry-off entries). Streaming programs never reach a
        cache key at all: their callback closes over this instance, so
        the drivers build them per call, uncached."""
        return ("telemetry", self.mode)

    # -- recorders ------------------------------------------------------

    def recorder_for(self, engine, energy_params=None) -> RoundRecorder:
        """The per-engine :class:`RoundRecorder` (memoized by engine
        identity, so the traced row fn and the host pricer agree).
        ``energy_params`` overrides this instance's pricing for the
        recorder CREATED here (first creation wins) — orchestrators like
        ``CaseStudy`` pre-register their engines with their own billing
        constants so the stream reconciles with their post-hoc ledger."""
        hit = self._recorders.get(id(engine))
        if hit is not None and hit[0] is engine:
            return hit[1]
        rec = RoundRecorder(engine, energy_params or self.energy_params)
        self._recorders[id(engine)] = (engine, rec)
        return rec

    # -- host ingestion (once per chunk) --------------------------------

    def record_rounds(self, recorder: RoundRecorder, rows, start,
                      driver: str = "fl", extra: Optional[dict] = None):
        """Finalize one chunk's stacked rows into events: price, append
        to the buffer, and (buffered mode) emit live rounds to sinks —
        streaming mode already emitted them from inside the chunk, so
        here it only fills the buffer."""
        if any(isinstance(x, jax.core.Tracer) for x in jax.tree.leaves(rows)):
            if self.streaming:
                return []       # sinks got the rounds via the callback
            raise ValueError(
                "buffered telemetry cannot ingest rows under an outer "
                "jit (they are tracers, not values) — run the driver "
                "outside jit, or use streaming mode, whose "
                "jax.debug.callback emits from inside the trace")
        copies = self._count_fetch(rows)
        with spans.span("telemetry.fetch", copies=copies):
            host = recorder.fetch(rows)
        with spans.span("telemetry.price"):
            events = recorder.finalize(host, int(start), driver=driver,
                                       extra=extra)
            self.buffer.extend(events)
            if not self.streaming:
                for e in events:
                    if e["live"]:
                        self._emit(e)
        return events

    def record_maml_rounds(self, metrics, start,
                           extra: Optional[dict] = None):
        """Meta-training rounds from a chunk's stacked metrics dict
        (``meta_loss`` required; ``meta_grad_norm`` optional)."""
        if any(isinstance(x, jax.core.Tracer)
               for x in jax.tree.leaves(metrics)):
            if self.streaming:
                return []
            raise ValueError(
                "buffered telemetry cannot ingest meta metrics under an "
                "outer jit — use streaming mode")
        copies = self._count_fetch(metrics)
        with spans.span("telemetry.fetch", copies=copies):
            loss = np.asarray(metrics["meta_loss"])
            gn = metrics.get("meta_grad_norm")
            gn = None if gn is None else np.asarray(gn)
        with spans.span("telemetry.price"):
            events = []
            for i in range(loss.shape[0]):
                e = {"type": "round", "driver": "maml",
                     "round": int(start) + i, "live": True,
                     "meta_loss": float(loss[i])}
                if gn is not None:
                    e["meta_grad_norm"] = float(gn[i])
                if extra:
                    e.update(extra)
                events.append(e)
            self.buffer.extend(events)
            if not self.streaming:
                for e in events:
                    self._emit(e)
        return events

    def _count_fetch(self, tree) -> int:
        """Device→host copies a chunk fetch of ``tree`` makes (one per
        device-array leaf), added to the run's totals."""
        copies = sum(isinstance(x, jax.Array) for x in jax.tree.leaves(tree))
        self.fetch_copies += copies
        self.fetched_chunks += 1
        return copies

    # -- streaming callbacks (called from INSIDE the chunk) -------------

    def stream_cb(self, recorder: RoundRecorder, driver: str = "fl",
                  extra: Optional[dict] = None):
        """Host function for ``jax.debug.callback(cb, t, row)`` — prices
        one round and emits it to the sinks as it happens. Frozen rounds
        are dropped. The buffer is NOT filled here (the chunk-boundary
        :meth:`record_rounds` does that in both modes, keeping buffer
        contents identical across modes)."""
        def cb(t, row):
            e = recorder.event(int(np.asarray(t)), row, driver=driver,
                               extra=extra)
            if e["live"]:
                self._emit(e)
        return cb

    def maml_stream_cb(self, extra: Optional[dict] = None):
        """Host function for the meta-training streaming callback:
        ``jax.debug.callback(cb, t, meta_loss, meta_grad_norm)``."""
        def cb(t, meta_loss, meta_grad_norm):
            e = {"type": "round", "driver": "maml",
                 "round": int(np.asarray(t)), "live": True,
                 "meta_loss": float(np.asarray(meta_loss)),
                 "meta_grad_norm": float(np.asarray(meta_grad_norm))}
            if extra:
                e.update(extra)
            self._emit(e)
        return cb

    def _emit(self, event: dict):
        for sink in self.sinks:
            sink.emit(event)

    # -- reading back ---------------------------------------------------

    def events(self, live_only: bool = True, driver: Optional[str] = None):
        out = self.buffer.rows(live_only=live_only)
        if driver is not None:
            out = [e for e in out if e.get("driver") == driver]
        return out

    def joules(self, driver: str = "fl",
               task_id: Optional[int] = None) -> float:
        """Summed per-round Eq.-(11) ledger over live rounds — plain
        left-to-right ``sum`` of the float64 stream, so under identical
        masks it equals the post-hoc replay
        (``ProtocolResult.fl_comm_joules_measured``) EXACTLY."""
        return sum(e["joules"] for e in self.events(driver=driver)
                   if task_id is None or e.get("task_id") == task_id)

    def report(self) -> dict:
        """Run summary + harness counters (see
        :func:`repro.telemetry.report.harness_report`)."""
        live = self.buffer.rows(live_only=True)
        out = {
            "mode": self.mode,
            "events": len(self.buffer),
            "live_rounds": len(live),
            "dropped": self.buffer.dropped,
            "joules": sum(e.get("joules", 0.0) for e in live),
            "wire_bits": sum(e.get("wire_bits", 0.0) for e in live),
            "fetch_copies": self.fetch_copies,
            "fetched_chunks": self.fetched_chunks,
        }
        out.update(harness_report())
        return out

    # -- lifecycle ------------------------------------------------------

    def reset(self):
        """Drop collected events and fetch counts (recorders and sinks
        stay)."""
        self.buffer.clear()
        self.fetch_copies = self.fetched_chunks = 0

    def close(self):
        for sink in self.sinks:
            close = getattr(sink, "close", None)
            if close is not None:
                close()
