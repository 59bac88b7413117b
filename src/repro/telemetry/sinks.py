"""Event sinks for :class:`repro.telemetry.Telemetry`.

A sink is anything with ``emit(event: dict)`` and (optionally)
``close()``. Sinks receive finalized HOST events only — plain dicts of
Python scalars (plus the length-K per-agent attribution lists), never
tracers — at chunk boundaries in buffered mode or per round (from the
``jax.debug.callback``) in streaming mode. Frozen padding rounds are
filtered before sinks see anything.
"""
from __future__ import annotations

import json


class MemorySink:
    """Collect events in a list (tests)."""

    def __init__(self):
        self.events = []

    def emit(self, event: dict):
        self.events.append(event)

    def close(self):
        pass


class JsonlSink:
    """One JSON object per line. The file opens lazily on the first
    event and flushes per emit, so a live ``tail -f`` of a streaming run
    sees rounds as they happen."""

    def __init__(self, path):
        self.path = path
        self._fh = None
        self.count = 0

    def emit(self, event: dict):
        if self._fh is None:
            self._fh = open(self.path, "w")
        # allow_nan=False: the emitted log must be strict JSON — a NaN
        # metric would poison downstream schema validation
        self._fh.write(json.dumps(event, allow_nan=False) + "\n")
        self._fh.flush()
        self.count += 1

    def close(self):
        if self._fh is not None:
            self._fh.close()
            self._fh = None
