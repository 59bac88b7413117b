"""What every cell of the benchmark shares: locating its data files, the
device check, the compile cache, the window clock, the comparison
helpers and the result line.

Nothing here knows a cell by name. A cell is an entry of
``BENCHMARK.json``; its configuration, traffic mix, reference and
per-layer readers are files found by the names in that entry:

    bench/configs/<config>.json      sizes as run (+ the plain reference
    bench/reference/<config>.py       beside it, by the same name)
    bench/traffic/<traffic>.json     parameters of one traffic mix; its
                                     "driver" names bench/drivers/<x>.py
    bench/metrics/<metric>.py        a per-layer reader: read(run) -> float
                                     or None when there is nothing to read
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import pathlib
import statistics
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache: one fixed directory inside the
#: checkout, so that a cell's second run finds what its first compiled.
CACHE_DIR = ROOT / ".jax_cache"


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_module(path: pathlib.Path, name: str | None = None):
    """Import a file of the benchmark by path (names may hold '-' and
    '.', which ``import`` cannot)."""
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def cell_entries(bench: dict, workload: str):
    """(workload entry, config entry, traffic dict) of one cell."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(BENCH / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)
    return cell, cfg, traffic


def metrics_of(bench: dict, workload: str, kind: str):
    """The end-to-end or per-layer metric entries this cell reports."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def require_chips(chips: int):
    """The devices JAX sees, or NoDevice: the measurement path never
    falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs[:chips]


def enable_cache():
    """Point JAX's persistent cache at the checkout, cache every program
    however fast it compiled (so set-up is the same work on every warm
    run), and let the program's own helper take that directory."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from repro.launch import compile_cache
    compile_cache.enable_compile_cache()


class CompileCounter:
    """Counts tracing, backend compiles and persistent-cache reads as
    JAX reports them through ``jax.monitoring``; read as a delta around
    the window."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.n = 0
        self.backend_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.n += 1
            if event == self.EVENTS[1]:
                self.backend_s += duration


def trace_counts() -> int:
    from repro.core import scanloop
    return sum(scanloop.TRACE_COUNTS.values())


def device_info(devs) -> dict:
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def peaks(device_kind: str) -> dict:
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table['devices'])}")
    return table["devices"][device_kind]


@contextlib.contextmanager
def span(name: str):
    """A host span of the harness, written into the profiler's trace
    when one is recording (and nearly free when none is)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


def p95(values) -> float:
    """95th percentile, ``statistics.quantiles`` (exclusive method)."""
    return statistics.quantiles(values, n=20)[-1]


# ---------------------------------------------------------------------------
# comparison helpers (numpy on the host; no program code)
# ---------------------------------------------------------------------------

def worst_leaf_gap(prog: list, ref: list, grad_ref: list | None = None,
                   floor: float = 0.0):
    """The contract's gap of norms: per leaf |‖prog‖ − ‖ref‖| over the
    larger of the reference leaf's norm and the median leaf's (and
    ``floor``), worst leaf. Leaves whose reference gradient
    (``grad_ref``, default ``ref``) is under a thousandth of the median
    leaf's are left out: they move by round-off alone. Returns (gap,
    index of the worst leaf)."""
    basis = grad_ref if grad_ref is not None else ref
    med_b = statistics.median(basis)
    med = statistics.median(ref)
    worst, at = 0.0, -1
    for i, (p, r, b) in enumerate(zip(prog, ref, basis)):
        if b < 1e-3 * med_b:
            continue
        g = abs(p - r) / max(r, med, floor, 1e-30)
        if g > worst:
            worst, at = g, i
    return worst, at


def rel_gap(prog, ref, floor: float = 0.0) -> float:
    """Largest |prog − ref| / max(|ref|, floor) over paired sequences."""
    return max(abs(float(p) - float(r)) / max(abs(float(r)), floor, 1e-30)
               for p, r in zip(prog, ref))


def judge(checks: dict, limits: dict) -> tuple:
    """``checks`` name -> value; ``limits`` name -> limit. Returns
    (correct, {name: {"value", "limit"}}). A value that is missing, not
    finite or over its limit fails; every failure is printed by name."""
    out, ok = {}, True
    for name, limit in limits.items():
        v = checks.get(name)
        good = v is not None and math.isfinite(v) and v <= limit
        if not good:
            ok = False
            print(f"check failed: {name} = {v} exceeds its limit {limit}",
                  file=sys.stderr)
        out[name] = {"value": v, "limit": limit}
    return ok, out


def emit(result: dict, checks: dict):
    """The run's last lines: each compared number beside its limit on
    stderr, then the result object (``checks`` last) on stdout."""
    for name, c in checks.items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    result = dict(result)
    result["checks"] = checks
    sys.stdout.flush()
    print(json.dumps(result))
    sys.stdout.flush()


class Clock:
    """Host timestamps of a run: set-up from process start to the
    window, then one stamp per chunk boundary inside the window."""

    def __init__(self, t_start: float):
        self.t_start = t_start
        self.window_start = None
        self.stamps = []          # (t, rounds completed in the chunk)

    def now(self) -> float:
        return time.perf_counter()

    def open(self):
        self.window_start = self.now()
        self.stamps = []

    def chunk(self, rounds: int):
        self.stamps.append((self.now(), rounds))

    @property
    def setup_s(self) -> float:
        return self.window_start - self.t_start

    def chunk_ms(self) -> list:
        ts = [self.window_start] + [t for t, _ in self.stamps]
        return [(b - a) * 1e3 for a, b in zip(ts, ts[1:])]

    def rounds(self) -> int:
        return sum(n for _, n in self.stamps)


class Window:
    """The measured window: opened once set-up is done, stamped at every
    chunk boundary the host sees, with the profiler recording its first
    ``trace_seconds`` when a trace directory is given."""

    def __init__(self, clock: Clock, seconds: float, counter,
                 trace_dir: str | None = None, trace_seconds: float = 3.0):
        self.clock, self.seconds, self.counter = clock, seconds, counter
        self.trace_dir, self.trace_seconds = trace_dir, trace_seconds
        self.is_open = False
        self.closed = False
        self.traced_rounds = 0
        self.compiles = None
        self._tracing = None
        self._span = None

    def open(self):
        from bench import trace
        self._n0 = self.counter.n + trace_counts()
        if self.trace_dir is not None:
            self._tracing = trace.recording(self.trace_dir)
            self._tracing.__enter__()
            import jax
            self._span = jax.profiler.TraceAnnotation(trace.HOST_PREFIX
                                                      + "window")
            self._span.__enter__()
        self.clock.open()
        self.is_open = True

    def chunk(self, rounds: int):
        self.clock.chunk(rounds)
        if self._tracing is not None:
            self.traced_rounds += rounds
            if self.elapsed() >= self.trace_seconds:
                self._stop_trace()

    def _stop_trace(self):
        self._span.__exit__(None, None, None)
        self._tracing.__exit__(None, None, None)
        self._tracing = None

    def elapsed(self) -> float:
        return self.clock.now() - self.clock.window_start

    def expired(self) -> bool:
        return self.elapsed() >= self.seconds

    def close(self):
        if self._tracing is not None:
            self._stop_trace()
        self.compiles = self.counter.n + trace_counts() - self._n0
        self.is_open = False
        self.closed = True


class LedgerSink:
    """Keeps the Eq.-(11) ledger's running totals (the trainer's
    ``--metrics`` stream, held in memory instead of a file)."""

    def __init__(self):
        self.events = 0
        self.joules = 0.0

    def emit(self, event: dict):
        self.events += 1
        self.joules += event.get("joules", 0.0)


def chunk_telemetry(on_chunk):
    """A buffered ``repro.telemetry.Telemetry`` that calls
    ``on_chunk(driver, start, events)`` at every chunk boundary, after
    the host has the chunk's rows (span ``bench.sync``) and has priced
    them into the ledger (span ``bench.ledger``)."""
    import jax
    from repro.telemetry import Telemetry

    class ChunkTelemetry(Telemetry):
        def record_rounds(self, recorder, rows, start, driver="fl",
                          extra=None):
            with span("bench.sync"):
                jax.block_until_ready(rows)
            with span("bench.ledger"):
                events = super().record_rounds(recorder, rows, start,
                                               driver, extra)
            on_chunk(driver, start, events)
            return events

        def record_maml_rounds(self, metrics, start, extra=None):
            with span("bench.sync"):
                jax.block_until_ready(metrics)
            with span("bench.ledger"):
                events = super().record_maml_rounds(metrics, start, extra)
            on_chunk("maml", start, events)
            return events

    return ChunkTelemetry(sinks=(LedgerSink(),), capacity=4096)
