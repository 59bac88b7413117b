"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is read once into plain tuples, so that the arithmetic below
can be checked against a small recorded fixture
(``bench/tests/fixtures``) without JAX:

    {"device": {plane: [(name, start_ns, dur_ns, text), ...]},
     "host":   [(name, start_ns, dur_ns), ...]}

``device`` holds the "XLA Ops" lines of each device plane; ``text``
joins the names and string stats of an op (HLO op, module, long name),
which is what a kernel is found by. ``host`` holds the harness's own
spans (names starting with ``bench.``).
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import json
import os

HOST_PREFIX = "bench."


@contextlib.contextmanager
def recording(log_dir: str):
    """Profile the block into ``log_dir`` (device ops and host
    annotations; the Python call tracer stays off)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(log_dir: str) -> dict:
    """Events of the newest ``.xplane.pb`` under ``log_dir``."""
    import jax
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    strs = [str(v) for _, v in e.stats
                            if isinstance(v, str)]
                    ops.append((op_name(e.name), int(e.start_ns),
                                int(e.duration_ns),
                                " ".join([e.name] + strs)))
            if ops:
                device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.name, int(e.start_ns),
                                     int(e.duration_ns)))
    return {"device": device, "host": host}


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``: a TPU
    trace names an op by its whole HLO instruction."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def save(events: dict, path: str):
    with open(path, "w") as f:
        json.dump(events, f)


def read(path: str) -> dict:
    with open(path) as f:
        ev = json.load(f)
    return {"device": {k: [tuple(o) for o in v]
                       for k, v in ev["device"].items()},
            "host": [tuple(h) for h in ev["host"]]}


def window(events: dict, name: str = HOST_PREFIX + "window"):
    """(start_ns, end_ns) of the named host span (the traced window)."""
    spans = [(s, s + d) for n, s, d in events["host"] if n == name]
    if not spans:
        raise ValueError(f"the trace holds no {name!r} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clipped(ops, w0, w1):
    for name, s, d, text in ops:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            yield name, a, b, text


def busy_s(events: dict, w0: int, w1: int) -> float:
    """Seconds in which some op ran on the device inside the window,
    averaged over the device planes."""
    planes = list(events["device"].values())
    if not planes:
        return 0.0
    tot = 0
    for ops in planes:
        tot += sum(b - a for a, b in _union(
            [(a, b) for _, a, b, _ in _clipped(ops, w0, w1)]))
    return tot / len(planes) / 1e9


def op_seconds(events: dict, w0: int, w1: int, pattern: str = "") -> float:
    """Device seconds of the ops whose text contains ``pattern`` (all
    ops for ""), summed over planes and divided by their number."""
    planes = list(events["device"].values())
    if not planes:
        return 0.0
    tot = sum(b - a for ops in planes
              for _, a, b, text in _clipped(ops, w0, w1)
              if pattern in text)
    return tot / len(planes) / 1e9


def top_ops(events: dict, w0: int, w1: int, n: int = 10):
    """[[op name, seconds]] of the ops that took most device time."""
    acc = {}
    planes = list(events["device"].values())
    for ops in planes:
        for name, a, b, _ in _clipped(ops, w0, w1):
            acc[name] = acc.get(name, 0) + (b - a)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / max(len(planes), 1) / 1e9] for k, v in ranked]


def idle_gaps(events: dict, w0: int, w1: int, n: int = 10):
    """[[host activity, seconds]]: the device's idle time inside the
    window (first device plane), each gap named by the innermost
    harness span around its midpoint, summed by name, largest first."""
    planes = list(events["device"].values())
    busy = _union([(a, b) for _, a, b, _ in
                   _clipped(planes[0], w0, w1)]) if planes else []
    gaps, t = [], w0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    mids = [(a + b) / 2 for a, b in gaps]
    owner = [None] * len(gaps)
    # innermost first: the shortest span around a gap's midpoint names it
    for name, s, d in sorted(events["host"], key=lambda h: h[2]):
        for i in range(bisect.bisect_left(mids, s),
                       bisect.bisect_right(mids, s + d)):
            if owner[i] is None:
                owner[i] = name
    acc = {}
    for (a, b), name in zip(gaps, owner):
        name = name or "outside harness spans"
        acc[name] = acc.get(name, 0) + (b - a)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def idle_share(events: dict, w0: int, w1: int) -> float:
    """Percent of the window with no op on the device (1 − busy/window,
    busy the union of op intervals, averaged over device planes)."""
    return 100.0 * (1.0 - busy_s(events, w0, w1) / ((w1 - w0) / 1e9))
