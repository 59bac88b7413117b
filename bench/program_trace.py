"""The program's own host spans and device scopes in a profiler trace.

``bench/trace.py`` keeps the harness's host spans (``bench.*``) and the
device ops. The program writes spans of its own, ``repro.*``
(``repro.telemetry.spans``), on the same clock, and puts
``jax.named_scope`` names on its ops' ``op_name`` metadata. A TPU trace
keeps that path in the ``tf_op`` stat of each op's event metadata,
which ``jax.profiler.ProfileData`` does not expose: :func:`load` reads
it from the same file with the XSpace protobuf classes that the
installed TensorFlow ships (loaded by path; TensorFlow itself is not
imported). It returns the trace in ``trace.py``'s format, each op's text
followed by ``op_name=<path>`` where it has one, with both kinds of
host span, so that ``trace.idle_gaps`` names each gap by the innermost
program span.

On a program without such spans the host list holds the harness's
alone and no op names a scope: every reader built on this module then
returns None.
"""
from __future__ import annotations

import glob
import importlib.util
import os
import re

from bench import harness, trace

HOST_PREFIXES = (trace.HOST_PREFIX, "repro.")
#: the event-metadata stat that holds an op's ``op_name`` path
OP_NAME_STAT = "tf_op"
#: how an op's text gives its ``op_name`` path
OP_NAME = " op_name="


def _xplane_pb2():
    """``xplane_pb2`` of the installed TensorFlow, by path."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None:
        raise ModuleNotFoundError(
            "reading op_name paths from a trace needs the XSpace "
            "protobuf classes of an installed tensorflow")
    path = os.path.join(spec.submodule_search_locations[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    mspec = importlib.util.spec_from_file_location("bench_xplane_pb2", path)
    mod = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(mod)
    return mod


def _op_names(plane) -> dict:
    """Event-metadata id -> ``op_name`` path, of one XPlane proto."""
    stat = {k: v.name for k, v in plane.stat_metadata.items()}
    out = {}
    for k, md in plane.event_metadata.items():
        for st in md.stats:
            if stat.get(st.metadata_id) != OP_NAME_STAT:
                continue
            out[k] = (st.str_value if st.WhichOneof("value") == "str_value"
                      else stat.get(st.ref_value, ""))
    return out


def load(log_dir: str) -> dict:
    """Events of the newest ``.xplane.pb`` under ``log_dir``: device ops
    as ``trace.load`` reads them, their text followed by the op's
    ``op_name`` path; host spans named ``bench.*`` or ``repro.*``."""
    import jax
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    with open(files[-1], "rb") as f:
        space = _xplane_pb2().XSpace.FromString(f.read())
    protos = {p.name: p for p in space.planes}
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            proto = protos[plane.name]
            paths = _op_names(proto)
            texts = {}
            ops = []
            for line, pline in zip(plane.lines, proto.lines):
                if line.name != "XLA Ops":
                    continue
                for e, pe in zip(line.events, pline.events):
                    md = pe.metadata_id
                    text = texts.get(md)
                    if text is None:
                        if proto.event_metadata[md].name != e.name:
                            raise ValueError(
                                f"{files[-1]}: ProfileData and the XSpace "
                                f"proto disagree on {plane.name} events")
                        strs = [str(v) for _, v in e.stats
                                if isinstance(v, str)]
                        text = " ".join([e.name] + strs)
                        if paths.get(md):
                            text += OP_NAME + paths[md]
                        texts[md] = text
                    ops.append((trace.op_name(e.name), int(e.start_ns),
                                int(e.duration_ns), text))
            if ops:
                device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append((e.name, int(e.start_ns),
                                     int(e.duration_ns)))
    return {"device": device, "host": host}


def of(run) -> dict:
    """The events of ``run``'s traced window with the program's spans,
    read once per run from the directory ``bench/run.py`` traced into;
    ``run.events`` where that directory holds no trace."""
    ev = getattr(run, "program_events", None)
    if ev is None:
        d = harness.ROOT / ".bench_trace" / run.workload
        try:
            ev = load(str(d))
        except FileNotFoundError:
            ev = run.events
        run.program_events = ev
    return ev


def span_seconds(events: dict, w0: int, w1: int, names,
                 self_time: bool = True) -> float | None:
    """Seconds of the host spans named in ``names``, clipped to the
    window; with ``self_time``, less the spans nested directly in each
    (whatever their names). None when no such span lies in the window.
    Spans nest properly on one thread: a span that starts inside
    another and ends after it is not its child."""
    names = {names} if isinstance(names, str) else set(names)
    spans = sorted(((s, s + d, n) for n, s, d in events["host"]),
                   key=lambda x: (x[0], -x[1]))
    self_ns = [min(e, w1) - max(s, w0) for s, e, _ in spans]
    stack = []
    for i, (s, e, _) in enumerate(spans):
        while stack and spans[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= spans[stack[-1]][1] and self_time:
            self_ns[stack[-1]] -= max(0, min(e, w1) - max(s, w0))
        stack.append(i)
    hits = [ns for (s, e, n), ns in zip(spans, self_ns)
            if n in names and min(e, w1) > max(s, w0)]
    if not hits:
        return None
    return sum(hits) / 1e9


def scope_pattern(scope: str):
    """An ``op_name`` path component naming ``scope``, as such or
    wrapped by a transformation (``jvp(maml_step)``, ``vmap(...)``)."""
    return re.compile(rf"(^|[/(]){re.escape(scope)}([)/]|$)")


def _in_scope(ops, pat) -> list:
    """(start, end) of the ops in one scope: those whose ``op_name``
    path ``pat`` matches, and those without an ``op_name`` (control flow
    that the compiler rebuilt without metadata, as a TPU does with the
    while loop of a short scan) whose enclosed ops with an ``op_name``
    all match it. Ops on one device line nest properly."""
    named, memo = [], {}
    for _, a, d, text in ops:
        m = memo.get(text)
        if m is None:
            m = memo[text] = (
                None if OP_NAME not in text
                else pat.search(text.rsplit(OP_NAME, 1)[1]) is not None)
        named.append(m)
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    out, stack = [], []         # frames: [op, end, encloses in, out]

    def close(frame):
        i, end, f_in, f_out = frame
        own = named[i]
        inside = own if own is not None else (f_in and not f_out)
        if inside:
            out.append((ops[i][1], end))
        if stack:
            parent = stack[-1]
            parent[2] = parent[2] or f_in or own is True
            parent[3] = parent[3] or f_out or own is False

    for i in order:
        start = ops[i][1]
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        stack.append([i, start + ops[i][2], False, False])
    while stack:
        close(stack.pop())
    return out


def scope_seconds(events: dict, w0: int, w1: int,
                  scope: str) -> float | None:
    """Seconds of the union of the device ops in ``scope`` (an
    ``op_name`` path component; :func:`_in_scope`), clipped to the
    window and averaged over the device planes, like ``trace.busy_s``.
    None when no op in the window is in it."""
    planes = list(events["device"].values())
    pat = scope_pattern(scope)
    tot, found = 0, False
    for ops in planes:
        hit = [(max(a, w0), min(b, w1)) for a, b in _in_scope(ops, pat)
               if min(b, w1) > max(a, w0)]
        found = found or bool(hit)
        tot += sum(b - a for a, b in trace._union(hit))
    if not found:
        return None
    return tot / len(planes) / 1e9


def per_round_ms(run, seconds: float | None) -> float | None:
    """Milliseconds per traced round (the rounds ``round_ms`` counts in
    the traced window), or None."""
    if seconds is None or not run.traced_rounds:
        return None
    return seconds * 1e3 / run.traced_rounds


def span_ms(run, names) -> float | None:
    """Self time of the named spans per traced round, in ms."""
    return per_round_ms(run, span_seconds(of(run), *run.window_ns, names))


def scope_ms(run, scope: str) -> float | None:
    """Device time of one scope per traced round, in ms."""
    return per_round_ms(run, scope_seconds(of(run), *run.window_ns, scope))
