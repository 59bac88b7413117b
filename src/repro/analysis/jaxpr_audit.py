"""Layer 1 — jaxpr audits of the engine's compiled round programs.

Four rules, each checked against the ARTIFACT the drivers dispatch
(the registered program's own jaxpr / compiled executable, re-derived
from :func:`repro.core.scanloop.registered_programs`), never against a
reimplementation:

JX1  no data callbacks inside a CACHED program: ``pure_callback`` /
     ``io_callback`` primitives in a program admitted to
     ``scanloop.cached_program`` replay one host state against many
     cache hits (the impure-sampler fallback is exactly the case the
     drivers must never cache — see ``_scan_round_program``).
JX2  no decode-then-combine on the sparse/sharded paths: the Eq.-(6)
     combine must gather WIRE lanes (int8/int4 stay integer through the
     gather; dequant fuses inside the combine). A ``gather`` whose
     operand derives from an int-wire→float ``convert_element_type`` —
     or from a ``scatter`` densification of the wire (the top-k
     reconstruction) — mixes a dense f32 tensor the wire never shipped.
JX3  donation honored: for every program built with ``donate_argnums``,
     the compiled executable's ``input_output_alias`` directive must
     cover every donated leaf — XLA drops donation SILENTLY (no Python
     warning) when shapes fail to pair up, doubling peak memory.
JX4  no streaming telemetry inside a CACHED program: a
     ``debug_callback`` (the ``repro.telemetry`` streaming emitter)
     closes over host sink state, so the drivers must build streaming
     programs per call and never admit them to the cache — a cached
     one would replay a dead run's sinks against every later hit.
     (Buffered telemetry rows are pure scan outputs and cache fine.)
JX5  async carry donated: any registered program whose recorded
     abstract args hold an ``AsyncState`` (the per-agent clocks and
     per-lane wire ages the async protocol threads chunk to chunk)
     must list that argument in ``donate_argnums`` — it is a carry
     exactly like the params, and a dropped alias keeps two
     generations of the availability bookkeeping alive through every
     dispatch.

``run_jaxpr_audit()`` drives tiny FL/MAML configurations through the
real chunked drivers — telemetry off, buffered, and streaming — to
populate the program registry, audits every registered record, then
traces ``engine.scan_rounds`` for all four plans (× int8 / top-k wires
on the sparse/sharded paths) for JX1/JX2.
"""
from __future__ import annotations

from typing import List, Optional

from repro.analysis.findings import Finding

#: JX4 domain: the streaming-telemetry emitter primitive.
_STREAMING_PRIMS = {"debug_callback"}
_CALLBACK_PRIMS = {"pure_callback", "debug_callback", "io_callback"}
_INT_WIRE_DTYPES = {"int4", "uint4", "int8", "uint8"}
_PASSTHROUGH = {"reshape", "transpose", "broadcast_in_dim", "squeeze",
                "slice", "rev", "copy", "expand_dims",
                # elementwise: a decoded wire scaled/shifted is STILL the
                # decoded wire — density and derivation are preserved
                "mul", "add", "sub", "div", "neg", "max", "min",
                "select_n"}


# ---------------------------------------------------------------------------
# jaxpr plumbing
# ---------------------------------------------------------------------------

def _source_of(eqn):
    """(file, line) of the user frame that emitted ``eqn``, or
    ``("<jaxpr>", 0)`` when the equation carries no traceback. Paths are
    cut down to repo-relative (``src/...``) so findings — and the
    committed baseline keyed on them — match across checkouts."""
    from jax._src import source_info_util
    fr = source_info_util.user_frame(eqn.source_info.traceback)
    if fr is None:
        return "<jaxpr>", 0
    f = fr.file_name.replace("\\", "/")
    if "/src/repro/" in f:
        f = "src/repro/" + f.rsplit("/src/repro/", 1)[1]
    return f, int(fr.start_line)


def _sub_closed_jaxprs(eqn):
    """Every (Closed)Jaxpr in ``eqn.params`` (incl. inside lists/tuples),
    duck-typed: a raw Jaxpr has ``eqns``, a ClosedJaxpr wraps one."""
    subs = []
    for v in eqn.params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for x in vals:
            if hasattr(x, "eqns") or (hasattr(x, "jaxpr")
                                      and hasattr(x.jaxpr, "eqns")):
                subs.append(x)
    return subs


def _closed(x):
    return x.jaxpr if hasattr(x, "jaxpr") else x


def iter_eqns(closed_jaxpr):
    """Depth-first over every equation, through all nested jaxprs."""
    stack = [_closed(closed_jaxpr)]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            yield eqn
            for sub in _sub_closed_jaxprs(eqn):
                stack.append(_closed(sub))


def find_callbacks(closed_jaxpr):
    """[(primitive name, file, line)] of every host-callback equation."""
    out = []
    for eqn in iter_eqns(closed_jaxpr):
        name = eqn.primitive.name
        if name in _CALLBACK_PRIMS or any(p in name
                                          for p in _CALLBACK_PRIMS):
            f, ln = _source_of(eqn)
            out.append((name, f, ln))
    return out


def _dtype_name(v) -> Optional[str]:
    aval = getattr(v, "aval", None)
    dt = getattr(aval, "dtype", None)
    return None if dt is None else getattr(dt, "name", str(dt))


def _is_float(v) -> bool:
    name = _dtype_name(v) or ""
    return name.startswith(("float", "bfloat"))


def find_decode_then_combine(closed_jaxpr):
    """[(kind, file, line)] where a ``gather`` consumes a tensor derived
    from an int-wire upcast or a scatter densification — the
    decode-then-combine regression class (rule JX2).

    Taint sources: ``convert_element_type`` int8/int4 → float, and
    ``scatter*``. Taint propagates through shape/layout ops and into
    sub-jaxprs whose invars map positionally onto the call's operands
    (pjit / scan / cond branches / custom-derivative calls).
    """
    found = []

    def hit(v, tainted):
        # Literals (inline constants) are unhashable and never tainted
        return not hasattr(v, "val") and v in tainted

    def walk(jaxpr, tainted):
        j = _closed(jaxpr)
        tainted = set(tainted)
        for eqn in j.eqns:
            name = eqn.primitive.name
            ins = eqn.invars
            if name == "convert_element_type":
                src = _dtype_name(ins[0]) or ""
                if src in _INT_WIRE_DTYPES and _is_float(eqn.outvars[0]):
                    tainted.update(eqn.outvars)
                    continue
                if hit(ins[0], tainted) and _is_float(eqn.outvars[0]):
                    tainted.update(eqn.outvars)
                continue
            if name.startswith("scatter"):
                tainted.update(eqn.outvars)
                continue
            if name == "gather" and ins and hit(ins[0], tainted):
                f, ln = _source_of(eqn)
                found.append(("gather-of-decoded-wire", f, ln))
                continue
            if name in _PASSTHROUGH:
                if any(hit(v, tainted) for v in ins):
                    tainted.update(eqn.outvars)
                continue
            subs = _sub_closed_jaxprs(eqn)
            if subs:
                operands = ins[1:] if name == "cond" else ins
                for sub in subs:
                    inner = _closed(sub)
                    seed = set()
                    if len(inner.invars) == len(operands):
                        seed = {iv for iv, ov in zip(inner.invars,
                                                     operands)
                                if hit(ov, tainted)}
                    out_taint = walk(sub, seed)
                    if len(inner.outvars) == len(eqn.outvars):
                        tainted.update(
                            ov for iv, ov in zip(inner.outvars,
                                                 eqn.outvars)
                            if iv in out_taint)
        return tainted

    walk(closed_jaxpr, set())
    return found


def has_int_lane_gather(closed_jaxpr) -> bool:
    """True when some combine gathers integer WIRE lanes directly."""
    for eqn in iter_eqns(closed_jaxpr):
        if eqn.primitive.name == "gather" and eqn.invars:
            if (_dtype_name(eqn.invars[0]) or "") in _INT_WIRE_DTYPES:
                return True
    return False


# ---------------------------------------------------------------------------
# compiled-executable donation check
# ---------------------------------------------------------------------------

def alias_param_indices(hlo_text: str):
    """Parameter indices covered by the module's ``input_output_alias``
    directive (balanced-brace segment; a non-greedy regex truncates at
    the first inner ``}``). Empty set when the directive is absent —
    which is exactly how XLA reports a silently dropped donation."""
    import re
    i = hlo_text.find("input_output_alias=")
    if i < 0:
        return set()
    j = hlo_text.index("{", i)
    depth, k = 0, j
    for k in range(j, len(hlo_text)):
        if hlo_text[k] == "{":
            depth += 1
        elif hlo_text[k] == "}":
            depth -= 1
            if depth == 0:
                break
    seg = hlo_text[j:k + 1]
    return {int(m.group(1)) for m in re.finditer(r"\((\d+)\s*,", seg)}


def check_donation(fn, donate_argnums, abstract_args, *,
                   jit_kwargs=None, label="program") -> List[Finding]:
    """JX3 for one program: compile ``fn`` WITH donation requested and
    verify the executable aliases every donated leaf."""
    import jax
    findings: List[Finding] = []
    jitted = jax.jit(fn, donate_argnums=tuple(donate_argnums),
                     **(jit_kwargs or {}))
    try:
        txt = jitted.lower(*abstract_args).compile().as_text()
    except Exception as e:                  # pragma: no cover - diagnostics
        return [Finding("JX3", label, 0,
                        f"could not compile for donation check: {e}")]
    aliased = alias_param_indices(txt)
    starts, n = [], 0
    for a in abstract_args:
        starts.append(n)
        n += len(jax.tree.leaves(a))
    for argnum in donate_argnums:
        leaves = len(jax.tree.leaves(abstract_args[argnum]))
        missing = [i for i in range(starts[argnum], starts[argnum] + leaves)
                   if i not in aliased]
        if missing:
            findings.append(Finding(
                "JX3", label, 0,
                f"donation dropped: arg {argnum} of {label} donates "
                f"{leaves} leaves but {len(missing)} have no "
                "input_output_alias in the compiled executable"))
    return findings


def _holds_async_state(tree) -> bool:
    """True iff ``tree`` contains an :class:`AsyncState` anywhere —
    ``scanloop._abstractify`` maps leaves but PRESERVES container
    structure (NamedTuples included), so the recorded abstract args
    still carry the carry's type."""
    from repro.core.engine import AsyncState
    if isinstance(tree, AsyncState):
        return True
    if isinstance(tree, dict):
        return any(_holds_async_state(v) for v in tree.values())
    if isinstance(tree, (list, tuple)):
        return any(_holds_async_state(v) for v in tree)
    return False


def check_async_state_donated(rec) -> List[Finding]:
    """JX5 for one program record: every argument that carries an
    ``AsyncState`` (the async protocol's per-agent clocks + per-lane
    wire ages) must be in ``donate_argnums``. The state is a carry
    exactly like the params — threaded chunk to chunk — so a dropped
    donation keeps BOTH generations of (clock, age) buffers alive
    through every dispatch, silently doubling the async bookkeeping's
    footprint at fleet scale."""
    if rec.abstract_args is None:
        return []
    donated = set(rec.donate_argnums or ())
    findings: List[Finding] = []
    for i, arg in enumerate(rec.abstract_args):
        if _holds_async_state(arg) and i not in donated:
            findings.append(Finding(
                "JX5", rec.name, 0,
                f"arg {i} of {rec.name!r} carries the AsyncState "
                f"(clock, age) but donate_argnums={tuple(sorted(donated))} "
                "leaves it undonated — the async carry must alias "
                "through the chunk like the params (add the arg to "
                "donate_argnums in the driver's donating_jit)"))
    return findings


# ---------------------------------------------------------------------------
# registry + engine sweeps
# ---------------------------------------------------------------------------

def audit_registered_programs(records=None) -> List[Finding]:
    """JX1 + JX3 + JX4 + JX5 over the scanloop program registry."""
    import jax
    from repro.core import scanloop
    findings: List[Finding] = []
    records = (scanloop.registered_programs()
               if records is None else list(records))
    for rec in records:
        if rec.abstract_args is None:
            continue                       # never dispatched: nothing baked
        try:
            closed = jax.make_jaxpr(rec.fn)(*rec.abstract_args)
        except Exception as e:             # pragma: no cover - diagnostics
            findings.append(Finding(
                "JX1", rec.name, 0, f"could not re-trace for audit: {e}"))
            continue
        if rec.cache_key is not None:
            for prim, f, ln in find_callbacks(closed):
                if any(s in prim for s in _STREAMING_PRIMS):
                    findings.append(Finding(
                        "JX4", f, ln,
                        f"{prim} inside CACHED program {rec.name!r} "
                        f"(cache key {rec.cache_key[0]!r}) — streaming "
                        "telemetry callbacks close over host sinks, so "
                        "the drivers must build streaming programs per "
                        "call and never admit them to "
                        "scanloop.cached_program"))
                else:
                    findings.append(Finding(
                        "JX1", f, ln,
                        f"{prim} inside CACHED program {rec.name!r} "
                        f"(cache key {rec.cache_key[0]!r}) — impure "
                        "programs must never be admitted to "
                        "scanloop.cached_program"))
        if rec.donate_argnums:
            findings.extend(check_donation(
                rec.fn, rec.donate_argnums, rec.abstract_args,
                jit_kwargs=rec.jit_kwargs, label=rec.name))
        findings.extend(check_async_state_donated(rec))
    return findings


def _tiny_drivers():
    """Drive minimal FL + MAML configurations through the REAL chunked
    drivers so the registry holds the programs tier-1 actually runs —
    telemetry off, buffered (cached, must audit clean), and streaming
    (never cached, so JX4 stays silent on the live tree)."""
    import jax
    import jax.numpy as jnp
    from repro import telemetry as telemetry_lib
    from repro.core import federated, maml, topology as topo_lib
    from repro.core.engine import ConsensusEngine

    K, D = 4, 8

    def loss_fn(p, batch):
        pred = batch["x"] @ p["w"] + p["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    def sample_batches(key, t):
        ks = jax.random.split(key, K)

        def one(k):
            x = jax.random.normal(k, (4, D))
            return {"x": x, "y": jnp.sum(x, -1, keepdims=True)}

        return jax.vmap(one)(ks)

    def target_fn(stacked):
        # input-DEPENDENT on purpose: a constant target would trip the
        # traceable() impurity fallback, the driver would build the
        # program per call instead of admitting it to the cache, and
        # the registry would hold NOTHING for this audit to check —
        # the async chunk (JX5's whole surface) included
        d = jnp.mean(jnp.asarray(jax.tree.leaves(stacked)[0],
                                 jnp.float32))
        return d < jnp.float32(-1e9), d

    params = {"w": jnp.zeros((D, 1)), "b": jnp.zeros((1,))}
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (K,) + x.shape), params)
    engine = ConsensusEngine(topo_lib.ring(K), codec="int8")
    federated.run_fl_until_scan(
        loss_fn, stacked, sample_batches, engine, 0.1,
        target_fn=target_fn, max_rounds=2, key=jax.random.PRNGKey(0),
        chunk=2)
    # async FL: churn + dropout + staleness bound through the REAL
    # chunked driver — the availability draws, staleness weights, and
    # per-agent freezes run in-scan and must audit callback-free like
    # every other cached program
    async_engine = ConsensusEngine(
        topo_lib.ring(K), codec="int8",
        graph=topo_lib.GraphProcess.dropout(0.3, seed=0),
        agents=topo_lib.AgentProcess.bernoulli(0.6, seed=0), tau=2)
    federated.run_fl_until_scan(
        loss_fn, stacked, sample_batches, async_engine, 0.1,
        target_fn=target_fn, max_rounds=2, key=jax.random.PRNGKey(0),
        chunk=2, telemetry=telemetry_lib.Telemetry())
    # buffered telemetry: rows ride the ys, program is cached under the
    # telemetry-extended key and must re-audit callback-free (JX1/JX4)
    federated.run_fl_until_scan(
        loss_fn, stacked, sample_batches, engine, 0.1,
        target_fn=target_fn, max_rounds=2, key=jax.random.PRNGKey(0),
        chunk=2, telemetry=telemetry_lib.Telemetry())
    # streaming telemetry: the debug_callback program is built per call
    # and never admitted to the cache — nothing for JX4 to flag
    federated.run_fl_until_scan(
        loss_fn, stacked, sample_batches, engine, 0.1,
        target_fn=target_fn, max_rounds=2, key=jax.random.PRNGKey(0),
        chunk=2, telemetry=telemetry_lib.Telemetry(mode="streaming"))

    def sample_tasks(key, t):
        ks = jax.random.split(key, 2)

        def one(k):
            x = jax.random.normal(k, (3, 4, D))
            return {"x": x, "y": jnp.sum(x, -1, keepdims=True)}

        sup = jax.vmap(one)(jax.random.split(ks[0], 2))
        qry = jax.vmap(one)(jax.random.split(ks[1], 2))
        return sup, qry

    maml.maml_train_scan(loss_fn, params, sample_tasks, rounds=2,
                         inner_lr=0.1, outer_lr=0.1, chunk=2,
                         key=jax.random.PRNGKey(1))


def audit_engine_plans(k: int = 8) -> List[Finding]:
    """JX1 + JX2 over ``engine.scan_rounds`` jaxprs for all four plans
    (int8 and top-k wires on the sparse/sharded paths), each audited
    both static and MASKED (a ``GraphProcess.dropout`` engine — the
    in-scan per-lane survival draws and σ renormalization must stay
    callback-free and keep the integer wire integer through the
    combine), plus one ASYNC configuration per plan (``AgentProcess``
    churn + staleness bound τ — availability draws, staleness weights,
    and the per-agent freeze are in-scan too)."""
    import jax
    import jax.numpy as jnp
    from repro.core import topology as topo_lib
    from repro.core.engine import ConsensusEngine, PLAN_KINDS

    findings: List[Finding] = []
    topo = topo_lib.ring(k)
    params = {"w": jnp.zeros((k, 16), jnp.float32)}

    for plan in PLAN_KINDS:
        codecs = ("int8", "topk:0.25") if plan in ("sparse-pallas",
                                                   "sharded") else (None,)
        configs = [(c, p, False) for c in codecs for p in (0.0, 0.3)]
        # one async config per plan: churn + dropout + τ, the maximal
        # in-scan branch (staleness weights, renormalized float σ, age
        # clocks, per-agent freeze)
        configs.append((codecs[0], 0.3, True))
        for codec, dropout, asynchronous in configs:
            kw = {"num_blocks": 2} if plan == "sharded" else {}
            graph = (topo_lib.GraphProcess.dropout(dropout, seed=0)
                     if dropout else None)
            agents = (topo_lib.AgentProcess.bernoulli(0.6, seed=0)
                      if asynchronous else None)
            eng = ConsensusEngine(topo, codec=codec, plan=plan,
                                  graph=graph, agents=agents,
                                  tau=2 if asynchronous else None, **kw)
            meta = eng.audit_meta()
            label = (f"scan_rounds[{plan}/{codec}"
                     + (f"/p={dropout}" if dropout else "")
                     + ("/async]" if asynchronous else "]"))
            closed = jax.make_jaxpr(
                lambda p: eng.scan_rounds(p, rounds=2))(params)
            for prim, f, ln in find_callbacks(closed):
                rule = ("JX4" if any(s in prim for s in _STREAMING_PRIMS)
                        else "JX1")
                findings.append(Finding(
                    rule, f, ln,
                    f"{prim} inside {label} — scan_rounds programs are "
                    "cached by the chunked drivers and must stay pure"))
            if not meta["int_lane_gather"]:
                continue
            for kind, f, ln in find_decode_then_combine(closed):
                findings.append(Finding(
                    "JX2", f, ln,
                    f"decode-then-combine ({kind}) in {label}: the "
                    "Eq.-(6) combine consumes a densified f32 tensor "
                    "the wire never shipped"))
            if meta["qbits"] is not None and not has_int_lane_gather(closed):
                findings.append(Finding(
                    "JX2", f"engine:{plan}", 0,
                    f"no integer-lane gather in {label}: the int wire "
                    "was decoded before the combine"))
    return findings


def run_jaxpr_audit() -> List[Finding]:
    """The full Layer-1 pass (drives tiny drivers first)."""
    _tiny_drivers()
    return audit_registered_programs() + audit_engine_plans()
