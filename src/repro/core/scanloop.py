"""Shared machinery for the device-resident (chunked ``lax.scan``) round
loops of :mod:`repro.core.maml` and :mod:`repro.core.federated`.

The paper's energy balance is measured in ROUNDS (t0 meta rounds, t_i
adaptation rounds per task), so Monte-Carlo sweeps execute tens of
thousands of them — and a host loop pays a Python-level jit dispatch
plus a blocking device→host sync per round. The scanned drivers compile
``chunk`` rounds into ONE XLA program and sync once per chunk, which
drops the host overhead from O(rounds) to O(rounds/chunk). Three pieces
are shared:

* :func:`donating_jit` — ``jax.jit`` with ``donate_argnums`` on backends
  that implement buffer donation, so the K-stacked population params and
  error-feedback residuals are updated IN PLACE chunk over chunk instead
  of doubling peak memory. CPU does not support donation (XLA would warn
  and copy anyway), so the gate keeps the test path quiet. The DONATION
  INVARIANT: arrays passed as donated arguments are dead after the
  call. The public drivers keep this INTERNAL — they :func:`own` (copy
  once, on donating backends only) any caller-provided pytree before
  the first chunk, so callers may freely reuse their own params across
  driver calls; only the driver-owned carries are donated.
* :func:`traceable` — the ``sample_tasks_traced`` contract probe: a
  sampler that traces under abstract (key, round) arguments — AND whose
  output actually depends on them — runs INSIDE the scan; anything
  else (host RNG, ``int(t)`` round logic, stateful iterators whose
  trace would bake one batch in as a constant) is wrapped in
  ``jax.pure_callback`` — with a one-time warning naming it — so the
  scanned drivers accept every sampler the host-loop drivers did, at
  the cost of one host round-trip per round for that sampler only.
* :func:`first_hit` — recover the EXACT first round that hit the target
  from a per-round reached mask (the scanned FL driver freezes state
  with ``lax.cond`` once the target is reached, so t_i is bit-identical
  to the host loop's early ``break``, not approximated by the chunk
  grid).
* :func:`cached_program` — the compiled-chunk-program cache: the scanned
  drivers used to REBUILD their ``donating_jit`` wrapper per call, so
  every Monte-Carlo repetition re-traced (and re-compiled) the whole
  chunk program. Drivers now memoize the wrapper on a key of everything
  baked into the trace — the round functions (loss / sampler / target,
  by identity), the engine (whose identity covers plan kind, codec,
  graph process, and the concrete mix), the baked scalars (lr,
  max_rounds, eval_every), and the carry's :func:`tree_signature` (leaf
  shapes/dtypes + treedef) — so repeated invocations with identical
  configuration dispatch the SAME jit object and XLA's executable cache
  does the rest (one compile per distinct ``ts`` length).
  :data:`TRACE_COUNTS` counts actual retraces per driver (a counter
  bumped inside the traced Python body, i.e. only on jit cache misses)
  — the tier-1 trace-count guard asserts it stays flat across
  repetitions.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import logging
import weakref
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

log = logging.getLogger(__name__)


@dataclasses.dataclass
class ProgramRecord:
    """Audit-facing record of one :func:`donating_jit` program.

    ``repro.analysis`` walks :func:`registered_programs` to re-derive
    each program's jaxpr (``jax.make_jaxpr(fn)(*abstract_args)``) and
    compiled HLO, so the invariants — no callbacks inside cached
    programs, donation actually honored — are checked against the
    artifacts the drivers dispatch, not against reimplementations.
    """
    name: str
    fn: Callable                      # the raw traced round body
    jitted: Callable                  # the jit handle dispatch calls
    donate_argnums: tuple             # as REQUESTED by the driver
    donation_gated: bool              # True: the CPU gate dropped them
    jit_kwargs: dict
    abstract_args: Optional[tuple] = None   # SDS tree of the first call
    cache_key: Optional[tuple] = None       # set on cached_program admit


#: weakrefs to live dispatch wrappers — entries vanish with their
#: program (LRU eviction + driver GC), so the registry never extends a
#: compiled executable's lifetime.
_PROGRAM_REFS: list = []


def registered_programs():
    """Live :class:`ProgramRecord`\\ s of every :func:`donating_jit`
    program still referenced somewhere (program cache, driver closures).
    Dead weakrefs are pruned in passing."""
    out, alive = [], []
    for ref in _PROGRAM_REFS:
        w = ref()
        if w is not None:
            alive.append(ref)
            out.append(w._program_record)
    _PROGRAM_REFS[:] = alive
    return out


def clear_program_registry():
    """Forget every registered program (tests)."""
    _PROGRAM_REFS.clear()


#: strong references held by an active :func:`retained_programs` block
_RETAINED: Optional[list] = None


@contextlib.contextmanager
def retained_programs():
    """Keep every :func:`donating_jit` program built inside the block
    alive, and yield the list their :class:`ProgramRecord`\\ s land in —
    for auditing drivers whose programs are local to one call (the
    weakref'd registry drops those as soon as the call returns)."""
    global _RETAINED
    outer, kept = _RETAINED, []
    _RETAINED = kept
    records = []
    try:
        yield records
    finally:
        _RETAINED = outer
        records.extend(d._program_record for d in kept)


def _abstractify(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        tree)


def donating_jit(fn: Callable, donate_argnums=(), **jit_kwargs):
    """``jax.jit`` that donates ``donate_argnums`` where the backend
    supports it (TPU/GPU). On CPU donation is unimplemented — XLA logs a
    "donated buffers were not usable" warning and copies — so the gate
    compiles without donation there. See the module docstring for the
    donation invariant callers must respect.

    Every program is registered for ``repro.analysis`` (see
    :class:`ProgramRecord`); the returned callable dispatches straight
    to the jit handle after recording the first call's abstract args.
    """
    gated = jax.default_backend() == "cpu"
    if gated:
        jitted = jax.jit(fn, **jit_kwargs)
    else:
        jitted = jax.jit(fn, donate_argnums=donate_argnums, **jit_kwargs)
    rec = ProgramRecord(
        name=getattr(fn, "__name__", repr(fn)), fn=fn, jitted=jitted,
        donate_argnums=tuple(donate_argnums), donation_gated=gated,
        jit_kwargs=dict(jit_kwargs))

    @functools.wraps(fn)
    def dispatch(*args, **kwargs):
        if rec.abstract_args is None:
            rec.abstract_args = _abstractify(args)
        return jitted(*args, **kwargs)

    dispatch._program_record = rec
    _PROGRAM_REFS.append(weakref.ref(dispatch))
    if _RETAINED is not None:
        _RETAINED.append(dispatch)
    return dispatch


def own(tree):
    """Driver-owned copy of a CALLER-provided pytree on donating
    backends (no-op on CPU, where :func:`donating_jit` never donates).
    The chunked drivers copy incoming params/state once before the
    first chunk so donation consumes only driver-owned buffers — the
    caller's pytree stays valid across repeated driver calls (e.g.
    Monte-Carlo sweeps from one meta-init)."""
    if jax.default_backend() == "cpu":
        return tree
    return jax.tree.map(jnp.copy, tree)


def _outputs_all_constant(closed_jaxpr) -> bool:
    """True when no output of a traced function (transitively) depends
    on any input — i.e. everything it returns is a baked-in constant.
    That is the signature of an IMPURE sampler (``next(iterator)``,
    cached host arrays): it traces fine, but inside a scan its single
    traced value would replay every round. Dependence is propagated
    conservatively through equations, so mixed const/input ops count as
    input-dependent (classified traced, never falsely demoted)."""
    j = closed_jaxpr.jaxpr
    dependent = set(j.invars)
    for eqn in j.eqns:
        if any(not hasattr(v, "val") and v in dependent
               for v in eqn.invars):
            dependent.update(eqn.outvars)
    return all(hasattr(v, "val") or v not in dependent
               for v in j.outvars)


#: names whose host-callback fallback has been logged (once each)
_FELL_BACK: set = set()


def traceable(fn: Callable, *probe_args, name: str = "sampler"):
    """Return a scan-safe version of ``fn`` plus whether it traced.

    ``fn(*probe_args)`` is probed with ``jax.make_jaxpr`` (abstract
    values, nothing executes): success — with outputs that actually
    DEPEND on the inputs — means ``fn`` satisfies the traced contract
    (pure jax ops, no host concretization of the round index or key)
    and it is returned as-is to run on-device inside the scan.

    Two cases fall back: functions that fail to trace because they
    concretize a tracer (host RNG, ``int(t)`` round logic — JAX's
    tracer type and index errors), and traceable-but-impure ones whose
    outputs are input-independent constants (a stateful
    ``next(batch_iter)`` sampler would otherwise silently bake ONE batch
    into the compiled loop). Any other exception is a bug in ``fn`` and
    propagates. The fallback is logged once per ``name``, then calls
    ``fn`` once CONCRETELY to learn the output structure and wraps it
    in ``jax.pure_callback``: the scanned loop stays one compiled
    program, and this one function round-trips to the host each round
    with concrete (numpy) arguments — exactly the values the host-loop
    driver would have passed, so results are unchanged, only slower.
    Samplers should migrate to the traced contract to drop the round
    trip.
    """
    try:
        if not _outputs_all_constant(jax.make_jaxpr(fn)(*probe_args)):
            return fn, True
        why = "its outputs do not depend on its inputs"
    except (jax.errors.JAXTypeError, jax.errors.JAXIndexError) as e:
        why = f"it does not trace ({type(e).__name__})"
    if name not in _FELL_BACK:
        _FELL_BACK.add(name)
        log.warning("%s %r runs as a host callback every round: %s",
                    name, getattr(fn, "__name__", fn), why)
    out = fn(*probe_args)
    sds = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        out)

    def host_fn(*args):
        np_args = jax.tree.map(np.asarray, args)
        return jax.tree.map(np.asarray, fn(*np_args))

    def wrapped(*args):
        return jax.pure_callback(host_fn, sds, *args)

    wrapped.__name__ = f"host_callback_{name}"
    return wrapped, False


#: retrace counters per driver family ("fl_chunk", "maml_chunk"):
#: incremented inside the traced Python chunk body, so they only move
#: when jax actually re-traces — the observable the trace-count guard
#: in tier-1 asserts on (compile once across >= 3 repetitions).
TRACE_COUNTS: collections.Counter = collections.Counter()

#: compiled-program LRU capacity. Keys hold strong references to the
#: functions/engines they were built from, which both bounds memory and
#: prevents id()-reuse collisions while an entry is alive.
PROGRAM_CACHE_SIZE = 32
_program_cache: "collections.OrderedDict" = collections.OrderedDict()

#: program-cache counters ("hits", "misses", "inserts", "evictions") —
#: the runtime-inspectable complement to :data:`TRACE_COUNTS`. Bumped by
#: :func:`get_cached_program` / :func:`cached_program`; read them
#: through :func:`cache_stats`, not directly.
CACHE_STATS: collections.Counter = collections.Counter()


def cache_stats() -> dict:
    """Snapshot of the compiled-program cache counters plus registry
    size — the harness half of ``telemetry.report()``.

    Returns a plain dict: ``hits`` / ``misses`` (from the drivers'
    :func:`get_cached_program` probes), ``inserts`` / ``evictions``
    (from :func:`cached_program`), ``size`` / ``capacity`` (current LRU
    occupancy), ``registered_programs`` (live :class:`ProgramRecord`
    count), and ``trace_counts`` (a dict copy of
    :data:`TRACE_COUNTS`)."""
    return {
        "hits": CACHE_STATS["hits"],
        "misses": CACHE_STATS["misses"],
        "inserts": CACHE_STATS["inserts"],
        "evictions": CACHE_STATS["evictions"],
        "size": len(_program_cache),
        "capacity": PROGRAM_CACHE_SIZE,
        "registered_programs": len(registered_programs()),
        "trace_counts": dict(TRACE_COUNTS),
    }


def reset_cache_stats():
    """Zero the hit/miss/eviction counters AND :data:`TRACE_COUNTS`
    (tests, benchmark sections). Does NOT drop cached programs — use
    :func:`clear_program_cache` for that."""
    CACHE_STATS.clear()
    TRACE_COUNTS.clear()


def tree_signature(tree):
    """Hashable (treedef, ((shape, dtype), …)) signature of a pytree —
    the shapes/dtypes part of a program-cache key."""
    leaves, treedef = jax.tree.flatten(tree)
    return (treedef, tuple((tuple(jnp.shape(x)), str(jnp.result_type(x)))
                           for x in leaves))


def _cache_lookup(key):
    """LRU-bumping lookup WITHOUT touching :data:`CACHE_STATS` — the
    shared primitive under :func:`get_cached_program` (which counts) and
    :func:`cached_program` (whose driver already counted its probe, so
    re-counting here would double every miss)."""
    try:
        fn = _program_cache.pop(key)       # move-to-end on hit
    except KeyError:
        return None
    _program_cache[key] = fn
    return fn


def get_cached_program(key):
    """Cached program for ``key`` (LRU-bumped), or None. Drivers check
    this BEFORE probing their round functions, so cache hits skip the
    per-call ``traceable``/``eval_shape`` probes too — an entry only
    exists if the probe verdict was 'traced' when it was built. Each
    probe bumps ``hits`` or ``misses`` in :data:`CACHE_STATS`."""
    fn = _cache_lookup(key)
    CACHE_STATS["hits" if fn is not None else "misses"] += 1
    return fn


def cached_program(key, build: Callable):
    """Memoize a compiled chunk program (LRU, size
    :data:`PROGRAM_CACHE_SIZE`). ``key`` must be a hashable tuple
    covering EVERYTHING the trace bakes in (see the module docstring for
    the convention the drivers use); ``build()`` constructs the jitted
    program on a miss. Returns the cached callable.

    Admissions bump ``inserts`` and LRU drops bump ``evictions`` in
    :data:`CACHE_STATS` (the lookup itself is stats-silent — drivers
    count their entry probe via :func:`get_cached_program`)."""
    fn = _cache_lookup(key)
    if fn is None:
        fn = build()
        rec = getattr(fn, "_program_record", None)
        if rec is not None:
            rec.cache_key = key        # audit: this program was admitted
        CACHE_STATS["inserts"] += 1
    _program_cache[key] = fn
    while len(_program_cache) > PROGRAM_CACHE_SIZE:
        _program_cache.popitem(last=False)
        CACHE_STATS["evictions"] += 1
    return fn


def clear_program_cache():
    """Drop every cached chunk program (tests; frees engine refs)."""
    _program_cache.clear()


def first_hit(reached_mask) -> Optional[int]:
    """Index of the first True in a per-round reached mask (host-side,
    one chunk), or None if the chunk never hit the target."""
    mask = np.asarray(reached_mask)
    idx = np.flatnonzero(mask)
    return int(idx[0]) if idx.size else None
