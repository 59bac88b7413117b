"""Model-Agnostic Meta-Learning — paper Eqs. (2)–(5).

One MAML round (Sect. II-A):

  task-specific training (Eq. 3):
      φ_{t,τ_i} = W_t − μ Σ_k ∇_W L_k(W_t | E^(a)_{i,k})
  meta-model update (Eq. 4):
      W_{t+1} = W_t − η Σ_i Σ_k ∇_W L_k[φ_{t,τ_i} | E^(b)_{i,k}]
  where (Eq. 5) ∇_W L = J_W[φ] · ∇_φ L — the gradient-through-gradient.

``first_order=True`` applies the paper's J ≈ I approximation (β = 1 in the
energy model); ``False`` differentiates through the inner SGD exactly
(β > 1 — the Jacobian-vector products cost extra backward passes).

Everything is model-agnostic: ``loss_fn(params, batch) -> scalar`` and
params is any pytree. Tasks are vmapped, so the Q tasks of a MAML round
lower to one batched XLA program (shardable over the mesh's data axis).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scanloop


def inner_adapt(loss_fn: Callable, params, batch, lr: float,
                steps: int = 1):
    """Eq. (3): ``steps`` SGD steps on one task's support data.

    ``batch`` may have a leading steps axis (one mini-batch per step) or be
    a single batch reused every step. Differentiable (used by 2nd-order).
    """

    def one_step(p, b):
        g = jax.grad(loss_fn)(p, b)
        return jax.tree.map(
            lambda w, gw: w - lr * gw.astype(w.dtype), p, g), None

    if steps == 1:
        p, _ = one_step(params, batch)
        return p

    leaves = jax.tree.leaves(batch)
    has_step_axis = leaves and all(
        hasattr(x, "shape") and x.shape[:1] == (steps,) for x in leaves)
    if has_step_axis:
        p, _ = jax.lax.scan(one_step, params, batch)
        return p
    for _ in range(steps):
        p, _ = one_step(params, batch)
        params = p
    return params


@jax.named_scope("maml_step")
def maml_meta_step(loss_fn: Callable, meta_params, support, query, *,
                   inner_lr: float, outer_lr: float,
                   inner_steps: int = 1, first_order: bool = True,
                   grad_reduce: Optional[Callable] = None):
    """One MAML round over Q tasks (support/query have leading task axis Q).

    Returns (new_meta_params, metrics dict).
    ``grad_reduce``: optional tree-map'd reduction applied to the meta
    gradient before the update (e.g. a psum for multi-host sharding).
    """

    def task_meta_loss(p, sup, qry):
        phi = inner_adapt(loss_fn, p, sup, inner_lr, inner_steps)
        if first_order:
            # J ≈ I: grads flow to φ only, not through the inner gradient
            phi = jax.tree.map(
                lambda w, pw: jax.lax.stop_gradient(w - pw) + pw, phi, p)
        return loss_fn(phi, qry)

    def mean_meta_loss(p):
        losses = jax.vmap(lambda s, q: task_meta_loss(p, s, q))(
            support, query)
        return jnp.mean(losses), losses

    (mloss, task_losses), g = jax.value_and_grad(
        mean_meta_loss, has_aux=True)(meta_params)
    if grad_reduce is not None:
        g = grad_reduce(g)
    new_params = jax.tree.map(
        lambda w, gw: (w.astype(jnp.float32)
                       - outer_lr * gw.astype(jnp.float32)).astype(w.dtype),
        meta_params, g)
    metrics = {"meta_loss": mloss, "task_losses": task_losses,
               "meta_grad_norm": jnp.sqrt(sum(
                   jnp.sum(jnp.square(x.astype(jnp.float32)))
                   for x in jax.tree.leaves(g)))}
    return new_params, metrics


def _scan_round_program(loss_fn: Callable, sample_tasks: Callable, key, *,
                        inner_lr: float, outer_lr: float, inner_steps: int,
                        first_order: bool, telemetry=None):
    """The ONE compiled MAML round-loop program both drivers share.

    Data is sampled INSIDE the scan from per-round derived keys (the
    carried key is split per round exactly like the legacy host loop,
    so the PRNG stream — and therefore every batch — is unchanged), and
    the per-round metrics accumulate as stacked device arrays, synced
    only when the caller pulls them. Samplers that satisfy the
    ``sample_tasks_traced`` contract (pure traced jax function of
    ``(key, int32 round)``; vmapped task samplers qualify) run
    on-device; anything else is transparently routed through
    ``jax.pure_callback`` by :func:`repro.core.scanloop.traceable`.

    ``jax.lax.scan`` compiles the SAME loop-body HLO for every chunk
    length, so driving this program with length-1 ``ts`` (the host
    loop) or length-``chunk`` ``ts`` produces bit-identical params and
    losses — which is the whole parity contract between
    :func:`maml_train` and :func:`maml_train_scan`. The params buffer
    is donated on backends with donation support (scanloop's donation
    invariant: don't reuse a pytree after passing it in).

    Programs are memoized through
    :func:`repro.core.scanloop.cached_program` on (loss_fn,
    sample_tasks — by identity — and the baked hyper-parameters), so
    Monte-Carlo sweeps re-entering the drivers with one configuration
    re-trace only when the meta-params' shapes change (jit's own
    per-shape cache); ``scanloop.TRACE_COUNTS["maml_chunk"]`` observes
    the retraces. Samplers that failed the traced contract (the
    ``pure_callback`` fallback) are never cached — the probe consumes
    elements from stateful host samplers, and skipping it on a cache
    hit would shift their stream between invocations.

    Telemetry: the per-round metrics (``meta_loss`` etc.) ALREADY ride
    the scan outputs, so BUFFERED telemetry needs no program change at
    all — the drivers ingest the same stacked metrics host-side and the
    cache key is untouched (buffered runs share the telemetry-off
    program). STREAMING telemetry plants a ``jax.debug.callback`` in
    the body that emits each round's meta-loss live; that callback
    closes over host state, so streaming programs are built per call
    and never cached (rule JX4).
    """
    streaming = telemetry is not None and telemetry.streaming
    cache_key = ("maml_chunk", loss_fn, sample_tasks, float(inner_lr),
                 float(outer_lr), int(inner_steps), bool(first_order))
    if not streaming:
        cached = scanloop.get_cached_program(cache_key)
        if cached is not None:
            return cached              # hit: skip the probe entirely
    sampler, sampler_traced = scanloop.traceable(
        sample_tasks, key, jnp.int32(0), name="sample_tasks")
    stream_cb = telemetry.maml_stream_cb() if streaming else None

    def build():
        step = functools.partial(
            maml_meta_step, loss_fn, inner_lr=inner_lr, outer_lr=outer_lr,
            inner_steps=inner_steps, first_order=first_order)

        def body(carry, t):
            p, k = carry
            k, sk = jax.random.split(k)
            support, query = sampler(sk, t)
            p, m = step(p, support, query)
            if stream_cb is not None:
                jax.debug.callback(stream_cb, t, m["meta_loss"],
                                   m["meta_grad_norm"], ordered=True)
            return (p, k), m

        def run_chunk(p, k, ts):
            scanloop.TRACE_COUNTS["maml_chunk"] += 1   # trace-time only
            return jax.lax.scan(body, (p, k), ts)

        return scanloop.donating_jit(run_chunk, donate_argnums=(0,))

    if streaming or not sampler_traced:
        # streaming telemetry / impure sampler: never cached
        return build()
    return scanloop.cached_program(cache_key, build)


def maml_train(loss_fn: Callable, meta_params, sample_tasks: Callable,
               *, rounds: int, inner_lr: float, outer_lr: float,
               inner_steps: int = 1, first_order: bool = True,
               key=None, callback: Optional[Callable] = None):
    """Run ``rounds`` MAML rounds. ``sample_tasks(key, round) -> (support,
    query)`` with leading task axis. Host-loop driver: one dispatch and
    one blocking ``float(meta_loss)`` sync per round — the
    ``chunk=1``-equivalent fallback of :func:`maml_train_scan` (both
    drive the same compiled round program, so their params and history
    agree bit for bit), and the only driver with a per-round host
    ``callback(t, params, metrics)``."""
    key = key if key is not None else jax.random.PRNGKey(0)
    meta_params = scanloop.own(meta_params)    # donation never touches
    run_round = _scan_round_program(           # the caller's pytree
        loss_fn, sample_tasks, key, inner_lr=inner_lr, outer_lr=outer_lr,
        inner_steps=inner_steps, first_order=first_order)
    history = []
    for t in range(rounds):
        (meta_params, key), ms = run_round(
            meta_params, key, jnp.arange(t, t + 1, dtype=jnp.int32))
        history.append(float(ms["meta_loss"][0]))
        if callback is not None:
            # own(): the carry is donated to the NEXT round's dispatch on
            # donating backends — a callback that retains the params
            # (snapshots, checkpoints) must not see buffers that round
            # t+1 will invalidate
            callback(t, scanloop.own(meta_params),
                     jax.tree.map(lambda x: x[0], ms))
    return meta_params, history


def maml_train_scan(loss_fn: Callable, meta_params, sample_tasks: Callable,
                    *, rounds: int, inner_lr: float, outer_lr: float,
                    inner_steps: int = 1, first_order: bool = True,
                    key=None, chunk: int = 32, telemetry=None):
    """Device-resident MAML driver: ``chunk`` rounds per compiled program.

    Bit-identical to :func:`maml_train` — same PRNG stream (the key is
    carried through the scan and split per round in the same order),
    same round body, same compiled scan program — but the host loop
    drops from O(rounds) jit dispatches + blocking ``float(meta_loss)``
    syncs to O(rounds/chunk): the meta-loss history accumulates as a
    device array and is synced once per chunk. See
    :func:`_scan_round_program` for the traced-sampler contract and the
    buffer-donation invariant. ``rounds`` need not be a multiple of
    ``chunk`` (the remainder runs as one shorter scan — at most two
    compiled programs in total).

    ``telemetry`` records one meta-round event per round from the
    chunk's stacked metrics (buffered mode reuses the telemetry-off
    program — metrics already ride the scan outputs; streaming mode
    emits each round live via ``jax.debug.callback`` from an uncached
    program). Params and history stay bit-identical in every mode."""
    key = key if key is not None else jax.random.PRNGKey(0)
    if rounds <= 0:
        return meta_params, []
    chunk = max(1, min(int(chunk), rounds))
    meta_params = scanloop.own(meta_params)    # donation never touches
    run_chunk = _scan_round_program(           # the caller's pytree
        loss_fn, sample_tasks, key, inner_lr=inner_lr, outer_lr=outer_lr,
        inner_steps=inner_steps, first_order=first_order,
        telemetry=telemetry)
    history = []
    for start in range(0, rounds, chunk):
        ts = jnp.arange(start, min(start + chunk, rounds), dtype=jnp.int32)
        (meta_params, key), ms = run_chunk(meta_params, key, ts)
        if telemetry is not None:
            telemetry.record_maml_rounds(
                {"meta_loss": ms["meta_loss"],
                 "meta_grad_norm": ms["meta_grad_norm"]}, start)
        history.extend(float(x) for x in np.asarray(ms["meta_loss"]))
    return meta_params, history
