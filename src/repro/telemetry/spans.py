"""Names of the program's host spans and device scopes.

Host spans mark what the Python drivers and the Eq.-(11) ledger do
between compiled chunks. :func:`span` writes one into the profiler's
trace as ``repro.<name>`` (``jax.profiler.TraceAnnotation``), on the
clock of the device planes, so that a trace names every gap in which
the device waits for the host. With no profiler recording, a span costs
one check.

Device scopes are ``jax.named_scope`` names that the round programs put
on their operations' ``op_name`` metadata. Each marks one phase of a
round and they never nest, so every device op carries at most one:

    episodes        ε-greedy rollouts resampled into minibatches
                    (``casestudy.sample_episode_batches``)
    maml_step       one MAML meta step (``maml.maml_meta_step``)
    local_sgd       clipped local SGD (``casestudy._clipped_sgd_steps``)
    eq6_mix         the Eq.-(6) consensus round (``ConsensusEngine.step``)
    greedy_eval     the greedy running reward (``rl.dqn.evaluate``)
    telemetry_row   the per-round metrics row (``RoundRecorder.row``)
"""
from __future__ import annotations

import jax

PREFIX = "repro."

#: every host span, as the trace names it
SPANS = tuple(PREFIX + n for n in (
    "driver.process",       # CaseStudy.run: one whole MTL process
    "driver.meta_train",    # stage 1
    "driver.adapt_task",    # one task's FL stage (arg task_id)
    "driver.setup",         # adapt_task's state before its chunk loop
    "driver.dispatch",      # round indices + the compiled chunk call
    "driver.sync",          # waiting for the chunk outputs the driver reads
    "driver.bill",          # adapt_task's post-hoc Eq.-(11) bill
    "telemetry.fetch",      # a chunk's telemetry rows copied to the host
                            # (arg copies: device→host copies it made,
                            # 1 for a chunk's packed ledger rows)
    "telemetry.price",      # float64 pricing, buffer and sinks
))

#: every device scope (``jax.named_scope`` name)
SCOPES = ("episodes", "maml_step", "local_sgd", "eq6_mix", "greedy_eval",
          "telemetry_row")


def span(name: str, **args):
    """Host span ``repro.<name>``; ``args`` become the event's stats."""
    full = PREFIX + name
    if full not in SPANS:
        raise ValueError(f"unknown span {full!r}; spans.SPANS has {SPANS}")
    return jax.profiler.TraceAnnotation(full, **args)
