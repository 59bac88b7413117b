"""The plain references against the system at reduced sizes on the CPU,
where both compute in float32: what the benchmark's check compares on
the chip must agree here to float32 rounding."""
import json
import pathlib
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness

FIX = pathlib.Path(__file__).parent / "fixtures"
REF = harness.BENCH / "reference"


def _ctx(config_file, config_name, traffic, **over):
    cfg = json.loads((FIX / config_file).read_text())
    cfg.update(over)
    tr = json.loads((harness.BENCH / "traffic" / traffic).read_text())
    return types.SimpleNamespace(
        workload="test", config_name=config_name, config=cfg, traffic=tr,
        seed=2 ** 31 + 7, seconds=0.0, devs=jax.devices(),
        clock=harness.Clock(time.perf_counter()),
        compiles=harness.CompileCounter(), trace_dir=None)


def test_dqn_forward_matches_the_model():
    from repro.models import dqn
    cs = harness.load_module(harness.BENCH / "drivers" / "casestudy.py")
    ref = harness.load_module(REF / "paper-dqn.py")
    c = json.loads((harness.BENCH / "configs" / "paper-dqn.json")
                   .read_text())
    cfg = cs.arch_config(c)
    key = jax.random.PRNGKey(5)
    p = dqn.init(key, cfg)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(ref.init(key, c))):
        np.testing.assert_array_equal(a, b)
    s = jax.nn.one_hot(jnp.arange(7), 40)
    np.testing.assert_allclose(dqn.forward(p, cfg, s)[0],
                               ref.forward(p, c, s), rtol=1e-6, atol=1e-6)


def test_casestudy_first_steps_match():
    drv = harness.load_module(harness.BENCH / "drivers" / "casestudy.py")
    ctx = _ctx("dqn-tiny.json", "paper-dqn", "mtl-paper.json")
    got = drv.compare_of(ctx, drv.first_steps_of(ctx),
                         drv.reference_of(ctx))
    assert max(got.values()) < 1e-4, got


def test_reference_takes_the_other_side_of_a_tie_where_asked():
    from bench.reference import casestudy as ref_cs
    ctx = _ctx("dqn-tiny.json", "paper-dqn", "mtl-paper.json")
    model = harness.load_module(REF / "paper-dqn.py")
    proto = ref_cs.Protocol(model, ctx.config, ctx.traffic)
    w = model.init(jax.random.PRNGKey(1), ctx.config)
    key = jax.random.PRNGKey(2)
    base, gap = proto.rollout(key, w, 0, 0.0, 1)
    flip, _ = proto.rollout(key, w, 0, 0.0, 1, flip_at=0)
    q = np.asarray(proto.q(w, base["state"][0, :1]))[0]
    order = np.argsort(-q)
    assert int(base["action"][0, 0]) == order[0]
    assert int(flip["action"][0, 0]) == order[1]
    assert float(gap[0, 0]) == pytest.approx(
        (q[order[0]] - q[order[1]]) / np.abs(q).max(), rel=1e-5)
    # explored steps carry no margin
    _, gap_all = proto.rollout(key, w, 0, 1.0, 1)
    assert np.isinf(np.asarray(gap_all)).all()
