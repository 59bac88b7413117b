"""Whole runs of ``bench/run.py`` on the CPU at a reduced size, past the
harness's look for a chip, with the timed path broken underneath: each
fault the cell can have must turn ``correct`` false, and the unbroken
run must stay true. The limits are the cell's own (bench/limits)."""
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from bench import harness

FIX = pathlib.Path(__file__).parent / "fixtures"


def run_cell(monkeypatch, capsys, workload, config, **traffic):
    from bench import run
    monkeypatch.setattr(harness, "require_chips",
                        lambda n: jax.devices()[:n])
    orig = harness.cell_entries

    def entries(bench, w):
        cell, cfg, tr = orig(bench, w)
        tr.update(traffic)
        return cell, dict(cfg, file=str(FIX / config)), tr

    monkeypatch.setattr(harness, "cell_entries", entries)
    assert run.main(["--workload", workload, "--seed", "2147483901",
                     "--seconds", "0.5", "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def mtl(monkeypatch, capsys):
    return run_cell(monkeypatch, capsys, "dqn-mtl-paper", "dqn-tiny.json",
                    t0=10, max_rounds=16)


def _identity_step(self, stacked, codec_state=None, key=None, **_):
    return stacked, codec_state


# -- dqn-mtl-paper ---------------------------------------------------------

def test_mtl_sound_run_is_correct(monkeypatch, capsys):
    assert mtl(monkeypatch, capsys)["correct"] is True


def test_mtl_state_left_unchanged(monkeypatch, capsys):
    from repro.core import maml
    orig = maml.maml_meta_step

    def frozen(loss_fn, meta_params, *a, **kw):
        return meta_params, orig(loss_fn, meta_params, *a, **kw)[1]

    monkeypatch.setattr(maml, "maml_meta_step", frozen)
    out = mtl(monkeypatch, capsys)
    assert out["correct"] is False
    assert out["checks"]["meta_change"]["value"] == pytest.approx(1.0)


def test_mtl_half_of_the_batch_left_out(monkeypatch, capsys):
    from repro.rl import dqn
    orig = dqn.td_loss

    def half(params, cfg, batch, target_params=None):
        b = {k: (v[: v.shape[0] // 2] if k != "target_params" else v)
             for k, v in batch.items()}
        return orig(params, cfg, b, target_params=target_params)

    monkeypatch.setattr(dqn, "td_loss", half)
    assert mtl(monkeypatch, capsys)["correct"] is False


def test_mtl_exchange_left_out(monkeypatch, capsys):
    from repro.core.engine import ConsensusEngine
    monkeypatch.setattr(ConsensusEngine, "step", _identity_step)
    assert mtl(monkeypatch, capsys)["correct"] is False


def test_mtl_answer_altered_where_produced(monkeypatch, capsys):
    from repro.rl import gridworld
    orig = gridworld.step

    def step(pos, action, task_id):
        new, r = orig(pos, action, task_id)
        hit = (new[..., 0] == 1) & (new[..., 1] == 2)
        return new, jnp.where(hit, r + 1.0, r)

    monkeypatch.setattr(gridworld, "step", step)
    assert mtl(monkeypatch, capsys)["correct"] is False


def test_mtl_fl_learning_rate_scaled(monkeypatch, capsys):
    from repro.rl import casestudy
    orig = casestudy._clipped_sgd_steps

    def half_lr(loss_fn, params, batches, lr, clip=5.0):
        return orig(loss_fn, params, batches, lr / 2, clip)

    monkeypatch.setattr(casestudy, "_clipped_sgd_steps", half_lr)
    out = mtl(monkeypatch, capsys)
    assert out["correct"] is False
    assert out["checks"]["fl_change"]["value"] > \
        out["checks"]["fl_change"]["limit"]


def test_mtl_fl_local_steps_cut(monkeypatch, capsys):
    from repro.rl import casestudy
    orig = casestudy._clipped_sgd_steps

    def one_step(loss_fn, params, batches, lr, clip=5.0):
        return orig(loss_fn, params, jax.tree.map(lambda x: x[:1], batches),
                    lr, clip)

    monkeypatch.setattr(casestudy, "_clipped_sgd_steps", one_step)
    out = mtl(monkeypatch, capsys)
    assert out["correct"] is False
    assert out["checks"]["fl_change"]["value"] > \
        out["checks"]["fl_change"]["limit"]


@pytest.mark.parametrize("fault", ["t_i_late", "hit_unseen"])
def test_mtl_reached_flag_misread(monkeypatch, capsys, fault):
    from repro.core import scanloop
    drv = harness.load_module(harness.BENCH / "drivers" / "casestudy.py")
    monkeypatch.setattr(scanloop, "first_hit",
                        drv.PROCESS_FAULTS[fault](scanloop.first_hit))
    out = mtl(monkeypatch, capsys)
    assert out["correct"] is False
    assert out["checks"]["t_i_errors"]["value"] > 0
