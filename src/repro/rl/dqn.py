"""Deep Q-Learning with double learning (paper Sect. II-C, Eq. 7):

    ℓ(x | W) = [ r + ν max_y q̃(x', y) − q(x, y | W) ]²

with ν = 0.99 and q̃ a target network (van Hasselt double-DQN: online net
picks the argmax action, target net evaluates it). The Q-network is the
DeepMind model shape (repro.models.dqn) on the gridworld one-hot state.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models import dqn as qmodel
from repro.rl import gridworld as gw

NU = 0.99
R_SCALE = 0.1     # TD-target reward scaling (argmax-invariant; keeps Q and
                  # the squared TD loss numerically tame under γ=0.99
                  # bootstrap — evaluation uses raw rewards)


class DQNState(NamedTuple):
    params: dict
    target_params: dict


def init(key, cfg) -> DQNState:
    p = qmodel.init(key, cfg)
    return DQNState(params=p, target_params=p)


def td_loss(params, cfg, batch, target_params=None):
    """Double-DQN TD loss on a batch of transitions.

    batch: {"state": (B, 40), "action": (B,), "reward": (B,),
            "next_state": (B, 40)}. If target_params is None it is taken
    from the batch dict (keyed 'target' as a pytree closed over by the
    caller) or falls back to params (plain DQN).
    """
    tp = target_params if target_params is not None else \
        batch.get("target_params", params)
    q, _, _ = qmodel.forward(params, cfg, batch["state"])
    q_sa = jnp.take_along_axis(q, batch["action"][:, None].astype(jnp.int32),
                               axis=1)[:, 0]
    q_next_online, _, _ = qmodel.forward(params, cfg, batch["next_state"])
    a_star = jnp.argmax(q_next_online, axis=-1)
    q_next_t, _, _ = qmodel.forward(tp, cfg, batch["next_state"])
    q_next = jnp.take_along_axis(q_next_t, a_star[:, None], axis=1)[:, 0]
    target = batch["reward"] * R_SCALE + NU * jax.lax.stop_gradient(q_next)
    return jnp.mean(jnp.square(target - q_sa))


def make_loss_fn(cfg):
    """loss_fn(params, batch) for the protocol/MAML machinery: the target
    network is frozen inside the batch (standard replay-style training)."""

    def loss_fn(params, batch):
        return td_loss(params, cfg, batch,
                       target_params=batch.get("target_params"))

    return loss_fn


def collect_experience(key, params, cfg, task_id: int, *, steps: int = 20,
                       epsilon: float = 0.1, batch: int = 2):
    """ε-greedy experience: the paper's E_ik (20 consecutive motions)."""
    qfn = lambda s: qmodel.forward(params, cfg, s)[0]
    data = gw.rollout(key, qfn, task_id, steps=steps, epsilon=epsilon,
                      batch=batch)
    flat = jax.tree.map(
        lambda x: x.reshape((-1,) + x.shape[2:]), data)
    return flat


def experience_batches(key, params, cfg, task_id: int, n_batches: int,
                       *, batch_size: int = 32, epsilon: float = 0.1,
                       target_params=None):
    """Sample ``n_batches`` TD mini-batches (leading batch axis stacked) —
    feeds inner_adapt / local_steps which scan over the leading axis."""
    k1, k2 = jax.random.split(key)
    episodes = max(batch_size * n_batches // 20, 2)
    data = collect_experience(k1, params, cfg, task_id, batch=episodes,
                              epsilon=epsilon)
    N = data["state"].shape[0]
    idx = jax.random.randint(k2, (n_batches, batch_size), 0, N)
    out = jax.tree.map(lambda x: x[idx], data)
    if target_params is not None:
        out["target_params"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_batches,) + x.shape),
            target_params)
    return out


@jax.named_scope("greedy_eval")
def evaluate(key, params, cfg, task_id: int, *, episodes: int = 4,
             steps: int = 20):
    """Mean greedy running reward R (paper's accuracy target R = 50)."""
    qfn = lambda s: qmodel.forward(params, cfg, s)[0]
    return gw.greedy_running_reward(key, qfn, task_id, steps=steps,
                                    episodes=episodes)
