"""Plain reference of the paper's Q-network (DOI 10.1109/PIMRC54779.2022
.9977688, Sect. IV; the DeepMind DQN shape of Mnih et al. 2015): a
multilayer perceptron from the one-hot landmark state to one Q-value per
action, ReLU between layers. Its weights are drawn from a seed with the
configuration's program's key tree (truncated normal at 1/√fan-in, zero
biases), so that both start from the same weights. It imports nothing of
the program.

``rnd`` rounds each value where a narrower-precision control would hold
it (``precision.rounder``); the reference itself is float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def init(key, c: dict):
    n, d = c["num_layers"], c["d_model"]
    dims = [c["state_dim"]] + [d] * (n - 1) + [c["num_actions"]]
    ks = jax.random.split(key, n)
    return {f"fc{i}": {
        "w": jax.random.truncated_normal(
            ks[i], -3.0, 3.0, (dims[i], dims[i + 1]), jnp.float32)
        * (1.0 / math.sqrt(dims[i])),
        "b": jnp.zeros((dims[i + 1],), jnp.float32)} for i in range(n)}


def forward(params, c: dict, state, rnd=lambda x: x):
    """state (B, state_dim) -> Q-values (B, num_actions)."""
    x = rnd(state)
    n = c["num_layers"]
    for i in range(n):
        p = params[f"fc{i}"]
        x = rnd(x @ rnd(p["w"]) + rnd(p["b"]))
        if i < n - 1:
            x = jax.nn.relu(x)
    return x
