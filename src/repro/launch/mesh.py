"""Production mesh construction (functions only — importing this module
never touches jax device state; see the dry-run's XLA_FLAGS contract)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever local devices exist (tests/examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(n // data, 1))
    return jax.make_mesh((data, model), ("data", "model"))


def make_agent_mesh(positions: int = 0, axis_name: str = "agents"):
    """1-D mesh whose axis carries consensus AGENTS (one agent per
    position for the engine's ``distributed`` plan; a block of agents
    per position for ``sharded``). ``positions`` 0 ⇒ all local devices;
    asking for more positions than there are devices is an error."""
    n = len(jax.devices())
    if positions > n:
        raise ValueError(
            f"make_agent_mesh(positions={positions}) needs {positions} "
            f"devices but {n} {jax.default_backend()} device(s) are "
            f"visible; use positions<={n} (0 takes all of them)")
    return jax.make_mesh((positions if positions > 0 else n,),
                         (axis_name,))
