"""The Pallas consensus kernels compile for a TPU v5e, called the way the
engine calls them: batched over agents, at the paper-dqn leaf widths.

Nothing runs — the programs are compiled for a described ``v5e:2x2``
chip, which needs only the TPU compiler, so these tests guard every
later change to the kernels at no chip time. The topology is described
inside a fixture, never at import: only one process at a time may load
the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core import topology as topo_lib
from repro.core.engine import ConsensusEngine
from repro.kernels import ops
from repro.models import dqn as qmodel

K = 12                  # the case study's 12 robots


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip cannot be read back from the
    # persistent cache, so keep them out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("H", [1, 3])
@pytest.mark.parametrize("N", [262144, 512])
@pytest.mark.parametrize("wire", ["f32", "int8", "int8:b64"])
def test_consensus_kernel_compiles_for_v5e(wire, N, H, one_chip):
    f32 = lambda *s: _sds(s, jnp.float32, one_chip)
    i8 = lambda *s: _sds(s, jnp.int8, one_chip)
    idx = _sds((K, H), jnp.int32, one_chip)
    if wire == "f32":
        text = _compiled_text(
            lambda x, sg, ix: ops.consensus_update(x, x, ix, sg,
                                                   impl="pallas"),
            f32(K, N), f32(K, H), idx)
    else:
        qblock = 64 if wire == "int8:b64" else None
        per = () if qblock is None else (-(-N // qblock),)
        text = _compiled_text(
            lambda x, q, s, ix, sg: ops.quant_consensus_update(
                x, q, s, q, s, ix, sg, impl="pallas", qblock=qblock),
            f32(K, N), i8(K, N), f32(K, *per), idx, f32(K, H))
    assert "tpu_custom_call" in text


def test_consensus_kernel_compiles_under_vmap(one_chip):
    """The sharded plan's one-device emulation vmaps over agent blocks
    that read one gathered population; the mapped axis folds into the
    kernel's agent axis."""
    B, N, H = 4, 512, 2
    f32 = lambda *s: _sds(s, jnp.float32, one_chip)
    i8 = lambda *s: _sds(s, jnp.int8, one_chip)
    nb = N // 64
    text = _compiled_text(
        jax.vmap(lambda *a: ops.quant_consensus_update(
            *a, impl="pallas", qblock=64)),
        f32(B, K, N), i8(B, K, N), f32(B, K, nb), i8(B, B * K, N),
        f32(B, B * K, nb), _sds((B, K, H), jnp.int32, one_chip),
        f32(B, K, H))
    assert "tpu_custom_call" in text


def test_sparse_pallas_int8_round_compiles_for_v5e(one_chip, monkeypatch):
    """One engine round at K=12 over the int8:b64 wire, paper-dqn
    payload: the plan takes its TPU branch and the round program holds
    the fused dequant-consensus kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_arch("paper-dqn")
    shapes = jax.eval_shape(jax.vmap(lambda k: qmodel.init(k, cfg)),
                            jax.random.split(jax.random.PRNGKey(0), K))
    stacked = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                           shapes)
    eng = ConsensusEngine(topo_lib.ring(K), codec="int8:b64",
                          plan="sparse-pallas")
    text = _compiled_text(lambda p: eng.step(p)[0], stacked)
    assert "tpu_custom_call" in text
