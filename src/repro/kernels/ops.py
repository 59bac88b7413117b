"""Jit'd public wrappers around the Pallas kernels with shape/dtype guards
and an ``impl`` switch:

    impl="pallas"     — TPU kernel (compile target)
    impl="interpret"  — kernel body executed in Python on CPU (validation)
    impl="xla"        — the pure-jnp oracle (CPU/dry-run production path)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import rglru_scan as _rg
from repro.kernels import consensus_update as _cu
from repro.kernels import quant_consensus as _qc
from repro.kernels import ref as _ref

_ALLOWED_DTYPES = (jnp.float32, jnp.bfloat16)


def _check_dtype(*arrays):
    for a in arrays:
        if a.dtype not in [jnp.dtype(d) for d in _ALLOWED_DTYPES]:
            raise TypeError(f"unsupported dtype {a.dtype}; use f32/bf16")


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "block_q", "block_k", "impl"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 128,
                    block_k: int = 128, impl: str = "xla"):
    """Batched GQA attention. q (B,S,H,hd); k,v (B,T,K,hd); H % K == 0."""
    _check_dtype(q, k, v)
    if q.ndim != 4 or k.shape != v.shape or q.shape[3] != k.shape[3]:
        raise ValueError(f"bad shapes {q.shape} {k.shape} {v.shape}")
    if q.shape[2] % k.shape[2]:
        raise ValueError(f"H={q.shape[2]} not a multiple of K={k.shape[2]}")
    if impl == "xla":
        return _ref.mha_reference(q, k, v, causal=causal, window=window,
                                  softcap=softcap)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, block_q=block_q,
                               block_k=block_k,
                               interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("block_w", "block_t", "impl"))
def rglru_scan(log_a, b, h0=None, *, block_w: int = 512, block_t: int = 256,
               impl: str = "xla"):
    """Linear recurrence h_t = exp(log_a_t)·h_{t-1} + b_t over (B, T, W)."""
    _check_dtype(log_a, b)
    if log_a.shape != b.shape or log_a.ndim != 3:
        raise ValueError(f"bad shapes {log_a.shape} {b.shape}")
    if impl == "xla":
        return _ref.rglru_scan_reference(log_a, b, h0)
    return _rg.rglru_scan(log_a, b, h0, block_w=block_w, block_t=block_t,
                          interpret=(impl == "interpret"))


def _fold_agents(kernel, n_own: int, n_src: int):
    """Make a population kernel map over an outer ``vmap`` by folding the
    mapped axis into its agent axis (agents are independent rows), so a
    vmapped call still lowers to ONE ``pallas_call`` with the same block
    shapes — the sharded plan's single-program emulation vmaps over
    agent blocks. ``kernel(*own, *src, idx, sigmas)`` takes ``n_own``
    per-agent arrays and ``n_src`` source arrays, all row-major on their
    leading axis; the folded ``idx`` is offset into the folded source."""
    f = jax.custom_batching.custom_vmap(kernel)

    @f.def_vmap
    def rule(axis_size, in_batched, *args):
        own, src = args[:n_own], args[n_own:n_own + n_src]
        idx, sig = args[n_own + n_src:]
        b_own = in_batched[:n_own]
        b_src, b_idx, b_sig = (in_batched[n_own:n_own + n_src],
                               *in_batched[n_own + n_src:])
        spread = lambda a, b: (a if b else jnp.broadcast_to(
            a[None], (axis_size,) + a.shape))
        merge = lambda a: a.reshape((-1,) + a.shape[2:])
        own = [merge(spread(a, b)) for a, b in zip(own, b_own)]
        idx = spread(idx, b_idx)
        if any(b_src):
            src = [spread(a, b) for a, b in zip(src, b_src)]
            rows = src[0].shape[1]
            idx = idx + rows * jnp.arange(axis_size,
                                          dtype=idx.dtype)[:, None, None]
            src = [merge(a) for a in src]
        out = f(*own, *src, merge(idx), merge(spread(sig, b_sig)))
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    return f


@functools.partial(jax.jit, static_argnames=("block_n", "impl"))
def consensus_update(x, src, idx, sigmas, *, block_n: int = 64 * 1024,
                     impl: str = "xla"):
    """Fused Eq.-(6) update of K agents over flat params:
    x_k + Σ_h σ_kh (src[idx_kh] − x_k). x (K, N); src (M, N) the models
    neighbours are read from; idx (K, H) int rows of src; sigmas
    (K, H)."""
    _check_dtype(x, src)
    K = x.shape[0]
    if (x.ndim != 2 or src.ndim != 2 or src.shape[1] != x.shape[1]
            or idx.ndim != 2 or idx.shape[0] != K
            or sigmas.shape != idx.shape):
        raise ValueError(f"bad shapes {x.shape} {src.shape} {idx.shape} "
                         f"{sigmas.shape}")
    if impl == "xla":
        return jax.vmap(_ref.consensus_update_reference)(x, src[idx],
                                                         sigmas)
    kernel = functools.partial(_cu.consensus_update, block_n=block_n,
                               interpret=(impl == "interpret"))
    return _fold_agents(kernel, 1, 1)(x, src, idx, sigmas)


@functools.partial(jax.jit, static_argnames=("block_n", "impl", "qblock"))
def quant_consensus_update(x, q_self, s_self, q_src, s_src, idx, sigmas, *,
                           block_n: int = 64 * 1024, impl: str = "xla",
                           qblock=None):
    """Fused int-dequant + Eq.-(6) update of K agents around each agent's
    own decoded model: x_k + Σ_h σ_kh (s_j·q_j − s_k·q_k), j = idx_kh.
    Wire models ride int8 lanes: q_self (K, N), q_src (M, N) the wires
    neighbours are read from, idx (K, H) int rows of q_src, sigmas
    (K, H). ``qblock=None``: one scale per model (s_self (K,), s_src
    (M,)); ``qblock=B``: per-channel block-wise scales (``"int8:b64"``
    wires) — s_self (K, ⌈N/B⌉), s_src (M, ⌈N/B⌉)."""
    _check_dtype(x)
    if q_self.dtype != jnp.int8 or q_src.dtype != jnp.int8:
        raise TypeError(
            f"wire models must be int8, got {q_self.dtype} {q_src.dtype}")
    K, M = x.shape[0], q_src.shape[0]
    nb = () if qblock is None else (-(-x.shape[-1] // int(qblock)),)
    if (x.ndim != 2 or q_self.shape != x.shape
            or q_src.shape[1:] != x.shape[1:]
            or idx.ndim != 2 or idx.shape[0] != K
            or sigmas.shape != idx.shape
            or s_self.shape != (K,) + nb or s_src.shape != (M,) + nb):
        raise ValueError(
            f"bad shapes {x.shape} {q_self.shape} {q_src.shape} "
            f"{idx.shape} {sigmas.shape}; scales {s_self.shape} "
            f"{s_src.shape} (qblock={qblock} wants {nb or 'one'} per "
            f"model)")
    if impl == "xla":
        return jax.vmap(functools.partial(
            _ref.quant_consensus_update_reference, qblock=qblock))(
            x, q_self, s_self, q_src[idx], s_src[idx], sigmas)
    kernel = functools.partial(
        _qc.quant_consensus_update, block_n=block_n,
        interpret=(impl == "interpret"), qblock=qblock)
    return _fold_agents(kernel, 3, 2)(
        x, q_self, s_self, q_src, s_src, idx, sigmas)
