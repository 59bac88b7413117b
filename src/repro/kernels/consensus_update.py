"""Pallas TPU kernel for the fused consensus update — paper Eq. (6):

    W_k  ←  W_k + Σ_h σ_{k,h} (W_h − W_k)

for a whole population of agents at once, over flat parameter tiles. The
XLA path materializes H neighbour deltas (H extra parameter-sized
temporaries); this kernel streams neighbour tiles through VMEM and
applies the weighted combine in one pass — HBM traffic is (H+2)·N per
agent instead of (3H+2)·N, which matters because the consensus round is
purely memory-bound (zero-FLOP roofline corner).

Layout: each agent's flat (N,) vector is viewed as (R, 128) lane rows
(N padded to a multiple of 128), so every VMEM block ends in (rows, 128)
and meets the TPU's (8, 128) tiling whatever the agent count. The agent
axis is explicit and leading: grid = (agent blocks, row blocks); the
σ weights ride in SMEM as one scalar per (agent, neighbour). The
neighbour tiles are gathered (in XLA) from the row view of the source
population, so they arrive in the kernel's layout without a relayout.
Oracle: ``ref.consensus_update_reference`` (one agent).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_N = 64 * 1024
LANES = 128
#: VMEM bytes the double-buffered blocks of one grid step may take
#: (below the v5e default scoped-VMEM limit of 16 MiB)
VMEM_BUDGET = 8 * 1024 * 1024


def plan_tiles(K: int, N: int, *, block_n: int, row_tile: int,
               bytes_per_elem: int):
    """Tiling of a (K, N) population into (agent, row) blocks.

    Returns ``(bk, Kp, rb, Rp)``: ``bk`` agents and ``rb`` 128-lane rows
    per block, with K padded to ``Kp`` and the row count to ``Rp``.
    ``rb`` is a multiple of ``row_tile`` (the dtype's sublane tile) or
    spans every row; a block holds about ``block_n`` elements, fewer
    when its double-buffered operands would outgrow :data:`VMEM_BUDGET`.
    """
    R = -(-N // LANES)
    elems = max(row_tile * LANES,
                min(block_n, VMEM_BUDGET // (2 * bytes_per_elem)))
    rows = elems // LANES
    if R <= rows:                       # whole rows: batch agents
        rb = Rp = R
        bk = min(K, max(1, rows // R))
    else:                               # long rows: one agent per block
        bk = 1
        rb = rows // row_tile * row_tile
        Rp = -(-R // rb) * rb
    Kp = -(-K // bk) * bk
    return bk, Kp, rb, Rp


def to_rows(a, Kp: int, Rp: int):
    """(K, N) → (Kp, Rp, 128), zero-padded."""
    K, N = a.shape
    a = jnp.pad(a, [(0, Kp - K), (0, Rp * LANES - N)])
    return a.reshape(Kp, Rp, LANES)


def pad_agents(a, Kp: int):
    """Zero-pad the leading (agent) axis to ``Kp``."""
    return jnp.pad(a, [(0, Kp - a.shape[0])] + [(0, 0)] * (a.ndim - 1))


def gather_rows(src_rows, idx, Kp: int):
    """Neighbour tiles (H, Kp, Rp, 128) of ``src_rows[idx[k, h]]``,
    gathered on the lane-row view (padded agents read row 0 at σ = 0).
    Gathering BEFORE the row view would leave an (H, K, N) tensor that
    XLA must relayout into rows, a copy the TPU compiler takes minutes
    over at published widths."""
    return src_rows[pad_agents(idx, Kp).T]


def _consensus_kernel(sig_ref, x_ref, nb_ref, o_ref, *, agents: int,
                      num_neighbors: int):
    base = pl.program_id(0) * agents

    def one(a, carry):
        x = x_ref[a].astype(jnp.float32)                   # (rb, 128)
        acc = jnp.zeros_like(x)
        for h in range(num_neighbors):
            sig = sig_ref[(base + a) * num_neighbors + h]
            acc = acc + sig * (nb_ref[h, a].astype(jnp.float32) - x)
        o_ref[a] = (x + acc).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, agents, one, 0)


def consensus_update(x, src, idx, sigmas, *,
                     block_n: int = DEFAULT_BLOCK_N,
                     interpret: bool = False):
    """x: (K, N) flat params of K agents; src: (M, N) the models
    neighbours are read from; idx: (K, H) int rows of ``src`` — agent
    k's h-th neighbour is ``src[idx[k, h]]``; sigmas: (K, H) weights.

    Returns the updated (K, N) params (Eq. 6, one round, every agent).
    """
    K, N = x.shape
    H = idx.shape[1]
    itemsize = jnp.dtype(x.dtype).itemsize
    bk, Kp, rb, Rp = plan_tiles(
        K, N, block_n=block_n, row_tile=8 * (4 // itemsize),
        bytes_per_elem=itemsize * (H + 2))
    sig = pad_agents(sigmas.astype(jnp.float32), Kp).reshape(Kp * H)
    nb = gather_rows(to_rows(src, src.shape[0], Rp), idx, Kp)

    out = pl.pallas_call(
        functools.partial(_consensus_kernel, agents=bk, num_neighbors=H),
        grid=(Kp // bk, Rp // rb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bk, rb, LANES), lambda i, j: (i, j, 0)),
            pl.BlockSpec((H, bk, rb, LANES), lambda i, j: (0, i, j, 0)),
        ],
        out_specs=pl.BlockSpec((bk, rb, LANES), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((Kp, Rp, LANES), x.dtype),
        interpret=interpret,
    )(sig, to_rows(x, Kp, Rp), nb)
    return out.reshape(Kp, Rp * LANES)[:K, :N]
