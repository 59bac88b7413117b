"""Fused Pallas TPU kernel: int8 dequantize + Eq.-(6) consensus update.

    W_k  ←  W_k + Σ_h σ_{k,h} (s_h·q_h − s_k·q_k)

where q are absmax-quantized int models in int8 lanes (the sidelink wire
format of :mod:`repro.comms.codecs`) and s their f32 scales — ONE scale
per tensor by default, or per-channel BLOCK-WISE scales (``qblock``:
each consecutive ``qblock``-long run of the flattened tensor carries its
own scale, the ``"int8:b64"`` wire). The unfused path materializes H
dequantized parameter-sized f32 temporaries before mixing; this kernel
streams int8 neighbour tiles through VMEM and dequantizes INSIDE the
combine, so HBM traffic for the neighbour models is H·N bytes (int8)
instead of 4·H·N (f32) plus the extra round trip — the consensus round
is purely memory-bound, so wire-dtype traffic is the whole game.

Layout (shared with :mod:`repro.kernels.consensus_update`): the agent
axis is explicit, every flat (N,) model is viewed as (R, 128) lane
rows, and the grid runs over (agent blocks, row blocks) with row blocks
a multiple of the int8 sublane tile (32). Neighbour wires are gathered
from the row view of the source population. Per-tensor scales and the
σ weights ride in SMEM, one scalar per agent or (agent, neighbour). Block
scales are laid out per lane row: a row of 128 lanes spans ``128 /
seg`` scale segments (``seg = gcd(qblock, 128)``), so each agent's
scales arrive as an (R, 128/seg) f32 tile and are broadcast across
their lanes inside the kernel — for ``int8:b64`` that is two scales
per row, read straight from the codec's scale vector without any
expansion in HBM.

Note the mixing recenters on the agent's OWN decoded model s_k·q_k (not
W_k): with a doubly-stochastic σ this keeps the population mean exact
under compression (the CHOCO-gossip trick), and it is what the
error-feedback wrapper assumes.

The σ weights are a RUNTIME operand, not trace-time structure — which is
what makes the fused gather time-varying-graph capable: the engine's
per-round survival masks (:class:`repro.core.topology.GraphProcess`)
feed a freshly renormalized σ each round with faded-neighbour lanes at
exactly 0.0, and a zero-σ lane contributes ``0 · (nb − xhat) = 0`` to
the combine — an exact no-op, same as the padding lanes — so one
compiled kernel serves every surviving subgraph without rebuilding the
neighbour indices.

Oracle: ``ref.quant_consensus_update_reference`` (one agent).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.consensus_update import (DEFAULT_BLOCK_N, LANES,
                                            gather_rows, pad_agents,
                                            plan_tiles, to_rows)

#: sublane tile of int8 blocks
INT8_ROWS = 32


def _lane_segment(qblock: int) -> int:
    """Lanes of a 128-lane row that share one block scale."""
    return math.gcd(int(qblock), LANES)


def _row_scales(s, qblock: int, Rp: int):
    """Block scales (M, ⌈N/qblock⌉) → per-row segment scales
    (M, Rp, 128/seg): segment g of row r covers lanes [g·seg, (g+1)·seg)
    of flat elements r·128 + lane."""
    seg = _lane_segment(qblock)
    per_row = LANES // seg
    rep = int(qblock) // seg
    e = jnp.repeat(s, rep, axis=-1) if rep > 1 else s
    n_seg = Rp * per_row
    if e.shape[-1] >= n_seg:
        e = e[:, :n_seg]
    else:                                  # padded q is 0: scale moot
        e = jnp.pad(e, [(0, 0), (0, n_seg - e.shape[-1])])
    return e.reshape(e.shape[0], Rp, per_row)


def _expand(s, seg: int):
    """(rb, 128/seg) segment scales → (rb, 128) lane scales."""
    per_row = s.shape[-1]
    if per_row == 1:
        return s
    lane = jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], LANES), 1)
    group = lane // seg
    out = jnp.broadcast_to(s[:, 0:1], (s.shape[0], LANES))
    for g in range(1, per_row):
        out = jnp.where(group == g, s[:, g:g + 1], out)
    return out


def _quant_consensus_kernel(sig_ref, ss_ref, sn_ref, x_ref, qs_ref, qn_ref,
                            o_ref, *, agents: int, num_neighbors: int):
    base = pl.program_id(0) * agents

    def one(a, carry):
        k = base + a                                       # global agent
        x = x_ref[a].astype(jnp.float32)                   # (rb, 128)
        xhat = qs_ref[a].astype(jnp.float32) * ss_ref[k]   # own decoded
        acc = jnp.zeros_like(x)
        for h in range(num_neighbors):
            i = k * num_neighbors + h
            nb = qn_ref[h, a].astype(jnp.float32) * sn_ref[i]   # dequant
            acc = acc + sig_ref[i] * (nb - xhat)
        o_ref[a] = (x + acc).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, agents, one, 0)


def _quant_consensus_kernel_blocked(sig_ref, x_ref, qs_ref, ss_ref, qn_ref,
                                    sn_ref, o_ref, *, agents: int,
                                    num_neighbors: int, seg: int):
    base = pl.program_id(0) * agents

    def one(a, carry):
        x = x_ref[a].astype(jnp.float32)                   # (rb, 128)
        xhat = qs_ref[a].astype(jnp.float32) * _expand(ss_ref[a], seg)
        acc = jnp.zeros_like(x)
        for h in range(num_neighbors):
            nb = (qn_ref[h, a].astype(jnp.float32)
                  * _expand(sn_ref[h, a], seg))            # fused dequant
            acc = acc + sig_ref[(base + a) * num_neighbors + h] * (
                nb - xhat)
        o_ref[a] = (x + acc).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, agents, one, 0)


def quant_consensus_update(x, q_self, s_self, q_src, s_src, idx, sigmas, *,
                           block_n: int = DEFAULT_BLOCK_N,
                           interpret: bool = False, qblock=None):
    """x: (K, N) own full-precision params of K agents; q_self: (K, N)
    own quantized models (int8 lanes); q_src: (M, N) the wire models
    neighbours are read from; idx: (K, H) int rows of the source — agent
    k's h-th neighbour is ``q_src[idx[k, h]]``; sigmas: (K, H) Eq.-(6)
    weights.

    Scale layout — ``qblock=None`` (per-tensor): s_self (K,), s_src
    (M,). ``qblock=B`` (block-wise, the ``"int8:b64"`` wire): s_self
    (K, ⌈N/B⌉), s_src (M, ⌈N/B⌉) — scale j dequantizes the flat run
    [j·B, (j+1)·B), exactly the codec's blocking, and the dequant stays
    fused inside the combine. Returns the updated (K, N) params, one
    round, every agent.
    """
    K, N = x.shape
    H = idx.shape[1]
    blocked = qblock is not None
    bk, Kp, rb, Rp = plan_tiles(
        K, N, block_n=block_n, row_tile=INT8_ROWS,
        bytes_per_elem=9 + H + (4 * (H + 1) if blocked else 0))
    grid = (Kp // bk, Rp // rb)
    sig = pad_agents(sigmas.astype(jnp.float32), Kp).reshape(Kp * H)
    rows = pl.BlockSpec((bk, rb, LANES), lambda i, j: (i, j, 0))
    nb_rows = pl.BlockSpec((H, bk, rb, LANES), lambda i, j: (0, i, j, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)     # whole array
    xr = to_rows(x, Kp, Rp)
    qsr = to_rows(q_self, Kp, Rp)
    qnr = gather_rows(to_rows(q_src, q_src.shape[0], Rp), idx, Kp)

    if not blocked:
        kernel = functools.partial(_quant_consensus_kernel, agents=bk,
                                   num_neighbors=H)
        in_specs = [smem, smem, smem, rows, rows, nb_rows]
        args = (sig,
                pad_agents(s_self.astype(jnp.float32), Kp),
                pad_agents(s_src.astype(jnp.float32)[idx],
                           Kp).reshape(Kp * H),
                xr, qsr, qnr)
    else:
        seg = _lane_segment(qblock)
        per_row = LANES // seg
        kernel = functools.partial(_quant_consensus_kernel_blocked,
                                   agents=bk, num_neighbors=H, seg=seg)
        in_specs = [
            smem, rows, rows,
            pl.BlockSpec((bk, rb, per_row), lambda i, j: (i, j, 0)),
            nb_rows,
            pl.BlockSpec((H, bk, rb, per_row), lambda i, j: (0, i, j, 0)),
        ]
        args = (sig, xr, qsr,
                pad_agents(_row_scales(s_self.astype(jnp.float32), qblock,
                                       Rp), Kp),
                qnr,
                gather_rows(_row_scales(s_src.astype(jnp.float32), qblock,
                                        Rp), idx, Kp))

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((Kp, Rp, LANES), x.dtype),
        interpret=interpret,
    )(*args)
    return out.reshape(Kp, Rp * LANES)[:K, :N]
