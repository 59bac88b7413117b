"""Decentralized federated learning by average consensus — paper Eq. (6).

    W^{(k)}_{t+1} = W^{(k)}_t + Σ_{h∈N_k} σ_{k,h} (W^{(h)}_t − W^{(k)}_t),
    σ_{k,h} = |E_h| / Σ_{j∈N_k} |E_j|                       (paper / ref [5])

Execution primitives (pick via :class:`repro.core.engine.ConsensusEngine`
rather than calling these directly):

* ``consensus_step``           — dense: agent-stacked params (K on the
  leading axis) mixed by a (K, K) matrix. This is the reference semantics
  and the CPU path for the paper's 12-robot case study; ``impl`` selects
  the dense matmul or the batched sparse gather / fused Pallas kernel.
* ``sharded_consensus_step``   — the population split into per-mesh-
  position BLOCKS of agents under shard_map; each block all_gathers the
  codec WIRE along the agent axis and mixes its own rows (K ≫ cores).
* ``distributed_consensus_step`` — each mesh position holds ONE agent;
  neighbour exchange is ``jax.lax.ppermute`` rounds from
  :func:`permutation_schedule`, shipping the codec wire format (int8
  lanes + scales, bf16, …) — the paper's sidelink SL traffic, priced by
  Eq. (11) at exactly the permuted bytes.
* ``ring_consensus_step``      — the legacy ring-only ppermute path
  (``message_dtype`` casts the wire); kept for the volume benchmark.

Also provides Metropolis–Hastings weights (symmetric, doubly-stochastic —
the consensus-theory default) behind ``kind="metropolis"``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# mixing matrices
# ---------------------------------------------------------------------------


def ring_adjacency(K: int, hops: int = 1) -> np.ndarray:
    """Symmetric ring: each agent sees ``hops`` neighbours each side."""
    A = np.zeros((K, K), bool)
    for k in range(K):
        for d in range(1, hops + 1):
            A[k, (k + d) % K] = True
            A[k, (k - d) % K] = True
    if K > 1:
        np.fill_diagonal(A, False)
    return A


def full_adjacency(K: int) -> np.ndarray:
    A = np.ones((K, K), bool)
    np.fill_diagonal(A, False)
    return A


def mixing_weights(data_sizes, adjacency, kind: str = "paper",
                   include_self: bool = True):
    """(K, K) row-stochastic mixing matrix Σ with Σ[k, h] = σ_{k,h}.

    kind="paper":  σ_{k,h} = |E_h| / Σ_j |E_j| with the sum over N_k
                   (``include_self=False``, the literal Eq. 6 reading) or
                   N_k ∪ {k} (``include_self=True``, default). Eq. (6)'s
                   text is ambiguous ("computed using |E_{i,h}| and
                   |{E_{i,j}}_{j∈N_{k,i}}|"); the literal reading has ZERO
                   self-weight, which is non-convergent under pure mixing
                   on even rings and a pure swap for the paper's own
                   2-robot clusters — so the implementation they ran must
                   keep the local share. Both are exposed; tests cover the
                   convergence difference.
    kind="metropolis": σ_{k,h} = 1 / (1 + max(deg_k, deg_h)), self weight
                   1 − Σ — symmetric, doubly stochastic.

    ``adjacency`` may be bool (the lockstep protocol: an edge is up or
    down) or FLOAT per-edge weights in [0, 1] (the async engine's
    staleness-decayed lanes: λ^age on stale wires, 1 on fresh, 0 on
    dropped). The float path scales each edge's mass by its weight
    before normalizing — a stale neighbour is a faded lane with memory
    — and a {0, 1}-valued float input reproduces the bool path bit for
    bit (IEEE: ``1.0·x == x`` and ``0.0·x == +0.0`` for the finite
    positive sizes here), which is what keeps the always-on/τ=∞
    reduction exact. Metropolis degrees generalize to weighted degrees
    ``Σ_h w_{k,h}`` on the float path.
    """
    sizes = jnp.asarray(data_sizes, jnp.float32)
    A = jnp.asarray(adjacency)
    if jnp.issubdtype(A.dtype, jnp.floating):
        A = A.astype(jnp.float32)
        if kind == "paper":
            w = A * sizes[None, :]
            denom = w.sum(axis=1, keepdims=True)
            if include_self:
                denom = denom + sizes[:, None]
            denom = jnp.maximum(denom, 1e-12)
            return w / denom
        if kind == "metropolis":
            deg = A.sum(axis=1)
            w = A * (1.0 / (1.0 + jnp.maximum(deg[:, None], deg[None, :])))
            self_w = 1.0 - w.sum(axis=1)
            return w + jnp.diag(self_w)
        raise ValueError(_unknown_kind_msg(kind))
    A = A.astype(bool)
    if kind == "paper":
        w = jnp.where(A, sizes[None, :], 0.0)
        denom = w.sum(axis=1, keepdims=True)
        if include_self:
            denom = denom + sizes[:, None]
        denom = jnp.maximum(denom, 1e-12)
        return w / denom
    if kind == "metropolis":
        deg = A.sum(axis=1).astype(jnp.float32)
        w = jnp.where(A, 1.0 / (1.0 + jnp.maximum(deg[:, None], deg[None, :])),
                      0.0)
        self_w = 1.0 - w.sum(axis=1)
        return w + jnp.diag(self_w)
    raise ValueError(_unknown_kind_msg(kind))


MIX_KINDS = ("paper", "metropolis")


def _unknown_kind_msg(kind) -> str:
    """Refusal text for a bad mixing kind, naming the nearest match."""
    import difflib
    close = difflib.get_close_matches(str(kind), MIX_KINDS, n=1)
    hint = f"; did you mean {close[0]!r}?" if close else ""
    return (f"unknown mixing kind {kind!r}: supported kinds are "
            f"'paper' (Eq.-(6) data-size weights) and 'metropolis' "
            f"(doubly stochastic){hint}")


def _effective_mix(mix):
    """Add the implicit self weight so rows sum to 1 exactly."""
    self_w = 1.0 - mix.sum(axis=1)
    return mix + jnp.diag(self_w)


def resolve_mix(mix, data_sizes=None, kind: str = "paper",
                include_self: bool = True):
    """Accept either a ready (K, K) σ matrix or a Topology object."""
    if hasattr(mix, "mixing"):
        return mix.mixing(data_sizes, kind=kind, include_self=include_self)
    return mix


#: K · max-degree floor below which the batched sparse gather cannot
#: amortize its per-agent dispatch overhead and ``auto`` keeps the
#: dense (K, K) matmul (the overhead scales with agents × neighbours,
#: not payload bytes, so the floor is codec-independent). Calibrated
#: against the recorded ``BENCH_consensus_scale.json`` rows: every f32
#: sparse-pallas pick at K·H < 512 LOST to dense-xla (K=12 ring 0.59×,
#: K=12 cluster 0.66×, K=64 ring 0.80× … small_world 0.30×), while the
#: first winning row is exactly at the floor (K=256 ring, K·H = 512,
#: 1.46×).
SPARSE_GATHER_FLOOR = 512


def auto_path(mix, codec=None) -> str:
    """What ``impl="auto"`` resolves to for this (concrete) mix: the sparse
    gather only wins while the graph is actually sparse — on dense graphs
    (max degree > K/4, e.g. star or full) the gathered (K, H, N) neighbour
    tensor exceeds the (K, K) matmul's traffic and ``auto`` falls back to
    the dense path.

    Small/dense-ish populations also stay dense: below
    :data:`SPARSE_GATHER_FLOOR` total gather work (K · max degree) the
    vmapped per-agent gather is pure overhead against one small matmul
    — the benchmark recorded the K=12 ring sparse pick running at
    0.59× dense — so ``auto`` keeps them on the (K, K) path regardless
    of sparsity. The floor uses the RAW K·H (per-agent gather dispatch
    overhead scales with agents × neighbours, not with payload bytes),
    so a codec never demotes a population the f32 rows showed winning.

    With an int ``codec`` the gathered payload is the WIRE format, not
    f32 — the fused dequant-consensus kernel consumes int8-lane
    neighbour blocks directly (plus per-block scales when the codec
    quantizes block-wise), a quarter of the bytes — so the degree is
    discounted by the wire's DEVICE bytes per parameter (int8 lanes for
    both int8 and int4: what the gather actually moves) before
    comparing against the dense threshold (the dense matmul always runs
    on decoded f32). The discount applies ONLY to codecs whose sparse
    path gathers the wire itself (IntCodec through the fused
    dequant-consensus kernel, per-tensor or block-wise scales); every
    other codec decodes to f32 BEFORE the gather, so its degree counts
    at full width. The old heuristic ignored payload bytes entirely and
    kicked graphs to the dense path that a compressed gather serves
    cheaper.
    """
    M = np.asarray(mix)
    K = M.shape[0]
    off = M.copy()
    np.fill_diagonal(off, 0.0)
    H = int((off != 0).sum(axis=1).max()) if K else 0
    if K * max(float(H), 1.0) < SPARSE_GATHER_FLOOR:
        return "dense"
    codec = getattr(codec, "inner", codec)       # unwrap ErrorFeedback
    qblock = getattr(codec, "block", None)
    gathers_wire = getattr(codec, "qbits", None) is not None
    # the gather moves int8 LANES for every IntCodec (int4 values ride
    # int8 storage on-device) plus one f32 scale per qblock params
    wire_bits = (8.0 + (32.0 / qblock if qblock else 0.0)
                 if gathers_wire else None)
    h_eff = H * (wire_bits / 32.0) if wire_bits else float(H)
    return "sparse" if h_eff <= max(K // 4, 1) else "dense"


def sparse_structure(mix):
    """(idx, sig): per-agent neighbour indices and σ's from a CONCRETE mix.

    idx: (K, H) int32, sig: (K, H) float32 with H = max degree; rows with
    fewer neighbours are padded with the agent's own index and σ = 0 (a
    zero-weight self message, exact no-op in Eq. 6). Diagonal self weights
    are dropped — the update form x + Σ σ(nb − x) carries them implicitly.
    """
    M = np.asarray(mix, np.float32)
    K = M.shape[0]
    off = M.copy()
    np.fill_diagonal(off, 0.0)
    H = max(int((off != 0).sum(axis=1).max()), 1)
    idx = np.tile(np.arange(K, dtype=np.int32)[:, None], (1, H))
    sig = np.zeros((K, H), np.float32)
    for k in range(K):
        nbr = np.flatnonzero(off[k])
        idx[k, :len(nbr)] = nbr
        sig[k, :len(nbr)] = off[k, nbr]
    return idx, sig


# ---------------------------------------------------------------------------
# dense consensus — reference (K, K) matmul and the batched sparse paths
# ---------------------------------------------------------------------------


def consensus_step(stacked_params, mix, *, impl: str = "xla",
                   block_n: Optional[int] = None,
                   codec=None, codec_state=None, key=None,
                   error_feedback: bool = True, gamma: float = 1.0,
                   structure=None):
    """Eq. (6) on agent-stacked params (leading axis K). mix: (K, K) σ or a
    :class:`repro.core.topology.Topology` (uniform paper weights).

    impl:
      * ``"xla"``    — dense matmul ``M @ xf`` per leaf (reference; fine for
        the 12-robot case study, O(K²·N) and H extra parameter-sized
        temporaries at large K);
      * ``"pallas"`` — batched-over-agents sparse gather feeding the fused
        :mod:`repro.kernels.consensus_update` kernel (interpret mode off
        TPU), O(K·H·N);
      * ``"sparse"`` — the same sparse gather, but off TPU it runs the
        pure-jnp kernel oracle instead of interpret mode (what the
        engine's ``sparse-pallas`` plan uses: Pallas where it compiles,
        the bit-identical oracle elsewhere);
      * ``"auto"``   — for sparse graphs (see :func:`auto_path`), pallas on
        TPU and otherwise the same sparse gather applied through the
        pure-jnp kernel oracle (bit-identical to
        ``ref.consensus_update_reference`` per agent); for dense graphs
        (star, full — max degree > K/4) it falls back to the dense matmul,
        which moves strictly fewer bytes there. With a codec the
        threshold is payload-aware (:func:`auto_path`).

    codec — compress the EXCHANGED models (:mod:`repro.comms`): a spec
    string (``"int8"``, ``"bf16"``, ``"topk:0.05"``, …) or Codec. Every
    agent consumes its neighbours' DECODED models x̂_h and recenters on
    its own decoded copy: W_k + Σ_h σ_{k,h} (x̂_h − x̂_k), which keeps the
    population mean exact under doubly-stochastic σ regardless of the
    compression (the CHOCO-gossip identity). Lossy codecs are wrapped in
    :class:`~repro.comms.codecs.ErrorFeedback` by default
    (``error_feedback=False`` opts out) so the per-round quantization
    error telescopes instead of accumulating; ``codec_state`` is the
    stacked residual pytree (None ⇒ zeros) and ``key`` enables
    stochastic rounding; ``gamma`` damps the off-diagonal σ (CHOCO-style
    consensus step size — aggressive sparsifiers like top-k need γ < 1
    to contract). With a codec the return value is
    ``(new_stacked_params, new_codec_state)``; without, just the params
    (unchanged API). Int wires (int8/int4 lanes, per-tensor or
    block-wise ``int8:b64`` scales) route through the fused
    dequantize-consensus kernel on the sparse path
    (:mod:`repro.kernels.quant_consensus`).

    The sparse paths need a CONCRETE mix (numpy / non-traced) — the
    neighbour structure is extracted at trace time — UNLESS ``structure``
    is given: a ``(idx, sig)`` pair in :func:`sparse_structure` layout
    where ``idx`` (K, H) int32 is the CONCRETE full-graph neighbour
    index table and ``sig`` (K, H) float32 may be TRACED. This is the
    time-varying-graph hook: per-round survival masks zero (and
    renormalize) the σ of faded neighbour lanes without rebuilding the
    gather indices, so sparse plans stay one compiled program across
    rounds (σ is already a runtime operand of the fused kernels).
    ``gamma`` is applied to the provided ``sig`` exactly as it would be
    to the extracted one.
    """
    mix = resolve_mix(mix)
    if impl not in ("xla", "pallas", "auto", "sparse"):
        raise ValueError(f"unknown impl {impl!r}; use xla/pallas/sparse/auto")
    if codec is None and (codec_state is not None or gamma != 1.0):
        raise ValueError(
            f"codec_state={'set' if codec_state is not None else None} "
            f"/ gamma={gamma} only apply to compressed consensus but "
            "codec=None — pass codec= (e.g. 'int8'), or drop them "
            "(they would be silently ignored otherwise)")
    if codec is not None:
        from repro import comms   # deferred: core stays import-light
        codec = comms.resolve_codec(codec, error_feedback)
        return _compressed_consensus_step(
            stacked_params, mix, codec, codec_state, key,
            impl=impl, block_n=block_n, gamma=gamma, structure=structure)
    if impl == "auto" and auto_path(mix) == "dense":
        impl = "xla"
    if impl == "xla":
        M = _effective_mix(jnp.asarray(mix, jnp.float32))

        def mix_leaf(x):
            xf = x.astype(jnp.float32).reshape(x.shape[0], -1)
            y = M @ xf
            return y.reshape(x.shape).astype(x.dtype)

        return jax.tree.map(mix_leaf, stacked_params)

    use_pallas = impl == "pallas" or (impl in ("auto", "sparse")
                                      and jax.default_backend() == "tpu")
    if structure is None:
        idx_np, sig_np = sparse_structure(mix)
        idx, sig = jnp.asarray(idx_np), jnp.asarray(sig_np)
    else:                  # per-round (possibly traced) σ on baked indices
        idx, sig = (jnp.asarray(structure[0]),
                    jnp.asarray(structure[1], jnp.float32))

    from repro.kernels import ops  # deferred: keeps consensus importable
                                   # without the Pallas toolchain

    kernel_impl = ("pallas" if jax.default_backend() == "tpu"
                   else "interpret") if use_pallas else "xla"

    kw = {} if block_n is None else {"block_n": block_n}

    def mix_leaf(x):
        K = x.shape[0]
        xf = x.astype(jnp.float32).reshape(K, -1)
        y = ops.consensus_update(xf, xf, idx, sig, impl=kernel_impl, **kw)
        return y.reshape(x.shape).astype(x.dtype)

    return jax.tree.map(mix_leaf, stacked_params)


def _compressed_consensus_step(stacked_params, mix, codec, codec_state,
                               key, *, impl: str, block_n: Optional[int],
                               gamma: float = 1.0, structure=None):
    """Eq. (6) over codec'd exchanges (see :func:`consensus_step`).

    Per leaf: (1) each agent encodes its message m_k = W_k + r_k (r = 0
    without error feedback) to the wire format and decodes x̂_k back,
    (2) the mixing update runs on the decoded models around the agent's
    own decoded copy, (3) residuals carry the compression error to the
    next round. Int wires (per-tensor or block-wise scales) take the
    fused Pallas dequant-consensus kernel on the sparse path; other
    codecs decode first and reuse the plain consensus kernel.
    ``structure``: per-round (idx, possibly-traced sig) override of the
    sparse neighbour structure (see :func:`consensus_step`).
    """
    from repro import comms
    from repro.kernels import ops

    base = codec.inner if isinstance(codec, comms.ErrorFeedback) else codec
    stateful = isinstance(codec, comms.ErrorFeedback)

    if impl == "auto":
        impl = "xla" if auto_path(mix, codec=base) == "dense" else "sparse"
    use_pallas = impl == "pallas" or (impl == "sparse"
                                      and jax.default_backend() == "tpu")
    sparse = impl in ("pallas", "sparse")
    kernel_impl = ("pallas" if jax.default_backend() == "tpu"
                   else "interpret") if use_pallas else "xla"
    kw = {} if block_n is None else {"block_n": block_n}

    if sparse:
        if structure is None:
            idx_np, sig_np = sparse_structure(mix)
            idx, sig = jnp.asarray(idx_np), gamma * jnp.asarray(sig_np)
        else:
            idx = jnp.asarray(structure[0])
            sig = gamma * jnp.asarray(structure[1], jnp.float32)
    else:
        M = jnp.asarray(mix, jnp.float32)
        off = gamma * (M - jnp.diag(jnp.diag(M)))
        rowsum = off.sum(axis=1)

    leaves, treedef = jax.tree.flatten(stacked_params)
    if stateful:
        state_leaves = (jax.tree.leaves(codec_state)
                        if codec_state is not None
                        else [jnp.zeros(jnp.shape(x), jnp.float32)
                              for x in leaves])
        if len(state_leaves) != len(leaves):
            raise ValueError(
                f"codec_state has {len(state_leaves)} leaves but "
                f"stacked_params has {len(leaves)} — thread the "
                "codec_state returned by the previous step (or pass "
                "None to start from zero error-feedback residuals)")
    else:
        state_leaves = [None] * len(leaves)

    new_leaves, new_state = [], []
    for li, (x, r) in enumerate(zip(leaves, state_leaves)):
        K = x.shape[0]
        xf = x.astype(jnp.float32).reshape(K, -1)
        agent_keys = (None if key is None else
                      jax.random.split(jax.random.fold_in(key, li), K))

        if stateful:     # the EF identity lives in ONE place: the codec
            step_fn = (lambda mm, rr, kk=None:
                       codec.encode_leaf_stateful(mm, rr, kk))
            if agent_keys is None:
                enc, xhat, r_new = jax.vmap(step_fn)(xf, r.reshape(K, -1))
            else:
                enc, xhat, r_new = jax.vmap(step_fn)(xf, r.reshape(K, -1),
                                                     agent_keys)
        else:
            if agent_keys is None:
                enc = jax.vmap(lambda mm: base.encode_leaf(mm, None))(xf)
            else:
                enc = jax.vmap(base.encode_leaf)(xf, agent_keys)
            like = jax.ShapeDtypeStruct(xf.shape[1:], jnp.float32)
            xhat = jax.vmap(lambda p: base.decode_leaf(p, like))(enc)

        if sparse and isinstance(base, comms.IntCodec):
            # int wire (per-tensor OR block-wise scales): neighbour
            # tiles stay int8 lanes through the gather; dequant happens
            # INSIDE the fused combine
            q, s = enc["q"], enc["scale"]
            qkw = dict(kw) if base.block is None \
                else dict(kw, qblock=base.block)

            y = ops.quant_consensus_update(
                xf, q, s, q, s, idx, sig, impl=kernel_impl, **qkw)
        elif sparse:
            mixed_hat = ops.consensus_update(
                xhat, xhat, idx, sig, impl=kernel_impl, **kw)
            y = xf + (mixed_hat - xhat)
        else:
            y = xf + off @ xhat - rowsum[:, None] * xhat

        new_leaves.append(y.reshape(x.shape).astype(x.dtype))
        if stateful:
            new_state.append(r_new.reshape(x.shape))

    new_params = jax.tree.unflatten(treedef, new_leaves)
    state_out = (jax.tree.unflatten(treedef, new_state)
                 if stateful else None)
    return new_params, state_out


def consensus_error(stacked_params) -> jnp.ndarray:
    """Mean squared deviation from the agent average (0 ⇒ consensus)."""
    tot, n = 0.0, 0
    for x in jax.tree.leaves(stacked_params):
        xf = x.astype(jnp.float32)
        dev = xf - xf.mean(axis=0, keepdims=True)
        tot = tot + jnp.sum(jnp.square(dev))
        n += dev.size
    return tot / n


# ---------------------------------------------------------------------------
# distributed (sharded) consensus — sidelink == ICI ring
# ---------------------------------------------------------------------------


def ring_consensus_step(params, data_size, axis_name: str, hops: int = 1,
                        include_self: bool = True, message_dtype=None):
    """One Eq.-(6) round where each ``axis_name`` position is an agent.

    Must run inside shard_map. ``data_size``: scalar |E_k| per agent.
    Exchanges params + sizes with ±1..hops ring neighbours via ppermute
    (2·hops messages of b(W) per agent per round — the paper's SL traffic).
    ``include_self`` as in :func:`mixing_weights`.

    ``message_dtype``: cast the EXCHANGED copy (e.g. bf16) — halves the
    Eq.-(11) sidelink bytes. An optimization_barrier pins the cast before
    the ppermute (XLA otherwise commutes converts past permutes and keeps
    the wire at the storage dtype — EXPERIMENTS.md §Perf P3).
    """
    K = jax.lax.axis_size(axis_name)
    perms = []
    for d in range(1, hops + 1):
        perms.append([(i, (i + d) % K) for i in range(K)])   # from left
        perms.append([(i, (i - d) % K) for i in range(K)])   # from right

    sizes = [jax.lax.ppermute(data_size, axis_name, p) for p in perms]
    denom = sum(sizes) + (data_size if include_self else 0.0)
    sigmas = [s / jnp.maximum(denom, 1e-12) for s in sizes]

    def combine(x):
        if message_dtype is not None and x.dtype != jnp.dtype(message_dtype):
            # the whole neighbour pathway stays in message_dtype: if the
            # received value were upcast, XLA CSEs the convert with the
            # local f32 accumulator and moves the WIRE back to f32 —
            # consuming neighbours only in bf16 pins a bf16 exchange.
            md = jnp.dtype(message_dtype)
            msg = x.astype(md)
            neigh = [jax.lax.ppermute(msg, axis_name, p) for p in perms]
            upd = sum((sig.astype(md) * (nb - msg)).astype(jnp.float32)
                      for sig, nb in zip(sigmas, neigh))
        else:
            neigh = [jax.lax.ppermute(x, axis_name, p) for p in perms]
            xf32 = x.astype(jnp.float32)
            upd = sum(sig * (nb.astype(jnp.float32) - xf32)
                      for sig, nb in zip(sigmas, neigh))
        return (x.astype(jnp.float32) + upd).astype(x.dtype)

    return jax.tree.map(combine, params)


def permutation_schedule(mix, gamma: float = 1.0):
    """Decompose a CONCRETE σ matrix into ppermute rounds for the
    distributed path: a list of ``(pairs, sig)`` where ``pairs`` is a full
    source→target permutation of the K mesh positions and ``sig`` is the
    (K,) vector of Eq.-(6) weights each target applies to the message it
    receives that round (γ·σ_{tgt,src}; 0 where the round carries no real
    edge for that target).

    Greedy maximal-matching cover: every directed edge of the graph is
    carried by exactly one round, so the number of ppermutes is ≥ the max
    degree and usually equal to it (ring hops=1 ⇒ 2 rounds). Each matching
    is completed to a FULL permutation — vmap's ppermute batching rule
    (and a clean SPMD lowering) wants every position as source and target
    exactly once — and the padding lanes land with σ = 0, an exact no-op
    in Eq. (6). Eq.-(11) pricing is untouched: it counts the graph's
    directed edges, not the permutation padding.
    """
    M = np.asarray(mix, np.float32)
    K = M.shape[0]
    off = M.copy()
    np.fill_diagonal(off, 0.0)
    edges = {(k, h) for k in range(K)
             for h in np.flatnonzero(off[k] != 0.0)}
    schedule = []
    while edges:
        used_src, used_tgt = set(), set()
        pairs, sig = [], np.zeros(K, np.float32)
        for k, h in sorted(edges):
            if h in used_src or k in used_tgt:
                continue
            pairs.append((h, k))
            sig[k] = gamma * off[k, h]
            used_src.add(h)
            used_tgt.add(k)
        edges -= {(tgt, src) for src, tgt in pairs}
        free_src = [s for s in range(K) if s not in used_src]
        free_tgt = [t for t in range(K) if t not in used_tgt]
        pairs.extend(zip(free_src, free_tgt))
        schedule.append((tuple(pairs), sig))
    return schedule


def _permute_agent_step(params, residual, sigs, akey, *, pairs_list,
                        axis_name: str, codec, stateful: bool,
                        pin_wire: bool = False):
    """One agent's Eq.-(6) round on the ppermute path (runs per mesh
    position under shard_map, or per vmapped lane in the emulation).

    The agent encodes its message once (m = W + r with error feedback),
    the WIRE payload (int8 q + scales, bf16, top-k pairs, …) rides every
    scheduled ppermute, and each received payload is decoded INSIDE the
    combine around the agent's own decoded copy x̂_k — the same CHOCO
    recentering as the dense path, so the population mean stays exact
    under doubly-stochastic σ regardless of the wire format.
    """
    leaves, treedef = jax.tree.flatten(params)
    res_leaves = (jax.tree.leaves(residual) if residual is not None
                  else [None] * len(leaves))
    new_leaves, new_res = [], []
    for li, (x, r) in enumerate(zip(leaves, res_leaves)):
        xf = jnp.asarray(x, jnp.float32).ravel()
        kk = None if akey is None else jax.random.fold_in(akey, li)
        like = jax.ShapeDtypeStruct(xf.shape, jnp.float32)
        if codec is None:
            payload, xhat = {"v": xf}, xf
        elif stateful:
            payload, xhat, r_new = codec.encode_leaf_stateful(
                xf, r.ravel(), kk)
            new_res.append(r_new.reshape(jnp.shape(x)))
        else:
            payload = codec.encode_leaf(xf, kk)
            xhat = codec.decode_leaf(payload, like)
        if pin_wire:
            # pin the wire format: XLA commutes pure-convert encodes
            # (bf16) past collective-permutes and would ship f32
            # otherwise (the barrier has no vmap batching rule, so the
            # emulation path — where no bytes cross a real link — skips it)
            payload = jax.lax.optimization_barrier(payload)
        acc = jnp.zeros_like(xf)
        for m, pairs in enumerate(pairs_list):
            nb = jax.tree.map(
                lambda a: jax.lax.ppermute(a, axis_name, pairs), payload)
            if pin_wire:
                # pin the RECEIVE side too: the decode convert otherwise
                # commutes back through the ppermute (convert(permute(x))
                # == permute(convert(x))) and the wire ships f32 even
                # though the send side was pinned — repro.analysis H2
                # caught exactly this on the bf16 distributed wire
                nb = jax.lax.optimization_barrier(nb)
            nb_hat = nb["v"] if codec is None else codec.decode_leaf(nb, like)
            acc = acc + sigs[m] * (nb_hat - xhat)
        new_leaves.append((xf + acc).reshape(jnp.shape(x)).astype(x.dtype))
    new_params = jax.tree.unflatten(treedef, new_leaves)
    res_out = jax.tree.unflatten(treedef, new_res) if stateful else None
    return new_params, res_out


def _mesh_axis(mesh, axis_name: str):
    if mesh is None:
        return None
    return dict(mesh.shape).get(axis_name)


def distributed_consensus_step(stacked_params, mix, *,
                               axis_name: str = "agents", mesh=None,
                               codec=None, codec_state=None, key=None,
                               gamma: float = 1.0,
                               error_feedback: bool = True,
                               schedule=None, sig_override=None):
    """Eq. (6) on the DISTRIBUTED path with codec-aware wires: one agent
    per mesh position, neighbour exchange via ``jax.lax.ppermute`` rounds
    from :func:`permutation_schedule` (works for ANY concrete graph, not
    just rings), and the permuted payload is the CODEC wire — int8/int4
    lanes plus their scales for :class:`~repro.comms.codecs.IntCodec`,
    bf16 for the cast codec — so ``Topology.round_comm_joules(codec=)``
    prices exactly what this path ships.

    With ``mesh`` holding an ``axis_name`` axis of size K, runs under
    shard_map (one agent per device; the ppermutes are ICI sidelink
    traffic). Otherwise runs the vmap-with-axis_name emulation, which
    shares the collective semantics — the CPU test path.

    ``sig_override``: traced (K, M) per-slot weights replacing the
    schedule's baked γ·σ stack for THIS round — the σ is a runtime
    operand of the compiled program (the ppermute pairs stay trace-time
    structure), which is how the time-varying engine masks individual
    schedule slots in-scan without a retrace: faded slots ride with
    σ = 0, exact no-ops in Eq. (6), while the wire still ships all M
    permutations of the fixed schedule superset.

    Returns ``(new_stacked_params, new_codec_state)``; the state is the
    stacked error-feedback residual (None for stateless codecs).
    """
    mix = resolve_mix(mix)
    if codec is not None:
        from repro import comms   # deferred: core stays import-light
        codec = comms.resolve_codec(codec, error_feedback)
    stateful = codec is not None and codec.stateful
    if schedule is None:
        schedule = permutation_schedule(mix, gamma)
    K = jax.tree.leaves(stacked_params)[0].shape[0]
    pairs_list = [p for p, _ in schedule]
    if sig_override is not None:
        sig_stack = jnp.asarray(sig_override, jnp.float32)
        if sig_stack.shape != (K, len(schedule)):
            raise ValueError(
                f"sig_override is {sig_stack.shape}, schedule wants "
                f"(K={K}, M={len(schedule)})")
    else:
        sig_stack = (jnp.stack([jnp.asarray(s) for _, s in schedule],
                               axis=1)
                     if schedule else jnp.zeros((K, 0), jnp.float32))
    keys = None if key is None else jax.random.split(key, K)
    if stateful and codec_state is None:
        codec_state = jax.tree.map(
            lambda x: jnp.zeros(jnp.shape(x), jnp.float32), stacked_params)
    if not stateful:
        codec_state = None

    use_mesh = _mesh_axis(mesh, axis_name) == K

    def agent_fn(p, r, sg, kk):
        return _permute_agent_step(p, r, sg, kk, pairs_list=pairs_list,
                                   axis_name=axis_name, codec=codec,
                                   stateful=stateful, pin_wire=use_mesh)

    if use_mesh:
        from jax.sharding import PartitionSpec

        spec = PartitionSpec(axis_name)

        def block_fn(p, r, sg, kk):     # each position holds ONE agent
            sq = lambda t: jax.tree.map(lambda a: a[0], t)
            out, res = agent_fn(sq(p), sq(r), sq(sg), sq(kk))
            un = lambda t: jax.tree.map(lambda a: a[None], t)
            return un(out), un(res)

        new, res = jax.shard_map(
            block_fn, mesh=mesh, in_specs=(spec,) * 4,
            out_specs=(spec, spec), check_vma=False)(
            stacked_params, codec_state, sig_stack, keys)
    else:
        new, res = jax.vmap(agent_fn, axis_name=axis_name)(
            stacked_params, codec_state, sig_stack, keys)
    return new, (res if stateful else None)


def _sharded_block_leaf(x_blk, r_blk, idx_blk, sig_blk, keys_blk, *, K: int,
                        codec, stateful: bool, axis_name: str,
                        kernel_impl: str, kw: dict,
                        pin_wire: bool = False):
    """One mesh position's block of agents, one leaf: encode the owned
    rows, all_gather the WIRE along the agent axis, then mix every owned
    row from the gathered wire (fused dequant-consensus kernel for every
    IntCodec wire — per-tensor AND block-wise scales stay int8 lanes
    through the gather; generic decode-then-combine otherwise)."""
    like = jax.ShapeDtypeStruct(x_blk.shape[1:], jnp.float32)
    r_new = None
    if codec is None:
        payload, xhat_blk = {"v": x_blk}, x_blk
    elif stateful:
        if keys_blk is None:
            payload, xhat_blk, r_new = jax.vmap(
                lambda m, rr: codec.encode_leaf_stateful(m, rr, None))(
                x_blk, r_blk)
        else:
            payload, xhat_blk, r_new = jax.vmap(
                codec.encode_leaf_stateful)(x_blk, r_blk, keys_blk)
    else:
        if keys_blk is None:
            payload = jax.vmap(lambda m: codec.encode_leaf(m, None))(x_blk)
        else:
            payload = jax.vmap(codec.encode_leaf)(x_blk, keys_blk)
        xhat_blk = jax.vmap(lambda p: codec.decode_leaf(p, like))(payload)
    if pin_wire:    # pin the wire dtype (no batching rule: mesh path only)
        payload = jax.lax.optimization_barrier(payload)
    gathered = jax.tree.map(
        lambda a: jax.lax.all_gather(a, axis_name
                                     ).reshape((K,) + a.shape[1:]),
        payload)
    if pin_wire:
        # receive-side pin: without it the generic decode below commutes
        # back through the all_gather and the wire reverts to f32 (the
        # int-wire fused path is immune — its gather operands are int8)
        gathered = jax.lax.optimization_barrier(gathered)

    from repro.kernels import ops   # deferred: keeps consensus importable

    base = getattr(codec, "inner", codec)
    if codec is not None and getattr(base, "qbits", None) is not None:
        # int wire (per-tensor OR block-wise scales): neighbour tiles
        # stay int8 lanes through the gather; dequant happens INSIDE the
        # fused combine — block-scaled wires no longer decode-then-
        # combine on the sharded plan
        qblock = getattr(base, "block", None)
        qkw = dict(kw) if qblock is None else dict(kw, qblock=qblock)

        y = ops.quant_consensus_update(
            x_blk, payload["q"], payload["scale"], gathered["q"],
            gathered["scale"], idx_blk, sig_blk, impl=kernel_impl, **qkw)
    else:
        xhat_all = (gathered["v"] if codec is None else
                    jax.vmap(lambda p: codec.decode_leaf(p, like))(gathered))
        mixed_hat = ops.consensus_update(xhat_blk, xhat_all, idx_blk,
                                         sig_blk, impl=kernel_impl, **kw)
        y = x_blk + (mixed_hat - xhat_blk)
    return y, r_new


def sharded_consensus_step(stacked_params, mix, *, num_blocks: int,
                           axis_name: str = "agents", mesh=None,
                           codec=None, codec_state=None, key=None,
                           gamma: float = 1.0,
                           error_feedback: bool = True,
                           block_n: Optional[int] = None,
                           structure=None):
    """Eq. (6) on the SHARDED path: the K-agent population is split into
    ``num_blocks`` contiguous blocks of B = K/num_blocks agents, each
    owned by one mesh position. Per round, every position encodes its own
    block's wires, ``all_gather``s the (K, ·) WIRE along the agent axis
    (codec-compressed bytes, not f32), and mixes its owned rows through
    the sparse gather — so no single program ever materializes the
    (K, K) mixing stack or the K×H f32 neighbour tensor, which is what
    lifts the single-program vmap limit for K ≫ core count.

    With ``mesh`` holding an ``axis_name`` axis of size ``num_blocks``,
    runs under shard_map; otherwise the vmap-with-axis_name emulation
    (identical collective semantics — the CPU test path).

    Returns ``(new_stacked_params, new_codec_state)`` like the other
    compressed paths; the sparse structure needs a CONCRETE mix unless
    ``structure`` supplies a per-round ``(idx, sig)`` override — ``idx``
    concrete, ``sig`` possibly traced — in which case faded-neighbour
    lanes carry σ = 0 and the all_gather/gather indices stay baked (the
    time-varying-graph contract of :func:`consensus_step`).
    """
    mix = resolve_mix(mix)
    if codec is not None:
        from repro import comms
        codec = comms.resolve_codec(codec, error_feedback)
    stateful = codec is not None and codec.stateful
    leaves, treedef = jax.tree.flatten(stacked_params)
    K = leaves[0].shape[0]
    if num_blocks < 1 or K % num_blocks:
        raise ValueError(
            f"num_blocks={num_blocks} must divide the population K={K}")
    B = K // num_blocks
    if structure is None:
        idx_np, sig_np = sparse_structure(mix)
        idx = jnp.asarray(idx_np)
        sig = gamma * jnp.asarray(sig_np)
    else:
        idx = jnp.asarray(structure[0])
        sig = gamma * jnp.asarray(structure[1], jnp.float32)
    kernel_impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    kw = {} if block_n is None else {"block_n": block_n}

    if stateful:
        state_leaves = (jax.tree.leaves(codec_state)
                        if codec_state is not None
                        else [jnp.zeros(jnp.shape(x), jnp.float32)
                              for x in leaves])
        if len(state_leaves) != len(leaves):
            raise ValueError(
                f"codec_state has {len(state_leaves)} leaves but "
                f"stacked_params has {len(leaves)} — thread the "
                "codec_state returned by the previous step (or pass "
                "None to start from zero error-feedback residuals)")
    else:
        state_leaves = [None] * len(leaves)

    use_mesh = _mesh_axis(mesh, axis_name) == num_blocks

    def _run(fn, *args):
        """Map ``fn`` over the block axis: shard_map on a real mesh,
        vmap(axis_name) emulation otherwise. args are (K, ...) or None."""
        if use_mesh:
            from jax.sharding import PartitionSpec

            spec = PartitionSpec(axis_name)
            return jax.shard_map(fn, mesh=mesh,
                                 in_specs=(spec,) * len(args),
                                 out_specs=(spec, spec),
                                 check_vma=False)(*args)
        blk = jax.tree.map(
            lambda a: a.reshape((num_blocks, B) + a.shape[1:]), args)
        out, res = jax.vmap(fn, axis_name=axis_name)(*blk)
        return jax.tree.map(
            lambda a: a.reshape((K,) + a.shape[2:]), (out, res))

    new_leaves, new_state = [], []
    for li, (x, r) in enumerate(zip(leaves, state_leaves)):
        xf = x.astype(jnp.float32).reshape(K, -1)
        rf = None if r is None else r.reshape(K, -1)
        keys_leaf = (None if key is None else
                     jax.random.split(jax.random.fold_in(key, li), K))
        block_fn = functools.partial(
            _sharded_block_leaf, K=K, codec=codec, stateful=stateful,
            axis_name=axis_name, kernel_impl=kernel_impl, kw=kw,
            pin_wire=use_mesh)
        y, r_new = _run(block_fn, xf, rf, idx, sig, keys_leaf)
        new_leaves.append(y.reshape(x.shape).astype(x.dtype))
        if stateful:
            new_state.append(r_new.reshape(x.shape))

    new_params = jax.tree.unflatten(treedef, new_leaves)
    state_out = (jax.tree.unflatten(treedef, new_state)
                 if stateful else None)
    return new_params, state_out


def cluster_ring_consensus_step(params, data_size, axis_name: str,
                                cluster_size: int,
                                include_self: bool = True):
    """Ring consensus restricted to contiguous clusters of ``cluster_size``
    agents along ``axis_name`` (the paper's per-task clusters C_i: only
    same-cluster agents exchange models)."""
    K = jax.lax.axis_size(axis_name)
    assert K % cluster_size == 0
    if cluster_size == 1:
        return params
    perm_fwd, perm_bwd = [], []
    for i in range(K):
        c = i // cluster_size
        perm_fwd.append((i, c * cluster_size + (i + 1 - c * cluster_size)
                         % cluster_size))
        perm_bwd.append((i, c * cluster_size + (i - 1 - c * cluster_size)
                         % cluster_size))
    perms = [perm_fwd, perm_bwd] if cluster_size > 2 else [perm_fwd]

    sizes = [jax.lax.ppermute(data_size, axis_name, p) for p in perms]
    denom = sum(sizes) + (data_size if include_self else 0.0)
    sigmas = [s / jnp.maximum(denom, 1e-12) for s in sizes]

    def combine(x):
        neigh = [jax.lax.ppermute(x, axis_name, p) for p in perms]
        xf = x.astype(jnp.float32)
        upd = sum(sig * (nb.astype(jnp.float32) - xf)
                  for sig, nb in zip(sigmas, neigh))
        return (xf + upd).astype(x.dtype)

    return jax.tree.map(combine, params)
