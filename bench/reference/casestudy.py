"""Plain reference of the paper's multi-task process (Sect. IV): robots
on an 8×5 landmark grid, six trajectory tasks, MAML meta-training at
the data center, then per-task decentralized FL in 2-robot clusters
until the greedy running reward reaches its target.

* Environment: one-hot landmark state, moves F/B/L/R clipped at the
  border, a position-reward table per task (5 + 5·progress on the
  task's trajectory, 1 next to it, 0 two cells off, −0.5 elsewhere).
* Data (Sect. IV-A): ε-greedy episodes of 20 motions from the common
  entry point under the agent's current Q-network, resampled into
  minibatches of 16 transitions.
* Loss (Eq. 7): double-DQN TD error, target network frozen in the
  batch, rewards scaled by ``reward_scale``, discount ``discount``.
* Meta round (Eqs. 3–5, first order): ``inner_steps`` SGD steps per
  meta task on support episodes, the query loss at the adapted weights,
  its mean over tasks differentiated at the meta weights (J ≈ I).
* FL round (Eq. 6): every robot collects an episode with its own Q and
  takes ``fl_local_steps`` clipped SGD steps against robot 0's weights
  as target, all robots in one computation batched over them (on TPU
  the Q-values of a single state come out as in float32 unbatched and
  rounded as one bfloat16 pass batched, 0.3–1.5% apart, enough to flip
  an ε-greedy decision); the cluster mixes by Eq. (6) (each robot keeps its own
  share: σ = 1/2 at 2 robots); robot 0 is evaluated greedily over 4
  episodes; the round that reaches the target freezes the cluster.
* Near ties: a greedy decision whose best and runner-up Q differ by
  less than rounding sends the episode either way. The reference
  records each decision's margin and can take the other side of the
  closest one (``first_round``), so that a comparison can accept
  either outcome of a tie and no other.

Keys are split in the configuration's program's order, so both draw the
same episodes. Nothing here imports the program. ``rnd`` rounds values
and ``store`` the stored weights for a narrower-precision control.
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np

GRID = (8, 5)
ENTRY = (0, 2)
MOVES = np.array([[1, 0], [-1, 0], [0, 1], [0, -1]], np.int32)


def trajectories():
    prefix = [(x, 2) for x in range(3)]
    exits = [
        [(3, 2), (4, 2), (5, 2), (6, 2), (7, 2)],
        [(3, 3), (4, 3), (5, 4), (6, 4), (7, 4)],
        [(3, 1), (4, 1), (5, 0), (6, 0), (7, 0)],
        [(3, 3), (3, 4), (4, 4), (5, 4), (5, 3)],
        [(3, 1), (3, 0), (4, 0), (5, 0), (5, 1)],
        [(3, 2), (4, 2), (4, 3), (5, 3), (6, 3), (7, 3)],
    ]
    return [prefix + e for e in exits]


def reward_tables() -> np.ndarray:
    out = []
    for tr in trajectories():
        R = np.full(GRID, -0.5, np.float32)
        for x in range(GRID[0]):
            for y in range(GRID[1]):
                d, i = min((abs(x - tx) + abs(y - ty), i)
                           for i, (tx, ty) in enumerate(tr))
                if d == 0:
                    R[x, y] = 5.0 + 5.0 * i / max(len(tr) - 1, 1)
                elif d == 1:
                    R[x, y] = 1.0
                elif d == 2:
                    R[x, y] = 0.0
        out.append(R)
    return np.stack(out)


class Protocol:
    """The process above for a Q-network reference ``model`` (a module
    with ``init(key, c)`` and ``forward(params, c, state, rnd)``)."""

    def __init__(self, model, c: dict, p: dict, rnd=lambda x: x,
                 store=lambda x: x):
        self.model, self.c, self.p = model, c, p
        self.rnd, self.store = rnd, store
        self.rewards = jnp.asarray(reward_tables())
        self.cells = GRID[0] * GRID[1]
        M = np.full((p["robots"], p["robots"]), 1.0 / p["robots"],
                    np.float32)
        self.mix = jnp.asarray(M)
        self.meta_round = jax.jit(self._meta_round)
        self.fl_round = jax.jit(self._fl_round)

    # -- environment and data ------------------------------------------
    def q(self, params, s):
        return self.model.forward(params, self.c, s, self.rnd)

    def one_hot(self, pos):
        return jax.nn.one_hot(pos[..., 0] * GRID[1] + pos[..., 1],
                              self.cells, dtype=jnp.float32)

    def rollout(self, key, params, task, epsilon, batch, steps=20,
                flip_at=-1):
        """ε-greedy episodes from the entry point, and each greedy
        decision's relative margin (best Q over the runner-up; ``inf``
        where the step explored). ``flip_at`` takes the runner-up at that
        step instead of the best: the other side of a near tie."""
        pos0 = jnp.broadcast_to(jnp.asarray(ENTRY, jnp.int32), (batch, 2))

        def body(pos, kt):
            k, t = kt
            s = self.one_hot(pos)
            ka, ke = jax.random.split(k)
            q = self.q(params, s)
            greedy = jnp.argmax(q, axis=-1)
            best = jnp.take_along_axis(q, greedy[:, None], axis=1)[:, 0]
            rest = jnp.where(jax.nn.one_hot(greedy, 4, dtype=bool),
                             -jnp.inf, q)
            second = jnp.argmax(rest, axis=-1)
            gap = (best - jnp.max(rest, axis=-1)) / jnp.maximum(
                jnp.max(jnp.abs(q), axis=-1), 1e-12)
            rand = jax.random.randint(ka, (batch,), 0, 4)
            explore = jax.random.uniform(ke, (batch,)) < epsilon
            a = jnp.where(explore, rand,
                          jnp.where(t == flip_at, second, greedy)
                          ).astype(jnp.int32)
            new = jnp.clip(pos + jnp.asarray(MOVES)[a], jnp.array([0, 0]),
                           jnp.array([GRID[0] - 1, GRID[1] - 1]))
            r = self.rewards[task, new[:, 0], new[:, 1]]
            return new, (s, a, r, self.one_hot(new),
                         jnp.where(explore, jnp.inf, gap))

        _, (s, a, r, s2, gap) = jax.lax.scan(
            body, pos0, (jax.random.split(key, steps), jnp.arange(steps)))
        sw = lambda x: x.swapaxes(0, 1)
        return {"state": sw(s), "action": sw(a), "reward": sw(r),
                "next_state": sw(s2)}, sw(gap)

    def episode_batches(self, key, params, task, n_batches, flip_at=-1):
        """(minibatches, the episode's decision margins)."""
        k1, k2 = jax.random.split(key)
        data, gap = self.rollout(k1, params, task, self.p["epsilon"], 1,
                                 flip_at=flip_at)
        flat = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]),
                            data)
        idx = jax.random.randint(k2, (n_batches, self.p["minibatch"]), 0,
                                 flat["state"].shape[0])
        return jax.tree.map(lambda x: x[idx], flat), gap[0]

    def td_loss(self, params, target, b):
        q = self.q(params, b["state"])
        q_sa = jnp.take_along_axis(q, b["action"][:, None], axis=1)[:, 0]
        a_star = jnp.argmax(self.q(params, b["next_state"]), axis=-1)
        q_next = jnp.take_along_axis(self.q(target, b["next_state"]),
                                     a_star[:, None], axis=1)[:, 0]
        y = b["reward"] * self.p["reward_scale"] \
            + self.p["discount"] * jax.lax.stop_gradient(q_next)
        return jnp.mean(jnp.square(y - q_sa))

    def sgd(self, params, g, lr):
        return jax.tree.map(lambda w, gw: self.store(w - lr * gw), params, g)

    # -- meta stage ----------------------------------------------------
    def _meta_round(self, params, key):
        p = self.p
        tasks = p["meta_tasks"]
        ks = jax.random.split(key, 2 * len(tasks))
        sup = [self.episode_batches(ks[2 * j], params, t,
                                    p["inner_steps"])[0]
               for j, t in enumerate(tasks)]
        qry = [jax.tree.map(lambda x: x[0], self.episode_batches(
            ks[2 * j + 1], params, t, 1)[0]) for j, t in enumerate(tasks)]

        def task_loss(w, s, qb):
            def inner(phi, b):
                return self.sgd(phi, jax.grad(self.td_loss)(phi, w, b),
                                p["inner_lr"]), None

            phi = jax.lax.scan(inner, w, s)[0]
            # first order: the meta gradient is the query gradient at φ
            phi = jax.tree.map(lambda a, b_: jax.lax.stop_gradient(a - b_)
                               + b_, phi, w)
            return self.td_loss(phi, w, qb)

        def mean_loss(w):
            return sum(task_loss(w, s, qb) for s, qb in zip(sup, qry)) \
                / len(tasks)

        loss, g = jax.value_and_grad(mean_loss)(params)
        return self.sgd(params, g, p["outer_lr"]), loss

    def meta_train(self, key, rounds: int):
        """(initial weights, weights after ``rounds``, per-round losses)."""
        kinit, kdata = jax.random.split(key)
        w0 = jax.tree.map(self.store, self.model.init(kinit, self.c))
        w, losses = w0, []
        for _ in range(rounds):
            kdata, sk = jax.random.split(kdata)
            w, loss = self.meta_round(w, sk)
            losses.append(float(loss))
        return w0, w, losses

    # -- FL stage ------------------------------------------------------
    def _fl_round(self, task, stacked, key, flip_at):
        """One FL round; ``flip_at`` (robots,) takes each robot's
        runner-up action at that step of its episode (-1: none).
        Returns (mixed weights, greedy running reward, margins)."""
        p = self.p
        C = p["robots"]
        ks = jax.random.split(key, C + 1)
        target = jax.tree.map(lambda x: x[0], stacked)

        def local_step(w, b):
            g = jax.grad(self.td_loss)(w, target, b)
            gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
            s = jnp.minimum(1.0, p["fl_clip"] / jnp.maximum(gn, 1e-9))
            return jax.tree.map(lambda x, gx: self.store(
                x - p["fl_lr"] * s * gx), w, g), None

        def robot(w, k, flip):
            b, gap = self.episode_batches(k, w, task, p["fl_local_steps"],
                                          flip)
            return jax.lax.scan(local_step, w, b)[0], gap

        # one computation batched over the robots: on TPU the batched
        # Q-network products round as the program's do (see FL round
        # above), where one robot's alone would not
        stacked, gaps = jax.vmap(robot)(stacked, jnp.stack(ks[:C]), flip_at)
        mixed = jax.tree.map(lambda x: self.store(
            (self.rnd(self.mix) @ self.rnd(x.reshape(C, -1))
             ).reshape(x.shape)), stacked)
        w0 = jax.tree.map(lambda x: x[0], mixed)
        ev, _ = self.rollout(ks[C], w0, task, 0.0, p["eval_episodes"])
        disc = p["discount"] ** jnp.arange(ev["reward"].shape[-1])
        R = jnp.mean(jnp.sum(ev["reward"] * disc, axis=-1))
        return mixed, R, gaps

    def first_round(self, key, task: int, init, tie: float):
        """The first FL round of one task from ``init``, as its
        outcomes: [(weights, greedy running reward)] for the round as
        computed and, where robots' episodes took a greedy decision whose
        margin is under ``tie`` (rounding can then tip it either way),
        one more for each non-empty set of those robots, with each one's
        closest decision taken the other way. Keys are split as the
        program's chunk splits them (one split per round). Also returns
        the smallest margin."""
        C = self.p["robots"]
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (C,) + x.shape), init)
        _, sk = jax.random.split(key)
        mixed, R, gaps = self.fl_round(task, stacked, sk,
                                       jnp.full((C,), -1, jnp.int32))
        out = [(mixed, float(R))]
        gaps = np.asarray(gaps)
        near = [a for a in range(C) if gaps[a].min() < tie]
        for n in range(1, len(near) + 1):
            for robots in itertools.combinations(near, n):
                flip = np.full((C,), -1, np.int32)
                for a in robots:
                    flip[a] = gaps[a].argmin()
                mixed, R, _ = self.fl_round(task, stacked, sk,
                                            jnp.asarray(flip))
                out.append((mixed, float(R)))
        return out, float(gaps.min())
