"""DynSGD — staleness-scaled updates, with *real* staleness under SPMD.

Reference semantics (workers.py:~530 + parameter_servers.py:~280): each
worker commits ``{delta, last_seen_update}`` and the PS scales the commit by
``1/(staleness+1)`` where ``staleness = num_updates - last_seen_update``.

Staleness is meaningless if all workers commit in lockstep, so a plain
windowed port would degenerate to DOWNPOUR (SURVEY.md §7 hard part #1).
Instead we *stagger* the commit schedule: worker ``i`` commits every
``communication_window`` steps at phase offset ``i*W/N``.  Commits from
different workers then land at different global steps, the center variable
moves between a worker's pull and its next commit, and the DynSGD staleness
counter measures exactly what it does in the reference — how many center
updates the worker missed.  The commit itself is a masked ``psum`` executed
every step (zero contribution from non-committing workers), so the whole
schedule stays one compiled ``lax.scan`` with no data-dependent control flow.

Round 4: the dispatch is STEP-granular through the shared ``ChunkRunner``
(``trainers/chunking.py``), which buys DynSGD the two capabilities the
windowed family got in rounds 3-4 — ``checkpoint_every_windows`` saves
mid-epoch (the staggered schedule has the most state to lose on
preemption: pulled snapshots, staleness counters, the in-epoch rng are
all in the payload and resume bit-exactly) and
``stream_chunk_windows``/``max_resident_bytes`` stream the data through
the double-buffered ChunkFeed, so an epoch no longer has to fit in HBM
(the reference's partition-iterator property, workers.py:~60).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from dist_keras_tpu.parallel.collectives import tree_psum, tree_pvary
from dist_keras_tpu.parallel.mesh import WORKER_AXIS
from dist_keras_tpu.comm import backend as comm
from dist_keras_tpu.trainers.chunking import run_chunked
from dist_keras_tpu.trainers.windowed import AsynchronousDistributedTrainer
from dist_keras_tpu.utils.pytree import tree_merge_floats, tree_zeros_like


def _make_body(step, window, num_workers, steps_per_epoch, T, streamed):
    """Chunked scan body over a flat range of GLOBAL steps [t0, t0+T).

    All per-worker state (pulled snapshot, local replica, optimizer
    state, staleness counters, in-epoch rng) is carried in/out, so the
    staggered-staleness schedule survives chunk boundaries at ANY step —
    including mid-epoch checkpoint cuts and streaming data-chunk cuts.
    The epoch's rng stream starts at its first step (``t % spe == 0``)
    and is carried through the rest, so a mid-epoch resume replays the
    identical stream (same construction as windowed.build_chunk).
    """
    def body(center, pulled, local, opt_state, last_seen, global_count,
             rng, xs, ys, key, t0):
        xs, ys = xs[0], ys[0]  # (spe | T, batch, ...)
        widx = jax.lax.axis_index(WORKER_AXIS)
        phase = (widx * window) // num_workers  # staggered commit schedule

        # per-worker carry arrives stacked (1, ...) on the worker shard
        unstack = lambda t: t[0]  # noqa: E731
        pulled = jax.tree.map(unstack, pulled)
        local = jax.tree.map(unstack, local)
        opt_state = jax.tree.map(unstack, opt_state)
        last_seen = unstack(last_seen)
        rng = rng[0]

        def one_step(carry, inp):
            (center, pulled, local, opt_state, rng,
             last_seen, global_count) = carry
            t, x, y = inp
            e, si = t // steps_per_epoch, t % steps_per_epoch
            fresh = tree_pvary(jax.random.fold_in(
                jax.random.fold_in(key, e), widx))
            rng = jnp.where(si == 0, fresh, rng)
            (local, opt_state, rng), loss = step(
                (local, opt_state, rng), (x, y))

            commit = ((t + 1 + phase) % window == 0)
            m = commit.astype(jnp.float32)
            staleness = (global_count - last_seen).astype(jnp.float32)
            scale = m / (staleness + 1.0)

            # integer leaves (Keras seed-generator counters) are RNG
            # state, not weights: zero contribution, never pulled
            # (tree_merge_floats implements the exemption policy)
            contribution = tree_merge_floats(
                jax.tree.map(lambda l, p: scale * (l.astype(jnp.float32)
                                                   - p.astype(jnp.float32)),
                             local, pulled),
                tree_zeros_like(local))
            center = jax.tree.map(
                lambda c, d: (c + d).astype(c.dtype), center,
                tree_psum(contribution))
            global_count = global_count + jax.lax.psum(
                commit.astype(jnp.int32), WORKER_AXIS)
            # committing workers pull the fresh center
            local = tree_merge_floats(
                jax.tree.map(lambda l, c: jnp.where(commit, c, l),
                             local, center), local)
            pulled = tree_merge_floats(
                jax.tree.map(lambda p, c: jnp.where(commit, c, p),
                             pulled, center), pulled)
            last_seen = jnp.where(commit, global_count, last_seen)
            return (center, pulled, local, opt_state, rng,
                    last_seen, global_count), loss

        carry = (center, pulled, local, opt_state, rng,
                 last_seen, global_count)
        if streamed:
            carry, losses = jax.lax.scan(
                one_step, carry, (jnp.arange(T) + t0, xs, ys))
        else:
            def indexed(c, t):
                si = t % steps_per_epoch
                x = jax.lax.dynamic_index_in_dim(xs, si, 0, keepdims=False)
                y = jax.lax.dynamic_index_in_dim(ys, si, 0, keepdims=False)
                return one_step(c, (t, x, y))

            carry, losses = jax.lax.scan(
                indexed, carry, jnp.arange(T) + t0)
        (center, pulled, local, opt_state, rng,
         last_seen, global_count) = carry
        stack = lambda t: t[None]  # noqa: E731
        return (center, jax.tree.map(stack, pulled),
                jax.tree.map(stack, local), jax.tree.map(stack, opt_state),
                stack(last_seen), global_count, rng[None],
                losses[None])  # losses: (1, T)

    return body


class DynSGD(AsynchronousDistributedTrainer):
    """trainers.py:~700 / workers.py:~530; inherits the windowed family's
    checkpoint/streaming kwargs (cadences are counted in communication
    windows = ``communication_window`` steps)."""

    def merge(self, center, local):  # pragma: no cover - not windowed
        raise NotImplementedError(
            "DynSGD commits per-step with staggered phases; it does not "
            "use the windowed merge hook")

    def train(self, dataset, shuffle=False):
        model, loss_fn, tx = self._resolve()
        if shuffle:
            dataset = dataset.shuffle(seed=self.seed)
        xs, ys = self._shards(dataset)  # (workers, steps, batch, ...)
        spe = xs.shape[1]  # steps per epoch
        total_t = self.num_epoch * spe
        W = self.communication_window
        mesh = self.mesh
        step, opt_init = self._make_step(model, loss_fn, tx)
        key = jax.random.PRNGKey(self.seed)

        def build_chunk(T, streamed=False):
            body = _make_body(step, W, self.num_workers, spe, T, streamed)
            return jax.jit(shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(WORKER_AXIS), P(WORKER_AXIS),
                          P(WORKER_AXIS), P(WORKER_AXIS), P(),
                          P(WORKER_AXIS), P(WORKER_AXIS), P(WORKER_AXIS),
                          P(), P()),
                out_specs=(P(), P(WORKER_AXIS), P(WORKER_AXIS),
                           P(WORKER_AXIS), P(WORKER_AXIS), P(),
                           P(WORKER_AXIS), P(WORKER_AXIS)),
            ))

        center = model.params
        pulled = self._stack_workers(center)
        local = self._stack_workers(center)
        opt_state = self._stack_workers(opt_init(center))
        last_seen = self._stack_workers(jnp.zeros((), jnp.int32))
        global_count = jnp.zeros((), jnp.int32)
        rng = self._stack_workers(jnp.zeros((2,), jnp.uint32))
        template = {"center": center, "pulled": pulled, "local": local,
                    "opt_state": opt_state, "last_seen": last_seen,
                    "global_count": global_count, "rng": rng}
        start_t, restored = self._maybe_resume(
            template,
            incompatible_hint=(
                "if this checkpoint predates step-granular DynSGD "
                "training state (round 3: no 'rng' leaf, step counted "
                "epochs not steps), restart training or point "
                "checkpoint_dir at a fresh directory"))
        if restored is not None:
            if "rng" not in restored:
                raise ValueError(
                    "checkpoint predates step-granular DynSGD training "
                    "state (no 'rng' leaf; its step counts epochs, not "
                    "steps) — restart training or point checkpoint_dir "
                    "at a fresh directory")
            center = restored["center"]
            pulled = restored["pulled"]
            local = restored["local"]
            opt_state = restored["opt_state"]
            last_seen = restored["last_seen"]
            global_count = restored["global_count"]
            rng = restored["rng"]

        def dispatch(i, T, steps_done, data):
            nonlocal center, pulled, local, opt_state, last_seen, \
                global_count, rng
            streamed = self._streamed
            fn = self._compiled(
                lambda: build_chunk(T, streamed=streamed),
                extra_key=("stream", T, spe) if streamed else (T, spe))
            (center, pulled, local, opt_state, last_seen, global_count,
             rng, losses) = fn(center, pulled, local, opt_state,
                               last_seen, global_count, rng, *data,
                               key, jnp.int32(steps_done))
            return losses

        # cadence kwargs stay in window units for API parity with the
        # family; the dispatch machinery runs in STEP units.  History
        # entries are (workers, T) per chunk; whole-epoch runs reshape
        # to (workers, epochs, steps), mid-epoch resumes stay flat.
        cadence = (self.checkpoint_every_windows * W
                   if self.checkpoint_every_windows
                   else self.checkpoint_every * spe
                   if self.checkpoint_every else None)
        history = run_chunked(
            self, xs, ys, start=start_t, total=total_t, per_epoch=spe,
            stream_units=(self.stream_chunk_windows * W
                          if self.stream_chunk_windows else None),
            cadence=cadence,
            samples_per_unit=self.num_workers * self.batch_size,
            dispatch=dispatch, sync_ref=lambda: center,
            state_fn=lambda: {"center": center, "pulled": pulled,
                              "local": local, "opt_state": opt_state,
                              "last_seen": last_seen,
                              "global_count": global_count, "rng": rng},
            carry_leaves=(center, pulled, local, opt_state, last_seen,
                          rng),
            fetch_global=comm.fetch_global)
        return self._finalize(center, history)
