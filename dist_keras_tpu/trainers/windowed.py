"""Windowed-commit machinery + the async optimizer family.

The reference's asynchronous parameter-server optimizers (workers.py:~230-600
+ parameter_servers.py:~200-330) share one skeleton: train locally for
``communication_window`` batches, then exchange an update with the center
variable.  On lockstep SPMD hardware the exchange compiles to one collective:

- DOWNPOUR  (workers.py:~230): commit the accumulated weight delta; pull.
  -> center += psum(local - center); local = center.
- ADAG      (workers.py:~300): DOWNPOUR with the delta normalised by the
  window length before commit.
  -> center += psum((local - center) / W).
- AEASGD    (workers.py:~370): elastic averaging; every tau steps the worker
  moves toward the center by E = alpha*(theta_i - center) and commits E.
  -> E_i = alpha*(local - center); local -= E_i; center += psum(E_i).
- EAMSGD    (workers.py:~450): AEASGD + Nesterov momentum on the local
  update (handled by wrapping the worker optimizer with optax.trace).

Mechanism-vs-behavior note (SURVEY.md §7 "hard parts"): in the reference
these commits are *asynchronous* and interleave arbitrarily; under SPMD all
workers commit at the same step, which reproduces the communication pattern
and the update algebra but with zero staleness.  DynSGD, whose whole point is
staleness, gets a genuinely staggered emulation in ``dynsgd.py``.

Everything here runs inside one jitted ``shard_map``: outer ``lax.scan`` over
windows, inner ``lax.scan`` over the window's batches, one pytree collective
per window riding ICI.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from dist_keras_tpu.parallel.collectives import (
    AsyncMerge,
    tree_psum,
    tree_pvary,
)
from dist_keras_tpu.parallel.mesh import WORKER_AXIS
from dist_keras_tpu.comm import backend as comm
from dist_keras_tpu.trainers.base import DistributedTrainer
from dist_keras_tpu.trainers.chunking import init_streaming, run_chunked
from dist_keras_tpu.utils import knobs
from dist_keras_tpu.utils.pytree import (
    tree_add,
    tree_merge_floats,
    tree_scale,
    tree_sub,
    tree_zeros_like,
)


class AsynchronousDistributedTrainer(DistributedTrainer):
    """Base of the windowed family (trainers.py:~420).

    ``parallelism_factor`` (trainers.py:~310) is accepted for parity but is
    a deliberate no-op: the reference oversubscribes Spark partitions so a
    straggling executor can be load-balanced, a failure mode lockstep SPMD
    does not have — every worker is one mesh slot and ``worker_shards``
    already deals all rows evenly across workers.
    """

    def __init__(self, keras_model, num_workers=2, communication_window=5,
                 parallelism_factor=1, checkpoint_every_windows=None,
                 stream_chunk_windows=None, max_resident_bytes=None,
                 comm_overlap=None, **kw):
        super().__init__(keras_model, num_workers=num_workers, **kw)
        self.communication_window = int(communication_window)
        self.parallelism_factor = int(parallelism_factor)
        # overlapped window collectives (round 19): None defers to the
        # DK_COMM_OVERLAP knob at train() time (launcher-export wins),
        # an explicit bool pins it per trainer
        self.comm_overlap = comm_overlap
        self._overlap = False  # resolved per train() call
        # window-granular checkpoint cadence: a preemption then loses at
        # most ``checkpoint_every_windows`` communication windows, not a
        # whole epoch (the reference's big-DataFrame case,
        # trainers.py:~360, can make one epoch arbitrarily long)
        self.checkpoint_every_windows = (
            int(checkpoint_every_windows) if checkpoint_every_windows
            else None)
        if self.checkpoint_every_windows and not self.checkpoint_dir:
            raise ValueError(
                "checkpoint_every_windows requires checkpoint_dir")
        # ---- streaming input pipeline (the reference's partition-iterator
        # property, workers.py:~60: an epoch never has to fit on-device).
        # stream_chunk_windows=C streams the data C windows per dispatch
        # through a double-buffered ChunkFeed (<= 2 chunks ever resident);
        # max_resident_bytes=B auto-enables streaming whenever the epoch
        # tensor would exceed B bytes of device memory, sizing C so two
        # in-flight chunks fit inside B.  Default (both None) keeps the
        # round-1 whole-run-resident fast path.
        init_streaming(self, stream_chunk_windows, max_resident_bytes)

    def _cache_extras(self):
        # the per-chunk epoch count is appended via _compiled(extra_key=)
        # (the overlap flag changes the scan carry STRUCTURE, so it must
        # key the executable cache too)
        return super()._cache_extras() + (self.communication_window,
                                          self._overlap)

    # --- strategy hooks -------------------------------------------------
    def wrap_optimizer(self, tx):
        return tx

    def merge(self, center, local):
        """(center, local) -> (center', local'), called once per window with
        the worker axis bound.  The BLOCKED merge — kept verbatim so
        ``DK_COMM_OVERLAP=0`` compiles byte-identical window bodies to
        every round before the overlap existed."""
        raise NotImplementedError

    # --- overlap decomposition (DK_COMM_OVERLAP) ------------------------
    # The blocked ``merge`` is algebraically  commit -> psum -> apply ->
    # absorb  with the apply consumed IMMEDIATELY.  The overlapped path
    # splits those so the psum's result has no consumer until the NEXT
    # window boundary (the one-window-stale center — exactly the paper's
    # async commit model, where a worker's commit is "in flight" while
    # it already trains on): XLA is then free to run the collective
    # concurrently with window k+1's local steps, and the host-level
    # ``AsyncMerge`` flush at the end of train() plays the same trick
    # for the final pending commit.
    def commit(self, center, local):
        """The worker's window commit delta (pre-psum), computed against
        the center this window's local steps started from."""
        raise NotImplementedError(
            f"{type(self).__name__} defines no commit/absorb overlap "
            "decomposition — DK_COMM_OVERLAP needs both (or run this "
            "trainer with the blocked merge: comm_overlap=False)")

    def absorb(self, center, local, delta):
        """The worker-local post-commit update: ``center`` is the
        (one-window-stale) merged center the worker syncs to, ``delta``
        its OWN just-committed delta (pre-psum)."""
        raise NotImplementedError(
            f"{type(self).__name__} defines no commit/absorb overlap "
            "decomposition — DK_COMM_OVERLAP needs both (or run this "
            "trainer with the blocked merge: comm_overlap=False)")

    def _ckpt_cadence_windows(self, wpe):
        """Save cadence in WINDOW units — the single source both the
        chunk plan and the save decision use, so dispatch boundaries and
        checkpoint writes can never desynchronize."""
        if self.checkpoint_every_windows:
            return self.checkpoint_every_windows
        if self.checkpoint_every:
            return self.checkpoint_every * wpe
        return None

    # --- shared training loop ------------------------------------------
    def train(self, dataset, shuffle=False):
        """The whole run is one flat ``lax.scan`` over communication
        windows on device-resident shard tensors (one H2D transfer).
        With no hooks requested all ``num_epoch * windows_per_epoch``
        windows are ONE dispatch; ``checkpoint_every``/``callbacks``
        chunk at epoch boundaries and ``checkpoint_every_windows`` at
        WINDOW boundaries — mid-epoch — with all worker state (local
        replicas, optimizer state, the in-epoch rng) carried across
        chunks, so a preemption loses at most one cadence of windows.
        The reference analogue: a long-lived worker's state persists
        across its entire partition pass (workers.py:~150).

        Metrics cadence: per-epoch metrics/callbacks fire at dispatch
        boundaries whose window count is an exact epoch multiple.  With
        ``checkpoint_every_windows`` not dividing windows-per-epoch and
        no callbacks registered, several epochs can collapse into one
        metrics entry (nothing is lost — accumulators carry across and
        the final emit always fires); register any callback to force
        true epoch-boundary chunking."""
        model, loss_fn, tx = self._resolve()
        tx = self.wrap_optimizer(tx)
        # overlapped window collectives: resolved per call so a
        # launcher-exported DK_COMM_OVERLAP wins regardless of when the
        # trainer was constructed (the knobs-registry contract)
        overlap = self._overlap = bool(
            self.comm_overlap if self.comm_overlap is not None
            else knobs.get("DK_COMM_OVERLAP"))
        if shuffle:
            dataset = dataset.shuffle(seed=self.seed)
        xs, ys = self._shards(dataset)  # (workers, steps, batch, ...)

        W = min(self.communication_window, xs.shape[1])
        wpe = xs.shape[1] // W  # windows per epoch
        # Whole windows only, cut per epoch (remainder dropped every epoch,
        # like the reference's fixed mini-batching) — warn so silent data
        # loss / window shrinkage is visible.
        if W < self.communication_window:
            warnings.warn(
                f"communication_window={self.communication_window} > "
                f"{xs.shape[1]} steps per worker per epoch; effective "
                f"window shrunk to {W}", stacklevel=2)
        dropped = xs.shape[1] - wpe * W
        if dropped:
            warnings.warn(
                f"dropping {dropped} trailing step(s) per worker per epoch "
                f"(not a whole communication window)", stacklevel=2)
        # leading axis is LOCAL workers (== num_workers single-process;
        # this host's slice when multi-host, see base._shards)
        xs = xs[:, :wpe * W].reshape(xs.shape[0], wpe, W, *xs.shape[2:])
        ys = ys[:, :wpe * W].reshape(ys.shape[0], wpe, W, *ys.shape[2:])
        total_w = self.num_epoch * wpe

        mesh = self.mesh
        merge = self.merge
        step, opt_init = self._make_step(model, loss_fn, tx)

        def build_chunk(K, streamed=False):
            """K-window dispatch.  Resident mode: the whole (wpe, W, ...)
            epoch tensor is an argument and windows are selected by
            dynamic index modulo wpe (data reused across epochs inside
            one dispatch).  Streaming mode: ONLY the chunk's (K, W, ...)
            slice arrives and the scan consumes it directly — identical
            window algebra, so the two paths are bit-equal on the same
            data (asserted in tests/test_streaming_feed.py).

            Under ``overlap`` (DK_COMM_OVERLAP) the carry grows a
            replicated ``pending`` leaf set — the previous window's
            psum'd commit, applied ONE window late.  The psum issued at
            boundary k has no consumer until boundary k+1, so it
            carries no data dependency into window k+1's local steps
            and the compiler overlaps the collective with them; the
            algebra is the paper's async model (every worker trains on
            a center missing exactly the cluster's last window of
            commits).  ``pending`` rides the scan carry, the chunk
            carry AND the checkpoint state, so the staleness semantics
            are chunk-plan-invariant (gates.py --speed-only pins a
            per-window-dispatched run bit-equal to the fused one)."""
            def window(carry, g, xw, yw, widx, key):
                if overlap:
                    center, pending, local, opt_state, rng = carry
                else:
                    center, local, opt_state, rng = carry
                e, wi = g // wpe, g % wpe
                # the epoch's rng stream starts at its first window
                # and is CARRIED through the rest (and across chunk
                # boundaries via the checkpointed rng), so a
                # mid-epoch resume replays the identical stream
                fresh = tree_pvary(jax.random.fold_in(
                    jax.random.fold_in(key, e), widx))
                rng = jnp.where(wi == 0, fresh, rng)
                (local, opt_state, rng), losses = jax.lax.scan(
                    step, (local, opt_state, rng), (xw, yw))
                if overlap:
                    # deferred merge: commit this window's delta, apply
                    # the PREVIOUS window's summed commit, hand the new
                    # psum to the next boundary.  Integer leaves (Keras
                    # seed-generator counters) are RNG state, not
                    # weights: exempt everywhere, like the blocked path.
                    delta = self.commit(center, local)
                    center = tree_merge_floats(
                        tree_add(center, pending), center)
                    local = tree_merge_floats(
                        self.absorb(center, local, delta), local)
                    local = tree_pvary(local)
                    pending = tree_merge_floats(tree_psum(delta),
                                                pending)
                    return (center, pending, local, opt_state,
                            rng), losses
                new_center, new_local = merge(center, local)
                # integer leaves (Keras seed-generator counters) are
                # RNG state, not weights: exempt from merge algebra
                center = tree_merge_floats(new_center, center)
                local = tree_merge_floats(new_local, local)
                # merges that reset local to the (replicated) center
                # must hand back a varying-typed local for next window
                local = tree_pvary(local)
                return (center, local, opt_state, rng), losses

            def body(*args):
                if overlap:
                    (center, pending, local, opt_state, rng, xs, ys,
                     key, g0) = args
                else:
                    center, local, opt_state, rng, xs, ys, key, g0 = args
                xs, ys = xs[0], ys[0]  # (wpe | K, W, batch, ...)
                widx = jax.lax.axis_index(WORKER_AXIS)
                # carry state arrives stacked (1, ...) per worker shard
                local = jax.tree.map(lambda t: t[0], local)
                opt_state = jax.tree.map(lambda t: t[0], opt_state)
                rng = rng[0]

                carry = ((center, pending, local, opt_state, rng)
                         if overlap else (center, local, opt_state, rng))
                if streamed:
                    carry, losses = jax.lax.scan(
                        lambda c, inp: window(c, *inp, widx, key), carry,
                        (jnp.arange(K) + g0, xs, ys))
                else:
                    def indexed(c, g):
                        wi = g % wpe
                        xw = jax.lax.dynamic_index_in_dim(
                            xs, wi, 0, keepdims=False)
                        yw = jax.lax.dynamic_index_in_dim(
                            ys, wi, 0, keepdims=False)
                        return window(c, g, xw, yw, widx, key)

                    carry, losses = jax.lax.scan(
                        indexed, carry, jnp.arange(K) + g0)
                stack = lambda t: t[None]  # noqa: E731
                if overlap:
                    center, pending, local, opt_state, rng = carry
                    return (center, pending, jax.tree.map(stack, local),
                            jax.tree.map(stack, opt_state), rng[None],
                            losses[None])
                center, local, opt_state, rng = carry
                return (center, jax.tree.map(stack, local),
                        jax.tree.map(stack, opt_state), rng[None],
                        losses[None])  # losses: (1, K, W)

            rep = (P(),) if overlap else ()  # pending: replicated
            return jax.jit(shard_map(
                body, mesh=mesh,
                in_specs=(P(), *rep, P(WORKER_AXIS), P(WORKER_AXIS),
                          P(WORKER_AXIS), P(WORKER_AXIS), P(WORKER_AXIS),
                          P(), P()),
                out_specs=(P(), *rep, P(WORKER_AXIS), P(WORKER_AXIS),
                           P(WORKER_AXIS), P(WORKER_AXIS)),
            ))

        # initial carry (stacked per worker on the leading axis)
        center = model.params
        local = self._stack_workers(center)
        opt_state = self._stack_workers(opt_init(center))
        rng = self._stack_workers(jnp.zeros((2,), jnp.uint32))
        # the overlap carry: the previous window's psum'd commit, not
        # yet applied (zeros before the first boundary — nothing is in
        # flight at window 0)
        pending = tree_zeros_like(center) if overlap else None
        template = {"center": center, "local": local,
                    "opt_state": opt_state, "rng": rng}
        if overlap:
            template["pending"] = pending
        start_w, restored = self._maybe_resume(
            template,
            incompatible_hint=(
                "if this checkpoint predates window-granular training "
                "state (round 2: no 'rng' leaf, step counted epochs not "
                "windows), restart training or point checkpoint_dir at "
                "a fresh directory; if it carries a 'pending' leaf the "
                "run was overlapped — resume with DK_COMM_OVERLAP=1"))
        if restored is not None:
            if "rng" not in restored:
                raise ValueError(
                    "checkpoint predates window-granular training state "
                    "(no 'rng' leaf; its step counts epochs, not "
                    "windows) — restart training or point "
                    "checkpoint_dir at a fresh directory")
            if "pending" in restored and not overlap:
                raise ValueError(
                    "checkpoint carries an in-flight overlapped window "
                    "commit (a 'pending' leaf: it was written under "
                    "DK_COMM_OVERLAP=1) — resume with DK_COMM_OVERLAP=1 "
                    "so the commit lands, or restart from a fresh "
                    "checkpoint_dir")
            center = restored["center"]
            local = restored["local"]
            opt_state = restored["opt_state"]
            rng = restored["rng"]
            if overlap:
                # a blocked-era checkpoint resumes into an overlapped
                # run with nothing in flight — semantically the run's
                # first boundary simply applies a zero commit
                pending = restored.get("pending", pending)

        key = jax.random.PRNGKey(self.seed)

        def dispatch(i, K, windows_done, data):
            nonlocal center, pending, local, opt_state, rng
            if self._streamed:
                fn = self._compiled(lambda: build_chunk(K, streamed=True),
                                    extra_key=("stream", K, wpe))
            else:
                fn = self._compiled(lambda: build_chunk(K),
                                    extra_key=(K, wpe))
            if overlap:
                center, pending, local, opt_state, rng, losses = fn(
                    center, pending, local, opt_state, rng, *data, key,
                    jnp.int32(windows_done))
            else:
                center, local, opt_state, rng, losses = fn(
                    center, local, opt_state, rng, *data, key,
                    jnp.int32(windows_done))
            return losses

        def state_fn():
            state = {"center": center, "local": local,
                     "opt_state": opt_state, "rng": rng}
            if overlap:
                state["pending"] = pending
            return state

        carry_leaves = ((center, pending, local, opt_state, rng)
                        if overlap else (center, local, opt_state, rng))
        # history entries are (workers, K, W) per chunk; run_chunked
        # reshapes whole-epoch runs to the round-2 get_history contract
        # (workers, epochs, windows, W) — a run RESUMED mid-epoch stays
        # (workers, windows, W)
        history = run_chunked(
            self, xs, ys, start=start_w, total=total_w, per_epoch=wpe,
            stream_units=self.stream_chunk_windows,
            cadence=self._ckpt_cadence_windows(wpe),
            samples_per_unit=self.num_workers * W * self.batch_size,
            dispatch=dispatch, sync_ref=lambda: center,
            state_fn=state_fn,
            carry_leaves=carry_leaves,
            fetch_global=comm.fetch_global)
        if overlap:
            # flush the LAST window's in-flight commit so the returned
            # center includes every worker's final delta — the host-
            # level half of the double buffer (AsyncMerge: async submit,
            # deferred block_until_ready; here the wait is immediate
            # because training is over, but the enqueue/blocking walls
            # still land in the comm_overlap/comm_blocked split)
            flush = AsyncMerge(
                lambda c, p: tree_merge_floats(tree_add(c, p), c))
            # dklint: ignore[unbounded-wait] block_until_ready on the
            # just-dispatched flush (an XLA program, which terminates),
            # not a thread/event wait
            center = flush.submit(center, pending).wait()
        return self._finalize(center, history)


class DOWNPOUR(AsynchronousDistributedTrainer):
    """trainers.py:~470 / workers.py:~230."""

    def __init__(self, keras_model, communication_window=5, **kw):
        super().__init__(keras_model,
                         communication_window=communication_window, **kw)

    def merge(self, center, local):
        delta = tree_sub(local, center)
        center = tree_add(center, tree_psum(delta))
        return center, center

    def commit(self, center, local):
        return tree_sub(local, center)

    def absorb(self, center, local, delta):
        # DOWNPOUR pulls the center after its commit; overlapped, the
        # pulled center is one window stale (the commit is in flight)
        return center


class ADAG(AsynchronousDistributedTrainer):
    """Accumulated-gradient normalisation (trainers.py:~530,
    workers.py:~300): the window's accumulated delta is divided by the
    window length before the commit."""

    def __init__(self, keras_model, communication_window=12, **kw):
        super().__init__(keras_model,
                         communication_window=communication_window, **kw)

    def merge(self, center, local):
        delta = tree_scale(tree_sub(local, center),
                           1.0 / self.communication_window)
        center = tree_add(center, tree_psum(delta))
        return center, center

    def commit(self, center, local):
        return tree_scale(tree_sub(local, center),
                          1.0 / self.communication_window)

    def absorb(self, center, local, delta):
        return center


class AEASGD(AsynchronousDistributedTrainer):
    """Asynchronous elastic averaging SGD (trainers.py:~590,
    workers.py:~370). alpha = learning_rate * rho."""

    def __init__(self, keras_model, communication_window=32, rho=5.0,
                 learning_rate=0.1, **kw):
        super().__init__(keras_model,
                         communication_window=communication_window, **kw)
        self.rho = float(rho)
        self.learning_rate = float(learning_rate)

    def _cache_extras(self):
        return super()._cache_extras() + (self.rho, self.learning_rate)

    def merge(self, center, local):
        alpha = self.learning_rate * self.rho
        elastic = tree_scale(tree_sub(local, center), alpha)
        local = tree_sub(local, elastic)
        center = tree_add(center, tree_psum(elastic))
        return center, local

    def commit(self, center, local):
        alpha = self.learning_rate * self.rho
        return tree_scale(tree_sub(local, center), alpha)

    def absorb(self, center, local, delta):
        # the elastic force moves the worker toward the center it
        # MEASURED against (one window stale under overlap); the
        # worker keeps its own replica, unlike the pull-based family
        return tree_sub(local, delta)


class EAMSGD(AEASGD):
    """AEASGD + Nesterov momentum on the local update (trainers.py:~650,
    workers.py:~450): the worker optimizer's updates go through a Nesterov
    momentum trace."""

    def __init__(self, keras_model, momentum=0.9, **kw):
        super().__init__(keras_model, **kw)
        self.momentum = float(momentum)

    def _cache_extras(self):
        return super()._cache_extras() + (self.momentum,)

    def wrap_optimizer(self, tx):
        return optax.chain(
            tx, optax.trace(decay=self.momentum, nesterov=True))
