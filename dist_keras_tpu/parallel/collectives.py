"""Collective helpers over pytrees.

This file is the TPU-native replacement for the reference's entire wire layer
(``distkeras/networking.py`` — length-prefixed pickle over TCP) and the
parameter-server commit/pull protocol (``distkeras/parameter_servers.py``):
weight exchange compiles into XLA collectives riding ICI instead of a
hub-and-spoke socket server on the driver.

All helpers are meant to be called *inside* ``shard_map``-decorated functions
where the named axis is bound.
"""

from __future__ import annotations

import jax
from jax import lax

from dist_keras_tpu.parallel.mesh import WORKER_AXIS


def tree_psum(tree, axis=WORKER_AXIS):
    """Sum a pytree across the axis — the 'everybody commits a delta'
    aggregate (parameter_servers.py:~240 handle_commit, all workers at
    once)."""
    return jax.tree.map(lambda x: lax.psum(x, axis), tree)


def tree_pmean(tree, axis=WORKER_AXIS):
    """Average a pytree across the axis — AveragingTrainer's merge
    (trainers.py:~190) as one fused collective."""
    return jax.tree.map(lambda x: lax.pmean(x, axis), tree)


def tree_pmean_sync(tree, axis=WORKER_AXIS):
    """Average floating leaves across the axis; ``pmax`` the rest.

    The merge algebra only makes sense for float weights.  Integer leaves
    (Keras seed-generator counters riding in a stateful model's params)
    advance in lockstep on every worker, so ``pmax`` returns their common
    value — and, unlike keeping the local copy, the result is typed
    axis-invariant, which scan carries declared replicated require.
    """
    import jax.numpy as jnp

    def _merge(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return lax.pmean(x, axis)
        return lax.pmax(x, axis)

    return jax.tree.map(_merge, tree)


def tree_all_gather(tree, axis=WORKER_AXIS):
    return jax.tree.map(lambda x: lax.all_gather(x, axis), tree)


def tree_ppermute(tree, perm, axis=WORKER_AXIS):
    return jax.tree.map(lambda x: lax.ppermute(x, axis, perm), tree)


def tree_pvary(tree, axis=WORKER_AXIS):
    """Mark a replicated pytree as device-varying along ``axis``.

    CRITICAL for per-worker local state inside shard_map: differentiating a
    worker-varying loss w.r.t. *replicated* params transposes the implicit
    replicated->varying promotion into a hidden ``psum`` — every "local"
    gradient step silently becomes a summed-all-workers step and the params
    stay replicated.  Casting the local copy to varying first keeps worker
    updates genuinely local; only explicit collectives then cross workers.
    """
    def _pvary(x):
        if axis in jax.typeof(x).vma:  # already varying: pcast would reject
            return x
        return lax.pcast(x, (axis,), to="varying")

    return jax.tree.map(_pvary, tree)


def axis_index(axis=WORKER_AXIS):
    return lax.axis_index(axis)


def axis_size(axis=WORKER_AXIS):
    return lax.axis_size(axis)


class AsyncMerge:
    """Double-buffered host-level async pytree merge (``DK_COMM_OVERLAP``
    machinery, round 19).

    The blocked pattern at a window boundary is::

        merged = merge_fn(center, delta)
        jax.block_until_ready(merged)      # the boundary blocking wall

    ``AsyncMerge`` splits that into :meth:`submit` (dispatch the jitted
    merge — ``jax.jit`` dispatch is asynchronous, so the host returns as
    soon as the work is enqueued and the merge executes under whatever
    the caller dispatches next) and :meth:`wait` (the deferred
    ``block_until_ready``), the same trick ``data/feed.py``'s ChunkFeed
    plays for H2D.  At most ONE merge is ever in flight — a second
    :meth:`submit` first waits out the previous one, which bounds device
    memory at two result buffers exactly like the feed's two-chunk
    residency rule.

    Perf attribution: the submit (enqueue) wall lands in the
    ``perf.phase.comm_overlap`` histogram and the wait (blocking) wall
    in ``perf.phase.comm_blocked`` — the split that makes an overlap win
    attributable (a blocked merge pays its whole wall in
    ``comm_blocked``; an overlapped one pays enqueue in ``comm_overlap``
    and only the un-hidden remainder in ``comm_blocked``).

    ``donate_argnums`` forwards to ``jax.jit`` so the delta buffers can
    be donated into the merge (the accumulator never holds delta +
    merged copies at once); the default donates nothing — callers that
    reuse their arguments stay safe.

    Mixed-dtype and zero-size leaves pass through whatever ``merge_fn``
    does with them — the machinery itself never touches leaf values
    (covered by tests/test_speed.py).
    """

    def __init__(self, merge_fn, donate_argnums=()):
        self._fn = jax.jit(merge_fn, donate_argnums=donate_argnums)
        self._inflight = None     # result pytree of the dispatched merge
        self.submits = 0
        self.waits = 0

    @property
    def pending(self):
        """True while a dispatched merge has not been waited yet."""
        return self._inflight is not None

    def submit(self, *args):
        """Dispatch ``merge_fn(*args)`` asynchronously; -> self.

        If a previous merge is still in flight it is waited FIRST (the
        double-buffer bound).  The injectable ``comm.merge`` fault point
        fires here, so the chaos schedule can kill or delay exactly the
        Nth boundary merge."""
        from dist_keras_tpu.observability import perf
        from dist_keras_tpu.resilience.faults import fault_point

        if self._inflight is not None:
            # dklint: ignore[unbounded-wait] AsyncMerge.wait is a jax
            # block_until_ready on an already-dispatched XLA program
            # (which terminates), not a thread/event wait
            self.wait()
        fault_point("comm.merge")
        with perf.phase("comm_overlap"):
            self._inflight = self._fn(*args)
        self.submits += 1
        return self

    def wait(self):
        """Block until the in-flight merge's buffers are ready; -> the
        merged pytree (or the LAST result again when nothing is in
        flight — callers may wait defensively at shutdown)."""
        from dist_keras_tpu.observability import perf

        result = self._inflight
        if result is None:
            return self._last()
        with perf.phase("comm_blocked"):
            jax.block_until_ready(result)
        self._inflight = None
        self._result = result
        self.waits += 1
        return result

    def _last(self):
        return getattr(self, "_result", None)
