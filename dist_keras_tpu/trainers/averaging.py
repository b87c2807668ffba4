"""AveragingTrainer + EnsembleTrainer.

- ``AveragingTrainer`` (trainers.py:~160): per epoch, every worker trains a
  full pass over its shard, then weights are averaged.  The reference
  collects weight lists to the driver and numpy-means them
  (trainers.py:~190); here the merge is one fused ``lax.pmean`` over the ICI
  mesh inside the compiled epoch loop — no host round-trip at all.

- ``EnsembleTrainer`` (trainers.py:~230): N independent models trained in
  parallel (one per mesh slot), no merge; returns the list of models.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from dist_keras_tpu.parallel.collectives import tree_pmean_sync, tree_pvary
from dist_keras_tpu.parallel.mesh import WORKER_AXIS
from dist_keras_tpu.comm import backend as comm
from dist_keras_tpu.trainers.base import DistributedTrainer
from dist_keras_tpu.trainers.chunking import (
    reject_stale_checkpoint,
    run_chunked,
    scan_units,
)
from dist_keras_tpu.utils.sync import drain


class AveragingTrainer(DistributedTrainer):
    """Per-epoch weight averaging (trainers.py:~160).

    Round 4: the run is a flat scan over GLOBAL steps through the shared
    ``ChunkRunner`` — per-worker local state is re-initialized at each
    epoch's first step and ``pmean``-merged at its last (identical math
    to the round-3 per-epoch scan), which buys the same streaming feed as
    the rest of the family (``stream_chunk_steps`` counts chunks in
    STEPS here; ``max_resident_bytes`` auto-switches)."""

    def __init__(self, keras_model, stream_chunk_steps=None,
                 max_resident_bytes=None, **kw):
        super().__init__(keras_model, **kw)
        from dist_keras_tpu.trainers.chunking import init_streaming

        init_streaming(self, stream_chunk_steps, max_resident_bytes,
                       name="stream_chunk_steps")

    def train(self, dataset, shuffle=False):
        model, loss_fn, tx = self._resolve()
        if shuffle:
            dataset = dataset.shuffle(seed=self.seed)
        xs, ys = self._shards(dataset)  # (workers, steps, batch, ...)
        spe = xs.shape[1]
        total_t = self.num_epoch * spe
        mesh = self.mesh
        step, opt_init = self._make_step(model, loss_fn, tx)
        key = jax.random.PRNGKey(self.seed)

        def build_chunk(T, streamed=False):
            def body(params, local, opt_state, rng, xs, ys, key, t0):
                xs, ys = xs[0], ys[0]
                widx = jax.lax.axis_index(WORKER_AXIS)
                local = jax.tree.map(lambda a: a[0], local)
                opt_state = jax.tree.map(lambda a: a[0], opt_state)
                rng = rng[0]

                def one_step(carry, inp):
                    params, local, opt_state, rng = carry
                    t, x, y = inp
                    e, si = t // spe, t % spe
                    # epoch start: fresh local replica from the merged
                    # params, fresh worker optimizer (the reference
                    # recompiles per epoch, trainers.py:~170), fresh
                    # per-epoch rng — all carried thereafter so chunk
                    # boundaries at ANY step preserve the epoch math.
                    # si is worker-UNIFORM (derived from the replicated
                    # t), so lax.cond keeps the reset/merge work — incl.
                    # the cross-worker pmean — off the per-step hot path
                    # (a per-step where-form would all-reduce the full
                    # parameter tree EVERY step).
                    def reset(_):
                        fresh = tree_pvary(jax.random.fold_in(
                            jax.random.fold_in(key, e), widx))
                        pv = tree_pvary(params)
                        # pvary the fresh opt state too: its integer
                        # count leaf inits invariant, but the carried
                        # state is worker-sharded (varying) — cond
                        # branches must agree
                        return pv, tree_pvary(opt_init(pv)), fresh

                    local, opt_state, rng = jax.lax.cond(
                        si == 0, reset,
                        lambda _: (local, opt_state, rng), None)
                    (local, opt_state, rng), loss = step(
                        (local, opt_state, rng), (x, y))
                    # epoch end: pmean float weights; pmax integer
                    # leaves (lockstep seed counters) back to an
                    # axis-invariant type for the replicated carry
                    params = jax.lax.cond(
                        si == spe - 1,
                        lambda l: tree_pmean_sync(l),
                        lambda l: params, local)
                    return (params, local, opt_state, rng), loss

                (params, local, opt_state, rng), losses = scan_units(
                    one_step, (params, local, opt_state, rng),
                    xs, ys, T, t0, spe, streamed)
                stack = lambda t_: t_[None]  # noqa: E731
                return (params, jax.tree.map(stack, local),
                        jax.tree.map(stack, opt_state), rng[None],
                        losses[None])

            return jax.jit(shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(WORKER_AXIS), P(WORKER_AXIS),
                          P(WORKER_AXIS), P(WORKER_AXIS), P(WORKER_AXIS),
                          P(), P()),
                out_specs=(P(), P(WORKER_AXIS), P(WORKER_AXIS),
                           P(WORKER_AXIS), P(WORKER_AXIS)),
            ))

        params = model.params
        local = self._stack_workers(params)
        opt_state = self._stack_workers(opt_init(params))
        rng = self._stack_workers(jnp.zeros((2,), jnp.uint32))
        template = {"params": params, "local": local,
                    "opt_state": opt_state, "rng": rng}
        start_t, restored = self._maybe_resume(
            template,
            incompatible_hint=(
                "if this checkpoint predates step-granular "
                "AveragingTrainer state (round 3: params only, step "
                "counted epochs not steps), restart training or point "
                "checkpoint_dir at a fresh directory"))
        reject_stale_checkpoint(
            restored, "local", "AveragingTrainer",
            "params only; its step counts epochs, not steps")
        if restored is not None:
            params = restored["params"]
            local = restored["local"]
            opt_state = restored["opt_state"]
            rng = restored["rng"]

        def dispatch(i, T, steps_done, data):
            nonlocal params, local, opt_state, rng
            streamed = self._streamed
            fn = self._compiled(
                lambda: build_chunk(T, streamed=streamed),
                extra_key=("stream", T, spe) if streamed else (T, spe))
            params, local, opt_state, rng, losses = fn(
                params, local, opt_state, rng, *data, key,
                jnp.int32(steps_done))
            return losses

        cadence = (self.checkpoint_every * spe
                   if self.checkpoint_every else None)
        history = run_chunked(
            self, xs, ys, start=start_t, total=total_t, per_epoch=spe,
            stream_units=self.stream_chunk_steps, cadence=cadence,
            samples_per_unit=self.num_workers * self.batch_size,
            dispatch=dispatch, sync_ref=lambda: params,
            state_fn=lambda: {"params": params, "local": local,
                              "opt_state": opt_state, "rng": rng},
            carry_leaves=(params, local, opt_state, rng),
            fetch_global=comm.fetch_global)
        return self._finalize(params, history)


class EnsembleTrainer(DistributedTrainer):
    """Trains ``num_models`` independent replicas; returns a list of models
    (majority voting at predict time is up to the user, as upstream).

    ``num_models`` may exceed the device count (the reference trains any
    N over however many executors Spark has): models are laid out
    ``(mesh slots, models_per_slot)`` and each slot ``vmap``s its
    replicas — one compiled program regardless of the ratio.

    Round 5: the run is a flat scan over GLOBAL steps through the shared
    ``ChunkRunner`` — each step ``vmap``s the model step across the
    slot's replicas and the per-model per-epoch rng is re-derived at
    each epoch's first step (identical math to the round-4 nested
    epoch scan), which buys the ensemble the same streaming feed as
    every other trainer (``stream_chunk_steps`` counts chunks in STEPS;
    ``max_resident_bytes`` auto-switches): the last resident-only
    trainer is gone — an ensemble whose data exceeds HBM streams
    through the two-buffer ChunkFeed like the rest of the family
    (reference property: an epoch never has to fit in executor memory,
    workers.py:~60).

    ``get_history()`` shape contract (mirrors the windowed family's
    convention, see ``Trainer.get_history``): a run whose executed span
    covers WHOLE epochs returns ``(num_models, epochs,
    steps_per_epoch)``; a run RESUMED mid-epoch (its partial first epoch
    breaks the alignment) returns the flat ``(num_models, steps_run)``
    layout instead.  Callers that index history per epoch should check
    ``ndim``/the middle axis, or keep ``checkpoint_every`` in whole
    epochs so every resume stays epoch-aligned.  The flat layout is
    deliberate: padding the partial epoch would fabricate loss values,
    and splitting it would misalign epoch indices against an
    uninterrupted run's."""

    def __init__(self, keras_model, num_models=2, stream_chunk_steps=None,
                 max_resident_bytes=None, **kw):
        from dist_keras_tpu.parallel.mesh import num_available_devices
        from dist_keras_tpu.trainers.chunking import init_streaming

        self.num_models = int(num_models)
        slots = kw.pop("num_workers", None)
        if slots is None:
            # device count must come AFTER multi-host bring-up (querying
            # devices initializes the backend; see base.mesh ordering)
            comm.initialize()
            slots = min(self.num_models, num_available_devices())
        if self.num_models % slots:
            raise ValueError(
                f"num_models={num_models} must divide evenly over "
                f"{slots} mesh slots (pad num_models or pass "
                "num_workers=<divisor>)")
        super().__init__(keras_model, num_workers=slots, **kw)
        self.models_per_slot = self.num_models // slots
        init_streaming(self, stream_chunk_steps, max_resident_bytes,
                       name="stream_chunk_steps")

    def _cache_extras(self):
        # slots alone no longer distinguishes configs: equal slot counts
        # with different num_models bake different mps into the body
        return super()._cache_extras() + (self.num_models,)

    def train(self, dataset, shuffle=False):
        model, loss_fn, tx = self._resolve()
        if shuffle:
            dataset = dataset.shuffle(seed=self.seed)
        # one data shard per MODEL (reference: one partition per model);
        # leading axis regrouped (slots, steps, models_per_slot, ...) —
        # steps on axis 1 so the ChunkFeed's axis-1 spans slice the scan
        # axis while mps rides inside each chunk's put.  Multi-host:
        # host h owns mesh slots [lo, hi), hence global model ids
        # [lo*mps, hi*mps) — slice exactly those models' rows so the
        # concatenation over hosts equals the single-host deal.
        mps = self.models_per_slot
        mesh = self.mesh  # prime the mesh (and multi-host bring-up)
        if comm.is_multi_host():
            lo, hi = self._local_worker_range()
            model_range = (lo * mps, hi * mps)
        else:
            model_range = None
        xs, ys = dataset.worker_shards(
            self.num_models, self.batch_size,
            features_col=self.features_col, label_col=self.label_col,
            worker_range=model_range, dtype=self.data_dtype)

        def _regroup(a):
            # -1, not self.num_workers: on multi-host only this host's
            # models are materialized (leading dim = LOCAL slot count)
            a = a.reshape(-1, mps, *a.shape[1:])
            return np.ascontiguousarray(
                a.transpose(0, 2, 1, *range(3, a.ndim)))

        xs, ys = _regroup(xs), _regroup(ys)  # (slots, steps, mps, ...)
        spe = xs.shape[1]
        total_t = self.num_epoch * spe
        step, opt_init = self._make_step(model, loss_fn, tx)
        key = jax.random.PRNGKey(self.seed)

        def build_chunk(T, streamed=False):
            def body(params, opt_state, rng, xs, ys, key, t0):
                # carry arrives stacked (1, mps, ...) per slot
                xs, ys = xs[0], ys[0]
                params = jax.tree.map(lambda t: t[0], params)
                opt_state = jax.tree.map(lambda t: t[0], opt_state)
                rng = rng[0]
                slot = jax.lax.axis_index(WORKER_AXIS)
                midx = slot * mps + jnp.arange(mps)  # global model ids

                def one_step(carry, inp):
                    params, opt_state, rng = carry
                    t, x, y = inp  # x, y: (mps, batch, ...)
                    e, si = t // spe, t % spe

                    # epoch start: fresh per-model per-epoch rng —
                    # identical derivation to the round-4 nested epoch
                    # scan (fold_in(fold_in(key, model_id), epoch)), so
                    # chunk boundaries at ANY step preserve the epoch
                    # math.  si is worker-UNIFORM (derived from the
                    # replicated t): lax.cond keeps the re-derivation
                    # off the per-step hot path.
                    def reset(_):
                        return jax.vmap(lambda mi: tree_pvary(
                            jax.random.fold_in(
                                jax.random.fold_in(key, mi), e)))(midx)

                    rng = jax.lax.cond(si == 0, reset,
                                       lambda _: rng, None)

                    def per_model(p, o, r, xm, ym):
                        (p, o, r), loss = step((p, o, r), (xm, ym))
                        return p, o, r, loss

                    params, opt_state, rng, loss = jax.vmap(per_model)(
                        params, opt_state, rng, x, y)
                    return (params, opt_state, rng), loss

                (params, opt_state, rng), losses = scan_units(
                    one_step, (params, opt_state, rng),
                    xs, ys, T, t0, spe, streamed)
                stack = lambda t_: t_[None]  # noqa: E731
                # losses: (T, mps) -> (1, T, mps): run_chunked's unit
                # axis is 1, models ride behind it
                return (jax.tree.map(stack, params),
                        jax.tree.map(stack, opt_state), rng[None],
                        losses[None])

            return jax.jit(shard_map(
                body, mesh=mesh,
                in_specs=(P(WORKER_AXIS), P(WORKER_AXIS), P(WORKER_AXIS),
                          P(WORKER_AXIS), P(WORKER_AXIS), P(), P()),
                out_specs=(P(WORKER_AXIS), P(WORKER_AXIS), P(WORKER_AXIS),
                           P(WORKER_AXIS)),
            ))

        stacked = self._stack_workers(model.params, inner=(mps,))
        opt_state = self._stack_workers(opt_init(model.params),
                                        inner=(mps,))
        rng = self._stack_workers(jnp.zeros((2,), jnp.uint32),
                                  inner=(mps,))
        template = {"params": stacked, "opt_state": opt_state, "rng": rng}
        hint = ("if this checkpoint predates step-granular "
                "EnsembleTrainer state (round 4: no rng leaf, step "
                "counted epochs not steps), restart training or point "
                "checkpoint_dir at a fresh directory")
        start_t, restored = self._maybe_resume(
            template, incompatible_hint=hint)
        reject_stale_checkpoint(
            restored, "rng", "EnsembleTrainer",
            "no rng leaf; its step counts epochs, not steps")
        if restored is not None:
            stacked = restored["params"]
            opt_state = restored["opt_state"]
            rng = restored["rng"]

        def dispatch(i, T, steps_done, data):
            nonlocal stacked, opt_state, rng
            streamed = self._streamed
            fn = self._compiled(
                lambda: build_chunk(T, streamed=streamed),
                extra_key=("stream", T, spe) if streamed else (T, spe))
            stacked, opt_state, rng, losses = fn(
                stacked, opt_state, rng, *data, key,
                jnp.int32(steps_done))
            return losses

        cadence = (self.checkpoint_every * spe
                   if self.checkpoint_every else None)
        hist = run_chunked(
            self, xs, ys, start=start_t, total=total_t, per_epoch=spe,
            stream_units=self.stream_chunk_steps, cadence=cadence,
            samples_per_unit=self.num_models * self.batch_size,
            dispatch=dispatch, sync_ref=lambda: stacked,
            state_fn=lambda: {"params": stacked, "opt_state": opt_state,
                              "rng": rng},
            carry_leaves=(stacked, opt_state, rng),
            fetch_global=comm.fetch_global)
        # (slots, epochs, spe, mps) -> (num_models, epochs, spe); a
        # mid-epoch resume's partial run stays flat (slots, T, mps) ->
        # (num_models, T), mirroring the windowed family's convention
        arr = np.asarray(hist)
        if arr.ndim == 4:
            arr = arr.transpose(0, 3, 1, 2).reshape(
                self.num_models, arr.shape[1], arr.shape[2])
        elif arr.ndim == 3:
            arr = arr.transpose(0, 2, 1).reshape(self.num_models, -1)
        self.history = arr.tolist()

        # one device->host transfer for the whole ensemble, then slice
        # (fetch_global: multi-host gathers every host's slots so ALL
        # hosts hold all models, matching the driver-side collect of the
        # reference; np.asarray alone cannot read non-addressable shards)
        host = jax.tree.map(
            lambda x: np.asarray(x).reshape(
                self.num_models, *x.shape[2:]),
            comm.fetch_global(stacked))
        models = []
        for i in range(self.num_models):
            m = self._fresh_model()
            m.set_params(jax.tree.map(lambda x: x[i], host))
            models.append(m)
        return models
