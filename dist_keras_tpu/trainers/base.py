"""Trainer base classes — parity with ``distkeras/trainers.py``.

``Trainer`` (trainers.py:~35) holds the serialized model + loss + worker
optimizer, records wall-clock training time (``record_training_start/stop``,
trainers.py:~60) and exposes ``get_history()`` / ``get_training_time()``.

``DistributedTrainer`` (trainers.py:~290) adds ``num_workers`` and the mesh
(the TPU stand-in for the Spark executor pool + parameter-server service:
``start_service``/``stop_service`` became "construct a Mesh").  The
``master_port``/``master_host`` kwargs of the reference are accepted and
ignored — there is no socket server to bind; the exchange compiles into ICI
collectives (see parallel/collectives.py).
"""

from __future__ import annotations

import time

import jax
import numpy as np

from dist_keras_tpu.ops.losses import get_loss
from dist_keras_tpu.ops.optimizers import get_optimizer
from dist_keras_tpu.parallel.mesh import worker_mesh
from dist_keras_tpu.utils.serialization import deserialize_model, serialize_model


class Trainer:
    def __init__(self, keras_model, loss="categorical_crossentropy",
                 worker_optimizer="adam", optimizer_kwargs=None,
                 features_col="features", label_col="label",
                 batch_size=32, num_epoch=1, seed=0, compute_dtype=None,
                 data_dtype=np.float32,
                 checkpoint_dir=None, checkpoint_every=None,
                 max_checkpoints=3, resume=False, callbacks=None,
                 nan_policy="raise", handle_preemption=False):
        self.serialized_model = serialize_model(keras_model)
        self.loss = loss
        self.worker_optimizer = worker_optimizer
        self.optimizer_kwargs = dict(optimizer_kwargs or {})
        self.features_col = features_col
        self.label_col = label_col
        self.batch_size = int(batch_size)
        self.num_epoch = int(num_epoch)
        self.seed = int(seed)
        self.compute_dtype = compute_dtype
        # dtype the host batches are materialized (and H2D-shipped) in;
        # None keeps the dataset columns' native dtypes — uint8 images
        # then transfer at 1/4 the float32 volume and the train step
        # casts on-device (cast-late, like the reference's uint8 MNIST
        # feed).  float32 default = the round-1..3 behavior.
        self.data_dtype = data_dtype
        # ---- mid-training hooks (beyond the reference: SURVEY §5 owes
        # checkpoint/resume + structured metrics) ----
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = (int(checkpoint_every)
                                 if checkpoint_every else None)
        if self.checkpoint_every and not self.checkpoint_dir:
            raise ValueError(
                "checkpoint_every requires checkpoint_dir (otherwise the "
                "dispatch would be chunked but nothing ever saved)")
        if self.checkpoint_dir and self.checkpoint_every is None:
            self.checkpoint_every = 1
        self.max_checkpoints = int(max_checkpoints)
        # resume: False = fresh run; True = continue from the latest
        # (verified — restore() falls back past a corrupt step) step;
        # an INT = continue from exactly that step.  The explicit form
        # is what the auto-resume supervisor passes: its fn receives
        # the latest VERIFIED step as resume_step and hands it straight
        # to Trainer(resume=resume_step), so the relaunch provably
        # consumes the agreed units_done instead of whatever the
        # directory happens to hold by the time the trainer starts.
        if isinstance(resume, bool) or resume is None:
            self.resume = bool(resume)
        else:
            self.resume = int(resume)
        self.callbacks = list(callbacks or [])
        # ---- resilience (round 6) ----
        # nan_policy: what the loss sentinel does on NaN/Inf —
        # "raise" (default: abort BEFORE the boundary checkpoint, so the
        # last save predates the divergence), "skip" (device-side guard:
        # a non-finite step keeps the previous params/opt state), "halt"
        # (stop dispatching at the boundary, return what trained), or
        # None/"off" (count only).  Counted per epoch in
        # metrics[...]["nonfinite_steps"] either way.
        from dist_keras_tpu.resilience.guards import normalize_policy

        self.nan_policy = normalize_policy(nan_policy)
        # handle_preemption: install SIGTERM/SIGINT handlers around the
        # dispatch loop; on delivery, checkpoint at the next chunk
        # boundary and raise resilience.Preempted (exit code 128+signum)
        self.handle_preemption = bool(handle_preemption)
        self.nonfinite_steps = 0   # cumulative non-finite loss entries
        self._nonfinite_emitted = 0
        self.metrics = []  # per-epoch {"epoch", "mean_loss", ...}
        self._checkpointer = None
        self.history = []
        self._t_start = None
        self._t_stop = None

    # ---- timing (trainers.py:~60) ----
    def record_training_start(self):
        self._t_start = time.time()
        from dist_keras_tpu.observability import events, timeseries

        events.emit("train_start", trainer=type(self).__name__,
                    num_epoch=self.num_epoch,
                    batch_size=self.batch_size)
        # live-telemetry plane: with DK_OBS_SAMPLE_S set this arms the
        # per-process MetricsSampler (time-series rings + anomaly
        # watchdog) and the DK_METRICS_PORT Prometheus exporter; one
        # env read when unset.  Deliberately NOT stopped at train end —
        # the series/watchdog keep covering whatever the process does
        # next (another train, a serving phase), like the registry.
        timeseries.maybe_start_sampler()

    def record_training_end(self):
        # drain any in-flight async checkpoint save FIRST (bounded by
        # the coordination deadline) so a completed train() leaves its
        # last cadence save promoted — but NEVER raise from here: this
        # is the post-mortem stamper, and it runs on the preempt/halt
        # path right before `raise Preempted` (a raise would replace
        # the typed 128+signum exit and skip the report.txt that must
        # exist precisely for abnormal exits).  The CLEAN path
        # surfaces deferred background-save errors one line later, in
        # ChunkRunner.run's post-record drain.
        ckptr = getattr(self, "_checkpointer", None)
        if ckptr is not None:
            from dist_keras_tpu.resilience.coordination import (
                default_timeout_s,
            )

            ckptr.wait_until_finished(timeout_s=default_timeout_s(),
                                      raise_errors=False)
        self._t_stop = time.time()
        from dist_keras_tpu.observability import events, timeseries

        events.emit("train_end", trainer=type(self).__name__,
                    seconds=self.get_training_time())
        # the sampler keeps running (see record_training_start), but
        # the watchdog must learn this quiet is COMPLETION: without a
        # quiesce, the dispatch counter stopping at train end reads as
        # a throughput stall and pages the operator for a run that
        # succeeded
        sampler = timeseries.get_sampler()
        if sampler is not None and sampler.watchdog is not None:
            sampler.watchdog.quiesce()
        # leader-side merged report: when the obs dir is shared
        # storage, rank 0 leaves report.txt next to the logs at run
        # end — the post-hoc CLI remains for collected/per-host dirs.
        # Best-effort like every emit: telemetry must not kill a run
        # that just finished training.
        if events.rank() == 0:
            try:
                from dist_keras_tpu.observability import report

                report.write_report(events.obs_dir())
            # dklint: ignore[broad-except] best-effort report write on the way out of training
            except Exception:  # pragma: no cover - fs failure
                pass

    def get_training_time(self):
        if self._t_start is None or self._t_stop is None:
            return 0.0
        return self._t_stop - self._t_start

    def get_history(self):
        """Per-step training losses.

        Shapes by trainer: SingleTrainer -> (steps,); AveragingTrainer ->
        (workers, epochs, steps); EnsembleTrainer -> (num_models, epochs,
        steps); windowed family (DOWNPOUR/ADAG/AEASGD/EAMSGD) ->
        (workers, epochs, windows, W) — except a run RESUMED mid-epoch
        (``checkpoint_every_windows``), whose partial first epoch makes
        its own losses (workers, windows_run, W); DynSGD -> (workers,
        epochs, steps).
        """
        return self.history

    def get_averaged_history(self):
        return float(np.mean(np.asarray(self.history))) if len(
            np.ravel(self.history)) else float("nan")

    # ---- compiled-program cache ----
    # XLA compilation is expensive; trainers with equal configuration
    # produce identical traced programs, so the jitted callables are
    # shared process-wide.  Shape/dtype
    # changes are handled by jit's own retracing — the key only carries what
    # changes the *structure* of the traced program.  LRU-bounded: cached
    # builder closures pin model params, so unbounded growth would leak a
    # weight copy per hyperparameter-sweep point.
    _jit_cache = {}
    _jit_cache_max = 32
    # Non-string key components are tokened by id(); pin them (dict keyed
    # by id, so repeated _cache_key calls — e.g. once per epoch chunk —
    # never duplicate) so a GC'd object's address can never be reused by a
    # different config.  Pins are refcounted per CACHE KEY and released
    # when eviction drops the last key referencing them, so a long
    # hyperparameter sweep can't leak one pinned object per point.
    _id_pins = {}
    _id_pin_refs = {}

    def _cache_extras(self):
        """Subclass hook: hyperparameters baked into the trace."""
        return ()

    def _cache_key(self):
        def _tok(v):
            if isinstance(v, str):
                return v
            Trainer._id_pins[id(v)] = v
            return f"obj:{id(v)}"

        # num_epoch is deliberately absent: trainers that bake the epoch
        # count into the trace (epoch-scan) add it via _cache_extras;
        # trainers that loop epochs on the host must share executables
        # across different epoch counts.
        # nan_policy="skip" compiles a different step (finite-guarded
        # update); the other policies are host-side and share executables
        return (type(self).__name__,
                self.serialized_model["model"],
                _tok(self.loss), _tok(self.worker_optimizer),
                tuple(sorted(self.optimizer_kwargs.items())),
                str(self.compute_dtype),
                self.nan_policy == "skip",
                self._cache_extras())

    @staticmethod
    def _key_obj_ids(key):
        """ids of every ``obj:<id>`` token inside a (nested) cache key."""
        out = []

        def walk(t):
            if isinstance(t, tuple):
                for e in t:
                    walk(e)
            elif isinstance(t, str) and t.startswith("obj:"):
                out.append(int(t[4:]))

        walk(key)
        return out

    def _compiled(self, builder, extra_key=()):
        key = self._cache_key() + tuple(extra_key)
        cache = Trainer._jit_cache
        refs, pins = Trainer._id_pin_refs, Trainer._id_pins
        fn = cache.pop(key, None)
        if fn is None:
            try:
                fn = builder()
            # dklint: ignore[broad-except] a failed jit build must drop its key pins, then re-raise
            except Exception:
                # _cache_key's _tok pinned the key's objects into
                # _id_pins before the lookup; a failed build never gets
                # a refcount, so drop any pin no live key refcounts or
                # it leaks for the process lifetime
                for i in Trainer._key_obj_ids(key):
                    if i not in refs:
                        pins.pop(i, None)
                raise
            for i in Trainer._key_obj_ids(key):  # new key: pin its objs
                refs[i] = refs.get(i, 0) + 1
            while len(cache) >= Trainer._jit_cache_max:
                old_key = next(iter(cache))  # evict least recently used
                cache.pop(old_key)
                for i in Trainer._key_obj_ids(old_key):
                    n = refs.get(i, 1) - 1
                    if n <= 0:  # last key using this obj: unpin it
                        refs.pop(i, None)
                        pins.pop(i, None)
                    else:
                        refs[i] = n
        cache[key] = fn  # (re)insert at the back = most recent
        return fn

    # ---- epoch chunking / checkpoint / callbacks ----------------------
    # The whole num_epoch run compiles into ONE dispatch when no hooks are
    # requested (fastest path, round-1 behavior).  checkpoint_every=K
    # chunks the dispatch at K-epoch boundaries; any registered callback
    # forces per-epoch chunks so on_epoch_end really fires every epoch.
    def _chunk_plan(self, start_epoch=0):
        remaining = self.num_epoch - start_epoch
        if remaining <= 0:
            return []
        if self.callbacks:
            size = 1
        elif self.checkpoint_every:
            size = min(self.checkpoint_every, remaining)
        else:
            size = remaining
        chunks = [size] * (remaining // size)
        if remaining % size:
            chunks.append(remaining % size)
        return chunks

    def _checkpointer_or_none(self):
        if self.checkpoint_dir and self._checkpointer is None:
            from dist_keras_tpu.checkpoint import Checkpointer

            self._checkpointer = Checkpointer(
                self.checkpoint_dir, max_to_keep=self.max_checkpoints)
        return self._checkpointer

    def _maybe_resume(self, template, incompatible_hint=None):
        """-> (start_epoch, restored_state | None).

        ``incompatible_hint``: actionable message appended when the
        restore fails on a template/checkpoint structure mismatch (e.g.
        a round-3 checkpoint without the round-4 'rng' leaf — orbax
        raises its own opaque tree error long before a key check on the
        restored dict could run)."""
        ckptr = self._checkpointer_or_none()
        # resume=0 is an EXPLICIT step (the supervisor's resume_step can
        # legitimately be the unit-0 preemption save), so the gate tests
        # identity against False, not truthiness
        if self.resume is False or ckptr is None:
            return 0, None
        explicit = None if self.resume is True else int(self.resume)
        if explicit is None and ckptr.latest_step() is None:
            return 0, None
        from dist_keras_tpu.checkpoint import CheckpointCorrupt

        try:
            step, state = ckptr.restore(step=explicit, template=template)
        except (OSError, CheckpointCorrupt):
            # NOT wrapped in ValueError: the auto-resume supervisor
            # classifies ValueError as a never-retried config mistake,
            # but a transient I/O error is the one failure mode the
            # self-healing layer exists to absorb, and CheckpointCorrupt
            # is its typed verdict — laundering either into ValueError
            # would turn a retryable restart into a permanent giveup
            raise
        # dklint: ignore[broad-except] re-raised (with an actionable incompatible-checkpoint hint)
        except Exception as e:
            if incompatible_hint:
                raise ValueError(
                    f"checkpoint restore failed ({type(e).__name__}); "
                    f"{incompatible_hint}") from e
            raise
        # the RETURNED step is authoritative — a verified fallback may
        # have restored an earlier step than requested, and the cadence
        # counter below plus the dispatch start must follow the state
        # actually loaded, not the step asked for
        from dist_keras_tpu.observability import events

        events.emit("resume", step=int(step),
                    requested=explicit, trainer=type(self).__name__)
        self._last_ckpt_epoch = int(step)
        return int(step), state

    # (the cadence-save implementation lives in ChunkRunner._maybe_ckpt
    # — every trainer routes through the chunked dispatch loop, which
    # also owns the async-handle drain/error-surfacing scaffolding; a
    # second copy here would silently drop AsyncSaveHandles)

    def _emit_epoch_end(self, epochs_done, losses, seconds, samples):
        """Record structured per-epoch metrics; fire callbacks.

        Under nan_policy="skip" — and ONLY there — ``mean_loss``
        averages the finite losses: one exploding batch must not poison
        the epoch's metric (and any loss-watching callback) after the
        step itself was correctly skipped.  Every other policy keeps the
        plain mean, so with the sentinel opted out (None) a divergence
        still surfaces as a NaN mean_loss exactly as before round 6;
        the non-finite count is reported alongside either way."""
        arr = np.asarray(losses, dtype=np.float64)
        if self.nan_policy == "skip" and arr.size:
            arr = arr[np.isfinite(arr)]
        logs = {
            "epoch": epochs_done,
            "mean_loss": float(np.mean(arr)) if arr.size else
            float("nan"),
            "seconds": float(seconds),
            "samples_per_sec": float(samples / seconds) if seconds > 0
            else float("nan"),
            # non-finite loss entries seen since the previous emit (the
            # NaN sentinel's per-epoch ledger; cumulative total lives on
            # trainer.nonfinite_steps)
            "nonfinite_steps": self.nonfinite_steps
            - self._nonfinite_emitted,
        }
        self._nonfinite_emitted = self.nonfinite_steps
        self.metrics.append(logs)
        # the epoch boundary is the natural telemetry cadence: one
        # typed event carrying the epoch record, plus a snapshot of the
        # process-wide metrics registry riding the same stream (both
        # no-ops when DK_OBS_DIR is unset)
        from dist_keras_tpu.observability import events
        from dist_keras_tpu.observability import metrics as obs_metrics

        events.emit("epoch_end", trainer=type(self).__name__, **logs)
        obs_metrics.emit_snapshot(epoch=epochs_done)
        for cb in self.callbacks:
            hook = getattr(cb, "on_epoch_end", cb)
            hook(self, epochs_done, logs)

    # ---- shared plumbing ----
    def _fresh_model(self):
        return deserialize_model(self.serialized_model)

    def _resolve(self):
        """-> (model, loss_fn, optimizer transform)."""
        model = self._fresh_model()
        return (model, get_loss(self.loss),
                get_optimizer(self.worker_optimizer, **self.optimizer_kwargs))

    def _make_step(self, model, loss_fn, tx):
        """``make_model_step`` with this trainer's NaN policy compiled in
        — the single seam every trainer family builds its step through,
        so ``nan_policy="skip"`` guards all of them identically."""
        from dist_keras_tpu.trainers.step import make_model_step

        return make_model_step(
            model, loss_fn, tx, self.compute_dtype,
            skip_nonfinite=(self.nan_policy == "skip"))

    def _finalize(self, params, history):
        """Install trained params into a fresh model; record history."""
        self.history = history
        model = self._fresh_model()
        model.set_params(jax.tree.map(np.asarray, params))
        return model

    def train(self, dataset, shuffle=False):
        raise NotImplementedError


class DistributedTrainer(Trainer):
    """Base for every multi-worker trainer (trainers.py:~290)."""

    def __init__(self, keras_model, num_workers=2, master_host=None,
                 master_port=5000, mesh=None, **kw):
        super().__init__(keras_model, **kw)
        self.num_workers = int(num_workers)
        # master_host/master_port: reference PS kwargs, accepted for parity.
        del master_host, master_port
        self._mesh = mesh

    def _cache_extras(self):
        custom = id(self._mesh) if self._mesh is not None else None
        return (self.num_workers, custom)

    @property
    def mesh(self):
        if self._mesh is None:
            from dist_keras_tpu.comm import backend as comm

            # multi-host bring-up: no-op single-process; on a pod it reads
            # the JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES /
            # JAX_PROCESS_ID env that launch.Job exports per host
            comm.initialize()
            self._mesh = worker_mesh(self.num_workers)
        return self._mesh

    def _local_worker_range(self):
        """[lo, hi) worker-mesh slots whose device lives on this process.

        jax.devices() orders devices by process, so a 1-D worker mesh
        gives every host a contiguous run of workers."""
        import jax as _jax

        devs = list(self.mesh.devices.ravel())
        mine = [i for i, d in enumerate(devs)
                if d.process_index == _jax.process_index()]
        if not mine:
            return 0, 0
        lo, hi = mine[0], mine[-1] + 1
        if mine != list(range(lo, hi)):  # pragma: no cover - defensive
            raise RuntimeError(
                "non-contiguous local worker slots; pass an explicit mesh")
        return lo, hi

    def _shards(self, dataset):
        """-> (xs, ys) host arrays with a leading worker axis.

        Single-process: the full (num_workers, steps, batch, ...) deal.
        Multi-host: ONLY this host's workers' rows are materialized
        (leading axis = local worker count); every host computes the
        identical global geometry from the dataset length, so the
        concatenation over hosts equals the single-host deal.  Feed the
        result through ``_put_worker_chunk`` to get the global sharded
        array.
        The reference analogue is Spark shipping each executor only its
        partitions (trainers.py:~365) — via ``comm.local_data_slice``
        semantics (comm/backend.py).
        """
        from dist_keras_tpu.comm import backend as comm

        _ = self.mesh  # force process-group bring-up (informative error
        # if comm.initialize() was forgotten at program start)
        return dataset.worker_shards(
            self.num_workers, self.batch_size,
            features_col=self.features_col, label_col=self.label_col,
            worker_range=(self._local_worker_range()
                          if comm.is_multi_host() else None),
            dtype=self.data_dtype)

    def _put_worker_chunk(self, *arrays):
        """Async device_put of host ``(local_workers, ...)`` arrays with
        the worker sharding — the ONE transfer primitive, for the
        resident path and the streaming feed (``data/feed.py``) alike:
        each device receives only its own worker's rows (never the whole
        stack on device 0), straight from the host, and the H2D can
        overlap a running dispatch.  On multi-host the global array is
        assembled from each process's local block without any host
        materializing the global data."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dist_keras_tpu.comm import backend as comm
        from dist_keras_tpu.parallel.mesh import WORKER_AXIS

        sharding = NamedSharding(self.mesh, P(WORKER_AXIS))
        if not comm.is_multi_host():
            return tuple(jax.device_put(a, sharding) for a in arrays)
        out = []
        for a in arrays:
            a = np.ascontiguousarray(a)
            out.append(jax.make_array_from_process_local_data(
                sharding, a, (self.num_workers,) + a.shape[1:]))
        return tuple(out)

    def _stack_workers(self, tree, inner=()):
        """Replicate a pytree with a leading (num_workers, *inner) axis —
        the host-side layout of per-worker carry state (local replicas,
        optimizer state) that crosses chunked-dispatch boundaries sharded
        over the worker mesh axis.  ``inner`` adds unsharded replica dims
        inside each slot (EnsembleTrainer's models-per-slot).

        The broadcast stays a zero-copy numpy view on the host and each
        leaf is ``device_put`` (or process-local assembly on multi-host)
        directly with the worker sharding, so no device ever holds more
        than its own (1, ...) shard — materializing the full
        (workers, ...) stack on one chip could OOM where the per-worker
        state fits fine."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dist_keras_tpu.comm import backend as comm
        from dist_keras_tpu.parallel.mesh import WORKER_AXIS

        n = self.num_workers
        sharding = NamedSharding(self.mesh, P(WORKER_AXIS))

        lead = (1,) * (1 + len(inner))
        if comm.is_multi_host():
            lo, hi = self._local_worker_range()

            def _stack(x):
                x = np.asarray(x)
                return jax.make_array_from_process_local_data(
                    sharding,
                    np.broadcast_to(x.reshape(lead + x.shape),
                                    (hi - lo,) + inner + x.shape),
                    (n,) + inner + x.shape)
        else:
            def _stack(x):
                x = np.asarray(x)
                return jax.device_put(
                    np.broadcast_to(x.reshape(lead + x.shape),
                                    (n,) + inner + x.shape), sharding)

        return jax.tree.map(_stack, tree)
