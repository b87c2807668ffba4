"""SingleTrainer — parity with ``distkeras/trainers.py:~100``.

Reference path: coalesce the DataFrame to one partition and run a plain
epochs x train_on_batch loop in one Spark task (SURVEY.md §3.1).  TPU-native:
the run is a flat ``lax.scan`` over GLOBAL steps under ``jit`` — one
dispatch when no hooks are requested — driven through the shared
``ChunkRunner`` (``trainers/chunking.py``), which as of round 4 gives the
single-worker path the same streaming feed as the distributed family:
``stream_chunk_steps=C`` (or ``max_resident_bytes=B``) feeds C steps per
dispatch through the double-buffered ChunkFeed, so a dataset larger than
device memory trains at resident-speed parity; ``data_dtype=None`` ships
uint8 batches cast on-device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from dist_keras_tpu.trainers.base import Trainer


class SingleTrainer(Trainer):
    def __init__(self, keras_model, stream_chunk_steps=None,
                 max_resident_bytes=None, **kw):
        super().__init__(keras_model, **kw)
        from dist_keras_tpu.trainers.chunking import init_streaming

        init_streaming(self, stream_chunk_steps, max_resident_bytes,
                       name="stream_chunk_steps")

    # single-device transfer primitives with the ChunkFeed's
    # (leading-dummy-axis, slice-axis-1) calling convention
    def _put_worker_chunk(self, *arrays):
        return tuple(jax.device_put(np.ascontiguousarray(a[0]))
                     for a in arrays)

    def train(self, dataset, shuffle=False):
        model, loss_fn, tx = self._resolve()
        if shuffle:
            dataset = dataset.shuffle(seed=self.seed)
        xb, yb = dataset.batches(
            self.batch_size, self.features_col, self.label_col,
            dtype=self.data_dtype)
        spb = xb.shape[0]  # steps per epoch
        total_t = self.num_epoch * spb

        step, opt_init = self._make_step(model, loss_fn, tx)
        params = model.params
        opt_state = opt_init(params)
        rng = jax.random.PRNGKey(self.seed)

        # t_units marks the checkpoint's step counter as STEP-granular
        # (round 3 counted epochs); restoring an old checkpoint fails the
        # template match and surfaces the actionable hint below
        template = {"params": params, "opt_state": opt_state, "rng": rng,
                    "t_units": jnp.zeros((), jnp.int32)}
        start_t, restored = self._maybe_resume(
            template,
            incompatible_hint=(
                "if this checkpoint predates step-granular SingleTrainer "
                "state (round 3: no 't_units' leaf, step counted epochs "
                "not steps), restart training or point checkpoint_dir "
                "at a fresh directory"))
        if restored is not None:
            if "t_units" not in restored:
                # pickle-fallback checkpoints restore without a template
                # match, so the orbax-path structure error can't fire
                raise ValueError(
                    "checkpoint predates step-granular SingleTrainer "
                    "state (no 't_units' leaf; its step counts epochs, "
                    "not steps) — restart training or point "
                    "checkpoint_dir at a fresh directory")
            params = restored["params"]
            opt_state = restored["opt_state"]
            rng = jnp.asarray(restored["rng"])

        def build_chunk(T, streamed=False):
            # the rng chain is CONTINUOUS across epochs (the round-1..3
            # behavior: one PRNG stream for the whole run), so a flat
            # step scan needs no per-epoch reseeding
            @jax.jit
            def run(params, opt_state, rng, xs, ys, t0):
                if streamed:
                    (params, opt_state, rng), ls = jax.lax.scan(
                        step, (params, opt_state, rng), (xs, ys))
                else:
                    def indexed(c, t):
                        si = t % spb
                        x = jax.lax.dynamic_index_in_dim(
                            xs, si, 0, keepdims=False)
                        y = jax.lax.dynamic_index_in_dim(
                            ys, si, 0, keepdims=False)
                        return step(c, (x, y))

                    (params, opt_state, rng), ls = jax.lax.scan(
                        indexed, (params, opt_state, rng),
                        jnp.arange(T) + t0)
                return params, opt_state, rng, ls[None]  # (1, T)

            return run

        def dispatch(i, T, steps_done, data):
            nonlocal params, opt_state, rng
            streamed = self._streamed
            fn = self._compiled(
                lambda: build_chunk(T, streamed=streamed),
                extra_key=("sstream", T, spb) if streamed
                else ("single", T, spb))
            params, opt_state, rng, losses = fn(
                params, opt_state, rng, *data, jnp.int32(steps_done))
            return losses

        cadence = (self.checkpoint_every * spb
                   if self.checkpoint_every else None)
        # dummy leading axis: the shared feed slices axis 1
        history = _run_single(
            self, xb[None], yb[None], start=start_t, total=total_t,
            per_epoch=spb, stream_units=self.stream_chunk_steps,
            cadence=cadence, samples_per_unit=self.batch_size,
            dispatch=dispatch,
            sync_ref=lambda: params,
            state_fn=lambda: {"params": params, "opt_state": opt_state,
                              "rng": rng,
                              "t_units": jnp.zeros((), jnp.int32)},
            carry_leaves=(params, opt_state))
        return self._finalize(params, history)


def _run_single(trainer, xs, ys, **kw):
    """run_chunked with SingleTrainer's flat (steps,) history contract."""
    from dist_keras_tpu.trainers.chunking import run_chunked

    history = run_chunked(trainer, xs, ys, fetch_global=lambda x: x, **kw)
    return np.asarray(history).reshape(-1).tolist() if history else []
