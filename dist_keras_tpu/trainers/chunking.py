"""Shared chunked-dispatch machinery for scan-based trainers.

Every distributed trainer has the same outer shape: a run of N scan units
(communication windows for the windowed family, steps for DynSGD) is cut
into dispatch chunks at the union of epoch boundaries, checkpoint-cadence
points and streaming data-chunk boundaries, then driven through a loop
that pipelines streamed chunks (depth 2, preserving the ChunkFeed's
two-buffer residency bound), syncs at boundaries, saves checkpoints
BEFORE user callbacks, and emits per-epoch metrics.  Round 3 had this
loop hand-written inside ``windowed.py``; hoisting it here lets DynSGD —
whose staggered-staleness schedule has the most state to lose on
preemption — share the identical cadence/resume/streaming semantics
instead of re-implementing (and subtly diverging from) them.

The reference analogue of the whole mechanism: a long-lived Spark worker
streams its partition through an iterator (workers.py:~60) while the
driver polls trained models per epoch (trainers.py:~360); there is no
single-dispatch fast path to preserve there because every batch is a
Python step.  Here the no-hooks case stays ONE compiled dispatch.
"""

from __future__ import annotations

import signal
import time

import numpy as np

from dist_keras_tpu.observability import events as obs_events
from dist_keras_tpu.observability import perf
from dist_keras_tpu.observability import spans as obs_spans
from dist_keras_tpu.resilience import coordination, preemption
from dist_keras_tpu.resilience.faults import fault_point
from dist_keras_tpu.resilience.guards import check_losses
from dist_keras_tpu.resilience.preemption import Preempted
from dist_keras_tpu.utils.sync import drain


def init_streaming(trainer, chunk, budget, name="stream_chunk_windows"):
    """Validate and install the streaming kwargs every streaming-capable
    trainer shares (one definition instead of a per-class copy)."""
    # None = off; anything else must be a positive int (0 raises like
    # every other out-of-range value rather than silently meaning "off")
    value = None if chunk is None else int(chunk)
    if value is not None and value < 1:
        raise ValueError(f"{name}={chunk} must be >= 1")
    setattr(trainer, name, value)
    trainer.max_resident_bytes = None if budget is None else int(budget)
    if trainer.max_resident_bytes is not None \
            and trainer.max_resident_bytes < 1:
        raise ValueError(f"max_resident_bytes={budget} must be >= 1")
    trainer._streamed = False  # set by train(); introspectable by tests


def scan_units(one_step, carry, xs, ys, T, t0, spe, streamed):
    """Scan ``one_step(carry, (t, x, y))`` over ``T`` global units
    starting at ``t0`` — the shared inner-scan shape of every flat-step
    trainer body.  Streamed mode consumes ``xs``/``ys`` directly as the
    scanned sequence (the chunk IS exactly its data, epoch-aligned by
    ``epoch_spans``); resident mode dynamically indexes the
    epoch-resident tensors at ``si = t % spe``."""
    import jax
    import jax.numpy as jnp

    ts = jnp.arange(T) + t0
    if streamed:
        return jax.lax.scan(one_step, carry, (ts, xs, ys))

    def indexed(c, t):
        si = t % spe
        x = jax.lax.dynamic_index_in_dim(xs, si, 0, keepdims=False)
        y = jax.lax.dynamic_index_in_dim(ys, si, 0, keepdims=False)
        return one_step(c, (t, x, y))

    return jax.lax.scan(indexed, carry, ts)


def reject_stale_checkpoint(restored, required_key, trainer, detail):
    """Raise the shared actionable error for a checkpoint written by a
    pre-step-granular version of ``trainer``.  Needed because
    pickle-fallback checkpoints restore without a template match, so the
    orbax-path structure error can't fire — the missing key is the only
    tell."""
    if restored is not None and required_key not in restored:
        raise ValueError(
            f"checkpoint predates step-granular {trainer} state "
            f"({detail}) — restart training or point checkpoint_dir at "
            "a fresh directory")


def chunk_plan(start, total, per_epoch, *, epoch_bounds=False,
               cadence=None, data_chunk=None):
    """Chunk sizes (in scan units) for the dispatch loop.

    - ``epoch_bounds``: cut at every epoch boundary (callbacks need
      on_epoch_end at real epoch ends).
    - ``cadence=N``: cut every N units counted from ``start`` (the
      resume point) — the checkpoint grid.
    - ``data_chunk=C``: streaming mode — cut at every epoch boundary
      AND every C-th unit within each epoch, aligned to the epoch start
      (NOT the resume point, so a resumed run reuses the identical
      chunk grid); each chunk's data is then one contiguous
      epoch-relative slice of <= C units, the ChunkFeed transfer unit.

    No hooks = one dispatch (the round-1 perf path).
    """
    remaining = total - start
    if remaining <= 0:
        return []
    bounds = {total}
    if epoch_bounds:
        first = (start // per_epoch + 1) * per_epoch
        bounds |= set(range(first, total, per_epoch))
    if cadence:
        bounds |= set(range(start + cadence, total, cadence))
    if data_chunk:
        # k=0 of the grid lands on every epoch boundary too
        for e in range(start // per_epoch, -(-total // per_epoch)):
            bounds |= {e * per_epoch + k
                       for k in range(0, per_epoch, data_chunk)
                       if start < e * per_epoch + k}
    cuts = sorted(b for b in bounds if start < b <= total)
    out, prev = [], start
    for b in cuts:
        out.append(b - prev)
        prev = b
    return out


def resolve_stream_chunk(requested, budget, per_device_epoch_bytes,
                         per_epoch):
    """-> effective streaming chunk size in scan units, or None.

    ``requested`` wins when set; otherwise ``budget`` (bytes of
    per-device data residency) auto-sizes a chunk so TWO in-flight
    chunks (executing + prefetched) fit inside it — only when the
    epoch tensor actually exceeds the budget.
    """
    C = requested
    if C is None and budget and per_device_epoch_bytes > budget:
        per_unit = max(1, per_device_epoch_bytes // per_epoch)
        C = max(1, budget // (2 * per_unit))
    if C:
        C = max(1, min(int(C), per_epoch))
    return C


def epoch_spans(plan, start, per_epoch):
    """Epoch-relative (offset, length) data slices, one per chunk."""
    u, spans = start, []
    for K in plan:
        spans.append((u % per_epoch, K))
        u += K
    return spans


def run_chunked(trainer, xs, ys, *, start, total, per_epoch, stream_units,
                cadence, samples_per_unit, dispatch, sync_ref, state_fn,
                carry_leaves, fetch_global):
    """The full chunked-dispatch recipe shared by the windowed family and
    DynSGD: streaming decision -> chunk plan -> feed-or-resident data
    setup (with the pre-clock drain) -> ChunkRunner -> history reshape.

    ``stream_units`` is the trainer's requested streaming chunk already
    converted to scan units (windows for the windowed family, steps for
    DynSGD); ``carry_leaves`` are the device carries whose distribution
    must complete before the clock starts.  Returns the history list:
    losses concatenated over chunks and reshaped to
    ``(workers, epochs, per_epoch, *rest)`` when the run covered whole
    epochs (a mid-epoch resume keeps its partial run flat — see
    ``Trainer.get_history``).
    """
    stream_C = resolve_stream_chunk(
        stream_units, trainer.max_resident_bytes,
        (xs.nbytes + ys.nbytes) // max(1, xs.shape[0]), per_epoch)
    trainer._streamed = bool(stream_C)
    plan = chunk_plan(start, total, per_epoch,
                      epoch_bounds=bool(trainer.callbacks),
                      cadence=cadence, data_chunk=stream_C)
    feed = None
    if stream_C:
        from dist_keras_tpu.data.feed import ChunkFeed

        feed = ChunkFeed(epoch_spans(plan, start, per_epoch),
                         trainer._put_worker_chunk, xs, ys)
        trainer._last_feed = feed  # test introspection
        # chunk 0's transfer and the carry state land OUTSIDE the clock,
        # like the resident path's one-shot H2D; chunks 1.. transfer
        # inside it, overlapped under the running dispatch (plan may be
        # empty: resume of an already-finished run)
        first = feed.get(0) if plan else ()
        drain(*carry_leaves, *first)
        resident = ()
    else:
        xs_d, ys_d = trainer._put_worker_chunk(xs, ys)
        # data AND carry-state distribution completes OUTSIDE the clock
        drain(xs_d, ys_d, *carry_leaves)
        resident = (xs_d, ys_d)

    runner = ChunkRunner(
        trainer, plan=plan, start=start, total=total, per_epoch=per_epoch,
        samples_per_unit=samples_per_unit, cadence=cadence, feed=feed,
        fetch_global=fetch_global)
    all_losses = runner.run(dispatch, sync_ref=sync_ref, state_fn=state_fn,
                            resident_data=resident)
    if not all_losses:
        return []
    flat = np.concatenate(all_losses, axis=1)
    if flat.shape[1] % per_epoch == 0:
        flat = flat.reshape(flat.shape[0], -1, per_epoch, *flat.shape[2:])
    return flat.tolist()


class ChunkRunner:
    """Drives a chunk plan through dispatch/pipeline/sync/checkpoint.

    The trainer supplies closures:

    - ``dispatch(i, K, units_done, data) -> device losses`` — enqueue
      chunk i (the trainer reassigns its carry state inside);
    - ``sync_ref() -> pytree`` — what to ``drain`` at boundaries (the
      latest carry; per-device in-order execution makes it cover the
      whole chunk);
    - ``state_fn() -> dict`` — the checkpoint payload (lazy: only
      evaluated when a save is due).

    Timing: boundary-time host work (loss fetches, checkpoint I/O, user
    callbacks) happens between ``t_mark`` resets — off the clock, like
    the round-3 loop.  The ONE exception is the streamed path's mid-loop
    depth-2 backpressure retire: it blocks until the PREVIOUS chunk's
    compute finishes (so at most two chunks' data is ever
    device-resident), which is genuine training wall-time and is
    counted; the loss bytes it also fetches are KBs riding that same
    round trip.  One blocking fetch is the cheapest correct barrier (a
    ``drain`` probe + boundary-deferred fetch adds a dispatch per
    retire inside the clock), so the fetch stays in-window.
    """

    def __init__(self, trainer, *, plan, start, total, per_epoch,
                 samples_per_unit, cadence=None, feed=None,
                 fetch_global=None):
        self.tr = trainer
        self.plan = plan
        self.start = start
        self.total = total
        self.per_epoch = per_epoch
        self.samples_per_unit = samples_per_unit
        self.cadence = cadence
        self.feed = feed
        self._fetch = fetch_global or (lambda x: x)

    # checkpoint cadence in scan units; trainer._last_ckpt_epoch is the
    # unit count of the last save (set by _maybe_resume on restore)
    def _ckpt_due(self, units_done):
        if self.tr._checkpointer_or_none() is None:
            return False
        last = getattr(self.tr, "_last_ckpt_epoch", 0)
        cadence = self.cadence or self.total
        return units_done - last >= cadence or units_done >= self.total

    def _maybe_ckpt(self, units_done, state_fn):
        if self._ckpt_due(units_done):
            # async (DK_CKPT_ASYNC, default): only the host snapshot
            # runs here.  The returned handle is deliberately dropped —
            # the preempt boundary and the end-of-run drain wait
            # through Checkpointer.wait_until_finished, which covers
            # whatever write is in flight regardless of coalescing.
            # A PREVIOUS background failure re-raises out of save() at
            # this boundary — like a synchronous failure one cadence
            # late.  Rapid boundary saves coalesce latest-wins inside
            # the Checkpointer (bounded: one in flight + one pending).
            self.tr._checkpointer_or_none().save(units_done, state_fn())
            self.tr._last_ckpt_epoch = units_done

    def _drain_saves(self, raise_errors, timeout_s=None):
        """Wait (bounded by the coordination deadline, or an explicit
        ``timeout_s``) for any in-flight async save — a run leaving the
        dispatch loop must never leave a background writer racing a
        relaunched incarnation in the same checkpoint directory."""
        ckptr = self.tr._checkpointer_or_none()
        if ckptr is None:
            return
        ckptr.wait_until_finished(
            timeout_s=(coordination.default_timeout_s()
                       if timeout_s is None else timeout_s),
            raise_errors=raise_errors)

    def _preempt_save(self, units_done, state_fn, world=1):
        """Boundary checkpoint on a delivered SIGTERM/SIGINT — saved
        regardless of cadence (deduped against a save that already
        landed at this unit), so the restart loses nothing.  The None
        sentinel (vs the 0 default used by the cadence math) matters: a
        fresh run preempted before any save still writes its unit-0
        state, so ``Preempted.saved_step`` never claims a checkpoint
        that does not exist.

        The save is VERIFIED before the exit (single-host; on a pod the
        non-leaders return before the leader's promotion, so there is
        no committed step for them to probe yet): the whole point of
        the typed 128+signum exit is that the restart can stand on this
        exact checkpoint — a torn boundary save must surface as a typed
        ``CheckpointCorrupt`` NOW, not as a restore explosion in the
        relaunched incarnation.  ``Preempted.saved_step`` is therefore
        a *checked* claim.  (Skipped under ``DK_CKPT_VERIFY=0``: no
        manifest was written, ``verify`` reports a soft
        "unverifiable".)"""
        ckptr = self.tr._checkpointer_or_none()
        if ckptr is None:
            return None
        # the async pipeline must not stretch the SIGTERM→exit window:
        # the boundary save (and any still-in-flight cadence save it
        # coalesced behind) is waited on with a bounded deadline —
        # Preempted is only raised once the bytes are promoted, so
        # saved_step stays a checked claim under DK_CKPT_ASYNC too
        deadline = coordination.default_timeout_s()
        if getattr(self.tr, "_last_ckpt_epoch", None) != units_done:
            handle = ckptr.save(units_done, state_fn())
            self.tr._last_ckpt_epoch = units_done
            handle.wait(timeout_s=deadline)
        else:
            # a cadence save of this exact unit may still be in flight
            ckptr.wait_until_finished(timeout_s=deadline)
        if world == 1:
            ckptr.verify(units_done)
        return units_done

    def run(self, dispatch, sync_ref, state_fn, resident_data=()):
        tr = self.tr
        all_losses, acc_losses = [], []
        acc_dt, acc_samples = 0.0, 0
        units_done = self.start
        self._halt = False  # set by the NaN sentinel under policy "halt"
        # pipelined in-flight chunks whose losses are not yet fetched
        pending = []  # [(chunk_idx, device losses, units when done)]

        def _retire_one():
            # the blocking fetch doubles as the backpressure barrier —
            # see the class docstring for why a drain + deferred fetch
            # is NOT cheaper here.  perf attribution: the fetch wall is
            # the host-side "step" phase (it blocks on the dispatched
            # compute) and the fetched bytes are the D2H proxy row; the
            # step.loss fault stays INSIDE the phase so an injected
            # delay (gates.py --watchdog-only) reads as a slow step.
            j, lj, units_after = pending.pop(0)
            with perf.phase("step"):
                t_fetch = time.perf_counter()
                arr = np.asarray(self._fetch(lj))  # blocks: chunk j done
                perf.d2h(arr.nbytes, time.perf_counter() - t_fetch)
                # deterministic NaN injection rides the fetched host
                # array (device math untouched) — the nan_policy hook
                arr = fault_point("step.loss", value=arr)
            if self.feed is not None:
                self.feed.release(j)
            all_losses.append(arr)
            acc_losses.append(arr)
            # the sentinel: count NaN/Inf, apply the trainer's policy
            # ("raise" aborts HERE — before any boundary save can
            # persist post-divergence state; "halt" drains and stops)
            if check_losses(tr, arr, units_done=units_after):
                self._halt = True

        # graceful preemption window: handlers only set a flag; the loop
        # notices it at the next chunk boundary below.  Off the main
        # thread there is no graceful window (strict=False) — signal
        # handlers are main-thread-only, the run proceeds uninstalled.
        installed = (tr.handle_preemption
                     and preemption.install(strict=False))
        # cluster consensus (tentpole, ISSUE 2): on a pod the SIGTERM
        # reaches hosts at different instants, so a LOCAL flag is not
        # enough — every chunk boundary piggybacks an any_flag vote, and
        # all hosts agree on one save step and exit Preempted together.
        # Single-process this is the trivial LocalCoordinator (its only
        # cost is the "coord.flag" fault point's dict lookup, which is
        # also what makes the whole path injectable without a cluster).
        # The coordinator is resolved REGARDLESS of handle_preemption:
        # the NaN-halt verdict below must be cluster-wide on ANY
        # multi-host run — a host halting alone would strand its peers'
        # next two-phase save against a marker that never comes.  Only
        # the preemption VOTE is gated on handle_preemption (a config
        # every host shares, so the collective op order stays SPMD).
        coord = coordination.get_coordinator()
        # perf attribution (observability.perf): retrace listener on,
        # phases + dispatch counts below — always-on host-side proxies
        # for the device-only perf story (one flag check when already
        # installed)
        perf.install()
        tr.record_training_start()
        t_mark = time.time()
        # the run's ROOT span: every per-chunk breadcrumb, coordination
        # vote and checkpoint event below auto-stamps its trace identity
        # (and the async writer's ckpt.save span resumes it), so a whole
        # training run stitches into one trace — on a launched pod,
        # DK_TRACE_ID makes that trace span every host.  Entered/exited
        # manually: the existing try/except/finally unwind structure
        # must stay byte-identical.
        _run_span = obs_spans.span("train.run", start=self.start)
        _run_span.__enter__()
        try:
            for i, K in enumerate(self.plan):
                sig = (preemption.requested()
                       if tr.handle_preemption else None)
                # did THIS host's OS deliver the signal?  (vs adopting
                # it from the vote below) — the report uses this to
                # attribute the preemption to the right rank
                signalled = sig is not None
                if tr.handle_preemption:
                    # boundary vote: did ANY host see the signal?  A
                    # host whose own flag is clear adopts SIGTERM — its
                    # scheduler's signal is merely in flight.
                    with perf.phase("comm"):
                        voted = coord.any_flag(sig is not None)
                    if voted:
                        sig = signal.SIGTERM if sig is None else sig
                    if sig is not None and coord.world > 1:
                        with perf.phase("comm"):
                            agreed = coord.agree_min(units_done)
                        if agreed != units_done:  # pragma: no cover
                            # identical plans + the same vote boundary
                            # make this impossible unless hosts diverged
                            # (NOT a lost peer — PeerLost is reserved
                            # for heartbeat-proven deaths)
                            raise RuntimeError(
                                f"coordinated save step disagreement: "
                                f"this host at {units_done}, cluster "
                                f"min {agreed} — hosts ran different "
                                "chunk plans")
                if sig is not None:
                    # checkpoint at the boundary, then exit 128+signum
                    # (Preempted is a SystemExit) so the scheduler
                    # restarts with resume=True.  The drain can trip the
                    # NaN sentinel ("raise" aborts inside _retire_one;
                    # "halt" sets the flag) — a halted run's diverged
                    # state must NOT be persisted here either.
                    # (this is also where the preemption SIGNAL becomes
                    # an event: the handler itself must not emit — see
                    # preemption._handler — so the boundary that notices
                    # the flag stamps signum + where the run was.
                    # adopted=True marks a host that only learned of the
                    # signal through the vote: the report attributes the
                    # preemption to the non-adopted rank(s) only)
                    obs_events.emit("preempt", signum=int(sig),
                                    units_done=units_done,
                                    adopted=not signalled)
                    # crash-safe tail: the grace window may not survive
                    # the drain+save below, so the recorder dumps NOW —
                    # the post-mortem exists even if the scheduler's
                    # second SIGTERM lands mid-checkpoint
                    if obs_events.enabled():
                        from dist_keras_tpu.observability import flight
                        flight.dump("preempt", signum=int(sig),
                                    units_done=units_done)
                    while pending:
                        _retire_one()
                    if coord.world > 1:
                        # the halt verdict must be CLUSTER-wide too: a
                        # NaN seen by one host only would otherwise make
                        # it skip the save while its peers enter the
                        # two-phase commit — the leader would then wait
                        # out the whole deadline on a marker that never
                        # comes.  Either every host saves or none does.
                        self._halt = coord.any_flag(self._halt)
                    with perf.phase("ckpt"):
                        saved = (None if self._halt
                                 else self._preempt_save(
                                     units_done, state_fn,
                                     world=coord.world))
                    if coord.world > 1:
                        # every host's save (incl. the leader's
                        # promotion) lands before ANY host exits — the
                        # scheduler restarts a pod whose checkpoint is
                        # fully committed, never torn
                        with perf.phase("comm"):
                            coord.barrier("preempt_exit")
                    obs_events.emit("preempt_exit", signum=int(sig),
                                    saved_step=saved)
                    # the run ENDED here: stamp the wall clock (the
                    # trained-time answer is truthful — training
                    # stopped at this boundary) — which also writes
                    # the leader's merged report.txt; the flagship
                    # post-mortem artifact must exist precisely for
                    # ABNORMAL exits, not only clean completions
                    tr.record_training_end()
                    raise Preempted(sig, saved_step=saved)
                with perf.phase("data"):
                    data = (self.feed.get(i) if self.feed is not None
                            else resident_data)
                with perf.phase("step"):
                    losses = dispatch(i, K, units_done, data)
                perf.count_dispatch()
                units_done += K
                # per-CHUNK (not per-step — steps live inside the
                # compiled scan) breadcrumb: the last of these in a
                # host's log is where a hung run stopped
                obs_events.emit("chunk", i=i, units=K,
                                units_done=units_done,
                                streamed=self.feed is not None)
                pending.append((i, losses, units_done))
                if self.feed is not None:
                    # retire the previous chunk BEFORE prefetching the
                    # next: at most two chunks' data is ever
                    # device-resident, and the i+1 transfer still
                    # overlaps chunk i's execution
                    while len(pending) > 1:
                        _retire_one()
                    with perf.phase("data"):
                        self.feed.prefetch(i + 1)
                multi = coord.world > 1
                # multi-host: a locally-tripped halt must NOT cut a
                # boundary only this host sees — every consensus op has
                # to happen at the same loop position on every host
                # (SPMD discipline), so halt waits for the next NATURAL
                # boundary and is put to a cluster vote there
                boundary = (units_done % self.per_epoch == 0
                            or i == len(self.plan) - 1
                            or self._ckpt_due(units_done)
                            or (self._halt and not multi))
                acc_samples += self.samples_per_unit * K
                if not boundary:
                    continue
                with perf.phase("step"):
                    drain(sync_ref())
                acc_dt += time.time() - t_mark
                # host-side work below (loss fetches, checkpoint I/O,
                # user callbacks) stays OUTSIDE the clock
                while pending:
                    _retire_one()
                if multi:
                    # cluster halt verdict: one host's NaN halts the
                    # whole pod together (or nobody) — an uncoordinated
                    # break here would leave the peers blocking in their
                    # next vote until the deadline
                    with perf.phase("comm"):
                        self._halt = coord.any_flag(self._halt)
                # save BEFORE user callbacks run: a callback that dies
                # (preemption simulation) must not lose the chunk — but
                # NEVER persist a halted (diverged) run's state
                if not self._halt:
                    with perf.phase("ckpt"):
                        self._maybe_ckpt(units_done, state_fn)
                if units_done % self.per_epoch == 0:
                    tr._emit_epoch_end(
                        units_done // self.per_epoch,
                        np.concatenate(acc_losses, axis=1),
                        acc_dt, acc_samples)
                    acc_losses, acc_dt, acc_samples = [], 0.0, 0
                if self._halt:
                    obs_events.emit("nan_halt", units_done=units_done)
                    # halting mid-epoch: emit the partial epoch too
                    # (numbered as the epoch in progress) so the
                    # nonfinite ledger reaches trainer.metrics — a
                    # monitor reading metrics must see WHY the run
                    # stopped early, not a clean truncation
                    if acc_losses:
                        tr._emit_epoch_end(
                            units_done // self.per_epoch + 1,
                            np.concatenate(acc_losses, axis=1),
                            acc_dt, acc_samples)
                    break
                t_mark = time.time()
        # dklint: ignore[broad-except] re-raised immediately — this arm
        # only drains the async writer on the UNWIND path (bounded,
        # no-raise, so the original exception is never masked); the
        # clean path drains exactly once inside record_training_end
        # below (a double drain would double the worst-case stall on a
        # wedged writer).  An unwinding run must not leave a background
        # writer racing a relaunched incarnation in the same directory.
        except BaseException as e:
            # a TimeoutError unwinding here means a handle wait ALREADY
            # burned one full deadline against this same wedged writer
            # (_preempt_save) — paying a second would double the
            # SIGTERM→exit stall the preemption contract bounds; a
            # zero-timeout probe keeps the no-zombie intent for the
            # wedged case without the second wait
            self._drain_saves(
                raise_errors=False,
                timeout_s=0 if isinstance(e, TimeoutError) else None)
            raise
        finally:
            _run_span.__exit__(None, None, None)
            # exception-safe (a raising user callback must not leave
            # the feed pinning the host epoch tensors)
            if self.feed is not None:
                self.feed.close()
            if installed:
                preemption.restore()
        tr.record_training_end()
        # the CLEAN-path error surface: record_training_end already
        # drained (no-raise — it also runs right before `raise
        # Preempted` — and it paid the one bounded deadline).  This
        # zero-timeout probe only CLASSIFIES that outcome: it raises
        # the deferred background-save error, or TimeoutError for a
        # writer still wedged past the deadline, without waiting a
        # second one.  A completed run must fail exactly like a
        # synchronous save raising at the last boundary.
        ckptr = tr._checkpointer_or_none()
        if ckptr is not None:
            ckptr.wait_until_finished(timeout_s=0, raise_errors=True)
        return all_losses
