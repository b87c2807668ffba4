"""Perf attribution — host-side counts behind device-side perf claims.

This layer records what the HOST can always measure, cheaply enough to
stay on in production (<5% of train wall, gated):

- **Retraces** — every XLA executable build, counted via a
  ``jax.monitoring`` duration listener on
  ``/jax/core/compile/backend_compile_duration`` (plus ``perf.traces``
  for jaxpr traces and a ``perf.compile_s`` histogram).  Steady-state
  training and a ladder-bounded serving engine should both read ZERO
  after warm-up; a nonzero rate in the time series is the "why did this
  run get slow" answer no wall clock gives.
- **Dispatches** — compiled-program launches enqueued by the
  framework's own hot loops (:func:`count_dispatch` at the
  ``ChunkRunner`` chunk dispatch and each serving replica batch).  A
  deliberate seam count, not an XLA-internal hook: it measures the
  dispatch *granularity the framework chose*, which is exactly the knob
  chunk plans and batch ladders turn.
- **H2D / D2H bytes + walls** — :func:`h2d` at the ``ChunkFeed``
  transfer and at the decode worker's one packed transfer a dispatch
  (bytes shipped + the async enqueue wall) and :func:`d2h` at
  the trainers' blocking loss retire (bytes fetched + the blocking
  wall, which on the streamed path is the documented backpressure
  barrier — the honest "host overlap wall").
- **Per-phase step-time breakdown** — :func:`phase` wraps the host
  loops' phases (the trainers' ``data`` / ``step`` / ``comm`` /
  ``ckpt``, the decode worker's ``decode.*``) into always-on
  ``perf.phase.<name>`` registry histograms, each sample stamped with
  the region's start.  The time domain rides the sampler's
  ``perf_sample`` events, NOT per-call span events: phases run at
  per-chunk or per-token cadence, and two JSON lines per phase is
  exactly the hot-loop emission volume the <5% overhead contract
  forbids (measured: it tripled the obs gate's emit wall).  Every
  region is also a ``jax.profiler.TraceAnnotation`` named
  ``perf.<name>``: nothing while no profiler session is open, and a
  host region on the device trace's clock in ANY session that is —
  ``utils.profiling.trace``, a benchmark's own ``start_trace``, a
  remote capture through ``jax.profiler.start_server``.
- **Host stalls** — :func:`watch_stalls` keeps ONE daemon thread a
  process awake a hundred times a second while a ``DecodeEngine``
  lives, and every wake-up that comes more than 30 ms late is a sample
  of ``perf.host_stall_s`` (the seconds it was late, stamped with the
  moment it was due) with the process's CPU seconds over the same
  interval beside it (``perf.host_stall_cpu_s``): "the machine stood
  still" against "the interpreter was busy", which a region's wall
  (``decode.step.wait`` grows by 0.1 s either way, and equally when
  the device is slow) cannot tell apart.  It opens no trace region
  and writes nothing.

Everything lands in the process metrics registry, so it rides the
epoch-boundary snapshots, the ``MetricsSampler`` time series, the
``perf_sample`` events, and the Prometheus exposition with no extra
plumbing.  No device profiler is ever required.
"""

from __future__ import annotations

import threading
import time

from dist_keras_tpu.observability import metrics

# one executable build per fire — the retrace proxy
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# one jaxpr trace per fire — the (noisier) Python-side tracing proxy
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"

_lock = threading.Lock()
_installed = False

# comm_overlap / comm_blocked are the round-19 split of the boundary
# collective wall: AsyncMerge (parallel/collectives.py) charges the
# async enqueue to comm_overlap and the deferred block_until_ready to
# comm_blocked, so "how much of the collective hid under compute" is a
# first-class histogram instead of a guess inside "comm"
PHASES = ("data", "step", "comm", "comm_overlap", "comm_blocked", "ckpt",
          # the decode worker loop (serving/decode.py), parents before
          # their children: the locked scheduling pass, the idle park,
          # one prompt's prefill and one slot set's token step, each
          # split into building the ONE packed host array, its transfer
          # (counted by h2d) and the launch returning, the wait for the
          # result, and (step) the token callbacks and exits.  A step
          # stays in flight: a ``decode.step`` parent holds the launch
          # of one step (build, dispatch) and the landing of the one
          # before it (wait, emit), each child once a step; a
          # ``decode.prefill`` parent closes around the launch and opens
          # again around the wait when such a pass runs between.  Leaves
          # stay leaves: a trace's reader credits an idle gap to the
          # innermost region by its exact name
          "decode.sched", "decode.park",
          "decode.prefill", "decode.prefill.build",
          "decode.prefill.dispatch", "decode.prefill.wait",
          "decode.step", "decode.step.build", "decode.step.dispatch",
          "decode.step.wait", "decode.step.emit")

_annotation = None  # jax.profiler.TraceAnnotation, imported on first use

# The stall witness's two constants, from the scratch witnesses of PR 38
# (PERF.md section 6, "After the refusal"; section 7).  The wait: the
# machine's freezes last 0.09-0.11 s, so a wait of 10 ms places one to a
# tenth of its length, and a hundred wake-ups a second of microseconds
# each take the interpreter's lock only while the worker waits on the
# device.  The threshold: a wake-up of a busy serving process comes up to
# a few milliseconds late (another thread's switch interval of 5 ms, a
# burst of callbacks), and every freeze seen was at least 88 ms: 30 ms
# lies three times clear of both, as PR 38's child process had it.
STALL_WAIT_S = 0.010
STALL_LATE_S = 0.030

_witness = {"users": 0, "thread": None, "stop": None}


def _on_duration(name, duration_secs, **kw):
    if name == _COMPILE_EVENT:
        metrics.counter("perf.retraces").inc()
        metrics.histogram("perf.compile_s").observe(duration_secs)
    elif name == _TRACE_EVENT:
        metrics.counter("perf.traces").inc()


def install():
    """Register the retrace listener (idempotent; one module flag check
    per call, so hot loops may call it freely).  -> True when the
    listener is active, False when jax/monitoring is unavailable —
    callers never gate on the result, the counters just stay zero."""
    global _installed
    if _installed:
        return True
    with _lock:
        if _installed:
            return True
        try:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
        # dklint: ignore[broad-except] jax.monitoring is optional; no listener means no retrace counts
        except Exception:
            return False
        _installed = True
    return True


def installed():
    return _installed


def count_dispatch(n=1):
    """Count ``n`` compiled-program launches enqueued by a framework
    hot loop (per chunk / per serving batch — NOT per compiled step,
    which lives inside the dispatch and cannot host a Python hook)."""
    metrics.counter("perf.dispatches").inc(n)


def h2d(nbytes, seconds):
    """Record one host->device transfer: bytes shipped + the enqueue
    wall (``device_put`` is async — the DMA itself overlaps compute by
    design, so the enqueue wall is the host-side cost that exists)."""
    metrics.counter("perf.h2d_bytes").inc(int(nbytes))
    metrics.histogram("perf.h2d_s").observe(seconds)


def d2h(nbytes, seconds):
    """Record one device->host fetch: bytes + the BLOCKING wall.  On
    the streamed training path this wall doubles as the depth-2
    backpressure barrier (see ``ChunkRunner``), so it includes the wait
    for the dispatched compute — which is precisely the "host overlap
    wall" a device-only claim needs a CPU-measurable proxy for."""
    metrics.counter("perf.d2h_bytes").inc(int(nbytes))
    metrics.histogram("perf.d2h_s").observe(seconds)


class phase:
    """Always-on timed region ``with perf.phase(name, **fields):`` —
    the one hot-loop region primitive.  Observes ``perf.phase.<name>``
    (registry histogram: a clock read + deque append, no I/O), the
    sample stamped with the region's START, and brackets the region
    with a ``jax.profiler.TraceAnnotation("perf.<name>", **fields)``
    (a ``TraceMe``: about a microsecond with no profiler session open),
    so whoever opens a session sees the host regions on the device's
    clock with ``fields`` as their arguments.  No flag, no knob."""

    __slots__ = ("_name", "_ann", "_t0")

    def __init__(self, name, **fields):
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation

            _annotation = TraceAnnotation
        self._name = name
        self._ann = _annotation(f"perf.{name}", **fields)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t0 = self._t0
        # dklint: metrics=perf.phase.*
        metrics.histogram(f"perf.phase.{self._name}").observe(
            time.perf_counter() - t0, at=t0)
        self._ann.__exit__(*exc)
        return False


def _witness_loop(wait, clock=time.perf_counter, cpu=time.process_time):
    """The witness thread's body: ``wait(STALL_WAIT_S)`` (the stop
    event's, True once it is set) until stopped; a wake-up more than
    ``STALL_LATE_S`` after it was due is ONE sample of the lateness and
    one of the process's CPU seconds since the wake-up before, both
    stamped with the moment it was due, so that
    ``samples_between`` puts the stall in the window it fell in.  The
    histograms are looked up at the stall, not held: a registry reset
    under a running witness (tests) leaves it observing the live ones."""
    woke, used = clock(), cpu()
    while not wait(STALL_WAIT_S):
        due = woke + STALL_WAIT_S
        woke, before, used = clock(), used, cpu()
        if woke - due > STALL_LATE_S:
            metrics.histogram("perf.host_stall_s").observe(
                woke - due, at=due)
            metrics.histogram("perf.host_stall_cpu_s").observe(
                used - before, at=due)


def watch_stalls():
    """One more user of the process's stall witness (a ``DecodeEngine``
    being built): the first starts the thread.  The two histograms
    exist from here on, empty until the first stall, so a reader can
    tell "no stall" from "no witness"."""
    with _lock:
        _witness["users"] += 1
        metrics.histogram("perf.host_stall_s")
        metrics.histogram("perf.host_stall_cpu_s")
        if _witness["thread"] is None:
            stop = _witness["stop"] = threading.Event()
            _witness["thread"] = threading.Thread(
                target=_witness_loop, args=(stop.wait,), daemon=True,
                name="dk-perf-stall-witness")
            _witness["thread"].start()


def unwatch_stalls():
    """A user of the witness is gone (its engine closed): the last one
    stops the thread and waits for it, a wait's length at most."""
    with _lock:
        _witness["users"] = max(0, _witness["users"] - 1)
        if _witness["users"] or _witness["thread"] is None:
            return
        thread, _witness["thread"] = _witness["thread"], None
        _witness["stop"].set()
    thread.join(timeout=5.0)


def snapshot(snap=None):
    """Compact JSON-ready perf-attribution snapshot — the
    ``perf_sample`` event payload and the report's per-rank row.
    Percentile-free (totals only): this runs on every sampler tick,
    which passes its already-taken registry ``snap`` in so one tick
    walks the registry once, not twice."""
    if snap is None:
        snap = metrics.snapshot(percentiles=False)
    counters, hists = snap["counters"], snap["histograms"]
    phases = {}
    for name, h in hists.items():
        if name.startswith("perf.phase."):
            phases[name[len("perf.phase."):]] = {
                "count": h["count"],
                "total_s": round(h["total"], 6),
                "mean_s": (round(h["total"] / h["count"], 6)
                           if h["count"] else None),
            }
    out = {
        "retraces": counters.get("perf.retraces", 0),
        "traces": counters.get("perf.traces", 0),
        "dispatches": counters.get("perf.dispatches", 0),
        "h2d_bytes": counters.get("perf.h2d_bytes", 0),
        "d2h_bytes": counters.get("perf.d2h_bytes", 0),
        "phases": phases,
    }
    compile_h = hists.get("perf.compile_s")
    if compile_h and compile_h["count"]:
        out["compile_s_total"] = round(compile_h["total"], 4)
    return out
