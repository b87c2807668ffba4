"""Profiling / tracing hooks — the §5 "tracing" subsystem.

The reference's only instrumentation is trainer wall-clock timing
(``record_training_start/stop``, trainers.py:~60), which our Trainer base
already reproduces.  This module adds the TPU-native layer on top:
``trace(logdir)``, a context manager around ``jax.profiler`` producing an
XProf/TensorBoard trace of everything inside (compiled steps, collectives,
transfers).  While it is open, ``observability.span`` regions forward
their names into the device trace as ``TraceAnnotation``s; the hot-loop
regions of ``observability.perf.phase`` are there in any session and need
no help from here.  Host-side timing lives in the metrics registry
(``observability.metrics.Histogram``).
"""

from __future__ import annotations

import contextlib

import jax

from dist_keras_tpu.observability import spans as _spans


@contextlib.contextmanager
def trace(logdir):
    """Capture a device trace into ``logdir`` (view with TensorBoard).

    Also flips the span-forwarding flag so every
    ``observability.span(...)`` opened inside shows up as a
    ``TraceAnnotation`` in the captured timeline."""
    jax.profiler.start_trace(str(logdir))
    _spans.set_device_trace(True)
    try:
        yield
    finally:
        _spans.set_device_trace(False)
        jax.profiler.stop_trace()
