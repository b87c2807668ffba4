"""Small instruments the kinds share: compile counting, the profiler
window with the benchmark's own host spans, and the device's memory."""

from __future__ import annotations

import contextlib
import shutil
import threading


class CompileCounter:
    """Programs jax compiled, or fetched from the persistent cache, since
    ``reset``: inside the measured window this has to read 0.  (After
    ``chip_smoke.CompileMeter``.)"""

    _EVENTS = ("/jax/core/compile/backend_compile_duration",
               "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.count = 0
        self._lock = threading.Lock()
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self._EVENTS:
            with self._lock:
                self.count += 1

    def reset(self):
        with self._lock:
            self.count = 0

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on)


class Spans:
    """Host spans from the benchmark's own files, around its calls into
    each layer.  They reach the profiler's trace as ``TraceAnnotation``
    while a trace is open and cost nothing otherwise."""

    def __init__(self):
        self.active = False

    def __call__(self, name):
        if not self.active:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


class Profiler:
    """One traced segment: ``start`` opens the profiler (Python tracer
    off: a call per Python function would swamp the scheduler threads)
    and the span ``bench.window``; ``close_window`` ends the span,
    ``stop`` stops the profiler and ``reduced`` reduces the trace
    (``finish``: both)."""

    WINDOW = "bench.window"

    def __init__(self, logdir, spans):
        self.logdir = logdir
        self.spans = spans
        self._window = None

    def start(self):
        import jax

        shutil.rmtree(self.logdir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.logdir, profiler_options=options)
        self.spans.active = True
        self._window = jax.profiler.TraceAnnotation(self.WINDOW)
        self._window.__enter__()

    def close_window(self):
        """End the traced segment, on the thread that began it."""
        self._window.__exit__(None, None, None)
        self.spans.active = False

    def stop(self):
        """Stop the profiler, which writes the trace out (any thread;
        seconds of work, most of it outside the interpreter)."""
        import jax

        jax.profiler.stop_trace()

    def reduced(self):
        """-> the stopped profiler's trace, reduced: seconds of work in
        the interpreter, so a serving kind does it once its window has
        closed (inside the window it took the worker's thread a fifth of
        its steps at 100 steps a second, and made the generator late)."""
        from benchmark.trace import reduce

        trace = reduce.Trace.from_file(reduce.find_xplane(self.logdir))
        return trace.reduce(window_span=self.WINDOW)

    def finish(self):
        self.stop()
        return self.reduced()


def memory_peak_bytes(devices):
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
