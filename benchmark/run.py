"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

A new process each time: enable the compile cache, build the cell's state
on the device from the seed, warm the cell's shapes (set-up), measure for
``--seconds``, decide ``correct`` against the plain reference, print one
JSON object as the last line of standard output.  Its ``metrics`` are the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics;
an untraced line carries beside them, under ``per_layer``, the per-layer
metrics that need no trace (the program's own counters and spans), and
every line ends with the numbers ``correct`` compared, each beside its
limit (``compared``, also the last lines of standard error).  Set-up runs
from the process's start to the first timed step or request.  The seconds
inside ``jax.devices()``, the machine bringing its chip up, are reported
beside it (the counter ``chip_startup_s``) and stay inside it: what that
call does not do then is done at the first executions (measured, PR 23),
so taking it out makes set-up less steady, not more.  Without the
accelerator the cell asks for, or on a ``device_kind`` the table of peaks
does not list, the run exits non-zero and prints no result.
"""

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
# the package is imported as ``benchmark``; its own directory must not
# lead the path, or ``benchmark/trace`` would shadow the library's ``trace``
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != _HERE]
sys.path.insert(0, _ROOT)


class Context:
    """What a kind gets: the cell's data files, the seed, the window, the
    devices, and the clock set-up is measured on."""

    def __init__(self, cell, config, traffic, seed, seconds, trace,
                 devices, peaks, scratch, process_start):
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.devices = devices
        self.peaks = peaks
        self.scratch = scratch
        self.process_start = process_start
        self.setup_s = None
        # left by a reader whose value is of the window's tail alone: the
        # seconds into the window from which it runs
        self.tails = []

    def mark(self, what):
        """One line of the run's log: seconds since the process began."""
        print(f"[{time.perf_counter() - self.process_start:7.2f} s] {what}",
              flush=True)

    def setup_done(self):
        """Called by the kind right before the first timed step or
        request."""
        self.setup_s = time.perf_counter() - self.process_start
        self.mark("set-up done, window begins")


def _read(wanted, outcome, ctx):
    """Each metric's reader over the closed window -> {name: value and
    unit}; a reader that finds nothing to read leaves its metric out."""
    metrics = {}
    for entry, spec, reader in wanted:
        ctx.tails = []
        value = reader.read(outcome, ctx, **spec.get("args", {}))
        if value is not None:
            metrics[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}
            if ctx.tails:
                # not the whole window's: said beside the value
                metrics[entry["name"]]["window_from_s"] = max(ctx.tails)
    return metrics


def run_cell(man, cell_name, seed, seconds, trace, devices, peaks,
             scratch, process_start=None, chip_startup_s=0.0):
    """Drive one cell on ``devices`` -> the result object.  The look for a
    chip is the caller's: tests hand in whatever devices they have."""
    from benchmark import device, manifest

    cell = manifest.cell(man, cell_name)
    config = manifest.config_of(man, cell)
    traffic = manifest.traffic_of(cell)
    kind = manifest.kind_of(traffic)
    group = "per_layer" if trace else "end_to_end"
    wanted = manifest.metrics_for(man, cell_name, group)
    # what needs no trace is read in every run, once the window has closed
    beside = [] if trace else manifest.metrics_for(
        man, cell_name, "per_layer", manifest.PROGRAM_SOURCES)
    ctx = Context(cell, config, traffic, seed, seconds, trace, devices,
                  peaks, scratch,
                  _PROCESS_START if process_start is None
                  else process_start)
    outcome = kind.run(ctx)
    outcome["counters"]["setup_s"] = ctx.setup_s
    outcome["counters"]["chip_startup_s"] = chip_startup_s
    metrics = _read(wanted, outcome, ctx)
    for check in outcome["checks"]:
        print("check", json.dumps(check), flush=True)
    result = {
        "correct": bool(outcome["checks"])
        and all(c["ok"] for c in outcome["checks"]),
        "attempted": int(outcome["attempted"]),
        "failed": int(outcome["failed"]),
        "metrics": metrics,
        "device": device.describe(
            devices, outcome["counters"]["memory_peak_bytes"]),
    }
    if not trace:
        result["per_layer"] = _read(beside, outcome, ctx)
    reduced = outcome.get("trace")
    if trace and reduced is not None:
        from benchmark.trace import reduce

        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": reduce.top(reduced["ops"]),
            "idle_gaps": reduce.top(reduced["idle_gaps"]),
        }
    # last in the line: each number compared, beside its limit (one that
    # is not finite as text: the line stays plain JSON)
    result["compared"] = {
        c["name"]: {"value": c["value"] if math.isfinite(c["value"])
                    else repr(c["value"]), "limit": c["limit"]}
        for c in outcome["checks"]}
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import device, manifest

    man = manifest.load()
    cell = manifest.cell(man, args.workload)

    # the program is the system under test: without it there is nothing
    # to measure, and the run says so instead of printing a result
    try:
        from dist_keras_tpu.utils import compile_cache
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout: {e}")
        return 3
    cache_dir = compile_cache.enable()
    imported = time.perf_counter()
    devices, peaks = device.require(cell["chips"])
    chip_startup_s = time.perf_counter() - imported
    first = devices[0]
    print(f"device platform={first.platform} device_kind="
          f"{first.device_kind!r} count={len(devices)} "
          f"compile_cache={cache_dir} imports "
          f"{imported - _PROCESS_START:.2f} s chip start-up "
          f"{chip_startup_s:.2f} s", flush=True)
    scratch = os.path.join(_ROOT, ".bench_scratch")
    os.makedirs(scratch, exist_ok=True)
    result = run_cell(man, args.workload, args.seed, args.seconds,
                      bool(args.trace), devices, peaks, scratch,
                      chip_startup_s=chip_startup_s)
    print(json.dumps(result), flush=True)
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
