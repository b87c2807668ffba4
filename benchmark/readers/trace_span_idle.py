"""Share of the traced segment in which chip 0 was idle while the program's
host region ``span`` was the innermost open, in percent.

The reduced trace's ``idle_gaps`` already name each gap by the decode
worker's own region (``perf.decode.*``: one thread, one replica a cell)
wherever one is open, and by the benchmark's span only elsewhere
(``trace/reduce.py``): an idle gap goes to what the engine's worker was
doing, not to where the load generator slept.  The whole table goes to
the log.  A program that opens no such region reads nothing."""

PREFIX = "perf.decode."


def read(outcome, ctx, span):
    reduced = outcome.get("trace")
    if not reduced or reduced["window_s"] <= 0:
        return None
    idle = reduced["idle_gaps"]
    if not any(name.startswith(PREFIX) for name in reduced["host_spans"]):
        return None
    if not reduced.get("idle_table_printed"):
        reduced["idle_table_printed"] = True
        total = sum(idle.values())
        for name, seconds in sorted(idle.items(), key=lambda kv: -kv[1]):
            print(f"reader trace_span_idle: {name} {seconds:.6f} s idle, "
                  f"{100.0 * seconds / total:.1f}% of the idle time")
    return 100.0 * idle.get(PREFIX + span, 0.0) / reduced["window_s"]
