"""A memory-bound decode kernel's share of its roofline: the bytes one
call MUST move over the time the trace shows a call took times the HBM's
peak.  The family states, for the kernel's name, which of the program's
stamped histograms counts a step's units and how many bytes a unit costs
a call (``outcome["counters"]["kernel_unit_bytes"]``, from the family's
``opcount`` file); the bytes are the window's mean step's, the time the
mean duration of the traced events of that name.  A run without the
counter, the histogram or such an event reads nothing."""

import re

from benchmark.readers import program_window
from benchmark.trace import reduce


def read(outcome, ctx, kernel):
    reduced = outcome.get("trace")
    unit = (outcome["counters"].get("kernel_unit_bytes") or {}).get(kernel)
    if not reduced or not unit:
        return None
    histogram, unit_bytes = unit
    units = program_window.window_samples(ctx, histogram)
    want = re.compile(kernel)
    calls = [seconds for seconds, text in reduced["events"]
             if want.search(reduce.op_name(text))]
    if not units or not calls:
        return None
    required = unit_bytes * sum(units) / len(units)
    taken = sum(calls) / len(calls)
    print(f"reader kernel_roofline: {kernel} {required / 1e6:.1f} MB a call "
          f"required, {1e3 * taken:.3f} ms over {len(calls)} traced calls")
    return 100.0 * required / ctx.peaks["hbm_bytes_per_s"] / taken
