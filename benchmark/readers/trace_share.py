"""Device time of the operations whose name matches ``pattern``, as a
share of the device's busy time."""

import re

from benchmark.trace import reduce


def read(outcome, ctx, pattern):
    reduced = outcome.get("trace")
    if not reduced or reduced["busy_s"] <= 0:
        return None
    want = re.compile(pattern)
    total = sum(seconds for seconds, text in reduced["events"]
                if want.search(reduce.op_name(text)))
    # a traced device on which no such operation ran reads 0, not nothing
    return 100.0 * total / reduced["busy_s"]
