"""Device time of the operations whose name matches ``pattern`` (and,
with ``shape_key``, whose result has the shape the kind recorded under
that key), as a share of the device's busy time."""

import re

from benchmark.trace import reduce


def read(outcome, ctx, pattern, shape_key=None):
    reduced = outcome.get("trace")
    if not reduced or reduced["busy_s"] <= 0:
        return None
    want = re.compile(pattern)
    shape = None
    if shape_key is not None:
        shape = outcome["shapes"].get(shape_key)
        if shape is None:
            return None
        shape = tuple(shape)
    total = 0.0
    for seconds, text in reduced["events"]:
        if not want.search(reduce.op_name(text)):
            continue
        if shape is not None and reduce.shapes_in(text)[0][1] != shape:
            continue
        total += seconds
    # a traced device on which no such operation ran reads 0, not nothing
    return 100.0 * total / reduced["busy_s"]
