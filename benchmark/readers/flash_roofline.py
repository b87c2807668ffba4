"""The flash forward kernel's share of its roofline: the least time the
chip could take for the calls' required operations and bytes
(``opcount.flash_fwd``), over the time the trace shows the kernel took.  Shapes come from each event's own
HLO text, so prompts of different lengths are each charged their own."""

import re

from benchmark.trace import opcount, reduce

_ITEMSIZE = {"bf16": 2, "f32": 4, "f16": 2}
_PEAK = {"bf16": "bfloat16", "f32": "float32_default_precision",
         "f16": "bfloat16"}


def read(outcome, ctx, pattern, causal):
    reduced = outcome.get("trace")
    if not reduced:
        return None
    want = re.compile(pattern)
    least, taken, bounds = 0.0, 0.0, {}
    for seconds, text in reduced["events"]:
        if not want.search(reduce.op_name(text)):
            continue
        # result (bh, tq, d); operands q (bh, tq, d), k and v (bh, tk, d)
        shapes = reduce.shapes_in(text)
        dtype, (bh, tq, d) = shapes[0]
        keyed = [s for t, s in shapes[1:] if len(s) == 3 and s[0] == bh
                 and s[2] == d]
        tk = keyed[1][1] if len(keyed) > 1 else tq
        ops, moved = opcount.flash_fwd(bh, tq, tk, d, causal,
                                       _ITEMSIZE[dtype])
        t, bound = opcount.roofline_seconds(
            ops, moved, ctx.peaks["flops_per_s"][_PEAK[dtype]],
            ctx.peaks["hbm_bytes_per_s"])
        least += t
        taken += seconds
        bounds[bound] = bounds.get(bound, 0.0) + t
    if taken <= 0:
        return None
    print(f"reader flash_roofline: {pattern} bound by "
          f"{max(bounds, key=bounds.get)} ({bounds})")
    return 100.0 * least / taken
