"""The flash forward kernel's share of its roofline where fewer key/value
heads serve the query heads (grouped-query attention): ``flash_roofline``'s
reckoning with ``opcount_lfm2_moe.flash_fwd_grouped``.  Shapes come from
each event's own HLO text: the result ``(bh, tq, d)`` first, the operands
q ``(bh, tq, d)``, k and v ``(bh_kv, tk, d)`` last.  A program whose
flash calls have as many K/V heads as query heads reads the same number
as ``flash_roofline``; one with no such call reads nothing."""

import re

from benchmark.trace import opcount, opcount_lfm2_moe, reduce

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
        shapes = reduce.shapes_in(text)
        dtype, (bh, tq, d) = shapes[0]
        cubes = [s for _, s in shapes[1:] if len(s) == 3 and s[2] == d]
        if len(cubes) < 3:
            continue
        bh_kv, tk, _ = cubes[-1]
        ops, moved = opcount_lfm2_moe.flash_fwd_grouped(
            bh, bh_kv, tq, tk, d, causal, _ITEMSIZE[dtype])
        t, bound = opcount.roofline_seconds(
            ops, moved, ctx.peaks["flops_per_s"][_PEAK[dtype]],
            ctx.peaks["hbm_bytes_per_s"])
        least += t
        taken += seconds
        bounds[bound] = bounds.get(bound, 0.0) + t
    if taken <= 0:
        return None
    print(f"reader flash_roofline_grouped: {pattern} bound by "
          f"{max(bounds, key=bounds.get)} ({bounds})")
    return 100.0 * least / taken
