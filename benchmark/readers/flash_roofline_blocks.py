"""The flash forward kernel's share of its roofline under the BLOCK-causal
mask over grouped heads: ``flash_roofline_grouped``'s reckoning with
``opcount_sdar_moe.flash_fwd_block_causal`` (a query keeps its own block of
``mask_block`` positions whole, where the causal count keeps the lower
triangle).  Shapes come from each event's own HLO text: the result ``(bh,
tq, d)`` first, the operands q ``(bh, tq, d)``, k and v ``(bh_kv, tk, d)``
last.  A program with no such call reads nothing."""

import re

from benchmark.readers.flash_roofline_grouped import _ITEMSIZE, _PEAK
from benchmark.trace import opcount, opcount_sdar_moe, reduce


def read(outcome, ctx, pattern, mask_block):
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
        ops, moved = opcount_sdar_moe.flash_fwd_block_causal(
            bh, bh_kv, tq, tk, d, mask_block, _ITEMSIZE[dtype])
        t, bound = opcount.roofline_seconds(
            ops, moved, ctx.peaks["flops_per_s"][_PEAK[dtype]],
            ctx.peaks["hbm_bytes_per_s"])
        least += t
        taken += seconds
        bounds[bound] = bounds.get(bound, 0.0) + t
    if taken <= 0:
        return None
    print(f"reader flash_roofline_blocks: {pattern} bound by "
          f"{max(bounds, key=bounds.get)} ({bounds})")
    return 100.0 * least / taken
