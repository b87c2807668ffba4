"""The flash forward kernel's share of its roofline where the values are
of another width than the queries and keys (latent attention's prefill:
192 and 128): ``flash_roofline``'s reckoning with
``opcount_mla.flash_fwd_mixed``.  Shapes come from each event's own HLO
text: the result ``(bh, tq, d_v)`` first, the operands q ``(bh, tq,
d_qk)``, k ``(bh, tk, d_qk)`` and v ``(bh, tk, d_v)`` last."""

import re

from benchmark.trace import opcount, opcount_mla, reduce

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
        dtype, (bh, tq, d_v) = shapes[0]
        cubes = [s for _, s in shapes[1:] if len(s) == 3 and s[0] == bh
                 and s[2] > 1]
        if len(cubes) < 3:
            continue
        (_, _, d_qk), (_, tk, _) = cubes[-3], cubes[-2]
        ops, moved = opcount_mla.flash_fwd_mixed(
            bh, tq, tk, d_qk, d_v, causal, _ITEMSIZE[dtype])
        t, bound = opcount.roofline_seconds(
            ops, moved, ctx.peaks["flops_per_s"][_PEAK[dtype]],
            ctx.peaks["hbm_bytes_per_s"])
        least += t
        taken += seconds
        bounds[bound] = bounds.get(bound, 0.0) + t
    if taken <= 0:
        return None
    print(f"reader flash_roofline_mixed: {pattern} bound by "
          f"{max(bounds, key=bounds.get)} ({bounds})")
    return 100.0 * least / taken
