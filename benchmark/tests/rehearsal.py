"""A CPU rehearsal of a cell at a toy size: the real manifest, kinds,
readers and traffic files, with the configuration swapped for the toy one
and the traffic's sizes cut to fit it.  Reports no speed."""

from __future__ import annotations

import copy
import os
import time

from benchmark import manifest

TINY = os.path.join("benchmark", "tests", "data", "tiny-config.json")
PEAKS = {"flops_per_s": {"bfloat16": 197e12,
                         "float32_default_precision": 197e12},
         "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def shrink(traffic):
    t = copy.deepcopy(traffic)
    if "seq_len" in t:
        t.update(seq_len=16, batches=4)
    for cls in t.get("classes", []):
        cls["prompt_len"] = {"dist": "uniform", "min": 3, "max": 28}
        cls["output_len"] = {"dist": "uniform", "min": 2, "max": 6}
    if "arrival" in t:
        t["arrival"]["rate_per_s"] = 20.0
    if "requests" in t:
        t["requests"] = 16
    t["sub_windows"] = 5
    t["trace_seconds"] = 0.3
    t["check_requests"] = 3
    return t


def rehearse(monkeypatch, tmp_path, cell_name, seed=7, seconds=1.5,
             trace=False):
    import jax

    from benchmark import run

    man = manifest.load()
    man = copy.deepcopy(man)
    for c in man["configs"]:
        c["file"] = TINY
    real = manifest.traffic_of
    monkeypatch.setattr(manifest, "traffic_of",
                        lambda cell: shrink(real(cell)))
    chips = manifest.cell(man, cell_name)["chips"]
    return run.run_cell(man, cell_name, seed, seconds, trace,
                        jax.devices()[:chips], PEAKS, str(tmp_path),
                        process_start=time.perf_counter())
