"""A CPU rehearsal of a cell at a toy size: the real manifest, kinds,
readers and traffic files, with the configuration swapped for the toy one
of its block family and the traffic's sizes cut to fit it.  Reports no
speed."""

from __future__ import annotations

import copy
import glob
import json
import os
import time

from benchmark import manifest

DATA = os.path.join("benchmark", "tests", "data")
TINY = os.path.join(DATA, "tiny-config.json")
PEAKS = {"flops_per_s": {"bfloat16": 197e12,
                         "float32_default_precision": 197e12},
         "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def toy_files():
    """{family named by a toy configuration (None: the transformer's):
    its file}, from the files themselves."""
    out = {}
    for path in sorted(glob.glob(os.path.join(manifest.ROOT, DATA,
                                              "*.json"))):
        with open(path) as f:
            out[json.load(f).get("family")] = os.path.relpath(
                path, manifest.ROOT)
    return out


def shrink(traffic, outputs=(2, 6)):
    t = copy.deepcopy(traffic)
    if "seq_len" in t:
        t.update(seq_len=16, batches=4)
    for cls in t.get("classes", []):
        cls["prompt_len"] = {"dist": "uniform", "min": 3, "max": 28}
        cls["output_len"] = {"dist": "uniform", "min": outputs[0],
                             "max": outputs[1]}
    if "arrival" in t:
        t["arrival"]["rate_per_s"] = 20.0
    if "requests" in t:
        t["requests"] = 16
    if "lead_in_s" in t:
        t["lead_in_s"] = 0.5
    t["sub_windows"] = 5
    t["trace_seconds"] = 0.3
    t["check_requests"] = 3
    return t


def rehearse(monkeypatch, tmp_path, cell_name, seed=7, seconds=1.5,
             trace=False, outputs=(2, 6)):
    import jax

    from benchmark import run

    man = manifest.load()
    man = copy.deepcopy(man)
    toys = toy_files()
    for c in man["configs"]:
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            c["file"] = toys[json.load(f).get("family")]
    real = manifest.traffic_of
    monkeypatch.setattr(manifest, "traffic_of",
                        lambda cell: shrink(real(cell), outputs))
    chips = manifest.cell(man, cell_name)["chips"]
    return run.run_cell(man, cell_name, seed, seconds, trace,
                        jax.devices()[:chips], PEAKS, str(tmp_path),
                        process_start=time.perf_counter())
