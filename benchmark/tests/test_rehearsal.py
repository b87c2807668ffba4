"""A CPU rehearsal of every cell at a toy size: the shape of the result,
never a speed."""

import json

import pytest

from benchmark import manifest
from benchmark.tests import rehearsal

CELLS = [c["name"] for c in manifest.load()["workloads"]]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", CELLS)
def test_last_line_shape(monkeypatch, tmp_path, cell, trace):
    result = rehearsal.rehearse(monkeypatch, tmp_path, cell,
                                seed=2 ** 31 + 5, trace=trace)
    json.dumps(result)
    assert set(result) == KEYS | ({"breakdown"} if trace else set())
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "setup_s" in result["metrics"] or trace
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        result["device"])
    man = manifest.load()
    group = "per_layer" if trace else "end_to_end"
    allowed = {e["name"]: e["unit"] for e, _, _ in
               manifest.metrics_for(man, cell, group)}
    for name, m in result["metrics"].items():
        assert m["unit"] == allowed[name]
        assert isinstance(m["value"], float)
    if trace:
        assert {"busy_s", "window_s"} <= set(result["device"])
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(len(v) <= 10 for v in result["breakdown"].values())
