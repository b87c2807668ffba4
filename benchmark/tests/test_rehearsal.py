"""A CPU rehearsal of every cell at a toy size: the shape of the result,
never a speed."""

import json

import pytest

from benchmark import manifest
from benchmark.tests import rehearsal

CELLS = [c["name"] for c in manifest.load()["workloads"]]
KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def _units(man, cell, group, sources=None):
    return {e["name"]: e["unit"] for e, _, _ in
            manifest.metrics_for(man, cell, group, sources)}


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", CELLS)
def test_last_line_shape(monkeypatch, tmp_path, cell, trace):
    # replies long enough that some pass of the loop admits nobody: only
    # a launch outside a pass that ran a prefill says whether it was fed
    result = rehearsal.rehearse(monkeypatch, tmp_path, cell,
                                seed=2 ** 31 + 5, trace=trace,
                                outputs=(8, 20))
    json.dumps(result, allow_nan=False)
    assert set(result) == KEYS | {"breakdown" if trace else "per_layer"}
    assert list(result)[-1] == "compared"
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "setup_s" in result["metrics"] or trace
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        result["device"])
    man = manifest.load()
    # ``metrics`` is the contract's group and nothing else; what an
    # untraced run reads beside it has a key of its own
    groups = {"metrics": _units(man, cell,
                                "per_layer" if trace else "end_to_end")}
    if not trace:
        groups["per_layer"] = _units(man, cell, "per_layer",
                                     manifest.PROGRAM_SOURCES)
        assert groups["per_layer"] and not (
            set(groups["per_layer"]) & set(groups["metrics"]))
    for key, allowed in groups.items():
        for name, m in result[key].items():
            assert m["unit"] == allowed[name]
            assert isinstance(m["value"], float)
    assert result["compared"]
    for c in result["compared"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    if trace:
        assert {"busy_s", "window_s"} <= set(result["device"])
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(len(v) <= 10 for v in result["breakdown"].values())
    elif manifest.traffic_of(manifest.cell(man, cell))["kind"].startswith(
            "serve"):
        # what the host cost the window is beside every run's rate
        for stem in ("host_stall_s.", "host_stalls.", "steps_fed_share."):
            assert any(n.startswith(stem) for n in result["per_layer"]), stem
