"""The harness refuses what it cannot measure honestly, and stays driven
by data: no cell, configuration or metric name in ``run.py`` or a kind."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import device, manifest

ROOT = manifest.ROOT
HERE = manifest.HERE


def test_manifest_loads_and_every_file_resolves():
    man = manifest.load()
    for cell in man["workloads"]:
        conf = manifest.config_of(man, cell)
        traffic = manifest.traffic_of(cell)
        assert manifest.kind_of(traffic).run
        assert conf["reduced"] == next(
            c["reduced"] for c in man["configs"]
            if c["name"] == cell["config"])
        for group in ("end_to_end", "per_layer"):
            got = manifest.metrics_for(man, cell["name"], group)
            assert got, (cell["name"], group)
            for entry, spec, reader in got:
                assert reader.read
                for key in ("unit", "source"):
                    assert spec[key] == entry[key], entry["name"]
                if group == "per_layer":
                    assert spec["layer"] == entry["layer"]
                    assert spec["moves"] == entry["moves"]
        limits = os.path.join(HERE, "limits", cell["name"] + ".json")
        assert os.path.isfile(limits)


def test_text_fields_keep_to_their_lengths():
    man = manifest.load()
    for group in ("configs", "workloads", "per_layer"):
        for entry in man[group]:
            for key in ("why", "source", "layer"):
                text = entry.get(key, "x")
                if key == "source" and group != "configs":
                    continue
                assert 1 <= len(text) <= 200, (entry["name"], key)
                assert "\n" not in text and "\t" not in text


def test_each_layer_metric_moves_a_metric_its_cells_report():
    man = manifest.load()
    e2e = {m["name"]: m for m in man["end_to_end"]}
    cells = [c["name"] for c in man["workloads"]]
    for m in man["per_layer"]:
        target = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in target.get("workloads", cells), (m["name"], cell)


def test_no_per_layer_entry_is_a_copy_of_another():
    """Every entry's file exists, and no two entries that move the same
    end-to-end metric have identical files: such a pair is ONE entry
    whose ``workloads`` lists both cells (the list holds at most 128)."""
    _no_copies(manifest.load())


def _no_copies(man, root=ROOT):
    assert len(man["per_layer"]) <= 128, len(man["per_layer"])
    metrics = os.path.join(root, "benchmark", "metrics")
    seen = {}
    for entry in man["per_layer"]:
        path = os.path.join(metrics, entry["name"] + ".json")
        assert os.path.isfile(path), entry["name"]
        with open(path) as f:
            spec = json.load(f)
        key = (entry["moves"], json.dumps(spec, sort_keys=True))
        assert key not in seen, (entry["name"], seen[key])
        seen[key] = entry["name"]
    listed = {e["name"] + ".json" for g in ("end_to_end", "per_layer")
              for e in man[g]}
    assert set(os.listdir(metrics)) == listed


def test_a_fifth_serving_cell_joins_by_data_alone(tmp_path, monkeypatch):
    """On a copy of the benchmark: a cell appended to ``workloads``, to
    its end-to-end metric's list and to every shared per-layer list, with
    one entry and file of its own, resolves like the cells that are there;
    one entry past the driver's 128 does not."""
    import shutil

    copy = tmp_path / "benchmark"
    shutil.copytree(HERE, copy, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    man = manifest.load()
    # the end-to-end metric several cells share, and the first of them
    rate = next(m for m in man["end_to_end"]
                if len(m.get("workloads", [])) > 1)
    closed = list(rate["workloads"])
    donor = manifest.cell(man, closed[0])
    shared = [m["name"] for m in man["per_layer"]
              if m.get("workloads") == closed]
    assert len(shared) > 10
    new = dict(donor, name="fifth_serve_cell", traffic="fifth")
    shutil.copy(copy / "traffic" / (donor["traffic"] + ".json"),
                copy / "traffic" / "fifth.json")
    shutil.copy(copy / "limits" / (donor["name"] + ".json"),
                copy / "limits" / "fifth_serve_cell.json")

    def own_entry(name):
        entry = {"name": name, "unit": "count", "better": "lower",
                 "source": "program_counter", "layer": "device",
                 "moves": rate["name"], "workloads": [new["name"]]}
        spec = {k: entry[k] for k in ("unit", "layer", "moves", "source")}
        spec.update(reader="counter", args={"counter": name})
        (copy / "metrics" / (name + ".json")).write_text(json.dumps(spec))
        return entry

    own = own_entry("passes_a_block.fifth")
    grown = json.loads(json.dumps(man))
    grown["workloads"].append(new)
    for m in grown["end_to_end"] + grown["per_layer"]:
        if m.get("workloads") == closed:
            m["workloads"].append(new["name"])
    grown["per_layer"].append(own)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(grown))

    monkeypatch.setattr(manifest, "HERE", str(copy))
    got = manifest.load(str(tmp_path))
    cell = manifest.cell(got, new["name"])
    assert manifest.config_of(got, cell, str(tmp_path))
    assert manifest.kind_of(manifest.traffic_of(cell)).run

    def names(cell_name, group="per_layer"):
        return [e["name"] for e, _, _ in
                manifest.metrics_for(got, cell_name, group)]

    assert names(new["name"], "end_to_end") == names(donor["name"],
                                                     "end_to_end")
    theirs = set(names(donor["name"]))
    mine = set(names(new["name"]))
    assert set(shared) <= mine and set(shared) <= theirs
    assert mine - theirs == {own["name"]}
    _no_copies(got, str(tmp_path))

    # the driver's cap: 128 entries pass, one more does not
    while len(got["per_layer"]) < 129:
        got["per_layer"].append(
            own_entry(f"filler_{len(got['per_layer'])}.fifth"))
    with pytest.raises(AssertionError, match="129"):
        _no_copies(got, str(tmp_path))
    os.remove(copy / "metrics" / (got["per_layer"].pop()["name"] + ".json"))
    _no_copies(got, str(tmp_path))


@pytest.mark.parametrize("bad", ["", "a b", "a,b", "a/b", "-a", ".a",
                                 "x" * 65, "µs"])
def test_forbidden_names(bad):
    with pytest.raises(manifest.BadManifest):
        manifest.check_name(bad, "name")


@pytest.mark.parametrize("bad", ["", "tokens per second", "µs",
                                 "x" * 17, "a,b"])
def test_forbidden_units(bad):
    with pytest.raises(manifest.BadManifest):
        manifest.check_unit(bad, "metric")


def test_duplicate_and_missing(tmp_path):
    man = manifest.load()
    broken = json.loads(json.dumps(man))
    broken["workloads"].append(dict(broken["workloads"][0]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(broken))
    with pytest.raises(manifest.BadManifest, match="twice"):
        manifest.load(str(tmp_path))
    with pytest.raises(manifest.BadManifest, match="no workload"):
        manifest.cell(man, "no_such_cell")
    ghost = {"name": "g", "config": man["configs"][0]["name"],
             "traffic": "no_such_mix", "chips": 1}
    with pytest.raises(manifest.BadManifest, match="no file"):
        manifest.traffic_of(ghost)
    with pytest.raises(manifest.BadManifest, match="no file"):
        manifest.kind_of({"kind": "no_such_kind"})
    orphan = json.loads(json.dumps(man))
    orphan["per_layer"].append({"name": "no_such_metric", "unit": "ms"})
    with pytest.raises(manifest.BadManifest, match="no file"):
        manifest.metrics_for(orphan, man["workloads"][0]["name"],
                             "per_layer")


def test_unknown_device_kind_is_an_error(capsys):
    assert device.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit) as e:
        device.peaks_for("TPU v9 imaginary")
    assert e.value.code == 3
    assert "peaks.json" in capsys.readouterr().out
    with pytest.raises(SystemExit):
        device.peaks_for("source")      # a key of the file, not a device


def test_no_accelerator_prints_no_result():
    """On the CPU the command exits non-zero and prints no result line."""
    man = manifest.load()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         man["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode == 3, out.stderr[-2000:]
    assert "no accelerator" in out.stdout
    assert not out.stdout.rstrip().endswith("}")


def test_no_name_of_the_manifest_in_the_code():
    man = manifest.load()
    names = {e["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in man[g]}
    names |= {c["traffic"] for c in man["workloads"]}
    files = [os.path.join(HERE, "run.py"), os.path.join(HERE, "serving.py"),
             os.path.join(HERE, "manifest.py")]
    kinds = os.path.join(HERE, "kinds")
    files += [os.path.join(kinds, f) for f in os.listdir(kinds)
              if f.endswith(".py")]
    for path in files:
        with open(path) as f:
            words = set(re.findall(r"[A-Za-z0-9_.-]+", f.read()))
        # ``setup_s`` is the one metric the contract itself names
        assert not (names - {"setup_s"}) & words, path


def test_without_the_program_there_is_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    own files the command exits non-zero and prints no result."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = manifest.load()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         man["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert "program is not in this checkout" in out.stdout
    assert not out.stdout.rstrip().endswith("}")
