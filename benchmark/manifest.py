"""``BENCHMARK.json`` and the files a cell's names resolve to.

A cell names a configuration and a traffic mix; the mix's ``kind`` names
the module that drives it; each metric names its reader.  Nothing here
knows any particular cell, configuration or metric.

How a cell joins (``PERF.md`` section 3): it appends its name to the
``workloads`` list of each shared entry whose reader's inputs its kind
produces, and brings entries and files of its own only for what is its own.
"""

from __future__ import annotations

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
# the sources whose readers take the program's own counters, spans and
# stamped histograms and so need no device trace
PROGRAM_SOURCES = ("program_counter", "program_span")


class BadManifest(ValueError):
    pass


def _json(path, what):
    if not os.path.isfile(path):
        raise BadManifest(f"{what}: no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def check_name(name, what):
    if not isinstance(name, str) or not NAME.match(name):
        raise BadManifest(f"{what} {name!r}: a name is 1-64 letters, "
                          "digits, '_', '.', '-' and starts with none of "
                          "'.', '-'")
    return name


def check_unit(unit, what):
    if not isinstance(unit, str) or not UNIT.match(unit):
        raise BadManifest(f"{what}: unit {unit!r} has a forbidden "
                          "character or length")
    return unit


def load(root=ROOT):
    """-> the manifest, with every name and unit checked."""
    man = _json(os.path.join(root, "BENCHMARK.json"), "manifest")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for entry in man[group]:
            name = check_name(entry["name"], group)
            if name in seen:
                raise BadManifest(f"{group}: {name!r} appears twice")
            seen.add(name)
            if "unit" in entry:
                check_unit(entry["unit"], name)
    for cell in man["workloads"]:
        check_name(cell["config"], "config")
        check_name(cell["traffic"], "traffic")
    return man


def cell(man, name):
    for entry in man["workloads"]:
        if entry["name"] == name:
            return entry
    raise BadManifest(f"no workload {name!r} in BENCHMARK.json")


def config_of(man, cell_entry, root=ROOT):
    for entry in man["configs"]:
        if entry["name"] == cell_entry["config"]:
            return _json(os.path.join(root, entry["file"]),
                         f"configuration {entry['name']}")
    raise BadManifest(f"workload {cell_entry['name']}: configuration "
                      f"{cell_entry['config']!r} is not in configs")


def traffic_of(cell_entry):
    return _json(os.path.join(HERE, "traffic",
                              cell_entry["traffic"] + ".json"),
                 f"traffic {cell_entry['traffic']}")


def _module(package, name, what):
    check_name(name, what)
    path = os.path.join(HERE, package, name + ".py")
    if not os.path.isfile(path):
        raise BadManifest(f"{what}: no file "
                          f"{os.path.relpath(path, ROOT)}")
    return importlib.import_module(f"benchmark.{package}.{name}")


def kind_of(traffic):
    return _module("kinds", traffic["kind"], "traffic kind")


def metrics_for(man, cell_name, group, sources=None):
    """The metrics of ``group`` this cell reports -> [(entry, spec,
    reader module)], ``spec`` being the metric's own file; with
    ``sources``, those alone whose ``source`` is one of them."""
    out = []
    for entry in man[group]:
        if "workloads" in entry and cell_name not in entry["workloads"]:
            continue
        if sources is not None and entry["source"] not in sources:
            continue
        spec = _json(os.path.join(HERE, "metrics", entry["name"] + ".json"),
                     f"metric {entry['name']}")
        out.append((entry, spec,
                    _module("readers", spec["reader"], "reader")))
    return out
