"""The chip a run is on: refusal without one, the table of peaks, memory."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class NoChip(SystemExit):
    """Raised (exit code 3, no result line) when the accelerator a cell
    asks for is not there.  Nothing falls back to the CPU."""

    def __init__(self, why):
        print(f"benchmark: {why}", flush=True)
        super().__init__(3)


def peaks_for(kind):
    """-> the peaks of ``device_kind``; an unknown kind is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table or not isinstance(table[kind], dict):
        raise NoChip(f"device_kind {kind!r} is not in peaks.json; add its "
                     "published peaks with their source before measuring")
    return table[kind]


def require(chips):
    """-> (devices, peaks) for a cell that needs ``chips`` accelerators."""
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform == "cpu":
        raise NoChip("JAX found no accelerator (platform cpu); a "
                     "measurement never falls back to the CPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices[:chips], peaks_for(first.device_kind)


def describe(devices, memory_peak_bytes):
    """The ``device`` object of the result line (without trace fields);
    the peak is the program's own, read before the reference ran."""
    first = devices[0]
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(memory_peak_bytes)}
