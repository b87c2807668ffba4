"""One number compared beside its limit, and the file of a cell's limits."""

from __future__ import annotations

import json
import math
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def limit(name, value, bound):
    """-> the check: it holds when ``value`` is finite and at most
    ``bound``."""
    value = float(value)
    return {"name": name, "value": value, "limit": bound,
            "ok": bool(math.isfinite(value) and value <= bound)}


def limits_for(cell_name):
    """The limits of ``limits/<cell>.json``, each set from readings that
    the file gives beside it."""
    with open(os.path.join(HERE, "limits", cell_name + ".json")) as f:
        return json.load(f)["limits"]
