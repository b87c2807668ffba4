"""Utility layer: pytree algebra, serialization, misc helpers.

Parity surface of the reference's ``distkeras/utils.py`` plus TPU-native
pytree helpers used throughout the framework.

Submodules resolve LAZILY (PEP 562): ``serialization`` imports jax at
module level, but import-light consumers — ``observability.events``,
``resilience.faults`` — need :mod:`~dist_keras_tpu.utils.knobs` (the
stdlib-only env-knob registry) without paying for the device stack.
``from dist_keras_tpu.utils import tree_add`` still works: from-imports
fall back to the module ``__getattr__``.
"""

import importlib

_LAZY_MODULES = (
    "compile_cache", "knobs", "misc", "profiling", "pytree",
    "serialization", "sync",
)

_LAZY_NAMES = {
    # misc
    "history_average_loss": "misc",
    "new_dataframe_row": "misc",
    "precache": "misc",
    "shuffle": "misc",
    "to_vector": "misc",
    # pytree
    "tree_add": "pytree",
    "tree_axpy": "pytree",
    "tree_cast": "pytree",
    "tree_global_norm": "pytree",
    "tree_mean": "pytree",
    "tree_scale": "pytree",
    "tree_size": "pytree",
    "tree_sub": "pytree",
    "tree_zeros_like": "pytree",
    # serialization
    "deserialize_keras_model": "serialization",
    "deserialize_model": "serialization",
    "pickle_object": "serialization",
    "serialize_keras_model": "serialization",
    "serialize_model": "serialization",
    "to_host": "serialization",
    "unpickle_object": "serialization",
    "uniform_weights": "serialization",
}


def __getattr__(name):
    if name in _LAZY_MODULES:
        mod = importlib.import_module(f"{__name__}.{name}")
        globals()[name] = mod  # resolve once
        return mod
    sub = _LAZY_NAMES.get(name)
    if sub is None:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}")
    value = getattr(importlib.import_module(f"{__name__}.{sub}"), name)
    globals()[name] = value
    return value


def __dir__():
    return sorted(set(globals()) | set(_LAZY_MODULES)
                  | set(_LAZY_NAMES))


__all__ = [
    "tree_add", "tree_sub", "tree_scale", "tree_axpy", "tree_zeros_like",
    "tree_mean", "tree_global_norm", "tree_cast", "tree_size",
    "serialize_model", "deserialize_model", "serialize_keras_model",
    "deserialize_keras_model", "pickle_object", "unpickle_object",
    "uniform_weights", "to_host",
    "to_vector", "shuffle", "precache", "new_dataframe_row",
    "history_average_loss",
    "compile_cache", "knobs", "misc", "profiling", "pytree",
    "serialization", "sync",
]
