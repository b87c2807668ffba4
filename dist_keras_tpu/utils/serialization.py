"""Model / object serialization.

Parity with the reference's ``distkeras/utils.py``:

- ``serialize_keras_model`` / ``deserialize_keras_model`` (utils.py:~40/~55):
  the reference stores ``{'model': model.to_json(), 'weights':
  model.get_weights()}``.  We keep the exact same dict contract — ``'model'``
  is an architecture-JSON string and ``'weights'`` a flat list of numpy
  arrays — so user code that inspects the serialized form keeps working.
- ``pickle_object`` / ``unpickle_object`` (utils.py:~170).
- ``uniform_weights`` (utils.py:~75): re-initialise all weights uniformly in
  ``bounds``.

TPU-first difference: deserialization produces our JAX-native ``Model`` whose
parameters are a pytree; weights cross the boundary as host numpy arrays so a
serialized model is device-free and picklable.
"""

from __future__ import annotations

import pickle

import jax
import numpy as np


def serialize_model(model):
    """Model -> picklable dict, same contract as utils.py:~40."""
    return {
        "model": model.to_json(),
        "weights": [np.asarray(w) for w in model.get_weights()],
    }


def deserialize_model(d):
    """dict -> Model, same contract as utils.py:~55.

    Native ``Sequential`` JSON deserializes directly; anything else is
    treated as Keras 3 architecture JSON and comes back wrapped in
    ``KerasModelAdapter`` (same trainer-facing contract).
    """
    import json

    from dist_keras_tpu.models.families import DECODERS
    from dist_keras_tpu.models.model import model_from_json

    arch = json.loads(d["model"])
    if arch.get("class_name") in DECODERS:
        # a decoder ``DecodeEngine`` serves: a ``cfg`` in, its weights after
        model = DECODERS[arch["class_name"]](cfg=arch["config"])
        model.set_weights(d["weights"])
        return model
    if arch.get("class_name") == "Sequential" and "layers" in arch and all(
            "class_name" in spec for spec in arch["layers"]):
        try:
            model = model_from_json(d["model"])
        except KeyError:
            model = None  # layer classes not ours -> fall through to Keras
        if model is not None:
            model.set_weights(d["weights"])
            return model
    from dist_keras_tpu.models.keras_adapter import from_keras_json

    return from_keras_json(d["model"], d["weights"])


# Reference-spelled aliases so a dist-keras user finds the names they know.
serialize_keras_model = serialize_model
deserialize_keras_model = deserialize_model


def pickle_object(o):
    """utils.py:~170 — object -> bytes."""
    return pickle.dumps(o, protocol=pickle.HIGHEST_PROTOCOL)


def unpickle_object(b):
    """utils.py:~170 — bytes -> object."""
    return pickle.loads(b)


def uniform_weights(model, bounds=(-0.5, 0.5), seed=0):
    """utils.py:~75 — re-init every weight array uniformly in ``bounds``.

    Returns the model (weights replaced in place, reference-style).
    """
    low, high = bounds
    rng = np.random.default_rng(seed)
    new = [rng.uniform(low, high, size=np.shape(w)).astype(np.asarray(w).dtype)
           for w in model.get_weights()]
    model.set_weights(new)
    return model


def to_host(tree):
    """Device pytree -> numpy pytree (for checkpoint / wire / collect)."""
    return jax.tree.map(np.asarray, tree)
