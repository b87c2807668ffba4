"""Sequential model container with Keras-surface parity.

The reference's models are Keras ``Sequential`` instances that cross process
boundaries as (architecture JSON, flat weight list) — see
``distkeras/utils.py:~40-70``.  This module provides the same contract:

- ``Sequential([...layers]).build(input_shape)`` — creates the params pytree.
- ``model.to_json()`` / ``model_from_json(js)`` — architecture round-trip.
- ``model.get_weights()`` / ``set_weights(list)`` — Keras-ordered flat numpy
  weight lists (kernel then bias, layer by layer).
- ``model(x)`` / ``model.predict(x)`` — inference.

JAX-native core: the model is a *pure function* ``model.apply(params, x)``;
``model.params`` is just a convenience pointer used by the stateful Keras-like
helpers.  Trainers operate exclusively on ``(apply_fn, params)``.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from dist_keras_tpu.models.layers import LAYER_REGISTRY, Layer


class Sequential:
    def __init__(self, layers=None, name="sequential"):
        self.layers = list(layers or [])
        self.name = name
        self.input_shape = None   # sans batch dim
        self.output_shape = None
        self.params = None        # list of per-layer param dicts

    def add(self, layer: Layer):
        self.layers.append(layer)

    # ------------------------------------------------------------------
    # build / init
    # ------------------------------------------------------------------
    def build(self, input_shape, seed=0):
        """Initialise parameters for ``input_shape`` (no batch dim).

        Init runs on the HOST CPU backend and the params are materialized
        as numpy: a freshly-built model is device-free (the reference
        builds on the Spark driver the same way), so serialize_model
        never round-trips weights through the accelerator.  Trainers
        ship the numpy params with ONE device_put when training starts."""
        try:
            # local_devices, not devices: on a multi-process group the
            # latter's device 0 belongs to process 0, and pinning another
            # process's default_device to it routes this purely-local
            # init through cross-host Gloo collectives (which time out)
            cpu = jax.local_devices(backend="cpu")[0]
        except RuntimeError:  # pragma: no cover - cpu platform disabled
            cpu = None
        if cpu is not None:
            with jax.default_device(cpu):
                params = self.init(jax.random.PRNGKey(seed),
                                   tuple(input_shape))
        else:
            params = self.init(jax.random.PRNGKey(seed),
                               tuple(input_shape))
        import numpy as _np

        self.params = jax.tree.map(_np.asarray, params)
        return self

    def init(self, key, input_shape):
        """Pure init: -> list of per-layer param dicts (the params pytree)."""
        self.input_shape = tuple(input_shape)
        params = []
        shape = tuple(input_shape)
        keys = jax.random.split(key, max(len(self.layers), 1))
        for layer, k in zip(self.layers, keys):
            p, shape = layer.init(k, shape)
            params.append(p)
        self.output_shape = shape
        return params

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------
    def apply(self, params, x, *, training=False, rng=None):
        """Pure forward pass over the whole stack."""
        if rng is not None:
            rngs = jax.random.split(rng, max(len(self.layers), 1))
        for i, (layer, p) in enumerate(zip(self.layers, params)):
            r = rngs[i] if rng is not None else None
            x = layer.apply(p, x, training=training, rng=r)
        return x

    def apply_with_state(self, params, x, *, training=False, rng=None):
        """Forward pass returning ``(y, states)`` where ``states`` is a
        per-layer list of state-leaf updates (empty dicts for stateless
        layers) — the aux-state channel consumed by trainers/step.py so
        BatchNorm moving statistics actually advance during training."""
        if rng is not None:
            rngs = jax.random.split(rng, max(len(self.layers), 1))
        states = []
        for i, (layer, p) in enumerate(zip(self.layers, params)):
            r = rngs[i] if rng is not None else None
            x, s = layer.apply_with_state(p, x, training=training, rng=r)
            states.append(s)
        return x, states

    # ------------------------------------------------------------------
    # aux-state channel (BatchNorm moving stats & co.)
    # ------------------------------------------------------------------
    def has_state(self):
        return any(layer.state_names() for layer in self.layers)

    def split_state(self, params):
        """params -> (trainable, state): two parallel per-layer dict lists.
        The optimizer only ever sees ``trainable``; ``state`` is advanced by
        ``apply_with_state`` and rejoined with ``join_state``."""
        trainable, state = [], []
        for layer, p in zip(self.layers, params):
            names = set(layer.state_names())
            trainable.append({k: v for k, v in p.items() if k not in names})
            state.append({k: v for k, v in p.items() if k in names})
        return trainable, state

    def join_state(self, trainable, state):
        return [{**t, **s} for t, s in zip(trainable, state)]

    def cast_params(self, params, dtype):
        """Compute-dtype cast that leaves state leaves (moving stats) in
        f32 — their momentum blend needs more resolution than bf16."""
        from dist_keras_tpu.utils.pytree import tree_cast

        trainable, state = self.split_state(params)
        return self.join_state(tree_cast(trainable, dtype), state)

    def __call__(self, x, *, training=False, rng=None):
        self._require_built()
        return self.apply(self.params, jnp.asarray(x), training=training, rng=rng)

    def predict(self, x, batch_size=None):
        """Host-facing inference -> numpy (Keras ``model.predict`` parity)."""
        self._require_built()
        x = np.asarray(x)
        if batch_size is None or len(x) <= batch_size:
            return np.asarray(self(x))
        outs = [np.asarray(self(x[i:i + batch_size]))
                for i in range(0, len(x), batch_size)]
        return np.concatenate(outs, axis=0)

    # ------------------------------------------------------------------
    # weights (Keras flat-list contract)
    # ------------------------------------------------------------------
    def get_weights(self):
        self._require_built()
        out = []
        for layer, p in zip(self.layers, self.params):
            for name in layer.weight_names():
                out.append(np.asarray(p[name]))
        return out

    def set_weights(self, weights):
        self._require_built()
        weights = list(weights)
        idx = 0
        new_params = []
        for layer, p in zip(self.layers, self.params):
            q = dict(p)
            for name in layer.weight_names():
                w = np.asarray(weights[idx])
                want = tuple(np.shape(p[name]))
                if tuple(w.shape) != want:
                    raise ValueError(
                        f"weight {idx} for {layer!r}.{name}: shape "
                        f"{w.shape} != {want}")
                q[name] = jnp.asarray(w, dtype=p[name].dtype)
                idx += 1
            new_params.append(q)
        if idx != len(weights):
            raise ValueError(f"got {len(weights)} weights, used {idx}")
        self.params = new_params

    def set_params(self, params):
        """Install a params pytree (trainer output) directly."""
        self.params = jax.tree.map(jnp.asarray, params)

    def _require_built(self):
        if self.params is None:
            raise RuntimeError(
                "Model is not built; call .build(input_shape) first")

    # ------------------------------------------------------------------
    # JSON round-trip (utils.py:~40 contract)
    # ------------------------------------------------------------------
    def to_json(self):
        return json.dumps({
            "class_name": "Sequential",
            "name": self.name,
            "input_shape": self.input_shape,
            "layers": [
                {"class_name": type(l).__name__, "config": l.get_config()}
                for l in self.layers
            ],
        })

    def summary(self):
        lines = [f"Model: {self.name}", "-" * 60]
        shape = self.input_shape
        for layer in self.layers:
            lines.append(f"{type(layer).__name__:<20} {layer.get_config()}")
        if self.params is not None:
            n = sum(int(np.prod(np.shape(w))) for w in self.get_weights())
            lines.append("-" * 60)
            lines.append(f"Total params: {n:,}")
        return "\n".join(lines)

    @property
    def count_params(self):
        return sum(int(np.prod(np.shape(w))) for w in self.get_weights())


def model_from_json(js):
    """Architecture JSON -> built Sequential (fresh weights if input_shape
    was recorded; call set_weights to restore trained ones)."""
    d = json.loads(js)
    if d.get("class_name") != "Sequential":
        raise ValueError(f"Unsupported class {d.get('class_name')!r}")
    layers = []
    for spec in d["layers"]:
        cls = LAYER_REGISTRY[spec["class_name"]]
        layers.append(cls.from_config(spec["config"]))
    m = Sequential(layers, name=d.get("name", "sequential"))
    if d.get("input_shape") is not None:
        m.build(tuple(d["input_shape"]))
    return m
