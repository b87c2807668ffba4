"""Functional layer library (Keras-flavoured surface, JAX-native core).

The reference builds models with Keras ``Sequential`` + ``Dense``/``Conv2D``
etc. and ships them to workers as (architecture JSON, weight list)
(``distkeras/utils.py:~40``).  We reproduce that *surface* — layers with the
familiar constructor args, JSON round-trip, Keras-ordered weight lists — on a
functional core: every layer is stateless, with

    params, out_shape = layer.init(key, in_shape)
    y = layer.apply(params, x, training=..., rng=...)

so a whole model is a pure function of a params pytree: exactly what
``jax.jit`` / ``shard_map`` / ``jax.grad`` want.

TPU notes:
- Default parameter dtype is float32; compute casting to bf16 is applied by
  trainers via a policy, keeping the MXU fed with bf16 matmuls while the
  optimizer state stays f32.
- ``Conv2D`` uses NHWC, the layout XLA:TPU prefers.
- No Python control flow depends on data; dropout uses ``jax.random`` with an
  explicit rng.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import nn as jnn

# --------------------------------------------------------------------------
# activations
# --------------------------------------------------------------------------

_ACTIVATIONS = {
    "linear": lambda x: x,
    "relu": jnn.relu,
    "tanh": jnp.tanh,
    "sigmoid": jnn.sigmoid,
    "softmax": lambda x: jnn.softmax(x, axis=-1),
    "gelu": jnn.gelu,
    "elu": jnn.elu,
    "softplus": jnn.softplus,
    "leaky_relu": jnn.leaky_relu,
    "silu": jnn.silu,
}


def get_activation(name):
    if name is None:
        return _ACTIVATIONS["linear"]
    if callable(name):
        return name
    try:
        return _ACTIVATIONS[name]
    except KeyError:
        raise ValueError(f"Unknown activation {name!r}") from None


# --------------------------------------------------------------------------
# initializers (Keras defaults)
# --------------------------------------------------------------------------

def glorot_uniform(key, shape, dtype=jnp.float32):
    fan_in, fan_out = _fans(shape)
    limit = np.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, dtype, -limit, limit)


def _fans(shape):
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    # conv kernels: (kh, kw, in, out)
    receptive = int(np.prod(shape[:-2]))
    return shape[-2] * receptive, shape[-1] * receptive


# --------------------------------------------------------------------------
# expert selection (parallel/moe.py at k = 1, models/mla_moe.py at k > 1)
# --------------------------------------------------------------------------

def select_top_k(scores, bias, k):
    """Choose ``k`` experts a token by ``scores + bias`` -> (ids (N, k)
    int32, the chosen ``scores`` WITHOUT the bias (N, k)); of equal
    scores the first wins."""
    biased = scores if bias is None else scores + bias
    _, idx = lax.top_k(biased, k)
    return idx.astype(jnp.int32), jnp.take_along_axis(scores, idx, -1)


# --------------------------------------------------------------------------
# layer base + registry
# --------------------------------------------------------------------------

LAYER_REGISTRY = {}


def register_layer(cls):
    LAYER_REGISTRY[cls.__name__] = cls
    return cls


class Layer:
    """Stateless layer: config in the object, parameters in a pytree."""

    def init(self, key, in_shape):
        """-> (params, out_shape). in/out shapes exclude the batch dim."""
        return {}, in_shape

    def apply(self, params, x, *, training=False, rng=None):
        return x

    def apply_with_state(self, params, x, *, training=False, rng=None):
        """-> (y, state_update).  ``state_update`` maps the layer's state
        leaves (see ``state_names``) to their post-batch values; stateless
        layers return an empty dict.  This is the aux-state channel the
        trainers thread through their scans (see trainers/step.py)."""
        return self.apply(params, x, training=training, rng=rng), {}

    # ---- state leaves (non-trainable, updated via the aux channel) ----
    def state_names(self):
        """Parameter names that are running state, not trainable weights."""
        return ()

    # ---- config round-trip (Keras `get_config` / `from_config` parity) ----
    def get_config(self):
        return {}

    @classmethod
    def from_config(cls, config):
        return cls(**config)

    # ---- weight ordering (Keras: kernel then bias, layer by layer) ----
    def weight_names(self):
        """Ordered parameter names for get_weights/set_weights."""
        return []

    def __repr__(self):
        cfg = ", ".join(f"{k}={v!r}" for k, v in self.get_config().items())
        return f"{type(self).__name__}({cfg})"


@register_layer
class Dense(Layer):
    def __init__(self, units, activation=None, use_bias=True):
        self.units = int(units)
        self.activation = activation
        self.use_bias = bool(use_bias)

    def init(self, key, in_shape):
        in_dim = in_shape[-1]
        params = {"kernel": glorot_uniform(key, (in_dim, self.units))}
        if self.use_bias:
            params["bias"] = jnp.zeros((self.units,), jnp.float32)
        return params, (*in_shape[:-1], self.units)

    def apply(self, params, x, *, training=False, rng=None):
        y = x @ params["kernel"]
        if self.use_bias:
            y = y + params["bias"]
        return get_activation(self.activation)(y)

    def get_config(self):
        return {"units": self.units, "activation": self.activation,
                "use_bias": self.use_bias}

    def weight_names(self):
        return ["kernel", "bias"] if self.use_bias else ["kernel"]


def _conv_im2col(x, kernel, strides, padding):
    """NHWC conv as shifted-slice im2col + one matmul, or None if the
    config isn't supported.

    XLA:CPU pathology (measured on this image): the *gradient* convs
    (weight-grad / input-grad) inside a rolled ``lax.scan`` body lose the
    Eigen fast path and run ~80x slower than the same ops unrolled — which
    made every scanned CNN epoch unusable on the CPU test harness.  Slices
    and matmuls keep their fast paths (and their VJPs are slices/matmuls
    again), so on the CPU backend convs are lowered this way; TPU keeps the
    native MXU conv above.  Numerically identical to lax conv (~1e-7).
    """
    kh, kw, cin, cout = kernel.shape
    sh, sw = strides
    n, h, w, _ = x.shape
    if padding == "SAME":
        oh, ow = -(-h // sh), -(-w // sw)
        ph = max(0, (oh - 1) * sh + kh - h)
        pw = max(0, (ow - 1) * sw + kw - w)
        x = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                        (pw // 2, pw - pw // 2), (0, 0)))
    elif padding == "VALID":
        oh, ow = (h - kh) // sh + 1, (w - kw) // sw + 1
    else:
        return None
    if oh <= 0 or ow <= 0:
        return None
    cols = jnp.concatenate(
        [x[:, i:i + sh * (oh - 1) + 1:sh, j:j + sw * (ow - 1) + 1:sw, :]
         for i in range(kh) for j in range(kw)], axis=-1)
    return cols @ kernel.reshape(kh * kw * cin, cout).astype(cols.dtype)


@register_layer
class Conv2D(Layer):
    """NHWC conv. Kernel layout HWIO (XLA:TPU native)."""

    def __init__(self, filters, kernel_size, strides=(1, 1), padding="valid",
                 activation=None, use_bias=True):
        self.filters = int(filters)
        self.kernel_size = tuple(np.broadcast_to(kernel_size, (2,)).tolist())
        self.strides = tuple(np.broadcast_to(strides, (2,)).tolist())
        self.padding = padding
        self.activation = activation
        self.use_bias = bool(use_bias)

    def init(self, key, in_shape):
        h, w, c = in_shape
        kh, kw = self.kernel_size
        params = {"kernel": glorot_uniform(key, (kh, kw, c, self.filters))}
        if self.use_bias:
            params["bias"] = jnp.zeros((self.filters,), jnp.float32)
        out = jax.eval_shape(
            lambda k: self._conv(jnp.zeros((1, h, w, c)), k),
            jax.ShapeDtypeStruct((kh, kw, c, self.filters), jnp.float32),
        )
        return params, tuple(out.shape[1:])

    def _conv(self, x, kernel):
        if jax.default_backend() == "cpu":
            y = _conv_im2col(x, kernel, self.strides, self.padding.upper())
            if y is not None:
                return y
        return lax.conv_general_dilated(
            x, kernel, window_strides=self.strides,
            padding=self.padding.upper(),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    def apply(self, params, x, *, training=False, rng=None):
        y = self._conv(x, params["kernel"].astype(x.dtype))
        if self.use_bias:
            y = y + params["bias"].astype(y.dtype)
        return get_activation(self.activation)(y)

    def get_config(self):
        return {"filters": self.filters, "kernel_size": self.kernel_size,
                "strides": self.strides, "padding": self.padding,
                "activation": self.activation, "use_bias": self.use_bias}

    def weight_names(self):
        return ["kernel", "bias"] if self.use_bias else ["kernel"]


class _Pool2D(Layer):
    _reducer = None
    _init_val = None
    _np_reducer = None

    def __init__(self, pool_size=(2, 2), strides=None, padding="valid"):
        self.pool_size = tuple(np.broadcast_to(pool_size, (2,)).tolist())
        self.strides = (tuple(np.broadcast_to(strides, (2,)).tolist())
                        if strides is not None else self.pool_size)
        self.padding = padding

    def init(self, key, in_shape):
        h, w, c = in_shape
        out = jax.eval_shape(
            lambda: self.apply({}, jnp.zeros((1, h, w, c))))
        return {}, tuple(out.shape[1:])

    def _pool(self, x):
        ph, pw = self.pool_size
        sh, sw = self.strides
        n, h, w, c = x.shape
        # Non-overlapping, evenly-dividing windows (the common CNN case)
        # reduce over a reshape: same forward result as reduce_window, but
        # the VJP is slices/broadcasts instead of select-and-scatter —
        # which, like grad-convs, collapses off the fast path inside
        # scanned loop bodies on XLA:CPU (see _conv_im2col).  VJP caveat:
        # at *tied* window maxima jnp.max splits the cotangent evenly
        # while select-and-scatter routes it all to the first maximum;
        # both are valid subgradients but trajectories can differ on
        # quantized/replicated activations.
        if (jax.default_backend() == "cpu"
                and (sh, sw) == (ph, pw) and h % ph == 0 and w % pw == 0
                and self._np_reducer is not None):
            xr = x.reshape(n, h // ph, ph, w // pw, pw, c)
            return self._np_reducer(xr, axis=(2, 4))
        return lax.reduce_window(
            x, self._init_val, self._reducer,
            window_dimensions=(1, ph, pw, 1),
            window_strides=(1, sh, sw, 1),
            padding=self.padding.upper(),
        )

    def get_config(self):
        return {"pool_size": self.pool_size, "strides": self.strides,
                "padding": self.padding}


@register_layer
class MaxPool2D(_Pool2D):
    _np_reducer = staticmethod(jnp.max)

    def apply(self, params, x, *, training=False, rng=None):
        self._reducer = lax.max
        self._init_val = -jnp.inf
        return self._pool(x)


@register_layer
class AvgPool2D(_Pool2D):
    _np_reducer = staticmethod(jnp.sum)

    def apply(self, params, x, *, training=False, rng=None):
        self._reducer = lax.add
        self._init_val = 0.0
        summed = self._pool(x)
        ph, pw = self.pool_size
        if self.padding.upper() == "VALID":
            return summed / (ph * pw)
        # 'same': Keras/TF average pooling divides by the number of VALID
        # (non-padded) positions in each window, not the full window size —
        # pool an all-ones tensor to get that count per output position.
        counts = self._pool(jnp.ones_like(x))
        return summed / counts


@register_layer
class Flatten(Layer):
    def init(self, key, in_shape):
        return {}, (int(np.prod(in_shape)),)

    def apply(self, params, x, *, training=False, rng=None):
        return x.reshape(x.shape[0], -1)


@register_layer
class Reshape(Layer):
    def __init__(self, target_shape):
        self.target_shape = tuple(target_shape)

    def init(self, key, in_shape):
        return {}, self.target_shape

    def apply(self, params, x, *, training=False, rng=None):
        return x.reshape(x.shape[0], *self.target_shape)

    def get_config(self):
        return {"target_shape": self.target_shape}


@register_layer
class Activation(Layer):
    def __init__(self, activation):
        self.activation = activation

    def apply(self, params, x, *, training=False, rng=None):
        return get_activation(self.activation)(x)

    def get_config(self):
        return {"activation": self.activation}


@register_layer
class Dropout(Layer):
    def __init__(self, rate):
        self.rate = float(rate)

    def apply(self, params, x, *, training=False, rng=None):
        if not training or self.rate <= 0.0:
            return x
        if rng is None:
            raise ValueError("Dropout needs an rng when training=True")
        keep = 1.0 - self.rate
        thresh = int(round(keep * 256))
        if abs(thresh - keep * 256) < 1e-9 and 0 < thresh < 256:
            # keep-rates expressible in 8 bits (0.25/0.5/0.75, the Keras
            # staples): threshold uint8 random bits — mask generation is
            # random-bit-bound on the VPU and 8-bit words quarter the
            # threefry work (~30% cheaper masks measured on v5e);
            # P(bits < thresh) = thresh/256 = keep, exactly.
            # RNG-STREAM NOTE (round 3 change): this path samples a
            # DIFFERENT mask stream than jax.random.bernoulli for the
            # same key, so runs/checkpoints spanning the round-3 commit
            # do not reproduce bit-identically at these rates (keep-rate
            # itself is exact and tested)
            bits = jax.random.bits(rng, x.shape, jnp.uint8)
            mask = bits < thresh
        else:
            mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype)

    def get_config(self):
        return {"rate": self.rate}


@register_layer
class LayerNorm(Layer):
    def __init__(self, epsilon=1e-5):
        self.epsilon = float(epsilon)

    def init(self, key, in_shape):
        dim = in_shape[-1]
        return {"scale": jnp.ones((dim,), jnp.float32),
                "bias": jnp.zeros((dim,), jnp.float32)}, in_shape

    def apply(self, params, x, *, training=False, rng=None):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mu) * lax.rsqrt(var + self.epsilon)
        return y * params["scale"].astype(x.dtype) + params["bias"].astype(x.dtype)

    def get_config(self):
        return {"epsilon": self.epsilon}

    def weight_names(self):
        return ["scale", "bias"]


@register_layer
class BatchNorm(Layer):
    """Batch normalisation.

    Functional twist: running statistics are *parameters* (leaves named
    ``moving_mean``/``moving_var``, flagged by ``state_names``) updated by
    the trainers through the aux-state channel: ``apply_with_state`` returns
    the momentum-blended stats each training batch and the step machinery
    folds them back into the params pytree (the optimizer never touches
    them — see ``split_state`` in models/model.py).  In training mode the
    layer normalises with batch statistics; in inference mode with the
    stored moving stats — matching Keras ``BatchNormalization``.
    """

    def __init__(self, momentum=0.99, epsilon=1e-3):
        self.momentum = float(momentum)
        self.epsilon = float(epsilon)

    def init(self, key, in_shape):
        dim = in_shape[-1]
        return {
            "gamma": jnp.ones((dim,), jnp.float32),
            "beta": jnp.zeros((dim,), jnp.float32),
            "moving_mean": jnp.zeros((dim,), jnp.float32),
            "moving_var": jnp.ones((dim,), jnp.float32),
        }, in_shape

    def _stats(self, params, x, training):
        axes = tuple(range(x.ndim - 1))
        if training:
            return jnp.mean(x, axis=axes), jnp.var(x, axis=axes)
        return params["moving_mean"], params["moving_var"]

    def _norm(self, params, x, mu, var):
        y = (x - mu.astype(x.dtype)) * lax.rsqrt(
            var.astype(x.dtype) + self.epsilon)
        return (y * params["gamma"].astype(x.dtype)
                + params["beta"].astype(x.dtype))

    def apply(self, params, x, *, training=False, rng=None):
        mu, var = self._stats(params, x, training)
        return self._norm(params, x, mu, var)

    def apply_with_state(self, params, x, *, training=False, rng=None):
        mu, var = self._stats(params, x, training)
        y = self._norm(params, x, mu, var)
        if not training:
            return y, {}
        # Blend in f32 regardless of the compute dtype: with momentum 0.99
        # the per-batch increment is below bf16 resolution and would be
        # rounded away.  The stored moving stats are never cast (state
        # leaves are exempt from the compute-dtype policy).
        m = self.momentum
        new_mean = (m * params["moving_mean"].astype(jnp.float32)
                    + (1.0 - m) * mu.astype(jnp.float32))
        new_var = (m * params["moving_var"].astype(jnp.float32)
                   + (1.0 - m) * var.astype(jnp.float32))
        return y, {"moving_mean": jax.lax.stop_gradient(new_mean),
                   "moving_var": jax.lax.stop_gradient(new_var)}

    def get_config(self):
        return {"momentum": self.momentum, "epsilon": self.epsilon}

    def weight_names(self):
        return ["gamma", "beta", "moving_mean", "moving_var"]

    def state_names(self):
        return ("moving_mean", "moving_var")


@register_layer
class Embedding(Layer):
    def __init__(self, input_dim, output_dim):
        self.input_dim = int(input_dim)
        self.output_dim = int(output_dim)

    def init(self, key, in_shape):
        table = jax.random.normal(
            key, (self.input_dim, self.output_dim)) * 0.02
        return {"embeddings": table}, (*in_shape, self.output_dim)

    def apply(self, params, x, *, training=False, rng=None):
        return jnp.take(params["embeddings"], x.astype(jnp.int32), axis=0)

    def get_config(self):
        return {"input_dim": self.input_dim, "output_dim": self.output_dim}

    def weight_names(self):
        return ["embeddings"]
