"""Seeded weights, made on the device, in the parameter layout the
program's transformer entry points take (``proj``/``pos``/``blocks``/
``ln_f``/``head``).

The benchmark makes the weights and hands the same ones to the program
and, layer by layer, to the plain reference: neither takes anything the
other has made.  Every leaf is a function of (seed, layer, leaf name)
alone, so one layer can be made again without the others.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_BLOCK_LEAVES = ("wq", "wk", "wv", "wo", "w1", "w2")


def base_key(seed):
    """A key from any whole-number seed (the driver's pass 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _uniform(key, shape, fan_in, fan_out):
    limit = np.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, jnp.float32, -limit, limit)


def block(key, layer, d, heads, ff):
    """One pre-LN block's leaves.  Biases and layer-norm offsets are zero
    (the published model has none), scales one."""
    dh = d // heads
    ks = dict(zip(_BLOCK_LEAVES, jax.random.split(
        jax.random.fold_in(key, 1 + layer), len(_BLOCK_LEAVES))))
    return {
        "ln1": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
        "wq": _uniform(ks["wq"], (d, heads, dh), d, d),
        "wk": _uniform(ks["wk"], (d, heads, dh), d, d),
        "wv": _uniform(ks["wv"], (d, heads, dh), d, d),
        "wo": _uniform(ks["wo"], (heads, dh, d), d, d),
        "ln2": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
        "w1": _uniform(ks["w1"], (d, ff), d, ff),
        "b1": jnp.zeros((ff,)),
        "w2": _uniform(ks["w2"], (ff, d), ff, d),
        "b2": jnp.zeros((d,)),
    }


def outer(key, input_dim, positions, d, n_classes):
    """Everything outside the blocks: input projection (the embedding
    when ``input_dim`` is the vocabulary), learned positions, final layer
    norm and the output head."""
    k_proj, k_pos, k_head = jax.random.split(jax.random.fold_in(key, 0), 3)
    return {
        "proj": _uniform(k_proj, (input_dim, d), input_dim, d),
        "pos": 0.02 * jax.random.normal(k_pos, (positions, d), jnp.float32),
        "ln_f": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
        "head": {"kernel": _uniform(k_head, (d, n_classes), d, n_classes),
                 "bias": jnp.zeros((n_classes,))},
    }


def transformer(key, cfg):
    """The whole parameter tree for a program ``transformer_config``."""
    d, heads, ff = cfg["d_model"], cfg["n_heads"], cfg["d_ff"]
    tree = outer(key, cfg["input_dim"], cfg["seq_len"], d, cfg["n_classes"])
    tree["blocks"] = [block(key, i, d, heads, ff)
                      for i in range(cfg["n_layers"])]
    return tree
