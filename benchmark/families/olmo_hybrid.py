"""The gated-delta-rule, full-attention family (``models/olmo_hybrid.py``):
the program's configuration from the benchmark's file, seeded weights in
the program's parameter layout, the engine with the configuration's count
of state rows, and the comparison with the plain reference
(``reference/olmo_hybrid_check.py``).

The benchmark makes the weights and hands the same ones to the program
and, layer by layer, to the reference; every leaf is a function of (seed,
layer, leaf name) alone, so one layer can be made again without the
others.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp

from benchmark import weights
from benchmark.families import mla_moe
from benchmark.families.mla_moe import _swiglu, _uniform
from benchmark.trace import opcount_olmo_hybrid

_LEAVES = ("wq", "wk", "wv", "wg", "wo", "wab", "conv", "a_log", "dt_bias",
           "ffn")
LINEAR = "linear_attention"


def model_config(conf):
    """The program's configuration of the served depth: the first
    ``num_hidden_layers.serve`` layers of the published pattern."""
    from dist_keras_tpu.models.olmo_hybrid import olmo_hybrid_config

    depth = conf["num_hidden_layers"]["serve"]
    return olmo_hybrid_config(
        vocab_size=conf["vocab_size"], seq_len=conf["serve"]["positions"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        d_ff=conf["intermediate_size"],
        layer_types=conf["layer_types"][:depth],
        linear_heads=conf["linear_num_value_heads"],
        linear_key_dim=conf["linear_key_head_dim"],
        linear_value_dim=conf["linear_value_head_dim"],
        conv_kernel=conf["linear_conv_kernel_dim"],
        allow_neg_eigval=conf["linear_allow_neg_eigval"],
        norm_eps=conf["rms_norm_eps"])


def reference_config(cfg):
    """The same sizes under the published names the reference reads."""
    return {"linear_num_heads": cfg["linear_heads"],
            "linear_key_head_dim": cfg["linear_key_dim"],
            "linear_value_head_dim": cfg["linear_value_dim"],
            "linear_allow_neg_eigval": cfg["allow_neg_eigval"],
            "num_attention_heads": cfg["n_heads"],
            "rms_norm_eps": cfg["rms_norm_eps"]}


def _softplus_inverse(y):
    return y + jnp.log(-jnp.expm1(-y))


def _layer(key, cfg, index, kind):
    """The leaves of layer ``index`` (which may be traced) with a mixer of
    ``kind``, and its SwiGLU."""
    d, heads = cfg["d_model"], cfg["n_heads"]
    hd = d // heads
    h, dk, dv = (cfg["linear_heads"], cfg["linear_key_dim"],
                 cfg["linear_value_dim"])
    ks = dict(zip(_LEAVES, jax.random.split(
        jax.random.fold_in(key, 1 + index), len(_LEAVES))))
    blk = {"mixer_norm": jnp.ones((d,)), "ffn_norm": jnp.ones((d,)),
           "mlp": _swiglu(ks["ffn"], d, cfg["d_ff"])}
    if kind == LINEAR:
        taps = cfg["conv_kernel"]
        blk["linear"] = {
            # three projections, each with its own fans, side by side:
            # the columns of q~ | k~ | v~
            "w_qkv": jnp.concatenate(
                [_uniform(ks["wq"], (d, h * dk), d, h * dk),
                 _uniform(ks["wk"], (d, h * dk), d, h * dk),
                 _uniform(ks["wv"], (d, h * dv), d, h * dv)], 1),
            "conv": _uniform(ks["conv"], (h * (2 * dk + dv), taps), taps, 1),
            "w_gate": _uniform(ks["wg"], (d, h * dv), d, h * dv),
            "w_ab": _uniform(ks["wab"], (d, 2 * h), d, h),
            # as the delta-rule layers' reference code seeds them
            "a_log": jnp.log(jax.random.uniform(
                ks["a_log"], (h,), jnp.float32, 1.0, 16.0)),
            "dt_bias": _softplus_inverse(jax.random.uniform(
                ks["dt_bias"], (h,), jnp.float32, 0.001, 0.1)),
            "o_norm": jnp.ones((dv,)),
            "w_out": _uniform(ks["wo"], (h * dv, d), h * dv, d),
        }
    else:
        blk["attn"] = {
            "wq": _uniform(ks["wq"], (d, heads, hd), d, d),
            "wk": _uniform(ks["wk"], (d, heads, hd), d, d),
            "wv": _uniform(ks["wv"], (d, heads, hd), d, d),
            "q_norm": jnp.ones((d,)),
            "k_norm": jnp.ones((d,)),
            "wo": _uniform(ks["wo"], (heads, hd, d), d, d),
        }
    return blk


def layer(key, cfg, index):
    """One layer's leaves: its mixer (gated delta rule or full attention,
    by the pattern) and its SwiGLU."""
    return _layer(key, cfg, index, cfg["layer_types"][index])


def layer_maker(cfg):
    """-> ``make(key, index)``: :func:`layer` on the device, one compiled
    program a kind of layer, not a layer."""
    programs = {}

    def make(key, index):
        kind = cfg["layer_types"][index]
        if kind not in programs:
            programs[kind] = jax.jit(lambda k, i: _layer(k, cfg, i, kind))
        return programs[kind](key, index)

    return make


def outer(key, cfg):
    """Everything outside the layers: the embedding table, the final norm
    and the untied head."""
    return mla_moe.outer(key, cfg)


def tree(key, cfg):
    """The whole parameter tree ``OlmoHybridDecoder`` takes."""
    out = outer(key, cfg)
    out["blocks"] = [layer(key, cfg, i) for i in range(cfg["n_layers"])]
    return out


class ModelSpec(mla_moe.ModelSpec):
    """What the engine's serialization layer round-trips to an
    ``OlmoHybridDecoder``: the latent family's spec (the weights as a list
    of leaves, the device copy let go leaf by leaf as the host copy is
    made) under this family's class name."""

    def to_json(self):
        return json.dumps({"class_name": "OlmoHybridDecoder",
                           "config": self.cfg})


def device_tree(key, cfg):
    """:func:`tree` made on the device, a layer a jitted call: one call
    for all of them would hold every layer's random bits beside the
    weights."""
    make = layer_maker(cfg)
    out = jax.jit(lambda k: outer(k, cfg))(key)
    out["blocks"] = [make(key, i) for i in range(cfg["n_layers"])]
    return out


def build_engine(ctx):
    """Weights on the device from the seed, then the program's
    ``DecodeEngine`` on one replica with the configuration's count of
    state rows (a row is 14 MB here: the default that suits a row of 96 KB
    would not fit) -> (engine, model cfg)."""
    from dist_keras_tpu.serving.decode import DecodeEngine

    cfg = model_config(ctx.config)
    serve = ctx.config["serve"]
    engine = DecodeEngine(
        ModelSpec(cfg, device_tree(weights.base_key(ctx.seed), cfg)),
        replicas=1,
        prefill_ladder=tuple(serve["prefill_ladder"]),
        decode_ladder=tuple(serve["decode_ladder"]),
        page_size=serve["page_size"], max_queue=serve["max_queue"],
        state_rows=serve["state_rows"], devices=list(ctx.devices[:1]))
    return engine, cfg


def vocab(cfg):
    return cfg["vocab_size"]


def compare(ctx, cfg, samples):
    """The served samples against the plain reference, which needs the
    device to itself: this configuration fills 14 of the chip's 16 GB, and
    when the kind hands over the engine is closed but not gone (every
    record's ``Generation`` still refers to it, and through it to the
    weights and pools).  What the window needed of the device has been
    read by now, on the host's side, so every array still on it is let
    go here."""
    import gc

    from benchmark.reference import olmo_hybrid_check

    gc.collect()
    for array in jax.live_arrays():
        array.delete()
    return olmo_hybrid_check.compare(ctx, cfg, samples)


def counters(engine, cfg):
    """What the family's readers need beside the window's counters: the
    bytes a decode step has to move, by what they depend on, and the
    bytes a call of each of its kernels has to."""
    return {"decode_bytes": opcount_olmo_hybrid.decode_step_bytes(
                cfg, engine.max_slots),
            "kernel_unit_bytes": opcount_olmo_hybrid.kernel_unit_bytes(cfg)}
