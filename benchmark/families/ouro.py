"""The looped family (``models/ouro.py``: one stack of layers run several
times a token on shared weights): the program's configuration from the
benchmark's file, seeded weights in the program's parameter layout, the
engine, and the comparison with the plain reference
(``reference/ouro_check.py``).

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
from benchmark.trace import opcount_ouro

_LEAVES = ("wq", "wk", "wv", "wo", "ffn")
_OUTER = ("embed", "head", "gate")


def model_config(conf):
    """The program's configuration of the served depth: the first
    ``num_hidden_layers.serve`` layers, every pass of them."""
    from dist_keras_tpu.models.ouro import ouro_config

    return ouro_config(
        vocab_size=conf["vocab_size"], seq_len=conf["serve"]["positions"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"],
        n_layers=conf["num_hidden_layers"]["serve"],
        ut_steps=conf["total_ut_steps"],
        early_exit_threshold=conf["early_exit_threshold"],
        rope_theta=conf["rope_theta"], rms_norm_eps=conf["rms_norm_eps"])


def reference_config(cfg):
    """The same sizes under the published names the reference reads."""
    return {"total_ut_steps": cfg["ut_steps"],
            "early_exit_threshold": cfg["early_exit_threshold"],
            "rope_theta": cfg["rope_theta"],
            "rms_norm_eps": cfg["rms_norm_eps"]}


def layer(key, cfg, index):
    """One layer's leaves (``index`` may be traced): attention without
    biases or per-head norms, the SwiGLU, and the four norms of the
    sandwich."""
    d, h, hk, hd = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                    cfg["head_dim"])
    ks = dict(zip(_LEAVES, jax.random.split(
        jax.random.fold_in(key, 1 + index), len(_LEAVES))))
    return {
        "attn_norm": jnp.ones((d,)),
        "attn": {
            "wq": _uniform(ks["wq"], (d, h, hd), d, h * hd),
            "wk": _uniform(ks["wk"], (d, hk, hd), d, hk * hd),
            "wv": _uniform(ks["wv"], (d, hk, hd), d, hk * hd),
            "wo": _uniform(ks["wo"], (h, hd, d), h * hd, d),
        },
        "attn_out_norm": jnp.ones((d,)),
        "mlp_norm": jnp.ones((d,)),
        "mlp": _swiglu(ks["ffn"], d, cfg["d_ff"]),
        "mlp_out_norm": jnp.ones((d,)),
    }


def layer_maker(cfg):
    """-> ``make(key, index)``: :func:`layer` on the device, one compiled
    program for every layer (they are all of one kind)."""
    return jax.jit(lambda k, i: layer(k, cfg, i))


def outer(key, cfg):
    """Everything outside the layers: the embedding table, the final norm
    the passes share, the exit gate (a ``d -> 1`` product and its bias)
    and the untied head."""
    ks = dict(zip(_OUTER, jax.random.split(jax.random.fold_in(key, 0),
                                           len(_OUTER))))
    d, v = cfg["d_model"], cfg["vocab_size"]
    return {"embed": 0.02 * jax.random.normal(ks["embed"], (v, d),
                                              jnp.float32),
            "norm_f": jnp.ones((d,)),
            "gate": {"w": _uniform(ks["gate"], (d,), d, 1),
                     "b": jnp.zeros(())},
            "head": _uniform(ks["head"], (d, v), d, v)}


def tree(key, cfg):
    """The whole parameter tree ``OuroDecoder`` takes."""
    out = outer(key, cfg)
    out["blocks"] = [layer(key, cfg, i) for i in range(cfg["n_layers"])]
    return out


class ModelSpec(mla_moe.ModelSpec):
    """What the engine's serialization layer round-trips to an
    ``OuroDecoder``: the latent family's spec (the weights as a list of
    leaves, the device copy let go leaf by leaf as the host copy is made)
    under this family's class name."""

    def to_json(self):
        return json.dumps({"class_name": "OuroDecoder", "config": self.cfg})


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
    ``DecodeEngine`` on one replica -> (engine, model cfg)."""
    from dist_keras_tpu.serving.decode import DecodeEngine

    cfg = model_config(ctx.config)
    serve = ctx.config["serve"]
    engine = DecodeEngine(
        ModelSpec(cfg, device_tree(weights.base_key(ctx.seed), cfg)),
        replicas=1,
        prefill_ladder=tuple(serve["prefill_ladder"]),
        decode_ladder=tuple(serve["decode_ladder"]),
        page_size=serve["page_size"], max_queue=serve["max_queue"],
        devices=list(ctx.devices[:1]))
    return engine, cfg


def vocab(cfg):
    return cfg["vocab_size"]


def compare(ctx, cfg, samples):
    """The served samples against the plain reference, which needs the
    device to itself: this configuration fills 13 of the chip's 16 GB, and
    when the kind hands over the engine is closed but not gone (every
    record's ``Generation`` still refers to it, and through it to the
    weights and the pool), so every array still on the device is let go
    here, as ``families/olmo_hybrid.py`` does."""
    import gc

    from benchmark.reference import ouro_check

    gc.collect()
    for array in jax.live_arrays():
        array.delete()
    return ouro_check.compare(ctx, cfg, samples)


def counters(engine, cfg):
    """What the family's readers need beside the window's counters: the
    bytes a decode step has to read, by what they depend on, and the
    bytes a call of its read kernel has to."""
    return {"decode_bytes": opcount_ouro.decode_step_bytes(
                cfg, engine.max_slots),
            "kernel_unit_bytes": opcount_ouro.kernel_unit_bytes(cfg)}
