"""The gated short-convolution, grouped-query, sparse-expert family
(``models/lfm2_moe.py``): the program's configuration from the
benchmark's file, seeded weights in the program's parameter layout, the
engine, and the comparison with the plain reference
(``reference/lfm2_moe_check.py``).

The benchmark makes the weights and hands the same ones to the program
and, layer by layer, to the reference; every leaf is a function of (seed,
layer, leaf name) alone, so one layer can be made again without the
others.  Every routed expert is held.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp

from benchmark import weights
from benchmark.families import mla_moe
from benchmark.families.mla_moe import _swiglu, _uniform
from benchmark.trace import opcount_lfm2_moe

_LEAVES = ("w_in", "kernel", "w_out", "wq", "wk", "wv", "wo", "ffn",
           "router", "router_bias")


def model_config(conf):
    """The program's configuration of the served depth: the first
    ``num_hidden_layers.serve`` layers of the published pattern."""
    from dist_keras_tpu.models.lfm2_moe import lfm2_moe_config

    depth = conf["num_hidden_layers"]["serve"]
    return lfm2_moe_config(
        vocab_size=conf["vocab_size"], seq_len=conf["serve"]["positions"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"],
        moe_d_ff=conf["moe_intermediate_size"],
        n_routed_experts=conf["num_experts"],
        top_k=conf["num_experts_per_tok"],
        layer_types=conf["layer_types"][:depth],
        num_dense_layers=conf["num_dense_layers"],
        conv_l_cache=conf["conv_L_cache"],
        routed_scaling_factor=conf["routed_scaling_factor"],
        rope_theta=conf["rope_theta"], norm_eps=conf["norm_eps"])


def reference_config(cfg):
    """The same sizes under the published names the reference reads."""
    return {"num_experts_per_tok": cfg["top_k"],
            "routed_scaling_factor": cfg["routed_scaling_factor"],
            "rope_theta": cfg["rope_theta"],
            "norm_eps": cfg["rms_norm_eps"]}


def _layer(key, cfg, index, operator, dense):
    """The leaves of layer ``index`` (which may be traced) with the given
    operator, and one SwiGLU if ``dense``, else the routed experts."""
    d, h, hk = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd = d // h
    ks = dict(zip(_LEAVES, jax.random.split(
        jax.random.fold_in(key, 1 + index), len(_LEAVES))))
    blk = {"op_norm": jnp.ones((d,)), "ffn_norm": jnp.ones((d,))}
    if operator == "conv":
        taps = cfg["conv_l_cache"]
        blk["conv"] = {
            "w_in": _uniform(ks["w_in"], (d, 3 * d), d, 3 * d),
            "kernel": _uniform(ks["kernel"], (d, taps), taps, 1),
            "w_out": _uniform(ks["w_out"], (d, d), d, d),
        }
    else:
        blk["attn"] = {
            "wq": _uniform(ks["wq"], (d, h, hd), d, h * hd),
            "wk": _uniform(ks["wk"], (d, hk, hd), d, hk * hd),
            "wv": _uniform(ks["wv"], (d, hk, hd), d, hk * hd),
            "q_norm": jnp.ones((hd,)),
            "k_norm": jnp.ones((hd,)),
            "wo": _uniform(ks["wo"], (h, hd, d), h * hd, d),
        }
    if dense:
        blk["mlp"] = _swiglu(ks["ffn"], d, cfg["d_ff"])
        return blk
    n = cfg["n_routed_experts"]
    blk["moe"] = {
        "router": _uniform(ks["router"], (d, n), d, n),
        # small and not zero: selection reads s + b, weighting s alone
        "router_bias": jax.random.uniform(ks["router_bias"], (n,),
                                          jnp.float32, -0.02, 0.02),
        "experts": _swiglu(ks["ffn"], d, cfg["moe_d_ff"], (n,)),
    }
    return blk


def _kind(cfg, index):
    return cfg["layer_types"][index], index < cfg["num_dense_layers"]


def layer(key, cfg, index):
    """One layer's leaves: its operator (gated short convolution or
    grouped-query attention, by the pattern), then one SwiGLU or, past
    the leading dense layers, the router with its selection bias and
    every routed expert."""
    return _layer(key, cfg, index, *_kind(cfg, index))


def layer_maker(cfg):
    """-> ``make(key, index)``: :func:`layer` on the device, one compiled
    program a kind of layer (operator x dense or routed), not a layer."""
    programs = {}

    def make(key, index):
        kind = _kind(cfg, index)
        if kind not in programs:
            programs[kind] = jax.jit(
                lambda k, i: _layer(k, cfg, i, *kind))
        return programs[kind](key, index)

    return make


def outer(key, cfg):
    """Everything outside the layers: the embedding table, which is also
    the head, and the final norm."""
    ke = jax.random.fold_in(key, 0)
    d, v = cfg["d_model"], cfg["vocab_size"]
    return {"embed": 0.02 * jax.random.normal(ke, (v, d), jnp.float32),
            "norm_f": jnp.ones((d,))}


def tree(key, cfg):
    """The whole parameter tree ``Lfm2MoeDecoder`` takes."""
    out = outer(key, cfg)
    out["blocks"] = [layer(key, cfg, i) for i in range(cfg["n_layers"])]
    return out


class ModelSpec(mla_moe.ModelSpec):
    """What the engine's serialization layer round-trips to an
    ``Lfm2MoeDecoder``: the latent family's spec (the weights as a list
    of leaves, the device copy let go leaf by leaf as the host copy is
    made) under this family's class name."""

    def to_json(self):
        return json.dumps({"class_name": "Lfm2MoeDecoder",
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
    ``DecodeEngine`` on one replica -> (engine, model cfg).  The spec
    owns the only reference to the device copy and lets it go while the
    engine takes its own."""
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
    from benchmark.reference import lfm2_moe_check

    return lfm2_moe_check.compare(ctx, cfg, samples)


def counters(engine, cfg):
    """What the family's readers need beside the window's counters: the
    bytes a decode step has to read, by what they depend on."""
    return {"decode_bytes": opcount_lfm2_moe.decode_step_bytes(
        cfg, engine.max_slots)}
