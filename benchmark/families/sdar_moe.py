"""The block-diffusion, grouped-query, sparse-expert family
(``models/sdar_moe.py``): the program's configuration from the benchmark's
file, seeded weights in the program's parameter layout, the engine, and
the comparison with the plain reference
(``reference/sdar_moe_check.py``).

The benchmark makes the weights and hands the same ones to the program
and, layer by layer, to the reference; every leaf is a function of (seed,
layer, leaf name) alone, so one layer can be made again without the
others.  A chip's share holds the weights of its ``held_experts`` only.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp

from benchmark import weights
from benchmark.families import mla_moe
from benchmark.families.mla_moe import _swiglu, _uniform
from benchmark.trace import opcount_sdar_moe

_LEAVES = ("wq", "wk", "wv", "wo", "router", "experts")


def model_config(conf):
    """The program's configuration of the served depth and share, with
    the generation procedure the file states."""
    from dist_keras_tpu.models.sdar_moe import sdar_moe_config

    gen = conf["generation"]
    return sdar_moe_config(
        vocab_size=conf["vocab_size"], seq_len=conf["serve"]["positions"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        moe_d_ff=conf["moe_intermediate_size"],
        n_routed_experts=conf["num_experts_published"],
        top_k=conf["num_experts_per_tok"],
        n_layers=conf["num_hidden_layers"]["serve"],
        held_experts=conf["held_experts"],
        block_length=gen["block_length"],
        denoising_steps=gen["denoising_steps"],
        mask_token_id=gen["mask_token_id"],
        rope_theta=conf["rope_theta"], rms_norm_eps=conf["rms_norm_eps"])


def reference_config(cfg):
    """The same sizes under the published names the reference reads."""
    return {"num_experts_per_tok": cfg["top_k"],
            "rope_theta": cfg["rope_theta"],
            "rms_norm_eps": cfg["rms_norm_eps"],
            "block_length": cfg["block_length"],
            "denoising_steps": cfg["denoising_steps"],
            "mask_token_id": cfg["mask_token_id"]}


def layer(key, cfg, index):
    """One layer's leaves (``index`` may be traced): grouped-query
    attention with its two per-head norms, the router over all the
    published experts, the weights of the held ones."""
    d, h, hk, hd = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                    cfg["head_dim"])
    ks = dict(zip(_LEAVES, jax.random.split(
        jax.random.fold_in(key, 1 + index), len(_LEAVES))))
    n_all = cfg["n_routed_experts"]
    return {
        "op_norm": jnp.ones((d,)),
        "attn": {
            "wq": _uniform(ks["wq"], (d, h, hd), d, h * hd),
            "wk": _uniform(ks["wk"], (d, hk, hd), d, hk * hd),
            "wv": _uniform(ks["wv"], (d, hk, hd), d, hk * hd),
            "q_norm": jnp.ones((hd,)),
            "k_norm": jnp.ones((hd,)),
            "wo": _uniform(ks["wo"], (h, hd, d), h * hd, d),
        },
        "ffn_norm": jnp.ones((d,)),
        "moe": {
            "router": _uniform(ks["router"], (d, n_all), d, n_all),
            "experts": _swiglu(ks["experts"], d, cfg["moe_d_ff"],
                               (len(cfg["held_experts"]),)),
        },
    }


def layer_maker(cfg):
    """-> ``make(key, index)``: :func:`layer` on the device, one compiled
    program for every layer (they are all of one kind)."""
    return jax.jit(lambda k, i: layer(k, cfg, i))


def outer(key, cfg):
    """Everything outside the layers: the embedding table, the final norm
    and the untied head."""
    return mla_moe.outer(key, cfg)


def tree(key, cfg):
    """The whole parameter tree ``SdarMoeDecoder`` takes."""
    out = outer(key, cfg)
    out["blocks"] = [layer(key, cfg, i) for i in range(cfg["n_layers"])]
    return out


class ModelSpec(mla_moe.ModelSpec):
    """What the engine's serialization layer round-trips to an
    ``SdarMoeDecoder``: the latent family's spec (the weights as a list
    of leaves, the device copy let go leaf by leaf as the host copy is
    made) under this family's class name."""

    def to_json(self):
        return json.dumps({"class_name": "SdarMoeDecoder",
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
    """What the traffic draws prompt ids from: the ids below the mask id
    (the tokenizer's ordinary tokens; a prompt never holds a mask)."""
    return cfg["mask_token_id"]


def compare(ctx, cfg, samples):
    """The served trajectories against the plain reference, which needs
    the device to itself: when the kind hands over, the engine is closed
    but not gone (every record's ``Generation`` still refers to it, and
    through it to 8.6 GB of weights and the pool), so every array still
    on the device is let go here, as ``families/olmo_hybrid.py`` does."""
    import gc

    from benchmark.reference import sdar_moe_check

    gc.collect()
    for array in jax.live_arrays():
        array.delete()
    return sdar_moe_check.compare(ctx, cfg, samples)


def counters(engine, cfg):
    """What the family's readers need beside the window's counters: the
    bytes a pass has to read, by what they depend on, and the bytes a
    call of its read kernel has to."""
    return {"decode_bytes": opcount_sdar_moe.decode_step_bytes(
                cfg, engine.max_slots),
            "kernel_unit_bytes": opcount_sdar_moe.kernel_unit_bytes(cfg)}
