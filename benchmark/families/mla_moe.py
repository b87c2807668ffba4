"""The latent-attention, sparse-expert family (``models/mla_moe.py``):
the program's configuration from the benchmark's file, seeded weights in
the program's parameter layout, the engine, and the comparison with the
plain reference (``reference/mla_moe_check.py``).

The benchmark makes the weights and hands the same ones to the program
and, layer by layer, to the reference; every leaf is a function of (seed,
layer, leaf name) alone, so one layer can be made again without the
others.  A chip's share holds the weights of its ``held_experts`` only.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights
from benchmark.trace import opcount_mla

_ATTENTION = ("wq", "wkv_a", "w_uk", "w_uv", "wo")
_SWIGLU = ("w_gate", "w_up", "w_down")


def model_config(conf):
    """The program's configuration of the served depth and share."""
    from dist_keras_tpu.models.mla_moe import mla_moe_config

    return mla_moe_config(
        vocab_size=conf["vocab_size"], seq_len=conf["serve"]["positions"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        qk_nope_head_dim=conf["qk_nope_head_dim"],
        qk_rope_head_dim=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"], kv_lora_rank=conf["kv_lora_rank"],
        d_ff=conf["intermediate_size"], moe_d_ff=conf["moe_intermediate_size"],
        n_routed_experts=conf["n_routed_experts_published"],
        n_shared_experts=conf["n_shared_experts"],
        top_k=conf["num_experts_per_tok"],
        n_layers=conf["num_hidden_layers"]["serve"],
        first_k_dense=conf["first_k_dense_replace"],
        held_experts=conf["held_experts"],
        routed_scaling_factor=conf["routed_scaling_factor"],
        rope_theta=conf["rope_theta"], rms_norm_eps=conf["rms_norm_eps"])


def reference_config(cfg):
    """The same sizes under the published names the reference reads."""
    return {"qk_nope_head_dim": cfg["qk_nope_head_dim"],
            "qk_rope_head_dim": cfg["qk_rope_head_dim"],
            "kv_lora_rank": cfg["kv_lora_rank"],
            "num_experts_per_tok": cfg["top_k"],
            "routed_scaling_factor": cfg["routed_scaling_factor"],
            "rope_theta": cfg["rope_theta"],
            "rms_norm_eps": cfg["rms_norm_eps"]}


def _uniform(key, shape, fan_in, fan_out):
    limit = np.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, jnp.float32, -limit, limit)


def _swiglu(key, d, f, lead=()):
    kg, ku, kd = jax.random.split(key, 3)
    return {"w_gate": _uniform(kg, lead + (d, f), d, f),
            "w_up": _uniform(ku, lead + (d, f), d, f),
            "w_down": _uniform(kd, lead + (f, d), f, d)}


def _attention(key, cfg):
    d, h = cfg["d_model"], cfg["n_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, dv = cfg["kv_lora_rank"], cfg["v_head_dim"]
    ks = dict(zip(_ATTENTION, jax.random.split(key, len(_ATTENTION))))
    return {
        "attn_norm": jnp.ones((d,)),
        "wq": _uniform(ks["wq"], (d, h, nope + rope), d, h * (nope + rope)),
        "wkv_a": _uniform(ks["wkv_a"], (d, rank + rope), d, rank + rope),
        "kv_norm": jnp.ones((rank,)),
        "w_uk": _uniform(ks["w_uk"], (rank, h, nope), rank, h * nope),
        "w_uv": _uniform(ks["w_uv"], (rank, h, dv), rank, h * dv),
        "wo": _uniform(ks["wo"], (h, dv, d), h * dv, d),
        "ffn_norm": jnp.ones((d,)),
    }


def dense_layer(key, cfg, layer):
    ka, kf = jax.random.split(jax.random.fold_in(key, 1 + layer))
    blk = _attention(ka, cfg)
    blk["mlp"] = _swiglu(kf, cfg["d_model"], cfg["d_ff"])
    return blk


def expert_layer(key, cfg, layer):
    """A layer of routed experts: the router and its selection bias over
    all the published experts, the weights of the held ones, the shared
    expert (the published ``n_shared_experts`` as one SwiGLU)."""
    ka, kr, kb, ke, ks = jax.random.split(
        jax.random.fold_in(key, 1 + layer), 5)
    d, f = cfg["d_model"], cfg["moe_d_ff"]
    n_all = cfg["n_routed_experts"]
    blk = _attention(ka, cfg)
    blk["moe"] = {
        "router": _uniform(kr, (d, n_all), d, n_all),
        # small and not zero: selection reads s + b, weighting s alone
        "router_bias": jax.random.uniform(kb, (n_all,), jnp.float32,
                                          -0.02, 0.02),
        "experts": _swiglu(ke, d, f, (len(cfg["held_experts"]),)),
        "shared": _swiglu(ks, d, cfg["n_shared_experts"] * f),
    }
    return blk


def layer(key, cfg, index):
    make = dense_layer if index < cfg["first_k_dense"] else expert_layer
    return make(key, cfg, index)


def outer(key, cfg):
    """Everything outside the layers: the embedding table, the final norm
    and the untied head."""
    ke, kh = jax.random.split(jax.random.fold_in(key, 0))
    d, v = cfg["d_model"], cfg["vocab_size"]
    return {"embed": 0.02 * jax.random.normal(ke, (v, d), jnp.float32),
            "norm_f": jnp.ones((d,)),
            "head": _uniform(kh, (d, v), d, v)}


def tree(key, cfg):
    """The whole parameter tree ``LatentMoEDecoder`` takes."""
    out = outer(key, cfg)
    out["blocks"] = [layer(key, cfg, i) for i in range(cfg["n_layers"])]
    return out


class ModelSpec:
    """What the engine's serialization layer round-trips to a
    ``LatentMoEDecoder``: the architecture as JSON and the weights as a
    list of leaves, the device copy let go leaf by leaf as the host copy
    is made."""

    def __init__(self, cfg, params):
        self.cfg = cfg
        self._params = params

    def to_json(self):
        return json.dumps({"class_name": "LatentMoEDecoder",
                           "config": self.cfg})

    def get_weights(self):
        leaves = jax.tree.leaves(self._params)
        self._params = None
        out = []
        while leaves:
            out.append(np.asarray(leaves.pop(0)))
        return out


def build_engine(ctx):
    """Weights on the device from the seed in one jitted call, then the
    program's ``DecodeEngine`` on one replica -> (engine, model cfg)."""
    from dist_keras_tpu.serving.decode import DecodeEngine

    cfg = model_config(ctx.config)
    serve = ctx.config["serve"]
    spec = ModelSpec(cfg, jax.jit(lambda k: tree(k, cfg))(
        weights.base_key(ctx.seed)))
    engine = DecodeEngine(
        spec, replicas=1,
        prefill_ladder=tuple(serve["prefill_ladder"]),
        decode_ladder=tuple(serve["decode_ladder"]),
        page_size=serve["page_size"], max_queue=serve["max_queue"],
        devices=list(ctx.devices[:1]))
    return engine, cfg


def vocab(cfg):
    return cfg["vocab_size"]


def compare(ctx, cfg, samples):
    from benchmark.reference import mla_moe_check

    return mla_moe_check.compare(ctx, cfg, samples)


def counters(engine, cfg):
    """What the family's readers need beside the window's counters: the
    bytes a decode step has to read, by what they depend on."""
    return {"decode_bytes": opcount_mla.decode_step_bytes(
        cfg, engine.max_slots)}
