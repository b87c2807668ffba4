"""Mixture-of-Experts FFN with expert parallelism (EP) — new capability.

The reference has no MoE and no expert sharding (SURVEY.md §2.3: every
parallelism beyond data-parallel is absent upstream).  This is the
TPU-idiomatic Switch-Transformer-style layer:

- **Routing**: top-1 (Switch) router with a capacity limit
  ``C = ceil(tokens * capacity_factor / num_experts)`` per expert;
  overflowing tokens pass through unprocessed (standard Switch drop
  semantics — the residual connection carries them).
- **Expert parallelism**: experts live sharded over the ``experts`` mesh
  axis; tokens are dispatched to their expert's device with ONE
  ``lax.all_to_all`` each way (the EP collective), and every expert
  processes its global token queue as one batched matmul — MXU-friendly
  (E_local, capacity*ep, d) x (d, ff) instead of ragged gathers.
- **Oracle**: ``switch_moe_dense`` computes the same mixture without
  dispatch (every expert on every device) for parity tests; with ample
  capacity the EP output matches it exactly.

Use inside ``shard_map`` with the ``experts`` axis bound (tokens
data-sharded over the same axis), or single-device via ``ep=1``.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
import optax
from jax import lax

from dist_keras_tpu.models.layers import glorot_uniform, select_top_k

EXPERT_AXIS = "experts"


def init_moe_params(key, d_model, d_ff, num_experts):
    """Router + per-expert FFN stacks.  Shard leaves' leading expert dim
    over the ``experts`` mesh axis for EP (see ``moe_param_specs``)."""
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "router": glorot_uniform(k1, (d_model, num_experts)),
        "w1": glorot_uniform(k2, (num_experts, d_model, d_ff)),
        "b1": jnp.zeros((num_experts, d_ff)),
        "w2": glorot_uniform(k3, (num_experts, d_ff, d_model)),
        "b2": jnp.zeros((num_experts, d_model)),
    }


def moe_param_specs(axis=EXPERT_AXIS):
    """PartitionSpecs: experts sharded, router replicated."""
    from jax.sharding import PartitionSpec as P

    return {"router": P(), "w1": P(axis), "b1": P(axis),
            "w2": P(axis), "b2": P(axis)}


def _route(params, x, num_experts, capacity):
    """-> (dispatch (N, E, C), combine (N, E, C), aux_loss scalar).

    Top-1 routing with per-expert capacity; position in the expert queue
    is assignment order (deterministic).  ``combine = dispatch * gate``.
    The aux load-balancing loss is the Switch mean(frac_tokens *
    frac_probs) * E.
    """
    logits = x @ params["router"]                      # (N, E)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    # the one top-k selection the repo has (models/layers.py), at k = 1
    # and with no selection bias: the first of equal scores wins, as
    # argmax chose
    expert, gate = (a[:, 0] for a in select_top_k(probs, None, 1))
    # queue position of each token within its chosen expert — int32
    # cumsum: exact for any token count (float32 cumsum loses exactness
    # past 2^24 tokens and would silently corrupt capacity assignment)
    onehot_i = jax.nn.one_hot(expert, num_experts, dtype=jnp.int32)
    onehot = onehot_i.astype(jnp.float32)              # (N, E)
    pos = jnp.cumsum(onehot_i, axis=0) * onehot_i - onehot_i  # (N, E)
    keep = (pos < capacity) * onehot                    # (N, E)
    posc = jax.nn.one_hot(pos.sum(-1), capacity,
                          dtype=jnp.float32)            # (N, C)
    dispatch = keep[:, :, None] * posc[:, None, :]      # (N, E, C)
    combine = dispatch * gate[:, None, None]
    # Switch aux loss: encourages uniform load
    frac_tokens = onehot.mean(axis=0)
    frac_probs = probs.mean(axis=0)
    aux = jnp.sum(frac_tokens * frac_probs) * num_experts
    return dispatch, combine, aux


def _expert_ffn(w1, b1, w2, b2, xs, activation):
    h = activation(jnp.einsum("ecd,edf->ecf", xs, w1) + b1[:, None])
    return jnp.einsum("ecf,efd->ecd", h, w2) + b2[:, None]


def switch_moe_dense(params, x, capacity_factor=1.25,
                     activation=jax.nn.gelu):
    """Single-device oracle: same routing/capacity math, no dispatch
    collectives.  x: (N, d) -> (out (N, d), aux_loss)."""
    num_experts = params["router"].shape[1]
    n = x.shape[0]
    capacity = int(np.ceil(n * capacity_factor / num_experts))
    dispatch, combine, aux = _route(params, x, num_experts, capacity)
    xs = jnp.einsum("nec,nd->ecd", dispatch, x)         # (E, C, d)
    ys = _expert_ffn(params["w1"], params["b1"], params["w2"],
                     params["b2"], xs, activation)
    out = jnp.einsum("nec,ecd->nd", combine, ys)
    return out.astype(x.dtype), aux


def switch_moe_ep(params, x, axis=EXPERT_AXIS, capacity_factor=1.25,
                  activation=jax.nn.gelu):
    """Expert-parallel Switch FFN — call INSIDE shard_map with ``axis``
    bound; x: local tokens (N_local, d); params' expert dims hold only
    the local experts (E_local = E / ep).

    -> (out (N_local, d), aux_loss local mean-contribution).
    """
    ep = lax.axis_size(axis)
    e_local = params["w1"].shape[0]
    num_experts = ep * e_local
    n = x.shape[0]
    capacity = int(np.ceil(n * capacity_factor / num_experts))
    dispatch, combine, aux = _route(params, x, num_experts, capacity)

    xs = jnp.einsum("nec,nd->ecd", dispatch, x)         # (E, C, d)
    d = x.shape[-1]
    # (E, C, d) -> (ep, E_local, C, d): dim0 = destination device
    xs = xs.reshape(ep, e_local, capacity, d)
    # EP collective #1: tokens travel to their expert's device; dim0
    # becomes the SOURCE device after the exchange
    xs = lax.all_to_all(xs, axis, split_axis=0, concat_axis=0,
                        tiled=False)
    # each local expert processes its global queue in one batched matmul
    xs = jnp.moveaxis(xs, 0, 1).reshape(e_local, ep * capacity, d)
    ys = _expert_ffn(params["w1"], params["b1"], params["w2"],
                     params["b2"], xs, activation)
    # EP collective #2: results travel home
    ys = jnp.moveaxis(
        ys.reshape(e_local, ep, capacity, d), 1, 0)     # (ep, E_l, C, d)
    ys = lax.all_to_all(ys, axis, split_axis=0, concat_axis=0,
                        tiled=False)
    ys = ys.reshape(num_experts, capacity, d)
    out = jnp.einsum("nec,ecd->nd", combine, ys)
    return out.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# MoE transformer training step
# ---------------------------------------------------------------------------
def make_moe_train_step(cfg, optimizer=None, aux_weight=1e-2, causal=False,
                        attn_fn=None, remat=False):
    """-> (init_fn, step) for a MoE transformer
    (``transformer_config(moe_experts=E)``).

    The objective is ``nll + aux_weight * router_load_balance`` (the
    Switch recipe) — the reason MoE configs can't train through the
    plain ``transformer_apply`` path.  step(params, opt_state, x, y) ->
    (params, opt_state, {"loss", "nll", "aux"}).
    """
    tx = optimizer or optax.adam(1e-3)

    def init_fn(seed=0):
        from dist_keras_tpu.models.transformer import (
            init_transformer_params,
        )

        params = init_transformer_params(jax.random.PRNGKey(seed), cfg)
        return params, tx.init(params)

    @jax.jit
    def step(params, opt_state, x, y):
        from dist_keras_tpu.models.transformer import (
            transformer_apply_with_aux,
        )

        def loss_fn(p):
            logits, aux = transformer_apply_with_aux(
                p, x, cfg, causal=causal, attn_fn=attn_fn, remat=remat)
            logp = jax.nn.log_softmax(logits)
            nll = -jnp.take_along_axis(
                logp, y[:, None].astype(jnp.int32), axis=-1).mean()
            return nll + aux_weight * aux, (nll, aux)

        (loss, (nll, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, {"loss": loss, "nll": nll, "aux": aux}

    return init_fn, step


# ---------------------------------------------------------------------------
# expert-parallel MoE transformer training step
# ---------------------------------------------------------------------------
def moe_transformer_param_specs(params, axis=EXPERT_AXIS):
    """PartitionSpec pytree for an MoE transformer: expert stacks sharded
    over ``axis``, everything else (attention, LN, router, embeddings)
    replicated."""
    from jax.sharding import PartitionSpec as P

    def leaf_spec(path, leaf):
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if "moe" in keys and keys[-1] != "router":
            return P(axis)
        return P()

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def make_moe_ep_train_step(mesh, cfg, optimizer=None, aux_weight=1e-2,
                           causal=False, attn_fn=None, axis=EXPERT_AXIS):
    """-> (step_fn_factory, init_fn): MoE transformer training with real
    expert parallelism.

    Layout: sequences are batch-sharded over the ``experts`` mesh axis
    (attention stays device-local, full T per sequence); each block's
    expert stacks live sharded over the same axis and its FFN runs
    ``switch_moe_ep`` (all_to_all dispatch).  Replicated params get their
    gradient psum from AD's replicated->varying transpose, exactly like
    the TP step's data axis.

    step_fn(params, opt_state, x, y) -> (params, opt_state,
    {"loss","nll","aux"}).  x: (batch, T, input_dim) global with
    batch % mesh.shape[axis] == 0.
    """
    from jax.sharding import PartitionSpec as P

    from dist_keras_tpu.models.transformer import (
        init_transformer_params,
        layer_norm as _ln,
    )

    if not cfg.get("moe_experts", 0):
        raise ValueError("make_moe_ep_train_step needs moe_experts > 0")
    tx = optimizer or optax.adam(1e-3)
    cf = cfg.get("moe_capacity_factor", 1.25)

    if attn_fn is None:
        from dist_keras_tpu.ops.pallas.flash_attention import attention_auto

        attn = attention_auto
    else:
        attn = attn_fn

    def forward(params, x):
        import functools

        from dist_keras_tpu.models.transformer import apply_block_aux

        # the shared block definition, with the EP mixture injected; one
        # pmean at the end instead of one per layer
        moe_fn = functools.partial(switch_moe_ep, axis=axis,
                                   capacity_factor=cf)
        h = x @ params["proj"] + params["pos"][None, :x.shape[1]]
        aux = jnp.float32(0.0)
        for blk in params["blocks"]:
            h, a_loss = apply_block_aux(blk, h, attn, causal,
                                        moe_fn=moe_fn)
            aux = aux + a_loss
        aux = lax.pmean(aux, axis)
        pooled = jnp.mean(_ln(params["ln_f"], h), axis=1)
        logits = (pooled @ params["head"]["kernel"]
                  + params["head"]["bias"])
        return logits, aux

    def body(params, opt_state, x, y):
        def loss_fn(p):
            logits, aux = forward(p, x)
            logp = jax.nn.log_softmax(logits)
            nll = -jnp.take_along_axis(
                logp, y[:, None].astype(jnp.int32), axis=-1).mean()
            nll = lax.pmean(nll, axis)  # mean over the data shards
            return nll + aux_weight * aux, (nll, aux)

        (loss, (nll, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, {"loss": loss, "nll": nll, "aux": aux}

    def init_fn(seed=0):
        params = init_transformer_params(jax.random.PRNGKey(seed), cfg)
        return params, tx.init(params)

    def step_fn_factory(params, opt_state):
        from dist_keras_tpu.parallel.fsdp import match_specs_for_state

        pspecs = moe_transformer_param_specs(params, axis)
        ospecs = match_specs_for_state(params, pspecs, opt_state)
        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspecs, ospecs, P(axis), P(axis)),
            out_specs=(pspecs, ospecs, P()),
        ))

    return step_fn_factory, init_fn
