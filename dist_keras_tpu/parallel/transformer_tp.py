"""Composite dp x tp x sp transformer training step.

This is the framework's scale-out showcase: one jitted ``shard_map`` over a
3-D mesh ``(workers, model, seq)`` that combines every parallelism the
framework implements —

- **data parallelism** (``workers``): batch sharded; gradient psum comes out
  of AD automatically (the replicated->varying promotion of shared params
  transposes to a psum over every axis that promoted them);
- **tensor parallelism** (``model``): attention heads and MLP hidden units
  Megatron-split — wq/wk/wv/wo shard the head axis, w1 column-/w2
  row-parallel with a single psum after each block half;
- **sequence parallelism** (``seq``): activations sharded along tokens; the
  attention inner loop is ``ring_attention`` (K/V blocks rotate on ICI with
  an online-softmax accumulator).

The single-device oracle is ``models/transformer.py``; the TP/SP step reuses
its parameter layout, so the tests can assert the sharded loss and the
sharded gradients match the unsharded reference numerically.

New capability relative to dist-keras (SURVEY.md §2.3: TP/SP/long-context
all absent upstream).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from dist_keras_tpu.models.transformer import (
    init_transformer_params,
    layer_norm as _ln,
)
from dist_keras_tpu.ops.attention import ring_attention
from dist_keras_tpu.parallel.mesh import MODEL_AXIS, SEQ_AXIS, WORKER_AXIS, grid_mesh


def make_tp_mesh(dp=1, tp=1, sp=1, devices=None):
    """3-D mesh; tp/sp axes last so they ride the fastest ICI links."""
    return grid_mesh({WORKER_AXIS: dp, MODEL_AXIS: tp, SEQ_AXIS: sp},
                     devices=devices)


def param_specs(params):
    """PartitionSpec pytree: head axis / ff axis over ``model``, everything
    else replicated (LN, embeddings, head — small)."""

    def spec_block(blk):
        return {
            "ln1": {"scale": P(), "bias": P()},
            "wq": P(None, MODEL_AXIS, None),
            "wk": P(None, MODEL_AXIS, None),
            "wv": P(None, MODEL_AXIS, None),
            "wo": P(MODEL_AXIS, None, None),
            "ln2": {"scale": P(), "bias": P()},
            "w1": P(None, MODEL_AXIS),
            "b1": P(MODEL_AXIS),
            "w2": P(MODEL_AXIS, None),
            "b2": P(),
        }

    return {
        "proj": P(),
        "pos": P(),
        "blocks": [spec_block(b) for b in params["blocks"]],
        "ln_f": {"scale": P(), "bias": P()},
        "head": {"kernel": P(), "bias": P()},
    }


def _mlp_half(blk, h):
    with jax.named_scope("mlp"):
        y = _ln(blk["ln2"], h)
        u = jax.nn.gelu(y @ blk["w1"] + blk["b1"])  # column-parallel
        z = u @ blk["w2"]                           # row-parallel
        return h + lax.psum(z, MODEL_AXIS) + blk["b2"]


def _tp_block(blk, h, causal, remat_mlp=False):
    """One Megatron-split block on local shards (heads/ff over ``model``,
    tokens over ``seq`` via ring attention).

    ``remat_mlp``: checkpoint ONLY the MLP half.  The T x T logits never
    exist anyway (flash kernels), so the attention half's residuals are
    O(T x D); the 4x-wide MLP intermediate is the real long-context
    activation hog, and recomputing just it costs one cheap dense
    forward instead of re-running the flash kernels + collectives that
    full-block remat pays (measured v5e, T=32k d768/L4: full remat 89.8k
    tokens/s vs mlp-only 112k+ at a fraction of full-remat's memory)."""
    with jax.named_scope("attention"):
        y = _ln(blk["ln1"], h)
        # local heads only: wq/wk/wv are head-sharded over `model`
        q = jnp.einsum("btd,dhk->bthk", y, blk["wq"])
        k = jnp.einsum("btd,dhk->bthk", y, blk["wk"])
        v = jnp.einsum("btd,dhk->bthk", y, blk["wv"])
        a = ring_attention(q, k, v, axis=SEQ_AXIS, causal=causal)
        # partial over local heads -> reduce over the model axis
        o = jnp.einsum("bthk,hkd->btd", a, blk["wo"])
        h = h + lax.psum(o, MODEL_AXIS)
    mlp = jax.checkpoint(_mlp_half) if remat_mlp else _mlp_half
    return mlp(blk, h)


def tp_transformer_forward(params, x, cfg, causal=False, remat=False):
    """Sharded forward: call inside shard_map over (workers, model, seq).

    x: local activation block (B_local, T_local, input_dim).
    Returns logits (B_local, n_classes), replicated over model+seq axes.

    ``remat`` picks the rematerialization policy — the long-context
    memory lever:

    - ``False``: store all activations (fastest when they fit);
    - ``"mlp"``: checkpoint only each block's MLP half — drops the
      4x-wide MLP intermediates (the dominant activation term) for one
      cheap dense recompute, WITHOUT re-running the flash kernels or
      ring collectives.  The best default for long sequences;
    - ``True``: checkpoint whole blocks — minimal memory, but the
      backward re-runs every flash forward + its collectives (the
      round-3 behavior, kept for the tightest-memory regimes).
    """
    if remat not in (False, True, "mlp", None):
        raise ValueError(
            f"remat={remat!r}: expected False, True, or 'mlp'")
    t_local = x.shape[1]
    seq_idx = lax.axis_index(SEQ_AXIS)
    pos = lax.dynamic_slice_in_dim(
        params["pos"], seq_idx * t_local, t_local, axis=0)
    h = x @ params["proj"] + pos[None]
    if remat == "mlp":
        block = functools.partial(_tp_block, causal=causal,
                                  remat_mlp=True)
    else:
        block = functools.partial(_tp_block, causal=causal)
        if remat:
            block = jax.checkpoint(block)
    for blk in params["blocks"]:
        h = block(blk, h)
    pooled_local = jnp.sum(_ln(params["ln_f"], h), axis=1)
    pooled = lax.psum(pooled_local, SEQ_AXIS) / cfg["seq_len"]
    return pooled @ params["head"]["kernel"] + params["head"]["bias"]


def make_tp_train_step(mesh, cfg, optimizer=None, loss="softmax_xent",
                       causal=False, compute_dtype=None, remat=False):
    """-> (step_fn, init_fn).

    init_fn(seed) -> (params, opt_state) on host.
    step_fn(params, opt_state, x, y) -> (params, opt_state, loss).
      x: (batch, seq_len, input_dim) global; y: (batch,) int labels.
    ``compute_dtype=jnp.bfloat16`` casts params+activations for the
    forward/backward (MXU fast path) while master params, gradients as
    applied, and the loss stay f32 — same policy as trainers/step.py.
    """
    if cfg.get("moe_experts", 0):
        raise ValueError(
            "the Megatron TP step supports dense FFN blocks only; for "
            "MoE use make_moe_train_step (dense compute) or "
            "make_moe_ep_train_step (expert parallelism)")
    tx = optimizer or optax.adam(1e-3)

    def local_loss(p, x, y):
        """Per-device loss on this device's (worker, seq) data block —
        the quantity both factory paths differentiate."""
        if compute_dtype is not None:
            from dist_keras_tpu.utils.pytree import tree_cast

            p = tree_cast(p, compute_dtype)
            x = x.astype(compute_dtype)
        logits = tp_transformer_forward(p, x, cfg, causal=causal,
                                        remat=remat)
        with jax.named_scope("loss"):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            nll = -jnp.take_along_axis(
                logp, y[:, None].astype(jnp.int32), axis=-1).mean()
            # mean over the data-parallel axis -> AD emits the grad
            # psums
            return lax.pmean(nll, WORKER_AXIS)

    def body(params, opt_state, x, y):
        # x local block: (B/workers, T/seq, input_dim); y: (B/workers,)
        loss_val, grads = jax.value_and_grad(
            lambda p: local_loss(p, x, y))(params)
        with jax.named_scope("optimizer"):
            updates, new_opt = tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
        return new_params, new_opt, loss_val

    def init_fn(seed=0):
        params = init_transformer_params(jax.random.PRNGKey(seed), cfg)
        opt_state = tx.init(params)
        return params, opt_state

    def step_fn_factory(params, opt_state):
        pspecs, ospecs, data_x, data_y = tp_step_specs(params, opt_state)
        # grad INSIDE shard_map: the vma-aware transpose inserts the
        # cross-axis psums and proves the output replication
        return jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(pspecs, ospecs, data_x, data_y),
            out_specs=(pspecs, ospecs, P()),
        ))

    return step_fn_factory, init_fn


def tp_step_specs(params, opt_state):
    """The TP step's argument PartitionSpecs — the single source of truth
    shared by the compiled step's in_specs and host-side placement
    (``train_tp_transformer``).  Optimizer leaves inherit their mirrored
    param's spec by tree path (adam's mu/nu embed the param tree)."""
    from dist_keras_tpu.parallel.fsdp import match_specs_for_state

    pspecs = param_specs(params)
    ospecs = match_specs_for_state(params, pspecs, opt_state)
    return (pspecs, ospecs, P(WORKER_AXIS, SEQ_AXIS, None), P(WORKER_AXIS))


def train_tp_transformer(mesh, cfg, x, y, steps=10, optimizer=None,
                         seed=0, causal=False, compute_dtype=None,
                         remat=False):
    """Convenience host loop: compile once, run ``steps`` full-batch updates.

    x: (N, seq_len, input_dim); y: (N,) int labels.  N must divide by the
    mesh's ``workers`` size and seq_len by its ``seq`` size.
    """
    from dist_keras_tpu.parallel.fsdp import place_by_specs

    step_factory, init_fn = make_tp_train_step(
        mesh, cfg, optimizer=optimizer, causal=causal,
        compute_dtype=compute_dtype, remat=remat)
    params, opt_state = init_fn(seed)
    fn = step_factory(params, opt_state)
    # explicit global placement so the loop also runs on a multi-host
    # mesh (a host-committed jnp.asarray is not a valid global input);
    # specs come from the same helper the compiled step's in_specs use
    pspecs, ospecs, xspec, yspec = tp_step_specs(params, opt_state)
    params = place_by_specs(mesh, params, pspecs)
    opt_state = place_by_specs(mesh, opt_state, ospecs)
    xd = place_by_specs(mesh, x, xspec)
    yd = place_by_specs(mesh, y, yspec)
    losses = []
    for _ in range(steps):
        params, opt_state, loss_val = fn(params, opt_state, xd, yd)
        losses.append(float(loss_val))
    return params, losses
