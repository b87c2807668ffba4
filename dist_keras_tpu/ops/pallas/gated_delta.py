"""The gated delta rule's decode update on a pool of per-sequence states,
in place (Pallas, TPU).

A decode step reads AND writes every live sequence's recurrent matrices:
``heads x dk x dv`` float32 values a linear-attention layer (2.2 MB at 30
heads of 96 x 192), which for 32 slots is as much as the layer's weights.
In ``jnp`` the update is a gather of the slots' rows out of the pool, the
recurrence (:func:`~dist_keras_tpu.ops.gated_delta.gated_delta_step`) and a
scatter back: the rows cross the memory five times (the gather's read and
write, the recurrence's read, the new rows' write, the scatter's copy into
the pool) where the recurrence needs a read and a write.
:func:`state_step_kernel` does the needed two: grid ``(slots,)``, a slot's
row picked through the scalar-prefetched row ids by the block's index map,
the same block of the ALIASED output written back, so that the donated
pool is updated where it lies and nothing of its size is ever copied.

Per head, with the state ``S (dk, dv)`` in VMEM and everything on the VPU
in float32 (no product is rounded: what comes out is what
``gated_delta_step`` gives)::

    u   = beta v - sum_i S[i, :] (beta alpha k)[i]          (a row, dv)
    S'  = alpha S + k u^T
    o   = sum_i S'[i, :] q[i]

A head's ``k``, ``beta alpha k``, ``q`` and ``alpha`` are needed down the
``dk`` (sublane) dimension, one value a state row, so the caller lays them
out as COLUMNS of one ``(dk, 128)`` tile a slot (four columns a head, head
``h`` at lanes ``h``, ``H + h``, ``2 H + h``, ``3 H + h``): a column is a
lane slice of the tile and multiplies the state by lane broadcast, no
transpose anywhere.  Padding slots all name the pool's scratch row, whose
content nobody reads.

:func:`state_step_auto` picks by the platform alone, as ``attention_auto``
does: the kernel on a TPU, gather / ``gated_delta_step`` / scatter
elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dist_keras_tpu.ops.gated_delta import gated_delta_step
from dist_keras_tpu.ops.pallas.flash_attention import (
    _kernel_name,
    _sds,
    use_pallas,
)

_LANES = 128


def _step_kernel(rows_ref, cols_ref, vb_ref, s_ref, o_ref, s_out_ref, *,
                 heads):
    del rows_ref                       # read by the index maps
    cols = cols_ref[0]                                     # (dk, lanes)
    for h in range(heads):
        k, kba, q, alpha = (cols[:, j * heads + h:j * heads + h + 1]
                            for j in range(4))             # (dk, 1) each
        s = s_ref[0, h]                                    # (dk, dv)
        u = vb_ref[0, h:h + 1, :] - jnp.sum(s * kba, axis=0, keepdims=True)
        new = s * alpha + k * u
        s_out_ref[0, h] = new
        o_ref[0, h:h + 1, :] = jnp.sum(new * q, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def state_step_kernel(states, rows, q, k, v, g, beta, *, interpret=False):
    """``states (R, H, dk, dv)`` (any number of layers' rows, flat),
    ``rows (S,)`` the row of each slot, ``q, k (S, H, dk)``, ``v (S, H,
    dv)``, ``g, beta (S, H)`` -> (``o (S, H, dv)``, ``states`` with the
    slots' rows one position on, every other row as it was)."""
    n, h, dk = q.shape
    dv = v.shape[-1]
    lanes = -(-4 * h // _LANES) * _LANES
    alpha = jnp.exp(g)                                      # (S, H)
    cols = jnp.concatenate(
        [k, (beta * alpha)[..., None] * k, q,
         jnp.broadcast_to(alpha[..., None], k.shape)], 1)   # (S, 4 H, dk)
    cols = jnp.pad(jnp.swapaxes(cols, 1, 2),
                   ((0, 0), (0, 0), (0, lanes - 4 * h)))    # (S, dk, lanes)
    row_map = lambda i, rows: (rows[i], 0, 0, 0)            # noqa: E731
    slot_map = lambda i, rows: (i, 0, 0)                    # noqa: E731
    extra = ({} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=64 * 1024 * 1024)})
    o, states = pl.pallas_call(
        functools.partial(_step_kernel, heads=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[pl.BlockSpec((1, dk, lanes), slot_map),
                      pl.BlockSpec((1, h, dv), slot_map),
                      pl.BlockSpec((1, h, dk, dv), row_map)],
            out_specs=[pl.BlockSpec((1, h, dv), slot_map),
                       pl.BlockSpec((1, h, dk, dv), row_map)]),
        out_shape=[_sds((n, h, dv), v.dtype, v),
                   _sds(states.shape, states.dtype, states)],
        # counted over every operand, the row ids first
        input_output_aliases={3: 1},
        interpret=interpret,
        name=_kernel_name("gdn_state_step"),
        **extra,
    )(rows.astype(jnp.int32), cols, beta[..., None] * v, states)
    return o, states


def state_step_auto(pool, layer, rows, q, k, v, g, beta):
    """One position for the slots whose states are rows ``rows`` of layer
    ``layer`` of ``pool (layers, rows, H, dk, dv)`` -> (``o``, the pool
    updated)."""
    if use_pallas():
        o, flat = state_step_kernel(
            pool.reshape(-1, *pool.shape[2:]), rows + layer * pool.shape[1],
            q, k, v, g, beta)
        return o, flat.reshape(pool.shape)
    o, new = gated_delta_step(pool[layer, rows], q, k, v, g, beta)
    return o, pool.at[layer, rows].set(new)
