"""Attention ops: single-device reference + ring attention (sequence
parallelism over the ICI mesh).

New capability surface relative to the reference (SURVEY.md §2.3: no
attention, no sequence models anywhere in dist-keras) — built TPU-first:

- ``attention``: plain fused softmax(QK^T)V in jnp; XLA fuses this well for
  moderate sequence lengths.  Shapes are (batch, seq, heads, head_dim).
- ``attention_with_lse``: same, returning the per-row logsumexp — the
  contract shared with the Pallas flash kernel
  (``ops/pallas/flash_attention.py``) so either can be the block compute
  of ring attention.
- ``ring_attention``: blockwise attention over a named mesh axis.  Each
  device holds one sequence block of Q/K/V; K/V blocks rotate around the
  ring with ``ppermute`` while normalised block outputs are merged through
  their logsumexp (the flash-attention recurrence in logspace).  Peak
  memory is O(block^2) instead of O(seq^2) and the permute overlaps with
  the block matmuls on TPU.  Call it INSIDE ``shard_map`` with the
  sequence axis bound (see tests and ``parallel/transformer_tp.py``).
  On TPU backends each block is computed by the Pallas flash kernel; the
  jnp reference elsewhere.

Causal masking uses *global* positions, so the sharded result matches the
single-device reference bit-for-bit up to reduction order.  Ring blocks
are aligned and equally sized, so a K/V block is either fully visible
(earlier in the sequence), fully masked (later — zeroed via its lse), or
the diagonal (local causal mask); no kernel-side global offsets are needed
on the ring path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from dist_keras_tpu.parallel.mesh import SEQ_AXIS

_NEG_INF = -1e30


def attention(q, k, v, causal=False, scale=None, mask_block=1):
    """Reference attention. q,k,v: (B, T, H, D) -> (B, T, H, D).  With
    ``mask_block`` over 1 the causal mask is BLOCK-causal: position ``t``
    sees ``s`` iff ``s // mask_block <= t // mask_block`` (generation by
    diffusion over blocks: a block's positions all see one another)."""
    d = q.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    logits = jnp.einsum("bthd,bshd->bhts", q, k) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        if mask_block == 1:
            mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        else:
            mask = ((tk - tq + jnp.arange(tq))[:, None] // mask_block
                    >= jnp.arange(tk)[None, :] // mask_block)
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def attention_with_lse(q, k, v, causal=False, scale=None, q_offset=0,
                       kv_offset=0, mask_block=1):
    """Attention returning (out (B,T,H,D), lse (B,H,T) float32).

    ``q_offset``/``kv_offset`` shift the global positions used by the
    causal mask (sequence-parallel blocks), which is block-causal with
    ``mask_block`` over 1 (:func:`attention`).  Fully-masked rows produce
    a zero output row and lse = -1e30 (finite, so downstream logaddexp
    merges stay NaN-free).
    """
    d = q.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    logits = (jnp.einsum("bthd,bshd->bhts", q, k)
              .astype(jnp.float32) * scale)
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        qpos = q_offset + jnp.arange(tq)
        kpos = kv_offset + jnp.arange(tk)
        if mask_block != 1:
            qpos, kpos = qpos // mask_block, kpos // mask_block
        mask = qpos[:, None] >= kpos[None, :]
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    dead = m <= _NEG_INF / 2            # fully-masked rows
    p = jnp.exp(logits - jnp.where(dead, 0.0, m))
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = (jnp.einsum("bhts,bshd->bthd", p, v)
           / jnp.moveaxis(jnp.maximum(l, 1e-30), 1, 2))
    lse = jnp.where(dead[..., 0], _NEG_INF,
                    m[..., 0] + jnp.log(jnp.maximum(l[..., 0], 1e-30)))
    return out.astype(q.dtype), lse


def _auto_block_fn():
    """(q,k,v,causal,scale) -> (out, lse): Pallas flash kernel on TPU
    backends, the jnp reference elsewhere (trace-time dispatch)."""
    from dist_keras_tpu.ops.pallas.flash_attention import (
        flash_attention_with_lse,
        use_pallas,
    )

    if use_pallas():
        return flash_attention_with_lse
    return attention_with_lse


def _merge_blocks(acc, o_blk, lse_blk):
    """Fold a normalised block (o, lse) into the running (o, lse) — the
    flash recurrence in logspace; exact, order-independent up to fp."""
    o_acc, lse_acc = acc
    lse_new = jnp.logaddexp(lse_acc, lse_blk)
    w_old = jnp.exp(lse_acc - lse_new)
    w_new = jnp.exp(lse_blk - lse_new)
    o_new = (o_acc * jnp.moveaxis(w_old, 1, 2)[..., None]
             + o_blk * jnp.moveaxis(w_new, 1, 2)[..., None])
    return o_new, lse_new


def ring_attention(q, k, v, axis=SEQ_AXIS, causal=False, scale=None,
                   attn_fn=None):
    """Sequence-parallel attention inside shard_map.

    q,k,v: local blocks (B, T_local, H, D); the full sequence is the
    concatenation of blocks along the ``axis`` mesh dimension in device
    order.  Returns the local (B, T_local, H, D) output block.

    ``attn_fn(q, k, v, causal=..., scale=...) -> (out, lse)`` is the block
    compute; defaults to the Pallas flash kernel on TPU, jnp elsewhere.
    """
    d = q.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    attn_fn = attn_fn or _auto_block_fn()
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    t_local = q.shape[1]
    q_start = idx * t_local

    # step 0: the diagonal block — local causal mask (global offsets
    # cancel on the diagonal, so none are needed).  The merge accumulator
    # runs in f32 regardless of input dtype (logspace weights are f32 and
    # the fori_loop carry must be type-stable); cast back at the end.
    o, lse = attn_fn(q, k, v, causal=causal, scale=scale)
    o = o.astype(jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def ring_step(r, carry):
        o, lse, k, v = carry
        k = lax.ppermute(k, axis, perm)
        v = lax.ppermute(v, axis, perm)
        # K/V received at step r originated on device (idx - r) mod n
        kv_start = ((idx - r) % n) * t_local
        o_blk, lse_blk = attn_fn(q, k, v, causal=False, scale=scale)
        if causal:
            # aligned equal blocks: strictly-later K/V blocks are fully
            # masked; strictly-earlier ones fully visible
            hidden = kv_start > q_start
            lse_blk = jnp.where(hidden, _NEG_INF, lse_blk)
            o_blk = jnp.where(hidden, 0.0, o_blk)
        o, lse = _merge_blocks((o, lse), o_blk, lse_blk)
        return o, lse, k, v

    o, lse, k, v = lax.fori_loop(1, n, ring_step, (o, lse, k, v))
    # merges accumulate through float32 lse weights; restore the input
    # dtype so ring output matches the non-ring attn_fn contract
    return o.astype(q.dtype)
