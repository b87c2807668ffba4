"""Pallas TPU flash attention: blockwise Q *and* K/V, forward + backward.

The hot op of the transformer path (SURVEY.md §2.2: native-code effort
belongs in Pallas kernels).  Structure is the standard TPU flash attention:

- **Forward**: grid ``(batch*heads, q_blocks, kv_blocks)``, kv innermost.
  Each program folds one (block_q x block_k) tile into an online-softmax
  accumulator held in VMEM scratch (running max m, denominator l,
  unnormalised output acc); the normalised output block and the row
  logsumexp are written once, on the last kv step.  Logits never exist in
  HBM at any tile size, and VMEM stays O(block_q x block_k + block x d)
  regardless of sequence length — the round-1 kernel streamed the *full*
  K/V per program, which capped T at VMEM size.
- **Backward**: two Pallas kernels recomputing probabilities from the saved
  logsumexp (no logits residual): ``dq`` accumulates over kv blocks with
  the same grid as forward; ``dk/dv`` uses grid ``(bh, kv_blocks,
  q_blocks)`` so each program owns one K/V block and streams Q/dO.
  ``dS = P * (dO V^T - delta + g_lse)`` where ``delta = rowsum(dO * O)``
  (computed in jnp) and ``g_lse`` is the logsumexp cotangent — nonzero
  when ring attention's block-merge differentiates through the lse.
- **lse output**: the kernel returns ``(out, logsumexp)`` so sequence
  parallelism can merge per-device blocks exactly
  (``ops/attention.py: ring_attention``) — lse carries real gradients
  there, hence the ``g_lse`` term above.

Causal masking uses global positions via ``q_offset``/``kv_offset`` (static
ints) so ring attention's shifted blocks mask correctly.  Tiles entirely
above the causal diagonal are skipped with ``pl.when``.

Off-TPU (tests, CPU meshes) the same kernels run under ``interpret=True``;
``attention_auto`` dispatches per backend at trace time.

Precision: probability tiles ``p`` (and ``ds`` in the backward) are
computed in f32 and DOWNCAST TO THE INPUT DTYPE before the MXU matmuls —
on the bf16 trainer path the attention weights lose mantissa per
block-accumulate relative to all-f32 tiles (accumulation itself stays
f32; parity tests pass at the documented tolerances).  This is a
deliberate speed/precision trade: bf16xbf16 runs the MXU at full rate.
The opt-out is the input dtype itself — pass f32 q/k/v and every matmul
(including p/ds) runs in f32.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dist_keras_tpu.ops.attention import attention_with_lse as _ref_with_lse

_NEG_INF = -1e30
_SANITIZE_RE = re.compile(r"[^A-Za-z0-9_.]")


def use_pallas():
    """Single source of truth for the TPU-backend dispatch predicate
    (shared with ``ops.attention._auto_block_fn``)."""
    return jax.default_backend() == "tpu"


def _kernel_name(base):
    """Kernel name carrying the OPEN OBSERVABILITY SPAN path at trace
    time (``spans.current_path()``), so the XProf/TensorBoard timeline
    labels each flash kernel with the same vocabulary the host event
    log uses — a ``train.chunk`` span tracing a compile shows up as
    ``flash_fwd.train.chunk``, and the device trace and the run report
    attribute the same region to the same name (the ROADMAP span
    follow-up).  Resolved when the kernel is TRACED, not per call:
    naming is free on the hot path, and one jitted executable keeps one
    name.  Sanitized to the identifier charset mosaic accepts."""
    from dist_keras_tpu.observability.spans import current_path

    path = current_path()
    name = f"{base}.{path}" if path else base
    return _SANITIZE_RE.sub("_", name)


def _compiler_params(interpret):
    """bh / outer block dims are embarrassingly parallel; the innermost
    grid dim carries the online-softmax scratch, so it must stay
    sequential ('arbitrary')."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))}


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying ``like``'s varying-manual-axes set, so the
    kernels compose with shard_map(check_vma=True) — ring attention calls
    them with the seq axis bound (vma is how jax tracks which mesh axes a
    value varies over inside shard_map)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _causal_mask(logits, qi, ki, block_q, block_k, q_offset, kv_offset,
                 mask_block=1):
    """``mask_block`` over 1: the BLOCK-causal mask, a query sees the keys
    of its own block of ``mask_block`` positions and of every earlier
    one.  Tiles and offsets that are multiples of ``mask_block`` keep the
    callers' tile skipping valid as it is: a tile's first key then opens
    a block, so it is visible to a query iff it is not after it."""
    qpos = (q_offset + qi * block_q
            + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0))
    kpos = (kv_offset + ki * block_k
            + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1))
    if mask_block == 1:
        return jnp.where(qpos >= kpos, logits, _NEG_INF)
    return jnp.where(qpos // mask_block >= kpos // mask_block, logits,
                     _NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k, q_offset, kv_offset,
                mask_block=1):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip tiles strictly above the causal diagonal (their mask is all -inf)
    diag_visible = ((q_offset + (qi + 1) * block_q - 1)
                    >= (kv_offset + ki * block_k)) if causal else True

    @pl.when(diag_visible)
    def _tile():
        # keep tiles in their input dtype (bf16 on the trainer path): the
        # MXU runs bf16 x bf16 -> f32-accumulate at full rate, while
        # upcasting inputs to f32 first would force the ~3x slower f32
        # matmul path.  All reductions/softmax state stay f32.
        q = q_ref[0]                                 # (BQ, D)
        k = k_ref[0]                                 # (BK, D)
        v = v_ref[0]                                 # (BK, D)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (BQ, BK) f32
        if causal:
            logits = _causal_mask(logits, qi, ki, block_q, block_k,
                                  q_offset, kv_offset, mask_block)
        m_prev = m_scr[...]                          # (BQ, 1)
        m_new = jnp.maximum(m_prev, jnp.max(logits, -1, keepdims=True))
        # fully-masked rows inside a visible tile: m_new == -1e30, and
        # exp(logits - m_new) would be exp(0) = 1 per masked entry —
        # shift by 0 instead so those p rows underflow to exactly 0
        safe_m = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(logits - safe_m)                 # (BQ, BK) f32
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _emit():
        l = l_scr[...]
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l > 0, m_scr[...] + jnp.log(l_safe), _NEG_INF)
        lse_ref[0] = lse.astype(lse_ref.dtype)   # (BQ, 1)


def _kv_index_map(causal, block_q, block_k, q_offset, kv_offset, group=1):
    """K/V index map for (bh, q_blocks, kv_blocks=innermost) grids.
    With ``group`` query heads to a K/V head (grouped-query attention)
    query problem ``b`` reads K/V problem ``b // group``: both are
    (batch x heads) flat, heads minor, so the batch carries over.

    For causal attention, tiles strictly above the diagonal are skipped
    by ``pl.when`` — but Pallas still DMAs each grid step's blocks into
    VMEM, so at long T nearly half the K/V bandwidth went to dead tiles.
    Clamping the kv index to the last *visible* block makes every skipped
    step re-address the block already in VMEM; Pallas elides the copy
    when the index is unchanged, so masked tiles cost no HBM traffic."""
    if not causal:
        index = lambda b, i, j: (b, j, 0)                     # noqa: E731
    else:
        def index(b, i, j):
            jmax = jnp.maximum(
                (q_offset + (i + 1) * block_q - 1 - kv_offset) // block_k,
                0)
            return (b, jnp.minimum(j, jmax), 0)

    if group == 1:
        return index
    return lambda b, i, j: index(b // group, i, j)


def _fwd_call(q, k, v, causal, scale, block_q, block_k, q_offset,
              kv_offset, interpret, mask_block=1):
    """q: (BH, Tq, D), k: (BHkv, Tk, D), v: (BHkv, Tk, Dv) -> (out (BH,
    Tq, Dv), lse (BH, Tq, 1)).  The values may be narrower or wider than
    the queries and keys (latent attention: 192 and 128), and there may
    be fewer K/V heads than query heads (grouped-query attention: ``BH /
    BHkv`` consecutive query heads read one K/V head, fetched through the
    index map, never repeated in memory); the backward is written for
    ``Dv == D`` and ``BHkv == BH`` only, and for ``mask_block == 1``
    (the plain causal mask; over 1 it is block-causal, tiles and offsets
    multiples of it: :func:`_causal_mask`)."""
    bh, tq, d = q.shape
    tk, dv = k.shape[1], v.shape[2]
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, q_offset=q_offset, kv_offset=kv_offset,
        **({} if mask_block == 1 else {"mask_block": mask_block}))
    kv_map = _kv_index_map(causal, block_q, block_k, q_offset, kv_offset,
                           group=bh // k.shape[0])
    return pl.pallas_call(
        kernel,
        grid=(bh, tq // block_q, tk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            # lse rides as (BH, T, 1): mosaic wants last-two block dims
            # (8k, 128k) or full-dim, which (block_q, 1) satisfies
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _sds((bh, tq, dv), q.dtype, q),
            _sds((bh, tq, 1), jnp.float32, q),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, dv), jnp.float32)],
        interpret=interpret,
        name=_kernel_name("flash_fwd"),
        **_compiler_params(interpret),
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _p_tile(q, k, lse, *, scale, causal, qi, ki, block_q, block_k,
            q_offset, kv_offset):
    """Recompute the (BQ, BK) f32 probability tile from q/k/lse — the
    shared math of both backward kernels (dq and dk/dv; keeping ONE copy
    means a fix to e.g. the dead-row threshold cannot silently diverge
    between them)."""
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal:
        logits = _causal_mask(logits, qi, ki, block_q, block_k,
                              q_offset, kv_offset)
    # dead rows carry lse == -1e30; exp(logits - lse) would be 1
    safe_lse = jnp.where(lse <= _NEG_INF / 2, 0.0, lse)
    return jnp.exp(logits - safe_lse)


def _ds_tile(p, do, v, dl):
    """dS = P * (dO V^T + (g_lse - delta)) — shared by both backwards."""
    dov = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return p * (dov + dl)


def _bwd_q_index_map(causal, nq, block_q, block_k, q_offset, kv_offset):
    """q-block index map for (bh, kv, q) grids.  Causal skipped tiles
    sit at the START of the inner q loop (q blocks above the diagonal);
    clamping the q index UP to the first visible block elides their
    DMAs (see _kv_index_map)."""
    if not causal:
        return lambda b, i, j: (b, j, 0)

    def _q_clamp(b, i, j):
        jmin = jnp.clip(
            (kv_offset + i * block_k - q_offset) // block_q, 0, nq - 1)
        return (b, jnp.maximum(j, jmin), 0)

    return _q_clamp


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref,
               dq_scr, *, scale, causal, block_q, block_k, q_offset,
               kv_offset):
    """Grid (bh, q_blocks, kv_blocks): accumulate dq over kv.

    dl_ref carries ``g_lse - delta`` per row (combined outside)."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    diag_visible = ((q_offset + (qi + 1) * block_q - 1)
                    >= (kv_offset + ki * block_k)) if causal else True

    @pl.when(diag_visible)
    def _tile():
        # bf16 tiles straight into the MXU, f32 accumulation (see fwd)
        k = k_ref[0]
        p = _p_tile(q_ref[0], k, lse_ref[0].astype(jnp.float32),
                    scale=scale, causal=causal, qi=qi, ki=ki,
                    block_q=block_q, block_k=block_k, q_offset=q_offset,
                    kv_offset=kv_offset)
        ds = _ds_tile(p, do_ref[0], v_ref[0],
                      dl_ref[0].astype(jnp.float32))
        dq_scr[...] += scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _emit():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, scale, causal, block_q,
                block_k, q_offset, kv_offset):
    """Grid (bh, kv_blocks, q_blocks): accumulate dk/dv over q."""
    ki, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    diag_visible = ((q_offset + (qi + 1) * block_q - 1)
                    >= (kv_offset + ki * block_k)) if causal else True

    @pl.when(diag_visible)
    def _tile():
        # bf16 tiles straight into the MXU, f32 accumulation (see fwd)
        q = q_ref[0]
        do = do_ref[0]
        p = _p_tile(q, k_ref[0], lse_ref[0].astype(jnp.float32),
                    scale=scale, causal=causal, qi=qi, ki=ki,
                    block_q=block_q, block_k=block_k, q_offset=q_offset,
                    kv_offset=kv_offset)
        dv_scr[...] += jax.lax.dot_general(          # P^T dO
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = _ds_tile(p, do, v_ref[0], dl_ref[0].astype(jnp.float32))
        dk_scr[...] += scale * jax.lax.dot_general(  # dS^T Q
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _emit():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_call(q, k, v, do, lse, dl, causal, scale, block_q, block_k,
              q_offset, kv_offset, interpret):
    """lse/dl: (BH, Tq, 1) float32."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, q_offset=q_offset, kv_offset=kv_offset)
    qspec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    qrow = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    kv_map = _kv_index_map(causal, block_q, block_k, q_offset, kv_offset)
    kspec = pl.BlockSpec((1, block_k, d), kv_map)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(bh, tq // block_q, tk // block_k),
        in_specs=[qspec, kspec, kspec, qspec, qrow, qrow],
        out_specs=qspec,
        out_shape=_sds((bh, tq, d), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name=_kernel_name("flash_bwd_dq"),
        **_compiler_params(interpret),
    )(q, k, v, do, lse, dl)
    # swapped grid: (bh, kv, q) — index maps read i=kv-block, j=q-block
    _q_clamp = _bwd_q_index_map(causal, tq // block_q, block_q, block_k,
                                q_offset, kv_offset)
    qspec2 = pl.BlockSpec((1, block_q, d), _q_clamp)
    qrow2 = pl.BlockSpec((1, block_q, 1), _q_clamp)
    kspec2 = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common),
        grid=(bh, tk // block_k, tq // block_q),
        in_specs=[qspec2, kspec2, kspec2, qspec2, qrow2, qrow2],
        out_specs=[kspec2, kspec2],
        out_shape=[_sds((bh, tk, d), k.dtype, q),
                   _sds((bh, tk, d), v.dtype, q)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        name=_kernel_name("flash_bwd_dkv"),
        **_compiler_params(interpret),
    )(q, k, v, do, lse, dl)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp core on (BH, T, D) layout, returning (out, lse)
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_core(q, k, v, causal, scale, block_q, block_k, q_offset,
                kv_offset, interpret):
    out, lse = _fwd_call(q, k, v, causal, scale, block_q, block_k,
                         q_offset, kv_offset, interpret)
    return out, lse


def _flash_core_fwd(q, k, v, causal, scale, block_q, block_k, q_offset,
                    kv_offset, interpret):
    out, lse = _fwd_call(q, k, v, causal, scale, block_q, block_k,
                         q_offset, kv_offset, interpret)
    return (out, lse), (q, k, v, out, lse)


def _flash_core_bwd(causal, scale, block_q, block_k, q_offset, kv_offset,
                    interpret, res, cts):
    q, k, v, out, lse = res
    g_out, g_lse = cts
    g_out32 = g_out.astype(jnp.float32)
    delta = jnp.sum(g_out32 * out.astype(jnp.float32), axis=-1,
                    keepdims=True)                           # (BH, T, 1)
    g_lse = (jnp.zeros_like(delta) if g_lse is None
             else g_lse.astype(jnp.float32))
    dl = g_lse - delta
    return _bwd_call(q, k, v, g_out, lse, dl, causal, scale, block_q,
                     block_k, q_offset, kv_offset, interpret)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# ---------------------------------------------------------------------------
# public API on (B, T, H, D) layout
# ---------------------------------------------------------------------------
def _fit_block(t, want):
    """Largest block <= ``want`` that tiles ``t`` evenly and satisfies
    mosaic's sublane rule (multiple of 8, or the full dimension).  None if
    no such block exists — e.g. T=768 with want=512 picks 384 instead of
    silently falling back to the O(T^2) jnp reference."""
    for b in range(min(want, t), 7, -1):
        if t % b == 0 and (b % 8 == 0 or b == t):
            return b
    return t if t < 8 else None


def _to_bh(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _from_bh(x, b, h):
    bh, t, d = x.shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def flash_attention_with_lse(q, k, v, causal=False, scale=None,
                             block_q=1024, block_k=1024, q_offset=0,
                             kv_offset=0, interpret=False, mask_block=1):
    """q,k,v: (B, T, H, D) -> (out (B,T,H,D), lse (B,H,T) float32); k
    and v may have fewer heads, a divisor of H (grouped-query attention).

    Falls back to the jnp reference when T doesn't tile evenly (rare;
    tests and ragged tails).  Offsets shift the *global* positions of the
    local q / kv blocks for causal masking under sequence parallelism.
    ``mask_block`` over 1 makes the causal mask block-causal
    (:func:`_causal_mask`; forward only, and the reference where a tile
    or an offset is no multiple of it).
    """
    b, tq, h, d = q.shape
    tk = k.shape[1]
    scale = (d ** -0.5) if scale is None else scale
    bq = _fit_block(tq, block_q)
    bk = _fit_block(tk, block_k)
    if mask_block != 1 and bq is not None and bk is not None and any(
            n % mask_block for n in (bq, bk, q_offset, kv_offset)):
        bq = None
    if bq is None or bk is None:
        return _ref_with_lse(q, *repeat_kv_heads(h, k, v), causal=causal,
                             scale=scale, q_offset=q_offset,
                             kv_offset=kv_offset,
                             **({} if mask_block == 1
                                else {"mask_block": mask_block}))
    if mask_block != 1:
        out, lse = _fwd_call(_to_bh(q), _to_bh(k), _to_bh(v), causal,
                             scale, bq, bk, int(q_offset), int(kv_offset),
                             interpret, mask_block)
        return _from_bh(out, b, h), lse.reshape(b, h, tq)
    if v.shape[-1] != d or k.shape[2] != h:
        # values of another width than queries and keys, or fewer K/V
        # heads than query heads: forward only (the custom backward
        # assumes one head_dim and one head count)
        out, lse = _fwd_call(_to_bh(q), _to_bh(k), _to_bh(v), causal,
                             scale, bq, bk, int(q_offset), int(kv_offset),
                             interpret)
        return _from_bh(out, b, h), lse.reshape(b, h, tq)
    out, lse = _flash_core(_to_bh(q), _to_bh(k), _to_bh(v), causal, scale,
                           bq, bk, int(q_offset), int(kv_offset), interpret)
    return _from_bh(out, b, h), lse.reshape(b, h, tq)  # lse (BH, T, 1)


def repeat_kv_heads(h, k, v):
    """K and V ``(B, T, Hkv, D)`` with each head repeated to ``h`` query
    heads (query heads ``g*i .. g*i + g - 1`` read K/V head ``i``): what
    the ``jnp`` references take, which know one head count.  The arrays
    themselves when the counts are equal."""
    group = h // k.shape[2]
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


def flash_attention(q, k, v, causal=False, scale=None, block_q=1024,
                    block_k=1024, interpret=False, mask_block=1):
    """Pallas attention. q,k,v: (B, T, H, D) -> (B, T, H, D)."""
    out, _ = flash_attention_with_lse(q, k, v, causal=causal, scale=scale,
                                      block_q=block_q, block_k=block_k,
                                      interpret=interpret,
                                      mask_block=mask_block)
    return out


def attention_auto(q, k, v, causal=False, scale=None, block_q=1024,
                   block_k=1024, mask_block=1):
    """Backend-dispatching attention: Pallas kernel on TPU, jnp reference
    elsewhere.  Decided at trace time via ``jax.default_backend()`` so it
    works under jit/shard_map (tracers carry no device info).
    ``mask_block`` is the causal mask's block length (1: plain causal)."""
    if use_pallas():
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k,
                               mask_block=mask_block)
    from dist_keras_tpu.ops.attention import attention

    return attention(q, *repeat_kv_heads(q.shape[2], k, v), causal=causal,
                     scale=scale, mask_block=mask_block)
