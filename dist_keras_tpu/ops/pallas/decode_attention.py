"""Decode-shaped paged attention: one query position over a paged KV pool.

The flash kernels (``flash_attention.py``) are built for prefill-shaped
work — long Q and K/V extents tiled both ways.  Autoregressive decode
is the opposite regime: ONE query position per sequence, keys/values
scattered across the fixed-size pages the serving-side allocator
(``serving/kv_cache.py``) hands out.  This module is that kernel,
sharing the flash family's machinery (``_NEG_INF`` masking, the online
softmax scratch recurrence, ``_sds``/``_kernel_name``, the backend
dispatch predicate) rather than re-deriving any of it:

- :func:`paged_attention_reference` — pure-jnp oracle: gather the page
  table, mask positions at/after each sequence's length, softmax.  The
  DEFAULT serving path on every backend, and the parity baseline.
- :func:`latent_attention_reference` — the same read over a LATENT pool
  (``models/mla_moe.py``): every head's absorbed query against one shared
  row a position, whose leading values are also the "values".
- :func:`paged_attention_kernel` — the Pallas kernel.  Grid ``(slots,
  pages)`` with the page dim innermost carrying the online-softmax
  scratch of all heads; the page table and per-slot lengths ride as
  SCALAR-PREFETCH operands (``pltpu.PrefetchScalarGridSpec``) so each
  grid step's K/V block index is computed from the page table before
  the DMA issues — the pool is never gathered, each program streams
  exactly the pages its slot owns, one whole page (all heads) a step.
  Fully-masked slots (padding in a fixed-shape decode rung, ``length
  == 0``) produce exact zeros via the same dead-row guards as the
  flash forward.
- :func:`graduate` — the round-19 exact-parity graduation pattern
  (``fused_bwd_experimental``): ``DK_DECODE_KERNEL=1`` routes
  :func:`paged_attention_auto` through the kernel only after a cached
  per-(shape, page-geometry, compiler) :func:`selfcheck` parity run
  against the reference passes EXACT in this process; any other
  verdict falls back to the reference path with one
  ``decode_kernel_rejected`` event — typed fallback, never silent
  divergence.  Off-TPU the kernel runs under ``interpret=True`` (no
  coherence games here, unlike the fused backward, so interpret parity
  is meaningful and the CPU gates exercise the real kernel body).

Shapes: ``q (S, H, D)``; pools ``k/v (P, page_size, H, D)`` — PAGE-MAJOR,
the one layout this module knows: a page is one contiguous ``(page_size,
H, D)`` block, so the writer's scatter over (page, offset) indexes the
major dimensions (what the TPU compiler runs in place; a head-major pool
is converted, whole, on the way in and out of every step) and a reader
fetches a page with one block copy.  ``P`` may span several layers' pages
(the decode engine passes its whole pool viewed flat over (layer, page)
and offsets the page ids; a per-layer slice would be a copy).
``page_table (S, max_pages) int32`` (entries past a slot's allocation
must hold any valid page id — masked by ``lengths``); ``lengths (S,)
int32`` = valid KV positions per slot, INCLUDING the current token (its
k/v is written before attention).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dist_keras_tpu.ops.pallas.flash_attention import (
    _NEG_INF,
    _kernel_name,
    _sds,
    use_pallas,
)
from dist_keras_tpu.utils import knobs


def paged_attention_reference(q, k_pages, v_pages, page_table, lengths,
                              *, scale=None):
    """Pure-jnp oracle and default serving path.

    Gathers each slot's pages, whole ``(page_size, H, D)`` blocks by
    page id, into a contiguous ``(S, T, H, D)`` view (T = max_pages *
    page_size), masks positions past ``lengths``, and softmaxes — with
    the flash dead-row guards so a ``length == 0`` padding slot yields
    exact zeros, not NaN.
    """
    s, h, d = q.shape
    scale = (d ** -0.5) if scale is None else scale
    # (S, max_pages, ps, H, D) -> (S, T, H, D)
    k = k_pages[page_table].reshape(s, -1, h, d)
    v = v_pages[page_table].reshape(s, -1, h, d)
    t = k.shape[1]
    logits = (jnp.einsum("shd,sthd->sht", q, k)
              .astype(jnp.float32) * scale)
    kpos = jnp.arange(t, dtype=jnp.int32)
    mask = kpos[None, None, :] < lengths.astype(jnp.int32)[:, None, None]
    logits = jnp.where(mask, logits, _NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - jnp.where(m <= _NEG_INF / 2, 0.0, m))
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = (jnp.einsum("sht,sthd->shd", p, v)
           / jnp.maximum(l, 1e-30))
    return out.astype(q.dtype)


def latent_attention_reference(q, latent_pages, page_table, lengths, *,
                               rank, scale):
    """The read of a LATENT pool, absorbed form, pure ``jnp``.

    One row a cached position, shared by every head: its first ``rank``
    values are the normalised latent (key part and "values" alike), the
    rest the rotated shared key and zeros up to whole lanes.  ``q (S, H,
    R)`` are the absorbed queries (``q_nope w_uk^T | q_pe``, zeros
    behind), ``latent_pages (P, page_size, R)`` the pool (page-major, any
    number of layers' pages flat),
    ``page_table`` / ``lengths`` as for :func:`paged_attention_reference`
    -> ``sum p c`` ``(S, H, rank)``; a ``length == 0`` padding slot
    yields exact zeros (the same dead-row guard).

    Like the K/V reference it gathers each slot's whole table whatever
    its length (ROADMAP S1).  The gathered rows pass an optimisation
    barrier: without it the compiler moves the products' rounding of
    their operands ahead of the gather and rounds the WHOLE pool, every
    layer of it, once a read (10.9 ms of a 12.7 ms read at 32 slots x
    6656 positions x 9 layers on a v5e, PR 27).
    """
    s, h, r = q.shape
    rows = jax.lax.optimization_barrier(
        latent_pages[page_table].reshape(s, -1, r))
    t = rows.shape[1]
    logits = (jnp.einsum("shr,str->sht", q, rows)
              .astype(jnp.float32) * scale)
    kpos = jnp.arange(t, dtype=jnp.int32)
    mask = kpos[None, None, :] < lengths.astype(jnp.int32)[:, None, None]
    logits = jnp.where(mask, logits, _NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - jnp.where(m <= _NEG_INF / 2, 0.0, m))
    l = jnp.sum(p, axis=-1, keepdims=True)
    # over the whole row, the rotated key's columns dropped afterwards: a
    # slice of the gathered rows would be a second copy of them
    out = jnp.einsum("sht,str->shr", p, rows)[..., :rank]
    return (out / jnp.maximum(l, 1e-30)).astype(q.dtype)


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------
def _decode_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, page_size, scale):
    s, j = pl.program_id(0), pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[s]
    q = q_ref[0].astype(jnp.float32)                # (H, D)
    k = k_ref[0].astype(jnp.float32)                # (ps, H, D)
    v = v_ref[0].astype(jnp.float32)                # (ps, H, D)
    # one query row a head: the products are elementwise with a lane
    # reduction, every head at once; the page's positions stay the
    # leading (untiled) dimension throughout
    logits = jnp.sum(q[None] * k, axis=-1, keepdims=True) * scale
    kpos = (j * page_size
            + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0))
    logits = jnp.where(kpos < length, logits, _NEG_INF)     # (ps, H, 1)
    m_prev = m_scr[...]                             # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=0))
    # same dead-row shift as the flash forward: a fully-masked tile
    # (page past length / padding slot) contributes exactly nothing
    safe_m = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(logits - safe_m[None])              # (ps, H, 1)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=0)
    acc_scr[...] = acc_scr[...] * corr + jnp.sum(p * v, axis=0)
    m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _emit():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def paged_attention_kernel(q, k_pages, v_pages, page_table, lengths,
                           *, scale=None, interpret=False):
    """The Pallas paged decode kernel (see module docstring for the
    contract).  Callers route through :func:`paged_attention_auto`,
    which gates this on the graduation verdict."""
    s, h, d = q.shape
    ps = k_pages.shape[1]
    n_pages = page_table.shape[1]
    scale = (d ** -0.5) if scale is None else scale
    kernel = functools.partial(_decode_kernel, page_size=ps, scale=scale)
    # index maps see (*grid_indices, *scalar_prefetch_refs): the page
    # table picks each grid step's K/V page BEFORE its DMA issues
    kv_map = lambda si, j, pt, ln: (pt[si, j], 0, 0, 0)       # noqa: E731
    q_map = lambda si, j, pt, ln: (si, 0, 0)                  # noqa: E731
    extra = ({} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))})
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, n_pages),
        in_specs=[
            pl.BlockSpec((1, h, d), q_map),
            pl.BlockSpec((1, ps, h, d), kv_map),
            pl.BlockSpec((1, ps, h, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, h, d), q_map),
        scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, d), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_sds((s, h, d), q.dtype, q),
        interpret=interpret,
        name=_kernel_name("paged_decode"),
        **extra,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pages, v_pages)


# ---------------------------------------------------------------------------
# graduation (DK_DECODE_KERNEL) — the round-19 exact-parity pattern
# ---------------------------------------------------------------------------
def selfcheck(slots=4, heads=2, head_dim=64, page_size=8, n_pages=4,
              dtype=jnp.float32, seed=0, tol=1e-5, interpret=False):
    """Parity-check the kernel against the jnp reference at one exact
    slot/head/page geometry -> ``SelfCheckVerdict`` (the shared typed
    verdict class).  Lengths cover the awkward cases: 0 (padding slot),
    a partial page, an exact page boundary, and the full extent."""
    import numpy as np

    from dist_keras_tpu.ops.pallas.fused_bwd_experimental import (
        SelfCheckVerdict,
    )

    if not interpret and not use_pallas():
        return SelfCheckVerdict(
            False, None, "unverifiable",
            f"backend {jax.default_backend()!r} cannot run the "
            "un-interpreted kernel — the jnp reference stays in effect")
    rng = np.random.default_rng(seed)
    pool = n_pages * slots + 1          # +1 scratch-style spare
    q = jnp.asarray(rng.normal(size=(slots, heads, head_dim)), dtype)
    kp = jnp.asarray(
        rng.normal(size=(pool, page_size, heads, head_dim)), dtype)
    vp = jnp.asarray(
        rng.normal(size=(pool, page_size, heads, head_dim)), dtype)
    pt = jnp.asarray(
        rng.integers(0, pool, size=(slots, n_pages)), jnp.int32)
    t = n_pages * page_size
    picks = [0, min(1, t), page_size, t]
    lengths = jnp.asarray(
        [picks[i % len(picks)] for i in range(slots)], jnp.int32)
    ref = paged_attention_reference(q, kp, vp, pt, lengths)
    got = paged_attention_kernel(q, kp, vp, pt, lengths,
                                 interpret=interpret)
    a = np.asarray(ref, np.float32)
    b = np.asarray(got, np.float32)
    err = float(np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9))
    if err <= tol:
        return SelfCheckVerdict(True, err, "exact")
    return SelfCheckVerdict(
        False, err, "mismatch",
        f"paged decode kernel diverged from the jnp reference "
        f"(rel err {err:.3g} > tol {tol:g})")


_VERDICTS = {}


def clear_verdicts():
    """Drop the cached graduation verdicts (tests / compiler swap)."""
    _VERDICTS.clear()


def graduate(slots, heads, head_dim, page_size, n_pages, dtype,
             interpret=False):
    """-> the cached verdict deciding whether the kernel may serve this
    exact slot/head/page geometry on this compiler.  Only ``status ==
    "exact"`` graduates; a non-exact verdict emits one
    ``decode_kernel_rejected`` event when first cached."""
    from dist_keras_tpu.observability import events
    from dist_keras_tpu.ops.pallas.fused_bwd_experimental import (
        compiler_fingerprint,
    )

    key = (int(slots), int(heads), int(head_dim), int(page_size),
           int(n_pages), str(dtype), bool(interpret),
           compiler_fingerprint())
    v = _VERDICTS.get(key)
    if v is None:
        v = _VERDICTS[key] = selfcheck(
            slots=slots, heads=heads, head_dim=head_dim,
            page_size=page_size, n_pages=n_pages, dtype=dtype,
            interpret=interpret)
        if v.status != "exact":
            events.emit("decode_kernel_rejected", reason=v.status,
                        detail=v.reason, err=v.err,
                        shape=[slots, heads, head_dim],
                        pages=[page_size, n_pages])
    return v


def paged_attention_auto(q, k_pages, v_pages, page_table, lengths,
                         *, scale=None):
    """Trace-time dispatch: the graduated kernel when
    ``DK_DECODE_KERNEL=1`` and the parity verdict for this exact
    geometry is ``"exact"`` (interpret mode off-TPU); the jnp reference
    otherwise.  The decode engine calls this inside its jitted step, so
    the decision is made once per traced shape."""
    if knobs.get("DK_DECODE_KERNEL"):
        s, h, d = q.shape
        interpret = not use_pallas()
        v = graduate(s, h, d, k_pages.shape[1], page_table.shape[1],
                     q.dtype, interpret=interpret)
        if v.status == "exact":
            return paged_attention_kernel(
                q, k_pages, v_pages, page_table, lengths, scale=scale,
                interpret=interpret)
    return paged_attention_reference(
        q, k_pages, v_pages, page_table, lengths, scale=scale)
