"""Decode-shaped paged attention: one query position over a paged pool.

The flash kernels (``flash_attention.py``) are built for prefill-shaped
work — long Q and K/V extents tiled both ways.  Autoregressive decode
is the opposite regime: ONE query position per sequence, keys/values
scattered across the fixed-size pages the serving-side allocator
(``serving/kv_cache.py``) hands out.  What reads such a pool lives here,
sharing the flash family's machinery (``_NEG_INF`` masking, the online
softmax scratch recurrence, ``_sds``/``_kernel_name``, the backend
dispatch predicate):

- :func:`latent_attention_reference` — the read of a pool of ROWS, one a
  cached position, pure ``jnp``: every head's query against the row,
  whose leading values are also the "values"; gather the page table, mask
  positions at/after each sequence's length, softmax.  The oracle, and
  the path off the TPU.
- :func:`latent_attention_kernel` — the Pallas read of such a pool, and
  on a TPU the ONLY one (:func:`latent_attention_auto`: the platform
  chooses, nothing else does).  The pool stays in HBM; grid ``(slots,
  blocks of pages)``, each LIVE block's pages copied page by page into
  one of two VMEM buffers while the block before it computes, blocks at
  or past a slot's length skipped.  Every family reads through it:
  ``models/mla_moe.py`` its latent rows (every head's absorbed query
  against one shared row), and ``models/lfm2_moe.py``,
  ``models/olmo_hybrid.py``, ``models/sdar_moe.py``, ``models/ouro.py``
  and, since PR 37, ``models/transformer.py`` their ``v | k`` rows
  through ``models/blocks.py:attend_rows`` (head h's query
  laid into the lanes of its own keys, the row's leading half the values,
  head h keeping its own lanes of the sum), each stating the pages a
  block holds for its row's width.
- Why rows and nothing else: a kernel of one page a grid step over a K
  and a V pool lost to the rows twice on the chip (5.96 against 0.98 ms
  a read at lfm2's shape, PR 32; 4.18 against 1.39 at olmo's, PR 36:
  every table entry cost its grid step and its products, dead or not),
  and a ``jnp`` gather of every slot's whole K and V tables lost to them
  too (13.04 ms six reads at 8 slots of 1,024-2,048 positions against
  2.98, PERF.md, PR 37).  Neither is in the package; the gather is the
  oracle of the rows' tests (``tests/test_decode.py``).
- No gate and no knob: the platform alone picks a path; ``chip_smoke.py``
  stage D holds each kernel to a "highest" reference on the chip.

Shapes: ``q (S, H, R)``; a pool of rows ``(P, page_size, R)`` —
PAGE-MAJOR, the one layout this module knows: a page is one contiguous
``(page_size, R)`` block, so the writer's scatter over (page, offset)
indexes the major dimensions (what the TPU compiler runs in place; a
head-major pool is converted, whole, on the way in and out of every step)
and a reader fetches a page with one block copy.  ``P`` may span several
layers' pages (the decode engine passes its whole pool viewed flat over
(layer, page) and offsets the page ids; a per-layer slice would be a
copy).
``page_table (S, max_pages) int32`` (entries past a slot's allocation
must hold any valid page id — masked by ``lengths``); ``lengths (S,)
int32`` = valid cached positions per slot, INCLUDING the current token
(its row is written before attention).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dist_keras_tpu.ops.pallas.flash_attention import (
    _NEG_INF,
    _kernel_name,
    _sds,
    use_pallas,
)


def latent_attention_reference(q, latent_pages, page_table, lengths, *,
                               rank, scale):
    """The read of a LATENT pool, absorbed form, pure ``jnp``.

    One row a cached position, shared by every head: its first ``rank``
    values are the normalised latent (key part and "values" alike), the
    rest the rotated shared key and zeros up to whole lanes.  ``q (S, H,
    R)`` are the absorbed queries (``q_nope w_uk^T | q_pe``, zeros
    behind), ``latent_pages (P, page_size, R)`` the pool (page-major, any
    number of layers' pages flat), ``page_table`` / ``lengths`` as the
    module docstring states them -> ``sum p c`` ``(S, H, rank)``; a
    ``length == 0`` padding slot yields exact zeros (the flash forward's
    dead-row guard).

    It gathers each slot's whole table whatever its length: the oracle
    of :func:`latent_attention_kernel`, which walks the live pages in
    place, and what serves off the TPU (on a
    v5e the gather was 21.75 of a 28.19 ms decode step at 32 slots x
    6656 positions x 9 layers, PR 27; the kernel's reads 4.37 of 11.04,
    PR 28).  The gathered rows pass an optimisation barrier: without it
    the TPU's compiler moves the products' rounding of their operands
    ahead of the gather and rounds the WHOLE pool, every layer of it,
    once a read (PR 27); it costs the CPU nothing and keeps the
    reference usable as a control on the chip.
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
# the latent kernel: live pages read in place, a block of pages a grid step
# ---------------------------------------------------------------------------
# Pages a grid step fetches.  One 40 KB page (16 positions x 640 lanes x
# 4 B) is 0.05 us of a v5e's HBM time against about 0.35 us a grid step,
# so a step a page would cost more than the gather it replaces; 32 pages
# (512 positions, 1.3 MB) are 1.6 us, and two such buffers fit VMEM many
# times over.
LATENT_BLOCK_PAGES = 32


def latent_walked_positions(lengths, page_size,
                            block_pages=LATENT_BLOCK_PAGES):
    """The cached positions :func:`latent_attention_kernel` fetches for
    slots of these ``lengths``: each live slot's length rounded up to
    whole blocks (host arithmetic on numpy values, no device involved)."""
    block = int(page_size) * int(block_pages)
    return int((-(-np.asarray(lengths, np.int64) // block)).sum()) * block


def _page_copies(pages_ref, page_ids, buf, sem):
    """The copies that bring a block of pages from the pool (left in HBM)
    into ``buf (pages x page_size, ...)``, page by page, all signalling
    ``sem``; a wait needs copies of the same shapes, whatever their ids."""
    ps = pages_ref.shape[1]
    return [pltpu.make_async_copy(pages_ref.at[page],
                                  buf.at[pl.ds(i * ps, ps)], sem)
            for i, page in enumerate(page_ids)]


def operand_dtype():
    """What a product rounds float32 operands to under the ambient
    ``jax.default_matmul_precision``: bfloat16 (one MXU pass, what the
    reference's ``einsum``s do on a TPU) unless a caller asked for more,
    as the parity tests and the engine's oracles do on both sides."""
    ambient = jax.config.jax_default_matmul_precision
    return (jnp.bfloat16 if ambient in (None, "default", "bfloat16")
            else jnp.float32)


def _latent_kernel(pt_ref, len_ref, q_ref, pages_ref, o_ref, buf, sems,
                   state, m_scr, l_scr, acc_scr, *, scale, n_pages, operand):
    s, j = pl.program_id(0), pl.program_id(1)
    ns, nj = pl.num_programs(0), pl.num_programs(1)
    bk = buf.shape[1]                     # positions a block
    block_pages = bk // pages_ref.shape[1]
    length = len_ref[s]

    @pl.when((s == 0) & (j == 0))
    def _first():
        state[0] = 0                      # the buffer the next wait reads
        state[1] = 1                      # nothing is in flight yet

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def fetch(slot, block, b):
        """Copies of one block of one slot's pages into buffer ``b``, ids
        from the flat table; a table whose width is no multiple of the
        block repeats its last page, which the lengths mask."""
        first, last = slot * n_pages + block * block_pages, (
            slot + 1) * n_pages - 1
        return _page_copies(
            pages_ref, [pt_ref[jnp.minimum(first + i, last)]
                        for i in range(block_pages)], buf.at[b], sems.at[b])

    # a block whose first position is at or past ``length`` costs its
    # grid step and nothing else: no copy, no product
    @pl.when(j * bk < length)
    def _live():
        b = state[0]

        @pl.when(state[1] == 1)
        def _own():                       # the first live block of the call
            for c in fetch(s, j, b):
                c.start()
            state[1] = 0

        # the next live block's copies fly while this one computes: the
        # next block of this slot, else the first of the next live slot
        more = ((j + 1) * bk < length) & (j + 1 < nj)
        after = jax.lax.while_loop(
            lambda t: (t < ns) & (len_ref[jnp.minimum(t, ns - 1)] <= 0),
            lambda t: t + 1, s + 1)
        next_s = jnp.where(more, s, after)
        next_j = jnp.where(more, j + 1, 0)

        @pl.when(next_s < ns)
        def _prefetch():
            for c in fetch(next_s, next_j, 1 - b):
                c.start()

        for c in _page_copies(pages_ref, [0] * block_pages, buf.at[b],
                              sems.at[b]):
            # dklint: ignore[unbounded-wait] a DMA semaphore inside the kernel, not a thread: every wait has its start above
            c.wait()
        state[0] = 1 - b

        # the rounding of the configuration's precision happens HERE, on
        # the block in VMEM: the pool itself is never rounded or rewritten
        rows = buf[b].astype(operand)                       # (bk, R)
        precision = (jax.lax.Precision.HIGHEST
                     if operand == jnp.float32 else None)
        logits = jax.lax.dot_general(
            q_ref[0].astype(operand), rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision) * scale                    # (H, bk)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(kpos < length, logits, _NEG_INF)
        m_prev = m_scr[...]                                 # (H, 1)
        # a live block holds a live position: m_new is a real maximum
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(operand), rows[:, :acc_scr.shape[1]],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _emit():
        # a ``length == 0`` slot never ran a block: 0 / 1e-30, exact zeros
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = out[:, :o_ref.shape[2]].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret",
                                             "block_pages"))
def latent_attention_kernel(q, latent_pages, page_table, lengths, *, rank,
                            scale, interpret=False,
                            block_pages=LATENT_BLOCK_PAGES):
    """The Pallas read of a latent pool: the contract of
    :func:`latent_attention_reference`, the pool left where it is.

    Grid ``(slots, blocks of pages)``; the page table (flat) and the
    lengths ride as scalar prefetch, the pool stays in HBM (``pl.ANY``)
    and each LIVE block's ``block_pages`` pages are copied page by page
    into one of two VMEM buffers, the next live block's copies issued
    before this block's products so that they overlap (across slots too,
    which is why the slot dimension is ``"arbitrary"`` and not
    ``"parallel"``: the carried prefetch orders it).  A block is fetched
    once and meets every head's absorbed query as two MXU products,
    ``(H, R) x (R, T)`` and ``(H, T) x (T, rank)``, operands rounded as
    the ambient matmul precision says (:func:`operand_dtype`), sums,
    maximum and exponentials in float32.  Blocks at or past a slot's
    length are skipped; a padding slot (``length == 0``) yields exact
    zeros.  Under its own ``jax.jit`` so that a step traces and lowers
    the kernel once, not once a layer (a block's unrolled copies make a
    large jaxpr: 1.2 s a decode rung of nine layers otherwise, every
    set-up, before the compile cache can even be asked)."""
    s, h, r = q.shape
    ps = latent_pages.shape[1]
    n_pages = page_table.shape[1]
    n_blocks = -(-n_pages // block_pages)
    # the values are the row's first ``rank`` columns: a lane-aligned
    # slice of the block is free, any other would be a copy of it
    vcols = rank if rank % 128 == 0 else r
    kernel = functools.partial(_latent_kernel, scale=scale, n_pages=n_pages,
                               operand=operand_dtype())
    q_map = lambda si, j, pt, ln: (si, 0, 0)                  # noqa: E731
    extra = ({} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"))})
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, n_blocks),
        in_specs=[pl.BlockSpec((1, h, r), q_map),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, h, rank), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, block_pages * ps, r), latent_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, vcols), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_sds((s, h, rank), q.dtype, q),
        interpret=interpret,
        name=_kernel_name("latent_decode"),
        **extra,
    )(page_table.astype(jnp.int32).reshape(-1), lengths.astype(jnp.int32),
      q, latent_pages)


def latent_attention_auto(q, latent_pages, page_table, lengths, *, rank,
                          scale, block_pages=LATENT_BLOCK_PAGES):
    """Trace-time dispatch, the rule of ``flash_attention.attention_auto``:
    the kernel on a TPU, the ``jnp`` reference elsewhere.  ``block_pages``
    is the kernel's: a family whose rows are wide states a smaller block
    (two buffers of it must fit VMEM)."""
    if use_pallas():
        return latent_attention_kernel(q, latent_pages, page_table, lengths,
                                       rank=rank, scale=scale,
                                       block_pages=block_pages)
    return latent_attention_reference(q, latent_pages, page_table, lengths,
                                      rank=rank, scale=scale)
