"""EXPERIMENTAL single-pass flash backward — opt-in, self-checking.

The shipped backward (``flash_attention.py:_bwd_call``) runs TWO kernels
(dq with kv innermost; dk/dv with q innermost), recomputing the
probability tile in each — 7 matmul-tile units where 5 are useful, the
documented 1.4x structural recompute (README roofline).  This module is
the round-5 "dq-accumulation via HBM scratch" experiment VERDICT r4 #7
asked for: ONE kernel with grid ``(bh, kv_blocks, q_blocks)`` — dk/dv
accumulate in VMEM scratch over the inner q loop, and dq accumulates
ACROSS the outer kv loop by aliasing a zeros input to the dq output
(``input_output_aliases``: each revisit reads the block, adds its
contribution, writes it back).

Measured on TPU v5e (this image, 2026-07-31), T=32k, bq=bk=1024, causal,
d=128, bh=6: **bit-exact vs the two-kernel backward and 15% faster**
(59.9 ms -> 50.9 ms median of 5) — worth ~15% of the whole T=32k
training step.

Why it is NOT the default: whether a revisited aliased block observes
the previous visit's write is UNDOCUMENTED Mosaic pipelining behavior,
and it is empirically shape-dependent —

======================  =========================================
shape                   fused vs two-kernel dq
======================  =========================================
nq=1  (t=1024/1024)     exact (causal and non-causal, bh=4)
nq=2  (t=2048/1024)     CORRUPT: 2.5e-2 causal, 6.6e-1 non-causal
nq=8  (t=4096/512) bh=6 exact (causal)
nq=8  (t=4096/512) bh=2 CORRUPT: 2.0e-2 (causal) — same shape,
                        different batch*heads, different outcome
nq=8  (bq=128 bk=256)   CORRUPT: 5.2e-2 (causal); exact non-causal
nq=32 (t=32k/1024) bh=6 exact (causal)
interpret=True          always last-write-wins (a minimal kernel
                        adding +1 per revisit over 3 visits gives 3)
======================  =========================================

The bh dependence (the "parallel" grid dim, which Mosaic may split
across cores) is damning enough; the clincher is CONTEXT dependence:
the bh=6/t=4096/512 row above measured exact inside a ``jax.jit``-ed
closure and rel-err ~1.6e-2 when the same call ran eagerly in a fresh
process — coherence varies with the surrounding execution context, not
just the shape.  Exactness observed once (including the 32k headline
row) is therefore not a property of the shape at all; every "exact"
entry above is a single-context observation.

A Mosaic update could silently flip any row, and silent gradient
corruption is the worst failure mode a training framework can ship.
Hence: opt-in only, and ``selfcheck()`` exists so a caller can verify
exactness for ITS exact shape/blocking on ITS compiler before trusting
the kernel.  Reference point: jax's own canonical TPU flash kernels use
the same two-kernel backward structure as our default.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dist_keras_tpu.ops.pallas.flash_attention import (
    _bwd_call,
    _bwd_q_index_map,
    _ds_tile,
    _fwd_call,
    _p_tile,
    _sds,
    use_pallas,
)


def _fused_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                      dq_in_ref, dq_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                      *, scale, causal, block_q, block_k, q_offset,
                      kv_offset):
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
        q = q_ref[0]
        k = k_ref[0]
        do = do_ref[0]
        # shared tile math (flash_attention._p_tile/_ds_tile): this
        # kernel differs from the default backward ONLY in the aliased
        # dq accumulation below
        p = _p_tile(q, k, lse_ref[0].astype(jnp.float32), scale=scale,
                    causal=causal, qi=qi, ki=ki, block_q=block_q,
                    block_k=block_k, q_offset=q_offset,
                    kv_offset=kv_offset)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = _ds_tile(p, do, v_ref[0], dl_ref[0].astype(jnp.float32))
        dk_scr[...] += scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # the experiment: read-add-write the aliased HBM dq block
        dq_ref[0] = dq_in_ref[0] + scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_not(diag_visible))
    def _passthrough():
        # skipped tile: the aliased dq block must survive the visit
        dq_ref[0] = dq_in_ref[0]

    @pl.when(qi == nq - 1)
    def _emit():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def fused_bwd_call(q, k, v, do, lse, dl, causal, scale, block_q, block_k,
                   q_offset=0, kv_offset=0, interpret=False):
    """Single-pass backward.  EXPERIMENTAL — run :func:`selfcheck` for
    your exact shape/blocking first (see module docstring); real-TPU
    backends only (the aliased revisit is always wrong under
    ``interpret=True`` once the kv grid has more than one block —
    ``interpret`` exists so the selfcheck machinery itself can be
    exercised off-TPU, where that wrongness is the EXPECTED verdict)."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, q_offset=q_offset, kv_offset=kv_offset)
    _q_clamp = _bwd_q_index_map(causal, tq // block_q, block_q, block_k,
                                q_offset, kv_offset)
    qspec = pl.BlockSpec((1, block_q, d), _q_clamp)
    qrow = pl.BlockSpec((1, block_q, 1), _q_clamp)
    kspec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))
    dq0 = jnp.zeros((bh, tq, d), jnp.float32)
    extra = ({} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"))})
    dq, dk, dv = pl.pallas_call(
        functools.partial(_fused_bwd_kernel, **common),
        grid=(bh, tk // block_k, tq // block_q),
        in_specs=[qspec, kspec, kspec, qspec, qrow, qrow, qspec],
        out_specs=[qspec, kspec, kspec],
        out_shape=[_sds((bh, tq, d), jnp.float32, q),
                   _sds((bh, tk, d), k.dtype, q),
                   _sds((bh, tk, d), v.dtype, q)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        input_output_aliases={6: 0},
        interpret=interpret,
        **extra,
    )(q, k, v, do, lse, dl, dq0)
    return dq.astype(q.dtype), dk, dv


class SelfCheckVerdict(tuple):
    """Typed selfcheck outcome.  Unpacks as the round-5 ``(ok, err)``
    pair for existing callers; carries ``status`` / ``reason`` for the
    graduation layer:

    - ``"exact"``        — parity ran and matched within tolerance; the
      fused kernel may serve THIS configuration on THIS compiler.
    - ``"mismatch"``     — parity ran and diverged (``err`` has the
      measured relative error): the fallback is mandatory.
    - ``"unverifiable"`` — parity could NOT run on this backend (no
      un-interpreted Pallas path off-TPU); ``err`` is None.  The flag
      degrades to the reference backward — never an assertion failure.
    """

    def __new__(cls, ok, err, status, reason=""):
        self = super().__new__(cls, (bool(ok), err))
        self.status = status
        self.reason = reason
        return self

    @property
    def ok(self):
        return self[0]

    @property
    def err(self):
        return self[1]


def compiler_fingerprint():
    """A token that changes whenever the compiler that decides the
    aliased-revisit coherence could have changed — the cache axis the
    graduation verdicts are keyed on (a Mosaic update must re-run the
    parity check, not trust last month's)."""
    parts = [jax.__version__]
    try:
        import jaxlib

        parts.append(getattr(jaxlib, "__version__", "?"))
    except ImportError:  # pragma: no cover
        parts.append("no-jaxlib")
    try:
        parts.append(str(
            jax.devices()[0].client.platform_version))
    # dklint: ignore[broad-except] platform_version is best-effort backend metadata (absent on some clients)
    except Exception:
        parts.append("no-platform-version")
    return "|".join(parts)


def selfcheck(bh=2, t=2048, d=128, block_q=1024, block_k=1024,
              causal=True, dtype=jnp.bfloat16, seed=0, tol=1e-6,
              t_kv=None, interpret=False):
    """-> :class:`SelfCheckVerdict` (unpacks as ``(ok, max_rel_err)``):
    compare the fused kernel against the shipped two-kernel backward on
    random inputs at the given shape/blocking.  Callers MUST gate any
    use of :func:`fused_bwd_call` on this passing for their exact
    configuration (the coherence table in the module docstring is
    compiler-version-specific).

    Off-TPU with ``interpret=False`` the parity run cannot execute at
    all (no un-interpreted Pallas path), so the verdict is a typed
    ``"unverifiable"`` instead of a backend crash — the DK_FUSED_BWD
    flag then degrades to the reference backward.  ``interpret=True``
    runs both kernels in interpret mode: the aliased revisit is
    structurally last-write-wins there, so any multi-kv-block shape is
    EXPECTED to report a mismatch — which is precisely what makes the
    whole verdict machinery testable on CPU."""
    import numpy as np

    if not interpret and not use_pallas():
        return SelfCheckVerdict(
            False, None, "unverifiable",
            f"backend {jax.default_backend()!r} cannot run the "
            "un-interpreted fused kernel (and interpret mode is "
            "structurally last-write-wins) — the reference backward "
            "stays in effect")
    t_kv = t if t_kv is None else t_kv
    rng = np.random.default_rng(seed)
    mk = lambda tt: jnp.asarray(  # noqa: E731
        rng.normal(size=(bh, tt, d)), dtype) * 0.3
    # draw order kept q, k, v, do (the round-5 order, so a given seed
    # reproduces the same inputs it always did when t_kv == t)
    q, k, v, do = mk(t), mk(t_kv), mk(t_kv), mk(t)
    scale = d ** -0.5
    out, lse = _fwd_call(q, k, v, causal, scale, block_q, block_k,
                         0, 0, interpret)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    dl = -delta
    ref = _bwd_call(q, k, v, do, lse, dl, causal, scale, block_q,
                    block_k, 0, 0, interpret)
    got = fused_bwd_call(q, k, v, do, lse, dl, causal, scale, block_q,
                         block_k, interpret=interpret)
    err = 0.0
    for a, b in zip(ref, got):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        err = max(err, float(np.max(np.abs(a - b))
                             / (np.max(np.abs(a)) + 1e-9)))
    if err <= tol:
        return SelfCheckVerdict(True, err, "exact")
    return SelfCheckVerdict(
        False, err, "mismatch",
        f"fused backward diverged from the two-kernel reference "
        f"(rel err {err:.3g} > tol {tol:g})")


# -- graduation (DK_FUSED_BWD) ------------------------------------------
# One verdict per (shape, blocking, dtype, causal, interpret, compiler)
# per process: the parity run executes ONCE, at the first backward trace
# of that configuration, and every later trace reuses the cached
# verdict.  `fused_bwd_rejected` is emitted exactly when a non-exact
# verdict is first cached — the operator sees WHY the flag quietly kept
# the reference backward.
_VERDICTS = {}


def clear_verdicts():
    """Drop the cached graduation verdicts (tests / compiler swap)."""
    _VERDICTS.clear()


def graduate(bh, tq, tk, d, dtype, causal, block_q, block_k,
             q_offset=0, kv_offset=0, interpret=False):
    """-> the cached :class:`SelfCheckVerdict` deciding whether
    :func:`fused_bwd_call` may serve this exact configuration.

    Only ``status == "exact"`` graduates.  Nonzero offsets (the ring-
    attention path) never graduate: the parity run covers offset-0
    masking only, and an unverified configuration must not serve."""
    from dist_keras_tpu.observability import events

    if q_offset or kv_offset:
        key = ("offsets", bool(interpret))
        v = _VERDICTS.get(key)
        if v is None:
            v = _VERDICTS[key] = SelfCheckVerdict(
                False, None, "unverifiable",
                "nonzero q/kv offsets (ring attention) are outside the "
                "selfcheck parity surface")
            events.emit("fused_bwd_rejected", reason=v.status,
                        detail=v.reason, shape=[bh, tq, tk, d])
        return v
    key = (bh, tq, tk, d, str(dtype), bool(causal), block_q, block_k,
           bool(interpret), compiler_fingerprint())
    v = _VERDICTS.get(key)
    if v is None:
        v = _VERDICTS[key] = selfcheck(
            bh=bh, t=tq, t_kv=tk, d=d, block_q=block_q,
            block_k=block_k, causal=causal, dtype=dtype,
            interpret=interpret)
        if v.status != "exact":
            events.emit("fused_bwd_rejected", reason=v.status,
                        detail=v.reason, err=v.err,
                        shape=[bh, tq, tk, d],
                        blocks=[block_q, block_k])
    return v
