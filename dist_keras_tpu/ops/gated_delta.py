"""The gated delta rule: a linear-attention layer's recurrence, three ways.

A head keeps a matrix ``S (dk, dv)`` that every position decays, corrects
by a rank-one term and reads.  With ``alpha_t = exp(g_t)`` (``g_t <= 0``),
``beta_t`` in ``[0, 2]``, ``k_t`` of unit length and ``S_0 = 0``::

    S' = alpha_t S_(t-1)
    u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

- :func:`gated_delta_recurrent` is that, position by position: the
  definition, what tests hold the other two to, and nothing serves it.
- :func:`gated_delta_step` is one position for a set of slots, each from
  its own state: a decode step's update (exact float32, elementwise: the
  state is read twice and written once).
- :func:`gated_delta_chunked` is a prefill's scan over one padded prompt:
  ``CHUNK`` positions at a time as matrix products (the WY / UT-transform
  form), the state carried from chunk to chunk, so a prompt of 1,024
  positions is 16 dependent steps and not 1,024.  It is told the prompt's
  TRUE ``length``: a position at or past it carries ``beta = 0`` and ``g =
  0``, which leaves the state exactly as it was, so the state handed back
  is the one after position ``length - 1`` whatever the padding.

**The chunked form.**  Inside a chunk let ``G_t = g_1 + .. + g_t`` and
``D[t, s] = exp(G_t - G_s)`` for ``s <= t`` (every exponent is ``<= 0``:
nothing overflows, and what underflows is a decay to zero).  Unrolling the
recurrence from the chunk's incoming state ``S``::

    (I + diag(beta) tril(D * K K^T, -1)) U = diag(beta) (V - diag(e^G) K S)
    O = diag(e^G) Q S + tril(D * Q K^T) U
    S <- e^(G_C) S + (diag(e^(G_C - G)) K)^T U

The unit lower-triangular system does not depend on ``S``: it is solved
for every chunk at once, against ``diag(beta) V`` and ``diag(beta e^G) K``
together, by forward substitution (``solve_triangular``; the series of
powers that would do it in ``log C`` products cancels catastrophically in
float32).  What is left to the sequential pass over the chunks are four
products a chunk (a ``lax.scan``: a Mosaic kernel for that pass, a chunk
of every head a grid step and the state resident in VMEM, lost to it at
three of the cell's four rungs and went: PERF.md, PR 36; on the chip the
solve, not the pass, is two fifths of the scan).  **Precision:** the chunk's own ``C x C`` matrices and
the solve run at "highest" (they are 1% of a layer's operations and their
errors are fed back through ``S``); the four products that touch the state
run at the ambient precision, like every other product of a served model;
the state and every accumulation are float32.

``CHUNK`` is 64: the substitution is sequential in it, the products' tiles
grow with it, and a prompt's rung need only be padded to it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 64


def gated_delta_step(state, q, k, v, g, beta):
    """One position for every slot: ``state (..., dk, dv)``, ``q, k (...,
    dk)``, ``v (..., dv)``, ``g, beta (...)`` -> (``o (..., dv)``, the
    state one position on).  ``o`` is read off the OLD state (``S_t^T q =
    alpha S^T q + (k . q) u``), so that both reductions run over one pass
    of it and the new state is written in a second."""
    alpha = jnp.exp(g)[..., None]
    sk = jnp.sum(state * k[..., :, None], axis=-2)
    sq = jnp.sum(state * q[..., :, None], axis=-2)
    u = beta[..., None] * (v - alpha * sk)
    o = alpha * sq + u * jnp.sum(k * q, -1, keepdims=True)
    return o, alpha[..., None] * state + k[..., :, None] * u[..., None, :]


def gated_delta_recurrent(q, k, v, g, beta, state=None):
    """The definition over one sequence: ``q, k (T, H, dk)``, ``v (T, H,
    dv)``, ``g, beta (T, H)`` -> (``o (T, H, dv)``, ``S_T (H, dk, dv)``),
    from ``state`` or zeros."""
    if state is None:
        state = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)

    def position(s, x):
        o, s = gated_delta_step(s, *x)
        return s, o

    state, o = jax.lax.scan(position, state, (q, k, v, g, beta))
    return o, state


def _chunks(x, n):
    """``(n * CHUNK, H, ...)`` -> ``(H, n, CHUNK, ...)``."""
    x = x.reshape(n, CHUNK, *x.shape[1:])
    return jnp.moveaxis(x, 2, 0)


def gated_delta_chunked(q, k, v, g, beta, length):
    """One padded prompt, chunk by chunk: the shapes of
    :func:`gated_delta_recurrent` and ``length`` (traced), from a zero
    state -> (``o (T, H, dv)``, the state after position ``length - 1``).
    Outputs at or past ``length`` are those of a state that no longer
    moves; nothing reads them."""
    t, h, dk = q.shape
    dv = v.shape[-1]
    live = (jnp.arange(t) < length)[:, None]
    g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    pad = -t % CHUNK
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            for x in (q, k, v, g, beta))
    n = (t + pad) // CHUNK
    qc, kc, vc = _chunks(q, n), _chunks(k, n), _chunks(v, n)  # (H, n, C, d)
    gc, bc = _chunks(g, n), _chunks(beta, n)                  # (H, n, C)
    total = jnp.cumsum(gc, -1)
    at = jnp.arange(CHUNK)
    upto = at[:, None] >= at[None, :]                         # s <= t
    decay = jnp.exp(jnp.where(
        upto, total[..., :, None] - total[..., None, :], -jnp.inf))
    kk = jnp.einsum("hnci,hnsi->hncs", kc, kc, precision="highest")
    system = jnp.eye(CHUNK) + jnp.where(
        at[:, None] > at[None, :], bc[..., None] * decay * kk, 0.0)
    grown = jnp.exp(total)[..., None]                         # e^G
    solved = jax.scipy.linalg.solve_triangular(
        system, jnp.concatenate([bc[..., None] * vc,
                                 bc[..., None] * grown * kc], -1),
        lower=True, unit_diagonal=True)
    uv, w = solved[..., :dv], solved[..., dv:]
    qk = decay * jnp.einsum("hnci,hnsi->hncs", qc, kc, precision="highest")
    last = total[..., -1]
    kd = kc * jnp.exp(last[..., None] - total)[..., None]

    def chunk(s, x):
        uv_n, w_n, qk_n, qg_n, kd_n, decay_n = x
        u = uv_n - jnp.einsum("hci,hij->hcj", w_n, s)
        o = (jnp.einsum("hci,hij->hcj", qg_n, s)
             + jnp.einsum("hcs,hsj->hcj", qk_n, u))
        s = decay_n[:, None, None] * s + jnp.einsum("hci,hcj->hij", kd_n, u)
        return s, o

    state, o = jax.lax.scan(
        chunk, jnp.zeros((h, dk, dv), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0)
              for x in (uv, w, qk, qc * grown, kd, jnp.exp(last))))
    # (n, H, C, dv) -> (T, H, dv)
    return jnp.moveaxis(o, 1, 2).reshape(-1, h, dv)[:t], state
