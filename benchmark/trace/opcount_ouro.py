"""Operations and bytes the looped family's steps and kernels require, from
shapes alone (``opcount``'s rules: required work, one multiply-add two
operations).  Its flash forward is plain multi-head attention (16 heads of
128 at the published widths): ``opcount.flash_fwd`` counts a call, and a
prefill makes ``ut_steps x n_layers`` of them."""

from __future__ import annotations

from benchmark.trace import opcount

F32 = 4


def layer_values(cfg):
    """The float32 values of one layer of WEIGHTS: the four attention
    projections, the SwiGLU's three matrices, the sandwich's four norms."""
    d, h, hk, hd = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                    cfg["head_dim"])
    return 2 * d * h * hd + 2 * d * hk * hd + 3 * d * cfg["d_ff"] + 4 * d


def decode_step_bytes(cfg, slots):
    """Bytes one decode step of ``slots`` slots MUST read, by what they
    depend on -> ``fixed`` (``ut_steps`` x the layers' weights, then once
    each the untied head, the final norm, the exit gate and a slot's row
    of the embedding table) and ``per_live_position`` (a cached position's
    keys and values, ``2 x kv_heads x head_dim`` values, in every one of
    the ``ut_steps x n_layers`` entries: a pass reads only what it wrote
    itself).  float32 storage.

    **Why the layers count ``ut_steps`` times and the share cannot pass
    100% for it:** no pass can begin before the one before it has ended
    (its input is that pass's normed output), and 2.47 GB of layers at the
    published widths do not stay in the chip's 128 MiB of vector memory
    from one pass to the next, so every pass fetches every matrix from
    the HBM again.  What a program may do instead is fetch a SMALLER copy:
    the compiler rounds the matrices to bfloat16 once a step and lets the
    passes read that copy (``PERF.md``, PR 44), which moves 1.24 GB less
    than this count at four passes of twelve layers; the count stays the
    float32 storage's, as every other family's does."""
    d = cfg["d_model"]
    layers = cfg["ut_steps"] * cfg["n_layers"]
    fixed = (layers * layer_values(cfg) + d * cfg["vocab_size"] + d
             + (d + 1) + slots * d)
    return {"fixed": F32 * fixed,
            "per_live_position": F32 * layers * 2 * cfg["n_kv_heads"]
            * cfg["head_dim"]}


def kernel_unit_bytes(cfg):
    """Bytes ONE call of the decode step's read kernel must move for one
    unit of the histogram that says how many units a step had -> {the
    kernel's name in a trace: (histogram, bytes a unit)}: the K/V read of
    one (pass, layer) entry, keys and values of a live position."""
    return {"latent_decode": (
        "decode.kv.live_positions",
        F32 * 2 * cfg["n_kv_heads"] * cfg["head_dim"])}


def prefill_flash(cfg, positions):
    """Every flash forward of ONE prefill of ``positions`` positions ->
    (calls, operations, bytes): causal multi-head attention in float32, a
    call a layer a pass."""
    calls = cfg["ut_steps"] * cfg["n_layers"]
    ops, moved = opcount.flash_fwd(cfg["n_heads"], positions, positions,
                                   cfg["head_dim"], True, F32)
    return calls, calls * ops, calls * moved
