"""Bytes the gated-delta-rule, full-attention family's decode step
requires, from shapes alone (``opcount``'s rules: required work, not
executed work).  Its flash forward is plain multi-head attention (30
heads of 128 at the published widths): ``opcount.flash_fwd`` counts it."""

from __future__ import annotations

F32 = 4


def decode_step_bytes(cfg, slots):
    """Bytes one decode step of ``slots`` slots MUST move, by what they
    depend on -> ``fixed`` (every layer's weights, the untied head and the
    final norm: everything outside the embedding table, of which a slot
    reads one row), ``per_live_position`` (a cached position's keys and
    values, ``2 x heads x head_dim`` values, in every ATTENTION layer) and
    ``per_live_row`` (a live sequence's state in every LINEAR layer, the
    recurrent matrices and the convolutions' last inputs, read AND
    written: the recurrence leaves no value of ``S`` as it was).  float32
    storage; the values themselves, not the lanes a layout pads them to."""
    d, ff = cfg["d_model"], cfg["d_ff"]
    h, dk, dv = (cfg["linear_heads"], cfg["linear_key_dim"],
                 cfg["linear_value_dim"])
    channels, taps = h * (2 * dk + dv), cfg["conv_kernel"]
    n_lin = cfg["layer_types"].count("linear_attention")
    n_full = cfg["n_layers"] - n_lin
    shared = 2 * d + 3 * d * ff                   # both norms, the SwiGLU
    linear = (d * channels + channels * taps + 2 * d * h * dv + d * 2 * h
              + 2 * h + dv)
    full = 4 * d * d + 2 * d
    fixed = (cfg["n_layers"] * shared + n_lin * linear + n_full * full
             + d * cfg["vocab_size"] + d + slots * d)
    return {"fixed": F32 * fixed,
            "per_live_position": F32 * n_full * 2 * d,
            "per_live_row": F32 * n_lin * 2 * (
                h * dk * dv + (taps - 1) * channels)}


def kernel_unit_bytes(cfg):
    """Bytes ONE call of each of the decode step's kernels must move for
    one unit of the histogram that says how many units a step had -> {the
    kernel's name in a trace: (histogram, bytes a unit)}: the K/V read of
    one attention layer, keys and values of a live position; the state
    update of one linear layer, a live sequence's matrices read and
    written and its four vectors in, one out."""
    d = cfg["d_model"]
    h, dk, dv = (cfg["linear_heads"], cfg["linear_key_dim"],
                 cfg["linear_value_dim"])
    return {"latent_decode": ("decode.kv.live_positions", F32 * 2 * d),
            "gdn_state_step": ("decode.state.live_rows",
                               F32 * h * (2 * dk * dv + 2 * dk + 2 * dv
                                          + 2))}
