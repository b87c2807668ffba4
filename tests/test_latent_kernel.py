"""The Pallas read of the latent pool (``latent_attention_kernel``) against
``latent_attention_reference`` on the CPU, the kernel interpreted.

Tolerances.  Both sides run under ``jax.default_matmul_precision(
"highest")``: the kernel then keeps its operands float32, as the
reference's ``einsum``s do, and the two differ by the order of float32
sums alone — read 0 to 3.3e-7 of the largest output (1 to 2.3), limit
1e-5.  (A gate against a reference at DEFAULT precision means nothing:
on the chip that reference is itself 3e-3 from the exact result, PR 28's
chip run.)  At the default precision the kernel rounds the operands of
both products to bfloat16 and sums in float32, the configuration's own
precision: against the exact reference that reads 2e-3 to 4e-3 of the
largest output here (3e-3 of 0.76 on the chip at the cell's sizes, as
far as the default-precision reference lies from the exact one), limit
2e-2: a rounding's size, far under what a wrong page or a wrong mask
would do (order 1).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dist_keras_tpu.ops.pallas import decode_attention as da

H, R, RANK, PS = 4, 128, 32, 4
SCALE = 0.1
EXACT, ROUNDED = 1e-5, 2e-2


def case(lengths, n_pages=8, block_pages=2, layer=0, layers=2, seed=0):
    """A flat pool of ``layers`` layers, every slot's pages scattered over
    its layer and offset to it, entries past a slot's allocation left at
    page 0 of the pool as the engine leaves them."""
    rng = np.random.default_rng(seed)
    slots = len(lengths)
    per_layer = slots * n_pages + 1
    pool = jnp.asarray(rng.normal(size=(layers * per_layer, PS, R)),
                       jnp.float32)
    q = jnp.asarray(rng.normal(size=(slots, H, R)), jnp.float32)
    table = (rng.permutation(per_layer)[:slots * n_pages]
             .reshape(slots, n_pages) + layer * per_layer)
    for i, n in enumerate(lengths):
        table[i, -(-n // PS):] = 0
    return (q, pool, jnp.asarray(table, jnp.int32),
            jnp.asarray(lengths, jnp.int32)), block_pages


# a block is block_pages x PS positions: 8 by default
CASES = {
    "padding_slot_then_one_position": dict(lengths=[0, 1]),
    "partial_page": dict(lengths=[3, 6]),
    "page_boundary": dict(lengths=[4, 12]),
    "block_boundary": dict(lengths=[8, 16, 9]),
    "whole_table": dict(lengths=[32, 32]),
    "second_layer_of_a_flat_pool": dict(lengths=[5, 17, 32], layer=1),
    "table_no_multiple_of_the_block": dict(
        lengths=[1, 13, 27, 28], n_pages=7, block_pages=3),
    "table_narrower_than_the_block": dict(
        lengths=[0, 9, 12], n_pages=3, block_pages=32),
    "rung_8_with_padding_slots": dict(
        lengths=[21, 0, 8, 0, 0, 30, 0, 0]),
    "padding_first_and_between": dict(lengths=[0, 0, 7, 0, 25]),
}


def run(args, block_pages):
    kernel = jax.jit(lambda *a: da.latent_attention_kernel(
        *a, rank=RANK, scale=SCALE, interpret=True, block_pages=block_pages))
    return np.asarray(kernel(*args))


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_equals_the_reference_at_highest_precision(name):
    args, block_pages = case(**CASES[name])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(da.latent_attention_reference(
            *args, rank=RANK, scale=SCALE))
        got = run(args, block_pages)
    assert got.shape == want.shape == (len(args[3]), H, RANK)
    assert np.max(np.abs(got - want)) <= EXACT * np.max(np.abs(want))
    for i, n in enumerate(np.asarray(args[3])):
        if n == 0:
            assert not got[i].any(), i          # exact zeros, not small


@pytest.mark.parametrize("name", ["block_boundary", "whole_table",
                                  "rung_8_with_padding_slots"])
def test_kernel_rounds_operands_to_bfloat16_at_default_precision(name):
    args, block_pages = case(**CASES[name])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(da.latent_attention_reference(
            *args, rank=RANK, scale=SCALE))
    got = run(args, block_pages)
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    # rounded (not the float32 products of the highest-precision run),
    # and by no more than a rounding
    assert EXACT < err <= ROUNDED, err


def test_a_masked_position_contributes_nothing():
    """What lies behind a slot's length, in its last page or in pages the
    block fetched past it, never reaches the result."""
    (q, pool, table, lengths), block_pages = case(lengths=[5, 10])
    with jax.default_matmul_precision("highest"):
        got = run((q, pool, table, lengths), block_pages)
        # slot 0: the rest of page 1, and page 0 of the pool (fetched for
        # the block's second half); slot 1: the rest of its third page
        t = np.asarray(table)
        pool = pool.at[t[0, 1], 1:].set(1e4).at[0].set(-1e4)
        pool = pool.at[t[1, 2], 2:].set(1e4)
        np.testing.assert_array_equal(
            run((q, pool, table, lengths), block_pages), got)


def test_auto_takes_the_kernel_on_a_tpu_and_the_reference_elsewhere(
        monkeypatch):
    args, _ = case(lengths=[3, 9])
    auto = jax.jit(lambda *a: da.latent_attention_auto(
        *a, rank=RANK, scale=SCALE))
    assert "tpu_custom_call" not in auto.lower(*args).as_text()
    np.testing.assert_array_equal(
        np.asarray(auto(*args)),
        np.asarray(da.latent_attention_reference(*args, rank=RANK,
                                                 scale=SCALE)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = jax.jit(lambda *a: da.latent_attention_auto(
        *a, rank=RANK, scale=SCALE)).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and "latent_decode" in text


@pytest.mark.parametrize("lengths,page_size,block_pages,want", [
    ([0, 0], 16, 32, 0),
    ([1], 16, 32, 512),
    ([512, 513, 0], 16, 32, 512 + 1024),
    ([2100, 6500], 16, 32, 5 * 512 + 13 * 512),
    ([5, 8, 9], 4, 2, 8 + 8 + 16),
])
def test_walked_positions_are_lengths_rounded_up_to_blocks(
        lengths, page_size, block_pages, want):
    assert da.latent_walked_positions(
        np.asarray(lengths, np.int32), page_size, block_pages) == want
