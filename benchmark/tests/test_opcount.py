"""``opcount`` against counts made by hand."""

from benchmark.trace import opcount


def test_causal_pairs():
    assert opcount.causal_pairs(4, 4) == 10          # 1 + 2 + 3 + 4
    assert opcount.causal_pairs(1, 9) == 9           # one query sees all
    assert opcount.causal_pairs(2, 5) == 4 + 5


def test_flash_fwd_by_hand():
    # 64 problems, 2048 x 2048, head 128, bf16, causal
    ops, moved = opcount.flash_fwd(64, 2048, 2048, 128, True, 2)
    pairs = 2048 * 2049 // 2
    assert ops == 64 * pairs * 128 * 2 * 2           # QK^T and PV
    assert moved == 64 * 128 * 2 * 4 * 2048 + 64 * 2048 * 4
    full, _ = opcount.flash_fwd(64, 2048, 2048, 128, False, 2)
    assert full == 64 * 2048 * 2048 * 128 * 4


def test_block_by_hand():
    d, ff, t = 4096, 16384, 2048
    got = opcount.block_forward(t, d, ff, causal=True)
    proj = 4 * 2 * d * d                 # q, k, v, o
    mlp = 2 * 2 * d * ff                 # w1, w2
    attn = 2 * 2 * (t * (t + 1) // 2) * d // t
    assert got == proj + mlp + attn
    assert got == 134_217_728 + 268_435_456 + attn


def test_train_step_per_sample_is_three_forwards():
    one = opcount.train_step_per_sample(2048, 1, 4096, 16384, 32)
    two = opcount.train_step_per_sample(2048, 2, 4096, 16384, 32)
    block = 3 * 2048 * opcount.block_forward(2048, 4096, 16384)
    assert two - one == block
    assert one - block == 3 * 2048 * 2 * 32 * 4096


def test_roofline_names_the_bound():
    t, bound = opcount.roofline_seconds(197e12, 1.0, 197e12, 819e9)
    assert (t, bound) == (1.0, "compute")
    t, bound = opcount.roofline_seconds(1.0, 819e9, 197e12, 819e9)
    assert (t, bound) == (1.0, "memory")
