"""``chip_smoke.py`` from the CPU side: it refuses to run without a TPU,
its stages pass at toy sizes on the virtual CPU mesh, and every Pallas
kernel it compiles on the chip at least LOWERS for TPU from here (block
shapes are checked at lowering; Mosaic itself needs the chip)."""

import functools
import os
import re
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_refuses_to_run_without_a_tpu():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr, proc.stderr[-2000:]
    assert '"ok"' not in proc.stdout   # no result line


def test_stage_trainer_toy():
    out = chip_smoke.stage_trainer(workers=4, batch=16, steps=96,
                                   epochs=4, window=3, image=8,
                                   held_out=256)
    assert out["data_shards"] == [(d, 1) for d in range(4)]
    assert out["accuracy"] > 0.2
    assert out["last_loss"] < out["first_loss"]


def test_stage_train_step_toy():
    out = chip_smoke.stage_train_step(batch=2, seq=32, d_model=32,
                                      n_heads=2, n_layers=1)
    assert len(out["losses"]) == 3
    # off-TPU attention_auto traces the jnp reference: no custom call
    assert not any(out["kernels"].values())


def test_stage_decode_server_toy():
    out = chip_smoke.stage_decode_server(
        vocab=64, seq=64, d_model=32, n_heads=2, n_layers=2, replicas=2,
        prefill_ladder=(16, 32), decode_ladder=(1, 4), max_new=4,
        page_size=4)
    # rounds x 4 batched + 1 streamed
    assert out["completed"] == 4 * out["rounds"] + 1
    assert out["replica_devices"] == [0, 1]
    assert all(out["replica_peak_pages"])
    assert out["decode_attention"] == "latent_attention_reference"


@pytest.fixture(scope="module")
def chip_kernel_cases():
    # the chip run's tile geometry (dh 128, block 1024, page size 8) at a
    # smaller batch: the grid's parallel extent does not change a block
    cases, _ = chip_smoke.kernel_cases(
        batch=2, seq=2048, n_heads=6, head_dim=128, prefill=1024,
        slots=8, page_size=8)
    return cases


CASES = ["flash_fwd/bf16_train", "flash_bwd/bf16_train",
         "flash_fwd/f32_prefill", "flash_bwd/f32_prefill",
         "latent_decode/f32", "latent_decode/kv_rows_f32",
         "gdn_state_step/f32"]


@pytest.fixture(scope="module")
def lowered_for_tpu(chip_kernel_cases):
    """name -> the case's text lowered for TPU, each lowered once."""
    @functools.cache
    def lowered(name):
        fn, args = chip_kernel_cases[name]
        return jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
    return lowered


@pytest.mark.parametrize("name", CASES)
def test_kernel_lowers_for_tpu(lowered_for_tpu, name):
    assert "tpu_custom_call" in lowered_for_tpu(name)


def test_every_kernel_in_ops_pallas_has_a_case(chip_kernel_cases,
                                               lowered_for_tpu):
    """A ``pl.pallas_call`` is named through ``_kernel_name("<base>")``:
    every base in the sources of ``ops/pallas/`` must be a custom call of
    some case's lowered text, so a kernel added without a TPU lowering
    case (and a stage D parity run on the chip) fails here; and the table
    above is the whole of ``kernel_cases``."""
    assert sorted(chip_kernel_cases) == sorted(CASES)
    pallas = os.path.join(REPO, "dist_keras_tpu", "ops", "pallas")
    named, calls = set(), 0
    for fname in sorted(os.listdir(pallas)):
        if fname.endswith(".py"):
            with open(os.path.join(pallas, fname)) as f:
                src = f.read()
            named |= set(re.findall(r'name=_kernel_name\("(\w+)"\)', src))
            calls += len(re.findall(r"\bpl\.pallas_call\(", src))
    assert len(named) == calls, (named, calls)        # none goes unnamed
    lowered = set()
    for name in CASES:
        lowered |= set(re.findall(r'kernel_name = "(\w+)"',
                                  lowered_for_tpu(name)))
    assert lowered == named
