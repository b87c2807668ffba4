"""The six block families compile to the programs recorded here.

PR 32 widened the family seam (a pool states the layers it spans and
whether its rows are pages or sequences; a family with per-sequence state
gets one more column in the packed array) for a third family.  The three
serving cells of the two older families spread close to their bounds, so
that PR was held to leaving their compiled programs exactly as they were:
the lowered text (StableHLO, no source locations) of each family's packed
prefill and decode programs at a toy size is hashed here and compared with
a recorded hash, by this file's own ``lowered_hash``.

**PR 35 meant to change the four decode entries, and only those.**  A
decode step now takes the output of the step before it as one more device
argument and resolves, inside the program, the slots whose token is named
by its source (``serving/decode.py:_step_views``), and its output is as
wide as the top rung at every rung: an integer select over at most a rung
of values in front of the family's step, which is handed what it was
handed before.  Their hashes were recorded anew on that PR's tree.  The
four prefill entries are still those recorded from commit 9317672 (PR 31),
in a checkout of that commit: a prefill is synchronous and its program
did not move.

**PR 36 added a fourth family and the third's four entries.**  It shares
``lfm2_moe``'s depthwise taps (one function for both families'
convolutions) and gave ``latent_attention_auto`` a ``block_pages`` argument
whose default is the kernel's own, and was held to leaving the three older
families' programs as they were: the ``lfm2_moe`` hashes were recorded
from commit 0436209 (PR 35), in a checkout of that commit, and the eight
older entries pass as recorded.

**PR 37 meant to change the transformer family's four entries, and only
those.**  Its cache became ONE pool of ``v | k`` rows (a prefill writes a
row a position where it wrote a K and a V entry; a decode step reads the
slots' live pages through ``attend_rows`` (then ``lfm2_moe``'s, since PR
48 ``models/blocks.py``'s), on a TPU the ``latent_decode`` kernel, where
it gathered every slot's whole table), so its CPU and TPU decode programs
now differ.  The four hashes were recorded
anew on that PR's tree; the kernel, its dispatch and ``attend_rows`` were
not edited, and the eight entries of ``mla_moe`` and ``lfm2_moe`` pass as
recorded.

**PR 40 meant to change the transformer family's four entries, and only
those.**  Its steps embed a token by reading its row of ``proj``
(``params["proj"][tokens]``, a gather, as the other families read their
tables) where they multiplied ``one_hot(tokens)`` by the whole table, so
the ``embed`` scope of both programs changed and nothing else of them.
The four hashes were recorded anew on that PR's tree; the eight entries of
``mla_moe`` and ``lfm2_moe`` pass as recorded, and ``olmo_hybrid``'s four
programs (not pinned here) hash alike on that tree and its parent.

**PR 42 widened the seam for a fifth family and pinned the fourth's four
entries.**  ``models/sdar_moe.py`` generates in blocks: a family states
how many positions a slot a step computes (``step_width``), the packed
array holds a block's tokens and one more column where that is over 1, and
the flash forward takes the causal mask's block length.  It was held to
leaving every family of width 1 its programs: the twelve entries pass as
recorded, and ``olmo_hybrid``'s four were recorded from commit 42c93af (PR
41), in a checkout of that commit, and hash alike on PR 42's tree.

**PR 44 added a sixth family behind the seam as it stood and its own four
entries.**  ``models/ouro.py`` (one stack of layers run several times a
token as a loop of both programs, a cache entry a (pass, layer)) needed
no line of the engine but the registry's entry: the sixteen older entries
pass as recorded (``sdar_moe``'s programs, whose step is a pass over
blocks, were not pinned here until PR 48), and this family's four were
recorded on that PR's tree.

**PR 47 gave ``held_experts`` (then ``mla_moe``'s, since PR 48
``models/blocks.py``'s) a second form and was held to leaving every
program here as it was.**  A call of more than 1,024 tokens
(``GROUPED_OVER``) runs the held experts over the chosen pairs
sorted by expert, an expert at a time through plain products; a call of
1,024 or fewer runs the masked dense pass, the same operations in the
same order (the sum, then the counts).  The choice is by the call's
static token count, every rung here is a toy one far under it, and a
prefill of the grouped form alone sends one count more: the twenty
entries pass as recorded (``mla_moe``'s and ``lfm2_moe``'s, which call
the function, among them), and so do the decode programs and the prefill
rungs of 256-1,024 tokens that ``lfm2_serve_turns`` and
``sdar_serve_blocks`` run.

**PR 48 moved what the families share into ``models/blocks.py`` and was
held to leaving every program here as it was.**  Before a function moved,
``sdar_moe``'s four programs (a pass over blocks: the rung's spare entries,
a block's tokens an entry and one more column in the packed array) were
recorded on commit 21f5319 (PR 47), with this file alone edited: PR 43's
programs are pinned from there on.  The functions then moved with their
bodies and scopes letter for letter (``rms_norm``, the rotations, SwiGLU,
the head, the ``v | k`` rows, the taps, the expert layer with
``GROUPED_OVER``; the five decoder classes became one), and the
twenty-four entries pass as recorded.  One order had to be kept by hand:
the head names its table BEHIND the norm (``blocks.logits`` takes the
parameter tree, not the table: a tied table transposed in the caller is
lowered in front of the norm, and ``lfm2_moe``'s four hashes move).
Whoever edits a function of ``models/blocks.py`` finds in its docstring
which families call it; all of them are pinned here.

A change that means to alter one of these programs records the new hash
and says so; a change that does not, and fails here, has moved a
benchmark cell's program.
"""

import hashlib

import jax
import jax.numpy as jnp
import pytest

from dist_keras_tpu.models import (
    lfm2_moe,
    mla_moe,
    olmo_hybrid,
    ouro,
    sdar_moe,
)
from dist_keras_tpu.models.transformer import Transformer, transformer_config
from dist_keras_tpu.serving import DecodeEngine

LADDERS = dict(replicas=1, prefill_ladder=(8, 16), decode_ladder=(1, 4),
               page_size=4)


def _transformer():
    return Transformer(transformer_config(
        input_dim=16, seq_len=32, d_model=16, n_heads=2, n_layers=2,
        n_classes=16))


def _mla_moe():
    return mla_moe.LatentMoEDecoder(cfg=mla_moe.mla_moe_config(
        vocab_size=128, seq_len=48, d_model=64, n_heads=4,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        kv_lora_rank=32, d_ff=96, moe_d_ff=48, n_routed_experts=8,
        n_shared_experts=1, top_k=3, n_layers=3, held_experts=[2, 3, 4],
        routed_scaling_factor=2.446, rope_theta=800000.0), seed=1)


def _lfm2_moe():
    return lfm2_moe.Lfm2MoeDecoder(cfg=lfm2_moe.lfm2_moe_config(
        vocab_size=128, seq_len=48, d_model=64, n_heads=8, n_kv_heads=2,
        d_ff=96, moe_d_ff=48, n_routed_experts=8, top_k=2,
        layer_types=["conv", "conv", "full_attention", "conv"] * 2), seed=1)


def _olmo_hybrid():
    return olmo_hybrid.OlmoHybridDecoder(cfg=olmo_hybrid.olmo_hybrid_config(
        vocab_size=128, seq_len=48, d_model=64, n_heads=4, d_ff=96,
        layer_types=(["linear_attention"] * 3 + ["full_attention"]) * 2,
        linear_heads=4, linear_key_dim=8, linear_value_dim=16), seed=1)


def _ouro():
    return ouro.OuroDecoder(cfg=ouro.ouro_config(
        vocab_size=128, seq_len=48, d_model=64, n_heads=4, n_kv_heads=4,
        head_dim=16, d_ff=96, n_layers=3), seed=1)


def _sdar_moe():
    return sdar_moe.SdarMoeDecoder(cfg=sdar_moe.sdar_moe_config(
        vocab_size=128, seq_len=48, d_model=64, n_heads=8, n_kv_heads=2,
        head_dim=16, moe_d_ff=48, n_routed_experts=16, top_k=4, n_layers=3,
        held_experts=[4, 5, 6, 7]), seed=1)


MODELS = {"transformer": _transformer, "mla_moe": _mla_moe,
          "lfm2_moe": _lfm2_moe, "olmo_hybrid": _olmo_hybrid,
          "ouro": _ouro, "sdar_moe": _sdar_moe}
# what a family's engine is given beside the ladders (a state row of this
# family is large: the engine is told how many it holds)
ENGINE = {"olmo_hybrid": dict(state_rows=3)}

# sha256 of the lowered text, for the CPU (the ``jnp`` references serve)
# and for a TPU (the Pallas kernels do: ``use_pallas()`` asks
# ``jax.default_backend()``, patched here): the prefills' recorded on
# commit 9317672 (PR 31), the decode steps' on PR 35's tree, the
# transformer's four on PR 40's
RECORDED = {
    ("transformer", "prefill", "cpu"):
        "7b03fbe7591b94974e34c7e6270bdf8741c4f8d1bf002384047d9c5e585b17ce",
    ("transformer", "decode", "cpu"):
        "6878a25ead1b6dbfd1c8a5677854118847f3b18431cf0fb6fddd563b8a881788",
    ("mla_moe", "prefill", "cpu"):
        "e3ee6957027c2cdc8d3239a7007edb69d5e90c757b04248f8f17e061f3ea60af",
    ("mla_moe", "decode", "cpu"):
        "747fae6db7e70fb990ebd203437755c284129cbb3ef915740aed3ed6924d8e8f",
    ("transformer", "prefill", "tpu"):
        "7d037a6f7e92b9de29ab965bf57ab1954331a061fbe1c5dade8dec9015341905",
    ("transformer", "decode", "tpu"):
        "5b34faccbeb7297919bb6afacb1fb8d59d8abea483e42b1ebeb9481d664f6d16",
    ("mla_moe", "prefill", "tpu"):
        "d0ee9c0422cd0fd5708a6c3a56a0795d0b657947d10ffd2e0b6c075839fe6e5c",
    ("mla_moe", "decode", "tpu"):
        "e2591b9341c56b9da03fda179174e2a5be7d531be252bcd8a9163c4090676c8c",
    # recorded on commit 0436209 (PR 35)
    ("lfm2_moe", "prefill", "cpu"):
        "040e017d7c9a04402c5e44389f9a3ed663c03c060a33c944adebe8cd8568bcb8",
    ("lfm2_moe", "decode", "cpu"):
        "b5f6484c9d681f54d31d2490b1a5e4e01f2c737a95589c6aa5f65af2b81f02e8",
    ("lfm2_moe", "prefill", "tpu"):
        "cf4e1013665548eda09803ada4679cadf4f08caea1090ba280a72fab46a61e9a",
    ("lfm2_moe", "decode", "tpu"):
        "518c1f84f3d1775d6c360c57a48d99d3d3c60b57283b8971e8348596a23d50d2",
    # recorded on commit 42c93af (PR 41)
    ("olmo_hybrid", "prefill", "cpu"):
        "df63cb3f0ac5da35623f7628a07b8f4ede7c23df98a325ba23f699e1bc2b20c1",
    ("olmo_hybrid", "decode", "cpu"):
        "5dd6babf81056ac3024025256c341fb81f85fabe44b1f06ac461e238d492e6b9",
    ("olmo_hybrid", "prefill", "tpu"):
        "b1e87a964519051d5be74035447e0dce5a97ef638047a9f91585c62e70575a95",
    ("olmo_hybrid", "decode", "tpu"):
        "dcc76d0cb380b219c8853e188447db52cd8c80dde39477185813cb0efe4fb8ce",
    # recorded on PR 44's tree: the family is new there
    ("ouro", "prefill", "cpu"):
        "a38205adb71a39f35021726ee839654c4757762be53fab625312b479a06662a4",
    ("ouro", "decode", "cpu"):
        "27412625876cb9e2a960a00e2f04a89951b5849523756e5e8e560c2646da49da",
    ("ouro", "prefill", "tpu"):
        "f18f0e83b96469e9336f0281f852d4b5c51f34d918dde9f5819a6f721d674517",
    ("ouro", "decode", "tpu"):
        "44b36cf01f363809ef2b6425764d4d2ba15ce62fc9cf90827c4d55d87a36b142",
    # recorded on commit 21f5319 (PR 47), before PR 48 moved a function
    ("sdar_moe", "prefill", "cpu"):
        "807d8f465e81df32f3b283e92b49b5b74e22b7fd2a95e8c24c2a6a86a1a325e3",
    ("sdar_moe", "decode", "cpu"):
        "5571c8efdb0cff233f12d94685cc9db804ee2d9c81915184e43509c1d1f44a92",
    ("sdar_moe", "prefill", "tpu"):
        "7f6c23dd9964c4bac600b688a192c63de24f63490e0a908bdaeb6c7ff5ad66a0",
    ("sdar_moe", "decode", "tpu"):
        "fe114b6f57e5db4cbabcdeb418d3f565523e66a76aae09253477303a7708e6b3",
}


def lowered_hash(family, phase, platform):
    """sha256 of the lowered text of one family's packed ``phase``
    program: what the worker dispatches, at the top rung of the toy
    ladders, lowered for ``platform`` and the zero-filled packed array."""
    real = jax.default_backend
    # a Pallas kernel's serialized body carries the call stack of its
    # trace; with no frames kept, the text does not depend on who calls
    frames = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    with DecodeEngine(MODELS[family](), **LADDERS,
                      **ENGINE.get(family, {})) as eng:
        rep = eng._replicas[0]
        pmax = eng.max_pages_per_seq
        # a family with per-sequence state has one more packed column
        rows = int(eng._state)
        if phase == "decode":
            # behind the pools the output of the step before it; a pass
            # over blocks holds the rung's spare entries, a block's tokens
            # an entry and one more column (``_step_views``)
            width = eng._width
            step, n, carried = eng._decode_jit, eng._entries(4) * (
                pmax + 5 + rows + (width if width > 1 else 0)), \
                (rep.no_tokens,)
        else:
            step, n, carried = eng._prefill_jit, 3 * 16 + 1 + rows, ()
        jax.default_backend = lambda: platform
        try:
            lowered = step.trace(
                rep.params, *rep.pools, *carried,
                jnp.zeros((n,), jnp.int32)).lower(
                lowering_platforms=(platform,))
        finally:
            jax.default_backend = real
            jax.config.update("jax_traceback_in_locations_limit", frames)
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


@pytest.mark.parametrize("family,phase,platform", sorted(RECORDED))
def test_lowered_program_is_the_parents(family, phase, platform):
    assert lowered_hash(family, phase, platform) == \
        RECORDED[(family, phase, platform)]


if __name__ == "__main__":
    for key in sorted(RECORDED):
        print(key, lowered_hash(*key))
