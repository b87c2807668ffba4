"""The shape of ``dist_keras_tpu/models/``: a family imports no family, no
private name crosses a module, and every family of the one registry
(``models/families.py``) keeps the contract stated there.

What two families compute alike lives in ``models/blocks.py``; the arrows
between the six family modules pointed five ways before PR 48, and each
``model_config`` PR had reasonably added one.  The first test reads the
sources, so that the next family finds the rule where it would break it.
"""

import ast
import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dist_keras_tpu.models import Dense, Sequential, families
from dist_keras_tpu.models.blocks import FamilyDecoder
from dist_keras_tpu.serving import DecodeEngine
from dist_keras_tpu.utils.serialization import (
    deserialize_model,
    serialize_model,
)
from test_lowered_text import MODELS  # a toy decoder of every family

PACKAGE = pathlib.Path(families.__file__).parents[1]
MODELS_DIR = PACKAGE / "models"
# the name a saved model and the benchmark's specs carry as ``class_name``
PUBLISHED = {"transformer": "Transformer", "mla_moe": "LatentMoEDecoder",
             "lfm2_moe": "Lfm2MoeDecoder",
             "olmo_hybrid": "OlmoHybridDecoder",
             "sdar_moe": "SdarMoeDecoder", "ouro": "OuroDecoder"}
CONTRACT = ("FAMILY", "vocab", "cache_pools", "step_width", "prefill_step",
            "decode_step", "observe_step")


def _parsed(path):
    return ast.parse(path.read_text(), filename=str(path))


def _defined(tree):
    """The names a module binds itself at its top level (not by import)."""
    names = set()
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for target in targets:
                names |= {n.id for n in ast.walk(target)
                          if isinstance(n, ast.Name)}
    return names


def _imports(tree):
    """-> [(module, name or None)] of every import of the package in the
    file, at any depth."""
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module \
                and node.module.startswith("dist_keras_tpu"):
            found += [(node.module, alias.name) for alias in node.names]
        elif isinstance(node, ast.Import):
            found += [(alias.name, None) for alias in node.names
                      if alias.name.startswith("dist_keras_tpu")]
    return found


def test_a_family_imports_no_family_and_no_private_name_crosses():
    trees = {path.stem: _parsed(path)
             for path in sorted(MODELS_DIR.glob("*.py"))}
    stated = {stem for stem, tree in trees.items()
              if "FAMILY" in _defined(tree)}
    assert stated == set(families.FAMILIES) == set(PUBLISHED)
    for stem, tree in trees.items():
        for module, name in _imports(tree):
            assert not (name or "").startswith("_"), (stem, module, name)
            if stem not in stated:
                continue
            # ``from dist_keras_tpu.models import mla_moe`` and
            # ``from dist_keras_tpu.models.mla_moe import x`` alike
            reached = {module.rpartition(".")[2], name}
            assert not reached & (stated - {stem}), (stem, module, name)
    # and nobody in the package takes from a family what it only imported
    # (a re-export left behind "for compatibility")
    defined = {stem: _defined(trees[stem]) for stem in stated}
    for path in sorted(PACKAGE.rglob("*.py")):
        for module, name in _imports(_parsed(path)):
            family = module.rpartition(".")[2]
            if module == f"dist_keras_tpu.models.{family}" \
                    and family in stated and name is not None:
                assert name in defined[family], (str(path), module, name)


@pytest.mark.parametrize("family", sorted(PUBLISHED))
def test_a_registered_family_keeps_the_contract(family):
    module = families.FAMILIES[family]
    model = MODELS[family]()
    cfg = model.cfg
    # the names the registry's comment states
    for name in CONTRACT:
        assert hasattr(module, name), (family, name)
    assert module.FAMILY == family and families.family_of(cfg) is module
    width = module.step_width(cfg)
    assert (width > 1) == hasattr(module, "step_fixes")
    for layers, rows, entry in module.cache_pools(cfg):
        assert layers >= 1 and rows in ("page", "sequence") and entry
    assert module.vocab(cfg) >= 1
    # one decoder class, the family's own a subclass under its published
    # name, and the name is what a saved model carries
    decoder = families.DECODERS[PUBLISHED[family]]
    assert type(model) is decoder and decoder.__module__ == module.__name__
    if family != "transformer":
        assert issubclass(decoder, FamilyDecoder) and decoder.family is module
        assert {n for n in vars(decoder) if not n.startswith("__")} <= {
            "family", "config", "name"}
    saved = serialize_model(model)
    assert json.loads(saved["model"])["class_name"] == PUBLISHED[family]
    back = deserialize_model(saved)
    assert type(back) is decoder and back.cfg == cfg
    for a, b in zip(jax.tree.leaves(model.params),
                    jax.tree.leaves(back.params), strict=True):
        np.testing.assert_array_equal(a, b)
    tokens = jnp.arange(6, dtype=jnp.int32) % module.vocab(cfg)
    if family == "transformer":
        tokens = jax.nn.one_hot(tokens, cfg["input_dim"])[None]
    np.testing.assert_array_equal(model(tokens), back(tokens))
    # and what the engine says to a model of no family names this one
    stranger = Sequential([Dense(2)])
    stranger.build((3,), seed=0)
    with pytest.raises(ValueError, match=PUBLISHED[family]):
        DecodeEngine(stranger)
