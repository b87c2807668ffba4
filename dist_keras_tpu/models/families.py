"""The block families ``DecodeEngine`` serves: the one table of them.
``serving/decode.py`` looks a model's family up here and
``utils/serialization.py`` a saved decoder's class; a new family is one
module under ``models/`` and one line of :data:`_ROWS`.

A block family is a module under ``models/`` with ``FAMILY`` (the name a
model's ``cfg["family"]`` gives; a cfg that names none is a
``Transformer``'s), ``vocab(cfg)`` (which also refuses what the family
cannot decode), ``cache_pools(cfg)`` (for each pool ``(layers, rows,
entry)``: how many layers it spans (ENTRIES: a looped family states
passes times layers), whether its rows are ``"page"``s of
cached positions or one ``"sequence"`` each, and the trailing shape of
one entry), ``step_width(cfg)`` (positions a slot a step: 1 where a
step yields one token a sequence; a family that generates in BLOCKS
states the block's length, and with ``step_fixes(cfg)`` the id that
stands at a block position nothing is fixed at yet and how many such
positions a pass fixes; its ``decode_step`` is over ENTRIES, of which a
sequence may hold two, ``serving/decode.py:_step_views``),
``prefill_step`` / ``decode_step`` (``(cfg,
params, *pools, ...) -> (int32 array, *pools)``: the tokens first, then
whatever counts the family sends along; a family with a per-sequence
pool is also handed the state rows, last) and ``observe_step(counts, at,
lengths=None, page_size=None)`` for those counts (None when the family
sends none).  Its decoder class takes ``cfg=`` and then ``set_weights``,
and writes its own name as ``class_name`` in ``to_json``.  What two
families compute alike is ``models/blocks.py``'s: a family imports no
other family.
"""

from dist_keras_tpu.models import (
    lfm2_moe,
    mla_moe,
    olmo_hybrid,
    ouro,
    sdar_moe,
    transformer,
)

# a family's module and the class of its decoder
_ROWS = (
    (transformer, transformer.Transformer),
    (mla_moe, mla_moe.LatentMoEDecoder),
    (lfm2_moe, lfm2_moe.Lfm2MoeDecoder),
    (olmo_hybrid, olmo_hybrid.OlmoHybridDecoder),
    (sdar_moe, sdar_moe.SdarMoeDecoder),
    (ouro, ouro.OuroDecoder),
)
FAMILIES = {module.FAMILY: module for module, _ in _ROWS}
DECODERS = {decoder.__name__: decoder for _, decoder in _ROWS}


def family_of(cfg):
    """The module of the family ``cfg`` names; a cfg that names none is a
    ``Transformer``'s."""
    return FAMILIES[cfg.get("family", transformer.FAMILY)]
