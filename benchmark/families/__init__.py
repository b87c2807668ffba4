"""What a serving kind takes from the configuration's block family: the
engine built from the configuration and the comparison with that family's
plain reference.  The configuration file names its family (``"family"``),
a module here with ``build_engine(ctx) -> (engine, cfg)``, ``vocab(cfg)``,
``compare(ctx, cfg, samples) -> checks`` and ``counters(engine, cfg)``
(what its readers need beside the window's own counters)."""

import importlib


def of(conf):
    return importlib.import_module("benchmark.families." + conf["family"])
