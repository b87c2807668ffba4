"""Each one-chip cell's step program compiles for a v5e at its real size,
from the CPU sandbox: the TPU's compiler is installed and compiles for a
chip that is described and not attached.  Nothing runs; the numbers are
``memory_analysis`` only.  The topology is described inside a fixture, so
that importing this file loads no TPU library."""

import json
import os

import pytest

from benchmark import manifest, weights

GB = 1e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def galactica():
    man = manifest.load()
    path = next(c["file"] for c in man["configs"])
    with open(os.path.join(manifest.ROOT, path)) as f:
        return json.load(f)


@pytest.fixture
def as_tpu(monkeypatch):
    """The program picks its Pallas kernels by ``jax.default_backend()``,
    which still says cpu here; the test steers it, not a program option."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # a compile for a described chip can be written to the persistent cache
    # but never read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def footprint(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes) / GB


def test_train_step_fits(topo, galactica, as_tpu):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding

    from dist_keras_tpu.models.transformer import transformer_config
    from dist_keras_tpu.parallel.transformer_tp import (
        make_tp_mesh, make_tp_train_step, tp_step_specs)

    with open(os.path.join(manifest.HERE, "traffic",
                           "step_b4_seq2048.json")) as f:
        tr = json.load(f)
    train = galactica["train"]
    cfg = transformer_config(
        input_dim=train["input_dim"], seq_len=tr["seq_len"],
        d_model=galactica["hidden_size"],
        n_heads=galactica["num_attention_heads"],
        n_layers=galactica["num_hidden_layers"]["train"],
        d_ff=galactica["ffn_dim"], n_classes=train["n_classes"])
    tx = optax.adam(train["learning_rate"])
    mesh = make_tp_mesh(1, 1, 1, devices=[topo.devices[0]])
    factory, _ = make_tp_train_step(
        mesh, cfg, optimizer=tx, causal=True,
        compute_dtype=jnp.dtype(train["compute_dtype"]),
        remat=train["remat"])

    def make_state(k):
        p = weights.transformer(k, cfg)
        return p, tx.init(p)

    shapes = jax.eval_shape(make_state, jax.random.PRNGKey(0))
    pspecs, ospecs, xspec, yspec = tp_step_specs(*shapes)

    def described(tree, specs):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
            tree, specs)

    x = jax.ShapeDtypeStruct((tr["batch"], tr["seq_len"],
                              train["input_dim"]), jnp.float32,
                             sharding=NamedSharding(mesh, xspec))
    y = jax.ShapeDtypeStruct((tr["batch"],), jnp.int32,
                             sharding=NamedSharding(mesh, yspec))
    compiled = factory(*shapes).lower(
        described(shapes[0], pspecs), described(shapes[1], ospecs),
        x, y).compile()
    assert "flash_fwd" in compiled.as_text()
    assert footprint(compiled) < 16.0


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_serving_steps_fit(topo, galactica, as_tpu, phase):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import serving
    from dist_keras_tpu.models import transformer
    from dist_keras_tpu.serving.decode import DecodeEngine

    one = SingleDeviceSharding(topo.devices[0])
    cfg = serving.model_config(galactica)
    serve = galactica["serve"]
    slots = serve["decode_ladder"][-1]
    pages = -(-cfg["seq_len"] // serve["page_size"])
    # the two step bodies and the pools' shapes only: no weights are made
    engine = DecodeEngine.__new__(DecodeEngine)
    engine.cfg, engine._family = cfg, transformer
    engine.page_size, engine.num_pages = serve["page_size"], slots * pages
    engine._pools = tuple(transformer.cache_pools(cfg))
    engine._state, engine.state_rows = False, 0

    def S(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(lambda k: weights.transformer(k, cfg),
                       jax.random.PRNGKey(0)))
    pools = [S(shape, jnp.float32) for shape in engine.pool_shapes]
    if phase == "decode":
        fn, args = engine._decode_fn, (
            S((slots,)), S((slots,)), S((slots, pages)), S((slots,)),
            S((slots,)), S((slots,)))
    else:
        t = serve["prefill_ladder"][-1]
        fn, args = engine._prefill_fn, (S((t,)), S(()), S((t,)), S((t,)))
    compiled = jax.jit(
        fn, donate_argnums=tuple(range(1, 1 + len(pools)))).lower(
        params, *pools, *args).compile()
    assert footprint(compiled) < 16.0
