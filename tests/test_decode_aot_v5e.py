"""The serving steps, compiled for a v5e from the CPU, touch the KV pool
only through their in-place scatters.

The TPU's compiler is installed and compiles for a chip that is described
and not attached (the recipe of ``benchmark/tests/test_aot_v5e.py``);
nothing runs.  At the widths of galactica-6.7b cut to six layers, eight
slots of 2048 positions (the benchmark's serving cells), the page-major
pool of ``DecodeEngine.pool_shapes`` (since PR 37 ONE pool of ``v | k``
rows) must leave the compiled decode and prefill programs without a copy
of the pool and without a per-layer slice of it: a head-major pool cost
four whole-pool copies a step and twelve layer-sized slice fusions
(PERF.md, PR 25).
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest

from dist_keras_tpu.models import (
    lfm2_moe,
    mla_moe,
    olmo_hybrid,
    ouro,
    sdar_moe,
    transformer,
)
from dist_keras_tpu.models.transformer import (
    init_transformer_params,
    transformer_config,
)
from dist_keras_tpu.serving.decode import DecodeEngine

GB = 1e9
VOCAB, SEQ, SLOTS, PAGE = 50000, 2048, 8, 8
CFG = transformer_config(input_dim=VOCAB, seq_len=SEQ, d_model=4096,
                         n_heads=32, n_layers=6, d_ff=16384,
                         n_classes=VOCAB)
PAGES_PER_SEQ = SEQ // PAGE
# results that may be as large as the pool: the arguments, views of them
# that move nothing, and the scatters that update them in place
FREE = {"parameter", "bitcast", "get-tuple-element", "tuple"}
WRITES = {"scatter", "dynamic-update-slice"}

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = \(?[a-z0-9]+\[([0-9,]*)\]\S* "
    r"([\w\-]+)\(")


def _instructions(text):
    """(computation, name, elements, opcode, line) of every instruction
    with an array result; a tuple result counts by its first element."""
    comp = None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if head:
            comp = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m:
            dims = [int(d) for d in m.group(2).split(",") if d]
            yield comp, m.group(1), math.prod(dims), m.group(3), line


def _roots(text):
    """computation -> the opcode of its ROOT instruction."""
    roots = {}
    for comp, _, _, opcode, line in _instructions(text):
        if line.lstrip().startswith("ROOT "):
            roots[comp] = opcode
    return roots


def _expert_loops(text):
    """The loops of the held experts' grouped form in a compiled program
    (one over the experts, inside it one over an expert's sorted pairs):
    what ``blocks.held_experts`` runs in a call of more than 1,024 tokens
    (PR 47), and in no shorter one."""
    return [line for line in text.splitlines()
            if " while(" in line and "moe_experts/while" in line]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def as_tpu(monkeypatch):
    """The program picks its Pallas kernels by ``jax.default_backend()``,
    which still says cpu here; and a compile for a described chip can be
    written to the persistent cache but never read back without it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _bare_engine(cfg, family, page_size, num_pages, state_rows=0):
    """The step bodies and the pools' shapes only: no weights are made,
    no worker runs."""
    engine = DecodeEngine.__new__(DecodeEngine)
    engine.cfg, engine._family = cfg, family
    engine.page_size, engine.num_pages = page_size, num_pages
    engine._pools = tuple(family.cache_pools(cfg))
    engine._state = bool(state_rows)
    engine.state_rows = state_rows
    engine._width = family.step_width(cfg)
    if engine._width > 1:
        engine._mask_id, engine._fix_a_pass = family.step_fixes(cfg)
    return engine


def _step_and_args(engine, phase, rung, pages_per_seq, S, counts=0):
    """A step of ``phase`` and its integer arguments' shapes: the family's
    function on its arrays apart, or (``packed_*``) the program the worker
    dispatches, on the output of the step before it (as wide as this
    rung's own: ``rung`` stands for the top of the ladder, and ``counts``
    says how many values the family sends behind its tokens) and the ONE
    packed array (which cuts the tables out by the engine's
    ``max_pages_per_seq``, set here: the engine is a bare one).  A family
    with per-sequence state takes its rows as one array more."""
    engine.max_pages_per_seq = pages_per_seq
    engine.max_slots = rung
    rows = int(engine._state)
    width = engine._width
    if width > 1 and phase == "packed_decode":
        # a pass over blocks: the blocks' tokens in the carried output and
        # in the packed array, and the column of what each entry fixes;
        # the rung's sequences and its spare entries (a commit's next
        # block rides in one)
        entries = engine._entries(rung)
        return engine._packed_decode_fn, (
            S((entries * width + counts,)),
            S((entries * (pages_per_seq + width + 5 + rows),)))
    if phase == "decode":
        return engine._decode_fn, (
            S((rung,)), S((rung,)), S((rung, pages_per_seq)), S((rung,)),
            S((rung,)), S((rung,))) + (S((rung,)),) * rows
    if phase == "packed_decode":
        return engine._packed_decode_fn, (
            S((rung + counts,)), S((rung * (pages_per_seq + 5 + rows),)))
    if phase == "packed_prefill":
        return engine._packed_prefill_fn, (S((3 * rung + 1 + rows,)),)
    return engine._prefill_fn, (
        S((rung,)), S(()), S((rung,)), S((rung,))) + (S(()),) * rows


def _compiled_transformer_step(topo, phase, rung):
    """``(compiled, pool_shape)`` of one of the transformer family's steps
    at the widths above, its one pool donated."""
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])

    def S(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    engine = _bare_engine(CFG, transformer, PAGE, SLOTS * PAGES_PER_SEQ)
    (pool_shape,) = engine.pool_shapes
    assert pool_shape == (CFG["n_layers"], engine.num_pages + 1, PAGE,
                          2 * CFG["d_model"])
    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(lambda k: init_transformer_params(k, CFG),
                       jax.random.PRNGKey(0)))
    pool = S(pool_shape, jnp.float32)
    fn, args = _step_and_args(engine, phase, rung, PAGES_PER_SEQ, S)
    return jax.jit(fn, donate_argnums=(1,)).lower(
        params, pool, *args).compile(), pool_shape


@pytest.mark.parametrize("phase,rung,temp_gb", [
    ("decode", SLOTS, 0.1), ("prefill", 128, 1.0), ("prefill", 2048, 0.5),
    ("packed_decode", SLOTS, 0.1), ("packed_prefill", 2048, 0.5)])
def test_serving_step_leaves_the_pool_in_place(topo, as_tpu, phase, rung,
                                               temp_gb):
    """The ONE pool of ``v | k`` rows, donated, is written in place once a
    layer and never copied or sliced.  The 8-slot decode step reads it
    through one ``latent_decode`` kernel a layer that walks the live
    pages where they lie: no gathered copy of every slot's whole table
    (two of 268 MB a layer until PR 37, the temporaries then bounded
    under 1 GB), the temporaries under 0.1 GB.  A 2,048-rung prefill's
    are 0.374 GB, held under 0.5 (the same before PR 40: the compiler had
    folded the ``one_hot`` operand into the product it fed and never
    stored its 410 MB)."""
    compiled, pool_shape = _compiled_transformer_step(topo, phase, rung)
    text = compiled.as_text()

    pool_elems = math.prod(pool_shape)
    layer_elems = pool_elems // pool_shape[0]
    roots = _roots(text)
    scatters, offenders = 0, []
    for comp, name, elems, opcode, line in _instructions(text):
        if elems not in (pool_elems, layer_elems) or opcode in FREE:
            continue
        called = re.search(r"calls=%([\w.\-]+)", line)
        in_place = opcode == "scatter" or (
            opcode == "fusion" and called
            and roots.get(called.group(1)) == "scatter")
        if elems == pool_elems and in_place:
            scatters += opcode == "scatter"     # one at each fusion's root
            continue
        offenders.append(f"{comp}: %{name} = {opcode} of "
                         f"{'pool' if elems == pool_elems else 'layer'} size")
    assert not offenders, offenders
    # the parse saw the program: a row is written once a layer
    assert scatters == CFG["n_layers"], scatters
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == 4 * pool_elems          # donated
    assert m.temp_size_in_bytes < temp_gb * GB, m.temp_size_in_bytes
    # every slot's whole table gathered: what the read was until PR 37
    assert f"f32[{SLOTS * PAGES_PER_SEQ},{PAGE},32,128]" not in text
    if "decode" in phase:
        calls = re.findall(r"custom-call\([^\n]*latent_decode", text)
        assert len(calls) == CFG["n_layers"], len(calls)
    else:
        assert "flash_fwd" in text and "latent_decode" not in text


_OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.mark.parametrize("phase,rung", [
    ("packed_decode", SLOTS), ("packed_prefill", 2048)])
def test_serving_step_multiplies_by_the_vocabulary_once(topo, as_tpu, phase,
                                                        rung):
    """A token is embedded by reading its row of ``proj`` (PR 40): the
    program the worker dispatches holds exactly ONE product with the
    vocabulary's dimension, the head's (a ``one_hot(tokens) @ proj`` read
    the whole 0.82 GB table a step to select a rung of 16 KB rows, and
    led both galactica serving traces), nothing of the vocabulary's size
    under the ``embed`` scope, and no ``(rung, n_classes)`` mask of an
    ``iota`` compared with the tokens."""
    compiled, _ = _compiled_transformer_step(topo, phase, rung)
    text = compiled.as_text()
    dims = {m.group(1): [int(d) for d in m.group(2).split(",") if d]
            for m in map(_INSTRUCTION.match, text.splitlines()) if m}
    products, gathers = set(), []
    for _, name, _, opcode, line in _instructions(text):
        scope = _OP_NAME.search(line)
        scope = scope.group(1) if scope else ""
        operands = re.findall(r"%([\w.\-]+)", line.split("(", 1)[1])
        if "/embed/" in scope:
            assert VOCAB not in dims[name], line
            if opcode == "gather":
                gathers.append(name)
        # a product keeps its primitive's name however the compiler
        # realises it: a convolution, or (a prefill's one row times the
        # head) a multiply feeding its own reduce
        if scope.endswith("dot_general") and any(
                VOCAB in dims.get(n, ()) for n in [name, *operands]):
            products.add(scope.split("/", 1)[1])
        assert not (opcode == "compare"
                    and dims[name] == [rung, VOCAB]), line
    assert products == {"head/dot_general"}, products
    # the parse saw the scope: the read is a gather of (rung, d_model)
    assert gathers and all(
        dims[g] == [rung, CFG["d_model"]] for g in gathers), gathers


# -- the latent-attention, sparse-expert family (models/mla_moe.py) ------
# kimi-vl-a3b-instruct as the benchmark cuts it: every width as published,
# 1 dense + 8 expert layers, 8 of 64 experts held, 32 slots of 6656
# positions in pages of 16
L_SLOTS, L_POSITIONS, L_PAGE = 32, 6656, 16


def _latent_cfg():
    from dist_keras_tpu.models.mla_moe import mla_moe_config

    return mla_moe_config(
        vocab_size=163840, seq_len=L_POSITIONS, d_model=2048, n_heads=16,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        kv_lora_rank=512, d_ff=11264, moe_d_ff=1408, n_routed_experts=64,
        n_shared_experts=2, top_k=6, n_layers=9,
        held_experts=list(range(8)), routed_scaling_factor=2.446,
        rope_theta=800000.0)


@pytest.mark.parametrize("phase,rung,temp_gb", [
    ("decode", L_SLOTS, 0.1), ("decode", 8, 0.1), ("prefill", 2560, 0.4),
    ("prefill", 6144, 0.8), ("packed_decode", L_SLOTS, 0.1)])
def test_latent_step_leaves_the_pool_in_place(topo, as_tpu, phase, rung,
                                              temp_gb):
    """The new family's steps hold no copy of the latent pool (rows of
    whole lanes: at the entry's own 576 values the v5e's default layout
    puts the pages minor and every step converts the pool twice, 4.4 GB
    each way) and no vocabulary-sized temporary (the head's norm weight
    folded into the head); their temporaries stay under ``temp_gb``: for
    a prefill the flash forward's and one pass of 256 sorted pairs of the
    held experts (the masked dense pass held every expert's hidden rows
    of a layer, and its bounds were 0.8 and 1.5 GB until PR 47; the
    grouped form's experts are converted behind a barrier, or every
    layer's are at once), next to nothing for a decode step, whose read of the pool is one Mosaic kernel a
    layer that walks the live pages where they lie (no gathered rows:
    0.27 GB of them in bfloat16 until PR 28, under 0.5 GB then)."""
    import functools

    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])

    def S(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cfg = _latent_cfg()
    engine = _bare_engine(cfg, mla_moe, L_PAGE,
                          L_SLOTS * L_POSITIONS // L_PAGE)
    (pool_shape,) = engine.pool_shapes
    assert pool_shape == (9, engine.num_pages + 1, L_PAGE, 640)
    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(functools.partial(mla_moe.init_params, cfg=cfg),
                       jax.random.PRNGKey(0)))
    pool = S(pool_shape, jnp.float32)
    fn, args = _step_and_args(engine, phase, rung, L_POSITIONS // L_PAGE, S,
                              counts=len(cfg["held_experts"]) + 2)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pool, *args).compile()
    text = compiled.as_text()

    pool_elems = math.prod(pool_shape)
    big = {pool_elems: "pool", pool_elems // pool_shape[0]: "layer",
           cfg["vocab_size"] * cfg["d_model"]: "vocabulary"}
    roots = _roots(text)
    scatters, offenders = 0, []
    for comp, name, elems, opcode, line in _instructions(text):
        if elems not in big or opcode in FREE:
            continue
        called = re.search(r"calls=%([\w.\-]+)", line)
        root = roots.get(called.group(1)) if called else None
        if opcode == "fusion" and called.group(1).startswith("bitcast"):
            continue                                  # a view: moves nothing
        if big[elems] == "vocabulary" and "fus" in comp:
            # inside a fusion nothing of this size is written: a prefill's
            # one row times the head is a multiply feeding its own reduce
            continue
        if big[elems] == "pool" and (opcode == "scatter" or (
                opcode == "fusion" and root == "scatter")):
            scatters += opcode == "scatter"
            continue
        offenders.append(f"{comp}: %{name} = {opcode} of {big[elems]} size")
    assert not offenders, offenders
    assert scatters >= cfg["n_layers"], scatters     # one write a layer
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == 4 * pool_elems          # donated
    assert m.temp_size_in_bytes < temp_gb * GB, m.temp_size_in_bytes
    if phase == "prefill":
        assert "flash_fwd" in text
        # both rungs are over ``blocks.GROUPED_OVER``: each of the eight
        # expert layers runs its held experts over the sorted pairs, a
        # loop over the experts around a loop over one expert's pairs, and
        # the flash forward stays the program's only kernel
        assert len(_expert_loops(text)) == 2 * (cfg["n_layers"] - 1)
        assert all("flash_fwd" in line for line in text.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in line)
        return
    assert not _expert_loops(text)            # a step is the dense pass
    if phase == "packed_decode":
        # the worker's one array crosses into the step whole: nothing
        # else of its type comes in, and the program is still found by
        # the family function's name
        ints = [a for a in jax.tree.leaves(compiled.args_info)
                if a.dtype == jnp.int32]
        # the carried output (the tokens and the family's ten counts),
        # then the one array that crosses
        assert [a.shape for a in ints] == [
            (rung + 10,), (rung * (L_POSITIONS // L_PAGE + 5),)]
        assert re.search(r"^HloModule jit__packed_decode_fn", text, re.M)
    # the read: one kernel a layer over the flat float32 pool itself, and
    # nothing of the size of the slots' whole tables, in any type
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "latent_decode" in line]
    assert len(calls) == cfg["n_layers"], len(calls)
    flat = f"f32[{math.prod(pool_shape[:2])},{L_PAGE},640]"
    assert all(flat in line for line in calls), calls[0]
    table_rows = rung * (L_POSITIONS // L_PAGE)
    gathered = [
        f"{comp}: %{name} = {opcode}"
        for comp, name, elems, opcode, _ in _instructions(text)
        if elems >= table_rows * L_PAGE * 512 and elems not in big
        and opcode not in FREE]
    assert not gathered, gathered


# -- the convolution, grouped-query, expert family (models/lfm2_moe.py) ---
# lfm2-8b-a1b as the benchmark cuts it: every width as published, layers
# 0-7 (6 convolutions, 2 attentions, 2 dense and 6 expert layers, all 32
# experts held), 64 slots of 1280 positions in pages of 16, 256 state rows
C_SLOTS, C_POSITIONS, C_PAGE, C_ROWS = 64, 1280, 16, 256


def _conv_cfg():
    return lfm2_moe.lfm2_moe_config(
        vocab_size=65536, seq_len=C_POSITIONS, d_model=2048, n_heads=32,
        n_kv_heads=8, d_ff=7168, moe_d_ff=1792, n_routed_experts=32,
        top_k=4, layer_types=["conv", "conv", "full_attention", "conv",
                              "conv", "conv", "full_attention", "conv"])


@pytest.mark.parametrize("phase,rung,temp_gb", [
    ("packed_decode", C_SLOTS, 0.1), ("decode", 16, 0.1),
    ("packed_prefill", 768, 0.6), ("prefill", 256, 0.3)])
def test_conv_expert_step_leaves_both_pools_in_place(topo, as_tpu, phase,
                                                     rung, temp_gb):
    """The third family's steps hold no copy of the ``v | k`` pool (rows
    of 1,024 lanes over the two attention layers only) nor of the state
    pool (a row a sequence over the six convolution layers only), no
    expert-layer-sized or vocabulary-sized temporary, and their
    temporaries stay under ``temp_gb``; decoding reads the K/V with one
    ``latent_decode`` Mosaic kernel an attention layer over the flat
    float32 pool itself, the prefill attends with one ``flash_fwd`` each
    (32 query heads over 8 K/V heads of 64: the kernel's K/V operands
    have 8 heads, nothing repeats them)."""
    import functools

    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])

    def S(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cfg = _conv_cfg()
    pages_per_seq = C_POSITIONS // C_PAGE
    engine = _bare_engine(cfg, lfm2_moe, C_PAGE, C_SLOTS * pages_per_seq,
                          state_rows=C_ROWS)
    kv_shape, state_shape = engine.pool_shapes
    assert kv_shape == (2, engine.num_pages + 1, C_PAGE, 1024)
    assert state_shape == (6, C_ROWS + 1, 2, 2048)
    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(functools.partial(lfm2_moe.init_params, cfg=cfg),
                       jax.random.PRNGKey(0)))
    fn, args = _step_and_args(engine, phase, rung, pages_per_seq, S,
                              counts=cfg["n_routed_experts"] + 2)
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
        params, S(kv_shape, jnp.float32), S(state_shape, jnp.float32),
        *args).compile()
    text = compiled.as_text()

    kv_elems, state_elems = math.prod(kv_shape), math.prod(state_shape)
    big = {kv_elems: "K/V pool", kv_elems // kv_shape[0]: "K/V layer",
           state_elems: "state pool",
           cfg["vocab_size"] * cfg["d_model"]: "vocabulary",
           32 * 2048 * 1792: "expert matrix"}
    roots = _roots(text)
    scatters, offenders = {"K/V pool": 0, "state pool": 0}, []
    for comp, name, elems, opcode, line in _instructions(text):
        if elems not in big or opcode in FREE:
            continue
        called = re.search(r"calls=%([\w.\-]+)", line)
        root = roots.get(called.group(1)) if called else None
        if opcode == "fusion" and called.group(1).startswith("bitcast"):
            continue                                  # a view: moves nothing
        if big[elems] == "vocabulary" and "fus" in comp:
            # a prefill's one row times the tied table: a multiply feeding
            # its own reduce, nothing of this size is written
            continue
        # in place on the donated pool: a scatter over pages or rows, or
        # (a prefill's ONE row) a dynamic-update-slice
        if big[elems] in scatters and (opcode in WRITES or (
                opcode == "fusion" and root in WRITES)):
            scatters[big[elems]] += opcode in WRITES
            continue
        offenders.append(f"{comp}: %{name} = {opcode} of {big[elems]} size")
    assert not offenders, offenders
    # each layer writes its own pool once, in place
    assert scatters == {"K/V pool": 2, "state pool": 6}, scatters
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == 4 * (kv_elems + state_elems)  # donated
    assert m.temp_size_in_bytes < temp_gb * GB, m.temp_size_in_bytes
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    # rungs of 1,024 tokens or fewer: the held experts stay the dense pass
    assert not _expert_loops(text)
    if "prefill" in phase:
        assert len(kernels) == 2 and all("flash_fwd" in k for k in kernels)
        # q of 32 heads, k and v of 8: fetched through the index map
        assert all(f"f32[32,{rung},64]" in k and f"f32[8,{rung},64]" in k
                   for k in kernels), kernels[0]
        return
    assert len(kernels) == 2 and all("latent_decode" in k for k in kernels)
    flat = f"f32[{math.prod(kv_shape[:2])},{C_PAGE},1024]"
    assert all(flat in k for k in kernels), kernels[0]
    if phase == "packed_decode":
        # one array crosses: the six of every family and this one's rows
        ints = [a for a in jax.tree.leaves(compiled.args_info)
                if a.dtype == jnp.int32]
        assert [a.shape for a in ints] == [
            (rung + 34,), (rung * (pages_per_seq + 6),)]
        assert re.search(r"^HloModule jit__packed_decode_fn", text, re.M)


# -- the block-diffusion, sparse-expert family (models/sdar_moe.py) --------
# sdar-30b-a3b-chat as the benchmark cuts it: every width as published,
# layers 0-15, experts 0-15 of 128 held, 32 slots of 1536 positions in
# pages of 16, blocks of 4 positions
B_SLOTS, B_POSITIONS, B_PAGE, B_BLOCK = 32, 1536, 16, 4


def _blocks_cfg():
    return sdar_moe.sdar_moe_config(
        vocab_size=151936, seq_len=B_POSITIONS, d_model=2048, n_heads=32,
        n_kv_heads=4, head_dim=128, moe_d_ff=768, n_routed_experts=128,
        top_k=8, n_layers=16, held_experts=list(range(16)),
        block_length=B_BLOCK, denoising_steps=4, mask_token_id=151669)


@pytest.mark.parametrize("phase,rung,temp_gb", [
    ("packed_decode", B_SLOTS, 0.2), ("packed_prefill", 1024, 0.3)])
def test_block_diffusion_step_leaves_the_pool_in_place(topo, as_tpu, phase,
                                                       rung, temp_gb):
    """The fifth family's pass (32 slots and their 8 spare entries x 4
    positions: 160 rows) and its
    1,024 prefill hold no copy of the ``v | k`` pool (rows of 1,024 lanes
    over all 16 layers) nor of a layer of it, no expert-sized temporary
    and (the pass) one vocabulary-sized product, the head's; each layer
    writes its rows once, in place, and reads them back with one
    ``latent_decode`` kernel over the flat float32 pool at ``4 x 32 = 128``
    query rows an entry (the prefill: one ``flash_fwd``, 32 query heads over
    4 K/V heads of 128).  The configuration file's ``reduced_why`` quotes
    these programs' ``memory_analysis``."""
    import functools

    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])

    def S(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cfg = _blocks_cfg()
    pages_per_seq = B_POSITIONS // B_PAGE
    engine = _bare_engine(cfg, sdar_moe, B_PAGE, B_SLOTS * pages_per_seq)
    (kv_shape,) = engine.pool_shapes
    assert kv_shape == (16, engine.num_pages + 1, B_PAGE, 1024)
    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(functools.partial(sdar_moe.init_params, cfg=cfg),
                       jax.random.PRNGKey(0)))
    counts = len(cfg["held_experts"]) + 2
    fn, args = _step_and_args(engine, phase, rung, pages_per_seq, S,
                              counts=counts)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, S(kv_shape, jnp.float32), *args).compile()
    text = compiled.as_text()

    kv_elems = math.prod(kv_shape)
    big = {kv_elems: "K/V pool", kv_elems // kv_shape[0]: "K/V layer",
           16 * 2048 * 768: "expert matrix"}
    roots = _roots(text)
    scatters, offenders = 0, []
    for comp, name, elems, opcode, line in _instructions(text):
        if elems not in big or opcode in FREE:
            continue
        called = re.search(r"calls=%([\w.\-]+)", line)
        root = roots.get(called.group(1)) if called else None
        if opcode == "fusion" and called.group(1).startswith("bitcast"):
            continue                                  # a view: moves nothing
        if big[elems] == "K/V pool" and (opcode in WRITES or (
                opcode == "fusion" and root in WRITES)):
            scatters += opcode in WRITES
            continue
        if big[elems] == "expert matrix" and re.search(
                r"= f32\[16,\d+,\d+\]\{[^}]*S\(1\)\}", line):
            # the compiler's own prefetch of an ARGUMENT (a held layer's
            # gate, up or down matrices, 0.1 GB) into its fast memory
            # space ahead of the product: counted in the temporaries
            # below, nothing the program computes
            continue
        offenders.append(f"{comp}: %{name} = {opcode} of {big[elems]} size")
    assert not offenders, offenders
    assert scatters == 16, scatters
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == 4 * kv_elems            # donated
    assert m.temp_size_in_bytes < temp_gb * GB, m.temp_size_in_bytes
    # weights 8.55 GB and the pool 3.22 GB: 74% of the chip's 16 GB with
    # the pass's temporaries.  The prefill takes neither the head (it
    # yields no token) nor the last layer's experts (nothing reads what
    # they would add): 7.00 GB
    weights = m.argument_size_in_bytes - 4 * kv_elems
    assert (6.9 if "prefill" in phase else 8.5) * GB < weights < (
        7.1 if "prefill" in phase else 8.6) * GB
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 16
    # the top prefill rung is 1,024 tokens, a pass 160 rows: the dense pass
    assert not _expert_loops(text)
    if "prefill" in phase:
        assert all("flash_fwd" in k for k in kernels)
        assert all(f"f32[32,{rung},128]" in k and f"f32[4,{rung},128]" in k
                   for k in kernels), kernels[0]
        assert str(cfg["vocab_size"]) not in "".join(
            line for line in text.splitlines() if " dot(" in line
            or "convolution(" in line)
        return
    assert all("latent_decode" in k for k in kernels)
    flat = f"f32[{math.prod(kv_shape[:2])},{B_PAGE},1024]"
    # 32 sequences and the 8 entries their blocks of 4 passes ask for; an
    # entry's 4 x 32 query rows, each laid over the row's 1,024 lanes
    entries = engine._entries(rung)
    assert entries == 40
    assert all(flat in k and f"f32[{entries},128,1024]" in k
               for k in kernels), kernels[0]
    ints = [a for a in jax.tree.leaves(compiled.args_info)
            if a.dtype == jnp.int32]
    assert [a.shape for a in ints] == [
        (entries * B_BLOCK + counts,),
        (entries * (pages_per_seq + B_BLOCK + 5),)]
    assert re.search(r"^HloModule jit__packed_decode_fn", text, re.M)


# -- the gated-delta-rule, full-attention family (models/olmo_hybrid.py) ---
# olmo-hybrid-7b as the benchmark cuts it: every width as published, layers
# 0-7 (6 linear layers, 2 full ones), 32 slots of 1536 positions in pages
# of 16, 40 state rows
H_SLOTS, H_POSITIONS, H_PAGE, H_ROWS = 32, 1536, 16, 40


def _hybrid_cfg():
    return olmo_hybrid.olmo_hybrid_config(
        vocab_size=100352, seq_len=H_POSITIONS, d_model=3840, n_heads=30,
        d_ff=11008,
        layer_types=(["linear_attention"] * 3 + ["full_attention"]) * 2,
        linear_heads=30, linear_key_dim=96, linear_value_dim=192)


@pytest.mark.parametrize("phase,rung,temp_gb", [
    ("packed_decode", H_SLOTS, 0.1), ("decode", 8, 0.1),
    ("packed_prefill", 1024, 0.4), ("prefill", 384, 0.2)])
def test_delta_rule_step_leaves_its_three_pools_in_place(topo, as_tpu, phase,
                                                         rung, temp_gb):
    """The fourth family's steps hold no copy of the ``v | k`` pool (rows
    of 7,680 lanes over the two attention layers only), of the recurrent
    matrices' pool (2.2 MB a sequence and layer over the six linear layers
    only) nor of a layer of either, no vocabulary-sized temporary, and
    their temporaries stay under ``temp_gb``.  Decoding updates the
    matrices with one ``gdn_state_step`` Mosaic kernel a linear layer whose
    output IS its operand (the donated pool: nothing of its size is
    gathered, scattered or copied) and reads the K/V with one
    ``latent_decode`` kernel an attention layer over the flat float32 pool
    itself; the prefill attends with one ``flash_fwd`` each at 30 heads of
    128 and writes a sequence's matrices with one dynamic-update-slice a
    linear layer.  **Peak memory:** arguments (weights 9.74 GB, pools 3.79
    GB as the chip lays them out) and temporaries are held under 14.5 GB
    together, the issue's line for falling back to fewer positions."""
    import functools

    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])

    def S(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cfg = _hybrid_cfg()
    pages_per_seq = H_POSITIONS // H_PAGE
    engine = _bare_engine(cfg, olmo_hybrid, H_PAGE, H_SLOTS * pages_per_seq,
                          state_rows=H_ROWS)
    kv_shape, taps_shape, state_shape = engine.pool_shapes
    assert kv_shape == (2, engine.num_pages + 1, H_PAGE, 7680)
    assert taps_shape == (6, H_ROWS + 1, 3, 11520)
    assert state_shape == (6, H_ROWS + 1, 30, 96, 192)
    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(functools.partial(olmo_hybrid.init_params, cfg=cfg),
                       jax.random.PRNGKey(0)))
    fn, args = _step_and_args(engine, phase, rung, pages_per_seq, S)
    compiled = jax.jit(fn, donate_argnums=(1, 2, 3)).lower(
        params, S(kv_shape, jnp.float32), S(taps_shape, jnp.float32),
        S(state_shape, jnp.float32), *args).compile()
    text = compiled.as_text()

    kv_elems, state_elems = math.prod(kv_shape), math.prod(state_shape)
    big = {kv_elems: "K/V pool", kv_elems // kv_shape[0]: "K/V layer",
           state_elems: "state pool",
           state_elems // state_shape[0]: "state layer",
           cfg["vocab_size"] * cfg["d_model"]: "vocabulary"}
    roots = _roots(text)
    writes, offenders = {"K/V pool": 0, "state pool": 0}, []
    for comp, name, elems, opcode, line in _instructions(text):
        if elems not in big or opcode in FREE:
            continue
        called = re.search(r"calls=%([\w.\-]+)", line)
        root = roots.get(called.group(1)) if called else None
        if opcode == "fusion" and called.group(1).startswith("bitcast"):
            continue                                  # a view: moves nothing
        if big[elems] == "vocabulary" and "fus" in comp:
            continue        # a row times the head: nothing this size written
        if big[elems] in writes and (opcode in WRITES or (
                opcode == "fusion" and root in WRITES)):
            writes[big[elems]] += opcode in WRITES
            continue
        offenders.append(f"{comp}: %{name} = {opcode} of {big[elems]} size")
    assert not offenders, offenders
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    steps = [k for k in kernels if "gdn_state_step" in k]
    # a decode step's matrices are written by the kernel, whose second
    # result IS its fourth operand (the tuple counts by its first above)
    assert all("output_to_operand_aliasing={{1}: (3, {})}" in k
               for k in steps), steps[:1]
    writes["state pool"] += len(steps)
    # each layer writes its own pool in place (at 8 slots the compiler
    # splits one K/V scatter in two)
    assert writes["K/V pool"] >= 2 and writes["state pool"] == 6, writes
    m = compiled.memory_analysis()
    # all three donated, as the chip lays them out: a 192-wide minor
    # dimension takes 256 lanes, three rows of taps a few more
    pools = 4 * (kv_elems + math.prod(taps_shape)
                 + state_elems // 192 * 256)
    assert pools <= m.alias_size_in_bytes < 1.01 * pools
    assert m.temp_size_in_bytes < temp_gb * GB, m.temp_size_in_bytes
    peak = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert 13.0 * GB < peak < 14.5 * GB, peak
    if "prefill" in phase:
        assert len(kernels) == 2 and all("flash_fwd" in k for k in kernels)
        assert all(f"f32[30,{rung},128]" in k for k in kernels), kernels[0]
        return
    reads = [k for k in kernels if "latent_decode" in k]
    assert len(reads) == 2 and len(steps) == 6 and len(kernels) == 8
    flat = f"f32[{math.prod(kv_shape[:2])},{H_PAGE},7680]"
    assert all(flat in k for k in reads), reads[0]
    rows = f"f32[{math.prod(state_shape[:2])},30,96,192]"
    assert all(rows in k for k in steps), steps[0]
    if phase == "packed_decode":
        # one array crosses: the six of every family and this one's rows;
        # nothing rides behind the tokens
        ints = [a for a in jax.tree.leaves(compiled.args_info)
                if a.dtype == jnp.int32]
        assert [a.shape for a in ints] == [
            (rung,), (rung * (pages_per_seq + 6),)]
        assert re.search(r"^HloModule jit__packed_decode_fn", text, re.M)


# -- the looped family (models/ouro.py) ------------------------------------
# ouro-2.6b as the benchmark cuts it: every width as published, layers 0-11
# of 48, all four passes, 16 slots of 768 positions in pages of 16
O_SLOTS, O_POSITIONS, O_PAGE, O_LAYERS, O_PASSES = 16, 768, 16, 12, 4


def _looped_cfg():
    return ouro.ouro_config(
        vocab_size=49152, seq_len=O_POSITIONS, d_model=2048, n_heads=16,
        n_kv_heads=16, head_dim=128, d_ff=5632, n_layers=O_LAYERS,
        ut_steps=O_PASSES)


@pytest.mark.parametrize("phase,rung,temp_gb", [
    ("packed_decode", O_SLOTS, 0.05), ("packed_prefill", 288, 0.05)])
def test_looped_step_holds_the_stack_once_and_the_pool_in_place(
        topo, as_tpu, phase, rung, temp_gb):
    """The sixth family's decode step and its longest prefill, compiled
    for a v5e at the cell's sizes: ONE loop over the four passes whose body
    holds the twelve layers once (twelve ``latent_decode`` reads of the
    flat float32 pool at 16 query rows a slot, or twelve ``flash_fwd`` of
    16 heads of 128: not forty-eight), the ``v | k`` pool of ``48 x 769
    x 16 x 4,096`` values (9.68 GB) donated and aliased THROUGH the loop,
    every result of its size one of the twelve in-place scatters of the
    body (the entry's index a traced value), and no copy of the pool, of
    a pass's twelve entries or of one entry.  Weights 3.27 GB.  **The
    temporaries (0.012 and 0.022 GB: what the configuration's
    ``reduced_why`` quotes) say that the passes read the float32 leaves
    themselves:** without ``ouro._float32_reader`` and the barrier beside
    it the compiler holds a bfloat16 copy of the twelve layers, 1.27 GB,
    for as long as either program runs (the last lines)."""
    import functools

    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])

    def S(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cfg = _looped_cfg()
    pages_per_seq = O_POSITIONS // O_PAGE
    engine = _bare_engine(cfg, ouro, O_PAGE, O_SLOTS * pages_per_seq)
    (kv_shape,) = engine.pool_shapes
    assert kv_shape == (O_PASSES * O_LAYERS, engine.num_pages + 1, O_PAGE,
                        4096)
    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(functools.partial(ouro.init_params, cfg=cfg),
                       jax.random.PRNGKey(0)))
    fn, args = _step_and_args(engine, phase, rung, pages_per_seq, S,
                              counts=ouro.N_COUNTS)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, S(kv_shape, jnp.float32), *args).compile()
    text = compiled.as_text()

    kv_elems = math.prod(kv_shape)
    big = {kv_elems: "pool", kv_elems // O_PASSES: "a pass's entries",
           kv_elems // kv_shape[0]: "entry"}
    roots = _roots(text)
    scatters, offenders = set(), []
    for comp, name, elems, opcode, line in _instructions(text):
        if elems not in big or opcode in FREE | {"while"}:
            continue
        called = re.search(r"calls=%([\w.\-]+)", line)
        root = roots.get(called.group(1)) if called else None
        if opcode == "fusion" and called.group(1).startswith("bitcast"):
            continue                                  # a view: moves nothing
        if big[elems] == "pool" and opcode in WRITES:
            scatters.add(name)
            continue
        if big[elems] == "pool" and opcode == "fusion" and root in WRITES:
            continue                       # the fusion around a scatter
        offenders.append(f"{comp}: %{name} = {opcode} of {big[elems]} size")
    assert not offenders, offenders
    # a layer writes its rows once, in place (the decode step's compiler
    # clones one scatter's fusion: the same row written where it lies)
    assert O_LAYERS <= len(scatters) <= O_LAYERS + 1, scatters
    # the passes are a loop of the program: one while, its state the pool
    loops = [line for line in text.splitlines() if " while(" in line]
    assert len(loops) == 1
    assert f"f32[{','.join(map(str, kv_shape))}]" in loops[0]
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == 4 * kv_elems            # donated
    assert m.temp_size_in_bytes < temp_gb * GB, m.temp_size_in_bytes
    weights = m.argument_size_in_bytes - 4 * kv_elems
    assert 3.26 * GB < weights < 3.28 * GB, weights
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == O_LAYERS, len(kernels)
    if "prefill" in phase:
        assert all("flash_fwd" in k for k in kernels)
        assert all(f"f32[16,{rung},128]" in k for k in kernels), kernels[0]
        return
    assert all("latent_decode" in k for k in kernels)
    flat = f"f32[{math.prod(kv_shape[:2])},{O_PAGE},4096]"
    assert all(flat in k and f"f32[{rung},16,4096]" in k
               for k in kernels), kernels[0]
    # the head's is the one product with the vocabulary's dimension
    assert sum(str(cfg["vocab_size"]) in line for line in text.splitlines()
               if " convolution(" in line or " dot(" in line) == 1
    ints = [a for a in jax.tree.leaves(compiled.args_info)
            if a.dtype == jnp.int32]
    assert [a.shape for a in ints] == [
        (rung + ouro.N_COUNTS,), (rung * (pages_per_seq + 5),)]
    assert re.search(r"^HloModule jit__packed_decode_fn", text, re.M)
    # what the reader is for: without it the layers are held twice
    real = ouro._float32_reader
    ouro._float32_reader = lambda blk: 0.0
    try:
        # a function of its own: ``fn`` itself is traced already
        copied = jax.jit(lambda *a: fn(*a), donate_argnums=(1,)).lower(
            params, S(kv_shape, jnp.float32), *args).compile()
    finally:
        ouro._float32_reader = real
    assert 1.2 * GB < copied.memory_analysis().temp_size_in_bytes < 1.3 * GB


# -- the train step (parallel/transformer_tp.py) --------------------------
def test_train_step_holds_three_flash_kernels(topo, as_tpu):
    """``make_tp_train_step`` compiled for a v5e, one causal layer in
    bfloat16 with heads of 128: the attention of its forward and backward
    is exactly three Mosaic kernels, ``flash_fwd``, ``flash_bwd_dq`` and
    ``flash_bwd_dkv`` (the only backward the train cell has measured),
    and no other Mosaic call."""
    import optax
    from jax.sharding import NamedSharding

    from dist_keras_tpu.parallel.transformer_tp import (
        make_tp_mesh,
        make_tp_train_step,
        tp_step_specs,
    )

    cfg = transformer_config(input_dim=32, seq_len=256, d_model=256,
                             n_heads=2, n_layers=1, d_ff=512, n_classes=8)
    mesh = make_tp_mesh(1, 1, 1, devices=[topo.devices[0]])
    tx = optax.adam(1e-3)
    factory, _ = make_tp_train_step(mesh, cfg, optimizer=tx, causal=True,
                                    compute_dtype=jnp.bfloat16)

    def make_state(key):
        params = init_transformer_params(key, cfg)
        return params, tx.init(params)

    params, opt_state = jax.eval_shape(make_state, jax.random.PRNGKey(0))
    pspecs, ospecs, xspec, yspec = tp_step_specs(params, opt_state)

    def placed(shapes, specs):
        return jax.tree.map(
            lambda spec, a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
            specs, shapes,
            is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))

    x = jax.ShapeDtypeStruct((2, 256, 32), jnp.float32)
    y = jax.ShapeDtypeStruct((2,), jnp.int32)
    text = factory(params, opt_state).lower(
        placed(params, pspecs), placed(opt_state, ospecs),
        placed(x, xspec), placed(y, yspec)).compile().as_text()
    # a Mosaic call's instruction is named after its kernel
    kernels = sorted(
        re.match(r"\s*(?:ROOT )?%([a-z_]+)", line).group(1)
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line)
    assert kernels == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"], kernels
