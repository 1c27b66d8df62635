"""The main path's Pallas kernels, compiled at real widths by the TPU's own
compiler for a chip that is described and not attached (``v5e:2x2``).

Interpret mode accepts programs Mosaic refuses — a batched dot whose left
operand has no free dimension, a Pallas call the partitioner cannot split —
so these compiles guard every later PR at no chip time.  Nothing runs; a
compile that passes is not a chip run (``chip_smoke.py`` is).

Rules this file keeps, because the suite runs under several xdist workers:

- test ids are literal lists, the same in every process;
- nothing touches the TPU compiler at import or collection: the topology is
  asked for in a fixture, and only there may a test skip;
- one process at a time may load the TPU library unless
  ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` — set here, for the test, so that four
  workers compile side by side; a compile attaches no chip;
- the program's kernels ask ``jax.default_backend()`` whether to run
  interpreted, and here it says "cpu": the tests answer "tpu" for them
  (monkeypatch), the program has no option for it;
- the persistent compile cache is off around these compiles (an entry
  written for a described chip cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

KERNEL = "tpu_custom_call"

# (batch, length, heads, head_dim): gpt2-small's 64 and the 128 of the larger
# presets, at the training length and at the long-context cell's; the first
# and the last are the two training cells' own (gpt2s-train whole,
# c1p3b-train-x4 a chip's two rows)
FLASH_SHAPES = [(16, 1024, 12, 64), (4, 1024, 16, 128),
                (1, 8192, 12, 64), (1, 8192, 8, 128), (2, 2048, 16, 128)]
FLASH_IDS = ["D64-L1024", "D128-L1024", "D64-L8192", "D128-L8192",
             "D128-L2048"]
CELLS = [FLASH_SHAPES[0], FLASH_SHAPES[4]]
CELL_IDS = ["gpt2s-train", "c1p3b-train-x4"]
# the temporaries of one layer's forward + backward at the parent of PR 37
# (three kernels, float32 operands), compiled for the same described chip
# (at x4's shape the compiler reports none, for the parent and since)
PARENT_TEMP_BYTES = {FLASH_SHAPES[0]: 201_391_104, FLASH_SHAPES[4]: 0}

# the serving cell's pool geometry: 8 slots x 512 positions in 16-token
# blocks, a 256-row token budget
SLOTS, BLOCK, COLS, BUDGET = 8, 16, 32, 256
HEADS = {64: 12, 128: 16}


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["ALLOW_MULTIPLE_LIBTPU_LOAD"] = "1"
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever the plugin raises
        pytest.skip(f"the TPU compiler cannot describe v5e:2x2 here: {e}")


@pytest.fixture(autouse=True)
def _as_on_tpu_without_cache(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def compile_for(fn, *args):
    """Compile ``fn`` for the shardings its abstract ``args`` carry."""
    return jax.jit(fn).lower(*args).compile()


def on_one(topo, shape, dtype):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=SingleDeviceSharding(topo.devices[0]))


def flash_loss(mesh=None):
    from paddle_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               mesh=mesh).astype(jnp.float32).sum()
    return loss


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=FLASH_IDS)
def test_flash_forward_compiles(v5e, shape):
    q = on_one(v5e, shape, jnp.bfloat16)
    compiled = compile_for(flash_loss(), q, q, q)
    assert compiled.as_text().count(KERNEL) == 1


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=FLASH_IDS)
def test_flash_backward_compiles(v5e, shape):
    from paddle_tpu.ops.attention import flash_plan
    q = on_one(v5e, shape, jnp.bfloat16)
    compiled = compile_for(jax.grad(flash_loss(), argnums=(0, 1, 2)),
                           q, q, q)
    # forward and the fused backward; or forward, dQ, dK/dV where a head's
    # float32 dQ does not fit the plan's VMEM budget (8,192 rows)
    fused = flash_plan(shape[1], shape[3], True, jnp.bfloat16).form == "fused"
    assert fused == (shape[1] != 8192)
    assert compiled.as_text().count(KERNEL) == (2 if fused else 3)


@pytest.mark.parametrize("shape", CELLS, ids=CELL_IDS)
def test_flash_backward_is_one_kernel_at_the_cells_shapes(v5e, shape):
    """Both training cells' shapes: ONE backward kernel (five products),
    named, and the program holds no more temporaries than the parent's
    three kernels did — the gradients take their operands' buffers."""
    q = on_one(v5e, shape, jnp.bfloat16)
    compiled = compile_for(jax.grad(flash_loss(), argnums=(0, 1, 2)),
                           q, q, q)
    names = kernel_op_names(compiled.as_text())
    assert sorted(n.split("/")[-2] for n in names) == [
        "flash_attention_bwd", "flash_attention_fwd"]
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= PARENT_TEMP_BYTES[shape]


@pytest.mark.parametrize("dtype,causal,masked,dropout_p", [
    ("float32", True, False, 0.0),      # float32 products stay float32
    ("bfloat16", False, True, 0.1),     # BERT: padding mask, dropout
    ("bfloat16", True, True, 0.1)],
    ids=["float32-causal", "bert-mask-dropout", "causal-mask-dropout"])
def test_flash_variants_compile(v5e, dtype, causal, masked, dropout_p):
    from paddle_tpu.ops.attention import flash_attention
    shape = (4, 1024, 12, 64)
    q = on_one(v5e, shape, jnp.dtype(dtype))
    kmask = on_one(v5e, shape[:2], jnp.float32)
    seed = on_one(v5e, (), jnp.uint32)

    def loss(q, k, v, kmask, seed):
        return flash_attention(
            q, k, v, causal=causal, key_mask=kmask if masked else None,
            dropout_p=dropout_p, dropout_seed=seed if dropout_p else None,
        ).astype(jnp.float32).sum()

    compiled = compile_for(jax.grad(loss, argnums=(0, 1, 2)),
                           q, q, q, kmask, seed)
    assert compiled.as_text().count(KERNEL) == 2


def test_flash_under_dp2_mp2_mesh_compiles(v5e):
    """GSPMD cannot partition a Pallas call: under a mesh the kernels sit in
    a shard_map, each device on its own batch rows and heads."""
    mesh = Mesh(np.array(v5e.devices).reshape(2, 2), ("data", "model"))
    q = jax.ShapeDtypeStruct(
        FLASH_SHAPES[0], jnp.bfloat16,
        sharding=NamedSharding(mesh, P("data", None, "model", None)))
    compiled = compile_for(jax.grad(flash_loss(mesh), argnums=(0, 1, 2)),
                           q, q, q)
    assert compiled.as_text().count(KERNEL) == 2    # forward, backward
    with pytest.raises(Exception, match="shard_map"):
        compile_for(jax.grad(flash_loss(None), argnums=(0, 1, 2)), q, q, q)


def test_zero3_step_gathers_one_layer_for_v5e_2x2(v5e):
    """The ZeRO-3 GPT step for the four described chips (12 layers, and no
    other dim of it is 12; widths a fraction of the 1.3B cell's, whose own
    compile is ``benchmarks/tools/compile_real.py c1p3b-train-x4``): no
    all-gather inside the layer scan's loops has the layer count among
    its dims — a layer an iteration, never the stack — and the block
    gradients are reduced there (this compiler writes a reduce-scatter as
    an ``all-reduce-scatter`` fusion round an ``all-reduce``)."""
    from paddle_tpu.core import rng
    from paddle_tpu.distributed import spmd
    from paddle_tpu.distributed.sharding_rules import loop_collectives
    from paddle_tpu.models.gpt import GPTConfig, GPTModel
    from paddle_tpu.optimizer import AdamW

    L, H, I, B, S = 12, 256, 1024, 8, 1024
    cfg = GPTConfig(vocab_size=2048, hidden_size=H, num_layers=L,
                    num_attention_heads=2, intermediate_size=I,
                    max_position_embeddings=S, compute_dtype="bfloat16")
    mesh = Mesh(np.array(v5e.devices).reshape(1, 4), ("data", "sharding"))
    optimizer = AdamW(3e-4, weight_decay=0.01)
    holder = {}

    def init_state(key):
        with rng.rng_scope(key):
            holder["model"] = GPTModel(cfg)
        params = {n: p._data for n, p in holder["model"].named_parameters()}
        return {"params": params, "opt": optimizer.init_state(params),
                "buffers": {}}

    state_abs = jax.eval_shape(init_state, jax.random.key(0))
    model = holder["model"]

    def loss_of(params, key, x, labels):
        h = model.embed_fn(params, x, key)
        h = model.scan_blocks(params, h, key, remat="dots", mesh=mesh)
        return model.head_loss_fn(params, h, labels)

    p_specs = spmd.build_param_specs(state_abs["params"], mesh, model, 3)
    state_sh = spmd.build_state_shardings(state_abs, p_specs, mesh, 3,
                                          state_abs["params"])
    step = spmd._make_gspmd_step(loss_of, optimizer, mesh, p_specs, True)
    rep = NamedSharding(mesh, P())
    abstract = lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=sh)
    state = jax.tree.map(abstract, state_abs, state_sh)
    key = abstract(jax.eval_shape(lambda: jax.random.key(0)), rep)
    ids = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=rep)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    text = step.lower(state, lr, key, ids, ids).compile().as_text()

    rows = loop_collectives(text)
    gathers = [d for r in rows if r["op"] == "all-gather" for d in r["dims"]]
    assert gathers and all(L not in d for d in gathers), gathers
    assert {(H, 3 * H), (H, I)} <= {d[-2:] for d in gathers}
    reduced = {d[-2:] for r in rows if r["op"] == "all-reduce"
               for d in r["dims"] if r["computation"].startswith(
                   "all-reduce-scatter")}
    assert {(H, 3 * H), (H, I)} <= reduced, reduced
    assert text.count(KERNEL) == 3      # flash forward (twice: remat), backward


# the docs cell's own tick (benchmarks/traffic/docs-backlog.json): a
# 512-row budget over 14 slots of 2,048 positions, 1,900 blocks, and
# Cerebras-GPT 1.3B's 24 layers of 16 heads of 128
DOCS = dict(slots=14, cols=128, budget=512, blocks=1901, layers=24)


def pool_args(topo, hd, int8, slots=SLOTS, cols=COLS, blocks=None):
    nh = HEADS[hd]
    n_blocks = blocks or slots * cols + 1
    if int8:
        vals = on_one(topo, (n_blocks, BLOCK, nh, hd), jnp.int8)
        scales = on_one(topo, (n_blocks, BLOCK, nh), jnp.float32)
        pool = (vals, scales)
    else:
        pool = on_one(topo, (n_blocks, BLOCK, nh, hd), jnp.bfloat16)
    table = on_one(topo, (slots, cols), jnp.int32)
    per_slot = on_one(topo, (slots,), jnp.int32)
    return nh, pool, table, per_slot


def whole_pool_copies(text, pool):
    """Lines of compiled HLO that copy or transpose at least one layer of
    a pool (its largest leaf's elements)."""
    import re
    least = max(int(np.prod(p.shape[-4:])) for p in jax.tree.leaves(pool))
    hits = []
    for line in text.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\][^ ]* (copy|transpose|copy-start)\(",
                      line)
        if m and np.prod([int(d) for d in m.group(1).split(",")]) >= least:
            hits.append(line.strip()[:160])
    return hits


@pytest.mark.parametrize("hd", [64, 128], ids=["hd64", "hd128"])
def test_paged_decode_compiles(v5e, hd):
    from paddle_tpu.models._decode import PagedKV, cached_attention
    nh, pool, table, per_slot = pool_args(v5e, hd, int8=False)
    q = on_one(v5e, (SLOTS, 1, nh, hd), jnp.bfloat16)

    def decode(q, pk, pv, table, t, pad):
        return cached_attention(q, PagedKV(pk, table), PagedKV(pv, table),
                                t, pad_lens=pad)

    compiled = compile_for(decode, q, pool, pool, table, per_slot, per_slot)
    assert KERNEL in compiled.as_text()


@pytest.mark.parametrize("layers", [None, 24], ids=["one-layer", "stack"])
@pytest.mark.parametrize("hd,int8,cell", [
    (64, False, None), (64, True, None), (128, False, None),
    (128, True, None), (128, False, DOCS)],
    ids=["hd64-bf16", "hd64-int8", "hd128-bf16", "hd128-int8", "docs"])
def test_ragged_paged_compiles(v5e, hd, int8, cell, layers):
    """One layer's pools, or a stack of 24 addressed by a traced layer
    index, in every form the kernel takes from the shapes (strided heads
    and VPU rows, int8 quads with gathered scales, lane-slice heads), and
    at the docs cell's own shape: the pools reach the kernel as they are
    stored, no layer of them is sliced out, copied or transposed."""
    from paddle_tpu.models._decode import ragged_attention
    geometry = {k: cell[k] for k in ("slots", "cols", "blocks")} \
        if cell else {}
    budget = cell["budget"] if cell else BUDGET
    nh, pool, table, per_slot = pool_args(v5e, hd, int8, **geometry)
    q = on_one(v5e, (budget, nh, hd), jnp.bfloat16)
    per_row = on_one(v5e, (budget,), jnp.int32)
    layer = ()
    if layers:
        pool = jax.tree.map(
            lambda p: on_one(v5e, (layers,) + p.shape, p.dtype), pool)
        layer = (on_one(v5e, (), jnp.int32),)
    compiled = compile_for(ragged_attention, q, pool, pool, table, per_row,
                           per_row, per_slot, *layer)
    text = compiled.as_text()
    assert text.count(KERNEL) == 1
    # a (16, 128) tail is whole tiles and the stack is read where it lies.
    # gpt2-small's (12, 64) is not: the device keeps such an array in
    # another dimension order than any kernel's row-major view, and the
    # compiler copies it (one layer's pool or the stack's alike; 0.3 GB of
    # bfloat16 here, where the (T, C)-grid kernel's padded copies were
    # 0.8): PERF.md section 7
    if hd == 128:
        assert not whole_pool_copies(text, pool)
        assert compiled.memory_analysis().temp_size_in_bytes < 100e6


@pytest.mark.parametrize("rows", [8, 16])
@pytest.mark.parametrize("hd", [64, 128], ids=["hd64", "hd128"])
def test_ragged_paged_compiles_at_a_decode_rounds_rows(v5e, hd, rows):
    """A pack narrower than one 128-row grid step — the engine's program
    for rounds of decode rows only, its slots rounded up to 8 — is one
    grid step of that many rows, at gpt2-small's lane-slice form (hd 64,
    the smoke's 8 slots) as at the docs cell's geometry (hd 128, 14
    slots, 128 columns, 24 layers read in place)."""
    from paddle_tpu.models._decode import ragged_attention
    geometry = {k: DOCS[k] for k in ("slots", "cols", "blocks")} \
        if hd == 128 else {}
    nh, pool, table, per_slot = pool_args(v5e, hd, False, **geometry)
    pool = on_one(v5e, (DOCS["layers"],) + pool.shape, pool.dtype)
    q = on_one(v5e, (rows, nh, hd), jnp.bfloat16)
    per_row = on_one(v5e, (rows,), jnp.int32)
    compiled = compile_for(ragged_attention, q, pool, pool, table, per_row,
                           per_row, per_slot, on_one(v5e, (), jnp.int32))
    text = compiled.as_text()
    assert text.count(KERNEL) == 1
    if hd == 128:
        assert not whole_pool_copies(text, pool)
        assert compiled.memory_analysis().temp_size_in_bytes < 100e6


@pytest.mark.parametrize("rows", [BUDGET, 8], ids=["budget", "narrow"])
@pytest.mark.parametrize("hidden,heads,vocab", [(768, 12, 50304),
                                                (2048, 16, 8192)],
                         ids=["hd64", "hd128"])
def test_ragged_serving_step_compiles(v5e, hidden, heads, vocab, rows):
    """The engine's whole tick at gpt2-small width and at Cerebras-GPT
    1.3B's (depth cut to two layers, the second one's vocabulary cut
    too): embed, scatter into the pools, ragged kernel, sampler — at the
    budget's rows and at the 8 rows of the program that 8 slots' rounds
    of decode rows run (``chip_smoke.py`` serves the first at hd 64)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTModel
    from paddle_tpu.serving import RaggedPagedContinuousBatchingEngine
    paddle.seed(0)
    model = GPTModel(GPTConfig(
        vocab_size=vocab, hidden_size=hidden, num_layers=2,
        num_attention_heads=heads, max_position_embeddings=1024,
        compute_dtype="bfloat16"))
    # weights as the cells serve them: float32 ones are cast once for the
    # whole stack, and that cast would be the program's temporaries
    params = {n: p._data.astype(jnp.bfloat16)
              for n, p in model.named_parameters()}
    eng = RaggedPagedContinuousBatchingEngine(
        model, params, max_slots=SLOTS, max_len=BLOCK * COLS,
        block_size=BLOCK, prompt_buckets=[64, 128], token_budget=BUDGET)
    assert eng.narrow_rows == 8 and eng.MB == COLS
    args = jax.tree.map(lambda x: on_one(v5e, x.shape, x.dtype),
                        eng._ragged_scratch_args(COLS, rows))
    compiled = eng._build_ragged_step(rows, COLS).lower(*args).compile()
    assert KERNEL in compiled.as_text()
    # the tick holds its pools once: both are donated into the outputs,
    # and no second copy of a side (nor a layer of one) is a temporary.
    # (At hd 64 the compiler still re-orders both pools for the kernel,
    # once a tick: see test_ragged_paged_compiles.)
    side = min(x.nbytes for x in eng.caches)
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= sum(x.nbytes for x in eng.caches)
    if hidden // heads == 128:
        assert ma.temp_size_in_bytes < side // 2, (ma.temp_size_in_bytes,
                                                   side)


def test_narrow_gpt_tick_compiles_at_the_docs_cells_shape(v5e):
    """The program ``c1p3b-serve-docs`` runs a round of 14 decode rows
    through: the configuration file, the traffic file's engine, the
    tick at 16 rows over the widest table (128 columns), all 24 layers.
    Both pools are donated and held once; what the round reads is the
    weights and its rows' keys, so the temporaries are small."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.lib import harness, program, weights
    cfg = harness.load_json("configs", "cerebras-gpt-1.3b.json")
    eng = harness.load_json("traffic", "docs-backlog.json")["engine"]
    params = {n: on_one(v5e, shape, jnp.bfloat16)
              for n, (shape, _) in weights.gpt_param_table(cfg).items()}
    engine = program.build_engine(cfg, dict(eng, num_blocks=1), {}, None)
    engine.NB = eng["num_blocks"]
    assert (engine.narrow_rows, engine.MB) == (16, DOCS["cols"])
    assert engine.compile_grid()[-1] == "ragged_step:16:128"
    args = jax.eval_shape(
        lambda: engine._ragged_scratch_args(engine.MB, engine.narrow_rows))
    args = jax.tree.map(
        lambda a: on_one(v5e, a.shape, a.dtype) if hasattr(a, "shape")
        else a, (params,) + tuple(args[1:]))
    with jax.default_matmul_precision("default"):
        compiled = engine._build_ragged_step(
            engine.narrow_rows, engine.MB).lower(*args).compile()
    ma = compiled.memory_analysis()
    pools = 2 * DOCS["layers"] * DOCS["blocks"] * BLOCK * 2048 * 2
    assert ma.alias_size_in_bytes >= pools
    assert ma.temp_size_in_bytes < 100e6, ma
    assert compiled.as_text().count(KERNEL) == 1    # one rolled layer


@pytest.mark.parametrize("cols", [1, 8, 256, 1040],
                         ids=["C1", "C8", "C256", "C1040"])
def test_ragged_latent_compiles(v5e, cols):
    """The latent (MLA) kernel at the published widths — 128 heads, a
    512 + 64 latent in a 640-wide row, 16-token blocks, 2,048 rows — over
    a layer of a five-layer stack addressed in place, at table widths
    from one block to the long-document cell's 1,040 (not a power of
    two): up to 16 block specs of one pool in a grid step."""
    from paddle_tpu.models._decode import ragged_latent_attention
    T, nh, slots = 2048, 128, 16
    pool = on_one(v5e, (5, slots * 1040 + 1, BLOCK, 640), jnp.bfloat16)
    per_row = on_one(v5e, (T,), jnp.int32)

    def attend(qa, qr, pool, table, seq, pos, pad, layer):
        return ragged_latent_attention(qa, qr, pool, table, seq, pos, pad,
                                       scale=192 ** -0.5, layer=layer)

    compiled = compile_for(
        attend, on_one(v5e, (T, nh, 512), jnp.bfloat16),
        on_one(v5e, (T, nh, 64), jnp.bfloat16), pool,
        on_one(v5e, (slots, cols), jnp.int32), per_row, per_row,
        on_one(v5e, (slots,), jnp.int32), on_one(v5e, (), jnp.int32))
    text = compiled.as_text()
    assert KERNEL in text
    assert kernel_op_names(text)[0].endswith(
        "ragged_latent_attention/ragged_latent_attention/pallas_call") \
        or "ragged_latent_attention" in kernel_op_names(text)[0]
    # the pool reaches the kernel as it is stored: no copy of it is made
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("cols", [256, 3104], ids=["C256", "C3104"])
def test_sparse_attention_kernels_compile(v5e, cols):
    """DeepSeek sparse attention's three steps at the published widths and
    the long-context cell's shape — a 64-head x 128 indexer over its own
    paged key pool (128 numbers a token: one lane tile), the exact
    top-2048 threshold search over up to 49,664 scores a row, absorbed
    attention over the selected of a 640-wide latent pool — 2,048 rows, 6
    slots, a layer of a five-layer stack addressed in place.  Neither
    pool is copied, and each kernel is called under its own name inside
    its region."""
    from paddle_tpu.models._decode import (ragged_index_select,
                                           ragged_sparse_latent_attention)
    T, nh, slots = 2048, 128, 6
    blocks = slots * 3104 + 1
    latent = on_one(v5e, (5, blocks, BLOCK, 640), jnp.bfloat16)
    keys = on_one(v5e, (5, blocks, BLOCK, 128), jnp.bfloat16)
    per_row = on_one(v5e, (T,), jnp.int32)

    def attend(qa, qr, qi, wi, latent, keys, table, seq, pos, pad, layer):
        chosen = ragged_index_select(qi, wi, keys, table, seq, pos, pad,
                                     k=2048, layer=layer)
        return ragged_sparse_latent_attention(
            qa, qr, latent, *chosen, table, seq, pos, pad,
            scale=192 ** -0.5 * 1.87385, layer=layer)

    compiled = compile_for(
        attend, on_one(v5e, (T, nh, 512), jnp.bfloat16),
        on_one(v5e, (T, nh, 64), jnp.bfloat16),
        on_one(v5e, (T, 64, 128), jnp.bfloat16),
        on_one(v5e, (T, 64), jnp.float32), latent, keys,
        on_one(v5e, (slots, cols), jnp.int32), per_row, per_row,
        on_one(v5e, (slots,), jnp.int32), on_one(v5e, (), jnp.int32))
    names = kernel_op_names(compiled.as_text())
    assert len(names) == 3
    for name, region in zip(names, ("indexer/ragged_index_scores", "select",
                                    "ragged_sparse_latent_attention")):
        assert region in name, names
    # scores (T, cols * 16) float32 and the head weights' column are the
    # temporaries; neither pool (1.9 GB and 0.4 GB) is among them
    assert compiled.memory_analysis().temp_size_in_bytes \
        < T * cols * BLOCK * 4 + (192 << 20)


LATENT_CELLS = {
    # cell: (configuration, traffic, the lib of its driver, its weights,
    #        pool bytes a block position, kernels called once a stack)
    "longdocs": ("openpangu-ultra-moe-718b-ep16.json",
                 "longdocs-backlog.json", "serve_latent", "weights_pangu",
                 640 * 2, ("ragged_latent_attention/",)),
    "longctx": ("deepseek-v3.2-exp-ep16.json", "longctx-backlog.json",
                "serve_sparse", "weights_dsv32", (640 + 128) * 2,
                ("ragged_index_scores", "select/",
                 "ragged_sparse_latent_attention")),
}


@pytest.mark.parametrize("rows", ["budget", "narrow"])
@pytest.mark.parametrize("cell", ["longdocs", "longctx"])
def test_latent_serving_ticks_compile_at_the_cells_shapes(v5e, cell, rows):
    """The whole tick of ``pangu-serve-longdocs`` and of
    ``dsv32-serve-longctx`` — the configuration file, the traffic file's
    engine, the program's own tick builder — for a described v5e, at the
    budget's 2,048 rows and at the rows of the program that rounds of
    decode rows run (16 and 8): it fits (arguments + temporaries under
    15.0 GB), holds each pool once, and calls its kernels in both stacks.

    And the expert layer's weights are not copied out of their stacks
    (PR 46): the program has no ``conditional`` — ``_decode.rowwise``'s,
    which made every array its branches closed over an operand buffer, is
    gone with the function — and nothing outside a fused computation has
    the shape of a layer's slice of an expert stack: sliced by the layer
    scan each of the three was the operand buffer of its grouped product,
    1.4-1.5 GB a layer a round together; ``held_experts_ffn``'s
    ``layer=`` reads a layer's experts in place."""
    import importlib
    import re
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.lib import harness
    config, traffic, lib, weights, row_bytes, kernels = LATENT_CELLS[cell]
    lib, weights = (importlib.import_module(f"benchmarks.lib.{m}")
                    for m in (lib, weights))
    cfg = harness.load_json("configs", config)
    eng = harness.load_json("traffic", traffic)["engine"]
    table = weights.param_table(cfg)
    params = {n: on_one(v5e, shape, jnp.bfloat16)
              for n, (shape, _) in table.items()}
    engine = lib.build_engine(cfg, dict(eng, num_blocks=1), {}, None)
    engine.NB = eng["num_blocks"]
    C = eng["max_len"] // eng["block_size"]
    assert C == engine.MB
    assert engine.narrow_rows == -(-eng["max_slots"] // 8) * 8
    T = eng["token_budget"] if rows == "budget" else engine.narrow_rows
    args = jax.eval_shape(lambda: engine._ragged_scratch_args(C, T))
    args = jax.tree.map(
        lambda a: on_one(v5e, a.shape, a.dtype) if hasattr(a, "shape")
        else a, (params,) + tuple(args[1:]))
    # at the precision the chip runs with: the suite asks for "highest"
    # (conftest.py, for its numpy oracles), and the compiler's own kernel
    # for the experts' grouped product refuses a float32 product of
    # bfloat16 operands
    with jax.default_matmul_precision("default"):
        compiled = engine._build_ragged_step(T, C).lower(*args).compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 15.0e9, ma
    pools = cfg["num_hidden_layers"] * (eng["num_blocks"] + 1) * 16 \
        * row_bytes
    assert ma.alias_size_in_bytes >= pools          # donated, held once
    text = compiled.as_text()
    names = kernel_op_names(text)
    for stem in kernels:
        assert sum(stem in n for n in names) == 2, (stem, names)
    assert not re.search(r"\bconditional\(", text)
    experts = {shape[1:] for n, (shape, _) in table.items()
               if n.startswith("moe_e_")}
    H, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    assert experts == {(16, H, F), (16, F, H)}
    assert not materialised(text, experts)


def kernel_op_names(text):
    """The ``op_name`` of every Pallas kernel call in compiled HLO text."""
    import re
    return [re.search(r'op_name="([^"]*)"', line).group(1)
            for line in text.splitlines()
            if KERNEL in line and "custom-call(" in line]


def test_flash_kernels_are_called_under_their_own_name(v5e):
    """A trace finds a kernel by the region and the name of its call, not
    by the file it lives in: forward and backward say ``flash_attention``,
    the backward on the transposed path."""
    q = on_one(v5e, FLASH_SHAPES[1], jnp.bfloat16)
    names = kernel_op_names(compile_for(
        jax.grad(flash_loss(), argnums=(0, 1, 2)), q, q, q).as_text())
    assert len(names) == 2
    # the region reaches the call inside the wrapper that transformed it:
    # jvp(flash_attention)/..., transpose(jvp(flash_attention))/...
    assert all("(flash_attention)" in n for n in names)
    assert sorted(n.split("/")[-2] for n in names) == [
        "flash_attention_bwd", "flash_attention_fwd"]
    assert sum("transpose(" in n for n in names) == 1


def test_flash_split_kernels_keep_their_names(v5e):
    """Where the plan keeps two backward kernels (a head's dQ over the
    VMEM budget) they are the dQ and dK/dV calls a trace knew before."""
    q = on_one(v5e, FLASH_SHAPES[3], jnp.bfloat16)
    names = kernel_op_names(compile_for(
        jax.grad(flash_loss(), argnums=(0, 1, 2)), q, q, q).as_text())
    assert sorted(n.split("/")[-2] for n in names) == [
        "flash_attention_dkv", "flash_attention_dq", "flash_attention_fwd"]
    assert sum("transpose(" in n for n in names) == 2


def test_ragged_kernel_is_called_under_its_own_name(v5e):
    from paddle_tpu.models._decode import ragged_attention
    nh, pool, table, per_slot = pool_args(v5e, 128, int8=False)
    q = on_one(v5e, (BUDGET, nh, 128), jnp.bfloat16)
    per_row = on_one(v5e, (BUDGET,), jnp.int32)
    names = kernel_op_names(compile_for(
        ragged_attention, q, pool, pool, table, per_row, per_row,
        per_slot).as_text())
    assert names and all(
        "/ragged_paged_attention/ragged_paged_attention/" in n
        for n in names)


@pytest.mark.parametrize("rows,cols", [(2176, 128), (2176, 8), (6, 128),
                                       (8, 128), (16, 128)],
                         ids=["chunk-C128", "chunk-C8", "decode",
                              "narrow-8", "narrow-16"])
def test_eva_kernels_compile_at_real_widths(v5e, rows, cols):
    """The two kernels of EVA attention at EvaByte's widths (32 heads of
    128, a 2,048-row window, 16-row chunks, bfloat16): a window leaf that
    does not page and a summary leaf paged by chunk, whole stacks read in
    place by layer."""
    from paddle_tpu.models._decode import (eva_summarize,
                                           ragged_eva_attention)
    L, S, W, nh, hd, bs, NB = 16, 6, 2048, 32, 128, 16, 768
    win = on_one(v5e, (L, S, W, nh, hd), jnp.bfloat16)
    sums = on_one(v5e, (L, NB + 1, bs, nh, hd), jnp.bfloat16)
    q = on_one(v5e, (rows, nh, hd), jnp.bfloat16)
    per_row = on_one(v5e, (rows,), jnp.int32)
    table = on_one(v5e, (S, cols), jnp.int32)
    layer = on_one(v5e, (), jnp.int32)

    def attend(q, wk, wv, sk, sv, table, seq, pos, layer):
        return ragged_eva_attention(q, (wk, wv), (sk, sv), table, seq, pos,
                                    chunk=16, scale=hd ** -0.5, layer=layer)
    compiled = compile_for(attend, q, win, win, sums, sums, table, per_row,
                           per_row, layer)
    names = kernel_op_names(compiled.as_text())
    assert names and all(
        "/ragged_eva_attention/ragged_eva_attention/" in n for n in names)
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6

    n = rows // 16 + S
    vec = on_one(v5e, (nh, hd), jnp.bfloat16)
    closed = on_one(v5e, (n,), jnp.int32)

    def close(wk, wv, phi, mu, seq, at, layer):
        return eva_summarize(wk, wv, phi, mu, seq, at, chunk=16,
                             scale=hd ** -0.5, layer=layer)
    names = kernel_op_names(compile_for(
        close, win, win, vec, vec, closed, closed, layer).as_text())
    assert names and all("/eva_summarize/eva_summarize/" in n
                         for n in names)


def materialised(text, shapes):
    """Instructions of compiled HLO OUTSIDE the fused computations whose
    result has one of ``shapes`` (leading 1s aside): arrays the program
    writes to memory, as a layer's weight copied out of its stack is."""
    import re
    hits, fused = [], False
    for line in text.splitlines():
        if line and not line.startswith(" "):       # a computation opens
            fused = line.startswith("%fused_computation")
        m = re.match(r"\s+(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]+)\]", line)
        if not m or fused or " parameter(" in line:
            continue
        dims = tuple(int(d) for d in m.group(2).split(","))
        while len(dims) > 1 and dims[0] == 1:
            dims = dims[1:]
        if dims in shapes:
            hits.append(line.strip()[:120])
    return hits


@pytest.mark.parametrize("rows", ["budget", "narrow"])
def test_longcat_serving_tick_compiles_at_the_cells_shape(v5e, rows):
    """The whole tick of ``longcat-serve-toolturns`` — the configuration
    file, the traffic file's engine, the program's own tick builder — for
    a described v5e, at the budget's 2,048 rows and at the 16 rows of the
    program that rounds of decode rows run: it fits (arguments +
    temporaries under 15.0 GB; 13.42 and 12.23), holds its ONE pool leaf
    of eight sublayers once, and calls the latent kernel twice (one rolled
    layer of two sublayers).

    And no layer's large weights are copied out of their stacks: the
    program has no ``conditional``, and nothing outside a fused
    computation has the shape of a layer's expert stack (sliced by the
    layer scan, each of the three is a 403 MB operand buffer of its
    grouped product, written in every round: ``held_experts_ffn``'s
    ``layer=`` reads it in place) or of a sublayer's dense-FFN matrix,
    ``W_o`` or ``W_qa``, alone or as the pair a layer holds (sliced by
    the scan as pairs they were copied too, 1.27 GB a layer a round and
    11% of the device's time on the chip: the layer indexes the flat
    stack at ``2 l + j`` itself).  What remains is the transposing copy
    of ``W_qb`` and ``W_kvb`` (55 MB a sublayer), which the Pangu tick
    has too."""
    import re
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.lib import harness, serve_scmoe, weights_longcat
    cfg = harness.load_json("configs", "longcat-flash-chat-ep32.json")
    eng = harness.load_json("traffic", "toolturns-backlog.json")["engine"]
    table = weights_longcat.param_table(cfg)
    params = {n: on_one(v5e, shape, jnp.bfloat16)
              for n, (shape, _) in table.items()}
    engine = serve_scmoe.build_engine(
        cfg, dict(eng, num_blocks=1, max_slots=1), {}, None)
    engine.NB, engine.S = eng["num_blocks"], eng["max_slots"]
    engine.narrow_rows = -(-eng["max_slots"] // 8) * 8
    C = eng["max_len"] // eng["block_size"]
    assert C == engine.MB == 520 and engine.narrow_rows == 16
    T = eng["token_budget"] if rows == "budget" else engine.narrow_rows
    assert eng["token_budget"] > 2 * engine.narrow_rows
    args = jax.eval_shape(lambda: engine._ragged_scratch_args(C, T))
    args = jax.tree.map(
        lambda a: on_one(v5e, a.shape, a.dtype) if hasattr(a, "shape")
        else a, (params,) + tuple(args[1:]))
    with jax.default_matmul_precision("default"):
        compiled = engine._build_ragged_step(T, C).lower(*args).compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 15.0e9, ma
    pool = 8 * (eng["num_blocks"] + 1) * 16 * 640 * 2
    assert ma.alias_size_in_bytes >= pool           # donated, held once
    text = compiled.as_text()
    names = kernel_op_names(text)
    stem = "ragged_latent_attention"
    assert sum(f"/{stem}/" in n for n in names) == 2, names
    assert not re.search(r"\bconditional\(", text)
    experts = {(16, 6144, 2048), (16, 2048, 6144)}
    assert {shape[1:] for n, (shape, _) in table.items()
            if n.startswith("layers_e_")} == experts
    own = {(6144, 12288), (12288, 6144), (8192, 6144), (6144, 1536)}
    assert own <= {shape[2:] for n, (shape, _) in table.items()}
    own |= {(2,) + s for s in own}
    assert not materialised(text, experts | own)
    assert ma.temp_size_in_bytes < (1.9e9 if rows == "budget" else 0.6e9)



@pytest.mark.parametrize("rows", ["budget", "narrow"])
def test_eva_serving_tick_compiles_at_the_cells_shape(v5e, rows):
    """The whole tick of ``evabyte-serve-bytedocs`` — the configuration
    file, the traffic file's engine, the program's own tick builder — for
    a described v5e, at the budget's 2,176 rows and at the 8 rows of the
    program that rounds of 6 decode rows run: it fits (arguments +
    temporaries under 15.0 GB), holds both leaves once, and calls each
    kernel once (one rolled layer).

    And no layer's weights are copied out of their stack: the program has
    no ``conditional`` (an array a branch closes over is an operand of
    it, and an operand is a buffer: PR 28 to PR 38 paid 0.4 GB a layer a
    round for ``_decode.rowwise``), nothing outside a fused computation
    has a weight matrix's shape (the QKV product reshaped before it was
    cut into thirds cost a transposed copy of ``qkv_w`` a layer), and the
    temporaries stay under 0.30 GB (0.389 with the branch, 0.132 now)."""
    import re
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.lib import harness, serve_eva, weights_evabyte
    cfg = harness.load_json("configs", "evabyte-6.5b-pp2.json")
    eng = harness.load_json("traffic", "bytedocs-backlog.json")["engine"]
    table = weights_evabyte.param_table(cfg)
    params = {n: on_one(v5e, shape, jnp.bfloat16)
              for n, (shape, _) in table.items()}
    engine = serve_eva.build_engine(
        cfg, dict(eng, num_blocks=1, max_slots=1), {}, None)
    assert engine.narrow_rows == 8      # 1 slot or the cell's 6: 8 rows
    engine.NB, engine.S = eng["num_blocks"], eng["max_slots"]
    C = eng["max_len"] // serve_eva.block_positions(cfg, eng)
    assert C == engine.MB == 128
    T = eng["token_budget"] if rows == "budget" else engine.narrow_rows
    assert eng["token_budget"] > 2 * engine.narrow_rows
    args = jax.eval_shape(lambda: engine._ragged_scratch_args(C, T))
    args = jax.tree.map(
        lambda a: on_one(v5e, a.shape, a.dtype) if hasattr(a, "shape")
        else a, (params,) + tuple(args[1:]))
    with jax.default_matmul_precision("default"):
        compiled = engine._build_ragged_step(T, C).lower(*args).compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 15.0e9, ma
    row = 2 * 32 * 128 * 2              # K and V of a row, bfloat16
    leaves = 16 * row * (eng["max_slots"] * 2048
                         + (eng["num_blocks"] + 1) * 16)
    assert ma.alias_size_in_bytes >= leaves         # donated, held once
    text = compiled.as_text()
    names = kernel_op_names(text)
    for stem in ("ragged_eva_attention", "eva_summarize"):
        assert sum(f"/{stem}/{stem}/" in n for n in names) == 1, names
    assert not re.search(r"\bconditional\(", text)
    weights = {shape[1:] for n, (shape, _) in table.items()
               if n.startswith("blocks_") and np.prod(shape[1:]) * 2 > 1e6}
    assert len(weights) == 4            # QKV, W_o, gate = up, down
    assert not materialised(text, weights | {s[::-1] for s in weights})
    assert ma.temp_size_in_bytes < 0.30e9, ma
