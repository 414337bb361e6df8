"""The Pallas kernels a CLI choice can reach, compiled by the installed TPU
compiler for a DESCRIBED v5e:2x2 topology — no chip attached, nothing runs.

Interpret mode (tests/test_pallas_attention.py) pins the math but not what
Mosaic accepts: the prefill kernel passed every interpret test while the v5e
compiler refused it at real head shapes (20.25 MiB of scoped VMEM against a
16 MiB limit). These compiles are the guard: real head shapes, real page
sizes, both pool dtypes, and the shard_map placement tp>1 uses. A compile
that passes is not a chip run — chip_smoke.py is.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from vllm_production_stack_tpu.ops import paged_attention_pallas as pk
from vllm_production_stack_tpu.parallel import mesh as mesh_lib

# (num_heads, num_kv_heads, head_dim) of the registry presets
HEADS = {"qwen3-8b": (32, 8, 128), "llama-1b": (32, 8, 64),
         "qwen2-7b": (28, 4, 128), "olmoe-1b-7b": (16, 16, 128),
         # what one device runs under shard_map at tp=4
         "qwen3-8b/tp4": (8, 2, 128), "qwen2-7b/tp4": (7, 1, 128)}
POOL_BLOCKS = 1024
CTX = 4096  # the context 'auto' starts choosing the decode kernel at
BATCH, WINDOW, CHUNK = 8, 8, 512


@pytest.fixture(scope="module")
def topo():
    """The described topology, with the persistent compilation cache off
    around the module: a compile for an absent chip is written to the cache
    but cannot be read back, and the next one warns about it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"v5e:2x2 topology cannot be described here: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _decode_args(sds, model, block, kv_dtype, specs, nb=None):
    nh, kvh, d = HEADS[model]
    nb = nb or CTX // block
    q, pool, b2, b1, staged, rep = specs
    return (
        sds((BATCH, nh, d), jnp.bfloat16, q),
        sds((2, POOL_BLOCKS, block, kvh, d), kv_dtype, pool),
        sds((BATCH, nb), jnp.int32, b2),
        sds((BATCH,), jnp.int32, b1),
        sds((WINDOW, BATCH, kvh, d), jnp.bfloat16, staged),
        sds((WINDOW, BATCH, kvh, d), jnp.bfloat16, staged),
        sds((), jnp.int32, rep),
    )


def _prefill_args(sds, model, block, kv_dtype, specs, rows=1, nb=None):
    nh, kvh, d = HEADS[model]
    nb = nb or CTX // block
    q, pool, b2, b1 = specs
    return (
        sds((rows, CHUNK, nh, d), jnp.bfloat16, q),
        sds((2, POOL_BLOCKS, block, kvh, d), kv_dtype, pool),
        sds((rows, nb), jnp.int32, b2),
        sds((rows,), jnp.int32, b1),
        sds((rows,), jnp.int32, b1),
    )


def _one_chip(topo):
    """An sds(shape, dtype, spec) that ignores spec: all on the first chip."""
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype, _spec: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one
    )


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("model,block,kv_dtype,width", [
    # ids of the full-width cases as they were before the width was a case
    pytest.param("qwen3-8b", 32, "bfloat16", None,  # chip_smoke.py's served
                 id="qwen3-8b-32-bfloat16"),        # configuration
    pytest.param("qwen3-8b", 16, "bfloat16", None, id="qwen3-8b-16-bfloat16"),
    pytest.param("qwen3-8b", 32, "float8_e4m3fn", None,
                 id="qwen3-8b-32-float8_e4m3fn"),
    # D=64: pages do not fill the pool's tiles: the grid form of the kernel
    pytest.param("llama-1b", 32, "bfloat16", None, id="llama-1b-32-bfloat16"),
    pytest.param("llama-1b", 16, "float8_e4m3fn", None,
                 id="llama-1b-16-float8_e4m3fn"),
    # the benchmark's cells: 64-page tables (width_floor_blocks), both models
    ("qwen3-8b", 32, "bfloat16", 64),
    ("qwen2-7b", 32, "bfloat16", 64),
    ("qwen2-7b", 32, "bfloat16", None),
    ("qwen2-7b", 32, "float8_e4m3fn", 64),
    # tp shards: one bf16 kv head is half a tile's rows, two fp8 heads too
    # (the grid form of the kernel again)
    ("qwen3-8b/tp4", 32, "bfloat16", 64),
    ("qwen2-7b/tp4", 32, "bfloat16", 64),
    ("qwen3-8b/tp4", 32, "float8_e4m3fn", 64),
    # tables narrower than a page block (width_floor_blocks=1)
    ("qwen3-8b", 32, "bfloat16", 1),
    ("qwen3-8b", 32, "bfloat16", 2),
])
def test_decode_kernel_compiles_for_v5e(topo, model, block, kv_dtype, width):
    args = _decode_args(
        _one_chip(topo), model, block, jnp.dtype(kv_dtype), (None,) * 6,
        nb=width,
    )
    scale = HEADS[model][2] ** -0.5
    _assert_kernel(jax.jit(
        lambda *a: pk.paged_decode_attention(*a, scale=scale)
    ).lower(*args).compile())


@pytest.mark.parametrize("model,block,kv_dtype,rows,width", [
    # ids of the cases the kernel had before it walked a row's pages
    pytest.param("qwen3-8b", 32, "bfloat16", 1, None,
                 id="qwen3-8b-32-bfloat16"),
    pytest.param("qwen3-8b", 16, "float8_e4m3fn", 1, None,
                 id="qwen3-8b-16-float8_e4m3fn"),
    # the benchmark's cells: the 16 x 512 program the warm-up compiles and
    # the one-row program the window runs most, 64- and 128-page tables
    ("qwen3-8b", 32, "bfloat16", 16, 64),
    ("qwen2-7b", 32, "bfloat16", 16, 64),
    ("olmoe-1b-7b", 32, "bfloat16", 16, 64),
    ("qwen2-7b", 32, "bfloat16", 1, 128),
    ("olmoe-1b-7b", 32, "bfloat16", 1, 128),
    # 16-token pages; a tp=4 shard that still fills tiles (two bf16 heads)
    ("qwen3-8b", 16, "bfloat16", 4, 64),
    ("qwen3-8b/tp4", 32, "bfloat16", 4, 64),
])
def test_prefill_kernel_compiles_for_v5e(topo, model, block, kv_dtype, rows,
                                         width):
    args = _prefill_args(
        _one_chip(topo), model, block, jnp.dtype(kv_dtype), (None,) * 4,
        rows=rows, nb=width,
    )
    scale = HEADS[model][2] ** -0.5
    _assert_kernel(jax.jit(
        lambda *a: pk.paged_prefill_attention(*a, scale=scale)
    ).lower(*args).compile())


@pytest.mark.parametrize("kvh,kv_dtype,rows,tokens,blocks", [
    (4, "bfloat16", 4, CHUNK, 2048),   # qwen2-7b's cell: the pool XLA copied
    (8, "bfloat16", 1, CHUNK, 768),    # qwen3-8b's
    (16, "bfloat16", 2, CHUNK, 1280),  # olmoe-1b-7b's
    (4, "bfloat16", 2, 1024, 4096),    # SDAR's 1,024-token bucket
    (8, "bfloat16", 2, 2048, 1329),    # Laguna's window group, a long chunk
    (2, "bfloat16", 4, CHUNK, 1024),   # a tp=4 shard that still fills tiles
    (4, "float8_e4m3fn", 2, CHUNK, 1024),  # rows converted before the call
])
def test_kv_write_kernel_compiles_for_v5e(topo, kvh, kv_dtype, rows, tokens,
                                          blocks):
    """The chunk write kernel at the cells' pools: token spans copied HBM
    to HBM at a dynamic token offset of a page (whole (kvh, D) tiles under
    it), the pool aliased to the result — donated, the program holds no
    copy of it and no temps."""
    sds, d, bs = _one_chip(topo), 128, 32
    new = sds((tokens, kvh, d), jnp.bfloat16, None)
    i32 = lambda *shape: sds(shape, jnp.int32, None)
    compiled = jax.jit(pk.paged_kv_write, donate_argnums=0).lower(
        sds((2, blocks, bs, kvh, d), jnp.dtype(kv_dtype), None), new, new,
        i32(rows, (tokens - 1) // bs + 2), i32(rows), i32(rows), i32(rows),
    ).compile()
    _assert_kernel(compiled)
    text = compiled.as_text()
    assert "paged_kv_write" in text
    assert f"[2,{blocks},{bs},{kvh},{d}]" not in "".join(
        line for line in text.splitlines() if " copy(" in line)
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("nh,kvh,window,tiling,items", [
    (48, 8, 0, (128, 8), 2),     # Laguna-S-2.1's full layers
    (72, 8, 512, (128, 8), 3),   # ... and its window layers
    (64, 8, 0, (128, 8), 2),     # Solar-Open2's GQA layers
])
def test_long_document_prefill_kernel_compiles_for_v5e(topo, nh, kvh, window,
                                                       tiling, items):
    """The long-document cells' chunk: one row of 2,048 tokens (sixteen
    128-query tiles, a kv group's heads folded in two or three items of
    whole heads) under a 1,024-page table of 32-token pages."""
    d, bs, nb, t = 128, 32, 1024, 2048
    sds = _one_chip(topo)
    args = (
        sds((1, t, nh, d), jnp.bfloat16, None),
        sds((2, 1329, bs, kvh, d), jnp.bfloat16, None),
        sds((1, nb), jnp.int32, None), sds((1,), jnp.int32, None),
        sds((1,), jnp.int32, None),
    )
    assert pk._prefill_tiling(t, nb, bs, nh, kvh, d, 2, 2) == tiling
    group = nh // kvh * tiling[0]
    assert group // pk._prefill_sub_tile(tiling[0], nh // kvh) == items
    fn = (
        (lambda *a: pk.paged_window_prefill_attention(
            *a, scale=d ** -0.5, window=window)) if window
        else (lambda *a: pk.paged_prefill_attention(*a, scale=d ** -0.5)))
    compiled = jax.jit(fn).lower(*args).compile()
    _assert_kernel(compiled)
    assert ("paged_window_prefill_attention" if window
            else "paged_prefill_attention") in compiled.as_text()


def test_sharded_kernels_compile_for_four_v5e_chips(topo):
    """--tensor-parallel-size 4 with explicit pallas backends: shard_map
    places one kernel per chip over its own kvh/tp heads."""
    mesh = mesh_lib.make_mesh(tensor_parallel_size=4, devices=topo.devices)
    dp, tp = mesh_lib.DP_AXIS, mesh_lib.TP_AXIS

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec)
        )

    pool = P(None, None, None, tp, None)
    scale = HEADS["qwen3-8b"][2] ** -0.5
    dargs = _decode_args(sds, "qwen3-8b", 32, jnp.bfloat16, (
        P(dp, tp, None), pool, P(dp, None), P(dp),
        P(None, dp, tp, None), P(),
    ))
    _assert_kernel(jax.jit(
        lambda *a: pk.paged_decode_attention_sharded(mesh, *a, scale=scale)
    ).lower(*dargs).compile())
    pargs = _prefill_args(sds, "qwen3-8b", 32, jnp.bfloat16, (
        P(dp, None, tp, None), pool, P(dp, None), P(dp),
    ))
    _assert_kernel(jax.jit(
        lambda *a: pk.paged_prefill_attention_sharded(mesh, *a, scale=scale)
    ).lower(*pargs).compile())


@pytest.mark.parametrize("nb,bs,nh,kvh,d,kv_itemsize,want", [
    (64, 32, 32, 8, 128, 2, 8),    # qwen3-8b, the benchmark's table
    (128, 32, 28, 4, 128, 2, 8),   # qwen2-7b, the widest table
    (256, 16, 32, 8, 128, 2, 16),  # 16-token pages: the same 256 tokens
    (64, 64, 32, 8, 128, 2, 4),
    (64, 32, 32, 8, 128, 1, 8),    # fp8 pool: the widened copy still fits
    (64, 32, 64, 16, 128, 2, 8),   # 64/16 heads: 8 MiB, still inside
    (64, 32, 64, 16, 128, 1, 4),   # ... but not with an fp8 pool's copy
    (64, 32, 32, 32, 128, 2, 4),   # no GQA: every head its own columns
    (2, 32, 32, 8, 128, 2, 2),     # never wider than the table
    (1, 32, 32, 8, 128, 2, 1),
])
def test_decode_page_block_fits_scoped_vmem(nb, bs, nh, kvh, d, kv_itemsize,
                                            want):
    pages = pk._decode_pages_per_block(nb, bs, nh, kvh, d, kv_itemsize, 2)
    assert pages == want
    assert pages <= nb
    assert pages * bs <= max(pk.DECODE_BLOCK_TOKENS, bs)
    held = pk._decode_block_vmem(pages, bs, nh, kvh, d, kv_itemsize, 2)
    assert held <= pk.DECODE_VMEM_BUDGET < 16 * 2**20
    if pages < min(nb, pk.DECODE_BLOCK_TOKENS // bs):  # cut by the budget
        assert pk._decode_block_vmem(
            2 * pages, bs, nh, kvh, d, kv_itemsize, 2
        ) > pk.DECODE_VMEM_BUDGET


@pytest.mark.parametrize("kvh,d,itemsize,want", [
    (8, 128, 2, True), (4, 128, 2, True), (2, 128, 2, True),
    (1, 128, 2, False), (8, 64, 2, False), (8, 128, 1, True),
    (4, 128, 1, True), (2, 128, 1, False), (1, 128, 4, True),
    (16, 128, 2, True),
])
def test_decode_kernel_form_follows_the_pool_tiles(kvh, d, itemsize, want):
    """Which pools the walk kernel serves (it slices pages out of HBM) and
    which the grid kernel: both sides compile above, at these shapes."""
    assert pk._pages_fill_tiles(kvh, d, itemsize) is want


@pytest.mark.parametrize("t,nb,bs,nh,kvh,d,kv_itemsize,want", [
    (512, 64, 32, 32, 8, 128, 2, (256, 8)),   # qwen3-8b: cell 1
    (512, 64, 32, 28, 4, 128, 2, (256, 8)),   # qwen2-7b: cell 2
    (512, 64, 32, 16, 16, 128, 2, (256, 8)),  # olmoe-1b-7b: the MoE cell
    (512, 128, 32, 32, 8, 128, 2, (256, 8)),  # the table's width: no part
    (1024, 64, 32, 8, 2, 128, 2, (256, 8)),   # a tp=4 shard: the full tile
    (512, 256, 16, 32, 8, 128, 1, (256, 16)),  # fp8, 16-token pages
    (512, 64, 32, 64, 16, 128, 2, (128, 8)),
    # the long-document cells' 2,048-token chunk under a 1,024-page table:
    # Laguna-S-2.1's full and window layers, Solar-Open2's GQA layers
    (2048, 1024, 32, 48, 8, 128, 2, (128, 8)),
    (2048, 1024, 32, 72, 8, 128, 2, (128, 8)),
    (2048, 1024, 32, 64, 8, 128, 2, (128, 8)),
    (64, 64, 32, 32, 8, 128, 2, (64, 8)),     # never longer than the bucket
    (512, 2, 32, 32, 8, 128, 2, (256, 2)),    # ... nor wider than the table
])
def test_prefill_tile_and_page_block_fit_the_vmem_the_kernel_asks_for(
        t, nb, bs, nh, kvh, d, kv_itemsize, want):
    tt, pages = pk._prefill_tiling(t, nb, bs, nh, kvh, d, kv_itemsize, 2)
    assert (tt, pages) == want
    assert pages * bs <= max(pk.PREFILL_BLOCK_TOKENS, bs) and pages <= nb
    held = pk._prefill_vmem(tt, pages, bs, nh, kvh, d, kv_itemsize, 2)
    assert held <= pk.PREFILL_VMEM_BUDGET < pk.PREFILL_VMEM_LIMIT
    if tt < min(t, pk.PREFILL_Q_TILE):  # cut by the budget
        assert pk._prefill_vmem(
            2 * tt, pages, bs, nh, kvh, d, kv_itemsize, 2
        ) > pk.PREFILL_VMEM_BUDGET


# -- a sparse-MoE model's step programs (ISSUE 26) ----------------------------


def _moe_program_args(topo, cfg, batch, t, block=32, nb=64, window=8):
    """Abstract arguments on the first chip: the int8 param tree of `cfg`,
    a paged pool, and one step's inputs at (batch, t)."""
    from vllm_production_stack_tpu.models import llama
    from vllm_production_stack_tpu.models.quantization import quantize_params

    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    params = on_chip(jax.eval_shape(
        lambda k: quantize_params(cfg, llama.init_params(cfg, k)),
        jax.random.PRNGKey(0)))
    kv = on_chip(jax.eval_shape(
        lambda: llama.init_kv_cache(cfg, POOL_BLOCKS, block)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    staged = on_chip(jax.eval_shape(
        lambda: llama.init_staged_kv(cfg, window, batch)))
    return params, kv, i32, staged, nb


def _assert_no_widened_experts(cfg, text):
    """No operation of the program writes a bf16 copy of one layer's expert
    leaf (E, in, out): int8 experts are read as int8, and a slice of a
    stacked leaf that fed a custom call (which XLA would copy) is not
    taken."""
    import re

    e, h, it = cfg.held_experts, cfg.hidden_size, cfg.expert_intermediate_size
    copies = [
        line[:200] for line in text.splitlines()
        if re.search(rf"= bf16\[({e},{h},{it}|{e},{it},{h})\]", line)
        and " parameter(" not in line
    ]
    assert not copies, copies


def _packed_prefill(topo, cfg, rows, n, backend="xla", moe_backend="xla"):
    """The served prefill program, compiled: `rows` rows whose chunk tokens
    lie end to end on one axis of `n` positions (llama.forward with `pack`,
    blockwise KV write), two layers deep, int8 weights, the 64-page table."""
    from vllm_production_stack_tpu.models import llama

    params, kv, i32, _staged, nb = _moe_program_args(topo, cfg, rows, n)
    nbw = (n - 1) // 32 + 2

    def step(params, kv, tokens, positions, tables, lens, chunk, ids, off):
        stats: list | None = [] if cfg.num_experts else None
        hidden, kv = llama.forward(
            cfg, params, tokens, positions, kv, tables,
            jnp.zeros((1,), jnp.int32), lens,
            write_blocks={"ids": ids, "start_off": off, "chunk_lens": chunk},
            backend=backend, moe_stats=stats, moe_backend=moe_backend,
            pack=llama.pack_index(chunk, lens, n))
        return (llama.compute_logits(cfg, params, hidden[-rows:, 0]), kv,
                sum(s["touched"] for s in stats or ()))

    return jax.jit(step, donate_argnums=1).lower(
        params, kv, i32(n), i32(n), i32(rows, nb), i32(rows), i32(rows),
        i32(rows, nbw), i32(rows)).compile()


def _cell_config(model):
    import dataclasses

    from vllm_production_stack_tpu.models.registry import resolve_model_config

    return dataclasses.replace(
        resolve_model_config(model, quantization="int8"), num_layers=2)


@pytest.mark.parametrize("rows,tokens,moe_backend,want", [
    # the cell's 512-token chunk: five times the 96 tokens at which the
    # einsum's flops overtake its int8 bytes, so the routed pairs go sorted
    # through the fused kernel (int8 tiles widened in VMEM) on one TPU device
    (1, CHUNK, "pallas", "grouped_kernel"),
    # still 512: the token SUM chooses, not rows x bucket
    (2, CHUNK, "pallas", "grouped_kernel"),
    # where the program may hold no Pallas kernel (a mesh) the einsum stays
    # up to 512 tokens: `ragged_dot` loses to it there
    (1, CHUNK, "xla", "all_experts"),
    (1, CHUNK // 8, "pallas", "all_experts"),  # 64: the bytes still bound
    # 1,024 tokens: the kernel, or ragged_dot under a mesh
    (2, 2 * CHUNK, "pallas", "grouped_kernel"),
    (2, 2 * CHUNK, "xla", "grouped"),
])
def test_moe_prefill_program_compiles_for_v5e(topo, rows, tokens,
                                              moe_backend, want):
    """OLMoE-1B-7B's prefill step at published widths (two layers deep),
    int8 experts, on one described v5e chip."""
    from vllm_production_stack_tpu.models import llama

    cfg = _cell_config("olmoe-1b-7b")
    text = _packed_prefill(
        topo, cfg, rows, tokens, moe_backend=moe_backend).as_text()
    _assert_no_widened_experts(cfg, text)
    assert llama.moe_experts_form(
        cfg, tokens, kernel=moe_backend != "xla") == want
    assert ("ragged-dot" in text) == (want == "grouped")
    assert ("moe_prefill_experts" in text) == (want == "grouped_kernel")


@pytest.mark.parametrize("model", ["qwen3-8b", "qwen2-7b", "olmoe-1b-7b"])
def test_prefill_program_of_a_cell_holds_no_score_temp(topo, model):
    """The 16-row, 512-token prefill program the cells' warm-up compiles,
    with the kernel: no f32 buffer of (rows, heads, chunk, table tokens) —
    2 GiB at 32 heads — no gathered history, no gather_pages scope, and all
    its temps under that size. And it is PACKED: nothing that is linear in
    tokens exists at rows x chunk positions (no MLP intermediate of that
    height), and sixteen rows add to a lone row's temps only what attention
    and the KV write hold per row (the query row view and its result,
    (rows, chunk, heads x D) each, and the written pages)."""
    import re

    cfg = _cell_config(model)
    rows, s_ctx = 16, 64 * 32
    compiled = _packed_prefill(topo, cfg, rows, CHUNK, backend="pallas")
    text = compiled.as_text()
    assert "paged_prefill_attention" in text
    assert "gather_pages" not in text
    nh, kvh, d = HEADS[model]
    # the chunk's K/V go in through the write kernel, and no operation but
    # the kernels holds a layer's pool: nothing converts its layout (the
    # XLA scatter had each layer's whole pool copied there and back)
    assert "paged_kv_write" in text
    pool = rf"= \S+\[2,{POOL_BLOCKS},32,{kvh},{d}\]\S* (copy|scatter|fusion)\("
    held = [line[:200] for line in text.splitlines() if re.search(pool, line)]
    assert not held, held
    scores = rf"\[{rows},{nh},{CHUNK},{s_ctx}\]|\[{rows},{kvh},\d+,{CHUNK},{s_ctx}\]"
    history = rf"\[{rows},{s_ctx},{kvh},{d}\]"
    held = [line[:160] for line in text.splitlines()
            if re.search(scores, line) or re.search(history, line)]
    assert not held, held
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps < rows * nh * CHUNK * s_ctx * 4, temps
    tall = rf"\[({rows * CHUNK}|{rows},{CHUNK}),(1,)?{cfg.intermediate_size}\]"
    held = [line[:160] for line in text.splitlines() if re.search(tall, line)]
    assert not held, held
    lone = _packed_prefill(
        topo, cfg, 1, CHUNK, backend="pallas"
    ).memory_analysis().temp_size_in_bytes
    row_view = rows * CHUNK * nh * d * 2  # bf16 queries; the result too
    written = rows * ((CHUNK - 1) // 32 + 2) * 32 * kvh * d * 2  # K's pages
    # (as read, and as merged; V's the same)
    assert temps - lone <= 2 * row_view + 4 * written, (temps, lone)


@pytest.mark.parametrize("program", ["window", "chunk"])
@pytest.mark.parametrize("model,quantization", [
    ("qwen3-8b", "int8"), ("qwen2-7b", "int8"),
    ("kanana-2-30b-a3b-8l", None)])
def test_window_and_chunk_of_a_cell_read_their_weights_in_place(
        topo, model, quantization, program):
    """The eight-step decode window at 16 rows and the packed 2,048-token
    chunk of a cell's model, three layers deep: outside the dots' own
    fusions no operation of ENTRY or of the loop's body reads weights alone
    and writes a buffer with the element count of a weight leaf, of its
    slice, or of a stack — no copy, slice or re-layout of a weight
    (tools/weight_copies.py is the scan). A leaf stored (in, heads *
    head_dim) whose product is reshaped to heads was sliced a layer a STEP
    and its stack re-laid a window: 0.9 GB a step at qwen3-8b (PERF.md §6
    PR 56)."""
    from tools import weight_copies as wc

    cfg = wc.cell_config(model, quantization, 3)
    latent = bool(cfg.latent_layers)
    # (a latent cell's table is max_model_len / 32 wide over an 8k pool)
    sizes = dict(table=1024, blocks=8192) if latent else {}
    if program == "window":
        compiled, params = wc.compile_window(topo, cfg, **sizes)
    else:
        compiled, params = wc.compile_chunk(
            topo, cfg, rows=2 if latent else 4, **sizes)
    text = compiled.as_text()
    _assert_kernel(compiled)
    assert wc.writes_outside_dots(text)  # the scan reads this text
    copies = wc.weight_copies(text, params)
    assert not copies, [(c["where"], c["name"], c["bytes"], c["scope"])
                        for c in copies]


@pytest.mark.parametrize("model,quantization,layers,blocks,table", [
    ("qwen3-8b", "int8", 36, 768, 128),  # the cell: 50 MB of K, of V a layer
    ("qwen3-8b", "int8", 36, 256, 128),  # (a stated 16 MiB lets 512 pages in)
    ("laguna-s-2.1-ep4-9l", None, 9, 4096, 1024),  # the window kernel too
    ("kanana-2-30b-a3b-8l", None, 8, 1024, 1024),  # latent: 42 MB a layer
])
def test_runner_window_of_a_cell_parks_no_pool_in_vmem(
        topo, model, quantization, layers, blocks, table):
    """The window program the RUNNER builds (the qwen3 cell's: 36 layers, 16
    rows, the 128-page table, the 768-page pool, which fits a v5e's 128 MiB
    of VMEM; a pool a third of it; the Laguna cell's two page groups; a
    latent pool of 1,024 pages): the compiler prefetches no pool into VMEM
    — with the weights' copies gone from it, its memory-space assignment
    parked every layer's WHOLE pool there a step (`slice-done` + `copy-done`
    1.5 s of an 8-s slice on the chip) until the decode kernels claimed the
    VMEM, `DECODE_VMEM_LIMIT` — and no weight is copied here either
    (PERF.md §6 PR 56)."""
    import re

    from tools import weight_copies as wc

    cfg = wc.cell_config(model, quantization, layers)
    compiled, params = wc.compile_runner_window(
        topo, cfg, blocks=blocks, table=table)
    text = compiled.as_text()
    assert "decode_attention" in text
    # a page of any pool here: (32 tokens, 8 kv heads, 128) or (32, 640)
    parked = [line for line in wc.vmem_prefetches(text)
              if re.search(r"\[[\d,]*,32,(8,128|640)\]\S*S\(1\)", line)]
    assert not parked, parked[:4]
    assert not wc.weight_copies(text, params)
    if blocks == 768:
        # nothing of a megabyte at all, and the stacks re-laid in ENTRY
        # were 0.86 GB of 1.33 GB of temps
        assert not wc.vmem_prefetches(text)
        assert compiled.memory_analysis().temp_size_in_bytes < 2**27


def test_moe_decode_step_compiles_for_v5e(topo):
    """One decode step of OLMoE-1B-7B at 16 rows: the Pallas decode kernel
    at its third head shape (16 KV heads x 128) and the all-experts einsum
    on int8 experts — 16 rows x top-8 of 64 cover the experts, so a program
    that may hold the touched-experts kernel does not."""
    from vllm_production_stack_tpu.models import llama

    cfg = _cell_config("olmoe-1b-7b")
    rows = 16
    params, kv, i32, staged, nb = _moe_program_args(topo, cfg, rows, 1)

    def step(params, kv, staged, tokens, positions, tables, hist):
        stats: list = []
        hidden, staged = llama.decode_window_step(
            cfg, params, tokens, positions, kv, tables, staged,
            jnp.int32(0), hist, backend="pallas", moe_stats=stats,
            moe_backend="pallas")
        return (llama.compute_logits(cfg, params, hidden), staged,
                sum(s["touched"] for s in stats))

    compiled = jax.jit(step).lower(
        params, kv, staged, i32(rows), i32(rows), i32(rows, nb), i32(rows),
    ).compile()
    text = compiled.as_text()
    _assert_kernel(compiled)
    assert "paged_decode_attention" in text
    assert "moe_decode_experts" not in text
    _assert_no_widened_experts(cfg, text)


# -- a latent-attention (MLA) model's kernels and step programs (ISSUE 31) ----


@pytest.mark.parametrize("kv_dtype,width,window", [
    ("bfloat16", 1024, 8),  # the cell: one table width, max_model_len / 32
    ("bfloat16", 1024, 1),  # the 1-, 2-, 4-step windows the warm-up builds:
    ("bfloat16", 1024, 2),  # their staged latents are padded to a tile
    ("bfloat16", 64, 4),
    ("float8_e4m3fn", 1024, 8),  # the reference check's lower-precision arm
])
def test_latent_decode_kernel_compiles_for_v5e(topo, kv_dtype, width, window):
    from vllm_production_stack_tpu.ops import mla_attention_pallas as mk

    sds = _one_chip(topo)
    args = (
        sds((16, 32, 640), jnp.bfloat16, None),
        sds((1, 8192, 32, 640), jnp.dtype(kv_dtype), None),
        sds((16, width), jnp.int32, None), sds((16,), jnp.int32, None),
        sds((16, window, 640), jnp.bfloat16, None), sds((), jnp.int32, None),
    )
    compiled = jax.jit(lambda *a: mk.mla_decode_attention(
        *a, scale=192 ** -0.5, rank=512)).lower(*args).compile()
    _assert_kernel(compiled)
    assert "mla_decode_attention" in compiled.as_text()


@pytest.mark.parametrize("kv_dtype,rows", [
    ("bfloat16", 1), ("bfloat16", 16), ("float8_e4m3fn", 2)])
def test_latent_prefill_kernel_compiles_for_v5e(topo, kv_dtype, rows):
    """A 2,048-token chunk, 32 heads x 640: the query tile (128 tokens, 4,096
    rows of the two dots) within the VMEM the kernel asks for."""
    from vllm_production_stack_tpu.ops import mla_attention_pallas as mk

    sds = _one_chip(topo)
    args = (
        sds((rows, 2048, 32, 640), jnp.bfloat16, None),
        sds((1, 8192, 32, 640), jnp.dtype(kv_dtype), None),
        sds((rows, 1024), jnp.int32, None), sds((rows,), jnp.int32, None),
        sds((rows,), jnp.int32, None),
    )
    compiled = jax.jit(lambda *a: mk.mla_prefill_attention(
        *a, scale=192 ** -0.5, rank=512)).lower(*args).compile()
    _assert_kernel(compiled)
    assert "mla_prefill_attention" in compiled.as_text()
    assert mk._prefill_tile(2048, 8, 32, 32, 640, 512, 2, 2) == 128


def _kanana(layers=2):
    import dataclasses

    from vllm_production_stack_tpu.models.registry import resolve_model_config

    # layer 0 dense, layer 1 an expert layer: both MLP stacks
    return dataclasses.replace(
        resolve_model_config("kanana-2-30b-a3b-8l"), num_layers=layers)


def test_latent_prefill_program_compiles_for_v5e(topo):
    """Kanana-2-30B-A3B's served prefill step at published widths (two
    layers deep): two rows packed on 2,048 positions, the 1,024-page table,
    the latent kernel, the routed pairs through the fused grouped-expert
    kernel (2,048 tokens is above _ALL_EXPERTS_MAX_TOKENS) reading the
    stacked leaves in place, and no (rows, heads, T, S) score array nor
    gathered history."""
    import re

    from vllm_production_stack_tpu.models import llama

    cfg = _kanana()
    one = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one), tree)
    params = on_chip(jax.eval_shape(
        lambda k: llama.init_params(cfg, k), jax.random.PRNGKey(0)))
    kv = on_chip(jax.eval_shape(lambda: llama.init_kv_cache(cfg, 8192, 32)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    rows, n, nb = 2, 2048, 1024
    nbw = (n - 1) // 32 + 2

    def step(params, kv, tokens, positions, tables, lens, chunk, ids, off):
        stats: list = []
        hidden, kv = llama.forward(
            cfg, params, tokens, positions, kv, tables,
            jnp.zeros((1,), jnp.int32), lens,
            write_blocks={"ids": ids, "start_off": off, "chunk_lens": chunk},
            backend="pallas", moe_stats=stats, moe_backend="pallas",
            pack=llama.pack_index(chunk, lens, n))
        return (llama.compute_logits(cfg, params, hidden[-rows:, 0]), kv,
                sum(s["touched"] for s in stats))

    compiled = jax.jit(step, donate_argnums=1).lower(
        params, kv, i32(n), i32(n), i32(rows, nb), i32(rows), i32(rows),
        i32(rows, nbw), i32(rows)).compile()
    text = compiled.as_text()
    assert "mla_prefill_attention" in text and "moe_prefill_experts" in text
    assert "paged_prefill_attention" not in text and "ragged-dot" not in text
    _assert_no_widened_experts(cfg, text)
    held = [line[:160] for line in text.splitlines()
            if re.search(rf"\[{rows},32,{n},{nb * 32}\]", line)
            or re.search(rf"\[{rows},{nb * 32},(1,)?640\]", line)]
    assert not held, held
    # two rows' query row views and results, the written pages: well under
    # one row's float32 scores against a 16k context (4 GiB)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_latent_decode_step_compiles_for_v5e(topo):
    """One decode step at 16 rows: the latent decode kernel, the touched
    experts through the fused kernel (16 rows x top-6 of 128 cannot cover
    them) reading the stacked leaves in place, the shared experts, the
    leading dense layer."""
    import re

    from vllm_production_stack_tpu.models import llama

    cfg = _kanana()
    one = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one), tree)
    params = on_chip(jax.eval_shape(
        lambda k: llama.init_params(cfg, k), jax.random.PRNGKey(0)))
    kv = on_chip(jax.eval_shape(lambda: llama.init_kv_cache(cfg, 8192, 32)))
    staged = on_chip(jax.eval_shape(lambda: llama.init_staged_kv(cfg, 8, 16)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    def step(params, kv, staged, tokens, positions, tables, hist):
        stats: list = []
        hidden, staged = llama.decode_window_step(
            cfg, params, tokens, positions, kv, tables, staged,
            jnp.int32(0), hist, backend="pallas", moe_stats=stats,
            moe_backend="pallas")
        return (llama.compute_logits(cfg, params, hidden), staged,
                sum(s["touched"] for s in stats))

    compiled = jax.jit(step).lower(
        params, kv, staged, i32(16), i32(16), i32(16, 1024), i32(16),
    ).compile()
    text = compiled.as_text()
    _assert_kernel(compiled)
    assert "mla_decode_attention" in text and "moe_decode_experts" in text
    assert "paged_decode_attention" not in text and "ragged-dot" not in text
    # no operation writes a copy of a layer's experts (a slice of a stacked
    # leaf that fed the custom call would), nor an (N, E, inner) product
    e, h, it = cfg.num_experts, cfg.hidden_size, cfg.moe_intermediate_size
    copies = [
        line[:200] for line in text.splitlines()
        if re.search(rf"= bf16\[(1,)?({e},{h},{it}|{e},{it},{h}|16,{e},{it})\]",
                     line) and " parameter(" not in line]
    assert not copies, copies


@pytest.mark.parametrize("model,quantization,rows", [
    ("kanana-2-30b-a3b-8l", None, 16),  # a whole expert a grid step
    ("laguna-s-2.1-ep4-9l", None, 16),  # 64 held of 256, 18.9 MB an expert
    ("laguna-s-2.1-ep4-9l", None, 5),  # rows padded to a sublane tile
    ("olmoe-1b-7b", "int8", 16),  # int8 tiles widened in VMEM
])
def test_touched_experts_kernel_compiles_for_v5e(topo, model, quantization,
                                                 rows):
    """The fused expert kernel at the three cells' expert stacks as served
    (every layer stacked, the last layer read), inside the VMEM it asks
    for."""
    from vllm_production_stack_tpu.models.registry import resolve_model_config
    from vllm_production_stack_tpu.ops import moe_experts_pallas as mk

    cfg = resolve_model_config(model)
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    layers, e, k = cfg.num_moe_layers, cfg.held_experts, cfg.num_experts_per_tok
    h, it = cfg.hidden_size, cfg.expert_intermediate_size
    assert 2 * 3 * h * it * (1 if quantization else 2) // mk._inner_tiles(
        h, it, 1 if quantization else 2) <= mk.WEIGHT_VMEM_BUDGET

    def leaf(*shape):
        if quantization:
            return {"q": sds(shape, jnp.int8),
                    "s": sds((*shape[:2], 1, shape[3]), jnp.float32)}
        return sds(shape, jnp.bfloat16)

    compiled = jax.jit(lambda x, w, g, wg, wu, wd: mk.moe_decode_experts(
        x, w, g, wg, wu, wd, layer=layers - 1, slots=min(e, rows * k),
        act=jax.nn.silu)).lower(
        sds((rows, h), jnp.bfloat16), sds((rows, e), jnp.float32),
        sds((e,), jnp.int32), leaf(layers, e, h, it), leaf(layers, e, h, it),
        leaf(layers, e, it, h)).compile()
    _assert_kernel(compiled)
    # what the program holds beside its operands: the padded rows, not a
    # copy of anything expert-sized
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize("model,quantization,tokens", [
    ("kanana-2-30b-a3b-8l", None, 2048),  # 12,288 sorted rows, 128 experts
    ("laguna-s-2.1-ep4-9l", None, 2048),  # 20,480 rows, 64 held of 256
    ("olmoe-1b-7b", "int8", 1024),  # int8 tiles widened in VMEM
    ("mixtral-8x7b", None, 1024),  # an expert cut over its inner width
])
def test_grouped_experts_kernel_compiles_for_v5e(topo, model, quantization,
                                                 tokens):
    """The fused grouped-expert kernel of a prefill chunk at the cells'
    expert stacks as served (every layer stacked, the last layer read; the
    wide Mixtral expert two layers deep), inside the VMEM it asks for and
    with nothing expert- or row-sized beside its operands."""
    from vllm_production_stack_tpu.models.registry import resolve_model_config
    from vllm_production_stack_tpu.ops import moe_experts_pallas as mk

    cfg = resolve_model_config(model)
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    e, k = cfg.held_experts, cfg.num_experts_per_tok
    h, it = cfg.hidden_size, cfg.expert_intermediate_size
    layers = min(cfg.num_moe_layers, 16 if quantization else 8)
    if model.startswith("mixtral"):
        layers = 2
        assert mk._inner_tiles(h, it, 2) > 1

    def leaf(*shape):
        if quantization:
            return {"q": sds(shape, jnp.int8),
                    "s": sds((*shape[:2], 1, shape[3]), jnp.float32)}
        return sds(shape, jnp.bfloat16)

    compiled = jax.jit(lambda x, g, wg, wu, wd: mk.moe_prefill_experts(
        x, g, wg, wu, wd, layer=layers - 1, act=jax.nn.silu)).lower(
        sds((tokens * k, h), jnp.bfloat16), sds((e,), jnp.int32),
        leaf(layers, e, h, it), leaf(layers, e, h, it),
        leaf(layers, e, it, h)).compile()
    _assert_kernel(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


# -- generation by diffusion over blocks (SDAR, ISSUE 41) --------------------


def _sdar(layers=2):
    import dataclasses

    from vllm_production_stack_tpu.models.registry import resolve_model_config

    return dataclasses.replace(
        resolve_model_config("sdar-30b-a3b-chat-8l"), num_layers=layers)


def _bf16_program_args(topo, cfg, rows, window=8):
    from vllm_production_stack_tpu.models import llama

    one = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one), tree)
    params = on_chip(jax.eval_shape(
        lambda k: llama.init_params(cfg, k), jax.random.PRNGKey(0)))
    kv = on_chip(jax.eval_shape(lambda: llama.init_kv_cache(cfg, 4096, 32)))
    staged = on_chip(jax.eval_shape(
        lambda: llama.init_staged_kv(cfg, window, rows)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    return params, kv, staged, i32


def test_block_pass_compiles_for_v5e(topo):
    """One forward pass of a window of block passes at the cell's shapes (16
    rows x a block of 4, two layers deep): the block's queries folded into
    the GQA group axis of the decode kernel (4 kv heads x 32 query rows:
    128 a row, inside the block's VMEM budget at 8 pages), the all-experts
    einsum for 64 tokens of 128 experts, the transfer rule."""
    from vllm_production_stack_tpu.models import llama
    from vllm_production_stack_tpu.ops import paged_attention_pallas as pk

    cfg = _sdar()
    rows, t = 16, cfg.block_diffusion.block_length
    assert llama.moe_experts_form(cfg, rows * t, kernel=True) == "all_experts"
    assert pk._decode_pages_per_block(128, 32, 32 * t, 4, 128, 2, 2) == 8
    params, kv, staged, i32 = _bf16_program_args(topo, cfg, rows)

    def step(params, kv, staged, tokens, positions, tables, hist, slot0,
             masked, pas):
        stats: list = []
        hidden, staged = llama.decode_block_step(
            cfg, params, tokens, positions, kv, tables, staged, slot0[0],
            hist, backend="pallas", moe_stats=stats, moe_backend="pallas")
        logits = llama.compute_logits(
            cfg, params, hidden.reshape(rows * t, -1)).reshape(rows, t, -1)
        new, m_new, pick, over = llama.bd_transfer(
            cfg.block_diffusion, logits, tokens, masked > 0, pas[0])
        return new, m_new, staged, over, sum(s["touched"] for s in stats)

    compiled = jax.jit(step).lower(
        params, kv, staged, i32(rows, t), i32(rows, t), i32(rows, 128),
        i32(rows), i32(1), i32(rows, t), i32(1)).compile()
    text = compiled.as_text()
    _assert_kernel(compiled)
    assert "paged_decode_attention" in text
    assert "moe_decode_experts" not in text and "ragged-dot" not in text
    assert "bd_transfer" in text


def test_block_mask_prefill_program_compiles_for_v5e(topo):
    """A 1,024-token packed chunk of three rows under the block mask: the
    prefill kernel with `block` = 4 (its query tiles are multiples of 4) and
    the routed pairs through the fused grouped-expert kernel (1,024 > 512
    tokens), bf16."""
    from vllm_production_stack_tpu.models import llama

    cfg = _sdar()
    rows, n = 4, 1024
    assert llama.moe_experts_form(cfg, n, kernel=True) == "grouped_kernel"
    params, kv, _staged, i32 = _bf16_program_args(topo, cfg, rows)
    nbw = (n - 1) // 32 + 2

    def step(params, kv, tokens, positions, tables, lens, chunk, ids, off):
        stats: list = []
        hidden, kv = llama.forward(
            cfg, params, tokens, positions, kv, tables,
            jnp.zeros((1,), jnp.int32), lens,
            write_blocks={"ids": ids, "start_off": off, "chunk_lens": chunk},
            backend="pallas", moe_stats=stats, moe_backend="pallas",
            pack=llama.pack_index(chunk, lens, n))
        return hidden, kv, sum(s["touched"] for s in stats)

    compiled = jax.jit(step, donate_argnums=1).lower(
        params, kv, i32(n), i32(n), i32(rows, 128), i32(rows), i32(rows),
        i32(rows, nbw), i32(rows)).compile()
    text = compiled.as_text()
    _assert_kernel(compiled)
    assert "paged_prefill_attention" in text
    assert "moe_prefill_experts" in text and "ragged-dot" not in text
