"""The two Gated DeltaNet kernels (one gate a head, a 96 x 192 state) and the
step programs that hold them, compiled by the installed TPU compiler for a
DESCRIBED v5e topology — no chip attached, nothing runs
(tests/test_chip_compile.py says why such a guard exists): the cell's shapes
— 30 heads x 96 keys x 192 values, which fill no slab of whole 128-lane
tiles, a 2,048-token packed chunk of one and sixteen rows, 16 decode rows, 17
state slots in two banks — and one period of the cut (L L L F) with both
mixers' kernels in ONE program each. Interpret mode accepts what Mosaic
refuses (an unaligned lane concatenation, a (1, 1) value against a tile):
this file is what says the lane layout is one the chip takes."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from vllm_production_stack_tpu.models import llama
from vllm_production_stack_tpu.models.registry import resolve_model_config
from vllm_production_stack_tpu.ops import kda_attention as kda

from test_chip_compile import _assert_kernel, topo  # noqa: F401

HEADS, DK, DV, SLOTS, CHUNK = 30, 96, 192, 17, 2048
POOL_BYTES = 2 * SLOTS * HEADS * DK * DV * 4


def _sds(topo):
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)


@pytest.mark.parametrize("rows", [1, 16])
def test_prefill_kernel_compiles_at_the_cells_shapes(topo, rows):  # noqa: F811
    s = _sds(topo)
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    args = (
        s((2 * SLOTS, HEADS, DK, DV), f32), s((rows,), i32), s((rows,), i32),
        s((rows,), jnp.bool_), s((CHUNK, HEADS, DK), bf),
        s((CHUNK, HEADS, DK), bf), s((CHUNK, HEADS, DV), bf),
        s((CHUNK, HEADS), f32), s((CHUNK, HEADS), f32),
        s((rows,), i32), s((rows,), i32))
    compiled = jax.jit(
        kda.gdn_prefill_attention.__wrapped__, donate_argnums=0,
    ).lower(*args).compile()
    _assert_kernel(compiled)
    # the pool is updated in place: the temps are the chunk's padded
    # activations (q, k at 128 lanes a head, v and o at 256), no second pool
    assert compiled.memory_analysis().temp_size_in_bytes < POOL_BYTES
    (call,) = [e for e in jax.make_jaxpr(
        kda.gdn_prefill_attention.__wrapped__)(*args).eqns
        if e.primitive.name == "pallas_call"]
    per_step = kda.gdn_heads_per_step(HEADS)
    assert per_step == 3  # (a fall back to one head a step fails here)
    assert tuple(call.params["grid_mapping"].grid) == (
        HEADS // per_step, CHUNK // kda.BLOCK + rows)
    # the gate goes in as beta does: (N, H) float32, never (N, H, Dk)
    shapes = [v.aval.shape for v in call.invars]
    assert shapes.count((CHUNK, HEADS)) == 2
    assert (CHUNK, HEADS * kda.whole_lanes(DK)) in shapes


@pytest.mark.parametrize("rows", [1, 16])
def test_decode_kernel_compiles_at_the_cells_shapes(topo, rows):  # noqa: F811
    s = _sds(topo)
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    args = (
        s((2 * SLOTS, HEADS, DK, DV), f32), s((rows,), i32), s((rows,), i32),
        s((rows, HEADS, DK), bf), s((rows, HEADS, DK), bf),
        s((rows, HEADS, DV), bf), s((rows, HEADS), f32),
        s((rows, HEADS), f32))
    compiled = jax.jit(
        kda.gdn_decode_attention.__wrapped__, donate_argnums=0,
    ).lower(*args).compile()
    _assert_kernel(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_the_lane_layout():
    assert (kda.whole_lanes(96), kda.whole_lanes(192), kda.whole_lanes(128)) == (
        128, 256, 128)
    # any divisor of the head count is a slab once a head is whole lanes
    assert [kda.gdn_heads_per_step(h) for h in (30, 6, 5, 7, 64)] == [
        3, 3, 1, 1, 2]
    assert kda.gdn_decode_heads_per_step(30, 96) == 30
    assert kda.gdn_decode_heads_per_step(64, 128) == 8
    with pytest.raises(ValueError, match="do not fit"):
        kda.gdn_decode_heads_per_step(50, 96)


def _cut(layers=4):
    cfg = resolve_model_config("olmo-hybrid-7b-12l")
    return dataclasses.replace(
        cfg, num_layers=layers, layers=cfg.layers[:layers])


def _program_args(topo, cfg, blocks=256, block=32):
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    params = on_chip(jax.eval_shape(
        lambda k: llama.init_params(cfg, k), jax.random.PRNGKey(0)))
    kv = on_chip(jax.eval_shape(lambda: llama.init_kv_cache(
        cfg, blocks, block, state_slots=SLOTS)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one)
    return params, kv, i32


@pytest.mark.parametrize("rows", [1, 2])
def test_prefill_program_compiles_for_v5e(topo, rows):  # noqa: F811
    """The served prefill program of one period (three Gated DeltaNet layers
    and one full layer, every width as published): the packed chunk through
    the chunked kernel and the paged flash kernel, pages and state pools
    updated in place."""
    cfg, n, nb = _cut(), CHUNK, 64
    params, kv, i32 = _program_args(topo, cfg)
    nbw = (n - 1) // 32 + 2

    def step(params, kv, tokens, positions, tables, lens, chunk, ids, off,
             read, write):
        hidden, kv = llama.forward(
            cfg, params, tokens, positions, kv, tables,
            jnp.zeros((1,), jnp.int32), lens,
            write_blocks={"ids": ids, "start_off": off, "chunk_lens": chunk},
            backend="pallas", pack=llama.pack_index(chunk, lens, n),
            state_slots=(read, write))
        return llama.compute_logits(cfg, params, hidden[-rows:, 0]), kv

    compiled = jax.jit(step, donate_argnums=1).lower(
        params, kv, i32(n), i32(n), i32(rows, nb), i32(rows), i32(rows),
        i32(rows, nbw), i32(rows), i32(rows), i32(rows)).compile()
    text = compiled.as_text()
    for kernel in ("gdn_prefill_attention", "paged_prefill_attention"):
        assert kernel in text
    assert "kda_prefill_attention" not in text
    mem = compiled.memory_analysis()
    # every cache array aliases its donated input
    assert mem.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(kv))


def test_decode_window_compiles_for_v5e(topo):  # noqa: F811
    """A fused decode window of 16 rows x 8 steps: the state pools ride the
    loop carry and are advanced in place by the decode kernel; pages are
    committed once behind the loop."""
    cfg, rows, window, nb, block = _cut(), 16, 8, 64, 32
    params, kv, i32 = _program_args(topo, cfg)

    def fn(params, kv, tok, pos0, tables, read, write):
        staged = llama.init_staged_kv(cfg, window, rows, kv_caches=kv)

        def body(k, carry):
            staged, cur, out = carry
            hidden, staged = llama.decode_window_step(
                cfg, params, cur, pos0 + k, kv, tables, staged, k, pos0,
                backend="pallas", state_slots=(read, write))
            toks = jnp.argmax(
                llama.compute_logits(cfg, params, hidden), -1
            ).astype(jnp.int32)
            return staged, toks, out.at[:, k].set(toks)

        staged, _, out = jax.lax.fori_loop(
            0, window, body,
            (staged, tok, jnp.zeros((rows, window), jnp.int32)))
        pos = pos0[:, None] + jnp.arange(window)[None]
        slots = (jnp.take_along_axis(tables, pos // block, axis=1) * block
                 + pos % block).reshape(-1)
        return llama.commit_staged_kv(kv, staged, slots), out

    compiled = jax.jit(fn, donate_argnums=1).lower(
        params, kv, i32(rows), i32(rows), i32(rows, nb), i32(rows),
        i32(rows)).compile()
    text = compiled.as_text()
    for kernel in ("gdn_decode_attention", "paged_decode_attention"):
        assert kernel in text
    pools = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(kv))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pools
    # no second copy of the state pools (75 MiB a linear layer) in the loop
    assert mem.temp_size_in_bytes < POOL_BYTES
