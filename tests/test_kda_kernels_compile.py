"""The two KDA kernels and the step programs that hold them, compiled by the
installed TPU compiler for a DESCRIBED v5e topology — no chip attached,
nothing runs (tests/test_chip_compile.py says why such a guard exists): the
cell's shapes — 64 heads x 128, a 2,048-token packed chunk of one and two
rows, 16 decode rows, 17 state slots in two banks — and the cut's own
widths two layers deep (one GQA and one KDA layer) with the kernels of both
mixers and of the experts."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from vllm_production_stack_tpu.models import llama
from vllm_production_stack_tpu.models.registry import resolve_model_config
from vllm_production_stack_tpu.ops import kda_attention as kda
from vllm_production_stack_tpu.ops import kda_short_conv as ksc

from test_chip_compile import _assert_kernel, topo  # noqa: F401

HEADS, D, SLOTS, CHUNK = 64, 128, 17, 2048


def _sds(topo):
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)


@pytest.mark.parametrize("rows", [1, 2, 16])
def test_prefill_kernel_compiles_at_the_cells_shapes(topo, rows):  # noqa: F811
    s = _sds(topo)
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    args = (
        s((2 * SLOTS, HEADS, D, D), f32), s((rows,), i32), s((rows,), i32),
        s((rows,), jnp.bool_), s((CHUNK, HEADS, D), bf),
        s((CHUNK, HEADS, D), bf), s((CHUNK, HEADS, D), bf),
        s((CHUNK, HEADS, D), f32), s((CHUNK, HEADS), f32),
        s((rows,), i32), s((rows,), i32))
    compiled = jax.jit(
        kda.kda_prefill_attention.__wrapped__, donate_argnums=0,
    ).lower(*args).compile()
    _assert_kernel(compiled)
    # the pool is updated in place: no second copy of it among the temps
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * SLOTS * (
        HEADS * D * D * 4)
    # the grid the compiled kernel walks: several heads a step (a fall back
    # to one head a step fails here, not in a benchmark), the work items of
    # a chunk of `rows` rows
    (call,) = [e for e in jax.make_jaxpr(
        kda.kda_prefill_attention.__wrapped__)(*args).eqns
        if e.primitive.name == "pallas_call"]
    per_step = kda.heads_per_step(HEADS, D, 2)
    assert per_step > 1
    assert tuple(call.params["grid_mapping"].grid) == (
        HEADS // per_step, CHUNK // kda.BLOCK + rows)


@pytest.mark.parametrize("rows", [1, 16])
def test_decode_kernel_compiles_at_the_cells_shapes(topo, rows):  # noqa: F811
    s = _sds(topo)
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    args = (
        s((2 * SLOTS, HEADS, D, D), f32), s((rows,), i32), s((rows,), i32),
        s((rows, HEADS, D), bf), s((rows, HEADS, D), bf),
        s((rows, HEADS, D), bf), s((rows, HEADS, D), f32),
        s((rows, HEADS), f32))
    compiled = jax.jit(
        kda.kda_decode_attention.__wrapped__, donate_argnums=0,
    ).lower(*args).compile()
    _assert_kernel(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize("heads", [64, 32])  # Solar-Open2's, Ling's
def test_qkv_pass_compiles_at_the_cells_shapes(topo, heads):  # noqa: F811
    """2,048 x 24,576 and 2,048 x 12,288 of projections (a third each),
    16 rows' tails: one head's lanes x the whole chunk a grid step, nothing
    beside the kernel (no pad, no slice, no copy of the projections)."""
    s = _sds(topo)
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    width, rows = 3 * heads * D, 16
    args = (*(s((CHUNK, width // 3), bf),) * 3, s((4, width), bf),
            s((3 * rows, width), f32), s((3 * rows,), i32), s((rows,), i32))
    fn = functools.partial(ksc.kda_short_conv.__wrapped__, heads=heads)
    compiled = jax.jit(fn).lower(*args).compile()
    _assert_kernel(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20
    (call,) = [e for e in jax.make_jaxpr(fn)(*args).eqns
               if e.primitive.name == "pallas_call"]
    assert tuple(call.params["grid_mapping"].grid) == (1, heads)


def _cut(layers=2):
    cfg = resolve_model_config("solar-open2-250b-ep8-8l")
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
    """The served prefill program of the cut (one GQA + one KDA layer, every
    width as published): the packed chunk through the paged flash kernel,
    the chunked KDA kernel and the grouped-expert kernel, pages and state
    pools updated in place."""
    cfg, n, nb = _cut(), CHUNK, 64
    params, kv, i32 = _program_args(topo, cfg)
    nbw = (n - 1) // 32 + 2

    def step(params, kv, tokens, positions, tables, lens, chunk, ids, off,
             read, write):
        stats: list = []
        hidden, kv = llama.forward(
            cfg, params, tokens, positions, kv, tables,
            jnp.zeros((1,), jnp.int32), lens,
            write_blocks={"ids": ids, "start_off": off, "chunk_lens": chunk},
            backend="pallas", moe_stats=stats, moe_backend="pallas",
            pack=llama.pack_index(chunk, lens, n),
            state_slots=(read, write))
        return (llama.compute_logits(cfg, params, hidden[-rows:, 0]), kv,
                sum(s["touched"] for s in stats))

    compiled = jax.jit(step, donate_argnums=1).lower(
        params, kv, i32(n), i32(n), i32(rows, nb), i32(rows), i32(rows),
        i32(rows, nbw), i32(rows), i32(rows), i32(rows)).compile()
    text = compiled.as_text()
    for kernel in ("kda_prefill_attention", "paged_prefill_attention",
                   "moe_prefill_experts"):
        assert kernel in text
    mem = compiled.memory_analysis()
    # every cache array aliases its donated input
    assert mem.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(kv))


def test_decode_window_compiles_for_v5e(topo):  # noqa: F811
    """A fused decode window of 16 rows x 8 steps: the state pools ride the
    loop carry and are advanced in place by the KDA decode kernel; pages are
    committed once behind the loop."""
    cfg, rows, window, nb, block = _cut(), 16, 8, 64, 32
    params, kv, i32 = _program_args(topo, cfg)

    def fn(params, kv, tok, pos0, tables, read, write):
        staged = llama.init_staged_kv(cfg, window, rows, kv_caches=kv)

        def body(k, carry):
            staged, cur, out = carry
            hidden, staged = llama.decode_window_step(
                cfg, params, cur, pos0 + k, kv, tables, staged, k, pos0,
                backend="pallas", moe_stats=[], moe_backend="pallas",
                state_slots=(read, write))
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
    for kernel in ("kda_decode_attention", "paged_decode_attention",
                   "moe_decode_experts"):
        assert kernel in text
    pools = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(kv))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pools
    # no second copy of the state pools (142 MiB a KDA layer) in the loop
    assert mem.temp_size_in_bytes < 2 * SLOTS * HEADS * D * D * 4
