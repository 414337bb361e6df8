"""The two SSD kernels and the step programs that hold them, compiled by the
installed TPU compiler for a DESCRIBED v5e topology — no chip attached,
nothing runs (tests/test_chip_compile.py says why such a guard exists): the
cell's shapes — 32 heads x 128 over a state of 256 in 2 groups, a
2,048-token packed chunk of one, two and sixteen rows, 16 decode rows, 17
state slots in two banks — and the cut's own widths two layers deep, each
step program with the SSD and the paged-attention kernels in ONE program
(every layer holds pages AND state)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from vllm_production_stack_tpu.models import llama
from vllm_production_stack_tpu.models.registry import resolve_model_config
from vllm_production_stack_tpu.ops import ssd_scan as ssd

from test_chip_compile import _assert_kernel, topo  # noqa: F401

HEADS, P, NS, GROUPS, SLOTS, CHUNK = 32, 128, 256, 2, 17, 2048
STATE_BYTES = HEADS * P * NS * 4


def _sds(topo):
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)


@pytest.mark.parametrize("rows", [1, 2, 16])
def test_prefill_kernel_compiles_at_the_cells_shapes(topo, rows):  # noqa: F811
    s = _sds(topo)
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    args = (
        s((2 * SLOTS, HEADS, P, NS), f32), s((rows,), i32), s((rows,), i32),
        s((rows,), jnp.bool_), s((CHUNK, HEADS, P), bf),
        s((CHUNK, GROUPS, NS), bf), s((CHUNK, GROUPS, NS), bf),
        s((CHUNK, HEADS), f32), s((HEADS,), f32), s((HEADS,), f32),
        s((rows,), i32), s((rows,), i32))
    compiled = jax.jit(
        ssd.ssd_prefill_scan.__wrapped__, donate_argnums=0,
    ).lower(*args).compile()
    _assert_kernel(compiled)
    # the pool is updated in place: no second copy of it among the temps
    assert compiled.memory_analysis().temp_size_in_bytes < (
        2 * SLOTS * STATE_BYTES)
    # the grid the compiled kernel walks: several heads of one B / C group
    # a step, the work items of a chunk of `rows` rows
    (call,) = [e for e in jax.make_jaxpr(
        ssd.ssd_prefill_scan.__wrapped__)(*args).eqns
        if e.primitive.name == "pallas_call"]
    per_step = ssd.heads_per_step(HEADS, GROUPS)
    assert 1 < per_step <= HEADS // GROUPS
    assert tuple(call.params["grid_mapping"].grid) == (
        HEADS // per_step, CHUNK // ssd.BLOCK + rows)


@pytest.mark.parametrize("rows", [1, 16])
def test_decode_kernel_compiles_at_the_cells_shapes(topo, rows):  # noqa: F811
    s = _sds(topo)
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    args = (
        s((2 * SLOTS, HEADS, P, NS), f32), s((rows,), i32), s((rows,), i32),
        s((rows, HEADS, P), bf), s((rows, GROUPS, NS), bf),
        s((rows, GROUPS, NS), bf), s((rows, HEADS), f32),
        s((HEADS,), f32), s((HEADS,), f32))
    compiled = jax.jit(
        ssd.ssd_decode_step.__wrapped__, donate_argnums=0,
    ).lower(*args).compile()
    _assert_kernel(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**21


def _cut(layers=2):
    cfg = resolve_model_config("falcon-h1-34b-6l")
    return dataclasses.replace(
        cfg, num_layers=layers, layers=cfg.layers[:layers])


FP8 = "float8_e4m3fn"  # (`--kv-cache-dtype fp8`: chip_smoke.py's control arm)


def _program_args(topo, cfg, blocks=256, block=32, kv_dtype=None):
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    params = on_chip(jax.eval_shape(
        lambda k: llama.init_params(cfg, k), jax.random.PRNGKey(0)))
    kv = on_chip(jax.eval_shape(lambda: llama.init_kv_cache(
        cfg, blocks, block, dtype=kv_dtype, state_slots=SLOTS)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one)
    return params, kv, i32


@pytest.mark.parametrize("rows, kv_dtype", [(1, None), (2, None), (2, FP8)])
def test_prefill_program_compiles_for_v5e(topo, rows, kv_dtype):  # noqa: F811
    """The served prefill program of the cut (two layers, every width as
    published): the packed chunk through the paged flash kernel, the write
    kernel and the chunked SSD kernel of the SAME layer, pages and state
    pools updated in place — over bfloat16 pages and over float8 ones (the
    state stays float32)."""
    cfg, n, nb = _cut(), CHUNK, 64
    params, kv, i32 = _program_args(topo, cfg, kv_dtype=kv_dtype)
    assert kv[0]["kv"].dtype == (kv_dtype or "bfloat16")
    assert kv[0]["s"].dtype == "float32"
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
    for kernel in ("ssd_prefill_scan", "paged_prefill_attention",
                   "paged_kv_write"):
        assert kernel in text
    mem = compiled.memory_analysis()
    # every cache array aliases its donated input
    assert mem.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(kv))


@pytest.mark.parametrize("kv_dtype", [None, FP8])
def test_decode_window_compiles_for_v5e(topo, kv_dtype):  # noqa: F811
    """A fused decode window of 16 rows x 8 steps: the state pools ride the
    loop carry and are advanced in place by the SSD decode kernel beside the
    paged decode kernel of the same layer; pages are committed once behind
    the loop."""
    cfg, rows, window, nb, block = _cut(), 16, 8, 64, 32
    params, kv, i32 = _program_args(topo, cfg, kv_dtype=kv_dtype)

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
    for kernel in ("ssd_decode_step", "paged_decode_attention"):
        assert kernel in text
    pools = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(kv))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pools
    # no second copy of the state pools (136 MiB a layer) in the loop
    assert mem.temp_size_in_bytes < 2 * SLOTS * STATE_BYTES
