"""The selected-keys ops (ops/dsa_attention.py) and the step programs that
hold them, compiled by the installed TPU compiler for a DESCRIBED v5e
topology — no chip attached, nothing runs (tests/test_chip_compile.py says
why such a guard exists): the cell's shapes — 64 heads over a 640-wide
latent, an indexer of 32 x 128, top-2,048, a 2,048-token packed chunk of
one, two and sixteen rows over a table of 1,024 pages of 32, 16 decode rows
— and the cut's own widths two layers deep (one layer that selects, one that
borrows, with its experts), each step program with the ops and the expert
kernels in ONE program. The ops are XLA: what is asserted is that they
compile, that the pools are updated in place, and what a program holds
beside its arguments."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from vllm_production_stack_tpu.models import llama
from vllm_production_stack_tpu.models.registry import resolve_model_config
from vllm_production_stack_tpu.ops import dsa_attention as dsa

from test_chip_compile import topo  # noqa: F401

HEADS, WIDTH, RANK, HI, DI, TOPK = 64, 640, 512, 32, 128, 2048
CHUNK, BLOCK, PAGES, TABLE = 2048, 32, 8192, 1024
GIB = 2**30


def _sds(topo):
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)


@pytest.mark.parametrize("rows, selects", [
    (1, True), (2, True), (16, True), (2, False)])
def test_the_chunks_op_compiles_at_the_cells_shapes(topo, rows, selects):  # noqa: F811
    s = _sds(topo)
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32

    def fn(q, lat, tables, ctx, start, off, qi, wi, keys, sel):
        return dsa.dsa_prefill_attention(
            q, lat, tables, ctx, start, off,
            index={"q": qi, "w": wi, "pool": keys} if selects else None,
            selection=None if selects else sel, scale=0.0625, rank=RANK,
            topk=TOPK)

    compiled = jax.jit(fn).lower(
        s((CHUNK, HEADS, WIDTH), bf), s((1, PAGES, BLOCK, WIDTH), bf),
        s((rows, TABLE), i32), s((rows,), i32), s((rows,), i32),
        s((rows,), i32), s((CHUNK, HI, DI), bf), s((CHUNK, HI), f32),
        s((1, PAGES, BLOCK, DI), bf),
        s((CHUNK + dsa.Q_TILE, TABLE * BLOCK), jnp.bool_)).compile()
    # one row's tile at a time whatever the rows: the scores of a tile and
    # the selection of the chunk, never rows x chunk x keys
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * GIB


def test_the_steps_ops_compile_at_the_cells_shapes(topo):  # noqa: F811
    s = _sds(topo)
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    rows, window = 16, 8

    def fn(q, qi, wi, lat, keys, tables, hist, staged, staged_idx, k):
        chosen = dsa.dsa_index_decode(
            qi, wi, keys, tables, hist, staged_idx, k, topk=TOPK)
        return dsa.dsa_decode_attention(
            q, lat, tables, staged, *chosen, scale=0.0625, rank=RANK)

    compiled = jax.jit(fn).lower(
        s((rows, HEADS, WIDTH), bf), s((rows, HI, DI), bf),
        s((rows, HI), f32), s((1, PAGES, BLOCK, WIDTH), bf),
        s((1, PAGES, BLOCK, DI), bf), s((rows, TABLE), i32), s((rows,), i32),
        s((rows, window, WIDTH), bf), s((rows, window, DI), bf),
        s((), i32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.75 * GIB


def _cut(layers=2):
    """The cut's first layers: the dense one that selects, then one that
    borrows, with its experts."""
    cfg = resolve_model_config("glm-5.2-ep16-7l")
    return dataclasses.replace(
        cfg, num_layers=layers, layers=cfg.layers[:layers])


def _program_args(topo, cfg):
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    params = on_chip(jax.eval_shape(
        lambda k: llama.init_params(cfg, k), jax.random.PRNGKey(0)))
    kv = on_chip(jax.eval_shape(
        lambda: llama.init_kv_cache(cfg, PAGES, BLOCK)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one)
    return params, kv, i32


def prefill_program(topo, cfg, rows):  # noqa: F811
    params, kv, i32 = _program_args(topo, cfg)
    n, nbw = CHUNK, (CHUNK - 1) // BLOCK + 2

    def step(params, kv, tokens, positions, tables, lens, chunk, ids, off):
        hidden, kv = llama.forward(
            cfg, params, tokens, positions, kv, tables,
            jnp.zeros((1,), jnp.int32), lens,
            write_blocks={"ids": ids, "start_off": off, "chunk_lens": chunk},
            backend="pallas", pack=llama.pack_index(chunk, lens, n),
            moe_backend="pallas")
        return llama.compute_logits(cfg, params, hidden[-rows:, 0]), kv

    return kv, jax.jit(step, donate_argnums=1).lower(
        params, kv, i32(n), i32(n), i32(rows, TABLE), i32(rows), i32(rows),
        i32(rows, nbw), i32(rows)).compile()


def window_program(topo, cfg, rows=16, window=8):  # noqa: F811
    params, kv, i32 = _program_args(topo, cfg)

    def fn(params, kv, tok, pos0, tables):
        staged = llama.init_staged_kv(cfg, window, rows, kv_caches=kv)

        def body(k, carry):
            staged, cur, out = carry
            hidden, staged = llama.decode_window_step(
                cfg, params, cur, pos0 + k, kv, tables, staged, k, pos0,
                backend="pallas", moe_backend="pallas")
            toks = jnp.argmax(
                llama.compute_logits(cfg, params, hidden), -1
            ).astype(jnp.int32)
            return staged, toks, out.at[:, k].set(toks)

        staged, _, out = jax.lax.fori_loop(
            0, window, body,
            (staged, tok, jnp.zeros((rows, window), jnp.int32)))
        pos = pos0[:, None] + jnp.arange(window)[None]
        slots = (jnp.take_along_axis(tables, pos // BLOCK, axis=1) * BLOCK
                 + pos % BLOCK).reshape(-1)
        return llama.commit_staged_kv(kv, staged, slots), out

    return kv, jax.jit(fn, donate_argnums=1).lower(
        params, kv, i32(rows), i32(rows), i32(rows, TABLE)).compile()


def _pools(kv):
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(kv))


@pytest.mark.parametrize("rows", [1, 16])
def test_prefill_program_compiles_for_v5e(topo, rows):  # noqa: F811
    """The served prefill program of the cut's first two layers (every
    width as published): a layer that selects and one that borrows, the
    index keys written beside the latents, the experts through their
    kernel, every cache leaf updated in place."""
    kv, compiled = prefill_program(topo, _cut(), rows)
    assert "moe_prefill_experts" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _pools(kv)
    assert mem.temp_size_in_bytes < 2.5 * GIB


def test_decode_window_compiles_for_v5e(topo):  # noqa: F811
    """A fused decode window of 16 rows x 8 steps: the staged latents AND
    index keys ride the loop carry, the selection is made once a step by the
    first layer and read by the second, the pools are committed once behind
    the loop."""
    kv, compiled = window_program(topo, _cut())
    assert "moe_decode_experts" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _pools(kv)
    assert mem.temp_size_in_bytes < 1.0 * GIB


if __name__ == "__main__":
    # the WHOLE cut's programs, for a reading of what they hold beside their
    # arguments before a chip call (not a test: two long compiles)
    import sys

    from jax.experimental import topologies

    desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    whole = resolve_model_config("glm-5.2-ep16-7l")
    for name, (kv, c) in (
            [(f"prefill rows={r}", prefill_program(desc, whole, r))
             for r in map(int, sys.argv[1:] or ["1", "16"])]
            + [("window 16x8", window_program(desc, whole))]):
        m = c.memory_analysis()
        print(name, "temp GiB", round(m.temp_size_in_bytes / GIB, 3),
              "args GiB", round(m.argument_size_in_bytes / GIB, 3),
              "alias GiB", round(m.alias_size_in_bytes / GIB, 3),
              "code MiB", round(m.generated_code_size_in_bytes / 2**20, 1),
              flush=True)
