"""The step programs of Ling-3.0-flash's cut (ISSUE 45), compiled by the
installed TPU compiler for a DESCRIBED v5e topology — no chip attached,
nothing runs (tests/test_chip_compile.py says why such a guard exists): a
dense KDA layer, the latent layer and an expert KDA layer at the published
widths (32 heads x 128 in both mixers, 128 held experts of 768 under the
group limit), with the kernels of BOTH mixers and of the experts in one
prefill program and in one decode window."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from vllm_production_stack_tpu.models import llama
from vllm_production_stack_tpu.models.registry import resolve_model_config
from vllm_production_stack_tpu.ops import kda_attention as kda
from vllm_production_stack_tpu.ops import mla_attention_pallas as mk

from test_chip_compile import topo  # noqa: F401
from test_kda_kernels_compile import CHUNK, SLOTS, _program_args


def _cut():
    """Layers 0 (KDA, dense), 5 (latent, experts) and 6 (KDA, experts)."""
    cfg = resolve_model_config("ling-3.0-flash-ep4-7l")
    return dataclasses.replace(
        cfg, num_layers=3, layers=tuple(cfg.layers[i] for i in (0, 5, 6)))


@pytest.mark.parametrize("rows", [1, 2])
def test_prefill_program_compiles_for_v5e(topo, rows):  # noqa: F811
    """The served prefill program: the packed chunk through the chunked KDA
    kernel, the latent prefill kernel and the grouped-expert kernel, latent
    pages and state pools updated in place."""
    cfg, n, nb = _cut(), CHUNK, 64
    assert [s.attn_kind for s in cfg.layer_specs] == [
        "linear", "latent", "linear"]
    assert kda.heads_per_step(cfg.linear_num_heads, cfg.linear_head_dim,
                              2) > 1
    # the latent layer's tile is the Kanana cell's: 128 tokens x 32 heads
    # (tests/test_chip_compile.py compiles that kernel alone)
    assert mk._prefill_tile(n, 8, 32, cfg.num_heads, cfg.latent_width,
                            cfg.kv_lora_rank, 2, 2) == 128
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
                sum(s["touched"] for s in stats),
                sum(s["groups_kept_held"] for s in stats))

    compiled = jax.jit(step, donate_argnums=1).lower(
        params, kv, i32(n), i32(n), i32(rows, nb), i32(rows), i32(rows),
        i32(rows, nbw), i32(rows), i32(rows), i32(rows)).compile()
    text = compiled.as_text()
    for kernel in ("kda_prefill_attention", "mla_prefill_attention",
                   "moe_prefill_experts"):
        assert kernel in text
    # every cache array aliases its donated input
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(kv))


def test_decode_window_compiles_for_v5e(topo):  # noqa: F811
    """A fused decode window of 16 rows x 8 steps: the state pools ride the
    loop carry beside the staged latents and are advanced in place by the
    KDA decode kernel; the latents are committed once behind the loop."""
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
    for kernel in ("kda_decode_attention", "mla_decode_attention",
                   "moe_decode_experts"):
        assert kernel in text
    pools = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(kv))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pools
    # no second copy of the state pools (68 MiB a KDA layer) in the loop
    assert mem.temp_size_in_bytes < 2 * SLOTS * 32 * 128 * 128 * 4
