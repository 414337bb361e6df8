"""Attention backend sweep: Pallas paged kernels vs the XLA gather.

Measures one layer's decode attention per (block_size, context, batch)
config on the real chip — the evidence behind ModelRunner's
`_resolve_attention_backend` policy (VERDICT r2 #7: the shipped default
must be the measured winner at the shipped config) — and with --prefill one
layer's chunked-prefill attention: first the long-document cells' layers (a
2,048-token chunk behind 0-14k resident tokens, kernel only: ms and the
share of the chip's bf16 peak), then the chat cells' head shapes against
XLA, the evidence behind `resolve_auto_prefill_backend`. docs/22 has both
tables.

    python benchmarks/sweep_attention.py [--quick] [--fp8] [--prefill
        [--longdoc-only]]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


LOOP_ITERS = 64


def time_fn(fn, q, *args) -> float:
    """Per-iteration time of `fn(q, *args)` measured as ONE device
    dispatch running LOOP_ITERS dependent iterations in a lax.fori_loop
    (output feeds back into q): the per-dispatch host overhead is paid
    once per LOOP_ITERS iterations, identically for both backends."""

    @jax.jit
    def run(q0, *a):
        def body(_, qq):
            out = fn(qq, *a)
            return qq + 0.1 * out.reshape(qq.shape)

        return jax.lax.fori_loop(0, LOOP_ITERS, body, q0)

    run(q, *args).block_until_ready()  # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        run(q, *args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / LOOP_ITERS * 1000.0  # ms


def moved(qq, tb):
    """The table as a function of time_fn's loop carry (the same numbers):
    neither backend's reads of the pool can be hoisted out of the loop, as
    no engine step can hoist them."""
    return tb + (qq[0, 0, 0, 0] > 1e30).astype(tb.dtype)


def bench_config(
    batch: int, ctx: int, block_size: int, nh: int, kvh: int, d: int,
    window: int = 16, dtype=jnp.bfloat16, kv_dtype=None,
) -> dict:
    from vllm_production_stack_tpu.ops.attention import (
        paged_attention_with_staged,
    )
    from vllm_production_stack_tpu.ops.paged_attention_pallas import (
        paged_decode_attention,
    )

    rng = np.random.RandomState(0)
    nb = ctx // block_size
    num_blocks = batch * nb + 2
    scale = d ** -0.5
    # fp8 pools: pages + staged window store in the pool dtype, queries
    # stay bf16 — matching the engine's fused-window layout
    kvd = kv_dtype if kv_dtype is not None else dtype

    q = jnp.asarray(rng.randn(batch, nh, d), dtype)
    kv = jnp.asarray(
        rng.randn(2, num_blocks, block_size, kvh, d), kvd
    )
    tables = jnp.asarray(
        rng.randint(1, num_blocks, size=(batch, nb)), jnp.int32
    )
    hist_len = jnp.full((batch,), ctx, jnp.int32)
    staged_k = jnp.asarray(rng.randn(window, batch, kvh, d), kvd)
    staged_v = jnp.asarray(rng.randn(window, batch, kvh, d), kvd)
    step_k = jnp.int32(window - 1)
    hist_mask = jnp.ones((batch, ctx), bool)
    staged_mask = jnp.ones((window,), bool)

    pallas_fn = jax.jit(
        lambda *a: paged_decode_attention(*a, scale=scale)
    )
    pallas_ms = time_fn(
        pallas_fn, q, kv, tables, hist_len, staged_k, staged_v, step_k,
    )

    xla_fn = jax.jit(
        lambda q4, *a: paged_attention_with_staged(q4, *a, scale=scale)
    )
    xla_ms = time_fn(
        xla_fn, q[:, None], kv, tables, hist_mask, staged_k, staged_v,
        staged_mask,
    )
    return {
        "batch": batch, "ctx": ctx, "block_size": block_size,
        "kv_dtype": jnp.dtype(kvd).name,
        "pallas_ms": round(pallas_ms, 3), "xla_ms": round(xla_ms, 3),
        "winner": "pallas" if pallas_ms < xla_ms else "xla",
        "ratio": round(pallas_ms / xla_ms, 2),
    }


def bench_prefill_config(
    batch: int, ctx: int, table_pages: int, nh: int, kvh: int, d: int,
    block_size: int = 32, chunk: int = 512, dtype=jnp.bfloat16,
    kv_dtype=None,
) -> dict:
    """One layer's CHUNKED-PREFILL attention as the engine's prefill program
    runs it: `batch` rows of a `chunk`-token bucket, each holding `ctx`
    tokens INCLUDING its part of the chunk (written before attending) in a
    table `table_pages` wide — the paged flash-prefill kernel vs the XLA
    gather+mask formulation. A row with ctx < chunk is a short prompt in the
    bucket (its tail queries are padding); one with ctx > chunk is a later
    chunk of a long prompt. The XLA path gathers the table's whole width and
    builds a (B, chunk, width) mask whatever the rows hold."""
    from vllm_production_stack_tpu.ops.attention import (
        causal_page_mask, paged_attention_xla,
    )
    from vllm_production_stack_tpu.ops.paged_attention_pallas import (
        paged_prefill_attention,
    )

    rng = np.random.RandomState(0)
    num_blocks = batch * table_pages + 2
    scale = d ** -0.5
    kvd = kv_dtype if kv_dtype is not None else dtype

    q = jnp.asarray(rng.randn(batch, chunk, nh, d), dtype)
    kv = jnp.asarray(rng.randn(2, num_blocks, block_size, kvh, d), kvd)
    tables = jnp.asarray(
        rng.randint(1, num_blocks, size=(batch, table_pages)), jnp.int32
    )
    ctx_lens = jnp.full((batch,), ctx, jnp.int32)
    start = jnp.full((batch,), max(ctx - chunk, 0), jnp.int32)

    pallas_fn = jax.jit(
        lambda qq, pool, tb, *a: paged_prefill_attention(
            qq, pool, moved(qq, tb), *a, scale=scale)
    )
    pallas_ms = time_fn(pallas_fn, q, kv, tables, ctx_lens, start)

    positions = start[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None, :]

    def xla(qq, pool, tb, lens):
        # the engine builds the mask inside the program too (llama.forward)
        mask = causal_page_mask(positions, lens, table_pages * block_size)
        return paged_attention_xla(qq, pool, moved(qq, tb), mask, scale=scale)

    xla_ms = time_fn(jax.jit(xla), q, kv, tables, ctx_lens)
    return {
        "phase": "prefill", "heads": f"{nh}/{kvh}x{d}", "batch": batch,
        "ctx": ctx, "table_pages": table_pages, "chunk": chunk,
        "block_size": block_size, "kv_dtype": jnp.dtype(kvd).name,
        "pallas_ms": round(pallas_ms, 3), "xla_ms": round(xla_ms, 3),
        "winner": "pallas" if pallas_ms < xla_ms else "xla",
        "ratio": round(pallas_ms / xla_ms, 2),
    }


# a v5e chip's bf16 peak (benchmarks/chip/peaks.json names the source)
V5E_BF16_FLOPS = 197e12
# the long-document cells' layers: (heads, kv heads, head_dim, window) —
# Laguna-S-2.1's full and window layers, Solar-Open2's GQA layers
LONGDOC_LAYERS = [(48, 8, 128, 0), (72, 8, 128, 512), (64, 8, 128, 0)]
LONGDOC_RESIDENT = [0, 2048, 6144, 14336]


def bench_longdoc_prefill(
    nh: int, kvh: int, d: int, window: int, resident: int,
    chunk: int = 2048, table_pages: int = 1024, block_size: int = 32,
    dtype=jnp.bfloat16,
) -> dict:
    """One layer's prefill attention of a long-document chunk: one row, a
    `chunk`-token chunk behind `resident` tokens in a `table_pages`-page
    table, a window layer where `window` > 0 — ms a layer, and the flops of
    the (query, key) pairs the chunk covers (4 x head_dim a pair and head,
    as benchmarks/chip/costs_swa.py counts them) as a share of the chip's
    bf16 peak. No XLA column: its (heads, chunk, table) scores do not fit."""
    from vllm_production_stack_tpu.ops.paged_attention_pallas import (
        paged_prefill_attention, paged_window_prefill_attention,
    )

    rng = np.random.RandomState(0)
    num_blocks = table_pages + 2
    scale = d ** -0.5
    q = jnp.asarray(rng.randn(1, chunk, nh, d), dtype)
    kv = jnp.asarray(rng.randn(2, num_blocks, block_size, kvh, d), dtype)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, num_blocks))[None, :table_pages],
        jnp.int32)
    ctx_lens = jnp.full((1,), resident + chunk, jnp.int32)
    start = jnp.full((1,), resident, jnp.int32)
    kernel = (
        functools.partial(paged_window_prefill_attention, window=window)
        if window else paged_prefill_attention)
    fn = jax.jit(
        lambda qq, pool, tb, *a: kernel(
            qq, pool, moved(qq, tb), *a, scale=scale)
    )
    ms = time_fn(fn, q, kv, tables, ctx_lens, start)
    seen = np.arange(resident, resident + chunk, dtype=np.int64) + 1
    pairs = int((np.minimum(seen, window) if window else seen).sum())
    return {
        "phase": "prefill_longdoc", "heads": f"{nh}/{kvh}x{d}",
        "window": window, "resident": resident, "chunk": chunk,
        "table_pages": table_pages, "pairs": pairs,
        "pallas_ms": round(ms, 3),
        "peak_share_pct": round(
            100.0 * 4.0 * d * nh * pairs / V5E_BF16_FLOPS / (ms * 1e-3), 1),
    }


# the benchmark cells' head shapes (Qwen3-8B, Qwen2-7B, OLMoE-1B-7B)
PREFILL_HEADS = [(32, 8, 128), (28, 4, 128), (16, 16, 128)]
# (rows, tokens a row holds, table pages): the cells' 64-page table at every
# row bucket and at 128-2,048 resident tokens, then the widest table
PREFILL_ROWS = [
    (1, 128, 64), (1, 512, 64), (1, 1024, 64), (1, 2048, 64),
    (2, 512, 64), (4, 512, 64), (4, 2048, 64), (16, 512, 64),
    (1, 512, 128), (1, 2048, 128),
]


def main() -> None:
    import ml_dtypes

    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true")
    p.add_argument("--fp8", action="store_true",
                   help="fp8 (e4m3) KV pool rows — the north-star pool "
                        "config (VERDICT r3 #5: auto must have fp8 "
                        "measurements)")
    p.add_argument("--prefill", action="store_true",
                   help="sweep chunked-prefill attention instead of decode "
                        "(evidence for resolve_auto_prefill_backend)")
    p.add_argument("--longdoc-only", action="store_true",
                   help="with --prefill: stop after the long-document rows")
    args = p.parse_args()
    # llama-1b decode head shape
    nh, kvh, d = 32, 8, 64
    kvd = jnp.dtype(ml_dtypes.float8_e4m3fn) if args.fp8 else None
    if args.prefill:
        if kvd is None:  # the long-document cells' pools are bf16
            for layer in LONGDOC_LAYERS[:1] if args.quick else LONGDOC_LAYERS:
                for resident in LONGDOC_RESIDENT:
                    print(json.dumps(
                        bench_longdoc_prefill(*layer, resident)), flush=True)
        if args.longdoc_only:
            return
        for heads in PREFILL_HEADS[:1] if args.quick else PREFILL_HEADS:
            for batch, ctx, pages in PREFILL_ROWS:
                print(json.dumps(bench_prefill_config(
                    batch, ctx, pages, *heads, kv_dtype=kvd
                )), flush=True)
        return
    configs = [
        (16, 1024, 16), (16, 1024, 32), (16, 1024, 64),
        (16, 4096, 16), (16, 4096, 32), (16, 4096, 64),
    ]
    if not args.quick:
        configs += [(64, 1024, 16), (64, 1024, 64), (64, 4096, 64)]
    for batch, ctx, bs in configs:
        print(json.dumps(bench_config(
            batch, ctx, bs, nh, kvh, d, kv_dtype=kvd
        )), flush=True)


if __name__ == "__main__":
    main()
