"""Decode-attention backend sweep: Pallas paged kernel vs XLA gather.

Measures one layer's decode attention per (block_size, context, batch)
config on the real chip — the evidence behind ModelRunner's
`_resolve_attention_backend` policy (VERDICT r2 #7: the shipped default
must be the measured winner at the shipped config).

    python benchmarks/sweep_attention.py [--quick]
"""

from __future__ import annotations

import argparse
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
    batch: int, ctx: int, block_size: int, nh: int, kvh: int, d: int,
    chunk: int = 512, dtype=jnp.bfloat16, kv_dtype=None,
) -> dict:
    """One layer's CHUNKED-PREFILL attention: a chunk-token query tile
    attending [resident history + the chunk's own pages] — the paged
    flash-prefill kernel vs the XLA gather+mask formulation. `ctx` is the
    residency INCLUDING the chunk (the chunk is written before attending),
    so the XLA path gathers ctx tokens and builds a (B, chunk, ctx) mask."""
    from vllm_production_stack_tpu.ops.attention import (
        causal_page_mask, paged_attention_xla,
    )
    from vllm_production_stack_tpu.ops.paged_attention_pallas import (
        paged_prefill_attention,
    )

    rng = np.random.RandomState(0)
    nb = ctx // block_size
    num_blocks = batch * nb + 2
    scale = d ** -0.5
    kvd = kv_dtype if kv_dtype is not None else dtype

    q = jnp.asarray(rng.randn(batch, chunk, nh, d), dtype)
    kv = jnp.asarray(rng.randn(2, num_blocks, block_size, kvh, d), kvd)
    tables = jnp.asarray(
        rng.randint(1, num_blocks, size=(batch, nb)), jnp.int32
    )
    ctx_lens = jnp.full((batch,), ctx, jnp.int32)
    start = jnp.full((batch,), ctx - chunk, jnp.int32)

    pallas_fn = jax.jit(
        lambda qq, *a: paged_prefill_attention(qq, *a, scale=scale)
    )
    pallas_ms = time_fn(pallas_fn, q, kv, tables, ctx_lens, start)

    positions = start[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None, :]
    mask = causal_page_mask(positions, ctx_lens, nb * block_size)
    xla_fn = jax.jit(
        lambda qq, *a: paged_attention_xla(qq, *a, scale=scale)
    )
    xla_ms = time_fn(xla_fn, q, kv, tables, mask)
    return {
        "phase": "prefill", "batch": batch, "ctx": ctx, "chunk": chunk,
        "block_size": block_size, "kv_dtype": jnp.dtype(kvd).name,
        "pallas_ms": round(pallas_ms, 3), "xla_ms": round(xla_ms, 3),
        "winner": "pallas" if pallas_ms < xla_ms else "xla",
        "ratio": round(pallas_ms / xla_ms, 2),
    }


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
    args = p.parse_args()
    # llama-1b decode head shape
    nh, kvh, d = 32, 8, 64
    kvd = jnp.dtype(ml_dtypes.float8_e4m3fn) if args.fp8 else None
    if args.prefill:
        configs = [
            (4, 1024, 16), (4, 1024, 32),
            (4, 4096, 16), (4, 4096, 32), (4, 4096, 64),
        ]
        if not args.quick:
            configs += [(16, 4096, 32), (1, 8192, 32), (1, 8192, 64)]
        for batch, ctx, bs in configs:
            print(json.dumps(bench_prefill_config(
                batch, ctx, bs, nh, kvh, d, kv_dtype=kvd
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
