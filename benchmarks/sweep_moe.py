"""One MoE layer's expert computation on the chip, three ways — the evidence
behind `models/llama.py:_moe_mlp`'s choice of algorithm (ISSUE 26):

- `einsum`: every expert for every token (what `_moe_mlp` does up to 512
  tokens), int8 operands widened inside the einsum, weighted and summed;
- `ragged`: pairs sorted by expert, `jax.lax.ragged_dot` (what `_moe_mlp`
  does above 512 tokens), int8 experts, and the same with bf16 experts;
- `megablox`: the same sorted pairs through the stock Pallas grouped matmul
  (`jax.experimental.pallas.ops.tpu.megablox.gmm`, bf16 experts only).

At OLMoE-1B-7B's widths (64 experts of 2048 x 1024, 8 per token), at the
decode shape (16 tokens) and prefill shapes (512 to 8,192 tokens). The int8
experts are stacked over the model's 16 layers as the engine holds them and
every layer is run in turn (a layer's slice of a stacked leaf is what the
model's code reads); the bf16 comparators hold one layer (16 do not fit).
Prints one JSON line per (variant, tokens): ms a layer, the compiled
program's temp bytes (a widened or sliced copy of one layer's experts would
be 128 to 256 MiB a leaf) and the int8 expert bytes a layer must read.

    python benchmarks/sweep_moe.py [--tokens 16,512,2048,8192] [--layers 16]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from vllm_production_stack_tpu.models import llama  # noqa: E402
from vllm_production_stack_tpu.models.quantization import (  # noqa: E402
    _quantize,
)
from vllm_production_stack_tpu.models.registry import (  # noqa: E402
    resolve_model_config,
)

LOOP_ITERS = 2
EINSUM_MAX_TOKENS = 2048  # beyond: three (tokens, 64, 1024) bf16 temps


def all_experts(cfg, stacked, layer, x):
    """Every expert on every token (`llama._experts_all`) on layer
    `layer`'s slice of the stacked leaves, as the model slices them."""
    mp = jax.tree.map(lambda a: a[layer], stacked)
    xf = x.reshape(-1, x.shape[-1])
    topv, _, chosen, _ = llama._moe_route(cfg, mp["router"], xf)
    return llama._experts_all(cfg, mp, xf, topv, chosen).reshape(x.shape)


def grouped(cfg, stacked, layer, x, mm=None):
    """The routed pairs through `jax.lax.ragged_dot` over the stacked
    leaves (`llama._experts_grouped`), or with `mm` through another grouped
    matmul on the layer's slice."""
    xf = x.reshape(-1, x.shape[-1])
    topv, topi, _, sizes = llama._moe_route(
        cfg, stacked["router"][layer], xf)
    was = llama._grouped_mm
    if mm is not None:
        llama._grouped_mm = lambda xs, w, i, gs, _rows: mm(xs, w[i], gs)
    try:
        y = llama._experts_grouped(
            cfg, stacked, layer, xf, topv, topi, sizes)
    finally:
        llama._grouped_mm = was
    return y.reshape(x.shape)


def megablox(tiling):
    """The stock Pallas grouped matmul at one (m, k, n) tiling."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    return lambda xs, w, gs: gmm(
        xs, w, gs, preferred_element_type=xs.dtype, tiling=tiling)


def time_layers(fn, stacked, x) -> tuple[float, int]:
    """ms per layer: every layer of `stacked` in turn, LOOP_ITERS times, in
    one dispatch (each output feeds the next input, renormalised so that
    routing keeps moving), and the compiled program's temp bytes."""
    n_layers = stacked["router"].shape[0]

    @jax.jit
    def run(stacked, x0):
        def body(_, xx):
            for i in range(n_layers):
                y = fn(stacked, i, xx).astype(jnp.float32)
                y = y / (jnp.sqrt(jnp.mean(y * y, -1, keepdims=True)) + 1e-6)
                xx = (xx + y.astype(xx.dtype)) * 0.7071
            return xx

        return jax.lax.fori_loop(0, LOOP_ITERS, body, x0)

    compiled = run.lower(stacked, x).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    compiled(stacked, x).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        compiled(stacked, x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / (LOOP_ITERS * n_layers) * 1e3, temp


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="olmoe-1b-7b")
    ap.add_argument("--tokens", default="16,512,1024,2048,8192")
    ap.add_argument("--layers", type=int, default=None,
                    help="layers of int8 experts, stacked as the model "
                         "holds them (default: the model's)")
    args = ap.parse_args()
    cfg = resolve_model_config(args.model)
    dev = jax.devices()[0]
    n_layers = args.layers or cfg.num_layers
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                      "model": args.model, "int8_layers": n_layers,
                      "bf16_layers": 1}), flush=True)
    e, h, it = cfg.num_experts, cfg.hidden_size, cfg.intermediate_size
    dt = jnp.dtype(cfg.dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)

    def w(key, *shape):
        return (jax.random.normal(key, shape, jnp.float32)
                * shape[-2] ** -0.5).astype(dt)

    def tree(layers, leaf):
        # one jitted program per leaf: no float32 copy of a stacked leaf
        def make(key, *shape):
            return jax.jit(lambda k: leaf(w(k, *shape)))(key)

        return {"router": w(keys[0], layers, h, e),
                "gate": make(keys[1], layers, e, h, it),
                "up": make(keys[2], layers, e, h, it),
                "down": make(keys[3], layers, e, it, h)}

    int8 = tree(n_layers, _quantize)
    bf16 = tree(1, lambda a: a)
    expert_bytes = 3 * h * it  # one expert, int8

    for tokens in [int(t) for t in args.tokens.split(",")]:
        x = jax.random.normal(keys[4], (1, tokens, h), jnp.float32).astype(dt)
        touched = jax.jit(lambda st, x: llama._moe_mlp(
            cfg, jax.tree.map(lambda a: a[0], st), x)[1]["touched"])(int8, x)
        variants = [
            ("ragged_int8", lambda st, i, x: grouped(cfg, st, i, x), int8),
            ("ragged_bf16", lambda st, i, x: grouped(cfg, st, i, x), bf16)]
        if tokens <= EINSUM_MAX_TOKENS:
            variants.append((
                "einsum_int8", lambda st, i, x: all_experts(cfg, st, i, x),
                int8))
        pairs = tokens * cfg.num_experts_per_tok
        for tm in sorted({min(128, pairs), min(512, pairs)}):
            variants.append((
                f"megablox_bf16_tm{tm}",
                lambda st, i, x, tm=tm: grouped(
                    cfg, st, i, x, megablox((tm, 1024, 512))),
                bf16))
        for name, fn, stacked in variants:
            row = {"variant": name, "tokens": tokens, "pairs": pairs,
                   "touched": int(touched),
                   "touched_int8_bytes": int(touched) * expert_bytes}
            try:
                ms, temp = time_layers(fn, stacked, x)
                row.update(
                    ms=round(ms, 4), temp_mib=round(temp / 2**20, 1),
                    touched_bytes_bound_ms=round(
                        row["touched_int8_bytes"] / 819e9 * 1e3, 4),
                    flops_bound_ms=round(
                        2.0 * pairs * expert_bytes / 197e12 * 1e3, 4))
            except Exception as err:  # a variant the compiler refuses
                row["error"] = f"{type(err).__name__}: {err}"[:300]
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
