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

`--decode` is the decode arm (ISSUE 36), the evidence behind `_moe_mlp`'s
third regime and its crossover: the fused touched-experts kernel
(`ops/moe_experts_pallas.py:moe_decode_experts`) against `_experts_all` and
`ragged_dot` at 16, 32 and 64 rows, for Kanana-2's (128 x 2048 x 768 bf16,
7 layers), Laguna's (64 held of 256 x 3072 x 1024 bf16, 8 layers) and
OLMoE's (64 x 2048 x 1024 int8, 16 layers) expert stacks as served, with a
GIVEN share of the held experts touched (10 / 25 / 50 / 75 / 100%, or what
rows x k pairs can reach): the routing is an input of the timed program, so
one compile serves every share. Prints one JSON line per (model, rows,
share, variant): ms a layer, the share of 819 GB/s reached on the touched
experts' bytes, and the kernel's largest difference from the einsum.

    python benchmarks/sweep_moe.py --decode [--models kanana-2-30b-a3b-8l,...]
        [--rows 16,32,64] [--shares 10,25,50,75,100]

`--prefill` is the prefill arm (ISSUE 38), the evidence behind the
"grouped_kernel" form: the same three expert stacks as served at 512 to
8,192 tokens, the fused grouped-expert kernel
(`ops/moe_experts_pallas.py:moe_prefill_experts`) against `ragged_dot` and
(up to 2,048 tokens) the all-experts einsum, each as the model runs it —
sort, gather, experts, un-sort, combine — and, `*_only`, the expert
computation alone on rows that are already sorted; `aligned*` is the same
kernel over the other layout (every group starts on a row-tile boundary:
no tile straddles, M + E x tile rows gathered). The routing is an operand:
`balanced` is a random router's, `skewed` the same logits plus a
per-expert bias of 1.5 standard deviations. Prints one JSON line per
(model, tokens, routing, variant): ms a layer and the share of 819 GB/s
reached on the touched experts' and the held rows' bytes.

    python benchmarks/sweep_moe.py --prefill [--models ...]
        [--tokens 512,1024,2048,8192] [--row-tiles 128,256] [--aligned]
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
from vllm_production_stack_tpu.ops.moe_experts_pallas import (  # noqa: E402
    moe_prefill_experts,
)

LOOP_ITERS = 2
EINSUM_MAX_TOKENS = 2048  # beyond: three (tokens, 64, 1024) bf16 temps


def _interpret() -> bool:
    """Off the chip (a rehearsal) the Pallas kernels are interpreted."""
    return jax.devices()[0].platform != "tpu"


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


DECODE_MODELS = (
    # preset, quantization of the expert leaves as the cell serves them
    ("kanana-2-30b-a3b-8l", None),
    ("laguna-s-2.1-ep4-9l", None),
    ("olmoe-1b-7b", "int8"),
)
DECODE_LOOP_ITERS = 4
HBM_BYTES_PER_S = 819e9  # benchmarks/chip/peaks.json, TPU v5 lite


def given_routing(n: int, k: int, e_held: int, first: int, touched: int):
    """(topv (n, k), topi (n, k)) whose n * k pairs fall on exactly
    min(touched, n * k) of the held experts [first, first + e_held), spread
    evenly and strided over the expert axis; a row's experts are distinct
    where `touched` >= k (fewer: the row's weights on one expert add)."""
    t = max(1, min(touched, n * k))
    pair = jnp.arange(n * k, dtype=jnp.int32).reshape(n, k)
    topi = first + (pair % t) * e_held // t
    topv = jnp.full((n, k), 1.0 / k, jnp.float32)
    return topv, topi.astype(jnp.int32)


def decode_variants(cfg):
    """name -> fn(stacked, layer, xf, topv, topi) -> (n, h): one layer's
    routed experts, three ways, from a given routing."""
    def route(topv, topi):
        local = topi - cfg.first_expert_held
        chosen = jax.nn.one_hot(local, cfg.held_experts, dtype=jnp.float32)
        return chosen, jnp.sum(chosen, axis=(0, 1)).astype(jnp.int32)

    def kernel(st, i, xf, topv, topi):
        return llama._experts_touched(
            cfg, st, i, xf, topv, *route(topv, topi), _interpret())

    def einsum(st, i, xf, topv, topi):
        mp = jax.tree.map(lambda a: a[i], st)
        return llama._experts_all(cfg, mp, xf, topv, route(topv, topi)[0])

    def ragged(st, i, xf, topv, topi):
        return llama._experts_grouped(
            cfg, st, i, xf, topv, topi, route(topv, topi)[1])

    return {"kernel": kernel, "einsum": einsum, "ragged": ragged}


def time_decode(fn, stacked, n_layers, x, routings) -> list[float]:
    """ms a layer for each (topv, topi) of `routings`: every layer in turn
    DECODE_LOOP_ITERS times in one dispatch, compiled once (the routing is
    an operand), each output renormalised into the next input."""

    @jax.jit
    def run(stacked, x0, topv, topi):
        def body(_, xx):
            for i in range(n_layers):
                y = fn(stacked, i, xx, topv, topi).astype(jnp.float32)
                y = y / (jnp.sqrt(jnp.mean(y * y, -1, keepdims=True)) + 1e-6)
                xx = (xx + y.astype(xx.dtype)) * 0.7071
            return xx

        return jax.lax.fori_loop(0, DECODE_LOOP_ITERS, body, x0)

    compiled = run.lower(stacked, x, *routings[0]).compile()
    out = []
    for topv, topi in routings:
        compiled(stacked, x, topv, topi).block_until_ready()
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            compiled(stacked, x, topv, topi).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        out.append(best / (DECODE_LOOP_ITERS * n_layers) * 1e3)
    return out


def run_arm(args, arm: str, per_model) -> None:
    """`per_model(args, model, emit)` for each of `--models`; a chip call
    returns the end of its output only, so the lines are also kept where
    such a call brings files back from."""
    dev = jax.devices()[0]
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/sweep_moe_{arm}.jsonl", "a") as kept:
        def emit(row):
            for f in (sys.stdout, kept):
                print(json.dumps(row), file=f, flush=True)

        emit({"platform": dev.platform, "kind": dev.device_kind, "arm": arm})
        for model in args.models.split(","):
            per_model(args, model, emit)


def decode_model(args, model: str, emit) -> None:
    """One model's expert stack through every (rows, share, variant)."""
    quant = dict(DECODE_MODELS).get(model)
    cfg = resolve_model_config(model)
    n_layers = args.layers or cfg.num_moe_layers
    e, k = cfg.held_experts, cfg.num_experts_per_tok
    h, it = cfg.hidden_size, cfg.expert_intermediate_size
    dt = jnp.dtype(cfg.dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    stacked = make_stack(cfg, n_layers, quant, keys)
    expert_bytes = 3 * h * it * (1 if quant else dt.itemsize)
    for n in [int(r) for r in args.rows.split(",")]:
        x = jax.random.normal(keys[3], (n, h), jnp.float32).astype(dt)
        counts = sorted({max(1, min(round(e * s / 100), n * k))
                         for s in map(int, args.shares.split(","))})
        routings = [given_routing(n, k, e, cfg.first_expert_held, t)
                    for t in counts]
        fns = decode_variants(cfg)
        one = {name: jax.jit(lambda st, xx, tv, ti, f=f: f(
            st, n_layers - 1, xx, tv, ti)) for name, f in fns.items()}
        diffs = [
            float(jnp.max(jnp.abs(
                one["kernel"](stacked, x, *r).astype(jnp.float32)
                - one["einsum"](stacked, x, *r).astype(jnp.float32))))
            for r in routings]
        for name, fn in fns.items():
            row = {"model": model, "quantization": quant, "rows": n,
                   "layers": n_layers, "variant": name}
            try:
                times = time_decode(fn, stacked, n_layers, x, routings)
            except Exception as err:  # a variant the compiler refuses
                row["error"] = f"{type(err).__name__}: {err}"[:300]
                emit(row)
                continue
            for t, ms, diff in zip(counts, times, diffs):
                line = dict(
                    row, touched=t, touched_share=round(100 * t / e, 1),
                    ms=round(ms, 4),
                    all_bytes_bound_ms=round(
                        e * expert_bytes / HBM_BYTES_PER_S * 1e3, 4),
                    touched_bw_share=round(
                        100 * t * expert_bytes / HBM_BYTES_PER_S
                        / (ms * 1e-3), 1))
                if name == "kernel":
                    line["max_abs_diff_vs_einsum"] = round(diff, 5)
                emit(line)


def prefill_routings(cfg, n: int, key):
    """name -> (topv (n, k), topi (n, k)) over the PUBLISHED experts."""
    k1, k2 = jax.random.split(key)
    logits = jax.random.normal(k1, (n, cfg.num_experts), jnp.float32)
    bias = 1.5 * jax.random.normal(k2, (cfg.num_experts,), jnp.float32)
    out = {}
    for name, lg in (("balanced", logits), ("skewed", logits + bias)):
        topv, topi = jax.lax.top_k(
            jax.nn.softmax(lg, axis=-1), cfg.num_experts_per_tok)
        out[name] = (topv / jnp.sum(topv, -1, keepdims=True),
                     topi.astype(jnp.int32))
    return out


def _held_sizes(cfg, topi):
    local = topi - cfg.first_expert_held
    chosen = jax.nn.one_hot(local, cfg.held_experts, dtype=jnp.int32)
    return jnp.sum(chosen, axis=(0, 1))


def _sorted_pairs(cfg, topi):
    """(pair -> held expert or E, sorted row -> pair, pair held here?), as
    `_experts_grouped` sorts them."""
    local = topi.reshape(-1) - cfg.first_expert_held
    here = (local >= 0) & (local < cfg.held_experts)
    pair_expert = jnp.where(here, local, cfg.held_experts)
    return pair_expert, jnp.argsort(pair_expert, stable=True), here


def _aligned(cfg, tm, stacked, layer, xf, topv, topi):
    """The kernel over the OTHER layout: every group padded to whole row
    tiles (group sizes rounded up are what the kernel is told, so no tile
    straddles), the gather and the un-sort through the padded positions."""
    n, k = topi.shape
    e = cfg.held_experts
    sizes = _held_sizes(cfg, topi)
    pair_expert, order, here = _sorted_pairs(cfg, topi)
    tiles_e = (sizes + tm - 1) // tm
    pstart = (jnp.cumsum(tiles_e) - tiles_e) * tm
    start = jnp.cumsum(sizes) - sizes
    m_pad = n * k + e * tm
    r = jnp.arange(m_pad, dtype=jnp.int32)
    r_e = jnp.minimum(jnp.sum(
        (r // tm)[:, None] >= jnp.cumsum(tiles_e)[None, :], axis=1), e - 1)
    off = r - pstart[r_e]
    srow = jnp.clip(start[r_e] + off, 0, n * k - 1)
    token = jnp.where((off >= 0) & (off < sizes[r_e]), order[srow] // k, 0)
    out = _kernel_mm(cfg, tm, True)(stacked, layer, xf[token], sizes)
    pe = jnp.minimum(pair_expert, e - 1)
    pos = pstart[pe] + jnp.argsort(order) - start[pe]
    out = jnp.where(here[:, None], out[jnp.clip(pos, 0, m_pad - 1)], 0)
    return jnp.einsum("nkh,nk->nh", out.reshape(n, k, -1),
                      jnp.where(here.reshape(n, k), topv, 0).astype(xf.dtype))


def _kernel_mm(cfg, tm: int, aligned: bool):
    """(stacked, layer, rows, sizes) -> rows through the fused kernel;
    `aligned`: the rows are laid out with every group on a tile boundary,
    so the kernel is told the group sizes rounded up to whole tiles."""
    def mm(st, i, xs, sizes):
        if aligned:
            sizes = (sizes + tm - 1) // tm * tm
        return moe_prefill_experts(
            xs, sizes, st["gate"], st["up"], st["down"], layer=i,
            act=llama._activation(cfg), row_tile=tm, interpret=_interpret())
    return mm


def _ragged_mm(cfg):
    def mm(st, i, xs, sizes):
        row_expert = jnp.minimum(jnp.sum(
            jnp.arange(xs.shape[0])[:, None] >= jnp.cumsum(sizes)[None, :],
            axis=1), cfg.held_experts - 1)
        inner = llama._activation(cfg)(
            llama._grouped_mm(xs, st["gate"], i, sizes, row_expert)
        ) * llama._grouped_mm(xs, st["up"], i, sizes, row_expert)
        return llama._grouped_mm(inner, st["down"], i, sizes, row_expert)
    return mm


def _only(cfg, mm):
    """The expert computation alone, on rows that are sorted already (the
    carried array IS the sorted rows; rows behind the groups read 0)."""
    def fn(st, i, xs, topv, topi):
        sizes = _held_sizes(cfg, topi)
        out = mm(st, i, xs, sizes)
        row = jnp.arange(xs.shape[0])[:, None]
        return jnp.where(row < jnp.sum(sizes), out, 0)
    return fn


def prefill_variants(cfg, n: int, row_tiles, aligned: bool):
    """name -> (fn(stacked, layer, x, topv, topi) -> x-shaped, rows of x):
    the whole forms carry the n tokens, the `*_only` ones the sorted rows
    (n * k, and + E x tile where the groups sit on tile boundaries)."""
    m = n * cfg.num_experts_per_tok

    def kernel(st, i, xf, topv, topi):
        return llama._experts_grouped(
            cfg, st, i, xf, topv, topi, _held_sizes(cfg, topi), _interpret())

    def ragged(st, i, xf, topv, topi):
        return llama._experts_grouped(
            cfg, st, i, xf, topv, topi, _held_sizes(cfg, topi))

    def einsum(st, i, xf, topv, topi):
        mp = jax.tree.map(lambda a: a[i], st)
        chosen = jax.nn.one_hot(topi - cfg.first_expert_held,
                                cfg.held_experts, dtype=jnp.float32)
        return llama._experts_all(cfg, mp, xf, topv, chosen)

    out = {"kernel": (kernel, n), "ragged": (ragged, n),
           "ragged_only": (_only(cfg, _ragged_mm(cfg)), m)}
    for tm in row_tiles:
        out[f"kernel_only_tm{tm}"] = (_only(cfg, _kernel_mm(cfg, tm, False)), m)
        if not aligned:
            continue
        out[f"aligned_tm{tm}"] = (functools.partial(_aligned, cfg, tm), n)
        out[f"aligned_only_tm{tm}"] = (
            _only(cfg, _kernel_mm(cfg, tm, True)), m + cfg.held_experts * tm)
    if n <= EINSUM_MAX_TOKENS:
        out["einsum"] = (einsum, n)
    return out


def make_stack(cfg, n_layers: int, quant, keys):
    """The three expert leaves stacked over `n_layers`, as served."""
    e = cfg.held_experts
    h, it = cfg.hidden_size, cfg.expert_intermediate_size
    dt = jnp.dtype(cfg.dtype)

    def make(key, *shape):
        def leaf(kk):
            w = (jax.random.normal(kk, shape, jnp.float32)
                 * shape[-2] ** -0.5).astype(dt)
            return _quantize(w) if quant else w

        return jax.jit(leaf)(key)

    return {"gate": make(keys[0], n_layers, e, h, it),
            "up": make(keys[1], n_layers, e, h, it),
            "down": make(keys[2], n_layers, e, it, h)}


def prefill_model(args, model: str, emit) -> None:
    """One model's expert stack through every (tokens, routing, variant)."""
    quant = dict(DECODE_MODELS).get(model)
    cfg = resolve_model_config(model)
    n_layers = args.layers or cfg.num_moe_layers
    h, it = cfg.hidden_size, cfg.expert_intermediate_size
    dt = jnp.dtype(cfg.dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    stacked = make_stack(cfg, n_layers, quant, keys)
    expert_bytes = 3 * h * it * (1 if quant else dt.itemsize)
    row_tiles = [int(t) for t in args.row_tiles.split(",")]
    for n in [int(t) for t in args.tokens.split(",")]:
        routings = prefill_routings(cfg, n, keys[4])
        sizes = {name: jax.device_get(_held_sizes(cfg, r[1]))
                 for name, r in routings.items()}
        fns = prefill_variants(cfg, n, row_tiles, args.aligned)
        one = {name: jax.jit(lambda st, xx, tv, ti, f=f: f(
            st, n_layers - 1, xx, tv, ti))
            for name, (f, rows) in fns.items() if rows == n}
        x = jax.random.normal(keys[3], (n, h), jnp.float32).astype(dt)
        for name, (fn, rows) in fns.items():
            row = {"model": model, "quantization": quant, "tokens": n,
                   "layers": n_layers, "variant": name}
            try:
                times = time_decode(
                    fn, stacked, n_layers,
                    jax.random.normal(
                        keys[3], (rows, h), jnp.float32).astype(dt),
                    list(routings.values()))
            except Exception as err:  # a variant the compiler refuses
                row["error"] = f"{type(err).__name__}: {err}"[:300]
                emit(row)
                continue
            for (rname, r), ms in zip(routings.items(), times):
                gs = sizes[rname]
                touched, held = int((gs > 0).sum()), int(gs.sum())
                need = touched * expert_bytes + 2 * held * h * dt.itemsize
                line = dict(
                    row, routing=rname, touched=touched, held_pairs=held,
                    largest_group=int(gs.max()), ms=round(ms, 4),
                    bytes_bound_ms=round(need / HBM_BYTES_PER_S * 1e3, 4),
                    flops_bound_ms=round(
                        2.0 * held * 3 * h * it / 197e12 * 1e3, 4),
                    bw_share=round(
                        100 * need / HBM_BYTES_PER_S / (ms * 1e-3), 1))
                if name in one and name != "ragged":
                    line["max_abs_diff_vs_ragged"] = round(float(jnp.max(
                        jnp.abs(one[name](stacked, x, *r).astype(jnp.float32)
                                - one["ragged"](stacked, x, *r).astype(
                                    jnp.float32)))), 5)
                emit(line)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--prefill", action="store_true",
                    help="the prefill arm: fused grouped-expert kernel vs "
                         "ragged_dot vs einsum at 512 to 8,192 tokens")
    ap.add_argument("--row-tiles", default="128")
    ap.add_argument("--aligned", action="store_true",
                    help="--prefill: also the layout with every group on "
                         "a row-tile boundary")
    ap.add_argument("--decode", action="store_true",
                    help="the decode arm: touched-experts kernel vs einsum "
                         "vs ragged_dot at given touched shares")
    ap.add_argument("--models",
                    default=",".join(m for m, _ in DECODE_MODELS))
    ap.add_argument("--rows", default="16,32,64")
    ap.add_argument("--shares", default="10,25,50,75,100")
    ap.add_argument("--model", default="olmoe-1b-7b")
    ap.add_argument("--tokens", default=None,
                    help="default 16,512,1024,2048,8192; --prefill: from 512")
    ap.add_argument("--layers", type=int, default=None,
                    help="layers of int8 experts, stacked as the model "
                         "holds them (default: the model's)")
    args = ap.parse_args()
    if args.decode:
        return run_arm(args, "decode", decode_model)
    if args.prefill:
        args.tokens = args.tokens or "512,1024,2048,8192"
        return run_arm(args, "prefill", prefill_model)
    args.tokens = args.tokens or "16,512,1024,2048,8192"
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
