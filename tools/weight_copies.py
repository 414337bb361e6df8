"""Which operations of a served program write a weight-sized buffer outside a
matmul — read off the optimized HLO the installed TPU compiler produces for a
DESCRIBED v5e:2x2 topology (no chip attached, nothing runs; 2-8 s a compile).

A weight a decode step reads should cross HBM once, inside the fusion of the
dot that contracts against it. Where the dot's result is reshaped to heads and
the leaf is stored (L, in, heads * head_dim), XLA instead slices every layer's
leaf into a buffer of its own a STEP and re-lays the whole stack a window
(PERF.md §6 PR 56): bytes read three times. This is the scan that shows it, and
the design tool for any leaf's layout:

    python -m tools.weight_copies qwen3-8b --quantization int8 --layers 2
    python -m tools.weight_copies kanana-2-30b-a3b-8l --layers 3 --chunk
    python -m tools.weight_copies qwen3-8b --quantization int8 --layers 36 \
        --runner --table 128 --blocks 768     # what the cell runs

It prints every operation of ENTRY and of the loop bodies that is not a dot's
own fusion and writes over `--min-mb` to HBM, those that read weights alone
marked W, and (V) what the compiler prefetches into VMEM from 1 MB upwards;
tests/test_chip_compile.py asserts the same scan marks nothing.
"""

from __future__ import annotations

import argparse
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
             "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "f32": 4, "s32": 4,
             "u32": 4, "f64": 8, "s64": 8, "u64": 8}
_SHAPE = re.compile(
    r"\b(" + "|".join(_ITEMSIZE) + r")\[([\d,]*)\](\{[^}]*\})?")
# ops that write nothing of their own
_FREE = ("parameter", "get-tuple-element", "tuple", "bitcast", "constant",
         "while", "conditional", "call", "after-all", "partition-id",
         "custom-call", "opt-barrier")


def describe_topology():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


def cell_config(model: str, quantization: str | None, layers: int):
    from vllm_production_stack_tpu.models.registry import resolve_model_config

    cfg = resolve_model_config(model, quantization=quantization)
    # (a model that lists its layers keeps the first `layers` of the list)
    cut = {"layers": cfg.layers[:layers]} if cfg.layers else {}
    return dataclasses.replace(cfg, num_layers=layers, **cut)


def abstract_args(topo, cfg, rows: int, window: int, blocks: int, block: int):
    """The param tree, the pools and the staging buffer of `cfg` as shapes on
    the first described chip, and a maker of int32 inputs there."""
    from vllm_production_stack_tpu.models import llama
    from vllm_production_stack_tpu.models.quantization import quantize_params

    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    params = on_chip(jax.eval_shape(
        lambda k: quantize_params(cfg, llama.init_params(cfg, k)),
        jax.random.PRNGKey(0)))
    slots = rows + 1 if cfg.state_layers else 0
    kv = on_chip(jax.eval_shape(lambda: llama.init_kv_cache(
        cfg, blocks, block, state_slots=slots)))
    staged = on_chip(jax.eval_shape(lambda: llama.init_staged_kv(
        cfg, window, rows, kv_caches=kv)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    return params, kv, staged, i32


def compile_window(topo, cfg, rows=16, window=8, table=64, blocks=1024,
                   block=32):
    """The fused decode window as the runner builds it
    (engine/model_runner.py:_build_decode_window_fn): `window` greedy steps in
    a fori_loop, the pool loop-invariant, each step's K/V staged."""
    from vllm_production_stack_tpu.models import llama

    params, kv, staged, i32 = abstract_args(
        topo, cfg, rows, window, blocks, block)
    stateful = bool(cfg.state_layers)

    def fn(params, kv, staged, tokens, positions, tables, hist, slots):
        def body(k, carry):
            tokens, staged, out = carry
            stats: list | None = [] if cfg.num_experts else None
            hidden, staged = llama.decode_window_step(
                cfg, params, tokens, positions + k, kv, tables, staged, k,
                hist, backend="pallas", moe_stats=stats, moe_backend="pallas",
                state_slots=(slots, slots) if stateful else None)
            nxt = jnp.argmax(
                llama.compute_logits(cfg, params, hidden), -1).astype(jnp.int32)
            return nxt, staged, out.at[:, k].set(nxt)

        return jax.lax.fori_loop(
            0, window, body,
            (tokens, staged, jnp.zeros((rows, window), jnp.int32)))[1:]

    return jax.jit(fn).lower(
        params, kv, staged, i32(rows), i32(rows), i32(rows, table), i32(rows),
        i32(rows)).compile(), params


def compile_runner_window(topo, cfg, rows=16, window=8, table=128, blocks=768,
                          block=32):
    """The decode-window program the RUNNER builds (sampling, the pool
    donated and committed after the loop; `ModelRunner
    .decode_window_program`), lowered for the described chip.
    `compile_window` is what the model does; this is what a cell runs, and
    the compiler's memory-space assignment differs between the two: only
    here did it prefetch a 768-page pool into VMEM once the weights' copies
    had left it (PERF.md §6 PR 56)."""
    from vllm_production_stack_tpu.engine import model_runner as mr
    from vllm_production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, SchedulerConfig)
    from vllm_production_stack_tpu.parallel import mesh as mesh_lib

    config = EngineConfig(
        model=cfg,
        cache=CacheConfig(block_size=block, num_blocks=blocks,
                          enable_prefix_caching=False),
        scheduler=SchedulerConfig(
            max_num_seqs=rows, max_num_batched_tokens=512,
            decode_buckets=(rows,), prefill_buckets=(512,),
            decode_window=window),
        attention_backend="pallas", prefill_attention_backend="pallas")
    program = mr.ModelRunner.decode_window_program(
        config, mesh_lib.make_mesh(devices=topo.devices[:1]))
    params, kv, _staged, i32 = abstract_args(
        topo, cfg, rows, window, blocks, block)
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    f32 = sds((rows,), jnp.float32)
    slots = (i32(rows), i32(rows)) if cfg.state_layers else None
    return program.lower(
        params, None, kv, i32(rows), i32(rows), i32(rows, table), None,
        f32, f32, i32(rows), sds((2,), jnp.uint32), sds((rows,), jnp.uint32),
        sds((rows,), jnp.bool_), i32(rows), i32(rows),
        i32(rows, mr.SUPPRESS_IDS), slots, window=window,
        want_logprobs=False, want_min_tokens=False, want_grammar=False,
    ).compile(), params


def vmem_prefetches(text: str, min_bytes: int = 1 << 20) -> list[str]:
    """The async slices and copies of the program that land in VMEM (memory
    space 1) and are at least `min_bytes`: what the compiler's memory-space
    assignment chose to hold there ahead of its use."""
    found = []
    for line in text.splitlines():
        start = re.search(r"= (\(.*\)) (slice|copy)-start\(", line)
        if not start:
            continue
        result = start.group(1)
        result = result[result.find("),") + 1 if result.startswith("((")
                        else 0:]
        dest = _SHAPE.search(result)
        if dest and "S(1)" in (dest.group(3) or "") and math.prod(
                int(d) for d in dest.group(2).split(",") if d
        ) * _ITEMSIZE[dest.group(1)] >= min_bytes:
            found.append(line.strip()[:200])
    return found


def compile_chunk(topo, cfg, rows=4, tokens=2048, table=64, blocks=1024,
                  block=32):
    """The served prefill program: `rows` rows packed on `tokens` positions,
    page-granular KV write, the kernels."""
    from vllm_production_stack_tpu.models import llama

    params, kv, _staged, i32 = abstract_args(topo, cfg, rows, 1, blocks, block)
    nbw = (tokens - 1) // block + 2
    stateful = bool(cfg.state_layers)

    def fn(params, kv, toks, positions, tables, lens, chunk, ids, off, slots):
        stats: list | None = [] if cfg.num_experts else None
        hidden, kv = llama.forward(
            cfg, params, toks, positions, kv, tables,
            jnp.zeros((1,), jnp.int32), lens,
            write_blocks={"ids": ids, "start_off": off, "chunk_lens": chunk},
            backend="pallas", moe_stats=stats, moe_backend="pallas",
            pack=llama.pack_index(chunk, lens, tokens),
            state_slots=(slots, slots) if stateful else None)
        return llama.compute_logits(cfg, params, hidden[-rows:, 0]), kv

    return jax.jit(fn, donate_argnums=1).lower(
        params, kv, i32(tokens), i32(tokens), i32(rows, table), i32(rows),
        i32(rows), i32(rows, nbw), i32(rows), i32(rows)).compile(), params


def _computations(text: str) -> dict[str, list[str]]:
    """name -> its instruction lines, off an HLO module's text."""
    comps: dict[str, list[str]] = {}
    name = None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$", line)
        if head:
            name = ("ENTRY " if head.group(1) else "") + head.group(2)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None and " = " in line:
            comps[name].append(line.strip())
    return comps


def _operands(rhs: str, op: str) -> list[str]:
    """The %names an instruction reads (its call's arguments)."""
    args = rhs[rhs.index(op + "(") + len(op) + 1:]
    depth, i = 1, 0
    while i < len(args) and depth:
        depth += {"(": 1, ")": -1}.get(args[i], 0)
        i += 1
    return re.findall(r"%([\w.\-]+)", args[:i])


def writes_outside_dots(text: str, min_bytes: int = 0) -> list[dict]:
    """Every operation of ENTRY and of the while loops' bodies (the code a
    program runs at top level: fusions, copies, slices) that is not the
    fusion of a dot or convolution and writes at least `min_bytes` to HBM:
    {"where", "op", "name", "elements": [per output buffer], "bytes",
    "of_weights", "scope", "line"}. `of_weights`: everything it reads derives
    from the `params` argument alone (through slices, copies, converts, a
    loop's carry) — it is a copy, a slice or a re-layout of weights, whatever
    its size; an activation has a token's id somewhere behind it."""
    comps = _computations(text)
    has_dot = {name: any(re.search(r"\b(dot|convolution)\(", ln) for ln in lines)
               for name, lines in comps.items()}
    entry = next(n for n in comps if n.startswith("ENTRY "))
    found: list[dict] = []
    todo: list[tuple[str, dict[int, bool]]] = [(entry, {})]
    seen = set()
    while todo:
        where, carried = todo.pop(0)
        if where in seen:
            continue
        seen.add(where)
        # name -> derives from weights alone (None: a constant, neutral);
        # a tuple's name -> its elements'
        weighty: dict[str, bool | None] = {}
        tuples: dict[str, list] = {}
        for ln in comps.get(where, ()):
            lhs, rhs = re.sub(r"/\*index=\d+\*/", "", ln).split(" = ", 1)
            name = lhs.replace("ROOT ", "").strip().lstrip("%")
            op = re.match(r"(?:\([^=]*?\)|\S+)\s+([\w\-]+)\(", rhs)
            if not op:
                continue
            kind = op.group(1)
            reads = _operands(rhs, kind)
            if kind == "parameter":
                weighty[name] = name.startswith("params")
            elif kind in ("constant", "iota"):
                weighty[name] = None
            elif kind == "get-tuple-element":
                at = int(re.search(r"index=(\d+)", rhs).group(1))
                src = tuples.get(reads[0])
                weighty[name] = (src[at] if src is not None
                                 else carried.get(at, False)
                                 if weighty.get(reads[0]) == "carry" else False)
            else:
                of = [weighty.get(r, False) for r in reads]
                weighty[name] = (all(w is not False for w in of)
                                 and any(w is True for w in of))
            if kind == "tuple":
                tuples[name] = [weighty.get(r, False) for r in reads]
            if kind == "while":
                body = re.search(r"body=%?([\w.\-]+)", rhs).group(1)
                todo.append((body, dict(enumerate(tuples.get(reads[0], ())))))
                tuples[name] = tuples.get(reads[0], [])
            if kind == "parameter" and where != entry:
                weighty[name] = "carry"  # the loop's carried tuple
            if kind in _FREE:
                continue
            if kind == "fusion":
                called = re.search(r"calls=%?([\w.\-]+)", rhs)
                if called and has_dot.get(called.group(1)):
                    continue
            # (a buffer in memory space 1 is VMEM's: a prefetch the compiler
            # places where a cut-down stack fits, not bytes written to HBM)
            result = rhs[:rhs.index(kind + "(")]
            if kind.endswith("-start"):
                # an async op's result is (destination, source's alias,
                # context) or ((operands), destination, context)
                result = result[result.find("),") + 1 if result.startswith(
                    "((") else 0:]
                outs = _SHAPE.findall(result)[:1]
            else:
                outs = _SHAPE.findall(result)
            sizes = [(math.prod(int(d) for d in dims.split(",") if d),
                      _ITEMSIZE[dt]) for dt, dims, layout in outs
                     if "S(1)" not in layout]
            nbytes = sum(n * b for n, b in sizes)
            if not sizes or nbytes < min_bytes:
                continue
            scope = re.search(r'op_name="([^"]*)"', rhs)
            found.append({
                "where": where, "op": kind, "name": name,
                "elements": [n for n, _ in sizes], "bytes": nbytes,
                "of_weights": weighty[name] is True,
                "scope": scope.group(1) if scope else "", "line": ln[:240]})
    return found


def weight_sizes(params: dict, min_elements: int = 1 << 20) -> set[int]:
    """The element counts of the layers' matrices: of a stacked leaf and of
    one layer's slice of it, from `min_elements` a slice upwards (a norm's or
    a bias's count is an activation's too)."""
    sizes = set()
    for leaf in jax.tree.leaves(params["layers"]):
        if leaf.ndim >= 3 and leaf.size // leaf.shape[0] >= min_elements:
            sizes |= {leaf.size, leaf.size // leaf.shape[0]}
    return sizes


def weight_copies(text: str, params: dict) -> list[dict]:
    """The operations `writes_outside_dots` finds that read weights alone and
    write a buffer with the element count of a weight leaf, of its slice, or
    of a stack."""
    sizes = weight_sizes(params)
    return [w for w in writes_outside_dots(text)
            if w["of_weights"] and any(n in sizes for n in w["elements"])]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("model")
    ap.add_argument("--quantization", default=None)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--chunk", action="store_true",
                    help="the packed prefill chunk instead of the window")
    ap.add_argument("--runner", action="store_true",
                    help="the window as the RUNNER builds it (sampling, the "
                    "pool committed): what a cell runs")
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--tokens", type=int, default=2048)
    ap.add_argument("--table", type=int, default=64)
    ap.add_argument("--blocks", type=int, default=1024)
    ap.add_argument("--min-mb", type=float, default=2.0)
    args = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    topo = describe_topology()
    cfg = cell_config(args.model, args.quantization, args.layers)
    if args.runner:
        compiled, params = compile_runner_window(
            topo, cfg, rows=args.rows or 16, table=args.table,
            blocks=args.blocks)
    elif args.chunk:
        compiled, params = compile_chunk(
            topo, cfg, rows=args.rows or 4, tokens=args.tokens,
            table=args.table, blocks=args.blocks)
    else:
        compiled, params = compile_window(
            topo, cfg, rows=args.rows or 16, table=args.table,
            blocks=args.blocks)
    text = compiled.as_text()
    sizes = weight_sizes(params)
    total = 0
    for w in writes_outside_dots(text, int(args.min_mb * 2 ** 20)):
        # W: a weight leaf's, slice's or stack's size; w: of weights, another
        mark = (" " if not w["of_weights"] else
                "W" if any(n in sizes for n in w["elements"]) else "w")
        total += w["bytes"] if mark != " " else 0
        print(f"{mark} {w['where'][:28]:28s} {w['op']:8s} "
              f"{w['bytes'] / 2**20:9.1f} MB  {w['name'][:40]:40s} "
              f"{w['scope'][-70:]}")
    for line in vmem_prefetches(text):
        print("V", line[:150])
    print(f"writes of weights outside dots: {total / 2**20:.1f} MB "
          f"({args.model}, {args.layers} layers, "
          f"{'chunk' if args.chunk else 'window'})")


if __name__ == "__main__":
    main()
