"""Pallas TPU kernel: one MoE layer's routed experts at few tokens, reading
only the experts that got a pair.

A decode step of a many-expert model routes N rows x k pairs onto E experts
and cannot cover them (16 rows x top-6 of 128 touch about a quarter of the
experts at the live rows a serving batch holds): the all-experts einsum of
models/llama.py:_experts_all streams every expert's matrices from HBM to
multiply rows of zeros. Here the grid walks a COMPACTED list of the touched
experts — `ids`, scalar-prefetched, the touched experts' indices in
ascending order at the front and the last of them repeated behind — and the
weight blocks' index maps name `(layer, ids[slot], ...)`: a slot past
`n_touched` names the block that is already resident, so the pipeline
issues no copy for it, and its body is skipped.

One grid step is one expert (or one tile of its inner width, where a whole
expert's three matrices do not fit the VMEM budget twice): all N rows,
`act(x Wg) * (x Wu)` in float32, times that expert's column of the (N, E)
combine weights (0 for a row that did not choose it), rounded to the
model's dtype once, times Wd, accumulated in float32 over tiles and experts
and written once at the last step. SwiGLU splits over the inner width, so
gate, up and down fuse in one pass and no (N, E, inner) intermediate
exists. A row's k experts are added in ascending expert order with exact
zeros between them, whatever the other rows chose: a row's result does not
depend on its batch.

The stacked expert leaves `(L, E, in, out)` are read in place at a static
layer index (a slice that feeds a custom call would be copied:
models/llama.py:_grouped_mm). An int8 leaf `{"q", "s"}` is widened a tile
at a time in VMEM and its per-output-channel scale multiplies the float32
product.

The device op is named `moe_decode_experts` (the jitted wrapper's name).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# What the double-buffered weight blocks of one grid step may hold, of the
# VMEM_LIMIT the kernel asks the compiler for (a v5e core has 128 MiB of
# VMEM; 16 MiB is only the default scope). A whole Kanana expert (3 x 3 MiB
# bf16) or Laguna expert (3 x 6 MiB) fits twice; wider ones go by tiles of
# the inner width.
WEIGHT_VMEM_BUDGET = 40 * 2**20
VMEM_LIMIT = 64 * 2**20
# rows are padded to whole bf16 sublane tiles
ROW_TILE = 16


def _inner_tiles(h: int, inner: int, itemsize: int) -> int:
    """Tiles of the inner width a grid step is cut into: the fewest whose
    gate, up and down blocks fit the budget twice, each a whole number of
    128-lane tiles. An expert that cannot be cut so goes whole."""
    for tiles in range(1, inner // 128 + 1):
        if inner % (tiles * 128):
            continue
        if 2 * 3 * h * (inner // tiles) * itemsize <= WEIGHT_VMEM_BUDGET:
            return tiles
    return 1


def touched_ids(group_sizes: jax.Array, slots: int):
    """(ids (slots,), n_touched (1,)) int32 from the pairs each expert got:
    the experts with at least one pair, ascending, at the front of `ids`,
    the last of them repeated behind (expert 0 where none was touched)."""
    order = jnp.argsort(group_sizes == 0, stable=True).astype(jnp.int32)
    n = jnp.sum(group_sizes > 0, dtype=jnp.int32)
    slot = jnp.arange(slots, dtype=jnp.int32)
    ids = order[jnp.minimum(slot, jnp.maximum(n - 1, 0))]
    return ids, n.reshape(1)


def _kernel(ids_ref, n_ref, x_ref, cw_ref, *refs, act, quantized):
    if quantized:
        (wg_ref, wu_ref, wd_ref, sg_ref, su_ref, sd_ref,
         o_ref, acc_ref) = refs
    else:
        wg_ref, wu_ref, wd_ref, o_ref, acc_ref = refs
        sg_ref = su_ref = sd_ref = None
    slot, tile = pl.program_id(0), pl.program_id(1)
    last_tile = pl.num_programs(1) - 1

    @pl.when((slot == 0) & (tile == 0))
    def _reset():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(slot < n_ref[0])
    def _expert():
        x = x_ref[...]

        def mm(a, w_ref, s_ref):
            w = w_ref[0, 0]
            out = jnp.dot(a, w.astype(a.dtype) if quantized else w,
                          preferred_element_type=jnp.float32)
            return out * s_ref[0, 0] if quantized else out

        inner = act(mm(x, wg_ref, sg_ref)) * mm(x, wu_ref, su_ref)
        # this expert's column of the combine weights: one value a row,
        # picked by a lane mask (exact: every other lane adds 0)
        cw = cw_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, cw.shape, 1)
        col = jnp.sum(jnp.where(lane == ids_ref[slot], cw, 0.0),
                      axis=1, keepdims=True)
        acc_ref[...] += mm((inner * col).astype(x.dtype), wd_ref, sd_ref)

    @pl.when((slot == pl.num_programs(0) - 1) & (tile == last_tile))
    def _write():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("layer", "slots", "act", "interpret"))
def moe_decode_experts(
    xf: jax.Array,  # (N, h) — the rows
    combine: jax.Array,  # (N, E) float32 — routing weight or 0
    group_sizes: jax.Array,  # (E,) int32 — pairs each held expert got
    gate,  # (L, E, h, inner), or an int8 leaf {"q", "s": (L, E, 1, inner)}
    up,  # as gate
    down,  # (L, E, inner, h), or {"q", "s": (L, E, 1, h)}
    *,
    layer: int,  # this layer's index in the stacked leaves
    slots: int,  # grid slots: min(E, N * k), what the pairs can touch
    act,  # the gate's activation (float32 -> float32)
    interpret: bool = False,
) -> jax.Array:
    """The routed experts' part of one layer's MLP output, (N, h): for each
    row the sum over its experts of weight * (act(x Wg) * (x Wu)) Wd."""
    n, h = xf.shape
    quantized = isinstance(gate, dict)
    wg, wu, wd = ((w["q"] if quantized else w) for w in (gate, up, down))
    e, inner = wg.shape[1], wg.shape[3]
    tiles = _inner_tiles(h, inner, wg.dtype.itemsize)
    ti = inner // tiles
    pad = -n % ROW_TILE
    xf = jnp.pad(xf, ((0, pad), (0, 0)))
    combine = jnp.pad(combine.astype(jnp.float32), ((0, pad), (0, 0)))
    ids, n_touched = touched_ids(group_sizes, slots)

    # a slot past the touched experts names the last tile of the last of
    # them: the block the step before it left resident
    def tile_of(s, t, n_ref):
        return jnp.where(s < n_ref[0], t, tiles - 1)

    def by_column(s, t, ids_ref, n_ref):  # gate, up: (h, inner) matrices
        return (layer, ids_ref[s], 0, tile_of(s, t, n_ref))

    def by_row(s, t, ids_ref, n_ref):  # down: (inner, h)
        return (layer, ids_ref[s], tile_of(s, t, n_ref), 0)

    def whole(s, t, ids_ref, n_ref):  # down's scale: (1, h)
        return (layer, ids_ref[s], 0, 0)

    def resident(s, t, ids_ref, n_ref):
        return (0, 0)

    in_specs = [
        pl.BlockSpec((n + pad, h), resident),
        pl.BlockSpec((n + pad, e), resident),
        pl.BlockSpec((1, 1, h, ti), by_column),
        pl.BlockSpec((1, 1, h, ti), by_column),
        pl.BlockSpec((1, 1, ti, h), by_row),
    ]
    operands = [xf, combine, wg, wu, wd]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, 1, ti), by_column),
            pl.BlockSpec((1, 1, 1, ti), by_column),
            pl.BlockSpec((1, 1, 1, h), whole),
        ]
        operands += [gate["s"], up["s"], down["s"]]
    out = pl.pallas_call(
        functools.partial(_kernel, act=act, quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # ids, n_touched
            grid=(slots, tiles),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((n + pad, h), resident),
            scratch_shapes=[pltpu.VMEM((n + pad, h), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n + pad, h), xf.dtype),
        # experts run in order: one accumulator, ascending experts
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        name="moe_decode_experts",
        interpret=interpret,
    )(ids, n_touched, *operands)
    return out[:n]
