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


# -- many tokens: the sorted pairs of a prefill chunk -------------------------
# (below the decode kernel, whose lines stay where they were: a Mosaic body
# carries its source locations, and a shifted line recompiles every decode
# program that holds it)

# Rows of the sorted pairs a grid step of `moe_prefill_experts` runs under
# one expert's matrices: one MXU pass is 128 rows deep whatever the tile
# holds, so a smaller tile wastes it; a larger one multiplies more of a
# neighbouring group's rows where a tile straddles (table in the docstring).
PREFILL_ROW_TILE = 128


def visit_tables(group_sizes: jax.Array, m_tiles: int, tm: int):
    """The static grid of `moe_prefill_experts` as scalar tables: rows of the
    sorted pairs are cut into `m_tiles` tiles of `tm`, expert e's rows are
    [lo[e], hi[e]), and a VISIT is one (expert, row tile) that share a row.
    Visits run in ascending expert, then tile order — also ascending tile
    order, so the visits of one tile are consecutive. There are at most
    m_tiles + E - 1 (every expert after the first adds at most one tile it
    shares with its predecessor); steps past the last visit repeat it.
    -> (expert (S,), tile (S,), lo (E,), hi (E,), n_visits (1,)) int32."""
    e = group_sizes.shape[0]
    hi = jnp.cumsum(group_sizes, dtype=jnp.int32)
    lo = hi - group_sizes
    first = lo // tm
    visits = jnp.where(group_sizes > 0, (hi - 1) // tm - first + 1, 0)
    vend = jnp.cumsum(visits, dtype=jnp.int32)
    n = vend[-1]
    step = jnp.minimum(jnp.arange(m_tiles + e - 1, dtype=jnp.int32),
                       jnp.maximum(n - 1, 0))
    # the expert whose visits cover this step: the first with vend > step
    expert = jnp.minimum(
        jnp.sum(step[:, None] >= vend[None, :], axis=1, dtype=jnp.int32),
        e - 1)
    tile = first[expert] + step - (vend - visits)[expert]
    return expert, tile, lo, hi, n.reshape(1)


def _prefill_kernel(eid_ref, tid_ref, lo_ref, hi_ref, n_ref, x_ref, *refs,
                    act, quantized, tiles):
    if quantized:
        wg_ref, wu_ref, wd_ref, sg_ref, su_ref, sd_ref, o_ref, *acc = refs
    else:
        wg_ref, wu_ref, wd_ref, o_ref, *acc = refs
        sg_ref = su_ref = sd_ref = None
    step, tile = pl.program_id(0), pl.program_id(1)

    @pl.when(step < n_ref[0])
    def _visit():
        x = x_ref[...]
        tm = x.shape[0]

        def mm(a, w_ref, s_ref):
            w = w_ref[0, 0]
            out = jnp.dot(a, w.astype(a.dtype) if quantized else w,
                          preferred_element_type=jnp.float32)
            return out * s_ref[0, 0] if quantized else out

        inner = act(mm(x, wg_ref, sg_ref)) * mm(x, wu_ref, su_ref)
        y = mm(inner.astype(x.dtype), wd_ref, sd_ref)

        def store(y):
            # this expert's rows of the tile; the others keep what the
            # tile's earlier visits wrote (the block stays resident while
            # consecutive steps name it) or are written by a later one
            e = eid_ref[step]
            row = tid_ref[step] * tm + jax.lax.broadcasted_iota(
                jnp.int32, (tm, 1), 0)
            mine = (row >= lo_ref[e]) & (row < hi_ref[e])
            o_ref[...] = jnp.where(
                mine, y, o_ref[...].astype(jnp.float32)).astype(o_ref.dtype)

        if tiles == 1:
            store(y)
        else:  # an expert cut over its inner width: sum the tiles' parts
            acc_ref, = acc

            @pl.when(tile == 0)
            def _first():
                acc_ref[...] = y

            @pl.when(tile > 0)
            def _add():
                acc_ref[...] += y

            @pl.when(tile == tiles - 1)
            def _last():
                store(acc_ref[...])


@functools.partial(
    jax.jit, static_argnames=("layer", "act", "row_tile", "interpret"))
def moe_prefill_experts(
    xs: jax.Array,  # (M, h) — each pair's token row, sorted by expert
    group_sizes: jax.Array,  # (E,) int32 — pairs each held expert got
    gate,  # (L, E, h, inner), or an int8 leaf {"q", "s": (L, E, 1, inner)}
    up,  # as gate
    down,  # (L, E, inner, h), or {"q", "s": (L, E, 1, h)}
    *,
    layer: int,  # this layer's index in the stacked leaves
    act,  # the gate's activation (float32 -> float32)
    row_tile: int = PREFILL_ROW_TILE,
    interpret: bool = False,
) -> jax.Array:
    """Row i of the sorted pairs through its own expert, (M, h):
    (act(x Wg) * (x Wu)) Wd for the first sum(group_sizes) rows — expert 0's
    group_sizes[0] rows, then expert 1's, ... The rows behind them (pairs
    that landed on experts held elsewhere) are not computed: what their
    output rows hold is unspecified.

    One fused pass in place of three grouped matmuls: the grid walks the
    VISITS of `visit_tables` — (expert, row tile) in ascending order — and
    the weight blocks' index maps name `(layer, expert[step], ...)`, so the
    consecutive visits of one expert name the block that is resident and
    each held expert's gate, up and down matrices cross HBM once a layer
    and chunk; an expert without rows has no visit and steps past the last
    visit name its blocks and skip the body (no copy, ~0.4 us each). A
    visit multiplies the whole tile by that expert's matrices — gate and up
    in float32, their product rounded to the model's dtype once, down
    accumulated in float32 — and writes the expert's own rows of the tile
    (a select against the resident output block). No (pairs, inner)
    intermediate exists in HBM and the sorted rows are read once.

    Layout: a tile STRADDLES groups (the sorted rows are taken as
    `_experts_grouped` lays them out, nothing padded, dispatch and combine
    untouched) and is visited once per group it holds rows of. The
    alternative — groups laid out on tile boundaries with up to E x tile
    rows of padding — saves the straddling visits' MXU passes and pays for
    a gather of M + E x tile rows in the dispatch. On a v5e chip
    (benchmarks/sweep_moe.py --prefill --aligned, 2,048 tokens, ms a layer
    with sort, gather, un-sort and combine; balanced / skewed routing):
        stack                      straddling (this)   on tile boundaries
        Kanana 128 x 2048 x 768     2.683 / 2.044       2.659 / 2.458
        Laguna 64 held x 3072 x 1024  4.288 / 3.357     4.060 / 3.505
        OLMoE 64 x 2048 x 1024 int8   2.487 / 2.413     2.382 / 2.375
    a tie within 5% under a balanced routing (what the other layout saves
    in the kernel it spends on its gather) and up to 17% for straddling
    under a skewed one: the layout that changes nothing outside the kernel
    stays. Tiles of 256 rows read the same to 1% (bf16) or 20% worse (int8
    at 256 rows an expert).

    An expert wider than the VMEM budget goes by tiles of its inner width
    (`_inner_tiles`; its blocks are then read once per VISIT). A row's
    result does not depend on the other rows of the chunk. int8 leaves are
    widened a tile at a time in VMEM, as in `moe_decode_experts`.

    The device op is named `moe_prefill_experts`."""
    m, h = xs.shape
    quantized = isinstance(gate, dict)
    wg, wu, wd = ((w["q"] if quantized else w) for w in (gate, up, down))
    inner = wg.shape[3]
    tiles = _inner_tiles(h, inner, wg.dtype.itemsize)
    ti = inner // tiles
    tm = row_tile
    pad = -m % tm
    xs = jnp.pad(xs, ((0, pad), (0, 0)))
    tables = visit_tables(group_sizes, (m + pad) // tm, tm)

    # a step past the visits names the last tile of the last visit's
    # expert: the block the step before it left resident
    def tile_of(s, t, n_ref):
        return jnp.where(s < n_ref[0], t, tiles - 1)

    def rows(s, t, eid, tid, lo, hi, n):  # the sorted rows, the result
        return (tid[s], 0)

    def by_column(s, t, eid, tid, lo, hi, n):  # gate, up: (h, inner)
        return (layer, eid[s], 0, tile_of(s, t, n))

    def by_row(s, t, eid, tid, lo, hi, n):  # down: (inner, h)
        return (layer, eid[s], tile_of(s, t, n), 0)

    def whole(s, t, eid, tid, lo, hi, n):  # down's scale: (1, h)
        return (layer, eid[s], 0, 0)

    in_specs = [
        pl.BlockSpec((tm, h), rows),
        pl.BlockSpec((1, 1, h, ti), by_column),
        pl.BlockSpec((1, 1, h, ti), by_column),
        pl.BlockSpec((1, 1, ti, h), by_row),
    ]
    operands = [xs, wg, wu, wd]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, 1, ti), by_column),
            pl.BlockSpec((1, 1, 1, ti), by_column),
            pl.BlockSpec((1, 1, 1, h), whole),
        ]
        operands += [gate["s"], up["s"], down["s"]]
    out = pl.pallas_call(
        functools.partial(
            _prefill_kernel, act=act, quantized=quantized, tiles=tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,  # expert, tile, lo, hi, n_visits
            grid=(tables[0].shape[0], tiles),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tm, h), rows),
            scratch_shapes=(
                [pltpu.VMEM((tm, h), jnp.float32)] if tiles > 1 else []),
        ),
        out_shape=jax.ShapeDtypeStruct((m + pad, h), xs.dtype),
        # visits run in order: a tile's visits are consecutive steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        name="moe_prefill_experts",
        interpret=interpret,
    )(*tables, *operands)
    return out[:m]
