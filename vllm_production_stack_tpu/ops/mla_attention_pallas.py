"""Pallas TPU kernels: absorbed latent attention (MLA) over the paged pool.

Built after ops/paged_attention_pallas.py's walk kernels — the pool stays in
HBM, a row's pages are copied into double-buffered VMEM blocks of 256 tokens
by the kernel itself (page ids from the scalar-prefetched block table, the
next block's copies in flight under the current block's arithmetic, the
last block of a row or tile starting the first block of the next), a
flash-style online softmax across blocks in float32 — for a pool that holds
ONE latent a token (ops/mla_attention.py): a block is one (tokens, width)
matrix that is the keys whole and, in its first `rank` columns, the values.
There is one key head, so no per-head column mask and no regrouping: every
query head of a row (decode) or every (token, head) of a query tile
(prefill) is a row of ONE `(rows, width) @ (width, 256)` dot and one
`(rows, 256) @ (256, rank)` dot a block.

The device ops are named `mla_decode_attention` and `mla_prefill_attention`
(the jitted wrappers' names), apart from `paged_decode_attention`: the
benchmark's readers set an op's time against the bytes and operations of
the layout it reads, and a latent is a seventh of this model's expanded
K and V.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention_pallas import DECODE_VMEM_LIMIT

NEG_INF = -1e30

# tokens per page block, both kernels (8 pages of 32)
BLOCK_TOKENS = 256
# Prefill: a grid step is one row's query tile of up to PREFILL_Q_TILE
# tokens, every head of it: heads x tile rows against each block. The tile
# is halved until what a grid step holds fits PREFILL_VMEM_BUDGET of the
# PREFILL_VMEM_LIMIT the kernel asks the compiler for (a v5e core has
# 128 MiB of VMEM; 16 MiB is only the default scope).
PREFILL_Q_TILE = 128
PREFILL_VMEM_BUDGET = 52 * 2**20
PREFILL_VMEM_LIMIT = 64 * 2**20


def _pages_per_block(nb: int, bs: int) -> int:
    """From static shapes alone: a row's blocks start at multiples of
    pages * bs from position 0 whatever the table's width and whoever shares
    the batch, so its output does not depend on either."""
    return min(max(1, BLOCK_TOKENS // bs), nb)


def _flash_block(q, block, valid, carry, scale, rank):
    """One online-softmax update of the query rows against one block of
    latents (tokens, width): keys the block whole, values its first `rank`
    columns. Operands go to the MXU in the query dtype; scores, statistics
    and the accumulator are f32."""
    m_prev, l_prev, acc = carry
    if block.dtype != q.dtype:
        block = block.astype(jnp.float32).astype(q.dtype)
    scores = jax.lax.dot_general(
        q, block, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (rows, tokens)
    if valid is not None:
        scores = jnp.where(valid, scores, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)  # exactly 0 on masked columns
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc = acc * alpha + jax.lax.dot(
        p.astype(block.dtype), block[:, :rank],
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc


def _block_copies(kv_hbm, tables_ref, buf, sems, row, blk, slot):
    """The async copies of block `blk` of `row` into `slot`: table slots
    past the row's pages hold the null page or stale ids — read whole,
    masked by position; slots past the table are clamped."""
    pages, nb = buf.shape[1], tables_ref.shape[1]
    return [
        pltpu.make_async_copy(
            kv_hbm.at[0, tables_ref[row, jnp.minimum(blk * pages + j,
                                                     nb - 1)]],
            buf.at[slot, j],
            sems.at[slot],
        )
        for j in range(pages)
    ]


def _mla_decode_kernel(
    # scalar prefetch
    tables_ref,  # (B, nb) int32 — page id per (row, page-slot)
    hist_ref,  # (B,) int32 — pool history length per row
    step_ref,  # (1,) int32 — iteration index inside the fused window
    # inputs
    q_ref,  # (1, heads, width) absorbed queries of this row
    kv_hbm,  # (1, num_blocks, bs, width) — the whole pool, left in HBM
    staged_ref,  # (1, W, width) — this row's staged window latents
    # output
    out_ref,  # (1, heads, rank)
    # scratch
    buf,  # (2, P, bs, width) — two slots of one block's pages
    sems,  # DMA semaphore per slot
    state,  # SMEM (2,) int32: slot of the next block; is it in flight
    *,
    scale: float,
):
    """One grid step = one row: its ceil(hist_len / (P * bs)) blocks in a
    loop whose trip count is the row's own, then the staged window's
    latents (slot w written iff w <= step) as the last update. A padding
    row (hist_len 0) copies nothing and computes the staged window only."""
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    pages, bs, width = buf.shape[1:]
    nh, rank = out_ref.shape[1], out_ref.shape[2]
    span = pages * bs
    hist = hist_ref[b]
    n_blocks = pl.cdiv(hist, span)
    copies = functools.partial(_block_copies, kv_hbm, tables_ref, buf, sems)

    def start_block(row, blk, slot):
        for copy in copies(row, blk, slot):
            copy.start()

    @pl.when(b == 0)
    def _reset():
        state[0] = 0
        state[1] = 0

    slot0 = state[0]

    @pl.when((n_blocks > 0) & (state[1] == 0))
    def _first_block():
        start_block(b, 0, slot0)

    tokens = jax.lax.broadcasted_iota(jnp.int32, (nh, span), 1)
    q = q_ref[0]  # (heads, width), native dtype

    def visit(i, carry):
        slot = (slot0 + i) % 2

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            start_block(b, i + 1, 1 - slot)

        @pl.when(i + 1 == n_blocks)
        def _next_row():
            nxt = jax.lax.while_loop(
                lambda r: (r < rows)
                & (hist_ref[jnp.minimum(r, rows - 1)] == 0),
                lambda r: r + 1,
                b + 1,
            )

            @pl.when(nxt < rows)
            def _its_first_block():
                start_block(nxt, 0, 1 - slot)

            state[0] = 1 - slot
            state[1] = (nxt < rows).astype(jnp.int32)

        for copy in copies(b, i, slot):
            copy.wait()
        return _flash_block(
            q, buf[slot].reshape(span, width), tokens < hist - i * span,
            carry, scale, rank,
        )

    carry = jax.lax.fori_loop(0, n_blocks, visit, (
        jnp.full((nh, 1), NEG_INF, jnp.float32),
        jnp.zeros((nh, 1), jnp.float32),
        jnp.zeros((nh, rank), jnp.float32),
    ))
    w = staged_ref.shape[1]
    _, l, acc = _flash_block(
        q, staged_ref[0],
        jax.lax.broadcasted_iota(jnp.int32, (nh, w), 1) <= step_ref[0],
        carry, scale, rank,
    )
    out_ref[0] = (acc / l).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "rank", "interpret"))
def mla_decode_attention(
    q_abs: jax.Array,  # (B, heads, width) — one absorbed query per row
    kv: jax.Array,  # (1, num_blocks, bs, width) — the pool, read-only
    block_tables: jax.Array,  # (B, nb) int32
    hist_len: jax.Array,  # (B,) int32 — pool positions < hist_len are valid
    staged: jax.Array,  # (B, W, width) — the fused window's latents
    step_k: jax.Array,  # scalar int32 — current iteration in the window
    *,
    scale: float,
    rank: int,
    interpret: bool = False,
) -> jax.Array:
    """Complete absorbed decode attention (pooled latents + staged window)
    without materializing the per-row gather. Returns (B, heads, rank)."""
    b, nh, width = q_abs.shape
    bs, nb = kv.shape[2], block_tables.shape[1]
    # the window's latents are the kernel's last block: whole sublane tiles
    # of it (a 1-, 2- or 4-step window's rows are not; Mosaic refuses a dot
    # against a one-row operand). Slots past the step are masked anyway.
    w = -(-staged.shape[1] // 16) * 16
    staged = jnp.pad(staged, ((0, 0), (0, w - staged.shape[1]), (0, 0)))
    pages = _pages_per_block(nb, bs)
    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # tables, hist_len, step_k
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, nh, width), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, w, width), lambda i, *_: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, nh, rank), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages, bs, width), kv.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nh, rank), q_abs.dtype),
        # rows run in order: each hands the next its first block in flight
        # (the limit keeps a small pool out of VMEM: paged_attention_pallas)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=DECODE_VMEM_LIMIT),
        interpret=interpret,
    )(block_tables, hist_len, jnp.reshape(step_k, (1,)), q_abs, kv, staged)


def _prefill_vmem(tt: int, pages: int, bs: int, nh: int, width: int,
                  rank: int, kv_itemsize: int, q_itemsize: int) -> int:
    """Bytes of VMEM one prefill grid step holds: the double-buffered query
    and output tiles, the f32 accumulator with its running max and sum
    (STAT_LANES lanes each, a row's value in every lane), two slots of a
    block, and the block's f32 scores with their exponentials in both
    widths."""
    rows = nh * tt
    tiles = 2 * rows * (width + rank) * q_itemsize
    state = rows * (rank + 2 * STAT_LANES) * 4
    slots = 2 * pages * bs * width * kv_itemsize
    scores = rows * pages * bs * (4 + 4 + q_itemsize)
    return tiles + state + slots + scores


def _prefill_tile(t: int, pages: int, bs: int, nh: int, width: int,
                  rank: int, kv_itemsize: int, q_itemsize: int) -> int:
    tt = min(t, PREFILL_Q_TILE)
    while tt > 16 and _prefill_vmem(
        tt, pages, bs, nh, width, rank, kv_itemsize, q_itemsize
    ) > PREFILL_VMEM_BUDGET:
        tt //= 2
    return tt


# Prefill: the running max and sum of a tile's rows are kept STAT_LANES wide,
# a row's value in every lane (a (rows, 1) column holds as many vregs with one
# lane of each in use, and meets the scores and the accumulator only through
# a lane broadcast a vreg).
STAT_LANES = 128


def _lanes(stat, n: int):
    """A lane-replicated (rows, STAT_LANES) statistic as wide as the (rows,
    n) array it meets: whole copies side by side, or its first n lanes."""
    if n % STAT_LANES:
        assert n < STAT_LANES, n
        return stat[:, :n]
    return stat if n == STAT_LANES else pltpu.repeat(stat, n // STAT_LANES, 1)


def _mla_prefill_kernel(
    # scalar prefetch
    tables_ref,  # (B, nb) int32
    ctx_ref,  # (B,) int32 — resident tokens AFTER this chunk (incl. chunk)
    start_ref,  # (B,) int32 — logical position of the chunk's first token
    # inputs
    q_ref,  # (1, Tt * heads, width) — this tile's absorbed queries, rows
    # ordered (token, head): the activations' own order
    kv_hbm,  # (1, num_blocks, bs, width)
    # output
    out_ref,  # (1, Tt * heads, rank)
    # scratch
    buf,  # (2, P, bs, width)
    sems,
    state,  # SMEM (2,) int32
    m_ref,  # (Tt * heads, STAT_LANES) f32 running max, in every lane
    l_ref,  # (Tt * heads, STAT_LANES) f32 running denominator, likewise
    acc_ref,  # (Tt * heads, rank) f32 running numerator
    *,
    scale: float,
    nh: int,
):
    """One grid step = one query tile of one row, all heads as rows of the
    same two dots. The tile walks the blocks its row holds up to the tile's
    last position — none for a padding row or a tile past the row's tokens —
    and hands the next live tile its first block in flight.

    A block is folded into the tile in ONE basic block — the scores' dot,
    the mask, the softmax, the values' dot, nothing under a branch — so the
    compiler's scheduler runs the vector units' work on one part of the rows
    under the MXU's on another: by its own count the MXU is busy in 97% of
    the body's bundles, and cutting the rows into items by hand (what
    ops/paged_attention_pallas.py does, whose body has twice the vector
    work a flop) only adds to it (docs/22 has the counts and the chip's
    readings). The block is the MXU's stationary operand in both dots:
    transposed, as that kernel folds, the queries would be — a weight tile
    for 256 streamed rows where here one serves the tile's thousands. The
    running max and sum live STAT_LANES wide with a row's value in every
    lane, so neither is broadcast along lanes to meet the scores or the
    accumulator.

    Causality comes from iotas alone, on every block and without a branch
    (two vector operations a vreg of scores, under the MXU's time): the
    scheduler feeds chunks with CONTIGUOUS positions, so a query's position
    is chunk_start + its index; resident pages hold earlier chunks AND this
    chunk's latents (the model writes before attending)."""
    b, qt = pl.program_id(0), pl.program_id(1)
    rows, tiles = pl.num_programs(0), pl.num_programs(1)
    pages, bs, width = buf.shape[1:]
    tile_rows, rank = out_ref.shape[1:]
    tt = tile_rows // nh
    span = pages * bs
    copies = functools.partial(_block_copies, kv_hbm, tables_ref, buf, sems)

    def tile_blocks(row, tile):
        ctx, first = ctx_ref[row], start_ref[row] + tile * tt
        return jnp.where(
            first < ctx, pl.cdiv(jnp.minimum(ctx, first + tt), span), 0)

    def start_block(row, blk, slot):
        for copy in copies(row, blk, slot):
            copy.start()

    @pl.when((b == 0) & (qt == 0))
    def _reset():
        state[0] = 0
        state[1] = 0

    n_blocks = tile_blocks(b, qt)
    slot0 = state[0]

    @pl.when((n_blocks > 0) & (state[1] == 0))
    def _first_block():
        start_block(b, 0, slot0)

    ctx, first = ctx_ref[b], start_ref[b] + qt * tt
    key_offsets = jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)

    def visit(i, carry):
        slot = (slot0 + i) % 2

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            start_block(b, i + 1, 1 - slot)

        @pl.when(i + 1 == n_blocks)
        def _next_tile():
            same = (qt + 1 < tiles) & (
                tile_blocks(b, jnp.minimum(qt + 1, tiles - 1)) > 0)
            nxt = jax.lax.while_loop(
                lambda r: (r < rows)
                & (tile_blocks(jnp.minimum(r, rows - 1), 0) == 0),
                lambda r: r + 1,
                b + 1,
            )
            found = same | (nxt < rows)

            @pl.when(found)
            def _its_first_block():
                start_block(
                    jnp.where(same, b, jnp.minimum(nxt, rows - 1)), 0,
                    1 - slot,
                )

            state[0] = 1 - slot
            state[1] = found.astype(jnp.int32)

        for copy in copies(b, i, slot):
            copy.wait()
        key0 = i * span
        block = buf[slot].reshape(span, width)
        if block.dtype != q_ref.dtype:
            block = block.astype(jnp.float32).astype(q_ref.dtype)
        scores = jax.lax.dot_general(
            q_ref[0], block, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (Tt * heads, span)
        # the last key each row attends, from the block's first (built here:
        # hoisted, its 512 vregs are spilled a tile and filled a visit); a
        # token's heads attend alike
        last_key = jnp.minimum(
            first + jax.lax.broadcasted_iota(
                jnp.int32, (tile_rows, 1), 0) // nh,
            ctx - 1,
        ) - key0
        scores = jnp.where(key_offsets <= last_key, scores, NEG_INF)
        # block 0 starts the statistics: the scratch is never cleared
        m_prev = jnp.where(i == 0, NEG_INF, m_ref[...])
        l_prev = jnp.where(i == 0, 0.0, l_ref[...])
        acc = jnp.where(i == 0, 0.0, acc_ref[...])
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - _lanes(m_new, span))  # exactly 0 on masked keys
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc * _lanes(alpha, rank) + jax.lax.dot(
            p.astype(block.dtype), block[:, :rank],
            preferred_element_type=jnp.float32,
        )
        return carry

    jax.lax.fori_loop(0, n_blocks, visit, 0)

    @pl.when(n_blocks > 0)
    def _finalize():
        # every query of a live tile attends key 0 at least: l > 0
        out_ref[0] = (acc_ref[...] * _lanes(1.0 / l_ref[...], rank)).astype(
            out_ref.dtype)

    @pl.when(n_blocks == 0)
    def _padding():
        # never read, but finite
        out_ref[...] = jnp.zeros_like(out_ref)


@functools.partial(jax.jit, static_argnames=("scale", "rank", "interpret"))
def mla_prefill_attention(
    q_abs: jax.Array,  # (B, T, heads, width) — the chunk's absorbed queries
    kv: jax.Array,  # (1, num_blocks, bs, width) — pool, chunk latents in
    block_tables: jax.Array,  # (B, nb) int32
    context_lens: jax.Array,  # (B,) int32 — resident incl. this chunk
    chunk_start: jax.Array,  # (B,) int32 — logical position of q[:, 0]
    *,
    scale: float,
    rank: int,
    interpret: bool = False,
) -> jax.Array:
    """Complete chunked-prefill absorbed attention without the per-row
    gather, any (B, heads, T, S) scores or the (B, T, S) mask. Returns
    (B, T, heads, rank)."""
    b, t, nh, width = q_abs.shape
    bs, nb = kv.shape[2], block_tables.shape[1]
    pages = _pages_per_block(nb, bs)
    tt = _prefill_tile(t, pages, bs, nh, width, rank, kv.dtype.itemsize,
                       q_abs.dtype.itemsize)
    assert t % tt == 0, (t, tt)  # T is a power-of-two bucket
    rows = tt * nh  # a tile's (token, head) pairs: q_abs's own order
    out = pl.pallas_call(
        functools.partial(_mla_prefill_kernel, scale=scale, nh=nh),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # tables, context_lens, chunk_start
            grid=(b, t // tt),
            in_specs=[
                pl.BlockSpec((1, rows, width), lambda i, j, *_: (i, j, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, rows, rank),
                                   lambda i, j, *_: (i, j, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages, bs, width), kv.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((rows, STAT_LANES), jnp.float32),
                pltpu.VMEM((rows, STAT_LANES), jnp.float32),
                pltpu.VMEM((rows, rank), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, t * nh, rank), q_abs.dtype),
        # tiles run in order: each hands the next its first block in flight
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=PREFILL_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(block_tables, context_lens, chunk_start,
      q_abs.reshape(b, t * nh, width), kv)
    return out.reshape(b, t, nh, rank)
