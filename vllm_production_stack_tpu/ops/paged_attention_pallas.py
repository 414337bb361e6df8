"""Pallas TPU kernels: paged decode and prefill attention over the HBM KV pool.

The XLA fallback (ops/attention.py:paged_attention_with_staged) materializes
each row's gathered history — (B, S, kvH, D) per layer per window iteration —
in HBM before attending. The decode kernel never materializes the gather and
never walks more of a block table than a row has filled: the pool stays in
HBM, and per row the kernel copies the ceil(hist_len / block_size) pages the
row holds into VMEM itself, several pages (256 tokens) a block, page ids from
the scalar-prefetched block table, the next block's copies in flight while
the current block is computed. A flash-style online softmax accumulates
across blocks, and the window's staged K/V (see
models/llama.py:decode_window_step) are folded in last, so the kernel
computes the complete decode attention output in one op. The prefill
kernel walks the same blocks under one row's query tile, every head of it
resident: dots a kv group (its heads' rows in a few items, scores keys by
queries), not one matrix with the other heads masked: arithmetic, not bytes.

Reference parity: the reference stack delegates this op to vLLM's CUDA
paged-attention kernels inside its engine images (external to its repo);
SURVEY §7.3 ranks a TPU-native equivalent as hard part #1.

Layout notes (pallas_guide.md): last dim 128 lanes — head_dim maps onto
lanes; a page's (token, kv head) pairs map onto sublanes as they lie in the
pool, so a block is one (P * bs * kvH, D) matrix and a decode step is two
dots a block — (heads x D) @ (D x columns) and (heads x columns) @
(columns x D), operands in the pool's/activations' dtype, f32 accumulation
— with the columns of other kv heads masked. Decode stays HBM-bandwidth
bound: the MXU's cost is loading each K/V tile once, which it must do
however the heads are grouped. Measured on a v5e chip: docs/22.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# Decode: tokens per page block. A row's pages are visited DECODE_BLOCK_TOKENS
# at a time (8 pages of 32), cut down where the double-buffered K/V block and
# its score temporaries would not fit DECODE_VMEM_BUDGET of the 16 MiB
# scoped-VMEM limit (tests/test_chip_compile.py), and never wider than the
# block table.
DECODE_BLOCK_TOKENS = 256
DECODE_VMEM_BUDGET = 10 * 2**20


def _decode_block_vmem(
    pages: int, bs: int, nh: int, kvh: int, d: int, kv_itemsize: int,
    q_itemsize: int,
) -> int:
    """Bytes of VMEM one decode grid step holds with `pages` pages a block:
    two K/V slots at the pool's width, a widened copy of the block where the
    pool is narrower than the queries (fp8), and the f32 score rows every
    query head keeps against every (token, kv head) column."""
    lanes = -(-d // 128) * 128
    cols = pages * bs * kvh
    slots = 2 * 2 * cols * lanes * kv_itemsize
    widened = 0 if kv_itemsize >= q_itemsize else 2 * cols * lanes * (
        4 + q_itemsize)
    scores = 4 * (-(-nh // 8) * 8) * cols * 4
    return slots + widened + scores


def _decode_pages_per_block(
    nb: int, bs: int, nh: int, kvh: int, d: int, kv_itemsize: int,
    q_itemsize: int,
) -> int:
    """Pages per block, from static shapes alone: a row's blocks start at
    multiples of pages * bs from position 0 whatever the table's width and
    whoever shares the batch, so its output does not depend on either."""
    pages = max(1, DECODE_BLOCK_TOKENS // bs)
    while pages > 1 and _decode_block_vmem(
        pages, bs, nh, kvh, d, kv_itemsize, q_itemsize
    ) > DECODE_VMEM_BUDGET:
        pages //= 2
    return min(pages, nb)


def _pages_fill_tiles(kvh: int, d: int, itemsize: int) -> bool:
    """Whether a page's minor dims (kvh, D) fill whole tiles of the layout
    Mosaic gives the pool in HBM — 128 lanes, and rows in the power of two
    that holds kvh, from what one 32-bit sublane packs (2 bf16, 4 fp8) up to
    eight sublanes. Only then can a kernel slice single pages out of the pool
    for its own copies; a D=64 pool, one bf16 kv head or two fp8 ones (tp
    shards) are padded in HBM, and only the Pallas pipeline can window them."""
    packing = max(1, 4 // itemsize)
    tile = min(max(packing, 1 << (kvh - 1).bit_length()), 8 * packing)
    return kvh % tile == 0 and d % 128 == 0


def _flash_block(q, keys, values, valid, carry, scale):
    """One online-softmax update of every query head against a block laid out
    as (columns, D), a column being one (token, kv head) pair. Every head's
    row is scored against every column in ONE dot and `valid` keeps the
    columns of the head's own kv head: the MXU's work is set by the K/V tiles
    it must load, which are the same either way, and no per-head slice of the
    head-interleaved pool layout has to be cut. Operands go to the MXU in the
    query dtype (a narrower pool is widened, as ops/attention._to_compute
    does); scores, statistics and the accumulator are f32."""
    m_prev, l_prev, acc = carry
    if keys.dtype != q.dtype:
        # not every pair of dtypes converts directly on every chip: via f32
        keys = keys.astype(jnp.float32).astype(q.dtype)
        values = values.astype(jnp.float32).astype(q.dtype)
    scores = jax.lax.dot_general(
        q, keys, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (nh, columns)
    scores = jnp.where(valid, scores * scale, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)  # exactly 0 on masked columns
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc = acc * alpha + jax.lax.dot(
        p.astype(values.dtype), values, preferred_element_type=jnp.float32
    )
    return m_new, l_new, acc


def _column_tokens(nh: int, cols: int, kvh: int):
    """(nh, cols) int32: the token index of column c (c // kvh) where the
    column's kv head (c % kvh) is the one query head r attends (r // qpk),
    else a token index no length reaches."""
    col = jax.lax.broadcasted_iota(jnp.int32, (nh, cols), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (nh, cols), 0)
    own = col % kvh == row // (nh // kvh)
    return jnp.where(own, col // kvh, jnp.iinfo(jnp.int32).max)


def _fold_staged(q, staged_k_ref, staged_v_ref, step, carry, scale):
    """The last flash update, over the window's staged K/V (W, 1, kvh, D) of
    this row — slot w is written iff w <= step — and the normalized output."""
    w, _, kvh, d = staged_k_ref.shape
    _, l, acc = _flash_block(
        q,
        staged_k_ref[:, 0].reshape(w * kvh, d),
        staged_v_ref[:, 0].reshape(w * kvh, d),
        _column_tokens(q.shape[0], w * kvh, kvh) <= step,
        carry, scale,
    )
    return acc / l


def _decode_walk_kernel(
    # scalar prefetch
    tables_ref,  # (B, nb) int32 — page id per (row, page-slot)
    hist_ref,  # (B,) int32 — pool history length per row
    step_ref,  # (1,) int32 — iteration index inside the fused window
    # inputs
    q_ref,  # (1, nh, D)
    kv_hbm,  # (2, num_blocks, bs, kvh, D) — the whole pool, left in HBM
    staged_k_ref,  # (W, 1, kvh, D) — this row's staged window K
    staged_v_ref,  # (W, 1, kvh, D)
    # output
    out_ref,  # (1, nh, D)
    # scratch
    buf,  # (2, 2, P, bs, kvh, D) — two slots of one block's K and V pages
    sems,  # DMA semaphore per slot
    state,  # SMEM (2,) int32: slot of the next block; is it in flight
    *,
    scale: float,
    window: int = 0,
):
    """One grid step = one row. The row's ceil(hist_len / (P * bs)) page
    blocks are walked in a loop whose trip count is the row's own, each block
    copied HBM->VMEM by P async copies (page ids from the block table) while
    the block before it is computed; the last block of a row starts the first
    block of the next row that has any. A padding row (hist_len 0) copies and
    computes nothing but the staged window.

    `window` > 0 (a window layer): the query at position hist + step sees
    keys at positions > hist + step - window, so the walk starts at the PAGE
    that holds the first of them — pages before it may have been given back
    (engine/kv_cache.py: their table slots hold the null page) and are never
    read — and the head of that page is masked."""
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    nb = tables_ref.shape[1]
    pages, bs, kvh = buf.shape[2], buf.shape[3], buf.shape[4]
    nh, d = q_ref.shape[1], q_ref.shape[2]
    span = pages * bs  # tokens per block
    cols = span * kvh
    hist = hist_ref[b]
    blocks = buf.reshape(2, 2, cols, d)  # (slot, K|V, columns, D)

    # (with `window` == 0 every expression below traces to what it was
    # before the window existed: a full layer's kernel is unchanged)

    def first_page(row):
        """The page a window layer's walk of `row` starts at."""
        return jnp.maximum(
            hist_ref[row] + step_ref[0] - (window - 1), 0) // bs

    if window:
        page0 = first_page(b)
        n_blocks = pl.cdiv(hist - page0 * bs, span)
    else:
        n_blocks = pl.cdiv(hist, span)

    def block_copies(row, blk, slot):
        # table slots past the row's pages hold the null page or stale ids:
        # read whole, masked by position; slots past the table are clamped
        return [
            pltpu.make_async_copy(
                kv_hbm.at[:, tables_ref[row, jnp.minimum(
                    blk * pages + j + first_page(row) if window
                    else blk * pages + j, nb - 1)]],
                buf.at[slot, :, j],
                sems.at[slot],
            )
            for j in range(pages)
        ]

    def start_block(row, blk, slot):
        for copy in block_copies(row, blk, slot):
            copy.start()

    @pl.when(b == 0)
    def _reset():
        state[0] = 0
        state[1] = 0

    slot0 = state[0]

    @pl.when((n_blocks > 0) & (state[1] == 0))
    def _first_block():
        start_block(b, 0, slot0)

    col_tokens = _column_tokens(nh, cols, kvh)
    q = q_ref[0]  # (nh, D), native dtype

    def visit(i, carry):
        slot = (slot0 + i) % 2

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            start_block(b, i + 1, 1 - slot)

        @pl.when(i + 1 == n_blocks)
        def _next_row():
            nxt = jax.lax.while_loop(
                # (a window layer's row has blocks iff it has history:
                # its first page starts before hist, window > decode_window)
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

        for copy in block_copies(b, i, slot):
            copy.wait()
        keys, values = blocks[slot, 0], blocks[slot, 1]
        # the block's first token, as a position of the row
        base = i * span
        if window:
            base = base + page0 * bs
        valid = col_tokens < hist - base
        if window:
            valid &= col_tokens > hist + step_ref[0] - window - base
        return _flash_block(q, keys, values, valid, carry, scale)

    carry = jax.lax.fori_loop(0, n_blocks, visit, (
        jnp.full((nh, 1), NEG_INF, jnp.float32),
        jnp.zeros((nh, 1), jnp.float32),
        jnp.zeros((nh, d), jnp.float32),
    ))

    out_ref[0] = _fold_staged(
        q, staged_k_ref, staged_v_ref, step_ref[0], carry, scale
    ).astype(out_ref.dtype)


def _decode_grid_kernel(
    # scalar prefetch
    tables_ref,  # (B, nb) int32
    hist_ref,  # (B,) int32
    step_ref,  # (1,) int32
    # pipeline inputs
    q_ref,  # (1, nh, D)
    *refs,  # P pool pages (2, 1, bs, kvh, D), staged K and V, out, m, l, acc
    scale: float,
    window: int = 0,
):
    """The same page blocks for pools whose pages the walk kernel cannot
    slice out of HBM (_pages_fill_tiles): the grid is (rows, blocks the table
    could hold), a block's P pages arrive through P pipeline windows, and a
    row's dead steps come FIRST — their windows already name the row's first
    block, so its copies start under the previous row's last block, no step
    re-fetches an unchanged window, and only live steps compute."""
    *page_refs, staged_k_ref, staged_v_ref, out_ref, m_ref, l_ref, acc_ref = refs
    b, j = pl.program_id(0), pl.program_id(1)
    bs, kvh = page_refs[0].shape[2], page_refs[0].shape[3]
    nh, d = q_ref.shape[1], q_ref.shape[2]
    span = len(page_refs) * bs
    hist = hist_ref[b]
    blk = j - (pl.num_programs(1) - pl.cdiv(hist, span))
    q = q_ref[0]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    live = blk >= 0
    if window:
        # the last position the window leaves out; blocks wholly at or
        # before it are not computed (their pages may have been given back)
        behind = hist + step_ref[0] - window
        live &= (blk + 1) * span - 1 > behind

    @pl.when(live)
    def _visit():
        keys, values = (
            jnp.concatenate(
                [r[i, 0].reshape(bs * kvh, d) for r in page_refs], axis=0
            )
            for i in (0, 1)
        )
        col_tokens = _column_tokens(nh, span * kvh, kvh)
        valid = col_tokens < hist - blk * span
        if window:
            valid &= col_tokens > behind - blk * span
        m_ref[:], l_ref[:], acc_ref[:] = _flash_block(
            q, keys, values, valid,
            (m_ref[:], l_ref[:], acc_ref[:]), scale,
        )

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        out_ref[0] = _fold_staged(
            q, staged_k_ref, staged_v_ref, step_ref[0],
            (m_ref[:], l_ref[:], acc_ref[:]), scale,
        ).astype(out_ref.dtype)


# --------------------------------------------------------------------------
# Paged PREFILL (chunked-prefill-aware) flash attention
# --------------------------------------------------------------------------

# Prefill: a grid step is one row's query tile of up to PREFILL_Q_TILE
# tokens, every head of it resident; the row's pages are visited
# PREFILL_BLOCK_TOKENS at a time as in decode, and a block is folded into
# the tile a kv group at a time, the group's query rows in at most
# PREFILL_MAX_ITEMS equal ITEMS of whole PREFILL_SUB_TILEs of a head's
# tokens (_prefill_sub_tile).
# The tile is halved until what a grid step holds (_prefill_vmem) fits
# PREFILL_VMEM_BUDGET of the PREFILL_VMEM_LIMIT the kernel asks the compiler
# for (a v5e core has 128 MiB of VMEM; 16 MiB is only the default scope).
PREFILL_Q_TILE = 256
PREFILL_BLOCK_TOKENS = 256
PREFILL_SUB_TILE = 128
PREFILL_MAX_ITEMS = 4
PREFILL_VMEM_BUDGET = 24 * 2**20
PREFILL_VMEM_LIMIT = 32 * 2**20
# The decode kernels (this file's two and ops/mla_attention_pallas.py's)
# STATE a scoped-VMEM limit far above their own buffers (two page blocks in
# flight, inside the 16 MiB a kernel gets unasked) — to keep XLA from parking
# the kernel's POOL operand in VMEM. XLA reserves a custom call's stated
# limit for the call's duration, and its memory-space assignment prefetches
# an operand across the call only into what is left of a v5e's 128 MiB. The
# pool is `pl.ANY`: where a layer's pool fitted (768 pages of 32 x 8 x 128
# bf16 K and V, 50 MB each) the compiler prefetched every layer's WHOLE pool
# a decode step, 3.5 GB a qwen3 step of which the rows' pages are a few
# percent — `slice-done` 0.78 s and `copy-done` 0.72 s of an 8-s slice (my
# chip run, PR 56, PERF.md §6); before, the copies of the q, k and v weights
# stood in that VMEM. Neither an HBM memory space on the operand's BlockSpec
# nor `pltpu.with_memory_space_constraint(kv, pltpu.HBM)` stops it (libtpu
# 0.0.34: the custom call then carries `input_memory_space_colors` and the
# prefetch stays). So the value is a capacity: compiled for a described v5e
# (tests/test_chip_compile.py), a stated 16 MiB keeps the 768-page pool out
# but lets a 512-page one back in; 96 MiB leaves 32 MiB, which takes no GQA
# pool down to 256 pages nor a latent pool (32 x 640 bf16 a page) of 1,024
# pages — a 512-page latent pool, 21 MB and 16k tokens, is still parked, and
# 128 MiB would stop that too at the price of everything XLA itself keeps
# there across the call (the window's temps 40 -> 66 MB). 96 is what ran.
DECODE_VMEM_LIMIT = 96 * 2**20


def _prefill_sub_tile(tt: int, qpk: int) -> int:
    """Query rows one item holds, out of a kv group's qpk * tt rows ordered
    (head, token). An item is whole UNITS — PREFILL_SUB_TILEs of a head's
    tokens, or whole heads of a tile shorter than that — and a group is cut
    into the FEWEST equal items, but two where it can (one item's softmax
    runs under the next one's dot; every further item is traced and lowered
    again at every engine start): seven heads of a 256-token tile are two
    items of three and a half heads; a group that only many small items
    would divide stays whole."""
    unit = PREFILL_SUB_TILE if tt % PREFILL_SUB_TILE == 0 else tt
    units = qpk * tt // unit
    for items in range(2, PREFILL_MAX_ITEMS + 1):
        if units % items == 0:
            return qpk * tt // items
    return qpk * tt


def _prefill_vmem(
    tt: int, pages: int, bs: int, nh: int, kvh: int, d: int,
    kv_itemsize: int, q_itemsize: int,
) -> int:
    """Bytes of VMEM one prefill grid step holds: the double-buffered query
    and output tiles and the queries regrouped by kv group, the f32
    accumulator with its running max and sum (a row an item each,
    sublane-padded to 8), two K/V slots at the pool's width, and one kv
    group's f32 scores (every item's dot is issued before the first
    softmax) with their exponentials in both widths."""
    lanes = -(-d // 128) * 128
    tiles = 5 * tt * nh * lanes * q_itemsize
    state = nh * tt * (lanes + 2 * 8) * 4
    slots = 2 * 2 * pages * bs * kvh * lanes * kv_itemsize
    scores = (nh // kvh) * tt * pages * bs * (4 + 4 + q_itemsize)
    return tiles + state + slots + scores


def _prefill_tiling(
    t: int, nb: int, bs: int, nh: int, kvh: int, d: int, kv_itemsize: int,
    q_itemsize: int,
) -> tuple[int, int]:
    """(query tile, pages per block), from static shapes alone: as in decode
    a row's blocks start at multiples of pages * bs from position 0."""
    pages = min(max(1, PREFILL_BLOCK_TOKENS // bs), nb)
    tt = min(t, PREFILL_Q_TILE)
    while tt > 16 and _prefill_vmem(
        tt, pages, bs, nh, kvh, d, kv_itemsize, q_itemsize
    ) > PREFILL_VMEM_BUDGET:
        tt //= 2
    return tt, pages


def _kv_heads_by_stride(dtype, kvh: int) -> bool:
    """Whether a kv head's rows of a block are a strided load of 32-bit rows
    (f32 pools; bf16 pools with an even head count, two heads a word), and
    so can be read for a head index known only inside a loop."""
    return dtype.itemsize == 4 or (dtype == jnp.bfloat16 and kvh % 2 == 0)


def _kv_head_rows(buf, slot, which, g, dtype):
    """K (which=0) or V (1) of kv head g in the block held by `slot`, as a
    (tokens, D) matrix in `dtype`. The pool interleaves kv heads under each
    token, so these are every kvh-th row of the block: a strided load of
    32-bit rows — for a bf16 pool of the words that pack heads (2w, 2w+1),
    whose halves are f32s' upper halves — and for any other pool (fp8; an
    odd head count) an index into the head axis with a static g."""
    _, _, pages, bs, kvh, d = buf.shape
    span = pages * bs
    if not _kv_heads_by_stride(buf.dtype, kvh):
        rows = buf[slot, which, :, :, g, :].reshape(span, d)
        return rows.astype(jnp.float32).astype(dtype)
    if buf.dtype.itemsize == 4:
        flat = buf.reshape(2, 2, span * kvh, d)
        return flat.at[slot, which][pl.ds(g, span, stride=kvh), :].astype(dtype)
    words = buf.bitcast(jnp.uint32).reshape(2, 2, span * kvh // 2, d)
    words = words.at[slot, which][pl.ds(g // 2, span, stride=kvh // 2), :]
    # head 2w is the word's low half, head 2w + 1 its high half
    words = (words >> (16 * (g % 2)).astype(jnp.uint32)) << 16
    return pltpu.bitcast(words, jnp.float32).astype(dtype)


def _prefill_walk_kernel(
    # scalar prefetch
    tables_ref,  # (B, nb) int32 — page id per (row, page-slot)
    ctx_ref,  # (B,) int32 — resident tokens AFTER this chunk (incl. chunk)
    start_ref,  # (B,) int32 — logical position of the chunk's first token
    # inputs
    q_ref,  # (1, Tt, nh * D) — this tile's queries, heads side by side
    kv_hbm,  # (2, num_blocks, bs, kvh, D) — the whole pool, left in HBM
    # output
    out_ref,  # (1, Tt, nh * D)
    # scratch
    buf,  # (2, 2, P, bs, kvh, D) — two slots of one block's K and V pages
    sems,  # DMA semaphore per slot
    state,  # SMEM (2,) int32: slot of the next block; is it in flight
    q_rows,  # (kvh, q_per_kv * Tt, D) — the tile's queries by kv group,
    # and in `_finalize` its outputs
    m_ref,  # (items, 1, Q) f32 running max, an item = Q rows of a kv group
    l_ref,  # (items, 1, Q) f32 running denominator
    acc_ref,  # (items, D, Q) f32 running numerator, TRANSPOSED
    *,
    scale: float,
    window: int = 0,
    block: int = 0,
):
    """One grid step = one query tile of one row, all heads. The tile walks
    the page blocks its row holds up to the tile's last position —
    ceil(min(ctx, tile end) / (P * bs)) of them, none for a padding row or a
    tile past the row's tokens, whatever the table's width — each block
    copied HBM->VMEM by P async copies while the block before it is
    computed, and the tile's last block starts the first block of the next
    tile that has any (decode's hand-over).

    A block is folded into the tile one kv group at a time, the group's
    qpk * Tt query rows in ITEMS of Q rows (_prefill_sub_tile), with the
    scores TRANSPOSED: keys (P * bs, D) against an item's queries (Q, D)
    give (keys, queries), so the running max and sum of a query reduce over
    SUBLANES — element-wise maxima and sums of vregs, one rotate tree an
    item — and live as one (1, Q) row an item that broadcasts over sublanes;
    the second dot, V^T (D, keys) against the exponentials (keys, Q), leaves
    the accumulator transposed (D, Q), turned back once a tile in
    `_finalize`. (Scores as (queries, keys), which this kernel had, keep
    the statistics as (rows, 1) columns: one lane of 128 in use, a lane
    reduction and a lane broadcast each a block, and 192 vregs of scores a
    group that every pass spilled — docs/22 has both bundle counts.) A
    group's items sit in ONE basic block, every item's first dot issued
    before the first softmax: the MXU takes its operations in program
    order, so item j + 1's scores stream while item j's exponentials are
    computed, and the second dots follow as their operands get ready.
    Operands as they lie in pool and activations, f32 scores, statistics
    and accumulator.

    The mask comes from iotas alone and is applied to every block, without
    a branch (two vector operations a vreg of scores, under the MXU's
    time): the scheduler feeds chunks with CONTIGUOUS positions
    (scheduler.py: range(start, start + len)), so a query's position is
    chunk_start + its index. Chunked prefill needs no special casing —
    resident pages hold earlier chunks AND this chunk's freshly-written KV
    (forward writes before attending).

    `window` > 0 (a window layer): a query at position p sees keys at
    positions > p - window, so a tile's walk starts at the page that holds
    the first key its FIRST query sees and a tile of Tt queries visits at
    most window + Tt keys (plus page rounding) whatever the context: the
    layer's work is O(T * window), not O(T * context). Pages before that
    one may have been given back and are never read.

    `block` > 0 (generation by diffusion over blocks): a query sees the keys
    up to the END of its block of `block` positions. Chunks start and tiles
    are cut on multiples of `block`, so a tile still walks up to its own
    last position: only `last_keys` changes."""
    b, qt = pl.program_id(0), pl.program_id(1)
    rows, tiles = pl.num_programs(0), pl.num_programs(1)
    nb = tables_ref.shape[1]
    pages, bs, kvh, d = buf.shape[2:]
    tt = q_ref.shape[1]
    qpk = q_ref.shape[2] // d // kvh
    span = pages * bs  # tokens per block
    group = qpk * tt  # query rows of a kv group, ordered (head, token)
    sub = m_ref.shape[2]  # query rows an item folds at a time
    n_sub = group // sub

    def tile_page0(row, tile):
        """The page a window layer's tile starts its walk at."""
        return jnp.maximum(
            start_ref[row] + tile * tt - (window - 1), 0) // bs

    def tile_blocks(row, tile):
        ctx, first = ctx_ref[row], start_ref[row] + tile * tt
        walks = first < ctx
        last = jnp.minimum(ctx, first + tt)
        if window:
            last = last - tile_page0(row, tile) * bs
        return jnp.where(walks, pl.cdiv(last, span), 0)

    def block_copies(row, tile, blk, slot):
        return [
            pltpu.make_async_copy(
                kv_hbm.at[:, tables_ref[row, jnp.minimum(
                    blk * pages + j + tile_page0(row, tile) if window
                    else blk * pages + j, nb - 1)]],
                buf.at[slot, :, j],
                sems.at[slot],
            )
            for j in range(pages)
        ]

    def start_block(row, tile, blk, slot):
        for copy in block_copies(row, tile, blk, slot):
            copy.start()

    @pl.when((b == 0) & (qt == 0))
    def _reset():
        state[0] = 0
        state[1] = 0

    n_blocks = tile_blocks(b, qt)
    slot0 = state[0]

    @pl.when((n_blocks > 0) & (state[1] == 0))
    def _first_block():
        start_block(b, qt, 0, slot0)

    ctx, first = ctx_ref[b], start_ref[b] + qt * tt
    page0 = tile_page0(b, qt) if window else 0
    key_offsets = jax.lax.broadcasted_iota(jnp.int32, (span, sub), 0)

    def last_keys(j):
        """(1, Q): the last key position each query of a group's j-th
        sub-tile attends — a query's lane."""
        r = j * sub + jax.lax.broadcasted_iota(jnp.int32, (1, sub), 1)
        last = first + r % tt
        if block:
            last = (last // block + 1) * block - 1
        return jnp.minimum(last, ctx - 1)

    @pl.when(n_blocks > 0)
    def _regroup_queries():
        # heads lie side by side in the tile's lanes; a kv group's dot wants
        # its heads' rows one under the other
        for h in range(kvh * qpk):
            q_rows[h // qpk, pl.ds(h % qpk * tt, tt), :] = (
                q_ref[0, :, h * d:(h + 1) * d])

    def visit(i, carry):
        slot = (slot0 + i) % 2

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            start_block(b, qt, i + 1, 1 - slot)

        @pl.when(i + 1 == n_blocks)
        def _next_tile():
            # the next grid step that walks any block: this row's next
            # tile, else the first tile of the next row that holds tokens
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
                    jnp.where(same, b, jnp.minimum(nxt, rows - 1)),
                    jnp.where(same, qt + 1, 0) if window else None,
                    0, 1 - slot,
                )

            state[0] = 1 - slot
            state[1] = found.astype(jnp.int32)

        for copy in block_copies(b, qt, i, slot):
            copy.wait()
        key0 = i * span
        if window:
            key0 = key0 + page0 * bs

        def fold(g, j, keys, values):
            """Item j of kv group g against the block, a generator: the
            scores' dot, then the softmax, then the values' dot."""
            it = g * n_sub + j
            scores = jax.lax.dot_general(
                keys, q_rows[g, j * sub:(j + 1) * sub, :],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            ) * scale  # (span, Q)
            yield
            last = last_keys(j) - key0
            seen = key_offsets <= last
            if window:
                # (a padded query sees what the row's last token sees: its
                # row of the output is never read, but stays finite)
                seen &= key_offsets > last - window
            scores = jnp.where(seen, scores, NEG_INF)
            # block 0 starts the statistics: the scratch is never cleared
            m_prev = jnp.where(i == 0, NEG_INF, m_ref[it])
            l_prev = jnp.where(i == 0, 0.0, l_ref[it])
            m_new = jnp.maximum(m_prev, jnp.max(scores, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new)  # exactly 0 on masked keys
            m_ref[it] = m_new
            l_ref[it] = l_prev * alpha + jnp.sum(p, axis=0, keepdims=True)
            p = p.astype(values.dtype)
            yield
            acc = jnp.where(i == 0, 0.0, acc_ref[it])
            acc_ref[it] = acc * alpha + jax.lax.dot_general(
                values, p, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # V^T P^T: (D, Q)

        def attend(g, carry):
            dtype = q_rows.dtype
            keys = _kv_head_rows(buf, slot, 0, g, dtype)
            values = _kv_head_rows(buf, slot, 1, g, dtype)
            items = [fold(g, j, keys, values) for j in range(n_sub)]
            for item in items:  # every item's scores: the MXU runs ahead
                next(item)
            for item in items:
                next(item)
                next(item, None)
            return carry

        # the kv groups in a loop, not unrolled, where a head's rows can be
        # read for a traced head index: the body's size is what every
        # engine start pays to lower, once per prefill program
        if _kv_heads_by_stride(buf.dtype, kvh):
            return jax.lax.fori_loop(0, kvh, attend, carry)
        for g in range(kvh):
            attend(g, carry)
        return carry

    jax.lax.fori_loop(0, n_blocks, visit, 0)

    @pl.when(n_blocks > 0)
    def _finalize():
        # every query of a live tile attends key 0 at least: l > 0. The
        # accumulators are turned back into `q_rows` (the queries are done
        # with) a kv group a loop step — one traced body, not one an item —
        # and leave head by head, as the queries came
        def turn_back(g, carry):
            for j in range(n_sub):
                it = g * n_sub + j
                q_rows[g, j * sub:(j + 1) * sub, :] = (
                    acc_ref[it] * (1.0 / l_ref[it])).T.astype(q_rows.dtype)
            return carry

        jax.lax.fori_loop(0, kvh, turn_back, 0)
        for h in range(kvh * qpk):
            out_ref[0, :, h * d:(h + 1) * d] = (
                q_rows[h // qpk, pl.ds(h % qpk * tt, tt), :])

    @pl.when(n_blocks == 0)
    def _padding():
        # never read, but finite
        out_ref[...] = jnp.zeros_like(out_ref)


def _paged_prefill_attention(
    q: jax.Array,  # (B, T, nh, D) — the chunk's queries
    kv: jax.Array,  # (2, num_blocks, bs, kvh, D) — pool, chunk KV already in
    block_tables: jax.Array,  # (B, nb) int32
    context_lens: jax.Array,  # (B,) int32 — resident incl. this chunk
    chunk_start: jax.Array,  # (B,) int32 — logical position of q[:, 0]
    *,
    scale: float,
    window: int = 0,
    block: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """Complete chunked-prefill attention (resident pages + the chunk's own
    freshly-written pages) without materializing the per-row gather, the
    (B, heads, T, S) scores OR the (B, T, S) mask the XLA path builds.
    Returns (B, T, nh, D). On a chip the pool's pages have to fill whole HBM
    tiles (_pages_fill_tiles): the kernel slices them out of HBM itself.

    Reference parity: covers what the reference's engine images get from
    vLLM's CUDA flash-prefill over paged KV; SURVEY §7.1 names paged
    attention kernels as the TPU-native hard part."""
    b, t, nh, d = q.shape
    kvh, bs = kv.shape[3], kv.shape[2]
    nb = block_tables.shape[1]
    tt, pages = _prefill_tiling(
        t, nb, bs, nh, kvh, d, kv.dtype.itemsize, q.dtype.itemsize
    )
    assert t % tt == 0, (t, tt)  # T is a power-of-two bucket
    sub = _prefill_sub_tile(tt, nh // kvh)
    tile = pl.BlockSpec((1, tt, nh * d), lambda i, j, *_: (i, j, 0))
    # (a window layer's kernel is a kernel of its own, and named so: the
    # device trace tells the two apart)
    out = pl.pallas_call(
        functools.partial(_prefill_walk_kernel, scale=scale, window=window,
                          **({"block": block} if block else {})),
        **({"name": "paged_window_prefill_attention"} if window else {}),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # tables, context_lens, chunk_start
            grid=(b, t // tt),
            in_specs=[tile, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=tile,
            scratch_shapes=[
                pltpu.VMEM((2, 2, pages, bs, kvh, d), kv.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((kvh, nh // kvh * tt, d), q.dtype),
                pltpu.VMEM((nh * tt // sub, 1, sub), jnp.float32),
                pltpu.VMEM((nh * tt // sub, 1, sub), jnp.float32),
                pltpu.VMEM((nh * tt // sub, d, sub), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, t, nh * d), q.dtype),
        # tiles run in order: each hands the next its first block in flight
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=PREFILL_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(block_tables, context_lens, chunk_start, q.reshape(b, t, nh * d), kv)
    return out.reshape(b, t, nh, d)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "block"))
def paged_prefill_attention(q, kv, block_tables, context_lens, chunk_start,
                            *, scale: float, interpret: bool = False,
                            block: int = 0):
    return _paged_prefill_attention(
        q, kv, block_tables, context_lens, chunk_start, scale=scale,
        interpret=interpret, block=block)


paged_prefill_attention.__doc__ = _paged_prefill_attention.__doc__


@functools.partial(jax.jit, static_argnames=("scale", "window", "interpret"))
def paged_window_prefill_attention(
    q, kv, block_tables, context_lens, chunk_start, *, scale: float,
    window: int, interpret: bool = False,
):
    """`paged_prefill_attention` for a window layer: a query at position p
    sees keys at positions > p - window (itself among the `window`). Under
    its own name in the device trace."""
    return _paged_prefill_attention(
        q, kv, block_tables, context_lens, chunk_start, scale=scale,
        window=window, interpret=interpret)


def paged_prefill_attention_sharded(
    mesh,
    q: jax.Array,  # (B, T, nh, D) — batch over dp, heads over tp
    kv: jax.Array,  # (2, num_blocks, bs, kvh, D) — kv heads over tp
    block_tables: jax.Array,  # (B, nb)
    context_lens: jax.Array,  # (B,)
    chunk_start: jax.Array,  # (B,)
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """The prefill kernel under tensor/data parallelism — same shard_map
    placement as paged_decode_attention_sharded: prefill attention is
    embarrassingly parallel over (row, head) once KV pages are head-sharded
    (kv_cache_spec's layout), so no collective is needed."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DP_AXIS, TP_AXIS

    tp = mesh.shape[TP_AXIS]
    nh, kvh = q.shape[2], kv.shape[3]
    if nh % tp or kvh % tp:
        raise ValueError(
            f"pallas prefill under tp={tp} needs heads divisible by tp "
            f"(num_heads={nh}, num_kv_heads={kvh})"
        )
    fn = jax.shard_map(
        functools.partial(
            paged_prefill_attention, scale=scale, interpret=interpret
        ),
        mesh=mesh,
        in_specs=(
            P(DP_AXIS, None, TP_AXIS, None),  # q
            P(None, None, None, TP_AXIS, None),  # kv pool
            P(DP_AXIS, None),  # block tables
            P(DP_AXIS),  # context_lens
            P(DP_AXIS),  # chunk_start
        ),
        out_specs=P(DP_AXIS, None, TP_AXIS, None),
        check_vma=False,
    )
    return fn(q, kv, block_tables, context_lens, chunk_start)


def paged_decode_attention_sharded(
    mesh,
    q: jax.Array,  # (B, nh, D) — batch sharded over dp, heads over tp
    kv: jax.Array,  # (2, num_blocks, bs, kvh, D) — kv heads over tp
    block_tables: jax.Array,  # (B, nb)
    hist_len: jax.Array,  # (B,)
    staged_k: jax.Array,  # (W, B, kvh, D)
    staged_v: jax.Array,  # (W, B, kvh, D)
    step_k: jax.Array,
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """The kernel under tensor/data parallelism: pallas_call has no GSPMD
    partition rule, so shard_map places one kernel instance per device —
    each computes its own tp-shard of the heads (KV heads shard cleanly:
    q head g attends kv head g//q_per_kv, and megatron sharding keeps whole
    GQA groups per shard) over its own dp-shard of the rows. No collective
    is needed: decode attention is embarrassingly parallel over (row, head)
    once KV pages are head-sharded, which is exactly kv_cache_spec's layout
    (parallel/sharding.py — 'each chip only ever touches its own heads'
    pages')."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DP_AXIS, TP_AXIS

    tp = mesh.shape[TP_AXIS]
    nh, kvh = q.shape[1], kv.shape[3]
    if nh % tp or kvh % tp:
        raise ValueError(
            f"pallas under tp={tp} needs heads divisible by tp "
            f"(num_heads={nh}, num_kv_heads={kvh})"
        )
    fn = jax.shard_map(
        functools.partial(
            paged_decode_attention, scale=scale, interpret=interpret
        ),
        mesh=mesh,
        in_specs=(
            P(DP_AXIS, TP_AXIS, None),  # q
            P(None, None, None, TP_AXIS, None),  # kv pool
            P(DP_AXIS, None),  # block tables
            P(DP_AXIS),  # hist_len
            P(None, DP_AXIS, TP_AXIS, None),  # staged k
            P(None, DP_AXIS, TP_AXIS, None),  # staged v
            P(),  # step_k scalar
        ),
        out_specs=P(DP_AXIS, TP_AXIS, None),
        check_vma=False,
    )
    return fn(q, kv, block_tables, hist_len, staged_k, staged_v, step_k)


def _paged_decode_attention(
    q: jax.Array,  # (B, nh, D) — decode queries, one token per row
    kv: jax.Array,  # (2, num_blocks, bs, kvh, D) — the pool, read-only
    block_tables: jax.Array,  # (B, nb) int32
    hist_len: jax.Array,  # (B,) int32 — pool positions < hist_len are valid
    staged_k: jax.Array,  # (W, B, kvh, D) — fused-window staged keys
    staged_v: jax.Array,  # (W, B, kvh, D)
    step_k: jax.Array,  # scalar int32 — current iteration in the window
    *,
    scale: float,
    window: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """Complete decode attention (pooled history + staged window) without
    materializing the per-row gather. Returns (B, nh, D)."""
    b, nh, d = q.shape
    kvh, bs = kv.shape[3], kv.shape[2]
    nb = block_tables.shape[1]
    w = staged_k.shape[0]
    pages = _decode_pages_per_block(
        nb, bs, nh, kvh, d, kv.dtype.itemsize, q.dtype.itemsize
    )
    row = lambda i, *_: (i, 0, 0)  # q, out: one row a grid step
    staged = pl.BlockSpec((w, 1, kvh, d), lambda i, *_: (0, i, 0, 0))
    if _pages_fill_tiles(kvh, d, kv.dtype.itemsize):
        kernel, grid = _decode_walk_kernel, (b,)
        # the pool stays in HBM: the kernel copies the pages a row holds
        # itself, page ids from the prefetched block table
        pool = [pl.BlockSpec(memory_space=pl.ANY)]
        scratch = [
            pltpu.VMEM((2, 2, pages, bs, kvh, d), kv.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
        ]
    else:
        steps = -(-nb // pages)
        kernel, grid = _decode_grid_kernel, (b, steps)

        def page(k):
            def index_map(i, j, t, h, s):
                blocks = pl.cdiv(h[i], pages * bs)
                first = jnp.maximum(j - (steps - blocks), 0) * pages
                return (0, t[i, jnp.minimum(first + k, nb - 1)], 0, 0, 0)

            return pl.BlockSpec((2, 1, bs, kvh, d), index_map)

        pool = [page(k) for k in range(pages)]
        scratch = [
            pltpu.VMEM((nh, 1), jnp.float32),
            pltpu.VMEM((nh, 1), jnp.float32),
            pltpu.VMEM((nh, d), jnp.float32),
        ]
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, window=window),
        **({"name": "paged_window_decode_attention"} if window else {}),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # tables, hist_len, step_k
            grid=grid,
            in_specs=[pl.BlockSpec((1, nh, d), row), *pool, staged, staged],
            out_specs=pl.BlockSpec((1, nh, d), row),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, nh, d), q.dtype),
        # rows run in order: each hands the next its first block in flight
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=DECODE_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(
        block_tables, hist_len, jnp.reshape(step_k, (1,)), q,
        *[kv] * len(pool), staged_k, staged_v,
    )


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(q, kv, block_tables, hist_len, staged_k, staged_v,
                           step_k, *, scale: float, interpret: bool = False):
    return _paged_decode_attention(
        q, kv, block_tables, hist_len, staged_k, staged_v, step_k,
        scale=scale, interpret=interpret)


paged_decode_attention.__doc__ = _paged_decode_attention.__doc__


@functools.partial(jax.jit, static_argnames=("scale", "window", "interpret"))
def paged_window_decode_attention(
    q, kv, block_tables, hist_len, staged_k, staged_v, step_k, *,
    scale: float, window: int, interpret: bool = False,
):
    """`paged_decode_attention` for a window layer: the query at position
    hist_len + step_k sees pool keys at positions > hist_len + step_k -
    window (every staged slot lies inside the window: window >
    decode_window). Under its own name in the device trace."""
    return _paged_decode_attention(
        q, kv, block_tables, hist_len, staged_k, staged_v, step_k,
        scale=scale, window=window, interpret=interpret)


def _kv_write_kernel(
    # scalar prefetch
    ids_ref,  # (B, NBW) int32 — pool page per (row, page of its written span)
    off_ref,  # (B,) int32 — the chunk's first token's offset in its 1st page
    len_ref,  # (B,) int32 — the row's real chunk tokens (0: a padding row)
    row_ref,  # (B,) int32 — the row's first token on the packed axis
    # inputs, all left in HBM
    k_hbm,  # (N, kvh, D) — the packed chunk's new K rows, in the pool's dtype
    v_hbm,  # (N, kvh, D)
    kv_in,  # (2, num_blocks, bs, kvh, D) — the pool; aliased to kv_out
    # output
    kv_out,  # the same buffer
    # scratch
    sem,  # one DMA semaphore: every copy of the call
):
    """The chunk's new K and V rows into the pages its rows hold, by async
    copies HBM -> HBM of token spans only: for each page a row's chunk
    touches, the tokens of the packed axis that fall on it go to
    `pool[0|1, page, first : first + n]`. A copy's extent is static, so a
    span of n tokens (1 .. bs: a chunk that starts or ends mid-page) leaves
    as the powers of two of n's bits, end to end, largest first — a whole
    page is ONE copy a plane. Nothing is read from the pool and nothing
    outside [start_off, start_off + chunk_len) is written: pages past a
    row's span and padding rows issue no copy (the null page is left as it
    is). Every copy is started before the first is waited for; the walk is
    two loops over (row, page), no unrolling over pages, so the body stays
    a few dozen lines of Mosaic however wide the table."""
    del kv_in
    bs = kv_out.shape[2]
    pieces = [1 << i for i in reversed(range(bs.bit_length()))]

    def page_copies(b, j):
        """[(is it issued, K copy, V copy)] of page j of row b's span."""
        off, page = off_ref[b], ids_ref[b, j]
        lo = jnp.maximum(off - j * bs, 0)
        n = jnp.minimum(off + len_ref[b] - j * bs, bs) - lo
        src = row_ref[b] + j * bs + lo - off
        out = []
        for size in pieces:
            at = n - (n & (2 * size - 1))  # the larger pieces lie before it
            out.append(((n & size) != 0, *(
                pltpu.make_async_copy(
                    new.at[pl.ds(src + at, size)],
                    kv_out.at[which, page, pl.ds(lo + at, size)],
                    sem,
                ) for which, new in enumerate((k_hbm, v_hbm)))))
        return out

    def over_pages(do):
        def row(b, carry):
            def page(j, carry):
                for issued, *copies in page_copies(b, j):
                    @pl.when(issued)
                    def _():
                        for copy in copies:
                            do(copy)
                return carry

            span = jnp.where(
                len_ref[b] > 0, pl.cdiv(off_ref[b] + len_ref[b], bs), 0)
            return jax.lax.fori_loop(0, span, page, carry)

        jax.lax.fori_loop(0, ids_ref.shape[0], row, 0)

    over_pages(lambda copy: copy.start())
    over_pages(lambda copy: copy.wait())


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_write(
    kv: jax.Array,  # (2, num_blocks, bs, kvh, D) — the pool, updated in place
    k: jax.Array,  # (N, kvh, D) — the packed chunk's new K rows
    v: jax.Array,  # (N, kvh, D)
    write_block_ids: jax.Array,  # (B, NBW) int32 — the pages of each row's
    #   written span, in order (padding -> 0, the null page)
    start_off: jax.Array,  # (B,) int32 — first token's offset in page 0
    chunk_lens: jax.Array,  # (B,) int32 — real tokens a row
    row_off: jax.Array,  # (B,) int32 — each row's first token on the axis
    *,
    interpret: bool = False,
) -> jax.Array:
    """A prefill chunk's K/V write as ONE kernel a layer that takes the pool
    as the attention kernels do — in HBM, aliased input to output — so the
    pool keeps one layout through a prefill program and XLA has no scatter
    to lay it out for (ops/attention.py:write_kv_pages_blockwise is the XLA
    form and this kernel's oracle: the same values on the same pages, the
    rest of the pool bit for bit). A quantized pool's rows are converted
    before the call, as the XLA form's `.astype` does. On a chip the pool's
    pages have to fill whole HBM tiles (_pages_fill_tiles), as for the
    prefill kernel beside which this runs."""
    return pl.pallas_call(
        _kv_write_kernel,
        name="paged_kv_write",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,  # ids, start_off, chunk_lens, row_off
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct(kv.shape, kv.dtype),
        input_output_aliases={6: 0},  # the pool, behind 4 scalars + k + v
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(write_block_ids, start_off, chunk_lens, row_off,
      k.astype(kv.dtype), v.astype(kv.dtype), kv)
