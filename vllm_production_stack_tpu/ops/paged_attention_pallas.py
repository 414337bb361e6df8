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
computes the complete decode attention output in one op.

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
):
    """One grid step = one row. The row's ceil(hist_len / (P * bs)) page
    blocks are walked in a loop whose trip count is the row's own, each block
    copied HBM->VMEM by P async copies (page ids from the block table) while
    the block before it is computed; the last block of a row starts the first
    block of the next row that has any. A padding row (hist_len 0) copies and
    computes nothing but the staged window."""
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    nb = tables_ref.shape[1]
    pages, bs, kvh = buf.shape[2], buf.shape[3], buf.shape[4]
    nh, d = q_ref.shape[1], q_ref.shape[2]
    span = pages * bs  # tokens per block
    cols = span * kvh
    hist = hist_ref[b]
    n_blocks = pl.cdiv(hist, span)
    blocks = buf.reshape(2, 2, cols, d)  # (slot, K|V, columns, D)

    def block_copies(row, blk, slot):
        # table slots past the row's pages hold the null page or stale ids:
        # read whole, masked by position; slots past the table are clamped
        return [
            pltpu.make_async_copy(
                kv_hbm.at[:, tables_ref[row, jnp.minimum(blk * pages + j,
                                                         nb - 1)]],
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
        return _flash_block(
            q, blocks[slot, 0], blocks[slot, 1],
            col_tokens < hist - i * span, carry, scale,
        )

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

    @pl.when(blk >= 0)
    def _visit():
        keys, values = (
            jnp.concatenate(
                [r[i, 0].reshape(bs * kvh, d) for r in page_refs], axis=0
            )
            for i in (0, 1)
        )
        m_ref[:], l_ref[:], acc_ref[:] = _flash_block(
            q, keys, values,
            _column_tokens(nh, span * kvh, kvh) < hist - blk * span,
            (m_ref[:], l_ref[:], acc_ref[:]), scale,
        )

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        out_ref[0] = _fold_staged(
            q, staged_k_ref, staged_v_ref, step_ref[0],
            (m_ref[:], l_ref[:], acc_ref[:]), scale,
        ).astype(out_ref.dtype)


def _hist_kernel(
    # scalar prefetch
    hist_ref,  # (B,) int32 — history length per row
    step_ref,  # (1,) int32 — iteration index inside the fused window
    # pipeline inputs
    q_ref,  # (1, nh, D)
    k_ref,  # (1, C, kvh, D) — this grid step's contiguous history chunk
    v_ref,  # (1, C, kvh, D)
    staged_k_ref,  # (W, 1, kvh, D)
    staged_v_ref,  # (W, 1, kvh, D)
    # output
    out_ref,  # (1, nh, D)
    # scratch
    m_ref,  # (nh, 1) f32
    l_ref,  # (nh, 1) f32
    acc_ref,  # (nh, D) f32
    *,
    scale: float,
    chunk: int,
    num_kv_heads: int,
):
    """Flash decode over HOISTED CONTIGUOUS history (B, S, kvh, D): large
    sequential chunks streamed by the Pallas pipeline, so each HBM→VMEM DMA
    moves C·kvh·D elements (e.g. 512 tokens ≈ 0.5 MB). One grid step a
    chunk, per-kv-head dots; the staged window folds in on the final grid
    step."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    num_chunks = pl.num_programs(1) - 1

    nh, d = q_ref.shape[1], q_ref.shape[2]
    qpk = nh // num_kv_heads
    q = q_ref[0]  # (nh, D) native dtype; dots accumulate f32

    @pl.when(j == 0)
    def _init_acc():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def flash_update(scores, values):
        """scores: (nh, S) f32 already masked; values: (S, kvh, D)."""
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_cur = jnp.max(scores, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_new
        p = p.astype(values.dtype)
        acc = acc_ref[:] * alpha
        acc_ref[:] = jnp.concatenate(
            [
                acc[g * qpk : (g + 1) * qpk]
                + jax.lax.dot(
                    p[g * qpk : (g + 1) * qpk],
                    values[:, g, :],
                    preferred_element_type=jnp.float32,
                )
                for g in range(num_kv_heads)
            ],
            axis=0,
        )

    @pl.when(j < num_chunks)
    def _visit_chunk():
        k_chunk = k_ref[0]  # (C, kvh, D)
        v_chunk = v_ref[0]
        pos = j * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
        valid = pos < hist_ref[b]  # (1, C)
        scores = jnp.concatenate(
            [
                jax.lax.dot_general(
                    q[g * qpk : (g + 1) * qpk],
                    k_chunk[:, g, :],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for g in range(num_kv_heads)
            ],
            axis=0,
        )  # (nh, C)
        scores = jnp.where(valid, scores * scale, NEG_INF)
        flash_update(scores, v_chunk)

    @pl.when(j == num_chunks)
    def _finalize():
        w = staged_k_ref.shape[0]
        k_st = staged_k_ref[:, 0]  # (W, kvh, D)
        v_st = staged_v_ref[:, 0]
        widx = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
        valid = widx <= step_ref[0]
        scores = jnp.concatenate(
            [
                jax.lax.dot_general(
                    q[g * qpk : (g + 1) * qpk],
                    k_st[:, g, :],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for g in range(num_kv_heads)
            ],
            axis=0,
        )  # (nh, W)
        scores = jnp.where(valid, scores * scale, NEG_INF)
        flash_update(scores, v_st)
        out_ref[0] = (acc_ref[:] / l_ref[:]).astype(out_ref.dtype)


# --------------------------------------------------------------------------
# Paged PREFILL (chunked-prefill-aware) flash attention
# --------------------------------------------------------------------------

# query-tile length: q rows resident in VMEM across the page stream, capped
# so that every head's tile state fits the 16 MiB scoped-VMEM limit the v5e
# compiler enforces (tests/test_chip_compile.py). Per (head, row) the kernel
# holds the double-buffered q and out tiles, the f32 acc, and the (rows, 1)
# m/l scratch — which is lane-padded to 128, so it costs as much as acc at
# D=128. The budget leaves the rest of the limit to the page double-buffers
# and the per-head (Tt, bs) score temporaries.
PREFILL_Q_TILE = 256
PREFILL_VMEM_BUDGET = 10 * 2**20


def _prefill_q_tile(t: int, nh: int, d: int, itemsize: int) -> int:
    lanes = -(-d // 128) * 128
    per_row = 4 * itemsize * lanes + 4 * lanes + 2 * 4 * 128
    rows = PREFILL_VMEM_BUDGET // (nh * per_row)
    tt = min(t, PREFILL_Q_TILE)
    while tt > 8 and tt > rows:
        tt //= 2
    return tt


def _prefill_kernel(
    # scalar prefetch
    tables_ref,  # (B, nb) int32 — page id per (row, page-slot)
    ctx_ref,  # (B,) int32 — resident tokens AFTER this chunk (incl. chunk)
    start_ref,  # (B,) int32 — logical position of the chunk's first token
    # pipeline inputs
    q_ref,  # (1, nh, Tt, D) — head-major so per-head slices are static 2D
    kv_ref,  # (2, 1, bs, kvh, D) — this grid step's pool page
    # output
    out_ref,  # (1, nh, Tt, D)
    # scratch
    m_ref,  # (nh*Tt, 1) f32 running max, head-major rows
    l_ref,  # (nh*Tt, 1) f32 running denominator
    acc_ref,  # (nh*Tt, D) f32 running numerator
    *,
    scale: float,
    block_size: int,
    num_kv_heads: int,
):
    """Flash prefill over the paged pool: the page id for each grid step
    comes from the scalar-prefetched block table (the gather IS the
    pipeline's index_map), the query tile
    stays in VMEM, and causality is computed from iotas alone: the serving
    scheduler feeds chunks with CONTIGUOUS positions (scheduler.py
    work.positions = range(start, start+len)), so q position = chunk_start
    + tile offset + row. Chunked prefill needs no special casing — resident
    pages hold earlier chunks AND this chunk's freshly-written KV (forward
    writes before attending), and `pos_k <= pos_q` masks the not-yet-valid
    tail of the chunk's own pages."""
    b = pl.program_id(0)
    qt = pl.program_id(1)
    j = pl.program_id(2)
    num_pages = pl.num_programs(2)

    nh, tt, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    qpk = nh // num_kv_heads

    @pl.when(j == 0)
    def _init_acc():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # (Tt, bs) mask — identical for every head, built once per grid step
    q_pos = (
        start_ref[b]
        + qt * tt
        + jax.lax.broadcasted_iota(jnp.int32, (tt, block_size), 0)
    )
    pos_k = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (tt, block_size), 1
    )
    valid = (pos_k < ctx_ref[b]) & (pos_k <= q_pos)

    # skip pages fully outside this tile's attendable range: beyond the
    # row's residency, or entirely after the tile's last query position.
    # The DMA still lands (static block spec) but the 2*nh dots don't run
    page_live = (j * block_size < ctx_ref[b]) & (
        j * block_size <= start_ref[b] + qt * tt + tt - 1
    )

    @pl.when(page_live)
    def _visit_live_page():
        k_page = kv_ref[0, 0].astype(jnp.float32)  # (bs, kvh, D)
        v_page = kv_ref[1, 0].astype(jnp.float32)
        for h in range(nh):
            g = h // qpk
            q_h = q_ref[0, h].astype(jnp.float32)  # (Tt, D)
            scores = jax.lax.dot_general(
                q_h, k_page[:, g, :],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (Tt, bs)
            scores = jnp.where(valid, scores * scale, NEG_INF)
            r0, r1 = h * tt, (h + 1) * tt
            m_prev, l_prev = m_ref[r0:r1], l_ref[r0:r1]
            m_cur = jnp.max(scores, axis=1, keepdims=True)  # (Tt, 1)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new)  # (Tt, bs)
            l_ref[r0:r1] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            m_ref[r0:r1] = m_new
            acc_ref[r0:r1] = acc_ref[r0:r1] * alpha + jax.lax.dot(
                p, v_page[:, g, :], preferred_element_type=jnp.float32
            )

    @pl.when(j == num_pages - 1)
    def _finalize():
        for h in range(nh):
            r0, r1 = h * tt, (h + 1) * tt
            # padding rows attend nothing (ctx 0) — l stays 0; the max
            # keeps them finite (their outputs are never read)
            out_ref[0, h] = (
                acc_ref[r0:r1] / jnp.maximum(l_ref[r0:r1], 1e-30)
            ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_prefill_attention(
    q: jax.Array,  # (B, T, nh, D) — the chunk's queries
    kv: jax.Array,  # (2, num_blocks, bs, kvh, D) — pool, chunk KV already in
    block_tables: jax.Array,  # (B, nb) int32
    context_lens: jax.Array,  # (B,) int32 — resident incl. this chunk
    chunk_start: jax.Array,  # (B,) int32 — logical position of q[:, 0]
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Complete chunked-prefill attention (resident pages + the chunk's own
    freshly-written pages) without materializing the per-row gather OR the
    (B, T, S) mask the XLA path builds. Returns (B, T, nh, D).

    Reference parity: covers what the reference's engine images get from
    vLLM's CUDA flash-prefill over paged KV; SURVEY §7.1 names paged
    attention kernels as the TPU-native hard part."""
    b, t, nh, d = q.shape
    kvh, bs = kv.shape[3], kv.shape[2]
    nb = block_tables.shape[1]
    tt = _prefill_q_tile(t, nh, d, q.dtype.itemsize)
    assert t % tt == 0, (t, tt)  # T is a power-of-two bucket

    # head-major q so the kernel's per-head slices are static 2D views
    q_hm = q.transpose(0, 2, 1, 3)  # (B, nh, T, D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # tables, context_lens, chunk_start
        grid=(b, t // tt, nb),
        in_specs=[
            pl.BlockSpec((1, nh, tt, d), lambda i, qt, j, tb, c, st: (i, 0, qt, 0)),
            # the paged "gather": page id for grid step (i, qt, j) comes
            # straight from the prefetched block table
            pl.BlockSpec(
                (2, 1, bs, kvh, d),
                lambda i, qt, j, tb, c, st: (0, tb[i, j], 0, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, nh, tt, d), lambda i, qt, j, tb, c, st: (i, 0, qt, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((nh * tt, 1), jnp.float32),
            pltpu.VMEM((nh * tt, 1), jnp.float32),
            pltpu.VMEM((nh * tt, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _prefill_kernel, scale=scale, block_size=bs, num_kv_heads=kvh
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, t, d), q.dtype),
        interpret=interpret,
    )(block_tables, context_lens, chunk_start, q_hm, kv)
    return out.transpose(0, 2, 1, 3)  # back to (B, T, nh, D)


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


HIST_CHUNK = 512


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def hist_decode_attention(
    q: jax.Array,  # (B, nh, D) — decode queries, one token per row
    hist_k: jax.Array,  # (B, S, kvh, D) — hoisted contiguous history
    hist_v: jax.Array,  # (B, S, kvh, D)
    hist_len: jax.Array,  # (B,) int32
    staged_k: jax.Array,  # (W, B, kvh, D)
    staged_v: jax.Array,  # (W, B, kvh, D)
    step_k: jax.Array,  # scalar int32
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Flash decode attention over hoisted contiguous history + the staged
    window. Returns (B, nh, D). S must divide into HIST_CHUNK-or-smaller
    power-of-two chunks (the runner's context buckets are powers of two).

    Status: opt-in experiment, NOT wired into the serving path. Measured on a
    v5e chip (llama-1b decode, B=256, W=64, S=256): 20.2 ms/iter vs 12.1 for
    the XLA formulation over the same hoisted history
    (ops/attention.py:attention_with_hist) — at these shapes XLA's fused
    einsum pipeline beats the kernel's per-(row, chunk) grid; revisit for
    long-context shapes where the score matrix stops fitting fused form."""
    b, nh, d = q.shape
    kvh = hist_k.shape[2]
    s = hist_k.shape[1]
    chunk = min(s, HIST_CHUNK)
    assert s % chunk == 0, (s, chunk)
    n_chunks = s // chunk
    w = staged_k.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # hist_len, step_k
        grid=(b, n_chunks + 1),
        in_specs=[
            pl.BlockSpec((1, nh, d), lambda i, j, h, st: (i, 0, 0)),
            pl.BlockSpec(
                (1, chunk, kvh, d),
                lambda i, j, h, st: (i, jnp.minimum(j, (s // chunk) - 1), 0, 0),
            ),
            pl.BlockSpec(
                (1, chunk, kvh, d),
                lambda i, j, h, st: (i, jnp.minimum(j, (s // chunk) - 1), 0, 0),
            ),
            pl.BlockSpec((w, 1, kvh, d), lambda i, j, h, st: (0, i, 0, 0)),
            pl.BlockSpec((w, 1, kvh, d), lambda i, j, h, st: (0, i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nh, d), lambda i, j, h, st: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh, 1), jnp.float32),
            pltpu.VMEM((nh, 1), jnp.float32),
            pltpu.VMEM((nh, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _hist_kernel, scale=scale, chunk=chunk, num_kv_heads=kvh
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, d), q.dtype),
        interpret=interpret,
    )(hist_len, jnp.reshape(step_k, (1,)), q, hist_k, hist_v, staged_k, staged_v)


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


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(
    q: jax.Array,  # (B, nh, D) — decode queries, one token per row
    kv: jax.Array,  # (2, num_blocks, bs, kvh, D) — the pool, read-only
    block_tables: jax.Array,  # (B, nb) int32
    hist_len: jax.Array,  # (B,) int32 — pool positions < hist_len are valid
    staged_k: jax.Array,  # (W, B, kvh, D) — fused-window staged keys
    staged_v: jax.Array,  # (W, B, kvh, D)
    step_k: jax.Array,  # scalar int32 — current iteration in the window
    *,
    scale: float,
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
        functools.partial(kernel, scale=scale),
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
            dimension_semantics=("arbitrary",) * len(grid)
        ),
        interpret=interpret,
    )(
        block_tables, hist_len, jnp.reshape(step_k, (1,)), q,
        *[kv] * len(pool), staged_k, staged_v,
    )
