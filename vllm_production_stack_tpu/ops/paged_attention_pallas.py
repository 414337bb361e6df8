"""Pallas TPU kernel: paged decode attention over the HBM KV pool.

The XLA fallback (ops/attention.py:paged_attention_with_staged) materializes
each row's gathered history — (B, S, kvH, D) per layer per window iteration —
in HBM before attending. This kernel never materializes the gather: the
Pallas pipeline streams KV pages HBM→VMEM directly from the paged pool, with
the page id for each grid step read from the scalar-prefetched block table
(the "gather" IS the pipeline's index_map), and a flash-style online softmax
accumulates across pages in VMEM scratch. The window's staged K/V (see
models/llama.py:decode_window_step) are folded in by a final grid step so the
kernel computes the complete decode attention output.

Reference parity: the reference stack delegates this op to vLLM's CUDA
paged-attention kernels inside its engine images (external to its repo);
SURVEY §7.3 ranks a TPU-native equivalent as hard part #1.

Layout notes (pallas_guide.md): last dim 128 lanes — head_dim (64/128) maps
onto lanes; token-position and head axes map onto sublanes. All matmuls are
(≤heads × D) @ (D × page) — small for the MXU, but decode is HBM-bandwidth
bound, so the win is streaming pages once, not MXU utilization.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    # scalar prefetch
    tables_ref,  # (B, nb) int32 — page id per (row, page-slot)
    hist_ref,  # (B,) int32 — pool history length per row
    step_ref,  # (1,) int32 — iteration index inside the fused window
    # pipeline inputs
    q_ref,  # (1, nh, D)
    kv_ref,  # (2, 1, bs, kvh, D) — this grid step's pool page (K and V)
    staged_k_ref,  # (W, 1, kvh, D) — this row's staged window K
    staged_v_ref,  # (W, 1, kvh, D)
    # output
    out_ref,  # (1, nh, D)
    # scratch
    m_ref,  # (nh, 1) f32 running max
    l_ref,  # (nh, 1) f32 running denominator
    acc_ref,  # (nh, D) f32 running numerator
    *,
    scale: float,
    block_size: int,
    num_kv_heads: int,
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    num_pages = pl.num_programs(1) - 1  # last step handles the staged window

    nh, d = q_ref.shape[1], q_ref.shape[2]
    qpk = nh // num_kv_heads
    q = q_ref[0].astype(jnp.float32)  # (nh, D)

    @pl.when(j == 0)
    def _init_acc():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def flash_update(scores, values):
        """scores: (nh, S) f32 already masked; values: (S, kvh, D)."""
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_cur = jnp.max(scores, axis=1, keepdims=True)  # (nh, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)  # (nh, 1)
        p = jnp.exp(scores - m_new)  # (nh, S)
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_new
        # per-kv-head GQA: the qpk query heads of group g attend values[:, g].
        # Built by concatenation — value-level .at[].add lowers to scatter-add,
        # which the Pallas TPU lowering doesn't implement
        v_f = values.astype(jnp.float32)
        acc = acc_ref[:] * alpha  # (nh, D)
        acc_ref[:] = jnp.concatenate(
            [
                acc[g * qpk : (g + 1) * qpk]
                + jax.lax.dot(
                    p[g * qpk : (g + 1) * qpk],
                    v_f[:, g, :],
                    preferred_element_type=jnp.float32,
                )
                for g in range(num_kv_heads)
            ],
            axis=0,
        )

    @pl.when(j < num_pages)
    def _visit_page():
        k_page = kv_ref[0, 0].astype(jnp.float32)  # (bs, kvh, D)
        v_page = kv_ref[1, 0]
        # token positions covered by this page slot
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1
        )
        valid = pos < hist_ref[b]  # (1, bs)
        scores = jnp.concatenate(
            [
                jax.lax.dot_general(
                    q[g * qpk : (g + 1) * qpk],
                    k_page[:, g, :],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for g in range(num_kv_heads)
            ],
            axis=0,
        )  # (nh, bs)
        scores = jnp.where(valid, scores * scale, NEG_INF)
        flash_update(scores, v_page)

    @pl.when(j == num_pages)
    def _finalize():
        w = staged_k_ref.shape[0]
        k_st = staged_k_ref[:, 0].astype(jnp.float32)  # (W, kvh, D)
        v_st = staged_v_ref[:, 0]
        widx = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
        valid = widx <= step_ref[0]  # staged slot written iff w <= k
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
    sequential chunks instead of 16-token pool pages, so each HBM→VMEM DMA
    moves C·kvh·D elements (e.g. 512 tokens ≈ 0.5 MB) — the regime where the
    pipeline reaches memory bandwidth. Same flash recurrence as
    _decode_kernel; the staged window folds in on the final grid step."""
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
    pipeline's index_map — same trick as _decode_kernel), the query tile
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

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # tables, hist_len, step_k
        grid=(b, nb + 1),
        in_specs=[
            pl.BlockSpec((1, nh, d), lambda i, j, t, h, s: (i, 0, 0)),
            # the paged "gather": page id for grid step (i, j) comes straight
            # from the prefetched block table (clamped on the final step,
            # whose fetch is unused)
            pl.BlockSpec(
                (2, 1, bs, kvh, d),
                lambda i, j, t, h, s: (
                    0,
                    t[i, jnp.minimum(j, t.shape[1] - 1)],
                    0,
                    0,
                    0,
                ),
            ),
            pl.BlockSpec(
                (staged_k.shape[0], 1, kvh, d), lambda i, j, t, h, s: (0, i, 0, 0)
            ),
            pl.BlockSpec(
                (staged_v.shape[0], 1, kvh, d), lambda i, j, t, h, s: (0, i, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, nh, d), lambda i, j, t, h, s: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh, 1), jnp.float32),
            pltpu.VMEM((nh, 1), jnp.float32),
            pltpu.VMEM((nh, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, scale=scale, block_size=bs, num_kv_heads=kvh
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, d), q.dtype),
        interpret=interpret,
    )(block_tables, hist_len, jnp.reshape(step_k, (1,)), q, kv, staged_k, staged_v)
