"""Parity: the Pallas paged-decode kernel (interpret mode on CPU) must match
the XLA staged-attention reference bit-for-bit in float32.

The kernel itself streams pool pages via the Pallas pipeline on TPU
(ops/paged_attention_pallas.py); interpret mode runs the same program
host-side, so these tests pin the math (flash accumulation, GQA grouping,
history masking, staged-window masking) without a chip.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_production_stack_tpu.ops.attention import paged_attention_with_staged
from vllm_production_stack_tpu.ops.paged_attention_pallas import (
    paged_decode_attention,
)


def _setup(b=4, nb=3, bs=8, kvh=2, qpk=2, d=16, w=4, seed=0):
    rng = np.random.RandomState(seed)
    nh = kvh * qpk
    num_blocks = 32
    kv = rng.randn(2, num_blocks, bs, kvh, d).astype(np.float32)
    q = rng.randn(b, 1, nh, d).astype(np.float32)
    # distinct pages per row, none using the null page
    tables = rng.permutation(np.arange(1, num_blocks))[: b * nb].reshape(b, nb)
    tables = tables.astype(np.int32)
    hist_len = rng.randint(1, nb * bs, size=b).astype(np.int32)
    staged_k = rng.randn(w, b, kvh, d).astype(np.float32)
    staged_v = rng.randn(w, b, kvh, d).astype(np.float32)
    return q, kv, tables, hist_len, staged_k, staged_v


@pytest.mark.parametrize("step_k", [0, 2, 3])
def test_pallas_matches_xla_reference(step_k):
    q, kv, tables, hist_len, staged_k, staged_v = _setup()
    w = staged_k.shape[0]
    scale = q.shape[-1] ** -0.5

    hist_mask = (
        np.arange(tables.shape[1] * kv.shape[2])[None, :] < hist_len[:, None]
    )
    staged_mask = np.arange(w) <= step_k
    ref = paged_attention_with_staged(
        jnp.asarray(q), jnp.asarray(kv), jnp.asarray(tables),
        jnp.asarray(hist_mask), jnp.asarray(staged_k), jnp.asarray(staged_v),
        jnp.asarray(staged_mask), scale=scale,
    )[:, 0]

    out = paged_decode_attention(
        jnp.asarray(q[:, 0]), jnp.asarray(kv), jnp.asarray(tables),
        jnp.asarray(hist_len), jnp.asarray(staged_k), jnp.asarray(staged_v),
        jnp.asarray(np.int32(step_k)), scale=scale, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_pallas_zero_history():
    """First decode right after a 0-length history must only see staged."""
    q, kv, tables, _, staged_k, staged_v = _setup(seed=1)
    hist_len = np.zeros(q.shape[0], np.int32)
    scale = q.shape[-1] ** -0.5
    w = staged_k.shape[0]

    hist_mask = np.zeros((q.shape[0], tables.shape[1] * kv.shape[2]), bool)
    staged_mask = np.arange(w) <= 0
    ref = paged_attention_with_staged(
        jnp.asarray(q), jnp.asarray(kv), jnp.asarray(tables),
        jnp.asarray(hist_mask), jnp.asarray(staged_k), jnp.asarray(staged_v),
        jnp.asarray(staged_mask), scale=scale,
    )[:, 0]
    out = paged_decode_attention(
        jnp.asarray(q[:, 0]), jnp.asarray(kv), jnp.asarray(tables),
        jnp.asarray(hist_len), jnp.asarray(staged_k), jnp.asarray(staged_v),
        jnp.asarray(np.int32(0)), scale=scale, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def _ragged_case(hist, nb, bs, kvh, qpk, d, w=4, seed=0, dtype=np.float32,
                 dead="null"):
    """A batch whose rows hold exactly the pages their history needs. Dead
    table slots point at the null page, or (dead="garbage") at pages that
    hold other data and belong to nobody in the batch."""
    rng = np.random.RandomState(seed)
    hist = np.asarray(hist, np.int32)
    b, nh = len(hist), kvh * qpk
    held = -(-hist // bs)
    num_blocks = int(held.sum()) + 9
    kv = rng.randn(2, num_blocks, bs, kvh, d).astype(dtype)
    owned = rng.permutation(np.arange(1, num_blocks - 8))
    tables = np.zeros((b, nb), np.int32)
    if dead == "garbage":
        tables[:] = np.random.RandomState(seed + 1).randint(
            num_blocks - 8, num_blocks, size=(b, nb))
    at = 0
    for i, n in enumerate(held):
        tables[i, :n] = owned[at:at + n]
        at += n
    q = rng.randn(b, nh, d).astype(dtype)
    staged_k = rng.randn(w, b, kvh, d).astype(dtype)
    staged_v = rng.randn(w, b, kvh, d).astype(dtype)
    return q, kv, tables, hist, staged_k, staged_v


def _kernel(case, step_k=1):
    q, kv, tables, hist, staged_k, staged_v = case
    return np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kv), jnp.asarray(tables),
        jnp.asarray(hist), jnp.asarray(staged_k), jnp.asarray(staged_v),
        jnp.asarray(np.int32(step_k)), scale=q.shape[-1] ** -0.5,
        interpret=True,
    ).astype(jnp.float32))


def _reference(case, step_k=1):
    q, kv, tables, hist, staged_k, staged_v = case
    hist_mask = np.arange(tables.shape[1] * kv.shape[2])[None, :] < hist[:, None]
    return np.asarray(paged_attention_with_staged(
        jnp.asarray(q[:, None]), jnp.asarray(kv), jnp.asarray(tables),
        jnp.asarray(hist_mask), jnp.asarray(staged_k), jnp.asarray(staged_v),
        jnp.asarray(np.arange(staged_k.shape[0]) <= step_k),
        scale=q.shape[-1] ** -0.5,
    )[:, 0].astype(jnp.float32))


# (nb, bs, kvh, qpk, d): the page block is 256 tokens (P = 256 // bs pages).
# D=128 pools take the walk form of the kernel (its own page copies), D=64
# pools the grid form (pipeline windows): _pages_fill_tiles
RAGGED_SHAPES = {
    "qwen3-8b-heads": (64, 32, 8, 4, 128),
    "qwen2-7b-group7": (64, 32, 4, 7, 128),
    "tp-shard-kvh1": (32, 32, 1, 4, 128),
    "llama-1b-d64": (32, 32, 2, 4, 64),
    "page16": (64, 16, 2, 2, 64),
    "page64": (16, 64, 2, 2, 64),
}


@pytest.mark.parametrize("shape", list(RAGGED_SHAPES))
def test_pallas_ragged_batch_matches_xla_reference(shape):
    """One batch holding every edge of the page walk: no history, one token,
    a page less one / exact / plus one, a block plus a bit (not a multiple
    of P * bs), several blocks, and the full table."""
    nb, bs, kvh, qpk, d = RAGGED_SHAPES[shape]
    span = 256
    hist = [0, 1, bs - 1, bs, bs + 1, span + 7, 2 * span, nb * bs - 3,
            0, nb * bs]
    case = _ragged_case(hist, nb, bs, kvh, qpk, d, seed=2)
    np.testing.assert_allclose(_kernel(case), _reference(case), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("d", [64, 128])
def test_pallas_bf16_pool_matches_xla_reference(d):
    """bf16 operands, f32 statistics — what the chip runs: same products
    and accumulation width as the XLA decode attention."""
    case = _ragged_case([0, 40, 300, 700], 32, 32, 2, 4, d, seed=4,
                        dtype=jnp.bfloat16)
    np.testing.assert_allclose(_kernel(case), _reference(case), rtol=2e-2,
                               atol=2e-2)


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("nb", [1, 2, 3, 12])
def test_pallas_table_narrower_than_a_block(nb, d):
    """width_floor_blocks=1 gives tables of 1, 2, 4 pages: the block shrinks
    to the table; a width that is no multiple of the block (12 = 8 + 4) has
    its last block's slots clamped to the table."""
    bs = 32
    case = _ragged_case([0, 5, bs, nb * bs], nb, bs, 2, 2, d, seed=3)
    np.testing.assert_allclose(_kernel(case), _reference(case), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("d", [64, 128])
def test_pallas_dead_table_slots_are_not_attended(d):
    """Slots past a row's pages may hold stale ids of pages that are not the
    row's: what they point at must not reach the output, bit for bit."""
    hist = [0, 9, 250, 256, 300, 1000]
    null = _ragged_case(hist, 64, 32, 2, 4, d, seed=6)
    garbage = _ragged_case(hist, 64, 32, 2, 4, d, seed=6, dead="garbage")
    assert (null[2] != garbage[2]).any()
    np.testing.assert_array_equal(_kernel(null), _kernel(garbage))


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("hist", [9, 300, 2048])
def test_pallas_row_is_batch_and_table_width_invariant(hist, d):
    """The benchmark's batch-invariance probe: a row's output is bitwise the
    same alone, among 15 others, and in the 64- and the 128-page program."""
    rng = np.random.RandomState(hist)
    others = [int(h) for h in rng.randint(0, 2048, size=15)]
    at = 7
    batch_hist = others[:at] + [hist] + others[at:]
    q, kv, tables, lens, sk, sv = _ragged_case(
        batch_hist, 64, 32, 2, 4, d, seed=8)
    in_batch = _kernel((q, kv, tables, lens, sk, sv))[at]
    row = slice(at, at + 1)
    alone = (q[row], kv, tables[row], lens[row], sk[:, row], sv[:, row])
    np.testing.assert_array_equal(_kernel(alone)[0], in_batch)
    wide = np.zeros((len(batch_hist), 128), np.int32)
    wide[:, :64] = tables
    np.testing.assert_array_equal(
        _kernel((q, kv, wide, lens, sk, sv))[at], in_batch)


def test_decode_window_step_pallas_backend_matches_xla():
    """Full model step: interpret-mode pallas backend == xla backend."""
    from vllm_production_stack_tpu.engine.config import ModelConfig
    from vllm_production_stack_tpu.models import llama

    cfg = ModelConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    kv = llama.init_kv_cache(cfg, num_blocks=16, block_size=8)
    b, w = 2, 3
    staged = llama.init_staged_kv(cfg, w, b)
    tokens = jnp.asarray([3, 5], jnp.int32)
    positions = jnp.asarray([4, 9], jnp.int32)
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    hist_len = positions

    h_x, st_x = llama.decode_window_step(
        cfg, params, tokens, positions, kv, tables, staged,
        jnp.int32(0), hist_len, backend="xla",
    )
    h_p, st_p = llama.decode_window_step(
        cfg, params, tokens, positions, kv, tables, staged,
        jnp.int32(0), hist_len, backend="pallas_interpret",
    )
    np.testing.assert_allclose(np.asarray(h_p), np.asarray(h_x), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(st_p), np.asarray(st_x), rtol=2e-5,
                               atol=2e-5)


def test_flash_chunked_matches_direct_long_context():
    """masked_attention's online-softmax path (S > FLASH_CHUNK) must match
    the direct score-materializing path — long-context prefill correctness."""
    from vllm_production_stack_tpu.ops import attention as att

    rng = np.random.RandomState(0)
    b, t, kvh, qpk, d = 2, 8, 2, 2, 16
    s = 4096  # > FLASH_CHUNK and divisible
    q = jnp.asarray(rng.randn(b, t, kvh * qpk, d), jnp.float32)
    keys = jnp.asarray(rng.randn(b, s, kvh, d) * 0.3, jnp.float32)
    values = jnp.asarray(rng.randn(b, s, kvh, d), jnp.float32)
    # realistic mask: per-row valid length + causal-ish stagger, plus one
    # fully-masked padding row
    lens = np.array([3000, 1], dtype=np.int32)
    mask_np = np.zeros((b, t, s), bool)
    for i in range(b):
        for j in range(t):
            mask_np[i, j, : max(0, lens[i] - (t - 1 - j) * 7)] = True
    mask_np[1, 0, :] = False  # fully masked query row
    mask = jnp.asarray(mask_np)

    flash = att.masked_attention(q, keys, values, mask, scale=0.25)

    # force the direct path by raising the threshold
    orig = att.FLASH_CHUNK
    att.FLASH_CHUNK = s + 1
    try:
        direct = att.masked_attention(q, keys, values, mask, scale=0.25)
    finally:
        att.FLASH_CHUNK = orig
    np.testing.assert_allclose(
        np.asarray(flash), np.asarray(direct), rtol=2e-5, atol=2e-5
    )


def _prefill_setup(b=2, nb=4, bs=8, kvh=2, qpk=2, d=16, t=8, seed=5,
                   hists=(8, 13)):
    """Chunked-prefill scenario: each row has `hist` earlier tokens resident
    and a t-token chunk ALREADY WRITTEN to its pages (forward writes before
    attending), so context_len = hist + t and chunk_start = hist."""
    rng = np.random.RandomState(seed)
    nh = kvh * qpk
    num_blocks = 64
    kv = rng.randn(2, num_blocks, bs, kvh, d).astype(np.float32)
    q = rng.randn(b, t, nh, d).astype(np.float32)
    tables = rng.permutation(np.arange(1, num_blocks))[: b * nb].reshape(b, nb)
    tables = tables.astype(np.int32)
    chunk_start = np.asarray(hists[:b], np.int32)
    context_lens = chunk_start + t
    assert int(context_lens.max()) <= nb * bs
    return q, kv, tables, context_lens, chunk_start


def _prefill_ref(q, kv, tables, context_lens, chunk_start, scale):
    from vllm_production_stack_tpu.ops.attention import (
        causal_page_mask, paged_attention_xla,
    )

    t = q.shape[1]
    positions = chunk_start[:, None] + np.arange(t, dtype=np.int32)[None, :]
    s_ctx = tables.shape[1] * kv.shape[2]
    mask = causal_page_mask(
        jnp.asarray(positions), jnp.asarray(context_lens), s_ctx
    )
    return paged_attention_xla(
        jnp.asarray(q), jnp.asarray(kv), jnp.asarray(tables), mask,
        scale=scale,
    )


def test_prefill_kernel_matches_xla_reference():
    """Mid-sequence chunked prefill: resident history + the chunk's own
    freshly-written pages, causality inside the chunk included."""
    from vllm_production_stack_tpu.ops.paged_attention_pallas import (
        paged_prefill_attention,
    )

    q, kv, tables, ctx, start = _prefill_setup()
    scale = q.shape[-1] ** -0.5
    ref = _prefill_ref(q, kv, tables, ctx, start, scale)
    out = paged_prefill_attention(
        jnp.asarray(q), jnp.asarray(kv), jnp.asarray(tables),
        jnp.asarray(ctx), jnp.asarray(start), scale=scale, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_prefill_kernel_first_chunk_and_padding_row():
    """First chunk of a sequence (no history: start=0) next to a fully
    padded row (ctx=0). The padding row's output is unread garbage in both
    backends — only the real row is compared."""
    from vllm_production_stack_tpu.ops.paged_attention_pallas import (
        paged_prefill_attention,
    )

    q, kv, tables, ctx, start = _prefill_setup(hists=(0, 0))
    ctx = np.asarray([q.shape[1], 0], np.int32)  # row 1 is pure padding
    scale = q.shape[-1] ** -0.5
    ref = _prefill_ref(q, kv, tables, ctx, start, scale)
    out = paged_prefill_attention(
        jnp.asarray(q), jnp.asarray(kv), jnp.asarray(tables),
        jnp.asarray(ctx), jnp.asarray(start), scale=scale, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out)[0], np.asarray(ref)[0],
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.isfinite(np.asarray(out)[1]))  # l=0 guarded


def test_prefill_kernel_multi_tile():
    """T > PREFILL_Q_TILE splits the query axis over grid tiles; the flash
    state must reset per (row, tile). Exercised by shrinking the tile."""
    from vllm_production_stack_tpu.ops import paged_attention_pallas as pk

    q, kv, tables, ctx, start = _prefill_setup(t=16, hists=(5, 0))
    scale = q.shape[-1] ** -0.5
    ref = _prefill_ref(q, kv, tables, ctx, start, scale)
    orig = pk.PREFILL_Q_TILE
    pk.PREFILL_Q_TILE = 4
    try:
        # bypass the jit wrapper: the module constant is baked per trace
        out = pk.paged_prefill_attention.__wrapped__(
            jnp.asarray(q), jnp.asarray(kv), jnp.asarray(tables),
            jnp.asarray(ctx), jnp.asarray(start), scale=scale, interpret=True,
        )
    finally:
        pk.PREFILL_Q_TILE = orig
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def _prefill_case(rows, nb, bs, kvh, qpk, d, t, seed=0, dtype=np.float32):
    """Rows of (chunk_start, context_len) in one t-token bucket, each holding
    exactly the pages its context needs; dead table slots point at pages that
    hold other data and belong to no row."""
    rng = np.random.RandomState(seed)
    start = np.asarray([r[0] for r in rows], np.int32)
    ctx = np.asarray([r[1] for r in rows], np.int32)
    held = -(-ctx // bs)
    num_blocks = int(held.sum()) + 9
    kv = rng.randn(2, num_blocks, bs, kvh, d).astype(dtype)
    owned = rng.permutation(np.arange(1, num_blocks - 8))
    tables = rng.randint(num_blocks - 8, num_blocks, size=(len(rows), nb))
    at = 0
    for i, n in enumerate(held):
        tables[i, :n] = owned[at:at + n]
        at += n
    q = rng.randn(len(rows), t, kvh * qpk, d).astype(dtype)
    return q, kv, tables.astype(np.int32), ctx, start


def _prefill_kernel_out(case):
    from vllm_production_stack_tpu.ops.paged_attention_pallas import (
        paged_prefill_attention,
    )

    q, kv, tables, ctx, start = case
    return np.asarray(paged_prefill_attention(
        jnp.asarray(q), jnp.asarray(kv), jnp.asarray(tables),
        jnp.asarray(ctx), jnp.asarray(start), scale=q.shape[-1] ** -0.5,
        interpret=True,
    ).astype(jnp.float32))


def _real_queries(case):
    """(B, T) bool: the chunk's own tokens. Queries past a row's context are
    bucket padding: never read, and the two backends differ on them (a tile
    of nothing but padding is skipped by the kernel)."""
    q, _, _, ctx, start = case
    return (start[:, None] + np.arange(q.shape[1])[None, :]) < ctx[:, None]


# name: (nb, bs, kvh, qpk, d, t, rows of (chunk_start, context_len), dtype).
# The page block is 256 tokens; the query tile 256 rows, fewer where every
# head of it would not fit (_prefill_tiling).
PREFILL_CASES = {
    # the benchmark cells' head shapes, bf16 as the chip runs them: a first
    # chunk, one that starts mid-page, a padding row, a 4-page prompt
    "qwen3-8b-heads-bf16": (64, 32, 8, 4, 128, 128,
                            [(0, 128), (45, 173), (0, 0), (0, 100)],
                            jnp.bfloat16),
    "qwen2-7b-group7-bf16": (64, 32, 4, 7, 128, 128,
                             [(0, 128), (45, 173), (0, 0), (300, 428)],
                             jnp.bfloat16),
    # ... and in the 256-query tile its 512-token chunks run: a group of
    # seven heads in two items of three and a half
    "qwen2-7b-group7-tile256-bf16": (64, 32, 4, 7, 128, 256,
                                     [(45, 301), (0, 128), (0, 0)],
                                     jnp.bfloat16),
    "olmoe-mha-bf16": (64, 32, 16, 1, 128, 128,
                       [(0, 128), (45, 173), (0, 0), (300, 428)],
                       jnp.bfloat16),
    # the second 512-token chunk of a 1,024-token prompt (resident history +
    # itself) beside a 128-token prompt in the same bucket: several query
    # tiles, three of the short row's four skipped
    "second-chunk-of-1024": (64, 32, 8, 4, 128, 512,
                             [(512, 1024), (0, 128)], np.float32),
    # a row holding 4 pages, in the 64- and in the 128-page table
    "four-pages-table-64": (64, 32, 2, 2, 128, 256, [(0, 128), (0, 256)],
                            np.float32),
    "four-pages-table-128": (128, 32, 2, 2, 128, 256, [(0, 128), (0, 256)],
                             np.float32),
    # the last chunk of a full table; an odd head count (indexed, not
    # strided); 16-token pages; a table narrower than a block
    "full-table": (64, 32, 2, 2, 64, 64, [(1984, 2048), (1000, 1064)],
                   np.float32),
    "one-kv-head-bf16": (32, 32, 1, 4, 128, 64, [(0, 64), (70, 134)],
                         jnp.bfloat16),
    "page16": (64, 16, 2, 2, 64, 64, [(0, 64), (250, 300), (0, 0)],
               np.float32),
    "table-narrower-than-a-block": (3, 32, 2, 2, 64, 32, [(0, 20), (60, 92)],
                                    np.float32),
    # the long-document cells' head shapes (Laguna-S-2.1's full layers,
    # Solar-Open2's GQA layers), reduced in length only: a chunk of four
    # 128-query tiles behind several 256-key blocks that starts inside a
    # page, a short second row (three of its tiles skipped), a padding row
    "laguna-full-48-8-bf16": (64, 32, 8, 6, 128, 512,
                              [(300, 812), (0, 100), (0, 0)], jnp.bfloat16),
    "solar-gqa-64-8-bf16": (64, 32, 8, 8, 128, 512,
                            [(557, 1069), (45, 173), (0, 0)], jnp.bfloat16),
}


@pytest.mark.parametrize("name", list(PREFILL_CASES))
def test_prefill_walk_kernel_matches_xla_reference(name):
    nb, bs, kvh, qpk, d, t, rows, dtype = PREFILL_CASES[name]
    case = _prefill_case(rows, nb, bs, kvh, qpk, d, t, seed=3, dtype=dtype)
    q, kv, tables, ctx, start = case
    out = _prefill_kernel_out(case)
    ref = np.asarray(_prefill_ref(
        q, kv, tables, ctx, start, d ** -0.5).astype(jnp.float32))
    tol = 2e-5 if dtype is np.float32 else 2e-2
    real = _real_queries(case)
    np.testing.assert_allclose(out[real], ref[real], rtol=tol, atol=tol)
    assert np.all(np.isfinite(out))  # padding too: it flows on to the MLP


@pytest.mark.parametrize("name,qpk,window,block,rows", [
    # Laguna-S-2.1's window layers: 72 heads on 8 kv heads, window 512 — a
    # 128-query tile's walk of three 256-key blocks crosses the window's
    # edge of its first query, that of its last, and the diagonal; pages
    # behind the chunk's window were given back (the null page would show)
    ("laguna-window-72-8", 9, 512, 0, [(700, 956), (40, 140), (0, 0)]),
    # generation by diffusion over blocks of 4 at Solar-Open2's head shape:
    # a query sees the keys up to the end of its block
    ("block-4-64-8", 8, 0, 4, [(300, 556), (0, 64), (0, 0)]),
])
def test_prefill_kernel_masks_at_the_long_document_head_shapes(
        name, qpk, window, block, rows):
    """The window and the block mask of the prefill kernel against the
    masked XLA attention, bf16 at the cells' head shapes (1 bf16 ulp)."""
    from vllm_production_stack_tpu.ops import paged_attention_pallas as pk
    from vllm_production_stack_tpu.ops.attention import (
        causal_page_mask, paged_attention_xla,
    )

    nb, bs, kvh, d, t = 64, 32, 8, 128, 256
    case = _prefill_case(rows, nb, bs, kvh, qpk, d, t, seed=11,
                         dtype=jnp.bfloat16)
    q, kv, tables, ctx, start = case
    if window:
        # pages no query of the chunk sees any more: given back
        kv = np.array(kv)
        kv[:, 0] = 100.0
        for r, (s0, _) in enumerate(rows):
            tables[r, :max(0, s0 - window + 1) // bs] = 0
    kernel = (
        functools.partial(pk.paged_window_prefill_attention, window=window)
        if window else
        functools.partial(pk.paged_prefill_attention, block=block))
    out = np.asarray(kernel(
        jnp.asarray(q), jnp.asarray(kv), jnp.asarray(tables),
        jnp.asarray(ctx), jnp.asarray(start), scale=d ** -0.5,
        interpret=True).astype(jnp.float32))
    pos = start[:, None] + np.arange(t)[None]
    mask = causal_page_mask(
        jnp.asarray(pos), jnp.asarray(ctx), nb * bs,
        **({"window": window} if window else {"block": block}))
    ref = np.asarray(paged_attention_xla(
        jnp.asarray(q), jnp.asarray(kv), jnp.asarray(tables), mask,
        scale=d ** -0.5).astype(jnp.float32))
    real = _real_queries(case)
    np.testing.assert_allclose(out[real], ref[real], rtol=2e-2, atol=2e-2)
    assert np.all(np.isfinite(out))


@pytest.mark.parametrize("tt,qpk,sub", [
    (128, 6, 384),   # Laguna-S-2.1's full layers: two items of three heads
    (128, 9, 384),   # ... its window layers: three items of three heads
    (128, 8, 512),   # Solar-Open2: two items of four heads
    (256, 4, 512),   # qwen3-8b: two items of two heads
    (256, 7, 896),   # qwen2-7b: two items of three and a half heads
    (256, 1, 128),   # olmoe-1b-7b: one head, its tokens in two items
    (128, 1, 128),   # one head of one sub-tile: one item
    (128, 7, 896),   # a prime group that only seven items divide: whole
    (64, 4, 128),    # a short bucket: whole heads side by side
    (16, 6, 48),
    (4, 2, 4),       # the toy shapes of this file
    (8, 1, 8),
])
def test_prefill_items_are_the_fewest_equal_cuts_of_a_group(tt, qpk, sub):
    """Two items a kv group where whole sub-tiles allow it, never more than
    PREFILL_MAX_ITEMS: every item is traced and lowered again at every
    engine start."""
    from vllm_production_stack_tpu.ops import paged_attention_pallas as pk

    assert pk._prefill_sub_tile(tt, qpk) == sub
    assert (qpk * tt) % sub == 0
    assert qpk * tt // sub <= pk.PREFILL_MAX_ITEMS
    # an item is whole sub-tiles of a head's tokens, or whole short heads
    assert sub % (pk.PREFILL_SUB_TILE if tt % pk.PREFILL_SUB_TILE == 0
                  else tt) == 0


def test_prefill_dead_table_slots_and_width_do_not_reach_a_row():
    """What a row's output depends on: its own pages. Bitwise the same alone
    and among others, in the 64- and the 128-page table, and whatever the
    slots past its pages point at."""
    rows = [(0, 256), (0, 0), (512, 700), (45, 173)]
    case = _prefill_case(rows, 64, 32, 2, 2, 128, 256, seed=9)
    q, kv, tables, ctx, start = case
    out, real = _prefill_kernel_out(case), _real_queries(case)
    at = slice(2, 3)
    alone = _prefill_kernel_out(
        (q[at], kv, tables[at], ctx[at], start[at]))
    np.testing.assert_array_equal(alone[0][real[2]], out[2][real[2]])
    wide = np.zeros((len(rows), 128), np.int32)  # dead slots: the null page
    wide[:, :64] = tables
    np.testing.assert_array_equal(
        _prefill_kernel_out((q, kv, wide, ctx, start))[real], out[real])


def test_prefill_kernel_walks_only_the_blocks_a_tile_needs():
    """The walk's trip count, read off the copies the kernel starts: pages
    past a row's context and blocks after a tile's last position are never
    copied — poisoned, they cannot reach the output."""
    rows = [(0, 128), (256, 512), (0, 0)]
    case = _prefill_case(rows, 64, 32, 2, 2, 128, 256, seed=4)
    q, kv, tables, ctx, start = case
    clean = _prefill_kernel_out(case)
    poisoned = kv.copy()
    used = set()
    for i, n in enumerate(-(-ctx // 32)):
        used.update(tables[i, :n].tolist())
    # the pages of the 256-token block that holds row 0's four pages are
    # copied whole (a block is the unit), every other page is not
    in_block = set(tables[0, :8].tolist())
    spare = [b for b in range(1, kv.shape[1]) if b not in used | in_block]
    poisoned[:, spare] = np.nan
    np.testing.assert_array_equal(
        _prefill_kernel_out((q, poisoned, tables, ctx, start)), clean)


# The chunk write kernel's cases: (kv heads, pool dtype, page size, rows as
# (resident tokens before the chunk, chunk tokens) — (h, 0) a row of zero
# tokens, None a padding row: every id on the null page —, the packed axis).
KV_WRITE_CASES = {
    # a row that starts mid-page and ends mid-page, a page-aligned row
    "mid_page_start_and_end": (4, "bfloat16", 8, [(13, 22), (16, 8)], 32),
    # a chunk that ends mid-page from a page boundary; one token on a page
    "ends_mid_page": (4, "bfloat16", 8, [(0, 19), (31, 1)], 32),
    "row_of_zero_tokens": (4, "bfloat16", 8, [(5, 9), (12, 0), (0, 7)], 16),
    "padding_row_on_the_null_page": (
        4, "bfloat16", 8, [(3, 12), None, None, None], 16),
    # two rows end to end: the second's tokens start inside a block of the
    # packed axis (17 is no multiple of 8), and both run to the axis' end
    "seam_inside_a_block": (4, "bfloat16", 8, [(6, 17), (40, 47)], 64),
    "two_kv_heads": (2, "bfloat16", 8, [(13, 22), (0, 10)], 32),
    "eight_kv_heads": (8, "bfloat16", 8, [(13, 22), (0, 10)], 32),
    "float32_pool_32_token_pages": (
        4, "float32", 32, [(45, 70), (0, 33), (64, 25)], 128),
    "int8_pool": (4, "int8", 8, [(13, 22), (16, 8)], 32),
    "fp8_pool": (4, "float8_e4m3fn", 8, [(13, 22), (16, 8)], 32),
    # the chunk is the whole bucket and starts on a page's last slot: the
    # widest span a (B, NBW) table holds
    "widest_span": (4, "bfloat16", 8, [(7, 32)], 32),
}


def _kv_write_case(kvh, dtype, bs, rows, n, seed=0, num_blocks=48, d=128):
    """(pool, k, v, write ids, start_off, chunk_lens, row_off): rows hold
    distinct pages, none the null page; the new rows come in float32 (the
    activations' dtype) whatever the pool's."""
    rng = np.random.RandomState(seed)
    pool = jnp.asarray(
        rng.randn(2, num_blocks, bs, kvh, d) * 3).astype(dtype)
    k, v = (jnp.asarray(rng.randn(n, kvh, d) * 3, jnp.float32)
            for _ in range(2))
    nbw = (n - 1) // bs + 2
    pages = iter(rng.permutation(np.arange(1, num_blocks)))
    ids = np.zeros((len(rows), nbw), np.int32)
    off, lens, row_off = (np.zeros(len(rows), np.int32) for _ in range(3))
    at = 0
    for i, row in enumerate(rows):
        row_off[i] = at
        if row is None:
            continue
        hist, lens[i] = row
        off[i] = hist % bs
        if lens[i]:
            span = (hist + lens[i] - 1) // bs - hist // bs + 1
            ids[i, :span] = [next(pages) for _ in range(span)]
        at += lens[i]
    assert at <= n
    return pool, k, v, *map(jnp.asarray, (ids, off, lens, row_off))


def _same_bits(a, b):
    np.testing.assert_array_equal(
        np.asarray(a).view(np.uint8), np.asarray(b).view(np.uint8))


@pytest.mark.parametrize("name", [
    *KV_WRITE_CASES, "window_page_group", "row_major_chunk"])
def test_kv_write_kernel_matches_the_xla_form_bit_for_bit(name):
    """`paged_kv_write` (interpret mode) against its oracle,
    `write_kv_pages_blockwise`, over the WHOLE pool: the chunk's tokens on
    their pages and every other byte as it was, the null page included."""
    from vllm_production_stack_tpu.models import llama
    from vllm_production_stack_tpu.ops.attention import (
        write_kv_pages_blockwise,
    )
    from vllm_production_stack_tpu.ops.paged_attention_pallas import (
        paged_kv_write,
    )

    if name == "window_page_group":
        # a window layer writes the same span through the window group's
        # ids into the window group's (smaller) pool: llama._group picks
        # the table, the call is the same
        pool, k, v, ids, off, lens, row_off = _kv_write_case(
            4, "bfloat16", 8, [(13, 22), (16, 8)], 32, num_blocks=48)
        small, _, _, ids_w, *_ = _kv_write_case(
            4, "bfloat16", 8, [(13, 22), (16, 8)], 32, seed=1, num_blocks=12)
        for window, kv in ((0, pool), (16, small)):
            table = llama._group((ids, ids_w), window)
            assert table is (ids_w if window else ids)
            want = write_kv_pages_blockwise(
                kv, k, v, table, off, lens, row_off=row_off)
            _same_bits(paged_kv_write(
                kv, k, v, table, off, lens, row_off, interpret=True), want)
        return
    if name == "row_major_chunk":
        # (B, T) rows, as a dp > 1 prefill or a bare llama.forward has
        # them: the kernel takes them end to end, each row at i * T
        pool, k, v, ids, off, lens, _ = _kv_write_case(
            4, "bfloat16", 8, [(13, 9), (0, 16), (7, 0)], 48)
        k, v = k.reshape(3, 16, 4, 128), v.reshape(3, 16, 4, 128)
        ids = ids[:, :3]  # (T - 1) // bs + 2 pages a row
        want = write_kv_pages_blockwise(pool, k, v, ids, off, lens)
        got = paged_kv_write(
            pool, k.reshape(-1, 4, 128), v.reshape(-1, 4, 128), ids, off,
            lens, jnp.arange(3, dtype=jnp.int32) * 16, interpret=True)
        _same_bits(got, want)
        return
    pool, k, v, ids, off, lens, row_off = _kv_write_case(
        *KV_WRITE_CASES[name])
    want = write_kv_pages_blockwise(
        pool, k, v, ids, off, lens, row_off=row_off)
    got = paged_kv_write(pool, k, v, ids, off, lens, row_off, interpret=True)
    assert got.dtype == pool.dtype and got.shape == pool.shape
    _same_bits(got, want)
    # something was written, and only inside the rows' spans
    changed = np.asarray(
        (got.astype(jnp.float32) != pool.astype(jnp.float32))).sum()
    assert 0 < changed <= 2 * int(lens.sum()) * pool.shape[3] * pool.shape[4]


def test_kv_write_kernel_reads_no_page_and_spares_the_null_page():
    """Pages a chunk does not touch are never read or written — poisoned
    with NaN they stay NaN and nothing else turns NaN — and padding rows
    leave the null page alone."""
    from vllm_production_stack_tpu.ops.paged_attention_pallas import (
        paged_kv_write,
    )

    pool, k, v, ids, off, lens, row_off = _kv_write_case(
        2, "float32", 8, [(3, 12), None, (8, 8)], 32)
    used = sorted(set(np.asarray(ids).ravel().tolist()) - {0})
    spare = [b for b in range(pool.shape[1]) if b not in used]
    poisoned = pool.at[:, jnp.asarray(spare)].set(jnp.nan)
    got = np.asarray(paged_kv_write(
        poisoned, k, v, ids, off, lens, row_off, interpret=True))
    assert np.isnan(got[:, spare]).all()
    assert not np.isnan(got[:, used]).any()
    # the first row's first page keeps its three resident tokens
    first = int(ids[0, 0])
    np.testing.assert_array_equal(
        got[:, first, :3], np.asarray(pool)[:, first, :3])


@pytest.mark.parametrize("model", ["tiny-llama", "tiny-laguna"])
def test_engine_chunk_write_kernel_serves_the_xla_forms_tokens(model):
    """A chunked prefill, then decode, of a tiny preset: the Pallas backend
    (write kernel + prefill kernel, interpret mode) gives the tokens the XLA
    backend gives, and /debug/timing says which write ran: the kernel wrote
    the pages the rows' spans hold and read none back, the XLA form read
    and rewrote every slot of its (rows, pages) table. tiny-laguna writes
    through both page groups' tables."""
    from vllm_production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, SchedulerConfig,
    )
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.request import SamplingParams
    from vllm_production_stack_tpu.models.registry import (
        resolve_model_config,
    )

    def make(prefill_backend):
        return LLMEngine(EngineConfig(
            model=resolve_model_config(model),
            cache=CacheConfig(block_size=8, num_blocks=128,
                              enable_prefix_caching=False),
            scheduler=SchedulerConfig(
                max_num_seqs=2, max_num_batched_tokens=32,
                prefill_buckets=(32,), decode_buckets=(2,), decode_window=4,
            ),
            attention_backend="xla",
            prefill_attention_backend=prefill_backend,
        ))

    rng = np.random.RandomState(3)
    prompts = [list(rng.randint(1, 200, size=n)) for n in (45, 70)]
    sp = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    kernel, xla = make("pallas_interpret"), make("xla")
    assert kernel.runner.prefill_writes_by_kernel
    assert not xla.runner.prefill_writes_by_kernel
    out_k, out_x = kernel.generate(prompts, sp), xla.generate(prompts, sp)
    for a, b in zip(out_k, out_x):
        assert a["token_ids"] == b["token_ids"]
    tk, tx = kernel.flightrec.timing(), xla.flightrec.timing()
    assert tk["prefill_n"] == tx["prefill_n"] >= 4
    # 45 and 70 tokens lie on 6 and 9 pages of 8; a chunk that starts
    # mid-page touches that page again
    assert 15 <= tk["kv_write_pages_n"] <= 15 + tk["prefill_n"]
    assert tk["kv_write_read_pages_n"] == 0
    assert tx["kv_write_pages_n"] == tx["kv_write_read_pages_n"] > (
        tk["kv_write_pages_n"])


def test_prefill_sharded_matches_unsharded_tp2_dp2():
    """shard_map placement of the prefill kernel over (dp=2, tp=2): pure
    placement, no collective — must match the single-instance kernel."""
    from vllm_production_stack_tpu.ops.paged_attention_pallas import (
        paged_prefill_attention, paged_prefill_attention_sharded,
    )
    from vllm_production_stack_tpu.parallel import mesh as mesh_lib

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = mesh_lib.make_mesh(tensor_parallel_size=2, data_parallel_size=2,
                              devices=jax.devices()[:4])
    q, kv, tables, ctx, start = _prefill_setup(b=4, hists=(8, 13, 0, 21))
    scale = q.shape[-1] ** -0.5
    ref = paged_prefill_attention(
        jnp.asarray(q), jnp.asarray(kv), jnp.asarray(tables),
        jnp.asarray(ctx), jnp.asarray(start), scale=scale, interpret=True,
    )
    out = paged_prefill_attention_sharded(
        mesh, jnp.asarray(q), jnp.asarray(kv), jnp.asarray(tables),
        jnp.asarray(ctx), jnp.asarray(start), scale=scale, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_engine_chunked_prefill_pallas_backend_matches_xla():
    """End-to-end through the ENGINE: prompts longer than
    max_num_batched_tokens force CHUNKED prefill (later chunks attend
    resident earlier chunks + themselves); the pallas prefill backend must
    reproduce the XLA backend's greedy tokens exactly. Decode stays XLA in
    both so the diff isolates prefill."""
    from vllm_production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.request import SamplingParams

    def make(prefill_backend):
        return LLMEngine(EngineConfig(
            model=ModelConfig.tiny(max_model_len=512),
            cache=CacheConfig(block_size=8, num_blocks=128),
            scheduler=SchedulerConfig(
                max_num_seqs=2, max_num_batched_tokens=64,
                prefill_buckets=(32, 64), decode_buckets=(2,),
                decode_window=4,
            ),
            attention_backend="xla",
            prefill_attention_backend=prefill_backend,
        ))

    rng = np.random.RandomState(11)
    prompts = [list(rng.randint(1, 500, size=n)) for n in (90, 150)]
    sp = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    out_pallas = make("pallas_interpret").generate(prompts, sp)
    out_xla = make("xla").generate(prompts, sp)
    for i in range(2):
        assert out_pallas[i]["token_ids"] == out_xla[i]["token_ids"]


def test_auto_prefill_backend_policy_gates():
    """Prefill 'auto' is a pure predicate of static shapes and picks the
    kernel where the sweep measured it (bf16 pools whose pages fill HBM
    tiles, 32-token pages, one device); XLA everywhere else."""
    from vllm_production_stack_tpu.engine.model_runner import (
        resolve_auto_prefill_backend as auto,
    )

    base = dict(block_size=32, num_kv_heads=8, head_dim=128, kv_itemsize=2,
                kv_quantized=False, mesh_size=1, platform="tpu")
    assert auto(**base) == "pallas"  # qwen3-8b: cell 1
    assert auto(**{**base, "num_kv_heads": 4}) == "pallas"  # qwen2-7b
    assert auto(**{**base, "num_kv_heads": 16}) == "pallas"  # olmoe
    assert auto(**{**base, "head_dim": 64}) == "xla"  # llama-1b: half tiles
    assert auto(**{**base, "num_kv_heads": 1}) == "xla"  # a tp shard's head
    assert auto(**{**base, "kv_itemsize": 1, "kv_quantized": True}) == "xla"
    assert auto(**{**base, "block_size": 16}) == "xla"  # not swept
    assert auto(**{**base, "mesh_size": 4}) == "xla"  # no GSPMD rule
    assert auto(**{**base, "platform": "cpu"}) == "xla"  # needs Mosaic


@pytest.mark.parametrize(
    "model", ["tiny-gemma2", "tiny-mistral-sliding", "tiny-softcap"])
def test_prefill_backend_is_xla_for_softcapped_models_only(model):
    """The kernels have no softcap: 'auto' resolves to XLA for a softcapped
    model whatever its shapes, an explicit 'pallas' is refused at
    construction, and the model code refuses it too. A window model WITHOUT
    softcaps has kernels of its own (paged_window_*_attention): an explicit
    'pallas' is served."""
    import dataclasses

    from vllm_production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from vllm_production_stack_tpu.engine.model_runner import ModelRunner
    from vllm_production_stack_tpu.models import llama

    tiny = ModelConfig.tiny()
    cfg = {
        "tiny-gemma2": dataclasses.replace(
            tiny, sliding_window=16, sliding_window_pattern=2,
            attn_logit_softcap=50.0),
        "tiny-mistral-sliding": dataclasses.replace(tiny, sliding_window=16),
        "tiny-softcap": dataclasses.replace(tiny, attn_logit_softcap=30.0),
    }[model]

    def runner(backend):
        return ModelRunner(EngineConfig(
            model=cfg, cache=CacheConfig(block_size=32, num_blocks=32),
            scheduler=SchedulerConfig(
                max_num_seqs=2, max_num_batched_tokens=64,
                decode_buckets=(2,), prefill_buckets=(64,), decode_window=4,
            ),
            attention_backend="xla", prefill_attention_backend=backend,
        ))

    assert runner("auto")._prefill_backend == "xla"
    assert not runner("auto").prefill_uses_kernel
    if model == "tiny-mistral-sliding":
        assert runner("pallas_interpret").prefill_uses_kernel
        return
    with pytest.raises(ValueError, match="does not support softcapped"):
        runner("pallas_interpret")
    with pytest.raises(NotImplementedError, match="softcapped models"):
        llama.forward(cfg, None, None, jnp.zeros((1, 4), jnp.int32), None,
                      None, None, None, backend="pallas_interpret")


@pytest.mark.parametrize("backend,resolved", [
    ("pallas_interpret", "pallas_interpret"), ("xla", "xla"), ("auto", "xla"),
])
def test_prefill_kernel_counter_follows_the_backend(backend, resolved):
    """/debug/timing's prefill_kernel_n: prefill dispatches whose program
    holds the kernel — every one under pallas, none under xla (and 'auto'
    is XLA on this platform)."""
    from vllm_production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.request import SamplingParams

    eng = LLMEngine(EngineConfig(
        model=ModelConfig.tiny(max_model_len=256),
        cache=CacheConfig(block_size=8, num_blocks=64),
        scheduler=SchedulerConfig(
            max_num_seqs=2, max_num_batched_tokens=32,
            prefill_buckets=(32,), decode_buckets=(2,), decode_window=4,
        ),
        attention_backend="xla", prefill_attention_backend=backend,
    ))
    assert eng.runner.device_report()["attention"]["prefill"] == {
        "requested": backend, "resolved": resolved}
    eng.generate([list(range(1, 70))],
                 SamplingParams(max_tokens=3, temperature=0.0,
                                ignore_eos=True))
    t = eng.flightrec.timing()
    assert t["prefill_n"] >= 3  # 69 tokens in 32-token chunks
    assert t["prefill_kernel_n"] == (
        t["prefill_n"] if resolved.startswith("pallas") else 0)


def test_explicit_prefill_kernel_needs_pages_that_fill_tiles():
    """On a chip the kernel slices pages out of HBM itself: an explicit
    'pallas' over a pool whose pages do not fill whole tiles (D = 16 here;
    D = 64, one-head tp shards) is refused at construction, not at the
    first compile. Interpret mode has no tiles and serves any shape."""
    from vllm_production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from vllm_production_stack_tpu.engine.model_runner import ModelRunner

    def runner(backend):
        return ModelRunner(EngineConfig(
            model=ModelConfig.tiny(),
            cache=CacheConfig(block_size=32, num_blocks=32),
            scheduler=SchedulerConfig(
                max_num_seqs=2, max_num_batched_tokens=64,
                decode_buckets=(2,), prefill_buckets=(64,),
            ),
            attention_backend="xla", prefill_attention_backend=backend,
        ))

    with pytest.raises(ValueError, match="fill whole HBM tiles"):
        runner("pallas")
    assert runner("pallas_interpret").prefill_uses_kernel


def test_auto_backend_policy_gates():
    """'auto' picks the measured winner — every gate of the pure predicate
    covered directly (the sweep's decision table), plus the runner wiring
    on this (CPU) platform."""
    from vllm_production_stack_tpu.engine.model_runner import (
        resolve_auto_attention_backend as auto,
    )

    base = dict(block_size=32, max_model_len=8192, mesh_size=1,
                kv_quantized=False, platform="tpu")
    assert auto(**base) == "pallas"  # the winning regime
    assert auto(**{**base, "block_size": 16}) == "xla"  # small pages
    assert auto(**{**base, "max_model_len": 2048}) == "xla"  # short ctx
    assert auto(**{**base, "mesh_size": 2}) == "xla"  # no GSPMD rule
    assert auto(**{**base, "kv_quantized": True}) == "xla"  # fp8 pool
    assert auto(**{**base, "platform": "cpu"}) == "xla"  # needs Mosaic

    # runner wiring: on the CPU test platform auto must resolve to xla
    from vllm_production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from vllm_production_stack_tpu.engine.model_runner import ModelRunner

    r = ModelRunner(EngineConfig(
        model=ModelConfig.tiny(),
        cache=CacheConfig(block_size=32, num_blocks=32),
        scheduler=SchedulerConfig(
            max_num_seqs=2, max_num_batched_tokens=64,
            decode_buckets=(2,), prefill_buckets=(64,),
        ),
    ))
    assert r._attention_backend == "xla"


def test_pallas_fp8_pool_numerics():
    """fp8 KV pool through the Pallas kernel: same greedy outputs as the
    XLA backend over the same fp8 pool (both upconvert pages to the
    compute dtype — the kernel in VMEM, XLA in the gather)."""
    import numpy as np

    from vllm_production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.request import SamplingParams

    def make(backend):
        return LLMEngine(EngineConfig(
            model=ModelConfig.tiny(max_model_len=512),
            cache=CacheConfig(block_size=32, num_blocks=64,
                              kv_cache_dtype="fp8"),
            scheduler=SchedulerConfig(
                max_num_seqs=2, max_num_batched_tokens=128,
                prefill_buckets=(64, 128), decode_buckets=(2,),
                decode_window=4,
            ),
            attention_backend=backend,
        ))

    rng = np.random.RandomState(9)
    prompts = [list(rng.randint(1, 500, size=90)) for _ in range(2)]
    sp = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    out_pallas = make("pallas_interpret").generate(prompts, sp)
    out_xla = make("xla").generate(prompts, sp)
    for i in range(2):
        assert out_pallas[i]["token_ids"] == out_xla[i]["token_ids"]


def test_sharded_kernel_matches_unsharded_tp2_dp2():
    """shard_map placement over a (dp=2, tp=2) mesh must reproduce the
    single-instance kernel bit-for-bit: decode attention parallelizes over
    (row, head) with no collective, so sharding is pure placement."""
    from vllm_production_stack_tpu.ops.paged_attention_pallas import (
        paged_decode_attention_sharded,
    )
    from vllm_production_stack_tpu.parallel import mesh as mesh_lib

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = mesh_lib.make_mesh(tensor_parallel_size=2, data_parallel_size=2,
                              devices=jax.devices()[:4])
    q, kv, tables, hist_len, staged_k, staged_v = _setup(b=4, kvh=2, qpk=2)
    scale = q.shape[-1] ** -0.5
    ref = paged_decode_attention(
        jnp.asarray(q[:, 0]), jnp.asarray(kv), jnp.asarray(tables),
        jnp.asarray(hist_len), jnp.asarray(staged_k), jnp.asarray(staged_v),
        jnp.asarray(np.int32(2)), scale=scale, interpret=True,
    )
    out = paged_decode_attention_sharded(
        mesh, jnp.asarray(q[:, 0]), jnp.asarray(kv), jnp.asarray(tables),
        jnp.asarray(hist_len), jnp.asarray(staged_k), jnp.asarray(staged_v),
        jnp.asarray(np.int32(2)), scale=scale, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_engine_serves_pallas_under_tp2():
    """End-to-end: the ENGINE's fused decode window through the sharded
    kernel on a tp=2 mesh matches the XLA backend's greedy output."""
    from vllm_production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, ParallelConfig,
        SchedulerConfig,
    )
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.request import SamplingParams
    from vllm_production_stack_tpu.parallel import mesh as mesh_lib

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    cfg = ModelConfig.tiny(num_heads=4, num_kv_heads=2)
    base = EngineConfig(
        model=cfg,
        cache=CacheConfig(block_size=8, num_blocks=64),
        scheduler=SchedulerConfig(
            max_num_seqs=2, max_num_batched_tokens=64,
            decode_buckets=(2,), prefill_buckets=(32, 64), decode_window=4,
        ),
    )
    prompts = [
        list(np.random.RandomState(i).randint(1, cfg.vocab_size, size=20))
        for i in range(2)
    ]
    sampling = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)

    ref_eng = LLMEngine(base)
    ref_out = [o["token_ids"] for o in ref_eng.generate(prompts, sampling)]

    tp_mesh = mesh_lib.make_mesh(tensor_parallel_size=2,
                                 devices=jax.devices()[:2])
    tp_eng = LLMEngine(
        base.replace(
            parallel=ParallelConfig(tensor_parallel_size=2),
            attention_backend="pallas_interpret",
        ),
        mesh=tp_mesh,
    )
    tp_out = [o["token_ids"] for o in tp_eng.generate(prompts, sampling)]
    assert tp_out == ref_out
