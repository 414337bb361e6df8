"""Learned sparse attention over the paged latents (`model_type:
glm_moe_dsa`; docs/55-selected-keys.md): an INDEXER scores every key a query
may see, the best `topk` are SELECTED — exactly: no approximate top-k, no
block-granular stand-in, no "every key" shortcut — and absorbed latent
attention (ops/mla_attention.py) runs over the selected tokens alone.

    I[t, s] = Σ_h w[t, h] · ReLU(q_idx[t, h] · k_idx[s])      s <= t
    S_t     = the min(t + 1, topk) positions of largest I[t, ·]
              (a tie goes to the lower position)
    o[t]    = softmax over s in S_t of (q_abs[t] · latent[s]) · latent[s][:rank]

The pool of a layer that owns an indexer holds a second leaf beside its
latents: one index key a token, `(1, num_blocks, block_size, index_head_dim)`,
addressed by the SAME block table. What each path reads of the pool:

- a prefill chunk (`dsa_prefill_attention`): row by row, and a row's chunk
  in query tiles of `Q_TILE` tokens. A layer with an indexer scores the
  tile against every index key up to the tile's last position
  (`index_scores`), finds each query's k-th best score by bisection over the
  scores' bits and keeps what lies above it, ties by position
  (`exact_topk_mask`) — the SELECTION is a (query, key position) mask — and
  attends under the mask, walking the row's latents in blocks of `KEY_BLOCK`
  up to the tile's last position; a layer that BORROWS attends under the
  mask it was handed. A masked pass reads every visible latent: at a
  2,048-token chunk a query tile shares its keys' bytes 512 ways, so the
  bytes are not what a chunk waits for;
- a decode step (`dsa_index_decode`, `dsa_decode_attention`): the indexer
  reads the row's index keys (256 B a token) and takes an exact
  `jax.lax.top_k` (ties to the lower index); attention GATHERS the selected
  tokens' latents by slot — `topk` rows of the pool a query, never a page
  of what was not selected — and the window's own tokens from the staging
  buffer.

A row's arithmetic never meets another row's: each row is computed alone
from its own slice of the packed chunk, so the same row selects the same
positions alone and among fifteen others. Every function here is XLA — the
CPU tests, the chip and the reference check run the same code; the device
scopes (`dsa_index_score`, `dsa_select`, `dsa_gather`, `dsa_attn`) are what
a capture books its seconds to.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# a prefill chunk's query tile, the attention's key block and the indexer's
Q_TILE = 512
KEY_BLOCK = 512
INDEX_KEY_BLOCK = 2048


def _block(s: int, want: int) -> int:
    """The widest key block <= `want` that divides `s` keys."""
    b = min(s, want)
    while s % b:
        b -= 1
    return b


def _ordered_bits(x: jax.Array) -> jax.Array:
    """float32 -> uint32, monotone: a < b as floats iff bits(a) < bits(b)
    (-0.0 is read as +0.0)."""
    b = jax.lax.bitcast_convert_type(
        jnp.where(x == 0.0, 0.0, x), jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def exact_topk_mask(scores: jax.Array,  # (Q, S) float32
                    visible: jax.Array,  # (Q, S) bool
                    k: int) -> jax.Array:
    """(Q, S) bool: each query's min(k, visible) visible positions of
    largest score, a tie to the lower position. The k-th best score's bits
    are found one at a time, from the top (32 counts of `u >= candidate`);
    what lies above it is in, and of what equals it the first `need` are (a
    cumulative count, computed only where some query has more equals than
    it needs)."""
    u = jnp.where(visible, _ordered_bits(scores), jnp.uint32(0))
    k_eff = jnp.minimum(jnp.sum(visible, axis=1, dtype=jnp.int32), k)

    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        n = jnp.sum(u >= cand[:, None], axis=1, dtype=jnp.int32)
        return jnp.where(n >= k_eff, cand, t)

    kth = jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(u.shape[:1], jnp.uint32))[:, None]
    above, equal = u > kth, (u == kth) & visible
    need = k_eff - jnp.sum(above, axis=1, dtype=jnp.int32)

    def first_of_equals(_):
        return equal & (jnp.cumsum(equal, axis=1, dtype=jnp.int32)
                        <= need[:, None])

    tied = jnp.any(jnp.sum(equal, axis=1, dtype=jnp.int32) > need)
    kept = jax.lax.cond(tied, first_of_equals,
                        lambda _: equal & (need > 0)[:, None], None)
    return above | kept


def _index_block(q_idx, w, keys):
    """Σ_h w[q, h] ReLU(q_idx[q, h] · keys[s]) — (Q, Hi, D), (Q, Hi) float32,
    (S, D) -> (Q, S) float32."""
    dots = jnp.einsum("qhd,sd->qhs", q_idx, keys.astype(q_idx.dtype),
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots) * w[:, :, None], axis=1)


def index_scores(q_idx: jax.Array,  # (Q, Hi, D) one row's query tile
                 w: jax.Array,  # (Q, Hi) float32, the scales folded in
                 keys: jax.Array,  # (S, D) the row's index keys by position
                 n_keys: jax.Array,  # scalar: keys [0, n_keys) are scored
                 ) -> jax.Array:
    """(Q, S) float32, what lies past `n_keys` (rounded up to a block) left
    at 0: the caller masks by position."""
    s = keys.shape[0]
    blk = _block(s, INDEX_KEY_BLOCK)

    def visit(j, out):
        part = _index_block(
            q_idx, w, jax.lax.dynamic_slice_in_dim(keys, j * blk, blk))
        return jax.lax.dynamic_update_slice_in_dim(out, part, j * blk, 1)

    return jax.lax.fori_loop(
        0, -(-n_keys // blk), visit,
        jnp.zeros((q_idx.shape[0], s), jnp.float32))


def _attend_block(q, block, keep, carry, scale, rank):
    """One online-softmax update of (Q, H, width) queries against one block
    of latents (T, width) under `keep` (Q, T): keys the block whole, values
    its first `rank` columns; statistics and accumulator float32."""
    m_prev, l_prev, acc = carry
    block = block.astype(q.dtype)
    s = jnp.einsum("qhc,tc->qht", q, block,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(keep[:, None, :], s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    # (a query that keeps nothing of the block: exp(NEG - NEG) would be 1)
    p = jnp.where(keep[:, None, :], jnp.exp(s - m_new[..., None]), 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    acc = acc * alpha[..., None] + jnp.einsum(
        "qht,tr->qhr", p.astype(block.dtype), block[:, :rank],
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc


def selected_attention(q: jax.Array,  # (Q, H, width) one row's query tile
                       lat: jax.Array,  # (S, width) the row's latents
                       keep: jax.Array,  # (Q, S) bool, the selection
                       n_keys: jax.Array,  # scalar: nothing kept past it
                       *, scale: float, rank: int) -> jax.Array:
    """Absorbed attention of a query tile over the keys `keep` marks, the
    latents walked in blocks up to `n_keys`. Returns (Q, H, rank); a query
    that keeps nothing (padding) gets zeros."""
    nq, nh, _ = q.shape
    blk = _block(lat.shape[0], KEY_BLOCK)

    def visit(j, carry):
        return _attend_block(
            q, jax.lax.dynamic_slice_in_dim(lat, j * blk, blk),
            jax.lax.dynamic_slice_in_dim(keep, j * blk, blk, axis=1),
            carry, scale, rank)

    _, l, acc = jax.lax.fori_loop(0, -(-n_keys // blk), visit, (
        jnp.full((nq, nh), NEG_INF, jnp.float32),
        jnp.zeros((nq, nh), jnp.float32),
        jnp.zeros((nq, nh, rank), jnp.float32)))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def dsa_prefill_attention(
    q_abs: jax.Array,  # (N, H, width) the PACKED chunk's absorbed queries
    lat_pool: jax.Array,  # (1, num_blocks, bs, width), the chunk's latents in
    block_tables: jax.Array,  # (B, nb)
    context_lens: jax.Array,  # (B,) resident tokens, this chunk included
    chunk_start: jax.Array,  # (B,) position of each row's first chunk token
    row_off: jax.Array,  # (B,) each row's offset on the packed axis
    *,
    index: dict | None,  # a layer that selects: {"q": (N, Hi, D), "w": (N,
    #   Hi) float32, "pool": (1, num_blocks, bs, D) the index keys, the
    #   chunk's in}; None: the layer borrows `selection`
    selection: jax.Array | None,  # (N + tile, S) bool, packed query x the
    #   key positions of the query's own row
    scale: float,
    rank: int,
    topk: int,
) -> tuple[jax.Array, jax.Array]:
    """A packed prefill chunk's attention under the selection, and the
    selection (made here, or handed through). Rows are computed one after
    another, each from its own slice of the packed axis and its own pages —
    a row's last tile runs past its tokens into the next row's, which is
    computed after it and overwrites what the tile left there."""
    n, nh, width = q_abs.shape
    b, nb = block_tables.shape
    bs = lat_pool.shape[2]
    s = nb * bs
    tile = min(n, Q_TILE)
    assert n % tile == 0, (n, tile)
    lens = context_lens - chunk_start
    q_pad = jnp.pad(q_abs, ((0, tile), (0, 0), (0, 0)))
    if index is not None:
        qi_pad = jnp.pad(index["q"], ((0, tile), (0, 0), (0, 0)))
        wi_pad = jnp.pad(index["w"], ((0, tile), (0, 0)))
    key_pos = jnp.arange(s, dtype=jnp.int32)[None, :]

    def row(r, carry):
        def live(carry):
            pages = block_tables[r]
            lat = lat_pool[0][pages].reshape(s, width)
            keys = (None if index is None else
                    index["pool"][0][pages].reshape(s, -1))

            def one_tile(t, carry):
                off = row_off[r] + t * tile
                in_tile = t * tile + jnp.arange(tile, dtype=jnp.int32)
                # a query's position; -1 past the row's chunk: it sees nothing
                pos = jnp.where(in_tile < lens[r],
                                chunk_start[r] + in_tile, -1)
                n_keys = jnp.minimum(
                    chunk_start[r] + (t + 1) * tile, context_lens[r])
                if index is None:
                    keep = jax.lax.dynamic_slice_in_dim(selection, off, tile)
                else:
                    with jax.named_scope("dsa_index_score"):
                        scores = index_scores(
                            jax.lax.dynamic_slice_in_dim(qi_pad, off, tile),
                            jax.lax.dynamic_slice_in_dim(wi_pad, off, tile),
                            keys, n_keys)
                    with jax.named_scope("dsa_select"):
                        keep = exact_topk_mask(
                            scores, key_pos <= pos[:, None], topk)
                    carry = {**carry, "sel": (
                        jax.lax.dynamic_update_slice_in_dim(
                            carry["sel"], keep, off, 0))}
                with jax.named_scope("dsa_attn"):
                    o = selected_attention(
                        jax.lax.dynamic_slice_in_dim(q_pad, off, tile),
                        lat, keep, n_keys, scale=scale, rank=rank)
                return {**carry, "out": jax.lax.dynamic_update_slice_in_dim(
                    carry["out"], o, off, 0)}

            return jax.lax.fori_loop(
                0, -(-lens[r] // tile), one_tile, carry)

        return jax.lax.cond(lens[r] > 0, live, lambda c: c, carry)

    # (a layer that borrows reads the selection it was handed and hands the
    # very array on: only a layer that selects carries one through the loop)
    carry = {"out": jnp.zeros((n + tile, nh, rank), q_abs.dtype)}
    if index is not None:
        carry["sel"] = jnp.zeros((n + tile, s), bool)
    done = jax.lax.fori_loop(0, b, row, carry)
    return done["out"][:n], done.get("sel", selection)


def selection_indices(selection: jax.Array, topk: int) -> jax.Array:
    """A selection mask (N, S) as sorted key positions (N, min(topk, S)),
    -1 past a query's count: what a tap hands the reference check."""
    k = min(topk, selection.shape[1])
    order = jnp.argsort(~selection, axis=1, stable=True)[:, :k]
    count = jnp.sum(selection, axis=1, dtype=jnp.int32)
    return jnp.where(jnp.arange(k)[None, :] < count[:, None], order, -1)


def dsa_index_decode(
    q_idx: jax.Array,  # (B, Hi, D) one query a row
    w: jax.Array,  # (B, Hi) float32, the scales folded in
    idx_pool: jax.Array,  # (1, num_blocks, bs, D) read-only
    block_tables: jax.Array,  # (B, nb)
    hist_len: jax.Array,  # (B,) pool positions < hist_len are history
    staged_idx: jax.Array,  # (B, W, D) the window's index keys so far
    step_k: jax.Array,  # scalar: staged slot w is written iff w <= step_k
    *,
    topk: int,
) -> tuple[jax.Array, jax.Array]:
    """A decode step's selection: (B, K) int32 indices into [the row's pool
    positions ‖ the window's staged slots] — position order — and which of
    them are real, K = min(topk, S + W). Exact: `jax.lax.top_k`, a tie to
    the lower index."""
    b, nb = block_tables.shape
    bs = idx_pool.shape[2]
    s, win = nb * bs, staged_idx.shape[1]
    with jax.named_scope("dsa_index_score"):
        keys = idx_pool[0][block_tables].reshape(b, s, -1)
        scores = jnp.concatenate([
            jax.vmap(_index_block)(q_idx[:, None], w[:, None], keys)[:, 0],
            jax.vmap(_index_block)(q_idx[:, None], w[:, None],
                                   staged_idx)[:, 0]], axis=1)
        seen = jnp.concatenate([
            jnp.arange(s, dtype=jnp.int32)[None, :] < hist_len[:, None],
            jnp.broadcast_to(
                jnp.arange(win, dtype=jnp.int32)[None, :] <= step_k,
                (b, win))], axis=1)
    with jax.named_scope("dsa_select"):
        # (`jax.lax.top_k` is exact, a tie to the lower index — the chunk's
        # rule; zeros of both signs are one value there as in
        # `_ordered_bits`. The chunk's threshold with the kept positions
        # listed by a running count and a binary search read 6.2 ms a layer
        # and step on the chip where this reads 1.3: PERF.md §6, PR 55)
        top, at = jax.lax.top_k(
            jnp.where(seen, jnp.where(scores == 0.0, 0.0, scores), -jnp.inf),
            min(topk, s + win))
    return at.astype(jnp.int32), top > -jnp.inf


def dsa_decode_attention(
    q_abs: jax.Array,  # (B, H, width) one absorbed query a row
    lat_pool: jax.Array,  # (1, num_blocks, bs, width) read-only
    block_tables: jax.Array,  # (B, nb)
    staged: jax.Array,  # (B, W, width) the window's latents so far
    chosen: jax.Array,  # (B, K) `dsa_index_decode`'s indices
    real: jax.Array,  # (B, K) bool
    *,
    scale: float,
    rank: int,
) -> jax.Array:
    """Absorbed attention over the selected tokens alone: their latents are
    gathered by slot — the pool's rows, not its pages — and the window's
    own from the staging buffer. Returns (B, H, rank)."""
    nb = block_tables.shape[1]
    bs, width = lat_pool.shape[2], lat_pool.shape[3]
    s = nb * bs
    with jax.named_scope("dsa_gather"):
        pooled = real & (chosen < s)
        page = jnp.take_along_axis(
            block_tables, jnp.clip(chosen // bs, 0, nb - 1), axis=1)
        # (what is not a pool token reads the null page's first slot)
        slot = jnp.where(pooled, page * bs + chosen % bs, 0)
        got = lat_pool.reshape(-1, width)[slot]  # (B, K, width)
        from_window = jnp.take_along_axis(
            staged, jnp.clip(chosen - s, 0, staged.shape[1] - 1)[..., None],
            axis=1)
        got = jnp.where(pooled[..., None], got.astype(q_abs.dtype),
                        from_window.astype(q_abs.dtype))
    with jax.named_scope("dsa_attn"):
        sc = jnp.einsum("bhc,bkc->bhk", q_abs, got,
                        preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(real[:, None, :], sc, NEG_INF), axis=-1)
        return jnp.einsum(
            "bhk,bkr->bhr", p.astype(got.dtype), got[..., :rank],
            preferred_element_type=jnp.float32).astype(q_abs.dtype)
