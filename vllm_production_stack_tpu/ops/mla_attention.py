"""Multi-head latent attention (MLA) over a paged pool of latents — XLA form.

An MLA layer (DeepSeek-V2/V3, Kanana-2) caches per token ONE latent: the
normed kv latent `c_kv` (kv_lora_rank values) beside the roped key
`k_rope` (qk_rope_head_dim values) that every head shares. In the absorbed
form the up-projections of keys and values move onto the query and the
output (models/llama.py:_mla_absorb), and attention itself is multi-query
attention: every query head against ONE key of `latent_dim` values a token
whose first `rank` columns are also the value.

The pool of one layer is `(1, num_blocks, block_size, width)`: one plane
(there is no second copy of the latent as "V"), `width` the latent padded
with zeros to whole lane tiles (ModelConfig.latent_width). Blocks, slots and
the null page 0 are those of ops/attention.py, and attention goes through
its `masked_attention` / `attention_with_hist` with one kv head: this module
is the CPU and multi-device fallback and the test oracle of the Pallas
kernels (ops/mla_attention_pallas.py), which serve on one chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import attention_with_hist, masked_attention


def write_latent_pages(
    kv: jax.Array,  # (1, num_blocks, bs, width)
    lat: jax.Array,  # (N, width)
    slot_mapping: jax.Array,  # (N,) flat slot per token (padding -> block 0)
) -> jax.Array:
    """Scatter new latents into the pool, one row a token."""
    flat = kv.reshape(-1, kv.shape[-1])
    return flat.at[slot_mapping].set(lat.astype(kv.dtype)).reshape(kv.shape)


def write_latent_pages_blockwise(
    kv: jax.Array,  # (1, num_blocks, bs, width)
    lat: jax.Array,  # (B, T, width), or packed (N, width) with `row_off`
    write_block_ids: jax.Array,  # (B, NBW)
    start_off: jax.Array,  # (B,)
    chunk_lens: jax.Array,  # (B,)
    row_off: jax.Array | None = None,  # (B,) rows' offsets on a packed axis
) -> jax.Array:
    """The chunk's latents at page granularity: the read-modify-write of
    ops/attention.py:write_kv_pages_blockwise on the pool's one plane."""
    b, nbw = write_block_ids.shape
    bs, width = kv.shape[2], kv.shape[3]
    tok = jnp.arange(nbw * bs, dtype=jnp.int32)[None, :] - start_off[:, None]
    mask = (tok >= 0) & (tok < chunk_lens[:, None])  # (B, S)
    if row_off is None:
        at = (jnp.arange(b)[:, None], jnp.clip(tok, 0, lat.shape[1] - 1))
    else:
        at = (jnp.clip(row_off[:, None] + tok, 0, lat.shape[0] - 1),)
    new = lat[at].reshape(b, nbw, bs, width).astype(kv.dtype)
    ids = write_block_ids.reshape(-1)
    old = kv[0, ids].reshape(b, nbw, bs, width)
    merged = jnp.where(mask.reshape(b, nbw, bs, 1), new, old)
    return kv.at[0, ids].set(merged.reshape(b * nbw, bs, width))


def gather_latent_pages(kv: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Each row's pages as contiguous latents (B, S_ctx, 1, width): the one
    kv head `masked_attention` takes."""
    b, nb = block_tables.shape
    return kv[0][block_tables].reshape(b, nb * kv.shape[2], 1, kv.shape[3])


def latent_attention_xla(
    q_abs: jax.Array,  # (B, T, heads, width) absorbed queries, zero padded
    kv: jax.Array,  # (1, num_blocks, bs, width)
    block_tables: jax.Array,  # (B, max_blocks)
    mask: jax.Array,  # (B, T, S) from causal_page_mask
    *,
    scale: float,
    rank: int,
) -> jax.Array:
    """Causal absorbed attention of a chunk against the paged latents (the
    chunk's own are written first). Returns (B, T, heads, rank)."""
    lat = gather_latent_pages(kv, block_tables)
    return masked_attention(q_abs, lat, lat, mask, scale=scale)[..., :rank]


def latent_attention_with_staged(
    q_abs: jax.Array,  # (B, 1, heads, width)
    kv: jax.Array,  # (1, num_blocks, bs, width), read-only
    block_tables: jax.Array,  # (B, max_blocks)
    hist_mask: jax.Array,  # (B, S)
    staged: jax.Array,  # (W, B, width) this window's latents so far
    staged_mask: jax.Array,  # (W,)
    *,
    scale: float,
    rank: int,
) -> jax.Array:
    """Decode-window attention: pooled latents + the window's staged ones.
    Returns (B, 1, heads, rank)."""
    lat = gather_latent_pages(kv, block_tables)
    st = staged[:, :, None]  # (W, B, 1, width)
    return attention_with_hist(
        q_abs, lat, lat, hist_mask, st, st, staged_mask, scale=scale,
    )[..., :rank]
