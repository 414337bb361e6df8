"""A KDA layer's q, k and v from its three projections in ONE pass over
their bytes: the short causal convolution, SiLU and the q / k L2 norm
of a PACKED prefill chunk as a Pallas TPU kernel (device op
`kda_short_conv`), beside the recurrence's kernels in ops/kda_attention.py.

What it computes is `models/llama.py:_Recurrence.qkv` — the XLA form, which
stays the `xla` backend's path and the tests' oracle — term for term: per
channel c of the projections x (q | k | v, `(N, H D)` each, read as their
matmuls wrote them: nothing concatenates them) and token n with in-row index
j(n) (its distance from its row's first token on the packed axis),

    y[n, c] = x[n, c] t[K-1, c] + sum_{d=1..K-1} [j(n) >= d] x[n-d, c] t[K-1-d, c]
              + corr[row, j(n), c]                      where j(n) < K - 1

in float32, the products added in that order; `corr` is what the row's old
tail (the layer's `conv` pool; zeros for a fresh row) adds to its first
K - 1 tokens, at most rows x (K - 1) token rows a chunk, made by the caller
as a small `(rows (K - 1), C)` array. Then SiLU, for the q and k thirds the
L2 norm over each head's D channels (`+ 1e-6` under the root, q also times
D^-0.5), one cast to the model's dtype and one write of q, k, v as flat
`(N, H D)` arrays, which is what `kda_prefill_attention` takes.

THE TILE. A grid step holds the SAME `(token tile, head tile)` of the three
thirds: a block of each projection, three of the one `(K, C)` tap array,
three of the correction, three outputs. The token tile is the whole chunk
where it has at most `_MAX_TOKEN_TILE` tokens (2,048: every shape the cells
run), so the projections' bytes move once in `(2,048, 128)` slabs and a
step's ~0.35 us of overhead is paid 64 times a layer, not 4,096. Inside a
step the work goes in SUB-tiles of `_SUB` tokens through a `fori_loop`, so
the body the compiler sees is one sub-tile long:

1. the convolution of a sub-tile, into a float32 scratch tile. The token d
   before comes from the sub-tile and the 8 tokens before it (the block
   itself; for a tile's first sub-tile the last 8 tokens of the tile before,
   CARRIED in scratch — the token axis is the outer grid axis, the carry
   holds every head tile's), shifted along the sublanes; the seam rule is a
   select on j(n) >= d, j read from a scratch column the step computes from
   the rows' offsets (prefetched scalars) when it enters a token tile;
2. the tail's corrections, rows x (K - 1) of them at most: for each, where
   its packed position (a prefetched scalar) lies in this token tile, ONE
   row of the scratch tile takes one row of `corr` — no scatter into an
   (N, C) array;
3. SiLU, the norms, the cast and the store, a sub-tile at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUB = 64  # tokens of a sub-tile: its float32 tiles and shifts stay in vregs
_HALO = 8  # tokens before a sub-tile the shifts may read (one f32 tile)
_MAX_TOKEN_TILE = 2048


def token_tile(n: int, most: int = _MAX_TOKEN_TILE) -> int:
    """Tokens of a grid step's tile for a packed axis of `n` positions
    (rounded up to whole sub-tiles): the largest multiple of `_SUB` that
    divides it and is at most `most`."""
    n += (-n) % _SUB
    return max(t for t in range(_SUB, min(n, most) + 1, _SUB) if n % t == 0)


def heads_per_tile(heads: int, d: int) -> int:
    """Heads of a grid step's tile: the fewest whose channels fill whole
    128-lane tiles (1 at D = 128), else all of them (a block as wide as its
    array is always allowed)."""
    return next((h for h in range(1, heads + 1)
                 if heads % h == 0 and (h * d) % 128 == 0), heads)


def _short_conv_kernel(off_ref, at_ref, xq_ref, xk_ref, xv_ref,
                       tq_ref, tk_ref, tv_ref, cq_ref, ck_ref, cv_ref,
                       q_ref, k_ref, v_ref, y_ref, carry_ref, j_ref,
                       *, d: int):
    f32, sub = jnp.float32, _SUB
    i, c = pl.program_id(0), pl.program_id(1)
    tn, tc = q_ref.shape
    kk = tq_ref.shape[0]
    n_sub = tn // sub

    @pl.when(c == 0)
    def _in_row_index():  # j(n) of the tile's tokens, once a token tile
        def of_sub_tile(s, carry):
            at = pl.multiple_of(s * sub, sub)
            tok = i * tn + at + jax.lax.broadcasted_iota(
                jnp.int32, (sub, 1), 0)

            def row(b, start):  # the largest row offset at or before
                o = off_ref[b]
                return jnp.where(o <= tok, jnp.maximum(start, o), start)

            start = jax.lax.fori_loop(
                0, off_ref.shape[0], row, jnp.full((sub, 1), -1, jnp.int32))
            j_ref[pl.ds(at, sub), :] = jnp.where(start >= 0, tok - start, -1)
            return carry

        jax.lax.fori_loop(0, n_sub, of_sub_tile, 0)

    @pl.when(i == 0)
    def _no_tile_before():  # (its tokens would be masked: j(n) <= n < d)
        carry_ref[c] = jnp.zeros(carry_ref.shape[1:], f32)

    thirds = ((xq_ref, tq_ref, cq_ref, q_ref), (xk_ref, tk_ref, ck_ref, k_ref),
              (xv_ref, tv_ref, cv_ref, v_ref))

    def conv(s, carry):
        at = pl.multiple_of(s * sub, sub)
        j = jnp.broadcast_to(j_ref[pl.ds(at, sub), :], (sub, tc))
        for t, (x_ref, t_ref, _, _) in enumerate(thirds):
            cur = x_ref[pl.ds(at, sub), :].astype(f32)
            # the 8 tokens before: the block's own (read as the 16-token
            # tile a packed dtype stores), or the carry
            before = pl.multiple_of(jnp.maximum(at - 2 * _HALO, 0), 2 * _HALO)
            halo = jnp.where(
                s == 0, carry_ref[c, t],
                x_ref[pl.ds(before, 2 * _HALO), :].astype(f32)[_HALO:])
            ext = jnp.concatenate([halo, cur], axis=0)
            taps = t_ref[...].astype(f32)
            y = cur * taps[kk - 1:kk]
            for back in range(1, kk):
                prev = ext[_HALO - back:_HALO - back + sub]
                y = y + jnp.where(j >= back, prev, 0.0) * taps[
                    kk - 1 - back:kk - back]
            y_ref[t, pl.ds(at, sub), :] = y
        return carry

    jax.lax.fori_loop(0, n_sub, conv, 0)
    for t, (x_ref, _, _, _) in enumerate(thirds):
        carry_ref[c, t] = x_ref[tn - 2 * _HALO:, :].astype(f32)[_HALO:]

    def tail(r, carry):
        local = at_ref[r] - i * tn

        @pl.when((local >= 0) & (local < tn))
        def _():
            for t, (_, _, c_ref, _) in enumerate(thirds):
                y_ref[t, pl.ds(local, 1), :] = (
                    y_ref[t, pl.ds(local, 1), :] + c_ref[pl.ds(r, 1), :])
        return carry

    jax.lax.fori_loop(0, at_ref.shape[0], tail, 0)

    def finish(s, carry):
        at = pl.multiple_of(s * sub, sub)
        for t, (_, _, _, o_ref) in enumerate(thirds):
            y = y_ref[t, pl.ds(at, sub), :]
            y = y * jax.nn.sigmoid(y)
            if t < 2:  # q and k: the L2 norm over a head's channels
                heads = []
                for h in range(tc // d):
                    a = y[:, h * d:(h + 1) * d]
                    a = a * jax.lax.rsqrt(
                        jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
                    heads.append(a * d ** -0.5 if t == 0 else a)
                y = jnp.concatenate(heads, axis=-1)
            o_ref[pl.ds(at, sub), :] = y.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n_sub, finish, 0)


@functools.partial(
    jax.jit, static_argnames=("heads", "interpret", "max_token_tile"))
def kda_short_conv(xq, xk, xv, taps, corr, corr_at, row_off, *, heads: int,
                   interpret: bool = False,
                   max_token_tile: int = _MAX_TOKEN_TILE):
    """xq, xk, xv (N, H D) the q, k and v projections of a packed chunk, in
    the model's dtype, as their matmuls wrote them; taps (K, 3 H D) over
    q | k | v; corr (R, 3 H D) float32 and corr_at (R,) int32: what the
    rows' old tails add, a token row each, and the packed position it is
    added at (outside [0, N): nowhere); row_off (B,) the rows' offsets on
    the packed axis, row 0's first. Returns q, k, v (N, H D) in the
    projections' dtype. (`max_token_tile`: tests.)"""
    n_tokens, hd = xq.shape
    d = hd // heads
    tc = heads_per_tile(heads, d) * d
    xs = (xq, xk, xv)
    pad = (-n_tokens) % _SUB
    if pad:  # (a bucket that is no whole number of sub-tiles: tests)
        xs = tuple(jnp.pad(x, ((0, pad), (0, 0))) for x in xs)
    n = n_tokens + pad
    tn = token_tile(n, max_token_tile)
    n_ct = hd // tc
    kk, rows = taps.shape[0], corr.shape[0]

    def const(t):
        return lambda i, c, *_: (0, t * n_ct + c)

    tile = pl.BlockSpec((tn, tc), lambda i, c, *_: (i, c))
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // tn, n_ct),
        in_specs=(
            [tile] * 3
            + [pl.BlockSpec((kk, tc), const(t)) for t in range(3)]
            + [pl.BlockSpec((rows, tc), const(t)) for t in range(3)]),
        out_specs=[tile] * 3,
        scratch_shapes=[
            pltpu.VMEM((3, tn, tc), jnp.float32),  # the convolution's sums
            pltpu.VMEM((n_ct, 3, _HALO, tc), jnp.float32),  # the carry
            pltpu.VMEM((tn, 1), jnp.int32),  # j(n)
        ],
    )
    q, k, v = pl.pallas_call(
        functools.partial(_short_conv_kernel, d=d),
        grid_spec=grid,
        out_shape=[jax.ShapeDtypeStruct((n, hd), xq.dtype)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 << 20),
        interpret=interpret,
        name="kda_short_conv",
    )(row_off.astype(jnp.int32), corr_at.astype(jnp.int32), *xs,
      taps, taps, taps, corr, corr, corr)
    if pad:
        q, k, v = q[:n_tokens], k[:n_tokens], v[:n_tokens]
    return q, k, v
