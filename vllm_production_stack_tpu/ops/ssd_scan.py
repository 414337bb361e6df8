"""The state-space duality (SSD) recurrence of Mamba-2 (arXiv:2405.21060):
a state-space branch's scan over a per-row, per-head float32 state S (P
channels x N state dims), and its two Pallas TPU kernels.

One token of one head j of group g (B and C are shared by a group's heads),
with dt > 0 the step (after its softplus) and A[j] < 0 the head's rate:

    S = exp(dt A[j]) S + (dt x) (outer) B[g]     scalar decay a head, no
    y = S C[g] + D[j] x                          correction term

`ssd_step` is that, written once (the XLA forms below and the tests' oracle
loop over it). The state of every seat lives in a POOL `(slots, H, P, N)`
float32 (engine/kv_cache.py:StateSlots); each op reads a row's state at
`read[row]` and writes it at `write[row]` — the same slot, or the slot's
other bank (ops/kda_attention.py, whose design this module reuses: work
items on the packed axis, the state as the kernel's resident output block,
the pool aliased in place).

Prefill, `ssd_prefill_scan` (device op of that name): the chunkwise form
over a PACKED token axis cut into blocks of 128 tokens; a WORK ITEM is the
part of one row's segment that lies in one block (a block that holds a seam
is two items), and the grid walks (head group, item): a grid step holds
`heads_per_step` heads of ONE B / C group, so the item's C B^T is formed
once for them. Inside an item, with G the cumulative dt A from the item's
first token (<= 0, decreasing) and L[s, r] = exp(G[s] - G[r]) for r <= s:

    Y  = (L o C B^T) (dt X) + exp(G) o (C S0^T) + D X
    S1 = exp(G_last) S0 + ((dt X) o exp(G_last - G))^T B

Every exponent is formed as a difference that is <= 0: no factor exp(-G)
exists (it overflows under strong decay; ops/kda_attention.py says the
same of its gates). A token outside the item's span has dt = 0: it decays
nothing and adds nothing. The state block of an item's row is the kernel's
resident OUTPUT block: loaded (or zeroed, for a row whose first token the
chunk holds) at the row's first item, carried in VMEM over the row's items,
written once when the grid moves to another row.

Decode, `ssd_decode_step`: one recurrent step for B rows x H heads on the
VPU, S read once from `read[row]` and written once to `write[row]`, in
place, float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kda_attention import work_items

BLOCK = 128  # tokens of a prefill work item (`mamba_chunk_size`)
_HI = jax.lax.Precision.HIGHEST
# heads of one grid step, at most: a head's state block is P x N float32
# (128 KiB at 128 x 256), in and out and double-buffered each — 4 MiB of the
# 16 MiB a kernel may use on a v5e at 8 heads
_MAX_HEADS_PER_STEP = 8


def ssd_step(s, x, b, c, dt, a, d):
    """One token: s (..., H, P, N) float32; x (..., H, P); b, c (..., G, N)
    with H a multiple of G (head j reads group j // (H / G)); dt (..., H)
    the step after its softplus; a (H,) the rates (negative) and d (H,) the
    skip weights. Returns (s', y (..., H, P)) in float32."""
    f32 = jnp.float32
    x, b, c, dt = (t.astype(f32) for t in (x, b, c, dt))
    rep = s.shape[-3] // b.shape[-2]
    b, c = (jnp.repeat(t, rep, axis=-2) for t in (b, c))  # (..., H, N)
    decay = jnp.exp(dt * a.astype(f32))
    s = (s * decay[..., None, None]
         + (dt[..., None] * x)[..., :, None] * b[..., None, :])
    y = jnp.einsum("...pn,...n->...p", s, c, precision=_HI)
    return s, y + d.astype(f32)[:, None] * x


def ssd_decode_xla(pool, read, write, x, b, c, dt, a, d):
    """One step for B rows in plain XLA: x (B, H, P), b, c (B, G, N), dt
    (B, H). Returns (y (B, H, P) float32, pool')."""
    s, y = ssd_step(pool[read], x, b, c, dt, a, d)
    return y, pool.at[write].set(s)


def ssd_prefill_xla(pool, read, write, fresh, x, b, c, dt, a, d, lens):
    """A chunk in plain XLA, token by token: rows first — x (B, T, H, P),
    b, c (B, T, G, N), dt (B, T, H); `lens` (B,) real tokens a row, `fresh`
    (B,) rows that start from a zero state. Returns (y (B, T, H, P)
    float32, pool'); a row without tokens writes back what it read."""
    s0 = jnp.where(fresh[:, None, None, None], 0.0, pool[read])

    def body(s, xs):
        t, (xt, bt, ct, dtt) = xs
        s1, y = ssd_step(s, xt, bt, ct, dtt, a, d)
        live = (t < lens)[:, None, None, None]
        return jnp.where(live, s1, s), y

    t = x.shape[1]
    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, b, c, dt))
    s, y = jax.lax.scan(body, s0, (jnp.arange(t), xs))
    return jnp.moveaxis(y, 0, 1), pool.at[write].set(s)


def heads_per_step(heads: int, groups: int) -> int:
    """How many heads one grid step of either kernel holds: the largest
    divisor of a group's head count not above `_MAX_HEADS_PER_STEP` (the
    step's heads share one B and one C)."""
    per_group = heads // groups
    return max(hb for hb in range(1, min(per_group, _MAX_HEADS_PER_STEP) + 1)
               if per_group % hb == 0)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    # (bf16 operands multiply exactly into the float32 accumulator in one
    # pass; the MXU takes no higher precision for them)
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32,
        precision=_HI if a.dtype == jnp.float32 else None)


_NT = (((1,), (1,)), ((), ()))  # contract both operands' minor axes
_TN = (((0,), (0,)), ((), ()))  # ... both operands' major axes


# -- prefill kernel ----------------------------------------------------------

def _prefill_kernel(blk_ref, rd_ref, wr_ref, lo_ref, hi_ref, fl_ref,
                    x_ref, b_ref, c_ref, dtc_ref, dtr_ref, coef_ref,
                    acol_ref, d_ref, s_in_ref, y_ref, s_ref):
    del blk_ref, rd_ref, wr_ref
    f32 = jnp.float32
    m = pl.program_id(1)
    lo, hi, fl = lo_ref[m], hi_ref[m], fl_ref[m]
    hb, p, ns = s_ref.shape[1], s_ref.shape[2], s_ref.shape[3]
    q = x_ref.shape[0]

    @pl.when((fl & 1) != 0)
    def _load():  # the row's first item: its heads' states, or zeros
        s_ref[...] = jnp.where((fl & 2) != 0, 0.0, s_in_ref[...])

    @pl.when(hi > lo)
    def _item():
        tok_c = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0)
        tok_r = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1)
        live_c = (tok_c >= lo) & (tok_c < hi)
        live_r = (tok_r >= lo) & (tok_r < hi)
        r_i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
        c_i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
        lower = r_i >= c_i
        # the steps of the step's heads, a token a row (q, hb) and a token a
        # column (hb, q): 0 outside the item's span
        dtc = jnp.where(live_c, dtc_ref[0], 0.0)
        dtr = jnp.where(live_r, dtr_ref[...], 0.0)
        a_row = coef_ref[0]  # (1, hb)
        # G, the inclusive cumulative dt A from the item's first token, in
        # both layouts (sums of non-positive terms)
        gc = _dot(lower.astype(f32), dtc * a_row)  # (q, hb)
        gr = _dot(dtr * acol_ref[...], (r_i <= c_i).astype(f32))  # (hb, q)
        # (what a token outside the span holds is never trusted: a padding
        # token's B and C may be anything, NaN included, and 0 x NaN is NaN)
        zero = jnp.zeros((), b_ref.dtype)
        bm = jnp.where(live_c, b_ref[...], zero)
        cm = jnp.where(live_c, c_ref[...], zero)
        cb = jnp.where(lower, _dot(cm, bm, _NT), 0.0)  # [s, r], r <= s
        b32, c32 = bm.astype(f32), cm.astype(f32)
        for j in range(hb):
            sl = slice(j * p, (j + 1) * p)
            x = jnp.where(live_c, x_ref[:, sl].astype(f32), 0.0)
            g_c, g_r = gc[:, j:j + 1], gr[j:j + 1, :]
            # L o C B^T with the key token's step folded in
            mm = cb * jnp.exp(jnp.minimum(g_c - g_r, 0.0)) * dtr[j:j + 1, :]
            s0 = s_ref[0, j]
            y = (_dot(mm, x) + jnp.exp(g_c) * _dot(c32, s0, _NT)
                 + d_ref[0, :, sl] * x)
            g_last = g_c[q - 1:q]
            w = dtc[:, j:j + 1] * jnp.exp(g_last - g_c)
            y_ref[:, sl] = jnp.where(
                live_c, y, y_ref[:, sl].astype(f32)).astype(y_ref.dtype)
            # the whole item's decay as a ROW over the state's lanes (Mosaic
            # broadcasts along sublanes or lanes, not both: a (1, 1) cannot
            # multiply a tile): the last token's G, picked by a reduction
            # over the tokens
            decay = jnp.exp(jnp.sum(jnp.where(
                tok_c == q - 1, jnp.broadcast_to(g_c, (q, ns)), 0.0),
                axis=0, keepdims=True))
            s_ref[0, j] = decay * s0 + _dot(x * w, b32, _TN)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_prefill_scan(pool, read, write, fresh, x, b, c, dt, a, d,
                     row_off, chunk_lens, *, interpret: bool = False):
    """The packed chunk through the chunked kernel: x (N, H, P), b, c
    (N, G, Ns) in the model's dtype, dt (N, H) float32; a, d (H,);
    `row_off`, `chunk_lens`, `read`, `write`, `fresh` (B,) a row. Returns
    (y (N, H, P) float32, pool')."""
    n_tokens, heads, p = x.shape
    groups, ns = b.shape[1], b.shape[2]
    hb = heads_per_step(heads, groups)
    per_group = heads // groups
    f32 = jnp.float32
    dt = dt.astype(f32)
    pad = (-n_tokens) % BLOCK
    if pad:  # (a bucket that is no whole number of blocks: tests)
        x, b, c, dt = (
            jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
            for t in (x, b, c, dt))
    n = n_tokens + pad
    items = work_items(row_off, chunk_lens, n, BLOCK)
    row = items["row"]
    flags = (items["first"].astype(jnp.int32)
             + 2 * (items["first"] & fresh[row]).astype(jnp.int32))
    m = row.shape[0]
    hg = heads // hb

    def tok_map(h, i, blk, *_):
        return blk[i], h

    def group_map(h, i, blk, *_):
        return blk[i], h * hb // per_group

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(hg, m),
        in_specs=[
            pl.BlockSpec((BLOCK, hb * p), tok_map),
            pl.BlockSpec((BLOCK, ns), group_map),
            pl.BlockSpec((BLOCK, ns), group_map),
            pl.BlockSpec((1, BLOCK, hb), lambda h, i, blk, *_: (h, blk[i], 0)),
            pl.BlockSpec((hb, BLOCK), lambda h, i, blk, *_: (h, blk[i])),
            pl.BlockSpec((1, 1, hb), lambda h, i, *_: (h, 0, 0)),
            pl.BlockSpec((hb, 1), lambda h, i, *_: (h, 0)),
            pl.BlockSpec((1, 1, hb * p), lambda h, i, *_: (h, 0, 0)),
            pl.BlockSpec((1, hb, p, ns),
                         lambda h, i, blk, rd, *_: (rd[i], h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK, hb * p), tok_map),
            pl.BlockSpec((1, hb, p, ns),
                         lambda h, i, blk, rd, wr, *_: (wr[i], h, 0, 0)),
        ],
    )
    a, d = a.astype(f32), d.astype(f32)
    y, pool = pl.pallas_call(
        _prefill_kernel,
        grid_spec=grid,
        out_shape=[jax.ShapeDtypeStruct((n, heads * p), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={14: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_prefill_scan",
    )(items["block"], read[row].astype(jnp.int32),
      write[row].astype(jnp.int32), items["lo"], items["hi"], flags,
      x.reshape(n, heads * p), b.reshape(n, groups * ns),
      c.reshape(n, groups * ns),
      jnp.moveaxis(dt.reshape(n, hg, hb), 1, 0), dt.T,
      a.reshape(hg, 1, hb), a[:, None],
      # the skip weight a channel: a row over a head's lanes
      jnp.repeat(d, p).reshape(hg, 1, hb * p), pool)
    return y.reshape(n, heads, p)[:n_tokens], pool


# -- decode kernel -----------------------------------------------------------

def _decode_kernel(rd_ref, wr_ref, x_ref, dec_ref, b_ref, c_ref, s_in_ref,
                   o_ref, s_ref):
    del rd_ref, wr_ref
    hb = s_ref.shape[1]
    xdt, dec = x_ref[0, 0], dec_ref[0, 0]  # (P, hb), (hb, N)
    b, c = b_ref[0, 0], c_ref[0, 0]  # (1, N)
    outs = []
    for j in range(hb):
        # a channel a sublane, a state dim a lane: x as a column, B and C
        # as rows
        s = s_in_ref[0, j] * dec[j:j + 1] + xdt[:, j:j + 1] * b
        s_ref[0, j] = s
        outs.append(jnp.sum(s * c, axis=1, keepdims=True))
    o_ref[0, 0] = jnp.concatenate(outs, axis=1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_decode_step(pool, read, write, x, b, c, dt, a, d, *,
                    interpret: bool = False):
    """One step for B rows through the decode kernel: x (B, H, P), b, c
    (B, G, Ns), dt (B, H); a, d (H,). Returns (y (B, H, P) float32,
    pool')."""
    rows, heads, p = x.shape
    groups, ns = b.shape[1], b.shape[2]
    hb = heads_per_step(heads, groups)
    per_group, hg = heads // groups, heads // hb
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    # a head's column of the step's (P, hb) tile: dt x, and its decay
    xdt = jnp.swapaxes((dt[..., None] * x).reshape(rows, hg, hb, p), 2, 3)
    # (a head's decay over the state's lanes: a (1, 1) cannot multiply a
    # tile in the kernel)
    dec = jnp.broadcast_to(
        jnp.exp(dt * a.astype(f32)).reshape(rows, hg, hb, 1),
        (rows, hg, hb, ns))

    def vec(i, j, rd, wr):
        return i, j, 0, 0

    def group_vec(i, j, rd, wr):
        return i, j * hb // per_group, 0, 0

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(rows, hg),
        in_specs=[
            pl.BlockSpec((1, 1, p, hb), vec),
            pl.BlockSpec((1, 1, hb, ns), vec),
            pl.BlockSpec((1, 1, 1, ns), group_vec),
            pl.BlockSpec((1, 1, 1, ns), group_vec),
            pl.BlockSpec((1, hb, p, ns),
                         lambda i, j, rd, wr: (rd[i], j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, p, hb), vec),
            pl.BlockSpec((1, hb, p, ns),
                         lambda i, j, rd, wr: (wr[i], j, 0, 0)),
        ],
    )
    o, pool = pl.pallas_call(
        _decode_kernel,
        grid_spec=grid,
        out_shape=[jax.ShapeDtypeStruct((rows, hg, p, hb), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssd_decode_step",
    )(read.astype(jnp.int32), write.astype(jnp.int32), xdt, dec,
      b.astype(f32)[:, :, None], c.astype(f32)[:, :, None], pool)
    y = jnp.swapaxes(o, 2, 3).reshape(rows, heads, p)
    return y + d.astype(f32)[:, None] * x, pool
