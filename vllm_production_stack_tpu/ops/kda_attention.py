"""The gated delta rule with a per-channel gate (KDA, Kimi Linear,
arXiv:2510.26692): a linear-attention layer's recurrence over a per-row,
per-head float32 state S (D key x D value), and its two Pallas TPU kernels.

One token of one head, with q and k L2-normalised (q also times D^-0.5),
g <= 0 the log of the per-key-channel decay and beta in (0, 2):

    S' = Diag(exp(g)) S            decay each key channel's row
    u  = beta (v - S'^T k)         what the state mispredicts of v
    S  = S' + k u^T                the delta rule's rank-one correction
    o  = S^T q

`kda_step` is that, written once (the XLA forms below and the tests' oracle
loop over it). The state of every seat lives in a POOL `(slots, H, D, D)`
float32 (engine/kv_cache.py:StateSlots); each op reads a row's state at
`read[row]` and writes it at `write[row]` — the same slot, or the slot's
other bank, which is how a rolled-back dispatch leaves the state it started
from untouched (engine/scheduler.py).

Prefill, `kda_prefill_attention` (device op of that name): the
chunkwise-parallel form over a PACKED token axis. The axis is cut into
blocks of 64 tokens; a WORK ITEM is the part of one row's segment that
lies in one block (a block that holds a seam between two rows is two
items), and the grid walks (head group, item): a grid step holds
`heads_per_step` HEADS of one item (4 of Solar-Open2's 64; read off the
head count and the blocks' VMEM, 1 for a one-head shape) — their token
tiles one (64, heads D) slab of the flat (N, H D) arrays, their states one
(1, heads, D, D) block. One head's item is a chain of ~30 dependent stages
(16 turns on the VPU, a dozen small float32 products at the highest
precision, six MXU passes each): alone it leaves the MXUs idle behind every
product, so the step issues a stage of each of its heads in turn, and what
bounds it is the MXU's pushes and pops, no longer the chain (PERF.md §6,
PR 40). Inside an item, with G the cumulative log-gates from the item's
first token:

    A[s, r] = sum_c k[s, c] k[r, c] exp(G[s, c] - G[r, c])      (r < s)
    B[s, r] = sum_c q[s, c] k[r, c] exp(G[s, c] - G[r, c])      (r <= s)
    T  = (I + Diag(beta) A)^-1     unit lower triangular: the diagonal
                                   sub-blocks by doubling, then block merges
    U  = T Diag(beta) (V - (K * exp(G)) S0)
    O  = (Q * exp(G)) S0 + B U
    S1 = Diag(exp(G_last)) S0 + (K * exp(G_last - G))^T U

Products that share an operand go as one (both gate masks against g; A's
and B's rows of a sub-block against the columns before it; (beta K exp G)
and (Q exp G) against S0), block-diagonal left operands as strips of their
blocks, and a turn holds only the rows at or under the diagonal: the same
sums of the same float32 terms, fewer MXU pushes and VPU tiles.

exp of a NEGATED cumulative gate overflows under strong decay, so no factor
exp(-G) is ever formed: A and B are built in sub-blocks of 16 tokens. Off the
diagonal, rows of sub-block i are scaled by exp(G[s] - G[16 i - 1]) and the
columns before it by exp(G[16 i - 1] - G[r]) — both exponents <= 0; on the
diagonal the difference G[s] - G[r] is formed per pair before the exp. Every
such exponent is summed directly over the log-gates of its own range (a
prefix or a suffix inside a sub-block plus whole sub-blocks), never taken as
the difference of two block-long cumulative sums: behind one strongly
decaying token those are large and their difference has lost the small
decays after it. The
state block of an item's row is the kernel's resident OUTPUT block: loaded
(or zeroed, for a row whose first token the chunk holds) at the row's first
item, carried in VMEM over its items, written back when the grid moves to
another row — the pool is aliased in place and slots the chunk does not name
keep their bytes.

Decode, `kda_decode_attention`: one recurrent step for B rows x H heads on
the VPU, S read once from `read[row]` and written once to `write[row]`,
in place (`input_output_aliases`), float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 64  # tokens of a prefill work item
SUB = 16  # tokens of a sub-block (where gate differences are formed)
_HI = jax.lax.Precision.HIGHEST
# heads of one prefill grid step, at most. On a v5e at 64 heads x 128
# (benchmarks/sweep_kda.py, PERF.md §6, PR 40): 2.77 / 1.87 / 1.54 / 1.49 us
# a head-item at 1 / 2 / 4 / 8 — from 4 on the MXU's slots bound the step,
# and 8 doubles what every engine start pays to trace and lower the body
_MAX_HEADS_PER_STEP = 4
_BLOCKS_VMEM_BYTES = 8 << 20  # of the 16 MiB a kernel may use on a v5e


def kda_step(s, q, k, v, g, beta):
    """One token: s (..., Dk, Dv) float32; q, k (..., Dk); v (..., Dv); beta
    (...); g (..., Dk), or (...): a gate a head. Returns (s', o (..., Dv))."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    s = s * jnp.exp(g if g.ndim == q.ndim else g[..., None])[..., :, None]
    pred = jnp.einsum("...kv,...k->...v", s, k, precision=_HI)
    u = beta[..., None] * (v - pred)
    s = s + k[..., :, None] * u[..., None, :]
    return s, jnp.einsum("...kv,...k->...v", s, q, precision=_HI)


def kda_decode_xla(pool, read, write, q, k, v, g, beta):
    """One step for B rows in plain XLA: q, k, v, g (B, H, D), beta (B, H).
    Returns (o (B, H, D) float32, pool')."""
    s, o = kda_step(pool[read], q, k, v, g, beta)
    return o, pool.at[write].set(s)


def kda_prefill_xla(pool, read, write, fresh, q, k, v, g, beta, lens):
    """A chunk in plain XLA, token by token: rows first — q, k, v, g
    (B, T, H, D), beta (B, T, H), `lens` (B,) real tokens a row, `fresh`
    (B,) rows that start from a zero state. Returns (o (B, T, H, D) float32,
    pool'); a row without tokens writes back what it read."""
    s0 = jnp.where(fresh[:, None, None, None], 0.0, pool[read])

    def body(s, xs):
        t, (qt, kt, vt, gt, bt) = xs
        s1, o = kda_step(s, qt, kt, vt, gt, bt)
        live = (t < lens)[:, None, None, None]
        return jnp.where(live, s1, s), o

    t = q.shape[1]
    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    s, o = jax.lax.scan(body, s0, (jnp.arange(t), xs))
    return jnp.moveaxis(o, 0, 1), pool.at[write].set(s)


# -- prefill kernel ----------------------------------------------------------

def work_items(row_off, chunk_lens, n: int, BLOCK: int = BLOCK):
    """The work items of a packed chunk of `n` positions, from the rows'
    offsets and lengths (B,): per item its row, its block of the packed
    axis, the span [lo, hi) of the block that is the row's, and whether it
    is the row's first item. `n // BLOCK + B` items, the real ones first;
    the rest repeat the last real item with an empty span (the blocks they
    name are already resident: nothing is copied for them). `BLOCK`: the
    tokens of a block (this module's; ops/ssd_scan.py cuts its own)."""
    b = chunk_lens.shape[0]
    m = n // BLOCK + b
    end = row_off + chunk_lens
    first_blk = row_off // BLOCK
    n_items = jnp.where(
        chunk_lens > 0, (end + BLOCK - 1) // BLOCK - first_blk, 0)
    ends = jnp.cumsum(n_items)
    it = jnp.arange(m, dtype=jnp.int32)
    real = it < ends[-1]
    it_c = jnp.minimum(it, jnp.maximum(ends[-1] - 1, 0))
    row = jnp.minimum(
        jnp.sum(it_c[:, None] >= ends[None, :], axis=1), b - 1)
    j = it_c - (ends - n_items)[row]
    blk = jnp.clip(first_blk[row] + j, 0, n // BLOCK - 1)
    lo = jnp.clip(row_off[row] - blk * BLOCK, 0, BLOCK)
    hi = jnp.clip(end[row] - blk * BLOCK, 0, BLOCK)
    return {
        "row": row.astype(jnp.int32), "block": blk.astype(jnp.int32),
        "lo": jnp.where(real, lo, 0).astype(jnp.int32),
        "hi": jnp.where(real, hi, 0).astype(jnp.int32),
        "first": real & (j == 0),
    }


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(
        a, b, dims, precision=_HI, preferred_element_type=jnp.float32)


def heads_per_step(heads: int, d: int, itemsize: int = 2) -> int:
    """How many heads one grid step of the prefill kernel holds: the
    largest divisor of `heads` not above `_MAX_HEADS_PER_STEP` whose blocks
    (the token slabs of q, k, v, g and o and the state in and out, each
    double-buffered by the pipeline) fit `_BLOCKS_VMEM_BYTES`."""
    per_head = 2 * (BLOCK * d * (3 * itemsize + 2 * 4) + 2 * d * d * 4)
    return max(hb for hb in range(1, min(heads, _MAX_HEADS_PER_STEP) + 1)
               if heads % hb == 0
               and (hb == 1 or hb * per_head <= _BLOCKS_VMEM_BYTES))


def _prefill_kernel(blk_ref, rd_ref, wr_ref, lo_ref, hi_ref, fl_ref,
                    q_ref, k_ref, v_ref, g_ref, b_ref, s_in_ref,
                    o_ref, s_ref):
    del blk_ref, rd_ref, wr_ref
    f32 = jnp.float32
    hg, m = pl.program_id(0), pl.program_id(1)
    lo, hi, fl = lo_ref[m], hi_ref[m], fl_ref[m]
    hb, d = s_ref.shape[1], s_ref.shape[2]

    @pl.when((fl & 1) != 0)
    def _load():  # the row's first item: its heads' states, or zeros
        s_ref[...] = jnp.where((fl & 2) != 0, 0.0, s_in_ref[...])

    @pl.when(hi > lo)
    def _item():
        tok = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, 1), 0)
        live = (tok >= lo) & (tok < hi)
        r_i = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 0)
        c_i = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 1)
        same = r_i // SUB == c_i // SUB
        pair = r_i // (2 * SUB) == c_i // (2 * SUB)
        # both gate masks as one (2 BLOCK, BLOCK) operand: a token's
        # inclusive prefix and its strict suffix inside its own sub-block
        masks = jnp.concatenate(
            [(same & (r_i >= c_i)).astype(f32),
             (same & (r_i < c_i)).astype(f32)], axis=0)
        eye = (jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (d, d), 1))
        n_sub, half = BLOCK // SUB, SUB // 2
        nt = (((1,), (1,)), ((), ()))
        # (BLOCK // 2, BLOCK), a turn: where a row of a half faces the
        # turn's column of its own sub-block
        cols = [(c_i - r_i // half * SUB)[:BLOCK // 2] == r
                for r in range(SUB)]

        def rows_of(fn):  # (BLOCK, D) from one (SUB, D) piece a sub-block
            return jnp.concatenate(
                [fn(i, slice(i * SUB, (i + 1) * SUB))
                 for i in range(n_sub)], axis=0)

        def halves(x):  # (BLOCK, .) -> the sub-blocks' upper, lower halves
            return [jnp.concatenate(
                [x[i * SUB + h * half:i * SUB + (h + 1) * half]
                 for i in range(n_sub)], axis=0) for h in range(2)]

        def whole(x):  # ... and back
            return jnp.concatenate(
                [x[h][i * half:(i + 1) * half]
                 for i in range(n_sub) for h in range(2)], axis=0)

        # a block-diagonal (BLOCK, BLOCK) times another is the same small
        # products whether its blocks stand on the diagonal or side by side
        # as an (n, BLOCK) STRIP: the left operand of such a product goes
        # as a strip (a quarter or a half of the rows through the MXU)
        def strip(x, n):
            return sum(x[i:i + n] for i in range(0, BLOCK, n))

        def diag(x, blocks):  # ... and back; `blocks`: `same` or `pair`
            return jnp.where(blocks, jnp.concatenate(
                [x] * (BLOCK // x.shape[0]), axis=0), 0.0)

        eye_s = strip((r_i == c_i).astype(f32), SUB)

        def head(q, k, v, g, beta, s0):
            """One head's item, a STAGE (a product, a turn) a `yield`: q, k,
            v, g (BLOCK, D) and beta (BLOCK, 1) zeroed outside the item's
            span, s0 (D, D). Yields (o, s1) last."""
            # log-gates summed DIRECTLY over the range each factor decays
            # across (sums of non-positive numbers: a difference of two
            # long cumulative sums would lose the small decays behind a
            # strong one): within a token's own sub-block its inclusive
            # prefix `lp` and its strict suffix `ls`, and each sub-block's
            # total
            sums = _dot(masks, g)  # (2 BLOCK, D)
            lp, ls = sums[:BLOCK], sums[BLOCK:]
            tot = [lp[(i + 1) * SUB - 1:(i + 1) * SUB] for i in range(n_sub)]

            def span(lo_sub, hi_sub):  # total of sub-blocks [lo_sub, hi_sub)
                return sum(tot[lo_sub:hi_sub], jnp.zeros_like(tot[0]))

            # from the item's first token to each token, and from each
            # token to the item's last
            gc = rows_of(lambda i, sl: lp[sl] + span(0, i))
            g_tail = rows_of(lambda i, sl: ls[sl] + span(i + 1, n_sub))
            yield

            # the diagonal sub-blocks, a column of EACH a turn: turn r
            # holds, in every sub-block, its r-th token against the rows at
            # or under it — both halves of the sub-block's rows while r is
            # in the upper half, the lower half alone after that (what lies
            # over the diagonal is never used)
            def at_turn(x, r):  # the r-th rows, each over a half's rows
                return jnp.concatenate(
                    [jnp.broadcast_to(x[i * SUB + r:i * SUB + r + 1],
                                      (half, d)) for i in range(n_sub)],
                    axis=0)

            lp_h, k_h, q_h = halves(lp), halves(k), halves(q)
            a_h = [jnp.zeros((BLOCK // 2, BLOCK), f32)] * 2
            b_h = list(a_h)
            for r in range(SUB):
                lp_r, k_r = at_turn(lp, r), at_turn(k, r)
                for h in range(r // half, 2):
                    kr = k_r * jnp.exp(jnp.minimum(lp_h[h] - lp_r, 0.0))
                    a_h[h] = jnp.where(cols[r], jnp.sum(
                        k_h[h] * kr, axis=1, keepdims=True), a_h[h])
                    b_h[h] = jnp.where(cols[r], jnp.sum(
                        q_h[h] * kr, axis=1, keepdims=True), b_h[h])
                yield
            # columns before a sub-block: the row decays from the
            # sub-block's doorstep (its own prefix), the column to it (its
            # suffix and the sub-blocks between) — both <= 0; A's and B's
            # rows of the sub-block against those columns in ONE product
            e_s = jnp.exp(lp)
            kq = (k * e_s, q * e_s)
            off = [jnp.zeros((2 * SUB, BLOCK), f32)]
            for i in range(1, n_sub):
                sl = slice(i * SUB, (i + 1) * SUB)
                to_door = jnp.concatenate(
                    [ls[j * SUB:(j + 1) * SUB] + span(j + 1, i)
                     for j in range(i)], axis=0)
                ab = _dot(jnp.concatenate([x[sl] for x in kq], axis=0),
                          k[:i * SUB] * jnp.exp(to_door), nt)
                off.append(jnp.concatenate(
                    [ab, jnp.zeros((2 * SUB, BLOCK - i * SUB), f32)],
                    axis=1))
                yield
            off_a = jnp.concatenate([x[:SUB] for x in off], axis=0)
            off_b = jnp.concatenate([x[SUB:] for x in off], axis=0)
            a = jnp.where(r_i > c_i, jnp.where(same, whole(a_h), off_a), 0.0)
            bm = jnp.where(
                r_i >= c_i, jnp.where(same, whole(b_h), off_b), 0.0)

            # T = (I + L)^-1, L = Diag(beta) A strictly lower. The diagonal
            # sub-blocks by doubling, (I - N)(I + N^2)(I + N^4)(I + N^8)
            # with N^16 = 0, then two merges of [[A, 0], [C, B]]^-1 =
            # [[A', 0], [-B' C A', B']] (16 -> 32 -> 64). (Doubling over
            # the whole block would go through L^32: at 64 keys in 16
            # dimensions its powers grow and the result lost two digits
            # against substitution.)
            low = beta * a
            nil = strip(jnp.where(same, low, 0.0), SUB)
            t = eye_s - nil
            p = _dot(nil, diag(nil, same))
            yield
            for lvl in range(3):
                t = t + _dot(t, diag(p, same))
                yield
                if lvl < 2:
                    p = _dot(p, diag(p, same))
                    yield
            t = diag(t, same)
            t_s = strip(t, 2 * SUB)
            t_p = _dot(t_s, jnp.where(pair & ~same, low, 0.0))
            yield
            t = diag(t_s - _dot(t_p, t), pair)
            yield
            t_p = _dot(t, jnp.where(pair, 0.0, low))
            yield
            t = t - _dot(t_p, t)
            yield
            # U = T Diag(beta) (V - (K exp(G)) S0); that product with S0
            # and O's (Q exp(G)) S0 as ONE product against S0
            e_g = jnp.exp(gc)
            on_s0 = _dot(jnp.concatenate(
                [beta * (k * e_g), q * e_g], axis=0), s0)
            yield
            u = _dot(t, beta * v - on_s0[:BLOCK])
            yield
            o = on_s0[BLOCK:] + _dot(bm, u)
            yield
            # S1 = Diag(exp(G_last)) S0 + (K exp(G_last - G))^T U: each key
            # channel's decay as a COLUMN (the diagonal's row sums)
            decay = jnp.sum(
                jnp.where(eye, jnp.exp(gc[BLOCK - 1:BLOCK]), 0.0), axis=1,
                keepdims=True)
            yield o, decay * s0 + _dot(k * jnp.exp(g_tail), u,
                                       (((0,), (0,)), ((), ())))

        at = [slice(j * d, (j + 1) * d) for j in range(hb)]

        def tile(ref, sl):  # a head's rows of the item's span
            return jnp.where(live, ref[:, sl].astype(f32), 0.0)

        head_i = jax.lax.broadcasted_iota(jnp.int32, b_ref.shape, 1)
        betas = jnp.where(live, b_ref[...].astype(f32), 0.0)
        # the step's heads side by side, a stage of each in turn (`zip`
        # draws one from every generator): the chains of an item's ~30
        # dependent stages are independent between heads, and issued next to
        # each other one head's waits on the MXU are another head's work
        *_, done = zip(*(
            head(tile(q_ref, sl), tile(k_ref, sl), tile(v_ref, sl),
                 tile(g_ref, sl),
                 jnp.sum(jnp.where(head_i == hg * hb + j, betas, 0.0),
                         axis=1, keepdims=True), s_ref[0, j])
            for j, sl in enumerate(at)))
        for j, (sl, (o, s1)) in enumerate(zip(at, done)):
            o_ref[:, sl] = jnp.where(
                live, o, o_ref[:, sl].astype(f32)).astype(o_ref.dtype)
            s_ref[0, j] = s1


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_prefill_attention(pool, read, write, fresh, q, k, v, g, beta,
                          row_off, chunk_lens, *, interpret: bool = False):
    """The packed chunk through the chunked kernel: q, k, v (N, H, D) in
    the model's dtype, g (N, H, D) float32, beta (N, H) float32; `row_off`, `chunk_lens`, `read`, `write`, `fresh`
    (B,) a row. Returns (o (N, H, D) float32, pool')."""
    n_tokens, heads, d = q.shape
    hb = heads_per_step(heads, d, q.dtype.itemsize)
    pad = (-n_tokens) % BLOCK
    if pad:  # (a bucket that is no whole number of blocks: tests)
        q, k, v, g, beta = (
            jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            for x in (q, k, v, g, beta))
    n = n_tokens + pad
    items = work_items(row_off, chunk_lens, n)
    row = items["row"]
    flags = (items["first"].astype(jnp.int32)
             + 2 * (items["first"] & fresh[row]).astype(jnp.int32))
    m = row.shape[0]

    def tok_map(h, i, blk, *_):
        return blk[i], h

    def flat(x):
        return x.reshape(n, heads * d)

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(heads // hb, m),
        in_specs=[
            pl.BlockSpec((BLOCK, hb * d), tok_map),
            pl.BlockSpec((BLOCK, hb * d), tok_map),
            pl.BlockSpec((BLOCK, hb * d), tok_map),
            pl.BlockSpec((BLOCK, hb * d), tok_map),
            pl.BlockSpec((BLOCK, heads), lambda h, i, blk, *_: (blk[i], 0)),
            pl.BlockSpec((1, hb, d, d),
                         lambda h, i, blk, rd, *_: (rd[i], h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK, hb * d), tok_map),
            pl.BlockSpec((1, hb, d, d),
                         lambda h, i, blk, rd, wr, *_: (wr[i], h, 0, 0)),
        ],
    )
    o, pool = pl.pallas_call(
        _prefill_kernel,
        grid_spec=grid,
        out_shape=[jax.ShapeDtypeStruct((n, heads * d), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={11: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kda_prefill_attention",
    )(items["block"], read[row].astype(jnp.int32),
      write[row].astype(jnp.int32), items["lo"], items["hi"], flags,
      flat(q), flat(k), flat(v), flat(g), beta.astype(jnp.float32), pool)
    return o.reshape(n, heads, d)[:n_tokens], pool


# -- decode kernel -----------------------------------------------------------

def _decode_kernel(rd_ref, wr_ref, q_ref, k_ref, v_ref, g_ref, b_ref,
                   s_in_ref, o_ref, s_ref):
    del rd_ref, wr_ref
    f32 = jnp.float32
    hb, d = q_ref.shape[1], q_ref.shape[2]
    # the heads' q, k and decay as COLUMNS (a key channel a sublane, as
    # the state's rows are): one transpose of a (3 hb padded to D, D) tile
    rows = jnp.concatenate(
        [q_ref[0].astype(f32), k_ref[0].astype(f32),
         jnp.exp(g_ref[0].astype(f32))], axis=0)
    pad = (-3 * hb) % d
    if pad:
        rows = jnp.concatenate([rows, jnp.zeros((pad, d), f32)], axis=0)
    cols = rows.T  # (D, >= 3 hb)
    v = v_ref[0].astype(f32)
    beta = b_ref[0].astype(f32)  # (hb, 1)
    outs = []
    for j in range(hb):
        qc, kc = cols[:, j:j + 1], cols[:, hb + j:hb + j + 1]
        s = s_in_ref[0, j] * cols[:, 2 * hb + j:2 * hb + j + 1]
        pred = jnp.sum(s * kc, axis=0, keepdims=True)  # (1, D)
        u = beta[j:j + 1] * (v[j:j + 1] - pred)
        s = s + kc * u
        s_ref[0, j] = s
        outs.append(jnp.sum(s * qc, axis=0, keepdims=True))
    o_ref[0] = jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_decode_attention(pool, read, write, q, k, v, g, beta, *,
                         interpret: bool = False):
    """One step for B rows through the decode kernel: q, k, v, g (B, H, D),
    beta (B, H). Returns (o (B, H, D) float32, pool')."""
    b, heads, d = q.shape
    hb = 8 if heads % 8 == 0 else heads

    def vec(i, j, rd, wr):
        return i, j, 0

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, heads // hb),
        in_specs=[
            pl.BlockSpec((1, hb, d), vec), pl.BlockSpec((1, hb, d), vec),
            pl.BlockSpec((1, hb, d), vec), pl.BlockSpec((1, hb, d), vec),
            pl.BlockSpec((1, hb, 1), vec),
            pl.BlockSpec((1, hb, d, d), lambda i, j, rd, wr: (rd[i], j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, d), vec),
            pl.BlockSpec((1, hb, d, d), lambda i, j, rd, wr: (wr[i], j, 0, 0)),
        ],
    )
    o, pool = pl.pallas_call(
        _decode_kernel,
        grid_spec=grid,
        out_shape=[jax.ShapeDtypeStruct((b, heads, d), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_decode_attention",
    )(read.astype(jnp.int32), write.astype(jnp.int32), q, k, v,
      g.astype(jnp.float32), beta.astype(jnp.float32)[..., None], pool)
    return o, pool


# -- one gate a head, key width != value width (Gated DeltaNet) --------------
#
# The same recurrence with ONE log-decay a head and token (arXiv:2412.06464)
# and a state of Dk x Dv a head (96 x 192 at Olmo-Hybrid's widths). With a
# scalar gate the chunk's pair products need no per-channel mask:
#
#     A[s, r] = (k_s . k_r) exp(G_s - G_r)        (r < s)
#     B[s, r] = (q_s . k_r) exp(G_s - G_r)        (r <= s)
#
# is ONE product [K; Q] K^T under a (64, 64) decay mask, where the
# per-channel form builds it in 16-token sub-blocks. The exponent of a pair
# is summed DIRECTLY over the pair's own range, D[s, r] = sum_{r < t <= s}
# g_t, as one product of two 0/1 masks around Diag(g) — never a difference
# of two block-long cumulative sums (the module docstring says why). No
# (N, H, Dk) gate array exists anywhere: the kernels take g (N, H) as they
# take beta.
#
# THE LANES. 30 heads x 96 key channels fill no slab of whole 128-lane
# tiles (h x 96 % 128 == 0 needs h % 4 == 0; 30's divisors are 1, 2, 3, 5,
# 6, 10, 15, 30), and neither 96 nor 192 is a whole number of lanes. The
# kernels therefore take q and k with each head's keys padded with zeros to
# `whole_lanes(Dk)` (128) and v with its values padded to `whole_lanes(Dv)`
# (256): a head's tile is whole lanes, any divisor of the head count is a
# slab, and
# every product inside is tile-aligned. What is padded is the chunk's
# ACTIVATIONS on their way into the kernel (XLA writes them padded in the
# fusion that makes them; the kernel's output comes back padded and its
# consumer reads the first Dv lanes) — never the pools: a slot holds
# (H, Dk, Dv) float32 as published, the kernel pads a head's state into a
# VMEM scratch tile and stores the published part back. The padding is a
# third more MXU columns than the published geometry needs; the benchmark's
# costs count the published geometry, so it reads as lost share.

_GDN_MAX_HEADS_PER_STEP = 3


def whole_lanes(d: int) -> int:
    return d + (-d) % 128


def gdn_heads_per_step(heads: int) -> int:
    """Heads of one grid step of `gdn_prefill_attention`: the largest
    divisor of `heads` not above `_GDN_MAX_HEADS_PER_STEP`."""
    return max(hb for hb in range(1, min(heads, _GDN_MAX_HEADS_PER_STEP) + 1)
               if heads % hb == 0)


def _gdn_prefill_kernel(blk_ref, rd_ref, wr_ref, lo_ref, hi_ref, fl_ref,
                        q_ref, k_ref, v_ref, g_ref, b_ref, s_in_ref,
                        o_ref, s_ref, pad_ref):
    del blk_ref, rd_ref, wr_ref
    f32 = jnp.float32
    hg, m = pl.program_id(0), pl.program_id(1)
    lo, hi, fl = lo_ref[m], hi_ref[m], fl_ref[m]
    hb, dk, dv = s_ref.shape[1], s_ref.shape[2], s_ref.shape[3]
    dkp, dvp = pad_ref.shape[1], pad_ref.shape[2]

    @pl.when((fl & 1) != 0)
    def _load():  # the row's first item: its heads' states, or zeros
        s_ref[...] = jnp.where((fl & 2) != 0, 0.0, s_in_ref[...])

    @pl.when(hi > lo)
    def _item():
        tok = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, 1), 0)
        live = (tok >= lo) & (tok < hi)
        r_i = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 0)
        c_i = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 1)
        same = r_i // SUB == c_i // SUB
        pair = r_i // (2 * SUB) == c_i // (2 * SUB)
        # a token's inclusive prefix and its strict suffix over the item
        sums_of = jnp.concatenate(
            [(r_i >= c_i).astype(f32), (r_i < c_i).astype(f32)], axis=0)
        after = (r_i > c_i).astype(f32)
        nt = (((1,), (1,)), ((), ()))

        def strip(x, n):  # see `_prefill_kernel`
            return sum(x[i:i + n] for i in range(0, BLOCK, n))

        def diag(x, blocks):
            return jnp.where(blocks, jnp.concatenate(
                [x] * (BLOCK // x.shape[0]), axis=0), 0.0)

        eye_s = strip((r_i == c_i).astype(f32), SUB)

        def head(q, k, v, g, beta, s0):
            """One head's item, a stage a `yield`: q, k (BLOCK, dkp), v
            (BLOCK, dvp) and g, beta (BLOCK, 1), zeroed outside the item's
            span; s0 (dkp, dvp). Yields (o, s1) last."""
            # the log-gates a lane: every sum below comes out of the MXU
            # with the value in every lane, as the tiles it scales want it
            g_l = jnp.broadcast_to(g, (BLOCK, dkp))
            sums = _dot(sums_of, g_l)  # (2 BLOCK, dkp)
            gc, g_tail = sums[:BLOCK], sums[BLOCK:]
            # D[s, r] = sum of g over (r, s]: <= 0, 0 on and over the
            # diagonal
            decay = jnp.exp(_dot(sums_of[:BLOCK], g_l[:, :BLOCK] * after))
            yield
            ab = _dot(jnp.concatenate([k, q], axis=0), k, nt)
            ab = ab * jnp.concatenate([decay, decay], axis=0)
            a = jnp.where(r_i > c_i, ab[:BLOCK], 0.0)
            bm = jnp.where(r_i >= c_i, ab[BLOCK:], 0.0)
            yield
            # T = (I + Diag(beta) A)^-1 as `_prefill_kernel` inverts it
            low = beta * a
            nil = strip(jnp.where(same, low, 0.0), SUB)
            t = eye_s - nil
            p = _dot(nil, diag(nil, same))
            yield
            for lvl in range(3):
                t = t + _dot(t, diag(p, same))
                yield
                if lvl < 2:
                    p = _dot(p, diag(p, same))
                    yield
            t = diag(t, same)
            t_s = strip(t, 2 * SUB)
            t_p = _dot(t_s, jnp.where(pair & ~same, low, 0.0))
            yield
            t = diag(t_s - _dot(t_p, t), pair)
            yield
            t_p = _dot(t, jnp.where(pair, 0.0, low))
            yield
            t = t - _dot(t_p, t)
            yield
            e_g = jnp.exp(gc)
            on_s0 = _dot(jnp.concatenate(
                [beta * (k * e_g), q * e_g], axis=0), s0)
            yield
            u = _dot(t, beta * v - on_s0[:BLOCK])
            yield
            o = on_s0[BLOCK:] + _dot(bm, u)
            yield
            # S1 = exp(G_last) S0 + (K exp(G_last - G))^T U
            # (the block's decay in every lane of a row: down the
            # sublanes, then lane tile by lane tile)
            last = jnp.broadcast_to(
                jnp.exp(gc[BLOCK - 1:BLOCK, :128]), (dkp, 128))
            last = jnp.concatenate([last] * (dvp // 128), axis=1)
            yield o, last * s0 + _dot(k * jnp.exp(g_tail), u,
                                      (((0,), (0,)), ((), ())))

        def tile(ref, j, w):  # a head's rows of the item's span
            return jnp.where(
                live, ref[:, j * w:(j + 1) * w].astype(f32), 0.0)

        head_i = jax.lax.broadcasted_iota(jnp.int32, b_ref.shape, 1)
        betas = jnp.where(live, b_ref[...].astype(f32), 0.0)
        gates = jnp.where(live, g_ref[...].astype(f32), 0.0)

        def of_head(x, j):
            return jnp.sum(jnp.where(head_i == hg * hb + j, x, 0.0),
                           axis=1, keepdims=True)

        # a head's published (dk, dv) state in a zeroed (dkp, dvp) tile
        pad_ref[...] = jnp.zeros(pad_ref.shape, f32)
        for j in range(hb):
            pad_ref[j, :dk, :dv] = s_ref[0, j]
        *_, done = zip(*(
            head(tile(q_ref, j, dkp), tile(k_ref, j, dkp),
                 tile(v_ref, j, dvp), of_head(gates, j), of_head(betas, j),
                 pad_ref[j])
            for j in range(hb)))
        for j, (o, s1) in enumerate(done):
            sl = slice(j * dvp, (j + 1) * dvp)
            o_ref[:, sl] = jnp.where(
                live, o, o_ref[:, sl].astype(f32)).astype(o_ref.dtype)
            s_ref[0, j] = s1[:dk, :dv]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_prefill_attention(pool, read, write, fresh, q, k, v, g, beta,
                          row_off, chunk_lens, *, interpret: bool = False):
    """The packed chunk through the chunked kernel, ONE gate a head: q, k
    (N, H, Dk) and v (N, H, Dv) in the model's dtype, g and beta (N, H)
    float32, pool (slots, H, Dk, Dv) float32; `row_off`, `chunk_lens`,
    `read`, `write`, `fresh` (B,) a row. Returns (o (N, H, Dv) float32,
    pool')."""
    n_tokens, heads, dk = q.shape
    dv = v.shape[-1]
    dkp, dvp = whole_lanes(dk), whole_lanes(dv)
    hb = gdn_heads_per_step(heads)
    pad = (-n_tokens) % BLOCK
    n = n_tokens + pad

    def flat(x, width):  # tokens to whole blocks, a head to whole lanes
        x = jnp.pad(x, ((0, pad), (0, 0), (0, width - x.shape[-1])))
        return x.reshape(n, heads * width)

    g, beta = (jnp.pad(x.astype(jnp.float32), ((0, pad), (0, 0)))
               for x in (g, beta))
    items = work_items(row_off, chunk_lens, n)
    row = items["row"]
    flags = (items["first"].astype(jnp.int32)
             + 2 * (items["first"] & fresh[row]).astype(jnp.int32))
    m = row.shape[0]

    def tok_map(h, i, blk, *_):
        return blk[i], h

    def head_map(h, i, blk, *_):
        return blk[i], 0

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(heads // hb, m),
        in_specs=[
            pl.BlockSpec((BLOCK, hb * dkp), tok_map),
            pl.BlockSpec((BLOCK, hb * dkp), tok_map),
            pl.BlockSpec((BLOCK, hb * dvp), tok_map),
            pl.BlockSpec((BLOCK, heads), head_map),
            pl.BlockSpec((BLOCK, heads), head_map),
            pl.BlockSpec((1, hb, dk, dv),
                         lambda h, i, blk, rd, *_: (rd[i], h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK, hb * dvp), tok_map),
            pl.BlockSpec((1, hb, dk, dv),
                         lambda h, i, blk, rd, wr, *_: (wr[i], h, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((hb, dkp, dvp), jnp.float32)],
    )
    o, pool = pl.pallas_call(
        _gdn_prefill_kernel,
        grid_spec=grid,
        out_shape=[jax.ShapeDtypeStruct((n, heads * dvp), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={11: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gdn_prefill_attention",
    )(items["block"], read[row].astype(jnp.int32),
      write[row].astype(jnp.int32), items["lo"], items["hi"], flags,
      flat(q, dkp), flat(k, dkp), flat(v, dvp), g, beta, pool)
    return o.reshape(n, heads, dvp)[:n_tokens, :, :dv], pool


def _gdn_decode_kernel(rd_ref, wr_ref, q_ref, k_ref, v_ref, g_ref, b_ref,
                       s_in_ref, o_ref, s_ref):
    del rd_ref, wr_ref
    f32 = jnp.float32
    hb, dkp = q_ref.shape[1], q_ref.shape[2]
    dk = s_ref.shape[2]
    # the heads' q, k and decay as COLUMNS (a key channel a sublane, as the
    # state's rows are): one transpose of a (dkp, dkp) tile whose rows are
    # q | k | the head's decay in every lane
    rows = jnp.concatenate(
        [q_ref[0].astype(f32), k_ref[0].astype(f32),
         jnp.broadcast_to(jnp.exp(g_ref[0].astype(f32)), (hb, dkp)),
         jnp.zeros((dkp - 3 * hb, dkp), f32)], axis=0)
    cols = rows.T  # (dkp, dkp)
    v = v_ref[0].astype(f32)
    beta = b_ref[0].astype(f32)  # (hb, 1)
    outs = []
    for j in range(hb):
        qc, kc = cols[:dk, j:j + 1], cols[:dk, hb + j:hb + j + 1]
        s = s_in_ref[0, j] * cols[:dk, 2 * hb + j:2 * hb + j + 1]
        pred = jnp.sum(s * kc, axis=0, keepdims=True)  # (1, Dv)
        u = beta[j:j + 1] * (v[j:j + 1] - pred)
        s = s + kc * u
        s_ref[0, j] = s
        outs.append(jnp.sum(s * qc, axis=0, keepdims=True))
    o_ref[0] = jnp.concatenate(outs, axis=0)


def gdn_decode_heads_per_step(heads: int, dk: int) -> int:
    """Heads of one grid step of `gdn_decode_attention`: 8 where they
    divide the head count (whole sublane tiles), else all of them (a block
    as tall as its array is always allowed) — whose q, k and decay rows
    have to fit the one (lanes, lanes) tile the step transposes."""
    hb = 8 if heads % 8 == 0 else heads
    if 3 * hb > whole_lanes(dk):
        raise ValueError(
            f"gdn_decode_attention: {heads} heads of {dk} keys — 3 x {hb} "
            f"rows do not fit the {whole_lanes(dk)}-row tile a step "
            "transposes")
    return hb


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_decode_attention(pool, read, write, q, k, v, g, beta, *,
                         interpret: bool = False):
    """One step for B rows, ONE gate a head: q, k (B, H, Dk), v (B, H,
    Dv), g and beta (B, H), pool (slots, H, Dk, Dv) float32. Returns
    (o (B, H, Dv) float32, pool')."""
    b, heads, dk = q.shape
    dv = v.shape[-1]
    dkp = whole_lanes(dk)
    hb = gdn_decode_heads_per_step(heads, dk)
    q, k = (jnp.pad(x, ((0, 0), (0, 0), (0, dkp - dk))) for x in (q, k))

    def vec(i, j, rd, wr):
        return i, j, 0

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, heads // hb),
        in_specs=[
            pl.BlockSpec((1, hb, dkp), vec), pl.BlockSpec((1, hb, dkp), vec),
            pl.BlockSpec((1, hb, dv), vec), pl.BlockSpec((1, hb, 1), vec),
            pl.BlockSpec((1, hb, 1), vec),
            pl.BlockSpec((1, hb, dk, dv),
                         lambda i, j, rd, wr: (rd[i], j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, dv), vec),
            pl.BlockSpec((1, hb, dk, dv),
                         lambda i, j, rd, wr: (wr[i], j, 0, 0)),
        ],
    )
    o, pool = pl.pallas_call(
        _gdn_decode_kernel,
        grid_spec=grid,
        out_shape=[jax.ShapeDtypeStruct((b, heads, dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        interpret=interpret,
        name="gdn_decode_attention",
    )(read.astype(jnp.int32), write.astype(jnp.int32), q, k, v,
      g.astype(jnp.float32)[..., None], beta.astype(jnp.float32)[..., None],
      pool)
    return o, pool
