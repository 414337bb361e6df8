"""Latent-attention (MLA) kernels on the chip: checked against the XLA form,
then timed — the evidence behind the ONE prefill form the program keeps
(PERF.md §6, PR 31), the decode kernel's share of its bound and what a block
visit and a tile of the prefill kernel cost (docs/22, PR 51).

    python benchmarks/sweep_mla.py [--quick] [--prefill-only]

At Kanana-2-30B-A3B's head shapes (32 heads, latent 512 + 64 stored 640
wide, 32-token pages, bf16), one layer:

- check: `mla_decode_attention` and `mla_prefill_attention` against
  ops/mla_attention.py on a bf16 and an fp8 pool (chip_smoke's kernel
  tolerance);
- decode: the kernel at 16 rows x {2k, 7k, 16k} tokens held, ms a layer and
  the share of the HBM bound of the latents read;
- prefill: the cell's programs — a 2,048-token chunk on ONE row and on TWO
  packed rows (1,024 tokens each in the row view) behind {0, 2k, 6k, 14k}
  resident tokens under the cell's 1,024-page table: the kernel's ms (a
  chain of calls in one dispatch: the op alone, as a trace reads it), one
  call's wall ms (the wrapper's XLA copies and the host's dispatch with
  it), the counted pairs' share of 197 TFLOP/s, the block visits and tiles
  walked, and after the rows their least-squares fit "us a visit + us a
  live tile + us a dead tile". Without --prefill-only the one-row rows also
  time the absorbed XLA form (the fallback) and the EXPANDED form in XLA
  (not kept; written out here only to be measured): gather the row's
  latents, W_kv_b on them once a chunk, then blocked flash attention over
  per-head keys of 192 and values of 128.

To compare two trees, copy this file over the other tree's and run both in
one chip call. One JSON line per measurement (kept in
chiprun_out/sweep_mla.jsonl too); the last line is {"ok": ...}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from vllm_production_stack_tpu.ops.attention import (  # noqa: E402
    causal_page_mask, masked_attention,
)
from vllm_production_stack_tpu.ops.mla_attention import (  # noqa: E402
    gather_latent_pages, latent_attention_with_staged, latent_attention_xla,
)
from vllm_production_stack_tpu.ops import (  # noqa: E402
    mla_attention_pallas as mk,
)
from vllm_production_stack_tpu.ops.mla_attention_pallas import (  # noqa: E402
    mla_decode_attention, mla_prefill_attention,
)

NH, RANK, ROPE, WIDTH, NOPE, VD = 32, 512, 64, 640, 128, 128
BS, POOL_BLOCKS = 32, 4096
TABLE_PAGES = 1024  # the cell's one table width (--width-floor-blocks)
SCALE = (NOPE + ROPE) ** -0.5
HBM_BYTES_PER_S = 819e9  # benchmarks/chip/peaks.json, TPU v5 lite
BF16_FLOPS_PER_S = 197e12
KERNEL_ATOL, KERNEL_RTOL = 1e-2, 2.0 ** -6  # chip_smoke.py's, its reason


OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "chiprun_out", "sweep_mla.jsonl")


def emit(obj: dict) -> None:
    line = json.dumps(obj)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def best_ms(fn, *args, iters: int = 5) -> float:
    fn(*args).block_until_ready()
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def looped(fn, n: int):
    """n dependent iterations in one dispatch (sub-millisecond ops)."""
    @jax.jit
    def run(q, *a):
        return jax.lax.fori_loop(
            0, n, lambda _, qq: qq + 0.01 * jnp.pad(
                fn(qq, *a), ((0, 0),) * (qq.ndim - 1) + ((0, WIDTH - RANK),)
            ).astype(qq.dtype), q)
    return run


def chained(n: int):
    """n calls of the prefill kernel in one dispatch, each waiting for the
    one before through `ctx` (which it leaves as it was)."""
    @jax.jit
    def run(q, pool, tb, ctx, start):
        def body(_, c):
            out = mla_prefill_attention(q, pool, tb, c, start, scale=SCALE,
                                        rank=RANK)
            return c + jnp.isnan(out[:, 0, 0, 0]).astype(jnp.int32)
        return jax.lax.fori_loop(0, n, body, ctx)
    return run


def walked(start, ctx, t: int) -> tuple[int, int, int]:
    """(block visits, live tiles, dead tiles) of one call, by the kernel's
    own tile rule."""
    tt = mk._prefill_tile(t, mk._pages_per_block(TABLE_PAGES, BS), BS, NH,
                          WIDTH, RANK, 2, 2)
    first = np.asarray(start)[:, None] + np.arange(0, t, tt)[None]
    ctx = np.asarray(ctx)[:, None]
    live = first < ctx
    visits = np.where(live, -(-np.minimum(ctx, first + tt)
                              // mk.BLOCK_TOKENS), 0)
    return int(visits.sum()), int(live.sum()), int((~live).sum())


def expanded_prefill(q, pool, tables, w_kvb, mask):
    """The expanded form for one row: q (1, T, heads, 192) un-absorbed."""
    lat = gather_latent_pages(pool, tables)[:, :, 0]  # (1, S, width)
    kv = (lat[..., :RANK] @ w_kvb).reshape(1, -1, NH, NOPE + VD)
    k_rope = jnp.broadcast_to(
        lat[:, :, None, RANK:RANK + ROPE], (1, lat.shape[1], NH, ROPE))
    keys = jnp.concatenate([kv[..., :NOPE], k_rope], axis=-1)
    values = jnp.pad(kv[..., NOPE:], ((0, 0),) * 3 + ((0, ROPE),))
    return masked_attention(q, keys, values, mask, scale=SCALE)[..., :VD]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--prefill-only", action="store_true",
                    help="the checks and the prefill kernel's rows only")
    args = ap.parse_args()
    dev = jax.devices()[0]
    emit({"platform": dev.platform, "kind": dev.device_kind})
    rng = np.random.RandomState(0)

    def arr(*shape, gain=1.0, dtype=jnp.bfloat16):
        return jnp.asarray(gain * rng.randn(*shape), dtype)

    def latents(*shape):
        return arr(*shape, WIDTH).at[..., RANK + ROPE:].set(0)

    def diff(got, want) -> dict:
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        err = np.abs(got - want)
        return {"max_abs_diff": float(err.max()),
                "ref_abs_max": float(np.abs(want).max()),
                "ok": bool(np.isfinite(got).all() and np.all(
                    err <= KERNEL_ATOL + KERNEL_RTOL * np.abs(want)))}

    ok = True
    pool = latents(1, POOL_BLOCKS, BS)
    interpret = dev.platform != "tpu"
    # -- check -----------------------------------------------------------
    nb, w, b = 128, 8, 8
    for name, p in (("bf16", pool), ("fp8", pool.astype(jnp.float8_e4m3fn))):
        tb = jnp.asarray(rng.randint(1, POOL_BLOCKS, (b, nb)), jnp.int32)
        hist = jnp.asarray([0, 5, 256, 257, *rng.randint(1, nb * BS, b - 4)],
                           jnp.int32)
        q = latents(b, NH) * 3.0
        staged = latents(b, w)
        got = mla_decode_attention(q, p, tb, hist, staged, jnp.int32(3),
                                   scale=SCALE, rank=RANK,
                                   interpret=interpret)
        want = latent_attention_with_staged(
            q[:, None], p, tb, jnp.arange(nb * BS)[None] < hist[:, None],
            jnp.moveaxis(staged, 0, 1), jnp.arange(w) <= 3, scale=SCALE,
            rank=RANK)[:, 0]
        r = diff(got, want)
        ok &= r["ok"]
        emit({"check": f"decode_{name}", **r})
        t = 256 if args.quick else 512
        start = jnp.asarray([1024, 0, 1000, 0], jnp.int32)
        ctx = jnp.asarray([1024 + t, t, 1300, 0], jnp.int32)
        pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
        qa = latents(4, t, NH) * 3.0
        got = mla_prefill_attention(qa, p, tb[:4], ctx, start, scale=SCALE,
                                    rank=RANK, interpret=interpret)
        want = latent_attention_xla(
            qa, p, tb[:4], causal_page_mask(pos, ctx, nb * BS), scale=SCALE,
            rank=RANK)
        real = np.asarray(pos < ctx[:, None])
        r = diff(np.asarray(got, np.float32)[real],
                 np.asarray(want, np.float32)[real])
        r["ok"] = r["ok"] and bool(np.isfinite(np.asarray(
            got, np.float32)).all())
        ok &= r["ok"]
        emit({"check": f"prefill_{name}", **r})
    if interpret:
        emit({"ok": bool(ok), "note": "not a TPU: nothing timed"})
        return 0 if ok else 1

    # -- decode ----------------------------------------------------------
    rows, w = 16, 8
    for ctx_len in (() if args.prefill_only else (2048,) if args.quick
                    else (2048, 7168, 16384)):
        nb = ctx_len // BS + 2
        tb = jnp.asarray(rng.randint(1, POOL_BLOCKS, (rows, nb)), jnp.int32)
        hist = jnp.full((rows,), ctx_len, jnp.int32)
        q, staged = latents(rows, NH), latents(rows, w)
        n = 32
        ms = best_ms(looped(lambda qq, *a: mla_decode_attention(
            qq, *a, scale=SCALE, rank=RANK), n),
            q, pool, tb, hist, staged, jnp.int32(3)) / n
        need = rows * ctx_len * (RANK + ROPE) * 2
        emit({"decode": {"rows": rows, "ctx": ctx_len}, "kernel_ms": ms,
              "bound_ms": need / HBM_BYTES_PER_S * 1e3,
              "roofline_pct": 100 * need / HBM_BYTES_PER_S / (ms * 1e-3)})

    # -- prefill: the cell's programs, a 2,048-token chunk on one row and
    # on two packed rows, behind the resident tokens ------------------------
    t, n = 2048, 8
    w_kvb = arr(RANK, NH * (NOPE + VD), gain=RANK ** -0.5)
    fit_x, fit_y = [], []
    for rows in (1, 2):
        for resident in ((0, 6144) if args.quick else (0, 2048, 6144, 14336)):
            tb = jnp.asarray(np.stack([
                rng.permutation(POOL_BLOCKS - 1)[:TABLE_PAGES] + 1
                for _ in range(rows)]), jnp.int32)
            start = jnp.full((rows,), resident, jnp.int32)
            qa = latents(rows, t, NH)
            n_row = t // rows  # a query at position p sees p + 1 keys
            ctx = start + n_row
            pairs = rows * (n_row * resident + n_row * (n_row + 1) // 2)
            visits, live, dead = walked(start, ctx, t)
            row = {"prefill": {"rows": rows, "tokens": t,
                               "resident": resident},
                   "visits": visits, "tiles": live, "dead_tiles": dead}
            row["kernel_ms"] = best_ms(
                chained(n), qa, pool, tb, ctx, start) / n
            row["call_ms"] = best_ms(jax.jit(lambda q, p, tb, c, s: (
                mla_prefill_attention(q, p, tb, c, s, scale=SCALE, rank=RANK)
            )), qa, pool, tb, ctx, start)
            row["kernel_mxu_pct"] = (
                100 * 2 * NH * (RANK + ROPE + RANK) * pairs / BF16_FLOPS_PER_S
                / (row["kernel_ms"] * 1e-3))
            fit_x.append((visits, live, dead))
            fit_y.append(row["kernel_ms"] * 1e3)
            if rows == 1 and not args.prefill_only:
                pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
                mask = causal_page_mask(pos, ctx, TABLE_PAGES * BS)
                row["absorbed_xla_ms"] = best_ms(jax.jit(lambda q, p, tb, m: (
                    latent_attention_xla(q, p, tb, m, scale=SCALE, rank=RANK)
                )), qa, pool, tb, mask, iters=3)
                row["expanded_xla_ms"] = best_ms(
                    jax.jit(expanded_prefill), arr(1, t, NH, NOPE + ROPE),
                    pool, tb, w_kvb, mask, iters=3)
            emit(row)
    us = np.linalg.lstsq(np.asarray(fit_x, float), np.asarray(fit_y),
                         rcond=None)[0]
    emit({"prefill_fit": dict(zip(
        ("us_per_visit", "us_per_tile", "us_per_dead_tile"),
        (round(float(u), 2) for u in us)))})
    emit({"ok": bool(ok)})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
