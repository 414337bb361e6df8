"""The selected-keys ops (ops/dsa_attention.py) on the chip: checked against
a plain top-k and softmax, then timed beside the DENSE latent kernels over
the same pool — what selection costs and buys at GLM-5.2's shapes (64 heads
over a 640-wide latent, an indexer of 32 x 128, top-2,048, 32-token pages,
bf16), one layer (PERF.md §6, PR 55; docs/55-selected-keys.md).

    python benchmarks/sweep_dsa.py [--quick] [--decode-only]

- check: `exact_topk_mask` against a sort at 4,096 keys, `dsa_index_decode`
  against the chunk's selection of the same query;
- prefill: a 2,048-token chunk of ONE row behind {0, 2k, 6k, 14k} resident
  tokens under the cell's 1,024-page table: a layer that SELECTS (scores,
  the exact top-k, attention under the mask), its three parts — and the
  tile's selection as `jax.lax.top_k` with a scatter, the decode step's
  form —, a layer that BORROWS (attention alone), and
  `mla_prefill_attention` — every visible latent, the kernel the Kanana
  cell runs — ms each, a chain of calls in one dispatch;
- decode: 16 rows x {2k, 8k, 16k} tokens held: the indexer with its top-k,
  the gather with its attention, and `mla_decode_attention` over every
  latent.

One JSON line per measurement (kept in chiprun_out/sweep_dsa.jsonl too); the
last line is {"ok": ...}.
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

from vllm_production_stack_tpu.ops import dsa_attention as dsa  # noqa: E402
from vllm_production_stack_tpu.ops.mla_attention_pallas import (  # noqa: E402
    mla_decode_attention, mla_prefill_attention,
)

HEADS, WIDTH, RANK, HI, DI, TOPK = 64, 640, 512, 32, 128, 2048
CHUNK, BLOCK, PAGES, TABLE = 2048, 32, 8192, 1024
SCALE = 256 ** -0.5
TIMES = 4  # applications chained in one dispatch
LINES: list[dict] = []
# (the dense Pallas kernels do not lower off the TPU: a CPU trial of this
# script's own paths leaves them out)
ON_TPU = jax.devices()[0].platform == "tpu"


def emit(obj: dict) -> None:
    LINES.append(obj)
    print(json.dumps(obj), flush=True)


def timed(fn, *args, reps: int = 3) -> float:
    """ms an application: `fn` chains TIMES of them in one dispatch."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / reps / TIMES


def chain(op):
    """TIMES applications of `op(q, ...)`, each fed the one before (so XLA
    cannot drop or merge them)."""
    def run(q, *rest):
        def body(_, q):
            out = op(q, *rest)
            return q + (jnp.sum(out) * 0).astype(q.dtype)
        return jax.lax.fori_loop(0, TIMES, body, q)
    return jax.jit(run)


def pools(rng):
    lat = jnp.asarray(
        rng.randn(1, PAGES, BLOCK, WIDTH).astype(np.float32) * 0.3,
        jnp.bfloat16)
    keys = jnp.asarray(
        rng.randn(1, PAGES, BLOCK, DI).astype(np.float32), jnp.bfloat16)
    return lat, keys


def check(rng) -> bool:
    scores = jnp.asarray(rng.randn(64, 4096).astype(np.float32))
    scores = scores.at[:, 100:400].set(0.25)  # ties around the k-th
    seen = jnp.arange(4096)[None, :] <= jnp.arange(64)[:, None] * 64
    got = np.asarray(jax.jit(dsa.exact_topk_mask, static_argnums=2)(
        scores, seen, TOPK))
    s, v = np.asarray(scores), np.asarray(seen)
    want = np.zeros_like(got)
    for q in range(64):
        at = np.flatnonzero(v[q])
        want[q, at[np.argsort(-s[q, at], kind="stable")][:TOPK]] = True
    ok = bool((got == want).all())
    emit({"check": "exact_topk_mask", "ok": ok,
          "kept": int(got.sum()), "want": int(want.sum())})
    return ok


def prefill(rng, lat, keys, resident: int) -> None:
    ctx = resident + CHUNK
    tables = jnp.asarray(
        rng.permutation(np.arange(1, PAGES))[:TABLE][None], jnp.int32)
    q = jnp.asarray(rng.randn(CHUNK, HEADS, WIDTH).astype(np.float32) * 0.3,
                    jnp.bfloat16)
    qi = jnp.asarray(rng.randn(CHUNK, HI, DI).astype(np.float32), jnp.bfloat16)
    wi = jnp.asarray(rng.rand(CHUNK, HI).astype(np.float32))
    lens, start, off = (jnp.asarray([ctx], jnp.int32),
                        jnp.asarray([resident], jnp.int32),
                        jnp.asarray([0], jnp.int32))

    def selects(q):
        return dsa.dsa_prefill_attention(
            q, lat, tables, lens, start, off,
            index={"q": qi, "w": wi, "pool": keys}, selection=None,
            scale=SCALE, rank=RANK, topk=TOPK)

    _, sel = jax.jit(selects)(q)

    def borrows(q):
        return dsa.dsa_prefill_attention(
            q, lat, tables, lens, start, off, index=None, selection=sel,
            scale=SCALE, rank=RANK, topk=TOPK)[0]

    def dense(q):
        return mla_prefill_attention(
            q[None], lat, tables, lens, start, scale=SCALE, rank=RANK)

    row_keys = keys[0][tables[0]].reshape(TABLE * BLOCK, DI)
    tile = dsa.Q_TILE
    pos = resident + jnp.arange(tile)
    seen = jnp.arange(TABLE * BLOCK)[None, :] <= pos[:, None]

    def scores_only(qt):
        return dsa.index_scores(qt, wi[:tile], row_keys, resident + tile)

    sc = jax.jit(scores_only)(qi[:tile])

    def select_only(s):
        return dsa.exact_topk_mask(s, seen, TOPK).astype(jnp.float32)

    def top_k_only(s):
        # the decode step's form on a chunk's tile: a sort of every visible
        # score and the kept positions scattered into the mask
        top, at = jax.lax.top_k(jnp.where(
            seen, jnp.where(s == 0.0, 0.0, s), -jnp.inf), TOPK)
        return jnp.zeros(s.shape, bool).at[
            jnp.arange(tile)[:, None], at].set(top > -jnp.inf).astype(
                jnp.float32)

    same = bool(jnp.all(jax.jit(select_only)(sc) == jax.jit(top_k_only)(sc)))
    line = {"prefill": True, "resident": resident, "chunk": CHUNK,
            "selects_ms": timed(chain(lambda q: selects(q)[0]), q),
            "borrows_ms": timed(chain(borrows), q),
            "dense_kernel_ms": timed(chain(dense), q) if ON_TPU else None,
            # one tile of 512 queries at the chunk's FIRST position
            "tile_scores_ms": timed(chain(scores_only), qi[:tile]),
            "tile_select_ms": timed(chain(select_only), sc),
            "tile_top_k_ms": timed(chain(top_k_only), sc),
            "tile_top_k_same": same,
            "selected_share": float(
                jnp.sum(sel[:CHUNK]) / (CHUNK * (resident + (CHUNK + 1) / 2)))}
    pairs = CHUNK * TOPK if resident >= TOPK else float(jnp.sum(sel[:CHUNK]))
    line["borrows_share_of_peak"] = (
        pairs * HEADS * 2 * (256 + 256) / 197e12 / (line["borrows_ms"] * 1e-3))
    emit(line)


def decode(rng, lat, keys, held: int) -> None:
    rows, win = 16, 8
    tables = jnp.asarray(np.stack([
        rng.permutation(np.arange(1, PAGES))[:TABLE] for _ in range(rows)]),
        jnp.int32)
    hist = jnp.full((rows,), held, jnp.int32)
    q = jnp.asarray(rng.randn(rows, HEADS, WIDTH).astype(np.float32) * 0.3,
                    jnp.bfloat16)
    qi = jnp.asarray(rng.randn(rows, HI, DI).astype(np.float32), jnp.bfloat16)
    wi = jnp.asarray(rng.rand(rows, HI).astype(np.float32))
    staged = jnp.zeros((rows, win, WIDTH), jnp.bfloat16)
    staged_idx = jnp.zeros((rows, win, DI), jnp.bfloat16)
    k = jnp.int32(3)

    def index(qi):
        at, real = dsa.dsa_index_decode(
            qi, wi, keys, tables, hist, staged_idx, k, topk=TOPK)
        return at.astype(jnp.float32) * real

    chosen = jax.jit(lambda qi: dsa.dsa_index_decode(
        qi, wi, keys, tables, hist, staged_idx, k, topk=TOPK))(qi)

    def attend(q):
        return dsa.dsa_decode_attention(
            q, lat, tables, staged, *chosen, scale=SCALE, rank=RANK)

    def dense(q):
        return mla_decode_attention(
            q, lat, tables, hist, staged, k, scale=SCALE, rank=RANK)

    line = {"decode": True, "rows": rows, "held": held,
            "index_ms": timed(chain(index), qi),
            "attend_ms": timed(chain(attend), q),
            "dense_kernel_ms": timed(chain(dense), q) if ON_TPU else None}
    line["index_share_of_hbm"] = (
        rows * held * 256 / 819e9 / (line["index_ms"] * 1e-3))
    line["attend_share_of_hbm"] = (
        rows * min(held, TOPK) * 1152 / 819e9 / (line["attend_ms"] * 1e-3))
    if ON_TPU:
        line["dense_share_of_hbm"] = (
            rows * held * 1152 / 819e9 / (line["dense_kernel_ms"] * 1e-3))
    emit(line)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--decode-only", action="store_true")
    args = ap.parse_args()
    dev = jax.devices()[0]
    emit({"platform": dev.platform, "kind": dev.device_kind})
    rng = np.random.RandomState(0)
    ok = check(rng)
    lat, keys = pools(rng)
    for resident in (() if args.decode_only else (2048,) if args.quick
                     else (0, 2048, 6144, 14336)):
        prefill(rng, lat, keys, resident)
    for held in ((8192,) if args.quick else (2048, 8192, 16384)):
        decode(rng, lat, keys, held)
    emit({"ok": ok and dev.platform == "tpu"})
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/sweep_dsa.jsonl", "w") as f:
        f.writelines(json.dumps(line) + "\n" for line in LINES)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
