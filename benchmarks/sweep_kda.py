"""The KDA kernels on the chip: checked against the token-by-token
recurrence (XLA, float32 at the highest matmul precision), then timed — the
evidence behind the shares `kda_prefill_roofline` and `kda_decode_roofline`
read in the cell (PERF.md §6, PR 39).

    python benchmarks/sweep_kda.py

At Solar-Open2's linear-attention shapes (64 heads x 128 keys and values,
bf16 q, k, v, float32 state), one layer:

- check: `kda_prefill_attention` on a packed 2,048-token axis (one row; two
  rows with a seam inside a block; a continued row, a fresh row and a
  padding row) and `kda_decode_attention` at 16 rows, against
  ops/kda_attention.py's XLA forms;
- time: the prefill kernel's ms a 2,048-token chunk and layer in each of the
  three layouts, with the heads a grid step holds and the us a HEAD-ITEM
  (one head x one row's part of one 64-token block: what the kernel's time
  goes by), the decode kernel's ms a 16-row step and layer, each with the
  share of the HBM bound of what it must move (benchmarks/chip/costs_kda.py's
  counts).

The pass that makes q, k and v (`kda_short_conv`, ops/kda_short_conv.py;
PERF.md §6, PR 53), at Solar-Open2's and Ling's widths (64 and 32 heads x
128; `--conv-only` stops after it):

- check: against the XLA form it stands in for (`models/llama.py:
  _Recurrence.qkv` over `_PackedRecurrence.conv`) in the same three layouts,
  bf16 in and out: the new tails bit for bit, q, k, v within one bf16 step
  and the share of values that differ at all;
- time: both forms' ms a 2,048-token chunk and layer, each with its share of
  the bytes' bound (the projections read once, q, k, v written once).

One JSON line per measurement, kept in chiprun_out/sweep_kda.jsonl too; the
last line is {"ok": ...}.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from vllm_production_stack_tpu.models import llama  # noqa: E402
from vllm_production_stack_tpu.ops import kda_attention as kda  # noqa: E402
from vllm_production_stack_tpu.ops import kda_short_conv as ksc  # noqa: E402

H, D, N, SLOTS = 64, 128, 2048, 34
HBM_BYTES_PER_S = 819e9  # benchmarks/chip/peaks.json, TPU v5 lite
OUT = os.path.join(REPO, "chiprun_out", "sweep_kda.jsonl")
# float32 state on both sides; q, k, v are the same bf16 values; what
# differs is a 64-token block solved at once against 64 updates in turn
ATOL, RTOL = 2e-3, 2e-3
TIMES = 8  # applications of the q, k, v pass in one timed program


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(json.dumps(obj) + "\n")


def inputs(rng, n):
    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.randn(n, H, D)) * D ** -0.5
    k = unit(rng.randn(n, H, D))
    v = rng.randn(n, H, D)
    # decays as the presets draw them: rate log U(1, 16), softplus of a
    # unit-normal input around a bias for a step of 0.001-0.1
    rate = np.exp(rng.uniform(0, np.log(16), (1, H, 1)))
    step = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (1, H, D)))
    bias = step + np.log(-np.expm1(-step))
    g = -rate * np.log1p(np.exp(rng.randn(n, H, D) + bias))
    beta = 2 / (1 + np.exp(-rng.randn(n, H)))
    return (jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
            jnp.asarray(v, jnp.bfloat16), jnp.asarray(g, jnp.float32),
            jnp.asarray(beta, jnp.float32))


def timed(fn, *args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out


LAYOUTS = (
    ("one_row", (N, 0), (True, False)),
    ("seam_inside_a_block", (1000, 1048), (False, True)),
    ("continued_fresh_padding", (700, 900, 0), (False, True, False)))


def short_conv(rng, heads: int) -> bool:
    """The q, k, v pass against the XLA form at `heads` x D, then both
    timed."""
    c, taps_n, bf = 3 * heads * D, 4, jnp.bfloat16
    proj = tuple(jnp.asarray(rng.randn(N, c // 3), bf) for _ in range(3))
    taps = jnp.asarray(rng.randn(taps_n, c) * 0.5, bf)
    pool = jnp.asarray(rng.randn(SLOTS, taps_n - 1, c), bf)
    need = 2 * N * c * 2
    ok = True
    for name, lens, fresh in LAYOUTS:
        b = len(lens)
        lens_a = jnp.asarray(lens, jnp.int32)
        pack = llama.pack_index(lens_a, lens_a, N)
        st = {"read": jnp.arange(1, b + 1, dtype=jnp.int32),
              "write": jnp.arange(18, b + 18, dtype=jnp.int32),
              "row_off": pack["row_off"], "tok_row": pack["tok_row"],
              "chunk_lens": lens_a, "fresh": jnp.asarray(fresh), "tap": None}

        def form(interpret, times=1):
            def run(pool, proj):
                # `times` applications in ONE program, each on the one
                # before's q, k, v (a call of its own is ~0.5 ms of host
                # dispatch: more than the pass); flat, as the recurrence's
                # kernel takes them (a relayout to (N, H, D) on the way out
                # would be timed with the form)
                for _ in range(times):
                    recur = llama._PackedRecurrence(
                        {"conv": pool}, {**st, "interpret": interpret}, None)
                    proj = tuple(z.reshape(N, heads * D) for z in recur.qkv(
                        proj, taps, heads, D))
                    pool = recur.pools["conv"]
                return proj, pool
            return jax.jit(run)

        _, (want, want_pool) = timed(form(None), pool, proj, reps=1)
        _, (got, got_pool) = timed(form(False), pool, proj, reps=1)
        xla_s = timed(form(None, TIMES), pool, proj)[0] / TIMES
        secs = timed(form(False, TIMES), pool, proj)[0] / TIMES
        tokens = sum(lens)
        w = np.concatenate(
            [np.asarray(z[:tokens], np.float32).ravel() for z in want])
        g = np.concatenate(
            [np.asarray(z[:tokens], np.float32).ravel() for z in got])
        # one step of bfloat16 at the value's size
        steps = float(np.max(np.abs(g - w) / np.maximum(
            2.0 ** (np.floor(np.log2(np.maximum(np.abs(w), 1e-30))) - 7),
            1e-30)))
        differ = float(np.mean(g != w))
        tails = bool(jnp.all(got_pool == want_pool))
        good = tails and steps <= 1.0 and differ < 1e-2
        ok &= good
        emit({"kernel": "kda_short_conv", "case": name, "heads": heads,
              "ok": good, "tails_equal": tails, "max_bf16_steps": steps,
              "share_that_differ": differ, "ms": secs * 1e3,
              "xla_form_ms": xla_s * 1e3,
              "token_tile": ksc.token_tile(N),
              "heads_per_tile": ksc.heads_per_tile(heads, D),
              "bytes_bound_ms": need / HBM_BYTES_PER_S * 1e3,
              "share_of_bytes_bound": need / HBM_BYTES_PER_S / secs,
              "xla_form_share_of_bytes_bound":
                  need / HBM_BYTES_PER_S / xla_s})
    return ok


def main() -> int:
    dev = jax.devices()[0]
    emit({"platform": dev.platform, "kind": dev.device_kind})
    rng = np.random.RandomState(0)
    ok = True
    for heads in (H, 32):  # Solar-Open2's and Ling's KDA layers
        ok &= short_conv(rng, heads)
    if "--conv-only" in sys.argv[1:]:
        emit({"ok": bool(ok)})
        return 0 if ok else 1
    pool = jnp.asarray(rng.randn(SLOTS, H, D, D) * 0.1, jnp.float32)
    q, k, v, g, beta = inputs(rng, N)
    for name, lens, fresh in LAYOUTS:
        b = len(lens)
        lens_a = jnp.asarray(lens, jnp.int32)
        off = jnp.cumsum(lens_a) - lens_a
        read = jnp.arange(1, b + 1, dtype=jnp.int32)
        write = read + 17
        fresh_a = jnp.asarray(fresh)
        kern = jax.jit(lambda p: kda.kda_prefill_attention(
            p, read, write, fresh_a, q, k, v, g, beta, off, lens_a))
        secs, (o, new) = timed(kern, pool)
        # the recurrence, rows first
        idx = jnp.clip(off[:, None] + jnp.arange(N)[None], 0, N - 1)
        with jax.default_matmul_precision("highest"):
            want_o, want = jax.jit(lambda p: kda.kda_prefill_xla(
                p, read, write, fresh_a, q[idx], k[idx], v[idx], g[idx],
                beta[idx], lens_a))(pool)
        errs = []
        for r, n in enumerate(lens):
            if n:
                a = np.asarray(o[int(off[r]):int(off[r]) + n])
                w = np.asarray(want_o[r, :n])
                errs.append(float(np.max(np.abs(a - w) - RTOL * np.abs(w))))
        # (a row without tokens has no work item: the kernel leaves its
        # write slot as it was, the XLA form copies what it read)
        wrote = jnp.asarray([int(write[r]) for r, n in enumerate(lens) if n])
        s_err = float(jnp.max(jnp.abs(new[wrote] - want[wrote])
                              - RTOL * jnp.abs(want[wrote])))
        good = max(errs) <= ATOL and s_err <= ATOL
        ok &= good
        tokens = sum(lens)
        # a row's work items: the 64-token blocks its segment touches
        items = sum(
            -(-(int(off[r]) + n) // kda.BLOCK) - int(off[r]) // kda.BLOCK
            for r, n in enumerate(lens) if n)
        need = (tokens * (H * D * (4 * 2 + 4) + H * 4)
                + sum(1 for n in lens if n) * 2 * H * D * D * 4)
        emit({"kernel": "kda_prefill_attention", "case": name, "ok": good,
              "o_err_over_tol": max(errs), "state_err_over_tol": s_err,
              "ms": secs * 1e3, "tokens": tokens,
              "heads_per_step": kda.heads_per_step(H, D, q.dtype.itemsize),
              "head_items": H * items,
              "us_per_head_item": secs * 1e6 / (H * items),
              "hbm_bound_ms": need / HBM_BYTES_PER_S * 1e3,
              "share_of_hbm_bound": need / HBM_BYTES_PER_S / secs})
    rows = 16
    qd, kd, vd, gd, bd = inputs(rng, rows)
    read = jnp.arange(1, rows + 1, dtype=jnp.int32)
    for name, write in (("in_place", read), ("other_bank", read + 17)):
        kern = jax.jit(lambda p: kda.kda_decode_attention(
            p, read, write, qd, kd, vd, gd, bd))
        secs, (o, new) = timed(kern, pool, reps=20)
        with jax.default_matmul_precision("highest"):
            want_o, want = jax.jit(lambda p: kda.kda_decode_xla(
                p, read, write, qd, kd, vd, gd, bd))(pool)
        o_err = float(jnp.max(jnp.abs(o - want_o) - RTOL * jnp.abs(want_o)))
        s_err = float(jnp.max(jnp.abs(new - want) - RTOL * jnp.abs(want)))
        good = o_err <= ATOL and s_err <= ATOL
        ok &= good
        need = rows * (2 * H * D * D * 4 + H * D * (3 * 2 + 8) + H * 4)
        emit({"kernel": "kda_decode_attention", "case": name, "ok": good,
              "o_err_over_tol": o_err, "state_err_over_tol": s_err,
              "ms": secs * 1e3, "rows": rows,
              "hbm_bound_ms": need / HBM_BYTES_PER_S * 1e3,
              "share_of_hbm_bound": need / HBM_BYTES_PER_S / secs})
    emit({"ok": bool(ok)})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
