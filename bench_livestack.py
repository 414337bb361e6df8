"""Live-stack north-star benchmark: router + engine as REAL processes.

This is the honest version of bench_northstar: the same multi-round-QA
workload (BASELINE.md; reference benchmarks/multi-round-qa/run.sh:14-18),
but driven over HTTP through the real router and the real engine server —
request admission, tokenization, SSE streaming, and the router proxy hop
are all inside the measurement, exactly as a user would see them.

Two measurement shapes, both from the reference harness:

- closed-loop (users re-ask as soon as the previous answer lands): the
  saturation throughput of the served stack. TTFT here is queue-dominated
  by construction (Little's law at ~100% utilization), so it is NOT the
  latency story.
- open-loop offered-QPS (reference multi-round-qa.py:349-354,383-402:
  each user issues one request every num_users/qps seconds, with per-user
  backpressure): TTFT at a fixed offered load — the reference's QPS-sweep
  protocol (run.sh:76-80) and the shape the p50-TTFT bar is defined on.

Token calibration: the llama presets have no vocabulary files (zero-egress
image), so the engine serves with the byte fallback tokenizer — one ASCII
character is one token. The harness therefore builds prompts from ASCII
payloads whose CHARACTER counts equal bench_northstar's token counts
(system prompt 1000, questions 250-650, answers capped at 100 history
chars/round), making served and in-process runs like-for-like.

Wall-clock discipline: every wait in run_livestack draws from ONE
deadline; the boot reuses the persistent XLA compilation cache (seconds
per program instead of 20-40s compiles) and falls back to --warmup-scope
coarse when the cache is cold; drain polls are capped; the open wave is
skipped (and reported as skipped) if the budget is nearly spent.

One process per chip: this module never imports JAX. The engine child
holds the chip and places its own compile cache
(utils/compile_cache.py); the parent only looks at that directory.

Run standalone:  python bench_livestack.py
From bench.py:   run_livestack() — the driver-captured headline.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import socket
import string
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from vllm_production_stack_tpu.utils.compile_cache import (  # noqa: E402
    compile_cache_dir,
)

ENGINE_FLAGS = [
    "--model", "llama-1b",
    "--kv-cache-dtype", "fp8",
    "--num-blocks", "8750",
    "--max-model-len", "6144",
    "--max-num-seqs", "20",
    "--max-num-batched-tokens", "1024",
    "--prefill-buckets", "512,1024",
    "--decode-buckets", "20",
    "--decode-window", "16",
    "--warmup",
]


def warmup_scope_for_cache() -> str:
    """full when the persistent cache is warm (reload is seconds/program),
    coarse when cold (the full ladder would cost tens of minutes of
    compiles — coarse boots in minutes and backfills in background).

    "Warm" requires SERVING programs (decode-window entries), not just any
    entries — a cache populated only by other phases (e.g. the microbench)
    must not trigger the full cold ladder inside the boot budget."""
    try:
        names = os.listdir(compile_cache_dir())
    except OSError:
        return "coarse"
    n_decode = sum(1 for n in names if "decode_window" in n)
    return "full" if len(names) >= 40 and n_decode >= 8 else "coarse"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_health(url: str, timeout_s: float, proc=None) -> None:
    import urllib.request

    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if proc is not None and proc.poll() is not None:
            raise RuntimeError(
                f"process exited rc={proc.returncode} before healthy"
            )
        try:
            with urllib.request.urlopen(url + "/health", timeout=2) as r:
                if r.status == 200:
                    return
        except Exception:
            pass
        time.sleep(2.0)
    raise TimeoutError(f"{url} not healthy after {timeout_s:.0f}s")


def ascii_filler(n_chars: int, seed: int) -> str:
    """Exactly n_chars of printable ASCII => n_chars byte-tokenizer tokens."""
    rng = np.random.RandomState(seed)
    alphabet = np.frombuffer(
        (string.ascii_letters + string.digits + "     ").encode(), dtype=np.uint8
    )
    return rng.choice(alphabet, size=max(1, n_chars)).tobytes().decode()


async def _drive(
    base_url: str,
    model: str,
    users: int,
    rounds: int,
    answer_tokens: int,
    sys_tokens: int,
    ramp_gap_s: float,
    q_range: tuple[int, int],
    seed: int,
    qps: float | None = None,
) -> dict:
    """Drive one multi-round wave.

    qps=None: closed-loop — each user re-asks immediately (ramped in at
    ramp_gap_s). qps=Q: open-loop — user u's round r is SCHEDULED at
    u/Q + r*(users/Q) seconds (aggregate offered load = Q req/s,
    uniformly interleaved), with per-user backpressure exactly like the
    reference (multi-round-qa.py:315-327): a round whose previous answer
    hasn't landed by its slot launches late and is counted in
    `slipped_requests`.
    """
    import aiohttp

    sys_prompt = ascii_filler(sys_tokens, seed=seed)
    rng = np.random.RandomState(seed + 1)
    q_lens = rng.randint(q_range[0], q_range[1], size=(users, rounds))

    ttfts: list[float] = []
    latencies: list[float] = []
    gen_tokens = [0]
    errors: list[str] = []
    slipped = [0]
    final_history_tokens: list[int] = []
    gap = (users / qps) if qps else None
    t_wave0 = time.perf_counter()

    async def one_user(u: int, session: aiohttp.ClientSession) -> None:
        if gap is None:
            await asyncio.sleep(u * ramp_gap_s)
        history = sys_prompt
        for r in range(rounds):
            if gap is not None:
                sched = u / qps + r * gap
                now = time.perf_counter() - t_wave0
                if now < sched:
                    await asyncio.sleep(sched - now)
                elif now > sched + 0.5:
                    slipped[0] += 1
            history += ascii_filler(int(q_lens[u][r]), seed=seed + 7919 * u + r)
            body = {
                "model": model,
                "prompt": history,
                "max_tokens": answer_tokens,
                "temperature": 0.0,
                "ignore_eos": True,
                "stream": True,
                "stream_options": {"include_usage": True},
            }
            t0 = time.perf_counter()
            first = None
            completion = 0
            try:
                async with session.post(
                    base_url + "/v1/completions", json=body
                ) as resp:
                    if resp.status != 200:
                        errors.append(f"HTTP {resp.status}")
                        return
                    async for raw in resp.content:
                        line = raw.decode().strip()
                        if not line.startswith("data: "):
                            continue
                        payload = line[len("data: "):]
                        if payload == "[DONE]":
                            break
                        chunk = json.loads(payload)
                        if chunk.get("error"):
                            errors.append(str(chunk["error"])[:120])
                            return
                        if chunk.get("choices") and first is None:
                            ch = chunk["choices"][0]
                            if ch.get("text") is not None or ch.get(
                                "finish_reason"
                            ):
                                first = time.perf_counter()
                                ttfts.append(first - t0)
                        if chunk.get("usage"):
                            completion = chunk["usage"].get(
                                "completion_tokens", 0
                            )
            except (aiohttp.ClientError, asyncio.TimeoutError) as e:
                errors.append(f"{type(e).__name__}: {e}")
                return
            latencies.append(time.perf_counter() - t0)
            gen_tokens[0] += completion or answer_tokens
            # history grows by the ROUND's answer budget, matching the
            # in-process northstar (append the generated ids); the decoded
            # random-byte text re-encodes at a different length, so append
            # a deterministic 100-char stand-in instead
            history += ascii_filler(answer_tokens, seed=seed + 104729 * u + r)
        final_history_tokens.append(len(history))

    timeout = aiohttp.ClientTimeout(total=600)
    t_start = time.perf_counter()
    async with aiohttp.ClientSession(timeout=timeout) as session:
        await asyncio.gather(*(one_user(u, session) for u in range(users)))
    elapsed = time.perf_counter() - t_start

    ttft_arr = np.array(ttfts) if ttfts else np.array([float("nan")])
    out = {
        "requests": len(latencies),
        "errors": len(errors),
        "error_samples": errors[:5],
        "elapsed_s": round(elapsed, 3),
        "req_per_s": round(len(latencies) / elapsed, 3),
        "gen_tok_s": round(gen_tokens[0] / elapsed, 1),
        "ttft_p50_s": round(float(np.percentile(ttft_arr, 50)), 3),
        "ttft_p90_s": round(float(np.percentile(ttft_arr, 90)), 3),
        "ttft_p99_s": round(float(np.percentile(ttft_arr, 99)), 3),
        "latency_p50_s": round(
            float(np.percentile(latencies, 50)), 3
        ) if latencies else None,
        "avg_final_history_tokens": int(
            np.mean(final_history_tokens)
        ) if final_history_tokens else 0,
    }
    if qps:
        out["offered_qps"] = qps
        out["slipped_requests"] = slipped[0]
    return out


def _fetch_json(url: str) -> dict:
    import urllib.request

    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read())


def _snapshot_profile(before: dict, after: dict, elapsed_s: float) -> dict:
    programs = after.get("programs", {})
    eng_t = {k: after["engine"][k] - before["engine"][k]
             for k in after["engine"]}
    loop_t = {k: after["loop"][k] - before["loop"][k] for k in after["loop"]}
    busy = loop_t["busy_s"]
    return {
        "steps": loop_t["steps"],
        "busy_s": round(busy, 2),
        "idle_s": round(loop_t["idle_s"], 2),
        "busy_share_of_elapsed": round(
            busy / elapsed_s, 3
        ) if elapsed_s else None,
        "submit_s": round(loop_t.get("submit_s", 0.0), 2),
        "submits": loop_t["submits"],
        "sched_s": round(eng_t["sched_s"], 2),
        "post_s": round(eng_t["post_s"], 2),
        "prefill_s": round(eng_t["prefill_s"], 2),
        "prefill_n": eng_t["prefill_n"],
        "prefill_tokens": eng_t["prefill_tokens"],
        "decode_s": round(eng_t["decode_s"], 2),
        "decode_n": eng_t["decode_n"],
        "decode_tokens": eng_t["decode_tokens"],
        "compile_fallbacks": programs.get("compile_fallbacks"),
        "bg_compiles": programs.get("bg_compiles"),
        "compiled_keys": programs.get("compiled_keys"),
    }


def run_livestack(
    model: str = "llama-1b",
    users: int = 20,
    rounds: int = 6,
    answer_tokens: int = 100,
    sys_tokens: int = 1000,
    ramp_gap_s: float = 0.25,
    q_range: tuple[int, int] = (250, 650),
    seed: int = 0,
    warmup_waves: int = 1,
    open_qps: float | None = 2.0,
    budget_s: float = 1500.0,
    engine_flags: list[str] | None = None,
    keep_logs: str | None = None,
) -> dict:
    """Launch engine + router as subprocesses, drive the north-star
    workload over HTTP (closed-loop saturation + open-loop offered-QPS),
    return the summaries + engine-side decomposition.

    Every wait draws from one budget_s deadline, so a wedged component
    fails THIS section inside the driver's window instead of eating it.
    """
    deadline = time.monotonic() + budget_s

    def remaining() -> float:
        return deadline - time.monotonic()

    engine_port, router_port = _free_port(), _free_port()
    env = dict(os.environ)
    log_dir = keep_logs or "/tmp/livestack"
    os.makedirs(log_dir, exist_ok=True)
    engine_log = open(os.path.join(log_dir, "engine.log"), "w")
    router_log = open(os.path.join(log_dir, "router.log"), "w")
    flags = list(engine_flags or ENGINE_FLAGS)
    if "--warmup-scope" not in flags:
        flags += ["--warmup-scope", warmup_scope_for_cache()]
    engine = subprocess.Popen(
        [sys.executable, "-m", "vllm_production_stack_tpu.engine.server",
         "--port", str(engine_port), *flags],
        cwd=REPO, env=env, stdout=engine_log, stderr=subprocess.STDOUT,
    )
    router = None
    result: dict = {
        "model": model, "users": users, "rounds": rounds, "kv_dtype": "fp8",
        "budget_s": budget_s,
        "warmup_scope": flags[flags.index("--warmup-scope") + 1],
    }
    try:
        # boot + warmup: leave room for at least the warmup wave + the
        # closed measured wave (the headline) before the deadline
        boot_budget = max(60.0, remaining() - 420.0)
        t0 = time.monotonic()
        _wait_health(f"http://127.0.0.1:{engine_port}",
                     timeout_s=boot_budget, proc=engine)
        result["engine_boot_s"] = round(time.monotonic() - t0, 1)
        router = subprocess.Popen(
            [sys.executable, "-m", "vllm_production_stack_tpu.router.app",
             "--port", str(router_port),
             "--service-discovery", "static",
             "--static-backends", f"http://127.0.0.1:{engine_port}",
             "--static-models", model,
             "--routing-logic", "prefixaware"],
            cwd=REPO, env=env, stdout=router_log, stderr=subprocess.STDOUT,
        )
        _wait_health(f"http://127.0.0.1:{router_port}",
                     timeout_s=min(120.0, max(30.0, remaining() - 300.0)),
                     proc=router)
        url = f"http://127.0.0.1:{router_port}"

        for wv in range(warmup_waves):
            # traffic wave with DIFFERENT prompt content: program keys the
            # --warmup ladder missed are DISCOVERED here (the runner pads
            # up and queues the exact keys); the capped inter-wave drain
            # compiles them. With a warm persistent cache both the ladder
            # and the residue are reloads, so the cap is comfortable.
            asyncio.run(_drive(
                url, model, users, rounds, answer_tokens, sys_tokens,
                ramp_gap_s, q_range, seed=seed + 555_000 + 77 * wv,
            ))
            # drain the idle-gated background compiles so the measured
            # wave dispatches exact programs — but CAPPED: a hung-but-
            # listening engine must not eat the driver budget (r4 failure
            # mode: 240 x 5s polls per wave)
            drain_cap = min(240.0, max(0.0, remaining() - 300.0))
            drain_end = time.monotonic() + drain_cap
            bad_polls = 0
            while time.monotonic() < drain_end:
                try:
                    progs = _fetch_json(
                        f"http://127.0.0.1:{engine_port}/debug/timing"
                    ).get("programs", {})
                except Exception:
                    # tracing holds the GIL in bursts — tolerate a few
                    # slow polls, then stop draining rather than stall
                    bad_polls += 1
                    if bad_polls >= 6:
                        result["drain_aborted"] = True
                        break
                    time.sleep(5)
                    continue
                bad_polls = 0
                if not progs.get("bg_pending", 0):
                    break
                time.sleep(5)

        # counters are cumulative: snapshot before/after and subtract (an
        # in-place reset would race the step thread's accumulates)
        t_before = _fetch_json(f"http://127.0.0.1:{engine_port}/debug/timing")
        closed = asyncio.run(_drive(
            url, model, users, rounds, answer_tokens, sys_tokens,
            ramp_gap_s, q_range, seed=seed,
        ))
        t_after = _fetch_json(f"http://127.0.0.1:{engine_port}/debug/timing")
        closed["engine_profile"] = _snapshot_profile(
            t_before, t_after, closed["elapsed_s"],
        )
        # headline (closed-loop) fields live top-level for BENCH
        # continuity; the open-loop wave nests under open_loop
        result.update(closed)

        # open-loop offered-QPS wave (the reference's QPS-sweep shape —
        # the TTFT bar is defined here). Needs ~rounds*users/qps seconds.
        if open_qps:
            need = rounds * users / open_qps + users / open_qps + 60.0
            if remaining() > need:
                t_before = _fetch_json(
                    f"http://127.0.0.1:{engine_port}/debug/timing")
                opened = asyncio.run(_drive(
                    url, model, users, rounds, answer_tokens, sys_tokens,
                    ramp_gap_s, q_range, seed=seed + 99_000, qps=open_qps,
                ))
                t_after = _fetch_json(
                    f"http://127.0.0.1:{engine_port}/debug/timing")
                opened["engine_profile"] = _snapshot_profile(
                    t_before, t_after, opened["elapsed_s"],
                )
                result["open_loop"] = opened
            else:
                result["open_loop"] = {
                    "skipped": f"budget: {remaining():.0f}s left, "
                               f"need ~{need:.0f}s"
                }
        return result
    finally:
        for proc in (router, engine):
            if proc is not None:
                proc.send_signal(signal.SIGTERM)
        for proc in (router, engine):
            if proc is not None:
                try:
                    proc.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    proc.kill()
        engine_log.close()
        router_log.close()


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--users", type=int, default=20)
    p.add_argument("--rounds", type=int, default=6)
    p.add_argument("--no-warmup-wave", action="store_true")
    p.add_argument("--open-qps", type=float, default=2.0,
                   help="offered load for the open-loop wave (0 disables)")
    p.add_argument("--budget-s", type=float, default=1500.0)
    p.add_argument("--keep-logs", default=None)
    args = p.parse_args()
    out = run_livestack(
        users=args.users, rounds=args.rounds,
        warmup_waves=0 if args.no_warmup_wave else 1,
        open_qps=args.open_qps or None,
        budget_s=args.budget_s,
        keep_logs=args.keep_logs,
    )
    print(json.dumps({"livestack": out}))


if __name__ == "__main__":
    main()
