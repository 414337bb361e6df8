"""Headline benchmark: the SERVED stack, measured end to end.

VERDICT r3 #1: the driver-captured number must BE the live-stack run —
router + engine as real OS processes, driven over HTTP/SSE with the
north-star multi-round-QA workload (BASELINE.md; reference
benchmarks/multi-round-qa/run.sh). VERDICT r4 #1: the bench must FINISH
inside the driver budget and must not lose completed sections to a late
timeout. Hence the structure here:

- every phase runs as a SUBPROCESS with its own wall-clock cap (a chip
  belongs to one process at a time, so the orchestrator itself never
  touches JAX — a hung phase dies alone and the chip frees for the
  next);
- every phase's JSON is printed AND FLUSHED the moment it completes, so
  a driver timeout preserves everything already measured (the driver
  keeps the output tail);
- phases are ordered cheapest-first; each checks the remaining global
  budget (BENCH_BUDGET_S, default 3300 s) before starting and reports
  itself as skipped rather than overrunning;
- engine boots reuse the persistent XLA compilation cache
  (utils/compile_cache.py: JAX_COMPILATION_CACHE_DIR, else a fixed
  directory in the checkout), falling back to --warmup-scope coarse when
  cold.

Phases, in order:

1. microbench: offline batch generation throughput (256 x 128+128) — the
   raw chip number tracked since round 1 (vs the 500 tok/s per-engine
   rate of the reference's router perf rig,
   src/tests/perftest/fake-openai-server.py)
2. livestack: THE HEADLINE — real router + engine processes over
   HTTP/SSE; closed-loop saturation throughput plus an open-loop
   offered-QPS wave (the reference's run.sh QPS-sweep shape, where the
   p50-TTFT bar is defined)
3. northstar: the same workload in-process (no HTTP) — the engine's
   ceiling, for attribution of serving overhead
4. int8_8b: Llama-3-8B with int8 weight quantization on ONE 16 GiB v5e
   chip (the reference's headline model, model.yaml:1-28) — req/s, TTFT
   percentiles, HBM accounting

CPU-only pre-preflight phases (routing, robustness, fairness, tracing,
saturation, kvflow, hydration) need no chip and run BEFORE the chip
preflight.

The final line is the ONE driver-parsed JSON: headline = served
closed-loop req/s vs the >=2.0 req/s bar, with every phase attached.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SERVED_BASELINE_REQ_S = 2.0  # VERDICT r3 "done" bar for the served stack
TOTAL_BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "3300"))

_t_start = time.monotonic()


def _remaining() -> float:
    return TOTAL_BUDGET_S - (time.monotonic() - _t_start)


def _emit(section: str, data: dict) -> None:
    """Print-and-flush one section's result the moment it exists — a
    later timeout cannot lose it (the driver keeps the tail)."""
    print(json.dumps({"bench_section": section, **data}), flush=True)


def _run_phase(section: str, argv: list[str], timeout_s: float,
               key: str | None = None, min_needed_s: float = 120.0) -> dict:
    """Run one phase as a subprocess; parse the last JSON line of its
    stdout. Returns {"error"/"skipped": ...} instead of raising so a bad
    phase never takes down the phases after it.

    The phase runs in its OWN process group: a timeout signals the whole
    group, so the engine/router grandchildren a hung bench_livestack
    would otherwise orphan (holding the chip and starving every later
    phase) die with it.
    """
    budget = min(timeout_s, _remaining() - 30.0)
    if budget < min_needed_s:
        result = {"skipped": f"budget: {_remaining():.0f}s left, "
                             f"need >={min_needed_s:.0f}s"}
        _emit(section, result)
        return result
    t0 = time.monotonic()
    proc = None
    try:
        proc = subprocess.Popen(
            [sys.executable, *argv], cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        try:
            out_b, _ = proc.communicate(timeout=budget)
            out = out_b.decode(errors="replace")
            result = _parse_phase_json(out, proc.returncode, key)
        except subprocess.TimeoutExpired:
            _kill_group(proc)
            out_b, _ = proc.communicate(timeout=30)
            out = (out_b or b"").decode(errors="replace")
            result = {"error": f"timeout after {budget:.0f}s",
                      "tail": out[-800:]}
    except Exception as e:  # noqa: BLE001 — phase isolation is the point
        if proc is not None and proc.poll() is None:
            _kill_group(proc)
        result = {"error": f"{type(e).__name__}: {e}"}
    result["phase_elapsed_s"] = round(time.monotonic() - t0, 1)
    _emit(section, result)
    return result


def _kill_group(proc: subprocess.Popen) -> None:
    """SIGTERM, a short grace, then ALWAYS SIGKILL the phase's whole
    process group — engine and router grandchildren included (they hold
    the chip). The direct child dying is NOT enough to stop: a
    grandchild stuck in a native compile ignores SIGTERM and would
    otherwise keep the chip and the stdout pipe."""
    import signal

    try:
        os.killpg(proc.pid, signal.SIGTERM)
    except (ProcessLookupError, PermissionError):
        return
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and proc.poll() is None:
        time.sleep(0.5)
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


def _parse_phase_json(out: str, rc: int, key: str | None) -> dict:
    last_json = None
    for line in out.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                last_json = json.loads(line)
            except json.JSONDecodeError:
                pass
    if last_json is None:
        return {"error": f"no JSON in output (rc={rc})", "tail": out[-800:]}
    result = last_json.get(key, last_json) if key else last_json
    if rc != 0:
        result.setdefault("rc", rc)
    return result


# -- bench regression gate ---------------------------------------------------
# Every BENCH_r*.json the driver archives is a full phase tree; comparing
# the current run against the newest USABLE one turns the trajectory into
# a gate: a phase metric drifting past tolerance is named in the output
# instead of waiting for a human to diff two JSON blobs. Advisory by
# design — the gate never fails the run.
_REGRESSION_TOL_PCT = 15.0

# direction heuristics by metric-name markers; HIGHER-better is checked
# first because throughput names like req_per_s/tok_s also end in the
# lower-better "_s" suffix. Unknown direction -> not compared (counts,
# config echoes, booleans-as-ints).
_HIGHER_BETTER = ("req_per_s", "tok_s", "per_s", "throughput", "rate",
                  "qps", "goodput", "value", "hit")
_LOWER_BETTER = ("ttft", "latency", "overhead_pct", "lag", "stall",
                 "wait", "_ms", "_s")
_NEVER_COMPARED = ("elapsed_s", "rc", "n", "timeout", "budget")


def _metric_direction(path: str) -> int:
    """+1 higher-is-better, -1 lower-is-better, 0 unknown (skip)."""
    leaf = path.rsplit(".", 1)[-1]
    if any(leaf == m or leaf.endswith(m) for m in _NEVER_COMPARED):
        return 0
    if any(m in leaf for m in _HIGHER_BETTER):
        return 1
    if any(m in leaf for m in _LOWER_BETTER):
        return -1
    return 0


def _numeric_leaves(tree, prefix: str = "") -> dict[str, float]:
    """Flatten a phase tree to dotted-path -> numeric leaf (bools are
    NOT numbers here; lists are opaque — per-rep samples, not metrics)."""
    out: dict[str, float] = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_numeric_leaves(v, f"{prefix}.{k}" if prefix else k))
    elif isinstance(tree, bool):
        pass
    elif isinstance(tree, (int, float)):
        out[prefix] = float(tree)
    return out


def _load_bench_baseline() -> tuple[str | None, dict | None]:
    """Newest BENCH_r*.json whose driver-parsed tree is usable; an
    archive with parsed=None is skipped and older history still serves
    as the baseline."""
    import glob

    for path in sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")),
                       reverse=True):
        try:
            doc = json.loads(open(path, encoding="utf-8").read())
        except (OSError, ValueError):
            continue
        parsed = doc.get("parsed")
        if isinstance(parsed, dict) and parsed:
            return os.path.basename(path), parsed
    return None, None


def _regression_gate(current: dict) -> dict:
    """Compare every shared numeric leaf against the newest usable
    baseline at ±_REGRESSION_TOL_PCT. Absent history -> baseline: none.
    NEVER raises and never fails the run — the flagged list is evidence
    in the trajectory, not a verdict."""
    try:
        fname, base = _load_bench_baseline()
        if base is None:
            return {"baseline": "none",
                    "tolerance_pct": _REGRESSION_TOL_PCT}
        b, c = _numeric_leaves(base), _numeric_leaves(current)
        flagged: list[dict] = []
        compared = 0
        for path, bv in sorted(b.items()):
            cv = c.get(path)
            if cv is None or bv == 0.0:
                continue  # metric absent this run / no baseline signal
            direction = _metric_direction(path)
            if direction == 0:
                continue
            compared += 1
            delta_pct = (cv - bv) / abs(bv) * 100.0
            if direction * delta_pct < -_REGRESSION_TOL_PCT:
                flagged.append({
                    "metric": path,
                    "baseline": bv,
                    "current": cv,
                    "delta_pct": round(delta_pct, 1),
                })
        flagged.sort(key=lambda r: -abs(r["delta_pct"]))
        return {
            "baseline": fname,
            "tolerance_pct": _REGRESSION_TOL_PCT,
            "compared": compared,
            "flagged": flagged[:40],
        }
    except Exception as e:  # noqa: BLE001 — advisory gate, never fatal
        return {"baseline": "none",
                "error": f"{type(e).__name__}: {e}"}


def run_microbench() -> dict:
    """Offline throughput: 256 concurrent 128-token prompts, 128 greedy
    tokens each, continuous batching over the paged fp8-capable pool.
    (Runs inside the `--phase micro` subprocess.)"""
    import numpy as np

    from vllm_production_stack_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        ParallelConfig,
        SchedulerConfig,
    )
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.request import SamplingParams
    from vllm_production_stack_tpu.models.registry import resolve_model_config

    n_seqs, prompt_len, gen_len = 256, 128, 128
    model_cfg = resolve_model_config("llama-1b", max_model_len=1024,
                                     dtype="bfloat16")
    config = EngineConfig(
        model=model_cfg,
        cache=CacheConfig(block_size=16, num_blocks=None,
                          hbm_utilization=0.78),
        scheduler=SchedulerConfig(
            max_num_seqs=n_seqs,
            max_num_batched_tokens=n_seqs * prompt_len,
            decode_buckets=(n_seqs,),
            prefill_buckets=(32, prompt_len, 2048, n_seqs * prompt_len),
            decode_window=128,
            width_floor_blocks=1,
        ),
        parallel=ParallelConfig(tensor_parallel_size=1),
    )
    engine = LLMEngine(config)
    sampling = SamplingParams(max_tokens=gen_len, temperature=0.0,
                              ignore_eos=True)

    def make_prompts(seed0: int) -> list[list[int]]:
        return [
            list(np.random.RandomState(seed0 + i).randint(
                1, model_cfg.vocab_size, size=prompt_len))
            for i in range(n_seqs)
        ]

    # warmup compiles every program the measured wave hits
    engine.generate(make_prompts(10_000), sampling)
    t0 = time.perf_counter()
    outs = engine.generate(make_prompts(0), sampling)
    elapsed = time.perf_counter() - t0
    gen = sum(len(o["token_ids"]) for o in outs)
    assert gen == n_seqs * gen_len, (gen, n_seqs * gen_len)
    return {
        "tok_s": round(n_seqs * gen_len / elapsed, 1),
        "total_s": round(elapsed, 3),
        "vs_fake_engine_rate": round(n_seqs * gen_len / elapsed / 500.0, 2),
    }


def run_steploop_bench() -> dict:
    """Decode step-loop attribution (pipelined-engine PR): steps/s and
    host-sync fraction (sync_s / step wall) for the serial loop vs the
    two-deep pipelined loop, same model/workload. The pipelined loop
    dispatches window N+1 against speculatively-advanced state before
    window N's tokens reach the host, so the host-sync fraction is the
    direct measure of what the overlap buys. Small wave — the microbench
    above owns the headline throughput number."""
    import numpy as np

    from vllm_production_stack_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        SchedulerConfig,
    )
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.request import SamplingParams
    from vllm_production_stack_tpu.models.registry import resolve_model_config

    import gc

    n_seqs, prompt_len, gen_len = 64, 64, 128
    model_cfg = resolve_model_config("llama-1b", max_model_len=512,
                                     dtype="bfloat16")
    out: dict = {}
    engine = None
    for mode, async_on in (("sync", False), ("pipelined", True)):
        # free the previous mode's weights + KV pool BEFORE building the
        # next engine — two live pools would OOM the chip
        del engine
        gc.collect()
        config = EngineConfig(
            model=model_cfg,
            cache=CacheConfig(block_size=16, num_blocks=None,
                              hbm_utilization=0.70),
            scheduler=SchedulerConfig(
                max_num_seqs=n_seqs,
                max_num_batched_tokens=n_seqs * prompt_len,
                decode_buckets=(n_seqs,),
                prefill_buckets=(prompt_len, n_seqs * prompt_len),
                decode_window=8,  # many short windows: the step-loop regime
                width_floor_blocks=1,
            ),
            async_scheduling=async_on,
        )
        engine = LLMEngine(config)
        sampling = SamplingParams(max_tokens=gen_len, temperature=0.0,
                                  ignore_eos=True)
        prompts = [
            list(np.random.RandomState(5000 + i).randint(
                1, model_cfg.vocab_size, size=prompt_len))
            for i in range(n_seqs)
        ]
        engine.generate(prompts, sampling)  # warmup: compile the wave
        t_before = dict(engine.timing)
        t0 = time.perf_counter()
        outs = engine.generate(prompts, sampling)
        wall = time.perf_counter() - t0
        gen = sum(len(o["token_ids"]) for o in outs)
        dt = {k: engine.timing[k] - t_before[k] for k in t_before}
        out[mode] = {
            "tok_s": round(gen / wall, 1),
            "steps_s": round(dt["decode_n"] / wall, 2),
            "sync_frac": round(dt["sync_s"] / wall, 3),
            "overlap_frac": round(
                dt["overlap_s"] / dt["step_wall_s"], 3
            ) if dt["step_wall_s"] else 0.0,
            "rollbacks": dt["rollback_n"],
            "wall_s": round(wall, 3),
        }
    if out["sync"]["tok_s"]:
        out["pipeline_speedup"] = round(
            out["pipelined"]["tok_s"] / out["sync"]["tok_s"], 3
        )
    del engine
    gc.collect()
    try:
        out["speculative"] = run_steploop_spec_arms()
    except Exception as e:  # never lose the loop numbers to the rider
        out["speculative"] = {"error": str(e)}
    return out


def run_steploop_spec_arms() -> dict:
    """Speculative decoding × step loop (docs/36-speculative-decoding.md):
    speculative+pipelined vs pipelined-only vs speculative-serial on a
    repetition-friendly decode workload — decode tok/s, acceptance rate,
    host-sync fraction. Asserts the composed arm strictly beats BOTH
    baselines, that its streams are bitwise identical to the serial
    speculative loop, and that the goodput-ledger partition is exact.

    Workload: random prompts into a CYCLIC-decode fixture model — the
    tiny-llama preset with attention-output and MLP-down projections
    zeroed, so the hidden state is a function of the current token alone
    and greedy decode iterates a fixed token→token map into a short cycle
    (the same crafted-fixture idiom the identical-weights draft tests
    lean on). Perfectly periodic output is the n-gram proposer's home
    regime: acceptance approaches 1 and the verify dispatch (ONE forward
    over k+1 positions) replaces a decode window's w sequential forwards,
    which is exactly the economics speculation is supposed to buy. The
    fixture keeps the cycle shorter than the proposer's lookback on any
    vocab; STEPLOOP_SPEC_MODEL overrides the model on a chip big enough
    to amortize a larger vocab's longer cycles."""
    import gc
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from vllm_production_stack_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        SchedulerConfig,
    )
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.request import SamplingParams
    from vllm_production_stack_tpu.models.registry import resolve_model_config

    n_seqs, prompt_len, gen_len, spec_k = 8, 32, 256, 8
    model_cfg = resolve_model_config(
        os.environ.get("STEPLOOP_SPEC_MODEL", "tiny-llama"),
        max_model_len=512,
    )

    def fixture_params(params):
        def zero_mixing(path, x):
            name = "/".join(str(getattr(k, "key", k)) for k in path)
            if name.endswith("attn/wo") or name.endswith("mlp/down"):
                return jnp.zeros_like(x)
            return x

        return jax.tree_util.tree_map_with_path(zero_mixing, params)

    prompts = [
        list(np.random.RandomState(900 + i).randint(
            1, model_cfg.vocab_size, size=prompt_len))
        for i in range(n_seqs)
    ]
    sampling = SamplingParams(max_tokens=gen_len, temperature=0.0,
                              ignore_eos=True)
    out: dict = {}
    streams: dict[str, list[list[int]]] = {}
    engine = None
    for mode, async_on, k in (
        ("pipelined_only", True, 0),
        ("spec_serial", False, spec_k),
        ("spec_pipelined", True, spec_k),
    ):
        del engine
        gc.collect()
        engine = LLMEngine(EngineConfig(
            model=model_cfg,
            cache=CacheConfig(block_size=16, num_blocks=1024),
            scheduler=SchedulerConfig(
                max_num_seqs=n_seqs,
                max_num_batched_tokens=n_seqs * prompt_len,
                decode_buckets=(n_seqs,),
                prefill_buckets=(prompt_len, n_seqs * prompt_len),
                decode_window=8,
                width_floor_blocks=1,
                num_speculative_tokens=k,
            ),
            async_scheduling=async_on,
        ))
        engine.runner.params = fixture_params(engine.runner.params)
        engine.generate(prompts, sampling)  # warmup: compile the wave
        sched = engine.scheduler
        best = None
        for _ in range(2):  # best of two: scheduler-noise tolerance
            t_before = dict(engine.timing)
            prop0 = sched.spec_proposed_tokens
            acc0 = sched.spec_accepted_tokens
            t0 = time.perf_counter()
            outs = engine.generate(prompts, sampling)
            wall = time.perf_counter() - t0
            gen = sum(len(o["token_ids"]) for o in outs)
            assert gen == n_seqs * gen_len, (gen, n_seqs * gen_len)
            dt = {kk: engine.timing[kk] - t_before[kk] for kk in t_before}
            proposed = sched.spec_proposed_tokens - prop0
            accepted = sched.spec_accepted_tokens - acc0
            balance = engine.goodput_balance()
            streams[mode] = [o["token_ids"] for o in outs]
            wave = {
                "tok_s": round(gen / wall, 1),
                "acceptance_rate": round(accepted / proposed, 3)
                if proposed else 0.0,
                "proposed": proposed,
                "sync_frac": round(dt["sync_s"] / wall, 3),
                "overlap_frac": round(
                    dt["overlap_s"] / dt["step_wall_s"], 3
                ) if dt["step_wall_s"] else 0.0,
                "rollbacks": dt["rollback_n"],
                "ledger_balanced": bool(balance["balanced"]),
                "wall_s": round(wall, 3),
            }
            if best is None or wave["tok_s"] > best["tok_s"]:
                best = wave
        out[mode] = best
        engine.runner.shutdown(wait=True)
    # the PR 1 equivalence bar, speculation active: composing with the
    # pipeline must not move a single token
    out["streams_bitwise_equal"] = (
        streams["spec_serial"] == streams["spec_pipelined"]
    )
    out["composed_beats_pipelined_only"] = (
        out["spec_pipelined"]["tok_s"] > out["pipelined_only"]["tok_s"]
    )
    out["composed_beats_spec_serial"] = (
        out["spec_pipelined"]["tok_s"] > out["spec_serial"]["tok_s"]
    )
    assert out["streams_bitwise_equal"], "spec streams diverged across loops"
    assert all(out[m]["ledger_balanced"] for m in streams), out
    assert out["composed_beats_pipelined_only"], out
    # the composed-beats-serial claim is the PIPELINE's contribution —
    # host work hidden behind device compute. On the cpu backend the
    # "device" IS the host (same cores execute both), so there is nothing
    # to hide behind and chaining's extra dispatch shows up as pure
    # overhead; `host_cores` rides the JSON (the fleet-bench honesty-note
    # idiom) so a serialized CPU result reads as what it is. Asserted on
    # a real accelerator, reported otherwise.
    out["host_cores"] = os.cpu_count()
    out["backend"] = jax.default_backend()
    if out["backend"] != "cpu":
        assert out["composed_beats_spec_serial"], out
    return out


async def _routing_bench() -> dict:
    """KV-aware routing lookup cost, fan-out vs indexed (CPU-only — pure
    host-side code, so this number survives even when TPU preflight fails).

    Old request path: router → controller → /kv/lookup probe on EVERY
    engine (each probe walking the hash chain server-side) — measured here
    as real aiohttp servers. New path: the event-driven cluster KV index
    embedded in the router process (kv_index.ClusterKVIndex fed from each
    pool's KVEventLog) answered in-process with zero network hops. The same
    probe set runs through both; answers must MATCH (same pool state ⇒ same
    matched_tokens) and indexed must be >=10x fan-out lookups/s."""
    import asyncio

    import numpy as np
    from aiohttp import web

    from vllm_production_stack_tpu.engine.kv_cache import KVBlockPool
    from vllm_production_stack_tpu.engine.kv_controller import KVController
    from vllm_production_stack_tpu.kv_index import ClusterKVIndex

    N_ENGINES, BLOCK, N_PROMPTS, PROMPT_TOKENS = 4, 16, 32, 512
    N_LOOKUPS = 300
    pools = [KVBlockPool(2048, BLOCK) for _ in range(N_ENGINES)]

    # index first sees each pool EMPTY via snapshot, then ingests the
    # admissions through the real event stream — the push protocol under
    # measurement, not a shortcut bulk load. Liveness TTL off: these
    # simulated engines never heartbeat, and the fan-out phase runs
    # between the feed and the indexed lookups
    index = ClusterKVIndex(stale_after_s=None)
    urls = [None] * N_ENGINES  # filled once servers bind

    rng = np.random.RandomState(7)
    prompts = [
        [int(t) for t in rng.randint(1, 30000, size=PROMPT_TOKENS)]
        for _ in range(N_PROMPTS)
    ]

    def admit(pool: KVBlockPool, ids: list[int]) -> None:
        parent = pool.root_hash()
        for i in range(len(ids) // BLOCK):
            blk = pool.allocate()
            assert blk is not None, "routing bench pool sized too small"
            parent = pool.register_full_block(
                blk, parent, tuple(ids[i * BLOCK : (i + 1) * BLOCK])
            )

    # fan-out side: each engine is a real aiohttp server whose /kv/lookup
    # walks its pool's chain — the per-probe server-side work the old path
    # pays on every routed request
    def engine_app(pool: KVBlockPool) -> web.Application:
        async def kv_lookup(request):
            body = await request.json()
            n = pool.match_length(list(body["token_ids"]))
            return web.json_response({"matched_tokens": n})

        app = web.Application()
        app.router.add_post("/kv/lookup", kv_lookup)
        return app

    runners = []
    try:
        for i, pool in enumerate(pools):
            runner = web.AppRunner(engine_app(pool))
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            port = runner.addresses[0][1]
            urls[i] = f"http://127.0.0.1:{port}"
            runners.append(runner)

        # snapshot (empty), admit, then drain the REAL event logs into the
        # index — each prompt lands on one engine; every third prompt's
        # first half is also resident on the next engine
        for i, pool in enumerate(pools):
            epoch, seq, hashes = pool.snapshot_events()
            index.apply({
                "engine": urls[i], "epoch": epoch, "block_size": BLOCK,
                "snapshot": True, "seq": seq,
                "hashes": [f"{h:x}" for h in hashes],
            })
        for j, pr in enumerate(prompts):
            k = j % N_ENGINES
            admit(pools[k], pr)
            if j % 3 == 0:
                admit(pools[(k + 1) % N_ENGINES], pr[: PROMPT_TOKENS // 2])
        for i, pool in enumerate(pools):
            while True:
                seq_start, events = pool.events.drain()
                if not events:
                    break
                reply = index.apply({
                    "engine": urls[i], "epoch": pool.events.epoch,
                    "block_size": BLOCK, "seq_start": seq_start,
                    "events": events,
                })
                assert reply.get("status") == "ok", reply

        controller = KVController(urls, mode="fanout")
        c_runner = web.AppRunner(controller.build_app())
        await c_runner.setup()
        c_site = web.TCPSite(c_runner, "127.0.0.1", 0)
        await c_site.start()
        runners.append(c_runner)
        c_url = f"http://127.0.0.1:{c_runner.addresses[0][1]}"

        # probe set: hits (full prompt), partial hits (prompt + junk tail),
        # misses (fresh random) — identical for both paths
        probes = []
        for i in range(N_LOOKUPS):
            pr = prompts[i % N_PROMPTS]
            kind = i % 3
            if kind == 0:
                probes.append(pr)
            elif kind == 1:
                probes.append(
                    pr + [int(t) for t in rng.randint(1, 30000, size=64)]
                )
            else:
                probes.append(
                    [int(t) for t in rng.randint(1, 30000, size=256)]
                )

        import aiohttp

        fanout_lat, fanout_ans = [], []
        async with aiohttp.ClientSession() as sess:
            for ids in probes:
                t0 = time.perf_counter()
                async with sess.post(
                    c_url + "/lookup", json={"token_ids": ids}
                ) as resp:
                    data = await resp.json()
                fanout_lat.append(time.perf_counter() - t0)
                fanout_ans.append(int(data["matched_tokens"]))

        indexed_lat, indexed_ans = [], []
        for ids in probes:
            t0 = time.perf_counter()
            _, n = index.lookup_token_ids(ids)
            indexed_lat.append(time.perf_counter() - t0)
            indexed_ans.append(n)
    finally:
        for runner in runners:
            await runner.cleanup()

    mismatches = sum(1 for a, b in zip(fanout_ans, indexed_ans) if a != b)

    def pct(lat, p):
        return round(sorted(lat)[min(len(lat) - 1, int(p * len(lat)))] * 1e3, 3)

    fanout_lps = round(N_LOOKUPS / sum(fanout_lat), 1)
    indexed_lps = round(N_LOOKUPS / sum(indexed_lat), 1)
    return {
        "engines": N_ENGINES,
        "lookups": N_LOOKUPS,
        "probes_per_fanout_lookup": controller.probes_sent / N_LOOKUPS,
        "fanout": {"lookups_s": fanout_lps,
                   "p50_ms": pct(fanout_lat, 0.50),
                   "p99_ms": pct(fanout_lat, 0.99)},
        "indexed": {"lookups_s": indexed_lps,
                    "p50_ms": pct(indexed_lat, 0.50),
                    "p99_ms": pct(indexed_lat, 0.99)},
        "speedup": round(indexed_lps / fanout_lps, 1) if fanout_lps else None,
        "answers_match": mismatches == 0,
        "mismatches": mismatches,
    }


def _phase_routing_main() -> None:
    """Subprocess entry for the CPU-only routing lookup bench."""
    import asyncio

    result = asyncio.run(_routing_bench())
    print(json.dumps({"routing": result}), flush=True)


async def _robustness_bench() -> dict:
    """Request-lifecycle robustness numbers (docs/26-robustness.md), on a
    CPU tiny engine behind its real HTTP server (the section needs no
    chip):

    - **shed latency** — how fast an overloaded engine turns a request
      away (429 + Retry-After) under a flood that overruns
      max_waiting_requests. Slow shedding is no shedding: the 429 must
      come back orders of magnitude faster than serving the request.
    - **drain time** — how long POST /drain?wait=true takes to pass the
      drain barrier with a stream in flight — the bound helm's preStop
      hook + terminationGracePeriodSeconds rely on.
    """
    import asyncio
    from dataclasses import replace

    from aiohttp.test_utils import TestClient, TestServer

    from vllm_production_stack_tpu.engine.config import EngineConfig
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.server import EngineServer

    N_FLOOD = 32
    cfg = EngineConfig.tiny()
    cfg = cfg.replace(
        scheduler=replace(cfg.scheduler, max_waiting_requests=4)
    )
    srv = EngineServer(
        LLMEngine(cfg), served_model_name="tiny", drain_timeout_s=30.0
    )
    client = TestClient(TestServer(srv.build_app()))
    await client.start_server()
    try:
        body = {"model": "tiny", "prompt": [5, 6, 7, 8],
                "temperature": 0.0, "max_tokens": 24, "ignore_eos": True}
        # warm up: the flood must measure shedding, not XLA compiles
        r = await client.post("/v1/completions", json=dict(body, max_tokens=4))
        assert r.status == 200, await r.text()

        async def one():
            t0 = time.monotonic()
            r = await client.post("/v1/completions", json=body)
            await r.read()
            return r.status, time.monotonic() - t0, r.headers.get("Retry-After")

        results = await asyncio.gather(*[one() for _ in range(N_FLOOD)])
        shed_lat = sorted(lat for st, lat, _ in results if st == 429)
        served_lat = sorted(lat for st, lat, _ in results if st == 200)
        retry_after = [float(ra) for st, _, ra in results if st == 429 and ra]

        def pct(lat, p):
            if not lat:
                return None
            return round(lat[min(len(lat) - 1, int(p * len(lat)))] * 1e3, 2)

        # drain with a stream in flight (one-way — runs LAST)
        stream_task = asyncio.ensure_future(
            client.post("/v1/completions",
                        json=dict(body, max_tokens=48, stream=True))
        )
        await asyncio.sleep(0.05)
        t0 = time.monotonic()
        r = await client.post("/drain?wait=true")
        drain_s = time.monotonic() - t0
        drained = (await r.json()).get("drained")
        stream_resp = await stream_task
        stream_text = await stream_resp.text()
        return {
            "flood_requests": N_FLOOD,
            "served": len(served_lat),
            "shed": len(shed_lat),
            "shed_latency_p50_ms": pct(shed_lat, 0.50),
            "shed_latency_p99_ms": pct(shed_lat, 0.99),
            "served_latency_p50_ms": pct(served_lat, 0.50),
            "retry_after_s": retry_after[0] if retry_after else None,
            "drain_s": round(drain_s, 3),
            "drained": bool(drained),
            "drained_stream_clean": "data: [DONE]" in stream_text,
        }
    finally:
        await client.close()


async def _tracing_bench() -> dict:
    """Request-tracing spine overhead (docs/28-request-tracing.md), on a
    CPU tiny engine behind its real HTTP server — the same flood shape as
    the robustness phase, run twice: --request-tracing false, then true.
    The spine's cost must be MEASURED, not asserted (acceptance bar:
    ≤2% p50 latency with tracing enabled). One engine serves both modes
    (the server rebuilds around it), so XLA compiles are paid once and
    the comparison is compile-noise-free."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from vllm_production_stack_tpu.engine.config import EngineConfig
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.server import EngineServer

    N_CLIENTS = 8
    N_PER_CLIENT = 8
    REPS = 6  # alternate off/on and keep each mode's BEST rep: a CPU
    # box's scheduling jitter (tens of ms on a shared host) dwarfs the
    # spine's per-request cost, and min-of-reps is the standard
    # noise-robust estimator — 3 reps were measured insufficient here
    body = {"model": "tiny", "prompt": [5, 6, 7, 8], "temperature": 0.0,
            "max_tokens": 12, "ignore_eos": True}
    engine = LLMEngine(EngineConfig.tiny())

    def pct(lat, p):
        if not lat:
            return None
        return round(lat[min(len(lat) - 1, int(p * len(lat)))] * 1e3, 2)

    last_buffered = 0

    async def flood(tracing: bool) -> list[float]:
        nonlocal last_buffered
        srv = EngineServer(
            engine, served_model_name="tiny", request_tracing=tracing
        )
        client = TestClient(TestServer(srv.build_app()))
        await client.start_server()
        try:
            lat: list[float] = []

            async def one_client(n: int):
                for _ in range(n):
                    t0 = time.monotonic()
                    r = await client.post("/v1/completions", json=body)
                    await r.read()
                    assert r.status == 200, await r.text()
                    lat.append(time.monotonic() - t0)

            await asyncio.gather(
                *[one_client(N_PER_CLIENT) for _ in range(N_CLIENTS)]
            )
            dbg = await (await client.get("/debug/requests")).json()
            last_buffered = dbg.get("finished_buffered", 0)
            return lat
        finally:
            await client.close()

    async def settle_compiles(timeout_s=60.0):
        """Wait until no background XLA compile is queued or running —
        the compiler's idle gate fires exactly when a flood stops, i.e.
        right inside the next measurement window, and the CPU it steals
        dwarfs the spine cost being measured."""
        t_end = time.monotonic() + timeout_s
        while time.monotonic() < t_end:
            with engine.runner._bg_lock:
                if not engine.runner._bg_inflight:
                    return
            await asyncio.sleep(0.25)

    # untimed warmup floods: the measured passes must compare the spine's
    # cost, not who paid the XLA compiles for the flood's batch shapes —
    # and one more after the compile settle so both modes start from the
    # same steady state (flood latency drifts downward while warming)
    for _ in range(2):
        await flood(False)
    await settle_compiles()
    await flood(False)
    # POOL the latencies of all reps per mode (alternating order, so
    # box-level drift lands evenly in both pools): percentiles over the
    # pooled distribution are far more stable than any single flood's —
    # one flood's p50 swings several percent with queue-phase alignment,
    # which would drown the spine's tens-of-µs per-request cost
    pools: dict[bool, list[float]] = {False: [], True: []}
    buffered = {False: 0, True: 0}
    for _ in range(REPS):
        for mode in (False, True):
            pools[mode].extend(await flood(mode))
            buffered[mode] = last_buffered

    def summarize(mode: bool) -> dict:
        lat = sorted(pools[mode])
        return {
            "tracing": mode,
            # fastest single request: the tightest bound on per-request
            # added cost (immune to queue-phase jitter entirely)
            "min_ms": round(lat[0] * 1e3, 2),
            "p50_ms": pct(lat, 0.50),
            "p99_ms": pct(lat, 0.99),
            "mean_ms": round(sum(lat) / len(lat) * 1e3, 2),
            "buffered_traces": buffered[mode],
        }

    off, on = summarize(False), summarize(True)
    return {
        "requests_per_mode": N_CLIENTS * N_PER_CLIENT * REPS,
        "reps": REPS,
        "off": off,
        "on": on,
        "p50_overhead_pct": round(
            (on["p50_ms"] / off["p50_ms"] - 1.0) * 100.0, 2
        ),
        "mean_overhead_pct": round(
            (on["mean_ms"] / off["mean_ms"] - 1.0) * 100.0, 2
        ),
        "min_overhead_pct": round(
            (on["min_ms"] / off["min_ms"] - 1.0) * 100.0, 2
        ),
    }


async def _structured_bench() -> dict:
    """Structured-output serving (docs/41-structured-output.md), CPU-only
    and pre-preflight: an agent swarm — concurrent chat sessions sharing
    ONE system+tools prefix — where every turn is a forced
    schema-constrained tool call (`tool_choice: "required"`).

    Evidence in the BENCH trajectory:
    - valid tool-call rate 1.0 with enforcement vs ~0 without (same
      model, same prompts — the grammar is the only difference)
    - constrained-vs-unconstrained decode overhead at MATCHED decode
      length (the unconstrained arm replays the constrained arm's median
      completion length with ignore_eos, so both arms run the same
      number of decode steps and the delta prices the mask alone)
    - TTFT under the swarm (streamed probes)
    - shared-prefix hit rate (every agent rides the same system+tools
      prefill)
    - ZERO new compiled programs after warmup — the mask is data, not
      shape, so constrained traffic must never recompile
    - bitwise serial-vs-pipelined identity of a constrained stream
    """
    import asyncio
    import dataclasses

    from aiohttp.test_utils import TestClient, TestServer

    from vllm_production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.request import SamplingParams
    from vllm_production_stack_tpu.engine.server import EngineServer

    N_AGENTS = 16
    REPS = 3
    N_TTFT = 8

    TOOLS = [{"type": "function", "function": {
        "name": "record_result",
        "description": "Record one benchmark observation.",
        "parameters": {"type": "object", "properties": {
            "status": {"enum": ["pass", "fail", "flaky"]},
            "cached": {"type": "boolean"},
            "tier": {"enum": [0, 1, 2]},
        }},
    }}]
    SYSTEM = ("You are one recorder in a swarm of benchmark agents. "
              "Observe the run named in the user turn and record exactly "
              "one observation by calling the tool.")

    # the tool-steering preamble alone outgrows the 256-token tiny
    # context, so the swarm engine gets a longer one
    engine = LLMEngine(EngineConfig(
        model=ModelConfig.tiny(max_model_len=1024),
        cache=CacheConfig(block_size=8, num_blocks=1536),
        scheduler=SchedulerConfig(
            max_num_seqs=8, max_num_batched_tokens=1024,
            decode_buckets=(4, 8), prefill_buckets=(256, 512, 1024),
        ),
    ))
    srv = EngineServer(engine, served_model_name="tiny")
    client = TestClient(TestServer(srv.build_app()))
    await client.start_server()

    def body(i: int, constrained: bool, max_tokens: int) -> dict:
        return {
            "model": "tiny",
            "messages": [
                {"role": "system", "content": SYSTEM},
                {"role": "user", "content": f"run #{i}: record it"},
            ],
            "tools": TOOLS,
            "tool_choice": "required" if constrained else "auto",
            # unconstrained replays the constrained arm's decode length
            "ignore_eos": not constrained,
            "temperature": 0.0, "max_tokens": max_tokens,
        }

    async def one(i: int, constrained: bool, max_tokens: int):
        t0 = time.monotonic()
        r = await client.post("/v1/chat/completions",
                              json=body(i, constrained, max_tokens))
        doc = await r.json()
        lat = time.monotonic() - t0
        assert r.status == 200, doc
        calls = doc["choices"][0]["message"].get("tool_calls") or []
        ok = False
        if len(calls) == 1 and calls[0]["function"]["name"] == "record_result":
            try:
                json.loads(calls[0]["function"]["arguments"])
                ok = True
            except ValueError:
                ok = False
        n_out = (doc.get("usage") or {}).get("completion_tokens") or 0
        return lat, ok, n_out

    async def flood(constrained: bool, max_tokens: int):
        return await asyncio.gather(
            *[one(i, constrained, max_tokens) for i in range(N_AGENTS)]
        )

    async def settle_compiles(timeout_s=60.0):
        t_end = time.monotonic() + timeout_s
        while time.monotonic() < t_end:
            with engine.runner._bg_lock:
                if not engine.runner._bg_inflight:
                    return
            await asyncio.sleep(0.25)

    def pct(lat, p):
        if not lat:
            return None
        return round(lat[min(len(lat) - 1, int(p * len(lat)))] * 1e3, 2)

    try:
        # untimed warmup: pay XLA compiles + the grammar build, and learn
        # the constrained arm's decode length for the matched replay
        warm = await flood(True, 192)
        n_toks = sorted(n for _, _, n in warm)
        matched = max(8, n_toks[len(n_toks) // 2])
        await flood(False, matched)
        await settle_compiles()
        grammar_builds = list(engine.stats().grammar_build_times)
        programs0 = len(engine.runner._aot_exec)
        bg0 = engine.runner.bg_compiles

        pools: dict[bool, list[float]] = {True: [], False: []}
        valid = {True: 0, False: 0}
        total = {True: 0, False: 0}
        for _ in range(REPS):
            for mode in (True, False):
                # constrained stops naturally at its accepting EOS (cap is
                # slack); unconstrained replays the matched median length
                for lat, ok, _n in await flood(mode, 192 if mode else matched):
                    pools[mode].append(lat)
                    valid[mode] += int(ok)
                    total[mode] += 1

        # streamed TTFT probes, constrained: time to the first SSE chunk
        async def ttft_one(i: int) -> float:
            t0 = time.monotonic()
            r = await client.post(
                "/v1/chat/completions",
                json=dict(body(i, True, 192), stream=True),
            )
            assert r.status == 200
            async for line in r.content:
                line = line.decode().strip()
                if line.startswith("data: ") and line != "data: [DONE]":
                    t = time.monotonic() - t0
                    r.close()
                    return t
            raise AssertionError("stream produced no chunk")

        ttft = sorted(await asyncio.gather(
            *[ttft_one(i) for i in range(N_TTFT)]
        ))

        snap = engine.stats()
        con = sorted(pools[True])
        unc = sorted(pools[False])
        result = {
            "agents": N_AGENTS,
            "requests_per_arm": total[True],
            "matched_decode_tokens": matched,
            "valid_rate_constrained": round(valid[True] / total[True], 3),
            "valid_rate_unconstrained": round(valid[False] / total[False], 3),
            "constrained_p50_ms": pct(con, 0.50),
            "constrained_p99_ms": pct(con, 0.99),
            "unconstrained_p50_ms": pct(unc, 0.50),
            "p50_overhead_pct": round(
                (pct(con, 0.50) / pct(unc, 0.50) - 1.0) * 100.0, 2
            ),
            "ttft_p50_ms": pct(ttft, 0.50),
            "ttft_p99_ms": pct(ttft, 0.99),
            "prefix_cache_hit_rate": round(snap.prefix_cache_hit_rate, 3),
            "grammar_builds": len(grammar_builds),
            "grammar_build_ms": [round(s * 1e3, 1) for s in grammar_builds],
            "new_programs_after_warmup":
                len(engine.runner._aot_exec) - programs0,
            "bg_compiles_after_warmup": engine.runner.bg_compiles - bg0,
            "structured_outcomes": dict(snap.structured_outcomes or {}),
        }
    finally:
        await client.close()
        engine.runner.shutdown(wait=True)

    # rider: serial vs pipelined constrained streams must be bitwise
    # identical (the async step loop may not change one masked token)
    spec = {"kind": "json_schema", "schema": {
        "type": "object", "properties": {
            "ok": {"type": "boolean"},
            "mode": {"enum": ["fast", "slow"]},
        },
    }}
    prompts = [list(range(5, 12)), list(range(40, 52))]
    streams = []
    for async_on in (True, False):
        eng = LLMEngine(EngineConfig.tiny().replace(async_scheduling=async_on))
        try:
            sp = SamplingParams(max_tokens=48, temperature=0.0)
            sp = dataclasses.replace(
                sp, grammar=eng.grammar_cache.get(spec)[0]
            )
            outs = eng.generate(prompts, sp)
            streams.append([o["token_ids"] for o in outs])
        finally:
            eng.runner.shutdown(wait=True)
    result["bitwise_serial_eq_pipelined"] = streams[0] == streams[1]
    return result


def _phase_structured_main() -> None:
    """Subprocess entry for the CPU-only structured-output bench. Forces
    CPU before anything touches jax — runs pre-preflight; the
    grammar-enforcement evidence needs no chip."""
    import asyncio
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    result = asyncio.run(_structured_bench())
    print(json.dumps({"structured": result}), flush=True)


async def _compile_bench() -> dict:
    """XLA compile telemetry (docs/42-compile-telemetry.md), CPU-only and
    pre-preflight: proves the pad-up guarantee the CompileWatch exists to
    police, on a mixed workload that walks every program-key dimension.

    Evidence in the BENCH trajectory:
    - ZERO mid-traffic compiles after coarse warmup across repeated mixed
      waves (bucket-ladder sweep + three grammar schemas + ngram spec
      decode) — the serving-path guarantee, now measured, not assumed
    - GET /debug/programs serves a non-empty inventory with per-program
      compile walls and dispatch counts
    - the storm arm feeds a cold engine unpadded shapes with a threshold
      of 3: the detector trips, and the ONE structured report names the
      offending shapes
    - watch-off vs watch-on p50 at the ≤2% noise floor (the dispatch-path
      bookkeeping must be free)
    """
    import asyncio
    import dataclasses

    import numpy as np
    from aiohttp.test_utils import TestClient, TestServer

    from vllm_production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.request import SamplingParams
    from vllm_production_stack_tpu.engine.server import EngineServer

    def make_config(**overrides) -> EngineConfig:
        return EngineConfig(
            model=ModelConfig.tiny(max_model_len=512),
            cache=CacheConfig(block_size=8, num_blocks=320),
            scheduler=SchedulerConfig(
                max_num_seqs=4, max_num_batched_tokens=128,
                decode_buckets=(2, 4), prefill_buckets=(32, 64, 128),
                decode_window=4, num_speculative_tokens=2,
            ),
        ).replace(**overrides)

    rng = np.random.RandomState(17)
    # the spec-decode arm uses FIXED prompts, one row per call: verify
    # program shapes derive from proposal lengths and batch composition,
    # so fresh random tokens each wave would compile fresh verify
    # programs forever and the steady-state assertion could never hold
    spec_rng = np.random.RandomState(23)
    SPEC_PROMPTS = [
        [int(t) for t in spec_rng.randint(1, 500, size=6)] * 4,
        [int(t) for t in spec_rng.randint(1, 500, size=6)] * 3,
    ]
    SCHEMAS = [
        {"kind": "json_schema", "schema": {
            "type": "object", "properties": {"ok": {"type": "boolean"}},
        }},
        {"kind": "json_schema", "schema": {
            "type": "object",
            "properties": {"mode": {"enum": ["fast", "slow"]}},
        }},
        {"kind": "json_schema", "schema": {
            "type": "object", "properties": {
                "tier": {"enum": [0, 1, 2]},
                "cached": {"type": "boolean"},
            },
        }},
    ]

    def mixed_wave(engine: LLMEngine) -> None:
        """One pass over every program-key dimension: prefill bucket
        ladder, grammar-keyed decode programs, spec-decode verify."""
        vocab = engine.config.model.vocab_size
        greedy = SamplingParams(
            max_tokens=6, temperature=0.0, ignore_eos=True
        )
        for plen in (20, 56, 120):  # pads to buckets 32 / 64 / 128
            engine.generate(
                [[int(t) for t in rng.randint(1, vocab, size=plen)]
                 for _ in range(3)],
                greedy,
            )
        for spec in SCHEMAS:
            sp = dataclasses.replace(
                SamplingParams(max_tokens=24, temperature=0.0),
                grammar=engine.grammar_cache.get(spec)[0],
            )
            engine.generate(
                [[int(t) for t in rng.randint(1, vocab, size=12)]], sp
            )
        # repeated tail -> the ngram proposer fires -> verify dispatches
        for prompt in SPEC_PROMPTS:
            engine.generate(
                [prompt],
                SamplingParams(max_tokens=12, temperature=0.0,
                               ignore_eos=True),
            )

    async def settle(engine: LLMEngine, timeout_s: float = 60.0) -> None:
        t_end = time.monotonic() + timeout_s
        while time.monotonic() < t_end:
            with engine.runner._bg_lock:
                if not engine.runner._bg_inflight:
                    return
            await asyncio.sleep(0.25)

    def by_trigger(compiles: dict) -> dict:
        out: dict[str, int] = {}
        for k, v in compiles.items():
            trig = k.rsplit("/", 1)[-1]
            out[trig] = out.get(trig, 0) + v
        return out

    def watch_overhead(engine: LLMEngine) -> dict:
        """Watch-on vs watch-off decode-wave p50 — the blackbox/
        saturation estimator (12 alternating reps, within-pair order
        flipped, step loop driven directly to dodge aiohttp jitter)."""
        vocab = engine.config.model.vocab_size
        prompts = [
            [int(t) for t in rng.randint(1, vocab, size=16)]
            for _ in range(8)
        ]
        sp = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True)
        for _ in range(3):  # pay any straggler compile before measuring
            engine.generate(prompts, sp)
        REPS = 12
        times: dict[bool, list[float]] = {False: [], True: []}
        for rep in range(REPS):
            order = (False, True) if rep % 2 == 0 else (True, False)
            for watching in order:
                engine.compile_watch.enabled = watching
                t0 = time.perf_counter()
                outs = engine.generate(prompts, sp)
                times[watching].append(time.perf_counter() - t0)
                lens = [len(o["token_ids"]) for o in outs]
                assert sum(lens) == 8 * 24, lens
        engine.compile_watch.enabled = True

        def p50(xs):
            return sorted(xs)[len(xs) // 2]

        off_p50, on_p50 = p50(times[False]), p50(times[True])
        result = {
            "reps": REPS,
            "off_p50_ms": round(off_p50 * 1e3, 2),
            "on_p50_ms": round(on_p50 * 1e3, 2),
            "p50_overhead_pct": round((on_p50 / off_p50 - 1.0) * 100.0, 2),
        }
        assert result["p50_overhead_pct"] <= 2.0, result
        result["overhead_ok"] = True
        return result

    # -- main arm: warmed engine, mixed traffic, zero mid-traffic compiles
    # storm threshold lifted way above the lazy shapes a tiny test engine
    # legitimately compiles on its first wave (verify + grammar-keyed
    # decode programs are not in the coarse lattice) — the storm DETECTOR
    # is exercised by the dedicated cold arm below
    engine = LLMEngine(make_config(compile_storm_threshold=50))
    try:
        t0 = time.monotonic()
        warm_passes = engine.warmup(scope="coarse")
        warm_s = time.monotonic() - t0
        # two untimed waves: pay the lazy shapes coarse warmup leaves
        # (grammar tables, grammar-keyed decode programs, verify) so the
        # measured waves run against a fully-populated program cache
        mixed_wave(engine)
        mixed_wave(engine)
        await settle(engine)
        base_snap = engine.compile_watch.stats_snapshot()
        mid0 = base_snap["mid_traffic"]

        for _ in range(3):
            mixed_wave(engine)
        await settle(engine)
        snap = engine.compile_watch.stats_snapshot()
        mid_traffic_after = snap["mid_traffic"] - mid0
        assert mid_traffic_after == 0, (
            f"{mid_traffic_after} mid-traffic compiles in steady state "
            f"(pad-up guarantee broken): {snap['compiles']}"
        )
        overhead = watch_overhead(engine)

        # server starts AFTER all blocking-generate traffic: _on_startup
        # spins the AsyncEngine step loop, which would co-drive step()
        # and steal outputs from engine.generate's collector (the
        # inventory read is the only thing that needs HTTP)
        srv = EngineServer(engine, served_model_name="tiny")
        client = TestClient(TestServer(srv.build_app()))
        await client.start_server()
        try:
            r = await client.get("/debug/programs")
            payload = await r.json()
        finally:
            await client.close()
        assert r.status == 200, payload
        assert payload["programs"], "empty inventory after mixed traffic"
        hits, misses = snap["hits"], snap["misses"]
        result = {
            "coarse_warmup_programs": warm_passes,
            "coarse_warmup_s": round(warm_s, 1),
            "inventory_programs": len(payload["programs"]),
            "compiles_by_trigger": by_trigger(snap["compiles"]),
            "mid_traffic_compiles": mid_traffic_after,
            "cache_hits": hits,
            "cache_misses": misses,
            "cache_hit_rate": round(hits / max(1, hits + misses), 3),
            "grammar_builds": sum(
                v for k, v in snap["compiles"].items()
                if k.startswith("grammar/")
            ),
            "watch_overhead": overhead,
        }
    finally:
        engine.runner.shutdown(wait=True)

    # -- storm arm: a COLD engine fed unpadded shapes, threshold 3 — the
    # detector must trip once and the report must name the shapes
    storm_engine = LLMEngine(make_config(
        compile_storm_threshold=3, compile_storm_window_s=60.0,
    ))
    try:
        vocab = storm_engine.config.model.vocab_size
        for plen in (20, 56, 120):  # three cold sync compiles, no warmup
            storm_engine.generate(
                [[int(t) for t in rng.randint(1, vocab, size=plen)]],
                SamplingParams(max_tokens=4, temperature=0.0,
                               ignore_eos=True),
            )
        watch = storm_engine.compile_watch
        report = watch.last_storm_report
        assert watch.storms_total >= 1, dict(watch.compiles)
        assert report and report["shapes"], report
        named = [s["key"] for s in report["shapes"]]
        assert any("'prefill'" in k for k in named), named
        result["storm"] = {
            "storms": watch.storms_total,
            "threshold": 3,
            "window_s": 60.0,
            "mid_traffic_compiles": report["mid_traffic_compiles"],
            "shapes_named": named[:4],
        }
    finally:
        storm_engine.runner.shutdown(wait=True)
    return result


def _phase_compile_main() -> None:
    """Subprocess entry for the CPU-only compile-telemetry bench (pad-up
    guarantee + storm detector + watch overhead, docs/42-compile-
    telemetry.md). Forces CPU before anything touches jax — this phase
    needs no chip."""
    import asyncio
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    result = asyncio.run(_compile_bench())
    print(json.dumps({"compile": result}), flush=True)


async def _blackbox_bench() -> dict:
    """Flight recorder / watchdog / postmortem evidence (docs/37-flight-
    recorder.md), CPU-only and pre-preflight: the stalls it diagnoses are
    injected, so the phase needs no chip.

    Three DISTINCT wedges injected with the chaos harness
    (testing/faults.py), each individually NAMED by the watchdog
    (correct thread=/kind=) with a postmortem JSON written for each:

    1. **stalled fetcher** — the hydration fetcher blocks under the
       disk-tier lock (faults.hold_lock): stale_heartbeat,
       thread=hydration_fetch;
    2. **blackholed publisher** — the KV-event publisher's resync POST
       lands in a black hole (faults.black_hole): stale_heartbeat,
       thread=kv_event_publisher;
    3. **frozen step loop** — engine.step blocks mid-request
       (faults.frozen_step_loop) behind the REAL HTTP server:
       stale_heartbeat, thread=step, /ready flips 503 while /health stays
       green, and the stall counter + heartbeat age render on /metrics.

    Plus the noise-floor bar: a recorder-on vs recorder-off decode-wave
    flood (alternating reps, p50 of wave wall times — the saturation
    phase's proven estimator) must show ≤2% p50 overhead — same bar as
    the StepMeter.
    """
    import asyncio
    import tempfile
    from dataclasses import replace as _dc_replace

    from aiohttp.test_utils import TestClient, TestServer

    from vllm_production_stack_tpu.engine.config import EngineConfig
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.flightrec import (
        PostmortemDumper,
        ThreadRegistry,
        Watchdog,
    )
    from vllm_production_stack_tpu.engine.server import EngineServer
    from vllm_production_stack_tpu.testing import faults

    pm_dir = tempfile.mkdtemp(prefix="tpu-blackbox-pm-")
    wedges: dict = {}

    async def _check_dump(dumper: PostmortemDumper, thread: str) -> dict:
        # the watchdog sets `stalled` BEFORE its report/dump finishes —
        # await the episode's dump WHILE the wedge is still held, so the
        # captured heartbeat table shows the stall, not the recovery
        deadline = time.monotonic() + 5.0
        while dumper.dumps_written < 1 and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        assert dumper.dumps_written >= 1, f"no postmortem for {thread}"
        doc = json.loads(open(dumper.last_path, encoding="utf-8").read())
        assert doc["trigger"] == "watchdog"
        hb = doc["heartbeats"][thread]
        assert hb["stale"] is True, hb
        return {"path": dumper.last_path, "age_s": hb["age_s"]}

    async def _await_stall(wd: Watchdog, timeout_s: float = 8.0):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if wd.stalled is not None:
                return wd.stalled
            await asyncio.sleep(0.05)
        raise AssertionError("watchdog never named the stall")

    # -- wedge 1: fetcher stalled under the disk-tier lock -----------------
    async def wedge_fetcher() -> dict:
        from vllm_production_stack_tpu.engine.hydration import (
            HydrationChunk,
            HydrationPlan,
        )

        cfg = EngineConfig.tiny()
        cfg = cfg.replace(cache=_dc_replace(
            cfg.cache, disk_kv_dir=tempfile.mkdtemp(prefix="bb-disk-"),
            disk_kv_gib=0.05,
        ))
        engine = LLMEngine(cfg)
        engine.threads.register("hydration_fetch", stall_after_s=0.3)
        dumper = PostmortemDumper(
            pm_dir, recorder=engine.flightrec, registry=engine.threads
        )
        wd = Watchdog(
            engine.threads, recorder=engine.flightrec, interval_s=0.05,
            on_stall=lambda r: dumper.dump("watchdog", "fetcher wedge"),
        )
        wd.start()
        chunk = HydrationChunk(index=0, start_block=0, hashes=[7],
                               tiers=["disk"], decision="load")
        plan = HydrationPlan("bb-req", [chunk], block_size=8,
                             deadline=time.monotonic() + 60.0, estimates={})
        t0 = time.monotonic()
        with faults.hold_lock(engine.host_tier.disk._mu):
            engine.hydrator._ensure_thread()
            engine.hydrator._q.put((plan, chunk))
            stall = await _await_stall(wd)
            detect_s = time.monotonic() - t0
            pm = await _check_dump(dumper, "hydration_fetch")
        threads = {f["thread"] for f in stall["findings"]}
        kinds = {f["kind"] for f in stall["findings"]}
        assert threads == {"hydration_fetch"}, stall
        assert kinds == {"stale_heartbeat"}, stall
        out = {"named": sorted(threads), "kinds": sorted(kinds),
               "detect_s": round(detect_s, 2), "postmortem": pm}
        wd.stop()
        engine.hydrator.close()
        return out

    # -- wedge 2: publisher blackholed mid-resync --------------------------
    async def wedge_publisher() -> dict:
        import aiohttp

        from vllm_production_stack_tpu.engine.kv_events import (
            KVEventLog,
            KVEventPublisher,
        )

        server, port = await faults.black_hole()
        reg = ThreadRegistry()
        hb = reg.register("kv_event_publisher", stall_after_s=0.3)
        dumper = PostmortemDumper(pm_dir, registry=reg)
        wd = Watchdog(
            reg, interval_s=0.05,
            on_stall=lambda r: dumper.dump("watchdog", "publisher wedge"),
        )
        wd.start()
        log = KVEventLog()
        log.emit_admit(1, 0)

        async def snapshot():
            return log.epoch, log.snapshot_mark(), [1]

        session = aiohttp.ClientSession()
        pub = KVEventPublisher(
            [f"http://127.0.0.1:{port}"], "http://bb:8000", log, snapshot,
            16, lambda: session, interval_s=0.05, send_timeout_s=30.0,
            heartbeat=hb,
        )
        t0 = time.monotonic()
        pub.start()
        try:
            stall = await _await_stall(wd)
            detect_s = time.monotonic() - t0
            pm = await _check_dump(dumper, "kv_event_publisher")
            threads = {f["thread"] for f in stall["findings"]}
            kinds = {f["kind"] for f in stall["findings"]}
            assert threads == {"kv_event_publisher"}, stall
            assert kinds == {"stale_heartbeat"}, stall
            return {"named": sorted(threads), "kinds": sorted(kinds),
                    "detect_s": round(detect_s, 2), "postmortem": pm}
        finally:
            wd.stop()
            await pub.stop()
            await session.close()
            server.close()
            await server.wait_closed()

    # -- wedge 3: frozen step loop, through the real HTTP server -----------
    async def wedge_step(engine: LLMEngine) -> dict:
        srv = EngineServer(
            engine, served_model_name="tiny",
            watchdog_interval_s=0.05, watchdog_stall_s=0.4,
            postmortem_dir=pm_dir,
        )
        client = TestClient(TestServer(srv.build_app()))
        await client.start_server()
        try:
            t0 = time.monotonic()
            with faults.frozen_step_loop(engine):
                resp = await client.post("/v1/completions", json={
                    "model": "tiny", "prompt": [3, 4, 5],
                    "max_tokens": 64, "temperature": 0.0, "stream": True,
                })
                assert resp.status == 200
                stall = None
                deadline = time.monotonic() + 10.0
                while time.monotonic() < deadline:
                    ready = await client.get("/ready")
                    if ready.status == 503:
                        body = await ready.json()
                        if body.get("reason") == "stalled":
                            stall = body["stall"]
                            break
                    await asyncio.sleep(0.05)
                assert stall is not None, "/ready never flipped on stall"
                detect_s = time.monotonic() - t0
                health = await client.get("/health")
                assert health.status == 200  # liveness never flips
                metrics = await (await client.get("/debug/flight")).json()
                resp.close()
            # recovery: the wedge released, /ready must come back
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if (await client.get("/ready")).status == 200:
                    break
                await asyncio.sleep(0.05)
            scrape = await (await client.get("/metrics")).text()
            threads = {f["thread"] for f in stall["findings"]}
            kinds = {f["kind"] for f in stall["findings"]}
            assert "step" in threads, stall
            assert "stale_heartbeat" in kinds, stall
            from vllm_production_stack_tpu import metrics_contract as mc

            stall_line = [
                line for line in scrape.splitlines()
                if line.startswith(mc.ENGINE_STEP_STALLS)
                and 'kind="stale_heartbeat"' in line
            ]
            assert stall_line and float(stall_line[0].split()[-1]) >= 1, \
                stall_line
            assert metrics["postmortems"]["written"] >= 1
            return {
                "named": sorted(threads), "kinds": sorted(kinds),
                "detect_s": round(detect_s, 2),
                "ready_flipped": True, "health_stayed_green": True,
                "postmortem": {"path": metrics["postmortems"]["last_path"]},
            }
        finally:
            await client.close()

    # -- noise floor: recorder-on vs recorder-off flood --------------------
    def overhead(engine: LLMEngine) -> dict:
        """Decode-wave flood on ONE warm engine, flight recording off vs
        on (the flag gates every ring append), 12 alternating reps, p50
        of wave wall times — the saturation phase's proven estimator.
        The recorder's cost lives entirely in the step loop, so driving
        step() directly measures it without aiohttp scheduling jitter
        (which was measured to swing an HTTP flood's p50 ±10% on a
        shared CPU box — two orders of magnitude above the signal)."""
        import numpy as np

        from vllm_production_stack_tpu.engine.request import SamplingParams

        rng = np.random.RandomState(11)
        vocab = engine.config.model.vocab_size
        prompts = [
            [int(t) for t in rng.randint(1, vocab, size=16)]
            for _ in range(8)
        ]
        wave_sampling = SamplingParams(
            max_tokens=24, temperature=0.0, ignore_eos=True
        )
        for _ in range(3):  # pay every XLA compile before measuring
            engine.generate(prompts, wave_sampling)
        REPS = 12
        times: dict[bool, list[float]] = {False: [], True: []}
        for rep in range(REPS):
            # alternate within-pair order too: a monotone box-level
            # drift must not always land on the same mode's slot
            order = (False, True) if rep % 2 == 0 else (True, False)
            for recording in order:
                engine.flightrec.enabled = recording
                t0 = time.perf_counter()
                outs = engine.generate(prompts, wave_sampling)
                times[recording].append(time.perf_counter() - t0)
                assert sum(len(o["token_ids"]) for o in outs) == 8 * 24
        engine.flightrec.enabled = True

        def p50(xs):
            return sorted(xs)[len(xs) // 2]

        off_p50, on_p50 = p50(times[False]), p50(times[True])
        result = {
            "reps": REPS,
            "wave_requests": 8,
            "off_p50_ms": round(off_p50 * 1e3, 2),
            "on_p50_ms": round(on_p50 * 1e3, 2),
            "off_min_ms": round(min(times[False]) * 1e3, 2),
            "on_min_ms": round(min(times[True]) * 1e3, 2),
            "p50_overhead_pct": round(
                (on_p50 / off_p50 - 1.0) * 100.0, 2
            ),
            "min_overhead_pct": round(
                (min(times[True]) / min(times[False]) - 1.0) * 100.0, 2
            ),
        }
        # the acceptance bar: same ≤2% p50 ceiling as the StepMeter
        assert result["p50_overhead_pct"] <= 2.0, result
        result["overhead_ok"] = True
        return result

    wedges["fetcher_disk_lock"] = await wedge_fetcher()
    wedges["publisher_blackholed"] = await wedge_publisher()
    engine = LLMEngine(EngineConfig.tiny())
    wedges["step_loop_frozen"] = await wedge_step(engine)
    flood_overhead = overhead(engine)
    named = {w["named"][0] if len(w["named"]) == 1 else tuple(w["named"])
             for w in wedges.values()}
    return {
        "wedges": wedges,
        "all_three_named": len(wedges) == 3 and all(
            w.get("postmortem") for w in wedges.values()
        ),
        "distinct_threads_named": sorted(
            t for w in wedges.values() for t in w["named"]
        ),
        "postmortem_dir": pm_dir,
        "overhead": flood_overhead,
        "_named_set_size": len(named),
    }


async def _fairness_bench() -> dict:
    """Multi-tenant QoS numbers (docs/27-multitenancy.md), on a CPU tiny
    engine behind its real HTTP server (stamped headers, the engines' own
    trust model — the router's stamping is exercised by tests/test_qos.py):

    - **qos-off throughput** — the same flood UNSTAMPED, run first (the
      fair-share path latches on the first stamped request): the QoS layer
      must cost nothing when unused.
    - **weighted share** — two batch-class tenants weighted 3:1, both
      saturating a deliberately small seat count: achieved decode-token
      share must track 75/25.
    - **probe TTFT** — a realtime-class probe under the batch flood must
      preempt a seat instead of queueing behind it: p50 TTFT bounded by a
      small multiple of its unloaded TTFT.
    """
    import asyncio
    from dataclasses import replace

    from aiohttp.test_utils import TestClient, TestServer

    from vllm_production_stack_tpu.engine.config import EngineConfig
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.server import EngineServer

    FLOOD_S = 8.0  # per measured flood window
    RAMP_S = 1.0
    N_CLIENTS = 6  # closed-loop clients per tenant (12 vs 4 seats: both
    # tenants keep the waiting queue populated, so EVERY admission is a
    # fair-share arbitration, not a default pick of the only waiter
    cfg = EngineConfig.tiny()
    # few seats + single-token decode windows: admission (where fair share
    # acts) happens often, and in-flight rows resolve every token so the
    # realtime probe's seat preemption lands immediately
    cfg = cfg.replace(
        scheduler=replace(
            cfg.scheduler, max_num_seqs=4, decode_buckets=(4,),
            decode_window=1, max_num_batched_tokens=32,
            prefill_buckets=(16, 32),
        )
    )
    engine = LLMEngine(cfg)
    srv = EngineServer(engine, served_model_name="tiny")
    client = TestClient(TestServer(srv.build_app()))
    await client.start_server()
    try:
        body = {"model": "tiny", "prompt": [5, 6, 7, 8],
                "temperature": 0.0, "max_tokens": 24, "ignore_eos": True}

        def stamps(tenant, priority, weight):
            return {"x-tenant-id": tenant, "x-priority": priority,
                    "x-tenant-weight": str(weight)}

        async def one(headers=None):
            r = await client.post("/v1/completions", json=body,
                                  headers=headers or {})
            await r.read()
            return r.status

        async def settle_compiles(timeout_s=60.0):
            """Wait until no background XLA compile is queued or running —
            the compiler's idle gate fires exactly when a flood stops, i.e.
            right inside the next measurement window."""
            t_end = time.monotonic() + timeout_s
            while time.monotonic() < t_end:
                with engine.runner._bg_lock:
                    if not engine.runner._bg_inflight:
                        return
                await asyncio.sleep(0.25)

        # warm up every compile the bench touches: a concurrent burst hits
        # the multi-row prefill/decode buckets the floods will use, then
        # wait out the background compiles — they otherwise steal CPU from
        # the first measured window
        for _ in range(2):
            statuses = await asyncio.gather(*[one() for _ in range(12)])
            assert all(s == 200 for s in statuses)
        await asyncio.sleep(1.0)
        await settle_compiles()

        import threading

        import aiohttp as _aiohttp

        port = client.server.port
        url = f"http://127.0.0.1:{port}/v1/completions"

        def flood_thread(stop_evt, header_sets):
            """Closed-loop flood clients on their OWN thread + event loop:
            real clients are remote, so their task churn must not share
            the probe's loop (a TestClient-colocation artifact that
            otherwise dominates the probe's first-byte latency)."""

            async def run():
                async with _aiohttp.ClientSession() as s:
                    async def fl(h):
                        while not stop_evt.is_set():
                            try:
                                async with s.post(
                                    url, json=body, headers=h or {}
                                ) as r:
                                    await r.read()
                            except _aiohttp.ClientError:
                                pass
                            await asyncio.sleep(0.005)

                    await asyncio.gather(*[
                        fl(h) for h in header_sets for _ in range(N_CLIENTS)
                    ])

            asyncio.run(run())

        def start_flood(header_sets):
            stop_evt = threading.Event()
            t = threading.Thread(
                target=flood_thread, args=(stop_evt, header_sets),
                daemon=True,
            )
            t.start()
            return stop_evt, t

        def tenant_tokens():
            counters, _ = engine.scheduler.accounting.snapshot()
            return {t: c.get("generation_tokens", 0)
                    for t, c in counters.items()}

        async def run_flood(header_sets, window_s):
            """Run closed-loop floods; returns generation-token deltas per
            tenant over the post-ramp window."""
            stop_evt, t = start_flood(header_sets)
            await asyncio.sleep(RAMP_S)
            t0, before = time.monotonic(), tenant_tokens()
            await asyncio.sleep(window_s)
            after, elapsed = tenant_tokens(), time.monotonic() - t0
            stop_evt.set()
            t.join(timeout=10)
            delta = {t: after.get(t, 0) - before.get(t, 0) for t in after}
            return delta, elapsed

        # 1) QoS OFF: unstamped flood FIRST (fair share latches on the
        # first stamped request — this measures the pre-QoS FIFO path)
        off_delta, off_s = await run_flood([None], FLOOD_S)
        qos_off_tps = round(sum(off_delta.values()) / off_s, 1)

        # 2) unloaded realtime probe TTFT (stamped: latches QoS)
        rt = stamps("probe", "realtime", 1)

        async def probe_ttft():
            t0 = time.monotonic()
            r = await client.post(
                "/v1/completions",
                json=dict(body, max_tokens=4, stream=True), headers=rt,
            )
            async for _ in r.content:
                break  # first SSE byte = first token out
            await r.read()
            return time.monotonic() - t0

        # compiles queued during the flood fire at its end (idle gate) —
        # wait them out; two discard probes warm the streaming path; a
        # full collect keeps the flood's garbage from pausing the probes
        import gc

        await settle_compiles()
        for _ in range(2):
            await probe_ttft()
        gc.collect()
        unloaded = []
        for _ in range(20):
            unloaded.append(await probe_ttft())
            await asyncio.sleep(0.1)  # engine goes idle between arrivals
        unloaded.sort()

        # 3) weighted 3:1 flood. Probes ride the flood FIRST — back to back
        # with the unloaded baseline, so box-level noise can't drift
        # between the two sides of the TTFT ratio — then share +
        # throughput are measured in a clean probe-free window (a
        # preempting probe perturbs both)
        heavy = stamps("heavy", "batch", 3)
        light = stamps("light", "batch", 1)
        stop_evt, flood_t = start_flood([heavy, light])
        await asyncio.sleep(RAMP_S)
        gc.collect()
        loaded = []
        t_end = time.monotonic() + FLOOD_S / 2
        while time.monotonic() < t_end:
            loaded.append(await probe_ttft())
            await asyncio.sleep(0.25)
        loaded.sort()
        await asyncio.sleep(1.0)  # probe preemption recompute settles
        t0, before = time.monotonic(), tenant_tokens()
        await asyncio.sleep(FLOOD_S)
        after, on_s = tenant_tokens(), time.monotonic() - t0
        stop_evt.set()
        flood_t.join(timeout=10)

        h_tok = after.get("heavy", 0) - before.get("heavy", 0)
        l_tok = after.get("light", 0) - before.get("light", 0)
        qos_on_tps = round((h_tok + l_tok) / on_s, 1)
        share = h_tok / max(1, h_tok + l_tok)

        def p50(lat):
            return round(lat[len(lat) // 2] * 1e3, 2) if lat else None

        return {
            "weights": "heavy=3 light=1 (both batch), probe realtime",
            "flood_clients_per_tenant": N_CLIENTS,
            "seats": 4,
            "heavy_tokens": h_tok,
            "light_tokens": l_tok,
            "heavy_share": round(share, 3),
            "target_share": 0.75,
            "share_within_10pct": bool(abs(share - 0.75) <= 0.10),
            "probe_ttft_unloaded_p50_ms": p50(unloaded),
            "probe_ttft_loaded_p50_ms": p50(loaded),
            "probe_ttft_ratio": (
                round(p50(loaded) / p50(unloaded), 2)
                if unloaded and loaded else None
            ),
            "probes": len(loaded),
            "qos_off_tokens_s": qos_off_tps,
            "qos_on_tokens_s": qos_on_tps,
            "qos_overhead_frac": (
                round(1.0 - qos_on_tps / qos_off_tps, 3)
                if qos_off_tps else None
            ),
        }
    finally:
        await client.close()
        engine.runner.shutdown(wait=True)


def _saturation_bench() -> dict:
    """Saturation & goodput telemetry proof (docs/29-saturation-slo.md),
    CPU-only (needs no chip):

    - **ledger exactness** — a flood engineered to hit every waste path
      (pipeline rollbacks via mid-window stops, pool-pressure preemptions,
      deadline expiry, QoS shed eviction, mid-flight aborts) must leave
      the goodput ledger balanced EXACTLY: delivered + wasted == sampled
      at quiescence, with every event class actually exercised.
    - **metering overhead** — the same decode wave on two engines, step
      metering off vs on, alternating reps: the meter's cost must be a
      measured number (bar: ≤ ~2% p50 wave latency), not an assertion.
    """
    import time as _t
    from dataclasses import replace

    import numpy as np

    from vllm_production_stack_tpu.engine.config import EngineConfig
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.request import SamplingParams
    from vllm_production_stack_tpu.qos import TenantContext

    # -- part 1: ledger exactness under a mixed-fate flood -----------------
    cfg = EngineConfig.tiny()
    cfg = cfg.replace(
        cache=replace(cfg.cache, num_blocks=16),  # tight pool: preemptions
        scheduler=replace(
            cfg.scheduler, max_num_seqs=4, decode_buckets=(4,),
            prefill_buckets=(16, 32, 64), max_num_batched_tokens=64,
            decode_window=4, max_waiting_requests=8,
        ),
    )
    eng = LLMEngine(cfg)
    rng = np.random.RandomState(7)
    vocab = cfg.model.vocab_size
    counts = {"submitted": 0, "deadline_armed": 0, "aborted": 0,
              "shed_marked": 0}
    for wave in range(8):
        rids = []
        for i in range(10):
            kind = (wave + i) % 4
            sampling = SamplingParams(
                max_tokens=int(rng.randint(3, 24)), temperature=0.0,
                ignore_eos=True,
            )
            deadline = None
            tenant = None
            if kind == 1:
                # expires while queued or mid-decode
                deadline = _t.monotonic() + float(rng.uniform(0.01, 0.08))
                counts["deadline_armed"] += 1
            elif kind == 2:
                # batch-class long decodes: the preemption/eviction victim
                # pool (also latches the QoS paths)
                tenant = TenantContext(
                    tenant_id="batch", priority=2, weight=1.0
                )
                sampling = SamplingParams(
                    max_tokens=32, temperature=0.0, ignore_eos=True
                )
            elif kind == 3:
                # stop on a spread of ids: greedy tokens from random
                # weights hit one mid-window, forcing overshoot discards
                # and pipeline rollbacks at the finish
                sampling = SamplingParams(
                    max_tokens=24, temperature=0.0,
                    stop_token_ids=tuple(
                        int(t) for t in rng.randint(1, vocab, size=48)
                    ),
                )
            prompt = [int(t) for t in
                      rng.randint(1, vocab, size=int(rng.randint(4, 24)))]
            rids.append(eng.add_request(
                prompt_token_ids=prompt, sampling=sampling,
                deadline=deadline, tenant=tenant,
            ))
            counts["submitted"] += 1
        steps = 0
        rt_sent = False
        while eng.has_unfinished() and steps < 400:
            eng.step()
            steps += 1
            if steps == 4 and len(rids) > 5:
                # severed mid-flight (client disconnect shape): a request
                # deep enough in the wave to be running or queued, not the
                # newest (the shed victim below targets that end)
                if eng.abort_request(rids[5]):
                    counts["aborted"] += 1
            if steps == 6 and not rt_sent:
                # realtime arrival with seats full of batch-class decodes:
                # priority seat preemption (the preempted victim keeps its
                # pending tokens — fate settles at its eventual finish)
                rt_sent = True
                eng.add_request(
                    prompt_token_ids=[int(t) for t in
                                      rng.randint(1, vocab, size=6)],
                    sampling=SamplingParams(
                        max_tokens=6, temperature=0.0, ignore_eos=True
                    ),
                    tenant=TenantContext(
                        tenant_id="rt", priority=0, weight=1.0
                    ),
                )
                counts["submitted"] += 1
            if steps == 10 and eng.scheduler.mark_shed_victim(0):
                # a realtime-rank arrival claims a lower-priority victim
                # (the admission gate's evict path) — after the preemption
                # above, the newest waiting victim may carry pending
                # tokens, exercising wasted{shed_evicted}
                counts["shed_marked"] += 1
    # bounded drain: a wedged regression must still report the ledger
    # diagnostic (unbalanced + pending) instead of eating the phase timeout
    drain_steps = 0
    while eng.has_unfinished() and drain_steps < 2000:
        eng.step()
        drain_steps += 1
    balance = eng.goodput_balance()
    events = {
        "rollbacks": int(eng.timing["rollback_n"]),
        "preemptions": eng.scheduler.total_preemptions,
        "deadline_expired": eng.scheduler.deadline_expired_total,
        "shed_evictions": eng.scheduler.shed_evictions,
        **counts,
    }
    sat = eng.stats().saturation
    eng.runner.shutdown(wait=True)

    # -- part 2: metering overhead (off vs on, alternating reps) -----------
    cfg2 = EngineConfig.tiny()
    cfg2 = cfg2.replace(
        scheduler=replace(
            cfg2.scheduler, max_num_seqs=8, decode_buckets=(8,),
            prefill_buckets=(16, 32, 64), max_num_batched_tokens=64,
            decode_window=4,
        ),
    )
    engines = {
        mode: LLMEngine(cfg2.replace(step_metering=mode))
        for mode in (False, True)
    }
    prompts = [
        [int(t) for t in rng.randint(1, vocab, size=16)] for _ in range(8)
    ]
    wave_sampling = SamplingParams(
        max_tokens=32, temperature=0.0, ignore_eos=True
    )
    for e in engines.values():  # pay every XLA compile before measuring
        e.generate(prompts, wave_sampling)
        e.generate(prompts, wave_sampling)
    REPS = 12
    times: dict[bool, list[float]] = {False: [], True: []}
    for _ in range(REPS):
        for mode in (False, True):
            t0 = time.perf_counter()
            outs = engines[mode].generate(prompts, wave_sampling)
            times[mode].append(time.perf_counter() - t0)
            assert sum(len(o["token_ids"]) for o in outs) == 8 * 32
    for e in engines.values():
        e.runner.shutdown(wait=True)

    def p50(xs):
        return sorted(xs)[len(xs) // 2]

    off_p50, on_p50 = p50(times[False]), p50(times[True])
    gen_tokens = 8 * 32
    return {
        "ledger": balance,
        "balanced": bool(balance["balanced"]),
        "events": events,
        "all_waste_paths_hit": all(
            balance["wasted"].get(r, 0) > 0
            for r in ("rollback", "preempted_recompute", "deadline_expired",
                      "severed", "shed_evicted", "overshoot")
        ),
        "meter_snapshot": {
            k: sat.get(k)
            for k in ("decode_seat_occupancy", "padding_waste_frac",
                      "achieved_flops_per_s", "mfu")
        },
        "metering": {
            "reps": REPS,
            "off_p50_ms": round(off_p50 * 1e3, 2),
            "on_p50_ms": round(on_p50 * 1e3, 2),
            "off_tok_s": round(gen_tokens / off_p50, 1),
            "on_tok_s": round(gen_tokens / on_p50, 1),
            "p50_overhead_pct": round((on_p50 / off_p50 - 1.0) * 100.0, 2),
            "min_overhead_pct": round(
                (min(times[True]) / min(times[False]) - 1.0) * 100.0, 2
            ),
        },
    }


def _kvflow_bench() -> dict:
    """KV-hierarchy flow telemetry proof (docs/30-kv-flow-telemetry.md),
    CPU-only (needs no chip):

    - **attribution exactness** — a mixed warm/cold/remote-resident prompt
      flood across two engines sharing one remote store must leave the
      hydration partition EXACT: hbm_hit + host_reload + disk_load +
      remote_fetch + recomputed == prompt tokens, with every source class
      actually exercised.
    - **bandwidth honesty** — for every (tier, direction) that moved
      blocks, the meter's bandwidth estimate must sit within 20% of
      hand-computed bytes/elapsed, where bytes are derived independently
      from the model's analytic per-block KV size (kv_block_bytes), not
      read back from the meter.
    - **metering overhead** — the same reload-heavy wave on two engines,
      --kv-flow-metering off vs on, alternating reps: the meters' cost
      must be a measured number (bar: ≤ ~2% p50), not an assertion.
    """
    import tempfile
    import time as _t
    from dataclasses import replace

    import numpy as np

    from vllm_production_stack_tpu.engine.config import EngineConfig
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.memory import kv_block_bytes
    from vllm_production_stack_tpu.engine.request import SamplingParams
    from vllm_production_stack_tpu.kvstore.server import run_in_thread

    BS = 8
    rng = np.random.RandomState(11)
    url, stop_store, _server = run_in_thread(capacity_bytes=1 << 26)
    tmp = tempfile.mkdtemp(prefix="bench-kvflow-")

    def make_engine(disk_dir: str, host_blocks=10, metering=True):
        cfg = EngineConfig.tiny()
        return LLMEngine(cfg.replace(
            cache=replace(
                cfg.cache, block_size=BS, num_blocks=14,  # tight: evicts
                num_host_blocks=host_blocks, disk_kv_dir=disk_dir,
                disk_kv_gib=0.05, remote_kv_url=url,
            ),
            scheduler=replace(
                cfg.scheduler, max_num_seqs=2, max_num_batched_tokens=64,
                decode_buckets=(2,), prefill_buckets=(32, 64),
                decode_window=4,
            ),
            kv_flow_metering=metering,
        ))

    vocab = 512
    GREEDY = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)

    def prompt(seed, n=4 * BS):
        return [int(t) for t in
                np.random.RandomState(seed).randint(1, vocab, size=n)]

    # -- part 1: attribution exactness across a warm/cold/remote mix -------
    eng_a = make_engine(f"{tmp}/a")
    warm = [prompt(s) for s in range(6)]
    for p in warm:  # seed pass: all recomputed; churn evicts older ones
        eng_a.generate([p], GREEDY)
    # re-issue NEWEST-first: the last-seeded prompt's blocks are still
    # HBM-resident (hbm_hit), older ones were pushed down the hierarchy
    # (host ring → disk) by the churn, plus fresh cold prompts to keep
    # recomputed > 0
    for p in list(reversed(warm)) + [prompt(100 + s) for s in range(2)]:
        eng_a.generate([p], GREEDY)
    eng_a.host_tier.flush()  # resolve pending offloads → remote writes
    assert eng_a.remote_tier.drain(timeout=30), "remote store drain hung"

    # engine B: same weights fingerprint (same config+seed), FRESH local
    # tiers — warm prompts can only come from the remote store
    eng_b = make_engine(f"{tmp}/b")
    for p in warm[:3]:
        eng_b.generate([p], GREEDY)

    def attribution(eng):
        snap = eng.flow.snapshot()
        hyd = dict(snap["hydration"])
        hyd["total"] = sum(hyd.values())
        return hyd, snap

    hyd_a, snap_a = attribution(eng_a)
    hyd_b, snap_b = attribution(eng_b)
    exact_a = hyd_a["total"] == eng_a._prompt_tokens
    exact_b = hyd_b["total"] == eng_b._prompt_tokens
    sources_hit = {
        "hbm_hit": hyd_a["hbm_hit"] > 0,
        "host_reload": hyd_a["host_reload"] > 0,
        "disk_load": hyd_a["disk_load"] > 0,
        "remote_fetch": hyd_b["remote_fetch"] > 0,
        "recomputed": hyd_a["recomputed"] > 0,
    }

    # -- bandwidth honesty: meter estimate vs analytic bytes / elapsed -----
    # per-block KV bytes from the model config alone (the disk tier adds a
    # ~100 B frame header per block — inside the 20% tolerance)
    blk_bytes = kv_block_bytes(
        eng_a.config.model, BS, 1, 1,
        kv_dtype=eng_a.config.cache.resolved_kv_dtype(
            eng_a.config.model.dtype
        ),
    )
    bandwidth: dict[str, dict] = {}
    bw_ok = True
    for eng, tag in ((eng_a, "a"), (eng_b, "b")):
        snap = eng.flow.snapshot()
        for key, blocks in snap["blocks"].items():
            if blocks <= 0:
                continue
            secs = snap["seconds_hist"][key]["sum"]
            hand = blocks * blk_bytes / secs if secs > 0 else 0.0
            meter = snap["bandwidth_bytes_per_s"][key]
            rel = abs(meter - hand) / hand if hand > 0 else 1.0
            bandwidth[f"{tag}:{key}"] = {
                "blocks": blocks,
                "meter_bytes": snap["bytes"][key],
                "hand_bytes": blocks * blk_bytes,
                "elapsed_s": round(secs, 6),
                "meter_bytes_per_s": round(meter, 1),
                "hand_bytes_per_s": round(hand, 1),
                "rel_err": round(rel, 4),
            }
            if rel > 0.20:
                bw_ok = False
    signal = eng_a.hydration_signal()
    eng_a.runner.shutdown(wait=True)
    eng_b.runner.shutdown(wait=True)

    # -- part 2: metering overhead (off vs on, alternating reps) -----------
    # a reload-heavy wave: the working set exceeds HBM, so every wave
    # exercises the metered offload/reload paths, not just decode
    engines = {
        mode: make_engine(f"{tmp}/ovh-{mode}", metering=mode)
        for mode in (False, True)
    }
    ovh_prompts = [prompt(300 + s) for s in range(5)]
    for e in engines.values():  # pay XLA compiles + first-touch paths
        for p in ovh_prompts:
            e.generate([p], GREEDY)
        for p in ovh_prompts:
            e.generate([p], GREEDY)
    REPS = 14
    times: dict[bool, list[float]] = {False: [], True: []}
    for rep in range(REPS):
        # alternate which mode runs first each rep so slow clock/cache
        # drift cancels instead of always taxing the second mode
        order = (False, True) if rep % 2 == 0 else (True, False)
        for mode in order:
            t0 = _t.perf_counter()
            for p in ovh_prompts:
                engines[mode].generate([p], GREEDY)
            times[mode].append(_t.perf_counter() - t0)
    for e in engines.values():
        e.runner.shutdown(wait=True)
    stop_store()

    def p50(xs):
        return sorted(xs)[len(xs) // 2]

    off_p50, on_p50 = p50(times[False]), p50(times[True])
    return {
        "attribution": {
            "engine_a": hyd_a,
            "engine_b": hyd_b,
            "prompt_tokens_a": eng_a._prompt_tokens,
            "prompt_tokens_b": eng_b._prompt_tokens,
            "exact": bool(exact_a and exact_b),
            "sources_hit": sources_hit,
            "all_sources_hit": all(sources_hit.values()),
        },
        "bandwidth": bandwidth,
        "bandwidth_within_20pct": bool(bw_ok),
        "hydration_signal": {
            k: signal[k]
            for k in ("fetch_bandwidth_bytes_per_s", "prefill_flops_per_s",
                      "flops_per_token", "block_bytes")
        },
        "metering": {
            "reps": REPS,
            "off_p50_ms": round(off_p50 * 1e3, 2),
            "on_p50_ms": round(on_p50 * 1e3, 2),
            "p50_overhead_pct": round((on_p50 / off_p50 - 1.0) * 100.0, 2),
            "min_overhead_pct": round(
                (min(times[True]) / min(times[False]) - 1.0) * 100.0, 2
            ),
        },
    }


def _hydration_bench() -> dict:
    """Compute-or-load hydration planner proof (docs/31-hydration-
    planner.md), CPU-only (needs no chip). The
    acceptance shape: cold-prefix 8k-token prompts resident on
    disk/remote (seeded by one engine, measured on fresh engines with
    the same weights fingerprint), TTFT for

    - **compute-only** (`--kv-hydration off`): full prefill, the
      lower-tier residency ignored;
    - **load-only** (`--kv-hydration sync`): the legacy blocking
      whole-prefix reload;
    - **planner** (`--kv-hydration auto`): chunked tier fetches
      pipelined with partial recompute, split chosen from MEASURED
      bandwidth vs MEASURED prefill FLOP/s.

    Remote scenario (the headline): the fetch link is throttled (a
    sleep proportional to payload bytes at the connection layer —
    INSIDE the flow meter's timing window, so the planner's bandwidth
    estimate sees the throttled truth) to the crossover point where
    fetch-everything ~= compute-everything — exactly where
    all-or-nothing policies are worst and the planner's max(fetch tail,
    compute tail) pays off. Disk scenario: the same arms against the
    local NVMe tier, unthrottled — disk is fast here, so the planner's
    job is to match load-only (reported with a 5% noise tolerance
    rather than asserted strictly). The planner engines warm honestly:
    one compute pass (FLOP/s estimate + XLA compiles — the width floor
    makes the program keys context-independent, so a short junk prompt
    warms the 8k shapes) and sync-fallback reloads of smaller resident
    prompts (the bandwidth samples that cross the TierBandwidth floor).
    The per-request hydration partition must stay EXACT on every engine
    with the planner on."""
    import time as _t
    from dataclasses import replace

    import numpy as np

    from vllm_production_stack_tpu.engine.config import EngineConfig
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.request import SamplingParams
    from vllm_production_stack_tpu.kvstore.server import run_in_thread

    import tempfile

    BS = 16
    PROMPT_TOKENS = 8192
    WARM_TOKENS = 2048
    url, stop_store, _server = run_in_thread(capacity_bytes=1 << 30)
    disk_dir = tempfile.mkdtemp(prefix="bench-hydration-")

    def make_engine(mode: str, remote: str = "", disk: str = "",
                    num_blocks: int = 560) -> LLMEngine:
        cfg = EngineConfig.tiny(max_model_len=PROMPT_TOKENS + 256)
        return LLMEngine(cfg.replace(
            cache=replace(
                cfg.cache, block_size=BS, num_blocks=num_blocks,
                num_host_blocks=16, remote_kv_url=remote,
                disk_kv_dir=disk, disk_kv_gib=1.0 if disk else 0.0,
            ),
            scheduler=replace(
                cfg.scheduler, max_num_seqs=2,
                max_num_batched_tokens=512, decode_buckets=(2,),
                prefill_buckets=(64, 512), decode_window=4,
                # ONE block-table width program: the phase measures
                # hydration, not the width compile ladder — and it makes
                # a short warmup prompt compile the 8k prompt's programs
                width_floor_blocks=600,
            ),
            kv_hydration=mode,
            kv_hydration_chunk_blocks=16,
        ))

    def prompt(seed: int, n: int) -> list[int]:
        return [int(t) for t in
                np.random.RandomState(seed).randint(1, 500, size=n)]

    GREEDY = SamplingParams(max_tokens=2, temperature=0.0, ignore_eos=True)
    target = prompt(1, PROMPT_TOKENS)
    warms = [prompt(10 + i, WARM_TOKENS) for i in range(2)]
    junk_small = prompt(98, 1024)  # compile warmup (width floor: same keys)
    junk_big = prompt(99, PROMPT_TOKENS)  # seeding churn only

    # -- seed BOTH lower tiers: engine A computes everything; churn pushes
    # every block through the ring, whose evictions persist to disk AND
    # write through to the remote store
    eng_a = make_engine("sync", remote=url, disk=disk_dir)
    ref_tokens = eng_a.generate([target], GREEDY)[0]["token_ids"]
    for w in warms:
        eng_a.generate([w], GREEDY)
    eng_a.generate([junk_big], GREEDY)  # evicts target+warm blocks
    eng_a.host_tier.flush()
    assert eng_a.remote_tier.drain(timeout=120), "remote store drain hung"
    seeded_remote = eng_a.remote_tier.stats.stores
    seeded_disk = eng_a.host_tier.disk.stats.stores
    eng_a.runner.shutdown(wait=True)

    def first_token_latency(eng: LLMEngine, ids: list[int]):
        t0 = _t.perf_counter()
        rid = eng.add_request(prompt_token_ids=ids, sampling=GREEDY)
        ttft = None
        tok = None
        while eng.has_unfinished():
            for out in eng.step():
                if out.request_id == rid and out.new_token_ids and ttft is None:
                    ttft = _t.perf_counter() - t0
                    tok = out.new_token_ids[0]
        return ttft, tok

    def throttle_store(bytes_per_s: float) -> None:
        """Slow every remote fetch connection (shared AND the hydrator's
        dedicated one) to `bytes_per_s` — the sleep happens inside
        fetch_run's metering window, so TierBandwidth measures the
        throttled link, exactly what a WAN-attached store looks like.
        Disk IO is untouched (the sleep keys on the /v1/mget path)."""
        from vllm_production_stack_tpu.kvstore import client as kvclient

        inner = kvclient._Conn.request

        def slowed(self, method, path, body=None, headers=None):
            status, hdrs, payload = inner(
                self, method, path, body=body, headers=headers
            )
            if path == "/v1/mget":
                _t.sleep(len(payload) / bytes_per_s)
            return status, hdrs, payload

        kvclient._Conn.request = slowed

    def run_arm(mode: str, remote: str = "", disk: str = "",
                warm_prompts=()):  # -> (ttft, first_token, details)
        eng = make_engine(mode, remote=remote, disk=disk)
        eng.generate([junk_small], GREEDY)  # XLA compiles + FLOP/s sample
        for w in warm_prompts:  # sync-fallback loads: bandwidth samples
            eng.generate([w], GREEDY)
        sig = eng.hydration_signal()
        ttft, tok = first_token_latency(eng, target)
        snap = eng.flow.snapshot()
        hyd = snap["hydration"]
        details = {
            "ttft_s": round(ttft, 3),
            "decisions": dict(snap["decisions"]),
            "partition_exact": sum(hyd.values()) == eng._prompt_tokens,
            "measured_before_run": dict(sig["fetch_bandwidth_measured"]),
        }
        eng.runner.shutdown(wait=True)
        return ttft, tok, details

    # -- remote scenario (throttled to the crossover) ----------------------
    ttft_c, tok_c, det_c = run_arm("off", remote=url)
    region_blocks = PROMPT_TOKENS // BS - 1
    from vllm_production_stack_tpu.engine.memory import kv_block_bytes

    tiny = EngineConfig.tiny(max_model_len=PROMPT_TOKENS + 256)
    blk_bytes = kv_block_bytes(
        tiny.model, BS, 1, 1,
        kv_dtype=tiny.cache.resolved_kv_dtype(tiny.model.dtype),
    )
    region_bytes = region_blocks * (blk_bytes + 160)  # + frame header
    bw = region_bytes / max(ttft_c, 0.05)
    throttle_store(bw)
    ttft_l, tok_l, det_l = run_arm("sync", remote=url)
    ttft_p, tok_p, det_p = run_arm("auto", remote=url, warm_prompts=warms)
    remote = {
        "compute_only": det_c,
        "load_only": det_l,
        "planner": det_p,
        "throttle_bytes_per_s": round(bw, 1),
        "tokens_agree": tok_c == tok_l == tok_p,
        "planner_ttft_le_min": bool(ttft_p <= min(ttft_c, ttft_l)),
        "speedup_vs_best_baseline": round(min(ttft_c, ttft_l) / ttft_p, 3),
    }

    # -- disk scenario (local NVMe, unthrottled: the planner should LOAD
    # nearly everything and match load-only; 5% noise tolerance) -----------
    d_ttft_l, d_tok_l, d_det_l = run_arm("sync", disk=disk_dir)
    d_ttft_p, d_tok_p, d_det_p = run_arm(
        "auto", disk=disk_dir, warm_prompts=warms[:1]
    )
    stop_store()
    disk = {
        "compute_only_ttft_s": det_c["ttft_s"],  # compute is tier-blind
        "load_only": d_det_l,
        "planner": d_det_p,
        "tokens_agree": d_tok_l == d_tok_p == tok_c,
        "planner_ttft_le_min_5pct": bool(
            d_ttft_p <= min(ttft_c, d_ttft_l) * 1.05
        ),
    }

    return {
        "workload": {
            "prompt_tokens": PROMPT_TOKENS,
            "block_size": BS,
            "seeded_remote_blocks": seeded_remote,
            "seeded_disk_blocks": seeded_disk,
        },
        "remote": remote,
        "disk": disk,
        "planner_ttft_le_min": remote["planner_ttft_le_min"],
        "speedup_vs_best_baseline": remote["speedup_vs_best_baseline"],
    }


def _kvquant_bench() -> dict:
    """At-rest KV quantization proof (docs/38-kv-quantization.md),
    CPU-only (needs no chip). Two arms, identical
    except for ``--kv-at-rest-codec``: a pool-precision-at-rest baseline
    and int4+per-group-scales. Each arm seeds a shared remote kvstore
    with a cold 4k-token prefix under its OWN codec fingerprint (the
    mixed-fleet namespace rule — the arms can share one store because
    they can never adopt each other's bytes), then a FRESH engine per
    arm reloads the prefix over a bandwidth-throttled link with sync
    hydration (the blocking whole-prefix reload: TTFT ~ wire bytes /
    link bandwidth, no planner cleverness to confound the codec's
    contribution — the throttle sleeps on the WIRE payload, so smaller
    frames are faster automatically, exactly like a real WAN link).

    Acceptance shape: the int4 arm moves >=3.5x fewer wire bytes per
    KVFlowMeter (the logical/wire quotient is the
    tpu:kv_tier_compression_ratio gauge), beats the baseline's TTFT,
    keeps the per-request hydration partition EXACT, and greedy decode
    tokens agree with the compute-from-scratch reference (int4's
    per-element dequant error is bounded by scale/2 — docs/38 — far
    below the argmax margin)."""
    import time as _t
    from dataclasses import replace

    import numpy as np

    from vllm_production_stack_tpu.engine.config import EngineConfig
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.request import SamplingParams
    from vllm_production_stack_tpu.kvstore.server import run_in_thread

    BS = 16
    PROMPT_TOKENS = 4096
    url, stop_store, _server = run_in_thread(capacity_bytes=1 << 30)

    def make_engine(codec: str) -> LLMEngine:
        cfg = EngineConfig.tiny(max_model_len=PROMPT_TOKENS + 256)
        return LLMEngine(cfg.replace(
            cache=replace(
                cfg.cache, block_size=BS, num_blocks=352,
                num_host_blocks=16, remote_kv_url=url,
                kv_at_rest_codec=codec,
            ),
            scheduler=replace(
                cfg.scheduler, max_num_seqs=2,
                max_num_batched_tokens=512, decode_buckets=(2,),
                prefill_buckets=(64, 512), decode_window=4,
                # ONE block-table width program (hydration-bench idiom):
                # the phase measures the codec, not the compile ladder
                width_floor_blocks=300,
            ),
            kv_hydration="sync",
        ))

    def prompt(seed: int, n: int) -> list[int]:
        return [int(t) for t in
                np.random.RandomState(seed).randint(1, 500, size=n)]

    GREEDY = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
    target = prompt(1, PROMPT_TOKENS)
    junk_small = prompt(98, 1024)  # compile warmup (width floor: same keys)
    # churn: enough distinct blocks that EVERY target block is evicted
    # through the ring (pool 320 blocks; target 256 + churn 512 >> 320)
    churn = [prompt(99 + i, PROMPT_TOKENS) for i in range(2)]

    def seed_store(codec: str):
        """Engine A computes the target from scratch (the greedy token
        reference) and churns junk through the pool so ALL the target's
        blocks spill through the ring, whose evictions write through to
        the remote store under `codec`'s at-rest form + fingerprint."""
        eng = make_engine(codec)
        ref = eng.generate([target], GREEDY)[0]["token_ids"]
        for c in churn:
            eng.generate([c], GREEDY)
        eng.host_tier.flush()
        assert eng.remote_tier.drain(timeout=120), "remote store drain hung"
        stores = eng.remote_tier.stats.stores
        eng.runner.shutdown(wait=True)
        return ref, stores

    ref_base, seeded_base = seed_store("none")
    ref_int4, seeded_int4 = seed_store("int4")

    # throttle AFTER seeding: every /v1/mget connection (fetch side only)
    # sleeps proportional to its WIRE payload — the link the baseline arm
    # crawls over is byte-for-byte the link the int4 arm flies over
    region_blocks = PROMPT_TOKENS // BS - 1
    from vllm_production_stack_tpu.engine.memory import kv_block_bytes

    tiny = EngineConfig.tiny(max_model_len=PROMPT_TOKENS + 256)
    pool_dtype = tiny.cache.resolved_kv_dtype(tiny.model.dtype)
    blk_bytes = kv_block_bytes(tiny.model, BS, 1, 1, kv_dtype=pool_dtype)
    region_bytes = region_blocks * (blk_bytes + 160)  # + frame header
    bw = region_bytes / 4.0  # baseline reload ~4s of pure link time

    from vllm_production_stack_tpu.kvstore import client as kvclient

    inner = kvclient._Conn.request

    def slowed(self, method, path, body=None, headers=None):
        status, hdrs, payload = inner(
            self, method, path, body=body, headers=headers
        )
        if path == "/v1/mget":
            _t.sleep(len(payload) / bw)
        return status, hdrs, payload

    kvclient._Conn.request = slowed

    def run_arm(codec: str) -> dict:
        eng = make_engine(codec)
        eng.generate([junk_small], GREEDY)  # XLA compiles (not resident)
        t0 = _t.perf_counter()
        rid = eng.add_request(prompt_token_ids=target, sampling=GREEDY)
        ttft = None
        tokens: list[int] = []
        while eng.has_unfinished():
            for out in eng.step():
                if out.request_id != rid:
                    continue
                if out.new_token_ids and ttft is None:
                    ttft = _t.perf_counter() - t0
                tokens.extend(out.new_token_ids)
        snap = eng.flow.snapshot()
        hyd = snap["hydration"]
        details = {
            "ttft_s": round(ttft, 3),
            "tokens": tokens,
            "wire_bytes_in": snap["bytes"]["remote/in"],
            "logical_bytes_in": snap["logical_bytes"]["remote/in"],
            "compression_ratio": round(
                snap["compression_ratio"]["remote/in"], 3
            ),
            "remote_fetch_tokens": hyd["remote_fetch"],
            "partition_exact": sum(hyd.values()) == eng._prompt_tokens,
        }
        eng.runner.shutdown(wait=True)
        return details

    base = run_arm("none")
    quant = run_arm("int4")
    stop_store()

    reduction = base["wire_bytes_in"] / max(quant["wire_bytes_in"], 1)
    return {
        "workload": {
            "prompt_tokens": PROMPT_TOKENS,
            "block_size": BS,
            "pool_dtype": str(pool_dtype),
            "seeded_blocks": {"base": seeded_base, "int4": seeded_int4},
            "throttle_bytes_per_s": round(bw, 1),
        },
        "base_at_rest": base,
        "int4_at_rest": quant,
        "wire_reduction_x": round(reduction, 3),
        "wire_reduction_ge_3p5": bool(reduction >= 3.5),
        "int4_beats_base_ttft": bool(quant["ttft_s"] < base["ttft_s"]),
        "ttft_speedup": round(base["ttft_s"] / max(quant["ttft_s"], 1e-9), 3),
        "partition_exact_all": bool(
            base["partition_exact"] and quant["partition_exact"]
        ),
        "tokens_identical": bool(
            ref_base == ref_int4 == base["tokens"] == quant["tokens"]
        ),
    }


async def _fleet_bench() -> dict:
    """Fleet-coherence telemetry baselines (docs/32-fleet-telemetry.md),
    CPU-only pre-preflight: M=3 REAL router apps × N=4 fake engines, the
    three numbers ROADMAP 1's multi-replica refactor must beat, measured
    through real wire traffic:

    1. **convergence**: a 10k-event KV storm pumped (with real publish
       timestamps) through POST /kv/events into each replica's embedded
       index + the controller — publish→apply lag p50/p95 per replica,
       plus the replica-restart arc: a cold replica's divergence on
       GET /fleet rises to the full slice, then heals to 0 after resync.
    2. **stickiness**: session flood spread across 3 routers with
       IDENTICAL ring membership → violation rate must be 0; the same
       flood with one router's membership forcibly skewed (a phantom
       backend the others don't list) → violations > 0 (detection proven,
       ring divergence flagged on /fleet).
    3. **tenant accounting**: a 3-replica flood against a 20 req/s tenant
       budget — each replica's local bucket admits the full budget, so the
       controller's fleet rollup must measure utilization ≈ 3× and
       over-admission ≈ 2; the single-router baseline measures ≈ 1× / ≈ 0.
    """
    import asyncio
    import socket

    import numpy as np
    from aiohttp import web

    import aiohttp
    from vllm_production_stack_tpu.engine.kv_cache import KVBlockPool
    from vllm_production_stack_tpu.engine.kv_controller import KVController
    from vllm_production_stack_tpu.fleet import SessionStickinessAudit
    from vllm_production_stack_tpu.qos import TenantTable
    from vllm_production_stack_tpu.router.app import build_app
    from vllm_production_stack_tpu.router.args import parse_args

    N_ENGINES, BLOCK = 4, 16
    N_REPLICAS = 3
    STORM_EVENTS = 10_000
    STORM_BATCH = 512

    runners: list[web.AppRunner] = []

    async def serve(app) -> tuple[web.AppRunner, str]:
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        runners.append(runner)
        return runner, f"http://127.0.0.1:{runner.addresses[0][1]}"

    # -- fake engines: real HTTP servers that answer /v1/completions and
    # feed the REAL engine-side stickiness audit from the router stamps
    audits: list[SessionStickinessAudit] = []
    engine_urls: list[str] = []

    def engine_app(audit_holder: list) -> web.Application:
        async def completions(request):
            audit_holder[0].observe_headers(request.headers)
            return web.json_response({
                "id": "cmpl-fleet", "object": "text_completion",
                "choices": [{"index": 0, "text": "ok",
                             "finish_reason": "stop"}],
            })

        app = web.Application()
        app.router.add_post("/v1/completions", completions)
        return app

    tenants_yaml = {
        "acme": {"api_key": "k-acme", "requests_per_s": 20.0},
    }
    import tempfile

    import yaml as _yaml

    tenant_file = tempfile.NamedTemporaryFile(
        "w", suffix=".yaml", delete=False
    )
    _yaml.safe_dump(tenants_yaml, tenant_file)
    tenant_file.close()

    try:
        for _ in range(N_ENGINES):
            holder = [None]
            _, url = await serve(engine_app(holder))
            holder[0] = SessionStickinessAudit(self_url=url)
            audits.append(holder[0])
            engine_urls.append(url)

        controller = KVController(
            engine_urls, mode="indexed",
            tenant_table=TenantTable.from_dict(tenants_yaml),
        )
        controller.fleet.rate_window_s = 10.0
        _, controller_url = await serve(controller.build_app())

        def router_args(replica: str, backends: list[str], policy: str,
                        with_qos: bool = False):
            argv = [
                "--static-backends", ",".join(backends),
                # static model list: skips the one-shot /v1/models probe
                # (the fake engines only serve /v1/completions)
                "--static-models", ";".join(["tiny"] * len(backends)),
                "--router-replica-id", replica,
                "--fleet-report-url", controller_url,
                "--fleet-report-interval", "0.25",
                # this phase measures the UNSCALED baseline (the N-way
                # bucket-split over-admission PR 9 quantified); the
                # fleet_scale phase proves the budget-scaling fix
                "--fleet-budget-scaling", "off",
                "--breaker-failure-threshold", "0",
            ]
            if policy == "session":
                argv += ["--routing-logic", "session",
                         "--session-key", "x-user-id"]
            else:
                argv += ["--routing-logic", "kvaware",
                         "--kv-index-mode", "embedded",
                         "--kv-index-tokenizer", "byte"]
            if with_qos:
                argv += ["--tenant-table-file", tenant_file.name]
            return parse_args(argv)

        # ---------------- 1. convergence storm -------------------------
        pools = [KVBlockPool(4096, BLOCK) for _ in range(N_ENGINES)]
        replicas = []
        for i in range(N_REPLICAS):
            runner, url = await serve(build_app(router_args(
                f"replica-{i}", engine_urls, "kvaware"
            )))
            replicas.append((runner.app["state"], url))

        async with aiohttp.ClientSession() as sess:
            async def publish(url: str, payload: dict):
                async with sess.post(url + "/kv/events", json=payload) as r:
                    assert r.status == 200, await r.text()
                    return await r.json()

            subscriber_urls = [u for _, u in replicas] + [controller_url]
            # snapshot-first (empty pools), mirroring the publisher's
            # first-contact resync
            for i, pool in enumerate(pools):
                epoch, seq, hashes = pool.snapshot_events()
                for sub in subscriber_urls:
                    await publish(sub, {
                        "engine": engine_urls[i], "epoch": epoch,
                        "block_size": BLOCK, "snapshot": True, "seq": seq,
                        "hashes": [f"{h:x}" for h in hashes],
                        "ts": time.time(),
                    })
            # admit ~STORM_EVENTS blocks across the pools (each admission
            # emits one sequenced event with its emit wall-time)
            rng = np.random.RandomState(11)
            per_engine = STORM_EVENTS // N_ENGINES
            for pool in pools:
                parent = pool.root_hash()
                for _ in range(per_engine):
                    blk = pool.allocate()
                    assert blk is not None
                    parent = pool.register_full_block(
                        blk, parent,
                        tuple(int(t) for t in rng.randint(1, 30000, BLOCK)),
                    )
            # pump the storm: real drain_timed batches (publish ts = the
            # oldest event's emit time, so lag includes in-buffer dwell)
            # POSTed to every subscriber over real wire
            events_pumped = 0
            for i, pool in enumerate(pools):
                while True:
                    seq_start, events, oldest_ts = (
                        pool.events.drain_timed(STORM_BATCH)
                    )
                    if not events:
                        break
                    events_pumped += len(events)
                    for sub in subscriber_urls:
                        reply = await publish(sub, {
                            "engine": engine_urls[i],
                            "epoch": pool.events.epoch,
                            "block_size": BLOCK, "seq_start": seq_start,
                            "events": events, "ts": oldest_ts,
                        })
                        assert reply.get("status") == "ok", reply

            def lag_pcts(state) -> dict:
                lags = sorted(state.policy.index.convergence.drain())
                if not lags:
                    return {"p50_ms": None, "p95_ms": None, "batches": 0}
                pick = lambda p: round(  # noqa: E731
                    lags[min(len(lags) - 1, int(p * len(lags)))] * 1e3, 3
                )
                return {"p50_ms": pick(0.50), "p95_ms": pick(0.95),
                        "batches": len(lags)}

            convergence = {
                f"replica-{i}": lag_pcts(state)
                for i, (state, _) in enumerate(replicas)
            }

            # replica-restart arc: a COLD index (replica-3 boots fresh)
            # reports positions without the storm → /fleet divergence is
            # the full authoritative slice; a snapshot resync heals it
            cold_runner, cold_url = await serve(build_app(router_args(
                "replica-cold", engine_urls, "kvaware"
            )))
            cold_state = cold_runner.app["state"]
            await cold_state.fleet_reporter.report_once()
            async with sess.get(controller_url + "/fleet") as r:
                fleet_before = await r.json()
            div_before = {
                rep["replica"]: rep["divergence_blocks"]
                for rep in fleet_before["replicas"]
            }
            for i, pool in enumerate(pools):
                epoch, seq, hashes = pool.snapshot_events()
                await publish(cold_url, {
                    "engine": engine_urls[i], "epoch": epoch,
                    "block_size": BLOCK, "snapshot": True, "seq": seq,
                    "hashes": [f"{h:x}" for h in hashes],
                    "ts": time.time(),
                })
            await cold_state.fleet_reporter.report_once()
            async with sess.get(controller_url + "/fleet") as r:
                fleet_after = await r.json()
            div_after = {
                rep["replica"]: rep["divergence_blocks"]
                for rep in fleet_after["replicas"]
            }

            # ---------------- 2. stickiness audit ----------------------
            def reset_audits():
                for holder in audits:
                    holder.violations = {
                        k: 0 for k in holder.violations
                    }
                    holder._sessions.clear()
                    holder.observed = 0

            async def session_flood(router_urls: list[str],
                                    sessions: int = 48,
                                    rounds: int = 4) -> dict:
                reset_audits()
                n = 0
                for rnd in range(rounds):
                    tasks = []
                    for s in range(sessions):
                        url = router_urls[(s + rnd) % len(router_urls)]
                        tasks.append(sess.post(
                            url + "/v1/completions",
                            json={"model": "tiny", "prompt": "hello"},
                            headers={"x-user-id": f"sess-{s}"},
                        ))
                    for resp in await asyncio.gather(*tasks):
                        n += 1
                        await resp.read()
                violations = {}
                for holder in audits:
                    for k, v in holder.counts().items():
                        violations[k] = violations.get(k, 0) + v
                return {
                    "requests": n,
                    "violations": violations,
                    "violation_rate": round(
                        sum(violations.values()) / max(1, n), 4
                    ),
                }

            session_routers = []
            for i in range(N_REPLICAS):
                runner, url = await serve(build_app(router_args(
                    f"sess-{i}", engine_urls, "session"
                )))
                session_routers.append(url)
            sticky_identical = await session_flood(session_routers)

            # forced membership skew: one replica also lists a PHANTOM
            # backend (a closed port — connect refused, breakers off), the
            # ring-divergence scenario a stale discovery view produces.
            # Sessions the skewed ring maps to the phantom fail over and
            # arrive stamped owner=phantom → non_owner_delivery; sessions
            # re-ringed after the phantom's removal flip owners →
            # owner_changed.
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            phantom = f"http://127.0.0.1:{s.getsockname()[1]}"
            s.close()
            skew_runner, skew_url = await serve(build_app(router_args(
                "sess-skewed", engine_urls + [phantom], "session"
            )))
            sticky_skewed = await session_flood(
                session_routers[:2] + [skew_url]
            )
            # deterministic ring-divergence snapshot: after a failover the
            # skewed ring re-syncs to the shrunken candidate list (phantom
            # evicted), momentarily matching the healthy replicas' hash.
            # Route one session that maps to a LIVE engine last — its
            # first pick re-syncs the ring to the full 5-node membership
            # and no failover follows, so the report carries the skew.
            from vllm_production_stack_tpu.router.hashring import HashRing

            probe_ring = HashRing()
            for u in engine_urls + [phantom]:
                probe_ring.add_node(u)
            live_sid = next(
                f"probe-{i}" for i in range(1000)
                if probe_ring.get_node(f"probe-{i}") != phantom
            )
            r = await sess.post(
                skew_url + "/v1/completions",
                json={"model": "tiny", "prompt": "x"},
                headers={"x-user-id": live_sid},
            )
            await r.read()
            await skew_runner.app["state"].fleet_reporter.report_once()
            # the unskewed replicas report on their own 0.25s interval
            await asyncio.sleep(0.4)
            async with sess.get(controller_url + "/fleet") as r:
                ring_divergent = (await r.json())["ring_divergent"]

            # ---------------- 3. fleet tenant accounting ----------------
            async def tenant_flood(router_urls: list[str],
                                   window_s: float = 6.0,
                                   offered_rps: float = 40.0) -> dict:
                t_end = time.monotonic() + window_s
                admitted = throttled = 0
                interval = 1.0 / offered_rps

                async def client(url: str):
                    nonlocal admitted, throttled
                    while time.monotonic() < t_end:
                        t0 = time.monotonic()
                        async with sess.post(
                            url + "/v1/completions",
                            json={"model": "tiny", "prompt": "hi"},
                            headers={"Authorization": "Bearer k-acme"},
                        ) as r:
                            await r.read()
                            if r.status == 200:
                                admitted += 1
                            elif r.status == 429:
                                throttled += 1
                        dt = interval - (time.monotonic() - t0)
                        if dt > 0:
                            await asyncio.sleep(dt)

                # 2 clients per router × offered_rps pacing each ≈ well
                # over the 20 req/s budget per replica
                await asyncio.gather(*[
                    client(u) for u in router_urls for _ in range(2)
                ])
                return {
                    "admitted": admitted, "throttled": throttled,
                    "admitted_rps": round(admitted / window_s, 2),
                }

            qos_routers = []
            qos_states = []
            for i in range(N_REPLICAS):
                runner, url = await serve(build_app(router_args(
                    f"qos-{i}", engine_urls, "session", with_qos=True
                )))
                qos_routers.append(url)
                qos_states.append(runner.app["state"])
            fleet_flood = await tenant_flood(qos_routers)
            # force a final report round so the controller sees the full
            # flood window before we read the rollup
            for st in qos_states:
                await st.fleet_reporter.report_once()
            async with sess.get(controller_url + "/fleet") as r:
                rollup = (await r.json())["tenants"].get("acme", {})

            baseline_runner, baseline_url = await serve(build_app(
                router_args("qos-solo", engine_urls, "session",
                            with_qos=True)
            ))
            controller.fleet._replicas.clear()  # fresh rollup window
            baseline_flood = await tenant_flood([baseline_url])
            await baseline_runner.app["state"].fleet_reporter.report_once()
            async with sess.get(controller_url + "/fleet") as r:
                baseline_rollup = (await r.json())["tenants"].get("acme", {})

        return {
            "replicas": N_REPLICAS,
            "engines": N_ENGINES,
            "convergence": {
                "storm_events": events_pumped,
                "per_replica_lag": convergence,
                "restart_divergence_blocks": {
                    "cold": div_before.get("replica-cold"),
                    "healed": div_after.get("replica-cold"),
                },
            },
            "stickiness": {
                "identical_membership": sticky_identical,
                "skewed_membership": sticky_skewed,
                "detection_proven": (
                    sticky_identical["violation_rate"] == 0.0
                    and sum(sticky_skewed["violations"].values()) > 0
                ),
                "ring_divergent_flagged": bool(ring_divergent),
            },
            "tenant_accounting": {
                "budget_rps": 20.0,
                "fleet_3_replicas": {
                    **fleet_flood,
                    "limit_utilization": rollup.get("limit_utilization"),
                    "overadmission_ratio": rollup.get("overadmission_ratio"),
                },
                "single_router_baseline": {
                    **baseline_flood,
                    "limit_utilization":
                        baseline_rollup.get("limit_utilization"),
                    "overadmission_ratio":
                        baseline_rollup.get("overadmission_ratio"),
                },
            },
        }
    finally:
        import os as _os

        for runner in reversed(runners):
            await runner.cleanup()
        _os.unlink(tenant_file.name)


def _phase_fleet_main() -> None:
    """Subprocess entry for the CPU-only fleet-coherence bench. Forces CPU
    before anything touches jax — runs pre-preflight and needs no chip."""
    import asyncio
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    result = asyncio.run(_fleet_bench())
    print(json.dumps({"fleet": result}), flush=True)


async def _fleet_scale_client(spec: dict) -> dict:
    """One load-generator process (bench.py --phase fleet_scale_client):
    closed-loop or paced completions, or long-hold SSE streams, against a
    list of router URLs. Separate OS processes so the CLIENT is never the
    serialization point when measuring multi-router aggregate req/s."""
    import asyncio

    import aiohttp

    from vllm_production_stack_tpu.utils.system import raise_fd_limit

    raise_fd_limit(200_000)
    routers = spec["routers"]
    mode = spec.get("mode", "throughput")
    seconds = float(spec.get("seconds", 6.0))
    conc = int(spec.get("concurrency", 32))
    prefix = spec.get("session_prefix", "s")
    body_base = {
        "model": spec.get("model", "tiny"),
        "prompt": "hello fleet",
        "max_tokens": int(spec.get("max_tokens", 1)),
    }
    if spec.get("tokens_per_sec"):
        # fake-engine pacing knob: slow token gaps = long-held streams
        body_base["tokens_per_sec"] = spec["tokens_per_sec"]
    headers_base = {}
    if spec.get("auth"):
        headers_base["Authorization"] = f"Bearer {spec['auth']}"
    # "errors" = anything that wasn't a 200/429 INCLUDING client-side
    # transport faults; "server_5xx" counts only actual 5xx statuses so
    # the outage drill's "kept serving" claim isn't contradicted by a
    # load-client connection blip
    counts = {"completed": 0, "throttled": 0, "errors": 0, "server_5xx": 0}
    retry_after: list[str] = []

    conn = aiohttp.TCPConnector(limit=0)
    timeout = aiohttp.ClientTimeout(
        total=None, sock_connect=60, sock_read=180
    )
    async with aiohttp.ClientSession(
        connector=conn, timeout=timeout
    ) as sess:
        async def stream_worker(i: int) -> None:
            url = routers[i % len(routers)]
            try:
                async with sess.post(
                    url + "/v1/completions",
                    json={**body_base, "stream": True},
                    headers={**headers_base, "x-user-id": f"{prefix}-{i}"},
                ) as r:
                    async for _ in r.content:
                        pass
                    counts["completed" if r.status == 200 else "errors"] += 1
            except Exception:
                counts["errors"] += 1

        async def loop_worker(i: int) -> None:
            url = routers[i % len(routers)]
            sid = f"{prefix}-{i}"
            paced = spec.get("paced_rps")
            interval = (1.0 / paced) if paced else 0.0
            t_end = time.monotonic() + seconds
            while time.monotonic() < t_end:
                t0 = time.monotonic()
                try:
                    async with sess.post(
                        url + "/v1/completions", json=body_base,
                        headers={**headers_base, "x-user-id": sid},
                    ) as r:
                        await r.read()
                        if r.status == 200:
                            counts["completed"] += 1
                        elif r.status == 429:
                            counts["throttled"] += 1
                            ra = r.headers.get("Retry-After")
                            if ra and len(retry_after) < 4:
                                retry_after.append(ra)
                        else:
                            counts["errors"] += 1
                            if r.status >= 500:
                                counts["server_5xx"] += 1
                except Exception:
                    counts["errors"] += 1
                if interval:
                    dt = interval - (time.monotonic() - t0)
                    if dt > 0:
                        await asyncio.sleep(dt)

        t0 = time.monotonic()
        worker = stream_worker if mode == "streams" else loop_worker
        await asyncio.gather(*(worker(i) for i in range(conc)))
        elapsed = time.monotonic() - t0
    return {**counts, "elapsed_s": round(elapsed, 3),
            "retry_after_sample": retry_after}


def _phase_fleet_scale_client_main() -> None:
    import asyncio

    os.environ["JAX_PLATFORMS"] = "cpu"
    spec = json.loads(sys.argv[sys.argv.index("--spec") + 1])
    print(json.dumps(asyncio.run(_fleet_scale_client(spec))), flush=True)


async def _fleet_scale_bench() -> dict:
    """Horizontal router scale-out (docs/34-fleet-routing.md), CPU-only
    pre-preflight — the EXECUTION half of ROADMAP 1, measured over real
    OS processes and real wire (the PR 9 `fleet` phase measures what
    breaks; this phase proves the fixes):

    1. **throughput scaling**: M ∈ {1,2,3} router processes × N=4 fake
       engine processes, one load-generator PROCESS per router — the
       aggregate req/s curve in M (near-linear when the host has the
       cores; `host_cores` rides the JSON so a serialized 1-core result
       reads as what it is).
    2. **10k concurrent streams**: long-held SSE streams spread across
       the M=3 fleet; peak sum of `tpu:router_active_streams` across
       replicas, with engine-side stickiness violations staying ~0 under
       stable membership (identical rings by construction).
    3. **fleet-scaled tenant budgets**: the 3-replica flood from PR 9's
       phase, now with --fleet-budget-scaling on — over-admission must
       fall from ≈2 to ≈0 with no admission-path hop; 429 Retry-After is
       sampled from the SCALED buckets.
    4. **controller-outage drill**: the controller process is killed
       mid-flood — replicas degrade to the full local budget inside
       ~3 report intervals and KEEP SERVING (fail open).
    5. **cold-replica heal**: a 10k-block engine publishes (real
       KVEventPublisher, fan-out) to 2 embedded-index replicas + the
       controller; a freshly booted replica's divergence on /fleet reads
       the full slice, then the publisher's own background resync heals
       it to 0 — no human, no per-request controller hop.
    """
    import asyncio
    import shlex
    import socket
    import tempfile

    import aiohttp
    import yaml as _yaml

    N_ENGINES = 4
    BUDGET_RPS = 30.0
    LOAD_S = float(os.environ.get("FLEET_SCALE_SECONDS", "6"))
    STREAM_TARGET = int(os.environ.get("FLEET_SCALE_STREAMS", "10000"))
    REPORT_INTERVAL = 0.25

    def free_port() -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    procs: list[subprocess.Popen] = []
    runners = []

    def spawn(module: str, args: list[str]) -> subprocess.Popen:
        p = subprocess.Popen(
            [sys.executable, "-m", module, *args], cwd=REPO, env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
        procs.append(p)
        return p

    tenant_file = tempfile.NamedTemporaryFile("w", suffix=".yaml",
                                              delete=False)
    _yaml.safe_dump(
        {"acme": {"api_key": "k-acme", "requests_per_s": BUDGET_RPS}},
        tenant_file,
    )
    tenant_file.close()

    sess = aiohttp.ClientSession(
        connector=aiohttp.TCPConnector(limit=0),
        timeout=aiohttp.ClientTimeout(total=15),
    )

    async def wait_http(url: str, path: str = "/health",
                        timeout_s: float = 60.0) -> None:
        deadline = time.monotonic() + timeout_s
        while True:
            try:
                async with sess.get(url + path) as r:
                    if r.status < 500:
                        return
            except Exception:
                pass
            if time.monotonic() > deadline:
                raise RuntimeError(f"{url}{path} never came up")
            await asyncio.sleep(0.2)

    async def scrape_gauge(url: str, name: str) -> float | None:
        async with sess.get(url + "/metrics") as r:
            text = await r.text()
        for line in text.splitlines():
            if line.startswith(name + " ") or line.startswith(name + "{"):
                try:
                    return float(line.rsplit(" ", 1)[1])
                except ValueError:
                    return None
        return None

    async def run_clients(specs: list[dict]) -> list[dict]:
        """One load-generator subprocess per spec; parse each last line."""
        children = []
        for spec in specs:
            children.append(await asyncio.create_subprocess_exec(
                sys.executable, os.path.join(REPO, "bench.py"),
                "--phase", "fleet_scale_client",
                "--spec", json.dumps(spec),
                cwd=REPO, env=env,
                stdout=asyncio.subprocess.PIPE,
                stderr=asyncio.subprocess.DEVNULL,
            ))
        outs = []
        for i, child in enumerate(children):
            out, _ = await child.communicate()
            lines = out.decode(errors="replace").strip().splitlines()
            if not lines:
                # a client that died before printing (import error, OOM)
                # must surface as a named failure, not an IndexError
                outs.append({
                    "completed": 0, "throttled": 0, "errors": 0,
                    "server_5xx": 0, "retry_after_sample": [],
                    "error": f"load client {i} produced no output "
                             f"(rc={child.returncode})",
                })
                continue
            outs.append(json.loads(lines[-1]))
        return outs

    try:
        # -- shared fleet: engines + controller -------------------------
        engine_ports = [free_port() for _ in range(N_ENGINES)]
        engine_urls = [f"http://127.0.0.1:{p}" for p in engine_ports]
        for port, url in zip(engine_ports, engine_urls):
            spawn("vllm_production_stack_tpu.testing.fake_engine", [
                "--port", str(port), "--model", "tiny",
                "--tokens-per-sec", "2000",
                "--self-url", url, "--no-request-log",
            ])
        ctrl_port = free_port()
        ctrl_url = f"http://127.0.0.1:{ctrl_port}"
        ctrl_proc = spawn("vllm_production_stack_tpu.engine.kv_controller", [
            "--host", "127.0.0.1", "--port", str(ctrl_port),
            "--tenant-table-file", tenant_file.name,
            # rate window < the flood length, so utilization measures the
            # steady state instead of diluting over pre-flood idle time
            "--fleet-rate-window", "10",
        ])
        for url in engine_urls:
            await wait_http(url)
        await wait_http(ctrl_url)

        def router_cmd(replica: str, port: int) -> list[str]:
            return [
                "--host", "127.0.0.1", "--port", str(port),
                "--static-backends", ",".join(engine_urls),
                "--static-models", ";".join(["tiny"] * N_ENGINES),
                "--routing-logic", "session", "--session-key", "x-user-id",
                "--router-replica-id", replica,
                "--fleet-report-url", ctrl_url,
                "--fleet-report-interval", str(REPORT_INTERVAL),
                "--tenant-table-file", tenant_file.name,
                "--breaker-failure-threshold", "0",
                "--request-tracing", "off",
            ]

        # -- 1. throughput scaling in M ----------------------------------
        throughput: dict = {}
        routers3: list[str] = []
        router3_procs: list[subprocess.Popen] = []
        for m in (1, 2, 3):
            ports = [free_port() for _ in range(m)]
            urls = [f"http://127.0.0.1:{p}" for p in ports]
            # replica ids OVERLAP across runs (r0..r{m-1}) so the
            # controller's fleet view counts exactly m live replicas
            # instead of accumulating dead ids from earlier runs
            batch = [
                spawn("vllm_production_stack_tpu.router.app",
                      router_cmd(f"r{i}", port))
                for i, port in enumerate(ports)
            ]
            for url in urls:
                await wait_http(url)
            outs = await run_clients([
                {"mode": "throughput", "routers": [url],
                 "seconds": LOAD_S, "concurrency": 48,
                 "max_tokens": 1, "session_prefix": f"m{m}-{j}"}
                for j, url in enumerate(urls)
            ])
            total = sum(o["completed"] for o in outs)
            throughput[f"m{m}"] = {
                "req_per_s": round(total / LOAD_S, 1),
                "completed": total,
                "errors": sum(o["errors"] for o in outs),
            }
            if m == 3:
                routers3, router3_procs = urls, batch
            else:
                for p in batch:
                    p.terminate()
                for p in batch:
                    p.wait(timeout=15)
        m1 = throughput["m1"]["req_per_s"] or 1.0
        throughput["scaling_m3_over_m1"] = round(
            throughput["m3"]["req_per_s"] / m1, 2
        )
        throughput["host_cores"] = os.cpu_count()
        if (os.cpu_count() or 1) < 5:
            throughput["note"] = (
                "router/engine/client processes timeshare "
                f"{os.cpu_count()} core(s) — aggregate req/s is "
                "serialized by the host, not the architecture"
            )

        # -- 2. 10k concurrent long-held streams through M=3 -------------
        n_clients = 4
        per_client = STREAM_TARGET // n_clients
        stream_clients = [
            asyncio.create_task(run_clients([
                {"mode": "streams", "routers": routers3,
                 "concurrency": per_client, "max_tokens": 4,
                 "tokens_per_sec": 0.2,  # 4 tokens @ 5s gap ≈ 20s hold
                 "session_prefix": f"st{j}"}
            ]))
            for j in range(n_clients)
        ]
        peak_streams = 0.0
        while not all(t.done() for t in stream_clients):
            total = 0.0
            for url in routers3:
                v = await scrape_gauge(url, "tpu:router_active_streams")
                total += v or 0.0
            peak_streams = max(peak_streams, total)
            await asyncio.sleep(0.5)
        stream_outs = [t.result()[0] for t in stream_clients]
        violations = 0
        observed = 0
        for url in engine_urls:
            async with sess.get(url + "/debug/stickiness") as r:
                snap = await r.json()
            violations += sum(snap["violations"].values())
            observed += snap["observed"]
        streams = {
            "target": STREAM_TARGET,
            "peak_active_streams": int(peak_streams),
            "completed": sum(o["completed"] for o in stream_outs),
            "errors": sum(o["errors"] for o in stream_outs),
            "stickiness": {
                "observed": observed,
                "violations": violations,
                "violation_rate": round(violations / max(1, observed), 6),
            },
        }

        # -- 3. fleet-scaled tenant budgets ------------------------------
        # reporters have long since learned replicas=3 (0.25s interval)
        FLOOD_S = 12.0
        tenant_specs = [
            {"mode": "tenant", "routers": [url], "seconds": FLOOD_S,
             "concurrency": 2, "paced_rps": 12.5, "max_tokens": 1,
             "auth": "k-acme", "session_prefix": f"t{j}"}
            for j, url in enumerate(routers3)
        ]
        scale_gauge = await scrape_gauge(
            routers3[0], "tpu:router_tenant_budget_scale"
        )
        outs = await run_clients(tenant_specs)
        await asyncio.sleep(3 * REPORT_INTERVAL)  # final reports land
        async with sess.get(ctrl_url + "/fleet") as r:
            rollup = (await r.json())["tenants"].get("acme", {})
        scaled = {
            "budget_rps": BUDGET_RPS,
            "offered_rps": 75.0,
            "admitted": sum(o["completed"] for o in outs),
            "throttled": sum(o["throttled"] for o in outs),
            "admitted_rps": round(
                sum(o["completed"] for o in outs) / FLOOD_S, 2
            ),
            "limit_utilization": rollup.get("limit_utilization"),
            "overadmission_ratio": rollup.get("overadmission_ratio"),
            "budget_scale_gauge": scale_gauge,
            "retry_after_sample": next(
                (o["retry_after_sample"] for o in outs
                 if o["retry_after_sample"]), []
            ),
        }

        # -- 4. controller-outage drill ----------------------------------
        ctrl_proc.terminate()
        ctrl_proc.wait(timeout=15)
        # a failed report past 3 intervals degrades budgets to full-local
        await asyncio.sleep(6 * REPORT_INTERVAL + 1.0)
        outs = await run_clients([
            dict(spec, seconds=6.0) for spec in tenant_specs
        ])
        outage = {
            "admitted": sum(o["completed"] for o in outs),
            "admitted_rps": round(
                sum(o["completed"] for o in outs) / 6.0, 2
            ),
            "errors_5xx": sum(o.get("server_5xx", 0) for o in outs),
            "client_errors": sum(o["errors"] for o in outs),
            "budget_scale_gauge": await scrape_gauge(
                routers3[0], "tpu:router_tenant_budget_scale"
            ),
            "kept_serving": sum(o["completed"] for o in outs) > 0,
            "degraded_to_full_local": None,  # filled below
        }
        outage["degraded_to_full_local"] = (
            outage["budget_scale_gauge"] == 1.0
            and outage["admitted_rps"] > 1.5 * BUDGET_RPS
        )
        for p in router3_procs:
            p.terminate()

        # -- 5. cold-replica heal through publisher fan-out --------------
        from aiohttp import web

        from vllm_production_stack_tpu.engine.kv_cache import KVBlockPool
        from vllm_production_stack_tpu.engine.kv_controller import (
            KVController,
        )
        from vllm_production_stack_tpu.engine.kv_events import (
            KVEventPublisher,
        )
        from vllm_production_stack_tpu.router.app import build_app
        from vllm_production_stack_tpu.router.args import parse_args

        BLOCK = 16
        HEAL_BLOCKS = 10_000

        async def serve(app, port: int = 0):
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", port)
            await site.start()
            runners.append(runner)
            return runner, runner.addresses[0][1]

        heal_ctrl = KVController(["http://e0"], mode="indexed")
        _, heal_ctrl_port = await serve(heal_ctrl.build_app())
        heal_ctrl_url = f"http://127.0.0.1:{heal_ctrl_port}"

        def heal_router_args(replica: str):
            return parse_args([
                "--static-backends", "http://e0",
                "--static-models", "tiny",
                "--routing-logic", "kvaware",
                "--kv-index-mode", "embedded",
                "--kv-index-tokenizer", "byte",
                "--router-replica-id", replica,
                "--fleet-report-url", heal_ctrl_url,
                "--fleet-report-interval", "0.2",
            ])

        _, port_a = await serve(build_app(heal_router_args("warm")))
        cold_port = free_port()

        pool = KVBlockPool(HEAL_BLOCKS + 16, BLOCK)

        async def snapshot_fn():
            return pool.snapshot_events()

        pub = KVEventPublisher(
            [f"http://127.0.0.1:{port_a}",
             f"http://127.0.0.1:{cold_port}", heal_ctrl_url],
            "http://e0", pool.events, snapshot_fn, BLOCK, lambda: sess,
            interval_s=0.05, jitter_frac=0.0,
        )
        parent = pool.root_hash()
        rng_base = 0
        for _ in range(HEAL_BLOCKS):
            blk = pool.allocate()
            assert blk is not None
            parent = pool.register_full_block(
                blk, parent,
                tuple(range(rng_base, rng_base + BLOCK)),
            )
            rng_base += BLOCK
        pub.start()
        # warm replica + controller converge; the cold replica's
        # subscriber keeps failing (port closed) and stays snapshot-owed
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if heal_ctrl.index.stats()["hashes"] >= HEAL_BLOCKS:
                break
            await asyncio.sleep(0.2)
        # pause publishing only to take a deterministic COLD reading (on
        # a fast host the background snapshot can land before the first
        # fleet report); production publishers never pause — the heal
        # below runs through the same background loop
        await pub.stop()
        # boot the cold replica on the pre-registered address; its first
        # fleet report carries an EMPTY index -> divergence = full slice
        runner_cold, _ = await serve(
            build_app(heal_router_args("cold")), cold_port
        )
        cold_state = runner_cold.app["state"]
        await cold_state.fleet_reporter.report_once()
        async with sess.get(heal_ctrl_url + "/fleet") as r:
            before = {
                x["replica"]: x["divergence_blocks"]
                for x in (await r.json())["replicas"]
            }
        # ...and the publisher's own background fan-out heals it: the
        # cold subscriber answers the next batch with "resync", gets the
        # snapshot, divergence returns to 0 — no human anywhere
        pub.start()
        t_heal0 = time.monotonic()
        healed = None
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            await cold_state.fleet_reporter.report_once()
            async with sess.get(heal_ctrl_url + "/fleet") as r:
                div = {
                    x["replica"]: x["divergence_blocks"]
                    for x in (await r.json())["replicas"]
                }.get("cold")
            if div == 0:
                healed = div
                break
            await asyncio.sleep(0.2)
        heal_time = time.monotonic() - t_heal0
        await pub.stop()
        cold_heal = {
            "blocks": HEAL_BLOCKS,
            "divergence_cold": before.get("cold"),
            "divergence_healed": healed,
            "heal_time_s": round(heal_time, 2),
            "publisher": pub.debug_snapshot(),
        }

        return {
            "engines": N_ENGINES,
            "host_cores": os.cpu_count(),
            "throughput": throughput,
            "streams": streams,
            "tenant_budget": {
                "fleet_scaled_3_replicas": scaled,
                "controller_outage_drill": outage,
            },
            "cold_replica": cold_heal,
            "command_shape": shlex.join(
                ["python", "-m", "vllm_production_stack_tpu.router.app",
                 *router_cmd("rN", 0)][:8]
            ) + " ...",
        }
    finally:
        for runner in reversed(runners):
            try:
                await runner.cleanup()
            except Exception:
                pass
        await sess.close()
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()
        os.unlink(tenant_file.name)


def _phase_fleet_scale_main() -> None:
    """Subprocess entry for the CPU-only horizontal-scale-out bench.
    Forces CPU before anything touches jax — runs pre-preflight and needs
    no chip."""
    import asyncio

    os.environ["JAX_PLATFORMS"] = "cpu"
    result = asyncio.run(_fleet_scale_bench())
    print(json.dumps({"fleet_scale": result}), flush=True)


def _phase_hydration_main() -> None:
    """Subprocess entry for the CPU-only hydration-planner bench. Forces
    CPU before anything touches jax — runs pre-preflight and needs
    no chip."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    result = _hydration_bench()
    print(json.dumps({"hydration": result}), flush=True)


def _phase_kvquant_main() -> None:
    """Subprocess entry for the CPU-only at-rest KV quantization bench.
    Forces CPU before anything touches jax — runs pre-preflight and needs
    no chip."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    result = _kvquant_bench()
    print(json.dumps({"kvquant": result}), flush=True)


async def _peer_bench() -> dict:
    """Peer-engine KV tier: priced route-vs-migrate vs owner-affinity
    under skewed prefix popularity (docs/35-peer-kv-reuse.md). CPU-only,
    pre-preflight — router + fake engines, no jax on the hot path.

    Scenario: 3 fake-engine-backed engines, 4 decode seats each; ONE
    engine owns the hot prefix (its residency fed into the router's
    embedded cluster KV index exactly as a publisher would, and its
    warm-prefix model marked warm). A closed-loop flood where 85% of
    requests share that hot prefix then runs twice against fresh fleets:

    - **affinity** (--kv-migrate-scoring off): KV-aware routing follows
      the prefix owner, so the hot traffic serializes behind ONE
      engine's seats while the other two idle;
    - **priced** (--kv-migrate-scoring priced): once the owner's load/
      TTFT exceeds the least-loaded engine's wait plus the measured
      migration cost, requests route there with x-kv-owner-hint stamped —
      the fake pays the (cheap) peer-pull once per engine, the prefix
      warms fleet-wide, and all three engines serve the hot traffic.

    Reported: aggregate completion tok/s and TTFT p50/p99 per arm, the
    router's migrate-decision counts, and the engines' peer-pull/cold-
    prefill counters. The acceptance bar: priced beats affinity on
    aggregate tok/s OR TTFT p99 (it should win both)."""
    import asyncio

    import aiohttp
    from aiohttp import web

    from vllm_production_stack_tpu.kv_index import chain_hashes
    from vllm_production_stack_tpu.router.app import build_app
    from vllm_production_stack_tpu.router.args import parse_args
    from vllm_production_stack_tpu.testing.fake_engine import FakeEngine
    from vllm_production_stack_tpu.utils.tokenizer import hashing_tokenizer

    N_ENGINES, SEATS, BLOCK = 3, 4, 16
    CLIENTS, DURATION_S, HOT_SHARE = 24, 8.0, 0.85
    GEN_TOKENS = 24
    # ~8KB hot prefix => ~8k byte-tokens: far above the 256-token
    # threshold, and a 4s cold prefill at 2000 tok/s vs 0.2s peer pull
    hot_prefix = "the shared system prompt " * 400
    # tpulint: allow(async-blocking) — constructing the byte tokenizer is
    # a trivial object init, not a tokenize; encoding runs off-loop below
    tok = hashing_tokenizer("byte")

    runners: list[web.AppRunner] = []

    async def serve(app) -> tuple[web.AppRunner, str]:
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        runners.append(runner)
        return runner, f"http://127.0.0.1:{runner.addresses[0][1]}"

    async def run_arm(scoring: str) -> dict:
        engines: list[FakeEngine] = []
        urls: list[str] = []
        arm_runners: list[web.AppRunner] = []
        for _ in range(N_ENGINES):
            eng = FakeEngine(
                model="fake-model", tokens_per_sec=120.0,
                default_tokens=GEN_TOKENS, log_requests=False,
                seats=SEATS, prefill_tps=2000.0, peer_pull_tps=40000.0,
                kv_bytes_per_token=4096.0,
            )
            runner = web.AppRunner(eng.build_app())
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            runners.append(runner)
            arm_runners.append(runner)
            engines.append(eng)
            urls.append(f"http://127.0.0.1:{runner.addresses[0][1]}")
        owner_eng, owner_url = engines[0], urls[0]
        owner_eng.warm_prefixes.add(
            hot_prefix[: FakeEngine.WARM_KEY_CHARS]
        )
        router_runner, router_url = await serve(build_app(parse_args([
            "--static-backends", ",".join(urls),
            "--static-models", ";".join(["fake-model"] * N_ENGINES),
            "--routing-logic", "kvaware",
            "--kv-index-mode", "embedded",
            "--kv-index-tokenizer", "byte",
            "--kv-migrate-scoring", scoring,
            "--engine-stats-interval", "0.5",
            "--request-stats-window", "5",
            "--breaker-failure-threshold", "0",
        ])))
        # feed the owner's hot-prefix residency into the embedded index
        # exactly as its KV event publisher would (snapshot POST);
        # tokenize+hash off-loop (multi-KB prompt)
        hashes = await asyncio.get_running_loop().run_in_executor(
            None, lambda: chain_hashes(tok.encode(hot_prefix), BLOCK)
        )
        async with aiohttp.ClientSession() as sess:
            async with sess.post(router_url + "/kv/events", json={
                "engine": owner_url, "epoch": "bench", "block_size": BLOCK,
                "snapshot": True, "seq": 0,
                "hashes": [f"{h:x}" for h in hashes],
            }) as resp:
                assert resp.status == 200, await resp.text()

            # closed-loop flood: each client loops hot/cold prompts until
            # the deadline; TTFT = first SSE chunk
            t_end = time.monotonic() + DURATION_S
            ttfts: list[float] = []
            done_tokens = [0]
            failures = [0]

            async def client(i: int) -> None:
                r = 0
                while time.monotonic() < t_end:
                    r += 1
                    hot = (i * 31 + r) % 100 < HOT_SHARE * 100
                    prompt = (
                        hot_prefix + f" user{i} round{r}"
                        if hot else f"cold prompt {i}-{r} " * 30
                    )
                    t0 = time.monotonic()
                    try:
                        async with sess.post(
                            router_url + "/v1/completions",
                            json={"model": "fake-model", "prompt": prompt,
                                  "max_tokens": GEN_TOKENS, "stream": True},
                        ) as resp:
                            if resp.status != 200:
                                failures[0] += 1
                                continue
                            first = True
                            async for _ in resp.content.iter_any():
                                if first:
                                    ttfts.append(time.monotonic() - t0)
                                    first = False
                            done_tokens[0] += GEN_TOKENS
                    except aiohttp.ClientError:
                        failures[0] += 1

            t0 = time.monotonic()
            await asyncio.gather(*(client(i) for i in range(CLIENTS)))
            elapsed = time.monotonic() - t0

            # migrate-decision counters off the router's own /metrics
            async with sess.get(router_url + "/metrics") as resp:
                metrics_text = await resp.text()

        def count(decision: str) -> float:
            needle = (
                "tpu:router_kv_migrate_decisions_total"
                f'{{decision="{decision}"}} '
            )
            for ln in metrics_text.splitlines():
                if ln.startswith(needle):
                    return float(ln.split()[-1])
            return 0.0

        ttfts.sort()

        def pct(p: float) -> float:
            if not ttfts:
                return 0.0
            return round(ttfts[min(len(ttfts) - 1, int(p * len(ttfts)))], 4)

        for runner in arm_runners:
            await runner.cleanup()
            runners.remove(runner)
        return {
            "scoring": scoring,
            "agg_tok_per_s": round(done_tokens[0] / elapsed, 1),
            "requests": len(ttfts),
            "failures": failures[0],
            "ttft_p50_s": pct(0.50),
            "ttft_p99_s": pct(0.99),
            "decisions": {"owner": count("owner"),
                          "migrate": count("migrate")},
            "owner_requests": owner_eng.total_requests,
            "per_engine_requests": [e.total_requests for e in engines],
            "peer_pulls": sum(e.peer_pulls for e in engines),
            "cold_prefills": sum(e.cold_prefills for e in engines),
        }

    async def bit_identical_check() -> dict:
        """REAL-engine half of the acceptance bar: engine A computes a
        prompt, engine B pulls it over the actual peer tier (owner hint,
        /kv/peer_contains + /kv/peer_fetch, frame adoption) — tokens must
        be bit-equal to A's compute and the hydration partition exact."""
        import numpy as np

        from vllm_production_stack_tpu.engine.config import (
            CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
        )
        from vllm_production_stack_tpu.engine.engine import LLMEngine
        from vllm_production_stack_tpu.engine.kv_flow import TierBandwidth
        from vllm_production_stack_tpu.engine.request import SamplingParams
        from vllm_production_stack_tpu.engine.server import EngineServer

        bs = 8
        greedy = SamplingParams(
            max_tokens=8, temperature=0.0, ignore_eos=True
        )

        def tiny(peer: bool) -> LLMEngine:
            return LLMEngine(EngineConfig(
                model=ModelConfig.tiny(),
                cache=CacheConfig(
                    block_size=bs, num_blocks=64, num_host_blocks=4,
                ),
                scheduler=SchedulerConfig(
                    max_num_seqs=2, max_num_batched_tokens=64,
                    decode_buckets=(2,), prefill_buckets=(32, 64),
                    decode_window=4,
                ),
                kv_hydration="planner" if peer else "sync",
                kv_hydration_chunk_blocks=2,
                kv_peer_fetch=peer,
            ))

        prompt = [int(t) for t in
                  np.random.RandomState(13).randint(1, 500, size=6 * bs)]
        loop = asyncio.get_running_loop()
        eng_a = tiny(peer=False)
        ref = eng_a.generate([prompt], greedy)[0]["token_ids"]
        runner = web.AppRunner(
            EngineServer(eng_a, served_model_name="tiny").build_app()
        )
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        a_url = f"http://127.0.0.1:{runner.addresses[0][1]}"
        try:
            eng_b = tiny(peer=True)
            # cross the sample floor + seed a compute-rate estimate (the
            # planner's trust gate), exactly like the tier-1 tests
            eng_b.flow.record("peer", "in", TierBandwidth.MIN_BYTES, 32, 0.01)
            eng_b.flow.record("peer", "in", TierBandwidth.MIN_BYTES, 32, 0.01)
            eng_b.generate([[7] * bs], greedy)
            t0 = time.perf_counter()
            got = await loop.run_in_executor(
                None,
                lambda: eng_b.generate(
                    [prompt], greedy, kv_owner_hint=a_url
                )[0]["token_ids"],
            )
            http_latency = time.perf_counter() - t0
            snap = eng_b.flow.snapshot()
            hyd = snap["hydration"]
            partition_exact = sum(hyd.values()) == eng_b._prompt_tokens
            result = {
                "tokens_equal": got == ref,
                "peer_fetch_tokens": hyd.get("peer_fetch", 0),
                "partition_exact": partition_exact,
                "latency_s": round(http_latency, 3),
                "wire_bytes": snap["bytes"].get("peer/in", 0),
            }
            assert result["tokens_equal"], (got, ref)
            assert result["peer_fetch_tokens"] > 0, hyd
            assert partition_exact, hyd
            await loop.run_in_executor(
                None, lambda: eng_b.runner.shutdown(True)
            )
        finally:
            await runner.cleanup()
        eng_a.runner.shutdown(wait=True)
        return result

    def device_arm() -> dict:
        """Device-transport half of the acceptance bar (docs/39): two REAL
        OS processes sharing a mesh group — the puller's hydration fetch
        lane moves the owner's pages over the shard-flip collective
        instead of HTTP, and the worker itself asserts token-identity vs
        a from-scratch oracle plus (device, in)-only metering. Reported
        side by side with the HTTP arm's latency/bytes above."""
        from vllm_production_stack_tpu.parallel.distributed import (
            run_multiprocess_device_peer_dryrun,
        )

        outs = run_multiprocess_device_peer_dryrun(timeout_s=240)
        result = {"ok": True}
        for line in "\n".join(outs).splitlines():
            if "DEVPEER_DRYRUN_OK" not in line:
                continue
            for tok in line.split():
                for key in ("pulled_bytes", "latency_s", "served_bytes"):
                    if tok.startswith(key + "="):
                        result[key] = float(tok.split("=", 1)[1])
        return result

    try:
        affinity = await run_arm("off")
        priced = await run_arm("priced")
        bit_identical = await bit_identical_check()
        loop = asyncio.get_running_loop()
        device = await loop.run_in_executor(None, device_arm)
    finally:
        for runner in runners:
            await runner.cleanup()

    return {
        "engines": N_ENGINES,
        "seats_per_engine": SEATS,
        "clients": CLIENTS,
        "hot_share": HOT_SHARE,
        "affinity": affinity,
        "priced": priced,
        "bit_identical": bit_identical,
        # HTTP arm (bit_identical.latency_s/wire_bytes) vs device arm
        # (device.latency_s/pulled_bytes): the same pull over both wires
        "device": device,
        "speedup_tok_per_s": (
            round(priced["agg_tok_per_s"] / affinity["agg_tok_per_s"], 2)
            if affinity["agg_tok_per_s"] else None
        ),
        "ttft_p99_ratio": (
            round(affinity["ttft_p99_s"] / priced["ttft_p99_s"], 2)
            if priced["ttft_p99_s"] else None
        ),
        # the acceptance bar (ISSUE 13): priced must beat owner-affinity
        # on aggregate tok/s or TTFT p99 under skewed popularity
        "priced_beats_affinity": bool(
            priced["agg_tok_per_s"] > affinity["agg_tok_per_s"]
            or priced["ttft_p99_s"] < affinity["ttft_p99_s"]
        ),
        "migrations_happened": priced["decisions"]["migrate"] > 0,
    }


def _phase_peer_main() -> None:
    """Subprocess entry for the CPU-only peer route-vs-migrate bench.
    Forces CPU before anything touches jax — runs pre-preflight and needs
    no chip."""
    import asyncio
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    result = asyncio.run(_peer_bench())
    print(json.dumps({"peer": result}), flush=True)


async def _rebalance_bench() -> dict:
    """Dynamic P/D pool rebalancing (docs/40-pool-rebalancing.md):
    a decode-heavy workload shift against a statically partitioned
    4-engine fleet (3 prefill + 1 decode, the wrong split for the
    traffic). CPU-only, pre-preflight — fake engines + real router +
    real KV controller hosting the real rebalancer, everything over
    actual aiohttp wire.

    - **static** (rebalancer off): decode queue-wait p95 blows through
      the TpuSeatStarvation trigger (>1s queued while most of the
      fleet's seats sit idle) and STAYS there — the imbalance needs a
      human;
    - **rebalance** (rebalancer on): the controller diagnoses the
      decode-starved pool from the routers' fleet reports, drains the
      least-loaded prefill engine, flips it via POST /role, and the
      starvation condition clears — with ZERO failed requests and ZERO
      severed streams (asserted; the 2-phase router path re-picks around
      the drain refusals mid-flip);
    - **chaos arms**: the flip target killed mid-drain (episode must
      abandon, traffic must keep flowing) and a black-holed controller
      (engines + routers fail open — the actuator's death must never
      take the data plane with it)."""
    import asyncio
    import tempfile

    import aiohttp
    from aiohttp import web

    from vllm_production_stack_tpu.engine.kv_controller import KVController
    from vllm_production_stack_tpu.engine.rebalancer import RebalanceConfig
    from vllm_production_stack_tpu.router.app import build_app
    from vllm_production_stack_tpu.router.args import parse_args
    from vllm_production_stack_tpu.testing.fake_engine import FakeEngine

    N_PREFILL, N_DECODE, SEATS = 3, 1, 2
    CLIENTS, GEN_TOKENS, TOKENS_PER_SEC = 12, 10, 40.0
    TRIGGER_S = 1.0  # TpuSeatStarvation's queue-wait threshold

    async def serve(app) -> tuple[web.AppRunner, str]:
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        return runner, f"http://127.0.0.1:{runner.addresses[0][1]}"

    async def run_arm(rebalance_on: bool, duration_s: float,
                      chaos: str = "") -> dict:
        runners: list[web.AppRunner] = []
        state_dir = tempfile.mkdtemp(prefix="rebalance-bench-")
        ctrl_url = ""
        controller = None
        if chaos == "blackhole_controller":
            from vllm_production_stack_tpu.testing.faults import black_hole
            hole, port = await black_hole()
            ctrl_url = f"http://127.0.0.1:{port}"
        else:
            controller = KVController([], rebalance=RebalanceConfig(
                enabled=rebalance_on, interval_s=0.2, observe_s=1.0,
                cooldown_s=3.0, verify_window_s=1.0,
                min_prefill=1, min_decode=1,
                queue_wait_trigger_s=TRIGGER_S, occupancy_rich_max=0.5,
                drain_timeout_s=10.0, unreachable_limit=3,
                episode_timeout_s=60.0, engine_cooldown_s=5.0,
                state_file=state_dir + "/rebalancer.json",
            ))
            ctrl_runner, ctrl_url = await serve(controller.build_app())
            runners.append(ctrl_runner)

        engines: list[FakeEngine] = []
        urls: list[str] = []
        labels: list[str] = []
        url_runner: dict[str, web.AppRunner] = {}
        for i in range(N_PREFILL + N_DECODE):
            role = "prefill" if i < N_PREFILL else "decode"
            eng = FakeEngine(
                model="fake-model", tokens_per_sec=TOKENS_PER_SEC,
                default_tokens=GEN_TOKENS, log_requests=False,
                seats=SEATS, prefill_tps=4000.0, role=role,
                kv_controller_url=ctrl_url,
            )
            runner, url = await serve(eng.build_app())
            runners.append(runner)
            eng.self_url = url
            await eng._register()  # startup ran before self_url was known
            engines.append(eng)
            urls.append(url)
            labels.append(role)
            url_runner[url] = runner

        router_runner, router_url = await serve(build_app(parse_args([
            "--static-backends", ",".join(urls),
            "--static-models", ";".join(["fake-model"] * len(urls)),
            "--static-model-labels", ",".join(labels),
            "--routing-logic", "disaggregated_prefill",
            "--prefill-model-labels", "prefill",
            "--decode-model-labels", "decode",
            "--engine-stats-interval", "0.5",
            "--fleet-report-url", ctrl_url,
            "--fleet-report-interval", "0.3",
            "--breaker-failure-threshold", "0",
        ])))
        runners.append(router_runner)

        t_end = time.monotonic() + duration_s
        t0_arm = time.monotonic()
        ttfts: list[float] = []
        completed = [0]
        failures = [0]  # non-200 / transport errors — must stay 0
        dropped = [0]   # 200 streams that never saw a clean [DONE]
        killed = {"url": None}

        async def client(i: int, sess: aiohttp.ClientSession) -> None:
            r = 0
            while time.monotonic() < t_end:
                r += 1
                prompt = f"pool shift load {i}-{r} " * 8
                t0 = time.monotonic()
                try:
                    async with sess.post(
                        router_url + "/v1/completions",
                        json={"model": "fake-model", "prompt": prompt,
                              "max_tokens": GEN_TOKENS, "stream": True},
                    ) as resp:
                        if resp.status != 200:
                            failures[0] += 1
                            continue
                        first, clean = True, False
                        async for line in resp.content:
                            if first:
                                ttfts.append(time.monotonic() - t0)
                                first = False
                            if line.decode().strip() == "data: [DONE]":
                                clean = True
                        if clean:
                            completed[0] += 1
                        else:
                            dropped[0] += 1
                except aiohttp.ClientError:
                    failures[0] += 1

        # starvation timeline off the controller's own /rebalance view:
        # the TpuSeatStarvation shape — queued work past the trigger
        # while most of the fleet's decode seats sit empty
        samples: list[dict] = []

        def starved_now(pools: dict) -> tuple[bool, float, float]:
            members = [p for pool in pools.values() for p in pool.values()]
            if not members:
                return False, 0.0, 0.0
            decode_qw = [p["queue_wait_p95"]
                         for p in pools.get("decode", {}).values()]
            mean_occ = (sum(p["seat_occupancy"] for p in members)
                        / len(members))
            max_qw = max(decode_qw) if decode_qw else 0.0
            return (bool(decode_qw and max_qw > TRIGGER_S
                         and mean_occ < 0.5), max_qw, mean_occ)

        async def sampler(sess: aiohttp.ClientSession) -> None:
            if controller is None:
                return  # black-holed controller has no view to sample
            while time.monotonic() < t_end:
                try:
                    async with sess.get(ctrl_url + "/rebalance") as resp:
                        snap = await resp.json()
                    starved, max_qw, mean_occ = starved_now(
                        snap.get("pools") or {})
                    samples.append({
                        "t": round(time.monotonic() - t0_arm, 2),
                        "starved": starved,
                        "decode_qw_max_s": round(max_qw, 2),
                        "mean_occupancy": round(mean_occ, 2),
                        "phase": snap.get("phase"),
                    })
                    if (chaos == "kill_mid_drain"
                            and killed["url"] is None
                            and snap.get("episode")):
                        victim = snap["episode"]["engine"]
                        await url_runner[victim].cleanup()
                        runners.remove(url_runner[victim])
                        killed["url"] = victim
                except aiohttp.ClientError:
                    pass
                await asyncio.sleep(0.2)

        try:
            async with aiohttp.ClientSession() as sess:
                await asyncio.gather(
                    sampler(sess),
                    *(client(i, sess) for i in range(CLIENTS)),
                )
                snap = {}
                if controller is not None:
                    async with sess.get(ctrl_url + "/rebalance") as resp:
                        snap = await resp.json()
        finally:
            for runner in runners:
                await runner.cleanup()
            if chaos == "blackhole_controller":
                hole.close()

        elapsed = time.monotonic() - t0_arm
        ttfts.sort()

        def pct(p: float) -> float:
            if not ttfts:
                return 0.0
            return round(ttfts[min(len(ttfts) - 1, int(p * len(ttfts)))], 3)

        tail = [s for s in samples if s["t"] > duration_s - 1.5]
        # run-length compress the starvation timeline: transitions only
        transitions = [s for i, s in enumerate(samples)
                       if i == 0 or s["starved"] != samples[i - 1]["starved"]]
        return {
            "timeline": transitions,
            "tail": tail,
            "rebalancer": "on" if rebalance_on else "off",
            "chaos": chaos or None,
            "completed": completed[0],
            "req_per_s": round(completed[0] / elapsed, 1),
            "failures": failures[0],
            "dropped_streams": dropped[0],
            "ttft_p50_s": pct(0.50),
            "ttft_p99_s": pct(0.99),
            "starvation_tripped": any(s["starved"] for s in samples),
            "starved_at_end": (bool(tail) and all(s["starved"]
                                                  for s in tail)),
            "cleared_at_end": (bool(tail) and not any(s["starved"]
                                                      for s in tail)),
            "flips": (snap.get("flips") if snap else None),
            "final_roles": {u: e.role for u, e in zip(urls, engines)},
            "role_flips": sum(e.role_flips for e in engines),
            "killed_engine": killed["url"],
        }

    static = await run_arm(rebalance_on=False, duration_s=7.0)
    dynamic = await run_arm(rebalance_on=True, duration_s=14.0)
    kill = await run_arm(rebalance_on=True, duration_s=10.0,
                         chaos="kill_mid_drain")
    blackhole = await run_arm(rebalance_on=False, duration_s=5.0,
                              chaos="blackhole_controller")

    # the acceptance bar (ISSUE 18): the static pool trips the
    # starvation condition and stays starved; the rebalancer flips a
    # role and CLEARS it with zero failed requests and zero severed
    # streams; both chaos arms finish with traffic still flowing
    assert static["starvation_tripped"], static
    assert static["starved_at_end"], static
    assert dynamic["starvation_tripped"], dynamic
    assert dynamic["flips"] and dynamic["flips"]["completed"] >= 1, dynamic
    assert dynamic["cleared_at_end"], dynamic
    for arm in (static, dynamic, kill, blackhole):
        assert arm["failures"] == 0, arm
        assert arm["dropped_streams"] == 0, arm
        assert arm["completed"] > 0, arm
    assert kill["killed_engine"] is not None, kill
    assert kill["flips"] and kill["flips"]["abandoned"] >= 1, kill

    return {
        "engines": N_PREFILL + N_DECODE,
        "initial_split": {"prefill": N_PREFILL, "decode": N_DECODE},
        "clients": CLIENTS,
        "static": static,
        "rebalance": dynamic,
        "chaos_kill_mid_drain": kill,
        "chaos_blackhole_controller": blackhole,
        "starvation_cleared_by_flip": bool(
            dynamic["starvation_tripped"] and dynamic["cleared_at_end"]
        ),
        "zero_dropped_streams": all(
            a["failures"] == 0 and a["dropped_streams"] == 0
            for a in (static, dynamic, kill, blackhole)
        ),
    }


def _phase_rebalance_main() -> None:
    """Subprocess entry for the CPU-only P/D pool-rebalancing bench.
    Forces CPU before anything touches jax — runs pre-preflight and needs
    no chip."""
    import asyncio
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    result = asyncio.run(_rebalance_bench())
    print(json.dumps({"rebalance": result}), flush=True)


def _phase_kvflow_main() -> None:
    """Subprocess entry for the CPU-only KV-flow telemetry bench. Forces
    CPU before anything touches jax — runs pre-preflight and needs no
    chip."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    result = _kvflow_bench()
    print(json.dumps({"kvflow": result}), flush=True)


def _phase_saturation_main() -> None:
    """Subprocess entry for the CPU-only saturation/goodput bench. Forces
    CPU before anything touches jax — runs pre-preflight and needs no
    chip."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    result = _saturation_bench()
    print(json.dumps({"saturation": result}), flush=True)


def _phase_fairness_main() -> None:
    """Subprocess entry for the CPU-only multi-tenant fairness bench.
    Forces CPU before anything touches jax — like routing/robustness, this
    phase needs no chip."""
    import asyncio
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    result = asyncio.run(_fairness_bench())
    print(json.dumps({"fairness": result}), flush=True)


def _phase_robustness_main() -> None:
    """Subprocess entry for the CPU-only robustness bench (shed latency +
    drain time). Forces CPU before anything touches jax — this phase
    needs no chip."""
    import asyncio
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    result = asyncio.run(_robustness_bench())
    print(json.dumps({"robustness": result}), flush=True)


def _phase_tracing_main() -> None:
    """Subprocess entry for the CPU-only tracing-overhead bench (same
    flood, spine off vs on). Forces CPU before anything touches jax."""
    import asyncio
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    result = asyncio.run(_tracing_bench())
    print(json.dumps({"tracing": result}), flush=True)


def _phase_blackbox_main() -> None:
    """Subprocess entry for the CPU-only flight-recorder/watchdog bench
    (three named wedges + recorder noise floor, docs/37-flight-recorder
    .md). Forces CPU before anything touches jax — this phase diagnoses
    wedges, so its own evidence must survive one."""
    import asyncio
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    result = asyncio.run(_blackbox_bench())
    print(json.dumps({"blackbox": result}), flush=True)


def _phase_micro_main() -> None:
    """Subprocess entry: enable the persistent compile cache, run the
    microbench (+ the step-loop attribution bench), print its JSON."""
    from vllm_production_stack_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    micro = run_microbench()
    try:
        micro["steploop"] = run_steploop_bench()
    except Exception as e:  # never lose the headline number to the rider
        micro["steploop"] = {"error": str(e)}
    print(json.dumps({"microbench": micro}), flush=True)


def _phase_preflight_main() -> None:
    """Subprocess entry: touch the chip with one trivial dispatch. A
    chip belongs to one process at a time: while another process holds it
    (a stale engine from a killed run), JAX start-up fails or hangs, and
    every phase would eat its full timeout; this makes the failure mode
    one cheap, explicit section instead.

    Watchdog: a daemon timer hard-kills this subprocess after
    PREFLIGHT_HARD_TIMEOUT_S (default 300 s, below the parent's kill
    window) having FIRST written the ENGINE-NATIVE postmortem artifact
    (flightrec.write_postmortem: thread stacks + redacted env + the hung
    init stage — the same JSON black box a stalled serving engine dumps,
    docs/37-flight-recorder.md) and printed a structured diagnostic
    naming that file plus which init stage hung (import / devices /
    dispatch), elapsed time, and the jax platform env. The parent then
    reports a named failure mode instead of a bare timeout."""
    import faulthandler
    import threading

    t0 = time.monotonic()
    stage = {"name": "import-jax"}
    hard_s = float(os.environ.get("PREFLIGHT_HARD_TIMEOUT_S", "300"))

    def watchdog() -> None:
        diag = {
            "error": f"watchdog: preflight hung after {hard_s:.0f}s",
            "stage": stage["name"],
            "elapsed_s": round(time.monotonic() - t0, 1),
            "jax_platforms": os.environ.get("JAX_PLATFORMS", ""),
            "tpu_library": os.environ.get("TPU_LIBRARY_PATH", ""),
            "hint": "another process may hold the chip — kill stale jax "
                    "processes before retrying",
        }
        # named failure mode FIRST, hard-exit ARMED second, postmortem
        # file last: the dump targets a filesystem that may itself be
        # wedged (hung PVC/NFS), and a blocked write must never suppress
        # the diagnostic or the kill this watchdog exists for
        print(json.dumps({"preflight": diag}), flush=True)
        faulthandler.dump_traceback()  # stderr merges into the phase log
        backstop = threading.Timer(20.0, lambda: os._exit(3))
        backstop.daemon = True
        backstop.start()
        try:
            from vllm_production_stack_tpu.engine.flightrec import (
                write_postmortem,
            )

            path, _doc = write_postmortem(
                os.environ.get("POSTMORTEM_DIR", "/tmp/tpu-postmortem"),
                "bench_preflight",
                f"preflight hung at stage {stage['name']} after "
                f"{hard_s:.0f}s",
                sections={"preflight": dict(diag)},
            )
            diag["postmortem"] = path
        except Exception as e:
            diag["postmortem_error"] = f"{type(e).__name__}: {e}"
        # re-print WITH the artifact path — the phase parser keeps the
        # LAST JSON line, so a successful dump names its file and a hung
        # one still left the first diagnostic (+ the backstop exit)
        print(json.dumps({"preflight": diag}), flush=True)
        os._exit(3)

    timer = threading.Timer(hard_s, watchdog)
    timer.daemon = True
    timer.start()
    import jax
    import jax.numpy as jnp

    stage["name"] = "enumerate-devices"
    dev = jax.devices()[0]
    stage["name"] = "first-dispatch"
    val = int(jax.jit(lambda a: a + 1)(jnp.int32(41)))
    assert val == 42, val
    timer.cancel()
    print(json.dumps({"preflight": {
        "platform": dev.platform,
        "device": str(dev),
        "first_dispatch_s": round(time.monotonic() - t0, 1),
    }}), flush=True)


def main() -> None:
    if "--phase" in sys.argv:
        phase = sys.argv[sys.argv.index("--phase") + 1]
        if phase == "preflight":
            _phase_preflight_main()
        elif phase == "routing":
            _phase_routing_main()
        elif phase == "robustness":
            _phase_robustness_main()
        elif phase == "fairness":
            _phase_fairness_main()
        elif phase == "tracing":
            _phase_tracing_main()
        elif phase == "blackbox":
            _phase_blackbox_main()
        elif phase == "structured":
            _phase_structured_main()
        elif phase == "compile":
            _phase_compile_main()
        elif phase == "saturation":
            _phase_saturation_main()
        elif phase == "kvflow":
            _phase_kvflow_main()
        elif phase == "hydration":
            _phase_hydration_main()
        elif phase == "kvquant":
            _phase_kvquant_main()
        elif phase == "peer":
            _phase_peer_main()
        elif phase == "rebalance":
            _phase_rebalance_main()
        elif phase == "fleet":
            _phase_fleet_main()
        elif phase == "fleet_scale":
            _phase_fleet_scale_main()
        elif phase == "fleet_scale_client":
            _phase_fleet_scale_client_main()
        else:
            assert phase == "micro", phase
            _phase_micro_main()
        return

    # -1) routing lookup bench: pure host-side CPU code, runs BEFORE the
    # chip preflight (it needs no chip)
    routing = _run_phase(
        "routing", ["bench.py", "--phase", "routing"],
        timeout_s=300, key="routing", min_needed_s=60.0,
    )

    # -0.5) robustness (shed latency + drain time): also CPU-only — the
    # BENCH trajectory captures regressions in how fast overload is turned
    # away and how long the drain barrier holds a terminating pod
    robustness = _run_phase(
        "robustness", ["bench.py", "--phase", "robustness"],
        timeout_s=300, key="robustness", min_needed_s=60.0,
    )

    # -0.25) multi-tenant fairness (weighted decode share + realtime-probe
    # TTFT under flood + qos-off overhead): CPU-only, needs no chip
    fairness = _run_phase(
        "fairness", ["bench.py", "--phase", "fairness"],
        timeout_s=300, key="fairness", min_needed_s=60.0,
    )

    # -0.125) request-tracing spine overhead (same flood, spine off vs
    # on): CPU-only — the observability layer's cost stays a measured
    # number in the BENCH trajectory, not an assertion
    tracing = _run_phase(
        "tracing", ["bench.py", "--phase", "tracing"],
        timeout_s=300, key="tracing", min_needed_s=60.0,
    )

    # -0.1) flight recorder / watchdog / postmortems (docs/37-flight-
    # recorder.md): three injected wedges each NAMED by the watchdog with
    # a postmortem file, plus the recorder's ≤2% p50 noise floor —
    # CPU-only, pre-preflight BY DESIGN: this phase exists because the
    # chip wedge produces no other evidence
    blackbox = _run_phase(
        "blackbox", ["bench.py", "--phase", "blackbox"],
        timeout_s=420, key="blackbox", min_needed_s=90.0,
    )

    # -0.08) structured output (docs/41-structured-output.md): agent
    # swarm of forced schema-constrained tool calls — valid rate 1.0 on
    # vs ~0 off, mask overhead at matched decode length, TTFT, shared-
    # prefix hit rate, zero recompiles after warmup, serial==pipelined —
    # CPU-only, pre-preflight, needs no chip
    structured = _run_phase(
        "structured", ["bench.py", "--phase", "structured"],
        timeout_s=420, key="structured", min_needed_s=90.0,
    )

    # -0.07) XLA compile telemetry (docs/42-compile-telemetry.md): the
    # pad-up zero-mid-traffic-compile guarantee measured on a mixed
    # workload, the recompile-storm detector tripped on purpose, and the
    # watch's own overhead at the noise floor — CPU-only, pre-preflight
    compile_ph = _run_phase(
        "compile", ["bench.py", "--phase", "compile"],
        timeout_s=420, key="compile", min_needed_s=90.0,
    )

    # -0.0625) saturation & goodput (docs/29-saturation-slo.md): ledger
    # exactness under a rollback+preemption+deadline flood + step-meter
    # overhead — CPU-only, pre-preflight, needs no chip
    saturation = _run_phase(
        "saturation", ["bench.py", "--phase", "saturation"],
        timeout_s=300, key="saturation", min_needed_s=60.0,
    )

    # -0.03125) KV-hierarchy flow telemetry (docs/30-kv-flow-telemetry.md):
    # hydration-attribution exactness + per-tier bandwidth honesty + flow-
    # meter overhead — CPU-only, pre-preflight, needs no chip
    kvflow = _run_phase(
        "kvflow", ["bench.py", "--phase", "kvflow"],
        timeout_s=300, key="kvflow", min_needed_s=60.0,
    )

    # -0.015625) compute-or-load hydration planner (docs/31-hydration-
    # planner.md): TTFT on cold remote-resident 8k prompts, planner vs
    # load-only vs compute-only — CPU-only, pre-preflight
    hydration = _run_phase(
        "hydration", ["bench.py", "--phase", "hydration"],
        timeout_s=540, key="hydration", min_needed_s=120.0,
    )

    # -0.013) at-rest KV quantization (docs/38-kv-quantization.md):
    # int4+scales vs pool-precision at rest on a throttled remote link —
    # wire-byte reduction, TTFT, exact hydration partition, greedy token
    # identity — CPU-only, pre-preflight, needs no chip
    kvquant = _run_phase(
        "kvquant", ["bench.py", "--phase", "kvquant"],
        timeout_s=480, key="kvquant", min_needed_s=120.0,
    )

    # -0.0117) peer-engine KV tier (docs/35-peer-kv-reuse.md): priced
    # route-vs-migrate vs owner-affinity under skewed prefix popularity —
    # CPU-only, pre-preflight (fake engines + real router, no chip)
    peer = _run_phase(
        "peer", ["bench.py", "--phase", "peer"],
        # the device arm spawns a 2-process jax.distributed dryrun that
        # cold-compiles the shard-flip program — budget for it
        timeout_s=480, key="peer", min_needed_s=60.0,
    )

    # -0.009) dynamic P/D pool rebalancing (docs/40-pool-rebalancing.md):
    # a decode-heavy shift against a mislabeled 4-engine fleet — static
    # pools trip and HOLD the seat-starvation condition; the rebalancer
    # flips a role and clears it with zero failed/severed streams, and
    # both chaos arms (target killed mid-drain, black-holed controller)
    # finish with traffic flowing — CPU-only, pre-preflight
    rebalance = _run_phase(
        "rebalance", ["bench.py", "--phase", "rebalance"],
        timeout_s=300, key="rebalance", min_needed_s=90.0,
    )

    # -0.0078125) fleet-coherence telemetry (docs/32-fleet-telemetry.md):
    # the ROADMAP-1 baselines — convergence lag across 3 router replicas
    # after a 10k-event storm, stickiness-violation detection, fleet
    # tenant over-admission vs 1 router — CPU-only, pre-preflight
    fleet = _run_phase(
        "fleet", ["bench.py", "--phase", "fleet"],
        timeout_s=300, key="fleet", min_needed_s=60.0,
    )

    # -0.00390625) horizontal router scale-out (docs/34-fleet-routing.md):
    # the execution half of ROADMAP 1 — aggregate req/s in M router
    # processes, 10k concurrent streams, fleet-scaled tenant budgets with
    # a controller-outage drill, and the cold-replica fan-out heal —
    # CPU-only, pre-preflight
    fleet_scale = _run_phase(
        "fleet_scale", ["bench.py", "--phase", "fleet_scale"],
        timeout_s=540, key="fleet_scale", min_needed_s=120.0,
    )

    # 0) chip preflight: one trivial dispatch. A chip that cannot be
    # reached fails HERE in minutes with an explicit section; the heavy
    # phases are then reported skipped instead of serially eating their
    # timeouts
    preflight = _run_phase(
        "preflight", ["bench.py", "--phase", "preflight"],
        timeout_s=420, key="preflight", min_needed_s=60.0,
    )
    if preflight.get("error"):
        for section in ("microbench", "livestack", "northstar", "int8_8b",
                        "int8_8b_kvauto"):
            _emit(section, {"skipped": "chip preflight failed "
                                       "(chip held or no device)"})
        out = {
            "metric": "served_northstar_throughput",
            "value": 0.0,
            "unit": "req/s",
            "vs_baseline": 0.0,
            "error": "chip preflight failed — no TPU dispatch possible",
            "preflight": preflight,
            "routing": routing,
            "robustness": robustness,
            "fairness": fairness,
            "tracing": tracing,
            "blackbox": blackbox,
            "structured": structured,
            "compile": compile_ph,
            "saturation": saturation,
            "kvflow": kvflow,
            "hydration": hydration,
            "kvquant": kvquant,
            "peer": peer,
            "rebalance": rebalance,
            "fleet": fleet,
            "fleet_scale": fleet_scale,
            "total_elapsed_s": round(time.monotonic() - _t_start, 1),
        }
        out["regressions"] = _regression_gate(out)
        print(json.dumps(out), flush=True)
        return

    # 1) cheap + fast: guarantees the tail is never empty
    micro = _run_phase("microbench", ["bench.py", "--phase", "micro"],
                       timeout_s=720, key="microbench")

    # 2) THE HEADLINE: the served stack (real router + engine processes),
    #    closed-loop saturation + open-loop offered-QPS @ 2 req/s.
    #    The child's --budget-s is derived FROM the kill window (minus
    #    teardown slack) so it always plans to finish before the parent
    #    would signal its group.
    live_cap = min(1620.0, _remaining() - 30.0)
    livestack = _run_phase(
        "livestack",
        ["bench_livestack.py", "--budget-s", str(max(0.0, live_cap - 120.0))],
        timeout_s=live_cap, key="livestack", min_needed_s=420.0,
    )

    # 3) the reference's headline model on ONE 16 GiB chip via int8 —
    # BEFORE the in-process ceiling: if the global budget runs short, the
    # 8B capture (a verdict ask) survives and the attribution-only
    # northstar is what gets skipped. Prefill stays on the XLA path until
    # the paged flash-prefill kernel's on-chip sweep lands — decode uses
    # the chip-validated Pallas kernel that makes 8B-class decode fit
    int8_8b = _run_phase(
        "int8_8b",
        ["bench_northstar.py", "--model", "llama-3-8b",
         "--quantization", "int8", "--users", "8", "--rounds", "3",
         "--block-size", "32", "--attention-backend", "pallas",
         "--prefill-attention-backend", "xla", "--kv-cache-dtype", "fp8",
         "--num-blocks", "1600", "--max-model-len", "6144"],
        timeout_s=1000, key="northstar", min_needed_s=300.0,
    )

    # 3b) the fp8-KV-pool arm's `auto` (bf16-pool) counterpart — the
    # ROADMAP item-4 datapoint: same 8B workload, pool at bf16, so only
    # HALF the blocks fit in the same HBM slice (800 x 32 = 25.6k tokens
    # vs fp8's 51.2k). Reported next to int8_8b: decode tok/s, effective
    # KV token capacity, and prefix hit rate quantify what fp8 KV buys
    int8_8b_kvauto = _run_phase(
        "int8_8b_kvauto",
        ["bench_northstar.py", "--model", "llama-3-8b",
         "--quantization", "int8", "--users", "8", "--rounds", "3",
         "--block-size", "32", "--attention-backend", "pallas",
         "--prefill-attention-backend", "xla", "--kv-cache-dtype", "auto",
         "--num-blocks", "800", "--max-model-len", "6144"],
        timeout_s=1000, key="northstar", min_needed_s=300.0,
    )

    # 4) in-process ceiling on the same workload shape (attribution)
    northstar = _run_phase("northstar", ["bench_northstar.py"],
                           timeout_s=800, key="northstar",
                           min_needed_s=240.0)

    served = livestack.get("req_per_s") or 0.0
    open_loop = livestack.get("open_loop") or {}
    out = {
        "metric": "served_northstar_throughput",
        "value": served,
        "unit": "req/s",
        "vs_baseline": round(served / SERVED_BASELINE_REQ_S, 3),
        "served_ttft_p50_s": livestack.get("ttft_p50_s"),
        "served_ttft_p90_s": livestack.get("ttft_p90_s"),
        "open_loop_qps": open_loop.get("offered_qps"),
        "open_loop_ttft_p50_s": open_loop.get("ttft_p50_s"),
        "open_loop_ttft_p90_s": open_loop.get("ttft_p90_s"),
        "livestack": livestack,
        "northstar": northstar,
        "int8_8b": int8_8b,
        "int8_8b_kvauto": int8_8b_kvauto,
        "microbench": micro,
        "routing": routing,
        "robustness": robustness,
        "fairness": fairness,
        "tracing": tracing,
        "blackbox": blackbox,
        "structured": structured,
        "compile": compile_ph,
        "saturation": saturation,
        "kvflow": kvflow,
        "hydration": hydration,
        "kvquant": kvquant,
        "peer": peer,
        "rebalance": rebalance,
        "fleet": fleet,
        "fleet_scale": fleet_scale,
        "total_elapsed_s": round(time.monotonic() - _t_start, 1),
    }
    out["regressions"] = _regression_gate(out)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
