"""XLA compile telemetry (docs/42-compile-telemetry.md): the program
inventory's bounding and bookkeeping, trigger classification on a real
engine, storm-window arithmetic under an injected clock, the
/debug/programs surface, compile_stall attribution on the blocked
request's trace timeline, exporter label cardinality against the closed
contract sets, and the watch-disabled no-op path."""

import asyncio
import re

import pytest
from aiohttp.test_utils import TestClient, TestServer

from vllm_production_stack_tpu.engine.compile_watch import (
    DEFAULT_CAPACITY, CompileWatch,
)
from vllm_production_stack_tpu.engine.config import EngineConfig
from vllm_production_stack_tpu.engine.engine import LLMEngine
from vllm_production_stack_tpu.engine.request import SamplingParams
from vllm_production_stack_tpu.engine.server import EngineServer

pytestmark = pytest.mark.compilewatch

GREEDY = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def _shutdown(eng: LLMEngine) -> None:
    eng.runner.shutdown(wait=True)
    if getattr(eng, "draft_runner", None) is not None:
        eng.draft_runner.shutdown(wait=True)


# -- unit: inventory + dispatch bookkeeping -----------------------------------


def test_inventory_bounded_fifo_and_dispatch_counts():
    w = CompileWatch(capacity=4)
    for i in range(6):
        w.record_build("prefill", ("prefill", i), 0.01 * (i + 1),
                       "warmup", rid=f"r{i}")
    inv = w.debug_payload()["programs"]
    assert len(inv) == 4  # FIFO at capacity: the two oldest evicted
    keys = [e["key"] for e in inv]
    assert "('prefill', 0)" not in keys and "('prefill', 5)" in keys
    # re-building a known key updates in place, never duplicates
    w.record_build("prefill", ("prefill", 5), 0.5, "mid_traffic")
    inv = w.debug_payload()["programs"]
    assert len(inv) == 4
    entry = next(e for e in inv if e["key"] == "('prefill', 5)")
    assert entry["trigger"] == "mid_traffic"
    assert entry["compile_wall_s"] == 0.5
    # dispatches charge the served key; hit/miss totals are global
    w.record_dispatch(("prefill", 5), hit=True)
    w.record_dispatch(("prefill", 5), hit=False)
    w.record_dispatch(("prefill", 999), hit=False)  # unknown key: counted
    p = w.debug_payload()
    entry = next(e for e in p["programs"] if e["key"] == "('prefill', 5)")
    assert entry["dispatches"] == 2
    assert p["cache"] == {"hits": 1, "misses": 2}
    assert DEFAULT_CAPACITY >= 256  # holds a full warmup lattice


def test_stats_snapshot_drains_walls_once():
    w = CompileWatch()
    w.record_build("decode", ("decode", 1), 0.2, "bg")
    w.record_build("decode", ("decode", 2), 0.3, "mid_traffic")
    s1 = w.stats_snapshot()
    assert sorted(s1["walls"]) == [0.2, 0.3]
    assert s1["mid_traffic"] == 1
    s2 = w.stats_snapshot()
    assert s2["walls"] == []  # each observation exported exactly once
    assert s2["compiles"] == s1["compiles"]  # counters stay monotonic


# -- unit: storm window arithmetic under an injected clock --------------------


def test_storm_window_edge_trigger_and_rearm():
    clk = FakeClock()
    w = CompileWatch(storm_threshold=3, storm_window_s=10.0, clock=clk)
    for i in range(3):
        clk.t = float(i)
        w.record_build("prefill", ("prefill", 64, i), 0.1, "mid_traffic",
                       rid=f"r{i}")
    assert w.storms_total == 1
    report = w.last_storm_report
    assert report["mid_traffic_compiles"] == 3
    assert report["threshold"] == 3 and report["window_s"] == 10.0
    named = [s["key"] for s in report["shapes"]]
    assert "('prefill', 64, 0)" in named  # the offending shapes are NAMED
    # further builds inside the live episode: no second report
    clk.t = 4.0
    w.record_build("decode", ("decode", 4), 0.1, "mid_traffic")
    assert w.storms_total == 1
    # window drains below threshold -> episode re-arms -> next burst trips
    clk.t = 20.0
    w.record_build("decode", ("decode", 20), 0.1, "mid_traffic")
    assert w.storms_total == 1  # 1 event in window: re-armed, not tripped
    clk.t = 21.0
    w.record_build("decode", ("decode", 21), 0.1, "mid_traffic")
    clk.t = 22.0
    w.record_build("decode", ("decode", 22), 0.1, "mid_traffic")
    assert w.storms_total == 2


def test_storm_counts_only_mid_traffic_xla_phases():
    clk = FakeClock()
    w = CompileWatch(storm_threshold=2, storm_window_s=100.0, clock=clk)
    # warmup/bg builds and grammar-table builds never enter the window
    for i in range(5):
        w.record_build("prefill", ("prefill", i), 0.1, "warmup")
        w.record_build("decode", ("decode", i), 0.1, "bg")
        w.record_build("grammar", ("grammar", i), 0.01, "mid_traffic")
    assert w.storms_total == 0
    assert w.stats_snapshot()["mid_traffic"] == 5  # counted, just not stormy


# -- engine: trigger classification on the real dispatch path -----------------


def test_cold_engine_classifies_sync_compiles_as_mid_traffic():
    """Also hosts the exporter-cardinality assertions (closed label sets,
    seeded at zero) — same cold engine, and an XLA compile per engine is
    the expensive part of this module."""
    from vllm_production_stack_tpu import metrics_contract as mc
    from vllm_production_stack_tpu.engine.metrics import EngineMetrics

    eng = LLMEngine(EngineConfig.tiny())
    try:
        eng.generate([[5, 6, 7, 8]], GREEDY)
        snap = eng.compile_watch.stats_snapshot()
        mid = {k: v for k, v in snap["compiles"].items()
               if k.endswith("/mid_traffic")}
        assert sum(mid.values()) >= 1  # cold prefill compiled on-path
        assert any(k.startswith("prefill/") for k in mid)
        assert snap["misses"] >= 1  # a sync compile is never a cache hit
        text = EngineMetrics("tiny-llama").render(eng.stats()).decode()
    finally:
        _shutdown(eng)
    base = mc.ENGINE_COMPILES[: -len("_total")]
    pairs = set(re.findall(
        re.escape(base) + r'_total\{[^}]*phase="([a-z_]+)"[^}]*'
        r'trigger="([a-z_]+)"', text,
    ))
    want = {(p, t) for p in mc.COMPILE_PHASE_VALUES
            for t in mc.COMPILE_TRIGGER_VALUES}
    assert pairs == want  # seeded full product, nothing outside the sets
    for name in (mc.ENGINE_COMPILE_SECONDS + "_bucket",
                 mc.ENGINE_PROGRAM_CACHE_PROGRAMS,
                 mc.ENGINE_PROGRAM_CACHE_HITS[: -len("_total")] + "_total",
                 mc.ENGINE_PROGRAM_CACHE_MISSES[: -len("_total")] + "_total",
                 mc.ENGINE_COMPILE_STORMS[: -len("_total")] + "_total"):
        assert name in text, name


def test_warmup_trigger_and_steady_state_hits():
    # minimal bucket lattice: warmup cost scales with program count, and
    # trigger classification needs only one warmed shape to hit
    from vllm_production_stack_tpu.engine.config import SchedulerConfig

    cfg = EngineConfig.tiny().replace(
        scheduler=SchedulerConfig(
            max_num_seqs=2, max_num_batched_tokens=16,
            decode_buckets=(2,), prefill_buckets=(16,),
        ),
    )
    eng = LLMEngine(cfg)
    try:
        eng.warmup(scope="coarse")
        snap0 = eng.compile_watch.stats_snapshot()
        warm = sum(v for k, v in snap0["compiles"].items()
                   if k.endswith("/warmup"))
        assert warm >= 1
        assert snap0["mid_traffic"] == 0  # warmup is not mid-traffic
        # traffic into the warmed lattice: zero NEW mid-traffic compiles
        eng.generate([[5, 6, 7, 8], [9, 10, 11]], GREEDY)
        snap1 = eng.compile_watch.stats_snapshot()
        assert snap1["mid_traffic"] == 0
        assert snap1["hits"] + snap1["misses"] > snap0["hits"] + snap0["misses"]
    finally:
        _shutdown(eng)


# -- server: /debug/programs + trace attribution ------------------------------


def _run_with_client(srv: EngineServer, coro_fn):
    async def runner():
        client = TestClient(TestServer(srv.build_app()))
        await client.start_server()
        try:
            return await coro_fn(client)
        finally:
            await client.close()

    return asyncio.run(runner())


def test_debug_programs_shape_and_stall_attribution():
    """One COLD request through the real server: the sync compile it eats
    must surface twice — as an inventory entry on /debug/programs and as
    a compile_stall event on ITS OWN trace timeline."""
    eng = LLMEngine(EngineConfig.tiny())
    srv = EngineServer(eng, served_model_name="tiny-llama")

    async def go(client):
        r = await client.post(
            "/v1/completions",
            json={"model": "tiny-llama", "prompt": [5, 6, 7, 8],
                  "max_tokens": 4, "temperature": 0.0, "ignore_eos": True},
            headers={"X-Request-Id": "cw-stall"},
        )
        assert r.status == 200
        d = await client.get("/debug/programs")
        t = await client.get("/debug/requests?rid=cw-stall")
        idx = await client.get("/debug")
        return await d.json(), await t.json(), await idx.json()

    try:
        programs, trace, index = _run_with_client(srv, go)
    finally:
        _shutdown(eng)
    assert "GET /debug/programs" in index["endpoints"]
    assert programs["programs"]
    entry = programs["programs"][0]
    for field in ("key", "phase", "role", "trigger", "compile_wall_s",
                  "dispatches", "last_used_age_s", "rid", "hbm_bytes"):
        assert field in entry, field
    # the cold prefill build is attributed to the request it blocked
    stalled = [e for e in programs["programs"]
               if e["trigger"] == "mid_traffic" and e["rid"] == "cw-stall"]
    assert stalled, programs["programs"]
    events = [e for s in trace["spans"] for e in s["events"]
              if e["name"] == "compile_stall"]
    assert events, trace
    assert events[0]["attrs"]["phase"] in ("prefill", "decode", "verify")
    assert "wall_ms" in events[0]["attrs"]
    # the flight recorder ring saw the same stall
    notes = [n for n in eng.flightrec.snapshot()
             if n.get("event") == "compile_stall"]
    assert notes and notes[0].get("rid") == "cw-stall"
