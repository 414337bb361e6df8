"""Flight recorder, thread-liveness watchdog, and crash postmortems
(docs/37-flight-recorder.md, engine/flightrec.py).

Layers:

* unit: ring bounding / disabled no-op, the dispatch→resolve liveness
  cursor, heartbeat busy-vs-idle staleness, closed-set enforcement,
  watchdog episode semantics (one trip per wedge, recovery clears),
  postmortem redaction;
* engine integration: the step loop writes dispatch/resolve records on
  BOTH loops and leaves no outstanding cursor at quiescence;
* server: GET /debug index, GET /debug/flight round-trip, POST
  /debug/postmortem (inline and file-backed), /ready flips on a stall
  while /health liveness stays green;
* chaos (marker `chaos`): the watchdog NAMES a fetcher stalled under the
  disk-tier lock and a publisher blackholed mid-resync — the two wedge
  shapes that kept the on-chip bench dark since r04;
* router/controller: the event-loop lag probe exports
  tpu:router_event_loop_lag_seconds and GET /debug lists the surface.
"""

from __future__ import annotations

import asyncio
import json
import os
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from vllm_production_stack_tpu import metrics_contract as mc
from vllm_production_stack_tpu.engine.config import EngineConfig
from vllm_production_stack_tpu.engine.engine import LLMEngine
from vllm_production_stack_tpu.engine.flightrec import (
    EventLoopLagProbe,
    FlightRecorder,
    Heartbeat,
    PostmortemDumper,
    ThreadRegistry,
    Watchdog,
    build_postmortem,
    redact,
    thread_stacks,
    write_postmortem,
)
from vllm_production_stack_tpu.engine.request import SamplingParams
from vllm_production_stack_tpu.engine.server import EngineServer
from vllm_production_stack_tpu.testing import faults
from vllm_production_stack_tpu.testing.serial_loop import serial_engine

pytestmark = pytest.mark.flightrec


# -- FlightRecorder ----------------------------------------------------------

def test_ring_bounds_and_sequence():
    fr = FlightRecorder(capacity=4)
    for i in range(10):
        seq = fr.dispatch("decode", rows=2, tokens=8, waiting=i)
        fr.resolve(seq)
    snap = fr.snapshot()
    assert len(snap) == 4  # bounded: oldest dropped
    assert fr.records_total == 20
    assert snap[-1]["event"] == "resolve"
    assert fr.outstanding_age_s() is None


def test_resolving_older_seq_keeps_newer_outstanding():
    # the pipelined loop dispatches step N+1 BEFORE resolving step N —
    # resolving N must not clear N+1's cursor
    fr = FlightRecorder()
    s1 = fr.dispatch("decode", rows=1, tokens=4)
    s2 = fr.dispatch("decode", rows=1, tokens=4)
    fr.resolve(s1)
    out = fr.outstanding_age_s()
    assert out is not None and out[1] == "decode"
    fr.resolve(s2)
    assert fr.outstanding_age_s() is None


def test_discard_and_fault_clear_the_cursor():
    fr = FlightRecorder()
    seq = fr.dispatch("verify", rows=1, tokens=3)
    fr.discard(seq)
    assert fr.outstanding_age_s() is None
    fr.dispatch("decode", rows=1, tokens=4)
    fr.fault("boom")
    assert fr.outstanding_age_s() is None
    events = [r["event"] for r in fr.snapshot()]
    assert "rollback" in events and "fault" in events


# -- Heartbeat / ThreadRegistry ----------------------------------------------

def test_idle_heartbeat_is_never_stale():
    hb = Heartbeat("step", stall_after_s=0.01)
    hb.idle()
    time.sleep(0.05)
    assert hb.age_s() > 0.01 and not hb.stale()  # parked, not wedged
    hb.beat()
    time.sleep(0.05)
    assert hb.stale()  # busy and silent past the threshold


def test_registry_rejects_names_outside_the_closed_set():
    reg = ThreadRegistry()
    with pytest.raises(ValueError):
        reg.register("bogus-thread")


def test_registry_reregister_refreshes_not_duplicates():
    reg = ThreadRegistry()
    a = reg.register("step", stall_after_s=5.0)
    b = reg.register("step", stall_after_s=9.0)
    assert a is b and a.stall_after_s == 9.0
    reg.unregister("step")
    assert reg.ages() == {}


def test_default_threshold_follows_the_knob():
    reg = ThreadRegistry(default_stall_after_s=120.0)
    step = reg.register("step")  # registry default
    bg = reg.register("bg_compile", stall_after_s=900.0)  # explicit
    reg.set_default_stall_after_s(2.0)
    assert step.stall_after_s == 2.0
    assert bg.stall_after_s == 900.0


# -- Watchdog ----------------------------------------------------------------

def test_watchdog_names_stale_thread_once_per_episode():
    reg = ThreadRegistry()
    hb = reg.register("hydration_fetch", stall_after_s=0.02)
    stalls = []
    wd = Watchdog(reg, interval_s=0.01, on_stall=stalls.append)
    hb.beat()
    time.sleep(0.05)
    report = wd.check()
    assert report is not None
    finding = report["findings"][0]
    assert finding["thread"] == "hydration_fetch"
    assert finding["kind"] == "stale_heartbeat"
    assert wd.stall_counts["stale_heartbeat"] == 1
    # a persisting wedge is ONE episode, not one trip per check round
    wd.check()
    wd.check()
    assert wd.stall_counts["stale_heartbeat"] == 1
    assert len(stalls) == 1
    # recovery clears; a NEW wedge is a new episode
    hb.idle()
    assert wd.check() is None and wd.stalled is None
    hb.beat()
    time.sleep(0.05)
    assert wd.check() is not None
    assert wd.stall_counts["stale_heartbeat"] == 2
    assert wd.stall_episodes == 2


def test_watchdog_unresolved_step_detection():
    reg = ThreadRegistry()
    fr = FlightRecorder()
    wd = Watchdog(reg, recorder=fr, stall_after_s=0.02)
    seq = fr.dispatch("decode", rows=4, tokens=32)
    time.sleep(0.05)
    report = wd.check()
    assert report is not None
    kinds = {f["kind"] for f in report["findings"]}
    assert kinds == {"unresolved_step"}
    assert report["findings"][0]["thread"] == "step"
    fr.resolve(seq)
    assert wd.check() is None


def test_watchdog_thread_start_stop():
    reg = ThreadRegistry()
    wd = Watchdog(reg, interval_s=0.01)
    wd.start()
    time.sleep(0.05)
    assert "watchdog" in reg.ages()  # the watchdog beats its own heart
    wd.stop()
    assert "watchdog" not in reg.ages()


# -- postmortems -------------------------------------------------------------

def test_redact_masks_secret_shaped_keys_recursively():
    doc = {
        "tenants": {"acme": {"api_key": "sk-acme-SECRET", "weight": 2}},
        "headers": [{"Authorization": "Bearer abc"}],
        "env": {"KV_CONTROLLER_API_KEY": "k", "JAX_PLATFORMS": "cpu"},
    }
    red = redact(doc)
    assert red["tenants"]["acme"]["api_key"] == "[redacted]"
    assert red["tenants"]["acme"]["weight"] == 2
    assert red["headers"][0]["Authorization"] == "[redacted]"
    assert red["env"]["KV_CONTROLLER_API_KEY"] == "[redacted]"
    assert red["env"]["JAX_PLATFORMS"] == "cpu"
    assert "SECRET" not in json.dumps(red)


def test_write_postmortem_file_is_valid_redacted_json(tmp_path, monkeypatch):
    monkeypatch.setenv("KV_CONTROLLER_API_KEY", "super-secret-bearer")
    fr = FlightRecorder()
    fr.dispatch("prefill", rows=1, tokens=64)
    reg = ThreadRegistry()
    reg.register("step").beat()
    path, doc = write_postmortem(
        str(tmp_path), "watchdog", "test wedge", recorder=fr, registry=reg,
        sections={"tenants": {"acme": {"api_key": "sk-tenant-key"}}},
    )
    assert os.path.isfile(path)
    on_disk = json.loads(open(path, encoding="utf-8").read())
    assert on_disk == doc
    assert on_disk["trigger"] == "watchdog"
    assert on_disk["flight"][0]["event"] == "dispatch"
    assert on_disk["heartbeats"]["step"]["busy"] is True
    assert on_disk["outstanding_step"]["kind"] == "prefill"
    # the dying threads' stacks are in the file (this test's own frame is)
    assert any("MainThread" in name for name in on_disk["threads"])
    # tenant keys and bearer env both redacted
    assert on_disk["tenants"]["acme"]["api_key"] == "[redacted]"
    assert on_disk["env"]["KV_CONTROLLER_API_KEY"] == "[redacted]"
    assert "super-secret-bearer" not in open(path, encoding="utf-8").read()


def test_dumper_without_dir_builds_inline():
    d = PostmortemDumper(out_dir="", context_fn=lambda: {"extra": 1})
    path, doc = d.dump("manual", "no dir configured")
    assert path is None and doc["extra"] == 1 and d.dumps_written == 0


def test_build_postmortem_survives_broken_context():
    d = PostmortemDumper(context_fn=lambda: 1 / 0)
    _, doc = d.dump("manual", "x")
    assert "context_error" in doc


def test_thread_stacks_cover_live_threads():
    done = threading.Event()
    t = threading.Thread(target=done.wait, name="stack-probe", daemon=True)
    t.start()
    try:
        stacks = thread_stacks()
        assert "stack-probe" in stacks
        assert any("wait" in line for line in stacks["stack-probe"])
    finally:
        done.set()
        t.join(timeout=2)


# -- engine integration ------------------------------------------------------

@pytest.mark.parametrize("pipelined", [False, True])
def test_step_loop_writes_records_both_loops(pipelined):
    engine = (LLMEngine if pipelined else serial_engine)(EngineConfig.tiny())
    engine.generate(
        [[1, 2, 3, 4, 5]],
        SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True),
    )
    events = [r["event"] for r in engine.flightrec.snapshot()]
    assert "dispatch" in events and "resolve" in events
    # every dispatch carries the decision summary the black box is for
    d = next(r for r in engine.flightrec.snapshot()
             if r["event"] == "dispatch")
    assert d["kind"] in ("prefill", "decode", "verify")
    assert {"rows", "tokens", "waiting", "running", "pool_usage"} <= set(d)
    # quiescence: nothing dispatched-but-unresolved
    assert engine.flightrec.outstanding_age_s() is None


# -- engine server surface ---------------------------------------------------

def _run_with_client(srv: EngineServer, coro_fn):
    async def runner():
        client = TestClient(TestServer(srv.build_app()))
        await client.start_server()
        try:
            return await coro_fn(client)
        finally:
            await client.close()

    return asyncio.run(runner())


@pytest.fixture(scope="module")
def tiny_engine():
    return LLMEngine(EngineConfig.tiny())


def test_debug_index_lists_every_debug_endpoint(tiny_engine):
    srv = EngineServer(tiny_engine, served_model_name="tiny-llama")

    async def go(client):
        return await (await client.get("/debug")).json()

    body = _run_with_client(srv, go)
    listed = set(body["endpoints"])
    # the index and the route table cannot drift: every mounted /debug
    # route appears, with a one-liner
    for ep in ("GET /debug/timing", "GET /debug/hydration",
               "GET /debug/requests", "GET /debug/flight",
               "POST /debug/postmortem", "POST /debug/profile/start"):
        assert ep in listed
    assert all(body["endpoints"][k] for k in listed)


def test_debug_flight_roundtrip_and_postmortem(tiny_engine, tmp_path):
    srv = EngineServer(
        tiny_engine, served_model_name="tiny-llama",
        postmortem_dir=str(tmp_path),
    )

    async def go(client):
        r = await client.post("/v1/completions", json={
            "model": "tiny-llama", "prompt": "hello there",
            "max_tokens": 4, "temperature": 0.0,
        })
        assert r.status == 200
        flight = await (await client.get("/debug/flight")).json()
        pm = await (await client.post("/debug/postmortem")).json()
        metrics = await (await client.get("/metrics")).text()
        return flight, pm, metrics

    flight, pm, metrics = _run_with_client(srv, go)
    # the live black box: records + heartbeat table + watchdog state
    events = [r["event"] for r in flight["flight"]]
    assert "dispatch" in events and "resolve" in events
    assert "step" in flight["heartbeats"]
    assert flight["watchdog"]["stalled"] is None
    # the on-demand dump landed as a file and carries the same ring
    assert pm["status"] == "written"
    doc = json.loads(open(pm["path"], encoding="utf-8").read())
    assert doc["trigger"] == "manual"
    assert [r["event"] for r in doc["flight"]][: len(events)] == events
    assert doc["config"]["fingerprint"] == tiny_engine.model_fingerprint
    assert "timing" in doc and "heartbeats" in doc
    # liveness series render with the closed label sets
    assert 'tpu:thread_heartbeat_age_seconds{' in metrics
    for thread in mc.THREAD_NAME_VALUES:
        assert f'thread="{thread}"' in metrics
    for kind in mc.STALL_KIND_VALUES:
        assert f'kind="{kind}"' in metrics


@pytest.mark.chaos
def test_frozen_step_loop_flips_ready_never_health(tiny_engine, tmp_path):
    """Wedge 3 of the blackbox bench, in-tree: freeze the step loop with
    the chaos harness while a request is in flight — the watchdog names
    thread=step, /ready flips 503 with the stall report, /health stays
    green, a postmortem lands; releasing the wedge recovers."""
    srv = EngineServer(
        tiny_engine, served_model_name="tiny-llama",
        watchdog_interval_s=0.05, watchdog_stall_s=0.4,
        postmortem_dir=str(tmp_path),
    )

    async def go(client):
        engine = srv.engine
        with faults.frozen_step_loop(engine):
            # SSE headers come back before the first (never-arriving)
            # token, so this returns while the step thread is frozen
            resp = await client.post("/v1/completions", json={
                "model": "tiny-llama", "prompt": "wedge me",
                "max_tokens": 64, "temperature": 0.0, "stream": True,
            })
            assert resp.status == 200
            stalled = None
            for _ in range(100):
                ready = await client.get("/ready")
                if ready.status == 503:
                    body = await ready.json()
                    if body.get("reason") == "stalled":
                        stalled = body["stall"]
                        break
                await asyncio.sleep(0.1)
            assert stalled is not None, "watchdog never named the stall"
            threads = {f["thread"] for f in stalled["findings"]}
            assert "step" in threads
            health = await client.get("/health")
            assert health.status == 200  # liveness NEVER flips on a stall
            resp.close()
        # release: the step thread resumes, the stall clears
        for _ in range(100):
            ready = await client.get("/ready")
            if ready.status == 200:
                break
            await asyncio.sleep(0.1)
        assert ready.status == 200
        flight = await (await client.get("/debug/flight")).json()
        return flight

    flight = _run_with_client(srv, go)
    assert flight["watchdog"]["counts"]["stale_heartbeat"] >= 1
    assert flight["postmortems"]["written"] >= 1
    doc = json.loads(
        open(flight["postmortems"]["last_path"], encoding="utf-8").read()
    )
    assert doc["trigger"] == "watchdog"
    assert "engine-step" in doc["threads"]  # the frozen thread's stack


# -- chaos: the named-wedge suite --------------------------------------------

@pytest.mark.chaos
def test_watchdog_names_fetcher_stalled_under_disk_lock(tmp_path):
    """Wedge 1: the hydration fetcher blocks under the disk-tier lock.
    The watchdog must name thread=hydration_fetch (stale while BUSY) and
    the postmortem must capture it; releasing the lock recovers."""
    from vllm_production_stack_tpu.engine.hydration import (
        HydrationChunk,
        HydrationPlan,
    )

    cfg = EngineConfig.tiny()
    cfg = cfg.replace(cache=__import__("dataclasses").replace(
        cfg.cache, disk_kv_dir=str(tmp_path / "disk"), disk_kv_gib=0.1,
    ))
    engine = LLMEngine(cfg)
    hyd = engine.hydrator
    assert hyd is not None
    disk = engine.host_tier.disk
    hb = engine.threads.register("hydration_fetch", stall_after_s=0.2)
    wd = Watchdog(engine.threads, recorder=engine.flightrec,
                  interval_s=0.05)
    chunk = HydrationChunk(
        index=0, start_block=0, hashes=[12345], tiers=["disk"],
        decision="load",
    )
    plan = HydrationPlan("req-x", [chunk], block_size=8,
                         deadline=time.monotonic() + 60.0, estimates={})
    with faults.hold_lock(disk._mu):
        hyd._ensure_thread()
        hyd._q.put((plan, chunk))
        deadline = time.monotonic() + 5.0
        report = None
        while time.monotonic() < deadline:
            report = wd.check()
            if report is not None:
                break
            time.sleep(0.05)
        assert report is not None, "fetcher stall never detected"
        assert {f["thread"] for f in report["findings"]} == {
            "hydration_fetch"
        }
        assert hb.busy
        doc = build_postmortem(
            "watchdog", "fetcher wedge", recorder=engine.flightrec,
            registry=engine.threads,
        )
        assert doc["heartbeats"]["hydration_fetch"]["stale"] is True
    # lock released: the fetch completes (as a miss) and the stall clears
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and wd.check() is not None:
        time.sleep(0.05)
    assert wd.check() is None
    hyd.close()


@pytest.mark.chaos
def test_watchdog_names_blackholed_publisher(tmp_path):
    """Wedge 2: the KV event publisher's resync snapshot POST lands in a
    black hole (accepts TCP, never answers). With the per-POST timeout
    wider than the heartbeat threshold the round hangs mid-resync and the
    watchdog must name thread=kv_event_publisher."""
    from vllm_production_stack_tpu.engine.kv_events import (
        KVEventLog,
        KVEventPublisher,
    )

    async def go():
        import aiohttp

        server, port = await faults.black_hole()
        reg = ThreadRegistry()
        hb = reg.register("kv_event_publisher", stall_after_s=0.3)
        wd = Watchdog(reg, interval_s=0.05)
        log = KVEventLog()
        log.emit_admit(1, 0)

        async def snapshot():
            return log.epoch, log.snapshot_mark(), [1]

        session = aiohttp.ClientSession()
        pub = KVEventPublisher(
            [f"http://127.0.0.1:{port}"], "http://e:8000", log, snapshot,
            16, lambda: session, interval_s=0.05, send_timeout_s=30.0,
            heartbeat=hb,
        )
        pub.start()
        try:
            report = None
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                report = wd.check()
                if report is not None:
                    break
                await asyncio.sleep(0.05)
            assert report is not None, "publisher stall never detected"
            assert {f["thread"] for f in report["findings"]} == {
                "kv_event_publisher"
            }
            path, doc = write_postmortem(
                str(tmp_path), "watchdog", "publisher blackholed",
                registry=reg,
            )
            assert json.loads(open(path).read())["heartbeats"][
                "kv_event_publisher"
            ]["stale"] is True
        finally:
            await pub.stop()
            await session.close()
            server.close()
            await server.wait_closed()

    asyncio.run(go())


# -- router / controller -----------------------------------------------------

def test_event_loop_lag_probe_decaying_peak():
    probe = EventLoopLagProbe(interval_s=0.05)
    probe._observe(2.0)
    assert probe.lag_s == 2.0
    probe._observe(0.0)  # peak decays toward the new reading, not to it
    assert 0.0 < probe.lag_s <= 2.0
    snap = probe.snapshot()
    assert snap["ticks"] == 2


def test_router_exports_loop_lag_and_debug_index():
    from vllm_production_stack_tpu.router.app import build_app
    from vllm_production_stack_tpu.router.args import parse_args

    async def go():
        app = build_app(parse_args([
            "--service-discovery", "static",
            "--static-backends", "http://127.0.0.1:1",
            "--health-probe-interval", "0",
        ]))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            # at least one probe tick (default interval 0.5s)
            await asyncio.sleep(0.7)
            idx = await (await client.get("/debug")).json()
            loop_dbg = await (await client.get("/debug/loop")).json()
            metrics = await (await client.get("/metrics")).text()
            return idx, loop_dbg, metrics
        finally:
            await client.close()

    idx, loop_dbg, metrics = asyncio.run(go())
    assert "GET /debug/fleet" in idx["endpoints"]
    assert "GET /debug/loop" in idx["endpoints"]
    assert loop_dbg["ticks"] >= 1
    assert mc.ROUTER_EVENT_LOOP_LAG in metrics


def test_controller_renders_loop_lag():
    from vllm_production_stack_tpu.engine.kv_controller import KVController

    async def go():
        c = KVController([], mode="fanout")
        client = TestClient(TestServer(c.build_app()))
        await client.start_server()
        try:
            await asyncio.sleep(0.1)
            return await (await client.get("/metrics")).text()
        finally:
            await client.close()

    metrics = asyncio.run(go())
    assert mc.ROUTER_EVENT_LOOP_LAG in metrics


# -- contract ----------------------------------------------------------------

def test_liveness_names_in_contract_checker():
    """The new names ride the same drift gate as everything else."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from tools.check_metrics_contract import check

    assert check() == []
