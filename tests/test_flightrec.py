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

# -- dry gaps: the newest enqueued program, polled at the phase boundaries ---

class FakeClock:
    """Stands in for the `time` module inside flightrec: the test moves it."""

    def __init__(self):
        self.ns = 1_000_000_000

    def monotonic_ns(self):
        return self.ns

    def monotonic(self):
        return self.ns * 1e-9

    def time(self):
        return 1.7e9 + self.ns * 1e-9

    def time_ns(self):
        return int(1.7e18) + self.ns

    def tick(self, ms: float) -> None:
        self.ns += int(ms * 1e6)


class FakeDevice:
    """A program that is complete from `done_at` (ns) on, and counts how
    often it was asked."""

    def __init__(self, clock, done_at=None):
        self.clock, self.done_at, self.asked = clock, done_at, 0

    def ready(self):
        self.asked += 1
        return self.done_at is not None and self.clock.ns >= self.done_at


@pytest.fixture
def clock(monkeypatch):
    from vllm_production_stack_tpu.engine import flightrec

    fake = FakeClock()
    monkeypatch.setattr(flightrec, "time", fake)
    return fake


def _phase(rec, clock, phase, ms):
    with rec.span(phase):
        clock.tick(ms)


def _dry(rec):
    t = rec.timing()
    return {k: v for k, v in t.items() if k.startswith("dry_")}


def test_dry_gap_brackets_the_true_instant_and_splits_by_phase(clock):
    from vllm_production_stack_tpu.engine.flightrec import PHASES

    rec = FlightRecorder()
    c = rec.dispatch("prefill", rows=1, tokens=8)
    dev = FakeDevice(clock)
    d = rec.dispatch("decode", rows=4, tokens=32, ready=dev.ready)
    t_enq = clock.ns
    true_end = t_enq + int(7.3e6)  # inside `sched`, between two polls
    dev.done_at = true_end
    _phase(rec, clock, "post", 3.0)  # still running: polls say no
    _phase(rec, clock, "admit", 1.0)
    _phase(rec, clock, "sched", 5.0)  # completes 3.3 ms into it
    _phase(rec, clock, "build", 4.0)  # the first boundary after: seen here
    with rec.span("build"):
        with rec.span("h2d"):
            clock.tick(2.0)
        with rec.span("enqueue"):
            clock.tick(1.0)
    e = rec.dispatch("prefill", rows=1, tokens=8, ready=lambda: False)
    t_close = clock.ns
    t = rec.timing()
    true_dry = (t_close - true_end) * 1e-9
    assert t["dry_n"] == 1 == t["dry_after_decode_n"]
    assert t["dry_after_prefill_n"] == 0 == t["dry_after_verify_n"]
    assert t["dry_s"] <= true_dry <= t["dry_s"] + t["dry_slack_s"]
    # seen at the exit of `sched` (9 ms in): 4 + 2 + 1 ms of gap, bracket
    # back to the poll at its entry (4 ms in)
    assert t["dry_s"] == pytest.approx(7e-3)
    assert t["dry_slack_s"] == pytest.approx(5e-3)
    assert t["dry_build_s"] == pytest.approx(4e-3)
    assert t["dry_h2d_s"] == pytest.approx(2e-3)
    assert t["dry_enqueue_s"] == pytest.approx(1e-3)
    assert sum(t["dry_" + p + "_s"] for p in PHASES) == pytest.approx(
        t["dry_s"], rel=1e-12)
    # the host never emptied the queue itself: the other account is still
    # the one gap since `start`, which ended at c
    assert t["starved_n"] == 1 == t["starved_after_start_n"]
    last = rec.snapshot()[-1]
    assert last["seq"] == e and last["gap"] == {
        "seen": "poll", "after": "decode", "ms": 7.0, "slack_ms": 5.0,
        "phases_ms": {"build": 4.0, "h2d": 2.0, "enqueue": 1.0}}
    first = next(r for r in rec.snapshot() if r["seq"] == c)
    assert first["gap"]["seen"] == "resolve"
    assert first["gap"]["after"] == "start" and "slack_ms" not in first["gap"]
    assert d == c + 1


def test_polls_stop_after_the_first_true(clock):
    rec = FlightRecorder()
    dev = FakeDevice(clock, done_at=clock.ns + int(2e6))
    rec.dispatch("decode", rows=1, tokens=8, ready=dev.ready)
    _phase(rec, clock, "post", 1.0)  # two polls: no, no
    _phase(rec, clock, "admit", 2.0)  # no at the entry, yes at the exit
    asked = dev.asked
    assert asked == 4 and rec.timing()["dry_poll_n"] == 4
    for phase in ("sched", "build", "h2d", "enqueue"):
        _phase(rec, clock, phase, 1.0)
    rec.poll()
    assert dev.asked == asked  # nobody asks a program that was seen ready
    assert rec.timing()["dry_poll_n"] == 4
    assert rec.timing()["dry_n"] == 0  # the gap is open, not yet counted
    rec.dispatch("decode", rows=1, tokens=8)
    assert rec.timing()["dry_n"] == 1
    assert rec.timing()["dry_s"] == pytest.approx(4e-3)


def test_nothing_watched_nothing_polled(clock):
    rec = FlightRecorder()
    _phase(rec, clock, "sched", 1.0)
    rec.poll()
    a = rec.dispatch("decode", rows=1, tokens=8)  # no `ready` handed over
    _phase(rec, clock, "post", 1.0)
    rec.poll()
    rec.resolve(a)
    t = rec.timing()
    assert t["dry_poll_n"] == 0 == t["dry_poll_s"]
    assert all(v == 0 for v in _dry(rec).values())


def test_gap_seen_only_by_the_dispatch_counts_with_length_zero(clock):
    rec = FlightRecorder()
    dev = FakeDevice(clock)
    rec.dispatch("decode", rows=1, tokens=8, ready=dev.ready)
    with rec.span("build"):
        clock.tick(6.0)
        dev.done_at = clock.ns - int(1e6)  # completed 1 ms before the exit
        # ... but after this phase's last poll: the exit's is skipped here
        rec._watch, keep = None, rec._watch
    rec._watch = keep
    clock.tick(0.5)
    rec.dispatch("prefill", rows=1, tokens=8)
    t = rec.timing()
    assert t["dry_n"] == 1 and t["dry_s"] == 0.0
    assert t["dry_slack_s"] == pytest.approx(6.5e-3)  # back to the entry
    gap = rec.snapshot()[-1]["gap"]
    assert gap["seen"] == "poll" and gap["ms"] == 0.0
    assert gap["slack_ms"] == 6.5 and gap["phases_ms"] == {}


def test_dry_gap_hands_over_to_a_starvation_gap_at_the_resolve(clock):
    rec = FlightRecorder()
    dev = FakeDevice(clock, done_at=clock.ns + int(1.5e6))
    a = rec.dispatch("decode", rows=1, tokens=8, ready=dev.ready)
    _phase(rec, clock, "sched", 2.0)  # nothing to enqueue; a seen complete
    with rec.span("post", a, "decode"):
        with rec.span("sync", a, "decode"):
            clock.tick(0.25)
        rec.resolve(a)  # the host empties the queue itself
        clock.tick(1.0)
    _phase(rec, clock, "idle", 10.0)
    _phase(rec, clock, "build", 3.0)
    rec.dispatch("prefill", rows=1, tokens=8)
    t = rec.timing()
    # the dry gap: exit of `sched` -> resolve; the starvation gap from there.
    # One stretch of an empty device, one late dispatch: `starved_n` has it
    assert t["dry_n"] == 0 and t["dry_s"] == pytest.approx(0.25e-3)
    assert t["dry_slack_s"] == pytest.approx(2e-3)
    assert t["dry_sync_s"] == pytest.approx(0.25e-3)
    assert t["starved_after_decode_n"] == 1
    assert t["starved_post_s"] == pytest.approx(1e-3)
    assert t["starved_idle_s"] == pytest.approx(10e-3)
    starved = t["starved_s"] - 0.0
    # never both at once: together they cover exit-of-sched -> enqueue once
    assert t["dry_s"] + starved == pytest.approx(14.25e-3)
    for p in ("post", "idle", "build"):
        assert t["dry_" + p + "_s"] == 0.0
    assert t["starved_sync_s"] == 0.0
    resolve = next(r for r in rec.snapshot() if r["event"] == "resolve")
    assert resolve["gap"]["seen"] == "poll"
    assert rec.snapshot()[-1]["gap"]["seen"] == "resolve"
    assert rec.snapshot()[-1]["gap"]["after"] == "decode"


def test_sync_of_the_watched_step_itself_opens_no_dry_gap(clock):
    """The serial loop: every dispatch is resolved before the next one. Its
    `sync` ends because the program completed; the resolve that follows
    knows the instant, so the whole wait is nobody's slack."""
    rec = FlightRecorder()
    for _ in range(3):
        dev = FakeDevice(clock, done_at=clock.ns + int(5e6))
        a = rec.dispatch("decode", rows=1, tokens=8, ready=dev.ready)
        with rec.span("post", a, "decode"):
            with rec.span("sync", a, "decode"):
                clock.tick(5.0)
            rec.resolve(a)
        _phase(rec, clock, "build", 1.0)
    t = rec.timing()
    assert t["dry_n"] == 0 == t["dry_s"] == t["dry_slack_s"]
    assert t["starved_after_decode_n"] == 2


def test_rolled_back_step_stays_watched(clock):
    rec = FlightRecorder()
    a = rec.dispatch("decode", rows=1, tokens=8, ready=lambda: False)
    dev = FakeDevice(clock, done_at=clock.ns + int(2e6))
    b = rec.dispatch("decode", rows=1, tokens=8, ready=dev.ready)
    with rec.span("post", a, "decode"):
        rec.resolve(a)
    rec.discard(b)  # the device still runs b
    _phase(rec, clock, "sched", 3.0)  # ... and is seen to finish it
    assert dev.asked > 0
    _phase(rec, clock, "build", 2.0)
    rec.dispatch("decode", rows=1, tokens=8)
    t = rec.timing()
    assert t["dry_n"] == 1 == t["dry_after_decode_n"]
    assert t["dry_s"] == pytest.approx(2e-3)
    assert t["starved_n"] == 1  # only the gap since `start`


@pytest.mark.parametrize("drop", ["fault", "quiet"])
def test_fault_and_quiet_drop_the_watch(clock, drop):
    rec = FlightRecorder()
    a = rec.dispatch("decode", rows=1, tokens=8, ready=lambda: False)
    dev = FakeDevice(clock, done_at=clock.ns + int(1e6))
    b = rec.dispatch("decode", rows=1, tokens=8, ready=dev.ready)
    _phase(rec, clock, "sched", 2.0)  # b seen complete: a dry gap opens
    assert dev.asked == 2
    _phase(rec, clock, "post", 1.0)
    if drop == "fault":
        rec.fault("boom")
    else:
        rec.resolve(a)
        rec.discard(b)
        rec.quiet()
    _phase(rec, clock, "idle", 4.0)
    assert dev.asked == 2
    rec.dispatch("prefill", rows=1, tokens=8)
    t = rec.timing()
    # the dry gap closed where the host counted the queue empty, and the
    # starvation gap ran from there (and counts the late dispatch)
    assert t["dry_n"] == 0 and t["dry_idle_s"] == 0.0
    assert t["dry_s"] == pytest.approx(1e-3) == t["dry_post_s"]
    assert t["starved_idle_s"] == pytest.approx(4e-3)
    after = "start" if drop == "fault" else "decode"
    assert t["starved_after_" + after + "_n"] == (2 if drop == "fault" else 1)
    # a watch that was never seen ready is dropped the same way
    rec2 = FlightRecorder()
    dev2 = FakeDevice(clock)  # never completes
    a2 = rec2.dispatch("decode", rows=1, tokens=8, ready=lambda: False)
    b2 = rec2.dispatch("decode", rows=1, tokens=8, ready=dev2.ready)
    if drop == "fault":
        rec2.fault("boom")
    else:
        rec2.resolve(a2)
        rec2.discard(b2)
        rec2.quiet()
    asked = dev2.asked
    _phase(rec2, clock, "idle", 1.0)
    assert dev2.asked == asked and rec2.timing()["dry_n"] == 0


def test_a_ready_that_raises_is_dropped_not_raised(clock):
    rec = FlightRecorder()

    def deleted():
        raise RuntimeError("Array has been deleted.")

    rec.dispatch("decode", rows=1, tokens=8, ready=deleted)
    _phase(rec, clock, "sched", 1.0)
    assert rec._watch is None and rec.timing()["dry_n"] == 0


def test_turn_is_the_seven_phases_outside_sync_and_idle(clock):
    from vllm_production_stack_tpu.engine.flightrec import (
        PHASES, TURN_PHASES,
    )

    rec = FlightRecorder()
    for i, phase in enumerate(PHASES):
        _phase(rec, clock, phase, 1.0 + i)
    t = rec.timing()
    assert set(PHASES) - set(TURN_PHASES) == {"sync", "idle"}
    assert len(TURN_PHASES) == 7
    assert t["turn_s"] == pytest.approx(
        sum(t[p + "_s"] for p in PHASES) - t["sync_s"] - t["idle_s"])
    assert t["turn_s"] == pytest.approx(t["admit_s"] + t["sched_s"]
                                        + t["dispatch_s"] + t["post_s"]
                                        + t["emit_s"])


def test_flightrec_module_stays_jax_free():
    """The router and the KV controller import it: `ready` is a callable
    the engine hands over, never a JAX type, and polling it imports nothing
    (only a span or a `dev:dry` event looks for jax.profiler, lazily)."""
    import subprocess
    import sys

    code = ("import sys; "
            "import vllm_production_stack_tpu.engine.flightrec as f; "
            "r = f.FlightRecorder(); "
            "r.dispatch('decode', 1, 1, ready=lambda: False); "
            "r.poll(); "
            "r.dispatch('decode', 1, 1); "
            "assert r.timing()['dry_poll_n'] == 2; "
            "sys.exit(1 if any(m == 'jax' or m.startswith('jax.') "
            "for m in sys.modules) else 0)")
    assert subprocess.run([sys.executable, "-c", code]).returncode == 0


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
