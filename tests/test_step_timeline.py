"""The step timeline (engine/flightrec.py PHASES): one phase boundary with
three sinks — cumulative /debug/timing totals, `step:<phase>` annotations in
an xprof capture, stamps on the flight records — the device-starvation
accounting by cause, and the named scopes inside the jitted steps
(docs/14-tracing.md, docs/37-flight-recorder.md)."""

import asyncio
import glob

import pytest

from vllm_production_stack_tpu.engine.async_engine import AsyncEngine
from vllm_production_stack_tpu.engine.config import EngineConfig
from vllm_production_stack_tpu.engine.engine import LLMEngine
from vllm_production_stack_tpu.engine.flightrec import (
    DISPATCH_PHASES,
    DRY_AFTER,
    GAP_AFTER,
    PHASES,
    TURN_PHASES,
    FlightRecorder,
    Watchdog,
    ThreadRegistry,
)
from vllm_production_stack_tpu.engine.request import SamplingParams
from vllm_production_stack_tpu.metrics_contract import DEVICE_SCOPES
from vllm_production_stack_tpu.testing.serial_loop import serial_engine

# every key /debug/timing's `engine` group served before this timeline
# existed (the chip benchmark's readers and the tests read them)
PARENT_KEYS = {
    "sched_s", "post_s", "prefill_s", "prefill_n", "prefill_tokens",
    "decode_s", "decode_n", "decode_tokens", "dispatch_s", "sync_s",
    "overlap_s", "step_wall_s", "rollback_n",
}
STEP_PHASES = ("sched", "build", "h2d", "enqueue", "sync", "post")
# the scopes a dense model's programs name, out of the one registry
# (metrics_contract.DEVICE_SCOPES): all but the other mixers', the experts',
# the output gate's and block diffusion's; a decode window alone commits its
# staged K/V and (XLA attention) gathers its pages
_NOT_DENSE = ("mla_", "kda_", "ssm_", "moe_", "h1_", "bd_", "dsa_",
              "attn_gate")
DECODE_SCOPES = {s for s in DEVICE_SCOPES if not s.startswith(_NOT_DENSE)}
PREFILL_SCOPES = DECODE_SCOPES - {"kv_commit", "gather_pages"}


def _engine() -> LLMEngine:
    return LLMEngine(EngineConfig.tiny())


def _greedy(n: int) -> SamplingParams:
    return SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)


@pytest.fixture(scope="module")
def ran():
    """A pipelined and a serial engine after the same small workload."""
    out = {}
    for name, make in (("pipelined", LLMEngine), ("serial", serial_engine)):
        eng = make(EngineConfig.tiny())
        eng.generate([[1, 2, 3, 4, 5], list(range(7, 30))], _greedy(24))
        out[name] = eng
    return out


@pytest.mark.parametrize("key", sorted(PARENT_KEYS))
def test_every_parent_timing_key_is_still_served(ran, key):
    assert key in ran["pipelined"].timing


@pytest.mark.parametrize("loop", ["pipelined", "serial"])
def test_phase_totals_reconcile(ran, loop):
    t = ran[loop].timing
    assert t["dispatch_n"] == t["prefill_n"] + t["decode_n"] > 0
    assert sum(t[p + "_s"] for p in DISPATCH_PHASES) == pytest.approx(
        t["dispatch_s"], rel=1e-9)
    assert all(t[p + "_s"] > 0 for p in STEP_PHASES)
    assert sum(t["starved_" + p + "_s"] for p in PHASES) == pytest.approx(
        t["starved_s"], rel=1e-9)
    assert sum(t["starved_after_" + k + "_n"] for k in GAP_AFTER) \
        == t["starved_n"] > 0
    assert sum(t["dry_" + p + "_s"] for p in PHASES) == pytest.approx(
        t["dry_s"], rel=1e-9)
    assert sum(t["dry_after_" + k + "_n"] for k in DRY_AFTER) == t["dry_n"]
    assert sum(t[p + "_s"] for p in TURN_PHASES) == pytest.approx(
        t["turn_s"], rel=1e-9)
    # the phases of a step call cover it: what runs between two spans is a
    # few Python statements
    covered = sum(t[p + "_s"] for p in STEP_PHASES)
    assert covered == pytest.approx(t["step_wall_s"], rel=0.01)
    assert 0 < t["starved_s"] < t["clock_s"]


@pytest.mark.parametrize("loop", ["pipelined", "serial"])
def test_prefill_positions_counts_what_the_program_computed(ran, loop):
    """`prefill_positions` beside `prefill_tokens`: the token positions the
    dispatched prefill programs computed, padding included. The fixture's
    two prompts (5 + 23 tokens) share one two-row dispatch, packed end to
    end on the 32-token bucket — rows x bucket would be 2 x 32."""
    t = ran[loop].timing
    assert t["prefill_n"] == 1 and t["prefill_tokens"] == 28
    assert t["prefill_positions"] == 32


def test_serial_loop_starves_after_every_dispatch(ran):
    t = ran["serial"].timing
    # the first dispatch ends the gap open since `start`; each later one
    # ends the gap its predecessor's resolve opened
    assert t["starved_n"] == t["dispatch_n"]
    assert t["starved_after_start_n"] == 1
    assert t["starved_after_decode_n"] == t["decode_n"] - 1 \
        or t["starved_after_decode_n"] == t["decode_n"]


def _delta(eng, before):
    now = eng.timing
    return {k: now[k] - before[k] for k in now}


def test_a_prefill_chunk_does_not_drain_the_pipeline():
    """decode -> decode -> prefill -> decode: the chunk is enqueued behind a
    window and the next window behind the chunk (the new row's first token
    chained on the device), so the device's queue never empties: no
    `starved_after_prefill` gap, and the chunk counts as chained. The one
    gap of the run is the one since `start`, on the flight record of the
    dispatch that ended it."""
    eng = _engine()
    eng.add_request("a", prompt_token_ids=[1, 2, 3, 4, 5, 6],
                    sampling=_greedy(200))
    while eng.timing["decode_n"] < 2:  # a's prefill, then chained windows
        eng.step()
    before = eng.timing
    for _ in range(3):  # decode -> decode: the queue never empties
        eng.step()
    d = _delta(eng, before)
    assert d["decode_n"] == 3 and d["prefill_n"] == 0
    assert d["starved_n"] == 0 and d["starved_s"] == 0

    before = eng.timing
    eng.add_request("b", prompt_token_ids=[9, 8, 7, 6, 5],
                    sampling=_greedy(200))
    kinds = []
    while _delta(eng, before)["decode_n"] < 4:
        eng.step()
        kinds.append(eng._inflight.handle.kind)
    d = _delta(eng, before)
    # b's chunk stayed in flight over a call, like a window
    assert "prefill" in kinds and kinds[-1] == "decode"
    assert d["prefill_n"] == 1 == d["prefill_chained_n"]
    assert d["starved_n"] == 0 == d["starved_s"]
    assert d["starved_after_prefill_n"] == 0 == d["rollback_n"]
    t = eng.timing
    assert t["prefill_chained_n"] <= t["prefill_n"]
    assert t["starved_n"] == 1 == t["starved_after_start_n"]
    # b decodes in the window behind its chunk: 16 seats x 8 steps would
    # hold it anyway, but its row is there from that window's first step
    records = eng.flightrec.snapshot()
    chunk = [r for r in records
             if r["event"] == "dispatch" and r["kind"] == "prefill"][-1]
    behind = next(r for r in records if r["event"] == "dispatch"
                  and r["seq"] == chunk["seq"] + 1)
    assert behind["kind"] == "decode" and behind["rows"] == 2
    # no starvation gap ended at it; a tiny chunk on the CPU may well have
    # run dry before the window's enqueue returned (a dry gap, seen by poll)
    assert behind["gap"] is None or behind["gap"]["seen"] == "poll"
    gaps = [r for r in records if r["event"] == "dispatch" and r["gap"]
            and r["gap"]["seen"] == "resolve"]
    assert len(gaps) == 1
    last = gaps[-1]
    assert last["kind"] == "prefill" and last["gap"]["after"] == "start"
    assert last["gap"]["ms"] == pytest.approx(
        sum(last["gap"]["phases_ms"].values()), abs=0.01)
    assert set(last["gap"]["phases_ms"]) <= set(PHASES)
    # the stamps: name, start, end on the monotonic clock, and the seq
    names = {p[0] for p in last["phases"]}
    assert {"sched", "build", "h2d", "enqueue"} <= names
    for name, start, end, seq in last["phases"]:
        assert start <= end <= last["mono_ns"]
        if name in DISPATCH_PHASES:
            assert seq == last["seq"]


def _chip_reader(name: str):
    """A reader module of the chip benchmark, loaded from its file."""
    import importlib.util
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "benchmarks", "chip", "readers", name + ".py")
    mod = importlib.util.spec_from_file_location(name, path)
    reader = importlib.util.module_from_spec(mod)
    mod.loader.exec_module(reader)
    return reader


def _chip_metric(name: str) -> tuple[dict, dict]:
    """(BENCHMARK.json entry, metrics/<name>.json) of a per-layer metric."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "chip", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == name]
    return entry, spec


@pytest.mark.parametrize("start, end, want", [
    (dict(prefill_n=10, prefill_chained_n=4),
     dict(prefill_n=50, prefill_chained_n=34), 75.0),
    # a parent that lacks the counter: the metric is left out, not raised
    (dict(prefill_n=10), dict(prefill_n=50), None),
    # no chunk in the window: nothing to divide by
    (dict(prefill_n=7, prefill_chained_n=7),
     dict(prefill_n=7, prefill_chained_n=7), None),
])
def test_prefill_chained_share_from_its_data_file(start, end, want):
    """The benchmark's `prefill_chained_share` is a data file for a reader
    the benchmark already has: chunks that had a window enqueued behind
    them before their resolve, of all chunks, over the window."""
    entry, spec = _chip_metric("prefill_chained_share")
    assert entry == {
        "name": "prefill_chained_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "out_tok_per_s"}  # no `workloads`: every cell reports it
    reader = _chip_reader(spec["reader"])
    ctx = {"start": {"timing": {"engine": start}},
           "end": {"timing": {"engine": end}}}
    got = reader.read(ctx, **spec["args"])
    assert got == (pytest.approx(want) if want is not None else None)
    # the counters it names are served by every engine
    assert {spec["args"]["num"], spec["args"]["den"]} <= set(
        FlightRecorder().timing())


@pytest.mark.parametrize("start, end, want", [
    # decode programs built with the touched-experts kernel: what they read
    # is what their rows touched
    (dict(moe_decode_slots_n=896, moe_decode_read_n=200),
     dict(moe_decode_slots_n=896 * 11, moe_decode_read_n=200 + 2240), 25.0),
    # built with the all-experts einsum: every slot
    (dict(moe_decode_slots_n=1024, moe_decode_read_n=1024),
     dict(moe_decode_slots_n=5120, moe_decode_read_n=5120), 100.0),
    # a parent that lacks the counter, and a dense model that serves
    # neither: the metric is left out, not raised
    (dict(moe_decode_slots_n=10), dict(moe_decode_slots_n=50), None),
    (dict(), dict(), None),
])
def test_moe_read_share_from_its_data_file(start, end, want):
    """The benchmark's `moe_read_share` is a data file for a reader the
    benchmark already has: the experts whose bytes the decode programs'
    expert computation read, of layer-steps x held experts, over the
    window (ISSUE 36)."""
    import json
    import os

    entry, spec = _chip_metric("moe_read_share")
    assert entry == {
        "name": "moe_read_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "out_tok_per_s", "workloads": [
            "olmoe-1b-7b-int8.chat-saturated",
            "kanana-2-30b-a3b-bf16-8l.longdoc-saturated",
            "laguna-s21-bf16-ep4-9l.longdoc-saturated",
            "solar-open2-bf16-ep8-8l.longdoc-saturated",
            "sdar-30b-a3b-bf16-8l.chat-saturated",
            "glm-5.2-bf16-ep16-7l.longdoc-saturated"]}
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        cells = {w["name"] for w in json.load(f)["workloads"]}
    assert set(entry["workloads"]) <= cells
    reader = _chip_reader(spec["reader"])
    ctx = {"start": {"timing": {"engine": start}},
           "end": {"timing": {"engine": end}}}
    got = reader.read(ctx, **spec["args"])
    assert got == (pytest.approx(want) if want is not None else None)


_W0 = dict(dry_s=1.0, dry_n=10, clock_s=100.0, turn_s=40.0, dispatch_n=50)


@pytest.mark.parametrize("name, source, end, want", [
    ("device_dry_share", "program_span",
     dict(dry_s=1.75, dry_n=60, clock_s=150.0, turn_s=46.0, dispatch_n=250),
     1.5),
    ("dry_dispatch_share", "program_counter",
     dict(dry_s=1.75, dry_n=60, clock_s=150.0, turn_s=46.0, dispatch_n=250),
     25.0),
    ("host_turn_ms", "program_span",
     dict(dry_s=1.75, dry_n=60, clock_s=150.0, turn_s=46.0, dispatch_n=250),
     30.0),
    # a window without a single dry gap reads 0, it is not left out
    ("device_dry_share", "program_span",
     dict(dry_s=1.0, dry_n=10, clock_s=150.0, turn_s=46.0, dispatch_n=250),
     0.0),
    ("dry_dispatch_share", "program_counter",
     dict(dry_s=1.0, dry_n=10, clock_s=150.0, turn_s=46.0, dispatch_n=250),
     0.0),
    # a parent that lacks the counters: left out, not raised
    ("device_dry_share", "program_span",
     dict(clock_s=150.0, dispatch_n=250), None),
    ("dry_dispatch_share", "program_counter",
     dict(clock_s=150.0, dispatch_n=250), None),
    ("host_turn_ms", "program_span",
     dict(clock_s=150.0, dispatch_n=250), None),
])
def test_dry_metrics_from_their_data_files(name, source, end, want):
    """Three of ISSUE 37's metrics are data files for a reader the benchmark
    already has (`timing_ratio_opt`), over the window."""
    entry, spec = _chip_metric(name)
    assert entry == {
        "name": name, "unit": "ms" if name.endswith("_ms") else "%",
        "better": "lower", "source": source, "layer": "scheduler",
        "moves": "out_tok_per_s"}  # no `workloads`: every cell reports it
    assert spec["reader"] == "timing_ratio_opt"
    reader = _chip_reader("timing_ratio_opt")
    start = _W0 if want is not None else dict(clock_s=100.0, dispatch_n=50)
    ctx = {"start": {"timing": {"engine": start}},
           "end": {"timing": {"engine": end}}}
    got = reader.read(ctx, **spec["args"])
    assert got == (pytest.approx(want) if want is not None else None)
    # the counters it names are served by every engine
    assert {spec["args"]["num"], spec["args"]["den"]} <= set(
        FlightRecorder().timing())


@pytest.mark.parametrize("t0, t1, want_unseen, want_untraced", [
    # the slice: 0.70 s idle, 0.02 starved + 0.62 dry seen -> 0.06 of 8 s;
    # before it: (0.1 + 0.5) of 20 s
    (dict(starved_s=1.1, dry_s=2.5, clock_s=120.0),
     dict(starved_s=1.12, dry_s=3.12, clock_s=128.0), 0.75, 3.0),
    # a parent: neither reader finds its counters
    (dict(starved_s=1.1, clock_s=120.0),
     dict(starved_s=1.12, clock_s=128.0), None, None),
])
def test_idle_unseen_and_untraced_share_on_fixture_documents(
        t0, t1, want_unseen, want_untraced):
    """The two readers ISSUE 37 adds: the idle of the device trace that
    neither account explains, over the traced slice; and the host-side
    share of an empty device from the window's open to the capture's start,
    where no profiler is attached."""
    entry, spec = _chip_metric("idle_unseen_share")
    assert (entry["layer"], entry["source"]) == ("device", "device_trace")
    assert "workloads" not in entry and entry["better"] == "lower"
    start = dict(starved_s=1.0, dry_s=2.0, clock_s=100.0)
    if want_unseen is None:
        del start["dry_s"]
    ctx = {"trace": {"gap_total_s": 0.70, "window_s": 8.0},
           "trace_span": {"timing0": {"engine": t0},
                          "timing1": {"engine": t1}},
           "start": {"timing": {"engine": start}}}
    unseen = _chip_reader(spec["reader"]).read(ctx, **spec.get("args", {}))
    assert unseen == (pytest.approx(want_unseen)
                      if want_unseen is not None else None)
    entry, spec = _chip_metric("device_empty_share_untraced")
    assert (entry["layer"], entry["source"]) == ("scheduler", "program_span")
    reader = _chip_reader(spec["reader"])
    got = reader.read(ctx, **spec["args"])
    assert got == (pytest.approx(want_untraced)
                   if want_untraced is not None else None)
    # an untraced run has no slice: both are left out
    bare = {**ctx, "trace": None, "trace_span": {}}
    assert reader.read(bare, **spec["args"]) is None
    assert _chip_reader("idle_unseen").read(bare) is None
    assert set(spec["args"]["nums"]) | {spec["args"]["den"]} <= set(
        FlightRecorder().timing())


def _slow_sched(eng, monkeypatch, seconds: float):
    """Make the host's turn longer than any tiny program: `sched` sleeps
    while a step is in flight."""
    import time

    schedule = eng.scheduler.schedule

    def slow(inflight=None):
        if inflight is not None:
            time.sleep(seconds)
        return schedule(inflight=inflight)

    monkeypatch.setattr(eng.scheduler, "schedule", slow)


def _count_dispatches_behind_a_step(eng, monkeypatch) -> list:
    behind = []
    dispatch = eng._dispatch

    def counting(work, prev=None):
        if prev is not None:
            behind.append(work)
        return dispatch(work, prev=prev)

    monkeypatch.setattr(eng, "_dispatch", counting)
    return behind


def test_slow_host_turn_reads_a_dry_gap_at_every_dispatch_behind_a_step(
        monkeypatch):
    """The program in flight completes while the host is still in `sched`:
    every enqueue behind it comes too late, and the starvation account sees
    none of it (the host never emptied the queue itself)."""
    eng = _engine()
    eng.generate([[1, 2, 3, 4, 5]], _greedy(24))  # compiled
    _slow_sched(eng, monkeypatch, 0.15)
    behind = _count_dispatches_behind_a_step(eng, monkeypatch)
    before = eng.timing
    eng.generate([[5, 4, 3, 2, 1, 7]], _greedy(40))
    d = _delta(eng, before)
    assert len(behind) >= 4
    assert d["dry_n"] == len(behind)
    assert d["dry_after_decode_n"] + d["dry_after_prefill_n"] == d["dry_n"]
    # the sleeping phase is where the gaps open (`sched` has no sub-step to
    # poll: its length is the bracket, or part of the gap where the program
    # was seen complete before it); what follows it up to the enqueue's
    # return is the lower bound
    assert d["dry_s"] > 0 and d["dry_s"] + d["dry_slack_s"] >= 0.15 * 0.9 * (
        d["dry_n"])
    # the last window ran dry too, with nothing to enqueue behind it: its
    # gap went on as a starvation gap from its resolve, counted there
    assert d["starved_after_decode_n"] <= 1
    assert d["dry_poll_n"] >= d["dry_n"] and d["dry_poll_s"] > 0
    gaps = [r["gap"] for r in eng.flightrec.snapshot()
            if r["event"] == "dispatch" and r["gap"]
            and r["gap"]["seen"] == "poll"]
    assert len(gaps) >= len(behind) and all(
        g["slack_ms"] >= 0 and g["after"] in DRY_AFTER for g in gaps)


def test_a_chunk_row_is_sliced_not_walked():
    """`Request.tokens_between` is `token_at` over a range, across the
    prompt's end (a resumed request's chunk holds outputs too): `sched` of a
    2,048-token chunk builds its row in two list operations, so a profiler's
    Python tracer has no 2,048 calls to stretch."""
    from vllm_production_stack_tpu.engine.request import Request

    req = Request("r", list(range(100, 110)), _greedy(4))
    req.output_token_ids.extend([7, 8, 9])
    for start, end in ((0, 10), (2, 5), (8, 13), (10, 13), (11, 12), (3, 3)):
        assert req.tokens_between(start, end) == [
            req.token_at(i) for i in range(start, end)]
    row = req.tokens_between(0, 10)
    row.append(1)
    assert len(req.prompt_token_ids) == 10  # a copy, never the prompt itself


def test_slow_device_program_reads_no_dry_gap(monkeypatch):
    """A program that outlasts the host's turn: the next one is enqueued
    while it still runs, at every dispatch, and the polls in between open
    no dry gap. No clock and no device decide how long a program runs here:
    the recorder is told that a program is complete once the host's wait
    for it has returned (or the host has thrown it away), and not before. (A
    sleep in a host callback behind the program did not say that: under
    load the tiny program had ended before the callback was enqueued, the
    CPU client then ran the callback on the step thread itself, and the
    program before the next dispatch WAS complete — one run in three under
    six test workers.)"""
    eng = _engine()
    execute_async = eng.runner.execute_async
    rec_dispatch = eng.flightrec.dispatch
    waited_for = []  # one flag a program, the newest last

    def ending(flag, method):
        def call():
            got = method()
            flag.append(True)
            return got
        return call

    def slow_program(work, prev=None):
        handle = execute_async(work, prev=prev)
        flag = []
        waited_for.append(flag)
        handle.resolve = ending(flag, handle.resolve)
        handle.discard = ending(flag, handle.discard)
        return handle

    def dispatch(*args, ready, **kw):
        flag = waited_for[-1]  # of the program `execute_async` just made
        return rec_dispatch(
            *args, ready=lambda: bool(flag) and ready(), **kw)

    monkeypatch.setattr(eng.runner, "execute_async", slow_program)
    monkeypatch.setattr(eng.flightrec, "dispatch", dispatch)
    eng.generate([[1, 2, 3, 4, 5]], _greedy(24))  # compiled
    behind = _count_dispatches_behind_a_step(eng, monkeypatch)
    before = eng.timing
    out = eng.generate([[5, 4, 3, 2, 1, 7]], _greedy(40))
    d = _delta(eng, before)
    assert len(out[0]["token_ids"]) == 40
    # every dispatch but the first had a running step to be enqueued behind
    assert len(behind) >= 4 and len(behind) == d["dispatch_n"] - 1
    assert d["dry_poll_n"] > len(behind)
    assert d["dry_n"] == 0 == d["dry_s"] == d["dry_slack_s"]
    assert all(waited_for)


def test_rolled_back_dispatch_does_not_empty_the_queue():
    rec = FlightRecorder()
    a = rec.dispatch("decode", rows=1, tokens=4)
    b = rec.dispatch("decode", rows=1, tokens=4)  # chained behind a
    n0 = rec.timing()["starved_n"]  # the gap since `start` ended at a
    rec.resolve(a)
    rec.discard(b)  # the device still runs b
    assert rec.outstanding_age_s() is None  # no stall candidate...
    c = rec.dispatch("decode", rows=1, tokens=4)
    assert rec.timing()["starved_n"] == n0  # ...and no gap ended at c
    with rec.span("post"):
        rec.resolve(c)  # completes b too: now the queue is empty
    d = rec.dispatch("prefill", rows=1, tokens=8)
    t = rec.timing()
    assert t["starved_n"] == n0 + 1 and t["starved_after_decode_n"] == 1
    assert rec.snapshot()[-1]["gap"]["after"] == "decode"
    rec.resolve(d)


def test_engine_rollback_opens_no_gap():
    """A stop token inside a decode window discards the speculative window
    dispatched behind it; the next decode is enqueued behind the discarded
    one, so the device's queue never emptied."""
    short = stop_at = None
    for seed in range(1, 16):  # a greedy stream that does not degenerate
        short = [(seed * 37 + i * 11) % 200 + 1 for i in range(7)]
        ref = _engine().generate([short], _greedy(24))[0]["token_ids"]
        cand = [t for t in ref[3:] if ref.index(t) >= 3]
        if cand:
            stop_at = cand[0]
            break
    assert stop_at is not None, "no non-degenerate greedy stream found"
    eng = _engine()
    eng.add_request("long", prompt_token_ids=[1, 2, 3, 4],
                    sampling=_greedy(64))
    eng.add_request("short", prompt_token_ids=short,
                    sampling=SamplingParams(max_tokens=24, temperature=0.0,
                                            stop_token_ids=(stop_at,)))
    while eng.has_unfinished():
        eng.step()
    t = eng.timing
    assert t["rollback_n"] >= 1 and t["decode_n"] > 2
    assert t["starved_after_decode_n"] == 0


def test_quiet_loop_counts_a_trailing_rollback_as_emptied():
    rec = FlightRecorder()
    a = rec.dispatch("decode", rows=1, tokens=4)
    b = rec.dispatch("decode", rows=1, tokens=4)
    rec.resolve(a)
    rec.discard(b)
    rec.quiet()  # nothing left to run: b will never be resolved
    with rec.span("idle"):
        pass
    rec.dispatch("prefill", rows=1, tokens=8)
    t = rec.timing()
    assert t["starved_after_decode_n"] == 1
    assert t["starved_idle_s"] > 0 and t["starved_idle_s"] == t["starved_s"]
    # the watchdog's liveness cursor: the rolled-back step left it, the
    # prefill nobody resolved is the stall candidate
    assert rec.outstanding_age_s()[1] == "prefill"
    dog = Watchdog(ThreadRegistry(), rec, stall_after_s=-1.0)
    assert dog.check()["findings"][0]["kind"] == "unresolved_step"


def test_async_loop_phases_and_loop_group():
    eng = _engine()
    async_engine = AsyncEngine(eng)

    async def go():
        async_engine.start(asyncio.get_running_loop())
        try:
            async for _ in async_engine.generate(
                    prompt_token_ids=[1, 2, 3, 4], sampling=_greedy(12)):
                pass
            await asyncio.sleep(0.1)  # a few idle rounds
        finally:
            async_engine.shutdown()

    asyncio.run(go())
    t = eng.timing
    assert all(t[p + "_s"] > 0 for p in PHASES), t
    assert t["starved_idle_s"] > 0
    loop = async_engine.loop_snapshot()
    assert set(loop) == {"steps", "busy_s", "idle_s", "submits", "submit_s"}
    assert loop["steps"] > 0 and loop["submits"] == 1
    assert loop["idle_s"] == t["idle_s"]


def test_capture_holds_step_spans_and_clock_pair(tmp_path):
    """A capture on the CPU backend: `step:*` events with their seq on the
    step thread's line, and the clock pair's own event."""
    import jax
    from jax.profiler import ProfileData

    eng = _engine()
    async_engine = AsyncEngine(eng)

    async def go():
        async_engine.start(asyncio.get_running_loop())
        try:
            async for _ in async_engine.generate(
                    prompt_token_ids=[1, 2, 3], sampling=_greedy(4)):
                pass  # compiled before the capture starts
            jax.profiler.start_trace(str(tmp_path))
            pair = eng.flightrec.clock_pair()
            async for _ in async_engine.generate(
                    prompt_token_ids=[4, 5, 6, 7], sampling=_greedy(12)):
                pass
            await asyncio.sleep(0.05)
            jax.profiler.stop_trace()
            return pair
        finally:
            async_engine.shutdown()

    pair = asyncio.run(go())
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[-1]
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    step_line = next(ln for ln in host.lines if ln.name == "engine-step")
    seen = {}
    for ev in step_line.events:
        if ev.name.startswith("step:"):
            seen.setdefault(ev.name[5:], dict(ev.stats))
    assert set(seen) == set(PHASES)
    assert all(stats["seq"] > 0 for stats in seen.values())
    assert seen["sync"]["kind"] in ("prefill", "decode")
    marks = [dict(ev.stats) for ln in host.lines for ev in ln.events
             if ev.name == "clock_pair"]
    assert marks == [pair]


def test_capture_holds_dev_dry_beside_step_spans(tmp_path, monkeypatch):
    """The first poll that sees the watched program complete leaves one
    zero-length `dev:dry` event with that program's seq on the step
    thread's line, beside the `step:*` spans and the clock pair."""
    import jax
    from jax.profiler import ProfileData

    eng = _engine()
    async_engine = AsyncEngine(eng)

    async def go():
        async_engine.start(asyncio.get_running_loop())
        try:
            async for _ in async_engine.generate(
                    prompt_token_ids=[1, 2, 3], sampling=_greedy(4)):
                pass  # compiled before the capture starts
            _slow_sched(eng, monkeypatch, 0.05)
            jax.profiler.start_trace(str(tmp_path))
            try:
                eng.flightrec.clock_pair()
                before = eng.timing
                async for _ in async_engine.generate(
                        prompt_token_ids=[4, 5, 6, 7], sampling=_greedy(24)):
                    pass
                await asyncio.sleep(0.05)
            finally:
                jax.profiler.stop_trace()
            return _delta(eng, before)
        finally:
            async_engine.shutdown()

    d = asyncio.run(go())
    assert d["dry_n"] >= 2
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[-1]
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    step_line = next(ln for ln in host.lines if ln.name == "engine-step")
    names = {ev.name for ev in step_line.events}
    assert {"step:sched", "step:build", "dev:dry"} <= names
    assert any(ev.name == "clock_pair" for ln in host.lines
               for ev in ln.events)
    dries = [dict(ev.stats) for ev in step_line.events
             if ev.name == "dev:dry"]
    # one event a gap that opened inside the capture: those a dispatch
    # ended (`dry_n`) and those the host's own resolve ended (the prompt's
    # chunk and the last window, with nothing to enqueue behind them)
    assert d["dry_n"] <= len(dries) <= d["dry_n"] + 3
    assert all(ev["seq"] > 0 and ev["slack_ns"] >= 0 for ev in dries)
    seqs = [ev["seq"] for ev in dries]
    assert seqs == sorted(set(seqs))  # one sighting a program, in order
    # the dispatch that closed each gap is the next seq's flight record
    closed = {r["seq"] for r in eng.flightrec.snapshot()
              if r["event"] == "dispatch" and r["gap"]
              and r["gap"]["seen"] == "poll"}
    assert {s + 1 for s in seqs[:-1]} <= closed


def test_xplane_timeline_rebuilds_both_accounts_from_the_capture():
    """engine/capture_summary.py (where tools/xplane_timeline.py's reduction
    lives now): starvation gaps and dry gaps over a capture from `step:*`
    spans and `dev:dry` events alone."""
    from vllm_production_stack_tpu.engine import capture_summary as tool

    ms = 1e6
    steps = [  # (phase, start, end, seq, kind), ns
        ("build", 0 * ms, 10 * ms, 1, "prefill"),
        ("sync", 10 * ms, 50 * ms, 1, "prefill"),  # nothing behind seq 1
        ("build", 52 * ms, 60 * ms, 2, "decode"),  # starved 50 -> 60
        ("build", 61 * ms, 70 * ms, 3, "prefill"),  # behind seq 2
        ("sync", 70 * ms, 90 * ms, 2, "decode"),
        ("sched", 90 * ms, 120 * ms, 4, ""),
        ("build", 120 * ms, 130 * ms, 4, "decode"),  # seq 3 ran dry at ~100
        ("sync", 130 * ms, 131 * ms, 3, "prefill"),
    ]
    dries = [(120 * ms, 3, 30 * ms)]  # seen at the exit of `sched`
    got = tool.host_account(steps, dries, 0.0, 200 * ms)
    assert got == {"starved_s": 0.01, "dry_s": 0.01, "dry_slack_s": 0.03,
                   "dry_n": 1}
    # clipped to the device plane's own window
    assert tool.host_account(steps, dries, 55 * ms, 125 * ms) == {
        "starved_s": 0.005, "dry_s": 0.005, "dry_slack_s": 0.03, "dry_n": 1}
    assert tool.host_account(steps, [], 0.0, 200 * ms)["dry_n"] == 0


def _scopes(hlo: str) -> set[str]:
    import re

    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo):
        found.update(op_name.split("/"))
    return found


def test_programs_name_their_scopes():
    """The documented scope names are in the compiled programs' HLO
    metadata (what the device planes' name-scope line shows)."""
    runner = _engine().runner
    nb = runner._width_bucket(runner.max_blocks)
    pre = ("prefill", 2, 32, nb, False, False, False)
    dec = ("decode", 4, nb, 4, False, False, None)
    for key in (pre, dec):
        assert runner._compile_key_now(key, "warmup")
    assert PREFILL_SCOPES <= _scopes(runner._aot_exec[pre].as_text())
    assert DECODE_SCOPES <= _scopes(runner._aot_exec[dec].as_text())
