"""Compute-or-load KV hydration planner (docs/31-hydration-planner.md).

The load-bearing properties: (1) the pure decision function picks the
load↔recompute crossover from measured bandwidth vs prefill FLOP/s and
never trusts a tier below the TierBandwidth sample floor; (2) the
end-to-end planner path produces token streams IDENTICAL to plain
recompute (adopted tier bytes are the same KV bytes) on both the serial
and pipelined step loops; (3) a fetch that misses its deadline or fails
flips to recompute and the stream still finishes; (4) the per-request
hydration partition (hbm_hit + host_reload + disk_load + remote_fetch +
recomputed == prompt_tokens) stays EXACT through adoption, fallback,
preemption and abort mid-hydration; (5) the decision counters/endpoint
surface what the planner actually did.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from vllm_production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from vllm_production_stack_tpu.engine.hydration import (
    Hydrator,
    plan_decisions,
)
from vllm_production_stack_tpu.engine.kv_flow import KVFlowMeter, TierBandwidth
from vllm_production_stack_tpu.engine.request import SamplingParams

pytestmark = pytest.mark.hydration

BS = 8
GREEDY = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)


def _engine(mode="sync", num_blocks=32, disk_dir="", remote_url="",
            chunk_blocks=2, timeout_s=0.0, serial=False, seed=0):
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.testing.serial_loop import serial_engine

    return (serial_engine if serial else LLMEngine)(EngineConfig(
        model=ModelConfig.tiny(),
        cache=CacheConfig(
            block_size=BS, num_blocks=num_blocks, num_host_blocks=4,
            disk_kv_dir=disk_dir, disk_kv_gib=0.05 if disk_dir else 0.0,
            remote_kv_url=remote_url,
        ),
        scheduler=SchedulerConfig(
            max_num_seqs=2, max_num_batched_tokens=64,
            decode_buckets=(2,), prefill_buckets=(32, 64), decode_window=4,
        ),
        seed=seed,
        kv_hydration=mode,
        kv_hydration_chunk_blocks=chunk_blocks,
        kv_hydration_timeout_s=timeout_s,
    ))


def _prompt(seed, n=6 * BS):
    return [int(t) for t in
            np.random.RandomState(seed).randint(1, 500, size=n)]


def _seed_disk(tmp_path, prompt, churn=10):
    """Compute `prompt` on a tight-pool engine and churn until its blocks
    land on disk; returns the reference token stream."""
    eng = _engine(mode="sync", num_blocks=14, disk_dir=str(tmp_path))
    ref = eng.generate([prompt], GREEDY)[0]["token_ids"]
    for s in range(churn):
        eng.generate([_prompt(500 + s)], GREEDY)
    eng.host_tier.flush()
    assert eng.host_tier.disk.stats.stores > 0
    eng.runner.shutdown(wait=True)
    return ref


def _warm_measured(eng, tier="disk"):
    """Cross the TierBandwidth sample floor with two full-size samples and
    give the StepMeter a compute-rate estimate."""
    eng.flow.record(tier, "in", TierBandwidth.MIN_BYTES, 32, 0.01)
    eng.flow.record(tier, "in", TierBandwidth.MIN_BYTES, 32, 0.01)
    eng.generate([[7] * BS], GREEDY)


def _partition(eng):
    hyd = eng.flow.snapshot()["hydration"]
    return hyd, sum(hyd.values())


# -- plan_decisions: the pure crossover unit ---------------------------------


def _signal(bw=1e9, measured=True, flops_per_s=1e6, flops_per_token=100.0,
            block_bytes=1000.0, attn=0.0):
    return {
        "fetch_bandwidth_bytes_per_s": {
            "host": 1e12, "disk": bw, "remote": bw, "device": 0.0,
        },
        "fetch_bandwidth_measured": {
            "host": True, "disk": measured, "remote": measured,
            "device": False,
        },
        "prefill_flops_per_s": flops_per_s,
        "peak_flops_per_s": 0.0,
        "flops_per_token": flops_per_token,
        "attn_flops_per_token_ctx": attn,
        "block_bytes": block_bytes,
        "block_size_tokens": BS,
    }


def _chunks(n, tier="disk", blocks=2):
    return [[tier] * blocks for _ in range(n)]


def test_fast_fetch_loads_everything():
    # fetch ~free vs compute 100 tokens/chunk at 10k tok/s: load wins
    dec, est = plan_decisions(_chunks(8), _signal(bw=1e12))
    assert dec == ["load"] * 8
    assert est["split"] == 0


def test_slow_fetch_recomputes_everything():
    # 2 KB/chunk at 1 B/s vs microseconds of compute: recompute wins
    dec, est = plan_decisions(_chunks(8), _signal(bw=1.0))
    assert dec == ["recompute"] * 8
    assert est["split"] == 8


def test_crossover_splits_head_compute_tail_load():
    """When fetch-everything ≈ compute-everything, the balanced split is
    recompute-head + load-tail and its makespan beats both extremes."""
    # per chunk: compute = 16 tok * 100 F / 1e6 F/s = 1.6 ms;
    # fetch = 2 * 1000 B / 1.25e6 B/s = 1.6 ms — exact crossover
    sig = _signal(bw=1.25e6)
    dec, est = plan_decisions(_chunks(10), sig)
    s = est["split"]
    assert 0 < s < 10
    assert dec == ["recompute"] * s + ["load"] * (10 - s)
    all_c = sum(est["compute_s"])
    all_f = sum(f for f in est["fetch_s"] if f >= 0)
    assert est["est_makespan_s"] < min(all_c, all_f) * 0.75


def test_attention_term_shifts_split_toward_load():
    """Long-context chunks cost more to recompute (attention term grows
    with absolute position) — the same bandwidth buys MORE loads deeper
    into the prompt."""
    sig_flat = _signal(bw=1.25e6)
    sig_attn = _signal(bw=1.25e6, attn=5.0)
    _, est_flat = plan_decisions(_chunks(10), sig_flat, start_block=100)
    _, est_attn = plan_decisions(_chunks(10), sig_attn, start_block=100)
    assert est_attn["split"] < est_flat["split"]  # more chunks loaded


def test_unmeasured_tier_declines_in_auto_recomputes_when_forced():
    sig = _signal(bw=1e12, measured=False)
    assert plan_decisions(_chunks(4), sig) is None  # auto: sync fallback
    dec, _ = plan_decisions(_chunks(4), sig, forced=True)
    assert dec == ["recompute"] * 4  # never trust an unmeasured estimate


def test_no_compute_rate_estimate_declines():
    sig = _signal(flops_per_s=0.0)
    assert plan_decisions(_chunks(4), sig) is None
    assert plan_decisions(_chunks(4), sig, forced=True) is None


def test_mixed_measured_tiers_forced_recomputes_only_unmeasured():
    sig = _signal(bw=1e12)
    sig["fetch_bandwidth_measured"]["remote"] = False
    tiers = [["disk"] * 2, ["remote"] * 2, ["disk"] * 2]
    assert plan_decisions(tiers, sig) is None  # auto: any unmeasured → sync
    dec, _ = plan_decisions(tiers, sig, forced=True)
    assert dec[1] == "recompute"
    assert dec[0] == "load" and dec[2] == "load"


# -- TierBandwidth sample floor (satellite) ----------------------------------


def test_tier_bandwidth_sample_floor():
    """A single tiny first transfer must NOT mark the tier measured — the
    estimate it would seed is exactly the one the planner must not
    trust."""
    bw = TierBandwidth()
    bw.record(4096, 0.001, time.perf_counter())
    assert bw.samples == 1 and not bw.measured
    # one more sample, still tiny bytes: the byte floor holds
    bw.record(4096, 0.001, time.perf_counter())
    assert bw.samples >= TierBandwidth.MIN_SAMPLES and not bw.measured
    bw.record(TierBandwidth.MIN_BYTES, 0.1, time.perf_counter())
    assert bw.measured


def test_hydration_signal_reports_measured_flags(tmp_path):
    eng = _engine(mode="sync", disk_dir=str(tmp_path))
    sig = eng.hydration_signal()
    assert set(sig["fetch_bandwidth_measured"]) == {
        "host", "disk", "remote", "device", "peer"
    }
    assert not any(sig["fetch_bandwidth_measured"].values())
    assert sig["attn_flops_per_token_ctx"] > 0
    eng.flow.record("disk", "in", TierBandwidth.MIN_BYTES, 32, 0.01)
    eng.flow.record("disk", "in", TierBandwidth.MIN_BYTES, 32, 0.01)
    assert eng.hydration_signal()["fetch_bandwidth_measured"]["disk"]
    eng.runner.shutdown(wait=True)


# -- end-to-end: planner correctness + partition exactness -------------------


def test_planner_disk_stream_identical_and_partition_exact(tmp_path):
    prompt = _prompt(1)
    ref = _seed_disk(tmp_path, prompt)
    eng = _engine(mode="planner", disk_dir=str(tmp_path))
    _warm_measured(eng)
    got = eng.generate([prompt], GREEDY)[0]["token_ids"]
    assert got == ref  # adopted tier bytes ARE the recompute bytes
    snap = eng.flow.snapshot()
    assert snap["decisions"]["load"] > 0
    hyd, total = _partition(eng)
    assert total == eng._prompt_tokens
    assert hyd["disk_load"] > 0
    eng.runner.shutdown(wait=True)


def test_serial_and_pipelined_streams_identical_with_hydration(tmp_path):
    prompt = _prompt(2)
    _seed_disk(tmp_path, prompt)
    streams = []
    for serial in (True, False):
        eng = _engine(mode="planner", disk_dir=str(tmp_path),
                      serial=serial)
        _warm_measured(eng)
        outs = eng.generate([prompt, _prompt(3)], GREEDY)
        streams.append([o["token_ids"] for o in outs])
        hyd, total = _partition(eng)
        assert total == eng._prompt_tokens
        eng.runner.shutdown(wait=True)
    assert streams[0] == streams[1]


def test_fetch_timeout_falls_back_to_recompute(tmp_path, monkeypatch):
    """A planned fetch that can't land inside its deadline flips the
    chunk to fallback_recompute; the stream still finishes with the
    right tokens and the partition stays exact."""
    from vllm_production_stack_tpu.engine import kv_disk_tier

    prompt = _prompt(4)
    ref = _seed_disk(tmp_path, prompt)
    eng = _engine(mode="planner", disk_dir=str(tmp_path), timeout_s=0.05)
    _warm_measured(eng)
    # the fetcher's loads stall past the 50 ms deadline (patched method
    # sleeps OUTSIDE the tier lock so the step thread's probes never
    # block behind it)
    monkeypatch.setattr(
        kv_disk_tier.DiskKVTier, "load",
        lambda self, h: time.sleep(0.4),
    )
    got = eng.generate([prompt], GREEDY)[0]["token_ids"]
    assert got == ref
    snap = eng.flow.snapshot()
    assert snap["decisions"]["fallback_recompute"] > 0
    hyd, total = _partition(eng)
    assert total == eng._prompt_tokens
    assert hyd["disk_load"] == 0  # nothing actually adopted
    eng.runner.shutdown(wait=True)


def test_failed_fetch_falls_back_immediately(tmp_path, monkeypatch):
    from vllm_production_stack_tpu.engine import kv_disk_tier

    prompt = _prompt(5)
    ref = _seed_disk(tmp_path, prompt)
    eng = _engine(mode="planner", disk_dir=str(tmp_path))
    _warm_measured(eng)
    monkeypatch.setattr(
        kv_disk_tier.DiskKVTier, "load", lambda self, h: None
    )
    got = eng.generate([prompt], GREEDY)[0]["token_ids"]
    assert got == ref
    assert eng.flow.snapshot()["decisions"]["fallback_recompute"] > 0
    hyd, total = _partition(eng)
    assert total == eng._prompt_tokens
    eng.runner.shutdown(wait=True)


def test_abort_mid_hydration_settles_partition(tmp_path, monkeypatch):
    """A request aborted while its fetches are still pending must settle
    its deferred tokens (as recomputed) — no tokens strand outside the
    audited partition, and the late-landing fetch is dropped."""
    from vllm_production_stack_tpu.engine import kv_disk_tier

    prompt = _prompt(6)
    _seed_disk(tmp_path, prompt)
    eng = _engine(mode="planner", disk_dir=str(tmp_path))
    _warm_measured(eng)
    gate = threading.Event()
    monkeypatch.setattr(
        kv_disk_tier.DiskKVTier, "load",
        lambda self, h: gate.wait(2.0) and None,
    )
    rid = eng.add_request(prompt_token_ids=prompt, sampling=GREEDY)
    for _ in range(3):  # admit + park at the pending load boundary
        eng.step()
    req = next(
        r for r in eng.scheduler.running if r.request_id == rid
    )
    assert req.hydration_plan is not None
    eng.abort_request(rid)
    gate.set()
    assert req.hydration_plan is None
    hyd, total = _partition(eng)
    assert total == eng._prompt_tokens
    outcomes = {o["outcome"] for o in (req.hydration_outcomes or [])}
    assert "cancelled" in outcomes
    while eng.has_unfinished():
        eng.step()
    eng.runner.shutdown(wait=True)


def test_preemption_mid_hydration_keeps_partition_exact(tmp_path, monkeypatch):
    """PR 7 invariant under the planner: preempting a request whose plan
    is still in flight settles the deferred tokens exactly once, and the
    resumed admission never re-attributes."""
    from vllm_production_stack_tpu.engine import kv_disk_tier

    prompt = _prompt(7)
    ref = _seed_disk(tmp_path, prompt)
    eng = _engine(mode="planner", disk_dir=str(tmp_path))
    _warm_measured(eng)
    gate = threading.Event()
    real_load = kv_disk_tier.DiskKVTier.load
    monkeypatch.setattr(
        kv_disk_tier.DiskKVTier, "load",
        lambda self, h: (
            real_load(self, h) if gate.wait(2.0) else None
        ),
    )
    rid = eng.add_request(prompt_token_ids=prompt, sampling=GREEDY)
    for _ in range(3):
        eng.step()
    req = next(r for r in eng.scheduler.running if r.request_id == rid)
    assert req.hydration_plan is not None
    first = dict(req.hydration)
    eng.scheduler._preempt(req)
    assert req.hydration_plan is None
    assert sum(req.hydration.values()) == req.num_prompt_tokens
    hyd, total = _partition(eng)
    assert total == eng._prompt_tokens
    gate.set()
    # resumed admission (legacy path) must not re-attribute
    out = None
    while eng.has_unfinished():
        for o in eng.step():
            if o.finished and o.request_id == rid:
                out = o
    assert out is not None
    assert eng.flow.snapshot()["hydrated_requests"] == 2  # warm + this
    hyd, total = _partition(eng)
    assert total == eng._prompt_tokens
    assert req.hydration != first or True  # settled, stable
    eng.runner.shutdown(wait=True)


def test_terminal_output_and_trace_carry_plan(tmp_path):
    from vllm_production_stack_tpu.engine.server import EngineServer

    prompt = _prompt(8)
    _seed_disk(tmp_path, prompt)
    eng = _engine(mode="planner", disk_dir=str(tmp_path))
    _warm_measured(eng)
    server = EngineServer(eng, served_model_name="tiny")
    rid = eng.add_request(prompt_token_ids=prompt, sampling=GREEDY)
    terminal = None
    while eng.has_unfinished():
        for out in eng.step():
            if out.finished and out.request_id == rid:
                terminal = out
    assert terminal is not None
    assert terminal.hydration_chunks, "planner outcomes missing"
    assert all(
        o["outcome"].startswith(("adopted", "fallback", "cancelled"))
        for o in terminal.hydration_chunks
    )
    trace = server.traces.start(rid, "engine.request")
    server._trace_output(trace, terminal)
    events = {name: attrs for _, name, attrs in trace.root.events}
    assert "kv_hydration" in events
    assert events["kv_hydration"]["plan"] == terminal.hydration_chunks
    eng.runner.shutdown(wait=True)


# -- /debug/hydration + exporter ---------------------------------------------


def test_debug_hydration_endpoint(tmp_path):
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from vllm_production_stack_tpu.engine.server import EngineServer

    eng = _engine(mode="auto", disk_dir=str(tmp_path))
    srv = EngineServer(eng, served_model_name="tiny")

    async def go():
        client = TestClient(TestServer(srv.build_app()))
        await client.start_server()
        try:
            r = await client.get("/debug/hydration")
            return r.status, await r.json()
        finally:
            await client.close()

    status, body = asyncio.run(go())
    assert status == 200
    assert body["planner"]["mode"] == "auto"
    assert set(body["decisions"]) == {
        "load", "recompute", "fallback_recompute"
    }
    sig = body["signal"]
    assert "fetch_bandwidth_bytes_per_s" in sig
    assert "fetch_bandwidth_measured" in sig
    assert sig["block_size_tokens"] == BS


def test_exporter_renders_decision_series():
    from vllm_production_stack_tpu.engine.engine import EngineStatsSnapshot
    from vllm_production_stack_tpu.engine.metrics import EngineMetrics

    m = EngineMetrics("tiny")
    flow = KVFlowMeter()
    flow.record_decision("load", 3)
    flow.record_decision("fallback_recompute")
    text = m.render(
        EngineStatsSnapshot(kv_flow=flow.snapshot())
    ).decode()
    lines = [
        ln for ln in text.splitlines()
        if ln.startswith("tpu:kv_hydration_decision_total{")
    ]
    assert len(lines) == 3  # closed choice set, seeded from first scrape
    assert (
        'tpu:kv_hydration_decision_total{choice="load",model_name="tiny"}'
        " 3.0" in text
    )
    assert (
        'tpu:kv_hydration_decision_total{choice="fallback_recompute",'
        'model_name="tiny"} 1.0' in text
    )


def test_flow_meter_decision_unknown_choice_fails_loud():
    flow = KVFlowMeter()
    with pytest.raises(KeyError):
        flow.record_decision("lod")


def test_null_flow_is_a_complete_noop():
    """A live meter feeds the metric side AND the TierBandwidth estimators
    (the planner's decision input) from one record; the shared NULL_FLOW
    singleton stays a COMPLETE no-op: unrelated standalone tiers must not
    cross-pollinate each other's samples."""
    from vllm_production_stack_tpu.engine.kv_flow import NULL_FLOW

    flow = KVFlowMeter()
    flow.record("disk", "in", TierBandwidth.MIN_BYTES, 32, 0.01)
    flow.record("disk", "in", TierBandwidth.MIN_BYTES, 32, 0.01)
    snap = flow.snapshot()
    assert snap["bytes"]["disk/in"] == 2 * TierBandwidth.MIN_BYTES
    assert snap["bandwidth_bytes_per_s"]["disk/in"] > 0
    assert flow.bandwidth_measured()[("disk", "in")]
    NULL_FLOW.record("disk", "in", TierBandwidth.MIN_BYTES, 32, 0.01)
    assert NULL_FLOW.bandwidth[("disk", "in")].samples == 0
    assert NULL_FLOW.snapshot()["bytes"]["disk/in"] == 0


def test_hydrator_rejects_bad_mode():
    with pytest.raises(ValueError):
        Hydrator(mode="always")


def test_sync_mode_has_no_hydrator(tmp_path):
    eng = _engine(mode="sync", disk_dir=str(tmp_path))
    assert eng.hydrator is None
    eng.runner.shutdown(wait=True)


def test_off_mode_ignores_disk_residency(tmp_path):
    prompt = _prompt(9)
    ref = _seed_disk(tmp_path, prompt)
    eng = _engine(mode="off", disk_dir=str(tmp_path))
    got = eng.generate([prompt], GREEDY)[0]["token_ids"]
    assert got == ref
    hyd, total = _partition(eng)
    assert total == eng._prompt_tokens
    assert hyd["disk_load"] == 0  # residency ignored: everything recomputed
    assert eng.host_tier.disk.stats.loads == 0
    eng.runner.shutdown(wait=True)


def test_auto_mode_unmeasured_falls_back_to_sync_load(tmp_path):
    """The auto-mode bootstrap: below the sample floor the admission uses
    the legacy blocking load — whose transfers are what cross the floor —
    so behavior (and attribution) matches the pre-planner stack
    exactly."""
    prompt = _prompt(11)
    ref = _seed_disk(tmp_path, prompt)
    eng = _engine(mode="auto", disk_dir=str(tmp_path))
    got = eng.generate([prompt], GREEDY)[0]["token_ids"]
    assert got == ref
    hyd, total = _partition(eng)
    assert total == eng._prompt_tokens
    assert hyd["disk_load"] > 0  # the sync path loaded the prefix
    assert eng.flow.snapshot()["decisions"]["load"] == 0  # no plan ran
    eng.runner.shutdown(wait=True)
