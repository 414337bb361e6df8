"""KV-hierarchy flow telemetry (docs/30-kv-flow-telemetry.md).

The load-bearing properties: (1) the hydration attribution partitions
every admitted request's prompt tokens EXACTLY — hbm_hit + host_reload +
disk_load + remote_fetch + recomputed == prompt_tokens — across warm,
host-resident, disk-resident and remote-resident prefixes; (2) every
tier move records bytes/blocks/latency into the flow meter, INCLUDING
failure paths (a stalled PD transfer, a tripped remote fetch); (3) the
exporter renders the closed (tier, direction)/(source) label sets with
bounded cardinality; (4) the contract checker validates closed label
sets against the exporters and the dashboard/rule references.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np
import pytest

from vllm_production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from vllm_production_stack_tpu.engine.kv_flow import (
    DIRECTIONS,
    HYDRATION_SOURCES,
    KVFlowMeter,
    TRANSFER_TIERS,
    TierBandwidth,
)
from vllm_production_stack_tpu.engine.request import SamplingParams

pytestmark = pytest.mark.kvflow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BS = 8
GREEDY = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)


def _engine(num_blocks=12, num_host_blocks=32, seed=0, disk_dir="",
            disk_gib=0.0, remote_url="", codec="none"):
    from vllm_production_stack_tpu.engine.engine import LLMEngine

    return LLMEngine(EngineConfig(
        model=ModelConfig.tiny(),
        cache=CacheConfig(
            block_size=BS, num_blocks=num_blocks,
            num_host_blocks=num_host_blocks,
            disk_kv_dir=disk_dir, disk_kv_gib=disk_gib,
            remote_kv_url=remote_url, kv_at_rest_codec=codec,
        ),
        scheduler=SchedulerConfig(
            max_num_seqs=2, max_num_batched_tokens=64,
            decode_buckets=(2,), prefill_buckets=(32, 64), decode_window=4,
        ),
        seed=seed,
    ))


def _prompt(seed, n=4 * BS):
    return [int(t) for t in
            np.random.RandomState(seed).randint(1, 500, size=n)]


def _hydration_total(eng) -> tuple[dict, int]:
    hyd = eng.flow.snapshot()["hydration"]
    return hyd, sum(hyd.values())


# -- meter unit --------------------------------------------------------------


def test_meter_accumulates_and_snapshot_shape():
    m = KVFlowMeter()
    m.record("disk", "in", 4096, 1, 0.001)
    m.record("disk", "in", 4096, 1, 0.001)
    m.record("remote", "out", 100, 2, 0.5)
    snap = m.snapshot()
    assert snap["bytes"]["disk/in"] == 8192
    assert snap["blocks"]["disk/in"] == 2
    assert snap["transfers"]["disk/in"] == 2
    assert snap["seconds_hist"]["disk/in"]["count"] == 2
    assert snap["bytes"]["remote/out"] == 100
    # every (tier, direction) combo exists even untouched (seeded at zero)
    assert set(snap["bytes"]) == {
        f"{t}/{d}" for t in TRANSFER_TIERS for d in DIRECTIONS
    }
    # recent-mean bandwidth of uniform back-to-back samples == plain mean
    assert snap["bandwidth_bytes_per_s"]["disk/in"] == pytest.approx(
        8192 / 0.002, rel=0.01
    )


def test_meter_unknown_tier_fails_loud():
    m = KVFlowMeter()
    with pytest.raises(KeyError):
        m.record("dsk", "in", 1, 1, 0.1)
    with pytest.raises(KeyError):
        m.record_hydration({"hbm": 8})
    with pytest.raises(KeyError):
        # even at count 0: a usually-zero mistyped key must not pass
        # silently until its rare nonzero hit drops tokens
        m.record_hydration({"hbm": 0})


def test_bandwidth_failed_transfers_drag_estimate_down():
    bw = TierBandwidth()
    now = time.perf_counter()
    bw.record(10_000, 0.01, now)  # 1 MB/s
    healthy = bw.bytes_per_s
    for i in range(20):  # outage: round trips burn time, move nothing
        bw.record(0, 2.0, now + i)
    assert bw.bytes_per_s < healthy / 100


# -- hydration attribution ---------------------------------------------------


def test_attribution_warm_vs_cold_partition_exact():
    eng = _engine()
    prompt = _prompt(0)
    eng.generate([prompt], GREEDY)
    hyd, total = _hydration_total(eng)
    assert hyd["recomputed"] == 4 * BS and total == 4 * BS
    # second pass: 3 full blocks hit HBM (the match keeps >=1 token to
    # compute, trimming the 4th), the rest recomputes — partition exact
    eng.generate([prompt], GREEDY)
    hyd, total = _hydration_total(eng)
    assert hyd["hbm_hit"] == 3 * BS
    assert hyd["recomputed"] == 4 * BS + BS
    assert total == eng._prompt_tokens == 8 * BS
    eng.runner.shutdown(wait=True)


def test_attribution_host_reload_and_disk_load(tmp_path):
    # the engine floors the ring at 16 blocks when a disk tier exists, so
    # churn 8 prompts (32 distinct blocks) through the 11-usable-block
    # pool: the first prompt's blocks overflow the ring onto disk, the
    # re-issue pulls them back up through both rungs
    eng = _engine(num_host_blocks=4, disk_dir=str(tmp_path), disk_gib=0.01)
    prompt = _prompt(1)
    eng.generate([prompt], GREEDY)
    for s in range(8):
        eng.generate([_prompt(100 + s)], GREEDY)
    assert eng.host_tier.disk.stats.stores > 0  # ring overflowed to disk
    eng.generate([prompt], GREEDY)
    hyd, total = _hydration_total(eng)
    assert hyd["host_reload"] + hyd["disk_load"] > 0
    assert total == eng._prompt_tokens
    # the hops metered: disk/in count matches the tier's own loads
    snap = eng.flow.snapshot()
    assert snap["blocks"]["disk/in"] == eng.host_tier.disk.stats.loads
    assert snap["blocks"]["host/in"] == eng.host_tier.stats.reloads
    eng.runner.shutdown(wait=True)


@pytest.mark.parametrize("codec", ["none", "int4"])
def test_attribution_remote_fetch_partition_exact(codec):
    """With the int4 at-rest codec (docs/38) the same reload keeps the
    partition exact and moves >3x fewer wire bytes. Only the lossless arm
    can hold the tokens to the from-scratch ones: a random tiny model's
    argmax margins are narrower than int4's dequantization error."""
    from vllm_production_stack_tpu.kvstore.server import run_in_thread

    url, stop, _ = run_in_thread(capacity_bytes=1 << 24)
    try:
        eng_a = _engine(remote_url=url, codec=codec)
        prompt = _prompt(7)
        ref = eng_a.generate([prompt], GREEDY)[0]["token_ids"]
        # churn so the prompt's blocks are EVICTED into the host ring —
        # only resolved ring entries write through to the remote store
        for s in (1, 2, 3, 4):
            eng_a.generate([_prompt(200 + s)], GREEDY)
        eng_a.host_tier.flush()
        assert eng_a.remote_tier.drain()
        # same fingerprint (same config+seed), fresh local tiers: the
        # prefix can only come from the remote store
        eng_b = _engine(remote_url=url, codec=codec)
        got = eng_b.generate([prompt], GREEDY)[0]["token_ids"]
        assert len(got) == len(ref) and (got == ref or codec == "int4")
        hyd, total = _hydration_total(eng_b)
        assert hyd["remote_fetch"] == 3 * BS
        assert hyd["recomputed"] == BS
        assert total == eng_b._prompt_tokens
        snap = eng_b.flow.snapshot()
        # the meter counts blocks MOVED (the whole 4-block resident run);
        # attribution counts blocks KEPT (the trim frees the 4th) — both
        # honest, deliberately different questions
        assert snap["blocks"]["remote/in"] == 4
        assert snap["bytes"]["remote/in"] > 0
        ratio = snap["logical_bytes"]["remote/in"] / snap["bytes"]["remote/in"]
        assert ratio > 3 if codec == "int4" else ratio == 1
        eng_a.runner.shutdown(wait=True)
        eng_b.runner.shutdown(wait=True)
    finally:
        stop()


def test_attribution_recorded_exactly_once_per_request():
    from vllm_production_stack_tpu.engine.scheduler import Scheduler

    sched = Scheduler(
        ModelConfig.tiny(),
        CacheConfig(block_size=BS, num_blocks=12),
        SchedulerConfig(
            max_num_seqs=2, max_num_batched_tokens=64,
            decode_buckets=(2,), prefill_buckets=(32, 64),
        ),
    )
    from vllm_production_stack_tpu.engine.request import Request

    req = Request(request_id="r0", prompt_token_ids=_prompt(3))
    sched.add_request(req)
    sched._admit(req)
    first = dict(req.hydration)
    assert sum(first.values()) == req.num_prompt_tokens
    assert sched.flow.snapshot()["hydrated_requests"] == 1
    # re-admission (preemption resume) must NOT re-attribute
    sched._attribute_hydration(req, 2)
    assert req.hydration == first
    assert sched.flow.snapshot()["hydrated_requests"] == 1


def test_terminal_output_carries_hydration_and_trace_event():
    from vllm_production_stack_tpu.engine.server import EngineServer

    eng = _engine()
    server = EngineServer(eng, served_model_name="tiny")
    rid = eng.add_request(prompt_token_ids=_prompt(9), sampling=GREEDY)
    terminal = None
    while eng.has_unfinished():
        for out in eng.step():
            if out.finished:
                terminal = out
    assert terminal is not None and terminal.request_id == rid
    assert terminal.hydration is not None
    assert sum(terminal.hydration.values()) == 4 * BS
    trace = server.traces.start(rid, "engine.request")
    server._trace_output(trace, terminal)
    events = {name: attrs for _, name, attrs in trace.root.events}
    assert "kv_hydration" in events
    assert events["kv_hydration"]["recomputed"] == 4 * BS
    eng.runner.shutdown(wait=True)


# -- tier transfer meters ----------------------------------------------------


def test_disk_tier_records_exact_bytes(tmp_path):
    from vllm_production_stack_tpu.engine.kv_disk_tier import DiskKVTier

    flow = KVFlowMeter()
    tier = DiskKVTier(str(tmp_path), max_bytes=1 << 20, flow=flow)
    arr = np.arange(64, dtype=np.float32).reshape(2, 32)
    tier.store(7, arr)
    snap = flow.snapshot()
    assert snap["blocks"]["disk/out"] == 1
    # stored payload = frame header + raw bytes: meter matches the file
    assert snap["bytes"]["disk/out"] == tier.total_bytes
    got = tier.load(7)
    np.testing.assert_array_equal(got, arr)
    snap = flow.snapshot()
    assert snap["blocks"]["disk/in"] == 1
    # WIRE bytes = the whole frame actually read back (symmetric with
    # store's whole-file accounting); the decoded array is the LOGICAL
    # side — with no at-rest codec the ratio is pure header overhead
    assert snap["bytes"]["disk/in"] == tier.total_bytes
    assert snap["logical_bytes"]["disk/in"] == arr.nbytes
    assert snap["seconds_hist"]["disk/in"]["count"] == 1


def test_remote_tier_put_and_fetch_metered():
    from vllm_production_stack_tpu.kvstore.client import RemoteKVTier
    from vllm_production_stack_tpu.kvstore.server import run_in_thread

    url, stop, _ = run_in_thread(capacity_bytes=1 << 24)
    try:
        flow = KVFlowMeter()
        tier = RemoteKVTier(url, fingerprint="fp", flow=flow)
        arr = np.full((2, 8), 3.0, dtype=np.float32)
        tier.put_async(11, arr)
        assert tier.drain()
        snap = flow.snapshot()
        assert snap["blocks"]["remote/out"] == 1
        assert snap["bytes"]["remote/out"] == arr.nbytes
        got = tier.fetch_run([11])
        assert len(got) == 1
        snap = flow.snapshot()
        assert snap["blocks"]["remote/in"] == 1
        assert snap["bytes"]["remote/in"] == arr.nbytes
        tier.close()
    finally:
        stop()


def test_remote_fetch_partial_failure_keeps_valid_prefix():
    """fetch_run on a response that goes corrupt mid-stream returns the
    valid prefix (it used to discard the whole batch), counts the partial
    blocks in RemoteTierStats, and records the batch's timing."""
    from vllm_production_stack_tpu.engine.kv_transfer import block_frame
    from vllm_production_stack_tpu.kvstore.client import RemoteKVTier

    flow = KVFlowMeter()
    tier = RemoteKVTier(
        "tpukv://127.0.0.1:1", fingerprint="fp", timeout=0.2, flow=flow
    )
    a1 = np.full((2, 4), 1.0, dtype=np.float32)
    a2 = np.full((2, 4), 2.0, dtype=np.float32)
    payload = (
        block_frame(11, a1) + block_frame(22, a2)
        + b"\xff\xff\xff\xffgarbage-that-claims-a-4GiB-header"
    )
    tier._fetch_conn.request = lambda *a, **k: (200, {}, payload)
    got = tier.fetch_run([11, 22, 33])
    assert len(got) == 2
    np.testing.assert_array_equal(got[0], a1)
    np.testing.assert_array_equal(got[1], a2)
    assert tier.stats.fetches == 1
    assert tier.stats.fetched_blocks == 2  # the partial batch IS recorded
    assert tier.stats.errors == 1
    snap = flow.snapshot()
    assert snap["blocks"]["remote/in"] == 2
    assert snap["bytes"]["remote/in"] == a1.nbytes + a2.nbytes
    tier.close()


def test_remote_tier_trip_then_recover_accounting():
    """Breaker trip (dead store) records the failed round trip at 0 bytes
    — the bandwidth signal collapses honestly — and recovery after the
    cooldown resumes exact accounting."""
    from vllm_production_stack_tpu.kvstore.client import RemoteKVTier
    from vllm_production_stack_tpu.kvstore.server import run_in_thread

    url, stop, _ = run_in_thread(capacity_bytes=1 << 24)
    try:
        flow = KVFlowMeter()
        tier = RemoteKVTier(url, fingerprint="fp", timeout=0.5,
                            cooldown_s=0.05, flow=flow)
        arr = np.full((2, 4), 5.0, dtype=np.float32)
        tier.put_async(42, arr)
        assert tier.drain()
        # sever the fetch connection: next fetch trips the breaker
        good_host, tier._fetch_conn.port = tier._fetch_conn.port, 1
        tier._fetch_conn.close()
        tier._fetch_conn.host, tier._fetch_conn.port = "127.0.0.1", 1
        assert tier.fetch_run([42]) == []
        assert tier.stats.errors == 1
        trip_snap = flow.snapshot()
        assert trip_snap["transfers"]["remote/in"] == 1
        assert trip_snap["bytes"]["remote/in"] == 0  # timing kept, 0 bytes
        # cooldown window: fetches short-circuit (no extra round trips)
        assert tier.fetch_run([42]) == []
        assert trip_snap["transfers"]["remote/in"] == 1
        # recover: restore the port, wait out the cooldown
        tier._fetch_conn.close()
        tier._fetch_conn.port = good_host
        time.sleep(0.06)
        got = tier.fetch_run([42])
        assert len(got) == 1
        assert tier.stats.fetches == 1 and tier.stats.fetched_blocks == 1
        snap = flow.snapshot()
        assert snap["transfers"]["remote/in"] == 2
        assert snap["bytes"]["remote/in"] == arr.nbytes
        tier.close()
    finally:
        stop()


def test_feed_partial_vs_feed_contract():
    from vllm_production_stack_tpu.engine.kv_transfer import (
        FrameParser,
        block_frame,
    )

    arr = np.ones((2, 2), dtype=np.float32)
    corrupt = block_frame(1, arr) + b"\xff\xff\xff\xffXXXX"
    with pytest.raises(ValueError):
        FrameParser().feed(corrupt)  # all-or-nothing path still raises
    p = FrameParser()
    frames = p.feed_partial(corrupt)
    assert len(frames) == 1 and frames[0][0] == 1
    assert p.error is not None
    assert p.feed_partial(b"more") == []  # parser is dead after the fault


@pytest.mark.chaos
def test_stalled_device_transfer_shows_in_flow_meter(monkeypatch):
    """Chaos: a PD device transfer that stalls then faults must surface in
    tpu:kv_transfer_seconds{tier="device"} (elapsed recorded, 0 bytes)
    rather than vanish — the abort path records BEFORE re-raising."""
    from vllm_production_stack_tpu.engine import kv_device_transfer as kdt

    eng_a = _engine(num_blocks=40)
    eng_b = _engine(num_blocks=40)
    prompt = _prompt(21, n=3 * BS)
    eng_a.generate([prompt], GREEDY)

    def stall_then_die(*a, **k):
        time.sleep(0.05)
        raise RuntimeError("injected device stall")

    monkeypatch.setattr(kdt, "_gather_blocks", stall_then_die)
    with pytest.raises(RuntimeError, match="injected device stall"):
        kdt.ship_kv_device(eng_a, eng_b, prompt)
    for eng, direction in ((eng_a, "out"), (eng_b, "in")):
        snap = eng.flow.snapshot()
        key = f"device/{direction}"
        assert snap["transfers"][key] == 1
        assert snap["bytes"][key] == 0  # nothing actually arrived
        assert snap["seconds_hist"][key]["sum"] >= 0.05  # the stall shows
    # and the destination pool leaked nothing: all blocks still free
    assert eng_b.scheduler.pool.num_free == eng_b.scheduler.pool.num_usable
    eng_a.runner.shutdown(wait=True)
    eng_b.runner.shutdown(wait=True)


def test_successful_device_transfer_metered(monkeypatch):
    from vllm_production_stack_tpu.engine import kv_device_transfer as kdt

    eng_a = _engine(num_blocks=40)
    eng_b = _engine(num_blocks=40)
    prompt = _prompt(22, n=3 * BS)
    eng_a.generate([prompt], GREEDY)
    n = kdt.ship_kv_device(eng_a, eng_b, prompt)
    assert n == 3
    snap = eng_b.flow.snapshot()
    assert snap["blocks"]["device/in"] == 3
    assert snap["bytes"]["device/in"] == 3 * kdt._block_nbytes(
        eng_a.runner.kv_caches
    )
    assert eng_a.flow.snapshot()["blocks"]["device/out"] == 3
    eng_a.runner.shutdown(wait=True)
    eng_b.runner.shutdown(wait=True)


# -- hydration signal / config -----------------------------------------------


def test_hydration_signal_shape():
    eng = _engine()
    sig = eng.hydration_signal()
    assert set(sig["fetch_bandwidth_bytes_per_s"]) == {
        "host", "disk", "remote", "device", "peer"
    }
    assert sig["flops_per_token"] > 0
    assert sig["block_bytes"] > 0
    assert sig["block_size_tokens"] == BS
    assert "prefill_flops_per_s" in sig and "peak_flops_per_s" in sig
    eng.runner.shutdown(wait=True)


# -- exporter ----------------------------------------------------------------


def test_exporter_renders_kv_flow_series_with_bounded_cardinality():
    from vllm_production_stack_tpu.engine.engine import EngineStatsSnapshot
    from vllm_production_stack_tpu.engine.metrics import EngineMetrics

    m = EngineMetrics("tiny")
    flow = KVFlowMeter()
    flow.record("disk", "in", 4096, 1, 0.002)
    flow.record_hydration({"hbm_hit": 16, "recomputed": 16})
    snap = EngineStatsSnapshot(kv_flow=flow.snapshot(), disk_kv_loads=1)
    text = m.render(snap).decode()

    def series(name):
        return [
            ln for ln in text.splitlines()
            if ln.startswith(name + "{") or ln.startswith(name + " ")
        ]

    assert len(series("tpu:kv_transfer_bytes_total")) == 10  # 5 tiers x 2
    assert len(series("tpu:kv_transfer_blocks_total")) == 10
    assert len(series("tpu:kv_tier_bandwidth_bytes_per_s")) == 10
    assert len(series("tpu:request_prefix_tokens_total")) == 6
    assert any(
        'tier="disk",direction="in"' in ln.replace("direction=", "direction=")
        or 'direction="in"' in ln and 'tier="disk"' in ln
        for ln in series("tpu:kv_transfer_bytes_total")
    )
    assert (
        'tpu:request_prefix_tokens_total{model_name="tiny",'
        'source="hbm_hit"} 16.0' in text
    )
    assert "tpu:disk_kv_loaded_blocks_total" in text
    assert "tpu:disk_kv_stored_blocks_total" in text
    # the latency histogram renders every combo from the first scrape
    bucket_lines = [
        ln for ln in text.splitlines()
        if ln.startswith("tpu:kv_transfer_seconds_bucket")
    ]
    combos = {
        (t, d)
        for t in TRANSFER_TIERS for d in DIRECTIONS
        if any(f'tier="{t}"' in ln and f'direction="{d}"' in ln
               for ln in bucket_lines)
    }
    assert len(combos) == 10  # 5 tiers x 2 directions
    # delta-bump idempotence: rendering the same snapshot twice must not
    # double-count the cumulative counters
    text2 = m.render(snap).decode()
    assert (
        'tpu:kv_transfer_bytes_total{direction="in",model_name="tiny",'
        'tier="disk"} 4096.0' in text2
    )


# -- contract checker label-set validation -----------------------------------


def _load_checker():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import check_metrics_contract as cmc
    finally:
        sys.path.pop(0)
    return cmc


def test_contract_label_sets_match_source_modules():
    """METRIC_LABEL_VALUES must reference the same tuples the recording
    modules use — aliased imports, so drift is impossible by construction
    (this guards against someone re-introducing a literal copy)."""
    from vllm_production_stack_tpu import metrics_contract as mc
    from vllm_production_stack_tpu.engine.saturation import WASTE_REASONS

    assert mc.METRIC_LABEL_VALUES[mc.WASTED_TOKENS]["reason"] is WASTE_REASONS
    assert mc.METRIC_LABEL_VALUES[mc.KV_TRANSFER_BYTES]["tier"] == (
        TRANSFER_TIERS
    )
    assert mc.METRIC_LABEL_VALUES[mc.REQUEST_PREFIX_TOKENS]["source"] == (
        HYDRATION_SOURCES
    )


def test_checker_validates_exported_label_sets():
    cmc = _load_checker()
    assert cmc.check_exported_label_sets() == []


def test_checker_clean_on_shipped_references():
    cmc = _load_checker()
    assert cmc.check_reference_label_values() == []


def test_checker_rejects_typoed_label_value(tmp_path, monkeypatch):
    """A rule matching tier="dsk" (typo) passed the old checker silently —
    the closed-set validation must flag it."""
    cmc = _load_checker()
    bad = tmp_path / "typo.yaml"
    bad.write_text(
        "groups:\n"
        "  - name: g\n"
        "    rules:\n"
        "      - record: tpu:typo:rate5m\n"
        "        expr: >-\n"
        "          sum(rate(tpu:kv_transfer_bytes_total"
        '{tier="dsk",direction="in"}[5m]))\n'
    )
    monkeypatch.setattr(cmc, "RULES_DIR", str(tmp_path))
    problems = cmc.check_reference_label_values()
    assert any("'dsk'" in p for p in problems), problems
    # the correctly-spelled matcher passes
    bad.write_text(
        "groups:\n"
        "  - name: g\n"
        "    rules:\n"
        "      - record: tpu:fine:rate5m\n"
        "        expr: >-\n"
        "          sum(rate(tpu:kv_transfer_bytes_total"
        '{tier="disk",direction="in"}[5m]))\n'
    )
    assert cmc.check_reference_label_values() == []


def test_full_contract_check_passes():
    cmc = _load_checker()
    assert cmc.check() == []
