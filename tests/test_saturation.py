"""Saturation & goodput telemetry (docs/29-saturation-slo.md).

The load-bearing property: the goodput ledger partitions every sampled
token EXACTLY — delivered + wasted{reason} + pending == sampled — across
the serial and pipelined step loops, rollbacks, preemptions, deadline
expiry, QoS shed evictions and severed (aborted) streams. Plus: the step
meter's accounting, exporter label-cardinality bounds, and the SLO rule
pack lint (valid YAML, sane PromQL, alert hygiene — no promtool needed).
"""

from __future__ import annotations

import os
import re
import sys
import time

import pytest

import engine_kit as ek
from vllm_production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from vllm_production_stack_tpu.engine.request import (
    Request,
    RequestStatus,
    SamplingParams,
)
from vllm_production_stack_tpu.engine.saturation import (
    FINISH_REASONS,
    GoodputLedger,
    StepMeter,
    WASTE_REASONS,
    detect_peak_flops,
    matmul_params,
    step_flops,
)
from vllm_production_stack_tpu.engine.scheduler import (
    PrefillWork,
    Scheduler,
)

pytestmark = pytest.mark.saturation

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def assert_balanced(engine) -> dict:
    bal = engine.goodput_balance()
    assert bal["balanced"], bal
    return bal


# -- ledger unit -------------------------------------------------------------


def test_ledger_partition_arithmetic():
    led = GoodputLedger()
    led.sampled(10)
    led.deliver(6)
    led.waste("overshoot", 3)
    led.waste("rollback", 1)
    snap = led.snapshot()
    assert snap["sampled"] == 10
    assert snap["delivered"] + snap["wasted_total"] == 10
    # negative / zero amounts are no-ops, not corruption
    led.waste("severed", 0)
    led.waste("severed", -5)
    led.deliver(-1)
    assert led.snapshot() == snap


def test_ledger_unknown_reason_fails_loud():
    with pytest.raises(KeyError):
        GoodputLedger().waste("not_a_reason", 1)


def test_finish_reason_map_covers_every_terminal_status():
    """Every finished RequestStatus must map to delivered-or-reason — an
    unmapped new status would silently fall back to 'severed'."""
    for status in RequestStatus:
        if status.finished:
            assert status.name in FINISH_REASONS, status


def test_classify_finish_unknown_status_still_partitions():
    led = GoodputLedger()
    led.sampled(4)
    led.classify_finish("FINISHED_FUTURE_THING", 4)
    assert led.wasted["severed"] == 4  # never escapes the partition


# -- meter unit --------------------------------------------------------------


def _sched_cfg(**kw):
    base = dict(
        max_num_seqs=8,
        max_num_batched_tokens=64,
        decode_buckets=(4, 8),
        prefill_buckets=(16, 32, 64),
        decode_window=4,
    )
    base.update(kw)
    return SchedulerConfig(**base)


def test_meter_occupancy_and_padding_accounting():
    m = StepMeter(ModelConfig.tiny(), _sched_cfg())
    # 6 rows of 8 seats → occupancy 0.75; decode bucket pads 6 → 8 rows
    m.record_decode(rows=6, window=4, accepted_tokens=20, sum_context=100)
    snap = m.snapshot()
    assert snap["steps"]["decode"] == 1
    assert snap["step_tokens"]["decode"] == 20
    assert snap["padded_tokens"]["decode"] == 8 * 4
    h = snap["occupancy_hist"]
    assert h["count"] == 1
    assert abs(h["sum"] - 0.75) < 1e-9
    # the 0.75 observation lands in the le=0.75 bucket
    idx = list(h["buckets"]).index(0.75)
    assert h["counts"][idx] == 1
    # prefill: 2 rows × 24 tokens = 48 useful, of the 64 positions the
    # program computed (the runner's count: the packed axis's bucket)
    m.record_prefill(chunk_tokens=48, sum_context=600, positions=64)
    snap = m.snapshot()
    assert snap["step_tokens"]["prefill"] == 48
    assert snap["padded_tokens"]["prefill"] == 2 * 32
    assert snap["model_flops_total"] > 0


def test_meter_gauges_decay_when_idle():
    """With no steps resolving, the EWMA gauges must fall toward 0 at
    READ time — a frozen last-busy occupancy would hold the KEDA
    occupancy trigger above threshold forever (no scale-in)."""
    m = StepMeter(ModelConfig.tiny(), _sched_cfg())
    m.record_decode(rows=8, window=4, accepted_tokens=32, sum_context=100)
    time.sleep(0.01)
    m.record_decode(rows=8, window=4, accepted_tokens=32, sum_context=100)
    busy = m.snapshot()["decode_seat_occupancy"]
    assert busy > 0
    m._last_t -= 120.0  # simulate two minutes of idle
    idle = m.snapshot()["decode_seat_occupancy"]
    assert idle < busy * 1e-4
    assert m.snapshot()["mfu"] <= idle  # achieved flops decayed too


def test_meter_padding_gauge_excludes_overshoot():
    """The padding EWMA measures bucket padding ONLY: a full-bucket
    dispatch whose rows all stopped mid-window has zero padding (the
    discards are the ledger's wasted{overshoot}, not a bucket problem)."""
    m = StepMeter(ModelConfig.tiny(), _sched_cfg())
    time.sleep(0.001)
    m.record_decode(rows=8, window=4, accepted_tokens=8, sum_context=100)
    time.sleep(0.01)
    m.record_decode(rows=8, window=4, accepted_tokens=8, sum_context=100)
    assert m.padding_waste == 0.0
    # but the counters keep the full picture: useful 16 vs 64 slots
    snap = m.snapshot()
    assert snap["step_tokens"]["decode"] == 16
    assert snap["padded_tokens"]["decode"] == 64


def test_ledger_counts_rejected_verify_positions_as_rollback():
    """Spec-decode verify: positions past the first draft mismatch were
    argmax-sampled on device and discarded — they must enter the ledger
    (reason rollback) or goodput would read 1.0 under 0% acceptance."""
    from vllm_production_stack_tpu.engine.scheduler import VerifyWork

    s = make_scheduler(window=4)
    r = req("a", 8, max_tokens=20, ignore_eos=True)
    s.add_request(r)
    drive(s, s.schedule())  # prefill → 1 output token
    base = s.ledger.snapshot()
    work = VerifyWork(
        requests=[r],
        token_ids=[[r.token_at(r.num_computed_tokens)] + [7, 7, 7]],
        positions=[list(range(r.num_computed_tokens,
                              r.num_computed_tokens + 4))],
        proposals=[[7, 7, 7]],
        context_lens=[r.num_computed_tokens + 4],
    )
    # model argmax disagrees with every proposal: accepted = [bonus] only
    s.postprocess(work, [[9, 1, 2, 3]])
    snap = s.ledger.snapshot()
    # 4 fed positions sampled: 1 accepted (pending), 3 rejected → rollback
    assert snap["sampled"] - base["sampled"] == 4
    assert snap["wasted"]["rollback"] - base["wasted"]["rollback"] == 3
    assert sched_balance(s)["balanced"]


def test_flop_model_sanity():
    tiny = ModelConfig.tiny()
    p = matmul_params(tiny)
    # hand count for the tiny config: per layer attn (64*64 + 2*64*32 +
    # 64*64) + mlp 3*64*128, 2 layers, + lm_head 512*64
    per_layer = (64 * 4 * 16 + 2 * 64 * 2 * 16 + 4 * 16 * 64) + 3 * 64 * 128
    assert p == 2 * per_layer + 512 * 64
    # flops grow with context (the attention term)
    assert step_flops(tiny, 8, 1000) > step_flops(tiny, 8, 10)
    assert step_flops(tiny, 8, 0) == 2.0 * p * 8


def test_peak_flops_env_override(monkeypatch):
    monkeypatch.setenv("TPU_PEAK_FLOPS", "1e12")
    peak = detect_peak_flops()
    # per-chip override × local device count (≥1 even on CPU)
    assert peak >= 1e12
    monkeypatch.delenv("TPU_PEAK_FLOPS")
    # CPU backend: unknown device kind → 0, and MFU must read 0, not junk
    assert detect_peak_flops() == 0.0


# -- scheduler-level ledger (fabricated sampled rows, no model runner) -------


def make_scheduler(num_blocks=16, block_size=4, max_batched=16, max_seqs=4,
                   window=4):
    return Scheduler(
        ModelConfig.tiny(max_model_len=128),
        CacheConfig(block_size=block_size, num_blocks=num_blocks,
                    enable_prefix_caching=True),
        SchedulerConfig(
            max_num_seqs=max_seqs,
            max_num_batched_tokens=max_batched,
            decode_buckets=(max_seqs,),
            prefill_buckets=(max_batched,),
            decode_window=window,
        ),
    )


def req(rid, n_prompt, **kw):
    return Request(
        request_id=rid,
        prompt_token_ids=list(range(100, 100 + n_prompt)),
        sampling=SamplingParams(**kw),
    )


def drive(sched, work, start_token=1000):
    if isinstance(work, PrefillWork):
        rows = [
            [start_token + i] if s else [] for i, s in enumerate(work.sample)
        ]
    else:
        rows = [
            [start_token + i * 100 + k for k in range(work.window)]
            for i in range(len(work.requests))
        ]
    return sched.postprocess(work, rows)


def sched_balance(s: Scheduler) -> dict:
    return s.goodput_balance()


def test_sched_overshoot_and_delivery():
    s = make_scheduler(window=4)
    r = req("a", 4, max_tokens=6, ignore_eos=True)
    s.add_request(r)
    drive(s, s.schedule())  # prefill: 1 sampled, pending
    assert s.ledger.sampled_total == 1
    assert r.ledger_pending == 1
    drive(s, s.schedule())  # window of 4 → 5 outputs
    drive(s, s.schedule())  # window clipped by max_tokens: overshoot
    assert r.status == RequestStatus.FINISHED_LENGTH
    snap = sched_balance(s)
    assert snap["balanced"], snap
    assert snap["delivered"] == 6
    assert snap["wasted"]["overshoot"] == snap["sampled"] - 6


def test_sched_preemption_keeps_pending_then_charges_recompute():
    s = make_scheduler(num_blocks=20, block_size=4, max_seqs=2, window=2)
    a, b = req("a", 8, max_tokens=20, ignore_eos=True), req(
        "b", 8, max_tokens=20, ignore_eos=True
    )
    s.add_request(a)
    s.add_request(b)
    held = []
    for _ in range(20):
        if not held and len(b.output_token_ids) >= 5:
            # admission reserved both rows' whole budgets: the pool goes
            # from under them
            held = ek.squeeze(s.pool)
        work = s.schedule()
        if work is None:
            break
        drive(s, work)
        if s.total_preemptions:
            break
    assert s.total_preemptions >= 1
    for blk in held:
        s.pool.free_scratch(blk)
    victim = next(r for r in (a, b) if r.num_preemptions > 0)
    # pending SURVIVES preemption — the token fate is still open
    assert victim.ledger_pending > 0
    before = s.ledger.wasted["preempted_recompute"]
    # let the victim resume and re-prefill its generated positions
    for _ in range(40):
        if not s.has_unfinished():
            break
        work = s.schedule()
        if work is None:
            break
        drive(s, work)
    assert s.ledger.wasted["preempted_recompute"] > before
    assert sched_balance(s)["balanced"]


def test_sched_shed_eviction_classifies_pending():
    s = make_scheduler(num_blocks=32, max_seqs=2, window=2)
    r = req("victim", 4, max_tokens=10, ignore_eos=True)
    r.priority = 2  # batch class — evictable by a realtime arrival
    s._qos_active = True
    s.add_request(r)
    drive(s, s.schedule())  # prefill
    drive(s, s.schedule())  # one decode window: pending grows
    assert r.ledger_pending > 0
    # preempt it back to waiting (pending survives), then evict it
    s._preempt(r)
    pending = r.ledger_pending
    assert pending > 0
    assert s.mark_shed_victim(0)
    s.apply_evictions()
    assert r.status == RequestStatus.FINISHED_SHED
    assert s.ledger.wasted["shed_evicted"] == pending
    assert sched_balance(s)["balanced"]


def test_sched_deadline_and_abort_classification():
    s = make_scheduler(window=2)
    a = req("a", 4, max_tokens=10, ignore_eos=True)
    b = req("b", 4, max_tokens=10, ignore_eos=True)
    s.add_request(a)
    s.add_request(b)
    for _ in range(3):
        drive(s, s.schedule())
    assert a.ledger_pending > 0 and b.ledger_pending > 0
    pa, pb = a.ledger_pending, b.ledger_pending
    a.deadline = time.monotonic() - 1.0
    s.expire_deadlines()
    assert s.ledger.wasted["deadline_expired"] == pa
    s.abort_request("b")
    assert s.ledger.wasted["severed"] == pb
    assert sched_balance(s)["balanced"]


# -- engine-level: serial ↔ pipelined equivalence + rollback -----------------


def _engine_cfg(**sched_kw):
    from dataclasses import replace

    cfg = EngineConfig.tiny()
    return cfg.replace(
        scheduler=replace(cfg.scheduler, decode_window=4, **sched_kw)
    )


def _flood(engine, rng_seed=3):
    import numpy as np

    from vllm_production_stack_tpu.qos import TenantContext

    rng = np.random.RandomState(rng_seed)
    vocab = engine.config.model.vocab_size
    rids = []
    for i in range(10):
        kind = i % 3
        sampling = SamplingParams(
            max_tokens=int(rng.randint(3, 12)), temperature=0.0,
            ignore_eos=True,
        )
        deadline = None
        tenant = None
        if kind == 1:
            # stop ids → mid-window cuts (overshoot) + pipeline rollbacks
            sampling = SamplingParams(
                max_tokens=16, temperature=0.0,
                stop_token_ids=tuple(
                    int(t) for t in rng.randint(1, vocab, size=48)
                ),
            )
        elif kind == 2:
            deadline = time.monotonic() + 0.03
            tenant = TenantContext(tenant_id="batch", priority=2, weight=1.0)
        prompt = [int(t) for t in rng.randint(1, vocab, size=8)]
        rids.append(engine.add_request(
            prompt_token_ids=prompt, sampling=sampling, deadline=deadline,
            tenant=tenant,
        ))
    steps = 0
    while engine.has_unfinished() and steps < 300:
        engine.step()
        steps += 1
        if steps == 2:
            engine.abort_request(rids[4])
    return rids


def test_engine_ledger_balances_serial_and_pipelined():
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.testing.serial_loop import serial_engine

    for async_on in (False, True):
        eng = None
        try:
            eng = (LLMEngine if async_on else serial_engine)(_engine_cfg())
            _flood(eng)
            bal = assert_balanced(eng)
            assert bal["pending"] == 0
            assert bal["delivered"] > 0
            assert bal["wasted"]["overshoot"] > 0
            if async_on:
                # the pipelined loop's finishes discard dispatched windows
                assert bal["wasted"]["rollback"] > 0
            assert bal["wasted"]["deadline_expired"] + bal["wasted"][
                "severed"
            ] > 0
        finally:
            if eng is not None:
                eng.runner.shutdown(wait=True)


def test_engine_rollback_tokens_match_timing_counter():
    from vllm_production_stack_tpu.engine.engine import LLMEngine

    eng = LLMEngine(_engine_cfg())
    try:
        # probe run discovers the greedy stream, then a stop token chosen
        # MID-window forces an unexpected finish while the next window is
        # already dispatched → speculation invalid → rollback, and the
        # discarded window's tokens must land in wasted{rollback}
        probe = eng.generate(
            [[7, 8, 9]],
            SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True),
        )[0]["token_ids"]
        stop_tok = next(t for t in probe[1:] if t != probe[0])
        before = eng.timing["rollback_n"]
        out = eng.generate(
            [[7, 8, 9]],
            SamplingParams(
                max_tokens=16, temperature=0.0,
                stop_token_ids=(stop_tok,),
            ),
        )[0]
        assert out["finish_reason"] == "stop"
        bal = assert_balanced(eng)
        assert eng.timing["rollback_n"] > before
        assert bal["wasted"]["rollback"] >= eng.timing["rollback_n"]
    finally:
        eng.runner.shutdown(wait=True)


# -- stats / exporter --------------------------------------------------------


def test_stats_saturation_snapshot_and_kv_tiers():
    from vllm_production_stack_tpu.engine.engine import LLMEngine

    eng = LLMEngine(_engine_cfg())
    try:
        eng.generate(
            [[5, 6, 7, 8]] * 2,
            SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True),
        )
        sat = eng.stats().saturation
        assert sat["steps"]["decode"] > 0
        assert sat["steps"]["prefill"] > 0
        assert 0.0 < sat["decode_seat_occupancy"] <= 1.0
        assert 0.0 <= sat["padding_waste_frac"] < 1.0
        assert sat["model_flops_total"] > 0
        assert set(sat["kv_tiers"]) == {"hbm", "host", "disk", "remote"}
        good = sat["goodput"]
        assert good["delivered"] == 2 * 8
        occ = sat["occupancy_hist"]
        assert occ["count"] == sat["steps"]["decode"]
    finally:
        eng.runner.shutdown(wait=True)


def test_exporter_renders_saturation_series_with_bounded_cardinality():
    from vllm_production_stack_tpu import metrics_contract as mc
    from vllm_production_stack_tpu.engine.engine import EngineStatsSnapshot
    from vllm_production_stack_tpu.engine.metrics import EngineMetrics

    sat = {
        "decode_seat_occupancy": 0.5,
        "padding_waste_frac": 0.25,
        "achieved_flops_per_s": 1e9,
        "mfu": 0.1,
        "step_tokens": {"prefill": 100, "decode": 200},
        "padded_tokens": {"prefill": 160, "decode": 256},
        "model_flops_total": 5e9,
        "goodput": {
            "delivered": 150,
            "wasted": {r: 1 for r in WASTE_REASONS},
            "sampled": 156,
            "wasted_total": 6,
        },
        "kv_tiers": {"hbm": 0.5, "host": 0.1, "disk": 0.0, "remote": 0.2},
        "occupancy_hist": {"buckets": (0.5, 1.0), "counts": [1, 2, 0],
                           "sum": 1.7, "count": 3},
        "step_wall_hist": {
            "decode": {"buckets": (0.01, 0.1), "counts": [2, 1, 0],
                       "sum": 0.05, "count": 3},
        },
    }
    m = EngineMetrics("tiny")
    text = m.render(EngineStatsSnapshot(saturation=sat)).decode()
    assert 'tpu:engine_decode_seat_occupancy{model_name="tiny"} 0.5' in text
    assert 'tpu:goodput_tokens_total{model_name="tiny"} 150.0' in text
    # reason label cardinality == the closed WASTE_REASONS set, exactly
    reasons = set(re.findall(r'tpu:wasted_tokens_total{[^}]*reason="([a-z_]+)"', text))
    assert reasons == set(WASTE_REASONS)
    phases = set(re.findall(r'tpu:engine_step_tokens_total{[^}]*phase="([a-z]+)"', text))
    assert phases == {"prefill", "decode"}
    tiers = set(re.findall(r'tpu:engine_kv_tier_usage_perc{[^}]*tier="([a-z]+)"', text))
    assert tiers == {"hbm", "host", "disk", "remote"}
    # histogram families render with cumulative buckets + _count/_sum
    assert 'tpu:engine_step_occupancy_bucket{le="+Inf",model_name="tiny"} 3.0' in text
    assert 'tpu:engine_step_occupancy_count{model_name="tiny"} 3.0' in text
    assert (
        'tpu:engine_step_wall_seconds_bucket{le="+Inf",model_name="tiny",phase="decode"} 3.0'
        in text
    )
    # counters are delta-bumped: a second render with the same snapshot
    # must not double-count
    text2 = m.render(EngineStatsSnapshot(saturation=sat)).decode()
    assert 'tpu:goodput_tokens_total{model_name="tiny"} 150.0' in text2


def test_exporter_openmetrics_renders_saturation_histograms():
    from vllm_production_stack_tpu.engine.engine import EngineStatsSnapshot
    from vllm_production_stack_tpu.engine.metrics import EngineMetrics

    m = EngineMetrics("tiny")
    text = m.render(EngineStatsSnapshot(), openmetrics=True).decode()
    # OpenMetrics forbids colons: prometheus_client rewrites the sample
    # names tpu:→tpu_ under this exposition (the scrape contract keeps the
    # colon names — ?format=openmetrics is opt-in, see wants_openmetrics)
    assert "tpu_engine_step_occupancy_bucket" in text
    assert "tpu_engine_step_wall_seconds_bucket" in text


def test_router_exports_severed_streams_counter():
    from vllm_production_stack_tpu import metrics_contract as mc
    from vllm_production_stack_tpu.router.metrics import RouterMetrics

    rm = RouterMetrics()
    rm.severed_streams.inc()
    from prometheus_client import generate_latest

    text = generate_latest(rm.registry).decode()
    assert mc.ROUTER_SEVERED_STREAMS + " 1.0" in text


# -- SLO rule pack lint (no promtool) ----------------------------------------


def _load_rule_pack():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import check_metrics_contract as cmc
    finally:
        sys.path.pop(0)
    return cmc


def _promql_shape_ok(expr: str) -> bool:
    """Minimal PromQL sanity without promtool: non-empty, balanced
    delimiters, no stray quotes, and at least one metric selector or
    recorded-series token."""
    if not expr.strip():
        return False
    pairs = {"(": ")", "[": "]", "{": "}"}
    stack: list[str] = []
    in_str = False
    for ch in expr:
        if ch == '"':
            in_str = not in_str
        if in_str:
            continue
        if ch in pairs:
            stack.append(pairs[ch])
        elif ch in pairs.values():
            if not stack or stack.pop() != ch:
                return False
    if stack or in_str:
        return False
    return bool(re.search(r"[a-zA-Z_:][a-zA-Z0-9_:]*", expr))


def test_rule_pack_lints_without_promtool():
    import yaml

    cmc = _load_rule_pack()
    files = cmc.rule_files()
    assert files, "observability/rules/ must ship at least one rule file"
    for path in files:
        with open(path, encoding="utf-8") as f:
            doc = yaml.safe_load(f)
        assert isinstance(doc, dict) and doc.get("groups"), path
        for group in doc["groups"]:
            assert group.get("name"), f"{path}: group without name"
            for rule in group.get("rules") or []:
                label = rule.get("record") or rule.get("alert")
                assert label, f"{path}: rule with neither record nor alert"
                assert ("record" in rule) != ("alert" in rule), label
                expr = str(rule.get("expr", ""))
                assert _promql_shape_ok(expr), f"{label}: bad expr {expr!r}"
                if "alert" in rule:
                    # alert hygiene: a debounce window, a severity to
                    # route on, and human-readable annotations
                    assert rule.get("for"), f"{label}: alert missing for:"
                    labels = rule.get("labels") or {}
                    assert labels.get("severity"), f"{label}: no severity"
                    ann = rule.get("annotations") or {}
                    assert ann.get("summary"), f"{label}: no summary"


def test_rule_pack_series_all_in_contract():
    cmc = _load_rule_pack()
    problems = cmc.check_rules()
    assert not problems, problems


def test_contract_checker_rejects_unknown_series(tmp_path, monkeypatch):
    cmc = _load_rule_pack()
    bad = tmp_path / "bad.yaml"
    bad.write_text(
        "groups:\n"
        "  - name: g\n"
        "    rules:\n"
        "      - record: tpu:thing:rate5m\n"
        "        expr: sum(rate(tpu:does_not_exist_total[5m]))\n"
    )
    monkeypatch.setattr(cmc, "RULES_DIR", str(tmp_path))
    problems = cmc.check_rules()
    assert any("tpu:does_not_exist_total" in p for p in problems)
