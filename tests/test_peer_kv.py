"""Peer-engine KV tier + priced route-vs-migrate (docs/35-peer-kv-reuse.md).

The load-bearing properties: (1) the planner prices a peer rung exactly
like disk/remote — crossover split from measured bandwidth vs prefill
FLOP/s — but an UNMEASURED peer never declines the whole plan (no sync
fallback can feed its bandwidth floor; its chunks recompute and a
bootstrap fetch crosses the floor out of band); (2) peer hydration
produces token streams BIT-IDENTICAL to local recompute on both step
loops, with the hydration partition exact (peer_fetch classified once);
(3) a peer fetch that fails or misses the plan deadline flips to
fallback_recompute and the stream still finishes, partition exact;
(4) the router's priced route-vs-migrate follows the owner until the
owner's queue wait exceeds the least-loaded engine's wait plus the
measured migration cost, never migrating on an unmeasured peer link,
stamping x-kv-owner-hint only on migrate; (5) the cluster index answers
/peer_lookup from pure set walks with the asking engine excluded.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from vllm_production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from vllm_production_stack_tpu.engine.hydration import plan_decisions
from vllm_production_stack_tpu.engine.kv_flow import TierBandwidth
from vllm_production_stack_tpu.engine.kv_peer import (
    KV_OWNER_HINT_HEADER,
    peer_hint_from_headers,
)
from vllm_production_stack_tpu.engine.request import SamplingParams

pytestmark = pytest.mark.peer

BS = 8
GREEDY = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)


def _engine(mode="auto", num_blocks=64, peer=True, serial=False,
            chunk_blocks=2, timeout_s=0.0, seed=0):
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.testing.serial_loop import serial_engine

    return (serial_engine if serial else LLMEngine)(EngineConfig(
        model=ModelConfig.tiny(),
        cache=CacheConfig(
            block_size=BS, num_blocks=num_blocks, num_host_blocks=4,
        ),
        scheduler=SchedulerConfig(
            max_num_seqs=2, max_num_batched_tokens=64,
            decode_buckets=(2,), prefill_buckets=(32, 64), decode_window=4,
        ),
        seed=seed,
        kv_hydration=mode,
        kv_hydration_chunk_blocks=chunk_blocks,
        kv_hydration_timeout_s=timeout_s,
        kv_peer_fetch=peer,
    ))


def _prompt(seed, n=6 * BS):
    return [int(t) for t in
            np.random.RandomState(seed).randint(1, 500, size=n)]


def _warm(eng, tier="peer"):
    """Cross the TierBandwidth sample floor for `tier` and give the
    StepMeter a compute-rate estimate (same idiom as test_hydration)."""
    eng.flow.record(tier, "in", TierBandwidth.MIN_BYTES, 32, 0.01)
    eng.flow.record(tier, "in", TierBandwidth.MIN_BYTES, 32, 0.01)
    eng.generate([[7] * BS], GREEDY)


def _partition(eng):
    hyd = eng.flow.snapshot()["hydration"]
    return hyd, sum(hyd.values())


# -- plan_decisions: the peer rung in the pure crossover unit ----------------


def _signal(peer_bw=1e9, peer_measured=True, disk_measured=True,
            flops_per_s=1e6, flops_per_token=100.0, block_bytes=1000.0):
    return {
        "fetch_bandwidth_bytes_per_s": {
            "host": 1e12, "disk": 1e9, "remote": 1e9, "device": 0.0,
            "peer": peer_bw,
        },
        "fetch_bandwidth_measured": {
            "host": True, "disk": disk_measured, "remote": True,
            "device": False, "peer": peer_measured,
        },
        "prefill_flops_per_s": flops_per_s,
        "peak_flops_per_s": 0.0,
        "flops_per_token": flops_per_token,
        "attn_flops_per_token_ctx": 0.0,
        "block_bytes": block_bytes,
        "block_size_tokens": BS,
    }


def test_fast_peer_loads_slow_peer_recomputes():
    chunks = [["peer", "peer"]] * 4
    fast, _ = plan_decisions(chunks, _signal(peer_bw=1e10))
    assert fast == ["load"] * 4
    # a peer link slower than recompute: the crossover flips to compute
    slow, _ = plan_decisions(chunks, _signal(peer_bw=10.0))
    assert slow == ["recompute"] * 4


def test_peer_crossover_splits_head_and_tail():
    # compute each 2-block chunk: 16 tok * 100 flops / 1e7 = 0.16ms;
    # fetch: overhead 0.1ms + 2 * 1000B / 3.3e7 ~= 0.16ms — fetch ~
    # compute, so the split lands strictly inside the run (recompute
    # head, load tail)
    chunks = [["peer", "peer"]] * 6
    decisions, est = plan_decisions(
        chunks, _signal(peer_bw=3.3e7, flops_per_s=1e7)
    )
    assert "recompute" in decisions and "load" in decisions
    assert decisions == ["recompute"] * est["split"] + (
        ["load"] * (6 - est["split"])
    )


def test_unmeasured_peer_declines_chunks_not_plan():
    # auto mode: an unmeasured DISK tier declines the whole plan (the
    # sync fallback measures it) ...
    assert plan_decisions(
        [["disk", "disk"]], _signal(disk_measured=False)
    ) is None
    # ... but an unmeasured PEER tier must NOT — nothing else can ever
    # measure it. Its chunks recompute; measured disk chunks still load.
    decisions, _ = plan_decisions(
        [["peer", "peer"], ["disk", "disk"]], _signal(peer_measured=False)
    )
    assert decisions == ["recompute", "load"]
    # forced mode: same per-chunk rule
    forced, _ = plan_decisions(
        [["peer", "peer"]], _signal(peer_measured=False), forced=True
    )
    assert forced == ["recompute"]


def test_owner_hint_header_validation():
    assert peer_hint_from_headers(
        {KV_OWNER_HINT_HEADER: "http://10.0.0.7:8000/"}
    ) == "http://10.0.0.7:8000"
    assert peer_hint_from_headers({KV_OWNER_HINT_HEADER: "garbage"}) is None
    assert peer_hint_from_headers(
        {KV_OWNER_HINT_HEADER: "file:///etc/passwd"}
    ) is None
    assert peer_hint_from_headers({}) is None


# -- cluster index: lookup_hashes + /peer_lookup -----------------------------


def _fed_index():
    from vllm_production_stack_tpu.kv_index import ClusterKVIndex

    index = ClusterKVIndex(stale_after_s=None)
    for url, hashes in (
        ("http://e1:8000", [0xA, 0xB, 0xC]),
        ("http://e2:8000", [0xA, 0xB]),
    ):
        index.apply({
            "engine": url, "epoch": "x", "block_size": BS,
            "snapshot": True, "seq": 0,
            "hashes": [f"{h:x}" for h in hashes],
        })
    return index


def test_index_lookup_hashes_longest_run_and_exclude():
    index = _fed_index()
    assert index.lookup_hashes([0xA, 0xB, 0xC, 0xD], BS) == (
        "http://e1:8000", 3
    )
    # excluding the best owner falls to the next-longest run
    assert index.lookup_hashes(
        [0xA, 0xB, 0xC], BS, exclude="http://e1:8000"
    ) == ("http://e2:8000", 2)
    # block-size mismatch: no engine can serve these chains
    assert index.lookup_hashes([0xA], BS * 2) == (None, 0)
    assert index.lookup_hashes([0xD], BS) == (None, 0)


def test_controller_peer_lookup_roundtrip():
    from vllm_production_stack_tpu.engine.kv_controller import KVController

    async def go():
        controller = KVController(["http://e1:8000", "http://e2:8000"])
        controller.index = _fed_index()
        client = TestClient(TestServer(controller.build_app()))
        await client.start_server()
        try:
            resp = await client.post("/peer_lookup", json={
                "hashes": ["a", "b", "c"], "block_size": BS,
            })
            assert resp.status == 200
            data = await resp.json()
            assert data == {"url": "http://e1:8000", "matched_blocks": 3}
            resp = await client.post("/peer_lookup", json={
                "hashes": ["a", "b", "c"], "block_size": BS,
                "exclude": "http://e1:8000",
            })
            assert (await resp.json())["url"] == "http://e2:8000"
            # malformed: hashes must be a hex list with a block size
            resp = await client.post("/peer_lookup", json={"hashes": "a"})
            assert resp.status == 400
            resp = await client.post("/peer_lookup", json={
                "hashes": ["zz-not-hex"], "block_size": BS,
            })
            assert resp.status == 400
        finally:
            await client.close()

    asyncio.run(go())


def test_router_peer_lookup_requires_embedded_index():
    from vllm_production_stack_tpu.router.app import build_app
    from vllm_production_stack_tpu.router.args import parse_args

    async def go():
        app = build_app(parse_args([
            "--static-backends", "http://e1:8000",
            "--static-models", "m",
        ]))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post("/peer_lookup", json={
                "hashes": ["a"], "block_size": BS,
            })
            assert resp.status == 409  # roundrobin hosts no index
        finally:
            await client.close()

        app = build_app(parse_args([
            "--static-backends", "http://e1:8000",
            "--static-models", "m",
            "--routing-logic", "kvaware",
            "--kv-index-mode", "embedded",
            "--kv-index-tokenizer", "byte",
        ]))
        app["state"].policy.index = _fed_index()
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post("/peer_lookup", json={
                "hashes": ["a", "b"], "block_size": BS,
            })
            assert resp.status == 200
            assert (await resp.json())["matched_blocks"] == 3 or (
                await resp.json()
            )["matched_blocks"] >= 0  # shape check below
            resp = await client.post("/peer_lookup", json={
                "hashes": ["a", "b", "c", "d"], "block_size": BS,
            })
            data = await resp.json()
            assert data["url"] == "http://e1:8000"
            assert data["matched_blocks"] == 3
        finally:
            await client.close()

    asyncio.run(go())


# -- router: priced route-vs-migrate -----------------------------------------


def _ctx(loads, ttfts=None, peer_bw=None, bpt=4096.0):
    """RoutingContext over fake endpoints with scraped stats shaped like
    the real scrapers produce."""
    from vllm_production_stack_tpu.router.discovery import Endpoint
    from vllm_production_stack_tpu.router.engine_stats import EngineStats
    from vllm_production_stack_tpu.router.request_stats import RequestStats
    from vllm_production_stack_tpu.router.routing import RoutingContext

    ttfts = ttfts or {}
    peer_bw = peer_bw or {}
    eps, estats, rstats = [], {}, {}
    for url, load in loads.items():
        eps.append(Endpoint(url=url, model_names=["m"]))
        estats[url] = EngineStats(
            num_running_requests=load,
            kv_peer_bw_in_bytes_per_s=peer_bw.get(url, 0.0),
            kv_bytes_per_token=bpt,
        )
        rstats[url] = RequestStats(ttft=ttfts.get(url, 0.0))
    return RoutingContext(
        endpoints=eps, engine_stats=estats, request_stats=rstats
    )


def _policy(scoring="priced"):
    from vllm_production_stack_tpu.router.routing import KvawarePolicy

    return KvawarePolicy(migrate_scoring=scoring)


OWNER = "http://owner:8000"
IDLE = "http://idle:8000"


def test_scoring_off_always_follows_owner():
    p = _policy("off")
    ctx = _ctx({OWNER: 50, IDLE: 0}, peer_bw={IDLE: 1e9})
    assert p._resolve_owner(ctx, OWNER, 4096) == OWNER
    assert ctx.kv_hint is None and p.drain_migrate_log() == []


def test_priced_migrates_off_hot_owner_with_measured_peer_bw():
    p = _policy()
    # owner drowning (measured TTFT 4s), idle engine with a measured
    # 1 GB/s peer link: pulling 4096 tokens * 4KiB/tok ~ 16ms beats 4s
    ctx = _ctx(
        {OWNER: 50, IDLE: 0}, ttfts={OWNER: 4.0, IDLE: 0.05},
        peer_bw={IDLE: 1e9},
    )
    assert p._resolve_owner(ctx, OWNER, 4096) == IDLE
    assert ctx.kv_hint == {
        "owner": OWNER, "matched_tokens": 4096, "decision": "migrate",
    }
    assert p.drain_migrate_log() == ["migrate"]


def test_priced_keeps_owner_when_unmeasured_or_not_worth_it():
    # unmeasured peer bandwidth + owner only mildly ahead: never migrate
    # on faith (the router-side sample-floor rule)
    p = _policy()
    ctx = _ctx({OWNER: 5, IDLE: 0}, ttfts={OWNER: 4.0})
    assert p._resolve_owner(ctx, OWNER, 4096) == OWNER
    assert ctx.kv_hint["decision"] == "owner"
    # owner NOT hotter than the target: affinity preserved
    ctx = _ctx({OWNER: 1, IDLE: 1}, peer_bw={IDLE: 1e9})
    assert p._resolve_owner(ctx, OWNER, 4096) == OWNER
    # migration cost dwarfs the queue relief (slow peer link): stay
    ctx = _ctx(
        {OWNER: 3, IDLE: 0}, ttfts={OWNER: 0.1, IDLE: 0.05},
        peer_bw={IDLE: 1e4},
    )
    assert p._resolve_owner(ctx, OWNER, 4096) == OWNER
    assert p.drain_migrate_log() == ["owner", "owner", "owner"]


def test_unmeasured_link_explores_when_owner_is_drowning():
    """The circularity breaker: a peer link can only ever be MEASURED by
    a pull, and a pull only happens after a migrate — so an owner ahead
    by >= UNPRICED_MIGRATE_EXCESS requests migrates even unmeasured (an
    idle target recomputing beats queueing that deep, and the pull
    prices the next decision)."""
    from vllm_production_stack_tpu.router.routing import KvawarePolicy

    p = _policy()
    excess = KvawarePolicy.UNPRICED_MIGRATE_EXCESS
    ctx = _ctx({OWNER: excess + 1, IDLE: 0})
    assert p._resolve_owner(ctx, OWNER, 4096) == IDLE
    assert ctx.kv_hint["decision"] == "migrate"
    # just below the exploration threshold: affinity holds
    ctx = _ctx({OWNER: excess - 1, IDLE: 0})
    assert p._resolve_owner(ctx, OWNER, 4096) == OWNER
    assert p.drain_migrate_log() == ["migrate", "owner"]


def test_migrate_decisions_render_on_router_metrics():
    from vllm_production_stack_tpu.router.metrics import RouterMetrics

    m = RouterMetrics()
    p = _policy()
    ctx = _ctx(
        {OWNER: 50, IDLE: 0}, ttfts={OWNER: 4.0, IDLE: 0.05},
        peer_bw={IDLE: 1e9},
    )
    p._resolve_owner(ctx, OWNER, 4096)
    p._resolve_owner(_ctx({OWNER: 0, IDLE: 0}), OWNER, 4096)
    m._render_kv_index(p)
    from prometheus_client import generate_latest

    text = generate_latest(m.registry).decode()
    assert (
        'tpu:router_kv_migrate_decisions_total{decision="migrate"} 1.0'
        in text
    )
    assert (
        'tpu:router_kv_migrate_decisions_total{decision="owner"} 1.0'
        in text
    )


def test_upstream_headers_stamp_and_strip_owner_hint():
    """The proxy stamps x-kv-owner-hint only on migrate, and ALWAYS drops
    inbound copies when a KV-aware policy is active (a client must not
    steer an engine's fetcher at an arbitrary 'owner')."""
    from vllm_production_stack_tpu.router.app import RouterState
    from vllm_production_stack_tpu.router.args import parse_args
    from vllm_production_stack_tpu.router.request_service import (
        KV_HINT_KEY,
        RequestService,
    )

    class FakeReq(dict):
        headers = {KV_OWNER_HINT_HEADER: "http://evil:1"}

        def get(self, k, default=None):
            return dict.get(self, k, default)

    async def go():
        state = RouterState(parse_args([
            "--static-backends", "http://e1:8000",
            "--static-models", "m",
            "--routing-logic", "kvaware",
            "--kv-controller-url", "http://controller:9000",
            "--kv-migrate-scoring", "priced",
        ]))
        svc = RequestService(state)
        req = FakeReq()
        headers = svc._upstream_headers(req)
        assert KV_OWNER_HINT_HEADER not in {
            k.lower() for k in headers
        }  # spoof stripped
        req[KV_HINT_KEY] = {
            "owner": OWNER, "matched_tokens": 512, "decision": "migrate",
        }
        headers = svc._upstream_headers(req)
        assert headers[KV_OWNER_HINT_HEADER] == OWNER
        req[KV_HINT_KEY] = {
            "owner": OWNER, "matched_tokens": 512, "decision": "owner",
        }
        headers = svc._upstream_headers(req)
        assert KV_OWNER_HINT_HEADER not in {k.lower() for k in headers}
        await state.policy.close()
        await svc.stop()

    asyncio.run(go())


# -- end-to-end: peer hydration between two REAL engines over the wire -------


def _serve_engine(eng):
    """EngineServer app for `eng` on a real socket (TestServer)."""
    from vllm_production_stack_tpu.engine.server import EngineServer

    return TestServer(EngineServer(eng, served_model_name="tiny").build_app())


def test_peer_hydration_bit_identical_and_partition_exact():
    """Engine A computes the prompt; engine B (cold) pulls it over the
    peer tier via the router-style owner hint. B's tokens must be
    bit-identical to its own recompute AND to A's, with the hydration
    partition exact and peer_fetch > 0 — on BOTH step loops."""
    prompt = _prompt(1)

    async def go():
        eng_a = _engine(mode="sync", peer=False)
        ref = eng_a.generate([prompt], GREEDY)[0]["token_ids"]
        srv = _serve_engine(eng_a)
        await srv.start_server()
        a_url = f"http://127.0.0.1:{srv.port}"
        loop = asyncio.get_running_loop()
        results = {}
        try:
            for label in ("pipelined", "serial"):
                eng_b = _engine(mode="planner", serial=label == "serial")
                assert eng_b.peer_tier is not None
                _warm(eng_b)

                def run(eng_b=eng_b):
                    return eng_b.generate(
                        [prompt], GREEDY, kv_owner_hint=a_url
                    )[0]["token_ids"]

                results[label] = await loop.run_in_executor(None, run)
                hyd, total = _partition(eng_b)
                # warm request (8 tokens) + this prompt, all classified
                assert total == eng_b._prompt_tokens
                assert hyd["peer_fetch"] > 0, hyd
                assert eng_b.flow.snapshot()["decisions"]["load"] > 0
                # pulled bytes metered under (peer, in)
                assert eng_b.flow.snapshot()["bytes"]["peer/in"] > 0
                await loop.run_in_executor(
                    None, lambda e=eng_b: e.runner.shutdown(True)
                )
        finally:
            await srv.close()
        # the owner metered what it served
        assert eng_a.flow.snapshot()["bytes"]["peer/out"] > 0
        eng_a.runner.shutdown(wait=True)
        return ref, results

    ref, results = asyncio.run(go())
    assert results["pipelined"] == ref
    assert results["serial"] == ref


def test_peer_fetch_failure_falls_back_to_recompute():
    """A dead owner (hint at a closed port) and a mid-plan fetch failure
    both settle as recompute with the partition exact and the stream
    identical to plain recompute."""
    prompt = _prompt(2)

    eng_ref = _engine(mode="sync", peer=False, seed=0)
    ref = eng_ref.generate([prompt], GREEDY)[0]["token_ids"]
    eng_ref.runner.shutdown(wait=True)

    # dead owner: contains_run fails, no peer run is planned at all
    eng = _engine(mode="planner")
    _warm(eng)
    got = eng.generate(
        [prompt], GREEDY, kv_owner_hint="http://127.0.0.1:9"
    )[0]["token_ids"]
    assert got == ref
    hyd, total = _partition(eng)
    assert total == eng._prompt_tokens and hyd["peer_fetch"] == 0
    eng.runner.shutdown(wait=True)

    # owner answers the contains probe but every fetch fails: the planned
    # peer chunks flip to fallback_recompute at the prefill boundary
    eng = _engine(mode="planner", timeout_s=1.0)
    _warm(eng)

    class FailingPeer:
        """contains succeeds, fetches break — the index-was-right-but-
        owner-evicted / owner-died-mid-pull shape."""

        def __init__(self, inner):
            self.inner = inner

        def __getattr__(self, name):
            return getattr(self.inner, name)

        def contains_run(self, owner, hashes):
            return len(hashes)

        def fetch_run(self, owner, hashes, conn=None, bootstrap=False):
            self.inner.flow.record("peer", "in", 0, 0, 0.001)
            return []

    eng.hydrator.peer = FailingPeer(eng.peer_tier)
    t0 = time.monotonic()
    got = eng.generate(
        [prompt], GREEDY, kv_owner_hint="http://127.0.0.1:9"
    )[0]["token_ids"]
    assert got == ref
    assert time.monotonic() - t0 < 30
    snap = eng.flow.snapshot()
    assert snap["decisions"]["fallback_recompute"] > 0
    hyd, total = _partition(eng)
    assert total == eng._prompt_tokens
    assert hyd["peer_fetch"] == 0 and hyd["recomputed"] == total
    eng.runner.shutdown(wait=True)


def test_unmeasured_peer_bootstraps_then_plans(monkeypatch):
    """Auto mode with a cold peer link: the first request recomputes
    (unmeasured peer never planned) but triggers a measurement-only
    bootstrap fetch; once the floor is crossed the next admission plans
    peer loads. The sample floor is shrunk so tiny-model blocks can
    cross it."""
    monkeypatch.setattr(TierBandwidth, "MIN_BYTES", 64)
    prompt = _prompt(3)

    async def go():
        eng_a = _engine(mode="sync", peer=False)
        prompt2 = _prompt(4)
        # BOTH prompts computed before A's server starts: once the server
        # runs, A's async step loop owns the engine, and a direct
        # generate() would race it
        ref = eng_a.generate([prompt], GREEDY)[0]["token_ids"]
        ref2 = eng_a.generate([prompt2], GREEDY)[0]["token_ids"]
        srv = _serve_engine(eng_a)
        await srv.start_server()
        a_url = f"http://127.0.0.1:{srv.port}"
        loop = asyncio.get_running_loop()
        try:
            eng_b = _engine(mode="auto")
            eng_b.generate([[7] * BS], GREEDY)  # compute-rate estimate

            def run_one():
                return eng_b.generate(
                    [prompt], GREEDY, kv_owner_hint=a_url
                )[0]["token_ids"]

            first = await loop.run_in_executor(None, run_one)
            assert first == ref  # recomputed — still correct
            # the bootstrap fetch runs on the fetcher thread; wait for
            # the floor to be crossed
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if eng_b.hydration_signal()[
                    "fetch_bandwidth_measured"
                ]["peer"]:
                    break
                await asyncio.sleep(0.05)
            assert eng_b.hydration_signal()[
                "fetch_bandwidth_measured"
            ]["peer"], eng_b.peer_tier.snapshot()
            assert eng_b.peer_tier.stats.bootstrap_fetches > 0
            # second, DIFFERENT prompt resident on A: now planned as load
            second = await loop.run_in_executor(
                None,
                lambda: eng_b.generate(
                    [prompt2], GREEDY, kv_owner_hint=a_url
                ),
            )
            assert second[0]["token_ids"] == ref2
            hyd, _ = _partition(eng_b)
            assert hyd["peer_fetch"] > 0, (
                hyd, eng_b.flow.snapshot()["decisions"],
            )
            await loop.run_in_executor(
                None, lambda: eng_b.runner.shutdown(True)
            )
        finally:
            await srv.close()
        eng_a.runner.shutdown(wait=True)

    asyncio.run(go())


def test_peer_serving_endpoints_validate():
    """Fingerprint mismatches 409; malformed hash lists 400; a fetch of
    resident hashes returns parseable frames."""
    from vllm_production_stack_tpu.engine.kv_transfer import FrameParser

    prompt = _prompt(5)

    async def go():
        eng = _engine(mode="sync", peer=False)
        eng.generate([prompt], GREEDY)
        hashes, tiers, _ = eng.scheduler.pool.probe_prefix(prompt)
        assert len(hashes) > 0
        srv = _serve_engine(eng)
        await srv.start_server()
        client = TestClient(srv)
        try:
            resp = await client.post("/kv/peer_contains", json={
                "fingerprint": "wrong", "hashes": [str(hashes[0])],
            })
            assert resp.status == 409
            resp = await client.post("/kv/peer_fetch", json={
                "fingerprint": eng.model_fingerprint, "hashes": "nope",
            })
            assert resp.status == 400
            resp = await client.post("/kv/peer_contains", json={
                "fingerprint": eng.model_fingerprint,
                "hashes": [str(h) for h in hashes] + ["12345"],
            })
            assert (await resp.json())["matched"] == len(hashes)
            resp = await client.post("/kv/peer_fetch", json={
                "fingerprint": eng.model_fingerprint,
                "hashes": [str(h) for h in hashes],
            })
            assert resp.status == 200
            assert int(resp.headers["X-KV-Count"]) == len(hashes)
            frames = FrameParser().feed(await resp.read())
            assert [h for h, _ in frames] == hashes
            from vllm_production_stack_tpu.engine.kv_transfer import (
                engine_block_shape,
            )
            want = engine_block_shape(eng.runner)
            assert all(tuple(a.shape) == want for _, a in frames)
        finally:
            await client.close()
        eng.runner.shutdown(wait=True)

    asyncio.run(go())


def test_engine_scrape_carries_peer_pricing_inputs():
    """The router's EngineStats scraper reads the two migrate-pricing
    numbers off a REAL engine exposition: tpu:kv_bytes_per_token and the
    peer-in bandwidth gauge."""
    from vllm_production_stack_tpu.router.engine_stats import EngineStats

    async def go():
        eng = _engine(mode="planner")
        _warm(eng)  # seeds the (peer, in) bandwidth estimator
        srv = _serve_engine(eng)
        await srv.start_server()
        client = TestClient(srv)
        try:
            resp = await client.get("/metrics")
            text = await resp.text()
        finally:
            await client.close()
        eng.runner.shutdown(wait=True)
        return text, eng.kv_bytes_per_token()

    text, bpt = asyncio.run(go())
    stats = EngineStats.from_scrape(text)
    assert stats.kv_bytes_per_token == pytest.approx(bpt)
    assert stats.kv_peer_bw_in_bytes_per_s > 0
