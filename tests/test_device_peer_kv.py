"""Device-path peer KV (docs/39-device-peer-kv.md): transport
negotiation, the /peer_lookup hint on both lookup services, device-tier
pricing, migration-aware eviction, the Hydrator's device fetch lane
(fake collective — the real 2-process pull lives in the
test_distributed dryrun), its degradation contract, and the
controller's flash-crowd push replication."""

import asyncio
import threading
import time

import numpy as np
import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from vllm_production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from vllm_production_stack_tpu.engine.hydration import plan_decisions
from vllm_production_stack_tpu.engine.kv_flow import TierBandwidth
from vllm_production_stack_tpu.engine.request import SamplingParams
from vllm_production_stack_tpu.kv_index import (
    ClusterKVIndex,
    negotiate_transport,
)

pytestmark = pytest.mark.peer

BS = 8
GREEDY = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)

IDENT_A = {"mesh_group": "pool-a", "process_index": 0, "process_count": 2}
IDENT_B = {"mesh_group": "pool-a", "process_index": 1, "process_count": 2}


def _engine(mode="auto", num_blocks=64, peer=True,
            chunk_blocks=2, timeout_s=0.0, seed=0, transport="http",
            codec="none"):
    from vllm_production_stack_tpu.engine.engine import LLMEngine

    return LLMEngine(EngineConfig(
        model=ModelConfig.tiny(),
        cache=CacheConfig(
            block_size=BS, num_blocks=num_blocks, num_host_blocks=4,
            kv_at_rest_codec=codec,
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
        kv_peer_transport=transport,
    ))


def _prompt(seed, n=6 * BS):
    return [int(t) for t in
            np.random.RandomState(seed).randint(1, 500, size=n)]


def _warm(eng, tier="peer"):
    eng.flow.record(tier, "in", TierBandwidth.MIN_BYTES, 32, 0.01)
    eng.flow.record(tier, "in", TierBandwidth.MIN_BYTES, 32, 0.01)
    eng.generate([[7] * BS], GREEDY)


def _seed_device_bw(eng, bytes_per_s=1e9):
    """Cross the device tier's sample floor directly on the estimator —
    the byte counters stay untouched, so device/in deltas in asserts
    measure only what the fetch lane actually moved."""
    now = time.perf_counter()
    est = eng.flow.bandwidth[("device", "in")]
    est.record(TierBandwidth.MIN_BYTES, TierBandwidth.MIN_BYTES / bytes_per_s,
               now)
    est.record(TierBandwidth.MIN_BYTES, TierBandwidth.MIN_BYTES / bytes_per_s,
               now + 1e-3)


def _partition(eng):
    hyd = eng.flow.snapshot()["hydration"]
    return hyd, sum(hyd.values())


def _serve_engine(eng):
    from vllm_production_stack_tpu.engine.server import EngineServer

    return TestServer(EngineServer(eng, served_model_name="tiny").build_app())


# -- transport negotiation ---------------------------------------------------


def test_negotiate_transport():
    assert negotiate_transport(IDENT_A, IDENT_B) == "device"
    assert negotiate_transport(IDENT_B, IDENT_A) == "device"
    # either side silent -> HTTP
    assert negotiate_transport(None, IDENT_B) == "http"
    assert negotiate_transport(IDENT_A, None) == "http"
    assert negotiate_transport(None, None) == "http"
    # group mismatch / empty group
    assert negotiate_transport(
        IDENT_A, dict(IDENT_B, mesh_group="pool-b")
    ) == "http"
    assert negotiate_transport(
        dict(IDENT_A, mesh_group=""), dict(IDENT_B, mesh_group="")
    ) == "http"
    # only the exactly-supported 2-process pairwise shape qualifies
    assert negotiate_transport(
        dict(IDENT_A, process_count=4), dict(IDENT_B, process_count=4)
    ) == "http"
    # the same process twice is not a pair
    assert negotiate_transport(IDENT_A, IDENT_A) == "http"


def test_index_transport_side_map_and_holders():
    index = ClusterKVIndex(stale_after_s=None)
    index.set_transport("http://e1:8000/", IDENT_A)
    assert index.get_transport("http://e1:8000") == IDENT_A
    # falsy clears (engine restarted without a mesh)
    index.set_transport("http://e1:8000", None)
    assert index.get_transport("http://e1:8000") is None
    # deregister drops the identity along with the slice
    index.set_transport("http://e1:8000", IDENT_A)
    index.remove_engine("http://e1:8000")
    assert index.get_transport("http://e1:8000") is None

    for url, hashes in (
        ("http://e1:8000", [0xA, 0xB, 0xC]),
        ("http://e2:8000", [0xA, 0xB]),
    ):
        index.apply({
            "engine": url, "epoch": "x", "block_size": BS,
            "snapshot": True, "seq": 0,
            "hashes": [f"{h:x}" for h in hashes],
        })
    assert index.holders([0xA, 0xB], BS) == [
        "http://e1:8000", "http://e2:8000"
    ]
    assert index.holders([0xA, 0xB, 0xC], BS) == ["http://e1:8000"]
    assert index.holders([0xA], BS * 2) == []
    assert index.holders([], BS) == []


def _fed_index():
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


def test_controller_peer_lookup_transport_hint():
    from vllm_production_stack_tpu.engine.kv_controller import KVController

    async def go():
        controller = KVController(["http://e1:8000", "http://e2:8000"])
        controller.index = _fed_index()
        controller.index.set_transport("http://e1:8000", IDENT_A)
        client = TestClient(TestServer(controller.build_app()))
        await client.start_server()
        try:
            # requester pairs with the owner's mesh -> hint rides the reply
            resp = await client.post("/peer_lookup", json={
                "hashes": ["a", "b", "c"], "block_size": BS,
                "transport": IDENT_B,
            })
            assert await resp.json() == {
                "url": "http://e1:8000", "matched_blocks": 3,
                "transport": "device",
            }
            # no requester identity -> HTTP -> key absent (pre-39 shape)
            resp = await client.post("/peer_lookup", json={
                "hashes": ["a", "b", "c"], "block_size": BS,
            })
            assert await resp.json() == {
                "url": "http://e1:8000", "matched_blocks": 3,
            }
            # owner without a registered identity -> HTTP
            resp = await client.post("/peer_lookup", json={
                "hashes": ["a", "b", "c"], "block_size": BS,
                "transport": IDENT_B, "exclude": "http://e1:8000",
            })
            assert await resp.json() == {
                "url": "http://e2:8000", "matched_blocks": 2,
            }
        finally:
            await client.close()

    asyncio.run(go())


def test_router_register_stores_transport_and_hints():
    from vllm_production_stack_tpu.router.app import build_app
    from vllm_production_stack_tpu.router.args import parse_args

    async def go():
        app = build_app(parse_args([
            "--static-backends", "http://e1:8000",
            "--static-models", "m",
            "--routing-logic", "kvaware",
            "--kv-index-mode", "embedded",
            "--kv-index-tokenizer", "byte",
        ]))
        index = _fed_index()
        app["state"].policy.index = index
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post("/register", json={
                "url": "http://e1:8000", "transport": IDENT_A,
            })
            assert resp.status == 200
            assert index.get_transport("http://e1:8000") == IDENT_A
            resp = await client.post("/peer_lookup", json={
                "hashes": ["a", "b", "c"], "block_size": BS,
                "transport": IDENT_B,
            })
            assert await resp.json() == {
                "url": "http://e1:8000", "matched_blocks": 3,
                "transport": "device",
            }
            # re-register without a mesh clears the stale advertisement
            await client.post("/register", json={"url": "http://e1:8000"})
            resp = await client.post("/peer_lookup", json={
                "hashes": ["a", "b", "c"], "block_size": BS,
                "transport": IDENT_B,
            })
            assert await resp.json() == {
                "url": "http://e1:8000", "matched_blocks": 3,
            }
        finally:
            await client.close()

    asyncio.run(go())


# -- pricing: the device rung in plan_decisions ------------------------------


def _signal(device_bw=0.0, device_measured=False, peer_bw=1e9,
            peer_measured=True, flops_per_s=1e6, flops_per_token=100.0,
            block_bytes=1000.0):
    return {
        "fetch_bandwidth_bytes_per_s": {
            "host": 1e12, "disk": 1e9, "remote": 1e9,
            "device": device_bw, "peer": peer_bw,
        },
        "fetch_bandwidth_measured": {
            "host": True, "disk": True, "remote": True,
            "device": device_measured, "peer": peer_measured,
        },
        "prefill_flops_per_s": flops_per_s,
        "peak_flops_per_s": 0.0,
        "flops_per_token": flops_per_token,
        "attn_flops_per_token_ctx": 0.0,
        "block_bytes": block_bytes,
        "block_size_tokens": BS,
    }


def test_unmeasured_device_prices_recompute_but_never_declines():
    chunks = [["device", "device"]] * 4
    out = plan_decisions(chunks, _signal())
    assert out is not None  # no sync path feeds the estimator: must engage
    decisions, _ = out
    assert decisions == ["recompute"] * 4


def test_measured_device_link_flips_recompute_to_load():
    """The acceptance crossover: a prefix priced recompute at the slow
    HTTP-peer bandwidth plans load once the device link is measured."""
    chunks = [["peer", "peer"]] * 4
    slow_http, _ = plan_decisions(chunks, _signal(peer_bw=10.0))
    assert slow_http == ["recompute"] * 4
    # same prefix, same owner — now over the shared-mesh device link
    chunks = [["device", "device"]] * 4
    dev, _ = plan_decisions(
        chunks, _signal(device_bw=1e10, device_measured=True, peer_bw=10.0)
    )
    assert dev == ["load"] * 4


def test_device_slower_than_recompute_still_recomputes():
    chunks = [["device", "device"]] * 4
    decisions, _ = plan_decisions(
        chunks, _signal(device_bw=10.0, device_measured=True)
    )
    assert decisions == ["recompute"] * 4


def test_hydration_signal_device_prices_pool_bytes():
    """The at-rest codec compresses the host-staged hops but never the
    device collective — it moves pool-precision pages, so the planner
    must price device fetches at full logical block bytes (satellite:
    compression ratio pinned at 1.0)."""
    eng = _engine(codec="int4", peer=True)
    try:
        sig = eng.hydration_signal()
        wire = sig["wire_block_bytes"]
        assert wire["device"] == sig["block_bytes"]
        assert wire["peer"] < sig["block_bytes"]  # int4 compresses the wire
        assert wire["disk"] == wire["peer"]
        # device bytes meter logical == wire: the ratio gauge stays 1.0
        eng.flow.record("device", "in", 4096, 1, 0.001)
        snap = eng.flow.snapshot()
        assert snap["compression_ratio"]["device/in"] == 1.0
        assert snap["logical_bytes"]["device/in"] == snap["bytes"]["device/in"]
    finally:
        eng.runner.shutdown(True)


# -- migration-aware eviction ------------------------------------------------


def test_pool_eviction_prefers_replicated_blocks():
    from vllm_production_stack_tpu.engine.kv_cache import KVBlockPool

    pool = KVBlockPool(num_blocks=4, block_size=BS)  # 3 usable + reserve
    hashes = []
    parent = pool.root_hash()
    for i in range(3):
        blk = pool.allocate()
        tokens = tuple(range(i * BS, (i + 1) * BS))
        parent = pool.register_full_block(blk, parent, tokens)
        hashes.append(parent)
        pool.free_block(blk)  # evictable, refcount 0
    # the cluster says a peer now holds copies of block[1] only
    assert pool.mark_replicated([hashes[1], 0xDEAD]) == 1
    blk = pool.allocate()  # pool full: someone must die
    # the replicated block dies first even though LRU order would have
    # evicted block[0]; the unreplicated hot blocks all survive
    assert hashes[1] not in pool._hash_to_block
    for h in (hashes[0], hashes[2]):
        assert h in pool._hash_to_block
    pool.free_block(blk)


def test_pool_mark_replicated_bound_resets():
    from vllm_production_stack_tpu.engine.kv_cache import KVBlockPool

    pool = KVBlockPool(num_blocks=4, block_size=BS)
    # the replicated set is bounded: a flood of marks for long-gone
    # blocks clears rather than grows without limit
    for i in range(5):
        pool.mark_replicated(list(range(i * 4, i * 4 + 4)))
    assert len(pool._replicated) <= 4 * 4 + 4


def test_host_ring_eviction_prefers_replicated_blocks():
    from vllm_production_stack_tpu.engine.kv_host_tier import HostKVTier

    class Dev:
        def __init__(self):
            self.mem = np.arange(16 * 2 * 4, dtype=np.float32).reshape(
                16, 2, 4
            )

        def fetch(self, blk):
            return [self.mem[blk, i].copy() for i in range(2)]

        def upload(self, blk, parts):
            for i, p in enumerate(parts):
                self.mem[blk, i] = p

    dev = Dev()
    tier = HostKVTier(3, dev.fetch, dev.upload)
    replicated: set[int] = set()
    tier.is_replicated = lambda h: h in replicated
    for h in (101, 102, 103):
        tier.store(h, h - 100)
    replicated.add(102)
    tier.store(104, 4)  # over budget: one of the three must go
    assert 102 not in tier, "replicated block should die first"
    assert 101 in tier and 103 in tier and 104 in tier
    # with nothing replicated, plain LRU order resumes (oldest first)
    replicated.clear()
    tier.store(105, 5)
    assert 101 not in tier
    assert 103 in tier and 104 in tier and 105 in tier


# -- the Hydrator's device fetch lane (fake collective) ----------------------


def _pair(transport="device"):
    """Owner engine A (served) + cold puller B with paired mesh
    identities assigned directly — jax.distributed isn't (and can't be)
    initialized inside the test process; the real collective is covered
    by the 2-process dryrun in test_distributed."""
    eng_a = _engine(mode="sync", peer=True, transport=transport)
    eng_b = _engine(mode="planner", transport=transport, timeout_s=60.0)
    eng_a.peer_tier.transport_identity = dict(IDENT_A)
    eng_b.peer_tier.transport_identity = dict(IDENT_B)
    return eng_a, eng_b


def test_device_lane_end_to_end_with_fake_collective():
    """Probe negotiates "device" against the owner's /kv/peer_contains
    echo, the planner prices the device tier, and the Hydrator routes
    the chunk through device_pull_fn — whose parked-adoption contract a
    fake collective satisfies via kv_peer_replicate. Tokens must be
    bit-identical to the owner's and the partition exact."""
    prompt = _prompt(3)

    async def go():
        eng_a, eng_b = _pair()
        ref = eng_a.generate([prompt], GREEDY)[0]["token_ids"]
        srv = _serve_engine(eng_a)
        await srv.start_server()
        a_url = f"http://127.0.0.1:{srv.port}"
        loop = asyncio.get_running_loop()
        try:
            _warm(eng_b)
            _seed_device_bw(eng_b)
            pulls = []

            def fake_pull(owner, hashes):
                # what the collective does: owner's pages land parked in
                # B's pool, priced as device wire bytes
                t0 = time.perf_counter()
                n = eng_b.kv_peer_replicate(owner, list(hashes))
                eng_b.flow.record(
                    "device", "in", n * 4096, n,
                    time.perf_counter() - t0,
                )
                pulls.append((owner, list(hashes), n))
                return n

            assert eng_b.hydrator is not None
            eng_b.hydrator.device_pull_fn = fake_pull

            out = await loop.run_in_executor(
                None,
                lambda: eng_b.generate(
                    [prompt], GREEDY, kv_owner_hint=a_url
                )[0]["token_ids"],
            )
            assert out == ref
            assert pulls and pulls[0][0].rstrip("/") == a_url
            assert eng_b.peer_tier.transport_for(a_url) == "device"
            hyd, total = _partition(eng_b)
            assert total == eng_b._prompt_tokens
            assert hyd["peer_fetch"] > 0, hyd
            snap = eng_b.flow.snapshot()
            assert snap["decisions"]["load"] > 0
            assert snap["bytes"]["device/in"] > 0
        finally:
            await srv.close()
            await loop.run_in_executor(
                None, lambda: eng_b.runner.shutdown(True)
            )
            await loop.run_in_executor(
                None, lambda: eng_a.runner.shutdown(True)
            )

    asyncio.run(go())


def test_device_pull_fault_records_zero_sample_and_falls_back():
    """Chaos contract: a device pull whose trigger never reaches the
    owner records an honest 0-byte device/in sample (visible in
    tpu:kv_transfer_seconds{tier="device"}), the chunk degrades to
    fallback_recompute, the partition stays exact, and the tokens are
    still correct — the fault costs time, never answers."""
    prompt = _prompt(4)

    async def go():
        eng_a, eng_b = _pair()
        ref = eng_a.generate([prompt], GREEDY)[0]["token_ids"]
        srv = _serve_engine(eng_a)
        await srv.start_server()
        a_url = f"http://127.0.0.1:{srv.port}"
        loop = asyncio.get_running_loop()
        try:
            _warm(eng_b)
            _seed_device_bw(eng_b)
            # the probe still negotiates "device" against the live owner;
            # the PULL goes to a black hole — connection refused, which is
            # _device_peer_pull's trigger-failure path
            eng_b.hydrator.device_pull_fn = (
                lambda owner, hashes: eng_b._device_peer_pull(
                    "http://127.0.0.1:9", list(hashes)
                )
            )
            out = await loop.run_in_executor(
                None,
                lambda: eng_b.generate(
                    [prompt], GREEDY, kv_owner_hint=a_url
                )[0]["token_ids"],
            )
            assert out == ref
            snap = eng_b.flow.snapshot()
            assert snap["bytes"]["device/in"] == 0
            assert snap["transfers"]["device/in"] >= 1  # the 0-byte sample
            hyd, total = _partition(eng_b)
            assert total == eng_b._prompt_tokens
            assert hyd["recomputed"] > 0, hyd  # the flipped chunk's tokens
            assert snap["decisions"]["fallback_recompute"] > 0
        finally:
            await srv.close()
            await loop.run_in_executor(
                None, lambda: eng_b.runner.shutdown(True)
            )
            await loop.run_in_executor(
                None, lambda: eng_a.runner.shutdown(True)
            )

    asyncio.run(go())


def test_stalled_device_pull_watchdog_names_fetcher_thread():
    """A wedged collective must never implicate the step thread: the
    pull runs on the hydration fetcher, and the PR 15 watchdog names
    "hydration_fetch" when it stalls."""
    from vllm_production_stack_tpu.engine.flightrec import (
        ThreadRegistry,
        Watchdog,
    )
    from vllm_production_stack_tpu.engine.hydration import Hydrator
    from vllm_production_stack_tpu.engine.kv_flow import KVFlowMeter

    reg = ThreadRegistry()
    hb = reg.register("hydration_fetch", stall_after_s=0.02)
    stalls: list = []
    wd = Watchdog(reg, interval_s=0.01, on_stall=stalls.append)
    release = threading.Event()

    def stalled_pull(owner, hashes):
        release.wait(timeout=5.0)
        return 0

    hyd = Hydrator(
        mode="auto", flow=KVFlowMeter(), heartbeat=hb,
        device_pull_fn=stalled_pull,
    )
    try:
        sig = _signal()  # device unmeasured: bootstrap engages
        hyd._maybe_bootstrap("http://owner:8000", [1, 2, 3], sig,
                             tier="device")
        time.sleep(0.1)  # beat() then silence inside the stalled pull
        report = wd.check()
        findings = [
            f for f in report["findings"]
            if f["thread"] == "hydration_fetch"
        ]
        assert findings, report
        assert findings[0]["kind"] == "stale_heartbeat"
    finally:
        release.set()
        hyd.close()


# -- controller: proactive flash-crowd replication ---------------------------


def test_controller_flash_crowd_replication():
    """Two /peer_lookup hits on the same prefix inside the window cross
    threshold=2: the controller orders the least-loaded non-holder to
    pull from the owner, then tells the owner its blocks are replicated
    — and counts it on /metrics."""
    from vllm_production_stack_tpu import metrics_contract as mc
    from vllm_production_stack_tpu.engine.kv_controller import KVController

    async def go():
        calls: dict[str, list] = {"replicate": [], "replicated": []}

        async def h_replicate(request):
            calls["replicate"].append(await request.json())
            return web.json_response({"adopted": 2})

        async def h_replicated(request):
            calls["replicated"].append(await request.json())
            return web.json_response({"resident": 2})

        owner_app, target_app = web.Application(), web.Application()
        owner_app.router.add_post("/kv/replicated", h_replicated)
        target_app.router.add_post("/kv/peer_replicate", h_replicate)
        owner_srv = TestServer(owner_app)
        target_srv = TestServer(target_app)
        await owner_srv.start_server()
        await target_srv.start_server()
        owner_url = f"http://127.0.0.1:{owner_srv.port}"
        target_url = f"http://127.0.0.1:{target_srv.port}"

        controller = KVController(
            [owner_url, target_url], replicate_threshold=2,
            replicate_window_s=10.0,
        )
        controller.index = ClusterKVIndex(stale_after_s=None)
        for url, hashes in (
            (owner_url, [0xA, 0xB, 0xC]),
            (target_url, [0xF]),  # fresh, same block size, not a holder
        ):
            controller.index.apply({
                "engine": url, "epoch": "x", "block_size": BS,
                "snapshot": True, "seq": 0,
                "hashes": [f"{h:x}" for h in hashes],
            })
        client = TestClient(TestServer(controller.build_app()))
        await client.start_server()
        try:
            for _ in range(2):
                resp = await client.post("/peer_lookup", json={
                    "hashes": ["a", "b", "c"], "block_size": BS,
                })
                assert (await resp.json())["url"] == owner_url
            for _ in range(100):  # the replication task is fire-and-forget
                if controller.replications_ordered:
                    break
                await asyncio.sleep(0.02)
            assert controller.replications_ordered == 1
            assert calls["replicate"] == [{
                "owner": owner_url,
                "hashes": [str(0xA), str(0xB), str(0xC)],
            }]
            # only the adopted prefix is marked replicated on the owner
            assert calls["replicated"] == [{
                "hashes": [str(0xA), str(0xB)],
            }]
            resp = await client.get("/metrics")
            text = await resp.text()
            assert f"{mc.CLUSTER_KV_REPLICATIONS} 1" in text
        finally:
            await client.close()
            await owner_srv.close()
            await target_srv.close()

    asyncio.run(go())


def test_controller_replication_off_by_default():
    from vllm_production_stack_tpu.engine.kv_controller import KVController

    async def go():
        controller = KVController(["http://e1:8000"])
        assert controller.replicate_threshold == 0
        controller.index = _fed_index()
        client = TestClient(TestServer(controller.build_app()))
        await client.start_server()
        try:
            for _ in range(5):
                await client.post("/peer_lookup", json={
                    "hashes": ["a", "b"], "block_size": BS,
                })
            assert controller.replications_ordered == 0
            assert not controller._crowd
        finally:
            await client.close()

    asyncio.run(go())
