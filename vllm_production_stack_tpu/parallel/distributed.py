"""Multi-host process bootstrap: the executable half of the helm env contract.

The multi-host StatefulSet template (helm/templates/statefulset-multihost.yaml)
exports `JAX_COORDINATOR_ADDRESS` / `JAX_NUM_PROCESSES` / `JAX_PROCESS_ID`
into every pod of a slice — one engine process per TPU host, pod 0 doubling
as the coordinator. This module consumes that contract: `maybe_initialize()`
turns it into a live `jax.distributed` service so `jax.devices()` spans every
host's chips and one GSPMD mesh (parallel/mesh.py) can cover a v5e-16's four
hosts. Reference equivalent: the RayCluster head gating on EXPECTED_NODES
before launching vLLM with pipeline parallelism
(/root/reference/helm/templates/ray-cluster.yaml:44-46,556-566) — here the
coordination service is JAX's own, not Ray.

Also provides the multi-PROCESS dryrun used by `__graft_entry__.
dryrun_multichip`: N real OS processes, each owning one virtual CPU device,
form one mesh through this exact code path and run a collective + a sharded
model forward — validating the statefulset contract end-to-end without TPU
hardware (`python -m vllm_production_stack_tpu.parallel.distributed --worker`
is the per-process entry).
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys

from ..utils.logging import init_logger

logger = init_logger(__name__)

ENV_COORDINATOR = "JAX_COORDINATOR_ADDRESS"
ENV_NUM_PROCESSES = "JAX_NUM_PROCESSES"
ENV_PROCESS_ID = "JAX_PROCESS_ID"


def distributed_env() -> tuple[str, int, int] | None:
    """(coordinator_address, num_processes, process_id) from the helm env
    contract, or None when the pod is not part of a multi-host slice."""
    addr = os.environ.get(ENV_COORDINATOR)
    if not addr:
        return None
    try:
        n = int(os.environ.get(ENV_NUM_PROCESSES, "1"))
        pid = int(os.environ.get(ENV_PROCESS_ID, "0"))
    except ValueError as e:
        raise ValueError(
            f"malformed {ENV_NUM_PROCESSES}/{ENV_PROCESS_ID}: {e}"
        ) from e
    if not 0 <= pid < max(n, 1):
        raise ValueError(
            f"{ENV_PROCESS_ID}={pid} out of range for "
            f"{ENV_NUM_PROCESSES}={n}"
        )
    return addr, n, pid


def maybe_initialize(mode: str = "auto") -> bool:
    """Call `jax.distributed.initialize` from the env contract.

    mode: "auto" initializes iff the contract names >1 process; "on"
    requires the contract (raises if absent); "off" never initializes.
    Must run before the first JAX backend touch. Returns True when the
    distributed service was started."""
    if mode == "off":
        return False
    env = distributed_env()
    if env is None or env[1] <= 1:
        if mode == "on":
            raise RuntimeError(
                f"--distributed on, but {ENV_COORDINATOR} is unset (or "
                f"{ENV_NUM_PROCESSES} <= 1); the multi-host statefulset "
                "exports these — see helm/templates/statefulset-multihost.yaml"
            )
        return False
    addr, n, pid = env
    import jax

    # the CPU PJRT client ships without cross-process collectives
    # ("Multiprocess computations aren't implemented on the CPU
    # backend"); gloo turns them on. Every cross-process transfer
    # program in this repo (PD ship, device-path peer pulls) and the
    # multiprocess dryruns need this on the cpu backend; it's a no-op
    # for TPU (the option only shapes the CPU client). Must land before
    # the first backend touch, which is why it lives here.
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:  # noqa: BLE001 — jax without the knob: keep going
        pass

    logger.info(
        "initializing jax.distributed: coordinator=%s processes=%d "
        "process_id=%d", addr, n, pid,
    )
    jax.distributed.initialize(
        coordinator_address=addr, num_processes=n, process_id=pid
    )
    logger.info(
        "distributed runtime up: process %d/%d, %d local / %d global devices",
        jax.process_index(), jax.process_count(),
        jax.local_device_count(), jax.device_count(),
    )
    return True


# -- multi-process dryrun ----------------------------------------------------


def _worker() -> None:
    """One process of the multi-process dryrun (spawned with the helm env
    contract set): initialize, form a dp mesh spanning every process, run a
    cross-process collective and a dp-sharded model forward."""
    import numpy as np

    ok = maybe_initialize("on")
    assert ok
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = jax.process_count()
    pid = jax.process_index()
    assert len(jax.devices()) == n * jax.local_device_count()

    from ..engine.config import ModelConfig
    from ..models import llama
    from . import mesh as mesh_lib

    mesh = mesh_lib.make_mesh(data_parallel_size=jax.device_count())

    # 1) collective across PROCESS boundaries: global sum of per-process
    # contributions through the mesh
    local = np.full((jax.local_device_count(), 1), pid + 1, np.float32)
    garr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P(mesh_lib.DP_AXIS, None)), local
    )
    total = jax.jit(jnp.sum)(garr)
    # every process holds the replicated global result
    want = sum(p + 1 for p in range(n)) * (jax.device_count() // n)
    assert float(total) == want, (float(total), want)

    # 2) dp-sharded model forward: identical params on every process (same
    # PRNGKey), batch rows sharded one per device across processes
    cfg = ModelConfig(
        model="dryrun-mp-llama", vocab_size=128, hidden_size=32,
        intermediate_size=64, num_layers=2, num_heads=2, num_kv_heads=2,
        head_dim=16, max_model_len=32, dtype="float32",
    )
    params = jax.jit(llama.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0)
    )
    t = 8
    rows_per_proc = jax.device_count() // n
    rng = np.random.RandomState(100 + pid)
    ids_local = rng.randint(1, cfg.vocab_size, size=(rows_per_proc, t))
    ids = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P(mesh_lib.DP_AXIS, None)),
        ids_local.astype(np.int32),
    )
    lens = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P(mesh_lib.DP_AXIS)),
        np.full((rows_per_proc,), t, np.int32),
    )

    @jax.jit
    def fwd(p, ids, lens):
        vecs = llama.embed_encode(cfg, p, ids, lens)
        return llama.compute_logits(cfg, p, vecs)

    logits = fwd(params, ids, lens)
    jax.block_until_ready(logits)
    for shard in logits.addressable_shards:
        assert np.all(np.isfinite(np.asarray(shard.data)))
    print(f"MP_DRYRUN_OK process={pid}/{n}", flush=True)


def _pd_worker() -> None:
    """One process of the cross-process PD dryrun: process 0 is a
    prefill-role engine, process 1 a decode-role engine — DIFFERENT
    jax.distributed processes, each with its own single-device mesh (the
    multi-host PD deployment shape, one engine pod per host). The prefill
    side computes the prompt's KV; `ship_kv_device_crossproc` moves the
    pages device-to-device (a cooperative shard-flip program — the DCN
    hop); the decode side adopts them and its continuation must be
    IDENTICAL to a from-scratch engine's (bit-identical pages ⇒ identical
    greedy tokens; a fresh same-seed engine recomputing the prompt is the
    oracle)."""
    import numpy as np

    ok = maybe_initialize("on")
    assert ok
    import jax
    from jax.experimental import multihost_utils

    n = jax.process_count()
    pid = jax.process_index()
    assert n == 2, f"PD dryrun is a 2-process shape, got {n}"
    tp = int(os.environ.get("PD_DRYRUN_TP", "1"))

    from ..engine.config import (
        CacheConfig, EngineConfig, ModelConfig, ParallelConfig,
        SchedulerConfig,
    )
    from ..engine.engine import LLMEngine
    from ..engine.kv_device_transfer import ship_kv_device_crossproc
    from ..engine.request import SamplingParams
    from . import mesh as mesh_lib

    local_mesh = mesh_lib.make_mesh(
        tensor_parallel_size=tp, devices=jax.local_devices()[:tp]
    )
    config = EngineConfig(
        model=ModelConfig(
            model="dryrun-pd-llama", vocab_size=128, hidden_size=32,
            intermediate_size=64, num_layers=2, num_heads=2, num_kv_heads=2,
            head_dim=16, max_model_len=64, dtype="float32",
        ),
        cache=CacheConfig(block_size=8, num_blocks=32),
        scheduler=SchedulerConfig(
            max_num_seqs=2, max_num_batched_tokens=32,
            prefill_buckets=(32,), decode_buckets=(2,), decode_window=4,
        ),
        parallel=ParallelConfig(tensor_parallel_size=tp),
    )
    engine = LLMEngine(config, mesh=local_mesh)
    rng = np.random.RandomState(7)
    prompt = [int(x) for x in rng.randint(1, 128, size=24)]
    sampling = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)

    if pid == 0:
        # PD prefill convention (router request_service): max_tokens=1
        engine.generate([prompt], SamplingParams(
            max_tokens=1, temperature=0.0, ignore_eos=True,
        ))
    multihost_utils.sync_global_devices("pd-prefill-done")

    adopted = ship_kv_device_crossproc(
        engine, role="prefill" if pid == 0 else "decode", token_ids=prompt,
    )
    if pid == 1:
        assert adopted > 0, "decode side adopted nothing"
        s0 = engine.stats()
        out = engine.generate([prompt], sampling)[0]["token_ids"]
        s1 = engine.stats()
        assert s1.prefix_cache_hits > s0.prefix_cache_hits, (
            "continuation did not hit the adopted blocks"
        )
        # oracle: a fresh same-seed engine that computes the prompt's KV
        # itself — identical continuation proves the shipped pages carry
        # the exact bytes
        oracle = LLMEngine(config, mesh=local_mesh)
        want = oracle.generate([prompt], sampling)[0]["token_ids"]
        assert out == want, (out, want)
    # re-ship the SAME prompt: everything is already resident on the
    # decode side, so adoption must be 0 AND must not leak the pins
    # stage_adoption takes on resident chain members (the empty-ship
    # abort path — a leak makes blocks unevictable over repeat ships)
    refs_before = dict(engine.scheduler.pool._ref)
    adopted2 = ship_kv_device_crossproc(
        engine, role="prefill" if pid == 0 else "decode", token_ids=prompt,
    )
    if pid == 1:
        assert adopted2 == 0, adopted2
        assert engine.scheduler.pool._ref == refs_before, (
            "re-ship leaked block pins",
            refs_before, engine.scheduler.pool._ref,
        )
        print(
            f"PD_DRYRUN_OK adopted={adopted} continuation={out[:4]}...",
            flush=True,
        )
    else:
        print("PD_DRYRUN_OK role=prefill", flush=True)
    multihost_utils.sync_global_devices("pd-done")


def _device_peer_worker() -> None:
    """One process of the device-path PEER KV dryrun (docs/39): process 0
    is an OWNER engine serving the real HTTP app (EngineServer — its
    AsyncEngine step loop shares the quiescence lock the device serve
    takes); process 1 is a PULLER whose hydration planner labels the
    prompt's continuation tier "device" through the owner-hint contains
    probe, and whose fetcher thread pulls the pages over the cooperative
    shard-flip collective instead of HTTP. The puller asserts the bytes
    moved on (device, in) — NOT (peer, in) — that the admitted prompt's
    tokens attribute to peer_fetch, and that the continuation is
    token-identical to a from-scratch oracle engine (bit-identical pages
    ⇒ identical greedy tokens)."""
    import json
    import threading
    import time

    import numpy as np

    ok = maybe_initialize("on")
    assert ok
    import jax

    n = jax.process_count()
    pid = jax.process_index()
    assert n == 2, f"device-peer dryrun is a 2-process shape, got {n}"
    assert os.environ.get("KV_MESH_GROUP"), (
        "spawner must export KV_MESH_GROUP — it is the transport identity"
    )

    from ..engine.config import (
        CacheConfig, EngineConfig, ModelConfig, ParallelConfig,
        SchedulerConfig,
    )
    from ..engine.engine import LLMEngine
    from ..engine.request import SamplingParams
    from . import mesh as mesh_lib

    local_mesh = mesh_lib.make_mesh(
        tensor_parallel_size=1, devices=jax.local_devices()[:1]
    )
    config = EngineConfig(
        model=ModelConfig(
            model="dryrun-devpeer-llama", vocab_size=128, hidden_size=32,
            intermediate_size=64, num_layers=2, num_heads=2, num_kv_heads=2,
            head_dim=16, max_model_len=64, dtype="float32",
        ),
        cache=CacheConfig(
            block_size=8, num_blocks=32, num_host_blocks=16,
        ),
        scheduler=SchedulerConfig(
            max_num_seqs=2, max_num_batched_tokens=32,
            prefill_buckets=(32,), decode_buckets=(2,), decode_window=4,
        ),
        parallel=ParallelConfig(tensor_parallel_size=1),
        kv_peer_fetch=True,
        kv_peer_transport="device",
        # generous plan deadline: the first pull cold-compiles the
        # shard-flip program, which would blow the 0.5s auto floor and
        # flip the chunk to fallback_recompute before the bytes land
        kv_hydration_timeout_s=120.0,
    )
    engine = LLMEngine(config, mesh=local_mesh)
    assert engine.peer_tier is not None
    assert engine.peer_tier.transport_identity is not None, (
        "no mesh identity — KV_MESH_GROUP + jax.distributed should have "
        "produced one"
    )
    rng = np.random.RandomState(7)
    prompt = [int(x) for x in rng.randint(1, 128, size=24)]
    sampling = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)

    if pid == 0:
        # ---- owner: compute the prompt's KV, then serve the real app.
        # Prefill BEFORE the server starts: once AsyncEngine's step loop
        # owns the engine, a second sync generate loop would race it for
        # this request's outputs.
        engine.generate([prompt], SamplingParams(
            max_tokens=1, temperature=0.0, ignore_eos=True,
        ))
        import asyncio
        import http.client

        from aiohttp import web

        from ..engine.server import EngineServer

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        srv = EngineServer(engine, watchdog=False)
        done = threading.Event()
        loop = asyncio.new_event_loop()

        def _serve() -> None:
            asyncio.set_event_loop(loop)

            async def _done(request):
                done.set()
                return web.json_response({"ok": True})

            app = srv.build_app()
            app.router.add_post("/dryrun_done", _done)
            runner = web.AppRunner(app)
            loop.run_until_complete(runner.setup())
            loop.run_until_complete(
                web.TCPSite(runner, "127.0.0.1", port).start()
            )
            loop.run_forever()

        # tpulint: allow(thread-lifecycle) — dryrun-subprocess serve loop;
        # the worker OS process exits (and reclaims the daemon thread)
        # right after the puller signals /dryrun_done
        threading.Thread(
            target=_serve, daemon=True, name="devpeer-serve"
        ).start()
        deadline = time.monotonic() + 60.0
        while True:  # publish the port only once the app answers
            try:
                c = http.client.HTTPConnection("127.0.0.1", port, timeout=1)
                c.request("GET", "/health")
                c.getresponse().read()
                c.close()
                break
            except OSError:
                assert time.monotonic() < deadline, "engine app never came up"
                time.sleep(0.05)
        # publish the port through the coordination service's KV store —
        # NOT an XLA collective (the CPU backend refuses whole-mesh
        # multiprocess computations; the pairwise shard-flip transfer
        # program is the only collective this dryrun should run)
        from jax._src.distributed import global_state

        global_state.client.key_value_set("devpeer_dryrun/port", str(port))
        assert done.wait(timeout=240.0), "puller never signalled completion"
        served = engine.flow.bytes[("device", "out")]
        assert served > 0, "owner served no device-path bytes"
        time.sleep(0.2)  # let the /dryrun_done reply flush before exit
        print(f"DEVPEER_DRYRUN_OK role=owner served_bytes={served}",
              flush=True)
    else:
        from jax._src.distributed import global_state

        port = int(global_state.client.blocking_key_value_get(
            "devpeer_dryrun/port", 120_000
        ))
        owner_url = f"http://127.0.0.1:{port}"
        # warm the compute estimator: plan_decisions cannot engage on a
        # cold engine (no achieved FLOP/s and no chip peak on CPU), and a
        # declined plan recomputes everything — the device lane would
        # never fire. A throwaway generate (disjoint tokens, no prefix
        # collision with the real prompt) gives the StepMeter its
        # sample, exactly like the peer tests' _warm helper. It must run
        # several dispatches: the meter's wall clock starts at the FIRST
        # record call (which reads wall=0 and cannot update the EWMA), so
        # only the decode steps after the prefill feed achieved-FLOP/s.
        engine.generate([[9] * 8], SamplingParams(
            max_tokens=4, temperature=0.0, ignore_eos=True,
        ))
        # seed the device estimator past the TierBandwidth sample floor:
        # an unmeasured device tier prices recompute (never declines, but
        # never loads either) — in production the Hydrator's bootstrap
        # pulls cross the floor; here we pin a fast estimate so the plan
        # deterministically decides "load" and the pull happens
        now = time.perf_counter()
        est = engine.flow.bandwidth[("device", "in")]
        est.record(1 << 20, 1e-3, now)
        est.record(1 << 20, 1e-3, now + 1e-3)
        base_peer = engine.flow.bytes[("peer", "in")]
        t0 = time.perf_counter()
        out = engine.generate(
            [prompt], sampling, kv_owner_hint=owner_url
        )[0]["token_ids"]
        latency = time.perf_counter() - t0
        dev_bytes = engine.flow.bytes[("device", "in")]
        assert dev_bytes > 0, "no bytes moved on the device path"
        assert engine.flow.transfers[("device", "in")] >= 1
        assert engine.flow.bytes[("peer", "in")] == base_peer, (
            "puller fell back to HTTP peer fetch"
        )
        assert engine.flow.hydration["peer_fetch"] > 0, (
            "admitted prompt attributed no tokens to peer_fetch"
        )
        # oracle: a fresh same-seed engine computing every token itself —
        # identical continuation proves the pulled pages carry the exact
        # bytes the owner's prefill produced
        oracle = LLMEngine(config, mesh=local_mesh)
        want = oracle.generate([prompt], sampling)[0]["token_ids"]
        assert out == want, (out, want)
        import http.client

        try:
            c = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
            c.request("POST", "/dryrun_done", body=json.dumps({}))
            c.getresponse().read()
            c.close()
        except OSError:
            pass  # owner may already be tearing down
        print(
            f"DEVPEER_DRYRUN_OK pulled_bytes={dev_bytes} "
            f"latency_s={latency:.3f} continuation={out[:4]}...",
            flush=True,
        )


def _spawn_workers(
    n_processes: int, flag: str, timeout_s: float, ok_marker: str,
    devices_per_proc: int = 1, extra_env: dict | None = None,
):
    """Spawn n real OS processes that form ONE jax.distributed runtime via
    the helm env contract (each process = one TPU host stand-in with 1 CPU
    device). Raises on any failure; returns the per-process outputs."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    # workers must import this package regardless of the caller's cwd
    pkg_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    procs = []
    for pid in range(n_processes):
        env = dict(os.environ)
        env.update({
            ENV_COORDINATOR: f"127.0.0.1:{port}",
            ENV_NUM_PROCESSES: str(n_processes),
            ENV_PROCESS_ID: str(pid),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count="
                         f"{devices_per_proc}",
            "PYTHONPATH": pkg_root + os.pathsep + env.get("PYTHONPATH", ""),
            **(extra_env or {}),
        })
        procs.append(subprocess.Popen(
            [sys.executable, "-m",
             "vllm_production_stack_tpu.parallel.distributed", flag],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        ))
    outputs = []
    failed = []
    for pid, proc in enumerate(procs):
        try:
            out, _ = proc.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, _ = proc.communicate()
            failed.append((pid, "timeout", out))
            continue
        outputs.append(out)
        if proc.returncode != 0 or ok_marker not in out:
            failed.append((pid, f"rc={proc.returncode}", out))
    if failed:
        detail = "\n".join(
            f"--- process {pid} ({why}):\n{out[-2000:]}"
            for pid, why, out in failed
        )
        raise RuntimeError(
            f"multi-process dryrun failed in {len(failed)}/{n_processes} "
            f"processes:\n{detail}"
        )
    return outputs


def run_multiprocess_dryrun(n_processes: int = 2, timeout_s: float = 300.0):
    """N processes form one mesh and run a collective + dp-sharded forward
    (the multi-host statefulset contract, executable)."""
    return _spawn_workers(n_processes, "--worker", timeout_s, "MP_DRYRUN_OK")


def run_multiprocess_pd_dryrun(timeout_s: float = 300.0, tp: int = 1):
    """2 processes: prefill engine + decode engine in DIFFERENT
    jax.distributed processes, device-path KV ship across them,
    bit-identical continuation asserted (VERDICT r4 #5). tp>1 gives each
    role a tp-sharded mesh (tp devices per process) and ships each kvh
    chunk over its own pairwise flip."""
    return _spawn_workers(
        2, "--pd-worker", timeout_s, "PD_DRYRUN_OK",
        devices_per_proc=tp, extra_env={"PD_DRYRUN_TP": str(tp)},
    )


def run_multiprocess_device_peer_dryrun(timeout_s: float = 300.0):
    """2 processes: an owner engine serving the real HTTP app and a puller
    whose hydration fetch lane pulls the prompt's KV over the device
    collective path (docs/39-device-peer-kv.md) — transport negotiated
    through the owner-hint contains probe, bytes metered under
    (device, in), continuation bit-identical to a from-scratch oracle."""
    return _spawn_workers(
        2, "--device-peer-worker", timeout_s, "DEVPEER_DRYRUN_OK",
        extra_env={"KV_MESH_GROUP": "devpeer-dryrun"},
    )


def main() -> None:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--worker", action="store_true",
                   help="run as one process of the multi-process dryrun")
    p.add_argument("--pd-worker", action="store_true",
                   help="run as one process of the cross-process PD dryrun")
    p.add_argument("--device-peer-worker", action="store_true",
                   help="run as one process of the device-path peer KV "
                        "dryrun")
    p.add_argument("--processes", type=int, default=2)
    args = p.parse_args()
    if args.worker:
        _worker()
    elif args.pd_worker:
        _pd_worker()
    elif args.device_peer_worker:
        _device_peer_worker()
    else:
        run_multiprocess_dryrun(args.processes)
        run_multiprocess_pd_dryrun()
        run_multiprocess_device_peer_dryrun()
        print(f"multi-process dryrun OK ({args.processes} processes + PD "
              "+ device-peer)")


if __name__ == "__main__":
    main()
