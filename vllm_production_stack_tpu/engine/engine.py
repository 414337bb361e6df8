"""LLMEngine: the synchronous serving core (scheduler + runner + detokenize).

The OpenAI HTTP layer (engine/server.py) drives `step()` from a background
loop; offline use (chip_smoke.py, tests) drives it directly. This composes the
pieces the reference gets from vLLM images, exporting the stats the router's
scraper contract expects (SURVEY §5 metrics contract).
"""

from __future__ import annotations

import contextlib
import itertools
import logging
import threading
import time
from dataclasses import dataclass, field

from .. import metrics_contract as mc
from ..qos import TenantContext
from ..utils.tokenizer import IncrementalDetokenizer, TokenizerWrapper
from .config import EngineConfig
from .model_runner import ModelRunner, StepHandle
from .request import Request, RequestOutput, RequestStatus, SamplingParams
from .saturation import StepMeter
from .scheduler import DecodeWork, PrefillWork, Scheduler, VerifyWork

logger = logging.getLogger(__name__)


class EngineOverloadedError(RuntimeError):
    """Admission refused: the waiting queue / queued-token watermark is
    full. The HTTP layer answers 429 with Retry-After = `retry_after_s`
    (computed from observed decode throughput)."""

    def __init__(self, message: str, retry_after_s: float):
        super().__init__(message)
        self.retry_after_s = retry_after_s


class DeadlineExceededError(RuntimeError):
    """Admission refused: the request's deadline has already passed, or the
    estimated queue wait would blow through it — shedding at the door is
    strictly cheaper than prefilling a reply nobody will read (503)."""


@dataclass
class EngineStatsSnapshot:
    """Mirrors the metric contract the router scrapes from engines
    (reference: src/vllm_router/stats/engine_stats.py:42-85)."""

    num_requests_running: int = 0
    num_requests_waiting: int = 0
    kv_usage_perc: float = 0.0  # the fuller page group's share
    kv_group_usage: dict = field(default_factory=dict)  # group -> share
    kv_window_share: float = 0.0
    kv_window_pages_given_back: int = 0
    # state slots (kv_cache.StateSlots): bytes and slots in use, and their
    # share of the cache bytes in use
    state_bytes: int = 0
    # generation by diffusion over blocks: the windows' device counts
    # (metrics_contract.DEBUG_TIMING_BD_COUNTS; empty for any other model)
    block_diffusion: dict = field(default_factory=dict)
    state_slots_in_use: int = 0
    state_share: float = 0.0
    latent_bytes: int = 0  # latent pages in use x their bytes
    index_bytes: int = 0  # ... x their index keys' bytes (learned selection)
    prefix_cache_hit_rate: float = 0.0
    prefix_cache_hits: int = 0
    prefix_cache_queries: int = 0
    num_preemptions: int = 0
    generation_tokens: int = 0
    prompt_tokens: int = 0
    # pipelined step loop: fraction of step-loop wall time in which host
    # scheduling/postprocess overlapped an in-flight device step
    step_overlap_frac: float = 0.0
    host_kv_usage_perc: float = 0.0
    host_kv_offloads: int = 0
    host_kv_reloads: int = 0
    remote_kv_stores: int = 0
    remote_kv_fetched_blocks: int = 0
    spec_draft_tokens: int = 0
    spec_accepted_tokens: int = 0
    # per-proposer split (closed set ngram|draft) behind the labeled
    # tpu:spec_decode_proposed/accepted_tokens_total contract counters
    spec_proposed_by: dict = field(default_factory=dict)
    spec_accepted_by: dict = field(default_factory=dict)
    # request-lifecycle robustness (metrics_contract REQUESTS_SHED /
    # REQUESTS_DEADLINE_EXPIRED / ENGINE_DRAINING)
    requests_shed: int = 0
    requests_deadline_expired: int = 0
    draining: bool = False
    # multi-tenant QoS (docs/27-multitenancy.md): cumulative per-tenant
    # counters {tenant: {requests, generation_tokens, shed, ...}} plus the
    # queue-wait observations drained for the exporter's histogram
    tenants: dict = field(default_factory=dict)
    tenant_queue_waits: list = field(default_factory=list)
    # saturation & goodput telemetry (docs/29-saturation-slo.md): the
    # StepMeter snapshot (occupancy / padding / MFU / per-step histograms)
    # plus "goodput" (the token-fate ledger) and "kv_tiers" (per-tier
    # occupancy hbm/host/disk/remote) — rendered by EngineMetrics
    saturation: dict = field(default_factory=dict)
    # KV flow telemetry (docs/30-kv-flow-telemetry.md): the KVFlowMeter
    # snapshot — per-(tier, direction) bytes/blocks/latency/bandwidth plus
    # the hydration-source partition counters — rendered by EngineMetrics
    kv_flow: dict = field(default_factory=dict)
    # disk-tier block counters (the rung between HOST_KV_* and REMOTE_KV_*)
    disk_kv_stores: int = 0
    disk_kv_loads: int = 0
    # peer-engine KV tier (docs/35-peer-kv-reuse.md): the migrate-pricing
    # constant (analytic KV bytes per token of this pool) the router reads
    # off /metrics as tpu:kv_bytes_per_token
    kv_bytes_per_token: float = 0.0
    # structured output (docs/41-structured-output.md): cumulative
    # {valid, invalid, fallback} terminal outcomes behind
    # tpu:structured_requests_total, plus the grammar-compile durations
    # drained for the exporter's build-time histogram (same drain pattern
    # as tenant_queue_waits)
    structured_outcomes: dict = field(default_factory=dict)
    grammar_build_times: list = field(default_factory=list)
    # XLA compile telemetry (docs/42-compile-telemetry.md): the
    # CompileWatch snapshot — program-inventory size, per-(phase, trigger)
    # compile counts, cache hits/misses, storm count, and the drained
    # compile-wall observations — rendered by EngineMetrics
    compile: dict = field(default_factory=dict)


@dataclass
class _RequestState:
    request: Request
    detok: IncrementalDetokenizer | None
    text: str = ""
    pending_text: str = ""


@dataclass
class _InflightStep:
    """A step (decode window, speculative verify or prefill chunk)
    dispatched to the device but not yet resolved — the unit the pipelined
    step loop keeps in flight while the host schedules and postprocesses
    around it."""

    work: DecodeWork | VerifyWork | PrefillWork
    handle: StepHandle


def _work_kind(work) -> str:
    """The flight recorder's name for a dispatch (flightrec.GAP_AFTER)."""
    if isinstance(work, PrefillWork):
        return "prefill"
    return "verify" if isinstance(work, VerifyWork) else "decode"


def _refuse_kv_tiers_for_latent_pool(config: EngineConfig) -> None:
    """Every KV tier below the pool was written against (2, kvH, D) pages;
    none has been run on a one-latent-per-token page (engine/kv_codec.py
    groups along head_dim, the disk/remote/peer wires carry the K/V page
    geometry in their fingerprinted frames). Until each is, an MLA model
    refuses it at start with one sentence instead of mis-sizing silently."""
    asked = config.kv_tiers_asked()
    if asked:
        raise ValueError(
            f"{config.model.model} keeps one latent a token in its pool "
            f"(multi-head latent attention); {'; '.join(asked)} "
            f"{'is' if len(asked) == 1 else 'are'} not implemented for a "
            "latent page: run without"
        )


def _window_pairs(start: int, n: int, window: int) -> int:
    """Σ min(q + 1, window) over the queries q = start .. start + n - 1 of
    one contiguous chunk: the (query, key) pairs a window layer covers."""
    short = max(0, min(start + n, window - 1) - start)  # queries with q + 1 < window
    return (short * (start + 1) + short * (short - 1) // 2
            + (n - short) * window)


def _refuse_request_for_block_diffusion(model: str, sampling) -> None:
    """What a request may not ask of a model that generates by diffusion
    over blocks (docs/44-block-diffusion.md), by name: a ValueError is the
    server's 400."""
    why = {
        "structured output (a grammar-constrained row)": (
            sampling is not None and sampling.grammar is not None,
            "the automaton advances one token at a time from the left, and "
            "a block is unmasked in order of confidence"),
        "min_tokens": (
            sampling is not None and sampling.min_tokens > 0,
            "the window of block passes does not suppress stop tokens on "
            "the device"),
    }
    for what, (asked, reason) in why.items():
        if asked:
            raise ValueError(
                f"{model}: {what} cannot be combined with generation by "
                f"diffusion over blocks — {reason}")


class LLMEngine:
    def __init__(
        self,
        config: EngineConfig,
        params=None,
        mesh=None,
        tokenizer: TokenizerWrapper | None = None,
    ):
        from dataclasses import replace

        from .memory import (
            derive_num_blocks, resolve_state_slots, resolve_window_blocks,
            window_group_blockers,
        )

        window_blocks = None
        if config.model.any_sliding:
            # the fused decode window's staged slots are globally
            # attendable — sound only while every staged position is
            # within the window; sp ring prefill has no window masking
            window = min(
                s.window for s in config.model.layer_specs if s.window)
            if window <= config.scheduler.decode_window:
                raise ValueError(
                    f"sliding_window ({window}) must exceed decode_window "
                    f"({config.scheduler.decode_window})"
                )
            if config.parallel.sequence_parallel_size > 1:
                raise ValueError(
                    "sequence parallelism does not support sliding-window "
                    "models yet"
                )
            # where the window layers' pages live is settled here, once:
            # the runner's arrays and the scheduler's allocators follow the
            # same rule (engine/memory.py:resolve_window_blocks)
            window_blocks = resolve_window_blocks(config)
            if window_blocks is not None:
                logger.info(
                    "%s: window layers keep their pages in a group of their "
                    "own (%d pages: the window, a chunk and two decode "
                    "windows a seat), given back behind the window",
                    config.model.model, window_blocks)
            elif not config.model.latent_layers:
                logger.info(
                    "%s: window layers hold the whole context like the full "
                    "layers (the window is a mask), because this "
                    "configuration asks for %s; without, their pages live "
                    "in a group of their own that holds the window only",
                    config.model.model,
                    "; ".join(window_group_blockers(config)))
        if config.model.state_layers and config.cache.enable_prefix_caching:
            # a hit would need the recurrent state at its boundary, which
            # no slot keeps (state snapshots: ROADMAP R-M5)
            logger.info(
                "%s: layers that hold a recurrent state — the engine runs "
                "WITHOUT prefix hits (--no-enable-prefix-caching says so "
                "outright)", config.model.model)
            config = config.replace(
                cache=replace(config.cache, enable_prefix_caching=False))
        if (config.model.block_diffusion is not None
                and config.cache.enable_prefix_caching):
            # a page is content only once every block in it is committed,
            # and a hit over a whole prompt would leave a prefill that
            # yields no token nothing to compute: unproven (ROADMAP R-M10)
            logger.info(
                "%s: generation by diffusion over blocks — the engine runs "
                "WITHOUT prefix hits (--no-enable-prefix-caching says so "
                "outright)", config.model.model)
            config = config.replace(
                cache=replace(config.cache, enable_prefix_caching=False))
        if config.cache.num_blocks is None:
            config = config.replace(
                cache=replace(
                    config.cache,
                    num_blocks=derive_num_blocks(
                        config.model,
                        config.cache,
                        config.parallel,
                        max_num_seqs=config.scheduler.max_num_seqs,
                        num_window_blocks=window_blocks,
                        state_slots=resolve_state_slots(config),
                    ),
                )
            )
        if config.model.latent_layers:
            _refuse_kv_tiers_for_latent_pool(config)
        self.config = config
        self.tokenizer = tokenizer or TokenizerWrapper(
            config.model.tokenizer or config.model.checkpoint
        )
        self.runner = ModelRunner(config, params=params, mesh=mesh)
        # identity of the weights this engine serves (needed BEFORE the KV
        # tiers: the remote store namespaces blocks by it) — see the
        # model_fingerprint comment below
        import hashlib

        # at-rest KV codec (docs/38-kv-quantization.md): blocks leaving
        # the pool for disk/remote/peer (and optionally the host ring)
        # travel as int4+scales or fp8, dequantized on adopt
        from .kv_codec import KVAtRestCodec

        self.kv_codec = KVAtRestCodec.from_config(config.cache)
        fp_parts = (
            config.model,
            config.seed,
            config.cache.resolved_kv_dtype(config.model.dtype),
        )
        if self.kv_codec.enabled:
            # the codec joins the fingerprint so a mixed-precision fleet
            # can never adopt bytes it would misdecode — appended only
            # when enabled, keeping existing codec-less disk caches and
            # remote namespaces valid across the upgrade
            fp_parts = (*fp_parts, self.kv_codec.spec)
        self.model_fingerprint = hashlib.sha256(
            repr(fp_parts).encode()
        ).hexdigest()[:16]
        # KV flow meter (docs/30-kv-flow-telemetry.md): ONE instance shared
        # by every tier object — host ring, disk tier, remote client,
        # device-path PD transfer — plus the scheduler's hydration
        # attribution.
        from .kv_flow import KVFlowMeter

        self.flow = KVFlowMeter()
        # flight recorder + thread-liveness registry (docs/37-flight-
        # recorder.md): created BEFORE every tier/thread owner so each
        # long-lived loop (remote writer, hydration fetcher, step thread,
        # bg compiles) can register its heartbeat at construction
        from .flightrec import (
            DEFAULT_BG_COMPILE_STALL_S,
            FlightRecorder,
            ThreadRegistry,
        )

        self.flightrec = FlightRecorder(capacity=config.flight_records)
        self.threads = ThreadRegistry()
        # the runner's build/h2d/enqueue/sync spans land on the same timeline
        self.runner.clock = self.flightrec
        # step quiescence lock: anything that mutates runner.kv_caches or
        # pool bookkeeping off the step thread (the device-collective peer
        # pull donates + reassigns the cache arrays) takes this around the
        # mutation. AsyncEngine adopts it as ITS step-loop lock, and sync
        # generate() steps under it, so holding it == no step in flight.
        self.step_lock = threading.Lock()
        self.host_tier = None
        self.remote_tier = None
        num_host_blocks = config.cache.num_host_blocks
        if config.cache.host_kv_gib > 0:
            from .memory import kv_block_bytes

            per_block = kv_block_bytes(
                config.model,
                config.cache.block_size,
                config.parallel.tensor_parallel_size,
                config.parallel.pipeline_parallel_size,
                kv_dtype=config.cache.resolved_kv_dtype(config.model.dtype),
            )
            num_host_blocks = max(
                num_host_blocks,
                int(config.cache.host_kv_gib * 2**30) // per_block,
            )
        if config.cache.remote_kv_url:
            from ..kvstore.client import RemoteKVTier

            self.remote_tier = RemoteKVTier(
                config.cache.remote_kv_url, self.model_fingerprint,
                flow=self.flow,
                heartbeat=self.threads.register("kv_writer"),
                codec=self.kv_codec if self.kv_codec.enabled else None,
            )
            # the remote tier stages through the host ring; give it a
            # minimal ring even when CPU offload wasn't asked for
            num_host_blocks = max(num_host_blocks, 16)
        disk_tier = None
        if config.cache.disk_kv_dir and config.cache.disk_kv_gib > 0:
            from .kv_disk_tier import DiskKVTier

            disk_tier = DiskKVTier(
                config.cache.disk_kv_dir,
                int(config.cache.disk_kv_gib * 2**30),
                fingerprint=self.model_fingerprint,
                flow=self.flow,
                codec=self.kv_codec if self.kv_codec.enabled else None,
            )
            num_host_blocks = max(num_host_blocks, 16)
        # peer-engine KV tier (docs/35-peer-kv-reuse.md): pull a prefix
        # resident only in ANOTHER engine's HBM/host tiers instead of
        # recomputing it. Opt-in (--kv-peer-fetch): the consuming side
        # needs a host ring to stage adoptions through, so enabling it
        # floors the ring like the remote tier does. The SERVING side
        # (/kv/peer_contains, /kv/peer_fetch) is always mounted — an
        # engine can be an owner without consuming the tier.
        self.peer_tier = None
        peer_enabled = (
            config.kv_peer_fetch and config.cache.enable_prefix_caching
        )
        if peer_enabled:
            num_host_blocks = max(num_host_blocks, 16)
        encode_ring = (
            self.kv_codec.enabled and config.cache.kv_at_rest_host_ring
        )
        if encode_ring and num_host_blocks > 0:
            # ring entries are held in wire form, so the same host-RAM
            # budget buys wire-ratio× more blocks — effective CPU-offload
            # capacity scales with the codec's compression
            ratio = self.kv_codec.wire_ratio(
                config.cache.resolved_kv_dtype(config.model.dtype)
            )
            num_host_blocks = int(num_host_blocks * ratio)
        if num_host_blocks > 0:
            from .kv_host_tier import HostKVTier

            self.host_tier = HostKVTier(
                num_host_blocks,
                self.runner.fetch_block,
                self.runner.upload_block,
                remote=self.remote_tier,
                upload_blocks=self.runner.upload_blocks,
                disk=disk_tier,
                flow=self.flow,
                codec=self.kv_codec if self.kv_codec.enabled else None,
                encode_ring=encode_ring,
            )
        if peer_enabled:
            # lookup/identity wiring mirrors the KV event publisher's
            # (engine/server.py): POD_IP/ENGINE_PORT is this engine's
            # cluster-visible identity, and the first KV_CONTROLLER_URL
            # subscriber doubles as the cluster-lookup service for owner
            # rediscovery (the router's x-kv-owner-hint skips it)
            import os

            from .kv_peer import PeerKVTier

            pod_ip = os.environ.get("POD_IP") or ""
            self_url = (
                f"http://{pod_ip}:{os.environ.get('ENGINE_PORT', '8000')}"
                if pod_ip else ""
            )
            subscribers = [
                u.strip()
                for u in (os.environ.get("KV_CONTROLLER_URL") or "").split(",")
                if u.strip()
            ]
            self.peer_tier = PeerKVTier(
                fingerprint=self.model_fingerprint,
                self_url=self_url,
                lookup_url=subscribers[0] if subscribers else "",
                timeout=config.kv_peer_fetch_timeout_s,
                flow=self.flow,
            )
            if config.kv_peer_transport in ("auto", "device"):
                # mesh-peer transport (docs/39-device-peer-kv.md): attach
                # this process's mesh identity so lookups/registrations
                # advertise it and /peer_lookup replies can negotiate the
                # device path. No identity (no KV_MESH_GROUP, or
                # jax.distributed uninitialized) degrades to HTTP.
                from .kv_device_transfer import device_transport_identity

                identity = device_transport_identity()
                if identity is None and config.kv_peer_transport == "device":
                    logger.warning(
                        "kv_peer_transport=device but no mesh identity "
                        "(KV_MESH_GROUP unset or jax.distributed not "
                        "initialized); peer pulls stay on HTTP"
                    )
                self.peer_tier.transport_identity = identity
        # compute-or-load hydration planner (docs/31-hydration-planner.md):
        # only engines with a rung BELOW the host ring (disk / remote /
        # peer) ever face the blocking-load-vs-recompute choice; everything
        # else keeps the legacy admission path untouched.
        self.hydrator = None
        if (
            config.kv_hydration != "sync"
            and config.cache.enable_prefix_caching
            and self.host_tier is not None
            and (
                disk_tier is not None
                or self.remote_tier is not None
                or self.peer_tier is not None
            )
        ):
            from .hydration import Hydrator

            device_pull_fn = None
            if (
                self.peer_tier is not None
                and self.peer_tier.transport_identity is not None
            ):
                device_pull_fn = self._device_peer_pull
            self.hydrator = Hydrator(
                mode=config.kv_hydration,
                chunk_blocks=config.kv_hydration_chunk_blocks,
                timeout_s=config.kv_hydration_timeout_s,
                flow=self.flow,
                signal_fn=lambda: self.hydration_signal(),
                host_tier=self.host_tier,
                peer=self.peer_tier,
                heartbeat=self.threads.register("hydration_fetch"),
                device_pull_fn=device_pull_fn,
            )
        self.scheduler = Scheduler(
            config.model, config.cache, config.scheduler,
            host_tier=self.host_tier,
            need_slot_mappings=config.parallel.sequence_parallel_size > 1,
            flow=self.flow,
            hydrator=self.hydrator,
            window_blocks=window_blocks,
        )
        if self.runner.kv_caches:
            # page geometry the remote-match path validates fetched blocks
            # against — same definition the KV import path enforces
            from .kv_transfer import engine_block_shape

            self.scheduler.pool.expected_block_shape = engine_block_shape(
                self.runner
            )
        # draft-model proposer (--speculative-config draft, docs/36): a
        # second, small ModelRunner whose paged KV lives in its OWN device
        # arrays but whose block ids come from the SHARED KVBlockPool via
        # the scratch namespace — one allocator, one byte budget, and a
        # draft page can never satisfy a prefix match or peer lookup
        # (never content-addressed). N-gram stays the zero-weight fallback.
        self.draft_runner = None
        sch = config.scheduler
        if sch.num_speculative_tokens > 0 and sch.speculative_method == "draft":
            import dataclasses as _dc

            from ..models.registry import resolve_model_config
            from .spec_decode import DraftModelProposer

            draft_model = resolve_model_config(
                sch.draft_model, max_model_len=config.model.max_model_len
            )
            if draft_model.vocab_size != config.model.vocab_size:
                raise ValueError(
                    f"draft model {sch.draft_model!r} vocab "
                    f"({draft_model.vocab_size}) differs from the target "
                    f"model's ({config.model.vocab_size}) — the proposer "
                    "contract is a shared tokenizer: a larger draft vocab "
                    "can propose ids the target's embedding cannot gather "
                    "(XLA clamps out-of-range gathers SILENTLY — garbage "
                    "KV, not an error), a smaller one cannot ingest every "
                    "target id at catch-up"
                )
            draft_cfg = EngineConfig(
                model=draft_model,
                # same block geometry so pool block ids map 1:1 onto the
                # draft arrays' page axis; no lower tiers — the draft's KV
                # is recompute-cheap scratch, never offloaded
                cache=_dc.replace(
                    config.cache, num_host_blocks=0, host_kv_gib=0.0,
                    disk_kv_dir="", disk_kv_gib=0.0, remote_kv_url="",
                ),
                # same bucket ladders: draft batches pad up through the
                # identical program cache, so draft-batch shapes can't
                # retrigger compilation mid-traffic. The draft itself never
                # runs a verify program.
                scheduler=_dc.replace(
                    config.scheduler, num_speculative_tokens=0,
                    draft_model="",
                ),
                # same seed: a random-weight draft that happens to share
                # the target's exact config reproduces its weights — the
                # acceptance≈1 fixture tests and benches lean on
                seed=config.seed,
            )
            self.draft_runner = ModelRunner(draft_cfg)
            self.scheduler.draft_proposer = DraftModelProposer(
                self.draft_runner,
                self.scheduler.pool,
                max_model_len=config.model.max_model_len,
            )
        # background-compile liveness: both runners' bg compile jobs beat
        # ONE "bg_compile" heartbeat (busy only while a compile runs — a
        # beat older than the generous threshold while busy is the "XLA
        # compiles forever" wedge, docs/37-flight-recorder.md)
        bg_hb = self.threads.register(
            "bg_compile", stall_after_s=DEFAULT_BG_COMPILE_STALL_S
        )
        self.runner.heartbeat = bg_hb
        if self.draft_runner is not None:
            self.draft_runner.heartbeat = bg_hb
        # XLA compile telemetry (docs/42-compile-telemetry.md): ONE watch
        # shared by both runners (the draft's program cache is the same
        # failure axis) — entries carry role="target"/"draft"
        from .compile_watch import CompileWatch

        self.compile_watch = CompileWatch(
            storm_threshold=config.compile_storm_threshold,
            storm_window_s=config.compile_storm_window_s,
            recorder=self.flightrec,
        )
        self.runner.compile_watch = self.compile_watch
        if self.draft_runner is not None:
            self.draft_runner.compile_watch = self.compile_watch
            self.draft_runner.compile_role = "draft"
        self._states: dict[str, _RequestState] = {}
        self._lora_slots: dict[str, int] = {}  # adapter name -> slot index
        self._lora_paths: dict[str, str] = {}  # adapter name -> source path
        # KV-chain salts per adapter (name, path) — see load_lora
        self._lora_salts: dict[str, int] = {}
        self._req_counter = itertools.count()
        self._prompt_tokens = 0
        self._generation_tokens = 0
        # admission-time shedding counters (the scheduler counts mid-queue/
        # mid-decode deadline expiry separately — stats() sums them)
        self.shed_requests = 0
        self.deadline_admission_rejects = 0
        # two-deep pipelined step loop: dispatch step N+1 against
        # speculatively-advanced scheduler state before step N's tokens
        # reach the host. Speculative decoding COMPOSES with it
        # (docs/36-speculative-decoding.md): verify dispatches are
        # in-flight work like decode windows, a verify CHAINS on an
        # in-flight verify (its fed proposals are host-known under full
        # acceptance; only the bonus token is spliced device-side), and a
        # partial acceptance is just another rollback.
        self._inflight: _InflightStep | None = None
        # saturation telemetry (docs/29-saturation-slo.md): per-resolved-
        # step occupancy / padding / MFU accounting. The goodput LEDGER
        # lives on the scheduler.
        self.meter = StepMeter(config.model, config.scheduler)
        # structured output (docs/41-structured-output.md): compiled-
        # grammar LRU, lazily built on the first constrained request (the
        # engine is the only layer holding both tokenizer and model vocab
        # size — padding tokens past the tokenizer's range lift to "never
        # admissible"), plus the terminal-outcome counters behind
        # tpu:structured_requests_total
        self._grammar_cache = None
        self._structured_outcomes = {"valid": 0, "invalid": 0, "fallback": 0}
        # model_fingerprint (computed above, before the KV tiers): same
        # config + same checkpoint (or same random seed) => same KV bytes
        # for same tokens. KV adoption (disaggregated prefill) refuses
        # mismatched senders, and the remote KV store namespaces blocks by
        # it — same-shape-different-weights KV would silently corrupt
        # attention. The pool storage dtype is part of the identity:
        # adopting e.g. fp8-quantized pages into an exact bf16 cache would
        # silently mark lossy KV as byte-identical to locally computed KV.

    # -- structured output -------------------------------------------------

    @property
    def timing(self) -> dict[str, float | int]:
        """Step-phase wall-time decomposition (/debug/timing → `engine`):
        the flight recorder's cumulative totals (flightrec.PHASES, the
        starvation counters, and the keys that predate them: dispatch_s =
        build + h2d + enqueue, overlap_s = host time that ran while a device
        step was in flight, step_wall_s = total step() wall, rollback_n =
        speculative steps discarded, prefill_chained_n = prefill chunks
        that had the next step enqueued behind them before their resolve,
        prefill_follow_n = chunks that followed a chunk while rows were
        decode-ready, round_chunks_max = the most chunks of one round,
        seat_held_by_pool_n = schedule() calls that left a seat empty with
        a request waiting because the pool could not hold it to its end)."""
        return self.flightrec.timing()

    @property
    def grammar_cache(self):
        """The engine's compiled-grammar LRU (grammar.GrammarCache),
        created on first use. The HTTP layer compiles specs through this
        so concurrent agent sessions sharing a schema share ONE automaton
        (and one set of device tables, keyed by grammar identity)."""
        if self._grammar_cache is None:
            from .grammar import GrammarCache

            self._grammar_cache = GrammarCache(
                self.tokenizer, self.config.model.vocab_size
            )
        return self._grammar_cache

    def count_structured(self, outcome: str) -> None:
        """Bump one tpu:structured_requests_total outcome — the engine
        counts terminal outcomes itself; the API layer calls this for
        requests that never reach the scheduler (compile-rejected =>
        invalid, constraints declined => fallback)."""
        self._structured_outcomes[outcome] = (
            self._structured_outcomes.get(outcome, 0) + 1
        )

    # -- request lifecycle -------------------------------------------------

    def add_request(
        self,
        request_id: str | None = None,
        prompt: str | None = None,
        prompt_token_ids: list[int] | None = None,
        sampling: SamplingParams | None = None,
        lora_name: str | None = None,
        deadline: float | None = None,
        tenant: TenantContext | None = None,
        kv_owner_hint: str | None = None,
    ) -> str:
        request_id = request_id or f"req-{next(self._req_counter)}"
        if prompt_token_ids is None:
            if prompt is None:
                raise ValueError("need prompt or prompt_token_ids")
            prompt_token_ids = self.tokenizer.encode(prompt)
        if lora_name is not None and lora_name not in self._lora_slots:
            # races with a concurrent unload land here too — a clear 4xx-able
            # error, not a KeyError 500
            raise ValueError(f"LoRA adapter {lora_name!r} is not loaded")
        if self.config.model.block_diffusion is not None:
            _refuse_request_for_block_diffusion(
                self.config.model.model, sampling)
        refused = self.config.model.refused_token_ids
        if refused and not set(refused).isdisjoint(prompt_token_ids):
            # image / video placeholder tokens: the tower that would fill
            # them in is not served (a ValueError is the server's 400)
            raise ValueError(
                f"{self.config.model.model}: the prompt holds image or "
                f"video placeholder tokens "
                f"{sorted(set(refused).intersection(prompt_token_ids))} — "
                "only the language model is served, text alone")
        tenant = tenant or TenantContext()
        req = Request(
            request_id=request_id,
            prompt_token_ids=list(prompt_token_ids),
            sampling=sampling or SamplingParams(),
            eos_token_id=self.tokenizer.eos_token_id,
            lora_index=self._lora_slots[lora_name] if lora_name else 0,
            lora_cache_salt=self._lora_salts[lora_name] if lora_name else 0,
            deadline=deadline,
            tenant_id=tenant.tenant_id,
            priority=tenant.priority,
            weight=tenant.weight,
            kv_owner_hint=kv_owner_hint,
        )
        if req.sampling.grammar is not None:
            from .grammar import GrammarState

            req.grammar = GrammarState(req.sampling.grammar)
        self.scheduler.add_request(req)
        self._states[request_id] = _RequestState(
            request=req, detok=IncrementalDetokenizer(self.tokenizer)
        )
        self._prompt_tokens += len(prompt_token_ids)
        return request_id

    def abort_request(self, request_id: str) -> bool:
        req = self.scheduler.abort_request(request_id)
        self._states.pop(request_id, None)
        return req is not None

    # -- LoRA adapters (reference contract: vLLM /v1/load_lora_adapter used
    #    by the LoRA controller, loraadapter_controller.go:582-611) ---------

    def load_lora(self, name: str, path: str) -> None:
        """Parse a PEFT adapter dir and install it into a free slot; serving
        `model=name` then computes base + (alpha/r)·B·A per request."""
        from ..models.lora_loader import load_lora_adapter

        if self.config.lora.max_loras == 0:
            raise RuntimeError(
                "LoRA is disabled (lora.max_loras=0); restart the engine "
                "with adapter slots to load adapters"
            )
        if name in self._lora_slots:
            raise ValueError(f"adapter {name!r} is already loaded")
        used = set(self._lora_slots.values())
        free = [
            s for s in range(1, self.config.lora.num_slots) if s not in used
        ]
        if not free:
            raise RuntimeError(
                f"all {self.config.lora.max_loras} adapter slots in use"
            )
        adapter = load_lora_adapter(path, self.config.model, self.config.lora)
        self.runner.install_lora(free[0], adapter)
        self._lora_slots[name] = free[0]
        self._lora_paths[name] = path
        # STABLE across engines serving the same adapter CONTENT — the LoRA
        # controller loads adapters under one name cluster-wide, and
        # cross-engine KV transfer needs the salted chains to line up, so
        # the seed is (name, file bytes) and deliberately NOT the local path
        # (per-node download dirs differ). The content digest also means
        # overwriting an adapter in place and reloading it can never
        # prefix-hit the old weights' cached KV. Chunked read: this runs
        # under the engine lock and adapters can be hundreds of MB
        import hashlib
        import os

        digest = hashlib.sha256(name.encode() + b"\0")
        sft = os.path.join(path, "adapter_model.safetensors")
        with open(sft, "rb") as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                digest.update(chunk)
        # 63 bits: chain_hash packs tuple entries as signed 8-byte ints
        self._lora_salts[name] = (
            int.from_bytes(digest.digest()[:8], "little") >> 1
        ) or 1

    def unload_lora(self, name: str) -> None:
        slot = self._lora_slots.get(name)
        if slot is None:
            raise KeyError(f"adapter {name!r} is not loaded")
        # an in-flight request would silently continue on zeroed (or, after a
        # slot-reusing load, a DIFFERENT adapter's) weights — refuse instead
        busy = [
            r.request_id
            for r in (*self.scheduler.running, *self.scheduler.waiting)
            if r.lora_index == slot
        ]
        if busy:
            raise RuntimeError(
                f"adapter {name!r} is serving request(s) {busy[:3]}; drain "
                "or abort them before unloading"
            )
        del self._lora_slots[name]
        self._lora_paths.pop(name, None)
        self._lora_salts.pop(name, None)
        self.runner.remove_lora(slot)

    def list_loras(self) -> list[str]:
        return sorted(self._lora_slots)

    @property
    def lora_adapters(self) -> dict[str, str]:
        """name → source path of loaded adapters (the single registry — the
        server and /v1/models read this view)."""
        return dict(self._lora_paths)

    def _cache_root(self, lora_name: str | None) -> int:
        """Chain root for lookups/exports: salted when the name is a loaded
        adapter (its KV differs from base KV), the pool root otherwise."""
        from .kv_cache import chain_hash

        salt = self._lora_salts.get(lora_name or "")
        if salt:
            return chain_hash(self.scheduler.pool.root_hash(), (salt,))
        return self.scheduler.pool.root_hash()

    def embed(
        self,
        inputs: list[str] | list[list[int]],
    ) -> tuple[list[list[float]], int]:
        """OpenAI /v1/embeddings backend: last-token pooled, L2-normalized
        final hidden states (how decoder-only embedding serving works in the
        reference's engines). Returns (vectors, total prompt tokens)."""
        import numbers

        if self.config.model.block_diffusion is not None:
            raise ValueError(
                f"{self.config.model.model}: /v1/embeddings is not built "
                "for generation by diffusion over blocks — the encode "
                "program masks causally, and the model attends whole blocks")
        rows: list[list[int]] = []
        vocab = self.config.model.vocab_size
        for x in inputs:
            if isinstance(x, str):
                rows.append(self.tokenizer.encode(x))
            elif isinstance(x, list) and all(
                isinstance(t, numbers.Integral) and not isinstance(t, bool)
                for t in x
            ):
                bad = [int(t) for t in x if not 0 <= t < vocab]
                if bad:
                    # JAX gathers CLAMP out-of-range ids — that would be a
                    # silent wrong-answer, not an error
                    raise ValueError(
                        f"token id(s) {bad[:3]} out of range [0, {vocab})"
                    )
                rows.append([int(t) for t in x])
            else:
                raise ValueError(
                    "each embedding input must be a string or a list of "
                    "token ids"
                )
        max_t = self.config.model.max_model_len
        for r in rows:
            if not r:
                raise ValueError("empty embedding input")
            if len(r) > max_t:
                raise ValueError(
                    f"embedding input of {len(r)} tokens exceeds "
                    f"max_model_len ({max_t})"
                )
        vectors = self.runner.embed(rows).tolist()
        return vectors, sum(len(r) for r in rows)

    def warmup(self, scope: str = "full") -> int:
        """Compile the serving program set BEFORE traffic: without this the
        first request into each shape bucket eats a 10-40s XLA compile while
        holding the engine lock (VERDICT r1 weak #7).

        scope="coarse": compile only the DOMINATING shape lattice — full
        rows × the largest chunk bucket, walking the context-width ladder to
        its top, plus every pow2 decode window at full batch/width. With
        the runner's pad-up fallback (model_runner._pick_prefill_shape), a
        coarse-warmed engine serves with ZERO compile stalls from boot:
        any finer program key pads up to a coarse program while the exact
        one compiles in the background. Minutes, not tens of minutes.

        scope="full": the coarse pass plus the fine ladder — every
        reachable prefill bucket × pow2 row count, every decode bucket ×
        window, the width ladder, and the logprobs/min_tokens static
        variants. Fallback is disabled for the duration so every wave
        compiles its exact program (deterministic steady-state perf;
        compiles land in the persistent XLA cache, so "full" costs its
        wall time once per model/bucket-set). Returns warmup passes run."""
        import numpy as np

        sched = self.config.scheduler
        cfg = self.config.model
        usable_tokens = (
            self.scheduler.pool.num_usable * self.config.cache.block_size
        )
        passes = 0

        def wave(
            rows: int, prompt_len: int, max_tokens: int,
            logprobs: int | None = None, min_tokens: int = 0,
            row_lens: list[int] | None = None,
            seed_base: int | None = None,
        ) -> None:
            nonlocal passes
            lens = row_lens if row_lens is not None else [prompt_len] * rows
            base = seed_base if seed_base is not None else 7000 + passes * 131
            prompts = [
                list(
                    np.random.RandomState(base + i).randint(
                        1, cfg.vocab_size, size=n
                    )
                )
                for i, n in enumerate(lens)
            ]
            self.generate(
                prompts,
                SamplingParams(max_tokens=max_tokens, temperature=0.0,
                               ignore_eos=True, logprobs=logprobs,
                               min_tokens=min_tokens),
            )
            passes += 1

        # every wave must compile its EXACT program — padding a warmup wave
        # up to an earlier coarse program would silently skip the compile
        # the wave exists for
        self.runner.fallback_enabled = False
        try:
            # -- coarse dominating pass (both scopes): AOT-compile the
            # dominating lattice directly — no tokens generated, no pool
            # capacity needed, and the TOP width program exists even when
            # the pool cannot physically hold max_num_seqs × max_model_len
            # (generate-based waves could never reach that shape)
            passes += self.runner.precompile_dominating()
            # ... and the gather that hands a window enqueued behind a
            # prefill chunk its rows' first tokens, per chunk row bucket
            self.runner.warm_chain_gathers()
            if scope == "coarse":
                logger.info("coarse warmup compiled %d programs", passes)
                return passes
            # -- fine ladder (scope="full") ---------------------------------
            longest_chunk = min(
                sched.max_num_batched_tokens, cfg.max_model_len - 1
            )
            prev_bucket = 0
            for t in sorted(sched.prefill_buckets):
                # bucket t is reachable iff some chunk length in
                # (prev_bucket, longest_chunk] pads up to it (bucket_for picks
                # the smallest bucket >= the chunk)
                if prev_bucket >= longest_chunk:
                    break
                prompt_len = min(t, longest_chunk)
                per_seq = prompt_len + sched.decode_window + 1
                rows = max(1, min(sched.max_num_seqs, usable_tokens // per_seq))
                wave(rows, prompt_len, 1)
                # row-COUNT buckets: the prefill program key includes the pow2-
                # padded row count, and production batches mix one long chunk
                # with many short residuals — 1..max_num_seqs rows all occur.
                # Missing these was the live-stack collapse mode: every new
                # (rows, bucket) pair stalled serving for a 30-60s compile
                # while queued decoders starved. One mixed-length wave per pow2
                # row count covers them: r rows that one dispatch holds and
                # whose program lands bucket t (the runner says which bucket
                # a dispatch lands and how long a row may be beside the
                # others): a lead row as long as (up to) 16-token residuals
                # and the token budget leave it.
                r = 1
                while r <= sched.max_num_seqs:
                    rest = [min(16, prompt_len // r)] * (r - 1)
                    lead = min(
                        prompt_len,
                        self.runner.prefill_row_room(rest, t),
                        sched.max_num_batched_tokens - sum(rest),
                    )
                    if (
                        r > prompt_len or lead <= 0
                        or self.runner.prefill_bucket([lead] + rest) != t
                    ):
                        r *= 2
                        continue  # no r-row dispatch lands this bucket
                    wave(r, lead, 1, row_lens=[lead] + rest)
                    r *= 2
                prev_bucket = t
            w = 1
            while w <= sched.decode_window:
                for b in sched.decode_buckets:
                    if b > sched.max_num_seqs:
                        continue  # unreachable batch bucket
                    per_seq = 8 + w + 2
                    rows = max(1, min(b, usable_tokens // per_seq))
                    if rows == b or b == min(sched.decode_buckets):
                        # prefill emits the FIRST output token, so max_tokens
                        # w+1 leaves exactly w for the fused window — hitting
                        # window program w, not round_up_pow2(w-1)
                        wave(rows, 8, w + 1)
                w *= 2
            # block-table WIDTH buckets: the (floored) pow2 width of the
            # batch's longest context is part of every program key
            # (model_runner._block_table_array). Without these waves, a long
            # conversation's first crossing of each width boundary stalls
            # serving for a 30-60s compile — the measured live-stack collapse
            # mode. One 1-row wave per width above the 64-block floor walks a
            # request's context up the ladder (chunked prefill compiles the
            # prefill widths on the way; the trailing window compiles the
            # decode width).
            bs_tok = self.config.cache.block_size
            max_w = self.runner.max_blocks
            floor_w = sched.width_floor_blocks  # ladder starts above the floor
            width = floor_w * 2
            widths = []
            while width < max_w:
                widths.append(width)
                width *= 2
            if max_w > floor_w and max_w not in widths:
                widths.append(max_w)
            prev_len = 0
            for w_blocks in widths:
                prompt_len = min(
                    w_blocks * bs_tok, cfg.max_model_len, usable_tokens
                ) - sched.decode_window - 2
                if prompt_len <= prev_len:
                    break  # achievable context saturated: nothing new compiles
                wave(1, prompt_len, sched.decode_window + 1)
                prev_len = prompt_len
            # logprobs variants (want_logprobs is a static jit arg -> separate
            # programs): warm the largest prefill bucket and every decode bucket
            # at the full window — the common production hit. Smaller windows'
            # logprob variants still compile lazily (warming the full cross
            # product would double warmup time for a rarely-mixed dimension).
            for extra in ({"logprobs": 0}, {"min_tokens": 1}):
                # largest reachable prefill bucket: the common production hit
                wave(1, min(sorted(sched.prefill_buckets)[-1], longest_chunk), 1,
                     **extra)
                for b in sched.decode_buckets:
                    if b > sched.max_num_seqs:
                        continue
                    per_seq = 8 + sched.decode_window + 2
                    rows = max(1, min(b, usable_tokens // per_seq))
                    if rows == b or b == min(sched.decode_buckets):
                        wave(rows, 8, sched.decode_window + 1, **extra)
            logger.info("warmup ran %d bucket passes", passes)
            return passes
        finally:
            self.runner.fallback_enabled = True

    def kv_export(
        self,
        text: str | None = None,
        token_ids: list[int] | None = None,
        lora_name: str | None = None,
    ):
        """Disaggregated prefill: export the prompt's resident KV blocks
        (engine/kv_transfer.py). Called on the prefill engine."""
        from .kv_transfer import KVTransfer

        if token_ids is None:
            token_ids = self.tokenizer.encode(text or "")
        return KVTransfer(self.scheduler.pool, self.runner).export_prompt(
            list(token_ids), parent=self._cache_root(lora_name)
        )

    def kv_export_lazy(
        self,
        text: str | None = None,
        token_ids: list[int] | None = None,
        lora_name: str | None = None,
    ):
        """Streaming-sender variant of kv_export: dispatches the device→host
        copies and returns (hashes, per-block device slices) — resolution to
        numpy happens off the engine lock, per block, as frames go out."""
        from .kv_transfer import KVTransfer

        if token_ids is None:
            token_ids = self.tokenizer.encode(text or "")
        return KVTransfer(
            self.scheduler.pool, self.runner
        ).export_prompt_lazy(list(token_ids), parent=self._cache_root(lora_name))

    def kv_import(self, hashes, blocks, fingerprint: str = "") -> int:
        """Disaggregated prefill: adopt shipped KV blocks into this
        engine's pool. Called on the decode engine. Refuses KV from a sender
        with different weights (fingerprint mismatch)."""
        from .kv_transfer import KVTransfer

        if fingerprint != self.model_fingerprint:
            # empty counts as mismatch too: the wire format always carries a
            # fingerprint, so a missing one means a foreign/corrupt sender
            raise ValueError(
                f"KV fingerprint mismatch: sender {fingerprint!r} != this "
                f"engine {self.model_fingerprint!r} — refusing foreign KV"
            )
        return KVTransfer(self.scheduler.pool, self.runner).import_blocks(
            hashes, blocks
        )

    def kv_peer_replicate(self, owner: str, hashes: list[int]) -> int:
        """Flash-crowd replication target half (docs/39-device-peer-kv.md):
        fetch `hashes` from `owner` over the HTTP peer path and adopt them
        as parked evictable blocks. The wire fetch runs on the caller's
        thread OFF the step lock (seconds of wire time must not stall
        decode); only the adoption quiesces the step loop."""
        import numpy as np

        if self.peer_tier is None:
            return 0
        got = self.peer_tier.fetch_run(owner, list(hashes))
        if not got:
            return 0
        from .kv_codec import decode_block
        from .kv_transfer import KVTransfer

        blocks = np.stack([decode_block(g) for g in got])
        with self.step_lock:
            return KVTransfer(
                self.scheduler.pool, self.runner
            ).import_blocks(list(hashes)[: len(got)], blocks)

    def kv_lookup(self, text: str | None = None,
                  token_ids: list[int] | None = None,
                  lora_name: str | None = None) -> int:
        """Longest KV prefix (tokens) resident across HBM + host tiers —
        the probe behind KV-aware routing (reference: LMCache controller
        LookupMsg, routing_logic.py:264-344). `lora_name` (the request's
        model field) salts the chain for adapter traffic so routing doesn't
        chase base-model KV a LoRA request can't reuse."""
        if token_ids is None:
            token_ids = self.tokenizer.encode(text or "")
        return self.scheduler.pool.match_length(
            list(token_ids), parent=self._cache_root(lora_name)
        )

    def kv_peer_contains(self, hashes: list[int]) -> int:
        """Consecutive count of `hashes` this engine can serve from its
        local tiers — the /kv/peer_contains probe another engine's
        hydration planner issues before trusting the cluster index
        (docs/35-peer-kv-reuse.md). Safe WITHOUT the engine lock: pure
        GIL-atomic containment, and the probe is staleness-tolerant by
        design (fetch/adoption re-validate)."""
        from .kv_transfer import KVTransfer

        return KVTransfer(self.scheduler.pool, self.runner).contains_hashes(
            hashes
        )

    def kv_peer_export(self, hashes: list[int]):
        """(served, entries) for the consecutive locally-resident prefix
        of `hashes` — the /kv/peer_fetch sender half. Caller holds the
        engine lock; entries resolve to numpy OFF the lock (see
        KVTransfer.export_hashes)."""
        from .kv_transfer import KVTransfer

        return KVTransfer(self.scheduler.pool, self.runner).export_hashes(
            hashes
        )

    def kv_bytes_per_token(self) -> float:
        """Analytic KV bytes per token as they'd cross a migration link
        (block_bytes / block_size, divided by the at-rest codec's wire
        ratio when one is configured) — the tpu:kv_bytes_per_token gauge
        the router's route-vs-migrate scoring prices transfers with. WIRE
        bytes on purpose: a migrate under int4-at-rest moves codec
        payloads, so pricing logical bytes would overstate its cost and
        bias the router toward routing."""
        from .memory import kv_block_bytes

        dtype_name = self.config.cache.resolved_kv_dtype(
            self.config.model.dtype
        )
        logical = kv_block_bytes(
            self.config.model,
            self.config.cache.block_size,
            self.config.parallel.tensor_parallel_size,
            self.config.parallel.pipeline_parallel_size,
            kv_dtype=dtype_name,
        ) / self.config.cache.block_size
        return logical / self.kv_codec.wire_ratio(dtype_name)

    def has_request(self, request_id: str) -> bool:
        return request_id in self._states

    def validate_new_request(
        self, prompt_token_ids: list[int], lora_name: str | None = None
    ) -> None:
        """Admission checks that need NO engine lock (static config + GIL-
        atomic dict reads) — the async server validates before queueing so
        rejections stay synchronous 4xx errors even though admission itself
        is deferred to the step thread (the submit path must never contend
        with a running device step)."""
        n = len(prompt_token_ids)
        if n >= self.config.model.max_model_len:
            raise ValueError(
                f"prompt of {n} tokens exceeds "
                f"max_model_len={self.config.model.max_model_len}"
            )
        if (
            self.scheduler._blocks_needed(n + 1)
            > self.scheduler.pool.num_usable
        ):
            raise ValueError(
                f"prompt of {n} tokens cannot fit the KV pool "
                f"({self.scheduler.pool.num_usable} blocks of "
                f"{self.scheduler.block_size})"
            )
        if lora_name is not None and lora_name not in self._lora_slots:
            raise ValueError(f"LoRA adapter {lora_name!r} is not loaded")

    # -- admission control / load shedding ---------------------------------

    def observed_tokens_per_s(self) -> float:
        """Generation throughput — the denominator for Retry-After and
        queue-wait estimates. Decode-phase timing, NOT total step wall:
        warmup/lazy XLA compiles land in step_wall_s and would poison the
        estimate for the process's whole life (a 3 tok/s "observed rate"
        right after boot made the admission gate shed everything). 0.0
        before the first decode resolves (callers treat as "unknown")."""
        timing = self.timing
        dec_s = float(timing["decode_s"])
        dec_t = float(timing["decode_tokens"])
        if dec_s > 0.0 and dec_t > 0:
            return dec_t / dec_s
        return 0.0

    def queue_depth(
        self, exclude_prefix: str | None = None
    ) -> tuple[int, int]:
        """(waiting requests, waiting prompt tokens still to prefill), read
        without the engine lock. Unlike len(), ITERATING a deque the step
        thread is mutating raises RuntimeError — retry the snapshot a few
        times and degrade to a request-count-only answer rather than turn
        an admission check or health probe into a 500. exclude_prefix
        drops a request's own sibling choices from the count (see
        check_admission)."""
        from .async_engine import _same_request

        for _ in range(5):
            waiting = self.scheduler.waiting
            try:
                snap = list(waiting)
            except RuntimeError:  # deque mutated during iteration
                continue
            if exclude_prefix is not None:
                snap = [
                    r for r in snap
                    if not _same_request(r.request_id, exclude_prefix)
                ]
            return len(snap), sum(
                max(0, r.prefill_target - r.num_computed_tokens)
                for r in snap
            )
        return len(self.scheduler.waiting), 0

    def estimate_retry_after_s(self, queued_tokens: int) -> float:
        """Seconds until the current backlog plausibly clears, from observed
        decode throughput — the Retry-After a 429 carries. Clamped to
        [1, 60]: never tell a client "retry now" while shedding, never park
        it for minutes on a stale estimate."""
        tps = self.observed_tokens_per_s()
        if tps <= 0.0:
            return 1.0
        return min(60.0, max(1.0, queued_tokens / tps))

    def check_admission(
        self,
        n_new_tokens: int,
        deadline: float | None = None,
        extra_waiting: int = 0,
        extra_tokens: int = 0,
        record: bool = True,
        exclude_prefix: str | None = None,
        tenant: TenantContext | None = None,
        evict: bool = False,
    ) -> None:
        """Load-shedding + deadline gate, run lock-free at submit time
        (extra_* carries the async server's not-yet-admitted pending queue).
        Raises EngineOverloadedError (→ 429 + Retry-After) when the bounded
        waiting queue / queued-token watermark is full, and
        DeadlineExceededError (→ 503) when the request would queue past its
        deadline — both strictly cheaper answered at the door than after
        burning prefill steps on a reply nobody will read. record=False is
        the would-this-shed probe (/ready, /health) — the shed counters
        must count refused REQUESTS, not probe polls. exclude_prefix keeps
        an n>1 request's own sibling choices out of its count — a request
        must never shed against itself."""
        cfg = self.config.scheduler
        n_waiting, queued_tokens = self.queue_depth(exclude_prefix)
        n_waiting += extra_waiting
        queued_tokens += extra_tokens

        def _shed(msg: str):
            if record:
                self.shed_requests += 1
                if tenant is not None:
                    self.scheduler.accounting.inc(tenant.tenant_id, "shed")
            raise EngineOverloadedError(
                msg, self.estimate_retry_after_s(queued_tokens)
            )

        # multi-tenant QoS: shedding is lowest-priority-first. A
        # higher-priority arrival at a full queue evicts the newest
        # strictly-lower-priority WAITING request (applied by the step
        # thread) instead of being refused itself. The victim is only
        # CLAIMED (mark_shed_victim) after every other refusal below has
        # passed — a token-watermark or deadline refusal of this arrival
        # must not also cost an already-queued request its slot — and only
        # by the submit-time check (evict=True); the pre-SSE check and
        # probe polls just peek, so one request can't evict twice.
        needs_eviction = False
        if cfg.max_waiting_requests > 0 and n_waiting >= cfg.max_waiting_requests:
            if tenant is not None and self.scheduler.has_shed_victim(
                tenant.priority
            ):
                needs_eviction = True
            else:
                _shed(
                    f"engine overloaded: {n_waiting} requests waiting "
                    f"(max_waiting_requests={cfg.max_waiting_requests})"
                )
        if cfg.max_queued_tokens > 0 and queued_tokens >= cfg.max_queued_tokens:
            _shed(
                f"engine overloaded: {queued_tokens} prompt tokens queued "
                f"(max_queued_tokens={cfg.max_queued_tokens})"
            )
        if deadline is not None:
            import time as _time

            now = _time.monotonic()
            if now > deadline:
                if record:
                    self.deadline_admission_rejects += 1
                raise DeadlineExceededError(
                    "request deadline already expired at admission"
                )
            tps = self.observed_tokens_per_s()
            if tps > 0.0:
                est_wait = (queued_tokens + n_new_tokens) / tps
                if now + est_wait > deadline:
                    if record:
                        self.deadline_admission_rejects += 1
                    raise DeadlineExceededError(
                        f"request would queue ~{est_wait:.1f}s past its "
                        "deadline; shed at admission"
                    )
        if needs_eviction and evict and record:
            # every other refusal passed: claim the lower-priority victim
            # now. The peek above and this mark race the step thread, so
            # the victim may have left the queue — then this arrival sheds
            # after all.
            if not self.scheduler.mark_shed_victim(tenant.priority):
                _shed(
                    f"engine overloaded: {n_waiting} requests waiting "
                    f"(max_waiting_requests={cfg.max_waiting_requests})"
                )

    def has_unfinished(self) -> bool:
        # a step still in flight is unfinished work even when every row of
        # it was aborted meanwhile: one more step() resolves it, so nothing
        # stays open behind an idle loop (the watchdog's unresolved-step
        # cursor, the handle's device arrays)
        return self.scheduler.has_unfinished() or self._inflight is not None

    # -- stepping ----------------------------------------------------------

    def step(self) -> list[RequestOutput]:
        """Schedule + execute one device step; returns per-request deltas.

        This drives a TWO-DEEP PIPELINE: the step dispatched on the
        previous call — a decode window, a verify, or a PREFILL CHUNK — is
        still executing on device while this call schedules and dispatches
        the next one against speculatively-advanced request state, and only
        then resolves the previous step (one batched D2H sync),
        postprocesses it, and reconciles. Decode inputs chain device-side
        from the in-flight step's output: the last column of a window's
        token matrix, a verify's bonus tokens, or — for a row whose prompt
        the in-flight chunk ends — the FIRST token that chunk samples, so
        the row joins the very window enqueued behind its chunk and the
        device's queue never empties at a prefill. When the reconcile shows
        the speculation was wrong (a mid-window stop token, max-tokens
        finish, stop-string hit, abort, or a first token that finished its
        row), the just-dispatched step is discarded and rolled back, so the
        emitted token streams are bitwise identical to the serial reference
        loop's (testing/serial_loop.py). A chunk follows a chunk in flight
        the same way (the scheduler's round: a row starts its next chunk
        where the one in flight ends). Where nothing can be enqueued behind
        the in-flight step (a priority stall, a row parked at a fetch, rows
        that sit a step out), the next step waits for its resolve and is
        dispatched in the same call. Outputs returned by
        one call therefore belong to the step dispatched on the PREVIOUS
        call (one step of latency)."""
        outputs: list[RequestOutput] = []
        inflight, self._inflight = self._inflight, None
        try:
            with self.flightrec.wall("step_wall_s"):
                self._step_pipelined_inner(inflight, outputs)
            return outputs
        except Exception:
            if inflight is not None and not inflight.handle.resolved:
                # the fault hit before the previous step was resolved (e.g.
                # a transient dispatch failure) — put it back so its
                # results aren't stranded; the next step (or the async
                # server's abort-all recovery) reconciles it
                self._inflight = inflight
            raise

    def _schedule(self, outputs: list[RequestOutput], inflight=None):
        """schedule() as the `sched` phase. Requests the scheduler
        terminated outside a step (impossible-fit re-admission abort,
        expired deadline) still need a terminal output or streaming
        clients would hang forever."""
        with self.flightrec.span("sched"):
            work = self.scheduler.schedule(inflight=inflight)
            if self.scheduler.seat_held_by_pool:
                # a seat stays empty although a request waits for it: the
                # pool could not carry that request to its end
                self.flightrec.count("seat_held_by_pool_n")
            for req in self.scheduler.take_finished_externally():
                outputs.append(
                    self._make_output(
                        req, [], "", self._finish_reason(req) or "abort"
                    )
                )
        return work

    def _dispatch(self, work, prev: StepHandle | None = None):
        """Build, copy and enqueue one program: the `build` phase, with the
        runner's `h2d` and `enqueue` inside it. The flight record is cut
        when the enqueue has returned — the device has work from there."""
        kind = _work_kind(work)
        with self.flightrec.span("build", kind=kind):
            handle = self.runner.execute_async(work, prev=prev)
            self.flightrec.poll()
            # rows advance speculatively by their fed length (full
            # acceptance; a chunk that ends its prompt by the one token it
            # samples): the next step can chain off this handle
            self.scheduler.begin_speculative(work)
        handle.seq, handle.kind = self._rec_dispatch(work, kind, handle), kind
        return handle

    def _resolve(self, work, handle: StepHandle,
                 outputs: list[RequestOutput]) -> None:
        """One dispatch's single host sync (`sync`, inside the handle),
        then its results reconciled into the scheduler and emitted
        (`post`). prefill_s / decode_s: the host's wait per dispatch."""
        rec, kind = self.flightrec, handle.kind
        key = "prefill" if kind == "prefill" else "decode"
        with rec.span("post", handle.seq, kind):
            with rec.wall(key + "_s"):
                rows = handle.resolve()
                rec.resolve(handle.seq, handle.bd_passes)
            rec.count(key + "_n")
            # layers of each mixer kind the dispatch ran: the model's count
            # a pass over the layers, a decode window `window` passes
            passes = work.window if kind == "decode" else 1
            for name, layers in (
                    ("mla_layers_n", self.config.model.latent_layers),
                    ("kda_layers_n", self.config.model.linear_layers),
                    ("ssd_layers_n", self.config.model.ssm_layers),
                    ("page_and_state_layers_n", tuple(
                        i for i in self.config.model.state_layers
                        if self.config.model.layer_specs[i].holds_pages))):
                if layers:
                    rec.count(name, passes * len(layers))
            self._count_selected(work, kind, passes)
            if kind == "prefill" and self.runner.prefill_uses_kernel:
                rec.count("prefill_kernel_n")
            self.scheduler.end_speculative(work)
            results = self.scheduler.postprocess(work, rows)
            rec.poll()  # `post` of a chunk is long: see FlightRecorder.poll
            if kind == "prefill":
                if work.round_chunk > 1:
                    # a chunk that followed a chunk although rows were
                    # decode-ready (the scheduler's round); the longest round
                    rec.count("prefill_follow_n")
                    rec.peak("round_chunks_max", work.round_chunk)
                # chunk tokens PROCESSED (mid-prompt chunks emit none)
                rec.count(
                    "prefill_tokens", sum(len(t) for t in work.token_ids)
                )
                # ... and the positions its program computed for them: the
                # packed axis with its padding (rows x bucket where the
                # chunk is row-major); tokens / positions = the fill share
                rec.count("prefill_positions", handle.positions)
                # pages a layer's chunk write wrote, and those it read back
                # to merge first (none where the write kernel runs)
                rec.count("kv_write_pages_n", handle.kv_write_pages[0])
                rec.count("kv_write_read_pages_n", handle.kv_write_pages[1])
                # (query, key) pairs its attention covers: a chunk's query
                # at position p sees keys 0..p (chunks are contiguous)
                rec.count("attn_prefill_pairs_n", sum(
                    len(p) * int(p[0]) + len(p) * (len(p) + 1) // 2
                    for p in work.positions if len(p)))
                # ... and the keys it must read at least once (its context)
                rec.count("attn_prefill_keys_n", sum(
                    int(p[-1]) + 1 for p in work.positions if len(p)))
                window = self.config.model.max_window
                if window:
                    # a model with window layers: the pairs a WINDOW layer's
                    # attention covers (a query at p sees min(p + 1, window)
                    # keys), and the keys a chunk's attention must read at
                    # least once in a window layer (the chunk and the window
                    # before it)
                    rec.count("attn_prefill_window_pairs_n", sum(
                        _window_pairs(int(p[0]), len(p), window)
                        for p in work.positions if len(p)))
                    rec.count("attn_prefill_window_keys_n", sum(
                        min(int(p[-1]) + 1, len(p) + window - 1)
                        for p in work.positions if len(p)))
                # (a recurrence's counters: what its kernels' work is
                # computed from, benchmarks/chip/costs_kda.py, costs_ssd.py)
                for name, layers in (
                        ("kda", self.config.model.linear_layers),
                        ("ssd", self.config.model.ssm_layers)):
                    if layers:
                        rec.count(name + "_prefill_tokens_n",
                                  sum(len(t) for t in work.token_ids))
                        rec.count(name + "_prefill_segments_n",
                                  sum(1 for t in work.token_ids if len(t)))
                if self.config.model.state_layers:
                    rec.count("state_slots_zeroed_n", sum(
                        1 for p in work.positions
                        if len(p) and int(p[0]) == 0))
                    # positions a row had computed before a preemption
                    # took its pages AND its state slot
                    rec.count("state_recomputed_tokens_n", sum(
                        max(0, min(end, req.state_recompute_until)
                            - (end - len(toks)))
                        for req, toks, end in zip(
                            work.requests, work.token_ids,
                            work.context_lens)))
                self._meter_prefill(work, handle.positions)
            else:
                # tokens actually ACCEPTED — exact for both the fused
                # window (mid-window stops discard the tail) and
                # spec-decode verify (1..k+1 accepted per row)
                accepted = sum(len(t) for _, t in results)
                rec.count("decode_tokens", accepted)
                for name, layers in (
                        ("kda", self.config.model.linear_layers),
                        ("ssd", self.config.model.ssm_layers)):
                    if layers and kind == "decode":
                        rec.count(name + "_decode_rows_n",
                                  len(work.requests) * work.window)
                self._meter_decode(work, accepted)
            rec.poll()
            self._emit_results(results, handle.logprob_rows, outputs)

    def _count_selected(self, work, kind: str, passes: int) -> None:
        """metrics_contract.DEBUG_TIMING_DSA_COUNTS of one dispatch, from
        the lengths the host knows (a model without learned sparse
        attention counts none): a query at position p sees p + 1 keys, an
        indexer scores them all, and every layer that attends under a
        selection attends min(p + 1, index_topk) of them."""
        model = self.config.model
        if not model.selecting_layers:
            return
        rec, k = self.flightrec, model.index_topk
        full, every = len(model.index_layers), len(model.selecting_layers)
        rec.count("dsa_full_layers_n", passes * full)
        rec.count("dsa_shared_layers_n", passes * (every - full))
        if kind == "prefill":
            spans = [(int(p[0]), len(p)) for p in work.positions if len(p)]
        else:
            spans = [(int(p), work.window) for p in work.positions]
            rec.count("dsa_decode_rows_n", len(spans) * work.window)
        def keys_seen(first, n):
            return n * first + n * (n + 1) // 2

        visible = sum(keys_seen(first, n) for first, n in spans)
        # (the first `under` queries of a span see no more than k keys)
        selected = sum(
            keys_seen(first, under) + (n - under) * k
            for first, n in spans
            for under in (max(0, min(n, k - first)),))
        rec.count("dsa_index_pairs_n", full * visible)
        rec.count("dsa_visible_keys_n", every * visible)
        rec.count("dsa_selected_keys_n", every * selected)
        if kind != "prefill":
            # (the decode windows' part of the two, which the benchmark's
            # rooflines set against the windows' device time)
            rec.count("dsa_decode_index_pairs_n", full * visible)
            rec.count("dsa_decode_selected_keys_n", every * selected)

    def _state_stats(self) -> dict:
        """The state slots' part of a stats snapshot (empty for a model no
        layer of which holds state): a slot in use holds its bytes in the
        bank that is current, and the other bank's are the price of an
        exact rollback — both count."""
        pool = self.scheduler.pool
        cfg = self.config
        if pool.state is None and not cfg.model.latent_layers:
            return {}
        from .memory import (
            index_block_bytes, kv_block_bytes, state_slot_bytes)

        held = pool.num_usable - pool.num_free
        kv_dtype = cfg.cache.resolved_kv_dtype(cfg.model.dtype)
        pages = held * kv_block_bytes(
            cfg.model, cfg.cache.block_size, kv_dtype=kv_dtype)
        # (a model's pages are all latents or none is; the index keys of
        # the layers that own an indexer are counted apart)
        index = held * index_block_bytes(
            cfg.model, cfg.cache.block_size, kv_dtype)
        out = ({"latent_bytes": pages - index, "index_bytes": index}
               if cfg.model.latent_layers else {})
        if pool.state is None:
            return out
        used = pool.state.num_in_use
        state = 2 * used * state_slot_bytes(cfg.model)
        return {**out, "state_bytes": state, "state_slots_in_use": used,
                "state_share": state / (state + pages) if state else 0.0}

    def _rollback(self, nxt: _InflightStep) -> None:
        self.scheduler.rollback_speculative(nxt.work)
        nxt.handle.discard()
        self.flightrec.discard(nxt.handle.seq)

    def _step_pipelined_inner(
        self,
        inflight: _InflightStep | None,
        outputs: list[RequestOutput],
    ) -> None:
        rec = self.flightrec
        nxt: _InflightStep | None = None
        # everything up to the resolve runs while the previous step is
        # still executing on device — the overlap the pipeline buys
        with rec.wall("overlap_s") if inflight else contextlib.nullcontext():
            work = self._schedule(
                outputs, inflight=inflight.work if inflight else None
            )
            if work is not None:
                # a verify or a prefill chunk pipelines exactly like a
                # decode window: enqueued behind the in-flight step, kept
                # in flight over the next call
                nxt = _InflightStep(work=work, handle=self._dispatch(
                    work, prev=inflight.handle if inflight else None
                ))
        if inflight is not None:
            try:
                self._resolve(inflight.work, inflight.handle, outputs)
            except Exception:
                # the previous step's resolve faulted AFTER nxt was
                # dispatched: roll nxt back too, or its speculative advance
                # would leak (rows stuck one window ahead with their
                # sampled tokens silently dropped)
                if nxt is not None:
                    self._rollback(nxt)
                raise
            if nxt is not None and inflight.handle.kind == "prefill":
                # counted where prefill_n is, so it never runs ahead of it
                rec.count("prefill_chained_n")
            if nxt is not None and not self.scheduler.speculation_valid(
                nxt.work
            ):
                # the reconciled state moved out from under the speculative
                # dispatch: discard it wholesale. The serial re-dispatch on
                # the next call reproduces the exact token stream (RNG is
                # rewound by discard()).
                self._rollback(nxt)
                rec.count("rollback_n")
                nxt = None
            elif work is None:
                # nothing could be enqueued behind the in-flight step: a
                # priority stall (a higher-priority waiter blocked by
                # in-flight victims), or rows that must see it resolved (a
                # hydration plan's, a constrained row behind its chunk; a
                # chunk FOLLOWS a chunk in flight without this: the
                # scheduler plans it at the speculative position). It is
                # resolved now — re-schedule IN THIS CALL, so the
                # preempt-and-admit, or the next step, happens a full loop
                # round-trip sooner.
                work = self._schedule(outputs)
                if work is not None:
                    nxt = _InflightStep(
                        work=work, handle=self._dispatch(work))
        if work is None and inflight is None and (
            self.scheduler.hydration_parked()
        ):
            # the only schedulable work is parked at a pending hydration
            # fetch: yield a beat instead of busy-spinning step() — the
            # spin would contend the GIL with the very fetcher thread
            # whose landing we're waiting on, inflating the fetch latency
            # the planner priced
            time.sleep(0.001)
        self._inflight = nxt
        with rec.span("post"):
            self._drop_finished(outputs)

    # -- flight recorder (docs/37-flight-recorder.md) ----------------------

    def _rec_dispatch(self, work, kind: str, handle: StepHandle) -> int:
        """One black-box record per device dispatch: batch shape + phase,
        the scheduler's decision summary, and queue/pool depths — what the
        engine was doing right before it (maybe) stopped doing anything.
        Also advances the dispatch/resolve cursor the watchdog's
        unresolved-step detection and the starvation accounting key off,
        and hands the recorder the program's `is_ready` to watch: the polls
        at the phase boundaries see the chip run dry behind it."""
        sched = self.scheduler
        if kind == "decode":
            window = work.window
            tokens = work.window * len(work.requests)
        else:
            window = 0
            tokens = sum(len(t) for t in work.token_ids)
        return self.flightrec.dispatch(
            kind, rows=len(work.requests), tokens=tokens, window=window,
            waiting=sched.num_waiting, running=sched.num_running,
            pool_usage=sched.pool.usage_perc,
            ready=getattr(handle.tokens, "is_ready", None),
        )

    # -- saturation & goodput telemetry (docs/29-saturation-slo.md) --------

    def _meter_decode(self, work, accepted: int) -> None:
        """Record one resolved decode/verify dispatch with the meter. The
        context sum feeds the attention term of the FLOP estimate: row i's
        window tokens attend ~positions[i] + j each."""
        if isinstance(work, VerifyWork):
            rows = len(work.requests)
            fed = sum(len(t) for t in work.token_ids)
            self.meter.record_decode(
                rows=rows,
                window=max(1, -(-fed // max(1, rows))),
                accepted_tokens=accepted,
                sum_context=sum(
                    sum(p) + len(p) for p in work.positions
                ),
            )
            return
        rows = len(work.requests)
        w = work.window
        self.meter.record_decode(
            rows=rows,
            window=w,
            accepted_tokens=accepted,
            sum_context=w * sum(work.positions) + rows * (w * (w + 1) // 2),
        )

    def _meter_prefill(self, work: PrefillWork, positions: int) -> None:
        chunk_tokens = sum(len(t) for t in work.token_ids)
        # each chunk token attends ~its absolute position: per row the sum
        # over [start, end) is len × (start + end) / 2
        sum_ctx = 0
        for ids, end in zip(work.token_ids, work.context_lens):
            n = len(ids)
            sum_ctx += n * (2 * end - n + 1) // 2
        self.meter.record_prefill(
            chunk_tokens=chunk_tokens,
            sum_context=sum_ctx,
            positions=positions,
        )

    def _kv_tier_usage(self) -> dict[str, float]:
        """Per-tier occupancy for tpu:engine_kv_tier_usage_perc. Remote is
        the store-reported fill fraction piggybacked on PUT acks
        (kvstore/client.py last_usage_perc) — 0 until the first ack."""
        tiers = {
            "hbm": self.scheduler.pool.usage_perc,
            "host": 0.0,
            "disk": 0.0,
            "remote": 0.0,
        }
        if self.host_tier is not None:
            tiers["host"] = self.host_tier.usage_perc
            disk = self.host_tier.disk
            if disk is not None and disk.max_bytes > 0:
                tiers["disk"] = min(
                    1.0, disk.total_bytes / disk.max_bytes
                )
        if self.remote_tier is not None:
            tiers["remote"] = getattr(
                self.remote_tier, "last_usage_perc", 0.0
            )
        return tiers

    def goodput_balance(self) -> dict:
        """Ledger balance check (delegates to the scheduler — the single
        definition of "live requests"): sampled == delivered + wasted +
        pending. At quiescence pending is 0, so delivered + wasted ==
        sampled EXACTLY — the tests assert `balanced`."""
        return self.scheduler.goodput_balance()

    def hydration_signal(self) -> dict:
        """The compute-or-load planner's inputs (ROADMAP item 3,
        docs/30-kv-flow-telemetry.md): measured fetch bandwidth per tier
        alongside the analytic prefill FLOP/s. The planner's per-chunk
        decision is `block_bytes / fetch_bw` (load cost) vs
        `block_size_tokens × flops_per_token / prefill_flops_per_s`
        (recompute cost) — both denominators MEASURED here, not guessed.
        prefill_flops_per_s is the StepMeter's achieved-FLOP/s EWMA (0
        before any step resolves — fall back to a peak_flops_per_s
        fraction until traffic warms it); bandwidths are 0 for tiers that
        have never moved bytes."""
        from .kv_flow import TRANSFER_TIERS
        from .memory import kv_block_bytes
        from .saturation import attn_flops_per_token_ctx, matmul_params

        cfg = self.config.model

        bw = self.flow.bandwidth_bytes_per_s()
        meas = self.flow.bandwidth_measured()
        sat = self.meter.snapshot()
        block_bytes = kv_block_bytes(
            self.config.model,
            self.config.cache.block_size,
            self.config.parallel.tensor_parallel_size,
            self.config.parallel.pipeline_parallel_size,
            kv_dtype=self.config.cache.resolved_kv_dtype(
                self.config.model.dtype
            ),
        )
        # per-tier WIRE bytes per block: the encoded tiers (disk/remote/
        # peer — and the host ring under kv_at_rest_host_ring) move codec
        # payloads, so the planner must price fetches at compressed size;
        # this is what shifts recompute→load crossovers under int4
        wire = block_bytes
        if self.kv_codec.enabled:
            wire = self.kv_codec.wire_block_bytes(
                block_bytes,
                self.config.cache.resolved_kv_dtype(self.config.model.dtype),
            )
        ring_encoded = (
            self.host_tier is not None
            and getattr(self.host_tier, "encode_ring", False)
        )
        return {
            "fetch_bandwidth_bytes_per_s": {
                tier: bw[(tier, "in")] for tier in TRANSFER_TIERS
            },
            # sample-floor state per tier (TierBandwidth.measured): the
            # planner never trusts an estimate built from a single tiny
            # transfer — unmeasured tiers fall back (auto: sync load;
            # planner mode: recompute)
            "fetch_bandwidth_measured": {
                tier: meas[(tier, "in")] for tier in TRANSFER_TIERS
            },
            "store_bandwidth_bytes_per_s": {
                tier: bw[(tier, "out")] for tier in TRANSFER_TIERS
            },
            "prefill_flops_per_s": sat["achieved_flops_per_s"],
            "peak_flops_per_s": sat["peak_flops_per_s"],
            "flops_per_token": 2.0 * matmul_params(cfg),
            # attention score/value FLOPs per (token, attended-context-
            # position) pair — the same coefficient the StepMeter's
            # analytic model charges (saturation.step_flops), so the
            # planner prices long-context recompute with the context term
            # the achieved-FLOP/s denominator was measured against
            "attn_flops_per_token_ctx": attn_flops_per_token_ctx(cfg),
            "block_bytes": block_bytes,
            "wire_block_bytes": {
                "hbm": block_bytes,
                "host": wire if ring_encoded else block_bytes,
                "disk": wire,
                "remote": wire,
                "peer": wire,
                # the device path moves pool-precision pages over ICI/DCN
                # collectives — the at-rest codec never touches it, so a
                # device fetch prices at full logical block bytes
                # (compression ratio pinned at 1.0, docs/39)
                "device": block_bytes,
            },
            "block_size_tokens": self.config.cache.block_size,
        }

    def _device_peer_pull(self, owner: str, hashes: list[int]) -> int:
        """Pull a hash run from `owner` over device collectives (the
        Hydrator's device fetch lane, docs/39-device-peer-kv.md). Runs on
        the FETCHER thread: the HTTP trigger (POST /kv/peer_device_pull,
        split send/read so both sides join the collective concurrently)
        happens OUTSIDE the step lock — a stalled owner stalls only this
        thread, named "hydration_fetch" by the watchdog — and only the
        collective itself quiesces the step loop under `step_lock`.
        Returns run hashes resident after the pull (parked at refcount 0
        for adoption), 0 on any failure — which records a 0-byte
        device/in sample so the fault is visible in
        tpu:kv_transfer_seconds{tier="device"}."""
        import http.client
        import json as _json
        from urllib.parse import urlsplit

        from .kv_device_transfer import pull_kv_device_crossproc

        t0 = time.perf_counter()
        conn = None
        try:
            u = urlsplit(owner)
            body = _json.dumps(
                {"hashes": [int(h) for h in hashes]}
            ).encode()
            conn = http.client.HTTPConnection(
                u.hostname, u.port or 80,
                timeout=max(5.0, self.config.kv_peer_fetch_timeout_s),
            )
            # split trigger: send the full request, DON'T read the reply
            # yet — the owner parses and enters the collective while we
            # enter ours below; the reply lands after both sides finish
            conn.putrequest("POST", "/kv/peer_device_pull")
            conn.putheader("Content-Type", "application/json")
            conn.putheader("Content-Length", str(len(body)))
            conn.endheaders()
            conn.send(body)
        except Exception:
            # trigger never reached the owner: no collective exists on
            # either side — record the honest 0-byte failure sample
            logger.exception("device peer pull trigger to %s failed", owner)
            self.flow.record(
                "device", "in", 0, 0, time.perf_counter() - t0
            )
            if conn is not None:
                conn.close()
            return 0
        try:
            with self.step_lock:
                n = pull_kv_device_crossproc(self, False, list(hashes))
        except Exception:
            # the cooperative program aborts BOTH sides (fingerprint
            # allgather / go-no-go barrier); post-barrier failures
            # metered inside the transfer, pre-barrier ones here
            logger.exception("device peer pull from %s faulted", owner)
            self.flow.record(
                "device", "in", 0, 0, time.perf_counter() - t0
            )
            n = 0
        try:
            resp = conn.getresponse()
            resp.read()
            if n and resp.status != 200:
                logger.warning(
                    "device peer pull: owner %s answered %d after a "
                    "locally-successful transfer", owner, resp.status,
                )
        except Exception:  # noqa: BLE001 — the bytes already landed
            logger.warning(
                "device peer pull: reply read from %s failed", owner
            )
        finally:
            conn.close()
        return n

    def _emit_results(
        self, results, lp_rows, outputs: list[RequestOutput]
    ) -> None:
        for row_i, (req, toks) in enumerate(results):
            if not toks:  # mid-prompt prefill chunk: progress, no tokens
                continue
            new_lp = None
            if lp_rows is not None and req.sampling.logprobs is not None:
                # accepted tokens are a prefix of the dispatched row
                new_lp = lp_rows[row_i][: len(toks)]
            self._generation_tokens += len(toks)
            if req.first_token_time is None:
                req.first_token_time = time.monotonic()
            state = self._states.get(req.request_id)
            new_text = state.detok.push(toks) if state and state.detok else ""

            if state is not None and req.sampling.stop:
                state.pending_text += new_text
                # vLLM's stop checker skips ALL stop conditions below the
                # min_tokens floor — stop STRINGS included, not just the
                # token-id conditions the scheduler masks. Text still goes
                # through pending_text so a straddling match fires once the
                # floor is crossed.
                below_min = (
                    len(req.output_token_ids) < req.sampling.min_tokens
                )
                hit = (
                    None
                    if below_min
                    else self._find_stop(state.pending_text, req.sampling.stop)
                )
                if hit is not None:
                    emit = state.pending_text[:hit]
                    state.text += emit
                    state.pending_text = ""
                    if not req.status.finished:
                        self.scheduler.finish_request(
                            req, RequestStatus.FINISHED_STOPPED
                        )
                    outputs.append(
                        self._make_output(req, toks, emit, "stop", new_lp)
                    )
                    continue
                if req.status.finished:  # eos/length: flush held-back text
                    emit = state.pending_text
                    state.text += emit
                    state.pending_text = ""
                else:  # hold back text that could be a stop-string prefix
                    emit = self._emittable(state, req.sampling.stop)
                outputs.append(
                    self._make_output(
                        req, toks, emit, self._finish_reason(req), new_lp
                    )
                )
                continue

            if state is not None:
                state.text += new_text
            outputs.append(
                self._make_output(
                    req, toks, new_text, self._finish_reason(req), new_lp
                )
            )

    def _drop_finished(self, outputs: list[RequestOutput]) -> None:
        for out in outputs:
            if out.finished:
                self._states.pop(out.request_id, None)

    def _make_output(
        self, req: Request, toks: list[int], text: str,
        finish_reason: str | None, new_logprobs=None,
    ) -> RequestOutput:
        out = RequestOutput(
            new_logprobs=new_logprobs,
            request_id=req.request_id,
            new_token_ids=toks,
            finished=req.status.finished,
            finish_reason=finish_reason,
            num_prompt_tokens=req.num_prompt_tokens,
            num_output_tokens=len(req.output_token_ids),
            num_cached_prompt_tokens=req.num_cached_prompt_tokens,
        )
        out.text_delta = text
        if req.spec_window is not None:
            # this step resolved a verify window: hand its (proposed,
            # accepted, proposer) to the tracing spine's decode_window
            # event and clear the stamp (one window, one event)
            out.spec_window, req.spec_window = req.spec_window, None
        if out.finished:
            # lifecycle stamps for the tracing spine's phase attribution —
            # carried on the terminal output because the request state is
            # reaped (_drop_finished) before the HTTP layer sees it.
            # Rollback-safe by construction: outputs only ever describe
            # RESOLVED steps (a discarded speculative dispatch never
            # reaches postprocess, so no stamp can come from it).
            out.phase_times = {
                "arrival": req.arrival_time,
                "first_seat": req.first_seat_time,
                "first_token": req.first_token_time,
                "finish": req.finish_time or time.monotonic(),
                "prompt_tokens": req.num_prompt_tokens,
                "output_tokens": len(req.output_token_ids),
                "cached_prompt_tokens": req.num_cached_prompt_tokens,
                "preemptions": req.num_preemptions,
            }
            # hydration-source partition for the trace timeline's
            # kv_hydration event (docs/30-kv-flow-telemetry.md); None for
            # requests that never got a seat
            out.hydration = req.hydration
            # planner per-chunk outcomes (docs/31-hydration-planner.md):
            # the kv_hydration event's "plan" view
            out.hydration_chunks = req.hydration_outcomes
            # structured outcome (docs/41-structured-output.md), terminal
            # only: valid iff the automaton sits in an accepting state (the
            # body parses against the schema by construction); invalid when
            # generation was cut mid-structure (length cap / abort /
            # stop-string); counted once per constrained request
            if req.sampling.grammar is not None and req.grammar is not None:
                req.grammar.sync(req.output_token_ids)
                out.structured_outcome = (
                    "valid" if req.grammar.accepting else "invalid"
                )
                self.count_structured(out.structured_outcome)
            # mid-traffic compile stalls this request blocked on, for the
            # trace timeline (docs/42-compile-telemetry.md)
            out.compile_stalls = req.compile_stalls
        return out

    @staticmethod
    def _finish_reason(req: Request) -> str | None:
        return {
            RequestStatus.FINISHED_STOPPED: "stop",
            RequestStatus.FINISHED_LENGTH: "length",
            RequestStatus.FINISHED_ABORTED: "abort",
            RequestStatus.FINISHED_DEADLINE: "deadline",
            RequestStatus.FINISHED_SHED: "shed",
        }.get(req.status)

    @staticmethod
    def _find_stop(text: str, stops: tuple[str, ...]) -> int | None:
        """Earliest match position across ALL stop strings (not first-in-tuple:
        a later-listed stop can occur earlier in the stream)."""
        best: int | None = None
        for s in stops:
            idx = text.find(s)
            if idx != -1 and (best is None or idx < best):
                best = idx
        return best

    @staticmethod
    def _emittable(state: _RequestState, stops: tuple[str, ...]) -> str:
        """Emit pending text minus the longest tail that prefixes a stop."""
        pending = state.pending_text
        hold = 0
        for s in stops:
            for k in range(min(len(s) - 1, len(pending)), 0, -1):
                if s.startswith(pending[-k:]):
                    hold = max(hold, k)
                    break
        emit = pending[: len(pending) - hold] if hold else pending
        state.pending_text = pending[len(pending) - hold :] if hold else ""
        state.text += emit
        return emit

    # -- convenience (offline / bench) ------------------------------------

    def generate(
        self,
        prompts: list[str] | list[list[int]],
        sampling: SamplingParams,
        lora_name: str | None = None,
        kv_owner_hint: str | None = None,
    ) -> list[dict]:
        """Blocking batch generation; returns [{request_id, token_ids, text}]."""
        ids = []
        for p in prompts:
            if isinstance(p, str):
                ids.append(
                    self.add_request(
                        prompt=p, sampling=sampling, lora_name=lora_name,
                        kv_owner_hint=kv_owner_hint,
                    )
                )
            else:
                ids.append(
                    self.add_request(
                        prompt_token_ids=p, sampling=sampling,
                        lora_name=lora_name, kv_owner_hint=kv_owner_hint,
                    )
                )
        done: dict[str, dict] = {
            i: {"request_id": i, "token_ids": [], "text": ""} for i in ids
        }
        while self.has_unfinished():
            # step under the quiescence lock so a concurrent device-path
            # peer pull (Hydrator fetcher thread) never races the step's
            # kv_caches donation — same discipline as AsyncEngine._lock
            with self.step_lock:
                outs = self.step()
            if not outs:
                # nothing progressed (every request parked on hydration):
                # yield the lock for real — a tight reacquire loop can
                # starve the fetcher thread whose device-path pull needs
                # the same lock to run its collective
                time.sleep(0.001)
            for out in outs:
                d = done.get(out.request_id)
                if d is None:
                    continue
                d["token_ids"].extend(out.new_token_ids)
                d["text"] += out.text_delta
                if out.finished:
                    d["finish_reason"] = out.finish_reason
        return [done[i] for i in ids]

    # -- stats / control ---------------------------------------------------

    def _overlap_frac(self) -> float:
        timing = self.timing
        wall = timing["step_wall_s"]
        return timing["overlap_s"] / wall if wall > 0 else 0.0

    def stats(self) -> EngineStatsSnapshot:
        pool = self.scheduler.pool
        tenants, waits = self.scheduler.accounting.snapshot(drain_waits=True)
        saturation = self.meter.snapshot()
        saturation["goodput"] = self.scheduler.ledger.snapshot()
        saturation["kv_tiers"] = self._kv_tier_usage()
        disk = self.host_tier.disk if self.host_tier is not None else None
        return EngineStatsSnapshot(
            saturation=saturation,
            kv_flow=self.flow.snapshot(),
            disk_kv_stores=disk.stats.stores if disk is not None else 0,
            disk_kv_loads=disk.stats.loads if disk is not None else 0,
            kv_bytes_per_token=self.kv_bytes_per_token(),
            num_requests_running=self.scheduler.num_running,
            num_requests_waiting=self.scheduler.num_waiting,
            kv_usage_perc=pool.fullest_usage_perc,
            kv_group_usage=pool.group_usage,
            kv_window_share=self.scheduler.window_share(),
            kv_window_pages_given_back=(
                pool.window.given_back_total if pool.window else 0),
            **self._state_stats(),
            block_diffusion={
                key: n for key, n in self.flightrec.timing().items()
                if key in mc.DEBUG_TIMING_BD_COUNTS},
            prefix_cache_hit_rate=pool.stats.hit_rate,
            prefix_cache_hits=pool.stats.hits,
            prefix_cache_queries=pool.stats.queries,
            num_preemptions=self.scheduler.total_preemptions,
            # queue evictions ARE load shedding (the victim got a 429-
            # shaped refusal, just after queueing instead of at the door)
            requests_shed=self.shed_requests + self.scheduler.shed_evictions,
            tenants=tenants,
            tenant_queue_waits=waits,
            requests_deadline_expired=(
                self.deadline_admission_rejects
                + self.scheduler.deadline_expired_total
            ),
            step_overlap_frac=self._overlap_frac(),
            spec_draft_tokens=self.scheduler.spec_proposed_tokens,
            spec_accepted_tokens=self.scheduler.spec_accepted_tokens,
            spec_proposed_by=dict(self.scheduler.spec_proposed_by),
            spec_accepted_by=dict(self.scheduler.spec_accepted_by),
            generation_tokens=self._generation_tokens,
            prompt_tokens=self._prompt_tokens,
            host_kv_usage_perc=(
                self.host_tier.usage_perc if self.host_tier else 0.0
            ),
            host_kv_offloads=(
                self.host_tier.stats.offloads if self.host_tier else 0
            ),
            host_kv_reloads=(
                self.host_tier.stats.reloads if self.host_tier else 0
            ),
            remote_kv_stores=(
                self.remote_tier.stats.stores if self.remote_tier else 0
            ),
            remote_kv_fetched_blocks=(
                self.remote_tier.stats.fetched_blocks
                if self.remote_tier else 0
            ),
            structured_outcomes=dict(self._structured_outcomes),
            grammar_build_times=(
                self._grammar_cache.drain_build_times()
                if self._grammar_cache is not None
                else []
            ),
            compile=self.compile_watch.stats_snapshot(),
        )

    @property
    def is_sleeping(self) -> bool:
        return self.runner.is_sleeping

    def sleep(self, level: int = 1) -> None:
        if self.has_unfinished():  # a step still in flight included
            raise RuntimeError("cannot sleep with unfinished requests")
        self.runner.sleep(level)
        # the device pool is dropped; its content-addressed hashes would
        # otherwise match new requests against zeroed pages after wake
        self.scheduler.pool.clear_prefix_cache()

    def wake(self) -> None:
        self.runner.wake()
