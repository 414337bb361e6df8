"""OpenAI-compatible HTTP server for the TPU engine (aiohttp.web).

Implements the exact surface the reference stack's router and operator expect
from an engine pod (SURVEY §7.1): /v1/chat/completions, /v1/completions,
/v1/models, /metrics, /health, /sleep, /wake_up, /is_sleeping,
/v1/load_lora_adapter, /v1/unload_lora_adapter, /tokenize, /detokenize,
/version (main_router.py:50-246; service_discovery.py model scrape;
loraadapter_controller.go:582-611).

Run: python -m vllm_production_stack_tpu.engine.server --model tiny-llama
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

import numpy as np
from aiohttp import web
from pydantic import ValidationError

from .. import __version__
from ..fleet import SessionStickinessAudit
from ..models.registry import resolve_model_config
from ..qos import tenant_from_headers
from ..utils.logging import init_logger
from .async_engine import AsyncEngine, EngineDrainingError, EngineSleepingError
from .config import (
    CacheConfig,
    EngineConfig,
    LoRAConfig,
    ParallelConfig,
    SchedulerConfig,
)
from .engine import (
    DeadlineExceededError,
    EngineOverloadedError,
    LLMEngine,
)
from ..tracing import TraceStore, mono_to_epoch
from .flightrec import PostmortemDumper, Watchdog
from .kv_peer import MAX_PEER_RUN_BLOCKS, peer_hint_from_headers
from .metrics import EngineMetrics, OPENMETRICS_CONTENT_TYPE, wants_openmetrics
from .protocol import (
    ChatCompletionRequest,
    CompletionRequest,
    EmbeddingRequest,
    ErrorResponse,
    ModelCard,
    ModelList,
    RerankRequest,
    ScoreRequest,
    random_id,
    usage,
)

logger = init_logger(__name__)

DEFAULT_MAX_TOKENS = 256
# n (parallel sampling) cap: each choice is its own engine request (the
# prefix cache dedups the shared prompt), so the cost model is the same as
# the scheduler's per-request admission — the cap just bounds fan-out
MAX_N_CHOICES = 8


def error(status: int, message: str, type_: str = "invalid_request_error",
          headers: dict | None = None):
    return web.json_response(
        ErrorResponse(message=message, type=type_, code=status).model_dump(),
        status=status,
        headers=headers,
    )


# relative time budget in ms, carried router → engine (clock-skew safe: the
# router injects/forwards it, each hop converts to its own monotonic clock)
DEADLINE_HEADER = "x-request-deadline-ms"


def deadline_from_headers(headers) -> float | None:
    """Absolute time.monotonic() deadline from the x-request-deadline-ms
    header, or None. Malformed values are ignored (a bad client header must
    not 500 the request — the deadline is an optimization, not input)."""
    raw = headers.get(DEADLINE_HEADER)
    if not raw:
        return None
    try:
        ms = float(raw)
    except (TypeError, ValueError):
        return None
    if ms <= 0:
        return None
    return time.monotonic() + ms / 1000.0


def _kv_subscriber_urls() -> list[str]:
    """KV_CONTROLLER_URL parsed as a comma-separated subscriber list: the
    KV controller, embedded-index router replicas, or any mix — the KV
    event publisher fans batches out to all of them and registration runs
    against each (docs/34-fleet-routing.md)."""
    import os

    raw = os.environ.get("KV_CONTROLLER_URL") or ""
    return [u.strip() for u in raw.split(",") if u.strip()]


class _StreamUnsupported(Exception):
    """Sender has no /kv/export_stream (older engine) — use the npz hop."""


class EngineServer:
    def __init__(self, engine: LLMEngine, served_model_name: str | None = None,
                 drain_timeout_s: float = 30.0, request_tracing: bool = True,
                 trace_buffer: int = 256, watchdog: bool = True,
                 watchdog_interval_s: float = 1.0,
                 watchdog_stall_s: float = 120.0, postmortem_dir: str = "",
                 pool_role: str = ""):
        self.engine = engine
        self.async_engine = AsyncEngine(engine)
        self.model_name = served_model_name or engine.config.model.model
        self.metrics = EngineMetrics(self.model_name)
        # disaggregated pool role (docs/40-pool-rebalancing.md): a RUNTIME
        # property, seeded from --pool-role and flipped by POST /role. The
        # engine is the authority — it advertises the role on /metrics
        # (tpu:pool_role), /health, and controller registration; the
        # router and rebalancer FOLLOW it. "" = not in a disaggregated
        # deployment.
        self.pool_role: str | None = pool_role or None
        self.metrics.set_pool_role(self.pool_role)
        # serializes POST /role flips against each other and the drain path
        self._role_lock = asyncio.Lock()
        # request-tracing spine (docs/28-request-tracing.md): per-request
        # span timelines joined to the router's trace via the inbound
        # traceparent header, served by /debug/requests. Disabled
        # (--request-tracing false) it degrades to the NULL_TRACE no-op
        # path; the tpu:request_* histograms are observed either way.
        self.traces = TraceStore(
            capacity=trace_buffer, enabled=request_tracing,
            service="tpu-engine",
        )
        # on-demand xprof capture (/debug/profile/start|stop): the dir of
        # the live jax.profiler trace, None when not capturing
        self._profile_dir: str | None = None
        # ... and what the last capture came to (engine/capture_summary.py),
        # or {"error", "dir"}: POST /debug/profile/stop's reply, GET
        # /debug/profile/summary, `device.last_capture` of /debug/programs
        self._last_capture: dict | None = None
        self._session = None  # lazy outbound ClientSession (kv_pull)
        self.kv_event_publisher = None  # started when KV_CONTROLLER_URL set
        self._tok_repr_cache: dict[int, tuple[str, list[int]]] = {}
        self._start_time = time.time()
        # graceful drain (SIGTERM / POST /drain): admissions stop, in-flight
        # streams finish (bounded by drain_timeout_s), the KV event log is
        # flushed and the engine deregisters from its controller
        self.drain_timeout_s = drain_timeout_s
        self._drain_task: asyncio.Task | None = None
        self._exit_task: asyncio.Task | None = None
        self._drained = asyncio.Event()
        # OpenAI system_fingerprint: identifies the serving configuration
        # whose outputs a seed reproduces — our model fingerprint (weights
        # + seed + kv dtype) is exactly that identity
        self.system_fingerprint = "fp_" + engine.model_fingerprint[:12]
        # session-stickiness audit (docs/32-fleet-telemetry.md): counts
        # consistent-hash affinity breaks from the router-stamped
        # x-session-sticky-* headers. self_url (the same POD_IP:ENGINE_PORT
        # identity the KV event publisher advertises) arms the
        # non_owner_delivery detection; without it owner_changed still works.
        self.stickiness = SessionStickinessAudit(
            self_url=self._advertised_url()
        )
        # flight recorder / watchdog / postmortems (docs/37-flight-
        # recorder.md): the dumper writes the redacted black box on
        # watchdog trip, SIGQUIT, fatal step-thread death, and POST
        # /debug/postmortem; the watchdog turns heartbeat silence and
        # never-resolved dispatches into a named stall that flips /ready
        # (never /health — restarting a wedged engine destroys the
        # evidence this layer exists to capture)
        # the knob is parsed after the engine registered its loops —
        # non-explicit heartbeats (step, fetcher, writer) follow it
        engine.threads.set_default_stall_after_s(watchdog_stall_s)
        self.postmortems = PostmortemDumper(
            out_dir=postmortem_dir,
            recorder=engine.flightrec,
            registry=engine.threads,
            context_fn=self._postmortem_context,
        )
        self.watchdog: Watchdog | None = None
        if watchdog:
            self.watchdog = Watchdog(
                engine.threads,
                recorder=engine.flightrec,
                interval_s=watchdog_interval_s,
                stall_after_s=watchdog_stall_s,
                on_stall=lambda report: self.postmortems.dump(
                    "watchdog", json.dumps(report.get("findings", []))
                ),
            )
        # a fatally wedged step loop dumps its own black box on the way
        # out — the dying thread's stack is the one that matters
        self.async_engine.on_fatal = lambda e: self.postmortems.dump(
            "fatal_step_error", f"{type(e).__name__}: {e}"
        )

    @staticmethod
    def _advertised_url() -> str | None:
        """This engine's cluster-visible base URL (http://POD_IP:ENGINE_PORT
        — the identity used for KV controller registration), or None
        outside a deployment that sets the downward-API env."""
        import os

        pod_ip = os.environ.get("POD_IP")
        if not pod_ip:
            return None
        return f"http://{pod_ip}:{os.environ.get('ENGINE_PORT', '8000')}"

    @property
    def lora_adapters(self) -> dict[str, str]:
        """Loaded adapters (name → path). The ENGINE is the single registry —
        a server-side mirror desyncs the moment a load/unload half-fails."""
        return self.engine.lora_adapters

    # -- app wiring --------------------------------------------------------

    def _refuse_for_latent_pool(self, handler):
        if not self.engine.config.model.latent_layers:
            return handler

        async def refused(request: web.Request) -> web.Response:
            return web.json_response({"error": {
                "message": (
                    f"{request.path}: KV page transfer is not implemented "
                    f"for {self.engine.config.model.model}, whose pool holds "
                    "one latent a token (multi-head latent attention), not "
                    "K and V pages"),
                "type": "not_implemented"}}, status=501)

        return refused

    def build_app(self) -> web.Application:
        app = web.Application(client_max_size=64 * 1024 * 1024)
        r = app.router
        r.add_post("/v1/chat/completions", self.chat_completions)
        r.add_post("/v1/completions", self.completions)
        r.add_post("/v1/embeddings", self.embeddings)
        r.add_post("/v1/score", self.score)
        r.add_post("/v1/rerank", self.rerank)
        r.add_get("/v1/models", self.list_models)
        r.add_get("/health", self.health)
        r.add_get("/ready", self.ready)
        r.add_post("/drain", self.drain)
        r.add_post("/role", self.set_role)
        r.add_get("/metrics", self.metrics_endpoint)
        r.add_get("/debug", self.debug_index)
        r.add_get("/debug/timing", self.debug_timing)
        r.add_get("/debug/hydration", self.debug_hydration)
        r.add_get("/debug/requests", self.debug_requests)
        r.add_get("/debug/flight", self.debug_flight)
        r.add_get("/debug/programs", self.debug_programs)
        r.add_post("/debug/postmortem", self.debug_postmortem)
        r.add_post("/debug/profile/start", self.debug_profile_start)
        r.add_post("/debug/profile/stop", self.debug_profile_stop)
        r.add_get("/debug/profile/summary", self.debug_profile_summary)
        r.add_post("/sleep", self.sleep)
        r.add_post("/wake_up", self.wake_up)
        r.add_get("/is_sleeping", self.is_sleeping)
        r.add_post("/v1/load_lora_adapter", self.load_lora_adapter)
        r.add_post("/v1/unload_lora_adapter", self.unload_lora_adapter)
        r.add_post("/kv/lookup", self.kv_lookup)
        r.add_post("/kv/peer_contains", self.kv_peer_contains)
        # the endpoints that move KV pages between engines ship (2, kvH, D)
        # pages: an engine whose pool holds latents refuses them, one
        # sentence, instead of mis-sizing a frame
        moves = self._refuse_for_latent_pool
        r.add_post("/kv/peer_fetch", moves(self.kv_peer_fetch))
        r.add_post("/kv/peer_device_pull", moves(self.kv_peer_device_pull))
        r.add_post("/kv/peer_replicate", moves(self.kv_peer_replicate))
        r.add_post("/kv/replicated", self.kv_replicated)
        r.add_post("/kv/export", moves(self.kv_export))
        r.add_post("/kv/export_stream", moves(self.kv_export_stream))
        r.add_post("/kv/import", moves(self.kv_import))
        r.add_post("/kv/pull", moves(self.kv_pull))
        r.add_post("/tokenize", self.tokenize)
        r.add_post("/detokenize", self.detokenize)
        r.add_get("/version", self.version)
        app.on_startup.append(self._on_startup)
        app.on_cleanup.append(self._on_cleanup)
        return app

    async def _on_startup(self, app: web.Application) -> None:
        self.async_engine.start(asyncio.get_running_loop())
        await self._register_with_kv_controller("/register")
        self._start_kv_event_publisher()
        self._install_signal_handlers()
        if self.watchdog is not None:
            self.watchdog.start()

    def _install_signal_handlers(self) -> None:
        """SIGTERM = graceful drain, then exit (k8s pod termination: preStop
        POSTs /drain first, the kubelet's SIGTERM follows; a bare SIGTERM
        without preStop gets the same drain). Replaces aiohttp's default
        immediate-GracefulExit handler; no-op where signals aren't available
        (non-main thread — the aiohttp TestServer harness)."""
        import signal

        try:
            loop = asyncio.get_running_loop()
            loop.add_signal_handler(
                signal.SIGTERM, self._begin_drain, True
            )
        except (NotImplementedError, RuntimeError, ValueError):
            pass
        # SIGQUIT = dump a postmortem and KEEP RUNNING (replacing the
        # default core-dump-and-die): the operator's "what is this engine
        # doing right now" signal, file-shaped instead of stderr-shaped.
        # The dump walks every thread stack and writes a file, so it runs
        # in the executor — blocking the event loop with it would stall
        # every in-flight stream and inflate the very liveness signals
        # being debugged (same discipline as POST /debug/postmortem).
        try:
            loop = asyncio.get_running_loop()
            loop.add_signal_handler(
                signal.SIGQUIT,
                lambda: loop.run_in_executor(
                    None, self.postmortems.dump, "sigquit",
                    "operator SIGQUIT",
                ),
            )
        except (NotImplementedError, RuntimeError, ValueError, AttributeError):
            pass

    def _start_kv_event_publisher(self) -> None:
        """Push-based cluster KV index: publish this pool's KV events to
        every subscriber named by KV_CONTROLLER_URL (comma-separated — the
        KV controller, embedded-index router replicas, or both) so /lookup
        never has to probe this engine per request (engine/kv_events.py).
        Each subscriber keeps its own cursor/resync state, so a cold router
        replica heals through its own snapshot while the rest stream
        batches (docs/34-fleet-routing.md)."""
        import os

        subscribers = _kv_subscriber_urls()
        pod_ip = os.environ.get("POD_IP")
        pool = self.engine.scheduler.pool
        if not subscribers or not pod_ip or pool.events is None:
            return
        from .kv_events import DEFAULT_FLUSH_INTERVAL_S, KVEventPublisher

        port = os.environ.get("ENGINE_PORT", "8000")
        interval_s = float(
            os.environ.get("KV_EVENTS_FLUSH_S", DEFAULT_FLUSH_INTERVAL_S)
        )
        self.kv_event_publisher = KVEventPublisher(
            subscribers,
            f"http://{pod_ip}:{port}",
            pool.events,
            self.async_engine.kv_events_snapshot,
            pool.block_size,
            self._client_session,
            interval_s=interval_s,
            headers=self._kv_controller_headers(),
            # liveness: one beat per publish round; the threshold rides
            # well above the per-POST send timeout so one slow subscriber
            # round isn't a wedge, a HELD one is
            heartbeat=self.engine.threads.register(
                "kv_event_publisher",
                stall_after_s=max(30.0, 20 * interval_s),
            ),
        )
        self.kv_event_publisher.start()
        logger.info("KV event publisher -> %s (flush every %.2fs)",
                    ", ".join(subscribers),
                    self.kv_event_publisher.interval_s)

    @staticmethod
    def _kv_controller_headers() -> dict:
        """Bearer key for a keyed KV-event subscriber (a router running with
        --api-key protects /kv/events and /register|/deregister)."""
        import os

        key = os.environ.get("KV_CONTROLLER_API_KEY")
        return {"Authorization": f"Bearer {key}"} if key else {}

    async def _register_with_kv_controller(self, endpoint: str) -> None:
        """Join/leave every KV subscriber's engine set when deployed with
        KV_CONTROLLER_URL (comma-separated; +POD_IP/ENGINE_PORT from the
        operator's downward API) — the LMCACHE_CONTROLLER_URL contract
        (deployment-vllm-multi.yaml:324-339), fanned out so embedded-index
        router replicas see the same membership the controller does."""
        import os

        subscribers = _kv_subscriber_urls()
        pod_ip = os.environ.get("POD_IP")
        if not subscribers or not pod_ip:
            return
        port = os.environ.get("ENGINE_PORT", "8000")
        my_url = f"http://{pod_ip}:{port}"

        body: dict = {"url": my_url}
        if self.pool_role:
            # the live pool role rides registration so the controller's
            # rebalancer sees membership per pool without a scrape hop
            body["role"] = self.pool_role
        identity = self._device_identity()
        if identity is not None:
            # mesh/process-group identity rides the registration so
            # /peer_lookup replies can negotiate the device-path peer
            # transport per (requester, owner) pair (docs/39)
            body["transport"] = identity

        async def post_one(controller: str) -> None:
            try:
                async with self._client_session().post(
                    controller.rstrip("/") + endpoint, json=body,
                    headers=self._kv_controller_headers(),
                ) as resp:
                    logger.info(
                        "KV controller %s%s (%s): HTTP %d",
                        controller, endpoint, my_url, resp.status,
                    )
            except Exception as e:
                logger.warning("KV controller %s failed: %s", endpoint, e)

        # concurrent, not sequential: one unreachable subscriber must not
        # delay registration with (or, worse, shutdown deregistration
        # from) the healthy ones by its full connect timeout
        await asyncio.gather(*(post_one(c) for c in subscribers))

    async def _on_cleanup(self, app: web.Application) -> None:
        if self.watchdog is not None:
            self.watchdog.stop()
        if self.kv_event_publisher is not None:
            await self.kv_event_publisher.stop()
            self.engine.threads.unregister("kv_event_publisher")
        await self._register_with_kv_controller("/deregister")
        self.async_engine.shutdown()
        if self._session is not None and not self._session.closed:
            await self._session.close()

    def _client_session(self):
        """Long-lived outbound session (KV pulls are on the PD hot path —
        per-request session churn taxes latency and file descriptors)."""
        import aiohttp

        if self._session is None or self._session.closed:
            self._session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=30)
            )
        return self._session

    # -- inference routes --------------------------------------------------

    @staticmethod
    def _admission_error(e: Exception) -> web.Response | None:
        """Map lifecycle-gate exceptions to their HTTP shape: overload →
        429 + Retry-After (from observed decode throughput), expired/
        unmeetable deadline → 503, draining → 503 + X-Engine-Draining (the
        router fails over on that header instead of surfacing it)."""
        if isinstance(e, EngineOverloadedError):
            import math

            return error(
                429, str(e), "overloaded",
                headers={"Retry-After": str(int(math.ceil(e.retry_after_s)))},
            )
        if isinstance(e, DeadlineExceededError):
            return error(503, str(e), "deadline_exceeded")
        if isinstance(e, EngineDrainingError):
            return error(
                503, str(e), "service_unavailable",
                headers={"X-Engine-Draining": "1"},
            )
        return None

    def _gate_admission(self, request):
        """(deadline, tenant, refusal) for one inference request — run
        BEFORE any SSE headers go out so 429/503 keep their status codes.
        The same checks rerun at submit time (this is the fast path, not
        the only line of defense). The tenant context comes from the
        router-stamped x-tenant-id / x-priority / x-tenant-weight headers
        (qos.tenant_from_headers); unstamped traffic is the default
        tenant, and a higher-priority class can pass a full queue by
        evicting lower-priority waiting work (lowest-priority-first
        shedding, claimed at submit time)."""
        deadline = deadline_from_headers(request.headers)
        tenant = tenant_from_headers(request.headers)
        # stickiness audit (docs/32-fleet-telemetry.md): every inference
        # request carrying a router session stamp is observed, refused or
        # not — an affinity break on a request the engine then sheds is
        # still an affinity break
        self.stickiness.observe_headers(request.headers)
        try:
            self.async_engine.precheck_admission(deadline, tenant=tenant)
        except (EngineOverloadedError, DeadlineExceededError,
                EngineDrainingError) as e:
            return deadline, tenant, self._admission_error(e)
        return deadline, tenant, None

    def _peer_hint(self, request: web.Request) -> str | None:
        """The validated x-kv-owner-hint, with a hint naming THIS engine
        dropped: a failover re-pick can deliver a migrate-stamped request
        back to the owner itself, and probing oneself over HTTP from the
        step thread (which holds the engine lock the handler needs) would
        stall an admission for the full peer timeout."""
        hint = peer_hint_from_headers(request.headers)
        if hint and hint == self._advertised_url():
            return None
        return hint

    # -- request tracing (docs/28-request-tracing.md) ----------------------

    def _trace_start(self, request: web.Request, rid: str, **attrs):
        """Open the engine-side timeline for one HTTP request, joining the
        router's trace via the inbound W3C traceparent header (a request
        without one starts a fresh engine-local trace)."""
        return self.traces.start(
            rid, "engine.request",
            traceparent=request.headers.get("traceparent"),
            attrs={"path": request.path, "model": self.model_name, **attrs},
        )

    def _trace_refused(self, trace, resp, rid: str):
        """Admission refusals (429 shed / 503 deadline / 503 draining) end
        the timeline immediately — short-circuits are exactly the requests
        a timeline must explain, and every refusal carries the correlation
        id the router's access log will echo."""
        trace.event("refused", status=resp.status)
        self.traces.finish(trace, status=f"refused:{resp.status}")
        resp.headers.setdefault("X-Request-Id", rid)
        return resp

    def _trace_respond(self, trace, resp, rid: str):
        """Terminal bookkeeping for a non-streaming response: stamp the
        correlation id and close the timeline with the HTTP outcome."""
        self.traces.finish(
            trace,
            status="ok" if resp.status < 400 else f"error:{resp.status}",
        )
        resp.headers.setdefault("X-Request-Id", rid)
        return resp

    def _trace_output(self, trace, out, choice: int = 0) -> None:
        """Record one resolved step's delta; on the terminal output, turn
        the request's lifecycle stamps into queue/prefill/decode phase
        spans and feed the tpu:request_* histograms. Rollback-safe: the
        engine only emits outputs for RESOLVED steps, so a discarded
        speculative dispatch can never appear here."""
        if out.new_token_ids:
            if out.num_output_tokens == len(out.new_token_ids):
                trace.event("first_token", choice=choice)
            sw = getattr(out, "spec_window", None)
            if sw is not None:
                # speculative-verify window (docs/36-speculative-decoding
                # .md): per-window acceptance rides the event, so a
                # timeline shows exactly where drafts paid off (or didn't)
                trace.event(
                    "decode_window", tokens=len(out.new_token_ids),
                    choice=choice, proposed=sw[0], accepted=sw[1],
                    proposer=sw[2],
                )
            else:
                trace.event(
                    "decode_window", tokens=len(out.new_token_ids),
                    choice=choice,
                )
        if not out.finished:
            return
        # structured output (docs/41-structured-output.md): the terminal
        # automaton verdict — "invalid" here with finish_reason=length is
        # the classic under-budgeted max_tokens truncation signature
        so = getattr(out, "structured_outcome", None)
        if so:
            trace.event("structured_outcome", outcome=so, choice=choice)
        # XLA compile stalls this request's dispatches blocked on
        # (docs/42-compile-telemetry.md): each names the program key and
        # wall — the timeline's explanation of a seconds-scale hole in an
        # otherwise steady decode cadence
        for st in getattr(out, "compile_stalls", None) or []:
            trace.event("compile_stall", choice=choice, **st)
        # getattr: error outputs (and RequestOutput-shaped test doubles)
        # carry no lifecycle to attribute
        pt = getattr(out, "phase_times", None)
        if not pt:
            return
        # hydration attribution (docs/30-kv-flow-telemetry.md): where the
        # prompt's KV came from — the timeline's explanation of a fast (or
        # slow) prefill, and the per-request view behind the
        # tpu:request_prefix_tokens_total counters. Emitted before the
        # phase spans so /debug/requests?rid= shows it with the prefill
        # span it explains.
        hyd = getattr(out, "hydration", None)
        if hyd:
            plan = getattr(out, "hydration_chunks", None)
            if plan:
                # compute-or-load planner (docs/31-hydration-planner.md):
                # the per-chunk decisions and outcomes that produced this
                # partition — which chunks adopted a tier fetch, which
                # fell back to recompute and why
                trace.event("kv_hydration", choice=choice, plan=plan, **hyd)
            else:
                trace.event("kv_hydration", choice=choice, **hyd)
        # ONE monotonic→epoch anchor for the whole timeline: converting
        # each stamp independently (mono_to_epoch per call) drifts the
        # shared phase boundaries apart by float noise
        anchor = mono_to_epoch(0.0)
        finish_e = anchor + pt["finish"]
        arrival_e = anchor + pt["arrival"]
        seat = pt.get("first_seat")
        ftok = pt.get("first_token")
        trace.span(
            "engine.queue", start=arrival_e,
            end=anchor + seat if seat is not None else finish_e,
            choice=choice,
        )
        if seat is not None:
            trace.span(
                "engine.prefill", start=anchor + seat,
                end=anchor + ftok if ftok is not None else finish_e,
                choice=choice,
                prompt_tokens=pt["prompt_tokens"],
                cached_prompt_tokens=pt["cached_prompt_tokens"],
            )
        if ftok is not None:
            trace.span(
                "engine.decode", start=anchor + ftok, end=finish_e,
                choice=choice, output_tokens=pt["output_tokens"],
                finish_reason=out.finish_reason or "",
                preemptions=pt["preemptions"],
            )
        # the contract histograms observe REGARDLESS of the tracing flag —
        # latency metrics are not a debug feature
        self.metrics.observe_request(pt, trace.trace_id or None)

    async def _resolve_grammar(self, body, trace=None):
        """(grammar, error_response) for a request's structured-output
        surface (docs/41-structured-output.md). A forced tool choice
        ("required" / a named function) wins over response_format — the
        forced call IS the response shape. Compilation runs in the
        executor (a pathological schema costs real milliseconds) and hits
        the engine's GrammarCache; behavior on an uncompilable schema
        follows EngineConfig.structured_output:

          enforce  -> 400 here (counted outcome=invalid),
          fallback -> decode unconstrained (counted outcome=fallback),
          off      -> constraints always declined (counted fallback).

        Malformed request SURFACES (response_format of an unknown type,
        tool_choice naming an absent function) are 400 in every mode —
        they are client errors, not grammar blowups."""
        from .grammar import (
            GrammarCompileError,
            extract_spec,
            tool_choice_spec,
        )

        try:
            spec = tool_choice_spec(
                getattr(body, "tools", None), getattr(body, "tool_choice", None)
            ) or extract_spec(body.response_format, body.guided_json)
        except GrammarCompileError as e:
            self.engine.count_structured("invalid")
            return None, error(400, f"structured output: {e}")
        if spec is None:
            return None, None
        mode = self.engine.config.structured_output
        if mode == "off":
            self.engine.count_structured("fallback")
            if trace is not None:
                trace.event("grammar", mode=mode, outcome="fallback")
            return None, None
        try:
            grammar, cached = await asyncio.get_running_loop().run_in_executor(
                None, self.engine.grammar_cache.get, spec
            )
        except GrammarCompileError as e:
            if mode == "enforce":
                self.engine.count_structured("invalid")
                return None, error(400, f"structured output: {e}")
            self.engine.count_structured("fallback")
            if trace is not None:
                trace.event(
                    "grammar", mode=mode, outcome="fallback", error=str(e)
                )
            return None, None
        if trace is not None:
            trace.event(
                "grammar", mode=mode, kind=spec.get("kind"), cached=cached,
                states=grammar.n_states, classes=grammar.n_classes,
                build_ms=round(grammar.build_s * 1000.0, 3),
            )
        return grammar, None

    async def chat_completions(self, request: web.Request) -> web.StreamResponse:
        try:
            body = ChatCompletionRequest.model_validate(await request.json())
        except (ValidationError, json.JSONDecodeError) as e:
            return error(400, f"invalid request: {e}")
        if not 1 <= body.n <= MAX_N_CHOICES:
            return error(400, f"n must be between 1 and {MAX_N_CHOICES}")
        if (err := self._check_model(body.model)) is not None:
            return err
        lora_name = body.model if body.model in self.lora_adapters else None
        messages = [m.model_dump() for m in body.messages]
        use_tools = bool(body.tools) and body.tool_choice != "none"
        if use_tools or any(
            m.get("role") == "tool" or m.get("tool_calls") for m in messages
        ):
            from .tool_calls import render_messages

            messages = render_messages(
                messages, body.tools if use_tools else None, body.tool_choice
            )
        prompt = self.async_engine.chat_prompt(messages)
        sampling = body.sampling(DEFAULT_MAX_TOKENS)
        if (err := self._check_logprobs(sampling)) is not None:
            return err
        rid = request.headers.get("X-Request-Id") or random_id("chatcmpl")
        trace = self._trace_start(
            request, rid, stream=bool(body.stream), n=body.n,
        )
        deadline, tenant, refused = self._gate_admission(request)
        if refused is not None:
            return self._trace_refused(trace, refused, rid)
        if tenant is not None:
            trace.set(tenant=tenant.tenant_id, priority=tenant.priority)
        trace.event("admitted")
        grammar, gerr = await self._resolve_grammar(body, trace)
        if gerr is not None:
            return self._trace_refused(trace, gerr, rid)
        if grammar is not None:
            import dataclasses

            sampling = dataclasses.replace(sampling, grammar=grammar)
        kv_hint = self._peer_hint(request)
        if body.stream:
            return await self._stream(
                request, rid, prompt, sampling, body, chat=True,
                lora_name=lora_name, parse_tools=use_tools, n=body.n,
                deadline=deadline, tenant=tenant, trace=trace,
                kv_owner_hint=kv_hint,
            )
        return await self._complete(
            rid, prompt, sampling, chat=True, lora_name=lora_name,
            parse_tools=use_tools, n=body.n, deadline=deadline,
            tenant=tenant, trace=trace, kv_owner_hint=kv_hint,
        )

    async def completions(self, request: web.Request) -> web.StreamResponse:
        try:
            body = CompletionRequest.model_validate(await request.json())
        except (ValidationError, json.JSONDecodeError) as e:
            return error(400, f"invalid request: {e}")
        if not 1 <= body.n <= MAX_N_CHOICES:
            return error(400, f"n must be between 1 and {MAX_N_CHOICES}")
        if (err := self._check_model(body.model)) is not None:
            return err
        lora_name = body.model if body.model in self.lora_adapters else None
        prompt, prompt_ids = self._resolve_prompt(body.prompt)
        if prompt is None and prompt_ids is None:
            return error(400, "batched prompts are not supported yet")
        sampling = body.sampling(DEFAULT_MAX_TOKENS)
        if (err := self._check_logprobs(sampling)) is not None:
            return err
        # echo: the prompt text precedes the completion (vLLM/OpenAI
        # legacy semantics). Prompt LOGPROBS under echo would need a
        # scoring forward pass — refuse rather than silently omit
        echo_text = None
        if body.echo:
            if sampling.logprobs is not None:
                return error(
                    400, "echo with logprobs is not supported "
                    "(prompt logprobs are not computed)",
                )
            echo_text = (
                prompt if prompt is not None
                else await asyncio.get_running_loop().run_in_executor(
                    None, self.async_engine.detokenize, prompt_ids
                )
            )
        rid = request.headers.get("X-Request-Id") or random_id("cmpl")
        trace = self._trace_start(
            request, rid, stream=bool(body.stream), n=body.n,
        )
        deadline, tenant, refused = self._gate_admission(request)
        if refused is not None:
            return self._trace_refused(trace, refused, rid)
        if tenant is not None:
            trace.set(tenant=tenant.tenant_id, priority=tenant.priority)
        trace.event("admitted")
        grammar, gerr = await self._resolve_grammar(body, trace)
        if gerr is not None:
            return self._trace_refused(trace, gerr, rid)
        if grammar is not None:
            import dataclasses

            sampling = dataclasses.replace(sampling, grammar=grammar)
        kv_hint = self._peer_hint(request)
        if body.stream:
            return await self._stream(
                request, rid, prompt, sampling, body, chat=False,
                prompt_ids=prompt_ids, lora_name=lora_name, n=body.n,
                echo_text=echo_text, deadline=deadline, tenant=tenant,
                trace=trace, kv_owner_hint=kv_hint,
            )
        return await self._complete(
            rid, prompt, sampling, chat=False, prompt_ids=prompt_ids,
            lora_name=lora_name, n=body.n, echo_text=echo_text,
            deadline=deadline, tenant=tenant, trace=trace,
            kv_owner_hint=kv_hint,
        )

    async def embeddings(self, request: web.Request) -> web.Response:
        """OpenAI embeddings: last-token pooled decoder hidden states."""
        try:
            body = EmbeddingRequest.model_validate(await request.json())
        except (ValidationError, json.JSONDecodeError) as e:
            return error(400, f"invalid request: {e}")
        model = body.model
        if (err := self._check_model(model)) is not None:
            return err
        if model in self.lora_adapters:
            return error(
                400,
                "embeddings through a LoRA adapter are not supported; use "
                "the base model name",
            )
        if body.encoding_format != "float":
            return error(
                400,
                f"encoding_format {body.encoding_format!r} is not supported "
                "(only 'float')",
            )
        if body.dimensions is not None:
            return error(
                400,
                "the dimensions parameter is not supported; vectors have "
                "the model's hidden size",
            )
        raw = body.input
        if isinstance(raw, str):
            inputs = [raw]
        elif isinstance(raw, list) and raw and isinstance(raw[0], int):
            inputs = [raw]
        elif isinstance(raw, list) and raw:
            inputs = raw
        else:
            return error(400, "input must be a string, token array, or list")
        try:
            vectors, n_tokens = await self.async_engine.embed(inputs)
        except ValueError as e:
            return error(400, str(e))
        except RuntimeError as e:
            return error(503, str(e), "service_unavailable")
        return web.json_response({
            "object": "list",
            "model": model,
            "data": [
                {"object": "embedding", "index": i, "embedding": v}
                for i, v in enumerate(vectors)
            ],
            "usage": {"prompt_tokens": n_tokens, "total_tokens": n_tokens},
        })

    async def score(self, request: web.Request) -> web.Response:
        """vLLM /v1/score: similarity of text_1 x text_2 pairs via
        last-token-pooled embeddings (decoder-only models have no
        cross-encoder head; cosine of the L2-normalized embedding vectors
        is the vLLM embedding-model scoring path). The reference router
        proxies this route to its engines (main_router.py:50-246)."""
        try:
            body = ScoreRequest.model_validate(await request.json())
        except (ValidationError, json.JSONDecodeError) as e:
            return error(400, f"invalid request: {e}")
        if (err := self._check_model(body.model)) is not None:
            return err
        t1 = [body.text_1] if isinstance(body.text_1, str) else body.text_1
        t2 = [body.text_2] if isinstance(body.text_2, str) else body.text_2
        if not t1 or not t2:
            return error(400, "text_1 and text_2 must be non-empty")
        if len(t1) == 1:
            pairs = [(t1[0], d) for d in t2]
        elif len(t1) == len(t2):
            pairs = list(zip(t1, t2))
        else:
            return error(
                400,
                f"text_1 ({len(t1)}) and text_2 ({len(t2)}) must be the "
                "same length, or text_1 a single string",
            )
        try:
            scores, n_tokens = await self._pair_scores(pairs)
        except ValueError as e:
            return error(400, str(e))
        except RuntimeError as e:
            return error(503, str(e), "service_unavailable")
        return web.json_response({
            "id": random_id("score"),
            "object": "list",
            "model": body.model,
            "data": [
                {"object": "score", "index": i, "score": s}
                for i, s in enumerate(scores)
            ],
            "usage": {"prompt_tokens": n_tokens, "total_tokens": n_tokens},
        })

    async def rerank(self, request: web.Request) -> web.Response:
        """Jina/Cohere-style /v1/rerank served by vLLM engines: order
        `documents` by embedding similarity to `query`."""
        try:
            body = RerankRequest.model_validate(await request.json())
        except (ValidationError, json.JSONDecodeError) as e:
            return error(400, f"invalid request: {e}")
        if (err := self._check_model(body.model)) is not None:
            return err
        if not body.documents:
            return error(400, "documents must be non-empty")
        if body.top_n is not None and body.top_n < 1:
            return error(400, "top_n must be >= 1")
        try:
            scores, n_tokens = await self._pair_scores(
                [(body.query, d) for d in body.documents]
            )
        except ValueError as e:
            return error(400, str(e))
        except RuntimeError as e:
            return error(503, str(e), "service_unavailable")
        order = sorted(
            range(len(scores)), key=lambda i: scores[i], reverse=True
        )
        if body.top_n is not None:
            order = order[: max(0, body.top_n)]
        results = []
        for i in order:
            entry = {"index": i, "relevance_score": scores[i]}
            if body.return_documents:
                entry["document"] = {"text": body.documents[i]}
            results.append(entry)
        return web.json_response({
            "id": random_id("rerank"),
            "model": body.model,
            "results": results,
            "usage": {"prompt_tokens": n_tokens, "total_tokens": n_tokens},
        })

    async def _pair_scores(
        self, pairs: list[tuple[str, str]]
    ) -> tuple[list[float], int]:
        """Cosine similarity per (a, b) pair. Each distinct text embeds
        once (reranks share one query across every document)."""
        texts: list[str] = []
        index: dict[str, int] = {}
        for a, b in pairs:
            for t in (a, b):
                if t not in index:
                    index[t] = len(texts)
                    texts.append(t)
        vectors, n_tokens = await self.async_engine.embed(texts)
        arr = np.asarray(vectors, dtype=np.float32)
        scores = [
            float(np.dot(arr[index[a]], arr[index[b]])) for a, b in pairs
        ]
        return scores, n_tokens

    def _check_model(self, model: str):
        """vLLM-compatible 404 for unknown model/adapter names — the
        router's model-filtered dispatch and the LoRA controller's
        reconciliation both rely on names being authoritative.

        Callers MUST test the return `is not None`, never by truthiness: an
        unprepared aiohttp Response is a MutableMapping with no items, so
        `bool(error(...))` is False and a bare `if err := ...` silently
        skips the rejection (the bug behind test_unknown_model_404)."""
        if model != self.model_name and model not in self.lora_adapters:
            return error(
                404, f"model '{model}' not found", "not_found_error"
            )
        return None

    @staticmethod
    def _resolve_prompt(prompt) -> tuple[str | None, list[int] | None]:
        if isinstance(prompt, str):
            return prompt, None
        if isinstance(prompt, list) and prompt and isinstance(prompt[0], int):
            return None, prompt
        if (
            isinstance(prompt, list)
            and len(prompt) == 1
            and isinstance(prompt[0], str)
        ):
            return prompt[0], None
        return None, None

    def _check_logprobs(self, sampling):
        from .model_runner import LOGPROBS_TOPN

        if sampling.logprobs is not None and not (
            0 <= sampling.logprobs <= LOGPROBS_TOPN
        ):
            return error(
                400,
                f"logprobs/top_logprobs must be between 0 and {LOGPROBS_TOPN}",
            )
        if sampling.min_tokens < 0:
            return error(400, "min_tokens must be >= 0")
        if sampling.min_tokens > sampling.max_tokens:
            return error(
                400,
                f"min_tokens ({sampling.min_tokens}) cannot exceed "
                f"max_tokens ({sampling.max_tokens})",
            )
        # linear-scanned per accepted token on the step thread — bound it
        if len(sampling.stop_token_ids) > 64:
            return error(400, "stop_token_ids supports at most 64 ids")
        return None

    def _tok_entry(self, tid: int) -> tuple[str, list[int]]:
        """(display string, byte list) for one token, cached per id. Tokens
        with no representation get a unique placeholder — the legacy
        completions top_logprobs dict is keyed by the string, and collisions
        would silently drop alternatives."""
        tid = int(tid)
        cached = self._tok_repr_cache.get(tid)
        if cached is not None:
            return cached
        s, bts = self.engine.tokenizer.token_repr(tid)
        if not s:
            s = f"<token_{tid}>"
        entry = (s, list(bts if bts else s.encode("utf-8")))
        if len(self._tok_repr_cache) < 65536:
            self._tok_repr_cache[tid] = entry
        return entry

    def _chat_logprobs(self, toks, entries, n):
        """OpenAI chat logprobs.content entries for one delta."""
        content = []
        for tid, (chosen, top_ids, top_lps) in zip(toks, entries):
            s, bts = self._tok_entry(tid)
            top = []
            for i, l in zip(top_ids[:n], top_lps[:n]):
                ts, tb = self._tok_entry(i)
                top.append({"token": ts, "logprob": l, "bytes": tb})
            content.append({
                "token": s,
                "logprob": chosen,
                "bytes": bts,
                "top_logprobs": top,
            })
        return {"content": content}

    def _completion_logprobs(self, toks, entries, n, offset0=0):
        """Legacy completions logprobs block for one delta."""
        tokens, token_logprobs, top_logprobs, text_offset = [], [], [], []
        off = offset0
        for tid, (chosen, top_ids, top_lps) in zip(toks, entries):
            s, _ = self._tok_entry(tid)
            tokens.append(s)
            token_logprobs.append(chosen)
            top_logprobs.append(
                {
                    self._tok_entry(i)[0]: l
                    for i, l in zip(top_ids[:n], top_lps[:n])
                }
            )
            text_offset.append(off)
            off += len(s)
        return {
            "tokens": tokens,
            "token_logprobs": token_logprobs,
            "top_logprobs": top_logprobs,
            "text_offset": text_offset,
        }, off

    @staticmethod
    def _choice_rids(rid: str, n: int) -> list[str]:
        """Per-choice engine request ids — ONE derivation shared by the
        stream and non-stream paths (cleanup/log correlation key on it)."""
        return [rid if i == 0 else f"{rid}-{i}" for i in range(n)]

    async def _tokenize_once_for_fanout(self, prompt, prompt_ids, n):
        """n>1 submits the same prompt n times — encode it ONCE here and
        hand every choice the ids (tokenization of a multi-KB rendered
        chat prompt is the expensive part of _submit)."""
        if n > 1 and prompt is not None and prompt_ids is None:
            loop = asyncio.get_running_loop()
            prompt_ids = await loop.run_in_executor(
                None, self.async_engine.tokenize, prompt
            )
            prompt = None
        return prompt, prompt_ids

    @staticmethod
    def _nth_sampling(sampling, i: int):
        """Per-choice sampling for n>1: an explicit seed derives seed+i
        (deterministic-but-distinct choices, vLLM's convention); without a
        seed the engine's RNG stream already decorrelates requests."""
        if i == 0 or sampling.seed is None:
            return sampling
        import dataclasses

        return dataclasses.replace(sampling, seed=sampling.seed + i)

    async def _run_single(self, rid, prompt, sampling, prompt_ids, lora_name,
                          deadline=None, parent_rid=None, tenant=None,
                          trace=None, choice=0, kv_owner_hint=None):
        """One full generation; returns the accumulated result dict.
        parent_rid (the HTTP request's base id) exempts sibling choices of
        the same n>1 request from this submission's admission count — a
        request gates against OTHER requests, never against itself."""
        text = ""
        token_ids: list[int] = []
        lp_entries: list = []
        finish_reason = None
        n_prompt = 0
        async for out in self.async_engine.generate(
            prompt=prompt, prompt_token_ids=prompt_ids,
            sampling=sampling, request_id=rid, lora_name=lora_name,
            deadline=deadline, admission_exclude_prefix=parent_rid,
            tenant=tenant, kv_owner_hint=kv_owner_hint,
        ):
            text += out.text_delta
            token_ids.extend(out.new_token_ids)
            if out.new_logprobs:
                lp_entries.extend(out.new_logprobs)
            finish_reason = out.finish_reason
            n_prompt = out.num_prompt_tokens
            if trace is not None:
                self._trace_output(trace, out, choice)
        return {
            "text": text, "token_ids": token_ids, "lp": lp_entries,
            "finish_reason": finish_reason, "n_prompt": n_prompt,
        }

    async def _complete(
        self, rid, prompt, sampling, *, chat: bool, prompt_ids=None,
        lora_name=None, parse_tools: bool = False, n: int = 1,
        echo_text: str | None = None, deadline: float | None = None,
        tenant=None, trace=None, kv_owner_hint=None,
    ) -> web.Response:
        if trace is None:
            trace = self.traces.start(rid, "engine.request")
        # n>1: concurrent submissions — continuous batching runs them in
        # one batch and the prefix cache dedups the shared prompt, so the
        # marginal cost per extra choice is its decode tokens only.
        # Tasks (not bare gather): the first failure CANCELS the siblings
        # — cancellation triggers generate()'s abort, freeing their KV
        # blocks instead of decoding to max_tokens for a doomed response
        prompt, prompt_ids = await self._tokenize_once_for_fanout(
            prompt, prompt_ids, n
        )
        tasks = [
            asyncio.ensure_future(self._run_single(
                crid, prompt,
                self._nth_sampling(sampling, i), prompt_ids, lora_name,
                deadline, parent_rid=rid, tenant=tenant,
                trace=trace, choice=i, kv_owner_hint=kv_owner_hint,
            ))
            for i, crid in enumerate(self._choice_rids(rid, n))
        ]
        try:
            runs = await asyncio.gather(*tasks)
        except (ValueError, EngineSleepingError, RuntimeError) as e:
            for t in tasks:
                if not t.done():
                    t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            if (resp := self._admission_error(e)) is not None:
                # raced past the handler's gate: same mapping
                return self._trace_respond(trace, resp, rid)
            if isinstance(e, ValueError):
                return self._trace_respond(trace, error(400, str(e)), rid)
            if isinstance(e, EngineSleepingError):
                return self._trace_respond(
                    trace, error(503, str(e), "service_unavailable"), rid
                )
            return self._trace_respond(
                trace, error(500, str(e), "internal_error"), rid
            )
        for r in runs:
            if r["finish_reason"] == "error":
                return self._trace_respond(
                    trace, error(500, r["text"], "internal_error"), rid
                )
            if r["finish_reason"] == "shed" and not r["token_ids"]:
                # evicted from the waiting queue by a higher-priority
                # admission before producing anything: same HTTP shape as
                # admission-time shedding (429 + Retry-After), so clients
                # handle both the same way
                import math

                waiting, queued = self.engine.queue_depth()
                retry = self.engine.estimate_retry_after_s(queued)
                return self._trace_refused(
                    trace,
                    error(
                        429,
                        "request shed for a higher-priority admission; "
                        "retry",
                        "overloaded",
                        headers={"Retry-After": str(int(math.ceil(retry)))},
                    ),
                    rid,
                )
        created = int(time.time())
        choices = []
        for i, r in enumerate(runs):
            finish_reason = r["finish_reason"]
            if chat:
                message = {"role": "assistant", "content": r["text"]}
                if parse_tools:
                    from .tool_calls import parse_tool_calls

                    content, calls = parse_tool_calls(r["text"])
                    if calls:
                        message = {"role": "assistant", "content": content,
                                   "tool_calls": calls}
                        finish_reason = "tool_calls"
                choice = {
                    "index": i,
                    "message": message,
                    "finish_reason": finish_reason,
                }
                if sampling.logprobs is not None:
                    choice["logprobs"] = self._chat_logprobs(
                        r["token_ids"], r["lp"], sampling.logprobs
                    )
            else:
                choice = {"index": i,
                          "text": (echo_text or "") + r["text"],
                          "finish_reason": finish_reason}
                if sampling.logprobs is not None:
                    choice["logprobs"], _ = self._completion_logprobs(
                        r["token_ids"], r["lp"], sampling.logprobs
                    )
            choices.append(choice)
        return self._trace_respond(
            trace,
            web.json_response(
                {
                    "id": rid,
                    "object": "chat.completion" if chat else "text_completion",
                    "created": created,
                    "model": self.model_name,
                    "system_fingerprint": self.system_fingerprint,
                    "choices": choices,
                    # prompt counted once; completion tokens sum over choices
                    "usage": usage(
                        runs[0]["n_prompt"],
                        sum(len(r["token_ids"]) for r in runs),
                    ),
                }
            ),
            rid,
        )

    async def _stream(
        self, request, rid, prompt, sampling, body, *, chat: bool,
        prompt_ids=None, lora_name=None, parse_tools: bool = False,
        n: int = 1, echo_text: str | None = None,
        deadline: float | None = None, tenant=None, trace=None,
        kv_owner_hint=None,
    ) -> web.StreamResponse:
        """SSE streaming for 1..n choices — ONE implementation (n=1 is a
        single pump), so single- and parallel-sampling semantics can never
        diverge. Each choice is its own engine request; chunks interleave
        on the wire tagged with their choice index (the OpenAI n>1 stream
        contract). Tool-call splitting and logprobs run per choice; every
        token-bearing step emits a chunk, even when detok held the text
        back — first-token latency is only observable if the first token's
        chunk actually goes out."""
        if trace is None:
            trace = self.traces.start(rid, "engine.request")
        if self.async_engine.is_sleeping:
            return self._trace_respond(
                trace, error(503, "engine is sleeping", "service_unavailable"),
                rid,
            )
        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "X-Request-Id": rid,
            },
        )
        await resp.prepare(request)
        created = int(time.time())
        obj = "chat.completion.chunk" if chat else "text_completion"
        include_usage = bool(
            body.stream_options and body.stream_options.include_usage
        )
        prompt, prompt_ids = await self._tokenize_once_for_fanout(
            prompt, prompt_ids, n
        )
        rids = self._choice_rids(rid, n)
        queue: asyncio.Queue = asyncio.Queue()

        async def pump(i: int) -> None:
            try:
                async for out in self.async_engine.generate(
                    prompt=prompt, prompt_token_ids=prompt_ids,
                    sampling=self._nth_sampling(sampling, i),
                    request_id=rids[i], lora_name=lora_name,
                    deadline=deadline, admission_exclude_prefix=rid,
                    tenant=tenant, kv_owner_hint=kv_owner_hint,
                ):
                    await queue.put((i, out))
            except Exception as e:
                # invalid prompt (too long) or raced into sleep/death
                # after the SSE headers went out: delivered as an error
                # event by the consumer, then DONE
                await queue.put((i, e))
            await queue.put((i, None))

        tasks = [asyncio.ensure_future(pump(i)) for i in range(n)]
        from .tool_calls import ToolCallStreamParser

        parsers = [
            ToolCallStreamParser() if parse_tools and chat else None
            for _ in range(n)
        ]
        n_prompt = 0
        n_out_total = 0
        lp_offs = [0] * n  # per-choice text offsets (completions logprobs)
        live = n
        sent_errors: set[str] = set()  # a request-wide failure (same
        # exception from every pump) emits ONE error event, not n copies

        async def send(payload: dict) -> None:
            await resp.write(f"data: {json.dumps(payload)}\n\n".encode())

        try:
            if chat:  # role preamble chunk per choice
                for i in range(n):
                    await send(self._chunk(
                        rid, obj, created, {"role": "assistant"}, None,
                        index=i,
                    ))
            elif echo_text:
                # echo: the prompt leads each choice's stream (vLLM
                # streams the same way — one prompt chunk, then deltas)
                for i in range(n):
                    await send(self._chunk(
                        rid, obj, created, echo_text, None, index=i,
                    ))
            while live:
                i, out = await queue.get()
                if out is None:
                    live -= 1
                    continue
                if isinstance(out, Exception):
                    if str(out) not in sent_errors:
                        sent_errors.add(str(out))
                        await send({"error": {"message": str(out)}})
                    continue
                n_prompt = out.num_prompt_tokens
                n_out_total += len(out.new_token_ids)
                self._trace_output(trace, out, choice=i)
                if out.finish_reason == "error":
                    # same dedup as pump exceptions: a step-thread death
                    # stamps the identical message into every choice
                    if out.text_delta not in sent_errors:
                        sent_errors.add(out.text_delta)
                        await send({"error": {"message": out.text_delta}})
                    continue
                if not (out.new_token_ids or out.text_delta or out.finished):
                    continue
                text_delta = out.text_delta
                finish = out.finish_reason if out.finished else None
                extra_delta = None
                if parsers[i] is not None:
                    text_delta = parsers[i].feed(text_delta)
                    if out.finished:
                        tail, calls = parsers[i].finish()
                        text_delta += tail
                        if calls:
                            extra_delta = {
                                "content": text_delta or None,
                                "tool_calls": [
                                    {**c, "index": ci}
                                    for ci, c in enumerate(calls)
                                ],
                            }
                            finish = "tool_calls"
                delta = (
                    extra_delta
                    if extra_delta is not None
                    else ({"content": text_delta} if chat else text_delta)
                )
                chunk = self._chunk(rid, obj, created, delta, finish, index=i)
                if sampling.logprobs is not None and out.new_logprobs:
                    if chat:
                        chunk["choices"][0]["logprobs"] = self._chat_logprobs(
                            out.new_token_ids, out.new_logprobs,
                            sampling.logprobs,
                        )
                    else:
                        chunk["choices"][0]["logprobs"], lp_offs[i] = (
                            self._completion_logprobs(
                                out.new_token_ids, out.new_logprobs,
                                sampling.logprobs, lp_offs[i],
                            )
                        )
                await send(chunk)
        except ConnectionResetError:
            # no abort-by-name here: _submit renames colliding request ids,
            # so abort(rids[i]) could kill a DIFFERENT live request that
            # owns that name. The finally-cancel below reaches generate()'s
            # own cleanup, which aborts under the TRUE engine-side id.
            self.traces.finish(trace, status="disconnected")
            return resp
        finally:
            for t in tasks:
                if not t.done():
                    t.cancel()
            self.traces.finish(
                trace, status="error:stream" if sent_errors else "ok"
            )
        if include_usage:
            final = self._chunk(rid, obj, created, None, None)
            final["choices"] = []
            final["usage"] = usage(n_prompt, n_out_total)
            await send(final)
        await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()
        return resp

    def _chunk(self, rid, obj, created, delta, finish_reason,
               index: int = 0) -> dict:
        if obj == "chat.completion.chunk":
            choice = {
                "index": index,
                "delta": delta if delta is not None else {},
                "finish_reason": finish_reason,
            }
        else:
            choice = {
                "index": index,
                "text": delta if isinstance(delta, str) else "",
                "finish_reason": finish_reason,
            }
        return {
            "id": rid,
            "object": obj,
            "created": created,
            "model": self.model_name,
            "system_fingerprint": self.system_fingerprint,
            "choices": [choice],
        }

    # -- discovery / control routes ---------------------------------------

    async def list_models(self, request: web.Request) -> web.Response:
        if self.draining:
            # discovery probes /v1/models: a 503 here is how the router
            # stops picking a draining engine within one probe interval
            return error(
                503, "engine is draining", "service_unavailable",
                headers={"X-Engine-Draining": "1"},
            )
        cards = [ModelCard(id=self.model_name)]
        cards += [
            ModelCard(id=name, parent=self.model_name, root=path)
            for name, path in self.lora_adapters.items()
        ]
        return web.json_response(ModelList(data=cards).model_dump())

    @property
    def draining(self) -> bool:
        return not self.async_engine.accepting

    def _begin_drain(self, exit_after: bool = False) -> None:
        """Idempotent drain trigger (POST /drain and SIGTERM both land
        here). A later exit_after=True (SIGTERM after a preStop /drain)
        still exits once the running drain's barrier passes."""
        self.async_engine.begin_drain()
        loop = asyncio.get_running_loop()
        if self._drain_task is None or self._drain_task.done():
            self._drain_task = loop.create_task(self._do_drain(exit_after))
        elif exit_after:
            async def _exit_when_drained():
                await self._drained.wait()
                raise web.GracefulExit()

            # strong ref: the loop holds tasks only weakly, and a GC'd
            # exit task would leave a SIGTERM'd pod running forever
            if self._exit_task is None or self._exit_task.done():
                self._exit_task = loop.create_task(_exit_when_drained())

    async def _do_drain(self, exit_after: bool) -> None:
        """Finish in-flight streams (bounded), flush the KV event log,
        deregister from the KV controller — then optionally exit the
        process (SIGTERM path) inside the grace period."""
        t0 = time.monotonic()
        idle = await self.async_engine.wait_idle(self.drain_timeout_s)
        if not idle:
            logger.warning(
                "drain timeout (%.1fs) with requests still in flight; "
                "proceeding", self.drain_timeout_s,
            )
        if self.kv_event_publisher is not None:
            try:
                await self.kv_event_publisher.flush()
            except Exception as e:  # flush is best-effort on the way out
                logger.warning("KV event flush during drain failed: %s", e)
            await self.kv_event_publisher.stop()
            self.kv_event_publisher = None
            self.engine.threads.unregister("kv_event_publisher")
        await self._register_with_kv_controller("/deregister")
        self._drained.set()
        logger.info(
            "drain complete in %.2fs (idle=%s)", time.monotonic() - t0, idle
        )
        if exit_after:
            # GracefulExit unwinds web.run_app through its normal cleanup
            raise web.GracefulExit()

    async def drain(self, request: web.Request) -> web.Response:
        """POST /drain: stop admissions, finish in-flight work, flush +
        deregister. ?wait=true blocks until the drain barrier passes (the
        helm preStop hook uses this so SIGTERM only ever lands on a drained
        process). The process does NOT exit — that's SIGTERM's job."""
        already = self.draining
        self._begin_drain(exit_after=False)
        if request.query.get("wait", "").lower() in ("1", "true", "yes"):
            await self._drained.wait()
        return web.json_response(
            {
                "status": "draining",
                "already_draining": already,
                "drained": self._drained.is_set(),
                "drain_timeout_s": self.drain_timeout_s,
            },
            status=200 if self._drained.is_set() else 202,
        )

    async def set_role(self, request: web.Request) -> web.Response:
        """POST /role {"role": "prefill"|"decode"}: flip the engine's
        disaggregated pool role and RE-ADMIT it (docs/40-pool-rebalancing
        .md). The rebalancer drains the engine first (POST /drain?wait=
        true), so arriving here mid-drain means waiting out the barrier;
        arriving with no drain at all is also legal (the flip phase
        re-POSTs idempotently). Refused 409 on the SIGTERM exit path —
        the process is going down, not changing jobs."""
        try:
            body = await request.json()
        except Exception:
            body = {}
        role = body.get("role")
        from .. import metrics_contract as mc

        if role not in mc.POOL_ROLE_VALUES:
            return error(
                400,
                f"role must be one of {list(mc.POOL_ROLE_VALUES)}",
                "invalid_request_error",
            )
        if self._exit_task is not None and not self._exit_task.done():
            return error(
                409, "engine is exiting (SIGTERM drain in progress)",
                "engine_exiting",
            )
        async with self._role_lock:
            was_draining = self.draining
            if self._drain_task is not None and not self._drain_task.done():
                # let the in-flight drain finish its barrier + deregister
                # before resurrecting the engine under the new identity
                try:
                    await asyncio.wait_for(
                        self._drained.wait(),
                        timeout=self.drain_timeout_s + 10.0,
                    )
                except asyncio.TimeoutError:
                    return error(
                        409, "drain barrier did not pass in time",
                        "engine_draining",
                    )
            previous = self.pool_role
            self.pool_role = role
            self.metrics.set_pool_role(role)
            # reopen admissions and reset the drain latch so a LATER
            # drain/SIGTERM starts a fresh barrier
            self.async_engine.end_drain()
            self._drain_task = None
            self._drained = asyncio.Event()
            if self.kv_event_publisher is None:
                self._start_kv_event_publisher()
            # re-register under the new role; the controller re-adds the
            # engine to its set and the router's next scrape follows the
            # advertised tpu:pool_role
            await self._register_with_kv_controller("/register")
        logger.info("pool role flip: %s -> %s (was_draining=%s)",
                    previous, role, was_draining)
        return web.json_response({
            "status": "ok",
            "role": role,
            "previous_role": previous,
            "was_draining": was_draining,
        })

    def _overload_state(self) -> str | None:
        """Reason the engine would currently shed a plain request, or None.
        Drives /ready so readiness flips BEFORE collapse. record=False:
        kubelet probe polls must not inflate tpu:requests_shed_total."""
        try:
            self.async_engine.precheck_admission(record=False)
        except EngineDrainingError:
            return "draining"
        except EngineOverloadedError as e:
            return str(e)
        return None

    async def health(self, request: web.Request) -> web.Response:
        """Liveness: 503 only when the step loop is dead (a draining or
        overloaded engine is still alive — restarting it would kill the
        in-flight streams drain exists to protect). Queue/drain state rides
        in the body; /ready is the readiness view."""
        if not self.async_engine.is_healthy:
            return web.json_response({"status": "dead"}, status=503)
        waiting, queued_tokens = self.engine.queue_depth()
        return web.json_response({
            "status": "draining" if self.draining else "ok",
            "draining": self.draining,
            "role": self.pool_role,
            "waiting_requests": waiting,
            "queued_tokens": queued_tokens,
            "overloaded": self._overload_state(),
        })

    async def ready(self, request: web.Request) -> web.Response:
        """Readiness: 503 while dead, draining, shedding, or STALLED (the
        watchdog's verdict — a wedged engine leaves the Service so traffic
        fails over, while /health liveness stays green: restarting it
        would destroy the very evidence /debug/flight and the postmortem
        exist to capture)."""
        if not self.async_engine.is_healthy:
            return web.json_response({"status": "dead"}, status=503)
        if self.watchdog is not None and self.watchdog.stalled is not None:
            return web.json_response(
                {
                    "status": "not_ready",
                    "reason": "stalled",
                    "stall": self.watchdog.stalled,
                },
                status=503,
            )
        reason = self._overload_state()
        if reason is not None:
            return web.json_response(
                {"status": "not_ready", "reason": reason}, status=503
            )
        return web.json_response({"status": "ok"})

    async def metrics_endpoint(self, request: web.Request) -> web.Response:
        om = wants_openmetrics(request)
        # fleet-coherence series owned by the server, not the engine
        # snapshot: publisher health + stickiness-audit counts
        pub = self.kv_event_publisher
        try:
            events_log = self.engine.scheduler.pool.events
        except AttributeError:  # engine test doubles carry no pool
            events_log = None
        self.metrics.update_fleet_health(
            publish_batches=pub.posts if pub is not None else 0,
            publish_failures=pub.publish_failures if pub is not None else 0,
            # depth is meaningful only with a publisher draining the log:
            # a standalone engine (no KV_CONTROLLER_URL) fills the bounded
            # buffer and parks at capacity — exporting that would be a
            # permanent false "publisher can't keep up" alarm
            pending_depth=(
                events_log.pending_depth()
                if pub is not None and events_log is not None else 0
            ),
            subscribers=len(pub.subscribers) if pub is not None else 0,
            stickiness=self.stickiness.counts(),
        )
        # thread-liveness series (docs/37-flight-recorder.md): ages are
        # computed HERE from the registry's beat stamps — a dead watchdog
        # cannot freeze its own gauge
        self.metrics.update_liveness(
            ages=self.engine.threads.ages(),
            stall_counts=(
                self.watchdog.stall_counts
                if self.watchdog is not None else None
            ),
        )
        payload = self.metrics.render(
            await self.async_engine.stats_async(), openmetrics=om
        )
        if om:
            # full content-type (incl. version params) — aiohttp's
            # content_type= kwarg rejects parameters
            return web.Response(
                body=payload,
                headers={"Content-Type": OPENMETRICS_CONTENT_TYPE},
            )
        return web.Response(body=payload, content_type="text/plain")

    # one-liner per mounted debug endpoint — the GET /debug index (they
    # number six+ now and were discoverable only by reading this file)
    DEBUG_ENDPOINTS = {
        "GET /debug": "this index",
        "GET /debug/timing": "step-thread phase totals, device-starvation "
                             "gaps by cause, submit waits, program-cache "
                             "state (docs/37)",
        "GET /debug/hydration": "compute-or-load planner live inputs + "
                                "decision counters (docs/31)",
        "GET /debug/requests": "tracing-spine timelines; ?rid= one full "
                               "trace (docs/28)",
        "GET /debug/flight": "flight-recorder ring + heartbeat table + "
                             "watchdog state (docs/37)",
        "GET /debug/programs": "XLA program inventory: compile walls, "
                               "dispatch counts, storm state, and the "
                               "device they were compiled for (docs/42)",
        "POST /debug/postmortem": "write (or return) a redacted postmortem "
                                  "JSON black box now (docs/37)",
        "POST /debug/profile/start": "start an xprof device capture "
                                     "({\"dir\": ...})",
        "POST /debug/profile/stop": "stop the capture, flush the dump, and "
                                    "answer with its summary: the chip's "
                                    "seconds by program and by named scope, "
                                    "idle gaps by host phase (docs/14)",
        "GET /debug/profile/summary": "the last capture's summary again",
    }

    async def debug_index(self, request: web.Request) -> web.Response:
        """GET /debug: every mounted debug endpoint with a one-liner."""
        return web.json_response({"endpoints": self.DEBUG_ENDPOINTS})

    async def debug_requests(self, request: web.Request) -> web.Response:
        """Tracing spine introspection (docs/28-request-tracing.md):
        recent / slowest / in-flight request timelines; ?rid= returns one
        full trace (every span + event) as JSON."""
        payload, status = self.traces.debug_response(request.query)
        return web.json_response(payload, status=status)

    def _postmortem_context(self) -> dict:
        """Extra postmortem sections (flightrec.PostmortemDumper calls
        this at dump time, possibly from a dying thread or a signal
        handler — everything here is lock-light reads)."""
        eng = self.engine
        ctx: dict = {
            "config": {
                "model": self.model_name,
                "fingerprint": eng.model_fingerprint,
                "kv_hydration": eng.config.kv_hydration,
            },
            "timing": dict(eng.timing),
            "loop_timing": self.async_engine.loop_snapshot(),
        }
        if self.watchdog is not None:
            ctx["watchdog"] = self.watchdog.snapshot()
        try:
            snap = eng.flow.snapshot()
            ctx["hydration"] = {
                "signal": eng.hydration_signal(),
                "decisions": snap.get("decisions", {}),
                "sources": snap.get("hydration", {}),
            }
        except Exception as e:  # a half-built engine still gets a dump
            ctx["hydration"] = {"error": f"{type(e).__name__}: {e}"}
        return ctx

    async def debug_flight(self, request: web.Request) -> web.Response:
        """GET /debug/flight: the live black box — last flight records
        (?last= bounds them), the heartbeat table, and the watchdog's
        state/counters (docs/37-flight-recorder.md)."""
        try:
            last = int(request.query.get("last", "128"))
        except ValueError:
            return error(400, "last must be an integer")
        eng = self.engine
        body = {
            "records_total": eng.flightrec.records_total,
            "flight": eng.flightrec.snapshot(last=max(1, last)),
            "heartbeats": eng.threads.snapshot(),
            "watchdog": (
                self.watchdog.snapshot()
                if self.watchdog is not None else None
            ),
            "postmortems": {
                "dir": self.postmortems.out_dir or None,
                "written": self.postmortems.dumps_written,
                "last_path": self.postmortems.last_path,
            },
        }
        out = eng.flightrec.outstanding_age_s()
        if out is not None:
            body["outstanding_step"] = {
                "age_s": round(out[0], 3), "kind": out[1],
            }
        return web.json_response(body)

    async def debug_programs(self, request: web.Request) -> web.Response:
        """GET /debug/programs: the CompileWatch program inventory —
        every recorded build's key, compile wall, dispatch count,
        last-used age and HBM footprint, plus cache hit/miss totals and
        the storm detector's state (docs/42-compile-telemetry.md). The
        storm runbook starts here: find the mid_traffic entry, read its
        key, fix the bucket ladder that let the shape through. The
        "device" section says what those programs were compiled FOR:
        platform, kind, count, the mesh's device ids and live per-device
        memory, the attention backends as resolved, and whether the native
        kvhash library loaded — an engine that came up on the CPU serves
        exactly like one on the chip, only slower, so it says so here."""
        from ..utils.native import kvhash_backend

        body = self.engine.compile_watch.debug_payload()
        body["device"] = {
            **self.engine.runner.device_report(),
            # off the loop: the first ask may build csrc/kvhash.cpp
            "kvhash": await asyncio.get_running_loop().run_in_executor(
                None, kvhash_backend
            ),
        }
        if self._last_capture is not None:
            # where the chip's time went in the last xprof capture
            body["device"]["last_capture"] = self._last_capture
        return web.json_response(body)

    async def debug_postmortem(self, request: web.Request) -> web.Response:
        """POST /debug/postmortem: dump the black box NOW. With
        --postmortem-dir configured the file is written (path in the
        reply); without it the full redacted document comes back inline —
        the operator's escape hatch on an ephemeral filesystem. The dump
        walks every thread stack, so it runs off the event loop."""
        path, doc = await asyncio.get_running_loop().run_in_executor(
            None, self.postmortems.dump, "manual", "POST /debug/postmortem"
        )
        if path is not None:
            return web.json_response({"status": "written", "path": path})
        return web.json_response({"status": "inline", "postmortem": doc})

    async def debug_profile_start(self, request: web.Request) -> web.Response:
        """On-demand xprof capture on a live engine: wraps
        jax.profiler.start_trace so a slow phase seen in /debug/requests
        or /debug/timing can be drilled into at the device level without
        restarting the pod. Load the dump in XProf/TensorBoard: the step
        thread's `step:<phase>` spans sit on the `engine-step` line, the
        jitted steps' scopes on the device's name-scope line."""
        body = {}
        if request.can_read_body:
            try:
                body = await request.json()
            except json.JSONDecodeError:
                return error(400, "body must be JSON (or empty)")
        log_dir = (body or {}).get("dir") or "/tmp/tpu-xprof"
        if self._profile_dir is not None:
            return error(
                409, f"already profiling to {self._profile_dir}", "conflict"
            )
        try:
            import jax

            jax.profiler.start_trace(log_dir)
        except ImportError:
            return error(501, "jax is not available in this process")
        except Exception as e:  # another tracer already active, bad dir...
            return error(409, f"profiler refused to start: {e}", "conflict")
        self._profile_dir = log_dir
        # one reading of both host clocks at the capture's start, in the
        # reply and in the flight ring: the flight records' monotonic
        # stamps, the spine's wall clock and the capture's `step:*` spans
        # can then be laid on one axis offline (docs/14-tracing.md)
        clocks = self.engine.flightrec.clock_pair()
        self.engine.flightrec.note("profile_start", dir=log_dir, **clocks)
        logger.info("xprof capture started -> %s", log_dir)
        return web.json_response(
            {"status": "profiling", "dir": log_dir, "clocks": clocks}
        )

    # the reduction of a capture is bounded: a child that has not answered
    # by then is killed, and the capture stays where it was written
    CAPTURE_SUMMARY_TIMEOUT_S = 180.0

    async def debug_profile_stop(self, request: web.Request) -> web.Response:
        """Stop the capture, flush the dump, and say where the chip's time
        went in it: the capture is reduced where it lies by a child process
        (engine/capture_summary.py: no JAX, no device, niced) that this
        handler awaits and the step thread never does. The summary is the
        reply's `summary`, `GET /debug/profile/summary` and
        `device.last_capture` of `GET /debug/programs`. A child that fails
        or runs out of time leaves `{"error", "dir"}` there and the reply is
        still 200 with the dump's directory: the capture is never lost to
        its summary."""
        if self._profile_dir is None:
            return error(409, "no profile capture in progress", "conflict")
        log_dir, self._profile_dir = self._profile_dir, None
        try:
            import jax

            # stop_trace flushes the dump to disk — do it off the loop
            await asyncio.get_running_loop().run_in_executor(
                None, jax.profiler.stop_trace
            )
        except Exception as e:
            return error(500, f"profiler stop failed: {e}", "internal_error")
        logger.info("xprof capture stopped (%s)", log_dir)
        self._last_capture = await self._summarize_capture(log_dir)
        return web.json_response({
            "status": "stopped", "dir": log_dir,
            "summary": self._last_capture,
        })

    async def _summarize_capture(self, log_dir: str) -> dict:
        out_path = os.path.join(log_dir, "capture_summary.json")
        package_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (package_root, env.get("PYTHONPATH")) if p)
        t0 = time.monotonic()
        proc = None
        try:
            proc = await asyncio.create_subprocess_exec(
                sys.executable, "-m",
                "vllm_production_stack_tpu.engine.capture_summary",
                log_dir, out_path, env=env,
                stdout=asyncio.subprocess.PIPE,
                stderr=asyncio.subprocess.PIPE,
            )
            out, err = await asyncio.wait_for(
                proc.communicate(), self.CAPTURE_SUMMARY_TIMEOUT_S)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"exit code {proc.returncode}: "
                    f"{err.decode(errors='replace')[-400:].strip()}")
            # (the child also writes it beside the dump)
            doc = await asyncio.get_running_loop().run_in_executor(
                None, json.loads, out)
        except (OSError, RuntimeError, ValueError, asyncio.TimeoutError) as e:
            logger.warning("capture summary of %s failed: %r", log_dir, e)
            doc = {"error": f"{type(e).__name__}: {e}"[:600], "dir": log_dir}
        finally:
            if proc is not None and proc.returncode is None:
                proc.kill()
                await proc.wait()
        doc["child_s"] = round(time.monotonic() - t0, 3)
        return doc

    async def debug_profile_summary(
        self, request: web.Request
    ) -> web.Response:
        """GET /debug/profile/summary: what POST /debug/profile/stop last
        answered with."""
        if self._last_capture is None:
            return error(404, "no capture has been stopped yet",
                         "not_found_error")
        return web.json_response(self._last_capture)

    async def debug_timing(self, request: web.Request) -> web.Response:
        """Served-stack profiling: where the step thread's wall time goes
        (device dispatch vs host scheduling vs idle) and how long request
        submissions wait on the engine lock. Counters are cumulative and
        monotonic — profilers snapshot before/after and subtract (an
        in-place reset would race the step thread's unlocked accumulates
        and could be silently lost)."""
        eng = self.async_engine.engine
        sched = eng.scheduler
        spec: dict = {
            "proposed": dict(sched.spec_proposed_by),
            "accepted": dict(sched.spec_accepted_by),
        }
        if sched.draft_proposer is not None:
            # draft-proposer pool discipline (docs/36): rows that fell
            # back to n-gram under pool pressure, and the scratch share
            # the draft currently holds out of the shared block pool
            spec["draft"] = {
                "declined_rows": sched.draft_proposer.declined_rows,
                "scratch_blocks": sched.pool.scratch_blocks,
            }
        return web.json_response({
            "engine": dict(eng.timing),
            "loop": self.async_engine.loop_snapshot(),
            "spec": spec,
            "programs": {
                "compile_fallbacks": eng.runner.compile_fallbacks,
                "bg_compiles": eng.runner.bg_compiles,
                "compiled_keys": len(eng.runner._compiled_keys),
                "bg_pending": len(eng.runner._bg_inflight),
            },
        })

    async def debug_hydration(self, request: web.Request) -> web.Response:
        """Operator view of the compute-or-load hydration planner
        (docs/31-hydration-planner.md): the LIVE decision inputs —
        per-tier measured fetch bandwidth + sample-floor state, achieved
        prefill FLOP/s, per-block KV bytes — alongside the cumulative
        per-chunk decision counters and the planner's configuration.
        Exactly the numbers the planner acted on, not a reconstruction."""
        eng = self.engine

        def work():
            sig = eng.hydration_signal()
            snap = eng.flow.snapshot()
            hydr = getattr(eng, "hydrator", None)
            return {
                "signal": sig,
                "decisions": snap.get("decisions", {}),
                "hydration_sources": snap.get("hydration", {}),
                "planner": (
                    hydr.snapshot() if hydr is not None
                    else {"mode": eng.config.kv_hydration, "enabled": False}
                ),
            }

        data = await asyncio.get_running_loop().run_in_executor(None, work)
        return web.json_response(data)

    async def sleep(self, request: web.Request) -> web.Response:
        level = int(request.query.get("level", "1"))
        try:
            await asyncio.get_running_loop().run_in_executor(
                None, self.async_engine.sleep, level
            )
        except RuntimeError as e:
            return error(409, str(e), "conflict")
        return web.json_response({"status": "sleeping", "level": level})

    async def wake_up(self, request: web.Request) -> web.Response:
        await asyncio.get_running_loop().run_in_executor(
            None, self.async_engine.wake
        )
        return web.json_response({"status": "awake"})

    async def is_sleeping(self, request: web.Request) -> web.Response:
        return web.json_response({"is_sleeping": self.async_engine.is_sleeping})

    async def load_lora_adapter(self, request: web.Request) -> web.Response:
        body = await request.json()
        name = body.get("lora_name")
        path = body.get("lora_path")
        if not name or not path:
            return error(400, "lora_name and lora_path are required")
        try:
            await self.async_engine.load_lora(name, path)
        except (ValueError, KeyError, FileNotFoundError) as e:
            return error(400, str(e))
        except RuntimeError as e:
            return error(409, str(e), "conflict")
        logger.info("loaded LoRA adapter %s from %s", name, path)
        return web.json_response({"status": "ok"})

    async def unload_lora_adapter(self, request: web.Request) -> web.Response:
        body = await request.json()
        name = body.get("lora_name")
        try:
            await self.async_engine.unload_lora(name)
        except KeyError:
            return error(404, f"adapter {name} not loaded", "not_found_error")
        except RuntimeError as e:  # in-flight requests still use the adapter
            return error(409, str(e), "conflict")
        return web.json_response({"status": "ok"})

    async def kv_lookup(self, request: web.Request) -> web.Response:
        """KV-aware routing probe: longest resident KV prefix for a prompt
        (HBM + host tiers). The KV controller fans /lookup out to this."""
        body = await request.json()
        text, token_ids = body.get("text"), body.get("token_ids")
        if text is None and token_ids is None:
            return error(400, "text or token_ids is required")
        n = await self.async_engine.kv_lookup(
            text=text, token_ids=token_ids, lora_name=body.get("model")
        )
        return web.json_response({"matched_tokens": n})

    def _device_identity(self) -> dict | None:
        """This engine's advertised mesh identity, or None: transport
        opt-in (kv_peer_transport auto|device) AND a live 2+-process
        jax.distributed runtime with KV_MESH_GROUP assigned. An engine
        can be a device-pull OWNER without consuming the peer tier, so
        this doesn't require peer_tier."""
        cfg = getattr(self.engine, "config", None)
        if cfg is None or getattr(
            cfg, "kv_peer_transport", "http"
        ) not in ("auto", "device"):
            return None
        peer = getattr(self.engine, "peer_tier", None)
        if peer is not None and peer.transport_identity is not None:
            return peer.transport_identity
        from .kv_device_transfer import device_transport_identity

        return device_transport_identity()

    @staticmethod
    def _parse_peer_hashes(body: dict) -> list[int] | None:
        """Decimal-string hash list of one peer probe/fetch, bounded and
        validated; None = malformed (caller 400s)."""
        raw = body.get("hashes")
        if not isinstance(raw, list):
            return None
        try:
            return [int(h) for h in raw[:MAX_PEER_RUN_BLOCKS]]
        except (TypeError, ValueError):
            return None

    async def kv_peer_contains(self, request: web.Request) -> web.Response:
        """Peer-engine KV tier, probe half (docs/35-peer-kv-reuse.md):
        how many of the requested hashes this engine can serve RIGHT NOW,
        consecutively, from its local tiers — the staleness guard a
        peer's hydration planner runs before planning chunks against the
        cluster index's possibly-seconds-old view of this pool."""
        body = await request.json()
        if body.get("fingerprint") != self.engine.model_fingerprint:
            return error(
                409, "KV fingerprint mismatch — refusing foreign probe",
                "conflict",
            )
        hashes = self._parse_peer_hashes(body)
        if hashes is None:
            return error(400, "hashes must be a list of decimal strings")
        n = await self.async_engine.kv_peer_contains(hashes)
        reply: dict = {"matched": n}
        identity = self._device_identity()
        if identity is not None:
            # echo this owner's mesh identity so the probing peer can
            # (re-)negotiate the transport against a FRESH view — the
            # owner-hint path never consults the controller, and a stale
            # index hint must re-validate here before any collective
            reply["transport"] = identity
        return web.json_response(reply)

    async def kv_peer_fetch(self, request: web.Request) -> web.Response:
        """Peer-engine KV tier, sender half: the consecutive locally-
        resident prefix of the requested hashes as kvstore-framed block
        payloads (engine/kv_transfer raw_frame — the same wire the remote
        store and the PD stream speak). The engine lock is held only for
        the residency walk + device-copy dispatch; numpy resolution, disk
        reads and framing run in an executor, and the served bytes meter
        under (tier="peer", direction="out"). With an at-rest codec the
        peer link ships WIRE form (int4+scales / fp8): ring entries held
        encoded frame as-is, logical arrays encode here — the puller
        dequantizes at its pool's adopt boundary."""
        from .kv_codec import logical_nbytes, wire_nbytes
        from .kv_transfer import encoded_frame

        body = await request.json()
        if body.get("fingerprint") != self.engine.model_fingerprint:
            return error(
                409, "KV fingerprint mismatch — refusing foreign fetch",
                "conflict",
            )
        hashes = self._parse_peer_hashes(body)
        if hashes is None:
            return error(400, "hashes must be a list of decimal strings")
        t0 = time.perf_counter()
        served, entries = await self.async_engine.kv_peer_export(hashes)

        def build() -> tuple[bytes, int, int, int]:
            host = self.engine.host_tier
            disk = getattr(host, "disk", None) if host is not None else None
            codec = self.engine.kv_codec
            frames: list[bytes] = []
            nbytes = 0
            logical = 0
            for h, (kind, val) in zip(served, entries):
                if kind == "dev":
                    obj = np.stack([np.asarray(p) for p in val])
                elif kind == "np":
                    obj = val  # ndarray, or EncodedKVBlock (encode_ring)
                else:  # "disk": file IO deferred off the engine lock
                    obj = disk.load(val) if disk is not None else None
                    if obj is None:
                        break  # evicted since the walk: stop clean
                if codec.enabled and isinstance(obj, np.ndarray):
                    obj = codec.encode(obj)
                frames.append(encoded_frame(h, obj))
                nbytes += wire_nbytes(obj)
                logical += logical_nbytes(obj)
            return b"".join(frames), len(frames), nbytes, logical

        payload, count, nbytes, logical = await asyncio.get_running_loop(
        ).run_in_executor(None, build)
        # peer/out: WIRE bytes this engine SERVED to a peer (failure paths
        # on the puller's side record their own 0-byte samples)
        self.engine.flow.record(
            "peer", "out", nbytes, count, time.perf_counter() - t0,
            logical_nbytes=logical,
        )
        return web.Response(
            body=payload,
            content_type="application/octet-stream",
            headers={
                "X-KV-Count": str(count),
                "X-KV-Fingerprint": self.engine.model_fingerprint,
            },
        )

    async def kv_peer_device_pull(self, request: web.Request) -> web.Response:
        """Owner trigger of a device-collective peer pull (docs/39): the
        puller POSTs the hash run, then BOTH processes meet inside the
        same cooperative transfer program (kv_device_transfer.pull_kv_
        device_crossproc). The handler always enters the collective once
        the run parses — the program's own fingerprint allgather and
        go/no-go barrier abort both sides cooperatively, so a refusal
        can never leave the puller wedged mid-collective. The reply
        lands only after the owner's half completes (the puller reads
        it AFTER its own half — split send/read)."""
        body = await request.json()
        hashes = self._parse_peer_hashes(body)
        if hashes is None:
            return error(400, "hashes must be a list of decimal strings")
        try:
            await self.async_engine.kv_peer_device_serve(hashes)
        except Exception as e:
            # aborted cooperatively (fingerprint gate, peer prep failure,
            # unsupported mesh shape) — the puller already degraded its
            # chunk to fallback_recompute; this status is informational
            logger.warning("device peer pull serve aborted: %s", e)
            return error(409, f"device pull aborted: {e}", "conflict")
        return web.json_response({"ok": True})

    async def kv_peer_replicate(self, request: web.Request) -> web.Response:
        """Proactive flash-crowd replication, target half (docs/39): the
        controller orders THIS engine to fetch a hot prefix from its
        owner (HTTP peer path) and adopt it parked — after which the
        cluster index shows a second holder and the router can fan the
        crowd out. The wire fetch runs off the step lock."""
        body = await request.json()
        owner = str(body.get("owner") or "").rstrip("/")
        hashes = self._parse_peer_hashes(body)
        if not owner or not owner.startswith("http") or hashes is None:
            return error(400, "need owner url and a hash list")
        n = await self.async_engine.kv_peer_replicate(owner, hashes)
        return web.json_response({"adopted": n})

    async def kv_replicated(self, request: web.Request) -> web.Response:
        """Replication notification to the OWNER: a peer now holds copies
        of these hashes, so migration-aware eviction prefers them as
        victims from here on (pool + host ring, docs/39)."""
        body = await request.json()
        hashes = self._parse_peer_hashes(body)
        if hashes is None:
            return error(400, "hashes must be a list of decimal strings")
        n = await self.async_engine.kv_mark_replicated(hashes)
        return web.json_response({"resident": n})

    async def kv_export(self, request: web.Request) -> web.Response:
        """Disaggregated prefill, sender side: the prompt's resident KV
        blocks as an npz payload (engine/kv_transfer.py wire format)."""
        from .kv_transfer import serialize_blocks

        body = await request.json()
        if body.get("text") is None and body.get("token_ids") is None:
            return error(400, "text or token_ids is required")
        hashes, blocks = await self.async_engine.kv_export(
            text=body.get("text"), token_ids=body.get("token_ids"),
            lora_name=body.get("model"),
        )
        # multi-MB payloads: never serialize on the event loop
        payload = await asyncio.get_running_loop().run_in_executor(
            None, serialize_blocks, hashes, blocks,
            self.engine.model_fingerprint,
        )
        return web.Response(
            body=payload,
            content_type="application/octet-stream",
            headers={"X-KV-Blocks": str(len(hashes))},
        )

    async def kv_export_stream(self, request: web.Request) -> web.StreamResponse:
        """Streaming sender: the prompt's resident KV blocks as
        self-delimiting frames (kv_transfer.block_frame). The engine lock is
        held only to walk the chain and dispatch the device→host copies;
        each block resolves to numpy and hits the socket while later
        copies are still in flight — no whole-prompt staging buffer
        (VERDICT r2 weak #3)."""
        import numpy as np

        from .kv_transfer import block_frame

        body = await request.json()
        if body.get("text") is None and body.get("token_ids") is None:
            return error(400, "text or token_ids is required")
        hashes, parts = await self.async_engine.kv_export_lazy(
            text=body.get("text"), token_ids=body.get("token_ids"),
            lora_name=body.get("model"),
        )
        resp = web.StreamResponse()
        resp.headers["Content-Type"] = "application/octet-stream"
        resp.headers["X-KV-Blocks"] = str(len(hashes))
        resp.headers["X-KV-Fingerprint"] = self.engine.model_fingerprint
        await resp.prepare(request)
        loop = asyncio.get_running_loop()
        for h, p in zip(hashes, parts):
            frame = await loop.run_in_executor(
                None,
                lambda h=h, p=p: block_frame(
                    h, np.stack([np.asarray(x) for x in p])
                ),
            )
            await resp.write(frame)
        await resp.write_eof()
        return resp

    async def kv_import(self, request: web.Request) -> web.Response:
        """Disaggregated prefill, receiver side: adopt shipped KV blocks."""
        from .kv_transfer import deserialize_blocks

        payload = await request.read()
        try:
            hashes, blocks, fp = await asyncio.get_running_loop(
            ).run_in_executor(None, deserialize_blocks, payload)
        except Exception as e:
            return error(400, f"malformed KV payload: {e}")
        try:
            n = await self.async_engine.kv_import(hashes, blocks, fp)
        except ValueError as e:  # geometry or fingerprint mismatch
            return error(409, str(e), "conflict")
        return web.json_response({"imported_blocks": n, "offered": len(hashes)})

    async def kv_pull(self, request: web.Request) -> web.Response:
        """Disaggregated prefill orchestration target: fetch the prompt's KV
        from the prefill engine (source_url) and adopt it locally. The router
        calls this on the DECODE engine between its two phases
        (reference request.py:305-431; NIXL receiver role)."""
        import aiohttp

        from .kv_transfer import deserialize_blocks

        body = await request.json()
        source = (body.get("source_url") or "").rstrip("/")
        if not source:
            return error(400, "source_url is required")
        if body.get("messages") is not None:
            probe = {"text": self.async_engine.chat_prompt(body["messages"])}
        elif body.get("text") is not None:
            probe = {"text": body["text"]}
        elif body.get("token_ids") is not None:
            probe = {"token_ids": body["token_ids"]}
        else:
            return error(400, "messages, text, or token_ids is required")
        if body.get("model"):
            probe["model"] = body["model"]
        try:
            return await self._pull_streamed(source, probe)
        except _StreamUnsupported:
            pass  # older sender: fall back to the one-shot npz hop
        except (aiohttp.ClientError, asyncio.TimeoutError) as e:
            return error(502, f"source engine unreachable: {e}", "bad_gateway")
        except ValueError as e:
            return error(409, str(e), "conflict")
        try:
            async with self._client_session().post(
                source + "/kv/export", json=probe
            ) as resp:
                if resp.status != 200:
                    return error(
                        502, f"source engine returned {resp.status}",
                        "bad_gateway",
                    )
                payload = await resp.read()
        except (aiohttp.ClientError, asyncio.TimeoutError) as e:
            return error(502, f"source engine unreachable: {e}", "bad_gateway")
        try:
            hashes, blocks, fp = await asyncio.get_running_loop(
            ).run_in_executor(None, deserialize_blocks, payload)
        except Exception as e:  # truncated/corrupt upstream payload
            return error(502, f"malformed KV payload from source: {e}",
                         "bad_gateway")
        try:
            n = await self.async_engine.kv_import(hashes, blocks, fp)
        except ValueError as e:
            return error(409, str(e), "conflict")
        return web.json_response({"imported_blocks": n, "offered": len(hashes)})

    # adopt in groups of this many blocks: each group's device upload runs
    # under a BRIEF engine lock while the next group downloads, so transfer
    # pipelines with decode instead of stalling it for the whole import
    _PULL_GROUP = 8

    async def _pull_streamed(self, source: str, probe: dict) -> web.Response:
        """Receiver half of the streaming PD path: read frames off the
        sender's chunked response and adopt them group-by-group."""
        import aiohttp
        import numpy as np

        from .kv_transfer import FrameParser

        async with self._client_session().post(
            source + "/kv/export_stream", json=probe
        ) as resp:
            if resp.status == 404:
                raise _StreamUnsupported
            if resp.status != 200:
                raise aiohttp.ClientError(
                    f"source engine returned {resp.status}"
                )
            fp = resp.headers.get("X-KV-Fingerprint", "")
            offered = int(resp.headers.get("X-KV-Blocks", "0"))
            if offered and fp != self.engine.model_fingerprint:
                # refuse before moving any bytes onto the device
                raise ValueError(
                    f"KV fingerprint mismatch: sender {fp!r} != this "
                    f"engine {self.engine.model_fingerprint!r} — refusing "
                    "foreign KV"
                )
            # bound each frame at a small multiple of this engine's own
            # per-block byte size — a corrupted stream fails fast instead of
            # buffering the rest of the response as residual bytes
            from .kv_transfer import engine_block_nbytes

            block_nbytes = (
                engine_block_nbytes(self.engine.runner)
                if self.engine.runner.kv_caches else 64 << 20
            )
            parser = FrameParser(max_frame_bytes=max(4 * block_nbytes, 1 << 20))
            batch_h: list[int] = []
            batch_b: list[np.ndarray] = []
            imported = 0

            async def adopt_batch():
                nonlocal imported
                if not batch_h:
                    return
                imported += await self.async_engine.kv_import(
                    list(batch_h), np.stack(batch_b), fp
                )
                batch_h.clear()
                batch_b.clear()

            async for chunk in resp.content.iter_any():
                try:
                    frames = parser.feed(chunk)
                except Exception as e:
                    # corrupt stream bytes are a bad-gateway condition (like
                    # a malformed npz payload → 502), NOT a 409 conflict —
                    # kv_pull's ValueError clause is for fingerprint/geometry
                    # mismatches. Broad on purpose: garbled headers surface
                    # as KeyError/TypeError/AttributeError too (missing
                    # nbytes, unknown dtype string — same family
                    # kv_disk_tier.load handles)
                    raise aiohttp.ClientPayloadError(
                        f"corrupt KV stream from {source}: "
                        f"{type(e).__name__}: {e}"
                    ) from e
                for h, arr in frames:
                    batch_h.append(h)
                    batch_b.append(arr)
                    if len(batch_h) >= self._PULL_GROUP:
                        await adopt_batch()
            await adopt_batch()
            if parser.residual:
                logger.warning(
                    "KV stream from %s ended mid-frame (%d residual bytes); "
                    "adopted %d complete blocks", source, parser.residual,
                    imported,
                )
        return web.json_response(
            {"imported_blocks": imported, "offered": offered,
             "transport": "stream"}
        )

    async def tokenize(self, request: web.Request) -> web.Response:
        body = await request.json()
        ids = self.async_engine.tokenize(body.get("prompt", ""))
        return web.json_response({"tokens": ids, "count": len(ids)})

    async def detokenize(self, request: web.Request) -> web.Response:
        body = await request.json()
        text = self.async_engine.detokenize(body.get("tokens", []))
        return web.json_response({"prompt": text})

    async def version(self, request: web.Request) -> web.Response:
        return web.json_response({"version": __version__})


# -- CLI -------------------------------------------------------------------


def _parse_bool_flag(v: str) -> bool:
    """Strict true/false parser — a typo like 'off' must not silently mean
    True (the flag often gates a correctness bisection)."""
    s = str(v).lower()
    if s in ("1", "true", "yes", "on"):
        return True
    if s in ("0", "false", "no", "off"):
        return False
    raise argparse.ArgumentTypeError(f"expected true/false, got {v!r}")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="TPU LLM serving engine")
    p.add_argument("--model", default="tiny-llama",
                   help="preset name or local HF checkpoint dir")
    p.add_argument("--served-model-name", default=None)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8100)
    p.add_argument("--max-model-len", type=int, default=None)
    p.add_argument("--dtype", default=None, choices=[None, "bfloat16", "float32"])
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--num-blocks", type=int, default=512,
                   help="HBM KV pages in the pool")
    p.add_argument("--num-host-blocks", type=int, default=0,
                   help="host-RAM KV offload tier size in blocks (0 = off)")
    p.add_argument("--host-kv-gib", type=float, default=0.0,
                   help="host-RAM KV offload tier byte budget in GiB — the "
                        "operator-facing unit (LMCACHE_MAX_LOCAL_CPU_SIZE "
                        "equivalent); overrides --num-host-blocks when "
                        "larger")
    p.add_argument("--remote-kv-url", default="",
                   help="remote KV store URL (tpukv://host:port, "
                        "kvstore/server.py) — the LMCACHE_REMOTE_URL lm:// "
                        "equivalent; enables cross-engine KV sharing")
    p.add_argument("--disk-kv-dir", default="",
                   help="local-disk KV tier directory (ring evictions "
                        "persist here; LMCACHE_LOCAL_DISK equivalent)")
    p.add_argument("--disk-kv-gib", type=float, default=0.0,
                   help="disk KV tier byte budget in GiB (0 = off)")
    p.add_argument("--max-num-seqs", type=int, default=64)
    p.add_argument("--max-num-batched-tokens", type=int, default=512)
    p.add_argument("--max-waiting-requests", type=int, default=0,
                   help="admission control: bound on the waiting queue — "
                        "beyond it new requests get 429 + Retry-After "
                        "computed from observed decode throughput "
                        "(0 = unbounded)")
    p.add_argument("--max-queued-tokens", type=int, default=0,
                   help="admission control: watermark on queued prompt "
                        "tokens awaiting prefill; beyond it new requests "
                        "are shed with 429 (0 = unbounded)")
    p.add_argument("--drain-timeout-s", type=float, default=30.0,
                   help="graceful drain bound (SIGTERM / POST /drain): "
                        "in-flight streams get this long to finish before "
                        "the KV flush + deregister + exit proceed anyway — "
                        "keep below terminationGracePeriodSeconds")
    p.add_argument("--pool-role", default="",
                   choices=["", "prefill", "decode"],
                   help="disaggregated pool role this engine BOOTS with "
                        "(docs/40-pool-rebalancing.md). A runtime "
                        "property: POST /role flips it live and the "
                        "engine re-registers + advertises tpu:pool_role; "
                        "empty = not in a disaggregated deployment")
    p.add_argument("--request-tracing", default=True, type=_parse_bool_flag,
                   help="per-request span timelines (docs/28-request-"
                        "tracing.md): admission, queue wait, prefill, "
                        "per-decode-window events, joined to the router's "
                        "trace via the inbound traceparent header and "
                        "served by /debug/requests. 'false' keeps only "
                        "the tpu:request_* latency histograms")
    p.add_argument("--trace-buffer", type=int, default=256,
                   help="finished request timelines kept in the in-process "
                        "ring buffer behind /debug/requests")
    p.add_argument("--flight-records", type=int, default=512,
                   help="flight-recorder ring capacity (the last-N step "
                        "records a stall report / postmortem carries)")
    p.add_argument("--watchdog", default=True, type=_parse_bool_flag,
                   help="thread-liveness watchdog (docs/37): stale "
                        "heartbeats and dispatched-but-never-resolved "
                        "steps become a named stall — one structured "
                        "report, tpu:engine_step_stalls_total, a "
                        "postmortem dump, and /ready flips 503 (liveness "
                        "/health never flips) until the stall clears")
    p.add_argument("--watchdog-interval-s", type=float, default=1.0,
                   help="seconds between watchdog liveness checks")
    p.add_argument("--watchdog-stall-s", type=float, default=120.0,
                   help="step-thread / unresolved-dispatch stall threshold "
                        "in seconds (keep above the longest legitimate "
                        "lazy-compile stall; per-loop thresholds for the "
                        "fetcher/publisher/bg-compile ride their own "
                        "registrations)")
    p.add_argument("--compile-storm-threshold", type=int, default=6,
                   help="mid-traffic compiles (sync compiles on the "
                        "dispatch path after warmup — shapes the bucket "
                        "ladder failed to absorb) inside the sliding "
                        "window that trip a recompile storm: one "
                        "structured report naming the offending shapes + "
                        "tpu:engine_compile_storms_total (backs the "
                        "TpuRecompileStorm alert)")
    p.add_argument("--compile-storm-window-s", type=float, default=300.0,
                   help="recompile-storm sliding window in seconds")
    p.add_argument("--postmortem-dir", default="",
                   help="directory for redacted postmortem JSON dumps "
                        "(watchdog trip, SIGQUIT, fatal step-thread "
                        "death, POST /debug/postmortem). Empty = no files "
                        "(/debug/postmortem then returns the document "
                        "inline)")
    p.add_argument("--kv-hydration", default="auto",
                   choices=["auto", "planner", "sync", "off"],
                   help="compute-or-load KV hydration for disk/remote-"
                        "resident prefixes (docs/31-hydration-planner.md): "
                        "auto chunks the resident run and picks "
                        "load-vs-recompute per chunk from measured tier "
                        "bandwidth vs prefill FLOP/s, pipelining async "
                        "fetches with chunked prefill (sync-load fallback "
                        "below the bandwidth sample floor); planner always "
                        "plans (unmeasured tiers recompute); sync is the "
                        "legacy blocking whole-prefix reload; off ignores "
                        "lower-tier residency (recompute-only)")
    p.add_argument("--kv-hydration-chunk-blocks", type=int, default=16,
                   help="hydration planner chunk granularity in KV blocks "
                        "(the fetch/adopt/decide unit)")
    p.add_argument("--kv-hydration-timeout-s", type=float, default=0.0,
                   help="seconds a planned chunk fetch may run before the "
                        "chunk falls back to recompute; 0 = auto (3x the "
                        "plan's own fetch estimate, clamped to [0.5, 30])")
    p.add_argument("--kv-peer-fetch", default=False, type=_parse_bool_flag,
                   help="peer-engine KV tier (docs/35-peer-kv-reuse.md): "
                        "let the hydration planner pull a prefix resident "
                        "only in ANOTHER engine's HBM/host tiers "
                        "(tier=peer, priced per chunk against recompute/"
                        "disk/remote from measured bandwidth). Owner "
                        "discovery: the router's x-kv-owner-hint stamp, "
                        "else a cluster-index lookup against the first "
                        "KV_CONTROLLER_URL subscriber. The serving "
                        "endpoints (/kv/peer_contains, /kv/peer_fetch) "
                        "are always mounted regardless")
    p.add_argument("--kv-peer-transport", default="http",
                   choices=["http", "device", "auto"],
                   help="wire of the peer KV tier (docs/39-device-peer-kv"
                        ".md): http always pulls over /kv/peer_fetch; "
                        "auto/device advertise this engine's mesh identity "
                        "(KV_MESH_GROUP + jax.distributed shape) through "
                        "KV registration and pull over ICI/DCN device "
                        "collectives when the owner shares the mesh, "
                        "falling back to HTTP otherwise; device "
                        "additionally warns when no identity is available")
    p.add_argument("--kv-peer-fetch-timeout-s", type=float, default=2.0,
                   help="per-round-trip timeout of peer lookups/probes/"
                        "fetches (probes run on the step thread, so this "
                        "bounds an admission's worst-case stall on a slow "
                        "peer); the hydration plan deadline "
                        "(--kv-hydration-timeout-s) still governs when a "
                        "pending peer chunk falls back to recompute")
    p.add_argument("--prefill-buckets", default="",
                   help="comma-separated prefill chunk buckets (default: "
                        "pow2 ladder up to --max-num-batched-tokens). "
                        "FEWER buckets = fewer XLA programs = faster "
                        "warmup and fewer lazy-compile stalls, at the cost "
                        "of padding small chunks up")
    p.add_argument("--decode-buckets", default="",
                   help="comma-separated decode batch buckets (default: "
                        "pow2 ladder up to --max-num-seqs)")
    p.add_argument("--width-floor-blocks", type=int, default=64,
                   help="floor of the context-width program ladder in pool "
                        "blocks — lower = tighter KV gathers but more "
                        "compiled programs (see SchedulerConfig)")
    p.add_argument("--decode-window", type=int, default=8,
                   help="decode iterations fused into one device dispatch: "
                        "dispatch overhead amortizes over window x batch "
                        "tokens, at "
                        "the cost of up to window-1 discarded tokens past a "
                        "stop condition")
    p.add_argument("--tensor-parallel-size", type=int, default=1)
    p.add_argument("--pipeline-parallel-size", type=int, default=1,
                   help="GSPMD stage sharding of the layer axis (multi-host)")
    p.add_argument("--data-parallel-size", type=int, default=1)
    p.add_argument("--sequence-parallel-size", type=int, default=1,
                   help="ring-attention context parallelism: shard prefill "
                        "chunks' sequence axis over an sp ring "
                        "(parallel/ring_attention.py); size it for "
                        "long-context / prefill-role engines")
    p.add_argument("--expert-parallel-size", type=int, default=1,
                   help="MoE expert parallelism: shard Mixtral-family "
                        "expert FFNs over an ep mesh axis")
    p.add_argument("--num-speculative-tokens", type=int, default=0,
                   help="speculative decoding: propose up to this many "
                        "tokens and verify them in one dispatch (greedy "
                        "requests only; 0 disables). Composes with the "
                        "pipelined step loop (docs/36)")
    p.add_argument("--speculative-min-ngram", type=int, default=2)
    p.add_argument("--speculative-config", default="ngram",
                   choices=["ngram", "draft"],
                   help="proposer: 'ngram' (prompt lookup, zero extra "
                        "weights) or 'draft' (a small draft model drafts "
                        "the k tokens, sharing the paged KV pool through a "
                        "scratch block namespace; n-gram stays the "
                        "fallback). Requires --num-speculative-tokens > 0")
    p.add_argument("--draft-model", default="",
                   help="registry name / checkpoint dir of the draft model "
                        "(--speculative-config draft); must share the "
                        "target model's tokenizer/vocabulary")
    p.add_argument("--structured-output", default="enforce",
                   choices=["enforce", "fallback", "off"],
                   help="grammar-constrained decoding (docs/41-structured-"
                        "output.md) for response_format / guided_json / "
                        "forced tool_choice: enforce compiles the schema "
                        "to an on-device token automaton (uncompilable "
                        "schemas get 400); fallback decodes such requests "
                        "unconstrained instead (counted "
                        "tpu:structured_requests_total{outcome=fallback}); "
                        "off declines all constraints")
    p.add_argument("--quantization", default=None,
                   choices=[None, "int8"],
                   help="weight-only quantization: int8 stores every linear "
                        "weight as int8 + per-output-channel scales (half "
                        "the weight HBM — how an 8B-class model fits one "
                        "16 GiB v5e chip)")
    p.add_argument("--attention-backend", default="auto",
                   choices=["auto", "xla", "pallas", "pallas_interpret"],
                   help="decode attention: auto picks the measured winner "
                        "for the pool's block size (the Pallas paged-decode "
                        "kernel at >=32-token pages with long context, XLA "
                        "staged attention otherwise)")
    p.add_argument("--prefill-attention-backend", default="auto",
                   choices=["auto", "xla", "pallas", "pallas_interpret"],
                   help="prefill/chunked-prefill attention, independent of "
                        "decode: pallas streams pool pages through the "
                        "paged flash-prefill kernel (no gather, no "
                        "(B,T,S) mask); auto gates on block size + context")
    p.add_argument("--kv-cache-dtype", default="auto",
                   choices=["auto", "fp8"],
                   help="KV pool storage dtype: fp8 (float8_e4m3fn) halves "
                        "KV HBM traffic and doubles pool capacity")
    p.add_argument("--kv-at-rest-codec", default="none",
                   choices=["none", "fp8", "int4"],
                   help="at-rest KV codec for blocks leaving the pool "
                        "(disk/remote/peer tiers): int4+per-group-scales "
                        "(~3.5x wire reduction) or fp8 passthrough; "
                        "dequantized on adopt. Joins the KV fingerprint "
                        "so mixed-precision fleets never cross-serve "
                        "(docs/38-kv-quantization.md)")
    p.add_argument("--kv-at-rest-group-size", type=int, default=32,
                   help="int4 codec quantization group size (elements per "
                        "shared scale); smaller = tighter error bound, "
                        "more scale overhead")
    p.add_argument("--kv-at-rest-host-ring", default=False,
                   type=_parse_bool_flag,
                   help="hold host-ring entries in at-rest wire form too: "
                        "the same host-RAM budget buys wire-ratio x more "
                        "blocks, at a dequant on every ring reload")
    p.add_argument("--enable-prefix-caching", action="store_true", default=True)
    p.add_argument("--no-enable-prefix-caching", dest="enable_prefix_caching",
                   action="store_false")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--warmup", action="store_true",
                   help="compile the prefill/decode bucket programs before "
                        "accepting traffic (first requests otherwise stall "
                        "on 10-40s XLA compiles)")
    p.add_argument("--warmup-scope", default="full",
                   choices=["full", "coarse"],
                   help="full: the whole bucket ladder (deterministic "
                        "steady-state perf; tens of minutes cold, seconds "
                        "with a warm --compilation-cache-dir). coarse: only "
                        "the dominating shape lattice (minutes) — finer "
                        "programs pad up and compile in the background "
                        "with zero serving stalls")
    p.add_argument("--max-loras", type=int, default=0,
                   help="runtime LoRA adapter slots (0 disables LoRA)")
    p.add_argument("--max-lora-rank", type=int, default=8)
    p.add_argument("--distributed", default="auto",
                   choices=["auto", "on", "off"],
                   help="multi-host bootstrap via jax.distributed from the "
                        "JAX_COORDINATOR_ADDRESS/JAX_NUM_PROCESSES/"
                        "JAX_PROCESS_ID env the multi-host statefulset "
                        "exports; auto = initialize iff >1 process named")
    p.add_argument("--compilation-cache-dir", default=None,
                   help="persistent XLA compilation cache: --warmup costs "
                        "its 20-40s-per-program compiles ONCE per "
                        "(model, bucket-set); every later boot reloads "
                        "them in seconds. In k8s, mount a PVC here. "
                        "Default: a fixed .jax_cache directory in the "
                        "checkout; ignored when JAX_COMPILATION_CACHE_DIR "
                        "is set (empty string disables)")
    return p


def engine_config_from_args(args: argparse.Namespace) -> EngineConfig:
    model_cfg = resolve_model_config(
        args.model, args.max_model_len, args.dtype,
        quantization=getattr(args, "quantization", None),
    )
    if getattr(args, "decode_buckets", ""):
        # sorted: bucket_for scans in tuple order for the first bucket >= n,
        # so an unordered list would silently pad everything to the first
        # (possibly oversized) entry
        decode_buckets = tuple(sorted(
            int(b) for b in args.decode_buckets.split(",") if b.strip()
        ))
    else:
        decode_buckets = tuple(
            b for b in (8, 16, 32, 64, 128, 256) if b <= args.max_num_seqs
        ) or (args.max_num_seqs,)
        if decode_buckets[-1] < args.max_num_seqs:
            decode_buckets += (args.max_num_seqs,)
    if getattr(args, "prefill_buckets", ""):
        prefill_buckets = tuple(sorted(
            int(b) for b in args.prefill_buckets.split(",") if b.strip()
        ))
    else:
        prefill_buckets = tuple(
            b for b in (64, 128, 256, 512, 1024, 2048)
            if b <= args.max_num_batched_tokens
        ) or (args.max_num_batched_tokens,)
        if prefill_buckets[-1] < args.max_num_batched_tokens:
            prefill_buckets += (args.max_num_batched_tokens,)
    return EngineConfig(
        model=model_cfg,
        cache=CacheConfig(
            block_size=args.block_size,
            kv_cache_dtype=args.kv_cache_dtype,
            num_blocks=args.num_blocks,
            num_host_blocks=args.num_host_blocks,
            host_kv_gib=args.host_kv_gib,
            disk_kv_dir=args.disk_kv_dir,
            disk_kv_gib=args.disk_kv_gib,
            remote_kv_url=args.remote_kv_url,
            enable_prefix_caching=args.enable_prefix_caching,
            kv_at_rest_codec=args.kv_at_rest_codec,
            kv_at_rest_group_size=args.kv_at_rest_group_size,
            kv_at_rest_host_ring=args.kv_at_rest_host_ring,
        ),
        scheduler=SchedulerConfig(
            max_num_seqs=args.max_num_seqs,
            max_num_batched_tokens=args.max_num_batched_tokens,
            decode_buckets=decode_buckets,
            prefill_buckets=prefill_buckets,
            decode_window=args.decode_window,
            width_floor_blocks=args.width_floor_blocks,
            num_speculative_tokens=args.num_speculative_tokens,
            speculative_min_ngram=args.speculative_min_ngram,
            speculative_method=getattr(args, "speculative_config", "ngram"),
            draft_model=getattr(args, "draft_model", ""),
            max_waiting_requests=getattr(args, "max_waiting_requests", 0),
            max_queued_tokens=getattr(args, "max_queued_tokens", 0),
        ),
        parallel=ParallelConfig(
            tensor_parallel_size=args.tensor_parallel_size,
            data_parallel_size=args.data_parallel_size,
            pipeline_parallel_size=args.pipeline_parallel_size,
            sequence_parallel_size=args.sequence_parallel_size,
            expert_parallel_size=args.expert_parallel_size,
        ),
        lora=LoRAConfig(
            max_loras=args.max_loras, max_lora_rank=args.max_lora_rank
        ),
        seed=args.seed,
        attention_backend=getattr(args, "attention_backend", "auto"),
        prefill_attention_backend=getattr(
            args, "prefill_attention_backend", "auto"
        ),
        kv_hydration=getattr(args, "kv_hydration", "auto"),
        kv_hydration_chunk_blocks=getattr(
            args, "kv_hydration_chunk_blocks", 16
        ),
        kv_hydration_timeout_s=getattr(args, "kv_hydration_timeout_s", 0.0),
        kv_peer_fetch=getattr(args, "kv_peer_fetch", False),
        kv_peer_transport=getattr(args, "kv_peer_transport", "http"),
        kv_peer_fetch_timeout_s=getattr(
            args, "kv_peer_fetch_timeout_s", 2.0
        ),
        flight_records=getattr(args, "flight_records", 512),
        structured_output=getattr(args, "structured_output", "enforce"),
        compile_storm_threshold=getattr(
            args, "compile_storm_threshold", 6
        ),
        compile_storm_window_s=getattr(
            args, "compile_storm_window_s", 300.0
        ),
    )


def main(argv: list[str] | None = None) -> None:
    args = build_parser().parse_args(argv)
    # multi-host bootstrap BEFORE any JAX backend touch: the helm multi-host
    # statefulset exports JAX_COORDINATOR_ADDRESS/JAX_NUM_PROCESSES/
    # JAX_PROCESS_ID per pod (parallel/distributed.py consumes them); after
    # this, jax.devices() spans every host in the slice and the engine's
    # mesh/pjit shardings cover them
    from ..parallel.distributed import maybe_initialize
    from ..utils.compile_cache import configure_compile_cache
    from ..utils.system import raise_fd_limit

    raise_fd_limit()
    maybe_initialize(args.distributed)
    cache_dir = configure_compile_cache(args.compilation_cache_dir)
    logger.info("persistent compile cache: %s", cache_dir or "off")
    config = engine_config_from_args(args)
    logger.info("starting engine for model=%s on %s:%d",
                args.model, args.host, args.port)
    engine = LLMEngine(config)
    if args.warmup:
        logger.info(
            "warming serving buckets (%s scope)...", args.warmup_scope
        )
        engine.warmup(scope=args.warmup_scope)
    server = EngineServer(
        engine,
        served_model_name=args.served_model_name,
        drain_timeout_s=args.drain_timeout_s,
        request_tracing=args.request_tracing,
        trace_buffer=args.trace_buffer,
        watchdog=args.watchdog,
        watchdog_interval_s=args.watchdog_interval_s,
        watchdog_stall_s=args.watchdog_stall_s,
        postmortem_dir=args.postmortem_dir,
        pool_role=args.pool_role,
    )
    web.run_app(server.build_app(), host=args.host, port=args.port,
                access_log=None)


if __name__ == "__main__":
    main()
