"""Engine /metrics exporter (Prometheus text format).

Exports the TPU metric contract (metrics_contract.py) the router scraper and
the observability stack consume — the HBM equivalent of the vLLM names the
reference scrapes (engine_stats.py:63-76). Names keep the `tpu:` prefix
(colons are valid Prometheus metric name characters, same convention as
vLLM's `vllm:` metrics)."""

from __future__ import annotations

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)
from prometheus_client.core import HistogramMetricFamily
from prometheus_client.openmetrics import exposition as om_exposition

from .. import metrics_contract as mc
from .engine import EngineStatsSnapshot
from .kv_flow import (
    DIRECTIONS,
    HYDRATION_SOURCES,
    TRANSFER_SECONDS_BUCKETS,
    TRANSFER_TIERS,
)
from .saturation import OCCUPANCY_BUCKETS, STEP_WALL_BUCKETS, WASTE_REASONS

OPENMETRICS_CONTENT_TYPE = om_exposition.CONTENT_TYPE_LATEST


def wants_openmetrics(request) -> bool:
    """/metrics?format=openmetrics serves the OpenMetrics exposition — the
    one that renders histogram exemplars (trace ids on buckets). An
    explicit query param, NOT Accept-header negotiation: OpenMetrics
    forbids colons, so prometheus_client rewrites `tpu:` to `tpu_` there,
    and honoring Prometheus's default Accept preference would silently
    rename the whole scrape contract out from under every dashboard."""
    return request.query.get("format") == "openmetrics"


def _hist_family(
    name: str, doc: str, labelnames: list[str]
) -> HistogramMetricFamily:
    return HistogramMetricFamily(name, doc, labels=labelnames)


def _cum_buckets(hist: dict) -> list[tuple[str, float]]:
    """(le, cumulative-count) pairs (incl. +Inf) from a StepMeter _Hist
    snapshot's per-bucket counts."""
    out: list[tuple[str, float]] = []
    running = 0
    counts = hist.get("counts") or []
    bounds = list(hist.get("buckets") or ()) + [float("inf")]
    for le, n in zip(bounds, counts):
        running += n
        out.append(("+Inf" if le == float("inf") else repr(float(le)),
                    float(running)))
    if not out:
        out = [("+Inf", 0.0)]
    return out


class _SaturationHistograms:
    """Custom collector rendering the StepMeter's per-step distributions
    (tpu:engine_step_occupancy, tpu:engine_step_wall_seconds) straight
    from the cumulative bucket counts the snapshot carries — the step
    thread increments plain ints; no prometheus objects ride the hot
    path."""

    _EMPTY_OCC = {"buckets": OCCUPANCY_BUCKETS,
                  "counts": [0] * (len(OCCUPANCY_BUCKETS) + 1),
                  "sum": 0.0, "count": 0}
    _EMPTY_WALL = {"buckets": STEP_WALL_BUCKETS,
                   "counts": [0] * (len(STEP_WALL_BUCKETS) + 1),
                   "sum": 0.0, "count": 0}

    def __init__(self, owner: "EngineMetrics"):
        self._owner = owner

    def collect(self):
        sat = self._owner.saturation or {}
        model = self._owner.model_name
        occ = _hist_family(
            mc.ENGINE_STEP_OCCUPANCY,
            "Decode-seat occupancy (rows / max_num_seqs) per resolved "
            "decode step",
            ["model_name"],
        )
        h = sat.get("occupancy_hist") or self._EMPTY_OCC
        occ.add_metric([model], _cum_buckets(h), h.get("sum", 0.0))
        yield occ
        wall = _hist_family(
            mc.ENGINE_STEP_WALL,
            "Resolve-cadence wall seconds per resolved step, by phase",
            ["model_name", "phase"],
        )
        walls = sat.get("step_wall_hist") or {}
        for phase in ("prefill", "decode"):
            h = walls.get(phase) or self._EMPTY_WALL
            wall.add_metric(
                [model, phase], _cum_buckets(h), h.get("sum", 0.0)
            )
        yield wall


class _KVFlowHistograms:
    """Custom collector rendering the KVFlowMeter's per-(tier, direction)
    transfer-latency distribution (tpu:kv_transfer_seconds) straight from
    the cumulative bucket counts its snapshot carries — same pattern as
    _SaturationHistograms: the transfer paths increment plain ints; no
    prometheus objects ride the engine/writer threads. Every (tier,
    direction) combo renders from the first scrape (closed label sets,
    seeded at zero)."""

    _EMPTY = {"buckets": TRANSFER_SECONDS_BUCKETS,
              "counts": [0] * (len(TRANSFER_SECONDS_BUCKETS) + 1),
              "sum": 0.0, "count": 0}

    def __init__(self, owner: "EngineMetrics"):
        self._owner = owner

    def collect(self):
        flow = self._owner.kv_flow or {}
        model = self._owner.model_name
        fam = _hist_family(
            mc.KV_TRANSFER_SECONDS,
            "Wall seconds per KV tier-transfer batch, by tier and "
            "direction (in = toward HBM / hydration, out = offload)",
            ["model_name", "tier", "direction"],
        )
        hists = flow.get("seconds_hist") or {}
        for tier in TRANSFER_TIERS:
            for direction in DIRECTIONS:
                h = hists.get(f"{tier}/{direction}") or self._EMPTY
                fam.add_metric(
                    [model, tier, direction],
                    _cum_buckets(h), h.get("sum", 0.0),
                )
        yield fam


class EngineMetrics:
    def __init__(self, model_name: str):
        self.registry = CollectorRegistry()
        self.model_name = model_name
        # latest snapshot's saturation dict, read by the histogram
        # collector at scrape time (update() refreshes it first)
        self.saturation: dict = {}
        # latest snapshot's kv_flow dict (the transfer-latency histogram
        # collector reads it at scrape time)
        self.kv_flow: dict = {}
        self._labels = {"model_name": model_name}
        names = list(self._labels)

        def gauge(name: str, doc: str) -> Gauge:
            return Gauge(name, doc, names, registry=self.registry)

        def counter(name: str, doc: str) -> Counter:
            # prometheus_client re-appends _total to counter names
            base = name[: -len("_total")] if name.endswith("_total") else name
            return Counter(base, doc, names, registry=self.registry)

        self.num_running = gauge(
            mc.NUM_REQUESTS_RUNNING, "Requests currently decoding"
        )
        self.num_waiting = gauge(
            mc.NUM_REQUESTS_WAITING, "Requests queued or prefilling"
        )
        self.kv_usage = gauge(
            mc.HBM_KV_USAGE_PERC, "Fraction of HBM KV pages in active use"
        )
        self.kv_group_usage = Gauge(
            mc.HBM_KV_GROUP_USAGE_PERC,
            "Fraction of each KV page group in active use (full | window)",
            [*names, "group"], registry=self.registry,
        )
        self.kv_group_usage.labels(**self._labels, group="full")
        self.kv_window_share = gauge(
            mc.HBM_KV_WINDOW_SHARE,
            "Window-group pages resident / pages the same rows would hold "
            "in those layers at full length",
        )
        self.kv_window_given_back = counter(
            mc.HBM_KV_WINDOW_PAGES_GIVEN_BACK,
            "Window-group pages given back behind live rows",
        )
        self.state_bytes = gauge(
            mc.HBM_STATE_BYTES,
            "Bytes of recurrent state the state slots in use hold",
        )
        self.state_slots_in_use = gauge(
            mc.HBM_STATE_SLOTS_IN_USE, "State slots held by rows"
        )
        self.state_share = gauge(
            mc.HBM_STATE_SHARE,
            "State bytes in use / (state + page bytes in use)",
        )
        self.latent_bytes = gauge(
            mc.HBM_LATENT_BYTES,
            "Bytes of latent pages in use (latent layers' pages held)",
        )
        self.index_bytes = gauge(
            mc.HBM_INDEX_BYTES,
            "Bytes of index keys in use (beside the latents of the layers "
            "that own an indexer)",
        )
        self.block_diffusion = {
            key: counter(name, f"Generation by diffusion over blocks: {key}")
            for key, name in mc.DEBUG_TIMING_BD_COUNTS.items()
        }
        self.prefix_hit_rate = gauge(
            mc.PREFIX_CACHE_HIT_RATE, "Prefix cache block hit rate"
        )
        self.prefix_hits = counter(mc.PREFIX_CACHE_HITS, "Prefix cache block hits")
        self.prefix_queries = counter(
            mc.PREFIX_CACHE_QUERIES, "Prefix cache block queries"
        )
        self.preemptions = counter(mc.NUM_PREEMPTIONS, "Scheduler preemptions")
        self.host_kv_usage = gauge(
            mc.HOST_KV_USAGE_PERC, "Fraction of host-RAM KV tier in use"
        )
        self.step_overlap = gauge(
            mc.STEP_OVERLAP_FRAC,
            "Fraction of step-loop wall time overlapping device execution",
        )
        self.host_offloads = counter(
            mc.HOST_KV_OFFLOADS, "KV blocks offloaded HBM to host RAM"
        )
        self.host_reloads = counter(
            mc.HOST_KV_RELOADS, "KV blocks reloaded host RAM to HBM"
        )
        self.remote_stores = counter(
            mc.REMOTE_KV_STORES, "KV blocks pushed to the remote store"
        )
        self.remote_fetches = counter(
            mc.REMOTE_KV_FETCHES, "KV blocks fetched from the remote store"
        )
        self.spec_draft = counter(
            mc.SPEC_DRAFT_TOKENS, "Speculative tokens proposed (all proposers)"
        )
        self.spec_accepted = counter(
            mc.SPEC_ACCEPTED_TOKENS, "Speculative tokens accepted"
        )
        # per-proposer acceptance accounting (docs/36): proposer= is the
        # closed ngram|draft set, seeded below so the acceptance-rate rule
        # has both series from the first scrape
        self.spec_proposed_by = Counter(
            mc.SPEC_PROPOSED_TOKENS[: -len("_total")],
            "Speculative tokens proposed, by proposer (closed label set: "
            + ", ".join(mc.SPEC_PROPOSER_VALUES) + ")",
            [*names, "proposer"],
            registry=self.registry,
        )
        self.spec_accepted_by = Counter(
            mc.SPEC_ACCEPTED_BY_PROPOSER[: -len("_total")],
            "Speculative tokens accepted at verification, by proposer",
            [*names, "proposer"],
            registry=self.registry,
        )
        for proposer in mc.SPEC_PROPOSER_VALUES:
            self.spec_proposed_by.labels(**self._labels, proposer=proposer)
            self.spec_accepted_by.labels(**self._labels, proposer=proposer)
        self.prompt_tokens = counter(mc.PROMPT_TOKENS, "Prompt tokens processed")
        self.generation_tokens = counter(mc.GENERATION_TOKENS, "Tokens generated")
        self.requests_shed = counter(
            mc.REQUESTS_SHED, "Requests refused 429 by admission control"
        )
        self.deadline_expired = counter(
            mc.REQUESTS_DEADLINE_EXPIRED,
            "Requests shed at admission or aborted mid-decode on deadline",
        )
        self.draining = gauge(
            mc.ENGINE_DRAINING, "1 while the engine is draining"
        )
        # -- saturation & goodput (docs/29-saturation-slo.md) -------------
        self.seat_occupancy = gauge(
            mc.ENGINE_DECODE_SEAT_OCCUPANCY,
            "Decode-seat occupancy EWMA (rows in the resolved decode "
            "dispatch / max_num_seqs)",
        )
        self.padding_waste = gauge(
            mc.ENGINE_PADDING_WASTE_FRAC,
            "Fraction of device-computed token slots that were bucket "
            "padding (EWMA)",
        )
        self.achieved_flops = gauge(
            mc.ENGINE_ACHIEVED_FLOPS,
            "Achieved forward-pass FLOP/s (analytic model estimate, "
            "resolve-cadence EWMA)",
        )
        self.mfu = gauge(
            mc.ENGINE_MFU,
            "Model FLOPs utilization estimate (achieved / chip peak; 0 "
            "when the peak is unknown)",
        )
        self.kv_tier_usage = Gauge(
            mc.ENGINE_KV_TIER_USAGE,
            "KV occupancy per cache tier (hbm / host / disk / remote)",
            [*names, "tier"],
            registry=self.registry,
        )

        def pcounter(name: str, doc: str) -> Counter:
            base = name[: -len("_total")] if name.endswith("_total") else name
            return Counter(base, doc, [*names, "phase"],
                           registry=self.registry)

        self.step_tokens = pcounter(
            mc.ENGINE_STEP_TOKENS,
            "Useful tokens processed per phase (prefill chunk tokens / "
            "decode host-accepted tokens)",
        )
        self.padded_tokens = pcounter(
            mc.ENGINE_PADDED_TOKENS,
            "Device-computed token slots per phase, including bucket "
            "padding",
        )
        self.model_flops = counter(
            mc.ENGINE_MODEL_FLOPS,
            "Cumulative analytic forward-pass FLOPs",
        )
        self.goodput_tokens = counter(
            mc.GOODPUT_TOKENS,
            "Sampled tokens delivered to a successfully finished request",
        )
        self.wasted_tokens = Counter(
            mc.WASTED_TOKENS[: -len("_total")],
            "Sampled tokens wasted, by reason (closed label set: "
            + ", ".join(WASTE_REASONS) + ")",
            [*names, "reason"],
            registry=self.registry,
        )
        # seed the closed label sets at zero so every series exists from
        # the first scrape (rate() over a counter that appears mid-flight
        # misses its first increment)
        for phase in ("prefill", "decode"):
            self.step_tokens.labels(**self._labels, phase=phase)
            self.padded_tokens.labels(**self._labels, phase=phase)
        for reason in WASTE_REASONS:
            self.wasted_tokens.labels(**self._labels, reason=reason)
        self.goodput_tokens.labels(**self._labels)
        self.model_flops.labels(**self._labels)
        for tier in ("hbm", "host", "disk", "remote"):
            self.kv_tier_usage.labels(**self._labels, tier=tier)
        self.registry.register(_SaturationHistograms(self))
        # -- KV flow telemetry (docs/30-kv-flow-telemetry.md) -------------
        flabels = [*names, "tier", "direction"]

        def fcounter(name: str, doc: str) -> Counter:
            base = name[: -len("_total")] if name.endswith("_total") else name
            return Counter(base, doc, flabels, registry=self.registry)

        self.kv_transfer_bytes = fcounter(
            mc.KV_TRANSFER_BYTES,
            "Bytes moved between KV tiers, by tier and direction (in = "
            "toward HBM / hydration, out = offload)",
        )
        self.kv_transfer_blocks = fcounter(
            mc.KV_TRANSFER_BLOCKS,
            "KV blocks moved between tiers, by tier and direction",
        )
        self.kv_transfer_logical_bytes = fcounter(
            mc.KV_TRANSFER_LOGICAL_BYTES,
            "Logical (decoded) bytes the tier transfers represent — "
            "kv_transfer_bytes counts WIRE bytes, so with an at-rest KV "
            "codec (docs/38-kv-quantization.md) this series is larger by "
            "the compression ratio; identical without one",
        )
        self.kv_tier_compression = Gauge(
            mc.KV_TIER_COMPRESSION_RATIO,
            "At-rest KV codec effectiveness per (tier, direction): "
            "logical bytes / wire bytes moved (1.0 with no codec)",
            flabels,
            registry=self.registry,
        )
        self.kv_tier_bandwidth = Gauge(
            mc.KV_TIER_BANDWIDTH,
            "Recent-mean transfer bandwidth per (tier, direction) — the "
            "measured fetch-GB/s half of the compute-or-load hydration "
            "signal",
            flabels,
            registry=self.registry,
        )
        self.prefix_tokens = Counter(
            mc.REQUEST_PREFIX_TOKENS[: -len("_total")],
            "Prompt tokens by hydration source (closed label set: "
            + ", ".join(HYDRATION_SOURCES)
            + ") — an audited partition: the sum over sources equals the "
            "prompt tokens of admitted requests",
            [*names, "source"],
            registry=self.registry,
        )
        self.disk_stores = counter(
            mc.DISK_KV_STORES, "KV blocks persisted to the local-disk tier"
        )
        self.disk_loads = counter(
            mc.DISK_KV_LOADS, "KV blocks loaded from the local-disk tier"
        )
        self.kv_bytes_per_token = gauge(
            mc.KV_BYTES_PER_TOKEN,
            "Analytic KV bytes per token of this engine's pool "
            "(block_bytes / block_size) — the constant the router's "
            "priced route-vs-migrate scoring multiplies by matched prefix "
            "tokens to price a peer migration (docs/35-peer-kv-reuse.md)",
        )
        self.hydration_decisions = Counter(
            mc.KV_HYDRATION_DECISIONS[: -len("_total")],
            "Compute-or-load hydration planner chunk decisions (closed "
            "label set: " + ", ".join(mc.KV_HYDRATION_CHOICES)
            + ") — fallback_recompute = a load chunk that missed its "
            "fetch deadline or whose fetch failed",
            [*names, "choice"],
            registry=self.registry,
        )
        # seed the closed label sets at zero (same rationale as the
        # saturation series: rate() over a counter appearing mid-flight
        # misses its first increment)
        for tier in TRANSFER_TIERS:
            for direction in DIRECTIONS:
                fl = {**self._labels, "tier": tier, "direction": direction}
                self.kv_transfer_bytes.labels(**fl)
                self.kv_transfer_blocks.labels(**fl)
                self.kv_transfer_logical_bytes.labels(**fl)
                self.kv_tier_compression.labels(**fl).set(1.0)
                self.kv_tier_bandwidth.labels(**fl)
        for source in HYDRATION_SOURCES:
            self.prefix_tokens.labels(**self._labels, source=source)
        for choice in mc.KV_HYDRATION_CHOICES:
            self.hydration_decisions.labels(**self._labels, choice=choice)
        self.disk_stores.labels(**self._labels)
        self.disk_loads.labels(**self._labels)
        self.kv_bytes_per_token.labels(**self._labels)
        self.registry.register(_KVFlowHistograms(self))
        # -- fleet-coherence telemetry (docs/32-fleet-telemetry.md) --------
        # session-stickiness audit (fleet.SessionStickinessAudit): closed
        # reason set, seeded so both series exist from the first scrape
        self.stickiness_violations = Counter(
            mc.SESSION_STICKINESS_VIOLATIONS[: -len("_total")],
            "Session-affinity violations detected engine-side (closed "
            "reason set: " + ", ".join(mc.STICKINESS_REASON_VALUES)
            + ") — zero with one router replica and stable membership",
            [*names, "reason"],
            registry=self.registry,
        )
        for reason in mc.STICKINESS_REASON_VALUES:
            self.stickiness_violations.labels(**self._labels, reason=reason)
        # KV event publisher health: the PUBLISHER vantage on a failing
        # event path (a dying publisher used to be visible only as
        # controller-side resync storms)
        self.kv_event_batches = counter(
            mc.KV_EVENT_PUBLISH_BATCHES,
            "KV event batches POSTed to the index subscriber (incl. "
            "heartbeats and snapshots)",
        )
        self.kv_event_failures = counter(
            mc.KV_EVENT_PUBLISH_FAILURES,
            "KV event publish rounds that failed (transport fault or "
            "subscriber error)",
        )
        self.kv_event_queue_depth = gauge(
            mc.KV_EVENT_QUEUE_DEPTH,
            "KV events buffered awaiting flush (pinned at capacity = the "
            "publisher cannot keep up and a resync gap is imminent)",
        )
        self.kv_event_subscribers = gauge(
            mc.KV_EVENT_SUBSCRIBERS,
            "Subscribers this engine's KV event publisher fans batches out "
            "to (the controller, embedded-index router replicas, or both; "
            "0 = no publisher configured)",
        )
        self.kv_event_batches.labels(**self._labels)
        self.kv_event_failures.labels(**self._labels)
        self.kv_event_queue_depth.labels(**self._labels).set(0)
        self.kv_event_subscribers.labels(**self._labels).set(0)
        # -- flight recorder & thread-liveness watchdog (docs/37-flight-
        # recorder.md): per-loop heartbeat age (thread= closed set; 0 for
        # loops not running in this deployment) and stall episodes by kind
        self.thread_heartbeat_age = Gauge(
            mc.THREAD_HEARTBEAT_AGE,
            "Seconds since each long-lived loop's last liveness beat "
            "(closed thread set: " + ", ".join(mc.THREAD_NAME_VALUES)
            + "; 0 = loop not running in this deployment) — a busy loop "
            "whose age passes its threshold is a named wedge",
            [*names, "thread"],
            registry=self.registry,
        )
        self.step_stalls = Counter(
            mc.ENGINE_STEP_STALLS[: -len("_total")],
            "Watchdog stall episodes by kind (closed set: "
            + ", ".join(mc.STALL_KIND_VALUES)
            + ") — counted once per episode, not per check round",
            [*names, "kind"],
            registry=self.registry,
        )
        for thread in mc.THREAD_NAME_VALUES:
            self.thread_heartbeat_age.labels(
                **self._labels, thread=thread
            ).set(0)
        for kind in mc.STALL_KIND_VALUES:
            self.step_stalls.labels(**self._labels, kind=kind)
        # -- pool rebalancing (docs/40-pool-rebalancing.md): the engine's
        # live prefill/decode role — 1 on the current role, both 0 when
        # the engine serves no disaggregated pool. The router's stats
        # scraper follows this instead of the frozen helm model label.
        self.pool_role = Gauge(
            mc.POOL_ROLE,
            "Live prefill/decode pool role (closed role set: "
            + ", ".join(mc.POOL_ROLE_VALUES)
            + "; 1 on the current role, both 0 without one)",
            [*names, "role"],
            registry=self.registry,
        )
        for role in mc.POOL_ROLE_VALUES:
            self.pool_role.labels(**self._labels, role=role).set(0)
        # -- structured output (docs/41-structured-output.md): finished
        # constrained requests by outcome (closed set) plus the grammar
        # compile-time histogram (cache hits do not observe)
        self.structured_requests = Counter(
            mc.STRUCTURED_REQUESTS[: -len("_total")],
            "Finished structured-output requests by outcome (closed set: "
            + ", ".join(mc.STRUCTURED_OUTCOME_VALUES)
            + ") — valid means the terminal automaton state was accepting",
            [*names, "outcome"],
            registry=self.registry,
        )
        for outcome in mc.STRUCTURED_OUTCOME_VALUES:
            self.structured_requests.labels(**self._labels, outcome=outcome)
        self.grammar_build_time = Histogram(
            mc.GRAMMAR_BUILD_TIME,
            "Wall seconds to compile one grammar into token-class tables "
            "(schema -> byte-DFA -> token lift); grammar-cache hits skip "
            "this entirely",
            names,
            buckets=(
                0.001, 0.005, 0.02, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
            ),
            registry=self.registry,
        )
        # -- XLA compile telemetry (docs/42-compile-telemetry.md): program
        # builds by (phase, trigger), compile walls, and the program-cache
        # inventory/hit/miss view — every (phase, trigger) series seeded so
        # rate() works from the first mid-traffic compile
        self.engine_compiles = Counter(
            mc.ENGINE_COMPILES[: -len("_total")],
            "Program (and grammar-table) builds by phase and trigger "
            "(closed sets: " + ", ".join(mc.COMPILE_PHASE_VALUES) + " x "
            + ", ".join(mc.COMPILE_TRIGGER_VALUES)
            + ") — trigger=mid_traffic is a dispatch-path stall",
            [*names, "phase", "trigger"],
            registry=self.registry,
        )
        for phase in mc.COMPILE_PHASE_VALUES:
            for trigger in mc.COMPILE_TRIGGER_VALUES:
                self.engine_compiles.labels(
                    **self._labels, phase=phase, trigger=trigger
                )
        self.compile_seconds = Histogram(
            mc.ENGINE_COMPILE_SECONDS,
            "Wall seconds per program build (all triggers; real-model XLA "
            "compiles run 30-60s)",
            names,
            buckets=mc.COMPILE_SECONDS_BUCKETS,
            registry=self.registry,
        )
        self.compile_seconds.labels(**self._labels)
        self.program_cache_programs = Gauge(
            mc.ENGINE_PROGRAM_CACHE_PROGRAMS,
            "Programs in the CompileWatch inventory (compiled and "
            "retained)",
            names,
            registry=self.registry,
        )
        self.program_cache_programs.labels(**self._labels).set(0)
        self.program_cache_hits = Counter(
            mc.ENGINE_PROGRAM_CACHE_HITS[: -len("_total")],
            "Dispatches whose exact program key was already compiled",
            names,
            registry=self.registry,
        )
        self.program_cache_misses = Counter(
            mc.ENGINE_PROGRAM_CACHE_MISSES[: -len("_total")],
            "Dispatches that padded up to a dominating program or "
            "compiled synchronously",
            names,
            registry=self.registry,
        )
        self.compile_storms = Counter(
            mc.ENGINE_COMPILE_STORMS[: -len("_total")],
            "Recompile-storm episodes (threshold mid-traffic compiles "
            "inside the sliding window; one bump per episode)",
            names,
            registry=self.registry,
        )
        for c in (self.program_cache_hits, self.program_cache_misses,
                  self.compile_storms):
            c.labels(**self._labels)
        # -- multi-tenant QoS (docs/27-multitenancy.md): tenant-labeled
        # series; cardinality bounded by qos.TenantAccounting.MAX_TENANTS
        tlabels = [*names, "tenant"]

        def tcounter(name: str, doc: str) -> Counter:
            base = name[: -len("_total")] if name.endswith("_total") else name
            return Counter(base, doc, tlabels, registry=self.registry)

        self.tenant_requests = tcounter(
            mc.TENANT_REQUESTS, "Requests admitted per tenant"
        )
        self.tenant_tokens = tcounter(
            mc.TENANT_GENERATION_TOKENS, "Tokens generated per tenant"
        )
        self.tenant_shed = tcounter(
            mc.TENANT_SHED,
            "Requests shed per tenant (admission refusals + lowest-"
            "priority-first queue evictions)",
        )
        self.tenant_queue_wait = Histogram(
            mc.TENANT_QUEUE_WAIT,
            "Seconds from submission to first scheduler seat, per tenant",
            tlabels,
            buckets=(
                0.005, 0.02, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                30.0, 60.0,
            ),
            registry=self.registry,
        )
        # -- per-request phase histograms (docs/28-request-tracing.md):
        # observed at request finish from the lifecycle stamps the tracing
        # spine attributes, with trace-id exemplars (OpenMetrics exposition)
        def phase_hist(name: str, doc: str) -> Histogram:
            return Histogram(
                name, doc, names,
                buckets=mc.REQUEST_PHASE_BUCKETS, registry=self.registry,
            )

        self.request_ttft = phase_hist(
            mc.REQUEST_TTFT, "Arrival to first generated token"
        )
        self.request_e2e = phase_hist(
            mc.REQUEST_E2E, "Arrival to request finish"
        )
        self.request_queue_wait = phase_hist(
            mc.REQUEST_QUEUE_WAIT, "Arrival to first scheduler seat"
        )
        self.request_prefill = phase_hist(
            mc.REQUEST_PREFILL, "First seat to first generated token"
        )
        self.request_decode = phase_hist(
            mc.REQUEST_DECODE, "First generated token to finish"
        )
        self._counter_values: dict[str, int] = {}

    @staticmethod
    def phase_durations(phases: dict) -> dict[str, float]:
        """{metric-suffix: seconds} from a terminal output's lifecycle
        stamps (engine.RequestOutput.phase_times). Phases that never
        happened (shed before a seat, no token before abort) are absent —
        a refusal must not log a 0-second decode."""
        arrival = phases.get("arrival")
        seat = phases.get("first_seat")
        first_tok = phases.get("first_token")
        finish = phases.get("finish")
        if arrival is None or finish is None:
            return {}
        out = {"e2e": max(0.0, finish - arrival)}
        if seat is not None:
            out["queue_wait"] = max(0.0, seat - arrival)
        if first_tok is not None:
            out["ttft"] = max(0.0, first_tok - arrival)
            out["decode"] = max(0.0, finish - first_tok)
            if seat is not None:
                out["prefill"] = max(0.0, first_tok - seat)
        return out

    def observe_request(self, phases: dict, trace_id: str | None = None) -> None:
        """Feed one finished request's phase durations into the contract
        histograms, tagging each bucket with the trace id as an exemplar
        so a dashboard outlier links straight to /debug/requests?rid=."""
        exemplar = {"trace_id": trace_id} if trace_id else None
        hists = {
            "ttft": self.request_ttft,
            "e2e": self.request_e2e,
            "queue_wait": self.request_queue_wait,
            "prefill": self.request_prefill,
            "decode": self.request_decode,
        }
        for key, seconds in self.phase_durations(phases).items():
            hists[key].labels(**self._labels).observe(seconds, exemplar=exemplar)

    def update(self, s: EngineStatsSnapshot) -> None:
        lb = self._labels
        self.num_running.labels(**lb).set(s.num_requests_running)
        self.num_waiting.labels(**lb).set(s.num_requests_waiting)
        self.kv_usage.labels(**lb).set(s.kv_usage_perc)
        for group, frac in s.kv_group_usage.items():
            self.kv_group_usage.labels(**lb, group=group).set(frac)
        self.kv_window_share.labels(**lb).set(s.kv_window_share)
        self._bump(self.kv_window_given_back, "win_back",
                   s.kv_window_pages_given_back)
        self.state_bytes.labels(**lb).set(s.state_bytes)
        self.state_slots_in_use.labels(**lb).set(s.state_slots_in_use)
        self.state_share.labels(**lb).set(s.state_share)
        self.latent_bytes.labels(**lb).set(s.latent_bytes)
        self.index_bytes.labels(**lb).set(s.index_bytes)
        for key, metric in self.block_diffusion.items():
            self._bump(metric, key, s.block_diffusion.get(key, 0))
        self.prefix_hit_rate.labels(**lb).set(s.prefix_cache_hit_rate)
        self._bump(self.prefix_hits, "hits", s.prefix_cache_hits)
        self._bump(self.prefix_queries, "queries", s.prefix_cache_queries)
        self._bump(self.preemptions, "preempt", s.num_preemptions)
        self.host_kv_usage.labels(**lb).set(s.host_kv_usage_perc)
        self.step_overlap.labels(**lb).set(s.step_overlap_frac)
        self._bump(self.host_offloads, "host_off", s.host_kv_offloads)
        self._bump(self.host_reloads, "host_re", s.host_kv_reloads)
        self._bump(self.remote_stores, "remote_store", s.remote_kv_stores)
        self._bump(
            self.remote_fetches, "remote_fetch", s.remote_kv_fetched_blocks
        )
        self._bump(self.spec_draft, "spec_draft", s.spec_draft_tokens)
        self._bump(self.spec_accepted, "spec_acc", s.spec_accepted_tokens)
        for proposer in mc.SPEC_PROPOSER_VALUES:
            pl = {**lb, "proposer": proposer}
            self._bump_labeled(
                self.spec_proposed_by, f"spec_prop:{proposer}",
                int((s.spec_proposed_by or {}).get(proposer, 0)), pl,
            )
            self._bump_labeled(
                self.spec_accepted_by, f"spec_accby:{proposer}",
                int((s.spec_accepted_by or {}).get(proposer, 0)), pl,
            )
        self._bump(self.prompt_tokens, "prompt", s.prompt_tokens)
        self._bump(self.generation_tokens, "gen", s.generation_tokens)
        self._bump(self.requests_shed, "shed", s.requests_shed)
        self._bump(
            self.deadline_expired, "deadline", s.requests_deadline_expired
        )
        self.draining.labels(**lb).set(1 if s.draining else 0)
        for tenant, c in s.tenants.items():
            tl = {**lb, "tenant": tenant}
            self._bump_labeled(
                self.tenant_requests, f"t_req:{tenant}",
                int(c.get("requests", 0)), tl,
            )
            self._bump_labeled(
                self.tenant_tokens, f"t_tok:{tenant}",
                int(c.get("generation_tokens", 0)), tl,
            )
            self._bump_labeled(
                self.tenant_shed, f"t_shed:{tenant}",
                int(c.get("shed", 0)), tl,
            )
        for tenant, seconds in s.tenant_queue_waits:
            # observations were DRAINED from the accounting by stats() —
            # each lands in the histogram exactly once
            self.tenant_queue_wait.labels(**lb, tenant=tenant).observe(
                seconds
            )
        # -- structured output (docs/41-structured-output.md) --------------
        for outcome in mc.STRUCTURED_OUTCOME_VALUES:
            self._bump_labeled(
                self.structured_requests, f"structured:{outcome}",
                int((s.structured_outcomes or {}).get(outcome, 0)),
                {**lb, "outcome": outcome},
            )
        for seconds in (s.grammar_build_times or []):
            # drained from the grammar cache by stats() — each compile
            # lands in the histogram exactly once
            self.grammar_build_time.labels(**lb).observe(seconds)
        # -- XLA compile telemetry (docs/42-compile-telemetry.md) ----------
        comp = s.compile or {}
        if comp:
            self.program_cache_programs.labels(**lb).set(
                int(comp.get("programs", 0))
            )
            builds = comp.get("compiles") or {}
            for phase in mc.COMPILE_PHASE_VALUES:
                for trigger in mc.COMPILE_TRIGGER_VALUES:
                    self._bump_labeled(
                        self.engine_compiles, f"compile:{phase}/{trigger}",
                        int(builds.get(f"{phase}/{trigger}", 0)),
                        {**lb, "phase": phase, "trigger": trigger},
                    )
            for seconds in (comp.get("walls") or []):
                # drained from the watch by stats() — one observation per
                # build
                self.compile_seconds.labels(**lb).observe(seconds)
            self._bump(
                self.program_cache_hits, "pc_hits", int(comp.get("hits", 0))
            )
            self._bump(
                self.program_cache_misses, "pc_miss",
                int(comp.get("misses", 0)),
            )
            self._bump(
                self.compile_storms, "storms", int(comp.get("storms", 0))
            )
        # -- saturation & goodput (docs/29-saturation-slo.md) -------------
        sat = s.saturation or {}
        self.saturation = sat  # histogram collector reads this at scrape
        self.seat_occupancy.labels(**lb).set(
            sat.get("decode_seat_occupancy", 0.0)
        )
        self.padding_waste.labels(**lb).set(
            sat.get("padding_waste_frac", 0.0)
        )
        self.achieved_flops.labels(**lb).set(
            sat.get("achieved_flops_per_s", 0.0)
        )
        self.mfu.labels(**lb).set(sat.get("mfu", 0.0))
        for tier, frac in (sat.get("kv_tiers") or {}).items():
            self.kv_tier_usage.labels(**lb, tier=tier).set(frac)
        for phase in ("prefill", "decode"):
            self._bump_labeled(
                self.step_tokens, f"step_tok:{phase}",
                int((sat.get("step_tokens") or {}).get(phase, 0)),
                {**lb, "phase": phase},
            )
            self._bump_labeled(
                self.padded_tokens, f"pad_tok:{phase}",
                int((sat.get("padded_tokens") or {}).get(phase, 0)),
                {**lb, "phase": phase},
            )
        self._bump(
            self.model_flops, "model_flops",
            sat.get("model_flops_total", 0.0),
        )
        good = sat.get("goodput") or {}
        self._bump(self.goodput_tokens, "goodput", good.get("delivered", 0))
        wasted = good.get("wasted") or {}
        for reason in WASTE_REASONS:
            # the CLOSED reason set bounds label cardinality by
            # construction — every reason series exists from first scrape
            self._bump_labeled(
                self.wasted_tokens, f"wasted:{reason}",
                int(wasted.get(reason, 0)), {**lb, "reason": reason},
            )
        # -- KV flow telemetry (docs/30-kv-flow-telemetry.md) -------------
        flow = s.kv_flow or {}
        self.kv_flow = flow  # histogram collector reads this at scrape
        fbytes = flow.get("bytes") or {}
        fblocks = flow.get("blocks") or {}
        flogical = flow.get("logical_bytes") or {}
        fratio = flow.get("compression_ratio") or {}
        fbw = flow.get("bandwidth_bytes_per_s") or {}
        fmeas = flow.get("bandwidth_measured") or {}
        for tier in TRANSFER_TIERS:
            for direction in DIRECTIONS:
                key = f"{tier}/{direction}"
                fl = {**lb, "tier": tier, "direction": direction}
                self._bump_labeled(
                    self.kv_transfer_bytes, f"kvb:{key}",
                    int(fbytes.get(key, 0)), fl,
                )
                self._bump_labeled(
                    self.kv_transfer_blocks, f"kvn:{key}",
                    int(fblocks.get(key, 0)), fl,
                )
                self._bump_labeled(
                    self.kv_transfer_logical_bytes, f"kvl:{key}",
                    int(flogical.get(key, 0)), fl,
                )
                # logical/wire over the whole run (1.0 with no codec or no
                # bytes) — the at-rest codec's effectiveness gauge
                self.kv_tier_compression.labels(**fl).set(
                    fratio.get(key, 1.0)
                )
                # gauge gated on the TierBandwidth sample floor: below it
                # the estimate is one tiny transfer's noise, and scrapers
                # (the router's migrate pricing above all) must read 0 =
                # "not measured", exactly what the planner trusts
                self.kv_tier_bandwidth.labels(**fl).set(
                    fbw.get(key, 0.0) if fmeas.get(key) else 0.0
                )
        hyd = flow.get("hydration") or {}
        for source in HYDRATION_SOURCES:
            self._bump_labeled(
                self.prefix_tokens, f"hyd:{source}",
                int(hyd.get(source, 0)), {**lb, "source": source},
            )
        decisions = flow.get("decisions") or {}
        for choice in mc.KV_HYDRATION_CHOICES:
            self._bump_labeled(
                self.hydration_decisions, f"hyd_dec:{choice}",
                int(decisions.get(choice, 0)), {**lb, "choice": choice},
            )
        self._bump(self.disk_stores, "disk_store", s.disk_kv_stores)
        self._bump(self.disk_loads, "disk_load", s.disk_kv_loads)
        self.kv_bytes_per_token.labels(**lb).set(s.kv_bytes_per_token)

    def update_fleet_health(
        self,
        publish_batches: int = 0,
        publish_failures: int = 0,
        pending_depth: int = 0,
        subscribers: int = 0,
        stickiness: dict[str, int] | None = None,
    ) -> None:
        """Fleet-coherence series owned by the HTTP server rather than the
        engine snapshot (docs/32-fleet-telemetry.md): KV event publisher
        health counters, the fan-out subscriber count, and the
        stickiness-audit violation counts, bumped delta-style from their
        monotonic owners at scrape time."""
        self._bump(self.kv_event_batches, "kvev_batches", publish_batches)
        self._bump(self.kv_event_failures, "kvev_failures", publish_failures)
        self.kv_event_queue_depth.labels(**self._labels).set(pending_depth)
        self.kv_event_subscribers.labels(**self._labels).set(subscribers)
        for reason, total in (stickiness or {}).items():
            if reason in mc.STICKINESS_REASON_VALUES:
                self._bump_labeled(
                    self.stickiness_violations, f"sticky:{reason}",
                    int(total), {**self._labels, "reason": reason},
                )

    def update_liveness(
        self,
        ages: dict[str, float] | None = None,
        stall_counts: dict[str, int] | None = None,
    ) -> None:
        """Thread-liveness series (docs/37-flight-recorder.md), computed by
        the EXPORTER from the registry's beat stamps at scrape time — a
        dead watchdog cannot freeze its own age gauge. Unregistered loops
        read 0 (not running here); stall counts bump delta-style from the
        watchdog's monotonic episode counters."""
        ages = ages or {}
        for thread in mc.THREAD_NAME_VALUES:
            self.thread_heartbeat_age.labels(
                **self._labels, thread=thread
            ).set(ages.get(thread, 0.0))
        for kind, total in (stall_counts or {}).items():
            if kind in mc.STALL_KIND_VALUES:
                self._bump_labeled(
                    self.step_stalls, f"stall:{kind}", int(total),
                    {**self._labels, "kind": kind},
                )

    def set_pool_role(self, role: str | None) -> None:
        """Advertise the engine's live pool role (docs/40-pool-rebalancing
        .md): 1 on `role`, 0 on the rest of the closed set; None clears
        both (the engine serves no disaggregated pool)."""
        for value in mc.POOL_ROLE_VALUES:
            self.pool_role.labels(**self._labels, role=value).set(
                1 if value == role else 0
            )

    def _bump(self, counter: Counter, key: str, total: int) -> None:
        self._bump_labeled(counter, key, total, self._labels)

    def _bump_labeled(
        self, counter: Counter, key: str, total: int, labels: dict
    ) -> None:
        prev = self._counter_values.get(key, 0)
        if total > prev:
            counter.labels(**labels).inc(total - prev)
            self._counter_values[key] = total

    def render(
        self, s: EngineStatsSnapshot, openmetrics: bool = False
    ) -> bytes:
        self.update(s)
        if openmetrics:
            return om_exposition.generate_latest(self.registry)
        return generate_latest(self.registry)
