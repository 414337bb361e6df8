"""The engine↔router metrics contract.

The reference router scrapes vLLM metric names from each engine's /metrics
(`vllm:num_requests_running`, `vllm:gpu_cache_usage_perc`,
`vllm:gpu_prefix_cache_hit_rate`, ... — src/vllm_router/stats/
engine_stats.py:63-76) and the observability stack / KEDA autoscaling key off
them (observability/prom-adapter.yaml:19-31). This module is the single
source of truth for the TPU equivalents: HBM paged-KV metrics instead of GPU
KV metrics. Both the engine exporter (engine/metrics.py) and the router
scraper (router/stats/engine_stats.py) import these names.
"""

# gauges
NUM_REQUESTS_RUNNING = "tpu:num_requests_running"
NUM_REQUESTS_WAITING = "tpu:num_requests_waiting"
HBM_KV_USAGE_PERC = "tpu:hbm_kv_usage_perc"
# page groups (engine/kv_cache.py: a model with window layers keeps their
# pages apart). HBM_KV_USAGE_PERC stays ONE series, the fuller group's
# share (the one that would preempt); the per-group shares ride a name of
# their own (group= closed set full|window) so that a reader that sums a
# name's series still reads one pool. HBM_KV_WINDOW_SHARE: the window
# group's resident pages / the pages the same live rows would hold in
# those layers at full length (0 without a window group).
HBM_KV_GROUP_USAGE_PERC = "tpu:hbm_kv_group_usage_perc"
HBM_KV_WINDOW_SHARE = "tpu:hbm_kv_window_share"
# the other KIND of cache (engine/kv_cache.py:StateSlots; 0 for a model no
# layer of which holds a recurrent state): the state pools' bytes in use
# (slots held x the bytes a slot holds across the linear layers), the slots
# held, and the state's share of the cache bytes in use — state bytes in
# use / (state + page bytes in use). HBM_KV_USAGE_PERC stays the page
# group's share: slots cannot run out before seats do.
HBM_STATE_BYTES = "tpu:hbm_state_bytes"
HBM_STATE_SLOTS_IN_USE = "tpu:hbm_state_slots_in_use"
HBM_STATE_SHARE = "tpu:hbm_state_share"
# ... and beside them the bytes of LATENT pages in use (pages held x the
# bytes a page holds across the latent layers, engine/memory.py:
# kv_block_bytes; 0 for a model no layer of which is a latent layer): with
# HBM_STATE_BYTES the two caches of a model whose layers are latent or
# recurrent.
HBM_LATENT_BYTES = "tpu:hbm_latent_bytes"
# ... and of INDEX KEYS in use beside them (pages held x the bytes a page's
# index keys take across the layers that own an indexer, engine/memory.py:
# index_block_bytes; 0 for a model without learned sparse attention). The
# latent gauge counts the latents alone: the two add up to the pool's bytes
# in use.
HBM_INDEX_BYTES = "tpu:hbm_index_bytes"
# generation by diffusion over blocks (ModelConfig.block_diffusion;
# docs/44-block-diffusion.md; all 0 for every other model): what the decode
# programs' windows of block passes counted on the device. A STEP of such a
# model is a forward pass over each row's open block, not a token:
# GENERATION_TOKENS stays tokens DELIVERED. Forward passes run; rows x
# passes; those in which a row's block was already final beyond its one
# commit pass (what running rows in lockstep costs); blocks committed;
# tokens unmasked; and those the confidence threshold unmasked beyond the
# schedule's count.
BD_PASSES = "tpu:block_diffusion_passes_total"
BD_ROW_PASSES = "tpu:block_diffusion_row_passes_total"
BD_REPEAT_ROW_PASSES = "tpu:block_diffusion_repeat_row_passes_total"
BD_BLOCKS = "tpu:block_diffusion_blocks_total"
BD_TOKENS = "tpu:block_diffusion_tokens_total"
BD_THRESHOLD_TOKENS = "tpu:block_diffusion_threshold_tokens_total"
PREFIX_CACHE_HIT_RATE = "tpu:hbm_prefix_cache_hit_rate"
# host-RAM offload tier (LMCache CPU-offload equivalent)
HOST_KV_USAGE_PERC = "tpu:host_kv_usage_perc"
# pipelined step loop (engine/engine.py): fraction of step-loop wall
# time in which host scheduling/postprocess overlapped an in-flight
# device step
STEP_OVERLAP_FRAC = "tpu:engine_step_overlap_frac"

# counters
PREFIX_CACHE_HITS = "tpu:hbm_prefix_cache_hits_total"
PREFIX_CACHE_QUERIES = "tpu:hbm_prefix_cache_queries_total"
NUM_PREEMPTIONS = "tpu:num_preemptions_total"
# window-group pages given back behind live rows (engine/kv_cache.py)
HBM_KV_WINDOW_PAGES_GIVEN_BACK = "tpu:hbm_kv_window_pages_given_back_total"
PROMPT_TOKENS = "tpu:prompt_tokens_total"
GENERATION_TOKENS = "tpu:generation_tokens_total"
HOST_KV_OFFLOADS = "tpu:host_kv_offloaded_blocks_total"
HOST_KV_RELOADS = "tpu:host_kv_reloaded_blocks_total"
# remote KV store tier (LMCache remote-server equivalent, kvstore/)
REMOTE_KV_STORES = "tpu:remote_kv_stored_blocks_total"
REMOTE_KV_FETCHES = "tpu:remote_kv_fetched_blocks_total"
# speculative decoding (vLLM parity: vllm:spec_decode_num_*_tokens) —
# aggregate totals across proposers
SPEC_DRAFT_TOKENS = "tpu:spec_decode_num_draft_tokens_total"
SPEC_ACCEPTED_TOKENS = "tpu:spec_decode_num_accepted_tokens_total"
# per-proposer acceptance accounting (docs/36-speculative-decoding.md):
# proposer= is a CLOSED label set (ngram = prompt lookup, draft = the
# draft-model proposer), exporter-seeded at zero. The acceptance-rate
# recording rule tpu:spec_decode_acceptance:rate5m divides these.
SPEC_PROPOSED_TOKENS = "tpu:spec_decode_proposed_tokens_total"
SPEC_ACCEPTED_BY_PROPOSER = "tpu:spec_decode_accepted_tokens_total"
SPEC_PROPOSER_VALUES = ("ngram", "draft")

# -- request-lifecycle robustness (docs/26-robustness.md) --------------------
# admission control: requests refused with 429 + Retry-After because the
# waiting queue / queued-token watermark was full (load shedding)
REQUESTS_SHED = "tpu:requests_shed_total"
# deadline enforcement: requests rejected at admission ("would queue past
# deadline") or aborted mid-decode after their deadline expired
REQUESTS_DEADLINE_EXPIRED = "tpu:requests_deadline_expired_total"
# 1 while the engine is draining (admissions stopped, in-flight finishing)
ENGINE_DRAINING = "tpu:engine_draining"

# -- multi-tenant QoS (docs/27-multitenancy.md). All labeled tenant= with
# cardinality bounded by the tenant table size (qos.TenantAccounting caps
# engine-side ids minted from headers; overflow aggregates under
# tenant="_overflow"). The engine exports requests/tokens/shed and the
# queue-wait histogram; the router exports requests/prompt-tokens admitted
# through the QoS gate plus per-tenant throttles (429s that never reached
# an engine). Shared names: dashboards key off one series wherever the
# enforcement happened.
TENANT_REQUESTS = "tpu:tenant_requests_total"
TENANT_PROMPT_TOKENS = "tpu:tenant_prompt_tokens_total"
TENANT_GENERATION_TOKENS = "tpu:tenant_generation_tokens_total"
# engine-side: admission refusals + queue evictions, lowest-priority-first
TENANT_SHED = "tpu:tenant_shed_total"
# router-side: per-tenant token-bucket / concurrency refusals (429 +
# per-tenant Retry-After, distinct from the engine's global-shed path)
TENANT_THROTTLED = "tpu:tenant_throttled_total"
# engine-side histogram: seconds from submission to first scheduler seat
TENANT_QUEUE_WAIT = "tpu:tenant_queue_wait_seconds"

TENANT_ENGINE_COUNTERS = (
    TENANT_REQUESTS,
    TENANT_GENERATION_TOKENS,
    TENANT_SHED,
)
TENANT_ROUTER_COUNTERS = (
    TENANT_REQUESTS,
    TENANT_PROMPT_TOKENS,
    TENANT_THROTTLED,
)

# -- per-request latency histograms (docs/28-request-tracing.md) ------------
# Observed at request finish from the tracing spine's phase attribution,
# with trace-id exemplars (visible under the OpenMetrics exposition —
# GET /metrics?format=openmetrics; deliberately a query param, not Accept
# negotiation, because OpenMetrics rewrites the `tpu:` prefix to `tpu_`
# and honoring Prometheus's default Accept would rename the whole scrape
# contract). The ENGINE exports
# all five (its clock sees the whole lifecycle: admission → first seat →
# first token → finish); the ROUTER exports TTFT and E2E from its own
# vantage (client-visible latency, including routing + proxy overhead).
REQUEST_TTFT = "tpu:request_ttft_seconds"
REQUEST_E2E = "tpu:request_e2e_seconds"
REQUEST_QUEUE_WAIT = "tpu:request_queue_wait_seconds"
REQUEST_PREFILL = "tpu:request_prefill_seconds"
REQUEST_DECODE = "tpu:request_decode_seconds"

REQUEST_PHASE_HISTOGRAMS = (
    REQUEST_TTFT,
    REQUEST_E2E,
    REQUEST_QUEUE_WAIT,
    REQUEST_PREFILL,
    REQUEST_DECODE,
)
# shared boundaries wherever a phase histogram lives (router and engine
# export the same names; dashboards aggregate across both)
REQUEST_PHASE_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
)
# inter-token latency (TPOT), ROUTER vantage only: the gap between
# consecutive streamed chunks as the client sees them — the client-visible
# SLO the engine-side decode histogram cannot capture (proxy + network
# included). NOT in REQUEST_PHASE_HISTOGRAMS: that tuple is the per-engine
# scrape set; this one lives on the router like its TTFT/E2E.
REQUEST_ITL = "tpu:request_itl_seconds"

# -- saturation & goodput (docs/29-saturation-slo.md) -----------------------
# Per-step utilization accounting from the engine step loop
# (engine/saturation.StepMeter) — the "why isn't the chip full" signals the
# SLO rule pack (observability/rules/) and the KEDA/prom-adapter autoscaling
# path key off. Gauges are resolve-cadence EWMAs (~10 s time constant).
ENGINE_DECODE_SEAT_OCCUPANCY = "tpu:engine_decode_seat_occupancy"
ENGINE_PADDING_WASTE_FRAC = "tpu:engine_padding_waste_frac"
# analytic-model achieved FLOP/s and the MFU estimate (achieved / chip peak;
# 0 when the peak is unknown — CPU backend or unrecognized device kind)
ENGINE_ACHIEVED_FLOPS = "tpu:engine_achieved_flops_per_s"
ENGINE_MFU = "tpu:engine_mfu"
# per-tier KV occupancy, labeled tier="hbm"|"host"|"disk"|"remote" (remote
# is the store-reported fill fraction piggybacked on PUT acks; 0 until the
# first ack lands)
ENGINE_KV_TIER_USAGE = "tpu:engine_kv_tier_usage_perc"
# token split + padding accounting, labeled phase="prefill"|"decode":
# step tokens are USEFUL tokens processed (prefill chunk tokens / decode
# host-accepted tokens); padded tokens are the device-computed token slots
# including bucket padding — padding-waste rate = 1 - step/padded by rule
ENGINE_STEP_TOKENS = "tpu:engine_step_tokens_total"
ENGINE_PADDED_TOKENS = "tpu:engine_padded_tokens_total"
# cumulative analytic forward-pass FLOPs (rate() ÷ chip peak = MFU by rule)
ENGINE_MODEL_FLOPS = "tpu:engine_model_flops_total"
# per-resolved-step distributions (histograms): decode-seat occupancy
# fraction, and the resolve-cadence wall per step labeled phase=
ENGINE_STEP_OCCUPANCY = "tpu:engine_step_occupancy"
ENGINE_STEP_WALL = "tpu:engine_step_wall_seconds"
# goodput ledger (engine/saturation.GoodputLedger): every device-sampled
# token classified exactly once — delivered + wasted == sampled at
# quiescence. reason= is the CLOSED WASTE_REASON_VALUES set below, the
# single definition saturation.WASTE_REASONS aliases (semantics of each
# reason are documented there).
GOODPUT_TOKENS = "tpu:goodput_tokens_total"
WASTED_TOKENS = "tpu:wasted_tokens_total"
WASTE_REASON_VALUES = (
    "rollback",
    "preempted_recompute",
    "deadline_expired",
    "severed",
    "shed_evicted",
    "overshoot",
)
# router-side: streams severed after headers (engine died mid-stream; the
# truncated transfer is the client's only honest signal). Request-level —
# the router can't see token boundaries; the engine-side ledger carries the
# token cost of torn streams under wasted{reason="severed"}.
ROUTER_SEVERED_STREAMS = "tpu:router_severed_streams_total"

SATURATION_GAUGES = (
    ENGINE_DECODE_SEAT_OCCUPANCY,
    ENGINE_PADDING_WASTE_FRAC,
    ENGINE_ACHIEVED_FLOPS,
    ENGINE_MFU,
    ENGINE_KV_TIER_USAGE,
)
SATURATION_COUNTERS = (
    ENGINE_STEP_TOKENS,
    ENGINE_PADDED_TOKENS,
    ENGINE_MODEL_FLOPS,
    GOODPUT_TOKENS,
    WASTED_TOKENS,
)
SATURATION_HISTOGRAMS = (
    ENGINE_STEP_OCCUPANCY,
    ENGINE_STEP_WALL,
)

# -- KV-hierarchy flow telemetry (docs/30-kv-flow-telemetry.md) -------------
# Per-tier transfer meters (engine/kv_flow.KVFlowMeter): every tier move —
# host-ring offload/reload, disk store/load, remote put/fetch, device-path
# PD transfer, peer-engine fetch — records bytes, blocks and wall latency.
# Labels are CLOSED sets (cardinality bounded by construction, series
# seeded at zero): tier= names the NON-HBM side of the hop, direction= is
# relative to HBM ("in" = toward the device pool / hydration, "out" =
# away / offload). "peer" is another engine's HBM/host tiers reached over
# /kv/peer_fetch (docs/35-peer-kv-reuse.md): "in" = blocks pulled FROM a
# peer, "out" = blocks this engine served TO a peer.
KV_TRANSFER_TIERS = ("host", "disk", "remote", "device", "peer")
KV_TRANSFER_DIRECTIONS = ("in", "out")
KV_TRANSFER_BYTES = "tpu:kv_transfer_bytes_total"
KV_TRANSFER_BLOCKS = "tpu:kv_transfer_blocks_total"
# at-rest KV quantization (docs/38-kv-quantization.md): KV_TRANSFER_BYTES
# counts WIRE bytes (what actually crossed the hop — int4+scales / fp8
# payloads under --kv-at-rest-codec); this pair exposes the logical side.
# logical_bytes = the decoded fp16/bf16 bytes those transfers represent;
# the gauge is their time-decayed ratio logical/wire per (tier, direction)
# (1.0 with no codec — the exporter seeds every combination).
KV_TRANSFER_LOGICAL_BYTES = "tpu:kv_transfer_logical_bytes_total"
KV_TIER_COMPRESSION_RATIO = "tpu:kv_tier_compression_ratio"
# histogram: wall seconds per transfer batch, labeled tier=/direction=
KV_TRANSFER_SECONDS = "tpu:kv_transfer_seconds"
# gauge: time-decayed recent-mean transfer bandwidth per (tier, direction)
# (engine/kv_flow.TierBandwidth) — the measured fetch-GB/s half of the
# compute-or-load hydration signal (LLMEngine.hydration_signal, ROADMAP 3)
KV_TIER_BANDWIDTH = "tpu:kv_tier_bandwidth_bytes_per_s"
# per-request hydration attribution: every admitted request's prompt
# tokens classified EXACTLY once by where their KV came from —
# hbm_hit + host_reload + disk_load + remote_fetch + peer_fetch +
# recomputed == prompt_tokens (same audited-partition discipline as the
# goodput ledger)
# ("peer_fetch" = blocks pulled from another engine's tiers over the
# peer-fetch path, docs/35-peer-kv-reuse.md)
KV_HYDRATION_SOURCES = (
    "hbm_hit", "host_reload", "disk_load", "remote_fetch", "peer_fetch",
    "recomputed",
)
REQUEST_PREFIX_TOKENS = "tpu:request_prefix_tokens_total"
# disk-tier block counters (the host ring has HOST_KV_*, the remote store
# REMOTE_KV_* — the disk rung was dark before this pair existed)
DISK_KV_STORES = "tpu:disk_kv_stored_blocks_total"
DISK_KV_LOADS = "tpu:disk_kv_loaded_blocks_total"
# compute-or-load hydration planner (docs/31-hydration-planner.md):
# per-CHUNK decisions over lower-tier-resident prefix runs. "load" =
# async tier fetch pipelined with prefill of the recomputed head;
# "recompute" = the chunk's measured fetch cost lost to prefill FLOP/s
# (or its tier sits below the TierBandwidth sample floor in forced
# mode); "fallback_recompute" = a load chunk that missed its deadline or
# whose fetch failed, flipped back to compute at the prefill boundary —
# an ADDITIONAL event on top of that chunk's plan-time "load" count, so
# share-of-plan rules must use {choice=~"load|recompute"} as their
# denominator (tpu:kv_hydration_load_share:rate5m does).
KV_HYDRATION_DECISIONS = "tpu:kv_hydration_decision_total"
KV_HYDRATION_CHOICES = ("load", "recompute", "fallback_recompute")

# -- peer-engine KV tier (docs/35-peer-kv-reuse.md) -------------------------
# gauge: analytic KV bytes per token of this engine's pool (block_bytes /
# block_size — a per-config constant). The router's priced route-vs-migrate
# scoring multiplies it by the matched prefix length and divides by the
# fleet-reported peer fetch bandwidth (tpu:kv_tier_bandwidth_bytes_per_s
# {tier="peer",direction="in"}) to price a migration in seconds without
# knowing the model.
KV_BYTES_PER_TOKEN = "tpu:kv_bytes_per_token"
# router counter labeled decision=: how the KV-aware policy resolved each
# owner-found request under --kv-migrate-scoring priced. "owner" = follow
# the prefix owner (affinity); "migrate" = route to the least-loaded engine
# and stamp the owner hint upstream so the target's hydration planner pulls
# the prefix over the peer tier instead of recomputing it.
ROUTER_KV_MIGRATE_DECISIONS = "tpu:router_kv_migrate_decisions_total"
KV_MIGRATE_DECISION_VALUES = ("owner", "migrate")

# Closed label sets per metric, the single source of truth the exporters
# seed from and tools/check_metrics_contract.py validates BOTH ways: the
# exporter registries must render exactly these values, and any literal
# label matcher in the dashboard / rule pack must name one of them (a
# typo'd tier="dsk" used to pass the checker silently). Open-but-bounded
# labels (tenant=, model_name=) are deliberately absent.
METRIC_LABEL_VALUES: dict[str, dict[str, tuple[str, ...]]] = {
    KV_TRANSFER_BYTES: {
        "tier": KV_TRANSFER_TIERS, "direction": KV_TRANSFER_DIRECTIONS,
    },
    KV_TRANSFER_BLOCKS: {
        "tier": KV_TRANSFER_TIERS, "direction": KV_TRANSFER_DIRECTIONS,
    },
    KV_TRANSFER_LOGICAL_BYTES: {
        "tier": KV_TRANSFER_TIERS, "direction": KV_TRANSFER_DIRECTIONS,
    },
    KV_TIER_COMPRESSION_RATIO: {
        "tier": KV_TRANSFER_TIERS, "direction": KV_TRANSFER_DIRECTIONS,
    },
    KV_TRANSFER_SECONDS: {
        "tier": KV_TRANSFER_TIERS, "direction": KV_TRANSFER_DIRECTIONS,
    },
    KV_TIER_BANDWIDTH: {
        "tier": KV_TRANSFER_TIERS, "direction": KV_TRANSFER_DIRECTIONS,
    },
    REQUEST_PREFIX_TOKENS: {"source": KV_HYDRATION_SOURCES},
    KV_HYDRATION_DECISIONS: {"choice": KV_HYDRATION_CHOICES},
    ROUTER_KV_MIGRATE_DECISIONS: {"decision": KV_MIGRATE_DECISION_VALUES},
    ENGINE_KV_TIER_USAGE: {"tier": ("hbm", "host", "disk", "remote")},
    ENGINE_STEP_TOKENS: {"phase": ("prefill", "decode")},
    ENGINE_PADDED_TOKENS: {"phase": ("prefill", "decode")},
    ENGINE_STEP_WALL: {"phase": ("prefill", "decode")},
    WASTED_TOKENS: {"reason": WASTE_REASON_VALUES},
    SPEC_PROPOSED_TOKENS: {"proposer": SPEC_PROPOSER_VALUES},
    SPEC_ACCEPTED_BY_PROPOSER: {"proposer": SPEC_PROPOSER_VALUES},
}

KV_FLOW_COUNTERS = (
    KV_TRANSFER_BYTES,
    KV_TRANSFER_BLOCKS,
    KV_TRANSFER_LOGICAL_BYTES,
    REQUEST_PREFIX_TOKENS,
    DISK_KV_STORES,
    DISK_KV_LOADS,
    KV_HYDRATION_DECISIONS,
)

# -- cluster KV index (event-driven KV-aware routing) -----------------------
# Exported by the KV controller's /metrics and re-exported by the router in
# embedded-index mode (router/metrics.py). NOT part of the per-engine scrape
# contract below — these describe the cluster-level index, not one engine.
CLUSTER_KV_INDEX_HASHES = "tpu:cluster_kv_index_hashes"
CLUSTER_KV_INDEX_ENGINES = "tpu:cluster_kv_index_engines"
CLUSTER_KV_INDEX_STALE_ENGINES = "tpu:cluster_kv_index_stale_engines"
CLUSTER_KV_EVENTS = "tpu:cluster_kv_events_total"
CLUSTER_KV_RESYNCS = "tpu:cluster_kv_resyncs_total"
# counter labeled mode=. The controller observes "indexed"|"fanout"|"mixed"
# (mixed = index for fresh engines + fan-out for the rest in one lookup)
# plus "peer" (/peer_lookup rediscovery calls from engines' peer tiers,
# docs/35-peer-kv-reuse.md — not a routed request); the router observes
# "indexed"|"controller"|"mixed" (controller = pure controller hop, mixed
# = non-authoritative index attempt escalated to the controller hop).
# Each ROUTED request is counted under exactly one of the routed modes.
CLUSTER_KV_LOOKUPS = "tpu:cluster_kv_lookups_total"
# histogram labeled mode= (kv_index.LookupLatency renders it)
CLUSTER_KV_LOOKUP_LATENCY = "tpu:cluster_kv_lookup_latency_seconds"
# counter: proactive flash-crowd replications the controller ordered AND
# saw adopted (docs/39-device-peer-kv.md — the BanaServe push-replication
# half; 0 while --replicate-threshold is unset/0)
CLUSTER_KV_REPLICATIONS = "tpu:cluster_kv_replications_total"

# -- fleet-coherence telemetry (docs/32-fleet-telemetry.md) ------------------
# The measurement layer ROADMAP 1's multi-replica router refactor builds
# against: replica index convergence, session-stickiness audit, and
# cluster-wide tenant accounting. Subscriber-side names are exported by
# every index host (KV controller /metrics AND the router registry in
# embedded mode) under the same names, like the CLUSTER_KV_* set above.
#
# histogram: publish-timestamp → apply-timestamp lag of KV event batches
# as seen by ONE subscriber (wall clocks across processes — honest to NTP
# skew, which is far below the ≥10ms replica-convergence granularity)
CLUSTER_KV_CONVERGENCE_LAG = "tpu:cluster_kv_convergence_lag_seconds"
# gauge labeled engine=: the subscriber's applied event-sequence position
# per publishing engine (cardinality bounded by the engine count; compare
# across replicas to see who lags whom)
CLUSTER_KV_ENGINE_SEQ = "tpu:cluster_kv_engine_seq"
# gauge: estimated blocks by which a replica's embedded index diverges
# from the controller's authoritative one (|seq gap| same-epoch, full
# slice on epoch mismatch / missing engine — fleet.index_divergence_blocks).
# The controller exports it per replica (labeled replica=); each router
# re-exports its OWN value unlabeled from the /fleet/report reply.
CLUSTER_KV_INDEX_DIVERGENCE = "tpu:cluster_kv_index_divergence_blocks"
# engine-side counter labeled reason= (closed set, fleet.STICKINESS_REASONS):
# "owner_changed" = consecutive requests of one session stamped with
# different ring-chosen owners; "non_owner_delivery" = a session request
# delivered to an engine that is not its stamped owner (failover moved it).
# Zero with 1 replica and STABLE membership — the baseline ROADMAP 1 must
# preserve. Endpoint churn legitimately remaps sessions at any N (the
# consistent-hash minimal-remap property bounds how many), so transient
# owner_changed blips during scaling are expected; a SUSTAINED rate on a
# stable fleet is the multi-replica affinity break.
SESSION_STICKINESS_VIOLATIONS = "tpu:session_stickiness_violations_total"
# gauges labeled tenant= (cardinality bounded by the tenant table):
# fleet-wide admitted request rate over the configured per-tenant budget
# (1.0 = the fleet admits exactly the global limit), and how far past the
# limit N per-replica buckets over-admit (≈ N-1 when every replica grants
# the full budget). Computed by the controller's FleetView from periodic
# router reports; each router re-exports the reply so the fleet view is
# scrapeable at every replica.
FLEET_TENANT_UTILIZATION = "tpu:fleet_tenant_limit_utilization"
FLEET_TENANT_OVERADMISSION = "tpu:fleet_tenant_overadmission_ratio"
# info-style gauge labeled hash= (value 1): the router's session-ring
# membership hash. Replicas whose hashes differ route the same session to
# different engines — `count(count by (hash)(...)) > 1` is the
# TpuRouterRingDivergence alert.
ROUTER_RING_MEMBERSHIP_HASH = "tpu:router_ring_membership_hash"
# router gauges the 10k-connection bench (ROADMAP 1) reads: in-flight
# proxied streams and the endpoint count discovery currently publishes
ROUTER_ACTIVE_STREAMS = "tpu:router_active_streams"
ROUTER_DISCOVERY_ENDPOINTS = "tpu:router_discovery_endpoints"
# engine-side KV event publisher health (engine/kv_events.py): batches
# POSTed (incl. heartbeats/snapshots), failed publish rounds, and the
# events buffered awaiting flush — the PUBLISHER vantage on a failing
# event path (before this pair, a dying publisher was only visible as
# controller-side resync storms, the wrong place to alert on)
KV_EVENT_PUBLISH_BATCHES = "tpu:kv_event_publish_batches_total"
KV_EVENT_PUBLISH_FAILURES = "tpu:kv_event_publish_failures_total"
KV_EVENT_QUEUE_DEPTH = "tpu:kv_event_pending_queue_depth"
# gauge: subscribers the engine's KV event publisher fans batches out to
# (KV_CONTROLLER_URL is a comma-separated list — the controller, embedded-
# index router replicas, or both; each keeps its own cursor/resync state).
# 0 = no publisher configured (docs/34-fleet-routing.md).
KV_EVENT_SUBSCRIBERS = "tpu:kv_event_subscribers"
# router gauge: the share of each tenant's GLOBAL budget this replica's
# local token buckets enforce (fleet budget scaling, 1/M for M live
# replicas per the controller's /fleet/report reply). 1.0 = full local
# budget — either a single replica, scaling off, or the controller-outage
# degradation (fail open toward availability, never stricter).
ROUTER_TENANT_BUDGET_SCALE = "tpu:router_tenant_budget_scale"

# closed reason set — the SINGLE definition (fleet.STICKINESS_REASONS
# aliases it, so the audit and the exporter can never drift). Registered
# into METRIC_LABEL_VALUES below — the dict literal predates this section.
STICKINESS_REASON_VALUES = ("owner_changed", "non_owner_delivery")
METRIC_LABEL_VALUES[SESSION_STICKINESS_VIOLATIONS] = {
    "reason": STICKINESS_REASON_VALUES,
}

# -- flight recorder & thread-liveness watchdog (docs/37-flight-recorder.md)
# A wedged engine produces no requests and therefore no request-vantage
# telemetry; these names turn SILENCE into signal. Every long-lived loop in
# the engine process beats a heartbeat into engine/flightrec.ThreadRegistry;
# the gauge below is each loop's seconds-since-last-beat at scrape time
# (0 for loops not running in this deployment — e.g. no hydration fetcher
# without a disk/remote tier). thread= is a CLOSED set:
#   step               the AsyncEngine step-loop thread (drives the device)
#   hydration_fetch    the compute-or-load planner's chunk fetcher thread
#   kv_event_publisher the cluster-KV-index event publisher task
#   kv_writer          the remote KV store's async PUT writer thread
#   bg_compile         background XLA compile jobs (busy only mid-compile —
#                      a beat older than its threshold while busy is the
#                      "XLA compiles forever" wedge)
#   watchdog           the watchdog thread itself (its age is computed by
#                      the exporter, not the watchdog, so a dead watchdog
#                      is visible here rather than self-reported)
#   rebalancer         the KV controller's pool-rebalancer tick loop
#                      (docs/40-pool-rebalancing.md) — hand-rendered on
#                      the CONTROLLER's /metrics; the engine exporter
#                      seeds it 0 like every loop not running locally
THREAD_HEARTBEAT_AGE = "tpu:thread_heartbeat_age_seconds"
THREAD_NAME_VALUES = (
    "step", "hydration_fetch", "kv_event_publisher", "kv_writer",
    "bg_compile", "watchdog", "rebalancer",
)
# watchdog trips, by kind (closed set):
#   stale_heartbeat  a registered loop stopped beating while busy
#   unresolved_step  a device step was dispatched and never resolved (the
#                    collective-stall shape: the host is alive, the
#                    device work never comes back)
# Counted once per stall EPISODE (a 10-minute wedge is one trip, not 600).
ENGINE_STEP_STALLS = "tpu:engine_step_stalls_total"
STALL_KIND_VALUES = ("stale_heartbeat", "unresolved_step")
METRIC_LABEL_VALUES[THREAD_HEARTBEAT_AGE] = {"thread": THREAD_NAME_VALUES}
METRIC_LABEL_VALUES[ENGINE_STEP_STALLS] = {"kind": STALL_KIND_VALUES}
# router/controller asyncio event-loop lag (engine/flightrec.
# EventLoopLagProbe): decaying peak of how far a short sleep overshot its
# deadline — a router whose loop is starved (blocking call, CPU overload)
# serves nothing while every request-vantage metric just goes quiet.
# Exported by the router registry AND hand-rendered by the KV controller,
# like the CLUSTER_KV_* names.
ROUTER_EVENT_LOOP_LAG = "tpu:router_event_loop_lag_seconds"

# -- prefill/decode pool rebalancing (docs/40-pool-rebalancing.md) -----------
# The role-flip actuator that closes the TpuSeatStarvation loop: the KV
# controller watches per-pool queue-wait p95 vs decode-seat occupancy and
# flips the least-loaded engine of the rich pool into the starved one.
#
# engine-side gauge labeled role= (closed set): 1 on the engine's CURRENT
# pool role, 0 on the other. Both series render 0 on engines that are not
# part of a disaggregated deployment — the absence of a 1 is itself the
# "this engine has no pool role" signal, and keeps the closed set seeded.
# The router's stats scraper reads this to follow live-advertised roles
# instead of frozen helm labels.
POOL_ROLE = "tpu:pool_role"
POOL_ROLE_VALUES = ("prefill", "decode")
# controller-side counter labeled outcome= (closed set): one increment per
# finished rebalance EPISODE.  completed = flip verified and kept;
# rolled_back = the verify window judged the imbalance worse and the flip
# was undone once; abandoned = the target engine went unreachable mid-
# episode (its restart restores the static role, so abandoning is safe).
# Hand-rendered live by the controller's /metrics; the router registry
# zero-seeds the same name so the contract check has one exporter home
# (the CLUSTER_KV_REPLICATIONS convention).
POOL_REBALANCE_FLIPS = "tpu:pool_rebalance_flips_total"
POOL_REBALANCE_OUTCOME_VALUES = ("completed", "rolled_back", "abandoned")
# controller-side gauge labeled phase= (closed set): 1 on the state
# machine's current phase, 0 elsewhere. "observe" = idle/watching,
# "cooldown" = post-episode hold-off; drain/flip/rejoin/verify are the
# transitional phases of an active episode — a transitional phase pinned
# at 1 for many minutes is the TpuRebalanceStuck alert.
POOL_REBALANCE_PHASE = "tpu:pool_rebalance_phase"
POOL_REBALANCE_PHASE_VALUES = (
    "observe", "cooldown", "drain", "flip", "rejoin", "verify",
)
METRIC_LABEL_VALUES[POOL_ROLE] = {"role": POOL_ROLE_VALUES}
METRIC_LABEL_VALUES[POOL_REBALANCE_FLIPS] = {
    "outcome": POOL_REBALANCE_OUTCOME_VALUES,
}
METRIC_LABEL_VALUES[POOL_REBALANCE_PHASE] = {
    "phase": POOL_REBALANCE_PHASE_VALUES,
}

# -- structured output (docs/41-structured-output.md) ------------------------
# Grammar-constrained decoding: requests carrying response_format /
# guided_json / a forced tool_choice decode under a token-class automaton
# whose mask is applied on device inside the jitted sampling path.
#
# engine-side counter labeled outcome= (closed set): one increment per
# FINISHED structured request. valid = the terminal automaton state was
# accepting (the body parses under the declared schema); invalid = it was
# not (length cap, client stop sequence, or a compile-rejected schema
# counted at the API layer); fallback = the schema compiled but the engine
# runs with structured_output=fallback, so constraints were declined and
# the request decoded free-form.
STRUCTURED_REQUESTS = "tpu:structured_requests_total"
STRUCTURED_OUTCOME_VALUES = ("valid", "invalid", "fallback")
# histogram: wall seconds to compile one grammar (schema -> byte-DFA ->
# token-class tables). Cache hits do not observe; a hot p99 here means the
# schema corpus is churning faster than the grammar cache can hold it.
GRAMMAR_BUILD_TIME = "tpu:grammar_build_time_seconds"
METRIC_LABEL_VALUES[STRUCTURED_REQUESTS] = {
    "outcome": STRUCTURED_OUTCOME_VALUES,
}

# -- XLA compile telemetry (docs/42-compile-telemetry.md) --------------------
# The TPU stack's third failure axis (after requests and pods): a shape
# that escapes the pad-up bucket ladder stalls every stream for a
# synchronous XLA compile. CompileWatch (engine/compile_watch.py) records
# every program build; these series make compile hygiene a standing
# production guarantee instead of a bench-time assertion.
#
# counter labeled (phase, trigger), both closed sets: one increment per
# program (or grammar-table) build. trigger=warmup is planned (boot
# waves / precompile_dominating), bg is the background AOT thread
# absorbing a pad-up fallback, mid_traffic is a synchronous compile ON
# the dispatch path after warmup — the stall the ladder exists to prevent.
ENGINE_COMPILES = "tpu:engine_compiles_total"
COMPILE_PHASE_VALUES = ("prefill", "decode", "verify", "grammar")
COMPILE_TRIGGER_VALUES = ("warmup", "bg", "mid_traffic")
METRIC_LABEL_VALUES[ENGINE_COMPILES] = {
    "phase": COMPILE_PHASE_VALUES,
    "trigger": COMPILE_TRIGGER_VALUES,
}
# histogram: wall seconds per program build (all triggers; the rules
# group records its p95). XLA compiles run 30-60s on real models — the
# boundaries stretch far past the request-phase buckets.
ENGINE_COMPILE_SECONDS = "tpu:engine_compile_seconds"
COMPILE_SECONDS_BUCKETS = (
    0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 20.0, 30.0,
    60.0, 120.0, 300.0,
)
# gauge: programs in the CompileWatch inventory (compiled and retained);
# counters: dispatches whose EXACT program key was already compiled (hit)
# vs pad-up fallbacks and sync compiles (miss)
ENGINE_PROGRAM_CACHE_PROGRAMS = "tpu:engine_program_cache_programs"
ENGINE_PROGRAM_CACHE_HITS = "tpu:engine_program_cache_hits_total"
ENGINE_PROGRAM_CACHE_MISSES = "tpu:engine_program_cache_misses_total"
# recompile-storm episodes (--compile-storm-threshold/-window): counted
# once per EPISODE like ENGINE_STEP_STALLS — the report names the shapes
ENGINE_COMPILE_STORMS = "tpu:engine_compile_storms_total"

CLUSTER_KV_GAUGES = (
    CLUSTER_KV_INDEX_HASHES,
    CLUSTER_KV_INDEX_ENGINES,
    CLUSTER_KV_INDEX_STALE_ENGINES,
)
CLUSTER_KV_COUNTERS = (
    CLUSTER_KV_EVENTS,
    CLUSTER_KV_RESYNCS,
    CLUSTER_KV_LOOKUPS,
    CLUSTER_KV_REPLICATIONS,
)

# -- router-side robustness (NOT part of the per-engine scrape contract:
# these describe the router's view of its upstreams). Exported by
# router/metrics.py; the breaker state/open counts follow the same
# value-owned-by-component gauge convention as CLUSTER_KV_EVENTS.
ROUTER_BREAKER_STATE = "tpu:router_breaker_state"  # 0 closed / 1 half / 2 open
ROUTER_BREAKER_OPENS = "tpu:router_breaker_opens_total"
ROUTER_UPSTREAM_FAILURES = "tpu:router_upstream_failures_total"

ROUTER_BREAKER_GAUGES = (
    ROUTER_BREAKER_STATE,
    ROUTER_BREAKER_OPENS,
    ROUTER_UPSTREAM_FAILURES,
)

ALL_GAUGES = (
    NUM_REQUESTS_RUNNING,
    NUM_REQUESTS_WAITING,
    HBM_KV_USAGE_PERC,
    HBM_KV_GROUP_USAGE_PERC,
    HBM_KV_WINDOW_SHARE,
    HBM_STATE_BYTES,
    HBM_STATE_SLOTS_IN_USE,
    HBM_STATE_SHARE,
    HBM_LATENT_BYTES,
    HBM_INDEX_BYTES,
    PREFIX_CACHE_HIT_RATE,
    HOST_KV_USAGE_PERC,
    STEP_OVERLAP_FRAC,
    ENGINE_DRAINING,
    # saturation (docs/29-saturation-slo.md)
    ENGINE_DECODE_SEAT_OCCUPANCY,
    ENGINE_PADDING_WASTE_FRAC,
    ENGINE_ACHIEVED_FLOPS,
    ENGINE_MFU,
    ENGINE_KV_TIER_USAGE,
    # KV flow telemetry (docs/30-kv-flow-telemetry.md)
    KV_TIER_BANDWIDTH,
    # at-rest codec effectiveness (docs/38-kv-quantization.md):
    # logical/wire per (tier, direction), 1.0 with no codec
    KV_TIER_COMPRESSION_RATIO,
    # peer-engine KV tier (docs/35-peer-kv-reuse.md): the migrate-pricing
    # constant the router reads off each engine's scrape
    KV_BYTES_PER_TOKEN,
    # fleet-coherence telemetry (docs/32-fleet-telemetry.md): engine-side
    # KV event publisher backlog + fan-out subscriber count
    KV_EVENT_QUEUE_DEPTH,
    KV_EVENT_SUBSCRIBERS,
    # thread-liveness watchdog (docs/37-flight-recorder.md): per-loop
    # heartbeat age (thread= closed set) — the signal a wedged engine
    # still emits when it serves nothing
    THREAD_HEARTBEAT_AGE,
    # pool rebalancing (docs/40-pool-rebalancing.md): the engine's live
    # pool role (role= closed set, 1 on the current role)
    POOL_ROLE,
    # compile telemetry (docs/42-compile-telemetry.md): programs in the
    # CompileWatch inventory
    ENGINE_PROGRAM_CACHE_PROGRAMS,
)
ALL_COUNTERS = (
    PREFIX_CACHE_HITS,
    PREFIX_CACHE_QUERIES,
    NUM_PREEMPTIONS,
    HBM_KV_WINDOW_PAGES_GIVEN_BACK,
    BD_PASSES,
    BD_ROW_PASSES,
    BD_REPEAT_ROW_PASSES,
    BD_BLOCKS,
    BD_TOKENS,
    BD_THRESHOLD_TOKENS,
    PROMPT_TOKENS,
    GENERATION_TOKENS,
    HOST_KV_OFFLOADS,
    HOST_KV_RELOADS,
    REMOTE_KV_STORES,
    REMOTE_KV_FETCHES,
    SPEC_DRAFT_TOKENS,
    SPEC_ACCEPTED_TOKENS,
    # per-proposer split (proposer= closed set, docs/36)
    SPEC_PROPOSED_TOKENS,
    SPEC_ACCEPTED_BY_PROPOSER,
    REQUESTS_SHED,
    REQUESTS_DEADLINE_EXPIRED,
    # tenant-labeled (cardinality bounded by the tenant table); rendered
    # by the engine exporter even before any stamped traffic arrives
    TENANT_REQUESTS,
    TENANT_GENERATION_TOKENS,
    TENANT_SHED,
    # saturation & goodput (docs/29-saturation-slo.md); phase=/reason=
    # labels are closed sets, so cardinality is bounded by construction
    ENGINE_STEP_TOKENS,
    ENGINE_PADDED_TOKENS,
    ENGINE_MODEL_FLOPS,
    GOODPUT_TOKENS,
    WASTED_TOKENS,
    # KV flow telemetry (docs/30-kv-flow-telemetry.md); tier=/direction=/
    # source= labels are closed sets (METRIC_LABEL_VALUES)
    KV_TRANSFER_BYTES,
    KV_TRANSFER_BLOCKS,
    KV_TRANSFER_LOGICAL_BYTES,
    REQUEST_PREFIX_TOKENS,
    DISK_KV_STORES,
    DISK_KV_LOADS,
    KV_HYDRATION_DECISIONS,
    # fleet-coherence telemetry (docs/32-fleet-telemetry.md): stickiness
    # audit (reason= is the closed STICKINESS_REASON_VALUES set) and the
    # KV event publisher's own health counters
    SESSION_STICKINESS_VIOLATIONS,
    KV_EVENT_PUBLISH_BATCHES,
    KV_EVENT_PUBLISH_FAILURES,
    # thread-liveness watchdog (docs/37-flight-recorder.md): stall
    # episodes by kind (closed STALL_KIND_VALUES set)
    ENGINE_STEP_STALLS,
    # structured output (docs/41-structured-output.md): finished
    # constrained requests by outcome (closed STRUCTURED_OUTCOME_VALUES)
    STRUCTURED_REQUESTS,
    # compile telemetry (docs/42-compile-telemetry.md): program builds by
    # (phase, trigger), program-cache hit/miss dispatches, and storm
    # episodes (counted once per episode, like ENGINE_STEP_STALLS)
    ENGINE_COMPILES,
    ENGINE_PROGRAM_CACHE_HITS,
    ENGINE_PROGRAM_CACHE_MISSES,
    ENGINE_COMPILE_STORMS,
)

# GET /debug/timing -> engine: the counts a model with layers that hold a
# recurrent state adds (engine/flightrec.py:count; absent otherwise): the
# tokens and the row segments its prefill chunks ran through the chunked
# kernel, the live rows x steps of its decode windows, the state slots a
# chunk that held a row's first token started from zeros, and the positions
# computed a second time after a preemption took a row's slot.
# ... and the counts a model that generates by diffusion over blocks adds
# (engine/model_runner.py:BD_COUNTS, in its order; absent otherwise), each
# beside the /metrics counter that serves the same number
DEBUG_TIMING_BD_COUNTS = {
    "bd_passes_n": BD_PASSES,
    "bd_row_passes_n": BD_ROW_PASSES,
    "bd_repeat_row_passes_n": BD_REPEAT_ROW_PASSES,
    "bd_blocks_n": BD_BLOCKS,
    "bd_tokens_n": BD_TOKENS,
    "bd_threshold_tokens_n": BD_THRESHOLD_TOKENS,
}

DEBUG_TIMING_STATE_COUNTS = (
    "kda_prefill_tokens_n",
    "kda_prefill_segments_n",
    "kda_decode_rows_n",
    "state_slots_zeroed_n",
    "state_recomputed_tokens_n",
    # linear layers run: the model's count a prefill dispatch, times the
    # window's steps a decode dispatch
    "kda_layers_n",
)
# ... what a model with state-space heads beside its attention heads counts
# in the KDA counts' place (LayerSpec.recurrence "ssd"; absent otherwise;
# `state_slots_zeroed_n` and `state_recomputed_tokens_n` above are any
# recurrence's): the tokens and row segments of its prefill chunks through
# `ssd_prefill_scan`, the live rows x steps through `ssd_decode_step`, the
# layers with such heads run, and of those the ones that hold pages too
DEBUG_TIMING_SSD_COUNTS = (
    "ssd_prefill_tokens_n",
    "ssd_prefill_segments_n",
    "ssd_decode_rows_n",
    "ssd_layers_n",
    "page_and_state_layers_n",
)
# ... the same count of LATENT layers run, from a model that has any
DEBUG_TIMING_LATENT_COUNTS = ("mla_layers_n",)
# ... what a model whose latent layers attend over SELECTED keys counts
# (LayerSpec.indexer; docs/55-selected-keys.md; absent otherwise), on the
# host at the resolve from lengths it knows: the layers that own an indexer
# and those that borrow a selection, run (the model's count a prefill
# dispatch, times the window's steps a decode dispatch); the (query, key)
# pairs the indexers scored; the keys the queries could see and those they
# attended — min(visible, index_topk) — summed over queries and over every
# layer that attends under a selection; the live rows x steps of the decode
# windows, and the decode windows' part of the pairs and of the attended keys
DEBUG_TIMING_DSA_COUNTS = (
    "dsa_full_layers_n",
    "dsa_shared_layers_n",
    "dsa_index_pairs_n",
    "dsa_visible_keys_n",
    "dsa_selected_keys_n",
    "dsa_decode_rows_n",
    "dsa_decode_index_pairs_n",
    "dsa_decode_selected_keys_n",
)
# ... and what group-limited routing counts (ModelConfig.n_group > 1;
# absent otherwise): the groups the router kept — `topk_group` a token and
# expert layer run, padding tokens included — and those of them that hold
# an expert this program holds (all of them without a held share)
DEBUG_TIMING_GROUP_COUNTS = ("moe_group_kept_n", "moe_group_slots_n")

# The device scopes: every `jax.named_scope` name of the package, each in ONE
# group — the lever that would move its seconds. They are metadata only (the
# compiled operations are the same) and land in each device operation's
# `tf_op` stat (`jit(decode_window_fn)/while/body/attn_qkv/dot_general`);
# engine/capture_summary.py books an operation to the INNERMOST name of its
# path that is a key here (`attn_gate` inside `attn_qkv` is `glue`), and to
# `other` where the path holds none. This is the one list: the docs point
# here, tests/test_capture_summary.py holds it equal to the literals in the
# source, and a scope added to a model is added here with its group.
DEVICE_SCOPES = {
    # proj — the mixers' matmuls (fused projections, weight layouts)
    "attn_qkv": "proj",     # input norm, Q/K/V projections, qk_norm, RoPE
    "attn_out": "proj",     # output projection, its norm, the residual
    "mla_q": "proj",        # a latent layer's query projection and its RoPE
    "mla_latent": "proj",   # ... the down-projection to the cached latent
    "mla_absorb": "proj",   # ... W_kv_b folded into the query / the output
    "kda_qkv": "proj",      # a gated-delta layer's norm and q|k|v matmuls
    "kda_out": "proj",      # ... output gate, norm and projection
    "ssm_in_proj": "proj",  # state-space heads: z|x|B|C|dt in one matmul
    "ssm_out": "proj",      # ... their output projection
    # glue — convolutions, gates and norms around the mixers
    "attn_gate": "glue",    # the attention output gate (logistic, multiply)
    "mla_gate": "glue",     # the same of a latent layer
    "kda_conv": "glue",     # short convolution, SiLU, q/k L2 norms
    "kda_gate": "glue",     # decay and beta gates (low-rank pairs, softplus)
    "ssm_conv": "glue",     # short convolution over x|B|C, SiLU
    "ssm_gate_norm": "glue",  # gate by z, grouped RMSNorm
    "h1_branches": "glue",  # attention and state-space branches summed
    "dsa_index_q": "proj",  # an indexer's queries and head weights
    "dsa_index_k": "proj",  # ... its one key a token: matmul, LayerNorm, RoPE
    # mixer — the attention / recurrence kernels and what XLA leaves beside
    "attn": "mixer",        # paged attention: the Pallas kernel, or XLA's
    "dsa_index_score": "mixer",  # an indexer's scores over the visible keys
    "dsa_select": "mixer",  # the exact top-k of them (threshold / top_k)
    "dsa_gather": "mixer",  # a decode step's gather of the selected latents
    "dsa_attn": "mixer",    # latent attention over the selected keys
    "kda_scan": "mixer",    # the gated delta rule over a chunk or a step
    "ssm_scan": "mixer",    # the state-space scan
    # mlp / experts / moe_route — the second half of a layer
    "mlp": "mlp",           # post-attention norm, gate/up/down, residual
    "moe_shared": "mlp",    # the shared experts of an expert layer
    "moe_experts": "experts",  # the routed experts' matmuls
    "moe_router": "moe_route",  # router matmul, softmax / sigmoid, top-k
    "moe_route_groups": "moe_route",  # group-limited routing's group top-k
    "moe_dispatch": "moe_route",  # sort by expert, the row gather
    "moe_combine": "moe_route",  # un-sort, weight and sum
    # ends — before the first layer and after the last
    "embed": "ends",        # token embedding lookup
    "final_norm": "ends",   # last norm
    "lm_head": "ends",      # logits
    "sample": "ends",       # masks, the draw, logprobs, the automaton step
    "bd_transfer": "ends",  # block diffusion: which masked tokens to keep
    # weights — the layer loop's slices of the stacked leaves: what XLA
    # copies (or prefetches) of a layer's weights before its matmuls
    "layer_weights": "weights",
    # kv — moving K/V and pages
    "kv_stage": "kv",       # this step's K/V into the pool or the staging
    "kv_commit": "kv",      # a decode window's one scatter into the pool
    "gather_pages": "kv",   # the hoisted history gather (XLA attention)
}
# ... the groups in the order a report prints them; the last is no scope's
DEVICE_SCOPE_GROUPS = ("proj", "glue", "mixer", "mlp", "experts",
                       "moe_route", "ends", "weights", "kv", "other")
# ... and the KIND of a device program, by the family of its module name
# (`jit_<function>`), for the functions engine/model_runner.py jits as step
# programs in its `_build_*_fn` methods: `step_fn` and `sp_step_fn` (a
# prefill chunk), `decode_window_fn` and `bd_window_fn` (a decode window),
# `verify_fn` (a speculation's verify). A program under a millisecond (an
# RNG split, a chain gather) is `glue_program` whatever its name; a longer
# one of another name `other_program`.
DEVICE_PROGRAM_KINDS = {
    "jit_step_fn": "prefill",
    "jit_sp_step_fn": "prefill",
    "jit_decode_window_fn": "window",
    "jit_bd_window_fn": "window",
    "jit_verify_fn": "verify",
}
