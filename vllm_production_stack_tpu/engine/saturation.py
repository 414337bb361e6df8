"""Saturation & goodput telemetry (docs/29-saturation-slo.md).

The tracing spine (docs/28-request-tracing.md) explains one slow request;
this module explains *why the chip isn't full*. Two instruments:

- **StepMeter** — per-resolved-step utilization accounting in the engine
  step loop: decode-seat occupancy (rows used / max_num_seqs), the
  prefill-vs-decode token split, the padding-waste fraction (useful tokens
  vs the padded device shape actually computed), and an achieved-FLOP/s →
  MFU estimate (analytic model FLOPs × tokens ÷ resolve-cadence wall).
  Cheap by construction: a handful of float ops and one bucket increment
  per resolved step, all on the step thread.

- **GoodputLedger** — classifies every device-sampled token exactly once
  as *delivered* or *wasted* with a bounded reason label
  (:data:`WASTE_REASONS`). The invariant the tests enforce:

      sampled == delivered + sum(wasted) + pending-on-live-requests

  where *pending* are accepted tokens whose request hasn't finished yet
  (classified at finish/preemption). At quiescence pending is zero, so
  ``delivered + wasted == sampled`` exactly — across the serial AND
  pipelined step loops, rollbacks, preemptions, deadline expiry, QoS shed
  evictions and severed/aborted streams.

Both feed the ``tpu:engine_*`` / ``tpu:goodput_*`` contract names
(metrics_contract.py) through EngineStatsSnapshot.saturation; the SLO rule
pack (observability/rules/) and the KEDA/prom-adapter autoscaling signal
path key off them.
"""

from __future__ import annotations

import math
import os
import time
from bisect import bisect_left

from ..metrics_contract import WASTE_REASON_VALUES

# Reason labels for tpu:wasted_tokens_total — a CLOSED set (exporter label
# cardinality is bounded by construction, not by a cap). The tuple itself
# lives in metrics_contract (single definition, validated against the
# exporters by the contract checker); the semantics live here:
#   rollback            sampled by a pipeline dispatch that was discarded
#                       (speculation invalidated / resolve fault), by a
#                       row whose request finished while the step was in
#                       flight, or by spec-decode verify positions past
#                       the first draft mismatch — the device executed
#                       them, nobody consumed them
#   preempted_recompute generated-token positions RE-computed by resumed
#                       prefill after a preemption dropped their KV —
#                       charged chunk-exactly as sampled+wasted per re-pass
#                       (the token values were already known; the request's
#                       own pending tokens keep their fate until finish)
#   deadline_expired    tokens of a request finished by deadline expiry —
#                       generated for a reply nobody will read
#   severed             tokens of an aborted request (client disconnect /
#                       router-severed stream / engine-side abort)
#   shed_evicted        tokens of a request evicted from the waiting queue
#                       by a higher-priority admission (QoS shedding)
#   overshoot           fused-decode-window candidates sampled past a
#                       per-request stop condition and discarded host-side
WASTE_REASONS = WASTE_REASON_VALUES

# finish-status → waste reason for a request's still-pending tokens
# (None = delivered). Keys are RequestStatus *names* so this module stays
# import-light (request.py imports nothing from here).
FINISH_REASONS = {
    "FINISHED_STOPPED": None,
    "FINISHED_LENGTH": None,
    "FINISHED_DEADLINE": "deadline_expired",
    "FINISHED_SHED": "shed_evicted",
    "FINISHED_ABORTED": "severed",
}


class GoodputLedger:
    """Monotonic token-fate counters, mutated only under the engine lock
    (scheduler postprocess/finish/preempt + the engine's rollback sites)."""

    def __init__(self) -> None:
        self.sampled_total = 0
        self.delivered_total = 0
        self.wasted: dict[str, int] = {r: 0 for r in WASTE_REASONS}

    def sampled(self, n: int) -> None:
        if n > 0:
            self.sampled_total += n

    def deliver(self, n: int) -> None:
        if n > 0:
            self.delivered_total += n

    def waste(self, reason: str, n: int) -> None:
        if n > 0:
            # an unknown reason is a programming error — fail loud in tests
            self.wasted[reason] += n

    def rollback(self, n: int) -> None:
        """Sampled AND wasted{rollback} in one motion — the shape every
        discard site shares (pipeline rollback, void rows for requests
        that finished in flight, rejected draft positions past a verify
        mismatch), so no site can count one half and drift the partition."""
        self.sampled(n)
        self.waste("rollback", n)

    def classify_finish(self, status_name: str, n: int) -> None:
        """Classify a finished request's pending tokens by its terminal
        status (FINISH_REASONS). Unknown statuses count as severed — a
        token must never escape the partition."""
        reason = FINISH_REASONS.get(status_name, "severed")
        if reason is None:
            self.deliver(n)
        else:
            self.waste(reason, n)

    @property
    def wasted_total(self) -> int:
        return sum(self.wasted.values())

    def snapshot(self) -> dict:
        return {
            "sampled": self.sampled_total,
            "delivered": self.delivered_total,
            "wasted": dict(self.wasted),
            "wasted_total": self.wasted_total,
        }


# -- analytic FLOP model -----------------------------------------------------


def matmul_params(cfg) -> int:
    """Dense matmul parameters touched per token (embedding GATHER
    excluded, unembedding matmul included — computed whether or not the
    weights are tied), from the same per-kind count `memory.param_bytes`
    weighs the tree with. For MoE, only the activated experts count, plus
    the shared experts and the router on every expert layer; leading dense
    layers count their dense MLP."""
    from .memory import layer_matrix_params

    m = layer_matrix_params(cfg)
    moe = (cfg.num_experts_per_tok * m["expert"] + m["shared"] + m["router"])
    return (
        cfg.num_layers * m["attn"] + m["dense_layers"] * m["dense_mlp"]
        + m["moe_layers"] * moe + cfg.vocab_size * cfg.hidden_size
    )


def attn_flops_per_token_ctx(cfg) -> float:
    """Attention score/value FLOPs per (token, attended position) pair,
    all layers: QK^T and PV over each head's widths (MLA, as published:
    qk_nope + qk_rope for the scores, v_head for the values)."""
    if cfg.latent_layers:
        per_head = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
                    + cfg.v_head_dim)
    else:
        per_head = 2 * cfg.head_dim
    return 2.0 * cfg.num_heads * per_head * cfg.num_layers


def step_flops(cfg, n_tokens: int, sum_context: int) -> float:
    """Forward-pass FLOPs for one dispatch: 2 × matmul-params per token
    plus the attention score/value term (`attn_flops_per_token_ctx` per
    (token, context-position) pair). `sum_context` is the summed
    attended context length over the dispatch's tokens — an estimate, like
    every MFU number."""
    return (
        2.0 * matmul_params(cfg) * n_tokens
        + attn_flops_per_token_ctx(cfg) * sum_context
    )


# bf16 peak FLOP/s per chip by accelerator generation (dense; public spec
# sheets). Override with TPU_PEAK_FLOPS (per chip) for new hardware.
_PEAK_BY_KIND = (
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v6", 918e12),  # Trillium
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def detect_peak_flops() -> float:
    """Aggregate peak FLOP/s of this process's local devices, 0.0 when
    unknown (CPU backend / unrecognized chip) — MFU reads 0 rather than a
    made-up denominator."""
    env = os.environ.get("TPU_PEAK_FLOPS")
    per_chip = 0.0
    n = 1
    try:
        import jax

        devs = jax.local_devices()
        n = max(1, len(devs))
        kind = getattr(devs[0], "device_kind", "") or ""
        if env:
            per_chip = float(env)
        else:
            low = kind.lower()
            for marker, peak in _PEAK_BY_KIND:
                if marker in low:
                    per_chip = peak
                    break
    except Exception:
        per_chip = float(env) if env else 0.0
    return per_chip * n


# occupancy fraction (0..1] buckets; +Inf is appended by the exporter
OCCUPANCY_BUCKETS = (0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0)
# per-resolved-step wall seconds (resolve cadence)
STEP_WALL_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
)

_EWMA_TAU_S = 10.0  # time constant for the gauge-shaped signals


class _Hist:
    """Fixed-bucket histogram as plain ints (the exporter renders it as a
    Prometheus histogram family; prometheus_client objects never ride the
    step thread)."""

    __slots__ = ("buckets", "counts", "total", "count")

    def __init__(self, buckets: tuple[float, ...]):
        self.buckets = buckets
        self.counts = [0] * (len(buckets) + 1)  # + the +Inf bucket
        self.total = 0.0
        self.count = 0

    def observe(self, v: float) -> None:
        self.counts[bisect_left(self.buckets, v)] += 1
        self.total += v
        self.count += 1

    def snapshot(self) -> dict:
        return {
            "buckets": self.buckets,
            "counts": list(self.counts),
            "sum": self.total,
            "count": self.count,
        }


class StepMeter:
    """Per-resolved-step utilization accounting (step thread only).

    Padding accounting mirrors the runner's shape planner: decode rows pad
    to the decode bucket, prefill pads to (pow2 rows × prefill token
    bucket). The pad-up program fallback can promote a dispatch to a
    coarser shape while the exact program compiles in the background —
    that transient is NOT metered (the planner's shape is), so the waste
    fraction describes steady state.
    """

    def __init__(self, model_cfg=None, sched_cfg=None):
        self.model_cfg = model_cfg
        self.sched_cfg = sched_cfg
        # cumulative counters (exporter _bump pattern)
        self.step_tokens = {"prefill": 0, "decode": 0}
        self.padded_tokens = {"prefill": 0, "decode": 0}
        self.flops_total = 0.0
        self.steps = {"prefill": 0, "decode": 0}
        # gauge-shaped EWMAs
        self.seat_occupancy = 0.0
        self.padding_waste = 0.0
        self.achieved_flops = 0.0
        self._peak_flops: float | None = None  # lazy (jax touch)
        # per-step distributions
        self.occupancy_hist = _Hist(OCCUPANCY_BUCKETS)
        self.wall_hist = {
            "prefill": _Hist(STEP_WALL_BUCKETS),
            "decode": _Hist(STEP_WALL_BUCKETS),
        }
        self._last_t: float | None = None

    # -- recording (step thread) -------------------------------------------

    def _wall(self, now: float) -> float:
        """Resolve-cadence wall: time since the previous resolved step.
        This is the honest MFU denominator for the pipelined loop (where
        dispatch and resolve of different steps overlap inside one call)
        AND it charges idle gaps against utilization — an idle chip is
        exactly what this meter exists to surface. Clamped so one long
        idle stretch can't freeze the EWMAs at ~0 forever."""
        if self._last_t is None:
            self._last_t = now
            return 0.0
        wall = min(60.0, now - self._last_t)
        self._last_t = now
        return wall

    def _ewma(self, prev: float, value: float, wall: float) -> float:
        alpha = 1.0 - math.exp(-max(wall, 1e-4) / _EWMA_TAU_S)
        return prev + alpha * (value - prev)

    def record_decode(
        self, rows: int, window: int, accepted_tokens: int, sum_context: int
    ) -> None:
        """One RESOLVED decode (or verify) dispatch. `accepted_tokens` are
        the host-accepted tokens; `sum_context` the summed context length
        over the dispatch's sampled positions (FLOP estimate)."""
        now = time.perf_counter()
        wall = self._wall(now)
        sched = self.sched_cfg
        capacity = sched.max_num_seqs if sched else rows
        try:
            padded_rows = (
                sched.bucket_for(rows, sched.decode_buckets) if sched else rows
            )
        except ValueError:
            padded_rows = rows
        occ = rows / capacity if capacity else 0.0
        padded = padded_rows * window
        self.step_tokens["decode"] += accepted_tokens
        self.padded_tokens["decode"] += padded
        self.steps["decode"] += 1
        self.occupancy_hist.observe(occ)
        if wall > 0.0:
            self.wall_hist["decode"].observe(wall)
        flops = 0.0
        if self.model_cfg is not None:
            flops = step_flops(self.model_cfg, rows * window, sum_context)
            self.flops_total += flops
        if wall > 0.0:
            self.seat_occupancy = self._ewma(self.seat_occupancy, occ, wall)
            # PURE bucket padding: dispatched slots (rows × window) vs the
            # padded device shape. Mid-window stop discards are the
            # ledger's wasted{overshoot} — charging them here too would
            # double-attribute one waste class and point the operator at
            # bucket tuning that can't help.
            waste = 1.0 - (rows * window) / padded if padded else 0.0
            self.padding_waste = self._ewma(self.padding_waste, waste, wall)
            self.achieved_flops = self._ewma(
                self.achieved_flops, flops / wall, wall
            )
        else:
            self.seat_occupancy = occ

    def record_prefill(
        self, chunk_tokens: int, sum_context: int, positions: int,
    ) -> None:
        """One resolved prefill dispatch: `chunk_tokens` useful prompt
        tokens of the `positions` its program computed (the runner's count:
        the packed token axis with its padding, or rows x bucket where the
        chunk is row-major)."""
        now = time.perf_counter()
        wall = self._wall(now)
        self.step_tokens["prefill"] += chunk_tokens
        self.padded_tokens["prefill"] += positions
        self.steps["prefill"] += 1
        if wall > 0.0:
            self.wall_hist["prefill"].observe(wall)
        flops = 0.0
        if self.model_cfg is not None:
            flops = step_flops(self.model_cfg, chunk_tokens, sum_context)
            self.flops_total += flops
        if wall > 0.0:
            waste = 1.0 - chunk_tokens / positions if positions else 0.0
            self.padding_waste = self._ewma(self.padding_waste, waste, wall)
            self.achieved_flops = self._ewma(
                self.achieved_flops, flops / wall, wall
            )

    # -- reporting ----------------------------------------------------------

    def peak_flops(self) -> float:
        if self._peak_flops is None:
            self._peak_flops = detect_peak_flops()
        return self._peak_flops

    def _decay(self) -> float:
        """Idle decay factor for the EWMA gauges, applied at READ time
        (state stays untouched): with no steps resolving, the gauges must
        fall toward 0 — a frozen last-busy occupancy would hold the KEDA
        occupancy trigger above threshold forever and the fleet would
        never scale back in."""
        if self._last_t is None:
            return 1.0
        idle = max(0.0, time.perf_counter() - self._last_t)
        return math.exp(-idle / _EWMA_TAU_S)

    def snapshot(self) -> dict:
        peak = self.peak_flops()
        decay = self._decay()
        occupancy = self.seat_occupancy * decay
        achieved = self.achieved_flops * decay
        return {
            "decode_seat_occupancy": occupancy,
            "padding_waste_frac": self.padding_waste * decay,
            "achieved_flops_per_s": achieved,
            "mfu": (achieved / peak) if peak > 0 else 0.0,
            "peak_flops_per_s": peak,
            "step_tokens": dict(self.step_tokens),
            "padded_tokens": dict(self.padded_tokens),
            "model_flops_total": self.flops_total,
            "steps": dict(self.steps),
            "occupancy_hist": self.occupancy_hist.snapshot(),
            "step_wall_hist": {
                k: h.snapshot() for k, h in self.wall_hist.items()
            },
        }
