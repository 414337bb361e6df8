"""KV-hierarchy flow telemetry (docs/30-kv-flow-telemetry.md).

The tiering stack's occupancy gauges (tpu:engine_kv_tier_usage_perc) say
how FULL each tier is; this module measures how fast bytes actually MOVE
between tiers and where each request's prefix actually came from — the
measurement substrate the compute-or-load hydration planner (ROADMAP
item 3, "Compute Or Load KV Cache? Why Not Both?") needs before it can
pick load-vs-recompute per chunk by measured fetch bandwidth vs prefill
FLOP/s.

Two instruments, mirroring the PR 6 StepMeter/GoodputLedger split:

- **Transfer meters**: every tier move — host-ring offload/reload,
  disk store/load, remote put/fetch, device-path PD transfer — records
  bytes, blocks and wall latency into per-(tier, direction) counters, a
  fixed-bucket latency histogram, and a :class:`TierBandwidth`
  recent-mean estimator. Plain
  ints under one small lock (transfers are orders of magnitude rarer
  than steps); the exporter renders histograms from cumulative bucket
  counts at scrape time, so no prometheus objects ride the engine or
  writer threads.

- **Hydration attribution** (its counters are part of the metric
  contract, like the goodput ledger): every admitted request's
  prompt tokens are classified EXACTLY once by KV origin, and the
  partition is audited::

      hbm_hit + host_reload + disk_load + remote_fetch + peer_fetch
          + recomputed == prompt_tokens

Direction semantics: ``"in"`` moves bytes toward the HBM pool
(hydration — reload/load/fetch/PD-adopt), ``"out"`` moves them away
(offload — store/put/PD-export). ``tier`` names the non-HBM side of the
hop, so a disk block promoted through the ring into HBM records one
``disk/in`` sample (disk → RAM) and one ``host/in`` sample (RAM → HBM):
per-tier meters count HOPS, not end-to-end journeys.
"""

from __future__ import annotations

import math
import threading
import time

from .. import metrics_contract as mc
from .saturation import _Hist

TRANSFER_TIERS = mc.KV_TRANSFER_TIERS
DIRECTIONS = mc.KV_TRANSFER_DIRECTIONS
HYDRATION_SOURCES = mc.KV_HYDRATION_SOURCES

# wall seconds per transfer batch: spans sub-µs host copies to multi-second
# remote fetches over a cold link
TRANSFER_SECONDS_BUCKETS = (
    1e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3,
    0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
)

_BW_TAU_S = 60.0  # horizon of the recent-mean bandwidth estimator


class TierBandwidth:
    """Time-decayed recent-mean bandwidth for one (tier, direction).

    Separate exponentially-decayed accumulators for bytes and busy
    seconds, decayed by the WALL gap since the previous sample; the
    estimate is their ratio. For a burst of back-to-back transfers this
    converges to total_bytes / total_seconds (a plain duration-weighted
    mean — robust to microsecond samples where a per-sample-value EWMA
    would be noise), while samples older than ~:data:`_BW_TAU_S` fade
    out. The estimate deliberately does NOT decay toward zero when idle:
    it answers "how fast CAN this tier move bytes", a capability the
    hydration planner consults exactly when the tier has been idle —
    unlike the occupancy EWMAs, which measure utilization and must fall.

    A failed transfer recorded as (0 bytes, elapsed) drags the estimate
    toward zero honestly: during a remote-store outage the measured
    fetch bandwidth IS ~0, which is precisely what should flip the
    planner to recompute.

    **Sample floor** (docs/31-hydration-planner.md): :attr:`measured` is
    False until at least :data:`MIN_SAMPLES` transfers totalling
    :data:`MIN_BYTES` undecayed bytes have been observed — a single tiny
    first transfer after startup must never become the estimate the
    compute-or-load planner trusts. ``hydration_signal()`` reports the
    flag per tier; the planner treats an unmeasured tier as
    recompute-only (forced mode) or declines the plan entirely (auto
    mode, where the synchronous fallback load is exactly what feeds the
    floor).
    """

    # floors for `measured`: enough independent samples that one outlier
    # can't own the ratio, and enough real payload that the estimate
    # reflects steady-state transfer, not connection setup
    MIN_SAMPLES = 2
    MIN_BYTES = 1 << 20

    __slots__ = ("_bytes", "_seconds", "_last_t", "samples", "total_bytes")

    def __init__(self) -> None:
        self._bytes = 0.0
        self._seconds = 0.0
        self._last_t: float | None = None
        self.samples = 0
        self.total_bytes = 0  # undecayed — feeds the measurement floor

    def record(self, nbytes: int, seconds: float, now: float) -> None:
        if self._last_t is not None:
            decay = math.exp(-max(0.0, now - self._last_t) / _BW_TAU_S)
            self._bytes *= decay
            self._seconds *= decay
        self._last_t = now
        self._bytes += nbytes
        self._seconds += max(seconds, 1e-9)
        self.samples += 1
        self.total_bytes += int(nbytes)

    @property
    def bytes_per_s(self) -> float:
        return self._bytes / self._seconds if self._seconds > 0 else 0.0

    @property
    def measured(self) -> bool:
        return (
            self.samples >= self.MIN_SAMPLES
            and self.total_bytes >= self.MIN_BYTES
        )


class KVFlowMeter:
    """Per-tier transfer meters + per-request hydration attribution.

    One instance per engine, shared by every tier object (host ring,
    disk tier, remote client, device-path transfer) — the engine thread,
    the remote writer thread and HTTP executor threads all record here,
    so mutation happens under ``_lock``.
    """

    def __init__(self, _null: bool = False):
        # NULL_FLOW only: a complete no-op, bandwidth estimators included
        # (the singleton is shared by UNRELATED standalone tier objects —
        # cross-polluting their bandwidth samples would fabricate a
        # "measured" tier out of other objects' transfers)
        self._null = _null
        self._lock = threading.Lock()
        self.bytes: dict[tuple[str, str], int] = {}
        # LOGICAL (decoded) bytes per key: equals `bytes` on uncompressed
        # tiers; under an at-rest codec (kv_codec) `bytes` holds WIRE
        # bytes and logical/wire is the tier's measured compression ratio
        # (tpu:kv_tier_compression_ratio)
        self.logical_bytes: dict[tuple[str, str], int] = {}
        self.blocks: dict[tuple[str, str], int] = {}
        self.transfers: dict[tuple[str, str], int] = {}
        self.seconds: dict[tuple[str, str], _Hist] = {}
        self.bandwidth: dict[tuple[str, str], TierBandwidth] = {}
        for tier in TRANSFER_TIERS:
            for direction in DIRECTIONS:
                key = (tier, direction)
                self.bytes[key] = 0
                self.logical_bytes[key] = 0
                self.blocks[key] = 0
                self.transfers[key] = 0
                self.seconds[key] = _Hist(TRANSFER_SECONDS_BUCKETS)
                self.bandwidth[key] = TierBandwidth()
        # audited partition counters (tokens), keyed by HYDRATION_SOURCES
        self.hydration: dict[str, int] = {s: 0 for s in HYDRATION_SOURCES}
        self.hydrated_requests = 0
        # compute-or-load planner decisions per CHUNK (closed choice set,
        # docs/31-hydration-planner.md): load / recompute at plan time,
        # plus fallback_recompute when a load chunk misses its deadline
        # or its fetch fails. Contract counters — always on, like the
        # attribution partition.
        self.decisions: dict[str, int] = {
            c: 0 for c in mc.KV_HYDRATION_CHOICES
        }

    # -- transfer meters ---------------------------------------------------

    def record(
        self, tier: str, direction: str, nbytes: int, blocks: int,
        seconds: float, logical_nbytes: int | None = None,
    ) -> None:
        """One transfer batch: `blocks` KV blocks totalling `nbytes` moved
        in `seconds` of wall time. `nbytes` is always WIRE bytes — what
        actually crossed the link or hit the disk — so the TierBandwidth
        estimators (and therefore the hydration planner) price the tier
        as it performs under the at-rest codec. `logical_nbytes` is the
        decoded size of the same batch (defaults to `nbytes` for
        uncompressed hops); the logical/wire quotient is the tier's
        compression-ratio gauge. A FAILED transfer should still be
        recorded with whatever partial batch completed (possibly 0 bytes)
        — the elapsed time is real, and losing it would overstate the
        tier's bandwidth exactly when the planner most needs the truth."""
        if self._null:
            self.bandwidth[(tier, direction)]  # unknown key: still loud
            return
        key = (tier, direction)  # unknown tier/direction: KeyError, loud
        now = time.perf_counter()
        with self._lock:
            self.bandwidth[key].record(int(nbytes), seconds, now)
            self.bytes[key] += int(nbytes)
            self.logical_bytes[key] += int(
                nbytes if logical_nbytes is None else logical_nbytes
            )
            self.blocks[key] += int(blocks)
            self.transfers[key] += 1
            self.seconds[key].observe(seconds)

    # -- hydration attribution ---------------------------------------------

    def record_hydration(
        self, counts: dict[str, int], requests: int = 1
    ) -> None:
        """One admitted request's prompt-token partition. Keys must come
        from HYDRATION_SOURCES (closed set — a typo fails loud, even at
        count 0: a mistyped key that's usually zero would otherwise drop
        tokens from the audited partition only on the rare nonzero hit).
        ``requests=0`` is the hydration planner's incremental form: a
        planned chunk's tokens are classified when its fate resolves
        (adopted → its tier's source, fallback/cancel → recomputed), so
        the partition stays exact while outcomes are still in flight."""
        with self._lock:
            for source, n in counts.items():
                self.hydration[source] += int(n)
            self.hydrated_requests += requests

    def record_decision(self, choice: str, n: int = 1) -> None:
        """One planner chunk decision (tpu:kv_hydration_decision_total).
        Closed choice set — unknown choices fail loud, like sources."""
        with self._lock:
            if choice not in self.decisions:
                raise KeyError(choice)
            self.decisions[choice] += n

    # -- reporting -----------------------------------------------------------

    def bandwidth_bytes_per_s(self) -> dict[tuple[str, str], float]:
        with self._lock:
            return {k: bw.bytes_per_s for k, bw in self.bandwidth.items()}

    def bandwidth_measured(self) -> dict[tuple[str, str], bool]:
        """Per-(tier, direction) sample-floor state — the planner's
        trust gate on each bandwidth estimate."""
        with self._lock:
            return {k: bw.measured for k, bw in self.bandwidth.items()}

    def snapshot(self) -> dict:
        """Cumulative counters + histograms + bandwidth estimates, in the
        shape EngineMetrics renders (keys are "tier/direction")."""
        with self._lock:
            return {
                "bytes": {f"{t}/{d}": v for (t, d), v in self.bytes.items()},
                "logical_bytes": {
                    f"{t}/{d}": v
                    for (t, d), v in self.logical_bytes.items()
                },
                # measured logical/wire ratio per key (1.0 until bytes
                # move — a ratio gauge that reads 0 would look like
                # infinite compression on dashboards)
                "compression_ratio": {
                    f"{t}/{d}": (
                        self.logical_bytes[(t, d)] / v if v > 0 else 1.0
                    )
                    for (t, d), v in self.bytes.items()
                },
                "blocks": {
                    f"{t}/{d}": v for (t, d), v in self.blocks.items()
                },
                "transfers": {
                    f"{t}/{d}": v for (t, d), v in self.transfers.items()
                },
                "seconds_hist": {
                    f"{t}/{d}": h.snapshot()
                    for (t, d), h in self.seconds.items()
                },
                "bandwidth_bytes_per_s": {
                    f"{t}/{d}": bw.bytes_per_s
                    for (t, d), bw in self.bandwidth.items()
                },
                # sample-floor state per key: the exporter gates the
                # bandwidth GAUGE on it (a sub-floor estimate is one tiny
                # transfer's noise — rendering it would let scrapers, e.g.
                # the router's migrate pricing, trust a number the planner
                # itself refuses to)
                "bandwidth_measured": {
                    f"{t}/{d}": bw.measured
                    for (t, d), bw in self.bandwidth.items()
                },
                "hydration": dict(self.hydration),
                "hydrated_requests": self.hydrated_requests,
                "decisions": dict(self.decisions),
            }


# Shared no-op singleton for tier objects constructed without an engine
# (unit tests, standalone tools): call sites never branch on `if flow:`.
NULL_FLOW = KVFlowMeter(_null=True)
