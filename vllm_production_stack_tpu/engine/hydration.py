"""Compute-or-load KV hydration planner (docs/31-hydration-planner.md).

Lower-tier prefix hits (disk tier, remote store) used to be
all-or-nothing: `match_prefix` either BLOCKED the step thread loading the
whole resident run or the engine recomputed it. "Compute Or Load KV
Cache? Why Not Both?" (PAPERS.md) shows the right shape: split the
resident run into chunks, recompute the HEAD while the TAIL fetches
concurrently, and pick the split point from measured fetch bandwidth vs
measured prefill FLOP/s — so TTFT ≈ max(fetch tail, compute tail)
instead of their sum. PR 7 built exactly the inputs this needs:
`LLMEngine.hydration_signal()` carries per-tier measured bandwidth
(`kv_flow.TierBandwidth`), the StepMeter's achieved prefill FLOP/s, and
the analytic per-block KV size.

Three pieces, all OFF the jitted hot path:

- :func:`plan_decisions` — the PURE decision function (unit-testable on
  synthetic bandwidth/FLOP grids). Prefill is sequential, so a loaded
  chunk blocks every later compute chunk until it lands; the only
  overlap-correct shape is *recompute the head, load the tail*. The
  planner picks the split minimizing ``max(compute(head), fetch(tail))``.
  Tiers below the :class:`~.kv_flow.TierBandwidth` sample floor are
  never trusted: in ``auto`` mode the plan declines (the admission falls
  back to the legacy synchronous load, which is also what *measures* the
  tier); in forced ``planner`` mode unmeasured chunks are recomputed.

- :class:`HydrationPlan` / :class:`HydrationChunk` — per-request chunk
  state shared between the step thread (adoption / fallback) and the
  fetcher thread (landing arrays), guarded by one small per-plan lock.

- :class:`Hydrator` — the engine-owned coordinator: builds plans at
  admission, runs ONE background fetcher thread pulling chunk bytes from
  the disk tier / remote store into host RAM, and records every decision
  into the flow meter's ``tpu:kv_hydration_decision_total{choice=}``
  contract counters. The scheduler consumes landed chunks at chunked-
  prefill admission (`Scheduler._consume_hydrated`), falling back to
  recompute when a fetch misses its deadline or fails.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass

from .. import metrics_contract as mc
from ..utils.logging import init_logger

logger = init_logger(__name__)

# decision values for tpu:kv_hydration_decision_total{choice=} — the
# contract owns the closed set; this module records against it
HYDRATION_CHOICES = mc.KV_HYDRATION_CHOICES

# tiers whose bytes are effectively free to "fetch" (already in HBM /
# host RAM) — they never gate a plan on bandwidth measurement
_LOCAL_TIERS = ("hbm", "host")

# fixed per-chunk adoption overhead charged to the fetch timeline (block
# registration + the batched device upload dispatch) so zero-cost local
# chunks don't flap the split point
_CHUNK_OVERHEAD_S = 1e-4

# when the StepMeter has no achieved-FLOP/s sample yet, assume this
# fraction of chip peak (a deliberately conservative MFU guess — prefill
# is the compute-bound phase)
_COLD_MFU_GUESS = 0.3


def plan_decisions(
    chunk_tiers: list[list[str]],
    signal: dict,
    *,
    forced: bool = False,
    start_block: int = 0,
) -> tuple[list[str], dict] | None:
    """Per-chunk load-vs-recompute decisions for one resident run.

    ``chunk_tiers[i]`` is the per-block serving tier of chunk i (from
    ``KVBlockPool.probe_prefix``); ``signal`` is
    ``LLMEngine.hydration_signal()``. Returns ``(decisions, estimates)``
    with ``decisions[i] in ("load", "recompute")`` and ``estimates``
    carrying the per-chunk cost model plus ``est_fetch_total_s`` (the
    deadline input), or ``None`` when the planner cannot engage:

    - no usable compute-rate estimate (no achieved FLOP/s AND no known
      chip peak — a cold engine), or
    - ``forced=False`` (auto mode) and any resident DISK/REMOTE tier in
      the run is below the bandwidth sample floor — the caller falls back
      to the legacy synchronous load, whose transfers are exactly what
      crosses the floor. An unmeasured PEER or DEVICE tier never declines
      the plan (no sync path fetches from peers, over HTTP or over the
      device link); its chunks are priced recompute until the Hydrator's
      bootstrap fetches cross the floor.

    With ``forced=True`` unmeasured-tier chunks are decided "recompute"
    (never trust an estimate built from a single tiny transfer — the
    TierBandwidth sample-floor satellite), and the split is chosen over
    the remaining loadable chunks.

    Prefill is sequential, so overlap only works as *recompute head,
    load tail*: for split s, chunks [0, s) recompute while [s, n) fetch
    concurrently; the makespan model is
    ``max(sum(compute of head + forced-recompute tail), sum(fetch of
    loaded tail))`` and the planner minimizes it over s.
    """
    flops_per_s = float(signal.get("prefill_flops_per_s") or 0.0)
    if flops_per_s <= 0.0:
        flops_per_s = (
            float(signal.get("peak_flops_per_s") or 0.0) * _COLD_MFU_GUESS
        )
    flops_per_token = float(signal.get("flops_per_token") or 0.0)
    if flops_per_s <= 0.0 or flops_per_token <= 0.0:
        return None  # cannot price compute — planner cannot engage
    # an unmeasured DISK/REMOTE tier declines the whole plan in auto mode
    # (the sync fallback load is what feeds the bandwidth floor); an
    # unmeasured PEER or DEVICE tier must NOT — no sync path ever fetches
    # from a peer over either transport, so declining would starve the
    # estimator forever. Peer/device chunks below the floor are priced
    # recompute instead, and the Hydrator's bootstrap fetch
    # (measurement-only) crosses the floor out of band.
    unmeasured_nonpeer = False
    # attention score/value coefficient (FLOPs per token × attended
    # position): at long context this term dominates the matmul term, and
    # pricing recompute without it biases the split toward compute
    attn_coeff = float(signal.get("attn_flops_per_token_ctx") or 0.0)
    block_bytes = float(signal.get("block_bytes") or 0.0)
    # per-tier WIRE bytes per block (engine.hydration_signal): with an
    # at-rest codec the disk/remote/peer hops move int4+scales or fp8
    # payloads, so a fetch costs codec-compressed bytes — this is exactly
    # what shifts load/recompute crossovers in the codec's favor. Tiers
    # absent from the map price at the logical block_bytes.
    wire_bytes = signal.get("wire_block_bytes") or {}
    block_tokens = int(signal.get("block_size_tokens") or 1)
    bw = signal.get("fetch_bandwidth_bytes_per_s") or {}
    measured = signal.get("fetch_bandwidth_measured") or {}

    inf = float("inf")
    compute_s: list[float] = []
    fetch_s: list[float] = []
    pos_tok = start_block * block_tokens  # absolute chunk start position
    for tiers in chunk_tiers:
        n_tok = len(tiers) * block_tokens
        # chunk tokens attend ~their absolute positions: sum over
        # [pos, pos + n) is n × (2·pos + n − 1) / 2
        sum_ctx = n_tok * (2 * pos_tok + n_tok - 1) / 2.0
        compute_s.append(
            (n_tok * flops_per_token + attn_coeff * sum_ctx) / flops_per_s
        )
        pos_tok += n_tok
        cost = _CHUNK_OVERHEAD_S
        for tier in tiers:
            if tier in _LOCAL_TIERS:
                continue  # bytes already local: adoption cost only
            rate = float(bw.get(tier) or 0.0)
            if not measured.get(tier) or rate <= 0.0:
                cost = inf  # below the sample floor: never trusted
                if tier not in ("peer", "device"):
                    unmeasured_nonpeer = True
                break
            cost += float(wire_bytes.get(tier) or block_bytes) / rate
        fetch_s.append(cost)

    if not forced and unmeasured_nonpeer:
        return None  # auto mode: fall back to the sync path (it measures)

    n = len(chunk_tiers)
    best_s, best_cost = n, inf
    for s in range(n + 1):
        head_c = sum(compute_s[:s])
        forced_c = sum(
            compute_s[i] for i in range(s, n) if fetch_s[i] == inf
        )
        tail_f = sum(
            fetch_s[i] for i in range(s, n) if fetch_s[i] < inf
        )
        cost = max(head_c + forced_c, tail_f)
        # strict < keeps the SMALLEST s (most loads) among ties: loading
        # saves the FLOPs even when it doesn't change the makespan
        if cost < best_cost:
            best_s, best_cost = s, cost
    decisions = [
        "load" if i >= best_s and fetch_s[i] != inf else "recompute"
        for i in range(n)
    ]
    est = {
        "compute_s": compute_s,
        "fetch_s": [c if c != inf else -1.0 for c in fetch_s],
        "split": best_s,
        "est_makespan_s": best_cost,
        "est_fetch_total_s": sum(
            fetch_s[i] for i in range(n)
            if decisions[i] == "load" and fetch_s[i] != inf
        ),
        "flops_per_s": flops_per_s,
    }
    return decisions, est


@dataclass
class HydrationChunk:
    """One contiguous run of resident full blocks with a single fate."""

    index: int
    start_block: int  # absolute block index within the prompt
    hashes: list[int]
    tiers: list[str]
    decision: str  # "load" | "recompute"
    # pending → landed | failed (fetcher, under plan.lock) →
    # adopted | recomputed | cancelled (step thread)
    status: str = "pending"
    arrays: list | None = None
    est_fetch_s: float = 0.0
    est_compute_s: float = 0.0

    def tokens(self, block_size: int) -> int:
        return len(self.hashes) * block_size


class HydrationPlan:
    """Per-request chunk ledger shared by the step and fetcher threads.

    The step thread owns ``cursor`` (consumption order is strictly
    front-to-back — prefill is sequential); the fetcher only ever moves
    a chunk pending → landed/failed under ``lock``. ``cancel()`` makes
    in-flight fetch jobs drop their results (preemption, abort, finish
    — the request's attribution was already settled by the scheduler)."""

    def __init__(
        self, request_id: str, chunks: list[HydrationChunk],
        block_size: int, deadline: float, estimates: dict,
        peer_owner: str = "",
    ):
        self.request_id = request_id
        self.chunks = chunks
        self.block_size = block_size
        self.deadline = deadline  # monotonic: pending past this → fallback
        self.estimates = estimates
        # engine URL serving this plan's "peer"-tier blocks (one owner per
        # plan: the probe's peer continuation is a single engine's run)
        self.peer_owner = peer_owner
        self.lock = threading.Lock()
        self.cancelled = False
        self.cursor = 0  # first chunk not fully consumed (step thread)

    def done(self) -> bool:
        return self.cursor >= len(self.chunks)

    def current(self) -> HydrationChunk:
        return self.chunks[self.cursor]

    def advance(self) -> None:
        self.cursor += 1

    def cancel(self) -> None:
        with self.lock:
            self.cancelled = True

    def deferred_tokens(self) -> int:
        """Prompt tokens whose hydration attribution is deferred to chunk
        resolution (load-decided chunks) — the admission-time partition
        counts everything else."""
        return sum(
            c.tokens(self.block_size)
            for c in self.chunks
            if c.decision == "load"
        )

    def unresolved(self) -> list[HydrationChunk]:
        """Load-decided chunks whose fate is still open (pending/landed/
        failed but never adopted or flipped) — the ones settle must
        classify when the request leaves the scheduler early."""
        return [
            c for c in self.chunks
            if c.decision == "load"
            and c.status in ("pending", "landed", "failed")
        ]


class Hydrator:
    """Engine-owned planner coordinator + background chunk fetcher.

    ONE instance per engine (None when no disk/remote tier exists or
    ``--kv-hydration sync``). ``mode``:

    - ``auto`` (default): plan when every resident tier is measured and
      a compute-rate estimate exists; otherwise the admission uses the
      legacy synchronous load — which is also what feeds the bandwidth
      estimator past its sample floor, so auto self-bootstraps.
    - ``planner``: always plan; unmeasured tiers are recomputed
      (the sample-floor rule), never synchronously loaded.
    - ``off``: ignore disk/remote residency entirely (recompute) — an
      operator kill switch.

    The fetcher is one daemon thread: chunk loads are bandwidth-bound
    (disk IO / one HTTP mget per remote span), so a second thread would
    only fight for the same pipe. Disk/remote tier objects are made
    fetch-thread-safe by their own small locks (kv_disk_tier /
    kvstore.client)."""

    MODES = ("auto", "planner", "sync", "off")

    # at most one measurement-only peer fetch per owner per this interval:
    # the sample floor needs MIN_SAMPLES small fetches, not a storm
    BOOTSTRAP_MIN_INTERVAL_S = 5.0

    def __init__(
        self,
        mode: str = "auto",
        chunk_blocks: int = 16,
        timeout_s: float = 0.0,
        flow=None,
        signal_fn=None,
        host_tier=None,
        peer=None,
        heartbeat=None,
        device_pull_fn=None,
    ):
        if mode not in self.MODES:
            raise ValueError(
                f"kv_hydration mode {mode!r}; expected one of {self.MODES}"
            )
        if chunk_blocks < 1:
            raise ValueError("hydration chunk_blocks must be >= 1")
        self.mode = mode
        self.chunk_blocks = chunk_blocks
        # 0 = auto: 3x the plan's estimated fetch total, clamped — a plan
        # that blows 3x past its own estimate was priced off stale
        # bandwidth and recompute is the honest answer
        self.timeout_s = timeout_s
        if flow is None:
            from .kv_flow import NULL_FLOW

            flow = NULL_FLOW
        self.flow = flow
        self.signal_fn = signal_fn
        self.host_tier = host_tier
        # peer-engine KV tier client (engine/kv_peer.PeerKVTier, None when
        # --kv-peer-fetch is off): "peer"-tier chunks fetch from the plan's
        # owner engine over dedicated per-owner connections
        self.peer = peer
        # device-collective peer pull (engine._device_peer_pull, None when
        # no mesh identity): "device"-tier chunks land straight in THIS
        # engine's HBM pool via ICI/DCN collectives — (owner_url, hashes)
        # -> resident block count, parked at refcount 0 for adoption
        self.device_pull_fn = device_pull_fn
        self._q: queue.Queue = queue.Queue()
        self._thread: threading.Thread | None = None
        self._closed = False
        # thread-liveness heartbeat (docs/37-flight-recorder.md,
        # flightrec.ThreadRegistry "hydration_fetch"): beaten per fetch
        # job, idle while blocked on the empty queue — a stale-while-busy
        # beat is the fetcher-deadlocked-under-a-tier-lock wedge
        self.heartbeat = heartbeat
        # dedicated remote connection for the fetcher thread: its chunk
        # mgets can run for seconds and must never hold the shared fetch
        # lock the step thread's probes contend on (kvstore/client.py)
        self._remote_conn = None
        # per-owner dedicated peer fetch connections (same rationale)
        self._peer_conns: dict[str, object] = {}
        # last measurement-only bootstrap per owner (step thread writes,
        # monotonic clock) — rate-limits the sample-floor warmup
        self._bootstrap_t: dict[str, float] = {}

    # -- planning (step thread) -------------------------------------------

    def build_plan(
        self,
        request_id: str,
        start_block: int,
        hashes: list[int],
        tiers: list[str],
        block_size: int,
        peer_owner: str = "",
    ) -> HydrationPlan | None:
        """Plan the resident run [start_block, start_block + len(hashes))
        or return None (caller falls back to the legacy sync path).
        `peer_owner` is the engine URL serving the run's "peer"-tier
        blocks (probe_prefix's peer continuation)."""
        if self.mode in ("sync", "off") or not hashes:
            return None
        chunk_tiers: list[list[str]] = [
            tiers[i : i + self.chunk_blocks]
            for i in range(0, len(tiers), self.chunk_blocks)
        ]
        signal = self.signal_fn()
        if peer_owner:
            # sample-floor warmup: the peer/device tiers have no sync
            # fallback to measure them, so an unmeasured one triggers a
            # bounded measurement-only fetch on the fetcher thread
            # (rate-limited per owner); until it crosses the floor, its
            # chunks price as recompute and the request loses nothing
            for wire_tier in ("peer", "device"):
                if wire_tier in tiers:
                    self._maybe_bootstrap(
                        peer_owner,
                        [h for h, t in zip(hashes, tiers)
                         if t == wire_tier],
                        signal, tier=wire_tier,
                    )
        planned = plan_decisions(
            chunk_tiers, signal,
            forced=self.mode == "planner", start_block=start_block,
        )
        if planned is None:
            return None
        decisions, est = planned
        chunks: list[HydrationChunk] = []
        off = 0
        for i, ct in enumerate(chunk_tiers):
            chunks.append(HydrationChunk(
                index=i,
                start_block=start_block + off,
                hashes=hashes[off : off + len(ct)],
                tiers=list(ct),
                decision=decisions[i],
                est_fetch_s=max(0.0, est["fetch_s"][i]),
                est_compute_s=est["compute_s"][i],
            ))
            off += len(ct)
        timeout = self.timeout_s
        if timeout <= 0.0:
            timeout = min(30.0, max(0.5, 3.0 * est["est_fetch_total_s"]))
        return HydrationPlan(
            request_id, chunks, block_size,
            deadline=time.monotonic() + timeout, estimates=est,
            peer_owner=peer_owner,
        )

    def _maybe_bootstrap(
        self, owner: str, peer_hashes: list[int], signal: dict,
        tier: str = "peer",
    ) -> None:
        """Enqueue one measurement-only fetch against `owner` when the
        wire tier's bandwidth estimate is still below the sample floor
        (step thread; the fetch itself runs on the fetcher thread). For
        ``tier="peer"`` the payload is DISCARDED — only the TierBandwidth
        samples matter; for ``tier="device"`` the pulled blocks land
        parked in the pool (a collective has no discard path) and the
        next admission re-plans against both the measured link and the
        now-HBM-resident run."""
        if tier == "device":
            if self.device_pull_fn is None or not peer_hashes:
                return
        elif self.peer is None or not peer_hashes:
            return
        if (signal.get("fetch_bandwidth_measured") or {}).get(tier):
            return
        now = time.monotonic()
        key = (owner, tier)
        if now - self._bootstrap_t.get(key, -1e9) < (
            self.BOOTSTRAP_MIN_INTERVAL_S
        ):
            return
        self._bootstrap_t[key] = now
        # enough blocks to cross MIN_BYTES in two samples where possible
        from .kv_flow import TierBandwidth

        block_bytes = float(signal.get("block_bytes") or 0.0)
        want = TierBandwidth.MIN_SAMPLES * max(
            1, int(TierBandwidth.MIN_BYTES // block_bytes) + 1
        ) if block_bytes > 0 else len(peer_hashes)
        self._ensure_thread()
        self._q.put(("bootstrap", owner, peer_hashes[:want], tier))

    def launch(self, plan: HydrationPlan) -> None:
        """Record the plan's decisions and enqueue its load chunks for the
        fetcher (step thread, right after the request admits). Host-ring
        blocks inside load chunks are resolved HERE — the ring is step-
        thread state the fetcher must never touch."""
        for chunk in plan.chunks:
            self.flow.record_decision(chunk.decision)
            if chunk.decision != "load":
                continue
            arrays: list = [None] * len(chunk.hashes)
            for i, (h, tier) in enumerate(zip(chunk.hashes, chunk.tiers)):
                if tier == "host" and self.host_tier is not None:
                    arrays[i] = self.host_tier.peek_bytes(h)
            chunk.arrays = arrays
            self._ensure_thread()
            self._q.put((plan, chunk))

    # -- fetcher (background thread) --------------------------------------

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._fetch_loop, name="kv-hydration-fetch",
                daemon=True,
            )
            self._thread.start()

    def _fetch_loop(self) -> None:
        hb = self.heartbeat
        while True:
            if hb is not None:
                hb.idle()  # parked on an empty queue is not a stall
            item = self._q.get()
            if hb is not None:
                hb.beat()  # busy: silence from here on IS a stall signal
            if item is None:
                if hb is not None:
                    hb.idle()
                return
            if item[0] == "bootstrap":
                _, owner, hashes, tier = item
                try:
                    self._bootstrap_fetch(owner, hashes, tier)
                except Exception:
                    logger.exception(
                        "%s bandwidth bootstrap against %s faulted",
                        tier, owner,
                    )
                continue
            plan, chunk = item
            try:
                self._fetch_chunk(plan, chunk)
            except Exception:
                logger.exception(
                    "hydration fetch of chunk %d (request %s) faulted",
                    chunk.index, plan.request_id,
                )
                with plan.lock:
                    if chunk.status == "pending":
                        chunk.status = "failed"

    def _bootstrap_fetch(
        self, owner: str, hashes: list[int], tier: str = "peer"
    ) -> None:
        """Measurement-only peer fetches (fetcher thread): split the hash
        list into MIN_SAMPLES round trips so one warmup crosses both
        halves of the sample floor. HTTP payloads are discarded — adopting
        them would need the step thread's pool, and the next admission
        re-plans against the now-measured tier anyway. Device pulls land
        parked blocks instead (the collective IS the adoption); the pull
        records its own flow samples under tier="device"."""
        from .kv_flow import TierBandwidth

        if tier == "device":
            if self.device_pull_fn is None or not hashes:
                return
            per = max(1, len(hashes) // TierBandwidth.MIN_SAMPLES)
            for i in range(0, len(hashes), per):
                got = self.device_pull_fn(owner, hashes[i : i + per])
                if not got:
                    return  # owner refused/evicted: stop burning pulls
            return
        if self.peer is None or not hashes:
            return
        per = max(1, len(hashes) // TierBandwidth.MIN_SAMPLES)
        conn = self._peer_conn(owner)
        for i in range(0, len(hashes), per):
            got = self.peer.fetch_run(
                owner, hashes[i : i + per], conn=conn, bootstrap=True
            )
            if not got:
                return  # owner unreachable/evicted: stop burning fetches

    def _peer_conn(self, owner: str):
        conn = self._peer_conns.get(owner)
        if conn is None and self.peer is not None:
            conn = self._peer_conns[owner] = self.peer.new_fetch_conn(owner)
        return conn

    def _fetch_chunk(self, plan: HydrationPlan, chunk: HydrationChunk) -> None:
        with plan.lock:
            if plan.cancelled:
                chunk.status = "cancelled"
                return
        host = self.host_tier
        disk = getattr(host, "disk", None) if host is not None else None
        remote = getattr(host, "remote", None) if host is not None else None
        arrays = list(chunk.arrays or [None] * len(chunk.hashes))
        ok = True
        i = 0
        while i < len(chunk.hashes):
            tier = chunk.tiers[i]
            if arrays[i] is not None or tier == "hbm":
                # hbm-tier blocks need no bytes: adoption re-acquires the
                # resident block (or the chunk falls back if it was
                # evicted in the meantime)
                i += 1
                continue
            if tier == "disk" and disk is not None:
                arr = disk.load(chunk.hashes[i])  # meters disk/in itself
                if arr is None:
                    ok = False
                    break
                arrays[i] = arr
                i += 1
            elif tier == "remote" and remote is not None:
                j = i
                while (
                    j < len(chunk.hashes)
                    and chunk.tiers[j] == "remote"
                    and arrays[j] is None
                ):
                    j += 1
                if self._remote_conn is None and hasattr(
                    remote, "new_fetch_conn"
                ):
                    self._remote_conn = remote.new_fetch_conn()
                got = remote.fetch_run(
                    chunk.hashes[i:j], conn=self._remote_conn
                )
                if len(got) < j - i:
                    ok = False  # run broke mid-span: partial is useless
                for k, arr in enumerate(got):
                    arrays[i + k] = arr
                if not ok:
                    break
                i = j
            elif (
                tier == "peer"
                and self.peer is not None
                and plan.peer_owner
            ):
                # one batched /kv/peer_fetch per consecutive peer span,
                # over this owner's dedicated connection — the owner
                # serves the run straight out of its HBM/host tiers
                j = i
                while (
                    j < len(chunk.hashes)
                    and chunk.tiers[j] == "peer"
                    and arrays[j] is None
                ):
                    j += 1
                got = self.peer.fetch_run(
                    plan.peer_owner, chunk.hashes[i:j],
                    conn=self._peer_conn(plan.peer_owner),
                )
                if len(got) < j - i:
                    ok = False  # owner evicted mid-run: partial is useless
                for k, arr in enumerate(got):
                    arrays[i + k] = arr
                if not ok:
                    break
                i = j
            elif (
                tier == "device"
                and self.device_pull_fn is not None
                and plan.peer_owner
            ):
                # one collective pull per consecutive device span: the
                # owner's blocks land straight in THIS engine's HBM pool
                # (parked at refcount 0), so arrays stay None and
                # adoption re-acquires them by hash — no host-RAM bytes
                # ever exist on this path
                j = i
                while (
                    j < len(chunk.hashes)
                    and chunk.tiers[j] == "device"
                    and arrays[j] is None
                ):
                    j += 1
                got = self.device_pull_fn(
                    plan.peer_owner, chunk.hashes[i:j]
                )
                if int(got or 0) < j - i:
                    # owner refused (fingerprint/geometry), evicted
                    # mid-run, or the trigger faulted: partial coverage
                    # is useless, the chunk falls back to recompute
                    ok = False
                    break
                i = j
            else:
                # a "host" block whose ring entry vanished before launch
                # could resolve it, or a tier with no backing object
                ok = False
                break
        with plan.lock:
            if plan.cancelled:
                chunk.status = "cancelled"
            elif chunk.status == "pending":
                # only a still-pending chunk takes the payload: a chunk
                # the step thread already flipped to fallback released
                # its arrays, and re-attaching them here would pin dead
                # multi-MB payloads on the live plan
                chunk.arrays = arrays
                chunk.status = "landed" if ok else "failed"

    def pending_jobs(self) -> int:
        return self._q.qsize()

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._thread is not None and self._thread.is_alive():
            self._q.put(None)
            self._thread.join(timeout=2)
        if self._remote_conn is not None:
            self._remote_conn.close()
            self._remote_conn = None
        for conn in self._peer_conns.values():
            conn.close()
        self._peer_conns.clear()
        if self.peer is not None:
            self.peer.close()

    def snapshot(self) -> dict:
        """Operator view for GET /debug/hydration."""
        snap = {
            "mode": self.mode,
            "chunk_blocks": self.chunk_blocks,
            "timeout_s": self.timeout_s,
            "queued_fetch_jobs": self._q.qsize(),
            "device_pull": self.device_pull_fn is not None,
        }
        if self.peer is not None:
            snap["peer"] = self.peer.snapshot()
        return snap
