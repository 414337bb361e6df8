"""Continuous-batching scheduler with chunked prefill and preemption.

The reference's engines run vLLM's scheduler (external, controlled via flags
like --enable-chunked-prefill, helm deployment-vllm-multi.yaml:140-146); this
is the TPU engine's own: it emits fixed-*logical* work items (one prefill
chunk, or one decode batch) which the model runner pads into bucketed device
shapes. Policy: a ROUND gives every seat one turn of the device — the rows
that decode take theirs together (one window), every other seat may take one
prefill chunk — so a long prompt can't stall token generation (the point of
chunked prefill), decode can't starve admissions, and seats do not stand
empty behind a queue (`Scheduler.schedule`).
"""

from __future__ import annotations

import threading
from collections import deque
from dataclasses import dataclass, field

from ..qos import FairShareClock, TenantAccounting
from .config import CacheConfig, ModelConfig, SchedulerConfig
from .kv_cache import KVBlockPool, StateSlots, WindowPageGroup, chain_hash
from .request import Request, RequestStatus
from .saturation import GoodputLedger
from .spec_decode import propose_ngram


def _is_stop_token(
    tok: int, sampling, eos: int | None, n_outputs: int
) -> bool:
    """THE stop-token predicate — shared by the bulk-accept cut scan and
    _maybe_finish so a new stop condition can't be added to one and silently
    missed by the other. n_outputs counts output tokens INCLUDING `tok`
    (min_tokens suppresses eos/stop finishes until satisfied)."""
    if n_outputs < sampling.min_tokens:
        return False
    return (eos is not None and tok == eos) or tok in sampling.stop_token_ids


@dataclass
class PrefillWork:
    """One batched prefill dispatch: one chunk from each of N requests, laid
    out by the runner (`ModelRunner._dispatch_prefill`: packed end to end on
    one token axis, or a padded (B, T) under dp > 1). Per-request `sample[i]` is set
    when row i's chunk reaches the end of its prompt (its last-token logits
    produce the first output token). Batching prompts into one dispatch is
    where prefill throughput comes from — B=1-per-dispatch serializes the MXU
    on small matmuls."""

    requests: list[Request] = field(default_factory=list)
    token_ids: list[list[int]] = field(default_factory=list)
    positions: list[list[int]] = field(default_factory=list)
    slot_mappings: list[list[int]] = field(default_factory=list)
    context_lens: list[int] = field(default_factory=list)
    sample: list[bool] = field(default_factory=list)
    # the chunk's place in its round (`Scheduler.schedule`): 1 = the first
    # after a decode window; 0 where no row was decode-ready (pure prefill)
    round_chunk: int = 0

    def add_row(
        self,
        request: Request,
        token_ids: list[int],
        positions: list[int],
        slot_mapping: list[int],
        context_len: int,
        sample: bool,
    ) -> None:
        self.requests.append(request)
        self.token_ids.append(token_ids)
        self.positions.append(positions)
        self.slot_mappings.append(slot_mapping)
        self.context_lens.append(context_len)
        self.sample.append(sample)


@dataclass
class DecodeWork:
    """A window of `window` decode steps for each request in the batch, fused
    into one device dispatch (engine/model_runner.py decode-window program).
    Blocks are pre-allocated to cover the whole window; tokens sampled past a
    per-request stop condition are discarded in postprocess."""

    requests: list[Request]
    window: int = 1
    token_ids: list[int] = field(default_factory=list)  # first token per req
    positions: list[int] = field(default_factory=list)  # first position per req
    # async pipeline: row index into the IN-FLIGHT step (a decode window,
    # a verify, or the prefill chunk that ends this row's prompt) whose last
    # sampled (device-resident) token is this row's input, or -1 when
    # token_ids[i] carries a host-known token. Empty = all host (sync path).
    chain_rows: list[int] = field(default_factory=list)
    # generation by diffusion over blocks (the model runner's window of
    # block passes): per row the tokens it already HAS in the window, from
    # `positions[i]` on — the tail of its prompt in its first window,
    # nothing after. The window yields `window - len(block_tokens[i])`
    # tokens to the row; no row chains (`token_ids` stays empty). Empty for
    # every other model
    block_tokens: list[list[int]] = field(default_factory=list)


@dataclass
class VerifyWork:
    """One speculative-verification dispatch (engine/spec_decode.py): each
    row feeds [current token] + its proposal; the model's argmax at every
    position confirms or replaces proposals, yielding 1..k+1 tokens per
    row in one dispatch. Rows without a proposal feed just their current
    token (a plain greedy decode step). On the pipelined step loop a
    verify dispatch is in-flight work like a decode window: its rows
    advance speculatively by their fed length (full acceptance), and a
    partial acceptance at resolve time invalidates whatever was chained on
    top — the same rollback a mid-window stop takes. A verify can itself
    be CHAINED on an in-flight verify (chain_rows): the in-flight step's
    fed proposals are host-known values under the full-acceptance
    speculation, so the proposer extends them, and only the in-flight
    bonus token — this row's first fed token — is spliced device-side."""

    requests: list[Request] = field(default_factory=list)
    token_ids: list[list[int]] = field(default_factory=list)  # fed tokens
    positions: list[list[int]] = field(default_factory=list)
    proposals: list[list[int]] = field(default_factory=list)
    context_lens: list[int] = field(default_factory=list)  # resident after
    # which proposer drafted each row ("ngram" | "draft") — acceptance
    # counters attribute per proposer (tpu:spec_decode_*_tokens_total)
    proposers: list[str] = field(default_factory=list)
    # async pipeline: row index into the IN-FLIGHT VerifyWork whose
    # device-resident bonus token is this row's first fed token, or -1
    # when token_ids[i][0] carries a host-known token (non-chained row /
    # sync path). Empty = all host.
    chain_rows: list[int] = field(default_factory=list)


ScheduleOutput = PrefillWork | DecodeWork | VerifyWork

# chained decode windows a verify-capable row rides after a failed propose
# attempt before it sits one step out to re-propose with resolved values —
# without the sit-out the steady two-deep pipeline never hands such a row a
# proposal window again (its tokens are perpetually in flight at schedule
# time), and speculation would silently disengage after the first window
SPEC_RETRY_WINDOWS = 1


class Scheduler:
    def __init__(
        self,
        model_config: ModelConfig,
        cache_config: CacheConfig,
        scheduler_config: SchedulerConfig,
        host_tier=None,
        need_slot_mappings: bool = False,
        accounting: TenantAccounting | None = None,
        flow=None,
        hydrator=None,
        window_blocks: int | None = None,
    ):
        self.model_config = model_config
        self.cache_config = cache_config
        self.config = scheduler_config
        self.block_size = cache_config.block_size
        # generation by diffusion over blocks: the block length (0: the
        # model yields one token a row and step, like every other)
        bd = model_config.block_diffusion
        self._bd_block = bd.block_length if bd is not None else 0
        if self._bd_block and cache_config.enable_prefix_caching:
            raise ValueError(
                f"{model_config.model}: prefix caching is not built for "
                "generation by diffusion over blocks (a hit that covers the "
                "whole prompt leaves a prefill nothing to compute and no "
                "token to yield: unproven here); LLMEngine turns it off")
        # per-token slot mappings feed only the sp>1 prefill path (row
        # scatter); the paged path commits blockwise, so skipping ~T Python
        # _slot calls per scheduled chunk keeps the host off the hot path
        self.need_slot_mappings = need_slot_mappings
        self.pool = KVBlockPool(
            cache_config.num_blocks,
            cache_config.block_size,
            cache_config.enable_prefix_caching,
            host_tier=host_tier,
        )
        # `window_blocks` (engine/memory.py:resolve_window_blocks; never
        # with prefix caching): the window layers' pages live in a second
        # group of that many pages (kv_cache.WindowPageGroup)
        if window_blocks is not None:
            from .memory import window_row_blocks

            if cache_config.enable_prefix_caching:
                raise ValueError(
                    "a window page group gives pages back behind the "
                    "window: a prefix hit could not find them")
            self._window_row_blocks = window_row_blocks(
                model_config, cache_config, scheduler_config)
            self.pool.window = WindowPageGroup(
                window_blocks, cache_config.block_size,
                model_config.max_window)
        if model_config.state_layers:
            # layers that hold a recurrent state: a slot a seat beside the
            # pages (kv_cache.StateSlots)
            if cache_config.enable_prefix_caching:
                raise ValueError(
                    "a prefix hit needs the recurrent state at its "
                    "boundary, which no slot keeps")
            self.pool.state = StateSlots(scheduler_config.max_num_seqs + 1)
        self.waiting: deque[Request] = deque()
        self.running: list[Request] = []
        self._hash_chains: dict[str, list[int]] = {}  # req id -> per-block hashes
        # prefill chunks dispatched since the last decode window: where the
        # round stands (schedule())
        self._round_chunks = 0
        self._last_was_verify = False
        # the round's count and the verify flag as the last schedule() that
        # returned work found them: a rollback of that work puts them back,
        # so the serial re-dispatch takes the turn the serial loop would
        self._turn_before = (0, False)
        # the last schedule() left a seat empty with a request waiting
        # because the pool could not carry it to its end (`_can_admit`)
        self.seat_held_by_pool = False
        self.total_preemptions = 0
        # speculative-decoding counters (vLLM metric parity:
        # spec_decode_num_draft_tokens / num_accepted_tokens), plus the
        # per-proposer split behind tpu:spec_decode_proposed_tokens_total /
        # accepted_tokens_total{proposer=} (closed label set)
        self.spec_proposed_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_proposed_by = {"ngram": 0, "draft": 0}
        self.spec_accepted_by = {"ngram": 0, "draft": 0}
        # draft-model proposer (engine/spec_decode.DraftModelProposer) —
        # the engine attaches it when --speculative-config draft; n-gram
        # stays the zero-weight fallback for rows it declines
        self.draft_proposer = None
        # requests whose deadline expired while queued or decoding (the
        # admission-time "would queue past deadline" rejections are counted
        # by the engine — they never reach the scheduler)
        self.deadline_expired_total = 0
        # requests finished outside a step (e.g. resumed request that outgrew
        # the pool) — the engine drains these to emit terminal outputs
        self._finished_externally: list[Request] = []
        # -- multi-tenant QoS (docs/27-multitenancy.md) --------------------
        # per-tenant weighted fair share + accounting. _qos_active latches
        # True on the first request carrying non-default tenant stamps;
        # until then every pick/victim path short-circuits to the pre-QoS
        # FIFO behavior, so an unconfigured stack pays nothing.
        self.accounting = accounting or TenantAccounting()
        self._fair = FairShareClock()
        self._qos_active = False
        # shed evictions: the admission gate (HTTP threads, lock-free) marks
        # a lowest-priority WAITING request for eviction when a higher-
        # priority request would otherwise be refused at a full queue; the
        # step thread applies the marks at the top of schedule().
        self._evict_lock = threading.Lock()
        self._evict_rids: set[str] = set()
        self.shed_evictions = 0
        # goodput ledger (docs/29-saturation-slo.md): every device-sampled
        # token classified exactly once as delivered or wasted{reason}.
        # Mutated only under the engine lock (postprocess / finish /
        # preempt here, plus the engine's pipeline-rollback sites).
        self.ledger = GoodputLedger()
        # KV flow meter (docs/30-kv-flow-telemetry.md): _admit records each
        # request's hydration partition here exactly once. Standalone
        # construction (tests) gets its own meter so the attribution
        # counters always exist; the engine passes its shared one.
        if flow is None:
            from .kv_flow import KVFlowMeter

            flow = KVFlowMeter()
        self.flow = flow
        # compute-or-load hydration planner (docs/31-hydration-planner.md,
        # engine/hydration.Hydrator): when set, first admissions with a
        # disk/remote-resident prefix split it into chunks decided
        # load-vs-recompute, and _try_add_chunk consumes fetched chunks as
        # they land instead of blocking the whole reload in match_prefix.
        # None (unit tests, engines without lower tiers) = legacy path.
        self.hydrator = hydrator

    # -- admission ---------------------------------------------------------

    def add_request(self, req: Request) -> None:
        if req.num_prompt_tokens >= self.model_config.max_model_len:
            raise ValueError(
                f"prompt of {req.num_prompt_tokens} tokens exceeds "
                f"max_model_len={self.model_config.max_model_len}"
            )
        if self._blocks_needed(req.num_prompt_tokens + 1) > self.pool.num_usable:
            raise ValueError(
                f"prompt of {req.num_prompt_tokens} tokens cannot fit the KV "
                f"pool ({self.pool.num_usable} blocks of {self.block_size})"
            )
        if req.tenant_id != "default" or req.priority != 1 or req.weight != 1.0:
            self._qos_active = True
        req.block_length = self._bd_block
        req.status = RequestStatus.WAITING
        self.waiting.append(req)

    def abort_request(self, request_id: str) -> Request | None:
        for q in (self.running, self.waiting):
            for req in q:
                if req.request_id == request_id:
                    q.remove(req)
                    self._finish(req, RequestStatus.FINISHED_ABORTED)
                    return req
        return None

    @property
    def num_waiting(self) -> int:
        return len(self.waiting) + sum(
            1 for r in self.running if not r.prefill_done
        )

    @property
    def num_running(self) -> int:
        return sum(1 for r in self.running if r.prefill_done)

    def has_unfinished(self) -> bool:
        return bool(self.waiting or self.running)

    # -- scheduling --------------------------------------------------------

    def expire_deadlines(self, now: float | None = None) -> int:
        """Sweep waiting + running for requests whose deadline passed and
        finish them with FINISHED_DEADLINE — an expired request must not
        burn another prefill chunk or decode window on a reply nobody will
        read. Finished requests surface through take_finished_externally
        (terminal output with finish reason "deadline"). Requests with
        tokens in flight (async pipeline) are finished too: postprocess
        voids their resolved rows and speculation_valid rolls back any step
        dispatched on top of them — the same path aborts take."""
        import time as _time

        now = _time.monotonic() if now is None else now

        def alive(r: Request) -> bool:
            return r.deadline is None or now <= r.deadline

        expired = [
            r for q in (self.waiting, self.running) for r in q if not alive(r)
        ]
        if not expired:
            return 0
        # rebuild each queue once — per-request remove() would make the
        # sweep O(expired × queue_len) at the top of every schedule() call
        kept_waiting = [r for r in self.waiting if alive(r)]
        self.waiting.clear()
        self.waiting.extend(kept_waiting)
        self.running = [r for r in self.running if alive(r)]
        for req in expired:
            self._finish(req, RequestStatus.FINISHED_DEADLINE)
            self._finished_externally.append(req)
            self.deadline_expired_total += 1
        return len(expired)

    # -- multi-tenant QoS: shed eviction + fair-share pick ------------------

    def mark_shed_victim(self, than_rank: int) -> bool:
        """Called LOCK-FREE from the admission gate (HTTP threads) when the
        waiting queue is full: pick the newest waiting request whose
        priority rank is strictly worse than `than_rank` and mark it for
        eviction, making room for the higher-priority arrival. Returns
        False (the arrival is shed instead) when no such victim exists.
        The actual eviction happens on the step thread (apply_evictions) —
        this only snapshots the deque, same retry discipline as
        queue_depth()."""
        if not self._qos_active:
            return False
        snap: list[Request] | None = None
        for _ in range(5):
            try:
                snap = list(self.waiting)
                break
            except RuntimeError:  # deque mutated during iteration
                continue
        if snap is None:
            return False
        with self._evict_lock:
            for r in reversed(snap):
                if (
                    r.priority > than_rank
                    and r.request_id not in self._evict_rids
                ):
                    self._evict_rids.add(r.request_id)
                    return True
        return False

    def has_shed_victim(self, than_rank: int) -> bool:
        """Peek-only twin of mark_shed_victim for the pre-SSE admission
        check and readiness probes: would a rank-`than_rank` arrival find
        an evictable lower-priority waiting request? Marks nothing — only
        the submit-time check actually claims a victim, so the two-phase
        admission (precheck, then recheck at submit) can't evict twice for
        one request."""
        if not self._qos_active:
            return False
        for _ in range(5):
            try:
                snap = list(self.waiting)
            except RuntimeError:
                continue
            with self._evict_lock:
                return any(
                    r.priority > than_rank
                    and r.request_id not in self._evict_rids
                    for r in snap
                )
        return False

    def apply_evictions(self) -> int:
        """Step-thread half of mark_shed_victim: finish marked requests
        still in the waiting queue with FINISHED_SHED (terminal output via
        take_finished_externally; the HTTP layer maps it to 429). A mark
        that raced its request into running is dropped — the bound is a
        watermark, not an invariant."""
        with self._evict_lock:
            if not self._evict_rids:
                return 0
            rids, self._evict_rids = self._evict_rids, set()
        evicted = [r for r in self.waiting if r.request_id in rids]
        if not evicted:
            return 0
        kept = [r for r in self.waiting if r.request_id not in rids]
        self.waiting.clear()
        self.waiting.extend(kept)
        for req in evicted:
            self._finish(req, RequestStatus.FINISHED_SHED)
            self._finished_externally.append(req)
            self.shed_evictions += 1
            self.accounting.inc(req.tenant_id, "shed")
        return len(evicted)

    def _pick_waiting(self) -> Request | None:
        """Admission pick: FIFO until any request carries tenant stamps;
        then strict priority tiers (realtime < standard < batch) broken by
        the weighted fair-share virtual clock, FIFO within a tenant. The
        scan keeps the first (oldest) waiting request per tenant, so the
        pick is deterministic for a given queue state — a requirement for
        serial/async-pipeline stream equivalence."""
        if not self.waiting:
            return None
        if not self._qos_active:
            return self.waiting[0]
        best: Request | None = None
        best_key: tuple[int, float] | None = None
        seen: set[str] = set()
        for r in self.waiting:
            if r.tenant_id in seen:
                continue
            seen.add(r.tenant_id)
            key = (r.priority, self._fair.key(r.tenant_id))
            if best_key is None or key < best_key:
                best, best_key = r, key
        return best

    def _seat_victim(self, rank: int) -> Request | None:
        """Running request a rank-`rank` admission may preempt for its SEAT
        (max_num_seqs full): the newest running request of the strictly
        lowest priority class worse than `rank`, skipping rows with tokens
        in flight (their device step is still writing KV). None when every
        seat is held by equal-or-better traffic."""
        cands = [
            r
            for r in reversed(self.running)  # newest first
            if r.num_inflight_tokens == 0 and r.priority > rank
        ]
        if not cands:
            return None
        return max(cands, key=lambda r: r.priority)  # first maximal = newest

    def schedule(
        self, inflight: ScheduleOutput | None = None
    ) -> ScheduleOutput | None:
        """Build the next work item. `inflight` (async pipeline) is the
        step currently executing on device: rows carried by it are planned
        at their speculatively-advanced positions and chain their input
        token from its device-resident output (chain_rows) — a verify in
        flight additionally lets its rows PROPOSE again (its fed proposals
        are host-known values under full acceptance), so verify steps chain
        on verify steps and speculation stays engaged in the steady
        two-deep pipeline. A PREFILL chunk in flight is planned the same
        way: a row whose prompt it ends is decode-ready at its prompt
        length and takes its first token from the chunk's device output,
        so the next window is enqueued behind the chunk; a row it leaves
        mid-prompt starts its next chunk where the chunk in flight ends
        (`_try_add_chunk`), and the device runs the two in order, so the
        second reads the pages and the state slot the first wrote.

        What is picked is the next turn of a ROUND, in which every seat gets
        one turn of the device: the rows that are decode-ready take theirs
        TOGETHER (one decode window), and every other seat — mid-prompt, or
        empty with a request waiting for it — may take one prefill chunk.
        So with something to decode and something to prefill, a chunk is
        chosen while the chunks dispatched since the last window are fewer
        than the seats that do not decode (and one at least), else the
        window: the more rows would wait for chunks, the fewer chunks they
        wait for. One seat that does not decode gives chunk, window, chunk,
        window; nothing to decode gives chunks only; every seat decoding
        gives windows only. The only inputs are `max_num_seqs` and what the
        seats hold: there is nothing to set."""
        self.expire_deadlines()
        self.apply_evictions()
        self.seat_held_by_pool = False
        if (
            self._qos_active
            and inflight is not None
            and self.waiting
            and len(self.running) >= self.config.max_num_seqs
        ):
            head = self._pick_waiting()
            if (
                head is not None
                and self._seat_victim(head.priority) is None
                and any(
                    r.num_inflight_tokens > 0 and r.priority > head.priority
                    for r in self.running
                )
            ):
                # a higher-priority arrival is blocked ONLY by in-flight
                # victims (their device step is still writing KV, so they
                # cannot be preempted yet — PR 1 invariant). Decline to
                # chain the next window: the step loop resolves the
                # in-flight step instead, and the NEXT schedule() finds a
                # resolvable victim. Bounds realtime priority inversion at
                # one decode window instead of a whole seat turnover.
                return None
        # prefill_done at the speculative position (an in-flight chunk
        # that reaches the row's target counts; a row that decodes has
        # passed its target already)
        decode_ready, prefilling = [], []
        for r in self.running:
            done = (
                r.num_computed_tokens + r.num_inflight_tokens
                >= r.prefill_target
            )
            (decode_ready if done else prefilling).append(r)
        # the rows whose turn the window is: one that the step in flight
        # ends (`_exhausted`) is no longer there once it has resolved
        deciding = sum(1 for r in decode_ready if not self._exhausted(r))
        can_admit = bool(self.waiting) and (
            len(self.running) < self.config.max_num_seqs
            or (
                self._qos_active
                and (head := self._pick_waiting()) is not None
                and self._seat_victim(head.priority) is not None
            )
        )

        want_prefill = bool(prefilling) or can_admit
        turn = (self._round_chunks, self._last_was_verify)
        work = None
        if want_prefill and (
            not decode_ready
            or self._round_chunks
            < max(1, self.config.max_num_seqs - deciding)
        ):
            work = self._schedule_prefill(prefilling)
            if work is None and self._bd_block and not decode_ready:
                # a prompt shorter than a block has nothing to prefill: the
                # row just admitted is decode-ready as it stands
                decode_ready = [
                    r for r in self.running
                    if r.num_computed_tokens + r.num_inflight_tokens
                    >= r.prefill_target]
        if work is None and decode_ready:
            work = self._schedule_decode_or_verify(decode_ready, inflight)
        if work is not None:
            if isinstance(work, PrefillWork):
                self._round_chunks += 1
                work.round_chunk = self._round_chunks if decode_ready else 0
            else:
                self._round_chunks = 0
            self._turn_before = turn
        return work

    def _exhausted(self, r: Request) -> bool:
        """The row's speculative state has used up `max_tokens` or the
        model's length: in the resolved world it has FINISHED (on the sync
        path it finishes in postprocess and leaves `running`, so this is
        never true there)."""
        return (
            r.sampling.max_tokens
            <= len(r.output_token_ids) + r.num_inflight_outputs
            or self.model_config.max_model_len
            <= r.num_computed_tokens + r.num_inflight_tokens
        )

    def _schedule_decode_or_verify(
        self, ready: list[Request], inflight=None
    ) -> ScheduleOutput | None:
        """With speculative decoding on, greedy rows route through the
        verify program (which subsumes plain decode: no proposal -> 1 bonus
        token); sampled rows keep the fused decode window. When both kinds
        are ready the two dispatch types alternate.

        Composition with the pipeline (docs/36-speculative-decoding.md):
        a row whose in-flight step is a VERIFY can propose AGAIN — under
        the full-acceptance speculation, that step's fed proposals are
        already host-known token values, so the proposer extends the
        speculatively-advanced sequence and only the step's bonus token
        (this row's next input) is unknown; the chained verify splices it
        device-side (VerifyWork.chain_rows). Rows riding a DECODE window
        can't propose (every window token's value is unresolved) — they
        chain decode windows for SPEC_RETRY_WINDOWS steps after a failed
        attempt, then sit one step out so the next schedule() sees their
        resolved values. The greedy tokens are identical on every path
        (sampling.greedy_argmax is the one greedy pick, and acceptance
        only ever emits the model's own argmax chain), so the streams
        stay bitwise equal to the serial speculative loop."""
        k = self.config.num_speculative_tokens
        if k <= 0:
            return self._schedule_decode(ready, inflight)
        vrow: dict[str, int] = (
            {r.request_id: i for i, r in enumerate(inflight.requests)}
            if isinstance(inflight, VerifyWork)
            else {}
        )

        def capable(r: Request) -> bool:
            # logprobs and min_tokens requests stay on the decode-window
            # path (the verify program returns raw argmax ids — no logprob
            # collection, no min_tokens stop suppression)
            return (
                r.sampling.temperature == 0.0
                and r.sampling.logprobs is None
                and r.sampling.min_tokens <= 0
            )

        # candidates: rows with nothing in flight propose from resolved
        # values; rows whose in-flight step is a verify propose from the
        # speculatively-advanced sequence (its fed proposals, `tails`)
        cands: list[Request] = []
        tails: dict[str, list[int]] = {}
        chain_idx: dict[str, int] = {}
        for r in ready:
            if not capable(r):
                continue
            if r.num_inflight_tokens == 0:
                cands.append(r)
                continue
            if r.sampling.grammar is not None:
                # constrained rows never chain verify-on-verify: the
                # chained step's masks need the automaton state AFTER the
                # in-flight step's bonus token, which is device-resident
                # only — the row sits out until the verify resolves
                continue
            i = vrow.get(r.request_id)
            if i is None or r.num_inflight_tokens != len(
                inflight.token_ids[i]
            ):
                continue
            # a chained step for a row whose full acceptance would already
            # finish it (max_tokens / model length) is guaranteed waste —
            # it sits out until the in-flight verify resolves it
            eff_out = len(r.output_token_ids) + r.num_inflight_outputs
            eff_pos = r.num_computed_tokens + r.num_inflight_tokens
            if (
                r.sampling.max_tokens - eff_out <= 0
                or eff_pos + 1 >= self.model_config.max_model_len
            ):
                continue
            cands.append(r)
            tails[r.request_id] = list(inflight.proposals[i])
            chain_idx[r.request_id] = i
        proposals: dict[str, list[int]] = {}
        proposers: dict[str, str] = {}
        if self.draft_proposer is not None and cands:
            for rid, p in self.draft_proposer.propose_batch(
                cands, k, spec_tails=tails
            ).items():
                if p:
                    proposals[rid] = p
                    proposers[rid] = "draft"
        for r in cands:
            rid = r.request_id
            if rid in proposals:
                continue
            if rid in tails:
                # chained: match against seq + in-flight proposals, ask one
                # extra token and drop it — cont[0] predicts the unknown
                # bonus position the device-chained first fed token covers
                cont = propose_ngram(
                    r.all_token_ids + tails[rid], k + 1,
                    self.config.speculative_min_ngram,
                )
                p = cont[1:] if cont else None
            else:
                p = propose_ngram(
                    r.all_token_ids, k, self.config.speculative_min_ngram
                )
            if p:
                proposals[rid] = p
                proposers[rid] = "ngram"
            else:
                r.spec_retry_in = SPEC_RETRY_WINDOWS
        # only rows whose proposer actually fires go through verify;
        # proposal-less greedy rows keep the fused decode window (1 token
        # per verify dispatch would re-expose the per-token round-trip the
        # window amortizes), as do sampled rows
        spec = [r for r in ready if r.request_id in proposals]
        plain = []
        retry_riders: set[str] = set()
        for r in ready:
            if r.request_id in proposals:
                continue
            if (
                capable(r)
                and r.num_inflight_tokens > 0
                and r.request_id not in vrow
            ):
                # verify-capable row riding a chained decode window: burn
                # its retry budget, then sit out one step so it can
                # propose against resolved values next schedule(). The
                # budget counts windows actually RIDDEN — it is debited
                # below only for rows the dispatched decode work carries
                # (the verify group may win this turn instead).
                if r.spec_retry_in <= 0:
                    continue
                retry_riders.add(r.request_id)
            plain.append(r)
        first, second = (
            (spec, plain) if not self._last_was_verify else (plain, spec)
        )
        for group in (first, second):
            if not group:
                continue
            if group is spec:
                work = self._schedule_verify(
                    group, proposals, proposers, chain_idx
                )
            else:
                work = self._schedule_decode(group, inflight)
                if work is not None and retry_riders:
                    for r in work.requests:
                        if r.request_id in retry_riders:
                            r.spec_retry_in -= 1
            if work is not None:
                self._last_was_verify = group is spec
                return work
        return None

    def _schedule_verify(
        self,
        ready: list[Request],
        proposals: dict[str, list[int]],
        proposers: dict[str, str],
        chain_idx: dict[str, int] | None = None,
    ) -> VerifyWork | None:
        chain_idx = chain_idx or {}
        work = VerifyWork()
        for req in ready[: self.config.max_num_seqs]:
            if req not in self.running:
                continue
            # chained rows plan from their speculatively-advanced position
            # (num_inflight_tokens is the in-flight verify's fed length);
            # non-chained rows have nothing in flight and reduce to the
            # plain num_computed_tokens
            start = req.num_computed_tokens + req.num_inflight_tokens
            src = chain_idx.get(req.request_id, -1)
            proposal = list(proposals.get(req.request_id, []))
            # bound by remaining model length (the fed chunk itself must fit)
            room = self.model_config.max_model_len - start - 1
            proposal = proposal[: max(0, room)]
            # clamp to pool headroom, mirroring the decode window's clamp: a
            # proposal must never make _ensure_blocks preempt the request
            # ITSELF (re-admit, recompute, re-propose — a livelock); shrunk
            # to nothing it degrades to a plain 1-token verify, the same
            # exposure as decode at window 1
            while proposal and (
                self._blocks_needed(start + 1 + len(proposal))
                - len(req.block_table)
                > self.pool.growable_blocks
            ):
                proposal.pop()
            if not self._ensure_blocks(req, start + 1 + len(proposal)):
                continue  # req preempted itself; others may still verify
            # a chained row's first fed token is the in-flight verify's
            # bonus token — device-resident only; the runner splices it
            # (placeholder 0 here, chain_rows carries the source row)
            fed = [0 if src >= 0 else req.token_at(start), *proposal]
            work.requests.append(req)
            work.token_ids.append(fed)
            work.positions.append(list(range(start, start + len(fed))))
            work.proposals.append(proposal)
            work.context_lens.append(start + len(fed))
            work.proposers.append(proposers.get(req.request_id, "ngram"))
            work.chain_rows.append(src)
        # a later _ensure_blocks may have preempted an earlier row's request
        if any(r not in self.running for r in work.requests):
            keep = [
                i for i, r in enumerate(work.requests) if r in self.running
            ]
            for name in (
                "requests", "token_ids", "positions", "proposals",
                "context_lens", "proposers", "chain_rows",
            ):
                setattr(work, name, [getattr(work, name)[i] for i in keep])
        return work if work.requests else None

    def _schedule_prefill(self, prefilling: list[Request]) -> PrefillWork | None:
        """Pack chunks from multiple requests into one dispatch: in-flight
        prefills continue first (FIFO), then new admissions, until the
        per-step token budget (max_num_batched_tokens) or the seat limit
        (max_num_seqs rows) is hit."""
        work = PrefillWork()
        budget = self.config.max_num_batched_tokens

        for req in prefilling:
            if budget <= 0 or len(work.requests) >= self.config.max_num_seqs:
                break
            budget -= self._try_add_chunk(work, req, budget)

        while (
            budget > 0
            and self.waiting
            and len(work.requests) < self.config.max_num_seqs
        ):
            req = self._pick_waiting()
            if req is None:
                break
            if len(self.running) >= self.config.max_num_seqs:
                # seats full: a strictly-higher-priority class may claim
                # one by preempting the newest lowest-priority running
                # request (QoS only — equal-priority traffic waits, the
                # pre-QoS behavior)
                victim = self._seat_victim(req.priority)
                if victim is None:
                    break
                need = self._blocks_needed(req.prefill_target + 1)
                if need > self.pool.num_usable:
                    self._can_admit(req)  # aborts the impossible fit
                    continue
                if not self._fits(req, victim):
                    # the memory watermark would still block the admission
                    # even with the victim's blocks back — don't pay a
                    # preemption (full recompute for the victim) for
                    # nothing
                    break
                self._preempt(victim)
            if not self._can_admit(req):
                if req in self.waiting:
                    break  # watermark: stop admitting until memory frees
                continue  # impossible-fit request was aborted; try the next
            self.waiting.remove(req)
            self._admit(req)
            if self._qos_active:
                # fair-share charge: expected device work (prompt left to
                # compute + output budget) over the tenant's weight
                self._fair.charge(
                    req.tenant_id,
                    req.prefill_target + req.sampling.max_tokens,
                    req.weight,
                )
            if req.num_preemptions == 0:
                # first admission only: queue wait + per-tenant served
                # count (a preempted request re-admitting is not a new
                # request, and its wait was already observed)
                import time as _time

                req.first_seat_time = _time.monotonic()
                self.accounting.inc(req.tenant_id, "requests")
                self.accounting.observe_wait(
                    req.tenant_id,
                    max(0.0, req.first_seat_time - req.arrival_time),
                )
            req.status = RequestStatus.RUNNING
            self.running.append(req)
            budget -= self._try_add_chunk(work, req, budget)

        # _ensure_blocks for a later row may have preempted an earlier row's
        # request (newest-victim policy); its slots now point at reallocated
        # blocks, so the row must be dropped — the request recomputes later
        if any(r not in self.running for r in work.requests):
            keep = [i for i, r in enumerate(work.requests) if r in self.running]
            for name in (
                "requests", "token_ids", "positions", "slot_mappings",
                "context_lens", "sample",
            ):
                setattr(work, name, [getattr(work, name)[i] for i in keep])
        return work if work.requests else None

    def _try_add_chunk(self, work: PrefillWork, req: Request, budget: int) -> int:
        """Add one chunk of `req` to the batch; returns tokens consumed.

        With an active hydration plan (docs/31-hydration-planner.md) this
        first consumes any landed load-chunks at the request's compute
        boundary (free tokens — adopted, not computed) and then bounds
        the prefill chunk at the next unresolved load boundary; a request
        parked exactly at a pending fetch contributes no row this step
        (decode and other prefills proceed around it).

        A row with a chunk still in flight (async pipeline) starts this one
        where that one ends, `num_computed_tokens + num_inflight_tokens`,
        like a decode window planned over a window: the device runs the two
        in order. A hydration plan is consumed at RESOLVED positions only
        (an adoption moves `num_computed_tokens`), so its row sits out while
        a chunk of it is in flight."""
        limit = None
        if req.hydration_plan is not None:
            if req.num_inflight_tokens:
                return 0
            limit = self._consume_hydrated(req)
        target = req.prefill_target
        start = req.num_computed_tokens + req.num_inflight_tokens
        chunk = min(budget, target - start)
        if limit is not None:
            chunk = min(chunk, limit)
        if self._bd_block:
            # seams on block boundaries from the row's position 0 (the
            # target is one): a block is never split over two chunks
            chunk -= chunk % self._bd_block
        if chunk <= 0:
            return 0
        if not self._ensure_blocks(req, start + chunk):
            return 0  # req preempted itself; it's back in waiting
        if req not in self.running:
            return 0
        idxs = range(start, start + chunk)
        work.add_row(
            request=req,
            token_ids=req.tokens_between(start, start + chunk),
            positions=list(idxs),
            slot_mapping=(
                [self._slot(req, i) for i in idxs]
                if self.need_slot_mappings
                else []
            ),
            context_len=start + chunk,
            # sample only when this chunk completes a *fresh* prompt; resumed
            # requests already know their next token
            # ... and no token comes out of a prefill of whole blocks
            sample=(start + chunk == target and not req.output_token_ids
                    and not self._bd_block),
        )
        return chunk

    def _schedule_decode(
        self, ready: list[Request], inflight: ScheduleOutput | None = None
    ) -> DecodeWork | None:
        # async pipeline: rows with tokens in flight plan from their
        # speculatively-advanced position / output count (assumes the whole
        # in-flight window gets accepted, and that a first token sampled by
        # an in-flight prefill chunk does not finish its row — any
        # deviation finishes the request, which the engine's reconcile
        # turns into a rollback of the step scheduled here). On the sync
        # path inflight counts are 0 and these reduce to the plain fields.
        def eff_computed(r: Request) -> int:
            return r.num_computed_tokens + r.num_inflight_tokens

        def eff_outputs(r: Request) -> int:
            return len(r.output_token_ids) + r.num_inflight_outputs

        # rows whose speculative state already exhausted max_tokens /
        # max_model_len would have FINISHED in the resolved world — they
        # must sit out until the in-flight step resolves them (no-op on the
        # sync path: exhausted rows finish in postprocess and leave running)
        cand = [
            r for r in ready[: self.config.max_num_seqs]
            if not self._exhausted(r)
            # constrained rows chain decode-on-decode (the window program
            # hands the next window its post-window automaton states on
            # device) but cannot chain onto a verify step or a prefill
            # chunk, which carry no state vector — those sit one step out
            and not (
                r.sampling.grammar is not None
                and r.num_inflight_tokens > 0
                and not isinstance(inflight, DecodeWork)
            )
        ]
        if not cand:
            return None
        if self._bd_block:
            return self._schedule_block_windows(cand, eff_computed)
        # window bounded by model length per seq and by the batch's largest
        # remaining output budget rounded UP to a power of two: past that
        # every token of every row would be discarded, but rounding up keeps
        # the window inside the same {1,2,4,...} compile set as the snap
        # below (each distinct window value is a ~20 s XLA compile; walking
        # the window down through arbitrary integers at the tail of a run
        # compiled fresh programs for tokens that cost microseconds to
        # overshoot)
        window = max(1, self.config.decode_window)
        max_remaining = max(
            r.sampling.max_tokens - eff_outputs(r) for r in cand
        )
        window = min(
            window,
            1 << max(0, max_remaining - 1).bit_length(),
            min(self.model_config.max_model_len - eff_computed(r)
                for r in cand),
        )
        # clamp to pool headroom: the batch's total new-block demand at this
        # window must fit in currently-free blocks, so _ensure_blocks below
        # never preempts a candidate to grow another candidate's window (a
        # sole running request preempting *itself* is a livelock: it re-admits,
        # recomputes, and hits the same wall forever)
        while window > 1 and (
            self._extra_blocks(cand, window) > self.pool.growable_blocks
        ):
            window -= 1
        # snap down to a power of two: `window` is a static jit arg of the
        # fused decode program, so every distinct value costs a compile —
        # bound the set to {1, 2, 4, ...} instead of walking through every
        # integer as free-block headroom fluctuates
        window = 1 << (window.bit_length() - 1)
        picked: list[Request] = []
        for req in cand:
            if req not in self.running:
                continue  # preempted while building this batch
            if not self._ensure_blocks(req, eff_computed(req) + window):
                continue  # req preempted itself; others may still decode
            picked.append(req)
        # a later _ensure_blocks may have preempted an earlier pick
        picked = [r for r in picked if r in self.running]
        if not picked:
            return None
        inflight_row = (
            {r.request_id: i for i, r in enumerate(inflight.requests)}
            if inflight is not None
            else {}
        )
        batch = DecodeWork(requests=picked, window=window)
        for req in picked:
            pos = eff_computed(req)
            batch.positions.append(pos)
            if req.num_inflight_outputs > 0:
                # input token is still device-resident (last column of the
                # in-flight window's output matrix, or the first token the
                # in-flight prefill chunk samples) — the runner chains it
                # without a D2H→H2D round trip
                src = inflight_row.get(req.request_id)
                if src is None:
                    raise RuntimeError(
                        f"request {req.request_id} has "
                        f"{req.num_inflight_outputs} in-flight tokens but is "
                        "not in the in-flight step"
                    )
                batch.token_ids.append(0)  # placeholder; chained on device
                batch.chain_rows.append(src)
            else:
                batch.token_ids.append(req.token_at(pos))
                batch.chain_rows.append(-1)
        return batch

    def _schedule_block_windows(
        self, cand: list[Request], eff_computed
    ) -> DecodeWork | None:
        """`_schedule_decode` for generation by diffusion over blocks: a
        window is whole blocks from a row's (block-aligned) computed
        position; the tokens the row already has there — the tail of its
        prompt, in its first window — are given to the window, which
        yields the rest. Every row's window after its first starts on
        masks alone, so nothing chains from the step in flight. A window
        is `decode_window` long whatever its rows have left to generate (a
        row's arithmetic, and so the order it unmasks in, does not depend
        on what its neighbours want; the last window is cut at max_tokens),
        shorter by whole blocks only where the pool's headroom or the
        context's end says so."""
        bl = self._bd_block

        def given(r: Request) -> int:
            # (a window in flight leaves none: its row's tokens end where
            # its positions do)
            return r.num_tokens + r.num_inflight_outputs - eff_computed(r)

        window = min(
            max(bl, self.config.decode_window),
            min(self.model_config.max_model_len - eff_computed(r)
                for r in cand))
        while window > bl and (
            self._extra_blocks(cand, window) > self.pool.growable_blocks
        ):
            window -= bl
        picked: list[Request] = []
        for req in cand:
            if req not in self.running:
                continue  # preempted while building this batch
            if not self._ensure_blocks(req, eff_computed(req) + window):
                continue
            picked.append(req)
        picked = [r for r in picked if r in self.running]
        if not picked:
            return None
        batch = DecodeWork(requests=picked, window=window)
        for req in picked:
            pos = eff_computed(req)
            batch.positions.append(pos)
            batch.block_tokens.append(
                req.tokens_between(pos, pos + given(req)))
        return batch

    # -- memory ------------------------------------------------------------

    def _blocks_needed(self, num_tokens: int) -> int:
        return (num_tokens + self.block_size - 1) // self.block_size

    def _extra_blocks(self, reqs: list[Request], window: int) -> int:
        """New blocks the batch needs to decode `window` tokens per request
        (from the speculative position when tokens are in flight)."""
        return sum(
            max(
                0,
                self._blocks_needed(
                    r.num_computed_tokens + r.num_inflight_tokens + window
                )
                - len(r.block_table),
            )
            for r in reqs
        )

    def _budget_blocks(self, req: Request) -> int:
        """Blocks `req` may come to hold: its prompt, the answer it declared
        (`max_tokens`) and the tail of the decode window that samples its
        last token (a window's blocks are allocated whole), within the
        model's length — and within the pool: a request that declares more
        than the pool holds is seated once it has the pool to itself."""
        tokens = (
            req.num_prompt_tokens + req.sampling.max_tokens
            + max(self._bd_block, self.config.decode_window, 1) - 1
        )
        return min(
            self._blocks_needed(
                min(tokens, self.model_config.max_model_len)),
            self.pool.num_usable,
        )

    def _fits(self, req: Request, victim: Request | None = None) -> bool:
        """Do the free blocks, less what every running row may still grow by
        (`_budget_blocks` beyond its table), hold `req`'s own budget? With
        `victim`: once that row's seat and blocks were taken for it."""
        free = self.pool.num_free
        if victim is not None:
            free += len(victim.block_table)
        owed = sum(
            max(0, self._budget_blocks(r) - len(r.block_table))
            for r in self.running if r is not victim
        )
        return free - owed >= self._budget_blocks(req)

    def _can_admit(self, req: Request) -> bool:
        """Admission keeps its promise: a request is seated only when the
        pool can hold it to its end — its whole recompute target AND the
        answer it declared — beside what the rows already seated may still
        grow by. Every running request can then finish; the pool, and not a
        preemption (a whole prompt recomputed), is what says "no more
        seats". A request reserves what it declares: a very large
        `max_tokens` keeps others out (docs/21-engine-configuration.md)."""
        need = self._blocks_needed(req.prefill_target + 1)
        # the window group holds at most a window and what is in flight of
        # a row, whatever its length
        wpool = self.pool.window
        if need > self.pool.num_usable or (
            wpool is not None
            and min(need, self._window_row_blocks) > wpool.num_usable
        ):
            # can never fit (e.g. resumed request outgrew the pool)
            self.waiting.remove(req)
            self._finish(req, RequestStatus.FINISHED_ABORTED)
            self._finished_externally.append(req)
            return False
        if wpool is not None:
            # ... and a row is admitted when its first chunk fits there
            # (later growth that finds the group full preempts the newest
            # row, as in the full group)
            first_chunk = self._blocks_needed(
                self.config.max_num_batched_tokens) + 1
            if wpool.num_free < min(need, first_chunk):
                return False
        if self.pool.state is not None and not self.pool.state.num_free:
            return False  # no admission without a free state slot
        if not self._fits(req):
            self.seat_held_by_pool = True
            return False
        return True

    def window_share(self) -> float:
        """Window-group pages resident / the pages the same live rows would
        hold in those layers at full length (their full-group tables'
        lengths): what giving pages back saves. 0 without a window group
        or without rows."""
        wpool = self.pool.window
        held = sum(len(r.block_table) for r in self.running)
        if wpool is None or not held:
            return 0.0
        return wpool.num_resident / held

    def take_finished_externally(self) -> list[Request]:
        out, self._finished_externally = self._finished_externally, []
        return out

    def goodput_balance(self) -> dict:
        """Ledger balance audit: sampled == delivered + wasted + pending
        tokens on live requests (docs/29-saturation-slo.md). Lives HERE —
        the single definition of "live requests" — so the invariant can't
        drift between the engine's view and scheduler-level tests."""
        snap = self.ledger.snapshot()
        snap["pending"] = sum(
            r.ledger_pending for q in (self.running, self.waiting) for r in q
        )
        snap["balanced"] = (
            snap["sampled"]
            == snap["delivered"] + snap["wasted_total"] + snap["pending"]
        )
        return snap

    def _chain_root(self, req: Request) -> int:
        """Root of a request's KV hash chain. Base model = the pool root;
        LoRA requests salt it with their adapter's load-unique id — adapter
        KV differs from base KV (k/v-projection deltas), so letting the two
        cross-match would be silent attention corruption."""
        if req.lora_cache_salt:
            return chain_hash(self.pool.root_hash(), (req.lora_cache_salt,))
        return self.pool.root_hash()

    def _admit(self, req: Request) -> None:
        """Prefix-cache lookup for a waiting (possibly resumed) request.
        The matchable sequence is everything that will be recomputed.

        With a hydrator (docs/31-hydration-planner.md), a FIRST admission
        whose resident run continues past the local rungs consumes the
        HBM/host-ring prefix synchronously (cheap — RAM→device dispatch)
        and plans the disk/remote remainder as chunked async loads
        pipelined with the recompute of whatever the planner prices as
        cheaper; resumed (preempted) requests keep the legacy blocking
        match — their blocks are usually still local, and their
        attribution is already settled."""
        seq = req.all_token_ids
        root = self._chain_root(req)
        plan = None
        if self.pool.state is not None:
            # its state slot, with its first pages (_can_admit saw one free)
            req.state_slot, req.state_bank = self.pool.state.allocate(), 0
        if (
            self.hydrator is not None
            and req.hydration is None  # first admission only
            and self.pool.enable_prefix_caching
        ):
            matched, plan = self._admit_planned(req, seq, root)
        else:
            matched = self.pool.match_prefix(seq, parent=root)
        # keep at least one token to actually compute (its logits / its KV
        # write are what the next step needs)
        while matched and len(matched) * self.block_size >= req.prefill_target:
            self.pool.free_block(matched.pop())
        self._attribute_hydration(
            req, len(matched),
            deferred=plan.deferred_tokens() if plan is not None else 0,
        )
        req.block_table = matched
        req.num_computed_tokens = len(matched) * self.block_size
        req.num_cached_prompt_tokens = min(
            req.num_computed_tokens, req.num_prompt_tokens
        )
        chain = [root]
        for i in range(len(matched)):
            chunk = tuple(seq[i * self.block_size : (i + 1) * self.block_size])
            chain.append(chain_hash(chain[-1], chunk))
        self._hash_chains[req.request_id] = chain
        if plan is not None:
            req.hydration_plan = plan
            self.hydrator.launch(plan)

    def _admit_planned(self, req: Request, seq: list[int], root: int):
        """Planner half of _admit: probe residency without moving bytes,
        take the leading HBM/ring run synchronously, and build a chunk
        plan over the disk/remote remainder. Returns (matched, plan);
        plan None means the caller behaves exactly like the legacy path
        (matched is then a full-hierarchy blocking match — the auto-mode
        fallback, whose transfers are what feed the bandwidth estimator
        past its sample floor)."""
        # kill switch: lower-tier residency is
        # ignored and — crucially — neither the remote store nor any peer
        # is ever probed (a sick store/peer is exactly why an operator
        # flips this off)
        off = self.hydrator.mode == "off"
        hashes, tiers, peer_owner = self.pool.probe_prefix(
            seq, parent=root, local_only=off,
            peer=None if off else self.hydrator.peer,
            owner_hint=None if off else req.kv_owner_hint,
        )
        # keep-one-token rule applied to the whole resident run: the plan
        # region must end at least one token short of the prefill target
        cap = max(0, (req.prefill_target - 1) // self.block_size)
        hashes, tiers = hashes[:cap], tiers[:cap]
        n_sync = 0
        while n_sync < len(tiers) and tiers[n_sync] in ("hbm", "host"):
            n_sync += 1
        if off:
            return (
                self.pool.match_prefix(seq, parent=root, limit_blocks=n_sync),
                None,
            )
        if n_sync == len(tiers):
            # nothing beyond the local rungs — the legacy match never
            # blocks on disk/remote here
            return self.pool.match_prefix(seq, parent=root), None
        plan = self.hydrator.build_plan(
            req.request_id, n_sync, hashes[n_sync:], tiers[n_sync:],
            self.block_size, peer_owner=peer_owner,
        )
        if plan is None:
            return self.pool.match_prefix(seq, parent=root), None
        matched = self.pool.match_prefix(
            seq, parent=root, limit_blocks=n_sync
        )
        if len(matched) != n_sync:
            # an eviction raced the probe: the plan's block indices no
            # longer line up — drop the plan (the region recomputes; the
            # next identical prompt re-plans against fresh residency)
            return matched, None
        # region blocks are real cache queries; hits count at adoption
        self.pool.stats.queries += len(hashes) - n_sync
        return matched, plan

    # -- hydration plan consumption (docs/31-hydration-planner.md) ---------

    def _consume_hydrated(self, req: Request) -> int | None:
        """Resolve the request's hydration plan at its compute boundary:
        adopt landed chunks (extend the block table without computing),
        flip failed/expired load-chunks to recompute, and return how many
        tokens prefill may compute before the next unresolved load
        boundary — 0 parks the request this step, None means
        unconstrained (plan exhausted or no load chunk ahead)."""
        import time as _time

        plan = req.hydration_plan
        bs = self.block_size
        while plan is not None and not plan.done():
            cur = plan.current()
            start_tok = cur.start_block * bs
            end_tok = start_tok + cur.tokens(bs)
            if req.num_computed_tokens >= end_tok:
                if cur.status == "pending":
                    cur.status = "recomputed"  # computed straight through
                plan.advance()
                continue
            if req.num_computed_tokens != start_tok or cur.decision != "load":
                break  # mid-compute of a recompute chunk
            with plan.lock:
                status = cur.status
                expired = (
                    status == "pending"
                    and _time.monotonic() > plan.deadline
                )
                if expired:
                    # claim the flip under the lock so a late fetcher
                    # landing can't resurrect the chunk
                    cur.status = "fallback"
            if status == "landed":
                blocks = self.pool.adopt_planned_run(cur.hashes, cur.arrays)
                if blocks is None:
                    # allocation/geometry/upload failure: recompute keeps
                    # liveness (the compute path can preempt for blocks;
                    # adoption must not)
                    self._flip_chunk(req, cur, "adopt_failed")
                    continue
                cur.arrays = None
                cur.status = "adopted"
                req.block_table.extend(blocks)
                req.num_computed_tokens = end_tok
                req.num_cached_prompt_tokens = min(
                    req.num_computed_tokens, req.num_prompt_tokens
                )
                chain = self._hash_chains.setdefault(
                    req.request_id, [self._chain_root(req)]
                )
                chain.extend(cur.hashes)
                self._record_outcome(req, cur, "adopted")
                for tier in cur.tiers:
                    self._attribute_increment(
                        req, self._HYDRATION_BY_TIER[tier], bs
                    )
                plan.advance()
                continue
            if expired:
                self._flip_chunk(req, cur, "timeout", already_claimed=True)
                continue
            if status in ("failed", "cancelled"):
                self._flip_chunk(req, cur, status)
                continue
            return 0  # pending within its deadline: park this request
        if plan is not None and plan.done():
            req.hydration_plan = None
            plan = None
        if plan is None:
            return None
        nxt = None
        for c in plan.chunks[plan.cursor:]:
            if c.decision == "load":
                nxt = c.start_block * bs
                break
        if nxt is None:
            return None
        return max(0, nxt - req.num_computed_tokens)

    def _flip_chunk(
        self, req: Request, chunk, why: str, already_claimed: bool = False,
    ) -> None:
        """A load chunk's fetch failed, expired, or could not adopt: it
        becomes a recompute chunk (choice counter: fallback_recompute)
        and its tokens classify as recomputed — the partition invariant
        holds no matter which way a chunk resolves."""
        plan = req.hydration_plan
        if not already_claimed and plan is not None:
            with plan.lock:
                chunk.status = "fallback"
        elif plan is None:
            chunk.status = "fallback"
        chunk.decision = "recompute"
        chunk.arrays = None
        self.flow.record_decision("fallback_recompute")
        self._record_outcome(req, chunk, f"fallback:{why}")
        self._attribute_increment(
            req, "recomputed", chunk.tokens(self.block_size)
        )

    def _record_outcome(self, req: Request, chunk, outcome: str) -> None:
        if req.hydration_outcomes is None:
            req.hydration_outcomes = []
        req.hydration_outcomes.append({
            "chunk": chunk.index,
            "start_block": chunk.start_block,
            "tokens": chunk.tokens(self.block_size),
            "tiers": sorted(set(chunk.tiers)),
            "decision": "load",
            "outcome": outcome,
        })

    def hydration_parked(self) -> bool:
        """True when some running request still has an active hydration
        plan — the engine's step loop sleeps a beat instead of busy-
        spinning when such a request is the only schedulable work (its
        fetch needs the CPU the spin would burn)."""
        return any(r.hydration_plan is not None for r in self.running)

    def _settle_hydration_plan(self, req: Request) -> None:
        """Cancel an active plan and classify every still-open load chunk
        as recomputed — a request leaving the scheduler mid-hydration
        (preemption, abort, deadline, shed) must not strand deferred
        tokens outside the audited partition. In-flight fetch jobs see
        the cancel flag and drop their results."""
        plan = req.hydration_plan
        if plan is None:
            return
        req.hydration_plan = None
        open_chunks = plan.unresolved()
        plan.cancel()
        for chunk in open_chunks:
            chunk.decision = "recompute"
            chunk.arrays = None
            self._record_outcome(req, chunk, "cancelled")
            self._attribute_increment(
                req, "recomputed", chunk.tokens(self.block_size)
            )

    _HYDRATION_BY_TIER = {
        "hbm": "hbm_hit",
        "host": "host_reload",
        "disk": "disk_load",
        "remote": "remote_fetch",
        "peer": "peer_fetch",
        # device-collective peer pulls attribute as peer_fetch too — the
        # SOURCE (a peer engine) is the same, only the wire differs, and
        # KV_HYDRATION_SOURCES is a closed contract set (docs/39)
        "device": "peer_fetch",
    }

    def _attribute_hydration(
        self, req: Request, n_matched: int, deferred: int = 0
    ) -> None:
        """Classify the request's prompt tokens by KV origin, EXACTLY once
        (first admission only — a preempted request re-admitting keeps its
        original attribution; the recompute cost is the goodput ledger's
        preempted_recompute story, not a hydration event). The partition is
        exact by construction: matched blocks are full blocks of the
        prompt's head (trimmed below prefill_target == prompt tokens at
        first admission), so

            hbm_hit + host_reload + disk_load + remote_fetch + peer_fetch
                + recomputed == prompt_tokens

        with recomputed >= 1 (the keep-one-token-to-compute rule).

        `deferred` (hydration planner) excludes the plan's load-decided
        chunk tokens from the admission-time counts: each classifies via
        _attribute_increment when its fate resolves — adopted under its
        tier's source, fallback/cancelled as recomputed — so the
        partition stays exact at every settle point."""
        if req.hydration is not None:
            return
        counts = dict.fromkeys(self._HYDRATION_BY_TIER.values(), 0)
        for tier in self.pool.last_match_sources[:n_matched]:
            counts[self._HYDRATION_BY_TIER[tier]] += self.block_size
        counts["recomputed"] = (
            req.num_prompt_tokens - n_matched * self.block_size - deferred
        )
        req.hydration = counts
        self.flow.record_hydration(counts)

    def _attribute_increment(self, req: Request, source: str, n: int) -> None:
        """Deferred-chunk classification (hydration planner): move n of
        the request's prompt tokens into `source`, mirrored into the
        shared flow counters without bumping hydrated_requests."""
        if req.hydration is None:  # planner admissions always attribute
            req.hydration = dict.fromkeys(
                (*self._HYDRATION_BY_TIER.values(), "recomputed"), 0
            )
        req.hydration[source] = req.hydration.get(source, 0) + n
        self.flow.record_hydration({source: n}, requests=0)

    def _ensure_blocks(self, req: Request, num_tokens: int) -> bool:
        """Grow req's block table to cover num_tokens. On pool exhaustion the
        NEWEST running request is preempted — possibly req itself (returns
        False, req is back in waiting) — so the oldest request always makes
        forward progress. A sole running request that can never fit its own
        next token is aborted at re-admission by `_can_admit`'s usable-pool
        check; the decode-window headroom clamp in `_schedule_decode` keeps
        windowed decode from self-preempting before that point."""
        need = self._blocks_needed(num_tokens)
        wpool = self.pool.window
        if wpool is not None:
            # first what the row no longer needs: pages wholly behind the
            # window of its RESOLVED position (a step in flight, or one
            # rolled back, still reads from there on)
            req.window_first_live = wpool.give_back(
                req.window_table, req.window_first_live,
                req.num_computed_tokens)
        while len(req.block_table) < need:
            # both groups grow in lockstep (one logical index, two pages);
            # whichever is full preempts
            wblk = 0
            blk = self.pool.allocate()
            if blk is not None and wpool is not None:
                wblk = wpool.allocate()
                if wblk is None:
                    self.pool.free_block(blk)
                    blk = None
            if blk is None:
                # LOWEST-priority-class-first, newest-within-class loses
                # (pure-default traffic: every rank ties, so this reduces
                # to the historical newest-admission rule) — but never a
                # request with tokens in flight (async pipeline): its
                # device step is still writing KV into its blocks and its
                # unresolved tokens would be lost, so it cannot be safely
                # recomputed yet
                cands = [
                    r
                    for r in reversed(self.running)  # newest first
                    if r.num_inflight_tokens == 0
                ]
                victim = (
                    max(cands, key=lambda r: r.priority)  # first max = newest
                    if cands
                    else None
                )
                if victim is None:
                    return False
                self._preempt(victim)
                if victim is req:
                    return False
                continue
            req.block_table.append(blk)
            if wpool is not None:
                req.window_table.append(wblk)
        return True

    def _preempt(self, req: Request) -> None:
        self.running.remove(req)
        # preemption mid-hydration: the plan dies with the seat (its
        # deferred tokens settle as recomputed — partition stays exact);
        # re-admission runs the legacy match, which will find whatever
        # the fetches already promoted into the ring
        self._settle_hydration_plan(req)
        # preemption mid-draft: the draft's scratch blocks go back to the
        # pool with the seat (the draft KV recomputes from scratch at
        # re-admission via the catch-up feed — cheap, it's a small model)
        if self.draft_proposer is not None:
            self.draft_proposer.release(req.request_id)
        req.state_recompute_until = max(
            req.state_recompute_until, req.num_computed_tokens)
        self._release_blocks(req)
        # goodput ledger: nothing to classify here — the preempted
        # request's pending tokens keep their unknown fate (the VALUES
        # survive in output_token_ids; they settle at finish). The
        # recompute cost lands when resumed prefill actually re-processes
        # generated positions (postprocess charges preempted_recompute
        # chunk-exactly).
        req.num_computed_tokens = 0
        req.num_preemptions += 1
        self.total_preemptions += 1
        req.status = RequestStatus.PREEMPTED
        self.waiting.appendleft(req)

    def _release_blocks(self, req: Request) -> None:
        # tail-first so deep prefix blocks stay hottest in the LRU
        for blk in reversed(req.block_table):
            self.pool.free_block(blk)
        req.block_table = []
        if self.pool.window is not None:
            self.pool.window.release(req.window_table, req.window_first_live)
            req.window_first_live = 0
        if self.pool.state is not None:
            self.pool.state.release(req.state_slot)
            req.state_slot = 0
        self._hash_chains.pop(req.request_id, None)

    def _slot(self, req: Request, token_idx: int) -> int:
        blk = req.block_table[token_idx // self.block_size]
        return blk * self.block_size + token_idx % self.block_size

    # -- async pipeline bookkeeping (engine/engine.py pipelined step loop) --
    #
    # A dispatched-but-unresolved step advances its rows SPECULATIVELY:
    # num_inflight_tokens carries the positions it computes (the decode
    # window, a verify row's fed length, a prefill chunk's tokens) and
    # num_inflight_outputs the tokens it samples (the same window under
    # full acceptance; the one first token of a chunk that ends its prompt)
    # so the next step can be scheduled (and dispatched, chaining its input
    # tokens device-side) before the sampled tokens ever reach the host.
    # The speculation is "every row accepts its whole window, and a first
    # token does not finish its row" — any deviation (mid-window stop
    # token, PARTIAL draft acceptance, max-tokens/model-len finish, abort,
    # stop-string hit) moves the reconciled base, which is what
    # speculation_valid detects and the engine answers with
    # rollback_speculative on the step dispatched on top of it. A
    # mispredicted draft is therefore just another rollback — the unifying
    # move that lets speculation ride the pipeline (docs/36).

    @staticmethod
    def _row_advance(work: ScheduleOutput) -> list[tuple[int, int]]:
        """Per-row speculative advance of a dispatched step, as (positions,
        sampled tokens): the uniform decode window twice, each verify row's
        fed length twice (current token + proposals == the tokens a full
        acceptance would resolve), a prefill row's chunk length and the one
        token it samples where the chunk ends its prompt."""
        if isinstance(work, PrefillWork):
            return [
                (len(t), int(s)) for t, s in zip(work.token_ids, work.sample)
            ]
        if isinstance(work, VerifyWork):
            return [(len(t), len(t)) for t in work.token_ids]
        if work.block_tokens:  # a window of block passes: what it was
            # given it does not sample
            return [(work.window, work.window - len(g))
                    for g in work.block_tokens]
        return [(work.window, work.window)] * len(work.requests)

    @staticmethod
    def _row_positions(work: ScheduleOutput) -> list[int]:
        if isinstance(work, DecodeWork):
            return list(work.positions)
        return [p[0] for p in work.positions]

    def begin_speculative(self, work: ScheduleOutput) -> None:
        """Mark `work`'s advance as in flight on its rows (called right
        after the engine dispatches the step to the device)."""
        for req, (n, out) in zip(work.requests, self._row_advance(work)):
            req.num_inflight_tokens += n
            req.num_inflight_outputs += out
            # the dispatch wrote the row's state into its slot's other bank
            # (kv_cache.StateSlots; ModelRunner._state_slot_arrays named
            # both banks to it)
            req.state_bank ^= 1

    def end_speculative(self, work: ScheduleOutput) -> None:
        """Clear `work`'s advance from its rows — the step has resolved and
        postprocess() is about to apply its real results (or it was
        discarded)."""
        for req, (n, out) in zip(work.requests, self._row_advance(work)):
            req.num_inflight_tokens = max(0, req.num_inflight_tokens - n)
            req.num_inflight_outputs = max(0, req.num_inflight_outputs - out)

    def speculation_valid(self, work: ScheduleOutput) -> bool:
        """After the PREVIOUS step resolved, is the speculatively dispatched
        `work` still consistent? Every row must still be running with its
        reconciled base position exactly where the dispatch assumed — a
        mid-window stop, a partial draft acceptance, max-tokens finish,
        stop-string hit, or abort moves (or removes) it; so does a first
        token that finished its row."""
        for req, pos, (n, _) in zip(
            work.requests, self._row_positions(work), self._row_advance(work)
        ):
            if req.status.finished or req not in self.running:
                return False
            base = req.num_computed_tokens + req.num_inflight_tokens - n
            if base != pos:
                return False
        return True

    def rollback_speculative(self, work: ScheduleOutput) -> None:
        """Discard a dispatched-but-invalidated step: clear its in-flight
        advance and free the blocks allocated beyond each row's real
        residency. The device still executes the discarded step, but its
        writes land only at positions >= the speculative base — beyond
        every registered prefix-cache block, and fully overwritten (in
        device order) by whichever dispatch next owns those slots. The
        round's count of chunks and the verify / decode turn go back to
        where the discarded work's schedule() found them (nothing is
        scheduled between a dispatch and its rollback), so the re-dispatch
        follows the serial loop's order. Goodput ledger: what the step
        samples (a window x its rows, a verify row's fed positions, a
        chunk's first tokens) nobody will consume — sampled AND wasted in
        one motion (they never reach postprocess)."""
        self.ledger.rollback(sum(out for _, out in self._row_advance(work)))
        self.end_speculative(work)
        self._round_chunks, self._last_was_verify = self._turn_before
        for req in work.requests:
            if req.status.finished or req not in self.running:
                continue  # blocks already released by its finish
            # the discarded step ran on the device and wrote the row's state
            # into the other bank: what it read is where it was
            req.state_bank ^= 1
            keep = self._blocks_needed(
                req.num_computed_tokens + req.num_inflight_tokens
            )
            while len(req.block_table) > keep:
                self.pool.free_block(req.block_table.pop())
                if self.pool.window is not None:
                    # (never a page given back: those lie behind the
                    # resolved position, `keep` ahead of it)
                    wblk = req.window_table.pop()
                    if wblk:
                        self.pool.window.free_block(wblk)

    # -- post-step ---------------------------------------------------------

    def postprocess(
        self, work: ScheduleOutput, sampled: list[list[int]]
    ) -> list[tuple[Request, list[int]]]:
        """Apply one step's results. `sampled` carries one row per request
        (prefill: 0 or 1 tokens; decode: up to `window` candidates). Returns
        [(request, accepted_new_tokens)] — an empty list marks a prefill chunk
        that didn't finish the prompt. Decode candidates past a stop condition
        are discarded."""
        results: list[tuple[Request, list[int]]] = []
        proposal_lens: list[int] | None = None
        row_proposers: list[str] | None = None
        if isinstance(work, VerifyWork):
            # acceptance: the model's argmax m[j] at fed position j is valid
            # output iff every earlier proposal matched; the first mismatch
            # position still yields m[j] itself (the "bonus" token) — so a
            # row emits 1..k+1 tokens, and a proposal-less row emits exactly
            # its plain greedy token. Acceptance COUNTERS are bumped in the
            # decode loop below, after the max_tokens/stop cut, so the
            # acceptance-rate metric never counts tokens that were clipped
            # before emission.
            proposal_lens = [len(p) for p in work.proposals]
            row_proposers = list(work.proposers) or ["ngram"] * len(
                work.requests
            )
            accepted_rows: list[list[int]] = []
            for i, req in enumerate(work.requests):
                m = sampled[i]
                p = work.proposals[i]
                accepted: list[int] = []
                for j in range(len(p) + 1):
                    accepted.append(int(m[j]))
                    if j < len(p) and int(m[j]) != p[j]:
                        break
                accepted_rows.append(accepted)
                # goodput ledger: the device argmax-sampled len(p)+1
                # positions; everything past the first mismatch is a
                # mispredicted draft — just another rollback (the accepted
                # prefix is ledgered by the decode loop below)
                self.ledger.rollback(len(p) + 1 - len(accepted))
            work = DecodeWork(requests=work.requests)  # shared accounting
            sampled = accepted_rows
        if isinstance(work, PrefillWork):
            for i, req in enumerate(work.requests):
                if req.status.finished:
                    # aborted or expired while its chunk was in flight: its
                    # blocks are released (the chunk's writes landed before
                    # any later dispatch could own them) and its stream is
                    # closed — a sampled first token is void, like a decode
                    # row's (ledger: sampled and wasted in one motion)
                    self.ledger.rollback(int(work.sample[i]))
                    results.append((req, []))
                    continue
                start = req.num_computed_tokens
                end = work.context_lens[i]
                req.num_computed_tokens = end
                self._register_full_blocks(req, start, work.context_lens[i])
                # goodput ledger: chunk positions past the prompt are
                # GENERATED tokens being re-computed after a preemption
                # dropped their KV — the device samples through them again
                # and the re-pass is pure waste (the values were already
                # known). Counting them as sampled+wasted here keeps the
                # partition exact: each token's FATE (pending → delivered/
                # wasted at finish) is still classified exactly once.
                recomputed = max(0, end - max(start, req.num_prompt_tokens))
                self.ledger.sampled(recomputed)
                self.ledger.waste("preempted_recompute", recomputed)
                if work.sample[i]:
                    tok = sampled[i][0]
                    if req.grammar is not None and req.sampling.grammar is not None:
                        req.grammar.sync(req.output_token_ids)
                        req.grammar.advance(int(tok))
                    req.output_token_ids.append(tok)
                    # goodput ledger: one sampled first token, pending until
                    # the request's fate is known (finish / preemption)
                    self.ledger.sampled(1)
                    req.ledger_pending += 1
                    self._maybe_finish(req)
                    results.append((req, [tok]))
                else:
                    results.append((req, []))
        else:
            for i, (req, row) in enumerate(zip(work.requests, sampled)):
                if req.status.finished:
                    # finished while the step was in flight (async abort /
                    # stop-string hit): its blocks are already released and
                    # its stream is closed — the sampled row is void.
                    # Ledger: the device executed the row for a request
                    # nobody is waiting on — pipeline machinery waste
                    self.ledger.rollback(len(row))
                    results.append((req, []))
                    continue
                # bulk accept: a decode window hands up to `window` candidate
                # tokens per row — the previous token-at-a-time loop
                # (computed += 1, register, append, finish-check per token)
                # cost ~0.4 s of host time per 256x128 wave. Compute the cut
                # point first, then apply in one pass; semantics match the
                # per-token loop exactly (first eos/stop token is ACCEPTED
                # then finishes; length caps clip the row).
                s = req.sampling
                n = min(
                    len(row),
                    s.max_tokens - len(req.output_token_ids),
                    self.model_config.max_model_len - req.num_tokens,
                )
                cut = n
                eos = None if s.ignore_eos else req.eos_token_id
                # structured output: the host cursor advances ONLY here, on
                # accepted tokens — discarded speculative steps never touch
                # it, so it needs no rollback and survives preemption with
                # output_token_ids. The admissibility check is belt-and-
                # suspenders (the device mask already guarantees sampled
                # tokens are admissible): a violating token cuts the row
                # BEFORE itself and the tail lands in the same "overshoot"
                # waste bucket as a stop cut, keeping the ledger partition
                # exact.
                gram = req.grammar if s.grammar is not None else None
                if gram is not None:
                    gram.sync(req.output_token_ids)
                if eos is not None or s.stop_token_ids or gram is not None:
                    n_out0 = len(req.output_token_ids)
                    for j in range(n):
                        if _is_stop_token(row[j], s, eos, n_out0 + j + 1):
                            cut = j + 1
                            if gram is not None:
                                # EOS is a terminator (state untouched); a
                                # non-EOS stop token is a real grammar byte
                                gram.advance(int(row[j]))
                            break
                        if gram is not None:
                            if not gram.allows(int(row[j])):
                                cut = j
                                break
                            gram.advance(int(row[j]))
                accepted = [int(t) for t in row[:cut]]
                # goodput ledger: every candidate in the row was sampled on
                # device; the tail past the stop/length cut is discarded
                # overshoot, the accepted prefix stays pending on the
                # request until its fate is known
                self.ledger.sampled(len(row))
                self.ledger.waste("overshoot", len(row) - len(accepted))
                req.ledger_pending += len(accepted)
                if proposal_lens is not None:
                    # every emitted token past the first rode a matched
                    # proposal; the first is the plain greedy/bonus token
                    n_acc = max(0, len(accepted) - 1)
                    self.spec_proposed_tokens += proposal_lens[i]
                    self.spec_accepted_tokens += n_acc
                    by = row_proposers[i]
                    self.spec_proposed_by[by] += proposal_lens[i]
                    self.spec_accepted_by[by] += n_acc
                    # per-window acceptance for the tracing spine's
                    # decode_window event — consumed (and cleared) by
                    # LLMEngine._make_output on this step's output
                    req.spec_window = (proposal_lens[i], n_acc, by)
                if accepted:
                    # outputs FIRST: _register_full_blocks hashes block
                    # contents via token_at over positions that include the
                    # just-accepted tokens
                    req.output_token_ids.extend(accepted)
                    start = req.num_computed_tokens
                    req.num_computed_tokens += len(accepted)
                    if work.block_tokens:
                        # ... and the window committed the positions of
                        # the tokens it was given too
                        req.num_computed_tokens += len(work.block_tokens[i])
                    self._register_full_blocks(
                        req, start, req.num_computed_tokens
                    )
                    self._maybe_finish(req)
                results.append((req, accepted))
        # per-tenant decode-share observability (tpu:tenant_generation_
        # tokens_total) — batched to one counter bump per tenant per step
        tok_counts: dict[str, int] = {}
        for req, toks in results:
            if toks:
                tok_counts[req.tenant_id] = (
                    tok_counts.get(req.tenant_id, 0) + len(toks)
                )
        for t, n in tok_counts.items():
            self.accounting.inc(t, "generation_tokens", n)
        return results

    def _register_full_blocks(self, req: Request, start: int, end: int) -> None:
        chain = self._hash_chains.setdefault(
            req.request_id, [self._chain_root(req)]
        )
        first_new = start // self.block_size
        last_full = end // self.block_size  # blocks [0, last_full) are full
        for i in range(first_new, last_full):
            if i + 1 < len(chain):
                continue  # already registered (cached prefix)
            tokens = tuple(
                req.token_at(j)
                for j in range(i * self.block_size, (i + 1) * self.block_size)
            )
            h = self.pool.register_full_block(req.block_table[i], chain[i], tokens)
            chain.append(h)

    def _maybe_finish(self, req: Request) -> None:
        s = req.sampling
        last = req.output_token_ids[-1]
        eos = None if s.ignore_eos else req.eos_token_id
        if _is_stop_token(last, s, eos, len(req.output_token_ids)):
            status = RequestStatus.FINISHED_STOPPED
        elif len(req.output_token_ids) >= s.max_tokens:
            status = RequestStatus.FINISHED_LENGTH
        elif req.num_tokens >= self.model_config.max_model_len:
            status = RequestStatus.FINISHED_LENGTH
        else:
            return
        self.running.remove(req)
        self._finish(req, status)

    def finish_request(self, req: Request, status: RequestStatus) -> None:
        """Externally finish a running request (e.g. stop-string hit found by
        the engine's detokenizer)."""
        if req in self.running:
            self.running.remove(req)
        self._finish(req, status)

    def _finish(self, req: Request, status: RequestStatus) -> None:
        import time

        req.status = status
        req.finish_time = time.monotonic()
        # a request finishing mid-hydration (abort / deadline / shed)
        # settles its plan first: deferred tokens classify as recomputed,
        # in-flight fetches drop their results
        self._settle_hydration_plan(req)
        # draft scratch blocks die with the request (abort mid-draft
        # included) — they were never content-addressed, so nothing to
        # unpublish
        if self.draft_proposer is not None:
            self.draft_proposer.release(req.request_id)
        # goodput ledger: the request's fate is sealed — classify its
        # pending tokens (delivered for stop/length; deadline_expired /
        # shed_evicted / severed for the rest, saturation.FINISH_REASONS)
        self.ledger.classify_finish(status.name, req.ledger_pending)
        req.ledger_pending = 0
        self._release_blocks(req)
