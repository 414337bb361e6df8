"""Request/sequence state for the serving engine.

The reference stack's requests live as OpenAI JSON bodies proxied to vLLM
(src/vllm_router/services/request_service/request.py); inside our TPU engine
each becomes a `Request` tracked by the scheduler through the continuous-
batching lifecycle.
"""

from __future__ import annotations

import enum
import time
from dataclasses import dataclass, field


@dataclass(frozen=True)
class SamplingParams:
    max_tokens: int = 128
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0  # 0 = disabled
    stop: tuple[str, ...] = ()
    stop_token_ids: tuple[int, ...] = ()
    ignore_eos: bool = False
    # vLLM extension: suppress eos/stop-token finishes until this many
    # output tokens exist (stop STRINGS and length caps still apply)
    min_tokens: int = 0
    seed: int | None = None
    # OpenAI logprobs: None = off; N = return the chosen token's logprob
    # plus the top-N alternatives per generated token (N <= runner
    # LOGPROBS_TOPN; 0 = chosen-only)
    logprobs: int | None = None
    # structured output (docs/41-structured-output.md): the COMPILED
    # grammar.TokenGrammar this request's generation must satisfy, or None
    # for unconstrained. Compiled once at the API layer (GrammarCache) and
    # shared across requests; compared by identity, which is exactly the
    # sharing semantics the runner's device-table cache keys on.
    grammar: object | None = None

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


class RequestStatus(enum.Enum):
    WAITING = "waiting"
    RUNNING = "running"
    PREEMPTED = "preempted"
    FINISHED_STOPPED = "finished_stopped"  # eos / stop string
    FINISHED_LENGTH = "finished_length"  # max_tokens / max_model_len
    FINISHED_ABORTED = "finished_aborted"
    # deadline expired while queued or decoding — aborted with a clean
    # "deadline" finish reason instead of burning further TPU steps
    FINISHED_DEADLINE = "finished_deadline"
    # evicted from the waiting queue by a higher-priority admission when
    # the queue bound was full (multi-tenant QoS: lowest-priority-first
    # shedding, docs/27-multitenancy.md) — the HTTP layer maps this back
    # to a 429 + Retry-After
    FINISHED_SHED = "finished_shed"

    @property
    def finished(self) -> bool:
        return self in (
            RequestStatus.FINISHED_STOPPED,
            RequestStatus.FINISHED_LENGTH,
            RequestStatus.FINISHED_ABORTED,
            RequestStatus.FINISHED_DEADLINE,
            RequestStatus.FINISHED_SHED,
        )


@dataclass(eq=False)  # identity semantics: requests live in sets/queues
class Request:
    request_id: str
    prompt_token_ids: list[int]
    sampling: SamplingParams = field(default_factory=SamplingParams)
    eos_token_id: int | None = None
    arrival_time: float = field(default_factory=time.monotonic)

    # adapter slot in the runner's stacked LoRA buffers (0 = base model)
    lora_index: int = 0
    # stable per-adapter-load salt for the KV hash chain (0 = base model).
    # Slot numbers get REUSED across adapter loads, so the prefix cache keys
    # on this instead: adapter KV differs from base KV whenever k/v
    # projections carry deltas, and cross-matching would be silent corruption
    lora_cache_salt: int = 0

    status: RequestStatus = RequestStatus.WAITING
    output_token_ids: list[int] = field(default_factory=list)
    # blocks owned by this request, logical order (block_table[i] = page of
    # tokens [i*block_size, (i+1)*block_size))
    block_table: list[int] = field(default_factory=list)
    # the row's pages in the WINDOW group (engine/kv_cache.py), where the
    # model has window layers: the same logical indexing and length as
    # block_table, 0 where a page fell behind the window and was given
    # back; window_first_live is the first index that may still hold one
    window_table: list[int] = field(default_factory=list)
    window_first_live: int = 0
    # the row's STATE SLOT (engine/kv_cache.py:StateSlots), where the model
    # has layers that hold a recurrent state: 0 = none; state_bank is the
    # bank that holds the state of the row's dispatched positions
    state_slot: int = 0
    state_bank: int = 0
    # the most positions the row had computed when a preemption took its
    # pages and its slot: what it computes again up to there is recompute
    state_recompute_until: int = 0
    # tokens whose KV is resident (prefix-cache hits + computed prefill/decode)
    num_computed_tokens: int = 0
    num_cached_prompt_tokens: int = 0  # prefix-cache hits at admission
    # lifecycle stamps (time.monotonic()) behind the tracing spine's phase
    # attribution (docs/28-request-tracing.md): queue wait = first_seat -
    # arrival, prefill = first_token - first_seat, decode = finish -
    # first_token. first_seat_time is the FIRST admission only — a
    # preempted request re-admitting keeps its original stamp, so phases
    # describe the caller-visible lifecycle, not scheduler churn.
    first_seat_time: float | None = None
    first_token_time: float | None = None
    finish_time: float | None = None
    num_preemptions: int = 0
    # positions dispatched in not-yet-resolved device steps (async
    # pipeline): a decode window's or a verify row's fed tokens, a prefill
    # chunk's tokens — their KV is being written. The scheduler plans the
    # NEXT step at num_computed_tokens + num_inflight_tokens; postprocess
    # of the resolved step moves these into num_computed_tokens for real
    num_inflight_tokens: int = 0
    # output tokens those steps sample that have not reached the host: the
    # whole window of a decode or verify step (full acceptance), the ONE
    # first token of a prefill chunk that ends its prompt, none for a
    # mid-prompt chunk. Counted as generated for max_tokens / window
    # clamping and the seeded-sampling fold; a row with one takes its next
    # input token from the in-flight step's device output
    num_inflight_outputs: int = 0
    # goodput ledger (engine/saturation.GoodputLedger): accepted output
    # tokens not yet classified delivered/wasted. Charged in postprocess,
    # settled exactly once at finish (delivered for stop/length,
    # wasted{reason} otherwise). Pending SURVIVES preemption — the token
    # values live on in output_token_ids, so their fate is still open; the
    # recompute cost is charged separately (preempted_recompute) when
    # resumed prefill re-processes generated positions.
    ledger_pending: int = 0
    # hydration attribution (docs/30-kv-flow-telemetry.md): where this
    # request's prompt-token KV came from, classified EXACTLY once at first
    # admission — {hbm_hit, host_reload, disk_load, remote_fetch,
    # recomputed} tokens summing to num_prompt_tokens. None until seated
    # (and forever for requests refused before a seat).
    hydration: dict | None = None
    # compute-or-load hydration planner (docs/31-hydration-planner.md):
    # the live chunk plan over this request's lower-tier-resident prefix
    # (engine/hydration.HydrationPlan), None when no plan is active —
    # cleared when fully consumed, cancelled on preempt/finish.
    hydration_plan: object | None = None
    # per-chunk outcome records appended as chunks resolve — surfaced on
    # the terminal output for the kv_hydration trace event's plan view
    hydration_outcomes: list | None = None
    # absolute time.monotonic() after which this request is worthless to its
    # caller (x-request-deadline-ms, carried router → engine → scheduler);
    # None = no deadline. The scheduler sweeps expired requests out of
    # waiting/running at the top of every schedule() call.
    deadline: float | None = None
    # multi-tenant QoS (docs/27-multitenancy.md), from the router-stamped
    # x-tenant-id / x-priority / x-tenant-weight headers. priority is the
    # RANK (0 realtime, 1 standard, 2 batch): lower wins admission,
    # higher is preempted/shed first. Unstamped traffic carries the
    # defaults and collapses to the pre-QoS FIFO behavior.
    tenant_id: str = "default"
    priority: int = 1
    weight: float = 1.0
    # peer-engine KV tier (docs/35-peer-kv-reuse.md): the router's
    # x-kv-owner-hint — the engine URL whose tiers hold this prompt's
    # prefix, stamped when priced route-vs-migrate sent the request AWAY
    # from the owner. The hydration planner's probe uses it to skip the
    # cluster-index rediscovery hop. None = rediscover (or no peer tier).
    kv_owner_hint: str | None = None
    # speculative decoding (docs/36-speculative-decoding.md): the LAST
    # resolved verify window's (proposed, accepted, proposer) — stamped by
    # postprocess, moved onto that step's RequestOutput by _make_output
    # (and cleared), so the tracing spine's decode_window event carries
    # per-window acceptance
    spec_window: tuple | None = None
    # pipelined spec-decode retry budget (scheduler.SPEC_RETRY_WINDOWS):
    # chained decode windows left to ride after a failed propose attempt
    # before the row sits one step out to re-propose on resolved values
    spec_retry_in: int = 0
    # structured output (docs/41-structured-output.md): per-request
    # automaton cursor (grammar.GrammarState), None when unconstrained.
    # Advanced ONLY on accepted tokens in scheduler.postprocess — so it
    # needs no rollback of its own (discarded speculative steps never
    # touched it) and survives preemption with output_token_ids.
    grammar: object | None = None
    # compile telemetry (docs/42-compile-telemetry.md): mid-traffic XLA
    # compiles this request's dispatches blocked on — {phase, key,
    # wall_ms} dicts stamped by the runner, moved onto the terminal
    # output by _make_output for the trace timeline's compile_stall
    # events. None (the steady state) = never stalled.
    compile_stalls: list | None = None
    # generation by diffusion over blocks (ModelConfig.block_diffusion): the
    # model's block length, stamped by the scheduler at add_request; 0 for
    # every other model. The pool holds COMMITTED blocks only: a prefill
    # ends at the last block boundary at or before the row's tokens, yields
    # no token, and what lies behind it (the tail of the prompt) is given
    # to the row's next decode window
    block_length: int = 0

    @property
    def num_prompt_tokens(self) -> int:
        return len(self.prompt_token_ids)

    @property
    def num_tokens(self) -> int:
        return len(self.prompt_token_ids) + len(self.output_token_ids)

    @property
    def all_token_ids(self) -> list[int]:
        return self.prompt_token_ids + self.output_token_ids

    @property
    def prefill_target(self) -> int:
        """Tokens whose KV must be resident before decode can run. For a fresh
        request that's the whole prompt; for a preempted-then-resumed request
        (which already has outputs to recompute) it's everything except the
        last token — that one is the next decode step's input."""
        if self.block_length:
            return self.num_tokens // self.block_length * self.block_length
        if self.output_token_ids:
            return self.num_tokens - 1
        return self.num_prompt_tokens

    @property
    def prefill_done(self) -> bool:
        return self.num_computed_tokens >= self.prefill_target

    def token_at(self, idx: int) -> int:
        np_ = len(self.prompt_token_ids)
        return (
            self.prompt_token_ids[idx]
            if idx < np_
            else self.output_token_ids[idx - np_]
        )

    def tokens_between(self, start: int, end: int) -> list[int]:
        """[token_at(i) for i in range(start, end)] by slices: a 2,048-token
        chunk's row in two list operations, not 2,048 calls (which a
        profiler's Python tracer stretches to ~10 ms of `sched`)."""
        np_ = len(self.prompt_token_ids)
        row = list(self.prompt_token_ids[start:min(end, np_)])
        if end > np_:
            row += self.output_token_ids[max(start - np_, 0):end - np_]
        return row


@dataclass
class RequestOutput:
    """Per-step incremental output handed to the API layer."""

    request_id: str
    new_token_ids: list[int]
    finished: bool
    finish_reason: str | None = None  # "stop" | "length" | "abort"
    num_prompt_tokens: int = 0
    num_output_tokens: int = 0
    num_cached_prompt_tokens: int = 0
    text_delta: str = ""
    # aligned with new_token_ids when the request asked for logprobs:
    # one (chosen_logprob, top_ids, top_logprobs) triple per token
    new_logprobs: list[tuple[float, list[int], list[float]]] | None = None
    # set on the TERMINAL output only: the request's lifecycle stamps
    # (time.monotonic(): arrival/first_seat/first_token/finish, None where
    # a phase never happened) — the HTTP layer turns these into trace
    # phase spans and the tpu:request_* histograms without reaching back
    # into engine state that _drop_finished already reaped
    phase_times: dict | None = None
    # terminal output only: the request's hydration-source partition
    # (Request.hydration) — the HTTP layer emits it as the timeline's
    # kv_hydration event (docs/30-kv-flow-telemetry.md)
    hydration: dict | None = None
    # terminal output only: the hydration planner's per-chunk outcomes
    # (Request.hydration_outcomes) — the kv_hydration trace event's
    # "plan" attribute (docs/31-hydration-planner.md)
    hydration_chunks: list | None = None
    # set when this step resolved a speculative-verify window for the
    # request: (proposed, accepted, proposer) — the tracing spine adds it
    # to the decode_window event (docs/36-speculative-decoding.md)
    spec_window: tuple | None = None
    # terminal output only, constrained requests only: "valid" when the
    # automaton finished in an accepting state (the body parses against
    # the schema by construction), "invalid" when terminated mid-structure
    # (length cut / abort), "fallback" when constraints were requested but
    # not applied (docs/41-structured-output.md)
    structured_outcome: str | None = None
    # terminal output only: mid-traffic compile stalls this request's
    # dispatches blocked on (Request.compile_stalls) — each becomes a
    # compile_stall event on the trace timeline
    # (docs/42-compile-telemetry.md)
    compile_stalls: list | None = None
