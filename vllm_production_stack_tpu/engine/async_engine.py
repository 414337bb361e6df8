"""Async bridge over the synchronous LLMEngine for the HTTP server.

A dedicated step thread drives the device (JAX dispatch must not block the
event loop — a single TPU step is milliseconds-to-tens-of-ms of host work);
per-request asyncio queues carry outputs back to handler coroutines. This is
the TPU stack's analogue of vLLM's AsyncLLMEngine, which the reference stack
always talks to over HTTP (request.py:99-105).
"""

from __future__ import annotations

import asyncio
import itertools
import logging
import threading
import time
from collections import deque
from collections.abc import AsyncIterator

from .engine import LLMEngine
from .flightrec import FlightRecorder
from .request import RequestOutput, SamplingParams

logger = logging.getLogger(__name__)


class EngineSleepingError(RuntimeError):
    """Request submitted while the engine is parked (router should have
    filtered this endpoint out via the sleeping label — discovery contract,
    reference service_discovery.py:414-496)."""


class EngineDrainingError(RuntimeError):
    """Request submitted while the engine is draining (SIGTERM / POST
    /drain): admissions are stopped so in-flight streams can finish and the
    process can exit inside its grace period. The HTTP layer answers 503
    with X-Engine-Draining so the router fails the request over instead of
    surfacing the refusal to the client."""


def _same_request(rid: str, parent: str) -> bool:
    """True when `rid` is `parent` itself or one of its n>1 sibling choice
    ids (server._choice_rids derivation: parent, parent-1, parent-2, …)."""
    return rid == parent or rid.startswith(parent + "-")


def _name_os_thread(name: str) -> None:
    """Give the calling thread its name at the OS level too (Python 3.12
    names threads only inside the interpreter): a profiler capture and
    `top -H` label the thread's line by it. Best effort, Linux only."""
    try:
        with open(f"/proc/self/task/{threading.get_native_id()}/comm",
                  "w") as f:
            f.write(name[:15])
    except OSError:
        pass


class AsyncEngine:
    def __init__(self, engine: LLMEngine):
        self.engine = engine
        # ONE quiescence lock shared with the engine (engine.step_lock):
        # the Hydrator's device-collective peer pull takes it on the
        # fetcher thread, so "holding it" must mean "no step in flight"
        # for the step loop here too. getattr keeps pre-step_lock test
        # doubles working.
        self._lock = getattr(engine, "step_lock", None) or threading.Lock()
        self._queues: dict[str, asyncio.Queue[RequestOutput]] = {}
        # deferred admissions: (rid, token_ids, sampling, lora_name).
        # Submissions NEVER take the engine lock — on a busy engine the step
        # thread holds it nearly continuously (a full device step each
        # time), and Python locks aren't fair, so a contending submit sat
        # behind multiple steps (measured: 1.7s mean submit wait under the
        # north-star load). The step thread drains this queue at the top of
        # every iteration instead.
        self._pending: deque = deque()
        self._pending_lock = threading.Lock()
        self._loop: asyncio.AbstractEventLoop | None = None
        self._wake = threading.Event()
        self._stop = False
        # graceful drain: False stops NEW admissions (submit raises
        # EngineDrainingError) while in-flight requests keep stepping;
        # _admitting counts requests popped from _pending but not yet in
        # the scheduler (wait_idle must not miss them)
        self.accepting = True
        self._admitting = 0
        self._thread: threading.Thread | None = None
        self._step_error: Exception | None = None
        # thread-liveness heartbeat (docs/37-flight-recorder.md): the step
        # loop beats every iteration — including the idle wait — so a beat
        # older than its threshold means the loop is WEDGED inside a step
        # (collective stall, runaway compile under the engine lock), not
        # merely quiet. Registered in start() so restartable servers
        # refresh rather than duplicate it.
        self._heartbeat = None
        # fatal-wedge hook: called ONCE with the exception when the step
        # loop marks the engine dead (the server points this at the
        # postmortem dumper — the dying step thread writes its own black
        # box before the /health flip is even scraped)
        self.on_fatal = None
        # the step thread's timeline (flightrec.PHASES): this loop's admit,
        # emit and idle phases land beside the engine's own. A test double
        # without a flight recorder gets a private one.
        self._clock = getattr(engine, "flightrec", None) or FlightRecorder()
        # the asyncio side of /debug/timing's `loop` group:
        # tokenize+validate+queue time of submissions
        self.loop_timing = {"submits": 0, "submit_s": 0.0}

    # -- lifecycle ---------------------------------------------------------

    def start(self, loop: asyncio.AbstractEventLoop) -> None:
        if self._thread is not None and self._thread.is_alive():
            self.shutdown()  # restartable (server rebuilt around one engine)
        self._loop = loop
        self._stop = False
        # background program compiles defer to traffic (model_runner
        # _bg_compile_job): compile only when nothing is queued or running
        def idle() -> bool:
            return (
                not self.engine.has_unfinished()
                and not self._pending
            )

        runner = getattr(self.engine, "runner", None)
        if runner is not None:
            runner.idle_check = idle
        draft = getattr(self.engine, "draft_runner", None)
        if draft is not None:
            draft.idle_check = idle
        threads = getattr(self.engine, "threads", None)
        if threads is not None:
            self._heartbeat = threads.register("step")
        self._thread = threading.Thread(
            target=self._step_loop, name="engine-step", daemon=True
        )
        self._thread.start()

    def shutdown(self) -> None:
        self._stop = True
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        threads = getattr(self.engine, "threads", None)
        if threads is not None:
            # a deliberate stop must not read as a wedge at the next check
            threads.unregister("step")
            self._heartbeat = None
        runner = getattr(self.engine, "runner", None)
        if runner is not None and hasattr(runner, "shutdown"):
            runner.shutdown()  # cancel queued background compiles
        draft = getattr(self.engine, "draft_runner", None)
        if draft is not None and hasattr(draft, "shutdown"):
            draft.shutdown()  # the draft proposer's runner compiles too
        hydrator = getattr(self.engine, "hydrator", None)
        if hydrator is not None:
            hydrator.close()  # stop the hydration fetcher thread
        peer = getattr(self.engine, "peer_tier", None)
        if peer is not None:
            peer.close()  # idempotent; hydrator.close already closed it
        host_tier = getattr(self.engine, "host_tier", None)
        remote = getattr(self.engine, "remote_tier", None)
        if host_tier is not None:
            # resolve pending device transfers (write-through to remote)
            host_tier.flush()
        if remote is not None:
            remote.drain(timeout=5)
            remote.close()

    @property
    def is_healthy(self) -> bool:
        return (
            self._thread is not None
            and self._thread.is_alive()
            and self._step_error is None
        )

    # consecutive step failures tolerated before the engine reports dead:
    # transient device faults (a dropped remote-compile connection, a
    # preempted dispatch) fail the in-flight requests but must not brick
    # the server — the reference stack gets this resilience from k8s
    # restart + readiness probes; a self-healing step loop is strictly
    # better (no pod churn, warm compile caches survive)
    MAX_CONSECUTIVE_STEP_FAILURES = 3

    def loop_snapshot(self) -> dict:
        """/debug/timing's `loop` group. busy_s: iterations that ran a step
        (the lock, admissions, step()); idle_s: parked on the wake event."""
        t = self._clock.timing()
        return {
            "steps": t["steps"], "busy_s": t["admit_s"] + t["step_wall_s"],
            "idle_s": t["idle_s"], **self.loop_timing,
        }

    def _step_loop(self) -> None:
        _name_os_thread("engine-step")
        clock = self._clock
        hb = self._heartbeat
        failures = 0
        while not self._stop:
            if hb is not None:
                # every iteration, idle path included: staleness then means
                # "wedged inside a step", never "no traffic"
                hb.beat()
            try:
                with clock.span("admit"):
                    self._lock.acquire()
                try:
                    with clock.span("admit"):
                        self._drain_pending()
                    has_work = (
                        not self.engine.is_sleeping and self.engine.has_unfinished()
                    )
                    outputs = self.engine.step() if has_work else []
                finally:
                    self._lock.release()
                failures = 0
            except Exception as e:
                failures += 1
                clock.fault(str(e))
                if failures >= self.MAX_CONSECUTIVE_STEP_FAILURES:
                    # persistent fault: surface to /health, fail everything
                    logger.exception(
                        "engine step failed %d times consecutively; "
                        "marking engine dead", failures,
                    )
                    self._step_error = e
                    self._notify_fatal(e)
                    self._fail_all(e)
                    return
                # transient fault: the failed step may have left requests
                # half-executed — abort ALL in-flight work (clients get a
                # terminal error output), then keep serving new requests
                logger.exception(
                    "engine step failed (attempt %d/%d); aborting in-flight "
                    "requests and continuing",
                    failures, self.MAX_CONSECUTIVE_STEP_FAILURES,
                )
                try:
                    with self._lock:
                        self._abort_all_inflight(e)
                except Exception:
                    logger.exception("in-flight abort failed; engine dead")
                    self._step_error = e
                    self._notify_fatal(e)
                    self._fail_all(e)
                    return
                continue
            if outputs:
                with clock.span("emit", seq=clock.done_seq):
                    for out in outputs:
                        self._dispatch(out)
            if has_work:
                clock.count("steps")
            else:
                clock.quiet()
                with clock.span("idle"):
                    self._wake.wait(timeout=0.02)
                    self._wake.clear()

    def _drain_pending(self) -> None:
        """Admit queued submissions (caller holds the engine lock). The
        per-item work is trivial (token ids precomputed, validation done at
        submit time); a failure here is a race (e.g. LoRA unloaded after
        validation) and fails that request's stream, never the loop."""
        while True:
            with self._pending_lock:
                if not self._pending:
                    return
                (rid, token_ids, sampling, lora_name, deadline, tenant,
                 kv_owner_hint) = self._pending.popleft()
                # popped but not yet in the scheduler: wait_idle must not
                # read this window as "drained" (pending empty + scheduler
                # empty) while the request is mid-admission
                self._admitting += 1
            try:
                self._admit_one(
                    rid, token_ids, sampling, lora_name, deadline, tenant,
                    kv_owner_hint,
                )
            finally:
                with self._pending_lock:
                    self._admitting -= 1

    def _admit_one(
        self, rid, token_ids, sampling, lora_name, deadline, tenant=None,
        kv_owner_hint=None,
    ):
        """Move one popped submission into the engine (step thread, engine
        lock held). A failure fails that request's stream, never the loop."""
        if rid not in self._queues:
            return  # consumer vanished (disconnect/abort) pre-admission
        if self.engine.is_sleeping:
            # raced sleep(): a silent hang (admitted but never stepped)
            # becomes the same deterministic error the submit-time check
            # gives
            self._fail_stream(
                rid,
                "engine is sleeping; wake it before sending requests",
            )
            return
        try:
            self.engine.add_request(
                request_id=rid,
                prompt_token_ids=token_ids,
                sampling=sampling,
                lora_name=lora_name,
                deadline=deadline,
                tenant=tenant,
                kv_owner_hint=kv_owner_hint,
            )
        except Exception as e:
            logger.warning("deferred admission failed for %s: %s", rid, e)
            self._fail_stream(rid, str(e))

    def _notify_fatal(self, exc: Exception) -> None:
        """Fire the fatal-wedge hook exactly where the engine dies (the
        step thread) — the postmortem must capture the dying stacks, not
        whatever the event loop looks like at the next scrape."""
        hook = self.on_fatal
        if hook is None:
            return
        try:
            hook(exc)
        except Exception:
            logger.exception("on_fatal hook failed")

    def _fail_stream(self, rid: str, message: str) -> None:
        """Deliver a terminal error output to a request's stream queue."""
        q = self._queues.get(rid)
        if q is not None and self._loop is not None:
            out = RequestOutput(
                request_id=rid, new_token_ids=[], finished=True,
                finish_reason="error",
            )
            out.text_delta = f"engine error: {message}"
            self._loop.call_soon_threadsafe(q.put_nowait, out)

    def _abort_all_inflight(self, exc: Exception) -> None:
        """Terminal-error every queued request and reap its engine state
        (caller holds the engine lock)."""
        with self._pending_lock:
            # unadmitted requests die here too — leaving them pending would
            # re-admit them (rid still in _queues until the event loop runs)
            # and decode to max_tokens into a stream that already ended.
            # Snapshot _queues under the SAME lock _submit inserts under, so
            # a submission racing this abort either fully precedes it (and
            # dies here) or fully follows it (and survives to be admitted)
            self._pending.clear()
            rids = list(self._queues)
        for rid in rids:
            self.engine.abort_request(rid)
            q = self._queues.pop(rid, None)
            if q is not None and self._loop is not None:
                out = RequestOutput(
                    request_id=rid, new_token_ids=[], finished=True,
                    finish_reason="error",
                )
                out.text_delta = f"engine error: {exc}"
                self._loop.call_soon_threadsafe(q.put_nowait, out)

    def _dispatch(self, out: RequestOutput) -> None:
        q = self._queues.get(out.request_id)
        if q is None or self._loop is None:
            return
        self._loop.call_soon_threadsafe(q.put_nowait, out)

    def _fail_all(self, exc: Exception) -> None:
        if self._loop is None:
            return
        for rid, q in list(self._queues.items()):
            out = RequestOutput(
                request_id=rid, new_token_ids=[], finished=True,
                finish_reason="error",
            )
            out.text_delta = f"engine error: {exc}"
            self._loop.call_soon_threadsafe(q.put_nowait, out)

    # -- serving API -------------------------------------------------------

    _rid_counter = itertools.count()

    def pending_depth(
        self, exclude_prefix: str | None = None
    ) -> tuple[int, int]:
        """(requests, prompt tokens) queued for admission but not yet seen
        by the scheduler — the share of the backlog only this bridge knows
        about, fed into the engine's admission gate. exclude_prefix drops
        a request's own sibling choices ({rid}, {rid}-i) from the count."""
        with self._pending_lock:
            items = list(self._pending)
        if exclude_prefix is not None:
            items = [
                it for it in items
                if not _same_request(it[0], exclude_prefix)
            ]
        return len(items), sum(len(it[1]) for it in items)

    def precheck_admission(
        self, deadline: float | None = None, n_new_tokens: int = 0,
        record: bool = True, tenant=None,
    ) -> None:
        """Lock-free admission gate for HTTP handlers, run BEFORE a stream's
        SSE headers go out so overload/drain/deadline refusals keep their
        proper status codes (429/503). The same checks rerun at submit time
        — this is the common-case fast path, not the only line of defense.
        record=False is the would-this-shed probe (/ready, /health): probe
        polls must not inflate the shed counters."""
        if not self.accepting:
            raise EngineDrainingError(
                "engine is draining; retry against another endpoint"
            )
        extra_waiting, extra_tokens = self.pending_depth()
        self.engine.check_admission(
            n_new_tokens, deadline,
            extra_waiting=extra_waiting, extra_tokens=extra_tokens,
            record=record, tenant=tenant,
        )

    def _submit(
        self, request_id, prompt, prompt_token_ids, sampling, q,
        lora_name=None, deadline=None, admission_exclude_prefix=None,
        tenant=None, kv_owner_hint=None,
    ) -> str:
        """Runs in an executor. Deliberately LOCK-FREE: tokenization +
        validation need no engine state mutation, and admission is deferred
        to the step thread via the pending queue — a submit contending for
        the engine lock used to wait out whole device steps (unfair lock +
        near-100% hold time = 1.7s mean TTFT tax under load)."""
        t0 = time.perf_counter()
        if not self.accepting:
            raise EngineDrainingError(
                "engine is draining; retry against another endpoint"
            )
        if self.engine.is_sleeping:
            raise EngineSleepingError(
                "engine is sleeping; wake it before sending requests"
            )
        if prompt_token_ids is None:
            if prompt is None:
                raise ValueError("need prompt or prompt_token_ids")
            prompt_token_ids = self.engine.tokenizer.encode(prompt)
        # synchronous 4xx for invalid requests, even with deferred admission
        self.engine.validate_new_request(prompt_token_ids, lora_name)
        # load shedding + would-queue-past-deadline, against the scheduler
        # queue PLUS the pending deque (both feed the same backlog).
        # admission_exclude_prefix (the HTTP request's parent rid) keeps an
        # n>1 request's sibling choices out of its own count — without it a
        # single n=8 request would shed itself against max_waiting_requests
        # on an idle engine.
        extra_waiting, extra_tokens = self.pending_depth(
            exclude_prefix=admission_exclude_prefix
        )
        self.engine.check_admission(
            len(prompt_token_ids), deadline,
            extra_waiting=extra_waiting, extra_tokens=extra_tokens,
            exclude_prefix=admission_exclude_prefix,
            # submit time is where a higher-priority arrival actually
            # claims its lowest-priority eviction victim (QoS)
            tenant=tenant, evict=True,
        )
        with self._pending_lock:
            # re-check under the SAME lock wait_idle samples _pending with:
            # a drain beginning while this thread was tokenizing must not
            # let the request slip into _pending after the drain barrier
            # already observed it empty (the stream would be severed by
            # process exit despite /drain?wait=true reporting drained)
            if not self.accepting:
                raise EngineDrainingError(
                    "engine is draining; retry against another endpoint"
                )
            # check + insert must be atomic vs concurrent submits: two
            # requests sharing an X-Request-Id would otherwise both pass
            # the check and cross-wire their output queues
            if request_id is not None and (
                request_id in self._queues
                or self.engine.has_request(request_id)
            ):
                request_id = f"{request_id}-{id(q) & 0xFFFFFF:x}"
            rid = request_id or f"req-a{next(self._rid_counter)}"
            self._queues[rid] = q
            self._pending.append((rid, list(prompt_token_ids), sampling,
                                  lora_name, deadline, tenant,
                                  kv_owner_hint))
        self.loop_timing["submits"] += 1
        self.loop_timing["submit_s"] += time.perf_counter() - t0
        self._wake.set()
        return rid

    async def generate(
        self,
        prompt: str | None = None,
        prompt_token_ids: list[int] | None = None,
        sampling: SamplingParams | None = None,
        request_id: str | None = None,
        lora_name: str | None = None,
        deadline: float | None = None,
        admission_exclude_prefix: str | None = None,
        tenant=None,
        kv_owner_hint: str | None = None,
    ) -> AsyncIterator[RequestOutput]:
        """Submit a request and yield its incremental outputs.
        admission_exclude_prefix (the parent request id of an n>1 fan-out)
        keeps sibling choices out of this submission's admission count —
        choices gate against OTHER requests, never against their own.
        tenant (qos.TenantContext, from the router-stamped headers) drives
        fair-share admission and priority-aware shedding."""
        if self._step_error is not None:
            raise RuntimeError(f"engine is dead: {self._step_error}")
        q: asyncio.Queue[RequestOutput] = asyncio.Queue()
        loop = asyncio.get_running_loop()
        rid = await loop.run_in_executor(
            None, self._submit, request_id, prompt, prompt_token_ids, sampling,
            q, lora_name, deadline, admission_exclude_prefix, tenant,
            kv_owner_hint,
        )
        finished = False
        try:
            while True:
                out = await q.get()
                yield out
                if out.finished:
                    finished = True
                    return
        finally:
            self._queues.pop(rid, None)
            if not finished:
                # consumer went away (disconnect/cancel): reap the engine-side
                # request or it would decode to max_tokens holding KV blocks
                loop.run_in_executor(None, self._abort_sync, rid)

    def _abort_sync(self, request_id: str) -> bool:
        with self._pending_lock:
            # not yet admitted: dropping the pending entry is the abort
            for item in self._pending:
                if item[0] == request_id:
                    self._pending.remove(item)
                    return True
        with self._lock:
            return self.engine.abort_request(request_id)

    async def abort(self, request_id: str) -> bool:
        ok = await asyncio.get_running_loop().run_in_executor(
            None, self._abort_sync, request_id
        )
        self._queues.pop(request_id, None)
        return ok

    # -- control -----------------------------------------------------------

    async def stats_async(self):
        return await asyncio.get_running_loop().run_in_executor(None, self.stats)

    def stats(self):
        with self._lock:
            snap = self.engine.stats()
        snap.draining = not self.accepting
        return snap

    # -- graceful drain ----------------------------------------------------

    def begin_drain(self) -> None:
        """Stop admissions; in-flight requests keep stepping to completion.
        Idempotent — the /drain handler and the SIGTERM path may both fire."""
        self.accepting = False

    def end_drain(self) -> None:
        """Reopen admissions after a rebalance drain (POST /role re-admits
        the engine under its new pool role — docs/40-pool-rebalancing.md).
        Idempotent; never called on the SIGTERM exit path."""
        self.accepting = True

    async def wait_idle(self, timeout_s: float) -> bool:
        """Wait (bounded) until every in-flight request has finished — the
        drain barrier between 'admissions stopped' and 'safe to exit'.
        Returns True when idle, False when the timeout expired with work
        still in flight (the caller exits anyway; clients of the stragglers
        see a severed stream rather than the process lingering forever)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._pending_lock:
                pending = bool(self._pending) or self._admitting > 0
            if not pending and not self.engine.has_unfinished():
                return True
            await asyncio.sleep(0.05)
        return False

    def tokenize(self, text: str) -> list[int]:
        return self.engine.tokenizer.encode(text)

    def detokenize(self, ids: list[int]) -> str:
        return self.engine.tokenizer.decode(ids)

    def chat_prompt(self, messages: list[dict]) -> str:
        return self.engine.tokenizer.chat_prompt(messages)

    @property
    def is_sleeping(self) -> bool:
        return self.engine.is_sleeping

    def sleep(self, level: int = 1) -> None:
        deadline = time.monotonic() + 30.0
        while True:
            with self._lock:
                with self._pending_lock:
                    pending = bool(self._pending)
                if not pending and not self.engine.has_unfinished():
                    self.engine.sleep(level)
                    return
            if time.monotonic() > deadline:
                raise RuntimeError("engine busy; cannot sleep")
            time.sleep(0.05)

    def wake(self) -> None:
        with self._lock:
            self.engine.wake()

    async def kv_lookup(self, text=None, token_ids=None, lora_name=None) -> int:
        def work():
            # tokenize OUTSIDE the lock: the controller fans lookups to every
            # engine per routed request, and encode() needs no engine state —
            # holding the lock for it would serialize probes against decode
            ids = (
                token_ids
                if token_ids is not None
                else self.engine.tokenizer.encode(text or "")
            )
            with self._lock:
                return self.engine.kv_lookup(token_ids=ids, lora_name=lora_name)

        return await asyncio.get_running_loop().run_in_executor(None, work)

    async def kv_events_snapshot(self) -> tuple[str, int, list[int]]:
        """Consistent (epoch, seq, hashes) resync snapshot for the cluster
        KV index — the pool is quiesced under the engine lock so the seq
        barrier and the hash set describe the same instant."""
        def work():
            with self._lock:
                return self.engine.scheduler.pool.snapshot_events()

        return await asyncio.get_running_loop().run_in_executor(None, work)

    async def embed(self, inputs) -> tuple[list[list[float]], int]:
        """Chunked so a large embedding batch can't monopolize the engine
        lock — decode steps interleave between chunks."""
        loop = asyncio.get_running_loop()
        vectors: list[list[float]] = []
        total_tokens = 0
        CHUNK = 16
        for i in range(0, len(inputs), CHUNK):
            chunk = inputs[i : i + CHUNK]

            def work(c=chunk):
                with self._lock:
                    return self.engine.embed(c)

            v, n = await loop.run_in_executor(None, work)
            vectors.extend(v)
            total_tokens += n
        return vectors, total_tokens

    async def kv_export(self, text=None, token_ids=None, lora_name=None):
        def work():
            ids = (
                token_ids
                if token_ids is not None
                else self.engine.tokenizer.encode(text or "")
            )
            with self._lock:
                return self.engine.kv_export(token_ids=ids, lora_name=lora_name)

        return await asyncio.get_running_loop().run_in_executor(None, work)

    async def kv_export_lazy(self, text=None, token_ids=None, lora_name=None):
        """Lock held only for the chain walk + device fetch dispatch; the
        per-block numpy resolution happens in the streaming handler."""
        def work():
            ids = (
                token_ids
                if token_ids is not None
                else self.engine.tokenizer.encode(text or "")
            )
            with self._lock:
                return self.engine.kv_export_lazy(
                    token_ids=ids, lora_name=lora_name
                )

        return await asyncio.get_running_loop().run_in_executor(None, work)

    async def kv_peer_contains(self, hashes: list[int]) -> int:
        # deliberately LOCK-FREE: the walk is pure GIL-atomic dict/set
        # containment (pool map, host ring, disk index — each
        # thread-safe or atomic on its own), and the answer is
        # staleness-tolerant BY DESIGN (the asking planner re-validates
        # at fetch/adoption). Taking the engine lock here would also
        # let a mis-aimed self-probe (hint naming this engine under a
        # URL scheme _advertised_url can't recognize) stall an
        # admission for the full peer timeout: the step thread waits on
        # this HTTP reply while holding the very lock this handler
        # would need.
        return await asyncio.get_running_loop().run_in_executor(
            None, self.engine.kv_peer_contains, hashes
        )

    async def kv_peer_export(self, hashes: list[int]):
        """Lock held only for the residency walk + device fetch dispatch
        (same discipline as kv_export_lazy); the per-block numpy / disk
        resolution happens in the /kv/peer_fetch handler off the lock."""
        def work():
            with self._lock:
                return self.engine.kv_peer_export(hashes)

        return await asyncio.get_running_loop().run_in_executor(None, work)

    async def kv_peer_device_serve(self, hashes: list[int]) -> int:
        """Owner half of a device-collective peer pull (docs/39): join the
        cooperative transfer program as the source. Holds the engine lock
        for the whole collective — the gather reads kv_caches, and the
        puller's side donates its own; both step loops must be quiesced.
        Returns 0 (the source never adopts)."""
        def work():
            from .kv_device_transfer import pull_kv_device_crossproc

            with self._lock:
                return pull_kv_device_crossproc(
                    self.engine, True, list(hashes)
                )

        return await asyncio.get_running_loop().run_in_executor(None, work)

    async def kv_peer_replicate(self, owner: str, hashes: list[int]) -> int:
        """Proactive flash-crowd replication target half (docs/39): fetch
        `hashes` from `owner` over the HTTP peer path and adopt them
        parked. The fetch runs OFF the lock (seconds of wire time);
        only the adoption quiesces the step loop."""
        def work():
            return self.engine.kv_peer_replicate(owner, hashes)

        return await asyncio.get_running_loop().run_in_executor(None, work)

    async def kv_mark_replicated(self, hashes: list[int]) -> int:
        """Record that a peer now holds copies of `hashes` — the owner's
        eviction policies prefer replicated blocks as victims from here
        on (pool + host ring, docs/39)."""
        def work():
            with self._lock:
                return self.engine.scheduler.pool.mark_replicated(hashes)

        return await asyncio.get_running_loop().run_in_executor(None, work)

    async def kv_import(self, hashes, blocks, fingerprint="") -> int:
        def work():
            with self._lock:
                return self.engine.kv_import(hashes, blocks, fingerprint)

        return await asyncio.get_running_loop().run_in_executor(None, work)

    async def load_lora(self, name: str, path: str) -> None:
        def work():
            with self._lock:
                self.engine.load_lora(name, path)

        await asyncio.get_running_loop().run_in_executor(None, work)

    async def unload_lora(self, name: str) -> None:
        def work():
            with self._lock:
                self.engine.unload_lora(name)

        await asyncio.get_running_loop().run_in_executor(None, work)
