"""Flight recorder, thread-liveness watchdog, and crash postmortems
(docs/37-flight-recorder.md).

Every telemetry layer before this one observes *requests that make
progress*: the tracing spine explains one slow request, the goodput
ledger explains wasted tokens, the saturation meter explains an
under-full chip. A WEDGED engine — a collective that never completes, a
fetcher deadlocked under a tier lock, an XLA compile that never returns
— produces no requests and therefore no evidence. Production engines
(RTP-LLM, PAPERS.md) treat hang diagnosis as a serving-stack feature:
when the process stalls, the process itself should name the stuck thread
and dump what it was doing. Four pieces:

- :class:`FlightRecorder` — a bounded, lock-light ring of structured
  step records appended from the step loop (dispatch/resolve sequence,
  batch shape and phase, scheduler decision summary, queue/pool depths,
  rollback and fault markers). The last N records are the black box:
  what the engine was doing right before it stopped doing anything.

- :class:`ThreadRegistry` / :class:`Heartbeat` — every long-lived loop
  in the process beats a heartbeat: the step thread, the hydration
  fetcher, the KV-event publisher, the remote-KV writer, background
  compile jobs. ``beat()`` marks the loop alive-and-busy; ``idle()``
  marks it parked waiting for work (an idle loop is never stale). Ages
  are computed by READERS (exporter, watchdog) from the beat stamps, so
  a dead loop cannot fake freshness.

- :class:`Watchdog` — one daemon thread that turns silence into signal:
  a busy heartbeat older than its threshold, or a device step dispatched
  and never resolved, starts a stall EPISODE — one structured report
  (thread stacks + the last flight records), one counter bump per kind
  (``tpu:engine_step_stalls_total``), one postmortem dump, and /ready
  flips 503 (never /health: restarting a wedged engine is an operator
  decision, not a kubelet reflex) until the stall clears.

- :func:`write_postmortem` / :class:`PostmortemDumper` — a redacted JSON
  black box (flight ring, heartbeat table, thread stacks, config
  fingerprint, timing/hydration snapshots, env) written to
  ``--postmortem-dir`` on watchdog trip, SIGQUIT, and fatal step-thread
  exceptions; served live at ``GET /debug/flight`` and on demand via
  ``POST /debug/postmortem``.

:class:`EventLoopLagProbe` rides along for the asyncio processes (router
and KV controller): a starved event loop serves nothing while every
request-vantage metric just goes quiet —
``tpu:router_event_loop_lag_seconds`` is the decaying peak of how far a
short sleep overshot its deadline.
"""

from __future__ import annotations

import itertools
import json
import math
import os
import re
import sys
import threading
import time
import traceback
from collections import deque

from .. import metrics_contract as mc
from ..utils.logging import init_logger

logger = init_logger(__name__)

# closed label sets — the contract owns them; this module records against
# them (a thread name outside THREAD_NAMES raises at registration, so the
# exporter's seeded cardinality can never be exceeded)
THREAD_NAMES = mc.THREAD_NAME_VALUES
STALL_KINDS = mc.STALL_KIND_VALUES

DEFAULT_CAPACITY = 512
DEFAULT_WATCH_INTERVAL_S = 1.0
DEFAULT_STALL_AFTER_S = 120.0
# background XLA compiles legitimately run for minutes (plus up to the
# 10-minute idle gate); only a compile past this is the "compiles
# forever" wedge
DEFAULT_BG_COMPILE_STALL_S = 900.0


# -- the step timeline ---------------------------------------------------
# Phases of the step thread, in the order one loop iteration meets them.
# Each is a `step:<phase>` TraceAnnotation in an xprof capture, a cumulative
# `<phase>_s` in /debug/timing and (ring on) a stamp on the flight record.
# A stable, documented list (docs/14-tracing.md), like the kernels' names.
PHASES = (
    "admit",  # engine lock + _drain_pending (queued submissions -> scheduler)
    "sched",  # Scheduler.schedule() and what it terminated on the way
    "build",  # host arrays of one dispatch: tokens, block tables, masks
    "h2d",  # the device_put calls of that dispatch
    "enqueue",  # the jitted call (a first-seen shape compiles here)
    "sync",  # blocked in the step's one device_get
    "post",  # rows -> scheduler.postprocess, metering, detokenize, outputs
    "emit",  # handing the outputs to the asyncio side
    "idle",  # nothing to run: parked on the wake event
)
_SPAN_NAMES = {p: "step:" + p for p in PHASES}  # nothing formatted per span
DISPATCH_PHASES = ("build", "h2d", "enqueue")  # dispatch_s is their sum
# the step thread's time outside `sync` and `idle` (turn_s is their sum): the
# host's turn, which has to fit inside the program in flight
TURN_PHASES = ("admit", "sched", *DISPATCH_PHASES, "post", "emit")
# what emptied the device's queue before a starvation gap
GAP_AFTER = ("start", "prefill", "decode", "verify")
# the kind of the program that ran dry before a dry gap
DRY_AFTER = GAP_AFTER[1:]

_TRACE_ANNOTATION = None


def _trace_annotation():
    """jax.profiler.TraceAnnotation, resolved on the first span: this module
    is imported by the router and the KV controller, which never import JAX.
    Without a live capture one annotation costs well under a microsecond and
    its name and arguments are never formatted."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation as cls
        except ImportError:  # a jax-free process still gets the totals
            import contextlib

            def cls(name, **kwargs):
                return contextlib.nullcontext()
        _TRACE_ANNOTATION = cls
    return _TRACE_ANNOTATION


class _Span:
    """The one phase boundary (FlightRecorder.span / .wall). A phase span
    owns the step thread's clock until it exits or an inner phase takes
    over, so the phases partition the thread's time: an outer span is
    credited only with its own slices. A wall span (phase None) adds its
    whole length to one key and takes part in nothing else."""

    __slots__ = ("rec", "phase", "key", "seq", "kind", "t0", "t_run",
                 "outer", "ann")

    def __init__(self, rec, phase, key, seq, kind):
        self.rec, self.phase, self.key = rec, phase, key
        self.seq, self.kind = seq, kind

    def __enter__(self):
        rec = self.rec
        self.t0 = self.t_run = now = time.monotonic_ns()
        if self.phase is None:
            return self
        if self.seq is None:
            self.seq = rec._seq + 1  # the dispatch this leads to
        self.outer = rec._cur
        if self.outer is not None:
            self.outer._credit(now)
        rec._cur = self
        if rec._watch is not None:
            rec._poll(now)
        self.ann = _trace_annotation()(
            self.key, seq=self.seq, kind=self.kind)
        self.ann.__enter__()
        return self

    def _credit(self, now: int) -> None:
        """Close this span's running slice at `now`."""
        rec = self.rec
        d = now - self.t_run
        self.t_run = now
        rec._tot[self.phase + "_s"] += d
        if rec._gap_after is not None:  # a starvation gap or a dry gap
            rec._tot[rec._gap_key + self.phase + "_s"] += d
            rec._gap[self.phase] = rec._gap.get(self.phase, 0) + d

    def __exit__(self, *exc):
        now = time.monotonic_ns()
        rec = self.rec
        if self.phase is None:
            rec._tot[self.key] += now - self.t0
            return False
        self.ann.__exit__(*exc)
        self._credit(now)
        rec._cur = self.outer
        if self.outer is not None:
            self.outer.t_run = now
        rec._stamps.append((self.phase, self.t0, now, self.seq))
        # the `sync` of the watched step itself ends BECAUSE the program
        # completed: the resolve that follows knows the instant
        if rec._watch is not None and (
                self.phase != "sync" or self.seq != rec._watch_seq):
            rec._poll(now)
        return False


class FlightRecorder:
    """Bounded ring of structured step records (the black box), and the
    step thread's timeline: phase spans, cumulative totals, and the
    device-starvation accounting (docs/37-flight-recorder.md).

    Appended from the step thread; snapshotted by the watchdog, the
    postmortem dumper, and GET /debug/flight. One small lock guards the
    ring (an append is a dict build + deque append — microseconds against
    a millisecond-scale step). The dispatch/resolve cursor is what the
    watchdog's unresolved-step detection reads; the totals and the
    starvation counters are what /debug/timing serves.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = capacity
        self._lock = threading.Lock()
        self._ring: deque[dict] = deque(maxlen=capacity)
        self.records_total = 0
        # the cursor. Programs run in order on the device, so a resolve of
        # seq n completes every seq <= n; `_enq == _done` means the device's
        # queue is empty from the host's side. `_open` holds the dispatches
        # the host still expects an answer from (seq -> (monotonic time,
        # kind)): a rolled-back step leaves it at once (it is no stall
        # candidate) but stays enqueued until a later step resolves.
        self._seq = 0  # == highest seq enqueued
        self._done = 0  # highest seq known complete
        self._open: dict[int, tuple[float, str]] = {}
        self._discarded_kind = "decode"
        # -- timeline (step thread only; readers see whole ints) ----------
        self._t_origin = time.monotonic_ns()
        # nanoseconds under `*_s` keys, counts under the others; timing()
        # serves seconds. Every key /debug/timing ever had lives here.
        self._tot: dict[str, int] = dict.fromkeys((
            *(p + "_s" for p in PHASES),
            "prefill_s", "prefill_n", "prefill_chained_n",
            "prefill_follow_n", "round_chunks_max", "seat_held_by_pool_n",
            "prefill_kernel_n", "prefill_tokens", "prefill_positions",
            "kv_write_pages_n", "kv_write_read_pages_n",
            "decode_s", "decode_n", "decode_tokens",
            "overlap_s", "step_wall_s", "rollback_n", "steps",
            "dispatch_n", "starved_n",
            *("starved_" + p + "_s" for p in PHASES),
            *("starved_after_" + k + "_n" for k in GAP_AFTER),
            "dry_n", "dry_slack_s", "dry_poll_n", "dry_poll_s",
            *("dry_" + p + "_s" for p in PHASES),
            *("dry_after_" + k + "_n" for k in DRY_AFTER),
        ), 0)
        self._cur: _Span | None = None
        # a gap is open while `_gap_after` names the program before it: a
        # starvation gap (`_gap_key` "starved_": the host's own resolve
        # emptied the device's queue) or a dry gap ("dry_": a poll saw the
        # newest enqueued program complete behind an unresolved step). Never
        # both; `_gap` splits it over the phases run inside
        self._gap_after: str | None = "start"
        self._gap_key = "starved_"
        self._gap: dict[str, int] = {}
        # a dry gap's bracket: its first true poll - the poll before it
        self._gap_slack = 0
        # the newest enqueued program's zero-argument `ready` (None: nothing
        # watched, or already seen ready), its seq and kind, and when it was
        # last known to be running (its enqueue's return, then each poll)
        self._watch = None
        self._watch_seq = 0
        self._watch_kind = ""
        self._poll_t = 0
        # phase stamps since the last ring record (bounded: an idle loop
        # spins admit/idle with no record to carry them)
        self._stamps: deque[tuple] = deque(maxlen=64)

    # -- the timeline (step thread) ----------------------------------------

    def span(self, phase: str, seq: int | None = None, kind: str = ""):
        """``with rec.span("sched"):`` — one phase of PHASES. `seq` names
        the dispatch that caused it (default: the next one)."""
        return _Span(self, phase, _SPAN_NAMES[phase], seq, kind)

    def wall(self, key: str):
        """``with rec.wall("step_wall_s"):`` — a region's whole length
        added to one cumulative key (regions overlap phases freely)."""
        return _Span(self, None, key, 0, "")

    def count(self, key: str, n: int = 1) -> None:
        """Add to a cumulative count. A key outside the fixed set (an MoE
        model's `moe_*_n`) appears in timing() once it has been counted: a
        dense model serves none of them."""
        self._tot[key] = self._tot.get(key, 0) + n

    def peak(self, key: str, n: int) -> None:
        """Keep the largest `n` seen under `key` (a running maximum: the
        one kind of total a reader does not subtract)."""
        if n > self._tot.get(key, 0):
            self._tot[key] = n

    @property
    def done_seq(self) -> int:
        """Highest dispatch seq known complete on the device."""
        return self._done

    def _open_gap(self, now: int, after: str, key: str = "starved_",
                  slack: int = 0) -> None:
        """Open a gap at `now` (`after`: what ran before it), splitting the
        running phase at the edge. A gap that is open stays as it is."""
        if self._gap_after is not None:
            return
        if self._cur is not None:
            self._cur._credit(now)
        self._gap_after, self._gap_key, self._gap_slack = after, key, slack

    def _close_gap(self, now: int, counted: bool = True) -> dict | None:
        """Close the open gap at `now` and return it for the flight record.
        `counted`: a dispatch ended it — the `_n` counters count dispatches
        that came too late, once each."""
        after = self._gap_after
        if after is None:
            return None
        if self._cur is not None:
            self._cur._credit(now)
        key, tot = self._gap_key, self._tot
        dry = key == "dry_"
        if counted:
            tot[key + "n"] += 1
            tot[key + "after_" + after + "_n"] += 1
        closed = {
            "seen": "poll" if dry else "resolve", "after": after,
            "ms": round(sum(self._gap.values()) * 1e-6, 3),
            "phases_ms": {p: round(d * 1e-6, 3)
                          for p, d in self._gap.items()},
        }
        if dry:
            tot["dry_slack_s"] += self._gap_slack
            closed["slack_ms"] = round(self._gap_slack * 1e-6, 3)
        self._gap = {}
        self._gap_after = None
        return closed

    def _poll(self, now: int) -> None:
        """Ask the watched program whether it is complete (callers test
        `_watch` first). The first poll that says yes opens a dry gap at
        `now` — the device has nothing to run behind an unresolved step —
        and ends the polling; the poll before it is the other end of the
        bracket that holds the true instant."""
        tot = self._tot
        t0 = time.monotonic_ns()
        try:
            done = self._watch()
        except RuntimeError:  # a deleted array: nothing left to watch
            self._watch = None
            return
        tot["dry_poll_s"] += time.monotonic_ns() - t0
        tot["dry_poll_n"] += 1
        if not done:
            self._poll_t = now
            return
        self._watch = None
        slack = now - self._poll_t
        self._open_gap(now, self._watch_kind, "dry_", slack)
        # the host's observation on the profiler's clock, beside the
        # program's true end on the device's line
        with _trace_annotation()(
                "dev:dry", seq=self._watch_seq, slack_ns=slack):
            pass

    def poll(self) -> None:
        """One more poll between two phase boundaries, for a phase long
        enough to leave the bracket wide."""
        if self._watch is not None:
            self._poll(time.monotonic_ns())

    def timing(self) -> dict:
        """The cumulative totals as /debug/timing serves them (seconds and
        counts; monotonic — readers subtract two snapshots). `dispatch_s`,
        `turn_s`, `starved_s` and `dry_s` are sums of their parts; `clock_s`
        is the step thread's clock at this snapshot, the denominator of a
        share."""
        tot = self._tot
        out = {k: (v * 1e-9 if k.endswith("_s") else v)
               for k, v in tot.items()}
        out["dispatch_s"] = sum(tot[p + "_s"] for p in DISPATCH_PHASES) * 1e-9
        out["turn_s"] = sum(tot[p + "_s"] for p in TURN_PHASES) * 1e-9
        for key in ("starved_", "dry_"):
            out[key + "s"] = sum(tot[key + p + "_s"] for p in PHASES) * 1e-9
        out["clock_s"] = (time.monotonic_ns() - self._t_origin) * 1e-9
        return out

    @staticmethod
    def clock_pair() -> dict:
        """One reading of both host clocks, also left in a live xprof
        capture as a `clock_pair` event that carries them: the flight
        records and the benchmark client (monotonic), the request spine
        (wall clock) and the capture (its own zero) can then be laid on one
        axis offline."""
        pair = {"monotonic_ns": time.monotonic_ns(),
                "time_ns": time.time_ns()}
        with _trace_annotation()("clock_pair", **pair):
            pass
        return pair

    # -- step-loop recording (step thread) ---------------------------------

    def _append(self, event: str, fields: dict) -> None:
        fields["event"] = event
        fields["t"] = time.time()
        fields["mono_ns"] = time.monotonic_ns()
        with self._lock:
            self._ring.append(fields)
            self.records_total += 1

    def _take_stamps(self) -> list:
        """[phase, start, end (monotonic ns), seq] of every phase span that
        ended since the last dispatch/resolve record."""
        stamps = list(self._stamps)
        self._stamps.clear()
        return stamps

    def dispatch(
        self, kind: str, rows: int, tokens: int,
        waiting: int = 0, running: int = 0, pool_usage: float = 0.0,
        window: int = 0, ready=None,
    ) -> int:
        """One device dispatch (decode window / verify / prefill chunk),
        called when its enqueue has returned: the device has work again, so
        an open gap — starvation or dry — ends here. `ready`, a zero-argument
        callable that says whether this program is complete, is what the
        polls at the phase boundaries watch from here on (the newest
        enqueued program only). Returns the dispatch seq the matching
        resolve()/discard() names."""
        now = time.monotonic_ns()
        if self._watch is not None:
            self._poll(now)  # seen only now: a dry gap of length 0
        with self._lock:
            self._seq += 1
            seq = self._seq
            self._open[seq] = (now * 1e-9, kind)
        self._tot["dispatch_n"] += 1
        gap = self._close_gap(now)
        self._watch, self._watch_seq, self._watch_kind = ready, seq, kind
        self._poll_t = now
        self._append("dispatch", {
            "seq": seq, "kind": kind, "rows": rows, "tokens": tokens,
            "window": window, "waiting": waiting, "running": running,
            "pool_usage": round(pool_usage, 4),
            "gap": gap, "phases": self._take_stamps(),
        })
        return seq

    def resolve(self, seq: int, passes: int | None = None) -> None:
        """The dispatch's results were synced to the host — it, and every
        earlier one, is no longer a stall candidate. If nothing later is
        enqueued the device starves from here on. `passes`: the forward
        passes a window of block passes ran (generation by diffusion over
        blocks: data, known only now), kept in the record."""
        now = time.monotonic_ns()
        with self._lock:
            kind = self._open.get(seq, (0.0, "start"))[1]
            for s in [s for s in self._open if s <= seq]:
                del self._open[s]
            self._done = max(self._done, seq)
            emptied = self._done >= self._seq
        fields = {"seq": seq, "phases": self._take_stamps()}
        if passes is not None:
            fields["passes"] = passes
        if emptied:
            # the watched step itself, nothing enqueued behind it: the host
            # knows this instant, so a dry gap hands over to a starvation gap
            closed = self._empty(now, kind)
            if closed is not None:
                fields["gap"] = closed
        self._append("resolve", fields)

    def discard(self, seq: int) -> None:
        """A dispatched pipeline step was rolled back (speculation
        invalidated) — discarded work is resolved work for liveness. The
        device still runs it, so its queue is not empty: it counts as
        complete when a later step resolves (or the loop goes quiet)."""
        with self._lock:
            gone = self._open.pop(seq, None)
        if gone is not None:
            self._discarded_kind = gone[1]
        self._append("rollback", {"seq": seq})

    def quiet(self) -> None:
        """The loop found nothing to run. What is still enqueued was
        rolled back and nobody will resolve it: count the queue as empty
        from here (early by at most that one step)."""
        with self._lock:
            if self._open or self._done >= self._seq:
                return
            self._done = self._seq
        self._empty(time.monotonic_ns(), self._discarded_kind)

    def _empty(self, now: int, after: str) -> dict | None:
        """The host counts the device's queue as empty from `now`: nothing
        is watched any more, an open dry gap closes (and is returned) and
        the starvation gap opens in its place — one stretch of an empty
        device, counted once, by `starved_n`, at the dispatch that ends it."""
        self._watch = None
        closed = None
        if self._gap_key == "dry_":
            closed = self._close_gap(now, counted=False)
        self._open_gap(now, after)
        return closed

    def fault(self, message: str) -> None:
        """A step-loop exception (transient or fatal)."""
        with self._lock:
            self._open.clear()  # the step loop abandoned it
            self._done = self._seq
        self._empty(time.monotonic_ns(), "start")
        self._append("fault", {"message": str(message)[:500]})

    def note(self, event: str, **fields) -> None:
        """Off-hot-path markers (watchdog stall/recovery, drain, ...)."""
        self._append(event, dict(fields))

    # -- reading (watchdog / exporter / debug) -----------------------------

    def outstanding_age_s(self) -> tuple[float, str] | None:
        """(seconds since dispatch, kind) of the oldest unresolved device
        step, or None when nothing is in flight."""
        with self._lock:
            out = self._open[min(self._open)] if self._open else None
        if out is None:
            return None
        return time.monotonic() - out[0], out[1]

    def snapshot(self, last: int | None = None) -> list[dict]:
        with self._lock:
            records = list(self._ring)
        if last is not None:
            records = records[-last:]
        return records


class Heartbeat:
    """One long-lived loop's liveness stamp. ``beat()`` = alive and busy;
    ``idle()`` = parked waiting for work (never stale). Readers compute
    age from the stamps — the loop itself never reports an age."""

    __slots__ = ("name", "stall_after_s", "explicit_threshold",
                 "_last_beat", "_busy", "beats")

    def __init__(self, name: str, stall_after_s: float,
                 explicit_threshold: bool = True):
        self.name = name
        self.stall_after_s = stall_after_s
        # loops registered WITHOUT their own threshold follow the
        # registry default (the --watchdog-stall-s knob); explicit ones
        # (bg_compile's generous compile budget, the publisher's
        # interval-derived bound) keep theirs
        self.explicit_threshold = explicit_threshold
        self._last_beat = time.monotonic()
        self._busy = False
        self.beats = 0

    def beat(self) -> None:
        self._last_beat = time.monotonic()
        self._busy = True
        self.beats += 1

    def idle(self) -> None:
        self._last_beat = time.monotonic()
        self._busy = False

    def age_s(self) -> float:
        return time.monotonic() - self._last_beat

    @property
    def busy(self) -> bool:
        return self._busy

    def stale(self) -> bool:
        return self._busy and self.age_s() > self.stall_after_s

    def snapshot(self) -> dict:
        return {
            "thread": self.name,
            "age_s": round(self.age_s(), 3),
            "busy": self._busy,
            "stall_after_s": self.stall_after_s,
            "beats": self.beats,
            "stale": self.stale(),
        }


class ThreadRegistry:
    """Where every long-lived loop registers and beats. Names come from
    the CLOSED contract set (metrics_contract.THREAD_NAME_VALUES) so the
    heartbeat-age gauge's cardinality is bounded by construction — an
    unknown name raises at registration, not at scrape."""

    def __init__(self, default_stall_after_s: float = DEFAULT_STALL_AFTER_S):
        self._lock = threading.Lock()
        self._beats: dict[str, Heartbeat] = {}
        self.default_stall_after_s = default_stall_after_s

    def register(
        self, name: str, stall_after_s: float | None = None
    ) -> Heartbeat:
        """Idempotent: re-registering a name (restartable loops) refreshes
        the existing heartbeat rather than minting a second one.
        ``stall_after_s=None`` follows the registry default (the
        --watchdog-stall-s knob, adjustable after registration via
        :meth:`set_default_stall_after_s`)."""
        if name not in THREAD_NAMES:
            raise ValueError(
                f"thread name {name!r} is not in the closed contract set "
                f"{THREAD_NAMES}"
            )
        explicit = stall_after_s is not None
        threshold = (
            stall_after_s if explicit else self.default_stall_after_s
        )
        with self._lock:
            hb = self._beats.get(name)
            if hb is None:
                hb = self._beats[name] = Heartbeat(
                    name, threshold, explicit_threshold=explicit
                )
            else:
                hb.stall_after_s = threshold
                hb.explicit_threshold = explicit
                hb.idle()  # a restarting loop starts fresh, not stale
            return hb

    def set_default_stall_after_s(self, stall_after_s: float) -> None:
        """Apply a new default threshold (the --watchdog-stall-s knob,
        parsed AFTER the engine registered its loops) to every heartbeat
        that did not pick its own."""
        with self._lock:
            self.default_stall_after_s = stall_after_s
            for hb in self._beats.values():
                if not hb.explicit_threshold:
                    hb.stall_after_s = stall_after_s

    def unregister(self, name: str) -> None:
        """A loop that stops ON PURPOSE (drain stopped the publisher)
        leaves the table — a deliberate stop must not read as a wedge."""
        with self._lock:
            self._beats.pop(name, None)

    def ages(self) -> dict[str, float]:
        """thread → seconds since last beat, for every registered loop."""
        with self._lock:
            beats = list(self._beats.values())
        return {hb.name: hb.age_s() for hb in beats}

    def stale(self) -> list[Heartbeat]:
        with self._lock:
            beats = list(self._beats.values())
        return [hb for hb in beats if hb.stale()]

    def snapshot(self) -> dict[str, dict]:
        with self._lock:
            beats = list(self._beats.values())
        return {hb.name: hb.snapshot() for hb in beats}


def thread_stacks() -> dict[str, list[str]]:
    """Current stack of every live thread, keyed by thread name — the
    faulthandler view as capturable strings (faulthandler itself can only
    write to a real file descriptor)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    stacks: dict[str, list[str]] = {}
    for ident, frame in sys._current_frames().items():
        name = names.get(ident, f"thread-{ident}")
        stacks[name] = [
            line.rstrip("\n")
            for line in traceback.format_stack(frame)
        ]
    return stacks


# -- postmortem dumps --------------------------------------------------------

# keys whose VALUES are secrets wherever they appear in a postmortem doc
# (tenant api keys, subscriber bearer keys, HF tokens, auth headers)
_REDACT_KEY_RE = re.compile(
    r"(api[-_]?key|authorization|auth|token|secret|password|bearer|"
    r"credential)", re.IGNORECASE,
)
# env vars worth carrying in a wedge postmortem (values of matching
# _REDACT_KEY_RE names are redacted even here)
_ENV_PREFIXES = (
    "JAX_", "TPU_", "XLA_", "LIBTPU", "KV_", "POD_", "ENGINE_",
    "PREFLIGHT_",
)


def redact(obj):
    """Recursively mask values under secret-shaped keys. Applied to the
    WHOLE postmortem doc right before serialization, so no section can
    leak a tenant key by forgetting to scrub its own fields."""
    if isinstance(obj, dict):
        return {
            k: ("[redacted]" if isinstance(k, str) and _REDACT_KEY_RE.search(k)
                else redact(v))
            for k, v in obj.items()
        }
    if isinstance(obj, (list, tuple)):
        return [redact(v) for v in obj]
    return obj


# disambiguates same-second dump filenames within one process
_DUMP_COUNTER = itertools.count()


def _captured_env() -> dict[str, str]:
    return {
        k: v for k, v in sorted(os.environ.items())
        if k.startswith(_ENV_PREFIXES)
    }


def build_postmortem(
    trigger: str,
    reason: str,
    recorder: FlightRecorder | None = None,
    registry: ThreadRegistry | None = None,
    sections: dict | None = None,
) -> dict:
    """The redacted black-box document. `sections` carries caller-provided
    context (config fingerprint, /debug/timing + /debug/hydration
    snapshots, watchdog state) — everything is redacted together."""
    doc: dict = {
        "postmortem": True,
        "trigger": trigger,
        "reason": reason,
        "written_at": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "pid": os.getpid(),
        "threads": thread_stacks(),
        "env": _captured_env(),
    }
    if recorder is not None:
        doc["flight"] = recorder.snapshot()
        out = recorder.outstanding_age_s()
        if out is not None:
            doc["outstanding_step"] = {
                "age_s": round(out[0], 3), "kind": out[1],
            }
    if registry is not None:
        doc["heartbeats"] = registry.snapshot()
    for key, value in (sections or {}).items():
        doc[key] = value
    return redact(doc)


def write_postmortem(
    out_dir: str,
    trigger: str,
    reason: str,
    recorder: FlightRecorder | None = None,
    registry: ThreadRegistry | None = None,
    sections: dict | None = None,
) -> tuple[str, dict]:
    """Build + write one postmortem JSON file; returns (path, doc).
    Filenames carry the trigger and a wall timestamp so repeated wedges
    never overwrite each other."""
    doc = build_postmortem(trigger, reason, recorder, registry, sections)
    os.makedirs(out_dir, exist_ok=True)
    # pid + a process-wide monotonic counter: two dumps landing in the
    # same SECOND (a watchdog episode racing a SIGQUIT, two wedges in one
    # bench run) must not overwrite each other's evidence
    fname = "postmortem-{}-{}-{}-{}.json".format(
        trigger, time.strftime("%Y%m%dT%H%M%S"), os.getpid(),
        next(_DUMP_COUNTER),
    )
    path = os.path.join(out_dir, fname)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    os.replace(tmp, path)  # a reader never sees a torn dump
    return path, doc


class PostmortemDumper:
    """The engine server's dump trigger: one place that knows the output
    dir, the recorder/registry, and the extra context callables (config,
    timing, hydration). ``out_dir=''`` disables files — build() still
    serves POST /debug/postmortem inline."""

    def __init__(
        self,
        out_dir: str = "",
        recorder: FlightRecorder | None = None,
        registry: ThreadRegistry | None = None,
        context_fn=None,
    ):
        self.out_dir = out_dir
        self.recorder = recorder
        self.registry = registry
        # zero-arg callable -> dict of extra sections, evaluated at dump
        # time (config fingerprint, timing, hydration snapshots)
        self.context_fn = context_fn
        self.dumps_written = 0
        self.last_path: str | None = None

    def _sections(self) -> dict:
        if self.context_fn is None:
            return {}
        try:
            return dict(self.context_fn())
        except Exception as e:  # a broken context must not lose the dump
            return {"context_error": f"{type(e).__name__}: {e}"}

    def build(self, trigger: str, reason: str) -> dict:
        return build_postmortem(
            trigger, reason, self.recorder, self.registry, self._sections()
        )

    def dump(self, trigger: str, reason: str) -> tuple[str | None, dict]:
        """Write (when a dir is configured) and return (path, doc). Never
        raises: the dumper runs on dying threads and signal handlers."""
        try:
            if not self.out_dir:
                return None, self.build(trigger, reason)
            path, doc = write_postmortem(
                self.out_dir, trigger, reason,
                self.recorder, self.registry, self._sections(),
            )
            self.dumps_written += 1
            self.last_path = path
            logger.error("postmortem (%s) written to %s", trigger, path)
            return path, doc
        except Exception:
            logger.exception("postmortem dump (%s) failed", trigger)
            return None, {"postmortem": False, "trigger": trigger}


class Watchdog:
    """The thread that turns silence into signal.

    Every ``interval_s`` it checks (a) each registered heartbeat's
    staleness and (b) the flight recorder's outstanding device step. A
    transition from clear to stalled starts one EPISODE: one structured
    stall report in the log (stacks + last flight records), one counter
    bump per kind, one ``on_stall`` callback (the server hooks the
    postmortem dumper there). While stalled, ``stalled`` is the live
    report the /ready handler 503s with — liveness (/health) is NEVER
    flipped: k8s restarting a wedged engine destroys the evidence this
    module exists to capture, and the operator may prefer a /debug/flight
    look first.
    """

    def __init__(
        self,
        registry: ThreadRegistry,
        recorder: FlightRecorder | None = None,
        interval_s: float = DEFAULT_WATCH_INTERVAL_S,
        stall_after_s: float = DEFAULT_STALL_AFTER_S,
        on_stall=None,
    ):
        self.registry = registry
        self.recorder = recorder
        self.interval_s = interval_s
        # default threshold for the unresolved-step check (heartbeats
        # carry their own per-loop thresholds)
        self.stall_after_s = stall_after_s
        self.on_stall = on_stall  # callable(report: dict), once per episode
        self.stall_counts: dict[str, int] = {k: 0 for k in STALL_KINDS}
        self.stall_episodes = 0
        self.stalled: dict | None = None  # live report while stalled
        self._hb = registry.register(
            "watchdog", stall_after_s=max(10.0, 10 * interval_s)
        )
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        # threads/kinds already counted in the CURRENT episode — a wedge
        # that persists for minutes is one trip per (kind, thread), not
        # one per check round
        self._episode_keys: set[tuple[str, str]] = set()

    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="engine-watchdog", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=max(2.0, 2 * self.interval_s))
            self._thread = None
        self.registry.unregister("watchdog")

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self._hb.beat()
                self.check()
            except Exception:  # the monitor must outlive its own bugs
                logger.exception("watchdog check failed")

    def check(self) -> dict | None:
        """One detection round (factored out so tests drive it without
        the thread). Returns the current stall report or None."""
        findings: list[dict] = []
        for hb in self.registry.stale():
            if hb.name == "watchdog":
                continue  # self-staleness is for the exporter to surface
            findings.append({
                "kind": "stale_heartbeat",
                "thread": hb.name,
                "age_s": round(hb.age_s(), 3),
                "stall_after_s": hb.stall_after_s,
            })
        if self.recorder is not None:
            out = self.recorder.outstanding_age_s()
            if out is not None and out[0] > self.stall_after_s:
                findings.append({
                    "kind": "unresolved_step",
                    "thread": "step",
                    "age_s": round(out[0], 3),
                    "dispatch_kind": out[1],
                    "stall_after_s": self.stall_after_s,
                })
        if not findings:
            if self.stalled is not None:
                logger.warning(
                    "watchdog: stall cleared after %d finding(s)",
                    len(self._episode_keys),
                )
                if self.recorder is not None:
                    self.recorder.note("stall_cleared")
            self.stalled = None
            self._episode_keys.clear()
            return None
        new = [
            f for f in findings
            if (f["kind"], f["thread"]) not in self._episode_keys
        ]
        self.stalled = {
            "since": self.stalled["since"] if self.stalled else time.time(),
            "findings": findings,
        }
        if new:
            if not self._episode_keys:
                self.stall_episodes += 1
            for f in new:
                self._episode_keys.add((f["kind"], f["thread"]))
                self.stall_counts[f["kind"]] += 1
            self._report(new)
        return self.stalled

    def _report(self, findings: list[dict]) -> None:
        """ONE structured stall report per new finding set: the named
        threads, their stacks, and the last flight records — the log line
        an operator greps for when the bench goes dark."""
        names = ", ".join(
            f"{f['thread']} ({f['kind']}, {f['age_s']:.1f}s)"
            for f in findings
        )
        stacks = thread_stacks()
        tail = (
            self.recorder.snapshot(last=16)
            if self.recorder is not None else []
        )
        logger.error(
            "watchdog: engine stalled — %s\nstall report: %s",
            names,
            json.dumps(redact({
                "findings": findings,
                "heartbeats": self.registry.snapshot(),
                "threads": stacks,
                "flight_tail": tail,
            }), indent=1),
        )
        if self.recorder is not None:
            self.recorder.note("stall", findings=findings)
        if self.on_stall is not None:
            try:
                self.on_stall({"findings": findings})
            except Exception:
                logger.exception("watchdog on_stall callback failed")

    def snapshot(self) -> dict:
        return {
            "interval_s": self.interval_s,
            "stall_after_s": self.stall_after_s,
            "stalled": self.stalled,
            "episodes": self.stall_episodes,
            "counts": dict(self.stall_counts),
        }


class EventLoopLagProbe:
    """Asyncio event-loop starvation probe (router / KV controller).

    Sleeps ``interval_s`` in a loop and measures how far each wakeup
    overshot its deadline; ``lag_s`` is a decaying peak (τ ~30s), so a
    scrape every 15s still sees a one-off 2s stall instead of whatever
    the last healthy tick read. A loop blocked OUTSIDE await (sync I/O,
    a giant json.loads — the tpulint async-blocking bug class, live) is
    exactly what inflates it."""

    _DECAY_TAU_S = 30.0

    def __init__(self, interval_s: float = 0.5):
        self.interval_s = interval_s
        self.last_lag_s = 0.0
        self.lag_s = 0.0  # decaying peak — the exported gauge
        self.ticks = 0
        self._task = None
        self._peak_t = time.monotonic()

    def _observe(self, lag: float) -> None:
        now = time.monotonic()
        decayed = self.lag_s * math.exp(
            -(now - self._peak_t) / self._DECAY_TAU_S
        )
        self.last_lag_s = lag
        self.lag_s = max(lag, decayed)
        self._peak_t = now
        self.ticks += 1

    async def _run(self) -> None:
        import asyncio

        while True:
            t0 = time.monotonic()
            await asyncio.sleep(self.interval_s)
            self._observe(max(0.0, time.monotonic() - t0 - self.interval_s))

    def start(self) -> None:
        import asyncio

        if self._task is None or self._task.done():
            self._task = asyncio.get_running_loop().create_task(self._run())

    async def stop(self) -> None:
        import asyncio

        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None

    def snapshot(self) -> dict:
        return {
            "interval_s": self.interval_s,
            "lag_s": round(self.lag_s, 6),
            "last_lag_s": round(self.last_lag_s, 6),
            "ticks": self.ticks,
        }
