"""XLA compile telemetry (docs/42-compile-telemetry.md).

The pad-up program cache (model_runner) exists so that serving never
stalls on a mid-traffic XLA compile — but until now nothing *watched*
whether that guarantee held in production. A shape that escapes the
bucket ladder freezes every decode stream for the compile wall and
reads as an anonymous latency spike. ``CompileWatch`` is the missing
observer: every program build lands here with its cache key, wall
time, and trigger class, and flows out three ways —

* a bounded program inventory served at ``GET /debug/programs``
  (key, compile wall, dispatch count, last-used age, HBM footprint
  from ``compiled.memory_analysis()`` where the backend provides it);
* the flight recorder ring and the blocked request's trace timeline
  (``compile_stall`` events name the request a sync compile blocked);
* contract series ``tpu:engine_compiles_total{phase,trigger}``,
  ``tpu:engine_compile_seconds``, the program-cache gauge and
  hit/miss counters, and ``tpu:engine_compile_storms_total``.

Trigger classes:

* ``warmup`` — builds during ``engine.warmup()`` / ``precompile_
  dominating()`` (fallback disabled, or explicitly tagged). Expected.
* ``bg`` — the background AOT thread absorbing a pad-up fallback.
  Expected; never blocks a request.
* ``mid_traffic`` — a synchronous compile on the dispatch path with
  fallbacks enabled: a shape no compiled program dominates. This is
  the failure the bucket ladder exists to prevent; each one stalls
  the batch it was dispatched for.

The recompile-storm detector follows the watchdog-episode idiom
(flightrec.Watchdog): a sliding window over mid-traffic builds,
edge-triggered — crossing the threshold emits ONE structured report
naming the offending shapes and bumps the storm counter once; the
episode re-arms only after the window drains below threshold.
"""

from __future__ import annotations

import json
import logging
import threading
import time
from collections import OrderedDict, deque

from .flightrec import redact

logger = logging.getLogger(__name__)

# inventory entries kept; FIFO-evicted beyond this. 256 programs is ~4x
# a full warmup lattice (bucket ladder x variants x grammar keys) — a
# healthy engine never evicts; an evicting inventory is itself a signal
DEFAULT_CAPACITY = 256

# compile-wall observations buffered between stats() drains (the
# grammar_build_times idiom in engine.metrics): bounded so a scrape
# outage cannot grow the list without limit
_MAX_PENDING_WALLS = 1024

# storm-report shape list cap — a pathological storm names the top
# offenders, not an unbounded dump
_REPORT_SHAPE_CAP = 16

PHASES = ("prefill", "decode", "verify", "grammar")
TRIGGERS = ("warmup", "bg", "mid_traffic")

# grammar-table builds are numpy-side (not XLA programs): they appear
# in the inventory and compile counters but never count toward the
# program-cache hit/miss ratio or the storm window
_STORM_PHASES = ("prefill", "decode", "verify")


class CompileWatch:
    """Thread-safe recorder for program builds and cache dispatches.

    One instance is shared by the target runner and (when spec decode
    runs a draft model) the PR 14 draft runner — entries carry a
    ``role`` tag so ``/debug/programs`` tells the two caches apart.
    """

    def __init__(
        self,
        storm_threshold: int = 6,
        storm_window_s: float = 300.0,
        capacity: int = DEFAULT_CAPACITY,
        recorder=None,
        clock=time.monotonic,
    ):
        self.storm_threshold = int(storm_threshold)
        self.storm_window_s = float(storm_window_s)
        self.capacity = int(capacity)
        self.recorder = recorder  # FlightRecorder | None
        self._clock = clock  # injectable for window-arithmetic tests
        self._lock = threading.Lock()
        # (role, key_str) -> inventory entry dict; FIFO-bounded
        self._inventory: OrderedDict[tuple, dict] = OrderedDict()
        # "phase/trigger" -> monotonic count (exporter reads deltas)
        self.compiles: dict[str, int] = {}
        self._pending_walls: list[float] = []
        self.cache_hits = 0
        self.cache_misses = 0
        self.storms_total = 0
        # builds that raised (a background compile logs and drops its
        # failure; the count keeps it visible) + the most recent one
        self.failed_builds = 0
        self.last_failure: dict | None = None
        # sliding window of (t, shape_str) mid-traffic builds
        self._storm_events: deque = deque()
        self._in_storm = False  # edge flag: one report per episode
        self.last_storm_report: dict | None = None

    # -- writing (model_runner) -------------------------------------------

    def record_build(
        self,
        phase: str,
        key: tuple,
        wall_s: float,
        trigger: str,
        *,
        rid: str | None = None,
        role: str = "target",
        memory_bytes: int | None = None,
    ) -> None:
        """One program (or grammar-table) build landed."""
        now = self._clock()
        key_str = _key_str(key)
        with self._lock:
            entry = self._inventory.get((role, key_str))
            if entry is None:
                entry = {
                    "key": key_str,
                    "phase": phase,
                    "role": role,
                    "trigger": trigger,
                    "compile_wall_s": round(float(wall_s), 4),
                    "dispatches": 0,
                    "built_t": now,
                    "last_used_t": now,
                    "rid": rid,
                    "hbm_bytes": memory_bytes,
                }
                self._inventory[(role, key_str)] = entry
                while len(self._inventory) > self.capacity:
                    self._inventory.popitem(last=False)
            else:
                # re-build of a known key (cache dropped and re-filled):
                # keep the freshest wall/trigger, it is the live program
                entry.update(
                    trigger=trigger,
                    compile_wall_s=round(float(wall_s), 4),
                    rid=rid or entry.get("rid"),
                )
                if memory_bytes is not None:
                    entry["hbm_bytes"] = memory_bytes
            ck = f"{phase}/{trigger}"
            self.compiles[ck] = self.compiles.get(ck, 0) + 1
            if len(self._pending_walls) < _MAX_PENDING_WALLS:
                self._pending_walls.append(float(wall_s))
            storm_hit = (
                trigger == "mid_traffic" and phase in _STORM_PHASES
            )
            if storm_hit:
                self._storm_events.append((now, key_str))
        rec = self.recorder
        if rec is not None:
            if trigger == "mid_traffic":
                rec.note(
                    "compile_stall", phase=phase, key=key_str,
                    wall_ms=round(wall_s * 1000.0, 1), rid=rid, role=role,
                )
            else:
                rec.note(
                    "compile_build", phase=phase, key=key_str,
                    wall_ms=round(wall_s * 1000.0, 1), trigger=trigger,
                    role=role,
                )
        if storm_hit:
            self._check_storm(now)

    def record_failure(
        self, phase: str, key: tuple, trigger: str, exc: BaseException,
        *, role: str = "target",
    ) -> None:
        """A program build raised instead of landing."""
        with self._lock:
            self.failed_builds += 1
            self.last_failure = {
                "key": _key_str(key),
                "phase": phase,
                "role": role,
                "trigger": trigger,
                "error": f"{type(exc).__name__}: {exc}"[:500],
            }

    def record_dispatch(self, served_key: tuple, hit: bool,
                        role: str = "target") -> None:
        """A dispatch was served: ``hit`` means the EXACT requested key
        was already compiled (no fallback, no sync compile)."""
        with self._lock:
            if hit:
                self.cache_hits += 1
            else:
                self.cache_misses += 1
            entry = self._inventory.get((role, _key_str(served_key)))
            if entry is not None:
                entry["dispatches"] += 1
                entry["last_used_t"] = self._clock()

    # -- storm detection ---------------------------------------------------

    def _check_storm(self, now: float) -> None:
        """Edge-triggered sliding-window detector (watchdog-episode
        idiom): one report + one counter bump per episode."""
        report = None
        with self._lock:
            horizon = now - self.storm_window_s
            while self._storm_events and self._storm_events[0][0] < horizon:
                self._storm_events.popleft()
            n = len(self._storm_events)
            if n >= self.storm_threshold and not self._in_storm:
                self._in_storm = True
                self.storms_total += 1
                shapes: dict[str, int] = {}
                for _, s in self._storm_events:
                    shapes[s] = shapes.get(s, 0) + 1
                top = sorted(
                    shapes.items(), key=lambda kv: -kv[1]
                )[:_REPORT_SHAPE_CAP]
                report = {
                    "event": "compile_storm",
                    "mid_traffic_compiles": n,
                    "window_s": self.storm_window_s,
                    "threshold": self.storm_threshold,
                    "shapes": [
                        {"key": s, "compiles": c} for s, c in top
                    ],
                }
                self.last_storm_report = report
            elif n < self.storm_threshold:
                self._in_storm = False  # episode over; re-arm
        if report is not None:
            logger.warning(
                "recompile storm: %d mid-traffic compiles in %.0fs — "
                "shapes escaping the bucket ladder: %s",
                report["mid_traffic_compiles"], report["window_s"],
                json.dumps(redact(report)),
            )
            rec = self.recorder
            if rec is not None:
                rec.note("compile_storm", **{
                    "mid_traffic_compiles": report["mid_traffic_compiles"],
                    "shapes": [s["key"] for s in report["shapes"]],
                })

    # -- reading (exporter / debug / stats) --------------------------------

    def stats_snapshot(self) -> dict:
        """Per-stats()-call snapshot for EngineStatsSnapshot.compile.
        Drains the pending wall-clock list (the grammar_build_times
        idiom — each observation is exported exactly once)."""
        with self._lock:
            walls, self._pending_walls = self._pending_walls, []
            return {
                "programs": len(self._inventory),
                "compiles": dict(self.compiles),
                "hits": self.cache_hits,
                "misses": self.cache_misses,
                "storms": self.storms_total,
                "walls": walls,
                "mid_traffic": sum(
                    v for k, v in self.compiles.items()
                    if k.endswith("/mid_traffic")
                ),
            }

    def debug_payload(self) -> dict:
        """GET /debug/programs body."""
        now = self._clock()
        with self._lock:
            programs = [
                {
                    "key": e["key"],
                    "phase": e["phase"],
                    "role": e["role"],
                    "trigger": e["trigger"],
                    "compile_wall_s": e["compile_wall_s"],
                    "dispatches": e["dispatches"],
                    "last_used_age_s": round(now - e["last_used_t"], 1),
                    "rid": e["rid"],
                    "hbm_bytes": e["hbm_bytes"],
                }
                for e in self._inventory.values()
            ]
            return {
                "programs": programs,
                "capacity": self.capacity,
                "compiles": dict(self.compiles),
                "failed_builds": {
                    "total": self.failed_builds,
                    "last": self.last_failure,
                },
                "cache": {
                    "hits": self.cache_hits,
                    "misses": self.cache_misses,
                },
                "storm": {
                    "threshold": self.storm_threshold,
                    "window_s": self.storm_window_s,
                    "total": self.storms_total,
                    "window_events": len(self._storm_events),
                    "last_report": self.last_storm_report,
                },
            }


def _key_str(key: tuple) -> str:
    """Stable human-readable form of a program cache key."""
    return repr(tuple(key))


def program_memory_bytes(compiled) -> int | None:
    """Best-effort HBM footprint of a compiled executable via
    ``memory_analysis()`` — absent on some backends/versions, so every
    failure degrades to None rather than breaking the compile path."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    total = 0
    seen = False
    for attr in (
        "generated_code_size_in_bytes",
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
    ):
        v = getattr(ma, attr, None)
        if isinstance(v, int):
            total += v
            seen = True
    return total if seen else None
