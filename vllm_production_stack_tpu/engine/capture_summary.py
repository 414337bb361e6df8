"""Reduce one xprof capture of a live engine (POST /debug/profile/start|stop,
docs/14-tracing.md) to the chip's seconds by PROGRAM and by named SCOPE, and
its idle stretches to what the host was doing in them:

- `programs`: the device's programs by kind (metrics_contract.
  DEVICE_PROGRAM_KINDS over the `XLA Modules` line: a prefill chunk, a decode
  window, a verify; anything under a millisecond is glue), count and seconds;
- `scopes`: XLA's own operations, each laid on the program that held it and
  booked to the innermost registered `jax.named_scope` of its `tf_op` path
  (metrics_contract.DEVICE_SCOPES) — rows [kind, group, scope, seconds, ops];
  `kernels`: the custom calls (the Pallas kernels, under the names the
  benchmark's `breakdown.device_ops` prints) — rows [kind, family, seconds,
  n, group]. Every operation is in one of the two, once; control flow
  (`while`, `conditional`, `call`) spans the operations inside it and counts
  in busy time only. `unscoped`: the largest families under `other`;
- `idle_gaps`: the device's idle stretches over a millisecond between step
  programs, the ten longest as [seconds, kind of the program before, the
  step-thread phase that covers most of it, lag of the `dev:dry` sighting
  behind that program's end in ms], and `idle`: all of them summed by phase
  (`step:<phase>` spans of the step thread, self time, profiler's clock);
- `host_account`: the program's two accounts of an empty device (docs/37)
  rebuilt from the capture's own spans; `clock_pair`: what ties the capture
  to the flight records.

    python -m vllm_production_stack_tpu.engine.capture_summary \
        <profile dir> <out.json>

The server runs exactly this in a child after `stop_trace` and serves the
document (`engine/server.py:debug_profile_stop`). Seconds are averaged over
the chips, as benchmarks/chip/trace_reduce.py averages them; gaps are read
on the first chip's line. An operation's scope lives in its event
METADATA's stats, which `jax.profiler.ProfileData` does not show, so this
reads the raw `.xplane.pb` through a generated `xplane_pb2`. No JAX, no
device: it reads a file.
"""

from __future__ import annotations

import argparse
import bisect
import functools
import glob
import importlib.util
import json
import os
import re
import sys
import time

from ..metrics_contract import DEVICE_PROGRAM_KINDS, DEVICE_SCOPES

# -- the benchmark's names for an operation and a program, and its union of
# intervals, restated (the package may not import benchmarks/): tests/
# test_capture_summary.py holds them equal to trace_reduce.py's
_SUFFIX = re.compile(r"[.:]\d+$")
CONTAINERS = ("while", "conditional", "call")
STEP_PROGRAM_MS = 1.0  # shorter programs (RNG split, chain gathers) are glue
MIN_GAP_MS = 1.0
TOP = 10  # rows of `unscoped` and of `idle_gaps`
# where a generated xplane_pb2.py lies: (top-level package, path inside it)
_PB2 = (("tensorflow", "tsl/profiler/protobuf/xplane_pb2.py"),
        ("tsl", "profiler/protobuf/xplane_pb2.py"),
        ("xprof", "protobuf/xplane_pb2.py"))


def op_family(name: str) -> str:
    """One row per kind of operation: on the chip an event's name is the
    whole HLO statement; its result's name, without `%` and its number."""
    name = name.split(" = ")[0].strip().lstrip("%")
    while True:
        cut = _SUFFIX.sub("", name)
        if cut == name:
            return name
        name = cut


def module_family(name: str) -> str:
    """`jit_decode_window_fn(1234567)` -> `jit_decode_window_fn`."""
    return op_family(name.split("(")[0].strip())


def union_seconds(intervals: list[tuple[float, float]]) -> float:
    """Total length of the union of [start, end) intervals."""
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


@functools.cache
def load_pb2():
    """A generated `xplane_pb2` module, executed from its FILE: it needs
    google.protobuf alone, and importing the package around it (TensorFlow:
    ten seconds, 800 MB) would cost more than the reduction. None where no
    installed package carries one."""
    for package, rel in _PB2:
        try:
            found = importlib.util.find_spec(package)
        except (ImportError, ValueError):
            continue
        for root in (found.submodule_search_locations or ()) if found else ():
            path = os.path.join(root, rel)
            if not os.path.exists(path):
                continue
            spec = importlib.util.spec_from_file_location("_xplane_pb2", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    return None


def load_xspace(path: str):
    pb2 = load_pb2()
    if pb2 is None:
        raise RuntimeError(
            f"no xplane_pb2.py under any of {[p for p, _ in _PB2]}")
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def scope_of(path: str) -> str | None:
    """The innermost registered scope in an op_name path, or None."""
    for part in reversed(re.split(r"[/:]", path)):
        part = re.sub(r"\(.*\)$", "", part)
        if part in DEVICE_SCOPES:
            return part
    return None


def program_kind(name: str, duration_ns: float) -> str:
    if duration_ns * 1e-6 < STEP_PROGRAM_MS:
        return "glue_program"
    return DEVICE_PROGRAM_KINDS.get(module_family(name), "other_program")


class Plane:
    """One XPlane with names and stats resolved."""

    def __init__(self, plane):
        self.name = plane.name
        self._stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        self.meta = plane.event_metadata
        self.lines: dict = {}
        for ln in plane.lines:
            self.lines.setdefault(ln.name or ln.display_name, []).append(ln)

    def stats(self, owner) -> dict:
        out = {}
        for st in owner.stats:
            kind = st.WhichOneof("value")
            val = getattr(st, kind) if kind else None
            if kind == "ref_value":
                val = self._stat_names.get(val, val)
            elif kind == "bytes_value":
                val = f"<{len(val)} bytes>"
            out[self._stat_names.get(st.metadata_id, st.metadata_id)] = val
        return out

    def events(self, line_name: str, wanted=None):
        """(metadata id, start_ns, end_ns, event) of a line's events, those
        whose metadata id is in `wanted` where it is given."""
        for ln in self.lines.get(line_name, ()):
            base = ln.timestamp_ns
            for ev in ln.events:
                mid = ev.metadata_id
                if wanted is not None and mid not in wanted:
                    continue
                start = base + ev.offset_ps * 1e-3
                yield mid, start, start + ev.duration_ps * 1e-3, ev

    def op_class(self, mid: int) -> tuple[str, str | None, bool] | None:
        """(family, scope, is a custom call) of an operation's metadata: its
        name is the HLO statement, its `tf_op` stat the path of scopes.
        None for control flow, whose body's operations are events too."""
        meta = self.meta[mid]
        family = op_family(meta.name)
        if family in CONTAINERS:
            return None
        path = str(self.stats(meta).get("tf_op") or "")
        return family, scope_of(path), " custom-call(" in meta.name


def host_spans(planes: list[Plane]) -> tuple[list, list, list]:
    """(step:* spans as (phase, start, end, seq, kind), clock_pair marks,
    dev:dry events as (time, seq, slack_ns)) over the host planes."""
    steps, marks, dries = [], [], []
    for pl in planes:
        if pl.name.startswith("/device:"):
            continue
        wanted = {k: m.name for k, m in pl.meta.items()
                  if m.name.startswith("step:")
                  or m.name in ("clock_pair", "dev:dry")}
        if not wanted:
            continue
        for lname in pl.lines:
            for mid, s, e, ev in pl.events(lname, wanted):
                name, st = wanted[mid], pl.stats(ev)
                if name == "clock_pair":
                    marks.append({"capture_ns": s, **st})
                elif name == "dev:dry":
                    dries.append((s, st.get("seq"), st.get("slack_ns") or 0))
                else:
                    steps.append((name[5:], s, e, st.get("seq"),
                                  st.get("kind")))
    return steps, marks, sorted(dries, key=lambda d: d[0])


def host_account(steps, dries, lo: float, hi: float) -> dict:
    """The program's two accounts of an empty device (docs/37), rebuilt from
    the capture alone and clipped to [lo, hi): a STARVATION gap runs from the
    end of the `sync` that resolved the newest enqueued step to the end of
    the next dispatch's `build`; a DRY gap from a `dev:dry` event (the first
    poll that saw the newest enqueued program complete behind an unresolved
    step) to the end of the next dispatch's `build`, or to that step's own
    resolve where nothing was enqueued behind it; `slack` is the bracket
    before each `dev:dry` (the poll before it)."""
    enq_end, sync_end = {}, {}
    for ph, _, e, seq, _ in steps:
        if seq is None:
            continue
        if ph == "build":
            enq_end[seq] = max(e, enq_end.get(seq, e))
        elif ph == "sync":
            sync_end[seq] = e

    def clipped(a: float, b: float) -> float:
        return max(0.0, min(b, hi) - max(a, lo))

    starved = sum(
        clipped(t, enq_end[n + 1]) for n, t in sync_end.items()
        if n + 1 in enq_end and enq_end[n + 1] > t)
    dry = slack = 0.0
    n_dry = 0
    for t, seq, slack_ns in dries:
        if seq is None or not lo <= t < hi:
            continue
        ends = [x for x in (enq_end.get(seq + 1), sync_end.get(seq))
                if x is not None and x >= t]
        if not ends:  # still open where the capture stops
            continue
        n_dry += 1
        dry += clipped(t, min(ends))
        slack += slack_ns
    return {"starved_s": round(starved * 1e-9, 6),
            "dry_s": round(dry * 1e-9, 6),
            "dry_slack_s": round(slack * 1e-9, 6), "dry_n": n_dry}


def self_segments(steps) -> list[tuple[float, float, str]]:
    """The step thread's spans as disjoint (start, end, phase) pieces in
    time order: at each instant the innermost open span (build holds h2d and
    enqueue, post holds sync), so a phase's pieces are its self time."""
    out: list = []
    stack: list = []  # open spans, outermost first: (phase, end)
    cursor = float("-inf")  # pieces are out up to here

    def close(until: float) -> None:
        nonlocal cursor
        while stack and stack[-1][1] <= until:
            ph, e = stack.pop()
            if e > cursor:
                out.append((cursor, e, ph))
                cursor = e

    for ph, s, e, *_ in sorted(steps, key=lambda r: (r[1], -r[2])):
        close(s)
        if stack and s > cursor:
            out.append((cursor, s, stack[-1][0]))
        cursor = max(cursor, s)
        stack.append((ph, e))
    close(float("inf"))
    return out


def phases_inside(segments, seg_starts, lo: float, hi: float) -> dict:
    """Nanoseconds of each phase's self time inside [lo, hi)."""
    inside: dict = {}
    i = max(0, bisect.bisect_right(seg_starts, lo) - 1)
    while i < len(segments) and segments[i][0] < hi:
        s, e, ph = segments[i]
        ov = min(e, hi) - max(s, lo)
        if ov > 0:
            inside[ph] = inside.get(ph, 0.0) + ov
        i += 1
    return inside


def reduce_gaps(mods, steps, dries) -> tuple[list, dict]:
    """Idle stretches over MIN_GAP_MS between step programs of one chip
    (glue programs inside a stretch are part of it): (rows of the longest,
    totals). A stretch's phases are the step thread's self time inside it;
    what no span covers is `none`."""
    progs = [m for m in mods if m[2] != "glue_program"]
    segments = self_segments(steps)
    seg_starts = [s[0] for s in segments]
    dry_times = [d[0] for d in dries]
    rows, by_phase, total = [], {}, 0.0
    for (_, e0, k0), (s1, _, _) in zip(progs, progs[1:]):
        gap = s1 - e0
        if gap * 1e-6 < MIN_GAP_MS:
            continue
        total += gap
        inside = phases_inside(segments, seg_starts, e0, s1)
        inside["none"] = max(0.0, gap - sum(inside.values()))
        for ph, ns in inside.items():
            by_phase[ph] = by_phase.get(ph, 0.0) + ns
        # the step thread's first sight of the program before the gap as
        # complete, and how long after the program's end that was
        i = bisect.bisect_left(dry_times, e0)
        lag = (round((dry_times[i] - e0) * 1e-6, 3)
               if i < len(dries) and dry_times[i] < s1 else None)
        rows.append([round(gap * 1e-9, 6), k0,
                     max(inside, key=inside.get), lag])
    rows.sort(key=lambda r: -r[0])
    return rows[:TOP], {
        "gaps_over_ms": MIN_GAP_MS, "gaps_n": len(rows),
        "gaps_s": round(total * 1e-9, 6),
        "by_phase_s": {ph: round(ns * 1e-9, 6) for ph, ns in sorted(
            by_phase.items(), key=lambda kv: -kv[1])},
    }


def reduce_device(pl: Plane) -> dict | None:
    """One chip's plane: its window and busy seconds, its programs, and its
    operations by (program kind, family, scope, custom call)."""
    mods = sorted((s, e, program_kind(pl.meta[mid].name, e - s))
                  for mid, s, e, _ in pl.events("XLA Modules"))
    starts = [m[0] for m in mods]
    classes: dict = {}
    by_op: dict = {}  # (kind, metadata id) -> [n, ns]
    intervals = []
    for mid, s, e, _ in pl.events("XLA Ops"):
        intervals.append((s, e))
        if mid not in classes:
            classes[mid] = pl.op_class(mid)
        if classes[mid] is None:
            continue
        i = bisect.bisect_right(starts, s) - 1
        kind = mods[i][2] if i >= 0 and s < mods[i][1] else "glue_program"
        row = by_op.get((kind, mid))
        if row is None:
            row = by_op[kind, mid] = [0, 0.0]
        row[0] += 1
        row[1] += e - s
    if not intervals:
        return None
    ops: dict = {}  # (kind, family, scope, custom call) -> [n, ns]
    for (kind, mid), (n, ns) in by_op.items():
        row = ops.setdefault((kind, *classes[mid]), [0, 0.0])
        row[0] += n
        row[1] += ns
    return {"lo": min(s for s, _ in intervals),
            "hi": max(e for _, e in intervals),
            "busy_ns": union_seconds(intervals), "events": len(intervals),
            "mods": mods, "ops": ops}


def summarize(space) -> dict:
    """The document `POST /debug/profile/stop` answers with (the module's
    docstring says what each part is)."""
    planes = [Plane(p) for p in space.planes]
    steps, marks, dries = host_spans(planes)
    chips = [d for d in (reduce_device(p) for p in planes
                         if p.name.startswith("/device:TPU:")) if d]
    doc: dict = {"chips": len(chips), "events": 0, "window_s": 0.0,
                 "busy_s": 0.0, "programs": {}, "scopes": [], "kernels": [],
                 "unscoped": [], "idle_gaps": [], "idle": {}}
    if chips:
        n = len(chips)
        lo = min(c["lo"] for c in chips)
        hi = max(c["hi"] for c in chips)
        doc["events"] = sum(c["events"] for c in chips)
        doc["window_s"] = round((hi - lo) * 1e-9, 6)
        doc["busy_s"] = round(sum(c["busy_ns"] for c in chips) / n * 1e-9, 6)
        scopes, kernels, unscoped = {}, {}, {}
        for c in chips:
            for s, e, kind in c["mods"]:
                row = doc["programs"].setdefault(
                    kind, {"n": 0.0, "device_s": 0.0})
                row["n"] += 1.0 / n
                row["device_s"] += (e - s) * 1e-9 / n
            for (kind, family, scope, custom), (cnt, ns) in c["ops"].items():
                group = DEVICE_SCOPES.get(scope, "other")
                if custom:
                    row = kernels.setdefault((kind, family, group), [0.0, 0.0])
                else:
                    row = scopes.setdefault(
                        (kind, group, scope or "other"), [0.0, 0.0])
                row[0] += ns * 1e-9 / n
                row[1] += cnt / n
                if group == "other":
                    unscoped[kind, family] = unscoped.get(
                        (kind, family), 0.0) + ns * 1e-9 / n
        for row in doc["programs"].values():
            row["n"] = round(row["n"], 3)
            row["device_s"] = round(row["device_s"], 6)
        doc["scopes"] = sorted(
            ([k, g, sc, round(t, 6), round(c, 3)]
             for (k, g, sc), (t, c) in scopes.items()), key=lambda r: -r[3])
        doc["kernels"] = sorted(
            ([k, f, round(t, 6), round(c, 3), g]
             for (k, f, g), (t, c) in kernels.items()), key=lambda r: -r[2])
        doc["unscoped"] = sorted(
            ([k, f, round(t, 6)] for (k, f), t in unscoped.items()),
            key=lambda r: -r[2])[:TOP]
        doc["idle_gaps"], doc["idle"] = reduce_gaps(
            chips[0]["mods"], steps, dries)
        doc["idle"]["trace_idle_s"] = round(
            doc["window_s"] - doc["busy_s"], 6)
    else:  # a CPU engine's capture has no device plane: the host's part
        lo = min((s for _, s, *_ in steps), default=0.0)
        hi = max((e for _, _, e, *_ in steps), default=0.0)
    doc["host_account"] = host_account(steps, dries, lo, hi)
    doc["step_spans"] = len(steps)
    doc["clock_pair"] = marks[0] if marks else None
    return doc


def summarize_dir(profile_dir: str) -> dict:
    """The newest capture under `profile_dir`, reduced; `reduce_s` is what
    that cost, reading and parsing included."""
    t0 = time.monotonic()
    files = sorted(glob.glob(
        os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    doc = {"dir": profile_dir, "file": os.path.relpath(files[-1], profile_dir),
           "capture_bytes": os.path.getsize(files[-1]),
           **summarize(load_xspace(files[-1]))}
    doc["reduce_s"] = round(time.monotonic() - t0, 3)
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("profile_dir")
    ap.add_argument("out_json")
    args = ap.parse_args(argv)
    if hasattr(os, "nice"):
        os.nice(10)  # beside an engine that is serving: never in its way
    try:
        doc = summarize_dir(args.profile_dir)
    except (FileNotFoundError, RuntimeError) as e:
        print(f"{type(e).__name__}: {e}", file=sys.stderr)
        return 1
    text = json.dumps(doc)
    with open(args.out_json, "w") as f:
        f.write(text)
    print(text)  # the server reads it here; the file stays with the dump
    return 0


if __name__ == "__main__":
    sys.exit(main())
