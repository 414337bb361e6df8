#!/usr/bin/env python3
"""Reduce one xprof capture of a live engine (POST /debug/profile/start|stop)
to its step timeline (docs/14-tracing.md):

- the device's idle stretches between step programs, each matched to the
  `step:<phase>` spans of the engine's step thread that ran inside it, to
  the program that ended before it, and to the `dev:dry` event in which the
  step thread first saw that program complete (with its lag behind the
  program's end on the device's line);
- the host's own account of an empty device over the capture — starvation
  gaps and dry gaps rebuilt from the `step:*` spans and `dev:dry` events —
  against the idle the device plane shows;
- device seconds per operation family, split by the jitted steps' named
  scopes and by program;
- the `clock_pair` event that ties the capture to the flight records.

    python tools/xplane_timeline.py <profile dir> <out.json> [--min-gap-ms 1]

Reads the raw `.xplane.pb` (an operation's scope is in its event metadata's
`tf_op` / `hlo_op` stats, which `jax.profiler.ProfileData` does not show), so
it needs an `xplane_pb2` module: TensorFlow's or xprof's. Run it with
JAX_PLATFORMS=cpu in a process that does not hold the chip.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import importlib
import json
import os
import re
import sys

# the named scopes of the jitted steps (docs/14-tracing.md), innermost wins
SCOPES = ("embed", "attn_qkv", "kv_stage", "attn", "attn_out", "mlp",
          "final_norm", "lm_head", "sample", "kv_commit", "gather_pages",
          # inside `mlp` of a sparse-MoE layer (models/llama.py:_moe_mlp)
          "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
          # an MLA layer (models/llama.py:_mla_attention) and the shared
          # experts of a DeepSeek-V3-style expert layer
          "mla_q", "mla_latent", "mla_absorb", "moe_shared")
# the benchmark's own names for an operation and a program, and its union of
# intervals: this reduction must agree with benchmarks/chip/trace_reduce.py
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "benchmarks", "chip"))
from trace_reduce import (  # noqa: E402
    _CONTAINERS, module_family, op_family, union_seconds,
)

_PB2 = ("tensorflow.tsl.profiler.protobuf.xplane_pb2",
        "tsl.profiler.protobuf.xplane_pb2", "xprof.protobuf.xplane_pb2")
STEP_PROGRAM_MS = 1.0  # shorter programs (RNG split, chain gathers) are glue


def load_xspace(path: str):
    for name in _PB2:
        try:
            pb2 = importlib.import_module(name)
        except ImportError:
            continue
        space = pb2.XSpace()
        with open(path, "rb") as f:
            space.ParseFromString(f.read())
        return space
    raise SystemExit(f"no xplane_pb2 module among {_PB2}")


def scope_of(path: str) -> str:
    """The innermost documented scope in an op_name path."""
    for part in reversed(re.split(r"[/:]", path)):
        if re.sub(r"\(.*\)$", "", part) in SCOPES:
            return re.sub(r"\(.*\)$", "", part)
    return "other"


class Plane:
    """One XPlane with names and stats resolved."""

    def __init__(self, plane):
        self.name = plane.name
        self._stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        self._meta = plane.event_metadata
        self.lines = {}
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

    def events(self, line_name: str, with_stats: bool = False):
        """[(name, start_ns, end_ns, stats)] — stats: the event's own over
        its metadata's (where an operation's `tf_op` lives)."""
        out = []
        for ln in self.lines.get(line_name, []):
            for ev in ln.events:
                meta = self._meta[ev.metadata_id]
                start = ln.timestamp_ns + ev.offset_ps * 1e-3
                st = None
                if with_stats:
                    st = {**self.stats(meta), **self.stats(ev)}
                out.append((meta.name, start, start + ev.duration_ps * 1e-3,
                            st))
        return out


def host_spans(planes) -> tuple[list, list, list, list]:
    """(step:* spans, clock_pair marks, names of the lines that hold
    spans, dev:dry events as (time, seq, slack_ns)) over the host planes."""
    steps, marks, lines, dries = [], [], set(), []
    for pl in planes:
        if pl.name.startswith("/device:"):
            continue
        for lname in pl.lines:
            for name, s, e, st in pl.events(lname, with_stats=True):
                if name.startswith("step:"):
                    steps.append((name[5:], s, e, st.get("seq"),
                                  st.get("kind")))
                    lines.add(lname)
                elif name == "clock_pair":
                    marks.append({"capture_ns": s, **st})
                elif name == "dev:dry":
                    dries.append((s, st.get("seq"), st.get("slack_ns") or 0))
    return steps, marks, sorted(lines), sorted(dries)


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


def self_ms_inside(steps, lo: float, hi: float) -> dict:
    """Milliseconds of each phase inside [lo, hi), a nested span's part
    taken off the span around it (build holds h2d and enqueue, post holds
    sync)."""
    inside: dict = {}
    for ph, s, e, *_ in steps:
        ov = min(e, hi) - max(s, lo)
        if ov > 0:
            inside[ph] = inside.get(ph, 0.0) + ov * 1e-6
    for outer, inner in (("build", ("h2d", "enqueue")), ("post", ("sync",))):
        if outer in inside:
            inside[outer] -= sum(inside.get(i, 0.0) for i in inner)
    return {k: round(v, 3) for k, v in inside.items()}


def reduce_device(pl: Plane, steps, dries, t0: float,
                  min_gap_ms: float) -> dict:
    ops = pl.events("XLA Ops", with_stats=True)
    mods = [(module_family(n), s, e) for n, s, e, _ in pl.events("XLA Modules")]
    dev: dict = {"plane": pl.name, "ops": len(ops), "modules": len(mods)}
    if not ops:
        return dev
    lo, hi = min(o[1] for o in ops), max(o[2] for o in ops)
    dev["window_s"] = (hi - lo) * 1e-9
    dev["busy_s"] = union_seconds([(s, e) for _, s, e, _ in ops]) * 1e-9
    # -- idle between step programs ---------------------------------------
    progs = sorted((s, e, n) for n, s, e in mods
                   if (e - s) * 1e-6 >= STEP_PROGRAM_MS)
    gaps = []
    for (_, e0, n0), (s1, _, n1) in zip(progs, progs[1:]):
        if (s1 - e0) * 1e-6 < min_gap_ms:
            continue
        glue = [(s, e) for n, s, e in mods
                if e0 <= s and e <= s1 and (e - s) * 1e-6 < STEP_PROGRAM_MS]
        # the step thread's first sight of the program before the gap as
        # complete, and how long after the program's end that was
        i = bisect.bisect_left(dries, (e0,))
        seen = dries[i] if i < len(dries) and dries[i][0] < s1 else None
        gaps.append({
            "at_s": round((e0 - t0) * 1e-9, 6),
            "ms": round((s1 - e0) * 1e-6, 3), "after": n0, "before": n1,
            "glue_programs": len(glue),
            "host_ms": self_ms_inside(steps, e0, s1),
            "dry": None if seen is None else {
                "seq": seen[1], "lag_ms": round((seen[0] - e0) * 1e-6, 3),
                "slack_ms": round(seen[2] * 1e-6, 3)},
        })
    dev["gaps_over_ms"] = min_gap_ms
    dev["gaps"] = gaps
    dev["gap_sum_s"] = round(sum(g["ms"] for g in gaps) * 1e-3, 6)
    dev["other_idle_s"] = round(
        dev["window_s"] - dev["busy_s"] - dev["gap_sum_s"], 6)
    by_after, by_phase = {}, {}
    for g in gaps:
        row = by_after.setdefault(g["after"], {"n": 0, "ms": 0.0})
        row["n"] += 1
        row["ms"] = round(row["ms"] + g["ms"], 3)
        for ph, ms in g["host_ms"].items():
            by_phase[ph] = round(by_phase.get(ph, 0.0) + ms, 3)
    dev["gaps_by_after"] = by_after
    dev["gaps_host_ms_by_phase"] = by_phase
    # trace idle against the host's own account over the same stretch
    host = host_account(steps, dries, lo, hi)
    seen = host["starved_s"] + host["dry_s"]
    dev["idle_against_host"] = {
        "trace_idle_s": round(dev["window_s"] - dev["busy_s"], 6), **host,
        "starved_plus_dry_s": round(seen, 6),
        "plus_slack_s": round(seen + host["dry_slack_s"], 6),
        "gaps_with_dry": sum(g["dry"] is not None for g in gaps),
    }
    # -- operations by program, scope and family --------------------------
    starts = [p[0] for p in progs]
    table, unscoped = {}, {}
    for n, s, e, st in ops:
        fam = op_family(n)
        if fam in _CONTAINERS:
            continue
        sc = scope_of(str(st.get("tf_op") or ""))
        i = bisect.bisect_right(starts, s) - 1
        prog = progs[i][2] if i >= 0 and s < progs[i][1] else "glue"
        row = table.setdefault((prog, sc, fam), [0, 0.0])
        row[0] += 1
        row[1] += (e - s) * 1e-9
        if sc == "other":  # say what it is some other way: its shape
            key = (fam, prog, str(st.get("tf_op") or "")[-60:],
                   str(st.get("shape_with_layout", ""))[:70])
            row = unscoped.setdefault(key, [0, 0.0])
            row[0] += 1
            row[1] += (e - s) * 1e-9
    dev["ops_without_scope"] = sorted(
        ([*k, n, round(t, 6)] for k, (n, t) in unscoped.items()),
        key=lambda r: -r[-1])[:25]
    dev["programs"] = {}
    for n, s, e in mods:
        row = dev["programs"].setdefault(n, {"n": 0, "s": 0.0})
        row["n"] += 1
        row["s"] = round(row["s"] + (e - s) * 1e-9, 6)
    by_scope, by_fam_scope = {}, {}
    for (prog, sc, fam), (n, t) in table.items():
        by_scope[(prog, sc)] = by_scope.get((prog, sc), 0.0) + t
        by_fam_scope[(fam, prog, sc)] = by_fam_scope.get(
            (fam, prog, sc), 0.0) + t
    dev["seconds_by_program_scope"] = sorted(
        ([p, sc, round(t, 6)] for (p, sc), t in by_scope.items()),
        key=lambda r: -r[2])
    dev["seconds_by_family_program_scope"] = sorted(
        ([f, p, sc, round(t, 6)] for (f, p, sc), t in by_fam_scope.items()),
        key=lambda r: -r[3])[:80]
    return dev


def reduce(space, min_gap_ms: float) -> dict:
    planes = [Plane(p) for p in space.planes]
    steps, marks, lines, dries = host_spans(planes)
    t0 = min((s for _, s, *_ in steps), default=0.0)
    out: dict = {
        "planes": [[p.name, {k: sum(len(ln.events) for ln in v)
                             for k, v in list(p.lines.items())[:40]}]
                   for p in planes],
        "clock_pair": marks, "step_thread_lines": lines, "step_spans": {},
        "dev_dry_events": len(dries),
    }
    for ph, s, e, *_ in steps:
        row = out["step_spans"].setdefault(ph, {"n": 0, "s": 0.0})
        row["n"] += 1
        row["s"] = round(row["s"] + (e - s) * 1e-9, 6)
    out["devices"] = [reduce_device(p, steps, dries, t0, min_gap_ms)
                      for p in planes if p.name.startswith("/device:TPU:")]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("profile_dir")
    ap.add_argument("out_json")
    ap.add_argument("--min-gap-ms", type=float, default=1.0)
    args = ap.parse_args(argv)
    files = sorted(glob.glob(
        os.path.join(args.profile_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        print(f"no .xplane.pb under {args.profile_dir}", file=sys.stderr)
        return 1
    result = reduce(load_xspace(files[-1]), args.min_gap_ms)
    with open(args.out_json, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"step_thread_lines": result["step_thread_lines"],
                      "step_spans": result["step_spans"],
                      "dev_dry_events": result["dev_dry_events"]}))
    for dev in result["devices"]:
        for g in dev.get("gaps", []):
            print(json.dumps(g))
        print(json.dumps({k: dev.get(k) for k in (
            "plane", "window_s", "busy_s", "gap_sum_s", "other_idle_s",
            "gaps_by_after", "gaps_host_ms_by_phase", "idle_against_host")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
