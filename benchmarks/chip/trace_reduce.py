"""xplane -> what the benchmark reports from a device trace: seconds in
which an operation ran on the device (union of intervals, averaged over the
chips), the traced window, seconds per operation name, and the idle gaps.

Run as a script (`trace_reduce.py <profile dir> <out.json>`) in a process of
its own, because reading an xplane needs `jax.profiler.ProfileData`; the
arithmetic (`reduce_events`) needs nothing and is what the tests check.

A device plane is one whose name starts with `/device:TPU:`. Its operations
are the events of the line named `XLA Ops`; where a plane has no such line,
every line but the step and module summaries is taken.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

_SUFFIX = re.compile(r"[.:]\d+$")
# control flow whose events span the operations inside them (the fused decode
# window is one `while`): counted in busy time, not in the per-operation sums
_CONTAINERS = ("while", "conditional", "call")
_SUMMARY_LINES = ("Steps", "XLA Modules", "Framework Ops", "Source code",
                  "Framework Name Scope", "XLA TraceMe")


def op_family(name: str) -> str:
    """One row per kind of operation. On the chip an event's name is the
    whole HLO statement (`paged_decode_attention.392 = bf16[16,32,128]{...}
    custom-call(...)`): the result's name, without `%` and its number."""
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


def gaps(intervals: list[tuple[float, float]], lo: float, hi: float,
         ) -> list[tuple[float, float]]:
    """The idle stretches of [lo, hi) between the intervals, longest first."""
    out, end = [], lo
    for s, e in sorted(intervals):
        if s > end:
            out.append((end, min(s, hi)))
        end = max(end, e)
    if end < hi:
        out.append((end, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


def reduce_events(planes: dict[str, list[tuple[str, float, float]]],
                  modules: dict[str, list[tuple[str, float, float]]] | None
                  = None) -> dict:
    """planes: device plane name -> [(op name, start_s, duration_s)];
    modules: the same for whole programs (the `XLA Modules` line).
    The window is the same for every chip: first start to last end over all
    of them. Busy seconds, per-op and per-module seconds and counts are
    averaged over the chips."""
    planes = {k: v for k, v in planes.items() if v}
    if not planes:
        raise ValueError("no device operation in the trace")
    lo = min(s for ev in planes.values() for _, s, _ in ev)
    hi = max(s + d for ev in planes.values() for _, s, d in ev)
    n = len(planes)
    busy, per_op, op_n, longest = 0.0, {}, {}, []
    for ev in planes.values():
        iv = [(s, s + d) for _, s, d in ev]
        busy += union_seconds(iv) / n
        for name, _, d in ev:
            fam = op_family(name)
            if fam in _CONTAINERS:
                continue  # its body's operations are events of their own
            per_op[fam] = per_op.get(fam, 0.0) + d / n
            op_n[fam] = op_n.get(fam, 0.0) + 1.0 / n
        longest += gaps(iv, lo, hi)[:50]
    per_mod, mod_n = {}, {}
    for ev in (modules or {}).values():
        for name, _, d in ev:
            fam = module_family(name)
            per_mod[fam] = per_mod.get(fam, 0.0) + d / n
            mod_n[fam] = mod_n.get(fam, 0.0) + 1.0 / n
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])
    return {
        "chips": n, "window_s": hi - lo, "busy_s": busy,
        "t_lo_s": lo, "t_hi_s": hi,
        "device_ops": [[k, v] for k, v in ops],
        "op_counts": op_n,
        "modules": sorted(([k, v] for k, v in per_mod.items()),
                          key=lambda kv: -kv[1]),
        "module_counts": mod_n,
        "gaps_s": sorted((g[1] - g[0] for g in longest), reverse=True)[:50],
        "gap_total_s": (hi - lo) - busy,
    }


def planes_of(data) -> tuple[dict, dict]:
    """(operations, modules) per device plane of a ProfileData."""
    def events(lines):
        return [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                for ln in lines for ev in ln.events]

    ops, mods = {}, {}
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = list(plane.lines)
        ops[plane.name] = events(
            [ln for ln in lines if ln.name == "XLA Ops"] or
            [ln for ln in lines if ln.name not in _SUMMARY_LINES])
        mods[plane.name] = events(
            [ln for ln in lines if ln.name == "XLA Modules"])
    return ops, mods


def describe(data) -> list:
    """Plane and line names with event counts: read one trace by hand before
    trusting the reduction (kept in the output for that)."""
    return [[p.name, [[ln.name, sum(1 for _ in ln.events)]
                      for ln in p.lines]] for p in data.planes]


def main(argv: list[str]) -> int:
    trace_dir, out_path = argv[1], argv[2]
    files = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        print(f"no .xplane.pb under {trace_dir}", file=sys.stderr)
        return 1
    from jax.profiler import ProfileData
    data = ProfileData.from_file(files[-1])  # parsed once: hundreds of MB
    result = reduce_events(*planes_of(data))
    result["planes"] = [
        [name, [ln for ln in lines if ln[1]][:12]]
        for name, lines in describe(data)][:24]
    with open(out_path, "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
