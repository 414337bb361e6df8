"""Device time out of the engine's own summary of the traced slice's capture
(`ctx["device"]["last_capture"]`: what POST /debug/profile/stop answered,
served again as `device.last_capture` of GET /debug/programs;
vllm_production_stack_tpu/engine/capture_summary.py says what it holds).

- `groups=None`: the seconds of the programs of `kind` (`prefill`, `window`,
  `verify`) from the `XLA Modules` line; with `groups`, XLA's own seconds in
  those programs under the named scope groups — the `scopes` rows, so the
  custom calls (the Pallas kernels, which have rooflines of their own) are
  left out of a group's sum.
- `per="program"`: milliseconds a program of that kind (a decode window);
  `per="prefill_ktok"`: milliseconds per 1,000 prompt tokens, the tokens
  being /debug/timing's `prefill_tokens` over the traced slice
  (`trace_span.timing0 -> timing1`, the convention of the prefill kernels'
  rooflines); `per="op_seconds"`: the groups' share of ALL operation
  seconds, custom calls included on both sides, in percent (`kind=None`:
  every program).

None where the engine served no summary (a parent of PR 52, a failed
reduction, a capture without a device plane) or ran no program of that
kind: the metric is left out. 0.0 where the programs ran and the group is
empty."""
from readers._spans import timing_delta


def read(ctx, kind=None, groups=None, per="program"):
    cap = (ctx.get("device") or {}).get("last_capture")
    if not cap or "error" in cap or not cap.get("chips"):
        return None
    if per == "op_seconds":
        rows = [(r[1], r[3]) for r in cap["scopes"]
                if kind in (None, r[0])]
        rows += [(r[4], r[2]) for r in cap["kernels"]
                 if kind in (None, r[0])]
        total = sum(t for _, t in rows)
        if total <= 0:
            return None
        return 100.0 * sum(t for g, t in rows if g in groups) / total
    prog = cap["programs"].get(kind)
    if not prog or prog["n"] <= 0:
        return None
    if groups is None:
        seconds = prog["device_s"]
    else:
        seconds = sum(r[3] for r in cap["scopes"]
                      if r[0] == kind and r[1] in groups)
    if per == "program":
        return 1e3 * seconds / prog["n"]
    if per != "prefill_ktok":
        raise ValueError(f"per={per!r}")
    span = ctx.get("trace_span") or {}
    if "timing0" not in span or "timing1" not in span:
        return None
    tokens = timing_delta(ctx, "prefill_tokens", span=True)
    return None if tokens <= 0 else 1e6 * seconds / tokens
