"""Shared by readers: deltas of the window's documents, and what the
breakdown says about idle gaps. Not a reader itself (no metric names it)."""

from __future__ import annotations

from stack import metric_sum


def metric_delta(ctx: dict, name: str) -> float:
    return (metric_sum(ctx["end"]["metrics"], name)
            - metric_sum(ctx["start"]["metrics"], name))


def timing_delta(ctx: dict, key: str, group: str = "engine",
                 span: bool = False) -> float:
    """Delta of a cumulative `/debug/timing` counter over the window, or
    (span=True) over the traced slice of it."""
    if span:
        a, b = ctx["trace_span"]["timing0"], ctx["trace_span"]["timing1"]
    else:
        a, b = ctx["start"]["timing"], ctx["end"]["timing"]
    return float(b[group][key]) - float(a[group][key])


def idle_gaps(ctx: dict) -> list:
    """What the host was doing while the device could idle, over the traced
    slice: the step thread's phases and the step loop's idle time, host
    clock. Until spans sit in the profiler's own trace (tracing issue), gaps
    cannot be attributed one by one; these totals are what can be said."""
    rows = [[f"host_clock:step_thread:{k}", timing_delta(ctx, k, span=True)]
            for k in ("sync_s", "dispatch_s", "overlap_s", "post_s",
                      "sched_s")]
    rows.append(["host_clock:step_loop:idle_s",
                 timing_delta(ctx, "idle_s", "loop", span=True)])
    return sorted(rows, key=lambda r: -r[1])


def live_tokens(ctx: dict, t0: float, t1: float, step: float = 0.1) -> float:
    """Mean number of context tokens held by requests in flight over
    [t0, t1), from the client's records: a request holds its prompt plus
    the tokens it has received so far, from its first token to its end."""
    total, n, t = 0.0, 0, t0
    while t < t1:
        for r in ctx["records"]:
            if not r.chunk_times or r.chunk_times[0] > t:
                continue
            if (r.done or r.chunk_times[-1]) <= t:
                continue
            total += r.prompt_tokens + r.tokens_between(0.0, t)
        n += 1
        t += step
    return total / max(n, 1)


def decode_steps(ctx: dict) -> float | None:
    """Decode steps inside the traced slice: the decode kernel runs once per
    layer per step, so its event count gives them exactly; without the
    kernel (XLA attention), fused windows times the window length."""
    trace = ctx["trace"]
    layers = ctx["config"]["num_hidden_layers"]
    n = trace["op_counts"].get("paged_decode_attention")
    if n:
        return n / layers
    windows = trace["module_counts"].get("jit_decode_window_fn")
    if windows:
        return windows * ctx["config"]["decode_window"]
    return None
