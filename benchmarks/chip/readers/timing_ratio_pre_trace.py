"""A ratio of `/debug/timing` counters over the part of a traced run's window
in which no profiler is attached: from the window's open (`start.timing`) to
the capture's start (`trace_span.timing0`), about 21 s of a 51-s window. The
profiler's Python tracer stretches host spans, so a host-side share read over
the traced slice is an upper figure for the untraced runs that are judged;
this is the same share without it. Sum of the increases of `nums` over the
increase of `den`, times `scale`. None where the run has no traced slice or
either document lacks a key (a parent commit that predates a counter)."""


def read(ctx, nums, den, scale=1.0, group="engine"):
    span = ctx.get("trace_span") or {}
    if "timing0" not in span:
        return None
    a, b = ctx["start"]["timing"][group], span["timing0"][group]
    if not all(k in doc for k in (*nums, den) for doc in (a, b)):
        return None
    d = float(b[den]) - float(a[den])
    if d <= 0:
        return None
    return scale * sum(float(b[k]) - float(a[k]) for k in nums) / d
