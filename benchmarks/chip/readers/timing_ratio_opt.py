"""`timing_ratio` for `/debug/timing` counters that a program may not serve
yet: where either counter is missing (a parent commit that predates it), the
metric is left out instead of raising. Ratio of the two counters' increases
over the window, times `scale`."""


def read(ctx, num, den, scale=1.0, group="engine"):
    a, b = ctx["start"]["timing"][group], ctx["end"]["timing"][group]
    if not all(k in doc for k in (num, den) for doc in (a, b)):
        return None
    d = float(b[den]) - float(a[den])
    return None if d <= 0 else scale * (float(b[num]) - float(a[num])) / d
