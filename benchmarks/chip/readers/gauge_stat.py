"""Mean or peak of a `/metrics` gauge over the samples taken every 0.25 s
inside the window, optionally as a share of a configuration value."""
from stack import metric_sum


def read(ctx, name, stat="mean", scale=1.0, over_config=None):
    vals = [metric_sum(s, name) for s in ctx["samples"]]
    if not vals:
        return None
    v = max(vals) if stat == "max" else sum(vals) / len(vals)
    if over_config is not None:
        v /= ctx["config"][over_config]
    return scale * v
