"""Ratio of two cumulative `/debug/timing` counters over the window, e.g.
seconds blocked on decode dispatches per fused decode step."""
from readers._spans import timing_delta


def read(ctx, num, den, scale=1.0, den_times_window=False):
    d = timing_delta(ctx, den)
    if den_times_window:
        d *= ctx["config"]["decode_window"]
    return None if d <= 0 else scale * timing_delta(ctx, num) / d
