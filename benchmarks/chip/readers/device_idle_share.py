"""1 - (union of device-op intervals) / traced window, from the xplane."""


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
