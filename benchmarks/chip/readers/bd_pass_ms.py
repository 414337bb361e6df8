"""Device milliseconds a forward pass of a window of block passes: the
summed device time of the decode program `jit_bd_window_fn` in the traced
slice over the passes the program counted there (`bd_passes_n`). A program
without the module or the counter leaves the metric out."""


def read(ctx):
    t, span = ctx["trace"], ctx["trace_span"]
    if not t:
        return None
    a = span.get("timing0", {}).get("engine", {})
    b = span.get("timing1", {}).get("engine", {})
    seconds = dict(t["modules"]).get("jit_bd_window_fn")
    if not seconds or "bd_passes_n" not in a or "bd_passes_n" not in b:
        return None
    passes = b["bd_passes_n"] - a["bd_passes_n"]
    return None if passes <= 0 else 1000.0 * seconds / passes
