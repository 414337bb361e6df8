"""The device's idle time that the program's own starvation counter does not
account for, as a share of the traced slice: (idle seconds of the device
trace - increase of `/debug/timing` `starved_s` over the slice) / traced
window x 100. Signed and not clipped: near 0 the host-side counter explains
the idle the device sees; clearly positive is idle inside or between
programs that no host phase explains; negative means the counter counts time
in which the device still ran (enqueue-to-start latency, a rolled-back step).
The two windows differ by the few milliseconds between the capture's start
and the first `/debug/timing` read. None without a device trace or without
the counter."""


def read(ctx):
    trace, span = ctx["trace"], ctx["trace_span"]
    if not trace or "timing0" not in span or "timing1" not in span:
        return None
    a, b = span["timing0"]["engine"], span["timing1"]["engine"]
    if "starved_s" not in a or "starved_s" not in b:
        return None
    starved = float(b["starved_s"]) - float(a["starved_s"])
    return 100.0 * (trace["gap_total_s"] - starved) / trace["window_s"]
