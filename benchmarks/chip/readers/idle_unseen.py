"""The device's idle time that NEITHER of the program's accounts explains, as
a share of the traced slice: (idle seconds of the device trace - increase of
`/debug/timing` `starved_s` - increase of `dry_s` over the slice) / traced
window x 100. `starved_s` is the time in which the host itself had emptied
the device's queue; `dry_s` the lower bound of the time in which the newest
enqueued program was seen complete behind a step the host had not resolved
yet (`dry_s + dry_slack_s` is the upper bound). Signed and not clipped, as
`idle_unattributed_share` is: clearly positive is idle inside or between
programs that no host observation explains (or the width of the dry gaps'
brackets); negative means an account counts time in which the device still
ran. None without a device trace or without either counter."""

KEYS = ("starved_s", "dry_s")


def read(ctx):
    trace, span = ctx["trace"], ctx["trace_span"]
    if not trace or "timing0" not in span or "timing1" not in span:
        return None
    a, b = span["timing0"]["engine"], span["timing1"]["engine"]
    if not all(k in doc for k in KEYS for doc in (a, b)):
        return None
    seen = sum(float(b[k]) - float(a[k]) for k in KEYS)
    return 100.0 * (trace["gap_total_s"] - seen) / trace["window_s"]
