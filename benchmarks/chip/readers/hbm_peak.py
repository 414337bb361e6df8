"""Peak bytes in use on the fullest chip (`memory_stats()` through
`/debug/programs`), GiB."""


def read(ctx):
    peaks = [d.get("peak_bytes_in_use") for d in ctx["device"]["devices"]]
    peaks = [p for p in peaks if p]
    return max(peaks) / 2 ** 30 if peaks else None
