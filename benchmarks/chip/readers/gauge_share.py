"""Mean over the samples taken every 0.25 s inside the window of one
`/metrics` gauge's share of the sum of itself and another, times `scale`:
`part` / (`part` + `rest`). A sample in which both read 0 is left out; a
program that serves no gauge of the name `part` (a parent commit) leaves the
metric out."""
from stack import metric_sum


def read(ctx, part, rest, scale=1.0):
    served = any(key == part or key.startswith(part + "{")
                 for s in ctx["samples"] for key in s)
    if not served:
        return None
    shares = []
    for s in ctx["samples"]:
        a, b = metric_sum(s, part), metric_sum(s, rest)
        if a + b > 0:
            shares.append(a / (a + b))
    return scale * sum(shares) / len(shares) if shares else None
