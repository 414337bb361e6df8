"""A `/metrics` counter's increase over the window."""
from readers._spans import metric_delta


def read(ctx, name):
    return metric_delta(ctx, name)
