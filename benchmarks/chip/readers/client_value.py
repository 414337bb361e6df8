"""A value the client side already computed for the window (a steadier or
secondary statistic that stands beside an end-to-end metric)."""


def read(ctx, name):
    return ctx["values"].get(name)
