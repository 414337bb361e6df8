"""The decode attention kernels' share of their roofline in a model with
window and full layers: the larger of (the K and V a step must read: every
live token's in a full layer, the last min(context, window) tokens' of each
row in a window layer) over HBM bandwidth and the matching flops over the
bf16 peak, times the steps of the traced slice, divided by the summed device
time of the `paged_decode_attention` (full layers) and
`paged_window_decode_attention` (window layers) events. A program without
the window kernel (another model, a parent commit, XLA attention) leaves the
metric out."""
import costs
import costs_swa

WINDOW_KERNEL = "paged_window_decode_attention"
FULL_KERNEL = "paged_decode_attention"


def decode_steps(ctx):
    """The window kernel runs once per window layer per step."""
    n = ctx["trace"]["op_counts"].get(WINDOW_KERNEL)
    layers = len(costs_swa.layers_of(ctx["config"], "sliding_attention"))
    return n / layers if n and layers else None


def live(ctx, t0: float, t1: float, step: float = 0.1):
    """(mean live context tokens, mean of the sum over live rows of
    min(context, sliding_window)) over [t0, t1), from the client's records:
    a request holds its prompt plus the tokens it has received so far, from
    its first token to its end."""
    window = ctx["config"]["sliding_window"]
    full = win = 0.0
    n, t = 0, t0
    while t < t1:
        for r in ctx["records"]:
            if not r.chunk_times or r.chunk_times[0] > t:
                continue
            if (r.done or r.chunk_times[-1]) <= t:
                continue
            held = r.prompt_tokens + r.tokens_between(0.0, t)
            full += held
            win += min(held, window)
        n += 1
        t += step
    return full / max(n, 1), win / max(n, 1)


def read(ctx):
    t, cfg = ctx["trace"], ctx["config"]
    if not t or not ctx["peaks"] or "layer_types" not in cfg:
        return None
    ops = dict(t["device_ops"])
    if WINDOW_KERNEL not in ops:
        return None
    seconds = ops[WINDOW_KERNEL] + ops.get(FULL_KERNEL, 0.0)
    steps = decode_steps(ctx)
    if not seconds or not steps:
        return None
    span = ctx["trace_span"]
    live_full, live_win = live(ctx, span["t0"], span["t1"])
    least, _ = costs.roofline_seconds(
        costs_swa.decode_attn_flops(cfg, live_full, live_win),
        costs_swa.decode_attn_bytes(cfg, live_full, live_win,
                                    cfg["kv_dtype"]),
        ctx["peaks"])
    return 100.0 * steps * least / seconds
