"""A whole decode step's share of its roofline: (every weight once + the
live KV once) over HBM bandwidth, or the step's operations over the bf16
peak where that is larger, divided by the device time of the decode-window
programs per step (XLA Modules line of the trace)."""
import costs
from readers._spans import decode_steps, live_tokens


def read(ctx):
    t = ctx["trace"]
    if not t or not ctx["peaks"]:
        return None
    seconds = dict(t["modules"]).get("jit_decode_window_fn")
    steps = decode_steps(ctx)
    if not seconds or not steps:
        return None
    span, cfg, peak = ctx["trace_span"], ctx["config"], ctx["peaks"]
    live = live_tokens(ctx, span["t0"], span["t1"])
    rows = cfg["max_num_seqs"]  # the one decode bucket: padded rows compute
    nbytes = costs.decode_step_bytes(
        cfg, live, cfg["weight_dtype"], cfg["kv_dtype"], cfg["tp"])
    flops = costs.decode_step_flops(cfg, rows, live, cfg["tp"])
    least, _ = costs.roofline_seconds(flops, nbytes, peak)
    return 100.0 * least / (seconds / steps)
