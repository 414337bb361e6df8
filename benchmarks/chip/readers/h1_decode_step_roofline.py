"""A decode step's share of its roofline in a model whose every layer holds
attention heads AND state-space heads: max((every layer's attention, mixer
and SwiGLU weights and the head once + the live tokens' K and V in every
layer + the live rows' state, read and written, and their tails) / HBM
bandwidth, flops / bf16 peak) divided by the device time of
`jit_decode_window_fn` per step. The live rows come from the program's
counter over the traced slice (`ssd_decode_rows_n`) over the steps the SSD
decode kernel's events count; a program without them leaves the metric
out."""
import costs
import costs_ssd
from readers._spans import live_tokens
from readers.ssd_decode_roofline import decode_steps

KEY = "ssd_decode_rows_n"


def read(ctx):
    t = ctx["trace"]
    cfg, peak, span = ctx["config"], ctx["peaks"], ctx["trace_span"]
    if not t or not peak or "mamba_d_state" not in cfg:
        return None
    a = span.get("timing0", {}).get("engine", {})
    b = span.get("timing1", {}).get("engine", {})
    if KEY not in a or KEY not in b:
        return None
    row_steps = b[KEY] - a[KEY]
    seconds = dict(t["modules"]).get("jit_decode_window_fn")
    steps = decode_steps(ctx)
    if not seconds or not steps or row_steps <= 0:
        return None
    # (the counter's live rows x steps over the kernel events' steps: where
    # the two disagree, the share shows it)
    live_rows = row_steps / steps
    live = live_tokens(ctx, span["t0"], span["t1"])
    nbytes = costs_ssd.decode_step_bytes(
        cfg, live, live_rows, cfg["weight_dtype"], cfg["kv_dtype"])
    flops = costs_ssd.decode_step_flops(
        cfg, cfg["max_num_seqs"], live, live_rows)
    least, _ = costs.roofline_seconds(flops, nbytes, peak)
    return 100.0 * least / (seconds / steps)
