"""A sparse-MoE decode step's share of its roofline: max((attention, router
and head weights + the TOUCHED experts' bytes + live KV) / HBM bandwidth,
flops / bf16 peak) divided by the device time of `jit_decode_window_fn` per
step. The touched share comes from the program's counters over the traced
slice (`moe_decode_touched_n` / `moe_decode_slots_n`); a program without
them (a dense model, a parent commit) leaves the metric out."""
import costs
import costs_moe
from readers._spans import decode_steps, live_tokens


def read(ctx):
    t = ctx["trace"]
    cfg, peak, span = ctx["config"], ctx["peaks"], ctx["trace_span"]
    if not t or not peak or not cfg.get("num_experts"):
        return None
    a = span.get("timing0", {}).get("engine", {})
    b = span.get("timing1", {}).get("engine", {})
    keys = ("moe_decode_touched_n", "moe_decode_slots_n")
    if not all(k in doc for k in keys for doc in (a, b)):
        return None
    slots = b[keys[1]] - a[keys[1]]
    seconds = dict(t["modules"]).get("jit_decode_window_fn")
    steps = decode_steps(ctx)
    if slots <= 0 or not seconds or not steps:
        return None
    touched_share = (b[keys[0]] - a[keys[0]]) / slots
    live = live_tokens(ctx, span["t0"], span["t1"])
    nbytes = costs_moe.decode_step_bytes(
        cfg, live, touched_share, cfg["weight_dtype"], cfg["kv_dtype"])
    flops = costs_moe.decode_step_flops(cfg, cfg["max_num_seqs"], live)
    least, _ = costs.roofline_seconds(flops, nbytes, peak)
    return 100.0 * least / (seconds / steps)
