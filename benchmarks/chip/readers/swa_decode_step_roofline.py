"""A decode step's share of its roofline in a model with window and full
layers and a share of its experts: max((attention matrices at each layer's
head count, the dense layer's MLP, routers, shared experts and the head's
slice once + the TOUCHED held experts + the KV a step must read) / HBM
bandwidth, flops / bf16 peak) divided by the device time of
`jit_decode_window_fn` per step. The touched share and the pairs that landed
on held experts come from the program's counters over the traced slice
(`moe_decode_touched_n` / `moe_decode_slots_n`, slots over HELD experts;
`moe_decode_held_pairs_n`); a program without them or without the window
kernel leaves the metric out."""
import costs
import costs_swa
from readers.swa_decode_attn_roofline import decode_steps, live

KEYS = ("moe_decode_touched_n", "moe_decode_slots_n",
        "moe_decode_held_pairs_n")


def read(ctx):
    t = ctx["trace"]
    cfg, peak, span = ctx["config"], ctx["peaks"], ctx["trace_span"]
    if not t or not peak or "layer_types" not in cfg:
        return None
    a = span.get("timing0", {}).get("engine", {})
    b = span.get("timing1", {}).get("engine", {})
    if not all(k in doc for k in KEYS for doc in (a, b)):
        return None
    touched, slots, held_pairs = (b[k] - a[k] for k in KEYS)
    seconds = dict(t["modules"]).get("jit_decode_window_fn")
    steps = decode_steps(ctx)
    if slots <= 0 or not seconds or not steps:
        return None
    rows = cfg["max_num_seqs"]
    # pairs a row and expert layer that landed here (padded rows included:
    # the program computes them)
    layer_steps = slots / cfg["num_experts"]
    pairs_per_row = held_pairs / (layer_steps * rows)
    live_full, live_win = live(ctx, span["t0"], span["t1"])
    nbytes = costs_swa.decode_step_bytes(
        cfg, live_full, live_win, touched / slots, cfg["weight_dtype"],
        cfg["kv_dtype"])
    flops = costs_swa.decode_step_flops(
        cfg, rows, pairs_per_row, live_full, live_win)
    least, _ = costs.roofline_seconds(flops, nbytes, peak)
    return 100.0 * least / (seconds / steps)
