"""A decode step's share of its roofline in a model of KDA and latent layers
with a leading dense layer and a share of its experts (`costs_ling`):
max((the mixers' weights by kind, the dense MLP, routers, shared experts and
the head's slice once + the TOUCHED held experts + the live tokens' latents
in the MLA layers + the live rows' state, read and written, in the KDA
layers) / HBM bandwidth, flops / bf16 peak) divided by the device time of
`jit_decode_window_fn` per step. The touched share and the pairs that landed
on held experts come from the program's counters over the traced slice
(`moe_decode_touched_n` / `moe_decode_slots_n`, slots over HELD experts and
expert layers; `moe_decode_held_pairs_n`), the live rows from
`kda_decode_rows_n` over the steps the KDA decode kernel's events count; a
program without them leaves the metric out."""
import costs
import costs_ling
from readers._spans import live_tokens
from readers.kda_decode_roofline import decode_steps

KEYS = ("moe_decode_touched_n", "moe_decode_slots_n",
        "moe_decode_held_pairs_n", "kda_decode_rows_n")


def read(ctx):
    t = ctx["trace"]
    cfg, peak, span = ctx["config"], ctx["peaks"], ctx["trace_span"]
    if not t or not peak or "layer_group_size" not in cfg:
        return None
    a = span.get("timing0", {}).get("engine", {})
    b = span.get("timing1", {}).get("engine", {})
    if not all(k in doc for k in KEYS for doc in (a, b)):
        return None
    touched, slots, held_pairs, row_steps = (b[k] - a[k] for k in KEYS)
    seconds = dict(t["modules"]).get("jit_decode_window_fn")
    # (the KDA decode kernel runs once per KDA layer per step)
    steps = decode_steps({**ctx, "config": costs_ling.as_kda(cfg)})
    if slots <= 0 or not seconds or not steps or row_steps <= 0:
        return None
    rows = cfg["max_num_seqs"]
    # pairs a row and EXPERT layer that landed here (padded rows included:
    # the program computes them)
    layer_steps = slots / cfg["num_experts"]
    pairs_per_row = held_pairs / (layer_steps * rows)
    live_rows = row_steps / steps
    live = live_tokens(ctx, span["t0"], span["t1"])
    nbytes = costs_ling.decode_step_bytes(
        cfg, live, live_rows, touched / slots, cfg["weight_dtype"],
        cfg["kv_dtype"])
    flops = costs_ling.decode_step_flops(
        cfg, rows, pairs_per_row, live, live_rows)
    least, _ = costs.roofline_seconds(flops, nbytes, peak)
    return 100.0 * least / (seconds / steps)
