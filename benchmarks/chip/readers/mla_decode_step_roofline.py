"""A latent-attention MoE decode step's share of its roofline: max((the
attention matrices, the dense layer's MLP, shared experts, routers and head
once + the TOUCHED routed experts + the live latents once) / HBM bandwidth,
flops / bf16 peak) divided by the device time of `jit_decode_window_fn` per
step. The touched share comes from the program's counters over the traced
slice (`moe_decode_touched_n` / `moe_decode_slots_n`, slots over expert
layers); a program without them or without the latent kernel leaves the
metric out."""
import costs
import costs_mla
from readers._spans import live_tokens
from readers.mla_decode_attn_roofline import decode_steps


def read(ctx):
    t = ctx["trace"]
    cfg, peak, span = ctx["config"], ctx["peaks"], ctx["trace_span"]
    if not t or not peak or "kv_lora_rank" not in cfg:
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
    nbytes = costs_mla.decode_step_bytes(
        cfg, live, touched_share, cfg["weight_dtype"], cfg["kv_dtype"])
    flops = costs_mla.decode_step_flops(cfg, cfg["max_num_seqs"], live)
    least, _ = costs.roofline_seconds(flops, nbytes, peak)
    return 100.0 * least / (seconds / steps)
