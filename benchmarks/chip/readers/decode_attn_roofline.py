"""The decode attention kernel's share of its roofline: the bytes it must
read (live context tokens x KV bytes per token, once per step) over the
chip's HBM bandwidth, divided by the summed device time of the
`paged_decode_attention` events in the trace. Memory-bound by a wide margin
(4 flops per KV byte pair), so bytes set the bound."""
import costs
from readers._spans import decode_steps, live_tokens


def read(ctx):
    t = ctx["trace"]
    if not t or not ctx["peaks"]:
        return None
    seconds = dict(t["device_ops"]).get("paged_decode_attention")
    steps = decode_steps(ctx)
    if not seconds or not steps:
        return None  # no Pallas call in this cell (XLA attention under tp>1)
    span = ctx["trace_span"]
    live = live_tokens(ctx, span["t0"], span["t1"])
    cfg = ctx["config"]
    need = steps * costs.decode_attn_bytes(cfg, live, cfg["kv_dtype"], cfg["tp"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / seconds
