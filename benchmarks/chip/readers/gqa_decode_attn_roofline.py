"""The decode attention kernel's share of its roofline in a model whose
softmax layers are the `gqa_layers` between KDA layers: the larger of (the
live tokens' K and V in those layers, read once a step) over HBM bandwidth
and the matching flops over the bf16 peak, times the steps of the traced
slice, divided by the summed device time of the `paged_decode_attention`
events (one a GQA layer and step). A program without the kernel (another
model has no `gqa_layers`; XLA attention) leaves the metric out."""
import costs
import costs_kda
from readers._spans import live_tokens

KERNEL = "paged_decode_attention"


def read(ctx):
    t, cfg = ctx["trace"], ctx["config"]
    if not t or not ctx["peaks"] or "linear_attn_config" not in cfg:
        return None
    seconds = dict(t["device_ops"]).get(KERNEL)
    events = t["op_counts"].get(KERNEL)
    if not seconds or not events:
        return None
    steps = events / len(costs_kda.gqa_layers(cfg))
    span = ctx["trace_span"]
    live = live_tokens(ctx, span["t0"], span["t1"])
    least, _ = costs.roofline_seconds(
        costs_kda.gqa_attn_flops(cfg, live),
        costs_kda.gqa_attn_bytes(cfg, live, cfg["kv_dtype"]), ctx["peaks"])
    return 100.0 * steps * least / seconds
