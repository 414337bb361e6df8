"""The latent decode kernel's share of its roofline: the larger of (live
tokens x latent bytes a token, once per step) over HBM bandwidth and its
absorbed flops over the bf16 peak, divided by the summed device time of the
`mla_decode_attention` events in the trace. A program without that kernel
(another model, a parent commit, XLA attention) leaves the metric out."""
import costs
import costs_mla
from readers._spans import live_tokens


def decode_steps(ctx):
    """The kernel runs once per layer per step."""
    n = ctx["trace"]["op_counts"].get("mla_decode_attention")
    return n / ctx["config"]["num_hidden_layers"] if n else None


def read(ctx):
    t, cfg = ctx["trace"], ctx["config"]
    if not t or not ctx["peaks"] or "kv_lora_rank" not in cfg:
        return None
    seconds = dict(t["device_ops"]).get("mla_decode_attention")
    steps = decode_steps(ctx)
    if not seconds or not steps:
        return None
    span = ctx["trace_span"]
    live = live_tokens(ctx, span["t0"], span["t1"])
    least, _ = costs.roofline_seconds(
        costs_mla.decode_attn_flops(cfg, live),
        costs_mla.decode_attn_bytes(cfg, live, cfg["kv_dtype"]),
        ctx["peaks"])
    return 100.0 * steps * least / seconds
