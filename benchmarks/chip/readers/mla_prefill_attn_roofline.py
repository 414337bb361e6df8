"""The latent prefill kernel's share of its roofline: the absorbed flops of
the (query, key) pairs the traced slice's prefill chunks cover (the
program's counter `attn_prefill_pairs_n`, x layers x heads x 2 x ((rank +
rope) + rank)) over the bf16 peak, divided by the summed device time of the
`mla_prefill_attention` events. Compute-bound by a wide margin (a latent is
read once per query tile of 128 tokens x 32 heads). A program without the
counter or the kernel leaves the metric out."""
import costs_mla


def read(ctx):
    t, cfg, span = ctx["trace"], ctx["config"], ctx["trace_span"]
    if not t or not ctx["peaks"] or "kv_lora_rank" not in cfg:
        return None
    a = span.get("timing0", {}).get("engine", {})
    b = span.get("timing1", {}).get("engine", {})
    key = "attn_prefill_pairs_n"
    seconds = dict(t["device_ops"]).get("mla_prefill_attention")
    if key not in a or key not in b or not seconds:
        return None
    pairs = b[key] - a[key]
    if pairs <= 0:
        return None
    need = costs_mla.prefill_attn_flops(cfg, pairs)
    return 100.0 * need / ctx["peaks"]["bf16_flops_per_s"] / seconds
