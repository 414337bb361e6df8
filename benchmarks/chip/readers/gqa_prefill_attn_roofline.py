"""The prefill attention kernel's share of its roofline in a model whose
softmax layers are the `gqa_layers` between KDA layers: the larger of the
flops of the (query, key) pairs the traced slice's prefill chunks cover (the
program's counter `attn_prefill_pairs_n`: a query at position p sees p + 1
keys) over the bf16 peak and the K and V each chunk must read at least once
(`attn_prefill_keys_n`: its context) over HBM bandwidth, in those layers,
divided by the summed device time of the `paged_prefill_attention` events.
A program without the counters or the kernel leaves the metric out."""
import costs
import costs_kda

KERNEL = "paged_prefill_attention"
KEYS = ("attn_prefill_pairs_n", "attn_prefill_keys_n")


def read(ctx):
    t, cfg, span = ctx["trace"], ctx["config"], ctx["trace_span"]
    if not t or not ctx["peaks"] or "linear_attn_config" not in cfg:
        return None
    a = span.get("timing0", {}).get("engine", {})
    b = span.get("timing1", {}).get("engine", {})
    if not all(k in doc for k in KEYS for doc in (a, b)):
        return None
    seconds = dict(t["device_ops"]).get(KERNEL)
    pairs, keys = (b[k] - a[k] for k in KEYS)
    if not seconds or pairs <= 0:
        return None
    least, _ = costs.roofline_seconds(
        costs_kda.gqa_attn_flops(cfg, pairs),
        costs_kda.gqa_attn_bytes(cfg, keys, cfg["kv_dtype"]), ctx["peaks"])
    return 100.0 * least / seconds
