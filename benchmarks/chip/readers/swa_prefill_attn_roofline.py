"""The prefill attention kernels' share of their roofline in a model with
window and full layers: the larger of the flops of the (query, key) pairs
the traced slice's prefill chunks cover — the program's counters
`attn_prefill_pairs_n` (full layers) and `attn_prefill_window_pairs_n`
(window layers: a query sees min(p + 1, window) keys), x the layers' heads x
4 x head_dim — over the bf16 peak, and the K and V each chunk must read at
least once (`attn_prefill_keys_n`, `attn_prefill_window_keys_n`) over HBM
bandwidth, divided by the summed device time of the
`paged_prefill_attention` and `paged_window_prefill_attention` events. A
program without the counters or the window kernel leaves the metric out."""
import costs
import costs_swa

KEYS = ("attn_prefill_pairs_n", "attn_prefill_window_pairs_n",
        "attn_prefill_keys_n", "attn_prefill_window_keys_n")


def read(ctx):
    t, cfg, span = ctx["trace"], ctx["config"], ctx["trace_span"]
    if not t or not ctx["peaks"] or "layer_types" not in cfg:
        return None
    a = span.get("timing0", {}).get("engine", {})
    b = span.get("timing1", {}).get("engine", {})
    ops = dict(t["device_ops"])
    if "paged_window_prefill_attention" not in ops or not all(
            k in doc for k in KEYS for doc in (a, b)):
        return None
    seconds = (ops["paged_window_prefill_attention"]
               + ops.get("paged_prefill_attention", 0.0))
    pairs, wpairs, keys, wkeys = (b[k] - a[k] for k in KEYS)
    if pairs <= 0 or not seconds:
        return None
    least, _ = costs.roofline_seconds(
        costs_swa.prefill_attn_flops(cfg, pairs, wpairs),
        costs_swa.prefill_attn_bytes(cfg, keys, wkeys, cfg["kv_dtype"]),
        ctx["peaks"])
    return 100.0 * least / seconds
