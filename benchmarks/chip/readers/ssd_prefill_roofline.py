"""The chunked SSD prefill kernel's share of its roofline: the larger of the
chunkwise algorithm's flops over the bf16 peak and its bytes over HBM
bandwidth (`costs_ssd`), for the tokens and row segments the program counted
over the traced slice (`ssd_prefill_tokens_n`, `ssd_prefill_segments_n`),
divided by the summed device time of the `ssd_prefill_scan` events. A
program without the kernel or the counters (another model, XLA attention)
leaves the metric out."""
import costs
import costs_ssd

KERNEL = "ssd_prefill_scan"
KEYS = ("ssd_prefill_tokens_n", "ssd_prefill_segments_n")


def read(ctx):
    t, cfg, span = ctx["trace"], ctx["config"], ctx["trace_span"]
    if not t or not ctx["peaks"] or "mamba_d_state" not in cfg:
        return None
    a = span.get("timing0", {}).get("engine", {})
    b = span.get("timing1", {}).get("engine", {})
    if not all(k in doc for k in KEYS for doc in (a, b)):
        return None
    seconds = dict(t["device_ops"]).get(KERNEL)
    tokens, segments = (b[k] - a[k] for k in KEYS)
    if not seconds or tokens <= 0:
        return None
    least, _ = costs.roofline_seconds(
        costs_ssd.ssd_prefill_flops(cfg, tokens),
        costs_ssd.ssd_prefill_bytes(cfg, tokens, segments,
                                    cfg["weight_dtype"]),
        ctx["peaks"])
    return 100.0 * least / seconds
