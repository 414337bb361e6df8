"""The prefill chunks' routed experts against their roofline: the larger of
the touched experts' bytes (the program's counter `moe_prefill_touched_n`
over the traced slice x one expert's three matrices) over HBM bandwidth and
the flops of the pairs that landed on held experts
(`moe_prefill_held_pairs_n` x 6 x hidden x inner) over the bf16 peak,
divided by the summed device time of the operations that compute them: XLA's
grouped matmul (`ragged-dot-none`, three calls a layer) and the fused kernel
(`moe_prefill_experts`) — the same work whichever implements it. A program
without the counters, a model without routed experts of their own width, or
a slice that holds neither operation leaves the metric out."""
import costs
import costs_gmm

KEYS = ("moe_prefill_touched_n", "moe_prefill_held_pairs_n")
OPS = ("ragged-dot-none", "moe_prefill_experts")


def read(ctx):
    t, cfg, span = ctx["trace"], ctx["config"], ctx["trace_span"]
    if not t or not ctx["peaks"] or "moe_intermediate_size" not in cfg:
        return None
    a = span.get("timing0", {}).get("engine", {})
    b = span.get("timing1", {}).get("engine", {})
    if not all(k in doc for k in KEYS for doc in (a, b)):
        return None
    ops = dict(t["device_ops"])
    seconds = sum(ops.get(name, 0.0) for name in OPS)
    touched, held_pairs = (b[k] - a[k] for k in KEYS)
    if touched <= 0 or not seconds:
        return None
    least, _ = costs.roofline_seconds(
        costs_gmm.prefill_expert_flops(cfg, held_pairs),
        costs_gmm.prefill_expert_bytes(cfg, touched, cfg["weight_dtype"]),
        ctx["peaks"])
    return 100.0 * least / seconds
