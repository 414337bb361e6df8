"""A forward pass's share of its roofline in a model that generates by
diffusion over blocks: max((dense weights + the TOUCHED experts' bytes +
the live KV once) / HBM bandwidth, flops of rows x block_length tokens /
bf16 peak) (`costs_bd.py`) divided by the device time of the decode program,
`jit_bd_window_fn`, per pass — the cell's share of a WHOLE step. The passes
are the decode kernel's events over the layers where the trace holds them,
else the program's `bd_passes_n` over the traced slice; the touched share
comes from `moe_decode_touched_n` / `moe_decode_slots_n` there. A program
without the module or the counters (another model, a parent commit) leaves
the metric out."""
import costs
import costs_bd
from readers._spans import live_tokens

KEYS = ("moe_decode_touched_n", "moe_decode_slots_n", "bd_passes_n")


def passes_in_slice(ctx) -> float | None:
    t, span = ctx["trace"], ctx["trace_span"]
    n = t["op_counts"].get("paged_decode_attention")
    if n:
        return n / ctx["config"]["num_hidden_layers"]
    a = span.get("timing0", {}).get("engine", {})
    b = span.get("timing1", {}).get("engine", {})
    if "bd_passes_n" in a and "bd_passes_n" in b:
        return float(b["bd_passes_n"] - a["bd_passes_n"]) or None
    return None


def read(ctx):
    t = ctx["trace"]
    cfg, peak, span = ctx["config"], ctx["peaks"], ctx["trace_span"]
    if not t or not peak or "block_length" not in cfg:
        return None
    a = span.get("timing0", {}).get("engine", {})
    b = span.get("timing1", {}).get("engine", {})
    if not all(k in doc for k in KEYS for doc in (a, b)):
        return None
    slots = b[KEYS[1]] - a[KEYS[1]]
    seconds = dict(t["modules"]).get("jit_bd_window_fn")
    passes = passes_in_slice(ctx)
    if slots <= 0 or not seconds or not passes:
        return None
    touched_share = (b[KEYS[0]] - a[KEYS[0]]) / slots
    live = live_tokens(ctx, span["t0"], span["t1"])
    nbytes = costs_bd.pass_bytes(
        cfg, live, touched_share, cfg["weight_dtype"], cfg["kv_dtype"])
    flops = costs_bd.pass_flops(cfg, cfg["max_num_seqs"], live)
    least, _ = costs.roofline_seconds(flops, nbytes, peak)
    return 100.0 * least / (seconds / passes)
