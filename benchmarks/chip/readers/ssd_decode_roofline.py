"""The SSD decode kernel's share of its roofline: the larger of (each row's
state read once and written once, plus its vectors) over HBM bandwidth and
the recurrence's flops over the bf16 peak, for the rows the kernel RAN —
the decode bucket's rows (`max_num_seqs`: the cell has one bucket) x the
steps its events count; a padding row moves the null slot's state as a live
row moves its own — divided by the summed device time of the
`ssd_decode_step` events. How many of those rows were live is
`seat_occupancy`'s to say, not this metric's. A program without the kernel
leaves the metric out."""
import costs
import costs_ssd

KERNEL = "ssd_decode_step"


def decode_steps(ctx):
    """The kernel runs once per layer per step."""
    n = ctx["trace"]["op_counts"].get(KERNEL)
    layers = ctx["config"]["num_hidden_layers"]
    return n / layers if n and layers else None


def read(ctx):
    t, cfg = ctx["trace"], ctx["config"]
    if not t or not ctx["peaks"] or "mamba_d_state" not in cfg:
        return None
    seconds = dict(t["device_ops"]).get(KERNEL)
    steps = decode_steps(ctx)
    if not seconds or not steps:
        return None
    rows = steps * cfg["max_num_seqs"]
    least, _ = costs.roofline_seconds(
        costs_ssd.ssd_decode_flops(cfg, rows),
        costs_ssd.ssd_decode_bytes(cfg, rows, cfg["weight_dtype"]),
        ctx["peaks"])
    return 100.0 * least / seconds
