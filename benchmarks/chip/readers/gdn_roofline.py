"""The five shares of a roofline of a model whose layers are Gated DeltaNet
layers between full-attention layers (`model_type: olmo_hybrid`;
`costs_gdn`), ONE reader, `which` a data file's argument:

- `gdn_prefill`: the chunked recurrence kernel — the larger of the chunkwise
  algorithm's flops over the bf16 peak and its bytes over HBM bandwidth, for
  the tokens and row segments the program counted over the traced slice
  (`kda_prefill_tokens_n`, `kda_prefill_segments_n`: the engine counts its
  delta-rule layers under those names whatever their gate), over the summed
  device time of the `gdn_prefill_attention` events;
- `gdn_decode`: the decode-step kernel — (each row's state read once and
  written once + its vectors) for the rows the kernel RAN (the decode
  bucket's rows x the steps its events count: a padding row moves the null
  slot's state), over the `gdn_decode_attention` events;
- `prefill_attn` / `decode_attn`: the paged kernels in the full layers
  (`paged_prefill_attention` from `attn_prefill_pairs_n` /
  `attn_prefill_keys_n`; `paged_decode_attention`, one event a full layer and
  step, from the live tokens);
- `decode_step`: the whole step — the larger of (weights + the live tokens' K
  and V + the live rows' state in and out) over HBM bandwidth and the flops
  over the bf16 peak, over the device time of `jit_decode_window_fn` a step.

Every count is of the PUBLISHED geometry. A program without the kernel or
the counters (another model, a parent commit, XLA attention) leaves the
metric out."""
import costs
import costs_gdn
from readers._spans import live_tokens

PREFILL, DECODE = "gdn_prefill_attention", "gdn_decode_attention"


def _delta(ctx, keys):
    span = ctx["trace_span"]
    a = span.get("timing0", {}).get("engine", {})
    b = span.get("timing1", {}).get("engine", {})
    if not all(k in doc for k in keys for doc in (a, b)):
        return None
    return [b[k] - a[k] for k in keys]


def _steps(ctx, kernel, layers):
    """Decode steps of the traced slice: `kernel` runs once a layer of
    `layers` and step."""
    n = ctx["trace"]["op_counts"].get(kernel)
    return n / len(layers) if n and layers else None


def _share(ctx, flops, nbytes, seconds):
    least, _ = costs.roofline_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * least / seconds


def read(ctx, which):
    t, cfg = ctx["trace"], ctx["config"]
    if (not t or not ctx["peaks"]
            or cfg.get("model_type") != "olmo_hybrid"):
        return None
    ops, act, kvd = dict(t["device_ops"]), cfg["weight_dtype"], cfg["kv_dtype"]
    lin, full = costs_gdn.linear_layers(cfg), costs_gdn.full_layers(cfg)
    if which == "gdn_prefill":
        seconds = ops.get(PREFILL)
        got = _delta(ctx, ("kda_prefill_tokens_n", "kda_prefill_segments_n"))
        if not seconds or not got or got[0] <= 0:
            return None
        tokens, segments = got
        return _share(ctx, costs_gdn.gdn_prefill_flops(cfg, tokens),
                      costs_gdn.gdn_prefill_bytes(cfg, tokens, segments, act),
                      seconds)
    if which == "gdn_decode":
        seconds, steps = ops.get(DECODE), _steps(ctx, DECODE, lin)
        if not seconds or not steps:
            return None
        rows = steps * cfg["max_num_seqs"]
        return _share(ctx, costs_gdn.gdn_decode_flops(cfg, rows),
                      costs_gdn.gdn_decode_bytes(cfg, rows, act), seconds)
    if which == "prefill_attn":
        seconds = ops.get("paged_prefill_attention")
        got = _delta(ctx, ("attn_prefill_pairs_n", "attn_prefill_keys_n"))
        if not seconds or not got or got[0] <= 0:
            return None
        pairs, keys = got
        return _share(ctx, costs_gdn.full_attn_flops(cfg, pairs),
                      costs_gdn.full_attn_bytes(cfg, keys, kvd), seconds)
    span = ctx["trace_span"]
    if which == "decode_attn":
        seconds = ops.get("paged_decode_attention")
        steps = _steps(ctx, "paged_decode_attention", full)
        if not seconds or not steps:
            return None
        live = live_tokens(ctx, span["t0"], span["t1"])
        return steps * _share(
            ctx, costs_gdn.full_attn_flops(cfg, live),
            costs_gdn.full_attn_bytes(cfg, live, kvd), seconds)
    if which == "decode_step":
        seconds = dict(t["modules"]).get("jit_decode_window_fn")
        steps = _steps(ctx, DECODE, lin)
        got = _delta(ctx, ("kda_decode_rows_n",))
        if not seconds or not steps or not got or got[0] <= 0:
            return None
        # (the counter's live rows x steps over the kernel events' steps)
        live_rows = got[0] / steps
        live = live_tokens(ctx, span["t0"], span["t1"])
        return _share(
            ctx,
            costs_gdn.decode_step_flops(
                cfg, cfg["max_num_seqs"], live, live_rows),
            costs_gdn.decode_step_bytes(cfg, live, live_rows, act, kvd),
            seconds / steps)
    raise ValueError(f"gdn_roofline: no share named {which!r}")
