"""An accepted kernel reader on a model whose EVERY layer holds attention
heads beside state-space heads (`costs_ssd`): the reader named `of`
(`gqa_prefill_attn_roofline`, `gqa_decode_attn_roofline`) reads the window's
documents as they are, and the configuration through `costs_ssd.<view>` —
`as_gqa`: every layer under `gqa_layers`, where `costs_kda`'s GQA
arithmetic multiplies by the layers named there. The kernel, its device-op
name, the counters and the arithmetic are the accepted reader's; only the
LAYER COUNT is this model's. Another configuration (no `mamba_d_state`)
leaves the metric out, and so does whatever leaves the accepted reader's
out (a parent commit that cannot run the model prints no line at all)."""
import importlib

import costs_ssd


def read(ctx, of, view):
    cfg = ctx["config"]
    if "mamba_d_state" not in cfg:
        return None
    reader = importlib.import_module(f"readers.{of}")
    return reader.read({**ctx, "config": getattr(costs_ssd, view)(cfg)})
