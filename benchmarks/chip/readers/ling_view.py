"""An accepted kernel reader on a model whose layers are KDA layers with one
latent (MLA) layer a period (`costs_ling`): the reader named `of`
(`kda_prefill_roofline`, `kda_decode_roofline`, `mla_prefill_attn_roofline`,
`mla_decode_attn_roofline`) reads the window's documents as they are, and the
configuration through `costs_ling.<view>` — `as_kda`: the KDA layers under
the keys `costs_kda` reads (every other layer under `gqa_layers`); `as_mla`:
`num_hidden_layers` = the MLA layers alone, where `costs_mla` and that
reader's step count multiply by every layer. The kernels, their device-op
names, the counters and the arithmetic are the accepted readers'; only the
LAYER COUNTS are this model's. Another configuration (no `layer_group_size`)
leaves the metric out, and so does whatever leaves the accepted reader's
out (a parent commit without the kernel or the counters)."""
import importlib

import costs_ling


def read(ctx, of, view):
    cfg = ctx["config"]
    if "layer_group_size" not in cfg:
        return None
    reader = importlib.import_module(f"readers.{of}")
    return reader.read({**ctx, "config": getattr(costs_ling, view)(cfg)})
