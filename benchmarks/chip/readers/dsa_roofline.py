"""A selected-keys op's share of its roofline (`costs_dsa`): the least work
an exact form does — from the program's `dsa_*` counters over the traced
slice — over the peaks, divided by the device seconds the engine's own
summary of the capture books to the op in the programs of its kind
(`ctx["device"]["last_capture"]`): XLA's own operations under the op's named
scopes (`scopes` rows [kind, group, scope, seconds, ops]; selection included:
the indexer's op is its scores AND its top-k, the attention's its gather AND
its softmax) PLUS the custom calls of the op's kernel family (`kernels` rows
[kind, family, seconds, n, group]: the summary books a Pallas kernel there
and never to `scopes`, whatever scope it runs under). The day an op becomes
a kernel its seconds move from the one list to the other and the share reads
on. The kernel that takes an op over is NAMED for it (`KERNELS`, the repo's
`kda_prefill_attention` / `mla_decode_attention` pattern):
`dsa_index_prefill`, `dsa_prefill_attention`, `dsa_index_decode`,
`dsa_decode_attention`; a kernel under another name is not counted, and the
share then reads too high.

`op`: `index_prefill` | `prefill_attn` (a chunk: compute-bound, its keys'
bytes shared by a tile's queries — the indexer's context keys once are
counted, the latents' are not) | `index_decode` | `decode_attn` (a step: the
larger of bytes and flops) | `decode_step` (the whole of a decode window's
device time a step against weights read + touched experts + those bytes).

None where the program serves no such counter or summary (a parent commit, a
CPU engine), runs another model, or the slice held no program of the kind."""
import costs
import costs_dsa
from readers._spans import timing_delta

SCOPES = {
    "index_prefill": ("prefill", ("dsa_index_score", "dsa_select")),
    "prefill_attn": ("prefill", ("dsa_attn",)),
    "index_decode": ("window", ("dsa_index_score", "dsa_select")),
    "decode_attn": ("window", ("dsa_gather", "dsa_attn")),
    "decode_step": ("window", None),
}
KERNELS = {
    "index_prefill": ("dsa_index_prefill",),
    "prefill_attn": ("dsa_prefill_attention",),
    "index_decode": ("dsa_index_decode",),
    "decode_attn": ("dsa_decode_attention",),
}
NEEDS = ("dsa_index_pairs_n", "dsa_selected_keys_n",
         "dsa_decode_index_pairs_n", "dsa_decode_selected_keys_n")


def read(ctx, op):
    cfg, peak = ctx["config"], ctx.get("peaks")
    cap = (ctx.get("device") or {}).get("last_capture")
    span = ctx.get("trace_span") or {}
    if (not peak or "indexer_types" not in cfg or not cap or "error" in cap
            or not cap.get("chips")):
        return None
    a = span.get("timing0", {}).get("engine", {})
    b = span.get("timing1", {}).get("engine", {})
    if not all(k in doc for k in NEEDS for doc in (a, b)):
        return None
    kind, scopes = SCOPES[op]
    prog = cap["programs"].get(kind)
    if not prog or prog["n"] <= 0:
        return None
    if scopes is None:
        seconds = prog["device_s"]
    else:
        seconds = sum(r[3] for r in cap["scopes"]
                      if r[0] == kind and r[2] in scopes)
        seconds += sum(r[2] for r in cap["kernels"]
                       if r[0] == kind and r[1] in KERNELS[op])
    if seconds <= 0:
        return None

    def d(key):
        return timing_delta(ctx, key, span=True)

    step_pairs, step_selected = (d("dsa_decode_index_pairs_n"),
                                 d("dsa_decode_selected_keys_n"))
    kv = cfg["kv_dtype"]
    if op == "index_prefill":
        flops = costs_dsa.index_flops(
            cfg, d("dsa_index_pairs_n") - step_pairs)
        nbytes = costs_dsa.prefill_index_bytes(
            cfg, d("attn_prefill_keys_n"), kv)
    elif op == "prefill_attn":
        flops = costs_dsa.attn_flops(
            cfg, d("dsa_selected_keys_n") - step_selected)
        nbytes = 0.0
    elif op == "index_decode":
        flops = costs_dsa.index_flops(cfg, step_pairs)
        nbytes = costs_dsa.decode_index_bytes(cfg, step_pairs, kv)
    elif op == "decode_attn":
        flops = costs_dsa.attn_flops(cfg, step_selected)
        nbytes = costs_dsa.decode_attn_bytes(cfg, step_selected, kv)
    else:
        moe = ("moe_decode_touched_n", "moe_decode_held_pairs_n")
        if not all(k in doc for k in moe for doc in (a, b)):
            return None
        # (the slice's windows, all of their steps together)
        steps = prog["n"] * cfg["decode_window"]
        flops = costs_dsa.decode_steps_flops(
            cfg, steps, cfg["max_num_seqs"], d(moe[1]), step_selected,
            step_pairs)
        nbytes = costs_dsa.decode_steps_bytes(
            cfg, steps, d(moe[0]), step_selected, step_pairs,
            cfg["weight_dtype"], kv)
    if flops <= 0 and nbytes <= 0:
        return None
    least, _ = costs.roofline_seconds(flops, nbytes, peak)
    return 100.0 * least / seconds
