"""Operations and bytes a model with WINDOW and FULL attention layers, a
per-head output gate and a share of its experts needs, from the published
keys (`layer_types`, `num_attention_heads_per_layer`, `sliding_window`,
`mlp_layer_types`, ...) and from what a step touched. Beside `costs.py`,
which reads one head count, one MLP and a full-length cache in every layer.

KV. A token's K and V in one layer are 2 x kv heads x head_dim values. A
decode step must read, once: in a FULL layer every live context token's; in
a WINDOW layer, per row, the last min(context, sliding_window) tokens'
(`live_window_tokens`: the sum of that over the live rows). These are the
values the algorithm needs: a program that reads whole pages reads up to a
page more at each end of a row's window (32-token pages: up to 12% of 512),
and that rounding is the program's cost, not the need's.

Weights a decode step must read, once: every layer's attention matrices at
the layer's own head count (W_q, W_k, W_v, W_o and the gate W_g), the dense
layers' MLP, each expert layer's router (at its published width) and shared
expert, the head's slice — and the routed experts HELD here that got at
least one (token, expert) pair (the program counts them:
`moe_decode_touched_n` of `moe_decode_slots_n`, slots over held experts).

Prefill attention flops: 4 x head_dim per (query, key) pair and query head
(QK^T and PV), the pairs counted by the program per layer kind:
`attn_prefill_pairs_n` (a query at p sees p + 1 keys) for the full layers,
`attn_prefill_window_pairs_n` (min(p + 1, window)) for the window layers.
Its bytes: the keys each chunk must read at least once
(`attn_prefill_keys_n`, `attn_prefill_window_keys_n`).
"""

from __future__ import annotations

import costs


def layers_of(model: dict, kind: str) -> list[int]:
    """Indices of the layers of one kind ("full_attention" |
    "sliding_attention")."""
    return [i for i, k in enumerate(model["layer_types"]) if k == kind]


def heads_of(model: dict, kind: str) -> int:
    """Query heads summed over the layers of one kind."""
    return sum(model["num_attention_heads_per_layer"][i]
               for i in layers_of(model, kind))


def decode_attn_bytes(model: dict, live_tokens: float,
                      live_window_tokens: float,
                      kv_dtype: str = "bfloat16") -> float:
    """Bytes one step's decode attention must read over all layers."""
    # (4,096 B for Laguna-S-2.1 in bf16: 8 kv heads x 128, K and V)
    per = costs.kv_bytes_per_token_per_layer(model, kv_dtype)
    return per * (
        len(layers_of(model, "full_attention")) * live_tokens
        + len(layers_of(model, "sliding_attention")) * live_window_tokens)


def decode_attn_flops(model: dict, live_tokens: float,
                      live_window_tokens: float) -> float:
    """QK^T and PV of one new token a row against what it sees, each layer
    at its own head count."""
    return 4.0 * model["head_dim"] * (
        heads_of(model, "full_attention") * live_tokens
        + heads_of(model, "sliding_attention") * live_window_tokens)


def prefill_attn_flops(model: dict, pairs: float,
                       window_pairs: float) -> float:
    """The same arithmetic per (query, key) pair."""
    return decode_attn_flops(model, pairs, window_pairs)


def prefill_attn_bytes(model: dict, keys: float, window_keys: float,
                       kv_dtype: str = "bfloat16") -> float:
    """The keys (and values) every chunk must read at least once."""
    return decode_attn_bytes(model, keys, window_keys, kv_dtype)


def attn_params(model: dict) -> int:
    """Every layer's W_q, W_k, W_v, W_o and gate at its own head count."""
    h, d = model["hidden_size"], model["head_dim"]
    kv = 2 * h * model["num_key_value_heads"] * d
    gate = h if model.get("gating") == "per-head" else 0
    return sum(kv + nh * (2 * h * d + gate)
               for nh in model["num_attention_heads_per_layer"])


def expert_layers(model: dict) -> int:
    return sum(k == "sparse" for k in model["mlp_layer_types"])


def expert_params(model: dict) -> int:
    """ONE routed expert: gate, up, down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def always_read_params(model: dict) -> int:
    """What every decode step reads whatever the routing: attention, the
    dense layers' MLP, routers (published width: `num_experts_published`
    where the file cuts `num_experts` to the held share), shared experts,
    the head's slice."""
    h = model["hidden_size"]
    dense = sum(k == "dense" for k in model["mlp_layer_types"])
    router = h * model.get("num_experts_published", model["num_experts"])
    shared = 3 * h * model["shared_expert_intermediate_size"]
    return (attn_params(model) + dense * 3 * h * model["intermediate_size"]
            + expert_layers(model) * (router + shared)
            + h * model["vocab_size"])


def decode_step_bytes(model: dict, live_tokens: float,
                      live_window_tokens: float, touched_share: float,
                      weight_dtype: str, kv_dtype: str = "bfloat16") -> float:
    """`touched_share` of the HELD experts (`num_experts`) a layer-step."""
    wb = costs._DTYPE_BYTES[weight_dtype]
    experts = (touched_share * expert_layers(model) * model["num_experts"]
               * expert_params(model))
    return ((always_read_params(model) + experts) * wb
            + decode_attn_bytes(model, live_tokens, live_window_tokens,
                                kv_dtype))


def decode_step_flops(model: dict, rows: float, held_pairs_per_row: float,
                      live_tokens: float, live_window_tokens: float) -> float:
    """What the routing needs HERE: each row through the experts of its
    top-k that are held on this chip (`held_pairs_per_row` a layer, ~
    top-k x held / published), not through all of them."""
    active = expert_layers(model) * held_pairs_per_row * expert_params(model)
    return (2.0 * (always_read_params(model) + active) * rows
            + decode_attn_flops(model, live_tokens, live_window_tokens))
