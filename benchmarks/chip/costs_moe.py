"""Operations and bytes a sparse-MoE decode step needs, from shapes and from
the share of experts it touched. Beside `costs.py`, whose `matmul_params`
counts ONE expert's MLP per layer (so `decode_step_roofline` reads low in an
MoE cell); this file counts what an MoE layer holds and what a step reads.

Per layer an MoE model holds `num_experts` SwiGLUs of width
`intermediate_size` and an h x E router. A decode step reads the attention
matrices, the routers and the output head once, the int8 bytes of the
experts that got at least one (token, expert) pair — the program counts them,
`moe_decode_touched_n` of `moe_decode_slots_n` (layer-steps x experts) — and
the live KV once. Counting only touched experts keeps the share under 100%
for a program that skips the others.
"""

from __future__ import annotations

import costs


def expert_params_per_layer(model: dict) -> int:
    """One layer's experts: E x (gate + up + down)."""
    return (model["num_experts"] * 3 * model["hidden_size"]
            * model["intermediate_size"])


def expert_params(model: dict) -> int:
    """All layers: 6,442,450,944 for OLMoE-1B-7B (93% of its 6.92 B)."""
    return model["num_hidden_layers"] * expert_params_per_layer(model)


def dense_params(model: dict) -> int:
    """What every decode step reads whatever the routing: attention
    matrices, routers, the output head (the embedding is gathered)."""
    h, hd = model["hidden_size"], model["head_dim"]
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    attn = h * nh * hd + 2 * h * nkv * hd + nh * hd * h
    router = h * model["num_experts"]
    return (model["num_hidden_layers"] * (attn + router)
            + h * model["vocab_size"])


def touched_expert_bytes(model: dict, touched_share: float,
                         weight_dtype: str) -> float:
    """Bytes of the experts one step touched, all layers."""
    return (touched_share * expert_params(model)
            * costs._DTYPE_BYTES[weight_dtype])


def decode_step_bytes(model: dict, live_tokens: float, touched_share: float,
                      weight_dtype: str, kv_dtype: str = "bfloat16") -> float:
    """One decode step on one chip: dense weights once, the touched experts
    once, the live KV once. (The router stays bf16 under int8 weights: 2 MiB
    of 0.2 GiB, counted at the weight dtype like `costs.weight_bytes`
    leaves out scales.)"""
    return (dense_params(model) * costs._DTYPE_BYTES[weight_dtype]
            + touched_expert_bytes(model, touched_share, weight_dtype)
            + costs.decode_attn_bytes(model, live_tokens, kv_dtype))


def decode_step_flops(model: dict, rows: float, live_tokens: float) -> float:
    """What the routing needs: each row through its `num_experts_per_tok`
    experts (not through all of them, whatever the program does)."""
    active = (model["num_hidden_layers"] * model["num_experts_per_tok"] * 3
              * model["hidden_size"] * model["intermediate_size"])
    return (2.0 * (dense_params(model) + active) * rows
            + costs.decode_attn_flops(model, live_tokens))
