"""Operations and bytes a latent-attention (MLA) MoE model needs, from the
published keys and from the share of experts a step touched. Beside
`costs.py`, which reads `num_key_value_heads x head_dim` K and V a token (for
this family: published keys its pool does not hold) and one MLP a layer.

An MLA layer caches ONE latent a token: `kv_lora_rank` values beside a rope
key of `qk_rope_head_dim` shared by all heads. In the absorbed form decode
attention is multi-query over it: every query head scores the token's
`kv_lora_rank + qk_rope_head_dim` values and sums its first `kv_lora_rank`.
A decode step reads: every layer's attention matrices, the leading dense
layers' MLP, the expert layers' shared experts and routers, the head — once;
the routed experts that got at least one (token, expert) pair (the program
counts them: `moe_decode_touched_n` of `moe_decode_slots_n`, slots over
expert layers); the live latents once.
"""

from __future__ import annotations

import costs


def latent_values(model: dict) -> int:
    """What a token's cache entry must hold in one layer (padding a program
    adds to fill lane tiles is its own cost, not the algorithm's)."""
    return model["kv_lora_rank"] + model["qk_rope_head_dim"]


def latent_bytes_per_token_per_layer(model: dict,
                                     kv_dtype: str = "bfloat16") -> int:
    """1,152 B for Kanana-2-30B-A3B in bf16."""
    return latent_values(model) * costs._DTYPE_BYTES[kv_dtype]


def decode_attn_bytes(model: dict, live_tokens: float,
                      kv_dtype: str = "bfloat16") -> float:
    """Bytes one step's decode attention must read over all layers: every
    live token's latent, once."""
    return (live_tokens * model["num_hidden_layers"]
            * latent_bytes_per_token_per_layer(model, kv_dtype))


def decode_attn_flops(model: dict, live_tokens: float) -> float:
    """Absorbed scores over the latent and rope key, values over the
    latent: 2 x heads x ((rank + rope) + rank) a live token and layer."""
    per = 2.0 * model["num_attention_heads"] * (
        latent_values(model) + model["kv_lora_rank"])
    return per * live_tokens * model["num_hidden_layers"]


def prefill_attn_flops(model: dict, pairs: float) -> float:
    """The same arithmetic per (query, key) pair, all layers."""
    return decode_attn_flops(model, pairs)


def attn_params_per_layer(model: dict) -> int:
    """W_q, W_kv_a, W_kv_b, W_o (no query latent): 26,345,472."""
    h, nh = model["hidden_size"], model["num_attention_heads"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    return (h * nh * qk + h * latent_values(model)
            + model["kv_lora_rank"] * nh * (model["qk_nope_head_dim"]
                                            + model["v_head_dim"])
            + nh * model["v_head_dim"] * h)


def moe_layers(model: dict) -> int:
    return model["num_hidden_layers"] - model["first_k_dense_replace"]


def expert_params_per_layer(model: dict) -> int:
    """One layer's routed experts: E x (gate + up + down)."""
    return (model["n_routed_experts"] * 3 * model["hidden_size"]
            * model["moe_intermediate_size"])


def always_read_params(model: dict) -> int:
    """What every decode step reads whatever the routing."""
    h = model["hidden_size"]
    shared = 3 * h * model["n_shared_experts"] * model["moe_intermediate_size"]
    router = h * model["n_routed_experts"]
    dense = 3 * h * model["intermediate_size"]
    return (model["num_hidden_layers"] * attn_params_per_layer(model)
            + model["first_k_dense_replace"] * dense
            + moe_layers(model) * (shared + router)
            + h * model["vocab_size"])


def decode_step_bytes(model: dict, live_tokens: float, touched_share: float,
                      weight_dtype: str, kv_dtype: str = "bfloat16") -> float:
    wb = costs._DTYPE_BYTES[weight_dtype]
    experts = touched_share * moe_layers(model) * expert_params_per_layer(model)
    return ((always_read_params(model) + experts) * wb
            + decode_attn_bytes(model, live_tokens, kv_dtype))


def decode_step_flops(model: dict, rows: float, live_tokens: float) -> float:
    """What the routing needs: each row through its `num_experts_per_tok`
    experts (not through all of them, whatever the program does)."""
    active = (moe_layers(model) * model["num_experts_per_tok"] * 3
              * model["hidden_size"] * model["moe_intermediate_size"])
    return (2.0 * (always_read_params(model) + active) * rows
            + decode_attn_flops(model, live_tokens))
