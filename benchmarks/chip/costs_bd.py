"""Operations and bytes ONE forward pass of a window of block passes needs
(generation by diffusion over blocks: every row's open block of
`block_length` positions goes through the model at once), from shapes, the
live KV and the share of experts the pass touched. Beside `costs_moe.py`,
whose expert width is `intermediate_size`: a `sdar_moe` config publishes the
experts' as `moe_intermediate_size` (its `intermediate_size`, 6144, is used
by no layer).

A pass reads the attention matrices, the routers and the output head once,
the bytes of the experts that got at least one (token, expert) pair — the
program counts them, `moe_decode_touched_n` of `moe_decode_slots_n`
(layer-passes x experts) — and the live KV ONCE: the block's positions of a
row share one visible range, so they are query rows of one attention call
(`paged_decode_attention`), not calls of their own. Its flops are those of
rows x block_length tokens, each through its `num_experts_per_tok` experts
(not through all of them, whatever the program does), and of that many
queries against the live context. Nothing here is clipped: a share of this
roofline over 100% means a count above is too high.
"""

from __future__ import annotations

import costs


def expert_params(model: dict) -> int:
    """One expert: gate, up and down (4,718,592 for SDAR-30B-A3B)."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def dense_params(model: dict) -> int:
    """What every pass reads whatever the routing: the attention matrices
    and the routers of every layer, and the output head (the embedding is
    gathered; norms are left out)."""
    h, hd = model["hidden_size"], model["head_dim"]
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    attn = h * nh * hd + 2 * h * nkv * hd + nh * hd * h
    router = h * model["num_experts"]
    return (model["num_hidden_layers"] * (attn + router)
            + h * model["vocab_size"])


def pass_bytes(model: dict, live_tokens: float, touched_share: float,
               weight_dtype: str, kv_dtype: str = "bfloat16") -> float:
    """One pass on one chip: dense weights once, the touched experts once,
    the live KV once."""
    experts = (touched_share * model["num_hidden_layers"]
               * model["num_experts"] * expert_params(model))
    return ((dense_params(model) + experts) * costs._DTYPE_BYTES[weight_dtype]
            + costs.decode_attn_bytes(model, live_tokens, kv_dtype))


def pass_flops(model: dict, rows: float, live_tokens: float) -> float:
    """rows x block_length tokens through the dense weights and their
    routed experts, and as many queries against the live context."""
    tokens = rows * model["block_length"]
    active = (model["num_hidden_layers"] * model["num_experts_per_tok"]
              * expert_params(model))
    return (2.0 * (dense_params(model) + active) * tokens
            + model["block_length"]
            * costs.decode_attn_flops(model, live_tokens))
