"""Operations and bytes a model needs whose layers are KDA layers (the gated
delta rule with a per-channel gate: a recurrent float32 state of heads x D x D
a row and layer, no keys) with ONE latent-attention (MLA) layer every
`layer_group_size` layers, a leading dense layer and a share of its experts —
from the published keys (`layer_group_size`, `first_k_dense_replace`,
`kv_lora_rank`, `num_experts`, ...) and from what a step touched. Beside
`costs_kda.py` (KDA between GQA layers, low-rank gates, every layer experts)
and `costs_mla.py` (a latent in EVERY layer), whose per-kernel arithmetic
is reused through the two views below (`as_kda`, `as_mla`: what
`readers/ling_view.py` hands the accepted kernel readers) and whose layer
counts would misread this model.

LAYERS. Layer i is an MLA layer where (i + 1) % `layer_group_size` == 0,
else a KDA layer; its MLP is a dense SwiGLU where i <
`first_k_dense_replace`, else the held experts.

CACHES. A row carries, in one KDA layer, heads x D x D float32 (2 MiB at 32
x 128 x 128) and the convolution's tail (72 KiB in bf16), whatever its
context length; in one MLA layer one latent a token, `kv_lora_rank +
qk_rope_head_dim` values (576; the pool stores them padded to whole 128-lane
tiles, 640: `latent_stored_values`, what the memory arithmetic counts — the
kernels' NEED counts the 576).

WEIGHTS a decode step must read, once: a KDA layer's W_q, W_k, W_v, W_o, the
two FULL-RANK gate projections (`no_kda_lora`), the step size's projection
and the taps; an MLA layer's W_q, W_kv_a, W_kv_b, W_o and its per-head gate;
the dense layer's SwiGLU; each expert layer's router at its PUBLISHED width
with its selection bias, its shared expert; the head's slice — and the
routed experts HELD here that got at least one (token, expert) pair (the
program counts them: `moe_decode_touched_n` of `moe_decode_slots_n`, slots
over held experts and expert layers).
"""

from __future__ import annotations

import costs
import costs_kda
import costs_mla


def mla_layers(model: dict) -> list[int]:
    size = model["layer_group_size"]
    return [i for i in range(model["num_hidden_layers"])
            if (i + 1) % size == 0]


def kda_layers(model: dict) -> list[int]:
    mla = set(mla_layers(model))
    return [i for i in range(model["num_hidden_layers"]) if i not in mla]


def moe_layers(model: dict) -> int:
    return model["num_hidden_layers"] - model["first_k_dense_replace"]


def as_kda(model: dict) -> dict:
    """The model as `costs_kda` reads one: its KDA heads under
    `linear_attn_config`, and the layers that are NOT KDA layers under
    `gqa_layers` (that file counts KDA layers as the rest)."""
    return {**model, "gqa_layers": mla_layers(model),
            "linear_attn_config": {
                "num_heads": model["num_attention_heads"],
                "head_dim": model["head_dim"],
                "short_conv_kernel_size": model["short_conv_kernel_size"]}}


def as_mla(model: dict) -> dict:
    """The model as `costs_mla` reads one: a latent in every one of
    `num_hidden_layers` layers — here the MLA layers alone."""
    return {**model, "num_hidden_layers": len(mla_layers(model))}


# -- parameters --------------------------------------------------------------

def kda_params(model: dict) -> int:
    """One KDA layer's mixer: q, k, v, o and the two full-rank gates (h x
    heads D each), the step size's projection, the taps (63,045,632)."""
    h = model["hidden_size"]
    hd = model["num_attention_heads"] * model["head_dim"]
    return (6 * h * hd + h * model["num_attention_heads"]
            + model["short_conv_kernel_size"] * 3 * hd)


def mla_params(model: dict) -> int:
    """One MLA layer's mixer: W_q, W_kv_a, W_kv_b, W_o and the gate a head
    (31,967,232)."""
    return (costs_mla.attn_params_per_layer(model)
            + model["hidden_size"] * model["num_attention_heads"])


def expert_params(model: dict) -> int:
    """ONE routed expert: gate, up, down (5,898,240)."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def shared_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model[
        "moe_shared_expert_intermediate_size"]


def router_params(model: dict) -> int:
    """The router at its PUBLISHED width."""
    return model["hidden_size"] * model.get(
        "num_experts_published", model["num_experts"])


def always_read_params(model: dict) -> int:
    """What every decode step reads whatever the routing (the embedding's
    rows of the step's tokens are left out: 16 rows)."""
    h = model["hidden_size"]
    return (len(kda_layers(model)) * kda_params(model)
            + len(mla_layers(model)) * mla_params(model)
            + model["first_k_dense_replace"] * 3 * h
            * model["intermediate_size"]
            + moe_layers(model) * (router_params(model)
                                   + shared_params(model))
            + h * model["vocab_size"])


def total_params(model: dict) -> int:
    """Every matrix the chip holds: the above, every held expert and the
    embedding's slice (5.23 B at the cell's cut)."""
    return (always_read_params(model)
            + moe_layers(model) * model["num_experts"] * expert_params(model)
            + model["hidden_size"] * model["vocab_size"])


# -- the two caches ----------------------------------------------------------

def latent_stored_values(model: dict) -> int:
    """A token's latent as the pool stores it: whole 128-lane tiles."""
    return -(-costs_mla.latent_values(model) // 128) * 128


def latent_pool_bytes(model: dict, kv_dtype: str = "bfloat16") -> int:
    """`num_blocks` pages of `block_size` tokens in every MLA layer."""
    return (model["num_blocks"] * model["block_size"]
            * len(mla_layers(model)) * latent_stored_values(model)
            * costs._DTYPE_BYTES[kv_dtype])


def state_pool_bytes(model: dict, act_dtype: str = "bfloat16") -> int:
    """Two banks of (seats + the null slot) slots in every KDA layer: the
    float32 state and the convolution's tail."""
    kda = as_kda(model)
    slot = (costs_kda.state_bytes_per_row_layer(kda)
            + costs_kda.tail_bytes_per_row_layer(kda, act_dtype))
    return 2 * (model["max_num_seqs"] + 1) * len(kda_layers(model)) * slot


# -- a decode step -----------------------------------------------------------

def decode_step_bytes(model: dict, live_tokens: float, live_rows: float,
                      touched_share: float, weight_dtype: str,
                      kv_dtype: str = "bfloat16") -> float:
    """One decode step: the weights above, `touched_share` of the HELD
    experts (`num_experts`) an expert layer, the live tokens' latents in the
    MLA layers, the live rows' state (read and written) and tail in the KDA
    layers."""
    wb = costs._DTYPE_BYTES[weight_dtype]
    kda = as_kda(model)
    experts = (touched_share * moe_layers(model) * model["num_experts"]
               * expert_params(model))
    state = live_rows * len(kda_layers(model)) * (
        2 * costs_kda.state_bytes_per_row_layer(kda)
        + 2 * costs_kda.tail_bytes_per_row_layer(kda, weight_dtype))
    return ((always_read_params(model) + experts) * wb
            + costs_mla.decode_attn_bytes(as_mla(model), live_tokens,
                                          kv_dtype) + state)


def decode_step_flops(model: dict, rows: float, held_pairs_per_row: float,
                      live_tokens: float, live_rows: float) -> float:
    """`held_pairs_per_row`: pairs a row and EXPERT layer that landed on a
    held expert."""
    active = moe_layers(model) * held_pairs_per_row * expert_params(model)
    return (2.0 * (always_read_params(model) + active) * rows
            + costs_mla.decode_attn_flops(as_mla(model), live_tokens)
            + costs_kda.kda_decode_flops(as_kda(model), live_rows))
