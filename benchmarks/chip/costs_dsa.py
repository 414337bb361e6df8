"""Operations and bytes a latent-attention model that attends over a LEARNED
SELECTION of keys needs (`model_type: glm_moe_dsa`), from the published keys
and from what the program counted. Beside `costs_mla.py`, which counts every
visible latent in the absorbed form.

The counts are the LEAST an exact form does, whatever implements it, so that
no share of these rooflines can pass 100:

- attention: each (query, selected key) pair in the EXPANDED form — heads x
  2 x ((qk_nope + qk_rope) + v_head) flops, 65,536 for GLM-5.2 (the absorbed
  form a program may run instead spends heads x 2 x (576 + 512) = 139,264 a
  pair: counting that would read an expanded kernel at 212%) — and, in a
  decode step, each selected token's latent (`kv_lora_rank + qk_rope_head_dim`
  values, 1,152 B) once. A prefill chunk's queries share their keys' bytes,
  so its need is its flops;
- the indexer: each scored (query, key) pair index_n_heads x index_head_dim x
  2 flops (8,192) and, in a decode step, each visible index key
  (`index_head_dim` values, 256 B) once — a step has one query a row, so its
  scored pairs ARE its visible keys; a chunk reads each key of its context
  once a layer that owns an indexer;
- a decode step: the weights every step reads (attention with the query
  latent, the indexers of the layers that own one, the dense MLPs, routers,
  shared experts, the head's rows held) + the TOUCHED held experts + those
  bytes.

The layers that own an indexer are `indexer_types == "full"`; every layer
attends under a selection. `n_routed_experts` in a cut configuration is the
HELD count (the router's width is `n_routed_experts_published`).
"""

from __future__ import annotations

import costs


def full_layers(model: dict) -> int:
    return sum(kind == "full" for kind in model["indexer_types"])


def moe_layers(model: dict) -> int:
    return sum(kind == "sparse" for kind in model["mlp_layer_types"])


def latent_bytes(model: dict, kv_dtype: str = "bfloat16") -> int:
    """A token's latent in one layer: 1,152 B for GLM-5.2 in bf16."""
    return ((model["kv_lora_rank"] + model["qk_rope_head_dim"])
            * costs._DTYPE_BYTES[kv_dtype])


def index_key_bytes(model: dict, kv_dtype: str = "bfloat16") -> int:
    """A token's index key in one layer that owns an indexer: 256 B."""
    return model["index_head_dim"] * costs._DTYPE_BYTES[kv_dtype]


def attn_flops(model: dict, selected: float) -> float:
    """`selected`: (query, selected key) pairs summed over queries and
    layers (the program's `dsa_selected_keys_n`)."""
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    return 2.0 * model["num_attention_heads"] * (
        qk + model["v_head_dim"]) * selected


def index_flops(model: dict, pairs: float) -> float:
    """`pairs`: scored (query, key) pairs summed over queries and the
    layers that own an indexer (`dsa_index_pairs_n`)."""
    return 2.0 * model["index_n_heads"] * model["index_head_dim"] * pairs


def decode_attn_bytes(model: dict, selected: float,
                      kv_dtype: str = "bfloat16") -> float:
    return selected * latent_bytes(model, kv_dtype)


def decode_index_bytes(model: dict, pairs: float,
                       kv_dtype: str = "bfloat16") -> float:
    return pairs * index_key_bytes(model, kv_dtype)


def prefill_index_bytes(model: dict, context_keys: float,
                        kv_dtype: str = "bfloat16") -> float:
    """`context_keys`: the keys each chunk's context holds, summed over
    chunks (`attn_prefill_keys_n`): read once a layer that owns an
    indexer."""
    return context_keys * full_layers(model) * index_key_bytes(
        model, kv_dtype)


def attn_params_per_layer(model: dict) -> int:
    """W_qa, W_qb, W_kva, W_kvb, W_o: 165,019,648 for GLM-5.2."""
    h, nh = model["hidden_size"], model["num_attention_heads"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    rq, rk = model["q_lora_rank"], model["kv_lora_rank"]
    return (h * rq + rq * nh * qk + h * (rk + model["qk_rope_head_dim"])
            + rk * nh * (model["qk_nope_head_dim"] + model["v_head_dim"])
            + nh * model["v_head_dim"] * h)


def indexer_params(model: dict) -> int:
    """W_iq, W_ik, W_iw of one layer that owns an indexer: 9,371,648."""
    hi, di = model["index_n_heads"], model["index_head_dim"]
    return (model["q_lora_rank"] * hi * di + model["hidden_size"] * di
            + model["hidden_size"] * hi)


def expert_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def router_width(model: dict) -> int:
    return model.get("n_routed_experts_published", model["n_routed_experts"])


def always_read_params(model: dict) -> int:
    """What every decode step reads whatever the routing."""
    h = model["hidden_size"]
    layers = len(model["indexer_types"])
    dense = layers - moe_layers(model)
    return (layers * attn_params_per_layer(model)
            + full_layers(model) * indexer_params(model)
            + dense * 3 * h * model["intermediate_size"]
            + moe_layers(model) * (
                model["n_shared_experts"] * expert_params(model)
                + h * router_width(model))
            + h * model["vocab_size"])


def decode_steps_bytes(model: dict, steps: float, touched_experts: float,
                       selected: float, pairs: float, weight_dtype: str,
                       kv_dtype: str = "bfloat16") -> float:
    """`steps` decode steps together: every step reads the always-read
    weights; over all of them `touched_experts` held experts were read (over
    the expert layers), `selected` (query, key) pairs attended (over the
    layers) and `pairs` index keys scored (over the layers that own an
    indexer)."""
    wb = costs._DTYPE_BYTES[weight_dtype]
    return ((steps * always_read_params(model)
             + touched_experts * expert_params(model)) * wb
            + decode_attn_bytes(model, selected, kv_dtype)
            + decode_index_bytes(model, pairs, kv_dtype))


def decode_steps_flops(model: dict, steps: float, rows: float,
                       held_pairs: float, selected: float,
                       pairs: float) -> float:
    """`rows` rows a step; `held_pairs`: the (row, expert) pairs of those
    steps that landed on a held expert, over the expert layers."""
    return (2.0 * always_read_params(model) * rows * steps
            + 2.0 * held_pairs * expert_params(model)
            + attn_flops(model, selected) + index_flops(model, pairs))
