"""Operations and bytes a model needs whose layers are Gated DeltaNet layers
(the gated delta rule with ONE gate a head: a recurrent float32 state of
heads x Dk x Dv a row and layer, Dk != Dv, no keys) between full-attention
layers with as many key-value heads as query heads, dense SwiGLUs in every
layer — from the PUBLISHED keys (`layer_types`, the `linear_*` keys, the head
counts) and from what a step touched. Beside `costs.py`, which reads softmax
attention over a full-length cache in every layer, and `costs_kda.py`, whose
gate is a key channel's and whose state is square.

Everything here counts the published geometry (30 x 96 x 192 at
Olmo-Hybrid-7B's widths). The kernels pad a head's keys and values to whole
128-lane tiles on their way in (ops/kda_attention.py "THE LANES"): that is
work the algorithm does not need, so it reads as LOST share, never as work
done.

STATE. A row carries, in one linear layer, heads x Dk x Dv float32 (2.11 MiB
at 30 x 96 x 192) and the convolution's tail, the last taps - 1 rows of the
q | k | v projections (heads x (2 Dk + Dv) channels: 67.5 KiB in bf16) —
whatever its context length. A decode step must read and write each LIVE
row's once a linear layer.

The decode kernel's need (`gdn_decode_*`): a live row's state read once and
written once, and its vectors: q, k, v in the activations' dtype, the output
in float32, ONE log-decay and one step size a head (4 B each).

The prefill kernel's need (`gdn_prefill_*`), the chunkwise algorithm at
blocks of C = 64 tokens, per token and head: the pair products A = K K^T and
B = Q K^T (2 C Dk each — with one gate a head they are plain products under a
(C, C) decay mask), the triangular solve (2 C^2), U = T (beta V - beta K~ S0)
and B U (2 C Dv each), [beta K~; Q~] S0 and K'^T U against the Dk x Dv state
(3 x 2 Dk Dv): 2 (2 C Dk + C^2 + 2 C Dv + 3 Dk Dv) flops. Bytes per token and
head: q, k (Dk), v and the output (Dv) in the activations' dtype, the gate and
the step size (4 B each — a gate of 4 B a HEAD and token, not a channel); per
row SEGMENT of a chunk and head, the state read once and written once (it is
carried on the chip from block to block).

The full layers' attention kernels (`full_attn_*`): 4 x head_dim flops a
(query, key) pair and query head, and the K and V of the keys read once (30
key-value heads: 15 KiB a token and layer in bf16) — a decode step's live
tokens, a prefill chunk's context.

Weights a decode step must read, once: a full layer's W_q, W_k, W_v, W_o; a
linear layer's W_q, W_k (h x heads Dk), W_v, W_z, W_o (h x heads Dv), the
gate's and the step size's projections (h x heads each) and the taps; every
layer's SwiGLU; the head.
"""

from __future__ import annotations

import costs

BLOCK = 64  # tokens of a block of the chunkwise algorithm


def linear_layers(model: dict) -> list[int]:
    return [i for i, kind in enumerate(model["layer_types"])
            if kind == "linear_attention"]


def full_layers(model: dict) -> list[int]:
    return [i for i, kind in enumerate(model["layer_types"])
            if kind == "full_attention"]


def head_dim(model: dict) -> int:
    return model.get("head_dim") or (
        model["hidden_size"] // model["num_attention_heads"])


def _lin(model: dict) -> tuple[int, int, int, int]:
    """(heads, key width, value width, taps), as published."""
    return (model["linear_num_key_heads"], model["linear_key_head_dim"],
            model["linear_value_head_dim"], model["linear_conv_kernel_dim"])


def state_bytes_per_row_layer(model: dict) -> int:
    """The float32 state of one row in one linear layer (2,211,840 B)."""
    heads, dk, dv, _ = _lin(model)
    return heads * dk * dv * 4


def tail_bytes_per_row_layer(model: dict, act_dtype: str = "bfloat16") -> int:
    """The convolution's tail of one row in one linear layer (67.5 KiB)."""
    heads, dk, dv, taps = _lin(model)
    return (taps - 1) * heads * (2 * dk + dv) * costs._DTYPE_BYTES[act_dtype]


def gdn_decode_bytes(model: dict, live_row_steps: float,
                     act_dtype: str = "bfloat16") -> float:
    """Bytes the decode kernel must move for `live_row_steps` (live rows x
    steps) over all linear layers: the state in and out, the vectors."""
    heads, dk, dv, _ = _lin(model)
    ab = costs._DTYPE_BYTES[act_dtype]
    vectors = heads * ((2 * dk + dv) * ab + dv * 4 + 4 + 4)
    return live_row_steps * len(linear_layers(model)) * (
        2 * state_bytes_per_row_layer(model) + vectors)


def gdn_decode_flops(model: dict, live_row_steps: float) -> float:
    """The recurrence's own arithmetic a row, step and layer: the decay
    (Dk Dv), S'^T k and S^T q (2 Dk Dv each), the rank-one update
    (2 Dk Dv)."""
    heads, dk, dv, _ = _lin(model)
    return live_row_steps * len(linear_layers(model)) * heads * 7.0 * dk * dv


def gdn_prefill_flops(model: dict, tokens: float) -> float:
    heads, dk, dv, _ = _lin(model)
    c = BLOCK
    return tokens * len(linear_layers(model)) * heads * 2.0 * (
        2 * c * dk + c * c + 2 * c * dv + 3 * dk * dv)


def gdn_prefill_bytes(model: dict, tokens: float, segments: float,
                      act_dtype: str = "bfloat16") -> float:
    heads, dk, dv, _ = _lin(model)
    ab = costs._DTYPE_BYTES[act_dtype]
    per_token = heads * ((2 * dk + 2 * dv) * ab + 4 + 4)
    return len(linear_layers(model)) * (
        tokens * per_token + segments * 2 * state_bytes_per_row_layer(model))


def full_attn_bytes(model: dict, keys: float,
                    kv_dtype: str = "bfloat16") -> float:
    """K and V of `keys` tokens in every full layer (15 KiB a token and
    layer in bf16: 30 kv heads x 128): what a decode step's attention must
    read of its live tokens, or a prefill chunk's of its context."""
    per = (2 * model["num_key_value_heads"] * head_dim(model)
           * costs._DTYPE_BYTES[kv_dtype])
    return per * len(full_layers(model)) * keys


def full_attn_flops(model: dict, pairs: float) -> float:
    """QK^T and PV of `pairs` (query, key) pairs in every full layer."""
    return (4.0 * head_dim(model) * model["num_attention_heads"]
            * len(full_layers(model)) * pairs)


def full_params(model: dict) -> int:
    """One full layer's mixer: W_q, W_o (h x heads D) and W_k, W_v (h x kv
    heads D) (58,982,400)."""
    h, d = model["hidden_size"], head_dim(model)
    return 2 * h * d * (
        model["num_attention_heads"] + model["num_key_value_heads"])


def linear_params(model: dict) -> int:
    """One linear layer's mixer: q, k (h x heads Dk); v, the output gate, o
    (h x heads Dv); the decay gate's and the step size's projections (h x
    heads); the taps (88,750,080)."""
    h = model["hidden_size"]
    heads, dk, dv, taps = _lin(model)
    return (2 * h * heads * dk + 3 * h * heads * dv + 2 * h * heads
            + taps * heads * (2 * dk + dv))


def mlp_params(model: dict) -> int:
    """One layer's SwiGLU: gate, up, down (126,812,160)."""
    return 3 * model["hidden_size"] * model["intermediate_size"]


def always_read_params(model: dict) -> int:
    """What every decode step reads: both kinds of mixer, every SwiGLU, the
    head."""
    return (len(full_layers(model)) * full_params(model)
            + len(linear_layers(model)) * linear_params(model)
            + model["num_hidden_layers"] * mlp_params(model)
            + model["hidden_size"] * model["vocab_size"])


def decode_step_bytes(model: dict, live_tokens: float, live_rows: float,
                      weight_dtype: str, kv_dtype: str = "bfloat16") -> float:
    """One decode step: the weights above, the live tokens' K and V in the
    full layers, the live rows' state (read and written) and tail in the
    linear layers."""
    wb = costs._DTYPE_BYTES[weight_dtype]
    kv = full_attn_bytes(model, live_tokens, kv_dtype)
    state = live_rows * len(linear_layers(model)) * (
        2 * state_bytes_per_row_layer(model)
        + 2 * tail_bytes_per_row_layer(model, weight_dtype))
    return always_read_params(model) * wb + kv + state


def decode_step_flops(model: dict, rows: float, live_tokens: float,
                      live_rows: float) -> float:
    return (2.0 * always_read_params(model) * rows
            + full_attn_flops(model, live_tokens)
            + gdn_decode_flops(model, live_rows))
