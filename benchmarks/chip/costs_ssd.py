"""Operations and bytes a model needs whose EVERY layer holds attention heads
(GQA over pages of K and V) AND Mamba-2 heads (the state-space duality
recurrence, SSD: a recurrent float32 state of heads x P x N a row and layer)
in one block, with a dense SwiGLU — from the published keys (`mamba_n_heads`,
`mamba_d_head`, `mamba_d_state`, `mamba_n_groups`, `mamba_d_conv`, ...).
Beside `costs.py`, which reads softmax attention over a full-length cache in
every layer (right for this model's attention too: `decode_attn_bytes`) and
knows no state, and `costs_kda.py` (a square state, layers of ONE kind each).

STATE. A row carries, in every layer, heads x P x N float32 (4 MiB at 32 x
128 x 256) and the convolution's tail, the last taps - 1 rows of the x | B | C
channels (30 KiB in bf16 at 5,120 channels) — whatever its context length —
BESIDE 2 x kv heads x head_dim of K and V a token (2 KiB a layer in bf16).

The prefill kernel's need (`ssd_prefill_*`), the chunkwise algorithm at
blocks of Q = 128 tokens: per token and GROUP the pair products C B^T
(2 Q N: B and C are shared by a group's heads, so the products are formed
once a group — counting them a head would count work no kernel has to do);
per token and head (L o C B^T)(dt X) (2 Q P), C S0^T (2 N P) and the
state's update (dt X)^T B (2 N P). Bytes per token: x and y (y in float32),
B, C and dt; per row SEGMENT of a chunk and head the state read once and
written once (it is carried on the chip from block to block).

The decode kernel's need (`ssd_decode_*`): a row's state read once and
written once a layer, and its vectors (x, B, C, dt in, y out); the decay
(N P), the rank-one update (2 N P) and S C (2 N P) a head.

A whole decode step (`decode_step_*`): every layer's attention (W_q, W_k,
W_v, W_o), mixer (the input and output projections, the taps and their bias,
the gated norm, A_log / D / dt_bias) and SwiGLU weights and the head ONCE,
the live tokens' K and V in every layer, the live rows' state in and out
and their tails.
"""

from __future__ import annotations

import costs

BLOCK = 128  # tokens of a block of the chunkwise algorithm


def _ssm(model: dict) -> tuple[int, int, int, int, int]:
    return (model["mamba_n_heads"], model["mamba_d_head"],
            model["mamba_d_state"], model["mamba_n_groups"],
            model["mamba_d_conv"])


def conv_channels(model: dict) -> int:
    """x | B | C: what the convolution runs over (5,120)."""
    heads, p, n, groups, _ = _ssm(model)
    return heads * p + 2 * groups * n


def state_bytes_per_row_layer(model: dict) -> int:
    """The float32 state of one row in one layer (4 MiB)."""
    heads, p, n, _, _ = _ssm(model)
    return heads * p * n * 4


def tail_bytes_per_row_layer(model: dict, act_dtype: str = "bfloat16") -> int:
    """The convolution's tail of one row in one layer (30 KiB)."""
    taps = model["mamba_d_conv"]
    return (taps - 1) * conv_channels(model) * costs._DTYPE_BYTES[act_dtype]


def slot_bytes(model: dict, act_dtype: str = "bfloat16") -> int:
    """One state slot over all layers (what engine/memory.py:
    state_slot_bytes counts)."""
    return model["num_hidden_layers"] * (
        state_bytes_per_row_layer(model)
        + tail_bytes_per_row_layer(model, act_dtype))


def _vector_bytes(model: dict, act_dtype: str) -> int:
    """x, B, C in the activations' dtype, dt and y in float32, a token."""
    heads, p, n, groups, _ = _ssm(model)
    ab = costs._DTYPE_BYTES[act_dtype]
    return (heads * p + 2 * groups * n) * ab + heads * 4 + heads * p * 4


def ssd_prefill_flops(model: dict, tokens: float) -> float:
    heads, p, n, groups, _ = _ssm(model)
    q = BLOCK
    return tokens * model["num_hidden_layers"] * 2.0 * (
        groups * q * n + heads * (q * p + 2 * n * p))


def ssd_prefill_bytes(model: dict, tokens: float, segments: float,
                      act_dtype: str = "bfloat16") -> float:
    return model["num_hidden_layers"] * (
        tokens * _vector_bytes(model, act_dtype)
        + segments * 2 * state_bytes_per_row_layer(model))


def ssd_decode_bytes(model: dict, row_steps: float,
                     act_dtype: str = "bfloat16") -> float:
    """Bytes the decode kernel must move for `row_steps` (rows x steps)
    over all layers: the state in and out, the vectors."""
    return row_steps * model["num_hidden_layers"] * (
        2 * state_bytes_per_row_layer(model)
        + _vector_bytes(model, act_dtype))


def ssd_decode_flops(model: dict, row_steps: float) -> float:
    heads, p, n, _, _ = _ssm(model)
    return row_steps * model["num_hidden_layers"] * heads * 5.0 * n * p


def attn_params(model: dict) -> int:
    h, d = model["hidden_size"], model["head_dim"]
    return (2 * h * model["num_attention_heads"] * d
            + 2 * h * model["num_key_value_heads"] * d)


def ssm_params(model: dict) -> int:
    """One layer's mixer: W_in (h x (2 d_ssm + 2 G N + heads)), W_out, the
    taps and their bias, the gated norm, A_log, D and dt_bias."""
    h = model["hidden_size"]
    heads, p, n, groups, taps = _ssm(model)
    inner = heads * p
    return (h * (2 * inner + 2 * groups * n + heads) + inner * h
            + (taps + 1) * conv_channels(model) + inner + 3 * heads)


def layer_params(model: dict) -> int:
    """One layer: both mixers, the SwiGLU, the two norms (430.1 M)."""
    h = model["hidden_size"]
    return (attn_params(model) + ssm_params(model)
            + 3 * h * model["intermediate_size"] + 2 * h)


def head_params(model: dict) -> int:
    return model["hidden_size"] * model["vocab_size"]


def step_read_params(model: dict) -> int:
    """What every decode step reads: every layer and the head (the
    embedding is gathered, a row a token)."""
    return (model["num_hidden_layers"] * layer_params(model)
            + head_params(model))


def decode_step_bytes(model: dict, live_tokens: float, live_rows: float,
                      weight_dtype: str, kv_dtype: str = "bfloat16") -> float:
    wb = costs._DTYPE_BYTES[weight_dtype]
    state = live_rows * model["num_hidden_layers"] * (
        2 * state_bytes_per_row_layer(model)
        + 2 * tail_bytes_per_row_layer(model, weight_dtype))
    return (step_read_params(model) * wb
            + costs.decode_attn_bytes(model, live_tokens, kv_dtype) + state)


def decode_step_flops(model: dict, rows: float, live_tokens: float,
                      live_rows: float) -> float:
    return (2.0 * step_read_params(model) * rows
            + costs.decode_attn_flops(model, live_tokens)
            + ssd_decode_flops(model, live_rows))


def as_gqa(model: dict) -> dict:
    """The model as `costs_kda`'s GQA arithmetic reads one (what
    `readers/h1_view.py` hands the accepted `gqa_*` readers): EVERY layer
    under `gqa_layers`, and the key those readers tell a model of theirs by
    (`linear_attn_config`, here without heads: no layer is a KDA layer)."""
    return {**model,
            "gqa_layers": list(range(model["num_hidden_layers"])),
            "linear_attn_config": {"num_heads": 0, "head_dim": 0,
                                   "short_conv_kernel_size":
                                   model["mamba_d_conv"]}}
