"""Operations and bytes a model needs whose layers are KDA layers (the gated
delta rule with a per-channel gate: a recurrent float32 state of heads x D x D
a row and layer, no keys) between gated NoPE GQA layers, with a share of its
experts — from the published keys (`gqa_layers`, `linear_attn_config`,
`n_routed_experts`, ...) and from what a step touched. Beside `costs.py`,
which reads softmax attention over a full-length cache in every layer.

STATE. A row carries, in one KDA layer, heads x D x D float32 (4 MiB at 64 x
128 x 128) and the convolution's tail, the last taps - 1 rows of the q | k | v
projections (144 KiB in bf16) — whatever its context length. A decode step
must read and write each LIVE row's once a KDA layer.

The decode kernel's need (`kda_decode_*`): a live row's state read once and
written once, and its vectors: q, k, v in the activations' dtype, the gate's
log-decays in float32, the step size, the output in float32.

The prefill kernel's need (`kda_prefill_*`), the chunkwise algorithm at
blocks of C = 64 tokens, per token and head: the pair products A = K K^T and
B = Q K^T (2 C D each), the triangular solve (2 C^2), T (beta K~) and
T (beta V) (2 C D each), W S0, Q~ S0 and K'^T U against the D x D state
(2 D^2 each), B U (2 C D): 2 (5 C D + C^2 + 3 D^2) flops. Bytes per token
and head: q, k, v, the output (the activations' dtype), the log-decays
(float32) and the step size; per row SEGMENT of a chunk and head, the state
read once and written once (it is carried on the chip from block to block).

The GQA layers' attention kernels (`gqa_attn_*`): 4 x head_dim flops a
(query, key) pair and query head, and the K and V of the keys read once — a
decode step's live tokens, a prefill chunk's context.

Weights a decode step must read, once: a GQA layer's W_q, W_k, W_v, W_o and
its elementwise gate W_g; a KDA layer's W_q, W_k, W_v, W_o, the two low-rank
pairs, the step size's projection and the taps; each layer's router at its
PUBLISHED width with its selection bias, its shared expert; the head's slice
— and the routed experts HELD here that got at least one (token, expert) pair
(the program counts them: `moe_decode_touched_n` of `moe_decode_slots_n`,
slots over held experts).
"""

from __future__ import annotations

import costs

BLOCK = 64  # tokens of a block of the chunkwise algorithm


def gqa_layers(model: dict) -> list[int]:
    return [i for i in model["gqa_layers"] if i < model["num_hidden_layers"]]


def kda_layers(model: dict) -> list[int]:
    gqa = set(gqa_layers(model))
    return [i for i in range(model["num_hidden_layers"]) if i not in gqa]


def _lin(model: dict) -> tuple[int, int, int]:
    lin = model["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def state_bytes_per_row_layer(model: dict) -> int:
    """The float32 state of one row in one KDA layer (4 MiB)."""
    heads, d, _ = _lin(model)
    return heads * d * d * 4


def tail_bytes_per_row_layer(model: dict, act_dtype: str = "bfloat16") -> int:
    """The convolution's tail of one row in one KDA layer (144 KiB)."""
    heads, d, taps = _lin(model)
    return (taps - 1) * 3 * heads * d * costs._DTYPE_BYTES[act_dtype]


def kda_decode_bytes(model: dict, live_row_steps: float,
                     act_dtype: str = "bfloat16") -> float:
    """Bytes the decode kernel must move for `live_row_steps` (live rows x
    steps) over all KDA layers: the state in and out, the vectors."""
    heads, d, _ = _lin(model)
    ab = costs._DTYPE_BYTES[act_dtype]
    vectors = heads * d * (3 * ab + 4 + 4) + heads * 4
    return live_row_steps * len(kda_layers(model)) * (
        2 * state_bytes_per_row_layer(model) + vectors)


def kda_decode_flops(model: dict, live_row_steps: float) -> float:
    """The recurrence's own arithmetic a row, step and layer: the decay
    (D^2), S'^T k and S^T q (2 D^2 each), the rank-one update (2 D^2)."""
    heads, d, _ = _lin(model)
    return live_row_steps * len(kda_layers(model)) * heads * 7.0 * d * d


def kda_prefill_flops(model: dict, tokens: float) -> float:
    heads, d, _ = _lin(model)
    c = BLOCK
    return tokens * len(kda_layers(model)) * heads * 2.0 * (
        5 * c * d + c * c + 3 * d * d)


def kda_prefill_bytes(model: dict, tokens: float, segments: float,
                      act_dtype: str = "bfloat16") -> float:
    heads, d, _ = _lin(model)
    ab = costs._DTYPE_BYTES[act_dtype]
    per_token = heads * d * (4 * ab + 4) + heads * 4
    return len(kda_layers(model)) * (
        tokens * per_token + segments * 2 * state_bytes_per_row_layer(model))


def gqa_attn_bytes(model: dict, keys: float,
                   kv_dtype: str = "bfloat16") -> float:
    """K and V of `keys` tokens in every GQA layer (4 KiB a token and
    layer in bf16: 8 kv heads x 128): what a decode step's attention must
    read of its live tokens, or a prefill chunk's of its context."""
    return (costs.kv_bytes_per_token_per_layer(model, kv_dtype)
            * len(gqa_layers(model)) * keys)


def gqa_attn_flops(model: dict, pairs: float) -> float:
    """QK^T and PV of `pairs` (query, key) pairs in every GQA layer."""
    return (4.0 * model["head_dim"] * model["num_attention_heads"]
            * len(gqa_layers(model)) * pairs)


def gqa_params(model: dict) -> int:
    """One GQA layer: W_q, W_o, the elementwise gate W_g (h x heads x D
    each) and W_k, W_v."""
    h, d = model["hidden_size"], model["head_dim"]
    q = h * model["num_attention_heads"] * d
    gate = q if model.get("use_gqa_gate") else 0
    return 2 * q + gate + 2 * h * model["num_key_value_heads"] * d


def kda_params(model: dict) -> int:
    """One KDA layer's mixer: q, k, v, o; the two low-rank pairs (rank
    `linear_gate_rank`, the head's width where the file does not say); the
    step size's projection; the taps."""
    h = model["hidden_size"]
    heads, d, taps = _lin(model)
    r = model.get("linear_gate_rank", d)
    return (4 * h * heads * d + 2 * (h * r + r * heads * d) + h * heads
            + taps * 3 * heads * d)


def expert_params(model: dict) -> int:
    """ONE routed expert: gate, up, down (15,728,640)."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def always_read_params(model: dict) -> int:
    """What every decode step reads whatever the routing."""
    h, layers = model["hidden_size"], model["num_hidden_layers"]
    router = h * model.get("n_routed_experts_published",
                           model["n_routed_experts"])
    shared = model["n_shared_experts"] * expert_params(model)
    return (len(gqa_layers(model)) * gqa_params(model)
            + len(kda_layers(model)) * kda_params(model)
            + layers * (router + shared) + h * model["vocab_size"])


def decode_step_bytes(model: dict, live_tokens: float, live_rows: float,
                      touched_share: float, weight_dtype: str,
                      kv_dtype: str = "bfloat16") -> float:
    """One decode step: the weights above, `touched_share` of the HELD
    experts (`n_routed_experts`) a layer, the live tokens' K and V in the
    GQA layers, the live rows' state (read and written) and tail in the KDA
    layers."""
    wb = costs._DTYPE_BYTES[weight_dtype]
    experts = (touched_share * model["num_hidden_layers"]
               * model["n_routed_experts"] * expert_params(model))
    kv = gqa_attn_bytes(model, live_tokens, kv_dtype)
    state = live_rows * len(kda_layers(model)) * (
        2 * state_bytes_per_row_layer(model)
        + 2 * tail_bytes_per_row_layer(model, weight_dtype))
    return (always_read_params(model) + experts) * wb + kv + state


def decode_step_flops(model: dict, rows: float, held_pairs_per_row: float,
                      live_tokens: float, live_rows: float) -> float:
    active = (model["num_hidden_layers"] * held_pairs_per_row
              * expert_params(model))
    attn = gqa_attn_flops(model, live_tokens)
    return (2.0 * (always_read_params(model) + active) * rows + attn
            + kda_decode_flops(model, live_rows))
