"""Operations and bytes the algorithm needs, from shapes alone. The
roofline shares divide these by the peaks in `peaks.json` and by device time
from the trace; no PR that claims a gain can change them.

Everything is per device: under tensor parallelism `tp` a device holds
1/tp of the KV heads and 1/tp of every matrix (embedding and head included),
as `engine/memory.py` lays them out.
"""

from __future__ import annotations

_DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1, "float8_e4m3fn": 1}


def kv_bytes_per_token_per_layer(model: dict, kv_dtype: str = "bfloat16",
                                 tp: int = 1) -> int:
    """K and V of one token in one layer, on one device."""
    kvh = max(1, model["num_key_value_heads"] // tp)
    return 2 * kvh * model["head_dim"] * _DTYPE_BYTES[kv_dtype]


def kv_bytes_per_token(model: dict, kv_dtype: str = "bfloat16",
                       tp: int = 1) -> int:
    """All layers: 144 KiB for Qwen3-8B, 56 KiB for Qwen2-7B (bf16)."""
    return (model["num_hidden_layers"]
            * kv_bytes_per_token_per_layer(model, kv_dtype, tp))


def matmul_params(model: dict) -> int:
    """Weights every decode step reads: the layers' attention and MLP
    matrices and the output head. The embedding table is gathered (one row
    per token), not read, so it is left out unless tied to the head."""
    h, hd = model["hidden_size"], model["head_dim"]
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    it, layers = model["intermediate_size"], model["num_hidden_layers"]
    attn = h * nh * hd + 2 * h * nkv * hd + nh * hd * h
    mlp = 3 * h * it
    head = h * model["vocab_size"]
    return layers * (attn + mlp) + head


def weight_bytes(model: dict, weight_dtype: str, tp: int = 1) -> int:
    """Bytes of those weights on one device (scales of int8 weights, one
    bf16 per output channel, are under 0.1% and left out)."""
    return matmul_params(model) * _DTYPE_BYTES[weight_dtype] // tp


def decode_attn_bytes(model: dict, live_tokens: float,
                      kv_dtype: str = "bfloat16", tp: int = 1) -> float:
    """Bytes the decode attention of ONE step must read over all layers:
    the K and V of every live context token, once."""
    return live_tokens * kv_bytes_per_token(model, kv_dtype, tp)


def decode_attn_flops(model: dict, live_tokens: float, tp: int = 1) -> float:
    """QK^T and PV for one new token per row against its context."""
    nh = model["num_attention_heads"] // tp
    return (4.0 * nh * model["head_dim"] * live_tokens
            * model["num_hidden_layers"])


def decode_step_bytes(model: dict, live_tokens: float, weight_dtype: str,
                      kv_dtype: str = "bfloat16", tp: int = 1) -> float:
    """One decode step: every weight once plus the live KV once."""
    return (weight_bytes(model, weight_dtype, tp)
            + decode_attn_bytes(model, live_tokens, kv_dtype, tp))


def decode_step_flops(model: dict, rows: float, live_tokens: float,
                      tp: int = 1) -> float:
    return (2.0 * matmul_params(model) * rows / tp
            + decode_attn_flops(model, live_tokens, tp))


def roofline_seconds(flops: float, nbytes: float, peak: dict,
                     ) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it. Operations
    are held to the bf16 peak: int8 weights are dequantised and multiplied
    in bf16 (weight-only quantisation), so the int8 peak is not theirs."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c > t_m else (t_m, "memory")
