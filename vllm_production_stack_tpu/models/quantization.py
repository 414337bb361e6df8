"""Weight-only int8 quantization (per-output-channel symmetric).

The reference serves 8B-class models on 16-24 GiB GPUs in bf16; one v5e chip
has 16 GiB HBM, so Llama-3-8B bf16 weights (~16 GiB) cannot fit next to a KV
pool. int8 weight-only quantization (the vLLM `--quantization` family's
simplest member) halves the weight bytes: every linear weight W (…, in, out)
— or (…, heads, head_dim, in), `llama.contraction_axis` — is stored as int8 with one
float32 scale per output channel
(scale = max|W|/127 over the contraction axis), and the matmul dequantizes
on the fly — `(x @ q.astype(bf16)) * s` — which XLA fuses into the matmul
epilogue. The HBM read of the weight is the int8 tensor, so bandwidth-bound
decode gets the 2x too.

Quantized leaves: attention wq/wk/wv/wo, dense MLP gate/up/down, the MoE
experts' gate/up/down (one scale per expert and output channel on the
(L, E, in, out) leaves; the grouped matmul of models/llama.py:_grouped_mm
takes the int8 tensor as it is — OLMoE-1B-7B's 6.44 B expert parameters fit
one v5e chip only so), lm_head.
NOT quantized: embedding (a gather, not a matmul; quality-sensitive), norms,
biases, and the MoE router (h x E per layer, and top-k is decided on it).

Enable with ModelConfig(quantization="int8") / engine `--quantization int8`.
The model fingerprint covers it (quantized weights produce different
activations, hence different KV bytes — cross-engine KV sharing between
int8 and bf16 engines must not match).
"""

from __future__ import annotations

import numpy as np

from .llama import contraction_axis

QUANTIZED_ATTN = ("wq", "wk", "wv", "wo")
QUANTIZED_MLP = ("gate", "up", "down")


def is_quantized_leaf(w) -> bool:
    return isinstance(w, dict) and "q" in w and "s" in w


def _quantize(w, axis: int = -2):
    """(…, in, out) -> {"q": int8, "s": float32 (…, 1, out)}: one scale an
    output channel, the maximum over the contraction `axis`
    (`llama.contraction_axis`). A leaf stored (…, heads, head_dim, in)
    contracts its LAST axis and its "s" is (…, heads, head_dim) — the same
    scale a channel, where the channel lies. Works on numpy (host-side
    checkpoint path) and jax arrays (jitted init path). A stacked expert
    leaf (L, E, in, out) goes one layer at a time, so the float32 working
    copy is one layer's experts (0.5 GiB at OLMoE-1B-7B), not the leaf's
    (8 GiB)."""
    if w.ndim >= 4 and axis == -2:
        if isinstance(w, np.ndarray):
            parts = [_quantize(layer) for layer in w]
            return {k: np.stack([p[k] for p in parts]) for k in ("q", "s")}
        import jax

        return jax.lax.map(_quantize, w)
    xp = np if isinstance(w, np.ndarray) else _jnp()
    wf = w.astype(xp.float32)
    amax = xp.max(xp.abs(wf), axis=axis, keepdims=True)
    scale = xp.maximum(amax, 1e-8) / 127.0
    q = xp.clip(xp.round(wf / scale), -127, 127).astype(xp.int8)
    scale = scale.astype(xp.float32)
    return {"q": q, "s": scale if axis == -2 else scale[..., 0]}


def _jnp():
    import jax.numpy as jnp

    return jnp


def quantize_params(cfg, params: dict) -> dict:
    """Quantize the linear weights of an init_params/load_checkpoint_params
    tree. Pure function of arrays — run it under jit for on-device
    quantization (XLA frees each bf16 leaf right after its int8 twin is
    built, so peak HBM is the int8 tree + the f32 copy of the largest
    stacked dense leaf, not the bf16 tree: 8.21 + 6.75 GiB at qwen3-8b,
    model_runner._init_device_params; an expert leaf is quantised one layer
    at a time, _quantize), or on numpy for the host-side checkpoint path."""
    if cfg.quantization is None:
        return params
    if cfg.quantization != "int8":
        raise ValueError(
            f"unknown quantization {cfg.quantization!r} (supported: int8)"
        )
    out = dict(params)
    layers = dict(params["layers"])
    attn = dict(layers["attn"])
    for name in QUANTIZED_ATTN:
        attn[name] = _quantize(attn[name], contraction_axis("attn", name))
    layers["attn"] = attn
    if "mlp" in layers:
        mlp = dict(layers["mlp"])
        for name in QUANTIZED_MLP:
            mlp[name] = _quantize(mlp[name])
        layers["mlp"] = mlp
    if "moe" in layers:
        moe = dict(layers["moe"])
        for name in QUANTIZED_MLP:
            moe[name] = _quantize(moe[name])
        layers["moe"] = moe
    out["layers"] = layers
    if "lm_head" in params:
        out["lm_head"] = _quantize(params["lm_head"])
    return out


def quantize_specs(cfg, specs: dict) -> dict:
    """Mirror quantize_params on a llama_param_specs tree: each quantized
    leaf's spec becomes {"q": <w spec>, "s": <w spec with the contraction
    axis unsharded>} — the scale's axis -2 has size 1; a leaf that contracts
    its last axis (`llama.contraction_axis`) has a scale without it."""
    if cfg.quantization is None:
        return specs
    from jax.sharding import PartitionSpec as P

    def q(spec: P, axis: int = -2) -> dict:
        parts = list(spec)
        if axis == -1:
            parts = parts[:-1]
        elif len(parts) >= 2:
            parts[-2] = None
        return {"q": spec, "s": P(*parts)}

    out = dict(specs)
    layers = dict(specs["layers"])
    attn = dict(layers["attn"])
    for name in QUANTIZED_ATTN:
        attn[name] = q(attn[name], contraction_axis("attn", name))
    layers["attn"] = attn
    if "mlp" in layers:
        mlp = dict(layers["mlp"])
        for name in QUANTIZED_MLP:
            mlp[name] = q(mlp[name])
        layers["mlp"] = mlp
    if "moe" in layers:
        moe = dict(layers["moe"])
        for name in QUANTIZED_MLP:
            moe[name] = q(moe[name])
        layers["moe"] = moe
    out["layers"] = layers
    if "lm_head" in specs:
        out["lm_head"] = q(specs["lm_head"])
    return out


def quantized_param_bytes(cfg, tp: int = 1, pp: int = 1, ep: int = 1) -> int:
    """Per-device weight bytes under int8 quantization (engine/memory.py
    delegates here when cfg.quantization is set): quantized leaves cost
    1 byte/param + 4 bytes/output-channel; embed (+norms, biases, the MoE
    router) stay at cfg.dtype. An MoE layer holds num_experts SwiGLUs
    (1/ep of them per device) and its router."""
    from ..engine.memory import dtype_bytes, qk_norm_params

    h, hd = cfg.hidden_size, cfg.head_dim
    nh, nkv, it, L = (
        cfg.num_heads, cfg.num_kv_heads, cfg.intermediate_size,
        cfg.num_layers,
    )
    db = dtype_bytes(cfg.dtype)
    layers_per_stage = (L + pp - 1) // pp
    # int8 payloads (sharded over tp like their bf16 counterparts)
    attn_q = (h * nh * hd + 2 * h * nkv * hd + nh * hd * h) // tp
    mlp_q = 3 * h * it // tp
    # per-output-channel f32 scales
    attn_s = (nh * hd + 2 * nkv * hd + h) // tp * 4
    mlp_s = (2 * it + h) // tp * 4
    router = 0
    if cfg.num_experts:
        local = cfg.num_experts // ep
        mlp_q, mlp_s = local * mlp_q, local * mlp_s
        router = h * cfg.num_experts * db
    per_layer = attn_q + mlp_q + attn_s + mlp_s + router + 2 * h * db
    total = cfg.vocab_size * h // tp * db  # embed stays unquantized
    total += layers_per_stage * per_layer + h * db
    if not cfg.tie_word_embeddings:
        total += h * cfg.vocab_size // tp + cfg.vocab_size // tp * 4
    if cfg.attention_bias:
        total += layers_per_stage * (nh * hd + 2 * nkv * hd) // tp * db
    total += layers_per_stage * qk_norm_params(cfg) * db
    return total

