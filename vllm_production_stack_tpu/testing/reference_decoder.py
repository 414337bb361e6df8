"""A plain reference for the decoders this stack serves: the forward pass in
straightforward `jax.numpy`, float32, every matmul under
`jax.default_matmul_precision("highest")` (a TPU's default float32 matmul is
bf16 passes). No KV cache, no kernels, no batching, no quantised arithmetic:
one sequence, all positions at once, one layer at a time so that only one
layer's float32 weights need to be live.

It follows the published layer equations of the Llama lineage, parametrised
by the published `config.json` keys, and covers what the benchmark serves:

    h = x + W_o · Attn(rope(q), rope(k), v)      q, k, v = W_{q,k,v} · norm(x) (+ bias)
    y = h + MLP(norm(h))

- `qk_norm` (Qwen3): RMSNorm over each head of q and k, before rope;
  `qk_norm_flat` (OLMoE): RMSNorm over the whole flat q and k projections;
- `attention_bias` (Qwen2): biases on q, k, v; grouped-query attention by
  `num_key_value_heads`; rotate-half rope with `rope_theta`, no scaling;
  causal softmax attention scaled by head_dim ** -0.5;
- dense SwiGLU `W_down(silu(W_gate x) ⊙ W_up x)`, or, with `num_experts`,
  `Σ_{e ∈ top-k} p_e · W_down,e(silu(W_gate,e x) ⊙ W_up,e x)` with
  `p = softmax(W_r x)` over all experts, renormalised over the chosen k only
  under `norm_topk_prob` (Mixtral: always; OLMoE: never).

Departures from `transformers`' `modeling_olmoe.py` (and its Qwen2, Qwen3 and
Mixtral siblings), each because this file is float32 throughout:

- RMSNorm multiplies by its weight in float32; HF downcasts to the input
  dtype first (the same thing when that dtype is float32);
- the routing weights stay float32 (HF casts them to the hidden dtype);
- every expert is evaluated on every position and weighted by `p_e` or 0,
  instead of gathering each expert's positions: the same sum, in expert
  order, with no gather;
- `clip_qkv` and rope scaling are not implemented: a config that sets either
  is refused (OLMoE-1B-7B's is null);
- ties in the top-k go to the lower expert index (`jax.lax.top_k`);
  `torch.topk` does not say.

The weights are the system's own tree (`models/llama.py:init_params`: (in,
out) matrices stacked over layers, experts (L, E, in, out)), taken AS SERVED:
an int8 leaf `{"q", "s"}` is widened to `q · s` in float32 (`widen`), so a
comparison sees the system's arithmetic and not its quantisation.

This file has a byte-identical copy under `benchmarks/chip/reference/` (a
tier-1 test holds them together); it imports nothing from the package.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_PRECISION = "highest"


def published_keys(model_cfg) -> dict:
    """The reference's configuration from the system's `ModelConfig` (or
    anything with its attributes), under the published key names."""
    unsupported = [
        k for k in ("rope_scaling_type", "sliding_window", "sandwich_norms",
                    "post_norms_only", "rms_norm_add_one", "scale_embeddings",
                    "attn_logit_softcap", "final_logit_softcap")
        if getattr(model_cfg, k, None)
    ]
    if unsupported or getattr(model_cfg, "hidden_act", "silu") != "silu":
        raise ValueError(f"the reference does not cover {unsupported or 'hidden_act'}")
    return {
        "hidden_size": model_cfg.hidden_size,
        "num_hidden_layers": model_cfg.num_layers,
        "num_attention_heads": model_cfg.num_heads,
        "num_key_value_heads": model_cfg.num_kv_heads,
        "head_dim": model_cfg.head_dim,
        "rms_norm_eps": model_cfg.rms_norm_eps,
        "rope_theta": model_cfg.rope_theta,
        "tie_word_embeddings": model_cfg.tie_word_embeddings,
        "attention_bias": model_cfg.attention_bias,
        "qk_norm": model_cfg.qk_norm,
        "qk_norm_flat": model_cfg.qk_norm_flat,
        "num_experts": model_cfg.num_experts,
        "num_experts_per_tok": model_cfg.num_experts_per_tok,
        "norm_topk_prob": model_cfg.norm_topk_prob,
    }


def check_config(cfg: dict) -> dict:
    """Refuse what the reference does not compute, fill the optional keys."""
    if cfg.get("clip_qkv") is not None or cfg.get("rope_scaling") is not None:
        raise ValueError("clip_qkv and rope_scaling are not implemented")
    out = dict(cfg)
    out.setdefault("head_dim", cfg["hidden_size"] // cfg["num_attention_heads"])
    for key in ("attention_bias", "qk_norm", "qk_norm_flat",
                "tie_word_embeddings", "norm_topk_prob"):
        out[key] = bool(cfg.get(key, False))
    out["num_experts"] = int(cfg.get("num_experts") or 0)
    return out


def widen(leaf) -> jax.Array:
    """A served weight as float32: an int8 leaf is `q · s`, its scale per
    output channel (and per expert) as the system applies it."""
    if isinstance(leaf, dict):
        return leaf["q"].astype(jnp.float32) * leaf["s"].astype(jnp.float32)
    return leaf.astype(jnp.float32)


def layer_weights(params: dict, i: int) -> dict:
    """Layer i of the stacked tree, widened: the only float32 copy of
    weights the reference holds at a time."""
    def cut(leaf):
        if isinstance(leaf, dict) and "q" in leaf:
            return widen({"q": leaf["q"][i], "s": leaf["s"][i]})
        if isinstance(leaf, dict):
            return {k: cut(v) for k, v in leaf.items()}
        return leaf[i].astype(jnp.float32)

    return cut(params["layers"])


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate-half rope on (T, heads, D)."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * cos + rotated * sin


def attention(cfg: dict, w: dict, x: jax.Array) -> jax.Array:
    """Causal self-attention of one sequence, (T, h) -> (T, h)."""
    t = x.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q, k, v = x @ w["wq"], x @ w["wk"], x @ w["wv"]
    if cfg["attention_bias"]:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    if cfg["qk_norm_flat"]:
        q = rms_norm(q, w["q_norm"], cfg["rms_norm_eps"])
        k = rms_norm(k, w["k_norm"], cfg["rms_norm_eps"])
    q, k, v = q.reshape(t, nh, d), k.reshape(t, nkv, d), v.reshape(t, nkv, d)
    if cfg["qk_norm"]:
        q = rms_norm(q, w["q_norm"], cfg["rms_norm_eps"])
        k = rms_norm(k, w["k_norm"], cfg["rms_norm_eps"])
    positions = jnp.arange(t)
    q = rope(q, positions, cfg["rope_theta"])
    k = rope(k, positions, cfg["rope_theta"])
    k = jnp.repeat(k, nh // nkv, axis=1)  # each kv head serves its group
    v = jnp.repeat(v, nh // nkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
    causal = positions[:, None] >= positions[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(t, nh * d) @ w["wo"]


def route(cfg: dict, router: jax.Array, x: jax.Array):
    """(combine weights (T, E), chosen experts (T, k), margin (T,)): the
    softmax mass of the chosen experts, 0 elsewhere; and how far the k-th
    chosen probability lies above the best one left out — a system in bf16
    can flip the two where that margin is within its rounding."""
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(x @ router, axis=-1)
    top, experts = jax.lax.top_k(probs, k + 1)
    margin = top[:, k - 1] - top[:, k]
    top, experts = top[:, :k], experts[:, :k]
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    combine = jnp.sum(
        jax.nn.one_hot(experts, probs.shape[-1]) * top[..., None], axis=1)
    return combine, experts, margin


def mlp(cfg: dict, w: dict, x: jax.Array):
    """(T, h) -> ((T, h), routing or None)."""
    if not cfg["num_experts"]:
        m = w["mlp"]
        return (jax.nn.silu(x @ m["gate"]) * (x @ m["up"])) @ m["down"], None
    m = w["moe"]
    combine, experts, margin = route(cfg, m["router"], x)
    inner = jax.nn.silu(jnp.einsum("th,ehi->tei", x, m["gate"])) * jnp.einsum(
        "th,ehi->tei", x, m["up"])
    out = jnp.einsum("tei,eih->teh", inner, m["down"])
    return (jnp.einsum("teh,te->th", out, combine),
            {"experts": experts, "margin": margin})


def layer(cfg: dict, w: dict, x: jax.Array):
    """One decoder layer on one sequence: (T, h) -> ((T, h), routing)."""
    with jax.default_matmul_precision(_PRECISION):
        h = x + attention(
            cfg, w["attn"], rms_norm(x, w["input_norm"], cfg["rms_norm_eps"]))
        out, routing = mlp(
            cfg, w, rms_norm(h, w["post_attn_norm"], cfg["rms_norm_eps"]))
        return h + out, routing


def logits(cfg: dict, params: dict, x: jax.Array) -> jax.Array:
    with jax.default_matmul_precision(_PRECISION):
        x = rms_norm(x, params["final_norm"].astype(jnp.float32),
                     cfg["rms_norm_eps"])
        if cfg["tie_word_embeddings"]:
            return x @ params["embed"].astype(jnp.float32).T
        return x @ widen(params["lm_head"])


def forward(cfg: dict, params: dict, tokens) -> tuple[jax.Array, list]:
    """Logits (T, vocab) of every position of one sequence, and each MoE
    layer's routing. `layer` is jitted per layer call, so one layer's
    widened weights are live at a time."""
    cfg = check_config(cfg)
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    step = jax.jit(lambda w, x: layer(cfg, w, x))
    routing = []
    for i in range(cfg["num_hidden_layers"]):
        x, r = step(layer_weights(params, i), x)
        routing.append(r)
    return logits(cfg, params, x), routing
