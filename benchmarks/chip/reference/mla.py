"""A plain reference for the DeepSeek-V3-family decoder this stack serves
(Kanana-2-30B-A3B, `model_type: deepseek_v3`): the forward pass in
straightforward `jax.numpy`, float32, every matmul under
`jax.default_matmul_precision("highest")` (a TPU's default float32 matmul is
bf16 passes). No KV cache, no kernels, no batching, no absorption: one
sequence, all positions, one layer at a time, the published equations in
their expanded form, parametrised by the published `config.json` keys:

    x' = RMSNorm(x)
    q  = W_q x'                     -> heads x (qk_nope ‖ qk_rope)
    c ‖ k_rope = W_kv_a x'          -> kv_lora_rank ‖ qk_rope (ONE for all heads)
    k_nope ‖ v = W_kv_b RMSNorm(c)  -> heads x (qk_nope ‖ v_head)
    k  = k_nope ‖ rope(k_rope),  q = q_nope ‖ rope(q_rope)
    h  = x + W_o softmax_causal(q · k / sqrt(qk_nope + qk_rope)) v
    layer <  first_k_dense_replace:  y = h + SwiGLU(RMSNorm(h))
    layer >= first_k_dense_replace:  s = sigmoid(W_r h') in float32;
        experts = top-k of s + e_score_correction_bias; w = s[experts],
        / Σ w under norm_topk_prob, x routed_scaling_factor;
        y = h + Σ_e w_e SwiGLU_e(h') + SwiGLU_shared(h')

Rope is the checkpoint's: with `rope_interleave` the rope columns are pairs
(x0, x1), (x2, x3), ..., pair i turned by position x theta^(-2i/d). (The
served program de-interleaves those columns of W_q and W_kv_a once at load
and applies rotate-half rope; scores are invariant under a permutation
applied to q_rope and k_rope alike. This file is handed the weights in
CHECKPOINT form, so that permutation is under test.)

Departures from `transformers`' `modeling_deepseek_v3.py`, each because this
file is float32 throughout or because the configuration needs no more:

- RMSNorm multiplies by its weight in float32 (HF downcasts first: the same
  thing in float32); the routing weights stay float32;
- every expert is evaluated on every position and weighted by `w_e` or 0
  (one expert at a time, so that one expert's float32 weights and
  intermediates are live), instead of gathering each expert's positions:
  the same sum, in expert order;
- no group step in the routing: `n_group` = `topk_group` = 1 (a config with
  more is refused); `q_lora_rank`, `rope_scaling` (yarn, its mscale),
  `attention_bias` are refused; ties in the top-k go to the lower expert
  index (`jax.lax.top_k`), `torch.topk` does not say;
- `forward(forced_experts=...)` can be told which experts a served program
  chose, and then weighs THOSE by its own scores (see `route`): with
  renormalised top-6-of-128 weights a boundary expert flipped by bf16
  rounding swaps a sixth of the routed output, and a free-running
  comparison then measures that, amplified over the layers, and nothing else;
- attention is computed in blocks of `query_block` queries (each against
  all its keys at once): the same softmax, without the (heads, T, T) array;
- the interleaved rope leaves its output in the checkpoint's pair order
  where HF's de-interleaves it: q and k alike, so scores do not change.

`weights` maps the checkpoint's tensor names (`model.layers.3.self_attn.
kv_b_proj.weight`, ... — (out, in) matrices as `transformers` stores them)
to arrays of any float dtype; each is widened to float32 where it is used.

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
    return {
        "hidden_size": model_cfg.hidden_size,
        "intermediate_size": model_cfg.intermediate_size,
        "moe_intermediate_size": model_cfg.moe_intermediate_size,
        "num_hidden_layers": model_cfg.num_layers,
        "num_attention_heads": model_cfg.num_heads,
        "kv_lora_rank": model_cfg.kv_lora_rank,
        "qk_nope_head_dim": model_cfg.qk_nope_head_dim,
        "qk_rope_head_dim": model_cfg.qk_rope_head_dim,
        "v_head_dim": model_cfg.v_head_dim,
        "q_lora_rank": None,
        "rope_theta": model_cfg.rope_theta,
        "rope_interleave": model_cfg.rope_interleave,
        "rope_scaling": None,
        "rms_norm_eps": model_cfg.rms_norm_eps,
        "tie_word_embeddings": model_cfg.tie_word_embeddings,
        "first_k_dense_replace": model_cfg.first_k_dense_replace,
        "n_routed_experts": model_cfg.num_experts,
        "num_experts_per_tok": model_cfg.num_experts_per_tok,
        "n_shared_experts": model_cfg.n_shared_experts,
        "norm_topk_prob": model_cfg.norm_topk_prob,
        "routed_scaling_factor": model_cfg.routed_scaling_factor,
        "scoring_func": model_cfg.scoring_func,
        "n_group": 1,
        "topk_group": 1,
    }


def check_config(cfg: dict) -> dict:
    """Refuse what the reference does not compute."""
    refused = [
        name for name, bad in (
            ("q_lora_rank", cfg.get("q_lora_rank") is not None),
            ("rope_scaling", cfg.get("rope_scaling") is not None),
            ("n_group", (cfg.get("n_group") or 1) != 1),
            ("topk_group", (cfg.get("topk_group") or 1) != 1),
            ("attention_bias", bool(cfg.get("attention_bias"))),
            ("scoring_func", cfg.get("scoring_func", "sigmoid") != "sigmoid"),
        ) if bad]
    if refused:
        raise ValueError(f"the reference does not cover {refused}")
    out = dict(cfg)
    out["rope_interleave"] = bool(cfg.get("rope_interleave", True))
    out["tie_word_embeddings"] = bool(cfg.get("tie_word_embeddings", False))
    return out


def f32(a) -> jax.Array:
    return jnp.asarray(a).astype(jnp.float32)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x: jax.Array, positions: jax.Array, theta: float,
         interleave: bool) -> jax.Array:
    """Rope on (T, heads, d): pair i of the vector turned by position x
    theta^(-2i/d). Interleaved: pair i is (x[2i], x[2i+1]); else
    (x[i], x[i + d/2]) (rotate-half)."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    if interleave:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(cfg: dict, get, p: str, x: jax.Array,
              query_block: int) -> jax.Array:
    """Causal latent attention of one sequence in its EXPANDED form,
    (T, h) normed input -> (T, h)."""
    t = x.shape[0]
    nh = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    positions = jnp.arange(t)
    q = (x @ get(p + "self_attn.q_proj.weight").T).reshape(t, nh, dn + dr)
    ckr = x @ get(p + "self_attn.kv_a_proj_with_mqa.weight").T  # (T, r + dr)
    c_kv = rms_norm(ckr[:, :r], get(p + "self_attn.kv_a_layernorm.weight"),
                    cfg["rms_norm_eps"])
    kv = (c_kv @ get(p + "self_attn.kv_b_proj.weight").T).reshape(
        t, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_rope = rope(q[..., dn:], positions, cfg["rope_theta"],
                  cfg["rope_interleave"])
    k_rope = rope(ckr[:, None, r:], positions, cfg["rope_theta"],
                  cfg["rope_interleave"])  # (T, 1, dr): one for all heads
    q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (t, nh, dr))], axis=-1)
    outs = []
    for lo in range(0, t, query_block):
        hi = min(t, lo + query_block)
        scores = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) * (
            dn + dr) ** -0.5
        causal = positions[lo:hi, None] >= positions[None, :hi]
        probs = jax.nn.softmax(
            jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v[:hi]))
    out = jnp.concatenate(outs, axis=0).reshape(t, nh * dv)
    return out @ get(p + "self_attn.o_proj.weight").T


def swiglu(get, p: str, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ get(p + "gate_proj.weight").T)
            * (x @ get(p + "up_proj.weight").T)) @ get(
                p + "down_proj.weight").T


def route(cfg: dict, get, p: str, x: jax.Array, forced=None):
    """(combine weights (T, E), chosen experts (T, k), margin (T,)): the
    weight of each chosen expert, 0 elsewhere; and how far the k-th chosen
    biased score lies above the best one left out — a system in bf16 can
    flip the two where that margin is within its rounding.

    `forced` (T, k): the experts a served program chose. The weights are
    then computed for THOSE experts (from this file's own scores), `experts`
    stays this file's own choice, and the margin says how far the worst of
    the forced experts lies below this file's k-th best biased score: 0
    where the two sets agree, within rounding where a system in bf16 chose
    otherwise, wide where its choice is a fault."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ get(p + "mlp.gate.weight").T)
    biased = scores + get(p + "mlp.gate.e_score_correction_bias")
    top, experts = jax.lax.top_k(biased, k + 1)
    margin = top[:, k - 1] - top[:, k]
    experts = experts[:, :k]
    used = experts
    if forced is not None:
        used = jnp.asarray(forced)
        worst = jnp.min(jnp.take_along_axis(biased, used, axis=-1), axis=-1)
        margin = top[:, k - 1] - worst
    w = jnp.take_along_axis(scores, used, axis=-1)  # never the bias
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    combine = jnp.sum(
        jax.nn.one_hot(used, scores.shape[-1]) * w[..., None], axis=1)
    return combine, experts, margin


def forward(cfg: dict, weights, tokens, logit_positions=None, *,
            query_block: int = 256, forced_experts=None,
            ) -> tuple[jax.Array, list]:
    """Logits (T, vocab) of every position of one sequence (or of
    `logit_positions` only), and each expert layer's routing (None for a
    dense layer). `forced_experts`: per expert layer, in order, the (T, k)
    experts a served program chose (`route`): the forward then follows the
    served choices and reports how far each lay from its own. Nothing is jitted as a whole: each tensor is widened to
    float32 where it is used, so one matrix's copy is live at a time."""
    cfg = check_config(cfg)
    eps = cfg["rms_norm_eps"]

    def get(name):
        return f32(weights[name])

    def run(fn, *args):
        with jax.default_matmul_precision(_PRECISION):
            return fn(*args)

    x = f32(jnp.asarray(weights["model.embed_tokens.weight"])[
        jnp.asarray(tokens)])
    routing = []
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        x = x + run(
            attention, cfg, get, p,
            rms_norm(x, get(p + "input_layernorm.weight"), eps), query_block)
        xn = rms_norm(x, get(p + "post_attention_layernorm.weight"), eps)
        if i < cfg["first_k_dense_replace"] or not cfg["n_routed_experts"]:
            x = x + run(swiglu, get, p + "mlp.", xn)
            routing.append(None)
            continue
        forced = None
        if forced_experts is not None:
            forced = forced_experts[sum(r is not None for r in routing)]
        combine, experts, margin = run(route, cfg, get, p, xn, forced)
        y = run(swiglu, get, p + "mlp.shared_experts.", xn) if cfg[
            "n_shared_experts"] else jnp.zeros_like(x)
        for e in range(cfg["n_routed_experts"]):
            # every expert on every position, weighted by w_e or 0
            y = y + combine[:, e:e + 1] * run(
                swiglu, get, p + f"mlp.experts.{e}.", xn)
        x = x + y
        routing.append({"experts": experts, "margin": margin})
    if logit_positions is not None:
        x = x[jnp.asarray(list(logit_positions))]
    x = rms_norm(x, get("model.norm.weight"), eps)
    head = ("model.embed_tokens.weight" if cfg["tie_word_embeddings"]
            else "lm_head.weight")
    with jax.default_matmul_precision(_PRECISION):
        return x @ get(head).T, routing
