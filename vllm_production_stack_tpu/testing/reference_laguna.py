"""A plain reference for the Laguna decoder this stack serves (Laguna-S-2.1,
`model_type: laguna`): the forward pass in straightforward `jax.numpy`,
float32, every matmul under `jax.default_matmul_precision("highest")` (a
TPU's default float32 matmul is bf16 passes). No KV cache, no kernels, no
batching: one sequence, all positions, one layer at a time, parametrised by
the published `config.json` keys (x the residual stream, pre-norm Llama
layout):

    u = RMSNorm(x)
    q = W_q u -> n_i x D;  k = W_k u, v = W_v u -> kvH x D   (no bias)
        n_i = num_attention_heads_per_layer[i]: the layer's own head count
    rope by the layer's kind (rope_parameters[layer_types[i]]):
        sliding_attention: every dim of a head, theta 10 000;
        full_attention: the first partial_rotary_factor x D dims (rotate-
        half over those, the rest pass through), theta 500 000, yarn:
        inv_freq_j = theta^(-2j/d), corr(b) = d ln(orig / (2 pi b)) /
        (2 ln theta), low = floor(corr(beta_fast)), high =
        ceil(corr(beta_slow)) clamped to [0, d - 1], ramp g_j =
        clip((j - low) / (high - low), 0, 1), inv_freq'_j = (1 - g_j)
        inv_freq_j + g_j inv_freq_j / factor; cos and sin x attention_factor
    a query at position p sees keys at positions <= p (full_attention) or
        p - sliding_window < position <= p (sliding_attention);
        softmax(q . k / sqrt(D)) v in float32
    g = sigmoid(W_g u) -> n_i: one gate a head and token; o_head *= g_head
    h = x + W_o concat(o)
    layer in mlp_only_layers:  y = h + SwiGLU(RMSNorm(h))
    else: s = sigmoid(W_r h') in float32 over ALL num_experts;
        experts = top-k of s; w = s[experts] / sum (norm_topk_prob) x
        moe_routed_scaling_factor; y = h + sum_e w_e SwiGLU_e(h')
        + SwiGLU_shared(h')

THE SHARE. `experts_held` = (first, count): the experts whose weights this
chip of an expert-parallel deployment holds. The router scores and the
top-k are over all `num_experts`; the sum runs over the chosen experts that
are HELD; what the absent experts would add is left out, here as in the
served program (models/llama.py:_moe_mlp), and that partial result goes on
to the next layer. `vocab_size` is the slice of the vocabulary held: the
embedding's rows and the head's columns [0, vocab_size).

ASSUMED, because the published config has no key for it (each also in the
benchmark configuration's `assumed`):

- the router's score function is the logistic sigmoid (every public config
  that pairs a route scale near 2.5 with renormalised top-k uses it:
  DeepSeek-V3, Kanana-2); softmax is the alternative not taken;
- the output gate's nonlinearity is the logistic sigmoid, head-wise (gated
  attention, arXiv:2505.06708), computed from the same normed input as q;
- no q/k norm (no key); the shared expert is ungated; `hidden_act` silu;
- no selection bias in the routing (no key).

Departures from a `transformers`-style implementation, each because this
file is float32 throughout or because the configuration needs no more:

- RMSNorm multiplies by its weight in float32; routing weights stay float32;
- every HELD expert is evaluated on every position and weighted by `w_e` or
  0 (one expert at a time), instead of gathering each expert's positions:
  the same sum, in expert order;
- ties in the top-k go to the lower expert index (`jax.lax.top_k`);
- `forward(forced_experts=...)` can be told which experts a served program
  chose, and then weighs THOSE by its own scores (see `route`): with
  renormalised top-10-of-256 weights a boundary expert flipped by bf16
  rounding swaps a tenth of the routed output, and a free-running comparison
  then measures that, amplified over the layers, and nothing else;
- attention is computed in blocks of `query_block` queries — their
  projection, gate and output projection with them — each against the keys
  it may see (a window layer's block against the window before it, a full
  layer's against every key under its mask): the same softmax, without the
  (heads, T, T) array or a long sequence's (T, heads, D) queries.

`weights` maps tensor names in the `transformers` convention
(`model.layers.3.self_attn.q_proj.weight`, ... — (out, in) matrices; the
gate is `self_attn.g_proj.weight`, the router `mlp.gate.weight`, experts
`mlp.experts.<global index>.{gate,up,down}_proj.weight`, the shared expert
`mlp.shared_expert.*`) to arrays of any float dtype; each is widened to
float32 where it is used.

This file has a byte-identical copy under `benchmarks/chip/reference/` (a
tier-1 test holds them together); it imports nothing from the package.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_PRECISION = "highest"


def published_keys(model_cfg) -> dict:
    """The reference's configuration from the system's `ModelConfig` (or
    anything with its attributes), under the published key names."""
    specs = model_cfg.layer_specs

    def rope_of(spec) -> dict:
        r = spec.rope
        out = {"rope_theta": r.theta, "rope_type": "default",
               "partial_rotary_factor":
                   (r.rotary_dim or model_cfg.head_dim) / model_cfg.head_dim}
        if r.scaling is not None:
            kind, factor, fast, slow, orig = r.scaling
            out.update(rope_type=kind, factor=factor, beta_fast=fast,
                       beta_slow=slow, attention_factor=r.attention_factor,
                       original_max_position_embeddings=orig)
        return out

    kinds = ["sliding_attention" if s.window else "full_attention"
             for s in specs]
    return {
        "hidden_size": model_cfg.hidden_size,
        "intermediate_size": model_cfg.intermediate_size,
        "moe_intermediate_size": model_cfg.expert_intermediate_size,
        "shared_expert_intermediate_size":
            model_cfg.n_shared_experts * model_cfg.expert_intermediate_size,
        "num_hidden_layers": model_cfg.num_layers,
        "num_key_value_heads": model_cfg.num_kv_heads,
        "head_dim": model_cfg.head_dim,
        "layer_types": kinds,
        "num_attention_heads_per_layer": [s.num_heads for s in specs],
        "mlp_only_layers": [i for i, s in enumerate(specs) if not s.experts],
        "sliding_window": model_cfg.max_window,
        "rope_parameters": {
            kind: rope_of(specs[kinds.index(kind)]) for kind in set(kinds)},
        "gating": "per-head" if model_cfg.attn_output_gate else None,
        "rms_norm_eps": model_cfg.rms_norm_eps,
        "tie_word_embeddings": model_cfg.tie_word_embeddings,
        "num_experts": model_cfg.num_experts,
        "num_experts_per_tok": model_cfg.num_experts_per_tok,
        "norm_topk_prob": model_cfg.norm_topk_prob,
        "moe_routed_scaling_factor": model_cfg.routed_scaling_factor,
        "experts_held": (model_cfg.first_expert_held,
                         model_cfg.held_experts),
        "vocab_size": model_cfg.vocab_size,
    }


def check_config(cfg: dict) -> dict:
    """Refuse what the reference does not compute."""
    refused = [
        name for name, bad in (
            ("attention_bias", bool(cfg.get("attention_bias"))),
            ("moe_router_logit_softcapping",
             bool(cfg.get("moe_router_logit_softcapping"))),
            ("moe_apply_router_weight_on_input",
             bool(cfg.get("moe_apply_router_weight_on_input"))),
            ("gating", cfg.get("gating") not in (None, "per-head")),
            ("rope_type", any(
                r.get("rope_type", "default") not in ("default", "yarn")
                for r in cfg["rope_parameters"].values())),
        ) if bad]
    if refused:
        raise ValueError(f"the reference does not cover {refused}")
    out = dict(cfg)
    out["tie_word_embeddings"] = bool(cfg.get("tie_word_embeddings", False))
    out.setdefault("experts_held", (0, cfg["num_experts"]))
    return out


def f32(a) -> jax.Array:
    return jnp.asarray(a).astype(jnp.float32)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def inv_frequencies(rp: dict, head_dim: int) -> tuple[jax.Array, float]:
    """(inv_freq over the rotated half-dims, the factor on cos and sin) of
    one layer kind's `rope_parameters` entry."""
    d = int(round(rp.get("partial_rotary_factor", 1.0) * head_dim))
    theta = float(rp["rope_theta"])
    j = jnp.arange(0, d, 2, dtype=jnp.float32)
    inv_freq = theta ** (-j / d)
    if rp.get("rope_type", "default") == "default":
        return inv_freq, 1.0
    orig = rp["original_max_position_embeddings"]

    def corr(beta):
        return d * math.log(orig / (beta * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(rp["beta_fast"])), 0)
    high = min(math.ceil(corr(rp["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    inv_freq = (1 - ramp) * inv_freq + ramp * inv_freq / rp["factor"]
    factor = rp.get("attention_factor")
    if factor is None:
        factor = 0.1 * math.log(rp["factor"]) + 1.0
    return inv_freq, float(factor)


def rope(x: jax.Array, positions: jax.Array, rp: dict) -> jax.Array:
    """Rope on (T, heads, D): the first d = partial_rotary_factor x D dims
    rotate (pair i is (x[i], x[i + d/2]): rotate-half), the rest pass."""
    inv_freq, factor = inv_frequencies(rp, x.shape[-1])
    d = 2 * inv_freq.shape[0]
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[:, None, :] * factor
    sin = jnp.sin(angles)[:, None, :] * factor
    a, b = x[..., : d // 2], x[..., d // 2:d]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., d:]], axis=-1)


def attention(cfg: dict, get, p: str, i: int, x: jax.Array,
              query_block: int) -> jax.Array:
    """Causal (windowed, by the layer's kind) attention of one sequence
    with the per-head output gate, (T, h) normed input -> (T, h)."""
    t = x.shape[0]
    nh = cfg["num_attention_heads_per_layer"][i]
    nkv, d = cfg["num_key_value_heads"], cfg["head_dim"]
    kind = cfg["layer_types"][i]
    window = cfg["sliding_window"] if kind == "sliding_attention" else 0
    rp = cfg["rope_parameters"][kind]
    positions = jnp.arange(t)
    k = (x @ get(p + "self_attn.k_proj.weight").T).reshape(t, nkv, d)
    v = (x @ get(p + "self_attn.v_proj.weight").T).reshape(t, nkv, d)
    k = rope(k, positions, rp)
    w_q, w_o = get(p + "self_attn.q_proj.weight"), get(
        p + "self_attn.o_proj.weight")
    w_g = (get(p + "self_attn.g_proj.weight")
           if cfg.get("gating") == "per-head" else None)
    outs = []
    for lo in range(0, t, query_block):
        # one block of queries at a time, projection and all: a long
        # sequence's (T, heads, D) queries are never held whole. A full
        # layer's block sees every key under its mask (one shape for every
        # block); a window layer's the window before it.
        hi = min(t, lo + query_block)
        k0 = max(0, lo - window + 1) if window else 0
        k1 = hi if window else t
        q = rope((x[lo:hi] @ w_q.T).reshape(hi - lo, nh, d),
                 positions[lo:hi], rp)
        # query head n reads kv head n // (nh / nkv)
        q = q.reshape(hi - lo, nkv, nh // nkv, d)
        scores = jnp.einsum("qgrd,kgd->grqk", q, k[k0:k1]) * d ** -0.5
        seen = positions[lo:hi, None] >= positions[None, k0:k1]
        if window:
            seen &= positions[None, k0:k1] > positions[lo:hi, None] - window
        probs = jax.nn.softmax(
            jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        out = jnp.einsum("grqk,kgd->qgrd", probs, v[k0:k1]).reshape(
            hi - lo, nh, d)
        if w_g is not None:
            out = out * jax.nn.sigmoid(x[lo:hi] @ w_g.T)[..., None]
        outs.append(out.reshape(hi - lo, nh * d) @ w_o.T)
    return jnp.concatenate(outs, axis=0)


def swiglu(get, p: str, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ get(p + "gate_proj.weight").T)
            * (x @ get(p + "up_proj.weight").T)) @ get(
                p + "down_proj.weight").T


def route(cfg: dict, get, p: str, x: jax.Array, forced=None):
    """(combine weights (T, E), chosen experts (T, k), margin (T,)): the
    weight of each chosen expert over ALL experts, 0 elsewhere; and how far
    the k-th chosen score lies above the best one left out — a system in
    bf16 can flip the two where that margin is within its rounding.

    `forced` (T, k): the experts a served program chose. The weights are
    then computed for THOSE experts (from this file's own scores), `experts`
    stays this file's own choice, and the margin says how far the worst of
    the forced experts lies below this file's k-th best score: 0 where the
    two sets agree, within rounding where a system in bf16 chose otherwise,
    wide where its choice is a fault. A row of -1 (the caller could not
    tell which choice was this token's) follows this file's own choice."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ get(p + "mlp.gate.weight").T)
    top, experts = jax.lax.top_k(scores, k + 1)
    margin = top[:, k - 1] - top[:, k]
    experts = experts[:, :k]
    used = experts
    if forced is not None:
        forced = jnp.asarray(forced)
        used = jnp.where(forced[:, :1] < 0, experts, forced)
        worst = jnp.min(jnp.take_along_axis(scores, used, axis=-1), axis=-1)
        margin = top[:, k - 1] - worst
    w = jnp.take_along_axis(scores, used, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["moe_routed_scaling_factor"]
    combine = jnp.sum(
        jax.nn.one_hot(used, scores.shape[-1]) * w[..., None], axis=1)
    return combine, experts, margin


def expert_layer(cfg: dict, get, p: str, xn: jax.Array, run, forced=None,
                 held=None):
    """One expert layer's output (before the residual add) and its routing:
    the held experts' part (`held` = (first, count), default the config's)
    plus the shared expert."""
    first, count = held if held is not None else cfg["experts_held"]
    combine, experts, margin = run(route, cfg, get, p, xn, forced)
    y = run(swiglu, get, p + "mlp.shared_expert.", xn) if cfg[
        "shared_expert_intermediate_size"] else jnp.zeros_like(xn)
    for e in range(first, first + count):
        # every held expert on every position, weighted by w_e or 0
        y = y + combine[:, e:e + 1] * run(
            swiglu, get, p + f"mlp.experts.{e}.", xn)
    return y, {"experts": experts, "margin": margin}


def forward(cfg: dict, weights, tokens, logit_positions=None, *,
            query_block: int = 256, forced_experts=None,
            ) -> tuple[jax.Array, list]:
    """Logits (T, vocab slice) of every position of one sequence (or of
    `logit_positions` only), and each expert layer's routing (None for a
    dense layer). `forced_experts`: per expert layer, in order, the (T, k)
    experts a served program chose (`route`): the forward then follows the
    served choices and reports how far each lay from its own. Nothing is
    jitted as a whole: each tensor is widened to float32 where it is used,
    so one matrix's copy is live at a time."""
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
            attention, cfg, get, p, i,
            rms_norm(x, get(p + "input_layernorm.weight"), eps), query_block)
        xn = rms_norm(x, get(p + "post_attention_layernorm.weight"), eps)
        if i in cfg["mlp_only_layers"] or not cfg["num_experts"]:
            x = x + run(swiglu, get, p + "mlp.", xn)
            routing.append(None)
            continue
        forced = None
        if forced_experts is not None:
            forced = forced_experts[sum(r is not None for r in routing)]
        y, r = expert_layer(cfg, get, p, xn, run, forced)
        x = x + y
        routing.append(r)
    if logit_positions is not None:
        x = x[jnp.asarray(list(logit_positions))]
    x = rms_norm(x, get("model.norm.weight"), eps)
    head = ("model.embed_tokens.weight" if cfg["tie_word_embeddings"]
            else "lm_head.weight")
    with jax.default_matmul_precision(_PRECISION):
        return x @ get(head).T, routing
