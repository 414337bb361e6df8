"""A plain reference for the Ling-3.0-flash language model this stack serves
(inclusionAI/Ling-3.0-flash-VL's decoder; the vision tower, multi-token
prediction and the SwiGLU clamp are not computed): the forward pass in
straightforward `jax.numpy`, float32, every matmul under
`jax.default_matmul_precision("highest")` (a TPU's default float32 matmul is
bf16 passes). No KV cache, no state slots, no kernels, no batching, no
absorption: one sequence, all positions, one layer at a time, parametrised by
the published `config.json` keys (x the residual stream, h = hidden_size,
pre-norm, eps `rms_norm_eps`: x += Mix(RMSNorm(x)); x += Mlp(RMSNorm(x))).

Layer i is an MLA layer where (i + 1) % `layer_group_size` == 0, else a KDA
layer; its MLP is a dense SwiGLU of `intermediate_size` where
i < `first_k_dense_replace`, else experts.

KDA layer (the gated delta rule with a per-channel gate, arXiv:2510.26692;
H = `num_attention_heads` heads x D = `head_dim` for keys and values alike:
`num_kv_heads_for_linear_attn` 0), u the normed input:

    q~ = W_q u, k~ = W_k u, v~ = W_v u                 (h -> H D, no bias)
    q_t = silu(sum_{i=0..3} c^q_i * q~_{t-3+i})        the short convolution:
        causal, depthwise, `short_conv_kernel_size` 4 taps, no bias;
        positions before the sequence's first token are ZERO; k, v likewise
        (`linear_silu`)
    q^_t = q_t / sqrt(|q_t|^2 + 1e-6) * D^-0.5,  k^_t = k_t / sqrt(|k_t|^2
        + 1e-6)                                        per head
    a_t = W_f u                (h -> H D at FULL rank: `no_kda_lora`)
    g_t = kda_lower_bound * sigmoid(exp(A_log[head]) * (a_t + dt_bias))
        in (kda_lower_bound, 0), per key channel (`kda_safe_gate`: the
        bounded form; false: -exp(A_log) softplus(a_t + dt_bias))
    beta_t = sigmoid(W_b u)    per head, in (0, 1)
    S_0 = 0;  S' = Diag(exp(g_t)) S_{t-1};
    S_t = S' + beta_t k^_t (v_t - S'^T k^_t)^T;  o_t = S_t^T q^_t
        per head, S (D key x D value), TOKEN BY TOKEN (`lax.scan`)
    z_t = W_g u (h -> H D, full rank);  o_t <- RMSNorm_D(o_t; o_norm, one
        weight shared by the heads: `group_norm_size` 1 read as one head a
        group) * sigmoid(z_t);  Mix = W_o concat(o)
    no position encoding.

MLA layer (DeepSeek-V2 form in its EXPANDED equations, no query latent:
`q_lora_rank` null):

    q  = W_q u                      -> heads x (qk_nope | qk_rope)
    c | k_r = W_kv_a u              -> kv_lora_rank | qk_rope (ONE for all heads)
    k_nope | v = W_kv_b RMSNorm(c)  -> heads x (qk_nope | v_head)
    k  = k_nope | rope(k_r),  q = q_nope | rope(q_rope)   rotate-half over
        the `rotary_dim` = qk_rope values, theta `rope_theta`
        (`use_mla_nope` false)
    o_h = softmax_causal(q_h . k_h / sqrt(qk_nope + qk_rope)) v_h
    o_h *= sigmoid(W_g u)[h]        one scalar a head
        (`gated_attention_proj_granularity_type` head_wise)
    Mix = W_o concat(o)

Experts (layers >= first_k_dense_replace), u = RMSNorm(x):

    s = sigmoid(W_r u) over ALL `num_experts`;  choice c = s + bias
    (`moe_router_enable_expert_bias`); the experts are `n_group` groups of
    num_experts / n_group consecutive experts, a group's score the sum of
    its two best c, the `topk_group` best groups kept; experts = top-k of c
    among the kept groups' experts;  w = s[experts] / sum (norm_topk_prob)
    x routed_scaling_factor;  Mlp = sum_e w_e SwiGLU_e(u) + SwiGLU_shared(u)

THE SHARE. `experts_held` = (first, count): the experts whose weights this
chip of an expert-parallel deployment holds. The router's scores, the groups
and the top-k are over all `num_experts`; the sum runs over the chosen
experts that are HELD; what the absent experts would add is left out, here
as in the served program (models/llama.py:_moe_mlp), and that partial result
goes on to the next layer. `vocab_size` is the slice of the vocabulary held:
the embedding's rows and the head's columns [0, vocab_size).

ASSUMED, because the published config's keys do not settle it (each also in
the benchmark configuration's `assumed`, with the alternative not taken):

- the bounded gate is bound * sigmoid(exp(A_log) (a + dt_bias)), the public
  gated-delta-rule kernels' safe form; -exp(A_log) softplus(.) is not taken;
- beta = sigmoid in (0, 1) (no key asks for negative eigenvalues);
- the output norm is an RMSNorm over one head's D channels;
- KDA layers carry no position encoding (`rotary_dim` 64 is the width of
  the MLA rope key);
- `use_qk_norm` is carried by KDA's L2 norms and the latent's RMSNorm: no
  further per-head norm on the MLA layers' expanded queries and keys;
- the output gates are computed from the layer's normed input, the shared
  expert is ungated, `hidden_act` silu, no bias anywhere;
- an expert outside the kept groups is never chosen (its choice score is
  -inf; DeepSeek-V3's zero fill chooses the same experts while choice scores
  are positive); ties go to the lower index, of group and of expert
  (`jax.lax.top_k`).

Departures from a `transformers`-style implementation, each because this
file is float32 throughout or because the configuration needs no more:

- RMSNorm multiplies by its weight in float32; routing weights stay float32;
- every HELD expert is evaluated on every position and weighted by `w_e` or
  0 (one expert at a time), instead of gathering each expert's positions;
- `forward(forced_experts=...)` can be told which experts a served program
  chose, and then weighs THOSE by its own scores (see `route`);
- MLA attention is computed in blocks of `query_block` queries — their
  projection, gate and output projection with them — each against every key
  under its mask: the same softmax without the (heads, T, T) array;
- a KDA layer is FED in segments of `token_block` tokens — projections,
  convolution and gates a segment at a time, the state and the convolution's
  last three rows carried from one to the next; the recurrence itself stays
  one token at a time, with no blocks and no kernel.

What a faulty program would compute, for the check that a comparison's
tolerance can tell (none is a published key; each defaults to the model):
`kda_state_dtype` ("bfloat16": the state rounded at every token),
`router_dtype` ("bfloat16": the router's product and scores in bfloat16),
`kda_state_inherited` / `kda_conv_inherited` (true: the state / the
convolution's history starts from what an identical row before this one left
in the slot), `kda_beta_scale` (2: beta in (0, 2)).

`weights` maps tensor names in the `transformers` convention ((out, in)
matrices): `model.layers.<i>.linear_attn.{q,k,v,o,f,g,b}_proj.weight`,
`.{q,k,v}_conv1d.weight` (H D, taps), `.A_log`, `.dt_bias`,
`.o_norm.weight`; `model.layers.<i>.self_attn.{q_proj,kv_a_proj_with_mqa,
kv_b_proj,o_proj,g_proj}.weight`, `.kv_a_layernorm.weight`; a dense layer's
`mlp.{gate,up,down}_proj.weight`; the router `mlp.gate.weight` and
`mlp.gate.e_score_correction_bias`, experts `mlp.experts.<global index>.
{gate,up,down}_proj.weight`, the shared expert `mlp.shared_expert.*`. Each is
widened to float32 where it is used. (No checkpoint of this family has been
read here: the names are this file's.)

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
    specs = model_cfg.layer_specs
    latent = [i for i, s in enumerate(specs) if s.mixer == "latent"]
    return {
        "hidden_size": model_cfg.hidden_size,
        "intermediate_size": model_cfg.intermediate_size,
        "moe_intermediate_size": model_cfg.expert_intermediate_size,
        "moe_shared_expert_intermediate_size": (
            model_cfg.n_shared_experts * model_cfg.expert_intermediate_size),
        "num_hidden_layers": model_cfg.num_layers,
        "num_attention_heads": model_cfg.num_heads,
        "head_dim": model_cfg.linear_head_dim,
        "num_kv_heads_for_linear_attn": 0,
        # (the MLA layers as a list: a cut keeps the published rule's layers)
        "mla_layers": latent,
        "layer_group_size": (latent[0] + 1) if latent else 0,
        "first_k_dense_replace": sum(not s.experts for s in specs),
        "kv_lora_rank": model_cfg.kv_lora_rank,
        "q_lora_rank": None,
        "qk_nope_head_dim": model_cfg.qk_nope_head_dim,
        "qk_rope_head_dim": model_cfg.qk_rope_head_dim,
        "v_head_dim": model_cfg.v_head_dim,
        "rotary_dim": model_cfg.qk_rope_head_dim,
        "use_mla_nope": False,
        "rope_theta": model_cfg.rope_theta,
        "gated_attention_proj_granularity_type": (
            "head_wise" if model_cfg.attn_output_gate == "head" else None),
        # (the one width the system serves: its mapper refuses another)
        "short_conv_kernel_size": 4,
        "linear_silu": True,
        "no_kda_lora": model_cfg.linear_gate_rank == 0,
        "kda_safe_gate": model_cfg.linear_gate_form == "bounded",
        "kda_lower_bound": model_cfg.linear_gate_lower_bound,
        "kda_beta_scale": model_cfg.linear_beta_scale,
        "group_norm_size": 1,
        "rms_norm_eps": model_cfg.rms_norm_eps,
        "tie_word_embeddings": model_cfg.tie_word_embeddings,
        "num_experts": model_cfg.num_experts,
        "num_experts_per_tok": model_cfg.num_experts_per_tok,
        "n_group": model_cfg.n_group,
        "topk_group": model_cfg.topk_group,
        "score_function": model_cfg.scoring_func,
        "moe_router_enable_expert_bias": model_cfg.moe_selection_bias,
        "norm_topk_prob": model_cfg.norm_topk_prob,
        "routed_scaling_factor": model_cfg.routed_scaling_factor,
        "experts_held": (model_cfg.first_expert_held,
                         model_cfg.held_experts),
        "vocab_size": model_cfg.vocab_size,
    }


def check_config(cfg: dict) -> dict:
    """Refuse what the reference does not compute."""
    refused = [
        name for name, bad in (
            ("q_lora_rank", cfg.get("q_lora_rank") is not None),
            ("use_mla_nope", bool(cfg.get("use_mla_nope"))),
            ("no_kda_lora false", not cfg.get("no_kda_lora", True)),
            ("use_kda_lora", bool(cfg.get("use_kda_lora"))),
            ("num_kv_heads_for_linear_attn", cfg.get(
                "num_kv_heads_for_linear_attn", 0) not in (
                    0, cfg["num_attention_heads"])),
            ("short_conv_kernel_size", cfg.get(
                "short_conv_kernel_size", 4) != 4),
            ("linear_silu false", not cfg.get("linear_silu", True)),
            ("group_norm_size", cfg.get("group_norm_size", 1) != 1),
            ("rotary_dim", cfg.get("rotary_dim", cfg["qk_rope_head_dim"])
             != cfg["qk_rope_head_dim"]),
            ("score_function", cfg.get("score_function",
                                       "sigmoid") != "sigmoid"),
            ("n_group", bool(cfg["num_experts"] % cfg.get("n_group", 1))),
            *((key, bool(cfg.get(key))) for key in (
                "use_nGPT", "value_norm", "up_proj_norm", "mtp_use_kda",
                "scale_router_input", "attention_bias")),
            *((key, any((cfg.get(key) or [])[:cfg["num_hidden_layers"]]))
              for key in ("expert_swiglu_limit_list",
                          "share_expert_swiglu_limit_list")),
        ) if bad]
    if refused:
        raise ValueError(f"the reference does not cover {refused}")
    out = dict(cfg)
    out["tie_word_embeddings"] = bool(cfg.get("tie_word_embeddings", False))
    out.setdefault("experts_held", (0, cfg["num_experts"]))
    out.setdefault("n_group", 1)
    out.setdefault("topk_group", 1)
    if "mla_layers" not in out:
        size = cfg["layer_group_size"]
        out["mla_layers"] = [i for i in range(cfg["num_hidden_layers"])
                             if (i + 1) % size == 0]
    return out


def f32(a) -> jax.Array:
    return jnp.asarray(a).astype(jnp.float32)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def short_conv(x: jax.Array, taps: jax.Array, before: jax.Array) -> jax.Array:
    """The causal depthwise convolution as its explicit sum: x (T, C), taps
    (C, K) (tap K - 1 multiplies the current token), `before` (K - 1, C)
    the rows before x's first (zeros at a sequence's start). (T, C)."""
    k = taps.shape[1]
    ext = jnp.concatenate([before, x], axis=0)
    t = x.shape[0]
    return sum(ext[i:i + t] * taps[:, i][None] for i in range(k))


def delta_rule(q, k, v, g, beta, s0, state_dtype=jnp.float32):
    """The recurrence, one token at a time: q, k, v, g (T, H, D), beta
    (T, H), s0 (H, D, D) -> (o (T, H, D), s_T). Elementwise products and
    sums only (no matmul whose precision could be lowered)."""

    def step(s, xs):
        qt, kt, vt, gt, bt = xs
        s = s.astype(jnp.float32) * jnp.exp(gt)[:, :, None]
        pred = jnp.sum(s * kt[:, :, None], axis=1)  # S'^T k, (H, D)
        s = s + kt[:, :, None] * (bt[:, None] * (vt - pred))[:, None, :]
        o = jnp.sum(s * qt[:, :, None], axis=1)
        # (a faulty state_dtype rounds what the next token reads)
        return s.astype(state_dtype), o

    s, o = jax.lax.scan(step, s0.astype(state_dtype), (q, k, v, g, beta))
    return o, s.astype(jnp.float32)


def kda_gate(cfg: dict, a: jax.Array, rate: jax.Array) -> jax.Array:
    """The log of the decay a key channel from the projected (and biased)
    gate input `a` and the head's rate exp(A_log)."""
    if cfg.get("kda_safe_gate", True):
        return cfg["kda_lower_bound"] * jax.nn.sigmoid(rate * a)
    return -rate * jax.nn.softplus(a)


def kda_layer(cfg: dict, get, p: str, x: jax.Array, token_block: int,
              ) -> jax.Array:
    """A KDA layer's mixer, (T, h) normed input -> (T, h)."""
    nh, d, taps = (cfg["num_attention_heads"], cfg["head_dim"],
                   cfg["short_conv_kernel_size"])
    a = p + "linear_attn."
    state_dtype = jnp.dtype(cfg.get("kda_state_dtype", "float32"))
    beta_scale = float(cfg.get("kda_beta_scale", 1.0))
    w_q, w_k, w_v = (get(a + n + "_proj.weight") for n in "qkv")
    c_q, c_k, c_v = (get(a + n + "_conv1d.weight") for n in "qkv")
    rate = jnp.exp(get(a + "A_log"))[None, :, None]
    dt_bias = get(a + "dt_bias").reshape(1, nh, d)

    def segment(u, before, s0):
        t = u.shape[0]
        raw = [u @ w.T for w in (w_q, w_k, w_v)]
        q, k, v = (
            jax.nn.silu(short_conv(r, c, b)).reshape(t, nh, d)
            for r, c, b in zip(raw, (c_q, c_k, c_v), before))
        q = q * jax.lax.rsqrt(
            jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * d ** -0.5
        k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
        gate_in = (u @ get(a + "f_proj.weight").T).reshape(t, nh, d)
        g = kda_gate(cfg, gate_in + dt_bias, rate)
        beta = beta_scale * jax.nn.sigmoid(u @ get(a + "b_proj.weight").T)
        o, s = delta_rule(q, k, v, g, beta, s0, state_dtype)
        z = u @ get(a + "g_proj.weight").T
        o = rms_norm(o, get(a + "o_norm.weight"), cfg["rms_norm_eps"])
        o = o.reshape(t, nh * d) * jax.nn.sigmoid(z)
        # the last taps - 1 rows of [before | this segment], for the next
        after = [jnp.concatenate([b, r], axis=0)[-(taps - 1):]
                 for b, r in zip(before, raw)]
        return o @ get(a + "o_proj.weight").T, after, s

    def sweep(before, s0):
        outs = []
        for lo in range(0, x.shape[0], token_block):
            out, before, s0 = segment(x[lo:lo + token_block], before, s0)
            outs.append(out)
        return jnp.concatenate(outs, axis=0), before, s0

    zeros = [jnp.zeros((taps - 1, nh * d), jnp.float32)] * 3
    before, s0 = zeros, jnp.zeros((nh, d, d), jnp.float32)
    if cfg.get("kda_state_inherited") or cfg.get("kda_conv_inherited"):
        # FAULTS: what an identical row before this one left in the slot
        _, left_before, left_s = sweep(before, s0)
        if cfg.get("kda_state_inherited"):
            s0 = left_s
        if cfg.get("kda_conv_inherited"):
            before = left_before
    return sweep(before, s0)[0]


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate-half rotary embedding over the whole last axis: x (T, n, d),
    column i paired with i + d / 2, turned by position x theta^(-2 i / d)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = positions.astype(jnp.float32)[:, None, None] * freqs[None, None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla_attention(cfg: dict, get, p: str, x: jax.Array, query_block: int,
                  ) -> jax.Array:
    """Causal latent attention of one sequence in its EXPANDED form with the
    per-head output gate, (T, h) normed input -> (T, h)."""
    t = x.shape[0]
    nh = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r, theta = cfg["kv_lora_rank"], cfg["rope_theta"]
    a = p + "self_attn."
    positions = jnp.arange(t)
    ckr = x @ get(a + "kv_a_proj_with_mqa.weight").T  # (T, r + dr)
    c_kv = rms_norm(ckr[:, :r], get(a + "kv_a_layernorm.weight"),
                    cfg["rms_norm_eps"])
    kv = (c_kv @ get(a + "kv_b_proj.weight").T).reshape(t, nh, dn + dv)
    k_rope = rope(ckr[:, None, r:], positions, theta)  # one for all heads
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (t, nh, dr))], axis=-1)
    v = kv[..., dn:]
    w_q, w_o = get(a + "q_proj.weight"), get(a + "o_proj.weight")
    w_g = (get(a + "g_proj.weight") if cfg.get(
        "gated_attention_proj_granularity_type") == "head_wise" else None)
    outs = []
    for lo in range(0, t, query_block):
        # one block of queries at a time, projection and all, each against
        # EVERY key under its mask (one shape a sequence: nothing eager
        # compiles again block after block)
        hi = min(t, lo + query_block)
        q = (x[lo:hi] @ w_q.T).reshape(hi - lo, nh, dn + dr)
        q = jnp.concatenate(
            [q[..., :dn], rope(q[..., dn:], positions[lo:hi], theta)], -1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) * (dn + dr) ** -0.5
        seen = positions[lo:hi, None] >= positions[None, :]
        probs = jax.nn.softmax(
            jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        out = jnp.einsum("hqk,khd->qhd", probs, v)
        if w_g is not None:
            out = out * jax.nn.sigmoid(x[lo:hi] @ w_g.T)[..., None]
        outs.append(out.reshape(hi - lo, nh * dv) @ w_o.T)
    return jnp.concatenate(outs, axis=0)


def swiglu(get, p: str, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ get(p + "gate_proj.weight").T)
            * (x @ get(p + "up_proj.weight").T)) @ get(
                p + "down_proj.weight").T


def group_limited(cfg: dict, choice: jax.Array) -> jax.Array:
    """The choice scores with every expert outside the `topk_group` best of
    the `n_group` groups at -inf (a group's score: the sum of its two best
    choice scores); unchanged with one group."""
    groups = cfg["n_group"]
    if groups <= 1:
        return choice
    t, e = choice.shape
    per = e // groups
    best2, _ = jax.lax.top_k(choice.reshape(t, groups, per), min(2, per))
    _, kept = jax.lax.top_k(jnp.sum(best2, axis=-1), cfg["topk_group"])
    keep = jnp.any(kept[..., None] == jnp.arange(groups), axis=1)
    return jnp.where(jnp.repeat(keep, per, axis=1), choice, -jnp.inf)


def route(cfg: dict, get, p: str, x: jax.Array, forced=None):
    """(combine weights (T, E), chosen experts (T, k), margin (T,)): the
    weight of each chosen expert over ALL experts, 0 elsewhere; and how far
    the k-th chosen choice score lies above the best one left out among the
    kept groups' experts — a system in bf16 can flip the two where that
    margin is within its rounding.

    `forced` (T, k): the experts a served program chose. The weights are
    then computed for THOSE experts (from this file's own scores), `experts`
    stays this file's own choice, and the margin says how far the worst of
    the forced experts' UNLIMITED choice scores lies below this file's k-th
    best: 0 where the two sets agree. A row of -1 (the caller could not tell
    which choice was this token's) follows this file's own choice."""
    k = cfg["num_experts_per_tok"]
    w_r = get(p + "mlp.gate.weight")
    if cfg.get("router_dtype", "float32") != "float32":
        # FAULT: the router's product and its scores in a lower precision
        low = jnp.dtype(cfg["router_dtype"])
        scores = jax.nn.sigmoid(
            x.astype(low) @ w_r.astype(low).T).astype(jnp.float32)
    else:
        scores = jax.nn.sigmoid(x @ w_r.T)
    # the bias moves the choice, never the weight
    biased = scores
    if cfg.get("moe_router_enable_expert_bias", True):
        biased = scores + get(p + "mlp.gate.e_score_correction_bias")[None]
    top, experts = jax.lax.top_k(group_limited(cfg, biased), k + 1)
    margin = top[:, k - 1] - top[:, k]
    experts = experts[:, :k]
    used = experts
    if forced is not None:
        forced = jnp.asarray(forced)
        used = jnp.where(forced[:, :1] < 0, experts, forced)
        worst = jnp.min(jnp.take_along_axis(biased, used, axis=-1), axis=-1)
        margin = top[:, k - 1] - worst
    w = jnp.take_along_axis(scores, used, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    combine = jnp.sum(
        jax.nn.one_hot(used, scores.shape[-1]) * w[..., None], axis=1)
    return combine, experts, margin


def expert_layer(cfg: dict, get, p: str, xn: jax.Array, run, forced=None,
                 held=None, shared: bool = True):
    """One expert layer's output (before the residual add) and its routing:
    the held experts' part (`held` = (first, count), default the config's)
    plus, with `shared`, the shared expert."""
    first, count = held if held is not None else cfg["experts_held"]
    combine, experts, margin = run(route, cfg, get, p, xn, forced)
    y = (run(swiglu, get, p + "mlp.shared_expert.", xn)
         if shared and cfg["moe_shared_expert_intermediate_size"]
         else jnp.zeros_like(xn))
    for e in range(first, first + count):
        # every held expert on every position, weighted by w_e or 0
        y = y + combine[:, e:e + 1] * run(
            swiglu, get, p + f"mlp.experts.{e}.", xn)
    return y, {"experts": experts, "margin": margin}


def forward(cfg: dict, weights, tokens, logit_positions=None, *,
            query_block: int = 256, token_block: int = 2048,
            forced_experts=None) -> tuple[jax.Array, list]:
    """Logits (T, vocab slice) of every position of one sequence (or of
    `logit_positions` only), and each layer's routing (None for a dense
    layer). `forced_experts`: per EXPERT layer, in order, the (T, k) experts
    a served program chose (`route`): the forward then follows the served
    choices and reports how far each lay from its own. Nothing is jitted as
    a whole: each tensor is widened to float32 where it is used, so one
    matrix's copy is live at a time."""
    cfg = check_config(cfg)
    eps = cfg["rms_norm_eps"]
    mla = set(cfg["mla_layers"])

    def get(name):
        return f32(weights[name])

    def run(fn, *args):
        with jax.default_matmul_precision(_PRECISION):
            return fn(*args)

    x = f32(jnp.asarray(weights["model.embed_tokens.weight"])[
        jnp.asarray(tokens)])
    routing = []
    forced = iter(forced_experts) if forced_experts is not None else None
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        u = rms_norm(x, get(p + "input_layernorm.weight"), eps)
        if i in mla:
            x = x + run(mla_attention, cfg, get, p, u, query_block)
        else:
            x = x + run(kda_layer, cfg, get, p, u, token_block)
        xn = rms_norm(x, get(p + "post_attention_layernorm.weight"), eps)
        if i < cfg["first_k_dense_replace"]:
            x = x + run(swiglu, get, p + "mlp.", xn)
            routing.append(None)
            continue
        y, r = expert_layer(cfg, get, p, xn, run,
                            None if forced is None else next(forced))
        x = x + y
        routing.append(r)
    if logit_positions is not None:
        x = x[jnp.asarray(list(logit_positions))]
    x = rms_norm(x, get("model.norm.weight"), eps)
    head = ("model.embed_tokens.weight" if cfg["tie_word_embeddings"]
            else "lm_head.weight")
    with jax.default_matmul_precision(_PRECISION):
        return x @ get(head).T, routing
