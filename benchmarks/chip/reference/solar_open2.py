"""A plain reference for the Solar-Open2 decoder this stack serves
(`model_type: solar_open2`): the forward pass in straightforward
`jax.numpy`, float32, every matmul under
`jax.default_matmul_precision("highest")` (a TPU's default float32 matmul is
bf16 passes). No KV cache, no state slots, no kernels, no batching: one
sequence, all positions, one layer at a time, parametrised by the published
`config.json` keys (x the residual stream, h = hidden_size, pre-norm:
x += Mix(RMSNorm(x)); x += Mlp(RMSNorm(x)); every layer's MLP is experts).

Layer i is a GQA layer if i is in `gqa_layers`, else a KDA layer.

KDA layer (`linear_attn_config`: H heads x D for keys and values alike; the
gated delta rule with a per-channel gate, Kimi Linear, arXiv:2510.26692),
u the normed input:

    q~ = W_q u, k~ = W_k u, v~ = W_v u                 (h -> H D, no bias)
    q_t = silu(sum_{i=0..3} c^q_i * q~_{t-3+i})        the short convolution:
        causal, depthwise, `short_conv_kernel_size` 4 taps, no bias;
        positions before the sequence's first token are ZERO; k, v likewise
    q^_t = q_t / sqrt(|q_t|^2 + 1e-6) * D^-0.5,  k^_t = k_t / sqrt(|k_t|^2
        + 1e-6)                                        per head
    a_t = W_f_b (W_f_a u)      (h -> D -> H D: `kda_use_full_proj` false)
    g_t = -exp(A_log[head]) * softplus(a_t + dt_bias)  <= 0, per key channel
    beta_t = 2 sigmoid(W_b u)  per head  (`kda_allow_neg_eigval` true: the
        factor 2; false: 1)
    S_0 = 0;  S' = Diag(exp(g_t)) S_{t-1};
    S_t = S' + beta_t k^_t (v_t - S'^T k^_t)^T;  o_t = S_t^T q^_t
        per head, S (D key x D value), TOKEN BY TOKEN (`lax.scan`)
    z_t = W_g_b (W_g_a u);  o_t <- RMSNorm_D(o_t; o_norm, shared by the
        heads) * sigmoid(z_t);  Mix = W_o concat(o)

GQA layer (`num_attention_heads` query / `num_key_value_heads` kv heads x
`head_dim`; `use_rope` false: NO position encoding at all):

    q = W_q u, k = W_k u, v = W_v u   (no bias, no q/k norm)
    a query sees the keys at positions <= its own; softmax(q . k / sqrt(D)) v
    g = sigmoid(W_g u) -> heads x D (`use_gqa_gate`): o <- g * o elementwise
    Mix = W_o concat(o)

Experts (every layer), u = RMSNorm(x):

    s = sigmoid(W_r u) over ALL n_routed_experts;  experts = top-k of
    s + e_score_correction_bias;  w = s[experts] / sum (norm_topk_prob)
    x routed_scaling_factor;  Mlp = sum_e w_e SwiGLU_e(u) + SwiGLU_shared(u)

THE SHARE. `experts_held` = (first, count): the experts whose weights this
chip of an expert-parallel deployment holds. The router scores and the
top-k are over all `n_routed_experts`; the sum runs over the chosen experts
that are HELD; what the absent experts would add is left out, here as in the
served program (models/llama.py:_moe_mlp), and that partial result goes on
to the next layer. `vocab_size` is the slice of the vocabulary held: the
embedding's rows and the head's columns [0, vocab_size).

ASSUMED, because the published config has no key for it (each also in the
benchmark configuration's `assumed`):

- the router's scores are the logistic sigmoid with a learned selection
  bias and no groups (the DeepSeek-V3 / GLM-4.5 lineage, whose key names the
  config uses); softmax is the alternative not taken;
- the GQA output gate is ELEMENTWISE (W_g: h -> heads x D), computed from
  the same normed input as q; head-wise (h -> heads) is the alternative not
  taken — the one public config that gates by head says so in a key;
- the two low-rank KDA projections have rank `head_dim`; no q/k norm in the
  GQA layers; the shared expert is ungated; `hidden_act` silu.

Departures from a `transformers`-style implementation, each because this
file is float32 throughout or because the configuration needs no more:

- RMSNorm multiplies by its weight in float32; routing weights stay float32;
- every HELD expert is evaluated on every position and weighted by `w_e` or
  0 (one expert at a time), instead of gathering each expert's positions;
- ties in the top-k go to the lower expert index (`jax.lax.top_k`);
- `forward(forced_experts=...)` can be told which experts a served program
  chose, and then weighs THOSE by its own scores (see `route`);
- GQA attention is computed in blocks of `query_block` queries — their
  projection, gate and output projection with them — each against every key
  under its mask: the same softmax without the (heads, T, T) array;
- a KDA layer is FED in segments of `token_block` tokens — projections,
  convolution and gates a segment at a time, the state and the convolution's
  last three rows carried from one to the next — so that 16k tokens' float32
  projections are never held whole; the recurrence itself stays one token at
  a time, with no blocks and no kernel.

What a faulty cache manager would compute, for the check that a comparison's
tolerance can tell (none is a published key; each defaults to the model):
`kda_state_dtype` ("bfloat16": the state rounded at every token),
`kda_state_inherited` / `kda_conv_inherited` (true: the state / the
convolution's history starts from what an identical row before this one
left in the slot, instead of zeros).

`weights` maps tensor names in the `transformers` convention ((out, in)
matrices): `model.layers.<i>.self_attn.{q,k,v,o,g}_proj.weight`;
`model.layers.<i>.linear_attn.{q,k,v,o,f_a,f_b,g_a,g_b,b}_proj.weight`,
`.{q,k,v}_conv1d.weight` (H D, taps), `.A_log`, `.dt_bias`,
`.o_norm.weight`; the router `mlp.gate.weight` and
`mlp.gate.e_score_correction_bias`, experts `mlp.experts.<global index>.
{gate,up,down}_proj.weight`, the shared expert `mlp.shared_expert.*`. Each
is widened to float32 where it is used.

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
    return {
        "hidden_size": model_cfg.hidden_size,
        "moe_intermediate_size": model_cfg.expert_intermediate_size,
        "num_hidden_layers": model_cfg.num_layers,
        "num_attention_heads": model_cfg.num_heads,
        "num_key_value_heads": model_cfg.num_kv_heads,
        "head_dim": model_cfg.head_dim,
        "gqa_layers": [i for i, s in enumerate(specs) if s.mixer != "linear"],
        "use_rope": any(s.rope is not None for s in specs),
        "use_gqa_gate": bool(model_cfg.attn_output_gate),
        "linear_attn_config": {
            "num_heads": model_cfg.linear_num_heads,
            "head_dim": model_cfg.linear_head_dim,
            "num_kv_heads": None,
            # (the one width the system serves: its mapper refuses another)
            "short_conv_kernel_size": 4},
        "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True,
        "first_k_dense_replace": 0,
        "rms_norm_eps": model_cfg.rms_norm_eps,
        "tie_word_embeddings": model_cfg.tie_word_embeddings,
        "n_routed_experts": model_cfg.num_experts,
        "n_shared_experts": model_cfg.n_shared_experts,
        "num_experts_per_tok": model_cfg.num_experts_per_tok,
        "norm_topk_prob": model_cfg.norm_topk_prob,
        "routed_scaling_factor": model_cfg.routed_scaling_factor,
        "experts_held": (model_cfg.first_expert_held,
                         model_cfg.held_experts),
        "vocab_size": model_cfg.vocab_size,
    }


def check_config(cfg: dict) -> dict:
    """Refuse what the reference does not compute."""
    lin = cfg["linear_attn_config"]
    refused = [
        name for name, bad in (
            ("use_rope", bool(cfg.get("use_rope"))),
            ("kda_use_full_proj", bool(cfg.get("kda_use_full_proj"))),
            ("linear_attn_config.num_kv_heads",
             lin.get("num_kv_heads") not in (None, lin["num_heads"])),
            ("first_k_dense_replace",
             bool(cfg.get("first_k_dense_replace"))),
            ("attention_bias", bool(cfg.get("attention_bias"))),
        ) if bad]
    if refused:
        raise ValueError(f"the reference does not cover {refused}")
    out = dict(cfg)
    out["tie_word_embeddings"] = bool(cfg.get("tie_word_embeddings", False))
    out.setdefault("experts_held", (0, cfg["n_routed_experts"]))
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


def kda_layer(cfg: dict, get, p: str, x: jax.Array, token_block: int,
              ) -> jax.Array:
    """A KDA layer's mixer, (T, h) normed input -> (T, h)."""
    lin = cfg["linear_attn_config"]
    nh, d, taps = lin["num_heads"], lin["head_dim"], lin[
        "short_conv_kernel_size"]
    a = p + "linear_attn."
    state_dtype = jnp.dtype(cfg.get("kda_state_dtype", "float32"))
    beta_scale = 2.0 if cfg.get("kda_allow_neg_eigval", True) else 1.0
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
        gate_in = (u @ get(a + "f_a_proj.weight").T) @ get(
            a + "f_b_proj.weight").T
        g = -rate * jax.nn.softplus(gate_in.reshape(t, nh, d) + dt_bias)
        beta = beta_scale * jax.nn.sigmoid(u @ get(a + "b_proj.weight").T)
        o, s = delta_rule(q, k, v, g, beta, s0, state_dtype)
        z = (u @ get(a + "g_a_proj.weight").T) @ get(a + "g_b_proj.weight").T
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


def attention(cfg: dict, get, p: str, x: jax.Array, query_block: int,
              ) -> jax.Array:
    """Causal attention of one sequence without any position encoding,
    with the elementwise output gate, (T, h) normed input -> (T, h)."""
    t = x.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    positions = jnp.arange(t)
    k = (x @ get(p + "self_attn.k_proj.weight").T).reshape(t, nkv, d)
    v = (x @ get(p + "self_attn.v_proj.weight").T).reshape(t, nkv, d)
    w_q, w_o = get(p + "self_attn.q_proj.weight"), get(
        p + "self_attn.o_proj.weight")
    w_g = (get(p + "self_attn.g_proj.weight")
           if cfg.get("use_gqa_gate") else None)
    outs = []
    for lo in range(0, t, query_block):
        # one block of queries at a time, projection and all: a long
        # sequence's (T, heads, D) queries are never held whole
        hi = min(t, lo + query_block)
        # query head n reads kv head n // (nh / nkv)
        q = (x[lo:hi] @ w_q.T).reshape(hi - lo, nkv, nh // nkv, d)
        scores = jnp.einsum("qgrd,kgd->grqk", q, k) * d ** -0.5
        seen = positions[lo:hi, None] >= positions[None, :]
        probs = jax.nn.softmax(
            jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        out = jnp.einsum("grqk,kgd->qgrd", probs, v).reshape(
            hi - lo, nh * d)
        if w_g is not None:
            out = out * jax.nn.sigmoid(x[lo:hi] @ w_g.T)
        outs.append(out @ w_o.T)
    return jnp.concatenate(outs, axis=0)


def swiglu(get, p: str, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ get(p + "gate_proj.weight").T)
            * (x @ get(p + "up_proj.weight").T)) @ get(
                p + "down_proj.weight").T


def route(cfg: dict, get, p: str, x: jax.Array, forced=None):
    """(combine weights (T, E), chosen experts (T, k), margin (T,)): the
    weight of each chosen expert over ALL experts, 0 elsewhere; and how far
    the k-th chosen biased score lies above the best one left out — a system
    in bf16 can flip the two where that margin is within its rounding.

    `forced` (T, k): the experts a served program chose. The weights are
    then computed for THOSE experts (from this file's own scores), `experts`
    stays this file's own choice, and the margin says how far the worst of
    the forced experts lies below this file's k-th best biased score: 0
    where the two sets agree. A row of -1 (the caller could not tell which
    choice was this token's) follows this file's own choice."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ get(p + "mlp.gate.weight").T)
    # the bias moves the choice, never the weight
    biased = scores + get(p + "mlp.gate.e_score_correction_bias")[None]
    top, experts = jax.lax.top_k(biased, k + 1)
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
         if shared and cfg["n_shared_experts"] else jnp.zeros_like(xn))
    for e in range(first, first + count):
        # every held expert on every position, weighted by w_e or 0
        y = y + combine[:, e:e + 1] * run(
            swiglu, get, p + f"mlp.experts.{e}.", xn)
    return y, {"experts": experts, "margin": margin}


def forward(cfg: dict, weights, tokens, logit_positions=None, *,
            query_block: int = 256, token_block: int = 2048,
            forced_experts=None) -> tuple[jax.Array, list]:
    """Logits (T, vocab slice) of every position of one sequence (or of
    `logit_positions` only), and each layer's routing. `forced_experts`:
    per layer, in order, the (T, k) experts a served program chose
    (`route`): the forward then follows the served choices and reports how
    far each lay from its own. Nothing is jitted as a whole: each tensor is
    widened to float32 where it is used, so one matrix's copy is live at a
    time."""
    cfg = check_config(cfg)
    eps = cfg["rms_norm_eps"]
    gqa = set(cfg["gqa_layers"])

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
        u = rms_norm(x, get(p + "input_layernorm.weight"), eps)
        if i in gqa:
            x = x + run(attention, cfg, get, p, u, query_block)
        else:
            x = x + run(kda_layer, cfg, get, p, u, token_block)
        xn = rms_norm(x, get(p + "post_attention_layernorm.weight"), eps)
        forced = None if forced_experts is None else forced_experts[i]
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
