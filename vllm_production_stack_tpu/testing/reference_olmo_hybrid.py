"""A plain reference for the Olmo-Hybrid decoder this stack serves
(`model_type: olmo_hybrid`): the forward pass in straightforward `jax.numpy`,
float32, every matmul under `jax.default_matmul_precision("highest")` (a
TPU's default float32 matmul is bf16 passes). No KV cache, no state slots,
no kernels, no batching: one sequence, one layer at a time, the recurrence
TOKEN BY TOKEN (`lax.scan`), attention as a plain masked softmax,
parametrised by the published `config.json` keys (x the residual stream, h =
`hidden_size`, eps = `rms_norm_eps`, no bias anywhere).

`layer_types[i]` says what layer i is. Both kinds sit in OLMo-2's block —
NO pre-norm, the sublayers' OUTPUTS are normed:

    x = x + RMSNorm(Mixer(x); post_attention_layernorm)
    x = x + RMSNorm(W_down(silu(W_gate x) * W_up x); post_feedforward_layernorm)
    logits = W_head RMSNorm(x_L; norm)            (`tie_word_embeddings` false)

`linear_attention` — Gated DeltaNet (arXiv:2412.06464), H =
`linear_num_key_heads` = `linear_num_value_heads` heads, Dk =
`linear_key_head_dim`, Dv = `linear_value_head_dim`, u the layer's input:

    q~ = W_q u, k~ = W_k u (h -> H Dk),  v~ = W_v u (h -> H Dv)
    q_t = silu(sum_{i=0..3} c^q_i * q~_{t-3+i})     the short convolution:
        causal, depthwise, `linear_conv_kernel_dim` 4 taps, no bias;
        positions before the sequence's first token are ZERO; k, v likewise
    q^_t = q_t / sqrt(|q_t|^2 + 1e-6) * Dk^-0.5,  k^_t = k_t / sqrt(|k_t|^2
        + 1e-6)                                     per head, over its Dk dims
    g_t = -exp(A_log[head]) * softplus(W_a u + dt_bias[head])   ONE log-decay
        a head and token (W_a: h -> H)
    beta_t = 2 sigmoid(W_b u)   a head (`linear_allow_neg_eigval` true)
    S_0 = 0;  S' = exp(g_t) S_{t-1};  w = beta_t (v_t - S'^T k^_t);
    S_t = S' + k^_t w^T;  o_t = S_t^T q^_t          per head, S (Dk x Dv)
    o_t <- RMSNorm over the head's Dv dims (one weight of Dv shared by the
        heads) * silu(W_z u)   (W_z: h -> H Dv);   Mixer = W_o concat(o)

`full_attention` — `num_attention_heads` query and `num_key_value_heads`
key-value heads x D (`head_dim`, or h / heads where it is null):

    q = RMSNorm(W_q u; q_norm) and k = RMSNorm(W_k u; k_norm) over the FLAT
        projection (all heads' channels at once), BEFORE the head split
    no position encoding (`rope_parameters.rope_theta` null);  a query sees
    the keys at positions <= its own;  softmax(q . k * D^-0.5) v;  W_o

ASSUMED, because the published config has no key for it (each also in the
benchmark configuration's `assumed`, with the alternative NOT taken):

- the `linear_attention` layers sit in the SAME post-norm block as the
  `full_attention` layers (a pre-norm block for them is the alternative);
- the output gate goes through SiLU (the gated delta rule's public
  implementations' default; the logistic sigmoid KDA uses is the
  alternative);
- `rope_theta: null` says NO position encoding (a default theta of 10,000
  or 500,000 is the alternative);
- `head_dim` null says h / heads (128); the QK-norm is OLMo-2's flat one (a
  norm a head is the alternative);
- the decay gate and the step size project at full rank; A_log, dt_bias and
  the state are float32.

The weights are the served tree's leaves (models/llama.py:
_init_params_by_kind: "attn" over the full layers, "attn_linear" over the
linear ones, "mlp" and the two norms over all, (in, out) matrices, a layer an
index of its own stack) — a checkpoint of the family was never read, so
there are no checkpoint names to read them under.

FAULT ARMS (`forward`'s keywords; the checks show each lands OUTSIDE a
limit): `state_dtype` (the state rounded to it at every token),
`pre_norm_linear` (the linear layers' block pre-norm: x + Mixer(Norm(x))),
`output_gate` ("sigmoid"), `rope_theta` (a rotary position encoding of that
base in the full layers), `qk_norm_per_head` (the QK-norm's statistic a
head).

This file has a byte-identical copy under `benchmarks/chip/reference/` (a
tier-1 test holds them together); it imports nothing from the package.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

TAPS = 4


def published_keys(model_cfg) -> dict:
    """The published keys of the ModelConfig the engine serves (the
    reference reads nothing else of it)."""
    return {
        "model_type": "olmo_hybrid",
        "vocab_size": model_cfg.vocab_size,
        "hidden_size": model_cfg.hidden_size,
        "intermediate_size": model_cfg.intermediate_size,
        "num_hidden_layers": model_cfg.num_layers,
        "num_attention_heads": model_cfg.num_heads,
        "num_key_value_heads": model_cfg.num_kv_heads,
        "head_dim": model_cfg.head_dim,
        "rms_norm_eps": model_cfg.rms_norm_eps,
        "layer_types": [
            "linear_attention" if s.mixer == "linear" else "full_attention"
            for s in model_cfg.layer_specs],
        "linear_num_key_heads": model_cfg.linear_num_heads,
        "linear_num_value_heads": model_cfg.linear_num_heads,
        "linear_key_head_dim": model_cfg.linear_head_dim,
        "linear_value_head_dim": model_cfg.linear_value_dim,
        "linear_conv_kernel_dim": TAPS,
        "linear_allow_neg_eigval": model_cfg.linear_beta_scale == 2.0,
        "kda_state_dtype": "float32",
    }


def f32(a) -> jax.Array:
    return jnp.asarray(a, jnp.float32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * f32(weight)


def rope(x, positions, theta):
    """Half-rotation rope over the whole head: x (T, heads, D). (A FAULT
    arm's: the model has no position encoding.)"""
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = f32(positions)[:, None] * f32(inv)[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def delta_rule(q, k, v, g, beta, s0, state_dtype=jnp.float32):
    """The recurrence, one token at a time: q, k (T, H, Dk), v (T, H, Dv),
    g and beta (T, H), s0 (H, Dk, Dv) -> (o (T, H, Dv), s_T). Elementwise
    products and sums only (no matmul whose precision could be lowered)."""

    def step(s, xs):
        qt, kt, vt, gt, bt = xs
        s = s.astype(jnp.float32) * jnp.exp(gt)[:, None, None]
        pred = jnp.sum(s * kt[:, :, None], axis=1)  # S'^T k, (H, Dv)
        s = s + kt[:, :, None] * (bt[:, None] * (vt - pred))[:, None, :]
        o = jnp.sum(s * qt[:, :, None], axis=1)
        # (a faulty state_dtype rounds what the next token reads)
        return s.astype(state_dtype), o

    s, o = jax.lax.scan(step, s0.astype(state_dtype),
                        tuple(f32(x) for x in (q, k, v, g, beta)))
    return o, s.astype(jnp.float32)


def _qk_norm(cfg, arms, x, weight, heads):
    """The QK-norm over the FLAT projection x (T, heads D) (the fault arm:
    a head at a time, under the same weight)."""
    if arms.get("qk_norm_per_head"):
        t = x.reshape(x.shape[0], heads, -1)
        t = t * jax.lax.rsqrt(
            jnp.mean(t * t, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
        return t.reshape(x.shape) * f32(weight)
    return rms_norm(x, weight, cfg["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("cfg_t", "arms_t"))
def _kv(cfg_t, arms_t, ap, u, positions):
    cfg, arms = dict(cfg_t), dict(arms_t)
    with jax.default_matmul_precision("highest"):
        nkv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
        k = _qk_norm(cfg, arms, u @ f32(ap["wk"]), ap["k_norm"], nkv)
        k = k.reshape(-1, nkv, hd)
        if arms.get("rope_theta"):
            k = rope(k, positions, arms["rope_theta"])
        return k, (u @ f32(ap["wv"])).reshape(-1, nkv, hd)


@functools.partial(jax.jit, static_argnames=("cfg_t", "arms_t"))
def _attn_block(cfg_t, arms_t, ap, u, positions, k, v):
    """A block of queries (u (Q, h) at `positions`) against every key."""
    cfg, arms = dict(cfg_t), dict(arms_t)
    with jax.default_matmul_precision("highest"):
        nh, nkv, hd = (cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"])
        q = _qk_norm(cfg, arms, u @ f32(ap["wq"]), ap["q_norm"], nh)
        q = q.reshape(-1, nh, hd)
        if arms.get("rope_theta"):
            q = rope(q, positions, arms["rope_theta"])
        q = q.reshape(-1, nkv, nh // nkv, hd)
        scores = jnp.einsum("qkgd,tkd->kgqt", q, k) * hd ** -0.5
        seen = jnp.arange(k.shape[0])[None] <= positions[:, None]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        o = jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(scores, -1), v)
        return o.reshape(-1, nh * hd) @ f32(ap["wo"])


@functools.partial(jax.jit, static_argnames=("cfg_t", "arms_t"))
def _linear_block(cfg_t, arms_t, ap, u, live, tail, s0):
    """A block of tokens through a Gated DeltaNet mixer: u (Q, h), `live`
    (Q,) which are the sequence's (padding behind it changes nothing that
    is kept), `tail` (K - 1, channels) the convolution's inputs before the
    block, s0 (H, Dk, Dv). Returns (out (Q, h), tail', state')."""
    cfg, arms = dict(cfg_t), dict(arms_t)
    with jax.default_matmul_precision("highest"):
        nh, dk, dv = (cfg["linear_num_key_heads"],
                      cfg["linear_key_head_dim"],
                      cfg["linear_value_head_dim"])
        t = u.shape[0]
        raw = jnp.concatenate(
            [u @ f32(ap[n]) for n in ("wq", "wk", "wv")], axis=-1)
        padded = jnp.concatenate([tail, raw], axis=0)
        w = f32(ap["conv"])
        act = jax.nn.silu(sum(padded[i:i + t] * w[i] for i in range(TAPS)))
        q, k, v = jnp.split(act, [nh * dk, 2 * nh * dk], axis=-1)
        q, k, v = (q.reshape(t, nh, dk), k.reshape(t, nh, dk),
                   v.reshape(t, nh, dv))
        q = q * jax.lax.rsqrt(
            jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * dk ** -0.5
        k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
        g = -jnp.exp(f32(ap["A_log"]))[None] * jax.nn.softplus(
            u @ f32(ap["wf"]) + f32(ap["dt_bias"]))
        beta = (2.0 if cfg["linear_allow_neg_eigval"] else 1.0
                ) * jax.nn.sigmoid(u @ f32(ap["wb"]))
        # (a padding token: no decay and no step — it changes nothing)
        g = jnp.where(live[:, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
        o, s = delta_rule(q, k, v, g, beta, s0, jnp.dtype(
            arms.get("state_dtype", cfg["kda_state_dtype"])))
        z = u @ f32(ap["wg"])
        gate = (jax.nn.sigmoid if arms.get("output_gate") == "sigmoid"
                else jax.nn.silu)
        o = rms_norm(o, ap["o_norm"], cfg["rms_norm_eps"])
        o = o.reshape(t, nh * dv) * gate(z)
        # the tail: the block's last K - 1 LIVE conv inputs
        new_tail = jax.lax.dynamic_slice_in_dim(
            padded, jnp.sum(live), TAPS - 1, 0)
        return o @ f32(ap["wo"]), new_tail, s


@functools.partial(jax.jit, static_argnames=("cfg_t",))
def _mlp_block(cfg_t, mp, norm, x):
    cfg = dict(cfg_t)
    with jax.default_matmul_precision("highest"):
        inner = (x @ f32(mp["up"])) * jax.nn.silu(x @ f32(mp["gate"]))
        return x + rms_norm(inner @ f32(mp["down"]), norm,
                            cfg["rms_norm_eps"])


def _hashable(d: dict) -> tuple:
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in d.items()))


def _kind_index(cfg: dict, layer: int) -> int:
    """Layer `layer`'s index among the layers of its own kind."""
    kinds = cfg["layer_types"]
    return sum(k == kinds[layer] for k in kinds[:layer])


def forward(cfg: dict, params: dict, tokens, logit_positions=None, *,
            token_block: int = 1024, query_block: int = 128,
            vocab_block: int = 32768, return_state: int | None = None,
            return_kv: int | None = None, **arms):
    """Logits (len(logit_positions), vocab) float32 of one sequence — every
    position's without `logit_positions` — and [] (no layer routes).
    `return_state`: a linear layer whose last state is returned in the place
    of the []; `return_kv`: a full layer whose (K, V) of the sequence's
    tokens are, and the forward stops there. `arms`: the fault arms above."""
    cfg_t, arms_t = _hashable(cfg), _hashable(arms)
    n = len(tokens)
    token_block = min(token_block, -(-n // query_block) * query_block)
    pad = (-n) % token_block
    ids = jnp.asarray(list(tokens) + [0] * pad, jnp.int32)
    t = n + pad
    positions = jnp.arange(t, dtype=jnp.int32)
    live = positions < n
    layers = params["layers"]
    eps = cfg["rms_norm_eps"]
    nh, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                  cfg["linear_value_head_dim"])
    x = f32(params["embed"][ids])
    kept = []
    for layer, kind in enumerate(cfg["layer_types"]):
        i = _kind_index(cfg, layer)
        out_norm = layers["attn_out_norm"][layer]
        if kind == "full_attention":
            ap = jax.tree.map(lambda a: a[i], layers["attn"])
            k, v = _kv(cfg_t, arms_t, ap, x, positions)
            if return_kv == layer:
                return None, (k[:n], v[:n])
            mixed = jnp.concatenate([
                _attn_block(cfg_t, arms_t, ap, x[lo:lo + query_block],
                            positions[lo:lo + query_block], k, v)
                for lo in range(0, t, query_block)])
            x = x + rms_norm(mixed, out_norm, eps)
        else:
            ap = jax.tree.map(lambda a: a[i], layers["attn_linear"])
            # FAULT arm: the block pre-norm, under the same weight
            u = rms_norm(x, out_norm, eps) if arms.get(
                "pre_norm_linear") else x
            tail = jnp.zeros((TAPS - 1, nh * (2 * dk + dv)), jnp.float32)
            s = jnp.zeros((nh, dk, dv), jnp.float32)
            outs = []
            for lo in range(0, t, token_block):
                out, tail, s = _linear_block(
                    cfg_t, arms_t, ap, u[lo:lo + token_block],
                    live[lo:lo + token_block], tail, s)
                outs.append(out)
            mixed = jnp.concatenate(outs)
            x = x + (mixed if arms.get("pre_norm_linear")
                     else rms_norm(mixed, out_norm, eps))
            if return_state == layer:
                kept = s
        mp = jax.tree.map(lambda a: a[layer], layers["mlp"])
        x = jnp.concatenate([
            _mlp_block(cfg_t, mp, layers["ffw_out_norm"][layer],
                       x[lo:lo + token_block])
            for lo in range(0, t, token_block)])
    x = rms_norm(x, params["final_norm"], eps)
    at = (jnp.arange(n) if logit_positions is None
          else jnp.asarray(list(logit_positions), jnp.int32))
    # (the head a block of the vocabulary at a time: 100,352 x 3,840 in
    # float32 at once is 1.5 GB beside the served weights)
    head = params["lm_head"]
    logits = jnp.concatenate([
        _head_block(x[at], head[:, lo:lo + vocab_block])
        for lo in range(0, head.shape[1], vocab_block)], axis=-1)
    return logits, kept


def first_page_layer(cfg: dict) -> int:
    return cfg["layer_types"].index("full_attention")


def first_layer_kv(cfg: dict, params: dict, tokens) -> tuple:
    """(K, V), each (len(tokens), kv heads, head dim) float32, of the first
    layer that HOLDS PAGES (the first `full_attention` layer): what its
    pages hold of one sequence — K after the flat norm. They rest on the
    linear layers before it, so they also hold those layers' served output
    to the reference's."""
    return forward(cfg, params, tokens, return_kv=first_page_layer(cfg))[1]


@jax.jit
def _head_block(x, w):
    with jax.default_matmul_precision("highest"):
        return x @ f32(w)
