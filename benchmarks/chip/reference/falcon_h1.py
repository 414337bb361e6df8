"""A plain reference for the Falcon-H1 decoder this stack serves
(`model_type: falcon_h1`): the forward pass in straightforward `jax.numpy`,
float32, every matmul under `jax.default_matmul_precision("highest")` (a
TPU's default float32 matmul is bf16 passes). No KV cache, no state slots,
no kernels, no batching: one sequence, one layer at a time, the recurrence
TOKEN BY TOKEN (`lax.scan`), plain softmax attention, parametrised by the
published `config.json` keys (x the residual stream, h = hidden_size).

Every block holds attention heads AND Mamba-2 heads; both read ONE normed
input and add to ONE residual (multipliers literally where the published
implementation puts them; `cfg[...]` are the config's keys):

    x0     = embed[ids] * embedding_multiplier
    block:   u  = RMSNorm(x; input_layernorm, rms_norm_eps)
             a  = Attn(u * attention_in_multiplier) * attention_out_multiplier
             m  = SSM (u * ssm_in_multiplier)       * ssm_out_multiplier
             x  = x + a + m
             f  = RMSNorm(x; pre_ff_layernorm)
             x  = x + W_down(W_up f * silu(W_gate f * mlp_multipliers[0]))
                      * mlp_multipliers[1]
    logits = W_head RMSNorm(x_L; final_layernorm) * lm_head_multiplier

    Attn(v): q = W_q v (num_attention_heads x head_dim), k = (W_k v) *
             key_multiplier, v' = W_v v (num_key_value_heads x head_dim);
             rope on q and k over the whole head, half-rotation form,
             `rope_theta`, no scaling; a query sees the keys at positions <=
             its own; softmax(q . k * head_dim^-0.5) v'; W_o; no bias.

    SSM(v):  p = (W_in v) * mup_vector   # z | x | B | C | dt, widths d_ssm,
                 d_ssm, n_groups d_state, n_groups d_state, n_heads;
                 mup_vector = ssm_multipliers[0..4] over those five spans
             xBC = silu(conv(x | B | C) + conv_bias)   causal, depthwise,
                 `mamba_d_conv` taps (tap K-1 on the current token);
                 positions before the sequence's first token are ZERO
             dt  = softplus(dt + dt_bias)              no clamp
             per head j (group g = j // (n_heads / n_groups)), A = -exp(A_log):
               S_t = exp(dt_t[j] A[j]) S_{t-1} + (dt_t[j] x_t[j]) (outer) B_t[g]
               y_t[j] = S_t C_t[g] + D[j] x_t[j]       S: d_head x d_state
             y = y * silu(z)                           the gate FIRST
                 (`mamba_norm_before_gate` false)
             y = RMSNorm over each group's d_ssm / n_groups channels * weight
             out = W_out y

ASSUMED, because the published config has no key for it (each also in the
benchmark configuration's `assumed`): the gated norm's group size (d_ssm /
n_groups; one norm over all of d_ssm is the alternative not taken); the
rope's half-rotation form (interleaved pairs not taken); float32 for S, dt,
A and the decay (`ssm_state_dtype`; the model's dtype not taken).

The weights are the served tree's leaves (models/llama.py:
_init_params_by_kind: "attn", "attn_ssm", "mlp", (in, out) matrices, a layer
an index of each stack) — a checkpoint of the family was never read, so
there are no checkpoint names to read them under.

FAULT ARMS (`forward`'s keywords; the checks show each one lands OUTSIDE a
limit): `state_dtype` (the state rounded to it at every token),
`zero_branch` ("attention" | "ssm"), `skip_multiplier` (a multiplier's key
left at 1; "ssm_multipliers" leaves the whole vector out),
`norm_before_gate`, `swap_groups` (the two groups' B and C exchanged),
`no_d`, `no_conv_bias`.

Work is done in blocks of `token_block` tokens (projections, the SSM branch
with its state and convolution tail carried from block to block, the MLP)
and `query_block` queries (attention, each block against every key under
its mask), so that 16k tokens' float32 activations at published widths fit
beside the served weights; the same sums in the same order as one pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

MULTIPLIER_KEYS = (
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers")


def published_keys(model_cfg) -> dict:
    """The published keys of the ModelConfig the engine serves (the
    reference reads nothing else of it)."""
    mup = model_cfg.mup
    return {
        "model_type": "falcon_h1",
        "vocab_size": model_cfg.vocab_size,
        "hidden_size": model_cfg.hidden_size,
        "intermediate_size": model_cfg.intermediate_size,
        "num_hidden_layers": model_cfg.num_layers,
        "num_attention_heads": model_cfg.num_heads,
        "num_key_value_heads": model_cfg.num_kv_heads,
        "head_dim": model_cfg.head_dim,
        "rope_theta": model_cfg.layer_specs[0].rope.theta,
        "rms_norm_eps": model_cfg.rms_norm_eps,
        "mamba_n_heads": model_cfg.ssm_num_heads,
        "mamba_d_head": model_cfg.ssm_head_dim,
        "mamba_d_ssm": model_cfg.ssm_inner,
        "mamba_d_state": model_cfg.ssm_state_dim,
        "mamba_n_groups": model_cfg.ssm_num_groups,
        "mamba_d_conv": 4,
        "embedding_multiplier": mup.embedding,
        "lm_head_multiplier": mup.lm_head,
        "attention_in_multiplier": mup.attention_in,
        "attention_out_multiplier": mup.attention_out,
        "key_multiplier": mup.key,
        "ssm_in_multiplier": mup.ssm_in,
        "ssm_out_multiplier": mup.ssm_out,
        "ssm_multipliers": list(mup.ssm),
        "mlp_multipliers": [mup.mlp_gate, mup.mlp_down],
        "ssm_state_dtype": "float32",
    }


def f32(a) -> jax.Array:
    return jnp.asarray(a, jnp.float32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * f32(weight)


def rope(x, positions, theta):
    """Half-rotation rope over the whole head: x (T, heads, D)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = f32(positions)[:, None] * f32(inv)[None]  # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def ssd_recurrence(x, b, c, dt, s0, a_log, d, *,
                   state_dtype=jnp.float32, swap_groups=False, no_d=False):
    """The state-space recurrence, token by token: x (T, H, P), b, c
    (T, G, N), dt (T, H) after its softplus, s0 (H, P, N); a_log, d (H,).
    Returns (y (T, H, P), the last state) — the state kept in `state_dtype`
    between tokens."""
    heads, groups = x.shape[1], b.shape[1]
    rate = -jnp.exp(f32(a_log))
    if swap_groups:
        b, c = b[:, ::-1], c[:, ::-1]

    def step(s, inp):
        xt, bt, ct, dtt = inp
        bt, ct = (jnp.repeat(t, heads // groups, axis=0) for t in (bt, ct))
        s = (jnp.exp(dtt * rate)[:, None, None] * f32(s)
             + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        s = s.astype(state_dtype)
        y = jnp.einsum("hpn,hn->hp", f32(s), ct)
        return s, y if no_d else y + f32(d)[:, None] * xt

    s, y = jax.lax.scan(step, s0.astype(state_dtype),
                        (f32(x), f32(b), f32(c), f32(dt)))
    return y, s


def _multiplier(cfg, arms, key, index=None):
    if arms.get("skip_multiplier") == key:
        return 1.0
    return cfg[key] if index is None else cfg[key][index]


@functools.partial(jax.jit, static_argnames=("cfg_t", "arms_t"))
def _kv(cfg_t, arms_t, ap, u, positions):
    cfg, arms = dict(cfg_t), dict(arms_t)
    with jax.default_matmul_precision("highest"):
        v_in = u * _multiplier(cfg, arms, "attention_in_multiplier")
        nkv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
        k = (v_in @ f32(ap["wk"])) * _multiplier(cfg, arms, "key_multiplier")
        k = rope(k.reshape(-1, nkv, hd), positions, cfg["rope_theta"])
        return k, (v_in @ f32(ap["wv"])).reshape(-1, nkv, hd)


@functools.partial(jax.jit, static_argnames=("cfg_t", "arms_t"))
def _attn_block(cfg_t, arms_t, ap, u, positions, k, v):
    """A block of queries (u (Q, h) at `positions`) against every key."""
    cfg, arms = dict(cfg_t), dict(arms_t)
    with jax.default_matmul_precision("highest"):
        nh, nkv, hd = (cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"])
        v_in = u * _multiplier(cfg, arms, "attention_in_multiplier")
        q = rope((v_in @ f32(ap["wq"])).reshape(-1, nh, hd), positions,
                 cfg["rope_theta"])
        q = q.reshape(-1, nkv, nh // nkv, hd)
        scores = jnp.einsum("qkgd,tkd->kgqt", q, k) * hd ** -0.5
        seen = jnp.arange(k.shape[0])[None] <= positions[:, None]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        o = jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(scores, -1), v)
        return o.reshape(-1, nh * hd) @ f32(ap["wo"])


@functools.partial(jax.jit, static_argnames=("cfg_t", "arms_t"))
def _ssm_block(cfg_t, arms_t, sp, u, live, tail, s0):
    """A block of tokens through the state-space branch: u (Q, h), `live`
    (Q,) which are the sequence's (padding behind it changes nothing that
    is kept), `tail` (K - 1, channels) the convolution's inputs before the
    block, s0 the state. Returns (out (Q, h), tail', state')."""
    cfg, arms = dict(cfg_t), dict(arms_t)
    with jax.default_matmul_precision("highest"):
        hs, hp = cfg["mamba_n_heads"], cfg["mamba_d_head"]
        g, ns, inner = (cfg["mamba_n_groups"], cfg["mamba_d_state"],
                        cfg["mamba_d_ssm"])
        taps = cfg["mamba_d_conv"]
        p = (u * _multiplier(cfg, arms, "ssm_in_multiplier")) @ f32(
            sp["w_in"])
        if arms.get("skip_multiplier") != "ssm_multipliers":
            p = p * jnp.concatenate([
                jnp.full((w,), m, jnp.float32) for w, m in zip(
                    (inner, inner, g * ns, g * ns, hs),
                    cfg["ssm_multipliers"])])
        z, xbc, dt = jnp.split(p, [inner, 2 * inner + 2 * g * ns], axis=-1)
        padded = jnp.concatenate([tail, xbc], axis=0)
        w = f32(sp["conv"])
        conv = sum(padded[i:i + xbc.shape[0]] * w[i] for i in range(taps))
        if not arms.get("no_conv_bias"):
            conv = conv + f32(sp["conv_bias"])
        act = jax.nn.silu(conv)
        x, b, c = jnp.split(act, [inner, inner + g * ns], axis=-1)
        dt = jax.nn.softplus(dt + f32(sp["dt_bias"]))
        # (a padding token: no step — it decays nothing and adds nothing)
        dt = jnp.where(live[:, None], dt, 0.0)
        y, s = ssd_recurrence(
            x.reshape(-1, hs, hp), b.reshape(-1, g, ns),
            c.reshape(-1, g, ns), dt, s0, sp["A_log"], sp["D"],
            state_dtype=jnp.dtype(arms.get("state_dtype", "float32")),
            swap_groups=bool(arms.get("swap_groups")),
            no_d=bool(arms.get("no_d")))
        y = y.reshape(-1, inner)
        gate = jax.nn.silu(z)

        def group_norm(t):
            t = t.reshape(-1, g, inner // g)
            t = t * jax.lax.rsqrt(
                jnp.mean(t * t, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
            return t.reshape(-1, inner) * f32(sp["norm"])

        y = (group_norm(y) * gate if arms.get("norm_before_gate")
             else group_norm(y * gate))
        # the tail: the block's last K - 1 LIVE conv inputs
        n_live = jnp.sum(live)
        new_tail = jax.lax.dynamic_slice_in_dim(padded, n_live, taps - 1, 0)
        return y @ f32(sp["w_out"]), new_tail, s


@functools.partial(jax.jit, static_argnames=("cfg_t", "arms_t"))
def _mlp_block(cfg_t, arms_t, mp, norm, x):
    cfg, arms = dict(cfg_t), dict(arms_t)
    with jax.default_matmul_precision("highest"):
        f = rms_norm(x, norm, cfg["rms_norm_eps"])
        gate = (f @ f32(mp["gate"])) * _multiplier(
            cfg, arms, "mlp_multipliers", 0)
        inner = (f @ f32(mp["up"])) * jax.nn.silu(gate)
        return x + (inner @ f32(mp["down"])) * _multiplier(
            cfg, arms, "mlp_multipliers", 1)


def _hashable(d: dict) -> tuple:
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in d.items()))


def forward(cfg: dict, params: dict, tokens, logit_positions=None, *,
            token_block: int = 1024, query_block: int = 128,
            vocab_block: int = 32768, return_state: int | None = None,
            **arms):
    """Logits (len(logit_positions), vocab) float32 of one sequence — every
    position's without `logit_positions` — and [] (no layer routes).
    `return_state`: a layer whose last state-space state is returned in the
    place of the []. `arms`: the fault arms above."""
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
    hs, hp, ns = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    channels = cfg["mamba_d_ssm"] + 2 * cfg["mamba_n_groups"] * ns
    zero = arms.get("zero_branch")
    x = f32(params["embed"][ids]) * _multiplier(
        cfg, arms, "embedding_multiplier")
    kept = []
    for layer in range(cfg["num_hidden_layers"]):
        lp = jax.tree.map(lambda a, i=layer: a[i], layers)
        u = rms_norm(x, lp["input_norm"], eps)
        mixed = jnp.zeros_like(x)
        if zero != "attention":
            k, v = _kv(cfg_t, arms_t, lp["attn"], u, positions)
            a = jnp.concatenate([
                _attn_block(cfg_t, arms_t, lp["attn"],
                            u[lo:lo + query_block],
                            positions[lo:lo + query_block], k, v)
                for lo in range(0, t, query_block)])
            mixed = mixed + a * _multiplier(
                cfg, arms, "attention_out_multiplier")
        if zero != "ssm":
            tail = jnp.zeros((cfg["mamba_d_conv"] - 1, channels), jnp.float32)
            s = jnp.zeros((hs, hp, ns), jnp.float32)
            outs = []
            for lo in range(0, t, token_block):
                out, tail, s = _ssm_block(
                    cfg_t, arms_t, lp["attn_ssm"], u[lo:lo + token_block],
                    live[lo:lo + token_block], tail, s)
                outs.append(out)
            mixed = mixed + jnp.concatenate(outs) * _multiplier(
                cfg, arms, "ssm_out_multiplier")
            if return_state == layer:
                kept = s
        x = x + mixed
        x = jnp.concatenate([
            _mlp_block(cfg_t, arms_t, lp["mlp"], lp["post_attn_norm"],
                       x[lo:lo + token_block])
            for lo in range(0, t, token_block)])
    x = rms_norm(x, params["final_norm"], eps)
    at = (jnp.arange(n) if logit_positions is None
          else jnp.asarray(list(logit_positions), jnp.int32))
    # (the head a block of the vocabulary at a time: 261,120 x 5,120 in
    # float32 at once is 5.3 GB beside the served weights)
    head = params["lm_head"]
    logits = jnp.concatenate([
        _head_block(x[at], head[:, lo:lo + vocab_block])
        for lo in range(0, head.shape[1], vocab_block)], axis=-1)
    return logits * _multiplier(cfg, arms, "lm_head_multiplier"), kept


def first_layer_kv(cfg: dict, params: dict, tokens) -> tuple:
    """(K, V), each (len(tokens), kv heads, head dim) float32, of the FIRST
    layer: what its pages hold of one sequence — K times `key_multiplier`
    and rotated. They rest on the embedding, one norm and two projections
    only, so the served pages can be held to them far closer than logprobs
    six layers later can hold anything."""
    ids = jnp.asarray(list(tokens), jnp.int32)
    ap = jax.tree.map(lambda a: a[0], params["layers"]["attn"])
    x = f32(params["embed"][ids]) * cfg["embedding_multiplier"]
    u = rms_norm(x, params["layers"]["input_norm"][0], cfg["rms_norm_eps"])
    return _kv(_hashable(cfg), (), ap, u,
               jnp.arange(len(ids), dtype=jnp.int32))


@jax.jit
def _head_block(x, w):
    with jax.default_matmul_precision("highest"):
        return x @ f32(w)
