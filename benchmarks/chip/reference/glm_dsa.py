"""A plain reference for the `model_type: glm_moe_dsa` decoder this stack
serves (GLM-5.2: multi-head latent attention with a query latent over a
LEARNED SELECTION of keys, sigmoid-routed experts): the forward pass in
straightforward `jax.numpy`, float32, every matmul under
`jax.default_matmul_precision("highest")` (a TPU's default float32 matmul is
bf16 passes). No KV cache, no kernels, no batching, no absorption: one
sequence, all positions, one layer at a time, token t at position t:

    u    = RMSNorm(x)
    c_q  = RMSNorm(W_qa u)                       -> q_lora_rank
    q    = W_qb c_q                              -> heads x (nope ‖ rope)
    c ‖ k_r = W_kva u                            -> kv_lora_rank ‖ rope
    k_nope ‖ v = W_kvb RMSNorm(c)                -> heads x (nope ‖ v_head)
    k    = k_nope ‖ rope(k_r),  q = q_nope ‖ rope(q_rope)

  a layer that owns an indexer (`indexer_types[i] == "full"`):
    q_i  = rope_64(W_iq c_q)                     -> index heads x index dim
    k_i  = rope_64(LayerNorm(W_ik u))            -> index dim, ONE a token
    w    = W_iw u                                -> index heads
    I[t, s] = Hi^-1/2 D^-1/2 Σ_j w[t, j] ReLU(q_i[t, j] · k_i[s])    s <= t
    S_t  = the min(t + 1, index_topk) positions s <= t of largest I[t, s],
           a tie to the lower position (a stable argsort)
  a layer that borrows ("shared"): S_t of the nearest "full" layer before it

    h    = x + W_o softmax over s in S_t of (q · k / sqrt(nope + rope)) v
    dense layer:   y = h + SwiGLU(RMSNorm(h))
    expert layer:  s = sigmoid(W_r h') in float32 over ALL the published
        experts; experts = top-k of s + bias; w = s[experts] / Σ w
        x routed_scaling_factor; y = h + Σ_{e held} w_e SwiGLU_e(h')
        + SwiGLU_shared(h') — the terms of the experts this chip holds
        (`first_expert_held`, `num_experts_held`): what the others would add
        is another chip's, as in the served program

`rope_64`: rope on the first `qk_rope_head_dim` values of the index dim.
Rope is rotate-half: the served tree's rope columns are in that order (a
checkpoint's interleaved pairs would be de-interleaved at load; random
weights are drawn in that order directly), so this file reads the SERVED
tree's leaves (models/llama.py:_init_params_mla) under their own names.

What a bf16 system cannot reproduce exactly, and how the check deals with
it: a bf16 index score flips the `index_topk`-th and the next key, as a bf16
router flips a boundary expert. `forward(forced_selection=...)` is told the
positions the served programs selected (per full layer (T, K), -1 padded;
a row of -2 where the tap could not name them) and ATTENDS OVER THOSE, and
reports per full layer how many of the served slots are not in its own
top-k and how far the worst of them lies under its own k-th best score
(`selection`): rounding moves the boundary by a hair, a fault in the
indexer moves it everywhere. `forced_experts` does the same
for the routing (testing/reference_mla.py says why).

The arms that compute something ELSE, each wrong in one way (the check shows
each lands outside a limit): `relu` False (the ReLU left out),
`head_weights` False (w left out), `last_k` (the LAST k keys instead of the
best), `topk` (another k), `key_norm` False (the index key un-normed),
`key_dtype` (the index keys as a cache of a lower precision would hold them:
"float8_e4m3fn", the precision below the configuration's; the pages' limit
tells it, `first_index_keys`), `borrow_lag` (a borrowing layer takes the
selection of the full layer BEFORE the right one), `own_selection` (every
layer selects for itself — with the indexer of the full layer it would
borrow from, on its OWN input).

This file has a byte-identical copy under `benchmarks/chip/reference/` (a
tier-1 test holds them together); it imports nothing from the package.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_PRECISION = "highest"
INDEX_KEY_NORM_EPS = 1e-6


def published_keys(model_cfg) -> dict:
    """The reference's configuration from the system's `ModelConfig` (or
    anything with its attributes), under the published key names."""
    specs = model_cfg.layer_specs
    return {
        "hidden_size": model_cfg.hidden_size,
        "num_hidden_layers": model_cfg.num_layers,
        "num_attention_heads": model_cfg.num_heads,
        "kv_lora_rank": model_cfg.kv_lora_rank,
        "q_lora_rank": model_cfg.q_lora_rank,
        "qk_nope_head_dim": model_cfg.qk_nope_head_dim,
        "qk_rope_head_dim": model_cfg.qk_rope_head_dim,
        "v_head_dim": model_cfg.v_head_dim,
        "index_n_heads": model_cfg.index_n_heads,
        "index_head_dim": model_cfg.index_head_dim,
        "index_topk": model_cfg.index_topk,
        "indexer_types": [s.indexer for s in specs],
        "mlp_layer_types": ["sparse" if s.experts else "dense" for s in specs],
        "rope_theta": model_cfg.rope_theta,
        "rms_norm_eps": model_cfg.rms_norm_eps,
        "tie_word_embeddings": model_cfg.tie_word_embeddings,
        "n_routed_experts": model_cfg.num_experts,
        "num_experts_per_tok": model_cfg.num_experts_per_tok,
        "n_shared_experts": model_cfg.n_shared_experts,
        "norm_topk_prob": model_cfg.norm_topk_prob,
        "routed_scaling_factor": model_cfg.routed_scaling_factor,
        # the chip's share (not published keys: the deployment's)
        "first_expert_held": model_cfg.first_expert_held,
        "num_experts_held": model_cfg.held_experts,
    }


def f32(a) -> jax.Array:
    return jnp.asarray(a).astype(jnp.float32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def layer_norm(x, weight, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + INDEX_KEY_NORM_EPS) * weight + bias


def rope(x, positions, theta: float, rotary: int | None = None):
    """Rotate-half rope on (T, heads, d), over the first `rotary` values of
    d (all of them by default): pair i is (x[i], x[i + rotary/2]), turned by
    position x theta^(-2i/rotary)."""
    d = rotary or x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:d]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., d:]], axis=-1)


def layer_leaves(params, cfg: dict, i: int, part: str) -> dict:
    """Layer i's leaves of the served tree, float32 on use — `part` "attn":
    the attention and norm stacks over all layers, the indexer's over the
    full layers; `part` "mlp": the dense or the expert MLP's, each stack
    over its own layers. A slice of a stacked leaf is a COPY on the device:
    a half is taken when it is needed, and an expert's three matrices one
    expert at a time (`expert`), not a layer's sixteen."""
    layers = params["layers"]
    kinds, mlps = cfg["indexer_types"], cfg["mlp_layer_types"]
    if part == "attn":
        out = {"attn": {k: v[i] for k, v in layers["attn"].items()},
               "input_norm": layers["input_norm"][i],
               "post_attn_norm": layers["post_attn_norm"][i]}
        if kinds[i] == "full":
            j = sum(k == "full" for k in kinds[:i])
            out["index"] = {k: v[j] for k, v in layers["attn_index"].items()}
        return out
    m = sum(t == mlps[i] for t in mlps[:i])
    if mlps[i] == "dense":
        return {"mlp": {k: v[m] for k, v in layers["mlp"].items()}}
    moe = layers["moe"]
    return {"moe": {
        "router": moe["router"][m], "bias": moe["bias"][m],
        "shared": {n: a[m] for n, a in moe.get("shared", {}).items()},
        "expert": lambda e: {n: moe[n][m, e] for n in ("gate", "up", "down")}}}


def index_keys(cfg: dict, ix: dict, u, positions, key_norm: bool = True,
               key_dtype: str | None = None):
    """(T, D): the index key of every token of one sequence — `key_dtype`:
    as a cache of that dtype would hold it (rounded there and back)."""
    k = u @ f32(ix["wk"])
    if key_norm:
        k = layer_norm(k, f32(ix["k_norm"]), f32(ix["k_norm_bias"]))
    k = rope(k[:, None, :], positions, cfg["rope_theta"],
             cfg["qk_rope_head_dim"])[:, 0]
    return k if key_dtype is None else f32(k.astype(jnp.dtype(key_dtype)))


def index_scores(cfg: dict, ix: dict, c_q, u, k_idx, positions, *,
                 relu: bool = True, head_weights: bool = True):
    """I[t, s] for the queries at `positions` against every key (T_q, T),
    -inf where s > t."""
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    q = rope((c_q @ f32(ix["wq_b"])).reshape(-1, hi, di), positions,
             cfg["rope_theta"], cfg["qk_rope_head_dim"])
    dots = jnp.einsum("qhd,sd->qhs", q, k_idx)
    if relu:
        dots = jax.nn.relu(dots)
    w = (u @ f32(ix["w_proj"])) if head_weights else jnp.ones(
        (q.shape[0], hi), jnp.float32)
    scores = jnp.einsum("qhs,qh->qs", dots, w) * (hi ** -0.5 * di ** -0.5)
    seen = positions[:, None] >= jnp.arange(k_idx.shape[0])[None, :]
    return jnp.where(seen, scores, -jnp.inf)


def select(scores, k: int, last_k: bool = False):
    """(T_q, T) bool: each query's k visible positions of largest score, a
    tie to the lower position (a stable argsort of the negated scores) —
    `last_k`: its LAST k visible positions instead."""
    t = scores.shape[1]
    seen = scores > -jnp.inf
    if last_k:
        rank = jnp.cumsum(seen[:, ::-1], axis=1)[:, ::-1]
        return seen & (rank <= k)
    order = jnp.argsort(-scores, axis=1, stable=True)[:, :min(k, t)]
    keep = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], order].set(True)
    return keep & seen


def attention(cfg: dict, lw: dict, x, selection, query_block: int, *,
              forced=None, arms: dict | None = None, head_block: int = 8):
    """Latent attention of one sequence in its EXPANDED form over each
    query's selected keys, (T, h) normed input -> ((T, h), the selection
    (T, T) bool the layer attended under, its report or None).

    `selection`: the mask a borrowing layer was handed (None for a layer
    that owns an indexer, which makes its own — or takes `forced`, the
    served programs' (T, K) positions, and reports on them)."""
    arms = arms or {}
    t = x.shape[0]
    ap, eps = lw["attn"], cfg["rms_norm_eps"]
    nh = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    positions = jnp.arange(t)
    c_q = rms_norm(x @ f32(ap["wq_a"]), f32(ap["q_a_norm"]), eps)
    ckr = x @ f32(ap["wkv_a"])
    c_kv = rms_norm(ckr[:, :r], f32(ap["kv_a_norm"]), eps)
    k_rope = rope(ckr[:, None, r:], positions, cfg["rope_theta"])
    report = None
    if "index" in lw:
        k_idx = index_keys(cfg, lw["index"], x, positions,
                           arms.get("key_norm", True), arms.get("key_dtype"))
        topk = arms.get("topk") or cfg["index_topk"]
        masks, differ, slots, margin = [], 0, 0, 0.0
        for lo in range(0, t, query_block):
            hi = min(t, lo + query_block)
            scores = index_scores(
                cfg, lw["index"], c_q[lo:hi], x[lo:hi], k_idx,
                positions[lo:hi], relu=arms.get("relu", True),
                head_weights=arms.get("head_weights", True))
            own = select(scores, topk, arms.get("last_k", False))
            if forced is None:
                masks.append(own)
                continue
            at = jnp.asarray(forced[lo:hi])  # (q, K) positions, -1 padded
            took = jnp.zeros(scores.shape, jnp.int32).at[
                jnp.arange(hi - lo)[:, None], jnp.maximum(at, 0)].max(
                    (at >= 0).astype(jnp.int32)) > 0
            # (a row of -2: a token whose served selection the tap could
            # not name — this file's own stands there)
            took = jnp.where(at[:, :1] == -2, own, took)
            masks.append(took)
            # the served slots that are not among this file's own top-k,
            # and how far the worst lies under its k-th best score
            kth = jnp.min(jnp.where(own, scores, jnp.inf), axis=1)
            off = took & ~own
            differ += int(jnp.sum(off))
            slots += int(jnp.sum(took))
            gap = jnp.where(off, kth[:, None] - scores, 0.0)
            margin = max(margin, float(jnp.max(gap)))
        selection = jnp.concatenate(masks, axis=0)
        if forced is not None:
            report = {"slots": slots, "differing": differ,
                      "margin_max": margin,
                      "score_scale": float(jnp.std(jnp.where(
                          scores > -jnp.inf, scores, 0.0)))}
    # the heads in groups of `head_block`, each group's output through its
    # rows of W_o at once (a long sequence's expanded keys, values and
    # outputs of all heads at once are GBs of float32)
    wq = ap["wq_b"].reshape(-1, nh, dn + dr)
    wkv = ap["wkv_b"].reshape(r, nh, dn + dv)
    wo = ap["wo"].reshape(nh, dv, -1)
    out = 0.0
    for h0 in range(0, nh, head_block):
        h1 = min(nh, h0 + head_block)
        q = jnp.einsum("tc,chd->thd", c_q, f32(wq[:, h0:h1]))
        q = jnp.concatenate(
            [q[..., :dn], rope(q[..., dn:], positions, cfg["rope_theta"])],
            axis=-1)
        kv = jnp.einsum("tc,chd->thd", c_kv, f32(wkv[:, h0:h1]))
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (t, h1 - h0, dr))],
            axis=-1)
        outs = []
        for lo in range(0, t, query_block):
            hi = min(t, lo + query_block)
            scores = jnp.einsum("qhd,khd->hqk", q[lo:hi], k) * (
                dn + dr) ** -0.5
            probs = jax.nn.softmax(
                jnp.where(selection[None, lo:hi], scores, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("hqk,khd->qhd", probs, kv[..., dn:]))
        out = out + jnp.einsum(
            "thd,hdm->tm", jnp.concatenate(outs, axis=0), f32(wo[h0:h1]))
    return out, selection, report


def swiglu(p: dict, x, token_block: int = 4096):
    """(in blocks of tokens: a long sequence's 12,288-wide intermediates
    are GBs of float32 beside the served weights)"""
    gate, up, down = f32(p["gate"]), f32(p["up"]), f32(p["down"])
    return jnp.concatenate([
        (jax.nn.silu(x[lo:lo + token_block] @ gate)
         * (x[lo:lo + token_block] @ up)) @ down
        for lo in range(0, x.shape[0], token_block)], axis=0)


def route(cfg: dict, moe: dict, x, forced=None):
    """(combine weights (T, E) over ALL the published experts, this file's
    chosen experts (T, k), margin (T,)) — testing/reference_mla.py:route."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ f32(moe["router"]))
    biased = scores + f32(moe["bias"])
    top, experts = jax.lax.top_k(biased, k + 1)
    margin = top[:, k - 1] - top[:, k]
    experts = experts[:, :k]
    used = experts
    if forced is not None:
        # (a row of -1: a token whose served choice the tap could not
        # name — this file's own choice stands there)
        forced = jnp.asarray(forced)
        used = jnp.where(jnp.all(forced >= 0, axis=-1, keepdims=True),
                         forced, experts)
        worst = jnp.min(jnp.take_along_axis(biased, used, axis=-1), axis=-1)
        margin = top[:, k - 1] - worst
    w = jnp.take_along_axis(scores, used, axis=-1)  # never the bias
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    combine = jnp.sum(
        jax.nn.one_hot(used, scores.shape[-1]) * w[..., None], axis=1)
    return combine, experts, margin


def experts_mlp(cfg: dict, moe: dict, x, combine):
    """Σ over the experts this chip HOLDS of w_e SwiGLU_e(x), plus the
    shared expert once."""
    y = swiglu(moe["shared"], x) if cfg["n_shared_experts"] else 0.0 * x
    first = cfg["first_expert_held"]
    # (`layer_leaves` hands an accessor, a test the layer's own leaves)
    expert = moe.get("expert") or (
        lambda e: {n: moe[n][e] for n in ("gate", "up", "down")})
    for e in range(cfg["num_experts_held"]):
        y = y + combine[:, first + e:first + e + 1] * swiglu(expert(e), x)
    return y


def first_index_keys(cfg: dict, params, tokens,
                     key_dtype: str | None = None) -> jax.Array:
    """(T, D): the index keys the FIRST layer (which owns an indexer) holds
    of `tokens` fed at positions 0..T-1 — what the engine's second cache
    leaf is held against (reference_check.served_index_pages); `key_dtype`:
    the arm of that name."""
    lw = layer_leaves(params, cfg, 0, "attn")
    x = f32(jnp.asarray(params["embed"])[jnp.asarray(tokens)])
    with jax.default_matmul_precision(_PRECISION):
        u = rms_norm(x, f32(lw["input_norm"]), cfg["rms_norm_eps"])
        return index_keys(cfg, lw["index"], u, jnp.arange(len(tokens)),
                          key_dtype=key_dtype)


def forward(cfg: dict, params, tokens, logit_positions=None, *,
            query_block: int = 64, forced_experts=None,
            forced_selection=None, **arms) -> tuple[jax.Array, list, list]:
    """Logits (T, vocab) of every position of one sequence (or of
    `logit_positions` only), each expert layer's routing (None for a dense
    layer) and each full layer's selection report (None unless
    `forced_selection`: per full layer, in order, the (T, K) positions the
    served programs selected). `arms`: see the module's docstring. Nothing
    is jitted as a whole: one layer's float32 copies are live at a time."""
    eps = cfg["rms_norm_eps"]
    kinds = cfg["indexer_types"]
    x = f32(jnp.asarray(params["embed"])[jnp.asarray(tokens)])
    routing, reports, made = [], [], []
    for i in range(cfg["num_hidden_layers"]):
        lw = layer_leaves(params, cfg, i, "attn")
        owner = dict(lw)
        if kinds[i] == "full":
            forced = (None if forced_selection is None
                      else forced_selection[len(made)])
            handed = None
        else:
            forced = None
            lag = 1 + bool(arms.get("borrow_lag")) if len(made) > 1 else 1
            handed = made[-lag]
            owner.pop("index", None)
            if arms.get("own_selection"):
                # the arm that must fail: the layer selects for itself, with
                # the indexer it would have borrowed the selection of
                j = max(n for n in range(i) if kinds[n] == "full")
                owner["index"] = layer_leaves(params, cfg, j, "attn")["index"]
                handed = None
        with jax.default_matmul_precision(_PRECISION):
            a, sel, report = attention(
                cfg, owner, rms_norm(x, f32(lw["input_norm"]), eps), handed,
                query_block, forced=forced, arms=arms)
        if kinds[i] == "full":
            if report:  # (which of the layers that own an indexer it is)
                report["full_layer"] = len(made)
            made.append(sel)
            reports.append(report)
        x = x + a
        xn = rms_norm(x, f32(lw["post_attn_norm"]), eps)
        del a, sel, owner
        lw = layer_leaves(params, cfg, i, "mlp")
        with jax.default_matmul_precision(_PRECISION):
            if "mlp" in lw:
                x = x + swiglu(lw["mlp"], xn)
                routing.append(None)
                continue
            forced_e = None
            if forced_experts is not None:
                forced_e = forced_experts[
                    sum(r is not None for r in routing)]
            combine, experts, margin = route(cfg, lw["moe"], xn, forced_e)
            x = x + experts_mlp(cfg, lw["moe"], xn, combine)
        routing.append({"experts": experts, "margin": margin})
    if logit_positions is not None:
        x = x[jnp.asarray(list(logit_positions))]
    x = rms_norm(x, f32(params["final_norm"]), eps)
    head = (f32(params["embed"]).T if cfg["tie_word_embeddings"]
            else f32(params["lm_head"]))
    with jax.default_matmul_precision(_PRECISION):
        return x @ head, routing, reports


def selection_summary(reports: list) -> dict:
    """The selection reports of one or more forwards, added up: the share
    of served (token, slot) pairs outside this file's own top-k, and the
    largest distance of one under the k-th best score, also as a multiple of
    the scores' spread. The share of the FIRST layer alone beside it: its
    input is the embedding's row, so nothing upstream of its indexer has
    rounded yet."""
    reports = [r for r in reports if r]
    slots = sum(r["slots"] for r in reports)
    differing = sum(r["differing"] for r in reports)
    first = [r for r in reports if r.get("full_layer") == 0]
    margin = max((r["margin_max"] for r in reports), default=0.0)
    scale = float(np.mean([r["score_scale"] for r in reports])) if reports \
        else 1.0
    return {"selection_slots": slots,
            "selection_disagreement_share": differing / max(slots, 1),
            "selection_first_layer_disagreement_share": sum(
                r["differing"] for r in first) / max(
                    sum(r["slots"] for r in first), 1),
            "selection_margin_max": margin,
            "selection_margin_max_in_spreads": margin / max(scale, 1e-30)}
