"""A plain reference for SDAR (`model_type: sdar_moe`, JetLM/SDAR-30B-A3B-Chat):
the Qwen3-MoE decoder under the BLOCK mask, and generation by diffusion over
blocks, in straightforward `jax.numpy`: float32, every matmul under
`jax.default_matmul_precision("highest")`, no KV cache, no kernels, no
batching — one sequence, all positions at once, one tensor widened to
float32 at a time.

The layer equations (x the residual stream, pre-norm, RMSNorm eps from the
config, every layer alike: `decoder_sparse_step` 1, `mlp_only_layers` []):

    x += W_o · Attn(rope(norm_h(q)), rope(norm_h(k)), v)   q, k, v = W · norm1(x)
    x += Σ_{e ∈ top-k} w_e · W_down,e(silu(W_gate,e u) ⊙ W_up,e u)   u = norm2(x)

- q and k get a per-head RMSNorm (one learned weight of `head_dim`, shared by
  the heads) before a rotate-half rope over the whole head at the token's
  absolute position; grouped-query attention, scores x head_dim ** -0.5,
  softmax in float32;
- THE MASK is the one thing that is not Qwen3's: with block length B a query
  at position i sees the key at position j iff j < (i // B + 1) * B — causal
  across blocks, bidirectional inside a block, blocks counted from position
  0 of the sequence, prompt included;
- p = softmax(u · W_r) over all experts in float32, the top-k renormalised
  to sum to 1 (`norm_topk_prob`), no shared expert, no bias, no scale;
- final RMSNorm, untied head, and NO SHIFT: the logits at position i are the
  distribution of the token AT position i.

`generate` is the family's published generation procedure
(`block_diffusion_generate`), written from its description:

1. the prompt's whole blocks (p // B of them) are context; no token comes out
   of them;
2. each further block holds the p mod B prompt tokens that did not fill a
   block (first block only) and mask tokens elsewhere; a pass is a forward
   over everything so far + the block's current tokens; at every masked
   position x0 = the argmax of that position's logits and its confidence =
   softmax(logits)[x0] in float32; the schedule's count n for the pass is
   B // steps (+1 for the first B mod steps passes), at most the masks left;
   `sequential` unmasks the leftmost n masked positions,
   `low_confidence_static` the n of highest confidence, and
   `low_confidence_dynamic` every masked position whose confidence is over
   the threshold if those are at least the schedule's count, else the n of
   highest confidence; the block is done when no mask is left;
3. the answer is cut at g tokens (the last block is run whole and cut).

"Kept K/V" are by construction those of final tokens here: every pass
recomputes every earlier block from its final tokens. A system with a cache
reaches the same only if it keeps, for a block, the K/V of one more pass over
the block's FINAL tokens (the commit pass).

Departures from the published code, and what is `assumed` (the config.json
has no key for any generation setting; each is a key of `published_keys`'s
result, so a check can state another value):

- `block_length` 4, `denoising_steps` 4, `remasking_strategy`
  low_confidence_dynamic, `confidence_threshold` 0.9, `mask_token_id` 151669
  are the -Chat checkpoints' convention. Not taken: blocks of 8-64 (other
  checkpoints of the family), fewer steps than positions, the static and
  sequential rules (implemented, not the default);
- ties in confidence go to the LOWEST position (the published code leaves
  it to its library's top-k);
- where fewer masks are left than the schedule's count, all of them are
  unmasked (the published static rule would pick unmasked positions too and
  overwrite them; it cannot happen at one token a pass);
- temperature 0 only (the argmax, confidence under the plain softmax): a
  draw has no reference value;
- stop words are the caller's: the tokens come back uncut by them;
- RMSNorm multiplies by its weight in float32; every expert is evaluated on
  every position and weighted by w_e or 0 (the same sum, no gather); ties in
  the router's top-k go to the lower index (`jax.lax.top_k`).

Switches a check uses to make the reference WRONG in one way each (all off
by default): `causal_mask` (Qwen3's own mask), `qk_norm` False,
`float32_statistics` False (router, attention softmax and — the caller's —
the final log-softmax in bfloat16, the nearest precision below).

The weights are the system's own tree (`models/llama.py:init_params`: (in,
out) matrices stacked over layers, experts (L, E, in, out)), as served.
This file has a byte-identical copy under `benchmarks/chip/reference/` (a
tier-1 test holds them together); it imports nothing from the package.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_PRECISION = "highest"


def published_keys(model_cfg) -> dict:
    """The reference's configuration from the system's `ModelConfig` (or
    anything with its attributes), under the published key names, and the
    generation settings (`assumed`: see the module's docstring)."""
    bd = model_cfg.block_diffusion
    return {
        "hidden_size": model_cfg.hidden_size,
        "num_hidden_layers": model_cfg.num_layers,
        "num_attention_heads": model_cfg.num_heads,
        "num_key_value_heads": model_cfg.num_kv_heads,
        "head_dim": model_cfg.head_dim,
        "rms_norm_eps": model_cfg.rms_norm_eps,
        "rope_theta": model_cfg.rope_theta,
        "num_experts": model_cfg.num_experts,
        "num_experts_per_tok": model_cfg.num_experts_per_tok,
        "norm_topk_prob": model_cfg.norm_topk_prob,
        "block_length": bd.block_length,
        "denoising_steps": bd.denoising_steps,
        "remasking_strategy": bd.remasking_strategy,
        "confidence_threshold": bd.confidence_threshold,
        "mask_token_id": bd.mask_token_id,
        # the switches that make it wrong in one way
        "causal_mask": False, "qk_norm": True, "float32_statistics": True,
    }


def f32(leaf) -> jax.Array:
    return jnp.asarray(leaf).astype(jnp.float32)


def rms_norm(x, weight, eps: float):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, positions, theta: float):
    """Rotate-half rope on (T, heads, D)."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * cos + rotated * sin


def _softmax(x, cfg: dict):
    """Softmax in float32 — in bfloat16 where the check asks for the
    nearest precision below."""
    if cfg["float32_statistics"]:
        return jax.nn.softmax(x, axis=-1)
    return jax.nn.softmax(x.astype(jnp.bfloat16), axis=-1).astype(jnp.float32)


def visible(cfg: dict, q_pos, k_pos):
    """(Tq, Tk) bool: may the query at q_pos see the key at k_pos."""
    if cfg["causal_mask"]:
        return k_pos[None, :] <= q_pos[:, None]
    b = cfg["block_length"]
    return k_pos[None, :] < ((q_pos // b + 1) * b)[:, None]


def attention(cfg: dict, w: dict, x, query_block: int):
    """Self-attention of one sequence under the block mask, (T, h) -> (T, h),
    `query_block` queries at a time (their scores are (heads, block, T))."""
    t = x.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = (x @ f32(w["wq"])).reshape(t, nh, d)
    k = (x @ f32(w["wk"])).reshape(t, nkv, d)
    v = (x @ f32(w["wv"])).reshape(t, nkv, d)
    if cfg["qk_norm"]:
        q = rms_norm(q, f32(w["q_norm"]), eps)
        k = rms_norm(k, f32(w["k_norm"]), eps)
    positions = jnp.arange(t)
    q = rope(q, positions, cfg["rope_theta"])
    k = rope(k, positions, cfg["rope_theta"])
    k = jnp.repeat(k, nh // nkv, axis=1)  # each kv head serves its group
    v = jnp.repeat(v, nh // nkv, axis=1)
    out = []
    for lo in range(0, t, query_block):
        qp = positions[lo:lo + query_block]
        scores = jnp.einsum("qhd,khd->hqk", q[lo:lo + query_block], k)
        scores = jnp.where(visible(cfg, qp, positions)[None],
                           scores * d ** -0.5, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", _softmax(scores, cfg), v))
    return jnp.concatenate(out).reshape(t, nh * d) @ f32(w["wo"])


def route(cfg: dict, router, x, forced=None):
    """(combine weights (T, E), chosen experts (T, k), margin (T,)): the
    renormalised softmax mass of the chosen experts, 0 elsewhere, and how
    far the k-th chosen probability lies above the best one left out.
    `forced` (T, k): the experts a served program chose (a row of -1: the
    reference's own); the weights are the reference's probabilities of
    them, and the margin how far the served choice lay under the
    reference's own k-th."""
    k = cfg["num_experts_per_tok"]
    logits = x @ router
    if not cfg["float32_statistics"]:
        logits = logits.astype(jnp.bfloat16).astype(jnp.float32)
    probs = _softmax(logits, cfg)
    top, experts = jax.lax.top_k(probs, k + 1)
    margin = top[:, k - 1] - top[:, k]
    top, experts = top[:, :k], experts[:, :k]
    if forced is not None:
        forced = jnp.asarray(forced)
        own = (forced < 0).all(-1, keepdims=True)
        picked = jnp.take_along_axis(probs, jnp.maximum(forced, 0), axis=1)
        margin = jnp.where(
            own[:, 0], margin,
            jnp.maximum(top[:, k - 1] - picked.min(-1), 0.0))
        experts = jnp.where(own, experts, forced)
        top = jnp.where(own, top, picked)
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    combine = jnp.sum(
        jax.nn.one_hot(experts, probs.shape[-1]) * top[..., None], axis=1)
    return combine, experts, margin


def forward(cfg: dict, params: dict, tokens, logit_positions=None, *,
            forced_experts=None, query_block: int = 256):
    """Logits (T, vocab) of every position of one sequence (or of
    `logit_positions` only) under the block mask, and each layer's routing.
    `forced_experts`: per layer the (T, k) experts a served program chose.
    Nothing is jitted as a whole: each tensor is widened to float32 where it
    is used, one expert at a time."""
    eps = cfg["rms_norm_eps"]
    layers = params["layers"]
    x = f32(jnp.asarray(params["embed"])[jnp.asarray(tokens)])
    routing = []
    with jax.default_matmul_precision(_PRECISION):
        for i in range(cfg["num_hidden_layers"]):
            attn = {name: leaf[i] for name, leaf in layers["attn"].items()}
            x = x + attention(
                cfg, attn, rms_norm(x, f32(layers["input_norm"][i]), eps),
                query_block)
            u = rms_norm(x, f32(layers["post_attn_norm"][i]), eps)
            moe = layers["moe"]
            combine, experts, margin = route(
                cfg, f32(moe["router"][i]), u,
                None if forced_experts is None else forced_experts[i])
            y = jnp.zeros_like(x)
            for e in range(cfg["num_experts"]):
                # every expert on every position, weighted by w_e or 0
                inner = jax.nn.silu(u @ f32(moe["gate"][i, e])) * (
                    u @ f32(moe["up"][i, e]))
                y = y + combine[:, e:e + 1] * (inner @ f32(moe["down"][i, e]))
            x = x + y
            routing.append({"experts": experts, "margin": margin})
        if logit_positions is not None:
            x = x[jnp.asarray(list(logit_positions))]
        x = rms_norm(x, f32(params["final_norm"]), eps)
        return x @ f32(params["lm_head"]), routing


def transfer_schedule(cfg: dict) -> list[int]:
    base, rem = divmod(cfg["block_length"], cfg["denoising_steps"])
    return [base + (s < rem) for s in range(cfg["denoising_steps"])]


def transfer(cfg: dict, confidence, masked, step: int) -> list[int]:
    """The positions of a block (indices into it) that pass `step` unmasks,
    from the confidences (float32, one a position) and which positions are
    masked."""
    where = [i for i, m in enumerate(masked) if m]
    n_s = transfer_schedule(cfg)[min(step, cfg["denoising_steps"] - 1)]
    n = min(n_s, len(where))
    strategy = cfg["remasking_strategy"]
    if strategy == "sequential":
        return where[:n]
    # by confidence, ties to the lowest position
    ranked = sorted(where, key=lambda i: (-float(confidence[i]), i))
    if strategy == "low_confidence_dynamic":
        high = [i for i in where
                if float(confidence[i]) > cfg["confidence_threshold"]]
        if len(high) >= n_s:
            return high
    elif strategy != "low_confidence_static":
        raise ValueError(f"unknown remasking_strategy {strategy!r}")
    return sorted(ranked[:n])


def generate(cfg: dict, params: dict, prompt, g: int, *,
             commit_pass: bool = True) -> dict:
    """`g` tokens after `prompt` by diffusion over blocks, temperature 0.
    Returns {"tokens": the g tokens, "passes": per token the pass of its
    block (from 0) that unmasked it, "logprobs": per token that pass's
    log-softmax row at its position (vocab,), "forward_passes": how many
    forwards a system with a cache would run, commit passes included,
    "threshold_tokens": tokens the threshold unmasked beyond the schedule's
    count}. Every pass is a full `forward` over prompt + finished blocks +
    the open block's current tokens, in a buffer of one length for the whole
    call (masks behind the open block, to p + g rounded up to 16: no query
    at or before the open block sees them, and the shapes repeat).

    `commit_pass` False is the WRONG procedure a check has to catch: a
    finished block stands in later passes as it stood in its LAST denoising
    pass — the positions that pass unmasked still masked — which is what a
    cache holds that keeps that pass's K/V."""
    b, mask_id = cfg["block_length"], cfg["mask_token_id"]
    prompt = [int(t) for t in prompt]
    p = len(prompt)
    done = p // b * b
    seq = prompt[:done]  # what later passes see of the finished blocks
    final = list(seq)
    total = -(-(-(-(p + g) // b) * b) // 16) * 16
    tokens, passes, logprobs = [], [], []
    n_forward = over = 0
    while len(tokens) < g:
        given = prompt[done:done + b] if done < p else []
        block = given + [mask_id] * (b - len(given))
        masked = [i >= len(given) for i in range(b)]
        unmasked_in = {}
        row_of = {}
        step = 0
        stale = list(block)
        while any(masked):
            behind = [mask_id] * (total - len(seq) - b)
            logits, _ = forward(cfg, params, seq + block + behind,
                                range(len(seq), len(seq) + b))
            n_forward += 1
            lp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
            x0 = lp.argmax(-1)
            conf = np.exp(lp[np.arange(b), x0].astype(np.float32))
            picked = transfer(cfg, conf, masked, step)
            n_s = transfer_schedule(cfg)[
                min(step, cfg["denoising_steps"] - 1)]
            over += len(picked) - min(n_s, sum(masked))
            stale = list(block)  # the block as this pass saw it
            for i in picked:
                block[i], masked[i] = int(x0[i]), False
                unmasked_in[i], row_of[i] = step, lp[i]
            step += 1
        n_forward += 1  # the commit pass (a cache's; nothing to compute here)
        for i in range(len(given), b):
            tokens.append(block[i])
            passes.append(unmasked_in[i])
            logprobs.append(row_of[i])
        seq = seq + (block if commit_pass else stale)
        final += block
        done += b
    return {"tokens": tokens[:g], "passes": passes[:g],
            "logprobs": logprobs[:g], "forward_passes": n_forward,
            "threshold_tokens": over, "sequence": final}
