"""Llama-family decoder (Llama 2/3, Mistral, Qwen2, TinyLlama) in pure JAX.

The reference stack never implements a model — it deploys vLLM images. The
TPU engine needs its own: a functional, scan-over-layers decoder whose
per-layer params are stacked along a leading axis so jit traces ONE layer
body (fast compiles, fixed shapes — XLA-friendly control flow instead of a
Python loop over 32 layers).

Every weight is an (in, out)-oriented matrix so the forward pass is plain
`x @ w` feeding the MXU — but for a projection whose result is split into
heads: its leaf is stored (heads, head_dim, in), the checkpoint's own (out,
in) with the head axis split, and the product contracts against it as stored
(`_mm_heads`). Stored (in, heads * head_dim), the reshape to heads rides on
the product and XLA slices every layer's leaf into a buffer of its own a
decode STEP and re-lays the whole stack a window — 0.9 GB a step at qwen3-8b
(PERF.md §6 PR 56; tools/weight_copies.py is the scan that shows it). Tensor
parallelism is expressed entirely by the PartitionSpecs in
parallel/sharding.py — no collective appears in this file (XLA/GSPMD inserts
them).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..engine.config import (
    STATE_CONV_TAPS, LayerSpec, ModelConfig, MupSpec)
from ..ops.attention import (
    apply_rope,
    attention_with_hist,
    causal_page_mask,
    gather_pages,
    masked_attention,
    paged_attention_with_staged,
    paged_attention_xla,
    write_kv_pages,
    write_kv_pages_blockwise,
)
from ..ops.kda_attention import (
    gdn_decode_attention,
    gdn_prefill_attention,
    kda_decode_attention,
    kda_decode_xla,
    kda_prefill_attention,
    kda_prefill_xla,
)
from ..ops.kda_short_conv import kda_short_conv
from ..ops.dsa_attention import (
    dsa_decode_attention,
    dsa_index_decode,
    dsa_prefill_attention,
    selection_indices,
)
from ..ops.mla_attention import (
    latent_attention_with_staged,
    latent_attention_xla,
    write_latent_pages,
    write_latent_pages_blockwise,
)
from ..ops.mla_attention_pallas import (
    mla_decode_attention,
    mla_prefill_attention,
)
from ..ops.moe_experts_pallas import moe_decode_experts, moe_prefill_experts
from ..ops.ssd_scan import (
    ssd_decode_step,
    ssd_decode_xla,
    ssd_prefill_scan,
    ssd_prefill_xla,
)
from ..ops.paged_attention_pallas import (
    paged_decode_attention,
    paged_decode_attention_sharded,
    paged_kv_write,
    paged_prefill_attention,
    paged_prefill_attention_sharded,
    paged_window_decode_attention,
    paged_window_prefill_attention,
)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# The leaves stored (L, heads, head_dim, in), by stack of the tree — what
# `_mm_heads` contracts against; every other matrix is (L, in, out).
HEADS_MAJOR = {"attn": ("wq", "wk", "wv", "wq_b", "wk_b", "wv_b"),
               "attn_window": ("wq", "wk", "wv"),
               "attn_latent": ("wq", "wk_b", "wv_b"),
               "attn_index": ("wq_b",)}


def contraction_axis(stack: str, name: str) -> int:
    """The axis of leaf `name` in stack `stack` of the tree that its product
    contracts: the last of a `HEADS_MAJOR` leaf, the one before the last of
    an (in, out) matrix. What follows a leaf's layout without naming its
    axes — the int8 scale's reduction and its spec (models/quantization.py)
    — asks here."""
    return -1 if name in HEADS_MAJOR.get(stack, ()) else -2


def heads_major(w: jax.Array, heads: int) -> jax.Array:
    """A stack of (in, heads * head_dim) matrices (..., in, out) as the leaf
    a head-split projection is stored as, (..., heads, head_dim, in): the
    same values, moved (`init_params` draws the matrix it always drew)."""
    *lead, n_in, out = w.shape
    return jnp.swapaxes(w, -1, -2).reshape(*lead, heads, out // heads, n_in)


# A test's tap on the routing of an MoE model's programs: when set (before a
# program is TRACED) to a host function, `forward` and `decode_window_step`
# hand it, per execution, (token ids, positions, each expert layer's chosen
# experts stacked (Lm, tokens, k)). None — always, outside
# testing/reference_check.py — traces nothing: the served programs are what
# they are without it.
ROUTING_TAP = None


def _tap_routing(token_ids, positions, layers_routing: list) -> None:
    if ROUTING_TAP is not None and layers_routing:
        experts = jnp.stack(
            [r["experts"].reshape(-1, r["experts"].shape[-1])
             for r in layers_routing])
        jax.debug.callback(
            ROUTING_TAP, token_ids.reshape(-1), positions.reshape(-1),
            experts)


# The same for the passes of a window of block passes (generation by
# diffusion over blocks; engine/model_runner.py:_build_bd_window_fn): per
# pass, IN ORDER, (block of the window, pass of the block, positions (B, T),
# the block's tokens before the pass, which it unmasked, the tokens after,
# each expert layer's chosen experts (Lm, B * T, k)); the runner tells the
# tap which requests a window's rows are when it dispatches it.
# testing/reference_check.py:BlockTap.
BD_TAP = None


def tap_block_pass(blk, pas, positions, before, picked, after,
                   layers_routing: list | None) -> None:
    if BD_TAP is not None:
        experts = jnp.stack(
            [r["experts"].reshape(-1, r["experts"].shape[-1])
             for r in layers_routing]) if layers_routing else jnp.zeros(
                 (0, before.size, 1), jnp.int32)
        jax.debug.callback(BD_TAP, blk, pas, positions, before, picked,
                           after, experts, ordered=True)


# The same for the recurrence of ONE layer that holds state (`STATE_TAP.layer`):
# per execution, (the pool index each token's row writes its state slot at
# — 0, the null slot, for padding —, positions, and q, k, v, g, beta as the
# program hands them to the scan). testing/reference_check.py:StateTap.
STATE_TAP = None


def _state_tap(layer: int, slots, positions):
    """What `_kda_attention` hands the scan's inputs to, or None."""
    if STATE_TAP is None or STATE_TAP.layer != layer:
        return None
    return lambda *fed: jax.debug.callback(
        STATE_TAP, slots, positions.reshape(-1), *fed)


# The same for the SELECTION of a model whose latent layers attend over
# selected keys (LayerSpec.indexer; docs/55-selected-keys.md): per execution
# and layer that owns an indexer, (the layer's index among those, token ids,
# positions, the selected key positions (tokens, K) in ascending order, -1
# past a query's count). testing/reference_check.py:SelectionTap.
SELECTION_TAP = None


def _tap_selection(layer: int, token_ids, positions, chosen) -> None:
    if SELECTION_TAP is not None:
        jax.debug.callback(
            SELECTION_TAP, jnp.int32(layer), token_ids.reshape(-1),
            positions.reshape(-1), chosen)


def _tap_step_selection(layer: int, token_ids, positions, chosen: tuple,
                        s_ctx: int, hist_len) -> None:
    """A decode step's selection (`dsa_index_decode`'s indices and which are
    real) as the tap takes it: positions — pool index s is position s, staged
    slot w position hist_len + w — ascending, -1 last."""
    if SELECTION_TAP is None:
        return
    at, real = chosen
    last = jnp.iinfo(jnp.int32).max
    at = jnp.where(at < s_ctx, at, hist_len[:, None] + at - s_ctx)
    at = jnp.sort(jnp.where(real, at, last), axis=1)
    _tap_selection(layer, token_ids, positions, jnp.where(at == last, -1, at))


# The same for the ROUTER of one expert layer (`ROUTER_TAP.moe_layer`, its
# index among the expert layers): per execution, (the router's input (N, h)
# as the program hands it to the router's matmul, the experts it chose
# (N, k)). testing/reference_check.py:RouterTap.
ROUTER_TAP = None


def init_params(cfg: ModelConfig, rng: jax.Array) -> dict:
    """Random-init a stacked param tree (tests + benchmarks without weights)."""
    if cfg.mla:
        return _init_params_mla(cfg, rng)
    if cfg.tree_by_kind:
        return _init_params_by_kind(cfg, rng)
    L = cfg.num_layers
    h, hd = cfg.hidden_size, cfg.head_dim
    nh, nkv, it = cfg.num_heads, cfg.num_kv_heads, cfg.intermediate_size
    dt = _dtype(cfg)
    keys = iter(jax.random.split(rng, 16))

    def w(key, *shape, scale=None):
        scale = scale if scale is not None else shape[-2] ** -0.5
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dt)

    if cfg.num_experts:
        # Mixtral-family sparse MoE: per-layer router + E expert SwiGLUs
        e = cfg.num_experts
        mlp = {
            "router": w(next(keys), L, h, e),
            "gate": w(next(keys), L, e, h, it),
            "up": w(next(keys), L, e, h, it),
            "down": w(next(keys), L, e, it, h),
        }
        mlp_key = "moe"
    else:
        mlp = {
            "gate": w(next(keys), L, h, it),
            "up": w(next(keys), L, h, it),
            "down": w(next(keys), L, it, h),
        }
        mlp_key = "mlp"
    params: dict[str, Any] = {
        "embed": w(next(keys), cfg.vocab_size, h, scale=0.02),
        "layers": {
            "attn": {
                # (heads, head_dim, in): see the module docstring
                "wq": heads_major(w(next(keys), L, h, nh * hd), nh),
                "wk": heads_major(w(next(keys), L, h, nkv * hd), nkv),
                "wv": heads_major(w(next(keys), L, h, nkv * hd), nkv),
                "wo": w(next(keys), L, nh * hd, h),
            },
            mlp_key: mlp,
            "input_norm": jnp.ones((L, h), dt),
            "post_attn_norm": jnp.ones((L, h), dt),
        },
        "final_norm": jnp.ones((h,), dt),
    }
    if cfg.attention_bias:
        params["layers"]["attn"]["bq"] = jnp.zeros((L, nh * hd), dt)
        params["layers"]["attn"]["bk"] = jnp.zeros((L, nkv * hd), dt)
        params["layers"]["attn"]["bv"] = jnp.zeros((L, nkv * hd), dt)
    if cfg.qk_norm:
        params["layers"]["attn"]["q_norm"] = jnp.ones((L, hd), dt)
        params["layers"]["attn"]["k_norm"] = jnp.ones((L, hd), dt)
    if cfg.qk_norm_flat:
        params["layers"]["attn"]["q_norm"] = jnp.ones((L, nh * hd), dt)
        params["layers"]["attn"]["k_norm"] = jnp.ones((L, nkv * hd), dt)
    if cfg.sandwich_norms or cfg.post_norms_only:
        init = jnp.zeros if cfg.rms_norm_add_one else jnp.ones
        params["layers"]["attn_out_norm"] = init((L, h), dt)
        params["layers"]["ffw_out_norm"] = init((L, h), dt)
    if cfg.post_norms_only:
        # olmo2 carries NO pre-norms at all
        del params["layers"]["input_norm"]
        del params["layers"]["post_attn_norm"]
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(next(keys), h, cfg.vocab_size, scale=0.02)
    return params


def _split_kv_b(w: jax.Array, heads: int, nope: int) -> dict:
    """W_kv_b drawn (..., rank, heads * (nope + v)), each head k_nope ‖ v, as
    the two leaves `_mla_attention` reads — "wk_b" (..., heads, nope, rank),
    W_UK, and "wv_b" (..., heads, v, rank), W_UV: the checkpoint's (out, in)
    rows, a head's split where its two products part. One leaf sliced for
    both is copied, a layer's slice a step (PERF.md §6 PR 56)."""
    w = heads_major(w, heads)
    return {"wk_b": w[..., :nope, :], "wv_b": w[..., nope:, :]}


def _init_params_mla(cfg: ModelConfig, rng: jax.Array) -> dict:
    """The tree of a DeepSeek-V3-family model (Kanana-2): latent attention
    in every layer; a dense SwiGLU in the first `first_k_dense_replace`
    layers and experts in the rest, so the MLP leaves are TWO stacks —
    "mlp" over the dense layers, "moe" over the expert layers (`_layer_params`
    indexes each by its own count; `_grouped_mm`'s L*E groups are the expert
    layers') — beside the attention leaves and norms stacked over all L.

    attn: wq (L, heads, nope+rope, h), each head nope ‖ rope (`HEADS_MAJOR`);
    wkv_a (L, h, rank+rope): the kv latent ‖ the shared rope key; kv_a_norm
    (L, rank); W_kv_b, each head k_nope ‖ v, as wk_b (L, heads, nope, rank)
    and wv_b (L, heads, v, rank) (`_split_kv_b`); wo (L, heads*v, h). Rope
    rows are in rotate-half order (a checkpoint's interleaved pairs are
    de-interleaved at load, models/loader.py).
    moe: router (Lm, h, E), bias (Lm, E) float32 — the selection bias, drawn
    NON-zero here: a zero bias would leave the rule that it moves the choice
    and never the weight untested; small (std 0.02 beside sigmoid scores
    that spread ~0.2 a token) as a trained router's is — it exists to
    BALANCE the experts, and at std 0.1 it concentrated a random router's
    choices on the experts it favoured (18% of experts touched a decode
    step where independent choices give 28%; my chip run, PR 31) —
    gate/up/down (Lm, E, ...), and "shared",
    one SwiGLU of n_shared_experts x the experts' width."""
    L, h, nh = cfg.num_layers, cfg.hidden_size, cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r, e = cfg.kv_lora_rank, cfg.num_experts
    k_dense = cfg.first_k_dense_replace if e else L
    lm, it, mit = L - k_dense, cfg.intermediate_size, cfg.expert_intermediate_size
    dt = _dtype(cfg)
    keys = iter(jax.random.split(rng, 24))
    # (the leaves this docstring's tree did not have are drawn from keys of
    # their own: the older models' draws stay what they were)
    more = iter(jax.random.split(jax.random.fold_in(rng, 55), 16))

    def w(*shape, scale=None, keys=keys):
        scale = scale if scale is not None else shape[-2] ** -0.5
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dt)

    def experts(n, *shape):
        """A stack of expert leaves: a held share (`num_experts_held`) is
        drawn one layer at a time — the float32 draw that is live is one
        layer's (0.8 GB at 16 experts of 6144 x 2048), not the leaf's."""
        if not cfg.num_experts_held:
            return w(n, e, *shape)
        layer_keys = jax.random.split(next(more), n)
        shape = (cfg.held_experts, *shape)

        def draw(i, buf):
            piece = jax.random.normal(layer_keys[i], shape, jnp.float32)
            return jax.lax.dynamic_update_index_in_dim(
                buf, (piece * shape[-2] ** -0.5).astype(dt), i, 0)

        return jax.lax.fori_loop(0, n, draw, jnp.zeros((n, *shape), dt))

    # a query latent (`q_lora_rank`): wq_a (L, h, rank), q_a_norm (L, rank),
    # wq_b (L, heads, nope + rope, rank) in wq's place
    rq = cfg.q_lora_rank
    layers: dict[str, Any] = {
        "attn": {
            **({"wq_a": w(L, h, rq, keys=more),
                "q_a_norm": jnp.ones((L, rq), dt),
                "wq_b": heads_major(w(L, rq, nh * (dn + dr)), nh)} if rq else
               {"wq": heads_major(w(L, h, nh * (dn + dr)), nh)}),
            "wkv_a": w(L, h, r + dr),
            "kv_a_norm": jnp.ones((L, r), dt),
            **_split_kv_b(w(L, r, nh * (dn + dv)), nh, dn),
            "wo": w(L, nh * dv, h),
        },
        "input_norm": jnp.ones((L, h), dt),
        "post_attn_norm": jnp.ones((L, h), dt),
    }
    if cfg.index_layers:
        # the indexers, a stack over the layers that own one
        # (LayerSpec.indexer "full"): wq_b (Lf, Hi, D, q rank) from the
        # query latent, wk (Lf, h, D) and the LayerNorm's weight and bias
        # over the key, w_proj (Lf, h, Hi) the weight a head and token. The
        # first `qk_rope_head_dim` of D are roped (rotate-half order)
        lf, hi, di = (len(cfg.index_layers), cfg.index_n_heads,
                      cfg.index_head_dim)
        layers["attn_index"] = {
            "wq_b": heads_major(w(lf, rq, hi * di, keys=more), hi),
            "wk": w(lf, h, di, keys=more),
            # (not ones and zeros: a norm left out, or its bias, has to show)
            "k_norm": (1.0 + 0.1 * jax.random.normal(
                next(more), (lf, di), jnp.float32)).astype(dt),
            "k_norm_bias": (0.1 * jax.random.normal(
                next(more), (lf, di), jnp.float32)).astype(dt),
            "w_proj": w(lf, h, hi, keys=more),
        }
    if k_dense:
        layers["mlp"] = {"gate": w(k_dense, h, it), "up": w(k_dense, h, it),
                         "down": w(k_dense, it, h)}
    if lm:
        sit = cfg.n_shared_experts * mit
        layers["moe"] = {
            "router": w(lm, h, e),
            "bias": 0.02 * jax.random.normal(next(keys), (lm, e), jnp.float32),
            "gate": experts(lm, h, mit), "up": experts(lm, h, mit),
            "down": experts(lm, mit, h),
        }
        if sit:
            layers["moe"]["shared"] = {
                "gate": w(lm, h, sit), "up": w(lm, h, sit),
                "down": w(lm, sit, h)}
    params = {"embed": w(cfg.vocab_size, h, scale=0.02), "layers": layers,
              "final_norm": jnp.ones((h,), dt)}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(h, cfg.vocab_size, scale=0.02)
    return params


def _init_params_by_kind(cfg: ModelConfig, rng: jax.Array) -> dict:
    """The tree of a model the flat tree cannot say
    (`ModelConfig.tree_by_kind`): window and full attention layers with
    their own head counts, a per-head output gate, a dense SwiGLU or a
    share of the experts a layer, a shared expert.

    The attention leaves are TWO stacks where the kinds' head counts differ
    — "attn" over the full layers, "attn_window" over the window layers:
    wq (L_kind, heads_kind, D, h), wk, wv (L_kind, kvH, D, h), wo
    (L_kind, heads_kind * D, h), wg (L_kind, h, heads_kind), the gate — and
    one "attn" stack over all L where they agree. The MLP leaves are two
    stacks as `_init_params_mla`'s: "mlp" over the dense layers, "moe" over
    the expert layers, whose router is `num_experts` wide (the published
    width) while gate/up/down hold the `held_experts` this program was
    given (ModelConfig.num_experts_held); "shared" is one ungated SwiGLU.
    `_layer_params` indexes each stack by the layer's own count. Layers
    whose mixer is linear (LayerSpec.holds_state) are a third stack,
    "attn_linear" (`attn_linear` below), and layers whose pages hold one
    latent a token (LayerSpec.latent) a fourth, "attn_latent"
    (`attn_latent` below: `_init_params_mla`'s attention leaves, and the
    per-head output gate where the model has one). A layer with
    state-space heads BESIDE its attention heads (LayerSpec.recurrence
    "ssd") owns leaves in its attention stack AND in a fifth, "attn_ssm"
    (`attn_ssm` below)."""
    unsaid = [name for name in (
        "attention_bias", "qk_norm", "sandwich_norms")
        if getattr(cfg, name)]
    if unsaid:
        raise ValueError(
            f"{cfg.model}: a param tree stacked by layer kind has no leaves "
            f"for {', '.join(unsaid)}")
    h, hd, nkv = cfg.hidden_size, cfg.head_dim, cfg.num_kv_heads
    specs = cfg.layer_specs
    it, mit = cfg.intermediate_size, cfg.expert_intermediate_size
    e, held = cfg.num_experts, cfg.held_experts
    dt = _dtype(cfg)
    keys = iter(jax.random.split(rng, 32))

    mup = cfg.mup or MupSpec()

    def w(*shape, scale=None, over=1.0):
        # `over`: the multiplier the forward pass scales this matrix's
        # output by (MupSpec) — the draw is that much wider, so that the
        # SCALED output is what the standard init's would be (a trained
        # maximal-update checkpoint's weights have grown likewise; with the
        # plain init every branch would vanish beside the residual and no
        # check could tell a branch left out)
        scale = (scale if scale is not None else shape[-2] ** -0.5) / over
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dt)

    def attn(n, nh):
        leaves = {"wq": heads_major(
                      w(n, h, nh * hd, over=mup.attention_in), nh),
                  "wk": heads_major(
                      w(n, h, nkv * hd, over=mup.attention_in * mup.key),
                      nkv),
                  "wv": heads_major(
                      w(n, h, nkv * hd, over=mup.attention_in), nkv),
                  "wo": w(n, nh * hd, h, over=mup.attention_out)}
        if cfg.attn_output_gate:
            # one gate a head, or one a head and channel
            leaves["wg"] = w(
                n, h, nh * (hd if cfg.attn_output_gate == "element" else 1))
        if cfg.qk_norm_flat:
            # OLMo-2's: one weight a channel of the FLAT projection
            leaves["q_norm"] = jnp.ones((n, nh * hd), dt)
            leaves["k_norm"] = jnp.ones((n, nkv * hd), dt)
        return leaves

    def attn_linear(n):
        """The delta-rule layers' stack (`_kda_attention`): q and k
        projections over heads x Dk keys, v and o over heads x Dv values;
        the decay gate's (to a key channel, or to a head:
        `linear_gate_per`) and the output gate's (to the values)
        projections, low-rank pairs or full rank; the step size's
        projection; the depthwise convolution's taps over q | k | v (tap 3
        multiplies the current token); per head the log of the decay's
        rate (drawn as log U(1, 16)) and per gate the bias of its softplus
        (the inverse softplus of a step drawn log-uniform in [0.001, 0.1],
        both as the gated delta rule's public implementations initialise
        them: decays from barely any to e^-1.6 a token at a zero gate
        input; under the bounded gate the bias that gives the same
        decays); the output norm's weight, shared by the heads."""
        lh, dk, dv, r = (cfg.linear_num_heads, cfg.linear_head_dim,
                         cfg.linear_value_dim, cfg.linear_gate_rank)
        taps = STATE_CONV_TAPS
        # gates a token: one a key channel, or one a head
        gw = lh * dk if cfg.linear_gate_per == "channel" else lh
        leaves = {
            "wq": w(n, h, lh * dk), "wk": w(n, h, lh * dk),
            "wv": w(n, h, lh * dv), "wo": w(n, lh * dv, h),
            # the decay gate's and the output gate's projections: low-rank
            # pairs, or (linear_gate_rank 0) one full-rank matrix each
            **({"wf_a": w(n, h, r), "wf_b": w(n, r, gw),
                "wg_a": w(n, h, r), "wg_b": w(n, r, lh * dv)} if r else
               {"wf": w(n, h, gw), "wg": w(n, h, lh * dv)}),
            "wb": w(n, h, lh),
            "conv": w(n, taps, lh * (2 * dk + dv), scale=taps ** -0.5),
            "A_log": jnp.log(jax.random.uniform(
                next(keys), (n, lh), jnp.float32, 1.0, 16.0)),
            "o_norm": jnp.ones((n, dv), dt),
        }
        step = jnp.exp(jax.random.uniform(
            next(keys), (n, gw), jnp.float32,
            jnp.log(1e-3), jnp.log(1e-1)))
        leaves["dt_bias"] = step + jnp.log(-jnp.expm1(-step))
        if cfg.linear_gate_form == "bounded":
            # the bounded gate, bound * sigmoid(rate (a + dt_bias)): the
            # bias that gives the SAME decay at a zero gate input as the
            # softplus form's, -rate x step (from barely any to e^-1.6 a
            # token) — logit(rate step / |bound|) / rate. The softplus
            # form's bias would leave every decay at ~0 (rate x its -2..-7)
            rate = jnp.repeat(jnp.exp(leaves["A_log"]), gw // lh, axis=-1)
            share = rate * step / abs(cfg.linear_gate_lower_bound)
            leaves["dt_bias"] = (jnp.log(share) - jnp.log1p(-share)) / rate
        return leaves

    def attn_ssm(n):
        """The state-space branches' stack (`_ssm_mixer`): the input
        projection over z | x | B | C | dt (each span drawn wider by its
        multiplier, see `w`); the depthwise convolution's taps over x | B |
        C (tap 3 multiplies the current token) and its bias (uniform in
        +-taps^-0.5, a convolution's usual init); per head the log of the
        rate (log U(1, 16)), the skip weight D (ones) and the bias of the
        step's softplus (the inverse softplus of a step drawn log-uniform
        in [0.001, 0.1]) as Mamba-2's public implementation initialises
        them, in float32; the gated norm's weight; the output projection."""
        hs, gn = cfg.ssm_num_heads, cfg.ssm_num_groups * cfg.ssm_state_dim
        inner, taps = cfg.ssm_inner, STATE_CONV_TAPS
        spans = (inner, inner, gn, gn, hs)
        step = jnp.exp(jax.random.uniform(
            next(keys), (n, hs), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        return {
            "w_in": jnp.concatenate(
                [w(n, h, width, over=mup.ssm_in * m)
                 for width, m in zip(spans, mup.ssm)], axis=-1),
            "conv": w(n, taps, cfg.ssm_conv_dim, scale=taps ** -0.5),
            "conv_bias": jax.random.uniform(
                next(keys), (n, cfg.ssm_conv_dim), jnp.float32,
                -taps ** -0.5, taps ** -0.5).astype(dt),
            "A_log": jnp.log(jax.random.uniform(
                next(keys), (n, hs), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((n, hs), jnp.float32),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "norm": jnp.ones((n, inner), dt),
            "w_out": w(n, inner, h, over=mup.ssm_out),
        }

    def attn_latent(n, nh):
        """The latent layers' stack (`_mla_attention`; the leaves of
        `_init_params_mla`'s "attn"), and with `attn_output_gate` "head"
        the gate's projection wg (n, h, heads)."""
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        rank = cfg.kv_lora_rank
        if cfg.attn_output_gate not in ("", "head"):
            raise ValueError(
                f"{cfg.model}: attn_output_gate={cfg.attn_output_gate!r} is "
                "not implemented for latent layers (a gate a head is: it "
                "commutes with the absorbed value projection)")
        leaves = {
            "wq": heads_major(w(n, h, nh * (dn + dr)), nh),
            "wkv_a": w(n, h, rank + dr),
            "kv_a_norm": jnp.ones((n, rank), dt),
            **_split_kv_b(w(n, rank, nh * (dn + dv)), nh, dn),
            "wo": w(n, nh * dv, h)}
        if cfg.attn_output_gate:
            leaves["wg"] = w(n, h, nh)
        return leaves

    L = cfg.num_layers
    # OLMo-2's block norms the sublayers' OUTPUTS and nothing else
    layers: dict[str, Any] = {
        name: jnp.ones((L, h), dt) for name in (
            ("attn_out_norm", "ffw_out_norm") if cfg.post_norms_only
            else ("input_norm", "post_attn_norm"))}
    if cfg.attn_stacks_by_kind:
        for name, idx in (("attn", cfg.full_layers),
                          ("attn_window", cfg.window_layers)):
            if idx:
                layers[name] = attn(len(idx), specs[idx[0]].num_heads)
        if cfg.linear_layers:
            layers["attn_linear"] = attn_linear(len(cfg.linear_layers))
        if cfg.latent_layers:
            layers["attn_latent"] = attn_latent(
                len(cfg.latent_layers),
                specs[cfg.latent_layers[0]].num_heads)
    else:
        layers["attn"] = attn(L, specs[0].num_heads)
    if cfg.ssm_layers:
        layers["attn_ssm"] = attn_ssm(len(cfg.ssm_layers))

    def w_by_layer(n, *shape):
        """A stacked leaf drawn one layer at a time into its buffer: the
        float32 draw that is live is one layer's (0.8 GB at 64 experts of
        3072 x 1024), not the whole leaf's (6.4 GB beside 10.6 GB of
        weights on a 16 GB chip)."""
        layer_keys = jax.random.split(next(keys), n)
        scale = shape[-2] ** -0.5

        def draw(i, buf):
            piece = jax.random.normal(layer_keys[i], shape, jnp.float32)
            return jax.lax.dynamic_update_index_in_dim(
                buf, (piece * scale).astype(dt), i, 0)

        return jax.lax.fori_loop(0, n, draw, jnp.zeros((n, *shape), dt))

    lm = cfg.num_moe_layers
    if L - lm:
        layers["mlp"] = {"gate": w(L - lm, h, it, over=mup.mlp_gate),
                         "up": w(L - lm, h, it),
                         "down": w(L - lm, it, h, over=mup.mlp_down)}
    if lm:
        layers["moe"] = {
            "router": w(lm, h, e),
            "gate": w_by_layer(lm, held, h, mit),
            "up": w_by_layer(lm, held, h, mit),
            "down": w_by_layer(lm, held, mit, h),
        }
        sit = cfg.n_shared_experts * mit
        if sit:
            layers["moe"]["shared"] = {
                "gate": w(lm, h, sit), "up": w(lm, h, sit),
                "down": w(lm, sit, h)}
    params = {"embed": w(cfg.vocab_size, h, scale=0.02), "layers": layers,
              "final_norm": jnp.ones((h,), dt)}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(h, cfg.vocab_size, scale=0.02,
                              over=mup.lm_head)
    if lm and cfg.moe_selection_bias:
        # the selection bias, non-zero and small (see _init_params_mla;
        # drawn last: the older trees' draws stay what they were)
        layers["moe"]["bias"] = 0.02 * jax.random.normal(
            next(keys), (lm, e), jnp.float32)
    return params


def rms_norm(
    x: jax.Array, weight: jax.Array, eps: float, add_one: bool = False,
    scale_f32: bool = False,
) -> jax.Array:
    """Llama convention: normalize, cast to input dtype, scale by weight.
    Gemma (add_one): weights are stored as (w - 1) and the scale by (1 + w)
    happens in float32 BEFORE the downcast. OLMo-2 (scale_f32): plain
    weights, but the multiply ALSO happens in float32 before the downcast
    (Olmo2RMSNorm) — in bf16 these orderings differ by ulps, and each
    matches its HF reference bit-for-bit."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    if add_one:
        return (normed * (1.0 + weight.astype(jnp.float32))).astype(dt)
    if scale_f32:
        return (normed * weight.astype(jnp.float32)).astype(dt)
    return normed.astype(dt) * weight


def _mm(x: jax.Array, w) -> jax.Array:
    """x @ w where w may be an int8 weight-only quantized leaf
    ({"q": int8 (…, in, out), "s": f32 (…, 1, out)},
    models/quantization.py). The HBM read is the int8 tensor; the cast and
    per-channel rescale fuse into the matmul epilogue."""
    if isinstance(w, dict):
        return (x @ w["q"].astype(x.dtype)) * w["s"].astype(x.dtype)
    return x @ w


def _mm_heads(x: jax.Array, w) -> jax.Array:
    """(..., in) times a leaf stored (heads, head_dim, in) -> (..., heads,
    head_dim): the product contracts against the leaf as it lies, so its
    fusion reads the stacked leaf in place. An int8 leaf's "s" is (heads,
    head_dim), one scale an output channel as `_mm`'s."""
    if isinstance(w, dict):
        return (jnp.einsum("...h,ndh->...nd", x, w["q"].astype(x.dtype))
                * w["s"].astype(x.dtype))
    return jnp.einsum("...h,ndh->...nd", x, w)


def _activation(cfg: ModelConfig):
    if cfg.hidden_act == "silu":
        return jax.nn.silu
    if cfg.hidden_act == "gelu_tanh":  # Gemma GeGLU
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown hidden_act {cfg.hidden_act!r}")


def _embed(cfg: ModelConfig, params: dict, token_ids: jax.Array) -> jax.Array:
    x = params["embed"][token_ids].astype(_dtype(cfg))
    if cfg.scale_embeddings:  # Gemma: sqrt(h) in the embedding dtype
        x = x * jnp.asarray(cfg.hidden_size**0.5, _dtype(cfg))
    if cfg.mup is not None:
        x = x * jnp.asarray(cfg.mup.embedding, _dtype(cfg))
    return x


def init_kv_cache(
    cfg: ModelConfig, num_blocks: int, block_size: int,
    dtype: Any | None = None, num_window_blocks: int | None = None,
    state_slots: int = 0,
) -> tuple[jax.Array, ...]:
    """Paged pool: a TUPLE of per-layer (2, num_blocks, block_size, kvH, D)
    arrays (a latent layer's: (1, num_blocks, block_size, latent_width); one
    that owns an indexer: {"kv": that, "idx": (1, num_blocks, block_size,
    index_head_dim)}, an index key a token beside the latent), NOT one stacked
    array. Per-layer leaves let jit donation alias
    each layer's pool in place; a stacked pool updated inside a scan forces
    XLA to hold a second full-pool buffer (observed +9.8 GiB on a
    utilization-sized pool — an instant OOM).

    With `num_window_blocks` the pages are TWO GROUPS (engine/kv_cache.py):
    a full-attention layer's array holds `num_blocks` pages, a window
    layer's `num_window_blocks` — it only ever holds a row's window and
    what is in flight. Without it every layer holds `num_blocks` (a window
    layer then masks a context it holds whole: tests, the XLA oracle).

    A layer that HOLDS STATE (LayerSpec.holds_state) has no pages: its leaf
    is {"s": (2 * state_slots, H, D, D) float32, "conv": (2 * state_slots,
    taps - 1, 3 * H * D)} — the recurrent state and the convolution's tail
    of every seat (slot 0: the null slot padding rows use), in two BANKS:
    a dispatch reads a row's slot in one bank and writes it in the other
    (engine/kv_cache.py:StateSlots). The geometry is the RECURRENCE's
    (`ModelConfig.state_geometry`): a state-space branch's is (H, P, N) and x | B | C
    channels. A layer that holds pages AND state (LayerSpec.recurrence
    "ssd") has both: its leaf is {"kv": the page array, "s": ..., "conv":
    ...}."""
    dt = jnp.dtype(dtype) if dtype is not None else _dtype(cfg)

    def pages(spec):
        if spec.latent:
            # a latent layer: ONE plane, a token's latent
            # (ops/mla_attention.py), no second copy of it as "V"
            latents = jnp.zeros(
                (1, num_blocks, block_size, cfg.latent_width), dt)
            if spec.indexer == "full":
                # ... and a layer that owns an indexer one index key a
                # token beside it, under the same block table
                # (ops/dsa_attention.py)
                return {"kv": latents, "idx": jnp.zeros(
                    (1, num_blocks, block_size, cfg.index_head_dim), dt)}
            return latents
        return jnp.zeros((
            2,
            num_window_blocks if spec.window and num_window_blocks
            else num_blocks,
            block_size, cfg.pool_kv_heads, cfg.head_dim), dt)

    def state(spec):
        shape, channels = cfg.state_geometry(spec.recurrence)
        return {"s": jnp.zeros((2 * state_slots, *shape), jnp.float32),
                "conv": jnp.zeros(
                    (2 * state_slots, STATE_CONV_TAPS - 1, channels),
                    _dtype(cfg))}

    return tuple(
        pages(spec) if not spec.holds_state else
        state(spec) if not spec.holds_pages else
        {"kv": pages(spec), **state(spec)}
        for spec in cfg.layer_specs)


def page_pool(kv_layer):
    """The page array of a layer's cache leaf: the leaf itself, or the
    "kv" of a layer that holds state beside its pages (None: the layer
    holds state alone)."""
    return kv_layer.get("kv") if isinstance(kv_layer, dict) else kv_layer


def _state_pools(kv_layer: dict) -> dict:
    """The state pools of a layer's cache leaf."""
    return {"s": kv_layer["s"], "conv": kv_layer["conv"]}


def _page_tokens(cfg: ModelConfig, kv_caches) -> int:
    """Tokens a page holds, read off the first layer that holds pages
    (axis 2 of a K/V pool and of a latent pool alike)."""
    return page_pool(kv_caches[cfg.page_layers[0]]).shape[2]


def _group(x, window: int):
    """Layer kind's part of an input that exists once a PAGE GROUP — a
    (full, window) pair of block tables, written-span ids or slots — or the
    one array every layer shares (a model with one group; a window model
    whose layers all hold the whole context)."""
    return x[1 if window else 0] if isinstance(x, tuple) else x


def lora_module_dims(cfg: ModelConfig) -> dict[str, tuple[int, int]]:
    """(in, out) dims per PEFT target-module name. MoE models expose only the
    attention projections (per-expert MLP LoRA would need per-expert deltas —
    the MoE path never consults the adapter tree, so advertising mlp modules
    there would be a silent no-op)."""
    if cfg.latent_layers:
        raise ValueError(
            "LoRA adapters are not implemented for multi-head latent "
            f"attention ({cfg.model}): its projections are wq, wkv_a, wk_b, "
            "wv_b")
    h, hd = cfg.hidden_size, cfg.head_dim
    nh, nkv, it = cfg.num_heads, cfg.num_kv_heads, cfg.intermediate_size
    dims = {
        "q_proj": (h, nh * hd),
        "k_proj": (h, nkv * hd),
        "v_proj": (h, nkv * hd),
        "o_proj": (nh * hd, h),
    }
    if not cfg.num_experts:
        dims |= {
            "gate_proj": (h, it),
            "up_proj": (h, it),
            "down_proj": (it, h),
        }
    return dims


def init_lora_params(cfg: ModelConfig, lora_cfg) -> dict:
    """Stacked adapter buffers: per target module A (n_slots, L, in, r) and
    B (n_slots, L, r, out), plus per-slot "scale" (n_slots,) = alpha/r. Slot
    0 is the reserved all-zeros base adapter, so a batch row with no adapter
    computes delta 0 through the exact same program — adapter selection is a
    per-row gather, never a recompile (SURVEY §7.3 hard part 3)."""
    n, r, L = lora_cfg.num_slots, lora_cfg.max_lora_rank, cfg.num_layers
    dt = _dtype(cfg)
    dims = lora_module_dims(cfg)
    unknown = [m for m in lora_cfg.target_modules if m not in dims]
    if unknown:
        raise ValueError(
            f"unknown LoRA target modules {unknown}; supported: "
            f"{sorted(dims)}"
        )
    tree: dict = {
        name: {
            "A": jnp.zeros((n, L, din, r), dt),
            "B": jnp.zeros((n, L, r, dout), dt),
        }
        for name, (din, dout) in dims.items()
        if name in lora_cfg.target_modules
    }
    tree["scale"] = jnp.zeros((n,), jnp.float32)
    return tree


def _lora_delta(
    x: jax.Array,  # (B, T, in) — the projection's input
    mod: dict,  # {"A": (n, in, r), "B": (n, r, out)} — this layer's slice
    idx: jax.Array,  # (B,) adapter slot per row
    scale: jax.Array,  # (B,) alpha/r per row (0 for base rows)
) -> jax.Array:
    a = mod["A"][idx]  # (B, in, r)
    b = mod["B"][idx]  # (B, r, out)
    u = jnp.einsum("bti,bir->btr", x, a)
    return jnp.einsum("btr,bro->bto", u, b) * scale[:, None, None].astype(x.dtype)


def _layer_body(
    cfg: ModelConfig,
    lp: dict,
    x: jax.Array,  # (B, T, h)
    positions: jax.Array,  # (B, T)
    attend,  # (q (B,T,nh,D), k (B,T,kvH,D), v (B,T,kvH,D)) -> (B,T,nh,D)
    lora: dict | None = None,  # layer-sliced init_lora_params tree
    lora_idx: jax.Array | None = None,  # (B,) slot per row
    moe_stats: list | None = None,  # collects each MoE layer's routing
    #   (_moe_mlp's second result; traced — what the caller leaves unused
    #   is never computed)
    spec: LayerSpec | None = None,  # this layer's entry of the per-layer
    #   list (its head count and rope); None: layer 0's
    moe_backend: str = "xla",  # see _moe_mlp
    recur=None,  # a layer that holds state: its _Recurrence (`attend` is
    #   None where the layer holds no pages)
) -> jax.Array:
    """The Llama layer math shared by every execution mode — prefill and the
    fused decode window differ ONLY in how attention consumes/stores KV (and
    a recurrence its state), so those strategies are injected as `attend`
    and `recur` and everything else (projections, bias, RoPE, residuals,
    MLP, LoRA deltas) exists exactly once. The multipliers are MupSpec's,
    applied here and never folded into a weight; each is 1 but for
    Falcon-H1."""
    b, t, h = x.shape
    spec = spec or cfg.layer_specs[0]
    hd, nh, nkv = cfg.head_dim, spec.num_heads, cfg.num_kv_heads
    rope = spec.rope

    if lora is not None:
        lscale = lora["scale"][lora_idx]

        def proj(xin, w, name, mm=_mm):
            out = mm(xin, w)
            if name in lora:
                out += _lora_delta(
                    xin, lora[name], lora_idx, lscale).reshape(out.shape)
            return out
    else:

        def proj(xin, w, name, mm=_mm):
            return mm(xin, w)

    res = x
    ap = lp["attn"]
    mup = cfg.mup or MupSpec()
    if spec.latent:
        x = _mla_attention(cfg, ap, lp["input_norm"], x, positions, attend,
                           lp.get("attn_index"))
        return _mlp_block(cfg, lp, res + x, proj, moe_stats, moe_backend)
    if spec.holds_state and not spec.holds_pages:
        x = _kda_attention(cfg, ap, lp.get("input_norm"), x, recur,
                           lp.get("attn_out_norm"))
        return _mlp_block(cfg, lp, res + x, proj, moe_stats, moe_backend)
    with jax.named_scope("attn_qkv"):
        if not cfg.post_norms_only:
            x = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps,
                         cfg.rms_norm_add_one)
        xa = _scaled(x, mup.attention_in)
        # (b, t, heads, head_dim) as the products leave them
        q = proj(xa, ap["wq"], "q_proj", _mm_heads)
        k = proj(xa, ap["wk"], "k_proj", _mm_heads)
        v = proj(xa, ap["wv"], "v_proj", _mm_heads)
        if cfg.attention_bias:
            q, k, v = (q + ap["bq"].reshape(nh, hd),
                       k + ap["bk"].reshape(nkv, hd),
                       v + ap["bv"].reshape(nkv, hd))
        k = _scaled(k, mup.key)
        if cfg.qk_norm_flat:
            # olmo2: RMSNorm over the whole flat projection
            q = rms_norm(q.reshape(b, t, nh * hd), ap["q_norm"],
                         cfg.rms_norm_eps, scale_f32=cfg.norm_scale_f32
                         ).reshape(b, t, nh, hd)
            k = rms_norm(k.reshape(b, t, nkv * hd), ap["k_norm"],
                         cfg.rms_norm_eps, scale_f32=cfg.norm_scale_f32
                         ).reshape(b, t, nkv, hd)
        if cfg.qk_norm:
            # qwen3: per-head RMSNorm on q/k BEFORE rope (HF Qwen3Attention)
            q = rms_norm(q, ap["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, ap["k_norm"], cfg.rms_norm_eps)
        if rope is not None:  # (None: no position encoding at all)
            q = apply_rope(q, positions, rope.theta, scaling=rope.scaling,
                           rotary_dim=rope.rotary_dim,
                           attention_factor=rope.attention_factor)
            k = apply_rope(k, positions, rope.theta, scaling=rope.scaling,
                           rotary_dim=rope.rotary_dim,
                           attention_factor=rope.attention_factor)
        if cfg.attn_output_gate:
            with jax.named_scope("attn_gate"):
                # one scalar a head (or a head and channel) and token, from
                # the same normed input as q (float32 logistic, as softmax
                # statistics are)
                gate = jax.nn.sigmoid(
                    _mm(x, ap["wg"]).astype(jnp.float32)).astype(x.dtype)

    # `attend` names its own parts: kv_stage (this step's K/V into the
    # pool or the window's staging buffer) and attn
    if cfg.pool_kv_heads != nkv:
        # a page holds more kv heads than the model has (whole HBM tiles:
        # ModelConfig.page_kv_heads): zero heads behind the model's own,
        # in k, v and — a group each — q; only the model's come back
        def more_heads(y, n):
            return jnp.pad(y, ((0, 0), (0, 0), (0, n - y.shape[2]), (0, 0)))

        attn = attend(
            more_heads(q, cfg.pool_kv_heads * (nh // nkv)),
            more_heads(k, cfg.pool_kv_heads),
            more_heads(v, cfg.pool_kv_heads))[:, :, :nh]
    else:
        attn = attend(q, k, v)
    if cfg.attn_output_gate:
        with jax.named_scope("attn_gate"):
            attn = attn * (gate.reshape(attn.shape)
                           if cfg.attn_output_gate == "element"
                           else gate[..., None])
    attn = attn.reshape(b, t, nh * hd)
    with jax.named_scope("attn_out"):
        attn_out = proj(attn, ap["wo"], "o_proj")
        if cfg.sandwich_norms or cfg.post_norms_only:
            # Gemma-2 / OLMo-2: norm the attention OUTPUT before the residual
            attn_out = rms_norm(attn_out, lp["attn_out_norm"],
                                cfg.rms_norm_eps, cfg.rms_norm_add_one,
                     scale_f32=cfg.norm_scale_f32)
    if spec.recurrence == "ssd":
        # Falcon-H1: state-space heads read the SAME normed input, and the
        # two scaled branches add to ONE residual
        #   a = Attn(u * attention_in) * attention_out
        #   m = SSM (u * ssm_in)       * ssm_out
        m = _ssm_mixer(cfg, lp["attn_ssm"], _scaled(x, mup.ssm_in), recur)
        with jax.named_scope("h1_branches"):
            attn_out = (_scaled(attn_out, mup.attention_out)
                        + _scaled(m.reshape(attn_out.shape), mup.ssm_out))
    with jax.named_scope("attn_out"):
        x = res + attn_out
    return _mlp_block(cfg, lp, x, proj, moe_stats, moe_backend)


def _kda_attention(cfg: ModelConfig, ap: dict, norm: jax.Array | None,
                   x: jax.Array, recur,
                   out_norm: jax.Array | None = None) -> jax.Array:
    """A linear-attention layer's mixer — the gated delta rule, its gate one
    a key channel (KDA) or one a head (Gated DeltaNet:
    `ModelConfig.linear_gate_per`), its state Dk x Dv a head;
    ops/kda_attention.py has the recurrence — (N, 1, h) -> the block's
    output before the residual add. The tokens lie on the first axis (a
    packed chunk, or a decode step's rows); what a token needs of the
    tokens before it — the convolution's tail and the state — `recur`
    brings: `recur.qkv((xq, xk, xv), taps, H, Dk)` returns q, k (N, H, Dk)
    and v (N, H, Dv) in the model's dtype — the causal depthwise
    convolution of the three projections over each row's own tokens, SiLU
    and the q / k L2 norms, in float32: XLA's, over `recur.conv` of their
    concatenation, for a decode step and for a chunk whose recurrence is
    XLA's; ONE Pallas pass (ops/kda_short_conv.py) for a chunk that runs
    the Pallas recurrence — and `recur.scan(q, k, v, g, beta)` runs the
    recurrence, g (N, H, Dk) or (N, H), and returns o (N, H, Dv) float32.
    `norm`: the block's pre-norm weight (None: OLMo-2's block has none);
    `out_norm`: the weight of its norm on the mixer's OUTPUT."""
    n = x.shape[0]
    lh, ld, dv = (cfg.linear_num_heads, cfg.linear_head_dim,
                  cfg.linear_value_dim)
    f32 = jnp.float32
    with jax.named_scope("kda_qkv"):
        u = (x[:, 0] if norm is None
             else rms_norm(x[:, 0], norm, cfg.rms_norm_eps))
        proj = (_mm(u, ap["wq"]), _mm(u, ap["wk"]), _mm(u, ap["wv"]))
    with jax.named_scope("kda_conv"):
        q, k, v = recur.qkv(proj, ap["conv"], lh, ld)
    with jax.named_scope("kda_gate"):
        # (the gates' projections: low-rank pairs, or full rank)
        a = (_mm(_mm(u, ap["wf_a"]), ap["wf_b"]) if "wf_a" in ap
             else _mm(u, ap["wf"])).astype(f32)
        if cfg.linear_gate_per == "head":
            # ONE log-decay a head: -exp(A_log) softplus(a + dt_bias), a
            # and dt_bias a head — (N, H), never broadcast over the keys
            g = (-jnp.exp(ap["A_log"].astype(f32))[None]
                 * jax.nn.softplus(a + ap["dt_bias"].astype(f32)))
        elif cfg.linear_gate_form == "bounded":
            # log of the decay a key channel, bounded below: bound *
            # sigmoid(exp(A_log) (a + dt_bias)), in (bound, 0) — a token
            # decays a channel by e^bound at most
            g = cfg.linear_gate_lower_bound * jax.nn.sigmoid(
                jnp.exp(ap["A_log"].astype(f32))[None, :, None]
                * (a + ap["dt_bias"].astype(f32)).reshape(n, lh, ld))
        else:
            # -exp(A_log) softplus(a + dt_bias), unbounded below
            g = (-jnp.exp(ap["A_log"].astype(f32))[None, :, None]
                 * jax.nn.softplus(a + ap["dt_bias"].astype(f32)).reshape(
                     n, lh, ld))
        # the step size in (0, linear_beta_scale): at 2 the eigenvalues of
        # I - beta k k^T lie in (-1, 1), at 1 in (0, 1)
        beta = jax.nn.sigmoid(_mm(u, ap["wb"]).astype(f32))
        if cfg.linear_beta_scale != 1.0:
            beta = cfg.linear_beta_scale * beta
    if recur.tap is not None:
        recur.tap(q, k, v, g, beta)
    with jax.named_scope("kda_scan"):
        o = recur.scan(q, k, v, g, beta)
    with jax.named_scope("kda_out"):
        z = (_mm(_mm(u, ap["wg_a"]), ap["wg_b"]) if "wg_a" in ap
             else _mm(u, ap["wg"])).astype(f32)
        o = rms_norm(o, ap["o_norm"].astype(f32), cfg.rms_norm_eps)
        gate = (jax.nn.silu if cfg.linear_output_gate == "silu"
                else jax.nn.sigmoid)
        o = (o.reshape(n, lh * dv) * gate(z)).astype(x.dtype)
        out = _mm(o, ap["wo"])
        if out_norm is not None:
            out = rms_norm(out, out_norm, cfg.rms_norm_eps,
                           scale_f32=cfg.norm_scale_f32)
        return out[:, None]


def _scaled(y: jax.Array, m: float) -> jax.Array:
    """y times a MupSpec multiplier, in y's dtype; y itself at 1."""
    return y if m == 1.0 else y * jnp.asarray(m, y.dtype)


def _ssm_mixer(cfg: ModelConfig, sp: dict, u: jax.Array, recur) -> jax.Array:
    """A state-space branch (Mamba-2; ops/ssd_scan.py has the recurrence),
    its scaled normed input (N, 1, h) -> (N, 1, h). The tokens lie on the
    first axis (a packed chunk, or a decode step's rows); what a token
    needs of the tokens before it `recur` brings, as `_kda_attention`'s:
    `recur.conv(x | B | C, taps)` and `recur.scan_ssd(x, B, C, dt, A, D)`
    -> y (N, H, P) float32."""
    n, dt_model = u.shape[0], u.dtype
    hs, hp = cfg.ssm_num_heads, cfg.ssm_head_dim
    g, ns, inner = cfg.ssm_num_groups, cfg.ssm_state_dim, cfg.ssm_inner
    f32 = jnp.float32
    mup = cfg.mup or MupSpec()
    with jax.named_scope("ssm_in_proj"):
        p = _mm(u[:, 0], sp["w_in"])  # z | x | B | C | dt
        if any(m != 1.0 for m in mup.ssm):
            # `mup_vector`: one multiplier a span
            p = p * jnp.concatenate([
                jnp.full((width,), m, p.dtype) for width, m in zip(
                    (inner, inner, g * ns, g * ns, hs), mup.ssm)])
        z, xbc, dt = jnp.split(p, [inner, inner + cfg.ssm_conv_dim], axis=-1)
    with jax.named_scope("ssm_conv"):
        xbc = jax.nn.silu(
            recur.conv(xbc, sp["conv"]) + sp["conv_bias"].astype(f32)
        ).astype(dt_model)
        x, bm, cm = jnp.split(xbc, [inner, inner + g * ns], axis=-1)
        # the step, float32, no clamp (the limits are 0 and infinity)
        dt = jax.nn.softplus(dt.astype(f32) + sp["dt_bias"].astype(f32))
        rate = -jnp.exp(sp["A_log"].astype(f32))
    x, bm, cm = (x.reshape(n, hs, hp), bm.reshape(n, g, ns),
                 cm.reshape(n, g, ns))
    if recur.tap is not None:
        recur.tap(x, bm, cm, dt)
    with jax.named_scope("ssm_scan"):
        y = recur.scan_ssd(x, bm, cm, dt, rate, sp["D"])
    with jax.named_scope("ssm_gate_norm"):
        # the gate FIRST (`mamba_norm_before_gate` false), then RMSNorm over
        # each group's channels
        y = y.reshape(n, inner) * jax.nn.silu(z.astype(f32))
        y = rms_norm(y.reshape(n, g, inner // g),
                     sp["norm"].astype(f32).reshape(g, inner // g),
                     cfg.rms_norm_eps)
        y = y.reshape(n, inner).astype(dt_model)
    with jax.named_scope("ssm_out"):
        return _mm(y, sp["w_out"])[:, None]


class _Recurrence:
    """What `_kda_attention` and `_ssm_mixer` ask of the tokens before
    their own: the convolution and the scan of ONE layer, reading a row's
    state
    slot at `read[row]` and writing it at `write[row]` of the layer's pools
    (`self.pools`, replaced by what the ops return). `tap`: see STATE_TAP."""

    def __init__(self, pools: dict, read, write, tap=None):
        self.pools, self.read, self.write, self.tap = pools, read, write, tap

    def qkv(self, proj: tuple, taps, heads: int, d: int):
        """A delta-rule layer's q, k (N, H, D) and v (N, H, Dv) in the
        projections' dtype from its three projections (N, H D | H Dv):
        `self.conv` of their
        concatenation, SiLU, the split and the L2 norm over a head's dims,
        in float32 — the plain form."""
        x = jnp.concatenate(proj, axis=-1)
        n = x.shape[0]
        y = jax.nn.silu(self.conv(x, taps))
        # (v at its own width: what the two key widths leave of the axis)
        q, k, v = (z.reshape(n, heads, -1) for z in jnp.split(
            y, (heads * d, 2 * heads * d), axis=-1))
        # q also times D^-0.5
        q = q * jax.lax.rsqrt(
            jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * d ** -0.5
        k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
        return tuple(z.astype(x.dtype) for z in (q, k, v))


class _PackedRecurrence(_Recurrence):
    """A packed prefill chunk: row i's tokens at [row_off[i], row_off[i] +
    chunk_lens[i]) of the axis; a row whose first token the chunk holds
    (`fresh`) starts from a zero state and a zero tail, whatever its slot
    held."""

    def __init__(self, pools, st: dict, rows: dict):
        super().__init__(pools, st["read"], st["write"], st["tap"])
        self.st, self.rows = st, rows

    def _old_tail(self):
        """The rows' tails as the chunk finds them, (B, K - 1, C): zeros
        for a fresh row, whatever its slot held."""
        return jnp.where(self.st["fresh"][:, None, None], 0,
                         self.pools["conv"][self.read])

    def _tail_terms(self, tail, taps, n: int):
        """What the tails add to their rows' first K - 1 tokens, a token
        row each (B (K - 1), C) float32, and the packed position of each
        (`n`: the row has no such token), then the index K - 1 long that
        `_keep_tail` goes on with. `taps` (K, C) float32."""
        kk = taps.shape[0]
        off, lens = self.st["row_off"], self.st["chunk_lens"]
        tail_f = tail.astype(jnp.float32)
        corr = jnp.stack([
            sum(tail_f[:, kk - 1 + jj - d] * taps[kk - 1 - d]
                for d in range(jj + 1, kk))
            for jj in range(kk - 1)], axis=1)  # (B, kk - 1, C)
        jj = jnp.arange(kk - 1, dtype=jnp.int32)[None]
        at = jnp.where(jj < lens[:, None], off[:, None] + jj, n)
        at = at.reshape(-1)
        return corr.reshape(-1, corr.shape[-1]), at, jj

    def _keep_tail(self, rows_of_x, n: int, tail, jj):
        """The layer's `conv` pool takes the rows' new tails: the last
        K - 1 of [old tail | the row's tokens]; `rows_of_x(index)`: those
        token rows of the packed (n, C) input."""
        km = tail.shape[1]
        off, lens = self.st["row_off"], self.st["chunk_lens"]
        back = lens[:, None] - km + jj  # in-row index, < 0: old tail
        new = jnp.where(
            (back >= 0)[..., None],
            rows_of_x(jnp.clip(off[:, None] + back, 0, n - 1)),
            jnp.take_along_axis(
                tail, jnp.clip(km + back, 0, km - 1)[..., None], axis=1))
        self.pools = {**self.pools, "conv": self.pools["conv"].at[
            self.write].set(new.astype(self.pools["conv"].dtype))}

    def conv(self, x, taps):
        st = self.st
        n, kk = x.shape[0], taps.shape[0]
        tail = self._old_tail()
        j = (jnp.arange(n, dtype=jnp.int32)
             - st["row_off"][st["tok_row"]])[:, None]
        xf, taps = x.astype(jnp.float32), taps.astype(jnp.float32)
        y = xf * taps[kk - 1]
        for d in range(1, kk):
            # the token d before, where the row has one in this chunk: a
            # shift along the packed axis must not read across a seam
            prev = jnp.concatenate([jnp.zeros_like(xf[:d]), xf[:-d]], axis=0)
            y = y + jnp.where(j >= d, prev, 0) * taps[kk - 1 - d]
        # a row's first kk - 1 tokens also read its tail
        corr, at, jj = self._tail_terms(tail, taps, n)
        y = y.at[at].add(corr, mode="drop")
        self._keep_tail(lambda at: x[at], n, tail, jj)
        return y

    def qkv(self, proj, taps, heads, d):
        st = self.st
        if st["interpret"] is None or proj[2].shape[-1] != heads * d:
            # (the one pass tiles three thirds of ONE width: a value width
            # of its own takes the plain form)
            return super().qkv(proj, taps, heads, d)
        # beside the Pallas recurrence: the same sums in ONE pass over the
        # projections as the matmuls wrote them (nothing concatenates them)
        n = proj[0].shape[0]
        tail = self._old_tail()
        corr, at, jj = self._tail_terms(tail, taps.astype(jnp.float32), n)
        self._keep_tail(
            lambda at: jnp.concatenate([x[at] for x in proj], axis=-1),
            n, tail, jj)
        return tuple(
            z.reshape(n, heads, d) for z in kda_short_conv(
                *proj, taps, corr, at, st["row_off"], heads=heads,
                interpret=st["interpret"]))

    def scan(self, q, k, v, g, beta):
        st, rows = self.st, self.rows
        if st["interpret"] is not None:
            # (g a head and key channel, or — one rank less — a head)
            kernel = (kda_prefill_attention if g.ndim == q.ndim
                      else gdn_prefill_attention)
            o, s = kernel(
                self.pools["s"], self.read, self.write, st["fresh"],
                q, k, v, g, beta, st["row_off"], st["chunk_lens"],
                interpret=st["interpret"])
        else:
            o, s = self._by_rows(kda_prefill_xla, (q, k, v, g, beta))
        self.pools = {**self.pools, "s": s}
        return o

    def _by_rows(self, scan_xla, per_token: tuple, *per_head):
        """A recurrence's XLA form, which takes rows first as attention
        does: the packed per-token arrays through the row view, the result
        gathered back."""
        st, rows = self.st, self.rows
        view = ((lambda y: y[None]) if rows["to_rows"] is None
                else (lambda y: y[rows["to_rows"]]))
        o, s = scan_xla(
            self.pools["s"], self.read, self.write, st["fresh"],
            *(view(y) for y in per_token), *per_head, st["chunk_lens"])
        o = (o[0] if rows["to_rows"] is None
             else o.reshape(-1, *o.shape[2:])[rows["to_packed"]])
        return o, s

    def scan_ssd(self, x, b, c, dt, a, d):
        st = self.st
        if st["interpret"] is not None:
            y, s = ssd_prefill_scan(
                self.pools["s"], self.read, self.write, st["fresh"],
                x, b, c, dt, a, d, st["row_off"], st["chunk_lens"],
                interpret=st["interpret"])
        else:
            y, s = self._by_rows(ssd_prefill_xla, (x, b, c, dt), a, d)
        self.pools = {**self.pools, "s": s}
        return y


class _StepRecurrence(_Recurrence):
    """One decode step: a token a row."""

    def __init__(self, pools, read, write, interpret: bool | None, tap=None):
        super().__init__(pools, read, write, tap)
        self.interpret = interpret

    def conv(self, x, taps):
        kk = taps.shape[0]
        tail = self.pools["conv"][self.read]  # (B, kk - 1, C)
        window = jnp.concatenate([tail, x[:, None].astype(tail.dtype)],
                                 axis=1)
        self.pools = {**self.pools, "conv": self.pools["conv"].at[
            self.write].set(window[:, 1:])}
        taps = taps.astype(jnp.float32)
        return sum(window[:, i].astype(jnp.float32) * taps[i]
                   for i in range(kk))

    def scan(self, q, k, v, g, beta):
        if self.interpret is None:
            o, s = kda_decode_xla(
                self.pools["s"], self.read, self.write, q, k, v, g, beta)
        else:
            kernel = (kda_decode_attention if g.ndim == q.ndim
                      else gdn_decode_attention)
            o, s = kernel(
                self.pools["s"], self.read, self.write, q, k, v, g, beta,
                interpret=self.interpret)
        self.pools = {**self.pools, "s": s}
        return o

    def scan_ssd(self, x, b, c, dt, a, d):
        if self.interpret is None:
            y, s = ssd_decode_xla(
                self.pools["s"], self.read, self.write, x, b, c, dt, a, d)
        else:
            y, s = ssd_decode_step(
                self.pools["s"], self.read, self.write, x, b, c, dt, a, d,
                interpret=self.interpret)
        self.pools = {**self.pools, "s": s}
        return y


def _mla_attention(cfg: ModelConfig, ap: dict, norm: jax.Array,
                   x: jax.Array, positions: jax.Array, attend,
                   index: dict | None = None) -> jax.Array:
    """Multi-head latent attention in the absorbed form, (B, T, h) -> the
    attention block's output before the residual add.

    Per token the layer makes its queries (heads x (nope ‖ rope), rope on
    the rope part) and ONE latent: the normed kv latent c_kv (rank) ‖ the
    roped key k_rope every head shares, zero padded to `latent_width` —
    what the pool holds. The published equations expand c_kv through W_kv_b
    into per-head k_nope and v; here W_kv_b's key half W_UK moves onto the
    query (q_abs = W_UK^T q_nope ‖ q_rope: scores q_abs · latent equal
    q_nope · k_nope + q_rope · k_rope) and its value half W_UV onto the
    output (o_head = W_UV Σ p · c_kv): the same mathematics, with attention
    itself multi-query over the latents. `attend(q_abs (B, T, heads, width),
    latent (B, T, width), None)` stores the latents and returns
    Σ p · c_kv, (B, T, heads, rank).

    A layer with the per-head output gate (`attn_output_gate` "head": a leaf
    "wg" (h, heads) beside the others) multiplies head h's output by
    sigmoid(u W_g)[h], u the layer's normed input, before W_o — one scalar a
    head and token, so it commutes with W_UV and is applied to the expanded
    head outputs.

    A model with a QUERY latent (`q_lora_rank`: leaves wq_a, q_a_norm, wq_b
    in wq's place) makes its queries from c_q = RMSNorm(W_qa u). A layer
    that owns an INDEXER (`index`: its "attn_index" leaves; LayerSpec.indexer
    "full") also makes, per token, the indexer's queries (Hi heads x D, from
    c_q), ONE index key (LayerNorm of a projection of u) — rope on the first
    `qk_rope_head_dim` of D of both — and a weight a head (from u, the two
    scales Hi^-1/2 D^-1/2 folded in, float32), and hands `attend` the three
    in the values' place: `attend` stores the key beside the latent, selects
    and attends over the selection (ops/dsa_attention.py). A layer that
    borrows hands it None, as every plain latent layer does."""
    b, t, _ = x.shape
    nh, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    pad = cfg.latent_width - cfg.latent_dim
    with jax.named_scope("attn_qkv"):
        x = rms_norm(x, norm, cfg.rms_norm_eps)
        with jax.named_scope("mla_q"):
            if "wq_a" in ap:
                c_q = rms_norm(_mm(x, ap["wq_a"]), ap["q_a_norm"],
                               cfg.rms_norm_eps)
                q = _mm_heads(c_q, ap["wq_b"])  # (b, t, heads, nope + rope)
            else:
                q = _mm_heads(x, ap["wq"])
            q_rope = apply_rope(q[..., dn:], positions, cfg.rope_theta)
        with jax.named_scope("mla_latent"):
            ckr = _mm(x, ap["wkv_a"])  # (B, T, rank + rope)
            c_kv = rms_norm(ckr[..., :r], ap["kv_a_norm"], cfg.rms_norm_eps)
            k_rope = apply_rope(
                ckr[..., None, r:], positions, cfg.rope_theta)[..., 0, :]
            latent = jnp.concatenate(
                [c_kv, k_rope, jnp.zeros((b, t, pad), x.dtype)], axis=-1)
        with jax.named_scope("mla_absorb"):
            q_lat = jnp.einsum("bthd,hdr->bthr", q[..., :dn], ap["wk_b"])
            q_abs = jnp.concatenate(
                [q_lat, q_rope, jnp.zeros((b, t, nh, pad), x.dtype)], axis=-1)
        if "wg" in ap:
            with jax.named_scope("mla_gate"):
                # (float32 logistic, as softmax statistics are)
                gate = jax.nn.sigmoid(
                    _mm(x, ap["wg"]).astype(jnp.float32)).astype(x.dtype)
        selecting = None
        if index is not None:
            hi, di = cfg.index_n_heads, cfg.index_head_dim
            with jax.named_scope("dsa_index_q"):
                q_idx = apply_rope(
                    _mm_heads(c_q, index["wq_b"]), positions,
                    cfg.rope_theta, rotary_dim=dr)
                w_idx = _mm(x, index["w_proj"]).astype(jnp.float32) * (
                    hi ** -0.5 * di ** -0.5)
            with jax.named_scope("dsa_index_k"):
                k_idx = apply_rope(
                    _layer_norm(_mm(x, index["wk"]), index["k_norm"],
                                index["k_norm_bias"])[:, :, None, :],
                    positions, cfg.rope_theta, rotary_dim=dr)[:, :, 0]
            selecting = {"q": q_idx, "k": k_idx, "w": w_idx}
    o_lat = attend(q_abs, latent, selecting)  # (B, T, heads, rank)
    with jax.named_scope("attn_out"):
        with jax.named_scope("mla_absorb"):
            o = jnp.einsum("bthr,hdr->bthd", o_lat, ap["wv_b"])
        if "wg" in ap:
            with jax.named_scope("mla_gate"):
                o = o * gate[..., None]
        return _mm(o.reshape(b, t, nh * dv), ap["wo"])


INDEX_KEY_NORM_EPS = 1e-6


def _layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array) -> jax.Array:
    """LayerNorm with weight and bias (the indexer's key), statistics in
    float32."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    normed = (xf - mean) * jax.lax.rsqrt(var + INDEX_KEY_NORM_EPS)
    return (normed * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _mlp_block(cfg: ModelConfig, lp: dict, x: jax.Array, proj,
               moe_stats: list | None, moe_backend: str = "xla") -> jax.Array:
    """The second half of a layer: norm, dense SwiGLU or experts, residual."""
    res = x
    with jax.named_scope("mlp"):
        if not cfg.post_norms_only:
            x = rms_norm(x, lp["post_attn_norm"], cfg.rms_norm_eps,
                         cfg.rms_norm_add_one)
        if "moe" in lp:
            mlp_out, routing = _moe_mlp(
                cfg, lp["moe"], x, lp.get("moe_stacked"), moe_backend)
            if moe_stats is not None:
                moe_stats.append(routing)
            return res + mlp_out
        mp, mup = lp["mlp"], cfg.mup or MupSpec()
        # W_down(W_up f * act(W_gate f * mlp_gate)) * mlp_down
        inner = _activation(cfg)(_scaled(
            proj(x, mp["gate"], "gate_proj"), mup.mlp_gate)) * proj(
                x, mp["up"], "up_proj")
        mlp_out = _scaled(proj(inner, mp["down"], "down_proj"), mup.mlp_down)
        if cfg.sandwich_norms or cfg.post_norms_only:
            mlp_out = rms_norm(mlp_out, lp["ffw_out_norm"],
                               cfg.rms_norm_eps, cfg.rms_norm_add_one,
                     scale_f32=cfg.norm_scale_f32)
        return res + mlp_out


# The four forms of `_moe_mlp`'s expert computation and the three constants
# that choose between them, measured on a v5e chip (benchmarks/sweep_moe.py;
# ms a layer, the expert stacks as served: every layer's leaves stacked, the
# layers run in turn).
#
# Few tokens (`--decode`, PERF.md §6 PR 36): the fused touched-experts kernel
# against the all-experts einsum and `ragged_dot` with a GIVEN number of
# experts touched, and the share of 819 GB/s the kernel reaches on the
# touched experts' bytes:
#   experts (stack), rows   touched    kernel (share)   einsum   ragged_dot
#   128 x 2048 x 768 bf16    13 (10%)   0.203 (74%)      1.633    0.329
#    (Kanana-2, 7 layers),   32 (25%)   0.437 (84%)      1.630    0.691
#    16 rows (all 128 at     64 (50%)   0.839 (88%)      1.628    1.234
#    64 rows)                96 (75%)   1.231 (90%)      1.630    1.808
#                           128 (all)   1.638 (90%)      1.633    2.781
#   64 held x 3072 x 1024     6 (10%)   0.189 (73%)      1.692    0.327
#    bf16 (Laguna, 8         16 (25%)   0.436 (85%)      1.689    0.586
#    layers), 16 rows        32 (50%)   0.835 (88%)      1.692    1.007
#                            48 (75%)   1.231 (90%)      1.691    1.371
#                            64 (all)   1.628 (91%)      1.688    1.792
#   64 x 2048 x 1024 int8     6 (10%)   0.081 (57%)      0.550    0.170
#    (OLMoE, 16 layers),     16 (25%)   0.162 (76%)      0.551    0.329
#    16 rows                 32 (50%)   0.293 (84%)      0.548    0.582
#                            48 (75%)   0.423 (87%)      0.549    0.834
#                            64 (all)   0.553 (89%)      0.550    1.084
# (32 and 64 rows read the same to 2% for kernel and einsum: at these sizes
# the rows are free.)
# The kernel's time follows the touched bytes at 84-91% of HBM bandwidth
# from a quarter touched upwards (an empty grid slot costs ~0.5 us, the
# first expert's copy is not overlapped), int8 leaves widened in VMEM
# included; the einsum streams EVERY expert at 87-90%, so the two tie where
# every expert is touched and the kernel wins by the untouched share below.
# `_TOUCHED_KERNEL_MAX_SHARE` is set under that tie, where a balanced router
# (expected_touched_share) leaves at least a fifth of the expert bytes
# unread: 16 rows of 128 x top-6 (0.54), 256 x top-10 (0.47), 256 x top-8
# (0.40) take the kernel; 16 rows of 64 x top-8 (0.88) keep the einsum —
# there a saving would be the routing's (seeded routers that agree), not
# the architecture's.
#
# Many tokens (PERF.md §6 PR 26; one OLMoE layer, 64 int8 experts, 8 per
# token):
#   tokens   all-experts einsum   ragged_dot   (bound: touched bytes | flops)
#       16         0.57              1.00           0.44 | 0.01
#      512         2.72              3.26           0.49 | 0.26
#     1024         5.67              3.95           0.49 | 0.52
#     2048        10.44              5.54           0.49 | 1.05
#     8192     (3 GiB of temps)     18.16           0.49 | 4.19
# XLA's grouped kernel pays for groups of two rows; from ~1k tokens the
# einsum's E/k-fold FLOPs lose to it: `_ALL_EXPERTS_MAX_TOKENS`, the limit
# of a program that may hold no Pallas kernel.
#
# Many tokens, the fused grouped-expert kernel (`--prefill`, PERF.md §6 PR
# 38): the sorted pairs through `moe_prefill_experts` against three
# `ragged_dot` calls and the einsum, each with its sort, gather, un-sort and
# combine as the model runs it, under a random router's (balanced) routing;
# in brackets the expert computation alone on sorted rows, and the share of
# 819 GB/s it reaches on the touched experts' and the held rows' bytes:
#   experts (stack)         tokens   kernel (alone, share)   ragged_dot (alone)   einsum
#   128 x 2048 x 768 bf16      512    1.916 (1.807, 83%)      4.719 (4.731)        3.973
#    (Kanana-2, 7 layers)    1,024    2.208 (2.027, 76%)      5.062 (4.998)        7.937
#                            2,048    2.683 (2.546, 63%)      5.610 (5.614)       16.809
#                            8,192    7.649 (5.410, 36%)     13.306 (11.247)         -
#   64 held of 256 x 3072      512    2.013 (1.833, 82%)      4.158 (4.020)        3.985
#    x 1024 bf16 (Laguna,    1,024    2.953 (2.178, 69%)      4.721 (4.511)        9.217
#    8 layers; 3/4 of the    2,048    4.288 (2.922, 53%)      6.798 (5.557)       14.922
#    sorted rows elsewhere)  8,192   12.245 (6.697, 27%)     16.176 (10.640)         -
#   64 x 2048 x 1024 int8      512    0.944 (0.843, 63%)      3.206 (3.117)        2.499
#    (OLMoE, 16 layers)      1,024    1.361 (1.209, 48%)      3.864 (3.741)        5.538
#                            2,048    2.487 (2.015, 33%)      5.370 (5.383)       10.277
#                            8,192    8.238 (6.521, 18%)     17.667 (15.479)         -
# (a skewed routing — the same logits plus a per-expert bias of 1.5 sigma, 78
# / 34 / 58 experts touched at 2,048 tokens — reads 2.044 / 3.357 / 2.413
# against ragged_dot's 3.912 / 4.771 / 5.174.) The kernel wins 1.6-2.6 x at
# every size, int8 leaves included (widened a tile at a time in VMEM), so the
# "grouped_kernel" form takes every leaf dtype. The "alone" columns include
# ~0.2-0.4 ms of the sweep's own renormalisation of the carried rows; what
# the kernel leaves on the table is the one-step lookahead of its pipeline
# (an expert's copy overlaps only the last visit of the expert before it)
# and, from ~4k tokens, MXU passes of 128 rows (78% of the peak; 256 rows
# reach 94%).
#
# Where the einsum ends for a program that may hold the kernel (the same
# sweep at `--tokens 64,128,192,256,384,512`, PERF.md §6 PR 42; balanced
# routing, the whole form with its sort and combine, ms a layer):
#   experts (stack)          tokens   kernel   einsum   ragged_dot
#   64 x 2048 x 1024 int8        64    0.617    0.554     2.631
#    (OLMoE, 16 layers)         128    0.666    0.901     2.723
#                               192    0.703    0.930     2.756
#                               256    0.762    1.393     2.879
#                               384    0.853    1.974     3.041
#                               512    0.946    2.502     3.208
#   128 x 2048 x 768 bf16        64    1.629    1.635     2.715
#    (Kanana-2, 7 layers)       128    1.711    1.945     3.248
#                               192    1.750    1.698     2.944
#                               256    1.767    2.256     4.520
#                               384    1.843    3.177     3.449
#                               512    1.915    3.973     4.723
#   64 held of 256 x 3072        64    1.547    1.741     1.916
#    x 1024 bf16 (Laguna,       128    1.722    2.199     2.549
#    8 layers; 58 / 63 of       192    1.777    1.856     2.380
#    the held touched at        256    1.815    2.296     4.032
#    64 / 128 tokens)           384    1.886    3.032     2.851
#                               512    2.013    3.982     4.156
# The einsum does 2 N E P flops over E P itemsize bytes (P an expert's
# parameters): 2 N / itemsize flops a byte whatever E and k are, against the
# chip's 240 (197 TF/s over 819 GB/s), so on paper it leaves its bytes' bound
# at 120 tokens a weight byte. Measured it leaves earlier: its matmuls reach
# ~79% of the MXU peak (3.973 ms for 3.14 ms of flops at 512 bf16 tokens) and
# near the crossover its reads and its products do not overlap (int8 leaves:
# the widening too), while the kernel stays within 10% of the touched bytes'
# time from 64 to 256 tokens. int8 leaves cross between 64 tokens (einsum by
# 10%) and 128 (kernel by 26%); bf16 leaves tie at 192 (-3% / +4%) and the
# kernel wins by 21-22% at 256: 0.8 x 120 = 96 tokens a weight byte,
# `_ALL_EXPERTS_KERNEL_TOKENS_PER_WEIGHT_BYTE` (96 tokens for int8 leaves,
# 192 for bf16; float32 leaves, which no chip serves, keep 384). Under it
# every form reads the same bytes and the einsum needs no sort; what a kernel
# saves there is untouched experts, `_TOUCHED_KERNEL_MAX_SHARE`'s business
# (the Laguna rows at 64 and 128 tokens). One reading stands off the trend:
# at exactly 128 tokens XLA's einsum is 19-38% over its own neighbours in all
# three stacks (a tiling choice), so a bf16 program of 128 tokens would do
# 12-22% better on the kernel; no cell runs one (PERF.md §7). `ragged_dot`
# loses to the einsum at every one of these sizes but Laguna's 384 tokens
# (2.851 against 3.032), so a program that may hold no kernel (any mesh, the
# CPU, `moe_backend` "xla") keeps `_ALL_EXPERTS_MAX_TOKENS`.
_ALL_EXPERTS_MAX_TOKENS = 512
_ALL_EXPERTS_KERNEL_TOKENS_PER_WEIGHT_BYTE = 96
_TOUCHED_KERNEL_MAX_SHARE = 0.8


def _expert_mm(spec: str, a: jax.Array, w) -> jax.Array:
    """einsum of activations with every expert's matrix (E, in, out); an
    int8 leaf is widened inside the einsum (XLA fuses the convert into the
    operand read: no bf16 copy of the experts is written) and its
    per-expert, per-output-channel scale multiplies the (..., E, out)
    result, as `_mm` does for the dense matrices."""
    if isinstance(w, dict):
        return (jnp.einsum(spec, a, w["q"].astype(a.dtype))
                * w["s"].astype(a.dtype)[:, 0])
    return jnp.einsum(spec, a, w)


def _grouped_mm(xs: jax.Array, w, layer: int, group_sizes: jax.Array,
                row_expert: jax.Array) -> jax.Array:
    """Rows of `xs` (M, in), sorted by expert, each times its own expert's
    matrix of layer `layer` of the stacked leaf `w` (L, E, in, out):
    `jax.lax.ragged_dot`, which XLA lowers on the TPU to a grouped-matmul
    kernel that reads only the groups that hold rows. The kernel is a
    custom call, and XLA copies a slice that feeds one (128 MiB per int8
    leaf and layer at OLMoE-1B-7B, 5 GiB of temps in a 16-layer program);
    so it gets the whole stacked leaf as L*E groups, all empty but this
    layer's E. An int8 leaf goes in as int8 — the kernel widens a tile in
    VMEM, no bf16 copy of the experts is written — and the per-expert,
    per-output-channel scale multiplies the output rows."""
    q = w["q"] if isinstance(w, dict) else w
    n_layers, e = q.shape[:2]
    out = jax.lax.ragged_dot(
        xs, q.reshape(n_layers * e, *q.shape[2:]),
        jnp.pad(group_sizes, (layer * e, (n_layers - 1 - layer) * e)),
        preferred_element_type=xs.dtype)
    if isinstance(w, dict):
        out = out * w["s"][layer].astype(xs.dtype)[row_expert, 0]
    return out


def _moe_route(cfg: ModelConfig, router: jax.Array, xf: jax.Array,
               bias: jax.Array | None = None, stats: dict | None = None):
    """(N, h) -> routing weights (N, k) float32, experts (N, k), their
    one-hot (N, k, E) and the pairs each expert got (E,). `stats`: a dict
    that receives what group-limited routing counts (`_sigmoid_top_k`)."""
    with jax.named_scope("moe_router"):
        if cfg.scoring_func == "sigmoid":
            # the router's matmul itself in float32, as published
            # (DeepseekV3TopkRouter): the top-k is a hard choice among 128
            # scores a few thousandths apart and the chosen weights are
            # renormalised, so a flipped boundary expert swaps a sixth of
            # the routed output — a bf16 product's rounding (2^-8 of a
            # logit) flipped 4% of tokens a layer (PERF.md §6, PR 31)
            logits = jnp.dot(
                xf.astype(jnp.float32), router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)  # (N, E)
            topv, topi = _sigmoid_top_k(cfg, logits, bias, stats)
        else:
            logits = (xf @ router).astype(jnp.float32)  # (N, E)
            topv, topi = _softmax_top_k(cfg, logits)
        # the experts this program holds, numbered from 0: a pair that
        # lands on another chip's expert is a row of zeros (one_hot of an
        # index out of range) and belongs to no group
        local = (topi - cfg.first_expert_held if cfg.num_experts_held
                 else topi)
        chosen = jax.nn.one_hot(local, cfg.held_experts, dtype=jnp.float32)
        group_sizes = jnp.sum(chosen, axis=(0, 1)).astype(jnp.int32)
    return topv, topi, chosen, group_sizes


def _sigmoid_top_k(cfg: ModelConfig, logits: jax.Array, bias: jax.Array,
                   stats: dict | None = None):
    """DeepSeek-V3 routing: scores are sigmoid(logits) in float32; the k
    experts are the top-k of the CHOICE scores, scores + the selection bias
    (ties to the lower index, jax.lax.top_k); their weights are the SCORES
    (the bias moves the choice, never the weight), divided by their sum
    under norm_topk_prob, times routed_scaling_factor.

    Group-limited (`n_group` > 1): the E router outputs — ALL of them,
    whatever share of the experts this program holds — are n_group groups
    of E / n_group consecutive experts; a group's score is the sum of its
    two best choice scores; the `topk_group` best groups are kept (ties to
    the lower group index, jax.lax.top_k again) and the top-k is taken
    among their experts alone: an expert of another group is never chosen
    (its choice score counts as -inf; the published zero fill chooses the
    same experts while the kept groups' choice scores are positive, which
    sigmoid scores + a small bias are). All of it in float32. `stats`
    receives "groups_kept_held": how many of the N x topk_group kept groups
    hold an expert this program holds."""
    scores = jax.nn.sigmoid(logits)
    # (a family without the selection bias has no such leaf: laguna)
    choice = scores if bias is None else scores + bias
    if cfg.n_group > 1:
        with jax.named_scope("moe_route_groups"):
            n, e = choice.shape
            per = e // cfg.n_group
            best2, _ = jax.lax.top_k(
                choice.reshape(n, cfg.n_group, per), min(2, per))
            _, kept = jax.lax.top_k(
                jnp.sum(best2, axis=-1), cfg.topk_group)  # (N, topk_group)
            keep = jnp.any(
                kept[..., None] == jnp.arange(cfg.n_group, dtype=kept.dtype),
                axis=1)  # (N, n_group)
            choice = jnp.where(
                jnp.repeat(keep, per, axis=1), choice, -jnp.inf)
            if stats is not None:
                lo = cfg.first_expert_held // per
                hi = (cfg.first_expert_held + cfg.held_experts - 1) // per
                stats["groups_kept_held"] = jnp.sum(
                    ((kept >= lo) & (kept <= hi)).astype(jnp.int32))
    _, topi = jax.lax.top_k(choice, cfg.num_experts_per_tok)
    topv = jnp.take_along_axis(scores, topi, axis=-1)
    if cfg.norm_topk_prob:
        topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-20)
    return topv * cfg.routed_scaling_factor, topi


def _softmax_top_k(cfg: ModelConfig, logits: jax.Array):
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        # Mixtral (always) / Qwen3-MoE norm_topk_prob=true: the selected
        # weights renormalize to sum to 1; otherwise (OLMoE) they stay
        # raw softmax mass
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    return topv, topi


def _combine_weights(topv, chosen):
    """(N, E) combine weights: topv scattered back onto the expert axis."""
    with jax.named_scope("moe_dispatch"):
        return jnp.sum(chosen * topv[..., None], axis=-2)


def _experts_all(cfg, mp, xf, topv, chosen):
    """Every expert on every token, weighted by the routing mass or 0."""
    w = _combine_weights(topv, chosen)
    with jax.named_scope("moe_experts"):
        inner = _activation(cfg)(
            _expert_mm("nh,ehi->nei", xf, mp["gate"])
        ) * _expert_mm("nh,ehi->nei", xf, mp["up"])
        out = _expert_mm("nei,eih->neh", inner, mp["down"])
    with jax.named_scope("moe_combine"):
        return jnp.einsum("neh,ne->nh", out, w.astype(xf.dtype))


def _experts_grouped(cfg, stacked, layer, xf, topv, topi, group_sizes,
                     interpret: bool | None = None):
    """The routed (token, expert) pairs sorted by expert, each expert's rows
    one group of a grouped matmul (over layer `layer` of the `stacked`
    expert leaves), then un-sorted, weighted and summed. The grouped matmul
    is three `ragged_dot` calls (`interpret` None: the "grouped" form), or
    the fused Pallas kernel that reads each expert once and keeps the
    (pairs, inner) intermediate in VMEM (ops/moe_experts_pallas.py:
    moe_prefill_experts, the "grouped_kernel" form; `interpret`: run it in
    interpret mode) — the sort and the un-sort are the same."""
    n, k = topi.shape
    with jax.named_scope("moe_dispatch"):
        pair_expert = topi.reshape(n * k)
        if cfg.num_experts_held:
            # pairs on experts held elsewhere sort behind every group: the
            # grouped matmul does not compute their rows, and their weight
            # is zeroed below (whatever those rows of its output hold)
            local = pair_expert - cfg.first_expert_held
            here = (local >= 0) & (local < cfg.held_experts)
            pair_expert = jnp.where(here, local, cfg.held_experts)
            topv = jnp.where(here.reshape(n, k), topv, 0.0)
        order = jnp.argsort(pair_expert, stable=True)  # sorted row -> pair
        row_expert = pair_expert[order]
        xs = xf[order // k]  # (N*k, h): each pair's token, expert-major
    with jax.named_scope("moe_experts"):
        if interpret is None:
            inner = _activation(cfg)(_grouped_mm(
                xs, stacked["gate"], layer, group_sizes, row_expert)
            ) * _grouped_mm(xs, stacked["up"], layer, group_sizes, row_expert)
            out = _grouped_mm(
                inner, stacked["down"], layer, group_sizes, row_expert)
        else:
            out = moe_prefill_experts(
                xs, group_sizes, stacked["gate"], stacked["up"],
                stacked["down"], layer=layer, act=_activation(cfg),
                interpret=interpret)
    with jax.named_scope("moe_combine"):
        # pair p sits at sorted row inv[p]
        out = out[jnp.argsort(order)].reshape(n, k, -1)
        if cfg.num_experts_held:
            out = jnp.where(here.reshape(n, k, 1), out, 0)
        return jnp.einsum("nkh,nk->nh", out, topv.astype(xf.dtype))


def expected_touched_share(cfg: ModelConfig, n: int) -> float:
    """The share of a layer's experts that `n` tokens touch under a
    perfectly balanced router, 1 - (1 - k/E)^n over the PUBLISHED expert
    count (a program that holds a share of the experts touches the same
    share of those): what the static shapes say of how sparse the expert
    reads of a program can be."""
    return 1.0 - (1.0 - cfg.num_experts_per_tok / cfg.num_experts) ** n


def all_experts_max_tokens(cfg: ModelConfig, *, kernel: bool) -> int:
    """The most tokens a program runs through the all-experts einsum. The
    einsum does 2 N / itemsize flops a weight byte whatever E and k are, so
    it leaves its bytes' bound at a token count that grows with the expert
    leaves' itemsize (int8 leaves are read as int8). Past it the fused
    kernel's N k pairs beat the einsum's N E where the program may hold one
    (`kernel`); `ragged_dot` loses to the einsum up to
    `_ALL_EXPERTS_MAX_TOKENS` (the tables above)."""
    if not kernel:
        return _ALL_EXPERTS_MAX_TOKENS
    itemsize = 1 if cfg.quantization else jnp.dtype(cfg.dtype).itemsize
    return min(_ALL_EXPERTS_MAX_TOKENS,
               _ALL_EXPERTS_KERNEL_TOKENS_PER_WEIGHT_BYTE * itemsize)


def moe_experts_form(cfg: ModelConfig, n: int, *, kernel: bool) -> str:
    """How a program of `n` tokens computes an expert layer (_moe_mlp), from
    static shapes alone: "touched_kernel" | "all_experts" | "grouped" |
    "grouped_kernel". `kernel`: the program may hold a Pallas kernel
    (`moe_backend` is not "xla": one TPU device, no mesh)."""
    if n > all_experts_max_tokens(cfg, kernel=kernel):
        return "grouped_kernel" if kernel else "grouped"
    if kernel and expected_touched_share(cfg, n) < _TOUCHED_KERNEL_MAX_SHARE:
        return "touched_kernel"
    return "all_experts"


def _experts_touched(cfg, stacked, layer, xf, topv, chosen, group_sizes,
                     interpret):
    """The experts that got a pair, and only those, through the fused
    Pallas kernel (ops/moe_experts_pallas.py) over layer `layer` of the
    `stacked` expert leaves."""
    n, k = topv.shape
    w = _combine_weights(topv, chosen)
    with jax.named_scope("moe_experts"):
        return moe_decode_experts(
            xf, w, group_sizes, stacked["gate"], stacked["up"],
            stacked["down"], layer=layer,
            slots=min(cfg.held_experts, n * k), act=_activation(cfg),
            interpret=interpret)


def _moe_mlp(cfg: ModelConfig, mp: dict, x: jax.Array,
             stacked: tuple[dict, int] | None = None,
             backend: str = "xla",
             ) -> tuple[jax.Array, dict]:
    """Sparse-MoE MLP, HF routing semantics (Mixtral, Qwen3-MoE, OLMoE):
    softmax over ALL expert logits in float32, take top-k, and renormalize
    the selected weights to sum to 1 only under `norm_topk_prob`.

    The expert computation is ONE family in four forms, chosen by
    `moe_experts_form` from the static token count N, the top-k, the
    published expert count, the expert leaves' itemsize and whether the
    program may hold a Pallas kernel (tables above; no flag, no model
    name). Where it may, "few" tokens are those under which the einsum is
    still bound by the experts' bytes, `all_experts_max_tokens`: 96 for
    int8 leaves, 192 for bf16; where it may not, 512:

    - "touched_kernel" — few tokens of a many-expert model, where the N*k
      pairs cannot cover the experts (the expected touched share under a
      balanced router, 1 - (1 - k/E)^N, lies under
      `_TOUCHED_KERNEL_MAX_SHARE`: 16 rows of 128 x top-6 or 256 x top-10):
      one fused Pallas kernel walks the experts that got a pair and reads
      only their bytes (`_experts_touched`). Only where the program may
      hold a Pallas kernel — `backend` "pallas" | "pallas_interpret", which
      the runner resolves to on one TPU device (under an ep / tp mesh the
      einsum stays: a pallas_call has no GSPMD partition rule, and the
      kernel is not wrapped in a shard_map as paged_decode_attention_sharded
      is). int8 leaves take it too: the kernel widens a tile in VMEM and
      the sweep's int8 arm wins below the same crossover.
    - "all_experts" — few tokens otherwise (a decode step of a 64 x top-8
      model touches 88% of its experts anyway): every expert evaluates
      every token and the routing weights zero the rest, bound by the
      expert bytes (`_experts_all`).
    - "grouped" — more tokens than that (a prefill program): the work
      follows the routed pairs (`_experts_grouped`) —
      B*T*k rows sorted by expert whatever the routing, an expert without
      rows costs nothing; the all-experts form would spend
      num_experts/top_k times the FLOPs and hold three (B*T, E, inter)
      intermediates, 3 GiB at 64 experts and 16 x 512 tokens. Three
      `ragged_dot` calls a layer: what a program under an ep / tp mesh, on
      the CPU and under `backend` "xla" runs (no shard_map wrapper), and
      the kernel's oracle in the tests.
    - "grouped_kernel" — the same sorted pairs where the program may hold a
      Pallas kernel, from the token count at which the einsum's flops
      overtake its bytes (a 512-token chunk of 64 int8 experts: 8 x the
      routed flops through the einsum): ONE fused kernel a layer
      (ops/moe_experts_pallas.py:moe_prefill_experts) walks the held
      experts in ascending order, brings each expert's gate, up and down
      matrices across HBM once a layer and chunk and runs its rows under
      them tile by tile; no (pairs, inner) intermediate in HBM. The sort,
      the gather and the un-sort are the "grouped" form's. int8 leaves
      take it too (widened a tile at a time in VMEM).

    Every form computes every routed pair (no capacity, no dropped token)
    from static shapes and reads int8 experts as int8; under expert
    parallelism the E axis of the expert leaves stays sharded over ep
    (parallel/sharding.py; GSPMD partitions the einsum or the grouped
    matmul and psums the combine).

    `mp` is one layer's leaves; `stacked` = (all layers' leaves, this
    layer's index) is what the grouped matmul and the kernel read in place
    (_grouped_mm); without it `mp` stands as a stack of one.

    A program that holds a share of the experts (ModelConfig
    .num_experts_held) routes over all `num_experts` and computes the pairs
    that land on its own: `chosen` and `group_sizes` are over the held
    experts, and the result is their part of the layer's output plus the
    shared expert's.

    x: (B, T, h) → ((B, T, h), routing): `touched`, the HELD experts that
    got at least one pair (a scalar int32 the runner sums into
    `moe_*_touched_n`), `held_pairs`, the pairs that landed on them (only
    from a program that holds a share: `moe_*_held_pairs_n`), and
    `experts` (B, T, k), each token's choice (read by the comparison with
    the reference, testing/reference_decoder.py); under group-limited
    routing also `groups_kept_held` (`_sigmoid_top_k`:
    `moe_group_kept_n`)."""
    b, t, h = x.shape
    n = b * t
    xf = x.reshape(n, h)
    group_stats: dict = {}
    topv, topi, chosen, group_sizes = _moe_route(
        cfg, mp["router"], xf, mp.get("bias"), group_stats)
    if (ROUTER_TAP is not None and stacked is not None
            and stacked[1] == ROUTER_TAP.moe_layer):
        jax.debug.callback(ROUTER_TAP, xf, topi)
    form = moe_experts_form(cfg, n, kernel=backend != "xla")
    if form == "all_experts":
        y = _experts_all(cfg, mp, xf, topv, chosen)
    else:
        if stacked is None:
            stacked = (jax.tree.map(lambda a: a[None], mp), 0)
        if form == "grouped":
            y = _experts_grouped(cfg, *stacked, xf, topv, topi, group_sizes)
        elif form == "grouped_kernel":
            y = _experts_grouped(cfg, *stacked, xf, topv, topi, group_sizes,
                                 backend == "pallas_interpret")
        else:
            y = _experts_touched(cfg, *stacked, xf, topv, chosen,
                                 group_sizes, backend == "pallas_interpret")
    if "shared" in mp:
        # the shared expert: one ungated SwiGLU on every token
        with jax.named_scope("moe_shared"):
            sp = mp["shared"]
            y = y + _mm(_activation(cfg)(_mm(xf, sp["gate"]))
                        * _mm(xf, sp["up"]), sp["down"])
    touched = jnp.sum((group_sizes > 0).astype(jnp.int32))
    routing = {"touched": touched, "experts": topi.reshape(b, t, -1)}
    if cfg.num_experts_held or cfg.n_group > 1:
        routing["held_pairs"] = jnp.sum(group_sizes)
    if cfg.n_group > 1:
        routing.update(group_stats)
    return y.reshape(b, t, h), routing


def _layer_params(cfg: ModelConfig, params: dict, i: int) -> dict:
    """Layer i's slice of the stacked per-layer leaves (static slices, fused
    into their consumers), and for an MoE model the stacked expert leaves
    themselves beside it (see _grouped_mm). Where a tree stacks a kind of
    layer on its own — dense and expert MLPs (_init_params_mla), full and
    window attention (_init_params_by_kind) — the layer's leaves sit at its
    index among the layers of its kind (ModelConfig.layer_stack_index)."""
    layers = params["layers"]
    spec = cfg.layer_specs[i]
    ai, mi = cfg.layer_stack_index[i]
    two_mlps = "moe" in layers and "mlp" in layers
    mlp_kind = "moe" if spec.experts else "mlp"
    # name in the layer's dict -> (stack in the tree, the layer's index there)
    picks = {name: (name, i) for name in layers
             if name not in ("attn", "attn_window", "attn_linear",
                             "attn_latent", "attn_ssm", "attn_index",
                             "mlp", "moe")}
    picks["attn"] = (
        ({"full": "attn", "window": "attn_window", "linear": "attn_linear",
          "latent": "attn_latent"}[spec.attn_kind], ai)
        if cfg.attn_stacks_by_kind else ("attn", i))
    if spec.recurrence == "ssd":
        picks["attn_ssm"] = ("attn_ssm", cfg.ssm_layers.index(i))
    if spec.indexer == "full":
        picks["attn_index"] = ("attn_index", cfg.index_layers.index(i))
    picks[mlp_kind] = (mlp_kind, j := mi if two_mlps else i)
    # (slices are traced in the tree's own sorted order, a kind-stacked MLP
    # last: the order the programs of every older model were traced in, so
    # their StableHLO stays byte-identical)
    # (a scope of its own: XLA fuses a layer's slices into copies of its
    # weights — 18 ms of a 123-ms decode window of qwen3-8b int8 — and names
    # the fusion after the slice, which no layer scope would hold)
    with jax.named_scope("layer_weights"):
        lp = {
            name: jax.tree.map(lambda a, k=picks[name][1]: a[k],
                               layers[picks[name][0]])
            for name in sorted(
                picks, key=lambda n: (two_mlps and n == mlp_kind, n))
        }
    if spec.experts:
        lp["moe_stacked"] = (layers["moe"], j)
    return lp


def _lora_layer_slice(lora: dict | None, i: int) -> dict | None:
    """Layer i's slice of the stacked adapter tree (scale is per-slot,
    layer-invariant)."""
    if lora is None:
        return None
    out: dict = {"scale": lora["scale"]}
    for name, mod in lora.items():
        if name != "scale":
            out[name] = {"A": mod["A"][:, i], "B": mod["B"][:, i]}
    return out


def pack_index(chunk_lens: jax.Array, context_lens: jax.Array,
               n: int) -> dict:
    """The index of a PACKED prefill chunk — the rows' tokens laid end to
    end on one axis of `n` positions, row i's at [row_off[i], row_off[i] +
    chunk_lens[i]) — from the per-row lengths alone (a cumulative sum and
    one compare on the device: nothing more to copy from the host).
    `tok_row` clamps the padding tokens past the last row onto the last
    row: what they compute is never read."""
    ends = jnp.cumsum(chunk_lens)
    tok = jnp.arange(n, dtype=jnp.int32)
    tok_row = jnp.sum(tok[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)
    return {
        "row_off": (ends - chunk_lens).astype(jnp.int32),  # (B,)
        "tok_row": jnp.minimum(tok_row, chunk_lens.shape[0] - 1),  # (N,)
        "chunk_start": context_lens - chunk_lens,  # (B,) position of the
        #   row's first chunk token
    }


def chunk_write_by_kernel(backend: str, mesh) -> bool:
    """Whether a prefill chunk's K/V reach the pool through the write kernel
    (ops/paged_attention_pallas.py:paged_kv_write): wherever the prefill
    kernel attends on one device. A Pallas backend on a chip means pages
    that fill whole HBM tiles (ModelRunner._resolve_prefill_backend refuses
    it for a head dim of 64 or a one-head tp shard), which is all the write
    kernel needs; the XLA backend and a mesh of several devices (pallas_call
    has no GSPMD rule, and the pool is sharded over tp there) keep
    write_kv_pages_blockwise."""
    return backend.startswith("pallas") and (mesh is None or mesh.size == 1)


def _block_mask(cfg: ModelConfig) -> dict:
    """The attention mask's `block` argument of a model that generates by
    diffusion over blocks (a query sees its whole block), nothing for any
    other model: their programs trace as they did."""
    bd = cfg.block_diffusion
    return {} if bd is None else {"block": bd.block_length}


def _row_view(pack: dict, n: int, b: int) -> dict:
    """The two gather indices between a packed chunk of `n` tokens and the
    (b, n) row view attention takes: "to_rows" (b, n), the packed index of
    each (row, column), clamped — columns past a row's tokens read a
    neighbour's, which attention masks by length — and "to_packed" (n,), the
    flat (row * n + column) of each token. A lone row is its own view: both
    None."""
    row_off, tok_row = pack["row_off"], pack["tok_row"]
    rows = {"row_off": row_off, "to_rows": None, "to_packed": None}
    if b > 1:
        tok = jnp.arange(n, dtype=jnp.int32)
        rows["to_rows"] = jnp.clip(row_off[:, None] + tok[None], 0, n - 1)
        rows["to_packed"] = tok_row * n + jnp.clip(
            tok - row_off[tok_row], 0, n - 1)
    return rows


def _layer(
    cfg: ModelConfig,
    lp: dict,
    kv_layer: jax.Array,
    x: jax.Array,
    positions: jax.Array,
    block_tables: jax.Array,
    slot_mapping: jax.Array,
    mask: jax.Array | None,
    lora: dict | None = None,
    lora_idx: jax.Array | None = None,
    write_blocks: dict | None = None,  # blockwise-write inputs (see forward)
    pallas_prefill: dict | None = None,  # {"context_lens", "chunk_start",
    #   "interpret", "mesh", "write_kernel"} — route attention through the
    #   paged flash-prefill kernel instead of the XLA gather (mask is None
    #   then), and the chunk's K/V write through its kernel
    moe_stats: list | None = None,  # see _layer_body
    rows: dict | None = None,  # _row_view(...) of a packed chunk: x is
    #   (N, 1, h) then
    spec: LayerSpec | None = None,  # the layer's entry of the per-layer list
    moe_backend: str = "xla",  # see _moe_mlp
    state: dict | None = None,  # a layer that holds state: the rows' slots
    #   and the pack (see forward); `kv_layer` is its pools then
    selected: dict | None = None,  # a layer that attends over selected keys
    #   (LayerSpec.indexer): {"context_lens", "chunk_start", "carry"} — see
    #   forward; `kv_layer` is {"kv", "idx"} where the layer owns an indexer
) -> tuple[jax.Array, jax.Array]:
    b, t = x.shape[0], x.shape[1]
    hd, nkv = cfg.head_dim, cfg.pool_kv_heads  # (k, v as `attend` gets them)
    spec = spec or cfg.layer_specs[0]
    recur = None
    if spec.holds_state:
        recur = _PackedRecurrence(_state_pools(kv_layer), state, rows)
        if not spec.holds_pages:
            x = _layer_body(cfg, lp, x, positions, None, lora, lora_idx,
                            moe_stats, spec, moe_backend, recur)
            return x, recur.pools
        kv_layer = kv_layer["kv"]  # pages AND state: both strategies
    # the layer kind's page group: its block tables, written span, slots
    block_tables = _group(block_tables, spec.window)
    slot_mapping = _group(slot_mapping, spec.window)
    if write_blocks is not None:
        write_blocks = {
            **write_blocks, "ids": _group(write_blocks["ids"], spec.window)}

    def _attention(q):
        if spec.latent:
            # q is the absorbed queries (B, T, heads, width)
            if pallas_prefill is not None:
                return mla_prefill_attention(
                    q, kv_layer, block_tables,
                    pallas_prefill["context_lens"],
                    pallas_prefill["chunk_start"], scale=cfg.attn_scale,
                    rank=cfg.kv_lora_rank,
                    interpret=pallas_prefill["interpret"],
                )
            return latent_attention_xla(
                q, kv_layer, block_tables, mask, scale=cfg.attn_scale,
                rank=cfg.kv_lora_rank,
            )
        if pallas_prefill is not None:
            mesh = pallas_prefill["mesh"]
            if mesh is not None and mesh.size > 1:
                return paged_prefill_attention_sharded(
                    mesh, q, kv_layer, block_tables,
                    pallas_prefill["context_lens"],
                    pallas_prefill["chunk_start"], scale=cfg.attn_scale,
                    interpret=pallas_prefill["interpret"],
                )
            if spec.window:
                return paged_window_prefill_attention(
                    q, kv_layer, block_tables,
                    pallas_prefill["context_lens"],
                    pallas_prefill["chunk_start"], scale=cfg.attn_scale,
                    window=spec.window,
                    interpret=pallas_prefill["interpret"],
                )
            return paged_prefill_attention(
                q, kv_layer, block_tables,
                pallas_prefill["context_lens"],
                pallas_prefill["chunk_start"], scale=cfg.attn_scale,
                interpret=pallas_prefill["interpret"],
                **_block_mask(cfg),
            )
        return paged_attention_xla(
            q, kv_layer, block_tables, mask, scale=cfg.attn_scale,
            softcap=cfg.attn_logit_softcap,
        )

    def attend_selected(q_abs, latent, selecting):
        """A packed chunk of a layer that attends over selected keys: the
        latents (and the index keys of a layer that selects) go to their
        pages, then ops/dsa_attention.py selects — or takes the selection
        the last selecting layer left in `selected["carry"]` — and
        attends."""
        nonlocal kv_layer
        span = (write_blocks["ids"], write_blocks["start_off"],
                write_blocks["chunk_lens"])
        with jax.named_scope("kv_stage"):
            pools = {"kv": write_latent_pages_blockwise(
                page_pool(kv_layer), latent[:, 0], *span,
                row_off=rows["row_off"])}
            index = None
            if selecting is not None:
                pools["idx"] = write_latent_pages_blockwise(
                    kv_layer["idx"], selecting["k"][:, 0], *span,
                    row_off=rows["row_off"])
                index = {"q": selecting["q"][:, 0], "w": selecting["w"][:, 0],
                         "pool": pools["idx"]}
        with jax.named_scope("attn"):
            out, selected["carry"] = dsa_prefill_attention(
                q_abs[:, 0], pools["kv"], block_tables,
                selected["context_lens"], selected["chunk_start"],
                rows["row_off"], index=index, selection=selected["carry"],
                scale=cfg.attn_scale, rank=cfg.kv_lora_rank,
                topk=cfg.index_topk)
        kv_layer = pools if selecting is not None else pools["kv"]
        return out[:, None]

    def attend(q, k, v):
        nonlocal kv_layer
        if spec.indexer:
            return attend_selected(q, k, v)
        if rows is not None:
            q, k = q[:, 0], k[:, 0]  # (N, heads, D)
            v = None if v is None else v[:, 0]
        with jax.named_scope("kv_stage"):
            if spec.latent and write_blocks is not None:
                # k is the token's latent (models/llama.py:_mla_attention)
                kv_layer = write_latent_pages_blockwise(
                    kv_layer, k, write_blocks["ids"],
                    write_blocks["start_off"], write_blocks["chunk_lens"],
                    row_off=None if rows is None else rows["row_off"],
                )
            elif spec.latent:
                kv_layer = write_latent_pages(
                    kv_layer, k.reshape(b * t, -1), slot_mapping)
            elif write_blocks is not None and pallas_prefill is not None and (
                    pallas_prefill["write_kernel"]):
                # beside the prefill kernel: the pool keeps its layout
                # through the program (a row-major chunk is its rows end to
                # end, each at a multiple of T)
                kv_layer = paged_kv_write(
                    kv_layer, k.reshape(-1, nkv, hd), v.reshape(-1, nkv, hd),
                    write_blocks["ids"], write_blocks["start_off"],
                    write_blocks["chunk_lens"],
                    (jnp.arange(b, dtype=jnp.int32) * t if rows is None
                     else rows["row_off"]),
                    interpret=pallas_prefill["interpret"],
                )
            elif write_blocks is not None:
                kv_layer = write_kv_pages_blockwise(
                    kv_layer, k, v, write_blocks["ids"],
                    write_blocks["start_off"], write_blocks["chunk_lens"],
                    row_off=None if rows is None else rows["row_off"],
                )
            else:
                kv_layer = write_kv_pages(
                    kv_layer, k.reshape(b * t, nkv, hd),
                    v.reshape(b * t, nkv, hd), slot_mapping,
                )
        with jax.named_scope("attn"):
            if rows is None:
                return _attention(q)
            # attention is the one place that needs rows: it gets the row
            # view it takes from a row-major chunk (one gather), and its
            # result is gathered back. A lone row is its own view.
            if rows["to_rows"] is None:
                return _attention(q[None])[0][:, None]
            out = _attention(q[rows["to_rows"]])
            return out.reshape(-1, *out.shape[2:])[rows["to_packed"]][:, None]

    x = _layer_body(cfg, lp, x, positions, attend, lora, lora_idx,
                    moe_stats, spec, moe_backend, recur)
    return x, kv_layer if recur is None else {"kv": kv_layer, **recur.pools}


def forward(
    cfg: ModelConfig,
    params: dict,
    token_ids: jax.Array,  # (B, T) int32; packed: (N,)
    positions: jax.Array,  # (B, T) int32 logical positions; packed: (N,)
    kv_caches: jax.Array,  # (L, 2, num_blocks, block_size, kvH, D)
    block_tables: jax.Array,  # (B, max_blocks) int32; or a (full, window)
    #   pair, a table a PAGE GROUP (engine/kv_cache.py): window layers
    #   read and write the second. `slot_mapping` and `write_blocks["ids"]`
    #   pair up the same way
    slot_mapping: jax.Array,  # (B*T,) flat slots (padding -> block 0 slots)
    context_lens: jax.Array,  # (B,) tokens resident after this step
    lora: dict | None = None,  # stacked adapter tree (init_lora_params)
    lora_idx: jax.Array | None = None,  # (B,) adapter slot per row; packed:
    #   (N,), the row's slot repeated over its tokens
    write_blocks: dict | None = None,  # {"ids": (B, NBW) written-span pool
    #   blocks, "start_off": (B,), "chunk_lens": (B,)} — when given, chunk
    #   K/V commits via the page-granular read-modify-write
    #   (ops/attention.py:write_kv_pages_blockwise) instead of the per-token
    #   row scatter; the serving prefill path passes this
    backend: str = "xla",  # "xla" | "pallas" | "pallas_interpret" — prefill
    #   attention path; pallas streams pool pages through the paged
    #   flash-prefill kernel and never builds the (B, T, S) mask
    mesh=None,  # required for the pallas backend on a >1-device mesh
    moe_stats: list | None = None,  # MoE models: a list that receives each
    #   layer's routing (see _moe_mlp)
    pack: dict | None = None,  # pack_index(...) — the chunk is PACKED: the
    #   rows' tokens end to end on one axis of N positions
    moe_backend: str = "xla",  # may an MoE layer's experts run the Pallas
    #   touched-experts kernel where the shapes choose it (see _moe_mlp)
    state_slots: tuple | None = None,  # a model with layers that hold state:
    #   (read (B,), write (B,)) — where each row's state slot is read and
    #   where it is written (engine/kv_cache.py:StateSlots); such a model
    #   prefills PACKED chunks only
) -> tuple[jax.Array, jax.Array]:
    """One model step over a token batch. Prefill is (B=1, T=chunk); decode is
    (B=batch, T=1). Returns (hidden (B,T,h), updated kv_caches).

    With `pack` the chunk is the served prefill's layout: everything that
    is linear in tokens (embed, norms, projections, rope, MLP / experts)
    runs on the N packed tokens as (N, 1, h) — the shape a decode step has,
    so `_layer_body` is what it is — whatever the number of rows; only
    attention gets the (B, N) row view it takes from a row-major chunk
    (`_layer`), and the KV write reads the packed k/v through each row's
    offset. Returns hidden (N, 1, h). The speculative verify programs and
    a dp > 1 prefill (rows sharded over replicas) stay row-major."""
    if backend.startswith("pallas") and cfg.attn_logit_softcap:
        raise NotImplementedError(
            "pallas prefill does not support softcapped models (the runner "
            "gates these to the XLA backend)"
        )
    if cfg.selecting_layers and (pack is None or write_blocks is None):
        raise NotImplementedError(
            f"{cfg.model}: layers that attend over selected keys prefill "
            "packed chunks only (ops/dsa_attention.py computes a row from "
            "its slice of the packed axis)")
    rows, row_positions, chunk_start = None, positions, None
    if pack is not None:
        rows = _row_view(
            pack, token_ids.shape[0], _group(block_tables, 0).shape[0])
        row_positions = (
            positions[None] if rows["to_rows"] is None
            else positions[rows["to_rows"]])
        chunk_start = pack["chunk_start"]
        token_ids, positions = token_ids[:, None], positions[:, None]
    with jax.named_scope("embed"):
        x = _embed(cfg, params, token_ids)
    selected = None
    if cfg.selecting_layers:
        # the SELECTION is carried down the layer loop: a layer that owns an
        # indexer leaves it in "carry", the layers behind it read it there
        selected = {"context_lens": context_lens,
                    "chunk_start": pack["chunk_start"], "carry": None}
    if backend.startswith("pallas"):
        # the kernel masks from scalars alone — the scheduler feeds chunks
        # with contiguous positions (scheduler.py: range(start, start+len)),
        # so chunk_start is the first column (a packed chunk brings it per
        # row). No (B, T, S) mask exists.
        masks = {}
        pallas_prefill = {
            "context_lens": context_lens,
            "chunk_start": (
                positions[:, 0] if chunk_start is None else chunk_start),
            "interpret": backend == "pallas_interpret",
            "mesh": mesh,
            "write_kernel": chunk_write_by_kernel(backend, mesh),
        }
    elif selected is not None:
        # (ops/dsa_attention.py masks from the same scalars)
        masks, pallas_prefill = {}, None
    else:
        # attention masks, built once per WINDOW KIND and reused by every
        # layer of that kind (full; a window's sliding variant)
        s_ctx = (_group(block_tables, 0).shape[1]
                 * _page_tokens(cfg, kv_caches))
        masks = {
            w: causal_page_mask(row_positions, context_lens, s_ctx, window=w,
                                **_block_mask(cfg))
            for w in cfg.window_kinds
        }
        pallas_prefill = None
    state = None
    if cfg.state_layers:
        if pack is None or state_slots is None:
            raise NotImplementedError(
                f"{cfg.model}: layers that hold a recurrent state prefill "
                "packed chunks with their rows' state slots only")
        state = {
            "read": state_slots[0], "write": state_slots[1],
            "row_off": pack["row_off"], "tok_row": pack["tok_row"],
            "chunk_lens": context_lens - pack["chunk_start"],
            # a row whose first token the chunk holds starts from zeros:
            # a slot is never trusted from its previous holder
            "fresh": pack["chunk_start"] == 0,
            "interpret": (None if pallas_prefill is None
                          else pallas_prefill["interpret"]),
        }
        tok_slot = None
        if STATE_TAP is not None:
            tok_row, lens = pack["tok_row"], state["chunk_lens"]
            in_row = (jnp.arange(token_ids.shape[0], dtype=jnp.int32)
                      - pack["row_off"][tok_row]) < lens[tok_row]
            tok_slot = jnp.where(in_row, state_slots[1][tok_row], 0)

    # unrolled layer loop (params stay stacked; each layer slices statically).
    # Unrolling instead of lax.scan lets each per-layer KV leaf alias its
    # donated input buffer — the scan alternatives all materialized a second
    # full pool (see init_kv_cache)
    new_kv: list[jax.Array] = []
    n_stats = len(moe_stats or ())
    for i, spec in enumerate(cfg.layer_specs):
        lp = _layer_params(cfg, params, i)
        x, layer_kv = _layer(
            cfg, lp, kv_caches[i], x, positions, block_tables, slot_mapping,
            masks.get(spec.window),
            _lora_layer_slice(lora, i), lora_idx, write_blocks,
            pallas_prefill, moe_stats, rows, spec, moe_backend,
            *(({**state, "tap": _state_tap(i, tok_slot, positions)},)
              if spec.holds_state else ()),
            **({"selected": selected} if spec.indexer else {}),
        )
        new_kv.append(layer_kv)
        if spec.indexer == "full" and SELECTION_TAP is not None:
            _tap_selection(
                cfg.index_layers.index(i), token_ids, positions,
                selection_indices(selected["carry"][:token_ids.shape[0]],
                                  cfg.index_topk))
    if moe_stats is not None:
        _tap_routing(token_ids, positions, moe_stats[n_stats:])
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps,
                     cfg.rms_norm_add_one, scale_f32=cfg.norm_scale_f32)
    return x, tuple(new_kv)


def _refuse_mla(cfg: ModelConfig, what: str) -> None:
    if cfg.latent_layers:
        raise NotImplementedError(
            f"{what} is not implemented for multi-head latent attention "
            f"({cfg.model}): its pool holds one latent a token, not K and V")


def init_staged_kv(
    cfg: ModelConfig, window: int, batch: int, dtype: Any | None = None,
    kv_caches: tuple | None = None,
) -> jax.Array:
    """Staging buffer for one fused decode window: (L, 2, W, B, kvH, D)
    (a model whose pages hold latents: (L, B, W, latent_width)).
    Small (MBs), so carrying it through the window loop is cheap — unlike the
    pool itself (see paged_attention_with_staged).

    A model with layers that hold state carries those layers' pools
    (`kv_caches[i]`, 68 MiB a layer at 17 slots x 64 heads x 128 x 128) through
    the window beside the buffer, as {"kv": the buffer, "state": {layer:
    pools}}: the state advances in place at every step and is handed back
    by `commit_staged_kv`, as the staged K/V are."""
    dt = jnp.dtype(dtype) if dtype is not None else _dtype(cfg)
    if cfg.latent_layers:
        # one latent a token, rows first: (L, B, W, width) — a row's window
        # is one block of the decode kernel, and (b, w) row-major is the
        # commit's slot order
        buf = jnp.zeros(
            (cfg.num_layers, batch, window, cfg.latent_width), dt)
    else:
        buf = jnp.zeros((cfg.num_layers, 2, window, batch,
                         cfg.pool_kv_heads, cfg.head_dim), dt)
    if cfg.index_layers:
        # ... and the window's index keys of the layers that own an
        # indexer, (Lf, B, W, D), committed beside the latents
        return {"kv": buf, "idx": jnp.zeros(
            (len(cfg.index_layers), batch, window, cfg.index_head_dim), dt)}
    if cfg.state_layers:
        # (the buffer keeps a plane for every layer, so a layer's index is
        # its plane; the planes of layers without pages, 0.5 MiB each, stay
        # unused)
        return {"kv": buf, "state": {
            i: _state_pools(kv_caches[i]) for i in cfg.state_layers}}
    return buf


def decode_window_step(
    cfg: ModelConfig,
    params: dict,
    token_ids: jax.Array,  # (B,) this iteration's input token per row
    positions: jax.Array,  # (B,) this iteration's position per row
    kv_caches: tuple[jax.Array, ...],  # read-only pool
    block_tables: jax.Array,  # (B, max_blocks), or a (full, window) pair
    #   (see forward)
    staged: jax.Array,  # (L, 2, W, B, kvH, D) window staging buffer
    step_k: jax.Array,  # scalar int32: iteration index within the window
    hist_len: jax.Array,  # (B,): pool positions < hist_len are history
    backend: str = "xla",  # "xla" | "pallas" (TPU kernel) | "pallas_interpret"
    lora: dict | None = None,  # stacked adapter tree (init_lora_params)
    lora_idx: jax.Array | None = None,  # (B,) adapter slot per row
    hists: tuple | None = None,  # per-layer pre-gathered (hist_k, hist_v)
    mesh=None,  # required for the pallas backend on a >1-device mesh
    moe_stats: list | None = None,  # as in forward
    moe_backend: str = "xla",  # as in forward
    state_slots: tuple | None = None,  # as in forward: the window's FIRST
    #   step reads a row's state at `read` and writes it at `write`, every
    #   later step reads and writes `write`
) -> tuple[jax.Array, jax.Array]:
    """One decode iteration inside a fused window: reads the pool, writes this
    token's K/V into `staged` (not the pool — the pool stays loop-invariant so
    XLA doesn't ping-pong it through the loop carry; see
    ops/attention.py:paged_attention_with_staged). When the runner hoisted the
    loop-invariant history gather out of the window loop, `hists` carries the
    contiguous per-layer (hist_k, hist_v) and the pool is not touched here
    (ops/attention.py:attention_with_hist). Returns (hidden (B, h), staged')."""
    states = staged_idx = chosen = None
    if cfg.index_layers:  # see init_staged_kv
        staged, staged_idx = staged["kv"], staged["idx"]
    if cfg.state_layers:  # see init_staged_kv
        staged, states = staged["kv"], dict(staged["state"])
        state_read = jnp.where(step_k == 0, state_slots[0], state_slots[1])
    window = staged.shape[2]  # (latents: (L, B, W, width), the same axis)
    n_stats = len(moe_stats or ())
    with jax.named_scope("embed"):
        x = _embed(cfg, params, token_ids)[:, None]  # (B, 1, h)
    # staged slot w is attendable once written: w <= k
    staged_mask = jnp.arange(window, dtype=jnp.int32) <= step_k
    if backend == "xla":
        s_ctx = (
            hists[cfg.page_layers[0]][0].shape[1]  # (layer 0 may hold none)
            if hists is not None
            else _group(block_tables, 0).shape[1]
            * _page_tokens(cfg, kv_caches)
        )
        arange = jnp.arange(s_ctx, dtype=jnp.int32)[None, :]
        hist_mask = arange < hist_len[:, None]
        # window layers: the query at `positions` sees only pool history
        # within the window. Staged slots stay globally attendable — they
        # are the most recent `decode_window` positions, always inside any
        # real window (asserted at engine init: window > decode_window)
        hist_masks = {
            w: hist_mask & (arange > (positions - w)[:, None]) if w
            else hist_mask
            for w in cfg.window_kinds
        }

    for i, spec in enumerate(cfg.layer_specs):
        lp = _layer_params(cfg, params, i)
        tables = _group(block_tables, spec.window)

        def attend_latent(q_abs, latent, _v, i=i):
            nonlocal staged
            with jax.named_scope("kv_stage"):
                staged = staged.at[i, :, step_k].set(
                    latent[:, 0].astype(staged.dtype))
            with jax.named_scope("attn"):
                if backend == "xla":
                    return latent_attention_with_staged(
                        q_abs, kv_caches[i], block_tables, hist_mask,
                        jnp.moveaxis(staged[i], 0, 1), staged_mask,
                        scale=cfg.attn_scale, rank=cfg.kv_lora_rank,
                    )
                return mla_decode_attention(
                    q_abs[:, 0], kv_caches[i], block_tables, hist_len,
                    staged[i], step_k, scale=cfg.attn_scale,
                    rank=cfg.kv_lora_rank,
                    interpret=backend == "pallas_interpret",
                )[:, None]

        def attend_selected(q_abs, latent, selecting, i=i):
            """A step of a layer that attends over selected keys: a layer
            that owns an indexer stages its index key beside the latent and
            selects (`chosen`: what the layers behind it read), every such
            layer gathers the selected latents and attends
            (ops/dsa_attention.py)."""
            nonlocal staged, staged_idx, chosen
            with jax.named_scope("kv_stage"):
                staged = staged.at[i, :, step_k].set(
                    latent[:, 0].astype(staged.dtype))
                if selecting is not None:
                    j = cfg.index_layers.index(i)
                    staged_idx = staged_idx.at[j, :, step_k].set(
                        selecting["k"][:, 0].astype(staged_idx.dtype))
            with jax.named_scope("attn"):
                if selecting is not None:
                    chosen = dsa_index_decode(
                        selecting["q"][:, 0], selecting["w"][:, 0],
                        kv_caches[i]["idx"], block_tables, hist_len,
                        staged_idx[j], step_k, topk=cfg.index_topk)
                    _tap_step_selection(
                        j, token_ids, positions, chosen,
                        block_tables.shape[1] * _page_tokens(cfg, kv_caches),
                        hist_len)
                return dsa_decode_attention(
                    q_abs[:, 0], page_pool(kv_caches[i]), block_tables,
                    staged[i], *chosen, scale=cfg.attn_scale,
                    rank=cfg.kv_lora_rank)[:, None]

        def attend(q, k, v, i=i, spec=spec, tables=tables,
                   pool=page_pool(kv_caches[i])):
            nonlocal staged
            with jax.named_scope("kv_stage"):
                staged = staged.at[i, 0, step_k].set(
                    k[:, 0].astype(staged.dtype))
                staged = staged.at[i, 1, step_k].set(
                    v[:, 0].astype(staged.dtype))
            with jax.named_scope("attn"):
                if backend == "xla":
                    h_mask = hist_masks[spec.window]
                    if hists is not None:
                        return attention_with_hist(
                            q, hists[i][0], hists[i][1], h_mask,
                            staged[i, 0], staged[i, 1], staged_mask,
                            scale=cfg.attn_scale,
                            softcap=cfg.attn_logit_softcap,
                        )
                    return paged_attention_with_staged(
                        q, pool, tables, h_mask,
                        staged[i, 0], staged[i, 1], staged_mask,
                        scale=cfg.attn_scale,
                        softcap=cfg.attn_logit_softcap,
                    )
                if cfg.attn_logit_softcap:
                    # self-enforcing invariant (the runner gates these models
                    # to XLA): the decode kernel has no softcap — silently
                    # wrong numerics otherwise
                    raise NotImplementedError(
                        "pallas decode does not support softcapped models"
                    )
                if mesh is not None and mesh.size > 1:
                    # pallas_call has no GSPMD partition rule — shard_map
                    # over (dp, tp) places one kernel instance per device
                    return paged_decode_attention_sharded(
                        mesh, q[:, 0], pool, tables, hist_len,
                        staged[i, 0], staged[i, 1], step_k,
                        scale=cfg.attn_scale,
                        interpret=backend == "pallas_interpret",
                    )[:, None]
                if spec.window:
                    return paged_window_decode_attention(
                        q[:, 0], pool, tables, hist_len,
                        staged[i, 0], staged[i, 1], step_k,
                        scale=cfg.attn_scale, window=spec.window,
                        interpret=backend == "pallas_interpret",
                    )[:, None]
                return paged_decode_attention(
                    q[:, 0], pool, tables, hist_len,
                    staged[i, 0], staged[i, 1], step_k, scale=cfg.attn_scale,
                    interpret=backend == "pallas_interpret",
                )[:, None]

        if spec.holds_state:
            recur = _StepRecurrence(
                states[i], state_read, state_slots[1],
                None if backend == "xla" else backend == "pallas_interpret",
                _state_tap(i, state_slots[1], positions))
        x = _layer_body(
            cfg, lp, x, positions[:, None],
            attend_selected if spec.indexer
            else attend_latent if spec.latent else attend,
            _lora_layer_slice(lora, i), lora_idx, moe_stats, spec,
            moe_backend, recur if spec.holds_state else None,
        )
        if spec.holds_state:
            states[i] = recur.pools
    if moe_stats is not None:
        _tap_routing(token_ids, positions, moe_stats[n_stats:])
    with jax.named_scope("final_norm"):
        x = rms_norm(x[:, 0], params["final_norm"], cfg.rms_norm_eps,
                     cfg.rms_norm_add_one, scale_f32=cfg.norm_scale_f32)
    if states is not None:
        staged = {"kv": staged, "state": states}
    if staged_idx is not None:
        staged = {"kv": staged, "idx": staged_idx}
    return x, staged


def decode_block_step(
    cfg: ModelConfig,
    params: dict,
    token_ids: jax.Array,  # (B, T) each row's open block, T = block_length
    positions: jax.Array,  # (B, T) the block's positions
    kv_caches: tuple[jax.Array, ...],  # read-only pool
    block_tables: jax.Array,  # (B, max_blocks)
    staged: jax.Array,  # (L, 2, W, B, kvH, D) window staging buffer
    slot0: jax.Array,  # scalar int32: the block's first staged slot
    hist_len: jax.Array,  # (B,): pool positions < hist_len are history
    backend: str = "xla",  # as decode_window_step
    hists: tuple | None = None,  # as decode_window_step
    moe_stats: list | None = None,  # as in forward
    moe_backend: str = "xla",  # as in forward
) -> tuple[jax.Array, jax.Array]:
    """`decode_window_step` for a model that generates by diffusion over
    blocks (ModelConfig.block_diffusion): ONE forward pass over each row's
    whole open block inside a fused window. The K/V of all T positions are
    written to the block's staged slots [slot0, slot0 + T) at every pass —
    overwritten by the next pass, final after the pass over the block's
    final tokens — and every query of the block sees the same keys: the
    pool history and the staged slots up to the END of the block. So the T
    queries of a row fold into the GQA group axis of `paged_decode_attention`
    (q_per_kv x T query rows a kv head, one visible range), which reads a
    row's KV once a pass, with `step_k` = the block's last slot. Plain GQA
    layers only (the engine refuses the rest by name). Returns (hidden
    (B, T, h), staged')."""
    b, t = token_ids.shape
    window = staged.shape[2]
    with jax.named_scope("embed"):
        x = _embed(cfg, params, token_ids)  # (B, T, h)
    last = slot0 + (t - 1)
    staged_mask = jnp.arange(window, dtype=jnp.int32) <= last
    if backend == "xla":
        s_ctx = (
            hists[0][0].shape[1] if hists is not None
            else block_tables.shape[1] * kv_caches[0].shape[2])
        hist_mask = (
            jnp.arange(s_ctx, dtype=jnp.int32)[None, :] < hist_len[:, None])

    for i, spec in enumerate(cfg.layer_specs):
        lp = _layer_params(cfg, params, i)

        def attend(q, k, v, i=i):
            nonlocal staged
            with jax.named_scope("kv_stage"):
                kv = jnp.stack([k, v]).swapaxes(1, 2)  # (2, T, B, kvH, D)
                staged = jax.lax.dynamic_update_slice(
                    staged, kv[None].astype(staged.dtype),
                    (i, 0, slot0, 0, 0, 0))
            with jax.named_scope("attn"):
                if backend == "xla":
                    if hists is not None:
                        return attention_with_hist(
                            q, hists[i][0], hists[i][1], hist_mask,
                            staged[i, 0], staged[i, 1], staged_mask,
                            scale=cfg.attn_scale)
                    return paged_attention_with_staged(
                        q, kv_caches[i], block_tables, hist_mask,
                        staged[i, 0], staged[i, 1], staged_mask,
                        scale=cfg.attn_scale)
                nkv, qpk, d = cfg.num_kv_heads, cfg.q_per_kv, cfg.head_dim
                folded = q.reshape(b, t, nkv, qpk, d).swapaxes(1, 2)
                out = paged_decode_attention(
                    folded.reshape(b, nkv * t * qpk, d), kv_caches[i],
                    block_tables, hist_len, staged[i, 0], staged[i, 1],
                    last, scale=cfg.attn_scale,
                    interpret=backend == "pallas_interpret")
                return out.reshape(b, nkv, t, qpk, d).swapaxes(1, 2).reshape(
                    b, t, nkv * qpk, d)

        x = _layer_body(cfg, lp, x, positions, attend, None, None,
                        moe_stats, spec, moe_backend)
    # (a pass's routing goes to BD_TAP with the pass, not to ROUTING_TAP:
    # a mask token at one position is fed by many passes)
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps,
                     cfg.rms_norm_add_one, scale_f32=cfg.norm_scale_f32)
    return x, staged


def bd_transfer(
    bd,  # engine/config.py:BlockDiffusion
    logits: jax.Array,  # (B, T, V) float32: this pass's, at the block
    tokens: jax.Array,  # (B, T) the block's current tokens
    masked: jax.Array,  # (B, T) bool: positions that still hold a mask
    pass_idx: jax.Array,  # scalar int32: the block's pass, from 0
    x0: jax.Array | None = None,  # (B, T) candidates drawn by the caller
    #   (temperature > 0); None: the argmax
    temperature: jax.Array | None = None,  # (B,) beside `x0`
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The transfer rule of generation by diffusion over blocks, a pure
    function of one pass: at every masked position the candidate x0 (the
    argmax of the position's OWN logits: no shift) and its confidence, that
    distribution's probability of x0 in float32 (the softmax at the row's
    temperature where x0 was drawn, the plain softmax under greedy);
    then, with n = the schedule's count for this pass (at most the masks
    left): "sequential" unmasks the leftmost n, "low_confidence_static" the
    n of highest confidence (ties: lowest position), and
    "low_confidence_dynamic" every masked position over the threshold if
    those are at least the schedule's count, else as static. Returns
    (tokens', masked', unmasked (B, T) bool, over (B,) int32: tokens the
    threshold unmasked beyond the schedule's count)."""
    with jax.named_scope("bd_transfer"):
        t = tokens.shape[1]
        scaled = logits
        if x0 is None:
            x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            temp = jnp.where(temperature > 0.0, temperature, 1.0)
            scaled = logits / temp[:, None, None]
        conf = jnp.exp(
            jnp.take_along_axis(scaled, x0[..., None], axis=-1)[..., 0]
            - jax.nn.logsumexp(scaled, axis=-1))
        schedule = jnp.asarray(bd.transfer_schedule, jnp.int32)
        n_s = schedule[jnp.minimum(pass_idx, schedule.shape[0] - 1)]
        n = jnp.minimum(n_s, jnp.sum(masked, axis=1, dtype=jnp.int32))
        if bd.remasking_strategy == "sequential":
            rank = jnp.cumsum(masked, axis=1, dtype=jnp.int32) - 1
        else:
            c = jnp.where(masked, conf, -jnp.inf)
            pos = jnp.arange(t, dtype=jnp.int32)
            # rank by confidence, ties to the lowest position
            ahead = (c[:, None, :] > c[:, :, None]) | (
                (c[:, None, :] == c[:, :, None])
                & (pos[None, None, :] < pos[None, :, None]))
            rank = jnp.sum(ahead, axis=2, dtype=jnp.int32)
        pick = masked & (rank < n[:, None])
        if bd.remasking_strategy == "low_confidence_dynamic":
            high = masked & (conf > bd.confidence_threshold)
            enough = jnp.sum(high, axis=1, dtype=jnp.int32) >= n_s
            pick = jnp.where(enough[:, None], high, pick)
        over = jnp.sum(pick, axis=1, dtype=jnp.int32) - n
        return (jnp.where(pick, x0, tokens), masked & ~pick, pick, over)


def commit_staged_kv(
    kv_caches: tuple[jax.Array, ...],
    staged: jax.Array,  # (L, 2, W, B, kvH, D)
    slot_mapping: jax.Array,  # (B*W,) flat pool slots, row-major (b, w)
    window_layers: tuple[int, ...] = (),  # layers whose slots are the
    #   second of a (full, window) `slot_mapping` pair
) -> tuple[jax.Array, ...]:
    """Scatter a whole window's staged K/V into the (donated) pool, one
    scatter per layer — the only pool write of the fused decode window."""
    states, staged_idx = {}, None
    if isinstance(staged, dict):  # layers that hold state, or that own an
        # indexer: init_staged_kv
        states, staged_idx = staged.get("state", {}), staged.get("idx")
        staged = staged["kv"]
    if staged.ndim == 4:  # latents: (L, B, W, width), rows (b, w) as slots
        def rows_of(buf):
            return buf.reshape(-1, buf.shape[-1])

        new_kv, owners = [], 0
        with jax.named_scope("kv_commit"):
            for i, kv in enumerate(kv_caches):
                if i in states:
                    new_kv.append(states[i])
                    continue
                pages = write_latent_pages(
                    page_pool(kv), rows_of(staged[i]), slot_mapping)
                if isinstance(kv, dict):
                    # a layer that owns an indexer: its index keys beside
                    pages = {"kv": pages, "idx": write_latent_pages(
                        kv["idx"], rows_of(staged_idx[owners]), slot_mapping)}
                    owners += 1
                new_kv.append(pages)
        return tuple(new_kv)
    L, _, w, b, kvh, d = staged.shape
    new_kv: list[jax.Array] = []
    with jax.named_scope("kv_commit"):
        for i in range(L):
            # (a state is advanced in place through the window)
            both = i in states and "kv" in kv_caches[i]
            if i in states and not both:
                new_kv.append(states[i])
                continue
            k_rows = jnp.moveaxis(staged[i, 0], 0, 1).reshape(b * w, kvh, d)
            v_rows = jnp.moveaxis(staged[i, 1], 0, 1).reshape(b * w, kvh, d)
            pages = write_kv_pages(
                page_pool(kv_caches[i]), k_rows, v_rows,
                _group(slot_mapping, i in window_layers))
            new_kv.append({"kv": pages, **states[i]} if both else pages)
    return tuple(new_kv)


def embed_encode(
    cfg: ModelConfig,
    params: dict,
    token_ids: jax.Array,  # (B, T) int32 (rows padded with 0s)
    lengths: jax.Array,  # (B,) true lengths
) -> jax.Array:
    """Plain causal self-attention encode (no paged KV): final-layer hidden
    state at each row's LAST real token, L2-normalized — the /v1/embeddings
    path (vLLM serves decoder embeddings the same way: last-token pooling).
    Returns (B, h) float32."""
    if cfg.state_layers:
        raise NotImplementedError(
            f"{cfg.model}: /v1/embeddings is not implemented for layers that "
            "hold a recurrent state (the encode path has no state slots)")
    b, t = token_ids.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    x = _embed(cfg, params, token_ids)
    masks = {  # (B, T, T) a window kind
        w: causal_page_mask(positions, lengths, t, window=w)
        for w in cfg.window_kinds
    }

    for i, spec in enumerate(cfg.layer_specs):
        lp = _layer_params(cfg, params, i)
        m = masks[spec.window]

        def attend(q, k, v, m=m, spec=spec):
            if spec.latent:  # absorbed queries against the chunk's latents
                k = k[:, :, None]
                return masked_attention(
                    q, k, k, m, scale=cfg.attn_scale)[..., :cfg.kv_lora_rank]
            return masked_attention(
                q, k, v, m, scale=cfg.attn_scale,
                softcap=cfg.attn_logit_softcap,
            )

        x = _layer_body(cfg, lp, x, positions, attend, spec=spec)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps, cfg.rms_norm_add_one,
                 scale_f32=cfg.norm_scale_f32)
    last = jnp.take_along_axis(
        x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1
    )[:, 0].astype(jnp.float32)  # (B, h)
    return last / jnp.maximum(
        jnp.linalg.norm(last, axis=-1, keepdims=True), 1e-9
    )


def forward_sp_prefill(
    cfg: ModelConfig,
    params: dict,
    token_ids: jax.Array,  # (B, T) chunk tokens, T sharded over sp
    positions: jax.Array,  # (B, T) GLOBAL positions of the chunk
    kv_caches: tuple[jax.Array, ...],
    block_tables: jax.Array,  # (B, max_blocks)
    slot_mapping: jax.Array,  # (B*T,) flat pool slots for the chunk
    chunk_lens: jax.Array,  # (B,) real tokens in this chunk per row
    hist_lens: jax.Array,  # (B,) already-resident context before the chunk
    mesh,  # the engine mesh (must carry an sp axis > 1 to be useful)
    lora: dict | None = None,
    lora_idx: jax.Array | None = None,
) -> tuple[jax.Array, tuple[jax.Array, ...]]:
    """The engine's CHUNKED-PREFILL step with the chunk's sequence axis
    sharded over the sp mesh axis: attention is ring attention
    (parallel/ring_attention.py) seeded with the pooled history block, so it
    supports exactly the same chunk-by-chunk contract as `forward` while no
    device ever holds a (T, T) score matrix or the whole chunk's activations.
    Projections / norms / MLP are token-parallel and shard over sp for free
    under GSPMD. Chunk K/V are written to the pool AFTER attention (the ring
    provides the chunk's own causality; the pool provides history).

    Returns (hidden (B, T, h) sp-sharded, updated kv_caches)."""
    from ..parallel.ring_attention import ring_attention

    _refuse_mla(cfg, "sequence-parallel prefill (ring attention)")
    b, t = token_ids.shape
    kv_valid = (
        jnp.arange(t, dtype=jnp.int32)[None, :] < chunk_lens[:, None]
    )  # (B, T) real chunk tokens
    x = _embed(cfg, params, token_ids)
    nkv, hd = cfg.num_kv_heads, cfg.head_dim

    new_kv: list[jax.Array] = []
    for i in range(cfg.num_layers):
        lp = _layer_params(cfg, params, i)

        def attend(q, k, v, i=i):
            hist_k, hist_v = gather_pages(kv_caches[i], block_tables)
            # quantized pools convert to the compute dtype as they stream in
            hist_k = hist_k.astype(q.dtype)
            hist_v = hist_v.astype(q.dtype)
            out = ring_attention(
                mesh, q, k, v, positions, kv_valid, scale=cfg.attn_scale,
                hist_k=hist_k, hist_v=hist_v, hist_len=hist_lens,
            )
            new_kv.append(
                write_kv_pages(
                    kv_caches[i],
                    k.reshape(b * t, nkv, hd),
                    v.reshape(b * t, nkv, hd),
                    slot_mapping,
                )
            )
            return out

        x = _layer_body(
            cfg, lp, x, positions, attend, _lora_layer_slice(lora, i), lora_idx
        )
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps, cfg.rms_norm_add_one,
                 scale_f32=cfg.norm_scale_f32)
    return x, tuple(new_kv)


def forward_context_parallel(
    cfg: ModelConfig,
    params: dict,
    token_ids: jax.Array,  # (B, T) int32, T sharded over the sp mesh axis
    lengths: jax.Array,  # (B,) true lengths (padding rows masked out)
    mesh,  # jax.sharding.Mesh with an "sp" axis
) -> tuple[jax.Array, jax.Array]:
    """Long-context prefill with the SEQUENCE axis sharded over the mesh's sp
    axis: every layer's attention runs as ring attention
    (parallel/ring_attention.py — flash accumulation + ppermute K/V rotation),
    so no device ever materializes the full (T, T) score matrix or the full
    sequence's K/V. Projections/norms/MLP are token-parallel and shard over
    sp for free under GSPMD.

    Returns (hidden (B, T, h) sp-sharded, per-layer stacked KV
    (L, 2, B, T, kvH, D) for the caller to commit into the paged pool).
    The reference inherits this capability from its engines' context-parallel
    attention; this is the TPU-native construction (SURVEY §2.4).
    """
    from ..parallel.ring_attention import ring_attention

    _refuse_mla(cfg, "context-parallel prefill (ring attention)")
    b, t = token_ids.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    kv_valid = positions < lengths[:, None]
    x = _embed(cfg, params, token_ids)

    kv_out: list[jax.Array] = []
    for i in range(cfg.num_layers):
        lp = _layer_params(cfg, params, i)

        def attend(q, k, v):
            kv_out.append(jnp.stack([k, v]).astype(_dtype(cfg)))
            return ring_attention(
                mesh, q, k, v, positions, kv_valid, scale=cfg.head_dim**-0.5
            )

        x = _layer_body(cfg, lp, x, positions, attend)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps, cfg.rms_norm_add_one,
                 scale_f32=cfg.norm_scale_f32)
    return x, jnp.stack(kv_out)


def compute_logits(cfg: ModelConfig, params: dict, hidden: jax.Array) -> jax.Array:
    """hidden: (N, h) -> logits (N, vocab) in float32 (Gemma-2 applies a
    final tanh softcap)."""
    from ..ops.attention import _softcap

    with jax.named_scope("lm_head"):
        if cfg.tie_word_embeddings:
            logits = (hidden @ params["embed"].T).astype(jnp.float32)
        else:
            logits = _mm(hidden, params["lm_head"]).astype(jnp.float32)
        if cfg.mup is not None:
            logits = logits * cfg.mup.lm_head
        return _softcap(logits, cfg.final_logit_softcap)
