"""Model name/path resolution to ModelConfig.

Named presets cover the reference target configs (opt-125m-class tiny
models for CI, Llama-3-8B for the headline benchmark, Llama-3-70B for
pipeline parallel), the benchmarked dense models (qwen3-8b, qwen2-7b), the
sparse-MoE ones (mixtral-8x7b, olmoe-1b-7b; tiny-mixtral and tiny-olmoe
for the CPU) and the latent-attention MoE (kanana-2-30b-a3b as published,
kanana-2-30b-a3b-8l at the benchmark cell's depth, tiny-kanana for the CPU);
a local directory with an HF config.json is parsed directly (zero-egress
environments can't download)."""

from __future__ import annotations

import dataclasses
import json
import math
import os

from ..engine.config import (
    STATE_CONV_TAPS, BlockDiffusion, LayerSpec, ModelConfig, MupSpec,
    RopeSpec)

_KANANA = dict(
    vocab_size=128256, hidden_size=2048, intermediate_size=6144,
    num_heads=32, num_kv_heads=32, head_dim=64, max_model_len=32768,
    rope_theta=1000000.0, rms_norm_eps=1e-6, architecture="deepseek_v3",
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, first_k_dense_replace=1, num_experts=128,
    num_experts_per_tok=6, moe_intermediate_size=768, n_shared_experts=2,
    scoring_func="sigmoid", routed_scaling_factor=2.448, norm_topk_prob=True,
    rope_interleave=True,
)


def laguna_layers(
    layer_types: list[str], heads_per_layer: list[int],
    mlp_layer_types: list[str], rope_parameters: dict, sliding_window: int,
    head_dim: int,
) -> tuple[LayerSpec, ...]:
    """The per-layer list of a `model_type: laguna` config, from its four
    per-layer lists and its `rope_parameters` (one entry a layer kind)."""

    def rope(kind: str) -> RopeSpec:
        rp = rope_parameters[kind]
        rot = int(round(float(rp.get("partial_rotary_factor", 1.0))
                        * head_dim))
        kw = dict(theta=float(rp["rope_theta"]),
                  rotary_dim=0 if rot == head_dim else rot)
        kind_ = rp.get("rope_type", "default")
        if kind_ == "yarn":
            factor = float(rp["factor"])
            af = rp.get("attention_factor")
            kw.update(
                scaling=("yarn", factor, float(rp.get("beta_fast", 32)),
                         float(rp.get("beta_slow", 1)),
                         int(rp["original_max_position_embeddings"])),
                # transformers' default where the config leaves it out
                attention_factor=float(af) if af is not None
                else 0.1 * math.log(factor) + 1.0)
        elif kind_ != "default":
            raise ValueError(
                f"laguna rope_type {kind_!r} is not implemented "
                "(default and yarn are)")
        return RopeSpec(**kw)

    ropes = {kind: rope(kind) for kind in set(layer_types)}
    return tuple(
        LayerSpec(
            window=sliding_window if kind == "sliding_attention" else 0,
            num_heads=nh, rope=ropes[kind], experts=mlp == "sparse")
        for kind, nh, mlp in zip(
            layer_types, heads_per_layer, mlp_layer_types))


# poolside/Laguna-S-2.1 config.json (`model_type: laguna`): 48 layers in
# periods of one full-attention layer (48 query heads; rope over the first
# half of a head, theta 500 000, yarn x128 from 8 192) and three
# sliding-window layers (72 query heads, window 512, default rope, theta
# 10 000), 8 kv heads x 128 everywhere, a per-head output gate; layer 0 a
# dense SwiGLU of 12 288, layers 1.. 256 experts of 1 024, top-10 of
# sigmoid scores renormalised x 2.5, one ungated shared SwiGLU of 1 024;
# vocabulary 100 352 untied; 1 048 576 positions published.
_LAGUNA_ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1},
}


def _laguna_preset(n_layers: int, rope: dict = _LAGUNA_ROPE,
                   heads: tuple[int, int] = (48, 72), **over) -> dict:
    """A laguna preset of `n_layers`: periods of one full layer (`heads[0]`
    query heads) and three window layers (`heads[1]`), a dense layer 0."""
    kinds = ["full_attention" if i % 4 == 0 else "sliding_attention"
             for i in range(n_layers)]
    kw = dict(
        vocab_size=100352, hidden_size=3072, intermediate_size=12288,
        num_layers=n_layers, num_heads=heads[0], num_kv_heads=8,
        head_dim=128, max_model_len=32768, rms_norm_eps=1e-6,
        architecture="laguna", num_experts=256, num_experts_per_tok=10,
        moe_intermediate_size=1024, n_shared_experts=1,
        scoring_func="sigmoid", routed_scaling_factor=2.5,
        norm_topk_prob=True, attn_output_gate="head", sliding_window=512,
    )
    kw.update(over)
    kw["layers"] = laguna_layers(
        kinds, [heads[k != "full_attention"] for k in kinds],
        ["dense"] + ["sparse"] * (n_layers - 1), rope,
        kw["sliding_window"], kw["head_dim"])
    return kw


def solar_open2_layers(n_layers: int, gqa_layers, num_heads: int,
                       ) -> tuple[LayerSpec, ...]:
    """The per-layer list of a `model_type: solar_open2` config: the layers
    named in `gqa_layers` are softmax attention over pages WITHOUT any
    position encoding (`use_rope` false), every other layer is a gated
    delta-rule (KDA) layer that holds a recurrent state; every layer's MLP
    is experts (`first_k_dense_replace` 0)."""
    gqa = set(gqa_layers)
    return tuple(
        LayerSpec(num_heads=num_heads, rope=None, experts=True)
        if i in gqa else
        LayerSpec(num_heads=num_heads, rope=None, experts=True,
                  mixer="linear")
        for i in range(n_layers))


def _solar_open2_preset(n_layers: int, **over) -> dict:
    """upstage/Solar-Open2-250B config.json (`model_type: solar_open2`): 48
    layers in periods of one gated NoPE GQA layer (64 query / 8 kv heads x
    128, an elementwise output gate) and three KDA layers (64 heads x 128
    keys and values, convolution of 4, low-rank gates of rank 128), every
    layer 320 experts of 1 280, top-8 of sigmoid scores + a selection bias,
    renormalised, one ungated shared SwiGLU of 1 280; vocabulary 196 608
    untied; 1 048 576 positions published."""
    kw = dict(
        vocab_size=196608, hidden_size=4096, intermediate_size=10240,
        num_layers=n_layers, num_heads=64, num_kv_heads=8, head_dim=128,
        max_model_len=32768, rms_norm_eps=1e-5, architecture="solar_open2",
        num_experts=320, num_experts_per_tok=8, moe_intermediate_size=1280,
        n_shared_experts=1, scoring_func="sigmoid", moe_selection_bias=True,
        routed_scaling_factor=1.0, norm_topk_prob=True,
        attn_output_gate="element", linear_num_heads=64,
        linear_head_dim=128, linear_value_head_dim=128,
        linear_gate_rank=128,
    )
    kw.update(over)
    kw["layers"] = solar_open2_layers(
        n_layers, range(0, n_layers, 4), kw["num_heads"])
    return kw


def ling_flash_layers(n_layers: int, layer_group_size: int,
                      first_k_dense: int, num_heads: int, rope_theta: float,
                      ) -> tuple[LayerSpec, ...]:
    """The per-layer list of a Ling-3.0-flash config: layer i is a LATENT
    layer (multi-head latent attention, rope on its rope key) where
    (i + 1) % layer_group_size == 0, else a gated delta-rule (KDA) layer
    that holds a recurrent state and has no position encoding; the first
    `first_k_dense` layers carry a dense SwiGLU, the rest experts."""
    rope = RopeSpec(theta=rope_theta)
    return tuple(
        LayerSpec(num_heads=num_heads, rope=rope, mixer="latent",
                  experts=i >= first_k_dense)
        if (i + 1) % layer_group_size == 0 else
        LayerSpec(num_heads=num_heads, rope=None, mixer="linear",
                  experts=i >= first_k_dense)
        for i in range(n_layers))


def _ling_flash_preset(n_layers: int, layer_group_size: int = 6,
                       **over) -> dict:
    """inclusionAI/Ling-3.0-flash-VL config.json, the language model: 42
    layers in periods of five KDA layers (32 heads x 128 keys and values,
    convolution of 4, full-rank gates, the bounded decay gate, beta in
    (0, 1)) and one latent layer (MLA: a 512-value latent and one 64-value
    rope key a token, 32 heads of 128 + 64 (q) and 128 (v), no query latent,
    a per-head output gate); two leading dense SwiGLUs of 6 144, then 512
    experts of 768, top-8 of sigmoid scores + a selection bias under a group
    limit (8 groups, 4 kept), renormalised x 2.5, one ungated shared SwiGLU
    of 768; vocabulary 157 184 untied; 131 072 positions published. The
    vision tower, multi-token prediction and the SwiGLU clamp are not
    served (docs/45-latent-beside-state.md)."""
    kw = dict(
        vocab_size=157184, hidden_size=2560, intermediate_size=6144,
        num_layers=n_layers, num_heads=32, num_kv_heads=32, head_dim=128,
        max_model_len=32768, rope_theta=6000000.0, rms_norm_eps=1e-6,
        architecture="ling_flash", kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, first_k_dense_replace=2,
        num_experts=512, num_experts_per_tok=8, moe_intermediate_size=768,
        n_shared_experts=1, scoring_func="sigmoid", moe_selection_bias=True,
        routed_scaling_factor=2.5, norm_topk_prob=True, n_group=8,
        topk_group=4, attn_output_gate="head", linear_num_heads=32,
        linear_head_dim=128, linear_value_head_dim=128, linear_gate_rank=0,
        linear_gate_form="bounded",
        linear_gate_lower_bound=-5.0, linear_beta_scale=1.0,
        # image_patch_token, video_patch_token, image_start_token,
        # video_start_token
        refused_token_ids=(157157, 156909, 157158, 157160),
    )
    kw.update(over)
    kw["layers"] = ling_flash_layers(
        n_layers, layer_group_size, kw["first_k_dense_replace"],
        kw["num_heads"], kw["rope_theta"])
    return kw


def glm_dsa_layers(indexer_types, mlp_layer_types, num_heads: int,
                   rope_theta: float) -> tuple[LayerSpec, ...]:
    """The per-layer list of a `model_type: glm_moe_dsa` config: every layer
    a LATENT layer that attends over a SELECTION of its keys — its own
    where `indexer_types` says "full" (the layer owns an indexer and an
    index key a token), the nearest full layer's before it where it says
    "shared" — with a dense SwiGLU where `mlp_layer_types` says "dense",
    experts where it says "sparse"."""
    if len(indexer_types) != len(mlp_layer_types):
        raise ValueError(
            f"{len(indexer_types)} indexer_types beside "
            f"{len(mlp_layer_types)} mlp_layer_types")
    rope = RopeSpec(theta=rope_theta)
    return tuple(
        LayerSpec(num_heads=num_heads, rope=rope, mixer="latent",
                  indexer=kind, experts=mlp == "sparse")
        for kind, mlp in zip(indexer_types, mlp_layer_types))


# zai-org/GLM-5.2 config.json: which layers own an indexer — 0-2, then
# every fourth (index_topk_freq 4 behind index_skip_topk_offset 3)
_GLM_52_INDEXER_TYPES = ("full",) * 3 + tuple(
    "full" if (i - 3) % 4 == 3 else "shared" for i in range(3, 78))


def _glm_dsa_preset(indexer_types=_GLM_52_INDEXER_TYPES,
                    first_k_dense: int = 3, **over) -> dict:
    """zai-org/GLM-5.2 config.json (`model_type: glm_moe_dsa`): 78 layers of
    multi-head latent attention (64 heads of 192 + 64 (q) and 256 (v), a
    512-value latent and one 64-value rope key a token, a QUERY latent of
    2 048) over a learned SELECTION of keys — an indexer of 32 heads x 128
    scores every visible key and the best 2 048 are attended; the layers
    `indexer_types` marks "full" own one, the "shared" ones borrow the
    selection of the full layer before them; three leading dense SwiGLUs of
    12 288, then 256 experts of 2 048, top-8 of sigmoid scores + a selection
    bias, renormalised x 2.5, one ungated shared SwiGLU; vocabulary 154 880
    untied; 1 048 576 positions published. The multi-token-prediction layer
    is not served (docs/55-selected-keys.md)."""
    n = len(indexer_types)
    kw = dict(
        vocab_size=154880, hidden_size=6144, intermediate_size=12288,
        num_layers=n, num_heads=64, num_kv_heads=64, head_dim=192,
        max_model_len=32768, rope_theta=8000000.0, rms_norm_eps=1e-5,
        architecture="glm_moe_dsa", kv_lora_rank=512, q_lora_rank=2048,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        index_n_heads=32, index_head_dim=128, index_topk=2048,
        first_k_dense_replace=first_k_dense, num_experts=256,
        num_experts_per_tok=8, moe_intermediate_size=2048,
        n_shared_experts=1, scoring_func="sigmoid",
        routed_scaling_factor=2.5, norm_topk_prob=True,
        rope_interleave=True,
    )
    kw.update(over)
    kw["layers"] = glm_dsa_layers(
        indexer_types,
        ("dense",) * first_k_dense + ("sparse",) * (n - first_k_dense),
        kw["num_heads"], kw["rope_theta"])
    return kw


# Architecture hyperparameters follow the public model cards.
# SDAR's generation settings (JetLM/SDAR-30B-A3B-Chat): the config.json has
# no key for any of them. Block length 4 and 4 passes are the -Chat
# checkpoints' convention (the family also trains blocks of 8, 16, 32, 64:
# other checkpoints), low_confidence_dynamic at 0.9 its published
# generation script's choice for them, 151669 the `<|MASK|>` token its
# tokenizer adds to Qwen3's. Every one of them is `assumed` in the
# benchmark's configuration file.
def _falcon_h1_keys(hf: dict, path: str) -> dict:
    """The `model_type: falcon_h1` keys — the per-layer list (every layer
    holds pages AND a state-space state), the Mamba-2 sizes and the
    maximal-update multipliers — and by name what of the family is not
    built: a config that sets one is refused, not guessed at."""
    n_heads, d_head = hf["mamba_n_heads"], hf["mamba_d_head"]
    refused = {
        "attn_layer_indices": hf.get("attn_layer_indices") is not None,
        "mamba_use_mlp false": not hf.get("mamba_use_mlp", True),
        "rope_scaling": bool(hf.get("rope_scaling")),
        **{f"{k} true": bool(hf.get(k)) for k in (
            "attention_bias", "mlp_bias", "projectors_bias",
            "mamba_proj_bias")},
        "mamba_conv_bias false": not hf.get("mamba_conv_bias", True),
        "mamba_norm_before_gate true": bool(
            hf.get("mamba_norm_before_gate")),
        "mamba_rms_norm false": not hf.get("mamba_rms_norm", True),
        f"mamba_d_conv != {STATE_CONV_TAPS}": hf.get(
            "mamba_d_conv", STATE_CONV_TAPS) != STATE_CONV_TAPS,
        "mamba_n_heads x mamba_d_head != mamba_d_ssm":
        n_heads * d_head != hf["mamba_d_ssm"],
        "mamba_n_heads not a multiple of mamba_n_groups":
        n_heads % hf["mamba_n_groups"] != 0,
        "hidden_act != silu": hf.get("hidden_act", "silu") != "silu",
    }
    hit = [k for k, v in refused.items() if v]
    if hit:
        raise ValueError(
            f"falcon_h1 config {path} sets {', '.join(hit)}: "
            "not implemented")
    # assumed (no key): rope over the whole head in the half-rotation form
    # (rotate-half), as the family's other models; the interleaved form is
    # not taken
    rope = RopeSpec(theta=float(hf.get("rope_theta", 10000.0)))
    mlp = hf.get("mlp_multipliers") or (1.0, 1.0)
    return dict(
        layers=(LayerSpec(num_heads=hf["num_attention_heads"], rope=rope,
                          recurrence="ssd"),) * hf["num_hidden_layers"],
        ssm_num_heads=n_heads, ssm_head_dim=d_head,
        ssm_state_dim=hf["mamba_d_state"],
        ssm_num_groups=hf["mamba_n_groups"],
        mup=MupSpec(
            embedding=float(hf.get("embedding_multiplier", 1.0)),
            lm_head=float(hf.get("lm_head_multiplier", 1.0)),
            attention_in=float(hf.get("attention_in_multiplier", 1.0)),
            attention_out=float(hf.get("attention_out_multiplier", 1.0)),
            key=float(hf.get("key_multiplier", 1.0)),
            ssm_in=float(hf.get("ssm_in_multiplier", 1.0)),
            ssm_out=float(hf.get("ssm_out_multiplier", 1.0)),
            ssm=tuple(float(m) for m in (
                hf.get("ssm_multipliers") or (1.0,) * 5)),
            mlp_gate=float(mlp[0]), mlp_down=float(mlp[1])),
    )


# tiiuae/Falcon-H1-34B-Instruct config.json (`model_type: falcon_h1`), the
# keys `_falcon_h1_keys` reads, as published
_FALCON_H1_34B = {
    "model_type": "falcon_h1", "vocab_size": 261120, "hidden_size": 5120,
    "intermediate_size": 21504, "num_hidden_layers": 72,
    "num_attention_heads": 20, "num_key_value_heads": 4, "head_dim": 128,
    "rope_theta": 100000000000, "rope_scaling": None, "rms_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "tie_word_embeddings": False,
    "attention_bias": False, "mlp_bias": False, "projectors_bias": False,
    "attn_layer_indices": None, "hidden_act": "silu",
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_d_state": 256,
    "mamba_expand": 2, "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
    "embedding_multiplier": 5.656854249492381,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845,
}


def _falcon_h1_preset(n_layers: int, **over) -> dict:
    """A Falcon-H1 preset of `n_layers` at the 34B's published keys (every
    layer alike: Mamba-2 heads beside attention heads, one residual), 32 768
    positions served; `over` replaces published keys (the CPU's toy
    widths)."""
    hf = {**_FALCON_H1_34B, "num_hidden_layers": n_layers,
          "max_position_embeddings": 32768, **over}
    heads = hf["num_attention_heads"]
    return dict(
        _falcon_h1_keys(hf, "preset"), architecture="falcon_h1",
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"], num_layers=n_layers,
        num_heads=heads, num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["head_dim"], rope_theta=float(hf["rope_theta"]),
        rms_norm_eps=hf["rms_norm_eps"],
        max_model_len=hf["max_position_embeddings"])


def _olmo_hybrid_keys(hf: dict, path: str) -> dict:
    """The `model_type: olmo_hybrid` keys — `layer_types`, the five
    `linear_*` keys, `rope_parameters` — as the per-layer list and the delta
    rule's geometry, and by name what of the family is not built: a config
    that sets one is refused, not guessed at. `linear_attention`: Gated
    DeltaNet (arXiv:2412.06464), ONE decay gate a head, keys and values of
    their own widths, a SiLU output gate; `full_attention`: as many
    key-value heads as the config says, a flat QK-norm; both in OLMo-2's
    post-norm block."""
    kinds = tuple(hf["layer_types"])
    heads = hf["linear_num_key_heads"]
    rope = (hf.get("rope_parameters") or {})
    refused = {
        "layer_types other than linear_attention / full_attention": bool(
            set(kinds) - {"linear_attention", "full_attention"}),
        "layer_types of another length than num_hidden_layers":
        len(kinds) != hf["num_hidden_layers"],
        "linear_num_value_heads != linear_num_key_heads":
        hf["linear_num_value_heads"] != heads,
        f"linear_conv_kernel_dim != {STATE_CONV_TAPS}": hf.get(
            "linear_conv_kernel_dim", STATE_CONV_TAPS) != STATE_CONV_TAPS,
        "linear_allow_neg_eigval false": not hf.get(
            "linear_allow_neg_eigval", True),
        # assumed: `rope_theta: null` says NO position encoding; a theta is
        # a rope in the full layers, which is not the published model
        "rope_parameters.rope_theta": rope.get("rope_theta") is not None,
        "rope_scaling": bool(hf.get("rope_scaling")),
        "attention_bias true": bool(hf.get("attention_bias")),
        "hidden_act != silu": hf.get("hidden_act", "silu") != "silu",
    }
    hit = [k for k, v in refused.items() if v]
    if hit:
        raise ValueError(
            f"olmo_hybrid config {path} sets {', '.join(hit)}: "
            "not implemented")
    return dict(
        layers=tuple(
            LayerSpec(num_heads=hf["num_attention_heads"], rope=None,
                      mixer="linear" if kind == "linear_attention"
                      else "softmax")
            for kind in kinds),
        linear_num_heads=heads, linear_head_dim=hf["linear_key_head_dim"],
        linear_value_head_dim=hf["linear_value_head_dim"],
        # assumed (no key): the gates project at full rank, the decay gate
        # is unbounded (-exp(A_log) softplus), the output gate goes through
        # SiLU — the gated delta rule's public implementations' defaults
        linear_gate_rank=0, linear_gate_per="head",
        linear_output_gate="silu", linear_beta_scale=2.0,
        # OLMo-2's block and norms
        qk_norm_flat=True, post_norms_only=True, norm_scale_f32=True,
        # a page holds whole sublane tiles of kv heads (30 -> 32; the toy
        # preset's 6 -> 8): ModelConfig.page_kv_heads
        page_kv_heads=_whole_tiles(hf["num_key_value_heads"]),
    )


def _whole_tiles(kv_heads: int) -> int:
    """The kv heads a page holds so that its (kv heads, head_dim) rows fill
    whole HBM tiles at every pool dtype the engine serves: the power of two
    that holds them up to 8, multiples of 32 above."""
    if kv_heads <= 8:
        return 1 << (kv_heads - 1).bit_length()
    return kv_heads + (-kv_heads) % 32


# allenai/Olmo-Hybrid-7B config.json (`model_type: olmo_hybrid`), the keys
# `_olmo_hybrid_keys` reads, as published
_OLMO_HYBRID_7B = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30, "head_dim": None,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": (("linear_attention",) * 3 + ("full_attention",)) * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}


def _olmo_hybrid_preset(n_layers: int, **over) -> dict:
    """An Olmo-Hybrid preset of the first `n_layers` (periods L L L F) at
    the 7B's published keys, 32 768 positions served; `over` replaces published
    keys (the CPU's toy widths)."""
    hf = {**_OLMO_HYBRID_7B, "num_hidden_layers": n_layers,
          "max_position_embeddings": 32768, **over}
    hf["layer_types"] = tuple(hf["layer_types"])[:n_layers]
    heads = hf["num_attention_heads"]
    return dict(
        _olmo_hybrid_keys(hf, "preset"), architecture="olmo_hybrid",
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"], num_layers=n_layers,
        num_heads=heads, num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["head_dim"] or hf["hidden_size"] // heads,
        rms_norm_eps=hf["rms_norm_eps"],
        max_model_len=hf["max_position_embeddings"])


_SDAR_GENERATION = BlockDiffusion(
    block_length=4, denoising_steps=4,
    remasking_strategy="low_confidence_dynamic", confidence_threshold=0.9,
    mask_token_id=151669)


def _sdar_preset(n_layers: int) -> dict:
    """JetLM/SDAR-30B-A3B-Chat config.json (`model_type: sdar_moe`): the
    Qwen3-MoE decoder key for key — 48 layers alike, 32 x 128 query heads
    over 4 kv heads with a per-head q/k RMSNorm, 128 experts of 768, top-8
    of a softmax renormalised, untied head — under the block mask, with
    generation by diffusion over blocks (`block_diffusion`)."""
    return dict(
        vocab_size=151936, hidden_size=2048, intermediate_size=768,
        num_layers=n_layers, num_heads=32, num_kv_heads=4, head_dim=128,
        max_model_len=32768, rope_theta=1000000.0, rms_norm_eps=1e-6,
        architecture="qwen3moe", qk_norm=True, num_experts=128,
        num_experts_per_tok=8, norm_topk_prob=True,
        block_diffusion=_SDAR_GENERATION)


PRESETS: dict[str, dict] = {
    "tiny-llama": dict(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, max_model_len=256,
        dtype="float32",
    ),
    # CI-class small model (stands in for facebook/opt-125m in the reference's
    # minikube tests, tests/e2e/run-k8s-routing-test.sh)
    "debug-125m": dict(
        vocab_size=32000, hidden_size=768, intermediate_size=2048,
        num_layers=12, num_heads=12, num_kv_heads=12, head_dim=64,
        max_model_len=2048, rope_theta=10000.0,
    ),
    "llama-1b": dict(
        vocab_size=128256, hidden_size=2048, intermediate_size=8192,
        num_layers=16, num_heads=32, num_kv_heads=8, head_dim=64,
        max_model_len=8192, rope_theta=500000.0,
    ),
    # Llama-3.2-3B shape: the biggest bf16 preset that fits ONE v5e chip
    # (≈6.0 GiB weights) with KV headroom — the north-star bench model
    "llama-3b": dict(
        vocab_size=128256, hidden_size=3072, intermediate_size=8192,
        num_layers=28, num_heads=24, num_kv_heads=8, head_dim=128,
        max_model_len=8192, rope_theta=500000.0, tie_word_embeddings=True,
    ),
    "llama-3-8b": dict(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
        max_model_len=8192, rope_theta=500000.0,
    ),
    "llama-3-70b": dict(
        vocab_size=128256, hidden_size=8192, intermediate_size=28672,
        num_layers=80, num_heads=64, num_kv_heads=8, head_dim=128,
        max_model_len=8192, rope_theta=500000.0,
    ),
    "qwen2-7b": dict(
        vocab_size=152064, hidden_size=3584, intermediate_size=18944,
        num_layers=28, num_heads=28, num_kv_heads=4, head_dim=128,
        max_model_len=8192, rope_theta=1000000.0, attention_bias=True,
        architecture="qwen2",
    ),
    "qwen3-8b": dict(
        vocab_size=151936, hidden_size=4096, intermediate_size=12288,
        num_layers=36, num_heads=32, num_kv_heads=8, head_dim=128,
        max_model_len=8192, rope_theta=1000000.0, architecture="qwen3",
        qk_norm=True,
    ),
    "tiny-gemma": dict(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=24, max_model_len=256,
        dtype="float32", architecture="gemma", hidden_act="gelu_tanh",
        rms_norm_add_one=True, scale_embeddings=True,
        tie_word_embeddings=True, rms_norm_eps=1e-6,
    ),
    "gemma-7b": dict(
        vocab_size=256000, hidden_size=3072, intermediate_size=24576,
        num_layers=28, num_heads=16, num_kv_heads=16, head_dim=256,
        max_model_len=8192, rope_theta=10000.0, architecture="gemma",
        hidden_act="gelu_tanh", rms_norm_add_one=True, scale_embeddings=True,
        tie_word_embeddings=True, rms_norm_eps=1e-6,
    ),
    "gemma2-9b": dict(
        vocab_size=256000, hidden_size=3584, intermediate_size=14336,
        num_layers=42, num_heads=16, num_kv_heads=8, head_dim=256,
        max_model_len=8192, rope_theta=10000.0, architecture="gemma2",
        hidden_act="gelu_tanh", rms_norm_add_one=True, scale_embeddings=True,
        tie_word_embeddings=True, rms_norm_eps=1e-6, sandwich_norms=True,
        attn_logit_softcap=50.0, final_logit_softcap=30.0,
        query_pre_attn_scalar=224,  # hidden/heads = 3584/16 (NOT head_dim)
        sliding_window=4096,
        sliding_window_pattern=2,
    ),
    "mixtral-8x7b": dict(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
        max_model_len=8192, rope_theta=1000000.0, architecture="mixtral",
        num_experts=8, num_experts_per_tok=2,
    ),
    "tiny-mixtral": dict(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, max_model_len=256,
        dtype="float32", architecture="mixtral", num_experts=4,
        num_experts_per_tok=2,
    ),
    # allenai/OLMoE-1B-7B-0125-Instruct config.json: 64 experts of width
    # 1024, 8 per token with raw softmax mass, MHA 16 x 128 (head_dim is
    # hidden/heads by the family's convention), RMSNorm over the flat q/k
    # projections with the Llama norm order (OlmoeRMSNorm), pre-norms
    "olmoe-1b-7b": dict(
        vocab_size=50304, hidden_size=2048, intermediate_size=1024,
        num_layers=16, num_heads=16, num_kv_heads=16, head_dim=128,
        max_model_len=4096, rope_theta=10000.0, rms_norm_eps=1e-5,
        architecture="olmoe", num_experts=64, num_experts_per_tok=8,
        norm_topk_prob=False, qk_norm_flat=True,
    ),
    "tiny-olmoe": dict(
        vocab_size=512, hidden_size=64, intermediate_size=32, num_layers=2,
        num_heads=4, num_kv_heads=4, head_dim=16, max_model_len=256,
        dtype="float32", architecture="olmoe", num_experts=8,
        num_experts_per_tok=2, norm_topk_prob=False, qk_norm_flat=True,
    ),
    # kakaocorp/kanana-2-30b-a3b-instruct-2601 config.json (model_type
    # deepseek_v3): MLA with a 512-value latent and one 64-value rope key a
    # token, 32 heads of 128 + 64 (q) and 128 (v), no query latent; layer 0
    # a dense SwiGLU of 6144, layers 1.. 128 experts of 768, top-6 of
    # sigmoid scores + a selection bias, weights renormalised x 2.448, one
    # shared SwiGLU of 2 x 768. num_kv_heads / head_dim are the published keys
    # (the serving path reads neither, engine/config.py)
    "kanana-2-30b-a3b": dict(**_KANANA, num_layers=48),
    # the benchmark cell's cut: layer 0 dense + 7 expert layers (one
    # pipeline stage of the published 48), every width as published
    "kanana-2-30b-a3b-8l": dict(**_KANANA, num_layers=8),
    "tiny-kanana": dict(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=3,
        num_heads=4, num_kv_heads=4, head_dim=8, max_model_len=256,
        dtype="float32", architecture="deepseek_v3", rms_norm_eps=1e-6,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, first_k_dense_replace=1, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=32, n_shared_experts=1,
        scoring_func="sigmoid", routed_scaling_factor=2.448,
        norm_topk_prob=True, rope_interleave=True,
    ),
    "laguna-s-2.1": _laguna_preset(48),
    # the benchmark cell's cut: ONE chip of a four-chip expert-parallel
    # host that is the first pipeline stage — layer 0 (full, dense) and
    # layers 1-8 (two whole periods S S S F S S S F), experts 0-63 of the
    # 256 the router scores, vocabulary rows 0-25 087; every width as
    # published, 32 768 positions served
    "laguna-s-2.1-ep4-9l": _laguna_preset(
        9, num_experts_held=64, first_expert_held=0, vocab_size=25088),
    # two periods at toy widths (float32): window 16, heads 4 (full) and 6
    # (window) x 16, 2 kv heads, a dense layer 0, 16 experts top-4 with
    # experts 4-7 held, yarn x8 from 64 positions
    "tiny-laguna": _laguna_preset(
        9, heads=(4, 6), vocab_size=512, hidden_size=64,
        intermediate_size=128, num_kv_heads=2, head_dim=16,
        max_model_len=512, dtype="float32", num_experts=16,
        num_experts_per_tok=4, num_experts_held=4, first_expert_held=4,
        moe_intermediate_size=32, sliding_window=16,
        rope={
            "full_attention": {
                **_LAGUNA_ROPE["full_attention"], "factor": 8,
                "original_max_position_embeddings": 64,
                "attention_factor": 1.2079441541679836},  # 0.1 ln 8 + 1
            "sliding_attention": _LAGUNA_ROPE["sliding_attention"]}),
    "sdar-30b-a3b-chat": _sdar_preset(48),
    # the benchmark cell's cut: one pipeline stage of six, eight whole
    # layers with all 128 experts and the whole vocabulary, every width as
    # published
    "sdar-30b-a3b-chat-8l": _sdar_preset(8),
    # toy widths (float32); a threshold low enough that BOTH branches of
    # the dynamic rule fire with random weights
    "tiny-sdar": dict(
        vocab_size=512, hidden_size=64, intermediate_size=32, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, max_model_len=512,
        dtype="float32", architecture="qwen3moe", qk_norm=True,
        rms_norm_eps=1e-6, num_experts=8, num_experts_per_tok=2,
        norm_topk_prob=True,
        block_diffusion=BlockDiffusion(
            block_length=4, denoising_steps=4,
            remasking_strategy="low_confidence_dynamic",
            confidence_threshold=0.0031, mask_token_id=511)),
    "solar-open2-250b": _solar_open2_preset(48),
    # the benchmark cell's cut: ONE chip of an eight-chip expert-parallel
    # host that is the first pipeline stage — layers 0-7 (two whole
    # periods G K K K G K K K), experts 0-39 of the 320 the router scores,
    # vocabulary rows 0-24 575; every width as published, 32 768 positions
    "solar-open2-250b-ep8-8l": _solar_open2_preset(
        8, num_experts_held=40, first_expert_held=0, vocab_size=24576),
    # two periods at toy widths (float32): 4 heads x 16 in both mixers, 2
    # kv heads, gates of rank 8, 16 experts top-4 with experts 4-7 held
    "tiny-solar-open2": _solar_open2_preset(
        8, vocab_size=512, hidden_size=64, intermediate_size=128,
        num_heads=4, num_kv_heads=2, head_dim=16, max_model_len=512,
        dtype="float32", num_experts=16, num_experts_per_tok=4,
        num_experts_held=4, first_expert_held=4, moe_intermediate_size=32,
        linear_num_heads=4, linear_head_dim=16, linear_value_head_dim=16,
        linear_gate_rank=8),
    "falcon-h1-34b": _falcon_h1_preset(72),
    # the benchmark cell's cut: ONE pipeline stage of twelve — layers 0-5
    # (every layer is alike: the pattern's period is one), every width, all
    # heads and the whole vocabulary as published; this stage also carries
    # the final norm and the head
    "falcon-h1-34b-6l": _falcon_h1_preset(6),
    # three layers at toy widths (float32), every ratio kept: GQA group 2,
    # 2 groups of B / C over 4 state-space heads, a state dim (16) that is
    # not the head dim (8), 4 taps, every multiplier as published
    "tiny-falcon-h1": dict(_falcon_h1_preset(
        3, vocab_size=512, hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        mamba_n_heads=4, mamba_d_head=8, mamba_d_ssm=32, mamba_d_state=16,
        max_position_embeddings=512), dtype="float32"),
    "ling-3.0-flash": _ling_flash_preset(42),
    # the benchmark cell's cut: ONE chip of a four-chip expert-parallel
    # host that is the first pipeline stage — layers 0-6 with ONE leading
    # dense layer (K(dense) K K K K M K: one whole period of expert
    # layers, 5 KDA : 1 MLA), experts 0-127 of the 512 the router scores
    # (groups 0 and 1 whole), vocabulary rows 0-39 295; every width as
    # published, 32 768 positions served
    "ling-3.0-flash-ep4-7l": _ling_flash_preset(
        7, first_k_dense_replace=1, num_experts_held=128,
        first_expert_held=0, vocab_size=39296),
    # one period at toy widths (float32): 4 heads x 16 in both mixers, a
    # 32-value latent + an 8-value rope key, a dense layer 0 and 6 expert
    # layers, 16 experts in 4 groups of 4 (2 groups kept, top-4) with groups
    # 1 and 2 (experts 4-11) held
    "tiny-ling-flash": _ling_flash_preset(
        7, vocab_size=512, hidden_size=64, intermediate_size=128,
        num_heads=4, num_kv_heads=4, head_dim=16, max_model_len=512,
        dtype="float32", kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, first_k_dense_replace=1,
        num_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2,
        num_experts_held=8, first_expert_held=4, moe_intermediate_size=32,
        linear_num_heads=4, linear_head_dim=16, linear_value_head_dim=16,
        refused_token_ids=()),
    "olmo-hybrid-7b": _olmo_hybrid_preset(32),
    # the benchmark cell's cut: ONE pipeline stage — layers 0-11 (three
    # whole periods L L L F: 9 Gated DeltaNet layers of 30 heads x 96 keys
    # x 192 values, 3 full-attention layers of 30 query and 30 key-value
    # heads x 128), every head, every width and the whole vocabulary as
    # published; this stage also carries the final norm and the head
    "olmo-hybrid-7b-12l": _olmo_hybrid_preset(12),
    # two periods at toy widths (float32) that keep the lane problem's
    # SHAPE: 6 heads (no multiple of 4) x 12 keys x 24 values (a key width
    # that is not the value width, neither a whole number of lanes), 6
    # key-value heads x 16 in the full layers
    "tiny-olmo-hybrid": dict(_olmo_hybrid_preset(
        8, vocab_size=512, hidden_size=96, intermediate_size=128,
        num_attention_heads=6, num_key_value_heads=6,
        linear_num_key_heads=6, linear_num_value_heads=6,
        linear_key_head_dim=12, linear_value_head_dim=24,
        max_position_embeddings=512), dtype="float32"),
    "glm-5.2": _glm_dsa_preset(),
    # the benchmark cell's cut: ONE chip (shard 0 of sixteen that share each
    # layer's experts) of the pipeline stage that holds published layers 2-8
    # — the last leading dense layer (full), then shared shared shared full
    # shared shared: a whole indexer period and more — experts 0-15 of the
    # 256 the router scores, vocabulary rows 0-19 359; every width as
    # published, 32 768 positions served
    "glm-5.2-ep16-7l": _glm_dsa_preset(
        ("full", "shared", "shared", "shared", "full", "shared", "shared"),
        first_k_dense=1, num_experts_held=16, first_expert_held=0,
        vocab_size=19360),
    # toy widths (float32) whose top-k (16) lies UNDER every test's context:
    # 4 heads of 16 + 8 (q) and 16 (v), a 32-value latent, a query latent of
    # 48, an indexer of 2 heads x 16 (rope on 8), a dense layer 0 (full),
    # then shared shared full shared; 8 experts, top-2, experts 2-5 held
    "tiny-glm-dsa": _glm_dsa_preset(
        ("full", "shared", "shared", "full", "shared"), first_k_dense=1,
        vocab_size=512, hidden_size=64, intermediate_size=128, num_heads=4,
        num_kv_heads=4, head_dim=16, max_model_len=512, dtype="float32",
        kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, index_n_heads=2,
        index_head_dim=16, index_topk=16, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=32,
        num_experts_held=4, first_expert_held=2),
}

_ARCH_MAP = {
    "LlamaForCausalLM": "llama",
    "MistralForCausalLM": "llama",
    "Qwen2ForCausalLM": "qwen2",
    "Qwen3ForCausalLM": "qwen3",
    "Phi3ForCausalLM": "phi3",
    "Qwen3MoeForCausalLM": "qwen3moe",
    "OlmoeForCausalLM": "olmoe",
    "Olmo2ForCausalLM": "olmo2",
    "MixtralForCausalLM": "mixtral",
    "GemmaForCausalLM": "gemma",
    "Gemma2ForCausalLM": "gemma2",
    "DeepseekV3ForCausalLM": "deepseek_v3",
    "LagunaForCausalLM": "laguna",
    "SolarOpen2ForCausalLM": "solar_open2",
}


def resolve_model_config(
    model: str,
    max_model_len: int | None = None,
    dtype: str | None = None,
    quantization: str | None = None,
) -> ModelConfig:
    """model: a preset name, or a local HF checkpoint dir (config.json)."""
    if model in PRESETS:
        kw = dict(PRESETS[model])
        kw["model"] = model
    elif os.path.isdir(model) and os.path.exists(os.path.join(model, "config.json")):
        kw = _from_hf_config(model)
    else:
        raise ValueError(
            f"unknown model '{model}': not a preset "
            f"({', '.join(PRESETS)}) and not a local checkpoint dir"
        )
    if max_model_len is not None:
        kw["max_model_len"] = max_model_len
    if dtype is not None:
        kw["dtype"] = dtype
    if quantization is not None:
        kw["quantization"] = quantization
    kw.setdefault("dtype", "bfloat16")
    return ModelConfig(**kw)


def _deepseek_v3_keys(hf: dict, path: str) -> dict:
    """The DeepSeek-V3-family keys (Kanana-2), and by name what of the
    family is not implemented: a config that sets one is refused, not
    guessed at."""
    n_group = hf.get("n_group") or 1
    refused = {
        "q_lora_rank (a query latent is served for `model_type: "
        "glm_moe_dsa`, models/registry.py:_glm_moe_dsa_keys, whose tree has "
        "attn.wq_a / q_a_norm / wq_b; a deepseek_v3 checkpoint's q_a_proj "
        "has no leaf here)": hf.get("q_lora_rank") is not None,
        "n_group that does not divide n_routed_experts": bool(
            hf["n_routed_experts"] % n_group),
        "topk_group outside 1..n_group": not (
            1 <= (hf.get("topk_group") or 1) <= n_group),
        "rope_scaling (yarn and its mscale)": bool(hf.get("rope_scaling")),
        "moe_layer_freq != 1": (hf.get("moe_layer_freq") or 1) != 1,
        "scoring_func other than sigmoid": hf.get(
            "scoring_func", "sigmoid") != "sigmoid",
        "attention_bias": bool(hf.get("attention_bias")),
    }
    hit = [k for k, v in refused.items() if v]
    if hit:
        raise ValueError(
            f"deepseek_v3 config {path} sets {', '.join(hit)}: not "
            "implemented (multi-head latent attention is served with a "
            "direct query projection, groups of equal size and plain rope)"
        )
    return dict(
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        first_k_dense_replace=hf.get("first_k_dense_replace", 0),
        num_experts=hf["n_routed_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        n_shared_experts=hf.get("n_shared_experts") or 0,
        scoring_func="sigmoid",
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        rope_interleave=bool(hf.get("rope_interleave", True)),
        n_group=n_group, topk_group=hf.get("topk_group") or 1,
    )


def _glm_moe_dsa_keys(hf: dict, path: str) -> dict:
    """The `model_type: glm_moe_dsa` keys (GLM-5.2): DeepSeek-V3's latent
    attention WITH a query latent, an indexer a "full" layer, and the
    per-layer lists that say who selects and who borrows; and by name what
    of the family is not served: a config that sets one is refused, not
    guessed at. (The engine refuses the CHECKPOINT beside such a config at
    start — engine/model_runner.py — its tensor names were never checked
    against a published one.)"""
    rope = hf.get("rope_parameters") or {}
    n = hf["num_hidden_layers"]
    kinds = hf.get("indexer_types") or []
    mlps = hf.get("mlp_layer_types") or (
        ["dense"] * hf.get("first_k_dense_replace", 0)
        + ["sparse"] * (n - hf.get("first_k_dense_replace", 0)))
    dense = [m == "dense" for m in mlps]
    k_dense = sum(dense)
    refused = {
        "index_topk_pattern (a top-k that differs by layer)": hf.get(
            "index_topk_pattern") is not None,
        "rope_scaling": bool(hf.get("rope_scaling")) or rope.get(
            "rope_type", "default") != "default",
        "index_kpool keys (an indexer over pooled keys)": any(
            k.startswith("index_kpool") for k in hf),
        "indexer_types that is not one entry a layer": len(kinds) != n,
        "indexer_types that does not start with 'full'": bool(
            kinds) and kinds[0] != "full",
        "dense layers behind a sparse one": dense != sorted(
            dense, reverse=True),
        "q_lora_rank null": not hf.get("q_lora_rank"),
        "n_group != 1": (hf.get("n_group") or 1) != 1,
        "topk_group != 1": (hf.get("topk_group") or 1) != 1,
        "moe_layer_freq != 1": (hf.get("moe_layer_freq") or 1) != 1,
        "scoring_func other than sigmoid": hf.get(
            "scoring_func", "sigmoid") != "sigmoid",
        "attention_bias": bool(hf.get("attention_bias")),
    }
    hit = [k for k, v in refused.items() if v]
    if hit:
        raise ValueError(
            f"glm_moe_dsa config {path} sets {', '.join(hit)}: not "
            "implemented (docs/55-selected-keys.md says what is served)")
    theta = float(rope.get("rope_theta", hf.get("rope_theta", 10000.0)))
    return dict(
        layers=glm_dsa_layers(kinds, mlps, hf["num_attention_heads"], theta),
        rope_theta=theta,
        kv_lora_rank=hf["kv_lora_rank"], q_lora_rank=hf["q_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        index_n_heads=hf["index_n_heads"],
        index_head_dim=hf["index_head_dim"], index_topk=hf["index_topk"],
        first_k_dense_replace=k_dense,
        num_experts=hf["n_routed_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        n_shared_experts=hf.get("n_shared_experts") or 0,
        scoring_func="sigmoid",
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        rope_interleave=bool(hf.get("rope_interleave", True)),
    )


def _laguna_keys(hf: dict, path: str) -> dict:
    """The `model_type: laguna` keys, and by name what of the family is not
    served: a config that sets one is refused, not guessed at."""
    n = hf["num_hidden_layers"]
    kinds = hf.get("layer_types") or ["full_attention"] * n
    mlp_kinds = hf.get("mlp_layer_types") or [
        "dense" if i in (hf.get("mlp_only_layers") or []) else "sparse"
        for i in range(n)]
    gating = hf.get("gating_types") or [
        "per_head" if hf.get("gating") == "per-head" else "none"] * n
    refused = {
        "attention_bias": bool(hf.get("attention_bias")),
        "moe_router_logit_softcapping": bool(
            hf.get("moe_router_logit_softcapping")),
        "moe_apply_router_weight_on_input": bool(
            hf.get("moe_apply_router_weight_on_input")),
        "decoder_sparse_step != 1": (hf.get("decoder_sparse_step") or 1) != 1,
        "gating other than per-head in every layer": any(
            g != "per_head" for g in gating),
        "a layer kind other than full_attention / sliding_attention": any(
            k not in ("full_attention", "sliding_attention") for k in kinds),
    }
    hit = [k for k, v in refused.items() if v]
    if hit:
        raise ValueError(
            f"laguna config {path} sets {', '.join(hit)}: not implemented")
    heads = hf.get("num_attention_heads_per_layer") or [
        hf["num_attention_heads"]] * n
    hd = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    sw = int(hf.get("sliding_window") or 0)
    return dict(
        layers=laguna_layers(
            kinds, heads, mlp_kinds, hf["rope_parameters"], sw, hd),
        sliding_window=sw,
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        # one ungated shared SwiGLU of shared_expert_intermediate_size
        n_shared_experts=(hf.get("shared_expert_intermediate_size") or 0)
        // hf["moe_intermediate_size"],
        # assumed (no key): the logistic sigmoid, as every public config
        # that pairs a route scale near 2.5 with renormalised top-k
        scoring_func="sigmoid",
        routed_scaling_factor=float(hf.get("moe_routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        attn_output_gate="head",
    )


def _solar_open2_keys(hf: dict, path: str) -> dict:
    """The `model_type: solar_open2` keys, and by name what of the family is
    not served: a config that sets one is refused, not guessed at."""
    lin = hf.get("linear_attn_config") or {}
    refused = {
        "use_rope": bool(hf.get("use_rope")),
        "kda_use_full_proj": bool(hf.get("kda_use_full_proj")),
        "linear_attn_config.num_kv_heads": lin.get("num_kv_heads")
        not in (None, lin.get("num_heads")),
        "first_k_dense_replace > 0": (hf.get("first_k_dense_replace") or 0)
        > 0,
        "linear_attn_config.short_conv_kernel_size != 4": lin.get(
            "short_conv_kernel_size", 4) != 4,
        "use_gqa_gate false": not hf.get("use_gqa_gate", True),
        "kda_allow_neg_eigval false": not hf.get(
            "kda_allow_neg_eigval", True),
    }
    hit = [k for k, v in refused.items() if v]
    if hit:
        raise ValueError(
            f"solar_open2 config {path} sets {', '.join(hit)}: "
            "not implemented")
    n = hf["num_hidden_layers"]
    gqa = hf.get("gqa_layers")
    if gqa is None:
        gqa = range(0, n, int(hf.get("gqa_interval", 3)) + 1)
    return dict(
        layers=solar_open2_layers(n, gqa, hf["num_attention_heads"]),
        num_experts=hf["n_routed_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        n_shared_experts=int(hf.get("n_shared_experts") or 0),
        # assumed (no key): sigmoid scores with a learned selection bias and
        # no groups, the lineage its key names follow (n_routed_experts,
        # routed_scaling_factor, first_k_dense_replace); softmax not taken
        scoring_func="sigmoid", moe_selection_bias=True,
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        # assumed (no key): the gate is elementwise; a head-wise one would
        # say so in a granularity key of its own
        attn_output_gate="element",
        linear_num_heads=lin["num_heads"], linear_head_dim=lin["head_dim"],
        # assumed (no key): the low-rank pairs' rank is the head's width
        linear_gate_rank=lin["head_dim"],
    )


def _is_ling_flash(hf: dict) -> bool:
    """Is this a Ling-3.0-flash config? Told by its own keys (latent layers
    every `layer_group_size`-th layer beside KDA layers): neither its
    `model_type` nor its `architectures` string could be checked offline."""
    return all(k in hf for k in (
        "layer_group_size", "kda_safe_gate", "kv_lora_rank"))


def _ling_flash_keys(hf: dict, path: str) -> dict:
    """The Ling-3.0-flash keys (the language model's), and by name what of
    the family is not served: a config that sets one is refused, not
    guessed at."""
    n, heads = hf["num_hidden_layers"], hf["num_attention_heads"]
    n_group = hf.get("n_group") or 1
    refused = {
        "use_nGPT": bool(hf.get("use_nGPT")),
        "value_norm": bool(hf.get("value_norm")),
        "up_proj_norm": bool(hf.get("up_proj_norm")),
        "use_kda_lora": bool(hf.get("use_kda_lora")),
        "no_kda_lora false": not hf.get("no_kda_lora", True),
        "mtp_use_kda": bool(hf.get("mtp_use_kda")),
        "scale_router_input": bool(hf.get("scale_router_input")),
        "q_lora_rank": hf.get("q_lora_rank") is not None,
        "use_mla_nope": bool(hf.get("use_mla_nope")),
        "kda_safe_gate false": not hf.get("kda_safe_gate", True),
        "linear_silu false": not hf.get("linear_silu", True),
        "short_conv_kernel_size != 4": hf.get(
            "short_conv_kernel_size", 4) != 4,
        "num_kv_heads_for_linear_attn": (
            hf.get("num_kv_heads_for_linear_attn") or 0) not in (0, heads),
        "group_norm_size != 1": (hf.get("group_norm_size") or 1) != 1,
        "rotary_dim other than qk_rope_head_dim": hf.get(
            "rotary_dim", hf["qk_rope_head_dim"]) != hf["qk_rope_head_dim"],
        "score_function other than sigmoid": hf.get(
            "score_function", "sigmoid") != "sigmoid",
        "gated_attention_proj_granularity_type other than head_wise": hf.get(
            "gated_attention_proj_granularity_type",
            "head_wise") != "head_wise",
        "n_group that does not divide num_experts": bool(
            hf["num_experts"] % n_group),
        "topk_group outside 1..n_group": not (
            1 <= (hf.get("topk_group") or 1) <= n_group),
        "rope_scaling": bool(hf.get("rope_scaling")),
        # the clamp of the SwiGLU's arguments: off (0) in every layer kept
        "a non-zero expert_swiglu_limit_list entry in a kept layer": any(
            (hf.get("expert_swiglu_limit_list") or [])[:n]),
        "a non-zero share_expert_swiglu_limit_list entry in a kept layer":
            any((hf.get("share_expert_swiglu_limit_list") or [])[:n]),
    }
    hit = [k for k, v in refused.items() if v]
    if hit:
        raise ValueError(
            f"ling_flash config {path} sets {', '.join(hit)}: "
            "not implemented")
    first_k = hf.get("first_k_dense_replace") or 0
    return dict(
        layers=ling_flash_layers(
            n, hf["layer_group_size"], first_k, heads,
            float(hf.get("rope_theta", 10000.0))),
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        first_k_dense_replace=first_k,
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        # one ungated shared SwiGLU of moe_shared_expert_intermediate_size
        n_shared_experts=(hf.get("moe_shared_expert_intermediate_size") or 0)
        // hf["moe_intermediate_size"],
        scoring_func="sigmoid",
        moe_selection_bias=bool(hf.get("moe_router_enable_expert_bias")),
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        n_group=n_group, topk_group=hf.get("topk_group") or 1,
        attn_output_gate="head",
        linear_num_heads=heads,
        # (keys and values alike: num_kv_heads_for_linear_attn 0)
        linear_head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
        linear_gate_rank=0, linear_gate_form="bounded",
        linear_gate_lower_bound=float(hf.get("kda_lower_bound", -5)),
        # assumed (no key): beta = sigmoid, in (0, 1)
        linear_beta_scale=1.0,
        refused_token_ids=tuple(
            hf[k] for k in ("image_patch_token", "video_patch_token",
                            "image_start_token", "video_start_token")
            if hf.get(k) is not None),
    )


def _refuse_other_sdar_generation(hf: dict, path: str) -> None:
    """The generation settings of a `model_type: sdar_moe` model are the
    preset's (`_SDAR_GENERATION`): the published config.json states none,
    and one that states another is refused by name, not served with it."""
    for key, ours in dataclasses.asdict(_SDAR_GENERATION).items():
        if hf.get(key) is not None and hf[key] != ours:
            raise ValueError(
                f"sdar_moe with {key}={hf[key]} is not implemented "
                f"({path}): served with {key}={ours} alone")


def _from_hf_config(path: str) -> dict:
    with open(os.path.join(path, "config.json")) as f:
        hf = json.load(f)
    archs = hf.get("architectures", [])
    arch = next((_ARCH_MAP[a] for a in archs if a in _ARCH_MAP), None)
    generation = {}
    if hf.get("model_type") == "sdar_moe":
        # keyed by model_type: the decoder is qwen3moe's key for key, and a
        # checkpoint's tensors are taken under qwen3moe's names (neither the
        # `architectures` string nor the names could be checked offline)
        arch = "qwen3moe"
        _refuse_other_sdar_generation(hf, path)
        generation = dict(block_diffusion=_SDAR_GENERATION)
    if _is_ling_flash(hf):
        arch = "ling_flash"
    if hf.get("model_type") == "falcon_h1":
        arch = "falcon_h1"
    if hf.get("model_type") == "glm_moe_dsa":
        arch = "glm_moe_dsa"
    if hf.get("model_type") == "olmo_hybrid":
        arch = "olmo_hybrid"
    if arch is None:
        raise ValueError(f"unsupported architecture(s) {archs} in {path}")
    heads = hf["num_attention_heads"]
    moe = (
        dict(
            num_experts=hf["num_local_experts"],
            num_experts_per_tok=hf.get("num_experts_per_tok", 2),
        )
        if arch == "mixtral"
        else {}
    )
    if arch == "qwen3moe":
        if hf.get("mlp_only_layers") or (hf.get("decoder_sparse_step", 1)
                                         != 1):
            raise ValueError(
                "qwen3moe with dense layers interleaved is not "
                f"implemented ({path})"
            )
        moe = dict(
            # HF use_diff serialization omits class-default fields —
            # fall back to Qwen3MoeConfig's defaults (which are the
            # published Qwen3-30B-A3B values)
            num_experts=hf.get("num_experts", 128),
            num_experts_per_tok=hf.get("num_experts_per_tok", 8),
            norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
        )
    if arch == "olmoe":
        if hf.get("clip_qkv") is not None:
            raise ValueError(
                f"olmoe with clip_qkv={hf['clip_qkv']} is not implemented "
                f"({path}): the published OLMoE configs carry null"
            )
        moe = dict(
            # OlmoeConfig's class defaults, for use_diff serialization
            num_experts=hf.get("num_experts", 64),
            num_experts_per_tok=hf.get("num_experts_per_tok", 8),
            norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
        )
    if arch == "deepseek_v3":
        moe = _deepseek_v3_keys(hf, path)
    if arch == "laguna":
        moe = _laguna_keys(hf, path)
        # its ropes are per layer kind (rope_parameters), read above
        hf = {**hf, "partial_rotary_factor": None, "rope_scaling": None}
    if arch == "solar_open2":
        moe = _solar_open2_keys(hf, path)
        # no layer has a rope: rope_theta and partial_rotary_factor are dead
        hf = {**hf, "partial_rotary_factor": None, "rope_scaling": None}
    if arch == "falcon_h1":
        moe = _falcon_h1_keys(hf, path)
        # its one rope is the per-layer list's (rope_scaling is refused)
        hf = {**hf, "partial_rotary_factor": None, "rope_scaling": None}
    if arch == "olmo_hybrid":
        moe = _olmo_hybrid_keys(hf, path)
        # no layer has a rope (`rope_parameters.rope_theta` null)
        hf = {**hf, "partial_rotary_factor": None, "rope_scaling": None}
    if arch == "glm_moe_dsa":
        moe = _glm_moe_dsa_keys(hf, path)
        # its rope is `rope_parameters` (read there; scaling is refused)
        hf = {**hf, "partial_rotary_factor": None, "rope_scaling": None,
              "rope_theta": moe.pop("rope_theta")}
    if arch == "ling_flash":
        moe = _ling_flash_keys(hf, path)
        # the latent layers rope their 64-value rope key whole (rotary_dim
        # = qk_rope_head_dim): partial_rotary_factor says that of head_dim
        hf = {**hf, "partial_rotary_factor": None, "rope_scaling": None}
    gemma = (
        dict(
            hidden_act="gelu_tanh", rms_norm_add_one=True,
            scale_embeddings=True,
        )
        if arch == "gemma"
        else {}
    )
    if arch == "gemma2":
        gemma = dict(
            hidden_act="gelu_tanh", rms_norm_add_one=True,
            scale_embeddings=True, sandwich_norms=True,
            attn_logit_softcap=float(hf.get("attn_logit_softcapping") or 0),
            final_logit_softcap=float(
                hf.get("final_logit_softcapping") or 0
            ),
            query_pre_attn_scalar=int(hf.get("query_pre_attn_scalar") or 0),
            sliding_window=int(hf.get("sliding_window") or 0),
            sliding_window_pattern=2,  # HF layer_types: even layers slide
        )
    # per-architecture norm/attention convention flags
    arch_flags = (
        dict(qk_norm=True) if arch in ("qwen3", "qwen3moe") else {}
    )
    if arch == "olmo2":
        arch_flags = dict(qk_norm_flat=True, post_norms_only=True,
                          norm_scale_f32=True)
    if arch == "olmoe":
        # OLMo-2's flat QK-norm, but pre-norms and the Llama norm order
        # (OlmoeRMSNorm downcasts before the weight multiply)
        arch_flags = dict(qk_norm_flat=True)
    # sliding-window attention: Mistral-7B-v0.1 sets sliding_window=4096
    # on every layer (v0.2+ configs carry null). Silently serving full
    # attention would give wrong numerics past the window.
    sw = {}
    if (
        ("MistralForCausalLM" in archs or arch == "phi3")
        and hf.get("sliding_window")
    ):
        sw = dict(sliding_window=int(hf["sliding_window"]),
                  sliding_window_pattern=1)
    # RoPE scaling (Llama-3.1-class checkpoints — the reference's headline
    # model ships rope_scaling rope_type=llama3): silently ignoring it
    # would serve subtly wrong long-range positions, so unknown types are
    # a hard error, not a warning
    # partial rotary (Phi-4-mini class): unimplemented — refusing beats
    # silently serving full-rotary numerics that diverge from HF
    prf = hf.get("partial_rotary_factor")
    if prf is not None and float(prf) != 1.0:
        raise ValueError(
            f"unsupported partial_rotary_factor {prf} in {path} "
            "(only full rotary is implemented)"
        )
    rs = hf.get("rope_scaling") or {}
    rs_type = rs.get("rope_type") or rs.get("type")
    if rs_type in (None, "default"):
        scaling = {}
    elif rs_type in ("llama3", "linear"):
        scaling = dict(
            rope_scaling_type=rs_type,
            rope_scaling_factor=float(rs.get("factor", 1.0)),
            rope_low_freq_factor=float(rs.get("low_freq_factor", 1.0)),
            rope_high_freq_factor=float(rs.get("high_freq_factor", 4.0)),
            rope_original_max_position=int(
                rs.get("original_max_position_embeddings", 8192)
            ),
        )
    else:
        raise ValueError(
            f"unsupported rope_scaling type {rs_type!r} in {path} "
            "(supported: llama3, linear)"
        )
    # two MLP widths, kept apart: `intermediate_size` is the width of the
    # tree's dense MLP leaves or, where every layer is an expert layer
    # (qwen3moe publishes the experts' as moe_intermediate_size; mixtral
    # and olmoe as intermediate_size), of its expert leaves;
    # `moe_intermediate_size` is set only for a model that has BOTH
    # (deepseek_v3: a leading dense layer beside the expert layers)
    if arch == "qwen3moe":
        inter = hf.get("moe_intermediate_size", 768)
    else:
        inter = hf["intermediate_size"]
    return dict(
        **generation,
        **moe,
        **gemma,
        **arch_flags,
        **sw,
        **scaling,
        model=path,
        architecture=arch,
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=inter,
        num_layers=hf["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=hf.get("num_key_value_heads", heads),
        # some configs carry an explicit null head_dim — fall through to the
        # conventional hidden/heads in that case too
        head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        max_model_len=hf.get("max_position_embeddings", 4096),
        # Gemma ties by default and HF omits class-default fields from
        # config.json, so the fallback is architecture-dependent
        tie_word_embeddings=hf.get(
            "tie_word_embeddings", arch in ("gemma", "gemma2")
        ),
        attention_bias=hf.get("attention_bias", arch == "qwen2"),
        checkpoint=path,
        tokenizer=path,
    )
