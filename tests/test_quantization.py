"""int8 weight-only quantization (models/quantization.py): correctness
bounds, spec-tree mirroring, memory accounting, and end-to-end serving
(VERDICT r3 missing #3 — the 8B-on-one-chip path)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_production_stack_tpu.engine.config import EngineConfig, ModelConfig
from vllm_production_stack_tpu.engine.engine import LLMEngine
from vllm_production_stack_tpu.engine.memory import param_bytes
from vllm_production_stack_tpu.engine.request import SamplingParams
from vllm_production_stack_tpu.models import llama
from vllm_production_stack_tpu.models.quantization import (
    is_quantized_leaf,
    quantize_params,
    quantize_specs,
)
from vllm_production_stack_tpu.parallel.sharding import llama_param_specs


def _cfg(**kw):
    return ModelConfig.tiny(quantization="int8", **kw)


def test_dequantized_weight_within_rounding_bound():
    cfg = _cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_params(cfg, params)
    for name in ("wq", "wk", "wv", "wo"):
        leaf = qp["layers"]["attn"][name]
        assert is_quantized_leaf(leaf)
        w = np.asarray(params["layers"]["attn"][name], np.float32)
        scale = np.asarray(leaf["s"])
        if name != "wo":
            # stored (L, heads, head_dim, in): one scale an output channel,
            # (L, heads, head_dim)
            assert scale.shape == w.shape[:-1], name
            scale = scale[..., None]
        deq = np.asarray(leaf["q"], np.float32) * scale
        # symmetric rounding: |W - deq| <= scale/2 elementwise
        bound = np.broadcast_to(scale / 2 + 1e-8, w.shape)
        assert np.all(np.abs(w - deq) <= bound), name
    # embed and norms stay unquantized
    assert not is_quantized_leaf(qp["embed"])
    assert not is_quantized_leaf(qp["layers"]["input_norm"])


@pytest.mark.parametrize("name,heads", [("wq", "num_heads"),
                                        ("wk", "num_kv_heads"),
                                        ("wv", "num_kv_heads")])
def test_heads_major_leaf_quantizes_as_its_matrix_does(name, heads):
    """A leaf stored (L, heads, head_dim, in) holds the values and the scales
    the (L, in, heads * head_dim) matrix has, moved: the reduction runs over
    the contraction axis wherever it lies."""
    from vllm_production_stack_tpu.models.quantization import _quantize

    cfg = _cfg()
    n = getattr(cfg, heads)
    leaf = llama.init_params(cfg, jax.random.PRNGKey(0))["layers"]["attn"][name]
    assert leaf.shape == (cfg.num_layers, n, cfg.head_dim, cfg.hidden_size)
    matrix = jnp.swapaxes(leaf.reshape(cfg.num_layers, -1, cfg.hidden_size),
                          1, 2)  # (L, in, out)
    np.testing.assert_array_equal(llama.heads_major(matrix, n), leaf)
    got, want = _quantize(leaf, axis=-1), _quantize(matrix)
    np.testing.assert_array_equal(got["q"], llama.heads_major(want["q"], n))
    np.testing.assert_array_equal(
        got["s"], want["s"].reshape(cfg.num_layers, n, cfg.head_dim))


def test_spec_tree_mirrors_param_tree():
    cfg = _cfg(tie_word_embeddings=False)
    params = quantize_params(cfg, llama.init_params(cfg, jax.random.PRNGKey(0)))
    specs = quantize_specs(cfg, llama_param_specs(cfg))
    assert (
        jax.tree.structure(params)
        == jax.tree.structure(specs, is_leaf=lambda x: not isinstance(x, dict))
    )


def test_logits_close_to_full_precision():
    cfg = _cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    qp = quantize_params(cfg, params)
    ids = np.random.RandomState(0).randint(1, cfg.vocab_size, size=(2, 12))
    lens = np.full((2,), 12, np.int32)
    full = np.asarray(
        llama.compute_logits(
            cfg, params, llama.embed_encode(cfg, params, ids, lens)
        )
    )
    quant = np.asarray(
        llama.compute_logits(cfg, qp, llama.embed_encode(cfg, qp, ids, lens))
    )
    # per-channel int8 is near-lossless: logits rows stay tightly aligned
    for a, b in zip(full, quant):
        cos = np.dot(a, b) / (np.linalg.norm(a) * np.linalg.norm(b))
        assert cos > 0.999, cos


def test_param_bytes_accounting():
    bf16 = ModelConfig(
        model="x", vocab_size=1024, hidden_size=256, intermediate_size=512,
        num_layers=4, num_heads=4, num_kv_heads=2, head_dim=64,
        dtype="bfloat16",
    )
    q = dataclasses.replace(bf16, quantization="int8")
    full, quantized = param_bytes(bf16), param_bytes(q)
    assert quantized < full
    # layer linears dominate this shape: expect roughly half the bytes
    assert quantized < 0.75 * full
    # the estimate must track the real tree within a few percent
    params = quantize_params(q, llama.init_params(q, jax.random.PRNGKey(0)))
    real = sum(
        x.nbytes for x in jax.tree.leaves(params)
    )
    assert abs(real - quantized) / real < 0.05, (real, quantized)


def test_engine_serves_quantized_and_rejects_unknown():
    cfg = _cfg()
    engine = LLMEngine(EngineConfig.tiny().replace(model=cfg))
    outs = engine.generate(
        [list(np.random.RandomState(3).randint(1, cfg.vocab_size, size=24))],
        SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True),
    )
    assert len(outs[0]["token_ids"]) == 8
    # fingerprint must differ from the unquantized engine's (different
    # activations => different KV bytes; cross-matching would corrupt)
    plain = LLMEngine(EngineConfig.tiny())
    assert engine.model_fingerprint != plain.model_fingerprint

    with pytest.raises(ValueError, match="unknown quantization"):
        quantize_params(
            ModelConfig.tiny(quantization="int4"),
            llama.init_params(ModelConfig.tiny(), jax.random.PRNGKey(0)),
        )


def test_quantized_with_lora_and_sleep_wake():
    """LoRA deltas apply on top of quantized base matmuls; sleep/wake
    round-trips the quantized tree."""
    from vllm_production_stack_tpu.engine.config import LoRAConfig

    cfg = _cfg()
    engine = LLMEngine(
        EngineConfig.tiny().replace(
            model=cfg, lora=LoRAConfig(max_loras=1, max_lora_rank=4)
        )
    )
    prompt = list(np.random.RandomState(5).randint(1, cfg.vocab_size, size=16))
    before = engine.generate(
        [prompt], SamplingParams(max_tokens=6, temperature=0.0,
                                 ignore_eos=True),
    )[0]["token_ids"]
    engine.sleep(level=1)
    engine.wake()
    after = engine.generate(
        [prompt], SamplingParams(max_tokens=6, temperature=0.0,
                                 ignore_eos=True),
    )[0]["token_ids"]
    assert before == after


def test_quantized_qwen3_serves():
    """int8 + qk_norm compose (the qwen3-8b helm example's config): the
    norm leaves stay unquantized pass-throughs in both the param tree and
    the sharding spec, and the engine serves greedily."""
    from vllm_production_stack_tpu.models.quantization import quantize_specs
    from vllm_production_stack_tpu.parallel.sharding import llama_param_specs

    cfg = _cfg(architecture="qwen3", qk_norm=True)
    specs = quantize_specs(cfg, llama_param_specs(cfg))
    assert set(specs["layers"]["attn"]["wq"].keys()) == {"q", "s"}
    assert not isinstance(specs["layers"]["attn"]["q_norm"], dict)

    engine = LLMEngine(EngineConfig.tiny().replace(model=cfg))
    attn = engine.runner.params["layers"]["attn"]
    assert set(attn["wq"].keys()) == {"q", "s"}  # quantized
    assert not isinstance(attn["q_norm"], dict)  # NOT quantized
    prompts = [list(np.random.RandomState(3).randint(1, 512, size=24))]
    out = engine.generate(
        prompts, SamplingParams(max_tokens=6, temperature=0.0,
                                ignore_eos=True),
    )
    assert len(out[0]["token_ids"]) == 6


# -- int8 experts (ISSUE 26) ------------------------------------------------


def _moe_cfg():
    from vllm_production_stack_tpu.models.registry import resolve_model_config

    return resolve_model_config("tiny-olmoe", quantization="int8")


def test_int8_experts_shapes_and_rounding_bound():
    cfg = _moe_cfg()
    L, e, h, it = cfg.num_layers, 8, cfg.hidden_size, 32
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    qp = jax.jit(lambda p: quantize_params(cfg, p))(params)
    for name, shape in (("gate", (L, e, h, it)), ("up", (L, e, h, it)),
                        ("down", (L, e, it, h))):
        leaf = qp["layers"]["moe"][name]
        assert is_quantized_leaf(leaf)
        assert leaf["q"].shape == shape and leaf["q"].dtype == jnp.int8
        # one scale per layer, expert and output channel
        assert leaf["s"].shape == (L, e, 1, shape[-1])
        assert leaf["s"].dtype == jnp.float32
        w = np.asarray(params["layers"]["moe"][name], np.float32)
        deq = np.asarray(leaf["q"], np.float32) * np.asarray(leaf["s"])
        bound = np.broadcast_to(np.asarray(leaf["s"]) / 2 + 1e-8, w.shape)
        assert np.all(np.abs(w - deq) <= bound), name
    # the router decides top-k: it stays in the model's dtype
    assert not is_quantized_leaf(qp["layers"]["moe"]["router"])
    # the host (numpy) path quantises layer by layer to the same result
    host = quantize_params(cfg, jax.tree.map(np.asarray, params))
    for name in ("gate", "up", "down"):
        np.testing.assert_array_equal(
            host["layers"]["moe"][name]["q"],
            np.asarray(qp["layers"]["moe"][name]["q"]))


def test_moe_spec_tree_mirrors_param_tree():
    cfg = _moe_cfg()
    params = quantize_params(cfg, llama.init_params(cfg, jax.random.PRNGKey(0)))
    specs = quantize_specs(cfg, llama_param_specs(cfg))
    assert (
        jax.tree.structure(params)
        == jax.tree.structure(specs, is_leaf=lambda x: not isinstance(x, dict))
    )


@pytest.mark.parametrize("quantization", [None, "int8"])
def test_moe_param_bytes_equal_the_tree(quantization):
    """param_bytes counts E experts and the router per layer: pool
    auto-sizing was wrong by 6 GB for OLMoE-1B-7B when it counted one."""
    cfg = dataclasses.replace(_moe_cfg(), quantization=quantization)
    params = quantize_params(cfg, llama.init_params(cfg, jax.random.PRNGKey(0)))
    real = sum(x.nbytes for x in jax.tree.leaves(params))
    assert param_bytes(cfg) == real
    # the published model: 6.44 B expert parameters at one byte each
    from vllm_production_stack_tpu.models.registry import resolve_model_config

    olmoe = resolve_model_config("olmoe-1b-7b", quantization="int8")
    experts = 16 * 64 * 3 * 2048 * 1024
    assert experts == 6_442_450_944
    assert experts < param_bytes(olmoe) < experts + 0.6e9
    assert param_bytes(dataclasses.replace(olmoe, quantization=None)) > 2 * experts
    # experts shard over ep
    assert param_bytes(olmoe, ep=4) < param_bytes(olmoe) - 0.7 * experts
