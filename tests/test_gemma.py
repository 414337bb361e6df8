"""Gemma-family conventions (GeGLU, +1 RMSNorm, sqrt(h) embedding scaling,
tied embeddings, decoupled head_dim): HF logits parity and checkpoint
round-trip through the loader."""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
from transformers import GemmaConfig as HFGemmaConfig
from transformers import GemmaForCausalLM

import jax
import jax.numpy as jnp

from vllm_production_stack_tpu.engine.config import ModelConfig
from vllm_production_stack_tpu.models import llama


def make_cfg():
    # head_dim deliberately != hidden/heads (Gemma's signature trait)
    return ModelConfig.tiny(
        model="tiny-gemma", architecture="gemma", num_heads=4, num_kv_heads=2,
        head_dim=24, hidden_act="gelu_tanh", rms_norm_add_one=True,
        scale_embeddings=True, tie_word_embeddings=True, rms_norm_eps=1e-6,
    )


def hf_model_from_params(cfg: ModelConfig, params):
    hf_cfg = HFGemmaConfig(
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_norm_eps,
        max_position_embeddings=cfg.max_model_len,
        tie_word_embeddings=True,
        hidden_activation="gelu_pytorch_tanh",
        attention_bias=False,
    )
    model = GemmaForCausalLM(hf_cfg).eval()

    def t(x):
        return torch.from_numpy(np.asarray(x, dtype=np.float32).T.copy())

    def th(x):  # jax (heads, head_dim, in) -> torch (out, in): as it lies
        x = np.asarray(x, dtype=np.float32)
        return torch.from_numpy(x.reshape(-1, x.shape[-1]).copy())

    def v(x):
        return torch.from_numpy(np.asarray(x, dtype=np.float32).copy())

    sd = {"model.embed_tokens.weight": v(params["embed"])}
    lp = params["layers"]
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        sd[p + "self_attn.q_proj.weight"] = th(lp["attn"]["wq"][i])
        sd[p + "self_attn.k_proj.weight"] = th(lp["attn"]["wk"][i])
        sd[p + "self_attn.v_proj.weight"] = th(lp["attn"]["wv"][i])
        sd[p + "self_attn.o_proj.weight"] = t(lp["attn"]["wo"][i])
        sd[p + "mlp.gate_proj.weight"] = t(lp["mlp"]["gate"][i])
        sd[p + "mlp.up_proj.weight"] = t(lp["mlp"]["up"][i])
        sd[p + "mlp.down_proj.weight"] = t(lp["mlp"]["down"][i])
        sd[p + "input_layernorm.weight"] = v(lp["input_norm"][i])
        sd[p + "post_attention_layernorm.weight"] = v(lp["post_attn_norm"][i])
    sd["model.norm.weight"] = v(params["final_norm"])
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected, unexpected
    assert all("inv_freq" in m or "lm_head" in m for m in missing), missing
    return model


def jax_prefill_logits(cfg, params, tokens, block_size=8, num_blocks=32):
    t = len(tokens)
    kv = llama.init_kv_cache(cfg, num_blocks, block_size, jnp.float32)
    nb = (t + block_size - 1) // block_size
    bt = np.zeros((1, num_blocks), np.int32)
    bt[0, :nb] = np.arange(1, nb + 1)
    slots = (
        bt[0, np.arange(t) // block_size] * block_size
        + np.arange(t) % block_size
    )
    hidden, _ = llama.forward(
        cfg, params,
        jnp.asarray([tokens], jnp.int32),
        jnp.asarray([np.arange(t)], jnp.int32),
        kv, jnp.asarray(bt), jnp.asarray(slots, jnp.int32),
        jnp.asarray([t], jnp.int32),
    )
    return np.asarray(llama.compute_logits(cfg, params, hidden[0]))


def test_gemma_logits_match_hf():
    cfg = make_cfg()
    # gemma norm weights are stored centered on 0 (the +1 is in the op);
    # perturb them so the add_one path is actually exercised
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(42)
    params["layers"]["input_norm"] = 0.1 * jax.random.normal(
        key, params["layers"]["input_norm"].shape
    )
    hf = hf_model_from_params(cfg, params)
    rng = np.random.RandomState(0)
    tokens = rng.randint(1, cfg.vocab_size, size=21)
    ours = jax_prefill_logits(cfg, params, tokens)
    with torch.no_grad():
        theirs = hf(torch.tensor(tokens)[None]).logits[0].float().numpy()
    np.testing.assert_allclose(ours, theirs, atol=3e-4, rtol=2e-3)


def test_gemma_checkpoint_roundtrip(tmp_path):
    from vllm_production_stack_tpu.models.loader import load_checkpoint_params
    from vllm_production_stack_tpu.models.registry import resolve_model_config

    cfg0 = make_cfg()
    seed_params = llama.init_params(cfg0, jax.random.PRNGKey(1))
    hf = hf_model_from_params(cfg0, seed_params)
    hf.save_pretrained(tmp_path, safe_serialization=True)

    cfg = resolve_model_config(str(tmp_path), dtype="float32")
    assert cfg.architecture == "gemma"
    assert cfg.hidden_act == "gelu_tanh"
    assert cfg.rms_norm_add_one and cfg.scale_embeddings
    assert cfg.head_dim == cfg0.head_dim
    assert cfg.tie_word_embeddings
    params = jax.tree.map(jnp.asarray, load_checkpoint_params(cfg))

    rng = np.random.RandomState(2)
    tokens = rng.randint(1, cfg.vocab_size, size=13)
    ours = jax_prefill_logits(cfg, params, tokens)
    with torch.no_grad():
        theirs = hf(torch.tensor(tokens)[None]).logits[0].float().numpy()
    np.testing.assert_allclose(ours, theirs, atol=3e-4, rtol=2e-3)


def test_gemma_engine_generates():
    """The engine serves a Gemma-convention model end to end (greedy,
    deterministic across batching)."""
    from vllm_production_stack_tpu.engine.config import EngineConfig
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.request import SamplingParams

    cfg = make_cfg()
    engine = LLMEngine(
        EngineConfig.tiny().replace(model=cfg)
    )
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(1, cfg.vocab_size, size=7 + i)) for i in range(3)]
    greedy = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    solo = [engine.generate([p], greedy)[0]["token_ids"] for p in prompts]
    batched = [r["token_ids"] for r in engine.generate(prompts, greedy)]
    assert batched == solo
