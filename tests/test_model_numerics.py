"""Logits parity of the JAX Llama against HuggingFace transformers (CPU).

This is the engine-side analogue of the reference's tiny-stand-in test style
(SURVEY §4): same weights loaded into both implementations, full-prefill
logits must agree, and paged decode must agree with full prefill.
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
from transformers import LlamaConfig as HFLlamaConfig
from transformers import LlamaForCausalLM

import jax
import jax.numpy as jnp

from vllm_production_stack_tpu.engine.config import ModelConfig
from vllm_production_stack_tpu.models import llama


def make_cfg():
    return ModelConfig.tiny()


def hf_model_from_params(cfg: ModelConfig, params):
    rope_scaling = None
    if cfg.rope_scaling_type is not None:
        rope_scaling = {
            "rope_type": cfg.rope_scaling_type,
            "factor": cfg.rope_scaling_factor,
            "low_freq_factor": cfg.rope_low_freq_factor,
            "high_freq_factor": cfg.rope_high_freq_factor,
            "original_max_position_embeddings":
                cfg.rope_original_max_position,
        }
    hf_cfg = HFLlamaConfig(
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta,
        rope_scaling=rope_scaling,
        rms_norm_eps=cfg.rms_norm_eps,
        max_position_embeddings=cfg.max_model_len,
        tie_word_embeddings=cfg.tie_word_embeddings,
        attention_bias=False,
        mlp_bias=False,
    )
    model = LlamaForCausalLM(hf_cfg).eval()

    def t(x):  # jax (in, out) -> torch (out, in)
        return torch.from_numpy(np.asarray(x, dtype=np.float32).T.copy())

    def th(x):  # jax (heads, head_dim, in) -> torch (out, in): as it lies
        x = np.asarray(x, dtype=np.float32)
        return torch.from_numpy(x.reshape(-1, x.shape[-1]).copy())

    def v(x):
        return torch.from_numpy(np.asarray(x, dtype=np.float32).copy())

    sd = {}
    sd["model.embed_tokens.weight"] = v(params["embed"])
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
    sd["lm_head.weight"] = t(params["lm_head"])
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected
    # rotary inv_freq buffers may be "missing" from our sd; that's fine
    assert all("inv_freq" in m for m in missing)
    return model


def run_jax_prefill(cfg, params, tokens, block_size=8, num_blocks=32):
    t = len(tokens)
    kv = llama.init_kv_cache(cfg, num_blocks, block_size, jnp.float32)
    max_blocks = num_blocks
    nb = (t + block_size - 1) // block_size
    block_table = np.zeros((1, max_blocks), np.int32)
    block_table[0, :nb] = np.arange(1, nb + 1)  # block 0 reserved
    slots = block_table[0, np.arange(t) // block_size] * block_size + (
        np.arange(t) % block_size
    )
    hidden, kv = llama.forward(
        cfg,
        params,
        jnp.asarray([tokens], jnp.int32),
        jnp.asarray([np.arange(t)], jnp.int32),
        kv,
        jnp.asarray(block_table),
        jnp.asarray(slots, jnp.int32),
        jnp.asarray([t], jnp.int32),
    )
    logits = llama.compute_logits(cfg, params, hidden[0])
    return np.asarray(logits), kv, block_table


def test_prefill_logits_match_hf():
    cfg = make_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    hf = hf_model_from_params(cfg, params)
    tokens = list(np.random.RandomState(0).randint(0, cfg.vocab_size, size=21))

    ours, _, _ = run_jax_prefill(cfg, params, tokens)
    with torch.no_grad():
        theirs = hf(torch.tensor([tokens])).logits[0].numpy()

    np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)


def test_prefill_logits_match_hf_with_llama3_rope_scaling():
    """Llama-3.1-class rope_scaling (rope_type=llama3, the reference's
    headline checkpoint ships it): our piecewise frequency rescale must
    match HF transformers' _compute_llama3_parameters exactly — silently
    ignoring it (the pre-round-5 behavior) serves wrong long-range
    positions. The band parameters are scaled to the tiny context so all
    three regimes (unscaled / smoothed / divided) are exercised."""
    cfg = ModelConfig.tiny(
        rope_scaling_type="llama3",
        rope_scaling_factor=8.0,
        rope_low_freq_factor=1.0,
        rope_high_freq_factor=4.0,
        rope_original_max_position=64,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(2))
    hf = hf_model_from_params(cfg, params)
    tokens = list(np.random.RandomState(2).randint(0, cfg.vocab_size, size=33))

    ours, _, _ = run_jax_prefill(cfg, params, tokens)
    with torch.no_grad():
        theirs = hf(torch.tensor([tokens])).logits[0].numpy()
    np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)

    # and the scaling must actually CHANGE the logits vs vanilla rope
    # (guards against both sides silently no-opping)
    vanilla, _, _ = run_jax_prefill(ModelConfig.tiny(), params, tokens)
    assert np.abs(ours - vanilla).max() > 1e-3


def test_paged_decode_matches_full_prefill():
    """Decode one token at a time through the paged cache; logits at each step
    must match the full-prefill logits at the same position."""
    cfg = make_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.RandomState(1)
    tokens = list(rng.randint(0, cfg.vocab_size, size=13))
    block_size = 8

    full_logits, _, _ = run_jax_prefill(cfg, params, tokens, block_size)

    # prefill the first 5 tokens, then decode the rest one-by-one
    n0 = 5
    _, kv, block_table = run_jax_prefill(cfg, params, tokens[:n0], block_size)
    for pos in range(n0, len(tokens)):
        blk = pos // block_size
        if block_table[0, blk] == 0:
            block_table[0, blk] = blk + 1
        slot = block_table[0, blk] * block_size + pos % block_size
        hidden, kv = llama.forward(
            cfg,
            params,
            jnp.asarray([[tokens[pos]]], jnp.int32),
            jnp.asarray([[pos]], jnp.int32),
            kv,
            jnp.asarray(block_table),
            jnp.asarray([slot], jnp.int32),
            jnp.asarray([pos + 1], jnp.int32),
        )
        step_logits = np.asarray(llama.compute_logits(cfg, params, hidden[0]))[0]
        np.testing.assert_allclose(
            step_logits, full_logits[pos], rtol=2e-4, atol=2e-4
        )


def test_chunked_prefill_matches_full_prefill():
    cfg = make_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(2))
    tokens = list(np.random.RandomState(2).randint(0, cfg.vocab_size, size=19))
    block_size = 8
    full_logits, _, _ = run_jax_prefill(cfg, params, tokens, block_size)

    num_blocks = 32
    kv = llama.init_kv_cache(cfg, num_blocks, block_size, jnp.float32)
    block_table = np.zeros((1, num_blocks), np.int32)
    done = 0
    for chunk in (7, 4, 8):
        idx = np.arange(done, done + chunk)
        for blk in set(idx // block_size):
            if block_table[0, blk] == 0:
                block_table[0, blk] = blk + 1
        slots = block_table[0, idx // block_size] * block_size + idx % block_size
        hidden, kv = llama.forward(
            cfg,
            params,
            jnp.asarray([tokens[done : done + chunk]], jnp.int32),
            jnp.asarray([idx], jnp.int32),
            kv,
            jnp.asarray(block_table),
            jnp.asarray(slots, jnp.int32),
            jnp.asarray([done + chunk], jnp.int32),
        )
        chunk_logits = np.asarray(llama.compute_logits(cfg, params, hidden[0]))
        # every intra-chunk position must match full prefill, not just the tail
        np.testing.assert_allclose(
            chunk_logits, full_logits[idx], rtol=2e-4, atol=2e-4
        )
        done += chunk


def test_sliding_window_mask_and_pattern():
    """Window-mask semantics vs a numpy reference, and the alternating
    pattern plumbing: pattern=2 slides even layers only, so a 2-layer
    model's logits must differ BOTH from all-full and from all-sliding —
    pinning that the per-layer mask selection actually branches (the
    Gemma-2-style layout has no HF producer here yet; the mask math and
    the predicate are what this locks down)."""
    from vllm_production_stack_tpu.ops.attention import causal_page_mask

    q_pos = jnp.asarray([[3, 9, 15]], jnp.int32)
    lens = jnp.asarray([14], jnp.int32)
    got = np.asarray(causal_page_mask(q_pos, lens, 16, window=4))
    for ti, p in enumerate([3, 9, 15]):
        for j in range(16):
            want = (j < 14) and (j <= p) and (j > p - 4)
            assert got[0, ti, j] == want, (ti, j)

    cfg_full = ModelConfig.tiny()
    assert not cfg_full.layer_sliding(0)
    cfg_all = ModelConfig.tiny(sliding_window=8)
    assert cfg_all.layer_sliding(0) and cfg_all.layer_sliding(1)
    cfg_alt = ModelConfig.tiny(sliding_window=8, sliding_window_pattern=2)
    assert cfg_alt.layer_sliding(0) and not cfg_alt.layer_sliding(1)

    params = llama.init_params(cfg_full, jax.random.PRNGKey(4))
    tokens = list(np.random.RandomState(4).randint(0, 512, size=24))
    out_full, _, _ = run_jax_prefill(cfg_full, params, tokens)
    out_all, _, _ = run_jax_prefill(cfg_all, params, tokens)
    out_alt, _, _ = run_jax_prefill(cfg_alt, params, tokens)
    assert np.abs(out_alt - out_full).max() > 1e-3  # layer 0 slides
    assert np.abs(out_alt - out_all).max() > 1e-3  # layer 1 stays full


def test_rms_norm_orderings_match_hf_in_bf16():
    """The three RMSNorm weight-multiply orderings differ by ulps in
    bf16 and each must match its HF reference bitwise: Llama
    (downcast-then-scale), Gemma add_one and OLMo-2 scale_f32 (both
    f32-scale-then-downcast)."""
    import ml_dtypes

    rng = np.random.RandomState(31)
    x32 = rng.randn(4, 64).astype(np.float32) * 3
    w32 = (rng.randn(64).astype(np.float32) * 0.5 + 1.0)
    x_bf = jnp.asarray(x32).astype(jnp.bfloat16)
    w_bf = jnp.asarray(w32).astype(jnp.bfloat16)

    def torch_ref(scale_f32):
        xt = torch.from_numpy(x32).to(torch.bfloat16)
        wt = torch.from_numpy(w32).to(torch.bfloat16)
        h = xt.to(torch.float32)
        var = h.pow(2).mean(-1, keepdim=True)
        h = h * torch.rsqrt(var + 1e-5)
        if scale_f32:  # Olmo2RMSNorm
            out = (wt * h).to(torch.bfloat16)
        else:  # LlamaRMSNorm
            out = wt * h.to(torch.bfloat16)
        return out.to(torch.float32).numpy()

    ours_llama = np.asarray(
        llama.rms_norm(x_bf, w_bf, 1e-5).astype(jnp.float32)
    )
    ours_olmo = np.asarray(
        llama.rms_norm(x_bf, w_bf, 1e-5, scale_f32=True).astype(jnp.float32)
    )
    np.testing.assert_array_equal(ours_llama, torch_ref(False))
    np.testing.assert_array_equal(ours_olmo, torch_ref(True))
    # the orderings genuinely differ in bf16 (guards against a silent
    # collapse of the two paths)
    assert (ours_llama != ours_olmo).any()
