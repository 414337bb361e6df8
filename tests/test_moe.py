"""Mixtral-family sparse MoE: HF logits parity, expert-parallel sharding on
the virtual mesh, engine e2e, and checkpoint round-trip."""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
from transformers import MixtralConfig as HFMixtralConfig
from transformers import MixtralForCausalLM

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from vllm_production_stack_tpu.engine.config import ModelConfig
from vllm_production_stack_tpu.models import llama
from vllm_production_stack_tpu.parallel import mesh as mesh_lib
from vllm_production_stack_tpu.parallel.sharding import (
    kv_cache_spec,
    llama_param_specs,
)


def make_cfg():
    return ModelConfig.tiny(
        model="tiny-mixtral", architecture="mixtral", num_experts=4,
        num_experts_per_tok=2,
    )


def hf_model_from_params(cfg: ModelConfig, params):
    hf_cfg = HFMixtralConfig(
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads,
        num_local_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_norm_eps,
        max_position_embeddings=cfg.max_model_len,
        tie_word_embeddings=cfg.tie_word_embeddings,
        sliding_window=None,
        router_jitter_noise=0.0,
    )
    model = MixtralForCausalLM(hf_cfg).eval()

    def t(x):  # jax (in, out) -> torch (out, in)
        return torch.from_numpy(np.asarray(x, dtype=np.float32).T.copy())

    def v(x):
        return torch.from_numpy(np.asarray(x, dtype=np.float32).copy())

    sd = {}
    sd["model.embed_tokens.weight"] = v(params["embed"])
    lp = params["layers"]
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        sd[p + "self_attn.q_proj.weight"] = t(lp["attn"]["wq"][i])
        sd[p + "self_attn.k_proj.weight"] = t(lp["attn"]["wk"][i])
        sd[p + "self_attn.v_proj.weight"] = t(lp["attn"]["wv"][i])
        sd[p + "self_attn.o_proj.weight"] = t(lp["attn"]["wo"][i])
        sd[p + "block_sparse_moe.gate.weight"] = t(lp["moe"]["router"][i])
        for j in range(cfg.num_experts):
            e = p + f"block_sparse_moe.experts.{j}."
            sd[e + "w1.weight"] = t(lp["moe"]["gate"][i, j])
            sd[e + "w3.weight"] = t(lp["moe"]["up"][i, j])
            sd[e + "w2.weight"] = t(lp["moe"]["down"][i, j])
        sd[p + "input_layernorm.weight"] = v(lp["input_norm"][i])
        sd[p + "post_attention_layernorm.weight"] = v(lp["post_attn_norm"][i])
    sd["model.norm.weight"] = v(params["final_norm"])
    sd["lm_head.weight"] = t(params["lm_head"])
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected, unexpected
    assert all("inv_freq" in m for m in missing), missing
    return model


def jax_prefill_logits(cfg, params, tokens, block_size=8, num_blocks=32):
    t = len(tokens)
    kv = llama.init_kv_cache(cfg, num_blocks, block_size, jnp.float32)
    nb = (t + block_size - 1) // block_size
    block_table = np.zeros((1, num_blocks), np.int32)
    block_table[0, :nb] = np.arange(1, nb + 1)
    slots = (
        block_table[0, np.arange(t) // block_size] * block_size
        + np.arange(t) % block_size
    )
    hidden, _ = llama.forward(
        cfg, params,
        jnp.asarray([tokens], jnp.int32),
        jnp.asarray([np.arange(t)], jnp.int32),
        kv, jnp.asarray(block_table), jnp.asarray(slots, jnp.int32),
        jnp.asarray([t], jnp.int32),
    )
    return np.asarray(
        llama.compute_logits(cfg, params, hidden[0])
    )  # (T, vocab)


def test_moe_logits_match_hf_mixtral():
    cfg = make_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    hf = hf_model_from_params(cfg, params)
    rng = np.random.RandomState(0)
    tokens = rng.randint(1, cfg.vocab_size, size=24)

    ours = jax_prefill_logits(cfg, params, tokens)
    with torch.no_grad():
        theirs = (
            hf(torch.tensor(tokens)[None]).logits[0].float().numpy()
        )
    np.testing.assert_allclose(ours, theirs, atol=2e-4, rtol=2e-3)


def test_moe_routing_is_sparse():
    """Sanity: with one dominant expert per token the combine weights hit
    exactly top-k experts and sum to 1."""
    cfg = make_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    x = jnp.asarray(
        np.random.RandomState(1).standard_normal((1, 6, cfg.hidden_size)),
        jnp.float32,
    )
    mp = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    logits = (x @ mp["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    w = jnp.sum(
        jax.nn.one_hot(topi, cfg.num_experts, dtype=jnp.float32)
        * topv[..., None],
        axis=-2,
    )
    nz = np.asarray((w > 0).sum(-1))
    np.testing.assert_array_equal(nz, cfg.num_experts_per_tok)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-6)


def test_moe_ep_sharded_forward_matches_single_device():
    """(ep=2, tp=2) expert-parallel forward reproduces single-device logits
    (GSPMD inserts the psum over ep for the combine)."""
    cfg = make_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(2))
    rng = np.random.RandomState(2)
    tokens = rng.randint(1, cfg.vocab_size, size=12)
    ref = jax_prefill_logits(cfg, params, tokens)

    mesh = mesh_lib.make_mesh(
        tensor_parallel_size=2, expert_parallel_size=2,
        data_parallel_size=2,
    )
    specs = llama_param_specs(cfg)
    jax.tree.map(lambda p, s: None, params, specs)  # structural zip
    sharded = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )
    t, block_size, num_blocks = len(tokens), 8, 32
    kv = jax.device_put(
        llama.init_kv_cache(cfg, num_blocks, block_size, jnp.float32),
        NamedSharding(mesh, kv_cache_spec()),
    )
    nb = (t + block_size - 1) // block_size
    block_table = np.zeros((1, num_blocks), np.int32)
    block_table[0, :nb] = np.arange(1, nb + 1)
    slots = (
        block_table[0, np.arange(t) // block_size] * block_size
        + np.arange(t) % block_size
    )
    with mesh:
        hidden, _ = jax.jit(
            lambda p, *a: llama.forward(cfg, p, *a)
        )(
            sharded,
            jnp.asarray([tokens], jnp.int32),
            jnp.asarray([np.arange(t)], jnp.int32),
            kv, jnp.asarray(block_table), jnp.asarray(slots, jnp.int32),
            jnp.asarray([t], jnp.int32),
        )
        logits = np.asarray(llama.compute_logits(cfg, sharded, hidden[0]))
    np.testing.assert_allclose(logits, ref, atol=2e-4, rtol=2e-3)


def test_engine_e2e_mixtral_on_ep_mesh():
    """The PRODUCTION engine serving a Mixtral-family model on an
    (ep=2, tp=2, dp=2) mesh reproduces single-device greedy outputs."""
    from vllm_production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig,
    )
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.request import SamplingParams

    cfg = make_cfg()

    def build(tp, dp, ep):
        return LLMEngine(
            EngineConfig(
                model=cfg,
                cache=CacheConfig(block_size=8, num_blocks=33),
                scheduler=SchedulerConfig(
                    max_num_seqs=4, max_num_batched_tokens=32,
                    decode_buckets=(4,), prefill_buckets=(16, 32),
                    decode_window=4,
                ),
                parallel=ParallelConfig(
                    tensor_parallel_size=tp, data_parallel_size=dp,
                    expert_parallel_size=ep,
                ),
            ),
            mesh=mesh_lib.make_mesh(tp, dp, expert_parallel_size=ep),
        )

    rng = np.random.RandomState(9)
    prompts = [
        list(rng.randint(1, cfg.vocab_size, size=6 + i)) for i in range(4)
    ]
    sampling = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    ep_out = build(tp=2, dp=2, ep=2).generate(prompts, sampling)
    ref_out = build(tp=1, dp=1, ep=1).generate(prompts, sampling)
    for a, b in zip(ep_out, ref_out):
        assert a["token_ids"] == b["token_ids"]


def test_mixtral_checkpoint_roundtrip(tmp_path):
    """save_pretrained → our loader → logits match HF eager forward (the
    reference's model-URL→served-weights contract for MoE models,
    vllmruntime_controller.go:228-286)."""
    from vllm_production_stack_tpu.models.loader import load_checkpoint_params
    from vllm_production_stack_tpu.models.registry import resolve_model_config

    cfg0 = make_cfg()
    seed_params = llama.init_params(cfg0, jax.random.PRNGKey(4))
    hf = hf_model_from_params(cfg0, seed_params)
    hf.save_pretrained(tmp_path, safe_serialization=True)

    cfg = resolve_model_config(str(tmp_path), dtype="float32")
    assert cfg.architecture == "mixtral"
    assert cfg.num_experts == cfg0.num_experts
    params = jax.tree.map(jnp.asarray, load_checkpoint_params(cfg))

    rng = np.random.RandomState(4)
    tokens = rng.randint(1, cfg.vocab_size, size=16)
    ours = jax_prefill_logits(cfg, params, tokens)
    with torch.no_grad():
        theirs = hf(torch.tensor(tokens)[None]).logits[0].float().numpy()
    np.testing.assert_allclose(ours, theirs, atol=2e-4, rtol=2e-3)


def test_qwen3moe_checkpoint_parity(tmp_path):
    """Qwen3-MoE: qwen3 attention (QK-norm) + MoE with the
    norm_topk_prob switch and qwen3-style expert weight names
    (mlp.gate router, experts.*.gate_proj/up_proj/down_proj). Logits and
    engine greedy must match HF — both norm_topk_prob settings."""
    import torch
    from transformers import Qwen3MoeConfig, Qwen3MoeForCausalLM

    from vllm_production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, SchedulerConfig,
    )
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.request import SamplingParams
    from vllm_production_stack_tpu.models.loader import (
        load_checkpoint_params,
    )
    from vllm_production_stack_tpu.models.registry import (
        resolve_model_config,
    )

    for norm in (True, False):
        d = tmp_path / f"norm-{norm}"
        torch.manual_seed(123 + int(norm))
        hf_cfg = Qwen3MoeConfig(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=96, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            num_experts=4, num_experts_per_tok=2, norm_topk_prob=norm,
            rope_theta=10000.0, rms_norm_eps=1e-5,
            max_position_embeddings=256, tie_word_embeddings=False,
            decoder_sparse_step=1, mlp_only_layers=[],
            attn_implementation="eager", torch_dtype="float32",
        )
        model = Qwen3MoeForCausalLM(hf_cfg).eval()
        model.save_pretrained(d, safe_serialization=True)

        cfg = resolve_model_config(str(d), max_model_len=256,
                                   dtype="float32")
        assert cfg.architecture == "qwen3moe" and cfg.qk_norm
        assert cfg.num_experts == 4 and cfg.norm_topk_prob is norm
        assert cfg.intermediate_size == 96  # the EXPERT width
        params = load_checkpoint_params(cfg)
        tokens = list(np.random.RandomState(21).randint(0, 512, size=33))
        ours = jax_prefill_logits(cfg, params, tokens)
        with torch.no_grad():
            theirs = model(torch.tensor([tokens])).logits[0].numpy()
        np.testing.assert_allclose(ours, theirs, rtol=3e-4, atol=3e-4)

        if norm:  # engine e2e once (the slow half)
            engine = LLMEngine(EngineConfig(
                model=cfg,
                cache=CacheConfig(block_size=8, num_blocks=64),
                scheduler=SchedulerConfig(
                    max_num_seqs=2, max_num_batched_tokens=32,
                    prefill_buckets=(16, 32), decode_buckets=(2,),
                    decode_window=4,
                ),
            ))
            got = engine.generate(
                [tokens], SamplingParams(max_tokens=8, temperature=0.0,
                                         ignore_eos=True),
            )[0]["token_ids"]
            with torch.no_grad():
                want = model.generate(
                    torch.tensor([tokens]), max_new_tokens=8,
                    do_sample=False,
                )[0][len(tokens):].tolist()
            assert got == want, (got, want)


def test_qwen3moe_config_with_defaults_omitted(tmp_path):
    """HF use_diff serialization omits class-default fields: a config.json
    carrying ONLY the overrides must still resolve (the published
    30B-A3B values ARE the class defaults for num_experts /
    moe_intermediate_size, so re-saved checkpoints omit them)."""
    import json

    from vllm_production_stack_tpu.models.registry import (
        resolve_model_config,
    )

    (tmp_path / "config.json").write_text(json.dumps({
        "architectures": ["Qwen3MoeForCausalLM"],
        "vocab_size": 512, "hidden_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16,
        "max_position_embeddings": 256,
        # note: no intermediate_size, num_experts, moe_intermediate_size
    }))
    cfg = resolve_model_config(str(tmp_path), max_model_len=256,
                               dtype="float32")
    assert cfg.num_experts == 128 and cfg.num_experts_per_tok == 8
    assert cfg.intermediate_size == 768  # moe default, not dense
    assert cfg.qk_norm and not cfg.norm_topk_prob


# -- OLMoE (ISSUE 26): HF parity, the grouped expert path, the checkpoint ------


def olmoe_cfg():
    from vllm_production_stack_tpu.models.registry import resolve_model_config

    return resolve_model_config("tiny-olmoe")


def hf_olmoe_from_params(cfg: ModelConfig, params):
    from transformers import OlmoeConfig, OlmoeForCausalLM

    hf_cfg = OlmoeConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads,
        num_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_norm_eps,
        max_position_embeddings=cfg.max_model_len,
        tie_word_embeddings=cfg.tie_word_embeddings, clip_qkv=None,
    )
    model = OlmoeForCausalLM(hf_cfg).eval()

    def t(x):  # jax (in, out) -> torch (out, in)
        return torch.from_numpy(np.asarray(x, dtype=np.float32).T.copy())

    def v(x):
        return torch.from_numpy(np.asarray(x, dtype=np.float32).copy())

    lp = params["layers"]
    sd = {"model.embed_tokens.weight": v(params["embed"]),
          "model.norm.weight": v(params["final_norm"]),
          "lm_head.weight": t(params["lm_head"])}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                             ("wv", "v_proj"), ("wo", "o_proj")):
            sd[p + f"self_attn.{theirs}.weight"] = t(lp["attn"][ours][i])
        sd[p + "self_attn.q_norm.weight"] = v(lp["attn"]["q_norm"][i])
        sd[p + "self_attn.k_norm.weight"] = v(lp["attn"]["k_norm"][i])
        sd[p + "mlp.gate.weight"] = t(lp["moe"]["router"][i])
        for j in range(cfg.num_experts):
            e = p + f"mlp.experts.{j}."
            sd[e + "gate_proj.weight"] = t(lp["moe"]["gate"][i, j])
            sd[e + "up_proj.weight"] = t(lp["moe"]["up"][i, j])
            sd[e + "down_proj.weight"] = t(lp["moe"]["down"][i, j])
        sd[p + "input_layernorm.weight"] = v(lp["input_norm"][i])
        sd[p + "post_attention_layernorm.weight"] = v(lp["post_attn_norm"][i])
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected, unexpected
    assert all("inv_freq" in m for m in missing), missing
    return model


def _olmoe_params(cfg, seed):
    """Random weights with norm vectors away from 1, so that a wrong norm
    scope or order would show."""
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 100))
    attn = dict(params["layers"]["attn"])
    attn["q_norm"] = 1.0 + 0.3 * jax.random.normal(k1, attn["q_norm"].shape)
    attn["k_norm"] = 1.0 + 0.3 * jax.random.normal(k2, attn["k_norm"].shape)
    return {**params, "layers": {**params["layers"], "attn": attn}}


def test_moe_logits_match_hf_olmoe():
    cfg = olmoe_cfg()
    assert cfg.qk_norm_flat and not cfg.norm_topk_prob
    assert not cfg.post_norms_only and not cfg.norm_scale_f32
    params = _olmoe_params(cfg, 0)
    hf = hf_olmoe_from_params(cfg, params)
    tokens = np.random.RandomState(0).randint(1, cfg.vocab_size, size=24)
    ours = jax_prefill_logits(cfg, params, tokens)
    with torch.no_grad():
        theirs = hf(torch.tensor(tokens)[None]).logits[0].float().numpy()
    np.testing.assert_allclose(ours, theirs, atol=2e-4, rtol=2e-3)


def _routing(kind: str, n: int, e: int, k: int, rng):
    if kind == "random":
        topi = np.stack([rng.permutation(e)[:k] for _ in range(n)])
    elif kind == "empty-experts":  # experts e-2 and e-1 get no token
        topi = np.stack([rng.permutation(e - 2)[:k] for _ in range(n)])
    else:  # every token's first choice is expert 0
        topi = np.stack([
            np.concatenate([[0], 1 + rng.permutation(e - 1)[:k - 1]])
            for _ in range(n)])
    topv = rng.uniform(0.05, 0.5, size=(n, k)).astype(np.float32)
    return jnp.asarray(topv), jnp.asarray(topi, jnp.int32)


@pytest.mark.parametrize("quantization", [None, "int8"])
@pytest.mark.parametrize("kind", ["random", "empty-experts", "one-expert"])
def test_grouped_experts_equal_all_experts(kind, quantization):
    """The two expert computations `_moe_mlp` chooses between by token
    count give the same result on any routing: every pair computed, an
    expert without tokens costs nothing and breaks nothing, and one expert
    may take a row of every token."""
    from vllm_production_stack_tpu.models.quantization import quantize_params

    cfg = ModelConfig.tiny(
        model="tiny-olmoe", architecture="olmoe", intermediate_size=32,
        num_experts=8, num_experts_per_tok=3, norm_topk_prob=False,
        quantization=quantization,
    )
    params = quantize_params(
        cfg, llama.init_params(cfg, jax.random.PRNGKey(3)))
    mp = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    rng = np.random.RandomState(5)
    n, e, k = 37, cfg.num_experts, cfg.num_experts_per_tok
    xf = jnp.asarray(rng.normal(size=(n, cfg.hidden_size)), jnp.float32)
    topv, topi = _routing(kind, n, e, k, rng)
    chosen = jax.nn.one_hot(topi, e, dtype=jnp.float32)
    sizes = jnp.sum(chosen, axis=(0, 1)).astype(jnp.int32)
    if kind == "empty-experts":
        assert int(sizes[-1]) == 0 == int(sizes[-2])
    if kind == "one-expert":
        assert int(sizes[0]) == n
    dense = llama._experts_all(cfg, mp, xf, topv, chosen)
    # layer 1 of the stacked leaves, read in place
    assert cfg.num_layers == 2
    mp = jax.tree.map(lambda a: a[1], params["layers"]["moe"])
    dense = llama._experts_all(cfg, mp, xf, topv, chosen)
    grouped = llama._experts_grouped(
        cfg, params["layers"]["moe"], 1, xf, topv, topi, sizes)
    np.testing.assert_allclose(
        np.asarray(grouped), np.asarray(dense), atol=1e-5, rtol=1e-5)


def test_moe_mlp_takes_the_grouped_path_above_the_token_limit(monkeypatch):
    """`_moe_mlp` chooses by the static token count, and reports routing
    the same either way."""
    cfg = olmoe_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(7))
    mp = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 12, cfg.hidden_size))
    y_all, r_all = llama._moe_mlp(cfg, mp, x)
    called = []
    real = llama._experts_grouped
    monkeypatch.setattr(llama, "_ALL_EXPERTS_MAX_TOKENS", 23)
    monkeypatch.setattr(
        llama, "_experts_grouped",
        lambda *a: called.append(1) or real(*a))
    y_grp, r_grp = llama._moe_mlp(cfg, mp, x)
    assert called
    np.testing.assert_allclose(np.asarray(y_grp), np.asarray(y_all),
                               atol=1e-5, rtol=1e-5)
    assert int(r_grp["touched"]) == int(r_all["touched"]) <= cfg.num_experts
    assert r_grp["experts"].shape == (2, 12, cfg.num_experts_per_tok)


def test_olmoe_checkpoint_roundtrip_and_config_parse(tmp_path):
    """save_pretrained -> config.json parse -> our loader -> HF's logits."""
    import json

    from vllm_production_stack_tpu.models.loader import load_checkpoint_params
    from vllm_production_stack_tpu.models.registry import (
        PRESETS, resolve_model_config,
    )

    cfg0 = olmoe_cfg()
    hf = hf_olmoe_from_params(cfg0, _olmoe_params(cfg0, 4))
    hf.save_pretrained(tmp_path, safe_serialization=True)
    cfg = resolve_model_config(str(tmp_path), dtype="float32")
    assert cfg.architecture == "olmoe"
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (8, 2)
    assert cfg.qk_norm_flat and not cfg.qk_norm and not cfg.norm_topk_prob
    assert not cfg.post_norms_only and not cfg.norm_scale_f32
    assert cfg.intermediate_size == cfg0.intermediate_size
    params = jax.tree.map(jnp.asarray, load_checkpoint_params(cfg))
    tokens = np.random.RandomState(4).randint(1, cfg.vocab_size, size=16)
    ours = jax_prefill_logits(cfg, params, tokens)
    with torch.no_grad():
        theirs = hf(torch.tensor(tokens)[None]).logits[0].float().numpy()
    np.testing.assert_allclose(ours, theirs, atol=2e-4, rtol=2e-3)

    # a config that clips q/k/v is refused, not served without the clip
    doc = json.loads((tmp_path / "config.json").read_text())
    doc["clip_qkv"] = 8.0
    (tmp_path / "config.json").write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="clip_qkv"):
        resolve_model_config(str(tmp_path))

    # the preset carries the published numbers
    pub = PRESETS["olmoe-1b-7b"]
    assert (pub["num_layers"], pub["hidden_size"], pub["num_heads"],
            pub["num_kv_heads"], pub["head_dim"]) == (16, 2048, 16, 16, 128)
    assert (pub["num_experts"], pub["num_experts_per_tok"],
            pub["intermediate_size"], pub["vocab_size"]) == (
                64, 8, 1024, 50304)
    assert pub["max_model_len"] == 4096 and pub["rope_theta"] == 10000.0
