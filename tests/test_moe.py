"""Mixtral-family sparse MoE: HF logits parity, expert-parallel sharding on
the virtual mesh, engine e2e, and checkpoint round-trip."""

import functools

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

    def th(x):  # jax (heads, head_dim, in) -> torch (out, in): as it lies
        x = np.asarray(x, dtype=np.float32)
        return torch.from_numpy(x.reshape(-1, x.shape[-1]).copy())

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
            sd[p + f"self_attn.{theirs}.weight"] = (
                t if ours == "wo" else th)(lp["attn"][ours][i])
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


# -- the touched-experts kernel and the rule that chooses it (ISSUE 36) -------


def _touched_case(case: str, dtype: str, quantization, n: int = 16):
    """(cfg, params, xf, topv, topi) for one kernel case of `n` tokens: a
    model of two stacked expert layers at toy widths whose first and last
    two experts are held elsewhere in the "held-share" cases."""
    from vllm_production_stack_tpu.models.quantization import quantize_params

    held = case in ("held-share", "none-touched")
    # "cell-chunk": the OLMoE cell's routing at toy widths, 64 x top-8
    e, k = (64, 8) if case == "cell-chunk" else (8, 3)
    cfg = ModelConfig.tiny(
        model="tiny-olmoe", architecture="olmoe", intermediate_size=32,
        num_experts=e, num_experts_per_tok=k, norm_topk_prob=False,
        dtype=dtype, quantization=quantization,
        num_experts_held=4 if held else 0, first_expert_held=2)
    params = quantize_params(
        cfg, llama.init_params(cfg, jax.random.PRNGKey(3)))
    rng = np.random.RandomState(5)
    xf = jnp.asarray(rng.normal(size=(n, cfg.hidden_size)), cfg.dtype)
    if case == "one-expert":  # every pair of every row on expert 5
        topi = np.full((n, k), 5)
    elif case == "all-touched":
        topi = np.stack([(np.arange(k) + r) % e for r in range(n)])
    elif case == "none-touched":  # every pair on another chip's experts
        topi = np.stack([rng.permutation([0, 1, 6, 7])[:k] for _ in range(n)])
    elif case == "cell-chunk":  # a random router's: every expert touched
        topi = np.stack([rng.permutation(e)[:k] for _ in range(n)])
    else:  # "random", "held-share": some experts without a pair
        topi = np.stack([rng.permutation(e - 1)[:k] for _ in range(n)])
    topv = jnp.asarray(rng.uniform(0.05, 0.5, size=(n, k)), jnp.float32)
    return cfg, params, xf, topv, jnp.asarray(topi, jnp.int32)


def _both_forms(cfg, params, layer, xf, topv, topi):
    local = topi - cfg.first_expert_held if cfg.num_experts_held else topi
    chosen = jax.nn.one_hot(local, cfg.held_experts, dtype=jnp.float32)
    sizes = jnp.sum(chosen, axis=(0, 1)).astype(jnp.int32)
    stacked = params["layers"]["moe"]
    mp = jax.tree.map(lambda a: a[layer], stacked)
    dense = llama._experts_all(cfg, mp, xf, topv, chosen)
    kernel = llama._experts_touched(
        cfg, stacked, layer, xf, topv, chosen, sizes, True)
    return dense, kernel, sizes


@pytest.mark.parametrize("dtype,quantization", [
    ("bfloat16", None), ("bfloat16", "int8"), ("float32", None)])
@pytest.mark.parametrize("case", [
    "random", "one-expert", "all-touched", "held-share", "none-touched"])
def test_touched_kernel_equals_all_experts(case, dtype, quantization):
    """The fused kernel (interpret mode) against the all-experts einsum on
    layer 1 of the stacked leaves, read in place: equal to the rounding of
    the model's dtype (the kernel keeps float32 where the einsum rounds),
    at one, some and all experts touched, with pairs on experts held
    elsewhere, and zeros where no held expert got a pair."""
    cfg, params, xf, topv, topi = _touched_case(case, dtype, quantization)
    dense, kernel, sizes = _both_forms(cfg, params, 1, xf, topv, topi)
    touched = int(jnp.sum(sizes > 0))
    assert touched == {"one-expert": 1, "all-touched": 8,
                       "none-touched": 0}.get(case, touched)
    assert kernel.shape == dense.shape and kernel.dtype == dense.dtype
    if case == "none-touched":
        assert not np.asarray(kernel, np.float32).any()
    tol = 3e-2 if dtype == "bfloat16" else 1e-5
    np.testing.assert_allclose(
        np.asarray(kernel, np.float32), np.asarray(dense, np.float32),
        atol=tol * float(jnp.max(jnp.abs(dense.astype(jnp.float32))) + 1),
        rtol=tol)


def test_touched_ids_compact_the_touched_experts():
    from vllm_production_stack_tpu.ops.moe_experts_pallas import touched_ids

    ids, n = touched_ids(jnp.asarray([0, 2, 0, 1, 5, 0], jnp.int32), 6)
    assert int(n[0]) == 3 and ids.tolist() == [1, 3, 4, 4, 4, 4]
    ids, n = touched_ids(jnp.zeros((6,), jnp.int32), 4)
    assert int(n[0]) == 0 and ids.tolist() == [0, 0, 0, 0]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_a_rows_expert_output_does_not_depend_on_its_batch(dtype):
    """Bit for bit: a row alone (fifteen padding rows of zeros that route
    nowhere near it) and the same row among fifteen others that touch every
    expert — its k experts are summed in ascending order with exact zeros
    between them (the harness's batch-invariance probe)."""
    cfg, params, xf, topv, topi = _touched_case("all-touched", dtype, None)
    _, among, _ = _both_forms(cfg, params, 1, xf, topv, topi)
    alone_x = jnp.zeros_like(xf).at[7].set(xf[7])
    alone_i = jnp.broadcast_to(topi[7], topi.shape)
    _, alone, sizes = _both_forms(cfg, params, 1, alone_x, topv, alone_i)
    assert int(jnp.sum(sizes > 0)) == cfg.num_experts_per_tok
    assert np.asarray(alone[7]).tobytes() == np.asarray(among[7]).tobytes()


def test_moe_mlp_reads_a_leading_dense_models_expert_stack_in_place():
    """tiny-kanana: layer 1 is index 0 of the expert stack. `_moe_mlp`
    under a backend that may hold the kernel takes it at two rows (the
    rule) and gives what the einsum gives, routing and all."""
    from vllm_production_stack_tpu.models.registry import resolve_model_config

    cfg = resolve_model_config("tiny-kanana")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 1, cfg.hidden_size))
    assert llama.moe_experts_form(cfg, 2, kernel=True) == "touched_kernel"
    for i in (1, 2):
        lp = llama._layer_params(cfg, params, i)
        assert lp["moe_stacked"][1] == i - 1
        want, r_want = llama._moe_mlp(cfg, lp["moe"], x, lp["moe_stacked"])
        got, r_got = llama._moe_mlp(
            cfg, lp["moe"], x, lp["moe_stacked"], "pallas_interpret")
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)
        assert int(r_got["touched"]) == int(r_want["touched"])
        np.testing.assert_array_equal(r_got["experts"], r_want["experts"])


@pytest.mark.parametrize("model,tokens,quantization,want", [
    # the benchmark cells' one decode bucket (16 rows)
    ("kanana-2-30b-a3b-8l", 16, None, "touched_kernel"),  # 0.54
    ("laguna-s-2.1-ep4-9l", 16, None, "touched_kernel"),  # 0.47 (of 256)
    ("olmoe-1b-7b", 16, "int8", "all_experts"),  # 0.88
    ("tiny-mixtral", 16, None, "all_experts"),  # 1.00
    # the OLMoE cell's prefill chunk: 5 x the tokens at which the einsum's
    # flops overtake its int8 bytes (ISSUE 42)
    ("olmoe-1b-7b", 512, "int8", "grouped_kernel"),
    ("kanana-2-30b-a3b-8l", 64, None, "all_experts"),  # 0.95
    ("kanana-2-30b-a3b-8l", 32, None, "touched_kernel"),  # 0.79
    ("kanana-2-30b-a3b-8l", 2048, None, "grouped_kernel"),
    ("olmoe-1b-7b", 8, "int8", "touched_kernel"),  # 0.66: int8 leaves too
    # the long-document cells' one prefill bucket, and the token limit
    ("laguna-s-2.1-ep4-9l", 2048, None, "grouped_kernel"),
    ("kanana-2-30b-a3b-8l", 512, None, "grouped_kernel"),
    ("kanana-2-30b-a3b-8l", 513, None, "grouped_kernel"),
    ("olmoe-1b-7b", 513, "int8", "grouped_kernel"),  # int8 leaves too
    ("tiny-mixtral", 1024, None, "grouped_kernel"),
    # where the einsum ends: 96 tokens a weight byte of the expert leaves
    ("olmoe-1b-7b", 96, "int8", "all_experts"),
    ("olmoe-1b-7b", 97, "int8", "grouped_kernel"),
    ("kanana-2-30b-a3b-8l", 192, None, "all_experts"),  # bf16 leaves
    ("kanana-2-30b-a3b-8l", 193, None, "grouped_kernel"),
    ("laguna-s-2.1-ep4-9l", 192, None, "all_experts"),  # a held share too
    ("laguna-s-2.1-ep4-9l", 193, None, "grouped_kernel"),
    ("olmoe-1b-7b", 192, None, "all_experts"),  # the same model in bf16
    ("tiny-olmoe", 384, None, "all_experts"),  # float32 leaves
    ("tiny-olmoe", 385, None, "grouped_kernel"),
    # the SDAR cell's decode pass (16 rows x a block of 4) keeps the einsum
    ("sdar-30b-a3b-chat-8l", 64, None, "all_experts"),
    ("sdar-30b-a3b-chat-8l", 1024, None, "grouped_kernel"),  # its chunk
])
def test_the_shape_rule_picks_the_expert_form(model, tokens, quantization,
                                              want):
    """One family, four forms, from (N, k, published E, the expert leaves'
    itemsize) alone."""
    from vllm_production_stack_tpu.models.registry import resolve_model_config

    cfg = resolve_model_config(model, quantization=quantization)
    assert llama.moe_experts_form(cfg, tokens, kernel=True) == want
    # a program that may hold no Pallas kernel keeps its two forms and the
    # 512-token limit: `ragged_dot` loses to the einsum there
    assert llama.moe_experts_form(cfg, tokens, kernel=False) == (
        "grouped" if tokens > 512 else "all_experts")


@pytest.mark.parametrize("mesh_size,attention_backend,want", [
    (1, "pallas_interpret", "pallas_interpret"),
    (1, "xla", "xla"),  # off the TPU nothing but interpret mode runs it
    (2, "pallas_interpret", "xla"),  # any mesh keeps the einsum
])
def test_the_runner_resolves_the_einsum_under_a_mesh(mesh_size,
                                                     attention_backend, want):
    from types import SimpleNamespace

    from vllm_production_stack_tpu.engine.model_runner import ModelRunner

    stub = SimpleNamespace(
        config=SimpleNamespace(
            model=olmoe_cfg(), attention_backend=attention_backend),
        mesh=SimpleNamespace(size=mesh_size))
    assert ModelRunner._resolve_moe_backend(stub) == want


@pytest.mark.parametrize("tokens,mesh_size,want", [
    (2048, 1, "grouped_kernel"),
    (2048, 2, "grouped"),  # any mesh keeps ragged_dot: no shard_map wrapper
    (512, 1, "grouped_kernel"),  # the OLMoE cell's chunk (ISSUE 42)
    (512, 2, "all_experts"),  # under a mesh the einsum ends at 512 as before
])
def test_the_runner_reports_the_prefill_form(tokens, mesh_size, want):
    """`/debug/programs` -> `device.moe.prefill` and the form the read
    counter is told follow the rule and the mesh (ISSUE 38), at the OLMoE
    cell's int8 leaves (ISSUE 42)."""
    from types import SimpleNamespace

    from vllm_production_stack_tpu.engine.model_runner import ModelRunner
    from vllm_production_stack_tpu.models.registry import resolve_model_config

    stub = SimpleNamespace(
        config=SimpleNamespace(
            model=resolve_model_config("olmoe-1b-7b", quantization="int8"),
            attention_backend="pallas_interpret"),
        mesh=SimpleNamespace(size=mesh_size))
    stub._moe_backend = ModelRunner._resolve_moe_backend(stub)
    assert ModelRunner.moe_experts_form(stub, tokens) == want


# -- the fused grouped-expert kernel of a prefill chunk (ISSUE 38) ------------


def _np_grouped(xs, sizes, gate, up, down):
    """Each group of sorted rows through its own expert, in float64."""
    f = lambda a: np.asarray(a, np.float64)
    out, lo = np.zeros(xs.shape, np.float64), 0
    for e, n in enumerate(np.asarray(sizes)):
        x = f(xs[lo:lo + n])
        a = x @ f(gate[e])
        out[lo:lo + n] = (a / (1 + np.exp(-a)) * (x @ f(up[e]))) @ f(down[e])
        lo += n
    return out, lo


@pytest.mark.parametrize("sizes", [
    [7, 0, 40, 3, 10],  # an expert without rows; tiles shared by 2-3 groups
    [0, 0, 0, 0, 0],  # no pair landed here: every step is skipped
    [77, 0, 0, 0, 0],  # one expert with every row
    [0, 0, 0, 0, 37],  # the last expert only, rows behind it uncomputed
    [16, 16, 16, 16, 13],  # groups on tile boundaries
    [1, 1, 1, 1, 1],  # five groups inside one tile
])
def test_prefill_kernel_runs_each_group_under_its_expert(sizes):
    """`moe_prefill_experts` (interpret mode) on 77 sorted rows — no
    multiple of the 16-row tile — over layer 1 of stacked leaves: each
    group's rows through that expert, whatever the tiles straddle."""
    from vllm_production_stack_tpu.ops.moe_experts_pallas import (
        moe_prefill_experts,
    )

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    layers, e, h, inner, m = 2, 5, 128, 256, 77
    gate = jax.random.normal(ks[0], (layers, e, h, inner)) * h ** -0.5
    up = jax.random.normal(ks[1], (layers, e, h, inner)) * h ** -0.5
    down = jax.random.normal(ks[2], (layers, e, inner, h)) * inner ** -0.5
    xs = jax.random.normal(ks[3], (m, h))
    got = moe_prefill_experts(
        xs, jnp.asarray(sizes, jnp.int32), gate, up, down, layer=1,
        act=jax.nn.silu, row_tile=16, interpret=True)
    want, held = _np_grouped(xs, sizes, gate[1], up[1], down[1])
    assert got.shape == xs.shape and got.dtype == xs.dtype
    np.testing.assert_allclose(
        np.asarray(got)[:held], want[:held], atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("sizes,want", [
    # 3 tiles of 4 rows; group 0 = rows 0-5 (tiles 0, 1), group 2 = rows
    # 6-6 (tile 1), group 3 = rows 7-11 (tiles 1, 2): five visits of 6 steps
    ([6, 0, 1, 5], ([0, 0, 2, 3, 3, 3], [0, 1, 1, 1, 2, 2], 5)),
    ([0, 0, 0, 0], ([3, 3, 3, 3, 3, 3], [0, 0, 0, 0, 0, 0], 0)),
    ([0, 12, 0, 0], ([1, 1, 1, 1, 1, 1], [0, 1, 2, 2, 2, 2], 3)),
])
def test_visit_tables_walk_experts_then_tiles(sizes, want):
    from vllm_production_stack_tpu.ops.moe_experts_pallas import visit_tables

    expert, tile, lo, hi, n = visit_tables(
        jnp.asarray(sizes, jnp.int32), 3, 4)
    assert (expert.tolist(), tile.tolist(), int(n[0])) == want
    assert hi.tolist() == np.cumsum(sizes).tolist()
    assert (hi - lo).tolist() == sizes
    # a tile's visits are consecutive steps: the tile never steps back
    assert (np.diff(tile) >= 0).all()


def _prefill_case(case: str, dtype: str, quantization):
    """90 tokens x top-3 = 270 sorted rows: three row tiles of 128, the last
    short; "cell-chunk": the OLMoE cell's 512 tokens x top-8 of 64."""
    return _touched_case(
        case, dtype, quantization, n=512 if case == "cell-chunk" else 90)


def _grouped_forms(cfg, params, layer, xf, topv, topi):
    """(all-experts einsum, ragged_dot, fused kernel, group sizes)."""
    local = topi - cfg.first_expert_held if cfg.num_experts_held else topi
    chosen = jax.nn.one_hot(local, cfg.held_experts, dtype=jnp.float32)
    sizes = jnp.sum(chosen, axis=(0, 1)).astype(jnp.int32)
    stacked = params["layers"]["moe"]
    mp = jax.tree.map(lambda a: a[layer], stacked)
    return (llama._experts_all(cfg, mp, xf, topv, chosen),
            llama._experts_grouped(cfg, stacked, layer, xf, topv, topi, sizes),
            llama._experts_grouped(
                cfg, stacked, layer, xf, topv, topi, sizes, True),
            sizes)


@pytest.mark.parametrize("dtype,quantization", [
    ("bfloat16", None), ("bfloat16", "int8"), ("float32", None)])
@pytest.mark.parametrize("case", [
    "random", "one-expert", "all-touched", "held-share", "none-touched",
    "cell-chunk"])
def test_grouped_kernel_equals_ragged_dot_and_all_experts(
        case, dtype, quantization):
    """The "grouped_kernel" form (interpret mode) against its two oracles
    on layer 1 of the stacked leaves: equal to the rounding of the model's
    dtype (the kernel rounds once where `ragged_dot` rounds three times),
    with an expert that got no row ("random": expert 7), one that got every
    row, and exact zeros from pairs that land on experts held elsewhere.
    "cell-chunk" is the OLMoE cell's 512-token chunk (ISSUE 42): 4,096 pairs
    over 64 experts, ~64 a group, so every 128-row tile straddles two or
    three groups and every expert is visited."""
    cfg, params, xf, topv, topi = _prefill_case(case, dtype, quantization)
    dense, ragged, kernel, sizes = _grouped_forms(
        cfg, params, 1, xf, topv, topi)
    assert int(jnp.sum(sizes)) == {
        "none-touched": 0, "held-share": int(jnp.sum(sizes)),
        "cell-chunk": 4096}.get(case, 270)
    if case == "random":
        assert int(sizes[-1]) == 0
    if case == "cell-chunk":
        assert 0 < int(sizes.min()) and int(sizes.max()) < 128
    assert kernel.shape == dense.shape and kernel.dtype == dense.dtype
    if case == "none-touched":
        assert not np.asarray(kernel, np.float32).any()
    if case == "held-share":
        # a token none of whose experts is held here gets exact zeros
        away = np.asarray(jnp.all((topi < 2) | (topi > 5), axis=1))
        assert away.any()
        assert not np.asarray(kernel, np.float32)[away].any()
    tol = 3e-2 if dtype == "bfloat16" else 1e-5
    for oracle in (dense, ragged):
        np.testing.assert_allclose(
            np.asarray(kernel, np.float32), np.asarray(oracle, np.float32),
            atol=tol * float(jnp.max(jnp.abs(dense.astype(jnp.float32))) + 1),
            rtol=tol)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_a_rows_grouped_output_does_not_depend_on_its_chunk(dtype):
    """Bit for bit: token 7 among 89 others, and the same token where the
    others are zeros routed elsewhere — its pairs then sit at other rows of
    other tiles of the sorted order, under the same experts."""
    cfg, params, xf, topv, topi = _prefill_case("random", dtype, None)
    *_, among, _ = _grouped_forms(cfg, params, 1, xf, topv, topi)
    alone_x = jnp.zeros_like(xf).at[7].set(xf[7])
    others = (topi[7] + 1) % cfg.num_experts  # a rotation of token 7's
    alone_i = jnp.broadcast_to(others, topi.shape).at[7].set(topi[7])
    *_, alone, _ = _grouped_forms(cfg, params, 1, alone_x, topv, alone_i)
    assert np.asarray(alone[7]).tobytes() == np.asarray(among[7]).tobytes()


def test_moe_mlp_takes_the_grouped_kernel_where_it_may_hold_one(monkeypatch):
    """`_moe_mlp` above the token limit: ragged_dot under the XLA backend,
    the fused kernel under a backend that may hold one, the same result
    and the same routing report."""
    cfg = olmoe_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(7))
    mp = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 12, cfg.hidden_size))
    monkeypatch.setattr(llama, "_ALL_EXPERTS_MAX_TOKENS", 23)
    forms = []
    real = llama._experts_grouped
    monkeypatch.setattr(
        llama, "_experts_grouped",
        lambda *a: forms.append(len(a)) or real(*a))
    y_x, r_x = llama._moe_mlp(cfg, mp, x)
    y_k, r_k = llama._moe_mlp(cfg, mp, x, None, "pallas_interpret")
    assert forms == [7, 8]  # the kernel form hands on `interpret`
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_x),
                               atol=1e-5, rtol=1e-5)
    assert int(r_k["touched"]) == int(r_x["touched"])
    np.testing.assert_array_equal(r_k["experts"], r_x["experts"])


@pytest.mark.parametrize("model", ["tiny-llama", "tiny-olmoe", "tiny-mixtral"])
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_programs_the_rule_leaves_alone_are_the_same_text(model, program):
    """A dense model, and an MoE model whose 16 rows touch nearly every
    expert, lower to the SAME StableHLO whether or not their programs may
    hold the kernel: the rule, not the backend, decides. (The prefill
    program is 16 rows x 16 tokens: under the 384 tokens at which float32
    leaves leave the einsum where a kernel may be held, ISSUE 42.)"""
    from vllm_production_stack_tpu.models.registry import resolve_model_config

    cfg = resolve_model_config(model)
    params = jax.eval_shape(
        lambda k: llama.init_params(cfg, k), jax.random.PRNGKey(0))
    kv = jax.eval_shape(lambda: llama.init_kv_cache(cfg, 16, 8))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    rows = 16

    def decode(moe_backend, params, kv, staged, tokens, tables):
        return llama.decode_window_step(
            cfg, params, tokens, tokens, kv, tables, staged, jnp.int32(0),
            tokens, moe_stats=[] if cfg.num_experts else None,
            moe_backend=moe_backend)

    def prefill(moe_backend, params, kv, tokens, tables, lens):
        return llama.forward(
            cfg, params, tokens, tokens, kv, tables,
            jnp.zeros((rows * 16,), jnp.int32), lens,
            moe_stats=[] if cfg.num_experts else None,
            moe_backend=moe_backend)

    if program == "decode":
        staged = jax.eval_shape(lambda: llama.init_staged_kv(cfg, 4, rows))
        args = (params, kv, staged, i32(rows), i32(rows, 4))
        fn = decode
    else:
        args = (params, kv, i32(rows, 16), i32(rows, 8), i32(rows))
        fn = prefill
    texts = {
        b: jax.jit(fn, static_argnums=0).lower(b, *args).as_text()
        for b in ("xla", "pallas")}
    assert texts["xla"] == texts["pallas"]
    assert "moe_decode_experts" not in texts["pallas"]
    assert "moe_prefill_experts" not in texts["pallas"]


@pytest.mark.parametrize("model", ["tiny-olmoe", "tiny-mixtral", "tiny-kanana"])
def test_a_long_prefill_program_holds_the_kernel_or_ragged_dot(model):
    """Above the token limit (16 rows x 64 = 1,024 tokens) the backend does
    decide: ragged_dot and no kernel under XLA (the parent's program), the
    kernel and no ragged_dot where the program may hold one."""
    from vllm_production_stack_tpu.models.registry import resolve_model_config

    cfg = resolve_model_config(model)
    params = jax.eval_shape(
        lambda k: llama.init_params(cfg, k), jax.random.PRNGKey(0))
    kv = jax.eval_shape(lambda: llama.init_kv_cache(cfg, 16, 8))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    rows = 16

    def prefill(moe_backend, params, kv, tokens, tables, lens):
        return llama.forward(
            cfg, params, tokens, tokens, kv, tables,
            jnp.zeros((rows * 64,), jnp.int32), lens, moe_stats=[],
            moe_backend=moe_backend)

    # (a jaxpr, not a lowering: off the TPU only interpret mode lowers)
    texts = {
        b: str(jax.make_jaxpr(functools.partial(prefill, b))(
            params, kv, i32(rows, 64), i32(rows, 8), i32(rows)))
        for b in ("xla", "pallas")}
    assert "ragged_dot" in texts["xla"]
    assert "moe_prefill_experts" not in texts["xla"]
    assert "moe_prefill_experts" in texts["pallas"]
    assert "ragged_dot" not in texts["pallas"]


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
