"""The parts of GLM-5.2's `glm_moe_dsa` decoder (ISSUE 55) at tiny size on
the CPU, apart from the served engine against its reference
(tests/test_glm_dsa.py): the exact top-k against a stable argsort (ties,
fewer keys than k, padding), a chunk's selection against a decode step's,
the sixteen shares of the experts against the uncut layer, the byte counts
against the arrays, the refusals by name, the key mapper and the names."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import engine_kit as ek
from vllm_production_stack_tpu import metrics_contract as mc
from vllm_production_stack_tpu.engine import memory
from vllm_production_stack_tpu.engine.config import (
    CacheConfig, LayerSpec, LoRAConfig, ParallelConfig,
)
from vllm_production_stack_tpu.models import llama
from vllm_production_stack_tpu.models.registry import resolve_model_config
from vllm_production_stack_tpu.ops import dsa_attention as dsa
from vllm_production_stack_tpu.testing import reference_glm_dsa as reference


@pytest.fixture(scope="module")
def cfg():
    return resolve_model_config("tiny-glm-dsa")


# -- the layer kinds -----------------------------------------------------------

def test_selecting_is_something_a_layer_says(cfg):
    assert [s.indexer for s in cfg.layer_specs] == [
        "full", "shared", "shared", "full", "shared"]
    assert cfg.index_layers == (0, 3)
    assert cfg.selecting_layers == cfg.latent_layers == (0, 1, 2, 3, 4)
    assert cfg.mla and not cfg.tree_by_kind and not cfg.state_layers
    kanana = resolve_model_config("tiny-kanana")
    assert not kanana.index_layers and not kanana.selecting_layers
    with pytest.raises(ValueError, match="indexer 'full' on a 'softmax'"):
        LayerSpec(indexer="full")
    with pytest.raises(ValueError, match="indexer 'sometimes'"):
        LayerSpec(mixer="latent", indexer="sometimes")
    # the first layer has to make the selection the others read
    borrowed_first = dataclasses.replace(cfg, layers=tuple(
        dataclasses.replace(s, indexer="shared") for s in cfg.layers))
    with pytest.raises(ValueError, match="start with a 'full' one"):
        borrowed_first.layer_specs
    whole = resolve_model_config("glm-5.2")
    assert whole.index_layers[:5] == (0, 1, 2, 6, 10)
    assert (len(whole.index_layers), whole.num_moe_layers) == (21, 75)


# -- the exact top-k ---------------------------------------------------------

def _argsort_mask(scores, visible, k):
    """The rule, plainly: a stable argsort of the negated visible scores."""
    out = np.zeros(scores.shape, bool)
    for q in range(scores.shape[0]):
        seen = np.flatnonzero(visible[q])
        order = seen[np.argsort(-scores[q, seen], kind="stable")]
        out[q, order[:k]] = True
    return out


@pytest.mark.parametrize("case", ["random", "ties", "zeros", "few", "signs"])
def test_the_threshold_keeps_exactly_the_top_k(case):
    rng = np.random.RandomState(5)
    q, s, k = 24, 96, 16
    scores = rng.randn(q, s).astype(np.float32)
    visible = np.arange(s)[None, :] <= rng.randint(0, s, q)[:, None]
    if case == "ties":  # a handful of values: the k-th best has many equals
        scores = rng.randint(0, 4, (q, s)).astype(np.float32)
    if case == "zeros":  # a ReLU's zeros, of both signs, around the k-th
        scores = np.where(rng.rand(q, s) < 0.8, 0.0, scores)
        scores = np.where(rng.rand(q, s) < 0.3, -0.0, scores).astype(
            np.float32)
    if case == "few":  # fewer keys than k, one key, none (a padding query)
        visible = np.arange(s)[None, :] < np.arange(q)[:, None] // 2
    if case == "signs":  # the order of the bits across zero, and infinities
        scores[:, ::7] *= 1e30
        scores[:, 3] = -np.inf
        scores[:, 5] = np.inf
    got = np.asarray(jax.jit(dsa.exact_topk_mask, static_argnums=2)(
        jnp.asarray(scores), jnp.asarray(visible), k))
    np.testing.assert_array_equal(got, _argsort_mask(scores, visible, k))
    assert (got.sum(1) == np.minimum(visible.sum(1), k)).all()


def test_a_chunk_and_a_decode_step_select_the_same_keys():
    """The same query against the same index keys through the chunk's path
    (scores in blocks, the threshold) and through a decode step's (pool and
    staged keys, `top_k`): the same positions, the tied ones too."""
    rng = np.random.RandomState(2)
    bs, nb, hi, di, k, width, rank, nh = 8, 16, 2, 16, 16, 40, 32, 4
    hist, win, step = 83, 4, 2  # the query sits at position hist + step
    n = hist + step + 1
    keys = rng.randn(n, di).astype(np.float32)
    keys[5:80] = keys[5]  # 75 equal keys of 86: ties at the threshold
    lat = rng.randn(n, width).astype(np.float32)
    table = rng.permutation(np.arange(1, 40))[:nb].astype(np.int32)

    def pool_of(rows, upto):
        pool = np.zeros((1, 40, bs, rows.shape[1]), np.float32)
        for p in range(upto):
            pool[0, table[p // bs], p % bs] = rows[p]
        return jnp.asarray(pool)

    q_idx = jnp.asarray(rng.randn(1, hi, di).astype(np.float32))
    w = jnp.asarray(rng.rand(1, hi).astype(np.float32))
    q_abs = jnp.asarray(rng.randn(1, nh, width).astype(np.float32))
    tables = jnp.asarray(table[None])
    # the decode step: positions < hist in the pool, the rest staged
    staged = np.zeros((1, win, di), np.float32)
    staged[0, :step + 1] = keys[hist:n]
    staged_lat = np.zeros((1, win, width), np.float32)
    staged_lat[0, :step + 1] = lat[hist:n]
    chosen, real = dsa.dsa_index_decode(
        q_idx, w, pool_of(keys, hist), tables, jnp.asarray([hist]),
        jnp.asarray(staged), jnp.int32(step), topk=k)
    at = np.asarray(chosen[0])[np.asarray(real[0])]
    at = np.where(at < nb * bs, at, hist + at - nb * bs)
    stepped = dsa.dsa_decode_attention(
        q_abs, pool_of(lat, hist), tables, jnp.asarray(staged_lat), chosen,
        real, scale=0.2, rank=rank)
    # the chunk: ONE row whose chunk is the query alone, every key in pages
    out, sel = dsa.dsa_prefill_attention(
        jnp.pad(q_abs, ((0, 15), (0, 0), (0, 0))), pool_of(lat, n), tables,
        jnp.asarray([n]), jnp.asarray([n - 1]), jnp.asarray([0]),
        index={"q": jnp.pad(q_idx, ((0, 15), (0, 0), (0, 0))),
               "w": jnp.pad(w, ((0, 15), (0, 0))), "pool": pool_of(keys, n)},
        selection=None, scale=0.2, rank=rank, topk=k)
    assert sorted(at.tolist()) == np.flatnonzero(
        np.asarray(sel[0])).tolist()
    assert len(at) == k and 5 in at and 79 not in at  # lower positions first
    np.testing.assert_allclose(stepped[0], out[0], rtol=1e-5, atol=1e-6)
    assert not np.asarray(sel[1:16]).any()  # padding queries keep nothing


# -- the share ----------------------------------------------------------------

def test_the_sixteen_shares_add_up_to_the_uncut_layer(cfg):
    """The routed parts that ALL sixteen shares give (one expert of 16 each,
    as sixteen shares of 16 in the deployment) + the shared expert counted
    ONCE = the uncut layer, through the served `_moe_mlp` and through the
    reference alike; the router scores all 16 whatever share is held."""
    one = dataclasses.replace(
        ek.cut(cfg, 2), num_experts=16, num_experts_held=0,
        first_expert_held=0)
    params = llama.init_params(one, jax.random.PRNGKey(2))
    moe = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, one.hidden_size))
    keys = reference.published_keys(one)
    with jax.default_matmul_precision("highest"):
        combine, _, _ = reference.route(keys, moe, x[0])
        whole = reference.experts_mlp(keys, moe, x[0], combine)
        shared = reference.swiglu(moe["shared"], x[0])
        ref_parts = sum(
            reference.experts_mlp(
                {**keys, "first_expert_held": e, "num_experts_held": 1,
                 "n_shared_experts": 0},
                {**moe, **{n: moe[n][e:e + 1] for n in ("gate", "up", "down")}},
                x[0], combine)
            for e in range(16))
    np.testing.assert_allclose(ref_parts + shared, whole, rtol=1e-5,
                               atol=1e-6)
    served_parts = 0
    for e in range(16):
        share = dataclasses.replace(one, num_experts_held=1,
                                    first_expert_held=e)
        mp = {k: (v[e:e + 1] if k in ("gate", "up", "down") else v)
              for k, v in moe.items() if k != "shared"}
        y, _ = llama._moe_mlp(share, mp, x)
        served_parts = served_parts + y[0]
    np.testing.assert_allclose(served_parts + shared, whole, rtol=1e-5,
                               atol=2e-6)
    y, _ = llama._moe_mlp(one, moe, x)
    np.testing.assert_allclose(y[0], whole, rtol=1e-5, atol=2e-6)


# -- memory --------------------------------------------------------------------

def test_param_and_cache_bytes_are_the_arrays(cfg):
    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    assert memory.param_bytes(cfg) == nbytes(params)
    # the query latent's leaves in W_q's place, the indexers' over 2 layers
    assert set(params["layers"]["attn"]) == {
        "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wk_b", "wv_b",
        "wo"}
    # (a projection split into heads lies (heads, head_dim, in))
    nh, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    assert {k: params["layers"]["attn"][k].shape[1:]
            for k in ("wq_b", "wk_b", "wv_b")} == {
        "wq_b": (nh, dn + dr, cfg.q_lora_rank),
        "wk_b": (nh, dn, cfg.kv_lora_rank),
        "wv_b": (nh, cfg.v_head_dim, cfg.kv_lora_rank)}
    assert {k: v.shape for k, v in params["layers"]["attn_index"].items()} == {
        "wq_b": (2, 2, 16, 48), "wk": (2, 64, 16), "k_norm": (2, 16),
        "k_norm_bias": (2, 16), "w_proj": (2, 64, 2)}
    assert params["layers"]["moe"]["gate"].shape == (4, 4, 64, 32)  # held
    econf = ek.engine_config(cfg, num_blocks=40)
    caches = llama.init_kv_cache(cfg, 40, 8)
    assert memory.kv_pool_bytes(
        cfg, econf.cache, econf.parallel, None) == nbytes(caches)
    assert [sorted(c) if isinstance(c, dict) else c.shape
            for c in caches] == [
        ["idx", "kv"], (1, 40, 8, 128), (1, 40, 8, 128), ["idx", "kv"],
        (1, 40, 8, 128)]
    assert caches[0]["idx"].shape == (1, 40, 8, 16)
    assert memory.index_block_bytes(cfg, 8) == 2 * 8 * 16 * 4
    # at published widths (the issue's recount): the cut 10.996 GB, a token
    # 7 x 1,280 B of latent + 2 x 256 B of index key; the whole model 743 B
    # parameters (described as ~750B)
    big = resolve_model_config("glm-5.2-ep16-7l")
    assert abs(memory.param_bytes(big) / 1e9 - 10.996) < 0.011
    assert memory.kv_block_bytes(big, 32) == 32 * 9472
    assert memory.index_block_bytes(big, 32) == 32 * 2 * 256
    assert abs(8192 * memory.kv_block_bytes(big, 32) / 2**30 - 2.31) < 0.01
    whole = resolve_model_config("glm-5.2")
    assert abs(memory.param_bytes(whole) / 2 / 1e9 - 743.4) < 1.0
    # Kanana (every layer latent, no indexer) counts what it counted
    kanana = resolve_model_config("kanana-2-30b-a3b-8l")
    assert memory.kv_block_bytes(kanana, 32) == 8 * 32 * 1280
    assert memory.index_block_bytes(kanana, 32) == 0
    assert abs(memory.param_bytes(kanana) / 1e9 - 10.139) < 0.001


# -- refusals ------------------------------------------------------------------

@pytest.mark.parametrize("edit,message", [
    (dict(cache=CacheConfig(block_size=8, num_blocks=64, num_host_blocks=8,
                            enable_prefix_caching=False)),
     "the host-RAM tier"),
    (dict(kv_peer_fetch=True), "the peer-engine tier"),
    (dict(cache=CacheConfig(block_size=8, num_blocks=64,
                            kv_cache_dtype="fp8")),
     "--kv-cache-dtype fp8 cannot be combined with attention over selected"),
    (dict(sched=dict(num_speculative_tokens=3)),
     "speculative decoding (--speculative-config ngram) cannot be combined "
     "with attention over selected keys"),
    (dict(parallel=ParallelConfig(tensor_parallel_size=2)),
     "parallelism is not implemented for multi-head latent attention"),
    (dict(lora=LoRAConfig(max_loras=1)), "LoRA adapters"),
    (dict(model=dict(quantization="int8")), "--quantization int8"),
    (dict(model=dict(checkpoint="a directory")),
     "a checkpoint cannot be combined with attention over selected keys"),
])
def test_what_is_not_built_is_refused_by_name(cfg, kit, edit, message,
                                              tmp_path):
    edit = dict(edit)
    over = dict(edit.pop("model", {}))
    if "checkpoint" in over:  # (a weights-only directory: byte tokenizer)
        over["checkpoint"] = str(tmp_path)
    model = dataclasses.replace(cfg, **over)
    with pytest.raises(ValueError) as err:
        kit.fresh_engine(ek.engine_config(model, **edit))
    assert message in str(err.value)
    assert model.model in str(err.value)


def test_a_row_major_chunk_is_refused(cfg):
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    kv = llama.init_kv_cache(cfg, 8, 8)
    toks = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="packed chunks only"):
        llama.forward(cfg, params, toks, toks, kv, jnp.ones((1, 4), jnp.int32),
                      jnp.zeros((8,), jnp.int32), jnp.asarray([8]))


# -- the key mapper --------------------------------------------------------------

_KINDS = ["full"] * 3 + ["full" if (i - 3) % 4 == 3 else "shared"
                         for i in range(3, 78)]
# zai-org/GLM-5.2 config.json, as the catalog has it
_PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "head_dim": 192, "hidden_act": "silu", "hidden_size": 6144,
    "index_head_dim": 128, "index_n_heads": 32,
    "index_share_for_mtp_iteration": True, "index_skip_topk_offset": 3,
    "index_topk": 2048, "index_topk_freq": 4, "index_topk_pattern": None,
    "indexer_rope_interleave": True, "indexer_types": _KINDS,
    "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 1048576,
    "mlp_layer_types": ["dense"] * 3 + ["sparse"] * 75,
    "model_type": "glm_moe_dsa", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 78, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 1, "q_lora_rank": 2048, "qk_head_dim": 256,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_interleave": True,
    "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 256, "vocab_size": 154880,
}
_COMPARED = (
    "hidden_size", "intermediate_size", "num_heads", "head_dim",
    "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "index_n_heads", "index_head_dim", "index_topk",
    "first_k_dense_replace", "num_experts_per_tok", "moe_intermediate_size",
    "n_shared_experts", "scoring_func", "routed_scaling_factor",
    "norm_topk_prob", "vocab_size", "rms_norm_eps", "rope_theta",
    "rope_interleave", "architecture", "num_layers", "layers")


def _config_dir(tmp_path, **override):
    (tmp_path / "config.json").write_text(
        json.dumps({**_PUBLISHED, **override}))
    return str(tmp_path)


def test_the_published_keys_are_the_catalogs_and_map_to_the_presets(tmp_path):
    # the kept layers' keys, as the benchmark's configuration file has them
    with open("benchmarks/chip/configs/glm-5.2-bf16-ep16-7l.json") as f:
        cut = json.load(f)
    for key, value in _PUBLISHED.items():
        assert cut[key + "_published" if key in cut["reduced"] else key] == (
            value), key
    assert cut["indexer_types"] == _KINDS[2:9]
    assert cut["mlp_layer_types"] == _PUBLISHED["mlp_layer_types"][2:9]
    # the whole model
    got, want = (resolve_model_config(_config_dir(tmp_path)),
                 resolve_model_config("glm-5.2"))
    for key in _COMPARED + ("num_experts",):
        assert getattr(got, key) == getattr(want, key), key
    # the cut (the file's n_routed_experts is the HELD count; the preset's
    # router keeps the published width and holds 16)
    got = resolve_model_config(_config_dir(
        tmp_path, **{k: cut[k] for k in cut["reduced"]}))
    want = resolve_model_config("glm-5.2-ep16-7l")
    for key in _COMPARED:
        assert getattr(got, key) == getattr(want, key), key
    assert (got.num_experts, want.num_experts, want.num_experts_held) == (
        16, 256, 16)
    assert got.max_model_len == want.max_model_len == 32768


@pytest.mark.parametrize("override,message", [
    ({"index_topk_pattern": [2048, 1024]}, "index_topk_pattern"),
    ({"rope_parameters": {"rope_theta": 8e6, "rope_type": "yarn"}},
     "rope_scaling"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"index_kpool_stride": 4}, "index_kpool keys"),
    ({"indexer_types": _KINDS[:70]}, "indexer_types that is not one entry"),
    ({"indexer_types": ["shared"] + _KINDS[1:]},
     "indexer_types that does not start with 'full'"),
    ({"q_lora_rank": None}, "q_lora_rank null"),
    ({"n_group": 8}, "n_group != 1"),
    ({"scoring_func": "softmax"}, "scoring_func other than sigmoid"),
])
def test_the_mapper_refuses_by_name(tmp_path, override, message):
    with pytest.raises(ValueError) as err:
        resolve_model_config(_config_dir(tmp_path, **override))
    assert message in str(err.value) and "not implemented" in str(err.value)


def test_a_deepseek_v3_query_latent_is_pointed_at_what_serves_one(tmp_path):
    hf = {"architectures": ["DeepseekV3ForCausalLM"], "hidden_size": 64,
          "intermediate_size": 128, "num_hidden_layers": 3,
          "num_attention_heads": 4, "vocab_size": 512, "kv_lora_rank": 32,
          "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
          "first_k_dense_replace": 1, "n_routed_experts": 8,
          "num_experts_per_tok": 2, "moe_intermediate_size": 32,
          "n_shared_experts": 1, "q_lora_rank": 48}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    with pytest.raises(ValueError) as err:
        resolve_model_config(str(tmp_path))
    assert "q_lora_rank" in str(err.value)
    assert "_glm_moe_dsa_keys" in str(err.value)


# -- names -----------------------------------------------------------------------

def test_timing_and_metrics_names_are_the_contract(cfg, kit):
    from vllm_production_stack_tpu.engine.metrics import EngineMetrics

    engine, before = kit.engine(ek.engine_config(cfg))
    prompt = ek.prompts(cfg, (70,))[0]
    rid = engine.add_request(prompt_token_ids=prompt, sampling=ek.greedy(200))
    for _ in range(8):
        engine.step()
    timing = ek.since(engine, before)
    assert set(mc.DEBUG_TIMING_DSA_COUNTS) <= set(timing)
    # 2 layers own an indexer, 3 borrow, in a chunk and in a window alike
    assert 3 * timing["dsa_full_layers_n"] == 2 * timing["dsa_shared_layers_n"]
    assert timing["dsa_full_layers_n"] + timing["dsa_shared_layers_n"] == (
        timing["mla_layers_n"])
    # three chunks (32, 32, 6) then windows of 4 steps of one row
    steps = timing["dsa_decode_rows_n"]
    assert steps >= 4 and steps % 4 == 0
    n = 70 + steps
    visible = n * (n + 1) // 2
    assert timing["dsa_index_pairs_n"] == 2 * visible
    assert timing["dsa_visible_keys_n"] == 5 * visible
    assert timing["dsa_selected_keys_n"] == 5 * sum(
        min(p + 1, 16) for p in range(n))
    stats = engine.stats()
    pages = len(engine.scheduler.running[0].block_table)
    assert stats.index_bytes == pages * memory.index_block_bytes(cfg, 8)
    assert stats.latent_bytes + stats.index_bytes == (
        pages * memory.kv_block_bytes(cfg, 8))
    text = EngineMetrics("tiny-glm-dsa").render(stats).decode()
    for name in (mc.HBM_INDEX_BYTES, mc.HBM_LATENT_BYTES):
        assert name in mc.ALL_GAUGES and f"\n{name}{{" in text
    ek.finish(engine, rid)
    # a model without such layers serves none of the counts, and 0 B of keys
    plain, _ = kit.engine(ek.engine_config(resolve_model_config(
        "tiny-kanana")))
    ek.generate(plain, [[1, 2, 3]], 3)
    assert not set(mc.DEBUG_TIMING_DSA_COUNTS) & set(plain.flightrec.timing())
    assert plain.stats().index_bytes == 0 and plain.stats().latent_bytes == 0
    for scope in ("dsa_index_q", "dsa_index_k", "dsa_index_score",
                  "dsa_select", "dsa_gather", "dsa_attn"):
        assert scope in mc.DEVICE_SCOPES
