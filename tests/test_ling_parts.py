"""The parts of Ling-3.0-flash's language model (ISSUE 45) at tiny size on the
CPU, apart from the served engine against its reference (tests/test_ling.py):
group-limited routing against a NumPy loop, the KDA variants (the bounded
gate, full-rank projections, beta in (0, 1)), the latent layer's head gate,
the four shares of the experts against the uncut layer, the byte counts
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
    CacheConfig, LayerSpec, LoRAConfig, ModelConfig, ParallelConfig,
)
from vllm_production_stack_tpu.models import llama
from vllm_production_stack_tpu.models.loader import LingCheckpointView
from vllm_production_stack_tpu.models.registry import resolve_model_config
from vllm_production_stack_tpu.testing import reference_ling as reference

CHUNK = dict(chunk=64)


@pytest.fixture(scope="module")
def cfg():
    return resolve_model_config("tiny-ling-flash")


# -- the layer kinds -----------------------------------------------------------

def test_latent_is_something_a_layer_says(cfg):
    """`LayerSpec.mixer` has a third value; `ModelConfig.mla` is derived
    from the list (every layer latent: Kanana), not a second switch."""
    kinds = [s.attn_kind for s in cfg.layer_specs]
    assert kinds == 5 * ["linear"] + ["latent", "linear"]
    assert [s.experts for s in cfg.layer_specs] == [False] + 6 * [True]
    latent = cfg.layer_specs[5]
    assert latent.latent and latent.holds_pages and not latent.holds_state
    assert latent.rope is not None and cfg.layer_specs[0].rope is None
    assert (cfg.latent_layers, cfg.page_layers) == ((5,), (5,))
    assert cfg.linear_layers == (0, 1, 2, 3, 4, 6)
    assert not cfg.mla and cfg.tree_by_kind and cfg.full_layers == ()
    kanana = resolve_model_config("tiny-kanana")
    assert kanana.mla and not kanana.tree_by_kind
    assert all(s.latent for s in kanana.layer_specs)
    assert kanana.latent_layers == (0, 1, 2) and not kanana.linear_layers
    # latent pages beside K/V pages in one model have no layout
    with pytest.raises(ValueError, match="latent layers beside K/V-page"):
        dataclasses.replace(cfg, layers=(
            LayerSpec(num_heads=4), *cfg.layers[1:])).layer_specs
    tree = llama.init_params(cfg, jax.random.PRNGKey(0))["layers"]
    assert set(tree) == {"attn_linear", "attn_latent", "mlp", "moe",
                         "input_norm", "post_attn_norm"}
    assert tree["attn_latent"]["wkv_a"].shape == (1, 64, 32 + 8)
    assert tree["attn_latent"]["wg"].shape == (1, 64, 4)
    assert tree["attn_linear"]["wf"].shape == (6, 64, 64)  # full rank
    assert "wf_a" not in tree["attn_linear"]
    assert tree["mlp"]["gate"].shape[0] == 1
    assert tree["moe"]["gate"].shape[:2] == (6, 8)  # the held experts
    assert tree["moe"]["router"].shape == (6, 64, 16)  # published width


# -- group-limited routing -------------------------------------------------------

def _route_by_hand(scores, bias, n_group, topk_group, k, scale):
    """The routing as a NumPy loop over tokens: groups of consecutive
    experts, a group's score the sum of its two best choice scores, the
    best groups kept (ties to the lower index), the top-k among their
    experts (ties to the lower index), weights the scores renormalised."""
    n, e = scores.shape
    per = e // n_group
    experts, weights = [], []
    for t in range(n):
        choice = scores[t] + bias
        group_score = [np.sort(choice[g * per:(g + 1) * per])[-2:].sum()
                       for g in range(n_group)]
        kept = sorted(range(n_group),
                      key=lambda g: (-group_score[g], g))[:topk_group]
        allowed = [x for g in kept for x in range(g * per, (g + 1) * per)]
        chosen = sorted(allowed, key=lambda x: (-choice[x], x))[:k]
        w = scores[t][chosen]
        experts.append(chosen)
        weights.append(w / w.sum() * scale)
    return np.array(experts), np.array(weights)


def test_group_limited_routing_against_a_numpy_loop(cfg):
    rng = np.random.RandomState(0)
    logits = rng.randn(200, 16).astype(np.float32)
    bias = (0.02 * rng.randn(16)).astype(np.float32)
    scores = np.asarray(jax.nn.sigmoid(jnp.asarray(logits)))
    stats: dict = {}
    topv, topi = llama._sigmoid_top_k(
        cfg, jnp.asarray(logits), jnp.asarray(bias), stats)
    want_i, want_v = _route_by_hand(scores, bias, 4, 2, 4, 2.5)
    np.testing.assert_array_equal(np.asarray(topi), want_i)
    np.testing.assert_allclose(np.asarray(topv), want_v, rtol=1e-6)
    # every chosen expert lies in one of two groups a token
    assert all(len({x // 4 for x in row}) <= 2 for row in want_i)
    # the counter: kept groups that hold a held expert (groups 1 and 2)
    kept = [{x // 4 for x in row} for row in want_i]
    assert int(stats["groups_kept_held"]) >= sum(
        len(g & {1, 2}) for g in kept)
    # the reference's own routing agrees
    keys = reference.check_config(reference.published_keys(cfg))
    limited = reference.group_limited(keys, jnp.asarray(scores + bias))
    _, ref_i = jax.lax.top_k(limited, 4)
    np.testing.assert_array_equal(np.asarray(ref_i), want_i)


def test_routing_ties_go_to_the_lower_index(cfg):
    """Equal logits everywhere: groups 0 and 1 are kept (ties to the lower
    group) and experts 0, 1, 2, 3 chosen (ties to the lower expert)."""
    tie = jnp.zeros((3, 16))
    _, topi = llama._sigmoid_top_k(cfg, tie, jnp.zeros((16,)))
    np.testing.assert_array_equal(np.asarray(topi), [[0, 1, 2, 3]] * 3)
    # two groups tie for the second place: the lower of them is kept
    logits = jnp.zeros((1, 16)).at[0, 12:14].set(3.0).at[0, 4].set(
        1.0).at[0, 8].set(1.0)
    _, topi = llama._sigmoid_top_k(cfg, logits, jnp.zeros((16,)))
    assert {int(x) // 4 for x in topi[0]} == {3, 1}


def test_a_bias_flips_a_group_and_never_a_weight(cfg):
    """Expert 12 has the token's best score, alone in its group: the group
    limit keeps groups 0 and 2 (two good experts each) and leaves it out,
    where the plain top-k takes it. A bias on expert 13 lifts group 3 over
    group 2: the choice moves, and the weights stay the SCORES."""
    logits = jnp.full((1, 16), -2.0)
    logits = logits.at[0, jnp.array([0, 1])].set(1.0)
    logits = logits.at[0, jnp.array([8, 9])].set(0.5).at[0, 12].set(1.2)
    zero = jnp.zeros((16,))
    _, plain = llama._sigmoid_top_k(cfg, logits, zero)
    assert sorted(map(int, plain[0])) == [0, 1, 8, 9]
    one = dataclasses.replace(cfg, n_group=1, topk_group=1)
    _, free = llama._sigmoid_top_k(one, logits, zero)
    assert sorted(map(int, free[0])) == [0, 1, 8, 12]
    bias = zero.at[13].set(0.5)
    topv, flipped = llama._sigmoid_top_k(cfg, logits, bias)
    assert sorted(map(int, flipped[0])) == [0, 1, 12, 13]
    scores = np.asarray(jax.nn.sigmoid(logits))[0]
    chosen = scores[np.asarray(flipped[0])]
    np.testing.assert_allclose(np.asarray(topv[0]),
                               chosen / chosen.sum() * 2.5, rtol=1e-6)


# -- the KDA variants and the head gate -----------------------------------------

class _Capture(llama._Recurrence):
    """A recurrence that keeps what `_kda_attention` hands the scan (q, k,
    v by the plain `qkv` over a convolution that passes x through)."""

    def __init__(self):
        super().__init__({}, None, None)

    def conv(self, x, taps):
        return x.astype(jnp.float32)

    def scan(self, q, k, v, g, beta):
        self.fed = (q, k, v, g, beta)
        return jnp.zeros(q.shape, jnp.float32)


def test_the_bounded_gate_full_rank_and_beta_below_one(cfg):
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    ap = jax.tree.map(lambda a: a[0], params["layers"]["attn_linear"])
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(2), (40, 1, 64))
    recur = _Capture()
    llama._kda_attention(cfg, ap, jnp.ones((64,)), x, recur)
    g, beta = (np.asarray(a) for a in recur.fed[3:])
    assert g.shape == (40, 4, 16) and beta.shape == (40, 4)
    # (the open interval (-5, 0), closed where float32's sigmoid saturates)
    assert (g >= -5.0).all() and (g <= 0.0).all()
    assert ((g < -0.05) & (g > -4.95)).mean() > 0.2  # and not only there
    assert (beta > 0.0).all() and (beta < 1.0).all()
    u = np.asarray(llama.rms_norm(x[:, 0], jnp.ones((64,)), cfg.rms_norm_eps))
    a = (u @ np.asarray(ap["wf"]) + np.asarray(ap["dt_bias"])).reshape(
        40, 4, 16)
    rate = np.exp(np.asarray(ap["A_log"]))[None, :, None]
    np.testing.assert_allclose(g, -5.0 / (1.0 + np.exp(-rate * a)),
                               rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(
        beta, 1.0 / (1.0 + np.exp(-(u @ np.asarray(ap["wb"])))), rtol=2e-5)
    # the same leaves under Solar-Open2's form: unbounded, beta in (0, 2)
    other = dataclasses.replace(
        cfg, linear_gate_form="softplus", linear_beta_scale=2.0)
    llama._kda_attention(other, ap, jnp.ones((64,)), x, recur)
    g2, beta2 = (np.asarray(a) for a in recur.fed[3:])
    np.testing.assert_allclose(beta2, 2.0 * beta, rtol=1e-6)
    np.testing.assert_allclose(
        g2, -rate * np.log1p(np.exp(a)), rtol=2e-5, atol=1e-6)


def test_the_head_gate_multiplies_each_head(cfg):
    """`_mla_attention` without the leaf is `_init_params_mla`'s layer
    (Kanana's); a gate of logit 0 halves every head; and with W_o's rows of
    every head but head 0 zeroed, head 0's gate alone decides the output,
    whatever the other heads' gates are."""
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    ap = jax.tree.map(lambda a: a[0], params["layers"]["attn_latent"])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 6, 64))
    pos = jnp.arange(6)[None]

    def attend(q_abs, latent, _v):  # every query sees every latent
        s = jnp.einsum("bthw,bsw->bths", q_abs, latent)
        return jnp.einsum("bths,bsr->bthr", jax.nn.softmax(s, -1),
                          latent[..., :cfg.kv_lora_rank])

    def run(ap):
        return llama._mla_attention(
            cfg, ap, jnp.ones((64,)), x, pos, attend)

    def without_gate(ap):
        return {k: v for k, v in ap.items() if k != "wg"}

    ungated = run(without_gate(ap))
    half = run({**ap, "wg": jnp.zeros_like(ap["wg"])})
    np.testing.assert_allclose(half, 0.5 * ungated, rtol=1e-5, atol=1e-6)
    assert not np.allclose(np.asarray(run(ap)), np.asarray(ungated))
    head0 = {**ap, "wo": ap["wo"].at[cfg.v_head_dim:].set(0.0),
             "wg": ap["wg"].at[:, 0].set(0.0)}  # head 0: logit 0
    np.testing.assert_allclose(run(head0), 0.5 * run(without_gate(head0)),
                               rtol=1e-5, atol=1e-6)


# -- the share ---------------------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer(cfg):
    """The routed parts that ALL four shares give (one group of 4 experts
    each, as four shares of 128 in the deployment) + the shared expert
    counted ONCE = the uncut reference's layer, through the served
    `_moe_mlp` and through the reference alike; the groups are over all 16
    router outputs whatever share is held."""
    one = ek.cut(cfg, 2)  # the dense layer and one expert layer
    uncut = dataclasses.replace(one, num_experts_held=0, first_expert_held=0)
    params = llama.init_params(uncut, jax.random.PRNGKey(2))
    moe = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, uncut.hidden_size))
    view = LingCheckpointView(uncut, params)
    keys = reference.check_config(reference.published_keys(uncut))

    def run(fn, *a):
        with jax.default_matmul_precision("highest"):
            return fn(*a)

    def get(name):
        return reference.f32(view[name])

    p = "model.layers.1."
    whole, routing = reference.expert_layer(keys, get, p, x[0], run)
    assert all(len({int(e) // 4 for e in row}) <= 2
               for row in np.asarray(routing["experts"]))
    ref_parts = sum(
        reference.expert_layer(keys, get, p, x[0], run, held=(4 * s, 4),
                               shared=False)[0] for s in range(4))
    shared = run(reference.swiglu, get, p + "mlp.shared_expert.", x[0])
    np.testing.assert_allclose(ref_parts + shared, whole, rtol=1e-5,
                               atol=1e-6)
    served_parts, kept = 0, 0
    for s in range(4):
        share = dataclasses.replace(
            uncut, num_experts_held=4, first_expert_held=4 * s)
        mp = {k: (v[4 * s:4 * s + 4] if k in ("gate", "up", "down") else v)
              for k, v in moe.items() if k != "shared"}
        y, r = llama._moe_mlp(share, mp, x)
        served_parts = served_parts + y[0]
        kept += int(r["groups_kept_held"])
    np.testing.assert_allclose(served_parts + shared, whole, rtol=1e-5,
                               atol=2e-6)
    # each token keeps 2 groups, each held by exactly one of the shares
    assert kept == 24 * 2
    y, r = llama._moe_mlp(uncut, moe, x)
    np.testing.assert_allclose(y[0], whole, rtol=1e-5, atol=2e-6)
    assert int(r["groups_kept_held"]) == 24 * 2  # no share: every group


# -- memory --------------------------------------------------------------------------

def test_param_and_cache_bytes_are_the_arrays(cfg):
    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    assert memory.param_bytes(cfg) == nbytes(
        llama.init_params(cfg, jax.random.PRNGKey(0)))
    econf = ek.engine_config(cfg, num_blocks=40, **CHUNK)
    slots = memory.resolve_state_slots(econf)
    assert slots == 5
    caches = llama.init_kv_cache(cfg, 40, 8, state_slots=slots)
    assert memory.kv_pool_bytes(
        cfg, econf.cache, econf.parallel, None, state_slots=slots) == nbytes(
            caches)
    states = [c for c in caches if isinstance(c, dict)]
    pages = [c for c in caches if not isinstance(c, dict)]
    assert len(states) == 6 and memory.state_pool_bytes(cfg, slots) == nbytes(
        states)
    # ONE latent layer: a page is (8 tokens, 128 stored values), one plane
    assert [c.shape for c in pages] == [(1, 40, 8, 128)]
    assert 40 * memory.kv_block_bytes(cfg, 8) == nbytes(pages)
    assert memory.resolve_window_blocks(econf) is None
    # the cut at published widths (the issue's recount): 5.23 B parameters,
    # 2 MiB of state + 72 KiB of tail a slot and layer, 1,280 B a token
    big = resolve_model_config("ling-3.0-flash-ep4-7l")
    assert abs(memory.param_bytes(big) / 2 - 5.232e9) < 0.001e9
    assert memory.state_slot_bytes(big) == 6 * (2 * 2**20 + 72 * 2**10)
    assert abs(memory.state_pool_bytes(big, 17) / 2**30 - 0.412) < 0.001
    assert memory.kv_block_bytes(big, 32) == 32 * 1280
    assert abs(4096 * memory.kv_block_bytes(big, 32) / 2**30 - 0.156) < 0.001
    # Kanana (every layer latent) counts what it counted
    kanana = resolve_model_config("kanana-2-30b-a3b-8l")
    assert memory.kv_block_bytes(kanana, 32) == 8 * 32 * 1280
    # the pool is sized from what the state pools leave
    cache = CacheConfig(block_size=32, num_blocks=None,
                        enable_prefix_caching=False)
    free, held = (memory.derive_num_blocks(
        big, cache, ParallelConfig(), hbm_bytes=32 * 2**30, state_slots=n)
        for n in (0, 17))
    assert abs((free - held) - memory.state_pool_bytes(big, 17)
               / memory.kv_block_bytes(big, 32)) <= 1


# -- refusals -------------------------------------------------------------------------

@pytest.mark.parametrize("edit,message", [
    (dict(cache=CacheConfig(block_size=8, num_blocks=64, num_host_blocks=8,
                            enable_prefix_caching=False)),
     "the host-RAM tier"),
    (dict(kv_peer_fetch=True), "the peer-engine tier"),
    (dict(sched=dict(num_speculative_tokens=3)),
     "speculative decoding (--speculative-config ngram)"),
    (dict(parallel=ParallelConfig(tensor_parallel_size=2)),
     "parallelism is not implemented for multi-head latent attention"),
    (dict(lora=LoRAConfig(max_loras=1)), "LoRA adapters"),
    (dict(model=dict(quantization="int8")), "--quantization int8"),
    (dict(model=dict(checkpoint="a directory")), "a checkpoint"),
])
def test_what_is_not_built_is_refused_by_name(cfg, kit, edit, message,
                                              tmp_path):
    """Both refusals apply: what the latent pool has no layout for
    (engine.py:_refuse_kv_tiers_for_latent_pool, the runner's latent
    refusals) and what the state slots have none for."""
    edit = dict(edit)
    over = dict(edit.pop("model", {}))
    if "checkpoint" in over:  # (a weights-only directory: byte tokenizer)
        over["checkpoint"] = str(tmp_path)
    model = dataclasses.replace(cfg, **over)
    with pytest.raises(ValueError) as err:
        kit.fresh_engine(ek.engine_config(model, **CHUNK, **edit))
    assert message in str(err.value)
    assert model.model in str(err.value)


def test_image_tokens_in_a_prompt_and_other_paths_are_refused(cfg, kit):
    model = dataclasses.replace(cfg, refused_token_ids=(509, 510))
    engine = kit.fresh_engine(ek.engine_config(model, **CHUNK))
    with pytest.raises(ValueError, match="image or video placeholder"):
        engine.add_request(prompt_token_ids=[5, 509, 7],
                           sampling=ek.greedy(2))
    assert not engine.has_unfinished()
    assert ek.generate(engine, [[5, 6, 7]], 2)[0]  # text is served
    big = resolve_model_config("ling-3.0-flash-ep4-7l")
    assert big.refused_token_ids == (157157, 156909, 157158, 157160)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        llama.embed_encode(cfg, {}, jnp.zeros((1, 4), jnp.int32),
                           jnp.array([4]))
    with pytest.raises(NotImplementedError, match="latent attention"):
        llama._refuse_mla(cfg, "ring attention")
    with pytest.raises(ValueError, match="not implemented for latent"):
        llama.init_params(dataclasses.replace(
            cfg, attn_output_gate="element"), jax.random.PRNGKey(0))


# -- the key mapper -----------------------------------------------------------------

_PUBLISHED = {
    "image_patch_token": 157157, "video_patch_token": 156909,
    "image_start_token": 157158, "video_start_token": 157160,
    "num_hidden_layers": 42, "hidden_size": 2560, "intermediate_size": 6144,
    "first_k_dense_replace": 2, "max_position_embeddings": 131072,
    "moe_intermediate_size": 768, "num_experts_per_tok": 8,
    "num_attention_heads": 32, "q_lora_rank": None, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_experts": 512, "num_key_value_heads": 32, "rope_theta": 6000000,
    "rms_norm_eps": 1e-06, "head_dim": 128, "vocab_size": 157184,
    "partial_rotary_factor": 0.5, "moe_router_enable_expert_bias": True,
    "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4,
    "use_qk_norm": True, "score_function": "sigmoid",
    "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
    "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
    "linear_silu": True, "rotary_dim": 64, "use_mla_nope": False,
    "short_conv_kernel_size": 4, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
    "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True,
    "expert_swiglu_limit_list": 35 * [0] + 7 * [4],
    "share_expert_swiglu_limit_list": 34 * [0] + 6 * [5] + 2 * [7]}


def _config_dir(tmp_path, **override):
    hf = {**_PUBLISHED, **override}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    return str(tmp_path)


def test_the_published_keys_are_the_catalogs_and_map_to_the_preset(tmp_path):
    # the kept layers' keys, as the benchmark's configuration file has them
    with open("benchmarks/chip/configs/ling-hybrid-bf16-ep4-7l.json") as f:
        cut = json.load(f)
    for key, value in _PUBLISHED.items():
        assert cut[key + "_published" if key in cut["reduced"] else key] == (
            value), key
    got = resolve_model_config(_config_dir(
        tmp_path, **{k: cut[k] for k in cut["reduced"]}))
    want = resolve_model_config("ling-3.0-flash-ep4-7l")
    assert got.layers == want.layers
    for key in ("hidden_size", "intermediate_size", "num_heads", "head_dim",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "first_k_dense_replace",
                "num_experts_per_tok", "moe_intermediate_size",
                "n_shared_experts", "scoring_func", "moe_selection_bias",
                "routed_scaling_factor", "norm_topk_prob", "n_group",
                "topk_group", "attn_output_gate", "linear_num_heads",
                "linear_head_dim", "linear_gate_rank", "linear_gate_form",
                "linear_gate_lower_bound", "linear_beta_scale", "vocab_size",
                "rms_norm_eps", "rope_theta", "refused_token_ids",
                "architecture"):
        assert getattr(got, key) == getattr(want, key), key
    # (the file's num_experts is the HELD count; the preset's router keeps
    # the published width and holds 128)
    assert (got.num_experts, want.num_experts, want.num_experts_held) == (
        128, 512, 128)
    # the whole model is refused for its clamp in layers 34..41
    with pytest.raises(ValueError, match="swiglu_limit_list"):
        resolve_model_config(_config_dir(tmp_path))
    # ... and served with 34 layers of it
    whole = resolve_model_config(_config_dir(tmp_path, num_hidden_layers=34))
    assert len(whole.latent_layers) == 5 and whole.first_k_dense_replace == 2


@pytest.mark.parametrize("override,message", [
    ({"use_nGPT": True}, "use_nGPT"),
    ({"value_norm": True}, "value_norm"),
    ({"up_proj_norm": True}, "up_proj_norm"),
    ({"use_kda_lora": True}, "use_kda_lora"),
    ({"mtp_use_kda": True}, "mtp_use_kda"),
    ({"scale_router_input": True}, "scale_router_input"),
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    ({"kda_safe_gate": False}, "kda_safe_gate false"),
    ({"n_group": 7}, "n_group that does not divide num_experts"),
    ({"expert_swiglu_limit_list": [0, 4] + 5 * [0]},
     "a non-zero expert_swiglu_limit_list entry in a kept layer"),
    ({"share_expert_swiglu_limit_list": 6 * [0] + [5]},
     "a non-zero share_expert_swiglu_limit_list entry in a kept layer"),
    ({"gated_attention_proj_granularity_type": "element_wise"},
     "gated_attention_proj_granularity_type other than head_wise"),
])
def test_the_mapper_refuses_by_name(tmp_path, override, message):
    kept = {"num_hidden_layers": 7, "expert_swiglu_limit_list": 7 * [0],
            "share_expert_swiglu_limit_list": 7 * [0]}
    with pytest.raises(ValueError) as err:
        resolve_model_config(_config_dir(tmp_path, **{**kept, **override}))
    assert message in str(err.value) and "not implemented" in str(err.value)


def test_deepseek_v3_groups_are_no_longer_refused(tmp_path):
    hf = {"architectures": ["DeepseekV3ForCausalLM"], "hidden_size": 64,
          "intermediate_size": 128, "num_hidden_layers": 3,
          "num_attention_heads": 4, "vocab_size": 512, "kv_lora_rank": 32,
          "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
          "first_k_dense_replace": 1, "n_routed_experts": 8,
          "num_experts_per_tok": 2, "moe_intermediate_size": 32,
          "n_shared_experts": 1, "n_group": 4, "topk_group": 2}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    got = resolve_model_config(str(tmp_path))
    assert (got.n_group, got.topk_group, got.mla) == (4, 2, True)
    (tmp_path / "config.json").write_text(json.dumps({**hf, "n_group": 3}))
    with pytest.raises(ValueError, match="n_group that does not divide"):
        resolve_model_config(str(tmp_path))


# -- names ----------------------------------------------------------------------------

def test_timing_and_metrics_names_are_the_contract(cfg, kit):
    from vllm_production_stack_tpu.engine.metrics import EngineMetrics

    engine, before = kit.engine(ek.engine_config(cfg, **CHUNK))
    rid = engine.add_request(prompt_token_ids=ek.prompts(cfg, (70,))[0],
                             sampling=ek.greedy(200))
    for _ in range(6):
        engine.step()
    timing = ek.since(engine, before)
    for group in (mc.DEBUG_TIMING_STATE_COUNTS, mc.DEBUG_TIMING_LATENT_COUNTS,
                  mc.DEBUG_TIMING_GROUP_COUNTS):
        assert set(group) <= set(timing), group
    # two prefill chunks (one pass over the layers each) and decode windows
    # of 4 steps: 6 KDA layers and 1 latent layer a pass
    assert timing["kda_layers_n"] == 6 * timing["mla_layers_n"]
    assert timing["mla_layers_n"] >= 2 + 4
    # 2 kept groups a token and expert layer; groups 1 and 2 of 4 are held:
    # about half of the kept groups hold a held expert
    assert 0 < timing["moe_group_kept_n"] < timing["moe_group_slots_n"]
    assert timing["moe_group_slots_n"] == 2 * (
        timing["moe_prefill_pairs_n"] + timing["moe_decode_pairs_n"]) // 4
    stats = engine.stats()
    pages = len(engine.scheduler.running[0].block_table)
    assert stats.latent_bytes == pages * memory.kv_block_bytes(cfg, 8)
    assert stats.state_bytes == 2 * memory.state_slot_bytes(cfg)
    assert stats.state_share == pytest.approx(
        stats.state_bytes / (stats.state_bytes + stats.latent_bytes))
    text = EngineMetrics("tiny-ling-flash").render(stats).decode()
    for name in (mc.HBM_STATE_BYTES, mc.HBM_LATENT_BYTES):
        assert name in mc.ALL_GAUGES and f"\n{name}{{" in text
    ek.finish(engine, rid)
    # a model without such layers serves none of the counts
    plain, _ = kit.engine(ek.engine_config(ModelConfig.tiny()))
    ek.generate(plain, [[1, 2, 3]], 3)
    assert not (set(mc.DEBUG_TIMING_LATENT_COUNTS)
                | set(mc.DEBUG_TIMING_GROUP_COUNTS)) & set(
        plain.flightrec.timing())
    assert plain.stats().latent_bytes == 0
