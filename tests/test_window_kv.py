"""The two KV page groups of a model with window layers (ISSUE 33) at tiny
size on the CPU — give-back, preemption and resume, a full group, the
metrics and counters — the byte counts of both groups, the published sizes
of the Laguna-S-2.1 cut, the refusals by name and the config's key mapper.
(tests/test_laguna.py holds the model, the kernels and the reference.)"""

import dataclasses
import json
import math

import jax
import pytest

import engine_kit as ek
from vllm_production_stack_tpu.engine.config import (
    CacheConfig, EngineConfig, LoRAConfig, ModelConfig, ParallelConfig,
    SchedulerConfig,
)
from vllm_production_stack_tpu.engine.kv_cache import WindowPageGroup
from vllm_production_stack_tpu.engine.memory import (
    derive_num_blocks, kv_block_bytes, param_bytes, resolve_window_blocks,
)
from vllm_production_stack_tpu.engine.request import SamplingParams
from vllm_production_stack_tpu.models import llama
from vllm_production_stack_tpu.models.registry import (
    PRESETS, _from_hf_config, resolve_model_config,
)

@pytest.fixture(scope="module")
def cfg():
    return resolve_model_config("tiny-laguna")


# -- the two page groups -----------------------------------------------------

def test_window_group_gives_pages_back_and_never_reads_them(cfg):
    """Unit: pages wholly behind the window of a row's resolved position go
    back, the table keeps its indexing with 0 in their place, and no
    position a later query may see lies on a page given back."""
    group = WindowPageGroup(num_blocks=20, block_size=8, window=16)
    table = [group.allocate() for _ in range(10)]  # 80 tokens
    first = group.give_back(table, 0, num_computed_tokens=15)
    assert first == 0 and 0 not in table  # position 15 still sees 0..15
    first = group.give_back(table, first, num_computed_tokens=40)
    # the query at 40 sees 25..40: block 3 (24..31) is the first needed
    assert first == 3 and table[:3] == [0, 0, 0] and all(table[3:])
    assert group.given_back_total == 3 and group.num_free == 19 - 7
    for computed in range(40, 80):
        first = group.give_back(table, first, computed)
        lowest_seen = max(0, computed - 16 + 1)
        assert table[lowest_seen // 8] != 0
    group.release(table, first)
    assert group.num_free == group.num_usable and table == []


def _drain(engine, ids):
    done = {i: [] for i in ids}
    while engine.has_unfinished():
        with engine.step_lock:
            for out in engine.step():
                done[out.request_id].extend(out.new_token_ids)
    return [done[i] for i in ids]


def test_a_full_group_preempts_and_the_rows_resume(cfg, kit):
    """With a window group too small for the rows in flight, the window
    group preempts (the full group has room), the preempted rows resume
    from nothing, and every row's greedy tokens equal those of an engine
    with room for all: nothing read a page it had given back or lost."""
    config = ek.engine_config(ek.cut(cfg, 5))
    prompts = [list(range(3 + i, 60 + 5 * i)) for i in range(4)]

    def run(engine):
        ids = [engine.add_request(prompt_token_ids=p, sampling=ek.greedy(20))
               for p in prompts]
        return _drain(engine, ids)

    roomy, _ = kit.engine(config)
    preempted = roomy.scheduler.total_preemptions
    want = run(roomy)
    # the engine sizes the group for every seat's worst case, so it never
    # fills: hand an engine of its own an allocator of fewer pages than the
    # arrays hold
    tight = kit.fresh_engine(config)
    group = tight.scheduler.pool.window
    tight.scheduler.pool.window = WindowPageGroup(
        10, group.block_size, group.window)
    got = run(tight)
    assert roomy.scheduler.total_preemptions == preempted
    assert tight.scheduler.total_preemptions > 0
    assert tight.scheduler.pool.num_free > 64  # the full group never filled
    assert got == want
    for engine in (roomy, tight):
        pool = engine.scheduler.pool
        assert pool.window.num_free == pool.window.num_usable
        assert pool.num_free == pool.num_usable
    stats = tight.stats()
    assert stats.kv_window_pages_given_back > 0
    assert set(stats.kv_group_usage) == {"full", "window"}


def test_kv_usage_is_the_fuller_groups_share_and_metrics_carry_both(cfg, kit):
    from vllm_production_stack_tpu import metrics_contract as mc
    from vllm_production_stack_tpu.engine.metrics import EngineMetrics

    engine, before = kit.engine(ek.engine_config(ek.cut(cfg, 5)))
    rid = engine.add_request(
        prompt_token_ids=list(range(1, 100)), sampling=ek.greedy(40))
    for _ in range(5):
        with engine.step_lock:
            engine.step()
    pool = engine.scheduler.pool
    stats = engine.stats()
    assert stats.kv_usage_perc == max(
        pool.usage_perc, pool.window.usage_perc) > 0
    # the row holds 13 full pages; its window pages are fewer
    assert 0 < stats.kv_window_share < 1
    text = EngineMetrics("tiny-laguna").render(stats).decode()
    assert text.count(mc.HBM_KV_USAGE_PERC + "{") == 1  # ONE series
    for group in ("full", "window"):
        assert f'group="{group}"' in text
    assert mc.HBM_KV_WINDOW_SHARE in text
    timing = ek.since(engine, before)
    assert 0 < timing["moe_prefill_held_pairs_n"] < timing[
        "moe_prefill_pairs_n"]
    # slots count HELD experts: 4 expert layers x 4 held a step
    assert timing["moe_prefill_slots_n"] % 16 == 0
    ek.finish(engine, rid)


@pytest.mark.parametrize("name", [
    "tiny-laguna", "laguna-s-2.1-ep4-9l", "tiny-mistral-sliding"])
def test_byte_counts_equal_the_arrays_for_both_groups(name):
    """`param_bytes`, `kv_block_bytes` a group and `derive_num_blocks` say
    what the arrays weigh (shapes only: nothing is allocated)."""
    model = (ModelConfig.tiny(sliding_window=16) if name.startswith("tiny-m")
             else resolve_model_config(name))
    econf = EngineConfig(
        model=model, cache=CacheConfig(
            block_size=32, num_blocks=None, enable_prefix_caching=False),
        scheduler=SchedulerConfig(
            max_num_seqs=16, max_num_batched_tokens=2048,
            decode_buckets=(16,), prefill_buckets=(2048,), decode_window=8))
    wblocks = resolve_window_blocks(econf)
    assert wblocks == 16 * (
        math.ceil((model.max_window + 2048 + 16) / 32) + 2) + 1
    kv = jax.eval_shape(
        lambda: llama.init_kv_cache(model, 100, 32, num_window_blocks=wblocks))
    size = lambda a: a.size * a.dtype.itemsize  # noqa: E731
    full = sum(size(kv[i]) for i in model.full_layers)
    win = sum(size(kv[i]) for i in model.window_layers)
    assert full == 100 * kv_block_bytes(model, 32, group="full")
    assert win == wblocks * kv_block_bytes(model, 32, group="window")
    tree = jax.eval_shape(
        lambda: llama.init_params(model, jax.random.PRNGKey(0)))
    assert param_bytes(model) == sum(size(a) for a in jax.tree.leaves(tree))
    hbm = 16 * 2**30
    n = derive_num_blocks(
        model, econf.cache, ParallelConfig(), hbm_bytes=hbm,
        max_num_seqs=16, num_window_blocks=wblocks)
    if model.full_layers:
        budget = int(hbm * 0.85) - param_bytes(model) - 2**30 - win
        per = kv_block_bytes(model, 32, group="full")
        # (the hoist reserve and the seats' cap only ever lower it)
        assert 0 < n <= budget // per
    else:  # bookkeeping only
        assert n == 16 * math.ceil(model.max_model_len / 32) + 1


@pytest.mark.parametrize("name", sorted(PRESETS))
def test_param_bytes_equal_the_tree_of_every_preset(name):
    """One count from the per-layer list for every tree but the latent
    one: what `param_bytes` says is what `init_params` builds."""
    model = resolve_model_config(name)
    tree = jax.eval_shape(
        lambda: llama.init_params(model, jax.random.PRNGKey(0)))
    assert param_bytes(model) == sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def test_published_sizes_of_the_cut():
    """The cell's cut, from the preset: 5.69 B parameters in bf16, the
    published widths, 3 full + 6 window layers, 64 of 256 experts."""
    model = resolve_model_config("laguna-s-2.1-ep4-9l")
    assert (model.hidden_size, model.head_dim, model.num_kv_heads) == (
        3072, 128, 8)
    assert [s.num_heads for s in model.layer_specs] == [
        48, 72, 72, 72, 48, 72, 72, 72, 48]
    assert [s.window for s in model.layer_specs] == [
        0, 512, 512, 512, 0, 512, 512, 512, 0]
    assert [s.experts for s in model.layer_specs] == [False] + [True] * 8
    assert (model.num_experts, model.held_experts,
            model.num_experts_per_tok) == (256, 64, 10)
    assert model.vocab_size == 25088 and model.max_model_len == 32768
    assert 11.37e9 < param_bytes(model) < 11.40e9
    assert kv_block_bytes(model, 32, group="full") == 3 * 32 * 4096
    assert kv_block_bytes(model, 32, group="window") == 6 * 32 * 4096
    whole = resolve_model_config("laguna-s-2.1")
    assert whole.num_layers == 48 and whole.held_experts == 256
    assert sum(s.window > 0 for s in whole.layer_specs) == 36


# -- refusals and the config's keys -------------------------------------------

@pytest.mark.parametrize("kw,message", [
    (dict(parallel=ParallelConfig(tensor_parallel_size=2)),
     "parallelism .* not implemented for a param tree stacked by layer kind"),
    (dict(parallel=ParallelConfig(sequence_parallel_size=2)),
     "sequence parallelism does not support sliding-window"),
    (dict(lora=LoRAConfig(max_loras=1)), "LoRA adapters are not implemented"),
    (dict(model_kw=dict(quantization="int8")),
     "quantization int8 is not implemented for a param tree stacked"),
])
def test_unbuilt_engine_options_are_refused_by_name(cfg, kit, kw, message):
    """What knows the flat param tree only is refused for a tree stacked
    by layer kind, each by name."""
    kw = dict(kw)
    model = dataclasses.replace(cfg, **kw.pop("model_kw", {}))
    with pytest.raises(ValueError, match=message):
        kit.fresh_engine(ek.engine_config(model, **kw))


def _hf_config(tmp_path, **over):
    rope = {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
    kinds = ["full_attention" if i % 4 == 0 else "sliding_attention"
             for i in range(5)]
    hf = {
        "architectures": ["LagunaForCausalLM"], "model_type": "laguna",
        "vocab_size": 100352, "hidden_size": 3072, "intermediate_size": 12288,
        "num_hidden_layers": 5, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 1048576, "attention_bias": False,
        "rms_norm_eps": 1e-6, "num_experts": 256, "num_experts_per_tok": 10,
        "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head",
        "sliding_window": 512, "rope_parameters": rope, "layer_types": kinds,
        "mlp_layer_types": ["dense"] + ["sparse"] * 4,
        "gating_types": ["per_head"] * 5,
        "num_attention_heads_per_layer": [
            48 if k == "full_attention" else 72 for k in kinds],
        "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
        "moe_apply_router_weight_on_input": False,
    }
    hf.update(over)
    (tmp_path / "config.json").write_text(json.dumps(hf))
    return str(tmp_path)


def test_the_published_config_maps_onto_the_preset(tmp_path):
    got = ModelConfig(**{
        **_from_hf_config(_hf_config(tmp_path)), "checkpoint": None,
        "tokenizer": None, "model": "x"})
    want = resolve_model_config("laguna-s-2.1")
    assert got.layers == want.layers[:5]
    for key in ("hidden_size", "intermediate_size", "num_kv_heads",
                "head_dim", "num_experts", "num_experts_per_tok",
                "moe_intermediate_size", "n_shared_experts", "scoring_func",
                "routed_scaling_factor", "norm_topk_prob",
                "attn_output_gate", "vocab_size", "tie_word_embeddings"):
        assert getattr(got, key) == getattr(want, key), key


@pytest.mark.parametrize("override,message", [
    ({"attention_bias": True}, "attention_bias"),
    ({"moe_router_logit_softcapping": 30.0}, "moe_router_logit_softcapping"),
    ({"moe_apply_router_weight_on_input": True}, "router_weight_on_input"),
    ({"gating_types": ["per_head"] * 4 + ["none"]}, "gating other than"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
])
def test_unbuilt_config_keys_are_refused_by_name(tmp_path, override, message):
    with pytest.raises(ValueError, match=message):
        _from_hf_config(_hf_config(tmp_path, **override))


def test_a_checkpoint_of_the_family_is_refused_by_name(tmp_path):
    from vllm_production_stack_tpu.models.loader import load_checkpoint_params

    model = dataclasses.replace(
        resolve_model_config("tiny-laguna"), checkpoint=str(tmp_path))
    with pytest.raises(NotImplementedError, match="laguna checkpoint"):
        load_checkpoint_params(model)


def test_a_row_reads_the_same_alone_and_among_others(cfg, kit):
    """The batch-invariance probe of the benchmark's set-up, at tiny size:
    the same prompt alone and among other seats' rows (other contexts,
    other give-back states, a wider dispatch) gives the same first-token
    distribution — a row's two tables reach no other row's pages."""
    engine, _ = kit.engine(ek.engine_config(ek.cut(cfg, 5)))
    probe = list(range(7, 7 + 45))
    sampling = SamplingParams(max_tokens=1, temperature=0.0, logprobs=8)

    def first_logprobs(rid):
        out = {}
        while engine.has_unfinished():
            with engine.step_lock:
                for o in engine.step():
                    if o.request_id == rid and o.new_logprobs:
                        out = o.new_logprobs[0]
        return out

    alone = first_logprobs(
        engine.add_request(prompt_token_ids=probe, sampling=sampling))
    for i in range(3):  # holders: longer rows that decode meanwhile
        engine.add_request(
            prompt_token_ids=list(range(1 + i, 80 + 9 * i)),
            sampling=ek.greedy(24))
    for _ in range(8):
        with engine.step_lock:
            engine.step()
    crowded = first_logprobs(
        engine.add_request(prompt_token_ids=probe, sampling=sampling))
    assert alone[1] == crowded[1]  # the same top ids
    assert max(abs(a - b) for a, b in zip(alone[2], crowded[2])) < 1e-5


def test_ngram_speculation_verifies_through_both_groups(cfg, kit):
    """The speculative verify program writes and reads both page groups
    like a prefill chunk, and a rejected draft rolls both tables back: a
    repetitive prompt (drafts get proposed) decodes to the plain engine's
    greedy tokens."""
    model = ek.cut(cfg, 5)
    prompt = [5, 6, 7, 8, 9, 10, 11, 12] * 7

    def run(**sched):
        engine, _ = kit.engine(ek.engine_config(model, sched=sched))
        rid = engine.add_request(
            prompt_token_ids=prompt, sampling=ek.greedy(24))
        return engine, _drain(engine, [rid])[0]

    _, want = run()
    spec, got = run(num_speculative_tokens=3)
    assert got == want
    assert spec.scheduler.spec_proposed_tokens > 0
    pool = spec.scheduler.pool
    assert pool.window.num_free == pool.window.num_usable
