"""A model with window layers keeps what it could be served with before the
window page group existed (ISSUE 33, REVIEW): a mesh, LoRA adapters, a draft
model, KV tiers and prefix hits keep every layer on the one full-length
group; without them the window layers' pages live in a group of their own.
(tests/test_window_kv.py holds the group itself.)"""

import dataclasses

import pytest

import engine_kit as ek
from vllm_production_stack_tpu.engine.config import (
    CacheConfig, LoRAConfig, ModelConfig, ParallelConfig,
)
from vllm_production_stack_tpu.models.registry import resolve_model_config


def _sliding_model(name):
    tiny = ModelConfig.tiny(max_model_len=256)
    return {
        "tiny-gemma2": dataclasses.replace(
            tiny, model=name, sliding_window=16, sliding_window_pattern=2,
            attn_logit_softcap=50.0),
        "tiny-mistral-sliding": dataclasses.replace(
            tiny, model=name, sliding_window=16),
        "tiny-laguna": resolve_model_config("tiny-laguna"),
    }[name]


def _cut5(model):
    """A stated per-layer list cut to one period and a layer."""
    if not model.layers:
        return model
    return dataclasses.replace(model, num_layers=5, layers=model.layers[:5])


_ASKED = {
    "tp2": dict(parallel=ParallelConfig(tensor_parallel_size=2)),
    "lora": dict(lora=LoRAConfig(max_loras=1, max_lora_rank=4)),
    "host_tier": dict(cache=CacheConfig(
        block_size=8, num_blocks=128, num_host_blocks=8)),
    "draft": dict(sched=dict(
        num_speculative_tokens=2, speculative_method="draft",
        draft_model="tiny-llama")),
}


@pytest.mark.parametrize("name,asked", [
    *[(name, asked) for name in ("tiny-gemma2", "tiny-mistral-sliding")
      for asked in _ASKED],
    # (a tree stacked by layer kind refuses a mesh and LoRA by name:
    # tests/test_window_kv.py)
    ("tiny-laguna", "host_tier"), ("tiny-laguna", "draft")])
def test_a_sliding_model_keeps_what_it_was_served_with(kit, name, asked):
    """Gemma-2 and Mistral-v0.1 class models were served under a mesh, with
    LoRA adapters, a draft model and KV tiers before the window page group
    existed: asking for any of them keeps every layer on the one
    full-length group (the window is a mask), and the tokens are those of
    the engine that uses the window group."""
    prompts = [list(range(3 + i, 50 + 7 * i)) for i in range(2)]
    model = _cut5(_sliding_model(name))
    grouped, _ = kit.engine(ek.engine_config(model))
    assert grouped.scheduler.pool.window is not None
    engine, _ = kit.engine(ek.engine_config(model, **_ASKED[asked]))
    assert engine.scheduler.pool.window is None
    assert not engine.runner._window_group
    assert ek.generate(engine, prompts, 12) == ek.generate(
        grouped, prompts, 12)


@pytest.mark.parametrize("name,backend", [
    ("tiny-gemma2", "xla"), ("tiny-mistral-sliding", "xla"),
    ("tiny-mistral-sliding", "pallas_interpret"), ("tiny-laguna", "xla")])
def test_prefix_caching_keeps_the_one_group_and_its_hits(
        kit, name, backend, caplog):
    """Prefix caching (the default) and the window page group exclude each
    other — a hit would need pages the group gave back — so with it on a
    model with window layers keeps every layer's pages for the whole
    context, says so in one sentence at start, and HITS; with it off the
    window layers get their group. The tokens agree (under the window
    kernels too: they walk a full-length table from the page of the first
    visible key as they walk the group's)."""
    model = _cut5(_sliding_model(name))
    prompt = list(range(1, 60))
    # (built here: what an engine says at start is under test)
    with caplog.at_level("INFO"):
        cached = kit.fresh_engine(ek.engine_config(
            model, backend, cache=CacheConfig(block_size=8, num_blocks=128)))
    assert "hold the whole context like the full layers" in caplog.text
    assert "prefix caching" in caplog.text
    assert cached.scheduler.pool.window is None
    first = ek.generate(cached, [prompt], 6)
    again = ek.generate(cached, [prompt], 6)
    assert cached.scheduler.pool.stats.hits > 0
    caplog.clear()
    config = ek.engine_config(model, backend)
    with caplog.at_level("INFO"):
        grouped = kit.fresh_engine(config)
    assert "keep their pages in a group of their own" in caplog.text
    assert grouped.scheduler.pool.window is not None
    assert not grouped.scheduler.pool.enable_prefix_caching
    # (never driven, it compiled nothing: the module's engine of the same
    # configuration serves the tokens)
    assert first == again == ek.generate(kit.engine(config)[0], [prompt], 6)
