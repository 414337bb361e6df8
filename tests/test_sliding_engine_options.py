"""A model with window layers keeps what it could be served with before the
window page group existed (ISSUE 33, REVIEW): a mesh, LoRA adapters, a draft
model, KV tiers and prefix hits keep every layer on the one full-length
group; without them the window layers' pages live in a group of their own.
(tests/test_window_kv.py holds the group itself.)"""

import dataclasses

import pytest

from vllm_production_stack_tpu.engine.config import (
    CacheConfig, EngineConfig, LoRAConfig, ModelConfig, ParallelConfig,
    SchedulerConfig,
)
from vllm_production_stack_tpu.engine.engine import LLMEngine
from vllm_production_stack_tpu.engine.request import SamplingParams
from vllm_production_stack_tpu.models.registry import resolve_model_config


def _engine_config(cfg, backend="xla", **kw):
    # (no prefix caching: the window layers' pages live in their own group)
    cache = kw.pop("cache", None) or CacheConfig(
        block_size=8, num_blocks=128, enable_prefix_caching=False)
    return EngineConfig(
        model=cfg, cache=cache,
        scheduler=SchedulerConfig(
            max_num_seqs=8, max_num_batched_tokens=32, decode_buckets=(4, 8),
            prefill_buckets=(16, 32), decode_window=4),
        attention_backend=backend, prefill_attention_backend=backend, seed=5,
        **kw)


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
    "draft": dict(draft=True),
}


@pytest.mark.parametrize("name,asked", [
    *[(name, asked) for name in ("tiny-gemma2", "tiny-mistral-sliding")
      for asked in _ASKED],
    # (a tree stacked by layer kind refuses a mesh and LoRA by name:
    # tests/test_window_kv.py)
    ("tiny-laguna", "host_tier"), ("tiny-laguna", "draft")])
def test_a_sliding_model_keeps_what_it_was_served_with(name, asked):
    """Gemma-2 and Mistral-v0.1 class models were served under a mesh, with
    LoRA adapters, a draft model and KV tiers before the window page group
    existed: asking for any of them keeps every layer on the one
    full-length group (the window is a mask), and the tokens are those of
    the engine that uses the window group."""
    kw = dict(_ASKED[asked])
    prompts = [list(range(3 + i, 50 + 7 * i)) for i in range(2)]
    sampling = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    model = _cut5(_sliding_model(name))
    grouped = LLMEngine(_engine_config(model))
    assert grouped.scheduler.pool.window is not None
    econf = _engine_config(model, **{k: v for k, v in kw.items()
                                      if k != "draft"})
    if kw.get("draft"):
        econf = econf.replace(scheduler=dataclasses.replace(
            econf.scheduler, num_speculative_tokens=2,
            speculative_method="draft", draft_model="tiny-llama"))
    engine = LLMEngine(econf)
    assert engine.scheduler.pool.window is None
    assert not engine.runner._window_group
    want = grouped.generate(prompts, sampling)
    got = engine.generate(prompts, sampling)
    assert [o["token_ids"] for o in got] == [o["token_ids"] for o in want]


@pytest.mark.parametrize("name,backend", [
    ("tiny-gemma2", "xla"), ("tiny-mistral-sliding", "xla"),
    ("tiny-mistral-sliding", "pallas_interpret"), ("tiny-laguna", "xla")])
def test_prefix_caching_keeps_the_one_group_and_its_hits(
        name, backend, caplog):
    """Prefix caching (the default) and the window page group exclude each
    other — a hit would need pages the group gave back — so with it on a
    model with window layers keeps every layer's pages for the whole
    context, says so in one sentence at start, and HITS; with it off the
    window layers get their group. The tokens agree (under the window
    kernels too: they walk a full-length table from the page of the first
    visible key as they walk the group's)."""
    model = _cut5(_sliding_model(name))
    sampling = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    prompt = list(range(1, 60))
    with caplog.at_level("INFO"):
        cached = LLMEngine(_engine_config(model, backend, cache=CacheConfig(
            block_size=8, num_blocks=128)))
    assert "hold the whole context like the full layers" in caplog.text
    assert "prefix caching" in caplog.text
    assert cached.scheduler.pool.window is None
    first = cached.generate([prompt], sampling)[0]["token_ids"]
    again = cached.generate([prompt], sampling)[0]["token_ids"]
    assert cached.scheduler.pool.stats.hits > 0
    caplog.clear()
    with caplog.at_level("INFO"):
        grouped = LLMEngine(_engine_config(model, backend))
    assert "keep their pages in a group of their own" in caplog.text
    assert grouped.scheduler.pool.window is not None
    assert not grouped.scheduler.pool.enable_prefix_caching
    assert first == again == grouped.generate([prompt], sampling)[0]["token_ids"]
