"""Generation by diffusion over blocks (SDAR, ISSUE 41), the parts beside
tests/test_sdar.py's engine-against-reference cases: the block mask and the
transfer rule against the reference's, the presets and the mapper, what is
refused by name, the counters and the names, and the other models' step
programs against the parent's, byte for byte."""

import dataclasses
import hashlib
import json
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import engine_kit as ek
from vllm_production_stack_tpu import metrics_contract as mc
from vllm_production_stack_tpu.engine.config import (
    BD_STRATEGIES, BlockDiffusion, CacheConfig, EngineConfig, LoRAConfig,
    ParallelConfig,
)
from vllm_production_stack_tpu.engine.model_runner import ModelRunner
from vllm_production_stack_tpu.engine.scheduler import Scheduler
from vllm_production_stack_tpu.models import llama, registry
from vllm_production_stack_tpu.models.registry import resolve_model_config
from vllm_production_stack_tpu.ops.attention import causal_page_mask
from vllm_production_stack_tpu.testing import reference_sdar as reference


@pytest.fixture(scope="module")
def cfg():
    return resolve_model_config("tiny-sdar")


# what this file's engines differ in from the kit's: a decode window of two
# blocks of 4
WINDOW = dict(decode_window=8)


# -- the pieces --------------------------------------------------------------

def test_the_block_mask():
    """A query sees the keys up to the END of its block, from position 0 of
    the sequence; the reference's mask and the served XLA mask agree."""
    ref_cfg = {"causal_mask": False, "block_length": 4}
    pos = jnp.arange(11)
    want = np.asarray(reference.visible(ref_cfg, pos, pos))
    assert want[0, :4].all() and not want[0, 4:].any()
    assert want[5, :8].all() and not want[5, 8:].any()
    assert want[9].all()
    got = causal_page_mask(pos[None], jnp.array([11]), 11, block=4)[0]
    np.testing.assert_array_equal(np.asarray(got), want)
    causal = causal_page_mask(pos[None], jnp.array([11]), 11)[0]
    np.testing.assert_array_equal(
        np.asarray(causal),
        np.asarray(reference.visible({"causal_mask": True}, pos, pos)))


@pytest.mark.parametrize("strategy", BD_STRATEGIES)
@pytest.mark.parametrize("steps", [4, 3, 2])
def test_the_transfer_rule_against_the_reference(strategy, steps):
    """`llama.bd_transfer` against `reference.transfer` over random
    confidences, ties included (to the lowest position), every number of
    masks left, both branches of the dynamic rule."""
    bd = BlockDiffusion(block_length=4, denoising_steps=steps,
                        remasking_strategy=strategy,
                        confidence_threshold=0.3, mask_token_id=9)
    ref_cfg = {**dataclasses.asdict(bd)}
    rng = np.random.RandomState(steps)
    rows = 64
    # logits over 10 tokens with a tie between two positions in some rows
    logits = rng.randn(rows, 4, 10).astype(np.float32) * 2
    logits[::4, 2] = logits[::4, 0]
    masked = rng.rand(rows, 4) < 0.7
    tokens = np.where(masked, 9, rng.randint(0, 9, (rows, 4))).astype(np.int32)
    for pas in range(steps + 1):
        new, m_new, pick, over = llama.bd_transfer(
            bd, jnp.asarray(logits), jnp.asarray(tokens), jnp.asarray(masked),
            jnp.int32(pas))
        lp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits), axis=-1))
        conf = np.exp(lp.max(-1))
        for r in range(rows):
            want = reference.transfer(ref_cfg, conf[r], list(masked[r]), pas)
            assert sorted(np.flatnonzero(np.asarray(pick[r]))) == want, (
                r, pas)
            for i in want:
                assert int(new[r, i]) == int(lp[r, i].argmax())
            assert not np.asarray(m_new[r])[want].any()
            n_s = reference.transfer_schedule(ref_cfg)[min(pas, steps - 1)]
            assert int(over[r]) == len(want) - min(n_s, int(masked[r].sum()))


def test_the_schedule_and_the_record():
    assert BlockDiffusion(4, 4).transfer_schedule == (1, 1, 1, 1)
    assert BlockDiffusion(4, 3).transfer_schedule == (2, 1, 1)
    assert BlockDiffusion(8, 3).transfer_schedule == (3, 3, 2)
    with pytest.raises(ValueError, match="remasking_strategy"):
        BlockDiffusion(remasking_strategy="confident")
    with pytest.raises(ValueError, match="denoising_steps"):
        BlockDiffusion(block_length=4, denoising_steps=5)


def test_the_presets_and_the_mapper(tmp_path):
    """`sdar-30b-a3b-chat` is the published config key for key over the
    qwen3moe decoder + the generation record; a config.json with
    `model_type: sdar_moe` maps the same way whatever its `architectures`
    string says."""
    full = resolve_model_config("sdar-30b-a3b-chat")
    cut = resolve_model_config("sdar-30b-a3b-chat-8l")
    assert (full.num_layers, cut.num_layers) == (48, 8)
    assert dataclasses.replace(full, num_layers=8, model=cut.model) == cut
    assert cut.block_diffusion == BlockDiffusion(
        4, 4, "low_confidence_dynamic", 0.9, 151669)
    assert (cut.num_experts, cut.num_experts_per_tok, cut.qk_norm,
            cut.norm_topk_prob, cut.architecture) == (
                128, 8, True, True, "qwen3moe")
    hf = {"architectures": ["SDARMoeForCausalLM"], "model_type": "sdar_moe",
          "attention_bias": False, "decoder_sparse_step": 1,
          "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
          "intermediate_size": 6144, "max_position_embeddings": 32768,
          "mlp_only_layers": [], "moe_intermediate_size": 768,
          "norm_topk_prob": True, "num_attention_heads": 32,
          "num_experts": 128, "num_experts_per_tok": 8,
          "num_hidden_layers": 48, "num_key_value_heads": 4,
          "rms_norm_eps": 1e-06, "rope_scaling": None,
          "rope_theta": 1000000, "sliding_window": None,
          "tie_word_embeddings": False, "vocab_size": 151936}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    mapped = resolve_model_config(str(tmp_path))
    same = dataclasses.replace(
        mapped, model=full.model, checkpoint=None, tokenizer=None)
    assert same == full
    for key, other in (("block_length", 8), ("mask_token_id", 7)):
        (tmp_path / "config.json").write_text(json.dumps({**hf, key: other}))
        with pytest.raises(ValueError, match=f"{key}={other} is not"):
            resolve_model_config(str(tmp_path))
    assert set(registry.PRESETS) >= {
        "sdar-30b-a3b-chat", "sdar-30b-a3b-chat-8l", "tiny-sdar"}


# -- what is not built, by name ---------------------------------------------

@pytest.mark.parametrize("what,over", [
    ("speculative decoding", {"sched": {
        "num_speculative_tokens": 3, "speculative_method": "ngram"}}),
    ("a mesh of 2 devices", {"parallel": ParallelConfig(
        tensor_parallel_size=2)}),
    ("LoRA adapters", {"lora": LoRAConfig(max_loras=1)}),
    ("the host-RAM tier", {"cache": CacheConfig(
        block_size=8, num_blocks=64, num_host_blocks=8,
        enable_prefix_caching=False)}),
    ("the peer-engine tier", {"kv_peer_fetch": True}),
    ("--decode-window 6", {"sched": {"decode_window": 6}}),
    ("--block-size 6", {"cache": CacheConfig(
        block_size=6, num_blocks=64, enable_prefix_caching=False)}),
])
def test_refused_by_name_at_engine_start(cfg, what, over):
    with pytest.raises(ValueError) as err:
        ModelRunner(ek.engine_config(cfg, **{**WINDOW, **over}))
    assert what in str(err.value)
    assert "generation by diffusion over blocks" in str(err.value)


def test_refused_by_name_max_model_len_and_requests(cfg, kit):
    with pytest.raises(ValueError, match="--max-model-len 250"):
        ModelRunner(ek.engine_config(
            dataclasses.replace(cfg, max_model_len=250), **WINDOW))
    engine, _ = kit.engine(ek.engine_config(cfg, **WINDOW))
    with pytest.raises(ValueError, match="min_tokens"):
        engine.add_request(prompt_token_ids=[1, 2, 3], sampling=ek.greedy(
            4, min_tokens=2))
    with pytest.raises(ValueError, match="/v1/embeddings"):
        engine.embed([[1, 2, 3]])
    grammar = engine.grammar_cache.get({"kind": "json_object"})[0]
    with pytest.raises(ValueError, match="structured output"):
        engine.add_request(prompt_token_ids=[1, 2, 3], sampling=ek.greedy(
            4, grammar=grammar))
    assert not engine.has_unfinished()


def test_prefix_caching_is_turned_off_with_one_log_line(cfg, kit, caplog):
    """Asked for (the default), it is turned off by the engine, which says
    so once; the scheduler on its own refuses it by name. Two identical
    prompts therefore both compute: no hit, the same tokens."""
    config = ek.engine_config(cfg, **WINDOW, cache=CacheConfig(
        block_size=8, num_blocks=64, enable_prefix_caching=True))
    with caplog.at_level(logging.INFO):
        engine = kit.fresh_engine(config)
    said = [r for r in caplog.records if "WITHOUT prefix hits" in r.message]
    assert len(said) == 1
    assert not engine.config.cache.enable_prefix_caching
    (prompt,) = ek.prompts(cfg, [41], seed=8)
    (a,) = engine.generate([prompt], ek.greedy(6))
    (b,) = engine.generate([prompt], ek.greedy(6))
    assert a["token_ids"] == b["token_ids"]
    assert engine.scheduler.pool.stats.hits == 0
    with pytest.raises(ValueError, match="prefix caching is not built"):
        Scheduler(cfg, config.cache, config.scheduler)


# -- the counters and the names ---------------------------------------------

def test_counters_metrics_flight_record_and_programs(cfg, kit):
    """One row of 9 prompt tokens and 7 wanted: two windows (8 positions,
    then 4: one block covers what is left... the first is given 1 token
    and yields 7) — the counts by hand, the same numbers at /metrics, the
    passes in the window's flight record, the generation record at
    /debug/programs, and steps that are passes while tokens are tokens."""
    from vllm_production_stack_tpu.engine.metrics import EngineMetrics

    model = dataclasses.replace(cfg, block_diffusion=dataclasses.replace(
        cfg.block_diffusion, confidence_threshold=0.9))  # never fires
    engine, before = kit.engine(ek.engine_config(model, **WINDOW))
    (prompt,) = ek.prompts(model, [9], seed=6)
    (out,) = engine.generate([prompt], ek.greedy(7))
    assert len(out["token_ids"]) == 7
    timing = ek.since(engine, before)
    # one window of two blocks: 3 masks + commit, 4 masks + commit
    assert {k: timing[k] for k in mc.DEBUG_TIMING_BD_COUNTS} == {
        "bd_passes_n": 9, "bd_row_passes_n": 9, "bd_repeat_row_passes_n": 0,
        "bd_blocks_n": 2, "bd_tokens_n": 7, "bd_threshold_tokens_n": 0}
    assert timing["decode_tokens"] == 7 and timing["decode_n"] == 1
    # an MoE model's decode counts follow the passes: 4 tokens a pass
    # (padded rows too: 4 seats), 2 of 8 experts, 2 layers
    assert timing["moe_decode_pairs_n"] == 9 * 4 * 4 * 2 * 2
    stats = engine.stats()
    assert stats.block_diffusion == {
        k: timing[k] for k in mc.DEBUG_TIMING_BD_COUNTS}
    assert stats.generation_tokens == 7
    text = EngineMetrics("tiny-sdar").render(stats).decode()
    for key, name in mc.DEBUG_TIMING_BD_COUNTS.items():
        assert name in mc.ALL_COUNTERS
        line = next((ln for ln in text.splitlines()
                     if ln.startswith(name + "{")), name + "{} 0")
        # (a counter that never rose has no sample yet)
        assert float(line.rsplit(" ", 1)[1]) == timing[key], line
    resolved = [r for r in engine.flightrec.snapshot()
                if r.get("event") == "resolve" and "passes" in r]
    assert [r["passes"] for r in resolved] == [9]
    gen = engine.runner.device_report()["generation"]
    assert gen["kind"] == "block_diffusion" and gen["block_length"] == 4
    assert gen["window_blocks"] == 2 and gen["passes_per_block"] == [2, 5]
    assert engine.runner.device_report()["moe"]["decode"]["by_bucket"] == {
        "4": llama.moe_experts_form(model, 16, kernel=False)}
    # any other model serves none of it
    plain, _ = kit.engine(EngineConfig.tiny())
    plain.generate([[1, 2, 3]], ek.greedy(3))
    assert not set(mc.DEBUG_TIMING_BD_COUNTS) & set(plain.flightrec.timing())
    assert "generation" not in plain.runner.device_report()
    assert plain.stats().block_diffusion == {}


def test_the_passes_name_their_parts(cfg):
    """The window program's named scopes: the pass's own `attn`, `kv_stage`
    and expert scopes keep their names, and the transfer rule has one."""
    runner = ModelRunner(ek.engine_config(cfg, **WINDOW))
    lowered = runner._decode_window_fn.lower(
        runner._aval_tree(runner.params), None,
        runner._aval_tree(runner.kv_caches),
        *runner._decode_avals(4, 8, None, 8), window=8, want_logprobs=False)
    text = lowered.as_text(debug_info=True)
    for scope in ("bd_transfer", "kv_stage", "attn", "kv_commit", "lm_head",
                  "sample"):
        assert scope in text, scope
    assert "stablehlo.while" in text


# -- every other model's programs are what they were -------------------------

# sha256 of the StableHLO the PARENT commit (PR 40, fcbbba0) lowered for each
# tiny preset's prefill and decode-window program (`_program_digests`) — as
# replaced by PR 56, which changes EVERY program on purpose (the q, k and v
# leaves lie (heads, head_dim, in): models/llama.py:_mm_heads). A
# later PR that changes one of these programs on purpose replaces its pins:
# `PYTHONPATH=. JAX_PLATFORMS=cpu python tests/test_sdar_parts.py` prints this
# table for the tree it runs in.
_PARENT_PROGRAMS = {
    "tiny-llama": (
        "a6250089dc5c35e95b5bc2fec2ce159dcfeedb751d3e43176df201f4af0354a5",
        "9d6c74b69adf8883a96c980aa668f87f2a2886e501acf5f6654e62a9d414c4b0"),
    "tiny-olmoe": (
        "ea972abe9c513e9822d7e3cee55f95bcbd07a2c8153aa5917cd75de5f9ef743a",
        "29b0b9e6a9cf3190d87ed9780e14066cd46f3213aac19055d6d4ac4ec124dd13"),
    "tiny-kanana": (
        "835756d307acb047b15102431d6ee23bbf7e30eac71d3a3dce8f4f086f9ecf94",
        "1da1977a0cb1bfc4c488c1f427e5c68b0f78e454da84288e3934c65ff3151a28"),
    "tiny-laguna": (
        "84c5060c104db6a190498008aa633a649db6cd402c34af5cabe4342b9d0b9b5f",
        "39ac9f0d6ecd6186d94b640298f81664e7dd0a7d3ae47721175c30b94e85ad27"),
    "tiny-solar-open2": (
        "a727984aaff51ec817be0ea698e51c2966be3a60d65a115899843ba9a2a9a884",
        "b439e853cf18dadccde6caa9b2040cf99538fb38ebca3d07314d1c9a37a49c7d"),
}


def _program_digests(preset: str) -> tuple[str, str]:
    model = resolve_model_config(preset)
    assert model.block_diffusion is None
    runner = ModelRunner(ek.engine_config(model, num_blocks=64))
    params = runner._aval_tree(runner.params)
    pools = runner._aval_tree(runner.kv_caches)
    flags = dict(want_logprobs=False, want_min_tokens=False,
                 want_grammar=False)
    prefill = runner._step_fn.lower(
        params, None, pools, *runner._prefill_avals(4, 32, 8), **flags)
    decode = runner._decode_window_fn.lower(
        params, None, pools, *runner._decode_avals(4, 8, None, 4), window=4,
        **flags)
    return tuple(hashlib.sha256(low.as_text().encode()).hexdigest()
                 for low in (prefill, decode))


@pytest.mark.parametrize("preset", sorted(_PARENT_PROGRAMS))
def test_other_models_programs_lower_to_the_parents_stablehlo(preset):
    assert _program_digests(preset) == _PARENT_PROGRAMS[preset]


@pytest.mark.parametrize("alike", [False, True])
def test_the_taps_split_of_the_touched_share(alike):
    """`BlockTap.touched_split` by hand: 2 live rows x 4 positions over 16
    experts, top-2, one layer. Mask tokens that route alike read under what
    independent tokens of the layer's frequencies would touch; tokens that
    all differ read at or over it."""
    from vllm_production_stack_tpu.testing.reference_check import BlockTap

    mask = 99
    before = np.array([[5, mask, mask, mask], [mask, mask, mask, mask],
                       [0, 0, 0, 0]])  # (a padding row: not counted)
    experts = np.concatenate([
        np.array([[0, 1], [2, 3], [2, 3], [2, 3],
                  [2, 3], [2, 3], [2, 3], [2, 3]]) if alike
        else np.arange(16).reshape(8, 2), np.full((4, 2), 15)])[None]
    tap = BlockTap()
    tap.window(["a", "b"])
    tap(0, 0, np.zeros((3, 4), int), before, before, before, experts)
    split = tap.touched_split(mask, 16)
    assert [split[g]["tokens"] for g in ("all", "masks", "others")] == [
        8.0, 7.0, 1.0]
    assert split["all"]["balanced"] == pytest.approx(1 - (14 / 16) ** 8)
    assert split["others"]["touched"] == 2 / 16
    if alike:
        # expert frequencies a token: 1/8 for experts 0 and 1, 7/8 for 2, 3
        assert (split["all"]["touched"], split["masks"]["touched"]) == (
            4 / 16, 2 / 16)
        want = (2 * (1 - (7 / 8) ** 7) + 2 * (1 - (1 / 8) ** 7)) / 16
        assert split["masks"]["independent"] == pytest.approx(want)
        assert split["masks"]["touched"] < want
    else:
        assert (split["all"]["touched"], split["masks"]["touched"]) == (
            1.0, 14 / 16)
        assert split["all"]["independent"] == pytest.approx(
            1 - (7 / 8) ** 8)
        assert split["all"]["touched"] > split["all"]["independent"]


if __name__ == "__main__":
    import pprint

    pprint.pprint({p: _program_digests(p) for p in sorted(_PARENT_PROGRAMS)})
