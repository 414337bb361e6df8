"""The served engine against the plain float32 reference
(vllm_production_stack_tpu/testing/reference_decoder.py): chunked prefill,
then decode through the paged cache, compared by logprobs with the
reference's full forward — for OLMoE and for the variants the benchmark's
other models need (per-head QK-norm, QKV bias with GQA, Mixtral routing)."""

import filecmp
import os

import jax
import numpy as np
import pytest

from vllm_production_stack_tpu.engine.config import (
    CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
)
from vllm_production_stack_tpu.engine.engine import LLMEngine
from vllm_production_stack_tpu.models.registry import resolve_model_config
from vllm_production_stack_tpu.testing import reference_check
from vllm_production_stack_tpu.testing import reference_decoder as reference

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Engine and reference both compute in float32 here; they differ by the
# order of their sums (paged attention over pages and a staged window, the
# grouped matmul's row order, XLA's CPU dot against "highest") — a few
# float32 ulps of logprobs of size ~6 (ln 512), measured 5e-7 to 1e-6 over
# these cases. bfloat16 activations move the same logprobs by 4e-3 (the test
# below), so a lower precision than stated cannot pass.
F32_ATOL = 2e-5

CASES = {
    "tiny-olmoe": lambda: resolve_model_config("tiny-olmoe"),
    "tiny-olmoe-int8": lambda: resolve_model_config(
        "tiny-olmoe", quantization="int8"),
    "tiny-llama+qk_norm": lambda: ModelConfig.tiny(
        architecture="qwen3", qk_norm=True),
    "tiny-llama+bias+gqa": lambda: ModelConfig.tiny(
        architecture="qwen2", attention_bias=True),
    "tiny-mixtral": lambda: resolve_model_config("tiny-mixtral"),
}


def run_case(cfg: ModelConfig) -> dict:
    engine = LLMEngine(EngineConfig(
        model=cfg,
        cache=CacheConfig(block_size=8, num_blocks=64),
        # 16-token chunks: the 21- and 37-token prompts cross chunk edges
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=16,
            decode_buckets=(4,), prefill_buckets=(16,), decode_window=4),
    ))
    rng = np.random.RandomState(26)
    prompts = [list(map(int, rng.randint(1, cfg.vocab_size, size=n)))
               for n in (9, 21, 37)]
    served = reference_check.served_logprobs(engine, prompts, max_tokens=9)
    assert all(len(s["token_ids"]) == 9 == len(s["logprobs"]) for s in served)
    return reference_check.compare(
        reference, cfg, engine.runner.params, prompts, served)


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_matches_reference(case):
    cfg = CASES[case]()
    if cfg.attention_bias:
        assert cfg.num_heads > cfg.num_kv_heads  # the case is GQA too
    got = run_case(cfg)
    assert got["positions"] == 27
    assert got["max_abs_dlogprob"] < F32_ATOL, got
    if cfg.num_experts:
        # float32 both sides: the same experts everywhere
        assert got["routing_disagreement_share"] == 0.0, got


def test_bf16_activations_fail_the_float32_tolerance():
    """The tolerance is tight: the same engine in bfloat16 is outside it."""
    got = run_case(resolve_model_config("tiny-olmoe", dtype="bfloat16"))
    assert got["max_abs_dlogprob"] > 10 * F32_ATOL, got


def test_int8_weights_are_taken_as_served():
    """The reference widens q * s, so quantisation error is not compared:
    the int8 case passes the float32 tolerance above although int8 weights
    are ~1% away from the ones they were made from."""
    leaf = {"q": np.full((2, 3), 7, np.int8),
            "s": np.asarray([[0.5, 1.0, 2.0]], np.float32)}
    np.testing.assert_array_equal(
        np.asarray(reference.widen(leaf)), [[3.5, 7.0, 14.0]] * 2)


def test_reference_refuses_what_it_does_not_compute():
    with pytest.raises(ValueError):
        reference.check_config({"hidden_size": 8, "num_attention_heads": 2,
                                "clip_qkv": 8.0})
    with pytest.raises(ValueError):
        reference.published_keys(ModelConfig.tiny(sliding_window=16))


@pytest.mark.parametrize("package_file,benchmark_file", [
    ("reference_decoder.py", "decoder.py"),  # the Llama lineage
    ("reference_mla.py", "mla.py"),  # the DeepSeek-V3 family (ISSUE 31)
    ("reference_laguna.py", "laguna.py"),  # window + full layers (ISSUE 33)
    ("reference_solar_open2.py", "solar_open2.py"),  # KDA state (ISSUE 39)
    ("reference_sdar.py", "sdar.py"),  # diffusion over blocks (ISSUE 41)
    ("reference_ling.py", "ling.py"),  # latents beside state (ISSUE 45)
    ("reference_falcon_h1.py", "falcon_h1.py"),  # pages AND state in one
    #                                              layer (ISSUE 50)
    ("reference_glm_dsa.py", "glm_dsa.py"),  # selected keys (ISSUE 55)
    ("reference_olmo_hybrid.py", "olmo_hybrid.py"),  # a gate a head, a
    #                                      96 x 192 state (ISSUE 58)
])
def test_benchmark_copy_is_byte_identical(package_file, benchmark_file):
    """benchmarks/chip/reference/<file> is the yardstick's own copy: a later
    PR that edits the package's file has to edit the benchmark too."""
    assert filecmp.cmp(
        os.path.join(REPO, "vllm_production_stack_tpu", "testing",
                     package_file),
        os.path.join(REPO, "benchmarks", "chip", "reference", benchmark_file),
        shallow=False)


def test_reference_routing_margin():
    cfg = reference.check_config({
        "hidden_size": 4, "num_attention_heads": 1, "num_experts": 4,
        "num_experts_per_tok": 2, "norm_topk_prob": False})
    router = jax.numpy.eye(4) * jax.numpy.asarray([3.0, 2.0, 1.0, 0.0])
    combine, experts, margin = reference.route(
        cfg, router, jax.numpy.ones((1, 4)))
    assert sorted(np.asarray(experts)[0].tolist()) == [0, 1]
    probs = np.asarray(jax.nn.softmax(jax.numpy.asarray([3.0, 2, 1, 0])))
    np.testing.assert_allclose(np.asarray(combine)[0],
                               [probs[0], probs[1], 0, 0], rtol=1e-6)
    np.testing.assert_allclose(float(margin[0]), probs[1] - probs[2],
                               rtol=1e-5)
