"""End-to-end LLMEngine tests on the CPU mesh: continuous batching produces
the same greedy tokens as isolated generation, stop handling, seeded sampling
determinism, prefix-cache effects, and sleep/wake."""

import numpy as np
import pytest

from vllm_production_stack_tpu.engine.config import EngineConfig
from vllm_production_stack_tpu.engine.engine import LLMEngine
from vllm_production_stack_tpu.engine.request import SamplingParams


@pytest.fixture(scope="module")
def engine():
    return LLMEngine(EngineConfig.tiny())


def prompt_ids(seed, n):
    return list(np.random.RandomState(seed).randint(1, 500, size=n))


def test_greedy_batch_matches_solo(engine):
    greedy = SamplingParams(max_tokens=8, temperature=0.0)
    prompts = [prompt_ids(i, 5 + 3 * i) for i in range(3)]

    solo = [
        engine.generate([p], greedy)[0]["token_ids"] for p in prompts
    ]
    batched = [r["token_ids"] for r in engine.generate(prompts, greedy)]
    assert batched == solo
    for t in batched:
        assert len(t) == 8


def test_seeded_sampling_deterministic(engine):
    sp = SamplingParams(max_tokens=6, temperature=0.9, top_p=0.9, seed=42)
    a = engine.generate([prompt_ids(7, 6)], sp)[0]["token_ids"]
    b = engine.generate([prompt_ids(7, 6)], sp)[0]["token_ids"]
    assert a == b
    assert len(a) == 6


def test_stop_token_id(engine):
    greedy = SamplingParams(max_tokens=8, temperature=0.0)
    ref = engine.generate([prompt_ids(3, 6)], greedy)[0]["token_ids"]
    stop_at = ref[2]
    sp = SamplingParams(max_tokens=8, temperature=0.0, stop_token_ids=(stop_at,))
    out = engine.generate([prompt_ids(3, 6)], sp)[0]
    assert out["token_ids"][-1] == stop_at
    assert len(out["token_ids"]) == 3
    assert out["finish_reason"] == "stop"


def test_prefix_cache_hits_across_requests(engine):
    greedy = SamplingParams(max_tokens=2, temperature=0.0)
    shared = prompt_ids(11, 24)  # 3 full blocks of 8
    engine.generate([shared], greedy)
    before = engine.stats().prefix_cache_hits
    out = engine.generate([shared + [7, 8, 9]], greedy)[0]
    assert engine.stats().prefix_cache_hits > before
    # and greedy output unaffected by cache reuse
    fresh_engine = LLMEngine(EngineConfig.tiny())
    ref = fresh_engine.generate([shared + [7, 8, 9]], greedy)[0]
    assert out["token_ids"] == ref["token_ids"]


def test_stats_shape(engine):
    s = engine.stats()
    assert s.num_requests_running == 0
    assert s.num_requests_waiting == 0
    assert 0.0 <= s.kv_usage_perc <= 1.0


def test_sleep_wake(engine):
    greedy = SamplingParams(max_tokens=4, temperature=0.0)
    # long prompt (multiple full blocks) so a stale prefix cache surviving
    # sleep/wake would serve zeroed KV pages and corrupt the output
    ref = engine.generate([prompt_ids(5, 29)], greedy)[0]["token_ids"]
    engine.sleep(level=1)
    assert engine.is_sleeping
    rid = engine.add_request(prompt_token_ids=prompt_ids(5, 29), sampling=greedy)
    with pytest.raises(RuntimeError):
        while engine.has_unfinished():
            engine.step()
    engine.abort_request(rid)
    engine.wake()
    assert not engine.is_sleeping
    out = engine.generate([prompt_ids(5, 29)], greedy)[0]["token_ids"]
    assert out == ref  # weights survived; no stale prefix-cache KV served


def test_byte_tokenizer_text_roundtrip():
    eng = LLMEngine(EngineConfig.tiny())
    sp = SamplingParams(max_tokens=4, temperature=0.0)
    out = eng.generate(["hello world"], sp)[0]
    assert isinstance(out["text"], str)


def test_huge_seed_accepted(engine):
    sp = SamplingParams(max_tokens=3, temperature=0.8, seed=2**33 + 5)
    out = engine.generate([prompt_ids(1, 5)], sp)[0]
    assert len(out["token_ids"]) == 3


def test_request_outgrowing_pool_aborts_with_output():
    from vllm_production_stack_tpu.engine.config import CacheConfig

    cfg = EngineConfig.tiny().replace(
        cache=CacheConfig(block_size=4, num_blocks=8, enable_prefix_caching=False)
    )
    eng = LLMEngine(cfg)
    # 7 usable blocks * 4 = 28-token capacity; this request wants 8 + 40
    out = eng.generate(
        [prompt_ids(2, 8)], SamplingParams(max_tokens=40, temperature=0.0)
    )[0]
    assert out["finish_reason"] == "abort"
    assert eng.scheduler.pool.usage_perc == 0.0
    assert not eng._states  # no leaked per-request state


def test_find_stop_earliest_match():
    from vllm_production_stack_tpu.engine.engine import LLMEngine as E

    assert E._find_stop("hello world", ("world", "hello")) == 0
    assert E._find_stop("hello world", ("world",)) == 6
    assert E._find_stop("abc", ("x", "y")) is None


def test_incremental_detokenizer_multibyte():
    from vllm_production_stack_tpu.utils.tokenizer import (
        IncrementalDetokenizer,
        TokenizerWrapper,
    )

    tok = TokenizerWrapper()
    detok = IncrementalDetokenizer(tok)
    text = "héllo ✓ wörld"
    ids = tok.encode(text)[1:]  # drop BOS
    got = ""
    for i in ids:  # push byte-by-byte: multi-byte chars must be held back
        got += detok.push([i])
    assert got == text
    assert detok.text == text


def test_long_context_prefill_through_flash_path():
    """A prompt long enough that prefill attention takes the chunked
    online-softmax path (S > FLASH_CHUNK) must still generate correctly and
    match the same engine re-run (determinism through the flash path)."""
    import numpy as np

    from vllm_production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.request import SamplingParams
    from vllm_production_stack_tpu.ops.attention import FLASH_CHUNK

    cfg = ModelConfig.tiny(max_model_len=8192)

    def run():
        engine = LLMEngine(EngineConfig(
            model=cfg,
            cache=CacheConfig(block_size=8, num_blocks=1200),
            scheduler=SchedulerConfig(
                max_num_seqs=1, max_num_batched_tokens=2048,
                decode_buckets=(1,), prefill_buckets=(2048,),
                decode_window=4,
            ),
        ))
        # FLASH_CHUNK + a bit: enough to take the flash path (and its padding
        # branch) while keeping the chunked-prefill compile count low
        prompt = list(
            np.random.RandomState(0).randint(1, 500, size=FLASH_CHUNK + 300)
        )
        return engine.generate(
            [prompt],
            SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True),
        )[0]["token_ids"]

    out1 = run()
    assert len(out1) == 4
    assert run() == out1


def test_warmup_compiles_bucket_set():
    """engine.warmup() runs every prefill/decode bucket program; subsequent
    traffic reuses them (no mid-serving compile stalls)."""
    from vllm_production_stack_tpu.engine.config import EngineConfig
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.request import SamplingParams

    from vllm_production_stack_tpu.engine.config import (
        CacheConfig, ModelConfig, SchedulerConfig,
    )

    # minimal bucket sets: each warmup wave compiles programs, and this
    # test only needs to prove the passes run and drain
    engine = LLMEngine(EngineConfig(
        model=ModelConfig.tiny(),
        cache=CacheConfig(block_size=8, num_blocks=64),
        scheduler=SchedulerConfig(
            max_num_seqs=2, max_num_batched_tokens=16,
            decode_buckets=(2,), prefill_buckets=(16,), decode_window=2,
        ),
    ))
    warmed = engine.warmup()
    assert warmed > 0
    assert not engine.has_unfinished()  # warmup drains fully
    out = engine.generate(
        [[5, 6, 7]],
        SamplingParams(max_tokens=3, temperature=0.0, ignore_eos=True),
    )
    assert len(out[0]["token_ids"]) == 3


def test_midblock_chunked_prefill_matches_unchunked():
    """Chunk sizes that are NOT multiples of the block size force every
    continuation chunk to start mid-block — the blockwise KV commit
    (ops/attention.py:write_kv_pages_blockwise) must merge, not clobber, the
    earlier chunk's tokens in the shared page."""
    from vllm_production_stack_tpu.engine.config import (
        CacheConfig, ModelConfig, SchedulerConfig,
    )

    cfg = ModelConfig.tiny()

    def build(chunk):
        return LLMEngine(
            EngineConfig(
                model=cfg,
                cache=CacheConfig(block_size=8, num_blocks=64),
                scheduler=SchedulerConfig(
                    max_num_seqs=2, max_num_batched_tokens=chunk,
                    decode_buckets=(2,), prefill_buckets=(chunk,),
                    decode_window=4,
                ),
            )
        )

    prompts = [prompt_ids(40 + i, 29 + 5 * i) for i in range(2)]
    greedy = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
    # chunk 12 with block 8: chunks start at offsets 12, 24, ... (mid-block)
    chunked = [r["token_ids"] for r in build(12).generate(prompts, greedy)]
    whole = [r["token_ids"] for r in build(64).generate(prompts, greedy)]
    assert chunked == whole


def test_min_tokens_suppresses_stop(engine):
    """min_tokens holds off eos/stop-token finishes (vLLM extension): with
    the first greedy token as a stop id, min_tokens forces generation past
    it; without min_tokens it stops immediately."""
    prompt = prompt_ids(77, 9)
    probe = engine.generate(
        [prompt], SamplingParams(max_tokens=1, temperature=0.0,
                                 ignore_eos=True)
    )[0]["token_ids"][0]
    stopped = engine.generate(
        [prompt],
        SamplingParams(max_tokens=8, temperature=0.0,
                       stop_token_ids=[probe]),
    )[0]
    assert len(stopped["token_ids"]) == 1
    held = engine.generate(
        [prompt],
        SamplingParams(max_tokens=8, temperature=0.0,
                       stop_token_ids=[probe], min_tokens=4),
    )[0]
    assert len(held["token_ids"]) >= 4
    # vLLM semantics: below min_tokens the stop token is masked out of the
    # DISTRIBUTION, not accepted-then-ignored — it never appears early
    assert probe not in held["token_ids"][:4]


def test_width_floor_blocks_config():
    """The context-width program ladder floors at width_floor_blocks
    (default 64 — serving must not compile a program per short-context
    width); benches set 1 for true-width gathers."""
    from vllm_production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from vllm_production_stack_tpu.engine.model_runner import ModelRunner

    def runner(floor):
        return ModelRunner(EngineConfig(
            model=ModelConfig.tiny(max_model_len=2048),
            cache=CacheConfig(block_size=8, num_blocks=512),
            scheduler=SchedulerConfig(
                max_num_seqs=2, max_num_batched_tokens=64,
                decode_buckets=(2,), prefill_buckets=(64,),
                width_floor_blocks=floor,
            ),
        ))

    tables = [[1, 2, 3]]  # longest = 3 blocks
    assert runner(64)._block_table_array(tables).shape[1] == 64  # floored
    assert runner(1)._block_table_array(tables).shape[1] == 4  # true pow2
    # the ladder still grows past the floor and caps at max_blocks (256)
    wide = [list(range(1, 201))]  # 200 blocks
    assert runner(64)._block_table_array(wide).shape[1] == 256


def test_compile_fallback_pads_up_to_warm_program():
    """A first-seen (rows x chunk x width) program key must NOT compile on
    the hot path when a compiled program dominates it: the runner pads up
    (identical results) and backgrounds the exact compile — the structural
    fix for the live-serving compile-stall collapse."""
    import numpy as np

    from vllm_production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.request import SamplingParams

    cfg = ModelConfig.tiny()
    base = EngineConfig(
        model=cfg,
        cache=CacheConfig(block_size=8, num_blocks=128),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=128,
            decode_buckets=(2, 4), prefill_buckets=(32, 128),
            decode_window=4, width_floor_blocks=1,
        ),
    )
    prompts = [
        list(np.random.RandomState(i).randint(1, cfg.vocab_size, size=9))
        for i in range(1)
    ]
    sampling = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)

    control = LLMEngine(base)
    want = [o["token_ids"] for o in control.generate(prompts, sampling)]

    engine = LLMEngine(base)
    # warm ONE coarse program: full batch, big chunk, wide tables
    warm_prompts = [
        list(np.random.RandomState(50 + i).randint(
            1, cfg.vocab_size, size=100
        ))
        for i in range(4)
    ]
    engine.generate(warm_prompts, sampling)
    warmed_keys = set(engine.runner._compiled_keys)
    assert any(k[0] == "prefill" for k in warmed_keys)
    before = engine.runner.compile_fallbacks

    # a small request whose exact key was never compiled: must pad up to
    # the warm coarse program, not compile a new one synchronously
    got = [o["token_ids"] for o in engine.generate(prompts, sampling)]
    assert got == want
    assert engine.runner.compile_fallbacks > before
    # and the exact programs eventually land via the background thread
    ex = engine.runner._bg_executor
    if ex is not None:
        ex.shutdown(wait=True)
    assert engine.runner.bg_compiles > 0
    # once background-compiled, the same request dispatches the exact
    # (AOT) program with no fallback and identical output
    engine.scheduler.pool.clear_prefix_cache()
    before = engine.runner.compile_fallbacks
    got2 = [o["token_ids"] for o in engine.generate(prompts, sampling)]
    assert got2 == want
    assert any(k in engine.runner._aot_exec for k in
               engine.runner._compiled_keys)


def test_coarse_warmup_precompiles_dominating_lattice():
    """warmup(scope='coarse') AOT-compiles the dominating programs without
    generating tokens — afterwards EVERY runtime shape has a fallback, even
    widths the pool could never physically reach with real requests."""
    import numpy as np

    from vllm_production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from vllm_production_stack_tpu.engine.engine import LLMEngine
    from vllm_production_stack_tpu.engine.request import SamplingParams

    cfg = ModelConfig.tiny()
    engine = LLMEngine(EngineConfig(
        model=cfg,
        # pool far smaller than max_num_seqs * max_model_len: the
        # generate-based coarse pass could never reach the top width
        cache=CacheConfig(block_size=8, num_blocks=24),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=64,
            decode_buckets=(2, 4), prefill_buckets=(32, 64),
            decode_window=4, width_floor_blocks=1,
        ),
    ))
    n = engine.warmup(scope="coarse")
    assert n > 0
    keys = set(engine.runner._compiled_keys)
    top_w = engine.runner._width_bucket(engine.runner.max_blocks)
    b_top = engine.runner._batch_bucket(4)
    # every chunk bucket exists at full batch and TOP width
    for t in (32, 64):
        assert ("prefill", b_top, t, top_w, False, False, False) in keys
    # every pow2 window exists at the top decode bucket and TOP width
    for w in (1, 2, 4):
        assert ("decode", 4, top_w, w, False, False, None) in keys
    assert engine.scheduler.pool.stats.queries == 0  # no tokens generated
    # zero generation happened; pool is untouched and serving works
    before = engine.runner.compile_fallbacks
    out = engine.generate(
        [list(np.random.RandomState(1).randint(1, cfg.vocab_size, size=12))],
        SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True),
    )
    assert len(out[0]["token_ids"]) == 4
    assert engine.runner.compile_fallbacks > before  # padded up, no stall
