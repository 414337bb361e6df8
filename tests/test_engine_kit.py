"""tests/engine_kit.py on `tiny-llama`: a second checkout of one
configuration compiles nothing, an engine left undrained fails the next test
that asks for it, by the name of the one that left it, and an engine that is
handed the module's parameters serves the tokens of one that drew its own."""

import jax
import numpy as np
import pytest
from jax._src import monitoring

import engine_kit as ek
from engine_kit import EngineKit
from vllm_production_stack_tpu.engine.config import ModelConfig
from vllm_production_stack_tpu.engine.engine import LLMEngine

CONFIG = ek.engine_config(ModelConfig.tiny())
PROMPTS = [[5, 6, 7, 8, 9], list(range(20, 60))]


@pytest.fixture
def compiles():
    """The names of what XLA compiled since the test began: `jit(build)` is
    a runner's parameter draw, `jit(step_fn)` and `jit(decode_window_fn)` its
    step programs."""
    seen = []

    def listen(event, duration, fun_name=None, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(fun_name)

    monitoring.register_event_duration_secs_listener(listen)
    yield seen
    monitoring.unregister_event_duration_listener(listen)


def test_a_second_checkout_compiles_nothing(kit, compiles):
    def misses(engine):  # dispatches that had to compile their program
        return engine.runner.compile_watch.debug_payload()["cache"]["misses"]

    engine, _ = kit.engine(CONFIG)
    want = ek.generate(engine, PROMPTS)
    assert {"jit(build)", "jit(step_fn)", "jit(decode_window_fn)"} <= set(
        compiles)
    paid = misses(engine)
    del compiles[:]
    again, before = kit.engine(ek.engine_config(ModelConfig.tiny()))
    assert again is engine
    assert ek.generate(again, PROMPTS) == want
    assert misses(again) == paid > 0
    assert not [name for name in compiles if name != "jit(<lambda>)"]
    # ... and the snapshot counts what THIS checkout's requests did
    counted = ek.since(again, before)["decode_tokens"]
    assert 0 < counted == again.flightrec.timing()["decode_tokens"] / 2


def test_an_undrained_engine_fails_the_next_checkout_by_name(kit):
    engine, _ = kit.engine(CONFIG)
    rid = engine.add_request(prompt_token_ids=PROMPTS[1],
                             sampling=ek.greedy(50))
    for _ in range(3):
        engine.step()
    with pytest.raises(AssertionError) as err:
        kit.engine(CONFIG)
    said = str(err.value)
    assert "test_an_undrained_engine_fails_the_next_checkout_by_name" in said
    assert "1 running" in said and "of the full group's pages" in said
    ek.finish(engine, rid)
    kit.engine(CONFIG)  # drained again: handed out


def test_a_tap_or_a_patched_constant_is_refused(kit, monkeypatch):
    from vllm_production_stack_tpu.models import llama

    monkeypatch.setattr(llama, "_ALL_EXPERTS_MAX_TOKENS", 8)
    with pytest.raises(AssertionError, match="kit.fresh_engine"):
        kit.engine(CONFIG)


def test_handed_parameters_serve_the_drawn_ones_tokens(compiles):
    drew = LLMEngine(CONFIG)  # beside any kit: it draws its own
    want = ek.generate(drew, PROMPTS)
    kit = EngineKit()  # (its own: what the module's has drawn is its order's)
    kit.fresh_engine(CONFIG)  # a module's first draws, as ever
    kit.fresh_engine(CONFIG)  # the first reuse draws too, and compares
    assert compiles.count("jit(build)") == 3
    handed = kit.fresh_engine(CONFIG)
    assert compiles.count("jit(build)") == 3  # the tree came with the call
    for a, b in zip(jax.tree.leaves(handed.runner.params),
                    jax.tree.leaves(drew.runner.params)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert ek.generate(handed, PROMPTS) == want
    kit.close()
    # a tree that is not the seed's draw is told on the first reuse
    other = EngineKit()
    other._params[CONFIG.model, CONFIG.seed] = jax.tree.map(
        lambda a: a + 1, drew.runner.params)
    with pytest.raises(AssertionError):
        other.fresh_engine(CONFIG)
    other.close()
