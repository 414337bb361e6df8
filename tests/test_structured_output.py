"""Structured-output serving end-to-end (docs/41-structured-output.md):
grammar-constrained decode on the CPU mesh — always-valid output, bitwise
serial<->pipelined equivalence under constraints, composition with
speculative decoding (exact ledger partition) and QoS preemption, the
OpenAI surface (response_format / guided_json / forced tool_choice with
the 400-vs-fallback modes), and the fake engine's schema echo."""

import asyncio
import dataclasses
import json

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from vllm_production_stack_tpu.engine.config import (
    CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
)
from vllm_production_stack_tpu.engine.engine import LLMEngine
from vllm_production_stack_tpu.engine.grammar import GrammarCache, GrammarState
from vllm_production_stack_tpu.engine.request import SamplingParams
from vllm_production_stack_tpu.testing.serial_loop import serial_engine

# enum/boolean-heavy: a RANDOM model's constrained walk terminates fast
# (no open-ended strings or unbounded digit runs to wander in)
SCHEMA = {
    "type": "object",
    "properties": {
        "ok": {"type": "boolean"},
        "mode": {"enum": ["fast", "slow"]},
        "n": {"enum": [1, 2, 3]},
    },
}
SPEC = {"kind": "json_schema", "schema": SCHEMA}


def _build(async_on=True, spec_k=0):
    # minimal bucket ladders: every extra bucket is another background
    # XLA compile the engine's shutdown(wait=True) has to wait out
    return (LLMEngine if async_on else serial_engine)(EngineConfig(
        model=ModelConfig.tiny(),
        cache=CacheConfig(block_size=8, num_blocks=64),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=32,
            decode_buckets=(4,), prefill_buckets=(16, 32),
            decode_window=4, num_speculative_tokens=spec_k,
        ),
    ))


def _shutdown(*engines):
    for e in engines:
        e.runner.shutdown(wait=True)


def _grammar(engine):
    return engine.grammar_cache.get(SPEC)[0]


def _sp(grammar, max_tokens=48):
    return SamplingParams(
        max_tokens=max_tokens, temperature=0.0, grammar=grammar
    )


def _prompts(n):
    return [
        list(np.random.RandomState(i).randint(1, 250, size=6 + i))
        for i in range(n)
    ]


def _assert_valid(outs, grammar):
    for o in outs:
        json.loads(o["text"])
        st = GrammarState(grammar)
        st.sync(o["token_ids"])
        assert st.accepting


@pytest.fixture(scope="module")
def pipe():
    eng = _build(async_on=True)
    yield eng
    _shutdown(eng)


@pytest.fixture(scope="module")
def serial():
    eng = _build(async_on=False)
    yield eng
    _shutdown(eng)


@pytest.fixture(scope="module")
def pipe_ref(pipe):
    """The pipelined engine's constrained outputs for _prompts(3) — the
    shared reference several tests compare against (one generate, not
    one per test)."""
    return pipe.generate(_prompts(3), _sp(_grammar(pipe)))


def test_constrained_decode_always_valid_and_counted(pipe, pipe_ref):
    g = _grammar(pipe)
    outs = pipe_ref
    _assert_valid(outs, g)
    snap = pipe.stats()
    assert snap.structured_outcomes["valid"] >= 3
    assert snap.structured_outcomes["invalid"] == 0
    # build time drained into the snapshot exactly once
    assert len(snap.grammar_build_times) == 1
    assert pipe.stats().grammar_build_times == []


def test_unconstrained_baseline_is_not_valid(pipe):
    """The control: without the grammar the random tiny model essentially
    never emits schema-valid JSON — what makes the valid-rate-1.0
    assertion above meaningful."""
    outs = pipe.generate(
        _prompts(2), SamplingParams(max_tokens=32, temperature=0.0)
    )
    ok = 0
    for o in outs:
        try:
            json.loads(o["text"])
            ok += 1
        except (ValueError, UnicodeDecodeError):
            pass
    assert ok < len(outs)


def test_serial_pipelined_bitwise_equivalence_under_constraints(serial, pipe_ref):
    b = serial.generate(_prompts(3), _sp(_grammar(serial)))
    assert [o["token_ids"] for o in pipe_ref] == [o["token_ids"] for o in b]


def test_mixed_batch_constrained_and_free(pipe):
    """Constrained and unconstrained rows share one batch; the mask is
    per-row data (all-True for free rows), so the free row's stream must
    match its solo run exactly."""
    g = _grammar(pipe)
    free_sp = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    free_prompt = list(np.random.RandomState(99).randint(1, 250, size=7))
    solo = pipe.generate([free_prompt], free_sp)[0]["token_ids"]
    ids = [
        pipe.add_request(prompt_token_ids=_prompts(1)[0], sampling=_sp(g)),
        pipe.add_request(prompt_token_ids=free_prompt, sampling=free_sp),
    ]
    got = {i: [] for i in ids}
    texts = {i: "" for i in ids}
    while pipe.has_unfinished():
        for out in pipe.step():
            got[out.request_id].extend(out.new_token_ids)
            texts[out.request_id] += out.text_delta
    assert got[ids[1]] == solo
    json.loads(texts[ids[0]])


def test_spec_decode_constrained_bitwise_and_ledger_exact(pipe_ref):
    """Grammar + speculative decoding: a grammar-violating draft token is
    just another rejected position — streams stay bitwise identical to the
    non-speculative engine, and the goodput ledger partition stays exact
    (rejections = wasted{rollback})."""
    eng = _build(async_on=True, spec_k=3)
    try:
        g = _grammar(eng)
        ref = pipe_ref
        outs = eng.generate(_prompts(3), _sp(g))
        assert [o["token_ids"] for o in outs] == [
            o["token_ids"] for o in ref
        ]
        _assert_valid(outs, g)
        bal = eng.goodput_balance()
        assert bal["balanced"] and bal["pending"] == 0
    finally:
        _shutdown(eng)


def test_preempt_resume_mid_constrained_decode(serial):
    """QoS preemption mid-constrained-decode: the automaton cursor rides
    output_token_ids (sync() replays on resume), so the re-admitted
    request finishes with the exact same valid stream."""
    eng = serial
    g = _grammar(eng)
    prompt = _prompts(1)[0]
    ref = eng.generate([prompt], _sp(g))[0]
    rid = eng.add_request(prompt_token_ids=prompt, sampling=_sp(g))
    got, text, preempted = [], "", False
    while eng.has_unfinished():
        for out in eng.step():
            got.extend(out.new_token_ids)
            text += out.text_delta
        if not preempted and 0 < len(got) < len(ref["token_ids"]):
            victim = next(
                (r for r in eng.scheduler.running
                 if r.request_id == rid and r.prefill_done), None,
            )
            if victim is not None:
                eng.scheduler._preempt(victim)
                preempted = True
    assert preempted
    assert got == ref["token_ids"]
    json.loads(text)


def test_gkey_dominance_rules():
    from vllm_production_stack_tpu.engine.model_runner import ModelRunner

    dom = ModelRunner._gkey_dominates
    assert dom(None, None)
    assert not dom(None, (4, 64, 32))  # no-grammar program can't serve one
    assert not dom((4, 64, 32), None)  # output structures differ
    assert dom((4, 64, 32), (4, 64, 32))
    assert dom((8, 128, 32), (4, 64, 32))  # tables pad up
    assert not dom((4, 32, 32), (4, 64, 32))


def test_grammar_device_tables_cached_once(pipe):
    """The padded device tables are built once per (grammar set, pads) —
    repeat constrained traffic reuses both the tables and the compiled
    program (the mask is data, never a program shape)."""
    g = _grammar(pipe)
    before = dict(pipe.runner._grammar_tables_cache)
    pipe.generate(_prompts(2), _sp(g))
    after = dict(pipe.runner._grammar_tables_cache)
    assert len(after) >= 1
    pipe.generate(_prompts(2), _sp(g))
    assert pipe.runner._grammar_tables_cache.keys() == after.keys()
    assert len(after) <= len(before) + 1


# -- OpenAI surface ----------------------------------------------------------


@pytest.fixture(scope="module")
def srv():
    from vllm_production_stack_tpu.engine.server import EngineServer

    # one 768-context engine serves every HTTP test here, including forced
    # tool_choice (the tool-steering preamble alone outgrows tiny's
    # 256-token context); two prefill buckets — plain chats pad to 64,
    # tool-steered prompts to 512
    eng = LLMEngine(EngineConfig(
        model=ModelConfig.tiny(max_model_len=768),
        cache=CacheConfig(block_size=8, num_blocks=128),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=512,
            decode_buckets=(4,), prefill_buckets=(64, 512),
        ),
    ))
    yield EngineServer(eng, served_model_name="tiny-llama")
    _shutdown(eng)


def run_with_client(srv, coro_fn):
    async def runner():
        client = TestClient(TestServer(srv.build_app()))
        await client.start_server()
        try:
            return await coro_fn(client)
        finally:
            await client.close()

    return asyncio.run(runner())


def test_http_guided_json_yields_valid_body(srv):
    async def go(client):
        r = await client.post("/v1/chat/completions", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "emit json"}],
            "max_tokens": 64, "temperature": 0.0,
            "guided_json": SCHEMA,
        })
        return r.status, await r.json()

    status, body = run_with_client(srv, go)
    assert status == 200
    doc = json.loads(body["choices"][0]["message"]["content"])
    assert set(doc) <= {"ok", "mode", "n"}
    assert body["choices"][0]["finish_reason"] == "stop"


def test_http_response_format_streaming_valid(srv):
    async def go(client):
        r = await client.post("/v1/chat/completions", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "emit json"}],
            "max_tokens": 64, "temperature": 0.0, "stream": True,
            "response_format": {
                "type": "json_schema",
                "json_schema": {"name": "t", "schema": SCHEMA},
            },
        })
        assert r.status == 200
        text = ""
        async for line in r.content:
            line = line.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            chunk = json.loads(line[len("data: "):])
            for c in chunk.get("choices", []):
                text += c.get("delta", {}).get("content") or ""
        return text

    text = run_with_client(srv, go)
    json.loads(text)


def test_http_malformed_schema_400_never_500(srv):
    async def go(client):
        results = []
        for schema in (
            {"type": "string", "pattern": "a+"},
            {"enum": []},
            {"enum": list(range(10_000))},
        ):
            r = await client.post("/v1/chat/completions", json={
                "model": "tiny-llama",
                "messages": [{"role": "user", "content": "x"}],
                "max_tokens": 8, "guided_json": schema,
            })
            results.append((r.status, await r.json()))
        r = await client.post("/v1/chat/completions", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "x"}],
            "max_tokens": 8,
            "response_format": {"type": "grammar_xml"},
        })
        results.append((r.status, await r.json()))
        return results

    for status, body in run_with_client(srv, go):
        assert status == 400
        assert "structured output" in body["message"]
    snap = srv.engine.stats()
    assert snap.structured_outcomes["invalid"] >= 4


def test_http_forced_tool_choice_always_parses(srv):
    async def go(client):
        r = await client.post("/v1/chat/completions", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "call the tool"}],
            "max_tokens": 96, "temperature": 0.0,
            "tools": [{"type": "function", "function": {
                "name": "set_mode",
                "parameters": {"type": "object", "properties": {
                    "mode": {"enum": ["fast", "slow"]},
                }},
            }}],
            "tool_choice": "required",
        })
        return r.status, await r.json()

    status, body = run_with_client(srv, go)
    assert status == 200
    msg = body["choices"][0]["message"]
    assert body["choices"][0]["finish_reason"] == "tool_calls"
    calls = msg["tool_calls"]
    assert len(calls) == 1 and calls[0]["function"]["name"] == "set_mode"
    json.loads(calls[0]["function"]["arguments"])


def test_http_fallback_mode_decodes_unconstrained():
    from vllm_production_stack_tpu.engine.server import EngineServer

    eng = LLMEngine(EngineConfig(
        model=ModelConfig.tiny(),
        cache=CacheConfig(block_size=8, num_blocks=64),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=64,
            decode_buckets=(4,), prefill_buckets=(64,),
        ),
        structured_output="fallback",
    ))
    srv = EngineServer(eng, served_model_name="tiny-llama")
    try:
        async def go(client):
            # compiles fine -> still constrained even in fallback mode
            ok = await client.post("/v1/chat/completions", json={
                "model": "tiny-llama",
                "messages": [{"role": "user", "content": "x"}],
                "max_tokens": 48, "temperature": 0.0,
                "guided_json": SCHEMA,
            })
            # uncompilable -> decodes free-form instead of 400
            fb = await client.post("/v1/chat/completions", json={
                "model": "tiny-llama",
                "messages": [{"role": "user", "content": "x"}],
                "max_tokens": 8, "temperature": 0.0,
                "guided_json": {"type": "string", "pattern": "a+"},
            })
            return (ok.status, await ok.json()), (fb.status, await fb.json())

        (s1, b1), (s2, b2) = run_with_client(srv, go)
        assert s1 == 200
        json.loads(b1["choices"][0]["message"]["content"])
        assert s2 == 200
        snap = eng.stats()
        assert snap.structured_outcomes["fallback"] == 1
        assert snap.structured_outcomes["valid"] >= 1
    finally:
        _shutdown(eng)


# -- fake engine (router test rig) -------------------------------------------


def test_fake_engine_echoes_schema_valid_body():
    from vllm_production_stack_tpu.testing.fake_engine import FakeEngine

    text = FakeEngine._structured_text({"guided_json": SCHEMA})
    doc = json.loads(text)
    assert set(doc) <= {"ok", "mode", "n"}
    rf = {"type": "json_schema", "json_schema": {"schema": SCHEMA}}
    json.loads(FakeEngine._structured_text({"response_format": rf}))
    assert FakeEngine._structured_text({}) is None
    # malformed surfaces degrade to the free-form filler, never raise
    assert FakeEngine._structured_text(
        {"response_format": {"type": "grammar_xml"}}
    ) is None


# -- router validation -------------------------------------------------------


def test_router_check_structured_400s_uncompilable():
    from vllm_production_stack_tpu.router.request_service import RequestService

    async def go():
        bad = await RequestService._check_structured(
            "/v1/chat/completions",
            {"guided_json": {"type": "string", "pattern": "a+"}},
        )
        ok = await RequestService._check_structured(
            "/v1/chat/completions", {"guided_json": SCHEMA},
        )
        free = await RequestService._check_structured(
            "/v1/chat/completions", {"messages": []},
        )
        other_path = await RequestService._check_structured(
            "/v1/embeddings", {"guided_json": {"enum": []}},
        )
        tool = await RequestService._check_structured(
            "/v1/chat/completions",
            {"tools": [{"function": {"name": "f"}}],
             "tool_choice": {"type": "function",
                             "function": {"name": "absent"}}},
        )
        return bad, ok, free, other_path, tool

    bad, ok, free, other_path, tool = asyncio.run(go())
    assert bad is not None and bad.status == 400
    assert ok is None and free is None and other_path is None
    assert tool is not None and tool.status == 400
