"""The pipelined step loop (engine/engine.py LLMEngine.step).

The two-deep dispatch/resolve pipeline must emit BITWISE-identical token
streams to the serial reference loop (testing/serial_loop.py) — greedy and
seeded sampled decode, mid-window stop tokens, max-tokens truncation, and
abort_request landing while a step is in flight — and the decode hot path
must pay exactly ONE host sync (jax.device_get) per resolved step."""

import jax
import numpy as np
import pytest

import engine_kit as ek
from vllm_production_stack_tpu.engine.config import EngineConfig
from vllm_production_stack_tpu.engine.engine import LLMEngine
from vllm_production_stack_tpu.engine.request import SamplingParams
from vllm_production_stack_tpu.testing.serial_loop import serial_engine


@pytest.fixture(scope="module")
def pipe():
    engine = LLMEngine(EngineConfig.tiny())
    yield engine
    # cancel queued background compiles: leaked compile threads steal CPU
    # from whatever module runs next
    engine.runner.shutdown(wait=True)


@pytest.fixture(scope="module")
def serial():
    engine = serial_engine(EngineConfig.tiny())
    yield engine
    engine.runner.shutdown(wait=True)


def prompt_ids(seed, n):
    return list(np.random.RandomState(seed).randint(1, 500, size=n))


PROMPTS = [prompt_ids(1, 5), prompt_ids(2, 9), prompt_ids(3, 12)]


def streams(eng, prompts, sp):
    return [o["token_ids"] for o in eng.generate(prompts, sp)]


def test_engine_has_no_loop_option(pipe, serial):
    """The pipelined loop is the only one an engine can be started with:
    no config field, no flag. The serial reference is a test helper that
    replaces `step` on one instance."""
    import dataclasses

    from vllm_production_stack_tpu.engine.server import build_parser

    assert "async_scheduling" not in {
        f.name for f in dataclasses.fields(EngineConfig)
    }
    with pytest.raises(SystemExit):
        build_parser().parse_args(["--async-scheduling", "false"])
    assert "step" not in vars(pipe) and "step" in vars(serial)


def test_greedy_equivalence(pipe, serial):
    sp = SamplingParams(max_tokens=21, temperature=0.0, ignore_eos=True)
    assert streams(pipe, PROMPTS, sp) == streams(serial, PROMPTS, sp)
    # the pipeline actually ran: decode windows resolved, host work
    # overlapped in-flight device steps
    assert pipe.timing["decode_n"] > 0
    assert pipe.timing["overlap_s"] > 0


def test_seeded_sampling_equivalence(pipe, serial):
    sp = SamplingParams(
        max_tokens=18, temperature=0.9, top_p=0.9, seed=1234, ignore_eos=True
    )
    assert streams(pipe, PROMPTS, sp) == streams(serial, PROMPTS, sp)


def test_mid_window_stop_token_equivalence_and_rollback(pipe, serial):
    greedy = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True)
    # a stop token landing inside a decode window is the speculation
    # failure mode: the already-dispatched next window must be discarded.
    # Tiny-random-weight greedy streams can degenerate into one repeated
    # token (whose first occurrence is the prefill token — no mid-window
    # stop), so scan prompts for a usable stream.
    prompt = stop_at = None
    for seed in range(1, 16):
        p = prompt_ids(seed, 7)
        ref = streams(serial, [p], greedy)[0]
        cand = [t for t in ref[3:] if ref.index(t) >= 1]
        if cand:
            prompt, stop_at = p, cand[0]
            break
    assert prompt is not None, "no non-degenerate greedy stream found"
    sp = SamplingParams(
        max_tokens=24, temperature=0.0, stop_token_ids=(stop_at,)
    )
    before = pipe.timing["rollback_n"]
    got = streams(pipe, [prompt], sp)[0]
    want = streams(serial, [prompt], sp)[0]
    assert got == want
    assert got[-1] == stop_at and len(got) < 24
    assert pipe.timing["rollback_n"] > before  # speculative step discarded


def test_max_tokens_truncation_equivalence(pipe, serial):
    # mixed budgets: the short row finishes by length mid-window while the
    # long row keeps decoding — its stream must be unaffected
    out = {}
    for eng in (pipe, serial):
        a = eng.add_request(
            prompt_token_ids=PROMPTS[0],
            sampling=SamplingParams(
                max_tokens=3, temperature=0.0, ignore_eos=True
            ),
        )
        b = eng.add_request(
            prompt_token_ids=PROMPTS[1],
            sampling=SamplingParams(
                max_tokens=17, temperature=0.0, ignore_eos=True
            ),
        )
        got = {a: [], b: []}
        while eng.has_unfinished():
            for o in eng.step():
                got[o.request_id].extend(o.new_token_ids)
        out[eng is pipe] = (got[a], got[b])
    assert out[True] == out[False]
    assert len(out[True][0]) == 3 and len(out[True][1]) == 17


def test_abort_while_step_in_flight(pipe, serial):
    sp = SamplingParams(max_tokens=20, temperature=0.0, ignore_eos=True)
    ref = streams(serial, [PROMPTS[0], PROMPTS[1]], sp)
    a = pipe.add_request(prompt_token_ids=PROMPTS[0], sampling=sp)
    b = pipe.add_request(prompt_token_ids=PROMPTS[1], sampling=sp)
    got = {a: [], b: []}
    aborted = False
    while pipe.has_unfinished():
        outs = pipe.step()
        if not aborted and pipe._inflight is not None:
            assert pipe.abort_request(a)
            aborted = True
        for o in outs:
            got[o.request_id].extend(o.new_token_ids)
    assert aborted
    # the survivor's stream is untouched; the aborted stream is a strict
    # prefix of its no-abort reference
    assert got[b] == ref[1]
    assert len(got[a]) < 20
    assert ref[0][: len(got[a])] == got[a]
    assert pipe._inflight is None


def test_decode_hot_path_single_host_sync(pipe, monkeypatch):
    """Acceptance: exactly one jax.device_get per RESOLVED decode step on
    the pipelined hot path (the chained dispatch itself performs none)."""
    import jax as _jax

    import vllm_production_stack_tpu.engine.model_runner as mr

    sp = SamplingParams(max_tokens=40, temperature=0.0, ignore_eos=True)
    pipe.add_request(prompt_token_ids=prompt_ids(9, 6), sampling=sp)
    pipe.step()  # the prefill chunk, kept in flight
    pipe.step()  # first window enqueued behind it, the chunk resolved
    calls = []
    real = _jax.device_get

    def counting_get(x):
        calls.append(1)
        return real(x)

    monkeypatch.setattr(mr.jax, "device_get", counting_get)
    n0 = pipe.timing["decode_n"]
    while pipe.has_unfinished():
        pipe.step()
    monkeypatch.undo()
    resolved = pipe.timing["decode_n"] - n0
    assert resolved >= 3
    assert len(calls) == resolved, (len(calls), resolved)


def test_timing_keys_lockstep_with_metrics_contract(pipe):
    """Guard: the step-loop timing decomposition (/debug/timing)
    and the engine→router metric contract stay in lockstep with what the
    engine actually exports."""
    from vllm_production_stack_tpu import metrics_contract as mc
    from vllm_production_stack_tpu.engine.metrics import EngineMetrics

    expected = {
        "sched_s", "post_s",
        "prefill_s", "prefill_n", "prefill_tokens",
        "decode_s", "decode_n", "decode_tokens",
        "dispatch_s", "sync_s", "overlap_s", "step_wall_s", "rollback_n",
    }
    assert expected <= set(pipe.timing), sorted(expected - set(pipe.timing))
    snap = pipe.stats()
    assert 0.0 <= snap.step_overlap_frac <= 1.0
    assert mc.STEP_OVERLAP_FRAC in mc.ALL_GAUGES
    text = EngineMetrics("tiny-llama").render(snap).decode()
    for name in (*mc.ALL_GAUGES, *mc.ALL_COUNTERS):
        base = name[: -len("_total")] if name.endswith("_total") else name
        assert base in text, f"contract metric {name} missing from exporter"


def test_multi_tenant_mix_equivalence(pipe, serial):
    """Weighted fair-share admission (docs/27-multitenancy.md) is part of
    the scheduler state both loops share — per-request streams must stay
    BITWISE identical between the serial and pipelined loops under a
    multi-tenant mix of priorities and weights, including a seat
    preemption triggered by the realtime arrival."""
    from vllm_production_stack_tpu.qos import TenantContext

    mix = [
        (PROMPTS[0], TenantContext("bulk", priority=2, weight=1.0)),
        (PROMPTS[1], TenantContext("acme", priority=0, weight=3.0)),
        (PROMPTS[2], TenantContext("bulk", priority=2, weight=1.0)),
        (prompt_ids(4, 7), TenantContext("std", priority=1, weight=2.0)),
        (prompt_ids(5, 6), TenantContext()),  # unstamped default traffic
    ]
    sp = SamplingParams(max_tokens=15, temperature=0.0, ignore_eos=True)
    out = {}
    for eng in (pipe, serial):
        rids = [
            eng.add_request(prompt_token_ids=p, sampling=sp, tenant=t)
            for p, t in mix
        ]
        got = {rid: [] for rid in rids}
        while eng.has_unfinished():
            for o in eng.step():
                got[o.request_id].extend(o.new_token_ids)
        out[eng is pipe] = [got[rid] for rid in rids]
    assert out[True] == out[False]
    assert all(len(s) == 15 for s in out[True])  # everyone ran to budget


# -- a prefill chunk is an in-flight step (docs/37 "The step timeline") ------
#
# A chunk stays in flight over a call like a window; the window enqueued
# behind it takes the first tokens of the rows whose prompt it ends from the
# chunk's device output. Arrivals are scripted by step() CALL index, so both
# loops see the same requests at the same point of their dispatch sequence.


def drive(eng, arrivals, hook=None):
    """step() until everything finished. `arrivals`: {call index: [(rid,
    prompt, sampling[, tenant])]}; `hook(eng, i)` runs after call i.
    Returns {rid: token stream}."""
    got, i = {}, 0
    while i <= max(arrivals) or eng.has_unfinished():
        for rid, prompt, sp, *tenant in arrivals.get(i, ()):
            eng.add_request(rid, prompt_token_ids=prompt, sampling=sp,
                            tenant=tenant[0] if tenant else None)
            got[rid] = []
        for o in eng.step():
            got[o.request_id].extend(o.new_token_ids)
        if hook is not None:
            hook(eng, i)
        i += 1
        assert i < 2000, "the loop does not drain"
    return got


def dispatch_order(eng, after_seq):
    """(kind, rows) of every dispatch past `after_seq` that was not rolled
    back: the sequence whose RNG keys the token streams were drawn with."""
    records = eng.flightrec.snapshot()
    gone = {r["seq"] for r in records if r["event"] == "rollback"}
    return [
        (r["kind"], r["rows"]) for r in records
        if r["event"] == "dispatch" and r["seq"] > after_seq
        and r["seq"] not in gone
    ]


def _last_seq(eng):
    return max((r["seq"] for r in eng.flightrec.snapshot()
                if r["event"] == "dispatch"), default=0)


def _pool_is_whole(eng):
    pool = eng.scheduler.pool
    assert eng._inflight is None
    assert not eng.scheduler.running and not eng.scheduler.waiting
    return pool.num_free == pool.num_usable


def _mix(tag):
    from vllm_production_stack_tpu.qos import TenantContext

    return {
        "greedy": (SamplingParams(max_tokens=30, temperature=0.0,
                                  ignore_eos=True), {}),
        "seeded": (SamplingParams(max_tokens=26, temperature=0.9, top_p=0.9,
                                  seed=4321, ignore_eos=True), {}),
        # no seed: the streams depend on the dispatch ORDER alone (the
        # runner's RNG is split once per dispatch)
        "sampled": (SamplingParams(max_tokens=26, temperature=0.8,
                                   ignore_eos=True), {}),
        "tenants": (
            SamplingParams(max_tokens=22, temperature=0.0, ignore_eos=True),
            {0: TenantContext("bulk", priority=2, weight=1.0),
             1: TenantContext("acme", priority=0, weight=3.0),
             2: TenantContext("std", priority=1, weight=2.0)},
        ),
    }[tag]


@pytest.mark.parametrize("tag", ["greedy", "seeded", "sampled", "tenants"])
def test_prefills_arriving_while_rows_decode_equal_the_serial_loop(
        pipe, serial, tag):
    """Prompts arrive while other rows decode: every chunk is enqueued
    behind a window or a chunk and something behind every chunk, the new
    rows' first tokens chained on the device — streams and dispatch order
    are the serial loop's, and no window is thrown away for it."""
    sp, tenants = _mix(tag)
    calls = (0, 0, 3, 5, 6, 9, 14)
    out, order = {}, {}
    before = pipe.timing
    for eng in (pipe, serial):
        # the same RNG state on both sides: the unseeded case compares
        # streams drawn from the runner's own key
        eng.runner._rng = jax.random.PRNGKey(11)
        arrivals = {}
        for n, call in enumerate(calls):
            row = (f"{tag}-{n}", prompt_ids(20 + n, 5 + 3 * n), sp)
            if tenants:
                row += (tenants[n % 3],)
            arrivals.setdefault(call, []).append(row)
        seq0 = _last_seq(eng)
        out[eng is pipe] = drive(eng, arrivals)
        order[eng is pipe] = dispatch_order(eng, seq0)
    assert out[True] == out[False]
    assert all(len(t) == sp.max_tokens for t in out[True].values())
    assert order[True] == order[False]
    after = pipe.timing
    chained = after["prefill_chained_n"] - before["prefill_chained_n"]
    chunks = after["prefill_n"] - before["prefill_n"]
    # every chunk but the very first (nothing decodes beside it yet)
    assert 0 < chained <= chunks and chained >= chunks - 1
    assert after["rollback_n"] - before["rollback_n"] <= 1  # the tail only
    assert _pool_is_whole(pipe)


def _first_token_finisher(serial, how):
    """A prompt whose greedy FIRST token ends its request: as a stop token,
    or as the whole of a stop string."""
    greedy = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
    for seed in range(30, 130):
        p = prompt_ids(seed, 8)
        first = streams(serial, [p], greedy)[0][0]
        if how == "stop_token":
            return p, SamplingParams(max_tokens=12, temperature=0.0,
                                     stop_token_ids=(first,))
        if 32 <= first < 127:  # one printable ASCII byte of the tokenizer
            return p, SamplingParams(max_tokens=12, temperature=0.0,
                                     ignore_eos=True, stop=(chr(first),))
    raise AssertionError("no usable first token found")


@pytest.mark.parametrize("how", ["stop_token", "stop_string"])
def test_first_token_that_finishes_its_row_rolls_the_window_back(
        pipe, serial, how):
    """The one thing the window behind a chunk assumes is that no row
    finishes ON its first token. Where one does, the reconcile of the chunk
    discards the window (rollback_n + 1) and the re-dispatch takes the
    serial loop's turn: the round stands where the discarded window's
    schedule() found it, one chunk in with one seat that does not decode,
    so a decode window, not the chunk that waits."""
    prompt, sp = _first_token_finisher(serial, how)
    long = SamplingParams(max_tokens=40, temperature=0.7, ignore_eos=True)
    arrivals = {
        # seven of the eight seats decode: the round is one chunk long
        0: [(f"{how}-keeper{n}", prompt_ids(61 + 10 * n, 6), long)
            for n in range(7)],
        # the finisher's chunk, then a prompt that waits behind it
        4: [(f"{how}-once", prompt, sp)],
        5: [(f"{how}-late", prompt_ids(62, 9), long)],
    }
    out, order = {}, {}
    before = pipe.timing["rollback_n"]
    for eng in (pipe, serial):
        eng.runner._rng = jax.random.PRNGKey(5)
        seq0 = _last_seq(eng)
        out[eng is pipe] = drive(eng, arrivals)
        order[eng is pipe] = dispatch_order(eng, seq0)
    assert out[True] == out[False]
    assert len(out[True][f"{how}-once"]) == 1
    assert order[True] == order[False]
    # the window behind the finisher's chunk, and the tail's
    assert 1 <= pipe.timing["rollback_n"] - before <= 2
    assert _pool_is_whole(pipe)


def test_max_tokens_one_joins_no_window(pipe, serial):
    """A row that may emit one token is finished by its chunk, which is
    known beforehand: it joins no window and nothing is rolled back."""
    one = SamplingParams(max_tokens=1, temperature=0.0, ignore_eos=True)
    long = SamplingParams(max_tokens=17, temperature=0.0, ignore_eos=True)
    arrivals = {
        0: [("one-keeper", prompt_ids(63, 6), long)],
        3: [("one-shot", prompt_ids(64, 7), one)],
    }
    seen = []

    def rows_behind_the_chunk(eng, i):
        step = eng._inflight
        if step is not None and step.handle.kind == "decode":
            seen.extend(r.request_id for r in step.work.requests)

    before = pipe.timing
    got = drive(pipe, arrivals, hook=rows_behind_the_chunk)
    assert got == drive(serial, arrivals)
    assert len(got["one-shot"]) == 1 and "one-shot" not in seen
    after = pipe.timing
    assert after["prefill_chained_n"] - before["prefill_chained_n"] == 1
    # only the keeper's last window had a window thrown away behind it
    assert after["rollback_n"] - before["rollback_n"] <= 1
    assert _pool_is_whole(pipe)


@pytest.mark.parametrize("how", ["abort", "deadline"])
def test_row_of_an_unresolved_chunk_is_cut_between_two_calls(
        pipe, serial, how):
    """An abort or an expired deadline finishes a row whose chunk is still
    in flight: its pages go back at once (device order makes that safe),
    the window planned next does without it, the reconcile skips its first
    token, and the survivor's stream is untouched."""
    sp = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True)
    ref = streams(serial, [prompt_ids(65, 6)], sp)[0]
    cut = []

    def hook(eng, i):
        step = eng._inflight
        if cut or step is None or step.handle.kind != "prefill":
            return
        victim = next((r for r in step.work.requests
                       if r.request_id == f"{how}-victim"), None)
        if victim is None:
            return
        assert victim.num_inflight_tokens > 0 == victim.num_computed_tokens
        if how == "abort":
            assert eng.abort_request(victim.request_id)
        else:
            victim.deadline = 0.0  # long past on the monotonic clock
        cut.append(i)

    arrivals = {
        0: [(f"{how}-keeper", prompt_ids(65, 6), sp)],
        3: [(f"{how}-victim", prompt_ids(66, 11), sp)],
    }
    before = pipe.timing["rollback_n"]
    got = drive(pipe, arrivals, hook=hook)
    assert cut and got[f"{how}-keeper"] == ref
    assert got[f"{how}-victim"] == []
    assert pipe.timing["rollback_n"] - before <= 1  # the keeper's tail
    assert _pool_is_whole(pipe)
    bal = pipe.scheduler.goodput_balance()
    assert bal["balanced"], bal


def test_fault_in_a_resolve_with_a_chunk_enqueued_behind_it(pipe, serial):
    """The previous window's resolve raises after a chunk was enqueued
    behind it: the chunk is rolled back like a window (its rows keep their
    seats at the position the host knows, the turn goes back), the window
    is put back, and the next calls serve everything; nothing leaks."""
    sp = SamplingParams(max_tokens=20, temperature=0.0, ignore_eos=True)
    prompts = [prompt_ids(67, 6), prompt_ids(68, 10)]
    ref = streams(serial, prompts, sp)
    a = pipe.add_request("fault-a", prompt_token_ids=prompts[0], sampling=sp)
    got = {a: [], "fault-b": []}

    def pump():
        for o in pipe.step():
            got[o.request_id].extend(o.new_token_ids)

    while pipe._inflight is None or pipe._inflight.handle.kind != "decode":
        pump()
    pipe.add_request("fault-b", prompt_token_ids=prompts[1], sampling=sp)
    window = pipe._inflight.handle
    real = window.resolve

    def boom():
        raise RuntimeError("injected D2H fault")

    window.resolve = boom
    before = pipe.timing["prefill_n"]
    with pytest.raises(RuntimeError, match="injected"):
        pipe.step()
    # the window is back in flight, the chunk behind it was discarded
    assert pipe._inflight.handle is window and not window.resolved
    b = next(r for r in pipe.scheduler.running if r.request_id == "fault-b")
    assert b.num_inflight_tokens == 0 == b.num_inflight_outputs
    assert b.num_computed_tokens == 0
    window.resolve = real
    while pipe.has_unfinished():
        pump()
    assert [got[a], got["fault-b"]] == ref
    assert pipe.timing["prefill_n"] == before + 1
    assert _pool_is_whole(pipe)


def test_an_unresolved_chunk_is_protected_like_a_window(pipe):
    """Between the call that enqueued a chunk and the one that resolves it
    its rows are no seat victim and no preemption victim, the engine does
    not go to sleep over it, the watchdog's cursor names it, and one more
    step() resolves it even when every row of it was aborted meanwhile."""
    sp = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    pipe.add_request("held", prompt_token_ids=prompt_ids(72, 9), sampling=sp)
    pipe.step()
    step = pipe._inflight
    assert step.handle.kind == "prefill" and not step.handle.resolved
    (row,) = step.work.requests
    assert (row.num_inflight_tokens, row.num_inflight_outputs) == (9, 1)
    sched = pipe.scheduler
    assert sched._seat_victim(-1) is None  # whatever the arrival's class
    free = sched.pool.num_free
    assert not sched._ensure_blocks(row, (free + 64) * sched.block_size)
    assert row in sched.running and row.num_preemptions == 0
    while len(row.block_table) > sched._blocks_needed(9):  # the probe's
        sched.pool.free_block(row.block_table.pop())
    with pytest.raises(RuntimeError, match="unfinished"):
        pipe.sleep()
    assert pipe.flightrec.outstanding_age_s()[1] == "prefill"
    assert pipe.abort_request("held")
    assert not sched.has_unfinished() and pipe.has_unfinished()
    assert pipe.step() == []
    assert pipe.flightrec.outstanding_age_s() is None
    assert _pool_is_whole(pipe) and not pipe.has_unfinished()


def test_a_lone_prompts_chunks_follow_each_other_in_flight(pipe, serial):
    """A lone prompt of several chunks: there is no row to decode beside a
    chunk, and the next chunk is planned over the one in flight, from the
    position that one ends at — the device's queue does not empty between
    two chunks of a prompt."""
    sp = SamplingParams(max_tokens=9, temperature=0.0, ignore_eos=True)
    budget = pipe.config.scheduler.max_num_batched_tokens
    prompt = prompt_ids(69, 2 * budget + 7)  # three chunks
    states = []

    def hook(eng, i):
        step = eng._inflight
        if step is not None and step.handle.kind == "prefill":
            (row,) = step.work.requests
            states.append((row.num_computed_tokens, row.num_inflight_tokens))

    before = pipe.timing
    got = drive(pipe, {0: [("lone", prompt, sp)]}, hook=hook)
    assert got == drive(serial, {0: [("lone", prompt, sp)]})
    # after each call: the chunk before resolved, the next one in flight
    assert states == [(0, budget), (budget, budget), (2 * budget, 7)]
    after = pipe.timing
    assert after["prefill_n"] - before["prefill_n"] == 3
    # every chunk had the next step enqueued behind it: a chunk twice, then
    # the window of its own row
    assert after["prefill_chained_n"] - before["prefill_chained_n"] == 3
    # (nothing was decode-ready: pure prefill, no round)
    assert after["prefill_follow_n"] == before["prefill_follow_n"]
    assert _pool_is_whole(pipe)


# -- the scheduler's round through the pipelined loop -------------------------
#
# With rows that decode and seats that do not, a chunk follows a chunk: the
# second is planned over the first in flight, at the position that one ends
# at, and reads the pages (latents, state slot) it wrote.


def _round_engines(kind, kit, pipe, serial):
    """(pipelined engine, serial engine, chunk) of a model kind: dense is the
    module's pair; latent attention and state slots are the kit's presets."""
    from vllm_production_stack_tpu.models.registry import (
        resolve_model_config,
    )

    if kind == "dense":
        return pipe, serial, pipe.config.scheduler.max_num_batched_tokens
    model, chunk = {
        "latent": (resolve_model_config("tiny-kanana"), 32),
        # (G K K K: one period; a chunk is a block of the KDA prefill form)
        "state_slot": (ek.cut(resolve_model_config("tiny-solar-open2"), 4),
                       64),
    }[kind]
    config = ek.engine_config(model, chunk=chunk)
    return kit.engine(config)[0], kit.engine(config, serial=True)[0], chunk


@pytest.mark.parametrize("kind", ["dense", "latent", "state_slot"])
def test_chunks_follow_chunks_beside_decoding_rows_like_the_serial_loop(
        kind, kit, pipe, serial):
    """Two rows decode, long prompts of several chunks arrive: every round
    runs more than one chunk, each planned over the one in flight — token
    streams (unseeded sampling: they hang on the dispatch ORDER) and the
    order are the serial loop's, every chunk had its successor enqueued
    behind it, and nothing is thrown away but the tail."""
    fast, slow, chunk = _round_engines(kind, kit, pipe, serial)
    vocab = fast.config.model.vocab_size

    def prompt(seed, n):
        return list(map(int, np.random.RandomState(seed).randint(
            1, min(vocab, 500), n)))

    short = SamplingParams(max_tokens=40, temperature=0.8, ignore_eos=True)
    long = SamplingParams(max_tokens=20, temperature=0.8, ignore_eos=True)
    arrivals = {
        0: [(f"{kind}-a", prompt(81, 9), short),
            (f"{kind}-b", prompt(82, 12), short)],
        3: [(f"{kind}-long", prompt(83, 2 * chunk + 22), long),
            (f"{kind}-longer", prompt(84, 3 * chunk + 7), long)],
    }
    out, order, counted = {}, {}, {}
    for eng in (fast, slow):
        eng.runner._rng = jax.random.PRNGKey(13)
        seq0, before = _last_seq(eng), eng.timing
        out[eng is fast] = drive(eng, arrivals)
        order[eng is fast] = dispatch_order(eng, seq0)
        counted[eng is fast] = {
            k: eng.timing[k] - before[k]
            for k in ("prefill_n", "prefill_chained_n", "prefill_follow_n",
                      "rollback_n")}
        assert eng.timing["round_chunks_max"] >= 2
        assert eng.scheduler.total_preemptions == 0
        assert _pool_is_whole(eng) and not ek.undrained(eng)
    assert out[True] == out[False]
    assert [len(out[True][f"{kind}-{n}"]) for n in
            ("a", "b", "long", "longer")] == [40, 40, 20, 20]
    assert order[True] == order[False]
    # two rows decode: the prompts' chunks run two and more a round
    kinds = "".join(k[0] for k, _ in order[True])
    assert "pp" in kinds and "ppd" in kinds
    got = counted[True]
    assert got["prefill_follow_n"] > 0
    assert got["prefill_follow_n"] == counted[False]["prefill_follow_n"]
    assert got["prefill_chained_n"] == got["prefill_n"] >= 6
    assert got["rollback_n"] <= 1  # the tail only


@pytest.mark.parametrize("kind", ["dense", "latent", "state_slot"])
def test_a_queue_behind_full_seats_is_served_like_the_serial_loop(
        kind, kit, pipe, serial):
    """More requests than seats, long prompts among them: the round keeps
    the seats full (a chunk follows a chunk while seats stand empty) and
    every greedy stream is the serial loop's — a row's tokens hang on its
    own prompt alone, whatever the order its chunks ran in."""
    fast, slow, chunk = _round_engines(kind, kit, pipe, serial)
    seats = fast.config.scheduler.max_num_seqs
    lengths = [9, 2 * chunk + 5, 14, 3 * chunk + 1, 21, chunk + 9, 7,
               2 * chunk + 30, 11, 17][: seats + 4]
    prompts = ek.prompts(fast.config.model, lengths, seed=9)
    before = fast.timing
    want = ek.generate(slow, prompts, max_tokens=18)
    assert ek.generate(fast, prompts, max_tokens=18) == want
    after = fast.timing
    assert after["prefill_follow_n"] > before["prefill_follow_n"]
    assert fast.scheduler.total_preemptions == 0
    assert _pool_is_whole(fast)


def test_the_three_counters_of_the_round_and_of_admission(pipe, serial):
    """`/debug/timing` says what the scheduler did about the seats: chunks
    that followed chunks beside decoding rows (`prefill_follow_n`), the
    longest round (`round_chunks_max`, a maximum: it is read, not
    subtracted) and the turns in which the pool, not the seats, kept a
    request waiting (`seat_held_by_pool_n`). Eight requests of fifteen
    blocks' budget each over a pool of sixty-three: four sit, four wait with
    four seats empty, nobody is preempted, and the streams are the serial
    loop's."""
    sp = SamplingParams(max_tokens=40, temperature=0.0, ignore_eos=True)
    arrivals = {0: [(f"c{n}", prompt_ids(90 + n, 70), sp) for n in range(8)]}
    seats, out, counted = {}, {}, {}

    def hook(eng, i):
        seats[eng] = max(seats.get(eng, 0), len(eng.scheduler.running))

    for eng in (pipe, serial):
        before = eng.timing
        out[eng] = drive(eng, arrivals, hook=hook)
        after = eng.timing
        counted[eng] = {k: after[k] - before[k] for k in (
            "prefill_follow_n", "seat_held_by_pool_n", "prefill_n")}
        assert after["round_chunks_max"] >= 2
        assert eng.scheduler.total_preemptions == 0 and _pool_is_whole(eng)
    assert out[pipe] == out[serial]
    assert all(len(v) == 40 for v in out[pipe].values())
    assert seats == {pipe: 4, serial: 4}  # 4 x 15 blocks of 63
    for eng in (pipe, serial):
        assert counted[eng]["seat_held_by_pool_n"] > 0
        assert 0 < counted[eng]["prefill_follow_n"] < counted[eng]["prefill_n"]
    # (the pipelined loop plans some turns twice: only the chunks agree)
    assert counted[pipe]["prefill_n"] == counted[serial]["prefill_n"]
    # the counters are part of every snapshot, at zero too
    from vllm_production_stack_tpu.engine.flightrec import FlightRecorder

    fresh = FlightRecorder().timing()
    assert (fresh["prefill_follow_n"], fresh["round_chunks_max"],
            fresh["seat_held_by_pool_n"]) == (0, 0, 0)


def test_grammar_row_sits_out_the_window_behind_its_chunk(pipe, serial):
    """A constrained row's automaton state after its first token is on the
    host only once the chunk has resolved: it sits out ONE window and joins
    the next with host tokens; its stream is the serial loop's."""
    spec = {"kind": "json_schema", "schema": {
        "type": "object", "properties": {"ok": {"type": "boolean"}}}}
    plain = SamplingParams(max_tokens=30, temperature=0.0, ignore_eos=True)
    out, behind = {}, []

    def hook(eng, i):
        step = eng._inflight
        if eng is pipe and step is not None and step.handle.kind == "decode":
            behind.append([r.request_id for r in step.work.requests])

    before = pipe.timing["rollback_n"]
    for eng in (pipe, serial):
        gsp = SamplingParams(max_tokens=24, temperature=0.0,
                             grammar=eng.grammar_cache.get(spec)[0])
        arrivals = {0: [("gr-keeper", prompt_ids(70, 6), plain)],
                    4: [("gr-row", prompt_ids(71, 9), gsp)]}
        out[eng is pipe] = drive(eng, arrivals, hook=hook)
    assert out[True] == out[False] and out[True]["gr-row"]
    first = next(i for i, rows in enumerate(behind) if "gr-row" in rows)
    # the window right behind the chunk ran without the constrained row
    assert behind[first - 1] == ["gr-keeper"]
    assert pipe.timing["rollback_n"] - before <= 2
    assert _pool_is_whole(pipe)


def test_spec_decode_composes_with_pipeline():
    """Speculation no longer forces the serial loop: verify dispatches are
    in-flight pipeline work (docs/36-speculative-decoding.md). The deep
    equivalence/rollback coverage lives in tests/test_spec_decode.py —
    this guards the latch itself."""
    cfg = EngineConfig.tiny()
    from dataclasses import replace

    cfg = cfg.replace(
        scheduler=replace(cfg.scheduler, num_speculative_tokens=2)
    )
    eng = LLMEngine(cfg)
    try:
        assert "step" not in vars(eng)  # speculation swaps no loop in
    finally:
        eng.runner.shutdown(wait=True)  # no compile threads outlive the module
