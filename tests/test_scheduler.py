"""Scheduler behavior: chunked prefill, decode batching, the round (every
seat one turn of the device), prefix-cache admission, admission that keeps
its promise, preemption + recompute-resume."""

import dataclasses

import pytest

import engine_kit as ek
from vllm_production_stack_tpu.engine.config import (
    CacheConfig,
    ModelConfig,
    SchedulerConfig,
)
from vllm_production_stack_tpu.engine.request import Request, SamplingParams
from vllm_production_stack_tpu.engine.scheduler import (
    DecodeWork,
    PrefillWork,
    Scheduler,
    VerifyWork,
)


def make_scheduler(
    num_blocks=16, block_size=4, max_batched=8, max_seqs=4, window=1,
    model=None, prefix_caching=True, window_group=False, **sched
):
    """`window_group`: the window layers' pages in a group of their own,
    sized as the engine sizes it (`memory.resolve_window_blocks`)."""
    model = model or ModelConfig.tiny(max_model_len=128)
    cache = CacheConfig(
        block_size=block_size, num_blocks=num_blocks,
        enable_prefix_caching=prefix_caching
    )
    config = SchedulerConfig(
        max_num_seqs=max_seqs,
        max_num_batched_tokens=max_batched,
        decode_buckets=(max_seqs,),
        prefill_buckets=(max_batched,),
        decode_window=window,
        **sched,
    )
    window_blocks = None
    if window_group:
        from vllm_production_stack_tpu.engine.memory import window_row_blocks

        window_blocks = max_seqs * window_row_blocks(model, cache, config) + 1
    return Scheduler(model, cache, config, window_blocks=window_blocks)


def req(rid, n_prompt, base=100, **kw):
    return Request(
        request_id=rid,
        prompt_token_ids=list(range(base, base + n_prompt)),
        sampling=SamplingParams(**kw),
    )


def drive(sched, work, start_token=1000):
    """Apply fake sampled tokens for every sample slot in the work."""
    if isinstance(work, PrefillWork):
        rows = [
            [start_token + i] if s else [] for i, s in enumerate(work.sample)
        ]
    else:
        rows = [
            [start_token + i * 100 + k for k in range(work.window)]
            for i in range(len(work.requests))
        ]
    return sched.postprocess(work, rows)


def test_chunked_prefill_then_decode():
    s = make_scheduler(max_batched=8)
    r = req("a", 19, max_tokens=4)
    s.add_request(r)

    sizes = []
    while not r.prefill_done:
        w = s.schedule()
        assert isinstance(w, PrefillWork)
        sizes.append(len(w.token_ids[0]))
        drive(s, w)
    assert sizes == [8, 8, 3]
    assert len(r.output_token_ids) == 1  # sampled at prompt end

    w = s.schedule()
    assert isinstance(w, DecodeWork) and w.requests == [r]
    assert w.positions == [19]
    assert w.token_ids == [r.output_token_ids[-1]]
    drive(s, w)
    assert len(r.output_token_ids) == 2


def kinds_of(s, n, pipelined=False):
    """The next `n` turns as letters (P chunk, D window, V verify, - none),
    each resolved before the next is planned — or, `pipelined`, each planned
    over its predecessor in flight and that one resolved after it, as the
    step loop does."""
    out, prev = "", None
    for _ in range(n):
        w = s.schedule(inflight=prev) if pipelined else s.schedule()
        out += {PrefillWork: "P", DecodeWork: "D", VerifyWork: "V",
                type(None): "-"}[type(w)]
        if w is None:
            break
        if pipelined:
            s.begin_speculative(w)
            if prev is not None:
                s.end_speculative(prev)
                drive(s, prev)
                assert s.speculation_valid(w)
            prev = w
        elif isinstance(w, VerifyWork):
            # every proposal confirmed, and a bonus token that keeps the
            # row's sequence periodic (so that it proposes again)
            rows = []
            for r, p in zip(w.requests, w.proposals):
                period = r.prompt_token_ids[:3]
                rows.append([*p, period[(r.num_tokens + len(p)) % 3]])
            s.postprocess(w, rows)
        else:
            drive(s, w)
    return out


def decoding_rows(s, n, **kw):
    """`n` rows seated and decoding, their first window behind them: a
    round begins."""
    rows = [req(f"d{i}", 4, max_tokens=60, **kw) for i in range(n)]
    for r in rows:
        s.add_request(r)
    while any(not r.output_token_ids for r in rows):
        drive(s, s.schedule())
    w = s.schedule()
    assert isinstance(w, DecodeWork) and w.requests == rows
    drive(s, w)
    return rows


def _one_seat_does_not_decode():
    # chunk, window, chunk, window: the rule before the round was this case
    s = make_scheduler(num_blocks=128, max_batched=16)
    decoding_rows(s, 3)
    s.add_request(req("long", 60, base=300, max_tokens=8))  # four chunks
    return s, "PDPDPDPD" + "DDD"


def _ten_of_sixteen_seats_empty():
    # six rows decode, a queue of long prompts: ten chunks, then a window
    s = make_scheduler(num_blocks=256, max_batched=8, max_seqs=16)
    decoding_rows(s, 6)
    for i in range(12):
        s.add_request(req(f"q{i}", 100, base=300 + i, max_tokens=8))
    return s, "P" * 10 + "D" + "P" * 3


def _each_chunk_seats_a_row_that_then_waits():
    # one-chunk prompts: every chunk adds a row to those that wait for the
    # window and takes a seat from those that do not decode, so the round
    # ends where the two meet: 1 < 9, 2 < 8, ..., 5 = 5
    s = make_scheduler(num_blocks=256, max_batched=8, max_seqs=16)
    decoding_rows(s, 6)
    for i in range(12):
        s.add_request(req(f"q{i}", 8, base=300 + 10 * i, max_tokens=60))
    return s, "P" * 5 + "D" + "P" * 3 + "D"


def _nothing_decode_ready():
    s = make_scheduler(num_blocks=64, max_batched=8)
    s.add_request(req("long", 60, max_tokens=3))
    return s, "P" * 8 + "DD"


def _every_seat_decodes():
    s = make_scheduler(num_blocks=64, max_batched=16, max_seqs=3)
    decoding_rows(s, 3)
    s.add_request(req("waits", 20, max_tokens=8))
    return s, "DDDDDD"


def _verify_turn():
    # a greedy row that proposes and a sampled row that cannot take the
    # verify / window turns in turn, whatever chunks run between them
    s = make_scheduler(num_blocks=128, max_batched=8,
                       num_speculative_tokens=2)
    spec = Request(request_id="spec", prompt_token_ids=[7, 8, 9] * 3,
                   sampling=SamplingParams(max_tokens=60, temperature=0.0))
    s.add_request(spec)
    s.add_request(req("plain", 4, max_tokens=60, temperature=1.0))
    while not (spec.output_token_ids and len(s.running) == 2):
        drive(s, s.schedule(), start_token=7)  # the period goes on: 7, ...
    s.add_request(req("long", 60, base=300, max_tokens=8))  # two seats idle
    return s, "VPPDPPVPPDPPV"


def _a_prompt_under_a_block():
    # generation by diffusion over blocks: a prompt shorter than a block has
    # nothing to prefill and is decode-ready as it stands
    from vllm_production_stack_tpu.engine.config import BlockDiffusion

    model = dataclasses.replace(
        ModelConfig.tiny(max_model_len=128),
        block_diffusion=BlockDiffusion(block_length=4, mask_token_id=0))
    s = make_scheduler(num_blocks=64, max_batched=8, window=4, model=model,
                       prefix_caching=False)
    s.add_request(req("short", 3, max_tokens=9))
    return s, "DDD"


def _an_exhausted_row_does_not_count_as_deciding():
    # the pipelined loop: the chunk in flight ends the prompt of a request
    # that asked for ONE token. It is decode-ready at the speculative
    # position and gone once the chunk resolves, so two seats of four do not
    # decode and a second chunk follows, as in the serial loop (which plans
    # after the resolve and never sees the row); counted among the deciding
    # it would leave one seat, and the window would come a chunk early
    s = make_scheduler(num_blocks=128, max_batched=8)
    decoding_rows(s, 2)
    s.add_request(req("once", 8, base=300, max_tokens=1))
    s.add_request(req("next", 12, base=400, max_tokens=8))
    return s, "PPDPD", True


@pytest.mark.parametrize("case", [
    _one_seat_does_not_decode, _ten_of_sixteen_seats_empty,
    _each_chunk_seats_a_row_that_then_waits, _nothing_decode_ready,
    _every_seat_decodes, _verify_turn, _a_prompt_under_a_block,
    _an_exhausted_row_does_not_count_as_deciding,
], ids=lambda f: f.__name__.strip("_"))
def test_the_round_gives_every_seat_one_turn(case):
    """A round: the rows that decode take one window together, every other
    seat that can be advanced (mid-prompt, or empty with a request waiting)
    may take one chunk — a chunk is chosen while the chunks since the last
    window are fewer than the seats that do not decode, and one at least."""
    s, want, *pipelined = case()
    assert kinds_of(s, len(want), *pipelined) == want
    if pipelined:
        # the serial loop takes the same turns
        s, _, _ = case()
        assert kinds_of(s, len(want)) == want


def test_a_chunk_over_a_chunk_in_flight_and_its_rollback():
    """The pipelined loop's second chunk: planned while the first is in
    flight, it starts where that one ends; it is valid once the first has
    resolved as planned; rolled back it leaves positions, blocks, the state
    bank AND the round's count as its schedule() found them."""
    from vllm_production_stack_tpu.models.registry import (
        resolve_model_config,
    )

    # a model with state slots, so that a row has a bank to flip
    s = make_scheduler(
        num_blocks=128, block_size=4, max_batched=8, max_seqs=4,
        model=resolve_model_config("tiny-solar-open2"), prefix_caching=False)
    (keeper,) = rows = [req("keeper", 4, max_tokens=60)]
    s.add_request(keeper)
    drive(s, s.schedule())
    drive(s, s.schedule())  # the keeper decodes: a round begins
    long = req("long", 30, base=300, max_tokens=8)
    s.add_request(long)

    a = s.schedule()
    assert isinstance(a, PrefillWork) and a.positions == [list(range(8))]
    assert a.round_chunk == 1
    s.begin_speculative(a)  # dispatched, unresolved
    assert (long.num_computed_tokens, long.num_inflight_tokens) == (0, 8)
    assert long.state_bank == 1

    b = s.schedule(inflight=a)  # three seats do not decode: a chunk follows
    assert isinstance(b, PrefillWork) and b.requests == [long]
    assert b.positions == [list(range(8, 16))] and b.context_lens == [16]
    assert b.round_chunk == 2 and s._round_chunks == 2
    assert len(long.block_table) == 4  # blocks to the speculative end
    s.begin_speculative(b)
    assert (long.num_inflight_tokens, long.state_bank) == (16, 0)

    s.end_speculative(a)  # the first resolves as planned
    s.postprocess(a, [[]])
    assert (long.num_computed_tokens, long.num_inflight_tokens) == (8, 8)
    assert s.speculation_valid(b)

    s.rollback_speculative(b)  # (a fault in the engine's resolve, say)
    assert (long.num_computed_tokens, long.num_inflight_tokens) == (8, 0)
    assert len(long.block_table) == 2 and long.state_bank == 1
    assert s._round_chunks == 1
    again = s.schedule()  # the serial re-dispatch: the same chunk
    assert again.positions == b.positions and again.round_chunk == 2

    # a row that leaves while its chunk is in flight invalidates the next
    s.begin_speculative(again)
    c = s.schedule(inflight=again)
    assert c.positions == [list(range(16, 24))]
    s.begin_speculative(c)
    s.abort_request("long")
    s.end_speculative(again)
    s.postprocess(again, [[]])
    assert not s.speculation_valid(c)
    s.rollback_speculative(c)
    assert s._round_chunks == 2 and s.running == rows
    assert s.pool.state.num_in_use == 1


@pytest.mark.parametrize("window,group", [
    (1, False), (8, False), (1, True), (8, True)],
    ids=["window1", "window8", "window1-group", "window8-group"])
def test_admission_seats_nobody_it_cannot_finish(window, group):
    """A queue of requests that together outgrow the pool many times over:
    a request is seated only when the free blocks, less what the rows
    already seated may still grow by, hold its prompt AND the answer it
    declared — the pool fills, nobody is preempted, everybody finishes.
    `group`: a model with sliding-window layers, whose pages live in a
    window group sized for every seat's worst case; the full group's
    promise is the same beside it, and that group never says no."""
    from vllm_production_stack_tpu.models.registry import (
        resolve_model_config,
    )

    s = make_scheduler(
        num_blocks=41, block_size=4, max_batched=16, max_seqs=8,
        window=window, prefix_caching=False, window_group=group,
        model=resolve_model_config("tiny-laguna") if group else None)
    assert (s.pool.window is not None) == group
    rows = [req(f"r{i}", 10 + 3 * (i % 4), base=100 * i, max_tokens=9 + 5 * (i % 3))
            for i in range(24)]
    for r in rows:
        s.add_request(r)
    peak = seats = 0
    for _ in range(2000):
        w = s.schedule()
        if w is None:
            break
        drive(s, w)
        seats = max(seats, len(s.running))
        # the promise: what is free covers what the seated rows may still take
        owed = sum(max(0, s._budget_blocks(r) - len(r.block_table))
                   for r in s.running)
        assert owed <= s.pool.num_free
        # (held or spoken for, of the pool)
        peak = max(peak, s.pool.usage_perc + owed / s.pool.num_usable)
    assert all(r.status.name == "FINISHED_LENGTH" for r in rows)
    assert [len(r.output_token_ids) for r in rows] == [
        9 + 5 * (i % 3) for i in range(24)]
    assert s.total_preemptions == 0
    # the pool, not the seats, said "no more": all of it held or spoken for
    # with seats to spare
    assert peak > 0.8 and 2 <= seats < 8
    assert s.pool.num_free == s.pool.num_usable
    if group:
        assert s.pool.window.num_free == s.pool.window.num_usable


def test_a_request_that_declares_more_than_the_pool_runs_alone():
    """`max_tokens` beyond what the pool holds reserves the whole pool: the
    request is seated once nobody else is, and nobody joins it."""
    s = make_scheduler(num_blocks=16, block_size=4, max_batched=16,
                       max_seqs=4, prefix_caching=False)
    small, big, late = (req("small", 8, max_tokens=4),
                        req("big", 8, base=300, max_tokens=100),
                        req("late", 8, base=500, max_tokens=4))
    for r in (small, big, late):
        s.add_request(r)
    seen = []
    for _ in range(200):
        w = s.schedule()
        if w is None:
            break
        seen.append(sorted(r.request_id for r in s.running))
        drive(s, w)
        if len(big.output_token_ids) >= 10:
            s.abort_request("big")  # (it would run into the pool's end)
    assert ["big", "small"] not in seen and ["big", "late"] not in seen
    assert ["big"] in seen and small.status.finished and late.status.finished
    assert s.total_preemptions == 0


def test_a_row_with_a_hydration_plan_sits_out_while_its_chunk_is_in_flight():
    """A hydration plan is consumed at RESOLVED positions (an adoption moves
    `num_computed_tokens`): its row takes no chunk over its own chunk in
    flight, the rows beside it go on, and with the chunk resolved the row is
    back — parked at its pending fetch, then computing once that has
    failed."""
    import time

    from vllm_production_stack_tpu.engine.hydration import (
        HydrationChunk,
        HydrationPlan,
    )

    s = make_scheduler(num_blocks=128, max_batched=8, prefix_caching=False)
    (keeper,) = decoding_rows(s, 1)
    planned, plain = (req("planned", 30, base=300, max_tokens=8),
                      req("plain", 30, base=500, max_tokens=8))
    fetch = HydrationChunk(1, 2, [11, 12], ["disk", "disk"], "load")
    planned.hydration_plan = HydrationPlan(
        "planned", [HydrationChunk(0, 0, [9, 10], ["disk"] * 2, "recompute"),
                    fetch],
        block_size=4, deadline=time.monotonic() + 60, estimates={})
    s.add_request(planned)
    a = s.schedule()  # bounded at the load boundary: blocks 0 and 1
    assert a.requests == [planned] and a.positions == [list(range(8))]
    s.begin_speculative(a)
    s.add_request(plain)

    b = s.schedule(inflight=a)  # three seats do not decode: a chunk follows
    assert isinstance(b, PrefillWork) and b.requests == [plain]
    assert b.round_chunk == 2 and planned.num_inflight_tokens == 8
    s.begin_speculative(b)
    s.end_speculative(a)
    s.postprocess(a, [[]])
    c = s.schedule(inflight=b)  # parked at the pending fetch; `plain` goes on
    assert isinstance(c, PrefillWork) and c.requests == [plain]
    assert c.positions == [list(range(8, 16))]
    s.begin_speculative(c)
    s.end_speculative(b)
    s.postprocess(b, [[]])
    assert s.speculation_valid(c)
    d = s.schedule(inflight=c)  # the round's three chunks are spent
    assert isinstance(d, DecodeWork) and d.requests == [keeper]
    s.begin_speculative(d)
    s.end_speculative(c)
    s.postprocess(c, [[]])
    with planned.hydration_plan.lock:
        fetch.status = "failed"  # the fetcher gave up: compute it after all
    e = s.schedule(inflight=d)
    assert e.requests == [planned, plain][: len(e.requests)]
    assert e.positions[0] == list(range(8, 16))
    s.begin_speculative(e)
    # the plan is still live (its last chunk is being computed): no chunk
    # of `planned` over the one in flight
    assert planned.hydration_plan is not None
    f = s.schedule(inflight=e)
    assert isinstance(f, PrefillWork) and planned not in f.requests


def test_the_pool_not_the_seats_says_no_more_and_schedule_says_so():
    """`seat_held_by_pool`: the schedule() that left a seat empty with a
    request waiting because the pool could not carry that request to its
    end — not one that found the seats full, nor one with nobody waiting."""
    s = make_scheduler(num_blocks=17, block_size=4, max_batched=16,
                       max_seqs=4, prefix_caching=False)
    # 16 usable blocks; a request's budget is 8 + 20 tokens = 7 blocks
    rows = [req(f"r{i}", 8, base=100 * i, max_tokens=20) for i in range(3)]
    for r in rows[:2]:
        s.add_request(r)
    held = []
    for _ in range(3):
        drive(s, s.schedule())
        held.append(s.seat_held_by_pool)
    assert held == [False] * 3 and len(s.running) == 2
    s.add_request(rows[2])  # two seats empty, 2 blocks not spoken for
    seen = []
    while any(not r.status.finished for r in rows):
        w = s.schedule()
        seen.append((len(s.running), bool(s.waiting), s.seat_held_by_pool))
        drive(s, w)
    # said while two rows sat and the third waited, and only then
    assert (2, True, True) in seen
    assert all(waiting and running == 2
               for running, waiting, flag in seen if flag)
    assert s.total_preemptions == 0
    assert [len(r.output_token_ids) for r in rows] == [20] * 3


def test_prefix_cache_hit_on_second_request():
    s = make_scheduler(block_size=4, max_batched=16)
    a = req("a", 10, max_tokens=1)
    s.add_request(a)
    drive(s, s.schedule())  # full prefill + sample -> finished (max_tokens=1)
    assert a.status.finished

    b = req("b", 10, max_tokens=1)  # same prompt tokens
    s.add_request(b)
    w = s.schedule()
    assert isinstance(w, PrefillWork)
    # two full blocks (8 tokens) served from cache; only the tail computed
    assert b.num_cached_prompt_tokens == 8
    assert w.positions == [[8, 9]]


def test_batched_prefill_packs_multiple_requests():
    s = make_scheduler(num_blocks=32, max_batched=16, max_seqs=4)
    reqs = [req(f"r{i}", 5, max_tokens=4) for i in range(3)]
    for r in reqs:
        s.add_request(r)
    w = s.schedule()
    assert isinstance(w, PrefillWork)
    # 16-token budget fits all three 5-token prompts in ONE dispatch
    assert w.requests == reqs
    assert [len(t) for t in w.token_ids] == [5, 5, 5]
    assert w.sample == [True, True, True]
    results = drive(s, w)
    assert all(len(toks) == 1 for _, toks in results)
    assert all(len(r.output_token_ids) == 1 for r in reqs)


def test_batched_prefill_respects_token_budget():
    s = make_scheduler(num_blocks=64, max_batched=8, max_seqs=4)
    a, b = req("a", 6, max_tokens=4), req("b", 6, max_tokens=4)
    s.add_request(a)
    s.add_request(b)
    w = s.schedule()
    # 8-token budget: a's full 6-token chunk + b's first 2 tokens
    assert w.requests == [a, b]
    assert [len(t) for t in w.token_ids] == [6, 2]
    assert w.sample == [True, False]
    drive(s, w)
    assert a.output_token_ids and not b.output_token_ids


def test_preemption_and_resume():
    # 14 usable blocks of 4 tokens: two seqs of 7 blocks each at their end
    s = make_scheduler(num_blocks=15, block_size=4, max_batched=8, max_seqs=2)
    s.pool.enable_prefix_caching = False
    a, b = req("a", 8, max_tokens=20), req("b", 8, max_tokens=20)
    s.add_request(a)
    s.add_request(b)
    held = None
    for _ in range(80):
        if held is None and a.output_token_ids and b.output_token_ids:
            held = ek.squeeze(s.pool)  # both seated, decoding: the pool goes
        w = s.schedule()
        if held and s.total_preemptions:
            for blk in held:
                s.pool.free_scratch(blk)
            held = []
        if w is None:
            break
        drive(s, w)
        if a.status.finished and b.status.finished:
            break
    # growth found no block: the NEWEST row went, the oldest never does
    assert (a.num_preemptions, b.num_preemptions) == (0, 1)
    assert a.status.finished and b.status.finished
    # both produced the full 20 tokens despite recompute
    assert len(a.output_token_ids) == 20
    assert len(b.output_token_ids) == 20
    # all blocks released at the end
    assert s.pool.num_free == 14


def test_windowed_decode_accept_and_discard():
    s = make_scheduler(num_blocks=32, max_batched=16, window=4)
    a = req("a", 6, max_tokens=3)  # finishes mid-way through the joint window
    b = req("b", 6, max_tokens=10)
    s.add_request(a)
    s.add_request(b)
    drive(s, s.schedule())  # batched prefill of a AND b (+1 output each)
    assert a.output_token_ids and b.output_token_ids
    w = s.schedule()
    assert isinstance(w, DecodeWork)
    assert w.window == 4 and len(w.requests) == 2
    results = s.postprocess(w, [[11, 12, 13, 14], [21, 22, 23, 24]])
    by_id = {r.request_id: toks for r, toks in results}
    # a had 1 output + window 4, max_tokens=3 -> accepts 2, discards 2
    assert by_id["a"] == [11, 12]
    assert a.status.finished and a.status.name == "FINISHED_LENGTH"
    assert by_id["b"] == [21, 22, 23, 24]
    assert len(b.output_token_ids) == 5
    # b's computed tokens advanced by the full window
    assert b.num_computed_tokens == 6 + 4


def test_windowed_decode_no_self_preempt_livelock():
    """A request near pool exhaustion must not preempt itself to grow a decode
    window (round-1 livelock: 8-block pool, 8-token prompt, max_tokens=40)."""
    s = make_scheduler(num_blocks=8, block_size=4, max_batched=8, max_seqs=2, window=8)
    r = req("a", 8, max_tokens=40)
    s.add_request(r)
    for _ in range(400):
        w = s.schedule()
        if w is not None:
            drive(s, w)
        if s.take_finished_externally() or r.status.finished:
            break
        if w is None and not s.has_unfinished():
            break
    assert r.status.finished
    # either ran to a capacity abort or a length finish — never a livelock
    assert r.num_preemptions <= 2


def test_windowed_decode_eos_discards_tail():
    s = make_scheduler(num_blocks=32, max_batched=16, window=4)
    r = req("a", 6, max_tokens=10)
    r.eos_token_id = 777
    s.add_request(r)
    drive(s, s.schedule())
    w = s.schedule()
    results = s.postprocess(w, [[31, 777, 33, 34]])
    assert results[0][1] == [31, 777]
    assert r.status.name == "FINISHED_STOPPED"


def test_finish_frees_blocks_and_eos():
    s = make_scheduler()
    r = req("a", 4, max_tokens=10)
    r.eos_token_id = 1001  # second drive token
    s.add_request(r)
    drive(s, s.schedule())  # prefill, samples 1000
    drive(s, s.schedule(), start_token=1001)  # decode -> eos
    assert r.status.finished and r.status.name == "FINISHED_STOPPED"
    assert s.pool.usage_perc == 0.0 or s.pool.num_free == s.pool.num_usable
