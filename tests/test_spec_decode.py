"""Speculative decoding: the proposers (n-gram + draft model),
verification-path correctness (spec and non-spec engines must produce
IDENTICAL greedy outputs), composition with the pipelined step loop
(bitwise serial↔pipelined equivalence, partial-acceptance chain trim),
goodput-ledger exactness, draft KV-pool isolation, and the acceptance
counters."""

import numpy as np
import pytest

from vllm_production_stack_tpu.engine.config import (
    CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
)
from vllm_production_stack_tpu.engine.engine import LLMEngine
from vllm_production_stack_tpu.engine.request import SamplingParams
from vllm_production_stack_tpu.engine.scheduler import PrefillWork, VerifyWork
from vllm_production_stack_tpu.engine.spec_decode import propose_ngram
from vllm_production_stack_tpu.testing.serial_loop import serial_engine


def test_propose_ngram_basic():
    # tail [7, 8] recurs earlier; continuation follows the match
    toks = [1, 7, 8, 9, 4, 5, 7, 8]
    assert propose_ngram(toks, k=2) == [9, 4]
    # longest n-gram wins: tail [5, 7, 8] also recurs? it doesn't — [7, 8]
    assert propose_ngram(toks, k=5) == [9, 4, 5, 7, 8][:5]
    # no recurrence
    assert propose_ngram([1, 2, 3, 4], k=2) is None
    # most recent match wins
    toks = [7, 8, 1, 1, 7, 8, 2, 2, 7, 8]
    assert propose_ngram(toks, k=1) == [2]
    assert propose_ngram([], k=2) is None
    assert propose_ngram([1, 2, 3], k=0) is None


def _build(
    spec_k, async_on=True, method="ngram", draft="", model=None, **cache_kw
):
    cache = dict(block_size=8, num_blocks=64)
    cache.update(cache_kw)
    return (LLMEngine if async_on else serial_engine)(
        EngineConfig(
            model=model or ModelConfig.tiny(),
            cache=CacheConfig(**cache),
            scheduler=SchedulerConfig(
                max_num_seqs=4, max_num_batched_tokens=32,
                decode_buckets=(4,), prefill_buckets=(16, 32),
                decode_window=4, num_speculative_tokens=spec_k,
                speculative_method=method, draft_model=draft,
            ),
        )
    )


def _shutdown(*engines):
    """Cancel queued background compiles — leaked compile threads steal
    CPU from whatever module runs next (the PR 2 deflake lesson), and the
    draft proposer's runner compiles too."""
    for e in engines:
        e.runner.shutdown(wait=True)
        if getattr(e, "draft_runner", None) is not None:
            e.draft_runner.shutdown(wait=True)


def _streams(engine, prompts, sampling):
    ids = [
        engine.add_request(prompt_token_ids=p, sampling=s)
        for p, s in zip(prompts, sampling)
    ]
    got = {i: [] for i in ids}
    while engine.has_unfinished():
        for out in engine.step():
            got[out.request_id].extend(out.new_token_ids)
    return [got[i] for i in ids]


def test_spec_engine_matches_plain_greedy():
    """The whole point: speculation must be lossless for greedy decoding —
    identical tokens, whatever the acceptance pattern. Repetitive prompts
    give the proposer real n-gram hits."""
    rng = np.random.RandomState(0)
    base = list(rng.randint(1, 500, size=6))
    prompts = [
        base * 3,  # strongly repetitive: proposals fire
        list(rng.randint(1, 500, size=11)),  # random: proposals rarely fire
        base * 2 + list(rng.randint(1, 500, size=3)),
    ]
    greedy = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)

    plain = [r["token_ids"] for r in _build(0).generate(prompts, greedy)]
    spec_engine = _build(3)
    spec = [r["token_ids"] for r in spec_engine.generate(prompts, greedy)]
    assert spec == plain
    stats = spec_engine.stats()
    assert stats.spec_draft_tokens > 0  # proposals actually fired
    # generated text is model output on random weights; acceptance may be
    # low, but the counters must be consistent
    assert 0 <= stats.spec_accepted_tokens <= stats.spec_draft_tokens


def test_spec_mixed_sampling_batch():
    """Non-greedy rows keep the decode-window path (seeded sampling must be
    reproducible against a plain engine) while greedy rows verify."""
    rng = np.random.RandomState(1)
    prompts = [list(rng.randint(1, 500, size=7)) for _ in range(2)]
    seeded = SamplingParams(
        max_tokens=8, temperature=0.8, seed=42, ignore_eos=True
    )
    greedy = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)

    plain_engine = _build(0)
    spec_engine = _build(3)

    plain = [
        plain_engine.generate([p], s)[0]["token_ids"]
        for p, s in zip(prompts, (seeded, greedy))
    ]
    # submit both to the spec engine concurrently (mixed batch)
    ids = [
        spec_engine.add_request(prompt_token_ids=p, sampling=s)
        for p, s in zip(prompts, (seeded, greedy))
    ]
    outs = {i: [] for i in ids}
    while spec_engine.has_unfinished():
        for out in spec_engine.step():
            outs[out.request_id].extend(out.new_token_ids)
    assert [outs[i] for i in ids] == plain


def test_spec_respects_max_tokens_and_stops():
    """Accepted runs must clip at max_tokens and at stop tokens even when a
    whole proposal batch was accepted."""
    rng = np.random.RandomState(2)
    base = list(rng.randint(1, 500, size=5))
    engine = _build(4)
    out = engine.generate(
        [base * 4],
        SamplingParams(max_tokens=3, temperature=0.0, ignore_eos=True),
    )[0]
    assert len(out["token_ids"]) == 3

    # stop token: find what greedy generates first, then stop on it
    probe = engine.generate(
        [base * 4],
        SamplingParams(max_tokens=1, temperature=0.0, ignore_eos=True),
    )[0]["token_ids"][0]
    out = engine.generate(
        [base * 4],
        SamplingParams(
            max_tokens=8, temperature=0.0, ignore_eos=True,
            stop_token_ids=[probe],
        ),
    )[0]
    assert out["token_ids"][-1] == probe
    assert len(out["token_ids"]) == 1


def test_spec_sole_request_near_pool_exhaustion_finishes():
    """Round-1's scheduler livelock lesson, verify-path edition: a sole
    greedy request whose proposal would overrun the pool must shrink its
    proposal instead of self-preempting forever."""
    rng = np.random.RandomState(3)
    base = list(rng.randint(1, 500, size=4))
    engine = LLMEngine(
        EngineConfig(
            model=ModelConfig.tiny(),
            cache=CacheConfig(block_size=8, num_blocks=6),  # 5 usable blocks
            scheduler=SchedulerConfig(
                max_num_seqs=2, max_num_batched_tokens=16,
                decode_buckets=(2,), prefill_buckets=(16,),
                decode_window=4, num_speculative_tokens=4,
            ),
        )
    )
    # prompt 16 = 2 blocks; 24 more tokens stretch to the 5-block limit
    out = engine.generate(
        [base * 4],
        SamplingParams(max_tokens=22, temperature=0.0, ignore_eos=True),
    )[0]
    assert len(out["token_ids"]) == 22
    assert engine.scheduler.total_preemptions < 50


# -- composition with the pipelined step loop (docs/36) ----------------------
#
# What a random tiny model emits decides nothing here: the proposer is
# scripted from the plain engine's greedy stream, so which proposals are
# accepted — and with them every chained verify, rollback and counter — is
# what the test says it is.

VOCAB = ModelConfig.tiny().vocab_size


def _n_accept(pattern: str, base: int, k: int) -> int:
    """How many leading tokens of the proposal that starts at sequence
    index `base` are the true ones. A function of the position alone, so
    both loops — and a re-proposal after a rollback — script the same
    proposal for the same place in the stream."""
    if pattern == "all":
        return k
    if pattern == "none":
        return 0
    if pattern == "first_j":
        return k // 2
    assert pattern == "alternating"  # k+1 positions accepted, k+1 refused
    return k if (base // (k + 1)) % 2 == 0 else 0


class ScriptedProposer:
    """The scheduler's draft-proposer protocol (`propose_batch`, `release`),
    answered from recorded greedy streams: the true next k tokens are
    accepted whole, the true tokens with position j replaced are accepted up
    to j. A chained row (`spec_tails`) continues after its in-flight
    proposals AND the in-flight bonus token nobody knows yet."""

    def __init__(self, truth: dict[tuple, list[int]], pattern: str):
        self.truth = truth  # prompt -> prompt + greedy stream
        self.pattern = pattern
        self.chained_calls = 0

    def propose_batch(self, reqs, k, spec_tails=None):
        spec_tails = spec_tails or {}
        out = {}
        for req in reqs:
            full = self.truth[tuple(req.prompt_token_ids)]
            base = len(req.all_token_ids)
            if req.request_id in spec_tails:
                base += len(spec_tails[req.request_id]) + 1
                self.chained_calls += 1
            proposal = full[base:base + k]
            assert len(proposal) == k, "recorded stream too short"
            j = _n_accept(self.pattern, base, k)
            if j < k:
                proposal[j] = (proposal[j] + 1) % VOCAB
            out[req.request_id] = proposal
        return out

    def release(self, rid):
        pass


def _script_implies(pattern, n_prompt, k, max_tokens):
    """(proposed, accepted, rollbacks) of ONE greedy row under `pattern`:
    the serial loop's verify steps, and on the pipelined loop the chained
    steps a partial acceptance discards. A step is chained on a verify
    unless full acceptance would already finish the row."""
    proposed = accepted = rollbacks = 0
    n_out = 1  # the prefill's token
    while n_out < max_tokens:
        j = _n_accept(pattern, n_prompt + n_out, k)
        emitted = min(j + 1, max_tokens - n_out)
        proposed += k
        accepted += emitted - 1
        if n_out + k + 1 < max_tokens and emitted < k + 1:
            rollbacks += 1
        n_out += emitted
    return proposed, accepted, rollbacks


def _record_truth(prompts, n_tokens):
    """prompt -> prompt + the plain engine's greedy stream."""
    plain = _build(0)
    try:
        greedy = SamplingParams(
            max_tokens=n_tokens, temperature=0.0, ignore_eos=True
        )
        outs = plain.generate(prompts, greedy)
    finally:
        _shutdown(plain)
    return {
        tuple(p): list(p) + o["token_ids"] for p, o in zip(prompts, outs)
    }


def _true_stream(truth, prompt, n):
    return truth[tuple(prompt)][len(prompt):][:n]


def _spec_counters(eng):
    sch = eng.scheduler
    return (
        sch.spec_proposed_tokens, sch.spec_accepted_tokens,
        eng.timing["rollback_n"],
    )


SCRIPT_MAX_TOKENS = 24
SCRIPT_PROMPT = [int(t) for t in np.random.RandomState(21).randint(1, 500, 9)]


@pytest.fixture(scope="module")
def scripted():
    """The recorded stream of SCRIPT_PROMPT and, per k, a serial and a
    pipelined speculative engine (built once: the cases swap proposers)."""
    truth = _record_truth([SCRIPT_PROMPT], SCRIPT_MAX_TOKENS + 8)
    engines: dict = {}

    def get(k):
        if k not in engines:
            engines[k] = (_build(k, async_on=False), _build(k, async_on=True))
        return engines[k]

    yield truth, get
    for pair in engines.values():
        _shutdown(*pair)


@pytest.mark.parametrize("pattern,k", [
    (pattern, k)
    for pattern in ("all", "none", "first_j", "alternating")
    for k in (1, 3, 4)
    if (pattern, k) != ("first_j", 1)  # first 0 of 1 is the `none` pattern
])
def test_scripted_acceptance_serial_pipelined_exact(scripted, pattern, k):
    """Acceptance pattern x k on both loops: the stream is the plain
    engine's, bitwise; proposed / accepted tokens are what the script
    implies on BOTH loops; the pipelined loop chained verify on verify and
    rolled back exactly the chained steps a partial acceptance invalidated."""
    truth, get = scripted
    serial, pipe = get(k)
    greedy = [SamplingParams(
        max_tokens=SCRIPT_MAX_TOKENS, temperature=0.0, ignore_eos=True
    )]
    want = _true_stream(truth, SCRIPT_PROMPT, SCRIPT_MAX_TOKENS)
    proposed, accepted, rollbacks = _script_implies(
        pattern, len(SCRIPT_PROMPT), k, SCRIPT_MAX_TOKENS
    )
    for eng in (serial, pipe):
        proposer = ScriptedProposer(truth, pattern)
        eng.scheduler.draft_proposer = proposer
        before = _spec_counters(eng)
        got = _streams(eng, [SCRIPT_PROMPT], greedy)
        delta = tuple(
            b - a for a, b in zip(before, _spec_counters(eng))
        )
        assert got == [want]
        want_rollbacks = rollbacks if eng is pipe else 0
        assert delta == (proposed, accepted, want_rollbacks), (
            pattern, k, eng is pipe
        )
        assert proposed > 0
        # the chained verify ran: the script was asked past an in-flight
        # verify's proposals — on the pipelined loop only
        assert (proposer.chained_calls > 0) == (eng is pipe)
        bal = eng.goodput_balance()
        assert bal["balanced"] and bal["pending"] == 0, bal


def test_serial_pipelined_equivalence_with_speculation():
    """The PR 1 equivalence bar, speculation active: greedy AND seeded
    sampled rows in one batch must produce bitwise-identical streams on
    the serial and pipelined loops — verify dispatches are in-flight
    pipeline work now, and a partial acceptance is just another rollback.
    The scripted proposer fires for every greedy row on both loops."""
    rng = np.random.RandomState(7)
    prompts = [
        [int(t) for t in rng.randint(1, 500, size=n)] for n in (18, 9, 16)
    ]
    sampling = [
        SamplingParams(max_tokens=18, temperature=0.0, ignore_eos=True),
        SamplingParams(
            max_tokens=14, temperature=0.8, seed=99, ignore_eos=True
        ),
        SamplingParams(max_tokens=18, temperature=0.0, ignore_eos=True),
    ]
    truth = _record_truth([prompts[0], prompts[2]], 18 + 8)
    serial = _build(3, async_on=False)
    pipe = _build(3, async_on=True)
    try:
        for eng in (serial, pipe):
            eng.scheduler.draft_proposer = ScriptedProposer(
                truth, "alternating"
            )
        s = _streams(serial, prompts, sampling)
        p = _streams(pipe, prompts, sampling)
        assert p == s
        for i in (0, 2):  # speculation is lossless for the greedy rows
            assert s[i] == _true_stream(truth, prompts[i], 18)
        # the pipeline actually pipelined (overlap accrued) and the spec
        # path actually fired on both loops, verify chained on verify
        assert pipe.timing["overlap_s"] > 0
        assert serial.scheduler.spec_proposed_tokens > 0
        assert pipe.scheduler.spec_proposed_tokens > 0
        assert pipe.scheduler.draft_proposer.chained_calls > 0
        # a row's verify steps start where its stream says, on either
        # loop: the counters agree
        assert _spec_counters(pipe)[:2] == _spec_counters(serial)[:2]
    finally:
        _shutdown(serial, pipe)


def test_partial_acceptance_trims_inflight_chain():
    """A verify chained on top of an in-flight verify speculates full
    acceptance; a partial acceptance at resolve time must discard it
    (rollback_n) and re-dispatch — with the stream still bitwise equal to
    the serial speculative loop. The script accepts the first 2 of every 4
    proposed tokens, so every chained step is trimmed."""
    greedy = [SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True)]
    prompt = [int(t) for t in np.random.RandomState(100).randint(1, 500, 20)]
    truth = _record_truth([prompt], 24 + 8)
    serial = _build(4, async_on=False)
    pipe = _build(4, async_on=True)
    try:
        for eng in (serial, pipe):
            eng.scheduler.draft_proposer = ScriptedProposer(truth, "first_j")
        want = _streams(serial, [prompt], greedy)
        got = _streams(pipe, [prompt], greedy)
        assert got == want == [_true_stream(truth, prompt, 24)]
        proposed, accepted, rollbacks = _script_implies(
            "first_j", len(prompt), 4, 24
        )
        assert proposed > accepted > 0 and rollbacks > 0
        assert _spec_counters(serial) == (proposed, accepted, 0)
        assert _spec_counters(pipe) == (proposed, accepted, rollbacks)
        assert pipe.scheduler.draft_proposer.chained_calls > 0
    finally:
        _shutdown(serial, pipe)


def test_abort_while_chained_verify_in_flight():
    """abort_request lands while a verify chained on a verify is executing:
    the aborted row's in-flight tokens are pipeline waste, the other row's
    stream stays the plain engine's, and the ledger still balances."""
    rng = np.random.RandomState(31)
    prompts = [
        [int(t) for t in rng.randint(1, 500, size=n)] for n in (12, 10)
    ]
    greedy = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True)
    truth = _record_truth(prompts, 24 + 8)
    pipe = _build(3, async_on=True)
    try:
        pipe.scheduler.draft_proposer = ScriptedProposer(truth, "all")
        doomed, kept = (
            pipe.add_request(prompt_token_ids=p, sampling=greedy)
            for p in prompts
        )
        got: list[int] = []

        def step():
            for out in pipe.step():
                if out.request_id == kept:
                    got.extend(out.new_token_ids)

        def chained_in_flight():
            work = pipe._inflight.work if pipe._inflight else None
            return isinstance(work, VerifyWork) and any(
                src >= 0 and req.request_id == doomed
                for src, req in zip(work.chain_rows, work.requests)
            )

        while not chained_in_flight():
            assert pipe.has_unfinished(), "no verify ever chained"
            step()
        pipe.abort_request(doomed)
        while pipe.has_unfinished():
            step()
        assert got == _true_stream(truth, prompts[1], 24)
        bal = pipe.goodput_balance()
        assert bal["balanced"] and bal["pending"] == 0, bal
        assert bal["wasted"]["rollback"] > 0  # the aborted row's verify
    finally:
        _shutdown(pipe)


def test_ledger_exact_with_rejections_on_both_loops():
    """GoodputLedger partition exactness at quiescence with speculative
    rejections charged as wasted{rollback} — on the serial AND pipelined
    loops, with a scripted proposer whose every proposal is refused from
    its second token on, and with the draft-model proposer."""
    rng = np.random.RandomState(11)
    prompts = [
        [int(t) for t in rng.randint(1, 500, size=n)] for n in (18, 8)
    ]
    greedy = SamplingParams(max_tokens=16, temperature=0.0, ignore_eos=True)
    truth = _record_truth(prompts, 16 + 8)
    # the draft deliberately DIFFERS from the target (3 layers vs 2) so
    # draft rejections actually occur
    target = ModelConfig.tiny(num_layers=3)
    for async_on in (False, True):
        for method, draft, model in (
            ("scripted", "", None),
            ("draft", "tiny-llama", target),
        ):
            eng = _build(
                3, async_on=async_on, draft=draft, model=model,
                method="draft" if draft else "ngram",
            )
            if method == "scripted":
                eng.scheduler.draft_proposer = ScriptedProposer(
                    truth, "first_j"
                )
            try:
                eng.generate(prompts, greedy)
                bal = eng.goodput_balance()
                assert bal["balanced"], (method, async_on, bal)
                assert bal["pending"] == 0
                sch = eng.scheduler
                if method == "scripted":
                    assert sch.spec_proposed_tokens > sch.spec_accepted_tokens
                if sch.spec_proposed_tokens > sch.spec_accepted_tokens:
                    assert bal["wasted"]["rollback"] > 0
            finally:
                _shutdown(eng)


# -- draft-model proposer ----------------------------------------------------


def test_draft_proposer_matches_plain_greedy_and_attributes():
    """Draft-model speculation is lossless for greedy, and acceptance
    attributes under proposer=draft. An identical-weights draft (same
    tiny config + same seed) must be accepted at ~full rate — the proof
    that the draft's catch-up/KV state machine tracks the target."""
    rng = np.random.RandomState(5)
    prompts = [
        list(rng.randint(1, 500, size=9)),
        list(rng.randint(1, 500, size=12)),
    ]
    greedy = SamplingParams(max_tokens=16, temperature=0.0, ignore_eos=True)
    plain = _build(0)
    eng = _build(3, method="draft", draft="tiny-llama")
    try:
        ref = [r["token_ids"] for r in plain.generate(prompts, greedy)]
        got = [r["token_ids"] for r in eng.generate(prompts, greedy)]
        assert got == ref
        sch = eng.scheduler
        assert sch.spec_proposed_by["draft"] > 0
        assert sch.spec_proposed_by["ngram"] == 0
        # identical weights → the draft predicts the target's argmax:
        # near-total acceptance (ties/clipping allow a little slack)
        assert (
            sch.spec_accepted_by["draft"]
            >= 0.8 * sch.spec_proposed_by["draft"]
        )
    finally:
        _shutdown(plain, eng)


@pytest.mark.parametrize("sizes", [(20, 25), (40, 30, 9), (31, 31, 31, 31)])
def test_draft_catch_up_fits_the_largest_prefill_program(sizes):
    """The draft's catch-up feed is built outside the scheduler's token
    budget: on a first proposal every row brings its whole prompt. Rows
    whose tokens SUM past the draft runner's largest prefill bucket (32
    here) must be split over dispatches (`ModelRunner.prefill_row_room`),
    not handed to one program that does not exist; streams stay the plain
    greedy ones and the identical-weights draft is accepted."""
    rng = np.random.RandomState(11)
    prompts = [list(rng.randint(1, 500, size=n)) for n in sizes]
    greedy = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    plain = _build(0)
    eng = _build(3, method="draft", draft="tiny-llama")
    seen: list[int] = []
    execute = eng.draft_runner.execute

    def spy(work):
        if isinstance(work, PrefillWork):
            seen.append(sum(len(t) for t in work.token_ids))
        return execute(work)

    eng.draft_runner.execute = spy
    try:
        ref = [r["token_ids"] for r in plain.generate(prompts, greedy)]
        got = [r["token_ids"] for r in eng.generate(prompts, greedy)]
        assert got == ref
        assert seen and max(seen) <= 32
        # the rows really were combined up to the cap, not fed one by one
        assert max(seen) > max(min(sizes), 16)
        sch = eng.scheduler
        assert (
            sch.spec_accepted_by["draft"]
            >= 0.8 * sch.spec_proposed_by["draft"]
        )
    finally:
        _shutdown(plain, eng)


def test_draft_blocks_never_content_addressed():
    """KV-pool isolation: draft scratch blocks share the allocator but
    must never become matchable — no prefix match, /kv/lookup walk, or
    peer residency check can ever return one (they are never registered,
    so no hash chain points at them)."""
    rng = np.random.RandomState(6)
    prompt = list(rng.randint(1, 500, size=10))
    greedy = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
    eng = _build(3, method="draft", draft="tiny-llama")
    try:
        rid = eng.add_request(prompt_token_ids=prompt, sampling=greedy)
        pool = eng.scheduler.pool
        proposer = eng.scheduler.draft_proposer
        saw_scratch = False
        while eng.has_unfinished():
            eng.step()
            scratch = {
                blk
                for st in proposer._states.values()
                for blk in st.block_table
            }
            if scratch:
                saw_scratch = True
                # never registered: no content hash maps to a draft block
                assert not scratch & set(pool._hash_to_block.values())
                assert not scratch & set(pool._block_to_hash)
                # the cluster/peer-visible hash set can't name them either
                assert scratch.isdisjoint(
                    pool._hash_to_block.get(h)
                    for h in pool.published_hashes()
                )
        assert saw_scratch, "draft proposer never held scratch blocks"
        del rid
        # a fresh identical prompt's prefix match returns only REGISTERED
        # (target-computed) blocks; all draft scratch was released
        assert proposer._states == {}  # released at finish
        assert pool.scratch_blocks == 0
        matched = pool.match_prefix(list(prompt), parent=pool.root_hash())
        for blk in matched:
            assert blk in pool._block_to_hash
            pool.free_block(blk)
    finally:
        _shutdown(eng)


def test_preempt_and_abort_mid_draft():
    """A request leaving the scheduler mid-draft (preemption or abort)
    must release its draft scratch blocks, keep the ledger partition
    exact, and — for preemption — still finish with the exact greedy
    stream (the draft state rebuilds via catch-up at re-admission)."""
    rng = np.random.RandomState(8)
    prompts = [
        list(rng.randint(1, 500, size=9)),
        list(rng.randint(1, 500, size=9)),
    ]
    greedy = SamplingParams(max_tokens=14, temperature=0.0, ignore_eos=True)
    plain = _build(0, async_on=False)
    eng = _build(3, async_on=False, method="draft", draft="tiny-llama")
    try:
        ref = [r["token_ids"] for r in plain.generate(prompts, greedy)]
        ids = [
            eng.add_request(prompt_token_ids=p, sampling=greedy)
            for p in prompts
        ]
        got = {i: [] for i in ids}
        preempted = aborted = False
        while eng.has_unfinished():
            for out in eng.step():
                got[out.request_id].extend(out.new_token_ids)
            states = eng.scheduler.draft_proposer._states
            if not preempted and ids[0] in states:
                victim = next(
                    (
                        r
                        for r in eng.scheduler.running
                        if r.request_id == ids[0] and r.prefill_done
                    ),
                    None,
                )
                if victim is not None:
                    eng.scheduler._preempt(victim)
                    # the seat's draft state died with it
                    assert ids[0] not in states
                    preempted = True
            if preempted and not aborted and ids[1] in states:
                assert eng.abort_request(ids[1])
                assert ids[1] not in states  # released by the abort finish
                aborted = True
        assert preempted and aborted
        # the preempted request recomputed to the exact same greedy stream
        assert got[ids[0]] == ref[0]
        # the aborted one delivered a strict prefix
        assert ref[1][: len(got[ids[1]])] == got[ids[1]]
        assert eng.scheduler.pool.scratch_blocks == 0
        bal = eng.goodput_balance()
        assert bal["balanced"] and bal["pending"] == 0
    finally:
        _shutdown(plain, eng)


def test_draft_config_validation():
    from dataclasses import replace

    cfg = EngineConfig.tiny()
    with pytest.raises(ValueError, match="--draft-model"):
        replace(
            cfg.scheduler, num_speculative_tokens=2,
            speculative_method="draft",
        )
    with pytest.raises(ValueError, match="speculative_method"):
        replace(cfg.scheduler, speculative_method="nope")


def test_spec_counters_and_exporter_labels():
    """The per-proposer counters ride the metric contract: closed label
    set, exporter-seeded at zero, rendered from the snapshot."""
    from vllm_production_stack_tpu import metrics_contract as mc
    from vllm_production_stack_tpu.engine.metrics import EngineMetrics

    rng = np.random.RandomState(9)
    base = list(rng.randint(1, 500, size=6))
    eng = _build(3)
    try:
        eng.generate(
            [base * 3],
            SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True),
        )
        snap = eng.stats()
        assert snap.spec_proposed_by["ngram"] == (
            eng.scheduler.spec_proposed_by["ngram"]
        )
        text = EngineMetrics("tiny-llama").render(snap).decode()
        for proposer in mc.SPEC_PROPOSER_VALUES:
            assert f'proposer="{proposer}"' in text
        base_name = mc.SPEC_PROPOSED_TOKENS[: -len("_total")]
        assert base_name in text
    finally:
        _shutdown(eng)


def test_draft_vocab_must_match_target():
    """The proposer contract is a SHARED tokenizer: a draft whose vocab
    differs from the target's is rejected at engine construction in BOTH
    directions — a larger draft vocab can propose ids the target's
    embedding gather silently clamps (garbage KV, not an error), a
    smaller one cannot ingest every target id at catch-up."""
    with pytest.raises(ValueError, match="vocab"):
        _build(2, method="draft", draft="llama-1b")


def test_draft_proposal_memo_skips_redundant_dispatch():
    """The scheduler's verify/decode alternation can discard a whole
    propose_batch after the draft model already ran (the plain group won
    the turn); the proposer's memo answers the next identical ask without
    re-dispatching, and invalidates as soon as the sequence advances."""
    eng = _build(3, method="draft", draft="tiny-llama")
    try:
        proposer = eng.scheduler.draft_proposer
        calls = []
        real = proposer.runner.execute
        proposer.runner.execute = lambda w: (calls.append(w) or real(w))

        class _Row:
            request_id = "memo-row"
            all_token_ids = [3, 5, 7, 9, 11]

        first = proposer.propose_batch([_Row()], k=3)
        n = len(calls)
        assert n > 0 and len(first["memo-row"]) == 3
        again = proposer.propose_batch([_Row()], k=3)
        assert again == first
        assert len(calls) == n  # memo hit: zero draft dispatches
        # the sequence advancing (a verify resolved) invalidates the memo
        _Row.all_token_ids = _Row.all_token_ids + first["memo-row"][:1]
        moved = proposer.propose_batch([_Row()], k=3)
        assert len(moved["memo-row"]) == 3
        assert len(calls) > n
        proposer.release("memo-row")
        assert eng.scheduler.pool.scratch_blocks == 0
    finally:
        _shutdown(eng)
