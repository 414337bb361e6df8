"""Generation by diffusion over blocks (SDAR, `model_type: sdar_moe`, ISSUE 41)
at tiny size on the CPU, float32: the served engine — block mask in the packed
prefill, windows of block passes on the device, the transfer rule, the
scheduler's prefill without a token and tails — against the plain reference's
own generation procedure (testing/reference_sdar.py) token for token through
both attention backends and both step loops; the logits through the cache
against the reference's forward; engines that are wrong in one way each; the
refusals, the counters and the names; and the other models' step programs
against the parent's, byte for byte."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import engine_kit as ek
from vllm_production_stack_tpu.engine import model_runner
from vllm_production_stack_tpu.engine.request import SamplingParams
from vllm_production_stack_tpu.models import llama
from vllm_production_stack_tpu.models.registry import resolve_model_config
from vllm_production_stack_tpu.testing import reference_check
from vllm_production_stack_tpu.testing import reference_sdar as reference

# float32 on both sides; what differs is the order of sums (paged flash
# attention against one softmax, routed pairs against every expert): 4.8e-7
# measured. The engines that are wrong in one way read 0.2-0.6
# (test_a_faulty_engine_is_outside_the_limit).
LOGPROB_ATOL = 2e-5

BACKENDS = ("xla", "pallas_interpret")


@pytest.fixture(scope="module")
def cfg():
    return resolve_model_config("tiny-sdar")


# what this file's engines differ in from the kit's: a decode window of two
# blocks of 4
WINDOW = dict(decode_window=8)


def _reference_tokens(cfg, engine, prompt, g, **keys):
    ref_cfg = {**reference.published_keys(cfg), **keys}
    # (the reference reads (in, out) matrices: a view of the served tree)
    return reference.generate(
        ref_cfg, reference_check.reference_tree(engine.runner.params),
        prompt, g)


def _drive(engine, arrivals):
    """Run `arrivals` = [(step() call index, prompt, sampling)] to the end;
    {index in arrivals: tokens}."""
    ids, got, call = {}, {}, 0
    pending = sorted(enumerate(arrivals), key=lambda x: x[1][0])
    while pending or engine.has_unfinished():
        while pending and pending[0][1][0] <= call:
            i, (_, prompt, sampling) = pending.pop(0)
            ids[engine.add_request(
                prompt_token_ids=prompt, sampling=sampling)] = i
            got[i] = []
        for out in engine.step():
            got[ids[out.request_id]].extend(out.new_token_ids)
        call += 1
    return got


# -- the engine against the reference's generation procedure ----------------

_SHAPES = [(p, g) for p in (8, 9, 14, 23) for g in (1, 2, 5, 8, 13)]


@pytest.mark.parametrize("backend,p,g", [
    *(("xla", p, g) for p, g in _SHAPES),
    ("pallas_interpret", 8, 5), ("pallas_interpret", 9, 13),
    ("pallas_interpret", 14, 1), ("pallas_interpret", 23, 8),
    ("pallas_interpret", 3, 2)])
def test_tokens_equal_the_reference(cfg, kit, backend, p, g):
    """A prompt of p tokens (p mod 4 = 0, 1, 2, 3; one shorter than a block:
    nothing to prefill), g tokens wanted: the prefill ends at p // 4 * 4 and
    yields no token, the first window is given the p mod 4 tail, the last
    is cut at g — token for token the reference's `generate`."""
    engine, _ = kit.engine(ek.engine_config(cfg, backend, **WINDOW))
    (prompt,) = ek.prompts(cfg, [p], seed=p * 31 + g)
    (out,) = engine.generate([prompt], ek.greedy(g))
    want = _reference_tokens(cfg, engine, prompt, g)
    assert out["token_ids"] == want["tokens"]
    assert out["finish_reason"] == "length"
    assert len(out["token_ids"]) == g  # usage.completion_tokens == max_tokens


@pytest.mark.parametrize("backend,loop", [
    ("xla", "pipelined"), ("xla", "serial")])
def test_rows_in_flight_at_different_points(cfg, kit, backend, loop):
    """Six rows over four seats: prompts of every tail length, rows that
    join while others are mid-answer (their first window beside the others'
    later ones), rows that leave mid-window (max_tokens 3, 6: not whole
    blocks), a prompt of two chunks and two short prompts packed in one
    chunk — each row's tokens equal the reference's, whatever the others
    do, through the pipelined loop and the serial one."""
    engine, before = kit.engine(
        ek.engine_config(cfg, backend, **WINDOW), serial=loop == "serial")
    lengths, wants = (9, 8, 2, 45, 30, 17), (13, 6, 16, 9, 3, 21)
    prompts = ek.prompts(cfg, lengths)
    got = _drive(engine, [
        (at, prompt, ek.greedy(g)) for at, prompt, g in zip(
            (0, 0, 0, 2, 3, 5), prompts, wants)])
    for i, (prompt, g) in enumerate(zip(prompts, wants)):
        assert got[i] == _reference_tokens(
            cfg, engine, prompt, g)["tokens"], (i, lengths[i], g)
    timing = ek.since(engine, before)
    # rows in lockstep: some repeated a pass over a block that was final
    assert timing["bd_repeat_row_passes_n"] > 0
    assert timing["bd_tokens_n"] >= sum(wants)
    assert timing["decode_tokens"] == sum(wants)  # tokens DELIVERED


def test_both_branches_of_the_dynamic_rule_fire(cfg, kit):
    """tiny-sdar's threshold lies inside the range of its random weights'
    confidences: some passes unmask every position over it (|H| >= n_s,
    more than the schedule's one token), others fall back to the most
    confident one (|H| < n_s) — in one run, and the tokens still equal the
    reference's."""
    engine, before = kit.engine(ek.engine_config(cfg, **WINDOW))
    prompts = ek.prompts(cfg, (9, 8, 30, 17), seed=0)
    outs = engine.generate(prompts, ek.greedy(13))
    d = ek.since(engine, before)
    assert d["bd_threshold_tokens_n"] > 0  # |H| >= n_s fired, with |H| > 1
    # ... and not always: the passes are more than two a block
    assert d["bd_row_passes_n"] - d["bd_repeat_row_passes_n"] > (
        2 * d["bd_blocks_n"])
    over = 0
    for prompt, out in zip(prompts, outs):
        want = _reference_tokens(cfg, engine, prompt, 13)
        assert out["token_ids"] == want["tokens"]
        over += want["threshold_tokens"]
    assert over > 0 and any(
        p for prompt in prompts for p in _reference_tokens(
            cfg, engine, prompt, 13)["passes"])


@pytest.mark.parametrize("strategy,steps", [
    ("low_confidence_static", 4), ("sequential", 4),
    ("low_confidence_static", 2), ("low_confidence_dynamic", 3)])
def test_the_other_rules_and_schedules(cfg, kit, strategy, steps):
    """`static` and `sequential`, and schedules of fewer passes than
    positions (2: two tokens a pass; 3: the remainder goes to the first
    pass), against the reference under the same settings."""
    model = dataclasses.replace(cfg, block_diffusion=dataclasses.replace(
        cfg.block_diffusion, remasking_strategy=strategy,
        denoising_steps=steps))
    engine, _ = kit.engine(ek.engine_config(model, **WINDOW))
    prompts = ek.prompts(model, (9, 30, 6), seed=3)
    for prompt, out in zip(prompts, engine.generate(prompts, ek.greedy(10))):
        want = _reference_tokens(model, engine, prompt, 10)
        assert out["token_ids"] == want["tokens"]
        if strategy == "sequential":  # left to right inside a block
            first = 4 - len(prompt) % 4
            for lo in (0, *range(first, 10, 4)):
                hi = first if lo == 0 else lo + 4
                assert want["passes"][lo:hi] == sorted(want["passes"][lo:hi])


def test_chunked_prefill_with_a_seam_and_two_rows_in_a_chunk(cfg, kit):
    """A token budget of 16: a 45-token prompt prefills in chunks of 16,
    16, 12 (its target is 44: seams on multiples of 4 from position 0), a
    21-token prompt in 16 + 4, and the short rows share a chunk — what the
    later chunks attend is what the earlier ones wrote under the block
    mask."""
    engine, _ = kit.engine(ek.engine_config(cfg, chunk=16, **WINDOW))
    prompts = ek.prompts(cfg, (45, 6, 5, 21), seed=7)
    outs = engine.generate(prompts, ek.greedy(9))
    for prompt, out in zip(prompts, outs):
        assert out["token_ids"] == _reference_tokens(
            cfg, engine, prompt, 9)["tokens"]
    chunks = [r for r in engine.flightrec.snapshot()
              if r.get("event") == "dispatch" and r.get("kind") == "prefill"]
    assert len(chunks) >= 3
    assert any(r["rows"] > 1 for r in chunks)


@pytest.mark.parametrize("backend", BACKENDS)
def test_logits_through_the_cache_against_forward(cfg, kit, backend):
    """The prefill's pages, every denoising pass's staged K/V and every
    commit, against the reference's cache-less forward over the same block
    states: the engine's logprobs of the tokens each pass unmasked, top-8
    and chosen, at 52 positions."""
    prompts = ek.prompts(cfg, (9, 8, 2, 23), seed=0)
    with reference_check.BlockTap() as tap:
        engine = kit.fresh_engine(ek.engine_config(cfg, backend, **WINDOW))
        served = reference_check.served_block_logprobs(engine, prompts, 13)
    out = reference_check.compare_blocks(
        reference, cfg, engine.runner.params, prompts, served, tap)
    assert out["positions"] == 13 * len(prompts)
    assert out["max_abs_dlogprob"] <= LOGPROB_ATOL, out
    assert out["order_agreement_share"] == 1.0, out
    if backend != "xla":
        return
    # a reference that is wrong in one way is told from it
    for wrong in ({"commit_pass": False}, {"shift": True}):
        bad = reference_check.compare_blocks(
            reference, cfg, engine.runner.params, prompts, served, tap,
            **wrong)
        assert bad["max_abs_dlogprob"] > 1000 * LOGPROB_ATOL, (wrong, bad)
    for wrong in ({"causal_mask": True}, {"qk_norm": False},
                  {"float32_statistics": False}):
        bad = reference_check.compare_blocks(
            reference, cfg, engine.runner.params, prompts, served, tap,
            ref_cfg={**reference.published_keys(cfg), **wrong})
        assert bad["max_abs_dlogprob"] > 1000 * LOGPROB_ATOL, (wrong, bad)


def _no_commit(monkeypatch):
    monkeypatch.setattr(model_runner, "_BD_COMMIT_PASS", False)


def _causal(monkeypatch):
    monkeypatch.setattr(llama, "_block_mask", lambda cfg: {})


def _shifted(monkeypatch):
    real = llama.bd_transfer

    def shifted(bd, logits, *rest):
        return real(bd, jnp.roll(logits, 1, axis=1), *rest)

    monkeypatch.setattr(llama, "bd_transfer", shifted)


@pytest.mark.parametrize("break_it", [_no_commit, _causal, _shifted])
def test_a_faulty_engine_is_outside_the_limit(
        cfg, kit, monkeypatch, break_it):
    """An engine that keeps the K/V of a block's last DENOISING pass (a
    fifth fewer passes, another model), one whose prefill masks causally,
    one that reads a token off its left neighbour's logits: its tokens
    differ from the reference's, and (the first two: the third's logprobs
    are its own positions') its logprobs lie outside the limit."""
    break_it(monkeypatch)
    prompts = ek.prompts(cfg, (9, 8, 23, 30), seed=0)
    with reference_check.BlockTap() as tap:
        engine = kit.fresh_engine(ek.engine_config(cfg, **WINDOW))
        served = reference_check.served_block_logprobs(engine, prompts, 13)
    assert any(
        got["token_ids"] != _reference_tokens(cfg, engine, p, 13)["tokens"]
        for p, got in zip(prompts, served))
    if break_it is not _shifted:
        out = reference_check.compare_blocks(
            reference, cfg, engine.runner.params, prompts, served, tap)
        assert out["max_abs_dlogprob"] > 1000 * LOGPROB_ATOL, out


@pytest.mark.parametrize("loop", ["pipelined", "serial"])
def test_a_stop_token_inside_a_block(cfg, kit, loop):
    """The answer is cut at the first stop token, found when the window
    comes back: here the 6th token, mid-block, while another row goes on.
    The pipelined loop has the next window in flight by then and rolls it
    back."""
    engine, _ = kit.engine(
        ek.engine_config(cfg, **WINDOW), serial=loop == "serial")
    prompts = ek.prompts(cfg, (9, 14), seed=11)
    full = _reference_tokens(cfg, engine, prompts[0], 24)["tokens"]
    stop = full[5]
    cut = full.index(stop) + 1
    got = _drive(engine, [
        (0, prompts[0], SamplingParams(
            temperature=0.0, max_tokens=24, ignore_eos=True,
            stop_token_ids=[stop])),
        (0, prompts[1], ek.greedy(24))])
    assert got[0] == full[:cut]
    assert got[1] == _reference_tokens(cfg, engine, prompts[1], 24)["tokens"]


@pytest.mark.parametrize("when", [1, 3])
def test_preemption_with_a_block_open(cfg, kit, when):
    """A row preempted while the tail of its prompt waits for its first
    window (after step 1: the prefill) or between two windows: its pages go
    back, the pool held committed blocks only, and it runs again from its
    last block boundary to the same tokens."""
    engine, _ = kit.engine(ek.engine_config(cfg, **WINDOW), serial=True)
    preempted = engine.scheduler.total_preemptions
    prompts = ek.prompts(cfg, (14, 9), seed=5)
    ids = [engine.add_request(prompt_token_ids=p, sampling=ek.greedy(18))
           for p in prompts]
    got = {i: [] for i in ids}
    call = 0
    while engine.has_unfinished():
        if call == when:
            victim = next(r for r in engine.scheduler.running
                          if r.request_id == ids[0])
            assert (victim.num_tokens - victim.num_computed_tokens
                    == (2 if when == 1 else 0))  # the open block's tail
            engine.scheduler._preempt(victim)
        for out in engine.step():
            got[out.request_id].extend(out.new_token_ids)
        call += 1
    assert engine.scheduler.total_preemptions == preempted + 1
    for i, prompt in zip(ids, prompts):
        assert got[i] == _reference_tokens(cfg, engine, prompt, 18)["tokens"]


def test_preemption_by_a_pool_that_runs_out(cfg, kit):
    """Four rows whose pool runs out under them
    (`engine_kit.generate_squeezed`): the newest row is preempted as the
    others grow, through the pipelined loop, and every answer is still the
    reference's."""
    engine, _ = kit.engine(ek.engine_config(cfg, **WINDOW))
    preempted = engine.scheduler.total_preemptions
    prompts = ek.prompts(cfg, (21, 18, 11, 14), seed=9)
    outs = ek.generate_squeezed(engine, prompts, max_tokens=20)
    assert engine.scheduler.total_preemptions > preempted
    for prompt, out in zip(prompts, outs):
        assert out == _reference_tokens(cfg, engine, prompt, 20)["tokens"]


def test_sampling_with_a_temperature(cfg, kit):
    """temperature > 0: the candidates are draws (seeded: the same twice),
    the confidence is the drawn token's probability at that temperature,
    and the answer has its length."""
    engine, _ = kit.engine(ek.engine_config(cfg, **WINDOW))
    (prompt,) = ek.prompts(cfg, [10], seed=2)
    sampling = SamplingParams(temperature=0.8, top_p=0.9, max_tokens=11,
                              ignore_eos=True, seed=1234)
    (a,) = engine.generate([prompt], sampling)
    (b,) = engine.generate([prompt], sampling)
    (greedy,) = engine.generate([prompt], ek.greedy(11))
    assert a["token_ids"] == b["token_ids"] and len(a["token_ids"]) == 11
    assert a["token_ids"] != greedy["token_ids"]
    assert all(0 <= t < cfg.vocab_size for t in a["token_ids"])


def test_one_token_with_logprobs_alone_and_among_others(cfg, kit):
    """The harness's batch-invariance probe: max_tokens 1 with logprobs 8,
    alone and among other rows — the token, its logprobs (those of the pass
    that unmasked it) and so the order of unmasking do not depend on the
    neighbours."""
    engine, _ = kit.engine(ek.engine_config(cfg, **WINDOW))
    prompts = ek.prompts(cfg, (13, 9, 30, 6), seed=4)
    probe = ek.greedy(1, logprobs=8)

    def run(rows):
        ids = [engine.add_request(prompt_token_ids=p, sampling=s)
               for p, s in rows]
        first = None
        while engine.has_unfinished():
            for out in engine.step():
                if out.request_id == ids[0] and out.new_token_ids:
                    first = (out.new_token_ids, out.new_logprobs)
        return first

    alone = run([(prompts[0], probe)])
    among = run([(prompts[0], probe)] + [(p, ek.greedy(9)) for p in prompts[1:]])
    assert alone[0] == among[0] and len(alone[0]) == 1
    assert alone[1][0][1] == among[1][0][1]  # the same top ids
    np.testing.assert_array_equal(alone[1][0][2], among[1][0][2])
    want = _reference_tokens(cfg, engine, prompts[0], 1)
    assert alone[0] == want["tokens"]
    assert abs(alone[1][0][0] - want["logprobs"][0][alone[0][0]]) < (
        LOGPROB_ATOL)
