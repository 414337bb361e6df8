"""A layer that holds K/V pages AND a recurrent state at once: Mamba-2 (SSD)
heads beside attention heads in every block, both added to one residual
(Falcon-H1, ISSUE 50), at tiny size on the CPU: the served ENGINE against the
plain float32 reference (testing/reference_falcon_h1.py) — prefill and decode
logits, chunk seams, the served state — through both backends, what the
limits tell (each fault arm of the reference, a bfloat16 state), preemption
and recompute, a rolled-back window, and a row alone against a row in a
crowd. The parts (the recurrence, the kernels, groups, the gated norm, the
multipliers, bytes, refusals, names) are tests/test_falcon_h1_parts.py's.
"""

import numpy as np
import pytest

import engine_kit as ek
from vllm_production_stack_tpu.engine.request import SamplingParams
from vllm_production_stack_tpu.models.registry import resolve_model_config
from vllm_production_stack_tpu.testing import reference_check
from vllm_production_stack_tpu.testing import reference_falcon_h1 as reference

# float32 on both sides. What differs is the order of sums: paged flash
# attention against one softmax, and through the kernels the chunked form of
# the recurrence against one token at a time: 9.5e-7 measured over 40
# positions through the XLA forms and through the kernels alike (PR 50). A
# state rounded to bfloat16 at every token reads 1.7e-3, the gentlest fault
# arm (a branch's B and C groups exchanged) 0.4
# (test_the_limit_tells_every_fault_arm).
LOGPROB_ATOL = 2e-5
# the state a row leaves in its slot against the reference's recurrence over
# the same x, B, C, dt (reference_check.served_state): 5e-8 / 9e-8 measured;
# a state rounded to bfloat16 at every token reads 4.8e-3
STATE_RTOL = 1e-5

# a row's pages of the first layer against the reference's K and V
# (reference_check.served_pages): 1.7e-7 measured in float32; a float8 pool
# reads 2.9e-2 (and moves the logprobs by 0.016 at most)
PAGES_RTOL = 1e-5

# what this file's engines differ in from the kit's: chunks of 64 tokens
# (half a block of the SSD prefill kernel: a seam lies inside a block)
CHUNK = dict(chunk=64)


@pytest.fixture(scope="module")
def cfg():
    return resolve_model_config("tiny-falcon-h1")


def _serve_tapped(kit, cfg, backend, lengths, n_out, layer):
    prompts = ek.prompts(cfg, lengths)
    with reference_check.StateTap(layer) as tap:
        engine = kit.fresh_engine(ek.engine_config(cfg, backend, **CHUNK))
        served = reference_check.served_logprobs(engine, prompts, n_out)
    return engine, prompts, served, tap


@pytest.mark.parametrize("backend,lengths", [
    ("xla", (70, 9, 150)), ("pallas_interpret", (33, 150, 5))])
def test_engine_against_the_float32_reference(cfg, kit, backend, lengths):
    """Prefill in chunks of 64 (the 150-token prompt in three, the others
    packed beside it: a seam inside a kernel block), then fused decode
    windows through K/V pages AND state slots of the SAME layers, against
    the reference's full forward: the convolution with its bias across
    chunks and seams, the state carried from chunk to chunk and through the
    window, the two banks, B and C shared by a group's heads, the gate
    before the grouped norm, every multiplier."""
    layer = cfg.ssm_layers[-1]
    engine, prompts, served, tap = _serve_tapped(
        kit, cfg, backend, lengths, 12, layer)
    out = reference_check.compare(
        reference, cfg, engine.runner.params, prompts, served)
    assert out["positions"] == 12 * len(lengths)
    assert out["max_abs_dlogprob"] <= LOGPROB_ATOL, out
    leaf = engine.runner.kv_caches[layer]
    assert set(leaf) == {"kv", "s", "conv"}  # pages AND state, one layer
    state = reference_check.served_state(
        reference, cfg, tap, np.asarray(leaf["s"]),
        params=engine.runner.params)
    assert state["rows"] == len(lengths)
    assert state["state_rel_err"] <= STATE_RTOL, state
    slots = engine.scheduler.pool.state
    assert slots.num_free == slots.num_usable  # every slot came back
    report = engine.runner.device_report()["mixers"]
    assert report["by_layer"] == 3 * ["softmax+ssd"]
    assert (report["softmax"], report["ssd"], report["linear"]) == (3, 3, 0)
    kernels = backend != "xla"
    assert report["ssd_backend"]["prefill"] == (
        "ssd_prefill_scan" if kernels else "xla")
    assert report["ssd_backend"]["decode"] == (
        "ssd_decode_step" if kernels else "xla")


@pytest.fixture(scope="module")
def served_once(cfg, kit):
    return _serve_tapped(kit, cfg, "xla", (70, 9, 40), 10, cfg.ssm_layers[1])


ARMS = [
    {"state_dtype": "bfloat16"}, {"zero_branch": "attention"},
    {"zero_branch": "ssm"}, {"norm_before_gate": True},
    {"swap_groups": True}, {"no_d": True}, {"no_conv_bias": True},
    # (`attention_in_multiplier` is published as 1: leaving it out changes
    # nothing, which test_falcon_h1_parts.py shows on another value)
    *({"skip_multiplier": k} for k in reference.MULTIPLIER_KEYS
      if k != "attention_in_multiplier")]


@pytest.mark.parametrize("arm", ARMS, ids=lambda a: "-".join(
    f"{k}={v}" for k, v in a.items()))
def test_the_limit_tells_every_fault_arm(cfg, served_once, arm):
    """Each arm of the reference computes something ELSE (a lower-precision
    state, a branch zeroed, a multiplier or a term left out, the norm before
    the gate, the groups exchanged): the served logprobs lie OUTSIDE the
    limit they lie inside against the reference proper."""
    engine, prompts, served, _ = served_once
    out = reference_check.compare(
        reference, cfg, engine.runner.params, prompts, served,
        reference_arms=arm)
    assert out["max_abs_dlogprob"] > 20 * LOGPROB_ATOL, (arm, out)


def test_the_states_limit_tells_a_bfloat16_state(cfg, served_once):
    engine, _, _, tap = served_once
    pool = np.asarray(engine.runner.kv_caches[tap.layer]["s"])
    kw = dict(params=engine.runner.params)
    good = reference_check.served_state(reference, cfg, tap, pool, **kw)
    assert good["state_rel_err"] <= STATE_RTOL, good
    bad = reference_check.served_state(
        reference, cfg, tap, pool, state_dtype="bfloat16", **kw)
    assert bad["state_rel_err"] > 20 * STATE_RTOL, bad


def test_the_pages_limit_tells_a_float8_pool(cfg, kit, served_once):
    """The first layer's served pages, found through each row's block table,
    are the reference's K (times `key_multiplier`, rotated) and V of the
    tokens the row was fed; a float8 pool (`--kv-cache-dtype fp8`) serves
    the same state and fails that limit — by far, where its logprobs move
    by less than a bfloat16 engine's scatter."""
    from vllm_production_stack_tpu.engine.config import CacheConfig
    from vllm_production_stack_tpu.models.llama import page_pool

    engine, prompts, served, _ = served_once
    good = reference_check.served_pages(
        reference, cfg, engine.runner.params,
        page_pool(engine.runner.kv_caches[0]), prompts, served)
    assert (good["rows"], good["tokens"]) == (3, 70 + 9 + 40 + 3 * 9)
    assert good["pages_rel_err"] <= PAGES_RTOL, good
    fp8 = kit.fresh_engine(ek.engine_config(
        cfg, "xla", **CHUNK, cache=CacheConfig(
            block_size=8, num_blocks=128, enable_prefix_caching=False,
            kv_cache_dtype="fp8")))
    assert page_pool(fp8.runner.kv_caches[0]).dtype == "float8_e4m3fn"
    assert fp8.runner.kv_caches[0]["s"].dtype == "float32"
    served8 = reference_check.served_logprobs(fp8, prompts, 10)
    bad = reference_check.served_pages(
        reference, cfg, fp8.runner.params,
        page_pool(fp8.runner.kv_caches[0]), prompts, served8)
    assert bad["pages_rel_err"] > 1e-2, bad
    out = reference_check.compare(
        reference, cfg, fp8.runner.params, prompts, served8)
    assert out["max_abs_dlogprob"] < 0.05, out


def test_a_row_alone_among_fifteen_and_in_a_slot_just_left(cfg, kit):
    """The same prompt gives the same tokens alone, among 15 other rows (16
    seats, every slot and its pages held), and in the slot another row has
    just left."""
    prompt, others = ek.prompts(cfg, (90,))[0], ek.prompts(
        cfg, [20 + 7 * i for i in range(15)], seed=9)
    crowd, _ = kit.engine(
        ek.engine_config(cfg, seqs=16, num_blocks=512, **CHUNK))
    alone = ek.generate(crowd, [prompt])[0]
    assert ek.generate(crowd, [prompt] + others)[0] == alone
    one, _ = kit.engine(ek.engine_config(cfg, seqs=1, **CHUNK))
    assert one.scheduler.pool.state.num_usable == 1
    first = ek.generate(one, [others[3]])
    assert ek.generate(one, [prompt])[0] == alone  # the one slot, just left
    assert first[0] == ek.generate(crowd, [others[3]])[0]


def test_preemption_gives_pages_and_slot_back_and_the_same_tokens(cfg, kit):
    """A pool that runs out under both rows' growth preempts the newer one:
    it loses its pages AND its slot — of the same layers —, is computed
    again from its first token, and ends with the tokens of a run that never
    preempted."""
    prompts = ek.prompts(cfg, (60, 60), seed=4)
    engine, before = kit.engine(ek.engine_config(cfg, seqs=2, **CHUNK))
    want = ek.generate(engine, prompts, max_tokens=40)
    assert ek.since(engine, before)["state_recomputed_tokens_n"] == 0
    preempted = engine.scheduler.total_preemptions
    before = engine.flightrec.timing()
    got = ek.generate_squeezed(engine, prompts, max_tokens=40)
    assert engine.scheduler.total_preemptions > preempted
    assert got == want
    timing = ek.since(engine, before)
    assert timing["state_recomputed_tokens_n"] >= 60
    assert timing["state_slots_zeroed_n"] >= 3  # two rows, one again
    slots = engine.scheduler.pool.state
    assert slots.num_free == slots.num_usable == 2


@pytest.mark.parametrize("ends", [(3, 5, 9, 12)])
def test_a_rolled_back_window_leaves_the_state_it_read(cfg, kit, ends):
    """A row that meets a stop token in the middle of a decode window
    invalidates the window dispatched behind it: the device has advanced
    every row's state, and the re-dispatch starts from the other bank. The
    pipelined loop's tokens are the serial loop's."""
    prompts = ek.prompts(cfg, (30, 41, 52, 23), seed=8)
    config = ek.engine_config(cfg, **CHUNK)
    engine, _ = kit.engine(config)
    free = ek.generate(engine, prompts, 40)
    stops = [row[e] for row, e in zip(free, ends)]
    want = [row[:row.index(stop) + 1] for row, stop in zip(free, stops)]

    def run(engine):
        ids = [engine.add_request(
            prompt_token_ids=p, sampling=SamplingParams(
                max_tokens=40, temperature=0.0, stop_token_ids=(stop,)))
            for p, stop in zip(prompts, stops)]
        got = {i: [] for i in ids}
        while engine.has_unfinished():
            for out in engine.step():
                got[out.request_id].extend(out.new_token_ids)
        return [got[i] for i in ids]

    serial = run(kit.engine(config, serial=True)[0])
    free_run = engine.flightrec.timing()
    got = run(engine)
    assert ek.since(engine, free_run).get("rollback_n", 0) > 0
    assert got == serial == want
