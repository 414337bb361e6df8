"""The gated delta rule with ONE gate a head and a key width that is not the
value width (Gated DeltaNet, arXiv:2412.06464), three layers in four, beside
position-free full attention with a flat QK-norm, all in OLMo-2's post-norm
block (Olmo-Hybrid, ISSUE 58), at tiny size on the CPU: the served ENGINE
against the plain float32 reference (testing/reference_olmo_hybrid.py) —
prefill in packed chunks and fused decode windows, a seam inside a 64-token
kernel block, the served 12 x 24 state and the first full layer's pages —
through both backends; what the limits tell (each `assumed` alternative of
the reference, a bfloat16 state); preemption and recompute; a rolled-back
window. The parts (kernels, the per-channel tie, geometry, bytes, refusals,
names) are tests/test_olmo_hybrid_parts.py's."""

import numpy as np
import pytest

import engine_kit as ek
from vllm_production_stack_tpu.engine.request import SamplingParams
from vllm_production_stack_tpu.models.llama import page_pool
from vllm_production_stack_tpu.models.registry import resolve_model_config
from vllm_production_stack_tpu.testing import reference_check
from vllm_production_stack_tpu.testing import reference_olmo_hybrid as reference

# float32 on both sides. What differs is the order of sums: paged flash
# attention against one softmax, and through the kernels the chunked form of
# the recurrence against one token at a time (measured, PR 58: 1.2e-5 over
# 36 positions through the XLA forms, 1.0e-5 through the kernels). The
# gentlest `assumed` alternative (a QK-norm a head) reads 0.19, a state
# rounded to bfloat16 at every token 0.054
LOGPROB_ATOL = 5e-5
# the state a row leaves in its slot against the reference's recurrence over
# the same q, k, v, g, beta (reference_check.served_state): 1.4e-7 / 1.2e-7
# measured; a state rounded to bfloat16 at every token reads 6e-3
STATE_RTOL = 1e-5
# a row's pages of the first FULL layer (layer 3, behind three linear
# layers) against the reference's K and V: 1.9e-6 / 2.4e-6 measured
PAGES_RTOL = 1e-5

# chunks of 64 tokens (one block of the chunked kernel): rows of unequal
# length packed into one chunk put a seam INSIDE a block
CHUNK = dict(chunk=64)


@pytest.fixture(scope="module")
def cfg():
    return resolve_model_config("tiny-olmo-hybrid")


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
    windows over state slots in six layers and pages in two, against the
    reference's full forward: the convolution across chunks and seams at
    three widths (q, k at 12, v at 24), the 12 x 24 state carried from
    chunk to chunk and through the window's two banks, one gate a head, the
    SiLU output gate, the flat QK-norm, no position encoding, post-norms."""
    layer = cfg.linear_layers[-1]
    engine, prompts, served, tap = _serve_tapped(
        kit, cfg, backend, lengths, 12, layer)
    out = reference_check.compare(
        reference, cfg, engine.runner.params, prompts, served)
    assert out["positions"] == 12 * len(lengths)
    assert out["max_abs_dlogprob"] <= LOGPROB_ATOL, out
    leaf = engine.runner.kv_caches[layer]
    assert set(leaf) == {"s", "conv"}
    assert leaf["s"].shape[1:] == (6, 12, 24)  # (heads, keys, values)
    assert leaf["conv"].shape[1:] == (3, 6 * (2 * 12 + 24))
    state = reference_check.served_state(
        reference, cfg, tap, np.asarray(leaf["s"]))
    assert state["rows"] == len(lengths)
    assert state["state_rel_err"] <= STATE_RTOL, state
    first_full = cfg.page_layers[0]
    assert first_full == 3
    pages = reference_check.served_pages(
        reference, cfg, engine.runner.params,
        page_pool(engine.runner.kv_caches[first_full]), prompts, served)
    assert pages["pages_rel_err"] <= PAGES_RTOL, pages
    slots = engine.scheduler.pool.state
    assert slots.num_free == slots.num_usable  # every slot came back
    report = engine.runner.device_report()["mixers"]
    assert report["by_layer"] == 2 * (3 * ["linear"] + ["softmax"])
    assert (report["softmax"], report["linear"], report["ssd"]) == (2, 6, 0)
    backend_report = report["linear_backend"]
    kernels = backend != "xla"
    assert backend_report["prefill"] == (
        "gdn_prefill_attention" if kernels else "xla")
    assert backend_report["decode"] == (
        "gdn_decode_attention" if kernels else "xla")
    assert backend_report["state_geometry"] == [6, 12, 24]
    assert backend_report["gate_per"] == "head"
    assert backend_report["slot_bytes_a_layer"] == (
        6 * 12 * 24 * 4 + 3 * 6 * 48 * 4)
    timing = engine.flightrec.timing()
    assert timing["kda_prefill_tokens_n"] == sum(lengths)
    assert timing["kda_decode_rows_n"] > 0


@pytest.fixture(scope="module")
def served_once(cfg, kit):
    return _serve_tapped(
        kit, cfg, "xla", (70, 9, 40), 10, cfg.linear_layers[1])


# every `assumed` reading's alternative, and a lower-precision state
ARMS = [{"pre_norm_linear": True}, {"output_gate": "sigmoid"},
        {"rope_theta": 10000.0}, {"rope_theta": 500000.0},
        {"qk_norm_per_head": True}, {"state_dtype": "bfloat16"}]


@pytest.mark.parametrize("arm", ARMS, ids=lambda a: "-".join(
    f"{k}={v}" for k, v in a.items()))
def test_the_limit_tells_every_assumed_alternative(cfg, served_once, arm):
    """Each arm of the reference computes the alternative NOT taken of one
    `assumed` reading (a pre-norm linear block, a sigmoid output gate, a
    rope, a QK-norm a head) or a state rounded at every token: the served
    logprobs lie OUTSIDE the limit they lie inside against the reference
    proper — the reference wrong in one way at a time."""
    engine, prompts, served, _ = served_once
    out = reference_check.compare(
        reference, cfg, engine.runner.params, prompts, served,
        reference_arms=arm)
    assert out["max_abs_dlogprob"] > 20 * LOGPROB_ATOL, (arm, out)


def test_the_states_limit_tells_a_bfloat16_state(cfg, served_once):
    engine, _, _, tap = served_once
    pool = np.asarray(engine.runner.kv_caches[tap.layer]["s"])
    good = reference_check.served_state(reference, cfg, tap, pool)
    assert good["state_rel_err"] <= STATE_RTOL, good
    bad = reference_check.served_state(
        reference, cfg, tap, pool, state_dtype="bfloat16")
    assert bad["state_rel_err"] > 20 * STATE_RTOL, bad


def test_preemption_gives_pages_and_slot_back_and_the_same_tokens(cfg, kit):
    """A pool that runs out under both rows' growth preempts the newer one:
    it loses its pages and its slot, is computed again from its first token
    through every linear layer's state, and ends with the tokens of a run
    that never preempted."""
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
    slots = engine.scheduler.pool.state
    assert slots.num_free == slots.num_usable == 2


@pytest.mark.parametrize("ends", [(3, 5, 9, 12)])
def test_a_rolled_back_window_leaves_the_state_and_tail_it_read(
        cfg, kit, ends):
    """A row that meets a stop token in the middle of a decode window
    invalidates the window dispatched behind it: the device has advanced
    every row's 12 x 24 state and its convolution's tail, and the
    re-dispatch starts from the other bank. The pipelined loop's tokens are
    the serial loop's."""
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
