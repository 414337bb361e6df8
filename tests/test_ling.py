"""Latent-attention (MLA) layers beside linear-attention (KDA) layers whose
recurrent state lives in state slots, a leading dense layer, group-limited
routing over a held share of the experts (Ling-3.0-flash's language model,
ISSUE 45) at tiny size on the CPU: the served ENGINE against the plain float32
reference (testing/reference_ling.py) — prefill and decode logits, chunk
seams, the served state — through both backends, what the tolerance tells,
preemption and recompute, and a row alone against a row in a crowd. The parts
(routing, gates, shares, bytes, refusals, names) are tests/test_ling_parts.py's.
"""

import types

import numpy as np
import pytest

import engine_kit as ek
from vllm_production_stack_tpu.models.registry import resolve_model_config
from vllm_production_stack_tpu.testing import reference_check
from vllm_production_stack_tpu.testing import reference_ling as reference

# float32 on both sides. What differs is the order of sums: paged flash
# attention over latents in the ABSORBED form against one softmax over
# expanded keys, routed pairs against every expert, and through the kernels
# the chunked form of the recurrence against one token at a time: 3.3e-6
# measured over 30 positions through the XLA forms, 2.9e-6 through the
# kernels (PR 45). A state rounded to bfloat16 at every token, a left-out
# gate or the unbounded decay gate read 20 x the limit and more
# (test_the_tolerance_tells_a_faulty_reference).
LOGPROB_ATOL = 5e-5
# the state a row leaves in its slot against the reference's recurrence over
# the same q, k, v, g, beta (reference_check.served_state): 8e-8 / 2e-7
# measured; a state rounded to bfloat16 at every token reads over 1e-3
STATE_RTOL = 1e-5

# what this file's engines differ in from the kit's: chunks of 64 tokens, a
# block of the KDA prefill kernel
CHUNK = dict(chunk=64)


@pytest.fixture(scope="module")
def cfg():
    return resolve_model_config("tiny-ling-flash")


@pytest.mark.parametrize("backend,lengths", [
    ("xla", (70, 9, 150)), ("pallas_interpret", (33, 150))])
def test_engine_against_the_float32_reference(cfg, kit, backend, lengths):
    """Prefill in chunks of 64 (the 150-token prompt in three, the others
    packed beside it: a seam lies inside a kernel block), then fused decode
    windows through latent pages AND state slots, against the reference's
    full forward: the convolution across chunks and seams, the state carried
    from chunk to chunk and through the window, the two banks, the bounded
    gate, the latent layer in its absorbed form with its head gate, the
    dense layer, group-limited routing over the held experts."""
    prompts = ek.prompts(cfg, lengths)
    with (reference_check.RoutingTap() as tap,
          reference_check.StateTap(cfg.linear_layers[-1]) as state_tap):
        engine = kit.fresh_engine(ek.engine_config(cfg, backend, **CHUNK))
        served = reference_check.served_logprobs(engine, prompts, 12)
    out = reference_check.compare(
        reference, cfg, engine.runner.params, prompts, served,
        served_routing=tap)
    assert out["positions"] == 12 * len(lengths)
    assert out["max_abs_dlogprob"] <= LOGPROB_ATOL, out
    assert out["routing_disagreement_share"] == 0.0, out
    # six expert layers were compared (the dense layer routes nothing)
    assert out["routing_pairs"] == 6 * sum(n + 11 for n in lengths)
    state = reference_check.served_state(
        reference, cfg, state_tap,
        np.asarray(engine.runner.kv_caches[state_tap.layer]["s"]))
    assert state["rows"] == len(lengths)
    assert state["state_rel_err"] <= STATE_RTOL, state
    slots = engine.scheduler.pool.state
    assert slots.num_free == slots.num_usable  # every slot came back
    report = engine.runner.device_report()
    assert report["mixers"]["by_layer"] == 5 * ["linear"] + [
        "latent", "linear"]
    assert (report["mixers"]["softmax"], report["mixers"]["latent"],
            report["mixers"]["linear"]) == (0, 1, 6)
    assert report["mixers"]["linear_backend"]["prefill"] == (
        "xla" if backend == "xla" else "kda_prefill_attention")
    # the kernels of BOTH kinds sit in one prefill and one decode program
    want = "pallas_interpret" if backend != "xla" else "xla"
    assert report["attention"]["prefill"]["resolved"] == want
    assert report["attention"]["decode"]["resolved"] == want


def _faulty(**edits):
    """reference_ling, wrong in one way (chip_smoke.py's idiom)."""

    def published_keys(model_cfg):
        return {**reference.published_keys(model_cfg), **edits}

    return types.SimpleNamespace(
        forward=reference.forward, published_keys=published_keys,
        delta_rule=reference.delta_rule, route=reference.route,
        check_config=reference.check_config, f32=reference.f32)


@pytest.fixture(scope="module")
def served_once(cfg, kit):
    """(params, prompts, served logprobs, routing tap, state tap, the tapped
    layer's state pool, router tap) of an XLA engine that served two
    prompts."""
    prompts = ek.prompts(cfg, (70, 33), seed=3)
    with (reference_check.RoutingTap() as tap,
          reference_check.StateTap(cfg.linear_layers[-1]) as state_tap,
          reference_check.RouterTap(cfg.num_moe_layers - 1) as router_tap):
        engine = kit.fresh_engine(ek.engine_config(cfg, **CHUNK))
        served = reference_check.served_logprobs(engine, prompts, 8)
    pool = np.asarray(engine.runner.kv_caches[state_tap.layer]["s"])
    return (engine.runner.params, prompts, served, tap, state_tap, pool,
            router_tap)


@pytest.mark.parametrize("edits", [
    {"gated_attention_proj_granularity_type": None},
    {"kda_safe_gate": False}, {"kda_beta_scale": 2.0},
    {"kda_state_inherited": True}, {"kda_conv_inherited": True},
    {"kda_state_dtype": "bfloat16"}, {"use_mla_rope": False}],
    ids=lambda e: next(iter(e)))
def test_the_tolerance_tells_a_faulty_reference(cfg, served_once, edits):
    """The latent layer's gate left out, the unbounded decay gate, beta
    with a factor 2, a state or a convolution history not zeroed at a row's
    first token, a state kept in bfloat16: each lies outside the tolerance
    the served engine is inside. (`use_mla_rope` is no key of the reference:
    an edit it does not read leaves it INSIDE, the control.)"""
    params, prompts, served, tap = served_once[:4]
    good = reference_check.compare(
        reference, cfg, params, prompts, served, served_routing=tap)
    bad = reference_check.compare(
        _faulty(**edits), cfg, params, prompts, served, served_routing=tap)
    assert good["max_abs_dlogprob"] <= LOGPROB_ATOL
    if "use_mla_rope" in edits:
        assert bad["max_abs_dlogprob"] == good["max_abs_dlogprob"]
    else:
        assert bad["max_abs_dlogprob"] > 20 * LOGPROB_ATOL, bad


def test_the_served_states_limit_tells_a_bfloat16_reference(
        cfg, served_once):
    """Logprobs aside, the STATE has a limit of its own: the served slots
    against the float32 recurrence are inside it, against a recurrence that
    rounds its state to bfloat16 at every token far outside."""
    state_tap, pool = served_once[4:6]
    good = reference_check.served_state(reference, cfg, state_tap, pool)
    bad = reference_check.served_state(
        _faulty(kda_state_dtype="bfloat16"), cfg, state_tap, pool)
    # (every token fed: the prompts and the decoded tokens but the last)
    assert good["rows"] == 2 and good["tokens"] >= 70 + 33 + 2 * 7
    assert good["state_rel_err"] <= STATE_RTOL, good
    assert bad["state_rel_err"] > 1e-3, bad


def test_the_served_routers_limit_tells_a_bfloat16_router(cfg, served_once):
    """The ROUTER has a limit of its own too (reference_check.served_router):
    the reference's `route` on the very inputs the served router of the last
    expert layer was fed chooses the same experts for every token, padding
    included; a router whose product and scores are bfloat16 (the nearest
    precision below the float32 the configuration states) chooses other
    experts for a share of them."""
    from vllm_production_stack_tpu.models.loader import LingCheckpointView

    params, router_tap = served_once[0], served_once[6]
    view = LingCheckpointView(cfg, params)
    good = reference_check.served_router(reference, cfg, router_tap, view)
    bad = reference_check.served_router(
        _faulty(router_dtype="bfloat16"), cfg, router_tap, view)
    assert good["moe_layer"] == 5 and good["tokens"] >= 70 + 33 + 2 * 7
    assert good["choice_disagreement_share"] == 0.0, good
    assert bad["choice_disagreement_share"] > 0.005, bad
    assert bad["choice_margin_max_where_differing"] > 0.0


def test_preemption_recomputes_latents_and_state_to_the_same_tokens(
        cfg, kit):
    """A pool that runs out under both rows' growth preempts the newer one
    (`engine_kit.generate_squeezed`): it loses its latent pages AND its
    state slot, is computed again from its first token, and ends with the
    tokens of a run that never preempted."""
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


def test_a_row_alone_among_fifteen_and_in_a_slot_just_left(cfg, kit):
    """Batch invariance: the same prompt gives the same tokens alone, among
    15 other rows (16 seats, every slot held), and in the slot another row
    has just left (one seat)."""
    prompt, others = ek.prompts(cfg, (90,))[0], ek.prompts(
        cfg, [20 + 7 * i for i in range(15)], seed=9)
    crowd, _ = kit.engine(
        ek.engine_config(cfg, seqs=16, num_blocks=512, **CHUNK))
    alone = ek.generate(crowd, [prompt])[0]
    assert ek.generate(crowd, [prompt] + others)[0] == alone
    one, _ = kit.engine(ek.engine_config(cfg, seqs=1, **CHUNK))
    assert one.scheduler.pool.state.num_usable == 1
    first = ek.generate(one, [others[3]])
    again = ek.generate(one, [prompt])[0]  # the one slot, just left
    assert again == alone
    assert first[0] == ek.generate(crowd, [others[3]])[0]
