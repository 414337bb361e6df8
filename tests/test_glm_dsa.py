"""Latent attention over a LEARNED SELECTION of keys (GLM-5.2's
`glm_moe_dsa`, ISSUE 55) at tiny size on the CPU: the served ENGINE against
the plain float32 reference (testing/reference_glm_dsa.py) — chunked
prefill, chunk seams, fused decode windows, at contexts LONGER than the toy
top-k (16) — what each limit tells, a selection that does not move with the
batch, a preemption and a prefix hit, and who hands whom the selection. The
parts (the exact top-k, shares, bytes, refusals, names) are
tests/test_glm_dsa_parts.py's.
"""

import numpy as np
import pytest

import engine_kit as ek
from vllm_production_stack_tpu.engine.config import CacheConfig
from vllm_production_stack_tpu.models import llama
from vllm_production_stack_tpu.models.registry import resolve_model_config
from vllm_production_stack_tpu.testing import reference_check
from vllm_production_stack_tpu.testing import reference_glm_dsa as reference

# float32 on both sides, the reference FOLLOWING the served routing and
# selection. What differs is the order of sums: the absorbed form under a
# mask in blocks of keys (a chunk) or over gathered latents (a step) against
# one softmax over expanded keys: 9.5e-7 measured over 30 positions (PR 55).
# A borrowing layer handed the wrong layer's selection reads 6.7e-2, one
# that selects for itself 4.9e-1 (test_the_limits_tell_...).
LOGPROB_ATOL = 5e-5
# the served (token, slot) choices that are not in the reference's own
# top-k, and how far the worst lies under its k-th best score, in spreads of
# the scores: 1.4e-4 (one slot of 7,024: two float32 scores 1e-6 apart) and
# 1.9e-6 measured; a reference wrong in one way reads 0.13-0.54 and 1.7-4.2
SELECTION_DISAGREEMENT_MAX = 1e-3
SELECTION_MARGIN_SPREADS_MAX = 1e-4
# the index keys the first layer holds against the reference's: 6.8e-8
INDEX_PAGES_RTOL = 1e-5


@pytest.fixture(scope="module")
def cfg():
    return resolve_model_config("tiny-glm-dsa")


def _serve(kit, cfg, prompts, n_out, backend="xla", **kw):
    with (reference_check.RoutingTap() as routing,
          reference_check.SelectionTap() as selection):
        engine = kit.fresh_engine(ek.engine_config(cfg, backend, **kw))
        served = reference_check.served_logprobs(engine, prompts, n_out)
    return engine, served, routing, selection


@pytest.mark.parametrize("backend,lengths", [
    ("xla", (70, 9, 100)), ("pallas_interpret", (45, 100))])
def test_engine_against_the_float32_reference(cfg, kit, backend, lengths):
    """Prefill in chunks of 32 (the 100-token prompt in four, the others
    packed beside it), then fused decode windows of 4, every context past
    the first 16 tokens longer than the top-k: the query latent, the
    indexer's scores, the exact top-k in a chunk (threshold) and in a step
    (`top_k`), the selection handed from the layers that own an indexer
    (0, 3) to those that borrow (1, 2, 4), the index keys written beside the
    latents and committed with them, the held share of the experts."""
    prompts = ek.prompts(cfg, lengths)
    engine, served, routing, selection = _serve(
        kit, cfg, prompts, 10, backend)
    out = reference_check.compare(
        reference, cfg, engine.runner.params, prompts, served,
        served_routing=routing, served_selection=selection)
    assert out["positions"] == 10 * len(lengths)
    assert out["max_abs_dlogprob"] <= LOGPROB_ATOL, out
    assert out["routing_disagreement_share"] == 0.0, out
    # two layers own an indexer; a query at position p keeps min(p + 1, 16)
    assert out["selection_slots"] == 2 * sum(
        min(p + 1, 16) for n in lengths for p in range(n + 9))
    assert out["selection_disagreement_share"] <= SELECTION_DISAGREEMENT_MAX
    assert (out["selection_first_layer_disagreement_share"]
            <= 2 * SELECTION_DISAGREEMENT_MAX)  # (half the slots)
    assert (out["selection_margin_max_in_spreads"]
            <= SELECTION_MARGIN_SPREADS_MAX), out
    pages = reference_check.served_index_pages(
        reference, cfg, engine.runner.params,
        engine.runner.kv_caches[0]["idx"], prompts, served)
    assert pages["tokens"] == sum(n + 9 for n in lengths)
    assert pages["index_pages_rel_err"] <= INDEX_PAGES_RTOL, pages
    mixers = engine.runner.device_report()["mixers"]
    assert mixers["by_layer"] == [
        "latent+full", "latent+shared", "latent+shared", "latent+full",
        "latent+shared"]
    assert (mixers["full"], mixers["shared"], mixers["index_topk"]) == (
        2, 3, 16)
    assert "dsa_index_score" in mixers["indexer_backend"]["prefill"]


@pytest.fixture(scope="module")
def served_once(cfg, kit):
    prompts = ek.prompts(cfg, (90,), seed=3)
    engine, served, routing, selection = _serve(kit, cfg, prompts, 8)
    return (engine.runner.params, prompts, served, routing, selection,
            np.asarray(engine.runner.kv_caches[0]["idx"]))


@pytest.mark.parametrize("arms,limit", [
    ({"relu": False}, "selection"), ({"head_weights": False}, "selection"),
    ({"last_k": True}, "selection"), ({"topk": 8}, "selection"),
    ({"key_norm": False}, "selection"), ({"borrow_lag": True}, "logprob"),
    ({"own_selection": True}, "logprob"),
    ({"key_dtype": "float8_e4m3fn"}, "pages")])
def test_the_limits_tell_a_reference_wrong_in_one_way(
        cfg, served_once, arms, limit):
    """Each arm lands outside at least one limit: a fault in the INDEXER
    (the ReLU or the heads' weights left out, the last k keys instead of
    the best, another k, the key un-normed) leaves the logprobs alone — the
    reference follows the served selection — and shows as served choices far
    outside the reference's own top-k; a fault in WHO HANDS WHOM the
    selection (a borrowing layer takes the selection before the right one,
    or selects for itself) shows in the logprobs; index keys held in a
    LOWER PRECISION (what a float8 cache would hold) show in the pages the
    first layer holds, by a float8 key's rounding."""
    params, prompts, served, routing, selection, index_pool = served_once
    if limit == "pages":
        pages = reference_check.served_index_pages(
            reference, cfg, params, index_pool, prompts, served, **arms)
        assert 0.02 < pages["index_pages_rel_err"] < 0.035, pages
        return
    out = reference_check.compare(
        reference, cfg, params, prompts, served, served_routing=routing,
        served_selection=selection, reference_arms=arms)
    if limit == "selection":
        assert out["selection_disagreement_share"] > 50 * (
            SELECTION_DISAGREEMENT_MAX), out
        assert out["max_abs_dlogprob"] <= LOGPROB_ATOL
    else:
        assert out["max_abs_dlogprob"] > 100 * LOGPROB_ATOL, out


def test_a_free_running_reference_selects_the_same_keys(cfg, served_once):
    """Without the served selection the reference selects for itself, and in
    float32 agrees to the odd boundary key (6e-4 measured: one slot)."""
    params, prompts, served, routing, _, _ = served_once
    out = reference_check.compare(
        reference, cfg, params, prompts, served, served_routing=routing)
    assert out["max_abs_dlogprob"] <= 5e-3, out
    assert "selection_slots" not in out


def test_the_selection_is_handed_down_the_layer_loop(cfg, kit, monkeypatch):
    """Layers 0 and 3 own an indexer; 1 and 2 attend over EXACTLY what layer
    0 selected, 4 over what layer 3 did — the very array, in a chunk and in
    a decode step."""
    chunk_calls, step_calls = [], []
    prefill, decode = llama.dsa_prefill_attention, llama.dsa_decode_attention

    def spy_prefill(*a, index, selection, **kw):
        out, made = prefill(*a, index=index, selection=selection, **kw)
        chunk_calls.append((index is not None, selection, made))
        return out, made

    def spy_decode(q, pool, tables, staged, chosen, real, **kw):
        step_calls.append(chosen)
        return decode(q, pool, tables, staged, chosen, real, **kw)

    monkeypatch.setattr(llama, "dsa_prefill_attention", spy_prefill)
    monkeypatch.setattr(llama, "dsa_decode_attention", spy_decode)
    engine = kit.fresh_engine(ek.engine_config(cfg))
    ek.generate(engine, ek.prompts(cfg, (40,)), 3)
    assert [owns for owns, _, _ in chunk_calls[:5]] == [
        True, False, False, True, False]
    made = [m for _, _, m in chunk_calls[:5]]
    handed = [h for _, h, _ in chunk_calls[:5]]
    assert handed[1] is made[0] and handed[2] is made[0]
    assert made[1] is made[0] and made[2] is made[0]  # handed through
    assert handed[4] is made[3] and made[3] is not made[0]
    assert step_calls[1] is step_calls[0] and step_calls[2] is step_calls[0]
    assert step_calls[4] is step_calls[3]
    assert step_calls[3] is not step_calls[0]


def test_a_row_selects_the_same_keys_alone_and_among_fifteen(cfg, kit):
    """Batch invariance at a context over the top-k: the same prompt gives
    the same tokens AND the same selected positions, token by token and
    layer by layer, alone and among 15 other rows (16 seats)."""
    prompt, others = ek.prompts(cfg, (90,))[0], ek.prompts(
        cfg, [20 + 7 * i for i in range(15)], seed=9)
    with reference_check.SelectionTap() as tap:
        crowd = kit.fresh_engine(
            ek.engine_config(cfg, seqs=16, num_blocks=512))
        alone = ek.generate(crowd, [prompt])[0]
        seq = prompt + alone[:-1]
        chose_alone = tap.selection_of(seq)
        # (the same programs hand the same tap the crowd's executions)
        tap.records, tap._by_key = [], None
        together = ek.generate(crowd, [prompt] + others)
        assert together[0] == alone
        chose_crowded = tap.selection_of(seq)
    # (the tap keys a token by (position, id): where another row fed the
    # same id at the same position the key names neither's selection)
    shared = {key for other, out in zip(others, together[1:])
              for key in enumerate(other + out)}
    mine = [p for p, t in enumerate(seq) if (p, t) not in shared]
    assert len(chose_alone) == 2 and len(mine) > 80
    for a, b in zip(chose_alone, chose_crowded):
        assert a.shape == (len(seq), 16)
        np.testing.assert_array_equal(a[mine], b[mine])
    # a query past the top-k keeps 16 keys, itself not always among them
    assert (chose_alone[0][40:] >= 0).all()
    assert any(p not in row for p, row in enumerate(chose_alone[0]))


def test_a_preemption_and_a_resume_end_in_the_same_tokens(cfg, kit):
    """A pool that runs out under both rows' growth preempts the newer one
    (`engine_kit.generate_squeezed`): it loses its latent pages AND its
    index keys, is computed again from its first token, and ends with the
    tokens of a run that never preempted."""
    prompts = ek.prompts(cfg, (60, 60), seed=4)
    engine, _ = kit.engine(ek.engine_config(cfg, seqs=2))
    want = ek.generate(engine, prompts, max_tokens=40)
    preempted = engine.scheduler.total_preemptions
    got = ek.generate_squeezed(engine, prompts, max_tokens=40)
    assert engine.scheduler.total_preemptions > preempted
    assert got == want


def test_a_prefix_hit_brings_the_latents_and_the_index_keys(cfg, kit):
    """With prefix caching a second prompt that shares 64 tokens with a
    served one skips them: its later queries select among index keys the
    FIRST request wrote, and its tokens are those of an engine that computed
    everything."""
    first = ek.prompts(cfg, (96,), seed=6)[0]
    second = first[:64] + ek.prompts(cfg, (40,), seed=7)[0]
    plain, _ = kit.engine(ek.engine_config(cfg))
    want = ek.generate(plain, [second])[0]
    caching = kit.fresh_engine(ek.engine_config(cfg, cache=CacheConfig(
        block_size=8, num_blocks=128, enable_prefix_caching=True)))
    ek.generate(caching, [first])
    hits = caching.stats().prefix_cache_hits
    assert ek.generate(caching, [second])[0] == want
    assert caching.stats().prefix_cache_hits - hits >= 64 // 8
