"""`costs_gmm` against hand-computed numbers of the two long-document
configurations, and the `moe_prefill_experts_roofline` reader on hand-made
documents: with XLA's grouped matmul in the slice (a parent commit), with
the fused kernel (the change), and with nothing to read."""

import json
import os

import costs_gmm
import pytest
from readers import moe_prefill_experts_roofline as reader

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def kanana():
    return _config("kanana-2-30b-a3b-bf16-8l")


@pytest.fixture(scope="module")
def laguna():
    return _config("laguna-s21-bf16-ep4-9l")


def test_sizes(kanana, laguna):
    assert costs_gmm.expert_params(kanana) == 3 * 2048 * 768
    assert costs_gmm.expert_params(laguna) == 3 * 3072 * 1024
    # a 2,048-token chunk, every expert of every expert layer touched:
    # 8.46 GB (Kanana, 7 x 128) and 9.66 GB (Laguna, 8 x 64 held)
    assert costs_gmm.prefill_expert_bytes(
        kanana, 7 * 128, "bfloat16") == 7 * 128 * 3 * 2048 * 768 * 2
    assert 9.6e9 < costs_gmm.prefill_expert_bytes(
        laguna, 8 * 64, "bfloat16") < 9.7e9
    # 12,288 pairs x 7 layers x 9.44 MFLOP = 0.81 TFLOP
    assert costs_gmm.prefill_expert_flops(
        kanana, 7 * 2048 * 6) == 2.0 * 7 * 2048 * 6 * 3 * 2048 * 768
    assert 0.80e12 < costs_gmm.prefill_expert_flops(
        kanana, 7 * 2048 * 6) < 0.82e12


def _ctx(config, ops, timing0, timing1):
    return {"config": config, "peaks": PEAK,
            "trace": {"device_ops": ops},
            "trace_span": {"timing0": {"engine": timing0},
                           "timing1": {"engine": timing1}}}


CHUNKS = 57
T0 = {"moe_prefill_touched_n": 1000, "moe_prefill_held_pairs_n": 5000}


def _after(touched, held_pairs):
    return {"moe_prefill_touched_n": 1000 + touched,
            "moe_prefill_held_pairs_n": 5000 + held_pairs}


@pytest.mark.parametrize("ops,seconds", [
    # a parent commit: three ragged_dot calls a layer
    ([["fusion", 2.0], ["ragged-dot-none", 1.557]], 1.557),
    # the change: the fused kernel
    ([["moe_prefill_experts", 0.9], ["fusion", 2.0]], 0.9),
    # both (a slice that holds programs of either form): the same work
    ([["moe_prefill_experts", 0.5], ["ragged-dot-none", 0.25]], 0.75),
])
def test_reader_counts_either_operation(kanana, ops, seconds):
    touched = CHUNKS * 7 * 128
    pairs = CHUNKS * 7 * 2048 * 6
    got = reader.read(_ctx(kanana, ops, T0, _after(touched, pairs)))
    # memory-bound: 57 chunks x 8.46 GB at 819 GB/s = 0.589 s
    assert got == pytest.approx(
        100 * touched * 3 * 2048 * 768 * 2 / 819e9 / seconds)
    assert got < 100


def test_reader_takes_the_flops_where_they_are_the_bound(laguna):
    # few experts touched, many pairs on them: compute-bound
    touched, pairs = 8, 8 * 20_480
    got = reader.read(_ctx(laguna, [["moe_prefill_experts", 0.01]], T0,
                           _after(touched, pairs)))
    assert got == pytest.approx(
        100 * 2.0 * pairs * 3 * 3072 * 1024 / 197e12 / 0.01)


@pytest.mark.parametrize("case", [
    "no_counters", "still_counters", "no_operation", "no_trace",
    "other_model", "no_span"])
def test_reader_returns_nothing_without_something_to_read(kanana, case):
    after = _after(7 * 128, 7 * 12_288)
    ops = [["ragged-dot-none", 0.03]]
    ctx = {
        # a program that serves no such counters
        "no_counters": _ctx(kanana, ops, {"prefill_n": 1}, {"prefill_n": 2}),
        # no prefill chunk resolved inside the slice
        "still_counters": _ctx(kanana, ops, T0, T0),
        # neither operation in the slice
        "no_operation": _ctx(kanana, [["fusion", 1.0]], T0, after),
        "no_trace": {**_ctx(kanana, ops, T0, after), "trace": None},
        # a configuration without routed experts of their own width
        "other_model": {**_ctx(kanana, ops, T0, after),
                        "config": _config("qwen3-8b-int8")},
        "no_span": {**_ctx(kanana, ops, T0, after), "trace_span": {}},
    }[case]
    assert reader.read(ctx) is None
