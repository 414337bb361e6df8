"""`costs_moe` against hand-computed OLMoE-1B-7B numbers, the MoE decode
step's reader on hand-made documents and on a parent without the counters,
and the two counter ratios from their metric files."""

import json
import os

import costs
import costs_moe
import pytest
from readers import moe_decode_step_roofline, timing_ratio_opt

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def olmoe():
    with open(os.path.join(HERE, "configs", "olmoe-1b-7b-int8.json")) as f:
        return json.load(f)


def test_olmoe_sizes(olmoe):
    # 16 layers x 64 experts x 3 matrices of 2048 x 1024
    assert costs_moe.expert_params(olmoe) == 16 * 64 * 3 * 2048 * 1024
    assert costs_moe.expert_params(olmoe) == 6_442_450_944
    # attention 4 x 2048 x 2048 and a 2048 x 64 router per layer, the head
    assert costs_moe.dense_params(olmoe) == (
        16 * (4 * 2048 * 2048 + 2048 * 64) + 2048 * 50304)
    total = (costs_moe.expert_params(olmoe) + costs_moe.dense_params(olmoe)
             + 2048 * 50304)  # + the embedding
    assert 6.91e9 < total < 6.93e9  # the published 6.92 B
    assert costs_moe.expert_params(olmoe) / total == pytest.approx(0.93, abs=0.005)
    # MHA 16 x 128, bf16 K and V, 16 layers: 128 KiB a token
    assert costs.kv_bytes_per_token(olmoe) == 128 * 1024
    # costs.matmul_params counts one expert a layer: why the shared
    # decode_step_roofline reads low in this cell
    assert costs.matmul_params(olmoe) == (
        costs_moe.dense_params(olmoe) - 16 * 2048 * 64
        + costs_moe.expert_params(olmoe) // 64)


def test_touched_bytes_and_step(olmoe):
    # 56 of 64 experts of every layer, one byte a parameter
    share = 56 / 64
    assert costs_moe.touched_expert_bytes(olmoe, share, "int8") == (
        16 * 56 * 3 * 2048 * 1024)
    live = 8000.0
    nbytes = costs_moe.decode_step_bytes(olmoe, live, share, "int8")
    assert nbytes == pytest.approx(
        costs_moe.dense_params(olmoe) + 16 * 56 * 3 * 2048 * 1024
        + live * 128 * 1024)
    # ~5.6 GB of experts, 0.37 GB of everything else, ~1 GB of KV
    assert 6.9e9 < nbytes < 7.2e9
    flops = costs_moe.decode_step_flops(olmoe, 16, live)
    # 16 rows x 2 x (dense 0.37 B + 8 experts x 16 layers x 6.3 M)
    assert flops == pytest.approx(
        2 * 16 * (costs_moe.dense_params(olmoe) + 16 * 8 * 3 * 2048 * 1024)
        + 4.0 * 16 * 128 * live * 16)
    least, bound = costs.roofline_seconds(flops, nbytes, PEAK)
    assert bound == "memory" and 8.4e-3 < least < 8.8e-3
    # fewer touched experts, fewer bytes: a program that skips the
    # untouched ones is not credited with reading them
    assert costs_moe.decode_step_bytes(olmoe, live, 0.5, "int8") < nbytes


class Rec:
    def __init__(self, prompt, first, n, done):
        self.prompt_tokens, self.chunk_times, self.done = prompt, [first], done
        self._n = n

    def tokens_between(self, t0, t1):
        return self._n


def reader_ctx(olmoe, t0_engine, t1_engine, module_s=8.0, windows=100):
    layers = olmoe["num_hidden_layers"]
    steps = windows * olmoe["decode_window"]
    return {
        "config": olmoe, "peaks": PEAK,
        "trace": {"modules": [["jit_decode_window_fn", module_s]],
                  "op_counts": {"paged_decode_attention": steps * layers},
                  "module_counts": {"jit_decode_window_fn": windows}},
        "trace_span": {"t0": 10.0, "t1": 18.0,
                       "timing0": {"engine": t0_engine},
                       "timing1": {"engine": t1_engine}},
        "records": [Rec(400, 0.0, 100, 99.0)] * 16,
    }


def test_reader_on_hand_made_documents(olmoe):
    slots = 800 * 16 * 64
    c = reader_ctx(
        olmoe,
        {"moe_decode_touched_n": 1000, "moe_decode_slots_n": 5000},
        {"moe_decode_touched_n": 1000 + slots * 7 // 8,
         "moe_decode_slots_n": 5000 + slots})
    live = 16 * 500.0
    least, _ = costs.roofline_seconds(
        costs_moe.decode_step_flops(olmoe, 16, live),
        costs_moe.decode_step_bytes(olmoe, live, 7 / 8, "int8"), PEAK)
    want = 100.0 * least / (8.0 / 800)  # 10 ms a step on the device
    assert moe_decode_step_roofline.read(c) == pytest.approx(want)
    assert 80.0 < want < 90.0


def test_reader_returns_nothing_without_the_counters(olmoe):
    with_counters = {"moe_decode_touched_n": 1, "moe_decode_slots_n": 2}
    parent = reader_ctx(olmoe, {"decode_n": 1}, {"decode_n": 9})
    assert moe_decode_step_roofline.read(parent) is None
    still = reader_ctx(olmoe, with_counters, with_counters)
    assert moe_decode_step_roofline.read(still) is None
    no_trace = {**reader_ctx(olmoe, with_counters, with_counters),
                "trace": None}
    assert moe_decode_step_roofline.read(no_trace) is None
    dense = dict(olmoe)
    del dense["num_experts"]
    assert moe_decode_step_roofline.read(
        {**reader_ctx(olmoe, {}, {}), "config": dense}) is None
    assert moe_decode_step_roofline.read(
        {**reader_ctx(olmoe, {}, {}), "trace_span": {}}) is None


@pytest.mark.parametrize("name, want", [
    ("moe_touched_share", 87.5),
    ("moe_tokens_per_expert", 128 / 56),
])
def test_counter_ratios_from_their_files(name, want):
    with open(os.path.join(HERE, "metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "timing_ratio_opt"
    start = dict(moe_decode_touched_n=56, moe_decode_slots_n=64,
                 moe_decode_pairs_n=128)
    end = dict(moe_decode_touched_n=56 * 101, moe_decode_slots_n=64 * 101,
               moe_decode_pairs_n=128 * 101)
    c = {"start": {"timing": {"engine": start}},
         "end": {"timing": {"engine": end}}}
    assert timing_ratio_opt.read(c, **spec["args"]) == pytest.approx(want)
    parent = {"start": {"timing": {"engine": {}}},
              "end": {"timing": {"engine": {}}}}
    assert timing_ratio_opt.read(parent, **spec["args"]) is None
