"""`costs_swa` against hand-computed Laguna-S-2.1 numbers (the cell's cut),
and the three window-and-full readers on hand-made documents and on a parent
commit that has neither the window kernels nor the counters."""

import json
import os

import costs
import costs_swa
import pytest
from readers import (
    swa_decode_attn_roofline, swa_decode_step_roofline,
    swa_prefill_attn_roofline,
)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def laguna():
    path = os.path.join(HERE, "configs", "laguna-s21-bf16-ep4-9l.json")
    with open(path) as f:
        return json.load(f)


def test_laguna_sizes(laguna):
    assert costs_swa.layers_of(laguna, "full_attention") == [0, 4, 8]
    assert costs_swa.layers_of(laguna, "sliding_attention") == [
        1, 2, 3, 5, 6, 7]
    assert costs_swa.heads_of(laguna, "full_attention") == 3 * 48
    assert costs_swa.heads_of(laguna, "sliding_attention") == 6 * 72
    # K and V of 8 kv heads x 128 in bf16
    assert costs.kv_bytes_per_token_per_layer(laguna) == 4096
    # a full layer: q 18.87 M + k, v 6.29 M + o 18.87 M + gate 0.15 M; a
    # window layer: 28.31 + 6.29 + 28.31 + 0.22
    full = 3072 * 48 * 128 * 2 + 2 * 3072 * 8 * 128 + 3072 * 48
    win = 3072 * 72 * 128 * 2 + 2 * 3072 * 8 * 128 + 3072 * 72
    assert (full, win) == (44_187_648, 63_135_744)
    assert costs_swa.attn_params(laguna) == 3 * full + 6 * win
    assert costs_swa.expert_layers(laguna) == 8
    assert costs_swa.expert_params(laguna) == 3 * 3072 * 1024
    # attention, the dense MLP of 12,288, 8 x (router 256 wide + shared
    # expert of 1,024), the head's slice of 25,088 rows
    assert costs_swa.always_read_params(laguna) == (
        3 * full + 6 * win + 3 * 3072 * 12288
        + 8 * (3072 * 256 + 3 * 3072 * 1024) + 3072 * 25088)
    # the cell's cut: 5.69 B parameters with the embedding and norms aside
    total = (costs_swa.always_read_params(laguna) + 3072 * 25088
             + 8 * 64 * costs_swa.expert_params(laguna))
    assert 5.68e9 < total < 5.70e9


def test_step_bytes_and_flops(laguna):
    live, live_win, share = 66_000.0, 16 * 512.0, 30 / 64
    kv = costs_swa.decode_attn_bytes(laguna, live, live_win)
    assert kv == 4096 * (3 * live + 6 * live_win)
    # 0.81 GB in the full layers, 0.20 GB in the window layers: had the
    # window layers held the context they would read 1.62 GB more
    assert 1.0e9 < kv < 1.03e9
    nbytes = costs_swa.decode_step_bytes(
        laguna, live, live_win, share, "bfloat16")
    assert nbytes == pytest.approx(
        2 * (costs_swa.always_read_params(laguna)
             + share * 8 * 64 * 3 * 3072 * 1024) + kv)
    # ~1.5 GB always read, ~4.5 GB of touched experts, ~1.0 GB of KV
    assert 6.9e9 < nbytes < 7.2e9
    flops = costs_swa.decode_step_flops(laguna, 16, 2.5, live, live_win)
    assert flops == pytest.approx(
        2 * 16 * (costs_swa.always_read_params(laguna)
                  + 8 * 2.5 * 3 * 3072 * 1024)
        + 4.0 * 128 * (3 * 48 * live + 6 * 72 * live_win))
    least, bound = costs.roofline_seconds(flops, nbytes, PEAK)
    assert bound == "memory" and 0.008 < least < 0.009
    # prefill attention is compute-bound: a 2,048-token chunk at 8k of
    # context covers 14.7 M pairs a full layer, 1.0 M a window layer
    pairs = 2048 * 6144 + 2048 * 2049 // 2
    wpairs = 2048 * 512
    t_c = costs_swa.prefill_attn_flops(laguna, pairs, wpairs) / 197e12
    t_m = costs_swa.prefill_attn_bytes(laguna, 8192, 2048 + 511) / 819e9
    assert t_c > 10 * t_m
    assert costs_swa.prefill_attn_flops(laguna, pairs, wpairs) == (
        4.0 * 128 * (3 * 48 * pairs + 6 * 72 * wpairs))


class _Rec:
    def __init__(self, prompt, first, done):
        self.prompt_tokens, self.chunk_times, self.done = prompt, [first], done

    def tokens_between(self, a, b):
        return 0.0


def _ctx(cfg, ops, modules, counts, timing0, timing1):
    return {
        "config": cfg, "peaks": PEAK,
        "trace": {"device_ops": ops, "modules": modules, "op_counts": counts,
                  "module_counts": {}},
        "trace_span": {"t0": 1.0, "t1": 2.0, "timing0": {"engine": timing0},
                       "timing1": {"engine": timing1}},
        "records": ([_Rec(7000, 0.0, 10.0) for _ in range(15)]
                    + [_Rec(300, 0.0, 10.0)]),
    }


ZERO = {"moe_decode_touched_n": 0, "moe_decode_slots_n": 0,
        "moe_decode_held_pairs_n": 0, "attn_prefill_pairs_n": 0,
        "attn_prefill_window_pairs_n": 0, "attn_prefill_keys_n": 0,
        "attn_prefill_window_keys_n": 0}


def test_readers_on_hand_made_documents(laguna):
    live, live_win = 15 * 7000.0 + 300, 15 * 512.0 + 300
    steps = 50
    ctx = _ctx(
        laguna,
        ops=[["paged_decode_attention", 0.1],
             ["paged_window_decode_attention", 0.05],
             ["paged_prefill_attention", 0.3],
             ["paged_window_prefill_attention", 0.2]],
        modules=[["jit_decode_window_fn", 0.8]],
        counts={"paged_window_decode_attention": steps * 6,
                "paged_decode_attention": steps * 3},
        timing0=ZERO,
        timing1={"moe_decode_touched_n": 30 * 8 * steps,
                 "moe_decode_slots_n": 64 * 8 * steps,
                 "moe_decode_held_pairs_n": 40 * 8 * steps,
                 "attn_prefill_pairs_n": 40_000_000,
                 "attn_prefill_window_pairs_n": 4_000_000,
                 "attn_prefill_keys_n": 60_000,
                 "attn_prefill_window_keys_n": 20_000},
    )
    assert swa_decode_attn_roofline.live(ctx, 1.0, 2.0) == pytest.approx(
        (live, live_win))
    kv = 4096 * (3 * live + 6 * live_win)
    assert swa_decode_attn_roofline.read(ctx) == pytest.approx(
        100 * steps * (kv / 819e9) / 0.15)
    nbytes = costs_swa.decode_step_bytes(
        laguna, live, live_win, 30 / 64, "bfloat16")
    assert swa_decode_step_roofline.read(ctx) == pytest.approx(
        100 * (nbytes / 819e9) / (0.8 / steps))
    assert swa_prefill_attn_roofline.read(ctx) == pytest.approx(
        100 * 4.0 * 128 * (3 * 48 * 40e6 + 6 * 72 * 4e6) / 197e12 / 0.5)


def test_readers_find_nothing_on_a_parent_or_another_model(laguna):
    # a parent commit: no window kernels in the trace, no counters
    parent = _ctx(laguna, ops=[["fusion", 1.0],
                               ["paged_decode_attention", 0.2]],
                  modules=[["jit_decode_window_fn", 0.8]],
                  counts={"paged_decode_attention": 100},
                  timing0={}, timing1={})
    readers = (swa_decode_attn_roofline, swa_decode_step_roofline,
               swa_prefill_attn_roofline)
    for reader in readers:
        assert reader.read(parent) is None
    # another configuration (no layer_types key)
    with open(os.path.join(HERE, "configs", "qwen3-8b-int8.json")) as f:
        other = dict(parent, config=json.load(f))
    for reader in readers:
        assert reader.read(other) is None
    # no trace at all (a failed capture)
    for reader in readers:
        assert reader.read(dict(parent, trace=None)) is None
