"""`costs_mla` against hand-computed Kanana-2-30B-A3B numbers, and the three
latent-attention readers on hand-made documents and on a parent commit that
has neither the kernels nor the counters."""

import json
import os

import costs
import costs_mla
import pytest
from readers import (
    mla_decode_attn_roofline, mla_decode_step_roofline,
    mla_prefill_attn_roofline,
)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def kanana():
    path = os.path.join(HERE, "configs", "kanana-2-30b-a3b-bf16-8l.json")
    with open(path) as f:
        return json.load(f)


def test_kanana_sizes(kanana):
    # one latent a token and layer: 512 + 64 values, 1,152 B in bf16 — a
    # seventh of what costs.py makes of the published kv heads x head_dim
    assert costs_mla.latent_values(kanana) == 576
    assert costs_mla.latent_bytes_per_token_per_layer(kanana) == 1152
    assert costs.kv_bytes_per_token_per_layer(kanana) == 8192
    # W_q 12.58 M, W_kv_a 1.18 M, W_kv_b 4.19 M, W_o 8.39 M
    assert costs_mla.attn_params_per_layer(kanana) == (
        2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 32 * 128 * 2048)
    assert costs_mla.attn_params_per_layer(kanana) == 26_345_472
    assert costs_mla.moe_layers(kanana) == 7
    assert costs_mla.expert_params_per_layer(kanana) == 128 * 3 * 2048 * 768
    # attention x 8, one dense MLP of 6144, 7 x (shared 1536 + router), head
    assert costs_mla.always_read_params(kanana) == (
        8 * 26_345_472 + 3 * 2048 * 6144
        + 7 * (3 * 2048 * 1536 + 2048 * 128) + 2048 * 128256)
    # the cell's cut: 5.07 B parameters with the embedding
    total = (costs_mla.always_read_params(kanana) + 2048 * 128256
             + 7 * costs_mla.expert_params_per_layer(kanana))
    assert 5.06e9 < total < 5.08e9


def test_step_bytes_and_flops(kanana):
    live, share = 112_000.0, 96 / 128
    nbytes = costs_mla.decode_step_bytes(kanana, live, share, "bfloat16")
    assert nbytes == pytest.approx(
        2 * (costs_mla.always_read_params(kanana)
             + share * 7 * 128 * 3 * 2048 * 768)
        + live * 8 * 1152)
    # ~1.1 GB always read, ~6.3 GB of touched experts, ~1.0 GB of latents
    assert 8.3e9 < nbytes < 8.6e9
    flops = costs_mla.decode_step_flops(kanana, 16, live)
    assert flops == pytest.approx(
        2 * 16 * (costs_mla.always_read_params(kanana)
                  + 7 * 6 * 3 * 2048 * 768)
        + 2.0 * 32 * (576 + 512) * live * 8)
    least, bound = costs.roofline_seconds(flops, nbytes, PEAK)
    assert bound == "memory" and 0.010 < least < 0.011
    # attention alone: 1.03 GB of latents against 62 GFLOP: memory-bound
    assert costs_mla.decode_attn_bytes(kanana, live) == live * 8 * 1152
    t_m = costs_mla.decode_attn_bytes(kanana, live) / PEAK["hbm_bytes_per_s"]
    t_c = costs_mla.decode_attn_flops(kanana, live) / PEAK["bf16_flops_per_s"]
    assert t_m > 3 * t_c


class _Rec:
    def __init__(self, prompt, first, done):
        self.prompt_tokens, self.chunk_times, self.done = prompt, [first], done

    def tokens_between(self, a, b):
        return 0.0


def _ctx(kanana, ops, modules, counts, timing0, timing1):
    return {
        "config": kanana, "peaks": PEAK,
        "trace": {"device_ops": ops, "modules": modules, "op_counts": counts,
                  "module_counts": {}},
        "trace_span": {"t0": 1.0, "t1": 2.0, "timing0": {"engine": timing0},
                       "timing1": {"engine": timing1}},
        "records": [_Rec(7000, 0.0, 10.0) for _ in range(16)],
    }


def test_readers_on_hand_made_documents(kanana):
    live = 16 * 7000.0
    steps = 50
    ctx = _ctx(
        kanana,
        ops=[["mla_decode_attention", 0.2], ["mla_prefill_attention", 0.5]],
        modules=[["jit_decode_window_fn", 0.8]],
        counts={"mla_decode_attention": steps * 8},
        timing0={"moe_decode_touched_n": 0, "moe_decode_slots_n": 0,
                 "attn_prefill_pairs_n": 0},
        timing1={"moe_decode_touched_n": 75, "moe_decode_slots_n": 100,
                 "attn_prefill_pairs_n": 40_000_000},
    )
    want = 100 * steps * (live * 8 * 1152 / 819e9) / 0.2
    assert mla_decode_attn_roofline.read(ctx) == pytest.approx(want)
    nbytes = costs_mla.decode_step_bytes(kanana, live, 0.75, "bfloat16")
    assert mla_decode_step_roofline.read(ctx) == pytest.approx(
        100 * (nbytes / 819e9) / (0.8 / steps))
    assert mla_prefill_attn_roofline.read(ctx) == pytest.approx(
        100 * 40e6 * 8 * 2 * 32 * 1088 / 197e12 / 0.5)


def test_readers_find_nothing_on_a_parent_or_another_model(kanana):
    # a parent commit: no such kernels in the trace, no counters
    parent = _ctx(kanana, ops=[["fusion", 1.0]],
                  modules=[["jit_decode_window_fn", 0.8]], counts={},
                  timing0={}, timing1={})
    for reader in (mla_decode_attn_roofline, mla_decode_step_roofline,
                   mla_prefill_attn_roofline):
        assert reader.read(parent) is None
    # another configuration (no kv_lora_rank key)
    with open(os.path.join(HERE, "configs", "olmoe-1b-7b-int8.json")) as f:
        olmoe = json.load(f)
    other = dict(parent, config=olmoe)
    for reader in (mla_decode_attn_roofline, mla_decode_step_roofline,
                   mla_prefill_attn_roofline):
        assert reader.read(other) is None
    # no trace at all (an untraced run never calls them; a failed capture)
    assert mla_decode_attn_roofline.read(dict(parent, trace=None)) is None
