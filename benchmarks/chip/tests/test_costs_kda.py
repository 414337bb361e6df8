"""`costs_kda` against counts done by hand for the Solar-Open2 cut (the
cell's configuration), the KDA and GQA readers on hand-made documents and on
a parent commit that has neither the kernels nor the counters, and the
manifest's cross-references for what ISSUE 39 added."""

import json
import os

import costs
import costs_kda
import pytest
from readers import (
    gqa_decode_attn_roofline, gqa_prefill_attn_roofline, kda_decode_roofline,
    kda_decode_step_roofline, kda_prefill_roofline,
)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "solar-open2-bf16-ep8-8l.longdoc-saturated"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def solar():
    path = os.path.join(HERE, "configs", "solar-open2-bf16-ep8-8l.json")
    with open(path) as f:
        return json.load(f)


def test_the_cut_by_hand(solar):
    assert costs_kda.gqa_layers(solar) == [0, 4]
    assert costs_kda.kda_layers(solar) == [1, 2, 3, 5, 6, 7]
    # a row's state in one KDA layer: 64 heads x 128 x 128 float32 = 4 MiB;
    # its convolution tail: 3 rows x 3 x 8,192 bf16 = 144 KiB
    assert costs_kda.state_bytes_per_row_layer(solar) == 4 * 2**20
    assert costs_kda.tail_bytes_per_row_layer(solar) == 144 * 2**10
    # K and V of 8 kv heads x 128 in bf16
    assert costs.kv_bytes_per_token_per_layer(solar) == 4096
    # a GQA layer: q, o and the elementwise gate 33.55 M each, k + v 8.39 M
    assert costs_kda.gqa_params(solar) == 3 * 4096 * 8192 + 2 * 4096 * 1024
    assert costs_kda.gqa_params(solar) == 109_051_904
    # a KDA mixer: q, k, v, o 4 x 33.55 M; two low-rank pairs 2 x 1.57 M;
    # the step size 0.26 M; the taps 0.10 M
    kda = (4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
           + 4 * 3 * 8192)
    assert costs_kda.kda_params(solar) == kda == 137_723_904
    assert costs_kda.expert_params(solar) == 3 * 4096 * 1280 == 15_728_640
    # 2 GQA + 6 KDA mixers, 8 x (router 320 wide + the shared expert), the
    # head's slice of 24,576 rows
    always = (2 * 109_051_904 + 6 * kda + 8 * (4096 * 320 + 15_728_640)
              + 4096 * 24576)
    assert costs_kda.always_read_params(solar) == always
    # the cell's cut: 6.415 B parameters with the embedding aside
    total = always + 4096 * 24576 + 8 * 40 * 15_728_640
    assert 6.41e9 < total < 6.42e9


def test_a_steps_bytes_and_a_blocks_flops(solar):
    # 15.2 live rows of ~66k tokens, a third of the held experts touched
    live, rows, share = 66_000.0, 15.2, 13 / 40
    nbytes = costs_kda.decode_step_bytes(
        solar, live, rows, share, "bfloat16")
    weights = 2 * costs_kda.always_read_params(solar)
    experts = 2 * share * 8 * 40 * 15_728_640
    kv = 4096 * 2 * live
    state = rows * 6 * 2 * (4 * 2**20 + 144 * 2**10)
    assert nbytes == pytest.approx(weights + experts + kv + state)
    # 2.56 GB of always-read weights, 3.27 GB of touched experts, 0.54 GB of
    # K and V, 0.79 GB of state: 7.2 GB, 8.7 ms at 819 GB/s
    assert 2.5e9 < weights < 2.6e9 and 3.2e9 < experts < 3.3e9
    assert 0.53e9 < kv < 0.55e9 and 0.78e9 < state < 0.80e9
    least, bound = costs.roofline_seconds(
        costs_kda.decode_step_flops(solar, 16, 1.0, live, rows), nbytes, PEAK)
    assert bound == "memory" and 8.5e-3 < least < 9.3e-3
    # the decode kernel alone: the state in and out and the vectors
    per = 2 * 4 * 2**20 + 8192 * (3 * 2 + 8) + 64 * 4
    assert costs_kda.kda_decode_bytes(solar, 100.0) == 100 * 6 * per
    assert costs_kda.kda_decode_flops(solar, 1.0) == 6 * 64 * 7 * 128 * 128
    # a block of the chunkwise algorithm: per token and head
    # 2 (5 x 64 x 128 + 64^2 + 3 x 128^2) = 188,416 flops
    assert costs_kda.kda_prefill_flops(solar, 1.0) == 6 * 64 * 188_416
    # a 2,048-token chunk of one row: 148 GFLOP, 0.75 ms at the peak; its
    # bytes: 6 layers x (2,048 x 98,560 + 8 MiB) = 1.26 GB,
    # 1.54 ms at 819 GB/s — the bound
    flops = costs_kda.kda_prefill_flops(solar, 2048)
    nbytes = costs_kda.kda_prefill_bytes(solar, 2048, 1)
    assert flops == pytest.approx(148.2e9, rel=1e-3)
    assert nbytes == 6 * (2048 * (8192 * 12 + 256) + 2 * 4 * 2**20)
    least, bound = costs.roofline_seconds(flops, nbytes, PEAK)
    assert bound == "memory" and 1.5e-3 < least < 1.6e-3
    # the two GQA layers' attention: 4 x 128 x 64 flops a pair and layer,
    # 4 KiB of K and V a key and layer; a 2,048-token chunk that ends a
    # 16,384-token context: 2,048 x 14,336 + 2,048 x 2,049 / 2 pairs,
    # 2.06 TFLOP, 10.5 ms at the peak — its keys' 134 MB take 0.16 ms
    pairs = 2048 * 14336 + 2048 * 2049 // 2
    assert costs_kda.gqa_attn_flops(solar, 1.0) == 2 * 64 * 4 * 128
    assert costs_kda.gqa_attn_bytes(solar, 1.0) == 2 * 4096
    least, bound = costs.roofline_seconds(
        costs_kda.gqa_attn_flops(solar, pairs),
        costs_kda.gqa_attn_bytes(solar, 16384), PEAK)
    assert bound == "compute" and 10.4e-3 < least < 10.6e-3


class _Rec:
    def __init__(self, prompt, t0, t1):
        self.prompt_tokens, self.chunk_times, self.done = prompt, [t0], t1

    def tokens_between(self, a, b):
        return 0.0


def _ctx(config, ops, modules, counts, timing0, timing1):
    return {
        "config": config, "peaks": PEAK,
        "trace": {"device_ops": ops, "modules": modules,
                  "op_counts": counts},
        "trace_span": {"t0": 1.0, "t1": 2.0,
                       "timing0": {"engine": timing0},
                       "timing1": {"engine": timing1}},
        "records": [_Rec(4000, 0.0, 10.0) for _ in range(15)],
    }


ZERO = {"moe_decode_touched_n": 0, "moe_decode_slots_n": 0,
        "moe_decode_held_pairs_n": 0, "kda_prefill_tokens_n": 0,
        "kda_prefill_segments_n": 0, "kda_decode_rows_n": 0,
        "attn_prefill_pairs_n": 0, "attn_prefill_keys_n": 0}


def test_readers_on_hand_made_documents(solar):
    steps, live_rows = 40, 15
    ctx = _ctx(
        solar,
        ops=[["kda_prefill_attention", 0.4], ["kda_decode_attention", 0.06],
             ["paged_prefill_attention", 0.2],
             ["paged_decode_attention", 0.03]],
        modules=[["jit_decode_window_fn", 0.5]],
        counts={"kda_decode_attention": steps * 6,
                "paged_decode_attention": steps * 2},
        timing0=ZERO,
        timing1={"moe_decode_touched_n": 13 * 8 * steps,
                 "moe_decode_slots_n": 40 * 8 * steps,
                 "moe_decode_held_pairs_n": 16 * 8 * steps,
                 "kda_prefill_tokens_n": 20_000,
                 "kda_prefill_segments_n": 12,
                 "kda_decode_rows_n": live_rows * steps,
                 "attn_prefill_pairs_n": 150_000_000,
                 "attn_prefill_keys_n": 90_000},
    )
    least, _ = costs.roofline_seconds(
        costs_kda.kda_prefill_flops(solar, 20_000),
        costs_kda.kda_prefill_bytes(solar, 20_000, 12), PEAK)
    assert kda_prefill_roofline.read(ctx) == pytest.approx(
        100 * least / 0.4)
    # the kernel ran the bucket's 16 rows a step, live or padding
    assert kda_decode_roofline.read(ctx) == pytest.approx(
        100 * costs_kda.kda_decode_bytes(solar, 16 * steps) / 819e9 / 0.06)
    nbytes = costs_kda.decode_step_bytes(
        solar, 15 * 4000.0, live_rows, 13 / 40, "bfloat16")
    assert kda_decode_step_roofline.read(ctx) == pytest.approx(
        100 * (nbytes / 819e9) / (0.5 / steps))
    # a counter that disagrees with the kernel's events (20 live rows a
    # step of a 16-row bucket) is not clipped: the share shows it
    over = dict(ctx, trace_span=dict(ctx["trace_span"], timing1={"engine": {
        **ctx["trace_span"]["timing1"]["engine"],
        "kda_decode_rows_n": 20 * steps}}))
    more = costs_kda.decode_step_bytes(
        solar, 15 * 4000.0, 20, 13 / 40, "bfloat16")
    assert kda_decode_step_roofline.read(over) == pytest.approx(
        100 * (more / 819e9) / (0.5 / steps))
    # the GQA layers' kernels: the chunks' pairs against the peak, a step's
    # live keys against the bandwidth
    assert gqa_prefill_attn_roofline.read(ctx) == pytest.approx(
        100 * 150e6 * 2 * 64 * 4 * 128 / 197e12 / 0.2)
    assert gqa_decode_attn_roofline.read(ctx) == pytest.approx(
        100 * steps * (15 * 4000 * 2 * 4096 / 819e9) / 0.03)


def test_readers_find_nothing_on_a_parent_or_another_model(solar):
    # a parent commit given the new files: no kernel events, no counters
    parent = _ctx(solar, ops=[["fusion", 1.0]],
                  modules=[["jit_decode_window_fn", 0.8]],
                  counts={"paged_decode_attention": 100},
                  timing0={}, timing1={})
    readers = (kda_prefill_roofline, kda_decode_roofline,
               kda_decode_step_roofline, gqa_prefill_attn_roofline,
               gqa_decode_attn_roofline)
    for reader in readers:
        assert reader.read(parent) is None
    # another configuration (no linear_attn_config key)
    with open(os.path.join(
            HERE, "configs", "laguna-s21-bf16-ep4-9l.json")) as f:
        other = dict(parent, config=json.load(f))
    for reader in readers:
        assert reader.read(other) is None
    # no trace at all (a failed capture)
    for reader in readers:
        assert reader.read(dict(parent, trace=None)) is None


def test_the_manifest_names_what_was_added(solar):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"] if c["name"] == solar["name"])
    assert entry["file"].endswith("configs/solar-open2-bf16-ep8-8l.json")
    assert entry["source"] == solar["source"]
    assert sorted(entry["reduced"]) == sorted(solar["reduced"])
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        solar["name"], "longdoc-closed", 1)
    assert os.path.exists(
        os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    # looked up by name: later PRs append entries behind these, and cells
    # behind this one on a list
    for name in ("kda_prefill_roofline", "kda_decode_roofline",
                 "kda_decode_step_roofline", "kda_state_share",
                 "gqa_prefill_attn_roofline", "gqa_decode_attn_roofline"):
        m = by_name[name]
        assert CELL in m["workloads"] and m["moves"] == "out_tok_per_s"
        with open(os.path.join(HERE, "metrics", name + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(
            os.path.join(HERE, "readers", spec["reader"] + ".py"))
    # the accepted metrics whose readers read this configuration's keys as
    # they stand (counters; hidden_size, moe_intermediate_size, weight_dtype)
    for name in ("moe_touched_share", "moe_read_share",
                 "moe_held_pairs_per_expert", "moe_prefill_experts_roofline"):
        assert CELL in by_name[name]["workloads"]
    # every width as published; the cut's keys beside their published values
    assert (solar["hidden_size"], solar["moe_intermediate_size"],
            solar["num_experts_per_tok"], solar["head_dim"]) == (
                4096, 1280, 8, 128)
    assert solar["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert (solar["n_routed_experts"], solar["n_routed_experts_published"],
            solar["vocab_size_published"]) == (40, 320, 196608)
    assert "reference" in os.listdir(HERE) and os.path.exists(
        os.path.join(HERE, "reference", "solar_open2.py"))
