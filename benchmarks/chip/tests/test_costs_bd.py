"""`costs_bd` against hand-computed SDAR-30B-A3B numbers (8 of 48 layers),
the three readers of a window of block passes on hand-made documents and on
a parent without the module or the counters, and the cell's entries in the
manifest."""

import json
import os

import costs
import costs_bd
import pytest
from readers import bd_decode_step_roofline, bd_pass_ms, timing_ratio_opt

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(HERE))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "sdar-30b-a3b-bf16-8l.chat-saturated"


@pytest.fixture(scope="module")
def sdar():
    with open(os.path.join(
            HERE, "configs", "sdar-30b-a3b-bf16-8l.json")) as f:
        return json.load(f)


def test_sizes_by_hand(sdar):
    # one expert: gate, up, down of 2048 x 768
    assert costs_bd.expert_params(sdar) == 3 * 2048 * 768 == 4_718_592
    # a layer's attention 2 x (2048 x 4096) + 2 x (2048 x 512) and its
    # 2048 x 128 router; the head 2048 x 151,936
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512
    assert attn == 18_874_368
    assert costs_bd.dense_params(sdar) == 8 * (attn + 262_144) + 311_164_928
    assert costs_bd.dense_params(sdar) == 464_257_024
    # the cut's parameters (ISSUE 41): 8 layers + embedding + head + norm
    layer = attn + 256 + 4096 + 262_144 + 128 * 4_718_592
    assert layer == 623_120_640
    assert 8 * layer + 2 * 311_164_928 + 2048 == 5_607_297_024
    # 4 kv heads x 128, bf16 K and V, 8 layers: 16 KiB a token
    assert costs.kv_bytes_per_token(sdar) == 16 * 1024


def test_a_pass_by_hand(sdar):
    """16 rows of ~590 live tokens, 98.4% of the experts touched: 10.59 GB
    a pass — 12.9 ms at 819 GB/s — against 0.10 TFLOP, 0.52 ms: bound by
    bytes, as a decode step should be."""
    live, share = 9440, 0.984
    experts = share * 8 * 128 * 4_718_592
    want = (464_257_024 + experts) * 2 + live * 16_384
    got = costs_bd.pass_bytes(sdar, live, share, "bfloat16", "bfloat16")
    assert got == pytest.approx(want) and got == pytest.approx(
        10.592e9, rel=1e-3)
    # 64 tokens through the dense weights and 8 experts a layer, and 4
    # queries a row against the live context (4 flops a head, dim and key)
    flops = (2 * (464_257_024 + 8 * 8 * 4_718_592) * 64
             + 4 * (4 * 32 * 128 * live * 8))
    assert costs_bd.pass_flops(sdar, 16, live) == flops == 103_028_883_456
    least, bound = costs.roofline_seconds(flops, got, PEAK)
    assert bound == "memory" and least == pytest.approx(12.93e-3, rel=1e-3)
    # nothing is clipped: every expert touched reads them all
    assert costs_bd.pass_bytes(sdar, 0, 1.0, "bfloat16") == (
        464_257_024 + 8 * 128 * 4_718_592) * 2


class _Rec:
    """A client record that holds 600 context tokens over the slice."""
    prompt_tokens, chunk_times, done = 500, [0.0], 100.0

    def tokens_between(self, a, b):
        return 100


def _ctx(sdar, timing0, timing1, modules, op_counts):
    return {"trace": {"modules": modules, "op_counts": op_counts,
                      "device_ops": []},
            "config": sdar, "peaks": PEAK, "records": [_Rec()] * 16,
            "trace_span": {"t0": 1.0, "t1": 2.0,
                           "timing0": {"engine": timing0},
                           "timing1": {"engine": timing1}}}


T0 = {"moe_decode_touched_n": 0, "moe_decode_slots_n": 0, "bd_passes_n": 0}
# 50 passes of 8 layers x 128 experts, 98% touched
T1 = {"moe_decode_touched_n": 50_176, "moe_decode_slots_n": 51_200,
      "bd_passes_n": 50}


def test_the_step_roofline_reader(sdar):
    """50 passes in 1.0 s of `jit_bd_window_fn` (20 ms a pass) against the
    12.9 ms the bytes need: ~65%; the passes are the decode kernel's events
    over the layers where the trace has them."""
    modules = [["jit_bd_window_fn", 1.0], ["jit_step_fn", 0.2]]
    ctx = _ctx(sdar, T0, T1, modules, {"paged_decode_attention": 400})
    live = 16 * 600
    need, _ = costs.roofline_seconds(
        costs_bd.pass_flops(sdar, 16, live),
        costs_bd.pass_bytes(sdar, live, 0.98, "bfloat16", "bfloat16"), PEAK)
    assert bd_decode_step_roofline.read(ctx) == pytest.approx(
        100 * need / 0.02)
    assert 60 < bd_decode_step_roofline.read(ctx) < 70
    # XLA attention (no kernel event): the program's own count of passes
    ctx = _ctx(sdar, T0, T1, modules, {})
    assert bd_decode_step_roofline.read(ctx) == pytest.approx(
        100 * need / 0.02)
    assert bd_pass_ms.read(ctx) == pytest.approx(20.0)


@pytest.mark.parametrize("timing0,timing1,modules", [
    # a parent without the counters, another model's decode module, a
    # config without blocks: left out, never raised
    ({}, {}, [["jit_bd_window_fn", 1.0]]),
    (T0, T1, [["jit_decode_window_fn", 1.0]]),
    (T0, T0, [["jit_bd_window_fn", 1.0]]),
])
def test_nothing_to_read_leaves_the_metrics_out(sdar, timing0, timing1,
                                                modules):
    ctx = _ctx(sdar, timing0, timing1, modules, {})
    assert bd_decode_step_roofline.read(ctx) is None
    assert bd_pass_ms.read(ctx) is None
    other = {k: v for k, v in sdar.items() if k != "block_length"}
    assert bd_decode_step_roofline.read(
        _ctx(other, T0, T1, [["jit_bd_window_fn", 1.0]], {})) is None
    assert bd_pass_ms.read({"trace": None, "trace_span": {}}) is None


def test_tokens_per_pass_from_its_data_file():
    with open(os.path.join(HERE, "metrics", "bd_tokens_per_pass.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "timing_ratio_opt"
    ctx = {"start": {"timing": {"engine": {
        "bd_tokens_n": 100, "bd_row_passes_n": 200}}},
        "end": {"timing": {"engine": {
            "bd_tokens_n": 900, "bd_row_passes_n": 1200}}}}
    # 4 tokens a block over 5 passes: 0.8, the worst case
    assert timing_ratio_opt.read(ctx, **spec["args"]) == pytest.approx(0.8)
    ctx["start"]["timing"]["engine"] = {}
    assert timing_ratio_opt.read(ctx, **spec["args"]) is None


def test_the_cell_in_the_manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar-30b-a3b-bf16-8l", "chat-closed", 1)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("bd_tokens_per_pass", "bd_pass_ms",
                 "bd_decode_step_roofline"):
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "out_tok_per_s"
    for name in ("decode_attn_roofline", "moe_touched_share",
                 "moe_read_share", "moe_prefill_experts_roofline",
                 "moe_tokens_per_expert"):
        assert CELL in by_name[name]["workloads"]
    # ... and on no list whose reader divides by positions or reads another
    # model's keys
    for name in ("decode_step_roofline", "moe_decode_step_roofline",
                 "moe_held_pairs_per_expert"):
        assert CELL not in by_name[name]["workloads"]
