"""`costs_ssd` against counts done by hand for the Falcon-H1 cut (the cell's
configuration), the SSD readers and the two views of accepted readers on
hand-made documents and on a parent commit that has neither the kernels nor
the counters, that `decode_attn_roofline`'s accepted reader counts this
configuration right, and the manifest's cross-references for what ISSUE 50
added."""

import json
import os

import costs
import costs_ssd
import pytest
from readers import (
    decode_attn_roofline, gauge_stat, h1_decode_step_roofline, h1_view,
    ssd_decode_roofline, ssd_prefill_roofline,
)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "falcon-h1-34b-bf16-6l.longdoc-saturated"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("ssd_prefill_roofline", "ssd_decode_roofline",
       "h1_decode_step_roofline", "h1_prefill_attn_roofline",
       "h1_state_share")


@pytest.fixture(scope="module")
def h1():
    path = os.path.join(HERE, "configs", "falcon-h1-34b-bf16-6l.json")
    with open(path) as f:
        return json.load(f)


def test_the_cut_by_hand(h1):
    # a row's state in one layer: 32 heads x 128 x 256 float32 = 4 MiB; its
    # convolution tail: 3 rows x 5,120 channels (4,096 x + 2 x 2 x 256 B, C)
    # in bf16 = 30 KiB
    assert costs_ssd.conv_channels(h1) == 5120
    assert costs_ssd.state_bytes_per_row_layer(h1) == 4 * 2**20
    assert costs_ssd.tail_bytes_per_row_layer(h1) == 30 * 2**10
    assert costs_ssd.slot_bytes(h1) == 6 * (4 * 2**20 + 30 * 2**10)
    # K and V of 4 kv heads x 128 in bf16: 2 KiB a layer, 12 KiB a token
    assert costs.kv_bytes_per_token_per_layer(h1) == 2048
    assert costs.kv_bytes_per_token(h1) == 12 * 2**10
    # attention: W_q and W_o 5120 x 2560, W_k and W_v 5120 x 512
    assert costs_ssd.attn_params(h1) == 31_457_280
    # the mixer: W_in 5120 x 9248, W_out 4096 x 5120, 4 taps + bias over
    # 5,120 channels, the norm's 4,096, A_log / D / dt_bias 32 each
    ssm = (5120 * 9248 + 4096 * 5120 + 5 * 5120 + 4096 + 3 * 32)
    assert costs_ssd.ssm_params(h1) == ssm == 68_351_072
    layer = 31_457_280 + ssm + 3 * 5120 * 21504 + 2 * 5120
    assert costs_ssd.layer_params(h1) == layer
    assert 430.0e6 < layer < 430.2e6
    assert costs_ssd.head_params(h1) == 261120 * 5120
    # the cut: 6 layers, embedding and head = 5.255 B parameters
    total = 6 * layer + 2 * 261120 * 5120
    assert 5.254e9 < total < 5.256e9
    # the head is a third of a decode step's weight bytes here
    assert 0.33 < costs_ssd.head_params(h1) / costs_ssd.step_read_params(
        h1) < 0.35


def test_a_steps_bytes_and_a_blocks_flops(h1):
    live, rows = 100_000.0, 13.0
    nbytes = costs_ssd.decode_step_bytes(h1, live, rows, "bfloat16")
    weights = 2 * costs_ssd.step_read_params(h1)
    kv = live * 12 * 2**10
    state = rows * 6 * (2 * 4 * 2**20 + 2 * 30 * 2**10)
    assert nbytes == pytest.approx(weights + kv + state)
    assert 7.8e9 < weights < 7.9e9
    # the mixers' caches are a tenth of a step's bytes
    assert 0.05 < state / nbytes < 0.12
    # a 2,048-token chunk of one layer: C B^T once a GROUP, the rest a head
    flops = costs_ssd.ssd_prefill_flops(h1, 2048) / 6
    by_hand = 2048 * 2 * (2 * 128 * 256 + 32 * (128 * 128 + 2 * 256 * 128))
    assert flops == by_hand
    assert 0.010e12 < flops < 0.012e12
    # ... bytes: x, B, C in bf16, dt and y in float32, and a segment's state
    per_token = 5120 * 2 + 32 * 4 + 4096 * 4
    assert costs_ssd.ssd_prefill_bytes(h1, 2048, 3) == 6 * (
        2048 * per_token + 3 * 2 * 4 * 2**20)
    # decode: bytes bound (5 N P flops a head against 8 MiB of state)
    least, bound = costs.roofline_seconds(
        costs_ssd.ssd_decode_flops(h1, 16), costs_ssd.ssd_decode_bytes(
            h1, 16), PEAK)
    assert bound == "memory"
    assert costs_ssd.ssd_decode_bytes(h1, 16) == 16 * 6 * (
        2 * 4 * 2**20 + per_token)


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


ZERO = {"ssd_prefill_tokens_n": 0, "ssd_prefill_segments_n": 0,
        "ssd_decode_rows_n": 0, "attn_prefill_pairs_n": 0,
        "attn_prefill_keys_n": 0}


def _served(h1, steps=40, live_rows=15):
    return _ctx(
        h1,
        ops=[["ssd_prefill_scan", 0.2], ["ssd_decode_step", 0.3],
             ["paged_prefill_attention", 0.4],
             ["paged_decode_attention", 0.05]],
        modules=[["jit_decode_window_fn", 0.6]],
        counts={"ssd_decode_step": steps * 6,
                "paged_decode_attention": steps * 6},
        timing0=ZERO,
        timing1={"ssd_prefill_tokens_n": 20_000,
                 "ssd_prefill_segments_n": 12,
                 "ssd_decode_rows_n": live_rows * steps,
                 "attn_prefill_pairs_n": 150_000_000,
                 "attn_prefill_keys_n": 90_000})


def test_readers_on_hand_made_documents(h1):
    steps, live_rows = 40, 15
    ctx = _served(h1, steps, live_rows)
    least, _ = costs.roofline_seconds(
        costs_ssd.ssd_prefill_flops(h1, 20_000),
        costs_ssd.ssd_prefill_bytes(h1, 20_000, 12), PEAK)
    assert ssd_prefill_roofline.read(ctx) == pytest.approx(100 * least / 0.2)
    # the kernel ran the bucket's 16 rows a step, live or padding
    assert ssd_decode_roofline.read(ctx) == pytest.approx(
        100 * costs_ssd.ssd_decode_bytes(h1, 16 * steps) / 819e9 / 0.3)
    nbytes = costs_ssd.decode_step_bytes(
        h1, 15 * 4000.0, live_rows, "bfloat16")
    assert h1_decode_step_roofline.read(ctx) == pytest.approx(
        100 * (nbytes / 819e9) / (0.6 / steps))
    # the accepted GQA prefill reader through the view: ALL six layers
    assert h1_view.read(
        ctx, of="gqa_prefill_attn_roofline", view="as_gqa"
    ) == pytest.approx(100 * 150e6 * 6 * 20 * 4 * 128 / 197e12 / 0.4)


def test_the_accepted_decode_attention_reader_counts_this_model_right(h1):
    """`decode_attn_roofline` multiplies a token's K and V by
    `num_hidden_layers`: right here, where EVERY layer holds pages (12 KiB
    a token) — unlike the Solar and Ling configurations, whose cells stay
    off its list."""
    steps = 40
    ctx = _served(h1, steps)
    assert decode_attn_roofline.read(ctx) == pytest.approx(
        100 * steps * (15 * 4000 * 12 * 2**10 / 819e9) / 0.05)
    assert costs.decode_attn_bytes(h1, 1.0, "bfloat16", 1) == 6 * 2 * 4 * (
        128 * 2)


def test_readers_find_nothing_on_a_parent_or_another_model(h1):
    # no kernel events, no counters (XLA attention; a parent commit prints
    # no line at all for this configuration: it cannot build the model)
    bare = _ctx(h1, ops=[["fusion", 1.0]],
                modules=[["jit_decode_window_fn", 0.8]],
                counts={"paged_decode_attention": 100},
                timing0={}, timing1={})
    readers = (ssd_prefill_roofline, ssd_decode_roofline,
               h1_decode_step_roofline)
    for reader in readers:
        assert reader.read(bare) is None
    assert h1_view.read(bare, of="gqa_prefill_attn_roofline",
                        view="as_gqa") is None
    # another configuration (no mamba_d_state key), events and counters or
    # not
    with open(os.path.join(
            HERE, "configs", "solar-open2-bf16-ep8-8l.json")) as f:
        solar = json.load(f)
    other = dict(_served(h1), config=solar)
    for reader in readers:
        assert reader.read(other) is None
    assert h1_view.read(other, of="gqa_prefill_attn_roofline",
                        view="as_gqa") is None
    # no trace at all (a failed capture)
    for reader in readers:
        assert reader.read(dict(bare, trace=None)) is None


def test_the_state_share_is_the_generic_gauge(h1):
    with open(os.path.join(HERE, "metrics", "h1_state_share.json")) as f:
        mine = json.load(f)
    with open(os.path.join(HERE, "metrics", "kda_state_share.json")) as f:
        accepted = json.load(f)
    assert mine == accepted and mine["reader"] == "gauge_stat"
    assert callable(gauge_stat.read)


def test_the_manifest_names_what_was_added(h1):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"] if c["name"] == h1["name"])
    assert entry["file"].endswith("configs/falcon-h1-34b-bf16-6l.json")
    assert entry["source"] == h1["source"]
    assert sorted(entry["reduced"]) == sorted(h1["reduced"]) == [
        "max_position_embeddings", "num_hidden_layers"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        h1["name"], "longdoc-closed", 1)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_per_s"
        with open(os.path.join(HERE, "metrics", name + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(
            os.path.join(HERE, "readers", spec["reader"] + ".py"))
    # the accepted metric whose reader counts this configuration right
    assert CELL in by_name["decode_attn_roofline"]["workloads"]
    # ... and not those that read `gqa_layers` / `linear_attn_config`, know
    # no state (`decode_step_roofline`) or a square one
    for name in ("decode_step_roofline", "kda_prefill_roofline",
                 "kda_decode_roofline", "kda_decode_step_roofline",
                 "gqa_prefill_attn_roofline", "gqa_decode_attn_roofline"):
        assert CELL not in by_name[name]["workloads"]
    # every width as published; the cut's keys beside their published values
    assert (h1["hidden_size"], h1["intermediate_size"], h1["vocab_size"],
            h1["mamba_d_ssm"], h1["mamba_d_state"], h1["mamba_n_heads"],
            h1["num_attention_heads"], h1["num_key_value_heads"]) == (
                5120, 21504, 261120, 4096, 256, 32, 20, 4)
    assert (h1["num_hidden_layers"], h1["num_hidden_layers_published"],
            h1["max_position_embeddings"],
            h1["max_position_embeddings_published"]) == (
                6, 72, 32768, 262144)
    assert os.path.exists(os.path.join(HERE, "reference", "falcon_h1.py"))
