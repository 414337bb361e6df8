"""`costs_gdn` against counts done by hand for the Olmo-Hybrid cut (the
cell's configuration), the five shares of `readers/gdn_roofline.py` on
hand-made documents and on a program that has neither the kernels nor the
counters, and the manifest's cross-references for what ISSUE 58 added (every
entry found by NAME)."""

import json
import os

import costs
import costs_gdn
import pytest
from readers import gdn_roofline

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(HERE))
CONFIG = "olmo-hybrid-7b-bf16-12l"
CELL = CONFIG + ".longdoc-saturated"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SHARES = {"gdn_prefill_roofline": "gdn_prefill",
          "gdn_decode_roofline": "gdn_decode",
          "olmo_prefill_attn_roofline": "prefill_attn",
          "olmo_decode_attn_roofline": "decode_attn",
          "olmo_decode_step_roofline": "decode_step"}


@pytest.fixture(scope="module")
def olmo():
    with open(os.path.join(HERE, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_the_cut_by_hand(olmo):
    assert costs_gdn.linear_layers(olmo) == [0, 1, 2, 4, 5, 6, 8, 9, 10]
    assert costs_gdn.full_layers(olmo) == [3, 7, 11]
    assert costs_gdn.head_dim(olmo) == 128  # `head_dim` null: h / heads
    # a row's state in one linear layer: 30 heads x 96 x 192 float32; its
    # convolution tail: 3 rows x 30 x (96 + 96 + 192) bf16 = 67.5 KiB
    assert costs_gdn.state_bytes_per_row_layer(olmo) == 30 * 96 * 192 * 4
    assert costs_gdn.state_bytes_per_row_layer(olmo) == 2_211_840
    assert costs_gdn.tail_bytes_per_row_layer(olmo) == 69_120
    # K and V of 30 kv heads x 128 in bf16: 15 KiB a token and full layer
    assert costs_gdn.full_attn_bytes(olmo, 1.0) == 3 * 15 * 2**10
    # a full mixer: q, k, v, o 3840 x 3840 each
    assert costs_gdn.full_params(olmo) == 4 * 3840 * 3840 == 58_982_400
    # a linear mixer: q, k 2 x 11.06 M; v, z, o 3 x 22.12 M; the gate's and
    # the step size's projections 2 x 0.115 M; the taps 0.046 M
    lin = (2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30 + 4 * 11520)
    assert costs_gdn.linear_params(olmo) == lin == 88_750_080
    assert costs_gdn.mlp_params(olmo) == 3 * 3840 * 11008 == 126_812_160
    always = (3 * 58_982_400 + 9 * lin + 12 * 126_812_160
              + 3840 * 100352)
    assert costs_gdn.always_read_params(olmo) == always
    # the cell's cut: 3.268 B parameters with the embedding
    assert 3.26e9 < always + 3840 * 100352 < 3.27e9


def test_a_steps_bytes_and_a_blocks_flops(olmo):
    # 15.2 live rows of ~110k tokens
    live, rows = 110_000.0, 15.2
    nbytes = costs_gdn.decode_step_bytes(olmo, live, rows, "bfloat16")
    weights = 2 * costs_gdn.always_read_params(olmo)
    kv = 45 * 2**10 * live
    state = rows * 9 * 2 * (2_211_840 + 69_120)
    assert nbytes == pytest.approx(weights + kv + state)
    # 5.77 GB of weights, 5.07 GB of K and V, 0.62 GB of state in and out:
    # 11.5 GB, 14.0 ms at 819 GB/s
    assert 5.7e9 < weights < 5.8e9 and 5.0e9 < kv < 5.1e9
    assert 0.61e9 < state < 0.64e9
    least, bound = costs.roofline_seconds(
        costs_gdn.decode_step_flops(olmo, 16, live, rows), nbytes, PEAK)
    assert bound == "memory" and 13.5e-3 < least < 14.5e-3
    # the decode kernel alone: the state in and out and the vectors — q, k,
    # v in bf16, o in float32, ONE gate and one step size a head
    per = 2 * 2_211_840 + 30 * ((96 + 96 + 192) * 2 + 192 * 4 + 8)
    assert costs_gdn.gdn_decode_bytes(olmo, 100.0) == 100 * 9 * per
    assert costs_gdn.gdn_decode_flops(olmo, 1.0) == 9 * 30 * 7 * 96 * 192
    # a block of the chunkwise algorithm: per token and head
    # 2 (2 x 64 x 96 + 64^2 + 2 x 64 x 192 + 3 x 96 x 192) = 192,512 flops
    assert costs_gdn.gdn_prefill_flops(olmo, 1.0) == 9 * 30 * 192_512
    # per token: q, k, v, o in bf16 and 8 B of gate and step size a head;
    # per segment: the state in and out
    assert costs_gdn.gdn_prefill_bytes(olmo, 2048.0, 3.0) == 9 * (
        2048 * 30 * ((2 * 96 + 2 * 192) * 2 + 8) + 3 * 2 * 2_211_840)
    # a 2,048-token chunk of 3 rows: 106 GFLOP, 0.54 ms at the peak; its
    # 0.76 GB (q, k, v, o of every token and head; 6 states) 0.93 ms
    flops = costs_gdn.gdn_prefill_flops(olmo, 2048.0)
    least, bound = costs.roofline_seconds(
        flops, costs_gdn.gdn_prefill_bytes(olmo, 2048.0, 3.0), PEAK)
    assert 0.5e-3 < flops / 197e12 < 0.6e-3
    assert bound == "memory" and 0.9e-3 < least < 1.0e-3
    assert costs_gdn.full_attn_flops(olmo, 1.0) == 4 * 128 * 30 * 3


def _ctx(olmo, *, ops, counts, modules=(), timing=(None, None)):
    t0, t1 = timing
    return {
        "config": olmo, "peaks": PEAK,
        "trace": {"device_ops": list(ops.items()), "op_counts": counts,
                  "modules": list(modules)},
        "trace_span": {"t0": 10.0, "t1": 18.0,
                       **({"timing0": {"engine": t0},
                           "timing1": {"engine": t1}} if t0 is not None
                          else {})},
        # one request whose context stands at 1,000 tokens over the slice
        "records": [], "samples": [], "window": None,
    }


def test_the_kernel_shares_on_hand_made_documents(olmo, monkeypatch):
    monkeypatch.setattr(gdn_roofline, "live_tokens", lambda *a: 100_000.0)
    t0 = {"kda_prefill_tokens_n": 0, "kda_prefill_segments_n": 0,
          "attn_prefill_pairs_n": 0, "attn_prefill_keys_n": 0,
          "kda_decode_rows_n": 0}
    t1 = {"kda_prefill_tokens_n": 20480, "kda_prefill_segments_n": 30,
          "attn_prefill_pairs_n": 2e8, "attn_prefill_keys_n": 1e5,
          "kda_decode_rows_n": 15 * 80}
    ctx = _ctx(
        olmo,
        ops={"gdn_prefill_attention": 0.1, "gdn_decode_attention": 0.1,
             "paged_prefill_attention": 0.2, "paged_decode_attention": 0.5},
        counts={"gdn_decode_attention": 9 * 80,
                "paged_decode_attention": 3 * 80},
        modules=[("jit_decode_window_fn", 80 * 0.020)], timing=(t0, t1))
    def least(flops, nbytes):
        return costs.roofline_seconds(flops, nbytes, PEAK)[0]

    want = {
        "gdn_prefill": least(
            costs_gdn.gdn_prefill_flops(olmo, 20480),
            costs_gdn.gdn_prefill_bytes(olmo, 20480, 30)) / 0.1,
        # the rows the kernel RAN: 16 a step, padding rows included
        "gdn_decode": costs_gdn.gdn_decode_bytes(
            olmo, 80 * 16) / 819e9 / 0.1,
        "prefill_attn": costs_gdn.full_attn_flops(olmo, 2e8) / 197e12 / 0.2,
        "decode_attn": 80 * costs_gdn.full_attn_bytes(
            olmo, 100_000.0) / 819e9 / 0.5,
        # the LIVE rows' state: the counter's 15 a step
        "decode_step": costs_gdn.decode_step_bytes(
            olmo, 100_000.0, 15.0, "bfloat16") / 819e9 / 0.020,
    }
    for which, share in want.items():
        got = gdn_roofline.read(ctx, which)
        assert got == pytest.approx(100.0 * share), which
        assert 0 < got < 100, (which, got)
    with pytest.raises(ValueError):
        gdn_roofline.read(ctx, "no_such_share")


@pytest.mark.parametrize("which", sorted(SHARES.values()))
def test_a_program_without_the_kernels_reads_nothing(olmo, which,
                                                     monkeypatch):
    """A parent commit, another model, XLA attention: no kernel events, no
    counters — the reader returns None and does not raise."""
    monkeypatch.setattr(gdn_roofline, "live_tokens", lambda *a: 100_000.0)
    empty = _ctx(olmo, ops={}, counts={})
    assert gdn_roofline.read(empty, which) is None
    no_trace = {**empty, "trace": None}
    assert gdn_roofline.read(no_trace, which) is None
    other = {**empty, "config": {**olmo, "model_type": "solar_open2"}}
    assert gdn_roofline.read(other, which) is None
    # the kernels ran, the counters are another program's
    ran = _ctx(olmo, ops={"gdn_prefill_attention": 0.1,
                          "paged_prefill_attention": 0.1},
               counts={}, timing=({}, {}))
    assert gdn_roofline.read(ran, which) is None


def test_the_manifest_names_what_issue_58_added():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    config = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert config["file"] == f"benchmarks/chip/configs/{CONFIG}.json"
    assert config["reduced"] == [
        "num_hidden_layers", "layer_types", "max_position_embeddings"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longdoc-closed", 1)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, which in SHARES.items():
        m = by_name[name]
        assert m["workloads"] == [CELL], name
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == ("%", "higher", "device_trace", "kernels",
                                "out_tok_per_s")
        with open(os.path.join(HERE, "metrics", name + ".json")) as f:
            assert json.load(f) == {"reader": "gdn_roofline",
                                    "args": {"which": which}}
    for name in ("kda_state_share", "chunk_glue_ms_per_ktok",
                 "window_glue_ms"):
        assert CELL in by_name[name]["workloads"], name
    # no list whose reader counts layers another way
    for name in ("decode_attn_roofline", "gqa_prefill_attn_roofline",
                 "gqa_decode_attn_roofline", "moe_read_share",
                 "kda_prefill_roofline", "kda_decode_roofline"):
        assert CELL not in by_name[name]["workloads"], name
