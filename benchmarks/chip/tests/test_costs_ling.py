"""`costs_ling` against counts done by hand for the Ling-3.0-flash cut (the
cell's configuration: 5.23 B parameters, 0.41 GiB of state, 0.16 GiB of
latent pages), the new readers on hand-made documents and on a parent commit
that has neither the kernels nor the counters, the accepted readers the new
cell joins on this configuration's keys, and the manifest's cross-references
for what ISSUE 45 added."""

import json
import os

import costs
import costs_kda
import costs_ling
import costs_mla
import pytest
from readers import (
    gauge_share, gauge_stat, ling_decode_step_roofline, ling_view,
    moe_prefill_experts_roofline, timing_ratio_opt,
)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "ling-hybrid-bf16-ep4-7l.longdoc-saturated"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ["ling_kda_prefill_roofline", "ling_kda_decode_roofline",
       "ling_mla_prefill_attn_roofline", "ling_mla_decode_attn_roofline",
       "ling_decode_step_roofline", "ling_moe_group_held_share",
       "ling_latent_state_share"]


def spec(name):
    """(reader's name, its arguments) of a metric's data file."""
    with open(os.path.join(HERE, "metrics", name + ".json")) as f:
        doc = json.load(f)
    return doc["reader"], doc.get("args", {})


def read_view(name, ctx):
    """A `ling_*` kernel metric as run.py reads it: `ling_view` with the
    data file's arguments."""
    reader, args = spec(name)
    assert reader == "ling_view"
    return ling_view.read(ctx, **args)


@pytest.fixture(scope="module")
def ling():
    path = os.path.join(HERE, "configs", "ling-hybrid-bf16-ep4-7l.json")
    with open(path) as f:
        return json.load(f)


def test_the_cut_by_hand(ling):
    # K(dense) K K K K M K: the published rule (i + 1) % 6 == 0 -> MLA
    assert costs_ling.mla_layers(ling) == [5]
    assert costs_ling.kda_layers(ling) == [0, 1, 2, 3, 4, 6]
    assert costs_ling.moe_layers(ling) == 6
    # a KDA mixer: q, k, v, o and the two full-rank gates 6 x 10.49 M, the
    # step size 0.08 M, the taps 0.05 M
    kda = 6 * 2560 * 4096 + 2560 * 32 + 4 * 3 * 4096
    assert costs_ling.kda_params(ling) == kda == 63_045_632
    # an MLA mixer: W_q 2560 x 6144, W_kv_a 2560 x 576, W_kv_b 512 x 8192,
    # W_o 4096 x 2560, the gate 2560 x 32
    mla = (2560 * 6144 + 2560 * 576 + 512 * 8192 + 4096 * 2560 + 2560 * 32)
    assert costs_ling.mla_params(ling) == mla == 31_965_184
    assert costs_ling.expert_params(ling) == 3 * 2560 * 768 == 5_898_240
    assert costs_ling.shared_params(ling) == 5_898_240
    assert costs_ling.router_params(ling) == 2560 * 512  # published width
    always = (6 * kda + mla + 3 * 2560 * 6144
              + 6 * (2560 * 512 + 5_898_240) + 2560 * 39296)
    assert costs_ling.always_read_params(ling) == always
    # the cell's cut: 5.23 B parameters = 10.46 GB = 9.74 GiB in bf16
    total = always + 6 * 128 * 5_898_240 + 2560 * 39296
    assert costs_ling.total_params(ling) == total
    assert abs(total - 5.23e9) < 0.005e9
    assert abs(2 * total / 2**30 - 9.74) < 0.01
    # state: 34 slots x 6 layers x (2 MiB + 72 KiB) = 0.41 GiB
    kda_view = costs_ling.as_kda(ling)
    assert costs_kda.state_bytes_per_row_layer(kda_view) == 2 * 2**20
    assert costs_kda.tail_bytes_per_row_layer(kda_view) == 72 * 2**10
    assert costs_ling.state_pool_bytes(ling) == 34 * 6 * (
        2 * 2**20 + 72 * 2**10)
    assert abs(costs_ling.state_pool_bytes(ling) / 2**30 - 0.41) < 0.005
    # latents: 4,096 pages x 32 tokens x 1 layer x 1,280 B = 0.16 GiB (the
    # kernels' need counts the 576 values, 1,152 B)
    assert costs_ling.latent_stored_values(ling) == 640
    assert costs_ling.latent_pool_bytes(ling) == 4096 * 32 * 1280
    assert abs(costs_ling.latent_pool_bytes(ling) / 2**30 - 0.16) < 0.005
    assert costs_mla.latent_bytes_per_token_per_layer(ling) == 1152
    resident = (2 * total + costs_ling.state_pool_bytes(ling)
                + costs_ling.latent_pool_bytes(ling)) / 2**30
    assert abs(resident - 10.3) < 0.05
    # the views count THIS model's layers, where the files they lend from
    # would count 7 latent layers or read `gqa_layers`
    mla_view = costs_ling.as_mla(ling)
    assert costs_kda.kda_layers(kda_view) == [0, 1, 2, 3, 4, 6]
    assert costs_kda.kda_decode_bytes(kda_view, 1.0) == 6 * (
        2 * 2 * 2**20 + 4096 * (3 * 2 + 4 + 4) + 32 * 4)
    assert costs_mla.decode_attn_bytes(mla_view, 1000.0) == 1000 * 1152
    assert costs_mla.prefill_attn_flops(mla_view, 1.0) == (
        2.0 * 32 * (576 + 512))
    assert costs_kda.kda_prefill_flops(kda_view, 1.0) == 6 * 32 * 2.0 * (
        5 * 64 * 128 + 64 * 64 + 3 * 128 * 128)


def test_a_steps_bytes(ling):
    # 15.2 live rows of ~66k tokens, a fifth of the held experts touched
    live, rows, share = 66_000.0, 15.2, 0.2
    nbytes = costs_ling.decode_step_bytes(ling, live, rows, share, "bfloat16")
    by_hand = (
        (costs_ling.always_read_params(ling)
         + share * 6 * 128 * 5_898_240) * 2
        + live * 1152
        + rows * 6 * 2 * (2 * 2**20 + 72 * 2**10))
    assert nbytes == pytest.approx(by_hand)
    # the weights dominate: 0.60 B always + 0.91 B touched of 5.23 B
    # parameters (3.0 GB), then the state in and out (0.40 GB), then the
    # latents (0.08 GB)
    assert 3.4e9 < nbytes < 3.6e9
    flops = costs_ling.decode_step_flops(ling, 16, 2.0, live, rows)
    assert flops == pytest.approx(
        2.0 * (costs_ling.always_read_params(ling)
               + 6 * 2.0 * 5_898_240) * 16
        + 2.0 * 32 * (576 + 512) * live + rows * 6 * 32 * 7.0 * 128 * 128)


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
        "attn_prefill_pairs_n": 0, "moe_prefill_touched_n": 0,
        "moe_prefill_held_pairs_n": 0}


def test_readers_on_hand_made_documents(ling):
    steps, live_rows = 40, 15
    ctx = _ctx(
        ling,
        ops=[["kda_prefill_attention", 0.3], ["kda_decode_attention", 0.04],
             ["mla_prefill_attention", 0.1], ["mla_decode_attention", 0.01],
             ["moe_prefill_experts", 0.2]],
        modules=[["jit_decode_window_fn", 0.4]],
        counts={"kda_decode_attention": steps * 6,
                "mla_decode_attention": steps},
        timing0=ZERO,
        timing1={"moe_decode_touched_n": 26 * 6 * steps,
                 "moe_decode_slots_n": 128 * 6 * steps,
                 "moe_decode_held_pairs_n": 32 * 6 * steps,
                 "kda_prefill_tokens_n": 20_000,
                 "kda_prefill_segments_n": 12,
                 "kda_decode_rows_n": live_rows * steps,
                 "attn_prefill_pairs_n": 150_000_000,
                 "moe_prefill_touched_n": 6 * 128 * 10,
                 "moe_prefill_held_pairs_n": 6 * 4096 * 10},
    )
    kda_view = costs_ling.as_kda(ling)
    least, _ = costs.roofline_seconds(
        costs_kda.kda_prefill_flops(kda_view, 20_000),
        costs_kda.kda_prefill_bytes(kda_view, 20_000, 12), PEAK)
    assert read_view("ling_kda_prefill_roofline", ctx) == pytest.approx(
        100 * least / 0.3)
    # the kernel ran the bucket's 16 rows a step, live or padding
    assert read_view("ling_kda_decode_roofline", ctx) == pytest.approx(
        100 * costs_kda.kda_decode_bytes(kda_view, 16 * steps) / 819e9
        / 0.04)
    # ONE latent layer's pairs, not seven
    assert read_view("ling_mla_prefill_attn_roofline", ctx) == pytest.approx(
        100 * 150e6 * 2 * 32 * (576 + 512) / 197e12 / 0.1)
    # 15 live rows of 4,000 tokens, ONE layer's latents a step: the 1,152
    # bytes a token (84 us) outweigh its 2 x 32 x 1,088 flops (21 us)
    assert read_view("ling_mla_decode_attn_roofline", ctx) == pytest.approx(
        100 * steps * (15 * 4000 * 1152 / 819e9) / 0.01)
    nbytes = costs_ling.decode_step_bytes(
        ling, 15 * 4000.0, live_rows, 26 / 128, "bfloat16")
    assert ling_decode_step_roofline.read(ctx) == pytest.approx(
        100 * (nbytes / 819e9) / (0.4 / steps))
    # an accepted reader on this configuration's keys: the touched experts'
    # bytes (one expert 3 x 2560 x 768 bf16) against the pairs' flops
    least, _ = costs.roofline_seconds(
        2.0 * 6 * 4096 * 10 * 5_898_240, 6 * 128 * 10 * 5_898_240 * 2, PEAK)
    assert moe_prefill_experts_roofline.read(ctx) == pytest.approx(
        100 * least / 0.2)


def test_the_counter_and_gauge_metrics(ling):
    def timing(**kw):
        return {"timing": {"engine": kw}}

    ctx = {"start": timing(moe_group_kept_n=100, moe_group_slots_n=400,
                           moe_decode_touched_n=0, moe_decode_slots_n=0,
                           moe_decode_held_pairs_n=0),
           "end": timing(moe_group_kept_n=1100, moe_group_slots_n=2400,
                         moe_decode_touched_n=260, moe_decode_slots_n=1280,
                         moe_decode_held_pairs_n=520)}
    reader, args = spec("ling_moe_group_held_share")
    assert reader == "timing_ratio_opt"
    assert timing_ratio_opt.read(ctx, **args) == pytest.approx(50.0)
    # the accepted counter metrics the cell joins read the same counters
    # this configuration serves (slots over HELD experts)
    _, args = spec("moe_touched_share")
    assert timing_ratio_opt.read(ctx, **args) == pytest.approx(
        100 * 260 / 1280)
    _, args = spec("moe_held_pairs_per_expert")
    assert timing_ratio_opt.read(ctx, **args) == pytest.approx(2.0)
    # a parent without the group counters leaves the metric out
    parent = {"start": timing(moe_decode_slots_n=0),
              "end": timing(moe_decode_slots_n=5)}
    _, args = spec("ling_moe_group_held_share")
    assert timing_ratio_opt.read(parent, **args) is None
    # latent bytes / (latent + state bytes), sample by sample
    reader, share_args = spec("ling_latent_state_share")
    assert reader == "gauge_share"
    samples = [
        {'tpu:hbm_latent_bytes{model="m"}': 1e8,
         'tpu:hbm_state_bytes{model="m"}': 3e8,
         'tpu:hbm_state_share{model="m"}': 0.75},
        {'tpu:hbm_latent_bytes{model="m"}': 0.0,
         'tpu:hbm_state_bytes{model="m"}': 0.0,
         'tpu:hbm_state_share{model="m"}': 0.0},  # (nothing held: left out)
        {'tpu:hbm_latent_bytes{model="m"}': 2e8,
         'tpu:hbm_state_bytes{model="m"}': 2e8,
         'tpu:hbm_state_share{model="m"}': 0.5}]
    assert gauge_share.read(
        {"samples": samples}, **share_args) == pytest.approx(
            100 * (0.25 + 0.5) / 2)
    # ... and `kda_state_share` reads the gauge this configuration serves
    _, args = spec("kda_state_share")
    assert gauge_stat.read({"samples": samples}, **args) == pytest.approx(
        100 * (0.75 + 0.0 + 0.5) / 3)
    # a parent that serves no such gauge: left out, not 0
    old = [{'tpu:hbm_state_bytes{model="m"}': 3e8}]
    assert gauge_share.read({"samples": old}, **share_args) is None
    assert gauge_share.read({"samples": []}, **share_args) is None


def test_readers_find_nothing_on_a_parent_or_another_model(ling):
    # a parent commit given the new files: no kernel events, no counters
    parent = _ctx(ling, ops=[["fusion", 1.0]],
                  modules=[["jit_decode_window_fn", 0.8]],
                  counts={"paged_decode_attention": 100},
                  timing0={}, timing1={})
    def all_read(ctx):
        return [read_view(name, ctx) for name in NEW[:4]] + [
            ling_decode_step_roofline.read(ctx)]

    assert all_read(parent) == 5 * [None]
    # another configuration (no layer_group_size key)
    for other in ("solar-open2-bf16-ep8-8l", "kanana-2-30b-a3b-bf16-8l"):
        with open(os.path.join(HERE, "configs", other + ".json")) as f:
            ctx = dict(parent, config=json.load(f))
        assert all_read(ctx) == 5 * [None]
    # no trace at all (a failed capture)
    assert all_read(dict(parent, trace=None)) == 5 * [None]


def test_the_manifest_names_what_was_added(ling):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"] if c["name"] == ling["name"])
    assert entry["file"].endswith("configs/ling-hybrid-bf16-ep4-7l.json")
    assert entry["source"] == ling["source"]
    assert sorted(entry["reduced"]) == sorted(ling["reduced"])
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        ling["name"], "longdoc-closed", 1)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_per_s"
        assert os.path.exists(
            os.path.join(HERE, "readers", spec(name)[0] + ".py"))
    # the accepted metrics whose readers count this configuration right
    # (the tests above): the cell is on each list, whatever cells follow it
    for name in ("moe_touched_share", "moe_held_pairs_per_expert",
                 "moe_prefill_experts_roofline", "kda_state_share"):
        assert CELL in by_name[name]["workloads"]
    # ... and not those that multiply by num_hidden_layers or read
    # `gqa_layers` / `linear_attn_config`
    for name in ("mla_prefill_attn_roofline", "mla_decode_attn_roofline",
                 "mla_decode_step_roofline", "kda_prefill_roofline",
                 "kda_decode_roofline", "kda_decode_step_roofline"):
        assert CELL not in by_name[name]["workloads"]
    # every width as published; the cut's keys beside their published values
    assert (ling["hidden_size"], ling["moe_intermediate_size"],
            ling["num_experts_per_tok"], ling["head_dim"],
            ling["kv_lora_rank"], ling["intermediate_size"]) == (
                2560, 768, 8, 128, 512, 6144)
    assert (ling["num_experts"], ling["num_experts_published"],
            ling["vocab_size_published"], ling["num_hidden_layers_published"],
            ling["first_k_dense_replace_published"]) == (
                128, 512, 157184, 42, 2)
    assert set(ling["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "vocab_size", "max_position_embeddings", "expert_swiglu_limit_list",
        "share_expert_swiglu_limit_list"}
    for key in ("assumed", "deployment", "not_run", "rehearse"):
        assert ling[key]
    assert os.path.exists(os.path.join(HERE, "reference", "ling.py"))
