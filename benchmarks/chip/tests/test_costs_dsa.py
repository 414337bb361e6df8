"""`costs_dsa.py` and `readers/dsa_roofline.py`: the least work of attention
over SELECTED keys (GLM-5.2's cut), what the reader makes of a capture's
scopes and the program's counters, what leaves a metric out, and where the
new cell stands in the manifest — among them which ACCEPTED readers count
this configuration right."""

import json
import os

import costs_dsa
import costs_gmm
import pytest
from readers import dsa_roofline, moe_prefill_experts_roofline

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "glm-5.2-bf16-ep16-7l.longdoc-saturated"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("dsa_index_prefill_roofline", "dsa_prefill_attn_roofline",
       "dsa_index_decode_roofline", "dsa_decode_attn_roofline",
       "dsa_decode_step_roofline", "dsa_selected_share",
       "dsa_index_cache_share")


def load(path):
    with open(path) as f:
        return json.load(f)


CFG = load(os.path.join(HERE, "configs", "glm-5.2-bf16-ep16-7l.json"))


def test_the_issues_arithmetic():
    assert costs_dsa.attn_params_per_layer(CFG) == 165_019_648
    assert costs_dsa.indexer_params(CFG) == 9_371_648
    assert costs_dsa.expert_params(CFG) == costs_gmm.expert_params(
        CFG) == 37_748_736
    assert (costs_dsa.full_layers(CFG), costs_dsa.moe_layers(CFG)) == (2, 6)
    assert costs_dsa.router_width(CFG) == 256 and CFG["n_routed_experts"] == 16
    # a dense full layer 400.9 M, six expert layers' always-read part
    # (attention + shared expert + router) and one more indexer, the head's
    # 19,360 rows: what a decode step reads whatever the routing
    assert costs_dsa.always_read_params(CFG) == (
        7 * 165_019_648 + 2 * 9_371_648 + 3 * 6144 * 12288
        + 6 * (37_748_736 + 6144 * 256) + 6144 * 19360)
    assert costs_dsa.latent_bytes(CFG) == 1152
    assert costs_dsa.index_key_bytes(CFG) == 256
    # a pair in the EXPANDED form; the absorbed form would count 139,264
    assert costs_dsa.attn_flops(CFG, 1) == 64 * 2 * (256 + 256) == 65_536
    assert costs_dsa.index_flops(CFG, 1) == 32 * 128 * 2 == 8192
    assert costs_dsa.prefill_index_bytes(CFG, 1000) == 1000 * 2 * 256


def test_a_decode_step_reads_a_fifth_of_what_every_latent_would():
    """16 rows at 8,000 tokens of context: 2,048 selected latents a row and
    layer against 8,000 visible, and the index keys of two layers."""
    rows, ctx_len = 16, 8000
    selected = rows * 7 * 2048
    pairs = rows * 2 * ctx_len
    sparse = (costs_dsa.decode_attn_bytes(CFG, selected)
              + costs_dsa.decode_index_bytes(CFG, pairs))
    dense = rows * 7 * ctx_len * 1152
    assert sparse == 16 * (7 * 2048 * 1152 + 2 * 8000 * 256)
    assert 0.3 < sparse / dense < 0.33
    one = costs_dsa.decode_steps_bytes(
        CFG, 1, 6 * 6.0, selected, pairs, "bfloat16")
    assert one == pytest.approx(
        2 * (costs_dsa.always_read_params(CFG) + 36 * 37_748_736) + sparse)


def capture(window_s=0.4, prefill_s=1.0):
    return {"chips": 1, "programs": {
        "prefill": {"n": 10.0, "device_s": prefill_s},
        "window": {"n": 5.0, "device_s": window_s}},
        "scopes": [
            ["prefill", "mixer", "dsa_index_score", 0.02, 100.0],
            ["prefill", "mixer", "dsa_select", 0.03, 100.0],
            ["prefill", "mixer", "dsa_attn", 0.5, 100.0],
            ["prefill", "mixer", "attn", 0.7, 1.0],
            ["window", "mixer", "dsa_index_score", 0.010, 80.0],
            ["window", "mixer", "dsa_select", 0.006, 80.0],
            ["window", "mixer", "dsa_gather", 0.02, 280.0],
            ["window", "mixer", "dsa_attn", 0.01, 280.0],
            ["window", "proj", "attn_qkv", 0.1, 10.0]],
        "kernels": []}


def counters(**more):
    return {"dsa_index_pairs_n": 0, "dsa_selected_keys_n": 0,
            "dsa_decode_index_pairs_n": 0, "dsa_decode_selected_keys_n": 0,
            "attn_prefill_keys_n": 0, "moe_decode_touched_n": 0,
            "moe_decode_held_pairs_n": 0, **more}


def ctx(after, cap=None, cfg=CFG):
    return {"config": cfg, "peaks": PEAK,
            "device": {"last_capture": capture() if cap is None else cap},
            "trace_span": {"timing0": {"engine": counters()},
                           "timing1": {"engine": counters(**after)}}}


def test_each_op_reads_its_scopes_and_its_counters():
    # ten chunks: 2,048 queries at 6,144 keys before them, two full layers
    pairs = 2 * 10 * (2048 * 6144 + 2048 * 2049 // 2)
    selected = 7 * 10 * 2048 * 2048
    # five windows of 8 steps x 16 rows at 8,000 keys
    step_pairs, step_selected = 2 * 40 * 16 * 8000, 7 * 40 * 16 * 2048
    c = ctx({"dsa_index_pairs_n": pairs + step_pairs,
             "dsa_selected_keys_n": selected + step_selected,
             "dsa_decode_index_pairs_n": step_pairs,
             "dsa_decode_selected_keys_n": step_selected,
             "attn_prefill_keys_n": 10 * 8192,
             "moe_decode_touched_n": 40 * 36,
             "moe_decode_held_pairs_n": 40 * 48})
    assert dsa_roofline.read(c, "index_prefill") == pytest.approx(
        100 * pairs * 8192 / 197e12 / 0.05)
    assert dsa_roofline.read(c, "prefill_attn") == pytest.approx(
        100 * selected * 65536 / 197e12 / 0.5)
    # a step's indexer and attention are bound by their bytes
    assert dsa_roofline.read(c, "index_decode") == pytest.approx(
        100 * step_pairs * 256 / 819e9 / 0.016)
    assert dsa_roofline.read(c, "decode_attn") == pytest.approx(
        100 * step_selected * 1152 / 819e9 / 0.03)
    whole = costs_dsa.decode_steps_bytes(
        CFG, 40, 40 * 36, step_selected, step_pairs, "bfloat16")
    assert dsa_roofline.read(c, "decode_step") == pytest.approx(
        100 * whole / 819e9 / 0.4)
    for op in dsa_roofline.SCOPES:
        assert 0 < dsa_roofline.read(c, op) < 100, op


FULL = {"dsa_index_pairs_n": 10**9, "dsa_selected_keys_n": 10**9,
        "dsa_decode_index_pairs_n": 10**8,
        "dsa_decode_selected_keys_n": 10**8,
        "attn_prefill_keys_n": 10**5, "moe_decode_touched_n": 100,
        "moe_decode_held_pairs_n": 100}


@pytest.mark.parametrize("op, moved", [
    ("index_prefill", ("dsa_index_score", "dsa_select")),
    ("prefill_attn", ("dsa_attn",)),
    ("index_decode", ("dsa_index_score",)),  # the top-k stays XLA's
    ("decode_attn", ("dsa_attn",)),  # ... and so does the gather
])
def test_an_ops_roofline_follows_it_into_a_kernel(op, moved):
    """The engine's summary books a custom call to `kernels` and never to
    `scopes`: the same capture with an op's seconds moved from its scope rows
    to a row of its kernel family reads the same share, the families are the
    names the docstring gives, and a kernel of another family, or of the same
    family in programs of the other kind, adds nothing."""
    kind = dsa_roofline.SCOPES[op][0]
    (family,) = dsa_roofline.KERNELS[op]
    assert family in dsa_roofline.__doc__
    was = capture()
    rows = [r for r in was["scopes"] if r[0] == kind and r[2] in moved]
    assert len(rows) == len(moved)
    now = {**was,
           "scopes": [r for r in was["scopes"] if r not in rows],
           "kernels": [[kind, family, sum(r[3] for r in rows), 7.0, "mixer"]]}
    share = dsa_roofline.read(ctx(FULL, was), op)
    assert dsa_roofline.read(ctx(FULL, now), op) == pytest.approx(share)
    other = "window" if kind == "prefill" else "prefill"
    stray = {**was, "kernels": [[kind, "mla_decode_attention", 0.3, 7.0,
                                 "mixer"], [other, family, 0.3, 7.0, "mixer"]]}
    assert dsa_roofline.read(ctx(FULL, stray), op) == pytest.approx(share)
    # the whole step's share is the program's seconds: it has both already
    assert dsa_roofline.read(ctx(FULL, now), "decode_step") == pytest.approx(
        dsa_roofline.read(ctx(FULL, was), "decode_step"))
    assert set(dsa_roofline.KERNELS) == set(dsa_roofline.SCOPES) - {
        "decode_step"}


@pytest.mark.parametrize("op", sorted(dsa_roofline.SCOPES))
def test_what_leaves_a_metric_out(op):
    assert dsa_roofline.read(ctx(FULL), op) is not None
    # a parent commit: no summary, or a failed reduction, or a CPU engine
    assert dsa_roofline.read({**ctx(FULL), "device": None}, op) is None
    assert dsa_roofline.read(
        ctx(FULL, {"error": "TimeoutError", "dir": "/x"}), op) is None
    assert dsa_roofline.read(ctx(FULL, {**capture(), "chips": 0}), op) is None
    # ... or a program without the counters
    bare = ctx(FULL)
    for doc in bare["trace_span"].values():
        for key in dsa_roofline.NEEDS:
            del doc["engine"][key]
    assert dsa_roofline.read(bare, op) is None
    assert dsa_roofline.read({**ctx(FULL), "trace_span": {}}, op) is None
    # another model's configuration
    other = load(os.path.join(
        HERE, "configs", "kanana-2-30b-a3b-bf16-8l.json"))
    assert dsa_roofline.read(ctx(FULL, cfg=other), op) is None
    # no program of the kind in the slice, or none of the scopes
    kind = dsa_roofline.SCOPES[op][0]
    none = capture()
    del none["programs"][kind]
    assert dsa_roofline.read(ctx(FULL, none), op) is None
    if dsa_roofline.SCOPES[op][1]:
        assert dsa_roofline.read(
            ctx(FULL, {**capture(), "scopes": []}), op) is None


def test_the_accepted_expert_readers_count_this_configuration_right():
    """`moe_prefill_experts_roofline` reads `hidden_size` x
    `moe_intermediate_size` and the program's own counters (touched HELD
    experts, pairs that landed on them): 16 held of 256 changes neither.
    `moe_touched_share`, `moe_held_pairs_per_expert` and `moe_read_share`
    are ratios of those counters, slots over held experts."""
    c = {"config": CFG, "peaks": PEAK,
         "trace": {"device_ops": [["moe_prefill_experts", 0.2]]},
         "trace_span": {
             "timing0": {"engine": {"moe_prefill_touched_n": 0,
                                    "moe_prefill_held_pairs_n": 0}},
             "timing1": {"engine": {"moe_prefill_touched_n": 6 * 16 * 10,
                                    "moe_prefill_held_pairs_n": 61440}}}}
    flops = 2 * 61440 * 37_748_736
    nbytes = 960 * 37_748_736 * 2
    assert moe_prefill_experts_roofline.read(c) == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / 0.2)


def test_the_manifest_entries():
    """Every entry is found by its NAME: the driver holds a later PR to the
    end of each list, so nothing here may hold "last" or a list's length."""
    manifest = load(os.path.join(REPO, "BENCHMARK.json"))
    assert CFG["name"] == "glm-5.2-bf16-ep16-7l"
    entry = next(c for c in manifest["configs"] if c["name"] == CFG["name"])
    assert entry["file"].endswith("configs/glm-5.2-bf16-ep16-7l.json")
    assert entry["source"] == CFG["source"]
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"])
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CFG["name"], "longdoc-closed", 1)
    names = [m["name"] for m in manifest["per_layer"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    # the seven metrics of PR 55, together and in this order, wherever the
    # list has grown to since
    at = names.index(NEW[0])
    assert tuple(names[at:at + len(NEW)]) == NEW
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_per_s"
        spec = load(os.path.join(HERE, "metrics", name + ".json"))
        assert os.path.exists(
            os.path.join(HERE, "readers", spec["reader"] + ".py"))
    # exactly one whole-step share (test_dense_step_list.py asks one a cell)
    assert [n for n in names if n.endswith("decode_step_roofline")
            and CELL in by_name[n]["workloads"]] == [
        "dsa_decode_step_roofline"]
    # the accepted metrics whose readers count this configuration right (the
    # test above): ratios of the program's counters over HELD experts, and
    # `hidden_size` x `moe_intermediate_size` a touched expert
    for name in ("moe_read_share", "moe_touched_share",
                 "moe_held_pairs_per_expert", "moe_prefill_experts_roofline"):
        assert CELL in by_name[name]["workloads"]
    # ... and not those whose counts are dense over the latents
    for name in ("mla_prefill_attn_roofline", "mla_decode_attn_roofline",
                 "mla_decode_step_roofline"):
        assert CELL not in by_name[name]["workloads"]
    # every width as published; the cut's keys beside their published values
    assert (CFG["hidden_size"], CFG["moe_intermediate_size"],
            CFG["num_experts_per_tok"], CFG["kv_lora_rank"],
            CFG["q_lora_rank"], CFG["index_topk"], CFG["index_n_heads"],
            CFG["index_head_dim"], CFG["intermediate_size"]) == (
                6144, 2048, 8, 512, 2048, 2048, 32, 128, 12288)
    assert (CFG["n_routed_experts_published"], CFG["vocab_size_published"],
            CFG["num_hidden_layers_published"],
            CFG["first_k_dense_replace_published"]) == (256, 154880, 78, 3)
    for key in ("assumed", "deployment", "not_run", "rehearse"):
        assert CFG[key]
    assert os.path.exists(os.path.join(HERE, "reference", "glm_dsa.py"))
