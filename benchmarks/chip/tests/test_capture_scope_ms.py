"""`readers/capture_scope_ms.py` and its thirteen data files on a hand-made
summary (`device.last_capture`, the engine's own reduction of the traced
slice's capture): a chunk's and a window's device time, XLA's seconds by
scope group with the kernels left out, the share no scope covers. A parent
of PR 52 serves no summary: every one of the thirteen is then left out, none
raises."""

import json
import os

import pytest
from readers import capture_scope_ms

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(HERE))

# 4 chunks of 2,048 tokens (0.4 s) and 10 windows (1.0 s)
CAPTURE = {
    "chips": 1, "window_s": 2.0, "busy_s": 1.5,
    "programs": {"prefill": {"n": 4.0, "device_s": 0.4},
                 "window": {"n": 10.0, "device_s": 1.0},
                 "glue_program": {"n": 30.0, "device_s": 0.003}},
    "scopes": [  # [kind, group, scope, seconds, ops]
        ["prefill", "proj", "attn_qkv", 0.05, 32.0],
        ["prefill", "proj", "kda_qkv", 0.03, 12.0],
        ["prefill", "glue", "kda_conv", 0.04, 12.0],
        ["prefill", "mlp", "mlp", 0.08, 32.0],
        ["prefill", "moe_route", "moe_dispatch", 0.02, 8.0],
        ["prefill", "ends", "lm_head", 0.01, 4.0],
        ["prefill", "other", "other", 0.01, 9.0],
        ["window", "proj", "attn_qkv", 0.2, 800.0],
        ["window", "mlp", "mlp", 0.5, 800.0],
        ["window", "ends", "sample", 0.05, 80.0],
        ["window", "other", "other", 0.04, 90.0],
        ["glue_program", "other", "other", 0.003, 30.0],
    ],
    "kernels": [  # [kind, family, seconds, n, group]
        ["prefill", "paged_prefill_attention", 0.12, 32.0, "mixer"],
        ["prefill", "moe_prefill_experts", 0.03, 8.0, "experts"],
        ["window", "paged_decode_attention", 0.2, 800.0, "mixer"],
        ["window", "stray_kernel", 0.007, 10.0, "other"],
    ],
}
WANT = {
    "chunk_device_ms_per_ktok": 0.4 / 8192 * 1e6,
    "window_device_ms": 100.0,
    "chunk_proj_ms_per_ktok": 0.08 / 8192 * 1e6,
    "chunk_mlp_ms_per_ktok": 0.08 / 8192 * 1e6,
    "chunk_ends_ms_per_ktok": 0.01 / 8192 * 1e6,
    "chunk_glue_ms_per_ktok": 0.04 / 8192 * 1e6,
    "chunk_moe_route_ms_per_ktok": 0.02 / 8192 * 1e6,
    "window_proj_ms": 20.0,
    "window_mlp_ms": 50.0,
    "window_ends_ms": 5.0,
    "window_glue_ms": 0.0,  # the windows ran, the group is empty
    "window_moe_route_ms": 0.0,
    # (0.01 + 0.04 + 0.003 + 0.007) of the 1.033 + 0.357 operation seconds
    "capture_unscoped_share": 100.0 * 0.06 / 1.39,
}


def spec_of(name):
    with open(os.path.join(HERE, "metrics", name + ".json")) as f:
        return json.load(f)


def ctx(capture=CAPTURE, tokens=8192):
    return {
        "device": {"platform": "tpu", **(
            {} if capture is None else {"last_capture": capture})},
        "trace_span": {
            "timing0": {"engine": {"prefill_tokens": 100_000}},
            "timing1": {"engine": {"prefill_tokens": 100_000 + tokens}}},
    }


def read(name, c):
    spec = spec_of(name)
    assert spec["reader"] == "capture_scope_ms"
    return capture_scope_ms.read(c, **spec.get("args", {}))


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_data_file_reads_its_number(name):
    assert read(name, ctx()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_parent_or_a_failed_reduction_leaves_the_metric_out(name):
    assert read(name, ctx(capture=None)) is None  # no summary served
    assert read(name, {"device": None, "trace_span": {}}) is None
    assert read(name, ctx({"error": "TimeoutError: ", "dir": "/x"})) is None
    # a CPU engine's capture: no device plane, no program
    assert read(name, ctx({**CAPTURE, "chips": 0, "programs": {},
                           "scopes": [], "kernels": []})) is None


def test_none_without_programs_of_the_kind_zero_for_an_empty_group():
    no_chunks = {**CAPTURE, "programs": {"window": CAPTURE["programs"]["window"]}}
    assert read("chunk_proj_ms_per_ktok", ctx(no_chunks)) is None
    assert read("chunk_device_ms_per_ktok", ctx(no_chunks)) is None
    assert read("window_proj_ms", ctx(no_chunks)) == pytest.approx(20.0)
    assert read("window_glue_ms", ctx()) == 0.0
    assert read("window_glue_ms", ctx()) is not None
    # chunks ran but the slice's counter did not move, or is not there
    assert read("chunk_device_ms_per_ktok", ctx(tokens=0)) is None
    assert read("chunk_mlp_ms_per_ktok",
                {**ctx(), "trace_span": {}}) is None
    assert read("window_device_ms", {**ctx(), "trace_span": {}}) == 100.0


def test_kernels_are_left_out_of_a_groups_sum_and_in_the_share():
    mixer = capture_scope_ms.read(ctx(), kind="prefill", groups=["mixer"])
    assert mixer == 0.0  # the group's only seconds are a kernel's
    assert capture_scope_ms.read(
        ctx(), kind="window", groups=["other"], per="op_seconds"
    ) == pytest.approx(100.0 * 0.047 / 0.997)
    with pytest.raises(ValueError):
        capture_scope_ms.read(ctx(), kind="window", per="fortnight")


def test_the_thirteen_are_in_the_manifest_with_cells_that_exist():
    """Found by NAME, together and in `WANT`'s order: what stands behind
    them, and how many cells a list has grown to, is a later PR's to add."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"] for w in manifest["workloads"]}
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(next(iter(WANT)))
    assert names[at:at + len(WANT)] == list(WANT) and len(WANT) == 13
    mine = manifest["per_layer"][at:at + len(WANT)]
    for m in mine:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
        assert m["source"] == "device_trace" and m["better"] == "lower"
        assert m["moves"] == "out_tok_per_s"
        assert m["unit"] == ("%" if m["name"].endswith("_share") else "ms")
    glue = {m["name"]: m["workloads"] for m in mine if "glue" in m["name"]}
    moe = {m["name"]: m["workloads"] for m in mine if "moe_route" in m["name"]}
    # PR 52's four and six cells; a cell with such scopes joins at the end
    assert len(glue) == 2 and all(len(w) >= 4 for w in glue.values())
    assert len(moe) == 2 and all(len(w) >= 6 for w in moe.values())
