import json
import os

import pytest

import costs
import stats
import trace_reduce

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_percentile_refuses_a_thin_tail():
    vals = [float(i) for i in range(1, 101)]
    assert stats.percentile(vals, 90) == 90.0
    assert stats.percentile(vals, 50) == 50.0
    with pytest.raises(stats.TooFewSamples):
        stats.percentile(vals[:99], 90)       # 9 beyond
    with pytest.raises(stats.TooFewSamples):
        stats.percentile(vals[:19], 50)       # 9 beyond
    with pytest.raises(stats.TooFewSamples):
        stats.percentile([], 50)
    assert stats.percentile(vals[:50], 90, min_beyond=0) == 45.0


def test_a_failed_request_misses_the_percentile():
    vals = [1.0] * 100 + [float("inf")] * 12
    assert stats.percentile(vals, 90) == float("inf")
    assert stats.percentile(vals, 50) == 1.0


def test_spread_is_the_contracts():
    vals = [100.0, 101.0, 99.0, 100.5, 99.5, 100.0]
    import statistics
    q = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == (q[2] - q[0]) / statistics.median(vals)


@pytest.mark.parametrize("name,kib,tp", [
    ("qwen3-8b-int8", 144, 1), ("qwen2-7b-int8", 56, 1),
    ("qwen3-8b-int8", 36, 4),  # per device, were its KV heads sharded 4 ways
])
def test_kv_bytes_per_token(name, kib, tp):
    cfg = config(name)
    assert costs.kv_bytes_per_token(cfg, cfg["kv_dtype"], tp) == kib * 1024


def test_weight_bytes_at_the_two_sizes():
    q3, q2 = config("qwen3-8b-int8"), config("qwen2-7b-int8")
    # Qwen3-8B: 36 x (4096x4096x2 + 4096x1024x2 + 3x4096x12288) + head
    assert costs.matmul_params(q3) == 36 * (
        2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 12288
    ) + 4096 * 151936
    assert 7.5e9 < costs.weight_bytes(q3, "int8") < 7.7e9
    assert 7.0e9 < costs.weight_bytes(q2, "int8") < 7.2e9
    assert costs.weight_bytes(q3, "bfloat16", tp=4) == \
        costs.matmul_params(q3) * 2 // 4


def test_decode_bytes_and_roofline():
    q3 = config("qwen3-8b-int8")
    live = 16000
    assert costs.decode_attn_bytes(q3, live) == live * 144 * 1024
    step = costs.decode_step_bytes(q3, live, "int8")
    assert step == costs.weight_bytes(q3, "int8") + live * 144 * 1024
    with open(os.path.join(HERE, "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    least, bound = costs.roofline_seconds(
        costs.decode_step_flops(q3, 32, live), step, peak)
    assert bound == "memory" and 0.011 < least < 0.013
    # a 512-row pass over the same weights is bound by compute
    _, bound = costs.roofline_seconds(
        costs.decode_step_flops(q3, 2048, live), step, peak)
    assert bound == "compute"


def test_trace_reduce_on_the_recorded_fixture():
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "tests", "fixtures",
                           "small.xplane.txt")) as f:
        data = ProfileData.from_text_proto(f.read())
    r = trace_reduce.reduce_events(*trace_reduce.planes_of(data))
    assert r["chips"] == 1  # the host plane is not a device
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.008)
    assert dict(r["device_ops"]) == pytest.approx(
        {"paged_decode_attention": 0.004, "fusion": 0.002, "copy": 0.002})
    assert r["op_counts"]["paged_decode_attention"] == 2
    assert dict(r["modules"]) == pytest.approx(
        {"jit_decode_window_fn": 0.006, "jit_step_fn": 0.002})
    assert r["gaps_s"] == pytest.approx([0.002])


def test_union_gaps_and_two_chips():
    assert trace_reduce.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace_reduce.gaps([(1, 2), (4, 5)], 0, 6) == [(2, 4), (0, 1), (5, 6)]
    r = trace_reduce.reduce_events({
        "/device:TPU:0": [("fusion.1", 0.0, 1.0), ("all-reduce.2", 1.0, 1.0)],
        "/device:TPU:1": [("fusion.1", 0.0, 1.0), ("all-reduce.2", 1.5, 0.5)],
    })
    assert r["chips"] == 2 and r["window_s"] == 2.0
    assert r["busy_s"] == pytest.approx(1.75)  # averaged over the chips
    assert dict(r["device_ops"])["all-reduce"] == pytest.approx(0.75)
    with pytest.raises(ValueError):
        trace_reduce.reduce_events({"/device:TPU:0": []})
    assert trace_reduce.op_family("%fusion.12.3") == "fusion"
    assert trace_reduce.module_family("jit_step_fn(99)") == "jit_step_fn"


def test_tokens_are_spread_over_the_time_since_the_last_chunk():
    from client import Record
    r = Record(rid=0, kind="chat", prompt_tokens=10, max_tokens=17)
    r.chunk_times, r.done = [1.0, 2.0, 3.0], 3.0   # 1 + 8 + 8 tokens
    assert r.chunk_tokens() == [1.0, 8.0, 8.0]
    assert r.tokens_between(0.0, 10.0) == 17.0
    assert r.tokens_between(1.5, 2.5) == 8.0        # half of each window
    assert r.tokens_between(0.0, 1.0) == 0.0        # [t0, t1): 1.0 is outside
    assert r.tokens_between(1.0, 1.5) == 1.0 + 4.0
    # the two halves of any split add up to the whole
    assert r.tokens_between(0.0, 2.2) + r.tokens_between(2.2, 9.0) == \
        pytest.approx(17.0)
