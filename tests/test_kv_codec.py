"""At-rest KV quantization suite (docs/38-kv-quantization.md).

Covers the codec itself (int4+per-group-scales / fp8 round trips, error
bounds, ragged groups), the dtype-tagged wire framing in both parser
modes, the KVDtypeError degraded-miss guard, the mixed-precision-fleet
fingerprint refusal, the wire-vs-logical flow accounting, and the
hydration planner's wire-byte pricing crossover (the same scenario that
plans recompute at fp16 bytes plans load at int4 wire bytes).
"""

from __future__ import annotations

import sys

import numpy as np
import pytest

from vllm_production_stack_tpu.engine import kv_codec
from vllm_production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from vllm_production_stack_tpu.engine.hydration import plan_decisions
from vllm_production_stack_tpu.engine.kv_codec import (
    EncodedKVBlock,
    KVAtRestCodec,
    KVDtypeError,
    decode_block,
    decode_payload,
    logical_nbytes,
    logical_shape,
    np_dtype_from_name,
    wire_nbytes,
)
from vllm_production_stack_tpu.engine.kv_flow import KVFlowMeter
from vllm_production_stack_tpu.engine.kv_transfer import (
    FrameParser,
    encoded_frame,
    raw_frame,
)

pytestmark = pytest.mark.kvquant

BS = 8


def _block(seed=0, shape=(2, 4, 8, 16), dtype=np.float32):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape) * 3.0).astype(dtype)


# -- int4 codec: round trip + error bound ------------------------------------


@pytest.mark.parametrize("group", [1, 4, 8, 16, 32, 64, 128])
def test_int4_round_trip_error_bound(group):
    """Per-element error is bounded by scale/2 where scale = max|group|/7
    — the documented bound the decode must honor at EVERY group size."""
    arr = _block(group, dtype=np.float32)
    codec = KVAtRestCodec("int4", group)
    enc = codec.encode(arr)
    dec = decode_block(enc)
    assert dec.shape == arr.shape and dec.dtype == arr.dtype
    flat = arr.reshape(-1).astype(np.float64)
    err = np.abs(dec.reshape(-1).astype(np.float64) - flat)
    ngroups = -(-flat.size // group)
    padded = np.zeros(ngroups * group)
    padded[: flat.size] = flat
    scale = np.maximum(np.abs(padded.reshape(ngroups, group)).max(1), 1e-8) / 7
    bound = np.repeat(scale, group)[: flat.size] / 2
    # float16 scale storage adds ~2^-11 relative slack on top of the
    # analytic scale/2 quantization bound
    assert np.all(err <= bound * 1.01 + 1e-6)


@pytest.mark.parametrize("nelem", [1, 7, 31, 32, 33, 37, 100])
def test_int4_ragged_last_group(nelem):
    """Blocks whose element count is not a multiple of the group size
    (or odd, exercising the dead pack nibble) round-trip exactly in
    shape; the zero pad never leaks into decoded values."""
    arr = _block(nelem, shape=(1, 1, 1, nelem), dtype=np.float16)
    dec = decode_block(KVAtRestCodec("int4", 16).encode(arr))
    assert dec.shape == arr.shape and dec.dtype == arr.dtype
    assert np.abs(
        dec.astype(np.float64) - arr.astype(np.float64)
    ).max() <= np.abs(arr.astype(np.float64)).max() / 7


@pytest.mark.parametrize("dtype_name", ["float32", "float16", "bfloat16"])
def test_int4_pool_dtypes(dtype_name):
    dtype = np_dtype_from_name(dtype_name)
    arr = _block(3, dtype=dtype)
    enc = KVAtRestCodec("int4", 32).encode(arr)
    dec = decode_block(enc)
    assert dec.dtype == arr.dtype and dec.shape == arr.shape
    assert enc.dtype == dtype_name
    # better-than-fp16 wire cost: the acceptance bar is >= 3.5x against
    # a 2-byte pool element at the default group of 32
    if dtype.itemsize == 2:
        assert arr.nbytes / enc.nbytes >= 3.5


def test_int4_corrupt_payload_raises():
    enc = KVAtRestCodec("int4", 32).encode(_block())
    with pytest.raises(ValueError):
        decode_payload(
            "int4", enc.group, enc.dtype, enc.shape,
            enc.payload[: len(enc.payload) // 2], enc.scale_nbytes,
        )


def test_wire_ratio_analytics():
    """The analytic ratio the planner prices with must match the bytes
    the encoder actually produces."""
    for group in (8, 32, 128):
        codec = KVAtRestCodec("int4", group)
        arr = _block(group, shape=(4, 4, 8, group), dtype=np.float16)
        enc = codec.encode(arr)
        assert arr.nbytes / enc.nbytes == pytest.approx(
            codec.wire_ratio("float16"), rel=1e-6
        )
    assert KVAtRestCodec("int4", 32).wire_ratio("float16") >= 3.5
    assert KVAtRestCodec("fp8").wire_ratio("bfloat16") == 2.0
    assert KVAtRestCodec("none").wire_ratio("float32") == 1.0


# -- fp8 codec ---------------------------------------------------------------


def test_fp8_round_trip():
    arr = _block(9, dtype=np.float32)
    enc = KVAtRestCodec("fp8").encode(arr)
    assert enc.nbytes == arr.size  # 1 byte per element at rest
    dec = decode_block(enc)
    assert dec.dtype == arr.dtype and dec.shape == arr.shape
    # e4m3 relative error ~2^-3 worst case near the mantissa edge
    assert np.abs(dec - arr).max() <= np.abs(arr).max() * 0.07


def test_fp8_pool_passthrough_lossless():
    """An fp8 KV pool under the fp8 at-rest codec round-trips bit-exact
    (cast fp8 → fp8 is the identity)."""
    ml_dtypes = pytest.importorskip("ml_dtypes")
    arr = _block(2, dtype=np.float32).astype(ml_dtypes.float8_e4m3fn)
    dec = decode_block(KVAtRestCodec("fp8").encode(arr))
    assert dec.dtype == arr.dtype
    np.testing.assert_array_equal(dec, arr)


# -- framing: codec metadata through the shared wire format ------------------


def test_encoded_frame_parser_decodes_by_default():
    """Legacy consumers (disk load, PD stream, kvstore tests) see logical
    arrays from codec-tagged frames without opting in."""
    arr = _block(4, dtype=np.float16)
    enc = KVAtRestCodec("int4", 32).encode(arr)
    frames = FrameParser().feed(encoded_frame(77, enc))
    assert len(frames) == 1 and frames[0][0] == 77
    np.testing.assert_array_equal(frames[0][1], decode_block(enc))


def test_encoded_frame_deferred_decode_and_meta():
    """decode_codec=False hands back the wire form (dequant-on-adopt),
    and frame_meta carries (wire, logical) per frame for flow
    accounting."""
    arr = _block(5, dtype=np.float16)
    enc = KVAtRestCodec("int4", 32).encode(arr)
    parser = FrameParser(decode_codec=False)
    mixed = encoded_frame(1, enc) + encoded_frame(2, arr)  # plain 2nd
    out = parser.feed(mixed)
    assert isinstance(out[0][1], EncodedKVBlock)
    assert isinstance(out[1][1], np.ndarray)
    assert parser.frame_meta == [
        (enc.nbytes, arr.nbytes), (arr.nbytes, arr.nbytes),
    ]
    assert logical_shape(out[0][1]) == arr.shape
    assert wire_nbytes(out[0][1]) < logical_nbytes(out[0][1])


def test_unknown_codec_degrades_parser_to_miss():
    bad = raw_frame(9, b"\x00" * 8, "float16", [4], codec="zstd-lol",
                    group=0, scale_nbytes=0)
    parser = FrameParser()
    out = parser.feed_partial(bad)
    assert out == [] and isinstance(parser.error, KVDtypeError)
    assert parser.feed_partial(b"junk") == []  # dead parser stays dead


# -- satellite: KVDtypeError dtype guard -------------------------------------


def test_ml_dtypes_name_without_ml_dtypes(monkeypatch):
    """A frame tagged bfloat16 on a host where ml_dtypes is not
    importable must degrade to a clear KVDtypeError naming the dtype —
    not an unhandled TypeError on the step thread. Simulated: ml_dtypes
    registers its names with numpy on import (jax already imported it in
    this process), so the shim un-registers bfloat16 AND the sys.modules
    None entry makes `import ml_dtypes` raise — the state of a host that
    never had the package."""

    class _NumpyWithoutMlDtypes:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def dtype(name):
            if isinstance(name, str) and name == "bfloat16":
                raise TypeError(name)
            return np.dtype(name)

    monkeypatch.setattr(kv_codec, "np", _NumpyWithoutMlDtypes())
    monkeypatch.setitem(sys.modules, "ml_dtypes", None)  # import -> error
    with pytest.raises(KVDtypeError, match="bfloat16.*ml_dtypes"):
        np_dtype_from_name("bfloat16")
    # and through the parser it is the standard dead-parser degraded miss
    frame = raw_frame(3, b"\x00" * 8, "bfloat16", [4])
    parser = FrameParser()
    assert parser.feed_partial(frame) == []
    assert isinstance(parser.error, KVDtypeError)


def test_unknown_dtype_name_is_kv_dtype_error():
    with pytest.raises(KVDtypeError, match="not_a_dtype"):
        np_dtype_from_name("not_a_dtype")
    assert issubclass(KVDtypeError, ValueError)  # degrade handlers catch


# -- wire-vs-logical flow accounting -----------------------------------------


def test_flow_meter_logical_bytes_and_ratio():
    flow = KVFlowMeter()
    flow.record("remote", "in", 1000, 1, 0.01, logical_nbytes=3550)
    flow.record("remote", "in", 1000, 1, 0.01, logical_nbytes=3550)
    flow.record("disk", "out", 500, 1, 0.01)  # no codec: logical = wire
    snap = flow.snapshot()
    assert snap["bytes"]["remote/in"] == 2000
    assert snap["logical_bytes"]["remote/in"] == 7100
    assert snap["compression_ratio"]["remote/in"] == pytest.approx(3.55)
    assert snap["compression_ratio"]["disk/out"] == 1.0
    assert snap["compression_ratio"]["peer/in"] == 1.0  # no bytes yet


# -- tier round trips with the codec wired in --------------------------------


def test_disk_tier_stores_wire_bytes(tmp_path):
    from vllm_production_stack_tpu.engine.kv_disk_tier import DiskKVTier

    flow = KVFlowMeter()
    codec = KVAtRestCodec("int4", 32)
    tier = DiskKVTier(str(tmp_path), 1 << 20, fingerprint="fp",
                      flow=flow, codec=codec)
    arr = _block(11, shape=(4, 8, 16, 16), dtype=np.float16)
    tier.store(123, arr)
    loaded = tier.load(123)
    assert loaded.dtype == arr.dtype and loaded.shape == arr.shape
    assert np.abs(
        loaded.astype(np.float64) - arr.astype(np.float64)
    ).max() <= np.abs(arr).max() / 7
    snap = flow.snapshot()
    # the file on disk is wire-sized: ~3.5x smaller than logical
    assert snap["logical_bytes"]["disk/out"] / snap["bytes"]["disk/out"] > 3
    assert snap["compression_ratio"]["disk/in"] > 3


def test_host_ring_normalizes_insert_forms():
    """insert_resolved accepts either form and normalizes to the ring's
    configured one — encoded fetches insert into an encode_ring with no
    transcode, and decode when the ring is plain."""
    from vllm_production_stack_tpu.engine.kv_host_tier import HostKVTier

    codec = KVAtRestCodec("int4", 32)
    arr = _block(13, dtype=np.float16)
    enc = codec.encode(arr)

    uploads = {}
    plain = HostKVTier(4, None, lambda blk, a: uploads.__setitem__(blk, a),
                       codec=codec, encode_ring=False)
    plain.insert_resolved(1, enc)
    assert isinstance(plain._data[1], np.ndarray)

    ring = HostKVTier(4, None, lambda blk, a: uploads.__setitem__(blk, a),
                      codec=codec, encode_ring=True)
    ring.insert_resolved(1, arr)
    ring.insert_resolved(2, enc)
    assert isinstance(ring._data[1], EncodedKVBlock)
    assert ring._data[2] is enc  # no transcode
    assert ring.reload_into(2, 7) == "host"
    assert uploads[7].dtype == arr.dtype  # dequant at the device boundary
    np.testing.assert_array_equal(uploads[7], decode_block(enc))


# -- mixed-precision fleet: fingerprint refusal ------------------------------


def _engine(codec="none", group=32):
    from vllm_production_stack_tpu.engine.engine import LLMEngine

    return LLMEngine(EngineConfig(
        model=ModelConfig.tiny(),
        cache=CacheConfig(
            block_size=BS, num_blocks=16, num_host_blocks=4,
            kv_at_rest_codec=codec, kv_at_rest_group_size=group,
        ),
        scheduler=SchedulerConfig(
            max_num_seqs=2, max_num_batched_tokens=64,
            decode_buckets=(2,), prefill_buckets=(32, 64),
        ),
    ))


def test_mixed_fleet_fingerprints_never_cross_adopt():
    """Engines whose at-rest codecs differ must land in DISJOINT KV
    namespaces: fingerprints differ per codec spec (group size included),
    and the adopt path refuses a mismatched sender outright — the
    engine.py mixed-precision hazard."""
    eng_plain = _engine("none")
    eng_int4 = _engine("int4", 32)
    eng_int4b = _engine("int4", 64)
    eng_fp8 = _engine("fp8")
    try:
        fps = {
            e.model_fingerprint
            for e in (eng_plain, eng_int4, eng_int4b, eng_fp8)
        }
        assert len(fps) == 4  # group size is part of the spec
        blocks = np.zeros(
            (1, *eng_plain.scheduler.pool.expected_block_shape),
            dtype=np.float32,
        )
        with pytest.raises(ValueError, match="fingerprint mismatch"):
            eng_int4.kv_import([1], blocks, eng_plain.model_fingerprint)
    finally:
        for e in (eng_plain, eng_int4, eng_int4b, eng_fp8):
            e.runner.shutdown(wait=True)


def test_fingerprint_spec_unchanged_without_codec():
    """Default-config fingerprints must NOT change when the codec field
    exists but is off — existing disk caches and remote namespaces stay
    valid across the upgrade. The spec only joins when enabled."""
    assert KVAtRestCodec.from_config(CacheConfig()).enabled is False
    assert KVAtRestCodec("int4", 32).spec == "int4g32"
    assert KVAtRestCodec("int4", 64).spec == "int4g64"
    assert KVAtRestCodec("fp8").spec == "fp8"


def test_remote_store_namespaces_by_codec_fingerprint():
    """The kvstore serves bytes only under the exact fingerprint they
    were PUT with — two codec specs can never cross-serve."""
    from vllm_production_stack_tpu.kvstore.server import BlockStore

    store = BlockStore(1 << 20)
    store.put("fp-int4g32", "42", b"payload", {"shape": "4", "dtype": "f2"})
    assert store.contains("fp-int4g32", "42")
    assert not store.contains("fp-none", "42")
    assert store.get("fp-fp8", "42") is None


# -- hydration planner: wire-byte pricing shifts crossovers ------------------


def _signal(block_bytes, wire=None, bw=4e5):
    sig = {
        "fetch_bandwidth_bytes_per_s": {
            "host": 1e12, "disk": bw, "remote": bw, "peer": bw,
            "device": 0.0,
        },
        "fetch_bandwidth_measured": {
            "host": True, "disk": True, "remote": True, "peer": True,
            "device": False,
        },
        "prefill_flops_per_s": 1e6,
        "peak_flops_per_s": 0.0,
        "flops_per_token": 100.0,
        "attn_flops_per_token_ctx": 0.0,
        "block_bytes": block_bytes,
        "block_size_tokens": BS,
    }
    if wire is not None:
        sig["wire_block_bytes"] = wire
    return sig


def test_decision_grid_int4_wire_bytes_flip_recompute_to_load():
    """THE acceptance-criterion crossover: a remote-resident run whose
    fp16-byte fetch loses to recompute flips to load when the planner
    prices the same link at int4 wire bytes (~3.55x fewer)."""
    chunks = [["remote"] * 2 for _ in range(6)]
    logical = 1000.0
    bw = 1.5e5
    codec = KVAtRestCodec("int4", 32)
    wire = {"remote": codec.wire_block_bytes(1000, "float16")}
    # per chunk: compute = 16 tok * 100 F / 1e6 F/s = 1.6 ms (9.6 ms
    # total); fetch@logical = 2 * 1000 B / 1.5e5 B/s = 13.3 ms — even ONE
    # overlapped load exceeds the whole recompute budget, so fp16 bytes
    # plan pure recompute
    dec_fp16, _ = plan_decisions(chunks, _signal(logical, bw=bw))
    assert dec_fp16 == ["recompute"] * 6
    # fetch@wire = 2 * ~282 B / 1.5e5 B/s = 3.8 ms — the load tail now
    # beats its recompute makespan and the plan flips
    dec_int4, est = plan_decisions(chunks, _signal(logical, wire, bw=bw))
    assert "load" in dec_int4
    assert est["split"] < 6
    # full decision grid across the ratio: the flip is monotone in the
    # wire ratio, never oscillating
    prev_loads = -1
    for ratio in (1.0, 1.5, 2.0, 3.0, 3.55, 5.0):
        d, _ = plan_decisions(
            chunks, _signal(logical, {"remote": logical / ratio}, bw=bw)
        )
        loads = d.count("load")
        assert loads >= prev_loads
        prev_loads = loads
    assert prev_loads >= 2  # deepest ratio loads a real tail


def test_wire_bytes_default_to_logical_per_tier():
    """Tiers absent from wire_block_bytes price at block_bytes — a
    partially-populated map (or none at all) degrades to the legacy
    behavior rather than mispricing."""
    chunks = [["disk"] * 2 for _ in range(4)]
    base, _ = plan_decisions(chunks, _signal(1000.0))
    with_empty, _ = plan_decisions(chunks, _signal(1000.0, {}))
    with_other, _ = plan_decisions(
        chunks, _signal(1000.0, {"remote": 100.0})
    )
    assert base == with_empty == with_other


def test_engine_signal_carries_wire_block_bytes():
    eng = _engine("int4", 32)
    try:
        sig = eng.hydration_signal()
        wire = sig["wire_block_bytes"]
        ratio = eng.kv_codec.wire_ratio(
            eng.config.cache.resolved_kv_dtype(eng.config.model.dtype)
        )
        assert wire["remote"] == pytest.approx(
            sig["block_bytes"] / ratio, rel=0.01
        )
        assert wire["disk"] == wire["peer"] == wire["remote"]
        # host ring NOT encoded by default: host prices logical
        assert wire["host"] == sig["block_bytes"]
        # migrate pricing reports wire bytes too
        assert eng.kv_bytes_per_token() == pytest.approx(
            (sig["block_bytes"] / BS) / ratio, rel=0.01
        )
    finally:
        eng.runner.shutdown(wait=True)
