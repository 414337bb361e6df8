"""The parts of a model whose delta-rule layers have ONE gate a head and a
key width that is not the value width (Olmo-Hybrid, ISSUE 58), each alone on
the CPU: the two Gated DeltaNet kernels in interpret mode against
`kda_step`-style token-by-token loops (unequal widths that fill no lane
tile, seams, strong decay, beta near 2), the per-head path against the
accepted per-channel kernels given a broadcast gate, the geometry and the
memory arithmetic against the arrays' bytes, the post-norm block and the flat
QK-norm in the by-kind tree, every refusal by name, the published keys, the
names in the contract. The served engine against the reference is
tests/test_olmo_hybrid.py's."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import engine_kit as ek
from vllm_production_stack_tpu import metrics_contract as mc
from vllm_production_stack_tpu.engine import memory
from vllm_production_stack_tpu.engine.config import (
    CacheConfig, LoRAConfig, ParallelConfig,
)
from vllm_production_stack_tpu.models import llama, registry
from vllm_production_stack_tpu.models.registry import resolve_model_config
from vllm_production_stack_tpu.ops import kda_attention as kda

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK = dict(chunk=64)


@pytest.fixture(scope="module")
def cfg():
    return resolve_model_config("tiny-olmo-hybrid")


# -- the kernels -----------------------------------------------------------------

def _inputs(heads, dk, dv, lens, n, strong=False, seed=0):
    """A packed chunk of rows `lens` on an axis of `n` positions: q, k
    L2-normalised (q also x dk^-0.5) as the layer feeds them; `strong`: a
    tenth of the gates at -128 a token and beta pushed towards 0 and 2."""
    rng = np.random.RandomState(seed)
    f32 = np.float32
    q, k = (rng.randn(n, heads, dk).astype(f32) for _ in range(2))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.randn(n, heads, dv).astype(f32)
    g = -np.exp(rng.uniform(np.log(1e-3), np.log(3.0), (n, heads))).astype(f32)
    logits = rng.randn(n, heads) * (4 if strong else 1)
    if strong:
        g[rng.rand(n, heads) < 0.1] = -128.0
    beta = (2 / (1 + np.exp(-logits))).astype(f32)
    b = len(lens)
    pool = rng.randn(2 * (b + 1), heads, dk, dv).astype(f32)
    lens = np.asarray(lens, np.int32)
    row_off = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
    read = np.arange(1, b + 1, dtype=np.int32)
    return dict(pool=pool, read=read, write=read + b + 1,
                fresh=np.arange(b) % 2 == 0, q=q, k=k, v=v, g=g, beta=beta,
                row_off=row_off, lens=lens)


def _token_loop(x):
    """The recurrence one token at a time (`kda_step`), row by row."""
    pool = x["pool"].copy()
    o = np.zeros(x["v"].shape, np.float32)
    for r, (off, n) in enumerate(zip(x["row_off"], x["lens"])):
        s = (jnp.zeros_like(pool[0]) if x["fresh"][r]
             else jnp.asarray(pool[x["read"][r]]))
        for t in range(off, off + n):
            s, o[t] = kda.kda_step(s, *(x[key][t] for key in "qkv"),
                                   x["g"][t], x["beta"][t])
        pool[x["write"][r]] = np.asarray(s)
    return o, pool


def _through_kernel(x):
    o, pool = kda.gdn_prefill_attention(
        *(jnp.asarray(x[key]) for key in (
            "pool", "read", "write", "fresh", "q", "k", "v", "g", "beta",
            "row_off", "lens")), interpret=True)
    return np.asarray(o), np.asarray(pool)


@pytest.mark.parametrize("heads,dk,dv,lens,n,strong", [
    (3, 12, 24, (70, 5, 100), 192, False),  # seams inside blocks, a tail
    (6, 96, 192, (130, 62), 192, True),  # the published widths, 2 x 3 heads
    (5, 24, 40, (64, 64), 128, True),  # a head count only 1 and 5 divide
])
def test_chunked_kernel_against_the_recurrence(heads, dk, dv, lens, n,
                                               strong):
    """`gdn_prefill_attention` against the token-by-token loop: a state of
    dk x dv with dk != dv and neither a whole number of lanes, rows that
    start from zero or from their slot, gates of -128 a token (exp(-G) would
    overflow: every exponent is a sum over its own range) and beta near 2."""
    x = _inputs(heads, dk, dv, lens, n, strong)
    want_o, want_pool = _token_loop(x)
    got_o, got_pool = _through_kernel(x)
    live = int(np.sum(lens))
    np.testing.assert_allclose(got_o[:live], want_o[:live], atol=2e-6)
    np.testing.assert_allclose(got_pool, want_pool, atol=4e-6)
    # slots the chunk does not name keep their bytes
    untouched = sorted(set(range(len(x["pool"]))) - set(x["write"]))
    np.testing.assert_array_equal(got_pool[untouched], x["pool"][untouched])


@pytest.mark.parametrize("heads,dk,dv", [(6, 96, 192), (8, 12, 24)])
def test_decode_kernel_against_one_recurrence_step(heads, dk, dv):
    x = _inputs(heads, dk, dv, (1, 1, 1), 64, strong=True, seed=5)
    args = [jnp.asarray(x[key]) for key in ("pool", "read", "write")] + [
        jnp.asarray(x[key][:3]) for key in ("q", "k", "v", "g", "beta")]
    got_o, got_pool = kda.gdn_decode_attention(*args, interpret=True)
    want_o, want_pool = kda.kda_decode_xla(*args)
    np.testing.assert_allclose(got_o, want_o, atol=1e-6)
    np.testing.assert_allclose(got_pool, want_pool, atol=2e-6)


def test_the_per_head_path_is_the_per_channel_path_under_a_broadcast_gate():
    """Ties the generalisation to the accepted kernels: with a square state
    the KDA kernels, fed the gate broadcast over a head's key channels (an
    ORACLE — the served path never builds that array), give what the
    per-head kernels give from the (N, H) gate."""
    x = _inputs(4, 16, 16, (70, 58), 128, seed=7)
    args = {key: jnp.asarray(val) for key, val in x.items()}
    wide = jnp.broadcast_to(args["g"][..., None], args["q"].shape)
    o_head, pool_head = _through_kernel(x)
    o_chan, pool_chan = kda.kda_prefill_attention(
        args["pool"], args["read"], args["write"], args["fresh"], args["q"],
        args["k"], args["v"], wide, args["beta"], args["row_off"],
        args["lens"], interpret=True)
    np.testing.assert_allclose(o_head, o_chan, atol=2e-6)
    np.testing.assert_allclose(pool_head, pool_chan, atol=4e-6)
    step = [args[key] for key in ("pool", "read", "write")] + [
        args[key][:2] for key in ("q", "k", "v")]
    o_h, p_h = kda.gdn_decode_attention(
        *step, args["g"][:2], args["beta"][:2], interpret=True)
    o_c, p_c = kda.kda_decode_attention(
        *step, wide[:2], args["beta"][:2], interpret=True)
    np.testing.assert_allclose(o_h, o_c, atol=1e-6)
    np.testing.assert_allclose(p_h, p_c, atol=2e-6)
    # ... and `kda_step` itself takes either gate
    s0 = args["pool"][1]
    one = [args[key][0] for key in ("q", "k", "v")]
    for a, b in zip(kda.kda_step(s0, *one, args["g"][0], args["beta"][0]),
                    kda.kda_step(s0, *one, wide[0], args["beta"][0])):
        np.testing.assert_array_equal(a, b)


def test_no_gate_a_key_channel_is_built_for_a_gate_a_head(cfg):
    """The served chunk program of a per-head-gate model holds no (N, H, Dk)
    float32 array in front of its recurrence kernel: the kernel's gate
    operand is (N, H), as beta's."""
    cut = ek.cut(cfg, 4)  # one period: L L L F
    n, (lh, dk, dv) = 64, cut.state_geometry("delta")[0]
    params = llama.init_params(cut, jax.random.PRNGKey(0))
    ap = jax.tree.map(lambda a: a[0], params["layers"]["attn_linear"])
    st = dict(read=jnp.ones((1,), jnp.int32), write=jnp.ones((1,), jnp.int32),
              tap=None, fresh=jnp.ones((1,), bool), interpret=True,
              row_off=jnp.zeros((1,), jnp.int32),
              chunk_lens=jnp.full((1,), n, jnp.int32),
              tok_row=jnp.zeros((n,), jnp.int32))
    pools = llama._state_pools(llama.init_kv_cache(cut, 8, 8, state_slots=2)[0])

    def mixer(ap, x):
        recur = llama._PackedRecurrence(pools, st, {"to_rows": None})
        return llama._kda_attention(
            cut, ap, None, x, recur, jnp.ones((cut.hidden_size,)))

    text = str(jax.make_jaxpr(mixer)(
        ap, jnp.zeros((n, 1, cut.hidden_size), jnp.float32)))
    assert "gdn_prefill_attention" in text
    assert "kda_prefill_attention" not in text
    # the gate as the model makes it, a head each ...
    assert f"f32[{n},{lh}] = mul" in text
    # ... and no array of a gate a key channel, by broadcast or otherwise,
    # on the way to the kernel (q and k are the only (N, H, Dk) arrays)
    assert f"f32[{n},{lh},{dk}] = broadcast_in_dim" not in text
    assert f"f32[{n},{lh},{dk}] = exp" not in text


# -- geometry and bytes ----------------------------------------------------------

def test_the_geometry_is_one_place(cfg):
    assert cfg.state_geometry("delta") == ((6, 12, 24), 6 * (12 + 12 + 24))
    assert (cfg.linear_head_dim, cfg.linear_value_dim) == (12, 24)
    assert (cfg.linear_gate_per, cfg.linear_output_gate) == ("head", "silu")
    caches = llama.init_kv_cache(cfg, 16, 8, state_slots=3)
    for i, leaf in enumerate(caches):
        if i in cfg.linear_layers:
            assert leaf["s"].shape == (6, 6, 12, 24)  # 2 banks x 3 slots
            assert leaf["s"].dtype == jnp.float32
            assert leaf["conv"].shape == (6, 3, 288)
        else:
            # a page holds whole tiles of kv heads: 8 for the model's 6
            assert leaf.shape == (2, 16, 8, 8, 16)
    # Solar's and Ling's presets say their two widths, and they are equal
    for name in ("solar-open2-250b-ep8-8l", "ling-3.0-flash-ep4-7l"):
        other = resolve_model_config(name)
        assert (other.linear_head_dim, other.linear_value_head_dim) == (
            128, 128)
        heads = other.linear_num_heads
        assert other.state_geometry("delta") == (
            (heads, 128, 128), 3 * heads * 128)
        assert (other.linear_gate_per, other.linear_output_gate) == (
            "channel", "sigmoid")


def test_param_and_cache_bytes_are_the_arrays(cfg):
    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    assert memory.param_bytes(cfg) == nbytes(
        llama.init_params(cfg, jax.random.PRNGKey(0)))
    econf = ek.engine_config(cfg, num_blocks=40, **CHUNK)
    slots = memory.resolve_state_slots(econf)
    assert slots == 5
    caches = llama.init_kv_cache(cfg, 40, 8, state_slots=slots)
    assert memory.kv_pool_bytes(
        cfg, econf.cache, econf.parallel, None, state_slots=slots) == nbytes(
            caches)
    assert memory.state_slot_bytes(cfg) == 6 * (
        6 * 12 * 24 * 4 + 3 * 288 * 4)
    # the published widths: 30 x 96 x 192 x 4 B of state and a 3-row tail
    # of 11,520 bf16 channels a layer and slot; 45 KiB of K and V a token
    # over the cut's 3 full layers, 48 KiB in pages that hold 32 kv heads
    # for the model's 30; 3.268 B parameters
    cut = resolve_model_config("olmo-hybrid-7b-12l")
    assert cut.state_geometry("delta") == ((30, 96, 192), 11520)
    assert memory.state_slot_bytes(cut) == 9 * (
        30 * 96 * 192 * 4 + 3 * 11520 * 2)
    assert (cut.num_kv_heads, cut.pool_kv_heads) == (30, 32)
    assert memory.kv_block_bytes(cut, 32) == 32 * 48 * 2**10
    shapes = jax.eval_shape(
        lambda k: llama.init_params(cut, k), jax.random.PRNGKey(0))
    assert memory.param_bytes(cut) == nbytes(shapes)
    assert abs(memory.param_bytes(cut) / 2 - 3.268e9) < 0.002e9
    full = resolve_model_config("olmo-hybrid-7b")
    # 8 periods of 3 x 215.6 M + 185.8 M and 770.7 M of embedding and head
    assert abs(memory.param_bytes(full) / 2 - 7.43e9) < 0.005e9


def test_the_post_norm_block_and_the_flat_norm_in_the_by_kind_tree(cfg):
    assert cfg.tree_by_kind and cfg.post_norms_only and cfg.qk_norm_flat
    layers = llama.init_params(cfg, jax.random.PRNGKey(0))["layers"]
    assert {"attn_out_norm", "ffw_out_norm"} <= set(layers)
    assert not {"input_norm", "post_attn_norm"} & set(layers)
    assert layers["attn"]["q_norm"].shape == (2, 6 * 16)
    assert layers["attn"]["k_norm"].shape == (2, 6 * 16)
    lin = layers["attn_linear"]
    assert lin["wq"].shape == lin["wk"].shape == (6, 96, 72)
    assert lin["wv"].shape == lin["wg"].shape == (6, 96, 144)
    assert lin["wf"].shape == (6, 96, 6) and lin["dt_bias"].shape == (6, 6)
    assert lin["conv"].shape == (6, 4, 288) and lin["o_norm"].shape == (6, 24)
    assert all(s.rope is None for s in cfg.layer_specs)


# -- refusals --------------------------------------------------------------------

@pytest.mark.parametrize("edit,message", [
    (dict(cache=CacheConfig(block_size=8, num_blocks=64, num_host_blocks=8,
                            enable_prefix_caching=False)),
     "the host-RAM tier"),
    (dict(kv_peer_fetch=True), "the peer-engine tier"),
    (dict(sched=dict(num_speculative_tokens=3)),
     "speculative decoding (--speculative-config ngram)"),
    (dict(parallel=ParallelConfig(tensor_parallel_size=2)), "a mesh of 2"),
    (dict(lora=LoRAConfig(max_loras=1)), "LoRA adapters"),
    (dict(model=dict(quantization="int8")), "--quantization int8"),
    (dict(model=dict(checkpoint="a directory")), "a checkpoint"),
])
def test_what_has_no_state_slot_is_refused_by_name(cfg, kit, edit, message,
                                                   tmp_path):
    edit = dict(edit)
    over = dict(edit.pop("model", {}))
    if "checkpoint" in over:  # (a weights-only directory: byte tokenizer)
        over["checkpoint"] = str(tmp_path)
    model = dataclasses.replace(cfg, **over)
    with pytest.raises(ValueError) as err:
        kit.fresh_engine(ek.engine_config(model, **CHUNK, **edit))
    assert message in str(err.value)
    assert "cannot be combined with layers that hold a recurrent state" in str(
        err.value)


def test_prefix_hits_and_embeddings_are_refused_and_said(cfg, kit, caplog):
    import logging

    cache = CacheConfig(block_size=8, num_blocks=64)
    runner_log = logging.getLogger(
        "vllm_production_stack_tpu.engine.model_runner")
    runner_log.addHandler(caplog.handler)  # (the package's do not propagate)
    try:
        with caplog.at_level(logging.INFO):
            engine = kit.fresh_engine(
                ek.engine_config(cfg, cache=cache, **CHUNK))
    finally:
        runner_log.removeHandler(caplog.handler)
    assert not engine.config.cache.enable_prefix_caching
    said = [r.getMessage() for r in caplog.records]
    assert any("WITHOUT prefix hits" in m for m in said)
    # the start-up line: the state's geometry, the gate, the bytes a slot
    assert any("(6, 12, 24)" in m and "gate a head" in m
               and str(memory.state_slot_bytes(cfg)) in m for m in said)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        llama.embed_encode(cfg, {}, jnp.zeros((1, 4), jnp.int32),
                           jnp.array([4]))


def _config_dir(tmp_path, **override):
    hf = {**registry._OLMO_HYBRID_7B, **override}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    return str(tmp_path)


def test_the_published_keys_map_to_the_preset(tmp_path):
    got = resolve_model_config(_config_dir(tmp_path))
    want = resolve_model_config("olmo-hybrid-7b")
    assert got.layers == want.layers and len(got.layers) == 32
    assert [s.mixer for s in got.layers[:4]] == 3 * ["linear"] + ["softmax"]
    for key in ("hidden_size", "intermediate_size", "num_heads",
                "num_kv_heads", "head_dim", "vocab_size", "rms_norm_eps",
                "linear_num_heads", "linear_head_dim",
                "linear_value_head_dim", "linear_gate_per",
                "linear_output_gate", "linear_gate_rank",
                "linear_beta_scale", "qk_norm_flat", "post_norms_only",
                "norm_scale_f32", "tie_word_embeddings", "architecture",
                "attention_bias"):
        assert getattr(got, key) == getattr(want, key), key
    assert (got.head_dim, got.num_kv_heads) == (128, 30)
    assert got.max_model_len == 65536 and want.max_model_len == 32768
    # the benchmark's configuration file states the same keys
    with open(os.path.join(
            REPO,
            "benchmarks/chip/configs/olmo-hybrid-7b-bf16-12l.json")) as f:
        bench = json.load(f)
    assert bench["reduced"] == [
        "num_hidden_layers", "layer_types", "max_position_embeddings"]
    for key, value in registry._OLMO_HYBRID_7B.items():
        if key not in bench["reduced"]:
            assert bench[key] == value, key
    assert bench["layer_types"] == list(
        registry._OLMO_HYBRID_7B["layer_types"][:12])
    assert resolve_model_config(bench["model"]).num_layers == bench[
        "num_hidden_layers"] == 12


@pytest.mark.parametrize("override,message", [
    ({"layer_types": ["sliding_attention"] * 32}, "layer_types other than"),
    ({"num_hidden_layers": 31}, "layer_types of another length"),
    ({"linear_num_value_heads": 60}, "linear_num_value_heads !="),
    ({"linear_conv_kernel_dim": 3}, "linear_conv_kernel_dim != 4"),
    ({"linear_allow_neg_eigval": False}, "linear_allow_neg_eigval false"),
    ({"rope_parameters": {"rope_theta": 500000.0}},
     "rope_parameters.rope_theta"),
    ({"rope_scaling": {"rope_type": "linear", "factor": 2.0}},
     "rope_scaling"),
    ({"attention_bias": True}, "attention_bias true"),
    ({"hidden_act": "gelu"}, "hidden_act != silu"),
])
def test_the_mapper_refuses_by_name(tmp_path, override, message):
    with pytest.raises(ValueError) as err:
        resolve_model_config(_config_dir(tmp_path, **override))
    assert message in str(err.value) and "not implemented" in str(err.value)


# -- names -----------------------------------------------------------------------

def test_timing_and_metrics_names_are_the_contract(cfg, kit):
    from vllm_production_stack_tpu.engine.metrics import EngineMetrics

    engine, before = kit.engine(ek.engine_config(cfg, **CHUNK))
    rid = engine.add_request(prompt_token_ids=ek.prompts(cfg, (70,))[0],
                             sampling=ek.greedy(200))
    for _ in range(6):
        engine.step()
    timing = ek.since(engine, before)
    assert timing["kda_prefill_tokens_n"] == 70
    assert timing["kda_prefill_segments_n"] == 2  # chunks of 64
    assert timing["state_slots_zeroed_n"] == 1
    assert timing["kda_decode_rows_n"] > 0
    assert not set(mc.DEBUG_TIMING_SSD_COUNTS) & set(timing)
    stats = engine.stats()
    assert stats.state_slots_in_use == 1
    assert stats.state_bytes == 2 * memory.state_slot_bytes(cfg)
    pages = len(engine.scheduler.running[0].block_table) * (
        memory.kv_block_bytes(cfg, 8))
    assert stats.state_share == pytest.approx(
        stats.state_bytes / (stats.state_bytes + pages))
    text = EngineMetrics("tiny-olmo-hybrid").render(stats).decode()
    for name in (mc.HBM_STATE_BYTES, mc.HBM_STATE_SLOTS_IN_USE,
                 mc.HBM_STATE_SHARE):
        assert f"\n{name}{{" in text
    ek.finish(engine, rid)
