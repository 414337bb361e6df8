"""Multi-head latent attention and DeepSeek-V3-style experts (Kanana-2,
ISSUE 31) at tiny size on the CPU: the served engine against the plain
float32 reference (testing/reference_mla.py), the latent kernels in
interpret mode against the XLA form, the routing rule, the byte counts, the
checkpoint names with the rope de-interleave, and the refusals."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

import engine_kit as ek
from vllm_production_stack_tpu.engine.config import (
    CacheConfig, ParallelConfig,
)
from vllm_production_stack_tpu.engine.memory import (
    kv_block_bytes, param_bytes,
)
from vllm_production_stack_tpu.engine.saturation import matmul_params
from vllm_production_stack_tpu.models import llama
from vllm_production_stack_tpu.models.loader import (
    DeepseekCheckpointView, load_checkpoint_params,
)
from vllm_production_stack_tpu.models.registry import resolve_model_config
from vllm_production_stack_tpu.ops.attention import causal_page_mask
from vllm_production_stack_tpu.ops.mla_attention import (
    latent_attention_with_staged, latent_attention_xla,
)
from vllm_production_stack_tpu.ops import mla_attention_pallas as mk
from vllm_production_stack_tpu.ops.mla_attention_pallas import (
    mla_decode_attention, mla_prefill_attention,
)
from vllm_production_stack_tpu.testing import reference_check
from vllm_production_stack_tpu.testing import reference_mla as reference

# float32 on both sides: what differs is the order of sums (absorbed against
# expanded attention, routed pairs against every expert) — 1e-6 measured,
# logits of size ~1
LOGPROB_ATOL = 2e-5


@pytest.fixture(scope="module")
def cfg():
    return resolve_model_config("tiny-kanana")


@pytest.fixture(scope="module")
def params(cfg):
    return llama.init_params(cfg, jax.random.PRNGKey(3))


# what this file's engines differ in from the kit's: prefix caching as the
# flag's default has it (the latent pool is content-addressed like any other)
POOL = dict(cache=CacheConfig(block_size=8, num_blocks=128))


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_engine_against_the_float32_reference(cfg, kit, backend):
    """Prefill in chunks (packed, several rows a dispatch), then fused decode
    windows through the latent pool, against the reference's full forward
    in its EXPANDED form on checkpoint-form weights: the absorption, the
    pool, the staged window and the rope de-interleave are all under test."""
    engine, before = kit.engine(ek.engine_config(cfg, backend, **POOL))
    # 70 and 50: three and two chunks
    prompts = ek.prompts(cfg, (70, 9, 33, 50))
    served = reference_check.served_logprobs(engine, prompts, 10)
    out = reference_check.compare(
        reference, cfg, engine.runner.params, prompts, served,
        routing_chunk=32)
    assert out["positions"] == 40 and out["ids_compared"] == 320
    assert out["max_abs_dlogprob"] <= LOGPROB_ATOL, out
    assert out["routing_disagreement_share"] == 0.0, out
    # counters: slots count the two EXPERT layers, not the dense one
    t = ek.since(engine, before)
    assert t["moe_prefill_slots_n"] == t["prefill_n"] * 2 * cfg.num_experts
    assert t["moe_decode_slots_n"] % (2 * cfg.num_experts) == 0
    assert 0 < t["moe_decode_touched_n"] <= t["moe_decode_slots_n"]
    # each chunk's queries x the keys each may see: position p sees p + 1
    assert t["attn_prefill_pairs_n"] == sum(
        n * (n + 1) // 2 for n in (70, 9, 33, 50))


@pytest.mark.parametrize("backend,rows,form", [
    ("xla", 2, "all_experts"),  # no Pallas kernel in an XLA engine
    ("pallas_interpret", 2, "touched_kernel"),  # 1 - (3/4)^2 = 0.44
    ("pallas_interpret", 8, "all_experts"),  # 1 - (3/4)^8 = 0.90
])
def test_read_counter_and_report_follow_the_program_form(
        cfg, kit, backend, rows, form):
    """`moe_decode_read_n` counts the experts whose bytes the decode
    programs' expert computation read — the touched ones from a program
    built with the kernel, every slot from one built with the einsum — and
    /debug/programs' `device.moe.decode.resolved` says which; the host
    knows the form from the program's shapes (ISSUE 36)."""
    engine, before = kit.engine(
        ek.engine_config(cfg, backend, seqs=rows, **POOL))
    ek.generate(engine, [[5, 6, 7, 8, 9], list(range(20, 50))], 9)
    moe = engine.runner.device_report()["moe"]
    assert moe["decode"] == {"resolved": form, "by_bucket": {str(rows): form}}
    t = ek.since(engine, before)
    assert 0 < t["moe_decode_touched_n"] < t["moe_decode_slots_n"]
    assert t["moe_decode_read_n"] == (
        t["moe_decode_touched_n"] if form == "touched_kernel"
        else t["moe_decode_slots_n"])
    # a 32-token chunk covers tiny-kanana's 8 experts: the einsum
    assert t["moe_prefill_read_n"] == t["moe_prefill_slots_n"]


@pytest.mark.parametrize("backend,form", [
    ("xla", "grouped"), ("pallas_interpret", "grouped_kernel")])
def test_a_long_chunk_through_the_grouped_forms_against_the_reference(
        cfg, kit, backend, form, monkeypatch):
    """Above the token limit (lowered to 8 tokens here: this engine's
    chunks are 16 and 32) a prefill chunk sorts its routed pairs and runs
    them through `ragged_dot`, or through the fused kernel where the
    program may hold one (interpret mode): both within the float32
    reference's tolerance, `device.moe.prefill` says which, and the read
    counter counts the touched experts either way (ISSUE 38)."""
    monkeypatch.setattr(llama, "_ALL_EXPERTS_MAX_TOKENS", 8)
    engine = kit.fresh_engine(ek.engine_config(
        cfg, backend, sched=dict(prefill_buckets=(16, 32)), **POOL))
    prompts = ek.prompts(cfg, (70, 9, 33), seed=2)
    served = reference_check.served_logprobs(engine, prompts, 6)
    out = reference_check.compare(
        reference, cfg, engine.runner.params, prompts, served,
        routing_chunk=32)
    assert out["max_abs_dlogprob"] <= LOGPROB_ATOL, out
    assert out["routing_disagreement_share"] == 0.0, out
    moe = engine.runner.device_report()["moe"]
    assert moe["prefill"] == {
        "resolved": form, "by_bucket": {"16": form, "32": form}}
    t = engine.flightrec.timing()
    assert 0 < t["moe_prefill_touched_n"] <= t["moe_prefill_slots_n"]
    assert t["moe_prefill_read_n"] == t["moe_prefill_touched_n"]
    assert t["moe_prefill_held_pairs_n"] == t["moe_prefill_pairs_n"]


def test_forward_in_one_chunk_equals_the_expanded_reference(cfg, params):
    """Absorbed == expanded: one layer stack, one pass, no chunking."""
    tokens = np.random.RandomState(0).randint(1, cfg.vocab_size, 40).tolist()
    view = DeepseekCheckpointView(cfg, jax.tree.map(np.asarray, params))
    want, routing = reference.forward(
        reference.published_keys(cfg), view, tokens)
    assert [r is None for r in routing] == [True, False, False]
    bs, t = 8, len(tokens)
    kv = llama.init_kv_cache(cfg, t // bs + 2, bs)
    table = jnp.arange(1, t // bs + 2, dtype=jnp.int32)[None]
    pos = jnp.arange(t, dtype=jnp.int32)
    h, _ = llama.forward(
        cfg, params, jnp.asarray([tokens], jnp.int32), pos[None], kv, table,
        table[0, pos // bs] * bs + pos % bs, jnp.asarray([t], jnp.int32))
    got = llama.compute_logits(cfg, params, h[0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def _latents(rng, *shape, width=128, dim=40):
    return jnp.asarray(rng.randn(*shape, width), jnp.float32).at[
        ..., dim:].set(0)


def test_latent_decode_kernel_against_xla():
    """Interpret mode: a padding row (no history), a one-page row, a row
    that straddles the 256-token block edge, a longer one."""
    rng = np.random.RandomState(0)
    nh, rank, bs, blocks, nb, w = 4, 32, 8, 80, 40, 4
    kv = _latents(rng, 1, blocks, bs)
    q, staged = _latents(rng, 4, nh), _latents(rng, 4, w)
    tables = jnp.asarray(rng.randint(1, blocks, (4, nb)), jnp.int32)
    hist = jnp.asarray([0, 5, 256 + 17, 300], jnp.int32)
    for step in (0, 2):
        got = mla_decode_attention(
            q, kv, tables, hist, staged, jnp.int32(step), scale=0.2,
            rank=rank, interpret=True)
        want = latent_attention_with_staged(
            q[:, None], kv, tables, jnp.arange(nb * bs)[None] < hist[:, None],
            jnp.moveaxis(staged, 0, 1), jnp.arange(w) <= step, scale=0.2,
            rank=rank)[:, 0]
        assert got.shape == (4, nh, rank)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-6)


# name: (heads, rank, width, page tokens, pool pages, table pages, chunk
# bucket, dtypes (queries, pool), per row (resident tokens, chunk tokens),
# tolerance)
_F32, _BF16, _FP8 = jnp.float32, jnp.bfloat16, jnp.float8_e4m3fn
_TOL_F32 = dict(atol=2e-6)
_TOL_BF16 = dict(rtol=2e-2, atol=2e-2)  # tests/test_pallas_attention.py's
PREFILL_CASES = {
    # a padding row, a first chunk shorter than its tile, a continued chunk
    # that ends past a 256-token block edge, a chunk from mid-page
    "four_heads": (4, 32, 128, 8, 80, 40, 32, (_F32, _F32),
                   [(0, 0), (0, 20), (268, 32), (33, 7)], _TOL_F32),
    # many heads a token, and a count that is no power of two: the rows'
    # (token, head) order and the diagonal's `iota // heads`
    "128_heads": (128, 32, 128, 8, 80, 40, 32, (_F32, _F32),
                  [(268, 32), (0, 20)], _TOL_F32),
    "24_heads": (24, 32, 128, 8, 80, 40, 32, (_F32, _F32),
                 [(33, 7), (268, 32)], _TOL_F32),
    # four 128-token tiles a row. Row 0 starts inside a page behind 303
    # resident tokens, which end inside the 256-key block [256, 512): its
    # first tile visits block 0 (neither edge) and block 1 (the diagonal),
    # its last tile ends the row inside block 3 (ctx AND the diagonal);
    # beside it a short row, a padding row and a row whose last two tiles
    # lie past its tokens, its second one straddling ctx alone
    "several_tiles": (4, 32, 128, 8, 160, 128, 512, (_F32, _F32),
                      [(303, 512), (33, 7), (0, 0), (100, 200)], _TOL_F32),
    "fp8_pool": (4, 32, 128, 8, 80, 40, 32, (_BF16, _FP8),
                 [(0, 0), (0, 20), (268, 32), (33, 7)], _TOL_BF16),
    # the cell's: 32 heads x 640 / 512, 32-token pages, 128-token tiles of
    # 4,096 rows, behind 300 resident tokens
    "kanana_bf16": (32, 512, 640, 32, 40, 32, 256, (_BF16, _BF16),
                    [(300, 256), (0, 130)], _TOL_BF16),
}


@pytest.mark.parametrize("case", list(PREFILL_CASES))
def test_latent_prefill_kernel_against_xla(case):
    """Interpret mode against `latent_attention_xla`, every token a row
    holds; what a padded position reads is finite."""
    (nh, rank, width, bs, blocks, nb, t, (q_dtype, kv_dtype), rows,
     tol) = PREFILL_CASES[case]
    rng = np.random.RandomState(1)
    kv = _latents(rng, 1, blocks, bs, width=width,
                  dim=rank + 8).astype(kv_dtype)
    tables = jnp.asarray(rng.randint(1, blocks, (len(rows), nb)), jnp.int32)
    start = jnp.asarray([r for r, _ in rows], jnp.int32)
    ctx = start + jnp.asarray([n for _, n in rows], jnp.int32)
    q = _latents(rng, len(rows), t, nh, width=width,
                 dim=rank + 8).astype(q_dtype)
    got = mla_prefill_attention(q, kv, tables, ctx, start, scale=0.2,
                                rank=rank, interpret=True)
    pos = start[:, None] + jnp.arange(t)[None]
    want = latent_attention_xla(
        q, kv, tables, causal_page_mask(pos, ctx, nb * bs), scale=0.2,
        rank=rank)
    real = np.asarray(pos < ctx[:, None])
    got = np.asarray(got, np.float32)
    assert got.shape == (len(rows), t, nh, rank) and np.isfinite(got).all()
    np.testing.assert_allclose(
        got[real], np.asarray(want, np.float32)[real], **tol)


@pytest.mark.parametrize("n", [16, 128, 256, 512])
def test_a_statistic_in_every_lane_meets_any_width(n):
    """`_lanes`: what the kernel's (rows, STAT_LANES) running max and sum
    become beside (rows, n) scores or accumulator columns."""
    stat = jnp.broadcast_to(jnp.arange(8.0)[:, None], (8, mk.STAT_LANES))

    def kernel(stat_ref, out_ref):
        out_ref[...] = mk._lanes(stat_ref[...], n)

    got = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((8, n), jnp.float32),
        interpret=True)(stat)
    np.testing.assert_array_equal(
        got, np.broadcast_to(np.arange(8.0)[:, None], (8, n)))


def test_routing_bias_moves_the_choice_and_never_the_weight(cfg):
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    logits = jnp.asarray([[2.0, 1.0, 0.5, 0.0, -1.0, -1.0, -2.0, -3.0]])
    scores = np.asarray(jax.nn.sigmoid(logits))[0]
    topv, topi = llama._sigmoid_top_k(cfg, logits, jnp.zeros((e,)))
    assert np.asarray(topi)[0].tolist() == [0, 1]
    # the weights are the scores of the chosen, renormalised, x 2.448
    np.testing.assert_allclose(
        np.asarray(topv)[0],
        2.448 * scores[:2] / scores[:2].sum(), rtol=1e-6)
    np.testing.assert_allclose(float(topv.sum()), 2.448, rtol=1e-6)
    # a bias on expert 7 moves the CHOICE to it ...
    bias = jnp.zeros((e,)).at[7].set(5.0)
    topv, topi = llama._sigmoid_top_k(cfg, logits, bias)
    assert sorted(np.asarray(topi)[0].tolist()) == [0, 7]
    # ... and its weight is its own small score, not score + bias
    pair = scores[[7, 0]] if np.asarray(topi)[0, 0] == 7 else scores[[0, 7]]
    np.testing.assert_allclose(
        np.asarray(topv)[0], 2.448 * pair / pair.sum(), rtol=1e-6)
    np.testing.assert_allclose(float(topv.sum()), 2.448, rtol=1e-6)
    # without the renormalisation the weights are the raw scores x scale
    raw = dataclasses.replace(cfg, norm_topk_prob=False)
    topv, _ = llama._sigmoid_top_k(raw, logits, jnp.zeros((e,)))
    np.testing.assert_allclose(
        np.asarray(topv)[0], 2.448 * scores[:2], rtol=1e-6)
    # ties go to the lower index, in the program and in the reference
    tie = jnp.zeros((1, e))
    _, topi = llama._sigmoid_top_k(cfg, tie, jnp.zeros((e,)))
    assert np.asarray(topi)[0].tolist() == list(range(k))
    ref_cfg = reference.check_config(reference.published_keys(cfg))
    weights = {"p.mlp.gate.weight": np.zeros((e, cfg.hidden_size)),
               "p.mlp.gate.e_score_correction_bias": np.zeros((e,))}
    _, experts, margin = reference.route(
        ref_cfg, lambda n: jnp.asarray(weights[n], jnp.float32), "p.",
        jnp.ones((1, cfg.hidden_size)))
    assert np.asarray(experts)[0].tolist() == list(range(k))
    assert float(margin[0]) == 0.0


def test_init_draws_a_nonzero_selection_bias(cfg, params):
    bias = np.asarray(params["layers"]["moe"]["bias"])
    assert bias.shape == (2, cfg.num_experts) and bias.dtype == np.float32
    assert np.abs(bias).min() > 0 and bias.std() > 0.01
    # two stacks: one dense layer, two expert layers
    assert params["layers"]["mlp"]["gate"].shape[0] == 1
    assert params["layers"]["moe"]["gate"].shape[:2] == (2, cfg.num_experts)
    assert params["layers"]["attn"]["wq"].shape[0] == 3


@pytest.mark.parametrize("name", ["tiny-kanana", "tiny-olmoe", "tiny-llama",
                                  "tiny-mixtral"])
def test_byte_counts_equal_the_arrays(name):
    c = resolve_model_config(name)
    tree = llama.init_params(c, jax.random.PRNGKey(0))
    assert param_bytes(c) == sum(a.nbytes for a in jax.tree.leaves(tree))
    kv = llama.init_kv_cache(c, 5, 8)
    assert kv_block_bytes(c, 8) * 5 == sum(a.nbytes for a in kv)
    staged = llama.init_staged_kv(c, 4, 3)
    assert staged.shape[0] == c.num_layers


def test_published_sizes():
    """The cell's cut and the whole model, from shapes alone."""
    c8 = resolve_model_config("kanana-2-30b-a3b-8l")
    tree = jax.eval_shape(lambda k: llama.init_params(c8, k),
                          jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert 5.06e9 < n < 5.08e9  # one dense + 7 expert layers, whole vocab
    assert param_bytes(c8) == sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for a in jax.tree.leaves(tree))
    # one latent a token and layer, 576 values stored 640 wide in bf16
    assert c8.latent_dim == 576 and c8.latent_width == 640
    assert kv_block_bytes(c8, 32) == 8 * 32 * 1280
    pool = jax.eval_shape(lambda: llama.init_kv_cache(c8, 8192, 32))
    assert pool[0].shape == (1, 8192, 32, 640) and len(pool) == 8
    full = resolve_model_config("kanana-2-30b-a3b")
    assert 30.0e9 < param_bytes(full) / 2 < 30.8e9  # "30B"
    assert 3.0e9 < matmul_params(full) < 3.5e9  # "A3B": active a token
    # per expert layer: attention 26.35 M, shared 9.44 M, router 0.26 M,
    # six experts of 4.72 M
    assert matmul_params(full) == (
        48 * 26_345_472 + 3 * 2048 * 6144
        + 47 * (6 * 3 * 2048 * 768 + 3 * 2048 * 1536 + 2048 * 128)
        + 2048 * 128256)


def _write_checkpoint(tmp_path, cfg, params, **config_overrides):
    from safetensors.numpy import save_file

    view = DeepseekCheckpointView(cfg, jax.tree.map(np.asarray, params))
    tensors = {n: np.ascontiguousarray(np.asarray(view[n], np.float32))
               for n in view.names()}
    save_file(tensors, str(tmp_path / "model.safetensors"))
    hf = {
        "architectures": ["DeepseekV3ForCausalLM"],
        "model_type": "deepseek_v3", "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "kv_lora_rank": cfg.kv_lora_rank, "q_lora_rank": None,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "first_k_dense_replace": 1,
        "n_routed_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "n_shared_experts": cfg.n_shared_experts, "n_group": 1,
        "topk_group": 1, "norm_topk_prob": True, "scoring_func": "sigmoid",
        "routed_scaling_factor": 2.448, "rope_theta": cfg.rope_theta,
        "rope_interleave": True, "rope_scaling": None,
        "rms_norm_eps": cfg.rms_norm_eps, "max_position_embeddings": 256,
        "tie_word_embeddings": False, "torch_dtype": "float32",
    }
    hf.update(config_overrides)
    (tmp_path / "config.json").write_text(json.dumps(hf))
    return tensors


def test_checkpoint_names_round_trip_with_the_rope_de_interleave(
        tmp_path, cfg, params):
    tensors = _write_checkpoint(tmp_path, cfg, params)
    # the DeepSeek-V3 names, (out, in) orientation
    p = "model.layers.1."
    for name, shape in {
        "model.layers.0.mlp.gate_proj.weight": (128, 64),
        p + "self_attn.q_proj.weight": (4 * 24, 64),
        p + "self_attn.kv_a_proj_with_mqa.weight": (40, 64),
        p + "self_attn.kv_a_layernorm.weight": (32,),
        p + "self_attn.kv_b_proj.weight": (4 * 32, 32),
        p + "self_attn.o_proj.weight": (64, 4 * 16),
        p + "mlp.gate.weight": (8, 64),
        p + "mlp.gate.e_score_correction_bias": (8,),
        p + "mlp.experts.7.down_proj.weight": (64, 32),
        p + "mlp.shared_experts.up_proj.weight": (32, 64),
    }.items():
        assert tensors[name].shape == shape, name
    loaded_cfg = resolve_model_config(str(tmp_path), dtype="float32")
    assert loaded_cfg.architecture == "deepseek_v3" and loaded_cfg.mla
    assert loaded_cfg.rope_interleave and loaded_cfg.first_k_dense_replace == 1
    assert loaded_cfg.intermediate_size == 128
    assert loaded_cfg.moe_intermediate_size == 32
    loaded = load_checkpoint_params(loaded_cfg)
    want = jax.tree.map(np.asarray, params)
    assert jax.tree.structure(loaded) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    # the checkpoint's rope columns ARE interleaved: column 2i of the
    # checkpoint is the program's column i, column 2i+1 its column i + d/2
    # (the tree holds (heads, nope ‖ rope, h), the checkpoint's rows)
    wq = np.moveaxis(np.asarray(params["layers"]["attn"]["wq"][1]), -1, 0)
    assert wq.shape == (64, 4, 24)
    ck = tensors[p + "self_attn.q_proj.weight"].T.reshape(64, 4, 24)
    np.testing.assert_array_equal(ck[..., :16], wq[..., :16])  # nope: as is
    np.testing.assert_array_equal(ck[..., 16::2], wq[..., 16:20])
    np.testing.assert_array_equal(ck[..., 17::2], wq[..., 20:24])
    assert not np.array_equal(ck[..., 16:], wq[..., 16:])


@pytest.mark.parametrize("override,message", [
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    # (group-limited routing is served since PR 45 where the groups are
    # of equal size: tests/test_ling_parts.py)
    ({"n_group": 3, "topk_group": 2}, "n_group that does not divide"),
    ({"rope_scaling": {"type": "yarn", "factor": 40}}, "rope_scaling"),
])
def test_unbuilt_config_keys_are_refused_by_name(tmp_path, cfg, params,
                                                 override, message):
    _write_checkpoint(tmp_path, cfg, params, **override)
    with pytest.raises(ValueError, match=message):
        resolve_model_config(str(tmp_path))
    with pytest.raises(ValueError, match="does not cover"):
        reference.check_config(override)


@pytest.mark.parametrize("kw,message", [
    (dict(cache=CacheConfig(block_size=8, num_blocks=64, num_host_blocks=8)),
     "host-RAM tier"),
    (dict(cache=CacheConfig(block_size=8, num_blocks=64, disk_kv_dir="/tmp/x",
                            disk_kv_gib=1.0)), "disk tier"),
    (dict(cache=CacheConfig(block_size=8, num_blocks=64,
                            kv_at_rest_codec="int4")), "at-rest codec"),
    (dict(kv_peer_fetch=True), "peer-engine tier"),
    (dict(parallel=ParallelConfig(tensor_parallel_size=2)),
     "parallelism is not implemented for multi-head latent"),
    (dict(model="int8"), "attn.wkv_a"),
])
def test_unbuilt_engine_options_are_refused_at_start(cfg, kit, kw, message):
    base = ek.engine_config(cfg, **POOL)
    if kw.get("model") == "int8":
        kw = dict(model=dataclasses.replace(cfg, quantization="int8"))
    with pytest.raises(ValueError, match=message):
        kit.fresh_engine(base.replace(**kw))


def test_served_over_http_and_kv_page_transfer_is_refused(cfg, kit):
    """Chat and completion requests through the engine's HTTP server; the
    endpoints that ship K/V-shaped pages answer 501 with one sentence."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from vllm_production_stack_tpu.engine.server import EngineServer

    # (an engine of its own: the server's step thread drives it)
    srv = EngineServer(kit.fresh_engine(ek.engine_config(cfg, **POOL)),
                       served_model_name="tiny-kanana")

    async def drive():
        client = TestClient(TestServer(srv.build_app()))
        await client.start_server()
        try:
            r = await client.post("/v1/completions", json={
                "model": "tiny-kanana", "prompt": "latent attention " * 5,
                "max_tokens": 6, "temperature": 0})
            done = await r.json()
            assert r.status == 200, done
            assert done["usage"]["completion_tokens"] == 6
            r = await client.post("/v1/chat/completions", json={
                "model": "tiny-kanana", "max_tokens": 4, "temperature": 0,
                "messages": [{"role": "user", "content": "hello"}]})
            assert r.status == 200, await r.text()
            for path in ("/kv/export", "/kv/import", "/kv/pull",
                         "/kv/peer_fetch", "/kv/export_stream"):
                r = await client.post(path, json={})
                body = await r.json()
                assert r.status == 501, (path, body)
                assert "one latent a token" in body["error"]["message"]
        finally:
            await client.close()

    asyncio.run(drive())


def test_reference_follows_the_served_routing_in_bf16(cfg, kit):
    """In bf16 the router's hard top-k flips boundary experts, and each flip
    swaps a renormalised share of the routed output: a free-running
    comparison reads the flips. The served programs' own routing is tapped
    (llama.ROUTING_TAP, set while the programs are traced) and the
    reference follows it, reporting how far each served choice lay from its
    own: logprobs then agree to bf16 rounding and every differing choice
    sits within a rounding margin."""
    bf16 = dataclasses.replace(cfg, dtype="bfloat16")
    prompts = ek.prompts(cfg, (70, 9, 33, 50))
    with reference_check.RoutingTap() as tap:
        engine = kit.fresh_engine(ek.engine_config(bf16, **POOL))
        served = reference_check.served_logprobs(engine, prompts, 10)
    assert llama.ROUTING_TAP is None
    seq = prompts[0] + served[0]["token_ids"][:-1]
    per_layer = tap.routing_of(seq)
    assert len(per_layer) == 2 and per_layer[0].shape == (len(seq), 2)
    out = reference_check.compare(
        reference, bf16, engine.runner.params, prompts, served,
        served_routing=tap)
    # 0.0079 and 0.0036 measured: three layers of bf16, logits of size ~1
    assert out["max_abs_dlogprob"] <= 0.03, out
    assert out["routing_margin_max_where_differing"] <= 0.01, out
    assert out["routing_disagreement_share"] <= 0.1, out
