"""Window and full attention layers with their own head counts, ropes and
KV page groups, a per-head output gate and a share of the experts
(Laguna-S-2.1, ISSUE 33) at tiny size on the CPU: the served engine against
the plain float32 reference (testing/reference_laguna.py), the window
kernels in interpret mode against the masked XLA form at page boundaries,
yarn and partial rotary against the formula, the share and the sliced
vocabulary against the uncut model. (tests/test_window_kv.py holds the two
page groups, the byte counts and the refusals.)"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import engine_kit as ek
from vllm_production_stack_tpu.models import llama
from vllm_production_stack_tpu.models.loader import LagunaCheckpointView
from vllm_production_stack_tpu.models.registry import resolve_model_config
from vllm_production_stack_tpu.ops.attention import (
    apply_rope, causal_page_mask, paged_attention_with_staged,
    paged_attention_xla,
)
from vllm_production_stack_tpu.ops.paged_attention_pallas import (
    paged_window_decode_attention, paged_window_prefill_attention,
)
from vllm_production_stack_tpu.testing import reference_check
from vllm_production_stack_tpu.testing import reference_laguna as reference

# float32 on both sides: what differs is the order of sums (paged flash
# attention against one softmax, routed pairs against every expert) — 2.4e-6
# measured over 96 positions, logits of size ~1
LOGPROB_ATOL = 2e-5


@pytest.fixture(scope="module")
def cfg():
    return resolve_model_config("tiny-laguna")


@pytest.fixture(scope="module")
def params(cfg):
    return llama.init_params(cfg, jax.random.PRNGKey(3))


# -- the engine against the reference ---------------------------------------

@pytest.mark.parametrize("backend,layers,chunk,lengths", [
    ("xla", 9, 32, (70, 120)), ("pallas_interpret", 2, 32, (40, 90)),
    ("xla", 5, 8, (70, 9, 33, 120))])
def test_engine_against_the_float32_reference(
        cfg, kit, backend, layers, chunk, lengths):
    """Prefill in chunks (longer and shorter than the window of 16; packed,
    several rows a dispatch), then fused decode windows through BOTH page
    groups — prompts several windows long, so window pages are given back
    under the rows while they run — against the reference's full forward:
    the two ropes (yarn past its original 64 positions), the two head
    counts, the gate, the window in kernels and masks, the held experts and
    the give-back are all under test. (The interpret-mode kernels compile
    slowly: that case runs one full and one window layer.)"""
    model = ek.cut(cfg, layers)  # (a whole period is 4)
    engine, _ = kit.engine(ek.engine_config(model, backend, chunk=chunk))
    prompts = ek.prompts(model, lengths)
    served = reference_check.served_logprobs(engine, prompts, 12)
    out = reference_check.compare(
        reference, model, engine.runner.params, prompts, served,
        routing_chunk=32)
    assert out["positions"] == 12 * len(lengths)
    assert out["ids_compared"] == 8 * out["positions"]
    assert out["max_abs_dlogprob"] <= LOGPROB_ATOL, out
    assert out["routing_disagreement_share"] == 0.0, out
    wpool = engine.scheduler.pool.window
    assert wpool.given_back_total > 0
    assert wpool.num_free == wpool.num_usable  # every page came back
    assert engine.scheduler.total_preemptions == 0


def test_held_experts_through_the_touched_kernel(cfg, kit):
    """A program that holds 4 of 16 experts and decodes two rows runs the
    touched-experts kernel (interpret mode) over the held experts only:
    what it read is what was touched, pairs on other chips' experts touch
    nothing here, and the tokens are the einsum engine's."""
    model = ek.cut(cfg, 2)
    prompts = [[5, 6, 7, 8, 9], list(range(20, 45))]
    got = {}
    for backend in ("xla", "pallas_interpret"):
        engine, before = kit.engine(ek.engine_config(model, backend, seqs=2))
        got[backend] = (
            ek.generate(engine, prompts, 9), ek.since(engine, before),
            engine.runner.device_report()["moe"]["decode"]["resolved"])
    (tok_x, t_x, form_x), (tok_k, t_k, form_k) = got.values()
    assert (form_x, form_k) == ("all_experts", "touched_kernel")
    assert tok_k == tok_x
    assert t_k["moe_decode_touched_n"] == t_x["moe_decode_touched_n"] > 0
    assert t_k["moe_decode_read_n"] == t_k["moe_decode_touched_n"]
    assert t_x["moe_decode_read_n"] == t_x["moe_decode_slots_n"]
    assert t_k["moe_decode_held_pairs_n"] < t_k["moe_decode_pairs_n"]


def test_held_experts_through_the_grouped_kernel(cfg, kit, monkeypatch):
    """A program that holds 4 of 16 experts and prefills chunks above the
    token limit (lowered to 8 tokens here) sorts every routed pair but
    runs only those that land on its own experts through the fused kernel
    (interpret mode): the tokens and the counters are the `ragged_dot`
    engine's, and `device.moe.prefill` says which form each holds."""
    monkeypatch.setattr(llama, "_ALL_EXPERTS_MAX_TOKENS", 8)
    model = ek.cut(cfg, 2)
    prompts = [[5, 6, 7, 8, 9] * 4, list(range(20, 65))]
    got = {}
    for backend in ("xla", "pallas_interpret"):
        engine = kit.fresh_engine(ek.engine_config(model, backend))
        got[backend] = (
            ek.generate(engine, prompts, 6), engine.flightrec.timing(),
            engine.runner.device_report()["moe"]["prefill"]["resolved"])
    (tok_x, t_x, form_x), (tok_k, t_k, form_k) = got.values()
    assert (form_x, form_k) == ("grouped", "grouped_kernel")
    assert tok_k == tok_x
    for key in ("touched", "held_pairs", "pairs", "slots", "read"):
        assert t_k[f"moe_prefill_{key}_n"] == t_x[f"moe_prefill_{key}_n"] > 0
    assert t_k["moe_prefill_read_n"] == t_k["moe_prefill_touched_n"]
    assert 0 < t_k["moe_prefill_held_pairs_n"] < t_k["moe_prefill_pairs_n"]


def test_a_model_whose_layer_kinds_are_swapped_differs(cfg, params):
    """Head counts, ropes and windows belong to the layer's kind: the same
    tokens through a model with full and window layers swapped (the leaves
    drawn from the same key) give other logits, and the tree's two attention
    stacks have the kinds' own shapes."""
    layers = params["layers"]
    h, d = cfg.hidden_size, cfg.head_dim
    assert layers["attn"]["wq"].shape == (3, 4, d, h)
    assert layers["attn_window"]["wq"].shape == (6, 6, d, h)
    assert layers["attn"]["wg"].shape == (3, h, 4)
    assert layers["attn_window"]["wo"].shape == (6, 6 * d, h)
    assert layers["moe"]["router"].shape == (8, h, 16)
    assert layers["moe"]["gate"].shape == (8, 4, h, 32)  # 4 of 16 held
    full, win = cfg.layers[0], cfg.layers[1]
    swapped = dataclasses.replace(cfg, layers=tuple(
        dataclasses.replace(win if s.window == 0 else full,
                            experts=s.experts) for s in cfg.layers))
    toks = jnp.arange(1, 41, dtype=jnp.int32)

    def logits(c):
        p = llama.init_params(c, jax.random.PRNGKey(3))
        view = LagunaCheckpointView(c, p)
        return reference.forward(
            reference.published_keys(c), view, toks, [39])[0]

    assert float(jnp.abs(logits(cfg) - logits(swapped)).max()) > 1e-3


def test_the_gate_multiplies_each_head(cfg, params):
    """Zero gate weights give g = sigmoid(0) = 1/2 on every head: the
    attention block's output is exactly half the ungated model's."""
    model = ek.cut(cfg, 1)
    p = llama.init_params(model, jax.random.PRNGKey(0))
    p["layers"]["attn"]["wg"] = jnp.zeros_like(p["layers"]["attn"]["wg"])
    ungated = dataclasses.replace(model, attn_output_gate="")
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, model.hidden_size))
    pos = jnp.arange(6)[None]

    def attend(q, k, v):
        return llama.masked_attention(
            q, k, v, causal_page_mask(pos, jnp.array([6]), 6),
            scale=model.attn_scale)

    def block(c):
        lp = llama._layer_params(c, p, 0)
        lp["mlp"] = jax.tree.map(jnp.zeros_like, lp["mlp"])
        return llama._layer_body(
            c, lp, x, pos, attend, spec=c.layer_specs[0]) - x

    np.testing.assert_allclose(
        block(model), 0.5 * block(ungated), rtol=1e-5, atol=1e-6)


# -- ropes -------------------------------------------------------------------

def test_yarn_and_partial_rotary_against_the_formula():
    """The full layers' rope, written out: the first 64 of 128 dims rotate
    (rotate-half over those 64), theta 500 000, yarn x128 from 8 192
    positions with beta 32 / 1, cos and sin times the attention factor; the
    other 64 dims pass through."""
    spec = resolve_model_config("laguna-s-2.1-ep4-9l").layer_specs[0].rope
    assert spec.rotary_dim == 64 and spec.attention_factor == pytest.approx(
        0.1 * math.log(128) + 1)
    theta, d, orig, factor = 500000.0, 64, 8192, 128.0
    j = np.arange(32, dtype=np.float64)
    inv = theta ** (-2 * j / d)

    def corr(beta):
        return d * math.log(orig / (2 * math.pi * beta)) / (
            2 * math.log(theta))

    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (9, 18)
    ramp = np.clip((j - low) / (high - low), 0, 1)
    inv = (1 - ramp) * inv + ramp * inv / factor
    x = np.random.RandomState(0).randn(5, 3, 128)
    pos = np.array([0, 1, 511, 9000, 16383])
    ang = pos[:, None] * inv[None]
    cos = np.cos(ang)[:, None] * spec.attention_factor
    sin = np.sin(ang)[:, None] * spec.attention_factor
    want = np.concatenate(
        [x[..., :32] * cos - x[..., 32:64] * sin,
         x[..., 32:64] * cos + x[..., :32] * sin, x[..., 64:]], axis=-1)
    with jax.enable_x64(True):
        got = apply_rope(
            jnp.asarray(x), jnp.asarray(pos), spec.theta,
            scaling=spec.scaling, rotary_dim=spec.rotary_dim,
            attention_factor=spec.attention_factor)
    # (the program's angles are float32: position 16 383 x a frequency is
    # exact to ~1e-3 rad)
    np.testing.assert_allclose(np.asarray(got), want, rtol=5e-3, atol=5e-3)
    # the window layers: every dim, theta 10 000, no scaling
    wspec = resolve_model_config("laguna-s-2.1-ep4-9l").layer_specs[1].rope
    assert (wspec.theta, wspec.rotary_dim, wspec.scaling) == (1e4, 0, None)


# -- the window kernels at page boundaries -----------------------------------

def _window_pool(rng, num_blocks, bs, kvh, d):
    return rng.randn(2, num_blocks, bs, kvh, d).astype(np.float32)


@pytest.mark.parametrize("kvh,d", [(8, 128), (2, 16)])
@pytest.mark.parametrize("hist", [40, 47, 48, 49, 300])
def test_window_decode_kernel_against_masked_xla(hist, kvh, d):
    """The window decode kernel (the walk form at pages that fill tiles,
    the grid form otherwise) against the masked XLA attention, with the
    first visible position p - window + 1 at a page's first slot (hist 47 +
    step 1: 48 - 31 ... ) and last slot, and the pages behind it GIVEN BACK
    (their table slots point at the null page, filled with a value that
    would show): what is behind the window is never attended."""
    bs, window, w, nb, b = 8, 32, 4, 64, 3
    rng = np.random.RandomState(hist)
    kv = _window_pool(rng, 200, bs, kvh, d)
    kv[:, 0] = 100.0  # the null page: attending it would show
    nh = kvh * 3
    hists = np.array([hist, max(hist - 9, 1), 0], np.int32)
    tables = np.zeros((b, nb), np.int32)
    ids = iter(range(1, 200))
    for step in (0, 1, 3):
        for r in range(b):
            first = max(0, hists[r] + step - window + 1) // bs
            for jj in range(first, -(-hists[r] // bs)):
                if not tables[r, jj]:
                    tables[r, jj] = next(ids)
        q = rng.randn(b, nh, d).astype(np.float32)
        sk = rng.randn(w, b, kvh, d).astype(np.float32)
        sv = rng.randn(w, b, kvh, d).astype(np.float32)
        out = paged_window_decode_attention(
            jnp.asarray(q), jnp.asarray(kv), jnp.asarray(tables),
            jnp.asarray(hists), jnp.asarray(sk), jnp.asarray(sv),
            jnp.int32(step), scale=d ** -0.5, window=window, interpret=True)
        arange = np.arange(nb * bs)[None]
        mask = (arange < hists[:, None]) & (
            arange > (hists + step - window)[:, None])
        ref = paged_attention_with_staged(
            jnp.asarray(q)[:, None], jnp.asarray(kv), jnp.asarray(tables), jnp.asarray(mask), jnp.asarray(sk),
            jnp.asarray(sv), jnp.asarray(np.arange(w) <= step),
            scale=d ** -0.5)[:, 0]
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
        # give back what the NEXT steps no longer see
        for r in range(b):
            first = max(0, hists[r] + 3 - window + 1) // bs
            tables[r, :first] = 0


@pytest.mark.parametrize("start,ctx", [
    (0, 24), (31, 95), (32, 96), (33, 97), (200, 264), (7, 8)])
def test_window_prefill_kernel_against_masked_xla(start, ctx):
    """The window prefill kernel against the masked XLA attention: chunks
    whose first visible position lies at a page's first and last slot,
    tiles narrower than the window, pages behind the chunk's window given
    back (a null page that would show), a padding row, a short second row."""
    bs, window, kvh, d, t, nb = 8, 32, 2, 16, 64, 48
    nh = kvh * 3
    rng = np.random.RandomState(start)
    kv = _window_pool(rng, 160, bs, kvh, d)
    kv[:, 0] = 100.0
    starts = np.array([start, max(start - 5, 0), 0], np.int32)
    ctxs = np.array([ctx, max(start - 5, 0) + 3, 0], np.int32)
    tables = np.zeros((3, nb), np.int32)
    ids = iter(range(1, 160))
    for r in range(2):
        first = max(0, starts[r] - window + 1) // bs
        for jj in range(first, -(-ctxs[r] // bs)):
            tables[r, jj] = next(ids)
    q = rng.randn(3, t, nh, d).astype(np.float32)
    out = paged_window_prefill_attention(
        jnp.asarray(q), jnp.asarray(kv), jnp.asarray(tables),
        jnp.asarray(ctxs), jnp.asarray(starts), scale=d ** -0.5,
        window=window, interpret=True)
    pos = starts[:, None] + np.arange(t)[None]
    mask = causal_page_mask(
        jnp.asarray(pos), jnp.asarray(ctxs), nb * bs, window=window)
    ref = paged_attention_xla(
        jnp.asarray(q), jnp.asarray(kv), jnp.asarray(tables), mask,
        scale=d ** -0.5)
    assert np.isfinite(np.asarray(out)).all()  # padding rows and columns too
    for r in range(2):
        n = ctxs[r] - starts[r]
        np.testing.assert_allclose(
            out[r, :n], ref[r, :n], rtol=2e-5, atol=2e-5)


# -- the share and the slice -------------------------------------------------

def test_the_four_shares_add_up_to_the_whole_expert_layer(cfg):
    """The routed parts the four chips of the deployment compute (each its
    quarter of the 16 experts: the PROGRAM's `_moe_mlp` on a tree that holds
    that quarter) plus the shared expert counted ONCE equal the uncut
    reference's whole expert layer."""
    whole = dataclasses.replace(cfg, num_experts_held=0, first_expert_held=0)
    p_whole = llama.init_params(ek.cut(whole, 2), jax.random.PRNGKey(7))
    moe = jax.tree.map(lambda a: a[0], p_whole["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 40, cfg.hidden_size))
    view = LagunaCheckpointView(ek.cut(whole, 2), p_whole)
    ref_cfg = reference.check_config(reference.published_keys(ek.cut(whole, 2)))

    def run(fn, *args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)

    want, _ = reference.expert_layer(
        ref_cfg, lambda n: reference.f32(view[n]), "model.layers.1.", x[0],
        run)
    shared = run(reference.swiglu, lambda n: reference.f32(view[n]),
                 "model.layers.1.mlp.shared_expert.", x[0])
    total = jnp.zeros_like(want)
    for shard in range(4):
        part_cfg = dataclasses.replace(
            cfg, num_experts_held=4, first_expert_held=4 * shard)
        part = {**moe, **{
            k: moe[k][4 * shard:4 * shard + 4]
            for k in ("gate", "up", "down")}}
        y, routing = llama._moe_mlp(part_cfg, part, x)
        total = total + (y[0] - shared)
        assert int(routing["held_pairs"]) <= 40 * 4
    np.testing.assert_allclose(total + shared, want, rtol=2e-5, atol=2e-5)
    # ... and the grouped form (a prefill chunk's) computes the same share
    part_cfg = dataclasses.replace(
        cfg, num_experts_held=4, first_expert_held=4)
    part = {**moe, **{k: moe[k][4:8] for k in ("gate", "up", "down")}}
    xf = x.reshape(-1, cfg.hidden_size)
    topv, topi, chosen, sizes = llama._moe_route(part_cfg, part["router"], xf)
    grouped = llama._experts_grouped(
        part_cfg, jax.tree.map(lambda a: a[None], part), 0, xf, topv, topi,
        sizes)
    every = llama._experts_all(part_cfg, part, xf, topv, chosen)
    np.testing.assert_allclose(grouped, every, rtol=2e-5, atol=2e-5)
    assert int(sizes.sum()) < 40 * 4  # some pairs land on other chips


def test_logits_over_the_slice_equal_the_uncut_models_restricted(cfg):
    """A sliced vocabulary is a smaller vocabulary: with the embedding's
    rows and the head's columns [0, 128) of the uncut model's 512, the
    logits equal the uncut model's restricted to the slice."""
    whole = ek.cut(cfg, 2)
    sliced = dataclasses.replace(whole, vocab_size=128)
    p = llama.init_params(whole, jax.random.PRNGKey(2))
    p_slice = {**p, "embed": p["embed"][:128], "lm_head": p["lm_head"][:, :128]}
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 128, 30))

    def logits(c, pp):
        return reference.forward(
            reference.published_keys(c), LagunaCheckpointView(c, pp), toks)[0]

    np.testing.assert_allclose(
        logits(sliced, p_slice), logits(whole, p)[:, :128], rtol=1e-6,
        atol=1e-6)
    hidden = jax.random.normal(jax.random.PRNGKey(1), (3, cfg.hidden_size))
    np.testing.assert_allclose(
        llama.compute_logits(sliced, p_slice, hidden),
        llama.compute_logits(whole, p, hidden)[:, :128], rtol=1e-6, atol=1e-6)
