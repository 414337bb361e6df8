"""Linear-attention (KDA, the gated delta rule with a per-channel gate)
layers whose recurrent state lives in state slots beside the paged KV of
gated NoPE GQA layers, with a share of the experts (Solar-Open2, ISSUE 39) at
tiny size on the CPU: the served engine against the plain float32 reference
(testing/reference_solar_open2.py), the two kernels in interpret mode against
the token-by-token recurrence, the share against the uncut layer, the slots
through admission, preemption, abort and rollback, the byte counts, the
refusals and the names."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import engine_kit as ek
from vllm_production_stack_tpu import metrics_contract as mc
from vllm_production_stack_tpu.engine import memory
from vllm_production_stack_tpu.engine.config import (
    CacheConfig, EngineConfig, LoRAConfig, ParallelConfig, SchedulerConfig,
)
from vllm_production_stack_tpu.engine.request import (
    Request, SamplingParams,
)
from vllm_production_stack_tpu.engine.scheduler import Scheduler
from vllm_production_stack_tpu.models import llama
from vllm_production_stack_tpu.models.loader import SolarOpen2CheckpointView
from vllm_production_stack_tpu.models.registry import resolve_model_config
from vllm_production_stack_tpu.ops import kda_attention as kda
from vllm_production_stack_tpu.ops import kda_short_conv as ksc
from vllm_production_stack_tpu.testing import reference_check
from vllm_production_stack_tpu.testing import (
    reference_solar_open2 as reference,
)

# float32 on both sides. Through the XLA forms the engine runs the very
# recurrence the reference writes, token by token: what differs is the order
# of sums (paged flash attention against one softmax, routed pairs against
# every expert) — 6.2e-6 measured over 48 positions. Through the kernels the
# chunked form solves a 64-token block at once ((I + L)^-1, decays summed
# over ranges): 9.5e-7 measured at two layers; the same limit holds both. A
# state rounded to bfloat16 at every token reads 2e-2, a left-out gate 0.4
# (test_the_tolerance_tells_a_faulty_reference).
LOGPROB_ATOL = 5e-5
# the state a row leaves in its slot against the reference's recurrence over
# the same q, k, v, g, beta (reference_check.served_state), float32 on both
# sides: the order of sums again. A state rounded to bfloat16 at every token
# reads over 1e-3 (chip_smoke.py's KDA_STATE_RTOL, which bf16 inputs need).
STATE_RTOL = 1e-5


@pytest.fixture(scope="module")
def cfg():
    return resolve_model_config("tiny-solar-open2")


# what this file's engines differ in from the kit's: chunks of 64 tokens, a
# block of the KDA prefill kernel (cuts of the model are whole periods of 4
# layers, G K K K, or a stated part of one)
CHUNK = dict(chunk=64)


# -- the engine against the reference ---------------------------------------

@pytest.mark.parametrize("backend,layers,lengths", [
    ("xla", 8, (70, 9, 33, 150)), ("pallas_interpret", 2, (70, 9, 33, 150)),
    ("xla", 4, (200,))])
def test_engine_against_the_float32_reference(
        cfg, kit, backend, layers, lengths):
    """Prefill in chunks of 64 (the 150- and 200-token prompts in three and
    four, the others packed beside them: a chunk holds up to four rows'
    segments, and a seam lies inside a kernel block), then fused decode
    windows through pages AND state slots, against the reference's full
    forward: the convolution across chunks and seams, the state carried from
    chunk to chunk and through the window, the two banks, the elementwise
    gate, no rope, the held experts with their selection bias."""
    model = ek.cut(cfg, layers)
    prompts = ek.prompts(model, lengths)
    with (reference_check.RoutingTap() as tap,
          reference_check.StateTap(model.linear_layers[-1]) as state_tap):
        engine = kit.fresh_engine(ek.engine_config(model, backend, **CHUNK))
        served = reference_check.served_logprobs(engine, prompts, 12)
    out = reference_check.compare(
        reference, model, engine.runner.params, prompts, served,
        served_routing=tap)
    assert out["positions"] == 12 * len(lengths)
    assert out["max_abs_dlogprob"] <= LOGPROB_ATOL, out
    assert out["routing_disagreement_share"] == 0.0, out
    # ... and the state each row left in its slot against the reference's
    # recurrence over what the served programs fed that layer
    state = reference_check.served_state(
        reference, model, state_tap,
        np.asarray(engine.runner.kv_caches[state_tap.layer]["s"]))
    assert state["rows"] == len(lengths)
    assert state["state_rel_err"] <= STATE_RTOL, state
    slots = engine.scheduler.pool.state
    assert slots.num_free == slots.num_usable  # every slot came back
    report = engine.runner.device_report()["mixers"]
    assert (report["softmax"], report["linear"]) == (
        len(model.page_layers), len(model.linear_layers))
    assert report["linear_backend"]["prefill"] == (
        "xla" if backend == "xla" else "kda_prefill_attention")
    assert report["linear_backend"].get("heads_per_step") == (
        None if backend == "xla" else kda.heads_per_step(
            model.linear_num_heads, model.linear_head_dim, 4))


def _faulty(**edits):
    """reference_solar_open2, wrong in one way (chip_smoke.py's idiom)."""
    import types

    def published_keys(model_cfg):
        return {**reference.published_keys(model_cfg), **edits}

    return types.SimpleNamespace(
        forward=reference.forward, published_keys=published_keys,
        delta_rule=reference.delta_rule)


def _serve_tapped(kit, model, lengths, n_out, seed):
    """(params, prompts, served logprobs, routing tap, state tap, the tapped
    layer's state pool) of an XLA engine that served `lengths`."""
    prompts = ek.prompts(model, lengths, seed=seed)
    with (reference_check.RoutingTap() as tap,
          reference_check.StateTap(model.linear_layers[-1]) as state_tap):
        engine = kit.fresh_engine(ek.engine_config(model, **CHUNK))
        served = reference_check.served_logprobs(engine, prompts, n_out)
    pool = np.asarray(engine.runner.kv_caches[state_tap.layer]["s"])
    return engine.runner.params, prompts, served, tap, state_tap, pool


@pytest.fixture(scope="module")
def served_once(cfg, kit):
    model = ek.cut(cfg, 4)
    return model, *_serve_tapped(kit, model, (70, 33), 8, seed=3)


@pytest.mark.parametrize("edits", [
    {"use_gqa_gate": False}, {"kda_allow_neg_eigval": False},
    {"kda_state_inherited": True}, {"kda_conv_inherited": True},
    {"kda_state_dtype": "bfloat16"}], ids=lambda e: next(iter(e)))
def test_the_tolerance_tells_a_faulty_reference(served_once, edits):
    """A gate left out, beta without its factor 2, a state or a
    convolution history not zeroed at a row's first token, a state kept in
    bfloat16: each lies outside the tolerance the served engine is inside."""
    model, params, prompts, served, tap = served_once[:5]
    good = reference_check.compare(
        reference, model, params, prompts, served, served_routing=tap)
    bad = reference_check.compare(
        _faulty(**edits), model, params, prompts, served, served_routing=tap)
    assert good["max_abs_dlogprob"] <= LOGPROB_ATOL
    assert bad["max_abs_dlogprob"] > 20 * LOGPROB_ATOL, bad


# -- the kernels against the token-by-token recurrence -----------------------

def _kda_inputs(rng, n, heads, d, gate_scale, beta_logit=0.0):
    q = rng.randn(n, heads, d).astype(np.float32)
    k = rng.randn(n, heads, d).astype(np.float32)
    q *= d ** -0.5 / np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.randn(n, heads, d).astype(np.float32)
    g = -np.abs(rng.randn(n, heads, d)).astype(np.float32) * gate_scale
    beta = 2 / (1 + np.exp(-(rng.randn(n, heads) + beta_logit)))
    return q, k, v, g, beta.astype(np.float32)


def _recurrence(pool, read, write, fresh, row_off, lens, q, k, v, g, beta):
    """Row by row, token by token (kda_step), in float32."""
    new, o = np.array(pool), np.zeros_like(v)
    for r, n in enumerate(lens):
        if not n:
            continue
        s = jnp.zeros_like(pool[0]) if fresh[r] else jnp.asarray(
            pool[read[r]])
        for t in range(row_off[r], row_off[r] + n):
            s, o[t] = kda.kda_step(s, q[t], k[t], v[t], g[t], beta[t])
        new[write[r]] = s
    return o, new


# heads a grid step of the kernel holds (ops/kda_attention.heads_per_step):
# both of two, the one of one, two groups of four, and three of six, where
# four does not divide the heads
@pytest.mark.parametrize("heads,d,per_step", [
    (2, 16, 2), (1, 128, 1), (8, 128, 4), (6, 16, 3)])
@pytest.mark.parametrize("gate_scale,beta_logit,lens", [
    (1e-3, 0.0, (70, 5, 0, 100)),   # gates near 0: nothing decays
    (30.0, 0.0, (70, 5, 0, 100)),   # the strongest decay: exp(-30 |x|)
    (1.0, 6.0, (64, 1, 63, 40)),    # beta near 2
    (1.0, 0.0, (3, 130, 2, 1))])    # lengths that are no multiple of 64
def test_chunked_kernel_against_the_recurrence(heads, d, per_step,
                                               gate_scale, beta_logit, lens):
    """The chunked prefill kernel (interpret mode) over a packed axis —
    rows that start inside a block, a row of no tokens, a row that starts
    from zeros — against the recurrence, reading one bank of slots and
    writing the other; slots the chunk does not name keep their bytes."""
    assert kda.heads_per_step(heads, d, 4) == per_step
    rng = np.random.RandomState(int(gate_scale * 10) + d + heads)
    lens = np.asarray(lens, np.int32)
    n = 192
    row_off = (np.cumsum(lens) - lens).astype(np.int32)
    q, k, v, g, beta = _kda_inputs(rng, n, heads, d, gate_scale, beta_logit)
    pool = rng.randn(10, heads, d, d).astype(np.float32)
    read = np.array([1, 2, 0, 3], np.int32)
    write = np.array([6, 7, 0, 8], np.int32)
    fresh = np.array([False, True, False, False])
    want_o, want_pool = _recurrence(
        pool, read, write, fresh, row_off, lens, q, k, v, g, beta)
    o, got_pool = kda.kda_prefill_attention(
        jnp.asarray(pool), jnp.asarray(read), jnp.asarray(write),
        jnp.asarray(fresh), *(jnp.asarray(x) for x in (q, k, v, g, beta)),
        jnp.asarray(row_off), jnp.asarray(lens), interpret=True)
    live = np.zeros(n, bool)
    for r in range(4):
        live[row_off[r]:row_off[r] + lens[r]] = True
    # float32 against float32: a 64-token block solved at once against 64
    # rank-one updates in turn (|o| ~ 1, |S| ~ 3)
    np.testing.assert_allclose(
        np.asarray(o)[live], want_o[live], rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(got_pool), want_pool, rtol=1e-4, atol=5e-5)


def test_the_report_names_the_prefill_kernels_grid_step(cfg):
    """`/debug/programs` -> `device.mixers.linear_backend`: several heads a
    grid step at Solar-Open2's shape (64 heads x 128, bf16) and at the tiny
    preset's, one at a one-head shape, nothing under the XLA form."""
    from vllm_production_stack_tpu.engine.model_runner import (
        linear_backend_report,
    )

    solar = resolve_model_config("solar-open2-250b-ep8-8l")
    report = linear_backend_report(solar, "pallas", "pallas")
    assert report["prefill"] == "kda_prefill_attention"
    assert report["block_tokens"] == kda.BLOCK == 64
    assert 1 < report["heads_per_step"] <= solar.linear_num_heads
    assert solar.linear_num_heads % report["heads_per_step"] == 0
    # the pass that makes q, k, v beside it: one head's lanes x the chunk
    assert report["qkv"] == {
        "op": "kda_short_conv", "heads_per_tile": 1, "token_tile": 2048}
    assert linear_backend_report(solar, "pallas", "pallas", 8192)[
        "qkv"]["token_tile"] == 2048
    tiny = linear_backend_report(cfg, "pallas_interpret", "pallas_interpret")
    assert tiny["heads_per_step"] == kda.heads_per_step(
        cfg.linear_num_heads, cfg.linear_head_dim, 4) > 1
    one = linear_backend_report(
        dataclasses.replace(cfg, linear_num_heads=1), "pallas", "pallas")
    assert (one["heads_per_step"], one["block_tokens"]) == (1, 64)
    assert tiny["qkv"]["heads_per_tile"] == ksc.heads_per_tile(
        cfg.linear_num_heads, cfg.linear_head_dim)
    # (beside what a slot holds of one layer, whatever runs the recurrence:
    # the state's geometry, the gate's granularity — ISSUE 58)
    assert linear_backend_report(solar, "xla", "xla") == {
        "decode": "xla", "prefill": "xla",
        "state_geometry": [64, 128, 128], "gate_per": "channel",
        "output_gate": "sigmoid",
        "slot_bytes_a_layer": 4 * 2**20 + 144 * 2**10}


@pytest.mark.parametrize("heads,d", [(4, 16), (8, 128)])
def test_decode_kernel_against_one_recurrence_step(heads, d):
    rng = np.random.RandomState(d)
    q, k, v, g, beta = _kda_inputs(rng, 3, heads, d, 1.0)
    pool = rng.randn(8, heads, d, d).astype(np.float32)
    read = np.array([1, 2, 0], np.int32)
    write = np.array([5, 2, 0], np.int32)  # another bank, and in place
    want_o, want_pool = kda.kda_decode_xla(
        jnp.asarray(pool), read, write, q, k, v, g, beta)
    o, got = kda.kda_decode_attention(
        jnp.asarray(pool), jnp.asarray(read), jnp.asarray(write),
        *(jnp.asarray(x) for x in (q, k, v, g, beta)), interpret=True)
    np.testing.assert_allclose(o, want_o, rtol=1e-5, atol=1e-6)
    kept = [1, 2, 3, 4, 5, 6, 7]  # (slot 0 is the padding row's)
    np.testing.assert_allclose(
        np.asarray(got)[kept], np.asarray(want_pool)[kept],
        rtol=1e-5, atol=1e-6)
    s, want = kda.kda_step(pool[1], q[0], k[0], v[0], g[0], beta[0])
    np.testing.assert_allclose(np.asarray(got)[5], s, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(o[0], want, rtol=1e-5, atol=1e-6)


def test_the_served_states_limit_tells_a_bfloat16_reference(served_once):
    """What chip_smoke.py holds the served state to: the slots of a served
    engine agree with the reference's float32 recurrence over the inputs
    the programs were fed; the reference with its state rounded to bfloat16
    at every token — the faulty reference logprobs cannot tell at published
    widths — does not."""
    model, *_, state_tap, pool = served_once
    good = reference_check.served_state(reference, model, state_tap, pool)
    bad = reference_check.served_state(
        _faulty(kda_state_dtype="bfloat16"), model, state_tap, pool)
    assert good["rows"] == 2 and good["tokens"] >= 70 + 33 + 2 * 7
    assert good["state_rel_err"] <= STATE_RTOL, good
    assert bad["state_rel_err"] > 1e-3, bad


def test_an_engine_that_rounds_its_state_is_outside_the_states_limit(
        cfg, kit, monkeypatch):
    """The other way round: an engine whose state is rounded to bfloat16 at
    every token (what slots or kernels kept in bfloat16 would do) against
    the float32 reference."""
    step = kda.kda_step

    def rounded(s, *xs):
        s, o = step(s, *xs)
        return s.astype(jnp.bfloat16).astype(jnp.float32), o

    monkeypatch.setattr(kda, "kda_step", rounded)
    model = ek.cut(cfg, 4)
    *_, state_tap, pool = _serve_tapped(kit, model, (70, 33), 8, seed=3)
    out = reference_check.served_state(reference, model, state_tap, pool)
    assert out["state_rel_err"] > 1e-3, out


def test_work_items_cover_every_row_once():
    """The kernel's work items: each row's tokens exactly once, a seam
    inside a block as two items, padding items that name resident blocks."""
    lens = jnp.asarray([70, 5, 0, 100], jnp.int32)
    off = jnp.cumsum(lens) - lens
    it = {k: np.asarray(v) for k, v in kda.work_items(off, lens, 192).items()}
    seen = np.zeros((4, 192), int)
    for row, blk, lo, hi in zip(it["row"], it["block"], it["lo"], it["hi"]):
        seen[row, blk * 64 + lo:blk * 64 + hi] += 1
    for r in range(4):
        want = np.zeros(192, int)
        want[int(off[r]):int(off[r] + lens[r])] = 1
        assert (seen[r] == want).all()
    assert it["first"].sum() == 3 and len(it["row"]) == 192 // 64 + 4
    assert (it["block"][1], it["lo"][1], it["hi"][1]) == (1, 0, 6)
    assert (it["block"][2], it["lo"][2], it["hi"][2]) == (1, 6, 11)


# -- the share ---------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(cfg):
    """The routed parts that ALL four shares give (4 of 16 experts each,
    as eight shares of 40 in the deployment) + the shared expert counted
    once = the uncut reference's layer, through the served `_moe_mlp` and
    through the reference alike."""
    uncut = dataclasses.replace(ek.cut(cfg, 1), num_experts_held=0,
                                first_expert_held=0)
    params = llama.init_params(uncut, jax.random.PRNGKey(2))
    moe = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, uncut.hidden_size))
    view = SolarOpen2CheckpointView(uncut, params)
    keys = reference.check_config(reference.published_keys(uncut))

    def run(fn, *a):
        with jax.default_matmul_precision("highest"):
            return fn(*a)

    def get(name):
        return reference.f32(view[name])

    whole, _ = reference.expert_layer(
        keys, get, "model.layers.0.", x[0], run)
    ref_parts = sum(
        reference.expert_layer(keys, get, "model.layers.0.", x[0], run,
                               held=(4 * s, 4), shared=False)[0]
        for s in range(4))
    shared = run(reference.swiglu, get, "model.layers.0.mlp.shared_expert.",
                 x[0])
    np.testing.assert_allclose(ref_parts + shared, whole, rtol=1e-5,
                               atol=1e-6)
    served_parts = 0
    for s in range(4):
        share = dataclasses.replace(
            uncut, num_experts_held=4, first_expert_held=4 * s)
        mp = {k: (v[4 * s:4 * s + 4] if k in ("gate", "up", "down") else v)
              for k, v in moe.items() if k != "shared"}
        served_parts = served_parts + llama._moe_mlp(share, mp, x)[0][0]
    np.testing.assert_allclose(served_parts + shared, whole, rtol=1e-5,
                               atol=2e-6)
    np.testing.assert_allclose(
        llama._moe_mlp(uncut, moe, x)[0][0], whole, rtol=1e-5, atol=2e-6)


def test_the_gate_multiplies_each_channel(cfg):
    """Zero gate weights give g = 1/2 on every head AND channel: the GQA
    block's output is exactly half the ungated model's; and the gate's leaf
    is (h, heads x D), not (h, heads)."""
    model = ek.cut(cfg, 1)
    p = llama.init_params(model, jax.random.PRNGKey(0))
    h, d = model.hidden_size, model.head_dim
    assert p["layers"]["attn"]["wg"].shape == (1, h, 4 * d)
    p["layers"]["attn"]["wg"] = jnp.zeros_like(p["layers"]["attn"]["wg"])
    ungated = dataclasses.replace(model, attn_output_gate="")
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, h))
    pos = jnp.arange(6)[None]

    def attend(q, k, v):
        return llama.masked_attention(
            q, k, v, llama.causal_page_mask(pos, jnp.array([6]), 6),
            scale=model.attn_scale)

    def block(c):
        lp = llama._layer_params(c, p, 0)
        lp["moe"] = jax.tree.map(jnp.zeros_like, lp["moe"])
        lp.pop("moe_stacked")
        return llama._layer_body(
            c, lp, x, pos, attend, spec=c.layer_specs[0]) - x

    np.testing.assert_allclose(
        block(model), 0.5 * block(ungated), rtol=1e-5, atol=1e-6)


def test_no_position_reaches_a_layer(cfg):
    """`use_rope` false: every layer's spec says no rope, and the GQA
    block's output for one token does not depend on the position it is
    given."""
    assert all(s.rope is None for s in cfg.layer_specs)
    assert [s.mixer for s in cfg.layer_specs] == (
        ["softmax"] + ["linear"] * 3) * 2
    model = ek.cut(cfg, 1)
    p = llama.init_params(model, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 1, model.hidden_size))

    def at(position):
        lp = llama._layer_params(model, p, 0)
        return llama._layer_body(
            model, lp, x, jnp.array([[position]]),
            lambda q, k, v: jnp.repeat(v, 2, axis=2),
            spec=model.layer_specs[0])

    np.testing.assert_array_equal(at(0), at(300))


# -- state slots --------------------------------------------------------------

def test_a_row_alone_among_fifteen_and_in_a_slot_just_left(cfg, kit):
    """The same prompt gives the same tokens alone, among 15 other rows
    (16 seats, every slot held), and in the slot another row has just left
    (one seat: the slot's bytes are the previous holder's until the chunk
    that holds the row's first token starts it from zeros)."""
    model = ek.cut(cfg, 4)
    prompt, others = ek.prompts(model, (90,))[0], ek.prompts(
        model, [20 + 7 * i for i in range(15)], seed=9)
    crowd, _ = kit.engine(
        ek.engine_config(model, seqs=16, num_blocks=512, **CHUNK))
    alone = ek.generate(crowd, [prompt])[0]
    assert ek.generate(crowd, [prompt] + others)[0] == alone
    one, _ = kit.engine(ek.engine_config(model, seqs=1, **CHUNK))
    assert one.scheduler.pool.state.num_usable == 1
    first = ek.generate(one, [others[3]])
    again = ek.generate(one, [prompt])[0]  # the one slot, just left
    assert again == alone
    assert first[0] == ek.generate(crowd, [others[3]])[0]


def test_preemption_gives_the_slot_back_and_the_same_tokens(cfg, kit):
    """A pool that runs out under both rows' growth preempts the newer one
    (`engine_kit.generate_squeezed`): it loses its pages AND its slot, is
    computed again from its first token, and ends with the tokens of a run
    that never preempted."""
    model = ek.cut(cfg, 4)
    prompts = ek.prompts(model, (60, 60), seed=4)
    engine, before = kit.engine(ek.engine_config(model, seqs=2, **CHUNK))
    want = ek.generate(engine, prompts, max_tokens=40)
    assert ek.since(engine, before)["state_recomputed_tokens_n"] == 0
    preempted = engine.scheduler.total_preemptions
    before = engine.flightrec.timing()
    got = ek.generate_squeezed(engine, prompts, max_tokens=40)
    assert engine.scheduler.total_preemptions > preempted
    assert got == want
    timing = ek.since(engine, before)
    assert timing["state_recomputed_tokens_n"] >= 60
    assert timing["state_slots_zeroed_n"] >= 3  # two rows, one again
    slots = engine.scheduler.pool.state
    assert slots.num_free == slots.num_usable == 2


def test_abort_gives_the_slot_back(cfg, kit):
    model = ek.cut(cfg, 4)
    config = ek.engine_config(model, seqs=2, **CHUNK)
    engine, _ = kit.engine(config)
    rid = engine.add_request(prompt_token_ids=ek.prompts(model, (70,))[0],
                             sampling=ek.greedy(200))
    for _ in range(4):
        engine.step()
    slots = engine.scheduler.pool.state
    assert slots.num_in_use == 1
    ek.finish(engine, rid)
    assert slots.num_in_use == 0
    # and the slot serves the next row: the same tokens as a fresh engine
    prompt = ek.prompts(model, (50,), seed=6)
    assert ek.generate(engine, prompt) == ek.generate(
        kit.fresh_engine(config), prompt)


def test_no_admission_without_a_free_slot(cfg):
    model = ek.cut(cfg, 4)
    sched = Scheduler(
        model, CacheConfig(block_size=8, num_blocks=64,
                           enable_prefix_caching=False),
        SchedulerConfig(max_num_seqs=2, max_num_batched_tokens=64,
                        decode_buckets=(2,), prefill_buckets=(64,)))
    held = [sched.pool.state.allocate(), sched.pool.state.allocate()]
    assert sched.pool.state.allocate() is None
    sched.add_request(Request(
        request_id="r", prompt_token_ids=list(range(1, 20)),
        sampling=SamplingParams(max_tokens=4)))
    assert sched.schedule() is None and sched.num_waiting == 1
    sched.pool.state.release(held[0])
    work = sched.schedule()
    assert work is not None and work.requests[0].state_slot == held[0]


@pytest.mark.parametrize("ends", [(3, 5, 9, 12), (1, 2, 30, 7)])
def test_a_rolled_back_window_leaves_the_state_it_read(cfg, kit, ends):
    """A row that meets a stop token in the middle of a decode window
    invalidates the window dispatched behind it: the device has run that
    window and advanced EVERY row's state, and the re-dispatch must start
    from the state before it — the other bank. The pipelined loop's tokens
    are the serial loop's."""
    model = ek.cut(cfg, 4)
    prompts = ek.prompts(model, (30, 41, 52, 23), seed=8)
    config = ek.engine_config(model, **CHUNK)
    engine, _ = kit.engine(config)
    free = ek.generate(engine, prompts, 40)
    # each row stops at the first appearance of the token it would emit at
    # index `end` (nothing tells the scheduler when)
    stops = [row[e] for row, e in zip(free, ends)]
    want = [row[:row.index(stop) + 1] for row, stop in zip(free, stops)]

    def run(engine):
        ids = [engine.add_request(
            prompt_token_ids=p, sampling=SamplingParams(
                max_tokens=40, temperature=0.0, stop_token_ids=(stop,)))
            for p, stop in zip(prompts, stops)]
        got = {i: [] for i in ids}
        while engine.has_unfinished():
            for out in engine.step():
                got[out.request_id].extend(out.new_token_ids)
        return [got[i] for i in ids]

    serial = run(kit.engine(config, serial=True)[0])
    free_run = engine.flightrec.timing()
    got = run(engine)
    assert ek.since(engine, free_run).get("rollback_n", 0) > 0
    assert got == serial == want


# -- bytes --------------------------------------------------------------------

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
    states = [c for c in caches if isinstance(c, dict)]
    assert len(states) == 6 and memory.state_pool_bytes(cfg, slots) == nbytes(
        states)
    assert memory.state_slot_bytes(cfg) == 6 * (
        4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)
    # the cut at published widths: 4 MiB of state + 144 KiB of tail a layer
    big = resolve_model_config("solar-open2-250b-ep8-8l")
    assert memory.state_slot_bytes(big) == 6 * (4 * 2**20 + 144 * 2**10)
    assert abs(memory.param_bytes(big) - 12.83e9) < 0.01e9
    assert abs(memory.param_bytes(
        resolve_model_config("solar-open2-250b")) / 2 - 250.29e9) < 0.01e9
    # pages of the two GQA layers only: 4 KiB a token and layer
    assert memory.kv_block_bytes(big, 32) == 2 * 32 * 4096
    # the pool is sized from what the state pools leave
    cache = CacheConfig(block_size=32, num_blocks=None,
                        enable_prefix_caching=False)
    free, held = (memory.derive_num_blocks(
        big, cache, ParallelConfig(), hbm_bytes=32 * 2**30, state_slots=n)
        for n in (0, 17))
    assert abs((free - held) - memory.state_pool_bytes(big, 17)
               / memory.kv_block_bytes(big, 32)) <= 1


# -- refusals -----------------------------------------------------------------

@pytest.mark.parametrize("edit,message", [
    (dict(cache=CacheConfig(block_size=8, num_blocks=64, num_host_blocks=8,
                            enable_prefix_caching=False)),
     "the host-RAM tier"),
    (dict(cache=CacheConfig(block_size=8, num_blocks=64,
                            remote_kv_url="tpukv://x:1",
                            enable_prefix_caching=False)),
     "the remote KV store"),
    (dict(kv_peer_fetch=True), "the peer-engine tier"),
    (dict(sched=dict(num_speculative_tokens=3)),
     "speculative decoding (--speculative-config ngram)"),
    (dict(sched=dict(num_speculative_tokens=3, speculative_method="draft",
                     draft_model="tiny-llama")),
     "speculative decoding (--speculative-config draft)"),
    (dict(parallel=ParallelConfig(tensor_parallel_size=2)), "a mesh of 2"),
    (dict(parallel=ParallelConfig(sequence_parallel_size=2)), "a mesh of 2"),
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
    model = dataclasses.replace(ek.cut(cfg, 4), **over)
    with pytest.raises(ValueError) as err:
        kit.fresh_engine(ek.engine_config(model, **CHUNK, **edit))
    assert message in str(err.value)
    assert "cannot be combined with layers that hold a recurrent state" in str(
        err.value)


def test_prefix_caching_is_turned_off_and_said(cfg, kit, caplog):
    """With prefix caching asked for (the flag's default) the engine runs
    without prefix hits and says so; a scheduler built on its own refuses."""
    import logging

    model = ek.cut(cfg, 4)
    cache = CacheConfig(block_size=8, num_blocks=64)
    with caplog.at_level(logging.INFO):
        engine = kit.fresh_engine(
            ek.engine_config(model, cache=cache, **CHUNK))
    assert not engine.config.cache.enable_prefix_caching
    assert not engine.scheduler.pool.enable_prefix_caching
    assert any("WITHOUT prefix hits" in r.getMessage()
               for r in caplog.records)
    with pytest.raises(ValueError, match="at its boundary"):
        Scheduler(model, cache, SchedulerConfig(
            max_num_seqs=2, decode_buckets=(2,)))


def test_embeddings_are_refused(cfg):
    with pytest.raises(NotImplementedError, match="recurrent state"):
        llama.embed_encode(cfg, {}, jnp.zeros((1, 4), jnp.int32),
                           jnp.array([4]))


# -- the key mapper -----------------------------------------------------------

_PUBLISHED = {
    "architectures": ["SolarOpen2ForCausalLM"], "model_type": "solar_open2",
    "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": list(range(0, 48, 4)), "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "n_routed_experts": 320, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}


def _config_dir(tmp_path, **override):
    hf = {**_PUBLISHED, **override}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    return str(tmp_path)


def test_the_published_keys_map_to_the_preset(tmp_path):
    got = resolve_model_config(_config_dir(tmp_path))
    want = resolve_model_config("solar-open2-250b")
    assert got.layers == want.layers
    for key in ("hidden_size", "num_heads", "num_kv_heads", "head_dim",
                "num_experts", "num_experts_per_tok", "moe_intermediate_size",
                "n_shared_experts", "scoring_func", "moe_selection_bias",
                "routed_scaling_factor", "norm_topk_prob", "attn_output_gate",
                "linear_num_heads", "linear_head_dim", "linear_gate_rank",
                "vocab_size", "rms_norm_eps", "tie_word_embeddings",
                "architecture"):
        assert getattr(got, key) == getattr(want, key), key
    assert got.max_model_len == 1048576
    # gqa_layers left out: every (gqa_interval + 1)-th layer
    hf = {k: v for k, v in _PUBLISHED.items() if k != "gqa_layers"}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    assert resolve_model_config(str(tmp_path)).layers == want.layers


@pytest.mark.parametrize("override,message", [
    ({"use_rope": True}, "use_rope"),
    ({"kda_use_full_proj": True}, "kda_use_full_proj"),
    ({"linear_attn_config": {**_PUBLISHED["linear_attn_config"],
                             "num_kv_heads": 8}},
     "linear_attn_config.num_kv_heads"),
    ({"first_k_dense_replace": 1}, "first_k_dense_replace > 0"),
    ({"linear_attn_config": {**_PUBLISHED["linear_attn_config"],
                             "short_conv_kernel_size": 3}},
     "short_conv_kernel_size != 4"),
    ({"use_gqa_gate": False}, "use_gqa_gate false"),
    ({"kda_allow_neg_eigval": False}, "kda_allow_neg_eigval false"),
])
def test_the_mapper_refuses_by_name(tmp_path, override, message):
    with pytest.raises(ValueError) as err:
        resolve_model_config(_config_dir(tmp_path, **override))
    assert message in str(err.value) and "not implemented" in str(err.value)


# -- names ---------------------------------------------------------------------

def test_timing_and_metrics_names_are_the_contract(cfg, kit):
    from vllm_production_stack_tpu.engine.metrics import EngineMetrics

    model = ek.cut(cfg, 4)
    engine, before = kit.engine(ek.engine_config(model, **CHUNK))
    rid = engine.add_request(prompt_token_ids=ek.prompts(model, (70,))[0],
                             sampling=ek.greedy(200))
    for _ in range(6):
        engine.step()
    timing = ek.since(engine, before)
    assert set(mc.DEBUG_TIMING_STATE_COUNTS) <= set(timing)
    assert timing["kda_prefill_tokens_n"] == 70
    assert timing["kda_prefill_segments_n"] == 2  # chunks of 64
    assert timing["state_slots_zeroed_n"] == 1
    assert timing["kda_decode_rows_n"] > 0
    stats = engine.stats()
    assert stats.state_slots_in_use == 1
    assert stats.state_bytes == 2 * memory.state_slot_bytes(model)
    pages = len(engine.scheduler.running[0].block_table) * (
        memory.kv_block_bytes(model, 8))
    assert stats.state_share == pytest.approx(
        stats.state_bytes / (stats.state_bytes + pages))
    metrics = EngineMetrics("tiny-solar-open2")
    text = metrics.render(stats).decode()
    for name in (mc.HBM_STATE_BYTES, mc.HBM_STATE_SLOTS_IN_USE,
                 mc.HBM_STATE_SHARE):
        assert name in mc.ALL_GAUGES and f"\n{name}{{" in text
    ek.finish(engine, rid)
    # a model without such layers serves none of the counts
    plain, _ = kit.engine(EngineConfig.tiny())
    ek.generate(plain, [[1, 2, 3]], 3)
    assert not set(mc.DEBUG_TIMING_STATE_COUNTS) & set(
        plain.flightrec.timing())
    assert plain.stats().state_bytes == 0
