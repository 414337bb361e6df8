"""The parts of a model whose every layer holds K/V pages AND a recurrent
state (Falcon-H1, ISSUE 50), each alone on the CPU: the SSD recurrence
against a NumPy loop, the chunked kernel against the token-by-token form
(seams, strong decay), B and C shared by a group's heads, the gate before the
grouped norm, the multiplier vector's spans, the memory arithmetic against the
arrays' bytes, every refusal by name, `LayerSpec` and `StateSlots` for a layer
that holds both kinds of cache, the reference's copy in the benchmark, the
names in the contract, and the benchmark's own tests (nothing else in tier-1
guards the manifest). The served engine against the reference is
tests/test_falcon_h1.py's."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import engine_kit as ek
from vllm_production_stack_tpu import metrics_contract as mc
from vllm_production_stack_tpu.engine import memory
from vllm_production_stack_tpu.engine.config import (
    CacheConfig, LayerSpec, LoRAConfig, ModelConfig, MupSpec, ParallelConfig,
    SchedulerConfig,
)
from vllm_production_stack_tpu.engine.kv_cache import StateSlots
from vllm_production_stack_tpu.engine.scheduler import Scheduler
from vllm_production_stack_tpu.models import llama, registry
from vllm_production_stack_tpu.models.registry import resolve_model_config
from vllm_production_stack_tpu.ops import ssd_scan as ssd
from vllm_production_stack_tpu.testing import reference_falcon_h1 as reference

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK = dict(chunk=64)


@pytest.fixture(scope="module")
def cfg():
    return resolve_model_config("tiny-falcon-h1")


# -- the recurrence ------------------------------------------------------------

def _inputs(rng, n, heads=4, groups=2, p=8, ns=16, dt_hi=0.2):
    f32 = np.float32
    return dict(
        x=rng.normal(size=(n, heads, p)).astype(f32),
        b=rng.normal(size=(n, groups, ns)).astype(f32),
        c=rng.normal(size=(n, groups, ns)).astype(f32),
        dt=rng.uniform(1e-3, dt_hi, size=(n, heads)).astype(f32),
        a=-rng.uniform(1.0, 16.0, size=(heads,)).astype(f32),
        d=rng.normal(size=(heads,)).astype(f32))


def _numpy_loop(s, x, b, c, dt, a, d):
    """The published recurrence, one head and token at a time, float64."""
    s = s.astype(np.float64).copy()
    heads, per_group = x.shape[1], x.shape[1] // b.shape[1]
    ys = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        for j in range(heads):
            g = j // per_group
            s[j] = np.exp(dt[t, j] * a[j]) * s[j] + np.outer(
                dt[t, j] * x[t, j], b[t, g])
            ys[t, j] = s[j] @ c[t, g] + d[j] * x[t, j]
    return s, ys


def test_ssd_step_against_a_numpy_loop():
    rng = np.random.default_rng(0)
    k = _inputs(rng, 9)
    s0 = rng.normal(size=(4, 8, 16)).astype(np.float32)
    want_s, want_y = _numpy_loop(s0, **k)
    s, ys = jnp.asarray(s0), []
    for t in range(9):
        s, y = ssd.ssd_step(s, k["x"][t], k["b"][t], k["c"][t], k["dt"][t],
                            jnp.asarray(k["a"]), jnp.asarray(k["d"]))
        ys.append(y)
    np.testing.assert_allclose(s, want_s, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.stack(ys), want_y, rtol=2e-5, atol=2e-5)
    # ... and the reference's own recurrence is the same function
    y_ref, s_ref = reference.ssd_recurrence(
        k["x"], k["b"], k["c"], k["dt"], jnp.asarray(s0),
        np.log(-k["a"]), k["d"])
    np.testing.assert_allclose(s_ref, want_s, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(y_ref, want_y, rtol=2e-5, atol=2e-5)


def _chunked_vs_loop(lens, n, dt_hi, seed=0):
    """The chunked kernel (interpreted) on a packed chunk against the
    token-by-token XLA form on the rows: (worst y error over the largest
    |y|, worst state error over the largest |s|, untouched slots kept)."""
    rng = np.random.default_rng(seed)
    k = {name: jnp.asarray(v) for name, v in _inputs(
        rng, n, dt_hi=dt_hi).items()}
    rows = len(lens)
    slots = rows + 1
    pool = jnp.asarray(rng.normal(size=(2 * slots, 4, 8, 16)), jnp.float32)
    read = jnp.arange(1, rows + 1, dtype=jnp.int32)
    write = read + slots
    fresh = jnp.asarray([i % 2 == 0 for i in range(rows)])
    lens_a = jnp.asarray(lens, jnp.int32)
    off = (jnp.cumsum(lens_a) - lens_a).astype(jnp.int32)
    y, p2 = ssd.ssd_prefill_scan(
        pool, read, write, fresh, k["x"], k["b"], k["c"], k["dt"], k["a"],
        k["d"], off, lens_a, interpret=True)
    idx = np.clip(np.asarray(off)[:, None] + np.arange(max(lens))[None],
                  0, n - 1)
    y_ref, p_ref = ssd.ssd_prefill_xla(
        pool, read, write, fresh, k["x"][idx], k["b"][idx], k["c"][idx],
        k["dt"][idx], k["a"], k["d"], lens_a)
    y_err = max(
        float(jnp.max(jnp.abs(y[o:o + n_row] - y_ref[i, :n_row]))
              / jnp.max(jnp.abs(y_ref[i, :n_row])))
        for i, (o, n_row) in enumerate(zip(np.asarray(off), lens)) if n_row)
    live = np.asarray(write)[[i for i, n_row in enumerate(lens) if n_row]]
    s_err = float(jnp.max(jnp.abs(p2[live] - p_ref[live]))
                  / jnp.max(jnp.abs(p_ref[live])))
    others = np.setdiff1d(np.arange(2 * slots), live)
    return y_err, s_err, bool(jnp.all(p2[others] == pool[others]))


@pytest.mark.parametrize("lens,n", [
    ((5, 130, 60, 0), 256),  # seams inside a block, a row over two blocks,
    #                          a row without tokens
    ((300,), 384),  # one row over three blocks: the state carried in VMEM
    ((1, 1, 1), 128)])
def test_chunked_kernel_is_the_recurrence_token_by_token(lens, n):
    """dt A down to -3.2 a token (the init's widest): 2.5e-5 measured on
    the 300-token row, where G reaches ~ -200 inside a block and an exponent
    formed as a difference of two such sums carries their rounding."""
    y_err, s_err, kept = _chunked_vs_loop(lens, n, dt_hi=0.2)
    assert y_err <= 1e-4 and s_err <= 1e-5 and kept, (y_err, s_err, kept)


def test_chunked_kernel_under_strong_decay():
    """dt A down to -128 a token: G reaches -16,000 inside a block. Nothing
    overflows — no exp(-G) is formed — and the state stays exact (each
    factor is a decay to the block's end); y carries the rounding of the
    differences of two large sums, 1e-3 measured."""
    y_err, s_err, kept = _chunked_vs_loop((5, 130, 60), 256, dt_hi=8.0)
    assert np.isfinite(y_err) and y_err <= 5e-3, y_err
    assert s_err <= 1e-4 and kept, s_err


def test_decode_kernel_is_one_recurrence_step():
    rng = np.random.default_rng(3)
    k = {name: jnp.asarray(v) for name, v in _inputs(rng, 5).items()}
    pool = jnp.asarray(rng.normal(size=(12, 4, 8, 16)), jnp.float32)
    read = jnp.asarray([1, 2, 3, 0, 0], jnp.int32)  # two padding rows
    write = jnp.asarray([7, 8, 9, 0, 0], jnp.int32)
    y, p2 = ssd.ssd_decode_step(pool, read, write, k["x"], k["b"], k["c"],
                                k["dt"], k["a"], k["d"], interpret=True)
    y_ref, p_ref = ssd.ssd_decode_xla(pool, read, write, k["x"], k["b"],
                                      k["c"], k["dt"], k["a"], k["d"])
    np.testing.assert_allclose(y[:3], y_ref[:3], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(p2[7:10], p_ref[7:10], rtol=1e-6, atol=1e-6)
    assert bool(jnp.all(p2[1:7] == pool[1:7]))  # what it read is untouched


def test_a_groups_heads_share_b_and_c():
    """Heads 0-1 read group 0's B and C, heads 2-3 group 1's: changing
    group 1's leaves heads 0-1 where they were and moves heads 2-3."""
    rng = np.random.default_rng(1)
    k = _inputs(rng, 1)
    s0 = jnp.zeros((4, 8, 16), jnp.float32)
    args = lambda b, c: (s0, k["x"][0], b, c, k["dt"][0],  # noqa: E731
                         jnp.asarray(k["a"]), jnp.asarray(k["d"]))
    s1, y1 = ssd.ssd_step(*args(k["b"][0], k["c"][0]))
    b2, c2 = k["b"][0].copy(), k["c"][0].copy()
    b2[1], c2[1] = b2[1] + 1.0, c2[1] - 1.0
    s2, y2 = ssd.ssd_step(*args(b2, c2))
    assert bool(jnp.all(s1[:2] == s2[:2])) and bool(jnp.all(y1[:2] == y2[:2]))
    assert not bool(jnp.allclose(s1[2:], s2[2:]))
    assert ssd.heads_per_step(32, 2) == 8 and ssd.heads_per_step(4, 2) == 2


class _Feed:
    """A recurrence that hands `_ssm_mixer` a given scan output and the
    plain convolution of a lone chunk."""

    tap = None

    def __init__(self, y):
        self.y = y

    def conv(self, x, taps):
        return x.astype(jnp.float32) * taps[-1].astype(jnp.float32)

    def scan_ssd(self, x, b, c, dt, a, d):
        self.fed = (x, b, c, dt)
        return self.y


def test_the_gate_comes_before_the_grouped_norm(cfg):
    """y * silu(z) FIRST, then RMSNorm over each group's 16 channels: the
    mixer's output is W_out of exactly that, and not of the norm-then-gate
    form nor of one norm over all 32 channels."""
    plain = dataclasses.replace(cfg, mup=None)
    sp = jax.tree.map(
        lambda a: a[0],
        llama.init_params(plain, jax.random.PRNGKey(2))["layers"]["attn_ssm"])
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.normal(size=(5, 1, 64)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(5, 4, 8)), jnp.float32)
    got = llama._ssm_mixer(plain, sp, u, _Feed(y))[:, 0]
    z = (u[:, 0] @ sp["w_in"])[:, :32]
    gated = y.reshape(5, 32) * jax.nn.silu(z)

    def norm(t, groups):
        t = t.reshape(5, groups, -1)
        t = t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True) + 1e-5)
        return t.reshape(5, 32) * sp["norm"]

    np.testing.assert_allclose(
        got, norm(gated, 2) @ sp["w_out"], rtol=1e-5, atol=1e-6)
    for other in (norm(y.reshape(5, 32), 2) * jax.nn.silu(z),
                  norm(gated, 1)):
        assert not np.allclose(got, other @ sp["w_out"], atol=1e-3)


def test_the_multiplier_vector_scales_its_five_spans(cfg):
    """`mup_vector`: ssm_multipliers[0..4] over z | x | B | C | dt of W_in's
    output, and `ssm_in` on its input — read off what reaches the scan and
    the gate, against the same mixer without multipliers."""
    mup = MupSpec(ssm_in=0.5, ssm=(2.0, 3.0, 5.0, 7.0, 0.25))
    scaled = dataclasses.replace(cfg, mup=mup)
    plain = dataclasses.replace(cfg, mup=None)
    sp = jax.tree.map(
        lambda a: a[0],
        llama.init_params(plain, jax.random.PRNGKey(2))["layers"]["attn_ssm"])
    # (conv bias 0 and a positive input, so that silu'(.) does not hide the
    # scale: compare the PRE-activation through the inverse where needed)
    sp = {**sp, "conv_bias": jnp.zeros_like(sp["conv_bias"]),
          "dt_bias": jnp.zeros_like(sp["dt_bias"])}
    u = jnp.asarray(np.random.default_rng(5).normal(size=(3, 1, 64)),
                    jnp.float32)
    y = jnp.ones((3, 4, 8), jnp.float32)
    p = u[:, 0] @ sp["w_in"]
    z, x, b, c, dt = jnp.split(p, [32, 64, 96, 128], axis=-1)
    feed = _Feed(y)
    llama._ssm_mixer(scaled, sp, u * mup.ssm_in, feed)
    tap = sp["conv"][-1]
    want = [jax.nn.silu(0.5 * m * t * w) for m, t, w in (
        (3.0, x, tap[:32]), (5.0, b, tap[32:64]), (7.0, c, tap[64:]))]
    for got, exp in zip(feed.fed[:3], want):
        np.testing.assert_allclose(got.reshape(3, -1), exp, rtol=2e-5,
                                   atol=1e-6)
    np.testing.assert_allclose(
        feed.fed[3], jax.nn.softplus(0.5 * 0.25 * dt), rtol=2e-5, atol=1e-6)
    # z's span: the gate
    out = llama._ssm_mixer(scaled, sp, u * mup.ssm_in, _Feed(y))[:, 0]
    gated = y.reshape(3, 32) * jax.nn.silu(0.5 * 2.0 * z)
    t = gated.reshape(3, 2, 16)
    t = (t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True) + 1e-5)
         ).reshape(3, 32) * sp["norm"]
    np.testing.assert_allclose(out, t @ sp["w_out"], rtol=2e-5, atol=1e-6)


def test_a_multiplier_of_one_is_no_operation_and_another_is_told(cfg):
    """`attention_in_multiplier` is published as 1 (the engine test's arms
    cannot tell it left out); at 2 the reference tells it."""
    from vllm_production_stack_tpu.testing.reference_check import (
        reference_tree)

    # (the reference reads (in, out) matrices: a view of the tree)
    params = reference_tree(llama.init_params(cfg, jax.random.PRNGKey(0)))
    keys = reference.published_keys(cfg)
    tokens = list(range(3, 30))
    base, _ = reference.forward(keys, params, tokens, [26])
    same, _ = reference.forward(keys, params, tokens, [26],
                                skip_multiplier="attention_in_multiplier")
    assert bool(jnp.all(base == same))
    doubled = {**keys, "attention_in_multiplier": 2.0}
    moved, _ = reference.forward(doubled, params, tokens, [26])
    left, _ = reference.forward(doubled, params, tokens, [26],
                                skip_multiplier="attention_in_multiplier")
    assert bool(jnp.all(left == base))
    assert float(jnp.max(jnp.abs(moved - base))) > 1e-3


def test_the_init_is_drawn_wider_by_each_multiplier(cfg):
    """Every matrix whose output a multiplier scales is drawn that much
    wider, so that the scaled activation is the standard init's: with the
    published multipliers (0.0375, 0.088 against an embedding times 5.66) a
    branch would otherwise vanish beside the residual."""
    big = dataclasses.replace(
        cfg, hidden_size=256, intermediate_size=512, vocab_size=1024)
    layers = llama.init_params(big, jax.random.PRNGKey(0))["layers"]
    mup = big.mup

    def std(a):
        return float(jnp.std(a.astype(jnp.float32)))

    h = big.hidden_size
    assert std(layers["attn"]["wo"]) == pytest.approx(
        (big.num_heads * big.head_dim) ** -0.5 / mup.attention_out, rel=0.05)
    assert std(layers["attn"]["wk"]) == pytest.approx(
        h ** -0.5 / mup.key, rel=0.05)
    assert std(layers["attn_ssm"]["w_out"]) == pytest.approx(
        big.ssm_inner ** -0.5 / mup.ssm_out, rel=0.05)
    assert std(layers["mlp"]["down"]) == pytest.approx(
        big.intermediate_size ** -0.5 / mup.mlp_down, rel=0.05)
    w_in = layers["attn_ssm"]["w_in"]
    assert std(w_in[..., :big.ssm_inner]) == pytest.approx(
        h ** -0.5 / (mup.ssm_in * mup.ssm[0]), rel=0.05)
    assert std(w_in[..., -big.ssm_num_heads:]) == pytest.approx(
        h ** -0.5 / (mup.ssm_in * mup.ssm[4]), rel=0.1)


# -- LayerSpec, StateSlots, bytes ---------------------------------------------

def test_a_layer_says_pages_and_state_independently(cfg):
    both = cfg.layer_specs[0]
    assert both.holds_pages and both.holds_state
    assert (both.mixer, both.recurrence, both.attn_kind) == (
        "softmax", "ssd", "full")
    kda = LayerSpec(mixer="linear")
    assert (kda.holds_pages, kda.holds_state, kda.recurrence) == (
        False, True, "delta")
    plain = LayerSpec()
    assert (plain.holds_pages, plain.holds_state) == (True, False)
    for bad in (dict(mixer="linear", recurrence="ssd"),
                dict(recurrence="delta"), dict(recurrence="scan")):
        with pytest.raises(ValueError, match="not a layer any program has"):
            LayerSpec(**bad)
    assert cfg.state_layers == cfg.page_layers == cfg.ssm_layers == (0, 1, 2)
    assert cfg.linear_layers == () and cfg.tree_by_kind
    solar = resolve_model_config("tiny-solar-open2")
    assert solar.state_layers == solar.linear_layers and not solar.ssm_layers
    assert set(solar.state_layers) | set(solar.page_layers) == set(range(8))
    # the geometry is the recurrence's
    assert cfg.state_geometry("ssd") == ((4, 8, 16), 32 + 2 * 2 * 16)
    assert solar.state_geometry("delta") == ((4, 16, 16), 3 * 64)


def test_one_seat_is_pages_of_every_layer_and_one_slot_of_every_layer(cfg):
    sched = Scheduler(
        cfg, CacheConfig(block_size=8, num_blocks=64,
                         enable_prefix_caching=False),
        SchedulerConfig(max_num_seqs=2, max_num_batched_tokens=64,
                        decode_buckets=(2,), prefill_buckets=(64,)))
    assert isinstance(sched.pool.state, StateSlots)
    assert sched.pool.state.num_usable == 2
    caches = llama.init_kv_cache(cfg, 64, 8, state_slots=3)
    for leaf in caches:
        assert leaf["kv"].shape == (2, 64, 8, 2, 16)
        assert leaf["s"].shape == (6, 4, 8, 16)  # 2 banks x 3 slots
        assert leaf["s"].dtype == jnp.float32
        assert leaf["conv"].shape == (6, 3, 96)  # taps - 1 rows of x|B|C
        assert llama.page_pool(leaf) is leaf["kv"]


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
    assert memory.state_pool_bytes(cfg, slots) == nbytes(
        [{k: v for k, v in c.items() if k != "kv"} for c in caches])
    assert memory.state_slot_bytes(cfg) == 3 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert memory.kv_block_bytes(cfg, 8) == 3 * 2 * 8 * 2 * 16 * 4
    # the published widths: 4 MiB of state + 30 KiB of tail a layer and
    # slot; 12 KiB of K and V a token over the cut's 6 layers
    cut = resolve_model_config("falcon-h1-34b-6l")
    assert memory.state_slot_bytes(cut) == 6 * (4 * 2**20 + 30 * 2**10)
    assert memory.kv_block_bytes(cut, 32) == 32 * 12 * 2**10
    assert abs(memory.param_bytes(cut) - 10.51e9) < 0.01e9
    full = resolve_model_config("falcon-h1-34b")
    shapes = jax.eval_shape(
        lambda k: llama.init_params(full, k), jax.random.PRNGKey(0))
    assert memory.param_bytes(full) == nbytes(shapes)
    # 72 layers of 430.1 M and 2.674 B of embedding and head
    assert abs(memory.param_bytes(full) / 2
               - (72 * 430.1e6 + 2.674e9)) < 0.005e9


# -- refusals -----------------------------------------------------------------

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


def test_prefix_caching_is_turned_off_and_said(cfg, kit, caplog):
    import logging

    cache = CacheConfig(block_size=8, num_blocks=64)
    with caplog.at_level(logging.INFO):
        engine = kit.fresh_engine(ek.engine_config(cfg, cache=cache, **CHUNK))
    assert not engine.config.cache.enable_prefix_caching
    assert any("WITHOUT prefix hits" in r.getMessage()
               for r in caplog.records)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        llama.embed_encode(cfg, {}, jnp.zeros((1, 4), jnp.int32),
                           jnp.array([4]))


def _config_dir(tmp_path, **override):
    hf = {**registry._FALCON_H1_34B, **override}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    return str(tmp_path)


def test_the_published_keys_map_to_the_preset(tmp_path):
    got = resolve_model_config(_config_dir(tmp_path))
    want = resolve_model_config("falcon-h1-34b")
    assert got.layers == want.layers and len(got.layers) == 72
    for key in ("hidden_size", "intermediate_size", "num_heads",
                "num_kv_heads", "head_dim", "vocab_size", "rms_norm_eps",
                "ssm_num_heads", "ssm_head_dim", "ssm_state_dim",
                "ssm_num_groups", "mup", "tie_word_embeddings",
                "architecture", "attention_bias"):
        assert getattr(got, key) == getattr(want, key), key
    assert got.max_model_len == 262144 and want.max_model_len == 32768
    assert got.mup.ssm == (0.3535533905932738, 0.25, 0.1767766952966369,
                           0.5, 0.3535533905932738)
    assert got.layer_specs[0].rope.theta == 1e11
    # the benchmark's configuration file states the same keys
    with open(os.path.join(
            REPO, "benchmarks/chip/configs/falcon-h1-34b-bf16-6l.json")) as f:
        bench = json.load(f)
    for key, value in registry._FALCON_H1_34B.items():
        if key not in bench["reduced"]:
            assert bench[key] == value, key
    assert resolve_model_config(bench["model"]).num_layers == bench[
        "num_hidden_layers"] == 6


@pytest.mark.parametrize("override,message", [
    ({"attn_layer_indices": [0, 2]}, "attn_layer_indices"),
    ({"mamba_use_mlp": False}, "mamba_use_mlp false"),
    ({"rope_scaling": {"rope_type": "linear", "factor": 2.0}},
     "rope_scaling"),
    ({"attention_bias": True}, "attention_bias true"),
    ({"mlp_bias": True}, "mlp_bias true"),
    ({"projectors_bias": True}, "projectors_bias true"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias true"),
    ({"mamba_conv_bias": False}, "mamba_conv_bias false"),
    ({"mamba_norm_before_gate": True}, "mamba_norm_before_gate true"),
    ({"mamba_rms_norm": False}, "mamba_rms_norm false"),
    ({"mamba_d_conv": 3}, "mamba_d_conv != 4"),
    ({"mamba_d_ssm": 4000}, "mamba_n_heads x mamba_d_head != mamba_d_ssm"),
])
def test_the_mapper_refuses_by_name(tmp_path, override, message):
    with pytest.raises(ValueError) as err:
        resolve_model_config(_config_dir(tmp_path, **override))
    assert message in str(err.value) and "not implemented" in str(err.value)


# -- names ---------------------------------------------------------------------

def test_timing_and_metrics_names_are_the_contract(cfg, kit):
    from vllm_production_stack_tpu.engine.metrics import EngineMetrics

    engine, before = kit.engine(ek.engine_config(cfg, **CHUNK))
    rid = engine.add_request(prompt_token_ids=ek.prompts(cfg, (70,))[0],
                             sampling=ek.greedy(200))
    for _ in range(6):
        engine.step()
    timing = ek.since(engine, before)
    assert set(mc.DEBUG_TIMING_SSD_COUNTS) <= set(timing)
    assert not {"kda_prefill_tokens_n", "kda_layers_n"} & set(timing)
    assert timing["ssd_prefill_tokens_n"] == 70
    assert timing["ssd_prefill_segments_n"] == 2  # chunks of 64
    assert timing["state_slots_zeroed_n"] == 1
    assert timing["ssd_decode_rows_n"] > 0
    assert timing["ssd_layers_n"] == timing["page_and_state_layers_n"] > 0
    stats = engine.stats()
    assert stats.state_slots_in_use == 1
    assert stats.state_bytes == 2 * memory.state_slot_bytes(cfg)
    pages = len(engine.scheduler.running[0].block_table) * (
        memory.kv_block_bytes(cfg, 8))
    assert stats.state_share == pytest.approx(
        stats.state_bytes / (stats.state_bytes + pages))
    text = EngineMetrics("tiny-falcon-h1").render(stats).decode()
    for name in (mc.HBM_STATE_BYTES, mc.HBM_STATE_SLOTS_IN_USE,
                 mc.HBM_STATE_SHARE):
        assert f"\n{name}{{" in text
    ek.finish(engine, rid)
    # a KDA model serves none of the SSD counts
    solar, _ = kit.engine(ek.engine_config(
        ek.cut(resolve_model_config("tiny-solar-open2"), 4), **CHUNK))
    ek.generate(solar, [[1, 2, 3]], 3)
    assert not set(mc.DEBUG_TIMING_SSD_COUNTS) & set(solar.flightrec.timing())


def test_the_benchmarks_own_tests_pass():
    """Nothing else in tier-1 runs benchmarks/chip/tests: the manifest, the
    data files' cross-references and the cost functions this PR extends
    (PERF.md §7, PR 48's open item). Every case runs: since PR 57 they find
    their entries by name, so a PR that appends a metric deselects none."""
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "benchmarks/chip/tests", "-q",
         "-p", "no:cacheprovider"],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=60)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    assert "deselected" not in done.stdout, done.stdout[-500:]


def test_the_captures_thirteen_stand_together_and_only_dsa_follows():
    """PR 52's thirteen by name: in the manifest, together and in their
    order, with cells that exist, their fields, the glue lists at the four
    cells of the state and window models or more, the moe_route lists at
    the six MoE cells or more. (Nothing about what follows them: a PR
    appends at the END of `per_layer`, and
    benchmarks/chip/tests/test_append_safe.py guards that property.)"""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    thirteen = (
        "chunk_device_ms_per_ktok", "window_device_ms",
        "chunk_proj_ms_per_ktok", "chunk_mlp_ms_per_ktok",
        "chunk_ends_ms_per_ktok", "chunk_glue_ms_per_ktok",
        "chunk_moe_route_ms_per_ktok", "window_proj_ms", "window_mlp_ms",
        "window_ends_ms", "window_glue_ms", "window_moe_route_ms",
        "capture_unscoped_share")
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(thirteen[0])
    assert tuple(names[at:at + 13]) == thirteen
    cells = {w["name"] for w in manifest["workloads"]}
    mine = manifest["per_layer"][at:at + 13]
    for m in mine:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
        assert m["source"] == "device_trace" and m["better"] == "lower"
        assert m["moves"] == "out_tok_per_s"
        assert m["unit"] == ("%" if m["name"].endswith("_share") else "ms")
    glue = [m["workloads"] for m in mine if "glue" in m["name"]]
    moe = [m["workloads"] for m in mine if "moe_route" in m["name"]]
    assert len(glue) == len(moe) == 2
    assert all(len(w) >= 4 for w in glue)
    assert all(len(w) >= 6 for w in moe)
