"""The packed prefill chunk (models/llama.py:forward with `pack`) against the
row-major one it replaced in the served prefill: the rows' tokens end to end
on one axis for everything that is linear in tokens, rows only where
attention and the KV write need them.

The two layouts run the same per-token math at other matmul heights, so on
the CPU (float32) they agree to a few float32 ulps — far inside one bf16 ulp
— and exactly in every discrete result: sampled tokens, routed experts."""

import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_production_stack_tpu.engine.config import (
    EngineConfig, LoRAConfig, ModelConfig, ParallelConfig,
)
from vllm_production_stack_tpu.engine.engine import LLMEngine
from vllm_production_stack_tpu.engine.request import SamplingParams
from vllm_production_stack_tpu.engine.model_runner import ModelRunner
from vllm_production_stack_tpu.models import llama
from vllm_production_stack_tpu.models.registry import PRESETS
from vllm_production_stack_tpu.testing.serial_loop import serial_engine

BS = 8  # tokens a page
BUCKETS = (8, 16, 32, 64)
ATOL = 2e-5  # float32 sums in another order; one bf16 ulp is 4e-3


def _bucket(n: int) -> int:
    return next(b for b in BUCKETS if n >= 1 and n <= b)


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


class Chunk:
    """A prefill dispatch at toy size: row i holds `hist[i]` resident tokens
    (written by a row-major forward, the same pool for both layouts) and
    computes the next `lens[i]`."""

    def __init__(self, cfg, hist, lens, seed=0, lora=None, lora_rows=None):
        self.cfg, self.hist, self.lens = cfg, hist, lens
        self.b, self.b_pad = len(lens), _pow2(len(lens))
        rng = np.random.default_rng(seed)
        self.params = llama.init_params(cfg, jax.random.PRNGKey(seed))
        self.toks = [
            rng.integers(1, cfg.vocab_size, size=h + n)
            for h, n in zip(hist, lens)
        ]
        pages = [-(-(h + n) // BS) for h, n in zip(hist, lens)]
        self.nb = _pow2(max(pages))
        self.tables = np.zeros((self.b_pad, self.nb), np.int32)
        nxt = 1  # page 0 is the null page
        for i, p in enumerate(pages):
            self.tables[i, :p] = np.arange(nxt, nxt + p)
            nxt += p
        self.lora, self.lora_rows = lora, lora_rows
        kv = llama.init_kv_cache(cfg, nxt + 1, BS)
        if any(hist):
            _, kv, _ = self.run(kv, [0] * self.b, hist, packed=False)
        self.kv = kv

    def _per_row(self, starts, lens, t_pad):
        nbw = (t_pad - 1) // BS + 2
        ctx = np.zeros(self.b_pad, np.int32)
        cl = np.zeros(self.b_pad, np.int32)
        ids = np.zeros((self.b_pad, nbw), np.int32)
        off = np.zeros(self.b_pad, np.int32)
        for i, (s, n) in enumerate(zip(starts, lens)):
            if not n:
                continue
            ctx[i], cl[i], off[i] = s + n, n, s % BS
            first = s // BS
            span = (s + n - 1) // BS - first + 1
            ids[i, :span] = self.tables[i, first:first + span]
        return ctx, cl, ids, off

    def run(self, kv, starts, lens, packed, backend="xla"):
        """-> (hidden of every real token, rows end to end; pool; routing)"""
        t_pad = _bucket(sum(lens) if packed else max(lens))
        ctx, cl, ids, off = self._per_row(starts, lens, t_pad)
        shape = (t_pad,) if packed else (self.b_pad, t_pad)
        tok, pos = np.zeros(shape, np.int32), np.zeros(shape, np.int32)
        at = 0
        for i, (s, n) in enumerate(zip(starts, lens)):
            where = slice(at, at + n) if packed else (i, slice(n))
            tok[where] = self.toks[i][s:s + n]
            pos[where] = np.arange(s, s + n)
            at += n
        pack = lora_idx = None
        if packed:
            pack = llama.pack_index(jnp.asarray(cl), jnp.asarray(ctx), t_pad)
        if self.lora is not None:
            lora_idx = np.zeros(self.b_pad, np.int32)
            lora_idx[:self.b] = self.lora_rows
            lora_idx = jnp.asarray(lora_idx)
            if packed:
                lora_idx = lora_idx[pack["tok_row"]]
        stats = [] if self.cfg.num_experts else None
        hidden, kv = llama.forward(
            self.cfg, self.params, jnp.asarray(tok), jnp.asarray(pos), kv,
            jnp.asarray(self.tables), jnp.zeros((1,), jnp.int32),
            jnp.asarray(ctx), lora=self.lora, lora_idx=lora_idx,
            write_blocks={"ids": jnp.asarray(ids),
                          "start_off": jnp.asarray(off),
                          "chunk_lens": jnp.asarray(cl)},
            backend=backend, moe_stats=stats, pack=pack,
        )

        def real(a):  # (N, 1, ..) or (B, T, ..) -> the real tokens' entries
            a = np.asarray(a)
            if packed:
                return a[:sum(lens), 0]
            return np.concatenate([a[i, :n] for i, n in enumerate(lens)])

        experts = [real(s["experts"]) for s in stats or ()]
        return real(hidden), kv, experts

    def both(self, backend):
        rows = self.run(self.kv, self.hist, self.lens, False, backend)
        pack = self.run(self.kv, self.hist, self.lens, True, backend)
        return rows, pack


def _assert_same(chunk, rows, pack):
    (h_rows, kv_rows, ex_rows), (h_pack, kv_pack, ex_pack) = rows, pack
    np.testing.assert_allclose(h_pack, h_rows, rtol=0, atol=ATOL)
    for a, b in zip(kv_pack, kv_rows):
        # the whole pool: what the chunk wrote, and nothing else touched
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=ATOL)
    # every row's sampled (greedy) token, at its last chunk token
    last = np.cumsum(chunk.lens) - 1
    tok = [
        np.asarray(jnp.argmax(llama.compute_logits(
            chunk.cfg, chunk.params, jnp.asarray(h[last])), axis=-1))
        for h in (h_rows, h_pack)
    ]
    np.testing.assert_array_equal(tok[0], tok[1])
    assert len(ex_rows) == len(ex_pack)
    for a, b in zip(ex_rows, ex_pack):
        np.testing.assert_array_equal(np.sort(a, -1), np.sort(b, -1))


# rows x (resident history, chunk length): first chunks, chunks that start
# mid-page behind resident history, page-aligned continuations, a one-token row
LAYOUTS = {
    1: ([11], [21]),
    2: ([0, 19], [13, 5]),
    3: ([0, 11, 8], [13, 5, 9]),
    5: ([3, 0, 16, 9, 0], [7, 12, 1, 10, 6]),
}


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("rows", sorted(LAYOUTS))
def test_packed_chunk_matches_row_major(rows, backend):
    hist, lens = LAYOUTS[rows]
    chunk = Chunk(ModelConfig.tiny(), hist, lens, seed=rows)
    _assert_same(chunk, *chunk.both(backend))


MODELS = {
    "qk_norm": ModelConfig.tiny(architecture="qwen3", qk_norm=True),
    "qkv_bias_gqa": ModelConfig.tiny(
        architecture="qwen2", attention_bias=True, num_heads=4,
        num_kv_heads=1),
    "olmoe": ModelConfig(model="tiny-olmoe", **PRESETS["tiny-olmoe"]),
    "sliding_softcap": ModelConfig(
        model="tiny-gemma2", **{
            **PRESETS["tiny-gemma"], "architecture": "gemma2",
            "sandwich_norms": True, "attn_logit_softcap": 50.0,
            "sliding_window": 8, "sliding_window_pattern": 2}),
}


@pytest.mark.parametrize("model,backend", [
    (m, b) for m in sorted(MODELS) for b in ("xla", "pallas_interpret")
    # sliding-window and softcapped models take the XLA attention
    if (m, b) != ("sliding_softcap", "pallas_interpret")
])
def test_packed_chunk_matches_row_major_per_architecture(model, backend):
    hist, lens = LAYOUTS[3]
    chunk = Chunk(MODELS[model], hist, lens, seed=7)
    rows, pack = chunk.both(backend)
    if model == "olmoe":
        assert len(pack[2]) == chunk.cfg.num_layers  # routing was compared
    _assert_same(chunk, rows, pack)


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_packed_chunk_lora_two_adapters_and_a_bare_row(backend):
    cfg = ModelConfig.tiny()
    lora_cfg = LoRAConfig(max_loras=2, max_lora_rank=4)
    tree = llama.init_lora_params(cfg, lora_cfg)
    key = jax.random.PRNGKey(3)
    for name, mod in tree.items():
        if name == "scale":
            continue
        for leaf in ("A", "B"):
            key, sub = jax.random.split(key)
            # slot 0 stays the all-zeros base adapter
            mod[leaf] = mod[leaf].at[1:].set(
                0.2 * jax.random.normal(sub, mod[leaf][1:].shape))
    tree["scale"] = jnp.asarray([0.0, 2.0, 0.5], jnp.float32)
    hist, lens = LAYOUTS[3]
    chunk = Chunk(cfg, hist, lens, seed=5, lora=tree, lora_rows=[2, 0, 1])
    rows, pack = chunk.both(backend)
    _assert_same(chunk, rows, pack)
    bare = Chunk(cfg, hist, lens, seed=5)
    h_bare = bare.run(bare.kv, hist, lens, True, backend)[0]
    # the adapters did something, and not to the bare row
    assert np.abs(pack[0][:lens[0]] - h_bare[:lens[0]]).max() > 1e-3
    np.testing.assert_allclose(
        pack[0][lens[0]:lens[0] + lens[1]],
        h_bare[lens[0]:lens[0] + lens[1]], rtol=0, atol=ATOL)


def test_pack_index_rows_offsets_and_padding():
    idx = llama.pack_index(
        jnp.asarray([3, 0, 2, 0]), jnp.asarray([10, 0, 2, 0]), 8)
    assert idx["row_off"].tolist() == [0, 3, 3, 5]
    # an empty row owns no token; padding tokens clamp onto the last row
    assert idx["tok_row"].tolist() == [0, 0, 0, 2, 2, 3, 3, 3]
    assert idx["chunk_start"].tolist() == [7, 0, 0, 0]


# -- the engine ------------------------------------------------------------


def _greedy(n=12):
    return SamplingParams(temperature=0.0, max_tokens=n, ignore_eos=True)


def _prompts():
    rng = np.random.default_rng(11)
    return [list(map(int, rng.integers(1, 500, size=n))) for n in (37, 9, 21)]


def test_burst_of_mixed_lengths_streams_equal_one_at_a_time():
    """Three requests admitted together share packed dispatches of two and
    three rows (and a chunk boundary mid-prompt); each alone is a one-row
    dispatch, its own row view, through the serial reference loop."""
    prompts = _prompts()
    eng = LLMEngine(EngineConfig.tiny())
    eng.runner.fallback_enabled = False  # exact programs, no pad-up
    try:
        burst = eng.generate(prompts, _greedy())
        timing = eng.flightrec.timing()
    finally:
        eng.runner.shutdown(wait=True)
    ref = serial_engine(EngineConfig.tiny())
    try:
        alone = [ref.generate([p], _greedy())[0] for p in prompts]
    finally:
        ref.runner.shutdown(wait=True)
    assert [o["token_ids"] for o in burst] == [o["token_ids"] for o in alone]
    # 67 prompt tokens in two dispatches (64-token budget): 64 + 16
    # positions, where rows x bucket computed 4 x 64 + 1 x 16
    assert timing["prefill_tokens"] == sum(map(len, prompts))
    assert timing["prefill_positions"] == 64 + 16
    assert timing["prefill_n"] == 2


def test_dispatch_is_packed_on_one_replica_and_row_major_under_dp():
    one = ModelRunner(EngineConfig.tiny())
    tok, pos, tables = one._prefill_avals(4, 32, 8)[:3]
    assert tok.shape == pos.shape == (32,) and tables.shape == (4, 8)
    cfg = EngineConfig.tiny()
    two = ModelRunner(cfg.replace(
        parallel=ParallelConfig(data_parallel_size=2)))
    tok, pos, tables = two._prefill_avals(4, 32, 8)[:3]
    assert tok.shape == pos.shape == (4, 32)


def test_full_warmup_compiles_every_rows_x_bucket_program():
    """The fine ladder's mixed-length waves land each (pow2 rows, bucket)
    prefill program by the SUM of the wave's rows."""
    from vllm_production_stack_tpu.engine.config import (
        CacheConfig, SchedulerConfig,
    )

    eng = LLMEngine(EngineConfig(
        model=ModelConfig.tiny(),
        cache=CacheConfig(block_size=8, num_blocks=64),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=32, decode_buckets=(4,),
            prefill_buckets=(16, 32), decode_window=2),
    ))
    try:
        eng.warmup()
        have = {k[1:3] for k in eng.runner._compiled_keys
                if k[0] == "prefill" and not any(k[4:])}
    finally:
        eng.runner.shutdown(wait=True)
    assert have >= {(r, t) for r in (1, 2, 4) for t in (16, 32)}


def _ops(lowered) -> collections.Counter:
    """The StableHLO ops of a lowered program, counted by name."""
    return collections.Counter(
        re.findall(r"stablehlo\.([a-z_]+)", lowered.as_text()))


def _lower_prefill(r: ModelRunner, b: int, t: int, nb: int = 8):
    return r._step_fn.lower(
        r._aval_tree(r.params), None, r._aval_tree(r.kv_caches),
        *r._prefill_avals(b, t, nb), want_logprobs=False,
        want_min_tokens=False, want_grammar=False)


def _lower_verify(r: ModelRunner, b: int, t: int = 4, nb: int = 8):
    i32 = jnp.int32
    s = jax.ShapeDtypeStruct
    nbw = (t - 1) // r.config.cache.block_size + 2
    return r._verify_fn.lower(
        r._aval_tree(r.params), None, r._aval_tree(r.kv_caches),
        s((b, t), i32), s((b, t), i32), s((b, nb), i32), s((b,), i32),
        s((b,), i32), s((b, nbw), i32), s((b,), i32), None)


@pytest.mark.parametrize("program", ["prefill", "verify"])
def test_row_major_programs_hold_nothing_of_the_pack(program):
    """The speculative verify programs and a dp > 1 prefill (lowered on a
    dp = 2 mesh, rows sharded) stay row-major: no pack index (its
    cumulative sum is the one `reduce_window` of a packed program) and no
    row view — which ops a row-major program holds does not depend on its
    row count, while a packed program gathers more once it has rows to
    tell apart."""
    cfg = EngineConfig.tiny()
    if program == "verify":
        r = ModelRunner(cfg.replace(scheduler=dataclasses.replace(
            cfg.scheduler, num_speculative_tokens=3)))
        few, many = _lower_verify(r, 2), _lower_verify(r, 4)
    else:
        r = ModelRunner(cfg.replace(
            parallel=ParallelConfig(data_parallel_size=2)))
        assert not r.prefill_packed
        few, many = _lower_prefill(r, 2, 16), _lower_prefill(r, 4, 16)
    for lowered in (few, many):
        assert "cumsum" not in lowered.as_text()
        assert _ops(lowered)["reduce_window"] == 0
    assert _ops(few) == _ops(many)
    # the detector detects: the packed program of the same model
    one = ModelRunner(cfg)
    lone = _ops(_lower_prefill(one, 1, 16))
    rows = _ops(_lower_prefill(one, 4, 16))
    assert lone["reduce_window"] == rows["reduce_window"] == 1
    assert rows["gather"] > lone["gather"]
    if program == "prefill":
        # a lone packed row is its own view: the row-major gathers, no more
        assert lone["gather"] == _ops(many)["gather"]
