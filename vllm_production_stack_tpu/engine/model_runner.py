"""Model runner: jitted, bucketed device steps over the paged pool.

This is the layer the reference never has to build (its engines are external
vLLM images). Responsibilities:

- hold params + the device KV pool, sharded over the (dp, tp) mesh;
- compile ONE prefill step per (chunk-bucket) and ONE decode step per
  (batch-bucket) — static shapes so XLA compiles a handful of programs total
  (SURVEY §7.3 hard part 1: shape bucketing vs recompilation);
- fuse forward + logits + sampling into a single jit so the only per-step
  host transfer is the sampled token ids;
- donate the KV pool into each step so updates are in-place in HBM.

Work items arrive as logical (unpadded) batches from the scheduler; padding
rows write to the reserved null page (block 0) and their samples are dropped.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from collections.abc import Sequence
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import metrics_contract as mc
from ..models import llama
from ..ops import kda_attention, kda_short_conv, ssd_scan
from ..ops.attention import gather_pages
from ..ops.paged_attention_pallas import _pages_fill_tiles
from ..utils.logging import init_logger
from ..parallel import mesh as mesh_lib
from ..parallel.sharding import (
    kv_cache_spec,
    llama_param_specs,
    lora_param_specs,
)
from .compile_watch import CompileWatch, program_memory_bytes
from .flightrec import FlightRecorder
from .config import STATE_CONV_TAPS, EngineConfig
from .sampling import (
    SUPPRESS_IDS, apply_grammar_mask, greedy_argmax, sample,
    suppress_stop_tokens,
)
from .scheduler import DecodeWork, PrefillWork, ScheduleOutput, VerifyWork

logger = init_logger(__name__)

# top-N alternatives collected when a batch contains logprobs requests —
# static (one extra compiled variant per program, lazily); requests asking
# for more are rejected at the API layer
LOGPROBS_TOPN = 8


def resolve_auto_attention_backend(
    *, block_size: int, max_model_len: int, mesh_size: int,
    kv_quantized: bool, platform: str,
) -> str:
    """The 'auto' decode-attention choice as a pure predicate of the
    static engine config (testable without a device). Derived from the
    v5e sweep of the kernel as it was before PR 25 (one grid step per table
    slot): it won at >=32-token pages in the LONG-context regime (ctx ~4k:
    -7% to -19%) and at ~1k contexts the outcome was batch-dependent — the
    gate keys on max_model_len because batch varies at runtime while the
    program is compiled per config, a deliberately conservative trade. The
    sweep of the kernel as it is now (ModelRunner._resolve_attention_backend)
    says the gate can widen; it has not been (ROADMAP D3). Single-device
    unquantized pools on a real TPU only for 'auto' (no GSPMD partition
    rule; Mosaic-compiled); explicit 'pallas' also supports fp8 pools."""
    if (
        block_size >= 32
        and max_model_len >= 4096
        and mesh_size == 1
        and not kv_quantized
        and platform == "tpu"
    ):
        return "pallas"
    return "xla"


def resolve_auto_prefill_backend(
    *, block_size: int, num_kv_heads: int, head_dim: int, kv_itemsize: int,
    kv_quantized: bool, mesh_size: int, platform: str,
    latent_pages: bool = False,
) -> str:
    """The 'auto' PREFILL-attention choice, independent of decode, as a pure
    predicate of static shapes: the paged flash-prefill kernel
    (ops/paged_attention_pallas.py:paged_prefill_attention) walks the pages
    a row holds and never materializes the gathered (B, S, kvH, D) history,
    the (B, heads, T, S) scores or the (B, T, S) mask.

    The kernel where it was measured to win: a pool whose pages fill whole
    HBM tiles (_pages_fill_tiles: D = 128 with 2, 4, 8 or 16 bf16 kv heads do;
    the sweep has the three last),
    unquantized, >= 32-token pages, one device, a real TPU. XLA everywhere
    else until swept: fp8 pools, D = 64 or one-head tp shards (their pages
    the kernel cannot slice out of HBM), 16-token pages, meshes over one
    device. Sliding-window and softcapped models never reach here
    (ModelRunner._resolve_prefill_backend): the kernel has neither.

    benchmarks/sweep_attention.py --prefill on a v5e chip (PR 28; one
    layer, a 512-token bucket, 32-token pages, bf16; ms, kernel / XLA):

    rows x tokens held (table)   32/8 heads      28/4 heads      16/16 heads
     1 x 128   ( 64 pages)        0.040 / 0.646   0.037 / 0.171   0.036 / 0.136
     1 x 512   ( 64 pages)        0.105 / 0.645   0.091 / 0.171   0.068 / 0.137
     1 x 1024  ( 64 pages)        0.200 / 0.649   0.164 / 0.172   0.123 / 0.137
     1 x 2048  ( 64 pages)        0.388 / 0.645   0.314 / 0.169   0.234 / 0.137
     2 x 512   ( 64 pages)        0.191 / 0.839   0.165 / 0.711   0.117 / 0.741
     4 x 512   ( 64 pages)        0.372 / 1.658   0.320 / 1.421   0.218 / 1.755
     4 x 2048  ( 64 pages)        1.485 / 1.659   1.209 / 1.421   0.878 / 1.762
    16 x 512   ( 64 pages)        1.905 / 9.505   1.460 / 6.122   0.837 / 8.690
     1 x 512   (128 pages)        0.105 / 1.360   0.090 / 0.356   0.068 / 0.246
     1 x 2048  (128 pages)        0.387 / 1.358   0.317 / 0.356   0.236 / 0.247

    The kernel's time follows what the rows hold, XLA's the table's width
    and the row bucket: the kernel wins every program of two rows or more
    (4-10x at 512 tokens a row), every one-row program up to ~1,000 resident
    tokens, and at 32 heads all of them; a lone row whose 2,048 tokens fill
    a 64-page table is the one place XLA wins (28/4 and 16/16 heads, 1.7-1.9x:
    the kernel's f32 softmax arithmetic, not its bytes), and in the
    128-page table that row is a tie.

    `latent_pages`: the pool holds one latent a token (MLA) and the kernel
    is ops/mla_attention_pallas.py's — a page is (block_size, latent_width),
    whole lane tiles by construction and whole sublane tiles at the page
    sizes the gate admits; it beat both XLA forms at 2k, 8k and 16k of
    context (benchmarks/sweep_mla.py, PERF.md §6 PR 31).
    """
    if (
        (latent_pages
         or _pages_fill_tiles(num_kv_heads, head_dim, kv_itemsize))
        and not kv_quantized
        and block_size >= 32
        and mesh_size == 1
        and platform == "tpu"
    ):
        return "pallas"
    return "xla"


def _refuse_unbuilt_for_latent_attention(config: EngineConfig) -> None:
    """A model with latent layers (LayerSpec.latent: every layer,
    models/llama.py:_init_params_mla, or some, the "attn_latent" stack of
    _init_params_by_kind) runs on ONE device, unquantized, without
    adapters: what has no layout for its leaves (attn.wq — or, with a
    query latent, attn.wq_a, q_a_norm, wq_b — wkv_a, kv_a_norm, wk_b, wv_b, wo;
    the attn_index stack of the layers that own an indexer) or its latent
    pool is refused by name at start, never guessed."""
    cfg, name = config.model, config.model.model
    if config.parallel.world_size > 1:
        raise ValueError(
            f"{name}: tensor/data/pipeline/sequence/expert parallelism is not "
            "implemented for multi-head latent attention — parallel/"
            "sharding.py has no spec for its leaves (attn.wq, wkv_a, "
            "kv_a_norm, wk_b, wv_b; moe.bias, moe.shared; the mlp/moe stacks of "
            "unequal depth) nor for the one-latent-per-token pool, which has "
            "no kv-head axis to shard"
        )
    if cfg.quantization:
        raise ValueError(
            f"{name}: --quantization {cfg.quantization} is not implemented "
            "for multi-head latent attention — models/quantization.py names "
            "no int8 form for attn.wkv_a / attn.wk_b / attn.wv_b (the "
            "absorbed projections read W_kv_b per head) or moe.shared"
        )
    if config.lora.max_loras > 0:
        raise ValueError(
            f"{name}: LoRA adapters are not implemented for multi-head "
            "latent attention (its projections are wq, wkv_a, wk_b, wv_b)"
        )
    if config.scheduler.num_speculative_tokens and (
        config.scheduler.speculative_method == "draft"
    ):
        raise ValueError(
            f"{name}: a draft model sharing a latent pool is not implemented "
            "(the draft's K/V pages have another shape); use the n-gram "
            "proposer"
        )


def _refuse_unbuilt_for_selected_keys(config: EngineConfig) -> None:
    """A model whose latent layers attend over SELECTED keys
    (LayerSpec.indexer; docs/55-selected-keys.md) is refused, by name and
    one sentence each, what its second cache leaf and its selection have not
    been built for. (A mesh, int8, LoRA and a draft model are refused as
    for every latent model: `_refuse_unbuilt_for_latent_attention`, whose
    leaves here are attn.wq_a, q_a_norm, wq_b in wq's place and the
    attn_index stack.)"""
    cfg, name, sched = config.model, config.model.model, config.scheduler
    why = {
        **{tier: (True, "a page of a layer that owns an indexer is a latent "
                  "page AND an index-key page, and engine/kv_transfer.py "
                  "ships the first leaf only")
           for tier in config.kv_tiers_asked()},
        f"speculative decoding (--speculative-config "
        f"{sched.speculative_method})": (
            sched.num_speculative_tokens > 0,
            "the verify program is row-major, and ops/dsa_attention.py "
            "selects for packed chunks and single decode steps only (the "
            "model's own multi-token-prediction layer is not served)"),
        f"--kv-cache-dtype {config.cache.kv_cache_dtype}": (
            config.cache.kv_cache_dtype != "auto",
            "the index keys are stored in the model's dtype: a float8 index "
            "cache selects other keys, and which scale it would take is not "
            "published"),
        "a checkpoint": (
            bool(cfg.checkpoint),
            "the tensor names of a glm_moe_dsa checkpoint (the query "
            "latent's, the indexer's) were never checked against a "
            "published one offline; the presets serve random weights"),
    }
    for what, (asked, reason) in why.items():
        if asked:
            raise ValueError(
                f"{name}: {what} cannot be combined with attention over "
                f"selected keys — {reason}")


def _moe_device_counts(cfg, stats: list):
    """What an MoE program hands back of its routing, summed over `stats`
    (one entry an expert layer executed): the experts touched, a scalar
    int32 — and, from a program that holds a share of the experts
    (ModelConfig.num_experts_held), beside it the pairs that landed on the
    held experts, as (2,) int32."""
    touched = sum((s["touched"] for s in stats), jnp.int32(0))
    if not cfg.num_experts_held and cfg.n_group == 1:
        return touched
    counts = [touched, sum((s["held_pairs"] for s in stats), jnp.int32(0))]
    if cfg.n_group > 1:
        # group-limited routing: and the kept groups that hold a held
        # expert, as (3,) int32
        counts.append(
            sum((s["groups_kept_held"] for s in stats), jnp.int32(0)))
    return jnp.stack(counts)


def _refuse_unbuilt_for_tree_by_kind(config: EngineConfig) -> None:
    """A model whose param tree stacks its leaves by layer kind
    (ModelConfig.tree_by_kind, models/llama.py:_init_params_by_kind) runs
    on ONE device, unquantized, without adapters: what knows the flat tree
    only is refused by name at start. (Where its window layers' pages live
    is not refused but chosen: engine/memory.py:resolve_window_blocks.)"""
    cfg, name, par = config.model, config.model.model, config.parallel
    if par.world_size > 1:
        raise ValueError(
            f"{name}: tensor/data/pipeline/sequence/expert parallelism "
            f"(world size {par.world_size}) is not implemented for a param "
            "tree stacked by layer kind — parallel/sharding.py has no spec "
            "for its leaves (attn_window.*, attn.wg, moe.shared, the "
            "mlp/moe stacks of unequal depth, a held share of the experts)"
        )
    if cfg.quantization:
        raise ValueError(
            f"{name}: --quantization {cfg.quantization} is not implemented "
            "for a param tree stacked by layer kind — models/quantization.py "
            "names no int8 form for attn_window.*, attn.wg or moe.shared"
        )
    if config.lora.max_loras > 0:
        raise ValueError(
            f"{name}: LoRA adapters are not implemented for a param tree "
            "stacked by layer kind (adapter buffers are stacked over all "
            "layers at one head count)"
        )


def _refuse_unbuilt_for_recurrent_state(config: EngineConfig) -> None:
    """A model with layers that hold a recurrent state in state slots
    (LayerSpec.holds_state; engine/kv_cache.py:StateSlots) is refused, by
    name and one sentence each, what no state slot has been built for.
    (Prefix caching is not refused: LLMEngine turns it off and says so; the
    scheduler refuses it where it is built on its own.)"""
    cfg, name, sched = config.model, config.model.model, config.scheduler
    why = {
        **{tier: (True, "a page that comes back from a tier brings no "
                  "recurrent state with it, and PD transfer ships pages only")
           for tier in config.kv_tiers_asked()},
        f"speculative decoding (--speculative-config "
        f"{sched.speculative_method})": (
            sched.num_speculative_tokens > 0,
            "a rejected draft would already have advanced the state, and "
            "the verify program cannot roll it back"),
        f"a mesh of {config.parallel.world_size} devices (tp / dp / pp / "
        "sp / ep, context parallelism included)": (
            config.parallel.world_size > 1,
            "parallel/sharding.py has no spec for the attn_linear and "
            "attn_ssm leaves nor for the state pools, and the ring prefill "
            "carries no state"),
        "LoRA adapters": (
            config.lora.max_loras > 0,
            "the adapter buffers know the q/k/v/o projections of softmax "
            "layers in the flat tree only"),
        f"--quantization {cfg.quantization}": (
            bool(cfg.quantization),
            "models/quantization.py names no int8 form for the attn_linear "
            "and attn_ssm leaves"),
        "a checkpoint": (
            bool(cfg.checkpoint),
            "the tensor names of the linear-attention and state-space "
            "leaves were never checked against a published checkpoint "
            "offline; the presets serve random weights"),
    }
    for what, (asked, reason) in why.items():
        if asked:
            raise ValueError(
                f"{name}: {what} cannot be combined with layers that hold a "
                f"recurrent state — {reason}")


def _refuse_unbuilt_for_block_diffusion(config: EngineConfig) -> None:
    """A model that generates by diffusion over blocks
    (ModelConfig.block_diffusion; docs/44-block-diffusion.md) is refused, by
    name and one sentence each, what its block passes have not been built
    for. (Prefix caching is not refused: LLMEngine turns it off and says so.
    Per-request: engine/engine.py refuses a grammar-constrained row and
    `min_tokens`.)"""
    cfg, name, sched = config.model, config.model.model, config.scheduler
    bd = cfg.block_diffusion
    why = {
        **{tier: (True, "a page is content only once every block in it is "
                  "committed, and no tier nor a PD transfer ships an open "
                  "block")
           for tier in config.kv_tiers_asked()},
        f"speculative decoding (--speculative-config "
        f"{sched.speculative_method})": (
            sched.num_speculative_tokens > 0,
            "both proposers draft one causal token a position and the "
            "verify program masks causally"),
        f"a mesh of {config.parallel.world_size} devices (tp / dp / pp / "
        "sp / ep, context parallelism included)": (
            config.parallel.world_size > 1,
            "the sharded attention wrappers and the ring prefill carry no "
            "block mask"),
        "LoRA adapters": (
            config.lora.max_loras > 0,
            "the block pass feeds several positions a row and the adapter "
            "slots are one a decode row"),
        "multi-head latent attention, window layers or layers that hold "
        "state": (
            bool(cfg.latent_layers) or cfg.any_sliding
            or bool(cfg.state_layers),
            "the block mask is built into the GQA kernels only"),
        "softcapped attention": (
            bool(cfg.attn_logit_softcap),
            "the block pass has no softcap"),
        f"--decode-window {sched.decode_window}": (
            sched.decode_window % bd.block_length != 0,
            f"a window holds whole blocks of {bd.block_length}"),
        f"--block-size {config.cache.block_size}": (
            config.cache.block_size % bd.block_length != 0,
            f"a page holds whole blocks of {bd.block_length}"),
        f"--max-model-len {cfg.max_model_len}": (
            cfg.max_model_len % bd.block_length != 0,
            f"the context holds whole blocks of {bd.block_length}"),
        "prefill buckets that are not whole blocks": (
            any(t % bd.block_length for t in sched.prefill_buckets),
            "chunk seams lie on block boundaries"),
    }
    for what, (asked, reason) in why.items():
        if asked:
            raise ValueError(
                f"{name}: {what} cannot be combined with generation by "
                f"diffusion over blocks — {reason}")


# may a block's kept K/V be those of a pass whose inputs still held masks?
# Never: the commit pass is part of the mathematics. tests/test_sdar.py
# flips this to prove the comparison with the reference catches it.
_BD_COMMIT_PASS = True

# what a window of block passes counts on the device, in this order: the
# `bd_*_n` keys of /debug/timing (docs/44-block-diffusion.md)
BD_COUNTS = tuple(mc.DEBUG_TIMING_BD_COUNTS)


class StepHandle:
    """One dispatched-but-unresolved device step — the async pipeline's
    unit of in-flight work (engine/engine.py pipelined step loop).

    Holds the ON-DEVICE sampled-token matrix (and logprob arrays) so the
    engine can dispatch the NEXT step, chaining decode inputs device-side
    from `tokens`, before paying the single batched D2H transfer that
    resolve() performs. discard() is the rollback hook: the device still
    executes the step, but its results are dropped and the runner's RNG
    rewinds so the replacement dispatch draws the same step key the serial
    loop would have."""

    def __init__(self, runner, work, tokens, lp_arrays, rng_before, postproc):
        self.runner = runner
        self.work = work
        self.tokens = tokens  # device array; decode: (B_pad, window)
        self.lp_arrays = lp_arrays  # tuple of device arrays, or None
        self.rng_before = rng_before
        self._postproc = postproc
        self.logprob_rows: list | None = None
        # the flight recorder's dispatch seq and kind (the engine sets them
        # when the enqueue has returned): the `sync` span carries them
        self.seq, self.kind = 0, ""
        self._rows: list[list[int]] | None = None
        # verify handles: (B_pad,) device vector of each row's full-
        # acceptance bonus token — the chain source for a decode window
        # dispatched on top of this still-in-flight verify step (decode
        # handles chain from tokens[:, -1] instead; see _chain_fn). A
        # prefill handle's is its `tokens`: the first token of each row
        # whose prompt the chunk ends
        self.chain_vec = None
        # grammar-enabled decode handles: (B_pad,) device vector of each
        # row's automaton state AFTER the window — a chained next window
        # gathers its gr_state0 from it the same way tokens chain
        self.grammar_states = None
        # MoE models: (device int32 scalar — experts that got a pair, summed
        # over this program's layers and steps; "decode" | "prefill"; pairs
        # computed; layer-steps x experts). Fetched with the tokens.
        self.moe: tuple | None = None
        # prefill handles: the token positions the program computed (the
        # packed axis, padding included; rows x bucket where row-major)
        self.positions = 0
        # ... and (pages a layer's chunk write wrote, pages it read back
        # first): the rows' spans and none through the write kernel, every
        # slot of the (B, NBW) table twice through the XLA form
        self.kv_write_pages = (0, 0)
        # a window of block passes (ModelConfig.block_diffusion): the
        # device's (len(BD_COUNTS),) int32 counts, fetched with the tokens;
        # `bd_passes` is what the engine's flight record reads once resolved
        self.bd = None
        self.bd_passes: int | None = None

    def resolve(self) -> list[list[int]]:
        """Sync the step's results to the host — exactly ONE jax.device_get
        covering tokens + every logprob array (+ an MoE model's routing
        count) — and build the per-request token rows. Idempotent: the
        transfer happens once."""
        if self._rows is None:
            lp_arrays = self.lp_arrays or ()
            extra = (self.moe[0],) if self.moe is not None else ()
            if self.bd is not None:
                extra = (self.bd, *extra)
            with self.runner.clock.span("sync", self.seq, self.kind):
                got = jax.device_get((self.tokens, *lp_arrays, *extra))
            if self.bd is not None:
                # the passes are data: what follows from them (an MoE
                # model's pairs and slots) is known only now
                bd = [int(n) for n in got[len(lp_arrays) + 1]]
                self.bd_passes = bd[0]
                for key, n in zip(BD_COUNTS, bd):
                    self.runner.clock.count(key, n)
                if self.moe is not None:
                    self.moe = self.moe[1](bd[0])
            mat = np.asarray(got[0])
            lp = (
                tuple(np.asarray(x) for x in got[1:1 + len(lp_arrays)])
                if self.lp_arrays is not None else None
            )
            if self.moe is not None:
                _, kind, pairs, slots, form = self.moe
                clock = self.runner.clock
                # (touched,), or (touched, held pairs) from a program that
                # holds a share of the experts: _moe_device_counts
                counts = np.ravel(got[-1])
                clock.count(f"moe_{kind}_touched_n", int(counts[0]))
                clock.count(f"moe_{kind}_pairs_n", pairs)
                clock.count(f"moe_{kind}_slots_n", slots)
                # experts whose bytes the program's expert computation
                # read: every slot from the all-experts einsum, the touched
                # ones from the kernel and the grouped matmul
                clock.count(
                    f"moe_{kind}_read_n",
                    slots if form == "all_experts" else int(counts[0]))
                clock.count(
                    f"moe_{kind}_held_pairs_n",
                    int(counts[1]) if counts.size > 1 else pairs)
                if counts.size > 2:
                    # group-limited routing: the groups the router kept (a
                    # token and layer-step `topk_group`), and those of them
                    # that hold an expert this program holds
                    model = self.runner.config.model
                    clock.count("moe_group_kept_n", int(counts[2]))
                    clock.count(
                        "moe_group_slots_n",
                        pairs // model.num_experts_per_tok
                        * model.topk_group)
            self._rows, self.logprob_rows = self._postproc(mat, lp)
        return self._rows

    @property
    def resolved(self) -> bool:
        return self._rows is not None

    def discard(self) -> None:
        """Roll back this dispatch (speculation invalidated): rewind the
        runner RNG — valid because nothing else dispatches between a
        speculative step and its rollback decision — and drop the results."""
        self.runner._rng = self.rng_before
        self._rows = []
        self.logprob_rows = None


def _collect_logprobs(logits: jax.Array, tokens: jax.Array):
    """(chosen_lp (S,), top_lp (S, N), top_id (S, N)) from (S, V) logits."""
    lp = jax.nn.log_softmax(logits, axis=-1)
    chosen = jnp.take_along_axis(lp, tokens[:, None].astype(jnp.int32), 1)[:, 0]
    top_lp, top_id = jax.lax.top_k(lp, LOGPROBS_TOPN)
    return chosen, top_lp, top_id.astype(jnp.int32)


def linear_backend_report(cfg, attention_backend: str, prefill_backend: str,
                          max_tokens: int = 2048) -> dict:
    """Which form the linear-attention (delta-rule) layers' programs hold —
    by the gate's granularity the KDA kernels (a gate a key channel) or the
    Gated DeltaNet ones (a gate a head) of ops/kda_attention.py — beside
    what a state slot holds of ONE layer (`state_geometry` (heads, Dk, Dv)
    float32 and the convolution's tail: `slot_bytes_a_layer`), and for the
    chunked prefill kernel the shape of its grid step at this model's
    heads: `heads_per_step` heads x one row's part of a `block_tokens`
    block; `qkv`: the pass that makes q, k and v beside it — ONE Pallas
    pass (ops/kda_short_conv.py; a grid step's tile, heads x tokens of a
    chunk of `max_tokens` positions, the largest prefill bucket) where the
    three projections are equally wide, else XLA's."""
    from .memory import dtype_bytes

    per_head = cfg.linear_gate_per == "head"
    family = "gdn" if per_head else "kda"
    shape, channels = cfg.state_geometry("delta")
    lh, dk, dv = shape
    report = {"decode": (f"{family}_decode_attention"
                         if attention_backend != "xla" else "xla"),
              "prefill": "xla",
              "state_geometry": list(shape), "gate_per": cfg.linear_gate_per,
              "output_gate": cfg.linear_output_gate,
              "slot_bytes_a_layer": math.prod(shape) * 4 + (
                  STATE_CONV_TAPS - 1) * channels * dtype_bytes(cfg.dtype)}
    if prefill_backend.startswith("pallas"):
        report.update(
            prefill=f"{family}_prefill_attention",
            heads_per_step=(
                kda_attention.gdn_heads_per_step(lh) if per_head
                else kda_attention.heads_per_step(
                    lh, dk, jnp.dtype(cfg.dtype).itemsize)),
            block_tokens=kda_attention.BLOCK,
            qkv=({"op": "kda_short_conv",
                  "heads_per_tile": kda_short_conv.heads_per_tile(lh, dk),
                  "token_tile": kda_short_conv.token_tile(max_tokens)}
                 if dk == dv else {"op": "xla"}))
        if per_head:
            # what the kernels pad a head's keys and values to, in the
            # chunk's activations only (ops/kda_attention.py "THE LANES")
            report["lanes"] = [kda_attention.whole_lanes(dk),
                               kda_attention.whole_lanes(dv)]
    return report


def ssd_backend_report(cfg, attention_backend: str,
                       prefill_backend: str) -> dict:
    """The same for the state-space heads (ops/ssd_scan.py)."""
    report = {"decode": ("ssd_decode_step"
                         if attention_backend != "xla" else "xla"),
              "prefill": "xla"}
    if prefill_backend.startswith("pallas"):
        report.update(
            prefill="ssd_prefill_scan",
            heads_per_step=ssd_scan.heads_per_step(
                cfg.ssm_num_heads, cfg.ssm_num_groups),
            block_tokens=ssd_scan.BLOCK)
    return report


class ModelRunner:
    def __init__(
        self,
        config: EngineConfig,
        params: Any | None = None,
        mesh: jax.sharding.Mesh | None = None,
    ):
        self.config = config
        cfg = config.model
        if cfg.latent_layers:
            _refuse_unbuilt_for_latent_attention(config)
        if cfg.selecting_layers:
            _refuse_unbuilt_for_selected_keys(config)
        if cfg.state_layers:
            _refuse_unbuilt_for_recurrent_state(config)
        if cfg.tree_by_kind:
            _refuse_unbuilt_for_tree_by_kind(config)
        if cfg.block_diffusion is not None:
            _refuse_unbuilt_for_block_diffusion(config)
        pp = config.parallel.pipeline_parallel_size
        if pp > 1:
            # friendly errors beat JAX's deep 'dimension not divisible'
            if cfg.num_layers % pp:
                raise ValueError(
                    f"num_layers={cfg.num_layers} must be divisible by "
                    f"pipeline_parallel_size={pp} (layer axis shards over "
                    "pp stages)"
                )
            if config.cache.num_blocks and config.cache.num_blocks % pp:
                raise ValueError(
                    f"num_blocks={config.cache.num_blocks} must be divisible "
                    f"by pipeline_parallel_size={pp} (the pool's block axis "
                    "shards over pp stages); round it or leave num_blocks "
                    "unset to derive from HBM"
                )
        self.mesh = mesh or mesh_lib.make_mesh(
            config.parallel.tensor_parallel_size,
            config.parallel.data_parallel_size,
            config.parallel.pipeline_parallel_size,
            sequence_parallel_size=config.parallel.sequence_parallel_size,
            expert_parallel_size=config.parallel.expert_parallel_size,
        )
        self.max_blocks = config.cache.max_blocks_per_seq(cfg.max_model_len)
        dev0 = jax.devices()[0]
        logger.info(
            "device: platform=%s kind=%s count=%d mesh=%s mesh_device_ids=%s",
            dev0.platform, dev0.device_kind, jax.device_count(),
            dict(self.mesh.shape), [d.id for d in self.mesh.devices.flat],
        )

        param_shardings = self._param_shardings()
        if params is None and cfg.checkpoint:
            from ..models.loader import load_checkpoint_params

            params = load_checkpoint_params(cfg)
            if cfg.quantization:
                # host-side (numpy): the device never holds the bf16 tree
                from ..models.quantization import quantize_params

                params = quantize_params(cfg, params)
        self._random_weights = params is None
        if params is None:
            logger.info(
                "initializing random weights for %s%s", cfg.model,
                f" ({cfg.quantization} weight-only)" if cfg.quantization
                else "",
            )
            # materialized directly into the sharded HBM layout
            self.params = self._init_device_params(param_shardings)
        else:
            self.params = jax.tree.map(jax.device_put, params, param_shardings)
        self._kv_dtype = config.cache.resolved_kv_dtype(cfg.dtype)
        # a model with window layers keeps their pages in a group of their
        # own (engine/kv_cache.py:WindowPageGroup): a second block table,
        # written span and slot vector ride beside the full group's, as
        # (full, window) pairs (models/llama.py:forward)
        from .memory import (
            resolve_state_slots, resolve_window_blocks, state_slot_bytes)

        self.num_window_blocks = resolve_window_blocks(config)
        self._window_group = self.num_window_blocks is not None
        # a model with layers that hold a recurrent state keeps it in state
        # slots (engine/kv_cache.py:StateSlots): `state_slots` a bank, two
        # banks; each program takes its rows' (read, write) slot indices
        self.state_slots = resolve_state_slots(config)
        if cfg.linear_layers:
            logger.info(
                "%s: %d delta-rule layers hold a float32 state of (heads, "
                "keys, values) = %s a row, one decay gate a %s, output gate "
                "%s: %d slots a bank x 2 banks x %d bytes a slot over the "
                "layers that hold state",
                cfg.model, len(cfg.linear_layers),
                cfg.state_geometry("delta")[0], cfg.linear_gate_per,
                cfg.linear_output_gate, self.state_slots,
                state_slot_bytes(cfg))
        self.kv_caches = self._new_kv_caches()
        self._use_lora = config.lora.max_loras > 0
        if self._use_lora:
            lora_sh = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s),
                lora_param_specs(cfg, config.lora),
            )
            self.lora_params = jax.jit(
                lambda: llama.init_lora_params(cfg, config.lora),
                out_shardings=lora_sh,
            )()
            self._lora_shardings = lora_sh
        else:
            self.lora_params = None
        self._rng = jax.random.PRNGKey(config.seed ^ 0x5EED)
        self._rep = NamedSharding(self.mesh, P())
        # dp shards every batch-dim input across the dp mesh axis: each dp
        # group computes only its rows. The KV pool stays dp-replicated —
        # block ids are global, and the per-step cross-dp cost is only the
        # all-gather of the new K/V rows GSPMD inserts for the pool scatter.
        self._dp = config.parallel.data_parallel_size
        self._batch1 = NamedSharding(self.mesh, P(mesh_lib.DP_AXIS))
        self._batch2 = NamedSharding(self.mesh, P(mesh_lib.DP_AXIS, None))
        if self._dp > 1:
            if self._dp & (self._dp - 1):
                # _batch_bucket pads batches to max(dp, pow2) — only a pow2
                # dp always divides that evenly
                raise ValueError(
                    f"data_parallel_size={self._dp} must be a power of two"
                )
            bad = [
                b for b in config.scheduler.decode_buckets if b % self._dp
            ]
            if bad:
                raise ValueError(
                    f"decode_buckets {bad} not divisible by dp={self._dp}"
                )
        # sp shards the PREFILL chunk's sequence axis over the ring
        # (parallel/ring_attention.py); decode (T=1) has no sequence axis to
        # shard, so sp devices replicate decode work — size sp for prefill-
        # heavy / long-context serving (disaggregated prefill-role engines)
        self._sp = config.parallel.sequence_parallel_size
        self._seq2 = NamedSharding(
            self.mesh, P(mesh_lib.DP_AXIS, mesh_lib.SP_AXIS)
        )
        if self._sp > 1:
            bad_t = [
                t for t in config.scheduler.prefill_buckets if t % self._sp
            ]
            if bad_t:
                raise ValueError(
                    f"prefill_buckets {bad_t} not divisible by "
                    f"sequence_parallel_size={self._sp} (the chunk axis "
                    "shards evenly over the sp ring)"
                )
        if config.parallel.expert_parallel_size > 1 and not cfg.num_experts:
            raise ValueError(
                f"expert_parallel_size={config.parallel.expert_parallel_size} "
                f"requires an MoE model, but {cfg.model} has no experts — "
                "the ep axis would only replicate dense compute"
            )
        self._bind_program_inputs(
            self._resolve_attention_backend(),
            self._resolve_prefill_backend(),
            self._resolve_moe_backend(),
            self._compute_hoist_budget())
        self._step_fn = (
            self._build_sp_step_fn() if self._sp > 1 else self._build_step_fn()
        )
        self._decode_window_fn = self._build_decode_window_fn()
        self._verify_fn = (
            self._build_verify_fn()
            if config.scheduler.num_speculative_tokens > 0
            else None
        )
        # per-execute logprob rows (parallel to the returned token rows)
        # when the dispatched batch requested them; None otherwise. Read by
        # LLMEngine.step right after execute().
        self.last_logprobs: list | None = None
        # the step timeline (flightrec.PHASES): this runner's h2d, enqueue
        # and sync spans. The engine hands in its flight recorder; a runner
        # on its own (tests, the draft proposer) keeps a private one
        self.clock = FlightRecorder()
        # async pipeline: splice a chained row's input token from the
        # previous step's device-resident output matrix (last window
        # column), falling back to the host-provided token where
        # chain_idx < 0 — the D2H→H2D round trip the pipeline removes
        self._chain_fn = jax.jit(
            lambda prev_toks, host_toks, idx: jnp.where(
                idx >= 0,
                jnp.take(
                    prev_toks[:, -1],
                    jnp.clip(idx, 0, prev_toks.shape[0] - 1),
                ),
                host_toks,
            ),
            out_shardings=NamedSharding(self.mesh, P(mesh_lib.DP_AXIS)),
        )
        # chain variant for a previous VERIFY step: its per-row next-input
        # token is position-dependent (each row's last real fed column), so
        # the handle carries a precomputed (B_pad,) vector instead of a
        # matrix column
        self._chain_vec_fn = jax.jit(
            lambda prev_vec, host_toks, idx: jnp.where(
                idx >= 0,
                jnp.take(prev_vec, jnp.clip(idx, 0, prev_vec.shape[0] - 1)),
                host_toks,
            ),
            out_shardings=NamedSharding(self.mesh, P(mesh_lib.DP_AXIS)),
        )
        # verify-on-verify chaining (docs/36): a chained verify row's FIRST
        # fed token is the in-flight verify's bonus token (chain_vec) —
        # spliced into column 0 of the fed-token matrix device-side, since
        # its value exists nowhere on the host yet
        self._chain_verify_fn = jax.jit(
            lambda prev_vec, toks, idx: toks.at[:, 0].set(
                jnp.where(
                    idx >= 0,
                    jnp.take(
                        prev_vec, jnp.clip(idx, 0, prev_vec.shape[0] - 1)
                    ),
                    toks[:, 0],
                )
            ),
            out_shardings=NamedSharding(self.mesh, P(mesh_lib.DP_AXIS, None)),
        )
        self._zero_stop_arrays: dict[int, tuple] = {}
        # structured output (docs/41-structured-output.md): device-resident
        # automaton tables keyed by (grammar uids, pads) — a steady
        # constrained batch re-dispatches with zero H2D table traffic —
        # plus cached all-ones prefill masks (the identity rows a grammar-
        # enabled program feeds its unconstrained batches)
        self._grammar_tables_cache: dict[tuple, tuple] = {}
        self._ones_mask_cache: dict[int, Any] = {}
        self._gr_eos_dev: Any | None = None
        self._sleeping_params_host: Any | None = None
        self._sleeping_lora_host: Any | None = None
        self._upload_block_fn = None
        self._fetch_block_fn = None
        self._embed_fn = None
        # -- compile-stall avoidance (the measured live-serving collapse
        # mode: a first-seen (rows × chunk × width) program key froze
        # serving 30-60s mid-traffic). The runner tracks which program keys
        # are compiled; a miss PADS UP to an already-compiled dominating
        # program (more padding = identical results, bounded extra compute)
        # and hands the exact key to a background thread that AOT-compiles
        # it (.lower().compile() — populates jax's in-process+persistent
        # caches without executing), so the NEXT hit runs specialized.
        self._compiled_keys: set[tuple] = set()
        self._aot_exec: dict[tuple, Any] = {}
        self._bg_inflight: set[tuple] = set()
        self._bg_lock = threading.Lock()
        self._bg_executor: ThreadPoolExecutor | None = None
        self._bg_stop = threading.Event()  # shutdown() -> running job bails
        self.compile_fallbacks = 0  # profiling: pad-up substitutions taken
        self.bg_compiles = 0  # profiling: programs compiled off the hot path
        # warmup disables this so every wave compiles its EXACT program
        self.fallback_enabled = True
        # thread-liveness heartbeat (docs/37-flight-recorder.md,
        # flightrec.ThreadRegistry "bg_compile"; the engine wires it):
        # busy only while a background compile actually runs — a beat
        # older than its generous threshold while busy is the
        # "XLA compiles forever" wedge the watchdog names
        self.heartbeat = None
        # when set (AsyncEngine wires it), background compiles WAIT for the
        # engine to go idle: the compile contends with the step thread for
        # host cores, so compiling during traffic steals the serving time
        # the background thread exists to protect
        self.idle_check = None  # Callable[[], bool] | None
        # XLA compile telemetry (docs/42-compile-telemetry.md): the engine
        # replaces this with its shared CompileWatch; a runner on its own
        # keeps a private one. The draft runner shares the target's watch
        # with role="draft".
        self.compile_watch = CompileWatch()
        self.compile_role = "target"
        # verify programs have no pad-up fallback lattice (t_pad is the
        # pow2 of the fed width) — tracked separately for telemetry only
        self._verify_keys: set[tuple] = set()

    def _new_kv_caches(self):
        """The zeroed cache arrays: pages of the layers that hold pages,
        state pools of those that hold state (models/llama.py:
        init_kv_cache)."""
        config, cfg = self.config, self.config.model
        # (a latent pool has no kv-head axis and a state pool none to shard
        # over tp: both run on one device)
        spec = (P() if cfg.latent_layers or cfg.state_layers
                else kv_cache_spec())
        return jax.jit(
            lambda: llama.init_kv_cache(
                cfg, config.cache.num_blocks, config.cache.block_size,
                dtype=self._kv_dtype,
                num_window_blocks=self.num_window_blocks,
                state_slots=self.state_slots,
            ),
            out_shardings=NamedSharding(self.mesh, spec),
        )()

    def _state_slot_arrays(self, requests, pad_to: int):
        """(read, write) of a dispatch's rows, (pad_to,) int32 each: where on
        the state pools' first axis the program reads a row's state slot and
        where it writes it — the other bank of the same slot (kv_cache.
        StateSlots; the scheduler flips the row's bank once the dispatch is
        out). Padding rows read and write the null slot 0. None for a model
        without such layers: the programs are what they were."""
        if not self.state_slots:
            return None
        read = np.zeros(pad_to, np.int32)
        write = np.zeros(pad_to, np.int32)
        for i, req in enumerate(requests):
            read[i] = req.state_bank * self.state_slots + req.state_slot
            write[i] = (req.state_bank ^ 1) * self.state_slots + req.state_slot
        return (self._put(read, self._batch1), self._put(write, self._batch1))

    def _bind_program_inputs(self, attention: str, prefill: str, moe: str,
                             hoist_budget: int) -> None:
        """What the `_build_*_fn` program builders read of a runner beside
        `config` and `mesh` — set here and nowhere else, so that
        `decode_window_program` builds what `__init__` builds."""
        self._attention_backend = attention
        self._prefill_backend = prefill
        self._moe_backend = moe
        self._hoist_budget = hoist_budget

    @classmethod
    def decode_window_program(cls, config: EngineConfig,
                              mesh: jax.sharding.Mesh):
        """The jitted decode-window function a runner over `mesh` builds with
        the Pallas backends, WITHOUT a runner's device state: no weights, no
        pools, nothing probed — `mesh` may hold a described topology's
        devices. For `.lower(...)` against abstract arguments, the way
        `_call_decode_program` calls it (tools/weight_copies.py reads the
        compiled text: a cell's window as the installed TPU compiler lays
        it out, with no chip)."""
        self = object.__new__(cls)
        self.config, self.mesh = config, mesh
        self._bind_program_inputs("pallas", "pallas", "pallas", 0)
        return self._build_decode_window_fn()

    def _resolve_attention_backend(self) -> str:
        """'auto' → the measured winner for the pool's block size.

        Swept on a v5e chip on 2026-09-27 (PR 25: the kernel walks the pages
        a row holds, 256 tokens a block; benchmarks/sweep_attention.py,
        llama-1b decode head shape 32/8 x 64, bf16 pool, full tables,
        64-iteration on-device loops, ms/iter):

            batch ctx   block   pallas   xla     winner
            16    1024  16      0.134    0.112   xla
            16    1024  32      0.104    0.112   pallas (-7%)
            16    1024  64      0.092    0.112   pallas (-18%)
            16    4096  16      0.461    1.283   pallas (-64%)
            16    4096  32      0.401    1.284   pallas (-69%)
            16    4096  64      0.390    0.817   pallas (-52%)
            64    1024  16      0.477    1.287   pallas (-63%)
            64    1024  64      0.405    0.837   pallas (-52%)
            64    4096  64      1.513    3.974   pallas (-62%)

        (the sweep's pool and tables do not change inside its loop, so XLA
        may hoist its gather out of it: the 0.112 rows flatter XLA.) With an
        fp8 pool XLA still wins every row, 2.2-3.0x (16 x 4096 x 32: 2.00
        vs 0.92): the kernel's widening of a block costs more than the
        bytes it saves. Before PR 25 the kernel ran one grid step per table
        slot (16 x 4096 x 32: 2.71 vs 2.93; 16 x 1024 x 16: 1.93 vs 1.63),
        which is what the predicate below was written from: 'auto' requires
        BOTH block_size >= 32 and a long-context engine (max_model_len >=
        4096), single device, unquantized (resolve_auto_attention_backend —
        the pure predicate tests pin). The new table says it could widen to
        16-token pages and ~1k contexts at block >= 32 (ROADMAP D3); fp8
        stays XLA. The kernel also never materializes the O(BxS) gather
        scratch that OOMs large models. Under tp>1 an explicit 'pallas' runs one kernel per device
        through shard_map; CPU tests pin numerics via interpret mode."""
        backend = self.config.attention_backend
        if self.config.model.attn_logit_softcap:
            # softcapped scores (Gemma-2 class): the Pallas kernels have
            # no softcap — XLA only. (A window layer has kernels of its
            # own: paged_window_decode_attention.)
            if backend in ("pallas", "pallas_interpret"):
                raise ValueError(
                    "attention_backend='pallas' does not support "
                    "softcapped models; use 'xla'"
                )
            return "xla"
        if backend == "auto":
            return resolve_auto_attention_backend(
                block_size=self.config.cache.block_size,
                max_model_len=self.config.model.max_model_len,
                mesh_size=self.mesh.size,
                kv_quantized=self._kv_dtype != self.config.model.dtype,
                platform=jax.devices()[0].platform,
            )
        if backend not in ("xla", "pallas", "pallas_interpret"):
            raise ValueError(
                f"unknown attention_backend {backend!r}; expected one of "
                "'auto', 'xla', 'pallas', 'pallas_interpret'"
            )
        if backend.startswith("pallas") and self.mesh.size > 1:
            # shard_map places kernel instances per device over (dp, tp) —
            # the axes decode attention parallelizes over with no
            # collective. pp/sp/ep shard things the kernel can't split
            # (the pool's block axis, the sequence axis, experts).
            par = self.config.parallel
            if (
                par.pipeline_parallel_size > 1
                or par.sequence_parallel_size > 1
                or par.expert_parallel_size > 1
            ):
                raise ValueError(
                    "attention_backend='pallas' supports dp/tp meshes only "
                    "(pp/sp/ep shard axes the decode kernel cannot split)"
                )
            tp = par.tensor_parallel_size
            if self.config.model.num_heads % tp or (
                self.config.model.num_kv_heads % tp
            ):
                raise ValueError(
                    f"attention_backend='pallas' under tp={tp} needs "
                    "num_heads and num_kv_heads divisible by tp"
                )
        # quantized (fp8) pools are supported: the kernel casts pages to
        # f32 as they stream into VMEM (Mosaic handles f8e4m3 loads on
        # v5e), same upconvert the XLA path does — pinned by
        # tests/test_pallas_attention.py::test_pallas_fp8_pool_numerics
        return backend

    def _resolve_prefill_backend(self) -> str:
        """Prefill attention backend, resolved independently of decode
        (resolve_auto_prefill_backend has the gate rationale). The sp path
        (ring attention) ignores this — it has its own sharded prefill."""
        par = self.config.parallel
        tp = par.tensor_parallel_size
        heads_ok = (
            self.config.model.num_heads % tp == 0
            and self.config.model.num_kv_heads % tp == 0
            and par.sequence_parallel_size == 1
            and par.pipeline_parallel_size == 1
            and par.expert_parallel_size == 1
        )
        backend = self.config.prefill_attention_backend
        model = self.config.model
        if model.attn_logit_softcap:
            # the kernel has no softcap — XLA only
            if backend in ("pallas", "pallas_interpret"):
                raise ValueError(
                    "prefill_attention_backend='pallas' does not support "
                    "softcapped models; use 'xla'"
                )
            return "xla"
        kv_itemsize = jnp.dtype(self._kv_dtype).itemsize
        # an MLA model's kernel is the latent one (ops/mla_attention_pallas
        # .py), the one prefill form kept for it — the XLA form is the CPU
        # fallback and the kernel's oracle; its pages are (block_size,
        # latent_width), one "kv head" wide
        kv_heads, head_dim = (
            (1, model.latent_width) if model.latent_layers
            else (model.pool_kv_heads, model.head_dim))
        if backend == "auto":
            return resolve_auto_prefill_backend(
                block_size=self.config.cache.block_size,
                num_kv_heads=kv_heads,
                head_dim=head_dim,
                kv_itemsize=kv_itemsize,
                kv_quantized=self._kv_dtype != model.dtype,
                mesh_size=self.mesh.size,
                platform=jax.devices()[0].platform,
                latent_pages=bool(model.latent_layers),
            )
        if backend not in ("xla", "pallas", "pallas_interpret"):
            raise ValueError(
                f"unknown prefill_attention_backend {backend!r}; expected "
                "one of 'auto', 'xla', 'pallas', 'pallas_interpret'"
            )
        if backend.startswith("pallas") and self.mesh.size > 1 and not heads_ok:
            raise ValueError(
                f"prefill_attention_backend='pallas' under tp={tp} needs "
                "num_heads and num_kv_heads divisible by tp, and a dp/tp "
                "mesh (pp/sp/ep shard axes the kernel cannot split)"
            )
        if (backend == "pallas" and not model.latent_layers
                and not _pages_fill_tiles(
                    model.pool_kv_heads // tp, model.head_dim, kv_itemsize)):
            raise ValueError(
                "prefill_attention_backend='pallas' needs a pool whose "
                f"pages fill whole HBM tiles ({model.num_kv_heads // tp} kv "
                f"heads x {model.head_dim} a device do not): the kernel "
                "copies pages out of HBM itself; use 'xla'"
            )
        return backend

    def _resolve_moe_backend(self) -> str:
        """May this engine's programs hold the touched-experts kernel
        (models/llama.py:_moe_mlp, which then chooses per program from its
        static shapes)? Not an option: "pallas" on one TPU device, "xla"
        under any mesh (the kernel has no partition rule and no shard_map
        wrapper) and off the TPU — where an engine asked for its attention
        kernels in interpret mode (CPU tests) this one is interpreted too."""
        if not self.config.model.num_experts or self.mesh.size > 1:
            return "xla"
        if jax.devices()[0].platform == "tpu":
            return "pallas"
        if self.config.attention_backend == "pallas_interpret":
            return "pallas_interpret"
        return "xla"

    def moe_experts_form(self, tokens: int) -> str:
        """The form a program of `tokens` tokens computes its expert layers
        in: "touched_kernel" | "all_experts" | "grouped" |
        "grouped_kernel" — from the token count, the model's shapes (its
        expert leaves' itemsize among them) and whether this engine's
        programs may hold a Pallas kernel."""
        return llama.moe_experts_form(
            self.config.model, tokens, kernel=self._moe_backend != "xla")

    @property
    def prefill_packed(self) -> bool:
        """Does a prefill dispatch lay its rows' tokens end to end on one
        axis (`_dispatch_prefill`)? On one replica, yes. Under dp > 1 rows
        shard over replicas and under sp > 1 the chunk axis over devices:
        there a chunk is row-major, every row padded to the bucket."""
        return self._dp == 1 and self._sp == 1

    def prefill_bucket(self, lens: Sequence[int]) -> int:
        """The token bucket `t_pad` of the program that runs ONE prefill
        dispatch of rows with these chunk lengths: the bucket of their sum
        where the chunk is packed, of the longest row where it is row-major.
        This and `prefill_row_room` are all the rest of the engine knows of
        the layout. ValueError past the largest bucket."""
        sched = self.config.scheduler
        return sched.bucket_for(
            sum(lens) if self.prefill_packed else max(lens),
            sched.prefill_buckets,
        )

    def prefill_row_room(
        self, lens: Sequence[int], bucket: int | None = None
    ) -> int:
        """The longest chunk one MORE row may bring to a dispatch that holds
        rows of `lens` and is to stay within `bucket` (the largest, if none
        is given): what the others leave of it where the chunk is packed,
        the whole of it where every row has a bucket of its own. Whoever
        builds a `PrefillWork` outside the scheduler's token budget (the
        draft proposer's catch-up) sizes its rows by this."""
        cap = bucket or max(self.config.scheduler.prefill_buckets)
        return cap - sum(lens) if self.prefill_packed else cap

    @property
    def prefill_uses_kernel(self) -> bool:
        """Whether the prefill programs hold the Pallas kernel."""
        return self._prefill_backend.startswith("pallas")

    @property
    def prefill_writes_by_kernel(self) -> bool:
        """Whether a chunk's K and V reach the pool through the write kernel
        (llama.chunk_write_by_kernel), not the XLA read-merge-scatter."""
        return llama.chunk_write_by_kernel(self._prefill_backend, self.mesh)

    def device_report(self) -> dict:
        """The "device" section of GET /debug/programs: what the engine
        runs on (memory.device_report) and which attention backend each
        phase resolved to. An explicit 'pallas' is served as asked or
        refused at construction — it never degrades to interpret mode or
        XLA — so `resolved` differs from `requested` only for 'auto'."""
        from .memory import device_report

        bd = self.config.model.block_diffusion
        report = device_report(self.mesh)
        report["attention"] = {
            "decode": {
                "requested": self.config.attention_backend,
                "resolved": self._attention_backend,
            },
            "prefill": {
                "requested": self.config.prefill_attention_backend,
                "resolved": self._prefill_backend,
            },
        }
        cfg = self.config.model
        if cfg.selecting_layers:
            # learned sparse attention (docs/55-selected-keys.md): who
            # selects, who borrows, and what computes it
            report["mixers"] = {
                "by_layer": [f"latent+{s.indexer}" for s in cfg.layer_specs],
                "latent": len(cfg.latent_layers),
                "full": len(cfg.index_layers),
                "shared": len(cfg.selecting_layers) - len(cfg.index_layers),
                "index_topk": cfg.index_topk,
                "indexer_backend": {
                    "prefill": "xla: dsa_index_score (blocked einsum) + "
                               "dsa_select (k-th value by bisection)",
                    "decode": "xla: dsa_index_score + dsa_select "
                              "(jax.lax.top_k)"},
                "attention_backend": {
                    "prefill": "xla: dsa_attn (masked pass over the row's "
                               "latents)",
                    "decode": "xla: dsa_gather + dsa_attn (the selected "
                              "latents by slot)"},
            }
        if cfg.state_layers:
            # the mixer kinds of every program: layers that hold pages
            # (softmax attention) and layers that hold state (the gated
            # delta rule or state-space heads beside the attention heads,
            # through their kernels wherever attention runs through its
            # own)
            report["mixers"] = {
                "by_layer": [s.mixer + ("+ssd" if s.recurrence == "ssd"
                                        else "") for s in cfg.layer_specs],
                "softmax": len(cfg.page_layers) - len(cfg.latent_layers),
                "latent": len(cfg.latent_layers),
                "linear": len(cfg.linear_layers),
                "ssd": len(cfg.ssm_layers),
                "state_slots": self.state_slots,
            }
            if cfg.linear_layers:
                report["mixers"]["linear_backend"] = linear_backend_report(
                    cfg, self._attention_backend, self._prefill_backend,
                    max(self.config.scheduler.prefill_buckets))
            if cfg.ssm_layers:
                report["mixers"]["ssd_backend"] = ssd_backend_report(
                    cfg, self._attention_backend, self._prefill_backend)
        if self.config.model.num_experts:
            # the expert computation a program holds follows from its row
            # (decode) or token (prefill) bucket; `resolved`: the widest,
            # what a full batch or a full chunk runs
            sched = self.config.scheduler
            # (a pass of a window of block passes feeds every row's block)
            per_row = {"decode": bd.block_length if bd else 1, "prefill": 1}
            report["moe"] = {
                phase: {
                    "resolved": self.moe_experts_form(
                        max(buckets) * per_row[phase]),
                    "by_bucket": {
                        str(n): self.moe_experts_form(n * per_row[phase])
                        for n in buckets},
                }
                for phase, buckets in (("decode", sched.decode_buckets),
                                       ("prefill", sched.prefill_buckets))
            }
        if bd is not None:
            # what the model's GENERATION is (docs/44-block-diffusion.md):
            # the preset's settings, and what a decode window is made of
            report["generation"] = {
                "kind": "block_diffusion", **dataclasses.asdict(bd),
                "transfer_schedule": list(bd.transfer_schedule),
                "window_blocks": (
                    self.config.scheduler.decode_window // bd.block_length),
                "passes_per_block": [2, bd.denoising_steps + 1],
            }
        return report

    def _compute_hoist_budget(self) -> int:
        """Per-device HBM headroom (bytes) available for hoisting the decode
        window's loop-invariant history gather out of the loop (one
        contiguous per-layer K/V copy per window instead of a fresh gather
        per iteration — the measured decode bottleneck; see
        ops/attention.py:attention_with_hist). Headroom = utilization-capped
        HBM − pool − weights − reserve; each compiled window program compares
        its own static (batch, context) hoist footprint against this and
        falls back to the per-iteration gather when it doesn't fit. The cap
        matters: memory the operator withheld via hbm_utilization (co-located
        workloads) must not be absorbed by hoisted copies."""
        from .memory import headroom_budget, kv_pool_bytes

        par = self.config.parallel
        pool = kv_pool_bytes(
            self.config.model, self.config.cache, par,
            self.num_window_blocks, kv_dtype=self._kv_dtype,
            state_slots=self.state_slots,
        )
        return max(
            0,
            headroom_budget(self.config.model, self.config.cache, par) - pool,
        )

    def _hoist_bytes(self, batch: int, s_ctx: int) -> int:
        """Per-device bytes of hoisted contiguous history for one window
        program: all layers' (B, S, kvH, D) K+V, batch sharded over dp and
        kv heads over tp. Expressed via memory.kv_block_bytes so the hoist
        budget can never diverge from the pool accounting it is compared
        against."""
        from .memory import kv_block_bytes

        par = self.config.parallel
        block_size = self.config.cache.block_size
        b_local = max(1, batch // self._dp)
        return (
            b_local
            * (s_ctx // block_size)
            * kv_block_bytes(
                self.config.model, block_size,
                par.tensor_parallel_size, par.pipeline_parallel_size,
                kv_dtype=self._kv_dtype,
            )
        )

    # -- compiled step -----------------------------------------------------

    def _build_step_fn(self):
        cfg = self.config.model

        @functools.partial(
            jax.jit,
            donate_argnames=("kv_caches",),
            static_argnames=(
                "want_logprobs", "want_min_tokens", "want_grammar"
            ),
        )
        def step_fn(
            params,
            lora_params,  # stacked adapter tree, or None when LoRA disabled
            kv_caches,
            token_ids,  # (N,) the PACKED chunk: the rows' tokens end to
            #   end, N the bucket of their sum; (B, T) row-major under dp > 1
            #   (rows sharded over replicas) — the layout is read off the rank
            positions,  # as token_ids
            block_tables,  # (B, max_blocks)
            slot_mapping,  # (1,) placeholder — only the sp path row-scatters
            context_lens,  # (B,)
            chunk_lens,  # (B,) real chunk tokens this step
            write_ids,  # (B, NBW) pool blocks of the chunk's written span
            start_off,  # (B,) chunk's first-token offset in its first block
            lora_idx,  # (B,) adapter slot per row (None when disabled)
            sample_rows,  # (num_samples,) index into the flat hidden, (N)
            #   or (B*T), of each row's last chunk token
            temperature,  # (num_samples,)
            top_p,  # (num_samples,)
            top_k,  # (num_samples,)
            rng,
            seeds,  # (num_samples,) int32
            has_seed,  # (num_samples,) bool
            counts,  # (num_samples,) int32 output tokens so far
            min_toks,  # (num_samples,) min_tokens per row
            stop_ids,  # (num_samples, SUPPRESS_IDS) eos/stop ids, -1 pad
            state_slots,  # (read (B,), write (B,)): the rows' state slots
            #   (_state_slot_arrays); None for a model no layer of which
            #   holds state
            grammar_mask=None,  # (num_samples, V) bool — constrained rows'
            #   allowed tokens, all-True for unconstrained rows (mask is
            #   DATA; docs/41-structured-output.md)
            want_logprobs=False,  # static: also return chosen/top-N logprobs
            want_min_tokens=False,  # static: suppression costs a full-logits
            #   copy per dispatch, so it only compiles in when a row needs it
            want_grammar=False,  # static: grammar masking compiles in only
            #   when a batch row is constrained
        ):
            # an MoE model's program also returns, last, the experts its
            # layers touched (a dense model's outputs are what they were)
            moe_stats: list | None = [] if cfg.num_experts else None
            pack = None
            if token_ids.ndim == 1:
                pack = llama.pack_index(
                    chunk_lens, context_lens, token_ids.shape[0])
                if lora_idx is not None:
                    lora_idx = lora_idx[pack["tok_row"]]
            hidden, kv_caches = llama.forward(
                cfg, params, token_ids, positions, kv_caches,
                block_tables, slot_mapping, context_lens,
                lora=lora_params, lora_idx=lora_idx,
                write_blocks={
                    "ids": write_ids,
                    "start_off": start_off,
                    "chunk_lens": chunk_lens,
                },
                backend=self._prefill_backend,
                mesh=self.mesh,
                moe_stats=moe_stats,
                pack=pack,
                moe_backend=self._moe_backend,
                state_slots=state_slots,
            )
            moe_out = (
                (_moe_device_counts(cfg, moe_stats),) if moe_stats else ()
            )
            flat = hidden.reshape(-1, hidden.shape[-1])
            picked = flat[sample_rows]  # (num_samples, h)
            logits = llama.compute_logits(cfg, params, picked)
            with jax.named_scope("sample"):
                if want_grammar:
                    # masked logits flow into logprobs too: the reported
                    # distribution is the constrained one actually sampled
                    # from
                    logits = apply_grammar_mask(logits, grammar_mask)
                if want_min_tokens:
                    logits = suppress_stop_tokens(
                        logits, counts, min_toks, stop_ids
                    )
                tokens = sample(
                    logits, temperature, top_p, top_k, rng, seeds, has_seed,
                    counts,
                )
                if want_logprobs:
                    return (
                        kv_caches, tokens, _collect_logprobs(logits, tokens),
                        *moe_out,
                    )
            return (kv_caches, tokens, *moe_out)

        return step_fn

    def _build_sp_step_fn(self):
        """Prefill step with the chunk's sequence axis sharded over the sp
        mesh axis — ring attention seeded with the pooled history block
        (models/llama.py:forward_sp_prefill). Same signature as the paged
        step so the host-side batching code is identical."""
        cfg = self.config.model
        mesh = self.mesh

        @functools.partial(
            jax.jit,
            donate_argnames=("kv_caches",),
            static_argnames=(
                "want_logprobs", "want_min_tokens", "want_grammar"
            ),
        )
        def sp_step_fn(
            params,
            lora_params,
            kv_caches,
            token_ids,  # (B, T) — T sharded over sp
            positions,  # (B, T)
            block_tables,  # (B, max_blocks)
            slot_mapping,  # (B*T,)
            context_lens,  # (B,) resident AFTER this chunk
            chunk_lens,  # (B,) real chunk tokens this step
            write_ids,  # unused: the sp path row-scatters (sharded over sp)
            start_off,  # unused
            lora_idx,
            sample_rows,
            temperature,
            top_p,
            top_k,
            rng,
            seeds,
            has_seed,
            counts,
            min_toks,
            stop_ids,
            state_slots,  # always None: a mesh is refused beside state slots
            grammar_mask=None,
            want_logprobs=False,
            want_min_tokens=False,
            want_grammar=False,
        ):
            del write_ids, start_off, state_slots
            hist_lens = context_lens - chunk_lens
            hidden, kv_caches = llama.forward_sp_prefill(
                cfg, params, token_ids, positions, kv_caches, block_tables,
                slot_mapping, chunk_lens, hist_lens, mesh,
                lora=lora_params, lora_idx=lora_idx,
            )
            flat = hidden.reshape(-1, hidden.shape[-1])
            picked = flat[sample_rows]
            logits = llama.compute_logits(cfg, params, picked)
            with jax.named_scope("sample"):
                if want_grammar:
                    logits = apply_grammar_mask(logits, grammar_mask)
                if want_min_tokens:
                    logits = suppress_stop_tokens(
                        logits, counts, min_toks, stop_ids
                    )
                tokens = sample(
                    logits, temperature, top_p, top_k, rng, seeds, has_seed,
                    counts,
                )
                if want_logprobs:
                    return (
                        kv_caches, tokens, _collect_logprobs(logits, tokens)
                    )
            return kv_caches, tokens

        return sp_step_fn

    def _build_decode_window_fn(self):
        """K decode iterations fused into one dispatch: a lax.fori_loop feeds
        each iteration's sampled tokens into the next ON DEVICE and returns
        the (B, K) token matrix in a single fetch. Host↔device dispatch
        and sync latency amortizes over B*K tokens instead of B.

        The KV pool is deliberately NOT a loop carry: each iteration writes
        its K/V into a small (L, 2, W, B, kvH, D) staging buffer and attends
        over [pooled history + staged window]; the pool is scattered into
        once, after the loop. Carrying the pool ping-pongs it in the while
        body — two extra full-pool buffers of compile-time temp (measured
        2.0 GiB pool → 4.28 GiB temp), which is what used to cap pool sizes
        far below HBM."""
        cfg = self.config.model
        block_size = self.config.cache.block_size
        if cfg.block_diffusion is not None:
            return self._build_bd_window_fn()

        @functools.partial(
            jax.jit,
            static_argnames=(
                "window", "want_logprobs", "want_min_tokens", "want_grammar"
            ),
            donate_argnames=("kv_caches",),
        )
        def decode_window_fn(
            params,
            lora_params,  # stacked adapter tree, or None when LoRA disabled
            kv_caches,
            first_tokens,  # (B,) input token per request
            positions0,  # (B,) first decode position per request
            block_tables,  # (B, max_blocks) covering the whole window
            lora_idx,  # (B,) adapter slot per row (None when disabled)
            temperature,  # (B,)
            top_p,  # (B,)
            top_k,  # (B,)
            base_key,
            seeds,  # (B,) uint32
            has_seed,  # (B,) bool
            counts0,  # (B,) output tokens generated before this window
            min_toks,  # (B,) min_tokens per row
            stop_ids,  # (B, SUPPRESS_IDS) eos/stop ids, -1 pad
            state_slots,  # as the prefill program's
            # structured output (docs/41-structured-output.md): the token-
            # class automaton runs ON DEVICE inside the window loop — the
            # precomputed tables arrive as DATA padded to (G, S, C) buckets,
            # so every iteration masks AND advances without a host hop and
            # constrained rows keep full window throughput
            gr_token_class=None,  # (G, V) int32 vocab token -> class
            gr_class_dest=None,  # (G, S, C) int32 dest state, -1 = reject
            gr_accepting=None,  # (G, S) bool — EOS allowed here
            gr_idx=None,  # (B,) int32 row -> grammar index, -1 unconstrained
            gr_state0=None,  # (B,) int32 automaton state entering the window
            gr_eos=None,  # (1,) int32 EOS token id
            window: int = 1,
            want_logprobs: bool = False,
            want_min_tokens: bool = False,
            want_grammar: bool = False,
        ):
            b = first_tokens.shape[0]
            out = jnp.zeros((b, window), jnp.int32)
            lp_out = jnp.zeros((b, window), jnp.float32)
            top_lp_out = jnp.zeros((b, window, LOGPROBS_TOPN), jnp.float32)
            top_id_out = jnp.zeros((b, window, LOGPROBS_TOPN), jnp.int32)
            staged = llama.init_staged_kv(
                cfg, window, b, kv_caches=kv_caches)
            windows = [spec.window for spec in cfg.layer_specs]
            # hoist the loop-invariant history gather out of the window loop
            # when this program's contiguous copy fits HBM headroom (static
            # per compiled (batch, nb, window) program — no runtime branch)
            s_ctx = (llama._group(block_tables, 0).shape[1]
                     * self.config.cache.block_size)
            hoist = (
                self._attention_backend == "xla"
                and not cfg.latent_layers  # (the latent fallback gathers
                #   per step)
                and self._hoist_bytes(b, s_ctx) <= self._hoist_budget
            )
            hists = None
            if hoist:
                with jax.named_scope("gather_pages"):
                    hists = tuple(
                        gather_pages(
                            llama.page_pool(kv_caches[i]),
                            llama._group(block_tables, w))
                        if cfg.layer_specs[i].holds_pages else None
                        for i, w in enumerate(windows)
                    )

            if want_grammar:
                has_gr = gr_idx >= 0  # (B,)
                g = jnp.clip(gr_idx, 0, gr_token_class.shape[0] - 1)
                tclass = gr_token_class[g]  # (B, V)
                # dead sink: the LAST padded state row is all -1 by
                # construction (_grammar_device_tables pads S to a bucket
                # strictly above any real state count), so a rejected
                # transition parks there and stays there
                dead = gr_class_dest.shape[1] - 1

            def body(k, carry):
                # an MoE model's carry ends with the experts touched so far
                if not cfg.num_experts:
                    return step(k, carry, None)
                *carry, moe_n = carry
                stats: list = []
                out = step(k, carry, stats)
                return (*out, moe_n + _moe_device_counts(cfg, stats))

            def step(k, carry, moe_stats):
                if want_grammar:
                    (staged, cur, out, lp_out, top_lp_out, top_id_out,
                     gstate) = carry
                else:
                    staged, cur, out, lp_out, top_lp_out, top_id_out = carry
                # pool history for row r is positions < positions0[r]; the
                # window's own tokens live in `staged` until the final commit
                hidden, staged = llama.decode_window_step(
                    cfg, params, cur, positions0 + k, kv_caches,
                    block_tables, staged, k, positions0,
                    backend=self._attention_backend,
                    lora=lora_params, lora_idx=lora_idx, hists=hists,
                    mesh=self.mesh, moe_stats=moe_stats,
                    moe_backend=self._moe_backend, state_slots=state_slots,
                )
                logits = llama.compute_logits(cfg, params, hidden)
                with jax.named_scope("sample"):
                    if want_grammar:
                        # the automaton advances ON DEVICE: mask from the
                        # current state's class row, sample, then step the
                        # state — so a constrained row accepts the whole
                        # window like any other row instead of bailing after
                        # one host-masked token
                        dest_c = gr_class_dest[g, gstate]  # (B, C)
                        allowed = jnp.take_along_axis(
                            dest_c >= 0, tclass, axis=1
                        )  # (B, V)
                        # EOS is not a grammar byte: allowed exactly in
                        # accepting states (empty-content tokens hold dest -1
                        # everywhere, so BOS/PAD stay rejected)
                        allowed = allowed.at[:, gr_eos[0]].set(
                            gr_accepting[g, gstate]
                        )
                        allowed = allowed | ~has_gr[:, None]
                        logits = apply_grammar_mask(logits, allowed)
                    if want_min_tokens:
                        logits = suppress_stop_tokens(
                            logits, counts0 + k, min_toks, stop_ids
                        )
                    toks = sample(
                        logits, temperature, top_p, top_k,
                        jax.random.fold_in(base_key, k),
                        seeds, has_seed, counts0 + k,
                    )
                    if want_logprobs:
                        chosen, top_lp, top_id = _collect_logprobs(
                            logits, toks)
                        lp_out = lp_out.at[:, k].set(chosen)
                        top_lp_out = top_lp_out.at[:, k].set(top_lp)
                        top_id_out = top_id_out.at[:, k].set(top_id)
                    if want_grammar:
                        tcls = jnp.take_along_axis(
                            tclass, toks[:, None], axis=1)
                        nxt = jnp.take_along_axis(dest_c, tcls, axis=1)[:, 0]
                        gstate = jnp.where(
                            has_gr, jnp.where(nxt >= 0, nxt, dead), gstate
                        )
                        return (
                            staged, toks, out.at[:, k].set(toks),
                            lp_out, top_lp_out, top_id_out, gstate,
                        )
                    return (
                        staged, toks, out.at[:, k].set(toks),
                        lp_out, top_lp_out, top_id_out,
                    )

            moe_out = (
                (jnp.zeros_like(_moe_device_counts(cfg, [])),)
                if cfg.num_experts else ())
            if want_grammar:
                (staged, _, out, lp_out, top_lp_out, top_id_out,
                 gstates, *moe_out) = jax.lax.fori_loop(
                    0, window, body,
                    (staged, first_tokens, out, lp_out, top_lp_out,
                     top_id_out, gr_state0, *moe_out),
                )
            else:
                gstates = None
                (staged, _, out, lp_out,
                 top_lp_out, top_id_out, *moe_out) = jax.lax.fori_loop(
                    0, window, body,
                    (staged, first_tokens, out, lp_out, top_lp_out,
                     top_id_out, *moe_out),
                )
            # commit the window's KV to the pool: slots for row r, step k are
            # position positions0[r] + k via the row's block table
            pos = positions0[:, None] + jnp.arange(window, dtype=jnp.int32)[None, :]

            def slots_of(tables):
                blk = jnp.take_along_axis(tables, pos // block_size, axis=1)
                return (blk * block_size + pos % block_size).reshape(-1)

            # (a table a page group: a window layer's slots are its own)
            slots = jax.tree.map(slots_of, block_tables)
            kv_caches = llama.commit_staged_kv(
                kv_caches, staged, slots, cfg.window_layers)
            # grammar programs also return the final per-row automaton
            # state — a chained next window gathers its gr_state0 from it
            # on device (same pattern as chain_vec for tokens)
            ret = (kv_caches, out)
            if want_logprobs:
                ret = ret + ((lp_out, top_lp_out, top_id_out),)
            if want_grammar:
                ret = ret + (gstates,)
            return ret + tuple(moe_out)

        return decode_window_fn

    def _build_bd_window_fn(self):
        """The decode-window program of a model that generates by diffusion
        over blocks (docs/44-block-diffusion.md): a window is `window`
        positions = window / block_length blocks a row, and the forward
        passes run ON DEVICE in one `while_loop` of identical passes —
        "forward over each row's open block from its current tokens, stage
        its K/V, unmask where masks remain" (llama.decode_block_step,
        llama.bd_transfer). A block ends after the first pass in which NO
        row's block held a mask when it began: that pass ran over every
        row's final tokens, so it IS every row's commit pass (a row that
        filled its block earlier has repeated an idempotent pass:
        `repeat_row_passes`). So a window yields exactly `window` positions
        a row whatever the confidences, `commit_staged_kv` scatters final
        K/V once a window, and only the NUMBER of passes is data — inside
        one program, so no pass count needs a program of its own.

        Rows lock step by block: all rows work on block j of their windows
        until the slowest has committed it. A row's first `n_given`
        positions hold tokens it already has (the p mod block_length tail
        of a prompt): never masked, never sampled."""
        cfg = self.config.model
        bd = cfg.block_diffusion
        block_size = self.config.cache.block_size
        blk_len = bd.block_length

        @functools.partial(
            jax.jit,
            static_argnames=(
                "window", "want_logprobs", "want_min_tokens", "want_grammar"
            ),
            donate_argnames=("kv_caches",),
        )
        def bd_window_fn(
            params,
            lora_params,  # None: refused for such a model
            kv_caches,
            tokens0,  # (B, W) the window's tokens: given ones, then masks
            n_given,  # (B,) leading positions that hold given tokens
            live,  # (B,) bool: the row is a request's (padding rows are not)
            positions0,  # (B,) the window's first position per row
            block_tables,  # (B, max_blocks) covering the whole window
            temperature,  # (B,)
            top_p,  # (B,)
            top_k,  # (B,)
            base_key,
            seeds,  # (B,) uint32
            has_seed,  # (B,) bool
            counts0,  # (B,) output tokens generated before this window
            window: int = 1,
            want_logprobs: bool = False,
            want_min_tokens: bool = False,  # (never: refused a request)
            want_grammar: bool = False,  # (never: refused a request)
        ):
            b = tokens0.shape[0]
            n_blocks = window // blk_len
            slot = jnp.arange(window, dtype=jnp.int32)
            masked0 = slot[None, :] >= n_given[:, None]
            staged = llama.init_staged_kv(cfg, window, b)
            s_ctx = block_tables.shape[1] * block_size
            hists = None
            if (self._attention_backend == "xla"
                    and self._hoist_bytes(b, s_ctx) <= self._hoist_budget):
                with jax.named_scope("gather_pages"):
                    hists = tuple(
                        gather_pages(kv, block_tables) for kv in kv_caches)
            # the output index of the token at each window slot: what the
            # seeded draw folds in (a position's own, whatever the pass)
            out_idx = counts0[:, None] + slot[None, :] - n_given[:, None]

            def body(c):
                blk, pas = c["blk"], c["pass"]
                slot0 = blk * blk_len
                cur = jax.lax.dynamic_slice(c["tokens"], (0, slot0),
                                            (b, blk_len))
                m = jax.lax.dynamic_slice(c["masked"], (0, slot0),
                                          (b, blk_len))
                had_mask = jnp.any(m, axis=1)  # (B,) when the pass began
                pos = (positions0[:, None] + slot0
                       + jnp.arange(blk_len, dtype=jnp.int32)[None, :])
                stats: list | None = [] if cfg.num_experts else None
                hidden, staged = llama.decode_block_step(
                    cfg, params, cur, pos, kv_caches, block_tables,
                    c["staged"], slot0, positions0,
                    backend=self._attention_backend, hists=hists,
                    moe_stats=stats, moe_backend=self._moe_backend,
                )
                logits = llama.compute_logits(
                    cfg, params, hidden.reshape(b * blk_len, -1)
                ).reshape(b, blk_len, -1)
                with jax.named_scope("sample"):
                    # greedy rows take the argmax; the draw is skipped at
                    # run time where no row samples (sampling.sample)
                    rep = lambda a: jnp.repeat(a, blk_len)
                    x0 = sample(
                        logits.reshape(b * blk_len, -1), rep(temperature),
                        rep(top_p), rep(top_k),
                        jax.random.fold_in(base_key, c["n"][0]),
                        rep(seeds), rep(has_seed),
                        jax.lax.dynamic_slice(
                            out_idx, (0, slot0), (b, blk_len)).reshape(-1),
                    ).reshape(b, blk_len)
                new, m_new, pick, over = llama.bd_transfer(
                    bd, logits, cur, m, pas, x0, temperature)
                llama.tap_block_pass(blk, pas, pos, cur, pick, new, stats)
                out = dict(c)
                out["staged"] = staged
                out["tokens"] = jax.lax.dynamic_update_slice(
                    c["tokens"], new, (0, slot0))
                out["masked"] = jax.lax.dynamic_update_slice(
                    c["masked"], m_new, (0, slot0))
                if want_logprobs:
                    # a token's logprobs are those of the pass that
                    # unmasked it
                    chosen, top_lp, top_id = _collect_logprobs(
                        logits.reshape(b * blk_len, -1), new.reshape(-1))
                    for key, val in (("lp", chosen), ("top_lp", top_lp),
                                     ("top_id", top_id)):
                        val = val.reshape(b, blk_len, *val.shape[1:])
                        old = jax.lax.dynamic_slice_in_dim(
                            c[key], slot0, blk_len, axis=1)
                        sel = pick.reshape(pick.shape + (1,) * (val.ndim - 2))
                        out[key] = jax.lax.dynamic_update_slice_in_dim(
                            c[key], jnp.where(sel, val, old), slot0, axis=1)
                n_live = jnp.sum(live, dtype=jnp.int32)
                done_rows = live & ~had_mask
                # the block is done once no row's held a mask going in —
                # or, in a faulty build (tests only), once none holds one
                # coming out: the last denoising pass's K/V are kept
                done = ~jnp.any(had_mask if _BD_COMMIT_PASS
                                else jnp.any(m_new, axis=1))
                out["n"] = c["n"] + jnp.stack([
                    jnp.int32(1), n_live,
                    # a row's ONE pass over final tokens a block is its
                    # commit; what it runs beyond that it repeats
                    jnp.sum(done_rows, dtype=jnp.int32)
                    - jnp.where(done, n_live, 0),
                    jnp.where(done, n_live, 0),
                    jnp.sum(jnp.where(live[:, None], pick, False),
                            dtype=jnp.int32),
                    jnp.sum(jnp.where(live, over, 0), dtype=jnp.int32),
                ])
                if cfg.num_experts:
                    out["moe"] = c["moe"] + _moe_device_counts(cfg, stats)
                out["blk"] = blk + done.astype(jnp.int32)
                out["pass"] = jnp.where(done, 0, pas + 1)
                return out

            carry = {
                "blk": jnp.int32(0), "pass": jnp.int32(0),
                "tokens": tokens0, "masked": masked0, "staged": staged,
                "n": jnp.zeros(len(BD_COUNTS), jnp.int32),
            }
            if cfg.num_experts:
                carry["moe"] = jnp.zeros_like(_moe_device_counts(cfg, []))
            if want_logprobs:
                carry["lp"] = jnp.zeros((b, window), jnp.float32)
                carry["top_lp"] = jnp.zeros(
                    (b, window, LOGPROBS_TOPN), jnp.float32)
                carry["top_id"] = jnp.zeros(
                    (b, window, LOGPROBS_TOPN), jnp.int32)
            c = jax.lax.while_loop(
                lambda c: c["blk"] < n_blocks, body, carry)
            pos = positions0[:, None] + slot[None, :]
            blk = jnp.take_along_axis(block_tables, pos // block_size, axis=1)
            slots = (blk * block_size + pos % block_size).reshape(-1)
            kv_caches = llama.commit_staged_kv(kv_caches, c["staged"], slots)
            ret = (kv_caches, c["tokens"])
            if want_logprobs:
                ret = ret + ((c["lp"], c["top_lp"], c["top_id"]),)
            ret = ret + (c["n"],)
            return ret + ((c["moe"],) if cfg.num_experts else ())

        return bd_window_fn

    def _build_verify_fn(self):
        """Speculative-verification program (engine/spec_decode.py): a
        chunked-prefill-shaped forward over [current token + proposals] with
        GREEDY argmax at EVERY position (sampling.greedy_argmax — the same
        pick the decode window's temperature-0 branch makes) — m[j]
        confirms or replaces the proposal for position j+1, so one dispatch
        yields 1..k+1 tokens per row. Same paged attention + blockwise KV
        commit as prefill. Also returns each row's LAST usable prediction
        (the full-acceptance bonus token) as a (B,) vector so the pipelined
        loop can chain the next decode window's input from this still-in-
        flight step without a host round trip."""
        cfg = self.config.model

        @functools.partial(
            jax.jit,
            static_argnames=("want_grammar",),
            donate_argnames=("kv_caches",),
        )
        def verify_fn(
            params,
            lora_params,
            kv_caches,
            token_ids,  # (B, T) fed tokens: [cur, p0..pk-1], padded
            positions,  # (B, T)
            block_tables,  # (B, nb)
            context_lens,  # (B,) resident after this step
            chunk_lens,  # (B,) real fed tokens per row
            write_ids,  # (B, NBW)
            start_off,  # (B,)
            lora_idx,
            # structured output: per-position admissibility, host-built from
            # the host-known proposals (TokenGrammar.verify_masks) — "the
            # verifier masks, the proposer need not": a grammar-violating
            # draft token just loses the argmax match and gets cut by the
            # normal acceptance scan, riding PR 14's rollback machinery
            grammar_mask=None,  # (B, T, V) bool
            want_grammar: bool = False,
        ):
            hidden, kv_caches = llama.forward(
                cfg, params, token_ids, positions, kv_caches,
                block_tables, jnp.zeros((1,), jnp.int32), context_lens,
                lora=lora_params, lora_idx=lora_idx,
                write_blocks={
                    "ids": write_ids,
                    "start_off": start_off,
                    "chunk_lens": chunk_lens,
                },
                moe_backend=self._moe_backend,
            )
            logits = llama.compute_logits(
                cfg, params, hidden.reshape(-1, hidden.shape[-1])
            )
            with jax.named_scope("sample"):
                if want_grammar:
                    logits = apply_grammar_mask(
                        logits,
                        grammar_mask.reshape(-1, grammar_mask.shape[-1]),
                    )
                toks = greedy_argmax(logits)
                mat = toks.reshape(hidden.shape[0], hidden.shape[1])
                # row i's bonus token under full acceptance sits at its last
                # real fed column — the chain source for a dispatched-on-top
                # decode window
                nxt = jnp.take_along_axis(
                    mat, jnp.maximum(chunk_lens - 1, 0)[:, None], axis=1
                )[:, 0]
            return kv_caches, mat, nxt

        return verify_fn

    def _dispatch_verify(
        self, work: VerifyWork, prev: StepHandle | None = None
    ) -> StepHandle:
        """Dispatch one speculative-verify step WITHOUT syncing results —
        the pipelined loop's verify entry point (the serial path resolves
        the returned handle immediately). The handle carries `chain_vec`,
        the on-device per-row bonus-token vector a chained next step
        (decode window OR another verify) gathers its input from. Rows
        whose work.chain_rows entry is >= 0 take their FIRST fed token
        from `prev`'s chain_vec — the still-unresolved bonus token of the
        in-flight verify they stack on."""
        # logprobs requests are routed away from the verify path
        # (scheduler._schedule_decode_or_verify)
        self.last_logprobs = None
        sched = self.config.scheduler
        b = len(work.requests)
        b_pad = sched.bucket_for(b, sched.decode_buckets)
        t = max(len(row) for row in work.token_ids)
        t_pad = max(2, self._pow2(t))  # tiny chunks: k+1 <= 8 typically

        bs = self.config.cache.block_size
        nbw = (t_pad - 1) // bs + 2
        token_ids = np.zeros((b_pad, t_pad), np.int32)
        positions = np.zeros((b_pad, t_pad), np.int32)
        context_lens = np.zeros(b_pad, np.int32)
        chunk_lens = np.zeros(b_pad, np.int32)
        write_ids = np.zeros((b_pad, nbw), np.int32)
        start_off = np.zeros(b_pad, np.int32)
        lora_idx = np.zeros(b_pad, np.int32)
        for i, req in enumerate(work.requests):
            row = work.token_ids[i]
            token_ids[i, : len(row)] = row
            positions[i, : len(row)] = work.positions[i]
            context_lens[i] = work.context_lens[i]
            chunk_lens[i] = len(row)
            hist = work.context_lens[i] - len(row)
            first_blk = hist // bs
            n_span = (work.context_lens[i] - 1) // bs - first_blk + 1
            write_ids[i, :n_span] = req.block_table[first_blk : first_blk + n_span]
            start_off[i] = hist % bs
            lora_idx[i] = req.lora_index
        write_ids = self._with_window_span(write_ids, work, bs)
        block_tables = self._group_tables(work.requests, pad_to=b_pad)
        # structured output: per-position masks are host-buildable because a
        # verify row's fed tokens are host-known (the scheduler never chains
        # a constrained row's verify input from an in-flight step). Position
        # j's logits predict the token after fed[0..j], and fed[0] is the
        # last ACCEPTED token — already consumed by the host cursor — so
        # verify_masks(state, proposals, width) lines up exactly.
        want_gr = any(
            r.sampling.grammar is not None and r.grammar is not None
            for r in work.requests
        )
        grammar_mask = None
        if want_gr:
            v = self.config.model.vocab_size
            grammar_mask = np.ones((b_pad, t_pad, v), dtype=bool)
            for i, req in enumerate(work.requests):
                if req.sampling.grammar is None or req.grammar is None:
                    continue
                req.grammar.sync(req.output_token_ids)
                if req.grammar.state < 0:
                    continue  # dead cursor (can't be live) — unconstrained
                grammar_mask[i, : len(work.token_ids[i])] = (
                    req.sampling.grammar.verify_masks(
                        req.grammar.state,
                        work.token_ids[i][1:],
                        len(work.token_ids[i]),
                    )
                )
        if self._sleeping_params_host is not None:
            raise RuntimeError("engine is sleeping; wake it before running")
        # verify programs have no pad-up lattice: the synthetic key exists
        # so compile telemetry covers this dispatch path too
        vkey = ("verify", b_pad, t_pad, nbw, want_gr)
        new_program = vkey not in self._verify_keys
        self.compile_watch.record_dispatch(
            vkey, not new_program, role=self.compile_role
        )
        t0 = time.perf_counter()
        # verify draws no RNG (pure argmax): rng_before == rng after, so a
        # discard()'s rewind is a no-op — recorded anyway for uniformity
        rng_before = self._rng
        clock = self.clock
        with clock.span("h2d", kind="verify"):
            toks_dev = self._put(token_ids, self._batch2)
            dyn_args = (
                self._put(positions, self._batch2),
                self._put(block_tables, self._batch2),
                self._put(context_lens, self._batch1),
                self._put(chunk_lens, self._batch1),
                self._put(write_ids, self._batch2),
                self._put(start_off, self._batch1),
                self._put(lora_idx, self._batch1) if self._use_lora else None,
            )
            gm_dev = self._put(grammar_mask, self._batch1) if want_gr else None
        if work.chain_rows and any(c >= 0 for c in work.chain_rows):
            if prev is None or prev.chain_vec is None:
                raise RuntimeError(
                    "chained verify rows need an in-flight verify handle "
                    "(chain_vec) to splice their first fed token from"
                )
            idx = np.full(b_pad, -1, np.int32)
            idx[: len(work.chain_rows)] = work.chain_rows
            with clock.span("h2d", kind="verify"):
                idx_dev = self._put(idx, self._batch1)
            with clock.span("enqueue", kind="verify"):
                toks_dev = self._chain_verify_fn(
                    prev.chain_vec, toks_dev, idx_dev
                )
        with clock.span("enqueue", kind="verify"):
            self.kv_caches, toks, nxt = self._verify_fn(
                self.params, self.lora_params, self.kv_caches, toks_dev,
                *dyn_args, grammar_mask=gm_dev, want_grammar=want_gr,
            )
        if new_program:
            self._verify_keys.add(vkey)
            self._watch_sync_compile(
                "verify", vkey, time.perf_counter() - t0, work.requests
            )
        handle = StepHandle(
            runner=self, work=work, tokens=toks, lp_arrays=None,
            rng_before=rng_before,
            postproc=functools.partial(self._verify_rows, work, b),
        )
        handle.chain_vec = nxt
        return handle

    @staticmethod
    def _verify_rows(work: VerifyWork, b: int, mat, lp):
        """Host-side row building for a resolved verify handle: row i's
        usable predictions are its first len(fed) positions."""
        del lp
        return [
            list(map(int, mat[i, : len(work.token_ids[i])]))
            for i in range(b)
        ], None

    def _execute_verify(self, work: VerifyWork) -> list[list[int]]:
        handle = self._dispatch_verify(work)
        return handle.resolve()

    # -- public API --------------------------------------------------------

    def execute(self, work: ScheduleOutput) -> list[list[int]]:
        """Run one scheduled step; returns one token row per request
        (prefill: [[tok]] if work.sample else [[]]; decode: up to `window`
        candidate tokens per request; verify: argmax at every fed
        position)."""
        if isinstance(work, VerifyWork):
            return self._execute_verify(work)
        handle = self.execute_async(work)
        rows = handle.resolve()
        self.last_logprobs = handle.logprob_rows
        return rows

    def execute_async(
        self, work: ScheduleOutput, prev: StepHandle | None = None
    ) -> StepHandle:
        """Dispatch one step WITHOUT syncing its results — the async
        pipeline's entry point. `prev` is the still-unresolved previous
        decode/verify step; rows whose work.chain_rows entry is >= 0 take
        their input token from its device-resident output (no host round
        trip) — a decode row chains its single input token, a chained
        verify row chains its FIRST fed token (the in-flight verify's
        bonus token; its remaining fed tokens are the host-proposed
        continuation). Resolve the returned handle to get the token
        rows."""
        if isinstance(work, PrefillWork):
            return self._dispatch_prefill(work)
        if isinstance(work, DecodeWork):
            return self._dispatch_decode(work, prev)
        if isinstance(work, VerifyWork):
            return self._dispatch_verify(work, prev)
        raise TypeError(
            f"cannot dispatch {type(work).__name__} asynchronously"
        )

    def _dispatch_prefill(self, work: PrefillWork) -> StepHandle:
        """One dispatch for the whole prefill batch, PACKED: the rows'
        chunk tokens laid end to end on one axis padded to the chunk bucket
        of their SUM (`prefill_bucket`; the scheduler's token budget bounds
        it, and the draft proposer sizes its rows by `prefill_row_room`), so
        the program computes the tokens it was given whatever the number of
        rows; the per-row arrays (tables, lengths, sampling) pad to a power
        of two. Under dp > 1 rows shard over replicas, and under sp > 1 the
        chunk axis over devices: there every row pads to the bucket of the
        longest, (b_pad, t_pad). Every row samples at its chunk's last token
        (static shapes); non-sampling rows' tokens are discarded host-side."""
        sched = self.config.scheduler
        b = len(work.requests)
        b_pad = self._batch_bucket(b)
        packed = self.prefill_packed
        t_pad = self.prefill_bucket([len(row) for row in work.token_ids])
        want_lp = any(
            work.sample[i] and req.sampling.logprobs is not None
            for i, req in enumerate(work.requests)
        )
        want_mt = any(r.sampling.min_tokens > 0 for r in work.requests)
        # structured output: only SAMPLING rows constrain — a mid-prompt
        # chunk produces no token, so its mask would be dead weight
        want_gr = any(
            work.sample[i]
            and req.sampling.grammar is not None
            and req.grammar is not None
            for i, req in enumerate(work.requests)
        )
        nb = self._width_bucket(
            max((len(r.block_table) for r in work.requests), default=1)
        )
        self.clock.poll()  # (the first of this build's polls: see below)
        # a first-seen program key pads up to an already-compiled shape
        # instead of stalling serving on a synchronous XLA compile
        exact_key = ("prefill", b_pad, t_pad, nb, want_lp, want_mt, want_gr)
        aot_key = self._pick_prefill_shape(
            b_pad, t_pad, nb, want_lp, want_mt, want_gr
        )
        self._watch_dispatch(exact_key, aot_key)
        _, b_pad, t_pad, nb, _lp, use_mt, use_gr = aot_key
        self.clock.poll()

        tok_shape = (t_pad,) if packed else (b_pad, t_pad)
        token_ids = np.zeros(tok_shape, np.int32)
        positions = np.zeros(tok_shape, np.int32)
        # per-token slots feed only the sp path's row scatter; the paged path
        # commits blockwise (write_ids below) and takes a placeholder
        slots = (
            np.zeros((b_pad, t_pad), np.int32)  # padding -> null page
            if self._sp > 1
            else None
        )
        context_lens = np.zeros(b_pad, np.int32)
        chunk_lens = np.zeros(b_pad, np.int32)
        # blockwise KV commit: a T_pad chunk starting at worst-case offset
        # bs-1 spans (T_pad-1)//bs + 2 pool pages; padding -> null page
        bs = self.config.cache.block_size
        nbw = (t_pad - 1) // bs + 2
        write_ids = np.zeros((b_pad, nbw), np.int32)
        start_off = np.zeros(b_pad, np.int32)
        sample_rows = np.zeros(b_pad, np.int32)
        temps = np.zeros(b_pad, np.float32)
        top_ps = np.ones(b_pad, np.float32)
        top_ks = np.zeros(b_pad, np.int32)
        seeds: list[int | None] = [None] * b_pad
        counts = np.zeros(b_pad, np.int32)
        row_off = span_pages = 0
        for i, req in enumerate(work.requests):
            row = work.token_ids[i]
            # row i's tokens: the next len(row) of the packed axis, or the
            # head of row i
            at = (
                slice(row_off, row_off + len(row)) if packed
                else (i, slice(len(row)))
            )
            token_ids[at] = row
            positions[at] = work.positions[i]
            if slots is not None:
                slots[i, : len(row)] = work.slot_mappings[i]
            context_lens[i] = work.context_lens[i]
            chunk_lens[i] = len(row)
            hist = work.context_lens[i] - len(row)
            first_blk = hist // bs
            n_span = (work.context_lens[i] - 1) // bs - first_blk + 1
            write_ids[i, :n_span] = req.block_table[first_blk : first_blk + n_span]
            span_pages += n_span
            start_off[i] = hist % bs
            sample_rows[i] = (row_off if packed else i * t_pad) + len(row) - 1
            row_off += len(row)
            s = req.sampling
            temps[i], top_ps[i], top_ks[i] = s.temperature, s.top_p, s.top_k
            seeds[i] = s.seed
            counts[i] = len(req.output_token_ids)
        # (the recorder's polls between phase boundaries: the build of a
        # 2,048-token chunk is long enough to leave a dry gap's bracket wide)
        self.clock.poll()
        write_ids = self._with_window_span(write_ids, work, bs)
        block_tables = self._group_tables(
            work.requests, pad_to=b_pad, width=nb)
        self.clock.poll()
        lora_idx = np.zeros(b_pad, np.int32)
        for i, req in enumerate(work.requests):
            lora_idx[i] = req.lora_index
        min_toks, stop_ids_arr = self._stop_id_arrays(work.requests, b_pad)
        # the prefill sample's admissible-token mask is host-built: the
        # automaton state is host-known (sync replays accepted outputs, so
        # resumed-after-preempt rows land on the right cursor too)
        grammar_mask = None
        if want_gr:
            v = self.config.model.vocab_size
            grammar_mask = np.ones((b_pad, v), dtype=bool)
            for i, req in enumerate(work.requests):
                if (
                    work.sample[i]
                    and req.sampling.grammar is not None
                    and req.grammar is not None
                ):
                    req.grammar.sync(req.output_token_ids)
                    grammar_mask[i] = req.grammar.mask()
            with self.clock.span("h2d", kind="prefill"):
                grammar_mask = self._put(grammar_mask, self._batch2)
        elif use_gr:
            # a grammar-enabled program serving an unconstrained batch
            # (shape dominance): all-ones mask is the identity, cached per
            # batch bucket like the zero stop arrays
            grammar_mask = self._ones_mask_cache.get(b_pad)
            if grammar_mask is None:
                with self.clock.span("h2d", kind="prefill"):
                    grammar_mask = self._put(
                        np.ones(
                            (b_pad, self.config.model.vocab_size), dtype=bool
                        ),
                        self._batch2,
                    )
                self._ones_mask_cache[b_pad] = grammar_mask
        tokens_dev, lp_dev, rng_before, moe = self._run(
            token_ids, positions, block_tables,
            slots.reshape(-1) if slots is not None else np.zeros(1, np.int32),
            context_lens, chunk_lens, write_ids, start_off, lora_idx,
            sample_rows, temps, top_ps, top_ks, seeds=seeds, counts=counts,
            min_toks=min_toks, stop_ids_arr=stop_ids_arr,
            grammar_mask=grammar_mask,
            # use_mt may exceed want_mt (an mt=True program serves mt=False
            # batches: suppression is a no-op at min_toks=0); likewise a
            # gr=True program serves gr=False via the all-ones identity mask
            want_logprobs=want_lp, want_min_tokens=use_mt,
            want_grammar=use_gr,
            aot_key=aot_key, watch_reqs=work.requests,
            state=self._state_slot_arrays(work.requests, b_pad),
        )
        handle = StepHandle(
            runner=self, work=work, tokens=tokens_dev, lp_arrays=lp_dev,
            rng_before=rng_before,
            postproc=functools.partial(self._prefill_rows, work, b),
        )
        handle.moe = moe
        handle.positions = token_ids.size
        cfg = self.config.model
        if self._sp == 1 and len(cfg.page_layers) > len(cfg.latent_layers):
            handle.kv_write_pages = (
                (span_pages, 0) if self.prefill_writes_by_kernel
                else (b_pad * nbw,) * 2)
        handle.chain_vec = tokens_dev
        return handle

    @staticmethod
    def _prefill_rows(work: PrefillWork, b: int, tokens, lp):
        """Host-side row building for a resolved prefill handle."""
        if lp is None:
            lp_rows = None
        else:
            chosen, top_lp, top_id = lp
            lp_rows = [
                (
                    [(float(chosen[i]),
                      list(map(int, top_id[i])),
                      list(map(float, top_lp[i])))]
                    if work.sample[i]
                    else []
                )
                for i in range(b)
            ]
        rows = [
            [int(tokens[i])] if work.sample[i] else [] for i in range(b)
        ]
        return rows, lp_rows

    def _pick_decode_program(self, work: DecodeWork, want_lp: bool,
                             want_mt: bool, gkey) -> tuple:
        """The key of the compiled decode program this window runs on:
        never stall a decode window on a first-seen program key."""
        sched = self.config.scheduler
        b_pad = sched.bucket_for(len(work.requests), sched.decode_buckets)
        nb = self._width_bucket(
            max((len(r.block_table) for r in work.requests), default=1)
        )
        exact_key = ("decode", b_pad, nb, work.window, want_lp, want_mt, gkey)
        aot_key = self._pick_decode_shape(
            b_pad, nb, work.window, want_lp, want_mt, gkey
        )
        self._watch_dispatch(exact_key, aot_key)
        return aot_key

    def _decode_sampling_args(self, requests, b_pad: int):
        """(the rng before this window's split, the sampling arguments of a
        decode program in its order: each a host array and its sharding)."""
        pad = b_pad - len(requests)
        temps = [r.sampling.temperature for r in requests] + [0.0] * pad
        top_ps = [r.sampling.top_p for r in requests] + [1.0] * pad
        top_ks = [r.sampling.top_k for r in requests] + [0] * pad
        seeds = [r.sampling.seed for r in requests] + [None] * pad
        # effective output counts: tokens still in flight from the previous
        # step count as generated (seeded-sampling fold and min_tokens
        # suppression must see the serial-world counter); 0 on the sync path
        counts = [
            len(r.output_token_ids) + r.num_inflight_outputs
            for r in requests
        ] + [0] * pad
        rng_before = self._rng
        self._rng, step_key = jax.random.split(self._rng)
        has_seed = np.asarray([s is not None for s in seeds], bool)
        seed_vals = np.asarray([(s or 0) & 0xFFFFFFFF for s in seeds], np.uint32)
        return rng_before, (
            (np.asarray(temps, np.float32), self._batch1),
            (np.asarray(top_ps, np.float32), self._batch1),
            (np.asarray(top_ks, np.int32), self._batch1),
            (step_key, self._rep),
            (seed_vals, self._batch1),
            (has_seed, self._batch1),
            (np.asarray(counts, np.int32), self._batch1),
        )

    def _call_decode_program(self, aot_key: tuple, work: DecodeWork,
                             lora_params, dyn_args: tuple, **static):
        """Enqueue the window on its ahead-of-time executable, or through
        jit with the compile noted and watched."""
        clock = self.clock
        aot = self._aot_exec.get(aot_key)
        if aot is not None:
            with clock.span("enqueue", kind="decode"):
                return aot(
                    self.params, lora_params, self.kv_caches, *dyn_args
                )
        with self._bg_lock:
            new_program = aot_key not in self._compiled_keys
        t0 = time.perf_counter()
        with clock.span("enqueue", kind="decode"):
            result = self._decode_window_fn(
                self.params, lora_params, self.kv_caches, *dyn_args,
                window=work.window, **static,
            )
        self._note_compiled(aot_key)
        if new_program:
            self._watch_sync_compile(
                "decode", aot_key, time.perf_counter() - t0, work.requests,
            )
        return result

    def _dispatch_decode(
        self, work: DecodeWork, prev: StepHandle | None = None
    ) -> StepHandle:
        if self._sleeping_params_host is not None:
            raise RuntimeError("engine is sleeping; wake it before running")
        if self.config.model.block_diffusion is not None:
            return self._dispatch_bd_decode(work)
        b = len(work.requests)
        want_lp = any(
            r.sampling.logprobs is not None for r in work.requests
        )
        want_mt = any(r.sampling.min_tokens > 0 for r in work.requests)
        # structured output: distinct grammars in this batch, by identity
        # (TokenGrammar.uid) — gr_idx maps each row to its table slot
        grammars: list = []
        g_uid_to_slot: dict[int, int] = {}
        row_slots: list[tuple[int, int]] = []  # (row, table slot)
        for i, req in enumerate(work.requests):
            g = req.sampling.grammar
            if g is None or req.grammar is None:
                continue
            slot = g_uid_to_slot.get(g.uid)
            if slot is None:
                slot = len(grammars)
                g_uid_to_slot[g.uid] = slot
                grammars.append(g)
            row_slots.append((i, slot))
        want_gr = bool(grammars)
        gkey = None
        if want_gr:
            # pads are part of the PROGRAM KEY: bigger tables are the same
            # program, so dominance pads tables up instead of recompiling.
            # s_pad strictly exceeds every real state count, which makes
            # row s_pad-1 all -1 — the dead sink rejected transitions park in
            gkey = (
                self._pow2(len(grammars)),
                self._pow2(max(g.n_states for g in grammars) + 1),
                self._pow2(max(g.n_classes for g in grammars)),
            )
        aot_key = self._pick_decode_program(work, want_lp, want_mt, gkey)
        _, b_pad, nb, _w, _lp, use_mt, use_gkey = aot_key

        clock = self.clock
        first_tokens = np.zeros(b_pad, np.int32)
        first_tokens[:b] = work.token_ids
        chain = work.chain_rows
        idx_dev = None
        chained = any(c >= 0 for c in chain)
        if chained:
            # chained rows read their input token straight from the
            # previous (still in-flight) step's device output — the
            # D2H→H2D round trip the pipeline eliminates
            if prev is None:
                raise RuntimeError(
                    "decode work chains rows but no previous StepHandle "
                    "was supplied"
                )
            idx = np.full(b_pad, -1, np.int32)
            idx[: len(chain)] = chain
        positions0 = np.zeros(b_pad, np.int32)
        positions0[:b] = work.positions
        block_tables = self._group_tables(
            work.requests, pad_to=b_pad, width=nb)
        rng_before, sampling = self._decode_sampling_args(
            work.requests, b_pad)
        lora_idx = np.zeros(b_pad, np.int32)
        for i, req in enumerate(work.requests):
            lora_idx[i] = req.lora_index
        min_toks, stop_ids_arr = self._stop_id_arrays(work.requests, b_pad)
        with clock.span("h2d", kind="decode"):
            ft = self._put(first_tokens, self._batch1)
            if chained:
                idx_dev = self._put(idx, self._batch1)
        if chained:
            with clock.span("enqueue", kind="decode"):
                if prev.chain_vec is not None:  # a verify, or a prefill
                    ft = self._chain_vec_fn(prev.chain_vec, ft, idx_dev)
                else:
                    ft = self._chain_fn(prev.tokens, ft, idx_dev)
        put = self._put_polled
        with clock.span("h2d", kind="decode"):
            dyn_args = (
                ft,
                put(positions0, self._batch1),
                put(block_tables, self._batch2),
                put(lora_idx, self._batch1) if self._use_lora else None,
                *(put(a, sharding) for a, sharding in sampling),
                put(min_toks, self._batch1),
                put(stop_ids_arr, self._batch2),
                self._state_slot_arrays(work.requests, b_pad),
            )
        if want_gr:
            tc_dev, cd_dev, acc_dev = self._grammar_device_tables(
                grammars, use_gkey
            )
            gr_idx = np.full(b_pad, -1, np.int32)
            gs0 = np.zeros(b_pad, np.int32)
            grammar_chains = False
            for i, slot in row_slots:
                gr_idx[i] = slot
                req = work.requests[i]
                if chain[i] >= 0:
                    # input token is still in flight: the row's entering
                    # state rides the previous handle's device-side
                    # grammar_states vector instead of the host cursor
                    grammar_chains = True
                else:
                    req.grammar.sync(req.output_token_ids)
                    # a dead host cursor (-1) maps to the device dead sink
                    # (last padded state row) — never to a clamped index
                    gs0[i] = (
                        req.grammar.state
                        if req.grammar.state >= 0
                        else use_gkey[1] - 1
                    )
            with clock.span("h2d", kind="decode"):
                gs_dev = self._put(gs0, self._batch1)
                gr_idx_dev = self._put(gr_idx, self._batch1)
                if self._gr_eos_dev is None:
                    self._gr_eos_dev = self._put(
                        np.asarray([grammars[0].eos_token_id], np.int32),
                        self._rep,
                    )
            if grammar_chains:
                if prev is None or prev.grammar_states is None:
                    raise RuntimeError(
                        "constrained decode rows chain on a step without "
                        "grammar states (scheduler must not chain grammar "
                        "rows onto verify or unconstrained steps)"
                    )
                # same gather as token chaining: rows with idx >= 0 read
                # the in-flight step's post-window state, others keep the
                # host value (non-grammar chained rows gather junk their
                # gr_idx = -1 makes inert)
                with clock.span("enqueue", kind="decode"):
                    gs_dev = self._chain_vec_fn(
                        prev.grammar_states, gs_dev, idx_dev
                    )
            dyn_args = dyn_args + (
                tc_dev, cd_dev, acc_dev, gr_idx_dev, gs_dev,
                self._gr_eos_dev,
            )
        result = self._call_decode_program(
            aot_key, work, self.lora_params, dyn_args, want_logprobs=want_lp,
            want_min_tokens=use_mt, want_grammar=want_gr)
        moe = None
        if self.config.model.num_experts:
            *result, touched = result
            moe = self._moe_counts(touched, "decode", b_pad, work.window)
        gstates = None
        if want_lp and want_gr:
            self.kv_caches, tokens, lp_arrays, gstates = result
        elif want_lp:
            self.kv_caches, tokens, lp_arrays = result
        elif want_gr:
            self.kv_caches, tokens, gstates = result
            lp_arrays = None
        else:
            self.kv_caches, tokens = result
            lp_arrays = None
        handle = StepHandle(
            runner=self, work=work, tokens=tokens, lp_arrays=lp_arrays,
            rng_before=rng_before,
            postproc=functools.partial(self._decode_rows, work, b),
        )
        handle.grammar_states = gstates
        handle.moe = moe
        return handle

    def _dispatch_bd_decode(self, work: DecodeWork) -> StepHandle:
        """`_dispatch_decode` for a window of block passes
        (`_build_bd_window_fn`): a row's input is the tokens it already has
        in the window (`work.block_tokens`: the tail of its prompt in its
        first window, nothing after) and masks behind them, so no row
        chains a token from the step in flight — the windows chain on the
        pool alone."""
        bd = self.config.model.block_diffusion
        b = len(work.requests)
        want_lp = any(
            r.sampling.logprobs is not None for r in work.requests)
        aot_key = self._pick_decode_program(work, want_lp, False, None)
        _, b_pad, nb, _w, _lp, _mt, _g = aot_key

        if llama.BD_TAP is not None:  # (a check's tap: which rows these are)
            llama.BD_TAP.window([r.request_id for r in work.requests])
        clock = self.clock
        tokens0 = np.full((b_pad, work.window), bd.mask_token_id, np.int32)
        n_given = np.full(b_pad, work.window, np.int32)  # padding: no mask
        for i, given in enumerate(work.block_tokens):
            tokens0[i, :len(given)] = given
            n_given[i] = len(given)
        tokens0[b:] = 0
        live = np.arange(b_pad) < b
        positions0 = np.zeros(b_pad, np.int32)
        positions0[:b] = work.positions
        block_tables = self._group_tables(
            work.requests, pad_to=b_pad, width=nb)
        rng_before, sampling = self._decode_sampling_args(
            work.requests, b_pad)
        put = self._put_polled
        with clock.span("h2d", kind="decode"):
            dyn_args = (
                put(tokens0, self._batch2),
                put(n_given, self._batch1),
                put(live, self._batch1),
                put(positions0, self._batch1),
                put(block_tables, self._batch2),
                *(put(a, sharding) for a, sharding in sampling),
            )
        result = self._call_decode_program(
            aot_key, work, None, dyn_args, want_logprobs=want_lp)
        moe = None
        if self.config.model.num_experts:
            *result, touched = result
            # (touched, passes -> the counts that follow from shapes)
            moe = (touched, functools.partial(
                self._moe_counts, touched, "decode", b_pad * bd.block_length))
        *result, counts_dev = result
        self.kv_caches, tokens = result[0], result[1]
        handle = StepHandle(
            runner=self, work=work, tokens=tokens,
            lp_arrays=result[2] if want_lp else None, rng_before=rng_before,
            postproc=functools.partial(self._decode_rows, work, b),
        )
        handle.moe, handle.bd = moe, counts_dev
        return handle

    @staticmethod
    def _decode_rows(work: DecodeWork, b: int, mat, lp):
        """Host-side row building for a resolved decode handle. A window of
        block passes hands a row the tokens BEHIND those it was given."""
        skip = [len(g) for g in work.block_tokens] or [0] * b
        if lp is None:
            lp_rows = None
        else:
            lp_w, top_lp_w, top_id_w = lp
            # python-ify only the rows that asked — the device already
            # computed the whole batch, but 256x32x8 tuple-building on the
            # host for rows the engine will ignore is pure waste
            lp_rows = [
                (
                    [
                        (float(lp_w[i, k]),
                         list(map(int, top_id_w[i, k])),
                         list(map(float, top_lp_w[i, k])))
                        for k in range(skip[i], work.window)
                    ]
                    if req.sampling.logprobs is not None
                    else []
                )
                for i, req in enumerate(work.requests)
            ]
        return [list(map(int, mat[i, skip[i]:])) for i in range(b)], lp_rows

    # -- helpers -----------------------------------------------------------

    def _run(
        self, token_ids, positions, block_tables, slots, context_lens,
        chunk_lens, write_ids, start_off, lora_idx, sample_rows, temps,
        top_ps, top_ks, seeds, counts, min_toks, stop_ids_arr,
        grammar_mask=None,  # device (B, V) bool when want_grammar
        want_logprobs=False, want_min_tokens=False, want_grammar=False,
        aot_key=None, watch_reqs=None,
        state=None,  # _state_slot_arrays(...) of the rows
    ):
        if self._sleeping_params_host is not None:
            raise RuntimeError("engine is sleeping; wake it before running")
        clock = self.clock
        clock.poll()  # around the RNG split: an enqueue of its own
        rng_before = self._rng
        self._rng, step_key = jax.random.split(self._rng)
        clock.poll()
        has_seed = np.asarray([s is not None for s in seeds], bool)
        # 64-bit user seeds (legal per the OpenAI API) fold down to uint32
        seed_vals = np.asarray(
            [(s or 0) & 0xFFFFFFFF for s in seeds], np.uint32
        )
        # sp shards the chunk axis; dp-only meshes leave T unsharded; the
        # packed chunk (dp = sp = 1) is one axis of tokens, on every device
        tok_sh = (
            self._rep if token_ids.ndim == 1
            else self._seq2 if self._sp > 1 else self._batch2
        )
        put = self._put_polled
        with clock.span("h2d", kind="prefill"):
            dyn_args = (
                put(token_ids, tok_sh),
                put(positions, tok_sh),
                put(block_tables, self._batch2),
                # (B*T,) for the sp path (B divisible by dp); (1,)
                # placeholder (replicated) for the paged path
                put(slots, self._batch1 if self._sp > 1 else self._rep),
                put(context_lens, self._batch1),
                put(chunk_lens, self._batch1),
                put(write_ids, self._batch2),
                put(start_off, self._batch1),
                put(lora_idx, self._batch1) if self._use_lora else None,
                put(sample_rows, self._batch1),
                put(np.asarray(temps, np.float32), self._batch1),
                put(np.asarray(top_ps, np.float32), self._batch1),
                put(np.asarray(top_ks, np.int32), self._batch1),
                put(step_key, self._rep),
                put(seed_vals, self._batch1),
                put(has_seed, self._batch1),
                put(np.asarray(counts, np.int32), self._batch1),
                put(min_toks, self._batch1),
                put(stop_ids_arr, self._batch2),
                state,
            )
        if want_grammar:
            dyn_args = dyn_args + (grammar_mask,)  # already device-resident
        aot = self._aot_exec.get(aot_key) if aot_key is not None else None
        if aot is not None:
            with clock.span("enqueue", kind="prefill"):
                result = aot(
                    self.params, self.lora_params, self.kv_caches, *dyn_args
                )
        else:
            # a first-ever key means this _step_fn call traces+compiles
            # synchronously — the stall CompileWatch attributes to the
            # batch that blocked on it (must check BEFORE the call:
            # _note_compiled below adds the key)
            new_program = False
            if aot_key is not None:
                with self._bg_lock:
                    new_program = aot_key not in self._compiled_keys
            t0 = time.perf_counter()
            with clock.span("enqueue", kind="prefill"):
                result = self._step_fn(
                    self.params,
                    self.lora_params,
                    self.kv_caches,
                    *dyn_args,
                    want_logprobs=want_logprobs,
                    want_min_tokens=want_min_tokens,
                    want_grammar=want_grammar,
                )
            if aot_key is not None:
                self._note_compiled(aot_key)
                if new_program:
                    self._watch_sync_compile(
                        "prefill", aot_key, time.perf_counter() - t0,
                        watch_reqs,
                    )
        moe = None
        if self.config.model.num_experts and self._sp == 1:
            # (the sp prefill program counts no routing)
            *result, touched = result
            moe = self._moe_counts(touched, "prefill", token_ids.size, 1)
        if want_logprobs:
            self.kv_caches, tokens, lp = result
        else:
            self.kv_caches, tokens = result
            lp = None
        # NO host sync here: the caller wraps these in a StepHandle whose
        # resolve() performs the single batched D2H transfer
        return tokens, lp, rng_before, moe

    def _moe_counts(self, touched, kind: str, tokens: int, steps: int,
                    ) -> tuple:
        """StepHandle.moe for a program that ran `tokens` tokens (padded
        rows and positions included: they are computed) through every layer
        `steps` times: the pairs, the (layer-step, expert) slots and the
        form of the expert computation follow from shapes, only the touched
        experts come from the device."""
        cfg = self.config.model
        # expert layers only: leading dense layers route nothing
        layer_steps = cfg.num_moe_layers * steps
        return (touched, kind, tokens * cfg.num_experts_per_tok * layer_steps,
                layer_steps * cfg.held_experts, self.moe_experts_form(tokens))

    def _grammar_device_tables(self, grammars: list, gkey: tuple):
        """Replicated device copies of the batch's automaton tables, padded
        to the program key's (G, S, C) buckets: token_class (G, V) int32,
        class_dest (G, S, C) int32 (-1 = reject; padding rows/cols all -1,
        so state S-1 is the guaranteed dead sink), accepting (G, S) bool.
        Cached by (grammar uids, pads) — a steady constrained batch
        re-dispatches with zero table H2D traffic."""
        g_pad, s_pad, c_pad = gkey
        key = (tuple(g.uid for g in grammars), g_pad, s_pad, c_pad)
        hit = self._grammar_tables_cache.get(key)
        if hit is not None:
            return hit
        t0 = time.perf_counter()
        v = self.config.model.vocab_size
        tc = np.zeros((g_pad, v), np.int32)
        cd = np.full((g_pad, s_pad, c_pad), -1, np.int32)
        acc = np.zeros((g_pad, s_pad), bool)
        for j, g in enumerate(grammars):
            if g.vocab_size != v:
                raise RuntimeError(
                    f"grammar lifted over vocab {g.vocab_size}, model has {v}"
                )
            tc[j] = g.token_class
            cd[j, : g.n_states, : g.n_classes] = g.class_dest
            acc[j, : g.n_states] = g.accepting
        with self.clock.span("h2d", kind="decode"):
            out = (
                self._put(tc, self._rep),
                self._put(cd, self._rep),
                self._put(acc, self._rep),
            )
        # bounded: distinct (batch composition, pads) combos churn during
        # warmup then stabilize; evict oldest past a small cap
        if len(self._grammar_tables_cache) >= 32:
            self._grammar_tables_cache.pop(
                next(iter(self._grammar_tables_cache))
            )
        self._grammar_tables_cache[key] = out
        # telemetry: table builds are numpy-side (no XLA program) but they
        # sit on the dispatch path — the watch inventories them under
        # phase="grammar", excluded from cache hit/miss and storm counting
        self.compile_watch.record_build(
            "grammar", ("grammar", key[0], g_pad, s_pad, c_pad),
            time.perf_counter() - t0,
            "mid_traffic" if self.fallback_enabled else "warmup",
            role=self.compile_role,
        )
        return out

    def _stop_id_arrays(self, requests, pad_to: int):
        """(min_toks (B,), stop_ids (B, SUPPRESS_IDS)) for device-side
        min_tokens suppression (sampling.suppress_stop_tokens): eos first
        (unless ignore_eos), then stop_token_ids; -1 pads. Batches with no
        min_tokens rows (the steady state) reuse cached zero arrays — this
        runs on every dispatch."""
        if not any(r.sampling.min_tokens > 0 for r in requests):
            cached = self._zero_stop_arrays.get(pad_to)
            if cached is None:
                cached = (
                    np.zeros(pad_to, np.int32),
                    np.full((pad_to, SUPPRESS_IDS), -1, np.int32),
                )
                self._zero_stop_arrays[pad_to] = cached
            return cached
        min_toks = np.zeros(pad_to, np.int32)
        stop_ids = np.full((pad_to, SUPPRESS_IDS), -1, np.int32)
        for i, req in enumerate(requests):
            s = req.sampling
            if s.min_tokens <= 0:
                continue
            min_toks[i] = s.min_tokens
            ids = []
            if not s.ignore_eos and req.eos_token_id is not None:
                ids.append(req.eos_token_id)
            ids.extend(s.stop_token_ids)
            for j, tid in enumerate(ids[:SUPPRESS_IDS]):
                stop_ids[i, j] = tid
        return min_toks, stop_ids

    # -- compile-stall avoidance -------------------------------------------
    #
    # The measured live-serving collapse mode: traffic's first hit on a
    # new (rows × chunk × width) program key froze serving for a
    # 30-60s XLA compile while queued work starved, and the warmup ladder
    # cannot enumerate the full key crossproduct in reasonable boot time.
    # Structural fix: a program-key MISS never compiles on the hot path
    # when any already-compiled program DOMINATES the needed shape (every
    # axis >= needed) — padding further up is semantically identical, just
    # more compute — and the exact program is AOT-compiled concurrently on
    # a background thread (jit.lower().compile() traces/compiles without
    # executing; XLA compiles release the GIL, so serving dispatches
    # continue). Once ready, the next hit dispatches the specialized
    # executable. Serving therefore starts after warming only a COARSE
    # shape lattice and migrates to exact programs under live traffic with
    # zero stalls.

    @property
    def _dynamic_programs_ok(self) -> bool:
        # the sp prefill path has its own step fn and shardings; the
        # fallback machinery covers the common paged path
        return self._sp == 1

    def _sds(self, shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def _aval_tree(self, tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=x.sharding
            ),
            tree,
        )

    def _pick_prefill_shape(
        self, b_pad: int, t_pad: int, nb: int, want_lp: bool, want_mt: bool,
        want_gr: bool = False,
    ) -> tuple:
        """The program KEY to dispatch with: exact when that program is
        compiled (or nothing compiled dominates it — cold start compiles
        synchronously); otherwise the cheapest compiled dominating key,
        with the exact program queued for background compile.

        Dominance: every shape axis >= needed; want_logprobs must match
        exactly (it changes the output structure); a want_min_tokens=True
        program dominates False (suppression is a no-op at min_toks=0); a
        grammar-enabled program dominates a plain one (the all-ones mask is
        the identity)."""
        key = ("prefill", b_pad, t_pad, nb, want_lp, want_mt, want_gr)
        if not self._dynamic_programs_ok or not self.fallback_enabled:
            return key
        with self._bg_lock:
            if key in self._compiled_keys:
                return key
            candidates = [
                k for k in self._compiled_keys
                if k[0] == "prefill" and k[4] == want_lp and k[5] >= want_mt
                and k[6] >= want_gr
                and k[1] >= b_pad and k[2] >= t_pad and k[3] >= nb
            ]
        if not candidates:
            return key
        self.compile_fallbacks += 1
        self._bg_compile(key)
        return min(candidates, key=lambda k: (k[1] * k[2], k[3], k[5], k[6]))

    @staticmethod
    def _gkey_dominates(have, need) -> bool:
        """Grammar-table pads (G, S, C) dominate componentwise; None (no
        grammar path compiled in) serves only None — the output structures
        differ (grammar programs return the state vector). Tables pad UP to
        the candidate's buckets, so a bigger-table program is the same
        program."""
        if need is None:
            return have is None
        return have is not None and all(a >= b for a, b in zip(have, need))

    def _pick_decode_shape(
        self, b_pad: int, nb: int, window: int, want_lp: bool, want_mt: bool,
        gkey: tuple | None = None,
    ) -> tuple:
        """Like _pick_prefill_shape for the fused decode window. `window`
        is never substituted: it is semantic (tokens generated, pool blocks
        the scheduler reserved) — a larger window would scatter past the
        reserved blocks."""
        key = ("decode", b_pad, nb, window, want_lp, want_mt, gkey)
        if not self._dynamic_programs_ok or not self.fallback_enabled:
            return key
        with self._bg_lock:
            if key in self._compiled_keys:
                return key
            candidates = [
                k for k in self._compiled_keys
                if k[0] == "decode" and k[3] == window
                and k[4] == want_lp and k[5] >= want_mt
                and self._gkey_dominates(k[6], gkey)
                and k[1] >= b_pad and k[2] >= nb
            ]
        if not candidates:
            return key
        self.compile_fallbacks += 1
        self._bg_compile(key)
        return min(candidates, key=lambda k: (k[1], k[2], k[5]))

    def _note_compiled(self, key: tuple) -> None:
        with self._bg_lock:
            self._compiled_keys.add(key)

    def _watch_dispatch(self, exact_key: tuple, aot_key: tuple) -> None:
        """Program-cache hit/miss accounting: a HIT is the exact requested
        key already compiled (no pad-up, no sync compile). The dispatch is
        charged to the key actually served."""
        watch = self.compile_watch
        with self._bg_lock:
            hit = aot_key == exact_key and exact_key in self._compiled_keys
        watch.record_dispatch(aot_key, hit, role=self.compile_role)

    def _watch_sync_compile(
        self, phase: str, key: tuple, wall_s: float, requests
    ) -> None:
        """A program compiled ON the dispatch path. During warmup
        (fallback disabled, every wave compiles its exact program) that is
        the plan; mid-traffic it is the stall the pad-up cache exists to
        prevent — recorded against the requests whose step blocked, and
        stamped onto each request for its trace timeline."""
        watch = self.compile_watch
        trigger = "mid_traffic" if self.fallback_enabled else "warmup"
        rid = None
        if requests:
            rid = getattr(requests[0], "request_id", None)
        watch.record_build(
            phase, key, wall_s, trigger, rid=rid, role=self.compile_role,
        )
        if trigger != "mid_traffic" or not requests:
            return
        stall = {
            "phase": phase,
            "key": repr(tuple(key)),
            "wall_ms": round(wall_s * 1000.0, 1),
        }
        for req in requests:
            stalls = getattr(req, "compile_stalls", None)
            if stalls is None:
                req.compile_stalls = [dict(stall)]
            else:
                stalls.append(dict(stall))

    def _bg_compile(self, key: tuple) -> None:
        with self._bg_lock:
            if key in self._bg_inflight or key in self._compiled_keys:
                return
            self._bg_inflight.add(key)
            if self._bg_executor is None:
                self._bg_executor = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="xla-bg-compile"
                )
        self._bg_executor.submit(self._bg_compile_job, key)

    def shutdown(self, wait: bool = False) -> None:
        """Cancel queued background compiles — each is a 30-60s XLA compile
        behind an idle-gate sleep, and concurrent.futures' atexit hook
        would otherwise drain them all before the interpreter can exit.
        A job already running is signalled to bail too: without the stop
        event it would sit in the idle-gate sleep (up to 10 min) and fire
        its compile exactly when the process next goes quiet — observed as
        stolen CPU (pacing flakes) in whatever test module runs next.
        wait=True additionally blocks until the in-flight job (which XLA
        cannot interrupt) finishes — test teardowns use it so no compile
        thread ever outlives its module."""
        self._bg_stop.set()
        with self._bg_lock:
            ex, self._bg_executor = self._bg_executor, None
        if ex is not None:
            ex.shutdown(wait=wait, cancel_futures=True)

    def _bg_compile_job(self, key: tuple) -> None:
        try:
            # idle gate: wait (bounded) for a traffic lull before compiling
            # (see idle_check: the compile competes with the step thread)
            idle = self.idle_check
            if idle is not None:
                import time as _time

                deadline = _time.monotonic() + 600.0
                while not idle():
                    if self._bg_stop.is_set() or _time.monotonic() > deadline:
                        return  # still busy; the key stays un-compiled and
                        # the fallback keeps absorbing it
                    _time.sleep(0.25)
            if self._bg_stop.is_set():
                return
            hb = self.heartbeat
            if hb is not None:
                hb.beat()  # busy from here: the compile itself can wedge
            if self._compile_key_now(key):
                self.bg_compiles += 1
                logger.info(
                    "background-compiled %s program %s", key[0], key[1:]
                )
        except Exception as e:
            # serving goes on (the pad-up fallback keeps absorbing the key),
            # but the failure is counted where /debug/programs shows it
            logger.exception("background compile failed for %s", key)
            self.compile_watch.record_failure(
                key[0], key, "bg", e, role=self.compile_role
            )
        finally:
            hb = self.heartbeat
            if hb is not None:
                hb.idle()
            with self._bg_lock:
                self._bg_inflight.discard(key)

    def _compile_key_now(self, key: tuple, trigger: str = "bg") -> bool:
        """AOT-compile one program key (.lower().compile() — traces and
        compiles WITHOUT executing: no tokens, no pool writes, no pool
        capacity requirement). Returns True when a new executable landed."""
        with self._bg_lock:
            if key in self._compiled_keys:
                return False
        if self._sleeping_params_host is not None or self.kv_caches is None:
            return False  # parked; avals unavailable — compiles lazily later
        # avals, not live arrays: the step thread donates kv_caches every
        # dispatch, and lowering must not race buffer invalidation
        params_av = self._aval_tree(self.params)
        lora_av = (
            self._aval_tree(self.lora_params) if self._use_lora else None
        )
        kv_av = self._aval_tree(self.kv_caches)
        if key[0] == "prefill":
            _, b, t, nb, want_lp, want_mt, want_gr = key
            lowered = self._step_fn.lower(
                params_av, lora_av, kv_av,
                *self._prefill_avals(b, t, nb, want_gr),
                want_logprobs=want_lp, want_min_tokens=want_mt,
                want_grammar=want_gr,
            )
        else:
            _, b, nb, window, want_lp, want_mt, gkey = key
            lowered = self._decode_window_fn.lower(
                params_av, lora_av, kv_av,
                *self._decode_avals(b, nb, gkey, window),
                window=window, want_logprobs=want_lp,
                want_min_tokens=want_mt, want_grammar=gkey is not None,
            )
        t0 = time.perf_counter()
        compiled = lowered.compile()
        wall = time.perf_counter() - t0
        with self._bg_lock:
            self._aot_exec[key] = compiled
            self._compiled_keys.add(key)
        self.compile_watch.record_build(
            key[0], key, wall, trigger, role=self.compile_role,
            memory_bytes=program_memory_bytes(compiled),
        )
        return True

    def precompile_dominating(self) -> int:
        """Compile the DOMINATING program lattice directly: full batch ×
        each chunk bucket × the TOP width bucket for prefill, plus the top
        decode bucket × top width × each pow2 window. Shapes are virtual
        (no execution), so this works regardless of pool-vs-max_model_len
        sizing. Afterwards every finer program key has a pad-up fallback —
        serving cannot stall on a synchronous compile. This is the
        engine's warmup(scope=\"coarse\")."""
        if not self._dynamic_programs_ok:
            return 0
        sched = self.config.scheduler
        top_w = self._width_bucket(self.max_blocks)
        b_top = self._batch_bucket(sched.max_num_seqs)
        t_top = max(sched.prefill_buckets)
        n = 0
        for t in sorted(set(sched.prefill_buckets)):
            if self._compile_key_now(("prefill", b_top, t, top_w,
                                      False, False, False), "warmup"):
                n += 1
        # the pow2 ROWS ladder at (top chunk, top width): a packed program
        # computes t_pad tokens whatever its rows, but each padded row
        # still costs attention its query tiles' copies, the KV write a
        # span of null pages and the sampler a row of logits (and, under
        # dp > 1, t_pad tokens of dense FLOPs: rows x bucket there), so a
        # few extra programs here turn the worst-case fallback from "jump
        # to full batch" into "pad width only" (~1.2x HBM)
        b = 1
        while b < b_top:
            if self._compile_key_now(("prefill", b, t_top, top_w,
                                      False, False, False), "warmup"):
                n += 1
            b *= 2
        top_window = 1
        w = 1
        bd = self.config.model.block_diffusion
        while w <= sched.decode_window:
            if bd is not None and w % bd.block_length:
                w *= 2
                continue  # a window of block passes holds whole blocks
            top_window = w
            for d in sorted(set(sched.decode_buckets)):
                if d > sched.max_num_seqs:
                    continue  # unreachable batch bucket
                if self._compile_key_now(("decode", d, top_w, w,
                                          False, False, None), "warmup"):
                    n += 1
            w *= 2
        # min_tokens variants at the top shapes: an mt=True program
        # DOMINATES mt=False (suppression no-ops at min_toks=0), so these
        # two keep even min_tokens traffic stall-free after a coarse boot
        d_top = max(
            (d for d in sched.decode_buckets if d <= sched.max_num_seqs),
            default=min(sched.decode_buckets),
        )
        for key in (
            ("prefill", b_top, t_top, top_w, False, True, False),
            ("decode", d_top, top_w, top_window, False, True, None),
        ):
            if bd is not None:
                break  # (min_tokens is refused a request of such a model)
            if self._compile_key_now(key, "warmup"):
                n += 1
        logger.info("precompiled %d dominating programs", n)
        return n

    def warm_chain_gathers(self) -> int:
        """Compile the first-token gather (`_chain_vec_fn`) for every
        (prefill row bucket, decode bucket) pair the built prefill programs
        can feed, so the first window enqueued behind a chunk of each row
        count waits on no compile. The gather is not a step program (tenths
        of a second for all of them, no cache entry worth keeping): it is
        run once on zeros that carry the sharding the prefill programs give
        their token vector, which is part of the gather's cache key. Returns
        the pairs run."""
        sched = self.config.scheduler
        # (kv_caches, tokens, ...) -> the token vector's sharding, per b_pad
        srcs = {
            key[1]: exe.output_shardings[1]
            for key, exe in self._aot_exec.items() if key[0] == "prefill"
        }
        dsts = [
            (self._put(np.zeros(d, np.int32), self._batch1),
             self._put(np.full(d, -1, np.int32), self._batch1))
            for d in sorted(set(sched.decode_buckets))
        ]
        for b, sharding in srcs.items():
            vec = self._put(np.zeros(b, np.int32), sharding)
            for host, idx in dsts:
                self._chain_vec_fn(vec, host, idx)
        return len(srcs) * len(dsts)

    def _prefill_avals(self, b: int, t: int, nb: int, want_gr: bool = False):
        """ShapeDtypeStructs mirroring _run's dynamic args for one prefill
        shape — MUST stay in lockstep with the _step_fn call in _run."""
        bs = self.config.cache.block_size
        nbw = (t - 1) // bs + 2
        i32, f32 = jnp.int32, jnp.float32
        b1, b2, rep = self._batch1, self._batch2, self._rep
        s = self._sds
        v = self.config.model.vocab_size
        gr = ((s((b, v), jnp.bool_, b2),) if want_gr else ())  # grammar_mask
        # (sp > 1 compiles nothing through here: _dynamic_programs_ok)
        packed = self.prefill_packed
        tok = s((t,), i32, rep) if packed else s((b, t), i32, b2)
        # (a (full, window) pair of each where window layers have a group)
        groups = (lambda a: (a, a)) if self._window_group else (lambda a: a)
        return (
            tok,                      # token_ids
            tok,                      # positions
            groups(s((b, nb), i32, b2)),  # block_tables
            s((1,), i32, rep),        # slots placeholder (paged path)
            s((b,), i32, b1),         # context_lens
            s((b,), i32, b1),         # chunk_lens
            groups(s((b, nbw), i32, b2)),  # write_ids
            s((b,), i32, b1),         # start_off
            s((b,), i32, b1) if self._use_lora else None,  # lora_idx
            s((b,), i32, b1),         # sample_rows
            s((b,), f32, b1),         # temperature
            s((b,), f32, b1),         # top_p
            s((b,), i32, b1),         # top_k
            s(self._rng.shape, self._rng.dtype, rep),  # rng
            s((b,), jnp.uint32, b1),  # seeds
            s((b,), jnp.bool_, b1),   # has_seed
            s((b,), i32, b1),         # counts
            s((b,), i32, b1),         # min_toks
            s((b, SUPPRESS_IDS), i32, b2),  # stop_ids
            (s((b,), i32, b1),) * 2 if self.state_slots else None,
        ) + gr

    def _decode_avals(self, b: int, nb: int, gkey: tuple | None = None,
                      window: int = 1):
        """ShapeDtypeStructs mirroring _dispatch_decode's dynamic args —
        MUST stay in lockstep with the _decode_window_fn call."""
        i32, f32 = jnp.int32, jnp.float32
        b1, b2, rep = self._batch1, self._batch2, self._rep
        s = self._sds
        if self.config.model.block_diffusion is not None:
            # ... and with _dispatch_bd_decode's
            return (
                s((b, window), i32, b2),  # tokens0
                s((b,), i32, b1),         # n_given
                s((b,), jnp.bool_, b1),   # live
                s((b,), i32, b1),         # positions0
                s((b, nb), i32, b2),      # block_tables
                s((b,), f32, b1),         # temperature
                s((b,), f32, b1),         # top_p
                s((b,), i32, b1),         # top_k
                s(self._rng.shape, self._rng.dtype, rep),  # base_key
                s((b,), jnp.uint32, b1),  # seeds
                s((b,), jnp.bool_, b1),   # has_seed
                s((b,), i32, b1),         # counts0
            )
        v = self.config.model.vocab_size
        gr = ()
        if gkey is not None:
            g, sp, cp = gkey
            gr = (
                s((g, v), i32, rep),       # gr_token_class
                s((g, sp, cp), i32, rep),  # gr_class_dest
                s((g, sp), jnp.bool_, rep),  # gr_accepting
                s((b,), i32, b1),          # gr_idx
                s((b,), i32, b1),          # gr_state0
                s((1,), i32, rep),         # gr_eos
            )
        tables = s((b, nb), i32, b2)
        return (
            s((b,), i32, b1),         # first_tokens
            s((b,), i32, b1),         # positions0
            (tables, tables) if self._window_group else tables,  # block_tables
            s((b,), i32, b1) if self._use_lora else None,  # lora_idx
            s((b,), f32, b1),         # temperature
            s((b,), f32, b1),         # top_p
            s((b,), i32, b1),         # top_k
            s(self._rng.shape, self._rng.dtype, rep),  # base_key
            s((b,), jnp.uint32, b1),  # seeds
            s((b,), jnp.bool_, b1),   # has_seed
            s((b,), i32, b1),         # counts0
            s((b,), i32, b1),         # min_toks
            s((b, SUPPRESS_IDS), i32, b2),  # stop_ids
            (s((b,), i32, b1),) * 2 if self.state_slots else None,
        ) + gr

    @staticmethod
    def _pow2(n: int) -> int:
        """Next power of two — bounds compiled program count to log2 sizes."""
        return 1 << max(0, n - 1).bit_length()

    def _batch_bucket(self, b: int) -> int:
        """Batch rows pad to a power of two ≥ dp so the batch axis shards
        evenly (dp is validated to be a power of two)."""
        return max(self._dp, self._pow2(b))

    def _put_polled(self, x, sharding):
        """`_put` behind one poll of the recorder: the copies of one dispatch
        are its `h2d` phase's natural sub-steps (step thread only)."""
        self.clock.poll()
        return self._put(x, sharding)

    def _put(self, x, sharding):
        """Place a host array directly into its mesh sharding — one
        host→shards transfer, no staging hop through the default device
        (dp=1 meshes take the same path, so there is one path to test)."""
        return jax.device_put(x, sharding)

    def _width_bucket(self, longest: int) -> int:
        """Block-table width bucket for the widest table in a batch: pow2
        with a configurable FLOOR (default 64 blocks ≈ 1k tokens): every
        width is its own compiled program, and the fine-grained ladder
        below the floor bought little (short-context gathers are cheap to
        pad) while costing a compile per boundary crossing. Benches with
        exactly-warmed shapes set width_floor_blocks=1."""
        floor = self.config.scheduler.width_floor_blocks
        return max(1, min(max(floor, self._pow2(longest)), self.max_blocks))

    def _block_table_array(
        self,
        tables: list[list[int]],
        pad_to: int | None = None,
        width: int | None = None,
    ) -> np.ndarray:
        """(B, nb) table where nb is the *bucketed max blocks in use* — not
        max_model_len/block_size. The gathered context is nb*block_size wide,
        so sizing nb to the batch's real context (round-1 weak #2: the full
        max-len gather per layer per step was the dominant waste) cuts HBM
        traffic by max_model_len/actual_len; power-of-two nb keeps the
        compiled-program set logarithmic. `width` overrides the bucket (the
        compile-fallback path pads to an already-compiled width)."""
        b = pad_to or len(tables)
        longest = max((len(t) for t in tables), default=1)
        nb = width if width is not None else self._width_bucket(longest)
        arr = np.zeros((b, nb), np.int32)  # 0 = null page
        for i, tbl in enumerate(tables):
            arr[i, : len(tbl)] = tbl
        return arr

    def _group_tables(self, requests, pad_to=None, width=None):
        """The rows' block tables: one array, or for a model with window
        layers the (full, window) pair — the window group's table has the
        same logical indexing, 0 (the null page) where a page was given
        back (kv_cache.py:WindowPageGroup.give_back)."""
        full = self._block_table_array(
            [r.block_table for r in requests], pad_to=pad_to, width=width)
        if not self._window_group:
            return full
        return full, self._block_table_array(
            [r.window_table for r in requests], pad_to=pad_to,
            width=full.shape[1])

    def _with_window_span(self, write_ids: np.ndarray, work, bs: int):
        """`write_ids` (the full group's pages of each row's written span)
        paired with the window group's pages of the same span."""
        if not self._window_group:
            return write_ids
        ids_w = np.zeros_like(write_ids)
        for i, req in enumerate(work.requests):
            first_blk = (work.context_lens[i] - len(work.token_ids[i])) // bs
            n_span = (work.context_lens[i] - 1) // bs - first_blk + 1
            ids_w[i, :n_span] = req.window_table[
                first_blk : first_blk + n_span]
        return write_ids, ids_w

    # -- embeddings (/v1/embeddings; plain encode, no paged KV) ------------

    def embed(self, rows: list[list[int]]) -> np.ndarray:
        """L2-normalized last-token embeddings for a batch of token rows.
        Rows group by prefill bucket and batch into ONE dispatch per
        (batch-bucket, length-bucket) pair — same compile-bounding
        discipline as the serving steps, and no per-row device round-trips.
        Returns (N, hidden) float32."""
        if self._sleeping_params_host is not None:
            raise RuntimeError("engine is sleeping; wake it before running")
        if self._embed_fn is None:
            self._embed_fn = jax.jit(
                lambda p, ids, lens: llama.embed_encode(
                    self.config.model, p, ids, lens
                )
            )
        out = np.zeros(
            (len(rows), self.config.model.hidden_size), np.float32
        )
        # pow2 length buckets up to max_model_len — embeddings must accept
        # anything the model's context fits (the scheduler's prefill buckets
        # cap chunk sizes, not document lengths), with a log2-bounded
        # compiled-program set
        groups: dict[int, list[int]] = {}
        for idx, row in enumerate(rows):
            t_pad = min(
                self._pow2(len(row)), self.config.model.max_model_len
            )
            groups.setdefault(t_pad, []).append(idx)
        for t_pad, idxs in groups.items():
            b_pad = self._batch_bucket(len(idxs))
            ids = np.zeros((b_pad, t_pad), np.int32)
            lens = np.ones(b_pad, np.int32)  # padding rows pool token 0
            for j, idx in enumerate(idxs):
                ids[j, : len(rows[idx])] = rows[idx]
                lens[j] = len(rows[idx])
            vecs = self._embed_fn(
                self.params,
                self._put(ids, self._batch2),
                self._put(lens, self._batch1),
            )
            got = np.asarray(jax.device_get(vecs))
            for j, idx in enumerate(idxs):
                out[idx] = got[j]
        return out

    # -- host KV tier transfers (engine/kv_host_tier.py) -------------------

    def fetch_block(self, blk: int) -> list[jax.Array]:
        """HBM→host, non-blocking: slice one block's pages per layer and
        start their host copies. The caller (HostKVTier) resolves the
        transfer to numpy later — offloads happen inside the scheduler loop
        with the engine lock held, so blocking here would stall a device
        round-trip per evicted block (the transfer instead overlaps the next
        step's compute)."""
        if self._fetch_block_fn is None:
            self._fetch_block_fn = jax.jit(
                lambda kv, blk: tuple(leaf[:, blk] for leaf in kv)
            )
        parts = self._fetch_block_fn(self.kv_caches, jnp.int32(blk))
        for p in parts:
            p.copy_to_host_async()
        return list(parts)

    def upload_block(self, blk: int, data: np.ndarray) -> None:
        """Host→HBM: write offloaded pages into block `blk` in place."""
        if self._upload_block_fn is None:

            @functools.partial(jax.jit, donate_argnames=("kv_caches",))
            def upload_fn(kv_caches, data, blk):
                return tuple(
                    leaf.at[:, blk].set(data[i].astype(leaf.dtype))
                    for i, leaf in enumerate(kv_caches)
                )

            self._upload_block_fn = upload_fn
        self.kv_caches = self._upload_block_fn(
            self.kv_caches, data, jnp.int32(blk)
        )

    def upload_blocks(self, blks: list[int], data: np.ndarray) -> None:
        """Host→HBM for N blocks in ONE device dispatch — the PD import /
        remote-fetch path. Per-block upload_block costs a dispatch each
        (512 for an 8k prompt's blocks); this is one scatter for the whole
        group. `data`
        is (N, L, 2, block_size, kvH, D). N is padded up to a power of two
        (duplicating the last row — duplicate scatter indices with identical
        payloads are benign) so arbitrary run lengths compile at most
        log2(max) program variants instead of one per N."""
        n = len(blks)
        bucket = 1
        while bucket < n:
            bucket *= 2
        if bucket != n:
            blks = list(blks) + [blks[-1]] * (bucket - n)
            data = np.concatenate(
                [data, np.repeat(data[-1:], bucket - n, axis=0)]
            )
        if getattr(self, "_upload_blocks_fn", None) is None:

            @functools.partial(jax.jit, donate_argnames=("kv_caches",))
            def upload_many_fn(kv_caches, data, blks):
                return tuple(
                    leaf.at[:, blks].set(
                        jnp.swapaxes(data[:, i], 0, 1).astype(leaf.dtype)
                    )
                    for i, leaf in enumerate(kv_caches)
                )

            self._upload_blocks_fn = upload_many_fn
        self.kv_caches = self._upload_blocks_fn(
            self.kv_caches, np.ascontiguousarray(data),
            jnp.asarray(blks, jnp.int32),
        )

    # -- LoRA slots --------------------------------------------------------

    def install_lora(self, slot: int, adapter) -> None:
        """Write a parsed adapter (models/lora_loader.LoRAAdapter) into slot
        buffers on device. Same shapes every time — no recompile."""
        assert self._use_lora and 1 <= slot < self.config.lora.num_slots
        lp = self.lora_params
        for name, mod in lp.items():
            if name == "scale":
                continue
            if name in adapter.modules:
                a = jnp.asarray(adapter.modules[name]["A"], mod["A"].dtype)
                b = jnp.asarray(adapter.modules[name]["B"], mod["B"].dtype)
            else:  # module not targeted by this adapter: zero its delta
                a = jnp.zeros_like(mod["A"][slot])
                b = jnp.zeros_like(mod["B"][slot])
            mod["A"] = mod["A"].at[slot].set(a)
            mod["B"] = mod["B"].at[slot].set(b)
        lp["scale"] = lp["scale"].at[slot].set(adapter.scale)
        self.lora_params = jax.device_put(lp, self._lora_shardings)

    def remove_lora(self, slot: int) -> None:
        """Free a slot: zeroing its scale makes every delta exactly 0."""
        assert self._use_lora and 1 <= slot < self.config.lora.num_slots
        self.lora_params["scale"] = (
            self.lora_params["scale"].at[slot].set(0.0)
        )

    # -- sleep / wake (reference: router /sleep proxying, request.py:434-510;
    #    vLLM sleep levels; SURVEY §7.3 hard part 3) ------------------------

    @property
    def is_sleeping(self) -> bool:
        return self._sleeping_params_host is not None

    def sleep(self, level: int = 1) -> None:
        """Park the engine: move weights to host RAM (level 1) or drop them
        (level 2 — wake() re-inits from config), freeing HBM."""
        if self.is_sleeping:
            return
        if level >= 2 and not self._random_weights:
            # level 2 re-inits on wake; with loaded checkpoints that would
            # silently swap trained weights for random ones
            raise RuntimeError(
                "sleep level 2 requires re-initializable weights; use level 1 "
                "for checkpoint-loaded models"
            )
        if level >= 2:
            self._sleeping_params_host = "discarded"
        else:
            self._sleeping_params_host = jax.device_get(self.params)
        self.params = None
        # LoRA buffers are HBM-resident too (num_slots × L × 7 modules);
        # sleep's whole point is reclaiming HBM, so park them alongside
        if self.lora_params is not None:
            self._sleeping_lora_host = jax.device_get(self.lora_params)
            self.lora_params = None
        # drop the KV pool too; sleeping engines are drained by the router
        self.kv_caches = None

    def _param_shardings(self):
        """NamedSharding tree for the (possibly quantized) param tree."""
        cfg = self.config.model
        specs = llama_param_specs(cfg)
        if cfg.quantization:
            from ..models.quantization import quantize_specs

            specs = quantize_specs(cfg, specs)
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs)

    def _init_device_params(self, shardings):
        """Random-init (and quantize, when configured) in ONE compiled
        program straight into the sharded HBM layout. The full bf16 tree
        never exists, but the program's scratch is the f32 copy of the
        largest stacked leaf (models/quantization.py:_quantize upcasts a
        whole (L, in, out) leaf and reads it twice). Measured on a v5e at
        qwen3-8b int8 (PR 21): 8.21 GiB of outputs plus 6.75 GiB reserved
        scratch = 14.96 GiB of the chip's 15.75 GiB. It loads on an empty
        device and on nothing fuller: with 2 GiB already resident the
        runtime refuses the program. The allocator's peak_bytes_in_use
        (8.22 GiB) does not count that scratch."""
        cfg = self.config.model

        def build(c, key):
            p = llama.init_params(c, key)
            if c.quantization:
                from ..models.quantization import quantize_params

                p = quantize_params(c, p)
            return p

        init_fn = jax.jit(build, static_argnums=0, out_shardings=shardings)
        return init_fn(cfg, jax.random.PRNGKey(self.config.seed))

    def wake(self) -> None:
        if not self.is_sleeping:
            return
        cfg = self.config
        param_shardings = self._param_shardings()
        if isinstance(self._sleeping_params_host, str):  # discarded
            self.params = self._init_device_params(param_shardings)
        else:
            self.params = jax.tree.map(
                jax.device_put, self._sleeping_params_host, param_shardings
            )
        if self._sleeping_lora_host is not None:
            self.lora_params = jax.device_put(
                self._sleeping_lora_host, self._lora_shardings
            )
            self._sleeping_lora_host = None
        self.kv_caches = self._new_kv_caches()
        self._sleeping_params_host = None
