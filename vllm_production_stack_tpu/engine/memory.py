"""HBM budgeting: size the KV pool from what's left after weights.

The reference engines size their pools via vLLM's --gpu-memory-utilization
(deployment-vllm-multi.yaml:160-195; values.yaml `gpuMemoryUtilization`);
`CacheConfig.hbm_utilization` is the TPU analogue. Weights and KV bytes are
computed analytically from the model config (both are exact for our stacked
layouts), so sizing needs no trial allocation."""

from __future__ import annotations

import math

import jax

from ..utils.logging import init_logger
from .config import (
    STATE_CONV_TAPS, CacheConfig, EngineConfig, ModelConfig, ParallelConfig,
    SchedulerConfig,
)

logger = init_logger(__name__)

# stand-in device memory for the CPU backend, which reports none — tests
# and rehearsals only; an accelerator always states its own bytes_limit
CPU_ASSUMED_HBM_BYTES = 16 * 1024**3
# XLA workspace + fragmentation + activation headroom per device
RESERVE_BYTES = 1024**3
# extra pool capacity beyond live-sequence needs, kept as LRU prefix-cache room
PREFIX_CACHE_OVERPROVISION = 4
# context tokens per row the pool sizing sets aside (inside hbm_utilization)
# for the decode window's hoisted contiguous history copy — the runner hoists
# the loop-invariant gather only for programs whose footprint fits this
# funded headroom (model_runner._compute_hoist_budget); contexts past the
# allowance fall back to the per-iteration gather
HOIST_CTX_TOKENS = 256


def dtype_bytes(dtype: str) -> int:
    # jnp.dtype resolves the ml_dtypes names too (float8_e4m3fn, bfloat16)
    return jax.numpy.dtype(dtype).itemsize


def qk_norm_params(cfg: ModelConfig) -> int:
    """Per-layer q_norm + k_norm weights (replicated): per head (qwen3) or
    over the flat projections (olmo2, olmoe)."""
    if cfg.qk_norm_flat:
        return (cfg.num_heads + cfg.num_kv_heads) * cfg.head_dim
    return 2 * cfg.head_dim if cfg.qk_norm else 0


def layer_matrix_params(cfg: ModelConfig) -> dict:
    """Matrix parameters of the stacked tree by kind — the ONE count that
    `param_bytes` (what the tree weighs) and saturation.matmul_params (what
    a token multiplies through) both read. "attn": one layer's attention
    matrices (MLA: W_q, W_kv_a, W_kv_b, W_o); "dense_mlp": one dense SwiGLU;
    "expert": ONE expert's SwiGLU; "shared": the shared experts' SwiGLU;
    "router": h x E; "dense_layers" / "moe_layers": how many layers carry
    which MLP."""
    h = cfg.hidden_size
    if cfg.mla:
        attn = latent_attn_params(cfg, cfg.num_heads)
    else:
        attn = (
            h * cfg.num_heads * cfg.head_dim
            + 2 * h * cfg.num_kv_heads * cfg.head_dim
            + cfg.num_heads * cfg.head_dim * h
        )
    moe_layers = cfg.num_moe_layers
    return {
        "attn": attn,
        "dense_mlp": 3 * h * cfg.intermediate_size,
        "expert": 3 * h * cfg.expert_intermediate_size,
        "shared": 3 * h * cfg.n_shared_experts * cfg.expert_intermediate_size,
        "router": h * cfg.num_experts,
        "dense_layers": cfg.num_layers - moe_layers,
        "moe_layers": moe_layers,
    }


def indexer_params(cfg: ModelConfig) -> int:
    """Parameters of ONE indexer (a layer with LayerSpec.indexer "full";
    models/llama.py "attn_index"): W_q_b from the query latent, W_k and its
    LayerNorm's weight and bias, the heads' weight projection."""
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    return (cfg.q_lora_rank * hi * di + cfg.hidden_size * di + 2 * di
            + cfg.hidden_size * hi)


def index_block_bytes(cfg: ModelConfig, block_size: int,
                      kv_dtype: str | None = None) -> int:
    """Bytes of ONE pool block's index keys: one key of `index_head_dim`
    values a token and layer that owns an indexer (the pool's second leaf;
    0 for a model without)."""
    return (len(cfg.index_layers) * block_size * cfg.index_head_dim
            * dtype_bytes(kv_dtype or cfg.dtype))


def latent_attn_params(cfg: ModelConfig, num_heads: int) -> int:
    """Matrix parameters of ONE latent layer's attention: W_q (or, with a
    query latent, W_q_a, its norm and W_q_b), W_kv_a, W_kv_b, W_o."""
    h = cfg.hidden_size
    qk = num_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    rq = cfg.q_lora_rank
    return (
        (h * rq + rq + rq * qk if rq else h * qk)  # W_q
        + h * cfg.latent_dim  # W_kv_a
        + cfg.kv_lora_rank * num_heads * (
            cfg.qk_nope_head_dim + cfg.v_head_dim)  # W_kv_b
        + num_heads * cfg.v_head_dim * h  # W_o
    )


def param_bytes(cfg: ModelConfig, tp: int = 1, pp: int = 1,
                ep: int = 1) -> int:
    """Per-device bytes of the stacked Llama param tree (models/llama.py
    init_params) under tensor parallelism `tp`, pipeline stages `pp`
    (per-layer leaves shard their L axis over pp, parallel/sharding.py) and
    expert parallelism `ep` (an MoE layer's num_experts SwiGLUs shard their
    E axis over ep; its h x E router is replicated)."""
    if cfg.quantization:
        from ..models.quantization import quantized_param_bytes

        return quantized_param_bytes(cfg, tp, pp, ep)
    if cfg.mla:
        # one device (parallel layouts of the latent tree are refused at
        # engine start): the tree of models/llama.py:_init_params_mla
        m = layer_matrix_params(cfg)
        h, L = cfg.hidden_size, cfg.num_layers
        total = (
            cfg.vocab_size * h * (1 if cfg.tie_word_embeddings else 2) + h
            + L * (m["attn"] + 2 * h + cfg.kv_lora_rank)
            + len(cfg.index_layers) * indexer_params(cfg)
            + m["dense_layers"] * m["dense_mlp"]
            + m["moe_layers"] * (
                cfg.held_experts * m["expert"] + m["shared"] + m["router"])
        ) * dtype_bytes(cfg.dtype)
        # the selection bias is float32 whatever the model's dtype
        return total + m["moe_layers"] * cfg.num_experts * 4
    # every other tree, from the per-layer list: a layer's matrices shard
    # over tp (its held experts over ep first), its norms, q/k norms and
    # router are replicated; pp shards the layer axis (a tree stacked by
    # layer kind runs on one device: tp = pp = ep = 1 there)
    h, hd, nkv, L = (
        cfg.hidden_size, cfg.head_dim, cfg.num_kv_heads, cfg.num_layers)
    expert = 3 * h * cfg.expert_intermediate_size
    layers = 0
    f32_extra = 0  # bytes of float32 leaves beyond the model dtype's count
    for spec in cfg.layer_specs:
        nh = spec.num_heads
        sharded = 2 * h * nh * hd + 2 * h * nkv * hd
        norms = (0 if cfg.post_norms_only else 2) + (
            2 if cfg.sandwich_norms or cfg.post_norms_only else 0)
        replicated = norms * h + qk_norm_params(cfg)
        if spec.recurrence == "ssd":
            # the state-space branch BESIDE the attention heads
            # (models/llama.py:attn_ssm): the input and output projections,
            # the taps and their bias, the gated norm; A_log, D and dt_bias
            # in float32
            sharded += (h * (2 * cfg.ssm_inner
                             + 2 * cfg.ssm_num_groups * cfg.ssm_state_dim
                             + cfg.ssm_num_heads)
                        + cfg.ssm_inner * h
                        + (STATE_CONV_TAPS + 1) * cfg.ssm_conv_dim
                        + cfg.ssm_inner)
            f32_extra += 4 * 3 * cfg.ssm_num_heads
        elif spec.holds_state:
            # the KDA mixer (models/llama.py:attn_linear): q, k, v, o; the
            # two low-rank pairs; the step size's projection; the taps; the
            # output norm; A_log and dt_bias in float32
            lh = cfg.linear_num_heads
            replicated = norms * h  # (a flat QK-norm is the page layers')
            kw, vw = lh * cfg.linear_head_dim, lh * cfg.linear_value_dim
            r = cfg.linear_gate_rank
            # the decay gate projects to a key channel or to a head
            # (`linear_gate_per`), the output gate to the values
            gw = kw if cfg.linear_gate_per == "channel" else lh

            def gate(width):  # (rank 0: a gate projects at full rank)
                return (h * r + r * width) if r else h * width

            sharded = (2 * h * kw + 2 * h * vw + gate(gw) + gate(vw)
                       + h * lh + STATE_CONV_TAPS * (2 * kw + vw)
                       + cfg.linear_value_dim)
            f32_extra += 4 * (lh + gw)
        elif spec.latent:
            # the latent mixer (models/llama.py:attn_latent): its four
            # matrices, the latent's norm, the gate a head
            sharded = (latent_attn_params(cfg, nh) + cfg.kv_lora_rank
                       + (h * nh if cfg.attn_output_gate else 0))
        elif cfg.attn_output_gate:
            sharded += h * nh * (
                hd if cfg.attn_output_gate == "element" else 1)
        if spec.experts and cfg.moe_selection_bias:
            f32_extra += 4 * cfg.num_experts
        if spec.experts:
            sharded += (
                cfg.held_experts // ep + cfg.n_shared_experts) * expert
            replicated += h * cfg.num_experts  # the router, published width
        else:
            sharded += 3 * h * cfg.intermediate_size
        layers += sharded // tp + replicated
        if cfg.attention_bias:
            layers += (nh * hd + 2 * nkv * hd) // tp
    embed = cfg.vocab_size * h // tp
    head = 0 if cfg.tie_word_embeddings else h * cfg.vocab_size // tp
    total = embed + layers * ((L + pp - 1) // pp) // L + h + head
    return total * dtype_bytes(cfg.dtype) + f32_extra


def kv_block_bytes(cfg: ModelConfig, block_size: int, tp: int = 1,
                   pp: int = 1, kv_dtype: str | None = None,
                   group: str | None = None) -> int:
    """Per-device bytes of ONE pool block across all layers: kv heads shard
    over tp and the block axis shards over pp, so a device holds every
    layer's pages for 1/pp of the blocks — per-device cost of adding a
    block is therefore 1/pp of its global bytes. kv_dtype overrides the
    model dtype when the pool is quantized (CacheConfig.kv_cache_dtype).

    `group`: "full" or "window" counts the layers of that PAGE GROUP only
    (engine/kv_cache.py: a model with window layers keeps two groups of
    pages, each sized on its own; 0 for a group without layers); None
    counts every layer (a model with one group)."""
    if cfg.latent_layers:
        # one latent a token and latent layer, `latent_width` values as
        # stored (the zero padding to whole lane tiles is real HBM, so it
        # counts); a model's pages are all latents or none is, and a layer
        # that holds state has none
        # ... and one index key a token and layer that owns an indexer
        return max(1, (
            len(cfg.latent_layers) * block_size * cfg.latent_width
            * dtype_bytes(kv_dtype or cfg.dtype)
            + index_block_bytes(cfg, block_size, kv_dtype)
        ) // pp)
    # (the heads a PAGE holds: ModelConfig.page_kv_heads)
    kvh = max(1, cfg.pool_kv_heads // tp)
    # (a layer that holds state has no pages: LayerSpec.holds_pages)
    layers = {None: len(cfg.page_layers), "full": len(cfg.full_layers),
              "window": len(cfg.window_layers)}[group]
    nbytes = (
        layers * 2 * block_size * kvh * cfg.head_dim
        * dtype_bytes(kv_dtype or cfg.dtype)
    ) // pp
    return nbytes if group else max(1, nbytes)


def window_row_blocks(model: ModelConfig, cache: CacheConfig,
                      sched: SchedulerConfig) -> int:
    """Pages of the WINDOW group one row can hold at once: the window behind
    its resolved position, one prefill chunk in flight (the give-back runs
    on resolved positions only: a rolled-back step must find its pages),
    two decode windows (one in flight, one enqueued behind it), and the
    page rounding at both ends. (A second chunk planned over the one in
    flight spends another seat's share: two token budgets are in flight
    over ALL rows at most, and the group holds `max_num_seqs` of them; with
    ONE seat the second chunk may find no page, is then not planned, and
    the step loop resolves the first: `Scheduler._ensure_blocks`.)"""
    tokens = (model.max_window + sched.max_num_batched_tokens
              + 2 * sched.decode_window)
    return math.ceil(tokens / cache.block_size) + 2


def window_group_blockers(config: EngineConfig) -> list[str]:
    """What a configuration asks for that needs a window layer's pages to
    live as long as the row's, or that no layout of the window page group
    has been built for. Any of them keeps the window layers on the ONE
    full-length group (every layer holds the context, the window is a mask
    over it: what a sliding model ran on before the group existed), so
    nothing a sliding model could be served with is lost to the group."""
    asked = []
    if config.cache.enable_prefix_caching:
        # a hit would also need the window group's pages for the window
        # before its boundary, which are given back
        asked.append("prefix caching (on unless --no-enable-prefix-caching)")
    asked += config.kv_tiers_asked()  # content-addressed pages of one group
    if config.parallel.world_size > 1:
        # one window table a row on one device: no dp shard of it, no pp
        # shard of the group's block axis, no ring-attention window mask
        asked.append(f"a mesh of {config.parallel.world_size} devices")
    if config.lora.max_loras > 0:
        # adapters ran only over one table a row (and salt prefix hits)
        asked.append("LoRA adapters")
    sched = config.scheduler
    if sched.num_speculative_tokens and sched.speculative_method == "draft":
        # the draft's scratch pages live in the full group only
        asked.append("a draft model")
    return asked


def resolve_window_blocks(config: EngineConfig) -> int | None:
    """Pages of the WINDOW group (incl. its null page 0), or None where
    every layer's pages live in one group: a model without window layers,
    a latent pool, or a configuration that asks for something the group
    has no layout for (`window_group_blockers`). Every seat's worst case
    (`window_row_blocks`), so the group never runs out, whatever the rows
    do. The ONE rule the engine, the runner's arrays and the scheduler's
    allocator follow."""
    model = config.model
    if (not model.any_sliding or model.latent_layers
            or window_group_blockers(config)):
        return None
    return config.scheduler.max_num_seqs * window_row_blocks(
        model, config.cache, config.scheduler) + 1


def state_slot_bytes(model: ModelConfig) -> int:
    """Bytes ONE state slot holds across the layers that hold state,
    whatever the context length: a layer's float32 state and its
    convolution's tail — the last taps - 1 rows of the convolved channels
    in the model's dtype —, both of the geometry of the layer's RECURRENCE
    (ModelConfig.state_geometry: (heads, Dk, Dv) and q | k | v for the gated
    delta rule, (heads, P, N) and x | B | C for the state-space duality).
    0 for a model without such layers."""
    total = 0
    for i in model.state_layers:
        shape, channels = model.state_geometry(
            model.layer_specs[i].recurrence)
        total += (math.prod(shape) * 4 + (STATE_CONV_TAPS - 1) * channels
                  * dtype_bytes(model.dtype))
    return total


def resolve_state_slots(config: EngineConfig) -> int:
    """State slots a BANK of the state pools holds: one a seat and the null
    slot 0 that padding rows write to; 0 for a model no layer of which
    holds state. The arrays hold two banks (kv_cache.StateSlots)."""
    if not config.model.state_layers:
        return 0
    return config.scheduler.max_num_seqs + 1


def state_pool_bytes(model: ModelConfig, state_slots: int) -> int:
    """Bytes of the state pools: two banks of `state_slots` slots."""
    return 2 * state_slots * state_slot_bytes(model)


def kv_pool_bytes(model: ModelConfig, cache: CacheConfig,
                  parallel: ParallelConfig, num_window_blocks: int | None,
                  kv_dtype: str | None = None, state_slots: int = 0) -> int:
    """Per-device bytes of the whole cache: `num_blocks` pages of every
    layer that holds pages — or with a window group `num_blocks` pages of
    the full layers and `num_window_blocks` of the window layers — and the
    state pools of the layers that hold state (`state_slots` a bank)."""
    tp, pp = parallel.tensor_parallel_size, parallel.pipeline_parallel_size
    kv_dtype = kv_dtype or cache.resolved_kv_dtype(model.dtype)
    state = state_pool_bytes(model, state_slots)
    if num_window_blocks is None:
        return state + cache.num_blocks * kv_block_bytes(
            model, cache.block_size, tp, pp, kv_dtype=kv_dtype)
    return state + (
        cache.num_blocks * kv_block_bytes(
            model, cache.block_size, tp, pp, kv_dtype, group="full")
        + num_window_blocks * kv_block_bytes(
            model, cache.block_size, tp, pp, kv_dtype, group="window"))


def hoist_reserve_bytes(
    model: ModelConfig,
    cache: CacheConfig,
    parallel: ParallelConfig,
    max_num_seqs: int | None,
) -> int:
    """Per-device bytes set aside for hoisted decode-window history
    (HOIST_CTX_TOKENS of context per live row; same kv_block_bytes layout
    arithmetic the budget check uses, so funded == admissible)."""
    if max_num_seqs is None:
        return 0
    tokens = min(model.max_model_len, HOIST_CTX_TOKENS)
    blocks = math.ceil(tokens / cache.block_size)
    b_local = math.ceil(max_num_seqs / parallel.data_parallel_size)
    return b_local * blocks * kv_block_bytes(
        model, cache.block_size, parallel.tensor_parallel_size,
        parallel.pipeline_parallel_size,
        kv_dtype=cache.resolved_kv_dtype(model.dtype),
    )


def headroom_budget(
    model: ModelConfig,
    cache: CacheConfig,
    parallel: ParallelConfig,
    hbm_bytes: int | None = None,
) -> int:
    """Utilization-capped HBM minus weights minus reserve — the ONE
    definition of the engine's allocatable budget, shared by pool sizing
    (derive_num_blocks) and the runner's hoist admission
    (model_runner._compute_hoist_budget) so the two can't drift."""
    hbm = hbm_bytes if hbm_bytes is not None else device_hbm_bytes()
    return (
        int(hbm * cache.hbm_utilization)
        - param_bytes(
            model, parallel.tensor_parallel_size,
            parallel.pipeline_parallel_size,
            parallel.expert_parallel_size,
        )
        - RESERVE_BYTES
    )


def device_hbm_bytes() -> int:
    """Memory of one local device, as the device reports it. Only the CPU
    backend (no memory_stats) gets the stand-in: a KV pool sized for a chip
    that is not there would serve, slower, and say nothing."""
    dev = jax.local_devices()[0]
    stats = dev.memory_stats()
    if stats and "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    if dev.platform == "cpu":
        return CPU_ASSUMED_HBM_BYTES
    raise RuntimeError(
        f"{dev.platform} device {dev.id} ({dev.device_kind}) reports no "
        f"bytes_limit (memory_stats() = {stats!r}); refusing to size the KV "
        "pool from an assumed HBM size"
    )


def device_report(mesh) -> dict:
    """What this process runs on, as JAX reports it: platform, kind and
    count of all devices, plus the ids and live memory of the devices in the
    engine's mesh (memory keys are absent on backends that report none)."""
    devices = jax.devices()
    per_device = []
    for dev in mesh.devices.flat:
        stats = dev.memory_stats() or {}
        per_device.append({
            "id": dev.id,
            **{
                k: int(stats[k])
                for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                if k in stats
            },
        })
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "mesh_shape": {k: int(v) for k, v in mesh.shape.items()},
        "mesh_device_ids": [d["id"] for d in per_device],
        "devices": per_device,
    }


def derive_num_blocks(
    model: ModelConfig,
    cache: CacheConfig,
    parallel: ParallelConfig,
    hbm_bytes: int | None = None,
    max_num_seqs: int | None = None,
    num_window_blocks: int | None = None,
    state_slots: int = 0,
) -> int:
    """Blocks that fit in hbm_utilization × HBM after weights + reserve.

    The state pools of a model with layers that hold state (`state_slots` a
    bank, resolve_state_slots) come off the budget first.

    With a window page group (`num_window_blocks`, resolve_window_blocks)
    its bytes come off the budget first and the result counts the FULL
    group's pages, at the full layers' bytes a page; a model whose layers
    are all window layers keeps only bookkeeping in the full group.

    The fused decode window keeps the pool loop-invariant (staged-KV design,
    ops/attention.py:paged_attention_with_staged), so compile-time temps no
    longer scale with pool size and the pool really can take ~the whole
    post-weights budget. When `max_num_seqs` is known the pool is still
    capped at what the workload can use — live-sequence capacity, times
    PREFIX_CACHE_OVERPROVISION when prefix caching is on (LRU cache room) —
    so tiny models on big chips don't hold HBM they can never reference."""
    hbm = hbm_bytes if hbm_bytes is not None else device_hbm_bytes()
    tp = parallel.tensor_parallel_size
    pp = parallel.pipeline_parallel_size
    ep = parallel.expert_parallel_size
    budget = headroom_budget(
        model, cache, parallel, hbm
    ) - hoist_reserve_bytes(model, cache, parallel, max_num_seqs)
    budget -= state_pool_bytes(model, state_slots)
    kv_dtype = cache.resolved_kv_dtype(model.dtype)
    per_block = kv_block_bytes(model, cache.block_size, tp, pp, kv_dtype)
    if num_window_blocks is not None:
        budget -= num_window_blocks * kv_block_bytes(
            model, cache.block_size, tp, pp, kv_dtype, group="window")
        per_block = kv_block_bytes(
            model, cache.block_size, tp, pp, kv_dtype, group="full")
        if per_block == 0:
            # no full layer: the full group's block ids are the scheduler's
            # bookkeeping only, and no array holds them
            return (max_num_seqs or 1) * cache.max_blocks_per_seq(
                model.max_model_len) + 1
    # pp shards the block axis, so the pool must hold >= pp blocks (and the
    # pp-divisibility rounding below must never round UP past the budget)
    if budget < 2 * per_block * max(1, pp):
        raise ValueError(
            f"model weights ({param_bytes(model, tp, pp, ep) / 1024**3:.2f} GiB/device) "
            "+ reserve leave no room for a KV pool in "
            f"{cache.hbm_utilization:.0%} of {hbm / 1024**3:.2f} GiB HBM — "
            f"raise hbm_utilization, shard wider (tp={tp}), or shrink the model"
        )
    n = budget // per_block
    if max_num_seqs is not None:
        per_seq = cache.max_blocks_per_seq(model.max_model_len)
        over = PREFIX_CACHE_OVERPROVISION if cache.enable_prefix_caching else 1
        # +1: block 0 is the reserved null page, not usable capacity
        n = min(n, over * max_num_seqs * per_seq + 1)
    if pp > 1:
        # the pool's block axis shards over pp stages — keep it divisible
        # (round DOWN: the guard above ensures n >= 2*pp, so this never
        # under-runs the 2-block minimum or overruns the budget)
        n = (n // pp) * pp
    logger.info(
        "KV pool: %d blocks of %d tokens (%.2f GiB of %.2f GiB HBM; weights %.2f GiB)",
        n,
        cache.block_size,
        n * per_block / 1024**3,
        hbm / 1024**3,
        param_bytes(model, tp, pp, ep) / 1024**3,
    )
    return int(n)
