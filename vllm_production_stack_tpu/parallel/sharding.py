"""PartitionSpecs for model params, KV cache, and activations.

Megatron-style tensor parallelism expressed as named shardings — the TPU
equivalent of the reference's `--tensor-parallel-size` (SURVEY §2.4):

  - attention q/k/v projections: column-parallel (shard the head axis)
  - attention output projection: row-parallel (XLA inserts the psum)
  - MLP gate/up: column-parallel; down: row-parallel
  - embedding + lm_head: vocab-sharded (logits psum/all-gathered by XLA)
  - KV cache pages: sharded over kv-heads on the tp axis, so each chip only
    ever touches its own heads' pages (no cross-chip KV traffic in decode)

Param trees are "stacked": every per-layer leaf carries a leading num_layers
dimension and the model scans over it, so specs below include that axis first.
"""

from __future__ import annotations

from jax.sharding import PartitionSpec as P

from ..engine.config import ModelConfig
from .mesh import DP_AXIS, EP_AXIS, PP_AXIS, TP_AXIS


def llama_param_specs(cfg: ModelConfig) -> dict:
    """PartitionSpec tree structurally matching init_params(cfg)'s tree
    (optional leaves — attention biases, untied lm_head — included only when
    the config produces them).

    Pipeline parallelism is GSPMD stage sharding: every per-layer leaf's
    leading L axis is sharded over the pp mesh axis, so each stage holds a
    contiguous block of layers and XLA moves the activations between stages
    (the TPU replacement for the reference's Ray-deployed
    --pipeline-parallel-size, ray-cluster.yaml:556-566). On a pp=1 mesh the
    axis is size 1 and the specs degrade to pure tp.

    A multi-head-latent-attention tree (models/llama.py:_init_params_mla)
    has no sharded layout yet — the runner refuses a mesh over one device
    for it by name — so every leaf of it is replicated."""
    if cfg.mla or cfg.tree_by_kind:
        # (a tree stacked by layer kind has no sharded layout yet either,
        # and is refused by name the same way)
        import jax

        from ..models.llama import init_params

        return jax.tree.map(
            lambda _: P(),
            jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    attn = {
        # [L, heads, head_dim, hidden] — L over pp stages, head axis over tp
        "wq": P(PP_AXIS, TP_AXIS, None, None),
        "wk": P(PP_AXIS, TP_AXIS, None, None),
        "wv": P(PP_AXIS, TP_AXIS, None, None),
        # [L, heads*head_dim, hidden] — shard input (head) axis; psum after
        "wo": P(PP_AXIS, TP_AXIS, None),
    }
    if cfg.attention_bias:
        attn |= {
            "bq": P(PP_AXIS, TP_AXIS),
            "bk": P(PP_AXIS, TP_AXIS),
            "bv": P(PP_AXIS, TP_AXIS),
        }
    if cfg.qk_norm or cfg.qk_norm_flat:
        # replicated in both scopes: per-head (qwen3, [L, head_dim]) norm
        # weights are head-invariant, and the flat scope (olmo2,
        # [L, heads*head_dim]) needs the WHOLE axis for its mean-square —
        # a tp shard cannot compute it locally, and the vectors are tiny
        attn |= {
            "q_norm": P(PP_AXIS, None),
            "k_norm": P(PP_AXIS, None),
        }
    if cfg.num_experts:
        # Mixtral MoE: expert axis over ep (each device holds E/ep experts —
        # the reason ep exists: 8x7B expert weights don't fit one chip),
        # inner axis over tp; GSPMD psums the combine over ep and tp
        mlp = {
            "router": P(PP_AXIS, None, None),  # [L, hidden, E] tiny
            "gate": P(PP_AXIS, EP_AXIS, None, TP_AXIS),  # [L, E, h, inter]
            "up": P(PP_AXIS, EP_AXIS, None, TP_AXIS),
            "down": P(PP_AXIS, EP_AXIS, TP_AXIS, None),  # [L, E, inter, h]
        }
        mlp_key = "moe"
    else:
        mlp = {
            "gate": P(PP_AXIS, None, TP_AXIS),  # [L, hidden, inter]
            "up": P(PP_AXIS, None, TP_AXIS),
            "down": P(PP_AXIS, TP_AXIS, None),  # [L, inter, hidden]
        }
        mlp_key = "mlp"
    specs = {
        "embed": P(TP_AXIS, None),  # [vocab, hidden] vocab-sharded
        "layers": {
            "attn": attn,
            mlp_key: mlp,
            **(
                {}
                if cfg.post_norms_only
                else {"input_norm": P(PP_AXIS, None),
                      "post_attn_norm": P(PP_AXIS, None)}
            ),
            **(
                {"attn_out_norm": P(PP_AXIS, None),
                 "ffw_out_norm": P(PP_AXIS, None)}
                if cfg.sandwich_norms or cfg.post_norms_only
                else {}
            ),
        },
        "final_norm": P(None),
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, TP_AXIS)  # [hidden, vocab] vocab-sharded
    return specs


def lora_param_specs(cfg: ModelConfig, lora_cfg) -> dict:
    """Spec tree matching init_lora_params. Column-parallel modules shard
    B's out axis (like their base weight); row-parallel modules shard A's in
    axis; the rank axis stays replicated (it's ≤ max_lora_rank)."""
    from ..models.llama import lora_module_dims

    row_parallel = {"o_proj", "down_proj"}
    # same module filter as init_lora_params, so the spec tree and the param
    # tree can never diverge structurally; L (axis 1) shards over pp stages
    # alongside the base weights
    names = [m for m in lora_cfg.target_modules if m in lora_module_dims(cfg)]
    specs: dict = {"scale": P()}
    for name in names:
        if name in row_parallel:
            specs[name] = {
                "A": P(None, PP_AXIS, TP_AXIS, None),  # (n, L, in, r)
                "B": P(None, PP_AXIS, None, None),  # (n, L, r, out)
            }
        else:
            specs[name] = {
                "A": P(None, PP_AXIS, None, None),
                "B": P(None, PP_AXIS, None, TP_AXIS),
            }
    return specs


def kv_cache_spec() -> P:
    """Per-layer leaf [2, num_blocks, block_size, kv_heads, head_dim] — kv
    heads shard over tp; the block axis shards over pp so each stage holds
    1/pp of the pool (per-layer leaves can't be placed per-stage with one
    prefix spec, but block-sharding splits the memory the same way).
    Applies to every leaf of the per-layer KV tuple (jit/`device_put` treat
    a single spec as a pytree prefix)."""
    return P(None, PP_AXIS, None, TP_AXIS, None)


def decode_tokens_spec() -> P:
    """[B] token ids — shard batch over dp."""
    return P(DP_AXIS)


def prefill_tokens_spec() -> P:
    """[T] a single sequence's chunk — replicated (prefill batches one seq)."""
    return P()
