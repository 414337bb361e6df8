"""Checkpoint loading: HF safetensors → the stacked JAX param tree.

The reference stack's contract is model-URL → served weights — its operator
passes the model path straight to `vllm serve`
(reference: operator/internal/controller/vllmruntime_controller.go:228-286)
and caches weights on a PVC (helm/templates/pvc.yaml, tutorial 03). The TPU
engine's equivalent: a local HF checkpoint dir (config.json +
*.safetensors), mapped into the scan-stacked layout of
models/llama.py:init_params:

- HF stores projection weights (out, in); ours are (in, out) so the forward
  pass is plain ``x @ w`` — every matrix transposes on load.
- Per-layer weights stack along a leading L axis (one traced layer body).

Weights land on device via the caller's NamedShardings (ModelRunner
device_puts each leaf into its TP layout), so a checkpoint loads directly
into its sharded placement without a replicated copy first.
"""

from __future__ import annotations

import glob
import os

import numpy as np

from ..engine.config import ModelConfig
from ..utils.logging import init_logger

logger = init_logger(__name__)


class _ShardedCheckpoint:
    """All tensors across a checkpoint's *.safetensors shards, opened lazily."""

    def __init__(self, path: str):
        from safetensors import safe_open

        files = sorted(glob.glob(os.path.join(path, "*.safetensors")))
        if not files:
            raise FileNotFoundError(f"no *.safetensors under {path}")
        self._handles = [safe_open(f, framework="np") for f in files]
        self._index: dict[str, int] = {}
        for fi, h in enumerate(self._handles):
            for name in h.keys():
                self._index[name] = fi

    def __contains__(self, name: str) -> bool:
        return name in self._index

    def get(self, name: str) -> np.ndarray:
        if name not in self._index:
            raise KeyError(
                f"tensor {name!r} missing from checkpoint "
                f"(have e.g. {list(self._index)[:5]})"
            )
        return self._handles[self._index[name]].get_tensor(name)

    def keys(self):
        return self._index.keys()


def load_checkpoint_params(cfg: ModelConfig) -> dict:
    """Read cfg.checkpoint (HF dir) into the stacked param tree as numpy
    arrays in cfg.dtype. Llama / Mistral / Qwen2 weight naming."""
    import ml_dtypes

    assert cfg.checkpoint, "ModelConfig.checkpoint is not set"
    ckpt = _ShardedCheckpoint(cfg.checkpoint)
    dt = (
        ml_dtypes.bfloat16 if cfg.dtype == "bfloat16" else np.dtype(cfg.dtype)
    )

    def mat(name: str) -> np.ndarray:
        # HF (out, in) -> ours (in, out)
        return np.ascontiguousarray(ckpt.get(name).T).astype(dt)

    def vec(name: str) -> np.ndarray:
        return ckpt.get(name).astype(dt)

    def stack(fmt: str, kind) -> np.ndarray:
        return np.stack([kind(fmt.format(i)) for i in range(cfg.num_layers)])

    p = "model.layers.{}."
    if cfg.num_experts and cfg.architecture == "phi3":
        raise NotImplementedError(
            "phi3-fused loading with MoE experts is not implemented"
        )
    if cfg.num_experts:
        # Mixtral: block_sparse_moe.gate is the router; experts' w1/w3/w2
        # are gate/up/down. Expert matrices stack along a leading E axis
        # within each layer → (L, E, in, out)
        def estack(fmt: str) -> np.ndarray:
            return np.stack(
                [
                    np.stack(
                        [
                            mat(fmt.format(i, j))
                            for j in range(cfg.num_experts)
                        ]
                    )
                    for i in range(cfg.num_layers)
                ]
            )

        if cfg.architecture in ("qwen3moe", "olmoe"):
            # Qwen3-MoE and OLMoE naming: mlp.gate router; experts carry
            # gate_proj/up_proj/down_proj like dense layers
            ex = "model.layers.{0}.mlp.experts.{1}."
            mlp = {
                "router": stack(p + "mlp.gate.weight", mat),
                "gate": estack(ex + "gate_proj.weight"),
                "up": estack(ex + "up_proj.weight"),
                "down": estack(ex + "down_proj.weight"),
            }
        else:
            ex = "model.layers.{0}.block_sparse_moe.experts.{1}."
            mlp = {
                "router": stack(p + "block_sparse_moe.gate.weight", mat),
                "gate": estack(ex + "w1.weight"),
                "up": estack(ex + "w3.weight"),
                "down": estack(ex + "w2.weight"),
            }
        mlp_key = "moe"
    elif cfg.architecture != "phi3":
        mlp = {
            "gate": stack(p + "mlp.gate_proj.weight", mat),
            "up": stack(p + "mlp.up_proj.weight", mat),
            "down": stack(p + "mlp.down_proj.weight", mat),
        }
        mlp_key = "mlp"
    if cfg.architecture == "phi3":
        # Phi-3 fuses q/k/v into qkv_proj (row-stacked q, k, v) and
        # gate/up into gate_up_proj — split on the HF OUT axis (rows)
        # before the (out, in) -> (in, out) transpose. Each fused tensor
        # is read from disk ONCE per layer and sliced in memory.
        nh_rows = cfg.num_heads * cfg.head_dim
        nkv_rows = cfg.num_kv_heads * cfg.head_dim
        it = cfg.intermediate_size
        q_l, k_l, v_l, g_l, u_l = [], [], [], [], []
        for i in range(cfg.num_layers):
            qkv = ckpt.get(p.format(i) + "self_attn.qkv_proj.weight")
            q_l.append(np.ascontiguousarray(qkv[:nh_rows].T).astype(dt))
            k_l.append(np.ascontiguousarray(
                qkv[nh_rows:nh_rows + nkv_rows].T).astype(dt))
            v_l.append(np.ascontiguousarray(
                qkv[nh_rows + nkv_rows:nh_rows + 2 * nkv_rows].T
            ).astype(dt))
            gu_w = ckpt.get(p.format(i) + "mlp.gate_up_proj.weight")
            g_l.append(np.ascontiguousarray(gu_w[:it].T).astype(dt))
            u_l.append(np.ascontiguousarray(gu_w[it:2 * it].T).astype(dt))
        attn_tree = {
            "wq": np.stack(q_l), "wk": np.stack(k_l), "wv": np.stack(v_l),
            "wo": stack(p + "self_attn.o_proj.weight", mat),
        }
        mlp = {
            "gate": np.stack(g_l), "up": np.stack(u_l),
            "down": stack(p + "mlp.down_proj.weight", mat),
        }
        mlp_key = "mlp"
    else:
        attn_tree = {
            "wq": stack(p + "self_attn.q_proj.weight", mat),
            "wk": stack(p + "self_attn.k_proj.weight", mat),
            "wv": stack(p + "self_attn.v_proj.weight", mat),
            "wo": stack(p + "self_attn.o_proj.weight", mat),
        }
    params: dict = {
        "embed": vec("model.embed_tokens.weight"),
        "layers": {
            "attn": attn_tree,
            mlp_key: mlp,
            **(
                {}
                if cfg.post_norms_only
                else {"input_norm": stack(p + "input_layernorm.weight",
                                          vec)}
            ),
            # Gemma-2 sandwich layout: our pre-MLP norm slot maps to HF
            # pre_feedforward_layernorm; HF's post_attention_layernorm is
            # the attention-OUTPUT norm (attn_out_norm below)
            **(
                {}
                if cfg.post_norms_only
                else {"post_attn_norm": stack(
                    p + ("pre_feedforward_layernorm.weight"
                         if cfg.sandwich_norms
                         else "post_attention_layernorm.weight"), vec)}
            ),
        },
        "final_norm": vec("model.norm.weight"),
    }
    if cfg.attention_bias:
        params["layers"]["attn"]["bq"] = stack(p + "self_attn.q_proj.bias", vec)
        params["layers"]["attn"]["bk"] = stack(p + "self_attn.k_proj.bias", vec)
        params["layers"]["attn"]["bv"] = stack(p + "self_attn.v_proj.bias", vec)
    if cfg.qk_norm or cfg.qk_norm_flat:
        params["layers"]["attn"]["q_norm"] = stack(
            p + "self_attn.q_norm.weight", vec)
        params["layers"]["attn"]["k_norm"] = stack(
            p + "self_attn.k_norm.weight", vec)
    if cfg.sandwich_norms or cfg.post_norms_only:
        params["layers"]["attn_out_norm"] = stack(
            p + "post_attention_layernorm.weight", vec)
        params["layers"]["ffw_out_norm"] = stack(
            p + "post_feedforward_layernorm.weight", vec)

    if not cfg.tie_word_embeddings:
        params["lm_head"] = mat("lm_head.weight")
    logger.info(
        "loaded checkpoint %s (%d tensors, dtype %s)",
        cfg.checkpoint, len(list(ckpt.keys())), cfg.dtype,
    )
    return params
