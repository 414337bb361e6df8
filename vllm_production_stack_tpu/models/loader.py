"""Checkpoint loading: HF safetensors → the stacked JAX param tree.

The reference stack's contract is model-URL → served weights — its operator
passes the model path straight to `vllm serve`
(reference: operator/internal/controller/vllmruntime_controller.go:228-286)
and caches weights on a PVC (helm/templates/pvc.yaml, tutorial 03). The TPU
engine's equivalent: a local HF checkpoint dir (config.json +
*.safetensors), mapped into the scan-stacked layout of
models/llama.py:init_params:

- HF stores projection weights (out, in); ours are (in, out) so the forward
  pass is plain ``x @ w`` — every matrix transposes on load, but the GQA
  layers' q, k and v projections: those stay (out, in) with the head axis
  split, (heads, head_dim, in), the orientation their product reads
  (models/llama.py:_mm_heads).
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
    if cfg.architecture == "laguna":
        raise NotImplementedError(
            f"{cfg.model}: reading a laguna checkpoint is not implemented — "
            "the published file's tensor names (the per-head gate's, the "
            "shared expert's) could not be checked offline, and a guessed "
            "mapping would load silently wrong weights; the config is read "
            "(models/registry.py:_laguna_keys) and the registry presets "
            "serve random weights")
    ckpt = _ShardedCheckpoint(cfg.checkpoint)
    dt = (
        ml_dtypes.bfloat16 if cfg.dtype == "bfloat16" else np.dtype(cfg.dtype)
    )

    def mat(name: str) -> np.ndarray:
        # HF (out, in) -> ours (in, out)
        return np.ascontiguousarray(ckpt.get(name).T).astype(dt)

    def rows(name: str) -> np.ndarray:
        # HF (out, in), as it lies
        return ckpt.get(name).astype(dt)

    def heads(name: str) -> np.ndarray:
        # HF (heads * head_dim, in) -> ours (heads, head_dim, in)
        w = rows(name)
        return w.reshape(-1, cfg.head_dim, w.shape[-1])

    def vec(name: str) -> np.ndarray:
        return ckpt.get(name).astype(dt)

    def stack(fmt: str, kind) -> np.ndarray:
        return np.stack([kind(fmt.format(i)) for i in range(cfg.num_layers)])

    if cfg.mla:
        params = _deepseek_v3_tree(cfg, mat, vec, rows)
        logger.info(
            "loaded checkpoint %s (%d tensors, dtype %s)",
            cfg.checkpoint, len(list(ckpt.keys())), cfg.dtype,
        )
        return params
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
        # (gate/up: before the (out, in) -> (in, out) transpose). Each
        # fused tensor is read from disk ONCE per layer and sliced in memory.
        nh_rows = cfg.num_heads * cfg.head_dim
        nkv_rows = cfg.num_kv_heads * cfg.head_dim
        it = cfg.intermediate_size
        q_l, k_l, v_l, g_l, u_l = [], [], [], [], []
        for i in range(cfg.num_layers):
            qkv = ckpt.get(p.format(i) + "self_attn.qkv_proj.weight")
            for out, at in ((q_l, 0), (k_l, nh_rows),
                            (v_l, nh_rows + nkv_rows)):
                part = qkv[at:at + (nkv_rows if at else nh_rows)]
                out.append(part.reshape(
                    -1, cfg.head_dim, part.shape[-1]).astype(dt))
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
            "wq": stack(p + "self_attn.q_proj.weight", heads),
            "wk": stack(p + "self_attn.k_proj.weight", heads),
            "wv": stack(p + "self_attn.v_proj.weight", heads),
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


# -- DeepSeek-V3 family (Kanana-2): latent attention, leading dense layers ----

def _rope_columns(cfg: ModelConfig, w, to_checkpoint: bool):
    """The rope part of a projection's OUTPUT columns (last axis, width
    qk_rope_head_dim) between the checkpoint's order and the program's.
    With `rope_interleave` a checkpoint holds rope pairs side by side,
    (x0, x1), (x2, x3), ...; the program's rotate-half rope
    (ops/attention.py:apply_rope) pairs column i with i + d/2. Scores are
    invariant under one permutation of q_rope and k_rope alike, so the
    columns of W_q and W_kv_a are de-interleaved ONCE, here, and no
    activation is permuted at run time."""
    if not cfg.rope_interleave:
        return w
    d = w.shape[-1]
    if to_checkpoint:
        return np.stack([w[..., : d // 2], w[..., d // 2:]], axis=-1).reshape(
            w.shape)
    return np.concatenate([w[..., 0::2], w[..., 1::2]], axis=-1)


def _deepseek_v3_tree(cfg: ModelConfig, mat, vec, rows) -> dict:
    """Checkpoint names -> models/llama.py:_init_params_mla's tree (`mat`: a
    tensor (in, out), `rows`: (out, in) as the file holds it, `vec`)."""
    nh, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    r, k_dense = cfg.kv_lora_rank, cfg.first_k_dense_replace
    p = "model.layers.{}."

    def stack(fmt, kind, layers):
        return np.stack([kind(fmt.format(i)) for i in layers])

    def wq(name):
        # (heads, nope ‖ rope, h): the file's rows, the head axis split
        w = rows(name).reshape(nh, dn + dr, -1)
        w[:, dn:] = np.swapaxes(_rope_columns(
            cfg, np.swapaxes(w[:, dn:], -1, -2), to_checkpoint=False), -1, -2)
        return w

    def kv_b(name):
        # (heads, k_nope ‖ v, rank), split where the two products part
        return rows(name).reshape(nh, -1, r)

    def wkv_a(name):
        w = mat(name)  # (h, rank ‖ rope)
        w[:, r:] = _rope_columns(cfg, w[:, r:], to_checkpoint=False)
        return w

    every = range(cfg.num_layers)
    dense = range(k_dense if cfg.num_experts else cfg.num_layers)
    sparse = range(len(dense), cfg.num_layers)
    a = p + "self_attn."
    layers: dict = {
        "attn": {
            "wq": stack(a + "q_proj.weight", wq, every),
            "wkv_a": stack(a + "kv_a_proj_with_mqa.weight", wkv_a, every),
            "kv_a_norm": stack(a + "kv_a_layernorm.weight", vec, every),
            "wo": stack(a + "o_proj.weight", mat, every),
        },
        "input_norm": stack(p + "input_layernorm.weight", vec, every),
        "post_attn_norm": stack(
            p + "post_attention_layernorm.weight", vec, every),
    }
    w_kv_b = stack(a + "kv_b_proj.weight", kv_b, every)
    layers["attn"] |= {"wk_b": np.ascontiguousarray(w_kv_b[:, :, :dn]),
                       "wv_b": np.ascontiguousarray(w_kv_b[:, :, dn:])}
    if len(dense):
        layers["mlp"] = {
            key: stack(p + f"mlp.{key}_proj.weight", mat, dense)
            for key in ("gate", "up", "down")}
    if len(sparse):
        ex = "model.layers.{}.mlp.experts.{}."

        def experts(key):
            return np.stack([
                np.stack([mat((ex + f"{key}_proj.weight").format(i, j))
                          for j in range(cfg.num_experts)])
                for i in sparse])

        layers["moe"] = {
            "router": stack(p + "mlp.gate.weight", mat, sparse),
            # the selection bias stays float32, as the router's scores
            "bias": np.stack([
                np.asarray(vec(
                    p.format(i) + "mlp.gate.e_score_correction_bias"),
                    np.float32) for i in sparse]),
            **{key: experts(key) for key in ("gate", "up", "down")},
        }
        if cfg.n_shared_experts:
            layers["moe"]["shared"] = {
                key: stack(p + f"mlp.shared_experts.{key}_proj.weight", mat,
                           sparse)
                for key in ("gate", "up", "down")}
    params = {"embed": vec("model.embed_tokens.weight"), "layers": layers,
              "final_norm": vec("model.norm.weight")}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = mat("lm_head.weight")
    return params


def _kv_b_rows(attn: dict, i: int):
    """Layer i's W_kv_b as the checkpoint holds it, (heads * (nope + v),
    rank), from the tree's two leaves (models/llama.py:_split_kv_b)."""
    w = np.concatenate(
        [np.asarray(attn["wk_b"][i]), np.asarray(attn["wv_b"][i])], axis=1)
    return w.reshape(-1, w.shape[-1])


class DeepseekCheckpointView:
    """The served tree read back under the checkpoint's tensor names and
    orientation ((out, in) matrices, interleaved rope columns): the inverse
    of `_deepseek_v3_tree`, one tensor at a time and on demand, so that a
    comparison with the plain reference (testing/reference_mla.py) never
    holds a second copy of the weights. Works on numpy or device arrays."""

    def __init__(self, cfg: ModelConfig, params: dict):
        self.cfg, self.params = cfg, params
        layers = params["layers"]
        self._k_dense = (
            layers["mlp"]["gate"].shape[0] if "mlp" in layers else 0)

    def __getitem__(self, name: str):
        cfg, layers = self.cfg, self.params["layers"]
        top = {"model.embed_tokens.weight": lambda: self.params["embed"],
               "model.norm.weight": lambda: self.params["final_norm"],
               "lm_head.weight": lambda: self.params["lm_head"].T}
        if name in top:
            return top[name]()
        parts = name.split(".")  # model.layers.<i>.<...>
        i, rest = int(parts[2]), ".".join(parts[3:])
        k_dense = self._k_dense
        nh, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        attn = layers["attn"]
        if rest == "input_layernorm.weight":
            return layers["input_norm"][i]
        if rest == "post_attention_layernorm.weight":
            return layers["post_attn_norm"][i]
        if rest == "self_attn.q_proj.weight":
            w = np.array(attn["wq"][i])  # (heads, nope ‖ rope, h)
            w[:, dn:] = np.swapaxes(_rope_columns(
                cfg, np.swapaxes(w[:, dn:], -1, -2), to_checkpoint=True),
                -1, -2)
            return w.reshape(-1, w.shape[-1])
        if rest == "self_attn.kv_a_proj_with_mqa.weight":
            w = np.array(attn["wkv_a"][i])
            w[:, cfg.kv_lora_rank:] = _rope_columns(
                cfg, w[:, cfg.kv_lora_rank:], to_checkpoint=True)
            return w.T
        if rest == "self_attn.kv_a_layernorm.weight":
            return attn["kv_a_norm"][i]
        if rest == "self_attn.kv_b_proj.weight":
            return _kv_b_rows(attn, i)
        if rest == "self_attn.o_proj.weight":
            return attn["wo"][i].T
        if i < k_dense:
            return layers["mlp"][parts[4].split("_")[0]][i].T
        moe, j = layers["moe"], i - k_dense
        if rest == "mlp.gate.weight":
            return moe["router"][j].T
        if rest == "mlp.gate.e_score_correction_bias":
            return moe["bias"][j]
        if parts[4] == "shared_experts":
            return moe["shared"][parts[5].split("_")[0]][j].T
        if parts[4] == "experts":
            return moe[parts[6].split("_")[0]][j, int(parts[5])].T
        raise KeyError(name)

    def names(self) -> list[str]:
        """Every tensor name of the checkpoint this tree stands for."""
        cfg, layers, k_dense = self.cfg, self.params["layers"], self._k_dense
        out = ["model.embed_tokens.weight", "model.norm.weight"]
        if "lm_head" in self.params:
            out.append("lm_head.weight")
        for i in range(cfg.num_layers):
            p = f"model.layers.{i}."
            out += [p + "input_layernorm.weight",
                    p + "post_attention_layernorm.weight"]
            out += [p + f"self_attn.{n}.weight" for n in (
                "q_proj", "kv_a_proj_with_mqa", "kv_a_layernorm", "kv_b_proj",
                "o_proj")]
            projs = [f"{n}_proj.weight" for n in ("gate", "up", "down")]
            if i < k_dense:
                out += [p + "mlp." + n for n in projs]
                continue
            out += [p + "mlp.gate.weight",
                    p + "mlp.gate.e_score_correction_bias"]
            if "shared" in layers["moe"]:
                out += [p + "mlp.shared_experts." + n for n in projs]
            out += [p + f"mlp.experts.{e}." + n
                    for e in range(cfg.num_experts) for n in projs]
        return out


class LagunaCheckpointView:
    """The served tree of a laguna model (models/llama.py:
    _init_params_by_kind) read back under `transformers`-style tensor names
    and orientation ((out, in) matrices), one tensor at a time and on
    demand: what the plain reference (testing/reference_laguna.py) reads.
    Experts are named by their GLOBAL index: the tree holds
    [first_expert_held, first_expert_held + held_experts). No checkpoint of
    this family has been read here (load_checkpoint_params refuses one:
    its tensor names are not known offline), so the names are the
    reference's, not a published file's."""

    def __init__(self, cfg: ModelConfig, params: dict):
        self.cfg, self.params = cfg, params

    def __getitem__(self, name: str):
        cfg, layers = self.cfg, self.params["layers"]
        top = {"model.embed_tokens.weight": lambda: self.params["embed"],
               "model.norm.weight": lambda: self.params["final_norm"],
               "lm_head.weight": lambda: self.params["lm_head"].T}
        if name in top:
            return top[name]()
        parts = name.split(".")  # model.layers.<i>.<...>
        i, rest = int(parts[2]), ".".join(parts[3:])
        spec = cfg.layer_specs[i]
        ai, mi = cfg.layer_stack_index[i]
        if rest == "input_layernorm.weight":
            return layers["input_norm"][i]
        if rest == "post_attention_layernorm.weight":
            return layers["post_attn_norm"][i]
        if parts[3] == "self_attn":
            if cfg.attn_stacks_by_kind:
                attn, j = layers[
                    "attn_window" if spec.window else "attn"], ai
            else:
                attn, j = layers["attn"], i
            w = attn["w" + parts[4][0]][j]  # q_proj -> wq, g_proj -> wg
            # (q, k, v lie (heads, head_dim, in): (out, in) already)
            return w.reshape(-1, w.shape[-1]) if w.ndim == 3 else w.T
        both = "mlp" in layers and "moe" in layers
        j = mi if both else i
        if not spec.experts:
            return layers["mlp"][parts[4].split("_")[0]][j].T
        moe = layers["moe"]
        if rest == "mlp.gate.weight":
            return moe["router"][j].T
        if parts[4] == "shared_expert":
            return moe["shared"][parts[5].split("_")[0]][j].T
        if parts[4] == "experts":
            return moe[parts[6].split("_")[0]][
                j, int(parts[5]) - cfg.first_expert_held].T
        raise KeyError(name)


class SolarOpen2CheckpointView(LagunaCheckpointView):
    """The served tree of a solar_open2 model read back under the plain
    reference's tensor names (testing/reference_solar_open2.py): the GQA
    layers' `self_attn.*` and the experts as LagunaCheckpointView names
    them, the KDA layers' `linear_attn.*` leaves (the convolution's taps a
    projection, (channels, taps)), and the router's selection bias. As for
    laguna, no checkpoint of this family has been read here: the names are
    the reference's."""

    _LINEAR = {"q_proj": "wq", "k_proj": "wk", "v_proj": "wv",
               "o_proj": "wo", "f_a_proj": "wf_a", "f_b_proj": "wf_b",
               "g_a_proj": "wg_a", "g_b_proj": "wg_b", "b_proj": "wb"}

    def __getitem__(self, name: str):
        cfg, layers = self.cfg, self.params["layers"]
        parts = name.split(".")
        if len(parts) > 3 and parts[3] == "linear_attn":
            stack = layers["attn_linear"]
            j, leaf = cfg.layer_stack_index[int(parts[2])][0], parts[4]
            if leaf in self._LINEAR:
                return stack[self._LINEAR[leaf]][j].T
            if leaf.endswith("_conv1d"):
                width = cfg.linear_num_heads * cfg.linear_head_dim
                at = "qkv".index(leaf[0]) * width
                return stack["conv"][j][:, at:at + width].T
            return stack[leaf][j]  # A_log, dt_bias, o_norm
        if name.endswith("mlp.gate.e_score_correction_bias"):
            return layers["moe"]["bias"][int(parts[2])]
        return super().__getitem__(name)


class LingCheckpointView(SolarOpen2CheckpointView):
    """The served tree of a ling_flash model read back under the plain
    reference's tensor names (testing/reference_ling.py): the KDA layers'
    `linear_attn.*` leaves with their FULL-RANK gate projections (`f_proj`,
    `g_proj`), the latent layers' `self_attn.*` (the "attn_latent" stack:
    rope columns in rotate-half order, as the reference ropes them), a
    leading dense layer's `mlp.*`, the experts by GLOBAL index and the
    router's selection bias at the layer's index among the expert layers.
    No checkpoint of this family has been read here: the names are the
    reference's."""

    _LINEAR = {**SolarOpen2CheckpointView._LINEAR,
               "f_proj": "wf", "g_proj": "wg"}
    _LATENT = {"kv_a_proj_with_mqa": "wkv_a", "o_proj": "wo", "g_proj": "wg"}

    def __getitem__(self, name: str):
        cfg, layers = self.cfg, self.params["layers"]
        parts = name.split(".")
        if len(parts) > 3 and parts[3] == "self_attn":
            stack = layers["attn_latent"]
            j = cfg.layer_stack_index[int(parts[2])][0]
            if parts[4] == "kv_a_layernorm":
                return stack["kv_a_norm"][j]
            if parts[4] == "kv_b_proj":
                return _kv_b_rows(stack, j)
            if parts[4] == "q_proj":  # (heads, nope ‖ rope, h): its rows
                return stack["wq"][j].reshape(-1, stack["wq"].shape[-1])
            return stack[self._LATENT[parts[4]]][j].T
        if name.endswith("mlp.gate.e_score_correction_bias"):
            return layers["moe"]["bias"][
                cfg.layer_stack_index[int(parts[2])][1]]
        return super().__getitem__(name)
