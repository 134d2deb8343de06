"""HF-interop: load/save Llama weights as HF-named safetensors.

Parity target: the reference loads ``LlamaForCausalLM.from_pretrained`` from a
local path or hub id (open_diloco/train_fsdp.py:171-174) and ships a committed
2M-parameter test model (tests/models/llama-2m-fresh). We read/write the same
``model.safetensors`` naming so checkpoints interchange with HF tooling.

Layout differences handled here:
- HF linear weights are [out_features, in_features]; ours are [in, out]
  (we compute ``x @ W``) -> transpose on both directions.
- Our per-layer weights are stacked on a leading layer axis for
  ``lax.scan``; HF keys are per-layer -> stack/unstack.
"""

from __future__ import annotations

import json
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from opendiloco_tpu.models.llama import LlamaConfig, shapes

_PKG_CONFIG_DIR = os.path.join(os.path.dirname(__file__), "configs")

# (our layer-tree key, HF module name, transpose?)
_LAYER_KEYS = [
    ("input_norm", "input_layernorm", False),
    ("post_attn_norm", "post_attention_layernorm", False),
    ("q_proj", "self_attn.q_proj", True),
    ("k_proj", "self_attn.k_proj", True),
    ("v_proj", "self_attn.v_proj", True),
    ("o_proj", "self_attn.o_proj", True),
    ("gate_proj", "mlp.gate_proj", True),
    ("up_proj", "mlp.up_proj", True),
    ("down_proj", "mlp.down_proj", True),
]


def resolve_model_path(path_model: str) -> str:
    """Map a name like 'configs/config_150m.json', a packaged size name
    ('150m'), or a directory path to a concrete config path/dir."""
    if os.path.isdir(path_model) or os.path.isfile(path_model):
        return path_model
    short = path_model.removeprefix("configs/").removesuffix(".json")
    short = short.removeprefix("config_")
    candidate = os.path.join(_PKG_CONFIG_DIR, f"config_{short}.json")
    if os.path.isfile(candidate):
        return candidate
    raise FileNotFoundError(f"cannot resolve model path {path_model!r}")


def load_config(path_model: str) -> LlamaConfig:
    path = resolve_model_path(path_model)
    if os.path.isdir(path):
        path = os.path.join(path, "config.json")
    return LlamaConfig.from_json(path)


def _reject_moe(cfg: LlamaConfig, op: str) -> None:
    if cfg.linear or cfg.blocks:
        raise ValueError(
            f"cannot {op} this model as HF llama safetensors: the llama "
            "layout has no lightning linear-attention layers (their five "
            "projections, two head norms and output norm, and the decays their "
            "code computes), no gates and no norms per head, and HF's "
            "minicpm_sala layout is not mapped here. Such models train, serve "
            "and checkpoint through the framework checkpointer "
            "(opendiloco_tpu.ckpt); only this import/export is refused"
        )
    if cfg.kda:
        raise ValueError(
            f"cannot {op} this model as HF llama safetensors: the llama layout has no "
            "kda linear-attention layers (their convolution, decay, beta and gate leaves), and "
            "HF's solar_open2 layout is not mapped here. Such models train, serve and "
            "checkpoint through the framework checkpointer (opendiloco_tpu.ckpt); only this "
            "import/export is refused"
        )
    if cfg.eva or cfg.num_pred_heads > 1 or cfg.norm_add_unit_offset or cfg.fp32_skip_add:
        raise ValueError(
            f"cannot {op} this model as HF llama safetensors: the llama "
            "layout has no EVA attention (adaptive_phi, adaptive_mu_k), no head of "
            "several vocabularies, no norm under 1 + w and no float32 residual "
            "stream, and HF's evabyte layout is not mapped here. Such models "
            "train, serve and checkpoint through the framework checkpointer "
            "(opendiloco_tpu.ckpt); only this import/export is refused"
        )
    if cfg.sparse or cfg.qk_norm_per_head or cfg.mrope_section is not None:
        raise ValueError(
            f"cannot {op} this model as HF llama safetensors: the llama "
            "layout has no indexer (its queries, its one key under a LayerNorm, "
            "its head weights), no QK-norm per head and no rotation in sections, "
            "and HF's KeyeVL2 layout is not mapped here. Such models train, serve "
            "and checkpoint through the framework checkpointer "
            "(opendiloco_tpu.ckpt); only this import/export is refused"
        )
    if cfg.cca or cfg.router_hidden_size or cfg.residual_scaling:
        raise ValueError(
            f"cannot {op} this model as HF llama safetensors: the llama "
            "layout has no CCA (the two convolutions over q and k, the second "
            "value projection, k's temperature), no router MLP fed by the layer "
            "before and no learned residual scaling, and HF's zaya layout is not "
            "mapped here. Such models train, serve and checkpoint through the "
            "framework checkpointer (opendiloco_tpu.ckpt); only this "
            "import/export is refused"
        )
    if cfg.latent or cfg.leading_dense:
        raise ValueError(
            f"cannot {op} this model as HF llama safetensors: the llama "
            "layout has no latent attention (q_a_proj, q_a_layernorm, q_b_proj, "
            "kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj), no selection bias "
            "(mlp.gate.e_score_correction_bias), no shared experts and no dense "
            "layer before the expert layers, and HF's glm4_moe_lite layout is "
            "not mapped here (its rotated values are interleaved, these are in "
            "halves). Such models train, serve and checkpoint through the "
            "framework checkpointer (opendiloco_tpu.ckpt); only this "
            "import/export is refused"
        )
    if cfg.hybrid or cfg.shared_intermediate_size:
        raise ValueError(
            f"cannot {op} this model as HF llama safetensors: the llama "
            "layout has no Mamba-2 mixer (in_proj, conv1d, dt_bias, A_log, D, "
            "its gated norm, out_proj), no shared MLP beside the experts and "
            "no multipliers, and HF's granitemoehybrid layout (model.layers.N."
            "mamba.*, .shared_mlp.*, .block_sparse_moe.*) is not mapped here. "
            "Hybrid models train, serve and checkpoint through the framework "
            "checkpointer (opendiloco_tpu.ckpt); only this import/export is "
            "refused"
        )
    if cfg.num_experts or cfg.qk_norm:
        raise ValueError(
            f"cannot {op} this model as HF llama safetensors: the llama "
            "layout has no router, no per-expert gate/up/down projections "
            "and no q/k norms, and HF's OLMoE layout (model.layers.N.mlp."
            "experts.E.*, .mlp.gate, .self_attn.{q,k}_norm) is not mapped "
            "here. Routed-expert and QK-norm models train, serve and "
            "checkpoint through the framework checkpointer "
            "(opendiloco_tpu.ckpt); only this import/export is refused"
        )


def load_params(model_dir: str, cfg: Optional[LlamaConfig] = None) -> dict:
    """Read an HF llama ``model.safetensors`` into our stacked pytree."""
    from safetensors import safe_open

    if cfg is None:
        cfg = load_config(model_dir)
    _reject_moe(cfg, "load")
    st_path = os.path.join(model_dir, "model.safetensors")
    tensors: dict[str, np.ndarray] = {}
    with safe_open(st_path, framework="numpy") as f:
        for key in f.keys():
            tensors[key] = f.get_tensor(key)

    def get(name: str, transpose: bool) -> np.ndarray:
        t = tensors[name].astype(np.float32)
        return t.T if transpose else t

    L = cfg.num_hidden_layers
    layers = {}
    for ours, hf, tr in _LAYER_KEYS:
        layers[ours] = jnp.asarray(
            np.stack(
                [get(f"model.layers.{i}.{hf}.weight", tr) for i in range(L)], axis=0
            )
        )
    params = {
        "embed_tokens": jnp.asarray(get("model.embed_tokens.weight", False)),
        "layers": layers,
        "final_norm": jnp.asarray(get("model.norm.weight", False)),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(get("lm_head.weight", True))
    chex_shapes = shapes(cfg)
    got = jax.tree.map(lambda x: x.shape, params)
    want = jax.tree.map(lambda s: s.shape, chex_shapes)
    if got != want:
        raise ValueError(f"weight shapes mismatch config: {got} vs {want}")
    return params


def save_params(params: dict, cfg: LlamaConfig, model_dir: str) -> None:
    """Write our pytree as an HF-named ``model.safetensors`` + config.json."""
    from safetensors.numpy import save_file

    _reject_moe(cfg, "save")
    os.makedirs(model_dir, exist_ok=True)
    out: dict[str, np.ndarray] = {}
    np_params = jax.tree.map(lambda x: np.asarray(x, dtype=np.float32), params)
    out["model.embed_tokens.weight"] = np.ascontiguousarray(np_params["embed_tokens"])
    out["model.norm.weight"] = np.ascontiguousarray(np_params["final_norm"])
    if not cfg.tie_word_embeddings:
        out["lm_head.weight"] = np.ascontiguousarray(np_params["lm_head"].T)
    for ours, hf, tr in _LAYER_KEYS:
        stacked = np_params["layers"][ours]
        for i in range(cfg.num_hidden_layers):
            t = stacked[i]
            out[f"model.layers.{i}.{hf}.weight"] = np.ascontiguousarray(
                t.T if tr else t
            )
    save_file(out, os.path.join(model_dir, "model.safetensors"))
    with open(os.path.join(model_dir, "config.json"), "w") as f:
        json.dump(cfg.to_dict(), f, indent=2)


def get_model(path_model: str) -> tuple[LlamaConfig, Optional[dict]]:
    """Reference-shaped entry (train_fsdp.py:171-174): resolve a model source.

    Returns (config, params). params is None when the source is a bare size
    config (caller should ``init_params``); a directory with safetensors loads
    real weights.
    """
    path = resolve_model_path(path_model)
    if os.path.isdir(path):
        cfg = load_config(path)
        return cfg, load_params(path, cfg)
    cfg = LlamaConfig.from_json(path)
    return cfg, None
