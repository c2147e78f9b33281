"""Model weight checkpointing and conversion.

The reference has no model state at all — its weights live behind OpenAI's
API (SURVEY.md §5.4 "add model-weight checkpoint loading (Orbax) as a new
subsystem").  This module provides:

* Orbax save/restore of the native param pytree (sharding-aware: restore
  places shards directly onto a mesh, so a 70B checkpoint never materializes
  unsharded on one host);
* conversion from HuggingFace Llama/Gemma checkpoints (local safetensors
  files only — this environment has no egress) into the stacked-layer layout.
"""

from __future__ import annotations

import logging
from pathlib import Path
from typing import Any

import jax
import numpy as np

from lmrs_tpu.config import ModelConfig

logger = logging.getLogger("lmrs.loader")


# ------------------------------------------------------------------- orbax


def save_checkpoint(path: str, params: Any) -> None:
    """Write the param pytree with Orbax (atomic, async-flushed)."""
    import orbax.checkpoint as ocp

    ckpt = ocp.StandardCheckpointer()
    ckpt.save(Path(path).absolute(), params, force=True)
    ckpt.wait_until_finished()
    logger.info("saved checkpoint to %s", path)


def load_checkpoint(path: str, model_cfg: ModelConfig, mesh=None) -> Any:
    """Restore a param pytree; with a mesh, restore directly sharded."""
    import orbax.checkpoint as ocp

    from lmrs_tpu.models.transformer import init_params

    target = jax.eval_shape(
        lambda: init_params(model_cfg, jax.random.PRNGKey(0))
    )
    if mesh is not None:
        from lmrs_tpu.parallel.sharding import param_shardings

        shardings = param_shardings(mesh, model_cfg.tie_embeddings,
                                    moe=model_cfg.n_experts > 0,
                                    sandwich_norm=model_cfg.sandwich_norm)
        target = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            target, shardings,
        )
    ckpt = ocp.StandardCheckpointer()
    params = ckpt.restore(Path(path).absolute(), target)
    logger.info("restored checkpoint from %s", path)
    return params


# ------------------------------------------------- HF safetensors conversion


def convert_hf_llama(src_dir: str, cfg: ModelConfig, *, norm_offset: float = 1.0) -> Any:
    """Convert a local HF Llama-style checkpoint into the stacked layout.

    Expects ``model*.safetensors`` files in ``src_dir``.  HF per-layer names
    map to the stacked-axis pytree:

        model.layers.{i}.self_attn.{q,k,v,o}_proj.weight -> attn.w{q,k,v,o}[i]
        model.layers.{i}.mlp.{gate,up,down}_proj.weight  -> mlp.w_{...}[i]
        model.layers.{i}.(input|post_attention)_layernorm.weight -> ln_*[i]
        model.embed_tokens.weight / lm_head.weight / model.norm.weight

    MoE configs (cfg.n_experts > 0, e.g. mixtral-8x7b) read Mixtral's layout
    instead of the dense mlp keys:

        model.layers.{i}.block_sparse_moe.gate.weight          -> moe.router[i]
        model.layers.{i}.block_sparse_moe.experts.{j}.w1.weight -> moe.w_gate[i,j]
        model.layers.{i}.block_sparse_moe.experts.{j}.w3.weight -> moe.w_up[i,j]
        model.layers.{i}.block_sparse_moe.experts.{j}.w2.weight -> moe.w_down[i,j]

    HF stores projections as [out, in]; we store [in, out] (+ head split).
    ``norm_offset``: our RMSNorm multiplies by ``1 + scale``; HF Llama
    multiplies by ``w`` (offset 1.0 -> scale = w - 1), HF Gemma already by
    ``1 + w`` (offset 0.0 -> scale = w; see ``convert_hf_gemma``).
    """
    import json as _json

    if cfg.sandwich_norm:
        raise NotImplementedError(
            "sandwich_norm: no loader for the four-norm tree (a published "
            "checkpoint's second norm of each sublayer, ln_attn_out / "
            "ln_mlp_out here, is not mapped); weights are random-init only")
    try:
        from safetensors import safe_open
    except ImportError as e:  # pragma: no cover - gated dependency
        raise RuntimeError(
            "safetensors not available; convert checkpoints offline"
        ) from e

    src = Path(src_dir)
    files = sorted(src.glob("*.safetensors"))
    if not files:
        raise FileNotFoundError(f"no .safetensors under {src_dir}")

    tensors: dict[str, np.ndarray] = {}
    for f in files:
        with safe_open(str(f), framework="np") as fh:
            for name in fh.keys():
                tensors[name] = fh.get_tensor(name)

    hd = cfg.hd
    L = cfg.n_layers
    off = np.float32(norm_offset)
    dt = np.dtype(np.float32) if cfg.dtype == "float32" else np.dtype("bfloat16")

    def get(name):
        return tensors[name]

    def stack(fmt, transform):
        return np.stack([transform(get(fmt.format(i=i))) for i in range(L)]).astype(dt)

    if cfg.n_experts:
        E = cfg.n_experts

        def stack_experts(fmt):
            return np.stack([
                np.stack([get(fmt.format(i=i, j=j)).T for j in range(E)])
                for i in range(L)
            ]).astype(dt)  # [L, E, in, out]

        ffn = {
            "moe": {
                "router": stack("model.layers.{i}.block_sparse_moe.gate.weight",
                                lambda w: w.T),  # [D, E]
                "w_gate": stack_experts(
                    "model.layers.{i}.block_sparse_moe.experts.{j}.w1.weight"),
                "w_up": stack_experts(
                    "model.layers.{i}.block_sparse_moe.experts.{j}.w3.weight"),
                "w_down": stack_experts(
                    "model.layers.{i}.block_sparse_moe.experts.{j}.w2.weight"),
            }
        }
    else:
        ffn = {
            "mlp": {
                "w_gate": stack("model.layers.{i}.mlp.gate_proj.weight", lambda w: w.T),
                "w_up": stack("model.layers.{i}.mlp.up_proj.weight", lambda w: w.T),
                "w_down": stack("model.layers.{i}.mlp.down_proj.weight", lambda w: w.T),
            }
        }
    params = {
        "embed": {"weight": get("model.embed_tokens.weight").astype(dt)},
        "layers": {
            "ln_attn": {"scale": stack(
                "model.layers.{i}.input_layernorm.weight", lambda w: w - off)},
            "ln_mlp": {"scale": stack(
                "model.layers.{i}.post_attention_layernorm.weight", lambda w: w - off)},
            "attn": {
                "wq": stack("model.layers.{i}.self_attn.q_proj.weight",
                            lambda w: w.T.reshape(cfg.dim, cfg.n_heads, hd)),
                "wk": stack("model.layers.{i}.self_attn.k_proj.weight",
                            lambda w: w.T.reshape(cfg.dim, cfg.n_kv_heads, hd)),
                "wv": stack("model.layers.{i}.self_attn.v_proj.weight",
                            lambda w: w.T.reshape(cfg.dim, cfg.n_kv_heads, hd)),
                "wo": stack("model.layers.{i}.self_attn.o_proj.weight",
                            lambda w: w.T.reshape(cfg.n_heads, hd, cfg.dim)),
            },
            **ffn,
        },
        "final_norm": {"scale": (get("model.norm.weight") - off).astype(dt)},
    }
    if not cfg.tie_embeddings:
        head = tensors.get("lm_head.weight", tensors["model.embed_tokens.weight"])
        params["lm_head"] = {"weight": head.T.astype(dt)}
    logger.info("converted HF checkpoint %s (%d tensors)", src_dir, len(tensors))
    return jax.tree.map(lambda x: jax.numpy.asarray(x), params)


def convert_hf_gemma(src_dir: str, cfg: ModelConfig) -> Any:
    """Convert a local HF Gemma checkpoint (same tensor names as Llama, but
    HF GemmaRMSNorm already multiplies by ``1 + w`` — our parameterization —
    so norm weights pass through unshifted; embeddings are always tied, and
    ``cfg`` should carry Gemma's explicit head_dim / gelu activation /
    embed_scale (see the gemma presets in config.py)."""
    if not cfg.tie_embeddings:
        raise ValueError("Gemma checkpoints tie lm_head to the embedding")
    return convert_hf_llama(src_dir, cfg, norm_offset=0.0)
