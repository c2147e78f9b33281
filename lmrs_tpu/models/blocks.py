"""What the model files whose layers are not one homogeneous scan share
(models/latent.py, models/windowed.py): leading layers with a dense FFN
(``params["dense_layers"]``), then routed layers (``params["layers"]``:
ops/moe.routed_experts plus a shared expert through the dense path), each
group its own ``lax.scan`` over stacked weights."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from lmrs_tpu.config import ModelConfig


def swiglu(mp, cfg: ModelConfig, h):
    from lmrs_tpu.models.transformer import gate_act

    dt = h.dtype
    gate = jnp.einsum("bsd,df->bsf", h, mp["w_gate"])
    up = jnp.einsum("bsd,df->bsf", h, mp["w_up"])
    return jnp.einsum("bsf,fd->bsd", gate_act(cfg, gate).astype(dt) * up,
                      mp["w_down"])


_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def split_experts(group):
    """A layer group for a scan: (what the scan slices a layer at a time,
    the stacked expert leaves it must NOT slice).  ``ffn`` takes them
    whole with the layer's index (ops/moe.routed_experts says why)."""
    if "moe" not in group:
        return group, None
    moe = group["moe"]
    scanned = {**group, "moe": {k: v for k, v in moe.items()
                                if k not in _EXPERT_LEAVES}}
    return scanned, {k: moe[k] for k in _EXPERT_LEAVES}


def ffn(lp, cfg: ModelConfig, h, token_valid=None, experts=None,
        layer=None):
    """(out, stats): the dense FFN of a leading layer (stats None), or the
    held experts' part of a routed layer plus the shared expert.
    ``experts``/``layer``: the group's stacked expert leaves and this
    layer's index in it (``split_experts``)."""
    if "moe" not in lp:
        return swiglu(lp["mlp"], cfg, h), None
    from lmrs_tpu.ops.moe import routed_experts

    mp = lp["moe"] if experts is None else {**lp["moe"], **experts}
    out, stats = routed_experts(mp, cfg, h, token_valid,
                                layer=None if experts is None else layer)
    if "shared" in lp:
        with jax.named_scope("moe.shared"):
            out = out + swiglu(lp["shared"], cfg, h)
    return out, stats


def layer_groups(params):
    """The layer groups in model order: (stacked params, layer count)."""
    out = []
    if "dense_layers" in params:
        g = params["dense_layers"]
        out.append((g, g["attn"]["wo"].shape[0]))
    g = params["layers"]
    out.append((g, g["attn"]["wo"].shape[0]))
    return out
