"""Parameter & activation sharding layouts.

The scaling-book recipe: pick a mesh, annotate shardings on params and
activations, let XLA insert the collectives (all-gather/reduce-scatter ride
ICI on the ``tp`` axis; ``dp`` replicates params and shards the batch).

Layer params are stacked along a leading ``n_layers`` axis (scanned in the
model), so every spec below leads with None for that axis.

Layout (Megatron-style, collective-minimal for decoders):
* attention QKV projections: shard the HEAD axis over tp  → column parallel
* attention output:          shard the input-head axis    → row parallel
  (XLA inserts one psum per attention block)
* MLP gate/up: column parallel; MLP down: row parallel    → one psum per MLP
* embedding/lm_head: vocab axis over tp (logits all-gathered once per step)
* KV cache: batch over dp, kv-heads over tp
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def param_specs(tie_embeddings: bool = True, moe: bool = False,
                sandwich_norm: bool = False) -> dict[str, Any]:
    """PartitionSpec pytree matching models.transformer.init_params layout
    (``sandwich_norm``: the two norm leaves on the sublayers' outputs,
    replicated like every norm)."""
    if moe:
        ffn = {
            "moe": {
                "router": P(None, None, None),     # [L, D, E] replicated (tiny)
                "w_gate": P(None, "ep", None, "tp"),  # [L, E, D, F] experts over ep
                "w_up": P(None, "ep", None, "tp"),
                "w_down": P(None, "ep", "tp", None),  # [L, E, F, D]
            }
        }
    else:
        ffn = {
            "mlp": {
                "w_gate": P(None, None, "tp"),  # [L, D, F] column
                "w_up": P(None, None, "tp"),
                "w_down": P(None, "tp", None),  # [L, F, D] row
            }
        }
    norms = ("ln_attn", "ln_mlp")
    if sandwich_norm:
        norms += ("ln_attn_out", "ln_mlp_out")
    specs = {
        "embed": {"weight": P("tp", None)},  # vocab sharded
        "layers": {
            **{n: {"scale": P(None, None)} for n in norms},
            "attn": {
                "wq": P(None, None, "tp", None),  # [L, D, H, hd] heads sharded
                "wk": P(None, None, "tp", None),  # [L, D, K, hd]
                "wv": P(None, None, "tp", None),
                "wo": P(None, "tp", None, None),  # [L, H, hd, D] row parallel
            },
            **ffn,
        },
        "final_norm": {"scale": P(None)},
    }
    if not tie_embeddings:
        specs["lm_head"] = {"weight": P(None, "tp")}  # [D, V] vocab sharded
    return specs


def specs_to_shardings(specs: Any, mesh: Mesh) -> Any:
    """PartitionSpec pytree → NamedSharding pytree on ``mesh``."""
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def param_shardings(mesh: Mesh, tie_embeddings: bool = True, moe: bool = False,
                    sandwich_norm: bool = False):
    """NamedSharding pytree for jit in_shardings / device_put."""
    return specs_to_shardings(
        param_specs(tie_embeddings, moe, sandwich_norm), mesh)


def shard_params(params: Any, mesh: Mesh, tie_embeddings: bool = True,
                 moe: bool = False, sandwich_norm: bool = False) -> Any:
    """Place a host-side param pytree onto the mesh with the TP layout.
    Handles int8-quantized trees (ops/quant.py): the q tensor takes the
    weight's spec, scales replicate."""
    from lmrs_tpu.ops.quant import match_quantized_specs

    specs = match_quantized_specs(
        param_specs(tie_embeddings, moe, sandwich_norm), params)
    return jax.tree.map(jax.device_put, params, specs_to_shardings(specs, mesh))


def batch_spec(seq_sharded: bool = False) -> P:
    """Activation sharding for [B, S, ...] tensors: batch over dp, optionally
    sequence over sp (context parallelism)."""
    return P("dp", "sp") if seq_sharded else P("dp")


def kv_cache_spec() -> P:
    """[L, B, S, K, hd]: batch over dp, kv heads over tp."""
    return P(None, "dp", None, "tp", None)
