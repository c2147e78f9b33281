"""Sharded causal-LM training step.

The full SPMD recipe: params laid out tensor-parallel (parallel.sharding),
batch sharded data-parallel (and optionally sequence-parallel), one jitted
step — XLA inserts the tp collectives inside the model and the dp gradient
all-reduce at the boundary.  Used for fine-tuning and as the multi-chip
dry-run workload (__graft_entry__.dryrun_multichip).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from lmrs_tpu.config import ModelConfig
from lmrs_tpu.models.transformer import forward
from lmrs_tpu.parallel.sharding import batch_spec, param_shardings


def causal_lm_loss(params: Any, cfg: ModelConfig, tokens: jnp.ndarray,
                   loss_mask: jnp.ndarray | None = None,
                   attn_fn=None, remat: bool = False) -> jnp.ndarray:
    """Next-token cross-entropy in f32.  tokens [B, S]; predicts tokens[:,1:]."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    logits, _, aux = forward(params, cfg, tokens, positions, attn_fn=attn_fn,
                             return_aux=True, remat=remat)  # [B,S,V] f32
    logits = logits[:, :-1]
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if loss_mask is not None:
        m = loss_mask[:, 1:].astype(jnp.float32)
        loss = (nll * m).sum() / jnp.maximum(m.sum(), 1.0)
    else:
        loss = nll.mean()
    if cfg.n_experts and cfg.router_aux_coef:
        loss = loss + cfg.router_aux_coef * aux
    return loss


def make_train_step(
    cfg: ModelConfig,
    optimizer: optax.GradientTransformation,
    mesh: Mesh | None = None,
    seq_sharded: bool = False,
    remat: bool = False,
    masked: bool = False,
):
    """Build a jitted (params, opt_state, tokens) -> (params, opt_state, loss)
    step.  With a mesh: params tensor-parallel, batch over dp; when
    seq_sharded the sequence axis shards over sp and attention runs as a
    ring (parallel.ring_attention) — K/V blocks rotate over ICI instead of
    XLA all-gathering the whole sequence onto every sp shard.  ``remat``
    rematerializes each decoder layer in backward (jax.checkpoint), cutting
    activation HBM to one [B,S,D] residual per layer for long sequences."""

    attn_fn = None
    if mesh is not None and seq_sharded:
        from lmrs_tpu.parallel.ring_attention import ring_attention_sharded

        def attn_fn(q, k, v, positions):
            return ring_attention_sharded(q, k, v, positions, mesh)

    def step(params, opt_state, tokens, loss_mask=None):
        loss, grads = jax.value_and_grad(causal_lm_loss)(
            params, cfg, tokens, loss_mask=loss_mask, attn_fn=attn_fn,
            remat=remat)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    if mesh is None:
        return jax.jit(step)

    pspecs = param_shardings(mesh, cfg.tie_embeddings, moe=cfg.n_experts > 0,
                             sandwich_norm=cfg.sandwich_norm)
    batch_sh = NamedSharding(mesh, batch_spec(seq_sharded))
    in_sh = [pspecs, None, batch_sh] + ([batch_sh] if masked else [])
    # opt_state sharding left unconstrained: XLA propagates the param layout
    # into the optimizer tree (adam mu/nu mirror the params).
    return jax.jit(
        step,
        in_shardings=tuple(in_sh),
        out_shardings=(pspecs, None, None),
        donate_argnums=(0, 1),
    )
