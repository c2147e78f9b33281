"""Pipeline parallelism: layer stages over the ``pp`` mesh axis.

The reference has no model, so no pipeline anything (SURVEY.md §2.2 "PP: No
— optional for the 70B tier").  This is the TPU-native implementation:
GPipe-style fill/drain microbatching expressed as one SPMD program —

* the stacked layer params [L, ...] are sharded on the leading axis over
  ``pp`` (L/pp contiguous layers per stage — spec: sharding.param_specs
  with pp=True);
* inside ``shard_map``, a ``lax.scan`` runs M + pp - 1 ticks; each tick
  every stage applies its layers to one microbatch and hands the activation
  to the next stage via ``lax.ppermute`` over ICI (one hop — neighbors on
  the mesh ring);
* stage 0 feeds fresh microbatches into the ring, the last stage computes
  head + loss for the microbatch that has finished draining; the scalar is
  ``psum``-ed so every shard returns the same loss (SPMD requires all
  stages to run the same program — non-final stages' head FLOPs are masked,
  the standard cost of homogeneous-program pipelining);
* the pipeline bubble is the usual (pp-1)/(M+pp-1) — raise ``n_micro`` to
  amortize.

v1 scope: composes with ``dp`` (microbatches shard the batch axis) but not
with tp/sp inside the pipelined program — embedding/head are replicated
across stages.  autodiff flows through ppermute, so one jax.value_and_grad
over this function is the whole pp backward pass.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from lmrs_tpu.config import ModelConfig
from lmrs_tpu.models.transformer import decoder_layer, embed_tokens, lm_head
from lmrs_tpu.ops.rope import rope_table
from lmrs_tpu.utils.jax_compat import shard_map


def _stage_scan(layers_local, cfg: ModelConfig, x, positions, sin, cos):
    """Apply this stage's L/pp layers (scan over the local leading axis).
    Returns (x, aux_sum) — the summed MoE load-balance loss of the local
    layers (0 for dense models)."""
    def body(x, lp):
        x, aux = decoder_layer(lp, cfg, x, positions, sin, cos)
        return x, aux

    x, aux = lax.scan(body, x, layers_local)
    return x, aux.sum()


def pipeline_causal_lm_loss(
    params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, S] int32
    mesh: Mesh,
    n_micro: int = 4,
    pp_axis: str = "pp",
    dp_axis: str = "dp",
) -> jnp.ndarray:
    """Next-token cross-entropy computed through the pp pipeline.

    ``tokens`` batch must divide by n_micro (× dp shards).  Returns the
    token-mean loss as a replicated scalar.
    """
    pp = mesh.shape[pp_axis]
    if cfg.n_loops > 1:
        raise ValueError(
            f"pipeline stages over a looped stack (n_loops={cfg.n_loops}) "
            "are not built: a stage holds L/pp layers and every pass would "
            "have to travel the ring of stages again")
    if cfg.n_layers % pp != 0:
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by pp={pp}")

    # layers [L,...] -> [pp, L/pp, ...] so the stage axis is shardable
    def split_stage(x):
        return x.reshape((pp, cfg.n_layers // pp) + x.shape[1:])

    staged = {
        "embed": params["embed"],
        "layers": jax.tree.map(split_stage, params["layers"]),
        "final_norm": params["final_norm"],
    }
    if "lm_head" in params:
        staged["lm_head"] = params["lm_head"]

    layer_specs = jax.tree.map(lambda _: P(pp_axis), staged["layers"])
    param_specs = {
        "embed": jax.tree.map(lambda _: P(), staged["embed"]),
        "layers": layer_specs,
        "final_norm": jax.tree.map(lambda _: P(), staged["final_norm"]),
    }
    if "lm_head" in staged:
        param_specs["lm_head"] = jax.tree.map(lambda _: P(), staged["lm_head"])

    def body(sp, tok):  # runs per (dp, pp) shard
        stage = lax.axis_index(pp_axis)
        layers_local = jax.tree.map(lambda x: x[0], sp["layers"])  # [L/pp,...]
        b, s = tok.shape
        m = n_micro
        mb = b // m
        micro = tok.reshape(m, mb, s)
        positions = jnp.broadcast_to(jnp.arange(s)[None], (mb, s))
        hd = cfg.hd
        sin, cos = rope_table(s, hd, cfg.rope_theta)

        x_in = jax.vmap(lambda t: embed_tokens(sp, cfg, t))(micro)  # [M,mb,S,D]

        def tick(carry, t):
            y_prev, loss_sum, tok_count, aux_sum, aux_count = carry
            # previous tick's output moves one stage down the ring
            recv = lax.ppermute(
                y_prev, pp_axis,
                [(i, (i + 1) % pp) for i in range(pp)])
            feed = lax.dynamic_index_in_dim(
                x_in, jnp.clip(t, 0, m - 1), keepdims=False)
            x = jnp.where(stage == 0, feed, recv)
            y, stage_aux = _stage_scan(layers_local, cfg, x, positions, sin, cos)

            # stage s processes microbatch t-s at tick t; aux only counts
            # when that's a real microbatch (not warmup/drain garbage)
            mb_idx = t - stage
            aux_valid = jnp.logical_and(mb_idx >= 0, mb_idx < m)
            aux_sum = aux_sum + jnp.where(aux_valid, stage_aux, 0.0)
            aux_count = aux_count + jnp.where(
                aux_valid, layers_local["ln_attn"]["scale"].shape[0], 0)

            # the microbatch finishing at tick t on the last stage is t-(pp-1)
            out_idx = jnp.clip(t - (pp - 1), 0, m - 1)
            tgt = lax.dynamic_index_in_dim(micro, out_idx, keepdims=False)
            logits = lm_head(sp, cfg, y)[:, :-1]  # [mb, S-1, V]
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, tgt[:, 1:, None], axis=-1)[..., 0]
            valid = jnp.logical_and(stage == pp - 1, t >= pp - 1)
            loss_sum = loss_sum + jnp.where(valid, nll.sum(), 0.0)
            tok_count = tok_count + jnp.where(valid, nll.size, 0)
            return (y, loss_sum, tok_count, aux_sum, aux_count), None

        init = (jnp.zeros((mb, s, cfg.dim), x_in.dtype),
                jnp.float32(0.0), jnp.int32(0), jnp.float32(0.0), jnp.int32(0))
        (_, loss_sum, tok_count, aux_sum, aux_count), _ = lax.scan(
            tick, init, jnp.arange(m + pp - 1))

        loss_sum = lax.psum(lax.psum(loss_sum, pp_axis), dp_axis)
        tok_count = lax.psum(lax.psum(tok_count, pp_axis), dp_axis)
        loss = loss_sum / jnp.maximum(tok_count, 1)
        if cfg.n_experts and cfg.router_aux_coef:
            aux_sum = lax.psum(lax.psum(aux_sum, pp_axis), dp_axis)
            aux_count = lax.psum(lax.psum(aux_count, pp_axis), dp_axis)
            loss = loss + cfg.router_aux_coef * aux_sum / jnp.maximum(aux_count, 1)
        return loss

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, P(dp_axis)),
        out_specs=P(),
        check_vma=False,
    )
    return fn(staged, tokens)


def make_pp_train_step(cfg: ModelConfig, optimizer, mesh: Mesh,
                       n_micro: int = 4):
    """Jitted (params, opt_state, tokens) -> (params, opt_state, loss) with
    the loss computed through the pp pipeline.  Params stay in their normal
    stacked layout; the stage split happens inside the loss."""
    import optax

    def loss_fn(params, tokens):
        return pipeline_causal_lm_loss(params, cfg, tokens, mesh, n_micro)

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1))
