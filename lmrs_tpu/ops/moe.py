"""Mixture-of-experts FFN: top-k routing with capacity-bounded dispatch.

TPU-first formulation (GShard/Switch style): routing is expressed as two
einsums against one-hot dispatch/combine tensors, so the whole layer is
MXU matmuls with static shapes — no scatter, no dynamic shapes, scannable
and shardable.  Expert weights carry a leading expert axis that shards over
the ``ep`` mesh axis (parallel.sharding); under GSPMD the dispatch einsum
lowers to an all-to-all over ``ep``.

The reference has no experts (dense API models only; SURVEY.md §2.2 "EP:
out of scope unless a MoE checkpoint is adopted; design mesh axes so EP can
be added") — this module plus the ``ep`` axis is that design carried out.

Capacity semantics: each expert processes at most C tokens per call
(C = capacity_factor * N * k / E); overflow tokens lose that expert's
contribution (their residual stream passes through unchanged) — the
standard trade for static shapes on TPU.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from lmrs_tpu.config import ModelConfig
from lmrs_tpu.ops.quant import qeinsum


def expert_capacity(n_tokens: int, cfg: ModelConfig) -> int:
    """Static per-expert token capacity for a call with ``n_tokens`` tokens."""
    k = min(cfg.n_experts_per_token, cfg.n_experts)
    c = math.ceil(cfg.expert_capacity_factor * n_tokens * k / cfg.n_experts)
    return max(1, min(n_tokens, c))


def moe_mlp(mp, cfg: ModelConfig, x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """MoE SwiGLU FFN.  x [B,S,D] -> (out [B,S,D], aux_loss scalar f32).

    ``mp`` holds one layer's expert params: router [D,E], w_gate/w_up
    [E,D,F], w_down [E,F,D].  The aux loss is the Switch load-balancing
    term E * Σ_e f_e·P_e (≈1 when balanced), from top-1 assignments.
    """
    dt = x.dtype
    b, s, d = x.shape
    e = cfg.n_experts
    k = min(cfg.n_experts_per_token, e)
    n = b * s
    xt = x.reshape(n, d)

    # --- routing (f32 for a stable softmax) ---
    logits = jnp.einsum("nd,de->ne", xt.astype(jnp.float32),
                        mp["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)              # [N,E]
    gate_vals, gate_idx = jax.lax.top_k(probs, k)        # [N,k]
    gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    # --- capacity assignment: slot-major cumsum so primary (slot-0)
    # assignments claim capacity before secondary ones ---
    c = expert_capacity(n, cfg)
    expert_flat = gate_idx.T.reshape(k * n)              # [kN] slot-major
    onehot = jax.nn.one_hot(expert_flat, e, dtype=jnp.int32)
    pos = ((jnp.cumsum(onehot, axis=0) - onehot) * onehot).sum(-1)  # [kN]
    keep = (pos < c).astype(jnp.float32)
    gates_flat = gate_vals.T.reshape(k * n) * keep

    # dispatch/combine one-hots: [kN,E,C] -> merge the k slots -> [N,E,C]
    slot_oh = jax.nn.one_hot(jnp.clip(pos, 0, c - 1), c, dtype=jnp.float32)
    dispatch = (onehot.astype(jnp.float32) * keep[:, None])[:, :, None] * slot_oh[:, None, :]
    combine = gates_flat[:, None, None] * dispatch
    dispatch = dispatch.reshape(k, n, e, c).sum(0)
    combine = combine.reshape(k, n, e, c).sum(0)

    # --- expert FFN: all-MXU einsums over [E,C,·] ---
    xin = jnp.einsum("nd,nec->ecd", xt, dispatch.astype(dt))
    gate_h = qeinsum("ecd,edf->ecf", xin, mp["w_gate"], dt)
    up = qeinsum("ecd,edf->ecf", xin, mp["w_up"], dt)
    from lmrs_tpu.models.transformer import gate_act

    ff = gate_act(cfg, gate_h).astype(dt) * up
    y = qeinsum("ecf,efd->ecd", ff, mp["w_down"], dt)
    out = jnp.einsum("nec,ecd->nd", combine.astype(dt), y)

    # --- Switch load-balance loss ---
    f = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(f * probs.mean(0))
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------- routed share
#
# The DeepSeek-V3 routed layer, for an engine that holds a share of the
# experts: sigmoid scores over ALL ``n_routed_experts``, the top k of score
# + selection bias, weights = the chosen scores normalised and scaled; this
# engine computes the pairs that land on the experts it holds
# (``expert_first`` .. + ``experts_held``) and leaves out what the others
# would add (they live on other chips; nothing here stands in for them or
# for their exchange).  No token is dropped: the pairs on held experts are
# sorted by expert and go through grouped matrix products a fixed number of
# rows at a time, as many passes as their count needs.  The first pass is
# sized so that further ones, a ``while_loop``, normally run zero times.


def routed_first_pass_rows(n_tokens: int, cfg: ModelConfig) -> int:
    """Rows of one grouped-product pass for a call with ``n_tokens`` tokens:
    twice the pairs expected on the held experts under even routing, or six
    deviations over it where that is more (a decode step's few rows), in
    whole 16-row tiles, and never more than every pair of the call."""
    pairs = n_tokens * cfg.n_experts_per_token
    expect = pairs * cfg.experts_held / cfg.n_routed_experts
    rows = max(2.0 * expect, expect + 6.0 * math.sqrt(expect) + 16.0)
    return -(-min(pairs, math.ceil(rows)) // 16) * 16


def route_tokens(mp, cfg: ModelConfig, xt: jnp.ndarray):
    """xt [N, D] -> (experts chosen [N, k] int32, their weights [N, k] f32).
    Scores and weights in float32: a bf16 product is exact in float32, so
    the bf16 operands with a float32 accumulator ARE the float32 router."""
    k = cfg.n_experts_per_token
    logits = jnp.einsum("nd,de->ne", xt, mp["router"].astype(xt.dtype),
                        preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + mp["bias"].astype(jnp.float32), k)
    picked = jnp.take_along_axis(scores, idx, axis=1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), weights * cfg.routed_scaling_factor


def routed_experts(mp, cfg: ModelConfig, x: jnp.ndarray,
                   token_valid: jnp.ndarray | None = None,
                   layer: jnp.ndarray | None = None):
    """The held experts' part of a routed layer.  x [B,S,D] (normed) ->
    (out [B,S,D], stats int32 [experts_held + 3]: pairs landed on each held
    expert, the busiest one's, the passes beyond the first, and the held
    experts at least one pair chose: the ones whose weights a decode step
    reads).

    ``mp``: router [D, n_routed_experts], bias [n_routed_experts], w_gate /
    w_up [experts_held, D, F], w_down [experts_held, F, D].  ``token_valid``
    [B,S] keeps padding and idle rows out of the experts (their output is
    discarded anyway; routed, they would only take rows of a pass).

    With ``layer`` (a traced index) the three expert leaves are a whole
    model's, stacked [n_layers, experts_held, ...], and the grouped
    products run over all ``n_layers * experts_held`` groups with every
    other layer's sized 0.  A grouped product skips empty groups, so the
    work is the same; what it saves is the copy: a scan that hands one
    layer's experts to the product's custom call slices them out into a
    buffer of their own, 1 GB a routed layer a step at the published
    widths (PERF.md, PR 29: 14.3 s of a 24.2 s cycle)."""
    from lmrs_tpu.models.transformer import gate_act

    dt = x.dtype
    b, s, d = x.shape
    n, k = b * s, cfg.n_experts_per_token
    held = cfg.experts_held
    xt = x.reshape(n, d)
    with jax.named_scope("moe.route"):
        idx, weights = route_tokens(mp, cfg, xt)
        local = idx - cfg.expert_first
        on = (local >= 0) & (local < held)
        if token_valid is not None:
            on = on & token_valid.reshape(n, 1)
        # pairs sorted by held expert (stable: token order within one);
        # the rest carry ``held`` and sort behind them
        e_flat = jnp.where(on, local, held).reshape(n * k)
        e_sorted, order = jax.lax.sort(
            (e_flat, jnp.arange(n * k, dtype=jnp.int32)), num_keys=1)
        ends = jnp.searchsorted(e_sorted, jnp.arange(held, dtype=jnp.int32),
                                side="right").astype(jnp.int32)
        counts = jnp.diff(ends, prepend=0)
        total = ends[-1]
        rows = routed_first_pass_rows(n, cfg)
        # one pass of padding behind the pairs: a slice never runs off
        tok_sorted = jnp.pad(order // k, (0, rows))
        w_sorted = jnp.pad(weights.reshape(n * k)[order], (0, rows))

    w_gate, w_up, w_down = mp["w_gate"], mp["w_up"], mp["w_down"]
    if layer is not None:  # [L, held, ...] -> [L * held, ...]: no copy
        w_gate, w_up, w_down = (w.reshape(-1, *w.shape[2:])
                                for w in (w_gate, w_up, w_down))
    n_groups = w_gate.shape[0]

    def one_pass(out, p):
        start = p * rows
        toks = jax.lax.dynamic_slice(tok_sorted, (start,), (rows,))
        w = jax.lax.dynamic_slice(w_sorted, (start,), (rows,))
        sizes = (jnp.clip(ends - start, 0, rows)
                 - jnp.clip(ends - counts - start, 0, rows))
        live = (start + jnp.arange(rows) < total)[:, None]
        xs = xt[toks]
        if layer is not None:
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((n_groups,), sizes.dtype), sizes, (layer * held,))
        gate = jax.lax.ragged_dot(xs, w_gate, sizes)
        up = jax.lax.ragged_dot(xs, w_up, sizes)
        y = jax.lax.ragged_dot(gate_act(cfg, gate).astype(dt) * up,
                               w_down, sizes)
        # rows past the last pair belong to no group: whatever the product
        # left there is dropped, not scaled
        y = jnp.where(live, y.astype(jnp.float32) * w[:, None], 0.0)
        return out.at[toks].add(y.astype(dt))

    with jax.named_scope("moe.experts"):
        out = one_pass(jnp.zeros((n, d), dt), 0)
        n_passes = jax.lax.div(total + rows - 1, rows)
        out, _ = jax.lax.while_loop(
            lambda c: c[1] < n_passes,
            lambda c: (one_pass(c[0], c[1]), c[1] + 1), (out, jnp.int32(1)))
    stats = jnp.concatenate([
        counts, counts.max()[None], jnp.maximum(n_passes - 1, 0)[None],
        (counts > 0).sum()[None]])
    return out.reshape(b, s, d), stats.astype(jnp.int32)
