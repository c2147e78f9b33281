"""GQA decoder whose layers differ in kind (the EXAONE-4 block, and behind
it the DeepSeek-V3 routed layer that ``exaone_moe`` adds).

``ModelConfig.sliding_window > 0`` selects it: ``transformer.init_params``,
``forward`` and ``forward_paged`` hand over to the functions here, so the
engine, the scheduler and the page pools above them are the ones every
model takes.  What differs from the dense block:

* **Two kinds of attention layer in one stack** (``cfg.layer_windows``): a
  window layer's query at position i sees the keys j with 0 <= i - j <
  ``sliding_window``, a full layer's all of them.  The kind is a per-layer
  scalar that rides the layer scan beside the stacked weights (the scan
  stays one scan a group, whatever the pattern): the XLA attention and the
  decode walk take it traced; the flash prefill kernel's banded grid is
  static, so a ``lax.cond`` picks the windowed or the full kernel.
* **Two kinds of cache layer in one pool** (``pool_layout``): a full layer
  holds ``num_pages`` pages as every other model's layers do; a window
  layer holds a RING of ``ring`` = ceil(window / page) + 1 pages a slot,
  logical page j of the slot's sequence living in ring page j % ring.  A
  fresh prefill writes the ring's last pages only; a decode step walks at
  most ``ring`` pages (ops/paged_attention.window_walk).
* **Block switches** (``WindowMoEModelConfig``): ``norm_inputs`` False and
  ``sandwich_norm``: norms on the sublayers' outputs alone; ``qk_norm``;
  ``rope_window_only``: the full layers carry no rope.
* **Layer groups** as models/latent.py has them (models/blocks.py):
  ``n_dense_layers`` leading layers with a dense FFN, then the routed
  layers (ops/moe.routed_experts plus a shared expert); without routed
  experts, one group of dense layers.

Routed models also hand back, from every paged call, the held experts'
token counts ``[routed layers, experts_held + 3]`` (ops/moe.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from lmrs_tpu.config import ModelConfig
from lmrs_tpu.models.blocks import ffn, layer_groups, split_experts
from lmrs_tpu.ops.attention import attention
from lmrs_tpu.ops.norms import rms_norm
from lmrs_tpu.ops.rope import apply_rope, rope_table


def pool_layout(cfg: ModelConfig, page_size: int, slots: int,
                num_pages: int) -> dict:
    """Where each layer's pages lie in the one pool [total, K, ps, hd]:
    the full layers first, ``num_pages`` each (page 0 of each the null
    page), then the window layers, ``win_pages`` = slots x ring + 1 each
    (page 0 the null page, slot s's ring the pages 1 + s * ring ...).
    ``base[l]`` is layer l's first page."""
    wins = np.asarray(cfg.layer_windows, np.int64)
    is_win = wins > 0
    ring = cfg.window_ring_pages(page_size)
    win_pages = slots * ring + 1
    n_full = int((~is_win).sum())
    base = np.where(is_win, n_full * num_pages
                    + (np.cumsum(is_win) - 1) * win_pages,
                    (np.cumsum(~is_win) - 1) * num_pages)
    n_win = cfg.n_window_layers
    return {"base": base.astype(np.int32), "ring": ring,
            "win_pages": win_pages, "n_full": n_full, "n_win": n_win,
            "total": n_full * num_pages + n_win * win_pages}


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    """Random-init tree (fan-in scaling).  Routed layers hold the experts
    this engine holds (``cfg.experts_held``); the router and its selection
    bias are as wide as the model's (``n_routed_experts``)."""
    dt = jnp.dtype(cfg.dtype)
    d, h, kh, hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.hd

    def tn(key, shape, fan_in):
        return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dt)

    def mlp(key, lead, f):
        ks = jax.random.split(key, 3)
        return {"w_gate": tn(ks[0], (*lead, d, f), d),
                "w_up": tn(ks[1], (*lead, d, f), d),
                "w_down": tn(ks[2], (*lead, f, d), f)}

    def group(key, n, routed):
        ks = jax.random.split(key, 9)
        norms = (("ln_attn", "ln_mlp") if cfg.norm_inputs else ()) + (
            ("ln_attn_out", "ln_mlp_out") if cfg.sandwich_norm else ())
        g = {**{name: {"scale": jnp.zeros((n, d), dt)} for name in norms},
             "attn": {"wq": tn(ks[0], (n, d, h, hd), d),
                      "wk": tn(ks[1], (n, d, kh, hd), d),
                      "wv": tn(ks[2], (n, d, kh, hd), d),
                      "wo": tn(ks[3], (n, h, hd, d), h * hd)}}
        if cfg.qk_norm:
            g["attn"]["q_norm"] = jnp.zeros((n, hd), dt)
            g["attn"]["k_norm"] = jnp.zeros((n, hd), dt)
        if not routed:
            g["mlp"] = mlp(ks[4], (n,), cfg.dense_hidden_dim or cfg.hidden_dim)
            return g
        g["moe"] = {
            "router": tn(ks[5], (n, d, cfg.n_routed_experts), d),
            "bias": 0.02 * jax.random.normal(
                ks[6], (n, cfg.n_routed_experts), jnp.float32),
            **mlp(ks[4], (n, cfg.experts_held), cfg.hidden_dim)}
        if cfg.n_shared_experts:
            g["shared"] = mlp(ks[7], (n,),
                              cfg.hidden_dim * cfg.n_shared_experts)
        return g

    k_embed, k_dense, k_layers, k_head = jax.random.split(key, 4)
    n_routed = cfg.n_routed_layers
    params = {"embed": {"weight": tn(k_embed, (cfg.vocab_size, d), d)}}
    if n_routed and cfg.n_dense_layers:
        params["dense_layers"] = group(k_dense, cfg.n_dense_layers, False)
    params["layers"] = group(k_layers, n_routed or cfg.n_layers,
                             bool(n_routed))
    params["final_norm"] = {"scale": jnp.zeros((d,), dt)}
    if not cfg.tie_embeddings:
        params["lm_head"] = {"weight": tn(k_head, (d, cfg.vocab_size), d)}
    return params


# ------------------------------------------------------------------ the block

def project(lp, cfg: ModelConfig, x, positions, sin, cos, window):
    """[B,S,D] -> (q [B,S,H,hd], k, v [B,S,K,hd]), q and k normed over the
    head's width and rotated as the layer's kind asks (``window``: this
    layer's, a traced scalar)."""
    a = lp["attn"]
    h = (rms_norm(x, lp["ln_attn"]["scale"], cfg.norm_eps)
         if cfg.norm_inputs else x)
    q = jnp.einsum("bsd,dhk->bshk", h, a["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, a["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, a["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, a["q_norm"], cfg.norm_eps)
        k = rms_norm(k, a["k_norm"], cfg.norm_eps)
    q_r = apply_rope(q, positions, sin, cos)
    k_r = apply_rope(k, positions, sin, cos)
    if cfg.rope_window_only:  # a full layer's q and k stay as they are
        return (jnp.where(window > 0, q_r, q), jnp.where(window > 0, k_r, k),
                v)
    return q_r, k_r, v


def finish(lp, cfg: ModelConfig, x, attn_out, token_valid=None, experts=None,
           layer=None):
    """The residual adds around attention's output and the FFN: (x,
    routed-layer stats or None)."""
    def out(name, y):
        if cfg.sandwich_norm:
            return rms_norm(y, lp[name]["scale"], cfg.norm_eps)
        return y

    x = x + out("ln_attn_out",
                jnp.einsum("bshk,hkd->bsd", attn_out, lp["attn"]["wo"]))
    h = (rms_norm(x, lp["ln_mlp"]["scale"], cfg.norm_eps)
         if cfg.norm_inputs else x)
    ff, stats = ffn(lp, cfg, h, token_valid, experts, layer)
    return x + out("ln_mlp_out", ff), stats


def _head(params, cfg: ModelConfig, x):
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"]["weight"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"]["weight"])
    return logits.astype(jnp.float32)


def _scan_groups(params, cfg: ModelConfig, layer_fn, carry):
    """Every group's layers through ``layer_fn(experts, carry, (layer
    params, layer index, index in the group, window))``; returns the carry
    and the last group's stacked per-layer outputs."""
    wins = jnp.asarray(cfg.layer_windows, jnp.int32)
    first, ys = 0, None
    for group, n in layer_groups(params):
        scanned, experts = split_experts(group)
        carry, ys = jax.lax.scan(
            functools.partial(layer_fn, experts), carry,
            (scanned, first + jnp.arange(n), jnp.arange(n),
             wins[first:first + n]))
        first += n
    return carry, ys


def forward(params, cfg: ModelConfig, tokens, positions, kv_length=None):
    """Plain cache-less forward: (logits [B,S,V] f32, None)."""
    x = params["embed"]["weight"][tokens]
    sin, cos = rope_table(tokens.shape[1], cfg.hd, cfg.rope_theta)

    def layer(experts, x, xs):
        lp, _, lg, w = xs
        q, k, v = project(lp, cfg, x, positions, sin, cos, w)
        o = attention(q, k, v, positions, kv_length, window=w)
        return finish(lp, cfg, x, o, None, experts, lg)[0], None

    x, _ = _scan_groups(params, cfg, layer, x)
    return _head(params, cfg, x), None


def forward_paged(params, cfg: ModelConfig, tokens, positions, k_pages,
                  v_pages, page_tables, kv_lens, rope_max: int, *,
                  window_slots: int, use_ragged_kernel: bool = False,
                  window_prefill: bool = False, use_flash: bool = True,
                  interpret: bool = False, last_pos=None, token_valid=None,
                  scale_rows=None, decode_row_group: int = 1):
    """``transformer.forward_paged`` over the two-kind pool
    (``pool_layout``; ``window_slots`` is the engine's slot count, which
    sizes the rings).  ``scale_rows`` [B] maps a dispatch row to its slot
    (None: rows are slots): a window layer's pages are its slot's ring.
    Returns (logits, k_pages, v_pages) and, for a routed model, the
    experts' counts as a fourth element.  Fresh prefill (positions from 0)
    and single-token decode; the windowed continuation of a chunked prompt
    is not built (the scheduler refuses ``prefill_chunk`` < the window)."""
    from lmrs_tpu.models.transformer import _use_flash_prefill
    from lmrs_tpu.ops.paged_attention import (
        paged_decode_pallas_fused, paged_decode_xla, scatter_kv_pages,
        scatter_kv_rows, whole_pages)

    if window_prefill:
        raise NotImplementedError(
            "window cache: the continuation of a chunked prompt is not "
            "built (prefill_chunk must cover the serving window)")
    b, s = tokens.shape
    ps, width = k_pages.shape[2], page_tables.shape[1]
    # the full layers' pages a layer, from the pool's own size
    ring, window = cfg.window_ring_pages(ps), cfg.sliding_window
    n_win = cfg.n_window_layers
    num_pages = ((k_pages.shape[0] - n_win * (window_slots * ring + 1))
                 // max(cfg.n_layers - n_win, 1))
    base = jnp.asarray(pool_layout(cfg, ps, window_slots, num_pages)["base"])
    x = params["embed"]["weight"][tokens]
    sin, cos = rope_table(rope_max, cfg.hd, cfg.rope_theta)
    is_decode = s == 1

    # a window layer's table: the slot's ring, logical page j in ring page
    # j % ring; a row without pages (padding, an idle slot) keeps the null
    # page in every column, as its full-layer table does
    slot = (jnp.arange(b, dtype=jnp.int32) if scale_rows is None
            else scale_rows)
    live = (page_tables[:, :1] > 0) & (slot[:, None] < window_slots)
    win_tables = jnp.where(
        live, 1 + slot[:, None] * ring + jnp.arange(width)[None, :] % ring, 0)

    col = jnp.clip(positions // ps, 0, width - 1)
    offsets = positions % ps
    page_idx = win_idx = win_write = None
    n_whole = 0
    if not is_decode:
        valid = jnp.arange(s)[None, :] < kv_lens[:, None]
        # what a window layer keeps of a fresh prompt: the pages from the
        # one that holds position len - window on
        first = jnp.maximum(kv_lens - window, 0) // ps
        n_whole = whole_pages(s, ps, width)
        if n_whole:
            cols = jnp.arange(n_whole)[None, :]
            win_write = jnp.where(
                (cols >= first[:, None]) & (cols * ps < kv_lens[:, None]),
                win_tables[:, :n_whole], 0)
        else:
            # a row at a time; the bucket's padding goes to the null page
            page_idx = jnp.where(
                valid, jnp.take_along_axis(page_tables, col, axis=1), 0)
            win_idx = jnp.where(
                valid & (col >= first[:, None]),
                jnp.take_along_axis(win_tables, col, axis=1), 0)
    flash = (not is_decode and use_flash
             and _use_flash_prefill(s, cfg.hd, interpret))

    def layer_fn(experts, carry, xs):
        x, kp, vp = carry
        lp, li, lg, w = xs
        is_win = w > 0
        g_tables = base[li] + jnp.where(is_win, win_tables, page_tables)
        q, k, v = project(lp, cfg, x, positions, sin, cos, w)
        if is_decode and use_ragged_kernel:
            with jax.named_scope("attn.decode"):
                o, kp, vp = paged_decode_pallas_fused(
                    q[:, 0], k[:, 0], v[:, 0], kp, vp, g_tables, kv_lens,
                    interpret=interpret, row_group=decode_row_group, window=w)
            o = o[:, None]
        elif is_decode:
            at = jnp.take_along_axis(g_tables, col, axis=1)
            kp = scatter_kv_rows(kp, at, offsets, k)
            vp = scatter_kv_rows(vp, at, offsets, v)
            with jax.named_scope("attn.decode"):
                o = paged_decode_xla(q[:, 0], kp, vp, g_tables, kv_lens,
                                     window=w)[:, None]
        else:
            if n_whole:
                at = base[li] + jnp.where(is_win, win_write,
                                          page_tables[:, :n_whole])
                kp = scatter_kv_pages(kp, at, k)
                vp = scatter_kv_pages(vp, at, v)
            else:
                at = base[li] + jnp.where(is_win, win_idx, page_idx)
                kp = scatter_kv_rows(kp, at, offsets, k)
                vp = scatter_kv_rows(vp, at, offsets, v)
            o = _prefill_attention(cfg, q, k, v, positions, kv_lens, w,
                                   flash, interpret)
        x, stats = finish(lp, cfg, x, o, token_valid, experts, lg)
        return (x, kp, vp), stats

    (x, k_pages, v_pages), stats = _scan_groups(
        params, cfg, layer_fn, (x, k_pages, v_pages))
    if last_pos is not None:
        x = jnp.take_along_axis(
            x, jnp.clip(last_pos, 0, s - 1)[:, None, None], axis=1)
    logits = _head(params, cfg, x)
    if cfg.n_routed_experts:
        return logits, k_pages, v_pages, stats
    return logits, k_pages, v_pages


def _prefill_attention(cfg: ModelConfig, q, k, v, positions, kv_lens, w,
                       flash: bool, interpret: bool):
    """A fresh prefill's attention (the current tokens are the whole
    context) for a layer of window ``w`` (traced): the flash kernel, banded
    or full as the layer's kind says, or the XLA reference."""
    if not flash:
        return attention(q, k, v, positions, kv_lens, window=w)
    from lmrs_tpu.ops.flash_attention import flash_attention

    def windowed():
        with jax.named_scope("attn.window"):
            return flash_attention(q, k, v, kv_lens, interpret=interpret,
                                   window=cfg.sliding_window)

    def full():
        with jax.named_scope("attn.full"):
            return flash_attention(q, k, v, kv_lens, interpret=interpret)

    return jax.lax.cond(w > 0, windowed, full)
