"""Latent-attention decoder (MLA; the DeepSeek-V3 block that Kimi-K2 uses).

``ModelConfig.kv_lora_rank > 0`` selects it: ``transformer.init_params``,
``forward`` and ``forward_paged`` hand over to the functions here, so the
engine, the scheduler and the page pool above them are the ones every
model takes.  What differs from the dense block:

* **Attention.**  Queries come through a low-rank bottleneck (``wq_a``,
  ``q_norm``, ``wq_b``); keys and values through one shared latent a token
  (``wkv_a`` -> ``[c_kv | k_r]``, ``kv_norm`` on ``c_kv``, rope on ``k_r``,
  one rotary key for all heads).  The cache holds that row and nothing
  else: ``[c_kv | rope(k_r) | 0]``, ``cfg.latent_width`` lanes, ONE pool
  (no V pool: ``v_pages`` is None through every program).
* **Two attention forms over the same cache.**  Prefill expands the
  latent into per-head keys and values (``wkv_b``) and runs the flash
  kernel at 192-wide queries/keys and 128-wide values.  Decode keeps the
  latent: ``q_nope`` is carried through ``wkv_b``'s key half into the
  latent space, the kernel of ops/mla_attention.py reads each page once
  for scores and values, and the result goes back through ``wkv_b``'s
  value half.  Same mathematics (tests/test_latent_moe.py).
* **Layers are not one homogeneous scan**: ``n_dense_layers`` leading
  layers with a dense FFN (``params["dense_layers"]``), then the routed
  layers (``params["layers"]``: ops/moe.routed_experts plus a shared
  expert through the dense path), each group its own ``lax.scan``.
* YaRN rope on the rotary dimensions only (ops/rope.yarn_inv_freq); the
  softmax scale carries YaRN's ``mscale`` squared.

Routed models also hand back, from every paged call, the held experts'
token counts ``[routed layers, experts_held + 2]`` (ops/moe.py), which the
scheduler fetches with the tokens.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from lmrs_tpu.config import ModelConfig
from lmrs_tpu.models.blocks import ffn, layer_groups as _groups, split_experts
from lmrs_tpu.ops.attention import attention
from lmrs_tpu.ops.norms import rms_norm
from lmrs_tpu.ops.rope import (apply_rope, rope_table, yarn_inv_freq,
                               yarn_mscale)


def softmax_scale(cfg: ModelConfig) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_mscale_all_dim:
        scale *= yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
    return scale


def rope_tables(cfg: ModelConfig, max_pos: int):
    """(sin, cos) [max_pos, qk_rope_head_dim / 2], YaRN frequencies; the
    tables carry mscale / mscale_all_dim (1 for the published settings)."""
    inv = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor,
                        cfg.rope_orig_max_pos, cfg.rope_beta_fast,
                        cfg.rope_beta_slow)
    sin, cos = rope_table(max_pos, cfg.qk_rope_head_dim, inv_freq=inv)
    m = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
         / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return (sin, cos) if m == 1.0 else (sin * m, cos * m)


def _dense_width(cfg: ModelConfig) -> int:
    return cfg.dense_hidden_dim or cfg.hidden_dim


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    """Random-init tree (fan-in scaling).  Routed layers hold the experts
    this engine holds (``cfg.experts_held``); the router and its selection
    bias are as wide as the model's (``n_routed_experts``)."""
    dt = jnp.dtype(cfg.dtype)
    d, h = cfg.dim, cfg.n_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim

    def tn(key, shape, fan_in):
        return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dt)

    def attn(key, n):
        ks = jax.random.split(key, 5)
        return {
            "wq_a": tn(ks[0], (n, d, cfg.q_lora_rank), d),
            "q_norm": jnp.zeros((n, cfg.q_lora_rank), dt),
            "wq_b": tn(ks[1], (n, cfg.q_lora_rank, h, qk), cfg.q_lora_rank),
            "wkv_a": tn(ks[2], (n, d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
                        d),
            "kv_norm": jnp.zeros((n, cfg.kv_lora_rank), dt),
            "wkv_b": tn(ks[3], (n, cfg.kv_lora_rank, h,
                                cfg.qk_nope_head_dim + cfg.v_head_dim),
                        cfg.kv_lora_rank),
            "wo": tn(ks[4], (n, h, cfg.v_head_dim, d), h * cfg.v_head_dim),
        }

    def mlp(key, lead, f):
        ks = jax.random.split(key, 3)
        return {"w_gate": tn(ks[0], (*lead, d, f), d),
                "w_up": tn(ks[1], (*lead, d, f), d),
                "w_down": tn(ks[2], (*lead, f, d), f)}

    def group(key, n, routed):
        ks = jax.random.split(key, 5)
        g = {"ln_attn": {"scale": jnp.zeros((n, d), dt)},
             "ln_mlp": {"scale": jnp.zeros((n, d), dt)},
             "attn": attn(ks[0], n)}
        if not routed:
            g["mlp"] = mlp(ks[1], (n,), _dense_width(cfg))
            return g
        g["moe"] = {
            "router": tn(ks[2], (n, d, cfg.n_routed_experts), d),
            "bias": 0.02 * jax.random.normal(
                ks[3], (n, cfg.n_routed_experts), jnp.float32),
            **mlp(ks[1], (n, cfg.experts_held), cfg.hidden_dim)}
        if cfg.n_shared_experts:
            g["shared"] = mlp(ks[4], (n,),
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

def project(lp, cfg: ModelConfig, h, positions, sin, cos):
    """Normed [B,S,D] -> (q_nope [B,S,H,nope], q_rope [B,S,H,rope] rotated,
    c_kv [B,S,rank] normed, k_rope [B,S,rope] rotated)."""
    a = lp["attn"]
    with jax.named_scope("mla.proj"):
        c_q = rms_norm(jnp.einsum("bsd,dr->bsr", h, a["wq_a"]), a["q_norm"],
                       cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", c_q, a["wq_b"])
        q_nope = q[..., :cfg.qk_nope_head_dim]
        q_rope = apply_rope(q[..., cfg.qk_nope_head_dim:], positions, sin, cos)
        ckv = jnp.einsum("bsd,dr->bsr", h, a["wkv_a"])
        c_kv = rms_norm(ckv[..., :cfg.kv_lora_rank], a["kv_norm"],
                        cfg.norm_eps)
        k_rope = apply_rope(ckv[..., None, cfg.kv_lora_rank:], positions,
                            sin, cos)[..., 0, :]
    return q_nope, q_rope, c_kv, k_rope


def expand_kv(lp, cfg: ModelConfig, c_kv, k_rope):
    """Latent rows -> per-head keys [B,T,H,nope+rope] and values
    [B,T,H,v]: the expanded form (prefill, and the reference of the
    absorbed one)."""
    kv = jnp.einsum("btr,rhk->bthk", c_kv, lp["attn"]["wkv_b"])
    k_r = jnp.broadcast_to(k_rope[:, :, None, :],
                           (*kv.shape[:3], k_rope.shape[-1]))
    return (jnp.concatenate([kv[..., :cfg.qk_nope_head_dim], k_r], axis=-1),
            kv[..., cfg.qk_nope_head_dim:])


def absorb_query(lp, cfg: ModelConfig, q_nope, q_rope):
    """[B,H,nope], [B,H,rope] -> latent-space queries [B,H,latent_width]:
    ``q_nope W_kvb^K`` beside ``q_rope``, zero where the pool's rows are."""
    w_k = lp["attn"]["wkv_b"][..., :cfg.qk_nope_head_dim]  # [rank, H, nope]
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope, w_k)
    pad = cfg.latent_width - cfg.kv_lora_rank - cfg.qk_rope_head_dim
    return jnp.concatenate(
        [q_lat, q_rope, jnp.zeros((*q_rope.shape[:2], pad), q_rope.dtype)],
        axis=-1)


def latent_rows(cfg: ModelConfig, c_kv, k_rope):
    """The cache's row of each token: [..., latent_width]."""
    pad = cfg.latent_width - cfg.kv_lora_rank - cfg.qk_rope_head_dim
    return jnp.concatenate(
        [c_kv, k_rope, jnp.zeros((*c_kv.shape[:-1], pad), c_kv.dtype)],
        axis=-1)


def _out_proj(lp, o):
    return jnp.einsum("bshk,hkd->bsd", o, lp["attn"]["wo"])


def _head(params, cfg: ModelConfig, x):
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"]["weight"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"]["weight"])
    return logits.astype(jnp.float32)


def forward(params, cfg: ModelConfig, tokens, positions, kv_length=None):
    """Plain cache-less forward, expanded heads: (logits [B,S,V] f32,
    None)."""
    x = params["embed"]["weight"][tokens]
    sin, cos = rope_tables(cfg, tokens.shape[1])
    scale = softmax_scale(cfg)

    def layer(x, lp):
        h = rms_norm(x, lp["ln_attn"]["scale"], cfg.norm_eps)
        q_nope, q_rope, c_kv, k_rope = project(lp, cfg, h, positions, sin, cos)
        k, v = expand_kv(lp, cfg, c_kv, k_rope)
        o = attention(jnp.concatenate([q_nope, q_rope], axis=-1), k, v,
                      positions, kv_length, scale=scale)
        x = x + _out_proj(lp, o)
        h = rms_norm(x, lp["ln_mlp"]["scale"], cfg.norm_eps)
        ff, _ = ffn(lp, cfg, h)
        return x + ff, None

    for group, _ in _groups(params):
        x, _ = jax.lax.scan(layer, x, group)  # plain: a layer's slice
    return _head(params, cfg, x), None


def forward_paged(params, cfg: ModelConfig, tokens, positions, pool,
                  page_tables, kv_lens, rope_max: int, *,
                  use_ragged_kernel: bool = False,
                  window_prefill: bool = False, use_flash: bool = True,
                  interpret: bool = False, last_pos=None, token_valid=None):
    """``transformer.forward_paged`` for the latent cache.  ``pool`` is the
    one latent pool [L*P, 1, ps, latent_width]; returns (logits, pool, None)
    and, for a routed model, the experts' counts [routed layers, held + 2]
    as a fourth element.  Fresh prefill (positions from 0), the windowed
    continuation of a chunked prompt, and single-token decode."""
    from lmrs_tpu.models.transformer import _use_flash_prefill
    from lmrs_tpu.ops.mla_attention import (mla_paged_decode_pallas,
                                            mla_paged_decode_xla)
    from lmrs_tpu.ops.paged_attention import (scatter_kv_pages,
                                              scatter_kv_rows, whole_pages)

    b, s = tokens.shape
    ps = pool.shape[2]
    n_pool = pool.shape[0] // cfg.n_layers
    rank = cfg.kv_lora_rank
    scale = softmax_scale(cfg)
    x = params["embed"]["weight"][tokens]
    sin, cos = rope_tables(cfg, rope_max)
    is_decode = s == 1
    page_idx = jnp.take_along_axis(
        page_tables, jnp.clip(positions // ps, 0, page_tables.shape[1] - 1),
        axis=1)
    offsets = positions % ps
    if not is_decode:
        # the bucket's padding goes to the null page, as in
        # transformer.forward_paged (never onto the row's last real token)
        chunk_len = kv_lens - positions[:, 0] if window_prefill else kv_lens
        page_idx = jnp.where(jnp.arange(s)[None, :] < chunk_len[:, None],
                             page_idx, 0)
    # a fresh prefill writes whole pages, as transformer.forward_paged does
    n_whole = (0 if is_decode or window_prefill
               else whole_pages(s, ps, page_tables.shape[1]))

    def layer_fn(experts, carry, xs):
        x, pool = carry
        lp, li, lg = xs  # the layer's params, its index, its index in the group
        g_tables = li * n_pool + page_tables
        h = rms_norm(x, lp["ln_attn"]["scale"], cfg.norm_eps)
        q_nope, q_rope, c_kv, k_rope = project(lp, cfg, h, positions, sin, cos)
        # the one write of a token's row (pool axis 1 is 1): a page at a
        # time where the program is a fresh prefill over whole pages
        rows = latent_rows(cfg, c_kv, k_rope)[:, :, None, :]
        if n_whole:
            pool = scatter_kv_pages(pool, g_tables[:, :n_whole], rows)
        else:
            pool = scatter_kv_rows(pool, li * n_pool + page_idx, offsets, rows)
        with jax.named_scope("mla.attn"):
            if is_decode:
                q_cat = absorb_query(lp, cfg, q_nope[:, 0], q_rope[:, 0])
                if use_ragged_kernel:
                    o_lat = mla_paged_decode_pallas(
                        q_cat, pool, g_tables, kv_lens, rank=rank,
                        sm_scale=scale, interpret=interpret)
                else:
                    o_lat = mla_paged_decode_xla(
                        q_cat, pool, g_tables, kv_lens, rank=rank,
                        sm_scale=scale)
                o = jnp.einsum(
                    "bhr,rhk->bhk", o_lat,
                    lp["attn"]["wkv_b"][..., cfg.qk_nope_head_dim:])[:, None]
            else:
                q = jnp.concatenate([q_nope, q_rope], axis=-1)
                if window_prefill:
                    # earlier chunks' rows come back from the pool
                    win = pool[g_tables, 0].reshape(b, -1, pool.shape[-1])
                    k, v = expand_kv(lp, cfg, win[..., :rank],
                                     win[..., rank:rank + cfg.qk_rope_head_dim])
                    o = attention(q, k, v, positions, kv_lens, scale=scale)
                else:
                    k, v = expand_kv(lp, cfg, c_kv, k_rope)
                    if use_flash and _use_flash_prefill(s, cfg.hd, interpret):
                        from lmrs_tpu.ops.flash_attention import flash_attention

                        o = flash_attention(q, k, v, kv_lens,
                                            interpret=interpret, sm_scale=scale)
                    else:
                        o = attention(q, k, v, positions, kv_lens, scale=scale)
        x = x + _out_proj(lp, o)
        h = rms_norm(x, lp["ln_mlp"]["scale"], cfg.norm_eps)
        ff, stats = ffn(lp, cfg, h, token_valid, experts, lg)
        return (x + ff, pool), stats

    first, stats = 0, None
    for group, n in _groups(params):
        scanned, experts = split_experts(group)
        (x, pool), stats = jax.lax.scan(
            functools.partial(layer_fn, experts), (x, pool),
            (scanned, first + jnp.arange(n), jnp.arange(n)))
        first += n
    if last_pos is not None:
        x = jnp.take_along_axis(
            x, jnp.clip(last_pos, 0, s - 1)[:, None, None], axis=1)
    logits = _head(params, cfg, x)
    if cfg.n_routed_experts:
        return logits, pool, None, stats
    return logits, pool, None
