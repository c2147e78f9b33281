"""Decoder-only transformer (Llama-3 / Gemma family) as a functional pytree.

Design choices (TPU-first, not a torch translation):

* **Pure functions over pytrees** — params are nested dicts of arrays; no
  module classes.  Plays directly with jit/shard_map/optax.
* **Stacked layers + ``lax.scan``** — all layer weights carry a leading
  ``n_layers`` axis and the layer loop is a scan, so compile time and HLO
  size are O(1) in depth (32-layer 8B compiles as fast as the 4-layer tiny).
* **Single forward for prefill AND decode** — the same traced function
  handles [B, S] prefill and [B, 1] decode against a KV cache; masking is
  driven by absolute positions + valid-length arrays (static shapes only, no
  data-dependent Python control flow).
* **GQA + RoPE + RMSNorm + SwiGLU**, optional Gemma quirks (embedding scale,
  logit softcap, tied embeddings).
* **Looped layers** (``cfg.n_loops`` > 1): the stack runs several times over
  the same weights (``run_stack``), the final norm closing every pass; pass
  ``t``'s layer ``l`` keeps its own K/V in cache layer ``t * n_layers + l``.
  ``cfg.sandwich_norm`` norms each sublayer's output before the residual add.

The reference has no model code at all — the LLM lives behind OpenAI's API
(SURVEY.md L0, llm_executor.py:292).  This module is the heart of what the
TPU build internalizes.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from lmrs_tpu.config import ModelConfig
from lmrs_tpu.ops.attention import attention
from lmrs_tpu.ops.norms import rms_norm
from lmrs_tpu.ops.quant import qeinsum
from lmrs_tpu.ops.rope import apply_rope, rope_table

Params = dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random-init params (truncated-normal fan-in scaling), stacked layers."""
    if cfg.kv_lora_rank:  # latent attention: its own tree (models/latent.py)
        from lmrs_tpu.models import latent

        return latent.init_params(cfg, key)
    if cfg.sliding_window:  # window and full layers: models/windowed.py
        from lmrs_tpu.models import windowed

        return windowed.init_params(cfg, key)
    dt = _dtype(cfg)
    hd = cfg.hd
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    def tn(key, shape, fan_in):
        return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dt)

    L = cfg.n_layers
    lk = jax.random.split(k_layers, 8)
    if cfg.n_experts:
        E = cfg.n_experts
        ffn = {
            "moe": {
                "router": tn(lk[7], (L, cfg.dim, E), cfg.dim),
                "w_gate": tn(lk[4], (L, E, cfg.dim, cfg.hidden_dim), cfg.dim),
                "w_up": tn(lk[5], (L, E, cfg.dim, cfg.hidden_dim), cfg.dim),
                "w_down": tn(lk[6], (L, E, cfg.hidden_dim, cfg.dim), cfg.hidden_dim),
            }
        }
    else:
        ffn = {
            "mlp": {
                "w_gate": tn(lk[4], (L, cfg.dim, cfg.hidden_dim), cfg.dim),
                "w_up": tn(lk[5], (L, cfg.dim, cfg.hidden_dim), cfg.dim),
                "w_down": tn(lk[6], (L, cfg.hidden_dim, cfg.dim), cfg.hidden_dim),
            }
        }
    norms = ("ln_attn", "ln_mlp")
    if cfg.sandwich_norm:  # the norms on the two sublayers' outputs
        norms += ("ln_attn_out", "ln_mlp_out")
    params: Params = {
        "embed": {"weight": tn(k_embed, (cfg.vocab_size, cfg.dim), cfg.dim)},
        "layers": {
            **{n: {"scale": jnp.zeros((L, cfg.dim), dt)} for n in norms},
            "attn": {
                "wq": tn(lk[0], (L, cfg.dim, cfg.n_heads, hd), cfg.dim),
                "wk": tn(lk[1], (L, cfg.dim, cfg.n_kv_heads, hd), cfg.dim),
                "wv": tn(lk[2], (L, cfg.dim, cfg.n_kv_heads, hd), cfg.dim),
                "wo": tn(lk[3], (L, cfg.n_heads, hd, cfg.dim), cfg.n_heads * hd),
            },
            **ffn,
        },
        "final_norm": {"scale": jnp.zeros((cfg.dim,), dt)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"weight": tn(k_head, (cfg.dim, cfg.vocab_size), cfg.dim)}
    return params


def param_count(params: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict[str, jnp.ndarray]:
    """Dense per-slot KV cache [L, B, S, K, hd] (paged cache: engine/kv_cache);
    L counts cache layers, one per (pass, layer) of a looped stack."""
    hd = cfg.hd
    shape = (cfg.cache_layers, batch, max_len, cfg.n_kv_heads, hd)
    dt = _dtype(cfg)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def gate_act(cfg: ModelConfig, gate: jnp.ndarray) -> jnp.ndarray:
    """Gated-FFN activation in f32: SiLU (Llama SwiGLU) or tanh-approximate
    GELU (Gemma GeGLU), selected by ``cfg.activation``."""
    gf = gate.astype(jnp.float32)
    if cfg.activation == "gelu":
        return jax.nn.gelu(gf, approximate=True)
    if cfg.activation == "silu":
        return jax.nn.silu(gf)
    raise ValueError(f"unknown activation {cfg.activation!r}; silu|gelu")


def ffn_block(lp: Params, cfg: ModelConfig, h: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Post-norm FFN body: dense gated FFN or MoE.  h [B,S,D] (already normed)
    -> (out [B,S,D], aux f32 scalar — the MoE load-balance loss, 0 for dense)."""
    if cfg.n_experts:
        from lmrs_tpu.ops.moe import moe_mlp

        return moe_mlp(lp["moe"], cfg, h)
    dt = h.dtype
    gate = qeinsum("bsd,df->bsf", h, lp["mlp"]["w_gate"], dt)
    up = qeinsum("bsd,df->bsf", h, lp["mlp"]["w_up"], dt)
    ff = gate_act(cfg, gate).astype(dt) * up
    return qeinsum("bsf,fd->bsd", ff, lp["mlp"]["w_down"], dt), jnp.float32(0.0)


def qkv_proj(lp: Params, cfg: ModelConfig, h: jnp.ndarray):
    """Project a normed [B,S,D] into (q [B,S,H,hd], k, v [B,S,K,hd])."""
    dt = h.dtype
    q = qeinsum("bsd,dhk->bshk", h, lp["attn"]["wq"], dt)
    k = qeinsum("bsd,dhk->bshk", h, lp["attn"]["wk"], dt)
    v = qeinsum("bsd,dhk->bshk", h, lp["attn"]["wv"], dt)
    return q, k, v


def out_proj(lp: Params, cfg: ModelConfig, attn_out: jnp.ndarray) -> jnp.ndarray:
    """[B,S,H,hd] attention output back to [B,S,D]."""
    return qeinsum("bshk,hkd->bsd", attn_out, lp["attn"]["wo"],
                   attn_out.dtype)


def sublayer_out(lp: Params, cfg: ModelConfig, norm: str,
                 y: jnp.ndarray) -> jnp.ndarray:
    """What a sublayer adds to the residual stream: its output ``y``, under
    ``cfg.sandwich_norm`` normed first by the layer's ``norm`` leaf."""
    if cfg.sandwich_norm:
        return rms_norm(y, lp[norm]["scale"], cfg.norm_eps)
    return y


def run_stack(params: Params, cfg: ModelConfig, carry: tuple, layer_fn):
    """The layer stack over ``carry`` (``carry[0]`` is the residual stream
    [.., D]): ``layer_fn(carry, (layer params, cache layer)) -> (carry,
    None)`` scanned over the stacked weights, ``cfg.n_loops`` times over the
    SAME weights.  Pass ``t`` hands layer ``l`` the cache layer ``t *
    n_layers + l``, so every (pass, layer) keeps its own K/V, and the
    model's final norm closes every pass: its output is the next pass's
    input and, after the last pass, what the LM head reads (``lm_head``
    does not norm again).  With ``n_loops`` 1 this is the one layer scan,
    and the final norm is the head's."""
    ids = jnp.arange(cfg.n_layers)

    def stack(carry, cache_layers):
        return jax.lax.scan(layer_fn, carry, (params["layers"], cache_layers))[0]

    if cfg.n_loops == 1:
        return stack(carry, ids)

    def one_pass(carry, t):
        with jax.named_scope("loop.pass"):
            carry = stack(carry, t * cfg.n_layers + ids)
        with jax.named_scope("loop.close"):
            x = rms_norm(carry[0], params["final_norm"]["scale"],
                         cfg.norm_eps)
        return (x, *carry[1:]), None

    return jax.lax.scan(one_pass, carry, jnp.arange(cfg.n_loops))[0]


def decoder_layer(
    lp: Params,               # one layer's params (no leading L axis)
    cfg: ModelConfig,
    x: jnp.ndarray,           # [B, S, D]
    positions: jnp.ndarray,   # [B, S]
    sin: jnp.ndarray,
    cos: jnp.ndarray,
    attn_fn=None,
    kv_length: jnp.ndarray | None = None,  # [B] valid-length mask (padding)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One cache-less decoder block (attention + dense/MoE FFN, pre-norm).

    Returns (x, aux) where aux is the MoE load-balance loss for this layer
    (0 for dense).  The shared body for training/prefill paths that don't
    carry a KV cache: plain scan in ``forward``, ring attention
    (``attn_fn``), and the pipeline stages in parallel/pipeline.py.
    """
    h = rms_norm(x, lp["ln_attn"]["scale"], cfg.norm_eps)
    q, k, v = qkv_proj(lp, cfg, h)
    q = apply_rope(q, positions, sin, cos)
    k = apply_rope(k, positions, sin, cos)
    if attn_fn is not None:
        attn_out = attn_fn(q, k, v, positions)
    else:
        attn_out = attention(q, k, v, positions, kv_length, logit_softcap=None)
    x = x + sublayer_out(lp, cfg, "ln_attn_out", out_proj(lp, cfg, attn_out))
    h = rms_norm(x, lp["ln_mlp"]["scale"], cfg.norm_eps)
    ff, aux = ffn_block(lp, cfg, h)
    return x + sublayer_out(lp, cfg, "ln_mlp_out", ff), aux


def embed_tokens(params: Params, cfg: ModelConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    """Token embedding lookup (+ Gemma's sqrt(dim) scale)."""
    x = params["embed"]["weight"][tokens]
    if cfg.embed_scale:
        x = (x.astype(jnp.float32) * math.sqrt(cfg.dim)).astype(_dtype(cfg))
    return x


def lm_head(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Final norm + output projection to f32 logits (+ optional softcap).
    A looped stack (``run_stack``) has closed its last pass with that norm
    already."""
    if cfg.n_loops == 1:
        x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"]["weight"])
    else:
        logits = qeinsum("bsd,dv->bsv", x, params["lm_head"]["weight"], x.dtype)
    logits = logits.astype(jnp.float32)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,      # [B, S] int32
    positions: jnp.ndarray,   # [B, S] absolute positions
    cache: dict[str, jnp.ndarray] | None = None,  # dense KV cache or None
    kv_length: jnp.ndarray | None = None,         # [B] valid KV len AFTER this call's writes
    attn_fn=None,  # optional (q, k, v, positions) -> out override (e.g. ring
                   # attention for sequence-parallel training; cache-less only)
    return_aux: bool = False,  # also return the layer-mean MoE aux loss
    remat: bool = False,  # rematerialize each layer in the backward pass
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray] | None] | tuple[jnp.ndarray, Any, jnp.ndarray]:
    """Forward pass; returns (logits [B,S,V] f32, updated cache), plus the
    layer-mean MoE load-balance loss as a third element when ``return_aux``.

    With a cache: K/V for `tokens` are scattered into it at `positions` and
    attention reads the cache (prefill S>1 or decode S=1 both work).
    Without a cache: plain causal self-attention over the sequence — or
    ``attn_fn`` when given (context-parallel ring attention over ``sp``).
    """
    if cache is not None and attn_fn is not None:
        raise ValueError("attn_fn (ring attention) is cache-less only; "
                         "decode against a KV cache uses dense/paged attention")
    if kv_length is not None and attn_fn is not None:
        raise ValueError("attn_fn does not apply kv_length masking; "
                         "pad-free batches only on the ring-attention path")
    if cfg.kv_lora_rank:
        if cache is not None or attn_fn is not None or return_aux or remat:
            raise NotImplementedError(
                "latent attention (kv_lora_rank > 0) has the plain forward "
                "and the paged one: no dense KV cache, ring attention, aux "
                "loss or remat")
        from lmrs_tpu.models import latent

        return latent.forward(params, cfg, tokens, positions, kv_length)
    if cfg.sliding_window:
        if cache is not None or attn_fn is not None or return_aux or remat:
            raise NotImplementedError(
                "a windowed stack (sliding_window > 0) has the plain forward "
                "and the paged one: no dense KV cache, ring attention, aux "
                "loss or remat")
        from lmrs_tpu.models import windowed

        return windowed.forward(params, cfg, tokens, positions, kv_length)
    dt = _dtype(cfg)
    b, s = tokens.shape
    hd = cfg.hd
    x = embed_tokens(params, cfg, tokens)  # [B,S,D]

    max_pos = cache["k"].shape[2] if cache is not None else s
    sin, cos = rope_table(max_pos, hd, cfg.rope_theta)
    batch_idx = jnp.arange(b)[:, None]  # [B,1] for cache scatter

    if cache is not None:
        def layer_fn(carry, xs):
            # cache rides the carry, not xs/ys — as xs every iteration
            # would memcpy the full [L,B,S,K,hd] buffers into the stacked
            # scan output (see forward_paged's layer_fn note)
            x, ck_all, cv_all = carry
            lp, li = xs  # layer params, layer index
            ck = jax.lax.dynamic_index_in_dim(ck_all, li, 0, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(cv_all, li, 0, keepdims=False)
            h = rms_norm(x, lp["ln_attn"]["scale"], cfg.norm_eps)
            q, k, v = qkv_proj(lp, cfg, h)
            q = apply_rope(q, positions, sin, cos)
            k = apply_rope(k, positions, sin, cos)
            ck = ck.at[batch_idx, positions].set(k)
            cv = cv.at[batch_idx, positions].set(v)
            attn_out = attention(q, ck, cv, positions, kv_length,
                                 logit_softcap=None)
            x = x + sublayer_out(lp, cfg, "ln_attn_out",
                                 out_proj(lp, cfg, attn_out))

            h = rms_norm(x, lp["ln_mlp"]["scale"], cfg.norm_eps)
            ff, _ = ffn_block(lp, cfg, h)
            x = x + sublayer_out(lp, cfg, "ln_mlp_out", ff)
            ck_all = jax.lax.dynamic_update_index_in_dim(ck_all, ck, li, 0)
            cv_all = jax.lax.dynamic_update_index_in_dim(cv_all, cv, li, 0)
            return (x, ck_all, cv_all), None

        # lax.scan over stacked layers: wq etc. are [L, ...]; cache [L, B, ...]
        x, new_k, new_v = run_stack(
            params, cfg, (x, cache["k"], cache["v"]), layer_fn)
        new_cache = {"k": new_k, "v": new_v}
        aux = jnp.float32(0.0)
    else:
        def one_layer(lp, x):
            return decoder_layer(lp, cfg, x, positions, sin, cos,
                                 attn_fn, kv_length)

        if remat:
            # Trade FLOPs for HBM: save only each layer's input activation,
            # recompute the rest in backward — activation memory drops from
            # O(L * per-layer intermediates) to O(L * [B,S,D]).
            one_layer = jax.checkpoint(one_layer)

        def layer_fn_nocache(carry, xs):
            x, aux = carry
            x, layer_aux = one_layer(xs[0], x)
            return (x, aux + layer_aux), None

        x, aux = run_stack(params, cfg, (x, jnp.float32(0.0)),
                           layer_fn_nocache)
        aux = aux / cfg.cache_layers
        new_cache = None

    logits = lm_head(params, cfg, x)
    if return_aux:
        return logits, new_cache, aux
    return logits, new_cache


def _use_flash_prefill(seq_len: int, hd: int, interpret: bool = False) -> bool:
    """Route fresh prefill through the Pallas flash kernel: TPU backend (or
    interpret mode, for CPU-mesh tests), a sequence long enough that O(S²)
    logits materialization starts to matter, and a lane-aligned head dim
    (validated on hardware for multiples of 64; smaller head dims fail
    Mosaic lowering)."""
    from lmrs_tpu.utils.platform import on_tpu

    return seq_len >= 256 and hd % 64 == 0 and (interpret or on_tpu())


def forward_paged(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,       # [B, S] int32
    positions: jnp.ndarray,    # [B, S] absolute positions
    k_pages: jnp.ndarray,      # [L*P, K, ps, hd] (page-major, layer-flattened)
    v_pages: jnp.ndarray,      # [L*P, K, ps, hd]
    page_tables: jnp.ndarray,  # [B, W] LOGICAL page ids (< P)
    kv_lens: jnp.ndarray,      # [B] valid tokens AFTER this call's writes
    rope_max: int,
    use_ragged_kernel: bool = False,
    window_prefill: bool = False,
    use_flash: bool = True,  # allow the flash prefill kernel (when eligible)
    mesh=None,  # tensor-parallel mesh: Pallas calls run via shard_map over tp
    interpret: bool = False,  # Pallas interpret mode (CPU-mesh tests)
    token_pages: jnp.ndarray | None = None,   # [B, S] per-token LOGICAL page
    segment_ids: jnp.ndarray | None = None,   # [B, S] packed-prompt segments
    packed_last_idx: jnp.ndarray | None = None,  # [N] last-token row indices
    use_ring: bool = False,  # sp-mesh fresh prefill: ring attention over sp
    last_pos: jnp.ndarray | None = None,  # [B] per-row last-token index
    multi_decode: bool = False,  # speculative verify: S tokens, ragged walk
    kv_scales: tuple | None = None,  # (kscale, vscale) [L, Bs, K, hd] f32:
                                     # int8 KV pools (ops/quant.py KV section)
    scale_rows: jnp.ndarray | None = None,  # [B] dispatch row -> slot id
                                            # (None: rows ARE slots); >= Bs
                                            # rows are pads (updates dropped)
    decode_row_group: int = 1,  # rows per ragged-decode program (multi-row
                                # page walk, ops/paged_attention.py); 1 =
                                # per-row grid (the LMRS_MULTIROW=0 path)
    spans: tuple | None = None,  # (q_starts [B], q_lens [B], row_flat [Tp]):
                                 # ragged span mode — tokens is
                                 # ONE flat [1, Tp] row holding every row's
                                 # query span; kv_lens is then the context
                                 # BEFORE this dispatch (span base), and
                                 # attention runs through the unified span
                                 # kernel (ops ragged_spans_*).  Use
                                 # packed_last_idx to gather sampled rows.
    span_anc: jnp.ndarray | None = None,  # [Tp] int32 ancestor bitmasks for
                                 # tree-speculative spans (ISSUE 19): tokens
                                 # with a nonzero mask attend context + their
                                 # ancestor offsets only; 0 keeps the linear
                                 # causal rule.  Routes to the XLA span twin
                                 # (the Pallas ancestor variant is chip debt,
                                 # docs/PERF.md).
    token_valid: jnp.ndarray | None = None,  # [B, S] bool: tokens that carry
                                 # work (not padding, not an idle row); read
                                 # by routed layers only (ops/moe.py)
    window_slots: int = 0,  # a windowed stack's pool (models/windowed.py):
                            # the engine's slot count, which sizes the rings
) -> tuple:
    """Forward pass against a paged KV cache (engine/kv_cache.PagedKVCache).

    Returns (logits [B,S,V] f32, k_pages, v_pages) — plus a fourth element
    ``(kscale, vscale)`` (the updated scale buffers) when ``kv_scales`` is
    given.  K/V of `tokens` are scattered into the pages named by
    ``page_tables`` at (page_tables[b, pos//ps], pos%ps); with
    ``kv_scales`` the pools are int8 and the scattered rows quantize with
    the dispatch rows' per-(slot, kv head, channel) scales — owned by the
    prompt's FIRST prefill dispatch (fresh, or the start==0 window chunk),
    reused and clamped to by everything after.

    Prefill (S>1, fresh sequence starting at position 0) attends the current
    tokens directly (flash path eligible); decode (S==1) attends the paged
    pool — via the ragged Pallas kernel on TPU or the gather fallback.  A
    fresh prefill over whole pages (S a multiple of the page size) writes
    page ``page_tables[b, j]`` whole from positions [j*ps, (j+1)*ps)
    (``scatter_kv_pages``): what a row holds past its allocation goes to the
    null page (table column 0).  Every other prefill writes a row at a time
    and sends the bucket's padding (tokens at and past the row's length) to
    the null page too.

    ``window_prefill`` is the chunked-prefill path (SARATHI-style,
    PAPERS.md): S>1 queries at positions ``>= 0`` that must also see KV
    written by EARLIER chunks of the same prompt — attention runs against
    the gathered page window (pages are in logical order, so window index
    == absolute position), masked causally by absolute position + kv_lens.

    PACKED prefill (``segment_ids`` given): several fresh prompts
    concatenated into one [1, S] row — each token's page comes from
    ``token_pages`` (host-built per segment; ``page_tables`` is then
    ignored for writes), ``positions`` restart at 0 per segment (RoPE),
    attention is same-segment causal, and ``kv_lens`` holds the TOTAL
    packed length.  With ``packed_last_idx``, the LM head runs only on the
    gathered last-token rows (logits [B, N, V]) — the padding rows' vocab
    matmul is the FLOP waste packing exists to eliminate.

    ``last_pos`` is the per-ROW version of the same gather for the fresh
    and chunked-continuation paths (one prompt per row): the LM head runs
    only on row b's token ``last_pos[b]`` and logits come back [B, 1, V].
    At a real-model vocab (Llama-3: 128,256) the full [B, S, V] head is
    ~2 TFLOPs + a ~1 GB f32 buffer per [1, 4096] prefill, all discarded
    but the last row (VERDICT r2 weak #2).

    RING prefill (``use_ring`` + ``mesh``): serving-side context
    parallelism (SURVEY.md §5.7 tier b) — fresh-prefill attention runs as
    ring attention with the sequence sharded over the ``sp`` axis, so a
    chunk longer than one chip's attention budget prefills with O(S/sp)
    attention memory per device; the SAME program scatters K/V into the
    page pool (cache-aware: what the training-only ring path could not
    do), so decode then proceeds against the pages as usual.  Pad keys are
    masked positionally (kv position pushed past every real query).
    """
    from lmrs_tpu.ops.paged_attention import (
        paged_decode_fused_sharded,
        paged_decode_multi_xla,
        paged_decode_pallas_fused,
        paged_decode_pallas_multi,
        paged_decode_xla,
        ragged_spans_pallas,
        ragged_spans_xla,
        scatter_kv_pages,
        scatter_kv_rows,
        whole_pages,
    )
    from lmrs_tpu.ops.quant import (kv_dequant, kv_quant, kv_quant_tokens,
                                    kv_scale_from)

    if cfg.kv_lora_rank:
        # latent attention: one latent pool and its own two attention forms
        # (models/latent.py); the engine refuses at start what is named here
        if (kv_scales is not None or spans is not None or multi_decode
                or segment_ids is not None or use_ring or mesh is not None):
            raise NotImplementedError(
                "latent cache: no int8 KV, span or packed program, "
                "speculative verify, ring prefill or mesh")
        from lmrs_tpu.models import latent

        return latent.forward_paged(
            params, cfg, tokens, positions, k_pages, page_tables, kv_lens,
            rope_max, use_ragged_kernel=use_ragged_kernel,
            window_prefill=window_prefill, use_flash=use_flash,
            interpret=interpret, last_pos=last_pos, token_valid=token_valid)

    if cfg.sliding_window:
        # window and full layers over a two-kind pool (models/windowed.py);
        # the engine refuses at start what is named here
        if (kv_scales is not None or spans is not None or multi_decode
                or segment_ids is not None or use_ring or mesh is not None):
            raise NotImplementedError(
                "window cache: no int8 KV, span or packed program, "
                "speculative verify, ring prefill or mesh")
        from lmrs_tpu.models import windowed

        return windowed.forward_paged(
            params, cfg, tokens, positions, k_pages, v_pages, page_tables,
            kv_lens, rope_max, window_slots=window_slots,
            use_ragged_kernel=use_ragged_kernel,
            window_prefill=window_prefill, use_flash=use_flash,
            interpret=interpret, last_pos=last_pos, token_valid=token_valid,
            scale_rows=scale_rows, decode_row_group=decode_row_group)

    if kv_scales is not None:
        # int8 KV: packed prefill composes (per-SEGMENT scales, r4 — each
        # segment owns its slot's scale row, so the two headline
        # optimizations no longer subtract from each other, VERDICT r3
        # item 3); ring stays gated off at config time (sp-sharded writes
        # vs per-slot scales)
        assert not use_ring, (
            "int8 KV pools are incompatible with ring (sp) prefill "
            "(scheduler raises at construction)")

    dt = _dtype(cfg)
    b, s = tokens.shape
    hd = cfg.hd
    ps = k_pages.shape[2]
    n_pool = k_pages.shape[0] // cfg.cache_layers  # logical pages per layer
    # (page-major pool [L*P, K, ps, hd]: pages are axis 0.  The round-3
    # relayout left this reading axis 1 — the kv-head count — which
    # collapsed every layer's global page ids onto the same few pages and
    # corrupted all paged generation; VERDICT r3.)
    x = params["embed"]["weight"][tokens]
    if cfg.embed_scale:
        x = (x.astype(jnp.float32) * math.sqrt(cfg.dim)).astype(dt)

    sin, cos = rope_table(rope_max, hd, cfg.rope_theta)
    is_decode = s == 1

    if spans is not None:
        # span mode: [1, Tp] flat tokens vs [B_rows, W] tables — the span
        # kernels do their own per-token page addressing
        page_idx = None
    elif token_pages is not None:
        page_idx = token_pages  # packed path: host-built per-token pages
    else:
        page_idx = jnp.take_along_axis(
            page_tables, jnp.clip(positions // ps, 0, page_tables.shape[1] - 1),
            axis=1,
        )  # [B, S] logical page per token
    # a prefill row's tokens: those below its length (a window chunk counts
    # from its first position).  ``positions`` cannot say: the scheduler
    # clamps them into the row's allocation
    is_prefill = ((window_prefill or not is_decode) and not multi_decode
                  and spans is None and segment_ids is None)
    valid = None
    if is_prefill:
        chunk_len = kv_lens - positions[:, 0] if window_prefill else kv_lens
        valid = jnp.arange(s)[None, :] < chunk_len[:, None]
        if token_pages is None:
            # the bucket's padding goes to the null page.  Clamped onto the
            # row's last slot it would land ON the last real token of a
            # prompt that fills its pages exactly (length == allocation)
            page_idx = jnp.where(valid, page_idx, 0)
    offsets = positions % ps
    # pages a row that this program writes whole: a fresh prefill's rows sit
    # at positions 0 .. S-1 (the scheduler's fresh-prefill contract), so
    # where S is whole pages the wave is B x S/ps of them.  Everything else
    # (a window that starts mid-page, packed rows, decode, a bucket of 64)
    # writes a row at a time.  Static at trace time, like the program kind
    n_whole = 0
    if is_prefill and not window_prefill and token_pages is None:
        n_whole = whole_pages(s, ps, page_tables.shape[1])

    def layer_fn(carry, xs):
        # The page pools ride the scan CARRY (not xs/ys) and the layer axis
        # is flattened into the page axis, so each layer scatters straight
        # into the full pool at its GLOBAL page ids.  Either a per-layer
        # stacked scan output or a slice/update round trip moves the whole
        # pool (or a whole layer slice) every decode step — measured linear
        # in pool size; this layout moves only the tokens written.
        # With int8 pools the per-(slot, kv head, channel) scales ride the
        # carry too (tiny): layer li reads/updates slice [li].
        if kv_scales is not None:
            x, kp_all, vp_all, ksc, vsc = carry
        else:
            x, kp_all, vp_all = carry  # pools: [L*P, K, ps, hd]
            ksc = vsc = None
        lp, li = xs  # layer params, CACHE layer (run_stack: pass * L + layer)
        g_page_idx = (None if page_idx is None
                      else li * n_pool + page_idx)  # [B, S] global page ids
        g_tables = li * n_pool + page_tables     # [B, W]
        h = rms_norm(x, lp["ln_attn"]["scale"], cfg.norm_eps)
        q, k, v = qkv_proj(lp, cfg, h)
        q = apply_rope(q, positions, sin, cos)
        k = apply_rope(k, positions, sin, cos)

        if spans is not None:
            # ragged span mode: every phase is a list of
            # (row, query-span) pairs — write + attention run in the ONE
            # span kernel (or its XLA twin).  kv_lens here is the context
            # BEFORE the dispatch: span token j of row r sits at absolute
            # position kv_lens[r] + j.
            span_starts, span_lens, row_flat = spans
            ss = None
            if kv_scales is not None:
                # per-row frozen scales ride the span descriptor (the
                # int8-KV x mixed unlock): a span whose base is 0 is its
                # prompt's FIRST tokens and owns its slot's scale row —
                # segment-max over its own tokens, the packed path's
                # stats exactly; every later span reuses (and clamps to)
                # the frozen scales, decode spans included.
                nb = span_starts.shape[0]
                segx = jnp.clip(row_flat, 0, nb)  # out-of-span -> dropped

                def span_scales(kv):
                    a = jnp.abs(kv[0].astype(jnp.float32))  # [Tp, K, hd]
                    m = jax.ops.segment_max(a, segx, num_segments=nb + 1)
                    return jnp.maximum(m[:nb] / 127.0, 1e-8)

                s_k, s_v = span_scales(k), span_scales(v)
                rows_i = (jnp.arange(nb, dtype=jnp.int32)
                          if scale_rows is None else scale_rows)
                ksc_l, vsc_l = ksc[li][rows_i], vsc[li][rows_i]
                own = ((kv_lens == 0) & (span_lens > 0))[:, None, None]
                s_k = jnp.where(own, s_k, ksc_l)
                s_v = jnp.where(own, s_v, vsc_l)
                ksc = ksc.at[li, rows_i].set(s_k)
                vsc = vsc.at[li, rows_i].set(s_v)
                ss = (s_k, s_v)
            if use_ragged_kernel and span_anc is None:
                attn, kp_all, vp_all = ragged_spans_pallas(
                    q[0], k[0], v[0], kp_all, vp_all, g_tables, kv_lens,
                    span_starts, span_lens, interpret=interpret,
                    max_pos=rope_max,
                    kscale=ss[0] if ss is not None else None,
                    vscale=ss[1] if ss is not None else None)
            else:
                attn, kp_all, vp_all = ragged_spans_xla(
                    q[0], k[0], v[0], kp_all, vp_all, g_tables, kv_lens,
                    span_starts, span_lens, row_flat,
                    max_pos=rope_max, kv_scales=ss, anc_masks=span_anc)
            if cfg.n_experts:
                # tokens outside every span (the bucket's padding) leave
                # the span attention non-finite, and a routed layer mixes
                # every token of a call in one product (0 x NaN = NaN in
                # every expert slot): make them zero.  Routed models only:
                # a dense FFN keeps rows apart, and the pass is not free
                in_span = row_flat < span_starts.shape[0]
                attn = jnp.where(in_span[:, None, None], attn, 0)
            return _finish_layer(lp, x, attn[None], kp_all, vp_all,
                                 ksc, vsc)

        row_scales = None  # (k_scale, v_scale) [B, K, hd] for THIS dispatch
        tok_scales = None  # packed: per-token (k, v) scales [B, S, K, hd]
        if kv_scales is not None:
            is_fresh = (not is_decode and not window_prefill
                        and not multi_decode)
            if segment_ids is not None:
                # PACKED fresh prefill: one [1, S] row holds many prompts —
                # each SEGMENT owns its slot's scale row, computed from its
                # own tokens only (identical stats to the same prompt
                # prefilled unpacked: max-abs over the same token set).
                # Pads (segment id -1) route to an out-of-range segment so
                # segment_max drops them; empty segments hit the 1e-8 floor
                # and their scale_rows point past the buffer (scatter drop).
                n_seg = scale_rows.shape[0]
                seg = segment_ids[0]
                segx = jnp.where(seg >= 0, seg, n_seg)

                def seg_scales(kv):
                    a = jnp.abs(kv[0].astype(jnp.float32))  # [S, K, hd]
                    m = jax.ops.segment_max(a, segx, num_segments=n_seg + 1)
                    return jnp.maximum(m[:n_seg] / 127.0, 1e-8)

                s_k, s_v = seg_scales(k), seg_scales(v)
                ksc = ksc.at[li, scale_rows].set(s_k)
                vsc = vsc.at[li, scale_rows].set(s_v)
                # per-token gather for the scatter's quantization (pad
                # tokens clamp to some segment's scales; they land on the
                # null page regardless)
                gi = jnp.clip(segx, 0, n_seg - 1)
                tok_scales = (s_k[gi][None], s_v[gi][None])
            elif is_fresh or window_prefill:
                # a prefill OWNS its slots' scales when it is the prompt's
                # FIRST tokens: one-dispatch fresh prefill always, a window
                # (chunked) dispatch only for rows whose chunk starts at
                # position 0 — later chunks reuse (and clamp to) the first
                # chunk's scales, since written pages can't be requantized
                s_k = kv_scale_from(k, valid)
                s_v = kv_scale_from(v, valid)
                rows_i = (jnp.arange(b, dtype=jnp.int32)
                          if scale_rows is None else scale_rows)
                ksc_l, vsc_l = ksc[li][rows_i], vsc[li][rows_i]
                if window_prefill:
                    own = (positions[:, 0] == 0)[:, None, None]
                    s_k = jnp.where(own, s_k, ksc_l)
                    s_v = jnp.where(own, s_v, vsc_l)
                # pad rows carry scale_rows >= Bs: scatter drops them
                ksc = ksc.at[li, rows_i].set(s_k)
                vsc = vsc.at[li, rows_i].set(s_v)
                row_scales = (s_k, s_v)
            else:
                ksc_l, vsc_l = ksc[li], vsc[li]
                if scale_rows is not None:
                    ksc_l, vsc_l = ksc_l[scale_rows], vsc_l[scale_rows]
                row_scales = (ksc_l, vsc_l)

        if multi_decode:
            # speculative verify: the S tokens sit at consecutive positions
            # kv_lens - S + j; K/V write and the per-token-causal attention
            # run in ONE ragged page walk (kernel) or one window gather
            # (XLA fallback) — never the full window_prefill gather per
            # layer that made round-2 speculation a 12x loss.  Write slots
            # derive from kv_lens, which callers pass UNCLAMPED (base must
            # be the true position); tokens overhanging rope_max are
            # neither written nor attended (max_pos cap).
            if use_ragged_kernel:
                ks_m = row_scales[0] if kv_scales is not None else None
                vs_m = row_scales[1] if kv_scales is not None else None
                attn, kp_all, vp_all = paged_decode_pallas_multi(
                    q, k, v, kp_all, vp_all, g_tables, kv_lens,
                    interpret=interpret, max_pos=rope_max,
                    kscale=ks_m, vscale=vs_m,
                    row_group=decode_row_group)
            else:
                attn, kp_all, vp_all = paged_decode_multi_xla(
                    q, k, v, kp_all, vp_all, g_tables, kv_lens,
                    max_pos=rope_max, kv_scales=row_scales)
            return _finish_layer(lp, x, attn, kp_all, vp_all, ksc, vsc)

        if is_decode and use_ragged_kernel:
            # write-fused ragged kernel: the current token's K/V lands in
            # its page by in-place DMA inside the kernel (pools are i/o
            # aliased), replacing the XLA scatter below — which was measured
            # copying the whole pool every decode step.  Under a tp mesh the
            # kernel runs per kv-head shard via shard_map (XLA cannot
            # auto-partition a pallas_call).  Int8 pools pass the dispatch
            # rows' scales; the kernel folds dequant into q/acc per head.
            ks_r = row_scales[0] if kv_scales is not None else None
            vs_r = row_scales[1] if kv_scales is not None else None
            if mesh is not None:
                attn, kp_all, vp_all = paged_decode_fused_sharded(
                    q[:, 0], k[:, 0], v[:, 0], kp_all, vp_all, g_tables,
                    kv_lens, mesh, interpret=interpret,
                    kscale=ks_r, vscale=vs_r, row_group=decode_row_group)
            else:
                attn, kp_all, vp_all = paged_decode_pallas_fused(
                    q[:, 0], k[:, 0], v[:, 0], kp_all, vp_all, g_tables,
                    kv_lens, interpret=interpret,
                    kscale=ks_r, vscale=vs_r, row_group=decode_row_group)
            attn_out = attn[:, None]  # [B, 1, H, hd]
            return _finish_layer(lp, x, attn_out, kp_all, vp_all, ksc, vsc)

        # write current K/V into the page-major pool [L*P, K, ps, hd].
        # Int8 pools store the quantized rows; attention below reads the
        # ORIGINAL k/v wherever the current tokens are the whole context
        # (fresh prefill), so only pool readers pay quantization error
        k_store, v_store = k, v
        if kv_scales is not None:
            if tok_scales is not None:  # packed: per-token segment scales
                k_store = kv_quant_tokens(k, tok_scales[0])
                v_store = kv_quant_tokens(v, tok_scales[1])
            else:
                k_store = kv_quant(k, row_scales[0])
                v_store = kv_quant(v, row_scales[1])
        if n_whole:
            # fresh prefill: positions [j*ps, (j+1)*ps) of row b ARE page
            # page_tables[b, j], so the wave goes in a whole page at a time
            # (scatter_kv_pages).  A column past a row's allocation is 0
            # (the null page); padding inside the allocation lies behind
            # kv_lens for every reader
            kp_all = scatter_kv_pages(kp_all, g_tables[:, :n_whole], k_store)
            vp_all = scatter_kv_pages(vp_all, g_tables[:, :n_whole], v_store)
        else:
            # a row at a time, at [g_page_idx[b,s], :, offsets[b,s]]
            # (updates are [B, S, K, hd], the K/V's own layout;
            # scatter_kv_rows says why it is spelled the way it is)
            kp_all = scatter_kv_rows(kp_all, g_page_idx, offsets, k_store)
            vp_all = scatter_kv_rows(vp_all, g_page_idx, offsets, v_store)

        if is_decode:
            attn = paged_decode_xla(q[:, 0], kp_all, vp_all, g_tables, kv_lens,
                                    kv_scales=row_scales)
            attn_out = attn[:, None]  # [B, 1, H, hd]
        elif segment_ids is not None:
            # packed fresh prefill: same-segment causal attention over the
            # concatenated prompts (current tokens ARE the whole context)
            if use_flash and _use_flash_prefill(s, hd, interpret):
                from lmrs_tpu.ops.flash_attention import (
                    flash_attention, flash_attention_sharded)

                if mesh is not None:
                    attn_out = flash_attention_sharded(
                        q, k, v, kv_lens, mesh, interpret=interpret,
                        segment_ids=segment_ids)
                else:
                    attn_out = flash_attention(q, k, v, kv_lens,
                                               interpret=interpret,
                                               segment_ids=segment_ids)
            else:
                from lmrs_tpu.ops.attention import packed_attention

                attn_out = packed_attention(q, k, v, segment_ids, kv_lens)
        elif window_prefill:
            # continuation prefill: attend the page window (self K/V included
            # — this chunk was scattered into its pages above)
            w = page_tables.shape[1]
            k_win = kp_all[g_tables].transpose(0, 1, 3, 2, 4).reshape(
                b, w * ps, cfg.n_kv_heads, hd)
            v_win = vp_all[g_tables].transpose(0, 1, 3, 2, 4).reshape(
                b, w * ps, cfg.n_kv_heads, hd)
            if kv_scales is not None:
                k_win = kv_dequant(k_win, row_scales[0], q.dtype)
                v_win = kv_dequant(v_win, row_scales[1], q.dtype)
            attn_out = attention(q, k_win, v_win, positions, kv_lens)
        elif use_ring and mesh is not None:
            # serving CP: ring attention over the sp-sharded sequence; pad
            # keys get a position past every real query (ring attention has
            # no kv_length mask, so masking is purely positional)
            from lmrs_tpu.parallel.ring_attention import ring_attention_sharded

            idx = jnp.arange(s)[None, :]
            kvp = jnp.where(idx < kv_lens[:, None], positions, jnp.int32(1 << 30))
            attn_out = ring_attention_sharded(q, k, v, positions, mesh,
                                              kv_pos=kvp)
        else:
            # fresh prefill: current tokens ARE the whole context.  Row i's
            # position is i (scheduler fresh-prefill contract), which is
            # exactly the flash kernel's implicit layout — use it on TPU for
            # long chunks; XLA reference elsewhere.
            if use_flash and _use_flash_prefill(s, hd, interpret):
                from lmrs_tpu.ops.flash_attention import (
                    flash_attention, flash_attention_sharded)

                if mesh is not None:
                    attn_out = flash_attention_sharded(
                        q, k, v, kv_lens, mesh, interpret=interpret)
                else:
                    attn_out = flash_attention(q, k, v, kv_lens,
                                               interpret=interpret)
            else:
                attn_out = attention(q, k, v, positions, kv_lens)
        return _finish_layer(lp, x, attn_out, kp_all, vp_all, ksc, vsc)

    def _finish_layer(lp, x, attn_out, kp_all, vp_all, ksc, vsc):
        x = x + sublayer_out(lp, cfg, "ln_attn_out",
                             out_proj(lp, cfg, attn_out))
        h = rms_norm(x, lp["ln_mlp"]["scale"], cfg.norm_eps)
        ff, _ = ffn_block(lp, cfg, h)
        x = x + sublayer_out(lp, cfg, "ln_mlp_out", ff)
        if kv_scales is not None:
            return (x, kp_all, vp_all, ksc, vsc), None
        return (x, kp_all, vp_all), None

    init = ((x, k_pages, v_pages) if kv_scales is None
            else (x, k_pages, v_pages, kv_scales[0], kv_scales[1]))
    carry_out = run_stack(params, cfg, init, layer_fn)
    if kv_scales is None:
        x, new_k, new_v = carry_out
        new_scales = None
    else:
        x, new_k, new_v, new_ksc, new_vsc = carry_out
        new_scales = (new_ksc, new_vsc)
    if packed_last_idx is not None:
        # LM head only where tokens are sampled: [B, S, D] -> [B, N, D]
        x = x[:, packed_last_idx]
    elif last_pos is not None:
        # per-row gather: [B, S, D] -> [B, 1, D]
        x = jnp.take_along_axis(
            x, jnp.clip(last_pos, 0, s - 1)[:, None, None], axis=1)
    logits = lm_head(params, cfg, x)
    if kv_scales is not None:
        return logits, new_k, new_v, new_scales
    return logits, new_k, new_v
