"""Attention: XLA reference path with GQA, causal masking, KV-cache decode.

This is the always-correct baseline the Pallas kernels (ops/flash_attention.py,
ops/paged_attention.py) are validated against, and the fallback on non-TPU
platforms.  Softmax statistics in f32; matmuls in the input dtype (bf16 on
TPU) so they land on the MXU.
"""

from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e30


def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """Expand KV heads for grouped-query attention: [B,S,K,hd] -> [B,S,K*rep,hd]."""
    if n_rep == 1:
        return k
    b, s, kh, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kh, n_rep, hd)).reshape(
        b, s, kh * n_rep, hd
    )


def attention(
    q: jnp.ndarray,  # [B, Sq, H, hd]
    k: jnp.ndarray,  # [B, Skv, K, hd]
    v: jnp.ndarray,  # [B, Skv, K, hd]
    q_positions: jnp.ndarray,  # [B, Sq] absolute position of each query
    kv_length: jnp.ndarray | None = None,  # [B] valid KV prefix length
    logit_softcap: float | None = None,
    scale: float | None = None,  # softmax scale; None: hd ** -0.5
    window=None,  # int or traced int32 scalar: positions back a query sees
                  # (itself included); 0 or None: all of them
) -> jnp.ndarray:
    """Causal attention over a (possibly padded) KV buffer.  ``v`` may be
    narrower than ``q`` and ``k`` (latent attention): the output takes
    ``v``'s width.

    Masking rule: query at absolute position p attends KV slots [0, p], and
    only slots < kv_length are valid; with ``window`` w > 0, only slots j
    with p - j < w.  Works for both prefill (Sq == Skv, positions 0..S-1)
    and single-token decode (Sq == 1 against the cache).
    """
    b, sq, h, hd = q.shape
    kh = k.shape[2]
    k = _repeat_kv(k, h // kh)
    v = _repeat_kv(v, h // kh)

    if scale is None:
        scale = hd ** -0.5
    # [B, H, Sq, Skv]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if logit_softcap is not None:  # Gemma-2 style softcap
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)

    skv = k.shape[1]
    kv_pos = jnp.arange(skv)[None, None, None, :]  # [1,1,1,Skv]
    causal = kv_pos <= q_positions[:, None, :, None]  # [B,1,Sq,Skv]
    mask = causal
    if kv_length is not None:
        valid = kv_pos < kv_length[:, None, None, None]
        mask = jnp.logical_and(mask, valid)
    if window is not None:
        behind = q_positions[:, None, :, None] - kv_pos
        mask = jnp.logical_and(mask, (window <= 0) | (behind < window))
    logits = jnp.where(mask, logits, NEG_INF)

    probs = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


def packed_attention(
    q: jnp.ndarray,  # [B, S, H, hd]
    k: jnp.ndarray,  # [B, S, K, hd]
    v: jnp.ndarray,  # [B, S, K, hd]
    segment_ids: jnp.ndarray,  # [B, S] per-token segment (pad: any id < 0)
    length: jnp.ndarray | None = None,  # [B] total valid (packed) tokens
    logit_softcap: float | None = None,
) -> jnp.ndarray:
    """Packed-prompt self-attention (XLA reference / fallback): several
    prompts concatenated into one row, masked to same-segment pairs with
    causality on the global row index (segments are contiguous, so this is
    per-segment causal attention).  The correctness contract for the flash
    kernel's ``segment_ids`` path (tests/test_kernels.py)."""
    b, s, h, hd = q.shape
    kh = k.shape[2]
    k = _repeat_kv(k, h // kh)
    v = _repeat_kv(v, h // kh)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * hd**-0.5
    if logit_softcap is not None:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    idx = jnp.arange(s)
    causal = idx[None, :] <= idx[:, None]  # [Sq, Skv]: k at or before q
    same_seg = segment_ids[:, :, None] == segment_ids[:, None, :]  # [B,Sq,Skv]
    mask = jnp.logical_and(causal[None], same_seg)
    if length is not None:
        mask = jnp.logical_and(mask, (idx[None, None, :] < length[:, None, None]))
    logits = jnp.where(mask[:, None], logits, NEG_INF)
    probs = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
