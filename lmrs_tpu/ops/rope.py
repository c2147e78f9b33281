"""Rotary position embeddings.

Precomputed sin/cos tables (static shapes, computed once per compile) applied
to query/key heads.  Table layout [S, head_dim/2] keeps the apply step a pure
elementwise op that XLA fuses into the attention projections."""

from __future__ import annotations

import math

import jax.numpy as jnp


def rope_table(max_seq_len: int, head_dim: int, theta: float = 10000.0,
               inv_freq=None):
    """Returns (sin, cos) tables of shape [max_seq_len, head_dim // 2].
    ``inv_freq`` [head_dim // 2] replaces the plain ``theta ** (-i / half)``
    frequencies (``yarn_inv_freq``)."""
    half = head_dim // 2
    if inv_freq is None:
        freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    angles = jnp.outer(t, freqs)  # [S, half]
    return jnp.sin(angles), jnp.cos(angles)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature term: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  orig_max_pos: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0):
    """YaRN inverse frequencies [head_dim // 2] (DeepSeek-V3's
    ``DeepseekV3YarnRotaryEmbedding``): dimension i turns ``orig_max_pos *
    inv_freq_i / 2 pi`` times over the trained window; dimensions that turn
    more than ``beta_fast`` times keep their frequency, those under
    ``beta_slow`` are interpolated by ``factor``, and a linear ramp between
    the two correction dimensions blends the rest, at every position."""
    half = head_dim // 2
    plain = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if factor <= 1.0:
        return plain

    def correction_dim(rotations: float) -> float:
        return (head_dim * math.log(orig_max_pos / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    span = (high - low) or 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / span, 0, 1)
    return plain / factor * ramp + plain * (1.0 - ramp)


def apply_rope(
    x: jnp.ndarray,  # [..., S, n_heads, head_dim]
    positions: jnp.ndarray,  # [..., S] absolute positions
    sin: jnp.ndarray,
    cos: jnp.ndarray,
) -> jnp.ndarray:
    """Rotate pairs (x[..2i], x[..2i+1]) by the position angle.

    Uses the "split halves" convention (first half paired with second half),
    matching Llama's reference formulation.
    """
    half = x.shape[-1] // 2
    s = sin[positions]  # [..., S, half]
    c = cos[positions]
    s = s[..., None, :]  # broadcast over heads: [..., S, 1, half]
    c = c[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)
