"""Roofline accounting: FLOP / byte counts + chip peaks for MFU and
HBM-bandwidth utilization reporting (obs/perf.py, obs/ledger.py).

The reference publishes no perf model at all (its compute is a vendor API);
these counts are the standard decoder-transformer roofline: dense-matmul
FLOPs dominate prefill (MFU vs the MXU peak), weight+KV bytes dominate
decode (utilization vs the HBM peak).
"""

from __future__ import annotations

from dataclasses import dataclass

from lmrs_tpu.config import ModelConfig

# Public peak numbers per chip generation (bf16 TFLOP/s, HBM GB/s; Google
# Cloud TPU documentation), matched as substrings of
# jax.devices()[0].device_kind.  A device that matches none has NO peaks:
# it is never handed another chip's.
_CHIP_PEAKS = {
    "v5 lite": (197e12, 819e9),   # v5e reports "TPU v5 lite"
    "v5e": (197e12, 819e9),
    "v5p": (459e12, 2765e9),
    "v4": (275e12, 1228e9),
    "v6 lite": (918e12, 1640e9),  # Trillium
    "v6e": (918e12, 1640e9),
}


def pow2_bucket(n: int, lo: int) -> int:
    """Smallest power of two >= max(n, lo) — THE compile-key bucketing of
    the ragged-span family (query-token buckets, page windows).  One
    shared definition: the scheduler (via ops/paged_attention), the mock
    engine, and the bucket-economics accounting (obs/anatomy.py) must
    agree on bucket edges or the per-bucket padding-waste numbers
    attribute to the wrong key.  Lives here (jax-free) so the mock's
    import closure stays deviceless."""
    b = lo
    while b < n:
        b *= 2
    return b


@dataclass
class ChipSpec:
    kind: str
    peak_flops: float  # bf16 FLOP/s
    peak_hbm_bw: float  # bytes/s


def chip_spec() -> ChipSpec | None:
    """Peak specs of the default device, or None when its ``device_kind``
    is not in the table (the CPU backend in tests): callers then record
    no utilisation instead of one against assumed peaks."""
    import jax

    kind = jax.devices()[0].device_kind
    low = kind.lower()
    for key, (fl, bw) in _CHIP_PEAKS.items():
        if key in low:
            return ChipSpec(kind, fl, bw)
    return None


def matmul_params(cfg: ModelConfig) -> int:
    """Parameters that participate in per-token matmuls (embedding lookup
    excluded; the LM head included — tied or not, it is a [D, V] matmul).
    A looped stack (``cfg.n_loops``) multiplies, and a decode step reads,
    every layer's weights once a pass: its layers count ``n_loops`` times
    (``stored_matmul_params`` counts what the tree holds)."""
    if cfg.kv_lora_rank:
        return _latent_matmul_params(cfg)
    d, hd = cfg.dim, cfg.hd
    per_layer = (
        d * cfg.n_heads * hd          # wq
        + 2 * d * cfg.n_kv_heads * hd  # wk, wv
        + cfg.n_heads * hd * d        # wo
    )
    if cfg.n_routed_experts:  # GQA attention over routed layers
        return _routed_matmul_params(cfg, per_layer)
    if cfg.n_experts:
        # only the activated experts' FFN weights do per-token work
        per_layer += 3 * d * cfg.hidden_dim * cfg.n_experts_per_token
    else:
        per_layer += 3 * d * cfg.hidden_dim
    return cfg.cache_layers * per_layer + d * cfg.vocab_size


def stored_matmul_params(cfg: ModelConfig) -> int:
    """``matmul_params`` as the weight tree holds them: each layer once,
    however often the stack runs."""
    head = cfg.dim * cfg.vocab_size
    return (matmul_params(cfg) - head) // cfg.n_loops + head


def _latent_matmul_params(cfg: ModelConfig) -> int:
    """``matmul_params`` of a latent-attention model (models/latent.py):
    what one token multiplies HERE: the attention projections, a dense
    layer's FFN, and in a routed layer the router, the shared expert and
    its expected pairs on the experts this engine holds."""
    d, h = cfg.dim, cfg.n_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    attn = (d * cfg.q_lora_rank + cfg.q_lora_rank * h * qk
            + d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
            + cfg.kv_lora_rank * h * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + h * cfg.v_head_dim * d)
    return _routed_matmul_params(cfg, attn)


def _routed_matmul_params(cfg: ModelConfig, attn: int) -> int:
    """A model of ``n_dense_layers`` dense layers and then routed ones
    (models/blocks.py), ``attn`` a layer's attention projections."""
    d = cfg.dim
    n_routed = cfg.n_routed_layers
    dense = 3 * d * (cfg.dense_hidden_dim or cfg.hidden_dim)
    expert = 3 * d * cfg.hidden_dim
    routed = (d * cfg.n_routed_experts + cfg.n_shared_experts * expert
              + cfg.n_experts_per_token * cfg.experts_held
              / max(cfg.n_routed_experts, 1) * expert)
    return int(cfg.n_layers * attn + (cfg.n_layers - n_routed) * dense
               + n_routed * routed + d * cfg.vocab_size)


def _attn_width(cfg: ModelConfig) -> float:
    """Per-head multiply-adds a (query, key) pair costs, over 2: QK^T width
    plus PV width, halved (the dense model's ``hd``)."""
    if cfg.kv_lora_rank:
        return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
                + cfg.v_head_dim) / 2.0
    return cfg.hd


def prefill_flops(cfg: ModelConfig, n_tokens: int,
                  head_tokens: int | None = None,
                  kv_start: int = 0) -> float:
    """Forward FLOPs for a causal prefill of ``n_tokens``.

    Dense matmuls: 2 FLOPs per param per token.  Causal attention:
    2 * S^2 * hd * H per layer (QK^T + PV, averaged S/2 keys per query,
    2 FLOPs per MAC).  ``head_tokens`` restricts the LM-head matmul to the
    sampled rows (the packed-prefill gather, forward_paged).  ``kv_start``
    models a WINDOWED continuation chunk (chunked prefill): the chunk's
    tokens additionally attend ``kv_start`` earlier cached KV tokens —
    kv_start=0 reduces exactly to the fresh causal count."""
    d = cfg.dim
    body = matmul_params(cfg) - d * cfg.vocab_size
    fl = 2.0 * body * n_tokens
    fl += 2.0 * (head_tokens if head_tokens is not None else n_tokens) \
        * d * cfg.vocab_size
    # twice the (query, key) pairs a layer: the causal triangle on a full
    # layer, at most ``window`` keys a query on a window layer
    pairs2 = float(n_tokens) ** 2 + 2.0 * kv_start * n_tokens
    n_win = cfg.n_window_layers
    fl += 2.0 * ((cfg.cache_layers - n_win) * pairs2
                 + n_win * min(pairs2, 2.0 * cfg.sliding_window * n_tokens)) \
        * _attn_width(cfg) * cfg.n_heads
    return fl


def weight_bytes(cfg: ModelConfig, quantized: bool = False) -> float:
    """Bytes of MATMUL weights a decode step streams from HBM (all of
    them, once a pass of the stack — one read serves the whole batch).
    The embedding lookup gathers only B rows per step and is excluded
    (negligible; counting the full table would overstate untied models'
    bandwidth)."""
    import jax.numpy as jnp

    itemsize = 1 if quantized else jnp.dtype(cfg.dtype).itemsize
    return matmul_params(cfg) * itemsize


def kv_bytes_per_token(cfg: ModelConfig) -> float:
    """KV-cache bytes per cached token (K + V, all cache layers, one per
    (pass, layer) of a looped stack, all kv heads)."""
    import jax.numpy as jnp

    if cfg.kv_lora_rank:  # one latent row a layer, as stored
        return (cfg.n_layers * cfg.latent_width
                * jnp.dtype(cfg.dtype).itemsize)
    # a window layer's ring holds and reads ``sliding_window`` positions
    # whatever the context: left out of what a cached token costs
    return (2 * (cfg.cache_layers - cfg.n_window_layers) * cfg.n_kv_heads
            * cfg.hd
            * jnp.dtype(cfg.dtype).itemsize)


def decode_step_bytes(cfg: ModelConfig, total_live_tokens: int,
                      quantized: bool = False,
                      kv_quantized: bool = False) -> float:
    """HBM bytes one batched decode step moves: every weight once (batch
    amortized — one read serves all rows) + every live KV token's K and V
    (halved when the pages are int8)."""
    kv = kv_bytes_per_token(cfg) * total_live_tokens
    if kv_quantized:
        kv /= 2
    return weight_bytes(cfg, quantized) + kv
