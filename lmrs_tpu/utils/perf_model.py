"""Roofline accounting: FLOP / byte counts + chip peaks for MFU and
HBM-bandwidth utilization reporting (obs/perf.py, obs/ledger.py).

The reference publishes no perf model at all (its compute is a vendor API);
these counts are the standard decoder-transformer roofline: dense-matmul
FLOPs dominate prefill (MFU vs the MXU peak), weight+KV bytes dominate
decode (utilization vs the HBM peak).  FLOPs come from the configuration;
a decode step's bytes from the tree and pool the engine holds
(``StepBytes``).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

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


# a routed layer's held experts (ops/moe.routed_experts): read once a step
# for each expert a row chose, not with the layer
_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


@dataclass(frozen=True)
class StepBytes:
    """What a decode step has to read from HBM, counted once at engine start
    from what the engine holds: the weight tree and the KV pool on the
    device, not the configuration.  So int8 weights count with their scales,
    a looped stack's shared layers once a pass, a latent row, a window
    layer's ring, each as stored.  ``block`` prices a decode dispatch before
    its experts; ``expert`` is added for each held expert a step touched
    once the routed layers' counts are back (obs/anatomy.note_moe).  A lower
    bound: positions rather than pages, the rows live at the block's start,
    an expert that several rows chose once, and the int8 pages' scales (one
    per slot, head and channel, not per position) left out.  The embedding
    table is left out too (a step gathers a row of it) unless the head is
    tied to it."""

    layer: float        # one layer application outside the routed experts
    expert: float       # one held expert: its w_gate, w_up and w_down
    head: int           # the head (the embedding where tied), the final norm
    position: float     # one position of one cache layer: K and V, or a latent row
    full_layers: int    # cache layers that attend every position
    window_layers: int  # cache layers that attend the last ``window`` only
    window: int
    max_len: int

    @classmethod
    def held(cls, cfg: ModelConfig, params, k_pages, v_pages) -> "StepBytes":
        """From the engine's tree and its pool ``[pages, heads, page, width]``
        (``v_pages`` None for a latent pool): arrays or their shapes."""
        from lmrs_tpu.ops.quant import quantized_bytes as _nbytes

        rest = {k: v for k, v in params.items()
                if k not in ("embed", "lm_head", "final_norm")}
        head = (_nbytes(params.get("lm_head") or params["embed"])
                + _nbytes(params.get("final_norm")))
        experts = 0
        if cfg.n_routed_experts:
            moe = params["layers"]["moe"]
            experts = sum(_nbytes(moe[k]) for k in _EXPERT_LEAVES)
        positions = k_pages.shape[0] * k_pages.shape[2]
        return cls(
            layer=(_nbytes(rest) - experts) / cfg.n_layers,
            expert=experts / max(cfg.n_routed_layers * cfg.experts_held, 1),
            head=head,
            position=(_nbytes(k_pages) + _nbytes(v_pages)) / positions,
            full_layers=cfg.cache_layers - cfg.n_window_layers,
            window_layers=cfg.n_window_layers, window=cfg.sliding_window,
            max_len=cfg.max_seq_len)

    def block(self, lens, steps: int, layer_passes: int,
              queries: float = 1.0) -> int:
        """A decode dispatch of ``steps`` steps over live rows at ``lens``
        positions: its ``layer_passes`` layer applications, the head once a
        step, and at step t a row's ``len + t + 1`` positions (at most
        ``max_len``) in each full layer and at most ``window`` of them in
        each window layer (``scheduler._window_counts``' arithmetic, in
        positions); ``queries`` a row walks its positions a step (a span
        step's verify tokens)."""
        n = np.minimum(np.asarray(lens, np.int64)[:, None]
                       + np.arange(1, steps + 1)[None, :], self.max_len)
        pos = int(n.sum()) * self.full_layers
        if self.window_layers:
            pos += int(np.minimum(n, self.window).sum()) * self.window_layers
        return int(layer_passes * self.layer + steps * self.head
                   + queries * pos * self.position)
