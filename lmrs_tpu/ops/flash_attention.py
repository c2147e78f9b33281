"""Pallas flash-attention (prefill) kernel for TPU.

Online-softmax tiling (flash-attention v2 schedule): grid over
(batch, q_head, q_block, kv_block) with f32 running max / sum / accumulator
in VMEM scratch; KV blocks stream through VMEM, so memory is O(blocks) not
O(S²) and the matmuls are MXU-shaped.  GQA is handled in the index maps — a
query head reads its kv-head's blocks directly, no materialized repeat.

Causal + ragged masking: blocks entirely above the diagonal are skipped
(predicated off), the diagonal block is masked elementwise, and a per-row
valid-length (`lengths`, from SMEM) masks padded KV — the kernel equivalent
of ops.attention's (causal & kv_length) rule.

A sliding window (``window`` > 0: query i sees keys j with 0 <= i - j <
window) runs a BANDED grid: a query block visits only the kv blocks its
window reaches (``window_band`` of them, at tiles of ``window_block``), so
the blocks wholly behind the window cost neither a grid step nor a DMA.

Correctness contract: must match ops.attention.attention() to f32 tolerance —
see tests/test_kernels.py.  Interpret mode is explicit (``interpret=True``,
the CPU test path); there is no automatic fallback off-TPU — without it
the kernel lowers through Mosaic or raises.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lmrs_tpu.utils.env import env_int
from lmrs_tpu.utils.jax_compat import shard_map, tpu_compiler_params

NEG_INF = -1e30

# Default q/kv tile: bigger tiles = fewer grid programs = less per-program
# overhead, up to the VMEM ceiling (2048 tiles fail to compile at hd=128).
# Measured r2: 256 -> 512 was +3.6 MFU points; r4 interleaved sweep
# (min-of-4-rounds, RTT-amortized chains): 512 -> 1024 is a further 1.5x
# on the kernel at the bench packed shape (S=4096: 1.79 -> 1.20 ms, 19.5 ->
# 29.1% MFU; S=2048: 1.8x).  The wrapper clamps blocks to the sequence, so
# small buckets degrade gracefully.  Env knob for A/B sweeps.
_DEFAULT_BLOCK = env_int("LMRS_FLASH_BLOCK", 1024, lo=128)


def window_block(window: int, block: int = _DEFAULT_BLOCK) -> int:
    """The q and kv tile of a windowed call: twice the window rounded up to
    a power of two (a band of two tiles then holds a query tile's whole
    window, and at most half of what it multiplies lies outside it), at
    least 128 and at most the full kernel's tile."""
    return min(block, max(128, 2 << max(window - 1, 0).bit_length()))


def window_band(window: int, block: int) -> int:
    """kv tiles of ``block`` that a query tile of ``block`` reaches under
    ``window``: its own and those behind it that hold a position within
    ``window - 1`` of its first row."""
    return -(-(window - 1) // block) + 1


def _flash_kernel(
    lengths_ref,  # SMEM [B] valid kv length per batch row (unblocked)
    q_ref,        # VMEM [1, 1, QB, hd]
    k_ref,        # VMEM [1, 1, KB, hd]
    v_ref,        # VMEM [1, 1, KB, hd]
    *args,        # [sq_ref (1, QB), sk_ref (1, KB) when has_segs;]
                  # o_ref, m_scr, l_scr, acc_scr
    q_block: int,
    kv_block: int,
    sm_scale: float,
    skip_padded_q: bool,
    has_segs: bool = False,
    window: int = 0,  # > 0: the banded grid (module docstring)
    band: int = 0,    # kv tiles the band holds (clamped to the sequence's)
):
    if has_segs:
        # packed-prompt prefill: per-token segment ids; a key is visible to
        # a query only within the same segment (cross-segment attention is
        # the packing bug this mask exists to prevent)
        sq_ref, sk_ref, o_ref, m_scr, l_scr, acc_scr = args
    else:
        sq_ref = sk_ref = None
        o_ref, m_scr, l_scr, acc_scr = args
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = qi * q_block
    # banded: step ki of query tile qi is kv tile qi - (band - 1) + ki
    # (tiles are square); a tile before the sequence's first is off
    kb = qi - (band - 1) + ki if window else ki
    k_start = kb * kv_block
    length = lengths_ref[pl.program_id(0)]

    # A (q, kv) block pair is live iff some VALID query row can see it:
    # the kv block starts at or before the last query position, intersects
    # the valid prefix, and the q block contains at least one valid row —
    # padded q blocks (prompt bucketed up past its length) would otherwise
    # re-compute attention over the whole valid prefix for garbage rows
    # (~43% of the MXU work for a 2.3k prompt in the 4096 bucket).  Skipped
    # blocks still init/finalize, so their output rows are well-defined
    # zeros, and valid rows never attend them (causal + length mask).
    live = jnp.logical_and(k_start <= q_start + q_block - 1, k_start < length)
    if skip_padded_q:
        live = jnp.logical_and(live, q_start < length)
    if window:
        live = jnp.logical_and(live, kb >= 0)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [QB, hd]
        k = k_ref[0, 0].astype(jnp.float32)  # [KB, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [QB, KB]

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 1)
        mask = jnp.logical_and(k_pos <= q_pos, k_pos < length)
        if window:
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
        if has_segs:
            mask = jnp.logical_and(mask, sq_ref[0][:, None] == sk_ref[0][None, :])
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]                      # [QB, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # [QB, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)            # [QB, 1]
        p = jnp.exp(s - m_new)                     # [QB, KB]
        # fully-masked rows: m_new == NEG_INF -> p == exp(0) == 1; zero them
        p = jnp.where(m_new > NEG_INF * 0.5, p, 0.0)

        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)        # [KB, hd]
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("q_block", "kv_block", "interpret",
                              "skip_padded_q", "sm_scale", "window")
)
def flash_attention(
    q: jnp.ndarray,          # [B, Sq, H, hd]
    k: jnp.ndarray,          # [B, Skv, K, hd]
    v: jnp.ndarray,          # [B, Skv, K, hd_v]
    lengths: jnp.ndarray | None = None,  # [B] valid kv length
    q_block: int = _DEFAULT_BLOCK,
    kv_block: int = _DEFAULT_BLOCK,
    interpret: bool = False,
    skip_padded_q: bool = True,
    segment_ids: jnp.ndarray | None = None,  # [B, S] packed-prompt segments
    sm_scale: float | None = None,  # softmax scale; None: hd ** -0.5
    window: int = 0,  # > 0: query i sees keys j with 0 <= i - j < window
) -> jnp.ndarray:
    """Causal flash attention over fresh (position-0-based) sequences.

    Requires Sq == Skv (self-attention prefill / training).  Returns
    [B, Sq, H, hd_v] in q.dtype; ``hd_v`` is ``v``'s own head width, which
    latent attention makes narrower than the queries' and keys'.  With ``skip_padded_q`` (default), rows at
    positions >= lengths[b] are exactly zero — their blocks are predicated
    off entirely (a bucketed prompt would otherwise burn MXU time computing
    attention for garbage rows); pass False to compute them anyway.

    ``segment_ids`` enables packed-prompt prefill (several prompts
    concatenated into one row): attention is additionally masked to
    same-segment pairs, so causal masking on the global row index becomes
    per-segment causality (segments are contiguous).

    ``window`` > 0 runs the banded grid at square tiles of
    ``window_block`` (module docstring); not with ``segment_ids``.
    """
    b, sq, h, hd = q.shape
    skv, kh, hd_v = k.shape[1], k.shape[2], v.shape[3]
    assert sq == skv, "flash_attention is for self-attention prefill"
    n_rep = h // kh
    if lengths is None:
        lengths = jnp.full((b,), sq, jnp.int32)

    if window:
        assert segment_ids is None, "no windowed packed prefill"
        q_block = kv_block = window_block(window, min(q_block, kv_block))
    q_block = min(q_block, sq)
    kv_block = min(kv_block, skv)
    pad_q = (-sq) % q_block
    pad_kv = (-skv) % kv_block
    if pad_q or pad_kv:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        if segment_ids is not None:
            # pad tokens get segment -1: matches nothing valid (and the
            # length mask already excludes them as keys)
            segment_ids = jnp.pad(segment_ids, ((0, 0), (0, pad_q)),
                                  constant_values=-1)
    sq_p, skv_p = q.shape[1], k.shape[1]

    # head-major layout for blocking
    qt = q.transpose(0, 2, 1, 3)  # [B, H, S, hd]
    kt = k.transpose(0, 2, 1, 3)  # [B, K, S, hd]
    vt = v.transpose(0, 2, 1, 3)

    has_segs = segment_ids is not None
    n_kv = skv_p // kv_block
    band = min(window_band(window, kv_block), n_kv) if window else 0
    grid = (b, h, sq_p // q_block, band or n_kv)
    kernel = functools.partial(
        _flash_kernel, q_block=q_block, kv_block=kv_block,
        sm_scale=hd ** -0.5 if sm_scale is None else sm_scale,
        skip_padded_q=skip_padded_q, has_segs=has_segs,
        window=window, band=band,
    )

    def kv_tile(qi, ki):  # the kv tile of grid step (qi, ki)
        return jnp.maximum(qi - (band - 1) + ki, 0) if window else ki

    in_specs = [
        # whole [B] array in SMEM (rank-1 blocking is restricted on real
        # TPU lowering); the kernel indexes it by program_id(0)
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, 1, q_block, hd),
                     lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        pl.BlockSpec((1, 1, kv_block, hd),
                     lambda bi, hi, qi, ki: (bi, hi // n_rep,
                                             kv_tile(qi, ki), 0)),
        pl.BlockSpec((1, 1, kv_block, hd_v),
                     lambda bi, hi, qi, ki: (bi, hi // n_rep,
                                             kv_tile(qi, ki), 0)),
    ]
    operands = [lengths.astype(jnp.int32), qt, kt, vt]
    if has_segs:
        segs = segment_ids.astype(jnp.int32)
        in_specs += [
            pl.BlockSpec((1, q_block), lambda bi, hi, qi, ki: (bi, qi)),
            pl.BlockSpec((1, kv_block), lambda bi, hi, qi, ki: (bi, ki)),
        ]
        operands += [segs, segs]
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, q_block, hd_v),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, hd_v), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, 128), jnp.float32),
            pltpu.VMEM((q_block, 128), jnp.float32),
            pltpu.VMEM((q_block, hd_v), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)

    out = out.transpose(0, 2, 1, 3)  # back to [B, S, H, hd]
    if pad_q:
        out = out[:, :sq]
    return out


def flash_attention_sharded(
    q: jnp.ndarray,        # [B, Sq, H, hd] (H sharded over tp)
    k: jnp.ndarray,        # [B, Skv, K, hd] (K sharded over tp)
    v: jnp.ndarray,        # [B, Skv, K, hd]
    lengths: jnp.ndarray,  # [B] replicated
    mesh,
    interpret: bool = False,
    segment_ids: jnp.ndarray | None = None,  # [B, S] replicated
) -> jnp.ndarray:
    """Flash prefill under a tensor-parallel mesh: ``shard_map`` over the
    ``tp`` head axis (a pallas_call cannot be auto-partitioned by XLA).
    Attention is independent per head and Q heads shard together with their
    kv head (GQA grouping stays shard-local), so each shard runs the
    unmodified kernel on its local heads."""
    from jax.sharding import PartitionSpec as P

    head4 = P(None, None, "tp", None)
    if segment_ids is None:
        fn = shard_map(
            functools.partial(flash_attention, interpret=interpret),
            mesh=mesh,
            in_specs=(head4, head4, head4, P(None)),
            out_specs=head4,
            check_vma=False,
        )
        return fn(q, k, v, lengths)
    fn = shard_map(
        lambda q_, k_, v_, l_, s_: flash_attention(
            q_, k_, v_, l_, interpret=interpret, segment_ids=s_),
        mesh=mesh,
        in_specs=(head4, head4, head4, P(None), P(None, None)),
        out_specs=head4,
        check_vma=False,
    )
    return fn(q, k, v, lengths, segment_ids)
