"""Decode attention over a latent page pool (MLA, absorbed form).

The pool holds one row a token a layer, ``[c_kv | k_rope | 0]``: the normed
latent (``rank`` values), the rotary key all heads share, and zero lanes up
to a multiple of 128 (``ModelConfig.latent_width``).  Layout
``[L*P, 1, page_size, C]``: the page-major, layer-flattened pool of
engine/kv_cache.py with the kv-head axis held at 1, so page tables, the
allocator and ``scatter_kv_rows`` serve it unchanged.

A query arrives already carried into the latent space, ``[q_nope W_kvb^K |
q_rope | 0]`` a head, so one product of the query block against a page
gives every head's scores, and the same page, read once, gives the values:
``o_lat = P c_kv``.  The caller carries ``o_lat`` back through ``W_kvb^V``.
The same mathematics as expanding every cached token into per-head keys
and values (tests/test_latent_moe.py compares the two).

``mla_paged_decode_pallas`` is the kernel; ``mla_paged_decode_xla`` its
gather twin (CPU, and the parity reference).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# pages walked per loop step: one step's DMAs and its two products amortise
# the loop's fixed cost over 4 x 128 tokens (a 2k-token row is 4 steps)
_PAGES_PER_STEP = 4


def mla_paged_decode_xla(
    q_cat: jnp.ndarray,        # [B, H, C] latent-space queries
    pool: jnp.ndarray,         # [P_total, 1, ps, C]
    page_tables: jnp.ndarray,  # [B, W] GLOBAL page ids
    kv_lens: jnp.ndarray,      # [B] tokens in cache (incl. current)
    *, rank: int, sm_scale: float,
) -> jnp.ndarray:
    """[B, H, rank]: the gather form (every row's whole window)."""
    b, _, c = q_cat.shape
    ps = pool.shape[2]
    w = page_tables.shape[1]
    win = pool[page_tables, 0].reshape(b, w * ps, c)
    logits = jnp.einsum("bhc,btc->bht", q_cat, win).astype(
        jnp.float32) * sm_scale
    mask = jnp.arange(w * ps)[None, None, :] < kv_lens[:, None, None]
    probs = jax.nn.softmax(jnp.where(mask, logits, NEG_INF), axis=-1)
    return jnp.einsum("bht,btr->bhr", probs.astype(pool.dtype),
                      win[..., :rank])


def _mla_decode_kernel(pt_ref, len_ref, q_ref, pool_hbm, o_ref,
                       page_scr, acc_scr, m_scr, l_scr, sem, *,
                       ps: int, rank: int, sm_scale: float, pp: int):
    """One batch row: walk its live pages ``pp`` at a time through a
    double buffer, every head's online softmax against the shared rows."""
    b = pl.program_id(0)
    length = len_ref[b]
    w = pt_ref.shape[1]
    n_pages = jnp.minimum(jax.lax.div(length + ps - 1, ps), w)
    n_steps = jax.lax.div(n_pages + pp - 1, pp)

    def copies(step, slot):
        # a step past the row's last page re-reads a page of the table
        # (unused entries name the null page): masked by position below
        return [pltpu.make_async_copy(
            pool_hbm.at[pt_ref[b, jnp.minimum(step * pp + j, w - 1)], 0],
            page_scr.at[slot, j], sem.at[slot, j]) for j in range(pp)]

    @pl.when(n_steps > 0)
    def _prime():
        for cp in copies(0, 0):
            cp.start()

    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
    q = q_ref[0]  # [H, C]
    h = q.shape[0]

    def body(step, _):
        slot = jax.lax.rem(step, 2)

        @pl.when(step + 1 < n_steps)
        def _prefetch():
            for cp in copies(step + 1, 1 - slot):
                cp.start()

        for cp in copies(step, slot):
            cp.wait()
        rows = page_scr[slot].reshape(pp * ps, page_scr.shape[-1])
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [H, pp*ps]
        pos = step * (pp * ps) + jax.lax.broadcasted_iota(
            jnp.int32, (h, pp * ps), 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pw = jnp.exp(s - m_new)
        pw = jnp.where(m_new > NEG_INF * 0.5, pw, 0.0)
        l_scr[...] = jnp.broadcast_to(
            alpha * l_scr[:, :1] + jnp.sum(pw, axis=1, keepdims=True),
            l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            pw.astype(rows.dtype), rows[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        return _

    jax.lax.fori_loop(0, n_steps, body, None)
    l = l_scr[:, :1]
    # an inactive row (length 0) walked nothing: zeros, not NaN
    o_ref[0] = (acc_scr[...] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("rank", "sm_scale", "interpret"))
def mla_paged_decode_pallas(
    q_cat: jnp.ndarray,        # [B, H, C]
    pool: jnp.ndarray,         # [P_total, 1, ps, C]
    page_tables: jnp.ndarray,  # [B, W] GLOBAL page ids
    kv_lens: jnp.ndarray,      # [B] incl. current token
    *, rank: int, sm_scale: float, interpret: bool = False,
) -> jnp.ndarray:
    """[B, H, rank] in ``q_cat``'s type: one program a batch row, each live
    page of the row read once from the pool (its rows serve the scores and,
    through their first ``rank`` lanes, the values)."""
    b, h, c = q_cat.shape
    ps = pool.shape[2]
    assert pool.shape[1] == 1 and pool.shape[3] == c and rank % 128 == 0, (
        "latent pool [P, 1, ps, C] with a lane-aligned latent rank")
    pp = _PAGES_PER_STEP
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, c), lambda bi, *_: (bi, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, rank), lambda bi, *_: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pp, ps, c), pool.dtype),
            pltpu.VMEM((h, rank), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.SemaphoreType.DMA((2, pp)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, ps=ps, rank=rank,
                          sm_scale=sm_scale, pp=pp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rank), q_cat.dtype),
        interpret=interpret,
        name="mla_paged_decode",
    )(page_tables.astype(jnp.int32), kv_lens.astype(jnp.int32), q_cat, pool)
