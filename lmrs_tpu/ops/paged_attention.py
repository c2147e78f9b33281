"""Paged attention over a paged KV pool: decode, multi-token verify, spans.

Every Pallas kernel here has an XLA twin with the same contract:

* ``paged_decode_pallas_fused`` — write-fused ragged decode (TPU), the
  decode program's kernel: each program scatters its row's new token into
  the pool in place and walks ONLY that row's live pages — a dynamic
  ``fori_loop`` bound from SMEM — DMA-ing K/V pages HBM→VMEM and folding
  them into an online softmax.  Decode cost is proportional to the tokens
  actually in the cache (the Ragged Paged Attention idea, PAPERS.md), which
  is the whole point of paging: the bytes a decode step must move are
  exactly the live KV bytes.  ``paged_decode_fused_sharded`` is the same
  kernel under a tensor-parallel mesh.
* ``paged_decode_pallas_multi`` — the same write + walk for k+1 queries a
  row (speculative verify).
* ``ragged_spans_pallas`` — ragged SPANS: rows of any query length (decode
  rows, prefill chunks) in one flat token axis, each walked against its own
  pages; a narrow path (8-query tiles) and a wide one (128-256 queries a
  tile, long continuation spans).
* ``paged_decode_xla`` / ``paged_decode_multi_xla`` / ``ragged_spans_xla``
  — the gather-based twins (any platform): gather the slot's pages into a
  contiguous window and run masked attention.  Cost ∝ the (bucketed)
  window, independent of real lengths.

Cache layout: [P_total, K, page_size, hd], PAGE-major: one page's ALL kv
heads are a single contiguous [K, page_size, hd] DMA, and the kv-head axis
is folded INTO each program as a statically-unrolled loop over the buffered
block, so one DMA pipeline serves every head of a row.  The pipeline is a
ring of ``_RING`` page buffers: a step folds page p while the DMAs of pages
p+1 and p+2 are in flight, so the DMAs keep the HBM's rate through the fold
(``_RING``'s comment).
P_total flattens the layer axis into the page axis —
engine/kv_cache.PagedKVCache — and callers pass GLOBAL page ids.

The BATCH axis folds the same way with ``row_group > 1`` (the multi-row
page walk): one program walks a GROUP of G rows through the shared pipeline
— grid=(B/G,) — priming row r+1's first pages and running its RMW cycle
inside row r's compute bubbles (``_make_group_kernel``), so launch, scratch
init and the pipeline's prime are paid per group.  Callers pass a host-side
length-balanced row order (``balanced_row_order``) so one straggler row
cannot serialize a whole group.  ``row_group=1`` is the per-row grid, one
program a row.

What a page step of the walk costs on the v5e is the larger of its DMAs
and its fold, where each hides under the other.  The fold's cost is the
longest chain of latencies it strings together: with float32 operands on
both sides of its two products and the heads folded one after another a
step took the same time on bf16 and on int8 pages, so ``_fold_page``
multiplies in the pool's stored type, keeps the softmax state
lane-replicated and folds a page in three passes over the heads.  The
DMAs hide the fold only with two pages in flight (``_RING``); a bf16 step
then costs what its DMAs alone cost, an int8 one what its fold costs (the
readings: PERF.md section 6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lmrs_tpu.utils.jax_compat import shard_map, tpu_compiler_params

NEG_INF = -1e30


def balanced_row_order(lengths, row_group: int) -> np.ndarray:
    """Host-side length-balanced row→group assignment for the multi-row
    decode kernels (``row_group > 1``): a permutation of rows such that
    each consecutive size-G slice — one kernel program's group — carries a
    near-equal total live length.  Within a group the rows share ONE DMA
    pipeline and walk sequentially, so an unbalanced assignment lets a
    straggler row serialize its whole group (and, under megacore grid
    partitioning, unbalanced groups serialize the cores).

    LPT greedy: rows sorted by length descending, each placed in the
    group with the smallest running total that still has a free seat.
    When ``len(lengths) % row_group != 0`` the LAST group keeps the short
    seat count (the kernel pads the trailing rows with inactive ones).
    Deterministic — ties break on row index — so greedy A/B runs
    reproduce exactly.  Returns ``perm`` with dispatch row i holding
    original row ``perm[i]``: gather inputs by ``perm``, scatter outputs
    back through it.  Pure numpy; never traced.
    """
    lengths = np.asarray(lengths)
    b = len(lengths)
    g = max(1, int(row_group))
    n_groups = max(1, -(-b // g))
    # identity fast path: one group, or uniform lengths (the common
    # equal-chunk map workload) — balancing is a no-op, and returning
    # identity lets the scheduler skip the reorder entirely (it also
    # keeps sampled rows' draws aligned with the per-row grid's when
    # there was nothing to balance)
    if n_groups == 1 or (b and (lengths == lengths[0]).all()):
        return np.arange(b, dtype=np.int64)
    order = np.argsort(-lengths, kind="stable")
    sums = np.zeros(n_groups)
    seats = np.full(n_groups, g)
    if b % g:
        seats[-1] = b - g * (n_groups - 1)
    groups: list[list[int]] = [[] for _ in range(n_groups)]
    for r in order:
        gi = min((i for i in range(n_groups) if seats[i] > 0),
                 key=lambda i: sums[i])
        groups[gi].append(int(r))
        sums[gi] += lengths[r]
        seats[gi] -= 1
    return np.concatenate([np.asarray(grp, np.int64) for grp in groups])


def _n_live_pages(page_tables_ref, kv_lens_ref, row, page_size, length=None):
    """Live pages of ``row``, clamped to the table width: a row whose
    length exceeds its table (e.g. an inactive row carrying a stale/garbage
    length) must never index page_tables_ref out of bounds — SMEM reads are
    not range-checked.  ``length`` overrides the SMEM length (the span
    kernel's per-tile walks use a running prefix length, not the row's)."""
    length_ = kv_lens_ref[row] if length is None else length
    return jnp.minimum(
        jax.lax.div(length_ + page_size - 1, page_size),
        page_tables_ref.shape[1],
    )


def _fetch_page(page_tables_ref, k_hbm, v_hbm, k_scr, v_scr, sem,
                row, p, slot):
    """Start the K+V page DMAs for (row, page index p) into ring buffer
    ``slot`` — ONE [K, ps, hd] copy each brings every kv head's rows of the
    page (the page-major layout's point).  ONE shared implementation: the
    walk's steady-state prefetches and the cross-row prime must agree on
    the slot/semaphore layout or the next wait pairs with the wrong DMA."""
    page = page_tables_ref[row, p]
    pltpu.make_async_copy(k_hbm.at[page], k_scr.at[slot], sem.at[slot, 0]).start()
    pltpu.make_async_copy(v_hbm.at[page], v_scr.at[slot], sem.at[slot, 1]).start()


def _prime_walk(page_tables_ref, k_hbm, v_hbm, k_scr, v_scr, sem, row,
                n_pages):
    """Start a walk's first ``_RING - 1`` page fetches, page p into slot p
    (fewer where the row has fewer live pages): what the walk's first steps
    wait for.  The walk itself primes (``_ragged_decode_all_heads``), or a
    multi-row kernel does it for the next row once that row's RMW has
    drained (so even the newest token's page is read fresh)."""
    for p in range(_RING - 1):
        @pl.when(n_pages > p)
        def _(p=p):
            _fetch_page(page_tables_ref, k_hbm, v_hbm, k_scr, v_scr, sem,
                        row, p, p)


# ------------------------------------------------------------ XLA fallback


def scatter_kv_rows(pool: jnp.ndarray, page: jnp.ndarray, off: jnp.ndarray,
                    rows: jnp.ndarray) -> jnp.ndarray:
    """Write ``rows`` [..., K, hd] into the page-major pool [P, K, ps, hd]
    at ``[page[...], :, off[...]]`` — the XLA pool write of everything but
    a fresh prefill over whole pages (``scatter_kv_pages``): the windowed
    continuation of a chunked prompt (it starts mid-page), packed prefill,
    a fresh prefill whose bucket is no multiple of the page (a bucket of
    64), the latent pool's decode write, and the XLA decode / multi / span
    twins.

    All three leading dims are indexed (the kv heads through an arange), so
    the scatter window is a single hd row.  The obvious spelling
    ``pool.at[page, :, off].set(rows)`` has a [K, hd] window, for which the
    TPU compiler re-lays-out the WHOLE pool: a pool-sized HBM temporary per
    pool, copied in and out of every program that writes — 2 x 2 GiB at the
    Llama-3-8B shape with the default pool, which does not fit one chip.
    This form compiles with no temporary, also kv-head-sharded under tp.
    Its price is the window: one row of hd elements a scatter step, about
    70 ns each on the v5e whatever the row holds (27 ms for a [24 x 2048]
    wave's 393,216 int8 rows; PERF.md section 6, PR 32) — cheap for a
    decode step's B*K rows, and why a fresh prefill does not come here."""
    kh = pool.shape[1]
    return pool.at[page[..., None], jnp.arange(kh), off[..., None]].set(rows)


def whole_pages(s: int, page_size: int, table_width: int) -> int:
    """Pages a row that a FRESH prefill of ``s`` positions writes whole
    (``scatter_kv_pages``), or 0 where it must write row by row: the bucket
    is no multiple of the page (a bucket of 64) or the table is narrower
    than the bucket.  Static facts of a program's shapes — the model step
    and the scheduler's dispatch record (``page_writes``) both ask here."""
    n = s // page_size
    return n if n and s % page_size == 0 and table_width >= n else 0


def scatter_kv_pages(pool: jnp.ndarray, pages: jnp.ndarray,
                     rows: jnp.ndarray) -> jnp.ndarray:
    """Write ``rows`` [B, S, K, hd], the tokens at positions 0 .. S-1 of B
    sequences (S a multiple of the page size), into the page-major pool
    [P, K, ps, hd] a WHOLE PAGE at a time: positions [j*ps, (j+1)*ps) of
    row b are page ``pages[b, j]`` — the pool write of a fresh prefill.

    One transposing copy of ``rows`` to [B*S/ps, K, ps, hd], then a scatter
    whose window is a page: B*S/ps windows of K*ps*hd elements where the
    row form sends B*S*K windows of hd.  In place on a donated pool, like
    the row form.  ``pages`` may repeat (every pad row and every column
    past a row's allocation names the null page 0): which of those writes
    lands is unspecified, and nothing reads the null page."""
    b, s, kh, hd = rows.shape
    ps = pool.shape[2]
    n = s // ps
    tiles = rows.reshape(b, n, ps, kh, hd).transpose(0, 1, 3, 2, 4)
    return pool.at[pages.reshape(-1)].set(tiles.reshape(b * n, kh, ps, hd))


def paged_decode_xla(
    q: jnp.ndarray,            # [B, H, hd]
    k_pages: jnp.ndarray,      # [P, K, ps, hd]
    v_pages: jnp.ndarray,      # [P, K, ps, hd]
    page_tables: jnp.ndarray,  # [B, W] page ids (live window)
    kv_lens: jnp.ndarray,      # [B] tokens in cache (incl. current)
    kv_scales=None,            # (k_scale, v_scale) [B, K, hd] for int8 pools
    window=None,               # int32 scalar (may be traced): the newest
                               # ``window`` positions are seen; 0/None: all
) -> jnp.ndarray:
    b, h, hd = q.shape
    _, kh, ps, _ = k_pages.shape
    n_rep = h // kh
    w = page_tables.shape[1]
    # gather pages: [B, W, K, ps, hd] -> [B, W*ps, K, hd]
    k = k_pages[page_tables].transpose(0, 1, 3, 2, 4).reshape(b, w * ps, kh, hd)
    v = v_pages[page_tables].transpose(0, 1, 3, 2, 4).reshape(b, w * ps, kh, hd)
    if kv_scales is not None:
        from lmrs_tpu.ops.quant import kv_dequant

        k = kv_dequant(k, kv_scales[0], q.dtype)
        v = kv_dequant(v, kv_scales[1], q.dtype)
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=2)
        v = jnp.repeat(v, n_rep, axis=2)
    logits = jnp.einsum("bhd,bkhd->bhk", q, k).astype(jnp.float32) * hd**-0.5
    pos = jnp.arange(w * ps)[None, None, :]
    mask = pos < kv_lens[:, None, None]
    if window is not None:
        # a window layer's table is a ring (engine/kv_cache.py): what lies
        # behind the window may be a later page's rows, and is masked
        mask &= (window <= 0) | (pos >= (kv_lens - window)[:, None, None])
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", probs.astype(v.dtype), v)


# ------------------------------------------------------------ Pallas kernel


# bf16 parts an f32 left-side operand is split into: 8 significant bits
# each, so three carry all 24 and the split rounds nothing
_SPLIT = 3

# page buffers in a decode walk's DMA ring.  Step p waits for page p in slot
# p % _RING and starts page p + _RING - 1 into the slot page p - 1 left, so
# _RING - 1 pages are in flight while one folds.  One page DMA streaming
# alone does not reach the HBM's rate and two do: with two buffers the
# page that streamed while a page folded streamed alone, and a step cost
# its DMAs' time plus part of the fold.  Three, not more: the DMAs alone
# take the same time at any depth, and a fourth buffer reads the same as a
# third (PERF.md section 6)
_RING = 3


def _lanes(x, n: int):
    """A lane-replicated ``[rows, 128]`` value at width ``n``: its first
    ``n`` lanes, or copies side by side where ``n`` is a multiple of 128.
    Either way whole vregs: no cross-lane move."""
    if n <= 128:
        return x[:, :n]
    assert n % 128 == 0, n
    return jnp.concatenate([x] * (n // 128), axis=1)


def _mxu_dtype(pool_dtype):
    """The type a fold's products run in, read off the pool: an f32 pool
    (the CPU tests') multiplies in f32, bf16 pages are the MXU operand as
    they lie in VMEM, int8 pages convert to bf16 (exact: |x| <= 127)."""
    return jnp.float32 if pool_dtype == jnp.float32 else jnp.bfloat16


def _left_rows(x, mxu):
    """``x`` [rows, n] as the left side of a product against a page tile of
    type ``mxu``.  In f32, or already bf16 (q on a bf16 pool), it is ``x``.
    An f32 ``x`` against a bf16 tile (the scaled query of an int8 pool, the
    probabilities) is split into ``_SPLIT`` bf16 parts by masking mantissa
    bits — hi, what is left of x - hi, and so on: their sum is ``x`` — and
    the parts are STACKED as further rows, padded to the bf16 tile's 16.
    The page tile is the MXU's stationary operand, loaded once per product
    in one pass whatever the rows that ride on it; ``_part_sum`` adds the
    parts' results in f32, so the product differs from an f32 x f32 one by
    summation order alone.  Returns the left side and its number of parts
    (1: not split)."""
    if mxu == jnp.float32 or x.dtype == mxu:
        return x.astype(mxu), 1
    rest = x.astype(jnp.float32)
    parts = []
    for _ in range(_SPLIT - 1):
        hi = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(rest, jnp.int32) & -65536,
            jnp.float32)
        parts.append(hi)
        rest = rest - hi
    parts.append(rest)
    pad = -(_SPLIT * x.shape[0]) % 16
    if pad:
        parts.append(jnp.zeros((pad, x.shape[1]), jnp.float32))
    return jnp.concatenate(parts, axis=0).astype(mxu), _SPLIT


def _part_sum(r, rows: int, parts: int):
    """A product's ``rows`` result rows from those of its left side's
    ``parts`` stacked parts (``_left_rows``): added smallest first."""
    out = r[(parts - 1) * rows:parts * rows]
    for i in reversed(range(parts - 1)):
        out = out + r[i * rows:(i + 1) * rows]
    return out


def _softmax_step(s, m_ref, l_ref, *, guard: bool):
    """One page of an online softmax: masked scores ``s`` [rows, ps] against
    the running max / sum ``m_ref`` / ``l_ref`` ([rows, 128] f32, every lane
    of a row the same number), kept LANE-REPLICATED end to end: read whole,
    combined whole, stored whole.  Reading lane 0 and broadcasting it back
    costs four cross-lane permutes per 8 rows per page beside the two
    reductions (PERF.md section 6, PR 26 and PR 30).  Returns the page's
    probabilities ``pw`` [rows, ps] and the accumulator's rescale ``alpha``
    [rows, 128].  ``guard`` zeroes ``pw`` where a row has seen no valid
    position yet (its max is still NEG_INF, so ``exp(s - m)`` would be 1)."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    m_wide = _lanes(m_new, s.shape[1])
    pw = jnp.exp(s - m_wide)
    if guard:
        pw = jnp.where(m_wide > NEG_INF * 0.5, pw, 0.0)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(pw, axis=1, keepdims=True)
    m_ref[...] = m_new
    return pw, alpha


def _fold_page(qs, k_page, v_page, masked, sm_scale, acc_scr, m_scr, l_scr):
    """Fold one page, ALL its kv heads, into a walk's online softmax.
    ``qs[ki]`` is head ki's ``_left_rows`` pair; ``k_page`` / ``v_page`` the
    page's [kh, ps, hd] blocks as stored; acc/m/l the walk's state with its
    leading kh axis.  Both products run in the pool's ``_mxu_dtype`` with
    f32 accumulation; the all-masked guard is on (an inactive row, a verify
    row past ``max_pos``).

    In three passes over the heads — every head's scores, every head's
    softmax step, every head's values — and not head by head: an MXU takes
    its products in program order, so head by head the next head's scores
    queue behind this head's values product, which waits for this head's
    softmax, and a page step becomes one chain of products and lane
    reductions through the heads whatever else the units could overlap
    (the compiler's critical path either way: PERF.md section 6, PR 30)."""
    kh, rows, hd = acc_scr.shape
    mxu = _mxu_dtype(k_page.dtype)
    scores = []
    for ki in range(kh):
        q, parts = qs[ki]
        s = _part_sum(jax.lax.dot_general(
            q, k_page[ki].astype(mxu), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32), rows, parts) * sm_scale
        scores.append(jnp.where(masked, s, NEG_INF))  # [rows, ps]
    steps = [_softmax_step(scores[ki], m_scr.at[ki], l_scr.at[ki],
                           guard=True) for ki in range(kh)]
    for ki, (pw, alpha) in enumerate(steps):
        pw, parts = _left_rows(pw, mxu)
        pv = _part_sum(jax.lax.dot_general(
            pw, v_page[ki].astype(mxu), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32), rows, parts)
        acc_scr[ki] = acc_scr[ki] * _lanes(alpha, hd) + pv


def _ragged_decode_all_heads(
    # scalar prefetch
    page_tables_ref,  # SMEM [B, W]
    kv_lens_ref,      # SMEM [B]
    # inputs
    q_ref,            # VMEM [kh, n_tokens*n_rep_p, hd] (this row, all heads)
    k_hbm,            # ANY  [P, K, ps, hd] (full page-major pool)
    v_hbm,            # ANY  [P, K, ps, hd]
    # output
    o_ref,            # VMEM [kh, n_tokens*n_rep_p, hd]
    # scratch
    k_scr,            # VMEM [_RING, K, ps, hd] a ring of whole pages
    v_scr,            # VMEM [_RING, K, ps, hd]
    acc_scr,          # VMEM [kh, n_tokens*n_rep_p, hd] f32
    m_scr,            # VMEM [kh, n_tokens*n_rep_p, 128] f32
    l_scr,            # VMEM [kh, n_tokens*n_rep_p, 128] f32
    sem,              # DMA semaphores (_RING, 2): [ring slot, k/v]
    *,
    page_size: int,
    sm_scale: float,
    kh: int,
    n_rep_p: int = 0,   # rows per token (0 = single-token: all rows one group)
    n_tokens: int = 1,  # queries per row (speculative verify: k+1)
    max_pos: int | None = None,  # static cap: no position >= this is valid
    row=None,           # batch row to walk (default: this program's row)
    external_prime: bool = False,  # caller already primed (_prime_walk)
    after_walk=None,    # hook between the page loop and the output write:
                        # the multi-row group kernels prime the NEXT row's
                        # walk here so its DMAs overlap this row's
                        # epilogue (softmax normalize + output write)
    get_kscale=None,    # (row, ki) -> [hd] f32: int8 pools.  The scales are
    get_vscale=None,    # per-CHANNEL on the contracted axis, so K's dequant
                        # folds into q (one multiply per head, before the
                        # loop) and V's into the accumulator (after it) —
                        # pages stream as raw int8, only a type convert per
                        # page
    length=None,        # override for kv_lens_ref[row]: the span kernel
                        # walks each query TILE with a running prefix length
                        # (base + tiles-so-far * QT), not the row's total
    lo_ref=None,        # SMEM [B]: positions before lo_ref[row] are masked
                        # (a windowed walk: ``window_walk``)
):
    """Walk ONE batch row's live pages through a ring of ``_RING`` page
    buffers: each loop step DMAs one page's ALL kv heads as a single
    [K, ps, hd] copy (the page-major layout) and unrolls the heads' folds
    over the buffered block, pages p+1 and p+2 streaming while page p
    computes (``_RING``'s comment says why two).
    Every head keeps its own online-softmax state (acc/m/l gain a leading
    kh axis, statically indexed; m and l lane-replicated).

    What a page step costs beyond its DMA is ``_fold_page``'s business
    (module docstring; PERF.md section 6, PR 30).

    With ``n_tokens > 1`` (ragged speculative verify) the q rows group as
    [token j][query head group]: token j sits at absolute position
    ``length - n_tokens + j`` and its rows attend positions < that + 1 —
    per-row causal limits over the SAME single page walk, so verifying
    k drafts costs one walk, not a full page-window gather."""
    b = pl.program_id(0) if row is None else row
    if length is None:
        length = kv_lens_ref[b]
    n_pages = _n_live_pages(page_tables_ref, kv_lens_ref, b, page_size,
                            length=length)

    def fetch(p, slot):
        _fetch_page(page_tables_ref, k_hbm, v_hbm, k_scr, v_scr, sem,
                    b, p, slot)

    @pl.when(n_pages == 0)
    def _zero():  # inactive row: defined output, no page walk
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    if not external_prime:
        _prime_walk(page_tables_ref, k_hbm, v_hbm, k_scr, v_scr, sem, b,
                    n_pages)

    # int8 dequant is row-count-agnostic: the K scale folds into EVERY q
    # row (all tokens share the slot's per-channel scales — draft tokens
    # were quantized with the same scales in the RMW) and the V scale
    # folds into every accumulator row after the walk, so n_tokens > 1
    # (speculative verify) needs no special casing here.

    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
    # per-head q as the scores product's left side, once per walk; for int8
    # pools pre-scaled: q·(s⊙k8) = (q⊙s)·k8
    rows = q_ref.shape[1]
    qs = []
    for ki in range(kh):
        q = q_ref[ki]  # [rows, hd]
        if get_kscale is not None:
            q = q.astype(jnp.float32) * get_kscale(b, ki)[None, :]
        qs.append(_left_rows(q, _mxu_dtype(k_scr.dtype)))

    def body(p, _):
        slot = jax.lax.rem(p, _RING)
        ahead = p + _RING - 1

        # the page _RING - 1 ahead starts into the slot page p - 1 left
        # (folded last step), so pages p+1 .. p+_RING-1 stream while page
        # p computes
        @pl.when(ahead < n_pages)
        def _prefetch():
            fetch(ahead, jax.lax.rem(ahead, _RING))

        page = page_tables_ref[b, p]
        pltpu.make_async_copy(
            k_hbm.at[page], k_scr.at[slot], sem.at[slot, 0]).wait()
        pltpu.make_async_copy(
            v_hbm.at[page], v_scr.at[slot], sem.at[slot, 1]).wait()

        # positional causal mask: identical for every head, computed once
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        if n_tokens == 1:
            limit = length  # every row is the newest token
        else:
            # row r belongs to token j = r // n_rep_p at absolute position
            # length - n_tokens + j: strict per-row causality
            j = jax.lax.broadcasted_iota(
                jnp.int32, (rows, page_size), 0) // n_rep_p
            limit = length - n_tokens + j + 1
            if max_pos is not None:
                # positions >= max_pos were never written (write cap in the
                # RMW): a query past the cap sees the real prefix only
                limit = jnp.minimum(limit, max_pos)
        masked = pos < limit
        if lo_ref is not None:
            masked = jnp.logical_and(masked, pos >= lo_ref[b])

        _fold_page(qs, k_scr.at[slot], v_scr.at[slot], masked, sm_scale,
                   acc_scr, m_scr, l_scr)
        return _

    jax.lax.fori_loop(0, n_pages, body, None)

    # safe to issue new DMAs into the ring here: every copy the loop
    # started has been waited, and the last page's compute consumed its
    # buffer before the loop returned
    if after_walk is not None:
        after_walk()

    @pl.when(n_pages > 0)
    def _write():
        for ki in range(kh):
            l = _lanes(l_scr[ki], acc_scr.shape[-1])
            out = acc_scr[ki] / jnp.where(l > 0, l, 1.0)
            if get_vscale is not None:
                # per-channel V scale on the output axis: pw·(s⊙v8) =
                # (pw·v8)⊙s — folded once per head after the loop
                out = out * get_vscale(b, ki)[None, :]
            o_ref[ki] = out.astype(o_ref.dtype)


def _make_rmw(
    page_tables_ref, kv_lens_ref,
    get_knew,         # (row, ki) -> VMEM [t_pad, hd] the T new tokens' K
    get_vnew,
    k_out,            # ANY  [P, K, ps, hd] aliased pool
    v_out,
    k8_scr,           # VMEM [n_win, kh, wh, hd] (window-major: one window's
    v8_scr,           #   ALL heads are a single contiguous DMA block)
    wsem,             # DMA semaphores (n_win, 2)
    *,
    page_size: int,
    kh: int,
    n_tokens: int,
    t_pad: int,
    hd: int,
    max_pos: int | None = None,
    wh: int = 8,        # RMW window height = the pool dtype's sublane tile
                        # (8 for bf16/f32 pools, 32 for int8)
    get_kscale=None,    # (row, ki) -> [hd] f32: quantize new tokens into
    get_vscale=None,    # int8 pools with the row's per-channel scales
):
    """Row-parametrized RMW scatter of T consecutive new tokens' K/V into
    the page pool in place.  ``for_row(row)`` returns the three phases —
    ``(start_reads, blend_write, drain)`` — so a caller can interleave one
    row's RMW cycle with another row's page walk (the fused kernel runs row
    b+1's cycle inside row b's walk: their pages are disjoint because slots
    own their pages exclusively).  Exactly ONE cycle may be in flight at a
    time (the phases share k8/v8 scratch and ``wsem``).

    The positions are consecutive, so they cover at most
    ``n_win = (T-2)//8 + 2`` aligned 8-row windows, and page_size % 8 == 0
    means no window straddles a page — each window is ONE read-blend-write
    RMW covering ALL kv heads (a single strided [K, wh, hd] copy each way,
    not 2·K tiny per-head copies), reads all issued before any blend so
    they overlap.

    ``max_pos`` (static): tokens at positions >= it are NOT written — the
    max-seq-len cap for draft tokens that overhang the end of the cache
    (the caller passes the UNCLAMPED length, so the base position is
    always exact; a clamped length would slide the whole span backwards
    over real cache entries)."""
    assert page_size % wh == 0, (
        f"RMW window offsets are computed in {wh}-row units; a non-multiple "
        f"page_size={page_size} would silently alias (scheduler gates this)")
    n_win = 1 if n_tokens == 1 else (n_tokens - 2) // wh + 2

    def for_row(b, length=None):
        # ``length`` override: the span kernel RMWs one QT-token tile at a
        # time with a running prefix length instead of the row's total
        if length is None:
            length = kv_lens_ref[b]
        base = jnp.maximum(length - n_tokens, 0)  # first new token's position
        win0 = jax.lax.div(base, wh) * wh  # provably wh-aligned
        # A window is touched ONLY if it holds a valid token position.  An
        # overhanging window (past the table span or max_pos) must be
        # skipped entirely, not clipped: a clipped page index keeps the raw
        # offset and can ALIAS an earlier window's rows when
        # page_size <= wh*(n_win-1) (e.g. ps=8 with any draft span ending at
        # the table edge) — its stale write-back would then revert the valid
        # window's freshly written K/V.
        limit = jnp.minimum(base + n_tokens,
                            page_tables_ref.shape[1] * page_size)
        if max_pos is not None:
            limit = jnp.minimum(limit, max_pos)

        def win_page(wi):
            start = win0 + wh * wi
            page_idx = jnp.clip(jax.lax.div(start, page_size), 0,
                                page_tables_ref.shape[1] - 1)
            return start, page_tables_ref[b, page_idx]

        def read_copies(wi, start, page):
            # rem(start, ps) is wh-aligned (start = wh*k, ps % wh == 0) but
            # Mosaic's divisibility prover can't see through rem; the w*wh
            # form it can.  ONE [K, wh, hd] copy per direction covers every
            # head's rows of the window (strided on the HBM side, contiguous
            # in the window-major scratch).
            off = pl.ds(jax.lax.rem(jax.lax.div(start, wh), page_size // wh) * wh, wh)
            return (pltpu.make_async_copy(k_out.at[page, :, off],
                                          k8_scr.at[wi], wsem.at[wi, 0]),
                    pltpu.make_async_copy(v_out.at[page, :, off],
                                          v8_scr.at[wi], wsem.at[wi, 1]))

        def write_copies(wi, start, page):
            off = pl.ds(jax.lax.rem(jax.lax.div(start, wh), page_size // wh) * wh, wh)
            return (pltpu.make_async_copy(k8_scr.at[wi],
                                          k_out.at[page, :, off], wsem.at[wi, 0]),
                    pltpu.make_async_copy(v8_scr.at[wi],
                                          v_out.at[page, :, off], wsem.at[wi, 1]))

        def start_reads():
            for wi in range(n_win):
                start, page = win_page(wi)

                @pl.when(start < limit)
                def _read(wi=wi, start=start, page=page):
                    rk, rv = read_copies(wi, start, page)
                    rk.start()
                    rv.start()

        def blend_write():
            for wi in range(n_win):
                start, page = win_page(wi)

                @pl.when(start < limit)
                def _blend(wi=wi, start=start, page=page):
                    rk, rv = read_copies(wi, start, page)
                    wk, wv = write_copies(wi, start, page)
                    rk.wait()
                    rv.wait()
                    # row r of this window holds token j = start+r-base
                    # when 0 <= j < T; select token rows with a tiny 0/1
                    # matmul (no dynamic VMEM indexing) and blend where
                    # a token lands.  The mask is head-independent —
                    # computed once, blended per head.
                    row = jax.lax.broadcasted_iota(jnp.int32, (wh, t_pad), 0)
                    tok = jax.lax.broadcasted_iota(jnp.int32, (wh, t_pad), 1)
                    j = start + row - base
                    valid = (j == tok) & (tok < n_tokens)
                    if max_pos is not None:
                        valid &= (start + row) < max_pos
                    sel = valid.astype(jnp.float32)
                    hit = (jnp.sum(sel, axis=1, keepdims=True) > 0)
                    hit = jnp.broadcast_to(hit, (wh, hd))
                    for ki in range(kh):
                        k_rows = jax.lax.dot_general(
                            sel, get_knew(b, ki).astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
                        v_rows = jax.lax.dot_general(
                            sel, get_vnew(b, ki).astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
                        if get_kscale is not None:
                            # quantize the landing rows with the row's
                            # per-channel scales (int8 pools)
                            k_rows = jnp.clip(jnp.round(
                                k_rows / get_kscale(b, ki)[None, :]),
                                -127, 127)
                            v_rows = jnp.clip(jnp.round(
                                v_rows / get_vscale(b, ki)[None, :]),
                                -127, 127)
                        k8_scr[wi, ki] = jnp.where(
                            hit, k_rows.astype(k8_scr.dtype), k8_scr[wi, ki])
                        v8_scr[wi, ki] = jnp.where(
                            hit, v_rows.astype(v8_scr.dtype), v8_scr[wi, ki])
                    wk.start()
                    wv.start()

        def drain():
            for wi in range(n_win):
                start, page = win_page(wi)

                @pl.when(start < limit)
                def _drain(wi=wi, start=start, page=page):
                    wk, wv = write_copies(wi, start, page)
                    wk.wait()
                    wv.wait()

        return start_reads, blend_write, drain

    return for_row


def _write_new_tokens_all_heads(
    page_tables_ref, kv_lens_ref,
    knew_ref,         # VMEM [kh, t_pad, hd] the T new tokens' K (rows 0..T-1)
    vnew_ref,
    k_out,            # ANY  [P, K, ps, hd] aliased pool
    v_out,
    k8_scr,           # VMEM [n_win, kh, wh, hd]
    v8_scr,
    wsem,             # DMA semaphores (n_win, 2)
    *,
    page_size: int,
    kh: int,
    n_tokens: int,
    max_pos: int | None = None,
    wh: int = 8,
    get_kscale=None,    # (row, ki) -> [hd] f32: int8 pools (quantize the
    get_vscale=None,    # new tokens with the row's per-channel scales)
):
    """One whole RMW cycle for this program's own row (the multi-token
    verify kernel's path; the fused decode kernel uses ``_make_rmw``
    directly to pipeline the cycle across grid iterations)."""
    rmw = _make_rmw(
        page_tables_ref, kv_lens_ref,
        lambda _row, ki: knew_ref[ki], lambda _row, ki: vnew_ref[ki],
        k_out, v_out, k8_scr, v8_scr, wsem,
        page_size=page_size, kh=kh, n_tokens=n_tokens,
        t_pad=knew_ref.shape[1], hd=knew_ref.shape[-1], max_pos=max_pos,
        wh=wh, get_kscale=get_kscale, get_vscale=get_vscale,
    )
    start_reads, blend_write, drain = rmw(pl.program_id(0))
    start_reads()
    blend_write()
    drain()


def _make_group_kernel(*, g: int, ps: int, kh: int, hd: int, n_tokens: int,
                       t_pad: int, n_rep_p: int, max_pos: int | None,
                       wh: int, quantized: bool, sm_scale: float,
                       windowed: bool = False):
    """Row-GROUP decode kernel body (the multi-row page walk): one program
    walks ``g`` consecutive batch rows' live pages through a single shared
    DMA pipeline, the ring of ``_RING`` page buffers, instead of one
    program per row.  The per-program fixed cost — launch, scratch init,
    pipeline prime — is paid once per group, and the cross-row software
    pipeline runs at ROW granularity inside the program: while row r
    computes, row r+1's RMW windows read/blend/write and its first
    ``_RING - 1`` pages prefetch into row r's compute bubbles.  This is
    the per-row fused kernel's cross-iteration trick moved from grid
    iterations to unrolled in-program rows, where no program boundary
    sits between them.  Both dense cells of the benchmark
    dispatch it at ``g`` 4 (24 rows, 13-15 pages a live row): there the
    page steps, not the rows' fixed costs, are the kernel's time (PERF.md
    section 6, PR 30).

    Shared by the single-token fused decode (``n_tokens == 1``) and the
    speculative multi-token verify (``n_tokens > 1``): the RMW machinery
    and the walk are already row- and token-count-parametrized.  The
    pipeline invariants are the per-row kernel's, unchanged: rows' pages
    are disjoint (slots own their pages exclusively), exactly one RMW
    cycle is in flight at a time, and row r+1's prime (its first two
    pages, ``_prime_walk``) happens only after r+1's RMW drain, so a row
    whose newest token lies in its first or second page reads it fresh.
    The LAST row of group ``gi`` hands off to the FIRST row of group
    ``gi+1`` exactly as consecutive grid iterations used to — the
    pipeline crosses group boundaries seamlessly.

    Expects the caller's operand layout: q/o blocked ``(g, kh, rows, hd)``
    per group; knew/vnew (and int8 scales) as WHOLE-array blocks — row
    r+1's RMW runs inside row r's walk, so per-row blocks cannot work
    (same constraint as the per-row fused kernel).  The batch must be
    padded to a multiple of ``g``; padded rows carry length 0 (zero
    output, null-page RMW — the masked-row convention throughout).
    ``windowed``: a third scalar-prefetch operand, each row's first visible
    position (``window_walk``).
    """

    def kernel(pt_ref, len_ref, *refs):
        lo_ref = None
        if windowed:
            lo_ref, *refs = refs
        q_ref, knew_ref, vnew_ref, *rest = refs
        if quantized:
            (ksc_ref, vsc_ref, k_hbm, v_hbm, o_ref, k_out, v_out, k_scr,
             v_scr, acc_scr, m_scr, l_scr, k8_scr, v8_scr, sem, wsem) = rest
            gks = lambda row, ki: ksc_ref[row, ki]
            gvs = lambda row, ki: vsc_ref[row, ki]
        else:
            (k_hbm, v_hbm, o_ref, k_out, v_out, k_scr, v_scr, acc_scr,
             m_scr, l_scr, k8_scr, v8_scr, sem, wsem) = rest
            gks = gvs = None
        gi = pl.program_id(0)
        nrows = pl.num_programs(0) * g
        base = gi * g
        rmw = _make_rmw(
            pt_ref, len_ref,
            lambda row, ki: knew_ref[row, ki],
            lambda row, ki: vnew_ref[row, ki],
            k_out, v_out, k8_scr, v8_scr, wsem,
            page_size=ps, kh=kh, n_tokens=n_tokens, t_pad=t_pad, hd=hd,
            max_pos=max_pos, wh=wh, get_kscale=gks, get_vscale=gvs,
        )

        def prime_row(row):
            # the walk's own prime: its first steps wait for these
            _prime_walk(pt_ref, k_out, v_out, k_scr, v_scr, sem, row,
                        _n_live_pages(pt_ref, len_ref, row, ps))

        @pl.when(gi == 0)
        def _bootstrap():  # the very first row has no predecessor
            sr, bw, dr = rmw(0)
            sr()
            bw()
            dr()
            prime_row(0)

        for j in range(g):  # static unroll: one walk per group row
            row = base + j
            nxt = row + 1
            # clamped for closure creation only (same contract as the
            # per-row fused kernel): for_row's scalar SMEM reads trace
            # unguarded; the pl.when guards keep the phases from
            # EXECUTING past the last row
            nxt_reads, nxt_blend, nxt_drain = rmw(
                jnp.minimum(nxt, nrows - 1))

            @pl.when(nxt < nrows)
            def _next_rmw_reads(nxt_reads=nxt_reads):
                nxt_reads()

            def after_walk(nxt=nxt, nxt_blend=nxt_blend,
                           nxt_drain=nxt_drain):
                # row nxt's RMW completes and its walk primes while
                # row ``row``'s epilogue (normalize + output write) runs
                @pl.when(nxt < nrows)
                def _():
                    nxt_blend()
                    nxt_drain()
                    prime_row(nxt)

            _ragged_decode_all_heads(
                pt_ref, len_ref, q_ref.at[j], k_out, v_out, o_ref.at[j],
                k_scr, v_scr, acc_scr, m_scr, l_scr, sem,
                page_size=ps, sm_scale=sm_scale, kh=kh,
                n_rep_p=n_rep_p, n_tokens=n_tokens, max_pos=max_pos,
                row=row, external_prime=True, after_walk=after_walk,
                get_kscale=gks, get_vscale=gvs, lo_ref=lo_ref,
            )

    return kernel


def window_walk(page_tables, kv_lens, window, page_size: int):
    """A decode row's walk under a window of ``window`` positions (int32
    scalar, may be traced; <= 0: no window): (tables, lengths, lo) in the
    walk's own coordinates.  The walk starts at the page that holds the
    first visible position ``len - window``: the table is shifted so that
    page is column 0, the length counts from that page's first row, and
    ``lo`` is the first visible position in it (rows before it are
    masked).  The write of the newest token and the causal limit follow the
    shifted length, so the kernel needs nothing but ``lo``."""
    w = jnp.asarray(window, jnp.int32)
    first = jnp.where(w > 0, jnp.maximum(kv_lens - w, 0), 0)
    start = first // page_size
    width = page_tables.shape[1]
    cols = jnp.minimum(start[:, None] + jnp.arange(width)[None, :], width - 1)
    return (jnp.take_along_axis(page_tables, cols, axis=1),
            kv_lens - start * page_size, first - start * page_size)


def _pad_rows(x, bp: int, fill=0):
    """Pad axis 0 of ``x`` from b to ``bp`` rows with ``fill`` (group-path
    batch padding; padded rows carry length 0 and are inactive)."""
    b = x.shape[0]
    if b == bp:
        return x
    pad = [(0, bp - b)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad, constant_values=fill)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "max_pos", "row_group"))
def paged_decode_pallas_multi(
    q: jnp.ndarray,            # [B, T, H, hd] queries (token-major)
    k_new: jnp.ndarray,        # [B, T, K, hd] the T tokens' K (post-rope)
    v_new: jnp.ndarray,        # [B, T, K, hd]
    k_pages: jnp.ndarray,      # [P_total, K, ps, hd]
    v_pages: jnp.ndarray,      # [P_total, K, ps, hd]
    page_tables: jnp.ndarray,  # [B, W] GLOBAL page ids
    kv_lens: jnp.ndarray,      # [B] length INCLUDING all T tokens (UNclamped:
                               # may exceed max_pos near the cap; the base
                               # position kv_lens - T must be the true one)
    interpret: bool = False,
    max_pos: int | None = None,  # static position cap (max_seq_len)
    kscale: jnp.ndarray | None = None,  # [B, K, hd] f32: int8 pools — the
    vscale: jnp.ndarray | None = None,  # per-(slot, head, channel) scales
    row_group: int = 1,        # rows per program (multi-row page walk);
                               # 1 = the per-row grid
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Ragged multi-token verify: the speculative-decoding analog of
    ``paged_decode_pallas_fused``.  One program per batch row writes all T
    new tokens' K/V into their pages in place and attends each token's
    query rows to the live pages with strict per-token causality — ONE
    ragged page walk for the whole [B, T] verify step in place of a full
    page-window gather.

    Near the max-seq-len boundary the caller passes the UNclamped length
    (base = kv_lens - T is then always the true first-token position) and
    ``max_pos``: tokens overhanging the cap are neither written nor
    attended — a clamped length would instead slide the whole write span
    backwards over real cache entries.

    With ``kscale``/``vscale`` the pools are int8: the
    RMW quantizes the draft tokens' rows with the slot's frozen
    per-channel scales, windows widen to the int8 sublane tile (32), and
    the walk folds K's dequant into every token's q rows and V's into the
    accumulator — the same folds as the single-token fused kernel, which
    are row-count-agnostic."""
    b, t, h, hd = q.shape
    kh = k_pages.shape[1]
    ps = k_pages.shape[2]
    quantized = kscale is not None
    assert quantized == (k_pages.dtype == jnp.int8), (
        "int8 pools need scales and vice versa")
    wh = 32 if quantized else 8
    n_rep = h // kh
    n_rep_p = -(-n_rep // 8) * 8
    rows = t * n_rep_p
    # [B, T, H, hd] -> [B, kh, T*n_rep_p, hd], token-major row groups
    qg = q.reshape(b, t, kh, n_rep, hd)
    if n_rep_p != n_rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, n_rep_p - n_rep), (0, 0)))
    qg = qg.transpose(0, 2, 1, 3, 4).reshape(b, kh, rows, hd)
    t_pad = -(-t // 8) * 8
    knew = k_new.transpose(0, 2, 1, 3)  # [B, K, T, hd]
    vnew = v_new.transpose(0, 2, 1, 3)
    if t_pad != t:
        knew = jnp.pad(knew, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))
        vnew = jnp.pad(vnew, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))
    n_win = 1 if t == 1 else (t - 2) // wh + 2

    g = max(1, min(row_group, b))
    if g > 1:
        # multi-row page walk: pad the batch to a multiple of g (padded
        # rows: length 0, inactive) and dispatch one program per GROUP.
        # knew/vnew (and scales) become whole-array blocks — the group
        # kernel's pipeline runs row r+1's RMW inside row r's walk, so
        # per-row blocks cannot cross rows (same constraint as the fused
        # kernel); their VMEM footprint scales with batch.
        bp = -(-b // g) * g
        qg = _pad_rows(qg, bp)
        knew, vnew = _pad_rows(knew, bp), _pad_rows(vnew, bp)
        page_tables = _pad_rows(page_tables, bp)
        kv_lens = _pad_rows(kv_lens, bp)
        new_tok_bytes = 2 * bp * kh * t_pad * hd * knew.dtype.itemsize
        assert new_tok_bytes <= 4 * 1024 * 1024, (
            f"multi-row verify keeps all rows' draft K/V in VMEM "
            f"({new_tok_bytes/2**20:.1f} MiB at B={bp}, T={t_pad}, kh={kh}, "
            f"hd={hd}); shard the batch or lower max_batch_slots")
        scale_specs = []
        if quantized:
            # pad scales with ones: a padded row's null-page RMW still
            # quantizes (harmless garbage by convention), and a zero
            # scale would turn that into NaN rows
            kscale = _pad_rows(kscale.astype(jnp.float32), bp, fill=1)
            vscale = _pad_rows(vscale.astype(jnp.float32), bp, fill=1)
            scale_specs = [
                pl.BlockSpec((bp, kh, hd), lambda gi, *_: (0, 0, 0)),
                pl.BlockSpec((bp, kh, hd), lambda gi, *_: (0, 0, 0)),
            ]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bp // g,),
            in_specs=[
                pl.BlockSpec((g, kh, rows, hd), lambda gi, *_: (gi, 0, 0, 0)),
                pl.BlockSpec((bp, kh, t_pad, hd), lambda gi, *_: (0, 0, 0, 0)),
                pl.BlockSpec((bp, kh, t_pad, hd), lambda gi, *_: (0, 0, 0, 0)),
                *scale_specs,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((g, kh, rows, hd), lambda gi, *_: (gi, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            scratch_shapes=[
                pltpu.VMEM((_RING, kh, ps, hd), k_pages.dtype),  # page ring
                pltpu.VMEM((_RING, kh, ps, hd), v_pages.dtype),
                pltpu.VMEM((kh, rows, hd), jnp.float32),
                pltpu.VMEM((kh, rows, 128), jnp.float32),
                pltpu.VMEM((kh, rows, 128), jnp.float32),
                pltpu.VMEM((n_win, kh, wh, hd), k_pages.dtype),
                pltpu.VMEM((n_win, kh, wh, hd), v_pages.dtype),
                pltpu.SemaphoreType.DMA((_RING, 2)),
                pltpu.SemaphoreType.DMA((n_win, 2)),
            ],
        )
        kernel = _make_group_kernel(
            g=g, ps=ps, kh=kh, hd=hd, n_tokens=t, t_pad=t_pad,
            n_rep_p=n_rep_p, max_pos=max_pos, wh=wh, quantized=quantized,
            sm_scale=hd**-0.5)
        operands = [qg, knew, vnew]
        if quantized:
            operands += [kscale, vscale]
        pool_at = 2 + len(operands)
        out, k_pages, v_pages = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((bp, kh, rows, hd), q.dtype),
                jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
            ],
            input_output_aliases={pool_at: 1, pool_at + 1: 2},
            interpret=interpret,
        )(page_tables.astype(jnp.int32), kv_lens.astype(jnp.int32),
          *operands, k_pages, v_pages)
        out = out[:b].reshape(b, kh, t, n_rep_p, hd)[:, :, :, :n_rep]
        return (out.transpose(0, 2, 1, 3, 4).reshape(b, t, h, hd),
                k_pages, v_pages)

    scale_specs = []
    if quantized:
        scale_specs = [
            pl.BlockSpec((b, kh, hd), lambda bi, *_: (0, 0, 0)),
            pl.BlockSpec((b, kh, hd), lambda bi, *_: (0, 0, 0)),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, kh, rows, hd), lambda bi, *_: (bi, 0, 0, 0)),
            pl.BlockSpec((1, kh, t_pad, hd), lambda bi, *_: (bi, 0, 0, 0)),
            pl.BlockSpec((1, kh, t_pad, hd), lambda bi, *_: (bi, 0, 0, 0)),
            *scale_specs,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, kh, rows, hd), lambda bi, *_: (bi, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((_RING, kh, ps, hd), k_pages.dtype),  # page ring
            pltpu.VMEM((_RING, kh, ps, hd), v_pages.dtype),
            pltpu.VMEM((kh, rows, hd), jnp.float32),
            pltpu.VMEM((kh, rows, 128), jnp.float32),
            pltpu.VMEM((kh, rows, 128), jnp.float32),
            pltpu.VMEM((n_win, kh, wh, hd), k_pages.dtype),
            pltpu.VMEM((n_win, kh, wh, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((_RING, 2)),
            pltpu.SemaphoreType.DMA((n_win, 2)),
        ],
    )

    def kernel(pt_ref, len_ref, q_ref, knew_ref, vnew_ref, *rest):
        if quantized:
            (ksc_ref, vsc_ref, k_hbm, v_hbm, o_ref, k_out, v_out, k_scr,
             v_scr, acc_scr, m_scr, l_scr, k8_scr, v8_scr, sem, wsem) = rest
            gks = lambda row, ki: ksc_ref[row, ki]
            gvs = lambda row, ki: vsc_ref[row, ki]
        else:
            (k_hbm, v_hbm, o_ref, k_out, v_out, k_scr, v_scr, acc_scr,
             m_scr, l_scr, k8_scr, v8_scr, sem, wsem) = rest
            gks = gvs = None
        _write_new_tokens_all_heads(
            pt_ref, len_ref, knew_ref.at[0], vnew_ref.at[0], k_out, v_out,
            k8_scr, v8_scr, wsem, page_size=ps, kh=kh, n_tokens=t,
            max_pos=max_pos, wh=wh, get_kscale=gks, get_vscale=gvs,
        )
        _ragged_decode_all_heads(
            pt_ref, len_ref, q_ref.at[0], k_out, v_out, o_ref.at[0],
            k_scr, v_scr, acc_scr, m_scr, l_scr, sem,
            page_size=ps, sm_scale=hd**-0.5, kh=kh,
            n_rep_p=n_rep_p, n_tokens=t, max_pos=max_pos,
            get_kscale=gks, get_vscale=gvs,
        )

    operands = [qg, knew, vnew]
    if quantized:
        operands += [kscale.astype(jnp.float32), vscale.astype(jnp.float32)]
    pool_at = 2 + len(operands)  # k_pages index among ALL args
    out, k_pages, v_pages = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, kh, rows, hd), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        input_output_aliases={pool_at: 1, pool_at + 1: 2},
        interpret=interpret,
    )(page_tables.astype(jnp.int32), kv_lens.astype(jnp.int32),
      *operands, k_pages, v_pages)
    out = out.reshape(b, kh, t, n_rep_p, hd)[:, :, :, :n_rep]
    return out.transpose(0, 2, 1, 3, 4).reshape(b, t, h, hd), k_pages, v_pages


def paged_decode_multi_xla(
    q: jnp.ndarray,            # [B, T, H, hd]
    k_new: jnp.ndarray,        # [B, T, K, hd]
    v_new: jnp.ndarray,        # [B, T, K, hd]
    k_pages: jnp.ndarray,      # [P, K, ps, hd]
    v_pages: jnp.ndarray,      # [P, K, ps, hd]
    page_tables: jnp.ndarray,  # [B, W]
    kv_lens: jnp.ndarray,      # [B] incl. the T tokens (unclamped; see kernel)
    max_pos: int | None = None,
    kv_scales=None,            # (k_scale, v_scale) [B, K, hd] for int8 pools
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Scatter + gather reference for the multi-token verify: same contract
    as ``paged_decode_pallas_multi`` on any platform (correctness baseline
    + CPU fallback for the speculative verify forward).  A token is
    written ONLY when its position lies inside BOTH the table span (W*ps)
    and ``max_pos`` — matching the kernel, which SKIPS out-of-span
    windows; a clipped write would scribble real rows of the last tabled
    page (the stale-length degenerate class).  Skipped writes park on the
    reserved null page (id 0)."""
    b, t, h, hd = q.shape
    _, kh, ps, _ = k_pages.shape
    w = page_tables.shape[1]
    base = jnp.maximum(kv_lens - t, 0)
    pos = base[:, None] + jnp.arange(t)[None, :]  # [B, T]
    page = jnp.take_along_axis(
        page_tables, jnp.clip(pos // ps, 0, w - 1), axis=1)  # [B, T]
    off = pos % ps
    in_span = pos < w * ps
    if max_pos is not None:
        in_span &= pos < max_pos
    page = jnp.where(in_span, page, 0)  # overhang lands on the null page
    off = jnp.where(in_span, off, 0)
    if kv_scales is not None:
        from lmrs_tpu.ops.quant import kv_quant

        k_new = kv_quant(k_new, kv_scales[0])
        v_new = kv_quant(v_new, kv_scales[1])
    k_pages = scatter_kv_rows(k_pages, page, off, k_new)  # [B, T, K, hd]
    v_pages = scatter_kv_rows(v_pages, page, off, v_new)

    n_rep = h // kh
    k_win = k_pages[page_tables].transpose(0, 1, 3, 2, 4).reshape(
        b, w * ps, kh, hd)
    v_win = v_pages[page_tables].transpose(0, 1, 3, 2, 4).reshape(
        b, w * ps, kh, hd)
    if kv_scales is not None:
        from lmrs_tpu.ops.quant import kv_dequant

        k_win = kv_dequant(k_win, kv_scales[0], q.dtype)
        v_win = kv_dequant(v_win, kv_scales[1], q.dtype)
    if n_rep > 1:
        k_win = jnp.repeat(k_win, n_rep, axis=2)
        v_win = jnp.repeat(v_win, n_rep, axis=2)
    logits = jnp.einsum("bthd,bkhd->bthk", q, k_win).astype(jnp.float32) * hd**-0.5
    col = jnp.arange(w * ps)[None, None, None, :]
    mask = col <= pos[:, :, None, None]  # query t attends positions <= its own
    if max_pos is not None:
        mask &= col < max_pos
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bthk,bkhd->bthd", probs.astype(v_win.dtype), v_win)
    return out, k_pages, v_pages


# ------------------------------------------------- ragged span kernel (RPA)

# Query-tile height of the span kernel's narrow path.  Spans are host-packed
# to QT-token alignment (pack_spans), so every tile's flat offset is provably
# aligned for Mosaic's dynamic-slice prover and no tile straddles two spans.
SPAN_QT = 8
# Query-tile height of the wide path: a span of at least this many tokens is
# walked flash-style, SPAN_QT_WIDE queries against ONE pass over the row's
# pages.  Chosen by a sweep on the chip (PERF.md section 6, PR 26).
SPAN_QT_WIDE = 256


# canonical bucket edges of the ragged-span compile-key family — defined
# jax-free in utils/perf_model so the mock engine can share them without
# importing the kernel stack; re-exported here for kernel-side callers
from lmrs_tpu.utils.perf_model import pow2_bucket  # noqa: E402,F401


def pack_spans(q_lens, floor: int = 16):
    """Host-side span packer for the ragged span kernel: given per-row real
    query lengths (0 = inactive row), return ``(q_starts, total)`` where
    span i occupies flat tokens [q_starts[i], q_starts[i] + q_lens[i]) of a
    buffer whose rows are SPAN_QT-aligned, and ``total`` is the aligned
    token count (bucket it pow2 before allocating — the compile key).
    Pure numpy; never traced."""
    q_lens = np.asarray(q_lens, np.int64)
    aligned = -(-q_lens // SPAN_QT) * SPAN_QT
    q_starts = np.concatenate([[0], np.cumsum(aligned)[:-1]])
    return q_starts.astype(np.int32), int(max(floor, aligned.sum()))


def span_walk_counts(q_lens, kv_lens, page_size: int, table_pages: int,
                     max_pos: int | None = None,
                     qt_wide: int | None = None) -> tuple[int, int]:
    """What ``ragged_spans_pallas`` will do with one dispatch, counted on the
    host by the kernel's own rule: ``(wide_tokens, kv_page_reads)`` — the
    query tokens that fall in wide tiles, and the K/V pages the walks fetch
    (per tile, the pages up to its causal limit).

    The rule, per row with span length ``ql`` behind ``base`` cached tokens:
    ``ql >= W`` (``SPAN_QT_WIDE``) takes wide tiles at span offsets
    ``min(i*W, ql8 - W)`` (``ql8`` = ``ql`` rounded up to SPAN_QT; the last
    tile slides back over its predecessor instead of running past the span),
    each walking the pages below ``min(base + offset + W, base + ql, cap)``;
    a shorter span takes SPAN_QT-token tiles, tile ``i`` walking the pages
    below ``base + (i+1)*SPAN_QT``.  ``cap`` is the table span, and
    ``max_pos`` where given.  Pure numpy; never traced."""
    wide = SPAN_QT_WIDE if qt_wide is None else qt_wide
    ps = page_size
    cap = table_pages * ps if max_pos is None else min(table_pages * ps,
                                                       max_pos)
    q_lens = np.asarray(q_lens, np.int64)
    kv_lens = np.asarray(kv_lens, np.int64)
    # one-tile rows (decode, verify) in one vector pass; long spans, a few
    # a dispatch, row by row
    one = (q_lens > 0) & (q_lens <= SPAN_QT)
    reads = int(np.minimum(-(-(kv_lens[one] + SPAN_QT) // ps),
                           table_pages).sum())
    wide_tokens = 0
    for r in np.flatnonzero(q_lens > SPAN_QT):
        ql, base = int(q_lens[r]), int(kv_lens[r])
        if ql >= wide:
            ql8 = -(-ql // SPAN_QT) * SPAN_QT
            t0 = np.minimum(np.arange(-(-ql8 // wide)) * wide, ql8 - wide)
            end = np.minimum(base + np.minimum(t0 + wide, ql), cap)
            wide_tokens += ql
            reads += int((-(-end // ps)).sum())
        else:
            end = base + (np.arange(-(-ql // SPAN_QT)) + 1) * SPAN_QT
            reads += int(np.minimum(-(-end // ps), table_pages).sum())
    return wide_tokens, reads


def _fold_page_wide(q, k, v, masked, sm_scale, acc_ref, m_ref, l_ref):
    """Fold one page of one kv head into a wide span tile's online softmax:
    scores ``q·kᵀ`` ([rows, ps], f32 accumulation whatever the operands'
    dtype), masked, against the head's lane-replicated running max / sum
    (``_softmax_step``) and accumulator ([rows, hd] f32).  No all-masked
    guard: a tile's walk starts at page 0, whose position 0 every row sees,
    so the running max is finite from the first fold on.  The values are
    still multiplied in f32 (a lead: PERF.md section 7)."""
    hd = acc_ref.shape[-1]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale  # [rows, ps]
    s = jnp.where(masked, s, NEG_INF)
    pw, alpha = _softmax_step(s, m_ref, l_ref, guard=False)
    acc_ref[...] = acc_ref[...] * _lanes(alpha, hd) + jax.lax.dot_general(
        pw, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _wide_span_tiles(
    # scalar prefetch, and this program's row with its span
    pt_ref, qs_ref, bi, ql, base,
    # HBM operands
    q_hbm, kn_hbm, vn_hbm, k_out, v_out, o_hbm,
    # scratch
    k_scr, v_scr,     # VMEM [_RING, kh, ps, hd]: slots 0 and 1 alone, two
                      # whole pages (this path keeps one in flight)
    sem,              # DMA (_RING, 2): page fetches [buffer parity, k/v]
    q_scr,            # VMEM [2, kh, W*n_rep, hd] q tiles (tile parity)
    kn_scr, vn_scr,   # VMEM [2, kh, W, hd] the tiles' new-token K/V
    o_scr,            # VMEM [kh, W*n_rep, hd]
    acc_scr, m_scr, l_scr,  # f32 [kh, W*n_rep, hd | 128 | 128]
    psem,             # DMA (2, 2): page write-backs
    lsem,             # DMA (2, 3): tile loads [tile parity, q/kn/vn]
    osem,             # DMA (1,): tile output store
    qf_scr=None,      # VMEM [kh, W*n_rep, hd] f32: int8 pools (q ⊙ k scale)
    *, wide: int, n_rep: int, kh: int, page_size: int, sm_scale: float,
    max_pos: int | None, get_kscale, get_vscale,
):
    """The wide path of ``ragged_spans_pallas``: row ``bi``'s span (at least
    ``wide`` tokens) in ``wide``-token query tiles, flash-style — per tile
    ONE walk over the row's pages, each page's ``[ps, hd]`` block against
    the tile's ``[wide * n_rep, hd]`` query rows per kv head.

    The tile's own tokens are written on the way: a page the tile's
    positions fall in is blended in VMEM when its fetch lands (new rows
    over the fetched ones, a 0/1 selection matmul as in ``_make_rmw``),
    attended in its blended form, and written back whole — one ``[kh, ps,
    hd]`` DMA per such page instead of a read-blend-write cycle per 8 rows.
    Only real tokens below the position cap land (no padding garbage).

    Tile ``i`` covers span tokens ``[t0, t0 + wide)`` with ``t0 = min(i *
    wide, ql8 - wide)``: the last tile slides back over its predecessor
    rather than past the span's SPAN_QT-aligned end, so every tile is full
    width, in bounds of the flat buffer and inside its own span; the
    overlap recomputes the same rows to the same values (rows of a tile are
    independent, and a fully masked page folds as the identity).

    Pipeline: the loads of tile i+1 (q, new K/V) are issued before tile
    i's walk and the store of tile i drains under tile i+1's walk; inside a
    walk page p+1 streams while page p computes."""
    ps, qt = page_size, SPAN_QT
    w8 = wide // qt
    rows = wide * n_rep
    hd = q_scr.shape[-1]
    quantized = get_kscale is not None
    # bf16 q against a bf16 pool (f32 against f32) feeds the MXU as it is:
    # the products are the numbers an f32 cast would give
    direct = (not quantized) and q_scr.dtype == k_scr.dtype
    cap = pt_ref.shape[1] * ps
    if max_pos is not None:
        cap = min(cap, max_pos)
    ql8 = jax.lax.div(ql + qt - 1, qt)      # span length in QT units
    n_tiles = jax.lax.div(ql8 + w8 - 1, w8)
    qs8 = jax.lax.div(qs_ref[bi], qt)       # q_starts is QT-aligned

    def tile_off8(ti):
        return jnp.minimum(ti * w8, ql8 - w8)

    def flat8(ti):
        # the tile's flat offset in QT units: times a constant it is
        # provably aligned for Mosaic
        return qs8 + tile_off8(ti)

    def loads(ti, slot):
        tok8 = flat8(ti)
        return (
            pltpu.make_async_copy(
                q_hbm.at[:, pl.ds(tok8 * (qt * n_rep), rows)],
                q_scr.at[slot], lsem.at[slot, 0]),
            pltpu.make_async_copy(
                kn_hbm.at[:, pl.ds(tok8 * qt, wide)],
                kn_scr.at[slot], lsem.at[slot, 1]),
            pltpu.make_async_copy(
                vn_hbm.at[:, pl.ds(tok8 * qt, wide)],
                vn_scr.at[slot], lsem.at[slot, 2]))

    def store(ti):
        return pltpu.make_async_copy(
            o_scr, o_hbm.at[:, pl.ds(flat8(ti) * (qt * n_rep), rows)],
            osem.at[0])

    def writeback(page, slot):
        return (pltpu.make_async_copy(k_scr.at[slot], k_out.at[page],
                                      psem.at[slot, 0]),
                pltpu.make_async_copy(v_scr.at[slot], v_out.at[page],
                                      psem.at[slot, 1]))

    for c in loads(0, 0):
        c.start()

    def tile(ti, carry):
        slot = jax.lax.rem(ti, 2)
        t0 = tile_off8(ti) * qt
        p0 = base + t0                      # position of the tile's token 0
        # positions < end exist for this tile: its real tokens, capped
        end = jnp.minimum(p0 + jnp.minimum(wide, ql - t0), cap)
        n_pages = jax.lax.div(end + ps - 1, ps)
        first_new = jax.lax.div(p0, ps)     # pages >= it take new tokens

        @pl.when(n_pages > 0)
        def _prime():
            _fetch_page(pt_ref, k_out, v_out, k_scr, v_scr, sem, bi, 0, 0)

        for c in loads(ti, slot):
            c.wait()

        @pl.when(ti + 1 < n_tiles)
        def _next_loads():
            for c in loads(ti + 1, 1 - slot):
                c.start()

        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
        if quantized:
            for ki in range(kh):
                qf_scr[ki] = (q_scr[slot, ki].astype(jnp.float32)
                              * get_kscale(bi, ki)[None, :])
        # row r is token r // n_rep at position p0 + that: it attends
        # positions <= its own, below ``end``
        tok_of_row = jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) // n_rep
        limit = jnp.minimum(p0 + tok_of_row + 1, end)      # [rows, 1]

        def body(p, _):
            cur = jax.lax.rem(p, 2)

            @pl.when(p + 1 < n_pages)
            def _prefetch():
                # the other buffer last held page p-1: a write-back of it
                # must land before the next fetch overwrites the buffer
                @pl.when(p - 1 >= first_new)
                def _():
                    for c in writeback(0, 1 - cur):
                        c.wait()

                _fetch_page(pt_ref, k_out, v_out, k_scr, v_scr, sem,
                            bi, p + 1, 1 - cur)

            page = pt_ref[bi, p]
            pltpu.make_async_copy(
                k_out.at[page], k_scr.at[cur], sem.at[cur, 0]).wait()
            pltpu.make_async_copy(
                v_out.at[page], v_scr.at[cur], sem.at[cur, 1]).wait()

            @pl.when(p >= first_new)
            def _blend():
                # page row i holds position p*ps + i = tile token
                # (p*ps + i - p0): select it with a 0/1 matmul (no dynamic
                # VMEM indexing), head-independent mask computed once
                row_pos = p * ps + jax.lax.broadcasted_iota(
                    jnp.int32, (ps, wide), 0)
                tok = jax.lax.broadcasted_iota(jnp.int32, (ps, wide), 1)
                sel = ((row_pos - p0 == tok)
                       & (row_pos < end)).astype(kn_scr.dtype)
                pos = p * ps + jax.lax.broadcasted_iota(
                    jnp.int32, (ps, hd), 0)
                hit = (pos >= p0) & (pos < end)
                for ki in range(kh):
                    k_rows = jax.lax.dot_general(
                        sel, kn_scr[slot, ki], (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    v_rows = jax.lax.dot_general(
                        sel, vn_scr[slot, ki], (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    if quantized:
                        k_rows = jnp.clip(jnp.round(
                            k_rows / get_kscale(bi, ki)[None, :]), -127, 127)
                        v_rows = jnp.clip(jnp.round(
                            v_rows / get_vscale(bi, ki)[None, :]), -127, 127)
                    k_scr[cur, ki] = jnp.where(
                        hit, k_rows.astype(k_scr.dtype), k_scr[cur, ki])
                    v_scr[cur, ki] = jnp.where(
                        hit, v_rows.astype(v_scr.dtype), v_scr[cur, ki])
                for c in writeback(page, cur):
                    c.start()

            masked = (p * ps + jax.lax.broadcasted_iota(
                jnp.int32, (rows, ps), 1)) < limit
            for ki in range(kh):
                if quantized:
                    q, k = qf_scr[ki], k_scr[cur, ki].astype(jnp.float32)
                elif direct:
                    q, k = q_scr[slot, ki], k_scr[cur, ki]
                else:
                    q = q_scr[slot, ki].astype(jnp.float32)
                    k = k_scr[cur, ki].astype(jnp.float32)
                _fold_page_wide(q, k, v_scr[cur, ki], masked, sm_scale,
                                acc_scr.at[ki], m_scr.at[ki], l_scr.at[ki])
            return _

        jax.lax.fori_loop(0, n_pages, body, None)

        # write-backs the loop did not have to wait for: the last two pages
        for back in (2, 1):
            @pl.when(n_pages - back >= first_new)
            def _drain(back=back):
                for c in writeback(0, jax.lax.rem(n_pages - back, 2)):
                    c.wait()

        @pl.when(ti > 0)
        def _prev_store():  # it had this whole walk to land
            store(ti - 1).wait()

        for ki in range(kh):
            l = _lanes(l_scr[ki], hd)
            out = acc_scr[ki] / jnp.where(l > 0, l, 1.0)
            if quantized:
                out = out * get_vscale(bi, ki)[None, :]
            o_scr[ki] = out.astype(o_scr.dtype)
        store(ti).start()
        return carry

    jax.lax.fori_loop(0, n_tiles, tile, None)
    store(n_tiles - 1).wait()


@functools.partial(jax.jit,
                   static_argnames=("interpret", "max_pos", "qt_wide"))
def ragged_spans_pallas(
    q: jnp.ndarray,            # [Tp, H, hd] flat query tokens (all spans)
    k_new: jnp.ndarray,        # [Tp, K, hd] the tokens' K (post-rope)
    v_new: jnp.ndarray,        # [Tp, K, hd]
    k_pages: jnp.ndarray,      # [P_total, K, ps, hd]
    v_pages: jnp.ndarray,      # [P_total, K, ps, hd]
    page_tables: jnp.ndarray,  # [B, W] GLOBAL page ids
    kv_lens: jnp.ndarray,      # [B] tokens in cache BEFORE this dispatch
                               # (the span base positions; NOT including the
                               # span's own tokens — unlike the multi kernel)
    q_starts: jnp.ndarray,     # [B] SPAN_QT-aligned flat span offsets
    q_lens: jnp.ndarray,       # [B] real span lengths (0 = inactive row)
    interpret: bool = False,
    max_pos: int | None = None,
    kscale: jnp.ndarray | None = None,  # [B, K, hd] f32 (int8 pools)
    vscale: jnp.ndarray | None = None,
    qt_wide: int | None = None,  # tests and the tile sweep shrink the wide
                                 # tile here; every caller leaves it at
                                 # SPAN_QT_WIDE
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """ONE ragged kernel for every phase (the RPA shape, PAPERS.md): each
    dispatch is a list of (row, query-span) pairs over the paged pool —
    plain decode is q_len=1 rows, speculative verify q_len=k+1 rows, a
    SARATHI mixed step is decode rows plus one long prefill-slice row, and
    a prefill continuation chunk is a long-span row.  One program per
    batch row; the row's span length, read from ``q_lens`` inside the
    kernel, picks the query tile:

    * a span of at least ``SPAN_QT_WIDE`` tokens takes wide tiles
      (``_wide_span_tiles``): per tile the new tokens' K/V land in their
      pages once and the row's pages are walked ONCE against all the tile's
      query rows, so a c-token span behind n cached pages costs about
      c/SPAN_QT_WIDE walks of n..n+c/ps pages;
    * a shorter span (decode rows, verify spans, short mixed slices) takes
      SPAN_QT-token tiles: per tile it DMAs the tile's q rows and
      new-token K/V from HBM, RMWs the tokens into the row's pages
      (``_make_rmw`` with a running prefix length), and walks the prefix
      pages with per-token causal limits — c/SPAN_QT partial walks, each
      from page 0, which is what a span of a few tokens should cost.

    VMEM is bounded by the TILE — span length only moves the trip counts —
    so the compile bucket family is (pow2 total-query-tokens, page window)
    instead of the per-phase matrix, and a dispatch mixes both kinds of row.
    ``span_walk_counts`` is the same rule on the host.

    Token j of row b sits at absolute position ``kv_lens[b] + j``.  A
    narrow tile t walks with prefix length ``kv_lens[b] + (t+1)*QT`` so its
    per-token limits are exact; the last narrow tile's padding tokens write
    garbage K/V at FUTURE positions (masked by every real query's limit;
    overwritten by the row's next real tokens — the mixed path's existing
    convention).  Padding query rows of either path compute garbage outputs
    the consumer never gathers.  Flat tokens outside every span are
    untouched in the output buffer.

    Query rows are ``n_rep`` per token in both paths (a tile is a multiple
    of SPAN_QT tokens, so its rows are a multiple of 8 without padding the
    head group).  The flash path remains the tool for large FRESH prefills
    with no prior KV: it needs no page walk at all."""
    tp, h, hd = q.shape
    kh = k_pages.shape[1]
    ps = k_pages.shape[2]
    b = page_tables.shape[0]
    quantized = kscale is not None
    assert quantized == (k_pages.dtype == jnp.int8), (
        "int8 pools need scales and vice versa")
    assert tp % SPAN_QT == 0, "pad the flat token buffer to SPAN_QT"
    wide = SPAN_QT_WIDE if qt_wide is None else qt_wide
    assert wide % SPAN_QT == 0 and wide > SPAN_QT, wide
    # a flat buffer shorter than one wide tile holds no wide span: such a
    # dispatch (decode rows, a small mixed step) compiles the narrow path
    # alone, with the narrow path's scratch
    has_wide = tp >= wide
    wh = 32 if quantized else 8
    n_rep = h // kh
    qt = SPAN_QT
    tile_rows = qt * n_rep
    wide_rows = wide * n_rep
    n_win = (qt - 2) // wh + 2
    sm_scale = hd**-0.5

    # [Tp, H, hd] -> [kh, Tp*n_rep, hd], token-major row groups
    qg = q.reshape(tp, kh, n_rep, hd).transpose(1, 0, 2, 3).reshape(
        kh, tp * n_rep, hd)
    knew = k_new.transpose(1, 0, 2)  # [kh, Tp, hd]
    vnew = v_new.transpose(1, 0, 2)

    def kernel(pt_ref, len_ref, qs_ref, ql_ref, q_hbm, kn_hbm, vn_hbm,
               *rest):
        if quantized:
            ksc_ref, vsc_ref, *rest = rest
            gks = lambda row, ki: ksc_ref[row, ki]
            gvs = lambda row, ki: vsc_ref[row, ki]
        else:
            gks = gvs = None
        (k_hbm, v_hbm, o_hbm, k_out, v_out,
         k_scr, v_scr, sem, q_scr, o_scr, kn_scr, vn_scr,
         acc_scr, m_scr, l_scr, k8_scr, v8_scr, wsem, dsem,
         *wide_scr) = rest
        bi = pl.program_id(0)
        ql = ql_ref[bi]
        base = len_ref[bi]

        if has_wide:
            @pl.when(ql >= wide)
            def _wide_row():
                _wide_span_tiles(
                    pt_ref, qs_ref, bi, ql, base,
                    q_hbm, kn_hbm, vn_hbm, k_out, v_out, o_hbm,
                    k_scr, v_scr, sem, *wide_scr,
                    wide=wide, n_rep=n_rep, kh=kh, page_size=ps,
                    sm_scale=sm_scale, max_pos=max_pos,
                    get_kscale=gks, get_vscale=gvs)

        rmw = _make_rmw(
            pt_ref, len_ref,
            lambda _row, ki: kn_scr[ki], lambda _row, ki: vn_scr[ki],
            k_out, v_out, k8_scr, v8_scr, wsem,
            page_size=ps, kh=kh, n_tokens=qt, t_pad=qt, hd=hd,
            max_pos=max_pos, wh=wh, get_kscale=gks, get_vscale=gvs,
        )

        @pl.when((ql > 0) & (ql < wide))
        def _narrow_row():
            n_tiles = jax.lax.div(ql + qt - 1, qt)

            def tile(ti, carry):
                # tile index in QT units: q_starts is QT-aligned, so the
                # div-mul form gives Mosaic a provably aligned offset
                t8 = jax.lax.div(qs_ref[bi], qt) + ti
                cq = pltpu.make_async_copy(
                    q_hbm.at[:, pl.ds(t8 * tile_rows, tile_rows)],
                    q_scr, dsem.at[0])
                ck = pltpu.make_async_copy(
                    kn_hbm.at[:, pl.ds(t8 * qt, qt)], kn_scr, dsem.at[1])
                cv = pltpu.make_async_copy(
                    vn_hbm.at[:, pl.ds(t8 * qt, qt)], vn_scr, dsem.at[2])
                cq.start()
                ck.start()
                cv.start()
                cq.wait()
                ck.wait()
                cv.wait()
                tile_len = base + (ti + 1) * qt
                start_reads, blend_write, drain = rmw(bi, length=tile_len)
                start_reads()
                blend_write()
                drain()
                _ragged_decode_all_heads(
                    pt_ref, len_ref, q_scr, k_out, v_out, o_scr,
                    k_scr, v_scr, acc_scr, m_scr, l_scr, sem,
                    page_size=ps, sm_scale=sm_scale, kh=kh,
                    n_rep_p=n_rep, n_tokens=qt, max_pos=max_pos,
                    row=bi, length=tile_len,
                    get_kscale=gks, get_vscale=gvs,
                )
                co = pltpu.make_async_copy(
                    o_scr, o_hbm.at[:, pl.ds(t8 * tile_rows, tile_rows)],
                    dsem.at[3])
                co.start()
                co.wait()
                return carry

            jax.lax.fori_loop(0, n_tiles, tile, None)

    scale_specs = []
    operands = [qg, knew, vnew]
    if quantized:
        scale_specs = [
            pl.BlockSpec((b, kh, hd), lambda bi, *_: (0, 0, 0)),
            pl.BlockSpec((b, kh, hd), lambda bi, *_: (0, 0, 0)),
        ]
        operands += [kscale.astype(jnp.float32), vscale.astype(jnp.float32)]
    f32 = jnp.float32
    scratch = [
        pltpu.VMEM((_RING, kh, ps, hd), k_pages.dtype),  # page ring
        pltpu.VMEM((_RING, kh, ps, hd), v_pages.dtype),
        pltpu.SemaphoreType.DMA((_RING, 2)),         # page fetches
        # narrow tiles
        pltpu.VMEM((kh, tile_rows, hd), q.dtype),    # q tile
        pltpu.VMEM((kh, tile_rows, hd), q.dtype),    # o tile
        pltpu.VMEM((kh, qt, hd), k_new.dtype),       # new-token K tile
        pltpu.VMEM((kh, qt, hd), v_new.dtype),
        pltpu.VMEM((kh, tile_rows, hd), f32),
        pltpu.VMEM((kh, tile_rows, 128), f32),
        pltpu.VMEM((kh, tile_rows, 128), f32),
        pltpu.VMEM((n_win, kh, wh, hd), k_pages.dtype),
        pltpu.VMEM((n_win, kh, wh, hd), v_pages.dtype),
        pltpu.SemaphoreType.DMA((n_win, 2)),         # RMW windows
        pltpu.SemaphoreType.DMA((4,)),               # q/kn/vn loads, o store
    ]
    if has_wide:  # in ``_wide_span_tiles``' own order
        scratch += [
            pltpu.VMEM((2, kh, wide_rows, hd), q.dtype),
            pltpu.VMEM((2, kh, wide, hd), k_new.dtype),
            pltpu.VMEM((2, kh, wide, hd), v_new.dtype),
            pltpu.VMEM((kh, wide_rows, hd), q.dtype),
            pltpu.VMEM((kh, wide_rows, hd), f32),
            pltpu.VMEM((kh, wide_rows, 128), f32),
            pltpu.VMEM((kh, wide_rows, 128), f32),
            pltpu.SemaphoreType.DMA((2, 2)),         # page write-backs
            pltpu.SemaphoreType.DMA((2, 3)),         # q/kn/vn loads
            pltpu.SemaphoreType.DMA((1,)),           # o store
        ]
        if quantized:
            scratch.append(pltpu.VMEM((kh, wide_rows, hd), f32))  # q ⊙ scale
    vmem_bytes = sum(
        s.inner_aval.size * s.inner_aval.dtype.itemsize
        for s in scratch if s.memory_space == pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # q rows stream per tile
            pl.BlockSpec(memory_space=pl.ANY),  # knew
            pl.BlockSpec(memory_space=pl.ANY),  # vnew
            *scale_specs,
            pl.BlockSpec(memory_space=pl.ANY),  # k pool
            pl.BlockSpec(memory_space=pl.ANY),  # v pool
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # o rows stream per tile
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=scratch,
    )
    pool_at = 4 + len(operands)  # k_pages index among ALL (flat) args
    out, k_pages, v_pages = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((kh, tp * n_rep, hd), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        input_output_aliases={pool_at: 1, pool_at + 1: 2},
        # the wide tile's scratch passes the compiler's default scoped
        # limit; leave room beside it for the walk's own temporaries
        compiler_params=tpu_compiler_params(
            vmem_limit_bytes=vmem_bytes + (24 << 20)),
        interpret=interpret,
    )(page_tables.astype(jnp.int32), kv_lens.astype(jnp.int32),
      q_starts.astype(jnp.int32), q_lens.astype(jnp.int32),
      *operands, k_pages, v_pages)
    out = out.reshape(kh, tp, n_rep, hd)
    return out.transpose(1, 0, 2, 3).reshape(tp, h, hd), k_pages, v_pages


def ragged_spans_xla(
    q: jnp.ndarray,            # [Tp, H, hd]
    k_new: jnp.ndarray,        # [Tp, K, hd]
    v_new: jnp.ndarray,        # [Tp, K, hd]
    k_pages: jnp.ndarray,      # [P, K, ps, hd]
    v_pages: jnp.ndarray,      # [P, K, ps, hd]
    page_tables: jnp.ndarray,  # [B, W]
    kv_lens: jnp.ndarray,      # [B] tokens in cache BEFORE this dispatch
    q_starts: jnp.ndarray,     # [B]
    q_lens: jnp.ndarray,       # [B]
    row_flat: jnp.ndarray,     # [Tp] owning row per flat token (>= B: none)
    max_pos: int | None = None,
    kv_scales=None,            # (k_scale, v_scale) [B, K, hd] for int8 pools
    anc_masks: jnp.ndarray | None = None,  # [Tp] int32 ancestor bitmasks
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Scatter + gather reference for the ragged span kernel: same contract
    on any platform (correctness baseline, the sp>1 / tp-mesh path, and the
    CPU path).  ``row_flat`` is the host-built inverse
    of the span list — the kernel derives it from (q_starts, q_lens); XLA
    wants it materialized.  Out-of-span tokens park their writes on the
    reserved null page (id 0) and produce zero output rows.

    ``anc_masks`` generalizes the causal mask to token TREES (ISSUE 19
    tree speculation): flat token t with a nonzero mask attends the real
    context (cols strictly below its row's ``kv_lens``) plus exactly the
    span-local offsets whose bit is set — its root-to-self ancestor path,
    host-built, capacity 32 offsets per span.  Tokens with mask 0 (prefill
    chunks, plain rows, padding — any span-local layout that IS linear)
    keep the linear ``col <= pos`` rule bit-for-bit, so one dispatch mixes
    tree spans with arbitrarily long linear spans.  K/V writes are
    unchanged (span-offset columns): a tree node's K/V lands at a column
    only its own descendants can see this dispatch, and the scheduler
    heals accepted non-first-chain columns on the row's next span."""
    tp, h, hd = q.shape
    _, kh, ps, _ = k_pages.shape
    b, w = page_tables.shape
    rf = jnp.clip(row_flat, 0, b - 1)
    off = jnp.arange(tp) - q_starts[rf]
    in_span = (row_flat < b) & (off >= 0) & (off < q_lens[rf])
    pos = kv_lens[rf] + off  # absolute position of each flat token
    writable = in_span & (pos < w * ps)
    if max_pos is not None:
        writable &= pos < max_pos
    pos_c = jnp.where(writable, pos, 0)
    page = jnp.where(
        writable,
        page_tables[rf, jnp.clip(pos_c // ps, 0, w - 1)], 0)
    if kv_scales is not None:
        # per-token rule: each flat token quantizes with its OWN row's
        # scales (the span analog of the packed-prefill path)
        from lmrs_tpu.ops.quant import kv_quant_tokens

        k_new = kv_quant_tokens(k_new, kv_scales[0][rf])
        v_new = kv_quant_tokens(v_new, kv_scales[1][rf])
    k_pages = scatter_kv_rows(k_pages, page, pos_c % ps, k_new)
    v_pages = scatter_kv_rows(v_pages, page, pos_c % ps, v_new)

    n_rep = h // kh
    k_win = k_pages[page_tables].transpose(0, 1, 3, 2, 4).reshape(
        b, w * ps, kh, hd)
    v_win = v_pages[page_tables].transpose(0, 1, 3, 2, 4).reshape(
        b, w * ps, kh, hd)
    if kv_scales is not None:
        from lmrs_tpu.ops.quant import kv_dequant

        k_win = kv_dequant(k_win, kv_scales[0], q.dtype)
        v_win = kv_dequant(v_win, kv_scales[1], q.dtype)
    if n_rep > 1:
        k_win = jnp.repeat(k_win, n_rep, axis=2)
        v_win = jnp.repeat(v_win, n_rep, axis=2)
    kt = k_win[rf]  # [Tp, W*ps, H, hd] — per-token window gather
    vt = v_win[rf]
    logits = jnp.einsum("thd,tkhd->thk", q, kt).astype(jnp.float32) * hd**-0.5
    col = jnp.arange(w * ps)[None, None, :]
    mask = in_span[:, None, None] & (col <= pos[:, None, None])
    if anc_masks is not None:
        col_off = col - kv_lens[rf][:, None, None]
        bit = (anc_masks[:, None, None] >> jnp.clip(col_off, 0, 31)) & 1
        tree_ok = (col_off < 0) | ((col_off < 32) & (bit == 1))
        mask = in_span[:, None, None] & jnp.where(
            anc_masks[:, None, None] == 0, col <= pos[:, None, None], tree_ok)
    if max_pos is not None:
        mask &= col < max_pos
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    # fully-masked (out-of-span) rows: uniform probs -> zero them explicitly
    out = jnp.einsum("thk,tkhd->thd", probs.astype(vt.dtype), vt)
    out = jnp.where(in_span[:, None, None], out, 0).astype(q.dtype)
    return out, k_pages, v_pages


@functools.partial(jax.jit, static_argnames=("interpret", "row_group"))
def paged_decode_pallas_fused(
    q: jnp.ndarray,            # [B, H, hd]
    k_new: jnp.ndarray,        # [B, K, hd] current token K (post-rope)
    v_new: jnp.ndarray,        # [B, K, hd]
    k_pages: jnp.ndarray,      # [P_total, K, ps, hd]
    v_pages: jnp.ndarray,      # [P_total, K, ps, hd]
    page_tables: jnp.ndarray,  # [B, W] GLOBAL page ids
    kv_lens: jnp.ndarray,      # [B] incl. current token
    interpret: bool = False,
    kscale: jnp.ndarray | None = None,  # [B, K, hd] f32: int8 pools — the
    vscale: jnp.ndarray | None = None,  # per-(slot, head, channel) scales
    row_group: int = 1,        # rows per program (multi-row page walk);
                               # 1 = the per-row grid
    window=None,               # int32 scalar (may be traced): the walk
                               # starts at the page of position len -
                               # window and masks what lies before it; 0:
                               # the whole sequence.  None: no such operand
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Write-fused ragged decode: scatter the current token's K/V into the
    page pool (in place — the pools are input/output aliased) and attend the
    live pages, in one kernel, one program per BATCH ROW (all kv heads).
    Replaces XLA scatter + kernel: an XLA scatter on the multi-GiB pool
    copies the whole pool per decode step (no in-place aliasing through the
    scan carry).

    With ``row_group > 1`` one program walks a GROUP of rows through the
    shared pipeline (``_make_group_kernel``): programs/step drop by the
    group factor and the per-program fixed cost amortizes over the group.
    Exact-output-equal to the per-row grid; callers balance groups
    host-side (balanced_row_order).

    With ``kscale``/``vscale`` the pools are int8: pages stream as raw int8
    (half the decode bytes), K's per-channel dequant folds into q before
    the walk and V's into the accumulator after it, the RMW quantizes the
    new token's rows, and windows are 32 rows (the int8 sublane tile).

    This is the decode kernel of both dense cells of the benchmark
    (``paged_decode_roofline.offline`` reads it by the ``paged_decode`` in
    its name); what a page step of its walk costs is ``_fold_page``'s
    business (PERF.md section 6, PR 30)."""
    b, h, hd = q.shape
    kh = k_pages.shape[1]
    ps = k_pages.shape[2]
    quantized = kscale is not None
    assert quantized == (k_pages.dtype == jnp.int8), (
        "int8 pools need scales and vice versa")
    windowed = window is not None
    prefetch = [page_tables.astype(jnp.int32), kv_lens.astype(jnp.int32)]
    if windowed:
        prefetch = [a.astype(jnp.int32) for a in window_walk(
            prefetch[0], prefetch[1], window, ps)]
    n_pre = len(prefetch)
    wh = 32 if quantized else 8
    n_rep = h // kh
    n_rep_p = -(-n_rep // 8) * 8
    qg = q.reshape(b, kh, n_rep, hd)
    if n_rep_p != n_rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, n_rep_p - n_rep), (0, 0)))
    # pad the singleton row dim to 8 for sublane alignment (see n_rep_p)
    knew = jnp.broadcast_to(k_new[:, :, None], (b, kh, 8, hd))
    vnew = jnp.broadcast_to(v_new[:, :, None], (b, kh, 8, hd))
    # knew/vnew live whole in VMEM (the cross-row RMW needs the next row's
    # slice — see in_specs) so their footprint scales with batch; keep it
    # well under the ~16 MiB core budget alongside the page scratch
    new_tok_bytes = 2 * b * kh * 8 * hd * knew.dtype.itemsize
    assert new_tok_bytes <= 4 * 1024 * 1024, (
        f"fused decode keeps all rows' new-token K/V in VMEM "
        f"({new_tok_bytes/2**20:.1f} MiB at B={b}, kh={kh}, hd={hd}); "
        "shard the batch or lower max_batch_slots")

    g = max(1, min(row_group, b))
    if g > 1:
        # multi-row page walk: one program per GROUP of g rows (padded
        # rows are inactive), same operands as the per-row grid except
        # q/o block per group.  knew/vnew/scales were already whole-array
        # blocks here (the cross-row RMW needed them), so only the grid
        # and q/o blocking change.
        bp = -(-b // g) * g
        qg = _pad_rows(qg, bp)
        knew, vnew = _pad_rows(knew, bp), _pad_rows(vnew, bp)
        prefetch = [_pad_rows(a, bp) for a in prefetch]
        scale_specs = []
        if quantized:
            # ones, not zeros: a padded row's null-page RMW still divides
            # by its scale (garbage-by-convention, but NaN-free)
            kscale = _pad_rows(kscale.astype(jnp.float32), bp, fill=1)
            vscale = _pad_rows(vscale.astype(jnp.float32), bp, fill=1)
            scale_specs = [
                pl.BlockSpec((bp, kh, hd), lambda gi, *_: (0, 0, 0)),
                pl.BlockSpec((bp, kh, hd), lambda gi, *_: (0, 0, 0)),
            ]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_pre,
            grid=(bp // g,),
            in_specs=[
                pl.BlockSpec((g, kh, n_rep_p, hd),
                             lambda gi, *_: (gi, 0, 0, 0)),
                pl.BlockSpec((bp, kh, 8, hd), lambda gi, *_: (0, 0, 0, 0)),
                pl.BlockSpec((bp, kh, 8, hd), lambda gi, *_: (0, 0, 0, 0)),
                *scale_specs,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((g, kh, n_rep_p, hd),
                             lambda gi, *_: (gi, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            scratch_shapes=[
                pltpu.VMEM((_RING, kh, ps, hd), k_pages.dtype),  # page ring
                pltpu.VMEM((_RING, kh, ps, hd), v_pages.dtype),
                pltpu.VMEM((kh, n_rep_p, hd), jnp.float32),
                pltpu.VMEM((kh, n_rep_p, 128), jnp.float32),
                pltpu.VMEM((kh, n_rep_p, 128), jnp.float32),
                pltpu.VMEM((1, kh, wh, hd), k_pages.dtype),  # one RMW window
                pltpu.VMEM((1, kh, wh, hd), v_pages.dtype),
                pltpu.SemaphoreType.DMA((_RING, 2)),
                pltpu.SemaphoreType.DMA((1, 2)),
            ],
        )
        kernel = _make_group_kernel(
            g=g, ps=ps, kh=kh, hd=hd, n_tokens=1, t_pad=8, n_rep_p=0,
            max_pos=None, wh=wh, quantized=quantized, sm_scale=hd**-0.5,
            windowed=windowed)
        operands = [qg, knew, vnew]
        if quantized:
            operands += [kscale, vscale]
        pool_at = n_pre + len(operands)
        out, k_pages, v_pages = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((bp, kh, n_rep_p, hd), q.dtype),
                jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
            ],
            input_output_aliases={pool_at: 1, pool_at + 1: 2},
            interpret=interpret,
        )(*prefetch, *operands, k_pages, v_pages)
        return out[:b, :, :n_rep].reshape(b, h, hd), k_pages, v_pages

    scale_specs = []
    scale_scratch = []
    if quantized:
        # whole-array f32 blocks (~100 KB at bench shape): the cross-row
        # RMW quantizes the NEXT row's tokens, so per-row blocks can't work
        scale_specs = [
            pl.BlockSpec((b, kh, hd), lambda bi, *_: (0, 0, 0)),
            pl.BlockSpec((b, kh, hd), lambda bi, *_: (0, 0, 0)),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pre,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, kh, n_rep_p, hd), lambda bi, *_: (bi, 0, 0, 0)),
            # knew/vnew map as ONE whole-array block (constant index map):
            # iteration b runs row b+1's RMW cycle mid-walk, so it must read
            # the NEXT row's slice — a per-row block can't cross iterations
            pl.BlockSpec((b, kh, 8, hd), lambda bi, *_: (0, 0, 0, 0)),
            pl.BlockSpec((b, kh, 8, hd), lambda bi, *_: (0, 0, 0, 0)),
            *scale_specs,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, kh, n_rep_p, hd), lambda bi, *_: (bi, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((_RING, kh, ps, hd), k_pages.dtype),  # page ring
            pltpu.VMEM((_RING, kh, ps, hd), v_pages.dtype),
            pltpu.VMEM((kh, n_rep_p, hd), jnp.float32),
            pltpu.VMEM((kh, n_rep_p, 128), jnp.float32),
            pltpu.VMEM((kh, n_rep_p, 128), jnp.float32),
            pltpu.VMEM((1, kh, wh, hd), k_pages.dtype),  # one RMW window
            pltpu.VMEM((1, kh, wh, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((_RING, 2)),
            pltpu.SemaphoreType.DMA((1, 2)),
        ],
    )

    def kernel(pt_ref, len_ref, *refs):
        lo_ref = None
        if windowed:
            lo_ref, *refs = refs
        q_ref, knew_ref, vnew_ref, *rest = refs
        if quantized:
            (ksc_ref, vsc_ref, k_hbm, v_hbm, o_ref, k_out, v_out, k_scr,
             v_scr, acc_scr, m_scr, l_scr, k8_scr, v8_scr, sem, wsem) = rest
            gks = lambda row, ki: ksc_ref[row, ki]
            gvs = lambda row, ki: vsc_ref[row, ki]
        else:
            (k_hbm, v_hbm, o_ref, k_out, v_out, k_scr, v_scr, acc_scr,
             m_scr, l_scr, k8_scr, v8_scr, sem, wsem) = rest
            gks = gvs = None
        # Cross-row software pipeline: rows' pages are DISJOINT
        # (slots own their pages exclusively), so iteration b
        #   1. starts row b+1's RMW window READS (tiny DMAs that land
        #      while row b's pages stream),
        #   2. walks row b (its first pages were primed by iteration b-1),
        #   3. blends + writes + drains row b+1's RMW and primes row b+1's
        #      walk (``_prime_walk``; safe: the RMW just drained, so even
        #      a 1- or 2-page row reads fresh K/V).
        # Iteration 0 bootstraps its own RMW + prime inline.  Exactly one
        # RMW cycle is in flight at a time, so the shared scratch/sems are
        # race-free; the n_tokens=1 degenerate of the multi-token writer
        # keeps one shared RMW implementation.
        nb = pl.num_programs(0)
        bi = pl.program_id(0)
        rmw = _make_rmw(
            pt_ref, len_ref,
            lambda row, ki: knew_ref[row, ki], lambda row, ki: vnew_ref[row, ki],
            k_out, v_out, k8_scr, v8_scr, wsem,
            page_size=ps, kh=kh, n_tokens=1, t_pad=8, hd=hd,
            wh=wh, get_kscale=gks, get_vscale=gvs,
        )
        nxt = bi + 1
        # clamp for closure creation only: for_row's scalar SMEM reads trace
        # unguarded at kernel top level, and nxt == nb at the last iteration
        # would read past len_ref; the pl.when guards below keep the phases
        # from EXECUTING there, the clamp keeps the reads in bounds
        nxt_reads, nxt_blend, nxt_drain = rmw(jnp.minimum(nxt, nb - 1))

        def prime_row(row):
            # the walk's own prime: the next iteration's first steps wait
            # for these
            _prime_walk(pt_ref, k_out, v_out, k_scr, v_scr, sem, row,
                        _n_live_pages(pt_ref, len_ref, row, ps))

        @pl.when(bi == 0)
        def _bootstrap():
            sr, bw, dr = rmw(0)
            sr()
            bw()
            dr()
            prime_row(0)

        @pl.when(nxt < nb)
        def _next_rmw_reads():
            nxt_reads()

        _ragged_decode_all_heads(
            pt_ref, len_ref, q_ref.at[0], k_out, v_out, o_ref.at[0],
            k_scr, v_scr, acc_scr, m_scr, l_scr, sem,
            page_size=ps, sm_scale=hd**-0.5, kh=kh,
            external_prime=True,
            get_kscale=gks, get_vscale=gvs, lo_ref=lo_ref,
        )

        @pl.when(nxt < nb)
        def _next_rmw_write():
            nxt_blend()
            nxt_drain()
            prime_row(nxt)

    # operand order after the 2 scalar-prefetch args: qg, knew, vnew,
    # [kscale, vscale,] k_pages, v_pages — the pool alias indices shift by 2
    # when the scale operands are present
    operands = [qg, knew, vnew]
    if quantized:
        operands += [kscale.astype(jnp.float32), vscale.astype(jnp.float32)]
    pool_at = n_pre + len(operands)  # k_pages index among ALL args
    out, k_pages, v_pages = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, kh, n_rep_p, hd), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # indices count the scalar-prefetch operands; pools alias so the
        # page write happens in the caller's buffers, no pool copy
        input_output_aliases={pool_at: 1, pool_at + 1: 2},
        interpret=interpret,
    )(*prefetch, *operands, k_pages, v_pages)
    return out[:, :, :n_rep].reshape(b, h, hd), k_pages, v_pages


def paged_decode_fused_sharded(
    q: jnp.ndarray,            # [B, H, hd] (H sharded over tp)
    k_new: jnp.ndarray,        # [B, K, hd] (K sharded over tp)
    v_new: jnp.ndarray,        # [B, K, hd]
    k_pages: jnp.ndarray,      # [P_total, K, ps, hd] (kv-head sharded)
    v_pages: jnp.ndarray,      # [P_total, K, ps, hd]
    page_tables: jnp.ndarray,  # [B, W] replicated
    kv_lens: jnp.ndarray,      # [B] replicated
    mesh,
    interpret: bool = False,
    kscale: jnp.ndarray | None = None,  # [B, K, hd] (K sharded over tp)
    vscale: jnp.ndarray | None = None,
    row_group: int = 1,  # rows per program (multi-row page walk)
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Write-fused ragged decode under a tensor-parallel mesh.

    XLA cannot auto-partition a ``pallas_call``, so the kernel runs inside
    ``shard_map`` over the ``tp`` (kv-head) axis: the page pools are already
    kv-head-sharded (engine/kv_cache.py), each shard's page walk and in-place
    K/V write touch only local HBM, and query heads shard consistently with
    their kv head (H/tp = (K/tp) * n_rep) — no cross-chip KV traffic, same
    contract as the single-device kernel per shard (each shard's program
    loops its LOCAL kv heads).  Page tables and lengths replicate
    (host-built, O(B*W) ints)."""
    from jax.sharding import PartitionSpec as P

    head = P(None, "tp", None)
    pool = P(None, "tp", None, None)  # page-major: kv heads are axis 1
    extra_in = ()
    extra_args = ()
    if kscale is not None:
        # scales shard with their kv heads (axis 1 of [B, K, hd])
        extra_in = (head, head)
        extra_args = (kscale, vscale)

    def call(q_, kn_, vn_, kp_, vp_, pt_, kl_, *sc):
        ks_, vs_ = sc if sc else (None, None)
        return paged_decode_pallas_fused(
            q_, kn_, vn_, kp_, vp_, pt_, kl_, interpret=interpret,
            kscale=ks_, vscale=vs_, row_group=row_group)

    fn = shard_map(
        call,
        mesh=mesh,
        in_specs=(head, head, head, pool, pool, P(None, None), P(None),
                  *extra_in),
        out_specs=(head, pool, pool),
        check_vma=False,
    )
    return fn(q, k_new, v_new, k_pages, v_pages, page_tables, kv_lens,
              *extra_args)
