"""Pallas flash-attention kernel vs the XLA reference (interpret mode on CPU).

The kernel's correctness contract (ops/flash_attention.py): match
ops.attention.attention() to f32 tolerance on fresh (position 0-based)
self-attention, including GQA, ragged lengths, and non-divisible shapes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import PAGED_POOLS, PAGED_TOL, paged_pools
from lmrs_tpu.ops.attention import attention
from lmrs_tpu.ops.flash_attention import flash_attention


def _ref(q, k, v, lengths):
    b, s = q.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    return attention(q, k, v, positions, lengths)


@pytest.mark.parametrize("h,kh", [(4, 4), (8, 2)])
def test_flash_matches_reference(h, kh):
    b, s, hd = 2, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kh, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kh, hd), jnp.float32)
    lengths = jnp.asarray([s, s // 3], jnp.int32)
    got = flash_attention(q, k, v, lengths, q_block=128, kv_block=128,
                          interpret=True)
    want = _ref(q, k, v, lengths)
    # rows past a sequence's valid length are zeros (kernel, skip_padded_q)
    # vs garbage (XLA reference); compare only valid rows
    for i, n in enumerate([s, s // 3]):
        np.testing.assert_allclose(np.asarray(got[i, :n]),
                                   np.asarray(want[i, :n]),
                                   rtol=2e-5, atol=2e-5)


def test_flash_non_divisible_seq():
    b, s, h, kh, hd = 1, 300, 4, 2, 64  # not a multiple of the block size
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kh, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kh, hd), jnp.float32)
    got = flash_attention(q, k, v, None, q_block=128, kv_block=128,
                          interpret=True)
    want = _ref(q, k, v, jnp.asarray([s], jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_use_flash_prefill_gate():
    from lmrs_tpu.models.transformer import _use_flash_prefill

    assert not _use_flash_prefill(128, 128)  # short: XLA always
    assert not _use_flash_prefill(2048, 80)  # unaligned head dim
    # on the CPU test backend the long-seq gate must still say no
    assert not _use_flash_prefill(2048, 128)


# query heads per kv head in the fused-decode parity cases, by pool type
# (MHA, GQA 2, GQA 4; test_kv_quant.py runs int8 at 2 and 4, the verify
# cases below every type at 4 and 2).  One shape per type, so the cases of
# a type share one trace of the interpreted kernel.
_N_REP = {"f32": 1, "bf16": 2, "int8": 4}


def _decode_case(pool, seed, b=4, t=None, n_rep=None, kh=2, hd=128,
                 n_pages=12):
    """One decode parity case over a pool of type ``pool``: float32 queries
    (any float32: the walk splits them without rounding), new-token K/V the
    pool holds exactly, pages of 16 tokens (32 for int8: the write's
    window).  ``t`` new tokens a row make it a multi-token verify case."""
    ps = 32 if pool == "int8" else 16
    rng = np.random.default_rng(seed)
    pools = paged_pools(pool, rng, (n_pages, kh, ps, hd), b)
    lead = (b,) if t is None else (b, t)
    h = kh * (n_rep or _N_REP[pool])
    q = jnp.asarray(rng.standard_normal(lead + (h, hd)), jnp.float32)
    k_new = pools.new(jnp.asarray(rng.standard_normal(lead + (kh, hd)),
                                  jnp.float32))
    v_new = pools.new(jnp.asarray(rng.standard_normal(lead + (kh, hd)),
                                  jnp.float32))
    return q, k_new, v_new, pools, ps


def _scatter_new_token(pools, k_new, v_new, tables, kv_lens, rows):
    """The XLA write of ``rows``' current token (position ``kv_lens - 1``)
    into the stored pools: quantized with the row's scales for int8."""
    from lmrs_tpu.ops.quant import kv_quant

    ps = pools.k.shape[2]
    rows = np.asarray(rows)
    pos = jnp.asarray(kv_lens)[rows] - 1
    page = jnp.take_along_axis(jnp.asarray(tables)[rows],
                               (pos // ps)[:, None], 1)[:, 0]
    if pools.kw:
        k_rows = kv_quant(k_new[:, None], pools.kw["kscale"])[:, 0][rows]
        v_rows = kv_quant(v_new[:, None], pools.kw["vscale"])[:, 0][rows]
    else:
        k_rows = k_new[rows].astype(pools.k.dtype)
        v_rows = v_new[rows].astype(pools.v.dtype)
    return (pools.k.at[page, :, pos % ps].set(k_rows),
            pools.v.at[page, :, pos % ps].set(v_rows))


@pytest.mark.parametrize("pool", PAGED_POOLS)
def test_fused_decode_matches_scatter_plus_xla(pool):
    """The write-fused ragged decode kernel (interpret mode) must produce
    the same attention output AND the same pool contents as the XLA
    scatter + gather fallback, whatever the pool's stored type: the walk
    multiplies bf16 and int8 pages in bf16 with its float32 operands split
    into stacked bf16 rows, and owes the float32 reference the tolerance a
    float32 pool owes it."""
    from lmrs_tpu.ops.paged_attention import (
        paged_decode_pallas_fused,
        paged_decode_xla,
    )

    q, k_new, v_new, pools, ps = _decode_case(pool, 0)
    # row 0: pos ps+12 = page 1, offset 12 (an 8-row window starts at 8);
    # row 1: 5 tokens (window start 0); row 2: inactive, on the null page;
    # row 3: its last page holds one token
    tables = jnp.asarray([[3, 5, 7], [9, 0, 0], [0, 0, 0], [2, 4, 6]],
                         jnp.int32)
    kv_lens = jnp.asarray([ps + 13, 5, 0, 2 * ps + 1], jnp.int32)
    act = np.asarray(kv_lens) > 0

    k_ref, v_ref = _scatter_new_token(pools, k_new, v_new, tables, kv_lens,
                                      np.flatnonzero(act))
    want = paged_decode_xla(q, pools.ref(k_ref), pools.ref(v_ref), tables,
                            kv_lens, **pools.xkw)

    got, k_out, v_out = paged_decode_pallas_fused(
        q, k_new, v_new, pools.k, pools.v, tables, kv_lens, interpret=True,
        **pools.kw)
    np.testing.assert_allclose(np.asarray(got)[act], np.asarray(want)[act],
                               rtol=PAGED_TOL, atol=PAGED_TOL)
    assert not np.asarray(got)[~act].any()  # an inactive row reads zeros
    # page 0 is the null page: the inactive row's write parks there
    np.testing.assert_array_equal(np.asarray(k_out[1:]),
                                  np.asarray(k_ref[1:]))
    np.testing.assert_array_equal(np.asarray(v_out[1:]),
                                  np.asarray(v_ref[1:]))


@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_fold_with_bf16_rounded_probabilities_fails(pool, monkeypatch):
    """What PAGED_TOL tells apart.  One bf16 part in place of three is the
    ``pw.astype(bf16)`` spelling of the fold (the probabilities, and the
    float32 query, rounded to 8 bits): it misses the float32 reference by
    50 tolerances and more (90 and 600 here) where the fold as it stands passes."""
    import lmrs_tpu.ops.paged_attention as pa

    q, k_new, v_new, pools, ps = _decode_case(pool, 0)
    tables = jnp.asarray([[3, 5, 7], [9, 0, 0], [1, 8, 0], [2, 4, 6]],
                         jnp.int32)
    kv_lens = jnp.asarray([ps + 13, 5, ps + 1, 2 * ps + 1], jnp.int32)
    k_ref, v_ref = _scatter_new_token(pools, k_new, v_new, tables, kv_lens,
                                      range(4))
    want = np.asarray(pa.paged_decode_xla(
        q, pools.ref(k_ref), pools.ref(v_ref), tables, kv_lens,
        **pools.xkw))

    def worst(got):
        return float(np.max(np.abs(np.asarray(got) - want)
                            / (1 + np.abs(want))))

    args = (q, k_new, v_new, pools.k, pools.v, tables, kv_lens)
    assert worst(pa.paged_decode_pallas_fused(
        *args, interpret=True, **pools.kw)[0]) <= PAGED_TOL
    monkeypatch.setattr(pa, "_SPLIT", 1)
    # the undecorated function: the jit's cache holds the three-part trace
    rounded = pa.paged_decode_pallas_fused.__wrapped__(
        *args, interpret=True, **pools.kw)[0]
    assert worst(rounded) > 50 * PAGED_TOL


def test_left_rows_split_is_exact():
    """``_left_rows`` stacks a float32 operand as bf16 parts that add up to
    it bit for bit (so nothing is rounded that an f32 product would not
    round), pads the stack to the bf16 tile's 16 rows with zeros, hands a
    bf16 operand and an f32 product's operand through, and ``_part_sum``
    adds the parts' rows back."""
    from lmrs_tpu.ops.paged_attention import _SPLIT, _left_rows, _part_sum

    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 128)).astype(np.float32)
    x *= np.exp2(rng.integers(-30, 30, x.shape)).astype(np.float32)
    x[0, :3] = [0.0, -0.0, 1.0]
    stacked, n_parts = _left_rows(jnp.asarray(x), jnp.bfloat16)
    assert stacked.dtype == jnp.bfloat16 and n_parts == _SPLIT
    assert stacked.shape == (-(-_SPLIT * 8 // 16) * 16, 128)
    parts = np.asarray(stacked.astype(jnp.float32))
    np.testing.assert_array_equal(
        np.asarray(_part_sum(jnp.asarray(parts), 8, n_parts)), x)
    assert not parts[_SPLIT * 8:].any()
    xb = jnp.asarray(x, jnp.bfloat16)
    whole, n_parts = _left_rows(xb, jnp.bfloat16)
    assert whole is xb and n_parts == 1
    whole, n_parts = _left_rows(xb, jnp.float32)
    assert whole.dtype == jnp.float32 and n_parts == 1
    assert _part_sum(xb, 8, 1) is xb


@pytest.mark.parametrize("pool", PAGED_POOLS)
def test_ragged_decode_clamps_stale_lengths(pool):
    """Regression: a row whose kv_len exceeds its page table's width (a
    freed slot's stale length, or any degenerate input) must clamp its
    page walk and write index to the table instead of indexing SMEM out
    of bounds — on real TPUs the unclamped read DMA'd from garbage page
    ids (fixed alongside scheduler-side zeroing; see scheduler admit()/
    _maybe_finish)."""
    from lmrs_tpu.ops.paged_attention import (
        paged_decode_pallas_fused,
        paged_decode_xla,
    )

    q, k_new, v_new, pools, ps = _decode_case(pool, 1)
    # width 3 pages; rows 2 and 3 inactive, on the null page
    tables = jnp.asarray([[3, 5, 0], [9, 7, 2], [0, 0, 0], [0, 0, 0]],
                         jnp.int32)
    # row 0 normal; row 1 claims 12 pages' worth (> width 3)
    kv_lens = jnp.asarray([ps + 4, 11 * ps + 4, 0, 0], jnp.int32)
    clamped = jnp.minimum(kv_lens, tables.shape[1] * ps)

    got, k_out, v_out = paged_decode_pallas_fused(
        q, k_new, v_new, pools.k, pools.v, tables, kv_lens, interpret=True,
        **pools.kw)

    # reference mirrors the kernel: the degenerate row's write is SKIPPED
    # entirely (its position lies past the table span — a clipped-page
    # write would alias/scribble another window's rows), and the walk
    # attends each tabled page exactly once with the length capped at the
    # table capacity.  An unclamped kernel would re-attend its last
    # column's page for every overflow walk step, shifting row 1's softmax
    # — so output parity here genuinely discriminates fixed vs broken.
    k_ref, v_ref = _scatter_new_token(pools, k_new, v_new, tables, kv_lens,
                                      [0])  # row 1's write is skipped
    want = paged_decode_xla(q, pools.ref(k_ref), pools.ref(v_ref), tables,
                            clamped, **pools.xkw)
    np.testing.assert_allclose(np.asarray(got)[:2], np.asarray(want)[:2],
                               rtol=PAGED_TOL, atol=PAGED_TOL)
    # writes land ONLY on row 0's write page (pos ps+3 -> column 1 -> page
    # 5) and, for the inactive rows, on the null page; row 1's out-of-span
    # write is skipped, not clipped; K and V both
    for name, out_pool, in_pool in (("k", k_out, pools.k),
                                    ("v", v_out, pools.v)):
        touched = set(np.flatnonzero(
            (np.asarray(out_pool) != np.asarray(in_pool)).any(axis=(1, 2, 3))))
        assert touched - {0} == {5}, f"{name} wrote pages {touched}, want 5"


def _tp_mesh(tp=2):
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:tp]).reshape(1, tp, 1, 1, 1),
                ("dp", "tp", "sp", "ep", "pp"))


def test_fused_decode_sharded_matches_xla():
    """The shard_map-wrapped fused decode kernel under a tp=2 mesh must
    match the XLA scatter+gather reference — pools kv-head-sharded, tables
    and lengths replicated (the TP serving layout, kv_cache.py)."""
    import jax.numpy as jnp
    from lmrs_tpu.ops.paged_attention import (
        paged_decode_fused_sharded,
        paged_decode_xla,
    )

    b, h, kh, hd, ps, n_pages = 3, 8, 2, 128, 16, 12
    rng = jax.random.split(jax.random.PRNGKey(2), 5)
    k_pages = jax.random.normal(rng[0], (n_pages, kh, ps, hd), jnp.float32)
    v_pages = jax.random.normal(rng[1], (n_pages, kh, ps, hd), jnp.float32)
    q = jax.random.normal(rng[2], (b, h, hd), jnp.float32)
    k_new = jax.random.normal(rng[3], (b, kh, hd), jnp.float32)
    v_new = jax.random.normal(rng[4], (b, kh, hd), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 0]], jnp.int32)
    kv_lens = jnp.asarray([40, 17, 33], jnp.int32)

    pos = kv_lens - 1
    page = jnp.take_along_axis(tables, (pos // ps)[:, None], 1)[:, 0]
    off = pos % ps
    k_ref = k_pages.at[page, :, off].set(k_new)
    v_ref = v_pages.at[page, :, off].set(v_new)
    want = paged_decode_xla(q, k_ref, v_ref, tables, kv_lens)

    got, k_out, v_out = paged_decode_fused_sharded(
        q, k_new, v_new, k_pages, v_pages, tables, kv_lens,
        _tp_mesh(), interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(k_out), np.asarray(k_ref))
    np.testing.assert_array_equal(np.asarray(v_out), np.asarray(v_ref))


def test_flash_sharded_matches_reference():
    """The shard_map-wrapped flash prefill kernel under a tp=2 mesh must
    match the XLA attention reference (GQA heads shard with their kv head)."""
    from lmrs_tpu.ops.flash_attention import flash_attention_sharded

    b, s, h, kh, hd = 2, 512, 8, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kh, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kh, hd), jnp.float32)
    lengths = jnp.asarray([s, s // 3], jnp.int32)
    got = flash_attention_sharded(q, k, v, lengths, _tp_mesh(), interpret=True)
    want = _ref(q, k, v, lengths)
    for i, n in enumerate([s, s // 3]):
        np.testing.assert_allclose(np.asarray(got[i, :n]),
                                   np.asarray(want[i, :n]),
                                   rtol=2e-5, atol=2e-5)


def _assert_pools_equal(got, want, pages=slice(None)):
    """Stored pool against the reference's (float32 for a bf16 pool, whose
    new rows were drawn bf16-exact)."""
    np.testing.assert_array_equal(
        np.asarray(got[pages].astype(want.dtype)), np.asarray(want[pages]))


@pytest.mark.parametrize("pool", PAGED_POOLS)
def test_multi_token_verify_matches_xla_reference(pool):
    """The ragged multi-token verify kernel (speculative decode: T
    consecutive tokens written + attended with per-token causality in one
    page walk) must match the scatter+gather XLA reference — outputs AND
    pool contents, for every pool type (GQA 4).  Lengths chosen so the
    T-token span straddles a page boundary and an 8-row RMW window
    boundary."""
    from lmrs_tpu.ops.paged_attention import (
        paged_decode_multi_xla,
        paged_decode_pallas_multi,
    )

    q, k_new, v_new, pools, ps = _decode_case(pool, 3, b=3, t=5, n_rep=4)
    tables = jnp.asarray([[1, 2, 3], [4, 5, 6], [7, 8, 9]], jnp.int32)
    # row 0: span ps-3..ps+1 straddles page 0->1; row 1: span 1..5 in-page
    # but crosses the 8-row window at base offset 1; row 2: base 2ps-2
    # straddles page AND window
    kv_lens = jnp.asarray([ps + 2, 6, 2 * ps + 3], jnp.int32)

    want, k_ref, v_ref = paged_decode_multi_xla(
        q, k_new, v_new, pools.ref(pools.k), pools.ref(pools.v), tables,
        kv_lens, **pools.xkw)
    got, k_out, v_out = paged_decode_pallas_multi(
        q, k_new, v_new, pools.k, pools.v, tables, kv_lens, interpret=True,
        **pools.kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=PAGED_TOL, atol=PAGED_TOL)
    _assert_pools_equal(k_out, k_ref)
    _assert_pools_equal(v_out, v_ref)


def test_multi_token_verify_gqa_and_t1_degenerate():
    """GQA head grouping through the multi kernel, plus T=1 degenerating to
    the single-token contract (same mask, same write)."""
    import jax.numpy as jnp
    from lmrs_tpu.ops.paged_attention import (
        paged_decode_multi_xla,
        paged_decode_pallas_multi,
    )

    b, h, kh, hd, ps, n_pages = 2, 8, 2, 128, 16, 8
    for t in (1, 4):
        rng = jax.random.split(jax.random.PRNGKey(10 + t), 5)
        k_pages = jax.random.normal(rng[0], (n_pages, kh, ps, hd), jnp.float32)
        v_pages = jax.random.normal(rng[1], (n_pages, kh, ps, hd), jnp.float32)
        q = jax.random.normal(rng[2], (b, t, h, hd), jnp.float32)
        k_new = jax.random.normal(rng[3], (b, t, kh, hd), jnp.float32)
        v_new = jax.random.normal(rng[4], (b, t, kh, hd), jnp.float32)
        tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        kv_lens = jnp.asarray([t + 7, t], jnp.int32)  # row 1: fresh row
        want, k_ref, v_ref = paged_decode_multi_xla(
            q, k_new, v_new, k_pages, v_pages, tables, kv_lens)
        got, k_out, v_out = paged_decode_pallas_multi(
            q, k_new, v_new, k_pages, v_pages, tables, kv_lens, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(np.asarray(k_out), np.asarray(k_ref))
        np.testing.assert_array_equal(np.asarray(v_out), np.asarray(v_ref))


@pytest.mark.parametrize("pool", PAGED_POOLS)
def test_multi_token_verify_max_pos_boundary(pool):
    """Drafts overhanging max_pos (the max-seq-len cap, here at a page's
    edge) must be NEITHER written (earlier real cache entries stay intact —
    a clamped length would slide the write span backwards over them) NOR
    attended."""
    from lmrs_tpu.ops.paged_attention import (
        paged_decode_multi_xla,
        paged_decode_pallas_multi,
    )

    q, k_new, v_new, pools, ps = _decode_case(pool, 5, b=2, t=4, n_rep=2,
                                              kh=1, n_pages=8)
    max_pos = 2 * ps  # 2 pages of capacity
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    # row 0: base 2ps-2 -> two tokens valid, two overhang the cap;
    # row 1: fully inside
    kv_lens = jnp.asarray([2 * ps + 2, ps + 4], jnp.int32)  # UNclamped

    want, k_ref, v_ref = paged_decode_multi_xla(
        q, k_new, v_new, pools.ref(pools.k), pools.ref(pools.v), tables,
        kv_lens, max_pos=max_pos, **pools.xkw)
    got, k_out, v_out = paged_decode_pallas_multi(
        q, k_new, v_new, pools.k, pools.v, tables, kv_lens, interpret=True,
        max_pos=max_pos, **pools.kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=PAGED_TOL, atol=PAGED_TOL)
    # pool parity on the real pages (null page 0 excluded: the reference
    # parks overhang writes there by contract)
    _assert_pools_equal(k_out, k_ref, slice(1, 5))
    _assert_pools_equal(v_out, v_ref, slice(1, 5))
    # and the overhang really was suppressed: row 0's pre-cap cache entries
    # at positions 2ps-4..2ps-3 (page 2, offsets ps-4..ps-3) are untouched
    np.testing.assert_array_equal(np.asarray(k_out[2, :, ps - 4:ps - 2]),
                                  np.asarray(pools.k[2, :, ps - 4:ps - 2]))


def test_multi_token_verify_no_window_alias_at_table_edge():
    """Regression (round-3 review): with small pages an OVERHANGING padded
    RMW window clipped onto the last table column aliases an earlier
    window's physical rows — its stale write-back would revert freshly
    written K/V.  page_size=8, T=5, span ending exactly at the table edge:
    windows at offsets 0 (valid) and 8 (overhang, must be SKIPPED)."""
    import jax.numpy as jnp
    from lmrs_tpu.ops.paged_attention import (
        paged_decode_multi_xla,
        paged_decode_pallas_multi,
    )

    b, t, h, kh, hd, ps, n_pages = 1, 5, 4, 2, 128, 8, 8
    rng = jax.random.split(jax.random.PRNGKey(9), 5)
    k_pages = jax.random.normal(rng[0], (n_pages, kh, ps, hd), jnp.float32)
    v_pages = jax.random.normal(rng[1], (n_pages, kh, ps, hd), jnp.float32)
    q = jax.random.normal(rng[2], (b, t, h, hd), jnp.float32)
    k_new = jax.random.normal(rng[3], (b, t, kh, hd), jnp.float32)
    v_new = jax.random.normal(rng[4], (b, t, kh, hd), jnp.float32)
    tables = jnp.asarray([[1, 2]], jnp.int32)  # capacity 16 tokens
    # base = 11: tokens at 11..15 — all valid, spanning windows 8..15 of
    # page 2 AND the padded window at global offset 16 (start >= capacity)
    kv_lens = jnp.asarray([16], jnp.int32)

    want, k_ref, v_ref = paged_decode_multi_xla(
        q, k_new, v_new, k_pages, v_pages, tables, kv_lens)
    got, k_out, v_out = paged_decode_pallas_multi(
        q, k_new, v_new, k_pages, v_pages, tables, kv_lens, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # the freshly written rows must SURVIVE (an aliased stale write-back
    # reverted them before this fix); pages 1-2 are the row's real pages
    np.testing.assert_array_equal(np.asarray(k_out[1:3]),
                                  np.asarray(k_ref[1:3]))
    np.testing.assert_array_equal(np.asarray(v_out[1:3]),
                                  np.asarray(v_ref[1:3]))


# --------------------------------------------------- multi-row page walk
# Parity contract (ISSUE 4): with row_group > 1 every decode kernel's
# output AND pool contents must be BIT-IDENTICAL to the per-row grid
# (row_group=1, the LMRS_MULTIROW=0 path) across ragged lengths, inactive
# rows, batch sizes that don't divide the group, bf16 and int8 pools, and
# the n_tokens > 1 speculative-verify shape.  Page 0 (the reserved null
# page) is excluded from pool comparison: padded group rows park their
# masked writes there by the same convention as inactive dispatch rows.


def _ragged_fixture(seed, b=5, h=4, kh=2, hd=128, ps=16, n_pages=32,
                    dtype=jnp.float32):
    rng = jax.random.split(jax.random.PRNGKey(seed), 5)
    k_pages = jax.random.normal(rng[0], (n_pages, kh, ps, hd), dtype)
    v_pages = jax.random.normal(rng[1], (n_pages, kh, ps, hd), dtype)
    q = jax.random.normal(rng[2], (b, h, hd), dtype)
    k_new = jax.random.normal(rng[3], (b, kh, hd), dtype)
    v_new = jax.random.normal(rng[4], (b, kh, hd), dtype)
    tables = jnp.asarray(
        np.random.default_rng(seed).permutation(n_pages - 1)[: b * 3]
        .reshape(b, 3) + 1, jnp.int32)
    # ragged: multi-page, inactive (0), one token past a page's edge,
    # page-boundary, single-token rows
    kv_lens = jnp.asarray([40, 0, 17, 48, 1], jnp.int32)
    return q, k_new, v_new, k_pages, v_pages, tables, kv_lens


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_multirow_fused_parity_bf16(dtype):
    """Fused walk+RMW group kernel vs per-row: outputs and REAL pool pages
    bit-identical (the cross-row RMW pipeline crossing group boundaries),
    on a float32 pool and on the engine's own types (bf16 q, new K/V and
    pool)."""
    from lmrs_tpu.ops.paged_attention import paged_decode_pallas_fused

    q, kn, vn, kp, vp, tables, kv_lens = _ragged_fixture(1, dtype=dtype)
    want, k_ref, v_ref = paged_decode_pallas_fused(
        q, kn, vn, kp, vp, tables, kv_lens, interpret=True)
    # 4 is the dense cells' row_group (a tail of one row behind three
    # padded ones); 5 is one group and no tail
    for g in (2, 4, 5):
        got, k_out, v_out = paged_decode_pallas_fused(
            q, kn, vn, kp, vp, tables, kv_lens, interpret=True, row_group=g)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(k_out[1:]),
                                      np.asarray(k_ref[1:]))
        np.testing.assert_array_equal(np.asarray(v_out[1:]),
                                      np.asarray(v_ref[1:]))


def test_multirow_fused_parity_int8():
    """Group kernel over int8 pools (32-row RMW windows, folded per-channel
    dequant): bit-identical to the per-row int8 kernel — the quantize →
    clip → store path must round identically through the group pipeline."""
    from lmrs_tpu.ops.paged_attention import paged_decode_pallas_fused

    rng = np.random.default_rng(7)
    B, H, K, hd, ps, P = 5, 4, 2, 128, 32, 16
    kq = jnp.asarray(rng.integers(-127, 128, (P, K, ps, hd)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (P, K, ps, hd)), jnp.int8)
    tables = jnp.asarray(rng.permutation(P - 1)[: B * 3].reshape(B, 3) + 1,
                         jnp.int32)
    lens = jnp.asarray([ps * 2 + 17, ps + 1, 0, ps * 3, 1], jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((B, K, hd)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, K, hd)), jnp.float32)
    ks = jnp.asarray(rng.uniform(0.01, 0.05, (B, K, hd)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.01, 0.05, (B, K, hd)), jnp.float32)

    want, k_ref, v_ref = paged_decode_pallas_fused(
        q, kn, vn, kq, vq, tables, lens, interpret=True,
        kscale=ks, vscale=vs)
    for g in (2, 4, 5):  # 4: the dense cells' row_group
        got, k_out, v_out = paged_decode_pallas_fused(
            q, kn, vn, kq, vq, tables, lens, interpret=True,
            kscale=ks, vscale=vs, row_group=g)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(k_out[1:]),
                                      np.asarray(k_ref[1:]))
        np.testing.assert_array_equal(np.asarray(v_out[1:]),
                                      np.asarray(v_ref[1:]))


def test_multirow_multi_token_verify_parity():
    """Speculative-verify shape (n_tokens > 1) through the group kernel:
    bit-identical emit-path outputs and pool contents vs per-row, with
    token spans straddling pages and RMW windows, an out-of-span
    stale-length row, and a fresh (length == T) row."""
    from lmrs_tpu.ops.paged_attention import paged_decode_pallas_multi

    b, t, h, kh, hd, ps, n_pages = 5, 3, 4, 2, 128, 16, 32
    rng = jax.random.split(jax.random.PRNGKey(11), 5)
    k_pages = jax.random.normal(rng[0], (n_pages, kh, ps, hd), jnp.float32)
    v_pages = jax.random.normal(rng[1], (n_pages, kh, ps, hd), jnp.float32)
    q = jax.random.normal(rng[2], (b, t, h, hd), jnp.float32)
    k_new = jax.random.normal(rng[3], (b, t, kh, hd), jnp.float32)
    v_new = jax.random.normal(rng[4], (b, t, kh, hd), jnp.float32)
    tables = jnp.asarray(
        np.random.default_rng(11).permutation(n_pages - 1)[: b * 3]
        .reshape(b, 3) + 1, jnp.int32)
    # spans: page-straddling, in-page, stale (out-of-span), window-
    # straddling, fresh row (length == T)
    kv_lens = jnp.asarray([18, 6, 100, 35, t], jnp.int32)

    want, k_ref, v_ref = paged_decode_pallas_multi(
        q, k_new, v_new, k_pages, v_pages, tables, kv_lens, interpret=True)
    for g in (2, 5):  # a tail of one row; one group, no tail
        got, k_out, v_out = paged_decode_pallas_multi(
            q, k_new, v_new, k_pages, v_pages, tables, kv_lens,
            interpret=True, row_group=g)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(k_out[1:]),
                                      np.asarray(k_ref[1:]))
        np.testing.assert_array_equal(np.asarray(v_out[1:]),
                                      np.asarray(v_ref[1:]))


def test_multirow_multi_token_verify_parity_int8():
    """n_tokens > 1 over int8 pools through the group kernel: the draft
    rows' RMW quantization and the walk's folded dequant must reproduce
    the per-row kernel bit-for-bit."""
    from lmrs_tpu.ops.paged_attention import paged_decode_pallas_multi

    rng = np.random.default_rng(13)
    B, T, H, K, hd, ps, P = 3, 4, 4, 2, 128, 64, 12
    kq = jnp.asarray(rng.integers(-127, 128, (P, K, ps, hd)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (P, K, ps, hd)), jnp.int8)
    tables = jnp.asarray(rng.permutation(P - 1)[: B * 2].reshape(B, 2) + 1,
                         jnp.int32)
    lens = jnp.asarray([ps + 9, T, 70], jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, T, H, hd)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((B, T, K, hd)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, T, K, hd)), jnp.float32)
    ks = jnp.asarray(rng.uniform(0.01, 0.05, (B, K, hd)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.01, 0.05, (B, K, hd)), jnp.float32)

    want, k_ref, v_ref = paged_decode_pallas_multi(
        q, kn, vn, kq, vq, tables, lens, interpret=True,
        kscale=ks, vscale=vs)
    got, k_out, v_out = paged_decode_pallas_multi(
        q, kn, vn, kq, vq, tables, lens, interpret=True,
        kscale=ks, vscale=vs, row_group=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(k_out[1:]), np.asarray(k_ref[1:]))
    np.testing.assert_array_equal(np.asarray(v_out[1:]), np.asarray(v_ref[1:]))


def test_multirow_balanced_row_order():
    """Host-side length-balanced row→group assignment: a valid permutation,
    near-equal group sums, deterministic, short-tail-group aware."""
    from lmrs_tpu.ops.paged_attention import balanced_row_order

    lens = np.array([100, 1, 50, 49, 2, 99])
    perm = balanced_row_order(lens, 2)
    assert sorted(perm.tolist()) == list(range(6))
    sums = lens[perm.reshape(3, 2)].sum(axis=1)
    assert sums.max() - sums.min() <= 2, sums
    # deterministic
    np.testing.assert_array_equal(perm, balanced_row_order(lens, 2))
    # b % g != 0: the LAST group keeps the short seat count (kernel pads)
    perm5 = balanced_row_order(np.array([5, 4, 3, 2, 1]), 2)
    assert sorted(perm5.tolist()) == list(range(5))
    # identity-friendly degenerates
    np.testing.assert_array_equal(balanced_row_order(np.array([3, 3]), 1),
                                  np.argsort(-np.array([3, 3]), kind="stable"))


def test_multirow_sharded_fused_matches_xla():
    """The shard_map-wrapped fused kernel with row grouping under a tp=2
    mesh keeps the XLA reference contract (per-shard group walks)."""
    import jax.numpy as jnp
    from lmrs_tpu.ops.paged_attention import (
        paged_decode_fused_sharded,
        paged_decode_xla,
    )

    b, h, kh, hd, ps, n_pages = 3, 8, 2, 128, 16, 12
    rng = jax.random.split(jax.random.PRNGKey(2), 5)
    k_pages = jax.random.normal(rng[0], (n_pages, kh, ps, hd), jnp.float32)
    v_pages = jax.random.normal(rng[1], (n_pages, kh, ps, hd), jnp.float32)
    q = jax.random.normal(rng[2], (b, h, hd), jnp.float32)
    k_new = jax.random.normal(rng[3], (b, kh, hd), jnp.float32)
    v_new = jax.random.normal(rng[4], (b, kh, hd), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 0]], jnp.int32)
    kv_lens = jnp.asarray([40, 17, 33], jnp.int32)

    pos = kv_lens - 1
    page = jnp.take_along_axis(tables, (pos // ps)[:, None], 1)[:, 0]
    off = pos % ps
    k_ref = k_pages.at[page, :, off].set(k_new)
    v_ref = v_pages.at[page, :, off].set(v_new)
    want = paged_decode_xla(q, k_ref, v_ref, tables, kv_lens)

    got, k_out, v_out = paged_decode_fused_sharded(
        q, k_new, v_new, k_pages, v_pages, tables, kv_lens,
        _tp_mesh(), interpret=True, row_group=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # page 0 is the reserved null page (engine contract: never read as
    # data); with b % G != 0 the grouped kernel's padded row RMWs it as
    # scratch, so the pool comparison starts at page 1
    np.testing.assert_array_equal(np.asarray(k_out)[1:], np.asarray(k_ref)[1:])
    np.testing.assert_array_equal(np.asarray(v_out)[1:], np.asarray(v_ref)[1:])


def test_multi_token_verify_out_of_span_skips_on_both_paths():
    """A degenerate row whose length exceeds the table span (stale-length
    class) must write NOTHING on BOTH implementations — the XLA reference
    previously clipped onto the last tabled page and scribbled real rows
    (round-3 review finding); real pages must be untouched and the two
    paths must agree."""
    import jax.numpy as jnp
    from lmrs_tpu.ops.paged_attention import (
        paged_decode_multi_xla,
        paged_decode_pallas_multi,
    )

    b, t, h, kh, hd, ps, n_pages = 2, 4, 8, 2, 128, 16, 8
    rng = jax.random.split(jax.random.PRNGKey(21), 5)
    k_pages = jax.random.normal(rng[0], (n_pages, kh, ps, hd), jnp.float32)
    v_pages = jax.random.normal(rng[1], (n_pages, kh, ps, hd), jnp.float32)
    q = jax.random.normal(rng[2], (b, t, h, hd), jnp.float32)
    k_new = jax.random.normal(rng[3], (b, t, kh, hd), jnp.float32)
    v_new = jax.random.normal(rng[4], (b, t, kh, hd), jnp.float32)
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)  # span 32 tokens
    # row 0 normal; row 1 claims 100 tokens — its whole T-token span lies
    # past the table capacity, so no write may land anywhere real
    kv_lens = jnp.asarray([10, 100], jnp.int32)

    want, k_ref, v_ref = paged_decode_multi_xla(
        q, k_new, v_new, k_pages, v_pages, tables, kv_lens)
    got, k_out, v_out = paged_decode_pallas_multi(
        q, k_new, v_new, k_pages, v_pages, tables, kv_lens, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # row 1's real pages (3, 4) untouched on BOTH paths
    for pool_out, pool_in in ((k_ref, k_pages), (v_ref, v_pages),
                              (k_out, k_pages), (v_out, v_pages)):
        np.testing.assert_array_equal(np.asarray(pool_out[3:5]),
                                      np.asarray(pool_in[3:5]))


def test_multirow_engine_greedy_ab_parity(monkeypatch):
    """End-to-end A/B through the real continuous scheduler (interpret
    kernels): greedy output with the multi-row kernel + length-balanced
    dispatch permutation must be token-identical to LMRS_MULTIROW=0 (the
    per-row control) — the same convention as the LMRS_PACK_PREFILL A/B.
    Ragged prompt lengths so the balancer actually permutes."""
    from lmrs_tpu.config import EngineConfig, ModelConfig
    from lmrs_tpu.engine.api import GenerationRequest
    from lmrs_tpu.engine.jax_engine import JaxEngine

    monkeypatch.setenv("LMRS_FORCE_KERNELS", "interpret")
    mc = ModelConfig(vocab_size=512, dim=512, n_layers=2, n_heads=4,
                     n_kv_heads=2, hidden_dim=256, max_seq_len=256,
                     dtype="float32")

    def run():
        ec = EngineConfig(backend="jax", scheduler="continuous",
                          max_tokens=8, max_batch_slots=3, seed=0,
                          page_size=32, decode_block=4, retry_delay=0.0,
                          decode_row_group=2)
        eng = JaxEngine(ec, mc)
        reqs = [GenerationRequest(prompt=f"multi row probe {i} " * (1 + 3 * i),
                                  request_id=i, temperature=0.0,
                                  max_new_tokens=8) for i in range(3)]
        out = eng.generate_batch(reqs)
        assert all(r.error is None for r in out)
        return [r.text for r in out]

    monkeypatch.setenv("LMRS_MULTIROW", "0")
    want = run()
    monkeypatch.delenv("LMRS_MULTIROW")
    got = run()
    assert got == want
