"""Unified ragged-span dispatch (ISSUE 16): one kernel for every phase.

Two layers of contract:

* ops-level — ``ragged_spans_pallas`` (interpret mode) must reproduce the
  kernels it retires at their own shapes: the fused single-token decode
  kernel at q_len=1 spans, the multi-token verify kernel at q_len=T
  spans, and the ``ragged_spans_xla`` scatter+gather reference on mixed
  span lists (decode rows + a long prefill-slice row + inactive rows),
  bf16-free f32 inputs and int8 pools both.  Pool comparisons are
  restricted to each row's VALID prefix (positions < base + q_len): the
  span kernel's tile-padding tokens write garbage at FUTURE positions by
  the mixed path's existing convention, where the references park them
  on the null page.

* scheduler-level — greedy outputs of the span-dispatched mixed step must
  be token-identical with alternating dispatch (``mixed_batch=False``)
  across the prefix-cache x speculation x int8-KV matrix, and the span
  family must be the only mixed family a scheduler compiles.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import PAGED_TOL

from lmrs_tpu.config import EngineConfig, ModelConfig
from lmrs_tpu.engine.api import GenerationRequest
from lmrs_tpu.engine.jax_engine import JaxEngine
from lmrs_tpu.ops.paged_attention import (
    SPAN_QT,
    pack_spans,
    paged_decode_pallas_fused,
    paged_decode_pallas_multi,
    ragged_spans_pallas,
    ragged_spans_xla,
    span_walk_counts,
)

# --------------------------------------------------------------- ops level


def _span_fixture(seed, q_lens, h=8, kh=4, hd=128, ps=16, n_pages=32,
                  width=3, floor=16):
    """Flat span buffers + per-row pools/tables.  Every flat row gets
    random q/k/v — including the alignment-padding rows — so parity also
    proves the padding is masked, not merely zero."""
    b = len(q_lens)
    qs, total = pack_spans(np.asarray(q_lens, np.int32), floor=floor)
    rng = jax.random.split(jax.random.PRNGKey(seed), 5)
    qf = jax.random.normal(rng[0], (total, h, hd), jnp.float32)
    knf = jax.random.normal(rng[1], (total, kh, hd), jnp.float32)
    vnf = jax.random.normal(rng[2], (total, kh, hd), jnp.float32)
    k_pages = jax.random.normal(rng[3], (n_pages, kh, ps, hd), jnp.float32)
    v_pages = jax.random.normal(rng[4], (n_pages, kh, ps, hd), jnp.float32)
    tables = jnp.asarray(
        np.random.default_rng(seed).permutation(n_pages - 1)[: b * width]
        .reshape(b, width) + 1, jnp.int32)
    row_flat = np.full((total,), b, np.int32)
    for i, (s, l) in enumerate(zip(qs, q_lens)):
        row_flat[s:s + l] = i
    return qs, total, qf, knf, vnf, k_pages, v_pages, tables, row_flat


def _valid_windows(pool, tables, upto, ps):
    """Per-row gathered window prefix [upto[b], K, hd] — the region both
    implementations must agree on bit-for-bit (past it lies the span
    kernel's future-position padding garbage)."""
    win = np.asarray(pool)[np.asarray(tables)]          # [B, W, K, ps, hd]
    win = win.transpose(0, 1, 3, 2, 4).reshape(
        win.shape[0], -1, win.shape[2], win.shape[4])   # [B, W*ps, K, hd]
    return [win[i, :int(u)] for i, u in enumerate(np.asarray(upto))]


def _assert_pool_parity(got_pool, ref_pool, tables, upto, ps):
    for g, r in zip(_valid_windows(got_pool, tables, upto, ps),
                    _valid_windows(ref_pool, tables, upto, ps)):
        np.testing.assert_array_equal(g, r)


def test_rpa_decode_parity_vs_fused():
    """q_len=1 spans vs the retired fused single-token decode kernel:
    same attention outputs (each span's one real row) and same pool
    contents over every row's valid prefix.  Ragged bases including a
    fresh (base 0) row and an inactive (q_len=0) row."""
    q_lens = [1, 0, 1, 1, 1]
    bases = np.asarray([39, 0, 16, 47, 0], np.int32)
    ps = 16
    qs, total, qf, knf, vnf, kp, vp, tables, row_flat = _span_fixture(
        0, q_lens, ps=ps)

    got, k_out, v_out = ragged_spans_pallas(
        qf, knf, vnf, kp, vp, tables, jnp.asarray(bases),
        jnp.asarray(qs), jnp.asarray(q_lens, jnp.int32), interpret=True)

    # the fused kernel's kv_lens INCLUDE the written token; inactive = 0
    q1 = jnp.stack([qf[s] for s in qs])
    kn1 = jnp.stack([knf[s] for s in qs])
    vn1 = jnp.stack([vnf[s] for s in qs])
    fused_lens = jnp.asarray(
        [b + l for b, l in zip(bases, q_lens)], jnp.int32)
    want, k_ref, v_ref = paged_decode_pallas_fused(
        q1, kn1, vn1, kp, vp, tables, fused_lens, interpret=True)

    for i, l in enumerate(q_lens):
        if l:
            np.testing.assert_allclose(np.asarray(got[qs[i]]),
                                       np.asarray(want[i]),
                                       rtol=2e-5, atol=2e-5)
    upto = bases + np.asarray(q_lens)
    _assert_pool_parity(k_out, k_ref, tables, upto, ps)
    _assert_pool_parity(v_out, v_ref, tables, upto, ps)


def test_rpa_verify_parity_vs_multi():
    """q_len=T spans vs the retired multi-token verify kernel: all T
    per-token outputs and the written span, across page-straddling,
    in-page, window-straddling, and fresh (base 0) rows."""
    t, ps = 3, 16
    bases = np.asarray([15, 3, 32, 0], np.int32)
    q_lens = [t] * 4
    qs, total, qf, knf, vnf, kp, vp, tables, row_flat = _span_fixture(
        1, q_lens, ps=ps)

    got, k_out, v_out = ragged_spans_pallas(
        qf, knf, vnf, kp, vp, tables, jnp.asarray(bases),
        jnp.asarray(qs), jnp.asarray(q_lens, jnp.int32), interpret=True)

    qm = jnp.stack([qf[s:s + t] for s in qs])       # [B, T, H, hd]
    knm = jnp.stack([knf[s:s + t] for s in qs])
    vnm = jnp.stack([vnf[s:s + t] for s in qs])
    multi_lens = jnp.asarray(bases + t, jnp.int32)  # includes the T tokens
    want, k_ref, v_ref = paged_decode_pallas_multi(
        qm, knm, vnm, kp, vp, tables, multi_lens, interpret=True)

    for i, s in enumerate(qs):
        np.testing.assert_allclose(np.asarray(got[s:s + t]),
                                   np.asarray(want[i]),
                                   rtol=2e-5, atol=2e-5)
    upto = bases + t
    _assert_pool_parity(k_out, k_ref, tables, upto, ps)
    _assert_pool_parity(v_out, v_ref, tables, upto, ps)


@pytest.mark.parametrize("pool", ["f32", "bf16"])
def test_rpa_mixed_spans_match_xla_reference(pool):
    """A genuinely MIXED span list — decode rows, a long prefill-slice
    row whose length is not a SPAN_QT multiple, and an inactive row —
    against the scatter+gather reference (the sp>1 / CPU-fallback path):
    in-span outputs agree and pools agree over every valid prefix.  All
    spans are narrow (SPAN_QT-token tiles through the decode walk): on a
    bf16 pool the walk multiplies in bf16 with its float32 operands split
    into stacked rows, and owes the float32 reference the same tolerance."""
    q_lens = [1, 13, 1, 0]
    bases = np.asarray([20, 7, 0, 0], np.int32)
    ps = 16
    qs, total, qf, knf, vnf, kp, vp, tables, row_flat = _span_fixture(
        2, q_lens, kh=2, h=4, ps=ps)
    ref_pools = kp, vp
    if pool == "bf16":
        # new K/V the pool holds exactly; the reference reads float32 pages
        bf = jnp.bfloat16
        knf, vnf = knf.astype(bf).astype(jnp.float32), vnf.astype(bf).astype(
            jnp.float32)
        kp, vp = kp.astype(bf), vp.astype(bf)
        ref_pools = kp.astype(jnp.float32), vp.astype(jnp.float32)

    got, k_out, v_out = ragged_spans_pallas(
        qf, knf, vnf, kp, vp, tables, jnp.asarray(bases),
        jnp.asarray(qs), jnp.asarray(q_lens, jnp.int32), interpret=True)
    want, k_ref, v_ref = ragged_spans_xla(
        qf, knf, vnf, *ref_pools, tables, jnp.asarray(bases),
        jnp.asarray(qs), jnp.asarray(q_lens, jnp.int32),
        jnp.asarray(row_flat))

    in_span = row_flat < len(q_lens)
    np.testing.assert_allclose(np.asarray(got)[in_span],
                               np.asarray(want)[in_span],
                               rtol=PAGED_TOL, atol=PAGED_TOL)
    upto = bases + np.asarray(q_lens)
    _assert_pool_parity(k_out.astype(jnp.float32), k_ref, tables, upto, ps)
    _assert_pool_parity(v_out.astype(jnp.float32), v_ref, tables, upto, ps)


def test_rpa_mixed_spans_int8_parity():
    """The same mixed span list over int8 pools (the composition the
    legacy dispatcher forbade): per-token quantization through the span
    RMW must reproduce the XLA reference bit-for-bit over every valid
    prefix, and the dequantized walk must agree on in-span outputs."""
    q_lens = [1, 13, 1, 0]
    bases = np.asarray([20, 7, 0, 0], np.int32)
    b, kh, hd, ps, n_pages = 4, 4, 128, 64, 12
    qs, total, qf, knf, vnf, _, _, _, row_flat = _span_fixture(
        3, q_lens, ps=ps, n_pages=n_pages, width=2)
    rng = np.random.default_rng(3)
    kq = jnp.asarray(rng.integers(-127, 128, (n_pages, kh, ps, hd)),
                     jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (n_pages, kh, ps, hd)),
                     jnp.int8)
    tables = jnp.asarray(rng.permutation(n_pages - 1)[: b * 2]
                         .reshape(b, 2) + 1, jnp.int32)
    ks = jnp.asarray(rng.uniform(0.01, 0.05, (b, kh, hd)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.01, 0.05, (b, kh, hd)), jnp.float32)

    got, k_out, v_out = ragged_spans_pallas(
        qf, knf, vnf, kq, vq, tables, jnp.asarray(bases),
        jnp.asarray(qs), jnp.asarray(q_lens, jnp.int32), interpret=True,
        kscale=ks, vscale=vs)
    want, k_ref, v_ref = ragged_spans_xla(
        qf, knf, vnf, kq, vq, tables, jnp.asarray(bases),
        jnp.asarray(qs), jnp.asarray(q_lens, jnp.int32),
        jnp.asarray(row_flat), kv_scales=(ks, vs))

    in_span = row_flat < b
    np.testing.assert_allclose(np.asarray(got)[in_span],
                               np.asarray(want)[in_span],
                               rtol=2e-5, atol=2e-5)
    upto = bases + np.asarray(q_lens)
    _assert_pool_parity(k_out, k_ref, tables, upto, ps)
    _assert_pool_parity(v_out, v_ref, tables, upto, ps)


# the wide tile, shrunk for the interpreter through the kernel's own static
# argument: W tokens a tile where the module constant is SPAN_QT_WIDE
_W = 32
_WIDE_CASES = {
    # span lengths around the tile, behind a base that is not page-aligned
    # (one flat-buffer size for all of them: one compile in the interpreter)
    "len-1": dict(q_lens=[1], bases=[21]),
    "len-W-1": dict(q_lens=[_W - 1], bases=[21]),
    "len-W": dict(q_lens=[_W], bases=[21]),
    "len-W+1": dict(q_lens=[_W + 1], bases=[21]),
    "len-2W+5": dict(q_lens=[2 * _W + 5], bases=[21]),
    "len-2W+5-fresh": dict(q_lens=[2 * _W + 5], bases=[0]),
    "len-2W+5-nrep4": dict(q_lens=[2 * _W + 5], bases=[21], n_rep=4),
    # a page larger than the tile (the 512-token pages of the 8B presets)
    "len-2W+5-ps64": dict(q_lens=[2 * _W + 5], bases=[70], ps=64, width=3),
    # decode rows, an inactive row, one long span and a one-tile span
    "mixed": dict(q_lens=[1, 0, 2 * _W + 5, 1, _W], bases=[20, 0, 7, 0, 48]),
    # the position cap cuts the second wide tile (positions 10..79, cap 64)
    "max-pos": dict(q_lens=[70, 3], bases=[10, 62], max_pos=64),
    "bf16": dict(q_lens=[1, 0, 2 * _W + 5, _W - 1], bases=[20, 0, 7, 3],
                 pool="bf16"),
    "int8": dict(q_lens=[1, 0, 2 * _W + 5, _W - 1], bases=[20, 0, 7, 3],
                 ps=32, width=4, pool="int8"),
    "int8-nrep4-max-pos": dict(q_lens=[2 * _W + 5, 2], bases=[40, 100],
                               ps=32, width=4, n_rep=4, pool="int8",
                               max_pos=96),
}


@pytest.mark.parametrize("case", list(_WIDE_CASES), ids=list(_WIDE_CASES))
def test_rpa_wide_tile_parity(case):
    """The wide query tile against ``ragged_spans_xla``: a span of at
    least W tokens is walked W queries a page pass, a shorter one in
    SPAN_QT tiles, and a dispatch mixes both.  In-span outputs agree,
    pools agree over every row's valid prefix, and a wide row leaves its
    whole page window as the reference has it: the wide path writes real
    tokens below the position cap only, no padding garbage."""
    c = dict(ps=16, width=8, n_rep=2, pool="f32", max_pos=None)
    c.update(_WIDE_CASES[case])
    ps, width = c["ps"], c["width"]
    # five rows whatever the case (the spare ones inactive): the cases of
    # one geometry share one trace of the interpreted kernel
    q_lens = c["q_lens"] + [0] * (5 - len(c["q_lens"]))
    bases = np.asarray(c["bases"] + [0] * (5 - len(c["bases"])), np.int32)
    b, kh, hd = len(q_lens), 2, 128
    n_pages = 1 + b * width
    qs, total, qf, knf, vnf, kp, vp, _, row_flat = _span_fixture(
        11, q_lens, h=kh * c["n_rep"], kh=kh, hd=hd, ps=ps,
        n_pages=n_pages, width=width,
        floor=4 * _W - 8)  # the longest case's: one flat size for all
                           # (under _W the wide path would not compile in)
    rng = np.random.default_rng(11)
    tables = jnp.asarray(
        rng.permutation(n_pages - 1).reshape(b, width) + 1, jnp.int32)
    kw, xkw, tol = {}, {}, 2e-5
    ref_q, ref_pools = qf, None
    if c["pool"] == "bf16":
        # the engine's own dtypes: bf16 q and new K/V against a bf16 pool
        qf, knf, vnf, kp, vp = (x.astype(jnp.bfloat16)
                                for x in (qf, knf, vnf, kp, vp))
        # attention reference in f32 over the same bf16 numbers (the XLA
        # twin rounds its bf16 logits; the kernel keeps them f32)
        ref_q, tol = qf.astype(jnp.float32), 1e-2
        ref_pools = (kp.astype(jnp.float32), vp.astype(jnp.float32))
    elif c["pool"] == "int8":
        kp = jnp.asarray(rng.integers(-127, 128, kp.shape), jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, vp.shape), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.05, (b, kh, hd)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.05, (b, kh, hd)), jnp.float32)
        kw, xkw = dict(kscale=ks, vscale=vs), dict(kv_scales=(ks, vs))
    spans = (jnp.asarray(bases), jnp.asarray(qs),
             jnp.asarray(q_lens, jnp.int32))

    got, k_out, v_out = ragged_spans_pallas(
        qf, knf, vnf, kp, vp, tables, *spans, interpret=True,
        max_pos=c["max_pos"], qt_wide=_W, **kw)
    want, k_ref, v_ref = ragged_spans_xla(
        qf, knf, vnf, kp, vp, tables, *spans, jnp.asarray(row_flat),
        max_pos=c["max_pos"], **xkw)
    if ref_pools is not None:
        want, _, _ = ragged_spans_xla(
            ref_q, knf.astype(jnp.float32), vnf.astype(jnp.float32),
            *ref_pools, tables, *spans, jnp.asarray(row_flat),
            max_pos=c["max_pos"])

    in_span = row_flat < b
    np.testing.assert_allclose(
        np.asarray(got.astype(jnp.float32))[in_span],
        np.asarray(want.astype(jnp.float32))[in_span], rtol=tol, atol=tol)
    cap = width * ps if c["max_pos"] is None else min(width * ps,
                                                      c["max_pos"])
    # narrow rows: their valid prefix; wide rows: the whole window
    upto = np.where(np.asarray(q_lens) >= _W, width * ps,
                    np.minimum(bases + np.asarray(q_lens), cap))
    _assert_pool_parity(k_out, k_ref, tables, upto, ps)
    _assert_pool_parity(v_out, v_ref, tables, upto, ps)


def _brute_walk_counts(q_lens, bases, ps, w_pages, max_pos, wide):
    """``span_walk_counts`` the slow way: every tile of every row, one at
    a time, by the kernel's rule as its docstring states it."""
    cap = w_pages * ps if max_pos is None else min(w_pages * ps, max_pos)
    wide_tokens = reads = 0
    for ql, base in zip(q_lens, bases):
        if ql >= wide:
            wide_tokens += ql
            ql8 = -(-ql // SPAN_QT) * SPAN_QT
            for i in range(-(-ql8 // wide)):
                t0 = min(i * wide, ql8 - wide)
                end = min(base + t0 + min(wide, ql - t0), cap)
                reads += len(range(0, end, ps))
        else:
            for i in range(-(-ql // SPAN_QT)):
                reads += min(len(range(0, base + (i + 1) * SPAN_QT, ps)),
                             w_pages)
    return wide_tokens, reads


@pytest.mark.parametrize("wide,ps,w_pages,max_pos", [
    (256, 128, 16, 2048), (128, 128, 16, None), (32, 16, 8, 100)])
def test_span_walk_counts_match_brute_force(wide, ps, w_pages, max_pos):
    """The host rule behind the dispatch record's ``wide_tokens`` /
    ``kv_page_reads`` against a tile-by-tile count over the same spans,
    and the cell's own arithmetic: a 1,251-token span behind 512 cached
    tokens is 5 wide tiles walking 6+8+10+12+14 pages where 8-token tiles
    walked about 1,480."""
    rng = np.random.default_rng(wide)
    span = w_pages * ps
    for _ in range(20):
        q_lens = rng.integers(0, span // 2, 12)
        q_lens[rng.integers(0, 12, 3)] = [0, 1, wide]
        bases = rng.integers(0, span // 2, 12)
        got = span_walk_counts(q_lens, bases, ps, w_pages, max_pos,
                               qt_wide=wide)
        assert got == _brute_walk_counts(
            q_lens.tolist(), bases.tolist(), ps, w_pages, max_pos, wide)
        assert got[0] <= q_lens.sum()
    assert span_walk_counts([1251], [512], 128, 16, 2048,
                            qt_wide=256) == (1251, 50)
    narrow = span_walk_counts([1251], [512], 128, 16, 2048, qt_wide=4096)
    assert narrow[0] == 0 and 1400 < narrow[1] < 1500


# --------------------------------------------------------- scheduler level


def tiny_model():
    return ModelConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                       n_kv_heads=2, hidden_dim=128, max_seq_len=256,
                       dtype="float32")


def _cfg(**kw) -> EngineConfig:
    base = dict(backend="jax", scheduler="continuous", max_tokens=16,
                max_batch_slots=2, seed=0, decode_block=3,
                prefill_chunk=64, mixed_batch=True)
    base.update(kw)
    return EngineConfig(**base)


def _mix_requests(n: int = 4) -> list[GenerationRequest]:
    pre = "shared span preamble alpha beta "
    reqs = []
    for i in range(n):
        body = (f"request {i} " + "span probe words here " * (1 + 5 * (i % 2)))
        reqs.append(GenerationRequest(
            prompt=(pre if i % 2 else "") + body, request_id=i,
            temperature=0.0, max_new_tokens=12 + i))
    return reqs


def _run(cfg: EngineConfig, mc, reqs):
    """Returns (texts, metrics, keys of the programs that ran) for one
    engine run; audits clean."""
    eng = JaxEngine(cfg, mc)
    out = eng.generate_batch(reqs)
    sched = eng._scheduler
    assert sched.audit() == []
    assert all(r.error is None for r in out)
    texts = [(r.text, r.finish_reason, r.completion_tokens) for r in out]
    m = dict(sched.metrics)
    ran = set(sched._ran_ok)
    eng.shutdown()
    return texts, m, ran


@pytest.mark.parametrize("prefix_cache", [True, False])
@pytest.mark.parametrize("spec_k", [0, 3])
def test_rpa_greedy_identity_matrix(prefix_cache, spec_k):
    """Span-dispatched mixed steps vs alternating dispatch
    (``mixed_batch=False``): greedy token identity across prefix-cache x
    speculation — the ISSUE 16 acceptance bar.  The span arm must
    actually dispatch span programs."""
    mc = tiny_model()
    reqs = _mix_requests()
    cfg = lambda mixed: _cfg(prefix_cache=prefix_cache, speculate_k=spec_k,
                             mixed_batch=mixed)
    want, m_off, _ = _run(cfg(False), mc, reqs)
    assert m_off["rpa_dispatches"] == 0  # the reference arm alternates
    got, m_on, _ = _run(cfg(True), mc, reqs)
    assert m_on["rpa_dispatches"] > 0, "span path not exercised"
    assert got == want


@pytest.mark.parametrize("spec_k", [0, 3])
def test_rpa_greedy_identity_int8_kv(spec_k):
    """int8 KV x mixed (x spec) runs through the span path with greedy
    outputs identical to alternating dispatch of the same int8 engine."""
    mc = tiny_model()
    reqs = _mix_requests()
    cfg = lambda mixed: _cfg(page_size=32, kv_quantize="int8",
                             prefix_cache=False, speculate_k=spec_k,
                             mixed_batch=mixed)
    want, m_off, _ = _run(cfg(False), mc, reqs)
    assert m_off["rpa_dispatches"] == 0
    got, m_on, _ = _run(cfg(True), mc, reqs)
    assert m_on["rpa_dispatches"] > 0, "int8 span path not exercised"
    assert m_on["mixed_dispatches"] > 0, "int8 x mixed not armed"
    assert got == want


def test_rpa_is_the_only_mixed_family(monkeypatch):
    """The retired switch is no longer read: a scheduler built with
    ``LMRS_RPA=0`` in the environment serves its mixed steps with span
    programs, and no program of another mixed family runs."""
    monkeypatch.setenv("LMRS_RPA", "0")
    _, m, ran = _run(_cfg(), tiny_model(), _mix_requests())
    assert m["mixed_dispatches"] > 0 and m["rpa_dispatches"] > 0
    families = {k[0] for k in ran if isinstance(k[0], str)}
    assert "rpa" in families
    assert families <= {"rpa", "rpa_spec", "prefill", "packed", "decode",
                        "specfn"}, families
    assert m["rpa_compile_shapes"] == len(
        {k for k in ran if k[0] in ("rpa", "rpa_spec")})


def test_rpa_report_block_shape():
    """The ``rpa`` block of ``metrics_report()``: keys exist, dispatch
    counts agree with the counters, compile_shapes stays cumulative."""
    mc = tiny_model()
    eng = JaxEngine(_cfg(), mc)
    eng.generate_batch(_mix_requests())
    sched = eng._scheduler
    m = sched.metrics
    blk = sched.metrics_report()["rpa"]
    assert blk["enabled"] is True
    assert blk["dispatches"] == m["rpa_dispatches"]
    assert blk["span_tokens"] == m["rpa_span_tokens"]
    assert blk["compile_shapes"] == m["rpa_compile_shapes"]
    eng.shutdown()


def test_mock_engine_rpa_block():
    """No-device parity: the mock exposes the same ``rpa`` metrics
    block."""
    from lmrs_tpu.engine.mock import MockEngine

    reqs = [GenerationRequest(prompt="one " * 30, request_id=0),
            GenerationRequest(prompt="two " * 50, request_id=1),
            GenerationRequest(prompt="three " * 20, request_id=2)]
    eng = MockEngine(mixed_token_budget=64)
    assert eng.generate_batch(reqs)
    blk = eng.engine_metrics()["rpa"]
    assert blk["enabled"] and blk["dispatches"] > 0
    assert blk["span_tokens"] >= blk["dispatches"]
    assert blk["compile_shapes"] >= 1
