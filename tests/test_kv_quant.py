"""Int8 KV-cache quantization (EngineConfig.kv_quantize, ops/quant.py KV
section): per-(slot, kv head, channel) scales fixed at prefill, int8 page
pools, dequant at every pool reader.  No reference counterpart — the
reference has no KV cache at all (the model is behind OpenAI's API,
/root/reference/llm_executor.py:250-326)."""

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import PAGED_TOL

from lmrs_tpu.config import EngineConfig, ModelConfig
from lmrs_tpu.engine.api import GenerationRequest
from lmrs_tpu.engine.jax_engine import JaxEngine
from lmrs_tpu.ops.quant import kv_dequant, kv_quant, kv_scale_from


def tiny_model():
    # page_size 32 gate: int8 VMEM tiles are (32, 128)
    return ModelConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                       n_kv_heads=2, hidden_dim=128, max_seq_len=256,
                       dtype="float32")


def make_engine(kv: str | None, **kw):
    kw.setdefault("page_size", 32)
    ec = EngineConfig(backend="jax", scheduler="continuous", max_tokens=24,
                      max_batch_slots=2, seed=0, kv_quantize=kv,
                      retry_delay=0.0, **kw)
    return JaxEngine(ec, tiny_model())


# ------------------------------------------------------------- unit level


def test_kv_roundtrip_error_bound():
    """Symmetric per-channel int8: |x - deq(quant(x))| <= scale/2, with the
    scale computed only from VALID rows."""
    rng = np.random.default_rng(0)
    kv = jnp.asarray(rng.standard_normal((3, 16, 2, 8)) * 4.0, jnp.float32)
    valid = jnp.asarray(np.arange(16)[None, :] < np.array([16, 7, 1])[:, None])
    s = kv_scale_from(kv, valid)
    assert s.shape == (3, 2, 8)
    back = kv_dequant(kv_quant(kv, s), s, jnp.float32)
    err = jnp.abs(back - kv) * valid[:, :, None, None]
    assert float(jnp.max(err - s[:, None] / 2)) <= 1e-6


def test_kv_scale_ignores_masked_rows():
    """A huge outlier in a masked (padding) position must not inflate the
    scale."""
    kv = jnp.zeros((1, 4, 1, 4), jnp.float32).at[0, 3].set(1e6)
    kv = kv.at[0, 0].set(2.0)
    valid = jnp.asarray([[True, True, True, False]])
    s = kv_scale_from(kv, valid)
    assert float(jnp.max(s)) <= 2.0 / 127.0 + 1e-6


# ----------------------------------------------------------- engine level


@pytest.fixture(scope="module")
def engines():
    return make_engine(None), make_engine("int8")


def test_int8_pools_and_scales_materialize(engines):
    bf, q = engines
    assert bf._scheduler.cache.k.dtype == jnp.dtype(jnp.float32)
    assert q._scheduler.cache.k.dtype == jnp.dtype(jnp.int8)
    assert q._scheduler.kscale.shape == (2, 2, 2, 16)  # [L, B, K, hd]
    assert bf._scheduler.kscale is None


def test_generation_close_to_fullprecision(engines):
    """Greedy decode with int8 KV must track the full-precision engine: the
    first continuation token comes from a prefill whose attention reads the
    FRESH K/V (no quant error), so it must match exactly; later tokens may
    diverge on a random-weight model, but output must be well-formed and
    deterministic."""
    bf, q = engines
    reqs = [GenerationRequest(prompt="the quick brown fox jumps", request_id=0,
                              temperature=0.0, max_new_tokens=10)]
    out_bf = bf.generate_batch(list(reqs))
    out_q = q.generate_batch(list(reqs))
    assert out_q[0].error is None
    assert out_q[0].completion_tokens > 0
    # same first sampled token: prefill logits see no pool reads
    assert out_q[0].text[:1] == out_bf[0].text[:1]
    # deterministic under the same seed: rerun reproduces exactly
    q2 = make_engine("int8")
    out_q2 = q2.generate_batch(
        [GenerationRequest(prompt="the quick brown fox jumps", request_id=0,
                           temperature=0.0, max_new_tokens=10)])
    assert out_q2[0].text == out_q[0].text


def test_scales_land_on_the_right_slots(engines):
    """After serving requests, each slot's scale rows hold real (non-init)
    values set by ITS prefill — the row->slot scatter contract."""
    _, q = engines
    reqs = [GenerationRequest(prompt=f"slot check {i} " * (i + 2),
                              request_id=i, temperature=0.0, max_new_tokens=3)
            for i in range(2)]
    out = q.generate_batch(reqs)
    assert all(r.error is None for r in out)
    ks = np.asarray(q._scheduler.kscale)
    # both slots served a prompt: no row can still be all-ones init
    for b in range(2):
        assert not np.allclose(ks[:, b], 1.0), f"slot {b} scales never set"


def test_decode_logits_match_fake_quant_reference():
    """The int8 pool path must equal a full-precision run whose pool
    CONTENTS were quantize-dequantized with the same scales — same math,
    different storage — to float tolerance.  Wires checked: scatter
    quantizes with the right rows' scales, gather dequantizes with the
    same, scale rows map dispatch rows to slots."""
    from lmrs_tpu.models.transformer import forward_paged, init_params

    cfg = tiny_model()
    import jax

    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S, K, hd, ps = 2, 16, cfg.n_kv_heads, cfg.hd, 32
    npages = cfg.n_layers * 8
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(1, 500, (B, S), dtype=np.int32))
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)  # logical pages
    lens = jnp.asarray([S, S - 5], jnp.int32)

    # int8 run: fresh prefill computes scales + writes int8
    kq = jnp.zeros((npages, K, ps, hd), jnp.int8)
    vq = jnp.zeros((npages, K, ps, hd), jnp.int8)
    ksc = jnp.ones((cfg.n_layers, B, K, hd), jnp.float32)
    vsc = jnp.ones((cfg.n_layers, B, K, hd), jnp.float32)
    lg_q, kq, vq, (ksc, vsc) = forward_paged(
        params, cfg, tokens, positions, kq, vq, tables, lens,
        cfg.max_seq_len, kv_scales=(ksc, vsc))

    # full-precision run, then fake-quantize the pool contents in place
    kf = jnp.zeros((npages, K, ps, hd), jnp.float32)
    vf = jnp.zeros((npages, K, ps, hd), jnp.float32)
    lg_f, kf, vf = forward_paged(
        params, cfg, tokens, positions, kf, vf, tables, lens,
        cfg.max_seq_len)
    assert np.allclose(np.asarray(lg_q), np.asarray(lg_f), atol=1e-3), \
        "prefill logits must be identical: attention reads fresh K/V"

    # decode one token on both; the int8 path reads the quantized pool, the
    # reference reads a pool holding deq(quant(.)) of the same values
    ksc_n = np.asarray(ksc)
    vsc_n = np.asarray(vsc)
    kf_n, vf_n = np.array(kf), np.array(vf)  # writable copies
    for li in range(cfg.n_layers):
        for b in range(B):
            for w_, pg in enumerate(np.asarray(tables)[b]):
                g = li * 8 + pg
                sk = ksc_n[li, b][:, None]  # [K, 1, hd]
                sv = vsc_n[li, b][:, None]
                kf_n[g] = np.clip(np.round(kf_n[g] / sk), -127, 127) * sk
                vf_n[g] = np.clip(np.round(vf_n[g] / sv), -127, 127) * sv
    # the WRITE path must be exact: dequantizing the int8 pool reproduces
    # the fake-quantized full-precision pool bit-for-bit (same scales, same
    # round/clip) on every tabled page
    for li in range(cfg.n_layers):
        for b in range(B):
            n_valid = int(np.asarray(lens)[b])
            for w_, pg in enumerate(np.asarray(tables)[b]):
                g = li * 8 + pg
                rows = slice(0, max(0, min(ps, n_valid - w_ * ps)))
                deq_k = np.asarray(kq)[g].astype(np.float32) \
                    * ksc_n[li, b][:, None]
                np.testing.assert_allclose(
                    deq_k[:, rows], kf_n[g][:, rows], atol=1e-5)

    tok = jnp.asarray([[7], [9]], jnp.int32)
    pos1 = lens[:, None]
    lens1 = lens + 1
    lg_q1, *_ = forward_paged(
        params, cfg, tok, pos1, kq, vq, tables, lens1, cfg.max_seq_len,
        kv_scales=(ksc, vsc))
    lg_f1, *_ = forward_paged(
        params, cfg, tok, pos1, jnp.asarray(kf_n), jnp.asarray(vf_n),
        tables, lens1, cfg.max_seq_len)
    # the one remaining divergence source: the int8 path quantizes the NEW
    # decode token's K/V write, the reference writes it full-precision — a
    # single attended row of quant error, bounded well under a wiring bug
    # (wrong scale rows / pages show up as O(1) diffs)
    d = np.abs(np.asarray(lg_q1) - np.asarray(lg_f1)).max()
    assert d < 0.2, d


def test_kv_quant_gates():
    with pytest.raises(ValueError, match="page_size"):
        make_engine("int8", page_size=24)
    with pytest.raises(ValueError, match="kv_quantize"):
        EngineConfig(kv_quantize="int4")


def test_spec_int8_greedy_matches_plain_int8():
    """Speculation composes with int8 KV (VERDICT r4 item 4: the
    construction gate fell): greedy speculative decode on int8 pools must
    emit token-for-token what plain int8 decode emits — speculation is a
    scheduling optimization, and the draft rows are quantized with the
    same frozen slot scales the plain path uses."""
    reqs = [GenerationRequest(
        prompt="the cat sat on the mat the cat sat " * 3,
        request_id=i, max_new_tokens=16, temperature=0.0) for i in range(2)]
    plain = make_engine("int8")
    want = [r.text for r in plain.generate_batch(list(reqs))]
    plain.shutdown()

    spec = make_engine("int8", speculate_k=4)
    got_res = spec.generate_batch(list(reqs))
    m = spec.engine_metrics()
    spec.shutdown()
    assert all(r.error is None for r in got_res)
    assert [r.text for r in got_res] == want
    assert "spec_accepted_tokens" in m


def test_spec_int8_through_multi_kernel_matches_plain(monkeypatch):
    """The dequantizing RAGGED multi-token verify kernel (interpret mode)
    must match plain int8 decode token-for-token: the RMW quantizes draft
    rows with the slot's scales and the walk folds K/V dequant per head —
    same math as the single-token fused kernel, T rows at a time."""
    monkeypatch.setenv("LMRS_FORCE_KERNELS", "interpret")
    mc = ModelConfig(vocab_size=512, dim=512, n_layers=2, n_heads=4,
                     n_kv_heads=2, hidden_dim=256, max_seq_len=256,
                     dtype="float32")
    reqs = [GenerationRequest(
        prompt="the cat sat on the mat the cat sat " * 2,
        request_id=i, max_new_tokens=12, temperature=0.0) for i in range(2)]

    def make(k):
        return JaxEngine(EngineConfig(
            backend="jax", scheduler="continuous", max_tokens=12,
            max_batch_slots=2, seed=0, decode_block=6, page_size=32,
            kv_quantize="int8", speculate_k=k, retry_delay=0.0), mc)

    plain = make(0)
    assert plain._scheduler._use_ragged, "interpret mode should enable kernels"
    want = [r.text for r in plain.generate_batch(list(reqs))]
    plain.shutdown()

    spec = make(4)
    got_res = spec.generate_batch(list(reqs))
    spec.shutdown()
    assert all(r.error is None for r in got_res)
    assert [r.text for r in got_res] == want


@pytest.mark.parametrize("n_rep", [2, 4])
def test_int8_fused_kernel_matches_xla(n_rep):
    """Interpret-mode parity: the dequantizing fused kernel (32-row RMW
    windows, q/acc-folded per-channel dequant, pages multiplied as bf16
    with the scaled query and the probabilities split into stacked bf16
    rows) must match the int8 XLA scatter+gather path on the same pools
    and scales, to the tolerance a float32 pool owes (conftest.PAGED_TOL:
    probabilities rounded to bf16 miss it several hundred times over)."""
    import jax

    from lmrs_tpu.ops.paged_attention import (
        paged_decode_pallas_fused, paged_decode_xla)

    rng = np.random.default_rng(3)
    B, K, hd, ps, P = 3, 2, 128, 64, 16
    H, W = K * n_rep, 3
    kq = jnp.asarray(rng.integers(-127, 128, (P, K, ps, hd)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (P, K, ps, hd)), jnp.int8)
    tables = jnp.asarray(rng.permutation(P - 1)[: B * W].reshape(B, W) + 1,
                         jnp.int32)
    lens = jnp.asarray([ps * 2 + 17, 33, ps * 3], jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((B, K, hd)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, K, hd)), jnp.float32)
    ks = jnp.asarray(rng.uniform(0.01, 0.05, (B, K, hd)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.01, 0.05, (B, K, hd)), jnp.float32)

    got, kq1, vq1 = paged_decode_pallas_fused(
        q, kn, vn, kq, vq, tables, lens, interpret=True,
        kscale=ks, vscale=vs)

    # reference: quantized scatter + dequantized gather (the phase-1 path)
    from lmrs_tpu.ops.quant import kv_quant

    pos = lens - 1
    page = jnp.take_along_axis(tables, (pos // ps)[:, None], 1)[:, 0]
    off = pos % ps
    kq_ref = kq.at[page, :, off].set(kv_quant(kn[:, None], ks)[:, 0])
    vq_ref = vq.at[page, :, off].set(kv_quant(vn[:, None], vs)[:, 0])
    want = paged_decode_xla(q, kq_ref, vq_ref, tables, lens,
                            kv_scales=(ks, vs))

    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=PAGED_TOL, rtol=PAGED_TOL)
    # pool contents: the kernel's RMW must equal the XLA scatter
    np.testing.assert_array_equal(np.asarray(kq1), np.asarray(kq_ref))
    np.testing.assert_array_equal(np.asarray(vq1), np.asarray(vq_ref))


def test_int8_engine_with_interpret_kernels(monkeypatch):
    """The full continuous scheduler with kv int8 + the Pallas kernel path
    (interpret; needs the kernel-eligible head_dim 128): generation
    completes through the dequantizing fused kernel."""
    monkeypatch.setenv("LMRS_FORCE_KERNELS", "interpret")
    mc = ModelConfig(vocab_size=512, dim=512, n_layers=2, n_heads=4,
                     n_kv_heads=2, hidden_dim=256, max_seq_len=256,
                     dtype="float32")
    ec = EngineConfig(backend="jax", scheduler="continuous", max_tokens=24,
                      max_batch_slots=2, seed=0, page_size=32,
                      kv_quantize="int8", retry_delay=0.0)
    q = JaxEngine(ec, mc)
    assert q._scheduler._use_ragged, "interpret mode should enable kernels"
    out = q.generate_batch(
        [GenerationRequest(prompt="kernel path check", request_id=0,
                           temperature=0.0, max_new_tokens=6)])
    assert out[0].error is None and out[0].completion_tokens > 0


def test_chunked_prefill_sets_scales():
    """A prompt longer than prefill_chunk reaches the engine through the
    WINDOW (chunked) prefill path; its first chunk must still compute and
    store the slot's scales (review-caught: the window path previously
    quantized every long prompt with the all-ones init scales, silently
    zeroing small K/V values)."""
    q = make_engine("int8", prefill_chunk=64)
    prompt = "long prompt " * 30  # ~360 bytes >> 64-token chunks
    out = q.generate_batch(
        [GenerationRequest(prompt=prompt, request_id=0,
                           temperature=0.0, max_new_tokens=4)])
    assert out[0].error is None
    ks = np.asarray(q._scheduler.kscale)
    assert not np.allclose(ks[:, 0], 1.0), (
        "chunked prefill left slot 0's scales at init")
    # and the scale really is the FIRST chunk's: values are plausible
    # K-magnitudes (tiny), not the 1.0 init
    assert float(ks[:, 0].max()) < 0.5


def test_int8_composes_with_packed_prefill(monkeypatch):
    """int8 KV + packed prefill (VERDICT r3 item 3): same-wave fresh
    prompts concatenate into one segment-masked dispatch whose per-SEGMENT
    scales land on each segment's slot row — greedy output must match the
    unpacked int8 run (a segment's max-abs stats are identical to the same
    prompt prefilled alone), and the packed program must actually run."""
    reqs = [GenerationRequest(prompt=f"pack quant probe {i} " * (2 + 2 * i),
                              request_id=i, temperature=0.0, max_new_tokens=8)
            for i in range(2)]

    monkeypatch.setenv("LMRS_PACK_PREFILL", "0")
    plain = make_engine("int8")
    want = [r.text for r in plain.generate_batch(list(reqs))]
    assert not plain._scheduler._packed_prefill_fns
    plain.shutdown()

    monkeypatch.setenv("LMRS_PACK_PREFILL", "1")
    packed = make_engine("int8")
    got = [r.text for r in packed.generate_batch(list(reqs))]
    assert packed._scheduler._packed_prefill_fns, "packed path not exercised"
    # per-segment scales landed on their slots (not left at the ones init)
    ks = np.asarray(packed._scheduler.kscale)
    for b in range(2):
        assert not np.allclose(ks[:, b], 1.0), f"slot {b} scales never set"
    packed.shutdown()
    assert got == want


# ------------------------------------- the page-form pool write (PR 32)

_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}
# case -> (page size, bucket S, rows as (length, pages allocated))
_WAVES = {
    # two rows of different lengths, one allocated short of the bucket
    # (its columns past the allocation are 0) and a pad row (table all 0)
    "ragged_wave": (16, 64, [(64, 4), (37, 3), (20, 2), (1, 0)]),
    "one_page_bucket": (32, 32, [(32, 1), (5, 1), (1, 0)]),
    "lone_row": (16, 128, [(100, 7)]),
}


def _wave(case: str, dtype):
    ps, s, rows = _WAVES[case]
    rng = np.random.default_rng(3)
    kh, hd, b, n = 2, 8, len(rows), s // ps
    n_pages = 1 + sum(a for _, a in rows) + 3  # null page, owned, 3 unowned
    order = 1 + rng.permutation(n_pages - 1)
    table = np.zeros((b, n), np.int32)
    at = 0
    for r, (_, alloc) in enumerate(rows):
        table[r, :alloc] = order[at: at + alloc]
        at += alloc
    draw = ((lambda shape: rng.integers(-127, 128, shape))
            if dtype == jnp.int8 else
            (lambda shape: rng.standard_normal(shape)))
    pool = jnp.asarray(draw((n_pages, kh, ps, hd)), dtype)
    new = jnp.asarray(draw((b, s, kh, hd)), dtype)
    lens = np.asarray([length for length, _ in rows])
    alloc = np.asarray([max(a, 1) * ps for _, a in rows])
    return pool, jnp.asarray(table), new, lens, alloc, order[at:]


@pytest.mark.parametrize("case", sorted(_WAVES))
@pytest.mark.parametrize("dtype", sorted(_DTYPES))
def test_page_form_write_equals_row_form_below_lengths(dtype, case):
    """``scatter_kv_pages`` against ``scatter_kv_rows`` as the prefill
    program drives it (positions clamped into the row's allocation): the
    same bits at every position below each row's length, the positions
    past an allocation on the null page and nowhere else (no page outside
    the wave's tables changes), for f32, bf16 and int8 pools."""
    from lmrs_tpu.ops.paged_attention import (scatter_kv_pages,
                                              scatter_kv_rows, whole_pages)

    pool, table, new, lens, alloc, unowned = _wave(case, _DTYPES[dtype])
    ps = pool.shape[2]
    b, s = new.shape[:2]
    assert whole_pages(s, ps, table.shape[1]) == s // ps
    pos = jnp.minimum(jnp.arange(s)[None], jnp.asarray(alloc)[:, None] - 1)
    page = jnp.take_along_axis(table, pos // ps, axis=1)
    want = np.asarray(scatter_kv_rows(pool, page, pos % ps, new)
                      .astype(jnp.float32))
    got = np.asarray(scatter_kv_pages(pool, table, new).astype(jnp.float32))
    before = np.asarray(pool.astype(jnp.float32))
    for r in range(b):
        if not np.asarray(table)[r].any():
            continue  # a pad row: its token sits on the null page
        for j in range(-(-int(lens[r]) // ps)):
            g = int(np.asarray(table)[r, j])
            live = min(ps, int(lens[r]) - j * ps)
            np.testing.assert_array_equal(got[g][:, :live], want[g][:, :live])
            np.testing.assert_array_equal(
                got[g][:, :live],
                np.asarray(new.astype(jnp.float32))[
                    r, j * ps: j * ps + live].transpose(1, 0, 2))
    np.testing.assert_array_equal(got[unowned], before[unowned])
    np.testing.assert_array_equal(want[unowned], before[unowned])


def test_whole_pages_rule():
    """The one static rule both the model step and the dispatch record ask:
    a bucket of 64 under 128-token pages writes row by row, as does a table
    narrower than the bucket; whole pages otherwise."""
    from lmrs_tpu.ops.paged_attention import whole_pages

    assert whole_pages(2048, 128, 16) == 16
    assert whole_pages(128, 128, 16) == 1
    assert whole_pages(64, 128, 16) == 0      # a bucket of 64: S < ps
    assert whole_pages(192, 128, 16) == 0     # no multiple of the page
    assert whole_pages(2048, 128, 8) == 0     # the table is too narrow


@pytest.mark.parametrize("kv", ["int8", "bf16"])
def test_fresh_prefill_then_decode_equal_in_both_write_forms(kv, monkeypatch):
    """A fresh prefill of two rows (one short of its bucket) and six decode
    steps through ``forward_paged``: the page-form write (the bucket is
    whole pages) gives the logits, bit for bit, that the row form gave
    before it (``whole_pages`` held at 0), int8 KV and bf16."""
    import dataclasses

    import jax

    from lmrs_tpu.models.transformer import forward_paged, init_params
    from lmrs_tpu.ops import paged_attention

    cfg = tiny_model()
    if kv == "bf16":
        cfg = dataclasses.replace(cfg, dtype="bfloat16")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S, K, hd, ps, P = 2, 64, cfg.n_kv_heads, cfg.hd, 32, 8
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(1, 500, (B, S), dtype=np.int32))
    tables = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    lens = jnp.asarray([S, 41], jnp.int32)
    alloc = jnp.asarray([3 * ps, 2 * ps], jnp.int32)

    page_calls = []
    writer = paged_attention.scatter_kv_pages

    def run(form):
        monkeypatch.setattr(
            paged_attention, "scatter_kv_pages",
            lambda *a: page_calls.append(form) or writer(*a))
        if form == "rows":
            monkeypatch.setattr(paged_attention, "whole_pages",
                                lambda *a: 0)
        pdt = jnp.int8 if kv == "int8" else jnp.bfloat16
        kp = jnp.zeros((cfg.n_layers * P, K, ps, hd), pdt)
        vp = jnp.zeros((cfg.n_layers * P, K, ps, hd), pdt)
        sc = ((jnp.ones((cfg.n_layers, B, K, hd), jnp.float32),) * 2
              if kv == "int8" else None)
        pos = jnp.minimum(jnp.arange(S)[None], alloc[:, None] - 1)
        out = forward_paged(params, cfg, tokens, pos, kp, vp, tables, lens,
                            cfg.max_seq_len, kv_scales=sc,
                            last_pos=lens - 1)
        logits = [np.asarray(out[0][:, 0])]
        n = lens
        for _ in range(6):
            tok = jnp.asarray(np.argmax(logits[-1], -1)[:, None], jnp.int32)
            out = forward_paged(params, cfg, tok, n[:, None], out[1], out[2],
                                tables, n + 1, cfg.max_seq_len,
                                kv_scales=out[3] if sc else None)
            logits.append(np.asarray(out[0][:, 0]))
            n = n + 1
        monkeypatch.undo()
        return np.stack(logits)

    pages, rows = run("pages"), run("rows")
    assert page_calls == ["pages", "pages"]  # K and V of the one prefill
    assert np.isfinite(pages.astype(np.float32)).all()
    np.testing.assert_array_equal(pages, rows)


@pytest.mark.parametrize("program", ["fresh_pages", "fresh_rows", "window"])
def test_padding_never_lands_on_the_last_real_token(program, monkeypatch):
    """A prompt that fills its pages exactly (32 tokens of 16-token pages,
    nothing allocated beyond) in a bucket of 64: the bucket's padding, whose
    positions the scheduler clamps to the allocation's last slot, goes to
    the null page in every prefill program.  Clamped writes used to land ON
    position 31 and could replace the last real token's K/V (found by PR 32:
    the page form wrote it right and disagreed with the row form)."""
    import jax

    from lmrs_tpu.models.transformer import forward_paged, init_params
    from lmrs_tpu.ops import paged_attention

    cfg = tiny_model()
    params = init_params(cfg, jax.random.PRNGKey(0))
    if program == "fresh_rows":
        monkeypatch.setattr(paged_attention, "whole_pages", lambda *a: 0)
    n, ps, P = 32, 16, 4
    tokens = jnp.asarray(
        np.random.default_rng(9).integers(1, 500, (1, 64), dtype=np.int32))
    table = jnp.asarray([[1, 2, 0, 0]], jnp.int32)

    def pools(s):
        kp = jnp.zeros((cfg.n_layers * P, cfg.n_kv_heads, ps, cfg.hd),
                       jnp.float32)
        pos = jnp.minimum(jnp.arange(s)[None], n - 1)  # the scheduler's clamp
        out = forward_paged(params, cfg, tokens[:, :s], pos, kp, kp, table,
                            jnp.asarray([n]), cfg.max_seq_len,
                            window_prefill=program == "window")
        return np.asarray(out[1]), np.asarray(out[2])

    for padded, exact in zip(pools(64), pools(n)):
        for li in range(cfg.n_layers):
            # two programs of different shapes: float32 roundings apart,
            # where a pad token's K/V in place of a real one is O(1) off
            np.testing.assert_allclose(padded[li * P + 1: li * P + 3],
                                       exact[li * P + 1: li * P + 3],
                                       atol=1e-5, rtol=0)
