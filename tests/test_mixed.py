"""SARATHI-style mixed batches (ISSUE 11): chunked prefill fused into the
live decode step.

The tier-1 mixed gate: greedy outputs must be token-identical with
``LMRS_MIXED=0`` vs ``1`` across prefix-cache on/off and speculation
on/off (interpret mode runs the real ragged multi-token kernel), the
fused dispatcher must actually run (piggybacked-token accounting), decode
cadence must continue through an admission burst, and the scheduler
auditor must stay clean."""

from __future__ import annotations

import numpy as np
import pytest

from lmrs_tpu.config import EngineConfig, ModelConfig
from lmrs_tpu.engine.api import GenerationRequest
from lmrs_tpu.engine.jax_engine import JaxEngine


def tiny_model():
    return ModelConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                       n_kv_heads=2, hidden_dim=128, max_seq_len=256,
                       dtype="float32")


def kernel_model():
    # hd = 128: the ragged kernel gate is on under LMRS_FORCE_KERNELS
    return ModelConfig(vocab_size=512, dim=512, n_layers=2, n_heads=4,
                       n_kv_heads=2, hidden_dim=256, max_seq_len=512,
                       dtype="float32")


def _cfg(mixed: bool, *, prefix_cache: bool = True, spec_k: int = 0,
         slots: int = 2, **kw) -> EngineConfig:
    # decode_block small so admissions land while earlier requests still
    # decode — the regime mixed dispatch exists for
    base = dict(backend="jax", scheduler="continuous", max_tokens=16,
                max_batch_slots=slots, seed=0, decode_block=3,
                prefill_chunk=64, prefix_cache=prefix_cache,
                speculate_k=spec_k, mixed_batch=mixed)
    base.update(kw)
    return EngineConfig(**base)


def _mix_requests(n: int = 4) -> list[GenerationRequest]:
    """Shared-preamble mix of short + long prompts: long prompts chunk,
    short ones decode through the admissions, preambles collide in the
    prefix cache at page boundaries."""
    pre = "shared mixed preamble alpha beta "
    reqs = []
    for i in range(n):
        body = (f"request {i} " + "lorem ipsum dolor sit amet " * (1 + 5 * (i % 2)))
        reqs.append(GenerationRequest(
            prompt=(pre if i % 2 else "") + body, request_id=i,
            temperature=0.0, max_new_tokens=12 + i))
    return reqs


def _run(cfg: EngineConfig, mc, reqs):
    eng = JaxEngine(cfg, mc)
    out = eng.generate_batch(reqs)
    sched = eng._scheduler
    assert sched.audit() == []
    texts = [(r.text, r.finish_reason, r.completion_tokens) for r in out]
    assert all(r.error is None for r in out)
    m = dict(sched.metrics)
    eng.shutdown()
    return texts, m


@pytest.mark.parametrize("prefix_cache", [True, False])
@pytest.mark.parametrize("spec_k", [0, 3])
def test_mixed_greedy_identity_matrix(monkeypatch, prefix_cache, spec_k):
    """LMRS_MIXED=0 vs 1 token identity across the prefix-cache x
    speculation matrix (the ISSUE 11 acceptance bar).  The mixed arm must
    actually exercise the fused dispatcher — an identity proven on runs
    that never mixed proves nothing."""
    mc = tiny_model()
    reqs = _mix_requests()
    monkeypatch.setenv("LMRS_MIXED", "0")
    want, m_off = _run(_cfg(True, prefix_cache=prefix_cache,
                            spec_k=spec_k), mc, reqs)
    assert m_off["mixed_dispatches"] == 0  # kill switch really off
    monkeypatch.setenv("LMRS_MIXED", "1")
    got, m_on = _run(_cfg(True, prefix_cache=prefix_cache,
                          spec_k=spec_k), mc, reqs)
    assert m_on["mixed_dispatches"] > 0, "mixed path not exercised"
    assert m_on["prefill_tokens_piggybacked"] > 0
    assert got == want


@pytest.mark.parametrize("spec_k", [0, 3])
def test_mixed_identity_on_interpret_kernels(monkeypatch, spec_k):
    """The same A/B through the REAL ragged multi-token row-group kernel
    (interpret mode): mixed steps dispatch [B, T] batches where decode
    rows carry one real token and the prefill row its slice — the kernel
    must survive (no silent XLA fallback) and outputs must match the
    alternating path exactly."""
    monkeypatch.setenv("LMRS_FORCE_KERNELS", "interpret")
    mc = kernel_model()
    reqs = [GenerationRequest(prompt="short kernel probe", request_id=0,
                              temperature=0.0, max_new_tokens=9),
            GenerationRequest(prompt="mixed kernel probe words " * 14,
                              request_id=1, temperature=0.0,
                              max_new_tokens=9),
            GenerationRequest(prompt="third staggered prompt " * 6,
                              request_id=2, temperature=0.0,
                              max_new_tokens=9)]
    cfg = lambda mixed: _cfg(mixed, spec_k=spec_k, max_tokens=9)
    monkeypatch.setenv("LMRS_MIXED", "0")
    off = JaxEngine(cfg(True), mc)
    assert off._scheduler._use_ragged
    want = [r.text for r in off.generate_batch(reqs)]
    off.shutdown()
    monkeypatch.setenv("LMRS_MIXED", "1")
    on = JaxEngine(cfg(True), mc)
    got = [r.text for r in on.generate_batch(reqs)]
    sched = on._scheduler
    assert sched.metrics["mixed_dispatches"] > 0, "mixed path not exercised"
    assert sched._use_ragged, "multi-token kernel silently degraded"
    assert sched._rpa_fns, "no span program compiled"
    assert sched.audit() == []
    on.shutdown()
    assert got == want


def test_mixed_decode_cadence_through_admission_burst():
    """A long prompt admitted mid-decode must NOT pause the live decode
    rows: its prefill rides the decode steps as budget-clipped slices
    (piggybacked tokens cover the whole prompt) and the decode rows keep
    emitting between the admission and prefill completion."""
    mc = tiny_model()
    eng = JaxEngine(_cfg(True, slots=2, prefill_chunk=4096,
                         mixed_token_budget=64, max_tokens=24), mc)
    sched = eng._scheduler
    burst: list[GenerationRequest] = [
        # staggered budgets: request 1 finishes early, freeing the slot
        # for the burst admission WHILE request 0 still decodes
        GenerationRequest(prompt="steady decoder", request_id=0,
                          temperature=0.0, max_new_tokens=24),
        GenerationRequest(prompt="second steady", request_id=1,
                          temperature=0.0, max_new_tokens=6),
        # admitted when a slot frees, while the other still decodes: the
        # prompt (~190 tokens) exceeds the 64-token step budget, so its
        # prefill MUST split over several mixed steps
        GenerationRequest(prompt="burst admission prompt words " * 7,
                          request_id=2, temperature=0.0, max_new_tokens=4),
    ]
    out = eng.generate_batch(burst)
    assert all(r.error is None for r in out)
    m = sched.metrics
    assert m["mixed_dispatches"] >= 3, m  # sliced across several steps
    # the burst prompt's prefill rode decode steps, not dedicated waves
    burst_tokens = len(sched._encode(burst[2])[0])
    assert m["prefill_tokens_piggybacked"] >= burst_tokens
    rep = sched.metrics_report()["mixed_batch"]
    assert rep["enabled"] and rep["dispatches"] == m["mixed_dispatches"]
    assert 0.0 < rep["fill_ratio"] <= 1.0
    # decode rows advanced during the mixed window: every mixed dispatch
    # emitted one token per live decode row
    assert m["decode_tokens"] >= m["mixed_dispatches"]
    assert sched.audit() == []
    eng.shutdown()


def test_mixed_metrics_and_report_shape():
    """The mixed_batch report block and the windowable metric keys bench
    relies on (mixed_dispatches / mixed_fill_sum /
    prefill_tokens_piggybacked) exist and stay consistent."""
    mc = tiny_model()
    eng = JaxEngine(_cfg(True), mc)
    eng.generate_batch(_mix_requests())
    m = eng._scheduler.metrics
    rep = eng._scheduler.metrics_report()
    blk = rep["mixed_batch"]
    assert blk["dispatches"] == m["mixed_dispatches"]
    assert blk["prefill_tokens_piggybacked"] == m["prefill_tokens_piggybacked"]
    assert blk["token_budget"] == 256
    if m["mixed_dispatches"]:
        assert 0.0 < blk["fill_ratio"] <= 1.0
        assert m["prefill_tokens_piggybacked"] <= m["prefill_tokens"]
    # the block-gap scope label (docs/PERF.md): batch waves vs serving
    # cadence must be distinguishable from the report alone
    assert "decode_block_gap_scope" in rep
    eng.shutdown()


def test_mixed_int8_kv_armed_under_rpa(monkeypatch):
    """The retired composition gate (ISSUE 16): under ragged span
    dispatch int8 KV x mixed RUNS — per-row frozen scales ride the span
    descriptor (a fresh-start slice owns its slot's scales, every other
    row clamps) — with greedy token identity against the int8
    alternating path and a clean audit."""
    mc = tiny_model()
    reqs = _mix_requests()
    cfg = lambda mixed: _cfg(mixed, page_size=32, kv_quantize="int8",
                             prefix_cache=False)
    monkeypatch.setenv("LMRS_MIXED", "0")
    want, m_off = _run(cfg(True), mc, reqs)
    assert m_off["mixed_dispatches"] == 0
    monkeypatch.setenv("LMRS_MIXED", "1")
    got, m_on = _run(cfg(True), mc, reqs)
    assert m_on["mixed_dispatches"] > 0, "int8 x mixed not exercised"
    assert m_on["rpa_dispatches"] > 0
    assert got == want


def test_mixed_budget_floor_falls_back_to_alternating():
    """A budget the decode rows nearly exhaust leaves no room for a
    slice: the step must fall back to alternating dispatch (progress,
    never a degenerate 1-token slice loop)."""
    mc = tiny_model()
    # budget 32 (config floor) with 24 slots leaves < 16 slice tokens
    # whenever >= 17 rows decode; with 2 slots it mixes normally — use a
    # wide engine so the floor actually binds
    eng = JaxEngine(_cfg(True, slots=24, mixed_token_budget=32,
                         max_tokens=8), mc)
    reqs = [GenerationRequest(prompt=f"floor probe {i} " * 3, request_id=i,
                              temperature=0.0, max_new_tokens=8)
            for i in range(30)]
    out = eng.generate_batch(reqs)
    assert all(r.error is None for r in out)
    assert eng._scheduler.audit() == []
    eng.shutdown()


def test_mock_engine_mixed_block(monkeypatch):
    """The no-device arm exposes the same knob surface: mixed accounting
    appears in engine_metrics(), and the LMRS_MIXED kill switch disarms
    it (serving/jobs CI asserts knob parity without a device)."""
    from lmrs_tpu.engine.mock import MockEngine

    reqs = [GenerationRequest(prompt="one " * 30, request_id=0),
            GenerationRequest(prompt="two " * 50, request_id=1),
            GenerationRequest(prompt="three " * 20, request_id=2)]
    eng = MockEngine(mixed_token_budget=64)
    assert eng.generate_batch(reqs)
    blk = eng.engine_metrics()["mixed_batch"]
    assert blk["enabled"] and blk["dispatches"] > 0
    assert blk["prefill_tokens_piggybacked"] > 0
    assert 0.0 < blk["fill_ratio"] <= 1.0
    # deterministic emulation: same batch, same counters
    eng2 = MockEngine(mixed_token_budget=64)
    eng2.generate_batch(reqs)
    assert eng2.engine_metrics() == eng.engine_metrics()
    monkeypatch.setenv("LMRS_MIXED", "0")
    off = MockEngine(mixed_token_budget=64)
    off.generate_batch(reqs)
    # mixed accounting absent when disarmed (the cost/slo parity blocks
    # report regardless — they bill every request, mixed or not)
    assert "mixed_batch" not in off.engine_metrics()


def test_make_engine_threads_mixed_knobs():
    """EngineConfig.mixed_* reach the mock through make_engine (the same
    config path the serving CLI uses)."""
    from lmrs_tpu.engine.api import make_engine

    eng = make_engine(EngineConfig(backend="mock", mixed_batch=True,
                                   mixed_token_budget=128))
    assert eng.mixed_batch and eng.mixed_token_budget == 128
    off = make_engine(EngineConfig(backend="mock", mixed_batch=False))
    assert not off.mixed_batch


def test_mixed_streaming_deltas_concatenate_exactly():
    """on_tokens deltas emitted across mixed steps must concatenate to
    the final text (the per-block streaming contract survives the fused
    dispatch path)."""
    mc = tiny_model()
    eng = JaxEngine(_cfg(True), mc)
    deltas: dict[int, str] = {}

    def on_tokens(rid, text):
        deltas[rid] = deltas.get(rid, "") + text

    out = eng.generate_batch(_mix_requests(), on_tokens=on_tokens)
    assert eng._scheduler.metrics["mixed_dispatches"] > 0
    for r in out:
        assert deltas.get(r.request_id, "") == r.text
    eng.shutdown()
