"""Step-anatomy profiler + bucket economics (ISSUE 18, obs/anatomy.py).

Four layers of coverage:

* unit — ``StepAnatomy`` with an injected clock: pause semantics,
  conservation identity, abort/discard accounting, bucket arithmetic
  against hand-computed span lists, stale-RTT report gating, merge rules;
* engine — real CPU JaxEngines through the live scheduler loop: plain /
  mixed / spec / fault-armed chaos arms all end with
  ``scheduler.audit()`` clean (the conservation identity holds through
  dispatch faults by construction, not luck);
* parity — the ``LMRS_ANATOMY=0`` kill switch is byte-identical (greedy
  output, metrics_report keys) and the mock's deterministic anatomy
  matches the scheduler's report schema exactly;
* wire — ``GET /v1/anatomy`` serves the document, 501s when the switch
  is off or the backend has no hook, and the router's fleet merge rides
  the same endpoint.
"""

from __future__ import annotations

import json
import urllib.error
import urllib.request

import pytest

from lmrs_tpu.config import EngineConfig, ModelConfig
from lmrs_tpu.engine.api import GenerationRequest
from lmrs_tpu.engine.jax_engine import JaxEngine
from lmrs_tpu.engine.mock import MockEngine
from lmrs_tpu.obs.anatomy import (CLASSES, SEGMENTS, StepAnatomy,
                                  merge_anatomy)
from lmrs_tpu.obs.metrics import MetricsRegistry


def tiny_model() -> ModelConfig:
    return ModelConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                       n_kv_heads=2, hidden_dim=128, max_seq_len=256,
                       dtype="float32")


def _cfg(**kw) -> EngineConfig:
    base = dict(backend="jax", scheduler="continuous", max_tokens=32,
                max_batch_slots=2, seed=0, decode_block=4, page_size=16,
                num_pages=24, retry_delay=0.0)
    base.update(kw)
    return EngineConfig(**base)


def _reqs(n: int = 3, start: int = 0, budget: int = 8):
    return [GenerationRequest(prompt=f"anatomy probe {start + i} alpha "
                                     "bravo charlie",
                              request_id=start + i, temperature=0.0,
                              max_new_tokens=budget) for i in range(n)]


# ------------------------------------------------------------------ unit


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float) -> float:
        self.t += dt
        return self.t


@pytest.fixture
def an():
    clock = FakeClock()
    a = StepAnatomy(MetricsRegistry(), clock=clock)
    a.clock = clock  # test-side handle
    return a


def test_seg_pause_semantics_and_conservation(an):
    """Entering an inner segment pauses the outer one: elapsed time lands
    in exactly one segment, the explicit residual covers the rest, and
    wall == segments + residual EXACTLY on the fake clock."""
    c = an.clock
    an.iter_begin()
    c.tick(0.010)                      # residual (outside any segment)
    with an.seg("plan"):
        c.tick(0.020)                  # plan
        with an.seg("dispatch"):
            c.tick(0.030)              # dispatch — plan is paused
        c.tick(0.005)                  # plan resumes
    an.iter_end("plain")
    assert an.audit() == []
    rep = an.report()
    assert rep["iterations"] == 1
    assert rep["segments_ms"]["plan"] == pytest.approx(25.0)
    assert rep["segments_ms"]["dispatch"] == pytest.approx(30.0)
    assert rep["residual_ms"] == pytest.approx(10.0)
    assert rep["wall_ms"] == pytest.approx(65.0)
    # host overhead excludes dispatch+fetch: 65 - 30 = 35 ms = 35000 µs
    assert rep["host_overhead_us_step"] == pytest.approx(35000.0)
    p50 = rep["classes"]["plain"]["p50_us"]
    assert p50["plan"] == pytest.approx(25000.0)
    assert p50["wall"] == pytest.approx(65000.0)


def test_abort_discards_and_discard_counts_nothing(an):
    c = an.clock
    an.iter_begin()
    with an.seg("dispatch"):
        c.tick(0.5)
    an.iter_abort()                    # fault unwind: contributes nothing
    an.iter_begin()
    c.tick(0.1)
    an.iter_discard()                  # run-exit pass: not even "aborted"
    rep = an.report()
    assert rep["iterations"] == 0
    assert rep["aborted_iterations"] == 1
    assert rep["wall_ms"] == 0.0
    assert rep["segments_ms"]["dispatch"] == 0.0
    assert an.audit() == []


def test_unknown_segment_rejected(an):
    with pytest.raises(ValueError):
        an.seg("warp")


def test_audit_detects_broken_conservation(an):
    """The auditor must be PROVEN able to fail (same discipline as the
    page auditor's negative cases): corrupting a segment total breaks the
    wall == segments + residual identity."""
    c = an.clock
    an.iter_begin()
    with an.seg("fetch"):
        c.tick(0.010)
    an.iter_end("plain")
    assert an.audit() == []
    an._segs["fetch"] += 1.0
    assert any("conservation" in v for v in an.audit())
    an._segs["fetch"] -= 1.0
    assert an.audit() == []


def _rpa(an, tpb, w, real, *, cold=False, prompt=None, spec=False):
    """One span-program dispatch record on bucket (tpb, w)."""
    return an.dispatch("rpa", ("rpa_spec" if spec else "rpa", tpb, w),
                       rows=1, row_slots=2, q_tokens=real,
                       prompt_tokens=real if prompt is None else prompt,
                       q_slots=tpb, ctx_tokens=0, cold=cold)


def test_bucket_economics_hand_computed(an):
    """The buckets view of programs["rpa"] vs a hand-computed span list:
    three dispatches on bucket (32, 4) carrying 20/32/7 real tokens -> 59
    real, 37 padded, real + padded == dispatches * 32, pad_waste 37/96;
    the first was cold and its 0.25 s wall is the bucket's compile."""
    for i, real in enumerate((20, 32, 7)):
        with _rpa(an, 32, 4, real, cold=i == 0):
            an.clock.tick(0.25)
    with _rpa(an, 64, 8, 50, spec=True):  # rpa_spec shares the bucket view
        pass
    assert an.audit() == []
    rep = an.report()
    b = rep["buckets"]["32x4"]
    assert b["dispatches"] == 3
    assert b["real_tokens"] == 59
    assert b["padded_tokens"] == 37
    assert b["pad_waste"] == pytest.approx(37 / 96, abs=1e-4)
    assert b["compile_ms"] == pytest.approx(250.0)
    assert rep["buckets"]["64x8"]["padded_tokens"] == 14
    # overall ratio spans both buckets: (37+14) / (96+64)
    assert rep["rpa_pad_waste_ratio"] == pytest.approx(51 / 160, abs=1e-4)
    # the registry series keep their names and are fed from the table
    assert an._c_b_disp.value == 4
    assert an._c_b_real.value == 109 and an._c_b_pad.value == 51
    assert an._c_b_compile.value == pytest.approx(0.25)
    # negative case: a corrupted count breaks q_slots == dispatches*bucket
    an._table[("rpa", ("rpa", 32, 4))]["q_slots"] += 1
    assert any("rpa:32:4" in v for v in an.audit())
    an._table[("rpa", ("rpa", 32, 4))]["q_slots"] -= 1
    assert an.audit() == []


def test_dispatch_record_table_and_flat_counters(an):
    """Every field of a record lands in programs[program] and under its
    key; the prompt programs feed the prefill_* sums, decode stays in the
    table only, and emitted() adds the tokens a block turned out."""
    with an.dispatch("prefill", ("prefill", True, 4, 64, 4, False), rows=3,
                     row_slots=4, q_tokens=150, prompt_tokens=150,
                     q_slots=256, ctx_tokens=0, cold=False):
        pass
    with an.dispatch("decode", ("decode", 4, 4), rows=3, row_slots=4,
                     q_tokens=0, prompt_tokens=0, q_slots=16,
                     ctx_tokens=150, cold=False) as d:
        pass
    d.emitted(11)
    assert d.id == 2
    rep = an.report()["programs"]
    assert set(rep) == {"prefill", "decode"}
    pf = rep["prefill"]
    assert (pf["dispatches"], pf["rows"], pf["row_slots"]) == (1, 3, 4)
    assert (pf["q_tokens"], pf["prompt_tokens"], pf["q_slots"]) == (
        150, 150, 256)
    assert pf["keys"]["prefill:True:4:64:4:False"]["q_slots"] == 256
    assert rep["decode"]["q_tokens"] == 11
    assert rep["decode"]["ctx_tokens"] == 150
    assert an.counters() == {
        "prefill_dispatches": 1, "prefill_rows": 3, "prefill_row_slots": 4,
        "prefill_query_tokens": 150,
        "prefill_token_slots": 256, "prefill_page_writes": 0,
        "prefill_seq_rung_dispatches": 0, "layer_passes": 0,
        "decode_hbm_bytes": 0, "decode_kv_page_reads": 0,
        "rpa_wide_tokens": 0, "rpa_kv_page_reads": 0, "cold_dispatches": 0,
        "cold_seconds": 0.0}
    assert an.audit(prefill_tokens=150) == []
    with pytest.raises(ValueError):
        an.dispatch("warp", ("warp",), rows=0, row_slots=0, q_tokens=0,
                    prompt_tokens=0, q_slots=0, ctx_tokens=0, cold=False)


@pytest.mark.parametrize("window", [0, 16], ids=["dense", "windowed"])
def test_decode_kv_page_reads_are_the_walks_pages(an, window):
    """A decode record's ``kv_page_reads`` (``StepBytes.pages``) is the
    pages its walks fetch, counted walk by walk the kernel's way: at step t
    a row's walk covers ``len + t + 1`` positions, all its pages in a full
    layer and, in a window layer, the pages ``window_walk`` leaves it
    (8-position pages; rows below, at and past a page edge and the
    window).  ``decode_kv_page_reads`` is the decode records' sum."""
    import jax.numpy as jnp
    import numpy as np

    from lmrs_tpu.ops.paged_attention import window_walk
    from lmrs_tpu.utils.perf_model import StepBytes

    ps, steps, full, win = 8, 3, 2, 3 if window else 0
    sb = StepBytes(layer=0.0, expert=0.0, head=0, position=0.0,
                   full_layers=full, window_layers=win, window=window,
                   max_len=64, page_size=ps)
    blocks = [[0, 7, 15, 40], [16, 62]]
    table = jnp.arange(8)[None, :].repeat(4, 0)
    want = 0
    for lens in blocks:
        for t in range(steps):
            n = np.minimum(np.asarray(lens) + t + 1, sb.max_len)
            want += full * int(sum(-(-int(x) // ps) for x in n))
            if win:
                _, walk, _ = window_walk(table[: len(n)], jnp.asarray(n),
                                         jnp.int32(window), ps)
                want += win * int(sum(-(-int(x) // ps) for x in walk))
    got = [sb.pages(lens, steps) for lens in blocks]
    assert sum(got) == want
    for lens, pages in zip(blocks, got):
        with an.dispatch("decode", ("decode", 4, 8), rows=len(lens),
                         row_slots=4, q_tokens=0, prompt_tokens=0,
                         q_slots=4 * steps, ctx_tokens=sum(lens),
                         cold=False, kv_page_reads=pages):
            pass
    assert an.report()["programs"]["decode"]["kv_page_reads"] == want
    assert an.counters()["decode_kv_page_reads"] == want


def test_span_walk_fields_sum_and_audit(an):
    """A span dispatch's ``wide_tokens`` / ``kv_page_reads`` (what the
    ragged span kernel will do with it) land under its key, sum into the
    flat ``rpa_*`` counters, stay 0 on every other program, and the audit
    holds ``wide_tokens <= q_tokens`` per key."""
    with an.dispatch("rpa", ("rpa", 2048, 16), rows=2, row_slots=4,
                     q_tokens=1300, prompt_tokens=1300, q_slots=2048,
                     ctx_tokens=1024, cold=False, wide_tokens=1251,
                     kv_page_reads=53):
        pass
    with an.dispatch("rpa", ("rpa", 16, 4), rows=2, row_slots=4,
                     q_tokens=2, prompt_tokens=0, q_slots=16,
                     ctx_tokens=600, cold=False, kv_page_reads=5):
        pass
    with an.dispatch("decode", ("decode", 4, 4), rows=3, row_slots=4,
                     q_tokens=0, prompt_tokens=0, q_slots=16,
                     ctx_tokens=150, cold=False):
        pass
    rep = an.report()["programs"]
    assert rep["rpa"]["wide_tokens"] == 1251
    assert rep["rpa"]["kv_page_reads"] == 58
    assert rep["rpa"]["keys"]["rpa:16:4"]["wide_tokens"] == 0
    assert rep["decode"]["kv_page_reads"] == 0
    c = an.counters()
    assert (c["rpa_wide_tokens"], c["rpa_kv_page_reads"]) == (1251, 58)
    assert an.audit() == []
    an._table[("rpa", ("rpa", 16, 4))]["wide_tokens"] = 3
    assert any("wide_tokens 3 > q_tokens 2" in v for v in an.audit())


def test_programs_and_buckets_window_off_a_snapshot(an):
    """report(before=snapshot) holds only the dispatches after it — the
    buckets view included (cumulative before PR 25); without ``before``
    the document is the whole life, as /v1/anatomy serves it."""
    with _rpa(an, 32, 4, 20, cold=True):
        an.clock.tick(0.5)
    with _rpa(an, 64, 8, 40):
        pass
    snap = an.snapshot()
    with _rpa(an, 32, 4, 30):
        pass
    win = an.report(snap)
    assert list(win["buckets"]) == ["32x4"]
    assert win["buckets"]["32x4"] == {
        "dispatches": 1, "real_tokens": 30, "padded_tokens": 2,
        "pad_waste": pytest.approx(2 / 32, abs=1e-4), "compile_ms": 0.0}
    rpa = win["programs"]["rpa"]
    assert rpa["dispatches"] == 1 and rpa["cold"] == 0
    assert list(rpa["keys"]) == ["rpa:32:4"]
    assert win["rpa_pad_waste_ratio"] == pytest.approx(2 / 32, abs=1e-4)
    whole = an.report()
    assert whole["programs"]["rpa"]["dispatches"] == 3
    assert whole["programs"]["rpa"]["cold_ms"] == pytest.approx(500.0)
    assert set(whole["buckets"]) == {"32x4", "64x8"}
    # an empty window has no programs at all
    assert an.report(an.snapshot())["programs"] == {}


def test_cold_key_counted_once_with_its_wall(an):
    """A cold dispatch is one cold_dispatch and its segment wall is
    cold_seconds (the compile); the warm dispatch that follows on the same
    key adds nothing to either."""
    c = an.clock
    an.iter_begin()
    with _rpa(an, 32, 4, 20, cold=True):
        c.tick(1.5)
    with _rpa(an, 32, 4, 20):
        c.tick(0.01)
    an.iter_end("prefill")
    got = an.counters()
    assert got["cold_dispatches"] == 1
    assert got["cold_seconds"] == pytest.approx(1.5)
    key = an.report()["programs"]["rpa"]["keys"]["rpa:32:4"]
    assert key["cold"] == 1 and key["cold_ms"] == pytest.approx(1500.0)
    assert key["dispatches"] == 2
    # the record's segment is the dispatch segment: both walls are in it
    assert an.report()["segments_ms"]["dispatch"] == pytest.approx(1510.0)
    assert an.audit() == []


@pytest.mark.parametrize("corrupt,needle", [
    (lambda a: a._table[("rpa", ("rpa", 32, 4))].__setitem__(
        "prompt_tokens", 19), "prompt_tokens over all programs"),
    (lambda a: a._flat.__setitem__("prefill_query_tokens", 21),
     "q_tokens over the prompt programs"),
    (lambda a: a._table[("rpa", ("rpa", 32, 4))].__setitem__(
        "dispatches", 2), "dispatches*bucket"),
])
def test_audit_detects_each_broken_table_identity(an, corrupt, needle):
    """The three dispatch-table identities are each PROVEN able to fail."""
    with _rpa(an, 32, 4, 20):
        pass
    assert an.audit(prefill_tokens=20) == []
    corrupt(an)
    assert any(needle in v for v in an.audit(prefill_tokens=20))


def test_report_stale_rtt_guard(an):
    """Satellite 3: a fresh RTT sample yields the device-wait split; a
    STALE one (older than 2x the resample cadence) is flagged and the
    split is withheld rather than skewed."""
    c = an.clock
    an.iter_begin()
    with an.seg("fetch"):
        c.tick(0.010)
    an.iter_end("plain")
    fresh = an.report(rtt=(0.002, 1.0))
    assert fresh["rtt_ms"] == pytest.approx(2.0)
    assert fresh["rtt_stale"] is False
    # fetch 10 ms minus one 2 ms RTT -> 8 ms of true device wait
    assert fresh["device_wait_us_step"] == pytest.approx(8000.0)
    stale = an.report(rtt=(0.002, 100000.0))
    assert stale["rtt_stale"] is True
    assert "device_wait_us_step" not in stale
    none = an.report(rtt=(None, None))
    assert "rtt_ms" not in none and "device_wait_us_step" not in none


def test_ensure_rtt_resamples_on_slow_cadence(monkeypatch):
    """Satellite 3 regression (injected clock): within the cadence the
    cached sample is returned untouched; past it the probe re-runs and
    refreshes the timestamp, so a long-lived process tracks link drift."""
    from lmrs_tpu.obs.perf import DispatchAttribution

    da = DispatchAttribution(tiny_model(), MetricsRegistry())
    clock = FakeClock()
    da._clock = clock
    monkeypatch.setenv("LMRS_RTT_RESAMPLE_S", "100")
    da._rtt, da._rtt_t = 0.5, clock.t  # implausible cached sample
    clock.tick(99.0)
    assert da.ensure_rtt() == 0.5      # inside the cadence: no probe
    assert da._rtt_t == pytest.approx(1000.0)
    clock.tick(2.0)                    # past the cadence: re-probe
    rtt = da.ensure_rtt()
    assert rtt != 0.5                  # a real CPU probe is far below 0.5 s
    assert da._rtt_t == pytest.approx(clock.t)
    sample, age = da.rtt_sample()
    assert sample == rtt and age == 0.0


def test_merge_anatomy_sums_and_disabled_shape():
    a = {"object": "anatomy", "enabled": True, "iterations": 4,
         "aborted_iterations": 1, "wall_ms": 10.0, "residual_ms": 1.0,
         "segments_ms": {s: 1.0 for s in SEGMENTS},
         "host_overhead_us_step": 100.0,
         "classes": {"plain": {"iterations": 4,
                               "p50_us": {"wall": 100.0},
                               "p95_us": {"wall": 200.0}}},
         "buckets": {"32x4": {"dispatches": 2, "real_tokens": 40,
                              "padded_tokens": 24, "pad_waste": 0.375,
                              "compile_ms": 5.0}},
         "rpa_pad_waste_ratio": 0.375}
    b = dict(a, iterations=12, host_overhead_us_step=200.0,
             classes={"plain": {"iterations": 12,
                                "p50_us": {"wall": 300.0},
                                "p95_us": {"wall": 400.0}}})
    merged = merge_anatomy([a, b, {"object": "anatomy", "enabled": False}])
    assert merged["enabled"] is True
    assert merged["iterations"] == 16
    assert merged["aborted_iterations"] == 2
    assert merged["wall_ms"] == pytest.approx(20.0)
    assert merged["segments_ms"]["dispatch"] == pytest.approx(2.0)
    # iteration-weighted means: (100*4 + 200*12) / 16 = 175
    assert merged["host_overhead_us_step"] == pytest.approx(175.0)
    assert merged["classes"]["plain"]["p50_us"]["wall"] == pytest.approx(
        (100.0 * 4 + 300.0 * 12) / 16)
    mb = merged["buckets"]["32x4"]
    assert mb["dispatches"] == 4 and mb["padded_tokens"] == 48
    assert mb["pad_waste"] == pytest.approx(0.375)
    assert merge_anatomy([]) == {"object": "anatomy", "enabled": False}
    assert merge_anatomy([{"enabled": False}])["enabled"] is False


# ------------------------------------------------------ engine (CPU jax)


@pytest.fixture(scope="module")
def mixed_engine():
    eng = JaxEngine(_cfg(mixed_batch=True), tiny_model())
    yield eng
    eng.shutdown()


def test_jax_plain_and_mixed_arms_conserve(mixed_engine):
    """Real scheduler-loop traffic: the conservation identity holds, the
    report carries per-class percentiles, and every ragged-span bucket's
    token counts reconcile against its dispatch count."""
    sched = mixed_engine._scheduler
    an0 = sched.anatomy_snapshot()
    out = mixed_engine.generate_batch(_reqs(3))
    assert all(r.error is None for r in out)
    assert sched.audit() == []
    rep = sched.anatomy_report(an0)
    assert rep["enabled"] and rep["iterations"] > 0
    assert rep["wall_ms"] > 0.0
    assert set(rep["segments_ms"]) == set(SEGMENTS)
    assert set(rep["classes"]) <= set(CLASSES)
    assert rep["host_overhead_us_step"] > 0.0
    for cls_rep in rep["classes"].values():
        assert cls_rep["p95_us"]["wall"] >= cls_rep["p50_us"]["wall"]
    for key, b in rep["buckets"].items():
        tpb = int(key.split("x")[0])
        assert (b["real_tokens"] + b["padded_tokens"]
                == b["dispatches"] * tpb), key
        assert 0.0 <= b["pad_waste"] < 1.0
    # the anatomy block rides metrics_report under the same key
    assert sched.metrics_report()["anatomy"]["enabled"] is True


def test_jax_spec_arm_reports_nonzero_draft():
    """The spec-verify arm: draft plumbing (seed_history, reseeds) is a
    named segment and must be nonzero — the 3x spec-step mystery's
    attribution target (acceptance criterion)."""
    eng = JaxEngine(_cfg(speculate_k=4), tiny_model())
    try:
        sched = eng._scheduler
        out = eng.generate_batch(_reqs(2, budget=8))
        assert all(r.error is None for r in out)
        assert sched.audit() == []
        rep = sched.anatomy_report()
        assert "spec" in rep["classes"]
        assert rep["segments_ms"]["draft"] > 0.0
    finally:
        eng.shutdown()


def test_jax_fault_armed_chaos_arm_conserves(mixed_engine):
    """A dispatch fault kills an iteration mid-segment: the open record is
    DISCARDED (iter_abort), so wall == segments + residual still
    reconciles in scheduler.audit() and the abort shows up as an aborted
    iteration, never as skew."""
    from lmrs_tpu.engine.executor import MapExecutor
    from lmrs_tpu.testing import faults
    from lmrs_tpu.testing.faults import FaultPlan

    sched = mixed_engine._scheduler
    an0 = sched.anatomy_snapshot()
    ex = MapExecutor(mixed_engine, EngineConfig(retry_attempts=3,
                                                retry_delay=0.01))
    with faults.injected(FaultPlan(seed=13, faults=[
            {"site": "scheduler.step", "at": [3], "max_fires": 1}])):
        out = ex.run_requests(_reqs(3, start=50))
    assert all(r.finish_reason is not None for r in out)
    assert sched.audit() == []
    rep = sched.anatomy_report(an0)
    assert rep["aborted_iterations"] >= 1
    assert rep["iterations"] > 0


def _kernel_model() -> ModelConfig:
    # head_dim 128: the Pallas kernels arm under LMRS_FORCE_KERNELS, so a
    # prefix hit continues through the span program as it does on the chip
    return ModelConfig(vocab_size=512, dim=512, n_layers=2, n_heads=4,
                       n_kv_heads=2, hidden_dim=256, max_seq_len=512,
                       dtype="float32")


_LONG = "the quarterly planning review covered budgets and hiring " * 3

# path -> (env, engine config, model, batches of prompts, program it
# has to show).  Two batches where the second has to meet a warm cache.
_PATHS = {
    "fresh_prefill": ({}, dict(), tiny_model, [["one fresh prompt"]],
                      "prefill"),
    # 128-token pages: the bucket of 64 is short of a page (row-form write)
    "fresh_prefill_short_of_a_page": (
        {}, dict(page_size=128, num_pages=8), tiny_model,
        [["one fresh prompt"]], "prefill"),
    # three prompts of over half the context each (no two share a packed
    # bin) on 12 slots: one wave on the 8-row rung of the prefill ladder
    "fresh_prefill_narrow_wave": (
        {}, dict(max_batch_slots=12, num_pages=64), tiny_model,
        [[f"speaker {i}: " + "the review covered budgets " * 5
          for i in range(3)]], "prefill"),
    "packed": ({}, dict(), tiny_model,
               [["packed prompt one", "second packed prompt here"]],
               "packed"),
    "chunked": ({}, dict(prefill_chunk=64, page_size=16, num_pages=40),
                tiny_model, [[_LONG]], "prefill_chunk"),
    "prefix_hit_spans": (
        {"LMRS_FORCE_KERNELS": "interpret"},
        dict(prefix_cache=True, num_pages=64), _kernel_model,
        [[_LONG + "first question"], [_LONG + "second question"]], "rpa"),
    "span_mixed": ({}, dict(mixed_batch=True, prefill_chunk=64,
                            decode_block=3, num_pages=40), tiny_model,
                   [["short probe", _LONG, "third " * 12]], "rpa"),
    "span_mixed_spec": ({}, dict(mixed_batch=True, speculate_k=3,
                                 prefill_chunk=64, decode_block=3,
                                 num_pages=40), tiny_model,
                        [["short probe", _LONG, "third " * 12]], "rpa"),
    "decode": ({}, dict(), tiny_model, [["decode probe"]], "decode"),
    "spec": ({"LMRS_SPEC_TREE": "0"}, dict(speculate_k=4), tiny_model,
             [["spec probe alpha", "spec probe bravo"]], "spec"),
}


@pytest.mark.parametrize("path", sorted(_PATHS))
def test_every_dispatch_path_lands_in_the_programs_table(monkeypatch, path):
    """One tiny engine run per dispatch site: the site's program is in the
    windowed report with sane fields, the flat counters are the table's
    sums, the ring's prefill_dispatch carries the record, and the audit's
    three table identities hold."""
    from lmrs_tpu.obs import disable_tracing, enable_tracing
    from lmrs_tpu.obs.anatomy import PROMPT_PROGRAMS, RECORD_FIELDS

    env, cfg_kw, model, batches, want = _PATHS[path]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    disable_tracing()
    tracer = enable_tracing()
    eng = JaxEngine(_cfg(**cfg_kw), model())
    try:
        sched = eng._scheduler
        an0, m0 = sched.anatomy_snapshot(), dict(sched.metrics)
        rid = 0
        for prompts in batches:
            out = eng.generate_batch([
                GenerationRequest(prompt=p, request_id=rid + i,
                                  temperature=0.0, max_new_tokens=6)
                for i, p in enumerate(prompts)])
            rid += len(prompts)
            assert all(r.error is None for r in out)
        assert sched.audit() == []
        programs = sched.anatomy_report(an0)["programs"]
        assert want in programs, sorted(programs)
        assert "decode" in programs or "spec" in programs or (
            "rpa" in programs)
        for name, rec in programs.items():
            assert 0 < rec["rows"] <= rec["row_slots"], (name, rec)
            assert 0 <= rec["q_tokens"] <= rec["q_slots"], (name, rec)
            assert rec["prompt_tokens"] <= rec["q_tokens"] or (
                name not in PROMPT_PROGRAMS)
            assert rec["cold"] <= rec["dispatches"]
            assert rec["wide_tokens"] <= rec["q_tokens"], (name, rec)
            # a decode block counts its walks' pages whatever serves its
            # attention; a span dispatch only where the span KERNEL runs
            assert (rec["kv_page_reads"] > 0) == (name == "decode" or (
                path == "prefix_hit_spans" and name == "rpa")), (name, rec)
            for f in RECORD_FIELDS:
                assert rec[f] == sum(k[f] for k in rec["keys"].values())
            # a fresh prefill whose bucket is whole pages writes them whole:
            # row_slots x bucket / page size a dispatch; nothing else does
            for key, krec in rec["keys"].items():
                _, fresh, n, bucket, *_ = (key.split(":") + [""] * 4)
                ps = sched.cache.page_size
                whole = (name == "prefill" and fresh == "True"
                         and int(bucket) % ps == 0)
                assert krec["page_writes"] == (
                    krec["dispatches"] * int(n) * int(bucket) // ps
                    if whole else 0), (key, krec)
        if path == "prefix_hit_spans":
            # the continuation attends the cached pages it did not compute
            assert programs["rpa"]["ctx_tokens"] > 0
            assert programs["rpa"]["prompt_tokens"] > 0
        m1 = sched.metrics
        for f in ("wide_tokens", "kv_page_reads"):
            assert m1["rpa_" + f] - m0["rpa_" + f] == programs.get(
                "rpa", {}).get(f, 0)
        assert (m1["decode_kv_page_reads"] - m0["decode_kv_page_reads"]
                == programs.get("decode", {}).get("kv_page_reads", 0))
        prompt = [r for n, r in programs.items() if n in PROMPT_PROGRAMS]
        assert m1["prefill_dispatches"] - m0["prefill_dispatches"] == sum(
            r["dispatches"] for r in prompt)
        assert (m1["prefill_query_tokens"] - m0["prefill_query_tokens"]
                == sum(r["q_tokens"] for r in prompt))
        assert (m1["prefill_token_slots"] - m0["prefill_token_slots"]
                == sum(r["q_slots"] for r in prompt))
        for f in ("rows", "row_slots"):
            assert m1["prefill_" + f] - m0["prefill_" + f] == sum(
                r[f] for r in prompt), f
        assert (m1["prefill_page_writes"] - m0["prefill_page_writes"]
                == sum(r["page_writes"] for r in prompt))
        if want == "prefill":
            assert (programs["prefill"]["page_writes"] > 0) == (
                path in ("fresh_prefill", "fresh_prefill_narrow_wave"))
        if path == "fresh_prefill_narrow_wave":
            # the key carries the rungs, and the record pays for them: 3
            # rows of 8 (not of the 12 slots) of prompts of 147 tokens or
            # fewer, 8 x 160 positions (not 256), 8 x 160 / 16 pages a layer
            (key, krec), = programs["prefill"]["keys"].items()
            assert key == "prefill:True:8:160:16:False"
            assert (krec["dispatches"], krec["rows"], krec["row_slots"]) == (
                1, 3, 8)
            assert krec["q_slots"] == 1 * 8 * 160
            assert krec["page_writes"] == 8 * 160 // 16
            assert m1["prefill_row_slots"] - m0["prefill_row_slots"] == 8
            assert m1["prefill_rows"] - m0["prefill_rows"] == 3
            assert (m1["prefill_seq_rung_dispatches"]
                    - m0["prefill_seq_rung_dispatches"]) == 1
        assert m1["prefill_tokens"] - m0["prefill_tokens"] == sum(
            r["prompt_tokens"] for r in programs.values())
        # a fresh engine's first sight of each key is its one cold dispatch
        assert m1["cold_dispatches"] == sum(
            len(r["keys"]) for r in programs.values())
        assert m1["cold_seconds"] > 0.0
        evs = [e for e in tracer.events() if e["name"] == "prefill_dispatch"]
        spans = {e["args"]["id"]: e["args"] for e in tracer.events()
                 if e["name"] == "sched.dispatch"}
        assert len(spans) == sum(r["dispatches"] for r in programs.values())
        assert evs and {e["args"]["id"] for e in evs} <= set(spans)
        assert sum(e["args"]["prompt_tokens"] for e in evs) == (
            m1["prefill_tokens"] - m0["prefill_tokens"])
        for e in evs:
            assert {"id", "program", "key", *RECORD_FIELDS[1:]} == set(
                e["args"])
    finally:
        disable_tracing()
        eng.shutdown()


def test_dispatched_programs_are_the_documented_closed_list():
    """docs/OBSERVABILITY.md names the programs a dispatch record can
    carry: that list is ``PROGRAMS`` (six, and no ``mixed``), and a run
    with mixed steps, a warm prefix cache and speculation together
    dispatches nothing outside it."""
    import re
    from pathlib import Path

    from lmrs_tpu.obs.anatomy import PROGRAMS

    doc = (Path(__file__).resolve().parent.parent / "docs"
           / "OBSERVABILITY.md").read_text(encoding="utf-8")
    row = next(ln for ln in doc.splitlines()
               if ln.startswith("| `program` |"))
    documented = re.findall(r"`(\w+)` \(", row.split("|")[2])
    assert documented == list(PROGRAMS) == [
        "prefill", "packed", "prefill_chunk", "rpa", "decode", "spec"]
    eng = JaxEngine(_cfg(mixed_batch=True, prefix_cache=True, speculate_k=3,
                         prefill_chunk=64, decode_block=3, num_pages=64),
                    tiny_model())
    try:
        for start in (0, 10):  # the second batch meets a warm cache
            out = eng.generate_batch([
                GenerationRequest(prompt=_LONG + f"question {start + i}",
                                  request_id=start + i, temperature=0.0,
                                  max_new_tokens=6) for i in range(3)])
            assert all(r.error is None for r in out)
        sched = eng._scheduler
        assert sched.audit() == []
        assert sched.metrics["mixed_dispatches"] > 0
        programs = sched.anatomy_report()["programs"]
        assert programs and set(programs) <= set(documented), sorted(programs)
    finally:
        eng.shutdown()


def test_warm_rerun_is_not_cold_and_windows_to_its_own_dispatches(
        mixed_engine):
    """The same traffic again on one engine, once its prefix cache and
    its programs are warm: a window's programs table repeats the window
    before it and holds no cold dispatch."""
    sched = mixed_engine._scheduler
    for _ in range(2):  # the second pass meets the cache the first left
        mixed_engine.generate_batch(_reqs(3, start=200))
    snap, m0 = sched.anatomy_snapshot(), dict(sched.metrics)
    mixed_engine.generate_batch(_reqs(3, start=200))
    first = sched.anatomy_report(snap)["programs"]
    m1 = dict(sched.metrics)
    assert m1["cold_dispatches"] == m0["cold_dispatches"]
    assert m1["cold_seconds"] == m0["cold_seconds"]
    assert all(r["cold"] == 0 and r["cold_ms"] == 0.0
               for r in first.values())
    snap2 = sched.anatomy_snapshot()
    mixed_engine.generate_batch(_reqs(3, start=200))
    second = sched.anatomy_report(snap2)["programs"]
    strip = lambda ps: {n: {f: v for f, v in r.items() if f != "keys"}
                        for n, r in ps.items()}
    assert strip(second) == strip(first)
    assert sched.audit() == []


def test_table_identities_survive_a_dispatch_fault(mixed_engine):
    """A fault between two dispatches: the iteration is discarded from the
    segment totals, but the records of what was dispatched stay beside the
    scheduler's own counters — audit() keeps all three identities."""
    from lmrs_tpu.engine.executor import MapExecutor
    from lmrs_tpu.obs.anatomy import PROMPT_PROGRAMS
    from lmrs_tpu.testing import faults
    from lmrs_tpu.testing.faults import FaultPlan

    sched = mixed_engine._scheduler
    ex = MapExecutor(mixed_engine, EngineConfig(retry_attempts=3,
                                                retry_delay=0.01))
    with faults.injected(FaultPlan(seed=5, faults=[
            {"site": "scheduler.step", "at": [2, 5], "max_fires": 2}])):
        out = ex.run_requests(_reqs(4, start=300))
    assert all(r.finish_reason is not None for r in out)
    assert sched.audit() == []
    programs = sched.anatomy_report()["programs"]
    m = sched.metrics
    assert sum(r["prompt_tokens"] for r in programs.values()) == (
        m["prefill_tokens"])
    assert sum(r["q_tokens"] for n, r in programs.items()
               if n in PROMPT_PROGRAMS) == m["prefill_query_tokens"]
    for rec in programs.values():
        for key, k in rec["keys"].items():
            assert k["q_slots"] % k["dispatches"] == 0, key


def test_slow_step_postmortem_schema(mixed_engine, monkeypatch, tmp_path):
    """LMRS_ANATOMY_SLOW_MS armed at a hair-trigger threshold: every
    iteration files a schema-valid slow_step postmortem whose extra block
    carries the full segment split of the offending step."""
    from lmrs_tpu.obs import validate_postmortem_file

    monkeypatch.setenv("LMRS_POSTMORTEM_DIR", str(tmp_path))
    monkeypatch.setenv("LMRS_POSTMORTEM_MIN_S", "0")
    monkeypatch.setenv("LMRS_ANATOMY_SLOW_MS", "0.0001")
    mixed_engine.generate_batch(_reqs(1, start=70))
    dumps = sorted(tmp_path.glob("postmortem-slow_step-*.json"))
    assert dumps, "hair-trigger threshold produced no slow_step postmortem"
    doc = validate_postmortem_file(dumps[0])
    assert doc["reason"] == "slow_step"
    an = doc["extra"]["anatomy"]
    assert an["class"] in CLASSES
    assert an["wall_ms"] > an["threshold_ms"] == 0.0001
    assert set(an["segments_ms"]) == set(SEGMENTS)
    assert "residual_ms" in an
    # wall reconciles against the dumped split too (rounded to µs)
    assert an["wall_ms"] == pytest.approx(
        sum(an["segments_ms"].values()) + an["residual_ms"], abs=0.05)


def test_slow_step_disabled_by_default(mixed_engine, monkeypatch,
                                       tmp_path):
    monkeypatch.setenv("LMRS_POSTMORTEM_DIR", str(tmp_path))
    monkeypatch.delenv("LMRS_ANATOMY_SLOW_MS", raising=False)
    mixed_engine.generate_batch(_reqs(1, start=80))
    assert not list(tmp_path.glob("postmortem-slow_step-*.json"))


def test_scheduler_report_flags_stale_rtt(mixed_engine):
    """The scheduler's report wires the perf RTT sample through the stale
    guard: an aged sample is flagged, never subtracted."""
    sched = mixed_engine._scheduler
    clock = FakeClock()
    perf = sched._perf
    old = (perf._rtt, perf._rtt_t, perf._clock)
    try:
        perf._clock = clock
        perf._rtt, perf._rtt_t = 0.001, clock.t
        rep = sched.anatomy_report()
        assert rep["rtt_stale"] is False
        clock.tick(10_000.0)           # far past 2x the 300 s cadence
        rep = sched.anatomy_report()
        assert rep["rtt_stale"] is True
        assert "device_wait_us_step" not in rep
    finally:
        perf._rtt, perf._rtt_t, perf._clock = old


# -------------------------------------------------- kill-switch parity


def test_kill_switch_byte_parity(monkeypatch):
    """LMRS_ANATOMY=0 must be byte-identical: same greedy text, and
    metrics_report's key set is EXACTLY the on-report's minus "anatomy"
    (the pre-anatomy shape restored, nothing else disturbed)."""
    def run(off: bool):
        if off:
            monkeypatch.setenv("LMRS_ANATOMY", "0")
        else:
            monkeypatch.delenv("LMRS_ANATOMY", raising=False)
        eng = JaxEngine(_cfg(mixed_batch=True), tiny_model())
        try:
            out = eng.generate_batch(_reqs(2))
            rep = eng._scheduler.metrics_report()
            assert eng._scheduler.audit() == []
            return [(r.text, r.finish_reason) for r in out], rep
        finally:
            eng.shutdown()

    on_out, on_rep = run(off=False)
    off_out, off_rep = run(off=True)
    assert off_out == on_out
    assert "anatomy" not in off_rep
    assert set(off_rep) == set(on_rep) - {"anatomy"}


def test_mock_kill_switch_parity(monkeypatch):
    """The mock reads the switch live: identical results either way, no
    anatomy key in engine_metrics when off."""
    def run():
        eng = MockEngine(seed=0, mixed_batch=True)
        out = eng.generate_batch(_reqs(4, budget=12))
        return ([(r.text, r.completion_tokens, r.finish_reason)
                 for r in out], eng.engine_metrics())

    monkeypatch.delenv("LMRS_ANATOMY", raising=False)
    on_out, on_metrics = run()
    assert on_metrics["anatomy"]["enabled"] is True
    monkeypatch.setenv("LMRS_ANATOMY", "0")
    off_out, off_metrics = run()
    assert off_out == on_out
    assert "anatomy" not in off_metrics
    assert set(off_metrics) == set(on_metrics) - {"anatomy"}


# ----------------------------------------------------------- mock parity


def test_mock_anatomy_is_deterministic_and_schema_matched():
    """Two mock runs over identical traffic produce byte-identical
    anatomy documents (token-count-derived, never wall clocks), with the
    scheduler report's exact top-level schema and residual 0."""
    def doc():
        eng = MockEngine(seed=0, mixed_batch=True)
        eng.generate_batch(_reqs(4, budget=12))
        return eng.anatomy_report()

    a, b = doc(), doc()
    assert a == b
    assert a["residual_ms"] == 0.0
    assert a["iterations"] > 0
    # schema parity with the scheduler's report (the rtt keys are
    # optional extras the scheduler adds when a sample exists, and the
    # ``programs`` table is the scheduler's alone: the mock dispatches
    # no device program)
    want = {"object", "enabled", "iterations", "aborted_iterations",
            "wall_ms", "residual_ms", "segments_ms",
            "host_overhead_us_step", "classes", "buckets",
            "rpa_pad_waste_ratio"}
    assert set(a) == want
    # residual-0 construction: wall is exactly the segment sum
    assert a["wall_ms"] == pytest.approx(sum(a["segments_ms"].values()),
                                         abs=1e-6)
    for cls_rep in a["classes"].values():
        assert set(cls_rep) == {"iterations", "p50_us", "p95_us"}


def test_mock_bucket_math_hand_computed():
    """The emulated bucket note against hand arithmetic: 20 real tokens
    in a 32-token bucket -> 1 page -> window 4; padded 12; first sight
    charges the deterministic emulated compile (32 tokens * 1 µs)."""
    eng = MockEngine(seed=0, mixed_batch=True)
    eng._note_rpa_bucket(32, 20)
    eng._note_rpa_bucket(32, 30)
    rep = eng.anatomy_report()
    b = rep["buckets"]["32x4"]
    assert b["dispatches"] == 2
    assert b["real_tokens"] == 50
    assert b["padded_tokens"] == 14
    assert b["real_tokens"] + b["padded_tokens"] == 2 * 32
    assert b["pad_waste"] == pytest.approx(14 / 64, abs=1e-4)
    # first sight charged 32 µs of emulated compile exactly once (the
    # report's ms column rounds that to 0.0 at its 0.1 ms precision)
    assert eng._an_buckets[(32, 4)]["compile_s"] == pytest.approx(32e-6)
    assert b["compile_ms"] == 0.0
    assert rep["rpa_pad_waste_ratio"] == pytest.approx(14 / 64, abs=1e-4)


# ------------------------------------------------------------------ wire


def _get_json(host: str, port: int, path: str):
    with urllib.request.urlopen(f"http://{host}:{port}{path}",
                                timeout=10) as resp:
        return resp.status, json.loads(resp.read())


def test_v1_anatomy_endpoint_and_501s(monkeypatch):
    from lmrs_tpu.serving.server import EngineHTTPServer

    eng = MockEngine(seed=0, mixed_batch=True)
    srv = EngineHTTPServer(eng, port=0, batch_window_s=0.01)
    srv.start_background()
    try:
        eng.generate_batch(_reqs(2, budget=8))
        status, doc = _get_json(srv.host, srv.port, "/v1/anatomy")
        assert status == 200
        assert doc["object"] == "anatomy" and doc["enabled"] is True
        assert doc["iterations"] > 0
        # switch off live: the endpoint refuses rather than serving an
        # empty shell (explicit 501, typed error)
        monkeypatch.setenv("LMRS_ANATOMY", "0")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}/v1/anatomy", timeout=10)
        assert ei.value.code == 501
        err = json.loads(ei.value.read())
        assert err["error"]["type"] == "anatomy_error"
    finally:
        srv.shutdown()


def test_v1_anatomy_501_without_hook():
    from lmrs_tpu.serving.server import EngineHTTPServer

    class Bare:
        def generate_batch(self, requests, on_tokens=None):
            return []

        def shutdown(self):
            pass

    srv = EngineHTTPServer(Bare(), port=0, batch_window_s=0.01)
    srv.start_background()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}/v1/anatomy", timeout=10)
        assert ei.value.code == 501
    finally:
        srv.shutdown()


def test_router_fleet_anatomy_merge():
    """The router pulls every backend's /v1/anatomy page and serves the
    merged view with per-host raw documents alongside."""
    from lmrs_tpu.serving.router import RouterEngine
    from lmrs_tpu.serving.server import EngineHTTPServer

    eng = MockEngine(seed=0, mixed_batch=True)
    srv = EngineHTTPServer(eng, port=0, batch_window_s=0.01)
    srv.start_background()
    router = RouterEngine([f"127.0.0.1:{srv.port}"])
    try:
        router.generate_batch(_reqs(2, budget=8))
        doc = router.anatomy_report()
        assert doc["enabled"] is True and doc["fleet"] is True
        assert doc["iterations"] > 0
        assert len(doc["per_host"]) == 1
        assert doc["per_host"][0]["host"] == f"127.0.0.1:{srv.port}"
        assert doc["unreachable"] == []
        # the merged totals equal the single host's (one-backend fleet)
        assert doc["iterations"] == doc["per_host"][0]["iterations"]
    finally:
        router.shutdown()
        srv.shutdown()
