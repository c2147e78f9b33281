"""Continuous-batching scheduler tests (CPU, tiny model)."""

from pathlib import Path

import jax
import numpy as np
import pytest

from lmrs_tpu.config import EngineConfig, ModelConfig
from lmrs_tpu.engine.api import GenerationRequest
from lmrs_tpu.engine.jax_engine import JaxEngine


def tiny_model():
    return ModelConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                       hidden_dim=128, max_seq_len=256, dtype="float32")


@pytest.fixture(scope="module")
def cont_engine():
    ec = EngineConfig(backend="jax", scheduler="continuous", max_tokens=24,
                      max_batch_slots=2, seed=0)
    return JaxEngine(ec, tiny_model())


def test_more_requests_than_slots(cont_engine):
    """6 requests through 2 slots: slots must be recycled, all complete,
    order preserved."""
    reqs = [GenerationRequest(prompt=f"item {i} " * (i + 1), request_id=i,
                              temperature=0.8, max_new_tokens=8 + i)
            for i in range(6)]
    out = cont_engine.generate_batch(reqs)
    assert [r.request_id for r in out] == list(range(6))
    for i, r in enumerate(out):
        assert r.error is None
        assert r.completion_tokens <= 8 + i  # budget respected exactly
    m = cont_engine._scheduler.metrics
    assert m["prefill_tokens"] > 0
    assert m["decode_tokens"] > 0
    assert m["decode_dispatches"] > 0


def test_mixed_lengths_interleave(cont_engine):
    """A short and a long request share the batch; the short one's slot is
    reused while the long one still decodes."""
    reqs = [
        GenerationRequest(prompt="short", request_id=0, temperature=0.5, max_new_tokens=2),
        GenerationRequest(prompt="long " * 30, request_id=1, temperature=0.5, max_new_tokens=24),
        GenerationRequest(prompt="third", request_id=2, temperature=0.5, max_new_tokens=2),
    ]
    out = cont_engine.generate_batch(reqs)
    assert all(r.error is None for r in out)
    assert out[0].completion_tokens <= 2
    assert out[2].completion_tokens <= 2


def test_greedy_matches_static_scheduler():
    """Same greedy request through static and continuous scheduling must
    produce the same text (scheduling policy must not change results)."""
    mc = tiny_model()
    req = GenerationRequest(prompt="the quick brown fox", temperature=0.0,
                            max_new_tokens=12)
    static = JaxEngine(EngineConfig(backend="jax", scheduler="static",
                                    max_tokens=12, max_batch_slots=2, seed=0), mc)
    a = static.generate_batch([req])[0]
    cont = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                  max_tokens=12, max_batch_slots=2, seed=0), mc)
    b = cont.generate_batch([req])[0]
    assert a.text == b.text


def test_chunked_prefill_matches_fresh():
    """A prompt longer than prefill_chunk runs the windowed continuation
    path; greedy output must be identical to whole-prompt prefill."""
    mc = tiny_model()
    req = GenerationRequest(prompt="alpha beta gamma " * 12, temperature=0.0,
                            max_new_tokens=10)
    whole = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                   max_tokens=10, max_batch_slots=2, seed=0,
                                   prefill_chunk=4096), mc)
    a = whole.generate_batch([req])[0]
    chunked = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                     max_tokens=10, max_batch_slots=2, seed=0,
                                     prefill_chunk=64), mc)
    b = chunked.generate_batch([req])[0]
    assert a.text == b.text
    # the chunked run must actually have taken the window path
    assert chunked._scheduler._prefill_window_fns, "window path not exercised"


def test_chunked_prefill_piggybacks_decode():
    """While a long prompt prefills chunk by chunk, an already-active short
    request keeps decoding — and prefilling pages are never corrupted by
    decode's dummy writes (outputs stay identical to isolated runs)."""
    mc = tiny_model()
    ec = EngineConfig(backend="jax", scheduler="continuous", max_tokens=12,
                      max_batch_slots=2, seed=3, prefill_chunk=64)
    eng = JaxEngine(ec, mc)
    short = GenerationRequest(prompt="short prompt", request_id=0,
                              temperature=0.0, max_new_tokens=12)
    long_ = GenerationRequest(prompt="delta epsilon zeta " * 12, request_id=1,
                              temperature=0.0, max_new_tokens=12)
    together = eng.generate_batch([short, long_])

    solo_a = JaxEngine(ec, mc).generate_batch([short])[0]
    solo_b = JaxEngine(ec, mc).generate_batch([long_])[0]
    assert together[0].text == solo_a.text
    assert together[1].text == solo_b.text


def test_single_slot_serializes():
    mc = tiny_model()
    eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                 max_tokens=4, max_batch_slots=1, seed=1), mc)
    reqs = [GenerationRequest(prompt=f"r{i}", request_id=i, temperature=0.3,
                              max_new_tokens=4) for i in range(3)]
    out = eng.generate_batch(reqs)
    assert [r.request_id for r in out] == [0, 1, 2]
    assert all(r.error is None for r in out)


def test_engine_metrics_report(cont_engine):
    """engine_metrics() exposes derived serving metrics with sane ranges."""
    reqs = [GenerationRequest(prompt="metrics probe", request_id=0,
                              max_new_tokens=6)]
    cont_engine.generate_batch(reqs)
    em = cont_engine.engine_metrics()
    assert em["prefill_tokens"] > 0 and em["decode_tokens"] > 0
    assert em["prefill_tokens_per_sec"] > 0
    assert em["decode_tokens_per_sec"] > 0
    assert 0.0 < em["mean_decode_occupancy"] <= 1.0
    assert 0.0 < em["peak_kv_page_utilization"] <= 1.0
    assert em["scheduler_seconds"] > 0
    # device-wait attribution: every run() fetch is charged via _timed_get,
    # so a run that generated tokens must show blocked time, and the split
    # must stay within the scheduler wall (host share clamped >= 0)
    assert em["blocked_seconds"] > 0
    assert em["host_seconds"] >= 0
    assert em["blocked_seconds"] <= em["scheduler_seconds"] + 1e-6


def test_latency_percentiles_in_metrics(cont_engine):
    """TTFT and decode-block-gap percentiles (VERDICT r4 item 5) surface
    in metrics_report with sane values, and reset_latency_stats clears
    the sample windows."""
    sched = cont_engine._scheduler
    sched.reset_latency_stats()
    reqs = [GenerationRequest(prompt=f"latency probe {i}", request_id=i,
                              temperature=0.7, max_new_tokens=10)
            for i in range(3)]
    cont_engine.generate_batch(reqs)
    em = cont_engine.engine_metrics()
    ttft = em["ttft_ms"]
    # every fresh request contributes exactly one TTFT sample
    assert ttft is not None and ttft["n"] == 3
    assert 0.0 < ttft["p50"] <= ttft["p90"] <= ttft["p99"]
    # 10 new tokens through default decode_block=8 -> >= 2 dispatches per
    # wave -> at least one inter-dispatch gap
    gap = em["decode_block_gap_ms"]
    assert gap is not None and gap["n"] >= 1
    assert 0.0 < gap["p50"] <= gap["p99"]
    assert em["stalls"] >= 0 and em["cancelled"] >= 0
    sched.reset_latency_stats()
    em2 = cont_engine.engine_metrics()
    assert em2["ttft_ms"] is None and em2["decode_block_gap_ms"] is None


def test_mock_engine_metrics_empty():
    from lmrs_tpu.engine.mock import MockEngine

    assert MockEngine().engine_metrics() == {}


def test_ragged_kernel_failure_raises_no_xla_fallback():
    """A Pallas kernel that cannot lower RAISES out of the scheduler: there
    is no catch-and-retry on XLA attention (on hardware one Mosaic refusal
    would otherwise quietly move the whole engine off its kernels), and the
    kernel gate is never flipped behind the caller's back."""
    engine = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                    max_tokens=8, max_batch_slots=2, seed=0),
                       tiny_model())
    sched = engine._scheduler
    sched._use_ragged = True  # force the kernel on CPU, where it can't lower
    try:
        with pytest.raises(ValueError, match="interpret mode"):
            engine.generate_batch(
                [GenerationRequest(prompt="no fallback probe", request_id=0,
                                   max_new_tokens=4)])
        assert sched._use_ragged is True
        assert not hasattr(sched, "_invalidate_compiled")
    finally:
        engine.shutdown()


def test_tp_sharded_continuous_serving_matches_single_device():
    """Continuous-batching map over a tp=2 mesh: params AND the paged KV
    pool shard on the head axis; greedy output must equal single-device
    (BASELINE config #3's architecture, scaled to the virtual mesh)."""
    from lmrs_tpu.config import MeshConfig

    reqs = [GenerationRequest(prompt=f"tensor parallel serving probe {i} " * 6,
                              request_id=i, max_new_tokens=10)
            for i in range(3)]
    single = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                    max_tokens=16, max_batch_slots=2, seed=0),
                       tiny_model())
    want = [r.text for r in single.generate_batch(reqs)]
    single.shutdown()

    tp = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                max_tokens=16, max_batch_slots=2, seed=0),
                   tiny_model(), mesh_cfg=MeshConfig(dp=1, tp=2))
    kv = tp._scheduler.cache.k
    # page-major pool [L*P, K, ps, hd]: kv heads shard on axis 1
    assert kv.sharding.shard_shape(kv.shape)[1] == tiny_model().n_kv_heads // 2
    got = [r.text for r in tp.generate_batch(reqs)]
    tp.shutdown()
    assert got == want


def test_tp_sharded_kernels_continuous_serving(monkeypatch):
    """TP serving on the KERNEL path (VERDICT r1 item 2): with
    LMRS_FORCE_KERNELS=interpret the ragged decode + flash prefill Pallas
    kernels run via shard_map over the tp axis (interpret mode on the CPU
    mesh); greedy output must match the single-device XLA path and no
    runtime fallback may fire."""
    from lmrs_tpu.config import MeshConfig

    mc = ModelConfig(vocab_size=512, dim=512, n_layers=2, n_heads=4,
                     n_kv_heads=2, hidden_dim=256, max_seq_len=512,
                     dtype="float32")
    assert mc.hd == 128  # kernel-eligible head dim
    ec = lambda: EngineConfig(backend="jax", scheduler="continuous",
                              max_tokens=6, max_batch_slots=2, seed=0,
                              decode_block=3)
    # prompts long enough (>=256 byte tokens) to take the flash prefill path
    reqs = [GenerationRequest(prompt=f"tp kernel serving probe {i} " * 12,
                              request_id=i, temperature=0.0, max_new_tokens=6)
            for i in range(3)]

    single = JaxEngine(ec(), mc)
    assert not single._scheduler._use_ragged  # CPU: XLA fallback path
    want = [r.text for r in single.generate_batch(reqs)]
    single.shutdown()

    monkeypatch.setenv("LMRS_FORCE_KERNELS", "interpret")
    tp = JaxEngine(ec(), mc, mesh_cfg=MeshConfig(dp=1, tp=2))
    sched = tp._scheduler
    assert sched._use_ragged and sched._use_flash
    got = [r.text for r in tp.generate_batch(reqs)]
    # no silent degradation: the kernels must have survived the whole run
    assert sched._use_ragged and sched._use_flash
    tp.shutdown()
    assert got == want


def test_packed_prefill_matches_unpacked(monkeypatch):
    """Packed prompt prefill (VERDICT r1 item 3): same-wave fresh prompts
    concatenate into one [1, S] segment-masked dispatch; greedy output must
    be identical to per-prompt prefill (cross-segment leakage would change
    it), and the packed program must actually have run."""
    mc = tiny_model()
    reqs = [GenerationRequest(prompt=f"pack probe {i} " * (2 + 3 * i),
                              request_id=i, temperature=0.0, max_new_tokens=8)
            for i in range(4)]
    ec = lambda: EngineConfig(backend="jax", scheduler="continuous",
                              max_tokens=8, max_batch_slots=4, seed=0)
    monkeypatch.setenv("LMRS_PACK_PREFILL", "0")
    plain = JaxEngine(ec(), mc)
    want = [r.text for r in plain.generate_batch(reqs)]
    plain.shutdown()

    monkeypatch.setenv("LMRS_PACK_PREFILL", "1")
    packed = JaxEngine(ec(), mc)
    got = [r.text for r in packed.generate_batch(reqs)]
    assert packed._scheduler._packed_prefill_fns, "packed path not exercised"
    packed.shutdown()
    assert got == want


def test_packed_prefill_with_tp_kernels(monkeypatch):
    """Packing composes with the TP kernel path: segment-masked flash
    prefill via shard_map (interpret) must match the single-device
    unpacked XLA run."""
    from lmrs_tpu.config import MeshConfig

    mc = ModelConfig(vocab_size=512, dim=512, n_layers=2, n_heads=4,
                     n_kv_heads=2, hidden_dim=256, max_seq_len=1024,
                     dtype="float32")
    reqs = [GenerationRequest(prompt=f"tp pack probe {i} " * 12, request_id=i,
                              temperature=0.0, max_new_tokens=4)
            for i in range(3)]
    ec = lambda: EngineConfig(backend="jax", scheduler="continuous",
                              max_tokens=4, max_batch_slots=4, seed=0,
                              decode_block=2, prefill_chunk=1024)
    monkeypatch.setenv("LMRS_PACK_PREFILL", "0")
    single = JaxEngine(ec(), mc)
    want = [r.text for r in single.generate_batch(reqs)]
    single.shutdown()

    monkeypatch.setenv("LMRS_PACK_PREFILL", "1")
    monkeypatch.setenv("LMRS_FORCE_KERNELS", "interpret")
    tp = JaxEngine(ec(), mc, mesh_cfg=MeshConfig(dp=1, tp=2))
    got = [r.text for r in tp.generate_batch(reqs)]
    assert tp._scheduler._packed_prefill_fns, "packed path not exercised"
    assert tp._scheduler._use_flash, "flash kernel silently degraded"
    tp.shutdown()
    assert got == want


def test_ring_prefill_serving_cp_matches_single_device():
    """Cache-aware ring prefill (VERDICT r1 item 5, SURVEY §5.7 tier b):
    under an sp=4 mesh, a long chunk's fresh prefill runs ring attention
    with the sequence sharded over sp while K/V scatter into the page pool;
    greedy output must match the single-device run (decode then reads the
    pages as usual)."""
    from lmrs_tpu.config import MeshConfig

    mc = ModelConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                     n_kv_heads=2, hidden_dim=128, max_seq_len=2048,
                     dtype="float32")
    # one LONG chunk (~1.5k tokens) + a short one sharing the stream
    reqs = [GenerationRequest(prompt="long context line " * 80, request_id=0,
                              temperature=0.0, max_new_tokens=8),
            GenerationRequest(prompt="short probe", request_id=1,
                              temperature=0.0, max_new_tokens=8)]
    ec = lambda: EngineConfig(backend="jax", scheduler="continuous",
                              max_tokens=8, max_batch_slots=2, seed=0,
                              prefill_chunk=2048, decode_block=4)
    single = JaxEngine(ec(), mc)
    want = [r.text for r in single.generate_batch(reqs)]
    single.shutdown()

    cp = JaxEngine(ec(), mc, mesh_cfg=MeshConfig(dp=1, tp=1, sp=4))
    sched = cp._scheduler
    assert sched._use_ring, "ring prefill not selected under sp mesh"
    got = [r.text for r in cp.generate_batch(reqs)]
    cp.shutdown()
    assert got == want


def _short_ctx_model():
    # max_seq_len=96 @ page_size=16 -> max_pages_per_slot=6, so a small
    # explicit num_pages is HONORED (the pool floor is 7), making the page
    # budgets in the pressure tests below real
    return ModelConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                       n_kv_heads=2, hidden_dim=128, max_seq_len=96,
                       dtype="float32")


def test_prompt_only_admission_raises_concurrency():
    """Admission reserves prompt pages only (VERDICT r1 item 6): with 6
    usable pages and ~2-page prompts whose worst-case budget is 3 pages,
    at least 3 slots must run concurrently — worst-case reservation would
    cap at 2."""
    eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                 max_tokens=24, max_batch_slots=4, seed=0,
                                 page_size=16, num_pages=7, decode_block=4),
                    _short_ctx_model())
    assert eng._scheduler.cache.num_pages == 7  # budget honored, not floored
    # ~20 byte-token prompts -> 2 pages each; budget 20+24+4 = 48 -> 3 pages
    reqs = [GenerationRequest(prompt=f"concurrency probe {i}", request_id=i,
                              temperature=0.0, max_new_tokens=24)
            for i in range(4)]
    out = eng.generate_batch(reqs)
    assert all(r.error is None for r in out)
    m = eng._scheduler.metrics
    assert m["peak_active_slots"] >= 3, m
    eng.shutdown()


def test_preemption_under_page_pressure_preserves_output():
    """Under a pool too small for every admitted slot's decode growth, the
    youngest slot is preempted and requeued; every request must still
    complete with output identical to an abundant-pool run (continuation
    re-prefills prompt + generated-so-far), and no deadlock."""
    mc = _short_ctx_model()
    reqs = [GenerationRequest(prompt=f"pressure probe {i} " * 3, request_id=i,
                              temperature=0.0, max_new_tokens=40)
            for i in range(4)]
    roomy = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                   max_tokens=40, max_batch_slots=4, seed=0,
                                   page_size=16, num_pages=1, decode_block=4),
                      mc)
    want = roomy.generate_batch(reqs)
    assert all(r.error is None for r in want)
    roomy.shutdown()

    # 9 usable pages: four ~4-page prompts can't all fit worst-case (~6
    # pages each through a 40-token decode) -> growth collides, preemption
    tight = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                   max_tokens=40, max_batch_slots=4, seed=0,
                                   page_size=16, num_pages=10, decode_block=4),
                      mc)
    assert tight._scheduler.cache.num_pages == 10
    got = tight.generate_batch(reqs)
    m = tight._scheduler.metrics
    tight.shutdown()
    assert all(r.error is None for r in got)
    assert m["preemptions"] > 0, f"pressure never materialized: {m}"
    assert [r.text for r in got] == [r.text for r in want]
    # accounting must not double-count re-prefilled continuation tokens
    for g, w in zip(got, want):
        assert g.prompt_tokens == w.prompt_tokens
        assert g.completion_tokens == w.completion_tokens


def test_stalled_slot_keeps_first_token():
    """Regression: a slot that finishes prefill but must STALL (pool pages
    held by a mid-prefill neighbor, no preemptable decode victim) must not
    drop its deferred first token — output must equal a roomy-pool run."""
    mc = _short_ctx_model()
    # short prompt (31 ids: 2 pages, but 31+decode_block=35 needs a 3rd)
    # finishes prefill in one chunk and must grow immediately, while the
    # long prompt (2 chunks of 64, 5 pages) is still mid-prefill and not
    # preemptable: 2+5 = all 7 usable pages -> the short slot STALLS
    reqs = [GenerationRequest(prompt="s" * 30, request_id=0,
                              temperature=0.0, max_new_tokens=8),
            GenerationRequest(prompt="x" * 78, request_id=1,
                              temperature=0.0, max_new_tokens=8)]
    ec = lambda npages: EngineConfig(
        backend="jax", scheduler="continuous", max_tokens=8,
        max_batch_slots=2, seed=0, page_size=16, num_pages=npages,
        decode_block=4, prefill_chunk=64)
    roomy = JaxEngine(ec(1), mc)  # worst-case pool: no pressure
    want = [r.text for r in roomy.generate_batch(reqs)]
    roomy.shutdown()

    tight = JaxEngine(ec(8), mc)
    got = [r.text for r in tight.generate_batch(reqs)]
    m = tight._scheduler.metrics
    tight.shutdown()
    assert m["stalls"] > 0, f"stall branch never exercised: {m}"
    assert got == want


def test_pow2_bucket():
    from lmrs_tpu.engine.scheduler import _pow2_bucket

    assert _pow2_bucket(64, 64) == 64
    assert _pow2_bucket(65, 64) == 128
    for n in (1, 64, 100, 1000, 2049, 4096):
        assert _pow2_bucket(n, 64) >= n


def test_compact_batch_drain_matches_full():
    """With few live slots the decode dispatch compacts to a small batch;
    greedy output must be identical to a small-B engine."""
    mc = tiny_model()
    reqs = [GenerationRequest(prompt=f"compact drain probe {i}", request_id=i,
                              temperature=0.0, max_new_tokens=10)
            for i in range(2)]
    small = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                   max_tokens=10, max_batch_slots=2, seed=0), mc)
    want = [r.text for r in small.generate_batch(reqs)]
    small.shutdown()

    wide = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                  max_tokens=10, max_batch_slots=16, seed=0), mc)
    got = [r.text for r in wide.generate_batch(reqs)]
    wide.shutdown()
    assert got == want


def test_on_tokens_streaming_deltas_concat_to_result():
    """on_tokens deltas (one per decode block) must concatenate to exactly
    the final result text, including the stop-sequence trim — the contract
    the SSE front-end's streamed bodies rely on."""
    mc = tiny_model()
    eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                 max_tokens=24, max_batch_slots=2, seed=0,
                                 decode_block=4), mc)
    reqs = [GenerationRequest(prompt=f"stream probe {i}", request_id=i,
                              temperature=0.9, max_new_tokens=24)
            for i in range(3)]
    deltas: dict[int, list[str]] = {}
    calls: list[int] = []

    def on_tokens(rid, text):
        deltas.setdefault(rid, []).append(text)
        calls.append(rid)

    out = eng.generate_batch(reqs, on_tokens=on_tokens)
    for r in out:
        assert r.error is None
        assert "".join(deltas.get(r.request_id, [])) == r.text
    # decode_block=4 over 24 tokens: streaming must be incremental, not one
    # whole-text delta at completion
    assert any(len(v) > 1 for v in deltas.values()), deltas
    eng.shutdown()


def test_on_tokens_streaming_respects_stop_sequences():
    """A streamed request with a stop sequence must never emit text past
    the stop — deltas are cut from the trimmed text."""
    mc = tiny_model()
    eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                 max_tokens=24, max_batch_slots=2, seed=0,
                                 decode_block=4), mc)
    # greedy decode of the tiny random model produces SOME deterministic
    # text; use its own prefix as the stop to guarantee a mid-stream hit
    probe = eng.generate_batch([GenerationRequest(
        prompt="stop probe", temperature=0.0, max_new_tokens=24)])[0]
    assert probe.text
    stop = probe.text[max(0, len(probe.text) // 2):][:3]
    got: list[str] = []
    res = eng.generate_batch(
        [GenerationRequest(prompt="stop probe", temperature=0.0,
                           max_new_tokens=24, stop=(stop,))],
        on_tokens=lambda rid, t: got.append(t))[0]
    assert stop not in res.text
    assert "".join(got) == res.text
    eng.shutdown()


def test_on_tokens_freezes_on_non_prefix_stable_decode():
    """HF-style tokenizers can rewrite earlier characters as tokens arrive
    (cleanup_tokenization_spaces): the stream must FREEZE — never emit
    characters that later change — and the final result text stays
    authoritative (round-3 review finding)."""
    from lmrs_tpu.data.tokenizer import ByteTokenizer

    class UnstableTokenizer(ByteTokenizer):
        """Decodes normally until >8 ids, then rewrites the first char —
        a caricature of HF cleanup's retroactive edits."""

        def decode(self, ids):
            text = super().decode(ids)
            if len(list(ids)) > 8 and text:
                return "#" + text[1:]
            return text

    mc = tiny_model()
    eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                 max_tokens=20, max_batch_slots=1, seed=0,
                                 decode_block=4), mc,
                    tokenizer=UnstableTokenizer())
    got: list[str] = []
    res = eng.generate_batch(
        [GenerationRequest(prompt="prefix stability probe", request_id=0,
                           temperature=0.0, max_new_tokens=20)],
        on_tokens=lambda rid, t: got.append(t))[0]
    eng.shutdown()
    assert res.error is None
    streamed = "".join(got)
    # the retroactive rewrite ('#' at position 0) appears in the FINAL text
    # but must never have been streamed: the stream froze at the last
    # stable prefix instead of emitting characters that later changed
    assert res.text.startswith("#")
    assert "#" not in streamed
    assert streamed  # deltas did flow before the instability hit


def test_max_new_clamped_to_context_window():
    """A decode budget >= max_seq_len must clamp (a negative truncation
    limit previously DUPLICATED the prompt middle or emptied it)."""
    mc = tiny_model()  # max_seq_len 256
    eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                 max_tokens=100000, max_batch_slots=1,
                                 seed=0, decode_block=4), mc)
    ids, max_new = eng._scheduler._encode(
        GenerationRequest(prompt="x" * 500, request_id=0,
                          max_new_tokens=100000))
    assert max_new == mc.max_seq_len - 1
    assert 1 <= len(ids) <= mc.max_seq_len - max_new
    res = eng.generate_batch([
        GenerationRequest(prompt="short", request_id=0, temperature=0.0,
                          max_new_tokens=100000)])[0]
    eng.shutdown()
    assert res.error is None
    assert res.completion_tokens <= mc.max_seq_len - 1


# ------------------------------------------- the prefill programs' row ladder


@pytest.mark.parametrize("slots", [4, 8, 16, 24, 64])
def test_prefill_row_rung_holds_the_group_on_a_short_ladder(slots):
    """The operand rows of a batched prefill dispatch: 1 for a lone prompt,
    else the smallest of 8, 16, 32, ... capped at the slots that holds the
    group; a doubling ladder keeps the compiled row counts of a sequence
    bucket at ceil(log2(slots / 8)) + 2 or fewer (24 slots: 1 / 8 / 16 / 24)."""
    import math

    from lmrs_tpu.engine.scheduler import prefill_row_rung

    rungs = [prefill_row_rung(k, slots) for k in range(1, slots + 1)]
    assert rungs[0] == 1
    for k, n in enumerate(rungs, start=1):
        assert k <= n <= slots, (k, n)
        if k > 1:  # a power of two from 8 up, or the cap
            assert n == slots or (n >= 8 and n & (n - 1) == 0), (k, n)
            assert n == slots or n < 2 * k or n == 8, (k, n)  # the smallest
    assert rungs == sorted(rungs) and rungs[-1] == slots
    assert len(set(rungs)) <= max(math.ceil(math.log2(slots / 8)), 0) + 2
    want = {4: {1, 4}, 8: {1, 8}, 16: {1, 8, 16}, 24: {1, 8, 16, 24},
            64: {1, 8, 16, 32, 64}}[slots]
    assert set(rungs) == want


_BENCH = Path(__file__).resolve().parents[1] / "benchmarks"


def _family_model(name: str, max_len: int = 256):
    """(ModelConfig in float32, the family's seeded weights as float32) of a
    benchmark family's tiny rehearsal configuration, built as
    tests/test_latent_moe.py and tests/test_looped.py build theirs."""
    import dataclasses
    import json
    import sys

    import jax.numpy as jnp

    for d in (_BENCH.parent, _BENCH):
        if str(d) not in sys.path:
            sys.path.insert(0, str(d))
    import families

    config = json.loads((_BENCH / "configs" / name).read_text())
    family = families.load(config, name)
    m = family.sizes(config)
    cfg = dataclasses.replace(
        family.model_config("tiny", m, {"max_seq_len": max_len}),
        dtype="float32")
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          family.make_params(m, 11))
    return cfg, params


# case -> (model and weights, engine overrides, the program of its waves)
_LADDER_CASES = {
    "dense": (lambda: (tiny_model(), None), {}, "prefill"),
    "dense_int8_kv": (lambda: (tiny_model(), None),
                      {"kv_quantize": "int8", "page_size": 32}, "prefill"),
    "dense_chunked": (lambda: (tiny_model(), None),
                      {"prefill_chunk": 64}, "prefill_chunk"),
    "dense_tp2": (lambda: (tiny_model(), None), {"tp": 2}, "prefill"),
    "latent": (lambda: _family_model("tiny-mla-moe-rehearsal.json"), {},
               "prefill"),
    "looped": (lambda: _family_model("tiny-looped-rehearsal.json"), {},
               "prefill"),
}


def _spied_engine(monkeypatch, mc, params, over: dict, slots: int):
    """(engine, scheduler, served) of a tiny continuous engine whose
    ``served`` dict maps each finished request id to its token ids."""
    import inspect

    from lmrs_tpu.config import MeshConfig

    monkeypatch.setenv("LMRS_WATCHDOG", "0")
    kw = dict(backend="jax", scheduler="continuous", max_tokens=6,
              max_batch_slots=slots, page_size=16, num_pages=1,
              decode_block=3, prefix_cache=False, host_kv=False,
              retry_attempts=1, seed=0)
    kw.update(over)
    tp = kw.pop("tp", 1)
    eng = JaxEngine(EngineConfig(**kw), mc,
                    MeshConfig(dp=1, tp=tp) if tp > 1 else None,
                    params=params)
    sched = eng._scheduler
    served: dict[int, list[int]] = {}
    finish = sched._finish_slot
    names = list(inspect.signature(finish).parameters)

    def spy(*a, **k):
        bound = dict(zip(names, a), **k)
        served[bound["slots"][bound["b"]].req.request_id] = list(bound["gen"])
        return finish(*a, **k)

    monkeypatch.setattr(sched, "_finish_slot", spy)
    return eng, sched, served


def _pow2_plan(lens, slots, page_size, max_len):
    """The wave rule before PR 37 for a wave within one power of two: one
    dispatch, the row rung of the wave, the power-of-two bucket."""
    from lmrs_tpu.engine.scheduler import prefill_row_rung
    from lmrs_tpu.ops.paged_attention import pow2_bucket

    return [(len(lens), prefill_row_rung(len(lens), slots),
             min(pow2_bucket(lens[-1], 64), max_len))]


@pytest.mark.parametrize("case", sorted(_LADDER_CASES))
def test_a_narrow_prefill_wave_yields_the_tokens_of_the_full_width_one(
        monkeypatch, case):
    """Waves of 9, 16 and 5 prompts on 24 slots run the 16-, 16- and 8-row
    programs; at temperature 0 every request's token ids are those of the
    parent rule's 24-row dispatch of the same wave (pad rows were one token
    on the null page whose logits nobody read), on the fresh program, the
    windowed continuation, int8 pages, a tp mesh, a latent pool and a looped
    stack.  The row ladder alone: a wave is one dispatch on its power of
    two (the cut and the sequence rungs are the next tests')."""
    from lmrs_tpu.engine import scheduler as sched_mod

    make, over, program = _LADDER_CASES[case]
    mc, params = make()
    slots = 24
    monkeypatch.setattr(sched_mod, "prefill_wave_plan", _pow2_plan)
    eng, sched, served = _spied_engine(monkeypatch, mc, params, over, slots)

    def wave(k: int, start: int) -> list[GenerationRequest]:
        # over half the context each: no two share a packed bin, so every
        # prompt takes a row of the batched program, as a map prompt does
        return [GenerationRequest(
            prompt=f"speaker {start + i}: " + "the review covered budgets "
            * 5 + "and hiring " * (i % 4), request_id=start + i,
            temperature=0.0, max_new_tokens=6) for i in range(k)]

    def run_waves() -> tuple[dict, dict]:
        served.clear()
        an0 = sched.anatomy_snapshot()
        for k, start in ((9, 0), (16, 100), (5, 200)):
            out = eng.generate_batch(wave(k, start))
            assert all(r.error is None and r.completion_tokens > 0
                       for r in out)
        assert sched.audit() == []
        return dict(served), sched.anatomy_report(an0)["programs"][program]

    got, narrow = run_waves()
    monkeypatch.setattr(sched_mod, "prefill_row_rung",
                        lambda group, n: 1 if group <= 1 else n)
    want, full = run_waves()
    eng.shutdown()
    assert len(want) == 30 and got == want

    def rows_by_rung(rec: dict) -> dict[int, tuple[int, int, int]]:
        out: dict[int, tuple[int, int, int]] = {}
        for key, k in rec["keys"].items():
            n = int(key.split(":")[2])
            d, r, s = out.get(n, (0, 0, 0))
            out[n] = (d + k["dispatches"], r + k["rows"], s + k["row_slots"])
        return out

    by_rung = rows_by_rung(narrow)
    assert set(by_rung) == {8, 16}
    assert set(rows_by_rung(full)) == {slots}
    for n, (d, rows, row_slots) in by_rung.items():
        assert row_slots == d * n and rows < row_slots
    if program == "prefill":  # one dispatch a wave: 9, 16 of 16; 5 of 8
        assert by_rung == {16: (2, 25, 32), 8: (1, 5, 8)}
    assert narrow["rows"] == full["rows"]
    assert narrow["q_tokens"] == full["q_tokens"]
    assert narrow["q_slots"] < full["q_slots"]


# --------------------------------------- the prefill waves' sequence rungs


@pytest.mark.parametrize("page_size,max_len,cases", [
    # a 128-token page: the power of two at or below 1,024 (the step of an
    # eighth of the octave is under a page), rungs 128 apart in (1,024,
    # 2,048], 512 apart in (4,096, 8,192], the cap at the window
    (128, 2048, [(1, 64), (64, 64), (65, 128), (700, 1024), (1024, 1024),
                 (1025, 1152), (1152, 1152), (1153, 1280), (1719, 1792),
                 (1792, 1792), (1793, 1920), (1876, 1920), (1920, 1920),
                 (1921, 2048), (2048, 2048)]),
    (128, 8192, [(3069, 3072), (4097, 4608), (4322, 4608), (7196, 7680),
                 (7680, 7680), (7681, 8192), (8192, 8192)]),
    (128, 1900, [(1793, 1900), (1876, 1900), (1000, 1024)]),
    # a 32-token page: rungs from 512 up, 32 apart in (256, 512]
    (32, 2048, [(200, 256), (256, 256), (257, 288), (300, 320), (511, 512),
                (1719, 1792), (1876, 1920)]),
])
def test_prefill_seq_rung_table(page_size, max_len, cases):
    """The operand positions of a fresh prefill wave: the smallest multiple
    of an eighth of the octave below the power-of-two bucket that holds the
    longest prompt, where that step is whole pages; the power of two where
    it is not; never above the window."""
    from lmrs_tpu.engine.scheduler import prefill_seq_rung

    for n, want in cases:
        assert prefill_seq_rung(n, page_size, max_len) == want, n
    for n in range(1, max_len + 1, 7):  # every rung is whole pages or pow2
        r = prefill_seq_rung(n, page_size, max_len)
        assert n <= r <= max_len and (r % page_size == 0 or r == max_len
                                      or r & (r - 1) == 0), (n, r)


# lengths ascending -> the plan: [(prompts, rows, positions)]
_LONG, _SHORT = 1876, 1750


@pytest.mark.parametrize("lens,slots,want", [
    # 24 slots, a map wave with its job's long last chunk: the body keeps
    # the 24-row program on its rung, the long chunk runs the lone 2,048
    # program a reduce step compiles; without it the same body program
    ([_SHORT] * 23 + [_LONG], 24, [(23, 24, 1792), (1, 1, 2048)]),
    ([_SHORT] * 24, 24, [(24, 24, 1792)]),
    # 16 slots: the 16-row body and the lone program
    ([_SHORT] * 15 + [_LONG], 16, [(15, 16, 1792), (1, 1, 2048)]),
    # an 8-row wave saves exactly the lone dispatch: taken
    ([_SHORT] * 7 + [_LONG], 16, [(7, 8, 1792), (1, 1, 2048)]),
    # nine prompts on 24 slots: eight and one (9 rows, not 16)
    ([_SHORT] * 8 + [_LONG], 24, [(8, 8, 1792), (1, 1, 2048)]),
    # 4 slots: four rows an eighth of an octave below save less than a
    # lone dispatch, so the wave keeps its power of two, long chunk or not
    ([_SHORT] * 3 + [_LONG], 4, [(4, 4, 2048)]),
    ([_SHORT] * 4, 4, [(4, 4, 2048)]),
    ([7296] * 4, 4, [(4, 4, 8192)]),
    # four rows a quarter of an octave below save the lone dispatch
    ([1500] * 3 + [1600], 4, [(3, 4, 1536), (1, 1, 2048)]),
    # the median prompt is long: the body's rung holds every prompt
    ([_SHORT] * 10 + [_LONG] * 14, 24, [(24, 24, 1920)]),
    # half the wave long: a cut would pay more than the power of two
    ([_SHORT] * 12 + [_LONG] * 12, 24, [(24, 24, 2048)]),
    # two prompts: each alone on the lone program (2 rows, not 8)
    ([_SHORT, _LONG], 24, [(1, 1, 2048), (1, 1, 2048)]),
    # at or below 1,024 on a 128-token page the rung is the power of two
    ([700] * 8, 8, [(8, 8, 1024)]),
    # a lone prompt keeps its power of two
    ([_LONG], 24, [(1, 1, 2048)]),
    ([1308], 4, [(1, 1, 2048)]),
])
def test_prefill_wave_plan_puts_the_body_on_its_rung(lens, slots, want):
    from lmrs_tpu.engine.scheduler import prefill_wave_plan

    assert prefill_wave_plan(lens, slots, 128, 2048 if lens[-1] <= 2048
                             else 8192) == want


@pytest.mark.parametrize("slots", [4, 8, 16, 24])
def test_prefill_wave_plan_never_pays_more_than_the_power_of_two(slots):
    """Seeded groups of one power of two (1,025 to 2,048 tokens, a body
    and a few long prompts): at most two dispatches that carry every
    prompt in order, each on a rung that holds its longest prompt, and
    never more positions than the group's one power-of-two dispatch."""
    import random

    from lmrs_tpu.engine.scheduler import prefill_row_rung, prefill_wave_plan

    rng = random.Random(slots)
    for _ in range(300):
        n = rng.randint(1, slots)
        body = rng.randint(1025, 1990)
        lens = sorted(min(body + rng.choice([0, 0, 0, rng.randint(0, 300)]),
                          2048) for _ in range(n))
        plan = prefill_wave_plan(lens, slots, 128, 2048)
        assert 1 <= len(plan) <= 2 and sum(m for m, _, _ in plan) == n
        lo = 0
        for m, rows, seq in plan:
            assert rows == prefill_row_rung(m, slots)
            assert lens[lo + m - 1] <= seq <= 2048 and seq % 128 == 0
            assert m > 1 or seq == 2048  # a lone prompt: its power of two
            lo += m
        assert (sum(r * s for _, r, s in plan)
                <= prefill_row_rung(n, slots) * 2048), lens


# case -> (model and weights, engine overrides): every family whose fresh
# prefill is its own (dense on int8 pages, latent pool, looped stack on int8
# pages, window and full layers over a two-kind cache), a 1,024 window on
# 32-token pages (int8 pages need 32), where rungs lie 64 apart above 512
def _dense_1k():
    import dataclasses

    return dataclasses.replace(tiny_model(), max_seq_len=1024), None


_RUNG_CASES = {
    "dense_int8_kv": (_dense_1k, {"kv_quantize": "int8"}),
    "latent": (lambda: _family_model("tiny-mla-moe-rehearsal.json", 1024),
               {}),
    "looped_int8_kv": (lambda: _family_model("tiny-looped-rehearsal.json",
                                             1024), {"kv_quantize": "int8"}),
    "windowed": (lambda: _family_model("tiny-swa-moe-rehearsal.json", 1024),
                 {}),
}


def _map_wave(n_short: int, n_long: int, start: int, reps: int = 5,
              extra: int = 3):
    """A map wave: ``n_short`` prompts of one length, then ``n_long``
    longer ones (a job's long last chunk)."""
    return [GenerationRequest(
        prompt=f"speaker {i % 10}: " + "the review covered budgets " * reps
        + "and hiring " * (extra if i >= n_short else 0),
        request_id=start + i, temperature=0.0, max_new_tokens=6)
        for i in range(n_short + n_long)]


@pytest.mark.parametrize("case", sorted(_RUNG_CASES))
def test_a_prefill_wave_on_sequence_rungs_yields_the_tokens_of_the_pow2_one(
        monkeypatch, case):
    """Fifteen prompts of about 600 tokens and one of about 700 on 16
    slots: the body runs [16 x its rung] under the power of two and the
    long prompt the lone [1 x 1024] program; at temperature 0 every
    request's token ids are those of the one [16 x 1024] dispatch of the
    rule before (positions past a row's length are masked and written to
    the null page)."""
    from lmrs_tpu.engine import scheduler as sched_mod

    make, over = _RUNG_CASES[case]
    mc, params = make()
    eng, sched, served = _spied_engine(monkeypatch, mc, params,
                                       {"page_size": 32, **over}, 16)
    ps = sched.cache.page_size

    def run_wave() -> tuple[dict, dict, dict, list[int]]:
        served.clear()
        an0, m0 = sched.anatomy_snapshot(), dict(sched.metrics)
        out = eng.generate_batch(_map_wave(15, 1, 0, reps=22, extra=8))
        assert all(r.error is None and r.completion_tokens > 0 for r in out)
        assert sched.audit() == []
        m1 = sched.metrics
        return (dict(served), sched.anatomy_report(an0)["programs"]["prefill"],
                {k: m1[k] - m0[k] for k in ("prefill_token_slots",
                                            "prefill_seq_rung_dispatches")},
                sorted(r.prompt_tokens for r in out))

    got, rung, d_rung, lens = run_wave()
    monkeypatch.setattr(sched_mod, "prefill_wave_plan", _pow2_plan)
    want, pow2, d_pow2, _ = run_wave()
    eng.shutdown()
    assert len(want) == 16 and got == want

    a = sched_mod.prefill_seq_rung(lens[7], ps, mc.max_seq_len)
    assert lens[14] <= a < lens[15] < 1024 == mc.max_seq_len, (a, lens)
    keys = {(int(k.split(":")[2]), int(k.split(":")[3])): v["dispatches"]
            for k, v in rung["keys"].items()}
    assert keys == {(16, a): 1, (1, 1024): 1}
    assert set(pow2["keys"]) == {"prefill:True:16:1024:32:False"}
    assert rung["q_tokens"] == pow2["q_tokens"] == sum(lens)
    assert d_rung["prefill_token_slots"] == 16 * a + 1024
    assert d_rung["prefill_token_slots"] < d_pow2["prefill_token_slots"]
    assert d_rung["prefill_seq_rung_dispatches"] == 1
    assert d_pow2["prefill_seq_rung_dispatches"] == 0
    assert rung["page_writes"] == (16 * a + 1024) // ps


def test_a_wave_with_a_long_chunk_compiles_nothing_new(monkeypatch, caplog):
    """After a wave of eight short prompts and a lone long one, a wave of
    seven short and one long runs the same two programs, the body on
    [8 x 160] and the long prompt on [1 x 256], and compiles nothing: not
    a prefill program, and not the first tokens' scatter into the decode
    block, which is padded to the dispatch's rows so that its shape follows
    the row rung and not the count of prompts."""
    import logging

    eng, sched, _ = _spied_engine(monkeypatch, tiny_model(), None, {}, 8)

    def wave(n_short: int, n_long: int, start: int) -> None:
        out = eng.generate_batch(_map_wave(n_short, n_long, start))
        assert all(r.error is None and r.completion_tokens > 0 for r in out)

    wave(8, 0, 0)
    wave(0, 1, 100)
    keys = set(sched.anatomy_report()["programs"]["prefill"]["keys"])
    assert keys == {"prefill:True:8:160:16:False",
                    "prefill:True:1:256:16:False"}
    m0 = dict(sched.metrics)
    with caplog.at_level(logging.WARNING), jax.log_compiles(True):
        caplog.clear()
        wave(7, 1, 200)
    compiled = [r.getMessage() for r in caplog.records
                if "Finished XLA compilation" in r.getMessage()]
    assert set(sched.anatomy_report()["programs"]["prefill"]["keys"]) == keys
    assert compiled == []
    assert sched.metrics["prefill_dispatches"] - m0["prefill_dispatches"] == 2
    assert sched.audit() == []
    eng.shutdown()
