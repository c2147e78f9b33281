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


def _family_model(name: str):
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
        family.model_config("tiny", m, {"max_seq_len": 256}), dtype="float32")
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


@pytest.mark.parametrize("case", sorted(_LADDER_CASES))
def test_a_narrow_prefill_wave_yields_the_tokens_of_the_full_width_one(
        monkeypatch, case):
    """Waves of 9, 16 and 5 prompts on 24 slots run the 16-, 16- and 8-row
    programs; at temperature 0 every request's token ids are those of the
    parent rule's 24-row dispatch of the same wave (pad rows were one token
    on the null page whose logits nobody read), on the fresh program, the
    windowed continuation, int8 pages, a tp mesh, a latent pool and a looped
    stack."""
    import inspect

    from lmrs_tpu.config import MeshConfig
    from lmrs_tpu.engine import scheduler as sched_mod

    monkeypatch.setenv("LMRS_WATCHDOG", "0")
    make, over, program = _LADDER_CASES[case]
    mc, params = make()
    slots = 24
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
