"""Telemetry subsystem tests: histogram bucket math + percentile parity,
trace-event schema validation, Prometheus exposition golden output, label
propagation, and the scheduler's per-request span chain (including the
preempt and cancel paths)."""

import json
import logging

import numpy as np
import pytest

from lmrs_tpu.obs import (
    TID_SCHED,
    Histogram,
    MetricsRegistry,
    Tracer,
    add_label_to_exposition,
    disable_tracing,
    enable_tracing,
    log_buckets,
    merge_expositions,
    req_tid,
    validate_trace_events,
    validate_trace_file,
)


@pytest.fixture
def tracer():
    """Process tracer, cleared and torn down so span state never leaks
    between tests (tracing is process-global by design)."""
    tr = enable_tracing()
    tr.clear()
    yield tr
    disable_tracing()


# ------------------------------------------------------------------ metrics


def test_histogram_bucket_math():
    h = Histogram("h", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 3.0, 100.0):
        h.observe(v)
    # le semantics: 1.0 lands in the le=1 bucket, 100 overflows to +Inf
    assert h.counts == [2, 1, 1, 1]
    assert h.cumulative_counts() == [2, 3, 4, 5]
    assert h.count == 5
    assert h.sum == pytest.approx(106.0)
    h.reset()
    assert h.count == 0 and h.counts == [0, 0, 0, 0] and not h.samples


def test_histogram_percentile_parity_with_old_latency_pct():
    """percentile_report must reproduce the scheduler's former _latency_pct
    exactly: np.percentile p50/p90/p99 over the samples, seconds -> ms,
    0.1 ms precision, None when empty."""
    h = Histogram("h", buckets=(0.1, 1.0))
    assert h.percentile_report() is None
    rng = np.random.default_rng(7)
    samples = rng.gamma(2.0, 0.05, size=500).tolist()
    for v in samples:
        h.observe(v)
    p50, p90, p99 = np.percentile(np.asarray(samples), [50, 90, 99])
    expected = {"p50": round(float(p50) * 1e3, 1),
                "p90": round(float(p90) * 1e3, 1),
                "p99": round(float(p99) * 1e3, 1),
                "n": len(samples)}
    assert h.percentile_report() == expected


def test_histogram_sample_cap_drops_oldest_half():
    import lmrs_tpu.obs.metrics as om

    h = Histogram("h", buckets=(1.0,))
    old_cap = om._SAMPLE_CAP
    om._SAMPLE_CAP = 100
    try:
        for i in range(101):
            h.observe(float(i))
    finally:
        om._SAMPLE_CAP = old_cap
    # oldest half dropped, newest retained; bucket counts keep everything
    assert len(h.samples) == 51
    assert h.samples[0] == 50.0
    assert h.count == 101


def test_log_buckets_monotonic():
    b = log_buckets(0.001, 10.0)
    assert list(b) == sorted(set(b))
    assert b[0] == pytest.approx(0.001) and b[-1] == pytest.approx(10.0)
    with pytest.raises(ValueError):
        log_buckets(0.0, 1.0)


def test_registry_get_or_create_and_type_conflict():
    reg = MetricsRegistry()
    c1 = reg.counter("lmrs_a_total")
    assert reg.counter("lmrs_a_total") is c1
    with pytest.raises(ValueError):
        reg.gauge("lmrs_a_total")
    with pytest.raises(ValueError):
        reg.counter("lmrs_a_total").inc(-1)


def test_prometheus_exposition_golden():
    reg = MetricsRegistry()
    reg.counter("lmrs_reqs_total", "requests served").inc(3)
    reg.gauge("lmrs_slots", "active slots").set(2)
    h = reg.histogram("lmrs_ttft_seconds", buckets=(0.1, 1.0),
                      help="time to first token")
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    assert reg.render_prometheus() == (
        "# HELP lmrs_reqs_total requests served\n"
        "# TYPE lmrs_reqs_total counter\n"
        "lmrs_reqs_total 3\n"
        "# HELP lmrs_slots active slots\n"
        "# TYPE lmrs_slots gauge\n"
        "lmrs_slots 2\n"
        "# HELP lmrs_ttft_seconds time to first token\n"
        "# TYPE lmrs_ttft_seconds histogram\n"
        'lmrs_ttft_seconds_bucket{le="0.1"} 1\n'
        'lmrs_ttft_seconds_bucket{le="1"} 2\n'
        'lmrs_ttft_seconds_bucket{le="+Inf"} 3\n'
        "lmrs_ttft_seconds_sum 5.55\n"
        "lmrs_ttft_seconds_count 3\n"
    )


def _assert_valid_exposition(text: str) -> None:
    """Minimal format validator: single TYPE per metric, contiguous metric
    groups, cumulative bucket counts ending at _count."""
    typed: set[str] = set()
    for line in text.splitlines():
        s = line.strip()
        if not s:
            continue
        if s.startswith("# TYPE"):
            name = s.split()[2]
            assert name not in typed, f"duplicate TYPE for {name}"
            typed.add(name)
        elif not s.startswith("#"):
            assert " " in s, s


def test_label_propagation_and_merge():
    reg = MetricsRegistry()
    reg.counter("lmrs_reqs_total", "requests").inc(1)
    h = reg.histogram("lmrs_ttft_seconds", buckets=(1.0,), help="ttft")
    h.observe(0.5)
    pages = [add_label_to_exposition(reg.render_prometheus(), "host", hn)
             for hn in ("a:8000", "b:8000")]
    assert 'lmrs_reqs_total{host="a:8000"} 1' in pages[0]
    assert 'lmrs_ttft_seconds_bucket{host="b:8000",le="1"} 1' in pages[1]
    merged = merge_expositions(pages)
    _assert_valid_exposition(merged)
    # both hosts' series survive under one header, grouped contiguously
    assert merged.count("# TYPE lmrs_ttft_seconds histogram") == 1
    assert 'lmrs_ttft_seconds_count{host="a:8000"}' in merged
    assert 'lmrs_ttft_seconds_count{host="b:8000"}' in merged
    lines = merged.splitlines()
    fam = [i for i, ln in enumerate(lines) if ln.startswith("lmrs_ttft_")]
    assert fam == list(range(fam[0], fam[0] + len(fam))), "group split"


# -------------------------------------------------------------------- trace


def test_tracer_ring_bound():
    tr = Tracer(capacity=8)
    for i in range(20):
        tr.instant(f"e{i}", ts=float(i))
    assert len(tr.events()) == 8
    assert tr.recorded == 20
    assert tr.events()[0]["name"] == "e12"  # oldest dropped first


def test_trace_export_schema(tmp_path, tracer):
    tracer.instant("enqueue", tid=req_tid(0))
    tracer.complete("prefill", 1.0, 2.0, tid=req_tid(0), args={"tokens": 4})
    path = tmp_path / "t.json"
    n = tracer.export(path)
    events = validate_trace_file(path)
    assert n == len(events)
    data = json.loads(path.read_text())
    assert "traceEvents" in data  # Perfetto's expected container
    names = {e["name"] for e in events}
    # metadata survives export regardless of ring state
    assert {"process_name", "thread_name", "enqueue", "prefill"} <= names


def test_trace_validation_rejects_bad_events():
    with pytest.raises(ValueError):
        validate_trace_events([])
    with pytest.raises(ValueError):
        validate_trace_events([{"ph": "i", "ts": 0, "pid": 1, "tid": 1}])
    with pytest.raises(ValueError):
        validate_trace_events([{"name": "x", "ph": "??", "ts": 0,
                                "pid": 1, "tid": 1}])
    with pytest.raises(ValueError):  # X span without dur
        validate_trace_events([{"name": "x", "ph": "X", "ts": 0,
                                "pid": 1, "tid": 1}])


def _instant(name, args, ts=1.0):
    return {"name": name, "ph": "i", "s": "t", "ts": ts, "pid": 1,
            "tid": 10, "args": args}


def test_trace_validation_handoff_job_instant_contracts():
    """The handoff/job lifecycle instants carry contract args their
    consumers (stitcher skew anchors, jobs dashboard) parse — a dropped
    key must fail the gate, not silently break a reader."""
    # conforming instants pass
    validate_trace_events([
        _instant("handoff_export", {"pages": 4, "kv_len": 128}),
        _instant("handoff_import", {"pages": 4, "kv_len": 128, "slot": 0}),
        _instant("handoff_release", {"pages": 4, "orphaned": False}),
        _instant("job_submit", {"job": "job-abc"}),
        _instant("job_recover", {"job": "job-abc"}),
        _instant("job_resume", {"job": "job-abc", "resumed_chunks": 3}),
        _instant("job_done", {"job": "job-abc", "status": "done"}),
    ])
    # each required key missing is a schema violation
    for bad in (
        _instant("handoff_export", {"pages": 4}),            # no kv_len
        _instant("handoff_import", {"kv_len": 128}),         # no pages
        _instant("handoff_release", {"pages": 4}),           # no orphaned
        _instant("job_done", {"job": "job-abc"}),            # no status
        _instant("job_resume", {"job": "job-abc"}),          # no count
        _instant("job_submit", {}),                          # no job
    ):
        with pytest.raises(ValueError):
            validate_trace_events([bad])


def test_trace_validation_perf_attribution_args_numeric():
    """Perf-attribution args (flops_g/hbm_gb/mfu/...) must be finite
    non-negative numbers wherever they appear — a NaN or negative value
    poisons every aggregation built on the trace."""
    ok = {"name": "prefill_dispatch", "ph": "i", "s": "t", "ts": 1.0,
          "pid": 1, "tid": 0, "args": {"tokens": 512, "flops_g": 1.25}}
    validate_trace_events([ok])
    for key, val in (("flops_g", -1.0), ("flops_g", float("nan")),
                     ("hbm_gb", float("inf")), ("tokens", -5),
                     ("mfu", True), ("hbm_util", "0.5")):
        bad = {**ok, "args": {**ok["args"], key: val}}
        with pytest.raises(ValueError):
            validate_trace_events([bad])


def test_track_for_int_compat_and_trace_allocation(tracer):
    """int keys keep the legacy REQ_TID_BASE mapping; string (trace-id)
    keys allocate stable tids from a disjoint base and name their track
    trace:<id> — the metadata the cross-host stitcher keys on."""
    from lmrs_tpu.obs import TRACE_TRACK_PREFIX
    from lmrs_tpu.obs.trace import TRACE_TID_BASE

    assert tracer.track_for(7) == req_tid(7)
    t1 = tracer.track_for("trace-a")
    assert t1 == tracer.track_for("trace-a")  # stable
    t2 = tracer.track_for("trace-b")
    assert t1 != t2 and t1 >= TRACE_TID_BASE
    names = {(e["pid"], e["tid"]): e["args"]["name"]
             for e in tracer.payload()["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert names[(1, t1)] == f"{TRACE_TRACK_PREFIX}trace-a"
    assert names[(1, t2)] == f"{TRACE_TRACK_PREFIX}trace-b"


def test_stitch_traces_aligns_skewed_clocks(tracer):
    """Two synthetic host pages whose clocks disagree by 10 s (the decode
    host's import timestamps PRECEDE the export on the merged clock):
    the stitcher's handoff-pair skew anchor shifts the decode host
    forward so the stitched chain reads causally, and same-clock hosts
    are left untouched."""
    from lmrs_tpu.obs import stitch_traces, stitched_chains

    def host_page(events):
        tr = Tracer()
        tid = tr.track_for("tr-1")
        for name, ts, args in events:
            tr.instant(name, ts=ts, tid=tid, args=args)
        return tr.payload()

    t0 = 1000.0
    skew = -10.0  # decode host clock 10 s behind
    prefill = host_page([
        ("enqueue", t0, {"prompt_tokens": 8}),
        ("handoff_export", t0 + 1.0, {"pages": 2, "kv_len": 8}),
        ("handoff_release", t0 + 3.0, {"pages": 2, "orphaned": False}),
    ])
    decode = host_page([
        ("handoff_import", t0 + 2.0 + skew, {"pages": 2, "kv_len": 8}),
        ("finish", t0 + 4.0 + skew, {"reason": "stop",
                                     "completion_tokens": 4}),
    ])
    doc = stitch_traces([("pre:8000", prefill), ("dec:8000", decode)])
    validate_trace_events(doc["traceEvents"])
    off = doc["stitch"]["offsets_ms"]
    assert off["pre:8000"] == 0.0
    assert off["dec:8000"] > 0  # shifted forward to restore causality
    chains = stitched_chains(doc["traceEvents"])
    assert list(chains) == ["tr-1"]
    names = [e["name"] for e in chains["tr-1"]]
    assert names.index("handoff_export") < names.index("handoff_import")
    assert names[0] == "enqueue" and names[-1] == "finish"
    # hosts already on one clock are left untouched (0 in the interval)
    doc2 = stitch_traces([
        ("pre:8000", prefill),
        ("dec:8000", host_page([
            ("handoff_import", t0 + 2.0, {"pages": 2, "kv_len": 8}),
            ("finish", t0 + 4.0, {"reason": "stop"})]))])
    assert doc2["stitch"]["offsets_ms"]["dec:8000"] == 0.0


def test_timestamps_filter(tracer):
    tracer.complete("decode_block", 1.0, 1.5, tid=TID_SCHED)
    tracer.instant("decode_block", ts=1.0, tid=req_tid(3))
    tracer.complete("decode_block", 2.0, 2.5, tid=TID_SCHED)
    assert tracer.timestamps("decode_block", tid=TID_SCHED) == [1.0, 2.0]
    assert len(tracer.timestamps("decode_block")) == 3


# ------------------------------------------------ span(): one span, two sinks


class _StandInAnnotation:
    """What jax.profiler.TraceAnnotation is to span(): built with the name
    and the args, entered and left; it writes only while a session runs."""

    session = False
    log: list = []

    def __init__(self, name, **args):
        self.name, self.args = name, args

    def __enter__(self):
        if _StandInAnnotation.session:
            _StandInAnnotation.log.append(("enter", self.name, self.args))
        return self

    def __exit__(self, *exc):
        if _StandInAnnotation.session:
            _StandInAnnotation.log.append(("exit", self.name))
        return False


@pytest.fixture
def stand_in(monkeypatch):
    from lmrs_tpu.obs import trace

    monkeypatch.setattr(trace, "_annotation_cls", _StandInAnnotation)
    monkeypatch.setattr(_StandInAnnotation, "session", False)
    monkeypatch.setattr(_StandInAnnotation, "log", [])
    return _StandInAnnotation


@pytest.mark.parametrize("session,ring", [(True, True), (True, False),
                                          (False, True), (False, False)])
def test_span_records_name_and_args_in_the_sinks_that_are_on(
        stand_in, session, ring):
    from lmrs_tpu.obs import span

    disable_tracing()
    tr = enable_tracing() if ring else None
    if tr:
        tr.clear()
    stand_in.session = session
    try:
        with span("sched.dispatch", program="rpa", id=7, cold=False):
            with span("inner"):
                pass
    finally:
        disable_tracing()
    want_args = {"program": "rpa", "id": 7, "cold": False}
    if session:
        assert stand_in.log == [
            ("enter", "sched.dispatch", want_args), ("enter", "inner", {}),
            ("exit", "inner"), ("exit", "sched.dispatch")]
    else:
        assert stand_in.log == []
    if ring:
        evs = {e["name"]: e for e in tr.events()}
        assert set(evs) == {"sched.dispatch", "inner"}
        assert evs["sched.dispatch"]["args"] == want_args
        assert evs["sched.dispatch"]["ph"] == "X"
        assert "args" not in evs["inner"]
        validate_trace_events(tr.events())
        outer, inner = evs["sched.dispatch"], evs["inner"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def test_span_without_annotation_stays_off_jax(stand_in, tracer):
    """StageTimer without profile=: the ring gets the stage, the profiler
    class is never built (a mock-backend run must not import JAX for it)."""
    from lmrs_tpu.obs import PID_PIPELINE
    from lmrs_tpu.utils.timing import StageTimer

    stand_in.session = True
    timer = StageTimer(profile=False)
    with timer.stage("map"):
        pass
    assert stand_in.log == []
    assert [(e["name"], e["pid"]) for e in tracer.events()] == [
        ("map", PID_PIPELINE)]
    assert timer.spans["map"] >= 0.0
    with StageTimer(profile=True).stage("reduce"):
        pass
    assert [x[:2] for x in stand_in.log] == [("enter", "reduce"),
                                             ("exit", "reduce")]


def test_scheduler_segments_reach_the_profiler_sink(stand_in):
    """With a session on and the Chrome tracer off, a run writes sched.run
    (with unix_ns), every segment under its sched.* name, sched.dispatch
    with the record's arguments, and a sched.fetch that retires the
    dispatch ids issued before it."""
    from lmrs_tpu.config import EngineConfig
    from lmrs_tpu.engine.api import GenerationRequest
    from lmrs_tpu.engine.jax_engine import JaxEngine

    disable_tracing()
    eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                 max_tokens=8, max_batch_slots=2, seed=0),
                    _tiny_model())
    stand_in.session = True
    try:
        out = eng.generate_batch([GenerationRequest(
            prompt="profiler sink probe", request_id=0, temperature=0.0,
            max_new_tokens=5)])
    finally:
        stand_in.session = False
        eng.shutdown()
    assert out[0].error is None
    enters = [(x[1], x[2]) for x in stand_in.log if x[0] == "enter"]
    names = [n for n, _ in enters]
    assert names[0] == "sched.run"
    assert abs(enters[0][1]["unix_ns"] * 1e-9 - __import__("time").time()) < 600
    assert {"sched.admit", "sched.plan", "sched.dispatch", "sched.fetch",
            "sched.finish", "sched.io"} <= set(names)
    disp = [a for n, a in enters if n == "sched.dispatch"]
    assert set(disp[0]) == {"program", "key", "id", "rows", "q_tokens",
                            "q_slots", "cold"}
    assert [a["id"] for a in disp] == list(range(1, len(disp) + 1))
    assert disp[0]["program"] == "prefill" and disp[0]["cold"] is True
    retired = [int(i) for n, a in enters if n == "sched.fetch"
               for i in a["retires"].split("+") if i]
    assert retired == [a["id"] for a in disp]  # each once, in order
    # spans nest: every enter has its exit, innermost first
    depth = 0
    for kind, *_ in stand_in.log:
        depth += 1 if kind == "enter" else -1
        assert depth >= 0
    assert depth == 0


# ------------------------------------------------- scheduler span chains


def _tiny_model():
    from lmrs_tpu.config import ModelConfig

    return ModelConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                       n_kv_heads=2, hidden_dim=128, max_seq_len=96,
                       dtype="float32")


def _chain(events: list[dict]) -> list[str]:
    return [e["name"] for e in events]


def test_scheduler_emits_complete_span_chain(tracer):
    """Every admitted request must emit the full lifecycle chain, in
    timestamp order, ending in finish."""
    from lmrs_tpu.config import EngineConfig
    from lmrs_tpu.engine.api import GenerationRequest
    from lmrs_tpu.engine.jax_engine import JaxEngine

    eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                 max_tokens=8, max_batch_slots=2, seed=0),
                    _tiny_model())
    n = 4
    reqs = [GenerationRequest(prompt=f"chain probe {i} " * (i + 1),
                              request_id=i, temperature=0.5,
                              max_new_tokens=6) for i in range(n)]
    out = eng.generate_batch(reqs)
    assert all(r.error is None for r in out)
    spans = tracer.spans_by_tid()
    for rid in range(n):
        evs = spans.get(req_tid(rid), [])
        names = _chain(evs)
        for required in ("enqueue", "admit", "prefill", "first_token",
                         "finish"):
            assert required in names, f"rid {rid}: {names}"
        # chain ordering: lifecycle milestones are monotonically timestamped
        order = [names.index(x) for x in
                 ("enqueue", "admit", "first_token", "finish")]
        assert order == sorted(order), names
        ts = [e["ts"] for e in evs]
        assert ts == sorted(ts)
    # the scheduler track carries batch-level dispatch spans
    sched_names = _chain(spans.get(TID_SCHED, []))
    assert "decode_block" in sched_names and "prefill_dispatch" in sched_names
    eng.shutdown()


def test_scheduler_span_chain_preempt_path(tracer):
    """A preempted request's track must show preempt and a SECOND admit
    (the continuation), still ending in finish."""
    from lmrs_tpu.config import EngineConfig
    from lmrs_tpu.engine.api import GenerationRequest
    from lmrs_tpu.engine.jax_engine import JaxEngine

    eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                 max_tokens=40, max_batch_slots=4, seed=0,
                                 page_size=16, num_pages=10, decode_block=4),
                    _tiny_model())
    reqs = [GenerationRequest(prompt=f"pressure probe {i} " * 3,
                              request_id=i, temperature=0.0,
                              max_new_tokens=40) for i in range(4)]
    out = eng.generate_batch(reqs)
    assert all(r.error is None for r in out)
    assert eng._scheduler.metrics["preemptions"] > 0
    spans = tracer.spans_by_tid()
    preempted = [rid for rid in range(4)
                 if "preempt" in _chain(spans[req_tid(rid)])]
    assert preempted, "no request track recorded the preemption"
    for rid in preempted:
        names = _chain(spans[req_tid(rid)])
        assert names.count("admit") >= 2, names  # continuation re-admitted
        assert names[-1] == "finish", names
    # non-preempted requests still finish their plain chains
    for rid in range(4):
        assert "finish" in _chain(spans[req_tid(rid)])
    eng.shutdown()


def test_scheduler_span_chain_cancel_paths(tracer):
    """Both cancel paths emit a terminal cancel event: a live slot swept at
    a block boundary, and a queued request that never prefills."""
    from lmrs_tpu.config import EngineConfig
    from lmrs_tpu.engine.api import GenerationRequest
    from lmrs_tpu.engine.jax_engine import JaxEngine

    eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                 max_tokens=32, max_batch_slots=1, seed=0,
                                 decode_block=4), _tiny_model())
    reqs = [GenerationRequest(prompt="short", request_id=0, temperature=0.5,
                              max_new_tokens=2),
            GenerationRequest(prompt="long cancelled " * 4, request_id=1,
                              temperature=0.5, max_new_tokens=32),
            GenerationRequest(prompt="queued cancelled", request_id=2,
                              temperature=0.5, max_new_tokens=32)]

    def on_result(res, submit):
        if res.request_id == 0:  # rid 1 is decoding, rid 2 still queued
            eng.cancel(1)
            eng.cancel(2)

    out = eng.generate_batch(reqs, on_result=on_result)
    by_id = {r.request_id: r for r in out}
    assert by_id[1].finish_reason == "cancelled"
    assert by_id[2].finish_reason == "cancelled"
    spans = tracer.spans_by_tid()
    # live-slot path: full chain up to cancel
    names1 = _chain(spans[req_tid(1)])
    assert "admit" in names1 and names1[-1] == "cancel", names1
    # queued path: enqueued but never admitted
    names2 = _chain(spans[req_tid(2)])
    assert names2[0] == "enqueue" and names2[-1] == "cancel", names2
    assert "admit" not in names2, names2
    eng.shutdown()


def test_metrics_report_superset_of_pre_pr_keys():
    """metrics_report() keys must remain a superset of the pre-registry
    report (bench windowing and the CLI banner read these)."""
    from lmrs_tpu.config import EngineConfig
    from lmrs_tpu.engine.api import GenerationRequest
    from lmrs_tpu.engine.jax_engine import JaxEngine

    eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                 max_tokens=6, max_batch_slots=2, seed=0),
                    _tiny_model())
    eng.generate_batch([GenerationRequest(prompt="superset probe",
                                          request_id=0, max_new_tokens=4)])
    report = eng.engine_metrics()
    pre_pr = {"prefill_tokens", "decode_tokens", "prefill_tokens_per_sec",
              "decode_tokens_per_sec", "mean_decode_occupancy",
              "peak_kv_page_utilization", "scheduler_seconds",
              "blocked_seconds", "host_seconds", "preemptions", "stalls",
              "cancelled", "peak_active_slots", "ttft_ms",
              "decode_block_gap_ms", "prefix_cache"}
    assert pre_pr <= set(report), pre_pr - set(report)
    # raw snapshot keeps the old dict's keys for windowed deltas
    raw = eng._scheduler.metrics
    pre_pr_raw = {"prefill_tokens", "decode_tokens", "decode_dispatches",
                  "occupancy_sum", "peak_pages_in_use", "run_seconds",
                  "spec_accepted_tokens", "preemptions", "stalls",
                  "peak_active_slots", "cancelled", "blocked_seconds",
                  "prefix_queries", "prefix_hits", "prefix_tokens_reused"}
    assert pre_pr_raw <= set(raw)
    # Prometheus view exposes the ttft histogram the ISSUE names
    text = eng._scheduler.registry.render_prometheus()
    assert "lmrs_ttft_seconds_bucket" in text
    _assert_valid_exposition(text)
    eng.shutdown()


def test_perf_attribution_surface():
    """The live-attribution block rides metrics_report() and the
    Prometheus page (histograms + _last gauges + model-work counters);
    after real dispatches the model-work counters are nonzero and the
    step-gap histogram sampled (CPU run: ratios may be empty — compiling
    shapes and the garbage guard legitimately skip samples)."""
    from lmrs_tpu.config import EngineConfig
    from lmrs_tpu.engine.api import GenerationRequest
    from lmrs_tpu.engine.jax_engine import JaxEngine

    eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                 max_tokens=16, max_batch_slots=2, seed=0,
                                 decode_block=4), _tiny_model())
    for rid in range(2):  # second wave runs warm shapes
        eng.generate_batch([GenerationRequest(
            prompt="attribution probe " * 3, request_id=rid,
            temperature=0.0, max_new_tokens=12)])
    pa = eng.engine_metrics()["perf_attribution"]
    assert {"prefill_mfu", "decode_hbm_util", "step_gap_ms",
            "model_prefill_gflops", "model_decode_gb",
            "rtt_ms"} <= set(pa)
    assert pa["model_prefill_gflops"] > 0
    assert pa["model_decode_gb"] > 0
    assert (pa["step_gap_ms"] or {}).get("n", 0) >= 1
    text = eng._scheduler.registry.render_prometheus()
    for name in ("lmrs_prefill_mfu_ratio_bucket",
                 "lmrs_decode_hbm_util_ratio_bucket",
                 "lmrs_step_gap_ms_bucket",
                 "lmrs_prefill_model_flops_total",
                 "lmrs_decode_model_bytes_total",
                 "lmrs_step_gap_ms_last"):
        assert name in text, name
    _assert_valid_exposition(text)
    # warmup isolation: the distributions reset, the counters persist
    eng._scheduler.reset_latency_stats()
    pa2 = eng.engine_metrics()["perf_attribution"]
    assert pa2["step_gap_ms"] is None
    assert pa2["model_prefill_gflops"] == pa["model_prefill_gflops"]
    eng.shutdown()


# ----------------------------------------------------------------- logging


def test_setup_logging_honors_repeated_calls(capsys):
    import io

    from lmrs_tpu.utils.logging import setup_logging

    root = logging.getLogger("lmrs")
    saved = root.handlers[:]
    root.handlers = []
    try:
        setup_logging(quiet=False)
        assert root.level == logging.INFO
        buf = io.StringIO()
        setup_logging(quiet=True, stream=buf)  # later call must win
        assert root.level == logging.WARNING
        logging.getLogger("lmrs.test").warning("to the new stream")
        assert "to the new stream" in buf.getvalue()
    finally:
        root.handlers = saved


def test_setup_logging_json_formatter(monkeypatch):
    import io

    from lmrs_tpu.utils.logging import setup_logging

    root = logging.getLogger("lmrs")
    saved = root.handlers[:]
    root.handlers = []
    try:
        monkeypatch.setenv("LMRS_LOG_JSON", "1")
        buf = io.StringIO()
        setup_logging(stream=buf)
        logging.getLogger("lmrs.test").info("structured hello")
        line = buf.getvalue().strip()
        entry = json.loads(line)
        assert entry["msg"] == "structured hello"
        assert entry["level"] == "INFO"
        assert entry["logger"] == "lmrs.test"
    finally:
        root.handlers = saved


# ------------------------------------------------------------------ serving


def test_metrics_content_negotiation_and_router_labels():
    """GET /metrics serves JSON by default and Prometheus text under
    Accept: text/plain; the router's fleet page carries host labels and
    marks unreachable backends."""
    import urllib.request

    from lmrs_tpu.engine.mock import MockEngine
    from lmrs_tpu.serving.router import RouterEngine
    from lmrs_tpu.serving.server import EngineHTTPServer

    servers = [EngineHTTPServer(MockEngine(), port=0) for _ in range(2)]
    for s in servers:
        s.start_background()
    urls = [f"{s.host}:{s.port}" for s in servers]
    try:
        base = f"http://{urls[0]}/metrics"
        body = urllib.request.urlopen(urllib.request.Request(base)).read()
        assert "engine" in json.loads(body)
        req = urllib.request.Request(base, headers={"Accept": "text/plain"})
        resp = urllib.request.urlopen(req)
        assert "text/plain" in resp.headers["Content-Type"]
        text = resp.read().decode()
        assert "lmrs_http_requests_total" in text
        _assert_valid_exposition(text)

        # router aggregation: live hosts labeled, dead host visible
        router = RouterEngine(urls + ["127.0.0.1:1"])
        page = router.prometheus_metrics()
        _assert_valid_exposition(page)
        for u in urls:
            assert f'lmrs_http_requests_total{{host="{u}"}}' in page
            assert f'lmrs_router_host_scrape_ok{{host="{u}"}} 1' in page
        # dead host: router still BELIEVES it healthy (no request traffic
        # has condemned it), but the scrape failure is alertable
        assert 'lmrs_router_host_up{host="127.0.0.1:1"} 1' in page
        assert 'lmrs_router_host_scrape_ok{host="127.0.0.1:1"} 0' in page
        agg = router.engine_metrics()
        dead = [row for row in agg["per_host"]
                if row["host"] == "127.0.0.1:1"][0]
        assert dead.get("metrics_unreachable") is True
        assert "metrics" not in dead
        live = [row for row in agg["per_host"] if row["host"] == urls[0]][0]
        assert "metrics_unreachable" not in live

        # a server FRONTING the router must merge the fleet page with its
        # own counters into one valid exposition (the backends emit the
        # same lmrs_http_* families — exactly one TYPE header may survive)
        front = EngineHTTPServer(router, port=0)
        front.start_background()
        try:
            freq = urllib.request.Request(
                f"http://{front.host}:{front.port}/metrics",
                headers={"Accept": "text/plain"})
            ftext = urllib.request.urlopen(freq).read().decode()
            _assert_valid_exposition(ftext)
            assert ftext.count("# TYPE lmrs_http_requests_total counter") == 1
            assert f'lmrs_http_requests_total{{host="{urls[0]}"}}' in ftext
            assert "\nlmrs_http_requests_total 0\n" in ftext  # its own, bare
        finally:
            front.shutdown()
        router.shutdown()
    finally:
        for s in servers:
            s.shutdown()
