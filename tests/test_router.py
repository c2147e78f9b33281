"""Cross-process serving (VERDICT r4 item 7): two REAL lmrs-serve OS
processes — each with its own continuous-batching scheduler — fed from one
queue by ``serving/router.py``'s RouterEngine.

This is the multi-host serving deployment in miniature: per-host server
processes (DCN would carry only requests/completions), a router fanning one
request list over the fleet, cancellation crossing the process boundary as
a hangup, and per-host failure degrading instead of killing the wave.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import random
import threading
import time
import urllib.request

import pytest

from lmrs_tpu.engine.api import GenerationRequest
from lmrs_tpu.serving.router import RouterEngine
from lmrs_tpu.utils.platform import child_env


from tests.conftest import free_port as _free_port


def _wait_healthy(url: str, proc, deadline_s: float = 180.0) -> None:
    t0 = time.time()
    while time.time() - t0 < deadline_s:
        if proc.poll() is not None:
            raise RuntimeError(
                f"worker died rc={proc.returncode}: {proc.stderr.read().decode()[-2000:]}")
        try:
            with urllib.request.urlopen(f"{url}/healthz", timeout=2) as r:
                if r.status == 200:
                    return
        except OSError:
            time.sleep(0.3)
    raise TimeoutError(f"{url} never became healthy")


def _host_metrics(url: str) -> dict:
    with urllib.request.urlopen(f"{url}/metrics", timeout=5) as r:
        return json.loads(r.read())


def _spawn_mock_worker(port: int) -> subprocess.Popen:
    """One mock-backend lmrs-serve process (the shared worker-spawn used
    by the fleet tests that don't need a real scheduler)."""
    return subprocess.Popen(
        [sys.executable, "-m", "lmrs_tpu.serving.cli",
         "--backend", "mock", "--port", str(port), "-q"],
        env=child_env(JAX_PLATFORMS="cpu"),
        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)


def _teardown(procs) -> None:
    for proc in procs:
        proc.terminate()
    for proc in procs:
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


@pytest.fixture(scope="module")
def cluster():
    """Two lmrs-serve processes with REAL jax continuous schedulers
    (quality-tiny byte model — the same preset the CLI quality gate
    compiles on CPU) + a RouterEngine over both."""
    ports = [_free_port(), _free_port()]
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    env = child_env(JAX_PLATFORMS="cpu")
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "lmrs_tpu.serving.cli",
             "--backend", "jax", "--model", "quality-tiny",
             "--tokenizer", "byte", "--port", str(p),
             "--batch-slots", "2", "--max-tokens-cap", "1024", "-q"],
            env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        )
        for p in ports
    ]
    router = RouterEngine(urls, timeout_s=300.0)
    try:
        for url, proc in zip(urls, procs):
            _wait_healthy(url, proc)
        yield urls, procs, router
    finally:
        router.shutdown()
        _teardown(procs)


def test_wave_fans_over_both_processes(cluster):
    """One wave through the router completes on BOTH worker processes,
    order preserved, per-request accounting intact."""
    urls, _, router = cluster
    reqs = [GenerationRequest(prompt=f"router fan probe {i}", request_id=i,
                              temperature=0.0, max_new_tokens=6)
            for i in range(6)]
    out = router.generate_batch(reqs)
    assert [r.request_id for r in out] == list(range(6))
    assert all(r.error is None for r in out)
    assert all(0 < r.completion_tokens <= 6 for r in out)
    for url in urls:  # both schedulers actually decoded
        m = _host_metrics(url)
        assert m["engine"]["decode_tokens"] > 0, f"{url} served nothing"
        assert m["http_requests"] > 0


def test_streamed_matches_nonstreamed_greedy(cluster):
    """on_tokens through the router consumes the remote SSE stream; greedy
    text must match the non-streamed wire path (identical weights + seed
    on both workers, so host routing cannot change the answer)."""
    _, _, router = cluster
    req = dict(prompt="stream parity probe", temperature=0.0,
               max_new_tokens=8)
    plain = router.generate_batch([GenerationRequest(request_id=0, **req)])[0]
    deltas: list[str] = []
    streamed = router.generate_batch(
        [GenerationRequest(request_id=1, **req)],
        on_tokens=lambda rid, d: deltas.append(d))[0]
    assert plain.error is None and streamed.error is None
    assert streamed.text == plain.text
    assert "".join(deltas) == streamed.text


def test_cancel_crosses_process_boundary(cluster):
    """router.cancel() hangs up the in-flight socket; the worker's
    disconnect detection must cancel the request REMOTELY (its scheduler
    records the abort and frees the slot) while the router reports
    finish_reason='cancelled' locally."""
    urls, _, router = cluster
    cancelled_before = sum(
        _host_metrics(u)["engine"].get("cancelled", 0) for u in urls)

    result = {}

    def run() -> None:
        result["res"] = router.generate_batch(
            [GenerationRequest(prompt="cancel me over the wire",
                               request_id=77, temperature=0.0,
                               max_new_tokens=900)])[0]

    tokens_before = {u: _host_metrics(u)["engine"]["decode_tokens"]
                     for u in urls}
    t = threading.Thread(target=run)
    t.start()
    # cancel once a worker is provably mid-decode on THIS request: its
    # decode_tokens counter grows past the pre-test snapshot (900 tokens /
    # decode_block 16 = 56 block boundaries for the sweep to land on —
    # the budget is deliberately large so a fast warm decode cannot
    # complete inside the worker's 0.5 s disconnect-poll window and win
    # the race against the cancel)
    deadline = time.time() + 120
    while time.time() < deadline and t.is_alive():
        if any(_host_metrics(u)["engine"]["decode_tokens"]
               > tokens_before[u] for u in urls):
            break
        time.sleep(0.05)
    assert t.is_alive(), "victim finished before the cancel could land"
    router.cancel(77)
    t.join(timeout=120)
    assert not t.is_alive(), "cancelled request never returned"
    assert result["res"].finish_reason == "cancelled"
    # the abort reached the WORKER's scheduler (cross-process sweep)
    deadline = time.time() + 60
    while time.time() < deadline:
        cancelled_now = sum(
            _host_metrics(u)["engine"].get("cancelled", 0) for u in urls)
        if cancelled_now == cancelled_before + 1:
            break
        time.sleep(0.3)
    assert cancelled_now == cancelled_before + 1, \
        "worker never recorded the remote cancellation"


def test_streamed_cancel_is_cancelled_not_stop(cluster):
    """A cancel mid-SSE-stream must report finish_reason='cancelled' with
    only the deltas received — the server's unframed SSE body reads as a
    clean EOF on hangup, which must not masquerade as a normal 'stop'
    completion.  (Random-init workers flush deltas only at completion —
    invalid UTF-8 partials never form consistent prefixes — so the
    mid-decode trigger is the worker metrics poll, same as the
    non-streamed cancel test; the delta list is then typically empty.)"""
    urls, _, router = cluster
    deltas: list[str] = []
    result = {}

    def run() -> None:
        result["res"] = router.generate_batch(
            [GenerationRequest(prompt="stream cancel probe", request_id=5,
                               temperature=0.0, max_new_tokens=400)],
            on_tokens=lambda rid, piece: deltas.append(piece))[0]

    tokens_before = {u: _host_metrics(u)["engine"]["decode_tokens"]
                     for u in urls}
    t = threading.Thread(target=run)
    t.start()
    deadline = time.time() + 120
    while time.time() < deadline and t.is_alive():
        if any(_host_metrics(u)["engine"]["decode_tokens"]
               > tokens_before[u] for u in urls):
            break
        time.sleep(0.05)
    assert t.is_alive(), "victim finished before the cancel could land"
    router.cancel(5)
    t.join(timeout=120)
    assert not t.is_alive(), "cancelled streamed request never returned"
    res = result["res"]
    assert res.finish_reason == "cancelled", res
    assert res.text == "".join(deltas)
    assert res.completion_tokens < 400



def test_prefix_route_identity_on_jax_cluster(cluster):
    """The jax arm of the routing identity A/B: the same greedy
    same-preamble workload through the real two-scheduler cluster routed
    and round-robin — token-identical texts, and the routed arm reports
    prefix placements."""
    urls, _procs, _router = cluster
    hosts = [u.split("//", 1)[1] for u in urls]

    def run(prefix_route: bool) -> list[str]:
        router = RouterEngine(hosts, timeout_s=300.0,
                              prefix_route=prefix_route)
        try:
            out = []
            for w in range(3):  # single-request waves: RR scatters
                res = router.generate_batch([GenerationRequest(
                    prompt=_SHARED_PRE + "Chunk: facts here.",
                    request_id=w, temperature=0.0, max_new_tokens=12,
                    cache_prefix=len(_SHARED_PRE))])[0]
                assert res.error is None, res.error
                out.append(res.text)
            if prefix_route:
                em = router.engine_metrics()["prefix_route"]
                assert em["routed"] == 3, em
            return out
        finally:
            router.shutdown()

    routed = run(True)
    rr = run(False)
    assert routed == rr
    assert len(set(routed)) == 1  # same prompt, greedy: one text


def test_dead_host_degrades_not_fails(cluster):
    """Killing one worker mid-fleet must not fail the wave: requests
    reroute to the survivor and the dead host is marked unhealthy.
    (Runs LAST in this module — it takes a worker down.)"""
    urls, procs, router = cluster
    procs[1].kill()
    procs[1].wait(timeout=10)
    reqs = [GenerationRequest(prompt=f"survivor probe {i}", request_id=i,
                              temperature=0.0, max_new_tokens=4)
            for i in range(4)]
    out = router.generate_batch(reqs)
    assert all(r.error is None for r in out), [r.error for r in out]
    assert all(r.completion_tokens > 0 for r in out)
    m = router.engine_metrics()
    assert m["healthy_hosts"] == 1
    by_host = {row["host"]: row for row in m["per_host"]}
    dead = urls[1].removeprefix("http://")
    assert not by_host[dead]["healthy"]


def test_pipeline_map_reduce_over_http_fleet(tmp_path):
    """The COMPLETE map-reduce pipeline with backend='http': chunks fan
    over two lmrs-serve processes and the hierarchical reduce rides the
    same fleet — the reference's deployment shape (pipeline here, models
    behind HTTP there), with our servers on the far side."""
    import dataclasses

    from lmrs_tpu.config import EngineConfig, PipelineConfig
    from lmrs_tpu.pipeline import TranscriptSummarizer

    ports = [_free_port(), _free_port()]
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    procs = [_spawn_mock_worker(p) for p in ports]
    try:
        for url, proc in zip(urls, procs):
            _wait_healthy(url, proc, deadline_s=60)
        segs, t = [], 0.0
        for i in range(400):
            segs.append({"start": t, "end": t + 2.0,
                         "text": f"Fleet pipeline segment {i} covers point {i % 13}.",
                         "speaker": "SPEAKER_00"})
            t += 2.2
        cfg = PipelineConfig(engine=EngineConfig(
            backend="http", hosts=tuple(urls), retry_delay=0.0))
        cfg = dataclasses.replace(
            cfg, chunk=dataclasses.replace(cfg.chunk, max_tokens_per_chunk=400))
        stats = TranscriptSummarizer(cfg).summarize({"segments": segs})
        assert stats["num_chunks"] >= 4
        assert stats["failed_requests"] == 0
        assert stats["summary"].strip()
        served = [_host_metrics(u)["http_requests"] for u in urls]
        assert all(n > 0 for n in served), f"fleet imbalance: {served}"
    finally:
        _teardown(procs)


def test_dead_host_recovers_via_probe(cluster):
    """A host that comes back (worker restart on the same port) must be
    re-admitted by the per-wave /healthz probe — an unhealthy mark is not
    a life sentence.  Runs after test_dead_host_degrades_not_fails killed
    worker 1; restarts it (mock backend: the router is engine-agnostic)."""
    urls, procs, router = cluster
    assert not router.hosts[1].healthy  # left dead by the previous test
    port = urls[1].rsplit(":", 1)[1]
    procs[1] = subprocess.Popen(
        [sys.executable, "-m", "lmrs_tpu.serving.cli",
         "--backend", "mock", "--port", port, "-q"],
        env=child_env(JAX_PLATFORMS="cpu"),
        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)
    _wait_healthy(urls[1], procs[1], deadline_s=60)
    # each wave launches probes at unhealthy hosts; a couple of waves give
    # the async probe time to land and the router starts routing there
    deadline = time.time() + 30
    while time.time() < deadline and not router.hosts[1].healthy:
        router.generate_batch(
            [GenerationRequest(prompt="probe tick", request_id=900,
                               temperature=0.0, max_new_tokens=2)])
        time.sleep(0.2)
    assert router.hosts[1].healthy, "probe never re-admitted the host"
    served_before = router.hosts[1].served
    out = router.generate_batch(
        [GenerationRequest(prompt=f"rejoin probe {i}", request_id=i,
                           temperature=0.0, max_new_tokens=2)
         for i in range(4)])
    assert all(r.error is None for r in out)
    assert router.hosts[1].served > served_before, \
        "re-admitted host received no traffic"


@pytest.mark.parametrize("seed", [7, 41])
def test_fuzzed_router_waves_with_cancels_and_kills(seed):
    """Router invariants under churn (SURVEY §5.2 for the multi-host
    tier): random waves with random mid-wave cancels and a mid-test
    worker kill — every request must get exactly ONE result (cancelled,
    completed, or error), ids and order preserved, and the router must
    never raise.  Mock-backend workers: the fuzz targets the ROUTING
    layer's state machine, not the engine (the scheduler has its own
    fuzz suite)."""
    rng = random.Random(seed)
    ports = [_free_port(), _free_port()]
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    procs = [_spawn_mock_worker(p) for p in ports]
    router = RouterEngine(urls, timeout_s=60.0)
    try:
        for url, proc in zip(urls, procs):
            _wait_healthy(url, proc, deadline_s=60)
        rid = 0
        kill_wave = rng.randrange(2, 5)
        for wave in range(6):
            n = rng.randrange(1, 9)
            reqs = [GenerationRequest(prompt=f"fuzz {seed} {wave} {i}",
                                      request_id=rid + i, temperature=0.0,
                                      max_new_tokens=rng.randrange(1, 6))
                    for i in range(n)]
            rid += n
            victims = [r.request_id for r in reqs if rng.random() < 0.3]
            canceller = threading.Timer(
                0.001 * rng.randrange(0, 20),
                lambda v=victims: [router.cancel(x) for x in v])
            canceller.start()
            if wave == kill_wave:
                procs[1].kill()  # mid-fleet failure
            out = router.generate_batch(reqs)
            canceller.join()
            assert [r.request_id for r in out] == [r.request_id for r in reqs]
            for r in out:
                # mock waves are near-instant, so a cancel can land before,
                # during, or after its victim — any single coherent outcome
                # is legal, but exactly one result must exist per request
                assert r.finish_reason in ("stop", "length", "cancelled",
                                           "error"), r
            if wave == kill_wave:
                # restart so later waves can re-admit via the probe;
                # wait() first: SIGKILL returns before the kernel closes
                # the old listener, and a respawn would EADDRINUSE (same
                # reason the dead-host test reaps before asserting)
                procs[1].wait(timeout=10)
                procs[1] = _spawn_mock_worker(ports[1])
                _wait_healthy(urls[1], procs[1], deadline_s=60)
        # the fleet ends functional: one clean wave, no errors
        final = router.generate_batch(
            [GenerationRequest(prompt="post-fuzz", request_id=9999,
                               temperature=0.0, max_new_tokens=2)])
        assert final[0].error is None
    finally:
        router.shutdown()
        _teardown(procs)


# --------------------------------------------------- probe pacing (no fleet)


def test_probe_pacing_with_fake_clock():
    """A dead host under heavy traffic must not draw one /healthz probe per
    wave (a probe storm scaling with offered load): probes space at least
    probe_floor_s apart per host, plus jitter, enforced on an injectable
    clock so this test never sleeps."""
    clock = [100.0]
    router = RouterEngine(["127.0.0.1:1", "127.0.0.1:2"],
                          probe_floor_s=5.0, probe_jitter_s=2.0,
                          clock=lambda: clock[0])
    try:
        for h in router.hosts:
            h.healthy = False
            h.probe = lambda: False  # stays dead; no network touched
        assert len(router._launch_probes()) == 2  # both eligible at t=100
        # a storm of waves at the same instant: zero further probes
        for _ in range(50):
            assert router._launch_probes() == []
        clock[0] += 4.99  # just under the floor
        assert router._launch_probes() == []
        clock[0] += 5.0 + 2.0  # beyond floor + max jitter
        assert len(router._launch_probes()) == 2  # exactly one more each
        assert router._launch_probes() == []
        # a healthy host is never probed
        router.hosts[0].healthy = True
        clock[0] += 100.0
        assert router._launch_probes() == [router.hosts[1]]
    finally:
        router.shutdown()


# ------------------------------------------- fault-injection sites (no fleet)


def _mock_server():
    from lmrs_tpu.engine.mock import MockEngine
    from lmrs_tpu.serving.server import EngineHTTPServer

    srv = EngineHTTPServer(MockEngine(), port=0, batch_window_s=0.01)
    srv.start_background()
    return srv


def test_router_connect_fault_fails_over_and_marks_host():
    """An injected connection-phase fault must mark the first target
    unhealthy and fail the request over to the next host — the same path a
    dead backend takes, driven without killing a process."""
    from lmrs_tpu.testing import faults
    from lmrs_tpu.testing.faults import FaultPlan

    srv = _mock_server()
    url = f"127.0.0.1:{srv.port}"
    router = RouterEngine([url, url], timeout_s=30.0)  # same backend twice
    try:
        with faults.injected(FaultPlan(faults=[
                {"site": "router.connect", "at": [1], "max_fires": 1}])):
            res = router.generate_batch([GenerationRequest(
                prompt="failover probe", request_id=0)])[0]
        assert res.error is None  # the second target served it
        assert res.text
        fails = [h.failed for h in router.hosts]
        assert sorted(fails) == [0, 1], fails
        assert any(not h.healthy for h in router.hosts)  # condemned target
    finally:
        router.shutdown()
        srv.shutdown()


def test_router_recv_fault_surfaces_midstream_error():
    """An injected mid-stream fault AFTER deltas were forwarded must
    surface as an error result without a retry — a replay would duplicate
    the deltas already delivered (Engine streaming contract)."""
    from lmrs_tpu.testing import faults
    from lmrs_tpu.testing.faults import FaultPlan

    srv = _mock_server()
    router = RouterEngine([f"127.0.0.1:{srv.port}"], timeout_s=30.0)
    deltas: list[str] = []
    try:
        # SSE lines for the mock: role frame, blank, content frame, blank,
        # finish frame... — occurrence 5 lands after the content delta
        with faults.injected(FaultPlan(faults=[
                {"site": "router.recv", "at": [5], "max_fires": 1}])):
            res = router.generate_batch(
                [GenerationRequest(prompt="One fact. Two facts.",
                                   request_id=1)],
                on_tokens=lambda rid, d: deltas.append(d))[0]
        assert res.finish_reason == "error"
        assert deltas, "fault should land after the first content delta"
        assert router.hosts[0].healthy  # per-request fault, not a dead host
    finally:
        router.shutdown()
        srv.shutdown()


# ---------------------------------------------- prefix-aware routing (ISSUE 12)

_SHARED_PRE = ("You are summarizing one section of a much longer "
               "transcript. Keep every fact, decision, name, and number. ")


def _preamble_requests(lo: int, n: int) -> list[GenerationRequest]:
    return [GenerationRequest(
        prompt=_SHARED_PRE + f"Chunk {i}: the team discussed item {i}.",
        request_id=lo + i, temperature=0.0,
        system_prompt="Respond with the summary content only.",
        cache_prefix=len(_SHARED_PRE)) for i in range(n)]


def _mock_fleet(n: int = 2, **router_kw):
    from lmrs_tpu.engine.mock import MockEngine
    from lmrs_tpu.serving.server import EngineHTTPServer

    servers = [EngineHTTPServer(MockEngine(seed=0), port=0,
                                batch_window_s=0.01) for _ in range(n)]
    for s in servers:
        s.start_background()
    router = RouterEngine([f"127.0.0.1:{s.port}" for s in servers],
                          timeout_s=30.0, **router_kw)
    return servers, router


def test_request_body_forwards_cache_prefix():
    """The satellite regression (ISSUE 12): the wire must carry the
    prefix-cache hint end to end — _request_body emits it and the
    server's request builders parse it back — or routed requests insert
    uncapped into the backend radix tree."""
    from lmrs_tpu.serving.router import _request_body
    from lmrs_tpu.serving.server import (_chat_to_request,
                                         _messages_to_request)

    req = _preamble_requests(0, 1)[0]
    body = _request_body(req)
    assert body["cache_prefix"] == len(_SHARED_PRE)
    rebuilt = _chat_to_request(body, max_tokens_cap=4096)
    assert rebuilt.cache_prefix == len(_SHARED_PRE)
    assert rebuilt.prompt == req.prompt
    assert rebuilt.system_prompt == req.system_prompt
    body2 = dict(body, system=req.system_prompt)
    assert _messages_to_request(body2, 4096).cache_prefix == len(_SHARED_PRE)
    # hint-free requests forward no field and parse back None (and
    # garbage on the wire never crashes the builder)
    assert "cache_prefix" not in _request_body(
        GenerationRequest(prompt="p", request_id=1))
    assert _chat_to_request({"messages": [], "cache_prefix": True},
                            4096).cache_prefix is None


def test_routed_requests_hit_backend_prefix_cache():
    """Router→server regression: forwarded same-preamble requests REPORT
    prefix-cache hits on the backend (the hint actually reached the
    radix accounting), and prefix placement keeps them on ONE host."""
    servers, router = _mock_fleet(2)
    try:
        for w in range(5):  # single-request waves: RR would scatter
            res = router.generate_batch(_preamble_requests(w * 10, 1))[0]
            assert res.error is None
        per = [_host_metrics(f"http://127.0.0.1:{s.port}") for s in servers]
        blocks = [m["engine"].get("prefix_cache") for m in per
                  if m["engine"].get("prefix_cache")]
        assert len(blocks) == 1, "placement scattered across hosts"
        assert blocks[0]["queries"] == 5
        assert blocks[0]["hits"] == 4, blocks
        assert blocks[0]["prefill_tokens_saved"] > 0
        em = router.engine_metrics()["prefix_route"]
        assert em["enabled"] and em["routed"] == 5
    finally:
        router.shutdown()
        _shutdown_fleet(servers)


def _shutdown_fleet(servers) -> None:
    for s in servers:
        s.shutdown()


def test_prefix_route_identity_vs_round_robin():
    """Placement must never change outputs: the same workload through a
    routed fleet and a round-robin fleet produces identical texts (mock
    determinism is per (seed, prompt) — host-independent)."""
    servers, routed = _mock_fleet(2, summary_ttl_s=1.0)
    rr = RouterEngine([h.netloc for h in routed.hosts], timeout_s=30.0,
                      prefix_route=False)
    try:
        reqs = _preamble_requests(0, 6)
        t_routed = [r.text for r in routed.generate_batch(reqs)]
        t_rr = [r.text for r in rr.generate_batch(_preamble_requests(0, 6))]
        assert t_routed == t_rr
        assert all(t for t in t_routed)
        assert rr.engine_metrics()["prefix_route"]["enabled"] is False
        assert rr.engine_metrics()["prefix_route"]["routed"] == 0
    finally:
        routed.shutdown()
        rr.shutdown()
        _shutdown_fleet(servers)


def test_prefix_route_env_kill_switch(monkeypatch):
    monkeypatch.setenv("LMRS_PREFIX_ROUTE", "0")
    router = RouterEngine(["127.0.0.1:1"])
    try:
        assert router.prefix_route is False
        req = _preamble_requests(0, 1)[0]
        assert router._prefix_target(req) == (None, False, False)
    finally:
        router.shutdown()


def test_prefix_route_summary_predicted_placement():
    """With a short summary TTL the predicted path engages: the host that
    served the preamble publishes it via /healthz and later requests are
    placed on its summary, not just the rendezvous hash."""
    servers, router = _mock_fleet(2, summary_ttl_s=0.5)
    try:
        for i in range(3):
            router.generate_batch(_preamble_requests(i * 10, 1))
            time.sleep(0.4)  # let the wave-path summary refresh land
        em = router.engine_metrics()["prefix_route"]
        assert em["predicted"] >= 1, em
        assert em["routed"] == 3
    finally:
        router.shutdown()
        _shutdown_fleet(servers)


def test_prefix_route_ab_beats_round_robin_aggregate():
    """The acceptance A/B (ISSUE 12): over 2 hosts sharing preambles,
    routed placement raises the fleet-aggregate hit rate and
    prefill-tokens-saved vs round-robin (scripts/ab_prefix_route.py is
    the reporting harness; this is the tier-1 assertion)."""
    def run(prefix_route: bool) -> tuple[int, int]:
        servers, router = _mock_fleet(2, prefix_route=prefix_route)
        try:
            for w in range(6):
                res = router.generate_batch(
                    _preamble_requests(w * 10, 1))[0]
                assert res.error is None
            hits = saved = 0
            for s in servers:
                pc = _host_metrics(f"http://127.0.0.1:{s.port}")[
                    "engine"].get("prefix_cache") or {}
                hits += pc.get("hits", 0)
                saved += pc.get("prefill_tokens_saved", 0)
            return hits, saved
        finally:
            router.shutdown()
            _shutdown_fleet(servers)

    rr_hits, rr_saved = run(prefix_route=False)
    ro_hits, ro_saved = run(prefix_route=True)
    assert ro_hits > rr_hits, (ro_hits, rr_hits)
    assert ro_saved > rr_saved, (ro_saved, rr_saved)


def test_unhealthy_preferred_host_degrades_to_ordering():
    """A rendezvous/predicted pick that is unhealthy must degrade to the
    normal load/health order (the request still completes elsewhere)."""
    servers, router = _mock_fleet(2)
    try:
        req = _preamble_requests(0, 1)[0]
        prefer, _pred, eligible = router._prefix_target(req)
        assert eligible and prefer is not None
        prefer.healthy = False
        prefer2, _pred2, _el = router._prefix_target(req)
        assert prefer2 is not prefer
        res = router.generate_batch(_preamble_requests(0, 1))[0]
        assert res.error is None
        em = router.engine_metrics()["prefix_route"]
        assert em["routed"] >= 1
    finally:
        router.shutdown()
        _shutdown_fleet(servers)


def test_prefix_route_fair_share_keeps_fleet_busy():
    """A same-preamble BATCH wave must not serialize onto the sticky
    host: the wave planner caps the sticky share at ceil(group/healthy)
    and spreads the rest, so a map fan-out keeps every host busy while
    single-request waves stay fully sticky."""
    servers, router = _mock_fleet(2)
    try:
        out = router.generate_batch(_preamble_requests(0, 12))
        assert all(r.error is None for r in out)
        served = sorted(h.served for h in router.hosts)
        assert served[0] > 0, f"fleet imbalance: {served}"
        em = router.engine_metrics()["prefix_route"]
        # sticky share = ceil(12/2) = 6; the rest deliberately spread
        assert em["routed"] == 6 and em["fallback"] == 6, em
    finally:
        router.shutdown()
        _shutdown_fleet(servers)
