"""Hang-survival tier (ISSUE 14): dispatch watchdog, straggler hedging,
circuit-breaker quarantine, and supervised restart.

Every scenario drives ``action: "stall"`` plans (or real non-answering
sockets) through the new fault sites — ``scheduler.heartbeat``,
``replicated.shard``, ``router.hedge`` — and asserts the system-level
contract: a wedge becomes a BOUNDED, observable failure (wedged/deadline
results, postmortem, quarantine, respawn) instead of a silent freeze,
and ``LMRS_WATCHDOG=0`` restores the pre-watchdog inline dispatch
exactly.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from lmrs_tpu.config import EngineConfig, MeshConfig, ModelConfig
from lmrs_tpu.engine.api import GenerationRequest, GenerationResult
from lmrs_tpu.engine.jax_engine import JaxEngine
from lmrs_tpu.engine.mock import MockEngine
from lmrs_tpu.engine.replicated import ReplicatedEngine
from lmrs_tpu.testing import faults
from lmrs_tpu.testing.faults import FaultPlan
from lmrs_tpu.utils.platform import child_env

sys.path.insert(0, os.path.dirname(__file__))
import _job_worker as jw  # noqa: E402 - shared job transcript builder

TINY = ModelConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                   n_kv_heads=2, hidden_dim=128, max_seq_len=256,
                   dtype="float32")

ECFG = EngineConfig(backend="jax", scheduler="continuous", max_tokens=64,
                    max_batch_slots=2, seed=0, decode_block=4,
                    page_size=16, num_pages=20)


def _req(rid: int, prompt: str = "hang survival probe alpha bravo",
         max_new: int = 8, deadline_s: float | None = None):
    return GenerationRequest(prompt=prompt, request_id=rid,
                             temperature=0.0, max_new_tokens=max_new,
                             deadline_s=deadline_s)


def _stall_plan(occ: int, stall_s: float) -> FaultPlan:
    return FaultPlan(faults=[{"site": "scheduler.heartbeat", "at": [occ],
                              "action": "stall", "stall_s": stall_s}])


@pytest.fixture(scope="module")
def wd_engine():
    eng = JaxEngine(ECFG, TINY)
    # warm the compiled shapes AND the step-time EMA so the explicit tiny
    # LMRS_WATCHDOG_S thresholds below are the only gate (cold compiles
    # run under the watchdog's grace window and must not be part of the
    # scenario timing)
    for rid in (990, 991):
        eng.generate_batch([_req(rid, prompt="warmup wedge probe")])
    yield eng
    eng.shutdown()


# ------------------------------------------------------------- watchdog


def test_watchdog_ema_ignores_graced_windows():
    """A cold-compile wall must NOT fold into the step-time EMA even
    though grace_end() re-arms stall detection the moment the compile
    lands — folding it would inflate the auto wedge threshold ~30x per
    compile for the rest of the run."""
    from lmrs_tpu.engine.watchdog import DispatchWatchdog

    wd = DispatchWatchdog()
    wd.run_started()
    time.sleep(0.01)
    wd.beat()
    ema1 = wd.ema_step_s
    assert ema1 is not None
    wd.grace_cold()   # a "compile" opens...
    wd.grace_end()    # ...and lands: detection re-armed
    assert wd.stalled_for() >= 0.0  # no grace suppression left
    time.sleep(0.08)  # the compile-polluted window
    wd.beat()
    assert wd.ema_step_s == ema1, "graced window folded into the EMA"
    time.sleep(0.01)
    wd.beat()  # the next CLEAN window folds again
    assert wd.ema_step_s != ema1


def test_watchdog_grace_end_restarts_the_stall_clock(monkeypatch):
    """A graced compile longer than the wedge threshold is no wedge when
    it lands: grace_end() counts the stall from the compile's end, not from
    the beat before the compile (PERF.md section 6).  A genuine stall after
    it is still caught."""
    from lmrs_tpu.engine.watchdog import DispatchWatchdog

    monkeypatch.setenv("LMRS_WATCHDOG_S", "0.05")
    wd = DispatchWatchdog()
    wd.run_started()
    wd.grace_cold()
    time.sleep(0.12)  # the compile, longer than the threshold
    assert wd.stalled_for() == 0.0
    wd.grace_end()
    assert wd.stalled_for() < wd.timeout_s()
    time.sleep(0.12)
    assert wd.stalled_for() > wd.timeout_s()


def test_a_graced_compile_longer_than_the_threshold_is_no_wedge(
        wd_engine, monkeypatch):
    """The same through the engine: a cold prefill whose "compile" (1.5 s
    inside the grace) outlasts a 0.6 s threshold, and whose device sync
    (0.3 s) keeps the loop from its next beat after the grace closed,
    finishes its request with no watchdog fire."""
    monkeypatch.setenv("LMRS_WATCHDOG_S", "0.6")
    sched = wd_engine._scheduler
    fires = sched.metrics["watchdog_fires"]
    grace, ran_ok = sched._wd_grace_cold, sched._note_ran_ok
    seen = []

    def slow_grace():
        grace()
        if not seen:
            seen.append("compile")
            time.sleep(1.5)

    def ran_ok_then_sync(key):
        ran_ok(key)
        if seen == ["compile"]:
            seen.append("sync")
            time.sleep(0.3)

    # every prefill shape reads as new: the next prefill dispatch is cold
    sched._ran_ok -= {k for k in sched._ran_ok if str(k[0]).startswith(
        "prefill")}
    monkeypatch.setattr(sched, "_wd_grace_cold", slow_grace)
    monkeypatch.setattr(sched, "_note_ran_ok", ran_ok_then_sync)
    out = wd_engine.generate_batch([_req(70)])
    assert seen == ["compile", "sync"]
    assert out[0].error is None
    assert out[0].finish_reason in ("stop", "length")
    assert sched.metrics["watchdog_fires"] == fires
    assert not wd_engine.wedged()


def test_watchdog_armed_by_default(wd_engine):
    """LMRS_WATCHDOG defaults on: the runner thread exists, the scheduler
    carries a heartbeat, and a plain batch behaves exactly as before."""
    assert wd_engine._runner is not None
    assert wd_engine._scheduler.watchdog is not None
    assert wd_engine._scheduler.watchdog.ema_step_s is not None
    assert not wd_engine.wedged()


def test_wedge_mid_decode_bounded_wedged_results(wd_engine, monkeypatch,
                                                 tmp_path):
    """The tentpole scenario: a stall wedges the dispatch loop mid-decode.
    Within a bounded wall the watchdog declares the wedge — flight
    recorder postmortem written, in-flight requests terminate
    ``finish_reason="wedged"`` with the error marked, the engine runs
    fail-fast degraded — and once the stall ends the abandoned run
    recovers the engine with the auditor clean."""
    monkeypatch.setenv("LMRS_WATCHDOG_S", "0.3")
    monkeypatch.setenv("LMRS_POSTMORTEM_DIR", str(tmp_path))
    monkeypatch.setenv("LMRS_POSTMORTEM_MIN_S", "0")
    sched = wd_engine._scheduler
    fires = sched.metrics["watchdog_fires"]
    t0 = time.time()
    # occurrence 3: the loop has already dispatched (mid-run), so the
    # wedge lands while requests hold slots
    with faults.injected(_stall_plan(3, 2.5)):
        out = wd_engine.generate_batch([_req(0), _req(1)])
    wall = time.time() - t0
    assert wall < 2.0, f"wedge delivery not bounded: {wall:.2f}s"
    assert [r.finish_reason for r in out] == ["wedged", "wedged"]
    assert all(r.error and "wedged" in r.error for r in out)
    assert sched.metrics["watchdog_fires"] == fires + 1
    assert sched.metrics["wedged_requests"] >= 2
    assert wd_engine.wedged()
    # fail-fast while degraded: nothing queues behind the dead dispatch
    t0 = time.time()
    ff = wd_engine.generate_batch([_req(2)])[0]
    assert ff.finish_reason == "wedged" and time.time() - t0 < 0.5
    # postmortem: schema-valid, reason "watchdog"
    from lmrs_tpu.obs import validate_postmortem_file

    dumps = sorted(tmp_path.glob("postmortem-watchdog-*.json"))
    assert dumps, "watchdog fired no postmortem"
    doc = validate_postmortem_file(dumps[0])
    assert doc["reason"] == "watchdog"
    assert doc["extra"]["stalled_s"] >= 0.3
    # the stall ends; the abandoned run finishes and the engine re-arms
    assert wd_engine._runner.wait_idle(30.0)
    assert not wd_engine.wedged()
    good = wd_engine.generate_batch([_req(3)])[0]
    assert good.finish_reason in ("stop", "length") and good.error is None
    assert sched.audit() == []


def test_wedged_run_expired_deadlines_deliver_deadline(wd_engine,
                                                       monkeypatch):
    """Satellite: deadline-expired in-flight requests used to be swept
    only at block boundaries a wedged loop never reaches — the watchdog
    sweep delivers their contractual ``"deadline"`` results (no error;
    the executor must not retry an expired budget)."""
    monkeypatch.setenv("LMRS_WATCHDOG_S", "0.6")
    dl_before = wd_engine._scheduler.metrics["deadline_exceeded"]
    with faults.injected(_stall_plan(1, 2.0)):
        out = wd_engine.generate_batch(
            [_req(10, deadline_s=time.time() + 0.4, max_new=32)])
    assert out[0].finish_reason == "deadline", out[0]
    assert out[0].error is None
    assert (wd_engine._scheduler.metrics["deadline_exceeded"]
            == dl_before + 1)
    assert wd_engine._runner.wait_idle(30.0)
    assert wd_engine._scheduler.audit() == []


def test_watchdog_off_is_inline_and_token_identical(wd_engine, monkeypatch):
    """The kill switch: LMRS_WATCHDOG=0 builds no runner and no watchdog
    — dispatch runs inline on the caller thread (today's path) — and a
    heartbeat stall plan simply stalls the run, which then completes
    normally, token-identical to the armed engine's fault-free output."""
    want = wd_engine.generate_batch([_req(20)])[0]
    if wd_engine._runner is not None:  # None when CI re-runs this test
        assert wd_engine._runner.wait_idle(5.0)  # with LMRS_WATCHDOG=0
    monkeypatch.setenv("LMRS_WATCHDOG", "0")
    eng = JaxEngine(ECFG, TINY)
    try:
        assert eng._runner is None
        assert eng._scheduler.watchdog is None
        t0 = time.time()
        with faults.injected(_stall_plan(1, 0.7)):
            got = eng.generate_batch([_req(20)])[0]
        assert time.time() - t0 >= 0.7  # the stall really blocked the run
        assert got.finish_reason == want.finish_reason
        assert got.text == want.text
        assert eng._scheduler.metrics["watchdog_fires"] == 0
        assert eng._scheduler.audit() == []
    finally:
        eng.shutdown()


def test_executor_retry_completes_after_transient_wedge(wd_engine,
                                                        monkeypatch):
    """Acceptance: a deterministic stall plan at scheduler.heartbeat
    completes a workload with bounded wall time and outputs
    token-identical to a fault-free run — the wedged results carry an
    error, the executor retries once the transient stall clears."""
    from lmrs_tpu.engine.executor import MapExecutor

    monkeypatch.setenv("LMRS_WATCHDOG_S", "0.3")
    reqs = [_req(i, prompt=f"retry after wedge {i}") for i in range(3)]
    # retry_delay outlasts the stall AND the abandoned run's drain (it
    # keeps computing the workload after the stall clears, and the
    # engine stays fail-fast degraded until it finishes)
    ex = MapExecutor(wd_engine, EngineConfig(retry_attempts=3,
                                             retry_delay=2.5))
    baseline = [(r.request_id, r.text) for r in ex.run_requests(reqs)]
    assert wd_engine._runner.wait_idle(10.0)
    t0 = time.time()
    with faults.injected(_stall_plan(2, 1.0)):
        out = ex.run_requests([_req(i, prompt=f"retry after wedge {i}")
                               for i in range(3)])
    assert time.time() - t0 < 20.0
    assert [(r.request_id, r.text) for r in out] == baseline
    assert all(r.error is None for r in out)
    assert wd_engine._runner.wait_idle(30.0)
    assert wd_engine._scheduler.audit() == []


# ------------------------------------------- replicated straggler containment


@pytest.fixture(scope="module")
def dp2():
    eng = ReplicatedEngine(
        EngineConfig(backend="jax", max_tokens=16, max_batch_slots=4,
                     retry_delay=0.0, seed=0, decode_block=4,
                     prefill_chunk=128, num_pages=64, page_size=16),
        ModelConfig(name="tiny-test", vocab_size=512, dim=64, n_layers=2,
                    n_heads=4, n_kv_heads=2, hidden_dim=128,
                    max_seq_len=512),
        MeshConfig(dp=2, tp=1))
    yield eng
    eng.shutdown()


def _wave_reqs(n: int = 4):
    return [GenerationRequest(prompt=f"shard wedge probe {i}",
                              request_id=i, temperature=0.0,
                              max_new_tokens=6) for i in range(n)]


def test_replica_pools_are_daemonized(dp2):
    """Satellite: a wedged shard/probe future must never pin interpreter
    exit — every per-replica worker thread is a daemon."""
    for pool in dp2._pools:
        assert pool._thread.daemon


def test_wedged_shard_redispatches_token_identical(dp2):
    """A replica whose engine watchdog declared a wedge returns wedged
    results: the wave quarantines it and re-dispatches its shard onto the
    healthy replica — outputs token-identical to an all-healthy wave
    (greedy, identical weights), nothing surfaces as an error."""
    baseline = [(r.request_id, r.text) for r in
                dp2.generate_batch(_wave_reqs())]
    dp2._healthy[:] = [True, True]
    victim = dp2.replicas[0]
    orig = victim.generate_batch
    seen: list[str] = []

    def wedgy(requests, on_result=None, on_tokens=None):
        seen.extend(r.prompt for r in requests)
        return [GenerationResult(request_id=r.request_id,
                                 finish_reason="wedged",
                                 error="synthetic wedge")
                for r in requests]

    victim.generate_batch = wedgy
    try:
        out = dp2.generate_batch(_wave_reqs())
    finally:
        victim.generate_batch = orig
    assert seen, "victim replica saw no shard"
    assert [(r.request_id, r.text) for r in out] == baseline
    assert all(r.error is None for r in out)
    assert dp2._healthy == [False, True]
    # re-admission through the existing probe loop: the victim answers
    # again, a wave's probe re-admits it
    deadline = time.time() + 10
    while time.time() < deadline and not dp2._healthy[0]:
        dp2.generate_batch([GenerationRequest(prompt="probe tick",
                                              request_id=900,
                                              temperature=0.0,
                                              max_new_tokens=2)])
        time.sleep(0.05)
    assert dp2._healthy == [True, True]


def test_stalled_shard_quarantined_and_redispatched(dp2, monkeypatch):
    """``replicated.shard`` stall: the shard's worker wedges, the bounded
    wait times out, the replica is quarantined onto a fresh daemon pool,
    and the shard's requests complete on the healthy replica —
    token-identical, no errors."""
    monkeypatch.setenv("LMRS_SHARD_TIMEOUT_S", "1")
    dp2._healthy[:] = [True, True]
    baseline = [(r.request_id, r.text) for r in
                dp2.generate_batch(_wave_reqs())]
    dp2._healthy[:] = [True, True]
    old_pools = list(dp2._pools)
    plan = FaultPlan(faults=[{"site": "replicated.shard", "at": [1],
                              "action": "stall", "stall_s": 3.0,
                              "max_fires": 1}])
    t0 = time.time()
    with faults.injected(plan):
        out = dp2.generate_batch(_wave_reqs())
    assert time.time() - t0 < 3.0, "bounded wait did not contain the stall"
    assert [(r.request_id, r.text) for r in out] == baseline
    assert all(r.error is None for r in out)
    assert False in dp2._healthy  # one replica quarantined
    victim = dp2._healthy.index(False)
    assert dp2._pools[victim] is not old_pools[victim], \
        "quarantine must abandon the wedged pool"
    # the stall drains; the probe loop re-admits the quarantined replica
    time.sleep(3.0)
    deadline = time.time() + 15
    while time.time() < deadline and not all(dp2._healthy):
        dp2.generate_batch([GenerationRequest(prompt="probe tick",
                                              request_id=901,
                                              temperature=0.0,
                                              max_new_tokens=2)])
        time.sleep(0.1)
    assert all(dp2._healthy), "probe never re-admitted the replica"


# --------------------------------------------------- router circuit breaker


def _mock_server(latency_s: float = 0.0):
    from lmrs_tpu.serving.server import EngineHTTPServer

    srv = EngineHTTPServer(MockEngine(latency_s=latency_s), port=0,
                           batch_window_s=0.01)
    srv.start_background()
    return srv


def _wedge_listener():
    """A backend that accepts TCP but never answers — the hung-chip
    signature a connect-phase health check cannot see."""
    lst = socket.socket()
    lst.bind(("127.0.0.1", 0))
    lst.listen(16)
    held: list[socket.socket] = []

    def acceptor():
        while True:
            try:
                held.append(lst.accept()[0])
            except OSError:
                return

    threading.Thread(target=acceptor, daemon=True).start()
    return lst, held


def test_breaker_opens_on_consecutive_timeouts(monkeypatch):
    """Requests into a wedged (accepting, never answering) backend time
    out; LMRS_BREAKER_FAILURES consecutive failures open the breaker and
    the host leaves the dispatch order even though its port still
    accepts connections."""
    from lmrs_tpu.serving.router import RouterEngine

    monkeypatch.setenv("LMRS_BREAKER_FAILURES", "2")
    good = _mock_server()
    lst, held = _wedge_listener()
    wport = lst.getsockname()[1]
    router = RouterEngine([f"127.0.0.1:{wport}",
                           f"127.0.0.1:{good.port}"], timeout_s=0.5)
    try:
        h = router.hosts[0]
        for i in range(3):
            out = router.generate_batch([_req(i)])
            assert out[0].error is None, out[0]  # failover covered it
        assert h.breaker_state == "open"
        assert h.breaker_opens >= 1
        assert not h.healthy
        m = router.engine_metrics()
        assert m["per_host"][0]["breaker"] == "open"
        prom = router.prometheus_metrics()
        assert "lmrs_router_breaker_state" in prom
    finally:
        router.shutdown()
        good.shutdown()
        lst.close()
        for s in held:
            s.close()


def test_breaker_half_open_canary_closes(monkeypatch):
    """Open → (cooldown) → half-open canary (one tiny golden request
    through the REAL request path) → closed.  A failed canary re-opens
    for another cooldown."""
    from lmrs_tpu.serving.router import RouterEngine

    monkeypatch.setenv("LMRS_BREAKER_FAILURES", "2")
    monkeypatch.setenv("LMRS_BREAKER_COOLDOWN_S", "0.2")
    srv = _mock_server()
    router = RouterEngine([f"127.0.0.1:{srv.port}"])
    try:
        h = router.hosts[0]
        h.note_failed()
        h.note_failed()
        assert h.breaker_state == "open" and not h.healthy
        # inside the cooldown: the recovery pass must not canary yet
        router._recover_host(h)
        assert h.breaker_state == "open"
        time.sleep(0.25)
        router._recover_host(h)  # half-open canary against the live server
        assert h.breaker_state == "closed" and h.healthy
        # failure arm: open it again, kill the server, the canary re-opens
        h.note_failed()
        h.note_failed()
        assert h.breaker_state == "open"
        srv.shutdown()
        time.sleep(0.25)
        assert h.breaker_due()
        assert h.canary() is False
        assert h.breaker_state == "open" and not h.healthy
    finally:
        router.shutdown()


def test_breaker_disabled_keeps_binary_bit(monkeypatch):
    """LMRS_BREAKER_FAILURES=0 disables the breaker: any number of
    failures never opens it, and ``healthy`` degrades only through the
    legacy connect-phase condemnation — the pre-breaker behavior."""
    from lmrs_tpu.serving.router import _Host

    monkeypatch.setenv("LMRS_BREAKER_FAILURES", "0")
    h = _Host("127.0.0.1:1")
    for _ in range(10):
        h.note_failed()
    assert h.breaker_state == "closed" and h.healthy
    h.healthy = False
    assert not h.healthy
    h.healthy = True
    assert h.healthy


# ------------------------------------------------------------ tail hedging


def test_hedge_duplicates_straggler_first_result_wins(monkeypatch):
    """LMRS_HEDGE_MS: the primary leg straggles (slow backend), the hedge
    leg lands on the fast sibling and wins; the result is the same text
    either host would produce (mock determinism), the loser is hung up,
    and the hedge counters advance."""
    from lmrs_tpu.serving.router import RouterEngine

    slow = _mock_server(latency_s=1.5)
    fast = _mock_server()
    router = RouterEngine([f"127.0.0.1:{slow.port}",
                           f"127.0.0.1:{fast.port}"])
    try:
        monkeypatch.setenv("LMRS_HEDGE_MS", "150")
        t0 = time.time()
        res = router.generate_batch(
            [_req(0, prompt="hedge race alpha bravo charlie")])[0]
        wall = time.time() - t0
        assert res.error is None and res.finish_reason == "stop"
        assert wall < 1.4, f"hedge did not beat the straggler: {wall:.2f}s"
        assert router._hedges == 1 and router._hedge_wins == 1
        m = router.engine_metrics()
        assert m["hedge"] == {"hedges": 1, "wins": 1}
        prom = router.prometheus_metrics()
        assert "lmrs_router_hedges_total" in prom
        assert "lmrs_router_hedge_wins_total" in prom
    finally:
        router.shutdown()
        slow.shutdown()
        fast.shutdown()


def test_hedge_fault_site_abandons_hedge(monkeypatch):
    """``router.hedge`` raise: the hedge launch is abandoned — hedging is
    an optimization — and the primary leg still completes alone."""
    from lmrs_tpu.serving.router import RouterEngine

    slow = _mock_server(latency_s=0.5)
    fast = _mock_server()
    router = RouterEngine([f"127.0.0.1:{slow.port}",
                           f"127.0.0.1:{fast.port}"])
    try:
        monkeypatch.setenv("LMRS_HEDGE_MS", "100")
        with faults.injected(FaultPlan(faults=[
                {"site": "router.hedge", "at": [1], "max_fires": 1}])):
            res = router.generate_batch([_req(0)])[0]
        assert res.error is None
        assert router._hedges == 0 and router._hedge_wins == 0
    finally:
        router.shutdown()
        slow.shutdown()
        fast.shutdown()


def test_hedge_keeps_failover_on_fast_primary_failure(monkeypatch):
    """Arming LMRS_HEDGE_MS must never trade away availability: a
    primary that fails FAST (dead port, before the hedge delay) still
    gets the sibling attempt — as a plain failover, not a hedge (no
    hedge counters) — matching the un-hedged targets[:2] contract."""
    from lmrs_tpu.serving.router import RouterEngine

    good = _mock_server()
    with socket.socket() as s:  # a port nobody listens on
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
    router = RouterEngine([f"127.0.0.1:{dead_port}",
                           f"127.0.0.1:{good.port}"])
    try:
        monkeypatch.setenv("LMRS_HEDGE_MS", "500")
        res = router.generate_batch(
            [_req(0, prompt="failover under hedging")])[0]
        assert res.error is None and res.finish_reason == "stop"
        assert router._hedges == 0 and router._hedge_wins == 0
    finally:
        router.shutdown()
        good.shutdown()


def test_hedge_error_results_do_not_feed_breaker(monkeypatch):
    """_one_colocated parity: a backend-ANSWERED error result (the host
    served the request; the request itself failed) must not count toward
    the circuit breaker under hedging — otherwise a client sending
    deterministically-bad requests would evict healthy hosts."""
    from lmrs_tpu.serving.router import RouterEngine
    from lmrs_tpu.serving.server import EngineHTTPServer

    monkeypatch.setenv("LMRS_BREAKER_FAILURES", "2")
    monkeypatch.setenv("LMRS_HEDGE_MS", "50")
    srvs = [EngineHTTPServer(MockEngine(fail_pattern="boomtrigger"),
                             port=0, batch_window_s=0.01)
            for _ in range(2)]
    for s in srvs:
        s.start_background()
    router = RouterEngine([f"127.0.0.1:{s.port}" for s in srvs])
    try:
        for i in range(3):
            res = router.generate_batch(
                [_req(i, prompt="boomtrigger request")])[0]
            assert res.finish_reason == "error"
        for h in router.hosts:
            assert h.breaker_state == "closed" and h.healthy, h.netloc
    finally:
        router.shutdown()
        for s in srvs:
            s.shutdown()


def test_hedge_off_by_default(monkeypatch):
    """LMRS_HEDGE_MS unset: no hedging path runs at all (the kill-switch
    arm of the acceptance A/B)."""
    monkeypatch.delenv("LMRS_HEDGE_MS", raising=False)
    from lmrs_tpu.serving.router import RouterEngine

    srv = _mock_server(latency_s=0.3)
    router = RouterEngine([f"127.0.0.1:{srv.port}"])
    try:
        res = router.generate_batch([_req(0)])[0]
        assert res.error is None
        assert router._hedges == 0
    finally:
        router.shutdown()
        srv.shutdown()


# --------------------------------------------------------- supervised restart


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(method: str, url: str, body: dict | None = None,
          timeout: float = 30.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"Content-Type":
                                          "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read() or b"{}")


def test_supervised_sigkill_respawn_resumes_job_token_identical(tmp_path):
    """Acceptance scenario, layer 4: ``lmrs-serve --supervise`` runs the
    engine in a child process; SIGKILLing the child mid-map makes the
    supervisor respawn it, the replacement's startup recovery resumes the
    job from the WAL, and the final summary is token-identical to an
    uninterrupted run of the same (transcript, params)."""
    from lmrs_tpu.jobs import journal as jl
    from lmrs_tpu.serving.server import EngineHTTPServer

    transcript = jw.job_transcript(n=120)
    params = {"max_tokens_per_chunk": 700}  # small chunks: multi-chunk map
    # uninterrupted reference over the same HTTP config surface (a plain
    # in-process server with the cli's default PipelineConfig)
    ref = EngineHTTPServer(MockEngine(seed=0), port=0,
                           batch_window_s=0.01,
                           jobs_dir=str(tmp_path / "ref"))
    ref.start_background()
    try:
        base = f"http://{ref.host}:{ref.port}"
        _status, doc = _http("POST", f"{base}/v1/jobs",
                             {"transcript": transcript, "params": params})
        jid = doc["id"]
        want = _poll_job(base, jid)
    finally:
        ref.shutdown()
    assert want["status"] == "done"
    assert want["progress"]["num_chunks"] >= 3

    jobs_dir = tmp_path / "jobs"
    jobs_dir.mkdir()
    pidfile = tmp_path / "child.pid"
    port = _free_port()
    env = child_env(
        JAX_PLATFORMS="cpu",
        LMRS_SUPERVISE_PIDFILE=str(pidfile),
        LMRS_SUPERVISE_POLL_S="0.3",
        LMRS_SUPERVISE_BACKOFF_S="0.1",
        # pace the journal so the SIGKILL window mid-map is wide and
        # machine-speed independent (stalls never change what is written)
        LMRS_FAULT_PLAN=json.dumps({"faults": [
            {"site": "journal.append", "every": 1,
             "action": "stall", "stall_s": 0.3}]}))
    sup = subprocess.Popen(
        [sys.executable, "-m", "lmrs_tpu.serving.cli", "--supervise",
         "--backend", "mock", "--port", str(port),
         "--jobs-dir", str(jobs_dir), "-q"],
        env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)
    base = f"http://127.0.0.1:{port}"
    try:
        _wait_healthy(base, sup)
        pid1 = int(pidfile.read_text())
        _status, doc = _http("POST", f"{base}/v1/jobs",
                             {"transcript": transcript, "params": params})
        jid2 = doc["id"]
        wal = jobs_dir / f"{jid2}.wal"
        _wait_for_wal(wal, "chunk_done", 2)
        os.kill(pid1, signal.SIGKILL)  # kill the CHILD, not the supervisor
        # the supervisor notices and respawns: new child pid, healthz back
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                if (pidfile.exists()
                        and int(pidfile.read_text() or 0) != pid1
                        and _http("GET", f"{base}/healthz",
                                  timeout=2)[0] == 200):
                    break
            except (OSError, ValueError):
                pass
            time.sleep(0.2)
        else:
            raise TimeoutError("supervisor never respawned the child")
        state = jl.rebuild_state(jl.replay(wal)[0])
        assert state["done"] is None, "kill landed after completion"
        final = _poll_job(base, jid2)
        assert final["status"] == "done"
        assert final["recovered"] is True
        assert final["progress"]["num_resumed_chunks"] >= 2
        assert final["result"]["summary"] == want["result"]["summary"]
    finally:
        sup.terminate()
        try:
            sup.wait(timeout=30)
        except subprocess.TimeoutExpired:
            sup.kill()
            sup.wait(timeout=10)


def _wait_healthy(base: str, proc, deadline_s: float = 90.0) -> None:
    t0 = time.time()
    while time.time() - t0 < deadline_s:
        if proc.poll() is not None:
            raise RuntimeError("supervisor died: "
                               + proc.stderr.read().decode()[-2000:])
        try:
            if _http("GET", f"{base}/healthz", timeout=2)[0] == 200:
                return
        except OSError:
            pass
        time.sleep(0.1)
    raise TimeoutError(f"{base} never became healthy")


def _wait_for_wal(wal, rec_type: str, n: int,
                  deadline_s: float = 120.0) -> None:
    from lmrs_tpu.jobs import journal as jl

    t0 = time.time()
    while time.time() - t0 < deadline_s:
        if wal.exists():
            recs, _ = jl.replay(wal)
            if sum(1 for r in recs if r.get("type") == rec_type) >= n:
                return
        time.sleep(0.05)
    raise TimeoutError(f"never saw {n} {rec_type} record(s) in {wal}")


def _poll_job(base: str, jid: str, deadline_s: float = 120.0) -> dict:
    t0 = time.time()
    while time.time() - t0 < deadline_s:
        _status, doc = _http("GET", f"{base}/v1/jobs/{jid}")
        if doc.get("status") in ("done", "failed", "degraded",
                                 "cancelled"):
            return doc
        time.sleep(0.2)
    raise TimeoutError(f"job {jid} never finished")


def test_supervisor_wedged_healthz_is_503(monkeypatch, tmp_path):
    """The wedge signature the supervisor kills on: a server whose engine
    reports wedged answers /healthz with 503 + ``"wedged": true``."""
    from lmrs_tpu.serving.server import EngineHTTPServer

    class WedgedEngine(MockEngine):
        def wedged(self) -> bool:
            return True

    srv = EngineHTTPServer(WedgedEngine(), port=0, batch_window_s=0.01)
    srv.start_background()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _http("GET", f"http://{srv.host}:{srv.port}/healthz")
        assert ei.value.code == 503
        doc = json.loads(ei.value.read())
        assert doc["wedged"] is True
        from lmrs_tpu.serving.supervisor import Supervisor

        sup = Supervisor(["--backend", "mock"], host=srv.host,
                         port=srv.port)
        healthy, wedged = sup._poll_health()
        assert (healthy, wedged) == (False, True)
    finally:
        srv.shutdown()
