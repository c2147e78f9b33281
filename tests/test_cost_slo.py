"""Request-cost ledger + fleet SLO engine (ISSUE 15).

The tier-1 ``cost-slo`` gate: ledger conservation must hold as a
scheduler-audit invariant under mixed/spec/prefix-cache and chaos arms,
greedy outputs must be byte-identical with ``LMRS_COST_LEDGER`` on vs
off, the tenant label must propagate router → backend → journal
recovery, the SLO state machine must transition (and flap-damp)
deterministically, SLO-aware routing must shift traffic off a degraded
host without changing outputs, and fleet ``/v1/usage`` rollups must sum
exactly.
"""

from __future__ import annotations

import http.client
import json
import time

import pytest

from lmrs_tpu.config import EngineConfig, ModelConfig
from lmrs_tpu.engine.api import GenerationRequest, GenerationResult
from lmrs_tpu.engine.mock import MockEngine
from lmrs_tpu.obs.ledger import CostLedger, merge_usage
from lmrs_tpu.obs.slo import SLOEngine, SLOSpec


def tiny_model():
    return ModelConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                       n_kv_heads=2, hidden_dim=128, max_seq_len=256,
                       dtype="float32")


def _cfg(**kw) -> EngineConfig:
    base = dict(backend="jax", scheduler="continuous", max_tokens=16,
                max_batch_slots=2, seed=0, decode_block=3,
                prefill_chunk=64, retry_delay=0.0)
    base.update(kw)
    return EngineConfig(**base)


def _reqs(n: int = 4) -> list[GenerationRequest]:
    pre = "shared ledger preamble alpha beta "
    return [GenerationRequest(
        prompt=(pre if i % 2 else "") + f"request {i} "
        + "lorem ipsum dolor sit amet " * (1 + 4 * (i % 2)),
        request_id=i, temperature=0.0, max_new_tokens=10 + i,
        tenant=f"t{i % 2}") for i in range(n)]


# ------------------------------------------------------------ ledger unit


def test_ledger_apportionment_conserves_exactly():
    led = CostLedger(enabled=True)
    reqs = [GenerationRequest(prompt="x", request_id=i, tenant="a")
            for i in range(3)]
    # odd wall + odd weights: remainder correction must keep per-dispatch
    # sums exact
    led.note_step(0.123456789,
                  decode_rows=[(reqs[0], 3, 4), (reqs[1], 7, 2)],
                  prefill_rows=[(reqs[2], 11, 5.0)],
                  decode_cost_s=0.3, prefill_cost_s=0.7)
    led.note_step(0.001, decode_rows=[(reqs[0], 0, 1), (reqs[1], 0, 1)])
    assert led.audit() == []
    for r in reqs:
        led.finish(r, GenerationResult(request_id=r.request_id,
                                       completion_tokens=2,
                                       prompt_tokens=5))
    assert led.audit() == []
    doc = led.usage_report()
    assert doc["tenants"]["a"]["requests"] == 3
    assert abs(doc["totals"]["device_seconds"]
               - doc["tenants"]["a"]["device_seconds"]) < 1e-12


def test_kv_page_seconds_bill_the_full_dispatch_wall():
    """Pages are resident for the whole kernel launch: a fused mixed
    step whose roofline split hands most of the wall to prefill must
    still bill decode rows' pages x the FULL dispatch wall (the
    module-doc / metrics-catalog definition)."""
    led = CostLedger(enabled=True)
    r0 = GenerationRequest(prompt="x", request_id=0, tenant="a")
    rp = GenerationRequest(prompt="y", request_id=1, tenant="a")
    led.note_step(0.1, decode_rows=[(r0, 1, 10)],
                  prefill_rows=[(rp, 64, 8.0)],
                  decode_cost_s=0.2, prefill_cost_s=0.8)
    assert led.audit() == []
    u = led.finish(r0, GenerationResult(request_id=0, completion_tokens=1,
                                        prompt_tokens=1))
    assert abs(u["kv_page_seconds"] - 10 * 0.1) < 1e-9
    assert u["decode_device_seconds"] < 0.1  # phase split still applies


def test_tenant_cardinality_cap_folds_into_overflow(monkeypatch):
    """Past LMRS_COST_TENANTS_MAX distinct labels the rollups fold into
    the 'other' bucket — bounded memory under job/session-minted
    tenants, with conservation (and the tenants->totals sum) intact."""
    monkeypatch.setenv("LMRS_COST_TENANTS_MAX", "2")
    led = CostLedger(enabled=True)
    for i, tenant in enumerate(("a", "b", "c", "d")):
        r = GenerationRequest(prompt="x", request_id=i, tenant=tenant)
        led.note_step(0.25, decode_rows=[(r, 2, 1)])
        led.finish(r, GenerationResult(request_id=i, completion_tokens=2,
                                       prompt_tokens=1))
    assert led.audit() == []
    doc = led.usage_report()
    assert set(doc["tenants"]) == {"a", "b", "other"}
    assert doc["tenants"]["other"]["requests"] == 2
    assert doc["totals"]["requests"] == 4
    assert abs(doc["totals"]["device_seconds"] - 1.0) < 1e-9


def test_ledger_disabled_is_inert():
    led = CostLedger(enabled=False)
    r = GenerationRequest(prompt="x", request_id=1)
    led.note_step(1.0, decode_rows=[(r, 5, 1)])
    led.note_queue_wait(r, 1.0)
    assert led.finish(r, GenerationResult(request_id=1)) is None
    assert led.audit() == []
    assert led.usage_report()["enabled"] is False


def test_merge_usage_is_the_one_sum_rule():
    a, b = {}, {}
    u1 = {"prefill_device_seconds": 0.5, "decode_device_seconds": 1.5,
          "prompt_tokens": 10, "goodput_tokens": 4}
    u2 = {"prefill_device_seconds": 0.25, "decode_device_seconds": 0.25,
          "prompt_tokens": 3, "wasted_tokens": 2}
    merge_usage(a, u1)
    merge_usage(a, u2)
    merge_usage(b, merge_usage(dict(u1), u2))
    assert a["device_seconds"] == 2.5
    assert a["prompt_tokens"] == 13 and a["requests"] == 2


# --------------------------------------------------------- scheduler arms


@pytest.mark.parametrize("arm", ["plain", "mixed", "spec", "no_prefix"])
def test_ledger_conservation_scheduler_arms(arm):
    """Conservation gated in scheduler.audit() across the dispatch-path
    matrix: plain alternating, mixed fused steps, speculative blocks,
    prefix cache off.  Every arm must also actually bill someone."""
    from lmrs_tpu.engine.jax_engine import JaxEngine

    kw = dict(mixed_batch=arm == "mixed",
              prefix_cache=arm != "no_prefix",
              speculate_k=3 if arm == "spec" else 0)
    eng = JaxEngine(_cfg(**kw), tiny_model())
    out = eng.generate_batch(_reqs())
    sched = eng._scheduler
    assert sched.audit() == []
    assert all(r.error is None for r in out)
    assert all(r.usage is not None for r in out)
    doc = sched.usage_report()
    assert doc["tenants"]["t0"]["requests"] == 2
    assert doc["totals"]["device_seconds"] > 0
    # no orphaned entries: every finished request left the live table —
    # a dispatch note landing AFTER its row's finish would re-create the
    # entry and leak one per completed request
    assert doc["live_requests"] == 0
    # prompt/generated token attribution is exact per result
    for r in out:
        assert r.usage["prompt_tokens"] == r.prompt_tokens
        assert r.usage["generated_tokens"] == r.completion_tokens
    # a second batch keeps conserving (rollup + live entry interplay)
    eng.generate_batch(_reqs())
    assert sched.audit() == []
    eng.shutdown()


def test_ledger_conservation_under_chaos():
    """Faults firing mid-run (OutOfPages + scheduler.step) must leave the
    conservation invariant intact — recovery may drop work, never bill
    it twice."""
    from lmrs_tpu.engine.executor import MapExecutor
    from lmrs_tpu.engine.jax_engine import JaxEngine
    from lmrs_tpu.testing import faults
    from lmrs_tpu.testing.faults import FaultPlan

    eng = JaxEngine(_cfg(mixed_batch=True), tiny_model())
    ex = MapExecutor(eng, EngineConfig(retry_attempts=3, retry_delay=0.0))
    with faults.injected(FaultPlan(seed=91, faults=[
            {"site": "kv_cache.allocate", "p": 0.2, "max_fires": 3},
            {"site": "scheduler.step", "at": [4], "max_fires": 1}])):
        out = ex.run_requests(_reqs())
    sched = eng._scheduler
    assert sched.audit() == []
    assert all(r.finish_reason for r in out)
    eng.shutdown()


def test_cost_ledger_kill_switch_token_identical(monkeypatch):
    """LMRS_COST_LEDGER=0: outputs byte-identical, no usage blocks, no
    ledger state — the switch is inert on everything but the bill."""
    from lmrs_tpu.engine.jax_engine import JaxEngine

    def run():
        eng = JaxEngine(_cfg(mixed_batch=True), tiny_model())
        out = eng.generate_batch(_reqs())
        sched = eng._scheduler
        assert sched.audit() == []
        texts = [(r.text, r.finish_reason, r.completion_tokens)
                 for r in out]
        usages = [r.usage for r in out]
        rep = sched.metrics_report()
        eng.shutdown()
        return texts, usages, rep

    monkeypatch.setenv("LMRS_COST_LEDGER", "0")
    texts_off, usages_off, rep_off = run()
    assert all(u is None for u in usages_off)
    assert rep_off["cost"] == {"enabled": False}
    monkeypatch.setenv("LMRS_COST_LEDGER", "1")
    texts_on, usages_on, rep_on = run()
    assert all(u is not None for u in usages_on)
    assert rep_on["cost"]["enabled"] is True
    assert texts_on == texts_off


# --------------------------------------------------------- SLO unit tests


def _slo(clock, **kw):
    # hold_s > slow_s so the damping window is observable: samples age
    # out of both burn windows while the dwell clock still holds
    base = dict(enabled=True, fast_s=10.0, slow_s=20.0, hold_s=30.0,
                min_events=2, clock=clock,
                specs=(SLOSpec("error_rate", "rate", 0.1),
                       SLOSpec("ttft_p95_ms", "latency_p95", 100.0)))
    base.update(kw)
    return SLOEngine(**base)


class _Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def test_slo_state_machine_transitions_and_damping():
    clk = _Clock()
    slo = _slo(clk)
    # healthy traffic: ok
    for _ in range(4):
        slo.observe_ttft(0.01)
        slo.note_result("stop", tokens=10)
    assert slo.report()["state"] == "ok"
    # latency breach in both windows -> warn (burn 1.5)
    clk.t += 1
    for _ in range(8):
        slo.observe_ttft(0.150)
    assert slo.report()["state"] == "warn"
    # heavy breach -> critical (upgrade is immediate)
    for _ in range(20):
        slo.observe_ttft(0.500)
    assert slo.report()["state"] == "critical"
    # samples age out of the windows, but damping HOLDS the state until
    # hold_s elapses — no strobing back to ok on the first clean second
    clk.t += 25  # every sample left both windows, dwell (30s) still held
    doc = slo.report()
    assert doc["raw_state"] == "ok"
    assert doc["state"] == "critical", "downgrade must wait out hold_s"
    clk.t += 11  # dwell elapsed: the damped downgrade lands
    assert slo.report()["state"] == "ok"


def test_slo_rate_spec_min_volume_guard():
    clk = _Clock()
    slo = _slo(clk, min_events=4)
    slo.note_result("error", error="boom")  # 1/1 = 100% error rate...
    assert slo.report()["state"] == "ok"  # ...but below min volume
    for _ in range(5):
        slo.note_result("error", error="boom")
    assert slo.report()["state"] == "critical"


def test_slo_latency_specs_guard_volume_and_cold_outlier():
    """A lone cold-compile TTFT sample must not page: below min_events
    latency specs burn 0, and below 20 samples (where p95 == max) the
    single worst sample is dropped — while a host whose samples are ALL
    slow still breaches."""
    clk = _Clock()
    slo = _slo(clk)  # min_events=2, ttft target 100ms
    slo.observe_ttft(30.0)  # one 30s cold-compile sample
    assert slo.report()["state"] == "ok"  # below min volume
    for _ in range(3):
        slo.observe_ttft(0.01)
    # 4 samples: the cold outlier is dropped, healthy p95 remains
    assert slo.report()["state"] == "ok"
    for _ in range(19):
        slo.observe_ttft(0.500)  # genuinely degraded: every sample slow
    assert slo.report()["state"] == "critical"


def test_slo_critical_fires_postmortem(tmp_path, monkeypatch):
    monkeypatch.setenv("LMRS_POSTMORTEM_DIR", str(tmp_path))
    monkeypatch.setenv("LMRS_POSTMORTEM_MIN_S", "0")
    clk = _Clock()
    slo = _slo(clk, metrics_cb=lambda: {"x": 1})
    for _ in range(6):
        slo.note_result("error", error="boom")
    assert slo.report()["state"] == "critical"
    dumps = list(tmp_path.glob("postmortem-slo-*.json"))
    assert dumps, "critical transition must dump an 'slo' postmortem"
    from lmrs_tpu.obs import validate_postmortem_file

    doc = validate_postmortem_file(dumps[0])
    assert doc["reason"] == "slo"
    assert doc["extra"]["state"] == "critical"


def test_slo_disabled_pins_ok():
    slo = SLOEngine(enabled=False)
    slo.note_result("error", error="boom")
    assert slo.report() == {"enabled": False, "state": "ok", "specs": {}}


def test_slo_spec_env_overrides(monkeypatch):
    from lmrs_tpu.obs.slo import specs_from_env

    monkeypatch.setenv("LMRS_SLO_SPEC",
                       '{"ttft_p95_ms": 55, "bogus": 1, "error_rate": "x"}')
    specs = {s.name: s for s in specs_from_env()}
    assert specs["ttft_p95_ms"].target == 55.0
    assert specs["error_rate"].target == 0.05  # bad value kept default


# ------------------------------------------------- serving / fleet flows


def _post(port, body, headers=None, path="/v1/chat/completions"):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    c.request("POST", path, json.dumps(body),
              {"Content-Type": "application/json", **(headers or {})})
    r = c.getresponse()
    out = json.loads(r.read())
    c.close()
    return r.status, out


def _get(port, path):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    c.request("GET", path)
    r = c.getresponse()
    out = json.loads(r.read())
    c.close()
    return r.status, out


def test_tenant_propagates_router_to_backends_and_usage_sums():
    """X-LMRS-Tenant minted at the front server rides router forwards to
    the backends' ledgers; fleet /v1/usage per-tenant rollups sum to the
    router-reported totals exactly."""
    from lmrs_tpu.serving.router import RouterEngine
    from lmrs_tpu.serving.server import EngineHTTPServer

    servers = [EngineHTTPServer(MockEngine(seed=0), port=0)
               for _ in range(2)]
    for s in servers:
        s.start_background()
    router = RouterEngine([f"127.0.0.1:{s.port}" for s in servers],
                          timeout_s=30.0)
    front = EngineHTTPServer(router, port=0)
    front.start_background()
    try:
        for i in range(6):
            st, out = _post(front.port, {
                "messages": [{"role": "user",
                              "content": f"summarize item {i} with "
                                         "plenty of deterministic words "
                                         "in the transcript body."}],
                "max_tokens": 32},
                headers={"X-LMRS-Tenant": f"team{i % 2}"})
            assert st == 200
            cost = out["usage"]["cost"]
            assert cost["tenant"] == f"team{i % 2}"
            assert cost["device_seconds"] > 0
        st, fleet = _get(front.port, "/v1/usage")
        assert st == 200 and fleet["enabled"] and fleet.get("fleet")
        assert set(fleet["tenants"]) == {"team0", "team1"}
        assert sum(r["requests"] for r in fleet["tenants"].values()) == 6
        tenant_dev = sum(r["device_seconds"]
                         for r in fleet["tenants"].values())
        assert abs(tenant_dev - fleet["totals"]["device_seconds"]) < 1e-9
        # host pages sum to the fleet page too
        host_dev = 0.0
        for s in servers:
            st, hu = _get(s.port, "/v1/usage")
            assert st == 200
            host_dev += hu["totals"].get("device_seconds", 0.0)
        assert abs(host_dev - fleet["totals"]["device_seconds"]) < 1e-9
    finally:
        for s in servers + [front]:
            s.shutdown()
        router.shutdown()


def test_tenant_rides_disagg_handoff_legs():
    """Both disaggregation legs bill to the SAME tenant: the payload
    carries the label across the pod boundary (like the trace id)."""
    from lmrs_tpu.serving.router import RouterEngine
    from lmrs_tpu.serving.server import EngineHTTPServer

    pre = EngineHTTPServer(MockEngine(seed=0), port=0, role="prefill")
    dec = EngineHTTPServer(MockEngine(seed=0), port=0, role="decode")
    for s in (pre, dec):
        s.start_background()
    router = RouterEngine([], timeout_s=30.0,
                          prefill_hosts=[f"127.0.0.1:{pre.port}"],
                          decode_hosts=[f"127.0.0.1:{dec.port}"])
    front = EngineHTTPServer(router, port=0)
    front.start_background()
    try:
        st, out = _post(front.port, {
            "messages": [{"role": "user",
                          "content": "a transcript body long enough to "
                                     "hand off between the two pods "
                                     "with several sentences in it."}],
            "max_tokens": 48}, headers={"X-LMRS-Tenant": "acme"})
        assert st == 200, out
        st, du = _get(dec.port, "/v1/usage")
        assert "acme" in du["tenants"], du
    finally:
        for s in (pre, dec, front):
            s.shutdown()
        router.shutdown()


def test_job_tenant_survives_journal_recovery(tmp_path):
    """The tenant persists in the job journal header: a manager restart
    keeps billing the resumed job to the original tenant."""
    from lmrs_tpu.jobs.manager import JobManager

    tx = {"segments": [{"speaker": "A", "start_time": 0.0,
                        "end_time": 30.0,
                        "text": "a meeting about ledger recovery with "
                                "enough words to chunk properly."}]}
    m1 = JobManager(MockEngine(seed=0), tmp_path, start_worker=False)
    job = m1.submit(tx, tenant="acme")
    assert job.tenant == "acme"
    m1.run_job(job)
    assert job.status in ("done", "degraded")
    assert job.usage.get("requests", 0) > 0
    assert m1.status_doc(job)["usage"]["requests"] > 0
    m1.shutdown()
    m2 = JobManager(MockEngine(seed=0), tmp_path, start_worker=False)
    m2.recover()
    j2 = m2.get(job.job_id)
    assert j2 is not None and j2.tenant == "acme"
    assert m2.status_doc(j2)["tenant"] == "acme"
    m2.shutdown()
    # anonymous submits bill to the job's own identity
    m3 = JobManager(MockEngine(seed=0), tmp_path / "b", start_worker=False)
    j3 = m3.submit(tx)
    assert j3.tenant == f"job:{j3.job_id[:24]}"
    m3.shutdown()


def test_session_tenant_and_usage_rollup(tmp_path):
    from lmrs_tpu.live import SessionManager

    mgr = SessionManager(MockEngine(seed=0), tmp_path)
    s = mgr.create(tenant="acme")
    mgr.append(s.session_id, [{"speaker": "A", "start": 0.0, "end": 60.0,
                               "text": "live content to summarize with "
                                       "plenty of words in it now."}])
    mgr.refresh(s.session_id)
    doc = mgr.status_doc(s)
    assert doc["tenant"] == "acme"
    assert doc["usage"]["requests"] > 0
    mgr.shutdown()


def test_usage_501_without_ledger_hook():
    from lmrs_tpu.serving.server import EngineHTTPServer

    class Bare:
        def generate_batch(self, reqs, on_result=None, on_tokens=None):
            return [GenerationResult(request_id=r.request_id)
                    for r in reqs]

        def shutdown(self):
            pass

        def engine_metrics(self):
            return {}

    srv = EngineHTTPServer(Bare(), port=0)
    srv.start_background()
    try:
        st, out = _get(srv.port, "/v1/usage")
        assert st == 501
    finally:
        srv.shutdown()


def test_wire_cost_block_absent_with_kill_switch(monkeypatch):
    """LMRS_COST_LEDGER=0 end-to-end: the wire usage dict is exactly the
    pre-ledger shape and the text is identical."""
    from lmrs_tpu.serving.server import EngineHTTPServer

    body = {"messages": [{"role": "user",
                          "content": "kill switch wire parity check with "
                                     "some deterministic content."}],
            "max_tokens": 24}

    def run():
        srv = EngineHTTPServer(MockEngine(seed=0), port=0)
        srv.start_background()
        try:
            return _post(srv.port, body)
        finally:
            srv.shutdown()

    monkeypatch.setenv("LMRS_COST_LEDGER", "1")
    st_on, on = run()
    monkeypatch.setenv("LMRS_COST_LEDGER", "0")
    st_off, off = run()
    assert st_on == st_off == 200
    assert "cost" in on["usage"] and "cost" not in off["usage"]
    assert on["choices"][0]["message"] == off["choices"][0]["message"]
    assert set(off["usage"]) == {"prompt_tokens", "completion_tokens",
                                 "total_tokens"}


# --------------------------------------------------- SLO-aware routing A/B


def _slo_fleet(n=3, degraded_latency=0.08):
    from lmrs_tpu.serving.server import EngineHTTPServer

    servers = []
    for i in range(n):
        eng = MockEngine(seed=0,
                         latency_s=degraded_latency if i == 0 else 0.0)
        eng.slo = SLOEngine(
            enabled=True, fast_s=30.0, slow_s=30.0, hold_s=5.0,
            specs=(SLOSpec("ttft_p95_ms", "latency_p95", 50.0),))
        servers.append(EngineHTTPServer(eng, port=0))
    for s in servers:
        s.start_background()
    return servers


def _run_slo_arm(servers, routed: bool):
    from lmrs_tpu.serving.router import RouterEngine

    router = RouterEngine([f"127.0.0.1:{s.port}" for s in servers],
                          timeout_s=30.0, prefix_route=False,
                          slo_route=routed, summary_ttl_s=0.4)
    # warm SLO windows past the latency min-sample guard (min_events
    # per host) + the router's summary cache
    for k in range(4 * len(servers)):
        router.generate_batch([GenerationRequest(
            prompt=f"warmup {k}", request_id=900 + k, temperature=0.0,
            max_new_tokens=8)])
        time.sleep(0.04)
    time.sleep(0.5)
    served0 = {h.netloc: h.served for h in router.hosts}
    texts = {}
    for i in range(18):
        req = GenerationRequest(
            prompt=f"measured request {i} deterministic body words.",
            request_id=i, temperature=0.0, max_new_tokens=24)
        res = router.generate_batch([req])[0]
        assert res.error is None
        texts[req.prompt] = res.text
        time.sleep(0.01)
    served = {h.netloc: h.served - served0[h.netloc]
              for h in router.hosts}
    degraded = router.hosts[0].netloc
    share = served[degraded] / max(sum(served.values()), 1)
    router.shutdown()
    return share, texts


def test_slo_routing_sheds_degraded_host_token_identical():
    """The ISSUE 15 acceptance A/B: one host forced into warn by its own
    latency samples loses traffic share under LMRS_SLO_ROUTE while
    aggregate outputs stay token-identical."""
    servers = _slo_fleet()
    try:
        share_off, texts_off = _run_slo_arm(servers, routed=False)
    finally:
        for s in servers:
            s.shutdown()
    servers = _slo_fleet()
    try:
        share_on, texts_on = _run_slo_arm(servers, routed=True)
    finally:
        for s in servers:
            s.shutdown()
    assert share_on < share_off, (share_on, share_off)
    assert texts_on == texts_off


def test_slo_route_kill_switch_keeps_ordering(monkeypatch):
    """slo_route=False never consults SLO state: _targets ordering is
    byte-identical to the pre-SLO router even with a critical host."""
    from lmrs_tpu.serving.router import RouterEngine

    router = RouterEngine(["h1:1", "h2:2"], timeout_s=1.0,
                          slo_route=False)
    with router._summary_lock:
        router._summaries["h1:1"] = {"at": router._clock(), "map": {},
                                     "slo": "critical"}
    order = [h.netloc for h in router._targets(0)]
    assert order == ["h1:1", "h2:2"]  # critical host NOT demoted
    router.slo_route = True
    order = [h.netloc for h in router._targets(0)]
    assert order == ["h2:2", "h1:1"]
    assert router._slo_penalized == 1
    router.shutdown()
