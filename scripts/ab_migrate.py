"""Cross-host KV migration A/B over an in-process mock fleet (ISSUE 20
acceptance).

Two arms over the SAME traffic shape against two MockEngine-backed HTTP
workers behind a RouterEngine — the deviceless stand-in for a drain on a
live fleet:

* a WARM phase sends preamble-sharing map requests straight at host A,
  building the warm radix entries its /healthz summary advertises;
* a DRAIN takes host A out of the dispatch order; with migration armed
  the router moves A's warm page sets to host B over the /v1/kv wire
  (export ticket -> pull-import -> ack) before A is force-removed;
* a RESUME phase replays the same preamble traffic through the router —
  now served entirely by host B.

The arms differ ONLY by ``LMRS_KV_MIGRATE`` at construction time:

* ``migrate_on``: B's resume preamble queries hit the MIGRATED entries —
  the fabric re-serves the prefill tokens host A already paid for;
* ``migrate_off``: the /v1/kv surface answers 501, the router attempts
  no moves, and B cold-prefills the preamble from scratch (the byte-
  parity arm: no ``kv_migrate`` key appears in any metrics document).

The headline metric is ``migrate.tokens_from_fabric_ratio``: of the
preamble tokens B re-served during the resume, the fraction that came
off the fabric (reused from imported page sets) rather than cold
re-prefill.

PASS gate (all must hold):
  1. migrate_on fabric ratio >= 0.5 (the ISSUE 20 floor);
  2. migrate_off fabric ratio == 0 with zero imports AND no kv_migrate
     key in either host's metrics (the kill switch restores today's
     metric surface byte-for-byte);
  3. resume outputs token-identical across arms (migration moves KV,
     never changes generation);
  4. ledger conservation on every host (tenant rollups sum to totals,
     nothing live after the traffic drains) and >= 1 router move on the
     on arm, 0 on the off arm.

CPU-only, ~10 s.  Usage:
    JAX_PLATFORMS=cpu python scripts/ab_migrate.py [--artifact migrate.json]
"""

from __future__ import annotations

import _pathfix  # noqa: F401

import argparse
import json
import sys
import time

N_WARM = 6
N_RESUME = 6
_PREAMBLE = ("You are summarizing one section of a long transcript. "
             "Keep every fact, decision, owner, date, and number exactly "
             "as stated; never invent content; answer with the summary "
             "only and preserve the section ordering. ")


def _reqs(base_rid: int, n: int):
    from lmrs_tpu.engine.api import GenerationRequest

    return [GenerationRequest(
        prompt=_PREAMBLE + f"Chunk {i}: milestone {i} closed on time.",
        request_id=base_rid + i, temperature=0.0, max_new_tokens=24,
        cache_prefix=len(_PREAMBLE)) for i in range(n)]


def run_arm(migrate_on: bool) -> dict:
    from lmrs_tpu.engine.mock import MockEngine
    from lmrs_tpu.serving.router import RouterEngine
    from lmrs_tpu.serving.server import EngineHTTPServer

    engines = [MockEngine(seed=0) for _ in range(2)]
    servers = [EngineHTTPServer(e, port=0, batch_window_s=0.01)
               for e in engines]
    for s in servers:
        s.start_background()
    hosts = [f"127.0.0.1:{s.port}" for s in servers]
    router = RouterEngine(hosts)
    if router.kv_migrate and not migrate_on:
        # the off arm flips the SAME gate LMRS_KV_MIGRATE=0 sets at
        # construction, without mutating process-wide environment (the
        # ab_fairness constructor-mirror convention)
        router.kv_migrate = False
        for s in servers:
            s.kv_migrate = False
    elif migrate_on and not router.kv_migrate:
        raise SystemExit("ab_migrate: LMRS_KV_MIGRATE=0 in the "
                         "environment — the on arm cannot arm; unset "
                         "it and re-run")

    try:
        # warm host A directly: its radix picks up the shared preamble
        for r in engines[0].generate_batch(_reqs(0, N_WARM)):
            assert r.error is None, r.error

        # drain A; armed, the router migrates A's page sets to B first
        assert router.drain_host(hosts[0])
        deadline = time.time() + 20.0
        while (router.migrations_pending(hosts[0])
               and time.time() < deadline):
            time.sleep(0.05)
        pending = router.migrations_pending(hosts[0])
        assert router.remove_host(hosts[0], force=True)

        # resume through the router: only B is left to serve
        before = engines[1].engine_metrics()
        b_pc0 = before.get("prefix_cache") or {}
        resume = router.generate_batch(_reqs(100, N_RESUME))
        errors = [r.error for r in resume if r.error is not None]
        after = engines[1].engine_metrics()
        pc = after.get("prefix_cache") or {}
        mig = after.get("kv_migrate") or {}

        # fabric ratio: of the preamble tokens B re-served on resume,
        # the fraction reused from IMPORTED entries.  B held no warm
        # entries of its own before the drain, so with migration armed
        # every resume reuse is fabric-served; disarmed, imports are 0
        # and the ratio is 0 by definition (self-warmed reuse is local
        # re-prefill savings, not fabric traffic).
        queries = pc.get("queries", 0) - b_pc0.get("queries", 0)
        reused = pc.get("tokens_reused", 0) - b_pc0.get("tokens_reused", 0)
        imported = mig.get("tokens_imported", 0)
        if imported and queries:
            ratio = min(1.0, reused / (queries * imported))
        else:
            ratio = 0.0

        conserved, live = True, 0
        for e in engines:
            u = e.ledger.usage_report()
            tenant_sum = sum(r.get("device_seconds", 0.0)
                             for r in u["tenants"].values())
            if abs(tenant_sum
                   - u["totals"].get("device_seconds", 0.0)) > 1e-9:
                conserved = False
            live += int(u.get("live_requests", 0))
        rm = router.engine_metrics().get("kv_migrate") or {}
        return {
            "arm": "migrate_on" if migrate_on else "migrate_off",
            "errors": errors + (["migration still pending at removal"]
                                if pending else []),
            "resume_queries": queries,
            "resume_tokens_reused": reused,
            "tokens_imported": imported,
            "imports": mig.get("imports", 0),
            "router_moves": rm.get("moves", 0),
            "router_failures": rm.get("failures", 0),
            "tokens_from_fabric_ratio": round(ratio, 4),
            "kv_migrate_key_present": ("kv_migrate" in after
                                       or "kv_migrate" in before),
            "usage_conserved": conserved,
            "live_requests_after": live,
            "texts": {r.request_id: r.text for r in resume},
        }
    finally:
        router.shutdown()
        for s in servers:
            s.shutdown()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--artifact", default=None,
                    help="write the verdict and metrics as JSON here")
    args = ap.parse_args(argv)
    on = run_arm(migrate_on=True)
    off = run_arm(migrate_on=False)

    identical = on["texts"] == off["texts"]
    clean = (not on["errors"] and not off["errors"]
             and on["usage_conserved"] and off["usage_conserved"]
             and on["live_requests_after"] == 0
             and off["live_requests_after"] == 0)
    ok = (on["tokens_from_fabric_ratio"] >= 0.5
          and on["imports"] >= 1 and on["router_moves"] >= 1
          and off["tokens_from_fabric_ratio"] == 0.0
          and off["imports"] == 0 and off["router_moves"] == 0
          and not off["kv_migrate_key_present"]
          and identical and clean)
    detail = {
        "model": "mock-fleet",
        "hosts": 2,
        "warm_requests": N_WARM,
        "resume_requests": N_RESUME,
        "migrate": {
            "tokens_from_fabric_ratio": on["tokens_from_fabric_ratio"],
            "tokens_imported": on["tokens_imported"],
            "router_moves": on["router_moves"],
        },
    }
    report = {
        "object": "ab_migrate",
        "arms": [{k: v for k, v in arm.items() if k != "texts"}
                 for arm in (on, off)],
        "outputs_token_identical": identical,
        "detail": detail,
        "status": "PASS" if ok else "FAIL",
    }
    print(json.dumps(report, indent=2))
    if args.artifact:
        # rc + parsed.detail metrics
        with open(args.artifact, "w", encoding="utf-8") as f:
            json.dump({"rc": 0 if ok else 1, "ok": ok,
                       "parsed": {"detail": detail}}, f, indent=2)
            f.write("\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
