"""Multi-tenant fairness A/B over a slot-limited mock fleet (ISSUE 17
acceptance).

Two arms over the SAME traffic shape against N MockEngine hosts, each
with ONE admission slot (``slots=1``) and real per-request service
latency — the deviceless stand-in for a saturated TPU pod, serving the
same admission-gate surface the jax scheduler's admit loop enforces:

* a NOISY tenant floods ``batch``-class requests from many concurrent
  client threads (round-robin over the fleet, one outstanding request
  per thread — a map-wave fan-out's signature), keeping every host's
  admission queue saturated for the whole measured window;
* a QUIET tenant sends paced ``interactive`` requests and measures its
  client-side completion wall (TTFT for the mock: the whole completion
  emits at first-token time).

The arms differ ONLY by the engines' ``qos`` switch (the constructor
mirror of the ``LMRS_QOS`` master knob, so the harness never mutates
process-wide environment):

* ``qos_on``: each host's admission gate orders waiting tickets by the
  fair-share policy (fleet/qos.py) — the quiet tenant's interactive
  requests jump the flooded queue, so its TTFT p95 holds within the
  SLO target;
* ``qos_off``: byte-for-byte FIFO admission — the quiet tenant queues
  behind the flood and its TTFT p95 breaches the target.

PASS gate (all must hold):
  1. quiet TTFT p95 <= target under qos_on;
  2. quiet TTFT p95 >  target under qos_off (the flood really contends —
     without this the fairness win would be vacuous);
  3. the quiet tenant's outputs are token-identical across arms (QoS
     reorders admission, never generation);
  4. ledger conservation on every host: per-tenant device-second rollups
     sum to the host totals exactly and ``live_requests == 0`` once the
     flood drains (nothing leaked through the admission gate).

Writes its verdict and metrics as a JSON artifact with ``--artifact``.

CPU-only, ~15 s.  Usage:
    JAX_PLATFORMS=cpu python scripts/ab_fairness.py [--artifact fairness.json]
"""

from __future__ import annotations

import _pathfix  # noqa: F401

import argparse
import itertools
import json
import sys
import threading
import time

N_HOSTS = 2
FLOOD_THREADS = 12
FLOOD_REQS_EACH = 8
QUIET_REQS = 10
QUIET_PACE_S = 0.15
LATENCY_S = 0.08          # per-request service time while holding the slot
TTFT_TARGET_MS = 300.0    # quiet SLO: flood FIFO wait is ~N_waiters * latency


def _p95(vals_ms: list[float]) -> float:
    vs = sorted(vals_ms)
    return vs[int(0.95 * (len(vs) - 1))] if vs else 0.0


def run_arm(qos_on: bool) -> dict:
    from lmrs_tpu.engine.api import GenerationRequest
    from lmrs_tpu.engine.mock import MockEngine

    engines = [MockEngine(seed=0, latency_s=LATENCY_S, slots=1, qos=qos_on)
               for _ in range(N_HOSTS)]
    if qos_on and any(e.qos is None for e in engines):
        # qos=True still defers to the master knob; an ambient LMRS_QOS=0
        # would silently turn the on-arm into a second FIFO arm
        raise SystemExit("ab_fairness: LMRS_QOS=0 in the environment — "
                         "the qos_on arm cannot arm; unset it and re-run")
    rr = itertools.count()
    rid = itertools.count()
    rid_lock = threading.Lock()

    def submit(prompt: str, tenant: str, klass: str):
        with rid_lock:
            i, r = next(rr), next(rid)
        req = GenerationRequest(prompt=prompt, request_id=r,
                                temperature=0.0, max_new_tokens=32,
                                tenant=tenant, qos_class=klass)
        res = engines[i % N_HOSTS].generate_batch([req])[0]
        assert res.error is None, res.error
        return res

    errors: list[str] = []

    def flood(k: int) -> None:
        try:
            for j in range(FLOOD_REQS_EACH):
                submit(f"bulk map chunk {k}-{j}: summarize this block of "
                       "deterministic mock content end to end.",
                       "noisy", "batch")
        except Exception as e:  # noqa: BLE001 - surfaced in the gate
            errors.append(f"flood {k}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=flood, args=(k,), daemon=True)
               for k in range(FLOOD_THREADS)]
    for t in threads:
        t.start()
    time.sleep(4 * LATENCY_S)  # let the gates saturate before measuring
    quiet_ms: list[float] = []
    quiet_texts: dict[str, str] = {}
    for i in range(QUIET_REQS):
        prompt = f"live session turn {i}: what changed since last time?"
        t0 = time.time()
        res = submit(prompt, "quiet", "interactive")
        quiet_ms.append((time.time() - t0) * 1e3)
        quiet_texts[prompt] = res.text
        time.sleep(QUIET_PACE_S)
    for t in threads:
        t.join(timeout=120.0)
    alive = sum(t.is_alive() for t in threads)

    # ledger conservation, per host: tenant rollups sum to totals and
    # nothing is still live once the flood drained
    conserved = True
    live = 0
    qos_tenants: set[str] = set()
    for e in engines:
        u = e.ledger.usage_report()
        tenant_sum = sum(r.get("device_seconds", 0.0)
                         for r in u["tenants"].values())
        if abs(tenant_sum - u["totals"].get("device_seconds", 0.0)) > 1e-9:
            conserved = False
        live += int(u.get("live_requests", 0))
        q = e.qos_report()
        if q.get("enabled"):
            qos_tenants |= set(q.get("tenants", {}))
    return {
        "arm": "qos_on" if qos_on else "qos_off",
        "quiet_ttft_p95_ms": round(_p95(quiet_ms), 1),
        "quiet_ttft_max_ms": round(max(quiet_ms), 1),
        "quiet_ttft_ms": [round(v, 1) for v in quiet_ms],
        "flood_errors": errors + ([f"{alive} flood threads stuck"]
                                  if alive else []),
        "usage_conserved": conserved,
        "live_requests_after": live,
        "qos_tenants": sorted(qos_tenants) or None,
        "texts": quiet_texts,
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--artifact", default=None,
                    help="write the verdict and metrics as JSON here")
    args = ap.parse_args(argv)
    on = run_arm(qos_on=True)
    off = run_arm(qos_on=False)

    identical = on["texts"] == off["texts"]
    clean = (not on["flood_errors"] and not off["flood_errors"]
             and on["usage_conserved"] and off["usage_conserved"]
             and on["live_requests_after"] == 0
             and off["live_requests_after"] == 0)
    ok = (on["quiet_ttft_p95_ms"] <= TTFT_TARGET_MS
          and off["quiet_ttft_p95_ms"] > TTFT_TARGET_MS
          and identical and clean)
    detail = {
        "model": "mock-fleet",
        "hosts": N_HOSTS,
        "flood_requests": FLOOD_THREADS * FLOOD_REQS_EACH,
        "quiet_requests": QUIET_REQS,
        "latency_s": LATENCY_S,
        "ttft_target_ms": TTFT_TARGET_MS,
        "quiet_ttft_p95_ms_qos_on": on["quiet_ttft_p95_ms"],
        "quiet_ttft_p95_ms_qos_off": off["quiet_ttft_p95_ms"],
        "fairness_gain": round(
            off["quiet_ttft_p95_ms"] / max(on["quiet_ttft_p95_ms"], 1e-9),
            2),
    }
    report = {
        "object": "ab_fairness",
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
