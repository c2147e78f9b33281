"""Serving-config latency percentiles on the real chip (PERF round 5),
with a mixed-batch on/off A/B arm (ISSUE 11): bench-1b int8 weights /
bf16 KV at decode_block=16 — the TTFT / per-block-gap numbers a
streaming client sees, from the scheduler's always-on samples, measured
with SARATHI mixed dispatch armed and disarmed over the SAME traffic.
(bf16 KV on purpose: int8 KV auto-disarms mixed dispatch — the mixed
arm would silently measure the alternating path; see run_arm.)

The A/B answers ROADMAP item 1's question directly: does decode cadence
continue through admission bursts (48 requests over 24 slots re-admit
continuously, so every slot turnover is an admission landing mid-decode)?
The mixed arm's block-gap tail should collapse toward its p50 — no
admission-correlated spike — while the off arm reproduces today's
alternating-wave gaps.  TTFT and gap percentile DELTAS are reported
alongside both arms' raw numbers.

Chip knobs: LMRS_SERVE_MODEL overrides the preset (e.g. bench-8b).
CPU/interpret smoke: LMRS_SERVE_MODEL=bench-smoke LMRS_SERVE_CPU=1 runs
the identical harness without int8 (the no-chip admission-interleave
demonstration CI quotes)."""
import json, sys, time
import _pathfix  # noqa: F401  (repo-root import shim)
from lmrs_tpu.utils.platform import setup_compile_cache

setup_compile_cache()
import numpy as np
from lmrs_tpu.config import EngineConfig, model_preset
from lmrs_tpu.utils.env import env_bool, env_str

MODEL = env_str("LMRS_SERVE_MODEL", "bench-1b")
CPU = env_bool("LMRS_SERVE_CPU", False)  # no int8: the mock/interpret arm
from lmrs_tpu.engine.api import GenerationRequest
from lmrs_tpu.engine.jax_engine import JaxEngine

rng = np.random.default_rng(0)
PROMPT_WORDS = 60 if CPU else 300
N_WARM = 8 if CPU else 24
N_MEAS = 16 if CPU else 48
SLOTS = 8 if CPU else 24


def mk(i, words):
    body = " ".join(f"w{rng.integers(0, 999)}" for _ in range(words))
    # STAGGERED budgets: uniform budgets finish whole waves together and
    # admissions then land on an idle batch (nothing to mix with); real
    # traffic staggers by EOS.  The spread keeps slots turning over while
    # neighbors decode — every admission is a mid-decode burst.
    budget = (8 + (i % 5) * 8) if CPU else (48 + (i % 5) * 24)
    return GenerationRequest(prompt=body, request_id=i, temperature=0.3,
                             max_new_tokens=budget)


DECODE_BLOCK = 8 if CPU else 16


def run_arm(mixed: bool) -> dict:
    # int8 WEIGHTS only: kv_quantize="int8" auto-disarms mixed dispatch
    # (a mixed chunk cannot own its slot's frozen prefill scales —
    # scheduler gate), so an int8-KV "mixed arm" would silently run the
    # alternating dispatch and the A/B would measure nothing.  Both arms
    # therefore run bf16 KV — apples to apples, and the bar in
    # docs/PERF.md is defined at this config.  bf16 KV doubles the page
    # bytes: at 8B shape budget the pool accordingly (num_pages=1 =
    # worst-case sizing still fits one v5e with the 2048 window).
    quant = {} if CPU else dict(quantize="int8")
    eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                 max_tokens=48 if CPU else 168,
                                 max_batch_slots=SLOTS, seed=0,
                                 page_size=64 if CPU else 512,
                                 num_pages=1,
                                 decode_block=DECODE_BLOCK,
                                 prefill_chunk=4096, retry_delay=0.0,
                                 mixed_batch=mixed, **quant),
                    model_preset(MODEL))
    assert eng._scheduler._mixed == mixed, \
        "mixed arm disarmed itself — config incompatible with mixed dispatch"
    # warmup compiles every shape the measured wave uses (incl. the
    # bucketed mixed shapes on the mixed arm)
    eng.generate_batch([mk(i, PROMPT_WORDS) for i in range(N_WARM)])
    sched = eng._scheduler
    sched.reset_latency_stats()
    m0 = dict(sched.metrics)
    cost0 = sched._cost.report()
    an0 = sched.anatomy_snapshot()
    t0 = time.time()
    out = eng.generate_batch([mk(1000 + i, PROMPT_WORDS)
                              for i in range(N_MEAS)])
    wall = time.time() - t0
    rep = sched.metrics_report()
    m1 = sched.metrics
    arm = {
        "mixed": mixed,
        "wall_s": round(wall, 2),
        "ttft_ms": rep["ttft_ms"],
        # steady-state serving cadence: within-run dispatch gaps on live
        # traffic (NOT the batch-bench wave-level number — docs/PERF.md
        # "two block-gap numbers")
        "decode_block_gap_ms_steady_state": rep["decode_block_gap_ms"],
        "decode_dispatches": m1["decode_dispatches"] - m0["decode_dispatches"],
        "occupancy": round((m1["occupancy_sum"] - m0["occupancy_sum"]) /
                           max(m1["decode_dispatches"]
                               - m0["decode_dispatches"], 1), 3),
        # measured-window mixed stats (warmup's mixed dispatches excluded,
        # same windowing as decode_dispatches above)
        "mixed_batch": sched._mixed_report(m0),
        # measured-window ragged-span stats (same windowing)
        "rpa": sched._rpa_report(m0),
        # windowed cost/SLO attribution (ISSUE 15): per-tenant device-
        # seconds + goodput over the measured wave, and the burn-rate
        # state the wave left the host in — the A/B now reports WHO paid
        # for each arm's latency, not just the percentiles
        "cost": sched._cost.report(cost0),
        "slo": {"state": sched.slo_report().get("state", "ok")},
        # windowed step anatomy (ISSUE 18): host-segment split of the
        # measured wave + per-class p50/p95 — which microseconds between
        # dispatches each arm spends, not just how many.  Omitted (not
        # enabled:false) under LMRS_ANATOMY=0, wire-parity rule.
        **({"anatomy": sched.anatomy_report(an0)}
           if sched._an.enabled else {}),
        "failed": sum(r.error is not None for r in out),
    }
    eng.shutdown()
    return arm


def pct_delta(on: dict | None, off: dict | None) -> dict:
    if not on or not off:
        return {}
    return {p: round(on[p] - off[p], 1)
            for p in ("p50", "p90", "p99") if p in on and p in off}


off_arm = run_arm(False)
on_arm = run_arm(True)
print(json.dumps({
    "config": MODEL + (" cpu-smoke" if CPU else " int8 W, bf16 KV")
              + f", decode_block={DECODE_BLOCK}, {SLOTS} slots, "
              f"{N_MEAS} reqs (~{PROMPT_WORDS}-word prompts, staggered "
              "budgets), mixed A/B",
    "mixed_off": off_arm,
    "mixed_on": on_arm,
    # the ROADMAP item 1 numbers: negative = mixed is faster
    "delta_ms": {
        "ttft": pct_delta(on_arm["ttft_ms"], off_arm["ttft_ms"]),
        "decode_block_gap": pct_delta(
            on_arm["decode_block_gap_ms_steady_state"],
            off_arm["decode_block_gap_ms_steady_state"]),
    },
}, indent=1))
