"""Benchmark runner: end-to-end map-reduce summarization throughput at
~1B-param scale, plus device-level roofline numbers.

Runs on a TPU only.  Prints ONE JSON line: {"metric", "value", "unit",
"vs_baseline", "device", "detail"} — ``device`` is the platform, kind and
count JAX reports.  The detail block carries the roofline fields:
prefill_tokens_per_sec, decode_tokens_per_sec, model_flops_utilization
(prefill MFU vs the chip's bf16 peak), hbm_bw_utilization (decode
bytes/step vs the HBM peak) — measured with chained dispatches on the
device, so the fixed dispatch + fetch cost cancels (utils/perf_model.
time_chain).

Failure is visible: no TPU, a device whose peaks are unknown, or any error
in the run (the microbenchmark phases included) exits NON-ZERO with the
traceback on stderr.  If timed reps had already completed, their median is
still printed (flagged ``partial``) before the non-zero exit; a deadline
timer does the same for a run that wedges on a device call.

The timed region repeats LMRS_BENCH_REPS times (default 3); the headline is
the MEDIAN rep and the detail block carries per-rep values + spread, so one
slow rep is distinguishable from a regression.

vs_baseline: the reference has no published numbers (BASELINE.md); its implied
throughput ceiling with default settings is 5 concurrent API calls at
~20 s/request ≈ 0.25 chunks/sec (reference llm_executor.py:133-147).
vs_baseline = ours / 0.25.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import threading
import time
from pathlib import Path

from lmrs_tpu.utils.env import env_float, env_int, env_str

REFERENCE_BASELINE_CHUNKS_PER_SEC = 0.25

# the example fixture when the checkout carries it; synthesized otherwise
# (never a path outside the checkout)
TRANSCRIPT_CANDIDATES = [
    Path(__file__).parent / "tests" / "data" / "transcript-example.json",
]

_emit_lock = threading.Lock()
_emitted = False
_device: dict = {}  # platform / kind / count, set once the backend is up
# completed timed reps, appended as they finish: if the watchdog fires
# mid-run (slow link, wedged dispatch after some reps landed), it emits
# the median of what completed instead of throwing the data away
_partial_reps: list[dict] = []


def emit(value: float, detail: dict) -> None:
    """Print the one-line JSON artifact exactly once, whoever gets there
    first (main path, failure path, or watchdog)."""
    global _emitted
    with _emit_lock:
        if _emitted:
            return
        _emitted = True
        print(json.dumps({
            "metric": "e2e_map_reduce_chunks_per_sec",
            "value": round(value, 3),
            "unit": "chunks/s",
            "vs_baseline": round(value / REFERENCE_BASELINE_CHUNKS_PER_SEC, 2),
            "device": _device,
            "detail": detail,
        }), flush=True)


def summarize_reps(reps: list[dict]) -> tuple[float, dict]:
    """Headline = MEDIAN rep; detail = the rep NEAREST the median (never
    contradicting the headline) + per-rep values and spread.  The ONE
    summary used by the happy path, the watchdog, and the error path."""
    vals = sorted(r["chunks_per_sec"] for r in reps)
    value = statistics.median(vals)
    row = min(reps, key=lambda r: abs(r["chunks_per_sec"] - value))
    return value, {
        **row,
        "reps": len(reps),
        "rep_chunks_per_sec": [r["chunks_per_sec"] for r in reps],
        "spread": round(vals[-1] - vals[0], 3),
    }


def emit_salvage(note: str) -> None:
    """Emit the best artifact available after a failure: the median of any
    COMPLETED reps (flagged partial) — measured data must never be thrown
    away for a late error — else value 0 with the error alone."""
    reps = list(_partial_reps)  # snapshot: the main thread may append
    if reps:
        value, detail = summarize_reps(reps)
        emit(value, {**detail, "partial": True, "error": note})
    else:
        emit(0.0, {"error": note})


def start_watchdog(deadline_s: float) -> threading.Timer:
    """If the bench wedges on a device call, still print what was measured
    — the median of any COMPLETED reps, else an error — and exit non-zero."""
    def fire() -> None:
        emit_salvage(f"watchdog: bench exceeded {deadline_s:.0f}s deadline "
                     "(device call wedged?)")
        sys.stdout.flush()
        os._exit(1)

    t = threading.Timer(deadline_s, fire)
    t.daemon = True
    t.start()
    return t


def require_chip() -> None:
    """Bring the backend up and refuse anything but a TPU whose peaks are
    known: a measurement path that finds no chip fails, it does not fall
    back to the CPU."""
    import jax

    from lmrs_tpu.utils.perf_model import chip_spec
    from lmrs_tpu.utils.platform import setup_compile_cache

    setup_compile_cache()
    devs = jax.devices()
    _device.update(platform=devs[0].platform, kind=devs[0].device_kind,
                   count=len(devs))
    if devs[0].platform != "tpu":
        raise RuntimeError(f"bench.py found no TPU: JAX reports {_device}")
    if chip_spec() is None:
        raise RuntimeError(f"no peak FLOP/s / HBM figures known for "
                           f"{devs[0].device_kind!r} (utils/perf_model.py)")


def load_transcript() -> dict:
    data = None
    for p in TRANSCRIPT_CANDIDATES:
        if p.exists():
            data = json.loads(p.read_text())
            break
    if data is None:
        # synthesize a ~2h transcript if the fixture is missing
        segs = []
        t = 0.0
        for i in range(3000):
            segs.append({"start": t, "end": t + 2.4,
                         "text": f"Segment {i} discusses milestone {i % 97} of the plan.",
                         "speaker": f"SPEAKER_{i % 2:02d}"})
            t += 2.5
        data = {"segments": segs}
    # LMRS_BENCH_SEGMENTS: cap the workload (CPU smoke of the bench harness
    # itself — the driver never sets it, so chip runs get the full fixture)
    cap = env_int("LMRS_BENCH_SEGMENTS", 0, lo=0)
    if cap > 0:
        data = {"segments": data["segments"][:cap]}
    return data


def _param_count_m(params) -> float:
    from lmrs_tpu.models.transformer import param_count

    return param_count(params) / 1e6


def run_bench(trace_out: str | None = None) -> tuple[float, dict]:
    from lmrs_tpu.config import (
        ChunkConfig, EngineConfig, PipelineConfig, ReduceConfig, model_preset,
    )
    from lmrs_tpu.pipeline import TranscriptSummarizer
    from lmrs_tpu.utils.logging import setup_logging

    # logs -> stderr: this process's stdout is the one-JSON-line artifact
    # the driver parses; a WARNING on stdout would corrupt it
    setup_logging(quiet=True, stream=sys.stderr)
    if trace_out:
        from lmrs_tpu.obs import enable_tracing

        enable_tracing()
    transcript = load_transcript()

    # ~1.03B-param GQA decoder (config.model_preset "bench-1b"): big enough
    # that the bench measures the MXU and HBM, not the host link (the r1
    # 45M model ran at <1% MFU — VERDICT r1 item 1).  Random weights (no
    # egress) — throughput-identical to a trained model of this shape.
    # LMRS_BENCH_MODEL: A/B hook (e.g. "tiny" for a CPU smoke run of the
    # bench harness itself; the driver always runs the default on the chip)
    model_name = env_str("LMRS_BENCH_MODEL", "bench-1b")
    model = model_preset(model_name)
    cfg = PipelineConfig(
        # 1400-token chunks: chunk body (1250) + context header (150) + the
        # ~470-byte map template stay under the scheduler's truncation
        # limit max_seq_len - max_tokens = 1920, so no map prompt is
        # middle-truncated mid-run (at 1600 ~40% of prompts were)
        chunk=ChunkConfig(max_tokens_per_chunk=1400, context_tokens=150,
                          overlap_tokens=0, tokenizer="byte"),
        # Dispatch sizing (docs/PERF.md): 24 slots, decode_block ==
        # max_tokens (whole decode in one dispatch: one host sync per
        # request wave instead of one per block — the offline job has no
        # client waiting on partial output; retuning the block is a
        # measured PR's job), prefill_chunk > max prompt (one fresh
        # dispatch, packed), page_size 512 (decode was DMA-latency-bound
        # on page fetches), num_pages=1 -> worst-case pool floor sizing.
        # quantize="int8": ABBA-measured +5.9-7.1% on decode-heavy waves at
        # this scale (weight stream halves; docs/PERF.md round 2/3).
        # kv_quantize="int8": +3.9% more (KV walk bytes halve, capacity
        # doubles; docs/PERF.md round 3).  The LIBRARY defaults stay bf16 —
        # int8 weights/KV are quality tradeoffs a throughput bench need not
        # pay but a user must opt into.
        # tokenizer pinned to byte: the 8B preset carries the real 128k
        # vocabulary (the LM head's true byte share), which would otherwise
        # flip the engine's default-tokenizer heuristic off byte.
        # LMRS_BENCH_SLOTS: page-pool headroom knob for the 8B preset
        # (24 slots x 2048 x 64 KB/token int8 = 3.2 GB worst-case pool on
        # top of ~8.6 GB weights; the driver default stays 24).
        # page_size: 512 was the r4 sweep's optimum for bf16-page DMAs;
        # int8 KV halves page bytes, and at the 8B shape the r5 split
        # measured 1024 −7% per step at the bench's ~1.8k-token live mix
        # (the DMA-issue-per-byte argument, docs/PERF.md round 5).  Short-
        # context serving configs should stay at 512 (page-quantized reads
        # dominate there); this is the bench preset's live range talking.
        engine=EngineConfig(backend="jax", max_tokens=128,
                            max_batch_slots=env_int(
                                "LMRS_BENCH_SLOTS", 24, lo=1),
                            tokenizer="byte",
                            retry_delay=0.0, seed=0,
                            page_size=1024 if model_name == "bench-8b" else 512,
                            num_pages=1, decode_block=128, prefill_chunk=4096,
                            quantize="int8", kv_quantize="int8"),
        model=model,
        reduce=ReduceConfig(max_tokens_per_batch=6000),
    )
    s = TranscriptSummarizer(cfg)

    # Warm-up outside the timed region: the FULL fixture once, so every
    # shape the timed reps use — full decode slots, packed prefill at the
    # capped bucket set, every page-window bucket the steady-state reaches,
    # the compact-batch drain, and the whole hierarchical reduce tree — is
    # compiled by construction.  (r3's 900-segment warmup missed the
    # full-run shapes and rep 1 ran ~2x slow on mid-rep compiles —
    # VERDICT r3 weak #1.)
    s.summarize(transcript)

    # Device-level roofline on the live engine (chained dispatches).  A
    # probe that fails fails the bench: a kernel that does not run on the
    # chip is the finding, not a detail to drop.
    sched = s.executor.engine._scheduler
    roofline = sched.roofline_microbench()
    # multi-row page walk attribution: per-row kernel cost grouped vs
    # per-row — the measured, not asserted, per-row gain the grouped
    # dispatch buys
    roofline.update(sched.rowcost_microbench())

    # Timed region, repeated: the median + per-rep values let a reader
    # tell a real regression from one slow rep.
    # Latency samples reset here so warmup's compile-time dispatch gaps
    # (orders of magnitude over steady state) don't pollute the
    # percentiles; counter metrics are windowed via the snapshot below.
    sched.reset_latency_stats()
    metrics_before = dict(sched.metrics)
    cost_before = sched._cost.report()
    anatomy_before = sched.anatomy_snapshot()
    reps = env_int("LMRS_BENCH_REPS", 3, lo=1)
    rep_rows = _partial_reps  # shared with the watchdog (see start_watchdog)
    for _ in range(reps):
        tokens_before = s.executor.total_tokens_used
        failed_before = s.executor.failed_requests
        t0 = time.time()
        stats = s.summarize(transcript)
        wall = time.time() - t0
        rep_rows.append({
            "chunks_per_sec": round(stats["num_chunks"] / wall, 3),
            "wall_s": round(wall, 2),
            "map_s": round(stats["stage_times"].get("map", 0.0), 2),
            "reduce_s": round(stats["stage_times"].get("reduce", 0.0), 2),
            "total_tokens": stats["total_tokens_used"] - tokens_before,
            "failed": stats["failed_requests"] - failed_before,
            "num_chunks": stats["num_chunks"],
        })

    value, detail = summarize_reps(rep_rows)
    detail.update({
        "model": model.name,
        "params_m": round(_param_count_m(sched.params), 1),
        "backend": "jax",
        **roofline,
        **_scheduler_window(sched, metrics_before),
        # request-cost ledger over the timed window (obs/ledger.py):
        # windowed per-tenant device-seconds + goodput, and the host's
        # burn-rate SLO state at capture — attribution rides every BENCH
        # artifact next to the latency it explains
        "cost": sched._cost.report(cost_before),
        "slo": _slo_summary(sched.slo_report()),
    })
    # windowed step anatomy (ISSUE 18, obs/anatomy.py): named host
    # segments + ragged-span bucket economics over the timed reps only —
    # the block perf_sentry's anatomy.host_overhead_us_step /
    # anatomy.rpa_pad_waste_ratio columns resolve against.  Omitted (not
    # enabled:false) under LMRS_ANATOMY=0, wire-parity rule.
    if sched._an.enabled:
        detail["anatomy"] = sched.anatomy_report(anatomy_before)
    # live-vs-offline agreement (ISSUE 8 acceptance): the live attribution
    # gauges gathered DURING the timed reps against the RTT-amortized
    # roofline probe — rel = live/offline - 1 (within ±0.05 = agreeing)
    pa = detail.get("perf_attribution") or {}
    cmp_block = {}
    live_mfu = (pa.get("prefill_mfu") or {}).get("p50")
    if live_mfu and detail.get("model_flops_utilization"):
        cmp_block["prefill_mfu_rel"] = round(
            live_mfu / detail["model_flops_utilization"] - 1.0, 3)
    live_hbm = (pa.get("decode_hbm_util") or {}).get("p50")
    if live_hbm and detail.get("hbm_bw_utilization"):
        cmp_block["decode_hbm_rel"] = round(
            live_hbm / detail["hbm_bw_utilization"] - 1.0, 3)
    if cmp_block:
        detail["live_vs_roofline"] = cmp_block
    return float(value), detail


def _slo_summary(doc: dict) -> dict:
    """Compact SLO block for bench detail: state + per-spec burn rates
    (the full windows live on /healthz; the artifact needs the verdict
    and the why, not the raw series)."""
    return {
        "enabled": doc.get("enabled", False),
        "state": doc.get("state", "ok"),
        "specs": {name: {"state": s.get("state"),
                         "burn_fast": s.get("burn_fast"),
                         "burn_slow": s.get("burn_slow")}
                  for name, s in (doc.get("specs") or {}).items()},
    }


def _scheduler_window(sched, before: dict) -> dict:
    """Scheduler-level detail over the timed reps only (VERDICT r4 items
    2 and 5): decode occupancy, stall/preemption counts, the
    prefill/decode phase split, and the serving-latency percentiles —
    the e2e numbers needed to attribute any roofline-vs-e2e gap from the
    bench artifact alone, without rerunning a one-off script."""
    m = sched.metrics
    d_disp = m["decode_dispatches"] - before["decode_dispatches"]
    occ = ((m["occupancy_sum"] - before["occupancy_sum"]) / d_disp
           if d_disp else 0.0)
    report = sched.metrics_report()  # latency pct reset at window start
    g_disp = (m["group_dispatches"] - before["group_dispatches"])
    g_occ = ((m["group_occupancy_sum"] - before["group_occupancy_sum"])
             / g_disp if g_disp else 0.0)
    return {
        "mean_decode_occupancy": round(occ, 3),
        "decode_dispatches": d_disp,
        # multi-row kernel: configured group size and live-rows-over-group-
        # capacity occupancy over the timed window (1.0 = no padding waste)
        "decode_row_group": getattr(sched, "_row_group", 1),
        "mean_group_occupancy": round(g_occ, 3),
        "stalls": m["stalls"] - before["stalls"],
        "preemptions": m["preemptions"] - before["preemptions"],
        # device-wait vs host-bookkeeping split of the SCHEDULER wall over
        # the timed reps (map + reduce both run through the scheduler —
        # these are engine-wide, not map-only): the host share is time the
        # device sits idle between a block's fetch and the next dispatch
        # (the r5 overlap lever's attribution number)
        "sched_blocked_s": round(
            m["blocked_seconds"] - before["blocked_seconds"], 2),
        "sched_host_s": round(
            max((m["run_seconds"] - before["run_seconds"])
                - (m["blocked_seconds"] - before["blocked_seconds"]), 0.0),
            2),
        "phase_split_tokens": {
            "prefill": m["prefill_tokens"] - before["prefill_tokens"],
            "decode": m["decode_tokens"] - before["decode_tokens"],
        },
        "ttft_ms": report["ttft_ms"],
        # WAVE-LEVEL gaps (docs/PERF.md "two block-gap numbers"): on this
        # batch workload the samples include whole admission/prefill
        # waves between decode dispatches (BENCH8B_r05's 7.65 s p50 is
        # queueing, NOT serving cadence); the steady-state per-block
        # number a streaming client sees is serving_latency.py's
        # decode_block_gap_ms_steady_state.  Named distinctly so a
        # verdict can never compare the two as if they measured the same
        # thing.
        "decode_block_gap_ms_wave": report["decode_block_gap_ms"],
        # SARATHI mixed batches over the timed window (ISSUE 11): fused
        # dispatches, budget fill, and the prompt tokens that rode decode
        # steps instead of dedicated prefill waves — plus the wave gap
        # percentiles above, the MULTICHIP/BENCH tracking trio
        "mixed_batch": sched._mixed_report(before),
        # ragged-span unified dispatch (ISSUE 16): span tokens and the
        # distinct program shapes compiled over the window — the roofline
        # column perf_sentry tracks for the one-bucket-family collapse
        "rpa": sched._rpa_report(before),
        # tree speculation over the timed window (ISSUE 19): dispatches,
        # drafted nodes, and accepted tokens per dispatched row — the
        # acceptance trajectory perf_sentry tracks (spec_tree.accept_
        # per_step); zeros when speculate_k=0 or LMRS_SPEC_TREE=0
        "spec_tree": sched._spec_tree_report(before),
        # disaggregated handoff over the timed window: export/import
        # counts and orphaned pages are zero on a colocated bench by
        # construction — the block exists so MULTICHIP_* rounds that run
        # the two-tier topology can track transfer overhead against this
        # colocated baseline (capture/import latency percentiles included)
        "handoff": {
            "exports": m["handoff_exports"] - before["handoff_exports"],
            "imports": m["handoff_imports"] - before["handoff_imports"],
            "orphaned_pages": (m["handoff_orphaned_pages"]
                               - before["handoff_orphaned_pages"]),
            "pinned_pages": m["handoff_pinned_pages"],
            "capture_ms": sched._h_handoff_capture.percentile_report(),
            "import_ms": sched._h_handoff_import.percentile_report(),
        },
        # shared-prefix KV cache over the timed reps: hit rate across
        # admissions and the prompt tokens whose prefill was skipped
        # entirely (the map preamble re-use win; engine/prefix_cache.py)
        "prefix_cache": _prefix_window(m, before),
        # host-RAM spill tier over the timed window (engine/host_kv.py):
        # zero on a roomy-pool bench by construction — the block exists
        # so pressure rounds (budgeted num_pages) can track the
        # spill/prefetch traffic the tier converts re-prefills into
        "host_kv": sched._host_kv_report(before),
        # live per-phase roofline attribution (obs/perf.py): MFU / HBM
        # utilization / step-gap percentiles from the serving path's own
        # dispatch walls — what future BENCH_r* rounds record alongside
        # chunks/s, and the numbers the offline roofline block above is
        # checked against (live_vs_roofline)
        "perf_attribution": sched.perf_attribution_report(),
    }


def _prefix_window(m: dict, before: dict) -> dict:
    queries = m["prefix_queries"] - before["prefix_queries"]
    hits = m["prefix_hits"] - before["prefix_hits"]
    saved = m["prefix_tokens_reused"] - before["prefix_tokens_reused"]
    return {
        "hit_rate": round(hits / queries, 3) if queries else 0.0,
        "hits": hits,
        "queries": queries,
        "tokens_reused": saved,
        "prefill_tokens_saved": saved,
    }


def main() -> int:
    import argparse

    # minimal flag surface (the driver runs bench.py bare; --trace-out /
    # LMRS_TRACE_OUT opt into lifecycle tracing, --no-trace is the
    # overhead-A/B control) — unknown args are ignored, not fatal
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--trace-out",
                    default=env_str("LMRS_TRACE_OUT") or None)
    ap.add_argument("--no-trace", action="store_true")
    args, _ = ap.parse_known_args()
    trace_out = None if args.no_trace else args.trace_out

    deadline = env_float("LMRS_BENCH_DEADLINE_S", 1800.0, lo=1.0)
    start_watchdog(deadline)

    require_chip()  # raises (non-zero exit, traceback) without a TPU
    rc = 0
    try:
        value, detail = run_bench(trace_out)
        emit(value, detail)
    except Exception as e:  # noqa: BLE001 - salvage, then fail visibly
        import traceback
        traceback.print_exc()
        # a device error after completed reps must not throw measured
        # data away — print it flagged partial, and still exit non-zero
        emit_salvage(f"{type(e).__name__}: {e}"[:400])
        rc = 1
    finally:
        # trace salvage mirrors the rep salvage above: whatever the ring
        # buffer captured before a failure is still a diagnosable artifact
        if trace_out:
            from lmrs_tpu.obs import export_current

            n, err = export_current(trace_out)
            print(f"wrote {n} trace events to {trace_out}" if err is None
                  else f"could not write trace {trace_out}: {err}",
                  file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
