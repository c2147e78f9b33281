"""Decode-latency benchmark for the prefill_chunk default (VERDICT r1
item 10): distribution of decode-dispatch gaps for already-active slots
while a long prompt admits mid-stream, chunked (512) vs one-dispatch
(4096) prefill.  Dispatch timestamps come from the lifecycle tracer's
``decode_block`` span starts (obs/trace.py — the one dispatch-timestamp
path; the LMRS_TRACE_DISPATCH env hack this script used to flip is gone).

Run live:     python scripts/decode_latency.py
Read a trace: python scripts/decode_latency.py --from-trace stitched.json
              [--pod host:port]

``--from-trace`` analyzes an exported trace file instead of running an
engine — including a ROUTER-STITCHED multi-host trace (``GET /v1/trace``
on a router front, obs.stitch_traces), where each pod's scheduler track
is reported separately; ``--pod`` filters to process names containing
the given substring (a netloc, typically).
"""
import argparse
import time

import _pathfix  # noqa: F401  (repo-root import shim)
from lmrs_tpu.utils.platform import setup_compile_cache

setup_compile_cache()
import numpy as np

from lmrs_tpu.config import EngineConfig, model_preset
from lmrs_tpu.engine.api import GenerationRequest
from lmrs_tpu.obs import TID_SCHED, enable_tracing, validate_trace_file
from lmrs_tpu.utils.logging import setup_logging


def _gap_line(label: str, ts: np.ndarray, wall: float | None = None) -> None:
    if len(ts) < 2:
        print(f"{label}: only {len(ts)} dispatch(es); no gaps", flush=True)
        return
    gaps = np.diff(np.sort(ts)) * 1e3
    wall_part = f"wall={wall:.1f}s " if wall is not None else ""
    print(f"{label}: {wall_part}dispatches={len(ts)} "
          f"gap p50={np.percentile(gaps, 50):.0f}ms "
          f"p90={np.percentile(gaps, 90):.0f}ms "
          f"p99={np.percentile(gaps, 99):.0f}ms max={gaps.max():.0f}ms",
          flush=True)


def analyze_trace(path: str, pod: str | None = None) -> dict[str, np.ndarray]:
    """Decode-dispatch gap analysis of an exported trace file.  Handles
    both a single-host export (pid 1's scheduler track) and a stitched
    multi-host document (per-host pids; process names carry the netloc).
    Returns {pod name: dispatch start timestamps (s)}."""
    events = validate_trace_file(path)
    pnames = {e["pid"]: (e.get("args") or {}).get("name", "")
              for e in events
              if e.get("ph") == "M" and e.get("name") == "process_name"}
    per_pod: dict[str, list[float]] = {}
    for e in events:
        if (e.get("name") == "decode_block" and e.get("ph") == "X"
                and e.get("tid") == TID_SCHED):
            name = pnames.get(e["pid"], f"pid{e['pid']}") or f"pid{e['pid']}"
            if pod is not None and pod not in name:
                continue
            per_pod.setdefault(name, []).append(e["ts"] / 1e6)
    if not per_pod:
        have = sorted(n for n in pnames.values() if "engine" in n)
        raise SystemExit(
            f"no decode_block dispatch spans matched"
            + (f" pod filter {pod!r}" if pod else "")
            + (f"; engine tracks present: {have}" if have else
               "; the trace has no engine tracks"))
    return {name: np.asarray(ts) for name, ts in sorted(per_pod.items())}


def run(prefill_chunk, label):
    from lmrs_tpu.engine.jax_engine import JaxEngine

    tracer = enable_tracing()
    model = model_preset("bench-1b")
    eng = JaxEngine(EngineConfig(
        backend="jax", max_tokens=256, max_batch_slots=8,
        retry_delay=0.0, seed=0, page_size=512, num_pages=1,
        decode_block=8, prefill_chunk=prefill_chunk), model)
    rng = np.random.default_rng(0)
    # 6 active decoders (short prompts, long decodes)
    active = [GenerationRequest(
        prompt=" ".join(f"w{rng.integers(0, 97)}" for _ in range(30)),
        request_id=i, temperature=0.5, max_new_tokens=256) for i in range(6)]
    # 8 long prompts that admit mid-stream as slots churn
    longs = [GenerationRequest(
        prompt=" ".join(f"word{rng.integers(0, 997)}" for _ in range(230)),
        request_id=100 + i, temperature=0.5, max_new_tokens=8)
        for i in range(8)]
    eng.generate_batch(active[:2])  # warm compile
    tracer.clear()  # drop warmup dispatches (compile-time gaps)
    t0 = time.time()
    eng.generate_batch(active + longs)
    wall = time.time() - t0
    ts = np.asarray(tracer.timestamps("decode_block", tid=TID_SCHED))
    _gap_line(label, ts, wall)
    eng.shutdown()
    return np.diff(ts) * 1e3


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--from-trace", default=None, metavar="PATH",
                    help="analyze an exported (possibly router-stitched "
                         "multi-host) trace file instead of running live")
    ap.add_argument("--pod", default=None,
                    help="with --from-trace: only tracks whose process "
                         "name contains this substring (a host netloc)")
    args = ap.parse_args()
    setup_logging(quiet=True)
    if args.from_trace:
        for name, ts in analyze_trace(args.from_trace, args.pod).items():
            _gap_line(name, ts)
        return
    if args.pod:
        raise SystemExit("--pod requires --from-trace")
    for pc, label in ((512, "chunked-512"), (4096, "one-dispatch"),
                      (4096, "one-dispatch-2"), (512, "chunked-512-2")):
        run(pc, label)


if __name__ == "__main__":
    main()
