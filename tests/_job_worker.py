"""Subprocess entry point for the durable-job SIGKILL chaos scenarios.

Runs ONE durable job (JobManager over a mock or CPU-jax engine) inside
its own OS process so the parent test (tests/test_chaos.py,
tests/test_jobs.py) can SIGKILL it mid-map or mid-reduce by watching the
write-ahead journal grow, then resume the journal with its OWN engine
and assert the final greedy summary is token-identical to an
uninterrupted run.

The parent paces the child's journal appends with a ``journal.append``
stall fault plan (LMRS_FAULT_PLAN in the child env) so the kill window
between records is wide and machine-speed independent — stalls never
change WHAT is written, only when.

The config builders below are the single source of truth for both sides:
the parent resumes under the SAME PipelineConfig (and, for the jax arm,
the same engine/model geometry), so the job's config fingerprint matches
and the journal rehydrates instead of being set aside as stale.

Usage: ``python tests/_job_worker.py <spec.json>`` where the spec file
carries ``{"jobs_dir", "backend": "mock"|"jax", "transcript"}``.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# started as a script, so tests/ — not the checkout — is on sys.path
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def job_transcript(n: int = 30, seed: int = 1) -> dict:
    """Deterministic synthetic transcript (same schema as conftest's
    ``make_segments``; duplicated here so the child never imports the
    test-harness conftest)."""
    import random

    rng = random.Random(seed)
    words = ("the quarterly review covered the inference engine roadmap "
             "kernel design latency targets hiring plan and budget "
             "allocation for the serving tier").split()
    segs = []
    t = 0.0
    for i in range(n):
        dur = 2.0 + rng.random() * 6.0
        text = " ".join(rng.choice(words) for _ in range(8 + rng.randrange(14)))
        segs.append({"start": round(t, 2), "end": round(t + dur, 2),
                     "text": text.capitalize() + ".",
                     "speaker": f"SPEAKER_{i % 2:02d}"})
        t += dur + rng.random()
    return {"segments": segs}


def job_pipeline_config(backend: str = "mock"):
    """The (chunk, engine, reduce) surface both sides run under.  Small
    chunks force a multi-chunk map; a small reduce batch budget forces a
    hierarchical tree with several nodes, so "mid-reduce" is a real
    window.  temperature=0 end to end: the token-identity contract is
    greedy."""
    from lmrs_tpu.config import (ChunkConfig, EngineConfig, PipelineConfig,
                                 ReduceConfig)

    if backend == "jax":
        # the checkpointless tiny model generates near-empty text, so the
        # tree shape must hang on the deterministic [Time: ...] tags each
        # reduce input carries (~6 tokens/chunk): a budget well under the
        # total tag mass forces a multi-node hierarchical tree no matter
        # what the content-free weights emit
        reduce = ReduceConfig(max_tokens_per_batch=12, reserve_tokens=0,
                              max_summaries_per_batch=3)
    else:
        reduce = ReduceConfig(max_tokens_per_batch=300, reserve_tokens=50,
                              max_summaries_per_batch=3)
    return PipelineConfig(
        chunk=ChunkConfig(max_tokens_per_chunk=150, overlap_tokens=0,
                          context_tokens=30, tokenizer="approx"),
        engine=EngineConfig(backend=backend, temperature=0.0, seed=0,
                            max_tokens=48, retry_delay=0.0),
        reduce=reduce,
    )


def build_engine(backend: str):
    """mock: instantaneous deterministic text.  jax: the chaos-soak
    geometry (tests/test_chaos.py ``chaos_model``) on a real continuous
    scheduler — tiny enough to compile in CI, real enough that the
    resume-side ``scheduler.audit()`` exercises page conservation."""
    if backend == "mock":
        from lmrs_tpu.engine.mock import MockEngine

        return MockEngine(seed=0)
    from lmrs_tpu.config import EngineConfig, ModelConfig
    from lmrs_tpu.engine.jax_engine import JaxEngine

    model = ModelConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, hidden_dim=128, max_seq_len=256,
                        dtype="float32")
    cfg = job_pipeline_config("jax").engine
    return JaxEngine(
        EngineConfig(backend="jax", scheduler="continuous",
                     max_tokens=cfg.max_tokens, temperature=0.0,
                     max_batch_slots=2, seed=0, decode_block=4,
                     page_size=16, num_pages=48),
        model)


def serve(spec: dict) -> int:
    """``mode: "serve"``: a real EngineHTTPServer OS process with the job
    API armed, under the SAME pipeline config the parent's replacement
    server will use — the restart-mid-job scenario needs fingerprint
    equality across the two server generations or the journal would be
    set aside as stale instead of resumed."""
    from lmrs_tpu.serving.server import EngineHTTPServer

    server = EngineHTTPServer(
        build_engine(spec.get("backend", "mock")),
        port=int(spec["port"]), batch_window_s=0.01,
        jobs_dir=spec["jobs_dir"],
        pipeline_config=job_pipeline_config(spec.get("backend", "mock")))
    server.serve_forever()
    return 0


def main(spec_path: str) -> int:
    spec = json.loads(Path(spec_path).read_text(encoding="utf-8"))
    # share the parent's persistent XLA compile cache (conftest.py): the
    # child's engine compiles the same tiny shapes the suite already built
    from lmrs_tpu.utils.platform import setup_compile_cache

    setup_compile_cache()
    if spec.get("mode") == "serve":
        return serve(spec)

    from lmrs_tpu.jobs.manager import JobManager

    backend = spec.get("backend", "mock")
    engine = build_engine(backend)
    manager = JobManager(engine, spec["jobs_dir"],
                         config=job_pipeline_config(backend),
                         start_worker=False)
    job = manager.submit(spec["transcript"])
    manager.run_job(job)
    print(json.dumps({"job_id": job.job_id, "status": job.status,
                      "summary": (job.result or {}).get("summary")}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
