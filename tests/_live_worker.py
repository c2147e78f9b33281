"""Subprocess entry point for the live-session SIGKILL chaos scenarios.

Runs ONE live session (SessionManager over a mock engine) inside its own
OS process so the parent test (tests/test_live.py) can SIGKILL it
mid-refresh by watching the write-ahead journal grow, then resume the
session in-process and assert the next refresh is token-identical to an
uninterrupted run with the clean subtrees never recomputed.

The parent paces the child's journal appends with a ``journal.append``
stall fault plan (LMRS_FAULT_PLAN in the child env) so the kill window
between records is wide and machine-speed independent.

The config builders below are the single source of truth for both
sides: the parent resumes under the SAME PipelineConfig, so the
session's config fingerprint matches and the journal rehydrates instead
of being set aside as stale.

Usage: ``python tests/_live_worker.py <spec.json>`` with
``{"live_dir", "session_id", "batches": [[segment...], ...]}``.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# started as a script, so tests/ — not the checkout — is on sys.path
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def live_segments(n: int = 60, seed: int = 2) -> list[dict]:
    """Deterministic synthetic live transcript (duplicated from the
    conftest schema so the child never imports the test harness)."""
    import random

    rng = random.Random(seed)
    words = ("the standup covered the live summarization tier session "
             "journal refresh cadence rolling reduce tree deadline "
             "classes and the router stickiness design").split()
    segs = []
    t = 0.0
    for i in range(n):
        dur = 3.0 + rng.random() * 5.0
        text = " ".join(rng.choice(words) for _ in range(10 + rng.randrange(12)))
        segs.append({"start": round(t, 2), "end": round(t + dur, 2),
                     "text": text.capitalize() + ".",
                     "speaker": f"SPEAKER_{i % 2:02d}"})
        t += dur + 0.5
    return segs


def live_pipeline_config():
    """The (chunk, engine, reduce, live) surface both sides run under:
    small chunks force a multi-chunk map, arity 3 forces a multi-level
    stable tree, so "mid-refresh" is a real kill window.  temperature=0
    end to end — the token-identity contract is greedy."""
    from lmrs_tpu.config import (ChunkConfig, EngineConfig, LiveConfig,
                                 PipelineConfig, ReduceConfig)

    return PipelineConfig(
        chunk=ChunkConfig(max_tokens_per_chunk=120, overlap_tokens=0,
                          context_tokens=30, tokenizer="approx"),
        engine=EngineConfig(backend="mock", temperature=0.0, seed=0,
                            max_tokens=48, retry_delay=0.0),
        reduce=ReduceConfig(max_summaries_per_batch=3),
        live=LiveConfig(class_default="bulk"),
    )


def build_manager(live_dir: str):
    from lmrs_tpu.engine.mock import MockEngine
    from lmrs_tpu.live import SessionManager

    return SessionManager(MockEngine(seed=0), live_dir,
                          config=live_pipeline_config())


def main(spec_path: str) -> int:
    spec = json.loads(Path(spec_path).read_text(encoding="utf-8"))
    manager = build_manager(spec["live_dir"])
    sid = spec.get("session_id", "live")
    manager.create(session_id=sid)
    last = None
    for batch in spec["batches"]:
        doc = manager.append(sid, batch, refresh=True)
        last = doc.get("refresh")
    print(json.dumps({"session_id": sid,
                      "summary": (last or {}).get("summary")}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
