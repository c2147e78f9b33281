"""Test harness config.

Sharding tests run on a virtual 8-device CPU mesh — set platform flags BEFORE
jax is imported anywhere (SURVEY.md §4: emulate TP/DP without TPUs via
``xla_force_host_platform_device_count``).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent XLA compilation cache: the suite's wall-clock is dominated by
# CPU XLA compiles of the engine programs; cached, repeat runs skip every
# previously-seen shape.  Same placement rule as every entry point
# (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache), so the
# children the chaos tests spawn share it.
from lmrs_tpu.utils.platform import setup_compile_cache

setup_compile_cache()

import json
import math
import random
from pathlib import Path

import pytest

REFERENCE_EXAMPLE = Path("/root/reference/transcript-example.json")


def free_port() -> int:
    """OS-assigned local port (shared by the multi-process tests).  The
    probe socket closes before the caller binds, so a collision is
    possible (TOCTOU) — callers that can retry should (test_distributed's
    pair fixture does)."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def make_segments(n: int = 200, n_speakers: int = 2, seed: int = 0) -> list[dict]:
    """Deterministic synthetic diarized transcript (schema: README.md:162-175)."""
    rng = random.Random(seed)
    words = (
        "the project timeline depends on shipping the new inference engine "
        "before the quarterly review so we must finalize the kernel design "
        "budget allocation and hiring plan while keeping latency targets"
    ).split()
    segs = []
    t = 0.0
    for i in range(n):
        dur = 2.0 + rng.random() * 6.0
        text = " ".join(rng.choice(words) for _ in range(8 + rng.randrange(18)))
        segs.append(
            {
                "start": round(t, 2),
                "end": round(t + dur, 2),
                "text": text.capitalize() + ".",
                "speaker": f"SPEAKER_{(i // 5) % n_speakers:02d}",
            }
        )
        t += dur + rng.random()
    return segs


@pytest.fixture
def segments() -> list[dict]:
    return make_segments()


@pytest.fixture
def transcript(segments) -> dict:
    return {"segments": segments}


@pytest.fixture
def example_transcript() -> dict:
    if not REFERENCE_EXAMPLE.exists():
        pytest.skip("reference example transcript not available")
    return json.loads(REFERENCE_EXAMPLE.read_text())

