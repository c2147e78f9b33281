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



# ----------------------------------------- paged-kernel parity, by pool type
# The page walks fold a page in the pool's stored type (ops/paged_attention
# ``_fold_page``): every kernel parity test runs over the three types with
# the same reference (float32 ``jax.numpy`` over the values the pool holds)
# and the SAME tolerance.  The kernels read 4e-7 to 6e-6 off it at these
# shapes; a fold that rounded its probabilities to bfloat16 reads 5e-3
# (bf16 pages) and 2.6e-2 (int8 pages), and one that split them into two
# bfloat16 parts for three reads 5e-5 on int8 pages
# (test_kernels.py::test_fold_with_bf16_rounded_probabilities_fails).
PAGED_POOLS = ("f32", "bf16", "int8")
PAGED_TOL = 2e-5


def paged_pools(pool: str, rng, shape, b: int):
    """K/V pools ``shape`` = [n_pages, kh, ps, hd] of one stored type from
    ``rng`` (a numpy Generator), for ``b`` rows.  Returns a namespace:
    ``k`` / ``v`` the pools as stored; ``kw`` the kernels' scale arguments
    and ``xkw`` the XLA references' (int8: per-row-and-channel scales);
    ``ref(pool)`` the array a float32 reference takes for a stored pool
    (bf16 pages as float32: the XLA twins would round their probabilities
    to the pool's type); ``new(x)`` new-token K/V the pool holds exactly
    (rounded to bfloat16 for a bf16 pool, so a reference that scatters
    float32 rows holds what the kernel wrote).  int8 pages need ``ps`` a
    multiple of 32, the write's window."""
    import types

    import jax.numpy as jnp

    f32 = jnp.float32
    ns = types.SimpleNamespace(kw={}, xkw={}, ref=lambda p: p,
                               new=lambda x: x)
    if pool == "int8":
        kh, hd = shape[1], shape[3]
        ns.k = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        ns.v = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.05, (b, kh, hd)), f32)
        vs = jnp.asarray(rng.uniform(0.01, 0.05, (b, kh, hd)), f32)
        ns.kw, ns.xkw = dict(kscale=ks, vscale=vs), dict(kv_scales=(ks, vs))
        return ns
    dt = jnp.bfloat16 if pool == "bf16" else f32
    ns.k = jnp.asarray(rng.standard_normal(shape), f32).astype(dt)
    ns.v = jnp.asarray(rng.standard_normal(shape), f32).astype(dt)
    if pool == "bf16":
        ns.ref = lambda p: p.astype(f32)
        ns.new = lambda x: x.astype(dt).astype(f32)
    return ns

