"""Device probe, compile-cache placement and child-process environment —
the three things every entry point settles before first backend use.

``on_tpu()`` feeds the kernel-routing gates (scheduler._pick_kernel,
transformer._use_flash_prefill)."""

from __future__ import annotations

import os
from pathlib import Path

# jax is imported inside the functions that need it: the supervisor and
# autoscaler parents import this module for ``child_env`` and must stay
# off JAX (a parent that initialises a backend holds the chip its child
# needs)

# the checkout root (parent of the ``lmrs_tpu`` package): nothing is
# pip-installed, so children and caches are placed relative to this
REPO_ROOT = Path(__file__).resolve().parents[2]
COMPILE_CACHE_DIR = REPO_ROOT / ".jax_cache"


def on_tpu() -> bool:
    """True iff the default JAX backend is a TPU.  An exact test: a
    device-init error propagates rather than reading as "not a TPU" (which
    would silently select the CPU branch of every kernel gate)."""
    import jax

    return jax.devices()[0].platform == "tpu"


def setup_compile_cache() -> None:
    """Place JAX's persistent compilation cache.  Call before first
    backend use, from every entry point that builds an engine.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this sets
    no directory in code.  Unset: one fixed path inside the checkout (the
    path is part of the cache key, so a directory that moves never hits)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    # engine programs are many and mid-sized: cache all that took >= 1 s
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # A Pallas TPU kernel is serialized INTO its program with its debug
    # locations, which carry Python frames of whatever call stack traced
    # the kernel FIRST in this process — and JAX's cache key strips
    # locations from the outer module only.  The same scheduler program
    # then hashes differently in a server child and in a process that ran
    # something else first (measured on the chip, PR 22: 0, then 1, hits
    # of 30 compile requests in the second process).  No frames in
    # locations makes the key a function of the program alone; HLO ops
    # keep their name-stack ``op_name``, they lose file:line.
    jax.config.update("jax_traceback_in_locations_limit", 0)


def child_env(**extra: str) -> dict[str, str]:
    """``os.environ`` for a ``python -m lmrs_tpu...`` child, with the
    checkout root on ``PYTHONPATH`` so the child resolves the package
    whatever its cwd (there is no installed copy to fall back on)."""
    env = dict(os.environ, **extra)
    root = str(REPO_ROOT)
    parts = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    if root not in parts:
        env["PYTHONPATH"] = os.pathsep.join([root, *parts])
    return env
