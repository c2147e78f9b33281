"""The one place the repo names JAX APIs that have moved between releases.

All call sites import ``shard_map`` / ``tpu_compiler_params`` from HERE
(the lmrs-lint deprecated-API sub-pass flags direct ``jax.shard_map`` /
``pltpu.CompilerParams`` use anywhere else), so the next rename is a
one-file change.  Only the installed JAX is supported: no branch here
probes for another version.
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map``, keyword-only."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def tpu_compiler_params(**kwargs):
    """``pltpu.CompilerParams`` (``dimension_semantics`` et al.)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)
