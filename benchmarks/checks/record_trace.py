"""Record the small trace that checks/check_trace_reduce.py reads.

    chiprun -- python benchmarks/checks/record_trace.py

Run once on the chip; it writes benchmarks/data/small.xplane.pb (a few tens
of KB: three jitted programs, one of them the engine's flash kernel, with an
idle gap of known length between them) through chiprun_out/.
"""

from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE.parent), str(HERE)]


def main() -> int:
    import jax
    import jax.numpy as jnp

    from lmrs_tpu.ops.flash_attention import flash_attention
    import trace_reduce

    @jax.jit
    def matmul_chain(x):
        for _ in range(4):
            x = jnp.tanh(x @ x) * 0.01
        return x

    @jax.jit
    def flash(q, k, v, n):
        return flash_attention(q, k, v, n)

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    q = jnp.ones((1, 1024, 8, 128), jnp.bfloat16)
    kv = jnp.ones((1, 1024, 2, 128), jnp.bfloat16)
    n = jnp.array([1024], jnp.int32)
    matmul_chain(x).block_until_ready()
    flash(q, kv, kv, n).block_until_ready()
    out = Path("chiprun_out/small_trace")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        matmul_chain(x).block_until_ready()
        with jax.profiler.TraceAnnotation("host_sleep"):
            time.sleep(0.05)
        flash(q, kv, kv, n).block_until_ready()
        matmul_chain(x).block_until_ready()
    jax.profiler.stop_trace()
    f = trace_reduce.newest_trace(out)
    shutil.copy(f, "chiprun_out/small.xplane.pb")
    print(trace_reduce.describe(f))
    import json
    print(json.dumps(trace_reduce.reduce_file(f), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
