"""ABBA: int8 KV-cache pages vs bf16 at bench-1b scale (kv_quantize=int8,
both arms with int8 weights — the bench default).  Decode-heavy waves.
Run: python scripts/ab_kv_int8.py
"""
import _pathfix  # noqa: F401  (repo-root import shim)
from lmrs_tpu.utils.platform import setup_compile_cache

setup_compile_cache()
import time

import numpy as np

from lmrs_tpu.config import EngineConfig, model_preset
from lmrs_tpu.engine.jax_engine import JaxEngine
from lmrs_tpu.utils.logging import setup_logging

from _bench_common import wave


def main():
    setup_logging(quiet=True)
    model = model_preset("bench-1b")

    def make(kv):
        return JaxEngine(EngineConfig(
            backend="jax", max_tokens=128, max_batch_slots=24,
            retry_delay=0.0, seed=0, page_size=512, num_pages=1,
            decode_block=128, prefill_chunk=4096, quantize="int8",
            kv_quantize=kv), model)

    engines = {"bf16kv": make(None), "int8kv": make("int8")}
    n, max_new = 48, 128
    for name, e in engines.items():
        wave(e, n, max_new, f"warm-{name}", words=(160, 161))
    sums = {k: [] for k in engines}
    for r in range(3):
        for name in ["bf16kv", "int8kv", "int8kv", "bf16kv"]:
            dt = wave(engines[name], n, max_new,
                      f"r{r}-{name}-{len(sums[name])}", words=(160, 161))
            sums[name].append(dt)
        line = "  ".join(f"{k}={np.mean(v):.2f}s" for k, v in sums.items())
        print(f"round {r}: {line}", flush=True)
    a, b = np.mean(sums["bf16kv"]), np.mean(sums["int8kv"])
    print(f"MEAN bf16kv={a:.2f}s int8kv={b:.2f}s  "
          f"int8kv {'wins' if b < a else 'LOSES'} {abs(1 - a/b)*100:+.1f}%")


if __name__ == "__main__":
    main()
