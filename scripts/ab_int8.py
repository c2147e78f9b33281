"""ABBA: int8 weight-only quantization vs bf16 at bench-1b scale.

Two engines (params differ), alternating decode-heavy waves A B B A.
Run: python scripts/ab_int8.py
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

    def make(quant):
        return JaxEngine(EngineConfig(
            backend="jax", max_tokens=128, max_batch_slots=24,
            retry_delay=0.0, seed=0, page_size=512, num_pages=1,
            decode_block=128, prefill_chunk=4096, quantize=quant), model)

    a = make(None)     # bf16
    b = make("int8")
    n, max_new = 48, 128  # decode-heavy: int8 pays in the weight stream
    wave(a, n, max_new, "warmA", words=(160, 161))
    wave(b, n, max_new, "warmB", words=(160, 161))

    rounds = []
    for r in range(3):
        res = {}
        for arm, eng in (("A", a), ("B", b), ("B2", b), ("A2", a)):
            res[arm] = wave(eng, n, max_new, f"{r}{arm}", words=(160, 161))
        am = (res["A"] + res["A2"]) / 2
        bm = (res["B"] + res["B2"]) / 2
        rounds.append((am, bm))
        print(f"round {r}: bf16={am:.2f}s int8={bm:.2f}s "
              f"int8 wins {100*(am-bm)/am:+.1f}% ({res})", flush=True)
    am = np.mean([r[0] for r in rounds]); bm = np.mean([r[1] for r in rounds])
    print(f"MEAN bf16={am:.2f}s int8={bm:.2f}s  int8 wins {100*(am-bm)/am:+.1f}%")


if __name__ == "__main__":
    main()
