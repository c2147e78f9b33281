"""In-process ABBA: packed vs per-prompt prefill at bench-1b scale.

One engine; sched._pack_prefill toggled between runs (both program families
compile once).  Order A B B A per round; map-stage wall per arm.
Run on the real chip: python scripts/ab_pack.py [max_new]
LMRS_AB_KV=int8: both arms run int8 KV pools (the r4 composition row —
packed+int8 vs unpacked+int8, VERDICT r3 item 3).
"""
import _pathfix  # noqa: F401  (repo-root import shim)
from lmrs_tpu.utils.platform import setup_compile_cache

setup_compile_cache()
import sys
import time

import numpy as np

from lmrs_tpu.config import EngineConfig, model_preset
from lmrs_tpu.engine.jax_engine import JaxEngine
from lmrs_tpu.utils.logging import setup_logging

from _bench_common import wave

from lmrs_tpu.utils.env import env_str


def main():
    max_new = int(sys.argv[1]) if len(sys.argv) > 1 else 16
    setup_logging(quiet=True)
    model = model_preset("bench-1b")
    kv = env_str("LMRS_AB_KV") or None
    eng = JaxEngine(EngineConfig(
        backend="jax", max_tokens=max_new, max_batch_slots=24,
        retry_delay=0.0, seed=0, page_size=512, num_pages=1,
        decode_block=max_new, prefill_chunk=4096, kv_quantize=kv), model)
    if kv:
        print(f"kv_quantize={kv} (both arms)", flush=True)
    sched = eng._scheduler
    n = 48  # two full admission waves

    # warm BOTH paths (compile everything)
    sched._pack_prefill = True
    wave(eng, n, max_new, "warmA", words=(60, 231))
    sched._pack_prefill = False
    wave(eng, n, max_new, "warmB", words=(60, 231))

    rounds = []
    for r in range(3):
        res = {}
        for arm in ("A", "B", "B2", "A2"):
            sched._pack_prefill = arm.startswith("A")
            res[arm] = wave(eng, n, max_new, f"{r}{arm}", words=(60, 231))
        a = (res["A"] + res["A2"]) / 2
        b = (res["B"] + res["B2"]) / 2
        rounds.append((a, b))
        print(f"round {r}: packed={a:.2f}s unpacked={b:.2f}s "
              f"delta={100*(b-a)/b:+.1f}% ({res})", flush=True)
    am = np.mean([r[0] for r in rounds]); bm = np.mean([r[1] for r in rounds])
    print(f"MEAN packed={am:.2f}s unpacked={bm:.2f}s  packed wins {100*(bm-am)/bm:+.1f}%")


if __name__ == "__main__":
    main()
