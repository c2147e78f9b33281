"""Speculation overhead at bench-1b scale (random weights => ~zero draft
acceptance: this measures pure speculation cost; acceptance upside needs a
real checkpoint and is demonstrated separately on the trained tiny model).

Three engines: speculate_k in {0, 4, 8}; interleaved A B C C B A waves.
Run: python scripts/ab_spec.py
The spec arm takes the tree path (ISSUE 19) when LMRS_SPEC_TREE is
unset/1 and reports its accept/dispatch block; LMRS_SPEC_TREE=0 is the
linear-speculation A/B control for the same command line.
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

    def make(k):
        return JaxEngine(EngineConfig(
            backend="jax", max_tokens=128, max_batch_slots=24,
            retry_delay=0.0, seed=0, page_size=512, num_pages=1,
            decode_block=128, prefill_chunk=4096, speculate_k=k), model)

    import sys
    spec_k = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    # pairwise (0 vs spec_k): three 1B engines OOM a 16 GB chip
    engines = {0: make(0), spec_k: make(spec_k)}
    n, max_new = 48, 128
    for k, e in engines.items():
        wave(e, n, max_new, f"warm{k}", words=(160, 161))

    # two workloads per round (VERDICT r3 decision protocol): high-entropy
    # prompts measure speculation's pure overhead; repetitive prompts are
    # the acceptance-rich case where it must show >= 1.2x to ship ON
    for rep, label in ((False, "high-entropy"), (True, "repetitive")):
        sums = {k: [] for k in engines}
        for r in range(3):
            order = [0, spec_k, spec_k, 0]
            for k in order:
                dt = wave(engines[k], n, max_new,
                          f"{label}-{r}-{k}-{len(sums[k])}",
                          words=(160, 161), repetitive=rep)
                sums[k].append(dt)
            line = "  ".join(f"k={k}: {np.mean(v):.2f}s"
                             for k, v in sums.items())
            print(f"[{label}] round {r}: {line}", flush=True)
        speedup = np.mean(sums[0]) / np.mean(sums[spec_k])
        for k, v in sums.items():
            sch = engines[k]._scheduler
            acc = sch.metrics.get("spec_accepted_tokens", 0)
            st = sch._spec_tree_report()
            tree = (f"  tree: accept/step={st['accept_per_step']}"
                    f" mean_depth={st['mean_accept_depth']}"
                    f" dispatches={st['dispatches']}"
                    if st["enabled"] else "")
            print(f"[{label}] k={k}: mean {np.mean(v):.2f}s  "
                  f"accepted={acc}{tree}")
        print(f"[{label}] speculation speedup: {speedup:.2f}x "
              f"({'WIN' if speedup >= 1.2 else 'keep OFF'})", flush=True)


if __name__ == "__main__":
    main()
