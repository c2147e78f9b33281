"""The CPU rehearsal of the family ``gqa-swa-moe`` through the whole harness.

    JAX_PLATFORMS=cpu python benchmarks/checks/rehearse_swa_moe.py \
        --seed 7 --seconds 3 --trace 1 [--control fp8]

``run.py`` finds a rehearsal cell in ``rehearsal.json``, a file a PR that
adds a configuration may not edit; so the cell is stated here and handed to
``run.main`` in ``find_cell``'s place, as ``rehearse_mla_moe.py`` does:
``tiny-swa-moe.offline-jobs`` = ``configs/tiny-swa-moe-rehearsal.json``
(three window layers of 128 to a full one behind a dense leading layer,
experts 4-7 of 16 held, a shared expert, two query heads of 128 on one KV
head) x ``traffic/offline-jobs-8k-s4.json`` at its rehearsal sizes, limits
``limits/tiny-swa-moe-rehearsal.json``.  Kernels interpreted (the banded and
the full flash kernel, the windowed decode walk over a ring of 2 pages a
slot); the last line holds the count metrics only,
``window_walk_share.offline`` and ``expert_load_imbalance.offline`` among
them.  It has to end ``correct: true``, and ``correct: false`` under
``--control fp8``.
"""

from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE.parent), str(HERE)]

CELL = {"name": "tiny-swa-moe.offline-jobs",
        "config": "tiny-swa-moe-rehearsal", "traffic": "offline-jobs-8k-s4",
        "chips": 1, "limits": "tiny-swa-moe-rehearsal"}
REHEARSES = "k-exaone-offline"


def main(argv=None) -> int:
    import run as bench

    bench_json = bench.read_json(HERE.parent / "BENCHMARK.json")
    # the per-layer metrics this cell has to report are those of the cell it
    # rehearses
    for metric in bench_json["per_layer"]:
        if REHEARSES in metric.get("workloads", ()):
            metric["workloads"].append(CELL["name"])
    found = bench.find_cell
    bench.find_cell = lambda name: ((CELL, bench_json, True)
                                    if name == CELL["name"] else found(name))
    try:
        return bench.main(["--workload", CELL["name"],
                           *(sys.argv[1:] if argv is None else argv)])
    finally:
        bench.find_cell = found


if __name__ == "__main__":
    import os
    import threading

    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc) if threading.active_count() > 1 else sys.exit(rc)
