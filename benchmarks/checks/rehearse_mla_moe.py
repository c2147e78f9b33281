"""The CPU rehearsal of the family ``mla-moe`` through the whole harness.

    JAX_PLATFORMS=cpu python benchmarks/checks/rehearse_mla_moe.py \
        --seed 7 --seconds 3 --trace 1 [--control int8]

``run.py`` finds a rehearsal cell in ``rehearsal.json``, a file a PR that
adds a configuration may not edit; so the cell is stated here and handed to
``run.main`` in ``find_cell``'s place: ``tiny-mla-moe.offline-jobs`` =
``configs/tiny-mla-moe-rehearsal.json`` (latent attention, experts 4-7 of
16 held, a shared expert) x ``traffic/offline-jobs-s16.json`` at its
rehearsal sizes, limits ``limits/tiny-mla-moe-rehearsal.json``.  Kernels
interpreted (the flash kernel at two widths, the latent decode kernel);
the last line holds the count metrics only, ``expert_load_imbalance.offline``
among them.
"""

from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE.parent), str(HERE)]

CELL = {"name": "tiny-mla-moe.offline-jobs",
        "config": "tiny-mla-moe-rehearsal", "traffic": "offline-jobs-s16",
        "chips": 1, "limits": "tiny-mla-moe-rehearsal"}


def main(argv=None) -> int:
    import run as bench

    bench_json = bench.read_json(HERE.parent / "BENCHMARK.json")
    # the per-layer metrics this cell has to report are those of the cell it
    # rehearses
    for metric in bench_json["per_layer"]:
        if "kimi-k2.6-offline" in metric.get("workloads", ()):
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
