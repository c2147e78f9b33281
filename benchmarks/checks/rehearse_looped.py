"""The CPU rehearsal of the family ``looped-dense`` through the whole harness.

    JAX_PLATFORMS=cpu python benchmarks/checks/rehearse_looped.py \
        --seed 7 --seconds 3 --trace 1 [--control int8]

``run.py`` finds a rehearsal cell in ``rehearsal.json``, a file a PR that
adds a configuration may not edit; so the cell is stated here and handed to
``run.main`` in ``find_cell``'s place, as ``rehearse_mla_moe.py`` does:
``tiny-looped.offline-jobs`` = ``configs/tiny-looped-rehearsal.json`` (two
layers run three times over the same weights, sandwich norms, two heads of
128 with a KV head each, int8 pages: 6 cache layers) x
``traffic/offline-jobs-s4.json`` at its rehearsal sizes, limits
``limits/tiny-looped-rehearsal.json``.  Kernels interpreted (flash prefill,
paged decode over int8 pages); the last line holds the count metrics only.
It has to end ``correct: true``, and ``correct: false`` under
``--control int8``.
"""

from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE.parent), str(HERE)]

CELL = {"name": "tiny-looped.offline-jobs",
        "config": "tiny-looped-rehearsal", "traffic": "offline-jobs-s4",
        "chips": 1, "limits": "tiny-looped-rehearsal"}
REHEARSES = "ouro-2.6b-offline"


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
