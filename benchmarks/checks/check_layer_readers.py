"""The three readers of the scheduler's dispatch records, against facts that
were recorded and facts that lack what they read.

    JAX_PLATFORMS=cpu python benchmarks/checks/check_layer_readers.py
    JAX_PLATFORMS=cpu python benchmarks/checks/check_layer_readers.py --record

``data/rehearsal_facts.json`` holds the ``counters`` and the ``anatomy`` that
one CPU rehearsal run (``tiny.offline-jobs``, seed 7, ``--trace 1``) handed
the readers; ``--record`` makes it anew.  Checked:

* on the recorded facts each reader gives what its docstring's formula
  gives, worked out here from the recorded numbers by hand;
* on facts of a tree from before the records (the five counters and the
  ``programs`` block taken out) each reader gives None, and raises nothing;
* the CPU rehearsal's last line holds ``prefill_pad_waste.offline`` (a
  count, so a CPU run may print it), equal to what the counters of its own
  log line give, and neither of the two shares of time.
"""

from __future__ import annotations

import contextlib
import copy
import io
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
for d in (HERE.parent, HERE):
    if str(d) not in sys.path:
        sys.path.insert(0, str(d))

RECORDED = HERE / "data" / "rehearsal_facts.json"
READERS = ("prefill_pad_waste.offline", "host_share.offline",
           "cold_dispatch_share.offline")
NEW_COUNTERS = ("prefill_dispatches", "prefill_query_tokens",
                "prefill_token_slots", "cold_dispatches", "cold_seconds")
REHEARSAL = ["--workload", "tiny.offline-jobs", "--seed", "7", "--seconds",
             "3", "--trace", "1"]


def reader(name: str):
    import run as bench

    return bench.load_module(HERE / "layer_metrics" / f"{name}.py",
                             f"check_metric_{name.replace('.', '_')}")


def close(a, b, rel: float = 1e-9) -> bool:
    return a is not None and abs(a - b) <= rel * max(abs(a), abs(b), 1e-12)


def expected(facts: dict) -> dict:
    """Each metric by its formula in BENCHMARK.json's terms, from the
    recorded numbers."""
    c, an = facts["counters"], facts["anatomy"]
    seg = an["segments_ms"]
    return {
        "prefill_pad_waste.offline": 100.0 * (
            1.0 - c["prefill_query_tokens"] / c["prefill_token_slots"]),
        "host_share.offline": 100.0 * (
            an["wall_ms"] - seg["dispatch"] - seg["fetch"]) / an["wall_ms"],
        "cold_dispatch_share.offline": 100.0 * (
            c["cold_seconds"] / c["run_seconds"]),
    }


def older_tree(facts: dict) -> dict:
    """The same facts as a tree from before PR 25 gives them."""
    old = copy.deepcopy(facts)
    for k in NEW_COUNTERS:
        old["counters"].pop(k, None)
    old["anatomy"].pop("programs", None)
    return old


def check_recorded() -> dict[str, bool]:
    facts = json.loads(RECORDED.read_text())
    want = expected(facts)
    old = older_tree(facts)
    off = {"counters": old["counters"],
           "anatomy": {"object": "anatomy", "enabled": False}}
    checks = {}
    with contextlib.redirect_stderr(io.StringIO()):
        for name in READERS:
            read = reader(name).read
            checks[f"{name}: the recorded facts give {want[name]:.6f}"] = \
                close(read(facts), want[name])
            checks[f"{name}: None on a tree without the records"] = \
                read(old) is None
            checks[f"{name}: None with the anatomy switched off"] = \
                read(off) is None
    c = facts["counters"]
    checks["the recorded window dispatched prefills and padded some"] = (
        0 < c["prefill_query_tokens"] < c["prefill_token_slots"]
        and c["prefill_dispatches"] > 0)
    return checks


def run_rehearsal(capture=None) -> tuple[dict, str]:
    """One CPU rehearsal run through run.main: its last line and its log.
    ``capture`` is handed the facts the readers were given."""
    import run as bench

    real = bench.load_module

    def load(path, name):
        mod = real(path, name)
        if capture is not None and Path(path).parent.name == "layer_metrics":
            inner = mod.read

            def read(facts):
                capture(facts)
                return inner(facts)
            mod.read = read
        return mod

    bench.load_module = load
    out, err = io.StringIO(), io.StringIO()
    try:
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            rc = bench.main(REHEARSAL)
    finally:
        bench.load_module = real
    if rc != 0:
        raise SystemExit(f"the rehearsal run ended {rc}:\n{err.getvalue()}")
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()


def check_rehearsal() -> dict[str, bool]:
    line, log = run_rehearsal()
    counters = json.loads(next(
        ln for ln in log.splitlines() if "counters over the window: " in ln
    ).split("counters over the window: ", 1)[1])
    got = line["metrics"].get("prefill_pad_waste.offline", {})
    return {
        "the rehearsal's last line holds prefill_pad_waste.offline": bool(got),
        "and it is what the counters of its log line give": close(
            got.get("value"), 100.0 * (1.0 - counters["prefill_query_tokens"]
                                       / counters["prefill_token_slots"])),
        "in percent": got.get("unit") == "%",
        "a CPU run prints no share of time": not (
            {"host_share.offline", "cold_dispatch_share.offline"}
            & set(line["metrics"])),
        "the log names the window's programs and keys":
            "programs over the window: " in log,
    }


def record() -> None:
    seen: dict = {}
    run_rehearsal(capture=lambda facts: seen.update(
        counters=facts["counters"], anatomy=facts["anatomy"]))
    an = seen["anatomy"]
    RECORDED.write_text(json.dumps({
        "what": "counters and anatomy that the readers were handed by "
                "`run.py " + " ".join(REHEARSAL) + "` on the CPU: input "
                "for the readers' arithmetic, no measurement of a device",
        "counters": seen["counters"],
        "anatomy": {k: an[k] for k in ("object", "enabled", "iterations",
                                       "wall_ms", "residual_ms",
                                       "segments_ms", "programs")},
    }, indent=1) + "\n")
    print(f"recorded {RECORDED}")


def main() -> int:
    if "--record" in sys.argv[1:]:
        record()
        return 0
    checks = check_recorded()
    if "--recorded-only" not in sys.argv[1:]:
        checks.update(check_rehearsal())
    for what, ok in checks.items():
        print(("ok  " if ok else "BAD ") + what)
    bad = sum(not ok for ok in checks.values())
    print("check_layer_readers:", "FAILED" if bad else "passed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
