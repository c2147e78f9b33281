"""``decode_step_roofline.offline`` against facts worked out by hand and
against facts that lack what it reads.

    python benchmarks/checks/check_decode_step_reader.py

The facts are made up here: no run, no device, no JAX.  Checked:

* with the scheduler's ``decode_hbm_bytes`` over the window and a trace,
  the reader gives 100 x bytes / (HBM bandwidth x the device seconds of
  the ``jit_decode`` programs), and leaves the other programs out (the
  prefill, a speculative block's ``jit_spec_decode``);
* on facts of a program without the counter (the parent of the PR that
  brought it), of an untraced run, and of a trace without a decode
  program, it gives None and raises nothing.
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
READER = HERE / "layer_metrics" / "decode_step_roofline.offline.py"


def reader():
    spec = importlib.util.spec_from_file_location(
        "check_metric_decode_step_roofline", READER)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def checks() -> dict[str, bool]:
    read = reader()
    # 640 steps of 7.4 GB (mistral's int8 weights and 24 rows' KV pages)
    nbytes = 640 * 7.4e9
    facts = {"counters": {"decode_hbm_bytes": nbytes, "decode_tokens": 9},
             "peaks": {"hbm_bytes_per_s": 819e9},
             "trace": {"programs": {"jit_decode": 6.0, "jit_decode_1": 1.5,
                                    "jit_spec_decode": 3.0,
                                    "jit_prefill": 12.0}}}
    want = 100.0 * nbytes / (819e9 * 7.5)
    got = read(facts)
    parent = {**facts, "counters": {"decode_tokens": 9}}
    return {
        "the bytes over 819 GB/s x the 7.5 s of jit_decode*":
            got is not None and abs(got - want) <= 1e-9 * want,
        "None without decode_hbm_bytes (the parent)": read(parent) is None,
        "None on an untraced run": read({**facts, "trace": None}) is None,
        "None off the chip (no peaks)": read({**facts, "peaks": None}) is None,
        "None without a decode program in the trace": read(
            {**facts, "trace": {"programs": {"jit_prefill": 12.0}}}) is None,
    }


def main() -> int:
    results = checks()
    for what, ok in results.items():
        print(("ok  " if ok else "BAD ") + what)
    bad = sum(not ok for ok in results.values())
    print("check_decode_step_reader:", "FAILED" if bad else "passed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
