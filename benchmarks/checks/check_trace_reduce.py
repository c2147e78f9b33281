"""trace_reduce.py against the small recorded trace (data/small.xplane.pb).

    JAX_PLATFORMS=cpu python benchmarks/checks/check_trace_reduce.py

The trace was recorded on a v5e by checks/record_trace.py: inside a
``bench_window`` span, a jitted chain of four 2048^3 bf16 matmuls, a 50 ms
host sleep under the span ``host_sleep``, the engine's flash kernel, and the
chain again.  The numbers below were read off that trace by hand
(trace_reduce.describe) when it was recorded.  In it the device's clock
leads the host's by about 0.9 ms (the first chain's device event starts
0.8 ms before the host call that launched it), so the first chain falls
before the host's window and is clipped away: one chain and the flash
kernel are what the window holds.  A skew of a millisecond is nothing to a
window of tens of seconds and everything to one of 54 ms.
"""

from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE)]


def close(a: float, b: float, rel: float = 1e-3) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b))


def main() -> int:
    import trace_reduce

    r = trace_reduce.reduce_file(HERE / "data" / "small.xplane.pb")
    checks = {
        "window is the bench_window span": close(r["window_s"], 0.054368),
        "busy is the union of the device ops": close(r["busy_s"], 0.00042029,
                                                     5e-3),
        "one device": r["devices"] == 1,
        "program jit_matmul_chain, the one run inside the window": close(
            r["programs"]["jit_matmul_chain"], 0.00037404, 5e-3),
        "program jit_flash": close(r["programs"]["jit_flash"], 4.679e-5, 5e-3),
        "kernel flash_attention found by name": close(
            sum(v for k, v in r["kernels"].items()
                if "flash_attention" in k), 3.4886e-5, 5e-3),
        "no kernel but the flash kernel": all(
            "flash_attention" in k for k in r["kernels"]),
        "longest idle gap is the host sleep": (
            r["idle_gaps"][0][0] == "host_sleep"
            and 0.050 <= r["idle_gaps"][0][1] <= 0.0535),
        "second gap: the host launching the chain": (
            r["idle_gaps"][1][0] == "PjitFunction(matmul_chain)"),
        "idle share about 99.2 percent": close(
            1 - r["busy_s"] / r["window_s"], 0.99227, 1e-3),
        "top device op is a matmul fusion": r["device_ops"][0][0].startswith(
            "fusion"),
    }
    for what, ok in checks.items():
        print(("ok  " if ok else "BAD ") + what)
    bad = sum(not ok for ok in checks.values())
    print("check_trace_reduce:", "FAILED" if bad else "passed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
