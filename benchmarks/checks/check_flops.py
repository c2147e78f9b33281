"""The copied FLOP and byte functions, as the dense family hands them to the
readers, agree with lmrs_tpu/utils/perf_model.py on both configurations today.

    JAX_PLATFORMS=cpu python benchmarks/checks/check_flops.py
"""

from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE.parent), str(HERE)]


def main() -> int:
    import run as bench_run
    from lmrs_tpu.utils import perf_model as pm

    bad = 0
    for name in ("mistral-7b-v0.3", "internlm2-1.8b"):
        flops, m = bench_run.families.of_config(
            HERE / "configs" / f"{name}.json")
        cfg = flops.model_config(name, m, {"max_seq_len": 2048})
        kvq, wq = m["kv"] == "int8", m["weights"] == "int8"
        pairs = {
            "matmul_params": (flops.matmul_params(m), pm.matmul_params(cfg)),
            "prefill_flops(1900)": (flops.prefill_flops(m, 1900),
                                    pm.prefill_flops(cfg, 1900)),
            "prefill_flops(512, head 1, kv 384)": (
                flops.prefill_flops(m, 512, 1, 384),
                pm.prefill_flops(cfg, 512, 1, 384)),
            "kv_bytes_per_token": (
                flops.kv_bytes_per_token(m),
                pm.kv_bytes_per_token(cfg) / (2 if kvq else 1)),
            "decode_step_bytes(24 x 2000)": (
                flops.decode_step_bytes(m, 48000),
                pm.decode_step_bytes(cfg, 48000, quantized=wq,
                                     kv_quantized=kvq)),
        }
        # the benchmark's per-request count against the program's prefill
        # count: same matmul and LM-head terms, attention within S+1 over S
        # (the program takes S^2/2 keys, the benchmark S(S+1)/2)
        mine = flops.request_flops(m, 1900, 0, 0)
        theirs = pm.prefill_flops(cfg, 1900, head_tokens=1)
        pairs["request_flops(1900,0,0) vs prefill_flops(head 1)"] = (
            round(mine / theirs, 3), 1.0)
        for what, (a, b) in pairs.items():
            ok = abs(a - b) <= 1e-9 * max(abs(a), abs(b), 1)
            bad += not ok
            print(f"{'ok ' if ok else 'BAD'} {name} {what}: {a} vs {b}")
    print("check_flops:", "FAILED" if bad else "passed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
