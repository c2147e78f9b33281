"""The family ``looped-dense`` through the whole benchmark harness on the
CPU (``benchmarks/checks/rehearse_looped.py``: two layers run three times,
int8 pages, the flash and paged-decode kernels interpreted), in a child
process: the harness sets process-wide state (kernel mode, logging)."""

import json
import subprocess
import sys
from pathlib import Path

from lmrs_tpu.utils.platform import child_env

ROOT = Path(__file__).resolve().parents[1]


def test_the_looped_rehearsal_cell_ends_correct():
    run = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/checks/rehearse_looped.py"),
         "--seed", "7", "--seconds", "1", "--trace", "1"],
        cwd=ROOT, env=child_env(JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    # counts only from a CPU run: no time, no share, no roofline
    assert set(line["metrics"]) == {"decode_occupancy.offline",
                                    "prefill_pad_waste.offline"}
    assert line["checks"]["compared_tokens"]["value"] >= 112
    log = run.stderr
    counters = json.loads(log.split("counters over the window: ", 1)[1]
                          .splitlines()[0])
    programs = json.loads(log.split("programs over the window: ", 1)[1]
                          .splitlines()[0])
    # 6 cache layers: a prefill dispatch is 6 layer applications, a decode
    # block of 32 steps 192
    assert programs["prefill"]["layer_passes"] == \
        6 * programs["prefill"]["dispatches"]
    assert programs["decode"]["layer_passes"] == \
        6 * 32 * programs["decode"]["dispatches"]
    assert counters["layer_passes"] == sum(
        p["layer_passes"] for p in programs.values())
