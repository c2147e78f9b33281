"""The family ``gqa-swa-moe`` through the whole benchmark harness on the CPU
(``benchmarks/checks/rehearse_swa_moe.py``: three window layers of 128 to a
full one, a ring of 2 pages a slot, the banded and the full flash kernel and
the windowed decode walk interpreted), in a child process: the harness sets
process-wide state (kernel mode, logging)."""

import json
import subprocess
import sys
from pathlib import Path

from lmrs_tpu.utils.platform import child_env

ROOT = Path(__file__).resolve().parents[1]


def test_the_swa_moe_rehearsal_cell_ends_correct():
    run = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/checks/rehearse_swa_moe.py"),
         "--seed", "7", "--seconds", "1", "--trace", "1"],
        cwd=ROOT, env=child_env(JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    # counts only from a CPU run: no time, no share, no roofline
    assert set(line["metrics"]) == {
        "decode_occupancy.offline", "prefill_pad_waste.offline",
        "expert_load_imbalance.offline", "window_walk_share.offline"}
    assert line["checks"]["compared_tokens"]["value"] >= 112
    log = run.stderr
    counters = json.loads(log.split("counters over the window: ", 1)[1]
                          .splitlines()[0])
    cache = json.loads(log.split("window cache: ", 1)[1].splitlines()[0])
    # 4 slots x a ring of 2 pages x 4 window layers, whatever the traffic
    assert cache["cache_pages_window"] == 4 * 2 * 4
    assert (cache["full_layers"], cache["window_layers"]) == (1, 4)
    # a window layer walks at most 2 pages a step, the full layer 6-8 at
    # these 700-900-token contexts
    share = line["metrics"]["window_walk_share.offline"]["value"]
    assert 20 < share < 40
    assert counters["kv_pages_window"] <= 2 * 4 * counters["decode_tokens"] \
        * 4  # rows x steps, idle rows' steps included, is the upper bound
    assert counters["flash_blocks_skipped"] > 0


def test_the_window_readers_by_hand_and_without_their_counters():
    """benchmarks/checks/check_window_readers.py: the two readers PR 35
    brought, on made-up facts, in a child (it loads the harness)."""
    run = subprocess.run(
        [sys.executable,
         str(ROOT / "benchmarks/checks/check_window_readers.py")],
        cwd=ROOT, env=child_env(JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert run.returncode == 0, run.stdout[-2000:] + run.stderr[-2000:]
    assert "check_window_readers: passed" in run.stdout
    assert run.stdout.count("ok  ") == 7 and "BAD" not in run.stdout
