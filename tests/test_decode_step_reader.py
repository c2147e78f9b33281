"""The benchmark's reader of the scheduler's decode byte count
(benchmarks/layer_metrics/decode_step_roofline.offline.py) on made-up
facts: benchmarks/checks/check_decode_step_reader.py, in process."""

import importlib.util
from pathlib import Path

import pytest

CHECK = (Path(__file__).resolve().parents[1] / "benchmarks" / "checks"
         / "check_decode_step_reader.py")


@pytest.fixture(scope="module")
def results():
    spec = importlib.util.spec_from_file_location("check_decode_step_reader",
                                                  CHECK)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.checks()


def test_the_reader_by_hand_with_the_counter(results):
    mine = {what: ok for what, ok in results.items()
            if not what.startswith("None")}
    assert len(mine) == 1 and all(mine.values()), mine


def test_the_reader_gives_none_without_what_it_reads(results):
    mine = {what: ok for what, ok in results.items()
            if what.startswith("None")}
    assert len(mine) == 4 and all(mine.values()), mine
