"""The benchmark's readers of the scheduler's dispatch records
(benchmarks/layer_metrics/{prefill_pad_waste,host_share,cold_dispatch_share}
.offline.py) against recorded facts and against facts of an older tree:
benchmarks/checks/check_layer_readers.py, its part that needs no engine."""

import importlib.util
from pathlib import Path

import pytest

CHECK = (Path(__file__).resolve().parents[1] / "benchmarks" / "checks"
         / "check_layer_readers.py")


@pytest.fixture(scope="module")
def results():
    spec = importlib.util.spec_from_file_location("check_layer_readers",
                                                  CHECK)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, mod.check_recorded()


@pytest.mark.parametrize("name", ["prefill_pad_waste.offline",
                                  "host_share.offline",
                                  "cold_dispatch_share.offline"])
def test_reader_on_recorded_facts_and_on_an_older_tree(results, name):
    mod, checks = results
    assert name in mod.READERS
    mine = {what: ok for what, ok in checks.items() if what.startswith(name)}
    assert len(mine) == 3, mine  # the value, None without, None when off
    assert all(mine.values()), mine


def test_recorded_window_has_padding_to_measure(results):
    _, checks = results
    assert all(checks.values()), checks
