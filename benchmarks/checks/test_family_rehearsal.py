"""A second model family through the whole harness, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/checks/test_family_rehearsal.py -q

``tiny-moe.offline-jobs`` (rehearsal.json) is a routed model of the family
``moe-topk``: its files were added after the seam, and no file that was there
knows it.  Whole runs of ``run.main`` (as test_checks.py makes them): the
engine's own tokens come out ``correct: true``; the family's control (the
reference with fp8 weights in the program's place) and the timed path broken
underneath come out ``correct: false`` by the family's reference: the router
the engine is given rolled by one expert (every token sent to other experts
than the reference sends it to), one served token altered in every request,
and one altered in one slot's request alone (one request in three of a
wave): the family leaves out the tokens whose route is too close a call, by
the reference's own margins, and compares every other token in full, so a
fault in a few tokens shows.
"""

from __future__ import annotations

import pytest
from test_checks import _alter_tokens, _run_main, _with_params

CELL = "tiny-moe.offline-jobs"
SEED = 7


def _router_rolled(params):
    import jax
    import jax.numpy as jnp

    def roll(path, x):
        return jnp.roll(x, 1, axis=-1) \
            if "router" in jax.tree_util.keystr(path) else x
    return jax.tree_util.tree_map_with_path(roll, params)


def _reading(line: dict) -> dict:
    return line["checks"]["flipped_gap_msq"]


def test_routed_family_runs_and_is_correct():
    line = _run_main(CELL, SEED)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert _reading(line)["value"] <= _reading(line)["limit"]
    # 7 requests x 32 served tokens; the family leaves a few out, not most
    assert 112 <= line["checks"]["compared_tokens"]["value"] < 224


def test_routed_familys_control_comes_out_not_correct():
    line = _run_main(CELL, SEED, "--control", "fp8")
    assert line["correct"] is False, line["checks"]
    assert _reading(line)["value"] > _reading(line)["limit"]


@pytest.mark.parametrize("fault, plant", [
    ("router_of_another_expert", lambda: _with_params(_router_rolled)),
    ("token_altered", _alter_tokens),
    ("token_altered_in_one_slot", lambda: _alter_tokens(first_only=True)),
])
def test_broken_routed_run_comes_out_not_correct(fault, plant):
    undo = plant()
    try:
        line = _run_main(CELL, SEED)
    finally:
        undo()
    got = _reading(line)
    print(f"{CELL} {fault}: flipped_gap_msq {got['value']:.6f} against "
          f"limit {got['limit']}")
    assert line["correct"] is False and got["value"] > got["limit"]
