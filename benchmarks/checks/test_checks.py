"""What decides ``correct`` is shown to fail, at a size a test run can hold.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/checks/test_checks.py -q
    JAX_PLATFORMS=cpu python benchmarks/checks/test_checks.py

On the CPU, at the tiny rehearsal shapes (configs/tiny-rehearsal.json and
configs/tiny-tp2-rehearsal.json, in no cell), with the Pallas kernels
interpreted.  The readings at the cells' own sizes are made on the chip by
checks/readings.py and are in PERF.md.  Every case is a whole run of
run.main, the harness's look for a chip skipped (a rehearsal cell), whose
last line has to say ``correct: false``:

* the control: ``--control fp8`` puts the plain reference with fp8 weights
  in the program's place, on three seeds, while the engine's own tokens stay
  under the limit (at this size int8 weights read 0.01-0.11 against the
  engine's 0.005-0.02, too close to stand as the control);
* the timed path broken underneath: one served token altered where the
  engine hands its results over, in every request and in one slot's alone;
  the norm weights the engine is given
  zeroed (a norm weight that is skipped); the int8 scales it is given
  rolled by one output channel (a scale taken from another channel), on the
  sharded int8 rehearsal configuration.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import sys
from pathlib import Path

# the sharded rehearsal cell takes two (virtual) CPU devices, and the count
# is fixed when JAX starts
if "jax" not in sys.modules:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=2")

HERE = Path(__file__).resolve().parents[1]
for d in (HERE.parent, HERE):
    if str(d) not in sys.path:
        sys.path.insert(0, str(d))

SEEDS = (2147483659, 3000000019, 7)


def _run_main(workload: str, seed: int, *more: str) -> dict:
    import run as bench

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", "2", "--trace", "0", *more])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_control_comes_out_not_correct_and_the_engine_correct():
    for seed in SEEDS:
        sound = _run_main("tiny.offline-jobs", seed)
        ctrl = _run_main("tiny.offline-jobs", seed, "--control", "fp8")
        print(f"seed {seed}: engine {sound['checks']['logit_gap_max']}, "
              f"fp8 control {ctrl['checks']['logit_gap_max']}")
        assert sound["correct"] is True, sound["checks"]
        assert ctrl["correct"] is False, ctrl["checks"]


def _alter_one_token(monkeypatch_target, first_only: bool = False):
    """Wrap JaxEngine.generate_batch: the third served token of every
    request (``first_only``: of the first request of each call alone, one
    slot among the batch's) comes out as another id, in the result and in
    the stream."""
    from dataclasses import replace

    from tokenizer import ID_BASE

    real = monkeypatch_target.generate_batch

    def other(ch: str) -> str:
        return chr(ID_BASE + 3 + (ord(ch) - ID_BASE + 97) % 200)

    def broken(self, requests, on_result=None, on_tokens=None):
        seen: dict[int, int] = {}
        only = requests[0].request_id if first_only else None

        def hit(rid) -> bool:
            return only is None or rid == only

        def tokens(rid, delta):
            at = seen.get(rid, 0)
            if at <= 2 < at + len(delta) and hit(rid):
                i = 2 - at
                delta = delta[:i] + other(delta[i]) + delta[i + 1:]
            seen[rid] = at + len(delta)
            on_tokens(rid, delta)

        def result(res, submit):
            on_result(alter(res), submit)

        def alter(res):
            t = res.text
            return replace(res, text=t[:2] + other(t[2]) + t[3:]) \
                if len(t) > 2 and hit(res.request_id) else res

        kw = {}
        if on_tokens is not None:
            kw["on_tokens"] = tokens
        if on_result is not None:
            kw["on_result"] = result
        return [alter(r) for r in real(self, requests, **kw)]

    monkeypatch_target.generate_batch = broken
    return real


def _with_params(transform):
    """Wrap JaxEngine.__init__: the engine is built on ``transform`` of
    the weights the benchmark drew; the reference keeps the weights as
    drawn."""
    from lmrs_tpu.engine.jax_engine import JaxEngine

    real = JaxEngine.__init__

    def init(self, *a, params=None, **kw):
        real(self, *a, params=transform(params), **kw)

    JaxEngine.__init__ = init
    return lambda: setattr(JaxEngine, "__init__", real)


def _norms_skipped(params):
    import jax
    import jax.numpy as jnp

    def zero(path, x):
        return jnp.zeros_like(x) if "scale" in jax.tree_util.keystr(path) \
            else x
    return jax.tree_util.tree_map_with_path(zero, params)


def _scales_rolled(params):
    import jax
    import jax.numpy as jnp

    def roll(path, x):
        return jnp.roll(x, 1, axis=-1) \
            if jax.tree_util.keystr(path).endswith("['s']") else x
    return jax.tree_util.tree_map_with_path(roll, params)


def _alter_tokens(first_only: bool = False):
    from lmrs_tpu.engine.jax_engine import JaxEngine

    real = _alter_one_token(JaxEngine, first_only)
    return lambda: setattr(JaxEngine, "generate_batch", real)


FAULTS = {
    "token_altered": ("tiny.offline-jobs", _alter_tokens),
    "token_altered_in_one_slot": ("tiny.offline-jobs",
                                  lambda: _alter_tokens(first_only=True)),
    "norm_weight_skipped": ("tiny.offline-jobs",
                            lambda: _with_params(_norms_skipped)),
    "int8_scale_of_another_channel": ("tiny-tp2.offline-jobs",
                                      lambda: _with_params(_scales_rolled)),
}


def _broken_run(fault: str) -> None:
    workload, plant = FAULTS[fault]
    sound = _run_main(workload, SEEDS[0])
    assert sound["correct"] is True, sound["checks"]
    undo = plant()
    try:
        line = _run_main(workload, SEEDS[0])
    finally:
        undo()
    gap = line["checks"]["logit_gap_max"]
    print(f"{workload}: sound run gap "
          f"{sound['checks']['logit_gap_max']['value']:.4f}; {fault}: gap "
          f"{gap['value']:.4f} against limit {gap['limit']}")
    assert line["correct"] is False and gap["value"] > gap["limit"]


def test_altered_token_fails_the_run():
    _broken_run("token_altered")


def test_token_altered_in_one_slot_fails_the_run():
    _broken_run("token_altered_in_one_slot")


def test_skipped_norm_weight_fails_the_run():
    _broken_run("norm_weight_skipped")


def test_scale_of_another_channel_fails_the_sharded_int8_run():
    _broken_run("int8_scale_of_another_channel")


if __name__ == "__main__":
    test_control_comes_out_not_correct_and_the_engine_correct()
    test_altered_token_fails_the_run()
    test_token_altered_in_one_slot_fails_the_run()
    test_skipped_norm_weight_fails_the_run()
    test_scale_of_another_channel_fails_the_sharded_int8_run()
    print("test_checks: passed")
