"""What the scheduler's own spans say in a kept profiler trace.

    BENCH_KEEP_TRACE=1 python benchmarks/run.py --workload <cell> ... --trace 1
    python benchmarks/checks/trace_spans.py benchmarks/.trace/<cell> [out.json]

Since PR 25 every scheduler segment is a ``sched.*`` span in the xplane
(``lmrs_tpu/obs/trace.span``), on the clock of the device's events, and
``sched.dispatch`` carries ``program``, ``key``, ``id``, ``rows``,
``q_tokens``, ``q_slots``, ``cold``; ``sched.fetch`` carries ``retires``, the
ids of the dispatches whose results it waits for.  This reads one trace and
prints, as one JSON object:

* ``spans``: per ``sched.*`` name the count and the seconds (spans nest, so
  the seconds of ``sched.run`` hold all the others);
* ``dispatches``: every ``sched.dispatch`` with its arguments, start and
  duration;
* ``clock_lead_ms``: by how much the device's clock leads the host's.  The
  k-th execution of ``jit_decode`` on the device belongs to the k-th
  ``sched.dispatch`` of program ``decode``; the ``sched.fetch`` that retires
  that id returns when the result is on the host, which is after the
  program's end by a transfer of a few KB, so host end minus device end is
  that transfer plus the clocks' offset: negative where the device's clock
  is ahead.  Median, quartiles and count over the trace's decode blocks.
  Beside it ``device_start_minus_dispatch_start``, over the dispatches that
  found the device idle (the first after a fetch): the k-th execution of a
  program's module against the k-th dispatch of that program.  A launch
  cannot precede the call that makes it, so a negative reading is all
  offset: the device's events sit at least that much early on the host's
  clock.

A tree from before PR 25 has no such spans: ``spans`` is empty and the lead
is not given.  With a second argument the events read are written there too.
"""

from __future__ import annotations

import json
import statistics
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE)]

import trace_reduce  # noqa: E402  (beside this directory)


def read_events(path) -> dict:
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    host, modules = [], []
    for p in pd.planes:
        for line in p.lines:
            if p.name.startswith("/host:"):
                for e in line.events:
                    if e.name.startswith("sched.") or e.name == "bench_window":
                        host.append({"name": e.name, "start_ns": e.start_ns,
                                     "dur_ns": e.duration_ns,
                                     "args": {k: v for k, v in e.stats}})
            elif p.name == "/device:TPU:0" and line.name == "XLA Modules":
                for e in line.events:
                    modules.append({
                        "name": trace_reduce._program_name(e.name),
                        "start_ns": e.start_ns, "dur_ns": e.duration_ns})
    host.sort(key=lambda e: e["start_ns"])
    modules.sort(key=lambda e: e["start_ns"])
    return {"host": host, "modules": modules}


# the jitted function behind each scheduler program (scheduler.py)
MODULE_OF = {"prefill": "jit_prefill", "packed": "jit_packed_prefill",
             "prefill_chunk": "jit_prefill_chunk", "rpa": "jit_rpa_step",
             "mixed": "jit_mixed_step", "decode": "jit_decode",
             "spec": "jit_spec_decode"}


def spread(values: list[float]) -> dict:
    q = statistics.quantiles(values, n=4) if len(values) > 1 \
        else [values[0]] * 3
    return {"median": statistics.median(values), "q1": q[0], "q3": q[2],
            "min": min(values), "max": max(values), "count": len(values)}


def retired_ids(fetch: dict) -> list[int]:
    r = fetch["args"].get("retires", "")
    return [int(i) for i in str(r).split("+") if i]


def reduce_events(ev: dict) -> dict:
    spans: dict[str, list] = {}
    for e in ev["host"]:
        if e["name"].startswith("sched."):
            rec = spans.setdefault(e["name"], [0, 0.0])
            rec[0] += 1
            rec[1] += e["dur_ns"] * 1e-9
    dispatches = [{**e["args"], "start_s": e["start_ns"] * 1e-9,
                   "dur_ms": e["dur_ns"] * 1e-6}
                  for e in ev["host"] if e["name"] == "sched.dispatch"]
    fetch_end = {}
    for e in ev["host"]:
        if e["name"] == "sched.fetch":
            for i in retired_ids(e):
                fetch_end[i] = e["start_ns"] + e["dur_ns"]
    out = {"spans": {k: {"count": n, "seconds": s}
                     for k, (n, s) in sorted(spans.items())},
           "dispatches": dispatches}
    # per program, the k-th dispatch and the k-th execution of its module
    pairs: dict[int, dict] = {}
    for program, module in MODULE_OF.items():
        ds = [d for d in dispatches if d.get("program") == program]
        runs = [m for m in ev["modules"] if m["name"] == module]
        if ds and len(ds) != len(runs):
            out["clock_lead_ms"] = (
                f"not given: {len(ds)} dispatches of {program} on the "
                f"host, {len(runs)} executions of {module} on the device")
            return out
        pairs.update({d["id"]: m for d, m in zip(ds, runs)})
    ends = [(fetch_end[d["id"]] - (pairs[d["id"]]["start_ns"]
                                   + pairs[d["id"]]["dur_ns"])) * 1e-6
            for d in dispatches
            if d.get("program") == "decode" and d["id"] in fetch_end]
    # a dispatch found the device idle if the fetch that retired the one
    # before it had returned
    starts = [pairs[d["id"]]["start_ns"] * 1e-6 - d["start_s"] * 1e3
              for d in dispatches if d["id"] in pairs
              and fetch_end.get(d["id"] - 1, float("inf")) * 1e-9
              <= d["start_s"]]
    if ends:
        out["clock_lead_ms"] = {
            "host_fetch_end_minus_device_program_end": spread(ends),
            **({"device_start_minus_dispatch_start": spread(starts)}
               if starts else {})}
    return out


def main() -> int:
    target = Path(sys.argv[1])
    f = target if target.is_file() else trace_reduce.newest_trace(target)
    ev = read_events(f)
    if len(sys.argv) > 2:
        Path(sys.argv[2]).write_text(json.dumps(ev))
    print(json.dumps(reduce_events(ev)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
