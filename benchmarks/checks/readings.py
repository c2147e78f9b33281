"""The readings a cell's limits are set from, on the chip, through the
harness's own ``decide_correct``.

    chiprun -- python benchmarks/checks/readings.py <workload> \
        --seeds 12 --control-seeds 3 --control int8[,fp8] [--program-control int8]

One process walks the seeds (the compiled programs are shared between them):
for each it draws the weights, builds the engine, drives one short window
of the cell's own traffic at the cell's own sizes through the product's
entry, frees the engine and lets ``run.decide_correct`` compare the sample
with the plain reference: the same lines a run prints, the numbers beside
their limits and ``correct``.  On the first ``--control-seeds`` seeds the
same call is made again with the reference at each ``--control`` precision
put in the program's place (the gap of the token that precision puts first,
at every position of the same prompts and tokens), and, with
``--program-control``, a second window is driven with the program's own
lower-precision weight path switched on.  Each has to print
``correct: false``.  Every served token's gap goes to
``chiprun_out/readings/<workload>.json``, so that a statistic can be chosen
from what was read and not from a guess.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE.parent), str(HERE)]


def main() -> int:
    import jax

    import run as bench

    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--control", default="")
    ap.add_argument("--program-control", default="")
    ap.add_argument("--sample", type=int, default=None,
                    help="requests compared per window (default: the limits file's)")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--first-seed", type=int, default=2147484000)
    ap.add_argument("--set", action="append", default=[],
                    help="traffic override key=json, e.g. warmup=false")
    args = ap.parse_args()
    modes = [m for m in args.control.split(",") if m]
    rows = []
    out = HERE.parent / "chiprun_out" / "readings" / f"{args.workload}.json"
    out.parent.mkdir(parents=True, exist_ok=True)

    def window(ctx, quantize=None):
        bench.build_engine(ctx, quantize=quantize)
        ctx.window_begin = ctx.window_end = lambda: None
        ctx.gen.setup(ctx)
        win = ctx.gen.window(ctx)
        ctx.gen.teardown(ctx)
        bench.free_engine(ctx)
        return win

    def read(ctx, win, who, control=None):
        v = bench.decide_correct(ctx, win, control=control,
                                 sample_requests=args.sample)
        bench.print_verdict(v)
        row = {"seed": ctx.seed, "who": who, "correct": v["correct"],
               "checks": v["checks"], "gaps": v["gaps"]}
        rows.append(row)
        print("READING " + json.dumps({k: row[k] for k in
                                       ("seed", "who", "correct", "checks")}),
              flush=True)
        out.write_text(json.dumps(rows))

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        ctx = bench.open_cell(args.workload, seed, args.seconds, False)
        if isinstance(ctx, int):
            return ctx
        for kv in args.set:
            k, v = kv.split("=", 1)
            ctx.traffic[k] = json.loads(v)
        win = window(ctx)
        read(ctx, win, "program")
        if i < args.control_seeds:
            for m in modes:
                read(ctx, win, f"control-{m}", control=m)
            if args.program_control:
                for leaf in jax.tree.leaves(ctx.params):
                    leaf.delete()
                win = window(ctx, quantize=args.program_control)
                read(ctx, win, f"program-{args.program_control}")
        for leaf in jax.tree.leaves(ctx.params):
            leaf.delete()
        del ctx, win
        gc.collect()
    for who in sorted({r["who"] for r in rows}):
        mine = [r for r in rows if r["who"] == who]
        for name in mine[0]["checks"]:
            print(f"SUMMARY {who} {name}: " + " ".join(
                f"{r['checks'][name]['value']:.6g}" for r in mine), flush=True)
        print(f"SUMMARY {who} correct: " + " ".join(
            str(r["correct"]) for r in mine), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
