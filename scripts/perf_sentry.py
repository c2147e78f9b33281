"""perf_sentry — noise-aware perf-regression checker over the bench history.

The pre-round workflow appended a ``BENCH_r*.json`` / ``BENCH8B_r*.json`` /
``MULTICHIP_r*.json`` artifact to the repo root per hardware round (and the
A/B rounds ``FAIRNESS_r*.json`` / ``MIGRATE_r*.json``, scripts/ab_fairness.py
and scripts/ab_migrate.py).  Of the root history only ``BENCH8B_r05``, the
CPU-mesh ``MULTICHIP`` dry runs and ``FAIRNESS_r01`` remain (the
``BENCH_r01-r05`` rows left in PR 22; this round's numbers go to
``PERF_LEDGER.jsonl``).  Nothing READ such artifacts:
a regression slipped into a round would sit unnoticed until a human
diffed the trajectory.  The sentry makes the history a gate:

* artifacts are grouped by kind and (for bench rounds) by ``detail.model``
  — trajectories only compare like against like;
* the LATEST round's tracked metrics compare against the MEDIAN of the
  prior rounds (median, not best: a one-round fluke must not become the
  permanent bar, and a one-round dip must not hide behind one old spike);
* a delta in the BAD direction beyond the relative band
  (``--band`` / ``LMRS_SENTRY_BAND``, default 0.15 — bench rounds carry
  real run-to-run noise) is a regression; fewer than
  ``LMRS_SENTRY_MIN_ROUNDS`` prior rounds means "no trajectory yet",
  reported but never failed;
* ``MULTICHIP`` rounds gate on the ok/rc flags (a round that stopped
  passing is a regression regardless of numbers).

Output: a JSON report (stdout, or ``--out``) + human summary on stderr;
exit 1 on any regression, 0 otherwise.  ``--report`` forces exit 0 —
the tier-1 CI arm runs report mode over the checked-in history (CPU
runners must surface drift, not block on chip-only noise), while the
hardware-round workflow runs gating mode after appending its artifact.
"""

from __future__ import annotations

import _pathfix  # noqa: F401

import argparse
import json
import re
import sys
from pathlib import Path

from lmrs_tpu.utils.env import env_float, env_int

# tracked bench detail metrics: name -> direction ("up" = higher is
# better).  Percentile dicts are addressed as "name.p50".
TRACKED = {
    "chunks_per_sec": "up",
    "prefill_tokens_per_sec": "up",
    "decode_tokens_per_sec": "up",
    "model_flops_utilization": "up",
    "hbm_bw_utilization": "up",
    "decode_step_ms": "down",
    "decode_row_us_rpa": "down",
    "ttft_ms.p50": "down",
    "decode_block_gap_ms.p50": "down",
    # fairness A/B rounds (FAIRNESS_r*.json, scripts/ab_fairness.py):
    # the quiet tenant's protected TTFT and the QoS-on/off separation
    "quiet_ttft_p95_ms_qos_on": "down",
    "fairness_gain": "up",
    # step-anatomy metrics (ISSUE 18, obs/anatomy.py): per-iteration host
    # overhead between dispatches, and the ragged-span family's padding
    # waste — both live under the bench detail's windowed "anatomy" block
    "anatomy.host_overhead_us_step": "down",
    "anatomy.rpa_pad_waste_ratio": "down",
    # tree speculation (ISSUE 19): accepted draft tokens per dispatched
    # row must trend up, and the host draft segment must stay collapsed
    # (drafting is fused on-device — a draft-segment climb means host
    # n-gram scans crept back into the loop)
    "spec_tree.accept_per_step": "up",
    "anatomy.segments_ms.draft": "down",
    # KV-fabric A/B rounds (MIGRATE_r*.json, scripts/ab_migrate.py): of
    # the preamble tokens the resume host re-serves after a drain, the
    # fraction that came off the fabric (migrated page sets) instead of
    # cold re-prefill — a drop means migration stopped delivering
    "migrate.tokens_from_fabric_ratio": "up",
}

_ROUND_RE = re.compile(r"_r(\d+)\.json$")


def _round_no(path: Path) -> int:
    m = _ROUND_RE.search(path.name)
    return int(m.group(1)) if m else -1


def _lookup(detail: dict, dotted: str):
    cur = detail
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur if isinstance(cur, (int, float)) else None


def load_bench_rounds(root: Path, prefix: str) -> list[dict]:
    """[{round, path, model, metrics{}}] for one artifact family, round
    order.  Unparseable artifacts are skipped with a note, never fatal —
    the sentry must not be brickable by one corrupt file."""
    rounds = []
    for path in sorted(root.glob(f"{prefix}_r*.json"), key=_round_no):
        try:
            doc = json.loads(path.read_text("utf-8"))
            detail = (doc.get("parsed") or {}).get("detail") or {}
            metrics = {}
            for name in TRACKED:
                v = _lookup(detail, name)
                if v is not None:
                    metrics[name] = float(v)
            val = (doc.get("parsed") or {}).get("value")
            if isinstance(val, (int, float)):
                metrics.setdefault("chunks_per_sec", float(val))
            rounds.append({"round": _round_no(path), "path": path.name,
                           "model": detail.get("model") or "?",
                           "rc": doc.get("rc"), "metrics": metrics})
        except (OSError, ValueError) as e:
            rounds.append({"round": _round_no(path), "path": path.name,
                           "error": f"{type(e).__name__}: {e}",
                           "model": "?", "metrics": {}})
    return rounds


def load_multichip_rounds(root: Path) -> list[dict]:
    rounds = []
    for path in sorted(root.glob("MULTICHIP_r*.json"), key=_round_no):
        try:
            doc = json.loads(path.read_text("utf-8"))
            rounds.append({"round": _round_no(path), "path": path.name,
                           "ok": bool(doc.get("ok")),
                           "skipped": bool(doc.get("skipped")),
                           "rc": doc.get("rc")})
        except (OSError, ValueError) as e:
            rounds.append({"round": _round_no(path), "path": path.name,
                           "error": f"{type(e).__name__}: {e}"})
    return rounds


def _median(vals: list[float]) -> float:
    vs = sorted(vals)
    n = len(vs)
    return vs[n // 2] if n % 2 else 0.5 * (vs[n // 2 - 1] + vs[n // 2])


def check_family(rounds: list[dict], band: float,
                 min_rounds: int) -> tuple[list[dict], list[dict]]:
    """(regressions, checks) comparing each model-group's latest round
    against the median of its priors."""
    regressions: list[dict] = []
    checks: list[dict] = []
    by_model: dict[str, list[dict]] = {}
    for r in rounds:
        if r.get("metrics"):
            by_model.setdefault(r["model"], []).append(r)
    for model, group in by_model.items():
        if len(group) < 2:
            checks.append({"model": model, "rounds": len(group),
                           "status": "no-trajectory"})
            continue
        latest, prior = group[-1], group[:-1]
        for name, direction in TRACKED.items():
            cur = latest["metrics"].get(name)
            hist = [r["metrics"][name] for r in prior
                    if name in r["metrics"]]
            if cur is None or not hist:
                continue
            base = _median(hist)
            if base == 0:
                continue
            # signed relative delta in the GOOD direction (positive =
            # improved); a regression is delta < -band
            delta = (cur - base) / abs(base)
            if direction == "down":
                delta = -delta
            row = {"model": model, "metric": name, "latest": cur,
                   "median_prior": round(base, 4),
                   "rounds_prior": len(hist),
                   "latest_round": latest["path"],
                   "delta_rel": round(delta, 4),
                   "gated": len(hist) >= min_rounds}
            checks.append(row)
            if delta < -band and row["gated"]:
                regressions.append(row)
    return regressions, checks


def check_multichip(rounds: list[dict]) -> tuple[list[dict], list[dict]]:
    live = [r for r in rounds if not r.get("skipped") and "error" not in r]
    checks = [dict(r, path=str(r["path"])) for r in live]
    if len(live) < 2:
        return [], checks
    latest, prior = live[-1], live[:-1]
    if any(p["ok"] for p in prior) and not latest["ok"]:
        return [{"metric": "multichip_ok", "latest_round": latest["path"],
                 "latest": 0, "median_prior": 1, "delta_rel": -1.0,
                 "gated": True, "model": "multichip"}], checks
    return [], checks


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dir", default=str(Path(__file__).parent.parent),
                    help="artifact directory (default: repo root)")
    ap.add_argument("--band", type=float,
                    default=env_float("LMRS_SENTRY_BAND", 0.15, lo=0.0),
                    help="relative regression band (default 0.15)")
    ap.add_argument("--min-rounds", type=int,
                    default=env_int("LMRS_SENTRY_MIN_ROUNDS", 2, lo=1),
                    help="prior rounds required before a metric gates")
    ap.add_argument("--report", action="store_true",
                    help="report mode: print the same JSON, always exit 0 "
                         "(the tier-1 CI arm)")
    ap.add_argument("--out", default=None, help="write the JSON report here")
    args = ap.parse_args(argv)

    root = Path(args.dir)
    regressions: list[dict] = []
    families: dict[str, dict] = {}
    for prefix in ("BENCH", "BENCH8B", "FAIRNESS", "MIGRATE"):
        rounds = load_bench_rounds(root, prefix)
        if not rounds:
            continue
        regs, checks = check_family(rounds, args.band, args.min_rounds)
        regressions += [dict(r, family=prefix) for r in regs]
        families[prefix] = {"rounds": len(rounds), "checks": checks}
    mc = load_multichip_rounds(root)
    if mc:
        regs, checks = check_multichip(mc)
        regressions += [dict(r, family="MULTICHIP") for r in regs]
        families["MULTICHIP"] = {"rounds": len(mc), "checks": checks}

    report = {
        "object": "perf_sentry",
        "band": args.band,
        "min_rounds": args.min_rounds,
        "families": families,
        "regressions": regressions,
        "status": "regression" if regressions else "ok",
    }
    text = json.dumps(report, indent=2)
    if args.out:
        Path(args.out).write_text(text + "\n", encoding="utf-8")
    print(text)
    for r in regressions:
        print(f"REGRESSION {r.get('family')}/{r['model']} {r['metric']}: "
              f"{r['latest']} vs median {r['median_prior']} "
              f"({r['delta_rel']:+.1%}, band -{args.band:.0%}) "
              f"in {r['latest_round']}", file=sys.stderr)
    if regressions and not args.report:
        return 1
    if regressions:
        print("report mode: regressions reported, exit 0", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
