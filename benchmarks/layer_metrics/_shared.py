"""Arithmetic shared by the per-layer metric readers.

A reader is ``read(facts) -> float | None``.  ``facts`` holds what the
traced run gathered: ``model`` (sizes), ``peaks`` (peaks.json entry, None
off the chip), ``window_s``, ``counters`` (scheduler counters over the
window), ``anatomy`` / ``report`` (the scheduler's own reports), ``window``
(what the traffic generator returned), ``flops`` (flops.py) and, on the
chip, ``trace`` (trace_reduce.reduce_dir).  A reader that finds nothing to
read returns None and the metric is left out of the line.
"""

from __future__ import annotations


def cached_split(facts) -> list[tuple[int, int, int]]:
    """(prompt tokens computed, prompt tokens served from cache, tokens
    generated) per request of the window.  The scheduler counts reused
    tokens in total, not per request: they are spread over the requests in
    proportion to prompt length (attention is a few percent of the FLOPs,
    and the spread moves only that part)."""
    reqs = facts["window"]["requests"]
    reused = facts["counters"].get("prefix_tokens_reused", 0)
    total_prompt = sum(r["prompt"] for r in reqs) or 1
    share = min(reused / total_prompt, 1.0)
    out = []
    for r in reqs:
        cached = int(r["prompt"] * share)
        out.append((r["prompt"] - cached, cached, r["generated"]))
    return out


def model_flops(facts) -> float:
    fl = facts["flops"]
    return sum(fl.request_flops(facts["model"], c, k, g)
               for c, k, g in cached_split(facts))


def mfu_percent(facts) -> float | None:
    if not facts.get("peaks"):
        return None
    return 100.0 * model_flops(facts) / (
        facts["window_s"] * facts["peaks"]["bf16_flops"])


def device_idle_percent(facts) -> float | None:
    tr = facts.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def kernel_seconds(facts, *needles: str) -> float:
    """Device seconds of the kernels whose name holds any of ``needles``."""
    tr = facts.get("trace")
    if not tr:
        return 0.0
    return sum(s for name, s in tr["kernels"].items()
               if any(n in name for n in needles))
