"""From a profiler trace (.xplane.pb) to device busy and idle time, device
time by jitted program and by kernel, and the longest idle gaps by what the
host was doing.  Needs nothing but JAX (``jax.profiler.ProfileData``).

What a TPU trace holds (found by looking at one, PERF.md section 6): one plane
per chip, ``/device:TPU:<n>``, with a line ``XLA Modules`` (one event per
execution of a jitted program, named ``jit_<fn>(<fingerprint>)``) and a
line ``XLA Ops`` (one event per executed HLO op; a ``while`` encloses its
body's ops, so durations of ops must not be added up across nesting:
busy time is the union of intervals).  Host threads are lines of the plane
``/host:CPU``; ``TraceAnnotation`` spans and JAX's own ``PjitFunction(<fn>)``
spans sit on its line ``python`` (which, with the Python tracer switched
off as run.py does, holds nothing else), the runtime's on the others.

The window is the span of the host annotation ``bench_window`` that run.py
opens around the measured window; device events are clipped to it.  Without
one (the recorded test trace has it; a foreign trace may not) the window is
the span of the device events.
"""

from __future__ import annotations

import re
from pathlib import Path

WINDOW_SPAN = "bench_window"
_CONTAINERS = ("while", "conditional", "call")


def _program_name(event_name: str) -> str:
    return re.sub(r"\(.*\)$", "", event_name).strip()


def _op_name(event_name: str) -> str:
    return event_name.split(" = ")[0].lstrip("%").strip()


def _op_label(event_name: str) -> str:
    """``fusion.247 bf16[24,2048,4096]``: the op's name and what it yields,
    so that a reader of the ledger's breakdown can tell a matmul from a
    scatter."""
    name = _op_name(event_name)
    m = re.search(r" = \(?([a-z0-9]+\[[0-9,]*\])", event_name)
    return f"{name} {m.group(1)}" if m else name


def _union(intervals: list[tuple[float, float]]) -> tuple[float, list]:
    """Total covered length and the gaps between covered stretches."""
    if not intervals:
        return 0.0, []
    intervals.sort()
    covered, gaps = 0.0, []
    cur_s, cur_e = intervals[0]
    for s, e in intervals[1:]:
        if s > cur_e:
            covered += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    covered += cur_e - cur_s
    return covered, gaps


def _is_kernel(name: str, known: tuple[str, ...]) -> bool:
    low = name.lower()
    return any(k in low for k in known)


# substrings of the op names under which the engine's Pallas kernels show in
# the XLA Ops line (fixed from the first traces, PERF.md section 6)
KERNEL_NEEDLES = ("flash_attention", "paged_decode", "ragged_spans",
                  "ragged", "pallas")


def reduce_file(path, n_devices: int = 1) -> dict:
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    dev_planes = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    host_planes = [p for p in pd.planes if p.name.startswith("/host:")]
    host_events: list[tuple[float, float, str]] = []
    window = None
    for p in host_planes:
        for line in p.lines:
            for e in line.events:
                if e.duration_ns <= 0:
                    continue
                s, d = e.start_ns * 1e-9, e.duration_ns * 1e-9
                if e.name == WINDOW_SPAN:
                    window = (s, s + d)
                else:
                    host_events.append((s, s + d, e.name))
    per_device = []
    programs: dict[str, float] = {}
    ops: dict[str, float] = {}
    kernels: dict[str, float] = {}
    gaps_all: list[tuple[float, float]] = []
    for p in dev_planes[:max(n_devices, 1)]:
        lines = {ln.name: ln for ln in p.lines}
        op_line = lines.get("XLA Ops")
        mod_line = lines.get("XLA Modules")
        src = op_line or mod_line
        if src is None:
            continue
        evs = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                e.name) for e in src.events if e.duration_ns > 0]
        if window is None and evs:
            window = (min(s for s, _, _ in evs), max(e for _, e, _ in evs))
        lo, hi = window
        clipped = [(max(s, lo), min(e, hi), n) for s, e, n in evs
                   if e > lo and s < hi]
        busy, gaps = _union([(s, e) for s, e, _ in clipped])
        if clipped:
            first = min(s for s, _, _ in clipped)
            last = max(e for _, e, _ in clipped)
            gaps = [(lo, first)] + gaps + [(last, hi)]
        else:
            gaps = [(lo, hi)]
        per_device.append(busy)
        gaps_all.extend(g for g in gaps if g[1] > g[0])
        if op_line is not None:
            for s, e, n in clipped:
                name = _op_name(n)
                if name.split(".")[0] in _CONTAINERS:
                    continue
                label = _op_label(n)
                ops[label] = ops.get(label, 0.0) + (e - s)
                if _is_kernel(name, KERNEL_NEEDLES):
                    kernels[name] = kernels.get(name, 0.0) + (e - s)
        if mod_line is not None:
            for e in mod_line.events:
                s, t = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
                if t > lo and s < hi:
                    name = _program_name(e.name)
                    programs[name] = programs.get(name, 0.0) + (
                        min(t, hi) - max(s, lo))
    n_dev = max(len(per_device), 1)
    window_s = (window[1] - window[0]) if window else 0.0

    def host_doing(gs: float, ge: float) -> str:
        """The innermost host span among those that cover most of the gap."""
        cands = [(min(e, ge) - max(s, gs), e - s, name)
                 for s, e, name in host_events if min(e, ge) > max(s, gs)]
        if not cands:
            return "host: no annotation"
        most = max(c[0] for c in cands)
        return min((c for c in cands if c[0] >= 0.95 * most),
                   key=lambda c: c[1])[2]

    gaps_all.sort(key=lambda g: g[0] - g[1])
    idle_gaps = [[host_doing(s, e), e - s] for s, e in gaps_all[:10]]
    top = sorted(ops.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(per_device) / n_dev,
        "window_s": window_s,
        "devices": len(per_device),
        "programs": {k: v / n_dev for k, v in sorted(
            programs.items(), key=lambda kv: -kv[1])},
        "kernels": {k: v / n_dev for k, v in sorted(
            kernels.items(), key=lambda kv: -kv[1])},
        "device_ops": [[k, v / n_dev] for k, v in top[:40]],
        "idle_gaps": idle_gaps,
        "n_gaps": len(gaps_all),
    }


def newest_trace(trace_dir) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda f: f.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_dir(trace_dir, n_devices: int = 1) -> dict:
    return reduce_file(newest_trace(trace_dir), n_devices)


def describe(path, top: int = 25) -> str:
    """What a trace holds, for a first look by hand."""
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for p in pd.planes:
        out.append(f"PLANE {p.name}")
        for line in p.lines:
            tot: dict[str, list] = {}
            n = 0
            for e in line.events:
                n += 1
                rec = tot.setdefault(e.name[:120], [0, 0.0])
                rec[0] += 1
                rec[1] += e.duration_ns * 1e-9
            out.append(f"  LINE {line.name}: {n} events, {len(tot)} names")
            for name, (cnt, sec) in sorted(tot.items(),
                                           key=lambda kv: -kv[1][1])[:top]:
                out.append(f"      {sec:10.6f}s x{cnt:<7d} {name}")
    return "\n".join(out)


if __name__ == "__main__":
    import json
    import sys

    target = Path(sys.argv[1])
    f = target if target.is_file() else newest_trace(target)
    if len(sys.argv) > 2 and sys.argv[2] == "describe":
        print(describe(f))
    else:
        print(json.dumps(reduce_file(f), indent=1))
