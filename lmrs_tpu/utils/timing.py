"""Per-stage wall-clock spans + optional jax.profiler traces.

The reference reports manual ``time.time()`` deltas per stage (main.py:110,
239-245; llm_executor.py:129,150-154; result_aggregator.py:72,102-103); this
keeps that user-visible stage report and adds structured spans that also
land in a ``jax.profiler`` trace when profiling is enabled (SURVEY.md §5.1).
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field


def format_duration(seconds: float) -> str:
    """Human duration, reference _format_duration (main.py:324-332)."""
    seconds = int(seconds)
    h, rem = divmod(seconds, 3600)
    m, s = divmod(rem, 60)
    if h:
        return f"{h}h {m}m {s}s"
    if m:
        return f"{m}m {s}s"
    return f"{s}s"


@dataclass
class StageTimer:
    """Collects named stage spans; optionally mirrors them into jax.profiler."""

    profile: bool = False
    spans: dict[str, float] = field(default_factory=dict)
    _t0: float = field(default_factory=time.time)

    @contextlib.contextmanager
    def stage(self, name: str):
        # one span in both sinks (obs/trace.span): the jax.profiler trace
        # when ``profile`` is on, and the lifecycle tracer's pipeline track
        # (the engine-level spans nest under these in Perfetto)
        from lmrs_tpu.obs import PID_PIPELINE, span

        start = time.time()
        with span(name, annotate=self.profile, pid=PID_PIPELINE):
            yield
        self.spans[name] = self.spans.get(name, 0.0) + (time.time() - start)

    @property
    def total(self) -> float:
        return time.time() - self._t0

    def report(self) -> dict[str, float]:
        out = {k: round(v, 4) for k, v in self.spans.items()}
        out["total"] = round(self.total, 4)
        return out
