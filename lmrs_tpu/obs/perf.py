"""Live per-dispatch performance attribution + on-demand profiler capture.

This module turns the roofline model (utils/perf_model) into a LIVE
signal on the serving path:

* ``DispatchAttribution`` — owned by the continuous scheduler, fed from
  the real dispatch loop.  Each decode block brings its byte cost (the
  scheduler's count from the tree and pool it holds, utils/perf_model.
  StepBytes: the bytes its dispatch record carries) and each prefill
  dispatch its model FLOP cost; measured dispatch walls (minus the host<->device
  fetch round trip, which a small dispatch's wall is mostly made of) turn
  those into ``lmrs_decode_hbm_util_ratio`` and
  ``lmrs_prefill_mfu_ratio`` samples, plus ``lmrs_step_gap_ms`` — the
  host-side gap between consecutive decode dispatches (the device-idle
  share the overlap levers attack).

  Attribution method (documented limits, docs/OBSERVABILITY.md):

  - decode blocks with NO prefill work threaded into them are CLEAN
    samples: util = model_bytes / (wall - rtt) / peak_bw, and they feed
    a running utilization estimate;
  - blocks that carry a same-iteration prefill dispatch (the deferred
    tok0 path sequences prefill before the decode scan on device) are
    decomposed: the decode share is estimated from the running
    utilization, the remainder is charged to prefill → an MFU sample.
    No clean decode sample yet → the mixed block only counts bytes/FLOPs;
  - FUSED mixed steps (SARATHI mixed batches) are split EXACTLY: their
    per-row token counts are known, so the wall apportions proportionally
    to each phase's roofline time — no EMA estimate involved
    (``note_mixed_step``);
  - first-run (compiling) shapes never produce samples;
  - speculative-decode blocks contribute step gaps only (their dispatch
    record carries their bytes; spec is off on the bench and default-off
    in serving).

* ``start_profile_capture`` — the ``POST /v1/debug/profile`` /
  ``LMRS_PROFILE_ON_SLOW_STEP`` hook: a bounded, one-at-a-time
  ``jax.profiler`` trace capture into a directory, stopped by a timer so
  an abandoned capture can never run forever.
"""

from __future__ import annotations

import logging
import os
import threading
import time

from lmrs_tpu.utils.env import env_float, env_str

logger = logging.getLogger("lmrs.obs.perf")


class DispatchAttribution:
    """Roofline attribution fed from the live dispatch loop (see module
    doc).  Registers its metrics on the scheduler's registry so they ride
    the existing ``metrics_report()`` / Prometheus surfaces."""

    def __init__(self, model_cfg, registry):
        from lmrs_tpu.obs.metrics import MS_LATENCY_BUCKETS, RATIO_BUCKETS

        self.model_cfg = model_cfg
        self._rtt: float | None = None
        self._rtt_t: float | None = None  # clock time of the last probe
        self._clock = time.time  # injectable (stale-RTT regression test)
        self._hbm_util_est: float | None = None  # running clean-sample EMA
        self._last_block_end: float | None = None
        h, g, c = registry.histogram, registry.gauge, registry.counter
        self.h_mfu = h("lmrs_prefill_mfu_ratio", buckets=RATIO_BUCKETS,
                       help="live prefill model-FLOPs utilization per "
                            "attributed dispatch")
        self.h_hbm = h("lmrs_decode_hbm_util_ratio", buckets=RATIO_BUCKETS,
                       help="live decode HBM-bandwidth utilization per "
                            "clean decode block")
        self.h_gap = h("lmrs_step_gap_ms", buckets=MS_LATENCY_BUCKETS,
                       help="host-side gap between consecutive decode "
                            "dispatches (end of fetch to next issue)",
                       unit="ms")
        self.g_mfu = g("lmrs_prefill_mfu_ratio_last",
                       "most recent live prefill MFU sample")
        self.g_hbm = g("lmrs_decode_hbm_util_ratio_last",
                       "most recent live decode HBM-utilization sample")
        self.g_gap = g("lmrs_step_gap_ms_last",
                       "most recent decode step gap", "ms")
        self.c_flops = c("lmrs_prefill_model_flops_total",
                         "model-accounted prefill FLOPs dispatched",
                         "flops")
        self.c_bytes = c("lmrs_decode_model_bytes_total",
                         "model-accounted decode HBM bytes dispatched",
                         "bytes")
        # host-RAM KV prefetch (engine/host_kv.py): scatter bytes issued
        # asynchronously ride into the NEXT decode block's wall, so that
        # block must not feed the clean-sample EMA — the pending flag
        # marks it dirty and the bytes are counted here
        self.c_prefetch_bytes = c("lmrs_prefix_prefetch_bytes_total",
                                  "host→HBM bytes restored by KV spill "
                                  "prefetch", "bytes")
        self._prefetch_pending = False

    # ------------------------------------------------------------ plumbing

    def _spec(self):
        """Chip peaks, or None on a device without known peaks: the
        ``note_*`` paths then count work but record NO utilisation
        sample."""
        from lmrs_tpu.utils.perf_model import chip_spec

        return chip_spec()

    def ensure_rtt(self) -> float:
        """Median trivial dependent-fetch round trip, measured lazily and
        RE-SAMPLED on a slow cadence (``LMRS_RTT_RESAMPLE_S``, default
        300 s): a long-lived process can see its host<->device latency
        drift (a busy host, a remote device) and a once-per-process sample
        would then skew every dispatch wall it is subtracted from.  A re-probe
        FAILURE keeps the previous sample (but refreshes the timestamp so
        a flaky link is not hammered every call).  Subtracted from every
        dispatch wall — a small dispatch's wall is mostly this fixed round
        trip, and attribution without the subtraction would measure it
        instead of the chip (docs/PERF.md round 5)."""
        from lmrs_tpu.obs.anatomy import rtt_resample_s

        now = self._clock()
        if (self._rtt is not None and self._rtt_t is not None
                and now - self._rtt_t < rtt_resample_s()):
            return self._rtt
        try:
            import jax
            import jax.numpy as jnp
            import numpy as np

            x = jnp.zeros((8,), jnp.float32)
            np.asarray(jax.device_get(x + 1))  # warm the tiny program
            rtts = []
            for _ in range(3):
                t0 = time.time()
                np.asarray(jax.device_get(x + 1))
                rtts.append(time.time() - t0)
            self._rtt = sorted(rtts)[1]
        except Exception:  # noqa: BLE001 - attribution must never kill
            if self._rtt is None:
                logger.warning("RTT probe failed; attribution walls will "
                               "include the host link RTT", exc_info=True)
                self._rtt = 0.0
            else:
                logger.warning("RTT re-probe failed; keeping the previous "
                               "sample", exc_info=True)
        self._rtt_t = now
        return self._rtt

    def rtt_sample(self) -> tuple[float | None, float | None]:
        """``(rtt_s, age_s)`` of the current sample WITHOUT probing —
        the anatomy report's stale-RTT guard reads this so a report can
        never trigger a device round trip, and a sample older than its
        staleness horizon is flagged instead of silently skewing the
        dispatch/fetch split."""
        if self._rtt is None or self._rtt_t is None:
            return None, None
        return self._rtt, max(self._clock() - self._rtt_t, 0.0)

    def prefill_flops(self, chunk_tokens: int, kv_start: int = 0) -> float:
        """Model FLOPs of one prefill row: a fresh causal chunk
        (``kv_start=0``) or a windowed continuation chunk attending
        ``kv_start`` earlier KV tokens.  LM head on the sampled row only
        (the packed-prefill gather — forward_paged last_pos)."""
        from lmrs_tpu.utils.perf_model import prefill_flops

        return prefill_flops(self.model_cfg, max(1, chunk_tokens),
                             head_tokens=1, kv_start=kv_start)

    def note_prefetch(self, nbytes: float) -> None:
        """A KV spill prefetch was issued (async scatter): count its HBM
        bytes and mark the next decode block dirty — its wall includes
        the transfer, so it must count work but never sample utilization
        (same discipline as compiling shapes)."""
        if nbytes > 0:
            self.c_prefetch_bytes.inc(nbytes)
        self._prefetch_pending = True

    # ------------------------------------------------------------- samples

    def note_gap(self, t_start: float, t_end: float) -> None:
        """Record the host-side gap since the previous block's fetch
        completed (the device-idle window between dispatches), and mark
        this block's end.  Called by every block path — including
        speculative blocks, which contribute no byte/FLOP samples."""
        if self._last_block_end is not None:
            gap_ms = max(0.0, (t_start - self._last_block_end) * 1e3)
            self.h_gap.observe(gap_ms)
            self.g_gap.set(gap_ms)
        self._last_block_end = t_end

    def note_block(self, t_start: float, t_end: float, nbytes: float,
                   prefill_flops: float, warm: bool) -> None:
        """One decode-block dispatch: wall [t_start, t_end], ``nbytes``
        its steps had to read (the decode record's ``hbm_bytes``), with
        ``prefill_flops`` of same-iteration prefill work sequenced before
        it on device (0 for a clean decode block).  ``warm=False`` (a
        compiling shape) counts work but never samples."""
        self.note_gap(t_start, t_end)
        self.c_bytes.inc(nbytes)
        if prefill_flops > 0:
            self.c_flops.inc(prefill_flops)
        if self._prefetch_pending:
            # the wall includes an async spill-prefetch scatter sequenced
            # before this block: count the work, skip the samples
            self._prefetch_pending = False
            warm = False
        spec = self._spec() if warm else None
        if spec is None:
            return
        t = (t_end - t_start) - self.ensure_rtt()
        if t <= 1e-6:
            return
        if prefill_flops <= 0:
            util = nbytes / t / spec.peak_hbm_bw
            if 0.0 < util < 4.0:  # garbage guard (clock steps, CPU fallback)
                self.h_hbm.observe(util)
                self.g_hbm.set(util)
                self._hbm_util_est = (util if self._hbm_util_est is None
                                      else 0.8 * self._hbm_util_est
                                      + 0.2 * util)
            return
        # mixed block: subtract the decode share estimated from clean
        # samples; the remainder is the prefill compute the device spent
        if self._hbm_util_est is None or self._hbm_util_est <= 0:
            return
        t_decode = nbytes / (spec.peak_hbm_bw * self._hbm_util_est)
        t_prefill = t - t_decode
        if t_prefill <= 1e-6:
            return
        mfu = prefill_flops / t_prefill / spec.peak_flops
        if 0.0 < mfu < 4.0:
            self.h_mfu.observe(mfu)
            self.g_mfu.set(mfu)

    def note_mixed_step(self, t_start: float, t_end: float, nbytes: float,
                        prefill_flops: float, warm: bool) -> None:
        """One FUSED mixed dispatch (SARATHI mixed batches): the live
        decode rows advance one token and a prefill slice of known size
        rides the SAME program.  Unlike the sequenced-prefill decode
        blocks (``note_block``, whose decode share must be ESTIMATED from
        the clean-sample EMA), the fused step's per-row token counts are
        exact, so the split needs no estimate: the wall is apportioned
        proportionally to each phase's own roofline time
        (``bytes/peak_bw`` vs ``flops/peak_flops``), under which both
        phase samples equal the step's combined roofline utilization —
        the assumption-free number for a step whose two phases share one
        kernel launch (they cannot be timed apart host-side).  Clean
        decode samples alone keep feeding the EMA.  ``nbytes`` is the
        step's decode side, priced by the scheduler with the same
        ``StepBytes`` as a decode block (every query token of a row's
        span walks that row's positions)."""
        self.note_gap(t_start, t_end)
        self.c_bytes.inc(nbytes)
        if prefill_flops > 0:
            self.c_flops.inc(prefill_flops)
        if self._prefetch_pending:  # same contract as note_block
            self._prefetch_pending = False
            warm = False
        spec = self._spec() if warm else None
        if spec is None:
            return
        t = (t_end - t_start) - self.ensure_rtt()
        if t <= 1e-6:
            return
        t_model = (nbytes / spec.peak_hbm_bw
                   + max(prefill_flops, 0.0) / spec.peak_flops)
        util = t_model / t
        if 0.0 < util < 4.0:  # same garbage guard as note_block
            self.h_hbm.observe(util)
            self.g_hbm.set(util)
            if prefill_flops > 0:
                self.h_mfu.observe(util)
                self.g_mfu.set(util)

    def note_prefill_sync(self, flops: float, t_start: float,
                          t_end: float, warm: bool) -> None:
        """A prefill wave whose first tokens were fetched SYNCHRONOUSLY
        (handoff-export slots, speculation, LMRS_DEFER_TOK0=0): the wall
        covers exactly the prefill compute + one RTT — a clean MFU sample
        (this is the prefill pod's whole serving life under
        disaggregation)."""
        if flops <= 0:
            return
        self.c_flops.inc(flops)
        if self._prefetch_pending:  # the wave's wall includes the scatter
            self._prefetch_pending = False
            warm = False
        spec = self._spec() if warm else None
        if spec is None:
            return
        t = (t_end - t_start) - self.ensure_rtt()
        if t <= 1e-6:
            return
        mfu = flops / t / spec.peak_flops
        if 0.0 < mfu < 4.0:
            self.h_mfu.observe(mfu)
            self.g_mfu.set(mfu)

    # -------------------------------------------------------------- report

    def report(self) -> dict:
        """The ``perf_attribution`` block of ``metrics_report()`` / bench
        detail: per-phase live roofline ratios + the model-accounted work
        totals they were computed over."""
        return {
            "prefill_mfu": self.h_mfu.percentile_report(scale=1.0,
                                                        ndigits=4),
            "prefill_mfu_last": round(self.g_mfu.value, 4),
            "decode_hbm_util": self.h_hbm.percentile_report(scale=1.0,
                                                            ndigits=4),
            "decode_hbm_util_last": round(self.g_hbm.value, 4),
            "step_gap_ms": self.h_gap.percentile_report(scale=1.0),
            # 6 decimals: tiny test models dispatch MEGA-scale work, and
            # a report that rounds real nonzero totals to 0.0 reads as
            # "attribution dead" exactly where tests check liveness
            "model_prefill_gflops": round(self.c_flops.value / 1e9, 6),
            "model_decode_gb": round(self.c_bytes.value / 1e9, 6),
            "rtt_ms": (round(self._rtt * 1e3, 2)
                       if self._rtt is not None else None),
        }


# ------------------------------------------------ on-demand profiler capture

_capture_lock = threading.Lock()
_capture_active = False


def profile_capture_active() -> bool:
    with _capture_lock:
        return _capture_active


def default_profile_dir() -> str:
    """Where captures land unless the caller says otherwise: the ONE
    implementation of the LMRS_PROFILE_DIR fallback, shared by the
    ``/v1/debug/profile`` endpoint and the slow-step trigger so the two
    capture paths can never write to different places."""
    import tempfile

    return (env_str("LMRS_PROFILE_DIR")
            or os.path.join(tempfile.gettempdir(), "lmrs_profile"))


def start_profile_capture(out_dir: str, duration_s: float = 2.0
                          ) -> tuple[bool, str]:
    """Start a bounded ``jax.profiler`` trace capture into ``out_dir``,
    auto-stopped after ``duration_s`` by a daemon timer.  One capture at a
    time per process (the profiler is process-global); returns
    ``(ok, dir_or_reason)``.  Never raises — the caller is a serving
    endpoint or the slow-step trigger, neither of which may die on a
    profiler hiccup."""
    import math

    global _capture_active
    # NaN survives min/max clamps and would kill the stop timer's
    # Event.wait, leaving _capture_active wedged True forever — the same
    # reason the deadline parser refuses non-finite budgets
    duration_s = float(duration_s)
    if not math.isfinite(duration_s):
        duration_s = 2.0
    duration_s = min(max(duration_s, 0.1), 60.0)
    with _capture_lock:
        if _capture_active:
            return False, "a profile capture is already running"
        _capture_active = True
    try:
        import pathlib

        import jax

        pathlib.Path(out_dir).mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(out_dir))
    except Exception as e:  # noqa: BLE001 - report, never raise
        with _capture_lock:
            _capture_active = False
        return False, f"profiler start failed: {type(e).__name__}: {e}"

    def _stop() -> None:
        global _capture_active
        try:
            import jax

            jax.profiler.stop_trace()
            logger.info("profile capture written to %s", out_dir)
        except Exception:  # noqa: BLE001 - best-effort stop
            logger.warning("profiler stop failed", exc_info=True)
        finally:
            with _capture_lock:
                _capture_active = False

    timer = threading.Timer(duration_s, _stop)
    timer.daemon = True
    timer.start()
    logger.info("profile capture started: %s (%.1fs)", out_dir, duration_s)
    return True, out_dir


def slow_step_threshold_s() -> float:
    """The ``LMRS_PROFILE_ON_SLOW_STEP`` trigger threshold (seconds);
    0 = disabled.  Read per call so tests can arm it without rebuilding
    the engine."""
    return env_float("LMRS_PROFILE_ON_SLOW_STEP", 0.0, lo=0.0)
