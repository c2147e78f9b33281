"""Hang-survival tier, layer 1: the dispatch watchdog.

The reference pipeline's only robustness primitive was an HTTP timeout +
retry around each API call (llm_executor.py:198-228); collapsing the API
boundary onto the TPU removed that last line of defense — a dispatch
that *wedges* (hung chip, stuck DMA, stalled collective, or an injected
``scheduler.heartbeat`` stall) used to freeze the whole engine forever,
because the scheduler loop blocks synchronously in ``jax.device_get``
with no timeout.  This module turns a wedge into a bounded, observable
failure:

* :class:`DispatchWatchdog` — monotonic heartbeat state the scheduler
  loop stamps once per iteration (``beat``).  ``LMRS_WATCHDOG_S`` sets
  the wedge threshold explicitly; the default (0 = auto) scales off an
  EMA of the observed inter-beat step time, so a chip that normally
  steps in 20 ms is declared wedged long before one that legitimately
  runs 2 s decode blocks.  Compiling shapes get a one-shot grace window
  (``grace_cold``): a first-dispatch XLA compile can take minutes and
  must never read as a hang.

* :class:`WatchdogRunner` — owned by ``JaxEngine`` when the watchdog is
  armed (``LMRS_WATCHDOG``, default on).  The scheduler's ``run()``
  moves onto a dedicated daemon dispatch thread and the CALLER thread
  becomes the watchdog: it polls the heartbeat while waiting on the run.
  When no progress lands within the threshold it declares a wedge —
  flight-recorder postmortem (``reason="watchdog"``), then synthesizes
  terminal results for every request the run never delivered:
  deadline-expired requests get their contractual ``"deadline"`` result
  (the sweep a wedged loop can never reach — docs/ROBUSTNESS.md),
  everything else ``finish_reason="wedged"`` with ``error`` set so the
  executor's retry machinery re-dispatches them.  The engine then runs
  FAIL-FAST degraded — new batches return wedged results immediately
  instead of queueing behind the dead dispatch — until the abandoned
  run's thread eventually returns (a transient stall self-heals; the
  scheduler's own recovery/finally restores the pool) or the process is
  bounced by the supervisor (serving/supervisor.py).

* :class:`DaemonExecutor` — a minimal single-worker executor whose
  thread is a DAEMON: a wedged dispatch (or probe) future must never pin
  interpreter exit the way a stuck ``ThreadPoolExecutor`` worker does.
  Shared with ``engine/replicated.py``'s per-replica pools.

``LMRS_WATCHDOG=0`` restores today's byte-for-byte behavior: the
scheduler runs inline on the caller thread, no runner thread exists, and
the heartbeat sites cost one ``None`` check each.
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeout
from dataclasses import dataclass, field

from lmrs_tpu.engine.api import GenerationRequest, GenerationResult
from lmrs_tpu.obs import dump_postmortem
from lmrs_tpu.utils.env import env_float

logger = logging.getLogger("lmrs.watchdog")

# a compiling shape's first dispatch may take minutes (multi-second XLA
# compiles at real model sizes; tens of minutes cold on the CPU CI
# emulator) — a one-shot grace window this wide keeps every legitimate
# compile out of the wedge detector without a knob nobody should tune
COLD_COMPILE_GRACE_S = 3600.0


class DaemonExecutor:
    """Single-worker executor over one DAEMON thread.

    ``concurrent.futures.ThreadPoolExecutor`` workers are non-daemon:
    one wedged future pins interpreter exit forever (the
    ``engine/replicated.py`` probe note).  This executor keeps the same
    ``submit() -> Future`` surface on a thread that can never hold the
    process hostage.  Tasks run strictly in submission order, so it is a
    drop-in for the repo's max_workers=1 serialization pools."""

    def __init__(self, thread_name: str = "lmrs-worker"):
        import queue

        self._q: queue.Queue = queue.Queue()
        # orders the shutdown flag against enqueues: without it a submit
        # racing shutdown could land its item BEHIND the stop sentinel —
        # a future that never runs and is never cancelled, which a
        # watcher would poll forever
        self._mu = threading.Lock()
        self._shutdown = False  # guarded-by: _mu
        self._thread = threading.Thread(target=self._loop,
                                        name=thread_name, daemon=True)
        self._thread.start()

    def _loop(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            fut, fn, args, kwargs = item
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                fut.set_result(fn(*args, **kwargs))
            except BaseException as e:  # noqa: BLE001 - future carries it
                fut.set_exception(e)

    def submit(self, fn, *args, **kwargs) -> Future:
        fut: Future = Future()
        with self._mu:
            if self._shutdown:
                raise RuntimeError("executor is shut down")
            self._q.put((fut, fn, args, kwargs))
        return fut

    def shutdown(self, wait: bool = False, cancel_futures: bool = False) -> None:
        """Stop accepting work; the daemon thread drains (or dies with
        the process).  ``cancel_futures`` cancels everything still
        queued — a wedged RUNNING task is simply abandoned (daemon)."""
        with self._mu:
            self._shutdown = True
            if cancel_futures:
                import queue

                while True:
                    try:
                        item = self._q.get_nowait()
                    except queue.Empty:
                        break
                    if item is not None:
                        item[0].cancel()
            self._q.put(None)
        if wait:
            self._thread.join(timeout=5.0)


class DispatchWatchdog:
    """Monotonic heartbeat + wedge-threshold state (see module doc).

    Thread contract: ``beat``/``grace_cold``/``run_started``/
    ``run_ended`` are called by the dispatch thread; ``stalled_for`` /
    ``timeout_s`` by the watching caller thread.  All state writes are
    single plain-float/bool stores (GIL-atomic), read racily on purpose
    — a heartbeat landing mid-check just reads as progress."""

    def __init__(self):
        self.ema_step_s: float | None = None  # inter-beat EMA (warm steps)
        self._last_beat: float | None = None  # monotonic; None = no run live
        self._grace_until = 0.0  # monotonic deadline of a cold-shape grace
        # True while the CURRENT inter-beat window contained a compile
        # grace — the next beat must skip the EMA fold even though
        # grace_end() already re-armed stall detection (folding a 120s
        # compile wall would inflate the auto threshold ~30x for the
        # rest of the run)
        self._window_graced = False

    # ------------------------------------------------- dispatch-thread side

    def run_started(self) -> None:
        self._grace_until = 0.0
        self._window_graced = False
        self._last_beat = time.monotonic()

    def run_ended(self) -> None:
        self._last_beat = None

    def beat(self) -> None:
        """One scheduler-loop iteration landed: progress.  Folds the
        inter-beat gap into the step-time EMA unless a cold-compile
        grace opened anywhere in the window (a compile wall must not
        inflate the wedge threshold for the rest of the run — the flag,
        not ``_grace_until``, carries this: ``grace_end`` re-arms stall
        detection the moment the compile lands, but the wall still
        pollutes THIS window's gap)."""
        now = time.monotonic()
        prev = self._last_beat
        if prev is not None and not self._window_graced:
            gap = now - prev
            self.ema_step_s = (gap if self.ema_step_s is None
                               else 0.8 * self.ema_step_s + 0.2 * gap)
        self._window_graced = False
        self._grace_until = 0.0
        self._last_beat = now

    def grace_cold(self) -> None:
        """The next dispatch compiles a new shape: suspend wedge
        detection for one generous window (closed by ``grace_end`` when
        the compile lands, or by the next beat)."""
        self._grace_until = time.monotonic() + COLD_COMPILE_GRACE_S
        self._window_graced = True

    def grace_end(self) -> None:
        """The cold dispatch completed: re-arm the detector NOW, its clock
        restarted.  Without the re-arm, a grace opened for a compile in
        the same loop iteration would also mask a genuine stall at the
        next loop-top heartbeat site.  Without the restart, the stall
        would be counted from the beat BEFORE the compile, so a compile
        longer than the threshold read as a wedge the moment it landed
        (PERF.md section 6: a lead for PR 36's failed requests on ouro)."""
        self._grace_until = 0.0
        if self._last_beat is not None:
            self._last_beat = time.monotonic()

    # --------------------------------------------------- watcher-thread side

    def timeout_s(self) -> float:
        """The wedge threshold: ``LMRS_WATCHDOG_S`` when set (> 0), else
        scaled off the step-time EMA — generous (30x a normal step,
        floored well above any warm dispatch) because a false positive
        abandons a healthy run.  Read per call so tests can retune
        without rebuilding the engine."""
        explicit = env_float("LMRS_WATCHDOG_S", 0.0, lo=0.0)
        if explicit > 0:
            return explicit
        if self.ema_step_s is None:
            return 300.0  # no sample yet: only a gross hang trips
        return min(max(30.0 * self.ema_step_s, 60.0), 900.0)

    def stalled_for(self) -> float:
        """Seconds since the last heartbeat, 0.0 when no run is live or
        a cold-compile grace window is open."""
        last = self._last_beat
        if last is None or time.monotonic() < self._grace_until:
            return 0.0
        return time.monotonic() - last


@dataclass
class _RunCtx:
    """Per-run bookkeeping the wedge sweep synthesizes results from.
    Mutated only by the dispatch thread (callback wrappers) until
    ``abandoned`` flips — after which the wrappers are no-ops and the
    watcher thread owns the snapshot."""

    known: list[GenerationRequest]
    results: dict[int, GenerationResult] = field(default_factory=dict)
    streamed: dict[int, str] = field(default_factory=dict)
    abandoned: bool = False


class WatchdogRunner:
    """Run ``scheduler.run()`` on a daemon dispatch thread, watched from
    the caller thread (see module doc).  One runner per scheduler; calls
    to :meth:`run` are serialized by the engine's existing callers (the
    HTTP batcher loop / the executor / a replica's worker pool) exactly
    as direct ``scheduler.run`` calls were."""

    def __init__(self, scheduler):
        self.sched = scheduler
        self._pool = DaemonExecutor(thread_name="lmrs-dispatch")
        self._lock = threading.Lock()
        self._stuck: Future | None = None  # guarded-by: _lock
        self._stuck_since = 0.0            # guarded-by: _lock

    # ---------------------------------------------------------------- state

    @property
    def wedged(self) -> bool:
        """True while a wedged run still holds the dispatch thread (the
        engine's fail-fast degraded state)."""
        with self._lock:
            return self._stuck is not None and not self._stuck.done()

    def wait_idle(self, timeout_s: float = 30.0) -> bool:
        """Block until any abandoned run finishes and clear the degraded
        state (tests; the serving layer recovers lazily at the next
        batch).  Returns True when the dispatch thread is idle."""
        with self._lock:
            fut = self._stuck
        if fut is None:
            return True
        try:
            fut.result(timeout=timeout_s)
        except Exception:  # noqa: BLE001 - the run's own failure is logged
            pass
        with self._lock:
            if self._stuck is fut and fut.done():
                self._clear_stuck_locked(fut)
        return fut.done()

    def shutdown(self) -> None:
        self._pool.shutdown(wait=False, cancel_futures=True)

    def _clear_stuck_locked(self, fut: Future) -> None:  # holds-lock: _lock
        """Caller holds self._lock."""
        exc = fut.exception() if fut.done() else None
        if exc is not None:
            # the abandoned run died; the scheduler's except path already
            # ran pool recovery, so the engine is usable again
            logger.warning("abandoned wedged run finished with %s: %s",
                           type(exc).__name__, exc)
        else:
            logger.info("wedged dispatch recovered after %.1fs; engine "
                        "re-armed", time.monotonic() - self._stuck_since)
        self._stuck = None

    # ------------------------------------------------------------------ run

    def run(self, requests: list[GenerationRequest],
            on_result=None, on_tokens=None) -> list[GenerationResult]:
        stuck_for: float | None = None
        with self._lock:
            fut = self._stuck
            if fut is not None:
                if fut.done():
                    self._clear_stuck_locked(fut)
                else:
                    stuck_for = time.monotonic() - self._stuck_since
        if stuck_for is not None:
            # fail-fast degraded: nothing queues behind a dead dispatch —
            # the caller's retry/routing layers place the work elsewhere
            # (or the supervisor bounces us).  Delivery runs OUTSIDE the
            # lock: on_result callbacks are arbitrary caller code and may
            # themselves read wedged()/wait_idle() (the non-reentrant
            # lock would deadlock), same discipline as the wedge sweep.
            return self._deliver_synthesized(
                _RunCtx(list(requests)), on_result,
                err=f"engine wedged: dispatch thread stuck for "
                    f"{stuck_for:.1f}s")
        ctx = _RunCtx(list(requests))
        run_fut = self._pool.submit(
            self.sched.run, requests,
            on_result=self._wrap_on_result(ctx, on_result),
            on_tokens=self._wrap_on_tokens(ctx, on_tokens))
        wd = self.sched.watchdog
        while True:
            try:
                # the caller thread IS the watchdog while it waits: poll
                # granularity adapts to the threshold (cache-cheap; the
                # run future wakes it immediately on completion)
                return run_fut.result(
                    timeout=max(0.05, min(wd.timeout_s() / 4.0, 2.0)))
            except FutureTimeout:
                stalled = wd.stalled_for()
                timeout = wd.timeout_s()
                if stalled <= timeout:
                    continue
                return self._declare_wedge(ctx, run_fut, on_result,
                                           stalled, timeout)

    # ------------------------------------------------------------ callbacks

    def _wrap_on_result(self, ctx: _RunCtx, user_cb):
        """Track delivery + submissions; mute everything once abandoned
        (a resumed wedged run must not double-deliver into the caller's
        queues).  Returning None keeps the scheduler's no-callback fast
        path when the caller passed none — except that delivery tracking
        still matters for the wedge sweep, so a tracker is always
        installed."""
        def wrapped(res: GenerationResult, submit) -> None:
            if ctx.abandoned:
                return
            ctx.results[res.request_id] = res
            if user_cb is not None:
                def tracked_submit(more: list[GenerationRequest]) -> None:
                    ctx.known.extend(more)
                    submit(more)

                user_cb(res, tracked_submit)

        return wrapped

    def _wrap_on_tokens(self, ctx: _RunCtx, user_cb):
        """Delta tracker (the wedge sweep's partial-text source) — but
        ONLY when the caller actually streams: installing a callback on
        non-streaming runs would force the scheduler's per-block
        frontier-trimming path and hold a second copy of every
        completion for pure overhead.  Non-streaming wedged results
        carry text="" — their callers retry on the marked error anyway."""
        if user_cb is None:
            return None

        def wrapped(rid: int, delta: str) -> None:
            if ctx.abandoned:
                return
            ctx.streamed[rid] = ctx.streamed.get(rid, "") + delta
            user_cb(rid, delta)

        return wrapped

    # ---------------------------------------------------------- wedge sweep

    def _declare_wedge(self, ctx: _RunCtx, run_fut: Future, on_result,
                       stalled: float, timeout: float
                       ) -> list[GenerationResult]:
        """No heartbeat within the threshold: abandon the run, freeze the
        evidence, and terminate every undelivered request (see module
        doc).  The abandoned thread keeps the stuck device call; if it
        ever returns, the run's own finally/except restores the pool and
        the degraded state clears at the next batch."""
        ctx.abandoned = True  # flipped BEFORE any delivery: the stuck
        # thread may resume mid-sweep and must find its callbacks muted
        with self._lock:
            self._stuck = run_fut
            self._stuck_since = time.monotonic()
        # cancel everything the abandoned run still holds: if the stall
        # is transient, its first post-stall loop iteration sweeps the
        # cancels and the run drains in ~one block instead of recomputing
        # the whole abandoned workload to muted callbacks — the engine
        # re-arms while the caller's retry budget is still alive (the
        # end-to-end wedge drive caught a degraded engine outliving
        # 3 x retry_delay without this)
        for r in ctx.known:
            self.sched.cancel(r.request_id)
        self.sched._c_watchdog_fires.inc()
        undelivered = [r for r in ctx.known
                       if r.request_id not in ctx.results]
        logger.error("dispatch wedge: no scheduler heartbeat for %.1fs "
                     "(threshold %.1fs); abandoning the run, %d request(s) "
                     "terminate wedged/deadline", stalled, timeout,
                     len(undelivered))
        # postmortem FIRST, before synthesis mutates counters: the dump
        # must show the metrics as the wedge left them (the same ordering
        # rule as the dispatch-fault recovery path).  No-op unless
        # LMRS_POSTMORTEM_DIR is armed; never raises.
        dump_postmortem(
            "watchdog", metrics=self.sched.metrics,
            extra={"stalled_s": round(stalled, 3),
                   "timeout_s": round(timeout, 3),
                   "undelivered": len(undelivered),
                   "step_ema_s": self.sched.watchdog.ema_step_s})
        return self._deliver_synthesized(
            ctx, on_result,
            err=f"engine dispatch wedged: no progress for {stalled:.1f}s")

    def _deliver_synthesized(self, ctx: _RunCtx, on_result,
                             err: str) -> list[GenerationResult]:
        """Terminal results for every request the run never delivered:
        ``"deadline"`` for expired budgets (no error — the contractual
        outcome the caller asked for; the executor must not retry it),
        ``"wedged"`` + error for the rest (the executor retries those
        once a healthy engine can take them).  Partial streamed text is
        kept — it is real output a streaming client may already hold
        (the cancel/expiry contract, scheduler.cancel docstring)."""
        now = time.time()
        out: list[GenerationResult] = []
        for req in ctx.known:
            rid = req.request_id
            res = ctx.results.get(rid)
            if res is None:
                text = ctx.streamed.get(rid, "")
                expired = req.deadline_s is not None and req.deadline_s <= now
                if expired:
                    res = GenerationResult(
                        request_id=rid, text=text,
                        finish_reason="deadline")
                    self.sched._c_deadline.inc()
                else:
                    res = GenerationResult(
                        request_id=rid, text=text,
                        finish_reason="wedged", error=err)
                    self.sched._c_wedged.inc()
                # the wedge bill still lands in the cost ledger (and the
                # SLO outcome stream): a wedged request is exactly the
                # kind of waste per-tenant accounting must show
                self.sched.cost_finish(req, res)
                if on_result is not None:
                    on_result(res, self._dead_submit)
            out.append(res)
        return out

    @staticmethod
    def _dead_submit(more: list[GenerationRequest]) -> None:
        logger.warning("submit() ignored on a wedged run: %d request(s) "
                       "dropped (the caller's retry owns them)", len(more))
