"""Step-anatomy profiler + ragged-span bucket economics.

The obs stack measures dispatch WALLS (obs/perf.py roofline attribution,
the PR 14 cost ledger) but nothing decomposes the host side of a
scheduler iteration — and both remaining perf mysteries are host-side:
the spec-verify step costs ~3x a plain step while the verify kernel is
only 1.09x, and the 1B prefill MFU gap is "launch/tail overhead at small
shapes".  This module names every microsecond between two dispatches:

* ``StepAnatomy.seg(name)`` is a nestable context timer.  Entering an
  inner segment PAUSES the outer one (elapsed time is attributed to the
  outer segment first), so segments never overlap and their per-iteration
  sum can never exceed the iteration wall.  The difference is tracked as
  an explicit ``residual`` — the anatomy is conservation-audited like the
  ledger: ``wall == seg_sum + residual`` must reconcile within eps in
  ``scheduler.audit()``.
* ``iter_begin()`` / ``iter_end(cls)`` / ``iter_abort()`` bound one
  scheduler iteration.  ``iter_end`` folds the iteration's record into
  the cumulative totals (and the per-class reservoir for p50/p95);
  ``iter_abort`` DISCARDS the open record — an iteration killed by a
  dispatch fault contributes nothing, so the audit identity survives
  chaos arms by construction rather than by luck.
* ``dispatch(program, key, ...)`` is the ``dispatch`` segment of one
  device dispatch AND its record: the site says what the dispatch carried
  (rows and row slots, real and padded query positions, prompt positions,
  context tokens, the span kernel's wide-tile tokens and page fetches,
  the pages a fresh prefill writes whole, layer applications, the bytes a
  decode block's steps read, cold or warm) and the record
  folds into a per-program, per-key table that ``snapshot()`` /
  ``report(before=...)`` window like the segment totals.  On a cold key
  the segment's wall is the compile.
  The ragged-span bucket economics of PR 16/18 (``buckets``,
  ``rpa_pad_waste_ratio``) are a view of the table's ``rpa`` program.
* Every segment goes through ``obs.trace.span``: ``sched.<segment>`` in a
  running ``jax.profiler`` trace (the device's clock) and, when the
  Chrome-JSON ``Tracer`` is armed, the same name and args in its ring.

Always-on by default; ``LMRS_ANATOMY=0`` swaps in ``NULL_ANATOMY``, which
registers NO metrics and no-ops every call — output, wire format, and the
pre-existing metrics shape are byte-identical to a build without this
module.  Overhead when on is a handful of ``time.time()`` calls and dict
adds per iteration plus one ``span`` per segment, which outside a profiler
session and with the tracer off is two object allocations and a flag test
(same ≤2% budget discipline as obs/trace.py).
"""

from __future__ import annotations

import time
from collections import deque

from lmrs_tpu.obs.flight import dump_postmortem
from lmrs_tpu.obs.metrics import MetricsRegistry, log_buckets
from lmrs_tpu.obs.trace import get_tracer, span
from lmrs_tpu.utils.env import env_bool, env_float, env_int

# the named host segments of one scheduler iteration, in loop order:
#   admit    — fault/heartbeat/sweep bookkeeping + admission & QoS pick
#   plan     — span/operand/page-table build (host-side numpy plumbing)
#   draft    — spec draft+reseed plumbing (seed_history, stale reseeds)
#   dispatch — the jitted device call (compile time lands here, cold keys)
#   fetch    — result transfer (device_get / _timed_get)
#   finish   — emitted-token sweep + perf/ledger/SLO notes + slot finish
#   io       — journal/session delivery (on_result callbacks)
SEGMENTS: tuple[str, ...] = ("admit", "plan", "draft", "dispatch",
                             "fetch", "finish", "io")
_SEG_SET = frozenset(SEGMENTS)

# what a scheduler dispatch site may call itself (``dispatch(program, ...)``);
# the first four compute prompt positions and feed the prefill_* counters
PROGRAMS: tuple[str, ...] = ("prefill", "packed", "prefill_chunk", "rpa",
                             "decode", "spec")
PROMPT_PROGRAMS = frozenset(PROGRAMS[:4])
# the additive fields of one dispatch record, as the table and the report
# carry them (the report adds ``cold_ms`` and, per program, ``keys``)
RECORD_FIELDS: tuple[str, ...] = ("dispatches", "rows", "row_slots",
                                  "q_tokens", "prompt_tokens", "q_slots",
                                  "ctx_tokens", "wide_tokens",
                                  "kv_page_reads", "page_writes",
                                  "layer_passes", "hbm_bytes", "cold")

# what a routed model's dispatches add to their record once their counts
# are back (``note_moe``); absent from a dense model's records and report
MOE_FIELDS: tuple[str, ...] = ("moe_routed_pairs", "moe_expert_tokens_max",
                               "moe_expert_tokens_mean", "moe_extra_passes",
                               "moe_experts_touched")

# what a windowed model's dispatches add to their record (``dispatch``'s
# ``window``): pages its decode steps walk in full and in window layers,
# and the flash prefill's tile pairs, all and those the window leaves out;
# absent from every other model's records and report
WINDOW_FIELDS: tuple[str, ...] = ("kv_pages_full", "kv_pages_window",
                                  "flash_blocks", "flash_blocks_skipped")

# iteration step classes (the report's per-class split axis)
CLASSES: tuple[str, ...] = ("plain", "mixed", "spec", "prefill")

# host-overhead histogram: 1 µs (an idle-ish pass) .. 10 s (a compile)
_HOST_US_BUCKETS = log_buckets(1.0, 1e7, per_decade=3)


def anatomy_enabled() -> bool:
    """The ``LMRS_ANATOMY`` kill switch (default on)."""
    return env_bool("LMRS_ANATOMY", True)


def slow_step_ms() -> float:
    """Slow-step postmortem threshold in ms; 0 disables.  Read per
    iteration (not cached) so tests can arm it without rebuilding the
    engine — same convention as ``perf.slow_step_threshold_s``."""
    return env_float("LMRS_ANATOMY_SLOW_MS", 0.0, lo=0.0)


def reservoir_size() -> int:
    """Per-class percentile reservoir depth (``LMRS_ANATOMY_RESERVOIR``)."""
    return env_int("LMRS_ANATOMY_RESERVOIR", 512, lo=16)


class _Seg:
    """One ``with anatomy.seg(name):`` activation.  Stack-based with
    pause semantics: entering attributes the elapsed slice to the
    enclosing segment, exiting resumes it — re-entrant on the same name
    and exception-safe (an unwind closes every frame on the way out)."""

    __slots__ = ("a", "name", "_span")

    def __init__(self, a: "StepAnatomy", name: str):
        self.a = a
        self.name = name
        self._span = None

    def _span_args(self) -> dict:
        if self.name == "fetch":
            # the device runs dispatches in order, so the fetch that
            # returns retires every dispatch issued since the last one
            ids, self.a._unretired = self.a._unretired, []
            # "+"-joined: a comma would end the value in the profiler's
            # "name#key=value,key=value#" encoding of an annotation
            return {"retires": "+".join(map(str, ids))}
        return {}

    def __enter__(self):
        a = self.a
        self._span = span("sched." + self.name, **self._span_args())
        self._span.__enter__()
        if not a._open:
            return self
        t = a._clock()
        st = a._stack
        if st:
            p = st[-1]
            a._cur[p[0]] += t - p[2]  # pause the enclosing segment
        st.append([self.name, t, t])  # [name, t_enter, t_resume]
        return self

    def __exit__(self, exc_type, exc, tb):
        a = self.a
        st = a._stack
        if a._open and st:
            t = a._clock()
            e = st.pop()
            a._cur[e[0]] += t - e[2]
            if st:
                st[-1][2] = t  # resume the enclosing segment
        self._span.__exit__(exc_type, exc, tb)
        return False


class _Dispatch(_Seg):
    """One ``with anatomy.dispatch(program, key, ...) as d:`` activation:
    the ``dispatch`` segment, with the record of what it carried.  The
    counts fold into the table on entry, beside the scheduler's own
    counters (``lmrs_prefill_tokens_total`` counts before the call too), so
    a dispatch that raises keeps the audit identities; the wall of a cold
    key folds on exit.  ``d.emitted(n)`` adds query tokens known only once
    the result is back (a decode block's emitted tokens)."""

    __slots__ = ("rec", "id", "_cold_t0")

    def __init__(self, a: "StepAnatomy", rec: dict):
        super().__init__(a, "dispatch")
        self.rec = rec
        self.id = 0
        self._cold_t0 = 0.0

    def _span_args(self) -> dict:
        r = self.rec
        return {"program": r["program"], "key": _key_str(r["key"]),
                "id": self.id, "rows": r["rows"], "q_tokens": r["q_tokens"],
                "q_slots": r["q_slots"], "cold": r["cold"]}

    def __enter__(self):
        a, r = self.a, self.rec
        a._dispatch_id += 1
        self.id = a._dispatch_id
        a._unretired.append(self.id)
        a._fold(r)
        if r["cold"]:
            self._cold_t0 = a._clock()
        tr = get_tracer()
        if tr is not None and r["prompt_tokens"] > 0:
            # the ring's per-dispatch instant, under the name it has had
            # since PR 7, now with the record's one set of fields
            tr.instant("prefill_dispatch", args={
                "id": self.id, "program": r["program"],
                "key": _key_str(r["key"]),
                **{f: int(r[f]) for f in RECORD_FIELDS[1:]}})
        return super().__enter__()

    def __exit__(self, exc_type, exc, tb):
        if self.rec["cold"]:
            self.a._fold_cold(self.rec,
                              max(self.a._clock() - self._cold_t0, 0.0))
        return super().__exit__(exc_type, exc, tb)

    def emitted(self, n: int) -> None:
        self.a._table[(self.rec["program"], self.rec["key"])][
            "q_tokens"] += int(n)


def _key_str(key: tuple) -> str:
    """``("rpa", 32768, 16)`` -> ``"rpa:32768:16"``: a dispatch key as the
    report, the spans and the ring spell it."""
    return ":".join(str(k) for k in key)


class _NullSeg:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def emitted(self, n: int) -> None:
        pass


_NULL_SEG = _NullSeg()


class StepAnatomy:
    """Conservation-audited per-iteration host-segment profiler + the
    per-program dispatch table (module docstring).  One instance per
    scheduler run context; NOT thread-safe by design — only the scheduler loop thread
    touches the iteration lifecycle, matching every other per-run
    accumulator in the scheduler."""

    enabled = True

    def __init__(self, registry: MetricsRegistry, *, metrics_cb=None,
                 clock=time.time):
        self._clock = clock
        self._metrics_cb = metrics_cb
        # iteration lifecycle state
        self._open = False
        self._stack: list[list] = []
        self._cur: dict[str, float] = {}
        self._t_iter = 0.0
        # cumulative totals (floats keep sign for the audit identity;
        # counter incs are clamped at 0 because Counter refuses decrements)
        self._iters = 0
        self._aborted = 0
        self._wall = 0.0
        self._residual = 0.0
        self._segs = {s: 0.0 for s in SEGMENTS}
        self._host_us = 0.0  # sum of (wall - dispatch - fetch) in µs
        # per-class percentile reservoirs: cls -> deque[(wall, segs tuple)]
        cap = reservoir_size()
        self._res: dict[str, deque] = {c: deque(maxlen=cap) for c in CLASSES}
        self._cls_iters = {c: 0 for c in CLASSES}
        # the dispatch table: (program, key) -> RECORD_FIELDS + cold_s +
        # slots (the q_slots of the key's first dispatch: a key is one
        # compiled shape, so every dispatch on it holds as many)
        self._table: dict[tuple[str, tuple], dict] = {}
        self._dispatch_id = 0
        self._unretired: list[int] = []  # ids no fetch has retired yet
        # flat sums of the same records, for ``scheduler.metrics``
        self._flat = {"prefill_dispatches": 0, "prefill_rows": 0,
                      "prefill_row_slots": 0, "prefill_query_tokens": 0,
                      "prefill_token_slots": 0, "prefill_page_writes": 0,
                      "prefill_seq_rung_dispatches": 0, "layer_passes": 0,
                      "decode_hbm_bytes": 0, "decode_kv_page_reads": 0,
                      "rpa_wide_tokens": 0, "rpa_kv_page_reads": 0,
                      "cold_dispatches": 0, "cold_seconds": 0.0}
        # routed models only (the scheduler sets ``has_moe`` for one): a
        # dense model's counters and report stay as they were
        self._moe_flat = dict.fromkeys(MOE_FIELDS, 0)
        self.has_moe = False
        # windowed models only, likewise (``has_window``)
        self._window_flat = dict.fromkeys(WINDOW_FIELDS, 0)
        self.has_window = False

        c, g, h = (registry.counter, registry.gauge, registry.histogram)
        self._c_iters = c("lmrs_anatomy_iterations_total",
                          "scheduler iterations profiled by the anatomy")
        self._c_aborted = c("lmrs_anatomy_aborted_iterations_total",
                            "iterations discarded mid-flight (fault unwind)")
        self._c_wall = c("lmrs_anatomy_wall_seconds_total",
                         "summed iteration wall time", unit="s")
        self._c_residual = c("lmrs_anatomy_residual_seconds_total",
                             "iteration wall not covered by any segment",
                             unit="s")
        self._c_slow = c("lmrs_anatomy_slow_steps_total",
                         "iterations over LMRS_ANATOMY_SLOW_MS")
        self._seg_c = {
            "admit": c("lmrs_anatomy_admit_seconds_total",
                       "admission/QoS-pick + sweep host time", unit="s"),
            "plan": c("lmrs_anatomy_plan_seconds_total",
                      "span/operand/plan build host time", unit="s"),
            "draft": c("lmrs_anatomy_draft_seconds_total",
                       "spec draft+reseed plumbing host time", unit="s"),
            "dispatch": c("lmrs_anatomy_dispatch_seconds_total",
                          "jitted device dispatch call time", unit="s"),
            "fetch": c("lmrs_anatomy_fetch_seconds_total",
                       "device result fetch time", unit="s"),
            "finish": c("lmrs_anatomy_finish_seconds_total",
                        "finish sweep + ledger/SLO note host time",
                        unit="s"),
            "io": c("lmrs_anatomy_io_seconds_total",
                    "journal/session delivery host time", unit="s"),
        }
        self._h_host_us = h("lmrs_anatomy_host_us_step", _HOST_US_BUCKETS,
                            "per-iteration host overhead (wall - dispatch "
                            "- fetch)", unit="us")
        self._c_b_disp = c("lmrs_rpa_bucket_dispatches_total",
                           "ragged-span dispatches across all buckets")
        self._c_b_real = c("lmrs_rpa_bucket_real_tokens_total",
                           "real span tokens dispatched (pre-padding)")
        self._c_b_pad = c("lmrs_rpa_bucket_padded_tokens_total",
                          "padding tokens added by pow2 bucketing")
        self._c_b_compile = c("lmrs_rpa_bucket_compile_seconds_total",
                              "cold-key dispatch wall (compile) time",
                              unit="s")

    # ------------------------------------------------------------- lifecycle

    def iter_begin(self) -> None:
        if self._open:  # defensive: a lost iter_end must not leak forever
            self.iter_abort()
        self._stack = []
        self._cur = {s: 0.0 for s in SEGMENTS}
        self._t_iter = self._clock()
        self._open = True

    def seg(self, name: str):
        """Context timer for one named segment (see ``SEGMENTS``)."""
        if name not in _SEG_SET:
            raise ValueError(f"unknown anatomy segment {name!r} "
                             f"(want one of {SEGMENTS})")
        return _Seg(self, name)

    def iter_end(self, cls: str) -> None:
        """Fold the open iteration into the totals under step class
        ``cls`` — the only place cumulative state advances, so a caller
        that aborts instead contributes exactly nothing."""
        if not self._open:
            return
        # defensively close dangling frames (a seg left open by a caller
        # bug still participates in conservation rather than vanishing)
        t = self._clock()
        while self._stack:
            e = self._stack.pop()
            self._cur[e[0]] += t - e[2]
            if self._stack:
                self._stack[-1][2] = t
        wall = t - self._t_iter
        seg_sum = sum(self._cur.values())
        residual = wall - seg_sum
        self._open = False

        self._iters += 1
        self._wall += wall
        self._residual += residual
        self._c_iters.inc()
        self._c_wall.inc(max(wall, 0.0))
        self._c_residual.inc(max(residual, 0.0))
        host_us = max(wall - self._cur["dispatch"] - self._cur["fetch"],
                      0.0) * 1e6
        self._host_us += host_us
        self._h_host_us.observe(host_us)
        for s in SEGMENTS:
            self._segs[s] += self._cur[s]
            self._seg_c[s].inc(max(self._cur[s], 0.0))
        if cls not in self._res:  # unknown class: fold under "plain"
            cls = "plain"
        self._cls_iters[cls] += 1
        self._res[cls].append(
            (wall, tuple(self._cur[s] for s in SEGMENTS), residual))

        thresh = slow_step_ms()
        if thresh > 0.0 and wall * 1e3 > thresh:
            self._c_slow.inc()
            dump_postmortem("slow_step", metrics=(
                self._metrics_cb() if self._metrics_cb else None),
                extra={"anatomy": {
                    "class": cls,
                    "wall_ms": round(wall * 1e3, 3),
                    "threshold_ms": thresh,
                    "segments_ms": {s: round(self._cur[s] * 1e3, 3)
                                    for s in SEGMENTS},
                    "residual_ms": round(residual * 1e3, 3)}})

    def iter_abort(self) -> None:
        """Discard the open iteration (fault unwind / stop request).
        Idempotent — the scheduler calls it from ``finally``."""
        if not self._open:
            return
        self._open = False
        self._stack = []
        self._aborted += 1
        self._c_aborted.inc()

    def iter_discard(self) -> None:
        """Close the open iteration WITHOUT counting it anywhere — the
        run-exit pass (the loop's "all work done" break) is bookkeeping,
        not a step, and must pollute neither the totals nor the aborted
        count chaos arms assert on."""
        self._open = False
        self._stack = []

    # --------------------------------------------------------- dispatch table

    def dispatch(self, program: str, key: tuple, *, rows: int,
                 row_slots: int, q_tokens: int, prompt_tokens: int,
                 q_slots: int, ctx_tokens: int, cold: bool,
                 wide_tokens: int = 0, kv_page_reads: int = 0,
                 page_writes: int = 0, layer_passes: int = 0,
                 hbm_bytes: int = 0, seq_rung: bool = False,
                 window: dict | None = None) -> _Dispatch:
        """The ``dispatch`` segment of one device dispatch, with what it
        carried.  ``program`` is one of ``PROGRAMS`` and ``key`` the site's
        own compile key; ``rows`` carry work out of ``row_slots`` operand
        rows; ``q_tokens`` are the real query positions (``prompt_tokens``
        of them prompt positions) out of the ``q_slots`` the operand
        holds; ``ctx_tokens`` are the KV tokens already in pages that the
        dispatch attends; ``cold`` says the key has never run, so the
        segment's wall is its compile.  A span dispatch whose attention
        runs in the ragged span kernel also says what the kernel will do
        with it (``ops/paged_attention.span_walk_counts``, the kernel's
        rule on the host): ``wide_tokens`` of the query positions fall in
        wide tiles, and its page walks fetch ``kv_page_reads`` pages; a
        decode block says the pages its walks fetch over every cache layer
        and step (``kv_page_reads``: utils/perf_model.StepBytes.pages).  A
        prefill dispatch says how many pages a layer it writes into the
        pool WHOLE (``page_writes``: ``row_slots`` x bucket / page size for
        a fresh prefill over whole pages, ``ops/paged_attention.
        whole_pages``; 0 where the program writes a row at a time) and
        whether its operand's positions lie below the power-of-two bucket
        of its longest prompt (``seq_rung``, ``scheduler.
        prefill_seq_rung``; summed into the flat counters only).  Every
        dispatch says how many layer applications it runs
        (``layer_passes``: the model's ``cache_layers``, ``n_layers`` x
        the passes of a looped stack, times the steps of a decode or
        speculative block; one step for a prefill or span dispatch).  A
        decode or speculative block says how many bytes its steps had to
        read before its routed experts (``hbm_bytes``: utils/perf_model.
        StepBytes.block; ``note_moe`` adds the experts once their counts
        are back; 0 on the other programs).  A windowed model's dispatch
        adds ``window``: ``WINDOW_FIELDS`` by the host's rule
        (``ContinuousScheduler._window_counts``)."""
        if program not in PROGRAMS:
            raise ValueError(f"unknown dispatch program {program!r} "
                             f"(want one of {PROGRAMS})")
        return _Dispatch(self, {
            "program": program, "key": tuple(key), "dispatches": 1,
            "rows": int(rows), "row_slots": int(row_slots),
            "q_tokens": int(q_tokens), "prompt_tokens": int(prompt_tokens),
            "q_slots": int(q_slots), "ctx_tokens": int(ctx_tokens),
            "wide_tokens": int(wide_tokens),
            "kv_page_reads": int(kv_page_reads),
            "page_writes": int(page_writes),
            "layer_passes": int(layer_passes),
            "hbm_bytes": int(hbm_bytes), "cold": bool(cold),
            "seq_rung": bool(seq_rung),
            **{f: int(v) for f, v in (window or {}).items()}})

    def _fold(self, r: dict) -> None:
        rec = self._table.get((r["program"], r["key"]))
        if rec is None:
            rec = self._table[(r["program"], r["key"])] = {
                **dict.fromkeys(RECORD_FIELDS, 0), "cold_s": 0.0,
                "slots": r["q_slots"]}
        for f in RECORD_FIELDS:
            rec[f] += r[f]
        for f in WINDOW_FIELDS:
            if f in r:
                rec[f] = rec.get(f, 0) + r[f]
                self._window_flat[f] += r[f]
        flat = self._flat
        flat["cold_dispatches"] += r["cold"]
        flat["layer_passes"] += r["layer_passes"]
        if r["program"] == "decode":
            flat["decode_hbm_bytes"] += r["hbm_bytes"]
            flat["decode_kv_page_reads"] += r["kv_page_reads"]
        if r["program"] in PROMPT_PROGRAMS:
            flat["prefill_dispatches"] += 1
            flat["prefill_rows"] += r["rows"]
            flat["prefill_row_slots"] += r["row_slots"]
            flat["prefill_query_tokens"] += r["q_tokens"]
            flat["prefill_token_slots"] += r["q_slots"]
            flat["prefill_page_writes"] += r["page_writes"]
            flat["prefill_seq_rung_dispatches"] += r["seq_rung"]
        if r["program"] == "rpa":
            flat["rpa_wide_tokens"] += r["wide_tokens"]
            flat["rpa_kv_page_reads"] += r["kv_page_reads"]
            self._c_b_disp.inc()
            self._c_b_real.inc(r["q_tokens"])
            self._c_b_pad.inc(max(r["q_slots"] - r["q_tokens"], 0))

    def _fold_cold(self, r: dict, seconds: float) -> None:
        self._table[(r["program"], r["key"])]["cold_s"] += seconds
        self._flat["cold_seconds"] += seconds
        if r["program"] == "rpa":
            self._c_b_compile.inc(seconds)

    def note_moe(self, program: str, key: tuple, *, pairs: int,
                 tokens_max: int, extra_passes: int, touched: int,
                 held: int, hbm_bytes: int = 0) -> None:
        """The routed layers' counts of dispatches on ``(program, key)``,
        known once a fetch has brought them back: token-expert pairs that
        landed on the ``held`` experts here, the busiest held expert's
        tokens and the mean over the held experts, grouped-product passes
        beyond the first, and the held experts that at least one pair
        chose (each summed over the routed layers and, in a decode block,
        its steps); ``hbm_bytes`` are the touched experts' bytes, which a
        decode or speculative block's record adds to its own."""
        rec = self._table[(program, tuple(key))]
        for f, v in zip(MOE_FIELDS, (pairs, tokens_max, pairs / held,
                                     extra_passes, touched)):
            rec[f] = rec.get(f, 0) + v
            self._moe_flat[f] += v
        rec["hbm_bytes"] += int(hbm_bytes)
        if program == "decode":
            self._flat["decode_hbm_bytes"] += int(hbm_bytes)

    def counters(self) -> dict:
        """The flat sums ``ContinuousScheduler.metrics`` carries: prefill
        dispatches, their real query positions, the positions their
        operands held, the pages they wrote whole and the dispatches on a
        sequence rung below the power of two (the prompt programs), the
        span kernel's wide-tile tokens and page fetches (the ``rpa``
        program), layer applications, cold dispatches and their wall (all
        programs), the bytes the decode program's steps had to read and
        the pages its walks fetch (``decode_hbm_bytes``,
        ``decode_kv_page_reads``: its records' ``hbm_bytes`` and
        ``kv_page_reads``); for a routed
        model, the ``MOE_FIELDS`` sums over all programs."""
        return {**self._flat, **(self._moe_flat if self.has_moe else {}),
                **(self._window_flat if self.has_window else {})}

    # --------------------------------------------------------------- reading

    def snapshot(self) -> dict:
        """Window anchor for ``report(before=...)`` (bench/serving_latency
        delta their measurement window off this, same convention as the
        scheduler's raw ``metrics`` snapshot)."""
        return {"iters": self._iters, "aborted": self._aborted,
                "wall": self._wall, "residual": self._residual,
                "host_us": self._host_us,
                "segs": dict(self._segs),
                "table": {k: dict(rec) for k, rec in self._table.items()}}

    def audit(self, prefill_tokens: int | None = None) -> list[str]:
        """Conservation check over the CUMULATIVE totals (safe to call
        mid-iteration: totals only advance at ``iter_end``).  Violations
        are returned as strings for ``scheduler.audit()`` to aggregate.
        ``prefill_tokens`` is the scheduler's own count of prompt positions
        dispatched (``lmrs_prefill_tokens_total``): the table's
        ``prompt_tokens`` have to add up to it."""
        violations: list[str] = []
        seg_sum = sum(self._segs.values())
        eps = 1e-6 * max(1, self._iters) + 1e-9
        drift = abs(self._wall - (seg_sum + self._residual))
        if drift > eps:
            violations.append(
                f"anatomy conservation: |wall - (segments + residual)| = "
                f"{drift:.3e}s over {self._iters} iterations (eps {eps:.3e})")
        if self._residual < -eps:
            violations.append(
                f"anatomy residual is negative: {self._residual:.3e}s "
                f"(segments overlap — pause bookkeeping broken)")
        for s, v in self._segs.items():
            if v < -eps:
                violations.append(f"anatomy segment {s} went negative: {v}")
        prompt = q_prompt_programs = 0
        for (program, key), rec in self._table.items():
            prompt += rec["prompt_tokens"]
            if program in PROMPT_PROGRAMS:
                q_prompt_programs += rec["q_tokens"]
            if rec["wide_tokens"] > rec["q_tokens"]:
                violations.append(
                    f"anatomy dispatch table {_key_str(key)}: wide_tokens "
                    f"{rec['wide_tokens']} > q_tokens {rec['q_tokens']}")
            if rec["q_slots"] != rec["dispatches"] * rec["slots"]:
                violations.append(
                    f"anatomy dispatch table {_key_str(key)}: q_slots "
                    f"{rec['q_slots']} != dispatches*bucket "
                    f"({rec['dispatches']}*{rec['slots']})")
        if prefill_tokens is not None and prompt != prefill_tokens:
            violations.append(
                f"anatomy dispatch table: prompt_tokens over all programs "
                f"{prompt} != prefill_tokens {prefill_tokens}")
        if q_prompt_programs != self._flat["prefill_query_tokens"]:
            violations.append(
                f"anatomy dispatch table: q_tokens over the prompt programs "
                f"{q_prompt_programs} != prefill_query_tokens "
                f"{self._flat['prefill_query_tokens']}")
        return violations

    def report(self, before: dict | None = None, *,
               rtt: tuple | None = None) -> dict:
        """The ``anatomy`` block (``metrics_report()`` / ``/v1/anatomy`` /
        bench detail).  Top-level totals, the ``programs`` table and its
        ``buckets`` view window off ``before`` (a ``snapshot()``); the
        per-class percentiles stay cumulative (a reservoir).  ``rtt`` is
        ``(rtt_s | None, age_s | None)`` from ``DispatchAttribution.
        rtt_sample()`` — a STALE sample is reported but never subtracted
        from the fetch split (the satellite-3 guard)."""
        b = before or {}
        iters = self._iters - b.get("iters", 0)
        wall = self._wall - b.get("wall", 0.0)
        residual = self._residual - b.get("residual", 0.0)
        host_us = self._host_us - b.get("host_us", 0.0)
        b_segs = b.get("segs", {})
        segs_ms = {s: round((self._segs[s] - b_segs.get(s, 0.0)) * 1e3, 3)
                   for s in SEGMENTS}

        classes: dict[str, dict] = {}
        for cls in CLASSES:
            rs = self._res[cls]
            if not rs:
                continue
            walls = sorted(r[0] for r in rs)
            p50: dict[str, float] = {}
            p95: dict[str, float] = {}
            for i, s in enumerate(SEGMENTS):
                vals = sorted(r[1][i] for r in rs)
                p50[s] = round(_pct(vals, 50) * 1e6, 1)
                p95[s] = round(_pct(vals, 95) * 1e6, 1)
            p50["wall"] = round(_pct(walls, 50) * 1e6, 1)
            p95["wall"] = round(_pct(walls, 95) * 1e6, 1)
            classes[cls] = {"iterations": self._cls_iters[cls],
                            "p50_us": p50, "p95_us": p95}

        programs = _programs_report(self._table, b.get("table", {}))
        buckets, pad_ratio = _rpa_buckets(programs)

        rtt_s, rtt_age = (rtt if rtt is not None else (None, None))
        out = {
            "object": "anatomy",
            "enabled": True,
            "iterations": iters,
            "aborted_iterations": self._aborted - b.get("aborted", 0),
            "wall_ms": round(wall * 1e3, 3),
            "residual_ms": round(residual * 1e3, 3),
            "segments_ms": segs_ms,
            "host_overhead_us_step": (round(host_us / iters, 1)
                                      if iters > 0 else None),
            "classes": classes,
            "programs": programs,
            "buckets": buckets,
            "rpa_pad_waste_ratio": pad_ratio,
        }
        if rtt_s is not None:
            stale = rtt_age is None or rtt_age > 2.0 * rtt_resample_s()
            out["rtt_ms"] = round(rtt_s * 1e3, 3)
            out["rtt_stale"] = stale
            if not stale and iters > 0:
                # pure device-wait estimate: fetch minus one host RTT per
                # iteration, floored at 0 — only derived from a FRESH rtt
                fetch_s = (self._segs["fetch"]
                           - b_segs.get("fetch", 0.0))
                out["device_wait_us_step"] = round(
                    max(fetch_s / iters - rtt_s, 0.0) * 1e6, 1)
        return out


def _programs_report(table: dict, before: dict) -> dict:
    """The ``programs`` block: the table's records since ``before`` (a
    snapshot's copy of it), per program and, under ``keys``, per key; a key
    with no dispatch in the window is left out."""
    programs: dict[str, dict] = {}
    for (program, key), rec in sorted(
            table.items(), key=lambda kv: (kv[0][0], _key_str(kv[0][1]))):
        b = before.get((program, key), {})
        d = {f: rec[f] - b.get(f, 0) for f in RECORD_FIELDS}
        if not d["dispatches"]:
            continue
        moe = {f: rec[f] - b.get(f, 0)
               for f in (*MOE_FIELDS, *WINDOW_FIELDS) if f in rec}
        d["cold_ms"] = (rec["cold_s"] - b.get("cold_s", 0.0)) * 1e3
        tot = programs.setdefault(program, {
            **dict.fromkeys(RECORD_FIELDS, 0), "cold_ms": 0.0, "keys": {}})
        for f, v in {**d, **moe}.items():
            tot[f] = tot.get(f, 0) + v
        tot["keys"][_key_str(key)] = {**d, **moe}
    for tot in programs.values():
        for rec in (tot, *tot["keys"].values()):
            rec["cold_ms"] = round(rec["cold_ms"], 1)
    return programs


def _rpa_buckets(programs: dict) -> tuple[dict, float | None]:
    """The ragged-span bucket economics (``buckets``, ``rpa_pad_waste_
    ratio``) as a view of ``programs["rpa"]``: keys ``rpa:<tpb>:<w>`` and
    ``rpa_spec:<tpb>:<w>`` share the bucket ``"<tpb>x<w>"``."""
    buckets: dict[str, dict] = {}
    for key, rec in (programs.get("rpa") or {"keys": {}})["keys"].items():
        _, tpb, w = key.split(":")
        m = buckets.setdefault(f"{tpb}x{w}", {
            "dispatches": 0, "real_tokens": 0, "padded_tokens": 0,
            "pad_waste": 0.0, "compile_ms": 0.0})
        m["dispatches"] += rec["dispatches"]
        m["real_tokens"] += rec["q_tokens"]
        m["padded_tokens"] += rec["q_slots"] - rec["q_tokens"]
        m["compile_ms"] = round(m["compile_ms"] + rec["cold_ms"], 1)
    buckets = dict(sorted(buckets.items(),
                          key=lambda kv: tuple(map(int, kv[0].split("x")))))
    tot_real = tot_pad = 0
    for m in buckets.values():
        span_tokens = m["real_tokens"] + m["padded_tokens"]
        m["pad_waste"] = (round(m["padded_tokens"] / span_tokens, 4)
                          if span_tokens else 0.0)
        tot_real += m["real_tokens"]
        tot_pad += m["padded_tokens"]
    return buckets, (round(tot_pad / (tot_real + tot_pad), 4)
                     if (tot_real + tot_pad) else None)


def merge_anatomy(docs: list[dict]) -> dict:
    """Merge per-engine ``anatomy`` documents into one fleet view (the
    router's ``GET /v1/anatomy`` and the replicated engine's metrics
    block).  Additive totals sum exactly (iterations, walls, segments,
    bucket token counts — the same one-merge-rule discipline as
    ``merge_usage``); per-class percentiles cannot be merged exactly, so
    they are iteration-weighted means — close under balanced load and
    explicitly an estimate, which is why per-host raw docs travel next to
    the merged view on the router surface."""
    live = [d for d in docs if d and d.get("enabled")]
    if not live:
        return {"object": "anatomy", "enabled": False}
    iters = sum(int(d.get("iterations") or 0) for d in live)
    segs_ms = {s: round(sum(float((d.get("segments_ms") or {}).get(s, 0.0))
                            for d in live), 3) for s in SEGMENTS}
    hosts_us = [(float(d["host_overhead_us_step"]),
                 int(d.get("iterations") or 0)) for d in live
                if d.get("host_overhead_us_step") is not None]
    w_iters = sum(n for _, n in hosts_us)
    classes: dict[str, dict] = {}
    for cls in CLASSES:
        per = [(d["classes"][cls], int(d["classes"][cls]["iterations"]))
               for d in live if cls in (d.get("classes") or {})]
        n_cls = sum(n for _, n in per)
        if not n_cls:
            continue
        keys = (*SEGMENTS, "wall")
        classes[cls] = {
            "iterations": n_cls,
            "p50_us": {k: round(sum(c["p50_us"].get(k, 0.0) * n
                                    for c, n in per) / n_cls, 1)
                       for k in keys},
            "p95_us": {k: round(sum(c["p95_us"].get(k, 0.0) * n
                                    for c, n in per) / n_cls, 1)
                       for k in keys},
        }
    zero = {**dict.fromkeys(RECORD_FIELDS, 0), "cold_ms": 0.0}

    def add(tot: dict, rec: dict) -> None:
        for f in RECORD_FIELDS:
            tot[f] += int(rec.get(f) or 0)
        tot["cold_ms"] = round(tot["cold_ms"]
                               + float(rec.get("cold_ms") or 0.0), 1)

    programs: dict[str, dict] = {}
    for d in live:
        for program, rec in (d.get("programs") or {}).items():
            tot = programs.setdefault(program, {**zero, "keys": {}})
            add(tot, rec)
            for key, krec in (rec.get("keys") or {}).items():
                add(tot["keys"].setdefault(key, dict(zero)), krec)
    buckets: dict[str, dict] = {}
    tot_real = tot_pad = 0
    for d in live:
        for key, rec in (d.get("buckets") or {}).items():
            m = buckets.setdefault(key, {
                "dispatches": 0, "real_tokens": 0, "padded_tokens": 0,
                "pad_waste": 0.0, "compile_ms": 0.0})
            m["dispatches"] += int(rec.get("dispatches") or 0)
            m["real_tokens"] += int(rec.get("real_tokens") or 0)
            m["padded_tokens"] += int(rec.get("padded_tokens") or 0)
            m["compile_ms"] = round(
                m["compile_ms"] + float(rec.get("compile_ms") or 0.0), 1)
    for m in buckets.values():
        span = m["real_tokens"] + m["padded_tokens"]
        m["pad_waste"] = round(m["padded_tokens"] / span, 4) if span else 0.0
        tot_real += m["real_tokens"]
        tot_pad += m["padded_tokens"]
    return {
        "object": "anatomy",
        "enabled": True,
        "iterations": iters,
        "aborted_iterations": sum(int(d.get("aborted_iterations") or 0)
                                  for d in live),
        "wall_ms": round(sum(float(d.get("wall_ms") or 0.0)
                             for d in live), 3),
        "residual_ms": round(sum(float(d.get("residual_ms") or 0.0)
                                 for d in live), 3),
        "segments_ms": segs_ms,
        "host_overhead_us_step": (
            round(sum(v * n for v, n in hosts_us) / w_iters, 1)
            if w_iters else None),
        "classes": classes,
        "programs": programs,
        "buckets": dict(sorted(buckets.items())),
        "rpa_pad_waste_ratio": (
            round(tot_pad / (tot_real + tot_pad), 4)
            if (tot_real + tot_pad) else None),
    }


def rtt_resample_s() -> float:
    """RTT re-sample cadence (``LMRS_RTT_RESAMPLE_S``, satellite 3) — also
    the staleness horizon the anatomy report guards with (2x cadence)."""
    return env_float("LMRS_RTT_RESAMPLE_S", 300.0, lo=1.0)


def _pct(sorted_vals: list[float], q: float) -> float:
    """Nearest-rank percentile over a pre-sorted list (no numpy on the
    report path — /v1/anatomy serves from the HTTP thread)."""
    if not sorted_vals:
        return 0.0
    k = min(len(sorted_vals) - 1,
            max(0, int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[k]


class NullAnatomy:
    """The ``LMRS_ANATOMY=0`` object: registers no metrics, every call is
    a no-op, ``seg`` and ``dispatch`` hand back one shared null context
    and ``counters`` is empty — the scheduler
    keeps one unconditional code path while the kill switch restores the
    exact pre-anatomy metrics shape and wire format."""

    enabled = False

    def iter_begin(self) -> None:
        pass

    def seg(self, name: str):
        return _NULL_SEG

    def iter_end(self, cls: str) -> None:
        pass

    def iter_abort(self) -> None:
        pass

    def iter_discard(self) -> None:
        pass

    def dispatch(self, program: str, key: tuple, **record):
        return _NULL_SEG

    def note_moe(self, program: str, key: tuple, **counts) -> None:
        pass

    def counters(self) -> dict:
        return {}

    def snapshot(self) -> dict:
        return {}

    def audit(self, prefill_tokens: int | None = None) -> list[str]:
        return []

    def report(self, before: dict | None = None, *,
               rtt: tuple | None = None) -> dict:
        return {"object": "anatomy", "enabled": False}


NULL_ANATOMY = NullAnatomy()


def maybe_anatomy(registry: MetricsRegistry, *, metrics_cb=None,
                  clock=time.time):
    """``StepAnatomy`` when armed, the shared ``NULL_ANATOMY`` otherwise
    (so the disabled path allocates nothing per engine)."""
    if not anatomy_enabled():
        return NULL_ANATOMY
    return StepAnatomy(registry, metrics_cb=metrics_cb, clock=clock)
