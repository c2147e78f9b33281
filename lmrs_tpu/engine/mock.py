"""Deterministic no-device engine — the CPU-only test path.

Successor of the reference's mock backend (llm_executor.py:411-432 +
result_aggregator.py:243-245): with no API key the reference returns a canned
response so the whole pipeline runs offline.  Here the mock is a first-class
backend (BASELINE.json config #1) that additionally produces *content-bearing*
summaries — a deterministic extractive sketch of the prompt's transcript — so
reduce-stage logic and ROUGE-style parity harnesses have real signal to chew
on instead of a constant string.
"""

from __future__ import annotations

import hashlib
import re
import threading
import time

from lmrs_tpu.data.tokenizer import ApproxTokenizer
from lmrs_tpu.engine.api import (GenerationRequest, GenerationResult,
                                 apply_stop_sequences, preamble_key,
                                 preamble_text)
from lmrs_tpu.obs import get_tracer, req_tid
from lmrs_tpu.obs.anatomy import CLASSES, SEGMENTS, _pct, anatomy_enabled
from lmrs_tpu.testing import faults
from lmrs_tpu.utils.perf_model import pow2_bucket

_TS_RE = re.compile(r"\[(?:\d+:)?\d{2}:\d{2}\]")


def _mock_tid(tr, req: GenerationRequest) -> int:
    """The request's span-track id — same rule as the scheduler's
    ``_tid``: keyed on the propagated trace id when present (one causal
    chain fleet-wide; the stitcher's join key), else the legacy
    request-id track."""
    return (tr.track_for(req.trace_id) if req.trace_id
            else req_tid(req.request_id))


class MockEngine:
    """Offline deterministic engine.

    fail_pattern: substring that triggers a simulated failure — the fault
    injection hook the reference lacks (SURVEY.md §5.3 "no fault injection").
    """

    # disaggregated handoff is supported: the mock's "KV state" is its
    # deterministic completion text, pinned/transferred/resumed through
    # the same ticket lifecycle the paged engines use (the no-device arm
    # of the two-process topology gate)
    supports_handoff = True

    # the mock's emulated cache geometry (deterministic; no device):
    # "HBM" holds this many preamble tokens resident before LRU entries
    # spill to the emulated host pool, and each token claims this many
    # host-pool bytes against ``host_kv_gb``
    EMU_RESIDENT_TOKENS = 2048
    EMU_BYTES_PER_TOKEN = 1024
    EMU_PAGE_TOKENS = 128

    # deterministic emulated device-time: the mock "spends" this many
    # seconds per token, so usage bills are byte-reproducible across
    # arms and hosts (the A/B harnesses compare exact rollup sums)
    EMU_SECONDS_PER_TOKEN = 1e-6

    def __init__(self, seed: int = 0, latency_s: float = 0.0,
                 fail_pattern: str | None = None,
                 handoff_ttl_s: float = 60.0,
                 mixed_batch: bool | None = None,
                 mixed_token_budget: int = 256,
                 prefix_cache: bool = True,
                 host_kv: bool | None = None,
                 host_kv_gb: float = 1.0,
                 cost_ledger: bool | None = None,
                 slo: bool | None = None,
                 slots: int = 0,
                 qos: bool | None = None,
                 speculate_k: int = 0):
        from lmrs_tpu.utils.env import env_bool, env_int

        self.seed = seed
        self.latency_s = latency_s
        self.fail_pattern = fail_pattern
        self.handoff_ttl_s = handoff_ttl_s
        # SARATHI mixed-batch emulation (the scheduler's admission
        # interleave, on the no-device arm): the mock generates each
        # request instantly, so nothing can actually stall — what CI
        # needs is the same KNOB surface and accounting the jax engine
        # exposes.  When armed, every same-batch request admitted behind
        # the first is accounted as prefilling in budget-clipped slices
        # that ride the earlier requests' decode steps; deterministic,
        # text-identical either way (serving/jobs tests exercise the A/B
        # arms and the metrics block on CPU).  The LMRS_MIXED kill switch
        # composes with the config flag exactly as in the scheduler: env
        # 0 always disarms, config False always disarms.
        self.mixed_batch = (env_bool("LMRS_MIXED", True)
                            and (mixed_batch is None or bool(mixed_batch)))
        self.mixed_token_budget = max(32, int(mixed_token_budget))
        # Prefix-cache + host-RAM spill tier emulation (the scheduler's
        # knob surface on the no-device arm, same composition rules:
        # LMRS_PREFIX_CACHE / LMRS_HOST_KV env always disarm, config
        # always disarms).  Deterministic and output-free — the mock's
        # text never changes; what CI gets is the same accounting,
        # radix-summary publication, and budget behavior the jax engine
        # exposes, so the full routing+spill flow runs deviceless.
        self.prefix_cache = (env_bool("LMRS_PREFIX_CACHE", True)
                             and bool(prefix_cache))
        self.host_kv = (self.prefix_cache
                        and env_bool("LMRS_HOST_KV", True)
                        and (host_kv is None or bool(host_kv))
                        and host_kv_gb > 0)
        self.host_kv_budget_bytes = int(max(0.0, host_kv_gb) * 2**30)
        self._prefix_lock = threading.Lock()
        # key -> {"tokens", "tier" ("resident"|"spilled"), "tick"}
        self._prefix: dict[str, dict] = {}  # guarded-by: _prefix_lock
        self._prefix_tick = 0               # guarded-by: _prefix_lock
        self._prefix_queries = 0            # guarded-by: _prefix_lock
        self._prefix_hits = 0               # guarded-by: _prefix_lock
        self._prefix_tokens_reused = 0      # guarded-by: _prefix_lock
        self._spilled_hits = 0              # guarded-by: _prefix_lock
        self._tokens_prefetched = 0         # guarded-by: _prefix_lock
        self._spill_pages = 0               # guarded-by: _prefix_lock
        self._prefetch_pages = 0            # guarded-by: _prefix_lock
        self._host_dropped_pages = 0        # guarded-by: _prefix_lock
        self._migrate_exports = 0           # guarded-by: _prefix_lock
        self._migrate_imports = 0           # guarded-by: _prefix_lock
        self._migrate_tokens = 0            # guarded-by: _prefix_lock
        self._mixed_lock = threading.Lock()
        self._mixed_dispatches = 0  # guarded-by: _mixed_lock
        self._mixed_piggybacked = 0  # guarded-by: _mixed_lock
        self._mixed_fill_sum = 0.0  # guarded-by: _mixed_lock
        # Ragged-span parity: the jax scheduler routes every mixed
        # dispatch through one span-program family.  The mock mirrors the
        # accounting block (span tokens, distinct pow2 compile shapes) so
        # deviceless CI can assert the metrics surface; text is untouched.
        self._rpa_span_tokens = 0      # guarded-by: _mixed_lock
        self._rpa_dispatches = 0       # guarded-by: _mixed_lock
        self._rpa_shapes: set = set()  # guarded-by: _mixed_lock
        # Tree-speculation parity (the scheduler's spec-tree surface on
        # the no-device arm): same gate composition (speculate_k arms,
        # LMRS_SPEC_TREE=0 disarms, width clamped so the ancestor
        # bitmask capacity 1 + k*(W+1) fits in 32 bits) and the same
        # report block keys, deterministically emulated — a request
        # carrying a draft hint "accepts" full depth (the cross-refresh
        # hint restating itself), one without accepts half, so deviceless
        # CI can assert both the knob surface and the hint plumbing
        # end-to-end.  Text is untouched (advisory by contract).
        self.spec_k = max(0, int(speculate_k))
        self.spec_width = env_int("LMRS_SPEC_TREE_WIDTH", 2, lo=1, hi=8)
        while (self.spec_width > 1
               and 1 + self.spec_k * (self.spec_width + 1) > 32):
            self.spec_width -= 1
        self.spec_tree = (self.spec_k > 0 and self.mixed_batch
                          and 1 + self.spec_k * (self.spec_width + 1) <= 32
                          and env_bool("LMRS_SPEC_TREE", True))
        self.spec_adaptive = (self.spec_tree
                              and env_bool("LMRS_SPEC_ADAPTIVE", True))
        self._spec_dispatches = 0     # guarded-by: _mixed_lock
        self._spec_rows = 0           # guarded-by: _mixed_lock
        self._spec_nodes_sum = 0      # guarded-by: _mixed_lock
        self._spec_depth_sum = 0      # guarded-by: _mixed_lock
        self._spec_accepted = 0       # guarded-by: _mixed_lock
        # draft hints seen by generated requests, in generation order —
        # the test hook for cross-refresh drafting (tests assert the live
        # layer's previous-summary hint actually reached the engine)
        self.draft_hints: list[str] = []
        # Step-anatomy parity (obs/anatomy.py): the same report shape the
        # scheduler's profiler exposes, deterministically emulated — every
        # segment derives from token counts at EMU_SECONDS_PER_TOKEN,
        # never wall clocks, so two arms running identical traffic
        # produce byte-identical anatomy documents, and wall == segment
        # sum exactly (residual 0) by construction.  LMRS_ANATOMY=0
        # disarms the whole surface (report shape / wire parity with the
        # scheduler's kill switch).
        self._an_lock = threading.Lock()
        self._an_segs = {s: 0.0 for s in SEGMENTS}  # guarded-by: _an_lock
        self._an_cls: dict[str, list] = {c: [] for c in CLASSES}
        self._an_buckets: dict[tuple[int, int], dict] = {}
        self._tok = ApproxTokenizer()
        # Cost ledger + SLO parity (obs/ledger.py, obs/slo.py): the SAME
        # accounting/knob surface as the jax scheduler, deterministically
        # emulated — per-request device-seconds derive from token counts
        # (EMU_SECONDS_PER_TOKEN), never wall clocks, so the whole
        # usage -> /v1/usage -> router-aggregation -> SLO-routing flow
        # runs deviceless in CI with exact, reproducible sums.  The env
        # kill switches compose exactly as in the scheduler: LMRS_
        # COST_LEDGER=0 / LMRS_SLO=0 always disarm, constructor False
        # always disarms.
        from lmrs_tpu.obs.ledger import CostLedger
        from lmrs_tpu.obs.slo import SLOEngine

        cl_on = (env_bool("LMRS_COST_LEDGER", True)
                 and (cost_ledger is None or bool(cost_ledger)))
        slo_on = (env_bool("LMRS_SLO", True)
                  and (slo is None or bool(slo)))
        # frozen ledger clock: residency-derived meters (host-pool
        # byte-seconds) read 0 so usage sums stay byte-reproducible —
        # the mock bills work, never wall time
        self.ledger = CostLedger(enabled=cl_on, clock=lambda: 0.0)
        self.slo = SLOEngine(enabled=slo_on)
        # rid -> prompt tokens the prefix cache / prefetch served, so
        # _bill skips them like the real scheduler (saved tokens never
        # enter a prefill dispatch — they must not bill device time)
        self._billing_saved: dict[int, int] = {}  # guarded-by: _prefix_lock
        # ids cancel() was called for — generation is instantaneous here, so
        # the hook only records (tests assert the server propagated a
        # disconnect) and flags ids not yet generated in this batch
        self.cancelled: set[int] = set()
        # rid -> pinned handoff state (see _one); the lock mirrors the
        # scheduler's pinned-export contract — handler threads release
        # while generate_batch pins
        self._pinned: dict[int, dict] = {}
        self._pinned_lock = threading.Lock()
        # Multi-tenant QoS parity (fleet/qos.py): the same fair-share
        # admission surface as the jax scheduler.  slots=0 (default) is
        # byte-identical to the pre-QoS mock: every generate_batch call
        # runs immediately, no gate, no reordering.  slots>0 bounds the
        # number of concurrently *running* requests across handler
        # threads; waiting tickets are admitted FIFO when QoS is
        # disarmed and in fair-share order (class, windowed usage,
        # arrival) when armed — the contention source the fairness A/B
        # needs on a deviceless host.
        from lmrs_tpu.fleet.qos import maybe_qos

        self.qos = maybe_qos() if (qos is None or bool(qos)) else None
        if self.qos is not None:
            # same lock-ordering contract as the scheduler: the ledger
            # fires the observer after releasing its own lock
            self.ledger.observer = self.qos.note_usage
        self.slots = max(0, int(slots))
        self._adm_cv = threading.Condition()
        self._adm_queue: list = []  # waiting (seq, req) tickets  guarded-by: _adm_cv
        self._adm_seq = 0           # guarded-by: _adm_cv
        self._adm_running = 0       # guarded-by: _adm_cv

    def _adm_pick_locked(self):
        """Next ticket to admit.  FIFO by arrival seq when QoS is
        disarmed; the policy's fair-share order when armed.  The queue
        list stays append-ordered, so list index == FIFO rank and
        pick_index's tie-break matches arrival order."""
        # holds-lock: _adm_cv
        if self.qos is None:
            return self._adm_queue[0]
        return self._adm_queue[self.qos.pick_index(
            [t[1] for t in self._adm_queue])]

    def _admit_wait(self, req: GenerationRequest) -> None:
        """Block until a run slot is free and this request is the
        admission policy's pick.  No-op when slots=0 (unlimited)."""
        if self.slots <= 0:
            return
        with self._adm_cv:
            ticket = (self._adm_seq, req)
            self._adm_seq += 1
            self._adm_queue.append(ticket)
            while not (self._adm_running < self.slots
                       and self._adm_pick_locked() is ticket):
                # timed wait: a lost wakeup only delays, never deadlocks
                self._adm_cv.wait(timeout=0.2)
            self._adm_queue.remove(ticket)
            self._adm_running += 1
            # another slot may still be free for the next pick
            self._adm_cv.notify_all()

    def _admit_release(self) -> None:
        if self.slots <= 0:
            return
        with self._adm_cv:
            self._adm_running -= 1
            self._adm_cv.notify_all()

    def qos_report(self) -> dict:
        """Per-tenant fair-share snapshot — same shape as the
        scheduler's (served under /v1/usage as the "qos" block)."""
        if self.qos is None:
            return {"object": "qos", "enabled": False}
        return self.qos.report()

    def generate_batch(self, requests: list[GenerationRequest],
                       on_result=None, on_tokens=None) -> list[GenerationResult]:
        # cancel-set lifecycle mirrors ContinuousScheduler.run(): no
        # start-of-batch clear (a cancel can legitimately race the batch
        # boundary) but a full clear in the finally, so stale ids never
        # cancel a later batch's same-numbered request or accumulate
        # unboundedly; callers keep ids unique across cancels (the HTTP
        # batcher's rids are global)
        # injection site: same engine-level batch fault as JaxEngine — the
        # no-device arm of the chaos soak (tests/test_chaos.py)
        faults.fire("engine.batch")

        def one(req: GenerationRequest) -> GenerationResult:
            self._admit_wait(req)
            try:
                return _one_admitted(req)
            finally:
                self._admit_release()

        def _one_admitted(req: GenerationRequest) -> GenerationResult:
            tr = get_tracer()
            t0 = time.time()
            if req.draft_hint is not None:
                # recorded regardless of the spec arm: the hint is
                # advisory plumbing, and tests assert it arrived even on
                # engines that ignore it
                self.draft_hints.append(req.draft_hint)
            res = self._one(req)
            self._bill(req, res)
            if self.spec_tree and res.completion_tokens:
                # tree-spec arm: the plain iteration carries the prompt
                # only; emulated spec steps carry the decoded tokens (no
                # double-counted fetch)
                self._note_anatomy("plain",
                                   dispatch_tokens=res.prompt_tokens,
                                   fetch_tokens=0)
                self._note_spec(req, res.completion_tokens)
            else:
                # one emulated "plain" scheduler iteration per request:
                # dispatch carries the prompt, fetch the completion
                self._note_anatomy("plain",
                                   dispatch_tokens=res.prompt_tokens,
                                   fetch_tokens=res.completion_tokens)
            self.slo.observe_ttft(time.time() - t0)
            self.slo.note_result(res.finish_reason, res.completion_tokens,
                                 res.error)
            if tr:  # minimal lifecycle: the mock has no queue or slots
                # the tid is resolved AFTER _one so a handoff import's
                # adopted trace takes effect: CI's no-device disagg
                # traces stitch end-to-end through router → mock backends
                tid = _mock_tid(tr, req)
                tr.complete("generate", t0, time.time(), tid=tid,
                            args={"completion_tokens": res.completion_tokens})
                tr.instant("cancel" if res.finish_reason == "cancelled"
                           else "finish", tid=tid,
                           args={"reason": res.finish_reason})
            if on_tokens is not None and res.text:
                # no incremental decode in the mock: one delta per result
                on_tokens(res.request_id, res.text)
            return res

        self._note_mixed_batch(requests)
        try:
            if on_result is not None:
                from lmrs_tpu.engine.api import drain_with_callback

                return drain_with_callback(
                    lambda reqs: [one(r) for r in reqs], requests, on_result)
            return [one(r) for r in requests]
        finally:
            self.cancelled.clear()

    def _note_mixed_batch(self, requests: list[GenerationRequest]) -> None:
        """Mixed-batch accounting on the no-device arm: requests admitted
        behind the first in a batch are accounted as chunked prefills
        riding the earlier requests' decode steps, slice-clipped to the
        step budget — the same counters (dispatches, piggybacked tokens,
        fill) the scheduler's fused dispatcher reports, so serving/jobs
        CI can assert the knob surface end-to-end without a device.
        Deterministic and output-free: the mock's text is untouched."""
        if not self.mixed_batch or len(requests) < 2:
            return
        n_decode = len(requests) - 1  # rows decoding while the rest admit
        slice_cap = max(16, self.mixed_token_budget - n_decode)
        with self._mixed_lock:
            for req in requests[1:]:
                remaining = self._tok.count(req.prompt)
                while remaining > 0:
                    c = min(remaining, slice_cap)
                    self._mixed_dispatches += 1
                    self._mixed_piggybacked += c
                    self._mixed_fill_sum += min(
                        (n_decode + c) / self.mixed_token_budget, 1.0)
                    total = n_decode + c
                    self._rpa_dispatches += 1
                    self._rpa_span_tokens += total
                    # same pow2 bucket family the scheduler compiles
                    # (one shared definition — utils/perf_model)
                    bucket = pow2_bucket(total, 16)
                    self._rpa_shapes.add(bucket)
                    self._note_rpa_bucket(bucket, total)
                    # each emulated slice is one "mixed" iteration:
                    # dispatch carries the span, fetch the decode tokens
                    self._note_anatomy("mixed",
                                       dispatch_tokens=n_decode + c,
                                       fetch_tokens=n_decode)
                    remaining -= c

    def _note_spec(self, req: GenerationRequest,
                   completion_tokens: int) -> None:
        """Deterministic tree-speculation accounting for one generated
        request (no output effect; see __init__).  The emulated verify
        accepts full chain depth when the request carries a draft hint
        (cross-refresh: the previous summary restating itself) and half
        depth otherwise, so each step emits ``1 + acc`` tokens; step
        count, node count (1 + W*k drafted per row) and accepted depth
        all derive from token counts only — byte-reproducible across
        arms and hosts."""
        k, width = self.spec_k, self.spec_width
        acc = k if req.draft_hint else max(1, k // 2)
        steps = -(-completion_tokens // (1 + acc))
        with self._mixed_lock:
            self._spec_dispatches += steps
            self._spec_rows += steps
            self._spec_nodes_sum += steps * (1 + width * k)
            self._spec_depth_sum += steps * acc
            self._spec_accepted += steps * acc
        # each emulated spec step is one "spec" iteration: dispatch
        # carries the full tree span, fetch the emitted tokens; drafting
        # is fused on-device, so the draft segment stays dispatch-only
        # (zero host time) — exactly the anatomy shift the real tree
        # path exists to produce
        for _ in range(steps):
            self._note_anatomy("spec",
                               dispatch_tokens=1 + width * k,
                               fetch_tokens=1 + acc)

    def _note_anatomy(self, cls: str, *, dispatch_tokens: int,
                      fetch_tokens: int) -> None:
        """One emulated scheduler iteration (obs/anatomy.py parity, see
        __init__): fixed one-token admit/plan/finish segments plus
        token-count-derived dispatch/fetch, all at EMU_SECONDS_PER_TOKEN
        — wall equals the segment sum exactly, so the mock's anatomy is
        conservation-perfect and byte-reproducible."""
        if not anatomy_enabled():
            return
        spt = self.EMU_SECONDS_PER_TOKEN
        segs = {s: 0.0 for s in SEGMENTS}
        segs["admit"] = spt
        segs["plan"] = spt
        segs["dispatch"] = max(0, int(dispatch_tokens)) * spt
        segs["fetch"] = max(0, int(fetch_tokens)) * spt
        segs["finish"] = spt
        with self._an_lock:
            for s in SEGMENTS:
                self._an_segs[s] += segs[s]
            self._an_cls[cls].append(
                (sum(segs.values()), tuple(segs[s] for s in SEGMENTS)))

    def _note_rpa_bucket(self, tpb: int, real_tokens: int) -> None:
        """Bucket-economics parity for one emulated ragged-span dispatch:
        the real-vs-padded split the scheduler's profiler counts, with a
        deterministic emulated compile cost (bucket * EMU_SECONDS_PER_
        TOKEN) on first sight of a shape."""
        if not anatomy_enabled():
            return
        pages = -(-max(1, int(real_tokens)) // self.EMU_PAGE_TOKENS)
        w = pow2_bucket(pages, 4)
        with self._an_lock:
            first = (tpb, w) not in self._an_buckets
            rec = self._an_buckets.setdefault((tpb, w), {
                "dispatches": 0, "real": 0, "padded": 0, "compile_s": 0.0})
            rec["dispatches"] += 1
            rec["real"] += int(real_tokens)
            rec["padded"] += max(tpb - int(real_tokens), 0)
            if first:
                rec["compile_s"] = tpb * self.EMU_SECONDS_PER_TOKEN

    def anatomy_report(self) -> dict:
        """Optional Engine hook: the ``GET /v1/anatomy`` document — same
        shape as the scheduler's (obs/anatomy.py ``StepAnatomy.report``),
        deterministically derived from token counts."""
        if not anatomy_enabled():
            return {"object": "anatomy", "enabled": False}
        with self._an_lock:
            segs = dict(self._an_segs)
            cls_recs = {c: list(rs) for c, rs in self._an_cls.items()}
            bucket_recs = {k: dict(v) for k, v in self._an_buckets.items()}
        iters = sum(len(rs) for rs in cls_recs.values())
        wall = sum(segs.values())  # residual is 0 by construction
        host = wall - segs["dispatch"] - segs["fetch"]
        classes: dict[str, dict] = {}
        for cls in CLASSES:
            rs = cls_recs[cls]
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
            classes[cls] = {"iterations": len(rs),
                            "p50_us": p50, "p95_us": p95}
        buckets: dict[str, dict] = {}
        tot_real = tot_pad = 0
        for (tpb, w), rec in sorted(bucket_recs.items()):
            span = rec["real"] + rec["padded"]
            buckets[f"{tpb}x{w}"] = {
                "dispatches": rec["dispatches"],
                "real_tokens": rec["real"],
                "padded_tokens": rec["padded"],
                "pad_waste": round(rec["padded"] / span, 4) if span else 0.0,
                "compile_ms": round(rec["compile_s"] * 1e3, 1),
            }
            tot_real += rec["real"]
            tot_pad += rec["padded"]
        return {
            "object": "anatomy",
            "enabled": True,
            "iterations": iters,
            "aborted_iterations": 0,
            "wall_ms": round(wall * 1e3, 3),
            "residual_ms": 0.0,
            "segments_ms": {s: round(segs[s] * 1e3, 3) for s in SEGMENTS},
            "host_overhead_us_step": (round(host * 1e6 / iters, 1)
                                      if iters > 0 else None),
            "classes": classes,
            "buckets": buckets,
            "rpa_pad_waste_ratio": (
                round(tot_pad / (tot_real + tot_pad), 4)
                if (tot_real + tot_pad) else None),
        }

    def _note_prefix(self, req: GenerationRequest) -> None:
        """Deterministic prefix-cache + spill-tier accounting for one
        generated request (no output effect; see __init__).  First sight
        of a preamble inserts it resident; a later request with the same
        preamble is a hit (tokens_reused += preamble tokens); a hit on a
        SPILLED entry additionally accounts a prefetch and promotes it
        back.  Resident capacity is ``EMU_RESIDENT_TOKENS`` LRU — over
        it, oldest entries spill (tier armed) or drop (tier off), and
        the emulated host pool drops LRU entries past ``host_kv_gb``."""
        if not self.prefix_cache:
            return
        key = preamble_key(req.system_prompt, req.prompt, req.cache_prefix)
        if key is None:
            return
        tokens = self._tok.count(preamble_text(
            req.system_prompt, req.prompt, req.cache_prefix))
        pages = -(-tokens // self.EMU_PAGE_TOKENS)
        with self._prefix_lock:
            self._prefix_tick += 1
            self._prefix_queries += 1
            ent = self._prefix.get(key)
            if ent is not None:
                self._prefix_hits += 1
                self._prefix_tokens_reused += ent["tokens"]
                spilled = ent["tier"] == "spilled"
                if spilled:
                    self._spilled_hits += 1
                    self._tokens_prefetched += ent["tokens"]
                    self._prefetch_pages += pages
                    ent["tier"] = "resident"
                self.ledger.note_saved(
                    req,
                    prefix_tokens=0 if spilled else ent["tokens"],
                    prefetched_tokens=ent["tokens"] if spilled else 0,
                    prefetched_bytes=(ent["tokens"]
                                      * self.EMU_BYTES_PER_TOKEN
                                      if spilled else 0.0))
                if self.ledger.enabled:  # popped by _bill; no entry may
                    self._billing_saved[req.request_id] = (  # outlive it
                        self._billing_saved.get(req.request_id, 0)
                        + ent["tokens"])
            else:
                ent = {"tokens": tokens, "tier": "resident", "tick": 0}
                self._prefix[key] = ent
            ent["tick"] = self._prefix_tick
            self._enforce_emulated_budgets()

    def _enforce_emulated_budgets(self) -> None:  # holds-lock: _prefix_lock
        """Caller holds self._prefix_lock."""
        def lru(tier: str):
            cands = [(e["tick"], k) for k, e in self._prefix.items()
                     if e["tier"] == tier]
            return min(cands)[1] if cands else None

        def resident_tokens() -> int:
            return sum(e["tokens"] for e in self._prefix.values()
                       if e["tier"] == "resident")

        while resident_tokens() > self.EMU_RESIDENT_TOKENS:
            key = lru("resident")
            if key is None:
                break
            ent = self._prefix[key]
            pages = -(-ent["tokens"] // self.EMU_PAGE_TOKENS)
            if (self.host_kv and ent["tokens"] * self.EMU_BYTES_PER_TOKEN
                    <= self.host_kv_budget_bytes):
                ent["tier"] = "spilled"
                self._spill_pages += pages
            else:
                del self._prefix[key]

        def spilled_bytes() -> int:
            return sum(e["tokens"] * self.EMU_BYTES_PER_TOKEN
                       for e in self._prefix.values()
                       if e["tier"] == "spilled")

        while spilled_bytes() > self.host_kv_budget_bytes:
            key = lru("spilled")
            if key is None:
                break
            ent = self._prefix.pop(key)
            self._host_dropped_pages += -(-ent["tokens"]
                                          // self.EMU_PAGE_TOKENS)

    def prefix_summary(self, top_k: int = 16) -> list[dict]:
        """Deterministic radix-summary publication (the router's routing
        feed) — same row shape as the scheduler's."""
        if not self.prefix_cache:
            return []
        with self._prefix_lock:
            rows = sorted(self._prefix.items(),
                          key=lambda kv: -kv[1]["tick"])[:top_k]
            out = []
            for key, ent in rows:
                res = ent["tier"] == "resident"
                pages = -(-ent["tokens"] // self.EMU_PAGE_TOKENS)
                out.append({
                    "hash": key,
                    "depth_tokens": ent["tokens"],
                    "tick": ent["tick"],
                    "resident_tokens": ent["tokens"] if res else 0,
                    "resident_pages": pages if res else 0,
                    "spilled_tokens": 0 if res else ent["tokens"],
                    "spilled_pages": 0 if res else pages,
                })
        return out

    # ------------------------------------------------- KV-fabric migration
    # (optional Engine surface, same getattr convention as the handoff
    # hooks): page-set export/import on the no-device arm.  The mock's
    # "page set" is the emulated prefix entry itself — tokens plus a
    # deterministic content tag — so a migrated preamble counts as a
    # prefix HIT on the importing host (the chaos gate's fabric-token
    # assertion) without any device bytes moving.

    def kv_export(self, preamble: str) -> dict | None:
        """Wire payload for one warm preamble, or None when the cache is
        off / the preamble is cold (the server's 404 path).  Read-only:
        the exporting cache keeps its entry (source stays warm until it
        drains away naturally)."""
        if not self.prefix_cache:
            return None
        faults.fire("migrate.export")
        with self._prefix_lock:
            ent = self._prefix.get(preamble)
            if ent is None:
                return None
            self._migrate_exports += 1
            return {"kind": "kv_pageset", "version": 1, "emu": True,
                    "preamble": preamble, "tokens": ent["tokens"],
                    "seed": self.seed}

    def kv_import(self, payload: dict) -> int:
        """Install a migrated page set as a warm resident prefix entry.
        Geometry mismatch (a jax page-set payload, or a mock arm with a
        different seed — different completion bytes) raises ValueError:
        the server answers 409/4xx and the router falls back to cold
        resume, never a silently-wrong cache hit."""
        if not self.prefix_cache:
            raise RuntimeError("prefix cache disabled")
        if payload.get("kind") != "kv_pageset" or not payload.get("emu"):
            raise ValueError("not an emulated kv_pageset payload")
        if payload.get("seed", self.seed) != self.seed:
            raise ValueError("mock seed mismatch: emulated KV bytes differ")
        key = payload["preamble"]
        tokens = int(payload["tokens"])
        if not key or tokens <= 0:
            raise ValueError("malformed kv_pageset payload")
        faults.fire("migrate.import")
        with self._prefix_lock:
            self._prefix_tick += 1
            self._prefix[key] = {"tokens": tokens, "tier": "resident",
                                 "tick": self._prefix_tick}
            self._migrate_imports += 1
            self._migrate_tokens += tokens
            self._enforce_emulated_budgets()
        return tokens

    def _bill(self, req: GenerationRequest,
              res: GenerationResult) -> None:
        """Deterministic ledger entry for one finished mock request:
        prompt tokens bill as prefill, completion tokens as decode, at
        EMU_SECONDS_PER_TOKEN each (emulated pages at EMU_PAGE_TOKENS
        granularity).  Token-count-derived, so two arms running the same
        traffic produce byte-identical usage sums."""
        if not self.ledger.enabled:
            return
        spt = self.EMU_SECONDS_PER_TOKEN
        with self._prefix_lock:
            saved = self._billing_saved.pop(res.request_id, 0)
        # saved tokens never entered a prefill dispatch on the real
        # scheduler, so the mock must not bill them either — with the
        # cache serving the whole prompt there is NO prefill step
        billed = max(0, res.prompt_tokens - saved)
        if billed:
            self.ledger.note_step(
                billed * spt,
                prefill_rows=[(req, billed, float(billed))],
                prefill_cost_s=1.0)
        if res.completion_tokens:
            pages = -(-(res.prompt_tokens + res.completion_tokens)
                      // self.EMU_PAGE_TOKENS)
            self.ledger.note_step(
                res.completion_tokens * spt,
                decode_rows=[(req, res.completion_tokens, pages)],
                decode_cost_s=1.0)
        res.usage = self.ledger.finish(req, res)

    def usage_report(self) -> dict:
        """Optional Engine hook: the ``GET /v1/usage`` document (same
        shape as the scheduler's)."""
        return self.ledger.usage_report()

    def slo_report(self) -> dict:
        """Optional Engine hook: the ``/healthz`` ``slo`` block."""
        return self.slo.report()

    def shutdown(self) -> None:
        pass

    def cancel(self, request_id: int) -> None:
        """Engine optional abort hook (see engine/api.py).  Recorded; any
        request of the current batch not yet generated when its id lands
        here comes back finish_reason="cancelled"."""
        self.cancelled.add(request_id)

    def engine_metrics(self) -> dict:
        out: dict = {}
        with self._mixed_lock:
            d, p, f = (self._mixed_dispatches, self._mixed_piggybacked,
                       self._mixed_fill_sum)
        if d:
            out["mixed_batch"] = {
                "enabled": self.mixed_batch,
                "token_budget": self.mixed_token_budget,
                "dispatches": d,
                "fill_ratio": round(f / d, 3) if d else 0.0,
                "prefill_tokens_piggybacked": p,
            }
        with self._mixed_lock:
            rd, rt, rs = (self._rpa_dispatches, self._rpa_span_tokens,
                          len(self._rpa_shapes))
        if rd:
            out["rpa"] = {
                "enabled": self.mixed_batch,
                "dispatches": rd,
                "span_tokens": rt,
                "compile_shapes": rs,
            }
        with self._mixed_lock:
            sd, sr, sn, sdep, sacc = (
                self._spec_dispatches, self._spec_rows,
                self._spec_nodes_sum, self._spec_depth_sum,
                self._spec_accepted)
        if sd:
            # same keys as the scheduler's _spec_tree_report block
            out["spec_accepted_tokens"] = sacc
            out["spec_tree"] = {
                "enabled": self.spec_tree,
                "width": self.spec_width,
                "adaptive": self.spec_adaptive,
                "dispatches": sd,
                "mean_nodes": round(sn / sr, 3) if sr else 0.0,
                "mean_accept_depth": round(sdep / sr, 3) if sr else 0.0,
                "accept_per_step": round(sacc / sr, 3) if sr else 0.0,
            }
        with self._prefix_lock:
            if self._prefix_queries:
                out["prefix_cache"] = {
                    "hit_rate": round(
                        self._prefix_hits / self._prefix_queries, 3),
                    "hits": self._prefix_hits,
                    "queries": self._prefix_queries,
                    "tokens_reused": self._prefix_tokens_reused,
                    "prefill_tokens_saved": self._prefix_tokens_reused,
                    "spilled_hits": self._spilled_hits,
                    "tokens_prefetched": self._tokens_prefetched,
                }
                out["host_kv"] = {
                    "enabled": self.host_kv,
                    "budget_gb": round(
                        self.host_kv_budget_bytes / 2**30, 3),
                    "spilled_hits": self._spilled_hits,
                    "tokens_prefetched": self._tokens_prefetched,
                    "spill_pages": self._spill_pages,
                    "prefetch_pages": self._prefetch_pages,
                    "dropped_pages_total": self._host_dropped_pages,
                }
            if self._migrate_exports or self._migrate_imports:
                # same report-nothing-when-idle contract as the other
                # blocks: with LMRS_KV_MIGRATE=0 no migration ever runs,
                # so the block is absent and metrics stay byte-identical
                out["kv_migrate"] = {
                    "exports": self._migrate_exports,
                    "imports": self._migrate_imports,
                    "tokens_imported": self._migrate_tokens,
                }
        # the cost block appears once work flowed (the same
        # report-nothing-when-idle contract as the mixed/prefix blocks).
        # Deliberately NO slo block here: engine_metrics is contractually
        # deterministic for identical traffic (test_mixed asserts it) and
        # SLO burns are wall-clock-fed — consumers read slo_report()
        if self.ledger.enabled and self.ledger.finished_count:
            out["cost"] = self.ledger.report()
        # anatomy block: deterministic (token-count-derived), same
        # report-nothing-when-idle + LMRS_ANATOMY=0 shape contract as the
        # scheduler's metrics_report
        if anatomy_enabled():
            an = self.anatomy_report()
            if an.get("iterations"):
                out["anatomy"] = an
        # no work recorded at all: the mock reports no engine metrics,
        # as it always has
        return out

    # ---------------------------------------- disaggregated handoff hooks

    def export_handoff(self, request_id: int) -> dict:
        """Wire payload of a pinned mock handoff (KeyError when unknown /
        already released — the ticket 410 path)."""
        with self._pinned_lock:
            return self._pinned[request_id]["payload"]

    def release_handoff(self, request_id: int, orphaned: bool = False) -> int:
        with self._pinned_lock:
            return 1 if self._pinned.pop(request_id, None) else 0

    def sweep_handoffs(self, now: float | None = None) -> int:
        now = time.time() if now is None else now
        with self._pinned_lock:
            expired = [r for r, rec in self._pinned.items()
                       if rec["deadline_t"] <= now]
            for r in expired:
                self._pinned.pop(r)
        return len(expired)

    def pinned_handoffs(self) -> dict[int, int]:
        with self._pinned_lock:
            return {r: 1 for r in self._pinned}

    def _one(self, req: GenerationRequest) -> GenerationResult:
        def expired() -> bool:
            return (req.deadline_s is not None
                    and time.time() >= req.deadline_s)

        # deadline lifecycle on the no-device path, same split as the
        # scheduler: expired BEFORE any work -> shed (zero-cost explicit
        # rejection); expired during the simulated generation latency ->
        # deadline (work was spent)
        if expired():
            return GenerationResult(request_id=req.request_id,
                                    finish_reason="shed")
        if self.latency_s:
            time.sleep(self.latency_s)
            if expired():
                return GenerationResult(request_id=req.request_id,
                                        finish_reason="deadline")
        if req.request_id in self.cancelled:
            return GenerationResult(request_id=req.request_id,
                                    finish_reason="cancelled")
        if self.fail_pattern and self.fail_pattern in req.prompt:
            return GenerationResult(
                request_id=req.request_id,
                finish_reason="error",
                error="mock: injected failure",
            )
        if req.handoff_state is not None:
            # disaggregated decode role: resume from the TRANSFERRED state
            # — the payload's text is returned, never recomputed, so the
            # result proves the handoff actually carried the prefill pod's
            # state across (a recompute would mask a broken transfer)
            # fault degrades per request (same contract as the jax arm:
            # a marked import failure the router retries/falls back on,
            # never a whole-wave error)
            try:
                faults.fire("handoff.import")
            except Exception as e:  # noqa: BLE001 - injected fault
                return GenerationResult(
                    request_id=req.request_id, finish_reason="error",
                    error=f"handoff import failed: {type(e).__name__}: {e}")
            state = req.handoff_state
            # continue the exporter's trace across the pod boundary (the
            # same adoption rule as the scheduler's _admit_import)
            if not req.trace_id and isinstance(state.get("trace_id"), str):
                req.trace_id = state["trace_id"]
            if not req.tenant and isinstance(state.get("tenant"), str):
                req.tenant = state["tenant"]
            tr = get_tracer()
            if tr:
                tr.instant(
                    "handoff_import", tid=_mock_tid(tr, req),
                    args={"pages": 0,  # the mock's state is pageless text
                          "kv_len": int(state.get("prompt_tokens", 0))})
            text = state["text"]
            return GenerationResult(
                request_id=req.request_id,
                text=text,
                prompt_tokens=int(state.get("prompt_tokens", 0)),
                completion_tokens=self._tok.count(text),
                finish_reason=str(state.get("finish_reason", "stop")),
                stop_sequence=state.get("stop_sequence"),
            )
        # prefix-cache/spill accounting: every request that actually
        # "prefills" here (plain completions and prefill-role exports;
        # handoff imports resumed above without prefilling)
        self._note_prefix(req)
        text, stop_hit = apply_stop_sequences(
            self._extractive_sketch(req.prompt), req.stop)
        prompt_tokens = self._tok.count(req.prompt)
        if req.handoff_export:
            # prefill role: emit only the first "token" (up to the first
            # whitespace) and pin the full completion as the transferable
            # state; a completion that IS its first token returns as a
            # normal terminal result — nothing left to hand off
            cut = text.find(" ")
            first = text if cut < 0 else text[:cut + 1]
            if first != text:
                try:
                    faults.fire("handoff.export")
                except Exception as e:  # noqa: BLE001 - injected fault
                    return GenerationResult(
                        request_id=req.request_id, finish_reason="error",
                        error=f"handoff export failed: "
                              f"{type(e).__name__}: {e}")
                payload = {"text": text, "prompt_tokens": prompt_tokens,
                           "stop_sequence": stop_hit,
                           "finish_reason": "stop"}
                if req.trace_id:
                    payload["trace_id"] = req.trace_id
                if req.tenant:
                    payload["tenant"] = req.tenant
                if req.qos_class:
                    payload["qos_class"] = req.qos_class
                with self._pinned_lock:
                    self._pinned[req.request_id] = {
                        "payload": payload,
                        "deadline_t": time.time() + self.handoff_ttl_s}
                tr = get_tracer()
                if tr:  # the stitcher's skew anchor on the prefill pod
                    tr.instant(
                        "handoff_export", tid=_mock_tid(tr, req),
                        args={"pages": 0, "kv_len": prompt_tokens})
                return GenerationResult(
                    request_id=req.request_id,
                    text=first,
                    prompt_tokens=prompt_tokens,
                    completion_tokens=self._tok.count(first),
                    finish_reason="handoff",
                )
        return GenerationResult(
            request_id=req.request_id,
            text=text,
            prompt_tokens=prompt_tokens,
            completion_tokens=self._tok.count(text),
            finish_reason="stop",
            stop_sequence=stop_hit,
        )

    def _extractive_sketch(self, prompt: str) -> str:
        """First/middle/last content sentences + every timestamp, capped.

        Deterministic in (prompt, seed); no randomness so repeated runs are
        byte-identical (test requirement, SURVEY.md §4).
        """
        # Pull out the transcript / summaries body if the prompt embeds one.
        body = prompt
        for marker in ("Transcript section:", "Partial summaries:", "Intermediate summaries:"):
            if marker in body:
                body = body.split(marker, 1)[-1]
        sentences = [s.strip() for s in re.split(r"(?<=[.!?])\s+", body) if len(s.strip()) > 30]
        stamps = _TS_RE.findall(body)
        digest = hashlib.sha256(f"{self.seed}:{prompt}".encode()).hexdigest()[:8]
        picked = []
        if sentences:
            idx = sorted({0, len(sentences) // 2, len(sentences) - 1})
            picked = [sentences[i] for i in idx]
        lines = [f"[mock-{digest}] Summary:"]
        lines += [f"- {s[:240]}" for s in picked]
        if stamps:
            uniq = list(dict.fromkeys(stamps))[:12]  # cap so reduce inputs stay bounded
            lines.append("Timestamps: " + " ".join(uniq))
        return "\n".join(lines)
