"""Data-parallel serving: independent engine replicas over disjoint devices.

SURVEY.md §2.2 row 1: the TPU-native equivalent of the reference's
request-level fan-out is "continuous batching over DP replicas of the
model".  Sharding decode's batch dim over a ``dp`` mesh axis would be the
literal translation, but a paged KV cache has no meaningful batch axis to
shard — the page pool and the host-side allocator are per-engine state.
The TPU-idiomatic design is N fully independent engines, each with its own
(tp × sp) sub-mesh, pool, and scheduler, fed round-robin from one queue:

* within a replica: ICI collectives (TP) + continuous batching;
* across replicas: no communication at all — pure throughput scaling,
  exactly like the reference's concurrent HTTP requests but device-local;
* across hosts: run one process per host (`jax.distributed`,
  parallel/mesh.py:initialize_distributed) and give each host's engine its
  local devices — the same class, DCN never carries tensor traffic.

Host-side dispatch runs one thread per replica (device execution is async
and overlaps; the GIL only serializes Python-side batch assembly).
"""

from __future__ import annotations

import logging
from concurrent.futures import TimeoutError as FutureTimeout
from dataclasses import replace

import jax

from lmrs_tpu.config import EngineConfig, MeshConfig, ModelConfig
from lmrs_tpu.engine.api import GenerationRequest, GenerationResult
from lmrs_tpu.engine.jax_engine import needs_host_quant_init
from lmrs_tpu.engine.watchdog import DaemonExecutor
from lmrs_tpu.testing import faults
from lmrs_tpu.utils.env import env_bool, env_float

logger = logging.getLogger("lmrs.replicated")


class ReplicatedEngine:
    """dp independent JaxEngines over disjoint device subsets."""

    schedules_internally = True  # each replica admission-controls itself

    def __init__(
        self,
        engine_cfg: EngineConfig,
        model_cfg: ModelConfig,
        mesh_cfg: MeshConfig,
        devices=None,
    ):
        from lmrs_tpu.engine.jax_engine import JaxEngine

        devices = list(devices) if devices is not None else jax.devices()
        dp = mesh_cfg.dp
        per = mesh_cfg.n_devices // dp  # tp*sp*ep*pp per replica
        if dp < 2:
            raise ValueError("ReplicatedEngine needs mesh dp >= 2")
        if dp * per > len(devices):
            raise ValueError(
                f"mesh {mesh_cfg} needs {dp * per} devices, "
                f"have {len(devices)}")
        sub_cfg = replace(mesh_cfg, dp=1)

        # Load/init (and quantize) the weights ONCE on host; every replica
        # device_puts the same tree onto its own sub-mesh — dp identical
        # checkpoint reads would serialize startup on disk I/O.
        if engine_cfg.checkpoint_path:
            from lmrs_tpu.models.loader import load_checkpoint

            shared = load_checkpoint(engine_cfg.checkpoint_path, model_cfg)
        elif needs_host_quant_init(model_cfg, engine_cfg.quantize):
            # quantized random init builds the int8 tree host-side (numpy)
            # without ever materializing the full-precision tree — at 8B
            # shape that tree would OOM the default device.
            # SHARED gate with JaxEngine (needs_host_quant_init): small
            # quantized models keep the device init so the random-weight
            # workload matches the single-engine path exactly
            # (replica-vs-single comparability)
            from lmrs_tpu.ops.quant import random_quantized_init

            logger.warning("no checkpoint for %s: replicas share random-init "
                           "weights", model_cfg.name)
            shared = random_quantized_init(model_cfg, engine_cfg.seed)
        else:
            from lmrs_tpu.models.transformer import init_params

            logger.warning("no checkpoint for %s: replicas share random-init "
                           "weights", model_cfg.name)
            shared = init_params(model_cfg, jax.random.PRNGKey(engine_cfg.seed))
            if engine_cfg.quantize:
                from lmrs_tpu.ops.quant import quantize_params

                shared = quantize_params(shared)
        if engine_cfg.quantize and engine_cfg.checkpoint_path:
            from lmrs_tpu.ops.quant import quantize_params

            shared = quantize_params(shared)

        # ONE single-worker executor PER replica: a replica's scheduler is
        # not thread-safe, so everything aimed at it — construction, user
        # shards, health probes — funnels through its own queue and can
        # never run concurrently, while distinct replicas run in parallel.
        # DAEMON workers (engine/watchdog.py): a wedged shard or probe
        # future must never pin interpreter exit, and a quarantined
        # replica's stuck pool can simply be abandoned and replaced.
        self._pools = [DaemonExecutor(thread_name=f"lmrs-dp{i}")
                       for i in range(dp)]

        def build(i: int) -> JaxEngine:
            # per-replica sampling seed: identical weights, decorrelated
            # sampling streams (same prompt on two replicas must not emit
            # identical tokens at temperature > 0)
            cfg_i = replace(engine_cfg, seed=engine_cfg.seed + i,
                            checkpoint_path=None, quantize=None)
            return JaxEngine(cfg_i, model_cfg, sub_cfg, params=shared,
                             devices=devices[i * per: (i + 1) * per])

        self.replicas = [
            fut.result() for fut in
            [self._pools[i].submit(build, i) for i in range(dp)]
        ]
        # failure detection / elastic recovery (SURVEY.md §5.3): a replica
        # whose batch raises is marked unhealthy and routed around, so the
        # executor's retry of the failed requests lands on live replicas
        # instead of round-robining back onto the dead one.  Unhealthy
        # replicas get a tiny SYNTHETIC probe each wave (never user
        # traffic); a probe that completes re-admits the replica.  Probing
        # also bounds the poison-request case — a request that
        # deterministically crashes its batch marks replicas unhealthy as
        # it burns retries, but the probes (which are not the poison)
        # revive them right after.
        self._healthy = [True] * dp
        self._probes: dict[int, object] = {}  # replica idx -> Future
        logger.info("replicated engine: dp=%d replicas x %d device(s)", dp, per)

    # ------------------------------------------------------------------ API

    def _reap_probes(self) -> None:
        for ri in list(self._probes):
            fut = self._probes[ri]
            if not fut.done():
                continue
            del self._probes[ri]
            # cancelled() FIRST: a probe queued behind a quarantined
            # shard is cancelled by the pool teardown, and exception()
            # on a cancelled future RAISES CancelledError (a
            # BaseException no degrade path catches) instead of
            # returning it
            if fut.cancelled() or fut.exception() is not None:
                results = None
            else:
                results = fut.result()
            # a degraded (wedged) engine fail-fasts its probe as a RESULT
            # carrying an error, not an exception — both mean "still down"
            ok = results is not None and all(r.error is None
                                             for r in results)
            if ok:
                self._healthy[ri] = True
                logger.info("replica %d probe succeeded: re-admitted", ri)
            else:
                logger.warning("replica %d probe failed: still unhealthy", ri)

    def _launch_probes(self) -> None:
        for ri, ok in enumerate(self._healthy):
            if not ok and ri not in self._probes:
                probe = GenerationRequest(prompt="health probe",
                                          request_id=-1, max_new_tokens=1)
                self._probes[ri] = self._pools[ri].submit(
                    self.replicas[ri].generate_batch, [probe])

    def generate_batch(self, requests: list[GenerationRequest],
                       on_result=None, on_tokens=None) -> list[GenerationResult]:
        # on_tokens fans in from every replica's worker thread CONCURRENTLY —
        # callers must pass a thread-safe callback (the HTTP server's
        # per-job queues are; a bare list append is not)
        if on_result is not None:
            # replicas have no cross-replica mid-run hook: deliver per wave
            # and loop on callback submissions (engine/api.py)
            from lmrs_tpu.engine.api import drain_with_callback

            return drain_with_callback(
                lambda reqs: self._generate_wave(reqs, on_tokens=on_tokens),
                requests, on_result)
        return self._generate_wave(requests, on_tokens=on_tokens)

    def _shard_timeout_s(self) -> float | None:
        """Per-shard bound on the wave wait (straggler containment).
        None (untimed — the pre-watchdog behavior) when the hang-survival
        tier is killed via ``LMRS_WATCHDOG=0``; the timeout may only be
        armed WITH the member engines' watchdogs, whose fail-fast runner
        is what makes submitting to a quarantined replica's fresh pool
        safe (the abandoned worker can still be inside generate_batch —
        the runner refuses to touch the wedged scheduler concurrently)."""
        if not env_bool("LMRS_WATCHDOG", True):
            return None
        return env_float("LMRS_SHARD_TIMEOUT_S", 600.0, lo=1.0)

    def _shard_wait_s(self, ri: int, timeout: float | None) -> float | None:
        """Effective wait bound for one replica's shard: a member engine
        that has never completed a warm step (no step-time EMA yet) is
        still COLD-compiling, and a first-dispatch XLA compile can
        legitimately outlast LMRS_SHARD_TIMEOUT_S — extend to the
        watchdog's compile grace instead of quarantining healthy
        hardware mid-compile (the member watchdog itself graces compiles
        the same way)."""
        if timeout is None:
            return None
        wd = getattr(getattr(self.replicas[ri], "_scheduler", None),
                     "watchdog", None)
        if wd is not None and wd.ema_step_s is None:
            from lmrs_tpu.engine.watchdog import COLD_COMPILE_GRACE_S

            return max(timeout, COLD_COMPILE_GRACE_S)
        return timeout

    def _quarantine(self, ri: int, why: str) -> None:
        """A shard wedged: mark the replica unhealthy and ABANDON its
        worker pool (daemon thread — it can never pin interpreter exit).
        The fresh pool keeps probes and later waves from queueing behind
        the stuck call; re-admission goes through the existing probe
        loop once the replica answers again."""
        logger.error("replica %d quarantined: %s", ri, why)
        self._healthy[ri] = False
        self._pools[ri].shutdown(wait=False, cancel_futures=True)
        self._pools[ri] = DaemonExecutor(thread_name=f"lmrs-dp{ri}r")

    def _run_shard(self, replica, shard, on_tokens):
        # injection site (hang survival): a "stall" plan here wedges this
        # shard's worker thread the way a hung replica chip would —
        # exercising the bounded wait + quarantine + re-dispatch path;
        # "raise" takes the existing replica-fault path
        faults.fire("replicated.shard")
        return replica.generate_batch([req for _, req in shard],
                                      on_tokens=on_tokens)

    def _generate_wave(self, requests: list[GenerationRequest],
                       on_tokens=None) -> list[GenerationResult]:
        # route over healthy replicas only; if every replica is marked dead,
        # optimistically try them all again (a transient fault should not
        # permanently brick the fleet)
        self._reap_probes()
        targets = [i for i, ok in enumerate(self._healthy) if ok]
        if not targets:
            logger.warning("all %d replicas marked unhealthy; retrying all",
                           len(self.replicas))
            targets = list(range(len(self.replicas)))
        # round-robin keeps shard sizes balanced for any request count
        shards: list[list[tuple[int, GenerationRequest]]] = [[] for _ in targets]
        for i, req in enumerate(requests):
            shards[i % len(targets)].append((i, req))

        futures = [
            (ri, shard, self._pools[ri].submit(self._run_shard,
                                               self.replicas[ri], shard,
                                               on_tokens))
            for ri, shard in zip(targets, shards) if shard
        ]
        self._launch_probes()  # concurrent with the wave, on unhealthy replicas
        out: list[GenerationResult | None] = [None] * len(requests)
        timeout = self._shard_timeout_s()
        # straggler containment: shard entries whose replica wedged (stuck
        # future OR watchdog-wedged results), re-dispatched below onto the
        # replicas that survived this wave — greedy outputs are
        # replica-invariant (identical weights), so the re-dispatch is
        # token-identical to a healthy first placement
        redispatch: list[tuple[int, GenerationRequest]] = []
        survivors: list[int] = []
        for ri, shard, fut in futures:
            wait_s = self._shard_wait_s(ri, timeout)
            try:
                # bounded wait (timeout=None restores the untimed
                # pre-watchdog wait; cold-compiling members get the
                # compile grace): a shard that WEDGES inside a device
                # call is abandoned with its daemon worker — quarantined,
                # its requests re-dispatched — instead of stalling the
                # whole wave forever
                results = fut.result(timeout=wait_s)
            except FutureTimeout:
                self._quarantine(
                    ri, f"shard produced no result within {wait_s:.1f}s "
                        f"({len(shard)} request(s) re-dispatched)")
                redispatch.extend(shard)
                continue
            except Exception as e:  # degrade-and-continue per replica
                logger.exception("replica %d batch failure: marked unhealthy", ri)
                self._healthy[ri] = False
                for (pos, _), res in zip(shard, [
                    GenerationResult(request_id=req.request_id,
                                     finish_reason="error",
                                     error=str(e) or type(e).__name__)
                        for _, req in shard]):
                    out[pos] = res
                continue
            # the member engine's own watchdog may have declared the wedge
            # first (fail-fast wedged results instead of a stuck future):
            # same containment — route the wedged requests elsewhere
            wedged = [ent for ent, res in zip(shard, results)
                      if res.finish_reason == "wedged"]
            if wedged:
                self._healthy[ri] = False
                logger.warning("replica %d returned %d wedged result(s): "
                               "re-dispatching to healthy replicas",
                               ri, len(wedged))
                redispatch.extend(wedged)
                for ent, res in zip(shard, results):
                    if res.finish_reason != "wedged":
                        out[ent[0]] = res
                continue
            self._healthy[ri] = True
            survivors.append(ri)
            for (pos, _), res in zip(shard, results):
                out[pos] = res
        if redispatch:
            self._redispatch(redispatch, survivors, out, on_tokens, timeout)
        return [r for r in out if r is not None]

    def _redispatch(self, entries, survivors, out, on_tokens,
                    timeout) -> None:
        """One containment retry wave: the wedged shards' requests run on
        the replicas that answered this wave (all currently-healthy ones
        when none did).  A request that wedges or fails AGAIN terminates
        wedged/error — the executor's retry budget owns anything
        further."""
        targets = survivors or [i for i, ok in enumerate(self._healthy)
                                if ok] or list(range(len(self.replicas)))
        shards: list[list[tuple[int, GenerationRequest]]] = [
            [] for _ in targets]
        for k, ent in enumerate(entries):
            shards[k % len(targets)].append(ent)
        futures = [
            (ri, shard, self._pools[ri].submit(self._run_shard,
                                               self.replicas[ri], shard,
                                               on_tokens))
            for ri, shard in zip(targets, shards) if shard
        ]
        for ri, shard, fut in futures:
            wait_s = self._shard_wait_s(ri, timeout)
            try:
                results = fut.result(timeout=wait_s)
            except FutureTimeout:
                self._quarantine(
                    ri, f"re-dispatched shard wedged again within "
                        f"{wait_s:.1f}s")
                results = [
                    GenerationResult(request_id=req.request_id,
                                     finish_reason="wedged",
                                     error="re-dispatched shard wedged "
                                           "again")
                    for _, req in shard
                ]
            except Exception as e:  # noqa: BLE001 - degrade per replica
                logger.exception("replica %d re-dispatch failure", ri)
                self._healthy[ri] = False
                results = [
                    GenerationResult(request_id=req.request_id,
                                     finish_reason="error",
                                     error=str(e) or type(e).__name__)
                    for _, req in shard
                ]
            for (pos, _), res in zip(shard, results):
                out[pos] = res

    def cancel(self, request_id: int) -> None:
        """Engine optional abort hook: forward to every replica — request
        ids are unique across the wave (shards keep the caller's ids) and
        unknown ids are a no-op per the contract, so broadcasting is
        sufficient and race-free (scheduler.cancel is thread-safe)."""
        for replica in self.replicas:
            replica.cancel(request_id)

    def shutdown(self) -> None:
        for replica in self.replicas:
            replica.shutdown()
        for pool in self._pools:
            # daemon workers (DaemonExecutor): even a wedged shard or
            # probe future can never pin interpreter exit; cancel_futures
            # just drops anything still queued
            pool.shutdown(wait=False, cancel_futures=True)

    def usage_report(self) -> dict:
        """Optional Engine hook: per-tenant ledger rollups merged across
        replicas (obs.merge_usage — the one merge rule, so fleet totals
        equal the sum of replica totals exactly)."""
        from lmrs_tpu.obs.ledger import merge_usage, totals_from_tenants

        tenants: dict[str, dict] = {}
        enabled = False
        for r in self.replicas:
            hook = getattr(r, "usage_report", None)
            doc = hook() if hook is not None else {}
            enabled = enabled or bool(doc.get("enabled"))
            for t, roll in (doc.get("tenants") or {}).items():
                merge_usage(tenants.setdefault(t, {}), roll)
        return {"object": "usage", "enabled": enabled, "tenants": tenants,
                "totals": totals_from_tenants(tenants)}

    def anatomy_report(self) -> dict:
        """Optional Engine hook: replica anatomy documents merged with the
        one merge rule (obs.merge_anatomy) — additive totals sum exactly,
        per-class percentiles are iteration-weighted estimates."""
        from lmrs_tpu.obs.anatomy import merge_anatomy

        docs = []
        for r in self.replicas:
            hook = getattr(r, "anatomy_report", None)
            if hook is not None:
                docs.append(hook())
        return merge_anatomy(docs)

    def slo_report(self) -> dict:
        """Optional Engine hook: the replicated engine's health is the
        WORST replica's SLO state (one degraded shard degrades the
        host's placement score — the router cannot address replicas
        individually)."""
        from lmrs_tpu.obs.slo import state_rank

        docs = []
        for r in self.replicas:
            hook = getattr(r, "slo_report", None)
            if hook is not None:
                docs.append(hook())
        live = [d for d in docs if d.get("enabled")]
        if not live:
            return {"enabled": False, "state": "ok", "specs": {}}
        worst = max(live, key=lambda d: state_rank(d.get("state")))
        return {**worst, "replicas": len(live)}

    def engine_metrics(self) -> dict:
        """Fleet metrics in the same shape as one scheduler's report
        (engine/scheduler.py:metrics_report) so downstream consumers — the
        pipeline stats banner, /metrics — need no replica-awareness."""
        per = [r.engine_metrics() for r in self.replicas]
        per = [m for m in per if m]
        if not per:
            return {}
        # replicas run concurrently: aggregate rate = total work / the
        # longest replica's scheduler time
        secs = max((m.get("scheduler_seconds", 0.0) for m in per), default=0.0)
        prefill = sum(m.get("prefill_tokens", 0) for m in per)
        decode = sum(m.get("decode_tokens", 0) for m in per)
        # mixed-batch fleet view: per-replica fused dispatchers compile
        # their own bucketed mixed shapes; the fleet block sums their
        # work and averages budget fill (same shape as one scheduler's
        # mixed_batch block, minus the per-replica knobs)
        mixed = [m.get("mixed_batch") for m in per]
        mixed = [b for b in mixed if b]
        mixed_block = {}
        if mixed:
            disp = sum(b.get("dispatches", 0) for b in mixed)
            mixed_block = {"mixed_batch": {
                "enabled": any(b.get("enabled") for b in mixed),
                "dispatches": disp,
                "fill_ratio": round(
                    sum(b.get("fill_ratio", 0.0) * b.get("dispatches", 0)
                        for b in mixed) / disp, 3) if disp else 0.0,
                "prefill_tokens_piggybacked": sum(
                    b.get("prefill_tokens_piggybacked", 0) for b in mixed),
            }}
        # ragged-span fleet view (ISSUE 16): same summing shape; compile
        # shapes ADD across replicas — each compiles its own span family
        rpa = [b for b in (m.get("rpa") for m in per) if b]
        rpa_block = {}
        if rpa:
            rpa_block = {"rpa": {
                "enabled": any(b.get("enabled") for b in rpa),
                "dispatches": sum(b.get("dispatches", 0) for b in rpa),
                "span_tokens": sum(b.get("span_tokens", 0) for b in rpa),
                "compile_shapes": sum(
                    b.get("compile_shapes", 0) for b in rpa),
            }}
        return {
            "replicas": len(per),
            "healthy_replicas": sum(self._healthy),
            "prefill_tokens": prefill,
            "decode_tokens": decode,
            **mixed_block,
            **rpa_block,
            "prefill_tokens_per_sec": round(prefill / max(secs, 1e-9), 1),
            "decode_tokens_per_sec": round(decode / max(secs, 1e-9), 1),
            "mean_decode_occupancy": round(
                sum(m.get("mean_decode_occupancy", 0.0) for m in per) / len(per), 3),
            "peak_kv_page_utilization": max(
                m.get("peak_kv_page_utilization", 0.0) for m in per),
            "scheduler_seconds": round(secs, 3),
            "per_replica": per,
        }
