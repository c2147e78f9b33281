"""Seeded scheduler fuzz: bookkeeping invariants over randomized
adversarial workloads.

The latent-bug class this hunts (see the round-1 SMEM OOB fix, commit
e763805): host slot-state bookkeeping — stale lengths on slot reuse,
preemption/requeue, tight-pool growth, packed-vs-unpacked routing — only
breaks on *combinations* no hand-written scenario covers.

Exact cross-scheduler text equality is deliberately NOT asserted here: a
random-init model's greedy argmax is knife-edge, so different dispatch
bucketing (different pad shapes → different f32 reduction order) can flip
near-ties between the static and continuous paths without any bug — the
single calibrated shape in test_greedy_matches_static_scheduler covers
that equivalence.  What IS asserted, per scenario:

* determinism: the SAME continuous config on the same mix twice produces
  token-identical results — shape-identical dispatches have identical
  numerics, so any divergence is host-state corruption (stale slot
  arrays, preemption order, page recycling);
* the request contract: no errors, completion budgets respected, stop
  strings absent from returned text, every request finishes with a valid
  reason;
* accounting sanity: decode token counts match completion totals minus
  the prefill-sampled first tokens (bounded below), occupancy in [0, 1].
"""

from __future__ import annotations

import random

import pytest

from lmrs_tpu.config import EngineConfig, ModelConfig
from lmrs_tpu.engine.api import GenerationRequest
from lmrs_tpu.engine.jax_engine import JaxEngine

WORDS = ("plan kernel budget review latency timeline shipping quarter "
         "inference engine design hiring allocation targets").split()


def _model(dim: int = 64, hidden: int = 128) -> ModelConfig:
    return ModelConfig(vocab_size=512, dim=dim, n_layers=2, n_heads=4,
                       n_kv_heads=2, hidden_dim=hidden, max_seq_len=256,
                       dtype="float32")


def _requests(rng: random.Random, n: int) -> list[GenerationRequest]:
    reqs = []
    for i in range(n):
        n_words = rng.choice((2, 8, 30, 80))
        prompt = " ".join(rng.choice(WORDS) for _ in range(n_words))
        stop = ("ing",) if rng.random() < 0.3 else ()
        reqs.append(GenerationRequest(
            prompt=prompt, request_id=i, temperature=0.0,
            max_new_tokens=rng.choice((1, 3, 9, 20)), stop=stop))
    return reqs


def _check_contract(reqs, out):
    by_id = {r.request_id: r for r in reqs}
    assert [r.request_id for r in out] == [r.request_id for r in reqs]
    for res in out:
        req = by_id[res.request_id]
        assert res.error is None, res
        assert res.finish_reason in ("stop", "length")
        assert res.completion_tokens <= req.max_new_tokens
        for s in req.stop:
            assert s not in res.text


@pytest.mark.parametrize("seed", [11, 23, 37, 59])
def test_fuzzed_continuous_scheduler_is_deterministic(seed):
    rng = random.Random(seed)
    mc = _model()
    n_requests = rng.randint(1, 9)
    scenario = dict(
        max_batch_slots=rng.choice((1, 2, 3)),
        page_size=rng.choice((16, 32)),
        # small budgets force on-demand growth + youngest-slot preemption;
        # 1 = worst-case pool (never preempts)
        num_pages=rng.choice((1, 24, 48)),
        decode_block=rng.choice((2, 5, 8)),
        prefill_chunk=rng.choice((64, 4096)),  # chunked vs one-dispatch
    )
    reqs = _requests(rng, n_requests)

    runs = []
    metrics = []
    for _ in range(2):
        eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                     max_tokens=24, seed=0, **scenario), mc)
        out = eng.generate_batch(reqs)
        _check_contract(reqs, out)
        runs.append([(r.text, r.finish_reason, r.completion_tokens)
                     for r in out])
        m = eng._scheduler.metrics
        metrics.append(dict(m))
        assert 0.0 <= m["occupancy_sum"] <= m["decode_dispatches"] + 1e-9
        eng.shutdown()
    assert runs[0] == runs[1], (scenario, metrics)


def _prefix_requests(rng: random.Random, n: int) -> list[GenerationRequest]:
    """Prefix-sharing adversarial mix: requests draw one of three shared
    preambles (or none), diverge at random depths, and carry varied
    budgets — shared / partial / disjoint prefixes all collide in the
    radix tree at page boundaries."""
    preambles = [
        "shared preamble alpha " * rng.randint(1, 4),
        "shared preamble beta " * rng.randint(1, 4),
        "",
    ]
    reqs = []
    for i in range(n):
        pre = rng.choice(preambles)
        # partial sharing: sometimes truncate the preamble mid-page
        if pre and rng.random() < 0.4:
            pre = pre[: rng.randrange(1, len(pre))]
        body = " ".join(rng.choice(WORDS) for _ in range(rng.choice((2, 10, 40))))
        hint = len(pre) if (pre and rng.random() < 0.5) else None
        reqs.append(GenerationRequest(
            prompt=pre + body, request_id=i, temperature=0.0,
            max_new_tokens=rng.choice((1, 4, 12)), cache_prefix=hint))
    return reqs


def _check_pool_invariants(sched):
    """Post-run pool accounting: every page is either free (refcount 0) or
    retained by the prefix cache (refcount exactly 1 — no live sequences
    remain), the cache's page count agrees with the allocator, and no page
    is both free and referenced."""
    alloc = sched.cache.allocator
    cache = sched._prefix_cache
    cached = cache.cached_pages if cache else 0
    usable = sched.cache.num_pages - 1
    assert alloc.free_count == usable - cached, (alloc.free_count, cached)
    refs = [alloc.refcount(p) for p in range(1, sched.cache.num_pages)]
    assert sum(1 for r in refs if r > 0) == cached
    assert all(r in (0, 1) for r in refs), refs  # no leaked holders
    if cache:
        # every page the tree holds is live in the allocator
        stack = [cache.root]
        tree_pages = []
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            tree_pages.extend(node.pages)
        assert len(tree_pages) == len(set(tree_pages)) == cached
        assert all(alloc.refcount(p) == 1 for p in tree_pages)


@pytest.mark.parametrize("seed", [5, 17, 41])
def test_fuzzed_prefix_sharing_mixes(seed):
    """Randomized shared/partial/disjoint prefix mixes under page pressure:
    determinism across identical runs, the request contract, and pool
    accounting invariants (refcounts sum, no page both free and referenced)
    — with eviction exercised via small pools."""
    rng = random.Random(seed)
    mc = _model()
    scenario = dict(
        max_batch_slots=rng.choice((2, 3)),
        page_size=16,
        # small budgets force growth, preemption AND cache eviction under
        # pressure; 1 = worst-case pool (cache grows until close)
        num_pages=rng.choice((1, 20, 40)),
        decode_block=rng.choice((2, 6)),
        prefill_chunk=rng.choice((64, 4096)),
        prefix_cache_max_pages=rng.choice((0, 8)),
    )
    reqs = _prefix_requests(rng, rng.randint(4, 10))

    runs = []
    for _ in range(2):
        eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                     max_tokens=16, seed=0, **scenario), mc)
        out = eng.generate_batch(reqs)
        _check_contract(reqs, out)
        sched = eng._scheduler
        assert sched._prefix_cache is not None
        _check_pool_invariants(sched)
        m = sched.metrics
        assert m["prefix_queries"] >= len(reqs)
        assert m["prefix_tokens_reused"] >= 0
        runs.append([(r.text, r.finish_reason, r.completion_tokens)
                     for r in out])
        eng.shutdown()
    assert runs[0] == runs[1], scenario


def test_fuzzed_prefix_cache_on_off_parity():
    """Greedy outputs must be token-identical with the prefix cache on and
    off across a randomized shared-prefix mix (the cache may only change
    WHERE KV lives, never its values)."""
    rng = random.Random(77)
    mc = _model()
    reqs = _prefix_requests(rng, 8)
    texts = {}
    for on in (True, False):
        eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                     max_tokens=16, seed=0, max_batch_slots=2,
                                     page_size=16, decode_block=4,
                                     prefix_cache=on), mc)
        out = eng.generate_batch(reqs)
        _check_contract(reqs, out)
        if on:
            assert eng._scheduler.metrics["prefix_hits"] > 0
        texts[on] = [r.text for r in out]
        eng.shutdown()
    assert texts[True] == texts[False]


@pytest.mark.parametrize("seed", [13, 47])
def test_fuzzed_mixed_admission_bursts(seed, monkeypatch):
    """Mixed dispatch (ISSUE 11) under randomized admission bursts
    MID-DECODE: on_result callbacks submit fresh batches into the live
    stream, so new prompts are admitted while earlier requests decode —
    exactly the regime the fused mixed step serves.  Asserts, per seed:

    * greedy token-identity LMRS_MIXED=0 vs 1 over the identical burst
      workload (the mixed arm must actually have mixed);
    * determinism: the mixed arm twice is token-identical;
    * the request contract and the scheduler auditor, clean."""
    rng = random.Random(seed)
    mc = _model()
    scenario = dict(
        max_batch_slots=rng.choice((2, 3)),
        page_size=16,
        num_pages=rng.choice((1, 32)),  # 32 = real pressure mid-mix
        decode_block=rng.choice((2, 4)),
        prefill_chunk=rng.choice((64, 4096)),
        mixed_token_budget=rng.choice((48, 256)),
    )
    initial = _requests(rng, rng.randint(2, 4))
    # pre-generated burst batches: submitted when pinned request ids
    # complete, so the submission SCHEDULE is identical across arms
    bursts = [_requests(random.Random(seed + 1 + i), rng.randint(1, 3))
              for i in range(2)]
    for i, batch in enumerate(bursts):
        for r in batch:
            r.request_id += 100 * (i + 1)
    trigger = {initial[0].request_id: 0,
               initial[-1].request_id: 1}

    def run(mixed: str):
        monkeypatch.setenv("LMRS_MIXED", mixed)
        eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                     max_tokens=24, seed=0, **scenario), mc)
        fired = set()

        def on_result(res, submit):
            i = trigger.get(res.request_id)
            if i is not None and i not in fired:
                fired.add(i)
                submit(list(bursts[i]))

        out = eng.generate_batch(list(initial), on_result=on_result)
        assert eng._scheduler.audit() == []
        m = dict(eng._scheduler.metrics)
        eng.shutdown()
        every = initial + [r for b in bursts for r in b]
        assert {r.request_id for r in out} == {r.request_id for r in every}
        by_id = {r.request_id: r for r in every}
        for res in out:
            req = by_id[res.request_id]
            assert res.error is None, res
            assert res.finish_reason in ("stop", "length")
            assert res.completion_tokens <= req.max_new_tokens
        return sorted((r.request_id, r.text, r.finish_reason,
                       r.completion_tokens) for r in out), m

    base, m_off = run("0")
    assert m_off["mixed_dispatches"] == 0
    mixed1, m_on = run("1")
    mixed2, _ = run("1")
    assert mixed1 == mixed2, scenario  # determinism
    assert mixed1 == base, scenario    # greedy A/B identity
    # the bursts landed mid-decode, so the mixed arm must have mixed
    assert m_on["mixed_dispatches"] > 0, scenario
    assert m_on["prefill_tokens_piggybacked"] > 0, scenario


@pytest.mark.parametrize("seed", [19, 53])
def test_fuzzed_rpa_admission_bursts(seed):
    """Ragged-span dispatch (ISSUE 16) under the same randomized
    mid-decode admission bursts: greedy token-identity of span-dispatched
    mixed steps against alternating dispatch (``mixed_batch=False``; the
    span arm must actually dispatch span programs), span-arm
    determinism, the request contract, and a clean auditor — the fuzzed
    counterpart of the hand-written A/B matrix in test_rpa.py."""
    rng = random.Random(seed)
    mc = _model()
    scenario = dict(
        max_batch_slots=rng.choice((2, 3)),
        page_size=16,
        num_pages=rng.choice((1, 32)),
        decode_block=rng.choice((2, 4)),
        prefill_chunk=rng.choice((64, 4096)),
        mixed_token_budget=rng.choice((48, 256)),
        speculate_k=rng.choice((0, 3)),
    )
    initial = _requests(rng, rng.randint(2, 4))
    bursts = [_requests(random.Random(seed + 1 + i), rng.randint(1, 3))
              for i in range(2)]
    for i, batch in enumerate(bursts):
        for r in batch:
            r.request_id += 100 * (i + 1)
    trigger = {initial[0].request_id: 0,
               initial[-1].request_id: 1}

    def run(mixed: bool):
        eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                     max_tokens=24, seed=0,
                                     mixed_batch=mixed, **scenario), mc)
        fired = set()

        def on_result(res, submit):
            i = trigger.get(res.request_id)
            if i is not None and i not in fired:
                fired.add(i)
                submit(list(bursts[i]))

        out = eng.generate_batch(list(initial), on_result=on_result)
        assert eng._scheduler.audit() == []
        m = dict(eng._scheduler.metrics)
        eng.shutdown()
        every = initial + [r for b in bursts for r in b]
        assert {r.request_id for r in out} == {r.request_id for r in every}
        by_id = {r.request_id: r for r in every}
        for res in out:
            req = by_id[res.request_id]
            assert res.error is None, res
            assert res.finish_reason in ("stop", "length")
            assert res.completion_tokens <= req.max_new_tokens
        return sorted((r.request_id, r.text, r.finish_reason,
                       r.completion_tokens) for r in out), m

    base, m_off = run(False)
    assert m_off["rpa_dispatches"] == 0
    span1, m_on = run(True)
    span2, _ = run(True)
    assert span1 == span2, scenario  # determinism
    assert span1 == base, scenario   # greedy A/B identity
    assert m_on["rpa_dispatches"] > 0, scenario


@pytest.mark.parametrize("seed", [29, 71])
def test_fuzzed_qos_preemption_heavy_mix(seed, monkeypatch):
    """Fair-share admission + QoS preemption (ISSUE 17) under a
    preemption-heavy randomized multi-tenant mix: a tight page pool with
    several tenants and both priority classes, so slots preempt and the
    armed policy actually exercises its victim rule.  Asserts, per seed:

    * greedy token-identity LMRS_QOS=0 vs 1 over the identical workload
      (QoS changes admission and victim ORDER, never tokens);
    * determinism: the armed arm twice is token-identical;
    * preemption really happened in both arms (the mix is not vacuous);
    * the scheduler auditor and ledger conservation, clean through the
      preemption/requeue churn: per-tenant rollups sum to totals and no
      entry stays live."""
    rng = random.Random(seed)
    mc = _model()
    # short prompts (all slots admit at once) + long decodes into a pool
    # too small for every slot's worst-case growth: the collision that
    # triggers preemption (the test_scheduler.py pressure recipe)
    scenario = dict(
        max_batch_slots=4,
        page_size=16,
        num_pages=10,
        decode_block=rng.choice((2, 4)),
        prefill_chunk=rng.choice((64, 4096)),
    )
    tenants = ("noisy", "quiet", "bulk")
    reqs = []
    for i in range(rng.randint(6, 9)):
        n_words = rng.choice((4, 8, 12))
        reqs.append(GenerationRequest(
            prompt=" ".join(rng.choice(WORDS) for _ in range(n_words)),
            request_id=i, temperature=0.0,
            max_new_tokens=40,  # long growth: every slot crosses pages
            tenant=rng.choice(tenants),
            qos_class=rng.choice(("interactive", "batch"))))

    def run(qos: str):
        monkeypatch.setenv("LMRS_QOS", qos)
        eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                     max_tokens=40, seed=0, **scenario), mc)
        out = eng.generate_batch(list(reqs))
        _check_contract(reqs, out)
        sched = eng._scheduler
        assert sched.audit() == []
        preempts = int(sched._c_preemptions.value)
        usage = eng.usage_report()
        qos_rep = eng.qos_report()
        eng.shutdown()
        assert usage["live_requests"] == 0
        tenant_dev = sum(r["device_seconds"]
                         for r in usage["tenants"].values())
        # 1e-6: report values are rounded per tenant before summing
        assert abs(tenant_dev - usage["totals"]["device_seconds"]) < 1e-6
        assert set(usage["tenants"]) == {r.tenant for r in reqs}
        return ([(r.text, r.finish_reason, r.completion_tokens)
                 for r in out], preempts, qos_rep)

    base, pre_off, rep_off = run("0")
    assert rep_off == {"object": "qos", "enabled": False}
    armed1, pre_on, rep_on = run("1")
    armed2, _, _ = run("1")
    assert armed1 == armed2, scenario  # determinism
    assert armed1 == base, scenario    # greedy A/B identity
    assert rep_on["enabled"] is True
    # the pool was tight enough that both arms actually preempted
    assert pre_off > 0 and pre_on > 0, (scenario, pre_off, pre_on)


def test_fuzzed_slot_reuse_with_interpret_kernels(monkeypatch):
    """Slot recycling + varied lengths through the REAL kernel path
    (interpret): the exact conditions of the r1 stale-length SMEM bug —
    many short requests through few slots, lengths crossing page
    boundaries, pool pressure — twice, token-identical."""
    monkeypatch.setenv("LMRS_FORCE_KERNELS", "interpret")
    rng = random.Random(101)
    mc = _model(dim=512, hidden=256)  # hd=128: kernel gate on
    reqs = _requests(rng, 7)

    runs = []
    for _ in range(2):
        eng = JaxEngine(EngineConfig(backend="jax", scheduler="continuous",
                                     max_tokens=24, seed=0, max_batch_slots=2,
                                     page_size=16, num_pages=40,
                                     decode_block=4), mc)
        assert eng._scheduler._use_ragged
        out = eng.generate_batch(reqs)
        _check_contract(reqs, out)
        runs.append([r.text for r in out])
        eng.shutdown()
    assert runs[0] == runs[1]
