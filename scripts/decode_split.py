"""Where does the decode step spend its 15 ms?  Sweep live context at
bench-1b scale: per-step time vs live tokens separates the weight-stream
cost (intercept) from the KV-walk cost (slope).
Run: python scripts/decode_split.py
Env hooks: LMRS_SPLIT_MODEL (preset, default bench-1b),
LMRS_SPLIT_QUANT=int8 (int8 weights+KV, e.g. the bench-8b arm),
LMRS_SPLIT_PS (page_size, default 512),
LMRS_SPLIT_GROUP (decode_row_group, default 4; LMRS_MULTIROW=0 is the
per-row A/B control — the refreshed-intercept measurement for the
multi-row page walk is this script run with both settings),
LMRS_SPLIT_RPA=1 (sweep the unified ragged-span program — q_len=1 spans
through scheduler._get_rpa_fn — instead of the legacy decode-block fn:
the ISSUE-16 A/B is this script run with both settings; note the span
arm dispatches one step per call where the legacy arm scans
decode_block steps in-graph, so the intercept carries the per-dispatch
host cost the decode-block scan amortizes),
LMRS_SPLIT_ANATOMY=1 (ISSUE 18: instead of the raw-dispatch sweep, run
REAL scheduler-loop traffic through three step-class arms — plain
decode / mixed / spec-verify — and print each class's host-segment
p50/p95 split from the step-anatomy profiler, i.e. the 3x spec-step
mystery as named segments; runs on CPU with a tiny model),
LMRS_SPLIT_SPEC_TREE=1 (ISSUE 19: real scheduler-loop traffic on a
repetitive workload through three speculation arms — off / linear
(LMRS_SPEC_TREE=0) / tree — reporting accepted tokens per dispatched
row, the draft segment's host time (tree drafting is fused on-device,
so its draft segment must collapse vs linear's host n-gram scan) and
tok/s; runs on CPU with a tiny model).
"""
import json
import time


import _pathfix  # noqa: F401  (repo-root import shim)
from lmrs_tpu.utils.platform import setup_compile_cache

setup_compile_cache()
import jax
import jax.numpy as jnp
import numpy as np

from lmrs_tpu.config import EngineConfig, ModelConfig, model_preset
from lmrs_tpu.engine.jax_engine import JaxEngine
from lmrs_tpu.utils.logging import setup_logging
from lmrs_tpu.utils.perf_model import decode_step_bytes, weight_bytes
from lmrs_tpu.utils.env import env_bool, env_int, env_str


def anatomy_main():
    """The LMRS_SPLIT_ANATOMY arm: host-segment p50/p95 per step class
    through the live scheduler loop (obs/anatomy.py)."""
    from lmrs_tpu.engine.api import GenerationRequest

    setup_logging(quiet=True)
    mc = ModelConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                     n_kv_heads=2, hidden_dim=128, max_seq_len=256,
                     dtype="float32")
    out = {}
    for arm, kw in (("plain", dict(mixed_batch=False)),
                    ("mixed", dict(mixed_batch=True)),
                    ("spec", dict(mixed_batch=False, speculate_k=4))):
        eng = JaxEngine(EngineConfig(
            backend="jax", scheduler="continuous", max_tokens=24,
            max_batch_slots=4, seed=0, decode_block=4, prefill_chunk=64,
            retry_delay=0.0, **kw), mc)
        sched = eng._scheduler
        reqs = [GenerationRequest(
            prompt="anatomy probe " * (3 + 4 * (i % 3)), request_id=i,
            temperature=0.0, max_new_tokens=12 + 4 * (i % 3))
            for i in range(8)]
        eng.generate_batch(reqs)  # warmup: compiles every shape
        an0 = sched.anatomy_snapshot()
        eng.generate_batch([mk_r for mk_r in (
            GenerationRequest(prompt="anatomy probe " * (3 + 4 * (i % 3)),
                              request_id=100 + i, temperature=0.0,
                              max_new_tokens=12 + 4 * (i % 3))
            for i in range(8))])
        rep = sched.anatomy_report(an0)
        assert sched.audit() == [], "anatomy conservation violated"
        out[arm] = {
            "host_overhead_us_step": rep.get("host_overhead_us_step"),
            "segments_ms": rep.get("segments_ms"),
            "classes": rep.get("classes"),
            "buckets": rep.get("buckets"),
            "rpa_pad_waste_ratio": rep.get("rpa_pad_waste_ratio"),
        }
        eng.shutdown()
    print(json.dumps(out, indent=1))


def spec_tree_main():
    """The LMRS_SPLIT_SPEC_TREE arm (ISSUE 19): speculation A/B/C through
    the live scheduler loop — accepted tokens/step, draft host time,
    tok/s.  The workload repeats itself so the n-gram draft has signal;
    the tree arm must match or beat linear acceptance while its draft
    segment collapses to dispatch-only."""
    from lmrs_tpu.engine.api import GenerationRequest
    from lmrs_tpu.utils.env import env_override

    setup_logging(quiet=True)
    mc = ModelConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                     n_kv_heads=2, hidden_dim=128, max_seq_len=512,
                     dtype="float32")
    out = {}
    for arm, k, tree in (("off", 0, "0"), ("linear", 4, "0"),
                         ("tree", 4, "1")):
        # the gate is read once at scheduler construction, so flipping
        # the env per engine gives all three arms in one process
        with env_override("LMRS_SPEC_TREE", tree):
            eng = JaxEngine(EngineConfig(
                backend="jax", scheduler="continuous", max_tokens=64,
                max_batch_slots=4, seed=0, decode_block=4,
                prefill_chunk=64, retry_delay=0.0, speculate_k=k), mc)
        sched = eng._scheduler

        def reqs(base):
            # repetitive prompt: the acceptance-rich case (summaries
            # quoting their source) — greedy, so arms are comparable
            return [GenerationRequest(
                prompt="the quick brown fox jumps over the lazy dog. " * 6,
                request_id=base + i, temperature=0.0, max_new_tokens=48)
                for i in range(8)]

        eng.generate_batch(reqs(0))  # warmup: compiles every shape
        an0 = sched.anatomy_snapshot()
        m0 = sched.metrics
        t0 = time.time()
        res = eng.generate_batch(reqs(100))
        wall = time.time() - t0
        rep = sched.anatomy_report(an0)
        st = sched._spec_tree_report(m0)
        assert sched.audit() == [], "span/page accounting violated"
        spec_cls = (rep.get("classes") or {}).get("spec") or {}
        toks = sum(r.completion_tokens for r in res)
        out[arm] = {
            "tok_s": round(toks / wall, 1),
            "accepted_tokens": (sched.metrics["spec_accepted_tokens"]
                                - m0["spec_accepted_tokens"]),
            "accept_per_step": st["accept_per_step"],
            "mean_accept_depth": st["mean_accept_depth"],
            "tree_dispatches": st["dispatches"],
            "draft_ms_total": (rep.get("segments_ms") or {}).get("draft"),
            "draft_p50_us_spec_step": (spec_cls.get("p50_us")
                                       or {}).get("draft"),
        }
        eng.shutdown()
    print(json.dumps(out, indent=1))
    lin_d = out["linear"]["draft_ms_total"] or 0.0
    tree_d = out["tree"]["draft_ms_total"] or 0.0
    print(f"draft host-ms: linear={lin_d} tree={tree_d} "
          f"({'COLLAPSED' if tree_d <= lin_d else 'REGRESSION'}); "
          f"accept/step: linear={out['linear']['accept_per_step']} "
          f"tree={out['tree']['accept_per_step']}", flush=True)


def main():
    setup_logging(quiet=True)
    model = model_preset(env_str("LMRS_SPLIT_MODEL", "bench-1b"))
    quant = env_str("LMRS_SPLIT_QUANT")
    eng = JaxEngine(EngineConfig(
        backend="jax", max_tokens=128, max_batch_slots=24,
        retry_delay=0.0, seed=0,
        page_size=env_int("LMRS_SPLIT_PS", 512, lo=8), num_pages=1,
        decode_block=128, prefill_chunk=4096, tokenizer="byte",
        decode_row_group=env_int("LMRS_SPLIT_GROUP", 4, lo=1),
        quantize=quant or None, kv_quantize=quant or None), model)
    sched = eng._scheduler
    print(f"decode_row_group={sched._row_group} "
          f"(LMRS_MULTIROW={'0 (per-row control)' if sched._row_group == 1 else 'on'})",
          flush=True)
    rng = np.random.default_rng(0)
    B, S = sched.B, model.max_seq_len
    w = sched.cache.max_pages_per_slot
    rpa = env_bool("LMRS_SPLIT_RPA", False)
    if rpa:
        from lmrs_tpu.engine.scheduler import _pow2_bucket
        from lmrs_tpu.ops.paged_attention import pack_spans

        qs_np, total = pack_spans(np.ones((B,), np.int32))
        tpb = _pow2_bucket(total, 16)
        rfn = sched._get_rpa_fn(tpb, w)
        print(f"arm=rpa token_bucket={tpb} window={w}", flush=True)
    else:
        dfn = sched._get_decode_fn(w)

    x = jnp.zeros((8,), jnp.float32)
    np.asarray(jax.device_get(x + 1))
    t0 = time.time(); np.asarray(jax.device_get(x + 1)); rtt = time.time() - t0

    seqs = [sched.cache.open_sequence(S) for _ in range(B)]
    table = jnp.asarray(sched.cache.page_table_array(seqs)[:, :w])
    onesB = jnp.ones((B,), jnp.float32)
    results = []
    for live in (64, 512, 1024, 1536, 1920):
        if rpa:
            # one q_len=1 span per row through the unified program; each
            # call is ONE decode step, so chain decode_block of them
            # async and sync once — the legacy arm's in-graph scan, done
            # at the dispatch layer
            tokens = jnp.zeros((1, tpb), jnp.int32).at[0, jnp.asarray(
                qs_np)].set(jnp.asarray(
                    rng.integers(1, 255, (B,), dtype=np.int32)))
            row_flat = jnp.full((tpb,), B, jnp.int32).at[jnp.asarray(
                qs_np)].set(jnp.arange(B, dtype=jnp.int32))
            rargs = (jnp.arange(B, dtype=jnp.int32), tokens,
                     jnp.asarray(qs_np), jnp.ones((B,), jnp.int32),
                     row_flat, jnp.full((B,), live, jnp.int32),
                     jnp.asarray(qs_np), table, jax.random.PRNGKey(8),
                     onesB, jnp.zeros((B,), jnp.int32), onesB)
            k, v, ks, vs = (sched.cache.k, sched.cache.v, sched.kscale,
                            sched.vscale)
            nxt, k, v, ks, vs = rfn(sched.params, k, v, ks, vs, *rargs)
            np.asarray(jax.device_get(nxt))
            t0 = time.time()
            for _ in range(3 * sched.decode_block):
                nxt, k, v, ks, vs = rfn(sched.params, k, v, ks, vs,
                                        *rargs)
            np.asarray(jax.device_get(nxt))
            wall = time.time() - t0 - rtt
            sched.cache.k, sched.cache.v = k, v
            sched.kscale, sched.vscale = ks, vs
            per_step = wall / (3 * sched.decode_block)
            gb = decode_step_bytes(model, B * live, quantized=bool(quant),
                                   kv_quantized=bool(quant)) / 1e9
            results.append((live, per_step, gb))
            print(f"live={live:5d}  {per_step*1e3:7.3f} ms/step  "
                  f"{gb:5.2f} GB/step  {gb/per_step:6.0f} GB/s",
                  flush=True)
            continue
        dargs = (jnp.asarray(rng.integers(1, 255, (B,), dtype=np.int32)),
                 jnp.full((B,), live, jnp.int32), table,
                 jnp.ones((B,), bool), jax.random.PRNGKey(8), onesB,
                 jnp.zeros((B,), jnp.int32), onesB)
        k, v = sched.cache.k, sched.cache.v
        toks, n_valid, k, v = dfn(sched.params, k, v, sched.kscale,
                          sched.vscale, None, *dargs)
        np.asarray(jax.device_get(n_valid))
        t0 = time.time()
        for _ in range(3):
            toks, n_valid, k, v = dfn(sched.params, k, v, sched.kscale,
                          sched.vscale, None, *dargs)
        np.asarray(jax.device_get(n_valid))
        wall = time.time() - t0 - rtt
        sched.cache.k, sched.cache.v = k, v
        per_step = wall / (3 * sched.decode_block)
        gb = decode_step_bytes(model, B * live, quantized=bool(quant),
                               kv_quantized=bool(quant)) / 1e9
        results.append((live, per_step, gb))
        print(f"live={live:5d}  {per_step*1e3:7.3f} ms/step  "
              f"{gb:5.2f} GB/step  {gb/per_step:6.0f} GB/s", flush=True)
    # linear fit: intercept = weight+fixed cost, slope = per-KV-token cost
    lv = np.array([r[0] for r in results], float)
    ms = np.array([r[1] for r in results], float) * 1e3
    A = np.vstack([lv, np.ones_like(lv)]).T
    slope, intercept = np.linalg.lstsq(A, ms, rcond=None)[0]
    wgb = weight_bytes(model, quantized=bool(quant))
    # per-token KV bytes via the perf model's own halving rule (one source
    # of truth with the GB/step column above)
    kvgb = B * (decode_step_bytes(model, 1, quantized=bool(quant),
                                  kv_quantized=bool(quant)) - wgb) / 1e9
    print(f"fit: intercept {intercept:.2f} ms (weights {wgb/1e9:.2f} GB "
          f"-> floor {wgb/819e9*1e3:.2f} ms), "
          f"slope {slope*1e3:.3f} us/live-token "
          f"(KV floor {kvgb/819*1e6:.3f} us/token)")
    for s_ in seqs:
        sched.cache.close_sequence(s_)


if __name__ == "__main__":
    if env_bool("LMRS_SPLIT_SPEC_TREE", False):
        spec_tree_main()
    elif env_bool("LMRS_SPLIT_ANATOMY", False):
        anatomy_main()
    else:
        main()
