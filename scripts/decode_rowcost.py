"""Per-row fixed-cost probe for the ragged decode kernel, in isolation.

Times ONE attention layer's kernel (no model around it) at bench-1b's
attention shape across a batch sweep, for the arms:

* walk       — ``paged_decode_pallas`` (page walk only, no RMW)
* fused      — ``paged_decode_pallas_fused`` (walk + RMW + cross-row pipeline)
* rpa        — ``ragged_spans_pallas`` at q_len=1 spans: the unified
               span program the scheduler now routes EVERY phase through
               (ISSUE 16), measured at its decode-shaped corner so the
               us/row fit is directly comparable against the retired
               fused arm it replaced (perf_sentry tracks the
               ``decode_row_us_rpa`` bench-detail column)
* walk_gG / fused_gG — the multi-row kernels at row_group=G (one pair per
               entry in LMRS_ROWCOST_GROUPS, default "2,4,8"): the
               group-size sweep behind EngineConfig.decode_row_group —
               pick the G where the us/row curve flattens (past that,
               groups only add padding waste at partial occupancy).  The
               walk arms isolate the grouped pipeline itself; the fused
               arms are what the serving path actually runs.

Kernel calls are chained inside one jitted ``fori_loop`` (output feeds
the next q, pools ride the carry — the decode-block scan's shape) and
timed by the shared LONG-minus-SHORT chain method
(lmrs_tpu.utils.perf_model.time_chain): the fetch round trip and the
dispatch cost cancel exactly instead of polluting the fit (naive per-call
timing of one kernel call is mostly that fixed cost).
Run: python scripts/decode_rowcost.py
Env hooks: LMRS_ROWCOST_GROUPS (comma list, "" disables the group arms),
LMRS_ROWCOST_INTERPRET=1 (Pallas interpret mode — the CPU-only stand-in
harness: us/kernel numbers then measure the emulator and are only
meaningful RELATIVE to each other per arm, never absolutely).
"""

import _pathfix  # noqa: F401
from lmrs_tpu.utils.platform import setup_compile_cache

setup_compile_cache()
import jax
import jax.numpy as jnp
import numpy as np

from lmrs_tpu.ops.paged_attention import (
    pack_spans,
    paged_decode_pallas,
    paged_decode_pallas_fused,
    ragged_spans_pallas,
)
from lmrs_tpu.utils.env import env_bool, env_list
from lmrs_tpu.utils.perf_model import time_chain

KH, NREP, HD, PS = 8, 2, 128, 512   # bench-1b attention shape
LIVE = 64
LO, HI = 64, 2048
REPS = 5
INTERPRET = env_bool("LMRS_ROWCOST_INTERPRET", False)


def make_chain(arm, iters, kn, vn, pt, kl, row_group=1, spans=None):
    @jax.jit
    def chain(q, kp, vp):
        def body(_, carry):
            q, kp, vp = carry
            if arm == "rpa":
                qs, ql = spans
                out, kp, vp = ragged_spans_pallas(
                    q, kn, vn, kp, vp, pt, kl, qs, ql,
                    interpret=INTERPRET)
            elif arm.startswith("walk"):
                out = paged_decode_pallas(q, kp, vp, pt, kl,
                                          interpret=INTERPRET,
                                          row_group=row_group)
            else:
                out, kp, vp = paged_decode_pallas_fused(
                    q, kn, vn, kp, vp, pt, kl, interpret=INTERPRET,
                    row_group=row_group)
            return (out.astype(q.dtype), kp, vp)

        return jax.lax.fori_loop(0, iters, body, (q, kp, vp))

    return chain


def main():
    rng = np.random.default_rng(0)
    lo, hi, reps = LO, HI, REPS
    if INTERPRET:  # emulator chains are ~1000x slower; keep the harness usable
        lo, hi, reps = 2, 8, 2
    batches = (4, 8) if INTERPRET else (8, 16, 24, 32)
    groups = [int(g) for g in env_list("LMRS_ROWCOST_GROUPS",
                                       ("2", "4", "8"))]
    arms = [("walk", 1), ("fused", 1), ("rpa", 1)]
    for g in groups:
        arms += [(f"walk_g{g}", g), (f"fused_g{g}", g)]
    results = {}
    for B in batches:
        P = B + 1
        q = jnp.asarray(rng.standard_normal((B, KH * NREP, HD)), jnp.bfloat16)
        kn = jnp.asarray(rng.standard_normal((B, KH, HD)), jnp.bfloat16)
        vn = jnp.asarray(rng.standard_normal((B, KH, HD)), jnp.bfloat16)
        kp = jnp.asarray(rng.standard_normal((P, KH, PS, HD)), jnp.bfloat16)
        vp = jnp.asarray(rng.standard_normal((P, KH, PS, HD)), jnp.bfloat16)
        pt = jnp.asarray(
            (1 + np.arange(B))[:, None], jnp.int32)  # one live page per row
        kl = jnp.full((B,), LIVE, jnp.int32)

        # span-shaped inputs for the rpa arm: B decode rows = B q_len=1
        # spans over a SPAN_QT-aligned flat token buffer (kernel reads
        # only each span's first row; the padding rows are walked but
        # never gathered — the cost being measured IS that padding tax)
        ql_np = np.ones((B,), np.int32)
        qs_np, total = pack_spans(ql_np)
        qf = jnp.zeros((total, KH * NREP, HD), jnp.bfloat16)
        qf = qf.at[jnp.asarray(qs_np)].set(q)
        knf = jnp.zeros((total, KH, HD), jnp.bfloat16)
        knf = knf.at[jnp.asarray(qs_np)].set(kn)
        vnf = jnp.zeros((total, KH, HD), jnp.bfloat16)
        vnf = vnf.at[jnp.asarray(qs_np)].set(vn)
        spans = (jnp.asarray(qs_np), jnp.asarray(ql_np))

        for arm, g in arms:
            def chain(iters, arm=arm, g=g):
                if arm == "rpa":
                    fn = make_chain(arm, iters, knf, vnf, pt, kl,
                                    spans=spans)
                    return lambda: fn(qf, kp, vp)[0]
                fn = make_chain(arm, iters, kn, vn, pt, kl, row_group=g)
                return lambda: fn(q, kp, vp)[0]

            us = time_chain(chain, lo, hi, reps) * 1e6
            results.setdefault(arm, []).append((B, us))
            print(f"B={B:3d} {arm:9s} {us:8.2f} us/kernel"
                  f"  ({us/B:6.2f} us/row)", flush=True)

    for arm, rows in results.items():
        bs = np.array([r[0] for r in rows], float)
        us = np.array([r[1] for r in rows], float)
        A = np.vstack([bs, np.ones_like(bs)]).T
        slope, icpt = np.linalg.lstsq(A, us, rcond=None)[0]
        print(f"{arm:9s}: {slope:6.3f} us/row + {icpt:6.1f} us launch")


if __name__ == "__main__":
    main()
