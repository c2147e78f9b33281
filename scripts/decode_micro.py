"""The fused decode kernel alone, at the two dense cells' shapes, on one chip.

Calls ``paged_decode_pallas_fused`` directly (24 rows, 8 kv heads, head dim
128, 128-token pages, 13-15 live pages a row, ``row_group`` 4; bf16 pages
with 16 query heads as internlm2-1.8b has them, int8 pages with 32 as
mistral-7b has them) over a pool too large for VMEM, 200 calls chained in one
jitted ``fori_loop``, best of four.  Prints one JSON line a pool type:
microseconds a call and a page step, the share of the HBM roofline the live
K/V bytes reach, and the largest absolute error against a float32 reference.

    chiprun -- python scripts/decode_micro.py

It is what a kernel PR iterates on before it runs a cell (PERF.md section 6,
PR 30); a cell's ``paged_decode_roofline.offline`` is the number that counts.
Off the chip it runs a tiny shape interpreted, which proves the script and
the error bound and times nothing (``"timed": false``).
"""
import _pathfix  # noqa: F401  (repo-root import shim)
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from lmrs_tpu.ops.paged_attention import paged_decode_pallas_fused
from lmrs_tpu.utils.perf_model import chip_spec

KH, HD, PS, W, ROW_GROUP = 8, 128, 128, 16, 4


def inputs(kind: str, on_chip: bool, seed: int = 0):
    rng = np.random.default_rng(seed)
    b = 24 if on_chip else 4
    layers = 8 if on_chip else 1  # a pool too large for VMEM, as a real one
    h = 16 if kind == "bf16" else 32
    n_pages = 1 + b * W * layers
    bf = jnp.bfloat16
    q = jnp.asarray(rng.standard_normal((b, h, HD)), bf)
    kn = jnp.asarray(rng.standard_normal((b, KH, HD)), bf)
    vn = jnp.asarray(rng.standard_normal((b, KH, HD)), bf)
    tables = jnp.asarray(
        1 + rng.permutation(b * W * layers)[: b * W].reshape(b, W), jnp.int32)
    lens = jnp.asarray(
        rng.integers(*((1600, 1950) if on_chip else (200, 400)), (b,)),
        jnp.int32)
    if kind == "bf16":
        kp = jnp.asarray(rng.standard_normal((n_pages, KH, PS, HD)), bf)
        vp = jnp.asarray(rng.standard_normal((n_pages, KH, PS, HD)), bf)
        sc = {}
    else:
        kp = jnp.asarray(rng.integers(-127, 128, (n_pages, KH, PS, HD)),
                         jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, (n_pages, KH, PS, HD)),
                         jnp.int8)
        sc = dict(
            kscale=jnp.asarray(rng.uniform(0.01, 0.03, (b, KH, HD)),
                               jnp.float32),
            vscale=jnp.asarray(rng.uniform(0.01, 0.03, (b, KH, HD)),
                               jnp.float32))
    return q, kn, vn, kp, vp, tables, lens, sc


def reference(q, kn, vn, kp, vp, tables, lens, sc):
    """Float32 attention over each row's pages with the new token written,
    int8 pages dequantised by the row's scales, at highest precision."""
    f32 = jnp.float32
    b, h, hd = q.shape
    pos = lens - 1
    page = jnp.take_along_axis(tables, (pos // PS)[:, None], 1)[:, 0]
    off = pos % PS
    if sc:
        kn = jnp.clip(jnp.round(kn.astype(f32) / sc["kscale"]),
                      -127, 127).astype(jnp.int8)
        vn = jnp.clip(jnp.round(vn.astype(f32) / sc["vscale"]),
                      -127, 127).astype(jnp.int8)
    kp = kp.at[page, :, off].set(kn)
    vp = vp.at[page, :, off].set(vn)
    k = kp[tables].astype(f32)  # [b, W, KH, PS, hd]
    v = vp[tables].astype(f32)
    if sc:
        k = k * sc["kscale"][:, None, :, None, :]
        v = v * sc["vscale"][:, None, :, None, :]
    k = k.transpose(0, 2, 1, 3, 4).reshape(b, KH, W * PS, hd)
    v = v.transpose(0, 2, 1, 3, 4).reshape(b, KH, W * PS, hd)
    qg = q.astype(f32).reshape(b, KH, h // KH, hd)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bkrd,bktd->bkrt", qg, k) * hd**-0.5
        live = jnp.arange(W * PS)[None, None, None, :] < lens[:, None, None,
                                                              None]
        p = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
        o = jnp.einsum("bkrt,bktd->bkrd", p, v)
    return o.reshape(b, h, hd)


def measure(kind: str, on_chip: bool) -> dict:
    q, kn, vn, kp, vp, tables, lens, sc = inputs(kind, on_chip)
    call = lambda kp, vp: paged_decode_pallas_fused(
        q, kn, vn, kp, vp, tables, lens, row_group=ROW_GROUP,
        interpret=not on_chip, **sc)
    want = np.asarray(reference(q, kn, vn, kp, vp, tables, lens, sc))
    got = np.asarray(call(kp, vp)[0].astype(jnp.float32))
    out = {"kind": kind, "rows": int(q.shape[0]),
           "pages": int(np.ceil(np.asarray(lens) / PS).sum()),
           "max_abs_err_vs_f32": float(np.abs(got - want).max()),
           "timed": on_chip}
    if not on_chip:
        return out
    iters = 200

    @jax.jit
    def chain(kp, vp):
        def body(_, c):
            kp, vp, acc = c
            o, kp, vp = call(kp, vp)
            return kp, vp, acc + o.astype(jnp.float32)
        return jax.lax.fori_loop(
            0, iters, body, (kp, vp, jnp.zeros(q.shape, jnp.float32)))

    t0 = time.perf_counter()
    jax.block_until_ready(chain(kp, vp))
    out["compile_s"] = round(time.perf_counter() - t0, 1)
    times = []
    for _ in range(4):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(kp, vp))
        times.append((time.perf_counter() - t0) / iters)
    best = min(times)
    live_bytes = float(np.asarray(lens).sum()) * 2 * KH * HD * kp.dtype.itemsize
    out.update(call_us=best * 1e6, page_step_us=best * 1e6 / out["pages"],
               hbm_roofline_share=live_bytes / chip_spec().peak_hbm_bw / best,
               spread=(max(times) - best) / best)
    return out


if __name__ == "__main__":
    on_chip = jax.default_backend() == "tpu"
    if on_chip and chip_spec() is None:
        raise SystemExit("no peaks known for "
                         f"{jax.devices()[0].device_kind!r}")
    for kind in ("bf16", "int8"):
        print(json.dumps(
            {"device": jax.devices()[0].device_kind,
             **measure(kind, on_chip)}), flush=True)
