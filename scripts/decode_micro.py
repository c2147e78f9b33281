"""The fused decode kernel alone, at the cells' shapes, on one chip.

Calls ``paged_decode_pallas_fused`` directly (head dim 128, 128-token pages,
13-15 live pages a row, ``row_group`` 4) over a pool too large for VMEM, 200
calls chained in one jitted ``fori_loop``, best of four, at three shapes:

* ``bf16``: internlm2-1.8b's, 24 rows, 16 query / 8 kv heads, bf16 pages;
* ``int8``: mistral-7b's, 24 rows, 32 query / 8 kv heads, int8 pages;
* ``int8_kh16``: ouro-2.6b's, 4 rows, 16 query / 16 kv heads, int8 pages
  (a page is 512 KB, as a bf16 page at 8 heads), so the int8 fold's bound
  can be read at both head counts.

Each shape runs at the DMA ring depths ``--depths`` (default 2, 3 and 4; the
kernel's own is ``paged_attention._RING``) and in the arms ``--arms``:
``fold``, the kernel as it is, and ``dma``, the same walk with the page fold
taken out (its DMAs, their waits and the loop alone), which says what the
pipeline costs a page whatever the fold does.  The probe sets the depth and
the arm on the module for its own calls and puts them back; the program
never changes them.  Prints one JSON line a (shape, depth, arm):
microseconds a call and a page step, the share of the HBM roofline the live
K/V bytes reach, and for the ``fold`` arm the largest absolute error against
a float32 reference.

    python scripts/decode_micro.py [--shapes bf16,int8] [--depths 2,3]
                                   [--arms fold,dma]

It is what a kernel change iterates on before it runs a cell (PERF.md
section 6); a cell's ``paged_decode_roofline.offline`` is the number that
counts.  Off the chip it runs tiny shapes interpreted, which proves the
script and the error bound and times nothing (``"timed": false``).
"""
import _pathfix  # noqa: F401  (repo-root import shim)
import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from lmrs_tpu.ops import paged_attention
from lmrs_tpu.ops.paged_attention import paged_decode_pallas_fused
from lmrs_tpu.utils.perf_model import chip_spec

HD, PS, W, ROW_GROUP = 128, 128, 16, 4
# shape -> (rows on the chip, query heads, kv heads, pool type)
SHAPES = {"bf16": (24, 16, 8, "bf16"), "int8": (24, 32, 8, "int8"),
          "int8_kh16": (4, 16, 16, "int8")}


def inputs(shape: str, on_chip: bool, seed: int = 0):
    rng = np.random.default_rng(seed)
    rows, h, kh, pool = SHAPES[shape]
    b = rows if on_chip else 4
    layers = 8 if on_chip else 1  # a pool too large for VMEM, as a real one
    n_pages = 1 + b * W * layers
    bf = jnp.bfloat16
    q = jnp.asarray(rng.standard_normal((b, h, HD)), bf)
    kn = jnp.asarray(rng.standard_normal((b, kh, HD)), bf)
    vn = jnp.asarray(rng.standard_normal((b, kh, HD)), bf)
    tables = jnp.asarray(
        1 + rng.permutation(b * W * layers)[: b * W].reshape(b, W), jnp.int32)
    # off the chip 2-5 pages a row: walks of three pages and more run the
    # ring's steady state
    lens = jnp.asarray(
        rng.integers(*((1600, 1950) if on_chip else (200, 600)), (b,)),
        jnp.int32)
    if pool == "bf16":
        kp = jnp.asarray(rng.standard_normal((n_pages, kh, PS, HD)), bf)
        vp = jnp.asarray(rng.standard_normal((n_pages, kh, PS, HD)), bf)
        sc = {}
    else:
        kp = jnp.asarray(rng.integers(-127, 128, (n_pages, kh, PS, HD)),
                         jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, (n_pages, kh, PS, HD)),
                         jnp.int8)
        sc = dict(
            kscale=jnp.asarray(rng.uniform(0.01, 0.03, (b, kh, HD)),
                               jnp.float32),
            vscale=jnp.asarray(rng.uniform(0.01, 0.03, (b, kh, HD)),
                               jnp.float32))
    return q, kn, vn, kp, vp, tables, lens, sc


def reference(q, kn, vn, kp, vp, tables, lens, sc):
    """Float32 attention over each row's pages with the new token written,
    int8 pages dequantised by the row's scales, at highest precision."""
    f32 = jnp.float32
    b, h, hd = q.shape
    kh = kp.shape[1]
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
    k = kp[tables].astype(f32)  # [b, W, kh, PS, hd]
    v = vp[tables].astype(f32)
    if sc:
        k = k * sc["kscale"][:, None, :, None, :]
        v = v * sc["vscale"][:, None, :, None, :]
    k = k.transpose(0, 2, 1, 3, 4).reshape(b, kh, W * PS, hd)
    v = v.transpose(0, 2, 1, 3, 4).reshape(b, kh, W * PS, hd)
    qg = q.astype(f32).reshape(b, kh, h // kh, hd)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bkrd,bktd->bkrt", qg, k) * hd**-0.5
        live = jnp.arange(W * PS)[None, None, None, :] < lens[:, None, None,
                                                              None]
        p = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
        o = jnp.einsum("bkrt,bktd->bkrd", p, v)
    return o.reshape(b, h, hd)


def _no_fold(*_args, **_kw):
    """The ``dma`` arm's page fold: nothing (the output is zeros)."""


def measure(shape: str, depth: int, arm: str, on_chip: bool) -> dict:
    saved = paged_attention._RING, paged_attention._fold_page
    paged_attention._RING = depth
    if arm == "dma":
        paged_attention._fold_page = _no_fold
    # the kernel is jitted: a cached trace would keep the last depth / arm
    jax.clear_caches()
    try:
        return _measure(shape, depth, arm, on_chip)
    finally:
        paged_attention._RING, paged_attention._fold_page = saved
        jax.clear_caches()


def _measure(shape: str, depth: int, arm: str, on_chip: bool) -> dict:
    q, kn, vn, kp, vp, tables, lens, sc = inputs(shape, on_chip)
    call = lambda kp, vp: paged_decode_pallas_fused(
        q, kn, vn, kp, vp, tables, lens, row_group=ROW_GROUP,
        interpret=not on_chip, **sc)
    out = {"shape": shape, "depth": depth, "arm": arm,
           "rows": int(q.shape[0]),
           "pages": int(np.ceil(np.asarray(lens) / PS).sum()),
           "timed": on_chip}
    if arm == "fold":
        want = np.asarray(reference(q, kn, vn, kp, vp, tables, lens, sc))
        got = np.asarray(call(kp, vp)[0].astype(jnp.float32))
        out["max_abs_err_vs_f32"] = float(np.abs(got - want).max())
    if not on_chip:
        if arm == "dma":
            call(kp, vp)  # the walk without its fold still runs
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
    kh = kp.shape[1]
    live_bytes = (float(np.asarray(lens).sum()) * 2 * kh * HD
                  * kp.dtype.itemsize)
    out.update(call_us=best * 1e6, page_step_us=best * 1e6 / out["pages"],
               hbm_roofline_share=live_bytes / chip_spec().peak_hbm_bw / best,
               spread=(max(times) - best) / best)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--depths", default="2,3,4")
    ap.add_argument("--arms", default="fold,dma")
    args = ap.parse_args()
    on_chip = jax.default_backend() == "tpu"
    if on_chip and chip_spec() is None:
        raise SystemExit("no peaks known for "
                         f"{jax.devices()[0].device_kind!r}")
    for shape in args.shapes.split(","):
        for depth in (int(d) for d in args.depths.split(",")):
            for arm in args.arms.split(","):
                print(json.dumps(
                    {"device": jax.devices()[0].device_kind,
                     **measure(shape, depth, arm, on_chip)}), flush=True)


if __name__ == "__main__":
    main()
