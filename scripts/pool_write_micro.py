"""The prefill's pool write alone, at the two prefill cells' shapes, on one chip.

A fresh prefill wave writes its K (and V, or its latent rows) into the
page-major pool once a layer.  This times that write by itself, a layer
after a layer over a donated scan carry as ``forward_paged`` runs it:

* ``mistral``: a ``[24 x 2048]`` wave of int8 rows, 8 kv heads x 128, into
  one pool of 32 layers x 385 pages (``mistral7b-offline``; K and V are two
  such writes);
* ``latent``: a ``[16 x 2048]`` wave of bf16 rows of 640 lanes into the one
  latent pool of 6 layers x 257 pages (``kimi-k2.6-offline``).

Spellings: ``rows`` is ``scatter_kv_rows`` (a window of one ``hd`` row),
``pages`` is ``scatter_kv_pages`` (the one kept: a window of one page);
``dus`` (a ``fori_loop`` of ``dynamic_update_slice``, a page a step) and
``dma`` (a Pallas writer, one ``[K, ps, hd]`` DMA a page into the aliased
pool) are the alternatives PR 32 weighed against it and live only here.
Prints one JSON line a shape and spelling: milliseconds a pool a layer, best
of four, and whether every position below each row's length holds the same
bytes as the row form leaves.

    chiprun -- python scripts/pool_write_micro.py

Off the chip it runs a tiny shape (the Pallas writer interpreted), which
proves the script and the parity and times nothing (``"timed": false``).
"""
import _pathfix  # noqa: F401  (repo-root import shim)
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lmrs_tpu.ops.paged_attention import scatter_kv_pages, scatter_kv_rows

PS = 128
# name: (layers, pages a layer, kv heads, lanes, rows, bucket, dtype)
SHAPES = {
    "mistral": (32, 385, 8, 128, 24, 2048, jnp.int8),
    "latent": (6, 257, 1, 640, 16, 2048, jnp.bfloat16),
}
TINY = {
    "mistral": (2, 13, 2, 128, 3, 256, jnp.int8),
    "latent": (2, 9, 1, 128, 2, 256, jnp.bfloat16),
}


def _tiles(rows):
    b, s, kh, hd = rows.shape
    n = s // PS
    return rows.reshape(b, n, PS, kh, hd).transpose(0, 1, 3, 2, 4).reshape(
        b * n, kh, PS, hd)


def write_rows(pool, pages, rows, interpret=False):
    b, s = rows.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    page = jnp.take_along_axis(pages, pos // PS, axis=1)
    return scatter_kv_rows(pool, page, pos % PS, rows)


def write_pages(pool, pages, rows, interpret=False):
    return scatter_kv_pages(pool, pages, rows)


def write_dus(pool, pages, rows, interpret=False):
    tiles, flat = _tiles(rows), pages.reshape(-1)

    def body(i, pool):
        return jax.lax.dynamic_update_slice(
            pool, jax.lax.dynamic_slice_in_dim(tiles, i, 1), (flat[i], 0, 0, 0))

    return jax.lax.fori_loop(0, flat.shape[0], body, pool)


def write_dma(pool, pages, rows, interpret=False):
    tiles, flat = _tiles(rows), pages.reshape(-1)
    _, kh, ps, hd = pool.shape

    def kernel(pages_ref, tile_ref, pool_in, pool_out, sem):
        del pool_in  # aliased to pool_out
        cp = pltpu.make_async_copy(
            tile_ref.at[0], pool_out.at[pages_ref[pl.program_id(0)]], sem)
        cp.start()
        cp.wait()

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(flat.shape[0],),
            in_specs=[pl.BlockSpec((1, kh, ps, hd), lambda i, _: (i, 0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0}, interpret=interpret,
    )(flat, tiles, pool)


SPELLINGS = {"rows": write_rows, "pages": write_pages, "dus": write_dus,
             "dma": write_dma}


def inputs(name: str, on_chip: bool, seed: int = 0):
    layers, n_pool, kh, hd, b, s, dt = (SHAPES if on_chip else TINY)[name]
    rng = np.random.default_rng(seed)
    n = s // PS
    # the wave as the scheduler sends it: most rows whole, one short of its
    # bucket (columns past its allocation 0), one pad row (table all 0)
    lens = rng.integers(s - 2 * PS, s - PS // 2, (b,))
    lens[-1] = 1
    table = np.zeros((b, n), np.int32)
    free = 1 + rng.permutation(n_pool - 1)
    at = 0
    for r in range(b - 1):
        need = -(-int(lens[r]) // PS)
        table[r, :need] = free[at: at + need]
        at += need
    if jnp.issubdtype(dt, jnp.integer):
        rows = jnp.asarray(rng.integers(-127, 128, (b, s, kh, hd)), dt)
    else:
        rows = jnp.asarray(rng.standard_normal((b, s, kh, hd)), dt)
    pool = jnp.zeros((layers * n_pool, kh, PS, hd), dt)
    return pool, jnp.asarray(table), rows, lens, layers, n_pool


def program(write, layers: int, n_pool: int, interpret: bool):
    """``layers`` writes over a donated scan carry; each layer's rows differ
    (as a model's do), so no layer's transposing copy can be hoisted."""

    def prog(pool, table, rows):
        def body(pool, li):
            r = rows + li.astype(rows.dtype)
            return write(pool, li * n_pool + table, r, interpret), None

        return jax.lax.scan(body, pool, jnp.arange(layers))[0]

    return jax.jit(prog, donate_argnums=(0,))


def live(pool, table, lens, layers, n_pool):
    """Every live page of every layer, gathered on the device, with the
    positions at and past each row's length zeroed; pad rows left out (their
    one token sits on the null page, under every other pad write)."""
    real = table.any(axis=1)
    ids = (np.arange(layers)[:, None, None] * n_pool + table[real][None])
    below = (np.arange(table.shape[1] * PS)[None] < lens[real][:, None])
    below = below.reshape(1, int(real.sum()), table.shape[1], 1, PS, 1)
    return jnp.where(jnp.asarray(below), pool[jnp.asarray(ids)], 0)


def measure(name: str, on_chip: bool) -> list[dict]:
    results, want = [], None
    for spelling, write in SPELLINGS.items():
        pool, table, rows, lens, layers, n_pool = inputs(name, on_chip)
        fn = program(write, layers, n_pool, interpret=not on_chip)
        t0 = time.perf_counter()
        pool = jax.block_until_ready(fn(pool, table, rows))
        out = {"shape": name, "spelling": spelling,
               "pool": list(pool.shape), "wave": list(rows.shape[:2]),
               "compile_s": round(time.perf_counter() - t0, 1),
               "timed": on_chip}
        got = live(pool, np.asarray(table), lens, layers, n_pool)
        if want is None:
            want = got  # the row form, measured first, is the reference
        out["equal_below_lengths"] = bool(jnp.array_equal(got, want))
        if on_chip:
            times = []
            for _ in range(4):
                t0 = time.perf_counter()
                pool = jax.block_until_ready(fn(pool, table, rows))
                times.append((time.perf_counter() - t0) / layers)
            best = min(times)
            out.update(ms_a_pool_a_layer=best * 1e3,
                       bytes_ms_at_819=rows.nbytes / 819e9 * 1e3,
                       spread=(max(times) - best) / best)
        results.append(out)
        del pool
    return results


if __name__ == "__main__":
    on_chip = jax.default_backend() == "tpu"
    for name in SHAPES:
        for line in measure(name, on_chip):
            print(json.dumps({"device": jax.devices()[0].device_kind, **line}),
                  flush=True)
