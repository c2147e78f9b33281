"""Ask the TPU's compiler before the chip: the main path's Pallas kernels,
at the widths ``chip_smoke.py`` runs them, must COMPILE for a described
v5e (``jax.experimental.topologies``) — no device attached, nothing runs.

Interpret-mode tests cannot see what Mosaic refuses (a slice off the
tiling, too much VMEM) or what XLA's TPU layout assignment does around a
kernel, and a chip call to find that out costs minutes.  Each compile here
is a second or two.

Rules (on-chip-measurement guide §2): the topology is described inside a
module-scoped, non-autouse fixture of THIS file (only one process may load
the TPU library, and every xdist worker imports every test file), nothing
touches it at import, compiles run in the test's own process with the
persistent compilation cache off (a TPU executable written from here cannot
be read back without a chip).
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parent.parent

# (H, K, hd) — Llama-3-8B and Gemma-2B attention head shapes
LLAMA8B = (32, 8, 128)
GEMMA2B = (8, 1, 256)
SHAPES = [pytest.param(LLAMA8B, id="llama3-8b"),
          pytest.param(GEMMA2B, id="gemma-2b")]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    """Lower + compile ``fn`` for the described chip on abstract args;
    returns (compiled, optimized-HLO text)."""
    args = [None if s is None
            else jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


def test_topology_is_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"
    assert len(topo.devices) == 4


@pytest.mark.parametrize("packed", [False, True], ids=["ragged", "packed"])
@pytest.mark.parametrize("shape", SHAPES)
def test_flash_prefill_compiles(one_chip, shape, packed):
    from lmrs_tpu.ops.flash_attention import flash_attention

    h, k, hd = shape
    s = 4096 if packed else 2048
    bf = jnp.bfloat16

    def fn(q, kk, v, lengths, seg):
        return flash_attention(q, kk, v, lengths, segment_ids=seg)

    _, hlo = _compile(fn, one_chip, ((1, s, h, hd), bf), ((1, s, k, hd), bf),
                      ((1, s, k, hd), bf), ((1,), jnp.int32),
                      ((1, s), jnp.int32) if packed else None)
    assert "tpu_custom_call" in hlo


def _decode_shapes(shape, b, ps, w, pool_dtype, t=None):
    h, k, hd = shape
    n_pages = 1 + b * w
    bf = jnp.bfloat16
    lead = (b,) if t is None else (b, t)
    out = [(lead + (h, hd), bf), (lead + (k, hd), bf), (lead + (k, hd), bf),
           ((n_pages, k, ps, hd), pool_dtype),
           ((n_pages, k, ps, hd), pool_dtype),
           ((b, w), jnp.int32), ((b,), jnp.int32)]
    if pool_dtype == jnp.int8:
        out += [((b, k, hd), jnp.float32), ((b, k, hd), jnp.float32)]
    return out


# (page_size, row_group, pool dtype): the old bench geometry (ps 512 bf16,
# ps 1024 int8, B=24) and the CLI-default geometry chip_smoke.py serves
# (ps 128, int8, row_group 4)
FUSED_CASES = [
    pytest.param(512, 1, jnp.bfloat16, id="bf16-ps512-g1"),
    pytest.param(512, 4, jnp.bfloat16, id="bf16-ps512-g4"),
    pytest.param(1024, 4, jnp.int8, id="int8-ps1024-g4"),
    pytest.param(128, 4, jnp.int8, id="int8-ps128-g4"),
]


# the dense cells' decode kernels as dispatched (BENCHMARK.json: 16/8 and
# 32/8 heads x 128, pages of 128 tokens, 24 rows in groups of 4), on bf16
# and on int8 pages: Mosaic's verdict on the fold's stacked bf16 left side
INTERNLM2, MISTRAL7B = (16, 8, 128), (32, 8, 128)
CELL_CASES = [
    pytest.param(shape, 128, 4, dt, id=f"{sid}-{did}-ps128-g4")
    for shape, sid in [(INTERNLM2, "internlm2"), (MISTRAL7B, "mistral7b")]
    for dt, did in [(jnp.bfloat16, "bf16"), (jnp.int8, "int8")]
    if (shape, dt) != (LLAMA8B, jnp.int8)  # FUSED_CASES' int8-ps128-g4
]


@pytest.mark.parametrize(
    "shape,ps,row_group,pool_dtype",
    [pytest.param(*shape.values, *case.values, id=f"{shape.id}-{case.id}")
     for shape in SHAPES for case in FUSED_CASES] + CELL_CASES)
def test_fused_decode_compiles(one_chip, shape, ps, row_group, pool_dtype):
    from lmrs_tpu.ops.paged_attention import paged_decode_pallas_fused

    quant = pool_dtype == jnp.int8

    def fn(q, kn, vn, kp, vp, tables, lens, ks=None, vs=None):
        return paged_decode_pallas_fused(q, kn, vn, kp, vp, tables, lens,
                                         kscale=ks, vscale=vs,
                                         row_group=row_group)

    _, hlo = _compile(fn, one_chip,
                      *_decode_shapes(shape, 24, ps, 2048 // ps, pool_dtype))
    assert "tpu_custom_call" in hlo
    assert quant == ("s8[" in hlo)


@pytest.mark.parametrize(
    "shape,ps,row_group,pool_dtype",
    [pytest.param(*shape.values, 512, 1, dt, id=f"{shape.id}-{did}")
     for dt, did in [(jnp.bfloat16, "bf16"), (jnp.int8, "int8")]
     for shape in SHAPES] + [
        # the cells' geometry: a verify step's 5 x 8 query rows a kv head,
        # stacked as three bf16 parts (120 rows) on int8 pages
        pytest.param(INTERNLM2, 128, 4, jnp.bfloat16,
                     id="internlm2-bf16-ps128-g4"),
        pytest.param(MISTRAL7B, 128, 4, jnp.int8,
                     id="mistral7b-int8-ps128-g4")])
def test_multi_token_verify_compiles(one_chip, shape, ps, row_group,
                                     pool_dtype):
    from lmrs_tpu.ops.paged_attention import paged_decode_pallas_multi

    def fn(q, kn, vn, kp, vp, tables, lens, ks=None, vs=None):
        return paged_decode_pallas_multi(q, kn, vn, kp, vp, tables, lens,
                                         kscale=ks, vscale=vs,
                                         row_group=row_group)

    _, hlo = _compile(fn, one_chip, *_decode_shapes(
        shape, 24, ps, 2048 // ps, pool_dtype, t=5))
    assert "tpu_custom_call" in hlo


# (heads, kv heads, head_dim), flat tokens, rows, table pages, page size, pool
RAGGED_SPAN_CASES = [
    pytest.param(shape, 512, 24, 2048 // ps, ps, dt, id=f"{sid}-{did}")
    for shape, sid in [(LLAMA8B, "llama3-8b"), (GEMMA2B, "gemma-2b")]
    for ps, dt, did in [(512, jnp.bfloat16, "bf16-ps512"),
                        (512, jnp.int8, "int8-ps512"),
                        (128, jnp.int8, "int8-ps128")]
] + [
    # internlm2-offline's own span program (``rpa:32768:16``) and its int8
    # twin at 32/8 heads: the wide tile's VMEM shows here, not in a window
    pytest.param((16, 8, 128), 32768, 24, 16, 128, jnp.bfloat16,
                 id="internlm2-offline-bf16"),
    pytest.param((32, 8, 128), 32768, 24, 16, 128, jnp.int8,
                 id="cell-shape-32h-int8"),
]


@pytest.mark.parametrize("shape,tp,b,w,ps,pool_dtype", RAGGED_SPAN_CASES)
def test_ragged_spans_compile(one_chip, shape, tp, b, w, ps, pool_dtype):
    from lmrs_tpu.ops.paged_attention import ragged_spans_pallas

    h, k, hd = shape
    bf = jnp.bfloat16
    shapes = [((tp, h, hd), bf), ((tp, k, hd), bf), ((tp, k, hd), bf),
              ((1 + b * w, k, ps, hd), pool_dtype),
              ((1 + b * w, k, ps, hd), pool_dtype),
              ((b, w), jnp.int32), ((b,), jnp.int32), ((b,), jnp.int32),
              ((b,), jnp.int32)]
    if pool_dtype == jnp.int8:
        shapes += [((b, k, hd), jnp.float32), ((b, k, hd), jnp.float32)]

    def fn(q, kn, vn, kp, vp, tables, lens, qs, ql, ks=None, vs=None):
        return ragged_spans_pallas(q, kn, vn, kp, vp, tables, lens, qs, ql,
                                   kscale=ks, vscale=vs, max_pos=w * ps)

    _, hlo = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_pool_write_needs_no_pool_sized_temporary(one_chip, pool_dtype):
    """The XLA pool write (prefill / XLA decode paths) inside a donated
    scan carry, at the Llama-3-8B pool shape of the CLI defaults (32 layers
    x 512 pages): the TPU compiler must not re-lay-out the whole pool.  The
    ``pool.at[page, :, off]`` spelling cost a pool-sized temporary per pool
    here — the prefill program then needed 16.3 GB of a 15.75 GB chip."""
    from lmrs_tpu.ops.paged_attention import scatter_kv_rows

    lp, k, ps, hd, b, s = 32 * 512, 8, 128, 128, 8, 2048

    def prog(pool, page, off, rows):
        def body(carry, li):
            return scatter_kv_rows(carry, page + li, off, rows), None

        return jax.lax.scan(body, pool, jnp.arange(4))[0]

    args = [jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)
            for shp, dt in (((lp, k, ps, hd), pool_dtype),
                            ((b, s), jnp.int32), ((b, s), jnp.int32),
                            ((b, s, k, hd), pool_dtype))]
    compiled = jax.jit(prog, donate_argnums=(0,)).lower(*args).compile()
    ma = compiled.memory_analysis()
    pool_bytes = lp * k * ps * hd * jnp.dtype(pool_dtype).itemsize
    assert ma.alias_size_in_bytes >= pool_bytes  # donated in place
    assert ma.temp_size_in_bytes < pool_bytes // 8, ma.temp_size_in_bytes


# the page-form write of a fresh prefill at the two prefill cells' shapes:
# (layers, pages a layer, kv heads, lanes, rows, bucket, pool type)
_PAGE_WRITES = {
    "mistral-int8": (32, 385, 8, 128, 24, 2048, jnp.int8),
    "latent-bf16": (6, 257, 1, 640, 16, 2048, jnp.bfloat16),
}


def _page_write_program(layers, n_pool):
    from lmrs_tpu.ops.paged_attention import scatter_kv_pages

    def prog(pool, table, rows):
        def body(pool, li):
            # a layer's own rows, as a model's are: nothing to hoist
            return scatter_kv_pages(pool, li * n_pool + table,
                                    rows + li.astype(rows.dtype)), None

        return jax.lax.scan(body, pool, jnp.arange(layers))[0]

    return prog


def _assert_page_write_in_place(compiled, pool_bytes):
    import re

    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= pool_bytes  # donated in place
    assert ma.temp_size_in_bytes < pool_bytes // 8, ma.temp_size_in_bytes
    scatters = [ln for ln in compiled.as_text().splitlines()
                if re.search(r"= \S+ scatter\(", ln)]
    assert scatters, "the page write is no longer an XLA scatter"
    for ln in scatters:
        # the row form's window is one row: the updates' only window
        # dimension is then the last one
        dims = re.search(r"update_window_dims=\{([\d,]*)\}", ln).group(1)
        assert len(dims.split(",")) >= 2, ln


@pytest.mark.parametrize("cell", sorted(_PAGE_WRITES))
def test_page_form_write_stays_in_place_with_a_page_as_its_window(one_chip,
                                                                  cell):
    """``scatter_kv_pages`` over a donated scan carry at the mistral cell's
    pool (32 x 385 pages of [8, 128, 128] int8, a [24 x 2048] wave) and at
    the latent pool's ([1542, 1, 128, 640] bf16, [16 x 2048]): in place, no
    pool-sized temporary, and no scatter whose window is a single row (the
    row form's 393,216 windows of 128 bytes a pool a layer were 18% of the
    mistral cycle; PERF.md section 6, PR 32)."""
    layers, n_pool, kh, lanes, b, s, dt = _PAGE_WRITES[cell]
    ps = 128
    args = [jax.ShapeDtypeStruct(shp, d, sharding=one_chip)
            for shp, d in (((layers * n_pool, kh, ps, lanes), dt),
                           ((b, s // ps), jnp.int32),
                           ((b, s, kh, lanes), dt))]
    compiled = jax.jit(_page_write_program(layers, n_pool),
                       donate_argnums=(0,)).lower(*args).compile()
    pool_bytes = layers * n_pool * kh * ps * lanes * jnp.dtype(dt).itemsize
    _assert_page_write_in_place(compiled, pool_bytes)


@pytest.mark.parametrize("rows", [24, 16], ids=["b24", "b16"])
@pytest.mark.parametrize("axes", [("tp",), ("sp", "tp")],
                         ids=["tp4", "sp2-tp2"])
def test_page_form_write_stays_in_place_under_a_mesh(topo, axes, rows):
    """The same write with the pool kv-head-sharded over ``tp`` (and the
    wave's rows sequence-sharded over ``sp``, as a ring prefill leaves
    them), compiled for the described v5e:2x2: each chip writes its own
    heads' share of a page in place; no cell runs a mesh, so this compile is
    what lets ``forward_paged`` take the page form there too.  ``b16`` is a
    16-prompt wave on the 24 slots: a rung of the prefill programs' row
    ladder below the slots (``scheduler.prefill_row_rung``), whose operand
    no mesh axis divides."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lmrs_tpu.engine.scheduler import prefill_row_rung

    layers, n_pool, kh, lanes, slots, s, _ = _PAGE_WRITES["mistral-int8"]
    b = prefill_row_rung(rows, slots)
    assert b == rows
    ps, dt = 128, jnp.bfloat16
    shape = (4,) if len(axes) == 1 else (2, 2)
    mesh = Mesh(np.array(topo.devices).reshape(shape), axes)
    sh = lambda *spec: NamedSharding(mesh, P(*spec))
    seq = "sp" if "sp" in axes else None
    args = [jax.ShapeDtypeStruct((layers * n_pool, kh, ps, lanes), dt,
                                 sharding=sh(None, "tp")),
            jax.ShapeDtypeStruct((b, s // ps), jnp.int32, sharding=sh()),
            jax.ShapeDtypeStruct((b, s, kh, lanes), dt,
                                 sharding=sh(None, seq, "tp"))]
    compiled = jax.jit(_page_write_program(layers, n_pool),
                       donate_argnums=(0,),
                       out_shardings=sh(None, "tp")).lower(*args).compile()
    tp = mesh.shape["tp"]
    _assert_page_write_in_place(
        compiled, layers * n_pool * kh * ps * lanes * 2 // tp)


def test_flash_prefill_compiles_at_latent_attention_widths(one_chip):
    """MLA's expanded prefill at the published widths: 64 heads, queries
    and keys 192 wide (no multiple of 128), values 128 wide."""
    from lmrs_tpu.ops.flash_attention import flash_attention

    bf = jnp.bfloat16

    def fn(q, kk, v, lengths):
        return flash_attention(q, kk, v, lengths, sm_scale=0.14468)

    compiled, hlo = _compile(
        fn, one_chip, ((2, 2048, 64, 192), bf), ((2, 2048, 64, 192), bf),
        ((2, 2048, 64, 128), bf), ((2,), jnp.int32))
    assert "tpu_custom_call" in hlo
    assert compiled.output_shardings is not None


def test_latent_decode_compiles_and_its_pool_write_stays_in_place(one_chip):
    """The latent decode step's attention at the published widths (64
    heads, latent 512 + 64 rotary lanes in 640, 16 rows of 2k tokens), as
    models/latent.py spells it: the new token's row written by
    ``scatter_kv_rows``, then the kernel reading the pool, layer after
    layer over a donated scan carry.  The kernel must lower (Mosaic), and
    the write beside a reading kernel must not cost a copy of the pool."""
    from lmrs_tpu.ops.mla_attention import mla_paged_decode_pallas
    from lmrs_tpu.ops.paged_attention import scatter_kv_rows

    n_layers, pages, ps, c, b, h, w = 6, 257, 128, 640, 16, 64, 16
    bf = jnp.bfloat16

    def prog(pool, q, rows, tables, lens):
        def body(carry, li):
            pool, acc = carry
            page = (li * pages + tables[:, :1])
            pool = scatter_kv_rows(pool, page, (lens % ps)[:, None],
                                   rows[:, None, None, :])
            o = mla_paged_decode_pallas(q, pool, li * pages + tables, lens,
                                        rank=512, sm_scale=0.14468)
            return (pool, acc + o), None

        (pool, acc), _ = jax.lax.scan(
            body, (pool, jnp.zeros((b, h, 512), bf)), jnp.arange(n_layers))
        return pool, acc

    args = [jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)
            for shp, dt in (((n_layers * pages, 1, ps, c), bf),
                            ((b, h, c), bf), ((b, c), bf),
                            ((b, w), jnp.int32), ((b,), jnp.int32))]
    compiled = jax.jit(prog, donate_argnums=(0,)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    pool_bytes = n_layers * pages * ps * c * 2
    assert ma.alias_size_in_bytes >= pool_bytes  # donated in place
    assert ma.temp_size_in_bytes < pool_bytes // 8, ma.temp_size_in_bytes


# ------------------------------------------------ the new rules, no TPU needed


def test_compile_cache_helper_leaves_config_alone_when_env_set(monkeypatch,
                                                                tmp_path):
    from lmrs_tpu.utils import platform

    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", "sentinel-untouched")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        platform.setup_compile_cache()
        # the env var is JAX's to read; the helper set no directory in code
        assert jax.config.jax_compilation_cache_dir == "sentinel-untouched"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        platform.setup_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(
            ROOT / ".jax_cache")
        assert platform.COMPILE_CACHE_DIR == ROOT / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_on_tpu_is_exact_and_unknown_device_has_no_peaks():
    from lmrs_tpu.utils.perf_model import chip_spec
    from lmrs_tpu.utils.platform import on_tpu

    assert jax.devices()[0].platform == "cpu"
    assert on_tpu() is False
    assert chip_spec() is None


def test_chip_smoke_refuses_a_cpu():
    """``chip_smoke.py`` with JAX held to the CPU: non-zero exit, says it
    found no TPU, never prints ``"ok": true``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=str(ROOT))
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok": true' not in r.stdout
