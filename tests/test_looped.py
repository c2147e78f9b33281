"""Looped layers (one stack run ``n_loops`` times over shared weights, a cache
layer for every (pass, layer), sandwich norms, the final norm closing every
pass): the program against the plain float32 reference of
``benchmarks/families/looped-dense.py``, at a tiny size on the CPU (T 3, L 2,
d 128, 4 heads of 32, seeded weights).

The weights are the family's own bfloat16 draws; the program computes in
float32 here (``ModelConfig.dtype``), so the two sides differ by float32
roundings alone.  ``TOL`` = 2e-5 on logits of unit size.  Reason: both sides
are float32 sums of the same products in different orders over at most
256-long contractions through 6 layer applications, every one of them
re-normed (readings 3.6e-6 to 6.0e-6 over six pairs of weights and text).
int8 pages have their own limits on the decode rows, ``INT8_MAX`` = 0.6 on
the widest error and ``INT8_MEAN`` = 0.04 on the mean: the keys and values a
decode step reads back are rounded to 1/127 of the largest their channel took
in the 96-token prompt, and a later token's larger value is clamped to it
(readings: widest 0.14 to 0.35, mean 0.014 to 0.023; at 32 heads' worth of
32-wide channels this model averages little of it away); the prefill's own
rows read the unquantised keys and values and hold ``TOL``.  Every fault of ``test_a_fault_fails_by_its_factor``
misses ``TOL`` by the factor written beside it.  The chip's comparison at the
published widths is the cell's ``correct`` (PERF.md section 6).
"""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmarks"
for d in (ROOT, BENCH):
    if str(d) not in sys.path:
        sys.path.insert(0, str(d))

TOL = 2e-5
INT8_MAX, INT8_MEAN = 0.6, 0.04
PS, N_PAGES = 16, 16             # page size, logical pages (page 0: null)
N_PRE, N_DEC = 96, 64            # fresh prefill, then decode steps
ROPE_MAX = 256

CONFIG = {
    "family": "looped-dense", "hidden_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 32,
    "intermediate_size": 256, "vocab_size": 512, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False, "total_ut_steps": 3,
    "early_exit_threshold": 1.0,
    "engine": {"weights": "bfloat16", "kv": "bfloat16", "page_size": PS},
}


@pytest.fixture(scope="module")
def fam():
    import families

    family = families.load(CONFIG, "tests/test_looped.py")
    return family, family.sizes(CONFIG)


@pytest.fixture(scope="module")
def model(fam):
    """(ModelConfig in float32, the family's weights as float32)."""
    family, m = fam
    cfg = dataclasses.replace(
        family.model_config("tiny", m, {"max_seq_len": ROPE_MAX}),
        dtype="float32")
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          family.make_params(m, 11))
    return cfg, params


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(3).integers(3, 500, N_PRE + N_DEC + 1).tolist()


@pytest.fixture(scope="module")
def want(fam, model, ids):
    """The reference's full forward over the whole sequence: [S, V]."""
    family, m = fam
    return np.asarray(family.forward_logits(model[1], m, ids))


def _pools(cfg, int8: bool, layers: int | None = None):
    layers = cfg.cache_layers if layers is None else layers
    pool = jnp.zeros((layers * N_PAGES, cfg.n_kv_heads, PS, cfg.hd),
                     jnp.int8 if int8 else jnp.float32)
    scales = None
    if int8:
        one = jnp.ones((layers, 1, cfg.n_kv_heads, cfg.hd), jnp.float32)
        scales = (one, one)
    return pool, pool, scales


TABLE = jnp.arange(1, 13, dtype=jnp.int32)[None]  # 12 pages: 192 tokens


def _decode(cfg, params, ids, kp, vp, scales, start: int):
    """Single-token decode steps from position ``start`` to the end of
    ``ids`` over the pools: logits [len(ids) - start, V]."""
    from lmrs_tpu.models.transformer import forward_paged

    def step(kp, vp, scales, tok, pos):
        out = forward_paged(params, cfg, tok, pos, kp, vp, TABLE,
                            pos[:, 0] + 1, ROPE_MAX, kv_scales=scales)
        return out[0][0, 0], out[1], out[2], (out[3] if scales else None)

    step = jax.jit(step)
    got = []
    for t in range(start, len(ids)):
        lg, kp, vp, scales = step(kp, vp, scales,
                                  jnp.asarray([[ids[t]]], jnp.int32),
                                  jnp.asarray([[t]], jnp.int32))
        got.append(np.asarray(lg))
    return np.stack(got)


def _fresh(cfg, params, ids, n: int, int8: bool = False, layers=None):
    """Fresh prefill of ``ids[:n]`` into new pools: (logits [n, V], pools)."""
    from lmrs_tpu.models.transformer import forward_paged

    kp, vp, scales = _pools(cfg, int8, layers)
    out = forward_paged(params, cfg, jnp.asarray([ids[:n]], jnp.int32),
                        jnp.arange(n)[None], kp, vp, TABLE,
                        jnp.asarray([n]), ROPE_MAX, kv_scales=scales)
    return np.asarray(out[0][0]), out[1], out[2], (out[3] if int8 else None)


def _prefill_then_decode(cfg, params, ids, int8: bool = False, layers=None):
    """Fresh prefill of N_PRE tokens, then every further token a decode
    step through the paged cache: logits at every position, [S, V]."""
    lg, kp, vp, scales = _fresh(cfg, params, ids, N_PRE, int8, layers)
    return np.concatenate(
        [lg, _decode(cfg, params, ids, kp, vp, scales, N_PRE)])


def test_plain_forward_matches_the_reference(model, ids, want):
    """(a) ``forward``, the one tests and training call."""
    from lmrs_tpu.models.transformer import forward

    cfg, params = model
    got = forward(params, cfg, jnp.asarray([ids], jnp.int32),
                  jnp.arange(len(ids))[None])[0][0]
    assert np.abs(np.asarray(got) - want).max() < TOL


@pytest.mark.parametrize("int8", [False, True],
                         ids=["f32-pages", "int8-pages"])
def test_prefill_then_64_decode_steps_through_the_paged_cache(
        model, ids, want, int8):
    """(b) fresh prefill, then 64 single-token decode steps over the pool:
    pass t's layer l reads back what pass t's layer l wrote.  Pages in the
    model's type at ``TOL``; int8 pages at ``INT8_MAX`` / ``INT8_MEAN`` on
    the decode rows and at ``TOL`` on the prefill's own rows, whose
    attention reads the unquantised keys and values."""
    cfg, params = model
    got = _prefill_then_decode(cfg, params, ids, int8)
    assert np.abs(got[:N_PRE] - want[:N_PRE]).max() < TOL
    err = np.abs(got[N_PRE:] - want[N_PRE:])
    if not int8:
        assert err.max() < TOL
    else:  # the pages cost something (this is not the exact path), no more
        assert TOL < err.max() < INT8_MAX and err.mean() < INT8_MEAN


@pytest.mark.parametrize("program", ["span", "window"])
def test_a_prompt_continued_behind_cached_pages(model, ids, want, program):
    """(c) the first 96 tokens sit in pages (a prefix hit's state); the next
    16 tokens of the prompt continue through the span program, or as a
    window chunk; then decode.  Equal to the reference, as (b) is."""
    from lmrs_tpu.models.transformer import forward_paged

    cfg, params = model
    n_more = 16
    _, kp, vp, _ = _fresh(cfg, params, ids, N_PRE)
    chunk = jnp.asarray([ids[N_PRE: N_PRE + n_more]], jnp.int32)
    pos = (N_PRE + jnp.arange(n_more))[None]
    if program == "span":
        out = forward_paged(
            params, cfg, chunk, pos, kp, vp, TABLE, jnp.asarray([N_PRE]),
            ROPE_MAX, spans=(jnp.asarray([0]), jnp.asarray([n_more]),
                             jnp.zeros((n_more,), jnp.int32)))
    else:
        out = forward_paged(
            params, cfg, chunk, pos, kp, vp, TABLE,
            jnp.asarray([N_PRE + n_more]), ROPE_MAX, window_prefill=True)
    rows = slice(N_PRE, N_PRE + n_more)
    assert np.abs(np.asarray(out[0][0]) - want[rows]).max() < TOL
    got = _decode(cfg, params, ids, out[1], out[2], None, N_PRE + n_more)
    assert np.abs(got - want[N_PRE + n_more:]).max() < TOL


def _two_passes(cfg, params, monkeypatch):
    return dataclasses.replace(cfg, n_loops=2), params, None


def _shared_cache_slots(cfg, params, monkeypatch):
    """The pool built ``n_layers`` deep and every pass handed the same cache
    layers: pass t's layer l overwrites what pass t - 1's wrote."""
    from lmrs_tpu.config import ModelConfig
    from lmrs_tpu.models import transformer
    from lmrs_tpu.ops.norms import rms_norm

    def run_stack(params, cfg, carry, layer_fn):
        xs = (params["layers"], jnp.arange(cfg.n_layers))
        for _ in range(cfg.n_loops):
            carry = jax.lax.scan(layer_fn, carry, xs)[0]
            carry = (rms_norm(carry[0], params["final_norm"]["scale"],
                              cfg.norm_eps), *carry[1:])
        return carry

    monkeypatch.setattr(transformer, "run_stack", run_stack)
    monkeypatch.setattr(ModelConfig, "cache_layers",
                        property(lambda self: self.n_layers))
    return cfg, params, cfg.n_layers


def _closing_norm_twice(cfg, params, monkeypatch):
    from lmrs_tpu.models import transformer

    head = transformer.lm_head
    monkeypatch.setattr(transformer, "lm_head", lambda p, c, x: head(
        p, dataclasses.replace(c, n_loops=1), x))
    return cfg, params, None


def _sandwich_weight_skipped(cfg, params, monkeypatch):
    layers = dict(params["layers"])
    layers["ln_attn_out"] = {"scale": jnp.zeros_like(
        layers["ln_attn_out"]["scale"])}
    return cfg, {**params, "layers": layers}, None


@pytest.mark.parametrize("fault, factor", [
    # readings, in units of TOL: 1.4e5, 1.7e5 (its prefill rows are right:
    # a fresh prefill attends its own keys; every decode row is off), 3.3e4,
    # 7.4e4
    (_two_passes, 5e4), (_shared_cache_slots, 5e4),
    (_closing_norm_twice, 1e4), (_sandwich_weight_skipped, 2e4)],
    ids=["n_loops-2-for-3", "cache-slots-shared-between-passes",
         "closing-norm-applied-twice", "a-sandwich-norm-weight-skipped"])
def test_a_fault_fails_by_its_factor(model, ids, want, monkeypatch, fault,
                                     factor):
    """(d) what a bug here looks like: each faulty program, through the same
    prefill and 64 decode steps as (b), misses the reference by at least
    ``factor`` x ``TOL``."""
    cfg, params, layers = fault(*model, monkeypatch)
    got = _prefill_then_decode(cfg, params, ids, layers=layers)
    assert np.abs(got - want).max() > factor * TOL


def _count(jaxpr, name: str) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count(sub, name)
    return n


@pytest.mark.parametrize("s", [64, 1], ids=["prefill", "decode"])
def test_a_dense_model_traces_the_one_layer_scan(s):
    """(e) ``n_loops`` 1 without sandwich norms: ``forward_paged`` on the
    tiny dense preset holds one ``scan`` (the layers') and three norms (two
    a layer inside it, the head's), for a prefill and for a decode trace: the
    branch is static, there is no outer scan of length one."""
    from lmrs_tpu.config import model_preset
    from lmrs_tpu.models.transformer import forward_paged, init_params

    cfg = model_preset("bench-smoke")
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    pool = jax.ShapeDtypeStruct(
        (cfg.n_layers * N_PAGES, cfg.n_kv_heads, PS, cfg.hd), jnp.bfloat16)
    pos = jnp.arange(s)[None] + (0 if s > 1 else 40)
    jaxpr = jax.make_jaxpr(lambda p, kp, vp: forward_paged(
        p, cfg, jnp.zeros((1, s), jnp.int32), pos, kp, vp, TABLE,
        pos[:, -1] + 1, ROPE_MAX))(params, pool, pool).jaxpr
    assert _count(jaxpr, "scan") == 1
    assert _count(jaxpr, "rsqrt") + _count(jaxpr, "sqrt") == 3
    looped = model_preset("tiny-looped")
    params = jax.eval_shape(lambda: init_params(looped, jax.random.PRNGKey(0)))
    pool = jax.ShapeDtypeStruct(
        (looped.cache_layers * N_PAGES, looped.n_kv_heads, PS, looped.hd),
        jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda p, kp, vp: forward_paged(
        p, looped, jnp.zeros((1, s), jnp.int32), pos, kp, vp, TABLE,
        pos[:, -1] + 1, ROPE_MAX))(params, pool, pool).jaxpr
    # the passes' scan around the layers', four norms a layer, the closing
    # norm inside the outer scan and none after it
    assert _count(jaxpr, "scan") == 2
    assert _count(jaxpr, "rsqrt") + _count(jaxpr, "sqrt") == 5


# ------------------------------------------------- through the scheduler


def _engine(cfg, params, **over):
    from lmrs_tpu.config import EngineConfig
    from lmrs_tpu.engine.jax_engine import JaxEngine

    mesh_cfg = over.pop("mesh_cfg", None)
    kw = dict(backend="jax", scheduler="continuous", max_tokens=8,
              max_batch_slots=2, page_size=PS, num_pages=1, decode_block=4,
              prefix_cache=False, host_kv=False, retry_attempts=1, seed=0)
    kw.update(over)
    return JaxEngine(EngineConfig(**kw), cfg, mesh_cfg, params=params)


def _requests(n: int = 3):
    from lmrs_tpu.engine.api import GenerationRequest

    preamble = "the quarterly review covered the kernel design " * 3
    return [GenerationRequest(prompt=preamble + f"item {i} " * (i + 1),
                              request_id=i, max_new_tokens=6, temperature=0.0,
                              cache_prefix=len(preamble))
            for i in range(n)]


def _greedy(cfg, params, sched, req, n_tokens: int) -> list[int]:
    """The plain forward's greedy continuation of the prompt as the
    scheduler spells it ((a) holds the plain forward to the reference)."""
    from lmrs_tpu.models.transformer import forward

    ids, _ = sched._encode(req)
    n_prompt = len(ids)
    plain = jax.jit(lambda t, n: forward(
        params, cfg, t, jnp.arange(ROPE_MAX)[None], kv_length=n)[0][0])
    for _ in range(n_tokens):
        padded = jnp.asarray([ids + [0] * (ROPE_MAX - len(ids))], jnp.int32)
        logits = plain(padded, jnp.asarray([len(ids)]))
        ids.append(int(np.argmax(np.asarray(logits[len(ids) - 1]))))
    return ids[n_prompt:]


def test_scheduler_pages_prefix_cache_and_tiers_are_per_logical_page(
        model, monkeypatch):
    """JaxEngine / ContinuousScheduler / PagedKVCache with the prefix cache
    and the host tier on: the pool is ``cache_layers`` deep, the page
    accounting per logical page.  A second wave hits the cached preamble and
    continues behind it (the window-chunk program here: the span program
    takes its place where the kernels arm, (c) holds both); after the cached
    pages are spilled to the host tier a third wave brings them back.  Every wave's tokens are
    the plain forward's greedy ones, and the dispatch records count layer
    applications (``cache_layers`` a prefill or span step, x ``decode_block``
    a decode block)."""
    monkeypatch.setenv("LMRS_WATCHDOG", "0")
    cfg, params = model
    eng = _engine(cfg, params, prefix_cache=True, host_kv=True,
                  host_kv_gb=0.01)
    sched = eng._scheduler
    assert cfg.cache_layers == 6 and sched.cache.n_layers == 6
    assert sched.cache.k.shape[0] == 6 * sched.cache.num_pages
    reqs = _requests()
    tok = eng.tokenizer
    want = None
    for wave in range(3):
        res = eng.generate_batch(reqs)
        texts = [r.text for r in res]
        if want is None:
            want = [tok.decode(_greedy(cfg, params, sched, q,
                                       r.completion_tokens))
                    for q, r in zip(reqs, res)]
            assert all(r.completion_tokens > 0 for r in res)
        assert texts == want, wave
        if wave == 1:
            assert sched.metrics["prefix_hits"] > 0
            sched._prefix_cache.evict(10_000)  # spill: device -> host tier
    met = sched.metrics
    assert met["prefix_tokens_reused"] > 0
    progs = sched.anatomy_report()["programs"]
    assert {"prefill_chunk", "decode"} <= set(progs)
    for name, rec in progs.items():
        steps = sched.decode_block if name == "decode" else 1
        assert rec["layer_passes"] == rec["dispatches"] * 6 * steps, name
    assert met["layer_passes"] == sum(r["layer_passes"]
                                      for r in progs.values())
    assert sched.audit() == [] and sched._prefix_cache.audit() == []
    eng.shutdown()


def test_int8_pages_through_the_scheduler(model, monkeypatch):
    """int8 pools and their scale buffers are ``cache_layers`` deep; greedy
    tokens through fresh prefill and decode blocks are the plain forward's
    wherever its best two logits lie further apart than ``INT8_MAX``."""
    from lmrs_tpu.models.transformer import forward

    monkeypatch.setenv("LMRS_WATCHDOG", "0")
    cfg, params = model
    eng = _engine(cfg, params, kv_quantize="int8", max_tokens=4, page_size=32)
    sched = eng._scheduler
    assert sched.cache.k.dtype == jnp.int8
    assert sched.kscale.shape == (6, 2, cfg.n_kv_heads, cfg.hd)
    reqs = _requests(2)
    res = eng.generate_batch(reqs)
    for q, r in zip(reqs, res):
        assert r.error is None and r.completion_tokens > 0
        ids, _ = sched._encode(q)
        served = _greedy(cfg, params, sched, q, 1)
        logits = np.asarray(forward(
            params, cfg, jnp.asarray([ids], jnp.int32),
            jnp.arange(len(ids))[None])[0][0, -1])
        top2 = np.sort(logits)[-2:]
        if top2[1] - top2[0] > INT8_MAX:  # the first token comes from prefill
            assert r.text.startswith(eng.tokenizer.decode(served))
    assert sched.audit() == []
    eng.shutdown()


def test_page_export_and_import_carry_every_cache_layer(model):
    """The transferable unit of handoff, migration and the tiers reads its
    depth from the pool: 6 layers' copies of each logical page travel."""
    from lmrs_tpu.engine.kv_cache import PagedKVCache

    cfg, _ = model
    src = PagedKVCache(cfg, num_pages=8, page_size=PS, max_pages_per_slot=4)
    seq = src.open_sequence(40)  # 3 pages, the last one partial
    phys = jnp.asarray(src._phys_ids(seq.pages))
    assert len(phys) == 6 * 3
    rng = np.random.default_rng(5)
    fill = rng.standard_normal((len(phys),) + src.k.shape[1:])
    src.k = src.k.at[phys].set(jnp.asarray(fill, src.k.dtype))
    src.v = src.v.at[phys].set(jnp.asarray(-fill, src.v.dtype))
    payload = src.export_sequence(seq, 40)
    assert payload["n_layers"] == 6 and payload["k"].shape[:2] == (6, 3)
    dst = PagedKVCache(cfg, num_pages=12, page_size=PS, max_pages_per_slot=4)
    held = dst.alloc_pages(2)
    seq2 = dst.import_sequence(payload)
    got = np.asarray(dst.k[jnp.asarray(dst._phys_ids(seq2.pages))])
    np.testing.assert_array_equal(got, np.asarray(fill, got.dtype))
    assert set(seq2.pages).isdisjoint(held)
    dense = PagedKVCache(dataclasses.replace(cfg, n_loops=1), num_pages=12,
                         page_size=PS, max_pages_per_slot=4)
    with pytest.raises(ValueError):  # 2 layers deep: not this payload's pool
        dense.import_sequence(payload)


def test_tp2_mesh_gives_the_one_device_tokens(model, monkeypatch):
    """(f) the looped stack under a ``tp`` 2 CPU mesh: the four norm leaves
    replicated, kv heads and pages sharded; greedy tokens equal to one
    device's."""
    from lmrs_tpu.config import MeshConfig

    monkeypatch.setenv("LMRS_WATCHDOG", "0")
    cfg, params = model
    reqs = _requests(2)
    one = _engine(cfg, params)
    want = [r.text for r in one.generate_batch(reqs)]
    one.shutdown()
    eng = _engine(cfg, params, mesh_cfg=MeshConfig(tp=2))
    wq = eng.params["layers"]["attn"]["wq"]
    assert wq.sharding.shard_shape(wq.shape)[2] == cfg.n_heads // 2
    norm = eng.params["layers"]["ln_attn_out"]["scale"]
    assert norm.sharding.shard_shape(norm.shape) == norm.shape
    assert [r.text for r in eng.generate_batch(reqs)] == want
    eng.shutdown()


# ------------------------------------------------------ refused, by name


def test_what_is_not_built_is_refused_by_name(fam):
    from lmrs_tpu.config import (LoopedModelConfig, MeshConfig,
                                 model_preset)
    from lmrs_tpu.models.loader import convert_hf_llama
    from lmrs_tpu.parallel.mesh import build_mesh
    from lmrs_tpu.parallel.pipeline import pipeline_causal_lm_loss

    family, _ = fam
    with pytest.raises(ValueError, match="early_exit_threshold=0.5"):
        LoopedModelConfig(n_loops=4, early_exit_threshold=0.5)
    with pytest.raises(SystemExit, match="early_exit_threshold is 0.5"):
        family.sizes({**CONFIG, "early_exit_threshold": 0.5})
    with pytest.raises(ValueError, match="n_loops=0"):
        LoopedModelConfig(n_loops=0)
    looped = model_preset("tiny-looped")
    with pytest.raises(ValueError, match="pipeline stages over a looped"):
        pipeline_causal_lm_loss({}, looped, jnp.zeros((4, 8), jnp.int32),
                                build_mesh(MeshConfig(pp=2)))
    with pytest.raises(NotImplementedError, match="four-norm tree"):
        convert_hf_llama("/nonexistent", looped)


# ------------------------------------------------------------ the counts


@pytest.mark.parametrize("which", ["tiny", "published"])
def test_family_counts_and_perf_model_agree(fam, which):
    """``utils/perf_model.py`` follows ``n_loops`` as the family's counts do:
    a token multiplies, and a decode step reads (``StepBytes``, counted from
    the family's tree and a pool), every layer once a pass and the head
    once; K and V are held for every (pass, layer); the weight TREE holds
    each layer once."""
    import json

    from lmrs_tpu.engine.jax_engine import _bf16_tree_gb
    from lmrs_tpu.utils import perf_model

    family, m = fam
    if which == "published":
        config = json.loads(
            (BENCH / "configs" / "ouro-2.6b.json").read_text())
        m = family.sizes(config)
        assert (m["n_loops"], m["n_layers"], m["dim"]) == (4, 48, 2048)
        # the issue's arithmetic: 19.9 GB a decode step, 0.75 MiB a token
        assert abs(family.weight_stream_bytes(m) / 1e9 - 19.93) < 0.01
        assert family.kv_bytes_per_token(m) == 786432
    cfg = family.model_config("x", m, {"max_seq_len": 2048})
    assert cfg.cache_layers == m["n_layers"] * m["n_loops"]
    assert perf_model.matmul_params(cfg) == family.matmul_params(m)
    for n, head, start in ((1900, 1, 0), (256, None, 0), (128, 1, 1024)):
        assert perf_model.prefill_flops(cfg, n, head, start) == \
            family.prefill_flops(m, n, head, start)
    # the engine's byte count, from the family's tree and pool as held: a
    # position's K and V in every cache layer, the matmul weights every
    # pass plus the norms (four a layer application, the final one a step)
    shapes = family.param_shapes(m)
    pool = jax.ShapeDtypeStruct(
        (cfg.cache_layers * 3, m["n_kv_heads"], 16, cfg.hd),
        jnp.int8 if m["kv"] == "int8" else jnp.bfloat16)
    sb = perf_model.StepBytes.held(cfg, shapes, pool, pool)
    assert sb.position * cfg.cache_layers == family.kv_bytes_per_token(m)
    norms = (4 * cfg.cache_layers + 1) * m["dim"] * 2
    step = sb.block([1999], 1, cfg.cache_layers)
    assert step == family.decode_step_bytes(m, 2000) + norms
    assert step - 2000 * cfg.cache_layers * sb.position == \
        family.weight_stream_bytes(m) + norms
    # what the tree holds: every layer once, the embedding and the head
    projections = sum(
        x.size for path, x in jax.tree_util.tree_leaves_with_path(shapes)
        if x.ndim > 2 or "lm_head" in jax.tree_util.keystr(path)
        or "embed" in jax.tree_util.keystr(path))
    assert perf_model.stored_matmul_params(cfg) + m["dim"] * m["vocab_size"] \
        == projections
    assert _bf16_tree_gb(cfg) == projections * 2 / 1e9
    if which == "published":
        assert abs(_bf16_tree_gb(cfg) - 5.33) < 0.01


def test_decode_stream_roofline_reads_the_dispatch_records(fam):
    """The new reader: bytes from the decode records (``layer_passes``,
    ``ctx_tokens``) over the decode program's device seconds; None where
    the records lack the counter (the parent of this PR) or the family its
    byte counts."""
    import run as bench

    family, m = fam
    read = bench.load_module(
        BENCH / "layer_metrics" / "decode_stream_roofline.offline.py",
        "test_metric_decode_stream").read
    rec = {"dispatches": 2, "layer_passes": 2 * 6 * 128, "ctx_tokens": 9000}
    facts = {"model": m, "flops": family,
             "peaks": {"hbm_bytes_per_s": 819e9},
             "trace": {"programs": {"jit_decode": 0.5, "jit_prefill": 9.0}},
             "anatomy": {"programs": {"decode": rec}}}
    steps = 256
    nbytes = (steps * family.weight_stream_bytes(m)
              + 9000 / 2 * steps * family.kv_bytes_per_token(m))
    assert read(facts) == pytest.approx(100 * nbytes / 819e9 / 0.5, rel=1e-12)
    old = {**facts, "anatomy": {"programs": {"decode": {
        k: v for k, v in rec.items() if k != "layer_passes"}}}}
    assert read(old) is None
    assert read({**facts, "anatomy": {"enabled": False}}) is None
    assert read({**facts, "trace": None}) is None
    dense = bench.families.load({}, "a file without a family")
    assert read({**facts, "flops": dense}) is None
