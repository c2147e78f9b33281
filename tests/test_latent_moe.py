"""Latent attention (MLA) over routed experts: the program against the plain
float32 reference of ``benchmarks/families/mla-moe.py``, at a tiny size on
the CPU, kernels interpreted.

The weights are the family's own bfloat16 draws; the program computes in
float32 here (``ModelConfig.dtype``), so that the two sides differ by
float32 roundings alone and the tolerance can be tight: ``TOL`` = 2e-4 on
logits of unit size.  Reason: both sides are float32 sums of the same
products in different orders over at most 256-long contractions through 3
layers (readings: 1e-6 to 3e-5); int8 weights (the reference's own control,
``mode="int8"``) miss it by two orders (readings 2e-2 to 9e-2), which
``test_int8_weights_fail_the_tolerance`` holds.  The chip's comparison at
the published widths is the cell's ``correct`` (PERF.md section 6).
"""

from __future__ import annotations

import dataclasses
import json
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

TOL = 2e-4
SEQ = 48


@pytest.fixture(scope="module")
def fam():
    import families

    config = json.loads(
        (BENCH / "configs" / "tiny-mla-moe-rehearsal.json").read_text())
    family = families.load(config, "tiny-mla-moe-rehearsal.json")
    return family, family.sizes(config)


@pytest.fixture(scope="module")
def model(fam):
    """(ModelConfig in float32, the family's weights as float32)."""
    family, m = fam
    cfg = dataclasses.replace(
        family.model_config("tiny", m, {"max_seq_len": 256}), dtype="float32")
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          family.make_params(m, 11))
    return cfg, params


def _ids(seed: int, n: int = SEQ) -> list[int]:
    return np.random.default_rng(seed).integers(3, 500, n).tolist()


def _reference(fam, params, ids, mode=None):
    """[n - 1, V]: row j predicts ids[j + 1]."""
    family, m = fam
    return np.asarray(family.logits_for(params, m, ids[:1], ids[1:],
                                        mode=mode))


def _plain(cfg, params, ids, **kw):
    from lmrs_tpu.models import latent

    tokens = jnp.asarray([ids], jnp.int32)
    pos = jnp.arange(len(ids))[None]
    return np.asarray(latent.forward(params, cfg, tokens, pos, **kw)[0][0])


def test_plain_forward_matches_the_reference(fam, model):
    cfg, params = model
    ids = _ids(1)
    got = _plain(cfg, params, ids)[:-1]
    want = _reference(fam, params, ids)
    assert np.abs(got - want).max() < TOL


def test_int8_weights_fail_the_tolerance(fam, model):
    cfg, params = model
    ids = _ids(1)
    want = _reference(fam, params, ids)
    low = _reference(fam, params, ids, mode="int8")
    assert np.abs(low - want).max() > 20 * TOL


def test_absorbed_form_equals_expanded_form(model):
    """The same 40 tokens through the paged forward twice: all at once (a
    fresh prefill: the latent expanded into per-head keys and values), and
    39 then the last alone (a decode step: the absorbed form over the
    latent pool).  The last position's logits agree."""
    from lmrs_tpu.models.transformer import forward_paged

    cfg, params = model
    ids = _ids(2, 40)
    table = jnp.asarray([[1, 2, 3]], jnp.int32)

    def pool():
        return jnp.zeros((cfg.n_layers * 4, 1, 16, cfg.latent_width),
                         jnp.float32)

    def run(pool, toks, first):
        n = len(toks)
        return forward_paged(
            params, cfg, jnp.asarray([toks], jnp.int32),
            first + jnp.arange(n)[None], pool, None, table,
            jnp.asarray([first + n]), 256, use_flash=False)[:2]

    expanded, _ = run(pool(), ids, 0)
    _, filled = run(pool(), ids[:-1], 0)
    absorbed, _ = run(filled, ids[-1:], 39)
    assert np.abs(np.asarray(expanded[0, -1])
                  - np.asarray(absorbed[0, 0])).max() < TOL


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
def test_prefill_then_decode_through_the_latent_pool(fam, model, kernel):
    """Fresh prefill of 32 tokens into pages, then 15 single-token decode
    steps over the pool (the absorbed form; the Pallas kernel interpreted,
    or its gather twin): every step's logits against the reference's full
    forward over the whole sequence."""
    from lmrs_tpu.models.transformer import forward_paged

    cfg, params = model
    ids = _ids(3)
    want = _reference(fam, params, ids)
    ps, n_pre = 16, 32
    n_pages = 8
    pool = jnp.zeros((cfg.n_layers * n_pages, 1, ps, cfg.latent_width),
                     jnp.float32)
    table = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    out = forward_paged(
        params, cfg, jnp.asarray([ids[:n_pre]], jnp.int32),
        jnp.arange(n_pre)[None], pool, None, table, jnp.asarray([n_pre]),
        256, use_flash=False)
    logits, pool = out[0], out[1]
    assert out[2] is None  # no V pool beside the latent pool
    got = [np.asarray(logits[0, n_pre - 1])]
    rows = [n_pre - 1]
    step = jax.jit(lambda pool, tok, pos: forward_paged(
        params, cfg, tok, pos, pool, None, table, pos[:, 0] + 1, 256,
        use_ragged_kernel=kernel, interpret=kernel)[:2])
    for t in range(n_pre, len(ids) - 1):
        logits, pool = step(pool, jnp.asarray([[ids[t]]], jnp.int32),
                            jnp.asarray([[t]], jnp.int32))
        got.append(np.asarray(logits[0, 0]))
        rows.append(t)
    assert np.abs(np.stack(got) - want[rows]).max() < TOL


def test_latent_fresh_prefill_writes_whole_pages_and_decodes_the_same(
        model, monkeypatch):
    """The latent pool's fresh prefill through the page-form write (two
    rows over 32 positions of 16-token pages, one row short of its bucket
    with its last column 0) and four decode steps: logits bit for bit what
    the row form gives (``whole_pages`` held at 0); 40 positions (no
    multiple of the page) take the row form by themselves."""
    from lmrs_tpu.models.transformer import forward_paged
    from lmrs_tpu.ops import paged_attention

    cfg, params = model
    ps, n_pages = 16, 8
    tokens = jnp.asarray([_ids(5, 32), _ids(6, 32)], jnp.int32)
    table = jnp.asarray([[1, 2, 3], [4, 0, 0]], jnp.int32)
    lens = jnp.asarray([32, 11], jnp.int32)
    alloc = jnp.asarray([3 * ps, ps], jnp.int32)
    calls = []
    writer = paged_attention.scatter_kv_pages
    monkeypatch.setattr(paged_attention, "scatter_kv_pages",
                        lambda *a: calls.append(a[2].shape[1]) or writer(*a))

    def run(n_pre=32):
        pool = jnp.zeros((cfg.n_layers * n_pages, 1, ps, cfg.latent_width),
                         jnp.float32)
        pos = jnp.minimum(jnp.arange(n_pre)[None], alloc[:, None] - 1)
        out = forward_paged(params, cfg, tokens[:, :n_pre], pos, pool, None,
                            table, lens, 256, use_flash=False,
                            last_pos=lens - 1)
        got, n = [np.asarray(out[0][:, 0])], lens
        for _ in range(4):
            tok = jnp.asarray(np.argmax(got[-1], -1)[:, None], jnp.int32)
            out = forward_paged(params, cfg, tok, n[:, None], out[1], None,
                                table, n + 1, 256)
            got.append(np.asarray(out[0][:, 0]))
            n = n + 1
        return np.stack(got)

    pages = run()
    assert calls and set(calls) == {32}  # every layer's write, whole pages
    del calls[:]
    monkeypatch.setattr(paged_attention, "whole_pages", lambda *a: 0)
    rows = run()
    assert not calls
    np.testing.assert_array_equal(pages, rows)
    monkeypatch.undo()
    monkeypatch.setattr(paged_attention, "scatter_kv_pages",
                        lambda *a: calls.append(a[2].shape[1]) or writer(*a))
    forward_paged(params, cfg, jnp.asarray([_ids(7, 40)], jnp.int32),
                  jnp.arange(40)[None], jnp.zeros(
                      (cfg.n_layers * n_pages, 1, ps, cfg.latent_width),
                      jnp.float32), None, table[:1], jnp.asarray([40]), 256,
                  use_flash=False)
    assert not calls  # 40 is no multiple of 16: a row at a time


def test_yarn_table_matches_a_float64_formula(model):
    """The program's rope tables against the published formula worked in
    float64 here: inverse frequencies blended by the linear ramp between
    the correction dimensions, angle = position x frequency."""
    import math

    from lmrs_tpu.models.latent import rope_tables, softmax_scale

    cfg, _ = model
    for c in (cfg, dataclasses.replace(
            cfg, qk_rope_head_dim=64, rope_theta=50000.0, rope_factor=64.0,
            rope_orig_max_pos=4096, qk_nope_head_dim=128)):
        d, half = c.qk_rope_head_dim, c.qk_rope_head_dim // 2
        i = np.arange(half, dtype=np.float64)
        plain = c.rope_theta ** (-2 * i / d)

        def dim_of(rot):
            return (d * math.log(c.rope_orig_max_pos / (rot * 2 * math.pi))
                    / (2 * math.log(c.rope_theta)))

        low = max(math.floor(dim_of(c.rope_beta_fast)), 0)
        high = min(math.ceil(dim_of(c.rope_beta_slow)), d - 1)
        ramp = np.clip((i - low) / (high - low), 0, 1)
        inv = plain / c.rope_factor * ramp + plain * (1 - ramp)
        pos = np.arange(200, dtype=np.float64)
        sin, cos = rope_tables(c, 200)
        # float32 angles up to 200 rad: 200 x 2^-24 = 1.2e-5 of phase
        assert np.abs(np.asarray(sin) - np.sin(pos[:, None] * inv)).max() < 5e-5
        assert np.abs(np.asarray(cos) - np.cos(pos[:, None] * inv)).max() < 5e-5
    assert abs(softmax_scale(c) - 0.14468) < 1e-5  # the published settings


def _routed_layer_params(params, layer: int = 0):
    return jax.tree.map(lambda x: x[layer], params["layers"])["moe"]


def _reference_routed(fam_mod, m, mp, h, first, held):
    """The reference's routed part (no shared expert) for a share."""
    gates, close = fam_mod.route(h, mp["router"], mp["bias"], m["top_k"],
                                 m["route_scale"], first, held)
    g = jnp.einsum("sd,edf->esf", h, mp["w_gate"], precision="highest")
    u = jnp.einsum("sd,edf->esf", h, mp["w_up"], precision="highest")
    y = jnp.einsum("esf,efd->esd", jax.nn.silu(g) * u, mp["w_down"],
                   precision="highest")
    return jnp.einsum("se,esd->sd", gates, y, precision="highest"), close


def test_every_token_on_the_held_experts_is_computed_not_dropped(fam, model):
    """A selection bias that sends every token to all four held experts:
    4 pairs a token land here where 1 is expected, the first pass is full
    and the ``while_loop`` runs; the result is the reference's, no pair
    dropped."""
    from lmrs_tpu.ops.moe import routed_experts, routed_first_pass_rows

    family, m = fam
    cfg, params = model
    mp = _routed_layer_params(params)
    mp = dict(mp, bias=mp["bias"].at[cfg.expert_first:
                                     cfg.expert_first + cfg.experts_held].set(9.0))
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 40, cfg.dim))
    out, stats = jax.jit(lambda mp, h: routed_experts(mp, cfg, h))(mp, h)
    want, _ = _reference_routed(family, m, mp, h.reshape(80, -1),
                                cfg.expert_first, cfg.experts_held)
    assert np.abs(np.asarray(out).reshape(80, -1) - np.asarray(want)).max() < TOL
    held = cfg.experts_held
    assert stats[:held].tolist() == [80] * held  # every token, every expert
    assert 80 * held > routed_first_pass_rows(80, cfg)
    assert int(stats[held + 1]) >= 1  # passes beyond the first


def test_the_shares_add_up_to_the_uncut_layer(fam, model):
    """Four chips hold experts 0-3, 4-7, 8-11, 12-15 of the 16: the routed
    parts that the program computes for the four shares, with the shared
    expert counted once, are the reference's uncut layer (all 16 held)."""
    from lmrs_tpu.models.blocks import swiglu as _swiglu
    from lmrs_tpu.ops.moe import routed_experts

    family, m = fam
    cfg, params = model
    lp = jax.tree.map(lambda x: x[1], params["layers"])
    key = jax.random.split(jax.random.PRNGKey(8), 4)
    full = {"router": lp["moe"]["router"], "bias": lp["moe"]["bias"]}
    for i, name in enumerate(("w_gate", "w_up", "w_down")):
        shape = (16, *lp["moe"][name].shape[1:])
        full[name] = jax.random.normal(key[i], shape) / 8
    h = jax.random.normal(key[3], (1, 64, cfg.dim))
    total = _swiglu(lp["shared"], cfg, h)[0]
    for first in (0, 4, 8, 12):
        share = dataclasses.replace(cfg, expert_first=first, n_experts_held=4)
        mp = {**full, **{n: full[n][first:first + 4]
                         for n in ("w_gate", "w_up", "w_down")}}
        total = total + routed_experts(mp, share, h)[0][0]
    want, _ = _reference_routed(family, m, full, h[0], 0, 16)
    g = jnp.einsum("sd,df->sf", h[0], lp["shared"]["w_gate"],
                   precision="highest")
    u = jnp.einsum("sd,df->sf", h[0], lp["shared"]["w_up"],
                   precision="highest")
    want = want + jnp.einsum("sf,fd->sd", jax.nn.silu(g) * u,
                             lp["shared"]["w_down"], precision="highest")
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < TOL


def test_a_swap_between_two_absent_experts_is_under_a_rounding(fam):
    """The family compares a token whose 8th and 9th choices are close when
    neither is held here.  The two routes then differ only in the sum that
    normalises the held experts' weights: at the published router (384
    experts, top-8, 12 held, unit-size logits) that moves every held
    weight by under a bfloat16 rounding (2^-8) of itself, even with the
    9th placed the whole margin under the 8th."""
    family, _ = fam
    rng = np.random.default_rng(0)
    k, held = 8, 12
    sc = 1.0 / (1.0 + np.exp(-rng.standard_normal((512, 384))))
    order = np.argsort(-(sc + 0.02 * rng.standard_normal(384)), axis=1)
    edge = order[:, k - 1:k + 1]
    rows = np.flatnonzero((edge >= held).all(axis=1))
    assert len(rows) > 400  # most tokens: the boundary is rarely ours
    worst = 0.0
    for r in rows:
        picked = sc[r, order[r, :k]]
        s8 = picked[k - 1]
        other = picked.copy()
        other[k - 1] = s8 - family.ROUTE_MARGIN * s8 * (1 - s8)
        worst = max(worst, abs(other.sum() / picked.sum() - 1))
    assert worst < 2.0 ** -8


def _engine(cfg, params, **over):
    from lmrs_tpu.config import EngineConfig
    from lmrs_tpu.engine.jax_engine import JaxEngine

    kw = dict(backend="jax", model="tiny", temperature=0.0, max_tokens=6,
              max_batch_slots=2, page_size=16, num_pages=1, decode_block=3,
              prefix_cache=False, host_kv=False, retry_attempts=1, seed=0)
    kw.update(over)
    return JaxEngine(EngineConfig(**kw), cfg, params=params)


def test_greedy_tokens_through_the_scheduler_equal_the_plain_forward(
        model, monkeypatch):
    """JaxEngine / ContinuousScheduler / PagedKVCache, the decode kernel
    interpreted: fresh prefill, then decode blocks over the latent pool;
    the tokens are the plain forward's greedy ones, and the held experts'
    counts come back with them."""
    from lmrs_tpu.engine.api import GenerationRequest

    monkeypatch.setenv("LMRS_FORCE_KERNELS", "interpret")
    monkeypatch.setenv("LMRS_WATCHDOG", "0")
    cfg, params = model
    eng = _engine(cfg, params)
    sched = eng._scheduler
    assert sched._use_ragged and sched.cache.v is None
    reqs = [GenerationRequest(prompt="the quarterly review " * (i + 1),
                              request_id=i, max_new_tokens=6, temperature=0.0)
            for i in range(3)]
    res = eng.generate_batch(reqs)
    from lmrs_tpu.models import latent

    tok = eng.tokenizer
    plain = jax.jit(lambda t, n: latent.forward(
        params, cfg, t, jnp.arange(96)[None], kv_length=n)[0][0])
    for r, q in zip(res, reqs):
        ids, _ = sched._encode(q)  # the prompt as the scheduler spells it
        n_prompt = len(ids)
        for _ in range(r.completion_tokens):
            padded = jnp.asarray([ids + [0] * (96 - len(ids))], jnp.int32)
            logits = plain(padded, jnp.asarray([len(ids)]))
            ids.append(int(np.argmax(np.asarray(logits[len(ids) - 1]))))
        assert r.text == tok.decode(ids[n_prompt:])
        assert r.prompt_tokens == n_prompt and r.completion_tokens > 0
    met = sched.metrics
    assert met["moe_routed_pairs"] > 0 and met["moe_extra_passes"] == 0
    assert met["moe_expert_tokens_max"] >= met["moe_expert_tokens_mean"] > 0
    progs = sched.anatomy_report()["programs"]
    assert set(progs) == {"prefill", "decode"}
    assert (progs["prefill"]["moe_routed_pairs"]
            + progs["decode"]["moe_routed_pairs"] == met["moe_routed_pairs"])
    assert sched.audit() == []
    eng.shutdown()


@pytest.mark.parametrize("over, named", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"kv_quantize": "int8"}, "kv_quantize"),
    ({"quantize": "int8"}, "quantize"),
    ({"speculate_k": 2}, "speculate_k"),
    ({"mesh": {"tp": 2}}, "tp > 1"),
    ({"mesh": {"sp": 2}}, "sp > 1"),
])
def test_what_is_not_built_is_refused_by_name_at_engine_start(
        model, monkeypatch, over, named):
    """JaxEngine builds its ContinuousScheduler at start, and that is where
    a latent cache refuses what it cannot serve."""
    from lmrs_tpu.config import EngineConfig, MeshConfig
    from lmrs_tpu.engine.scheduler import ContinuousScheduler
    from lmrs_tpu.parallel.mesh import build_mesh

    monkeypatch.setenv("LMRS_WATCHDOG", "0")
    cfg, params = model
    over = dict(over)
    mesh = over.pop("mesh", None)
    kw = dict(backend="jax", prefix_cache=False, host_kv=False, page_size=16)
    with pytest.raises(ValueError, match=named):
        ContinuousScheduler(
            EngineConfig(**{**kw, **over}), cfg, params, None,
            mesh=build_mesh(MeshConfig(**mesh)) if mesh else None)


def test_flash_kernel_takes_values_narrower_than_queries_and_keys():
    """The prefill kernel at MLA's two widths (queries and keys 192,
    values 128) and its own softmax scale, interpreted, against the XLA
    attention: float32 in, the same sums in blocks (1e-5)."""
    from lmrs_tpu.ops.attention import attention
    from lmrs_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (2, 256, 2, 192))
    k = jax.random.normal(ks[1], (2, 256, 2, 192))
    v = jax.random.normal(ks[2], (2, 256, 2, 128))
    lens = jnp.asarray([256, 200])
    got = flash_attention(q, k, v, lens, q_block=128, kv_block=128,
                          interpret=True, sm_scale=0.14468)
    want = attention(q, k, v, jnp.broadcast_to(jnp.arange(256), (2, 256)),
                     lens, scale=0.14468)
    assert got.shape == (2, 256, 2, 128)
    valid = (jnp.arange(256)[None] < lens[:, None])[..., None, None]
    assert float(jnp.abs(jnp.where(valid, got - want, 0)).max()) < 1e-5


def test_page_export_for_spill_or_handoff_is_refused_by_name(model):
    from lmrs_tpu.engine.kv_cache import PagedKVCache

    cfg, _ = model
    cache = PagedKVCache(cfg, 8, 16, 4)
    assert cache.v is None and cache.k.shape == (3 * 8, 1, 16, 256)
    with pytest.raises(NotImplementedError, match="latent KV cache"):
        cache.export_pages([1])
