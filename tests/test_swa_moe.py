"""Window and full GQA attention over routed experts (models/windowed.py):
the program against the plain float32 reference of
``benchmarks/families/gqa-swa-moe.py``, the two-kind page pool, the windowed
kernels and what the engine refuses, at a tiny size on the CPU.

The weights are the family's own bfloat16 draws; the program computes in
float32 here (``ModelConfig.dtype``), so that the two sides differ by
float32 roundings alone and the tolerance can be tight: ``TOL`` = 2e-4 on
logits of unit size (both sides are float32 sums of the same products in
different orders through 5 layers, each re-normed; readings 1e-6 to 4e-5);
int8 weights (the reference's own control) miss it by two orders, which
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
WINDOW, PAGE = 16, 8


def _config(**over) -> dict:
    """The rehearsal configuration with every mechanism at test size: window
    16 (over pages of 8), three window layers to one full behind a dense
    leading layer, QK-norm, no rope on the full layer, output norms, 8
    routed experts top-2 (4 held) with a shared one."""
    config = json.loads(
        (BENCH / "configs" / "tiny-swa-moe-rehearsal.json").read_text())
    config.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, intermediate_size=96, moe_intermediate_size=32,
        num_experts=4, num_experts_per_tok=2, sliding_window=WINDOW,
        sliding_windows=[WINDOW, WINDOW, WINDOW, 0, WINDOW],
        published={"num_hidden_layers": 5, "num_experts": 8,
                   "vocab_size": 512},
        share={"expert_first": 4},
        calibration={"text": 64, "walks": 4, "walk_steps": 8})
    config.update(over)
    return config


def _load(config):
    import families

    family = families.load(config, "test")
    m = family.sizes(config)
    cfg = dataclasses.replace(
        family.model_config("tiny", m, {"max_seq_len": 512}), dtype="float32")
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          family.make_params(m, 11))
    return family, m, cfg, params


@pytest.fixture(scope="module")
def model():
    return _load(_config())


@pytest.fixture(scope="module")
def model_hd128():
    """The same stack with two query heads of 128 on one KV head, so that
    the Pallas kernels arm (interpreted)."""
    return _load(_config(num_attention_heads=2, num_key_value_heads=1,
                         head_dim=128))


def _ids(seed: int, n: int) -> list[int]:
    return np.random.default_rng(seed).integers(3, 500, n).tolist()


def _reference(model, ids, mode=None):
    """[n - 1, V]: row j predicts ids[j + 1]."""
    family, m, _, params = model
    return np.asarray(family.logits_for(params, m, ids[:1], ids[1:],
                                        mode=mode))


# ---- (i) the plain forward against the reference ---------------------------

def test_plain_forward_matches_the_reference(model):
    from lmrs_tpu.models.transformer import forward

    _, _, cfg, params = model
    assert cfg.layer_windows == (WINDOW, WINDOW, WINDOW, 0, WINDOW)
    ids = _ids(1, 72)  # several windows long
    got = np.asarray(forward(params, cfg, jnp.asarray([ids], jnp.int32),
                             jnp.arange(len(ids))[None])[0][0])[:-1]
    assert np.abs(got - _reference(model, ids)).max() < TOL


def test_int8_weights_fail_the_tolerance(model):
    ids = _ids(1, 72)
    low = _reference(model, ids, mode="int8")
    assert np.abs(low - _reference(model, ids)).max() > 20 * TOL


@pytest.mark.parametrize("switch", ["qk_norm", "rope_window_only",
                                    "sandwich_norm"])
def test_each_block_switch_is_in_the_equations(model, switch):
    """Flipping any one of the family's static switches moves the logits
    off the reference by far more than the tolerance (``norm_inputs`` adds
    leaves the tree lacks, so the output norms stand for A1)."""
    from lmrs_tpu.models.transformer import forward

    _, _, cfg, params = model
    ids = _ids(4, 40)
    flipped = dataclasses.replace(cfg, **{switch: not getattr(cfg, switch)})
    got = np.asarray(forward(params, flipped, jnp.asarray([ids], jnp.int32),
                             jnp.arange(len(ids))[None])[0][0])[:-1]
    assert np.abs(got - _reference(model, ids)).max() > 100 * TOL


# ---- (ii) prefill, then decode, through the two-kind cache ------------------

def _pool(cfg, slots, num_pages, page=PAGE):
    from lmrs_tpu.models.windowed import pool_layout

    lay = pool_layout(cfg, page, slots, num_pages)
    return lay, jnp.zeros((lay["total"], cfg.n_kv_heads, page, cfg.hd),
                          jnp.float32)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
def test_prefill_then_decode_through_the_two_kind_cache(
        model, model_hd128, kernel):
    """A fresh prefill of 72 tokens (4.5 windows, 9 pages) into the pool's
    two kinds of layer, then 30 decode steps that cross three page
    boundaries of the window layers' rings (the Pallas walk interpreted, or
    its gather twin): every step's logits against the reference's full
    forward over the whole sequence."""
    from lmrs_tpu.models.transformer import forward_paged

    mdl = model_hd128 if kernel else model
    _, _, cfg, params = mdl
    ids = _ids(3, 103)
    want = _reference(mdl, ids)
    n_pre, slots, width = 72, 2, 16
    lay, kp = _pool(cfg, slots, 1 + slots * width)
    assert lay["ring"] == 3
    vp = kp
    table = np.zeros((slots, width), np.int32)
    table[1] = 1 + np.arange(width)  # the sequence sits in slot 1
    table = jnp.asarray(table)
    tokens = np.zeros((slots, n_pre), np.int32)
    tokens[1] = ids[:n_pre]
    lens = jnp.asarray([0, n_pre], jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(n_pre)[None], (slots, n_pre))
    out = forward_paged(params, cfg, jnp.asarray(tokens), pos, kp, vp, table,
                        lens, 512, window_slots=slots, use_flash=False,
                        last_pos=lens - 1, token_valid=pos < lens[:, None])
    got, rows = [np.asarray(out[0][1, 0])], [n_pre - 1]
    kp, vp = out[1], out[2]
    step = jax.jit(lambda kp, vp, tok, n: forward_paged(
        params, cfg, tok, n[:, None], kp, vp, table,
        jnp.where(n > 0, n + 1, 0), 512, window_slots=slots,
        use_ragged_kernel=kernel, interpret=kernel,
        token_valid=(n > 0)[:, None])[:3])
    for t in range(n_pre, len(ids) - 1):
        logits, kp, vp = step(kp, vp, jnp.asarray([[0], [ids[t]]], jnp.int32),
                              jnp.asarray([0, t], jnp.int32))
        got.append(np.asarray(logits[1, 0]))
        rows.append(t)
    assert np.abs(np.stack(got) - want[rows]).max() < TOL


def test_fresh_prefill_through_the_banded_flash_kernel(model_hd128):
    """256 tokens through ``forward_paged`` with the flash kernels armed
    (interpreted): the ``lax.cond`` takes the banded kernel on the window
    layers and the full one on the full layer; the last row's logits are
    the reference's."""
    from lmrs_tpu.models.transformer import forward_paged

    _, _, cfg, params = model_hd128
    ids = _ids(6, 256)
    _, kp = _pool(cfg, 1, 33)
    table = jnp.asarray(1 + np.arange(32)[None], jnp.int32)
    out = forward_paged(
        params, cfg, jnp.asarray([ids], jnp.int32), jnp.arange(256)[None],
        kp, kp, table, jnp.asarray([250]), 512, window_slots=1,
        use_flash=True, interpret=True, last_pos=jnp.asarray([249]))
    want = _reference(model_hd128, ids[:250] + [3])[249]
    assert np.abs(np.asarray(out[0][0, 0]) - want).max() < TOL


# ---- (iii) the cache's bound -------------------------------------------------

def test_a_window_layers_pages_stay_bounded_while_the_sequence_grows(model):
    """A sequence prefilled at 24 tokens and decoded to 240 (tenfold): the
    pages of each window layer's region that were ever written number at
    most ceil(16 / 8) + 1 = 3 (its slot's ring), while the full layer has
    written ceil(240 / 8) = 30."""
    from lmrs_tpu.models.transformer import forward_paged

    _, _, cfg, params = model
    slots, width, n_pre, n_end = 2, 32, 24, 240
    lay, kp = _pool(cfg, slots, 1 + slots * width)
    vp = kp
    table = np.zeros((slots, width), np.int32)
    table[0] = 1 + np.arange(width)
    table = jnp.asarray(table)
    ids = _ids(9, n_end)
    tokens = np.zeros((slots, n_pre), np.int32)
    tokens[0] = ids[:n_pre]
    lens = jnp.asarray([n_pre, 0], jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(n_pre)[None], (slots, n_pre))
    out = forward_paged(params, cfg, jnp.asarray(tokens), pos, kp, vp, table,
                        lens, 512, window_slots=slots, use_flash=False,
                        token_valid=pos < lens[:, None])
    kp, vp = out[1], out[2]
    touched = np.zeros((lay["total"],), bool)

    def note(pool):
        nonlocal touched
        touched |= np.asarray(jnp.abs(pool).sum((1, 2, 3)) > 0)

    note(kp)
    step = jax.jit(lambda kp, vp, tok, n: forward_paged(
        params, cfg, tok, n[:, None], kp, vp, table,
        jnp.where(n > 0, n + 1, 0), 512, window_slots=slots,
        token_valid=(n > 0)[:, None])[1:3])
    for t in range(n_pre, n_end):
        kp, vp = step(kp, vp, jnp.asarray([[ids[t]], [0]], jnp.int32),
                      jnp.asarray([t, 0], jnp.int32))
        if t % 8 == 0:
            note(kp)
    note(kp)
    ring = -(-WINDOW // PAGE) + 1
    for li, w in enumerate(cfg.layer_windows):
        first = int(lay["base"][li])
        n = lay["win_pages"] if w else 1 + slots * width
        written = int(touched[first + 1:first + n].sum())  # null page aside
        assert written == (ring if w else n_end // PAGE), (li, written)


def test_the_pools_two_shares(model):
    from lmrs_tpu.engine.kv_cache import PagedKVCache

    _, _, cfg, _ = model
    cache = PagedKVCache(cfg, num_pages=41, page_size=PAGE,
                         max_pages_per_slot=10, slots=4)
    # 1 full layer x 40 pages; 4 window layers x 4 slots x a ring of 3
    assert cache.kind_pages() == (40, 4 * 4 * 3)
    assert cache.k.shape[0] == 41 + 4 * (4 * 3 + 1)
    with pytest.raises(NotImplementedError, match="window KV cache"):
        cache.export_pages([1])
    with pytest.raises(NotImplementedError, match="window KV cache"):
        cache.export_sequence(None, 8)


# ---- (iv) the kernels against ops/attention.attention ------------------------

@pytest.mark.parametrize("seq,window,lens", [
    (256, 300, [256, 7]),        # below the window: plain causal
    (384, 128, [128, 384]),      # at it, and three windows long
    (1024, 128, [1024, 640]),    # a band of 2 tiles of 256 out of 4
    (384, 16, [100, 384]),       # tiles of 128, far wider than the window
])
def test_flash_with_a_window_matches_attention(seq, window, lens):
    from lmrs_tpu.ops.attention import attention
    from lmrs_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(seq + window), 3)
    q = jax.random.normal(ks[0], (2, seq, 4, 128), jnp.float32)
    k = jax.random.normal(ks[1], (2, seq, 2, 128), jnp.float32)
    v = jax.random.normal(ks[2], (2, seq, 2, 128), jnp.float32)
    n = jnp.asarray(lens, jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(seq)[None], (2, seq))
    want = attention(q, k, v, pos, n, window=window)
    got = flash_attention(q, k, v, n, interpret=True, window=window)
    valid = (jnp.arange(seq)[None, :] < n[:, None])[:, :, None, None]
    assert float(jnp.abs(jnp.where(valid, got - want, 0)).max()) < 2e-5


@pytest.mark.parametrize("row_group", [1, 4])
@pytest.mark.parametrize("window", [16, 5, 64, 0])
def test_the_decode_walk_with_a_window_matches_attention(window, row_group):
    """Rows of 3, 16, 17, 40 and 64 tokens (below, at and above the window)
    over pages of 8: the fused kernel (interpreted) and its XLA twin against
    ``attention`` under the same mask, and the new token's row written where
    the table says."""
    from lmrs_tpu.ops.attention import attention
    from lmrs_tpu.ops.paged_attention import (paged_decode_pallas_fused,
                                              paged_decode_xla)

    rng = np.random.default_rng(window)
    b, h, kh, hd, ps, width = 5, 4, 2, 128, 8, 8
    lens = np.array([3, 16, 17, 40, 64], np.int32)  # the new token included
    kp = rng.standard_normal((1 + b * width, kh, ps, hd)).astype(np.float32)
    vp = rng.standard_normal(kp.shape).astype(np.float32)
    table = (1 + np.arange(b * width).reshape(b, width)).astype(np.int32)
    q = rng.standard_normal((b, h, hd)).astype(np.float32)
    kn = rng.standard_normal((b, kh, hd)).astype(np.float32)
    vn = rng.standard_normal((b, kh, hd)).astype(np.float32)
    kw, vw = kp.copy(), vp.copy()
    for i in range(b):
        at = lens[i] - 1
        kw[table[i, at // ps], :, at % ps] = kn[i]
        vw[table[i, at // ps], :, at % ps] = vn[i]

    def flat(pool):
        return jnp.asarray(pool[table].transpose(0, 1, 3, 2, 4).reshape(
            b, width * ps, kh, hd))

    want = attention(jnp.asarray(q)[:, None], flat(kw), flat(vw),
                     jnp.asarray(lens - 1)[:, None], jnp.asarray(lens),
                     window=window)[:, 0]
    got, kp2, vp2 = paged_decode_pallas_fused(
        *(jnp.asarray(a) for a in (q, kn, vn, kp, vp, table, lens)),
        interpret=True, row_group=row_group, window=jnp.int32(window))
    twin = paged_decode_xla(jnp.asarray(q), jnp.asarray(kw), jnp.asarray(vw),
                            jnp.asarray(table), jnp.asarray(lens),
                            window=jnp.int32(window))
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert float(jnp.abs(twin - want).max()) < 2e-5
    np.testing.assert_array_equal(np.asarray(kp2)[1:], kw[1:])  # null page aside
    np.testing.assert_array_equal(np.asarray(vp2)[1:], vw[1:])


def test_the_windowed_walk_starts_at_the_windows_first_page():
    from lmrs_tpu.ops.paged_attention import window_walk

    table = jnp.asarray([[10, 11, 12, 13, 14, 15, 16, 17]], jnp.int32)
    t, n, lo = window_walk(table, jnp.asarray([45]), jnp.int32(16), 8)
    # positions 29..44 are seen: the walk starts at page 3 (positions 24-31)
    assert t[0, :3].tolist() == [13, 14, 15]
    assert (int(n[0]), int(lo[0])) == (45 - 24, 29 - 24)
    t, n, lo = window_walk(table, jnp.asarray([45]), jnp.int32(0), 8)
    assert (t[0].tolist(), int(n[0]), int(lo[0])) == (table[0].tolist(), 45, 0)


# ---- (v) the share ties to the model -----------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(model):
    """Two chips hold experts 0-3 and 4-7 of the 8: the routed parts that
    the program computes for the two shares, with the shared expert counted
    once, are the reference's uncut layer (all 8 held), as the reference's
    own ``feed`` computes it (un-normed input, output norm)."""
    from lmrs_tpu.models.blocks import swiglu
    from lmrs_tpu.ops.moe import routed_experts

    family, m, cfg, params = model
    lp = jax.tree.map(lambda x: x[1], params["layers"])
    key = jax.random.split(jax.random.PRNGKey(8), 4)
    full = {"router": lp["moe"]["router"], "bias": lp["moe"]["bias"]}
    for i, name in enumerate(("w_gate", "w_up", "w_down")):
        full[name] = jax.random.normal(
            key[i], (8, *lp["moe"][name].shape[1:])) / 8
    h = jax.random.normal(key[3], (1, 64, cfg.dim))
    total = swiglu(lp["shared"], cfg, h)[0]
    for first in (0, 4):
        share = dataclasses.replace(cfg, expert_first=first, n_experts_held=4)
        mp = {**full, **{n: full[n][first:first + 4]
                         for n in ("w_gate", "w_up", "w_down")}}
        total = total + routed_experts(mp, share, h)[0][0]
    uncut = {**m, "expert_first": 0, "n_held": 8}
    _, feed = family._block(family._dims(uncut), None)
    ones = {"scale": jnp.zeros((cfg.dim,))}
    want, _ = feed(h[0], {"moe": full, "shared": lp["shared"],
                          "ln_mlp_out": ones})
    # feed adds the residual and norms the sum of the parts (scale 0: x /
    # rms): take both off
    rms = jnp.sqrt(jnp.mean(jnp.square(total), -1, keepdims=True)
                   + cfg.norm_eps)
    assert np.abs(np.asarray(total / rms)
                  - np.asarray(want - h[0])).max() < TOL


# ---- (vi) what the engine refuses --------------------------------------------

def _engine_cfg(**over):
    from lmrs_tpu.config import EngineConfig

    kw = dict(backend="jax", model="tiny-swa-moe", temperature=0.0,
              max_tokens=8, max_batch_slots=2, page_size=PAGE, num_pages=1,
              prefill_chunk=256, decode_block=8, prefix_cache=False,
              host_kv=False, tokenizer="byte", retry_attempts=1)
    kw.update(over)
    return EngineConfig(**kw)


@pytest.mark.parametrize("over,message", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"kv_quantize": "int8"}, "kv_quantize"),
    ({"quantize": "int8"}, "quantize"),
    ({"speculate_k": 2}, "speculate_k"),
    ({"prefill_chunk": 128}, "prefill_chunk=128 < max_seq_len=256"),
    ({"scheduler": "static"}, "scheduler='static'"),
])
def test_a_window_cache_refuses_by_name(over, message):
    from lmrs_tpu.config import model_preset
    from lmrs_tpu.engine.jax_engine import JaxEngine

    from lmrs_tpu.engine.scheduler import ContinuousScheduler

    with pytest.raises(ValueError, match="window KV cache") as err:
        if "scheduler" in over:  # the engine builds no page pool for it
            ContinuousScheduler._refuse_for_window(_engine_cfg(**over), None,
                                                   256)
        JaxEngine(_engine_cfg(**over), model_preset("tiny-swa-moe"))
    assert message in str(err.value)


@pytest.mark.parametrize("axis", ["tp", "sp"])
def test_a_window_cache_refuses_a_mesh(axis):
    from lmrs_tpu.config import MeshConfig
    from lmrs_tpu.engine.scheduler import ContinuousScheduler
    from lmrs_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(MeshConfig(**{axis: 2}))
    with pytest.raises(ValueError, match=f"{axis} > 1"):
        ContinuousScheduler._refuse_for_window(_engine_cfg(), mesh, 256)


def test_a_window_cache_runs_no_packed_mixed_or_span_program():
    from lmrs_tpu.config import model_preset
    from lmrs_tpu.engine.jax_engine import JaxEngine

    eng = JaxEngine(_engine_cfg(), model_preset("tiny-swa-moe"))
    sched = eng._scheduler
    assert not sched._pack_prefill and not sched._mixed
    assert sched.metrics_report()["rpa"]["enabled"] is False
    with pytest.raises(NotImplementedError, match="continuation of a chunked"):
        from lmrs_tpu.models.transformer import forward_paged

        forward_paged(sched.params, sched.model_cfg,
                      jnp.zeros((1, 8), jnp.int32), jnp.arange(8)[None],
                      sched.cache.k, sched.cache.v,
                      jnp.zeros((1, 4), jnp.int32), jnp.asarray([8]), 256,
                      window_prefill=True, window_slots=2)
    eng.shutdown()


# ---- (vii) the counters against a hand count ---------------------------------

def test_window_counters_of_one_prefill_and_one_decode_dispatch(monkeypatch):
    """One request of 300 prompt tokens and 8 generated through the engine,
    kernels interpreted (two heads of 128): the prefill dispatch's flash
    tile pairs and the decode block's page walks, by hand."""
    monkeypatch.setenv("LMRS_FORCE_KERNELS", "interpret")
    from lmrs_tpu.config import model_preset
    from lmrs_tpu.engine.api import GenerationRequest
    from lmrs_tpu.engine.jax_engine import JaxEngine

    cfg = dataclasses.replace(model_preset("tiny-swa-moe"), n_heads=2,
                              n_kv_heads=1, head_dim=128, max_seq_len=512)
    eng = JaxEngine(_engine_cfg(prefill_chunk=512, max_batch_slots=1), cfg)
    res = eng.generate_batch([GenerationRequest(
        request_id=0, prompt="x" * 299, max_new_tokens=8, temperature=0.0)])
    assert res[0].prompt_tokens == 300 and res[0].completion_tokens == 8
    sched = eng._scheduler
    m = sched.metrics
    # bucket 512, tiles of 128 (window_block(16)), band 2: 3 query tiles
    # hold a token, 1 + 2 + 3 = 6 causal pairs a layer, 1 + 2 + 2 seen
    # through the window
    assert m["flash_blocks"] == 6 * 5
    assert m["flash_blocks_skipped"] == 1 * 4
    # the block's 8 steps attend 301 .. 308 positions: ceil(n / 8) pages in
    # the one full layer; in each of the 4 window layers the pages from
    # position n - 16 to n - 1
    full = sum(-(-n // PAGE) for n in range(301, 309))
    win = sum((n - 1) // PAGE - (n - 16) // PAGE + 1 for n in range(301, 309))
    assert m["kv_pages_full"] == full
    assert m["kv_pages_window"] == 4 * win
    assert (m["cache_pages_full"], m["cache_pages_window"]) == (64, 4 * 3)
    programs = sched.anatomy_report()["programs"]
    assert programs["prefill"]["flash_blocks"] == 30
    assert programs["decode"]["kv_pages_window"] == 4 * win
    assert sched.metrics_report()["window_cache"] == {
        "window": 16, "ring_pages": 3, "full_layers": 1, "window_layers": 4,
        "cache_pages_full": 64, "cache_pages_window": 12}
    assert sched.audit() == []
    eng.shutdown()


# ---- (viii) the other models trace what they traced --------------------------

@pytest.mark.parametrize("preset,extra", [
    ("bench-smoke", ()),
    ("tiny-latent", ("moe_routed_pairs", "moe_expert_tokens_max",
                     "moe_expert_tokens_mean", "moe_extra_passes")),
])
def test_models_without_a_window_keep_their_keys_and_record_fields(
        preset, extra):
    from lmrs_tpu.config import model_preset
    from lmrs_tpu.engine.api import GenerationRequest
    from lmrs_tpu.engine.jax_engine import JaxEngine
    from lmrs_tpu.obs.anatomy import RECORD_FIELDS

    eng = JaxEngine(_engine_cfg(model=preset, page_size=16,
                                max_batch_slots=2), model_preset(preset))
    eng.generate_batch([GenerationRequest(
        request_id=0, prompt="hello there " * 4, max_new_tokens=8,
        temperature=0.0)])
    sched = eng._scheduler
    assert sched.cache.window is None
    assert not [k for k in sched.metrics
                if k.startswith(("kv_pages_", "flash_blocks", "cache_pages_"))]
    assert "window_cache" not in sched.metrics_report()
    programs = sched.anatomy_report()["programs"]
    assert set(programs) == {"prefill", "decode"}
    for name, rec in programs.items():
        assert set(rec) == {*RECORD_FIELDS, "cold_ms", "keys", *extra}
        (key,) = rec["keys"]
        assert key.split(":")[0] == name
        # ("prefill", fresh, rows, bucket, window, ring) / ("decode", rows, w)
        assert len(key.split(":")) == (6 if name == "prefill" else 3)
    eng.shutdown()
