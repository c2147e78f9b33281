"""Roofline accounting (utils/perf_model.py) — the live MFU/bandwidth
attribution (obs/perf.py) is only as honest as these counts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lmrs_tpu.config import EngineConfig, ModelConfig, model_preset
from lmrs_tpu.models.transformer import init_params, param_count
from lmrs_tpu.ops.quant import quantize_params
from lmrs_tpu.utils.perf_model import (
    StepBytes, chip_spec, matmul_params, prefill_flops,
)


def test_matmul_params_matches_initialized_tree():
    """matmul_params + norm scales == param_count for a tied-embedding
    model (the tied LM head is the embedding matrix, counted once in the
    tree but doing matmul work)."""
    cfg = ModelConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, hidden_dim=128, max_seq_len=256,
                      dtype="float32")
    total = param_count(init_params(cfg, jax.random.PRNGKey(0)))
    norms = cfg.n_layers * 2 * cfg.dim + cfg.dim
    assert matmul_params(cfg) + norms == total


def test_matmul_params_untied_head():
    cfg = ModelConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, hidden_dim=128, max_seq_len=256,
                      dtype="float32", tie_embeddings=False)
    total = param_count(init_params(cfg, jax.random.PRNGKey(0)))
    norms = cfg.n_layers * 2 * cfg.dim + cfg.dim
    embed = cfg.vocab_size * cfg.dim  # lookup-only, not a matmul
    assert matmul_params(cfg) + norms + embed == total


def test_bench_1b_scale():
    """The bench model must actually be >= 1B params (VERDICT r1 item 1)."""
    cfg = model_preset("bench-1b")
    assert matmul_params(cfg) >= 1_000_000_000
    assert cfg.hd % 128 == 0  # ragged-kernel eligible


def test_prefill_flops_components():
    cfg = model_preset("bench-1b")
    s = 2048
    fl = prefill_flops(cfg, s)
    dense = 2.0 * matmul_params(cfg) * s
    attn = 2.0 * cfg.n_layers * s * s * cfg.hd * cfg.n_heads
    assert fl == pytest.approx(dense + attn)
    # gathered LM head shrinks the vocab matmul, nothing else
    fl_packed = prefill_flops(cfg, s, head_tokens=24)
    assert fl - fl_packed == pytest.approx(
        2.0 * (s - 24) * cfg.dim * cfg.vocab_size)


def _leaf_bytes(tree) -> int:
    return sum(x.size * np.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def test_decode_bytes_components():
    """A decode block's bytes, from the tree and pool as held: its layer
    applications, the head once a step and at step t each live row's
    ``len + t + 1`` positions; bf16 weights two bytes a parameter, int8
    weights one plus their scales."""
    cfg = model_preset("bench-1b")
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    pool = jax.ShapeDtypeStruct((cfg.n_layers * 9, cfg.n_kv_heads, 16,
                                 cfg.hd), jnp.bfloat16)
    sb = StepBytes.held(cfg, shapes, pool, pool)
    norms = cfg.n_layers * 2 * cfg.dim + cfg.dim
    assert sb.layer * cfg.n_layers + sb.head == (matmul_params(cfg)
                                                 + norms) * 2
    assert sb.position == 2 * cfg.n_kv_heads * cfg.hd * 2
    assert (sb.full_layers, sb.window_layers, sb.expert) == (
        cfg.n_layers, 0, 0)
    lens, steps = [100, 1535], 4
    positions = sum(n + t + 1 for n in lens for t in range(steps))
    assert sb.block(lens, steps, cfg.n_layers * steps) == pytest.approx(
        steps * (matmul_params(cfg) + norms) * 2
        + positions * cfg.n_layers * sb.position, abs=1)
    q = jax.eval_shape(quantize_params, shapes)
    sq = StepBytes.held(cfg, q, pool, pool)
    embed = 0 if cfg.tie_embeddings else _leaf_bytes(q["embed"])
    assert sq.layer * cfg.n_layers + sq.head == _leaf_bytes(q) - embed
    assert sq.layer < 0.51 * sb.layer  # int8 values and f32 scales


@pytest.mark.parametrize("preset,kv", [
    ("tiny", None), ("tiny", "int8"), ("tiny-latent", None),
    ("tiny-swa-moe", None), ("tiny-looped", "int8")])
def test_step_bytes_are_the_held_leaves_of_each_family(preset, kv):
    """Every leaf of the tree counts once: the layer applications
    (``n_layers`` of them in the tree, however often a looped stack runs
    them), the held experts, and the head with the final norm; the
    embedding table only where it is the head.  A position's bytes are
    the pool's over its positions: K and V, int8 or not, or a latent row;
    full and window layers add up to the cache layers."""
    from lmrs_tpu.engine.kv_cache import PagedKVCache

    cfg = model_preset(preset)
    tree = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    cache = PagedKVCache(cfg, 5, 16, 4, kv_dtype=kv, slots=2)
    sb = StepBytes.held(cfg, tree, cache.k, cache.v)
    embed = 0 if cfg.tie_embeddings else _leaf_bytes(tree["embed"])
    held = cfg.n_routed_layers * cfg.experts_held
    assert (sb.layer * cfg.n_layers + sb.expert * held + sb.head
            == pytest.approx(_leaf_bytes(tree) - embed, abs=1e-6))
    if held:
        moe = tree["layers"]["moe"]
        assert sb.expert * held == sum(
            _leaf_bytes(moe[k]) for k in ("w_gate", "w_up", "w_down"))
    pool = cache.k.nbytes + (cache.v.nbytes if cache.v is not None else 0)
    assert sb.position * cache.k.shape[0] * 16 == pool
    assert sb.full_layers + sb.window_layers == cfg.cache_layers
    assert sb.window_layers == cfg.n_window_layers
    # a looped stack reads its layers once a pass: cache_layers a step
    steps = 3
    weights = sb.block([0], steps, cfg.cache_layers * steps) - sb.block(
        [0], steps, 0)
    assert weights == pytest.approx(
        steps * cfg.n_loops * (_leaf_bytes(tree) - embed - sb.head
                               - sb.expert * held), abs=1)


def test_an_int8_tree_handed_over_counts_as_int8():
    """The benchmark hands the engine weights that are already int8 with
    ``quantize`` None: the scheduler's count reads the tree, not the
    flag."""
    from lmrs_tpu.engine.scheduler import ContinuousScheduler
    from lmrs_tpu.ops.quant import random_quantized_init

    cfg = ModelConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, hidden_dim=128, max_seq_len=256,
                      dtype="bfloat16", tie_embeddings=False)
    q = random_quantized_init(cfg, 0)
    sched = ContinuousScheduler(
        EngineConfig(backend="jax", quantize=None, max_batch_slots=2,
                     page_size=16, prefix_cache=False, host_kv=False),
        cfg, q, None)
    sb = sched._bytes
    assert sb.layer * cfg.n_layers + sb.head == (_leaf_bytes(q)
                                                 - _leaf_bytes(q["embed"]))
    assert sb.layer * cfg.n_layers < 0.6 * 2 * (
        matmul_params(cfg) - cfg.dim * cfg.vocab_size)


def test_chip_spec_unknown_device_has_no_peaks():
    # CPU test backend: a device_kind that is not in the table is not a
    # v5e — no peaks at all, so no utilisation is ever computed against it
    assert chip_spec() is None
