"""Roofline accounting (utils/perf_model.py) — the live MFU/bandwidth
attribution (obs/perf.py) is only as honest as these counts."""

import jax
import pytest

from lmrs_tpu.config import ModelConfig, model_preset
from lmrs_tpu.models.transformer import init_params, param_count
from lmrs_tpu.utils.perf_model import (
    chip_spec, decode_step_bytes, kv_bytes_per_token, matmul_params,
    prefill_flops, weight_bytes,
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


def test_decode_bytes_components():
    cfg = model_preset("bench-1b")
    live = 24 * 1536
    assert decode_step_bytes(cfg, live) == pytest.approx(
        weight_bytes(cfg) + live * kv_bytes_per_token(cfg))
    # int8 halves the matmul-weight stream
    assert weight_bytes(cfg, quantized=True) == pytest.approx(
        matmul_params(cfg))


def test_chip_spec_unknown_device_has_no_peaks():
    # CPU test backend: a device_kind that is not in the table is not a
    # v5e — no peaks at all, so no utilisation is ever computed against it
    assert chip_spec() is None
