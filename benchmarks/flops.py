"""Operation and byte counts of the decoder the engine serves.

Copied from ``lmrs_tpu/utils/perf_model.py`` (``matmul_params``,
``prefill_flops``, ``kv_bytes_per_token``, ``decode_step_bytes``) so that a
later PR cannot move the yardstick; ``checks/check_flops.py`` shows the two
agree today.  Sizes come from a configuration file (run.model_sizes), not
from the program's ModelConfig.  Dense matmuls: 2 FLOPs per parameter per
token.  Causal attention: QK^T and PV, 2 FLOPs a multiply-add.
"""

from __future__ import annotations


def matmul_params(m: dict) -> int:
    """Parameters in per-token matmuls: projections, FFN and the LM head
    (the embedding is a gather)."""
    d, hd = m["dim"], m["head_dim"]
    per_layer = (d * m["n_heads"] * hd + 2 * d * m["n_kv_heads"] * hd
                 + m["n_heads"] * hd * d + 3 * d * m["hidden_dim"])
    return m["n_layers"] * per_layer + d * m["vocab_size"]


def attn_flops(m: dict, n_new: int, n_ctx: int = 0) -> float:
    """Attention FLOPs of ``n_new`` query tokens that follow ``n_ctx``
    cached tokens, causal among themselves: sum over queries of 4 * hd * H
    * (keys seen), all layers."""
    keys = n_new * n_ctx + n_new * (n_new + 1) / 2.0
    return 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * keys


def prefill_flops(m: dict, n_tokens: int, head_tokens: int | None = None,
                  kv_start: int = 0) -> float:
    """perf_model.prefill_flops, same arithmetic (its attention term is
    2*L*(S^2 + 2*kv_start*S)*hd*H: S/2 keys a query on average)."""
    d = m["dim"]
    body = matmul_params(m) - d * m["vocab_size"]
    fl = 2.0 * body * n_tokens
    fl += 2.0 * (n_tokens if head_tokens is None else head_tokens) \
        * d * m["vocab_size"]
    fl += 2.0 * m["n_layers"] * (float(n_tokens) ** 2
                                 + 2.0 * kv_start * n_tokens) \
        * m["head_dim"] * m["n_heads"]
    return fl


def request_flops(m: dict, n_prompt_computed: int, n_cached: int,
                  n_generated: int) -> float:
    """Model FLOPs one request needs: the prompt tokens the device computed
    (after ``n_cached`` served from the prefix cache), then ``n_generated``
    decode steps; the LM head runs once per sampled token."""
    body = matmul_params(m) - m["dim"] * m["vocab_size"]
    head = m["dim"] * m["vocab_size"]
    n_prompt = n_cached + n_prompt_computed
    fl = 2.0 * body * (n_prompt_computed + n_generated)
    fl += 2.0 * head * max(n_generated, 1)
    fl += attn_flops(m, n_prompt_computed, n_cached)
    # decode token j (0-based) attends n_prompt + j + 1 keys
    keys = n_generated * n_prompt + n_generated * (n_generated + 1) / 2.0
    fl += 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * keys
    return fl


def kv_bytes_per_token(m: dict) -> float:
    item = 1 if m["kv"] == "int8" else 2
    return 2.0 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"] * item


def weight_stream_bytes(m: dict) -> float:
    """Bytes of matmul weights one decode step reads."""
    return matmul_params(m) * (1 if m["weights"] == "int8" else 2)


def decode_step_bytes(m: dict, live_tokens: int) -> float:
    return weight_stream_bytes(m) + kv_bytes_per_token(m) * live_tokens


def decode_attn_bytes(m: dict, live_tokens: int) -> float:
    """KV bytes the paged decode kernel must read for one step over rows
    whose cached lengths sum to ``live_tokens`` (all layers)."""
    return kv_bytes_per_token(m) * live_tokens
