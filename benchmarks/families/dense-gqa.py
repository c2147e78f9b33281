"""The dense family: pre-norm RMSNorm, grouped-query attention with rotary
embeddings, SwiGLU, one homogeneous stack (Llama, Mistral, InternLM2).

Thin: the weights are ``weights.py``'s, the reference ``reference.py``'s and
the counts ``flops.py``'s, as they stand; what is written out here is what
the harness used to hold itself: the reading of the published keys, the
program's ``ModelConfig``, its layout of the tree on a mesh, and the four
buffers of its page pool.
"""

from __future__ import annotations

from families import bench_file

_weights = bench_file("weights")
_reference = bench_file("reference")
_flops = bench_file("flops")

param_shapes = _weights.param_shapes
make_params = _weights.make_params
weight_bytes = _weights.weight_bytes

logits_for = _reference.logits_for
served_gap = _reference.served_gap
control_gap = _reference.control_gap

matmul_params = _flops.matmul_params
attn_flops = _flops.attn_flops
prefill_flops = _flops.prefill_flops
request_flops = _flops.request_flops
kv_bytes_per_token = _flops.kv_bytes_per_token
weight_stream_bytes = _flops.weight_stream_bytes
decode_step_bytes = _flops.decode_step_bytes
decode_attn_bytes = _flops.decode_attn_bytes


def sizes(cfg: dict) -> dict:
    heads = cfg["num_attention_heads"]
    return {
        "dim": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": heads,
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg.get("head_dim") or cfg["hidden_size"] // heads,
        "hidden_dim": cfg["intermediate_size"],
        "vocab_size": cfg["vocab_size"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "tie": bool(cfg.get("tie_word_embeddings", False)),
        "weights": cfg["engine"]["weights"],
        "kv": cfg["engine"]["kv"],
        "page_size": cfg["engine"]["page_size"],
        # optional: fields of the program's EngineConfig that belong to the
        # configuration (prefix_cache, host_kv, kv_disk, scheduler ...), and
        # the mesh a sharded configuration runs on ({"tp": 4})
        "settings": dict(cfg["engine"].get("settings", {})),
        "mesh": dict(cfg["engine"].get("mesh", {})),
    }


def model_config(name: str, m: dict, eng: dict):
    from lmrs_tpu.config import ModelConfig

    return ModelConfig(
        name=name, vocab_size=m["vocab_size"], dim=m["dim"],
        n_layers=m["n_layers"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], hidden_dim=m["hidden_dim"],
        max_seq_len=eng["max_seq_len"], rope_theta=m["rope_theta"],
        norm_eps=m["norm_eps"], tie_embeddings=m["tie"], dtype="bfloat16",
        head_dim=m["head_dim"])


def param_specs(m: dict):
    from lmrs_tpu.ops.quant import match_quantized_specs
    from lmrs_tpu.parallel import sharding

    return match_quantized_specs(sharding.param_specs(m["tie"], False),
                                 param_shapes(m))


def cache_buffers(sched) -> list:
    return [sched.cache.k, sched.cache.v, sched.kscale, sched.vscale]
