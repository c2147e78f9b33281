"""Int8 weight-only quantization.

TPU decode is HBM-bandwidth-bound (SURVEY.md §7.4: the scheduler/kernel
design problem is feeding the MXU, not FLOPs) — storing the big projection
matrices as int8 halves the bytes streamed per decode step vs bfloat16.
Dequantization is a convert+multiply that XLA fuses into the consuming
matmul, so the bf16 tensor never materializes in HBM.

Scheme: symmetric per-output-channel scales.  For a weight of shape
[..., out], ``s = max|w| / 127`` over all axes except the last, ``q =
round(w / s)`` as int8; a quantized leaf is the dict ``{"q": int8, "s":
f32}``.  Weights stay in this form in the param pytree; every use site in
models/transformer.py goes through ``deq`` (a no-op passthrough for plain
arrays, so dense/bf16 params take the same code path).

The reference has no weights at all (the model is behind OpenAI's API) —
this is serving-stack surface with no reference counterpart.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def is_quantized(leaf: Any) -> bool:
    return isinstance(leaf, dict) and set(leaf.keys()) == {"q", "s"}


def quantize_weight(w: jnp.ndarray, axes: tuple[int, ...]) -> dict[str, jnp.ndarray]:
    """Symmetric int8 quantization; ``axes`` are the contracting axes of the
    consuming matmul — scales are shared only along them, so every output
    channel (and every stacked layer / expert) gets its own scale."""
    wf = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(wf), axis=axes, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(wf / s), -127, 127).astype(jnp.int8)
    return {"q": q, "s": s}


def deq(x: Any, dtype) -> jnp.ndarray:
    """Dequantize a {"q","s"} leaf to ``dtype``; plain arrays pass through.
    The convert*scale is an elementwise producer of the consuming matmul —
    XLA fuses it, so only int8 is read from HBM.  Matmul call sites should
    prefer ``qeinsum``, which folds the scale into the matmul OUTPUT
    instead of paying it per weight element."""
    if is_quantized(x):
        return (x["q"].astype(jnp.float32) * x["s"]).astype(dtype)
    return x


def qeinsum(spec: str, x: jnp.ndarray, leaf: Any, dtype) -> jnp.ndarray:
    """``einsum(spec, x, W)`` where W may be a quantized {"q","s"} leaf.

    Quantized weights contract as raw int8 values (converted to ``dtype``
    — lossless, |q| <= 127 fits bf16's 8-bit mantissa exactly) and the
    scale multiplies the OUTPUT: scales are per-output-channel by
    construction (``quantize_weight`` shares them only along the consuming
    matmul's contracting axes, which are 1-sized in ``s``), so
    ``einsum(x, q*s) == einsum(x, q) * s`` with ``s`` broadcasting
    right-aligned onto the result.  This moves the dequant multiply from
    one-per-WEIGHT-element — VPU work proportional to weight bytes, which
    measurably throttles the int8 weight stream below HBM rate at 8B
    shapes (docs/PERF.md round 5) — to one-per-OUTPUT-element (~D× fewer
    at decode), and drops a rounding step: the old path rounded q*s to
    bf16 per element before the MXU, this one feeds exact integers.
    """
    if is_quantized(leaf):
        y = jnp.einsum(spec, x, leaf["q"].astype(dtype))
        return (y * leaf["s"]).astype(dtype)
    return jnp.einsum(spec, x, leaf)


# Weight names eligible for quantization: the large projection matrices.
# Embeddings stay full-precision (gather path), router stays full-precision
# (tiny, and routing decisions are precision-sensitive), norms are vectors.
_QUANT_NAMES = frozenset({"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"})


def _contract_axes(name: str, ndim: int) -> tuple[int, ...]:
    """Contracting axes of the matmul that consumes each stacked weight:
    wq/wk/wv [L,D,H,hd] contract D; wo [L,H,hd,D] contracts (H,hd); dense
    FFN [L,in,out] contracts axis 1; MoE FFN [L,E,in,out] contracts axis 2;
    lm_head [D,V] contracts D."""
    if name == "wo":
        return (1, 2)
    if name in ("wq", "wk", "wv"):
        return (1,)
    if name in ("w_gate", "w_up", "w_down"):
        return (2,) if ndim == 4 else (1,)
    if name == "lm_head":
        return (0,)
    raise ValueError(f"no contraction rule for weight {name!r}")


def _walk_quantizable(params: Any, qfn, plain) -> Any:
    """Shared eligibility walk: eligible projection leaves map through
    ``qfn(leaf, contract_axes)``, everything else through ``plain(leaf)``.
    ``lm_head.weight`` is included; ``embed.weight`` is not."""
    def walk(tree: Any, path: tuple[str, ...]) -> Any:
        if isinstance(tree, dict) and not is_quantized(tree):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        name = path[-1] if path else ""
        if name in _QUANT_NAMES:
            return qfn(tree, _contract_axes(name, tree.ndim))
        if len(path) >= 2 and path[-2] == "lm_head":
            return qfn(tree, _contract_axes("lm_head", tree.ndim))
        return plain(tree)

    return walk(params, ())


def quantize_params(params: Any) -> Any:
    """Quantize the projection weights of a transformer param pytree.

    Returns a new pytree where eligible leaves become {"q","s"} dicts;
    structure is otherwise identical (scan/shard/jit all still work)."""
    return _walk_quantizable(params, quantize_weight, lambda x: x)


def random_quantized_init(cfg, seed: int) -> Any:
    """Random param tree in ALREADY-QUANTIZED form, built host-side with
    numpy — throughput-identical to quantize(random-init) without ever
    materializing the full-precision tree.  Needed for quantized
    random-init at 8B shape (bench-8b): the 16 GB bf16 tree cannot
    coexist with anything on a 16 GB chip.  Structure comes from
    ``jax.eval_shape`` over the real initializer, so it can never drift
    from ``init_params``."""
    import numpy as np

    from lmrs_tpu.models.transformer import init_params

    shapes = jax.eval_shape(
        lambda k: init_params(cfg, k), jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)

    def qfn(sd, axes):
        s_shape = tuple(1 if a in axes else n
                        for a, n in enumerate(sd.shape))
        return {"q": rng.integers(-127, 128, sd.shape, dtype=np.int8),
                "s": np.full(s_shape, 2e-4, np.float32)}

    def plain(sd):
        arr = rng.standard_normal(sd.shape, dtype=np.float32) * 0.02
        return arr.astype(sd.dtype)

    return _walk_quantizable(shapes, qfn, plain)


def quantized_bytes(params: Any) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))


def match_quantized_specs(specs: Any, params: Any) -> Any:
    """Adapt a PartitionSpec pytree to a quantized param pytree: wherever a
    param leaf is {"q","s"}, the spec leaf P becomes {"q": P, "s": P(...)}
    (scales replicated — they are tiny)."""
    from jax.sharding import PartitionSpec as P

    def walk(spec: Any, param: Any) -> Any:
        if is_quantized(param):
            return {"q": spec, "s": P(*([None] * param["s"].ndim))}
        if isinstance(param, dict):
            return {k: walk(spec[k], param[k]) for k in param}
        return spec

    return walk(specs, params)


# ------------------------------------------------------- KV-cache int8 (r3)
#
# Decode's slope term is the KV page walk (docs/PERF.md round 3: 3.5
# us/live-token vs a 2.16 us HBM floor); int8 pages halve the streamed
# bytes AND double the tokens each HBM GiB holds.  Scheme: symmetric int8
# with one scale per (slot, kv head, channel), fixed at prefill time from
# the prompt's K/V stats (per-channel handles K's channel-consistent
# outliers — the KIVI finding; the per-slot factor tracks sequence-level
# magnitude).  Decode/verify tokens quantize with the SAME slot scale
# (clamped): requantizing written pages on scale change is a non-starter.
# Scales live in scheduler-owned [L, B, K, hd] f32 buffers threaded
# through the dispatch programs — VMEM-resident at kernel time, no
# per-page scale DMAs (the layout analysis that rejected per-token scale
# pools, docs/PERF.md round 3).


def kv_scale_from(kv: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Per-(row, kv head, channel) symmetric scale from a prefill's K or V.

    kv: [B, S, K, hd]; valid: [B, S] bool (True where the token is a real
    prompt token — padding and out-of-prompt rows must not inflate the
    scale).  Returns [B, K, hd] f32, floored so dequant never divides by
    ~0 on all-masked rows."""
    a = jnp.where(valid[:, :, None, None], jnp.abs(kv.astype(jnp.float32)), 0.0)
    return jnp.maximum(jnp.max(a, axis=1) / 127.0, 1e-8)


def kv_quant_tokens(kv: jnp.ndarray, token_scales: jnp.ndarray) -> jnp.ndarray:
    """Quantize K or V rows with PER-TOKEN scales: the packed-prefill path,
    where one [1, S] row holds many prompts and each token quantizes with
    its own segment's (slot's) scales.  kv [B, S, K, hd],
    token_scales [B, S, K, hd] (or broadcastable) -> int8 [B, S, K, hd].
    THE int8 KV quantization rule — ``kv_quant`` delegates here so the
    packed and per-row paths can never diverge."""
    q = jnp.round(kv.astype(jnp.float32) / token_scales)
    return jnp.clip(q, -127, 127).astype(jnp.int8)


def kv_quant(kv: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Quantize K or V rows with their row scales.  kv [B, S, K, hd],
    scale [B, K, hd] -> int8 [B, S, K, hd] (clipped: decode tokens reuse
    the prefill-time scale, so out-of-range values saturate)."""
    return kv_quant_tokens(kv, scale[:, None])


def kv_dequant(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    """Dequantize gathered int8 KV.  q [B, T, K, hd], scale [B, K, hd]."""
    return (q.astype(jnp.float32) * scale[:, None]).astype(dtype)
