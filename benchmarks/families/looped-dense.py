"""The looped dense family: ONE stack of pre-norm GQA + SwiGLU layers run
``total_ut_steps`` times over the same weights, with sandwich norms (Ouro
"LoopLM", ByteDance 2025-10; written from the published ``config.json`` and
``modeling_ouro.py``).  ``T`` = ``total_ut_steps``, ``L`` =
``num_hidden_layers``, one set of weights:

    x = E[ids]
    for t in 0..T-1:
        for l in 0..L-1:                              # the SAME weights in every pass
            a = Attn_l(RMS(x; g_in[l]))               # rotary over the whole head (split
                                                      # halves), causal, no bias; pass t
                                                      # attends only the keys and values
                                                      # that pass t produced
            x = x + RMS(a; g_in2[l])                  # sandwich: the sublayer's OUTPUT is
                                                      # normed before the residual add
            m = W_down[l](silu(W_gate[l] h) * W_up[l] h),  h = RMS(x; g_post[l])
            x = x + RMS(m; g_post2[l])
        x = RMS(x; g_final)                           # the final norm closes EVERY pass
    logits = W_head x                                 # after pass T; no second final norm

The exit gate (a ``D -> 1`` linear on each pass's closing state) is not
computed: at the published ``early_exit_threshold`` 1.0 no token leaves
before pass T and the gate enters no logit.  A threshold below 1 makes
depth data-dependent per token and is refused here, by name, when the
configuration is read (``sizes``), as the program refuses it.

The dense tree is ``weights.py``'s plus two norm leaves a layer
(``ln_attn_out`` = g_in2, ``ln_mlp_out`` = g_post2; the program's names), all
drawn from the seed in one jitted call.  The reference below is this
family's own: ``jax.numpy`` at ``Precision.HIGHEST``, float32, no cache, no
batching, one layer's float32 weights at a time, nothing imported from the
program; it takes from ``reference.py`` only its leaf arithmetic (a leaf to
float32 under a control's precision, the norm with weight 1 + scale, the
rotary table).  K and V are exact: int8 pages, a storage choice of the
engine, are not modelled, so what they cost shows in the program's gap.

Counts: a token multiplies, and a decode step reads, every layer's weights
once a PASS, and holds K and V for every (pass, layer): everything
``flops.py`` counts per layer is counted over ``T x L`` layer applications,
the head once.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from families import HERE, bench_file, load_module

_weights = bench_file("weights")
_reference = bench_file("reference")
_flops = bench_file("flops")
_dense = load_module(HERE / "dense-gqa.py", "bench_family_dense_gqa")

HI, F32 = _reference.HI, _reference.F32
_weight, _rms, _rope = _reference._weight, _reference._rms, _reference._rope

weight_bytes = _weights.weight_bytes
cache_buffers = _dense.cache_buffers

OUT_NORMS = ("ln_attn_out", "ln_mlp_out")  # g_in2, g_post2


def sizes(cfg: dict) -> dict:
    exit_at = float(cfg.get("early_exit_threshold", 1.0))
    if exit_at != 1.0:
        raise SystemExit(
            f"family looped-dense: early_exit_threshold is {exit_at}: below "
            "1 a token leaves the loop once its exit gate passes it, depth "
            "chosen per token, which neither this reference nor the program "
            "computes; only 1.0 (every token runs every pass) is served")
    return {**_dense.sizes(cfg), "n_loops": int(cfg["total_ut_steps"]),
            "early_exit_threshold": exit_at}


def model_config(name: str, m: dict, eng: dict):
    from lmrs_tpu.config import LoopedModelConfig

    return LoopedModelConfig(
        name=name, vocab_size=m["vocab_size"], dim=m["dim"],
        n_layers=m["n_layers"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], hidden_dim=m["hidden_dim"],
        max_seq_len=eng["max_seq_len"], rope_theta=m["rope_theta"],
        norm_eps=m["norm_eps"], tie_embeddings=m["tie"], dtype="bfloat16",
        head_dim=m["head_dim"], n_loops=m["n_loops"], sandwich_norm=True,
        early_exit_threshold=m["early_exit_threshold"])


# ---------------------------------------------------------------- weights


def _builder(m: dict):
    dense = _weights._builder(m)
    shape = (m["n_layers"], m["dim"])

    def build(key):
        k_dense, *k_norms = jax.random.split(key, 1 + len(OUT_NORMS))
        params = dense(k_dense)
        for name, k in zip(OUT_NORMS, k_norms):
            params["layers"][name] = {"scale": _weights._draw_norm(k, shape)}
        return params

    return build


def param_shapes(m: dict):
    return jax.eval_shape(_builder(m), _weights._key(0))


def make_params(m: dict, seed: int, shardings=None):
    return jax.jit(_builder(m), out_shardings=shardings)(_weights._key(seed))


def param_specs(m: dict):
    from lmrs_tpu.ops.quant import match_quantized_specs
    from lmrs_tpu.parallel import sharding

    return match_quantized_specs(
        sharding.param_specs(m["tie"], False, sandwich_norm=True),
        param_shapes(m))


# -------------------------------------------------------------- reference


@partial(jax.jit, static_argnames=("dims", "mode"))
def _forward(params, ids, rows, *, dims, mode):
    """ids [S] int32 (padded past the real length; causal, so the padding
    reaches no earlier row); rows [R]: positions whose logits are wanted.
    Returns logits [R, V] float32."""
    n_heads, n_kv, hd, theta, eps, n_loops = dims
    rep = n_heads // n_kv
    s = ids.shape[0]
    x = params["embed"]["weight"][ids].astype(F32)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]  # [q, k]

    def layer(x, lp):
        a, m = lp["attn"], lp["mlp"]
        h = _rms(x, lp["ln_attn"]["scale"], eps)
        q = jnp.einsum("sd,dhk->shk", h, _weight(a["wq"], (0,), mode),
                       precision=HI)
        k = jnp.einsum("sd,dhk->shk", h, _weight(a["wk"], (0,), mode),
                       precision=HI)
        v = jnp.einsum("sd,dhk->shk", h, _weight(a["wv"], (0,), mode),
                       precision=HI)
        q = _rope(q, theta).reshape(s, n_kv, rep, hd)
        k = _rope(k, theta)
        logit = jnp.einsum("skrd,tkd->krst", q, k, precision=HI) * hd ** -0.5
        logit = jnp.where(causal[None, None], logit, -1e30)
        p = jax.nn.softmax(logit, axis=-1)
        o = jnp.einsum("krst,tkd->skrd", p, v, precision=HI)
        o = o.reshape(s, n_heads, hd)
        att = jnp.einsum("shk,hkd->sd", o, _weight(a["wo"], (0, 1), mode),
                         precision=HI)
        x = x + _rms(att, lp["ln_attn_out"]["scale"], eps)
        h = _rms(x, lp["ln_mlp"]["scale"], eps)
        g = jnp.einsum("sd,df->sf", h, _weight(m["w_gate"], (0,), mode),
                       precision=HI)
        u = jnp.einsum("sd,df->sf", h, _weight(m["w_up"], (0,), mode),
                       precision=HI)
        ff = jnp.einsum("sf,fd->sd", jax.nn.silu(g) * u,
                        _weight(m["w_down"], (0,), mode), precision=HI)
        return x + _rms(ff, lp["ln_mlp_out"]["scale"], eps), None

    def one_pass(x, _):
        x, _ = jax.lax.scan(layer, x, params["layers"])
        return _rms(x, params["final_norm"]["scale"], eps), None

    x, _ = jax.lax.scan(one_pass, x, None, length=n_loops)
    return jnp.einsum("rd,dv->rv", x[rows],
                      _weight(params["lm_head"]["weight"], (0,), mode),
                      precision=HI)


def _dims(m: dict) -> tuple:
    return (m["n_heads"], m["n_kv_heads"], m["head_dim"],
            float(m["rope_theta"]), float(m["norm_eps"]), int(m["n_loops"]))


def forward_logits(params, m: dict, ids, mode=None) -> jax.Array:
    """Reference logits at every position of ``ids`` [S]: [S, V] float32
    (the CPU tests' entry; the harness reads through ``logits_for``)."""
    ids = jnp.asarray(np.asarray(ids, np.int32))
    return _forward(params, ids, jnp.arange(ids.shape[0]), dims=_dims(m),
                    mode=mode)


def logits_for(params, m: dict, prompt_ids, served_ids, mode=None):
    """Reference logits at the positions that produced each served token:
    row j predicts ``served_ids[j]`` from prompt + served[:j].  [n, V]."""
    ids = list(prompt_ids) + list(served_ids)
    n_p, n_s = len(prompt_ids), len(served_ids)
    ids_a = np.zeros((_reference._bucket(len(ids)),), np.int32)
    ids_a[: len(ids)] = ids
    rows = np.full((_reference._bucket(n_s, 64),), n_p - 1, np.int32)
    rows[:n_s] = np.arange(n_p - 1, n_p - 1 + n_s)
    out = _forward(params, jnp.asarray(ids_a), jnp.asarray(rows),
                   dims=_dims(m), mode=mode)
    return out[:n_s]


def _gap(lg, pick) -> np.ndarray:
    gap = lg.max(axis=-1) - jnp.take_along_axis(lg, pick[:, None], 1)[:, 0]
    return np.asarray(jax.device_get(gap))


def served_gap(params, m: dict, prompt_ids, served_ids) -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at that position.  [n] float32, >= 0."""
    lg = logits_for(params, m, prompt_ids, served_ids)
    return _gap(lg, jnp.asarray(np.asarray(served_ids, np.int32)))


def control_gap(params, m: dict, prompt_ids, served_ids,
                mode: str) -> np.ndarray:
    """The control: at each position of the same prompt and tokens, the gap
    of the token that the lower weight precision puts first (weights
    re-quantised as ``reference.py`` does)."""
    lg = logits_for(params, m, prompt_ids, served_ids)
    lo = logits_for(params, m, prompt_ids, served_ids, mode=mode)
    return _gap(lg, jnp.argmax(lo, axis=-1))


# ----------------------------------------------------------------- counts


def _applied(m: dict) -> dict:
    """The sizes with every layer APPLICATION counted as a layer: what
    ``flops.py`` counts per layer (a token's matmuls, its attention, the
    K and V it holds, the weights a step reads) a looped stack pays once a
    pass; the head is counted once either way."""
    return {**m, "n_layers": m["n_layers"] * m["n_loops"]}


def cache_layers(m: dict) -> int:
    return m["n_layers"] * m["n_loops"]


def matmul_params(m: dict) -> int:
    return _flops.matmul_params(_applied(m))


def attn_flops(m: dict, n_new: int, n_ctx: int = 0) -> float:
    return _flops.attn_flops(_applied(m), n_new, n_ctx)


def prefill_flops(m: dict, n_tokens: int, head_tokens: int | None = None,
                  kv_start: int = 0) -> float:
    return _flops.prefill_flops(_applied(m), n_tokens, head_tokens, kv_start)


def request_flops(m: dict, n_prompt_computed: int, n_cached: int,
                  n_generated: int) -> float:
    return _flops.request_flops(_applied(m), n_prompt_computed, n_cached,
                                n_generated)


def kv_bytes_per_token(m: dict) -> float:
    return _flops.kv_bytes_per_token(_applied(m))


def weight_stream_bytes(m: dict) -> float:
    """Weight bytes one decode step reads: the stack once a pass, the head
    once (the tree itself holds each layer once: ``weight_bytes``)."""
    return _flops.weight_stream_bytes(_applied(m))


def head_bytes(m: dict) -> float:
    return m["dim"] * m["vocab_size"] * (1 if m["weights"] == "int8" else 2)


def layer_bytes(m: dict) -> float:
    """Matmul weight bytes of one layer application."""
    return (weight_stream_bytes(m) - head_bytes(m)) / cache_layers(m)


def decode_step_bytes(m: dict, live_tokens: int) -> float:
    return _flops.decode_step_bytes(_applied(m), live_tokens)


def decode_attn_bytes(m: dict, live_tokens: int) -> float:
    return _flops.decode_attn_bytes(_applied(m), live_tokens)
