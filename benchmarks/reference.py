"""The plain reference: the decoder's forward pass in float32.

Straight ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no cache, no
batching, one sequence at a time, the layers scanned so that one layer's
float32 weights exist at a time.  It imports nothing of the program and
takes from it nothing the program made: the weights are the arrays the
benchmark drew from the seed (weights.py), the ids are the benchmark's
own encoding of the prompt plus the ids the client was served.

Published equations (Mistral-7B / InternLM2, Llama-style block): pre-norm
RMSNorm with weight (1 + scale) as the engine stores it (weights.py draws it),
rotary embeddings in the split-halves convention over head_dim, grouped
query attention (query head h reads kv head h // (H / K)), causal softmax
in float32, SwiGLU feed-forward, final RMSNorm, untied LM head.
Departures: none in the mathematics; int8 KV pages (a storage choice of
the mistral configuration) are not modelled, the reference keeps K and V
exact, so what int8 pages cost shows in the program's gap.

``mode`` puts the reference in the program's place at a lower precision,
for the control that has to fail (run.py --control): ``int4`` / ``int8``
re-quantise every projection per output channel, ``fp8`` rounds it to
float8_e4m3.  Activations stay float32: the control is weight-only, as
the engine's own lower-precision path is.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _weight(leaf, contract, mode):
    """float32 weight of a leaf (int8 ``{"q","s"}`` or bf16 array), put
    through the control's precision when ``mode`` asks for one."""
    if isinstance(leaf, dict):
        w = leaf["q"].astype(F32) * leaf["s"].astype(F32)
    else:
        w = leaf.astype(F32)
    if mode in ("int4", "int8"):
        top = 7.0 if mode == "int4" else 127.0
        s = jnp.maximum(jnp.max(jnp.abs(w), axis=contract, keepdims=True)
                        / top, 1e-12)
        w = jnp.clip(jnp.round(w / s), -top, top) * s
    elif mode == "fp8":
        w = w.astype(jnp.float8_e4m3fn).astype(F32)
    elif mode is not None:
        raise ValueError(f"unknown control mode {mode!r}")
    return w


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + scale.astype(F32))


def _rope(x, theta):
    """x [S, heads, hd]; position of row i is i."""
    s, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("dims", "mode"))
def _forward(params, ids, rows, *, dims, mode):
    """ids [S] int32 (padded past the real length; causal, so the padding
    reaches no earlier row); rows [R]: positions whose logits are wanted.
    Returns logits [R, V] float32."""
    n_heads, n_kv, hd, theta, eps = dims
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
        x = x + jnp.einsum("shk,hkd->sd", o, _weight(a["wo"], (0, 1), mode),
                           precision=HI)
        h = _rms(x, lp["ln_mlp"]["scale"], eps)
        g = jnp.einsum("sd,df->sf", h, _weight(m["w_gate"], (0,), mode),
                       precision=HI)
        u = jnp.einsum("sd,df->sf", h, _weight(m["w_up"], (0,), mode),
                       precision=HI)
        ff = jax.nn.silu(g) * u
        x = x + jnp.einsum("sf,fd->sd", ff, _weight(m["w_down"], (0,), mode),
                           precision=HI)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x[rows], params["final_norm"]["scale"], eps)
    return jnp.einsum("rd,dv->rv", x,
                      _weight(params["lm_head"]["weight"], (0,), mode),
                      precision=HI)


def _bucket(n: int, lo: int = 256) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def logits_for(params, model: dict, prompt_ids, served_ids, mode=None):
    """Reference logits at the positions that produced each served token:
    row j predicts ``served_ids[j]`` from prompt + served[:j].  [n, V]."""
    ids = list(prompt_ids) + list(served_ids)
    n_p, n_s = len(prompt_ids), len(served_ids)
    s_pad = _bucket(len(ids))
    r_pad = _bucket(n_s, 64)
    ids_a = np.zeros((s_pad,), np.int32)
    ids_a[: len(ids)] = ids
    rows = np.full((r_pad,), n_p - 1, np.int32)
    rows[:n_s] = np.arange(n_p - 1, n_p - 1 + n_s)
    dims = (model["n_heads"], model["n_kv_heads"], model["head_dim"],
            float(model["rope_theta"]), float(model["norm_eps"]))
    out = _forward(params, jnp.asarray(ids_a), jnp.asarray(rows),
                   dims=dims, mode=mode)
    return out[:n_s]


def served_gap(params, model: dict, prompt_ids, served_ids) -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 where the served token is the
    reference's own greedy choice).  [n] float32, >= 0."""
    lg = logits_for(params, model, prompt_ids, served_ids)
    served = jnp.asarray(np.asarray(served_ids, np.int32))
    gap = lg.max(axis=-1) - jnp.take_along_axis(lg, served[:, None], 1)[:, 0]
    return np.asarray(jax.device_get(gap))


def control_gap(params, model: dict, prompt_ids, served_ids,
                mode: str) -> np.ndarray:
    """The control: at each position of the same prompt and tokens, the gap
    of the token that the lower precision puts first."""
    lg = logits_for(params, model, prompt_ids, served_ids)
    lo = logits_for(params, model, prompt_ids, served_ids, mode=mode)
    pick = jnp.argmax(lo, axis=-1)
    gap = lg.max(axis=-1) - jnp.take_along_axis(lg, pick[:, None], 1)[:, 0]
    return np.asarray(jax.device_get(gap))
