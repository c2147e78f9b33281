"""Weights on the device, from the seed, in the type they are served in.

One jitted call makes the whole tree in the layout the engine takes
(``lmrs_tpu/models/transformer.py`` ``init_params``: stacked layers, wq
[L, D, H, hd] ...).  int8 leaves are ``{"q": int8, "s": f32}`` with one
scale per output channel (the engine's weight-only scheme), each drawn
from the seed, log-uniform within a factor of ``exp(_S_SPREAD)`` around the
value at which the effective weight ``q * s`` has the fan-in standard
deviation a trained model's has, so activations keep their scale through
32 layers and a scale read from the wrong channel or axis shows.  Norm
scales (the engine stores ``weight - 1``) are drawn too, so a norm weight
that is skipped shows.  Stacked leaves are drawn layer by layer inside the
call (``lax.map``), so the generator's temporaries are one layer's, not
one leaf's.  ``shardings`` (a pytree like the result) places each leaf as
it is drawn: a sharded model never exists whole on one chip.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_Q_STD = 73.32  # std of an integer uniform on [-127, 127]
_S_SPREAD = 0.35  # per-channel scales: exp(U(-0.35, 0.35)) around fan-in
_NORM_SPREAD = 0.25  # norm scales: U(-0.25, 0.25) around 0 (weight 1)


def _draw_bf16(key, shape, fan_in):
    w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
    return w.astype(jnp.bfloat16)


def _draw_q(key, shape):
    return jax.random.randint(key, shape, -127, 128, dtype=jnp.int8)


def _stacked(key, n_layers, fn):
    return jax.lax.map(fn, jax.random.split(key, n_layers))


def _scale_shape(shape, contract_axes):
    return tuple(1 if a in contract_axes else n for a, n in enumerate(shape))


def _draw_scale(key, shape, fan_in):
    u = jax.random.uniform(key, shape, jnp.float32, -_S_SPREAD, _S_SPREAD)
    return jnp.exp(u) / (_Q_STD * math.sqrt(fan_in))


def _draw_norm(key, shape):
    return jax.random.uniform(key, shape, jnp.float32, -_NORM_SPREAD,
                              _NORM_SPREAD).astype(jnp.bfloat16)


def _builder(model: dict):
    D, L = model["dim"], model["n_layers"]
    H, K, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    F, V = model["hidden_dim"], model["vocab_size"]
    int8 = model["weights"] == "int8"

    def proj(key, shape, fan_in, contract):
        """One stacked projection [L, *shape]; ``contract`` are the axes
        (of the stacked tensor) its matmul contracts."""
        if not int8:
            return _stacked(key, L, lambda k: _draw_bf16(k, shape, fan_in))
        kq, ks = jax.random.split(key)
        q = _stacked(kq, L, lambda k: _draw_q(k, shape))
        s = _draw_scale(ks, _scale_shape((L, *shape), contract), fan_in)
        return {"q": q, "s": s}

    def build(key):
        ks = jax.random.split(key, 12)
        params = {
            "embed": {"weight": _draw_bf16(ks[0], (V, D), D)},
            "layers": {
                "ln_attn": {"scale": _draw_norm(ks[9], (L, D))},
                "ln_mlp": {"scale": _draw_norm(ks[10], (L, D))},
                "attn": {
                    "wq": proj(ks[1], (D, H, hd), D, (1,)),
                    "wk": proj(ks[2], (D, K, hd), D, (1,)),
                    "wv": proj(ks[3], (D, K, hd), D, (1,)),
                    "wo": proj(ks[4], (H, hd, D), H * hd, (1, 2)),
                },
                "mlp": {
                    "w_gate": proj(ks[5], (D, F), D, (1,)),
                    "w_up": proj(ks[6], (D, F), D, (1,)),
                    "w_down": proj(ks[7], (F, D), F, (1,)),
                },
            },
            "final_norm": {"scale": _draw_norm(ks[11], (D,))},
        }
        if int8:
            kq, ksc = jax.random.split(ks[8])
            params["lm_head"] = {"weight": {
                "q": _draw_q(kq, (D, V)),
                "s": _draw_scale(ksc, (1, V), D)}}
        else:
            params["lm_head"] = {"weight": _draw_bf16(ks[8], (D, V), D)}
        return params

    return build


def _key(seed: int):
    # seeds run past 2**31: fold the high bits in instead of overflowing
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def param_shapes(model: dict):
    """The tree's shapes and types, nothing drawn (to match shardings)."""
    return jax.eval_shape(_builder(model), _key(0))


def make_params(model: dict, seed: int, shardings=None):
    """``model``: the sizes of a configuration file (see run.model_sizes)."""
    return jax.jit(_builder(model), out_shardings=shardings)(_key(seed))


def weight_bytes(params) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
