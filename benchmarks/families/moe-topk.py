"""The routed family the program serves today (``ModelConfig.n_experts > 0``,
``lmrs_tpu/ops/moe.py``): the dense family's attention, and in place of its
feed-forward ``E`` SwiGLU experts behind a linear router, softmax over all
experts, the top ``k`` kept and their gates renormalised to sum to 1 (the
Mixtral block; published keys ``num_local_experts``, ``num_experts_per_tok``,
``intermediate_size`` = one expert's width).

It is a family of its own, and the worked example of one (README.md, "Adding
things"): its own reading of the keys, weight tree (``moe`` leaves in place
of ``mlp``), reference and counts.  Where its block is the dense block it
builds on the dense files (the draws of weights.py; the norm, the rotary
embedding, the control's re-quantisation and the padding buckets of
reference.py; the attention counts of flops.py), which no later PR may edit.

Capacity: the program gives each expert ``capacity_factor * N * k / E`` token
slots a call and drops what overflows.  A plain reference has no capacity, so
``model_config`` sets the factor to ``E / k``: every expert can take every
token and nothing is dropped (the configuration says so under ``assumed``).

Routing is a discrete choice.  Where a token's k-th and (k+1)-th router
logits lie closer than bfloat16 resolves, the program sends it to another
expert than this float32 reference does, and that token's logits then differ
by a whole expert's output, not by rounding: that is the architecture, and no
precision's fault.  So this family does not compare such a token, by a rule
on the reference alone: ``served_gap`` and ``control_gap`` give NaN where, in
any layer, the float32 margin between the k-th and the (k+1)-th router logit
at the position that predicts the token is under ``ROUTE_MARGIN``.  Every
other served token is compared in full (PERF.md section 6 has the readings
the margin was set from, and why the tokens behind such a one stay in).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from families import bench_file

_w = bench_file("weights")
_ref = bench_file("reference")
_flops = bench_file("flops")

HI, F32 = _ref.HI, _ref.F32

# Router logits are of unit size here (a normed row against columns drawn
# with fan-in D); the program's rows are bfloat16, 2**-8 a rounding, a dozen
# roundings a layer deep.  The readings it was set from (PERF.md section 6):
# of the 56 routings that the program's plain forward pass made otherwise
# than this reference over 20 seeds x 256 tokens x 2 layers, the 49 that
# were a token's first had margins of 0.0000-0.0499 (the others followed
# one in the layer before); of the engine's served tokens over 16 seeds x
# 224, every one off the reference's choice by more than 0.04 had a margin
# under 0.025.  2**-4 leaves out 6-26% of a sample's tokens.
ROUTE_MARGIN = 2.0 ** -4

weight_bytes = _w.weight_bytes
attn_flops = _flops.attn_flops
kv_bytes_per_token = _flops.kv_bytes_per_token
decode_attn_bytes = _flops.decode_attn_bytes


def sizes(cfg: dict) -> dict:
    if cfg["engine"]["weights"] != "bfloat16" or cfg.get("tie_word_embeddings"):
        raise SystemExit("families/moe-topk.py draws bfloat16 weights and an "
                         "untied head only")
    heads = cfg["num_attention_heads"]
    return {
        "dim": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": heads,
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg.get("head_dim") or cfg["hidden_size"] // heads,
        "hidden_dim": cfg["intermediate_size"],  # one expert's width
        "n_experts": cfg["num_local_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "vocab_size": cfg["vocab_size"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "weights": cfg["engine"]["weights"],
        "kv": cfg["engine"]["kv"],
        "page_size": cfg["engine"]["page_size"],
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
        norm_eps=m["norm_eps"], tie_embeddings=False, dtype="bfloat16",
        head_dim=m["head_dim"], n_experts=m["n_experts"],
        n_experts_per_token=m["top_k"],
        # no token dropped: see the module's docstring
        expert_capacity_factor=m["n_experts"] / m["top_k"])


# ---- weights ---------------------------------------------------------------

def _builder(m: dict):
    D, L, E = m["dim"], m["n_layers"], m["n_experts"]
    H, K, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    F, V = m["hidden_dim"], m["vocab_size"]

    def proj(key, shape, fan_in):
        return _w._stacked(key, L, lambda k: _w._draw_bf16(k, shape, fan_in))

    def build(key):
        ks = jax.random.split(key, 13)
        return {
            "embed": {"weight": _w._draw_bf16(ks[0], (V, D), D)},
            "layers": {
                "ln_attn": {"scale": _w._draw_norm(ks[9], (L, D))},
                "ln_mlp": {"scale": _w._draw_norm(ks[10], (L, D))},
                "attn": {
                    "wq": proj(ks[1], (D, H, hd), D),
                    "wk": proj(ks[2], (D, K, hd), D),
                    "wv": proj(ks[3], (D, K, hd), D),
                    "wo": proj(ks[4], (H, hd, D), H * hd),
                },
                "moe": {
                    "router": proj(ks[12], (D, E), D),
                    "w_gate": proj(ks[5], (E, D, F), D),
                    "w_up": proj(ks[6], (E, D, F), D),
                    "w_down": proj(ks[7], (E, F, D), F),
                },
            },
            "final_norm": {"scale": _w._draw_norm(ks[11], (D,))},
            "lm_head": {"weight": _w._draw_bf16(ks[8], (D, V), D)},
        }

    return build


def param_shapes(m: dict):
    return jax.eval_shape(_builder(m), _w._key(0))


def make_params(m: dict, seed: int, shardings=None):
    return jax.jit(_builder(m), out_shardings=shardings)(_w._key(seed))


def param_specs(m: dict):
    return None  # no sharded configuration of this family has been proven


def cache_buffers(sched) -> list:
    return [sched.cache.k, sched.cache.v, sched.kscale, sched.vscale]


# ---- the plain reference -----------------------------------------------------

def _route(h, router, top_k):
    """[S, E] float32: each token's gate on each expert, 0 off its top k;
    the experts chosen, [S, k]; and the margin of the choice, [S]: how far
    the k-th router logit lies above the (k+1)-th."""
    logits = jnp.einsum("sd,de->se", h, router.astype(F32), precision=HI)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, top_k)
    vals = vals / vals.sum(axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(idx, probs.shape[-1], dtype=F32)  # [S, k, E]
    ranked = jax.lax.top_k(logits, top_k + 1)[0]
    return (jnp.einsum("sk,ske->se", vals, onehot, precision=HI), idx,
            ranked[:, top_k - 1] - ranked[:, top_k])


@partial(jax.jit, static_argnames=("dims", "mode"))
def _forward(params, ids, rows, *, dims, mode):
    """reference.py's ``_forward`` with the routed feed-forward: every expert
    computed on every token, the router's gates as the mask.  Also returns
    the experts chosen, [L, S, k], for the count of routes that differ, and
    the margin of each choice, [L, S]."""
    n_heads, n_kv, hd, theta, eps, top_k = dims
    rep = n_heads // n_kv
    s = ids.shape[0]
    x = params["embed"]["weight"][ids].astype(F32)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]  # [q, k]
    wt = _ref._weight

    def layer(x, lp):
        a, e = lp["attn"], lp["moe"]
        h = _ref._rms(x, lp["ln_attn"]["scale"], eps)
        q = jnp.einsum("sd,dhk->shk", h, wt(a["wq"], (0,), mode), precision=HI)
        k = jnp.einsum("sd,dhk->shk", h, wt(a["wk"], (0,), mode), precision=HI)
        v = jnp.einsum("sd,dhk->shk", h, wt(a["wv"], (0,), mode), precision=HI)
        q = _ref._rope(q, theta).reshape(s, n_kv, rep, hd)
        k = _ref._rope(k, theta)
        logit = jnp.einsum("skrd,tkd->krst", q, k, precision=HI) * hd ** -0.5
        logit = jnp.where(causal[None, None], logit, -1e30)
        p = jax.nn.softmax(logit, axis=-1)
        o = jnp.einsum("krst,tkd->skrd", p, v, precision=HI)
        o = o.reshape(s, n_heads, hd)
        x = x + jnp.einsum("shk,hkd->sd", o, wt(a["wo"], (0, 1), mode),
                           precision=HI)
        h = _ref._rms(x, lp["ln_mlp"]["scale"], eps)
        # the router keeps its precision under the control, as it does on
        # the program's own int8 weight path (ops/quant.py)
        gates, chosen, margin = _route(h, e["router"], top_k)
        g = jnp.einsum("sd,edf->esf", h, wt(e["w_gate"], (1,), mode),
                       precision=HI)
        u = jnp.einsum("sd,edf->esf", h, wt(e["w_up"], (1,), mode),
                       precision=HI)
        y = jnp.einsum("esf,efd->esd", jax.nn.silu(g) * u,
                       wt(e["w_down"], (1,), mode), precision=HI)
        x = x + jnp.einsum("se,esd->sd", gates, y, precision=HI)
        return x, (chosen, margin)

    x, (chosen, margin) = jax.lax.scan(layer, x, params["layers"])
    x = _ref._rms(x[rows], params["final_norm"]["scale"], eps)
    return jnp.einsum("rd,dv->rv", x,
                      wt(params["lm_head"]["weight"], (0,), mode),
                      precision=HI), chosen, margin


def _logits_and_margins(params, m: dict, prompt_ids, served_ids, mode=None):
    """[n, V] logits, row j predicting ``served_ids[j]`` from prompt +
    served[:j], and [n] the smallest router margin over the layers at row
    j's position."""
    ids = list(prompt_ids) + list(served_ids)
    n_p, n_s = len(prompt_ids), len(served_ids)
    ids_a = np.zeros((_ref._bucket(len(ids)),), np.int32)
    ids_a[: len(ids)] = ids
    rows = np.full((_ref._bucket(n_s, 64),), n_p - 1, np.int32)
    rows[:n_s] = np.arange(n_p - 1, n_p - 1 + n_s)
    dims = (m["n_heads"], m["n_kv_heads"], m["head_dim"],
            float(m["rope_theta"]), float(m["norm_eps"]), m["top_k"])
    out, _, margin = _forward(params, jnp.asarray(ids_a), jnp.asarray(rows),
                              dims=dims, mode=mode)
    return out[:n_s], margin.min(axis=0)[rows[:n_s]]


def logits_for(params, m: dict, prompt_ids, served_ids, mode=None):
    """reference.logits_for for this family."""
    return _logits_and_margins(params, m, prompt_ids, served_ids, mode)[0]


def _gap(lg, pick, margin) -> np.ndarray:
    """How far ``pick``'s logit lies under the best; NaN where the route is
    too close a call to compare (the module's docstring)."""
    gap = lg.max(axis=-1) - jnp.take_along_axis(lg, pick[:, None], 1)[:, 0]
    return np.asarray(jax.device_get(
        jnp.where(margin < ROUTE_MARGIN, jnp.nan, gap)))


def served_gap(params, m: dict, prompt_ids, served_ids) -> np.ndarray:
    lg, margin = _logits_and_margins(params, m, prompt_ids, served_ids)
    return _gap(lg, jnp.asarray(np.asarray(served_ids, np.int32)), margin)


def control_gap(params, m: dict, prompt_ids, served_ids,
                mode: str) -> np.ndarray:
    lg, margin = _logits_and_margins(params, m, prompt_ids, served_ids)
    lo = logits_for(params, m, prompt_ids, served_ids, mode=mode)
    return _gap(lg, jnp.argmax(lo, axis=-1), margin)


# ---- counts ------------------------------------------------------------------

def _attn_params(m: dict) -> int:
    d, hd = m["dim"], m["head_dim"]
    return (d * m["n_heads"] * hd + 2 * d * m["n_kv_heads"] * hd
            + m["n_heads"] * hd * d)


def _expert_params(m: dict) -> int:
    return 3 * m["dim"] * m["hidden_dim"]


def matmul_params(m: dict) -> int:
    """Parameters held in matmul weights: attention, router, every expert,
    and the LM head (the tree's leaves but the embedding and the norms)."""
    per_layer = (_attn_params(m) + m["dim"] * m["n_experts"]
                 + m["n_experts"] * _expert_params(m))
    return m["n_layers"] * per_layer + m["dim"] * m["vocab_size"]


def active_params(m: dict) -> int:
    """Parameters one token multiplies: ``top_k`` of the experts."""
    return matmul_params(m) - m["n_layers"] * (
        m["n_experts"] - m["top_k"]) * _expert_params(m)


def prefill_flops(m: dict, n_tokens: int, head_tokens: int | None = None,
                  kv_start: int = 0) -> float:
    head = m["dim"] * m["vocab_size"]
    fl = 2.0 * (active_params(m) - head) * n_tokens
    fl += 2.0 * (n_tokens if head_tokens is None else head_tokens) * head
    return fl + attn_flops(m, n_tokens, kv_start)


def request_flops(m: dict, n_prompt_computed: int, n_cached: int,
                  n_generated: int) -> float:
    """flops.request_flops over the active parameters."""
    head = m["dim"] * m["vocab_size"]
    n_prompt = n_cached + n_prompt_computed
    fl = 2.0 * (active_params(m) - head) * (n_prompt_computed + n_generated)
    fl += 2.0 * head * max(n_generated, 1)
    fl += attn_flops(m, n_prompt_computed, n_cached)
    keys = n_generated * n_prompt + n_generated * (n_generated + 1) / 2.0
    return fl + 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * keys


def weight_stream_bytes(m: dict) -> float:
    """A decode step over a batch reads every expert: its rows' routes
    cover them all well before ``n_experts / top_k`` rows."""
    return matmul_params(m) * 2


def decode_step_bytes(m: dict, live_tokens: int) -> float:
    return weight_stream_bytes(m) + kv_bytes_per_token(m) * live_tokens
