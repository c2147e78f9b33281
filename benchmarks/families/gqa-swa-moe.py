"""Window and full GQA attention over routed experts: the block that
``exaone_moe`` configurations publish (EXAONE-4's decoder layer with the
DeepSeek-V3 routed layer behind it), as one chip of a deployment holds it.

**The published keys** and what they mean here (``sizes``): ``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim`` (GQA: query
head h reads kv head h // (H / K)); ``sliding_window`` and ``sliding_windows``
(per layer; 0 is a full layer; ``layer_types`` and ``sliding_window_pattern``
say the same and are checked against it); ``rope_parameters.rope_theta``;
``first_k_dense_replace`` leading layers with a dense SwiGLU of
``intermediate_size``, the others routed (``mlp_layer_types``):
``scoring_func`` sigmoid, ``n_group`` = ``topk_group`` = 1,
``num_experts_per_tok``, ``norm_topk_prob``, ``routed_scaling_factor``,
``num_shared_experts``, every expert SwiGLU of ``moe_intermediate_size``.
``num_hidden_layers`` is the layers HELD HERE (the per-layer lists keep
their published length; the first ``num_hidden_layers`` entries are read),
``num_experts`` the experts held here and ``vocab_size`` the rows of the
vocabulary held here (all three in ``reduced``); the file's ``published``
block states the model's own counts (the router stays that wide) and
``share`` which experts these are (``expert_first``).

**The equations**, for layer l with window w_l (0: full), input x [S, D]
(RMSNorm with weight 1 + scale as the engine stores it; float32,
``HIGHEST``):

    q = x Wq, k = x Wk, v = x Wv              (no norm on a sublayer's input: A1)
    q = RMSNorm(q; g_q), k = RMSNorm(k; g_k)  over each head's width          (A2)
    q, k = RoPE(q, k; theta, split halves) if w_l > 0 else q, k               (A3)
    p_ij = softmax_j(q_i . k_j / sqrt(hd)) over j <= i and (w_l == 0 or i - j < w_l)
    x = x + RMSNorm((sum_j p_ij v_j) Wo; g_post_attn)     (norm on the OUTPUT: A1)
    m = SwiGLU_dense(x) on a leading layer, else
        sum over chosen i HELD HERE of w_i E_i(x) + E_shared(x),
        sc = sigmoid(x Wr); the top k of sc + b chosen;
        w_i = sc_i / (sum of the chosen sc + 1e-20) * routed_scaling_factor
    x = x + RMSNorm(m; g_post_mlp)                                             (A1)
    logits = RMSNorm(x; g_final) W_head

A1-A3 are the family's published block (``Exaone4DecoderLayer`` /
``Exaone4Attention``); the configuration file lists them under ``assumed``.

**What is left out, in the program and here alike**: what the absent experts
would add to a routed layer, the layers beyond the cut, the vocabulary
outside the slice, and the multi-token-prediction module (a draft head; the
model's own logits do not pass through it).  Full softmax over a masked
[S, S] score matrix a head, every held expert on every token with the
router's gate as the mask, one layer's float32 weights at a time (and of a
routed layer one expert's); nothing of the program is imported for it.

**From families/mla-moe.py, by import**: the fit of the selection bias
(``_fit_bias``, ``_loads``), the route and the rule for routes too close to
call (``route``, ``ROUTE_MARGIN``), the calibration sample
(``calibration_ids``), the numbers compared (``gap_stats``, ``_gap``) and
the constants of the dealing; its docstring and its section "the selection
bias, and which experts a chip holds" argue them.  The dealing itself
(``_fit_and_deal``, ``_decode_shares``) passes the sample through THIS
block's equations (the router here reads the un-normed residual), so it is
written out below, as mla-moe.py has it but for what this cell's readings
forced (the section of that name below): the walks of greedy text see in a
window layer what a decode step sees there, a calibration prompt's
instructions are weighed as the cell's prompts hold them, and this chip is
made typical band by band where mla-moe.py picks the most typical of 32:
on map prompts of the cell's own length and make (``cell_ids``), layer by
layer behind the layers as it then holds them, and by the experts a decode
step's rows TOUCH, which is what a step reads, beside their pairs.

**Counts are of the work the architecture asks for**: a window layer's
attention is counted over its band (``_keys``), its cache over
``min(ctx, window)`` positions.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import families
from families import bench_file

_w = bench_file("weights")
_ref = bench_file("reference")
_mla = families.load_module(families.HERE / "mla-moe.py",
                            "bench_family_mla_moe")

HI, F32 = _ref.HI, _ref.F32
weight_bytes = _w.weight_bytes
gap_stats = _mla.gap_stats


def sizes(cfg: dict) -> dict:
    if cfg["engine"]["weights"] != "bfloat16" or cfg.get("tie_word_embeddings"):
        raise SystemExit("families/gqa-swa-moe.py draws bfloat16 weights and "
                         "an untied head only")
    if (cfg["scoring_func"] != "sigmoid" or cfg["n_group"] != 1
            or cfg["topk_group"] != 1 or not cfg["norm_topk_prob"]):
        raise SystemExit("families/gqa-swa-moe.py: sigmoid scores, one "
                         "group, normalised top-k weights")
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    windows = tuple(int(w) for w in cfg["sliding_windows"][:n])
    pattern = cfg["sliding_window_pattern"]
    window = int(cfg["sliding_window"])
    if (windows != tuple(window if pattern[i % len(pattern)] == "L" else 0
                         for i in range(n))
            or [t == "sliding_attention" for t in cfg["layer_types"][:n]]
            != [w > 0 for w in windows]
            or [t == "dense" for t in cfg["mlp_layer_types"][:n]]
            != [i < dense for i in range(n)]):
        raise SystemExit("families/gqa-swa-moe.py: sliding_windows, "
                         "layer_types, sliding_window_pattern and "
                         "mlp_layer_types disagree")
    pub, share = cfg["published"], cfg["share"]
    return {
        "dim": cfg["hidden_size"],
        "n_layers": n,
        "n_dense_layers": dense,
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "windows": windows,
        "window": window,
        "pattern": pattern,
        "dense_hidden": cfg["intermediate_size"],
        "hidden_dim": cfg["moe_intermediate_size"],  # one expert's width
        "n_router": pub["num_experts"],             # the router's width
        "n_held": cfg["num_experts"],               # experts held here
        "expert_first": share["expert_first"],
        "n_shared": cfg["num_shared_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "route_scale": float(cfg["routed_scaling_factor"]),
        "vocab_size": cfg["vocab_size"],
        "calibration": {**_mla.CALIBRATION, **CALIBRATION,
                        **cfg.get("calibration", {})},
        "rope_theta": float(cfg["rope_parameters"]["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "weights": cfg["engine"]["weights"],
        "kv": cfg["engine"]["kv"],
        "page_size": cfg["engine"]["page_size"],
        "settings": dict(cfg["engine"].get("settings", {})),
        "mesh": dict(cfg["engine"].get("mesh", {})),
    }


def model_config(name: str, m: dict, eng: dict):
    from lmrs_tpu.config import WindowMoEModelConfig

    return WindowMoEModelConfig(
        name=name, vocab_size=m["vocab_size"], dim=m["dim"],
        n_layers=m["n_layers"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], head_dim=m["head_dim"],
        hidden_dim=m["hidden_dim"], dense_hidden_dim=m["dense_hidden"],
        max_seq_len=eng["max_seq_len"], rope_theta=m["rope_theta"],
        norm_eps=m["norm_eps"], tie_embeddings=False, dtype="bfloat16",
        sliding_window=m["window"], window_pattern=m["pattern"],
        norm_inputs=False, sandwich_norm=True, qk_norm=True,
        rope_window_only=True, n_dense_layers=m["n_dense_layers"],
        n_routed_experts=m["n_router"], n_shared_experts=m["n_shared"],
        n_experts_per_token=m["top_k"],
        routed_scaling_factor=m["route_scale"],
        expert_first=m["expert_first"], n_experts_held=m["n_held"])


# ---- weights ---------------------------------------------------------------

def _builder(m: dict):
    D, H, K, hd = m["dim"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    F, V, E, Eh = m["hidden_dim"], m["vocab_size"], m["n_router"], m["n_held"]

    def group(key, n, routed):
        def proj(k, shape, fan_in):
            return _w._stacked(k, n, lambda kk: _w._draw_bf16(kk, shape, fan_in))

        def mlp(ks, lead, f):
            return {"w_gate": proj(ks[0], (*lead, D, f), D),
                    "w_up": proj(ks[1], (*lead, D, f), D),
                    "w_down": proj(ks[2], (*lead, f, D), f)}

        ks = jax.random.split(key, 20)
        g = {
            "ln_attn_out": {"scale": _w._draw_norm(ks[0], (n, D))},
            "ln_mlp_out": {"scale": _w._draw_norm(ks[1], (n, D))},
            "attn": {
                "wq": proj(ks[2], (D, H, hd), D),
                "wk": proj(ks[3], (D, K, hd), D),
                "wv": proj(ks[4], (D, K, hd), D),
                "wo": proj(ks[5], (H, hd, D), H * hd),
                "q_norm": _w._draw_norm(ks[6], (n, hd)),
                "k_norm": _w._draw_norm(ks[7], (n, hd)),
            },
        }
        if not routed:
            g["mlp"] = mlp(ks[8:11], (), m["dense_hidden"])
            return g
        g["moe"] = {
            "router": proj(ks[11], (D, E), D),
            "bias": _mla._BIAS_STD * jax.random.normal(ks[12], (n, E), F32),
            **mlp(ks[13:16], (Eh,), F),
        }
        if m["n_shared"]:
            g["shared"] = mlp(ks[16:19], (), F * m["n_shared"])
        return g

    def build(key, sample_ids, cell):
        ks = jax.random.split(key, 5)
        n_routed = m["n_layers"] - m["n_dense_layers"]
        # the embedding at std 2, as mla-moe.py draws it and for its reason:
        # every sublayer adds a normed output of unit size, and a token's
        # row has to stay visible beside them
        params = {"embed": {"weight": _w._draw_bf16(ks[0], (V, D),
                                                    _mla.EMBED_FAN_IN)}}
        if m["n_dense_layers"]:
            params["dense_layers"] = group(ks[1], m["n_dense_layers"], False)
        params["layers"] = group(ks[2], n_routed, True)
        params["final_norm"] = {"scale": _w._draw_norm(ks[3], (D,))}
        # the head's columns of pad, BOS and EOS (ids 0-2) are zero: a logit
        # of 0 is never the largest of 19,200, so no request ends before its
        # budget.  Greedy text of random weights has favourite ids, and on a
        # seed where EOS was one of them requests ended early, waves lost
        # rows and a prefill rung the warm-up had not met compiled inside
        # the window (PERF.md section 6, PR 35)
        head = _w._draw_bf16(ks[4], (D, V), D)
        params["lm_head"] = {"weight": head.at[:, :3].set(0)}
        if m["n_router"] % m["n_held"]:
            return params  # no whole number of chips to deal to
        return _fit_and_deal(params, m, sample_ids, cell,
                             jax.random.fold_in(key, 1))

    return build


def param_shapes(m: dict):
    return jax.eval_shape(_builder(m), _w._key(0),
                          _mla.calibration_ids(0, m), cell_ids(0, m))


def make_params(m: dict, seed: int, shardings=None):
    return jax.jit(_builder(m), out_shardings=shardings)(
        _w._key(seed), _mla.calibration_ids(seed, m), cell_ids(seed, m))


def param_specs(m: dict):
    return None  # one chip's share: nothing here is sharded


def cache_buffers(sched) -> list:
    """The K and V pools (both kinds of layer in each); no scales."""
    return [sched.cache.k, sched.cache.v, None, None]


# ---- the block ---------------------------------------------------------------

def _dims(m: dict) -> tuple:
    return (m["n_heads"], m["n_kv_heads"], m["head_dim"],
            float(m["rope_theta"]), float(m["norm_eps"]), m["top_k"],
            m["route_scale"], m["expert_first"], m["n_held"])


def _groups(params, m: dict):
    """(stacked layer group, its layers' windows [n] int32), model order."""
    wins = np.asarray(m["windows"], np.int32)
    out, first = [], 0
    for name in ("dense_layers", "layers"):
        if name in params:
            n = params[name]["attn"]["wo"].shape[0]
            out.append((name, params[name], jnp.asarray(wins[first:first + n])))
            first += n
    return out


def _rope(x, theta, pos):
    """x [S, heads, hd] at positions ``pos`` [S]; split halves."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[:, None] * freqs[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(dims, mode, exact=True):
    """The two halves of a layer: ``attend(x, lp, w) -> x`` over one
    sequence (causal, window ``w``, 0: full) and ``feed(x, lp) -> (x,
    close)``.  ``attend(x, lp, w, behind=c)`` (the calibration sample only)
    takes rows that each see the sequence ``c`` (that layer's input) and
    themselves, from the position behind it.  ``exact``: float32 weights
    and ``HIGHEST``, wherever an output is compared; without it (the
    calibration sample) the weights as stored and one bfloat16 pass."""
    n_heads, n_kv, hd, theta, eps, top_k, route_scale, first, held = dims
    rep = n_heads // n_kv
    prec = HI if exact else jax.lax.Precision.DEFAULT

    def mm(eq, x, w, lead):
        if exact:
            return jnp.einsum(eq, x, _ref._weight(w, lead, mode), precision=HI)
        return jnp.einsum(eq, x.astype(w.dtype), w, preferred_element_type=F32)

    def swiglu(h, mp):
        g = mm("sd,df->sf", h, mp["w_gate"], (0,))
        u = mm("sd,df->sf", h, mp["w_up"], (0,))
        return mm("sf,fd->sd", jax.nn.silu(g) * u, mp["w_down"], (0,))

    def qkv(x, lp, w, pos):
        a = lp["attn"]
        q = _ref._rms(mm("sd,dhk->shk", x, a["wq"], (0,)), a["q_norm"], eps)
        k = _ref._rms(mm("sd,dhk->shk", x, a["wk"], (0,)), a["k_norm"], eps)
        v = mm("sd,dhk->shk", x, a["wv"], (0,))
        return (jnp.where(w > 0, _rope(q, theta, pos), q),
                jnp.where(w > 0, _rope(k, theta, pos), k), v)

    def attend(x, lp, w, behind=None):
        s = x.shape[0]
        if behind is None:
            pos = jnp.arange(s)
            q, k, v = qkv(x, lp, w, pos)
            back = pos[:, None] - pos[None, :]
            seen = (back >= 0) & ((w <= 0) | (back < w))     # [query, key]

            def head(qh_kv):  # one query head at a time: [S, S] scores
                qh, kh, vh = qh_kv
                sc = jnp.einsum("sd,td->st", qh, kh, precision=prec) * hd ** -0.5
                p = jax.nn.softmax(jnp.where(seen, sc, -1e30), axis=-1)
                return jnp.einsum("st,td->sd", p, vh, precision=prec)

            kv_of = jnp.arange(n_heads) // rep
            o = jax.lax.map(head, (q.transpose(1, 0, 2),
                                   k.transpose(1, 0, 2)[kv_of],
                                   v.transpose(1, 0, 2)[kv_of]))
            o = o.transpose(1, 0, 2)
        else:
            t = behind.shape[0]
            q, k, v = qkv(x, lp, w, jnp.full((s,), t))
            _, k_c, v_c = qkv(behind, lp, w, jnp.arange(t))
            seen = (w <= 0) | (t - jnp.arange(t) < w)        # [key]
            qg = q.reshape(s, n_kv, rep, hd)
            sc = jnp.einsum("skrd,tkd->krst", qg, k_c,
                            precision=prec) * hd ** -0.5
            own = jnp.einsum("skrd,skd->krs", qg, k,
                             precision=prec) * hd ** -0.5
            p = jax.nn.softmax(jnp.concatenate(
                [jnp.where(seen, sc, -1e30), own[..., None]], axis=-1), axis=-1)
            o = (jnp.einsum("krst,tkd->skrd", p[..., :-1], v_c, precision=prec)
                 + p[..., -1].transpose(2, 0, 1)[..., None] * v[:, :, None, :])
            o = o.reshape(s, n_heads, hd)
        y = mm("shk,hkd->sd", o, lp["attn"]["wo"], (0, 1))
        return x + _ref._rms(y, lp["ln_attn_out"]["scale"], eps)

    def feed(x, lp):
        if "moe" not in lp:
            y, close = swiglu(x, lp["mlp"]), jnp.zeros(x.shape[:1], bool)
        else:
            e = lp["moe"]
            # the router keeps its precision under the control, as the
            # program's would (routing decisions are precision-sensitive)
            gates, close = _mla.route(x, e["router"], e["bias"], top_k,
                                      route_scale, first, held)

            def one(y, ge):  # one held expert's float32 weights at a time
                g, ep = ge
                return y + g[:, None] * swiglu(x, ep), None

            y, _ = jax.lax.scan(one, jnp.zeros_like(x), (gates.T, {
                k: e[k] for k in ("w_gate", "w_up", "w_down")}))
            if "shared" in lp:
                y = y + swiglu(x, lp["shared"])
        return x + _ref._rms(y, lp["ln_mlp_out"]["scale"], eps), close

    return attend, feed


# ---- the selection bias, and which experts a chip holds ------------------------
#
# mla-moe.py's section of this name, on this block's equations: the bias is
# fitted by the auxiliary-loss-free rule on the sample's generated-like half
# and the experts are dealt to the chips by load (bands of the transcript
# tokens' load, within a band by the generated-like tokens').  With 8 chips
# where mla-moe.py's deployment has 32, the most typical of the dealt chips
# still lies well off the even share on some seeds (a decode step reads 75 MB
# for every held expert a row's choice lands on), so this chip is not CHOSEN
# among them: it is MADE typical.  Band by band it trades its expert for
# another chip's of the same band (every chip keeps one expert of each
# band) so that what a request pays for its experts, in the decode steps'
# reads over greedy text of the model's own and in its prompt tokens' pairs,
# runs along the even share (``_typical``, ``_make_typical``).

# What a request of the cell pays for its tokens' choices of a held expert,
# seconds (my chip runs, PR 35; PERF.md section 6).  Its 7.4k prompt tokens
# each multiply one more row of a prefill wave (mla-moe.py's 1.65 us a
# pair).  Its 256 decode steps read an expert's 75 MB ONCE A STEP if any
# row of the step chose it, so what a step pays for is the experts its rows
# touch, not their pairs: two seeds' traced cycles, 20,756 pairs with 42% of
# them on one expert and 21,831 with 37%, spent 1.93 and 2.12 s in the decode
# steps' grouped products, 10% apart at pairs 5% apart; about 3 experts
# touched a step and routed layer, 97 us each.  A map request shares its
# steps with the other rows of its wave of ``WAVE_ROWS``; of a job's 12 + 8
# + 2 requests the two reduce requests decode alone (``LONE_SHARE``), and
# there a pair is a read
PROMPT_TOKENS, GENERATED = 7400, 256
READ_S, PREFILL_S = GENERATED * 97e-6, PROMPT_TOKENS * _mla.PREFILL_PAIR_S
WAVE_ROWS, LONE_SHARE = _mla.WALK_PROMPTS, 2 / 22
# of a job's prompt tokens, those that are generated ids and not text: every
# map request's summary comes back once, in a reduce prompt
SUMMARY_SHARE = GENERATED / (PROMPT_TOKENS + GENERATED)
# what a configuration's "calibration" block may set beside mla-moe.py's
# ``CALIBRATION``: the map prompts that this chip is made typical on
# (``cell_ids``): how many, of how many tokens (absent: ``text``), their
# transcript's segments of how many bytes.  The cell's configuration
# states its traffic's own: a prompt's whole length, 450-byte segments
CALIBRATION = {"cell_rows": 2, "cell_segment_bytes": 120}


def cell_ids(seed: int, m: dict) -> np.ndarray:
    """[cell_rows, cell_text] int32: map prompts as the cell's traffic
    writes them, at the cell's own length (the instructions and header,
    then a seeded transcript with a timestamp a segment, cut at the end).
    ``calibration_ids``' rows are a quarter as long, a third instructions,
    and carry a timestamp every 120 bytes where the cell's carry one every
    450; the bias is fitted and the experts are dealt on those, but a chip
    made typical on them sent 1.76-2.04 M pairs a window to its experts on
    six seeds where the cell's prompts should have sent 1.91 M, and
    `job_tok_s` followed (PERF.md section 6): which expert a frequent byte
    takes is a close call on some seeds, and what a layer sees behind the
    byte decides it."""
    gen = families.load_module(
        families.HERE.parent / "traffic" / "offline_jobs.py",
        "bench_traffic_offline_jobs")
    tok = bench_file("tokenizer").IdTokenizer(m["vocab_size"])
    c = m["calibration"]
    n_text, seg = c.get("cell_text", c["text"]), c["cell_segment_bytes"]
    rng = np.random.default_rng([seed, 1])  # not calibration_ids' stream
    rows = []
    for _ in range(c["cell_rows"]):
        segs = gen.synth_transcript(int(rng.integers(1 << 30)),
                                    n_text // seg + 2, (seg, seg))["segments"]
        ids = [tok.bos_id] + tok.encode(_mla._PROMPT_HEAD + " ".join(
            f"[{int(s['start']) // 60:02d}:{int(s['start']) % 60:02d}] "
            f"{s['text']}" for s in segs))
        assert len(ids) >= n_text
        rows.append(ids[:n_text])
    return np.asarray(rows, np.int32)


def _text_weights(n_text: int) -> np.ndarray:
    """[n_text] float32, summing to 1: what each position of a calibration
    prompt counts for in the prompt tokens' loads that the experts are dealt
    by.  A calibration prompt is the map prompt's instructions and header
    (``_PROMPT_HEAD``) and then transcript, 1,792 tokens of which a third is
    instructions; the cell's prompts are 7.4k tokens of which a twelfth is.
    Brackets and capitals take other experts than words do, by the seed's
    weights.  So the instructions' positions share the weight they have in a
    prompt of ``PROMPT_TOKENS`` and the transcript's the rest."""
    head = len(_mla._PROMPT_HEAD.encode()) + 1  # BOS, then one id a byte
    if not 0 < head < min(n_text, PROMPT_TOKENS):
        return np.full((n_text,), 1.0 / n_text, np.float32)
    w = np.full((n_text,), (1.0 - head / PROMPT_TOKENS) / (n_text - head),
                np.float32)
    w[:head] = 1.0 / PROMPT_TOKENS
    return w


def _fit_and_deal(params, m: dict, sample_ids, cell, key):
    held, n_router, top_k = m["n_held"], m["n_router"], m["top_k"]
    chips = n_router // held
    attend, feed = _block(_dims(m), None, exact=False)
    embed = params["embed"]["weight"]
    c = m["calibration"]
    xt = embed[sample_ids[:, :c["text"]]]
    xd = embed[sample_ids[:, c["text"]:]]
    bf16 = embed.dtype
    seqs, pieces, prompts = _mla.SEQS, _mla.PIECES, _mla.WALK_PROMPTS

    def attended(xt, xd, lp, w):
        def seq(ab):
            t, d = ab[0].astype(F32), ab[1]
            behind = jax.lax.map(
                lambda piece: attend(piece.astype(F32), lp, w,
                                     behind=t).astype(bf16),
                d.reshape(pieces, -1, d.shape[-1]))
            return attend(t, lp, w).astype(bf16), behind.reshape(d.shape)

        return jax.lax.map(seq, (xt, xd))

    def fed(xt, xd, lp):
        return jax.lax.map(lambda ab: tuple(
            feed(x.astype(F32), lp)[0].astype(bf16) for x in ab), (xt, xd))

    half = seqs // 2  # rows the bias is fitted on; the rest measure
    text_w = jnp.asarray(_text_weights(c["text"]))

    def text_loads(chosen):  # [seqs, T, k] expert ids -> [E] weighted counts
        w = jnp.broadcast_to(text_w[None, :, None] / chosen.shape[0],
                             chosen.shape)
        return jnp.zeros((n_router,), F32).at[chosen.reshape(-1)].add(
            w.reshape(-1))
    kept = {}  # each layer's input over the walks' prompts
    groups = {name: (g, wins) for name, g, wins in _groups(params, m)}
    if "dense_layers" in groups:
        (xt, xd), kept["dense_layers"] = jax.lax.scan(
            lambda x, a: (fed(*attended(*x, *a), a[0]), x[0][-prompts:]),
            (xt, xd), groups["dense_layers"])

    def one(x, a):
        lp, w = a
        xt_in = x[0][-prompts:]
        xt, xd = attended(*x, lp, w)
        e = lp["moe"]

        def scores(x):  # the router reads the residual as it is (A1)
            return jax.nn.sigmoid(jnp.einsum(
                "nsd,de->nse", x.astype(F32), e["router"].astype(F32),
                precision=HI))

        text, drawn = scores(xt), scores(xd)
        bias = _mla._fit_bias(drawn[:half].reshape(-1, n_router), e["bias"],
                              top_k)
        by_text = jnp.argsort(
            -text_loads(jax.lax.top_k(text + bias, top_k)[1]),
            stable=True).reshape(held, chips)
        load = _mla._loads(drawn[half:].reshape(-1, n_router), bias, top_k)
        within = jnp.argsort(-load[by_text], axis=1, stable=True)
        # [slot, chip] -> the router's old column; its new index is
        # chip * held + slot
        column = jnp.take_along_axis(by_text, within, axis=1).T.reshape(-1)
        dealt = {"router": e["router"][:, column], "bias": bias[column]}
        return fed(xt, xd, {**lp, "moe": {**e, **dealt}}), (dealt, xt_in)

    _, (dealt, kept["layers"]) = jax.lax.scan(one, (xt, xd), groups["layers"])
    placed = {**params, "layers": {**params["layers"], "moe": {
        **params["layers"]["moe"], **dealt}}}
    params["layers"]["moe"] = {
        **params["layers"]["moe"],
        **_make_typical(placed, m, _decode_shares(placed, m, kept, key,
                                                  attend, feed),
                        embed[cell], attend, feed)}
    return params


def _make_typical(params, m: dict, of_decode, x, attend, feed):
    """The dealt routers and biases [routed layers, ...] renumbered so that
    this chip is a typical one (``_typical``), layer by layer over the
    cell's own prompts ``x`` [rows, T, D] (``cell_ids``, embedded): a
    layer's prompt tokens are counted by the expert they choose behind the
    layers before it AS THIS CHIP WILL HOLD THEM, the layer is renumbered,
    and the rows go on through the experts it then holds.  ``of_decode``:
    ``_decode_shares``' two, a generated token's choices and a wave's
    touches; a prompt token is a generated id ``SUMMARY_SHARE`` of the
    time."""
    held, n_router, top_k = m["n_held"], m["n_router"], m["top_k"]
    chips, home = n_router // held, m["expert_first"] // held
    bf16 = x.dtype
    groups = {name: (g, wins) for name, g, wins in _groups(params, m)}

    def attended(x, lp, w):
        return jax.lax.map(
            lambda row: attend(row.astype(F32), lp, w).astype(bf16), x)

    def fed(x, lp):
        return jax.lax.map(
            lambda row: feed(row.astype(F32), lp)[0].astype(bf16), x)

    if "dense_layers" in groups:
        x, _ = jax.lax.scan(
            lambda x, a: (fed(attended(x, *a), a[0]), None), x,
            groups["dense_layers"])

    def one(x, a):
        (lp, w), (dec, touch) = a
        x = attended(x, lp, w)
        e = lp["moe"]
        sc = jax.nn.sigmoid(jnp.einsum(
            "nsd,de->nse", x.astype(F32), e["router"].astype(F32),
            precision=HI))
        chosen = jax.lax.top_k(sc + e["bias"], top_k)[1]
        txt = jnp.zeros((n_router,), F32).at[chosen.reshape(-1)].add(
            1.0 / (chosen.shape[0] * chosen.shape[1]))
        txt = (1.0 - SUMMARY_SHARE) * txt + SUMMARY_SHARE * dec
        # [E] both, by expert: what a request pays for its decode steps'
        # reads (in a wave, and alone) and for its prompt tokens' choices
        read = (1.0 - LONE_SHARE) * touch / WAVE_ROWS + LONE_SHARE * dec
        perm = _typical(READ_S * read, PREFILL_S * txt, chips=chips,
                        held=held, home=home)
        typical = {"router": e["router"][:, perm], "bias": e["bias"][perm]}
        return fed(x, {**lp, "moe": {**e, **typical}}), typical

    return jax.lax.scan(one, x, (groups["layers"], of_decode))[1]


def _typical(dec, txt, *, chips: int, held: int, home: int):
    """[E] int32: the routed layer's experts renumbered so that chip
    ``home`` is a typical chip.  ``dec`` and ``txt`` [E] are what a request
    pays for the choices that land on each expert (index = chip * held +
    band).  Band by band, chip ``home`` takes the expert of that band (its
    own or another chip's, which takes ``home``'s in exchange) that keeps
    its running sums of both costs nearest the even share's; a second round
    re-picks each band's with the others' fixed."""
    dec, txt = dec.reshape(chips, held), txt.reshape(chips, held)
    even_d, even_t = dec.sum() / chips, txt.sum() / chips

    def off(d, t):
        return jnp.abs(d) + jnp.abs(t)

    def first(run, s):  # running sums against the even share's ramp
        d = run[0] + dec[:, s] - even_d * (s + 1) / held
        t = run[1] + txt[:, s] - even_t * (s + 1) / held
        c = jnp.argmin(off(d, t))
        return (run[0] + dec[c, s], run[1] + txt[c, s]), c

    _, pick = jax.lax.scan(first, (0.0, 0.0), jnp.arange(held))

    def again(pick, s):  # the whole sums, one band's pick at a time
        rest_d = dec[pick, jnp.arange(held)].sum() - dec[pick[s], s] - even_d
        rest_t = txt[pick, jnp.arange(held)].sum() - txt[pick[s], s] - even_t
        c = jnp.argmin(off(rest_d + dec[:, s], rest_t + txt[:, s]))
        return pick.at[s].set(c), None

    pick, _ = jax.lax.scan(again, pick, jnp.arange(held))
    cols = jnp.arange(chips * held).reshape(chips, held)
    band = jnp.arange(held)
    theirs = cols[pick, band]
    return cols.at[home].set(theirs).at[pick, band].set(
        jnp.where(pick == home, theirs, cols[home])).reshape(-1)


def _decode_shares(params, m: dict, kept, key, attend, feed):
    """Two [routed layers, E] float32, over greedy text of the dealt model's
    own behind the sample's last prompts (mla-moe.py's ``_decode_shares``
    says why the text decides): a generated token's choices by expert, and
    how often a decode step of ``WAVE_ROWS`` rows (the prompts' walks of one
    index: requests of one wave) touches the expert with any of its rows.
    Each walk is a request's decode steps as the cache serves them: on the
    full layer a token sees its prompt and itself; on a WINDOW layer it sees
    the 127 positions before it, the prompt's tail at first and, as the walk
    goes on, its OWN earlier tokens (``hist``: each layer's input at those
    positions), and after 128 steps no prompt at all.  What a window layer
    sees decides: attention over random weights is nearly flat, so its
    output is the mean value row of its window, and bytes of transcript are
    not generated ids.  Eight seeds' decode steps sent 19,604-25,952 pairs
    to the held experts where walks that kept the prompt's tail there had
    promised the even 22,528 each, and 24,068-26,607 where each walk saw the
    other walks' tokens of its step instead of its own history (PERF.md
    section 6)."""
    n_router = m["n_router"]
    eps, top_k = float(m["norm_eps"]), m["top_k"]
    embed, head = params["embed"]["weight"], params["lm_head"]["weight"]
    groups = [(g, wins, kept[name], name == "layers")
              for name, g, wins in _groups(params, m)]
    n_layers, n_prompts = kept["layers"].shape[:2]
    walks, steps = m["calibration"]["walks"], m["calibration"]["walk_steps"]
    span = m["window"] - 1

    def through(x, a, routed):
        lp, w, behind, hist = a  # [P, T, D] the prompts, [P, W, span, D]

        def prompt(xb):
            xs, prompt_in, own = xb
            at = jax.lax.cond(
                w > 0,
                lambda: jax.vmap(lambda x1, h: attend(
                    x1[None], lp, w, behind=h.astype(F32))[0])(xs, own),
                lambda: attend(xs, lp, w, behind=prompt_in.astype(F32)))
            hit = jnp.zeros((xs.shape[0], n_router), bool)  # [walk, expert]
            if routed:
                e = lp["moe"]
                sc = jax.nn.sigmoid(jnp.einsum(
                    "sd,de->se", at, e["router"].astype(F32), precision=HI))
                hit = hit.at[jnp.arange(xs.shape[0])[:, None],
                             jax.lax.top_k(sc + e["bias"], top_k)[1]].set(True)
            return feed(at, lp)[0], hit

        out, hit = jax.lax.map(prompt, (x, behind, hist))
        hist = jnp.concatenate(
            [hist[:, :, 1:], x[:, :, None].astype(hist.dtype)], axis=2)
        # a step's rows: the prompts' walks of one index
        counts = jnp.stack([hit.sum((0, 1)), hit.any(0).sum(0)]).astype(F32)
        return out, (counts, hist)

    def step(carry, _):
        at, total, hists = carry
        x = embed[at].astype(F32)
        new = []
        for (g, wins, behind, routed), hist in zip(groups, hists):
            x, (counts, hist) = jax.lax.scan(
                partial(through, routed=routed), x, (g, wins, behind, hist))
            new.append(hist)
        x = _ref._rms(x, params["final_norm"]["scale"], eps)
        at = jnp.argmax(jnp.einsum(
            "pwd,dv->pwv", x.astype(head.dtype), head,
            preferred_element_type=F32), axis=-1)
        return (at, total + counts, tuple(new)), None  # the routed group's

    starts = jax.random.randint(key, (n_prompts, walks), 3, embed.shape[0])
    hists = tuple(jnp.broadcast_to(
        behind[:, :, None, -span:], (*behind.shape[:2], walks, span,
                                     behind.shape[-1]))
        for _, _, behind, _ in groups)
    (_, total, _), _ = jax.lax.scan(
        step, (starts, jnp.zeros((n_layers, 2, n_router), F32), hists), None,
        length=steps)
    return (total[:, 0] / (n_prompts * walks * steps),
            total[:, 1] / (walks * steps))


# ---- the plain reference -----------------------------------------------------

@partial(jax.jit, static_argnames=("dims", "mode"))
def _forward(groups, embed, final_norm, head, ids, rows, *, dims, mode):
    """ids [S] int32 (padded past the real length: causal, the padding
    reaches no earlier row); rows [R]: positions whose logits are wanted.
    Returns logits [R, V] float32 and [R] bool, the routes too close to
    call at those positions (any routed layer)."""
    attend, feed = _block(dims, mode)
    x = embed[ids].astype(F32)
    close = jnp.zeros(ids.shape, bool)
    for group, wins in groups:
        x, c = jax.lax.scan(
            lambda x, a: feed(attend(x, a[0], a[1]), a[0]), x, (group, wins))
        close = close | c.any(axis=0)
    x = _ref._rms(x[rows], final_norm, dims[4])
    return jnp.einsum("rd,dv->rv", x, _ref._weight(head, (0,), mode),
                      precision=HI), close[rows]


def _logits_and_close(params, m: dict, prompt_ids, served_ids, mode=None):
    """[n, V] logits, row j predicting ``served_ids[j]`` from prompt +
    served[:j], and [n] bool: row j's route is too close to call."""
    ids = list(prompt_ids) + list(served_ids)
    n_p, n_s = len(prompt_ids), len(served_ids)
    ids_a = np.zeros((_ref._bucket(len(ids)),), np.int32)
    ids_a[: len(ids)] = ids
    rows = np.full((_ref._bucket(n_s, 64),), n_p - 1, np.int32)
    rows[:n_s] = np.arange(n_p - 1, n_p - 1 + n_s)
    out, close = _forward(
        tuple((g, w) for _, g, w in _groups(params, m)),
        params["embed"]["weight"], params["final_norm"]["scale"],
        params["lm_head"]["weight"], jnp.asarray(ids_a), jnp.asarray(rows),
        dims=_dims(m), mode=mode)
    return out[:n_s], close[:n_s]


def logits_for(params, m: dict, prompt_ids, served_ids, mode=None):
    """reference.logits_for for this family."""
    return _logits_and_close(params, m, prompt_ids, served_ids, mode)[0]


def served_gap(params, m: dict, prompt_ids, served_ids) -> np.ndarray:
    lg, close = _logits_and_close(params, m, prompt_ids, served_ids)
    return _mla._gap(lg, jnp.asarray(np.asarray(served_ids, np.int32)), close)


def control_gap(params, m: dict, prompt_ids, served_ids,
                mode: str) -> np.ndarray:
    lg, close = _logits_and_close(params, m, prompt_ids, served_ids)
    lo = logits_for(params, m, prompt_ids, served_ids, mode=mode)
    return _mla._gap(lg, jnp.argmax(lo, axis=-1), close)


# ---- counts ------------------------------------------------------------------

def _attn_params(m: dict) -> int:
    d, hd = m["dim"], m["head_dim"]
    return 2 * d * m["n_heads"] * hd + 2 * d * m["n_kv_heads"] * hd


def _expert_params(m: dict) -> int:
    return 3 * m["dim"] * m["hidden_dim"]


def _n_routed(m: dict) -> int:
    return m["n_layers"] - m["n_dense_layers"]


pairs_per_token = _mla.pairs_per_token


def matmul_params(m: dict) -> int:
    """Parameters in matmul weights held HERE: attention, the dense layers'
    FFN, each routed layer's router, shared expert and held experts, and
    the head's slice (what a decode step streams)."""
    routed = (m["dim"] * m["n_router"]
              + (m["n_shared"] + m["n_held"]) * _expert_params(m))
    return (m["n_layers"] * _attn_params(m)
            + m["n_dense_layers"] * 3 * m["dim"] * m["dense_hidden"]
            + _n_routed(m) * routed + m["dim"] * m["vocab_size"])


def active_params(m: dict) -> float:
    """Parameters one token multiplies here: of the held experts only its
    expected pairs."""
    return matmul_params(m) - _n_routed(m) * (
        m["n_held"] - pairs_per_token(m)) * _expert_params(m)


def _keys(m: dict, n_new: int, n_ctx: int = 0) -> float:
    """(query, key) pairs of ``n_new`` queries behind ``n_ctx`` cached
    tokens, summed over the layers: the query at position p sees p + 1
    keys on a full layer and min(p + 1, window) on a window layer (the
    band, not the causal triangle)."""
    p = np.arange(n_ctx, n_ctx + n_new, dtype=np.float64) + 1.0
    return float(sum(p.sum() if not w else np.minimum(p, w).sum()
                     for w in m["windows"]))


def attn_flops(m: dict, n_new: int, n_ctx: int = 0) -> float:
    """A (query, key) pair of a head costs head_dim multiply-adds for the
    score and head_dim for the value."""
    return 2.0 * m["n_heads"] * 2 * m["head_dim"] * _keys(m, n_new, n_ctx)


def prefill_flops(m: dict, n_tokens: int, head_tokens: int | None = None,
                  kv_start: int = 0) -> float:
    head = m["dim"] * m["vocab_size"]
    fl = 2.0 * (active_params(m) - head) * n_tokens
    fl += 2.0 * (n_tokens if head_tokens is None else head_tokens) * head
    return fl + attn_flops(m, n_tokens, kv_start)


def request_flops(m: dict, n_prompt_computed: int, n_cached: int,
                  n_generated: int) -> float:
    """flops.request_flops over what a token multiplies here."""
    head = m["dim"] * m["vocab_size"]
    fl = 2.0 * (active_params(m) - head) * (n_prompt_computed + n_generated)
    fl += 2.0 * head * max(n_generated, 1)
    fl += attn_flops(m, n_prompt_computed, n_cached)
    return fl + attn_flops(m, n_generated, n_cached + n_prompt_computed)


def _kv_row_bytes(m: dict) -> float:
    """K and V of one position in one layer, bfloat16."""
    return 2.0 * 2 * m["n_kv_heads"] * m["head_dim"]


def kv_read_bytes(m: dict, ctx: int) -> float:
    """K/V bytes one decode step of one row reads at ``ctx`` cached
    positions (the newest included): a full layer all of them, a window
    layer min(ctx, window)."""
    return _kv_row_bytes(m) * sum(ctx if not w else min(ctx, w)
                                  for w in m["windows"])


def kv_bytes_per_token(m: dict) -> float:
    """Cache bytes a token of a long sequence holds for as long as the
    sequence lives: its rows in the FULL layers.  A window layer keeps
    min(ctx, window) positions a sequence whatever its length
    (``kv_read_bytes`` counts them); the token's share of that is 0 once
    the sequence has outgrown the window."""
    return _kv_row_bytes(m) * sum(1 for w in m["windows"] if not w)


def weight_stream_bytes(m: dict) -> float:
    """Every weight held here, once a decode step: an upper count, a
    grouped product skips the held experts no row chose."""
    return matmul_params(m) * 2.0


def decode_attn_bytes(m: dict, live_tokens: float, rows: int = 1) -> float:
    """The K/V a decode step reads over ``rows`` rows whose cached lengths
    sum to ``live_tokens``: the full layers every position, the window
    layers min(a row's length, window) (rows of equal length assumed: the
    step-by-step count is ``kv_read_bytes``)."""
    per_row = live_tokens / max(rows, 1)
    return _kv_row_bytes(m) * sum(
        live_tokens if not w else rows * min(per_row, w)
        for w in m["windows"])


def decode_step_bytes(m: dict, live_tokens: float, rows: int = 1) -> float:
    return weight_stream_bytes(m) + decode_attn_bytes(m, live_tokens, rows)
