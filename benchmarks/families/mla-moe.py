"""Latent attention over routed experts: the DeepSeek-V3 block that
``kimi_k2`` configurations publish, as one chip of a deployment holds it.

**The published keys** and what they mean here (``sizes``): ``hidden_size``,
``num_attention_heads``, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim`` (MLA);
``rope_theta`` and ``rope_scaling`` (YaRN); ``first_k_dense_replace`` leading
layers with a dense SwiGLU of ``intermediate_size``, the other
``num_hidden_layers`` routed: ``scoring_func`` sigmoid, ``topk_method``
noaux_tc with ``n_group`` = ``topk_group`` = 1 (no groups),
``num_experts_per_tok``, ``norm_topk_prob``, ``routed_scaling_factor``,
``n_shared_experts``, every expert SwiGLU of ``moe_intermediate_size``.
``n_routed_experts`` is the number of experts HELD HERE and ``vocab_size``
the rows of the vocabulary held here (both listed in ``reduced``); the
file's ``published`` block states the model's own counts (the router stays
that wide) and ``share`` which experts these are (``expert_first``).

**The equations** (h = RMSNorm(x), weight 1 + scale as the engine stores
it; float32, ``HIGHEST``):

    c_q = RMSNorm(h W_qa);  [q_nope | q_rope] = c_q W_qb      (a head: nope + rope)
    [c_kv | k_r] = h W_kva;  c_kv = RMSNorm(c_kv);  k_rope = RoPE(k_r), one for all heads
    [k_nope | v] = c_kv W_kvb;  scores (q_nope . k_nope + RoPE(q_rope) . k_rope) * s
    causal softmax;  o = P v;  y = o W_o
    s = (nope + rope)^-0.5 * (0.1 * mscale_all_dim * ln(factor) + 1)^2
    routed layer: sc = sigmoid(h W_r); the top k of sc + b chosen;
    w_i = sc_i / (sum of the chosen sc + 1e-20) * routed_scaling_factor
    y = sum over chosen i HELD HERE of w_i E_i(h)  +  E_shared(h)

YaRN: inverse frequencies blend ``theta^(-2i/d) / factor`` and
``theta^(-2i/d)`` by the linear ramp between the correction dimensions of
``beta_fast`` and ``beta_slow`` rotations over
``original_max_position_embeddings``, at every position; cos and sin are
scaled by mscale(factor, mscale) / mscale(factor, mscale_all_dim) (1 here).
Rope pairs dimension i with i + rope/2 (the engine's layout; with random
weights the checkpoint's interleaved layout is the same model: ``assumed``).

**What is left out, in the program and here alike**: what the absent experts
would add to a routed layer (they live on other chips; nothing stands in
for them or for their exchange), the layers beyond the cut (further
pipeline stages), the vocabulary outside the slice.  Expanded heads, full
softmax, every held expert on every token with the router's gate as the
mask, one layer's float32 weights at a time; nothing of the program is
imported for it.

**The weights** are drawn from the seed in bfloat16 at fan-in scale (the
embedding at std 2, so that the residual stream keeps the token), and then
the selection bias is fitted and the experts are dealt to the chips by load,
both on a seeded sample passed through this reference's equations (at one
bfloat16 pass: the sample decides loads, no output): a routed model's work
hangs on its weights, and a deployment leaves neither to the draw (the
section "the selection bias, and which experts a chip holds" below argues
it and PERF.md section 6 has the readings).

**Routes too close to call.**  Which 8 of 384 experts a token takes is a
discrete choice.  Where the 8th and 9th of ``sc + b`` lie closer than bfloat16
activations resolve, the program may take the other one, and if one of the
two is an expert held here the token's output differs by a whole expert's
output: the architecture, no precision's fault.  ``served_gap`` gives NaN
for a served token if, in some routed layer of this float32 reference at
the position that predicts it, ``(v8 - v9) < ROUTE_MARGIN * sc8 (1 - sc8)``
(the margin on the router's logit, carried to the score by the sigmoid's
slope at the 8th) **and the 8th or the 9th is held here**.  A swap between
two absent experts changes only the normalising sum, by at most the margin
itself: under a rounding of the weights (``checks``: tests/test_latent_moe.py
shows it).  Every other token is compared in full.  With 384 experts the
8th and 9th are close on most tokens (their spacing is 0.05 of a logit);
it is the 2 x 12 / 384 chance of a held expert at the boundary that keeps
the share left out near 4% a routed layer.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from families import bench_file

_w = bench_file("weights")
_ref = bench_file("reference")

HI, F32 = _ref.HI, _ref.F32

# the margin on the router's LOGIT under which a route is too close to call
# (moe-topk's, set from its readings: bfloat16 rows a dozen roundings deep);
# limits/kimi-k2.6-offline.json states what this cell's readings showed
ROUTE_MARGIN = 2.0 ** -4

weight_bytes = _w.weight_bytes


def sizes(cfg: dict) -> dict:
    if cfg["engine"]["weights"] != "bfloat16" or cfg.get("tie_word_embeddings"):
        raise SystemExit("families/mla-moe.py draws bfloat16 weights and an "
                         "untied head only")
    if (cfg["scoring_func"] != "sigmoid" or cfg["n_group"] != 1
            or cfg["topk_group"] != 1 or not cfg["norm_topk_prob"]
            or cfg.get("moe_layer_freq", 1) != 1
            or cfg["rope_scaling"]["type"] != "yarn"):
        raise SystemExit("families/mla-moe.py: sigmoid scores, one group, "
                         "normalised top-k weights, every layer past the "
                         "leading dense ones routed, YaRN rope")
    pub, share = cfg["published"], cfg["share"]
    return {
        "dim": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_dense_layers": cfg["first_k_dense_replace"],
        "n_heads": cfg["num_attention_heads"],
        "q_rank": cfg["q_lora_rank"],
        "kv_rank": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"],
        "v_dim": cfg["v_head_dim"],
        "dense_hidden": cfg["intermediate_size"],
        "hidden_dim": cfg["moe_intermediate_size"],  # one expert's width
        "n_router": pub["n_routed_experts"],        # the router's width
        "n_held": cfg["n_routed_experts"],          # experts held here
        "expert_first": share["expert_first"],
        "n_shared": cfg["n_shared_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "route_scale": float(cfg["routed_scaling_factor"]),
        "vocab_size": cfg["vocab_size"],
        "calibration": {**CALIBRATION, **cfg.get("calibration", {})},
        "rope_theta": float(cfg["rope_theta"]),
        "yarn": {k: float(cfg["rope_scaling"][k]) for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "mscale", "mscale_all_dim")},
        "norm_eps": float(cfg["rms_norm_eps"]),
        "weights": cfg["engine"]["weights"],
        "kv": cfg["engine"]["kv"],
        "page_size": cfg["engine"]["page_size"],
        "settings": dict(cfg["engine"].get("settings", {})),
        "mesh": dict(cfg["engine"].get("mesh", {})),
    }


def model_config(name: str, m: dict, eng: dict):
    from lmrs_tpu.config import LatentModelConfig

    y = m["yarn"]
    return LatentModelConfig(
        name=name, vocab_size=m["vocab_size"], dim=m["dim"],
        n_layers=m["n_layers"], n_heads=m["n_heads"], n_kv_heads=m["n_heads"],
        hidden_dim=m["hidden_dim"], dense_hidden_dim=m["dense_hidden"],
        max_seq_len=eng["max_seq_len"], rope_theta=m["rope_theta"],
        norm_eps=m["norm_eps"], tie_embeddings=False, dtype="bfloat16",
        q_lora_rank=m["q_rank"], kv_lora_rank=m["kv_rank"],
        qk_nope_head_dim=m["nope"], qk_rope_head_dim=m["rope"],
        v_head_dim=m["v_dim"], rope_factor=y["factor"],
        rope_orig_max_pos=int(y["original_max_position_embeddings"]),
        rope_beta_fast=y["beta_fast"], rope_beta_slow=y["beta_slow"],
        rope_mscale=y["mscale"], rope_mscale_all_dim=y["mscale_all_dim"],
        n_dense_layers=m["n_dense_layers"], n_routed_experts=m["n_router"],
        n_shared_experts=m["n_shared"], n_experts_per_token=m["top_k"],
        routed_scaling_factor=m["route_scale"],
        expert_first=m["expert_first"], n_experts_held=m["n_held"])


# ---- weights ---------------------------------------------------------------

# the selection bias starts around 0 at a twentieth of the scores' spread and
# is then fitted (below); either way it shows whether it is added before the
# choice and left out of the weights
_BIAS_STD = 0.02
# the embedding is drawn at std 2 (weights.py's draw takes a fan-in: 1/4)
EMBED_FAN_IN = 0.25


def _builder(m: dict):
    D, H = m["dim"], m["n_heads"]
    qk, kvw = m["nope"] + m["rope"], m["nope"] + m["v_dim"]
    F, V, E, Eh = m["hidden_dim"], m["vocab_size"], m["n_router"], m["n_held"]

    def group(key, n, routed):
        def proj(k, shape, fan_in):
            return _w._stacked(k, n, lambda kk: _w._draw_bf16(kk, shape, fan_in))

        ks = jax.random.split(key, 20)
        g = {
            "ln_attn": {"scale": _w._draw_norm(ks[0], (n, D))},
            "ln_mlp": {"scale": _w._draw_norm(ks[1], (n, D))},
            "attn": {
                "wq_a": proj(ks[2], (D, m["q_rank"]), D),
                "q_norm": _w._draw_norm(ks[3], (n, m["q_rank"])),
                "wq_b": proj(ks[4], (m["q_rank"], H, qk), m["q_rank"]),
                "wkv_a": proj(ks[5], (D, m["kv_rank"] + m["rope"]), D),
                "kv_norm": _w._draw_norm(ks[6], (n, m["kv_rank"])),
                "wkv_b": proj(ks[7], (m["kv_rank"], H, kvw), m["kv_rank"]),
                "wo": proj(ks[8], (H, m["v_dim"], D), H * m["v_dim"]),
            },
        }
        if not routed:
            Fd = m["dense_hidden"]
            g["mlp"] = {"w_gate": proj(ks[9], (D, Fd), D),
                        "w_up": proj(ks[10], (D, Fd), D),
                        "w_down": proj(ks[11], (Fd, D), Fd)}
            return g
        g["moe"] = {
            "router": proj(ks[12], (D, E), D),
            "bias": _BIAS_STD * jax.random.normal(ks[13], (n, E), F32),
            "w_gate": proj(ks[14], (Eh, D, F), D),
            "w_up": proj(ks[15], (Eh, D, F), D),
            "w_down": proj(ks[16], (Eh, F, D), F),
        }
        if m["n_shared"]:
            Fs = F * m["n_shared"]
            g["shared"] = {"w_gate": proj(ks[17], (D, Fs), D),
                           "w_up": proj(ks[18], (D, Fs), D),
                           "w_down": proj(ks[19], (Fs, D), Fs)}
        return g

    def build(key, sample_ids):
        ks = jax.random.split(key, 5)
        n_routed = m["n_layers"] - m["n_dense_layers"]
        # the embedding at unit variance, the size every layer's output
        # has under fan-in scaling: drawn at 1/sqrt(D) like a projection
        # (weights.py) it is a hundredth of the first attention output it
        # is added to, the residual stream forgets which token it carries,
        # and every position of a sequence takes the same experts
        # (PERF.md, PR 29: the decode steps' routes stuck to their rows)
        params = {"embed": {"weight": _w._draw_bf16(ks[0], (V, D),
                                                    EMBED_FAN_IN)}}
        if m["n_dense_layers"]:
            params["dense_layers"] = group(ks[1], m["n_dense_layers"], False)
        params["layers"] = group(ks[2], n_routed, True)
        params["final_norm"] = {"scale": _w._draw_norm(ks[3], (D,))}
        params["lm_head"] = {"weight": _w._draw_bf16(ks[4], (D, V), D)}
        if m["n_router"] % m["n_held"]:
            return params  # no whole number of chips to deal to
        return _fit_and_deal(params, m, sample_ids,
                             jax.random.fold_in(key, 1))

    return build


# ---- the selection bias, and which experts a chip holds ------------------------
#
# A random router with a random bias sends most tokens to the same few
# experts of a layer: every token's hidden state shares a large part (the
# attention over one prompt), which gives each expert an offset of its own,
# and whether a hot expert is among twelve fixed indices is the luck of the
# draw.  With experts 0-11 held and a random bias, six seeds' cycles took
# 9.68-10.35 s, the decode steps' expert reads being what differed (PERF.md,
# PR 29).  A deployment leaves neither to luck, and the devices it has are
# modelled here, on a seeded sample of the benchmark's own kind of tokens
# passed through the reference's equations, layer by layer (a layer's input
# depends on what the earlier layers hold here):
#
# * ``e_score_correction_bias`` is FITTED, by the published auxiliary-loss-
#   free rule (an expert over its share of the sample's choices loses a
#   step of bias, one under it gains one; the step decays from 0.05 to
#   0.0002 over ``BIAS_STEPS`` rounds), on the sample's generated-like part
#   (every id of the vocabulary once, each behind a map prompt, seeing the
#   prompt and itself as a first decode step does): that removes the
#   experts' offsets, as training does.
# * the experts are PLACED by load, as an inference deployment's balancer
#   places them (a relabelling of the router's columns and the bias: which
#   random column an index names), by two loads under the fitted bias.  The
#   transcript tokens' (ASCII bytes, some thirty distinct ids: lumps no
#   bias evens out; a prefill wave pays for each row): the experts ranked
#   by it are cut into ``held`` bands of ``chips`` experts, and every chip
#   gets one expert of each band as its slot 0, 1, ...  And, within a band,
#   the generated-like tokens' (a decode step pays 0.1 ms of weights for
#   each expert it touches), measured on sequences the fit did not see:
#   chip c gets the band's c-th busiest.  Every chip then holds a like
#   sample of busy and idle experts on both counts, whatever the seed.
# * this chip is a TYPICAL one of them: greedy text of the dealt model's own
#   is generated behind the sample's prompts, every choice of a generated
#   token and of a prompt token is counted by the chip it lands on, and
#   layer by layer the chip whose two counts cost a request least apart from
#   the even share gives its experts this chip's indices (``_decode_shares``
#   says why the text, and not an even sample of the vocabulary, decides the
#   first; the bands above leave a chip's prompt tokens +-8% between seeds).

# the sample: sequences (half for the fit, half to measure), the pieces a
# row's generated-like tokens attend in, the prompts that greedy text is
# generated behind (the sample's last)
SEQS, PIECES, WALK_PROMPTS = 16, 2, 4
# and what a configuration's "calibration" block may make smaller (the CPU
# rehearsal's does, for the tests' time)
CALIBRATION = {
    "text": 1792,      # tokens of a row's map prompt, as long as the cell's
    "walks": 64,       # greedy walks a prompt
    "walk_steps": 128,  # their length: the tokens a request generates
}
# the fit's step, from and to, and its rounds: the last step moves an
# expert's load by half a percent (0.001 moved it by 2.4%)
BIAS_STEP, BIAS_STEPS = (0.05, 0.0002), 300
# what a token's choice of a held expert costs on the chip, seconds (PERF.md,
# PR 29): a decode step reads the expert's weights (88 MB at HBM speed), a
# prefill wave multiplies one more row (`job_tok_s` over ten seeds' pairs)
DECODE_PAIR_S, PREFILL_PAIR_S = 120e-6, 1.65e-6


def calibration_drawn(m: dict) -> int:
    """Generated-like tokens behind each prompt: every id of the vocabulary
    once in the fit's half of the sequences and once in the other."""
    per = -(-2 * (m["vocab_size"] - 3) // SEQS)
    return -(-per // (8 * PIECES)) * 8 * PIECES


# a map prompt as the product writes it (lmrs_tpu/prompts, copied: the
# reference side imports nothing of the program), since the instructions
# are a third of what a decode step's attention sees
_PROMPT_HEAD = """You are summarizing one section of a much longer transcript. The section is
annotated with [MM:SS] timestamps and a header describing where it falls in
the full recording.

Write a summary of the following transcript section. Keep every
concrete fact, decision, name, and number. When you mention a specific moment,
carry its timestamp through in [MM:SS] form. Do not add greetings,
introductions, or meta-commentary \u2014 output the summary content only.

Transcript section:
[TRANSCRIPT SECTION 2 of 6]
[TIME RANGE: 05:30 - 11:06]
[SPEAKERS: SPEAKER_01, SPEAKER_02, SPEAKER_00]
[POSITION: 20% through the transcript]

"""


def calibration_ids(seed: int, m: dict) -> np.ndarray:
    """[SEQS, text + calibration_drawn] int32 (``CALIBRATION``): each
    row a map prompt of a seeded transcript as the traffic generator and
    the product write it, in the benchmark tokenizer's ids, cut in the
    middle as the serving contract cuts one (tokenizer.encode_prompt), then
    the generated-like ids: the first half of the rows shares out one
    seeded permutation of the whole vocabulary, the second half another
    (a sample WITH repeats of 8k ids left each expert's load on the others
    to chance: +-1.8% of the decode steps' pairs between seeds, PERF.md)."""
    import families

    here = families.HERE.parent
    gen = families.load_module(here / "traffic" / "offline_jobs.py",
                               "bench_traffic_offline_jobs")
    tok_mod = bench_file("tokenizer")
    vocab_size, n_text = m["vocab_size"], m["calibration"]["text"]
    tok = tok_mod.IdTokenizer(vocab_size)
    rng = np.random.default_rng(seed)
    rows = []
    for _ in range(SEQS):
        segs = gen.synth_transcript(int(rng.integers(1 << 30)), 40,
                                    (120, 120))["segments"]
        text = _PROMPT_HEAD + " ".join(
            f"[{int(s['start']) // 60:02d}:{int(s['start']) % 60:02d}] "
            f"{s['text']}" for s in segs)
        ids = tok_mod.encode_prompt(tok, None, text, n_text, 0)
        assert len(ids) == n_text
        rows.append(ids)
    half = SEQS // 2 * calibration_drawn(m)
    drawn = []
    for _ in range(2):
        ids = rng.permutation(np.arange(3, vocab_size))
        drawn.append(np.concatenate(
            [ids, rng.integers(3, vocab_size, half - len(ids))]))
    return np.concatenate(
        [np.stack(rows), np.concatenate(drawn).reshape(SEQS, -1)],
        axis=1).astype(np.int32)


def _loads(sc, bias, top_k):
    chosen = jax.lax.top_k(sc + bias, top_k)[1]
    return jnp.zeros((sc.shape[-1],), F32).at[chosen.reshape(-1)].add(1.0)


def _fit_bias(sc, bias, top_k):
    """The auxiliary-loss-free rule on fixed scores ``sc`` [T, E]."""
    share = sc.shape[0] * top_k / sc.shape[1]

    def step(i, b):
        u = BIAS_STEP[0] * (BIAS_STEP[1] / BIAS_STEP[0]) ** (
            i / (BIAS_STEPS - 1.0))
        return b - u * jnp.sign(_loads(sc, b, top_k) - share)

    return jax.lax.fori_loop(0, BIAS_STEPS, step, bias.astype(F32))


def _fit_and_deal(params, m: dict, sample_ids, key):
    held, n_router = m["n_held"], m["n_router"]
    chips, home = n_router // held, m["expert_first"] // held
    dims = _dims(m)
    eps, top_k = dims[5], m["top_k"]
    inv = jnp.asarray(yarn_inv_freq(m["rope"], m["rope_theta"], m["yarn"]),
                      F32)
    # the reference's equations on the weights as stored, at one bfloat16
    # pass for HIGHEST's six: the sample decides loads, no output, and is
    # five times the sample that six passes paid for
    attend, feed = _block(dims, None, inv, exact=False)
    embed = params["embed"]["weight"]
    # [seqs, T, D] the prompts and [seqs, N, D] the generated-like tokens,
    # each of which sees its row's prompt and itself; kept in bfloat16
    # between the layers' halves, as the program keeps its residual stream
    c = m["calibration"]
    xt = embed[sample_ids[:, :c["text"]]]
    xd = embed[sample_ids[:, c["text"]:]]
    bf16 = embed.dtype

    def attended(xt, xd, lp):
        def seq(ab):
            t, d = ab[0].astype(F32), ab[1]
            behind = jax.lax.map(  # in pieces: [heads, N, T] scores are large
                lambda piece: attend(piece.astype(F32), lp,
                                     behind=t).astype(bf16),
                d.reshape(PIECES, -1, d.shape[-1]))
            return attend(t, lp).astype(bf16), behind.reshape(d.shape)

        return jax.lax.map(seq, (xt, xd))

    def fed(xt, xd, lp):
        return jax.lax.map(lambda ab: tuple(
            feed(x.astype(F32), lp)[0].astype(bf16) for x in ab), (xt, xd))

    half = SEQS // 2  # rows the bias is fitted on; the rest measure
    kept = {}  # each layer's input over the walks' prompts
    if "dense_layers" in params:
        (xt, xd), kept["dense_layers"] = jax.lax.scan(
            lambda x, lp: (fed(*attended(*x, lp), lp), x[0][-WALK_PROMPTS:]),
            (xt, xd), params["dense_layers"])

    def one(x, lp):
        xt_in = x[0][-WALK_PROMPTS:]
        xt, xd = attended(*x, lp)
        e = lp["moe"]

        def scores(x):
            h = _ref._rms(x.astype(F32), lp["ln_mlp"]["scale"], eps)
            return jax.nn.sigmoid(jnp.einsum(
                "nsd,de->nse", h, e["router"].astype(F32), precision=HI))

        text, drawn = scores(xt), scores(xd)
        # fitted on one half of the sequences, the loads it leaves measured
        # on the other (on the sample it was fitted to, the rule leaves no
        # load to rank by)
        bias = _fit_bias(drawn[:half].reshape(-1, n_router), e["bias"], top_k)
        by_text = jnp.argsort(-_loads(text.reshape(-1, n_router), bias, top_k),
                              stable=True).reshape(held, chips)
        load = _loads(drawn[half:].reshape(-1, n_router), bias, top_k)
        within = jnp.argsort(-load[by_text], axis=1, stable=True)
        # [slot, chip] -> the router's old column; its new index is
        # chip * held + slot
        column = jnp.take_along_axis(by_text, within, axis=1).T.reshape(-1)
        dealt = {"router": e["router"][:, column], "bias": bias[column]}
        placed = {**lp, "moe": {**e, **dealt}}
        # [chips]: the share of a prompt token's choices that each chip holds
        chip = jnp.argsort(column)[jax.lax.top_k(text + bias, top_k)[1]] // held
        of_text = (chip[..., None] == jnp.arange(chips)).mean((0, 1, 2)) * top_k
        return fed(xt, xd, placed), (dealt, xt_in, of_text)

    _, (dealt, kept["layers"], of_text) = jax.lax.scan(
        one, (xt, xd), params["layers"])
    placed = {**params, "layers": {**params["layers"], "moe": {
        **params["layers"]["moe"], **dealt}}}
    of_decode = _decode_shares(placed, m, kept, key, attend, feed)
    # layer by layer, this chip is the one of them whose shares ([layers,
    # chips] both) cost a request's time least apart from the even share's
    even = top_k * held / n_router
    apart = (c["walk_steps"] * DECODE_PAIR_S * jnp.abs(of_decode - even)
             + c["text"] * PREFILL_PAIR_S * jnp.abs(of_text - even))
    which = jnp.argmin(apart, axis=1)
    cols = jnp.arange(n_router).reshape(chips, held)
    perm = jax.vmap(lambda w: cols.at[home].set(cols[w]).at[w].set(
        cols[home]).reshape(-1))(which)                       # [layers, E]
    dealt = {"router": jnp.take_along_axis(dealt["router"],
                                           perm[:, None, :], axis=2),
             "bias": jnp.take_along_axis(dealt["bias"], perm, axis=1)}
    params["layers"]["moe"] = {**params["layers"]["moe"], **dealt}
    return params


def _decode_shares(params, m: dict, kept, key, attend, feed):
    """[routed layers, chips] float32: the share of a generated token's
    choices that each chip's experts take, over greedy text of the model's
    own.  Greedy text from random weights is no even sample of the
    vocabulary: the id that follows an id hardly depends on the prompt
    (four ids in five have the same successor behind two prompts), walks
    that meet go on together, and a cycle's 8,448 generated tokens hold
    3,600 distinct ids, the same favourites in every job of a seed.  Which
    experts those take is the draw's luck, a chip in 32: the decode steps'
    pairs on the held experts spread 1.9% a deviation between seeds where
    even text would spread 1.0% (PERF.md, PR 29).  A deployment's balancer
    places experts by the traffic it has seen, so ``walks`` seeded starts
    behind each of the sample's last ``WALK_PROMPTS`` prompts are followed
    for ``walk_steps`` greedy steps through the dealt model (each token sees
    a prompt and itself), and every choice is counted by the chip it lands
    on."""
    n_layers, n_prompts = kept["layers"].shape[:2]
    chips = m["n_router"] // m["n_held"]
    step = _greedy_step(params, m, kept, attend, feed)
    walks, steps = m["calibration"]["walks"], m["calibration"]["walk_steps"]
    keys = jax.random.split(key, steps + 1)
    starts = jax.random.randint(keys[0], (n_prompts * walks,), 3,
                                params["embed"]["weight"].shape[0])

    def walk(carry, k):
        # every request of the traffic has a prompt of its own, and two
        # walks on one id part where their prompts give it other successors:
        # with a few prompts for many walks, the walks change prompts at
        # every step, or those behind one prompt would never part
        at, total = carry
        at = jax.random.permutation(k, at).reshape(n_prompts, walks)
        at, total = step(at, total)
        return (at.reshape(-1), total), None

    (_, total), _ = jax.lax.scan(
        walk, (starts, jnp.zeros((n_layers, chips), F32)), keys[1:])
    return total / (n_prompts * walks * steps)


def _greedy_step(params, m: dict, kept, attend, feed):
    """``step(at [prompts, walks] ids, total) -> (the ids that follow,
    total + [routed layers, chips] choices counted by chip)``."""
    held, chips = m["n_held"], m["n_router"] // m["n_held"]
    eps, top_k = float(m["norm_eps"]), m["top_k"]
    embed = params["embed"]["weight"]
    head = params["lm_head"]["weight"]

    def through(x, lp, behind, routed):
        def prompt(xb):
            a = attend(xb[0], lp, behind=xb[1].astype(F32))
            hits = jnp.zeros((chips,), F32)
            if routed:
                e = lp["moe"]
                h = _ref._rms(a, lp["ln_mlp"]["scale"], eps)
                sc = jax.nn.sigmoid(jnp.einsum(
                    "sd,de->se", h, e["router"].astype(F32), precision=HI))
                chip = jax.lax.top_k(sc + e["bias"], top_k)[1] // held
                hits = (chip[..., None] == jnp.arange(chips)).sum(
                    (0, 1)).astype(F32)
            return feed(a, lp)[0], hits

        x, hits = jax.lax.map(prompt, (x, behind))
        return x, hits.sum(0)

    def step(at, total):
        x = embed[at].astype(F32)
        if "dense_layers" in params:
            x, _ = jax.lax.scan(
                lambda x, a: (through(x, a[0], a[1], False)[0], None), x,
                (params["dense_layers"], kept["dense_layers"]))
        x, hits = jax.lax.scan(lambda x, a: through(x, a[0], a[1], True), x,
                               (params["layers"], kept["layers"]))
        x = _ref._rms(x, params["final_norm"]["scale"], eps)
        return (jnp.argmax(jnp.einsum(
            "pwd,dv->pwv", x.astype(head.dtype), head,
            preferred_element_type=F32), axis=-1), total + hits)

    return step


def param_shapes(m: dict):
    return jax.eval_shape(_builder(m), _w._key(0), calibration_ids(0, m))


def make_params(m: dict, seed: int, shardings=None):
    return jax.jit(_builder(m), out_shardings=shardings)(
        _w._key(seed), calibration_ids(seed, m))


def param_specs(m: dict):
    return None  # one chip's share: nothing here is sharded


def cache_buffers(sched) -> list:
    """One latent pool; the programs' V, K-scale and V-scale places stay
    empty."""
    return [sched.cache.k, None, None, None]


# ---- the plain reference -----------------------------------------------------

def yarn_inv_freq(rope: int, theta: float, y: dict) -> np.ndarray:
    """[rope / 2] float64, from the published formula (the module's
    docstring)."""
    i = np.arange(rope // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / rope)
    if y["factor"] <= 1.0:
        return plain

    def dim_of(rotations):
        return (rope * math.log(y["original_max_position_embeddings"]
                                / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(y["beta_fast"])), 0)
    high = min(math.ceil(dim_of(y["beta_slow"])), rope - 1)
    ramp = np.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    return plain / y["factor"] * ramp + plain * (1.0 - ramp)


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(m: dict) -> float:
    y = m["yarn"]
    s = (m["nope"] + m["rope"]) ** -0.5
    return s * _mscale(y["factor"], y["mscale_all_dim"]) ** 2 \
        if y["mscale_all_dim"] else s


def _rope(x, inv_freq, table_scale, pos=None):
    """x [S, heads, rope]; the position of row i is ``pos[i]``, or i."""
    s, _, r = x.shape
    half = r // 2
    pos = jnp.arange(s) if pos is None else pos
    ang = pos.astype(F32)[:, None] * inv_freq[None, :]
    sin = (jnp.sin(ang) * table_scale)[:, None, :]
    cos = (jnp.cos(ang) * table_scale)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def route(h, router, bias, top_k, scale, first, held):
    """[S, held] float32 gates of the held experts (0 where not chosen),
    and [S] bool: the choice between the k-th and the (k+1)-th of score +
    bias is too close to call AND one of the two is held here."""
    logits = jnp.einsum("sd,de->se", h, router.astype(F32), precision=HI)
    sc = jax.nn.sigmoid(logits)
    vals, idx = jax.lax.top_k(sc + bias.astype(F32), top_k + 1)
    picked = jnp.take_along_axis(sc, idx[:, :top_k], axis=1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scale
    local = idx[:, :top_k] - first
    gates = jnp.einsum(
        "sk,ske->se", w,
        (local[..., None] == jnp.arange(held)[None, None, :]).astype(F32),
        precision=HI)
    edge = idx[:, top_k - 1:top_k + 1]                       # 8th, 9th
    ours = ((edge >= first) & (edge < first + held)).any(axis=1)
    s8 = picked[:, top_k - 1]
    close = (vals[:, top_k - 1] - vals[:, top_k]) < ROUTE_MARGIN * s8 * (1 - s8)
    return gates, close & ours


def _block(dims, mode, inv_freq, exact=True):
    """The two halves of a layer: ``attend(x, lp) -> x`` over one sequence
    (causal) and ``feed(x, lp) -> (x, close)``.  ``attend(x, lp, behind=c)``
    (the calibration sample only) takes rows that each see the sequence
    ``c`` (that layer's input) and themselves, from the position behind it:
    every such row stands where a first generated token stands.  ``exact``:
    float32 weights and ``HIGHEST``, wherever an output is compared; without
    it (the calibration sample) the weights as stored and one bfloat16 pass."""
    (n_heads, nope, rope, v_dim, kv_rank, eps, sm_scale, table_scale,
     top_k, route_scale, first, held) = dims
    prec = HI if exact else jax.lax.Precision.DEFAULT

    def mm(eq, x, w, lead):
        if exact:
            return jnp.einsum(eq, x, _ref._weight(w, lead, mode), precision=HI)
        return jnp.einsum(eq, x.astype(w.dtype), w, preferred_element_type=F32)

    def swiglu(h, mp, lead):
        g = mm("sd,...df->...sf", h, mp["w_gate"], (lead,))
        u = mm("sd,...df->...sf", h, mp["w_up"], (lead,))
        return mm("...sf,...fd->...sd", jax.nn.silu(g) * u, mp["w_down"],
                  (lead,))

    def attend(x, lp, behind=None):
        a = lp["attn"]

        def latent(x, pos):
            h = _ref._rms(x, lp["ln_attn"]["scale"], eps)
            ckv = mm("sd,dr->sr", h, a["wkv_a"], (0,))
            c_kv = _ref._rms(ckv[:, :kv_rank], a["kv_norm"], eps)
            k_rope = _rope(ckv[:, None, kv_rank:], inv_freq, table_scale, pos)
            return h, mm("sr,rhk->shk", c_kv, a["wkv_b"], (0,)), k_rope[:, 0]

        s = x.shape[0]
        pos = (jnp.arange(s) if behind is None
               else jnp.full((s,), behind.shape[0]))
        h, kv, k_rope = latent(x, pos)
        c_q = _ref._rms(mm("sd,dr->sr", h, a["wq_a"], (0,)), a["q_norm"], eps)
        q = mm("sr,rhk->shk", c_q, a["wq_b"], (0,))
        q_nope = q[..., :nope]
        q_rope = _rope(q[..., nope:], inv_freq, table_scale, pos)

        def scores(kv_of, k_rope_of):
            return (jnp.einsum("shk,thk->hst", q_nope, kv_of[..., :nope],
                               precision=prec)
                    + jnp.einsum("shk,tk->hst", q_rope, k_rope_of,
                                 precision=prec)) * sm_scale

        if behind is None:
            causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
            p = jax.nn.softmax(jnp.where(causal[None], scores(kv, k_rope),
                                         -1e30), axis=-1)
            o = jnp.einsum("hst,thk->shk", p, kv[..., nope:], precision=prec)
        else:
            _, kv_c, k_rope_c = latent(behind, jnp.arange(behind.shape[0]))
            own = ((q_nope * kv[..., :nope]).sum(-1)
                   + (q_rope * k_rope[:, None]).sum(-1)).T * sm_scale  # [h, s]
            p = jax.nn.softmax(jnp.concatenate(
                [scores(kv_c, k_rope_c), own[..., None]], axis=-1), axis=-1)
            o = (jnp.einsum("hst,thk->shk", p[..., :-1], kv_c[..., nope:],
                            precision=prec)
                 + p[..., -1].T[..., None] * kv[..., nope:])
        return x + mm("shk,hkd->sd", o, a["wo"], (0, 1))

    def feed(x, lp):
        h = _ref._rms(x, lp["ln_mlp"]["scale"], eps)
        if "moe" not in lp:
            return x + swiglu(h, lp["mlp"], 0), jnp.zeros(x.shape[:1], bool)
        e = lp["moe"]
        # the router keeps its precision under the control, as the
        # program's would (routing decisions are precision-sensitive)
        gates, close = route(h, e["router"], e["bias"], top_k, route_scale,
                             first, held)
        y = jnp.einsum("se,esd->sd", gates, swiglu(h, e, 1), precision=prec)
        if "shared" in lp:
            y = y + swiglu(h, lp["shared"], 0)
        return x + y, close

    return attend, feed


@partial(jax.jit, static_argnames=("dims", "mode"))
def _forward(params, ids, rows, inv_freq, *, dims, mode):
    """ids [S] int32 (padded past the real length: causal, the padding
    reaches no earlier row); rows [R]: positions whose logits are wanted.
    Returns logits [R, V] float32 and [R] bool, the routes too close to
    call at those positions (any routed layer)."""
    attend, feed = _block(dims, mode, inv_freq)
    s = ids.shape[0]
    x = params["embed"]["weight"][ids].astype(F32)
    close = jnp.zeros((s,), bool)
    for name in ("dense_layers", "layers"):
        if name in params:
            x, c = jax.lax.scan(lambda x, lp: feed(attend(x, lp), lp), x,
                                params[name])
            close = close | c.any(axis=0)
    x = _ref._rms(x[rows], params["final_norm"]["scale"], dims[5])
    return jnp.einsum("rd,dv->rv", x,
                      _ref._weight(params["lm_head"]["weight"], (0,), mode),
                      precision=HI), close[rows]


def _dims(m: dict) -> tuple:
    y = m["yarn"]
    table_scale = (_mscale(y["factor"], y["mscale"])
                   / _mscale(y["factor"], y["mscale_all_dim"]))
    return (m["n_heads"], m["nope"], m["rope"], m["v_dim"], m["kv_rank"],
            float(m["norm_eps"]), softmax_scale(m), table_scale, m["top_k"],
            m["route_scale"], m["expert_first"], m["n_held"])


def _logits_and_close(params, m: dict, prompt_ids, served_ids, mode=None):
    """[n, V] logits, row j predicting ``served_ids[j]`` from prompt +
    served[:j], and [n] bool: row j's route is too close to call."""
    ids = list(prompt_ids) + list(served_ids)
    n_p, n_s = len(prompt_ids), len(served_ids)
    ids_a = np.zeros((_ref._bucket(len(ids)),), np.int32)
    ids_a[: len(ids)] = ids
    rows = np.full((_ref._bucket(n_s, 64),), n_p - 1, np.int32)
    rows[:n_s] = np.arange(n_p - 1, n_p - 1 + n_s)
    inv = jnp.asarray(yarn_inv_freq(m["rope"], m["rope_theta"], m["yarn"]),
                      F32)
    out, close = _forward(params, jnp.asarray(ids_a), jnp.asarray(rows), inv,
                          dims=_dims(m), mode=mode)
    return out[:n_s], close[:n_s]


def logits_for(params, m: dict, prompt_ids, served_ids, mode=None):
    """reference.logits_for for this family."""
    return _logits_and_close(params, m, prompt_ids, served_ids, mode)[0]


def _gap(lg, pick, close) -> np.ndarray:
    gap = lg.max(axis=-1) - jnp.take_along_axis(lg, pick[:, None], 1)[:, 0]
    return np.asarray(jax.device_get(jnp.where(close, jnp.nan, gap)))


def served_gap(params, m: dict, prompt_ids, served_ids) -> np.ndarray:
    lg, close = _logits_and_close(params, m, prompt_ids, served_ids)
    return _gap(lg, jnp.asarray(np.asarray(served_ids, np.int32)), close)


def control_gap(params, m: dict, prompt_ids, served_ids,
                mode: str) -> np.ndarray:
    lg, close = _logits_and_close(params, m, prompt_ids, served_ids)
    lo = logits_for(params, m, prompt_ids, served_ids, mode=mode)
    return _gap(lg, jnp.argmax(lo, axis=-1), close)


# This family's own numbers over the gaps, in ``flipped_gap_msq``'s place.
# A route the rule above does not foresee (a token whose own margins are
# wide but which attends hard to one that flipped) moves a logit by a whole
# expert's output: one or two gaps of 0.1-0.35 among 3,300 compared tokens on
# some seeds, which a mean of SQUARES follows further (0.0019) than int8
# weights move it (0.0007), so that no limit on it lies between the two.
# ``mean_gap`` (the plain mean over every compared token) follows such a
# gap a tenth as far and still sees a single token off by a whole logit;
# ``wide_gap_share`` (the share of compared tokens further than
# ``WIDE_GAP`` from the reference's choice) sees what moves many tokens a
# little.  limits/kimi-k2.6-offline.json has the readings of both.
WIDE_GAP = 0.02


def gap_stats(m: dict) -> dict:
    return {"mean_gap": lambda g: float(np.mean(g)),
            "wide_gap_share": lambda g: float((np.asarray(g) > WIDE_GAP).mean())}


# ---- counts ------------------------------------------------------------------

def _attn_params(m: dict) -> int:
    d, h = m["dim"], m["n_heads"]
    return (d * m["q_rank"] + m["q_rank"] * h * (m["nope"] + m["rope"])
            + d * (m["kv_rank"] + m["rope"])
            + m["kv_rank"] * h * (m["nope"] + m["v_dim"])
            + h * m["v_dim"] * d)


def _expert_params(m: dict) -> int:
    return 3 * m["dim"] * m["hidden_dim"]


def _n_routed(m: dict) -> int:
    return m["n_layers"] - m["n_dense_layers"]


def pairs_per_token(m: dict) -> float:
    """Token-expert pairs a token sends to the experts held here, a routed
    layer, under even routing: the expectation the FLOP counts use."""
    return m["top_k"] * m["n_held"] / m["n_router"]


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


def attn_flops(m: dict, n_new: int, n_ctx: int = 0) -> float:
    """Expanded-form attention of ``n_new`` queries behind ``n_ctx`` cached
    tokens: a (query, key) pair of a head costs nope + rope multiply-adds
    for the score and v_dim for the value."""
    keys = n_new * n_ctx + n_new * (n_new + 1) / 2.0
    return (2.0 * m["n_layers"] * m["n_heads"]
            * (m["nope"] + m["rope"] + m["v_dim"]) * keys)


def prefill_flops(m: dict, n_tokens: int, head_tokens: int | None = None,
                  kv_start: int = 0) -> float:
    head = m["dim"] * m["vocab_size"]
    fl = 2.0 * (active_params(m) - head) * n_tokens
    fl += 2.0 * (n_tokens if head_tokens is None else head_tokens) * head
    return fl + attn_flops(m, n_tokens, kv_start)


def request_flops(m: dict, n_prompt_computed: int, n_cached: int,
                  n_generated: int) -> float:
    """flops.request_flops over what a token multiplies here; decode
    attention counted in the expanded form too (the absorbed form the
    kernel runs multiplies more: not the model's FLOPs)."""
    head = m["dim"] * m["vocab_size"]
    fl = 2.0 * (active_params(m) - head) * (n_prompt_computed + n_generated)
    fl += 2.0 * head * max(n_generated, 1)
    fl += attn_flops(m, n_prompt_computed, n_cached)
    n_prompt = n_cached + n_prompt_computed
    keys = n_generated * n_prompt + n_generated * (n_generated + 1) / 2.0
    return fl + (2.0 * m["n_layers"] * m["n_heads"]
                 * (m["nope"] + m["rope"] + m["v_dim"]) * keys)


def kv_bytes_per_token(m: dict) -> float:
    """The latent and the rotary key, bfloat16, every layer: the values a
    token's rows hold (the pool pads a row to whole 128-lane tiles, 640
    for 576: PERF.md section 4)."""
    return 2.0 * m["n_layers"] * (m["kv_rank"] + m["rope"])


def weight_stream_bytes(m: dict) -> float:
    """Every weight held here, once a decode step: an upper count, a
    grouped product skips the held experts no row chose."""
    return matmul_params(m) * 2.0


def decode_step_bytes(m: dict, live_tokens: int) -> float:
    return weight_stream_bytes(m) + kv_bytes_per_token(m) * live_tokens


def decode_attn_bytes(m: dict, live_tokens: int) -> float:
    """The latent rows a decode step reads, each once for scores and
    values, over rows whose cached lengths sum to ``live_tokens``."""
    return kv_bytes_per_token(m) * live_tokens
