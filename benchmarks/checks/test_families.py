"""The seam between the harness and a model family (families/__init__.py),
without an engine.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/checks/test_families.py -q

* ``dense-gqa`` reads the four configuration files to the sizes and the
  ``ModelConfig`` that ``run.model_sizes`` / ``run.make_configs`` gave before
  they moved (written out below), and hands out ``weights.py``'s weights and
  ``reference.py``'s logits bit for bit: the two cells are the yardstick;
* a file without ``family`` is ``dense-gqa``; an unknown family and a family
  that lacks a function end the run with the file and the function named;
* ``moe-topk``: its reference against the program's own plain forward pass
  (``lmrs_tpu.models.transformer.forward``) on seeded weights, up to the first
  token that the two route to different experts; its counts against the
  leaves of its weight tree.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
for d in (HERE.parent, HERE):
    if str(d) not in sys.path:
        sys.path.insert(0, str(d))

import families  # noqa: E402

ENGINE = {"max_seq_len": 2048}

# family sizes by configuration, as run.model_sizes gave them at PR 26
DENSE = {
    "mistral-7b-v0.3": {
        "dim": 4096, "n_layers": 32, "n_heads": 32, "n_kv_heads": 8,
        "head_dim": 128, "hidden_dim": 14336, "vocab_size": 32768,
        "rope_theta": 1000000.0, "norm_eps": 1e-05, "tie": False,
        "weights": "int8", "kv": "int8", "page_size": 128, "settings": {},
        "mesh": {}},
    "internlm2-1.8b": {
        "dim": 2048, "n_layers": 24, "n_heads": 16, "n_kv_heads": 8,
        "head_dim": 128, "hidden_dim": 8192, "vocab_size": 92544,
        "rope_theta": 1000000.0, "norm_eps": 1e-05, "tie": False,
        "weights": "bfloat16", "kv": "bfloat16", "page_size": 128,
        "settings": {}, "mesh": {}},
    "tiny-rehearsal": {
        "dim": 256, "n_layers": 2, "n_heads": 2, "n_kv_heads": 1,
        "head_dim": 128, "hidden_dim": 512, "vocab_size": 512,
        "rope_theta": 10000.0, "norm_eps": 1e-05, "tie": False,
        "weights": "bfloat16", "kv": "bfloat16", "page_size": 128,
        "settings": {}, "mesh": {}},
    "tiny-tp2-rehearsal": {
        "dim": 256, "n_layers": 2, "n_heads": 2, "n_kv_heads": 2,
        "head_dim": 128, "hidden_dim": 512, "vocab_size": 512,
        "rope_theta": 10000.0, "norm_eps": 1e-05, "tie": False,
        "weights": "int8", "kv": "bfloat16", "page_size": 128,
        "settings": {}, "mesh": {"tp": 2}},
}
# what make_configs left at the program's defaults, for every configuration
MODEL_DEFAULTS = {
    "dtype": "bfloat16", "logit_softcap": None, "embed_scale": False,
    "activation": "silu", "n_experts": 0, "n_experts_per_token": 2,
    "expert_capacity_factor": 1.25, "router_aux_coef": 0.01}


def _family(config_name: str):
    return families.of_config(HERE / "configs" / f"{config_name}.json")


@pytest.mark.parametrize("name", sorted(DENSE))
def test_dense_family_reads_a_configuration_as_the_harness_did(name):
    import dataclasses

    fam, sizes = _family(name)
    assert Path(fam.__file__).name == "dense-gqa.py"  # no "family" key
    want = DENSE[name]
    assert sizes == want
    got = dataclasses.asdict(fam.model_config(name, sizes, ENGINE))
    assert got == {
        "name": name, "vocab_size": want["vocab_size"], "dim": want["dim"],
        "n_layers": want["n_layers"], "n_heads": want["n_heads"],
        "n_kv_heads": want["n_kv_heads"], "hidden_dim": want["hidden_dim"],
        "max_seq_len": 2048, "rope_theta": want["rope_theta"],
        "norm_eps": want["norm_eps"], "tie_embeddings": want["tie"],
        "head_dim": want["head_dim"], **MODEL_DEFAULTS}


def test_harness_builds_the_mesh_and_engine_settings_itself():
    import run as bench
    from lmrs_tpu.config import MeshConfig

    fam, sizes = _family("tiny-tp2-rehearsal")
    eng = {"max_seq_len": 1024, "max_tokens": 32, "max_batch_slots": 4,
           "num_pages": 1, "prefill_chunk": 4096, "decode_block": 32}
    model_cfg, engine_cfg, mesh_cfg = bench.make_configs(
        fam, "tiny-tp2-rehearsal", sizes, eng)
    assert mesh_cfg == MeshConfig(tp=2)
    assert model_cfg.max_seq_len == 1024 and engine_cfg.quantize is None
    assert engine_cfg.kv_quantize is None and engine_cfg.page_size == 128


def _stub_family(tmp_path: Path, name: str, without: str | None,
                 as_package: bool = False) -> None:
    body = "\n".join(f"def {fn}(*a, **k):\n    return None"
                     for fn in families.API if fn != without) + "\n"
    if as_package:
        (tmp_path / name).mkdir()
        (tmp_path / name / "__init__.py").write_text(
            "from ._part import *  # noqa: F401,F403\n")
        (tmp_path / name / "_part.py").write_text(body)
    else:
        (tmp_path / f"{name}.py").write_text(body)


def test_unknown_family_ends_the_run_with_the_files_named(tmp_path,
                                                          monkeypatch):
    monkeypatch.setattr(families, "HERE", tmp_path)
    with pytest.raises(SystemExit) as e:
        families.load({"family": "latent-moe"}, "configs/some-model.json")
    msg = str(e.value)
    assert "configs/some-model.json" in msg and "'latent-moe'" in msg
    assert str(tmp_path / "latent-moe.py") in msg


@pytest.mark.parametrize("name", ["../dense-gqa", "", 7])
def test_family_that_is_not_a_name_is_refused(name):
    with pytest.raises(SystemExit, match="not a name"):
        families.load({"family": name}, "configs/some-model.json")


@pytest.mark.parametrize("without", ["sizes", "param_specs", "served_gap",
                                     "decode_attn_bytes", "cache_buffers"])
def test_family_that_lacks_a_function_names_file_and_function(
        tmp_path, monkeypatch, without):
    monkeypatch.setattr(families, "HERE", tmp_path)
    _stub_family(tmp_path, "half-done", without)
    with pytest.raises(SystemExit) as e:
        families.load({"family": "half-done"}, "configs/some-model.json")
    msg = str(e.value)
    assert str(tmp_path / "half-done.py") in msg
    assert without + families.API[without][0] in msg
    assert sum(fn + "(" in msg for fn in families.API) == 1  # only that one


@pytest.mark.parametrize("as_package", [False, True])
def test_whole_family_loads_as_a_file_or_as_a_package(tmp_path, monkeypatch,
                                                      as_package):
    monkeypatch.setattr(families, "HERE", tmp_path)
    _stub_family(tmp_path, "whole", None, as_package)
    fam = families.load({"family": "whole"}, "configs/some-model.json")
    assert all(callable(getattr(fam, fn)) for fn in families.API)


def test_gap_stats_that_is_no_function_is_refused(tmp_path, monkeypatch):
    monkeypatch.setattr(families, "HERE", tmp_path)
    _stub_family(tmp_path, "odd-stats", None)
    with (tmp_path / "odd-stats.py").open("a") as f:
        f.write("gap_stats = {'gap_p90': 0.5}\n")
    with pytest.raises(SystemExit, match=r"odd-stats.py.*gap_stats\(sizes\)"):
        families.load({"family": "odd-stats"}, "configs/some-model.json")


@pytest.mark.parametrize("served, correct", [
    ([0.0, 0.2, float("nan"), 0.1, 0.3], True),    # the NaN token: left out
    ([0.0, 0.2, 9.0, 0.1, 0.3], False),            # the same token, compared
    ([float("nan")] * 4 + [0.0], False),           # too few left to compare
])
def test_what_correct_compares_is_the_familys(tmp_path, monkeypatch, served,
                                              correct):
    """A family file alone decides which served tokens count (NaN: left out)
    and by which number (``gap_stats``): no edit to run.py, whose
    ``decide_correct`` is driven here without an engine."""
    from types import SimpleNamespace

    import numpy as np
    import run as bench

    monkeypatch.setattr(families, "HERE", tmp_path)
    _stub_family(tmp_path, "own-stats", "served_gap")
    with (tmp_path / "own-stats.py").open("a") as f:
        f.write("import numpy as np\n"
                "def served_gap(params, sizes, prompt_ids, served_ids):\n"
                "    return np.asarray(params, np.float32)\n"
                "def gap_stats(sizes):\n"
                "    return {'gap_upper_half_mean': lambda g: "
                "float(np.sort(g)[len(g) // 2:].mean())}\n")
    fam = families.load({"family": "own-stats"}, "configs/some-model.json")
    (tmp_path / "limits").mkdir()
    (tmp_path / "limits" / "a-cell.json").write_text(
        '{"gap_upper_half_mean": 0.5, "compared_tokens_min": 3}')
    monkeypatch.setattr(bench, "HERE", tmp_path)
    one = SimpleNamespace(prompt_ids=[5, 6], reported_prompt_tokens=2,
                          served_ids=[7] * len(served), kind="drawn")
    ctx = SimpleNamespace(
        family=fam, model={}, params=served, limits_name="a-cell",
        traffic={"sample_requests": 1},
        gen=SimpleNamespace(sample=lambda ctx, window: [one]))
    verdict = bench.decide_correct(ctx, {"failed": 0})
    checks = verdict["checks"]
    assert set(checks) == {"gap_upper_half_mean", "prompt_len_mismatch",
                           "failed_requests", "compared_tokens"}
    assert checks["compared_tokens"]["value"] == int(
        np.sum(~np.isnan(served)))
    assert verdict["correct"] is correct, checks


def test_family_without_a_layout_cannot_take_a_mesh():
    from lmrs_tpu.config import MeshConfig

    fam, sizes = _family("tiny-moe-rehearsal")
    with pytest.raises(SystemExit, match="moe-topk.py: param_specs"):
        families.shardings(fam, sizes, MeshConfig(tp=2))


def test_kernel_named_metrics_list_their_cells():
    """A roofline is of one kernel: a cell of a family with another decode
    or prefill kernel must not be held to report it."""
    import run as bench

    spec = bench.read_json(HERE.parent / "BENCHMARK.json")
    rooflines = [m for m in spec["per_layer"] if "_roofline" in m["name"]]
    assert len(rooflines) >= 3
    assert all(m.get("workloads") for m in rooflines), rooflines


# ---- the yardstick: dense-gqa hands out weights.py and reference.py ---------

IDS = [3 + (37 * i * i + 11 * i) % 500 for i in range(150)]


@pytest.fixture(scope="module")
def tiny_dense():
    fam, sizes = _family("tiny-rehearsal")
    return fam, sizes, fam.make_params(sizes, 7)


def test_dense_weights_are_weights_py_bit_for_bit(tiny_dense):
    import jax
    import numpy as np

    fam, sizes, params = tiny_dense
    direct = families.load_module(HERE / "weights.py", "direct_weights")
    want = direct.make_params(sizes, 7)
    got_l, got_t = jax.tree.flatten(params)
    want_l, want_t = jax.tree.flatten(want)
    assert got_t == want_t
    for g, w in zip(got_l, want_l):
        assert g.dtype == w.dtype and np.array_equal(np.asarray(g),
                                                     np.asarray(w))
    assert fam.weight_bytes(params) == direct.weight_bytes(want)


@pytest.mark.parametrize("mode", [None, "int8"])
def test_dense_reference_is_reference_py_bit_for_bit(tiny_dense, mode):
    import numpy as np

    fam, sizes, params = tiny_dense
    direct = families.load_module(HERE / "reference.py", "direct_reference")
    prompt, served = IDS[:118], IDS[118:]
    want = np.asarray(direct.logits_for(params, sizes, prompt, served, mode))
    got = np.asarray(fam.logits_for(params, sizes, prompt, served, mode))
    assert np.array_equal(got, want)
    if mode is None:
        assert np.array_equal(
            fam.served_gap(params, sizes, prompt, served),
            direct.served_gap(params, sizes, prompt, served))
    else:
        assert np.array_equal(
            fam.control_gap(params, sizes, prompt, served, mode),
            direct.control_gap(params, sizes, prompt, served, mode))


@pytest.mark.parametrize("name", ["mistral-7b-v0.3", "internlm2-1.8b"])
def test_dense_counts_are_flops_py(name):
    fam, sizes = _family(name)
    direct = families.load_module(HERE / "flops.py", "direct_flops")
    for fn, args in {
            "matmul_params": (), "attn_flops": (1251, 512),
            "prefill_flops": (512, 1, 384), "request_flops": (1400, 512, 128),
            "kv_bytes_per_token": (), "weight_stream_bytes": (),
            "decode_step_bytes": (48000,),
            "decode_attn_bytes": (48000,)}.items():
        assert getattr(fam, fn)(sizes, *args) == getattr(direct, fn)(sizes,
                                                                    *args)


# ---- moe-topk -----------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_moe():
    return _family("tiny-moe-rehearsal")


def _program_logits_and_routes(model_cfg, params, ids):
    """The program's plain forward pass over ``ids`` (bfloat16, no cache, no
    kernels), and the experts its router chose: [S, V], [L, S, k].  The
    routes are read by a wrapper around ``ops.moe.moe_mlp`` that spells the
    program's own router expression, so XLA computes it once for both."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import lmrs_tpu.ops.moe as moe
    from lmrs_tpu.models import transformer

    routes: list = []
    real = moe.moe_mlp

    def spy(mp, cfg, x):
        xt = x.reshape(-1, x.shape[-1])
        logits = jnp.einsum("nd,de->ne", xt.astype(jnp.float32),
                            mp["router"].astype(jnp.float32))
        _, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                               cfg.n_experts_per_token)
        jax.debug.callback(lambda i: routes.append(np.asarray(i)), idx,
                           ordered=True)
        return real(mp, cfg, x)

    moe.moe_mlp = spy
    try:
        lg, _ = transformer.forward(
            params, model_cfg, jnp.asarray([ids], jnp.int32),
            jnp.arange(len(ids))[None])
        lg = np.asarray(lg[0])
    finally:
        moe.moe_mlp = real
    return lg, np.stack(routes)


# Seeded weights, 256 seeded ids.  Before the first token that the two sides
# route differently, they differ by bfloat16's rounding alone (8 bits kept, a
# dozen roundings a layer, two layers and the head, logits of unit spread):
# at most 0.083 over seeds 0-19 (my CPU runs, PR 27); 0.2 is above twice
# that.  From the first differing route on, a token's logits differ by an
# expert's whole output, 0.5-3.4 over those seeds, and every later token
# attends it: not compared.  Seed 0 routes all 256 tokens alike; seed 3 first
# differs at token 49.
@pytest.mark.parametrize("seed, compared_at_least", [(0, 256), (3, 40)])
def test_moe_reference_agrees_with_the_programs_forward(tiny_moe, seed,
                                                        compared_at_least):
    import jax.numpy as jnp
    import numpy as np

    fam, sizes = tiny_moe
    n = 256
    params = fam.make_params(sizes, seed)
    ids = np.random.default_rng(seed).integers(3, 500, size=n).tolist()
    prog, prog_routes = _program_logits_and_routes(
        fam.model_config("tiny-moe", sizes, {"max_seq_len": 1024}), params,
        ids)
    dims = (sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"],
            sizes["rope_theta"], sizes["norm_eps"], sizes["top_k"])
    ref, ref_routes, margins = fam._forward(
        params, jnp.asarray(ids, jnp.int32), jnp.arange(n), dims=dims,
        mode=None)
    differ = (np.sort(prog_routes, -1)
              != np.sort(np.asarray(ref_routes), -1)).any(-1).any(0)  # [S]
    first = int(np.argmax(differ)) if differ.any() else n
    assert first >= compared_at_least
    # the family's rule, on the reference's margins alone, leaves out every
    # token that the program routed otherwise, and not most of the others
    left_out = np.asarray(margins).min(0) < fam.ROUTE_MARGIN
    assert left_out[differ].all() and left_out.mean() < 0.25
    gap = np.abs(prog - np.asarray(ref)).max(-1)
    print(f"seed {seed}: {int(differ.sum())} of {n} tokens routed differently, "
          f"the first at {first}; logits differ by at most "
          f"{gap[:first].max():.4f} before it"
          + (f", {gap[first:].max():.4f} from it on" if first < n else ""))
    assert gap[:first].max() < 0.2
    # and logits_for reads those same logits at the served positions
    served = np.asarray(fam.logits_for(params, sizes, ids[:200], ids[200:]))
    assert np.allclose(served, np.asarray(ref)[199:255], atol=1e-4)
    # and served_gap / control_gap give NaN at just the rows the rule names
    for gaps in (fam.served_gap(params, sizes, ids[:200], ids[200:]),
                 fam.control_gap(params, sizes, ids[:200], ids[200:], "fp8")):
        assert np.array_equal(np.isnan(gaps), left_out[199:255])


def test_moe_counts_follow_its_weight_tree(tiny_moe):
    import jax

    fam, m = tiny_moe
    shapes = fam.param_shapes(m)
    matmul_leaves = sum(
        x.size for path, x in jax.tree_util.tree_leaves_with_path(shapes)
        if not any(k in jax.tree_util.keystr(path)
                   for k in ("embed", "ln_", "final_norm")))
    assert fam.matmul_params(m) == matmul_leaves
    expert = 3 * m["dim"] * m["hidden_dim"]
    assert (m["n_experts"], m["top_k"]) == (4, 2)
    assert fam.matmul_params(m) - fam.active_params(m) == \
        m["n_layers"] * (4 - 2) * expert  # a token multiplies k of E experts
    head = m["dim"] * m["vocab_size"]
    # one more generated token: the active body, the head, and attention
    # over the prompt and itself
    more = fam.request_flops(m, 100, 0, 2) - fam.request_flops(m, 100, 0, 1)
    assert more == 2.0 * fam.active_params(m) + \
        4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * 102
    assert fam.prefill_flops(m, 100, 1) == \
        2.0 * (fam.active_params(m) - head) * 100 + 2.0 * head + \
        fam.attn_flops(m, 100, 0)
    assert fam.weight_stream_bytes(m) == 2 * fam.matmul_params(m)  # all experts
    assert fam.weight_bytes(shapes) == 2 * sum(
        x.size for x in jax.tree.leaves(shapes))
    cfg = fam.model_config("tiny-moe", m, {"max_seq_len": 1024})
    assert (cfg.n_experts, cfg.n_experts_per_token,
            cfg.expert_capacity_factor) == (4, 2, 2.0)  # no token dropped
