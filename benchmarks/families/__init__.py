"""Model families: everything the harness takes from an architecture.

A configuration file names its family, ``"family": "<name>"`` (absent:
``dense-gqa``), and the harness loads ``families/<name>.py``, or the package
``families/<name>/``, and calls only what ``API`` and ``OPTIONAL`` list.  A
family owns the meaning of the configuration's published keys, and what
``correct`` compares: which served tokens (``served_gap``), by which numbers
(``gap_stats``).  The harness itself reads from ``sizes(config)`` only
``vocab_size`` (the tokenizer), ``weights``, ``kv``, ``page_size``,
``settings`` and ``mesh`` (the file's ``engine`` block), and hands the whole
dict back to the family's other functions, to the traffic generator
(``ctx.model``) and to the per-layer readers (``facts["model"]``, with the
family itself as ``facts["flops"]``).

A new architecture is a new file here, a configuration that names it, its
limits and its cell (README.md, "Adding things"); nothing that is here is
edited.  ``dense-gqa`` delegates to ``weights.py``, ``reference.py`` and
``flops.py``; ``moe-topk`` is the worked example of a family of its own.
"""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
DEFAULT = "dense-gqa"

API = {
    # function: (arguments, what it returns)
    "sizes": ("(config)", "the sizes the family's weights, reference and "
              "counts read, from the file's published keys, plus weights, kv, "
              "page_size, settings and mesh from its engine block"),
    "model_config": ("(name, sizes, engine_geometry)", "the program's "
                     "ModelConfig; engine_geometry is the traffic file's "
                     "engine block"),
    "param_shapes": ("(sizes)", "the weight tree as shapes and types, "
                     "nothing drawn"),
    "make_params": ("(sizes, seed, shardings)", "the weights on the "
                    "device(s), from the seed, in the served type, in one "
                    "jitted call"),
    "weight_bytes": ("(params)", "int"),
    "param_specs": ("(sizes)", "the program's PartitionSpec tree for that "
                    "weight tree, or None: no sharded configuration"),
    "served_gap": ("(params, sizes, prompt_ids, served_ids)", "[n] float32: "
                   "how far each served token's logit lies below the best of "
                   "the plain float32 reference (reference.py's docstring "
                   "states the contract); NaN marks a token the family does "
                   "not compare, by a rule on the reference alone that its "
                   "file states: the harness leaves it out and counts the "
                   "others as compared_tokens"),
    "control_gap": ("(params, sizes, prompt_ids, served_ids, mode)", "the "
                    "same for the token that the reference at the precision "
                    "`mode` puts first, the same tokens left out"),
    "matmul_params": ("(sizes)", "parameters in per-token matmuls"),
    "attn_flops": ("(sizes, n_new, n_ctx=0)", "float"),
    "prefill_flops": ("(sizes, n_tokens, head_tokens=None, kv_start=0)",
                      "float"),
    "request_flops": ("(sizes, n_prompt_computed, n_cached, n_generated)",
                      "float"),
    "kv_bytes_per_token": ("(sizes)", "cache bytes a token holds, all layers"),
    "weight_stream_bytes": ("(sizes)", "weight bytes one decode step reads"),
    "decode_step_bytes": ("(sizes, live_tokens)", "float"),
    "decode_attn_bytes": ("(sizes, live_tokens)", "float"),
    "cache_buffers": ("(scheduler)", "the device buffers of the engine's "
                      "cache (None entries allowed), in the order its "
                      "programs take them: freed before the reference runs"),
}


OPTIONAL = {
    "gap_stats": ("(sizes)", "{name: fn([n] float32 gaps) -> float}: numbers "
                  "of this family's own that a limits file may hold a limit "
                  "for, beside run.GAP_STATS or under one of its names in "
                  "its place; what `correct` compares is the family's too"),
}


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(
        name, path, submodule_search_locations=(
            [str(path.parent)] if path.name == "__init__.py" else None))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def bench_file(stem: str):
    """``benchmarks/<stem>.py`` as the module ``bench_<stem>``, loaded once
    a process: for a family that builds on weights.py, reference.py or
    flops.py."""
    path = HERE.parent / f"{stem}.py"
    mod = sys.modules.get(f"bench_{stem}")
    if mod is None or getattr(mod, "__file__", None) != str(path):
        mod = load_module(path, f"bench_{stem}")
    return mod


def load(config: dict, cfg_file):
    """The family module of a configuration, with every function of ``API``
    in it: a missing file or function ends the run here, by name."""
    name = config.get("family", DEFAULT)
    if not isinstance(name, str) or not re.fullmatch(r"[A-Za-z0-9_.-]+", name):
        raise SystemExit(f"{cfg_file}: \"family\" is {name!r}: not a name")
    path = HERE / f"{name}.py"
    if not path.is_file():
        path = HERE / name / "__init__.py"
    if not path.is_file():
        raise SystemExit(
            f"{cfg_file} names the family {name!r}: there is no "
            f"{HERE / (name + '.py')} and no {path}")
    fam = load_module(path, "bench_family_" + re.sub(r"\W", "_", name))
    missing = [fn for fn in API if not callable(getattr(fam, fn, None))]
    missing += [fn for fn in OPTIONAL
                if not callable(getattr(fam, fn, lambda: None))]
    if missing:
        raise SystemExit(
            f"{path} (family {name!r} of {cfg_file}) lacks "
            + ", ".join(fn + {**API, **OPTIONAL}[fn][0] for fn in missing)
            + ": families/__init__.py says what each returns")
    return fam


def of_config(cfg_file) -> tuple:
    """(family, sizes) of a configuration file."""
    config = json.loads(Path(cfg_file).read_text())
    fam = load(config, cfg_file)
    return fam, fam.sizes(config)


def shardings(fam, sizes: dict, mesh_cfg):
    """Where each leaf of the weights goes on the configuration's mesh: the
    program's own layout of the family's tree, so the engine finds every
    shard where it would have put it."""
    from lmrs_tpu.parallel.mesh import build_mesh
    from lmrs_tpu.parallel.sharding import specs_to_shardings

    specs = fam.param_specs(sizes)
    if specs is None:
        raise SystemExit(f"{fam.__file__}: param_specs gives None: the family "
                         "has no sharded configuration, and this one has a mesh")
    return specs_to_shardings(specs, build_mesh(mesh_cfg))
