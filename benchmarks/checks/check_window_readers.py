"""The two readers that PR 35 brought (``mixed_decode_roofline.offline``,
``window_walk_share.offline``) against facts worked out by hand and against
facts that lack what they read.

    JAX_PLATFORMS=cpu python benchmarks/checks/check_window_readers.py

``check_layer_readers.py``'s kind of check, in a file of its own (that one
lists its readers and its recorded facts; a PR that adds a configuration
edits neither).  The facts are made up here, of the rehearsal shape
(``configs/tiny-swa-moe-rehearsal.json``: windows 128, 128, 128, 0, 128, one
KV head of 128): no run, no device.  Checked:

* each reader gives what its docstring's formula gives, by hand;
* on facts of a program without the counters or the kernel (the parent of
  PR 35, a dense family, an untraced run) each gives None and raises
  nothing.
"""

from __future__ import annotations

import contextlib
import io
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
for d in (HERE.parent, HERE, HERE / "layer_metrics"):
    if str(d) not in sys.path:
        sys.path.insert(0, str(d))


def reader(name: str):
    import run as bench

    return bench.load_module(HERE / "layer_metrics" / f"{name}.py",
                             f"check_metric_{name.replace('.', '_')}")


def close(a, b, rel: float = 1e-9) -> bool:
    return a is not None and abs(a - b) <= rel * max(abs(a), abs(b), 1e-12)


def checks() -> dict[str, bool]:
    import families

    fam, m = families.of_config(
        HERE / "configs" / "tiny-swa-moe-rehearsal.json")
    dense, _ = families.of_config(HERE / "configs" / "tiny-rehearsal.json")
    cache = {"window": 128, "ring_pages": 2, "full_layers": 1,
             "window_layers": 4, "cache_pages_full": 32,
             "cache_pages_window": 32}
    walk = reader("window_walk_share.offline").read
    roof = reader("mixed_decode_roofline.offline").read
    counted = {"counters": {"kv_pages_full": 1504, "kv_pages_window": 1792},
               "report": {"window_cache": cache}}
    # one request of 300 prompt tokens and 3 generated: steps at 301, 302,
    # 303 cached positions; a position's K and V in a layer is 2 x 2 x 128
    # bytes; the full layer reads them all, each of 4 window layers 128
    nbytes = 512 * sum(n + 4 * 128 for n in (301, 302, 303))
    traced = {"model": m, "flops": fam, "peaks": {"hbm_bytes_per_s": 8e11},
              "window": {"requests": [{"prompt": 300, "generated": 3}]},
              "trace": {"kernels": {"paged_decode_fused.7": 0.25,
                                    "flash_attention.3": 1.0}}}
    out = {}
    with contextlib.redirect_stderr(io.StringIO()):
        out["window_walk_share.offline: 100 x 1792 / (4 x 1504 / 1)"] = close(
            walk(counted), 100.0 * 1792 / (4 * 1504))
        out["window_walk_share.offline: None without the counters"] = walk(
            {"counters": {"decode_tokens": 9}, "report": counted["report"]}
        ) is None
        out["window_walk_share.offline: None without the report's block"] = \
            walk({"counters": counted["counters"], "report": {}}) is None
        out["mixed_decode_roofline.offline: the bytes by hand over 0.25 s"] = \
            close(roof(traced), 100.0 * nbytes / 8e11 / 0.25)
        out["mixed_decode_roofline.offline: None on an untraced run"] = roof(
            {**traced, "trace": None}) is None
        out["mixed_decode_roofline.offline: None without the decode kernel"] \
            = roof({**traced, "trace": {"kernels": {"flash_attention.3": 1.0}}}
                   ) is None
        out["mixed_decode_roofline.offline: None for a family without "
            "window layers"] = roof({**traced, "flops": dense}) is None
    return out


def main() -> int:
    results = checks()
    for what, ok in results.items():
        print(("ok  " if ok else "BAD ") + what)
    bad = sum(not ok for ok in results.values())
    print("check_window_readers:", "FAILED" if bad else "passed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
