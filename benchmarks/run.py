"""One cell, one run, one last line.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one model family, one traffic
mix, one per-layer metric or one cell's limits is a file found by name
(README.md): the harness below knows none of them.  It builds the engine from
the configuration file, its family (families/__init__.py: the sizes, the
program's model configuration, the weights, the plain reference, the counts)
and the traffic file, makes the weights on the device from the seed, lets
the traffic's generator warm up and drive the window through the product's
entry, reads the peak memory, frees the engine's pool, and only then runs
the plain reference over a seeded sample of what the window served.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()  # set-up is counted from here

import argparse
import gc
import json
import os
import shutil
import sys
import threading
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import families  # noqa: E402  (benchmarks/families/: found beside this file)
from families import load_module  # noqa: E402,F401  (the checks take it from here)

EXIT_NO_CHIP = 3
EXIT_BAD_TREE = 4


def log(msg: str) -> None:
    print(f"[bench {time.time() - T_PROCESS:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def read_json(path: Path) -> dict:
    return json.loads(path.read_text())


def find_cell(name: str) -> tuple[dict, dict, bool]:
    """(cell, benchmark, rehearsal).  A cell of BENCHMARK.json, or, for the
    CPU rehearsal of the harness only, one of rehearsal.json."""
    bench = read_json(ROOT / "BENCHMARK.json")
    for w in bench["workloads"]:
        if w["name"] == name:
            return w, bench, False
    reh = HERE / "rehearsal.json"
    if reh.exists():
        for w in read_json(reh)["workloads"]:
            if w["name"] == name:
                return w, bench, True
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(bench: dict, group: str, cell_name: str) -> list[dict]:
    """The metrics of ``group`` that this cell has to report: those that
    list it under ``workloads``, and those that list nothing."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


class Recorder:
    """Stands between the product's executor and the engine and writes down
    what went in and what came out, with the time each request finished.
    It changes nothing: every call goes through to the engine as it came."""

    def __init__(self, inner):
        self.inner = inner
        self.records: list[SimpleNamespace] = []

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def generate_batch(self, requests, on_result=None, on_tokens=None):
        t_sub = time.time()
        by_id = {r.request_id: r for r in requests}

        def note(res, submit):
            def submit_and_note(more):
                by_id.update({r.request_id: r for r in more})
                submit(more)

            self.records.append(SimpleNamespace(
                req=by_id.get(res.request_id), res=res, t_sub=t_sub,
                t_done=time.time()))
            if on_result is not None:
                on_result(res, submit_and_note)

        kw = {"on_tokens": on_tokens} if on_tokens is not None else {}
        return self.inner.generate_batch(requests, on_result=note, **kw)


class CompileCounter:
    """Compile requests and persistent-cache hits, from JAX's monitoring
    events (copy of chip_smoke._CacheCounter)."""

    def __init__(self):
        import jax.monitoring as mon

        self.requests = self.hits = 0
        mon.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple[int, int]:
        return self.requests, self.hits


def make_configs(fam, name: str, m: dict, eng: dict, quantize=None):
    """The program's ModelConfig (the family's), EngineConfig and MeshConfig
    (None on one chip) from a configuration's sizes (the family's ``sizes``)
    and a traffic file's ``engine`` geometry.  ``quantize`` switches the
    program's own lower-precision weight path on: only the control does
    that."""
    from lmrs_tpu.config import EngineConfig, MeshConfig

    model_cfg = fam.model_config(name, m, eng)
    settings = {
        "scheduler": "continuous", "prefix_cache": True, "host_kv": True,
        "kv_disk": False,
        # the family's weights arrive already in their served type: the
        # engine must not quantise them again
        "quantize": quantize,
        "kv_quantize": "int8" if m["kv"] == "int8" else None,
        **m["settings"]}
    engine_cfg = EngineConfig(
        backend="jax", model=name, temperature=0.0,
        max_tokens=eng["max_tokens"], retry_attempts=1, retry_delay=0.0,
        seed=0, max_batch_slots=eng["max_batch_slots"],
        page_size=m["page_size"], num_pages=eng["num_pages"],
        prefill_chunk=eng["prefill_chunk"], decode_block=eng["decode_block"],
        tokenizer="", fault_plan="", request_deadline_s=0.0, **settings)
    mesh_cfg = MeshConfig(**m["mesh"]) if m["mesh"] else None
    return model_cfg, engine_cfg, mesh_cfg


def build_engine(ctx, quantize=None):
    """Weights on the device(s) from the seed, and the engine around them."""
    import jax

    from lmrs_tpu.engine.jax_engine import JaxEngine

    m, eng, fam = ctx.model, ctx.traffic["engine"], ctx.family
    ctx.model_cfg, ctx.engine_cfg, ctx.mesh_cfg = make_configs(
        fam, ctx.cell["config"], m, eng, quantize)
    t0 = time.time()
    # on a mesh every leaf is drawn where the program's own layout puts it;
    # on one chip there is nothing to place
    placed = (families.shardings(fam, m, ctx.mesh_cfg)
              if ctx.mesh_cfg is not None else None)
    ctx.params = fam.make_params(m, ctx.seed, placed)
    jax.block_until_ready(ctx.params)
    log(f"weights on device: {fam.weight_bytes(ctx.params) / 1e9:.2f} GB "
        f"in {time.time() - t0:.1f}s")
    t0 = time.time()
    ctx.engine = JaxEngine(ctx.engine_cfg, ctx.model_cfg, ctx.mesh_cfg,
                           params=ctx.params, tokenizer=ctx.tok)
    ctx.sched = ctx.engine._scheduler
    log(f"engine built in {time.time() - t0:.1f}s: pool "
        f"{ctx.sched.cache.num_pages} pages x {m['page_size']} tokens, "
        f"{eng['max_batch_slots']} slots, window {eng['max_seq_len']}")


def free_engine(ctx) -> None:
    """Shut the engine down and free its cache now; the weights stay, they
    are the benchmark's and the reference reads them."""
    ctx.engine.shutdown()
    for buf in ctx.family.cache_buffers(ctx.sched):
        if buf is not None:
            buf.delete()
    ctx.engine._scheduler = ctx.engine._runner = None
    ctx.sched = None
    gc.collect()


def peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


FLIPPED_MIN = 32


def _flipped_msq(g) -> float:
    """Mean squared gap of the served tokens that are not the reference's
    own choice, at least FLIPPED_MIN of them counted.  How often a token
    flips depends on how close the seed's text runs its best two logits
    (from no token in 4096 to one in 18, PERF.md section 6); how far off a
    flipped token lies depends on the noise in the logits alone, and its
    square adds up as noise does."""
    return float((g * g).sum() / max(int((g > 0).sum()), FLIPPED_MIN))


GAP_STATS = {
    # the numbers a limits file may hold a limit for, each over the gaps of
    # every compared token of the sample; a family may bring more, or its own
    # reading of these names (families/__init__.py, ``gap_stats``)
    "logit_gap_max": lambda g: float(g.max()),
    "flipped_gap_msq": _flipped_msq,
}


def decide_correct(ctx, window: dict, control: str | None = None,
                   sample_requests: int | None = None) -> dict:
    """The comparison with the plain reference (README.md, 'correct').
    Returns the numbers compared, each beside its limit, and under
    ``gaps`` every served token's gap, request by request.  With
    ``control`` (int8, fp8, int4) the reference at that precision stands in
    the program's place: at every position of the same prompts and tokens
    the gap is that of the token the lower precision puts first.  What is
    compared is the family's: a token whose gap it gives as NaN is left out
    (by a rule on the reference alone, which the family states), and its
    ``gap_stats``, where it has one, joins or replaces ``GAP_STATS``."""
    import numpy as np

    own_stats = getattr(ctx.family, "gap_stats", None)
    stats = {**GAP_STATS, **(own_stats(ctx.model) if own_stats else {})}
    limits = read_json(HERE / "limits" / f"{ctx.limits_name}.json")
    ctx.traffic["sample_requests"] = sample_requests or limits.get(
        "sample_requests", ctx.traffic["sample_requests"])
    sample = ctx.gen.sample(ctx, window)
    t0 = time.time()
    per_request, mismatched, left_out = [], 0, 0
    for s in sample:
        if len(s.prompt_ids) != s.reported_prompt_tokens:
            mismatched += 1
        if not s.served_ids:
            continue
        if control is None:
            gaps = ctx.family.served_gap(ctx.params, ctx.model,
                                         s.prompt_ids, s.served_ids)
        else:
            gaps = ctx.family.control_gap(ctx.params, ctx.model,
                                          s.prompt_ids, s.served_ids, control)
        gaps = np.asarray(gaps)
        left_out += int(np.isnan(gaps).sum())
        gaps = gaps[~np.isnan(gaps)]
        per_request.append(gaps)
        log(f"reference: request of {len(s.prompt_ids)} + {len(s.served_ids)} "
            f"tokens ({s.kind}): widest gap {float(gaps.max(initial=0)):.4f}, "
            f"greedy-equal {int((gaps == 0).sum())}/{len(gaps)}")
    n_tok = sum(len(g) for g in per_request)
    all_gaps = (np.concatenate(per_request) if n_tok
                else np.zeros((1,), np.float32))
    log(f"reference{' (control ' + control + ')' if control else ''} over "
        f"{len(sample)} requests, {n_tok} served tokens"
        + (f" ({left_out} more left out by the family's rule)" if left_out
           else "")
        + f", in {time.time() - t0:.1f}s; not the reference's own choice: "
        f"{int((all_gaps > 0).sum())} tokens; "
        + ", ".join(f"{k} {fn(all_gaps):.6f}" for k, fn in stats.items()))
    checks = {k: {"value": fn(all_gaps), "limit": limits[k]}
              for k, fn in stats.items() if k in limits}
    if not checks:
        raise SystemExit(f"limits/{ctx.limits_name}.json holds no limit")
    checks.update({
        "prompt_len_mismatch": {"value": mismatched, "limit": 0},
        "failed_requests": {"value": window["failed"], "limit": 0},
        "compared_tokens": {"value": n_tok,
                            "at_least": limits["compared_tokens_min"]},
    })
    ok = all(c["value"] <= c["limit"] if "limit" in c
             else c["value"] >= c["at_least"] for c in checks.values())
    return {"correct": bool(ok), "checks": checks,
            "gaps": [g.tolist() for g in per_request]}


def print_verdict(verdict: dict) -> None:
    """Each number compared beside its limit: standard error's last lines."""
    for name, c in verdict["checks"].items():
        bound = (f"limit {c['limit']}" if "limit" in c
                 else f"at least {c['at_least']}")
        print(f"check {name}: {c['value']} ({bound})", file=sys.stderr)
    print(f"correct: {verdict['correct']}", file=sys.stderr, flush=True)


def open_cell(workload: str, seed: int, seconds: float, trace: bool):
    """Everything up to (not including) the engine: the cell, its data
    files, the device check.  Returns the context, or an exit code."""
    if not (ROOT / "lmrs_tpu").is_dir():
        print("benchmarks/run.py: no lmrs_tpu/ beside benchmarks/: this is "
              "not a checkout of the system under test", file=sys.stderr)
        return EXIT_BAD_TREE
    for d in (HERE / "layer_metrics", ROOT):
        if str(d) not in sys.path:
            sys.path.insert(0, str(d))
    cell, bench, rehearsal = find_cell(workload)
    if rehearsal:
        # the CPU rehearsal of the harness: tiny shapes, kernels interpreted,
        # as many virtual CPU devices as the rehearsal cell's mesh takes
        os.environ.setdefault("LMRS_FORCE_KERNELS", "interpret")
        if cell["chips"] > 1 and "jax" not in sys.modules:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform"
                f"_device_count={cell['chips']}").strip()

    import jax

    from lmrs_tpu.utils.logging import setup_logging
    from lmrs_tpu.utils.platform import setup_compile_cache

    setup_logging(quiet=True, stream=sys.stderr)
    setup_compile_cache()  # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    devs = jax.local_devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    peaks_tab = read_json(HERE / "peaks.json")["kinds"]
    on_chip = device["platform"] == "tpu"
    if not rehearsal:
        if not on_chip or len(devs) < cell["chips"]:
            print(f"benchmarks/run.py: cell {cell['name']} needs "
                  f"{cell['chips']} TPU chip(s); JAX reports {device}",
                  file=sys.stderr)
            return EXIT_NO_CHIP
        if device["kind"] not in peaks_tab:
            print(f"benchmarks/run.py: no peaks for device kind "
                  f"{device['kind']!r} in benchmarks/peaks.json",
                  file=sys.stderr)
            return EXIT_NO_CHIP

    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == cell["config"]), None)
    cfg_file = (ROOT / cfg_entry["file"] if cfg_entry
                else HERE / "configs" / f"{cell['config']}.json")
    family, model = families.of_config(cfg_file)
    traffic = read_json(HERE / "traffic" / f"{cell['traffic']}.json")
    if rehearsal and "rehearsal" in traffic:
        # the tiny shapes of the CPU rehearsal, kept beside the real ones
        for k, v in traffic["rehearsal"].items():
            if isinstance(v, dict) and isinstance(traffic.get(k), dict):
                traffic[k] = {**traffic[k], **v}
            else:
                traffic[k] = v
    tok_mod = load_module(HERE / "tokenizer.py", "bench_tokenizer")
    ctx = SimpleNamespace(
        cell=cell, bench=bench, rehearsal=rehearsal,
        seed=seed, seconds=seconds, trace=trace,
        family=family, model=model, traffic=traffic, device=device,
        on_chip=on_chip,
        peaks=peaks_tab.get(device["kind"]), log=log, tok_mod=tok_mod,
        tok=tok_mod.IdTokenizer(model["vocab_size"]),
        Recorder=Recorder,
        facts={})
    ctx.limits_name = cell.get("limits", cell["name"])
    ctx.gen = load_module(
        HERE / "traffic" / f"{traffic['generator']}.py",
        f"bench_traffic_{traffic['generator']}")
    return ctx


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None, help=(
        "never given by the driver: run the control that has to come out "
        "correct: false.  int8 | fp8 | int4: the plain reference at that "
        "weight precision put in the program's place after the window; "
        "program-int8: the program's own int8 weight path switched on"))
    args = ap.parse_args(argv)
    ctx = open_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    if isinstance(ctx, int):
        return ctx
    import jax

    counter = CompileCounter()
    cell, bench, traffic, model = ctx.cell, ctx.bench, ctx.traffic, ctx.model
    device, on_chip = ctx.device, ctx.on_chip
    e2e = metrics_for(bench, "end_to_end", cell["name"])
    per_layer = metrics_for(bench, "per_layer", cell["name"])

    program_path = (args.control or "").startswith("program-")
    build_engine(ctx, quantize=args.control[8:] if program_path else None)
    ctx.gen.setup(ctx)  # the entry, the inputs, and the warm-up

    # ---- the window -----------------------------------------------------
    trace_dir = HERE / ".trace" / f"{cell['name']}"
    marks: dict = {}

    def window_begin() -> None:
        ctx.sched.reset_latency_stats()
        marks["metrics0"] = dict(ctx.sched.metrics)
        marks["anatomy0"] = ctx.sched.anatomy_snapshot()
        marks["compiles0"] = counter.snapshot()
        # anything that compiles inside the window is named on stderr
        jax.config.update("jax_log_compiles", True)
        if ctx.trace and on_chip:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # millions of events otherwise
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            marks["span"] = jax.profiler.TraceAnnotation("bench_window")
            marks["span"].__enter__()
        marks["t0"] = time.time()
        marks["setup_s"] = marks["t0"] - T_PROCESS

    def window_end() -> None:
        marks["t1"] = time.time()
        jax.config.update("jax_log_compiles", False)
        if ctx.trace and on_chip:
            marks["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()
        marks["metrics1"] = dict(ctx.sched.metrics)
        marks["anatomy"] = ctx.sched.anatomy_report(marks["anatomy0"])
        marks["report"] = ctx.sched.metrics_report()
        marks["compiles1"] = counter.snapshot()

    ctx.window_begin, ctx.window_end = window_begin, window_end
    window = ctx.gen.window(ctx)
    ctx.gen.teardown(ctx)
    window_s = marks["t1"] - marks["t0"]
    m0, m1 = marks["metrics0"], marks["metrics1"]
    delta = {k: m1[k] - m0[k] for k in m1
             if isinstance(m1[k], (int, float)) and not k.startswith("peak_")}
    c0, c1 = marks["compiles0"], marks["compiles1"]
    compiled_in_window = (c1[0] - c0[0]) - (c1[1] - c0[1])
    mem_peak = peak_bytes()
    log(f"window {window_s:.2f}s: attempted {window['attempted']} failed "
        f"{window['failed']}; compile requests in window {c1[0] - c0[0]} "
        f"(fresh {compiled_in_window}); peak device bytes {mem_peak}")
    log("counters over the window: " + json.dumps(
        {k: (round(v, 3) if isinstance(v, float) else v)
         for k, v in delta.items() if v}))
    pq = delta.get("prefix_queries", 0)
    log(f"prefix cache: {delta.get('prefix_hits', 0)}/{pq} hits, "
        f"{delta.get('prefix_tokens_reused', 0)} tokens reused; span "
        f"dispatches {delta.get('rpa_dispatches', 0)}, mixed "
        f"{delta.get('mixed_dispatches', 0)}, decode dispatches "
        f"{delta.get('decode_dispatches', 0)}")
    device["memory_peak_bytes"] = mem_peak

    # ---- correct: only now, with the peak read and the pool freed --------
    free_engine(ctx)
    verdict = decide_correct(
        ctx, window, control=None if program_path else args.control)

    # ---- metrics ---------------------------------------------------------
    out_metrics: dict = {}
    breakdown = None
    if not ctx.trace:
        values = dict(window["e2e"])
        values["setup_s"] = marks["setup_s"]
        # a time or a rate comes only from a chip run: off the chip (the CPU
        # rehearsal) the line carries no end-to-end metric at all
        for m in (e2e if on_chip else []):
            if m["name"] not in values:
                raise SystemExit(f"traffic generator {traffic['generator']} "
                                 f"gives no {m['name']}")
            out_metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        facts = ctx.facts
        facts.update(model=model, peaks=ctx.peaks, window_s=window_s,
                     counters=delta, anatomy=marks["anatomy"],
                     report=marks["report"], window=window, flops=ctx.family,
                     slots=traffic["engine"]["max_batch_slots"])
        if on_chip:
            reduce_mod = load_module(HERE / "trace_reduce.py",
                                     "bench_trace_reduce")
            t0 = time.time()
            red = reduce_mod.reduce_dir(trace_dir, n_devices=cell["chips"])
            log(f"trace reduced in {time.time() - t0:.1f}s: busy "
                f"{red['busy_s']:.3f}s of {red['window_s']:.3f}s traced")
            facts["trace"] = red
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"][:10],
                         "idle_gaps": red["idle_gaps"][:10]}
            log("device time by program: " + json.dumps(red["programs"]))
            log("device time by kernel: " + json.dumps(red["kernels"]))
            if not os.environ.get("BENCH_KEEP_TRACE"):
                shutil.rmtree(trace_dir, ignore_errors=True)
        for m in per_layer:
            if not on_chip and m["source"] != "program_counter":
                continue  # counts only from a CPU run: no time, no share
            reader = load_module(HERE / "layer_metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name'].replace('.', '_')}")
            value = reader.read(facts)
            if value is not None:
                out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    print_verdict(verdict)
    line = {"correct": verdict["correct"], "attempted": window["attempted"],
            "failed": window["failed"], "metrics": out_metrics,
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = verdict["checks"]
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the engine (watchdog runner) must not hold the exit
    os._exit(rc) if threading.active_count() > 1 else sys.exit(rc)
