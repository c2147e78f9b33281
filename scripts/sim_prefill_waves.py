"""Which fresh-prefill programs a benchmark cell's window meets that its
warm-up did not, and the window's prefill padding, under the scheduler's
``prefill_wave_plan`` and under the power of two alone: no model and no
chip.  A fake engine answers every request with ``max_tokens`` seeded ids
(the benchmark's weights are random, so its requests end on length too),
the cell's own chunker, tokenizer and traffic generator make the prompts
from ``--seed`` as ``benchmarks/run.py`` does, and each ``generate_batch``
call is cut into waves of ``max_batch_slots`` prompts (a closed loop of
equal generated lengths admits them so).  Internlm's prefix hits take the
span program, which this does not model.

    python scripts/sim_prefill_waves.py mistral7b-offline --seeds 1000 160
"""
import argparse
import json
import random
import sys
from multiprocessing import Pool
from pathlib import Path

import _pathfix  # noqa: F401

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmarks"))
import families  # noqa: E402
from families import load_module  # noqa: E402

from lmrs_tpu.engine.api import GenerationResult  # noqa: E402
from lmrs_tpu.engine.scheduler import (prefill_row_rung,  # noqa: E402
                                       prefill_wave_plan)
from lmrs_tpu.ops.paged_attention import pow2_bucket  # noqa: E402

tok_mod = load_module(ROOT / "benchmarks/tokenizer.py", "bench_tokenizer")
gen = load_module(ROOT / "benchmarks/traffic/offline_jobs.py", "bench_gen")
run = load_module(ROOT / "benchmarks/run.py", "bench_run")


class FakeEngine:
    """Records each ``generate_batch`` call's prompt lengths."""

    schedules_internally = True

    def __init__(self, tok, eng: dict, rng: random.Random):
        self.tokenizer, self.eng, self.rng, self.calls = tok, eng, rng, []

    def engine_metrics(self) -> dict:
        return {}

    def generate_batch(self, requests, on_result=None, on_tokens=None):
        out, lens = [], []
        for r in requests:
            max_new = min(r.max_new_tokens or self.eng["max_tokens"],
                          self.eng["max_tokens"], self.eng["max_seq_len"] - 1)
            ids = tok_mod.encode_prompt(self.tokenizer, r.system_prompt,
                                        r.prompt, self.eng["max_seq_len"],
                                        max_new)
            lens.append(len(ids))
            text = tok_mod.spell([self.rng.randrange(3, self.tokenizer.vocab_size)
                                  for _ in range(max_new)])
            out.append(GenerationResult(
                request_id=r.request_id, text=text, prompt_tokens=len(ids),
                completion_tokens=max_new, finish_reason="length"))
        self.calls.append(lens)
        for res in out:
            if on_result is not None:
                on_result(res, lambda more: None)
        return out


def cell_calls(cell_name: str, seed: int, cycles: int = 2):
    """(warm-up calls, window calls, engine geometry, page size)."""
    from lmrs_tpu.config import ChunkConfig, PipelineConfig, ReduceConfig
    from lmrs_tpu.engine.executor import MapExecutor
    from lmrs_tpu.pipeline import (TranscriptSummarizer, build_chunker,
                                   prepare_segments)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    cfg_file = next(c["file"] for c in bench["configs"]
                    if c["name"] == cell["config"])
    family, model = families.of_config(ROOT / cfg_file)
    t = json.loads((ROOT / "benchmarks/traffic" /
                    f"{cell['traffic']}.json").read_text())
    model_cfg, engine_cfg, _ = run.make_configs(family, cell["config"], model,
                                                t["engine"])
    cfg = PipelineConfig(
        chunk=ChunkConfig(tokenizer="approx", **t["chunk"]),
        engine=engine_cfg, model=model_cfg,
        reduce=ReduceConfig(temperature=0.0, **t["reduce"]))
    s = TranscriptSummarizer(cfg)
    eng = FakeEngine(tok_mod.IdTokenizer(model["vocab_size"]), t["engine"],
                     random.Random(seed ^ 77))
    s._executor = MapExecutor(eng, cfg.engine)
    s._chunker = build_chunker(cfg, eng)

    def count_chunks(transcript: dict) -> int:
        return len(s.chunker.chunk_transcript(
            prepare_segments(cfg, transcript)[1]))

    # the generator's order: the warm-up's sizes ascending, then the
    # window's cycles of the seed's shuffled sizes (offline_jobs.setup)
    rng, seg, sizes = random.Random(seed), tuple(t["segment_bytes"]), \
        list(t["job_chunks"])

    def jobs(order) -> list:
        eng.calls = []
        for n in order:
            s.summarize(gen.sized_transcript(rng.randrange(1 << 30), n,
                                             count_chunks, seg))
        return eng.calls

    warm = jobs(sorted(sizes))
    window = []
    for _ in range(cycles):
        order = list(sizes)
        rng.shuffle(order)
        window += jobs(order)
    return warm, window, t["engine"], model["page_size"]


def groups(calls, slots: int, max_len: int) -> list:
    """Each call's waves of ``slots`` prompts, cut by power of two and
    sorted, as ``_advance_prefills`` groups them."""
    out = []
    for c in calls:
        for i in range(0, len(c), slots):
            g: dict = {}
            for n in c[i: i + slots]:
                g.setdefault(min(pow2_bucket(n, 64), max_len), []).append(n)
            out.extend(sorted(v) for v in g.values())
    return out


def one(args) -> dict:
    cell, seed = args
    warm_calls, win_calls, eng, page = cell_calls(cell, seed)
    slots, max_len = eng["max_batch_slots"], eng["max_seq_len"]
    met = {(r, s) for g in groups(warm_calls, slots, max_len)
           for _, r, s in prefill_wave_plan(g, slots, page, max_len)}
    new, held, pow2, tokens = [], 0, 0, 0
    for g in groups(win_calls, slots, max_len):
        for _, r, s in prefill_wave_plan(g, slots, page, max_len):
            if (r, s) not in met:
                new.append((r, s))
                met.add((r, s))
            held += r * s
        pow2 += prefill_row_rung(len(g), slots) * min(pow2_bucket(g[-1], 64),
                                                      max_len)
        tokens += sum(g)
    return {"seed": seed, "new": new, "waste": 100 * (1 - tokens / held),
            "waste_pow2": 100 * (1 - tokens / pow2)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, nargs=2, default=[1000, 160],
                    metavar=("FIRST", "COUNT"))
    ap.add_argument("--procs", type=int, default=4)
    a = ap.parse_args()
    seeds = range(a.seeds[0], a.seeds[0] + a.seeds[1])
    with Pool(a.procs) as p:
        res = p.map(one, [(a.cell, s) for s in seeds])
    bad = [r for r in res if r["new"]]
    print(f"{a.cell}: {len(res)} seeds from {a.seeds[0]}; {len(bad)} meet a "
          f"program in the window that the warm-up did not "
          f"{[(r['seed'], r['new']) for r in bad[:3]]}; pad waste "
          f"{min(r['waste'] for r in res):.2f}-"
          f"{max(r['waste'] for r in res):.2f}% (power of two "
          f"{min(r['waste_pow2'] for r in res):.2f}-"
          f"{max(r['waste_pow2'] for r in res):.2f}%)")


if __name__ == "__main__":
    main()
