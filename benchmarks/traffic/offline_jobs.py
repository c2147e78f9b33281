"""Closed-loop whole-transcript jobs through the product's entry.

One general generator: every number it uses (chunk geometry, job sizes,
warm-up) comes from the traffic file.  Jobs run back to back, one in
flight, through ``TranscriptSummarizer.summarize``; the only thing put in
the product's way is the Recorder between its executor and the engine.

The window is made of whole cycles of the file's ``job_chunks`` (each size
once, in the seed's order, with the seed's words): cycles are started until
``--seconds`` have passed, and the window closes when the cycle in flight
ends.  ``job_tok_s`` is every token of every request of those jobs over
that whole time.  Two things force this.  A window cut at ``--seconds``
sharp would count a 24-row decode wave of 128 steps either whole or not at
all: a fifth of the reading on the slower cell.  And a job's rate depends
on its size (a second wave of 16 rows costs what one of 24 costs), so every
window has to hold the same sizes.  The warm-up is one such cycle (and,
under a prefix cache, its first job again): it reaches every shape the
window will, and it tells how many transcripts to prepare.
"""

from __future__ import annotations

import random
import time
from types import SimpleNamespace

_WORDS = ("the quarterly review covered the inference engine roadmap kernel "
          "design latency targets hiring plan budget allocation serving tier "
          "page pool prefix cache scheduler admission decode block rollout "
          "incident follow-up owners deadline risks mitigation").split()


def _text(rng, n_bytes: int) -> str:
    out, size = [], 0
    while size < n_bytes:
        w = rng.choice(_WORDS)
        out.append(w)
        size += len(w) + 1
    return " ".join(out)[:n_bytes]


def synth_transcript(seed: int, n_segments: int, seg_bytes=(90, 150)) -> dict:
    """Seeded diarized transcript in the README's schema (copy of
    chip_smoke.synth_transcript; the segment length is a parameter, and the
    traffic file fixes it so that every seed's chunks hold the same number
    of tokens)."""
    rng = random.Random(seed)
    segs, t = [], 0.0
    lo, hi = seg_bytes
    for i in range(n_segments):
        dur = 4.0 + rng.random() * 8.0
        segs.append({"start": round(t, 2), "end": round(t + dur, 2),
                     "text": _text(rng, rng.randint(lo, hi)).capitalize()
                     + ".",
                     "speaker": f"SPEAKER_{(i // 3) % 3:02d}"})
        t += dur + rng.random()
    return {"segments": segs}


def sized_transcript(seed: int, n_chunks: int, count_chunks,
                     seg_bytes=(90, 150)) -> dict:
    """The longest seeded transcript that the product's own chunker cuts
    into exactly ``n_chunks`` chunks (so the last chunk is nearly full)."""
    full = synth_transcript(seed, n_chunks * 14 + 40, seg_bytes)["segments"]
    lo, hi = 1, len(full)  # count(lo) <= n_chunks < count(hi)
    if count_chunks({"segments": full}) <= n_chunks:
        raise RuntimeError("transcript too short to size")
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if count_chunks({"segments": full[:mid]}) <= n_chunks:
            lo = mid
        else:
            hi = mid
    out = {"segments": full[:lo]}
    if count_chunks(out) != n_chunks:
        raise RuntimeError(f"could not size a transcript to {n_chunks} chunks")
    return out


def setup(ctx) -> None:
    from lmrs_tpu.config import (ChunkConfig, PipelineConfig, ReduceConfig)
    from lmrs_tpu.engine.executor import MapExecutor
    from lmrs_tpu.pipeline import (TranscriptSummarizer, build_chunker,
                                   prepare_segments)

    t = ctx.traffic
    cfg = PipelineConfig(
        chunk=ChunkConfig(tokenizer="approx", **t["chunk"]),
        engine=ctx.engine_cfg, model=ctx.model_cfg,
        reduce=ReduceConfig(temperature=0.0, **t["reduce"]))
    s = TranscriptSummarizer(cfg, profile=ctx.trace and ctx.on_chip)
    ctx.recorder = ctx.Recorder(ctx.engine)
    # in place of the executor its property would build through make_engine
    s._executor = MapExecutor(ctx.recorder, cfg.engine)
    s._chunker = build_chunker(cfg, ctx.recorder)
    ctx.summarizer = s

    def count_chunks(transcript: dict) -> int:
        _, processed = prepare_segments(cfg, transcript)
        return len(s.chunker.chunk_transcript(processed))

    rng = random.Random(ctx.seed)
    ctx.count_chunks = count_chunks
    ctx.job_rng = rng
    ctx.sizes = list(t["job_chunks"])
    ctx.seg_bytes = tuple(t["segment_bytes"])

    def warm(n: int) -> float:
        t0 = time.time()
        st = s.summarize(sized_transcript(rng.randrange(1 << 30), n,
                                          count_chunks, ctx.seg_bytes))
        ctx.log(f"warm-up job of {st['num_chunks']} chunks: "
                f"{time.time() - t0:.1f}s, stage_times {st['stage_times']}")
        return time.time() - t0

    # one cycle, smallest size first.  Where the scheduler's counters show
    # a prefix cache at work, the first size once more: the first job of a
    # process meets a cold cache and takes the fresh-prefill programs, every
    # later one hits it and takes the span programs, so each size has to be
    # seen behind a warm cache once (PERF.md section 6, cell 3's first trace).
    cycle_s = 0.0
    if t.get("warmup", True):
        order = sorted(ctx.sizes)
        walls = [warm(n) for n in order]
        if ctx.sched.metrics["prefix_queries"] > 0:
            walls[0] = warm(order[0])
        cycle_s = sum(walls)
    seconds = t["trace_seconds"] if ctx.trace else ctx.seconds
    n_cycles = int(seconds // cycle_s) + 1 if cycle_s > 0 else 1
    t0 = time.time()
    ctx.jobs = []
    for _ in range(n_cycles + 1):
        ctx.jobs.extend(_cycle(ctx))
    ctx.log(f"a cycle {ctx.sizes} took {cycle_s:.1f}s warm; "
            f"{len(ctx.jobs)} transcripts prepared in {time.time() - t0:.1f}s")
    ctx.recorder.records.clear()


def _cycle(ctx) -> list[dict]:
    sizes = list(ctx.sizes)
    ctx.job_rng.shuffle(sizes)
    return [sized_transcript(ctx.job_rng.randrange(1 << 30), n,
                             ctx.count_chunks, ctx.seg_bytes) for n in sizes]


def window(ctx) -> dict:
    s = ctx.summarizer
    seconds = ctx.traffic["trace_seconds"] if ctx.trace else ctx.seconds
    jobs = []
    ctx.window_begin()
    t0 = time.time()
    n = len(ctx.sizes)
    while time.time() - t0 < seconds:
        if len(ctx.jobs) < n:
            # more cycles than the warm-up's timing foresaw: made on the
            # spot, inside the window, and said so
            ctx.facts["cycles_made_in_window"] = ctx.facts.get(
                "cycles_made_in_window", 0) + 1
            ctx.jobs.extend(_cycle(ctx))
        cycle, ctx.jobs = ctx.jobs[:n], ctx.jobs[n:]
        for job in cycle:
            tj = time.time()
            st = s.summarize(job)
            jobs.append({"chunks": st["num_chunks"],
                         "wall_s": time.time() - tj,
                         "stage_times": st["stage_times"],
                         "levels": st["reduce_levels"]})
    wall = time.time() - t0
    ctx.window_end()
    recs = list(ctx.recorder.records)
    failed = sum(1 for r in recs if r.res.error is not None
                 or r.res.finish_reason not in ("stop", "length"))
    tokens = sum(r.res.prompt_tokens + r.res.completion_tokens for r in recs)
    ctx.log(f"{len(jobs)} jobs, {len(recs)} requests, {tokens} tokens in "
            f"{wall:.2f}s; per job (map + reduce): " + ", ".join(
                f"{j['chunks']}ch {j['wall_s']:.2f}s "
                f"({j['stage_times'].get('map', 0):.2f} + "
                f"{j['stage_times'].get('reduce', 0):.2f})" for j in jobs))
    return {
        "attempted": len(recs), "failed": failed,
        "e2e": {"job_tok_s": tokens / wall},
        "jobs": jobs, "wall_s": wall,
        "requests": [{"prompt": r.res.prompt_tokens,
                      "generated": r.res.completion_tokens} for r in recs],
        "_records": recs,
    }


def sample(ctx, win: dict) -> list:
    """A seeded sample of the finished requests, the longest in it."""
    recs = [r for r in win["_records"] if r.req is not None
            and r.res.error is None and r.res.completion_tokens > 0]
    if not recs:
        return []
    rng = random.Random(ctx.seed ^ 0x5EED)
    def size(r):
        return r.res.prompt_tokens + r.res.completion_tokens

    # the longest, the shortest (a reduce node, alone in its prefill) and
    # the rest drawn from the seed
    longest, shortest = max(recs, key=size), min(recs, key=size)
    rest = [r for r in recs if r is not longest and r is not shortest]
    rng.shuffle(rest)
    picked = [longest] + ([shortest] if shortest is not longest else [])
    picked += rest[: max(ctx.traffic["sample_requests"] - len(picked), 0)]
    eng = ctx.traffic["engine"]
    out = []
    for r in picked:
        max_new = min(r.req.max_new_tokens, eng["max_tokens"],
                      eng["max_seq_len"] - 1)
        out.append(SimpleNamespace(
            prompt_ids=ctx.tok_mod.encode_prompt(
                ctx.tok, r.req.system_prompt, r.req.prompt,
                eng["max_seq_len"], max_new),
            served_ids=ctx.tok_mod.unspell(r.res.text),
            reported_prompt_tokens=r.res.prompt_tokens,
            kind=("longest" if r is longest else
                  "shortest" if r is shortest else "drawn")))
    return out


def teardown(ctx) -> None:
    pass
