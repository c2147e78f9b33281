"""Offline quality gate, reduce-format half: the video-editor reduce contract,
GENERATED by a model fine-tuned inside the suite.

Lives in its own file (moved unchanged from tests/test_quality.py in PR 22)
because the driver deals test FILES whole to its six workers: with this
800-step fine-tune in the same file as the CLI gate's 1,500-step one, that
file alone ran ~19 of the run's ~24 minutes and the run ended 28-52 s short
of the limit.  Apart, the two train on different workers.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

# Condensed video-editor reduce template: the SAME instruction-following
# contract as prompts/assets/video_editor_reduce.txt (the reference's core
# reduce contract, result_aggregator.py:146-175 — five exact ### headers,
# [H:MM:SS] timestamps carried through, triggered by the literal
# "TIMELINE SUMMARY"), condensed to fit quality-tiny's 1024-byte window
# alongside the tagged summaries (the full ~1.2 KB asset would force a
# 2048 window and multiply the suite's CPU compile cost).
VIDEO_SECTIONS = ("TIMELINE SUMMARY", "KEY MOMENTS", "TOPIC SECTIONS",
                  "POTENTIAL B-ROLL", "QUOTE TIMESTAMPS")
VIDEO_REDUCE_TEMPLATE = (
    "Merge the edit notes. Keep every timestamp.\n{summaries}\n"
    "Reply with exactly these sections:\n"
    + "\n".join(f"### {s}" for s in VIDEO_SECTIONS) + "\n")


def _video_reduce_items(rng):
    """(start_s, topic) beats for one synthetic recording, times past one
    hour so format_timestamp emits the H:MM:SS form the contract names.
    Minute-aligned: the stamps stay arbitrary per example (the model must
    COPY them, not memorize them), but 3-4 varying digits per stamp keep
    byte-level copy induction learnable inside the suite's training
    budget — full second-resolution stamps (6 varying digits) measured
    0/6 exact carry-through at the same budget (digit spans resist
    copy-induction; the r4 speculation study hit the same wall)."""
    from lmrs_tpu.eval.synthetic import TOPICS

    n = int(rng.integers(2, 4))
    topics = [TOPICS[i] for i in rng.choice(len(TOPICS), n, replace=False)]
    t = 3600.0 + 60.0 * float(rng.integers(0, 30))
    items = []
    for topic in topics:
        items.append((t, topic))
        t += 60.0 * float(rng.integers(1, 8))
    return items


def _video_reduce_pair(items):
    """(prompt, target) in the EXACT product reduce format: chunk summaries
    carrying inline [H:MM:SS] markers, time-tagged and block-formatted by
    the real aggregator, with a five-section target document that copies
    every timestamp through (the reference's carry-every-timestamp
    contract)."""
    from types import SimpleNamespace

    from lmrs_tpu.config import EngineConfig
    from lmrs_tpu.data.preprocessor import format_timestamp
    from lmrs_tpu.data.tokenizer import ByteTokenizer
    from lmrs_tpu.reduce.aggregator import ResultAggregator

    agg = ResultAggregator(SimpleNamespace(config=EngineConfig()),
                           tokenizer=ByteTokenizer())
    tagged = []
    for start, topic in items:
        ts = format_timestamp(start)
        tagged.append(f"[Time: {ts} - {format_timestamp(start + 40)}]\n"
                      f"[{ts}] {topic}")
    prompt = agg._build_request(tagged, VIDEO_REDUCE_TEMPLATE,
                                metadata=None).prompt
    stamps = [format_timestamp(s) for s, _ in items]
    beats = "\n".join(f"[{ts}] {topic}" for (_, topic), ts
                      in zip(items, stamps))
    target = (
        f" ### TIMELINE SUMMARY\n{beats}\n"
        f"### KEY MOMENTS\n[{stamps[0]}] {items[0][1]}\n"
        f"### TOPIC SECTIONS\n[{stamps[0]}]-[{stamps[-1]}] "
        + ", ".join(t for _, t in items) + "\n"
        f"### POTENTIAL B-ROLL\n[{stamps[-1]}] {items[-1][1]}\n"
        f"### QUOTE TIMESTAMPS\n[{stamps[0]}] {items[0][1]}\n")
    return {"prompt": prompt, "summary": target}


@pytest.fixture(scope="module")
def video_format_model():
    """Fine-tune quality-tiny ONLY on video-editor reduce pairs (exact
    product prompt format).  A dedicated model because byte-level digit
    COPYING (timestamps must be carried, not invented) is a capacity-
    hungry skill: diluted into the CLI fixture's multi-task mix it never
    emerges at any suite-affordable step count (calibration 2026-08-01:
    mixed training produced perfect sections but 0/6 exact stamps;
    dedicated 800-step training reaches loss ~0.01 with stamps copied)."""
    import jax
    import jax.numpy as jnp
    import optax

    from lmrs_tpu.config import model_preset
    from lmrs_tpu.data.tokenizer import ByteTokenizer
    from lmrs_tpu.models.transformer import init_params
    from lmrs_tpu.training.cli import batches, load_examples
    from lmrs_tpu.training.train import make_train_step

    cfg = model_preset("quality-tiny")
    rng = np.random.default_rng(0)
    pairs = [_video_reduce_pair(_video_reduce_items(rng))
             for _ in range(1500)]
    assert max(len(p["prompt"]) + len(p["summary"])
               for p in pairs) <= 820, "video pair overflows the crop"

    import tempfile
    from pathlib import Path as P

    with tempfile.TemporaryDirectory() as td:
        data_path = P(td) / "video.jsonl"
        data_path.write_text("\n".join(json.dumps(p) for p in pairs))
        seqs, masks = load_examples(str(data_path), ByteTokenizer())

    params = init_params(cfg, jax.random.PRNGKey(0))
    steps = 800
    sched = optax.warmup_cosine_decay_schedule(0.0, 3e-3, 100, steps,
                                               3e-3 * 0.02)
    optimizer = optax.adamw(sched)
    opt_state = optimizer.init(params)
    step_fn = make_train_step(cfg, optimizer, None, masked=True)
    it = batches(seqs, masks, 8, 832, 0)
    loss = None
    for _ in range(steps):
        t, m = next(it)
        params, opt_state, loss = step_fn(params, opt_state,
                                          jnp.asarray(t), jnp.asarray(m))
    # calibration 2026-08-01: converges to ~0.01; stamp copying only
    # emerges well under ~0.06
    assert float(loss) < 0.05, f"video-format training failed: {float(loss)}"
    return cfg, ByteTokenizer(), params


def test_reduce_format_compliance(video_format_model):
    """The reference's core instruction-following contract, GENERATED
    (VERDICT r4 item 3): the trained model driven through the REAL
    video-editor reduce path (ResultAggregator.aggregate over real Chunk
    records — time-tagging, block formatting, engine wave) must emit the
    five ### sections in order, those five only, no preamble, with the
    input [H:MM:SS] timestamps carried through (result_aggregator.py:
    146-175's contract — previously the template was shipped but no test
    checked a generated document against it).  Three held-out recordings;
    format compliance must hold on ALL, exact stamp carry-through on >=2
    (calibration: 5/6 held-out fully compliant — one wobble allowed so a
    single hard example doesn't flake the gate)."""
    import re

    from lmrs_tpu.config import EngineConfig, ReduceConfig
    from lmrs_tpu.data.chunker import Chunk
    from lmrs_tpu.data.preprocessor import format_timestamp
    from lmrs_tpu.engine.executor import MapExecutor
    from lmrs_tpu.engine.jax_engine import JaxEngine
    from lmrs_tpu.reduce.aggregator import ResultAggregator

    cfg, tok, params = video_format_model
    ec = EngineConfig(backend="jax", scheduler="continuous", max_tokens=320,
                      max_batch_slots=2, seed=0, decode_block=16,
                      retry_delay=0.0)
    engine = JaxEngine(ec, cfg, params=params, tokenizer=tok)
    held = np.random.default_rng(777)
    stamps_ok = 0
    try:
        agg = ResultAggregator(MapExecutor(engine, ec),
                               ReduceConfig(temperature=0.0),
                               tokenizer="byte")
        for trial in range(3):
            items = _video_reduce_items(held)
            chunks = [
                Chunk(start_time=s, end_time=s + 40.0, chunk_index=i,
                      summary=f"[{format_timestamp(s)}] {topic}")
                for i, (s, topic) in enumerate(items)
            ]
            out = agg.aggregate(chunks,
                                prompt_template=VIDEO_REDUCE_TEMPLATE)
            text = out["final_summary"]
            positions = [text.find(f"### {s}") for s in VIDEO_SECTIONS]
            assert all(p >= 0 for p in positions), (trial, positions, text)
            assert positions == sorted(positions), (trial, positions, text)
            # exactly the five contract headers, no invented ones
            assert len(re.findall(r"### ", text)) == 5, (trial, text)
            # no greeting/preamble: the reply starts at the first header
            assert text.lstrip().startswith("### TIMELINE SUMMARY"), \
                (trial, text)
            if all(f"[{format_timestamp(s)}]" in text for s, _ in items):
                stamps_ok += 1
    finally:
        engine.shutdown()
    assert stamps_ok >= 2, f"timestamp carry-through {stamps_ok}/3"
