"""Offline quality gate (VERDICT r1 item 4): the stack must demonstrably
SUMMARIZE, not just stream tokens.

Two layers, both scored with the in-tree ROUGE harness against stored /
ground-truth baselines:

1. ``test_parity_vs_committed_baseline`` — the full pipeline on the real
   7.4 h example transcript scored against the committed curated baseline
   (examples/baseline_summary.json).  The mock engine is extractive, so
   the absolute score is modest; the gate is a calibrated regression
   tripwire (measured 0.042 ROUGE-L / 0.084 ROUGE-1 on 2026-07-30 — a
   format or content collapse drops it to ~0).
2. ``test_trained_model_beats_extractive_baseline`` — the REAL gate: a
   model is fine-tuned through the production training stack on synthetic
   transcript→summary pairs (eval/synthetic.py), held-out prompts are
   decoded through the production continuous-batching engine, and the
   mean ROUGE-L against ground truth must clear a non-trivial threshold
   AND beat the trivial lead-1 extractive baseline by a wide margin.
   Calibration (2026-07-30, CPU, fixed seeds): model 0.396, extractive
   0.048 — gates set at 0.30 and 3x.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

BASELINE_FIXTURE = (Path(__file__).parent.parent / "examples"
                    / "baseline_summary.json")


def test_parity_vs_committed_baseline(example_transcript):
    from lmrs_tpu.config import EngineConfig, PipelineConfig
    from lmrs_tpu.eval.parity import load_baseline, run_parity

    baseline = load_baseline(BASELINE_FIXTURE)
    assert len(baseline.split()) > 150  # a real summary, not a stub
    cfg = PipelineConfig(engine=EngineConfig(backend="mock"))
    report = run_parity(example_transcript, baseline, cfg, threshold=0.02)
    assert report.passed, report.to_dict()
    assert report.rouge1_f >= 0.04, report.to_dict()


@pytest.fixture(scope="module")
def trained_summarizer():
    """Fine-tune the tiny byte-level model on synthetic pairs through the
    production path: JSONL -> training.cli.load_examples (loss masked to
    the summary) -> make_train_step."""
    import jax
    import jax.numpy as jnp
    import optax

    from lmrs_tpu.config import ModelConfig
    from lmrs_tpu.data.tokenizer import ByteTokenizer
    from lmrs_tpu.eval.synthetic import make_dataset
    from lmrs_tpu.models.transformer import init_params
    from lmrs_tpu.training.cli import batches, load_examples
    from lmrs_tpu.training.train import make_train_step

    cfg = ModelConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, hidden_dim=128, max_seq_len=512,
                      dtype="float32")
    tok = ByteTokenizer()
    import tempfile
    from pathlib import Path

    with tempfile.TemporaryDirectory() as td:
        data_path = Path(td) / "train.jsonl"
        data_path.write_text("\n".join(
            json.dumps({"prompt": ex["prompt"], "summary": ex["summary"]})
            for ex in make_dataset(192, seed=0)))
        seqs, masks = load_examples(str(data_path), tok)

    params = init_params(cfg, jax.random.PRNGKey(0))
    optimizer = optax.adamw(4e-3)
    opt_state = optimizer.init(params)
    step_fn = make_train_step(cfg, optimizer, None, masked=True)
    it = batches(seqs, masks, 16, 320, 0)
    loss = None
    for _ in range(200):
        t, m = next(it)
        params, opt_state, loss = step_fn(params, opt_state,
                                          jnp.asarray(t), jnp.asarray(m))
    assert float(loss) < 0.5, f"training failed to converge: loss {float(loss)}"
    return cfg, tok, params


def test_trained_model_beats_extractive_baseline(trained_summarizer):
    from lmrs_tpu.config import EngineConfig
    from lmrs_tpu.engine.api import GenerationRequest
    from lmrs_tpu.engine.jax_engine import JaxEngine
    from lmrs_tpu.eval.rouge import rouge_l
    from lmrs_tpu.eval.synthetic import extractive_baseline, make_dataset

    cfg, tok, params = trained_summarizer
    engine = JaxEngine(
        EngineConfig(backend="jax", scheduler="continuous", max_tokens=48,
                     max_batch_slots=4, seed=0, decode_block=8),
        cfg, params=params, tokenizer=tok)
    # Seed-disjoint is not prompt-disjoint (ADVICE r2): with 12 topics and
    # 2-3 draws per example, a held-out prompt can collide verbatim with a
    # training prompt.  Filter exact-prompt overlap so the gate measures
    # generalization, drawing extra candidates to keep the set at 8.
    train_prompts = {ex["prompt"] for ex in make_dataset(192, seed=0)}
    held = [ex for ex in make_dataset(32, seed=999)
            if ex["prompt"] not in train_prompts][:8]
    assert len(held) == 8, "synthetic generator collided on all candidates"
    reqs = [GenerationRequest(prompt=ex["prompt"], request_id=i,
                              temperature=0.0, max_new_tokens=48)
            for i, ex in enumerate(held)]
    outs = engine.generate_batch(reqs)
    engine.shutdown()

    model_f = [rouge_l(o.text, ex["summary"])["f"]
               for ex, o in zip(held, outs)]
    extract_f = [rouge_l(extractive_baseline(ex["prompt"]), ex["summary"])["f"]
                 for ex in held]
    mean_model = float(np.mean(model_f))
    mean_extract = float(np.mean(extract_f))
    # non-trivial absolute gate + wide margin over the trivial baseline
    assert mean_model >= 0.30, (mean_model, model_f)
    assert mean_model > 3 * mean_extract, (mean_model, mean_extract)


def test_trained_model_quality_survives_kv_int8(trained_summarizer):
    """The REAL numerics gate for kv_quantize=int8 (per-slot/head/channel
    scales, ops/quant.py KV section): the fine-tuned model decoded through
    int8 KV pages must keep its learned-summarization quality, not merely
    not crash.  A scale-wiring bug (wrong rows, wrong channel axis) floors
    ROUGE-L to extractive-baseline territory instantly."""
    from lmrs_tpu.config import EngineConfig
    from lmrs_tpu.engine.api import GenerationRequest
    from lmrs_tpu.engine.jax_engine import JaxEngine
    from lmrs_tpu.eval.rouge import rouge_l
    from lmrs_tpu.eval.synthetic import make_dataset

    cfg, tok, params = trained_summarizer
    engine = JaxEngine(
        EngineConfig(backend="jax", scheduler="continuous", max_tokens=48,
                     max_batch_slots=4, seed=0, decode_block=8,
                     page_size=32, kv_quantize="int8"),
        cfg, params=params, tokenizer=tok)
    train_prompts = {ex["prompt"] for ex in make_dataset(192, seed=0)}
    held = [ex for ex in make_dataset(32, seed=999)
            if ex["prompt"] not in train_prompts][:8]
    reqs = [GenerationRequest(prompt=ex["prompt"], request_id=i,
                              temperature=0.0, max_new_tokens=48)
            for i, ex in enumerate(held)]
    outs = engine.generate_batch(reqs)
    engine.shutdown()
    model_f = [rouge_l(o.text, ex["summary"])["f"]
               for ex, o in zip(held, outs)]
    mean_model = float(np.mean(model_f))
    # same absolute gate as the full-precision test: int8 KV must not cost
    # the learned behavior (small per-example wobble is expected)
    assert mean_model >= 0.28, (mean_model, model_f)


# ---------------------------------------------------- CLI end-to-end gate


MAP_TEMPLATE = "List the topics.\n{transcript}\nTopics:"
REDUCE_TEMPLATE = "List the topics.\n{summaries}\nTopics:"
CLI_CHUNK_TOKENS = 384  # forces multi-chunk map on the held-out transcript


def _make_cli_transcript(rng):
    """A transcript in the CLI input schema (reference README.md:162-175)
    whose ground-truth summary is its topic list in order of appearance."""
    from lmrs_tpu.eval.synthetic import _FILLER, _OPENERS, TOPICS

    n_topics = int(rng.integers(3, 6))
    topics = [TOPICS[i] for i in rng.choice(len(TOPICS), n_topics,
                                            replace=False)]
    segs, t = [], 0.0
    for topic in topics:
        if rng.random() < 0.6:
            segs.append({"start": t, "end": t + 4.0, "speaker": "SPEAKER_00",
                         "text": str(rng.choice(_FILLER))})
            t += float(rng.integers(20, 50))
        opener = str(rng.choice(_OPENERS)).format(t=topic)
        segs.append({"start": t, "end": t + 4.0, "speaker": "SPEAKER_00",
                     "text": opener + "."})
        t += float(rng.integers(20, 50))
    return {"segments": segs}, topics


def _product_format_pairs(transcript, topics):
    """(prompt, summary) pairs in the EXACT formats the CLI will produce:
    map prompts through the real preprocessor + chunker (context header
    included), the reduce prompt through the real aggregator formatter."""
    from types import SimpleNamespace

    from lmrs_tpu.config import EngineConfig
    from lmrs_tpu.data.chunker import TranscriptChunker
    from lmrs_tpu.data.preprocessor import format_timestamp, preprocess_transcript
    from lmrs_tpu.data.tokenizer import ByteTokenizer
    from lmrs_tpu.prompts import safe_format
    from lmrs_tpu.reduce.aggregator import ResultAggregator

    chunker = TranscriptChunker(max_tokens_per_chunk=CLI_CHUNK_TOKENS,
                                overlap_tokens=0, context_tokens=150,
                                tokenizer=ByteTokenizer())
    agg = ResultAggregator(SimpleNamespace(config=EngineConfig()),
                           tokenizer=ByteTokenizer())
    processed = preprocess_transcript(transcript["segments"])
    chunks = chunker.chunk_transcript(processed)
    pairs, tagged = [], []
    for c in chunks:
        in_chunk = sorted((t for t in topics if t in c.text),
                          key=c.text.find)
        target = " " + ", ".join(in_chunk) + "." if in_chunk else " none."
        pairs.append({
            "prompt": safe_format(MAP_TEMPLATE,
                                  transcript=c.text_with_context),
            "summary": target,
        })
        tagged.append(
            f"[Time: {format_timestamp(c.start_time)} - "
            f"{format_timestamp(c.end_time)}]\n{target}")
    red = agg._build_request(tagged, REDUCE_TEMPLATE, metadata=None)
    pairs.append({"prompt": red.prompt,
                  "summary": " " + ", ".join(topics) + "."})
    return pairs


@pytest.fixture(scope="module")
def cli_checkpoint(tmp_path_factory):
    """Fine-tune quality-tiny on product-formatted pairs through the
    production training stack, save through the production Orbax path."""
    import jax
    import jax.numpy as jnp
    import optax

    from lmrs_tpu.config import model_preset
    from lmrs_tpu.data.tokenizer import ByteTokenizer
    from lmrs_tpu.models.loader import save_checkpoint
    from lmrs_tpu.models.transformer import init_params
    from lmrs_tpu.training.cli import batches, load_examples
    from lmrs_tpu.training.train import make_train_step

    cfg = model_preset("quality-tiny")
    rng = np.random.default_rng(0)
    pairs = []
    for _ in range(1000):
        transcript, topics = _make_cli_transcript(rng)
        pairs.extend(_product_format_pairs(transcript, topics))

    import tempfile
    from pathlib import Path as P

    with tempfile.TemporaryDirectory() as td:
        data_path = P(td) / "train.jsonl"
        data_path.write_text("\n".join(json.dumps(p) for p in pairs))
        seqs, masks = load_examples(str(data_path), ByteTokenizer())

    params = init_params(cfg, jax.random.PRNGKey(0))
    # warmup-cosine matters here: constant-lr runs oscillate and plateau at
    # held-out map ROUGE-L ~0.6 (calibration 2026-07-31); with decay the
    # same budget reaches ~0.94 map / 1.0 reduce (teacher-forced)
    steps = 1500
    sched = optax.warmup_cosine_decay_schedule(0.0, 3e-3, 100, steps,
                                               3e-3 * 0.02)
    optimizer = optax.adamw(sched)
    opt_state = optimizer.init(params)
    step_fn = make_train_step(cfg, optimizer, None, masked=True)
    it = batches(seqs, masks, 8, 704, 0)
    loss = None
    for _ in range(steps):
        t, m = next(it)
        params, opt_state, loss = step_fn(params, opt_state,
                                          jnp.asarray(t), jnp.asarray(m))
    assert float(loss) < 0.25, f"CLI-format training failed: loss {float(loss)}"
    ckpt = tmp_path_factory.mktemp("cli_ckpt") / "quality-tiny"
    save_checkpoint(str(ckpt), params)
    return str(ckpt)


@pytest.mark.parametrize("quant_args", [
    pytest.param([], id="fp"),
    pytest.param(["--quantize", "int8"], id="w8"),
    pytest.param(["--kv-quantize", "int8"], id="kv8"),
])
def test_cli_end_to_end_quality_gate(cli_checkpoint, tmp_path, monkeypatch,
                                     quant_args):
    """The PRODUCT surface, quality-gated (VERDICT r3 item 7): `lmrs`
    CLI -> preprocess -> chunk -> continuous-batching map -> reduce, with
    a trained checkpoint loaded via --checkpoint, scored against the
    held-out transcript's ground-truth topic summary.  Calibration
    (2026-07-31, CPU, fixed seeds): model 0.889 ROUGE-L end-to-end,
    extractive baseline 0.0 — gate at 0.45 is a format-or-content
    collapse tripwire, not a near-miss trap.

    Parametrized over the quantization flags (VERDICT r4 item 3): int8
    weights and int8 KV must keep LEARNED quality through the full CLI,
    not merely be throughput-measured on random weights."""
    from lmrs_tpu import cli
    from lmrs_tpu.eval.rouge import rouge_l

    monkeypatch.setenv("TEMPERATURE", "0.0")  # greedy map (env-config path)
    # generation budget via the reference's env knob (MAX_TOKENS,
    # SURVEY.md §5.6): the default 1000 would push the scheduler's prompt
    # truncation limit below the ~460-byte product prompts at this window
    monkeypatch.setenv("MAX_TOKENS", "96")
    held, topics = _make_cli_transcript(np.random.default_rng(4242))
    truth = " " + ", ".join(topics) + "."

    inp = tmp_path / "transcript.json"
    inp.write_text(json.dumps(held))
    out = tmp_path / "summary.txt"
    mapf = tmp_path / "map_prompt.txt"
    mapf.write_text(MAP_TEMPLATE)
    redf = tmp_path / "reduce_prompt.txt"
    redf.write_text(REDUCE_TEMPLATE)

    rc = cli.main([
        "--input", str(inp), "--output", str(out),
        "--backend", "jax", "--model", "quality-tiny",
        "--checkpoint", cli_checkpoint, "--tokenizer", "byte",
        "--max-tokens-per-chunk", str(CLI_CHUNK_TOKENS),
        "--overlap-tokens", "0",
        "--prompt-file", str(mapf),
        "--aggregator-prompt-file", str(redf),
        "--report", "--quiet",
        *quant_args,
    ])
    assert rc == 0
    text = out.read_text()
    score = rouge_l(text, truth)["f"]
    assert score >= 0.45, (score, text, truth)
    report = json.loads((tmp_path / "summary.txt.report.json").read_text())
    assert report["num_chunks"] >= 2, "held-out transcript must multi-chunk"
    assert report["failed_requests"] == 0
