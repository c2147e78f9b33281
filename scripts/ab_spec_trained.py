"""Speculation with REAL acceptance (VERDICT r3 item 6, the close-the-file
measurement): random-weight models cannot accept drafts (ab_spec.py measures
pure overhead, 0.5x), so this script TRAINS a ~370M byte-level model on chip
on an extractive agenda-copy task — the canonical prompt-lookup win case
(summaries quoting their source verbatim; ops/speculative.py module doc) —
then runs the k=0 vs k=4 ABBA on held-out prompts through the production
continuous-batching engine with the ragged multi-token verify kernel.

The model is sized so decode is WEIGHT-STREAM-bound (~280 MB bf16/step at
B=24: the (1+k)/(1+a*k) weight-amortization mechanism has something to
amortize), unlike the in-tree tiny quality model (RTT-bound; docs/PERF.md
round 3).  Run on the real chip: python scripts/ab_spec_trained.py
"""
import _pathfix  # noqa: F401  (repo-root import shim)
from lmrs_tpu.utils.platform import setup_compile_cache

setup_compile_cache()
import json
import tempfile
import time
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp
import optax

from lmrs_tpu.config import EngineConfig, ModelConfig
from lmrs_tpu.data.tokenizer import ByteTokenizer
from lmrs_tpu.engine.api import GenerationRequest
from lmrs_tpu.engine.jax_engine import JaxEngine
from lmrs_tpu.models.transformer import init_params
from lmrs_tpu.training.cli import batches, load_examples
from lmrs_tpu.training.train import make_train_step
from lmrs_tpu.utils.logging import setup_logging

WORDS = ["alpha", "beta", "gamma", "delta", "omega", "sigma", "theta",
         "kappa", "lambda", "zeta"]


def copy_example(rng) -> dict:
    """Agenda with unmemorizable content (random ids): the only way to low
    loss is COPYING from the prompt — which is exactly what prompt-lookup
    drafting can draft."""
    n = int(rng.integers(6, 10))
    # word-only content: unmemorizable combinations (10^3 per line) force
    # real copying, but avoid random DIGIT strings — measured: digit spans
    # resist induction far longer than word spans (2200 steps: words copy,
    # digits still garbled), and a wrong digit derails the whole line's
    # draft chain
    lines = [f"[{m:02d}:00] {WORDS[rng.integers(0, 10)]} "
             f"{WORDS[rng.integers(0, 10)]} {WORDS[rng.integers(0, 10)]}"
             for m in range(n)]
    agenda = "\n".join(lines)
    return {"prompt": f"Repeat the agenda.\n{agenda}\nAgenda:",
            "summary": "\n" + agenda}


def main():
    setup_logging(quiet=True)
    # f32 (bf16 training diverged to NaN at this lr on the first attempt);
    # ~370M params = 1.5 GB f32 weights -> the decode step is genuinely
    # weight-stream-bound at B=24 (floor ~1.8 ms vs ~2.5 ms launch cost)
    cfg = ModelConfig(name="spec-370m", vocab_size=512, dim=1280,
                      n_layers=14, n_heads=10, n_kv_heads=5,
                      hidden_dim=5120, max_seq_len=1024, dtype="float32")

    rng = np.random.default_rng(0)
    train = [copy_example(rng) for _ in range(1500)]
    with tempfile.TemporaryDirectory() as td:
        p = Path(td) / "copy.jsonl"
        p.write_text("\n".join(json.dumps(e) for e in train))
        seqs, masks = load_examples(str(p), ByteTokenizer())

    params = init_params(cfg, jax.random.PRNGKey(0))
    steps = 1200
    sched = optax.warmup_cosine_decay_schedule(0.0, 3e-4, 60, steps, 6e-6)
    optimizer = optax.chain(optax.clip_by_global_norm(1.0),
                            optax.adamw(sched))
    opt_state = optimizer.init(params)
    step_fn = make_train_step(cfg, optimizer, None, masked=True,
                              remat=True)  # 16 GB chip: f32 370M + adam needs it
    it = batches(seqs, masks, 4, 768, 0)
    t0 = time.time()
    for i in range(steps):
        t, m = next(it)
        params, opt_state, loss = step_fn(params, opt_state,
                                          jnp.asarray(t), jnp.asarray(m))
        if i % 100 == 0 or i == steps - 1:
            print(f"train step {i}: loss {float(loss):.4f} "
                  f"({time.time() - t0:.0f}s)", flush=True)
    if float(loss) > 0.1:
        print(f"WARNING: copy task not converged (loss {float(loss):.3f}); "
              "acceptance will undershoot")

    held = [copy_example(np.random.default_rng(10_000 + i)) for i in range(24)]

    def make_engine(k):
        return JaxEngine(
            EngineConfig(backend="jax", scheduler="continuous",
                         max_tokens=288, max_batch_slots=24, retry_delay=0.0,
                         seed=0, page_size=512, num_pages=1,
                         decode_block=120, prefill_chunk=4096,
                         speculate_k=k),
            cfg, params=params, tokenizer=ByteTokenizer())

    def wave(eng, tag):
        reqs = [GenerationRequest(prompt=ex["prompt"], request_id=i,
                                  temperature=0.0, max_new_tokens=288)
                for i, ex in enumerate(held)]
        t0 = time.time()
        out = eng.generate_batch(reqs)
        dt = time.time() - t0
        assert all(r.error is None for r in out)
        return dt, out

    engines = {0: make_engine(0), 4: make_engine(4)}
    outs = {}
    for k, e in engines.items():
        _, outs[k] = wave(e, f"warm{k}")  # compile + cache warm

    # copy fidelity: greedy output must actually BE the agenda (otherwise
    # acceptance is meaningless); exact-prefix tokens over the batch
    ok = sum(o.text.startswith(ex["summary"][:80])
             for ex, o in zip(held, outs[0]))
    print(f"copy fidelity: {ok}/24 rows reproduce the agenda prefix "
          f"(k=0 greedy)", flush=True)
    print("sample got :", repr(outs[0][0].text[:90]), flush=True)
    print("sample want:", repr(held[0]["summary"][:90]), flush=True)

    sums = {0: [], 4: []}
    for r in range(3):
        for k in (0, 4, 4, 0):
            dt, _ = wave(engines[k], f"{r}-{k}")
            sums[k].append(dt)
        print(f"round {r}: k=0 {np.mean(sums[0]):.2f}s  "
              f"k=4 {np.mean(sums[4]):.2f}s", flush=True)

    m0, m4 = np.mean(sums[0]), np.mean(sums[4])
    met = engines[4]._scheduler.metrics
    dec, acc = met["decode_tokens"], met["spec_accepted_tokens"]
    disp = met["decode_dispatches"]
    # verify steps = tokens / (1 + accepted-per-step); per-step acceptance
    a_hat = acc / max(dec - acc, 1)  # accepted drafts per verify step
    print(f"k=4 engine: {dec} tokens, {acc} accepted draft tokens, "
          f"{disp} dispatches -> mean accepted/verify-step = {a_hat:.2f}")
    pred = (1 + a_hat) / 1.09  # 1.09x = measured verify-kernel cost ratio
    print(f"speedup: measured {m0 / m4:.2f}x  "
          f"(weight-stream prediction (1+a)/1.09 = {pred:.2f}x)")
    verdict = ("WINS >= 1.2x — flip default ON for extractive workloads"
               if m0 / m4 >= 1.2 else "stays OFF")
    print(f"VERDICT: speculation {verdict}", flush=True)


if __name__ == "__main__":
    main()
