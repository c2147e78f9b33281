"""``lmrs-train``: fine-tune a model preset on text/summary data.

The reference has no training at all (its model is behind OpenAI's API);
this is new serving-stack surface: fine-tune the on-pod summarizer on
(transcript chunk, summary) pairs or raw text, with the same mesh axes as
serving (dp/tp/sp) and the remat/checkpoint machinery from
training/train.py + models/loader.py.

Data format: JSONL, one object per line —
    {"text": "..."}                       plain causal-LM text
    {"prompt": "...", "summary": "..."}   loss masked to the summary tokens
"""

from __future__ import annotations

import argparse
import json
import logging
import sys
import time
from pathlib import Path

import numpy as np

logger = logging.getLogger("lmrs.train")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        "lmrs-train", description="Fine-tune a summarization model on TPU")
    p.add_argument("--data", required=True, help="JSONL training data")
    p.add_argument("--model", default="tiny", help="model preset name")
    p.add_argument("--tokenizer", default="byte",
                   help='"byte", "approx", SentencePiece path, or HF id')
    p.add_argument("--init-checkpoint", default=None,
                   help="Orbax checkpoint to start from (default: random init)")
    p.add_argument("--output", required=True, help="Orbax checkpoint output dir")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--mesh", default=None,
                   help="device mesh axes dp,tp[,sp] e.g. 2,4 or 1,4,2")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize layers in backward (long sequences)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--quiet", "-q", action="store_true")
    return p


def load_examples(path: str, tokenizer) -> tuple[list[list[int]], list[list[int]]]:
    """Tokenize the JSONL file; returns (token_seqs, loss_masks)."""
    seqs, masks = [], []
    for lineno, line in enumerate(
        Path(path).read_text(encoding="utf-8").splitlines(), 1
    ):
        line = line.strip()
        if not line:
            continue
        row = json.loads(line)
        if "text" in row:
            ids = [tokenizer.bos_id] + tokenizer.encode(row["text"])
            mask = [1] * len(ids)
        elif "prompt" in row and "summary" in row:
            p_ids = [tokenizer.bos_id] + tokenizer.encode(row["prompt"])
            s_ids = tokenizer.encode(row["summary"]) + [tokenizer.eos_id]
            ids = p_ids + s_ids
            mask = [0] * len(p_ids) + [1] * len(s_ids)
        else:
            raise ValueError(
                f"{path}:{lineno}: row needs 'text' or 'prompt'+'summary' "
                f"keys, got {sorted(row)}")
        seqs.append(ids)
        masks.append(mask)
    if not seqs:
        raise ValueError(f"no examples in {path}")
    return seqs, masks


def batches(seqs, masks, batch_size: int, seq_len: int, seed: int):
    """Yield (tokens [B,S], loss_mask [B,S]) forever, shuffled per epoch;
    the tail batch fills up by cycling the epoch's permutation."""
    rng = np.random.default_rng(seed)
    n = len(seqs)
    while True:
        order = rng.permutation(n)
        for i in range(0, n, batch_size):
            idx = order[i : i + batch_size]
            if len(idx) < batch_size:  # tail: top up by cycling the epoch
                idx = np.concatenate(
                    [idx, np.resize(order, batch_size - len(idx))])
            t = np.zeros((batch_size, seq_len), np.int32)
            m = np.zeros((batch_size, seq_len), np.int32)
            for r, j in enumerate(idx):
                ids = seqs[j][:seq_len]
                t[r, : len(ids)] = ids
                m[r, : len(ids)] = masks[j][: len(ids)]
            yield t, m


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from lmrs_tpu.utils.logging import setup_logging
    from lmrs_tpu.utils.platform import setup_compile_cache

    setup_logging(quiet=args.quiet)
    setup_compile_cache()

    import jax
    import jax.numpy as jnp
    import optax

    from lmrs_tpu.config import model_preset
    from lmrs_tpu.data.tokenizer import get_tokenizer
    from lmrs_tpu.models.loader import load_checkpoint, save_checkpoint
    from lmrs_tpu.models.transformer import init_params
    from lmrs_tpu.training.train import make_train_step

    try:
        cfg = model_preset(args.model)
        tokenizer = get_tokenizer(args.tokenizer)
        seqs, masks = load_examples(args.data, tokenizer)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        logger.error("could not set up training: %s", e)
        return 1
    max_id = max(max(s) for s in seqs)
    if max_id >= cfg.vocab_size:
        # silently clamping would corrupt both inputs and loss targets
        logger.error(
            "tokenizer produced id %d but model %s has vocab_size %d — "
            "pick a tokenizer matching the model's vocabulary",
            max_id, cfg.name, cfg.vocab_size)
        return 1
    logger.info("loaded %d examples from %s", len(seqs), args.data)

    mesh = None
    mesh_cfg = None
    if args.mesh:
        from lmrs_tpu.config import parse_mesh
        from lmrs_tpu.parallel.mesh import build_mesh

        try:
            mesh_cfg = parse_mesh(args.mesh)
        except ValueError as e:
            logger.error("bad --mesh: %s", e)
            return 1
        mesh = build_mesh(mesh_cfg)
        logger.info("mesh: dp=%d tp=%d sp=%d pp=%d", mesh_cfg.dp,
                    mesh_cfg.tp, mesh_cfg.sp, mesh_cfg.pp)

    if args.init_checkpoint:
        params = load_checkpoint(args.init_checkpoint, cfg, mesh=mesh)
    else:
        params = init_params(cfg, jax.random.PRNGKey(args.seed))
        if mesh is not None:
            from lmrs_tpu.parallel.sharding import shard_params

            params = shard_params(params, mesh, cfg.tie_embeddings,
                                  moe=cfg.n_experts > 0,
                                  sandwich_norm=cfg.sandwich_norm)
    optimizer = optax.adamw(args.lr)
    opt_state = optimizer.init(params)
    step_fn = make_train_step(cfg, optimizer, mesh,
                              seq_sharded=bool(mesh_cfg and mesh_cfg.sp > 1),
                              remat=args.remat, masked=True)

    it = batches(seqs, masks, args.batch_size, args.seq_len, args.seed)
    t0 = time.time()
    for step in range(1, args.steps + 1):
        tokens, mask = next(it)
        params, opt_state, loss = step_fn(params, opt_state,
                                          jnp.asarray(tokens), jnp.asarray(mask))
        if step % args.log_every == 0 or step == args.steps:
            tok_s = step * args.batch_size * args.seq_len / (time.time() - t0)
            logger.info("step %d/%d  loss %.4f  %.0f tok/s",
                        step, args.steps, float(loss), tok_s)

    save_checkpoint(args.output, params)
    logger.info("saved fine-tuned checkpoint to %s", args.output)
    return 0


if __name__ == "__main__":
    sys.exit(main())
