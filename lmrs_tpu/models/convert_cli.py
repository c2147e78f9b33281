"""`lmrs-convert`: HuggingFace checkpoint → native Orbax, one command.

The missing entry point between "a user downloaded Llama-3/Gemma/Mixtral
safetensors" (the models behind the reference's API, llm_executor.py:
250-326) and this framework's serving/training stack: the converters in
``models/loader.py`` were library-only.

    lmrs-convert --src /path/to/hf-llama3-8b --model llama3-8b \
                 --output ckpt/llama3-8b
    lmrs-serve --backend jax --model llama3-8b --checkpoint ckpt/llama3-8b \
               --tokenizer /path/to/hf-llama3-8b

Family is inferred from the preset (gemma presets → the Gemma converter,
which handles tied embeddings / (1+w) norms / GeGLU; everything else takes
the Llama/Mixtral path), overridable with ``--family``.
"""

from __future__ import annotations

import argparse
import logging
import sys

logger = logging.getLogger("lmrs.convert")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="lmrs-convert",
        description="Convert a local HF safetensors checkpoint to the "
                    "native Orbax layout")
    p.add_argument("--src", required=True,
                   help="directory with HF *.safetensors shards")
    p.add_argument("--model", required=True,
                   help="model preset the checkpoint matches "
                        "(e.g. llama3-8b, gemma-2b, mixtral-8x7b)")
    p.add_argument("--output", required=True, help="Orbax checkpoint dir")
    p.add_argument("--family", choices=["llama", "gemma"], default=None,
                   help="converter family (default: inferred from preset)")
    p.add_argument("--quiet", "-q", action="store_true")
    return p


def main(argv: list[str] | None = None) -> int:
    from lmrs_tpu.utils.logging import setup_logging

    args = build_parser().parse_args(argv)
    setup_logging(quiet=args.quiet)
    from lmrs_tpu.config import model_preset
    from lmrs_tpu.models.loader import (
        convert_hf_gemma, convert_hf_llama, save_checkpoint,
    )
    from lmrs_tpu.models.transformer import param_count

    try:
        cfg = model_preset(args.model)
    except (KeyError, ValueError) as e:
        logger.error("unknown model preset %r: %s", args.model, e)
        return 1
    family = args.family or ("gemma" if "gemma" in cfg.name.lower()
                             or cfg.activation == "gelu" else "llama")
    convert = convert_hf_gemma if family == "gemma" else convert_hf_llama
    try:
        params = convert(args.src, cfg)
    except (FileNotFoundError, KeyError, ValueError) as e:
        logger.error("conversion failed: %s", e)
        return 1
    save_checkpoint(args.output, params)
    logger.info(
        "converted %s (%s family, %.1fM params) -> %s\n"
        "serve with:  lmrs-serve --backend jax --model %s --checkpoint %s",
        args.src, family, param_count(params) / 1e6, args.output,
        args.model, args.output)
    return 0


if __name__ == "__main__":
    sys.exit(main())
