"""``lmrs-serve``: stand up the OpenAI/Anthropic-compatible server.

Inverts the reference's deployment: instead of the summarizer calling out to
``api.openai.com`` (llm_executor.py:292), any OpenAI/Anthropic-format client
calls in to the TPU pod.

    lmrs-serve --backend mock --port 8000
    lmrs-serve --backend jax --model gemma-2b --mesh 2,4 --port 8000
"""

from __future__ import annotations

import argparse
import logging

from lmrs_tpu.config import EngineConfig, parse_mesh
from lmrs_tpu.engine.api import make_engine
from lmrs_tpu.utils.env import env_bool
from lmrs_tpu.utils.logging import setup_logging

logger = logging.getLogger("lmrs.serving")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="lmrs-serve",
        description="OpenAI/Anthropic-wire-compatible HTTP server over the "
                    "in-tree TPU engine",
        # no prefix abbreviation: --supervise re-execs this CLI with the
        # flag stripped by EXACT match — an abbreviated "--supervis"
        # would survive the strip and fork supervisors recursively
        allow_abbrev=False,
    )
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--backend", default="mock", choices=["mock", "jax"])
    p.add_argument("--model", default="tiny", help="model preset name")
    p.add_argument("--mesh", default=None,
                   help="device mesh axes as dp,tp[,sp[,pp]], e.g. 2,4")
    p.add_argument("--checkpoint", default=None, help="Orbax checkpoint dir")
    p.add_argument("--tokenizer", default=None,
                   help="serving tokenizer: 'byte', a *.model SentencePiece "
                        "path, or an HF tokenizer dir (the checkpoint's own "
                        "vocabulary; default: model-derived)")
    p.add_argument("--quantize", default=None, choices=["int8"])
    p.add_argument("--kv-quantize", default=None, choices=["int8"])
    p.add_argument("--batch-slots", type=int, default=8,
                   help="continuous-batching decode slots")
    p.add_argument("--max-tokens-cap", type=int, default=4096,
                   help="upper bound on any request's max_tokens")
    p.add_argument("--batch-window-ms", type=float, default=20.0,
                   help="micro-batching window for pooling concurrent requests")
    p.add_argument("--role", default="both",
                   choices=["prefill", "decode", "both"],
                   help="disaggregated serving role: 'prefill' stops "
                        "handoff-flagged requests after the first token "
                        "and publishes a KV-page ticket, 'decode' imports "
                        "tickets and continues, 'both' (default) serves "
                        "colocated (docs/SERVING.md)")
    p.add_argument("--handoff-ttl", type=float, default=None,
                   help="seconds an un-acked handoff ticket pins its KV "
                        "pages before the orphan sweep reclaims them "
                        "(default: LMRS_HANDOFF_TTL or 60)")
    p.add_argument("--jobs-dir", default=None,
                   help="enable the durable async job API (POST/GET/DELETE "
                        "/v1/jobs): write-ahead journals live here and "
                        "interrupted jobs resume on startup (default: "
                        "LMRS_JOBS_DIR; unset disables — 501)")
    p.add_argument("--live-dir", default=None,
                   help="enable the live-session API (POST/GET/DELETE "
                        "/v1/sessions*): growing transcripts summarized "
                        "incrementally, journaled here and rehydrated on "
                        "startup (default: LMRS_LIVE_DIR; unset disables "
                        "— 501)")
    p.add_argument("--supervise", action="store_true",
                   help="run the server in a supervised CHILD process: "
                        "the parent polls /healthz and SIGKILL-respawns "
                        "the child on a watchdog-declared wedge, a hang, "
                        "or a crash; jobs/sessions resume from their "
                        "journals across the bounce (docs/ROBUSTNESS.md "
                        "§ Supervised restart)")
    p.add_argument("--trace", action="store_true",
                   help="enable the in-process lifecycle tracer; GET "
                        "/v1/trace then serves this host's span ring "
                        "(Chrome-trace JSON) for the router-side fleet "
                        "stitcher (also: LMRS_TRACE=1)")
    p.add_argument("--quiet", "-q", action="store_true")
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    setup_logging(quiet=args.quiet)
    if args.supervise:
        # parent mode: never builds an engine — it spawns this same CLI
        # (minus --supervise) as a child and owns only its lifecycle
        import sys as _sys

        from lmrs_tpu.serving.supervisor import Supervisor

        raw = list(argv) if argv is not None else _sys.argv[1:]
        child_argv = [a for a in raw if a != "--supervise"]
        return Supervisor(child_argv, host=args.host, port=args.port).run()
    from lmrs_tpu.utils.platform import setup_compile_cache

    setup_compile_cache()
    if args.trace or env_bool("LMRS_TRACE", False):
        # before the engine builds: the scheduler captures the tracer per
        # run, and serving spans must cover the first request
        from lmrs_tpu.obs import enable_tracing

        enable_tracing()
    engine_cfg = EngineConfig(
        backend=args.backend,
        model=args.model,
        max_batch_slots=args.batch_slots,
        checkpoint_path=args.checkpoint,
        tokenizer=args.tokenizer or "",
        quantize=args.quantize,
        kv_quantize=args.kv_quantize,
        max_tokens=args.max_tokens_cap,
        # explicit flag wins over LMRS_HANDOFF_TTL; validated by the
        # config's __post_init__ (a non-positive TTL would disable the
        # orphan-sweep backstop)
        **({"handoff_ttl_s": args.handoff_ttl}
           if args.handoff_ttl is not None else {}),
    )
    mesh_cfg = parse_mesh(args.mesh) if args.mesh else None
    try:
        engine = make_engine(engine_cfg, mesh_cfg=mesh_cfg)
    except ValueError as e:
        logger.error("engine init failed: %s", e)
        return 1

    from lmrs_tpu.serving.server import EngineHTTPServer

    try:
        from lmrs_tpu.config import PipelineConfig

        server = EngineHTTPServer(
            engine, host=args.host, port=args.port, model_name=args.model,
            max_tokens_cap=args.max_tokens_cap,
            batch_window_s=args.batch_window_ms / 1000.0,
            role=args.role, handoff_ttl_s=engine_cfg.handoff_ttl_s,
            jobs_dir=args.jobs_dir,
            live_dir=args.live_dir,
            # the job/session fingerprints must reflect the SERVED
            # model/config, not PipelineConfig defaults
            pipeline_config=PipelineConfig(engine=engine_cfg),
        )
    except OSError as e:
        logger.error("cannot bind %s:%d: %s", args.host, args.port, e)
        engine.shutdown()
        return 1
    # SIGTERM (the supervisor's and any launcher's graceful stop) takes
    # the same path as Ctrl-C: drain the server, release the device
    import signal

    def _on_sigterm(_signum, _frame):
        raise KeyboardInterrupt

    try:
        signal.signal(signal.SIGTERM, _on_sigterm)
    except ValueError:
        pass  # not the main thread (tests drive main() from a thread)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        logger.info("shutting down")
    finally:
        server.shutdown()
        engine.shutdown()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
