"""Command-line interface.

Flag surface keeps parity with the reference CLI (main.py:406-474) — same
spellings where they exist (``--input/-i``, ``--output/-o``, ``--model``,
``--max-tokens-per-chunk``, ``--max-concurrent-requests``,
``--max-segment-duration``, ``--no-merge``, ``--no-hierarchical``,
``--limit-segments``, ``--report``, ``--prompt-file``,
``--system-prompt-file``, ``--save-chunks``, ``--aggregator-prompt-file``,
``--quiet/-q``) — plus TPU-era additions (``--backend``, ``--tokenizer``,
``--mesh``, ``--resume-from``, ``--profile``, ``--time-interval``).
``--provider`` is accepted as a deprecated alias of ``--backend``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import sys
from pathlib import Path

from lmrs_tpu.config import (
    ChunkConfig,
    DataConfig,
    EngineConfig,
    MeshConfig,
    PipelineConfig,
    ReduceConfig,
    parse_mesh,
)
from lmrs_tpu.pipeline import TranscriptSummarizer
from lmrs_tpu.utils.logging import setup_logging
from lmrs_tpu.utils.timing import format_duration

logger = logging.getLogger("lmrs.cli")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="lmrs",
        description="TPU-native map-reduce summarization of long transcripts",
    )
    p.add_argument("--input", "-i", required=True, help="input transcript JSON")
    p.add_argument("--output", "-o", help="write final summary to this file")
    p.add_argument("--backend", "--provider", dest="backend", default=None,
                   help="engine backend: mock | jax | http (default: env/config)")
    p.add_argument("--hosts", default=None,
                   help="backend=http: comma-separated lmrs-serve addresses "
                        "(host:port,...) the map/reduce waves fan over")
    p.add_argument("--model", default=None, help="model preset or checkpoint name")
    p.add_argument("--checkpoint", default=None,
                   help="Orbax checkpoint directory with the model weights "
                        "(lmrs-train output or lmrs-convert from HF)")
    p.add_argument("--max-tokens-per-chunk", type=int, default=4000)
    p.add_argument("--overlap-tokens", type=int, default=200)
    p.add_argument("--max-concurrent-requests", type=int, default=None)
    p.add_argument("--max-segment-duration", type=float, default=120.0)
    p.add_argument("--time-interval", type=float, default=None,
                   help="re-bucket segments into fixed intervals (seconds)")
    p.add_argument("--no-merge", action="store_true", help="skip same-speaker merging")
    p.add_argument("--no-hierarchical", action="store_true", help="single-pass reduce only")
    p.add_argument("--stream-reduce", action="store_true",
                   help="feed reduce batches into the map stage's engine "
                        "stream as summaries complete (best for long-decode "
                        "workloads; see ReduceConfig.streaming)")
    p.add_argument("--limit-segments", type=int, default=None)
    p.add_argument("--report", action="store_true", help="write <output>.report.json stats")
    p.add_argument("--prompt-file", help="map prompt file ({transcript} placeholder)")
    p.add_argument("--system-prompt-file", help="system prompt file")
    p.add_argument("--aggregator-prompt-file", help="reduce prompt file ({summaries})")
    p.add_argument("--save-chunks", help="dump per-chunk summaries JSON after map stage")
    p.add_argument("--resume-from", help="reuse summaries from a prior --save-chunks dump")
    p.add_argument("--summary-type", default="summary")
    p.add_argument("--tokenizer", default="approx",
                   help='token-count authority: "approx", "byte", sp model path, HF id')
    p.add_argument("--mesh", default=None,
                   help="device mesh axes as dp,tp[,sp[,pp]] e.g. 2,4 or 1,4,2,1")
    p.add_argument("--profile", action="store_true", help="emit jax.profiler spans")
    p.add_argument("--quantize", default=None, choices=["int8"],
                   help="weight-only quantization for the jax backend")
    p.add_argument("--kv-quantize", default=None, choices=["int8"],
                   help="int8 KV-cache pages (halves decode KV bytes, "
                        "doubles tokens per HBM GiB; page_size %% 32 == 0)")
    p.add_argument("--speculate-k", type=int, default=None,
                   help="prompt-lookup speculative decoding draft length "
                        "(0 = off; output distribution is unchanged)")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable shared-prefix KV reuse (the map/reduce "
                        "preamble normally prefills once and is shared "
                        "read-only across requests; greedy output is "
                        "identical either way)")
    p.add_argument("--trace-out", default=None, metavar="FILE",
                   help="record per-request lifecycle spans and write a "
                        "Chrome-trace JSON loadable in Perfetto "
                        "(docs/OBSERVABILITY.md)")
    p.add_argument("--no-trace", action="store_true",
                   help="force tracing off even if --trace-out is given "
                        "(overhead A/B control)")
    p.add_argument("--quiet", "-q", action="store_true")
    return p


def config_from_args(args: argparse.Namespace) -> PipelineConfig:
    mesh = parse_mesh(args.mesh) if args.mesh else MeshConfig()
    engine = EngineConfig()
    if args.backend:
        engine = dataclasses.replace(engine, backend=args.backend)
    if args.model:
        engine = dataclasses.replace(engine, model=args.model)
    if args.max_concurrent_requests is not None:
        engine = dataclasses.replace(engine, max_concurrent_requests=args.max_concurrent_requests)
    if args.hosts:
        engine = dataclasses.replace(
            engine,
            hosts=tuple(h.strip() for h in args.hosts.split(",") if h.strip()))
    if args.checkpoint:
        engine = dataclasses.replace(engine, checkpoint_path=args.checkpoint)
    if args.quantize:
        engine = dataclasses.replace(engine, quantize=args.quantize)
    if args.kv_quantize:
        engine = dataclasses.replace(engine, kv_quantize=args.kv_quantize)
    if args.speculate_k is not None:
        engine = dataclasses.replace(engine, speculate_k=args.speculate_k)
    if args.no_prefix_cache:
        engine = dataclasses.replace(engine, prefix_cache=False)
    if args.tokenizer and args.tokenizer != "approx":
        # ONE token authority (SURVEY §7.4 item 4): an explicit --tokenizer
        # names the serving tokenizer too, not just the chunker's counter
        engine = dataclasses.replace(engine, tokenizer=args.tokenizer)
    return PipelineConfig(
        data=DataConfig(
            merge_same_speaker=not args.no_merge,
            time_interval_seconds=args.time_interval,
            max_segment_duration=args.max_segment_duration,
            limit_segments=args.limit_segments,
        ),
        chunk=ChunkConfig(
            max_tokens_per_chunk=args.max_tokens_per_chunk,
            overlap_tokens=args.overlap_tokens,
            tokenizer=args.tokenizer,
        ),
        engine=engine,
        mesh=mesh,
        reduce=ReduceConfig(hierarchical=not args.no_hierarchical,
                            streaming=args.stream_reduce),
    )


def _export_trace(trace_out: str) -> None:
    from lmrs_tpu.obs import export_current

    n, err = export_current(trace_out)
    if err is None:
        logger.info("wrote %d trace events to %s (open in "
                    "https://ui.perfetto.dev)", n, trace_out)
    else:  # degraded, not fatal (same as --output)
        logger.error("could not write trace %s: %s", trace_out, err)


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    setup_logging(quiet=args.quiet)
    trace_out = None if args.no_trace else args.trace_out
    if trace_out:
        from lmrs_tpu.obs import enable_tracing

        enable_tracing()
    from lmrs_tpu.utils.platform import setup_compile_cache

    setup_compile_cache()

    try:
        transcript = json.loads(Path(args.input).read_text(encoding="utf-8"))
    except (OSError, json.JSONDecodeError) as e:
        logger.error("could not read transcript %s: %s", args.input, e)
        return 1

    summarizer = TranscriptSummarizer(config_from_args(args), profile=args.profile)
    try:
        try:
            stats = summarizer.summarize(
                transcript,
                prompt_file=args.prompt_file,
                system_prompt_file=args.system_prompt_file,
                aggregator_prompt_file=args.aggregator_prompt_file,
                summary_type=args.summary_type,
                save_chunks=args.save_chunks,
                resume_from=args.resume_from,
            )
        except ValueError as e:
            logger.error("pipeline configuration error: %s", e)
            return 1
    finally:
        # export whatever the ring buffer holds even when the pipeline
        # fails — a failed run is exactly when the trace matters most
        if trace_out:
            _export_trace(trace_out)
    summarizer.shutdown()

    summary = stats["summary"]
    if not args.quiet:
        # final stats banner (main.py:370-379)
        print("\n" + "=" * 60)
        print("SUMMARY")
        print("=" * 60)
        print(summary)
        print("=" * 60)
        print(
            f"segments: {stats['num_input_segments']} -> {stats['num_segments']}  "
            f"chunks: {stats['num_chunks']}  "
            f"duration: {stats['transcript_duration_str']}  "
            f"tokens: {stats['total_tokens_used']}  "
            f"device-s: {stats['total_device_seconds']}  "
            f"wall: {format_duration(stats['processing_time'])}"
        )
        em = stats.get("engine_metrics") or {}
        if "prefill_tokens_per_sec" in em:  # scheduler-shaped metrics
            print(
                f"engine: prefill {em['prefill_tokens_per_sec']} tok/s  "
                f"decode {em['decode_tokens_per_sec']} tok/s  "
                f"occupancy {em['mean_decode_occupancy']}  "
                f"kv-pages {em['peak_kv_page_utilization']}"
            )
        elif "hosts" in em:  # router-shaped metrics (backend=http)
            print(f"engine: {em['healthy_hosts']}/{em['hosts']} hosts healthy  "
                  + "  ".join(
                      f"{row['host']}: {row['served']} served"
                      for row in em.get("per_host", [])))

    if args.output:
        try:
            Path(args.output).write_text(summary, encoding="utf-8")
        except OSError as e:  # degraded, not fatal (main.py:400-402)
            logger.error("could not write output %s: %s", args.output, e)
        if args.report:
            report_path = str(args.output) + ".report.json"
            report = {k: v for k, v in stats.items() if k != "summary"}
            try:
                Path(report_path).write_text(json.dumps(report, indent=2), encoding="utf-8")
            except OSError as e:
                logger.error("could not write report %s: %s", report_path, e)
    return 0


if __name__ == "__main__":
    sys.exit(main())
