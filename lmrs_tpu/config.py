"""Typed configuration tree for the whole framework.

Replaces the reference's three ad-hoc config layers (env via ``LLMConfig``
at llm_executor.py:31-52, argparse flags at main.py:412-472, ctor kwargs on
every component) with one dataclass tree and the same precedence:
explicit kwargs > CLI flags > environment > defaults  (SURVEY.md §5.6).

Reference-compatible environment variables (MAX_CONCURRENT_REQUESTS,
TEMPERATURE, MAX_TOKENS, REQUEST_TIMEOUT, RETRY_ATTEMPTS, RETRY_DELAY,
DEFAULT_PROVIDER; .env.template:1-22) are honored so a reference user's
``.env`` keeps working.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any


def _env(name: str, default: Any, cast: type = str) -> Any:
    """Config-field env override, routed through the shared validated
    parser (utils/env.py): empty string means default, non-finite numbers
    are rejected, bad values warn once and keep the default."""
    from lmrs_tpu.utils import env as _envmod

    if cast is bool:
        return _envmod.env_bool(name, bool(default))
    if cast is int:
        return _envmod.env_int(name, default)
    if cast is float:
        return _envmod.env_float(name, default)
    raw = _envmod.env_str(name, "" if default is None else str(default))
    return raw if default is not None or raw else default


@dataclass
class DataConfig:
    """Preprocessing stage knobs (reference: preprocessor.py:15-67)."""

    merge_same_speaker: bool = True
    time_interval_seconds: float | None = None
    max_segment_duration: float = 120.0
    preserve_timestamps: bool = True
    limit_segments: int | None = None  # reference --limit-segments (main.py:450-452)


@dataclass
class ChunkConfig:
    """Chunker knobs (reference: big_chunkeroosky.py:23-44).

    Unlike the reference, ``overlap_tokens`` is actually implemented
    (reference accepts-but-ignores it; SURVEY.md §2.3 quirk 1).
    ``tokenizer`` names the token-count authority — in the TPU build this is
    the *serving model's* tokenizer, not cl100k_base (SURVEY.md §7.4 item 4).
    """

    max_tokens_per_chunk: int = 4000
    overlap_tokens: int = 200
    context_tokens: int = 150
    tokenizer: str = "approx"  # "approx" | "byte" | HF repo id / sp model path

    @property
    def effective_max_tokens(self) -> int:
        return self.max_tokens_per_chunk - self.context_tokens


@dataclass
class ModelConfig:
    """Decoder-only transformer hyperparameters (lmrs_tpu.models)."""

    name: str = "tiny"
    vocab_size: int = 512
    dim: int = 256
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4
    hidden_dim: int = 688
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = True
    dtype: str = "bfloat16"
    # Gemma-style differences
    logit_softcap: float | None = None
    embed_scale: bool = False  # Gemma multiplies embeddings by sqrt(dim)
    head_dim: int | None = None  # explicit per-head dim (Gemma-7B: 256 != dim/heads)
    activation: str = "silu"  # FFN gate activation: "silu" (Llama) | "gelu" (Gemma)
    # Mixture-of-experts (0 experts = dense FFN; ops/moe.py)
    n_experts: int = 0
    n_experts_per_token: int = 2
    expert_capacity_factor: float = 1.25
    router_aux_coef: float = 0.01  # load-balance loss weight in training
    # What ``LatentModelConfig`` (below) adds, at the values a dense model
    # has: plain class attributes, NOT dataclass fields, so that a dense
    # ``ModelConfig`` is field for field what it was (its ``asdict``, its
    # ``replace``), and the static ``if cfg.kv_lora_rank`` / ``if
    # cfg.n_routed_experts`` branches read 0 on it.
    q_lora_rank = kv_lora_rank = 0
    qk_nope_head_dim = qk_rope_head_dim = v_head_dim = 0
    rope_factor, rope_orig_max_pos = 1.0, 0
    rope_beta_fast, rope_beta_slow = 32.0, 1.0
    rope_mscale, rope_mscale_all_dim = 1.0, 0.0
    n_routed_experts = n_shared_experts = 0
    routed_scaling_factor = 1.0
    expert_first = n_experts_held = 0
    n_dense_layers = dense_hidden_dim = 0
    # What ``LoopedModelConfig`` adds, likewise: the stack runs once, no
    # second norm on a sublayer's output, no token leaves the loop early.
    n_loops, sandwich_norm, early_exit_threshold = 1, False, 1.0
    # What ``WindowMoEModelConfig`` adds, likewise: every layer a full
    # layer, a norm on every sublayer's input, no norm on q and k, rope on
    # every layer.
    sliding_window, window_pattern = 0, ""
    norm_inputs, qk_norm, rope_window_only = True, False, False

    def __post_init__(self) -> None:
        if self.n_loops < 1:
            raise ValueError(f"n_loops={self.n_loops}: the stack runs at "
                             "least once")
        if self.early_exit_threshold < 1.0:
            raise ValueError(
                f"early_exit_threshold={self.early_exit_threshold} < 1 (a "
                "token leaves the loop once its exit gate passes the "
                "threshold: depth chosen per token, a scheduler change) is "
                "not built; every token runs all n_loops passes, which is "
                "what the threshold 1.0 means")

    @property
    def cache_layers(self) -> int:
        """Layers the KV cache is deep: one per (pass, layer).  Weights are
        ``n_layers`` deep."""
        return self.n_layers * self.n_loops

    @property
    def hd(self) -> int:
        """Per-head dimension; ``head_dim`` overrides the dim/n_heads default.
        Under latent attention: a head's query/key width."""
        if self.kv_lora_rank:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_dim or self.dim // self.n_heads

    @property
    def latent_width(self) -> int:
        """Lanes of one latent cache row: latent + rotary values, padded
        to a multiple of 128 (the pad stays zero)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def n_routed_layers(self) -> int:
        return (self.n_layers - self.n_dense_layers
                if self.n_routed_experts else 0)

    @property
    def layer_windows(self) -> tuple[int, ...]:
        """Per layer, the positions back that its attention sees: 0 on a
        full layer (every earlier position), ``sliding_window`` where the
        layer's letter in ``window_pattern`` (repeated over the layers) is
        ``L``."""
        if not self.sliding_window:
            return (0,) * self.n_layers
        pat = self.window_pattern or "L"
        return tuple(self.sliding_window if pat[i % len(pat)] == "L" else 0
                     for i in range(self.n_layers))

    @property
    def n_window_layers(self) -> int:
        return sum(1 for w in self.layer_windows if w)

    def window_ring_pages(self, page_size: int) -> int:
        """Pages a window layer keeps a sequence, however long it grows:
        ``ceil(window / page_size) + 1`` (engine/kv_cache.py)."""
        return -(-self.sliding_window // page_size) + 1

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_routed_experts


@dataclass
class LatentModelConfig(ModelConfig):
    """A latent-attention model over routed experts (models/latent.py): the
    DeepSeek-V3 block.  ``hidden_dim`` is one expert's width and
    ``n_experts_per_token`` the experts a token chooses."""

    # Latent attention (MLA, models/latent.py): ``kv_lora_rank`` > 0 selects
    # it.  A head's queries and keys are ``qk_nope_head_dim`` values from
    # the latent plus ``qk_rope_head_dim`` rotary ones shared by all heads;
    # the cache holds one [kv_lora_rank + qk_rope_head_dim] row a token.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rope scaling (ops/rope.py); factor 1 is plain rope
    rope_factor: float = 1.0
    rope_orig_max_pos: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # Routed experts behind a sigmoid router with a selection bias
    # (ops/moe.routed_experts; ``hidden_dim`` is one expert's width,
    # ``n_experts_per_token`` the experts chosen): the router is
    # ``n_routed_experts`` wide, this engine holds ``n_experts_held`` of them
    # (0: all) from ``expert_first`` on and computes their part of a layer.
    # The first ``n_dense_layers`` layers keep a dense FFN of
    # ``dense_hidden_dim``.
    n_routed_experts: int = 0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    expert_first: int = 0
    n_experts_held: int = 0
    n_dense_layers: int = 0
    dense_hidden_dim: int = 0


@dataclass
class LoopedModelConfig(ModelConfig):
    """Looped layers (models/transformer.run_stack): the one stack of
    ``n_layers`` layers runs ``n_loops`` times over the same weights, the
    final norm closing every pass; pass t's layer l keeps its own K/V, cache
    layer ``t * n_layers + l`` (``cache_layers``).  ``sandwich_norm``: a
    second norm on each sublayer's OUTPUT, before the residual add.  Depth
    is fixed: an exit gate that lets a token leave early
    (``early_exit_threshold`` < 1) would make a step's depth data-dependent
    per row and is refused (``ModelConfig.__post_init__``)."""

    n_loops: int = 1
    sandwich_norm: bool = False
    early_exit_threshold: float = 1.0


@dataclass
class WindowMoEModelConfig(ModelConfig):
    """GQA attention whose layers differ in kind (models/windowed.py; the
    EXAONE-4 block, with the DeepSeek-V3 routed layer behind it where
    ``n_routed_experts`` > 0).  ``sliding_window`` > 0 selects it: a layer
    whose letter in ``window_pattern`` (``"LLLG"``, repeated over the
    layers) is ``L`` sees ``sliding_window`` positions back, position i the
    keys j with 0 <= i - j < window; a ``G`` layer sees them all.  A window
    layer's cache is a ring of ``window_ring_pages`` pages a slot.  The
    routed fields are ``LatentModelConfig``'s, over K and V pools.

    Three block switches, each static: ``norm_inputs`` False leaves a
    sublayer's input un-normed (with ``sandwich_norm`` the norms sit on the
    outputs alone); ``qk_norm`` norms q and k over each head's width before
    rope; ``rope_window_only`` leaves the full layers without rope."""

    sliding_window: int = 0
    window_pattern: str = ""
    norm_inputs: bool = True
    qk_norm: bool = False
    rope_window_only: bool = False
    sandwich_norm: bool = False
    n_routed_experts: int = 0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    expert_first: int = 0
    n_experts_held: int = 0
    n_dense_layers: int = 0
    dense_hidden_dim: int = 0

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.sliding_window <= 0:
            raise ValueError("WindowMoEModelConfig: sliding_window > 0 is "
                             "what selects models/windowed.py")
        if set(self.window_pattern) - set("LG"):
            raise ValueError(f"window_pattern {self.window_pattern!r}: L "
                             "(window) and G (full) only")
        if self.n_loops != 1:
            raise ValueError("a windowed stack runs once (n_loops 1)")


@dataclass
class MeshConfig:
    """Device mesh axes: data, tensor (ICI), sequence/context, pipeline.

    The reference has no device parallelism at all (SURVEY.md §2.2); these
    axes are the TPU-native replacement for its asyncio request fan-out.
    """

    dp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1  # expert parallel (MoE expert axis; ops/moe.py)
    pp: int = 1
    axis_names: tuple[str, ...] = ("dp", "tp", "sp", "ep", "pp")

    @property
    def n_devices(self) -> int:
        return self.dp * self.tp * self.sp * self.ep * self.pp


@dataclass
class EngineConfig:
    """Generation engine knobs.

    Mirrors the reference's ``LLMConfig`` env surface (llm_executor.py:31-52)
    but the "provider" is an in-tree backend, not an HTTP vendor:
    ``backend`` ∈ {"mock", "jax", "http"} — "http" fans over remote
    lmrs-serve hosts (multi-host serving, serving/router.py).
    ``max_concurrent_requests`` maps to the continuous-batching decode slot
    count (admission control; SURVEY.md §2.2).
    """

    backend: str = field(default_factory=lambda: _env("LMRS_BACKEND", _env("DEFAULT_PROVIDER", "mock")))
    model: str = field(default_factory=lambda: _env("LMRS_MODEL", "tiny"))
    # backend="http": remote lmrs-serve hosts the RouterEngine fans over
    # (multi-host DP serving, serving/router.py); comma-separated in env
    hosts: tuple[str, ...] = field(
        default_factory=lambda: tuple(
            h.strip() for h in _env("LMRS_HOSTS", "").split(",") if h.strip()))
    # Disaggregated serving pools (serving/router.py + docs/SERVING.md):
    # prefill-role and decode-role lmrs-serve hosts.  When BOTH are
    # non-empty the router runs the two-tier handoff (admission to the
    # prefill pool, KV-page ticket to the decode pool); either pool empty
    # or fully degraded falls back to colocated operation over
    # ``hosts``/the surviving pool.  Comma-separated in env.
    prefill_hosts: tuple[str, ...] = field(
        default_factory=lambda: tuple(
            h.strip() for h in _env("LMRS_PREFILL_HOSTS", "").split(",")
            if h.strip()))
    decode_hosts: tuple[str, ...] = field(
        default_factory=lambda: tuple(
            h.strip() for h in _env("LMRS_DECODE_HOSTS", "").split(",")
            if h.strip()))
    temperature: float = field(default_factory=lambda: _env("TEMPERATURE", 0.3, float))
    max_tokens: int = field(default_factory=lambda: _env("MAX_TOKENS", 1000, int))
    max_concurrent_requests: int = field(
        default_factory=lambda: _env("MAX_CONCURRENT_REQUESTS", 5, int)
    )
    request_timeout: float = field(default_factory=lambda: _env("REQUEST_TIMEOUT", 60.0, float))
    retry_attempts: int = field(default_factory=lambda: _env("RETRY_ATTEMPTS", 3, int))
    retry_delay: float = field(default_factory=lambda: _env("RETRY_DELAY", 5.0, float))
    seed: int = 0
    # serving-side knobs (no reference counterpart — SURVEY.md §7.4 item 1)
    scheduler: str = "continuous"  # "continuous" (slot-based) | "static" (lockstep waves)
    max_batch_slots: int = 8
    page_size: int = 128
    num_pages: int = 512
    # Prompt tokens prefilled per scheduler turn.  Default = one-dispatch
    # prefill: chunking (e.g. 512) was ABBA-measured a throughput LOSS and,
    # per the decode-latency histogram (docs/PERF.md round 2), DOUBLES p50
    # decode latency for active slots (272-302 vs 140-144 ms/block) while
    # only trimming p90 (330 vs 443-485 ms).  Set a small value only when
    # worst-case tail fairness under very long prompts outweighs both.
    prefill_chunk: int = 4096
    decode_block: int = 16  # decode steps per host sync (see scheduler)
    # Multi-row decode page walk (ops/paged_attention.py): each ragged
    # decode program walks `decode_row_group` batch rows' live pages
    # through one shared double-buffered DMA pipeline, amortizing the
    # per-program fixed cost that one-row-per-program dispatch pays per
    # row (~2.8 ms of the 8B decode step; docs/PERF.md r5 intercept
    # decomposition).  The scheduler length-balances the row→group
    # assignment per dispatch and clamps to the slot count.
    # LMRS_MULTIROW=0 is the kill switch (per-row grid, exact previous
    # behavior — same A/B convention as LMRS_PACK_PREFILL);
    # LMRS_DECODE_ROW_GROUP overrides the group size.
    decode_row_group: int = field(
        default_factory=lambda: _env("LMRS_DECODE_ROW_GROUP", 4, int))
    # SARATHI-style mixed batches (PAPERS.md): while a prompt is mid-
    # prefill, each scheduler step dispatches ONE fused batch carrying all
    # live decode rows (one token each) plus a chunked-prefill slice
    # clipped to `mixed_token_budget - decode_tokens`, through the ragged
    # multi-token row-group path — decode cadence never pauses for an
    # admission and prefill rides the decode step's spare FLOPs (the
    # block-gap / TTFT coupling ROADMAP item 1 measured).  LMRS_MIXED=0 is
    # the kill switch (exact alternating prefill/decode dispatch — same
    # A/B convention as LMRS_PACK_PREFILL / LMRS_MULTIROW).  Auto-disabled
    # with kv_quantize (a mixed chunk cannot own its slot's frozen
    # prefill scales) and under sp>1 meshes (ring prefill replaces
    # chunking there, so there is no slice to piggyback).
    mixed_batch: bool = True
    # Token budget of one mixed step: live decode tokens first, the
    # remainder is the prefill slice (clipped; a budget the decode rows
    # already exhaust falls back to alternating dispatch for that step).
    mixed_token_budget: int = field(
        default_factory=lambda: _env("LMRS_MIXED_TOKEN_BUDGET", 256, int))
    # prompt-lookup speculative decoding: draft length per step (0 = off).
    # Exact-distribution verify (ops/speculative.py) — output quality is
    # unchanged; latency drops when summaries quote their source.
    speculate_k: int = 0
    # n-gram length for prompt-lookup drafting (ops/speculative.draft_lookup):
    # 3 collides far less than 2 on byte-level vocabularies (measured r4)
    speculate_ngram: int = 3
    checkpoint_path: str | None = None
    quantize: str | None = None  # None | "int8" (weight-only; ops/quant.py)
    # int8 KV-cache pages (ops/quant.py KV section): halves decode's KV
    # bytes and doubles tokens per HBM GiB; per-slot/head/channel scales
    # fixed at prefill.  Gates packed + ring prefill off (per-slot scales
    # can't cover a packed row's many prompts / sp-sharded writes).
    kv_quantize: str | None = None  # None | "int8"
    # Shared-prefix KV cache (engine/prefix_cache.py): completed prompts
    # donate their full-page KV prefix to a radix tree; a new request whose
    # prompt shares that prefix clones the pages (ref-counted, read-only)
    # and starts prefill at the first uncached token.  Default ON — the
    # map/reduce stages repeat the same preamble per chunk; LMRS_PREFIX_CACHE=0
    # or prefix_cache=False is the kill switch.  Auto-disabled with
    # kv_quantize (per-slot scales cannot cover donor-quantized pages) and
    # under sp>1 meshes (cache hits enter the windowed-continuation prefill,
    # which does not ride the ring).
    prefix_cache: bool = True
    # cap on pages the prefix cache retains (0 = no explicit cap: retained
    # pages stay bounded by the pool, drained on demand by the OutOfPages
    # back-pressure eviction)
    prefix_cache_max_pages: int = 0
    # Host-RAM KV spill tier (engine/host_kv.py, ROADMAP item 3): evicted
    # refcount-zero prefix-cache pages capture their content into a
    # bounded host-memory pool and prefetch back on a later radix match
    # instead of re-prefilling — the fleet's HBM + host RAM become one
    # cache hierarchy.  LMRS_HOST_KV=0 (or host_kv=False) is the kill
    # switch: eviction means gone, byte-for-byte today's behavior.  Only
    # meaningful with prefix_cache on (and therefore never with int8 KV,
    # which disables the prefix cache).
    host_kv: bool = field(
        default_factory=lambda: _env("LMRS_HOST_KV", True, bool))
    # host pool budget in GiB (LRU over spilled subtrees past it); an
    # entry bigger than the whole budget skips the spill entirely
    host_kv_gb: float = field(
        default_factory=lambda: _env("LMRS_HOST_KV_GB", 1.0, float))
    # Disk spill tier (engine/host_kv.DiskKVPool, ROADMAP item 4): host
    # pool budget pressure demotes LRU entries to mmap'd spill files
    # instead of dropping them; promotion reads disk→host→device on the
    # prefetch path.  OPT-IN (writing GBs of KV to disk is a deployment
    # decision); LMRS_KV_DISK=0 restores host-pressure-means-gone
    # byte-for-byte.  Only meaningful with the host tier armed.
    kv_disk: bool = field(
        default_factory=lambda: _env("LMRS_KV_DISK", False, bool))
    # disk pool budget in GiB (LRU subtree drops past it)
    kv_disk_gb: float = field(
        default_factory=lambda: _env("LMRS_KV_DISK_GB", 4.0, float))
    # spill-file root directory ("" = system temp); each pool makes its
    # own fresh subdirectory, so engines sharing the root never collide
    kv_disk_dir: str = field(
        default_factory=lambda: _env("LMRS_KV_DISK_DIR", ""))
    # engine-side tokenizer spec ("" = model default: byte for random-init
    # vocabs, the checkpoint's tokenizer for real ones).  Accepts the same
    # forms as data.tokenizer.get_tokenizer: "byte", a *.model SentencePiece
    # path, or an HF tokenizer directory/repo id (local_files_only).
    tokenizer: str = ""
    # Fault-injection plane (lmrs_tpu/testing/faults.py): a JSON FaultPlan
    # (or "@/path/to/plan.json") installed process-globally by make_engine.
    # Empty = disabled — every injection site is a module-level no-op and
    # the hot path pays nothing (the tier-1 A/B gate asserts the greedy
    # output is token-identical with the plane disarmed).
    fault_plan: str = field(
        default_factory=lambda: _env("LMRS_FAULT_PLAN", ""))
    # Deadline budget (seconds) the MAP EXECUTOR stamps onto every request
    # it runs that doesn't already carry one (0 = no deadline).  A
    # deadline-carrying request is shed at admission when the remaining
    # budget can't cover the TTFT estimate (finish_reason="shed") and
    # expired in flight at the next block boundary ("deadline"); executor
    # and router retries clip to the remaining budget.
    request_deadline_s: float = field(
        default_factory=lambda: _env("LMRS_REQUEST_DEADLINE", 0.0, float))
    # Disaggregated handoff pin TTL (seconds): pages exported for a
    # prefill→decode handoff stay pinned (ref-counted) until the decode
    # side acks the import; a ticket never acked is orphan-swept after
    # this long and its pages reclaimed (the crash-safety backstop for a
    # dead decode pod or a lost ack — docs/SERVING.md ticket lifecycle).
    # A request deadline tighter than the TTL clips it.
    handoff_ttl_s: float = field(
        default_factory=lambda: _env("LMRS_HANDOFF_TTL", 60.0, float))

    def __post_init__(self) -> None:
        # Reference DEFAULT_PROVIDER values name HTTP vendors; both map to
        # the local engine choice "mock" when no backend is explicitly set.
        if self.backend in ("openai", "anthropic"):
            self.backend = "mock"
        if self.quantize not in (None, "int8"):
            raise ValueError(f"unknown quantize mode {self.quantize!r}; "
                             "supported: int8")
        if self.kv_quantize not in (None, "int8"):
            raise ValueError(f"unknown kv_quantize mode {self.kv_quantize!r}; "
                             "supported: int8")
        if self.decode_row_group < 1:
            raise ValueError(f"decode_row_group must be >= 1 "
                             f"(got {self.decode_row_group}); use "
                             "LMRS_MULTIROW=0 to disable row grouping")
        if self.mixed_token_budget < 32:
            raise ValueError(f"mixed_token_budget must be >= 32 "
                             f"(got {self.mixed_token_budget}); use "
                             "mixed_batch=False / LMRS_MIXED=0 to disable "
                             "mixed dispatch")
        if self.host_kv_gb < 0:
            raise ValueError(f"host_kv_gb must be >= 0 "
                             f"(got {self.host_kv_gb}); use host_kv=False / "
                             "LMRS_HOST_KV=0 to disable the spill tier")
        if self.kv_disk_gb < 0:
            raise ValueError(f"kv_disk_gb must be >= 0 "
                             f"(got {self.kv_disk_gb}); use kv_disk=False / "
                             "LMRS_KV_DISK=0 to disable the disk tier")
        if self.request_deadline_s < 0:
            raise ValueError(f"request_deadline_s must be >= 0 "
                             f"(got {self.request_deadline_s}); 0 disables "
                             "deadlines")
        if self.handoff_ttl_s <= 0:
            raise ValueError(f"handoff_ttl_s must be > 0 "
                             f"(got {self.handoff_ttl_s}): un-acked "
                             "handoff pins need a finite orphan-sweep "
                             "deadline or a dead decode pod leaks pages")


@dataclass
class ReduceConfig:
    """Reduce-tree knobs (reference: result_aggregator.py:32-53,357-380).

    The reference tree is capped at exactly two levels (quirk 11); here
    ``max_levels`` allows true recursion until the batch fits.
    """

    max_tokens_per_batch: int = 6000
    hierarchical: bool = True
    reserve_tokens: int = 1000
    max_summaries_per_batch: int = 10
    # stream reduce batches into the map stage's engine stream as their
    # member summaries complete (reduce/streaming.py) instead of the
    # reference's hard map→reduce barrier (main.py:169-236).  Default OFF:
    # measured a ~2% LOSS on the bench workload (in-process ABBA,
    # docs/PERF.md) — with short decodes the reduce share is too small to
    # hide and the mixed-shape admissions cost more than the overlap wins.
    # Worth enabling for long-decode workloads (max_tokens ~1000) or deep
    # reduce trees, where the tail is a real fraction of the run.
    streaming: bool = False
    max_levels: int = 4
    temperature: float = 0.2  # reference hardcodes 0.2 (result_aggregator.py:238)
    # Stable reduce-tree shape for APPEND-ONLY workloads (lmrs_tpu/live/):
    # fixed-arity (`max_summaries_per_batch`) leaf-aligned batching with
    # position-free batch metadata, so appending leaves changes only the
    # last (partial) batch per level and the root path — every sibling
    # subtree keeps a byte-identical prompt and answers from the node
    # cache.  The default token-budget shape re-batches the WHOLE level
    # when sizes drift and bakes "batch i/n" positions into each prompt,
    # which poisons every cached node on any append.  Off by default: the
    # batch pipeline keeps its historical tree.
    stable_tree: bool = False


@dataclass
class JobsConfig:
    """Durable-job knobs (lmrs_tpu/jobs/: write-ahead journal + async job
    API — docs/ROBUSTNESS.md job-durability section).

    ``jobs_dir`` empty = the job API is disabled (lmrs-serve answers 501;
    batch pipeline runs are unaffected).  ``max_failed_chunk_fraction``
    is the degraded-completion policy: a job whose failed-chunk fraction
    stays at or under it finishes ``status="degraded"`` with the
    per-chunk ``degraded_reason``s attached instead of all-or-nothing
    failure; above it the job is ``status="failed"`` (the summary —
    degrade-and-continue output — is still attached either way).
    """

    jobs_dir: str = field(default_factory=lambda: _env("LMRS_JOBS_DIR", ""))
    max_failed_chunk_fraction: float = field(
        default_factory=lambda: _env("LMRS_JOBS_DEGRADED_FRACTION", 0.2,
                                     float))

    def __post_init__(self) -> None:
        if not 0.0 <= self.max_failed_chunk_fraction <= 1.0:
            raise ValueError(
                f"max_failed_chunk_fraction must be in [0, 1] "
                f"(got {self.max_failed_chunk_fraction}); 0 = any failed "
                "chunk fails the job, 1 = always finish degraded")


@dataclass
class LiveConfig:
    """Live-session knobs (lmrs_tpu/live/: incremental summarization of
    growing transcripts — docs/SERVING.md § Live sessions).

    ``sessions_dir`` empty = the session API is disabled (lmrs-serve
    answers 501; batch pipeline and jobs are unaffected).
    ``refresh_tokens`` > 0 auto-triggers a refresh when a session has
    accumulated that many appended-but-unsummarized tokens (0 = refresh
    only on request).  ``class_default`` is the deadline class a refresh
    runs under when the request names none: ``interactive`` refreshes
    carry a per-request deadline (``interactive_deadline_s``) and ride
    PR 5's shed/expiry lifecycle ahead of ``bulk`` backfill, which runs
    unbounded.
    """

    sessions_dir: str = field(default_factory=lambda: _env("LMRS_LIVE_DIR", ""))
    refresh_tokens: int = field(
        default_factory=lambda: _env("LMRS_LIVE_REFRESH_TOKENS", 0, int))
    class_default: str = field(
        default_factory=lambda: _env("LMRS_LIVE_CLASS_DEFAULT", "interactive"))
    interactive_deadline_s: float = 120.0

    def __post_init__(self) -> None:
        if self.class_default not in ("interactive", "bulk"):
            raise ValueError(
                f"unknown live deadline class {self.class_default!r}; "
                "want interactive|bulk")
        if self.refresh_tokens < 0:
            raise ValueError(
                f"refresh_tokens must be >= 0 (got {self.refresh_tokens}); "
                "0 disables auto-refresh")
        if self.interactive_deadline_s <= 0:
            raise ValueError(
                f"interactive_deadline_s must be > 0 "
                f"(got {self.interactive_deadline_s}); use class 'bulk' "
                "for unbounded refreshes")


@dataclass
class PipelineConfig:
    """Top-level config: one object wires the whole pipeline."""

    data: DataConfig = field(default_factory=DataConfig)
    chunk: ChunkConfig = field(default_factory=ChunkConfig)
    engine: EngineConfig = field(default_factory=EngineConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    reduce: ReduceConfig = field(default_factory=ReduceConfig)
    jobs: JobsConfig = field(default_factory=JobsConfig)
    live: LiveConfig = field(default_factory=LiveConfig)

    def replace(self, **kw: Any) -> "PipelineConfig":
        return dataclasses.replace(self, **kw)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def parse_mesh(spec: str) -> "MeshConfig":
    """``"dp,tp[,sp[,pp]]"`` → MeshConfig (shared by the lmrs/lmrs-train
    CLIs so the axis order can't drift between them)."""
    dims = [int(x) for x in spec.split(",")]
    if not 1 <= len(dims) <= 4:
        raise ValueError(f"mesh spec {spec!r}: expected 1-4 axes dp,tp[,sp[,pp]]")
    dims += [1] * (4 - len(dims))
    return MeshConfig(dp=dims[0], tp=dims[1], sp=dims[2], pp=dims[3])


def model_preset(name: str) -> ModelConfig:
    """Named model configurations (L3 model zoo presets)."""
    presets: dict[str, dict] = {
        "tiny": {},
        "tiny-gemma": dict(
            logit_softcap=30.0, embed_scale=True, rope_theta=10000.0,
            tie_embeddings=True, activation="gelu", norm_eps=1e-6,
        ),
        "llama3-8b": dict(
            vocab_size=128256, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
            hidden_dim=14336, max_seq_len=8192, rope_theta=500000.0,
            tie_embeddings=False,
        ),
        "llama3-70b": dict(
            vocab_size=128256, dim=8192, n_layers=80, n_heads=64, n_kv_heads=8,
            hidden_dim=28672, max_seq_len=8192, rope_theta=500000.0,
            tie_embeddings=False,
        ),
        "gemma-2b": dict(
            vocab_size=256128, dim=2048, n_layers=18, n_heads=8, n_kv_heads=1,
            hidden_dim=16384, max_seq_len=8192, rope_theta=10000.0,
            tie_embeddings=True, embed_scale=True, head_dim=256,
            activation="gelu", norm_eps=1e-6,
        ),
        "gemma-7b": dict(
            vocab_size=256128, dim=3072, n_layers=28, n_heads=16, n_kv_heads=16,
            hidden_dim=24576, max_seq_len=8192, rope_theta=10000.0,
            tie_embeddings=True, embed_scale=True, head_dim=256,  # != dim/heads
            activation="gelu", norm_eps=1e-6,
        ),
        "bench-1b": dict(
            # ~1.03B params, Llama-3 proportions at 1B scale (GQA 16q/8kv,
            # head_dim 128 engages the ragged decode kernel), byte vocab so
            # it needs no downloaded tokenizer.  The scale exists so a run
            # measures the MXU/HBM, not the host link (a 45M model
            # under-utilizes the chip ~20x).
            vocab_size=512, dim=2048, n_layers=18, n_heads=16, n_kv_heads=8,
            hidden_dim=7168, max_seq_len=2048, rope_theta=500000.0,
            tie_embeddings=True,
        ),
        "tiny-moe": dict(
            hidden_dim=512, n_experts=4, n_experts_per_token=2,
        ),
        "bench-8b": dict(
            # The BASELINE north-star model shape (Llama-3-8B: BASELINE.md
            # headline row), full vocabulary included so the LM head
            # streams its real 525 MB share of the decode bytes.  Window
            # 2048 = the bench's measured-optimal serving window (the 8192
            # training window is irrelevant to chunked map serving —
            # docs/PERF.md round 4 rejected 4096).  Run with int8 weights
            # + int8 KV: ~8.6 GB weights + ~3.2 GB worst-case page pool
            # fits one 16 GB v5e chip.
            vocab_size=128256, dim=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, hidden_dim=14336, max_seq_len=2048,
            rope_theta=500000.0, tie_embeddings=False,
        ),
        "quality-tiny": dict(
            # CLI end-to-end quality gate (tests/test_quality.py): a byte-
            # level model small enough to fine-tune inside the test suite on
            # CPU, with a context window that fits the product-formatted map
            # prompt (template + chunk context header + chunk body) without
            # middle-truncation at the CLI's default generation budget.
            # max_seq_len 1024: the product-formatted prompts are ~460
            # bytes; CPU XLA compile time scales badly with the window
            # (tests run this preset through the full CLI)
            vocab_size=512, dim=96, n_layers=2, n_heads=4, n_kv_heads=2,
            hidden_dim=256, max_seq_len=1024, dtype="float32",
        ),
        "bench-smoke": dict(
            # CPU smoke at bench-1b's scheduler shapes: tiny compute but
            # bench-1b's max_seq_len, so a 1400-token chunk budget (+ context
            # + template < 1920 truncation line) holds and the exact same
            # scheduler shapes compile — in seconds on a CPU, not minutes
            # ("tiny" inherits max_seq_len 8192, whose packed/decode shapes
            # thrash CPU XLA).
            vocab_size=512, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
            hidden_dim=256, max_seq_len=2048,
        ),
        "kimi-k2.6": dict(
            # moonshotai/Kimi-K2.6 config.json (model_type kimi_k2, the
            # DeepSeek-V3 block) as published: 1.04T parameters, no chip
            # holds it; benchmarks/configs/kimi-k2.6.json is one chip's
            # share of it (fewer layers, 12 experts held, a vocabulary slice)
            vocab_size=163840, dim=7168, n_layers=61, n_heads=64,
            n_kv_heads=64, hidden_dim=2048, dense_hidden_dim=18432,
            max_seq_len=8192, rope_theta=50000.0, tie_embeddings=False,
            q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, rope_factor=64.0,
            rope_orig_max_pos=4096, rope_mscale=1.0, rope_mscale_all_dim=1.0,
            n_dense_layers=1, n_routed_experts=384, n_shared_experts=1,
            n_experts_per_token=8, routed_scaling_factor=2.827,
        ),
        "tiny-latent": dict(
            # the same block at test size: one dense layer, two routed
            # layers that hold experts 4-7 of 16; the latent is 128 wide so
            # that the decode kernel's lane-aligned slices hold
            vocab_size=512, dim=64, n_layers=3, n_heads=4, n_kv_heads=4,
            hidden_dim=32, dense_hidden_dim=96, max_seq_len=256,
            rope_theta=10000.0, tie_embeddings=False, q_lora_rank=48,
            kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=16,
            v_head_dim=32, rope_factor=4.0, rope_orig_max_pos=64,
            rope_mscale=1.0, rope_mscale_all_dim=1.0, n_dense_layers=1,
            n_routed_experts=16, n_shared_experts=1, n_experts_per_token=4,
            routed_scaling_factor=2.5, expert_first=4, n_experts_held=4,
        ),
        "ouro-2.6b": dict(
            # ByteDance/Ouro-2.6B config.json (model_type ouro, "LoopLM"):
            # one stack of 48 layers run total_ut_steps = 4 times over the
            # same weights, sandwich norms, the final norm closing every
            # pass; 2.67 B parameters, 192 cache layers: 1.5 MiB of bf16
            # K/V a token (benchmarks/configs/ouro-2.6b.json serves it on
            # int8 pages)
            vocab_size=49152, dim=2048, n_layers=48, n_heads=16,
            n_kv_heads=16, head_dim=128, hidden_dim=5632, max_seq_len=8192,
            rope_theta=1e6, norm_eps=1e-6, tie_embeddings=False,
            n_loops=4, sandwich_norm=True,
        ),
        "tiny-looped": dict(
            # the same block at test size: two layers run three times
            vocab_size=512, dim=128, n_layers=2, n_heads=4, n_kv_heads=4,
            hidden_dim=256, max_seq_len=256, rope_theta=10000.0,
            norm_eps=1e-6, tie_embeddings=False, n_loops=3,
            sandwich_norm=True,
        ),
        "tiny-swa-moe": dict(
            # the EXAONE-4 block over routed experts at test size: three
            # window layers (16) to a full one, a dense leading layer, 8
            # routed experts top-2 with a shared one
            vocab_size=512, dim=64, n_layers=5, n_heads=4, n_kv_heads=2,
            head_dim=16, hidden_dim=32, dense_hidden_dim=96, max_seq_len=256,
            rope_theta=10000.0, tie_embeddings=False, sliding_window=16,
            window_pattern="LLLG", norm_inputs=False, sandwich_norm=True,
            qk_norm=True, rope_window_only=True, n_dense_layers=1,
            n_routed_experts=8, n_shared_experts=1, n_experts_per_token=2,
            routed_scaling_factor=2.5,
        ),
        "mixtral-8x7b": dict(
            vocab_size=32000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
            hidden_dim=14336, max_seq_len=8192, rope_theta=1e6,
            tie_embeddings=False, n_experts=8, n_experts_per_token=2,
        ),
    }
    if name not in presets:
        raise ValueError(f"unknown model preset {name!r}; have {sorted(presets)}")
    cls = (LatentModelConfig if "kv_lora_rank" in presets[name]
           else WindowMoEModelConfig if "sliding_window" in presets[name]
           else LoopedModelConfig if "n_loops" in presets[name]
           else ModelConfig)
    return cls(name=name, **presets[name])
