"""Continuous-batching scheduler over a paged KV cache.

The chunk list becomes a prefill/decode work queue over fixed decode slots
(SURVEY.md §2.2: the reference's asyncio-semaphore fan-out,
llm_executor.py:133-147, re-based onto batch-slot + page admission control):

* a request is admitted when a slot is free AND the page pool can hold its
  prompt + token budget (admission = free KV pages, the semaphore analog);
* prefill runs one bucketed [1, S] forward writing K/V straight into the
  sequence's pages and samples the first token on device;
* all active slots decode together in blocks of ``decode_block`` steps per
  dispatch (one ``lax.scan`` on device; the host syncs once per block);
* decode attention cost is proportional to LIVE context: the page window
  passed to the decode program is bucketed to the widest active sequence
  (compile-per-bucket), and on TPU the ragged Pallas kernel walks only each
  row's real pages (ops/paged_attention.py);
* a finished slot frees its pages and the next queued request is admitted —
  prefill and decode interleave across requests.

Static shapes throughout: prompt buckets and page-window buckets are powers
of two, the decode block is fixed — a handful of XLA compilations total,
reused for the whole run.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from lmrs_tpu.config import EngineConfig, ModelConfig
from lmrs_tpu.engine.api import (GenerationRequest, GenerationResult,
                                 apply_stop_sequences, preamble_key,
                                 preamble_text, remaining_budget)
from lmrs_tpu.engine.kv_cache import (OutOfPages, PagedKVCache, SequencePages,
                                      audit_allocator)
from lmrs_tpu.engine.prefix_cache import PrefixCache
from lmrs_tpu.fleet.qos import maybe_qos
from lmrs_tpu.models.transformer import forward_paged
from lmrs_tpu.ops.paged_attention import (pack_spans, pow2_bucket,
                                          span_walk_counts, whole_pages)
from lmrs_tpu.obs import (POW2_TOKEN_BUCKETS, RATIO_BUCKETS, CostLedger,
                          DispatchAttribution, MetricsRegistry, SLOEngine,
                          dump_postmortem, get_tracer, maybe_anatomy, req_tid,
                          span)
from lmrs_tpu.obs.anatomy import WINDOW_FIELDS
from lmrs_tpu.ops.sampling import sample_logits
from lmrs_tpu.testing import faults
from lmrs_tpu.utils.env import env_bool, env_float, env_int, env_str
from lmrs_tpu.utils.perf_model import StepBytes, walk_pages

logger = logging.getLogger("lmrs.scheduler")

# bucket edges shared with the kernel family and the bucket-economics
# accounting (ops/paged_attention.pow2_bucket — one definition)
_pow2_bucket = pow2_bucket


# NOTE: sequence buckets.  Quarter-step buckets for EVERY prompt were
# measured 3x WORSE end-to-end (2026-07, before the benchmark): each new
# shape was a multi-second XLA compile at runtime.  Now the BODY of a fresh
# prefill wave (two prompts or more in one power-of-two bucket) runs on a
# rung of ``prefill_seq_rung``, an eighth of the octave apart above 1,024
# positions on a 128-token page, and its longer prompts on the power of two
# (``prefill_wave_plan``): the shapes of a wave then do not follow whether
# it holds a job's one long chunk, so a window meets the programs its
# warm-up met.  A LONE prompt (a reduce step) keeps the power-of-two
# bucket: its length follows the text generated before it, so a rung would
# be a new shape in the window.  The windowed continuation, ring-sharded
# prompts and packed bins stay on powers of two.  Compiles: a wave's body
# takes the rung of its median prompt, so a traffic whose prompts sit in
# one rung compiles one program a row rung, as the powers of two did.


def prefill_seq_rung(n: int, page_size: int, max_len: int) -> int:
    """Operand positions of a fresh prefill dispatch whose longest prompt
    holds ``n`` tokens: the smallest multiple of ``P // 16`` that holds it, ``P``
    its power-of-two bucket (floor 64), so the rungs lie an eighth of the
    octave ``(P/2, P]`` apart (1,152 ... 1,920, 2,048 in (1,024, 2,048];
    512 apart in (4,096, 8,192]); the power of two itself where that step
    is not a whole number of pages (at or below 1,024 on a 128-token page).
    Every rung is whole pages, so the pool write stays a page at a time
    (``ops/paged_attention.whole_pages``).  Capped at ``max_len``.  Reads
    nothing but its three arguments: one rule for every model, cache type
    and mesh."""
    p = pow2_bucket(n, 64)
    step = p // 16
    if step >= page_size and step % page_size == 0:
        p = -(-n // step) * step
    return min(p, max_len)


def prefill_wave_plan(lens: list[int], slots: int, page_size: int,
                      max_len: int) -> list[tuple[int, int, int]]:
    """The dispatches of one group of fresh prompts that share a
    power-of-two bucket: ``[(prompts, rows, positions)]``, one or two, over
    ``lens`` sorted ascending (the first ``prompts`` go to the first
    dispatch).  The wave's BODY runs on the rung of its median prompt
    (``prefill_seq_rung``); the prompts longer than that rung, if any, are
    cut off into a second dispatch on their power of two.  So a wave's
    shapes do not follow whether it holds one long prompt: the body keeps
    its rows and rung, and one long prompt runs the lone-prompt program
    that every reduce step of the bucket compiles anyway.  The rung is
    taken only where it saves at least that lone dispatch
    (``rows x (pow2 - rung) >= pow2``: 8 rows an eighth of an octave
    below, 4 rows a quarter), and no plan pays more positions than
    the group's one power-of-two dispatch (the rule before rungs); else the
    group takes that dispatch.  A lone prompt keeps its power of two (the
    NOTE above).  Reads nothing but its arguments."""
    n = len(lens)
    top = min(pow2_bucket(lens[-1], 64), max_len)
    rows = prefill_row_rung(n, slots)
    body = prefill_seq_rung(lens[(n - 1) // 2], page_size, max_len)
    if n == 1 or rows * (top - body) < top:
        return [(n, rows, top)]
    k = sum(1 for x in lens if x <= body)
    if k == n:
        return [(n, rows, body)]
    plan = [(k, prefill_row_rung(k, slots), body if k > 1 else top),
            (n - k, prefill_row_rung(n - k, slots), top)]
    if sum(r * s for _, r, s in plan) > rows * top:
        return [(n, rows, top)]
    return plan


@jax.jit
def _put_first_tokens(last_tok, tok0, slot_idx, row_idx):
    """``last_tok[slot_idx] = tok0[row_idx]`` on the device, entries whose
    slot is out of range dropped: one program per (prefill rows, decode
    rows), where eager ops compiled a gather and a scatter per count."""
    return last_tok.at[slot_idx].set(tok0[row_idx], mode="drop")


def prefill_row_rung(group: int, slots: int) -> int:
    """Operand rows of a batched prefill dispatch that carries ``group``
    prompts on an engine of ``slots`` slots: 1 for a lone prompt, else the
    smallest rung of ``8, 16, 32, ...`` capped at ``slots`` that holds the
    group (24 slots: 1 / 8 / 16 / 24; 4 slots: 1 / 4).  The program pays
    for its operand, not for the rows that carry work, so a 16-prompt wave
    on 24 slots must not run the 24-row program; the ladder doubles so a
    sequence bucket compiles at most ``ceil(log2(slots / 8)) + 2`` row
    counts.  Reads nothing but its two arguments: one rule for every
    model, cache type and mesh."""
    if group <= 1:
        return 1
    return min(pow2_bucket(group, 8), slots)


@dataclass
class _SlotState:
    req: GenerationRequest
    prompt_ids: list[int]  # ids to prefill (after a preemption: prompt + prior)
    max_new: int
    seq: SequencePages
    generated: list[int] = field(default_factory=list)
    kv_len: int = 0
    done: bool = False
    t_start: float = 0.0
    # chunked prefill (SARATHI-style): a slot is admitted in "prefill" phase
    # and advances one prompt chunk per scheduler iteration, so active decode
    # slots keep decoding between chunks instead of stalling behind one long
    # prompt.  ``prefill_pos`` = prompt tokens already written to KV.
    phase: str = "prefill"
    prefill_pos: int = 0
    # tracing anchors (obs/trace.py): admission and prefill-complete times
    # for this SLOT LIFE — a preemption continuation opens fresh spans
    t_admit: float = 0.0
    t_decode_start: float = 0.0
    # preemption bookkeeping: a preempted slot re-enters the queue with its
    # generated-so-far tokens folded into ``prompt_ids`` (the continuation
    # re-prefills them); ``n_prompt`` keeps the ORIGINAL prompt length for
    # accounting and ``prior`` the tokens generated before the preemption.
    n_prompt: int = 0
    prior: list[int] = field(default_factory=list)
    # tree speculation (ISSUE 19): accepted tokens of a NON-FIRST chain
    # sit at that chain's span-offset KV columns, so the row's next span
    # re-sends them as leading "healing" query tokens (base = kv_len -
    # len(spec_heal)) to rewrite K/V at their true columns; ``spec_ema``
    # is the windowed acceptance rate feeding the adaptive depth ramp,
    # ``spec_hoff`` the history-buffer offset of a cross-refresh draft
    # hint seeded ahead of the prompt, ``spec_hint`` its token ids.
    spec_heal: list[int] = field(default_factory=list)
    spec_ema: float = 0.5
    spec_depth: int = 0
    spec_probe: int = 0  # steps spent at depth 0 (periodic re-probe timer)
    spec_hoff: int = 0
    spec_hint: list[int] = field(default_factory=list)


class ContinuousScheduler:
    """Host-side scheduling loop over device-side prefill/decode programs."""

    def __init__(self, engine_cfg: EngineConfig, model_cfg: ModelConfig,
                 params, tokenizer, mesh=None):
        self.cfg = engine_cfg
        self.model_cfg = model_cfg
        self.params = params
        self.tokenizer = tokenizer
        self.mesh = mesh  # tensor-parallel serving: params + pages sharded
        self.B = max(1, engine_cfg.max_batch_slots)
        self.max_len = model_cfg.max_seq_len
        # decode steps per dispatch: the host syncs once per block (a
        # dispatch, a blocking fetch and the host bookkeeping between
        # them), so a bigger block amortizes that fixed cost; overshoot past a slot's budget is
        # trimmed in _maybe_finish and its pages are pre-reserved in admit()
        self.decode_block = max(1, engine_cfg.decode_block)
        # speculation: each scan step verifies spec_k drafts + 1 bonus, so
        # fewer steps per dispatch keep tokens-per-block ~= decode_block
        self.spec_k = max(0, engine_cfg.speculate_k)
        self.decode_steps = (max(1, self.decode_block // (self.spec_k + 1))
                             if self.spec_k else self.decode_block)
        self.prefill_chunk = max(64, engine_cfg.prefill_chunk)
        # Defer the prefill first-token fetch into the decode block's
        # transfer (one fewer host RTT per admission wave).  Tradeoff: a
        # request finishing ON its first token (tok0==EOS, or max_new<=1)
        # burns one decode-block dispatch whose tokens are trimmed — rare
        # for summarization workloads.  LMRS_DEFER_TOK0=0 restores the
        # synchronous fetch for A/B measurement.
        self.defer_tok0 = env_bool("LMRS_DEFER_TOK0", True)
        ps = engine_cfg.page_size
        max_pages_per_slot = -(-self.max_len // ps)
        # Pool sizing: an explicit num_pages (> 1) is an HBM budget and is
        # honored, floored at one full-length sequence + the reserved null
        # page — under pressure, slots grow on demand and the youngest is
        # preempted (vLLM-style) instead of over-provisioning.  num_pages <= 1
        # asks for worst-case sizing (every slot can hold a full sequence;
        # preemption can then never trigger).
        if engine_cfg.num_pages > 1:
            num_pages = max(engine_cfg.num_pages, max_pages_per_slot + 1)
        else:
            num_pages = self.B * max_pages_per_slot + 1
        # int8 KV pages (EngineConfig.kv_quantize): per-(slot, kv head,
        # channel) scales fixed at prefill ride [L, B, K, hd] buffers
        # through the dispatch programs (ops/quant.py KV section)
        self._kv_quant = engine_cfg.kv_quantize
        if self._kv_quant and ps % 32:
            # int8 VMEM tiles are (32, 128): the RMW window machinery needs
            # 32-row-aligned windows that never straddle a page
            raise ValueError(f"kv_quantize=int8 needs page_size % 32 == 0 "
                             f"(got {ps})")
        # A latent cache (models/latent.py) is one pool with no kv-head
        # axis: what is not built for it is refused here, by name
        self._latent = bool(model_cfg.kv_lora_rank)
        if self._latent:
            self._refuse_for_latent(engine_cfg, mesh)
        # routed models hand back the held experts' token counts from
        # every program (``_moe_take``)
        self._moe_on = bool(model_cfg.n_routed_experts)
        self._moe_pending: list[tuple[str, tuple, object]] = []
        # A windowed stack (models/windowed.py) keeps two kinds of cache
        # layer in one pool, a window layer's share a ring a slot: what is
        # not built for that is refused here too
        self._window = int(model_cfg.sliding_window)
        if self._window:
            self._refuse_for_window(engine_cfg, mesh, self.max_len)
        self.cache = PagedKVCache(model_cfg, num_pages, ps, max_pages_per_slot,
                                  mesh=mesh,
                                  kv_dtype="int8" if self._kv_quant else None,
                                  slots=self.B)
        if self._kv_quant:
            sshape = (model_cfg.cache_layers, self.B, model_cfg.n_kv_heads,
                      model_cfg.hd)
            self.kscale = jnp.ones(sshape, jnp.float32)
            self.vscale = jnp.ones(sshape, jnp.float32)
        else:
            self.kscale = self.vscale = None
        # what a decode step reads, from the tree and pool held here: every
        # decode and spec record's ``hbm_bytes`` and the live attribution
        self._bytes = StepBytes.held(model_cfg, params, self.cache.k,
                                     self.cache.v)
        # LMRS_FORCE_KERNELS=interpret: run the Pallas kernels in interpret
        # mode regardless of platform — the CPU-mesh test path for the
        # shard_map-wrapped kernels (tests can't see a real TPU)
        self._interpret = (env_str("LMRS_FORCE_KERNELS").lower()
                           == "interpret")
        self._use_ragged = self._pick_kernel()
        # Multi-row decode page walk (ops/paged_attention.py): G batch rows
        # per ragged-decode program, sharing one DMA pipeline — amortizes
        # the per-row program fixed cost that dominated the 8B decode
        # intercept (docs/PERF.md r5).  Dispatches permute rows through a
        # host-side length-balanced assignment (balanced_row_order) so a
        # straggler row cannot serialize its group.  LMRS_MULTIROW=0 is
        # the kill switch (exact per-row grid + unpermuted dispatch, the
        # LMRS_PACK_PREFILL A/B convention).
        self._row_group = 1
        if env_bool("LMRS_MULTIROW", True):
            self._row_group = max(1, min(engine_cfg.decode_row_group, self.B))
        # flash prefill: same tp-only-mesh limit as the ragged gate (under a
        # mesh the kernel runs via shard_map over the tp head axis)
        self._use_flash = self._tp_only_mesh()
        # Packed prefill: concatenate same-wave fresh prompts into one [1, S]
        # row with segment-id masking — the dense matmuls (QKV/FFN/head) then
        # run on real tokens only instead of ~pow2-bucket padding per prompt
        # (measured ~43% padded q rows at the bench shape).  LMRS_PACK_PREFILL=0
        # restores per-prompt prefill for A/B measurement.
        self._pack_prefill = (env_bool("LMRS_PACK_PREFILL", True)
                              and not self._latent and not self._window)
        # int8 KV composes with packing since r4 (VERDICT r3 item 3): the
        # packed program computes per-SEGMENT scales and scatters them into
        # each segment's slot row — no gate needed
        # Serving-side context parallelism (SURVEY.md §5.7 tier b): under an
        # sp>1 mesh, LONG fresh prefills run cache-aware ring attention —
        # the sequence shards over sp, K/V still scatter into the page pool.
        # Short prompts (< _ring_min) keep the packed/flash path: at those
        # lengths ring hops buy no memory and cost ppermute latency.
        # Chunked (window) prefill cannot ride the ring (the window K/V is
        # pool-side, not sequence-sharded), so under sp the whole prompt
        # prefills in ONE ring dispatch: ring replaces chunking as the
        # long-prompt strategy.
        self._sp = 1 if mesh is None else mesh.shape.get("sp", 1)
        self._use_ring = self._sp > 1
        if self._kv_quant and self._use_ring:
            raise ValueError(
                "kv_quantize=int8 does not support ring (sp) prefill yet: "
                "scales are per-slot and ring writes are sequence-sharded")
        # kv_quantize=int8 composes with speculative decoding since r5:
        # the multi-token verify kernel carries the same per-channel
        # dequant folds as the single-token fused kernel (q-prescale /
        # accumulator-postscale are row-count-agnostic) and its RMW
        # quantizes draft rows with the slot's frozen scales
        # (ops/paged_attention.paged_decode_pallas_multi).
        self._ring_min = 1024
        # Fail fast at construction: ring buckets are rounded UP to a
        # multiple of sp at dispatch, which stays <= max_len only when
        # max_len itself divides.  Without this check a long chunk would
        # have to fall back to fully-materialized attention — on exactly
        # the configs ring exists for, that is the OOM path (VERDICT r2
        # weak #6: impossible by construction, not by coincidence).
        if self._use_ring and self.max_len % self._sp:
            raise ValueError(
                f"max_seq_len={self.max_len} is not divisible by sp="
                f"{self._sp}; ring prefill shards the sequence over sp — "
                "pick a max_seq_len that divides (pow2 lengths with pow2 "
                "sp always do)")
        if self._use_ring and self.prefill_chunk < self.max_len:
            logger.info("sp=%d mesh: chunked prefill disabled in favor of "
                        "one-dispatch ring prefill", self._sp)
            self.prefill_chunk = self.max_len
        # Shared-prefix KV cache (engine/prefix_cache.py): completed prompt
        # prefixes stay in the pool as ref-counted pages keyed by a radix
        # tree; admission clones the matched page-table prefix and enters
        # the chunked-prefill path at the match boundary.  LMRS_PREFIX_CACHE=0
        # is the A/B kill switch (same convention as LMRS_PACK_PREFILL).
        pc_on = (engine_cfg.prefix_cache
                 and env_bool("LMRS_PREFIX_CACHE", True))
        if pc_on and self._kv_quant:
            # int8 KV scales are per-SLOT, frozen at prefill: a hit slot
            # would dequantize donor-quantized pages with its own scales
            logger.info("prefix cache disabled: incompatible with int8 KV "
                        "(per-slot scales cannot cover donor pages)")
            pc_on = False
        if pc_on and self._use_ring:
            # cache hits enter the windowed-continuation prefill, which
            # cannot ride the ring (window K/V is pool-side, not
            # sequence-sharded)
            logger.info("prefix cache disabled under sp>1 mesh")
            pc_on = False
        # SARATHI-style mixed batches (config.EngineConfig.mixed_batch):
        # while any slot is mid-prefill AND any slot is decoding, each
        # step dispatches ONE ragged-span batch — every live decode row
        # carries its span (one token, or a verify span under
        # speculation), one prefilling slot carries a prompt slice clipped
        # to `mixed_token_budget - decode_tokens`.  Decode cadence never
        # pauses for an admission and prefill rides the decode step's
        # spare FLOPs.  LMRS_MIXED=0 is the kill switch (exact alternating
        # dispatch, the LMRS_PACK_PREFILL A/B convention).  Gated off
        # under sp>1 meshes — ring prefill replaced chunking, so there is
        # no prompt slice to piggyback — and over a latent cache.
        # Ragged span dispatch (ISSUE 16): ONE kernel family where every
        # dispatch is a list of (row, query-span) pairs — decode is
        # q_len=1 rows, verify q_len=k+1 rows, a mixed step decode rows
        # plus one prefill-slice row, continuation chunks long-span rows.
        # Compile buckets collapse to (pow2 total-query-tokens, pow2 page
        # window).  int8 KV mixes (per-row frozen scales ride the span
        # descriptor — a fresh-start slice owns its slot's scales exactly
        # like a fresh prefill) and so does speculation (decode rows carry
        # verify spans in-graph).  The span program serves every K/V
        # cache; a latent cache has no span program.
        if self._latent:
            logger.info("latent KV cache: packed prefill, mixed steps and "
                        "the span program are off (fresh prefill, chunked "
                        "continuation and decode blocks serve it)")
        if self._window:
            logger.info("window KV cache: packed prefill, mixed steps and "
                        "the span program are off (a packed row and a span "
                        "write every layer's pages of a sequence; a window "
                        "layer keeps a ring): fresh prefill and decode "
                        "blocks serve it")
        self._rpa_fns: dict[tuple, object] = {}
        self._mixed = (engine_cfg.mixed_batch and env_bool("LMRS_MIXED", True)
                       and not self._use_ring and not self._latent
                       and not self._window)
        self.mixed_token_budget = max(32, engine_cfg.mixed_token_budget)
        # Tree speculation on the span family (ISSUE 19): the linear draft
        # becomes LMRS_SPEC_TREE_WIDTH root-branching chains drafted
        # in-graph from the device history buffer and verified in ONE
        # ("rpa_spec", tpb, w) span dispatch whose causal mask follows
        # parent pointers (ancestor bitmasks, ragged_spans_xla).  Requires
        # the span dispatch + mixed routing (a token tree IS a span);
        # LMRS_SPEC_TREE=0 restores the linear spec path byte-for-byte
        # and speculate_k=0 keeps everything inert.
        self._spec_width = env_int("LMRS_SPEC_TREE_WIDTH", 2, lo=1, hi=8)
        # ancestor bitmasks are int32 over span-local offsets: the span is
        # [heal (<= depth), cur, width x depth], so clamp width until
        # 1 + depth*(width+1) fits in 32 bits; a depth that cannot fit
        # even one chain falls back to linear speculation
        while (self._spec_width > 1
               and 1 + self.spec_k * (self._spec_width + 1) > 32):
            self._spec_width -= 1
        self._spec_tree = (bool(self.spec_k) and self._mixed
                           and 1 + self.spec_k * (self._spec_width + 1) <= 32
                           and env_bool("LMRS_SPEC_TREE", True))
        # adaptive per-request depth: a windowed acceptance EMA per slot
        # ramps chain depth up on accept streaks and down to off on
        # acceptance collapse or page pressure (LMRS_SPEC_ADAPTIVE=0
        # pins every row at full depth)
        self._spec_adaptive = (self._spec_tree
                               and env_bool("LMRS_SPEC_ADAPTIVE", True))
        # per-heal-length (pos_off, ancestor-bitmask) span templates —
        # host-side operand build is a dict lookup + two copies per row
        self._spec_tmpl: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        # prefix cache constructed AFTER the metrics registry below (the
        # host-RAM spill tier feeds registry instruments); _pc_on carries
        # the gate decision down
        self._prefix_cache: PrefixCache | None = None
        self._pc_on = pc_on
        # Host-RAM KV spill tier (engine/host_kv.py): LMRS_HOST_KV=0 /
        # host_kv=False restores evict-means-gone byte-for-byte;
        # LMRS_HOST_KV_SYNC=1 blocks each prefetch scatter (A/B fallback
        # for the default async overlap).
        self._host_kv_sync = env_bool("LMRS_HOST_KV_SYNC", False)
        # Published radix summary (prefix-aware fleet routing,
        # docs/SERVING.md): distinct request preambles seen by this
        # engine, keyed by api.preamble_key — the router fetches
        # ``prefix_summary()`` through /healthz and routes
        # sticky-by-expected-prefix-hit.  Written by the scheduler thread
        # (_note_preamble); read by HTTP handler threads through the
        # guarded, memoized prefix_summary() snapshot.
        self._preambles: dict[str, dict] = {}
        self._preamble_tick = 0
        self._summary_memo: tuple[float, list] | None = None
        self._key = jax.random.PRNGKey(engine_cfg.seed + 17)
        # Request abort (VERDICT r3 item 4): ids land here from any thread
        # (set.add is atomic under the GIL — the HTTP server cancels from a
        # handler thread while run() owns the scheduling loop) and are
        # swept at the next block boundary: the slot's pages free
        # immediately instead of decoding an abandoned request to
        # max_tokens.  The reference got this for free from asyncio — a
        # dropped connection cancelled the task (llm_executor.py:290-296);
        # a continuous-batching engine must build it.
        self._cancelled: set[int] = set()
        self._prefill_fns: dict[int, object] = {}
        self._prefill_window_fns: dict[tuple[int, int], object] = {}
        self._packed_prefill_fns: dict[int, object] = {}
        self._decode_fns: dict[int, object] = {}
        self._ran_ok: set = set()  # fn-cache keys that have executed once
        self._spec_buf = None  # device token-history buffer (speculation)
        # rows whose device history row went stale during mixed steps
        # (decode advanced outside the spec scan): re-seeded LAZILY at
        # the next spec block, once per row per mixed window — an eager
        # per-step seed would be O(B*max_len) host uploads per token
        self._spec_stale: set[int] = set()
        self._on_tokens = None  # per-block streaming callback (run()-scoped)
        self._streamed: dict[int, str] = {}
        # Engine metrics (SURVEY.md §5.5: tokens/s, occupancy, HBM analog),
        # migrated from the former raw dict onto a typed registry
        # (obs/metrics.py): counters/gauges keep the old dict's exact key
        # semantics via the ``metrics`` snapshot property, histograms
        # replace the former unbounded-ish _ttft/_block_gaps sample lists
        # (same bounded reservoir, plus fixed buckets for Prometheus).
        self.registry = MetricsRegistry()
        c, g, h = (self.registry.counter, self.registry.gauge,
                   self.registry.histogram)
        self._c_prefill_tokens = c("lmrs_prefill_tokens_total",
                                   "prompt tokens prefilled", "tokens")
        self._c_decode_tokens = c("lmrs_decode_tokens_total",
                                  "tokens generated by decode blocks",
                                  "tokens")
        self._c_decode_dispatches = c("lmrs_decode_dispatches_total",
                                      "decode-block dispatches issued")
        self._c_run_seconds = c("lmrs_run_seconds_total",
                                "scheduler wall-clock inside run()",
                                "seconds")
        # time inside blocking device fetches (run() path only): the device
        # is busy while the host waits here, so
        # run_seconds - blocked_seconds is the host-side share — bookkeeping
        # the device sits idle for (r5: ~17% of 8B map wall; the
        # attribution number for any overlap lever)
        self._c_blocked_seconds = c("lmrs_blocked_seconds_total",
                                    "host time blocked in device fetches",
                                    "seconds")
        self._c_spec_accepted = c("lmrs_spec_accepted_tokens_total",
                                  "draft tokens accepted (speculation)",
                                  "tokens")
        self._c_preemptions = c("lmrs_preemptions_total",
                                "slots evicted to the queue under page "
                                "pressure")
        self._c_stalls = c("lmrs_stalls_total",
                           "dispatches a slot sat out waiting for pages")
        self._c_cancelled = c("lmrs_cancelled_total",
                              "requests aborted via cancel()")
        # deadline lifecycle (GenerationRequest.deadline_s): in-flight
        # expiries swept at block boundaries, admission-time sheds, and the
        # slack requests arrive with (how close to the line the fleet runs)
        self._c_deadline = c("lmrs_deadline_exceeded_total",
                             "requests expired in flight "
                             "(finish_reason=deadline)")
        self._c_shed = c("lmrs_requests_shed_total",
                         "requests shed at admission "
                         "(finish_reason=shed)")
        self._h_deadline_remaining = h("lmrs_deadline_remaining_seconds",
                                       help="remaining deadline budget at "
                                            "admission", unit="seconds")
        # prefix-cache counters (present even when the cache is off, so
        # bench windowing can always delta them): admissions that queried
        # the radix tree, admissions that matched, and prompt tokens whose
        # prefill was skipped via cached pages
        self._c_prefix_queries = c("lmrs_prefix_queries_total",
                                   "admissions that queried the prefix tree")
        self._c_prefix_hits = c("lmrs_prefix_hits_total",
                                "admissions that matched a cached prefix")
        self._c_prefix_tokens = c("lmrs_prefix_tokens_reused_total",
                                  "prompt tokens served from cached pages",
                                  "tokens")
        # host-RAM spill tier (engine/host_kv.py): device-evicted cache
        # pages captured host-side and prefetched back on later matches —
        # present even when the tier is off, so bench windowing can
        # always delta them (same convention as the prefix counters)
        self._c_spill_pages = c("lmrs_prefix_spill_pages_total",
                                "prefix-cache pages captured into the "
                                "host-RAM spill tier at eviction", "pages")
        self._c_spill_dropped = c("lmrs_prefix_spill_dropped_pages_total",
                                  "spilled pages dropped from the host "
                                  "pool (budget LRU / subtree drops)",
                                  "pages")
        self._h_spill_capture = h("lmrs_prefix_spill_capture_seconds",
                                  help="device→host capture of one "
                                       "spilled node's pages",
                                  unit="seconds")
        self._c_prefetch_pages = c("lmrs_prefix_prefetch_pages_total",
                                   "spilled pages restored into device "
                                   "pages on a radix match", "pages")
        self._c_prefetch_tokens = c("lmrs_prefix_tokens_prefetched_total",
                                    "prompt tokens restored from the host "
                                    "tier instead of re-prefilled",
                                    "tokens")
        self._c_spilled_hits = c("lmrs_prefix_spilled_hits_total",
                                 "admissions whose prefix match extended "
                                 "into spilled segments")
        self._h_prefetch = h("lmrs_prefix_prefetch_seconds",
                             help="host→device prefetch issue per "
                                  "admission (async unless "
                                  "LMRS_HOST_KV_SYNC)", unit="seconds")
        self._g_host_pool = g("lmrs_prefix_host_pool_bytes",
                              "bytes currently held by the host-RAM KV "
                              "spill pool", "bytes")
        # disk spill tier (host_kv.DiskKVPool, ROADMAP item 4) — present
        # even when the tier is off, same delta-ability convention
        self._c_disk_demoted = c("lmrs_kv_disk_demoted_pages_total",
                                 "spilled pages demoted host→disk under "
                                 "host-pool budget pressure", "pages")
        self._c_disk_promoted = c("lmrs_kv_disk_promoted_pages_total",
                                  "disk-tier pages promoted back via the "
                                  "prefetch path (disk→host→device)",
                                  "pages")
        self._c_disk_dropped = c("lmrs_kv_disk_dropped_pages_total",
                                 "disk-tier pages dropped (disk budget "
                                 "LRU / subtree drops)", "pages")
        self._c_disk_read_fail = c("lmrs_kv_disk_read_failures_total",
                                   "disk spill reads that failed "
                                   "(missing/torn/corrupt file) and "
                                   "degraded to re-prefill")
        self._g_disk_bytes = g("lmrs_kv_disk_bytes",
                               "bytes currently held by the disk spill "
                               "pool", "bytes")
        # cross-host KV migration (docs/SERVING.md KV fabric): page sets
        # exported to / imported from sibling hosts through /v1/kv
        self._c_migrate_exports = c("lmrs_kv_migrate_exports_total",
                                    "warm page sets exported for "
                                    "cross-host migration")
        self._c_migrate_imports = c("lmrs_kv_migrate_imports_total",
                                    "migrated page sets imported into "
                                    "the prefix cache")
        self._c_migrate_tokens = c("lmrs_kv_migrate_tokens_total",
                                   "prompt tokens installed warm via "
                                   "cross-host migration", "tokens")
        if self._pc_on:
            pool = None
            cb = None
            pb = 0
            if engine_cfg.host_kv and engine_cfg.host_kv_gb > 0:
                from lmrs_tpu.engine.host_kv import DiskKVPool, HostKVPool

                disk = None
                if engine_cfg.kv_disk and engine_cfg.kv_disk_gb > 0:
                    disk = DiskKVPool(int(engine_cfg.kv_disk_gb * 2**30),
                                      engine_cfg.kv_disk_dir)
                pool = HostKVPool(int(engine_cfg.host_kv_gb * 2**30),
                                  disk=disk)
                cb = self.cache.export_pages
                pb = self.cache.page_payload_bytes()
            self._prefix_cache = PrefixCache(
                self.cache.allocator, ps,
                max_pages=engine_cfg.prefix_cache_max_pages,
                spill_pool=pool, capture_cb=cb, page_bytes=pb,
                metrics={"spill_pages": self._c_spill_pages,
                         "spill_dropped": self._c_spill_dropped,
                         "spill_capture_s": self._h_spill_capture,
                         "pool_bytes": self._g_host_pool,
                         "disk_demoted": self._c_disk_demoted,
                         "disk_promoted": self._c_disk_promoted,
                         "disk_dropped": self._c_disk_dropped,
                         "disk_read_fail": self._c_disk_read_fail,
                         "disk_bytes": self._g_disk_bytes})
            self.cache.reclaim_cb = self._prefix_cache.evict
        # mixed-batch dispatch: real tokens (decode + piggybacked prefill
        # slice) over the step's token budget, and the prompt tokens whose
        # prefill rode a decode step instead of a dedicated prefill wave
        self._h_mixed_fill = h("lmrs_mixed_batch_fill_ratio",
                               buckets=RATIO_BUCKETS,
                               help="real tokens over mixed_token_budget "
                                    "per mixed fused dispatch")
        self._c_piggybacked = c("lmrs_prefill_tokens_piggybacked_total",
                                "prompt tokens prefilled inside mixed "
                                "decode steps", "tokens")
        # ragged span dispatch: real query tokens per RPA dispatch (the
        # padding complement of the pow2 total-token bucket), and the
        # headline compile-zoo number — distinct (bucket, window) program
        # shapes built so far
        self._h_rpa_span = h("lmrs_rpa_span_tokens",
                             buckets=POW2_TOKEN_BUCKETS,
                             help="real query-span tokens per ragged span "
                                  "dispatch", unit="tokens")
        self._c_rpa_shapes = c("lmrs_rpa_compile_shapes_total",
                               "distinct ragged-span program shapes "
                               "compiled", "shapes")
        # tree speculation (ISSUE 19): drafted tree size per row, accepted
        # root-to-leaf depth per row, and the tree-span dispatch count —
        # present even when tree spec is off, so bench windowing can
        # always delta them (the prefix-counter convention)
        self._h_spec_nodes = h("lmrs_spec_tree_nodes",
                               help="drafted tree nodes per decode row "
                                    "per tree-spec dispatch", unit="nodes")
        self._h_spec_depth = h("lmrs_spec_accept_depth",
                               help="accepted draft tokens per decode row "
                                    "per tree-spec dispatch", unit="tokens")
        self._c_spec_tree_disp = c("lmrs_spec_tree_dispatches_total",
                                   "tree-speculative span dispatches")
        self._g_peak_pages = g("lmrs_peak_pages_in_use",
                               "max KV pages simultaneously allocated",
                               "pages")
        self._g_peak_slots = g("lmrs_peak_active_slots",
                               "max simultaneously-occupied batch slots")
        # TTFT: scheduler-enqueue -> first host-visible token per fresh
        # request; block gap: seconds between consecutive decode dispatches
        # within a run — the cadence a streaming client receives delta
        # batches at (VERDICT r4 item 5: always on, never script-only)
        self._h_ttft = h("lmrs_ttft_seconds",
                         help="time to first token (engine-side)",
                         unit="seconds")
        self._h_block_gap = h("lmrs_decode_block_gap_seconds",
                              help="gap between consecutive decode "
                                   "dispatches", unit="seconds")
        self._h_queue_wait = h("lmrs_queue_wait_seconds",
                               help="enqueue -> slot admission wait",
                               unit="seconds")
        self._h_prefill_batch = h("lmrs_prefill_batch_tokens",
                                  buckets=POW2_TOKEN_BUCKETS,
                                  help="real prompt tokens per prefill "
                                       "dispatch", unit="tokens")
        self._h_occupancy = h("lmrs_decode_occupancy_ratio",
                              buckets=RATIO_BUCKETS,
                              help="fraction of batch slots live per "
                                   "decode dispatch")
        # multi-row kernel group occupancy: live rows over the dispatched
        # group capacity (ceil(rows/G)*G) — the padding waste the
        # row-group layout introduces; only observed when grouping is on
        self._h_group_occupancy = h("lmrs_decode_group_occupancy_ratio",
                                    buckets=RATIO_BUCKETS,
                                    help="live rows over row-group "
                                         "capacity per decode dispatch")
        self._tr = get_tracer()  # refreshed at each run()
        # Deadline bookkeeping: fastest TTFT ever observed on this engine —
        # the OPTIMISTIC admission estimate (shed only what is provably
        # unmeetable; the mean would embed multi-second first-compile
        # samples and shed healthy requests).  _any_deadline gates the
        # per-iteration expiry sweep so deadline-free workloads pay zero.
        self._ttft_min = float("inf")
        self._any_deadline = False
        # auditor bookkeeping: result records that OVERWROTE an existing
        # result (every submitted id must terminate exactly once)
        self._audit_double_finish = 0
        # Disaggregated handoff (docs/SERVING.md): sequences whose pages
        # are PINNED for export — prefill finished, first token sampled,
        # payload captured host-side, waiting for the decode pod's ack.
        # rid -> {seq, payload, deadline_t, t_pinned}.  The lock covers
        # the dict AND the run-liveness flag: export/release run on HTTP
        # handler threads while the scheduler loop pins and sweeps.  Like
        # cancel(), off-thread releases never touch the allocator while a
        # run is live — a released record is parked on _release_deferred
        # and its pages freed by the scheduler thread at the next block
        # boundary (the allocator and prefix-cache refcounts have no
        # internal synchronization).  With no run live the free happens
        # inline, under the lock, so a starting run (which flips
        # _run_live under the same lock before its first allocation)
        # can never overlap it.  audit() accounts both classes as
        # pinned-for-export holders.
        self._pinned: dict[int, dict] = {}  # guarded-by: _pinned_lock
        # guarded-by: _pinned_lock
        self._release_deferred: list[tuple[int, dict, bool]] = []
        self._run_live = False  # guarded-by: _pinned_lock
        self._pinned_lock = threading.Lock()
        self._c_handoff_exports = c("lmrs_handoff_exports_total",
                                    "requests pinned for prefill→decode "
                                    "handoff")
        self._c_handoff_imports = c("lmrs_handoff_imports_total",
                                    "sequences imported from a handoff "
                                    "payload")
        self._c_handoff_orphaned = c("lmrs_handoff_orphaned_pages_total",
                                     "pinned pages reclaimed by the "
                                     "orphan sweep (ticket never acked)",
                                     "pages")
        self._g_pinned_pages = g("lmrs_handoff_pinned_pages",
                                 "KV pages currently pinned for export",
                                 "pages")
        self._h_handoff_capture = h("lmrs_handoff_capture_seconds",
                                    help="pin-time host capture of an "
                                         "exported page set",
                                    unit="seconds")
        self._h_handoff_import = h("lmrs_handoff_import_seconds",
                                   help="device scatter of an imported "
                                        "page set at admission",
                                   unit="seconds")
        # Live performance attribution (obs/perf.py): per-dispatch
        # FLOPs/bytes from the roofline model, measured dispatch walls
        # (minus host RTT) -> lmrs_prefill_mfu_ratio /
        # lmrs_decode_hbm_util_ratio / lmrs_step_gap_ms.  Pending-flops
        # bookkeeping: prefill dispatches issued this iteration are
        # sequenced on device before the decode block that fetches their
        # tok0s, so their model FLOPs are attributed to that block's wall.
        self._perf = DispatchAttribution(model_cfg, self.registry)
        self._attr_pending_flops = 0.0
        self._attr_prefill_cold = False  # a compiling shape in the wave
        self._attr_last_gb = 0.0  # last block's model bytes (span arg)
        # Request-cost ledger (obs/ledger.py): every dispatch wall —
        # already phase-split by the attribution above — apportions one
        # level further down, to the live rows, accumulating an honest
        # per-request device-time bill with a conservation invariant in
        # audit().  LMRS_COST_LEDGER=0 turns every note into a no-op
        # (pure host bookkeeping; outputs byte-identical either way).
        self._cost = CostLedger(self.registry)
        # Fair-share QoS (fleet/qos.py): admission picks by (class rank,
        # windowed device-seconds / weight, FIFO) and preemption
        # victimizes over-quota bulk work first.  The ledger's per-
        # dispatch apportionment feeds the policy's sliding window (the
        # observer fires outside the ledger lock).  LMRS_QOS=0 leaves
        # _qos None and every hook below is a single is-None branch —
        # byte-for-byte today's FIFO admission and youngest-victim rule.
        self._qos = maybe_qos(self.registry)
        if self._qos is not None:
            self._cost.observer = self._qos.note_usage
        # per-row prefill work issued since the last consumption —
        # (req, tokens, flops) mirrors of _attr_pending_flops, consumed
        # by whichever dispatch fetch charges the wave's wall
        self._cost_pending_prefill: list[tuple] = []
        # (wall_s, decode_cost_s, prefill_cost_s, prefill_rows) of the
        # last decode/spec dispatch, consumed by run()'s emitted loop
        # where the per-row token counts become known
        self._cost_step: tuple | None = None
        # SLO engine (obs/slo.py): burn-rate health states over the
        # stream's own TTFT / block-gap / outcome samples; /healthz and
        # the router's placement penalty read slo_report().
        self._slo = SLOEngine(self.registry, metrics_cb=lambda: self.metrics)
        # Step-anatomy profiler (obs/anatomy.py): every run() iteration is
        # split into named host segments via _an.seg(...), conservation-
        # audited (wall == segments + residual) in audit(), plus bucket
        # economics for the ragged-span pow2 family.  LMRS_ANATOMY=0
        # swaps in the shared null object — no metrics registered, every
        # call a no-op, outputs and wire byte-identical.
        self._an = maybe_anatomy(self.registry,
                                 metrics_cb=lambda: self.metrics)
        if self._moe_on:
            self._an.has_moe = True  # the moe_* counters, from the start
        if self._window:
            self._an.has_window = True  # the kv_pages_* / flash_* counters
            full, win = self.cache.kind_pages()
            g = self.registry.gauge
            g("lmrs_cache_pages_full", "pool pages the full attention "
              "layers hold (null pages left out)").set(full)
            g("lmrs_cache_pages_window", "pool pages the window attention "
              "layers hold: slots x ring x window layers").set(win)
        # LMRS_PROFILE_ON_SLOW_STEP: a decode block slower than the
        # threshold (warm shapes only) triggers ONE jax.profiler capture
        # per process into LMRS_PROFILE_DIR — the "why was that step
        # slow" hook that needs no redeploy
        self._slow_step_fired = False
        # Hang survival (engine/watchdog.py): the dispatch loop stamps a
        # monotonic heartbeat each iteration; JaxEngine's WatchdogRunner
        # watches it and declares a wedge when no progress lands within
        # the threshold.  LMRS_WATCHDOG=0 removes the watchdog entirely —
        # run() then executes inline on the caller thread, byte-for-byte
        # today's dispatch path (the acceptance A/B).
        self.watchdog = None
        if env_bool("LMRS_WATCHDOG", True):
            from lmrs_tpu.engine.watchdog import DispatchWatchdog

            self.watchdog = DispatchWatchdog()
        self._c_watchdog_fires = c("lmrs_watchdog_fires_total",
                                   "dispatch wedges declared by the "
                                   "watchdog (run abandoned, engine "
                                   "degraded fail-fast)")
        self._c_wedged = c("lmrs_wedged_requests_total",
                           "requests terminated finish_reason=\"wedged\" "
                           "by the watchdog sweep")

    @property
    def metrics(self) -> dict:
        """Raw cumulative metric values under the pre-registry key names —
        the read-only snapshot tests and bench windowing delta (the former
        mutable dict's exact keys and value types)."""
        return {
            "prefill_tokens": int(self._c_prefill_tokens.value),
            "decode_tokens": int(self._c_decode_tokens.value),
            "decode_dispatches": int(self._c_decode_dispatches.value),
            "occupancy_sum": self._h_occupancy.sum,
            "peak_pages_in_use": int(self._g_peak_pages.value),
            "run_seconds": self._c_run_seconds.value,
            "spec_accepted_tokens": int(self._c_spec_accepted.value),
            "preemptions": int(self._c_preemptions.value),
            "stalls": int(self._c_stalls.value),
            "peak_active_slots": int(self._g_peak_slots.value),
            "cancelled": int(self._c_cancelled.value),
            "deadline_exceeded": int(self._c_deadline.value),
            "shed": int(self._c_shed.value),
            "blocked_seconds": self._c_blocked_seconds.value,
            "prefix_queries": int(self._c_prefix_queries.value),
            "prefix_hits": int(self._c_prefix_hits.value),
            "prefix_tokens_reused": int(self._c_prefix_tokens.value),
            "prefix_spilled_hits": int(self._c_spilled_hits.value),
            "prefix_tokens_prefetched": int(self._c_prefetch_tokens.value),
            "prefix_spill_pages": int(self._c_spill_pages.value),
            "prefix_prefetch_pages": int(self._c_prefetch_pages.value),
            "group_occupancy_sum": self._h_group_occupancy.sum,
            "group_dispatches": int(self._h_group_occupancy.count),
            "handoff_exports": int(self._c_handoff_exports.value),
            "handoff_imports": int(self._c_handoff_imports.value),
            "handoff_orphaned_pages": int(self._c_handoff_orphaned.value),
            "handoff_pinned_pages": int(self._g_pinned_pages.value),
            "mixed_dispatches": int(self._h_mixed_fill.count),
            "mixed_fill_sum": self._h_mixed_fill.sum,
            "prefill_tokens_piggybacked": int(self._c_piggybacked.value),
            "rpa_dispatches": int(self._h_rpa_span.count),
            "rpa_span_tokens": self._h_rpa_span.sum,
            "rpa_compile_shapes": int(self._c_rpa_shapes.value),
            "spec_tree_dispatches": int(self._c_spec_tree_disp.value),
            "spec_tree_nodes_sum": self._h_spec_nodes.sum,
            "spec_tree_rows": int(self._h_spec_nodes.count),
            "spec_accept_depth_sum": self._h_spec_depth.sum,
            "watchdog_fires": int(self._c_watchdog_fires.value),
            "wedged_requests": int(self._c_wedged.value),
            # sums of the anatomy's dispatch records (obs/anatomy.py):
            # prefill_dispatches / _rows / _row_slots / _query_tokens /
            # _token_slots / _page_writes / _seq_rung_dispatches,
            # cold_dispatches / cold_seconds; no keys under LMRS_ANATOMY=0
            **self._an.counters(),
            # the pool's two shares (gauges; a windowed model only)
            **(dict(zip(("cache_pages_full", "cache_pages_window"),
                        self.cache.kind_pages())) if self._window else {}),
        }

    def metrics_registry(self) -> MetricsRegistry:
        """Engine-protocol optional hook: the registry behind
        ``metrics_report()``, for Prometheus exposition (serving/server.py
        content-negotiates ``GET /metrics`` over it)."""
        return self.registry

    def perf_attribution_report(self) -> dict:
        """Live per-phase roofline attribution (obs/perf.py) — the
        ``perf_attribution`` block of metrics_report() and bench detail."""
        return self._perf.report()

    def _tid(self, req: GenerationRequest) -> int:
        """The request's span-track id: keyed on its distributed trace id
        when it carries one (one causal chain fleet-wide, stable across
        pods and run epochs) — else the legacy per-run request-id track.
        Call only under an ``if self._tr:`` guard."""
        if req.trace_id:
            return self._tr.track_for(req.trace_id)
        return req_tid(req.request_id)

    def _consume_prefill_attr(self) -> tuple[float, bool]:
        """Take (and reset) the pending prefill-FLOPs attribution: the
        model FLOPs of every prefill dispatch issued since the last
        consumption, plus whether any of them was a compiling (cold)
        shape — cold waves never produce MFU samples."""
        flops, cold = self._attr_pending_flops, self._attr_prefill_cold
        self._attr_pending_flops = 0.0
        self._attr_prefill_cold = False
        return flops, cold

    def _consume_prefill_cost(self) -> list[tuple]:
        """Take (and reset) the per-row prefill cost rows mirroring
        _consume_prefill_attr — the ledger's row-level view of the same
        pending work."""
        rows, self._cost_pending_prefill = self._cost_pending_prefill, []
        return rows

    def _roofline_phase_costs(self, nbytes: float,
                              flops: float) -> tuple[float, float]:
        """(decode_cost_s, prefill_cost_s): each phase's own roofline
        time — the exact-split denominators the ledger apportions dispatch
        walls by (obs/perf.note_mixed_step's rule, one level down).
        (0, 0) on a device without known peaks: the ledger then splits by
        token counts."""
        spec = self._perf._spec()
        if spec is None:
            return 0.0, 0.0
        return (max(nbytes, 0.0) / spec.peak_hbm_bw,
                max(flops, 0.0) / spec.peak_flops)

    # ------------------------------------------------ cost / SLO surfaces

    def usage_report(self) -> dict:
        """Per-tenant cost rollups (the ``GET /v1/usage`` document)."""
        return self._cost.usage_report()

    def slo_report(self) -> dict:
        """Burn-rate SLO evaluation (the ``/healthz`` ``slo`` block)."""
        return self._slo.report()

    def qos_report(self) -> dict:
        """Fair-share window state (the ``GET /v1/usage`` ``qos`` block)."""
        if self._qos is None:
            return {"object": "qos", "enabled": False}
        return self._qos.report()

    def anatomy_report(self, before: dict | None = None) -> dict:
        """Step-anatomy decomposition + ragged bucket economics (the
        ``GET /v1/anatomy`` document and the ``anatomy`` block of
        metrics_report()/bench detail).  ``before`` is an
        ``anatomy_snapshot()`` window anchor; the RTT rides along so the
        report can flag a stale sample instead of letting it skew the
        dispatch/fetch split (obs/anatomy.py)."""
        return self._an.report(before, rtt=self._perf.rtt_sample())

    def anatomy_snapshot(self) -> dict:
        """Window anchor for ``anatomy_report(before=...)`` (bench /
        serving_latency delta their measurement window off this)."""
        return self._an.snapshot()

    def cost_finish(self, req: GenerationRequest, res: GenerationResult
                    ) -> None:
        """Finalize a request's ledger entry for a result synthesized
        OUTSIDE the scheduler loop (the watchdog's wedge sweep): attaches
        the usage bill and feeds the SLO outcome stream, same as
        _record_result does for loop-delivered results."""
        res.usage = self._cost.finish(req, res)
        self._slo.note_result(res.finish_reason, res.completion_tokens,
                              res.error)

    def _maybe_profile_slow_step(self, wall_s: float, warm: bool) -> None:
        """LMRS_PROFILE_ON_SLOW_STEP trigger: the first WARM decode block
        slower than the threshold starts one bounded jax.profiler capture
        (LMRS_PROFILE_DIR, default <tmp>/lmrs_profile) — once per
        process, so a persistently slow engine cannot profile forever."""
        if self._slow_step_fired:
            return
        from lmrs_tpu.obs.perf import (default_profile_dir,
                                       slow_step_threshold_s,
                                       start_profile_capture)

        thresh = slow_step_threshold_s()
        if not thresh or not warm or wall_s <= thresh:
            return
        self._slow_step_fired = True
        dur = env_float("LMRS_PROFILE_CAPTURE_S", 3.0, lo=0.1, hi=60.0)
        ok, msg = start_profile_capture(default_profile_dir(), dur)
        logger.warning("slow decode block (%.3fs > %.3fs threshold): "
                       "profiler capture %s (%s)", wall_s, thresh,
                       "started" if ok else "NOT started", msg)

    def _wd_grace_cold(self) -> None:
        """The next dispatch compiles a new shape: open the watchdog's
        one-shot compile grace window so a legitimate multi-second (or
        multi-minute) XLA compile can never read as a wedge.  Call sites
        are exactly the existing cold-shape checks (``_ran_ok``)."""
        if self.watchdog is not None:
            self.watchdog.grace_cold()

    def _note_ran_ok(self, key) -> None:
        """Mark a dispatch shape proven AND close the cold-compile grace
        window it opened: the compile is done, so the wedge detector
        re-arms immediately — a stall in the same iteration (or the next
        loop-top heartbeat) must still be caught."""
        self._ran_ok.add(key)
        if self.watchdog is not None:
            self.watchdog.grace_end()


    def _timed_get(self, x):
        """``jax.device_get`` with the blocking wait charged to the
        ``blocked_seconds`` metric (device-busy attribution; see the
        metric's init comment)."""
        t0 = time.time()
        out = jax.device_get(x)
        # clamped: counters refuse to decrease, and a backwards clock step
        # (NTP correction mid-fetch) must cost a sample, not the whole run
        self._c_blocked_seconds.inc(max(0.0, time.time() - t0))
        return out

    def metrics_report(self) -> dict:
        """Derived engine metrics, cumulative over every run() on this
        scheduler (the same lifetime semantics as the executor's token
        counters, llm_executor.py:86-90): throughput (tokens/s over
        scheduler wall-clock), mean decode batch occupancy (fraction of
        slots live per dispatch), and peak KV page utilization over the
        usable pool (the HBM-pressure analog)."""
        m = self.metrics
        secs = max(m["run_seconds"], 1e-9)
        return {
            "prefill_tokens": m["prefill_tokens"],
            "decode_tokens": m["decode_tokens"],
            "prefill_tokens_per_sec": round(m["prefill_tokens"] / secs, 1),
            "decode_tokens_per_sec": round(m["decode_tokens"] / secs, 1),
            "mean_decode_occupancy": round(
                m["occupancy_sum"] / max(m["decode_dispatches"], 1), 3),
            "peak_kv_page_utilization": round(
                m["peak_pages_in_use"] / (self.cache.num_pages - 1), 3),
            "scheduler_seconds": round(m["run_seconds"], 3),
            "blocked_seconds": round(m["blocked_seconds"], 3),
            "host_seconds": round(
                max(m["run_seconds"] - m["blocked_seconds"], 0.0), 3),
            "preemptions": m["preemptions"],
            "stalls": m["stalls"],
            "cancelled": m["cancelled"],
            "deadline_exceeded": m["deadline_exceeded"],
            "shed": m["shed"],
            "peak_active_slots": m["peak_active_slots"],
            "ttft_ms": self._h_ttft.percentile_report(),
            "decode_block_gap_ms": self._h_block_gap.percentile_report(),
            # Gap-scope label (docs/PERF.md "two block-gap numbers"):
            # gaps are sampled between consecutive decode dispatches
            # WITHIN each run().  On a steady serving stream that is the
            # per-block cadence a client sees; on a batch/bench workload
            # the same samples include whole admission/prefill waves
            # between decode dispatches (BENCH8B_r05's 7.65 s p50 is
            # wave-level queueing, NOT serving cadence — the capture's
            # 363 ms is).  Consumers must not compare across scopes.
            "decode_block_gap_scope": "within-run dispatch gaps "
                                      "(wave-level on batch workloads; "
                                      "steady-state only on serving "
                                      "captures)",
            "queue_wait_ms": self._h_queue_wait.percentile_report(),
            "mixed_batch": self._mixed_report(),
            "rpa": self._rpa_report(),
            "host_kv": self._host_kv_report(),
            "perf_attribution": self._perf.report(),
            "cost": self._cost.report(),
            "slo": self._slo.report(),
            # kill-switch shape contract: NO anatomy key at all under
            # LMRS_ANATOMY=0 — the pre-anatomy report is byte-identical
            **({"anatomy": self.anatomy_report()}
               if self._an.enabled else {}),
            # a windowed model only: the pool's two kinds of layer
            **({"window_cache": {
                "window": self._window,
                "ring_pages": self.cache.window["ring"],
                "full_layers": self.cache.window["n_full"],
                "window_layers": self.cache.window["n_win"],
                "cache_pages_full": m["cache_pages_full"],
                "cache_pages_window": m["cache_pages_window"]}}
               if self._window else {}),
            **({"spec_accepted_tokens": m["spec_accepted_tokens"]}
               if self.spec_k else {}),
            **({"spec_tree": self._spec_tree_report()}
               if self.spec_k else {}),
            **({"prefix_cache": self._prefix_cache_report()}
               if self._prefix_cache is not None else {}),
        }

    def _mixed_report(self) -> dict:
        """Mixed-batch block of metrics_report(): whether mixed dispatch is
        armed, how many fused steps ran, budget fill, and the prompt tokens
        that rode decode steps."""
        m = self.metrics
        disp = m["mixed_dispatches"]
        return {
            "enabled": self._mixed,
            "token_budget": self.mixed_token_budget,
            "dispatches": disp,
            "fill_ratio": (round(m["mixed_fill_sum"] / disp, 3)
                           if disp else 0.0),
            "prefill_tokens_piggybacked": m["prefill_tokens_piggybacked"],
        }

    def _rpa_report(self) -> dict:
        """Ragged-span block of metrics_report(): whether the cache has a
        span program (every K/V cache does), how many span dispatches
        ran, the real query tokens they carried, and the HEADLINE number
        — distinct compiled program shapes (the span family is (pow2
        tokens, pow2 window) only)."""
        m = self.metrics
        return {
            "enabled": not self._latent and not self._window,
            "dispatches": m["rpa_dispatches"],
            "span_tokens": int(m["rpa_span_tokens"]),
            "compile_shapes": m["rpa_compile_shapes"],
        }

    def _spec_tree_report(self) -> dict:
        """Tree-speculation block of metrics_report(): whether the tree
        path is armed, how many tree-span dispatches ran, mean drafted
        nodes and accepted depth per row, and accepted tokens per
        dispatched row."""
        m = self.metrics
        rows = m["spec_tree_rows"]
        return {
            "enabled": self._spec_tree,
            "width": self._spec_width,
            "adaptive": self._spec_adaptive,
            "dispatches": m["spec_tree_dispatches"],
            "mean_nodes": (round(m["spec_tree_nodes_sum"] / rows, 3)
                           if rows else 0.0),
            "mean_accept_depth": (round(m["spec_accept_depth_sum"] / rows, 3)
                                  if rows else 0.0),
            "accept_per_step": (round(m["spec_accepted_tokens"] / rows, 3)
                                if rows else 0.0),
        }

    def _prefix_cache_report(self) -> dict:
        """Prefix-cache block of metrics_report(): hit rate over admissions,
        tokens reused from cached pages (== prefill tokens saved — exactly
        the prompt tokens the scheduler never dispatched), and the cache's
        current/ cumulative page footprint."""
        m = self.metrics
        s = self._prefix_cache.stats()
        return {
            "hit_rate": round(m["prefix_hits"] / m["prefix_queries"], 3)
            if m["prefix_queries"] else 0.0,
            "hits": m["prefix_hits"],
            "queries": m["prefix_queries"],
            "tokens_reused": m["prefix_tokens_reused"],
            "prefill_tokens_saved": m["prefix_tokens_reused"],
            "spilled_hits": m["prefix_spilled_hits"],
            "tokens_prefetched": m["prefix_tokens_prefetched"],
            "cached_pages": s["cached_pages"],
            "evicted_pages": s["evicted_pages"],
        }

    def _host_kv_report(self) -> dict:
        """Host-RAM spill tier block of metrics_report(): whether the tier
        is armed, its budget and occupancy, and the spill/prefetch work
        counters."""
        pc = self._prefix_cache
        armed = pc is not None and pc.pool is not None
        m = self.metrics
        out = {
            "enabled": armed,
            "budget_gb": round(self.cfg.host_kv_gb, 3) if armed else 0.0,
            "spilled_hits": m["prefix_spilled_hits"],
            "tokens_prefetched": m["prefix_tokens_prefetched"],
            "spill_pages": m["prefix_spill_pages"],
            "prefetch_pages": m["prefix_prefetch_pages"],
        }
        if armed:
            out["spilled_pages_resident"] = pc.spilled_pages()
            out["pool_bytes"] = pc.pool.used_bytes
            out["pool_entries"] = len(pc.pool)
            out["dropped_pages_total"] = pc.pool.dropped_pages_total
            if pc.disk is not None:
                # disk-tier keys appear only when the tier is armed:
                # LMRS_KV_DISK=0 keeps this block byte-identical
                out["disk_pages_resident"] = pc.disk_pages()
                out.update(pc.disk.stats())
        return out

    def reset_latency_stats(self) -> None:
        """Drop accumulated TTFT / block-gap / queue-wait observations.
        Benchmarks call this after warmup so compile-time dispatch gaps
        (orders of magnitude above steady state) don't pollute the
        percentiles — or the Prometheus buckets."""
        self._h_ttft.reset()
        self._h_block_gap.reset()
        self._h_queue_wait.reset()
        # live-attribution distributions ride the same warmup isolation
        # (the totals counters stay cumulative, like every counter here)
        self._perf.h_mfu.reset()
        self._perf.h_hbm.reset()
        self._perf.h_gap.reset()

    def _pick_kernel(self) -> bool:
        from lmrs_tpu.utils.platform import on_tpu

        if self.cfg.scheduler == "continuous":
            # ragged kernel wants MXU-friendly head_dim, a TPU backend (or
            # forced interpret mode), and a mesh whose only sharded serving
            # axis is tp — the kernel then runs per kv-head shard inside
            # shard_map (ops/paged_attention.paged_decode_fused_sharded);
            # XLA cannot auto-partition a pallas_call, but pages are already
            # kv-head-sharded so each shard's walk is local.  The fused
            # write RMWs an 8-row-aligned DMA window, which only stays
            # inside the page when the page size is a multiple of 8.
            if self.model_cfg.kv_lora_rank:
                # the latent decode kernel (ops/mla_attention.py) slices
                # the latent off a page at a lane boundary
                return ((on_tpu() or self._interpret)
                        and self.model_cfg.kv_lora_rank % 128 == 0
                        and self.cfg.page_size % 16 == 0)
            return ((on_tpu() or self._interpret)
                    and self.model_cfg.hd % 128 == 0
                    and self.cfg.page_size % 8 == 0 and self._tp_only_mesh())
        return False

    @staticmethod
    def _refuse_for_latent(engine_cfg: EngineConfig, mesh) -> None:
        """What a latent KV cache cannot be combined with yet, each by its
        name: served wrongly is worse than not served."""
        tp = 1 if mesh is None else mesh.shape.get("tp", 1)
        sp = 1 if mesh is None else mesh.shape.get("sp", 1)
        for bad, what in (
            (engine_cfg.prefix_cache and env_bool("LMRS_PREFIX_CACHE", True),
             "prefix_cache (a hit continues through the span program, "
             "which reads K and V pools)"),
            (engine_cfg.kv_quantize, "kv_quantize (int8 KV pages: per-head "
                                     "scales, and the latent has no head)"),
            (engine_cfg.quantize, "quantize (int8 weights: the latent and "
                                  "expert projections have no int8 path)"),
            (tp > 1, "tp > 1 (pages shard by kv head; the latent has none)"),
            (sp > 1, "sp > 1 (ring prefill writes K and V shards)"),
            (mesh is not None and mesh.devices.size > 1,
             "a mesh of more than one device"),
            (engine_cfg.speculate_k, "speculate_k (the verify kernels read "
                                     "K and V pools)"),
            (engine_cfg.scheduler != "continuous",
             f"scheduler={engine_cfg.scheduler!r} (only the continuous "
             "scheduler carries the latent pool)"),
        ):
            if bad:
                raise ValueError(
                    f"latent KV cache (kv_lora_rank > 0) does not support "
                    f"{what}; turn it off for this model")

    @staticmethod
    def _refuse_for_window(engine_cfg: EngineConfig, mesh,
                           max_len: int) -> None:
        """What a window KV cache (a ring of pages a slot in the window
        layers) cannot be combined with yet, each by its name."""
        tp = 1 if mesh is None else mesh.shape.get("tp", 1)
        sp = 1 if mesh is None else mesh.shape.get("sp", 1)
        for bad, what in (
            (engine_cfg.prefix_cache and env_bool("LMRS_PREFIX_CACHE", True),
             "prefix_cache (a page behind a shared prefix is valid for the "
             "full layers and only partly for the window layers, and a hit "
             "continues through the span program)"),
            (engine_cfg.kv_quantize, "kv_quantize (int8 KV pages: the "
                                     "windowed decode walk and the ring "
                                     "write have no scaled form)"),
            (engine_cfg.quantize, "quantize (int8 weights: the routed "
                                  "experts have no int8 path)"),
            (engine_cfg.speculate_k, "speculate_k (the verify kernels walk "
                                     "every layer from page 0; the model's "
                                     "own draft module is not held)"),
            (tp > 1, "tp > 1 (the rings are not sharded by kv head)"),
            (sp > 1, "sp > 1 (ring prefill writes every layer's pages)"),
            (mesh is not None and mesh.devices.size > 1,
             "a mesh of more than one device"),
            (engine_cfg.prefill_chunk < max_len,
             f"prefill_chunk={engine_cfg.prefill_chunk} < max_seq_len="
             f"{max_len} (the continuation of a chunked prompt reads "
             "earlier chunks' pages, which a window layer has not kept)"),
            (engine_cfg.scheduler != "continuous",
             f"scheduler={engine_cfg.scheduler!r} (only the continuous "
             "scheduler carries the two-kind pool)"),
        ):
            if bad:
                raise ValueError(
                    f"window KV cache (sliding_window > 0) does not support "
                    f"{what}; turn it off for this model")

    def _window_counts(self, *, prefill=None, decode=None) -> dict | None:
        """A windowed model's share of a dispatch record (obs/anatomy.
        WINDOW_FIELDS), by the kernels' rules on the host.  ``decode``
        (lengths of the live rows at the block's start, steps): the pages
        the block's steps walk, a full layer ceil(len / page) at every
        step, a window layer the pages from position len - window on
        (ops/paged_attention.window_walk).  ``prefill`` (real tokens a
        row, bucket): the flash kernel's causal (q tile, kv tile) pairs at
        the windowed call's tile over ALL attention layers, and those of
        the window layers that lie wholly behind the window (the banded
        grid never visits them); 0 where the XLA attention serves."""
        if not self._window:
            return None
        ps, w = self.cache.page_size, self._window
        lay = self.cache.window
        out = dict.fromkeys(WINDOW_FIELDS, 0)
        if decode is not None:
            lens, steps = decode
            # step t of the block attends len + t + 1 positions
            n = (np.asarray(lens, np.int64)[:, None]
                 + np.arange(1, steps + 1)[None, :])
            n = np.minimum(n, self.max_len)
            full, win = walk_pages(n, ps), walk_pages(n, ps, w)
            out["kv_pages_full"] = int(full.sum()) * lay["n_full"]
            out["kv_pages_window"] = int(win.sum()) * lay["n_win"]
        if prefill is not None:
            from lmrs_tpu.models.transformer import _use_flash_prefill
            from lmrs_tpu.ops.flash_attention import (window_band,
                                                      window_block)
            lens, bucket = prefill
            if self._use_flash and _use_flash_prefill(
                    bucket, self.model_cfg.hd, self._interpret):
                tile = min(window_block(w), bucket)
                band = window_band(w, tile)
                for n in lens:
                    nq = -(-int(n) // tile)
                    pairs = nq * (nq + 1) // 2
                    seen = sum(min(q + 1, band) for q in range(nq))
                    out["flash_blocks"] += pairs * (lay["n_full"]
                                                    + lay["n_win"])
                    out["flash_blocks_skipped"] += ((pairs - seen)
                                                    * lay["n_win"])
        return out

    def _moe_take(self, program: str, key: tuple, out: tuple) -> tuple:
        """A routed model's programs return the held experts' counts last
        ([routed layers, experts_held + 3], ops/moe.py): kept on the device
        until the next fetch brings them back with the tokens."""
        if not self._moe_on:
            return out
        self._moe_pending.append((program, key, out[-1]))
        return out[:-1]

    def _moe_note(self, pending: list, fetched) -> int:
        """Fold the fetched counts into their dispatches' records; returns
        the bytes of the experts the decode blocks among them touched."""
        held = self.model_cfg.experts_held
        decode_bytes = 0
        for (program, key, _), st in zip(pending, fetched):
            st = np.asarray(st)
            touched = int(st[:, held + 2].sum())
            nbytes = (int(touched * self._bytes.expert)
                      if program in ("decode", "spec") else 0)
            self._an.note_moe(
                program, key, pairs=int(st[:, :held].sum()),
                tokens_max=int(st[:, held].sum()),
                extra_passes=int(st[:, held + 1].sum()), touched=touched,
                held=held, hbm_bytes=nbytes)
            if program == "decode":
                decode_bytes += nbytes
        return decode_bytes

    def _moe_drain(self) -> None:
        """Counts that no decode fetch picked up (a run whose last
        dispatch was a prefill)."""
        if self._moe_pending:
            pending, self._moe_pending = self._moe_pending, []
            self._moe_note(pending, jax.device_get([p[2] for p in pending]))

    def _single_device(self) -> bool:
        return self.mesh is None or self.mesh.devices.size == 1

    def _tp_only_mesh(self) -> bool:
        """True when there is no mesh, a 1-device mesh, or a mesh whose only
        >1 axes are ``tp``/``sp`` — the layouts the shard_map-wrapped
        kernels support.  Pages shard over tp and replicate over sp, so
        each sp replica runs the kernel on identical inputs (duplicated
        but parallel work — same wall time as sp=1, and decode keeps the
        fused kernel instead of regressing to the gather fallback just
        because sp was enabled for prefill CP)."""
        if self._single_device():
            return True
        return self.mesh.devices.size == (self.mesh.shape.get("tp", 1)
                                          * self.mesh.shape.get("sp", 1))

    def _kernel_mesh(self):
        """Mesh to hand the Pallas paths: None on a single device (plain
        pallas_call), the tp mesh otherwise (shard_map wrapping)."""
        return None if self._single_device() else self.mesh

    # ----------------------------------------------------------- public API

    def cancel(self, request_id: int) -> None:
        """Abort ``request_id`` (of the CURRENT run) at the next block
        boundary: a live slot is finished early with
        ``finish_reason="cancelled"`` and its pages freed; a queued entry
        never prefills.  Callable from any thread (the HTTP server cancels
        from a handler thread on client disconnect); unknown or already-
        finished ids are a no-op.  Tokens generated before the sweep are
        kept in the result — they are real output a streaming client may
        already hold."""
        self._cancelled.add(request_id)

    def run(self, requests: list[GenerationRequest],
            on_result=None, on_tokens=None) -> list[GenerationResult]:
        """Run the stream to completion and return results in request order.

        ``on_result(result, submit)``, when given, is invoked INSIDE the
        scheduling loop as each request completes; the callback may call
        ``submit(more_requests)`` to feed new work into the same stream —
        this is how the reduce tree rides the map stage's batch slots
        instead of waiting behind a full-queue barrier (map→reduce
        overlap).  Single-threaded: callbacks run between dispatches, so
        they need no locking but must be quick.  request_ids must be
        unique across everything submitted to one run().

        ``on_tokens(request_id, text_delta)``, when given, fires after each
        decode-block dispatch with the newly generated text for every slot
        that advanced (SSE streaming on the serving front-end).  Deltas are
        cut from the stop-trimmed, budget-capped text, so their
        concatenation equals the final result's ``text`` exactly — a
        streaming client never sees tokens past a stop sequence.  A
        preempted slot resumes deltas where it left off (progress is
        tracked per request id, not per slot).
        """
        # sched.run: the outermost scheduler span; unix_ns is the host's
        # wall clock at its start, so the Chrome-JSON export (on
        # time.time()) can be laid over an xplane
        with span("sched.run", unix_ns=time.time_ns()):
            return self._run(requests, on_result, on_tokens)

    def _run(self, requests, on_result, on_tokens):
        t_run = time.time()
        # taken BEFORE the first allocator touch: an off-thread
        # release_handoff freeing inline holds this lock, so it either
        # completes before we flip the flag or sees it set and defers
        with self._pinned_lock:
            self._run_live = True
        # per-run tracer capture: the CLI/bench enable tracing before the
        # engine runs; a None tracer keeps every site a single branch
        tr = self._tr = get_tracer()
        # NOTE: the cancel set is deliberately NOT cleared here.  A client
        # disconnect can race the run boundary (cancel lands after
        # generate_batch is invoked but before run() begins executing); a
        # start-of-run clear would erase that legitimate cancel and the
        # abandoned request would decode to max_tokens after all.  Cross-run
        # id collisions are prevented by callers instead: the HTTP batcher
        # assigns globally-unique wave rids, and the end-of-run clear (the
        # finally below) drops ids that were never matched.
        self._on_tokens = on_tokens
        self._streamed: dict[int, str] = {}  # rid -> text already emitted
        # slot rows don't survive runs: stale-history marks from a prior
        # run's mixed window mean nothing for this run's occupants
        self._spec_stale.clear()
        # queue entries: (req, prefill_ids, max_new, n_prompt,
        # prior_generated, t_start) — the last three are preemption-
        # continuation state (len(ids), [], None for fresh requests)
        queue: deque[tuple] = deque()
        all_requests = list(requests)
        # rid -> enqueue time, consumed at the request's FIRST generated
        # token (TTFT sample).  Run-local: ids cancelled while queued just
        # leave their entry to be dropped with the dict.
        t_enq: dict[int, float] = {}
        last_block_t: float | None = None  # prev decode-dispatch timestamp

        # deadline-free runs skip the per-iteration expiry sweep entirely
        self._any_deadline = any(r.deadline_s is not None for r in requests)

        def submit(new_requests: list[GenerationRequest]) -> None:
            for req in new_requests:
                ids, max_new = self._encode(req)
                queue.append((req, ids, max_new, len(ids), [], None))
                all_requests.append(req)
                t_enq[req.request_id] = time.time()
                if req.deadline_s is not None:
                    self._any_deadline = True
                if tr:
                    tr.instant("enqueue", ts=t_enq[req.request_id],
                               tid=self._tid(req),
                               args={"prompt_tokens": len(ids)})

        fresh: deque[int] = deque()  # completed rids awaiting delivery
        for req in requests:
            ids, max_new = self._encode(req)
            queue.append((req, ids, max_new, len(ids), [], None))
            t_enq[req.request_id] = time.time()
            if tr:
                tr.instant("enqueue", ts=t_enq[req.request_id],
                           tid=self._tid(req),
                           args={"prompt_tokens": len(ids)})

        slots: list[_SlotState | None] = [None] * self.B
        last_tok = np.zeros((self.B,), np.int32)
        kv_lens = np.zeros((self.B,), np.int32)
        active = np.zeros((self.B,), bool)
        temps = np.zeros((self.B,), np.float32)
        top_k = np.zeros((self.B,), np.int32)
        top_p = np.ones((self.B,), np.float32)
        results: dict[int, GenerationResult] = {}

        usable_pages = self.cache.num_pages - 1  # minus reserved null page

        def admit():
            for b in range(self.B):
                if slots[b] is not None:
                    continue
                # Fair-share admission (fleet/qos.py): promote the policy's
                # pick from the queue's head window to the front — best
                # (class rank, normalized windowed usage, FIFO) entry.
                # The remaining entries keep their relative order (this is
                # a targeted promotion, not a rotation — skipped entries
                # must not migrate to the back and starve).  Head window
                # bounded so a deep backlog costs O(window) per slot, not
                # O(queue).  _qos is None under LMRS_QOS=0: FIFO exactly.
                if self._qos is not None and len(queue) > 1:
                    win = min(len(queue), 64)
                    k = self._qos.pick_index(
                        [queue[i][0] for i in range(win)])
                    if k:
                        ent = queue[k]
                        del queue[k]
                        queue.appendleft(ent)
                        if tr:
                            # fleet-drift contract (trace.py): a QoS
                            # promotion is an auditable scheduling decision
                            tr.instant("qos_reorder",
                                       args={"picked": k, "window": win,
                                             "tenant": ent[0].tenant
                                             or "default"})
                # Deadline admission control (load shedding): drop head
                # entries whose remaining budget cannot cover the TTFT
                # estimate — a fast explicit rejection BEFORE prefill beats
                # letting a saturated pod convert overload into queue wait
                # that expires in a slot anyway.
                while queue and self._any_deadline:
                    rem = remaining_budget(queue[0][0])
                    if rem is None or rem >= self._ttft_estimate(
                            len(queue[0][1])):
                        break
                    self._expire_queue_entry(queue, 0, results, fresh)
                if not queue:
                    break
                req, ids, max_new, n_prompt, prior, t0 = queue[0]
                if req.handoff_state is not None:
                    # disaggregated decode role: the head entry's KV pages
                    # arrive by import, not prefill (the slot enters decode
                    # phase directly).  False = page back-pressure: stop
                    # admitting and wait, same as the prefill path below.
                    if not self._admit_import(b, queue, slots, results,
                                              fresh, kv_lens, last_tok,
                                              active, temps, top_k, top_p):
                        break
                    continue
                # Prefix-cache probe: clone the longest cached page prefix
                # (ref-counted, read-only) and start prefill at the match
                # boundary.  match_hier() always leaves >= 1 prompt token
                # to prefill (the sampled-first-token chunk), so a "full"
                # hit is a one-chunk tail prefill straight into decode.
                # ``spill_chain`` is the host-tier extension: spilled
                # segments that will PREFETCH into freshly allocated pages
                # instead of re-prefilling (no references held — dropping
                # the chain on back-pressure costs nothing).
                cached_pages: list[int] = []
                cached_tokens = 0
                spill_chain: list = []
                if self._prefix_cache is not None:
                    cached_pages, cached_tokens, spill_chain = \
                        self._prefix_cache.match_hier(ids)
                # Admission reserves PROMPT pages only; decode capacity is
                # grown per block (_ensure_decode_capacity), with youngest-
                # slot preemption under pressure.  No fail-fast branch here:
                # a slot never holds more than max_pages_per_slot pages
                # (sequences cap at max_len) and the pool floor guarantees
                # usable_pages >= max_pages_per_slot, so every request can
                # complete alone in the pool — oversized prompts were
                # truncated at submit and oversized decodes trim at max_len
                # (ADVICE r2: the former "can NEVER complete" branch was
                # unreachable under these invariants).  Cached pages only
                # tighten this: match covers at most len(ids)-1 tokens, so
                # need >= 1 fresh page always remains to allocate.
                need = min(self.cache.pages_needed(len(ids)),
                           self.cache.max_pages_per_slot) - len(cached_pages)
                if need > self.cache.allocator.free_count:
                    if self._prefix_cache is not None:
                        # LRU-evict refcount-zero cache before declaring
                        # back-pressure: retained pages must never starve
                        # admission (the matched pages themselves are
                        # pinned by the extra match reference)
                        self._prefix_cache.evict(
                            need - self.cache.allocator.free_count)
                    if need > self.cache.allocator.free_count:
                        if cached_pages:  # release the match references
                            self.cache.allocator.free(cached_pages)
                        break  # back-pressure: wait for pages to free up
                queue.popleft()
                try:
                    # NB: named fresh_pages, not fresh — admit() closes
                    # over run()'s ``fresh`` results deque
                    fresh_pages = self.cache.alloc_pages(need)
                except OutOfPages:
                    # pressure raced (or was injected) past the free-count
                    # check above: release the match references, requeue at
                    # the head, and wait — back-pressure, never failure
                    if cached_pages:
                        self.cache.allocator.free(cached_pages)
                    queue.appendleft((req, ids, max_new, n_prompt, prior, t0))
                    break
                prefetched_tokens = 0
                if spill_chain:
                    # spilled hit: restore each segment into its share of
                    # the fresh pages (async scatter, overlapped with the
                    # dispatch cadence); a failed/dropped segment truncates
                    # the match there and its pages become prefill tail —
                    # admission never wedges on the host tier
                    (cached_pages, fresh_pages, cached_tokens,
                     prefetched_tokens) = self._prefetch_spilled(
                        spill_chain, cached_pages, fresh_pages,
                        cached_tokens)
                seq = SequencePages(pages=cached_pages + fresh_pages)
                # counted at ADMISSION, not per probe: a back-pressured
                # request re-probes every scheduler tick until pages free
                # up, and retry ticks must not dilute the hit rate
                if self._prefix_cache is not None:
                    self._c_prefix_queries.inc()
                    if cached_tokens:
                        self._c_prefix_hits.inc()
                        self._c_prefix_tokens.inc(cached_tokens)
                        self._cost.note_saved(
                            req,
                            prefix_tokens=cached_tokens - prefetched_tokens,
                            prefetched_tokens=prefetched_tokens,
                            prefetched_bytes=(
                                self.cache.pages_needed(prefetched_tokens)
                                * self.cache.page_payload_bytes()
                                if prefetched_tokens else 0.0))
                # a continuation keeps its ORIGINAL t_start: device_seconds
                # then spans the whole request, and the slot stays "old" for
                # youngest-victim selection (a refreshed t_start would make
                # the same request the perpetual preemption victim)
                now = time.time()
                if req.deadline_s is not None:
                    self._h_deadline_remaining.observe(req.deadline_s - now)
                st = _SlotState(req=req, prompt_ids=ids, max_new=max_new,
                                seq=seq,
                                t_start=t0 if t0 is not None else now,
                                n_prompt=n_prompt, prior=list(prior))
                st.t_admit = now
                if self._spec_tree:
                    # tree speculation starts at full depth (the adaptive
                    # ramp takes over per accepted step); a cross-refresh
                    # draft hint tokenizes ONCE here, clipped so hint +
                    # prompt + budget still fit the history buffer
                    st.spec_depth = self.spec_k
                    if req.draft_hint:
                        room = (self.max_len - len(ids) - max_new - 1
                                - self._spec_width * self.spec_k)
                        if room > 0:
                            st.spec_hint = self.tokenizer.encode(
                                req.draft_hint)[:room]
                rid = req.request_id
                # queue wait = enqueue -> FIRST admission.  Continuation
                # detection is ``t0`` (the carried original t_start), NOT
                # ``prior``: a slot preempted before its deferred first
                # token re-queues with prior=[] but t0 set, and must not
                # re-sample an enqueue->re-admission wait
                t_q = t_enq.get(rid)
                if t_q is not None and t0 is None:
                    self._h_queue_wait.observe(now - t_q)
                    self._cost.note_queue_wait(req, now - t_q)
                    if tr:
                        tr.complete("queue_wait", t_q, now,
                                    tid=self._tid(req))
                if tr:
                    tr.instant("admit", ts=now, tid=self._tid(req),
                               args={"slot": b,
                                     "continuation": t0 is not None})
                    if cached_tokens:
                        tr.instant("prefix_match", ts=now,
                                   tid=self._tid(req),
                                   args={"tokens_reused": cached_tokens,
                                         "tokens_prefetched":
                                             prefetched_tokens})
                # a cache hit enters the existing chunked-prefill machinery
                # at the match boundary: the first chunk dispatches as a
                # windowed continuation attending the cloned pages
                st.prefill_pos = cached_tokens
                slots[b] = st  # phase="prefill"; device work happens in the loop
                # a decode dispatch can run while this slot is still
                # mid-prefill (chunked prefill): its row must carry length
                # 0, not the previous occupant's stale length — the ragged
                # kernel derives its page-walk bound from kv_lens and a
                # stale value over-runs the [B, w] table in SMEM
                kv_lens[b] = 0
                last_tok[b] = 0
                temps[b] = req.temperature
                top_k[b] = req.top_k
                top_p[b] = min(max(req.top_p, 0.0), 1.0)
                # usable pages only: the reserved null page is neither
                # allocatable nor counted, so utilization can reach 0 and 1
                in_use = usable_pages - self.cache.allocator.free_count
                self._g_peak_pages.track_max(in_use)
                self._g_peak_slots.track_max(
                    sum(s is not None for s in slots))

        wd = self.watchdog
        if wd is not None:
            wd.run_started()
        try:
            while True:
                # step anatomy (obs/anatomy.py): one iteration record per
                # pass; every ``continue``/bottom closes it with iter_end
                # (classed), the exit break discards it, and the finally
                # aborts whatever a fault left open
                self._an.iter_begin()
                with self._an.seg("admit"):
                    # injection site: a fired plan fails this scheduler
                    # iteration the way a bad dispatch would — exercising
                    # the pool-recovery path in the except below
                    faults.fire("scheduler.step")
                    # injection site + heartbeat (hang survival, engine/
                    # watchdog.py): a "stall" plan here wedges the loop the
                    # way a hung chip would — no beat lands, the watchdog
                    # declares the wedge.  With LMRS_WATCHDOG=0 the same
                    # stall simply hangs the run (today's behavior).
                    faults.fire("scheduler.heartbeat")
                    if wd is not None:
                        wd.beat()
                    # sweep cancellations first (block boundary): their
                    # results are then delivered with this iteration's
                    # fresh batch
                    if self._cancelled:
                        self._sweep_cancelled(queue, slots, results, active,
                                              fresh, kv_lens, last_tok)
                    # acked/orphaned handoff releases parked by handler/
                    # sweeper threads free here, on the scheduler thread
                    # (see release_handoff) — their pages rejoin the pool
                    # within one block of the ack
                    if self._release_deferred:
                        self._drain_released()
                    # deadline expiry rides the same block-boundary cadence
                    # as the cancel sweep: an in-flight request expires
                    # within one decode block of its deadline
                    if self._any_deadline:
                        self._sweep_deadlines(queue, slots, results, active,
                                              fresh, kv_lens, last_tok)
                # deliver fresh results first: the callback may submit new work,
                # which the loop-exit check below must see (a reduce batch
                # submitted by the LAST map result must still run)
                with self._an.seg("io"):
                    if on_result is not None:
                        while fresh:
                            on_result(results[fresh.popleft()], submit)
                if not (queue or any(s is not None for s in slots)):
                    self._an.iter_discard()
                    break
                with self._an.seg("admit"):
                    admit()
                # SARATHI mixed step: when a prompt is mid-prefill WHILE
                # other slots decode, fuse one prompt slice into the
                # decode step as a single multi-token dispatch — decode
                # cadence continues through the admission instead of
                # draining behind a packed prefill wave.  Falls through to
                # the alternating path when there is nothing to mix (pure
                # prefill / pure decode iterations are unchanged, so
                # LMRS_MIXED=0 restores today's dispatch byte-for-byte).
                if self._mixed:
                    # anatomy: the mixed handler re-segments its own
                    # draft/dispatch/fetch/finish internally; the "plan"
                    # wrapper catches the remaining operand plumbing
                    with self._an.seg("plan"):
                        did, last_block_t = self._mixed_iteration(
                            slots, queue, results, fresh, kv_lens, last_tok,
                            active, temps, top_k, top_p, t_enq, last_block_t)
                    if did:
                        self._an.iter_end("spec" if self.spec_k else "mixed")
                        continue
                # advance every prefilling slot by ONE prompt chunk, then give
                # decode a turn — long prompts never monopolize the device.
                # Same-shape chunks batch into one dispatch (a [N,S] prefill
                # feeds the MXU far better than N serialized [1,S] programs).
                # First tokens are NOT fetched here: every host bookkeeping step
                # except generated.append(tok0) is tok0-independent, so tok0
                # stays on device, is scattered into the decode dispatch's
                # last_tok input, and rides back in the decode block's single
                # device_get — one fewer ~full-RTT host sync per admission wave.
                t_pf = time.time()  # prefill-wave dispatch-issue anchor
                with self._an.seg("plan"):
                    # operand build inside; the jitted calls re-segment
                    # themselves as "dispatch" (pause semantics)
                    pending = self._advance_prefills(slots)
                deferred: list[tuple[int, int, int]] = []  # (slot, pend idx, row)
                with self._an.seg("finish"):
                    for p, (tok0_dev, rows) in enumerate(pending):
                        for b, row in rows:
                            st = slots[b]
                            st.phase = "decode"
                            st.t_decode_start = time.time()
                            if tr:
                                tr.complete(
                                    "prefill", st.t_admit, st.t_decode_start,
                                    tid=self._tid(st.req),
                                    args={"prompt_tokens":
                                          len(st.prompt_ids)})
                            st.kv_len = len(st.prompt_ids)
                            kv_lens[b] = st.kv_len
                            active[b] = True
                            # donate the prompt's full-page prefix to the
                            # prefix cache NOW (not at finish): the dispatch
                            # writing these pages is already issued, and
                            # later admissions in the same run can hit
                            # immediately
                            self._cache_insert(st)
                            deferred.append((b, p, row))
                if pending and (self.spec_k or not self.defer_tok0
                                or any(slots[b] is not None
                                       and slots[b].req.handoff_export
                                       for b, _, _ in deferred)):
                    # speculation seeds a host-built history row per admission —
                    # it needs tok0 values now, so it keeps the synchronous
                    # fetch (also selectable via LMRS_DEFER_TOK0=0 for A/B runs).
                    # Handoff-export slots force it too: their budget is 1, so
                    # the sync fetch finishes (pins) them here and the prefill
                    # pod never burns a decode-block dispatch on tokens the
                    # handoff would trim anyway.
                    with self._an.seg("fetch"):
                        fetched = self._timed_get([t for t, _ in pending])
                    # clean prefill MFU sample: the wall from dispatch
                    # issue to this fetch covers exactly the prefill
                    # compute (+1 RTT) — the prefill pod's whole life
                    t_fetch = time.time()
                    with self._an.seg("finish"):
                        flops, cold = self._consume_prefill_attr()
                        self._perf.note_prefill_sync(flops, t_pf, t_fetch,
                                                     warm=not cold)
                        self._cost.note_step(
                            max(0.0, t_fetch - t_pf),
                            prefill_rows=self._consume_prefill_cost(),
                            prefill_cost_s=1.0)
                        for (b, p, row) in deferred:
                            st = slots[b]
                            tok0 = int(fetched[p][row])
                            st.generated.append(tok0)
                            self._note_first_token(st, t_enq)
                            last_tok[b] = tok0
                            with self._an.seg("draft"):
                                self.seed_history(b, st)
                            self._maybe_finish(b, slots, results, active,
                                               fresh, kv_lens, last_tok)
                    deferred = []
                    pending = []
                if not any(active):
                    self._an.iter_end("prefill")
                    continue
                # grow every decode slot's pages to cover the coming block;
                # under pool pressure the youngest decode slot is preempted
                # back to the queue (its pending tok0, if any, is simply
                # re-sampled when it re-prefills)
                with self._an.seg("admit"):
                    stalled = self._ensure_decode_capacity(
                        slots, queue, kv_lens, last_tok, active)
                if not any(active):
                    if deferred:
                        # no dispatch will carry these first tokens: fetch them
                        # now — a stalled slot's tok0 is real output and must
                        # not be dropped (preempted slots resample theirs)
                        with self._an.seg("fetch"):
                            fetched = self._timed_get([t for t, _ in pending])
                        t_fetch = time.time()
                        with self._an.seg("finish"):
                            flops, cold = self._consume_prefill_attr()
                            self._perf.note_prefill_sync(flops, t_pf, t_fetch,
                                                         warm=not cold)
                            self._cost.note_step(
                                max(0.0, t_fetch - t_pf),
                                prefill_rows=self._consume_prefill_cost(),
                                prefill_cost_s=1.0)
                            for (b, p, row) in deferred:
                                if slots[b] is None:
                                    continue
                                tok0 = int(fetched[p][row])
                                slots[b].generated.append(tok0)
                                self._note_first_token(slots[b], t_enq)
                                last_tok[b] = tok0
                                self._maybe_finish(b, slots, results, active,
                                                   fresh, kv_lens, last_tok)
                    for b in stalled:  # re-arm before looping back
                        if slots[b] is not None:
                            active[b] = True
                    self._an.iter_end("prefill")
                    continue
                if self.spec_k and self._spec_tree:
                    # tree speculation (ISSUE 19): pure-decode spec steps
                    # route through the ragged-span family too — the
                    # legacy spec block must never see a row whose heal
                    # prefix or hint-offset history columns only the tree
                    # path understands.  The span handler owns its own
                    # occupancy/gap/dispatch metrics.  A False return
                    # means every row stalled under page pressure: loop
                    # (preemption guarantees progress, same as the legacy
                    # stall spin).
                    with self._an.seg("plan"):
                        did, last_block_t = self._rpa_mixed_iteration(
                            None, slots, queue, results, fresh, kv_lens,
                            last_tok, active, temps, top_k, top_p, t_enq,
                            last_block_t)
                    self._an.iter_end("spec")
                    continue
                n_live = int(np.sum(active))
                self._h_occupancy.observe(n_live / self.B)
                self._c_decode_dispatches.inc()
                now = time.time()
                if last_block_t is not None:
                    self._h_block_gap.observe(now - last_block_t)
                    self._slo.observe_gap(now - last_block_t)
                last_block_t = now
                # anatomy: the block methods re-segment their own draft/
                # dispatch/fetch internally; the "plan" wrapper catches
                # the operand build + result scatter plumbing around them
                if self.spec_k:
                    with self._an.seg("plan"):
                        emitted = self._spec_decode_block(
                            slots, last_tok, kv_lens, active, temps, top_k,
                            top_p)
                else:
                    with self._an.seg("plan"):
                        toks, n_valid, tok0s = self._decode_block(
                            slots, last_tok, kv_lens, active, temps, top_k,
                            top_p, pending)
                        emitted = [toks[b, : int(n_valid[b])].tolist()
                                   for b in range(self.B)]
                with self._an.seg("finish"):
                    if self._cost.enabled and self._cost_step is not None:
                        # the dispatch wall stashed by _decode_block /
                        # _spec_decode_block meets its per-row token counts
                        # here — one ledger note per dispatch, issued BEFORE
                        # any of this iteration's finishes (the mixed path's
                        # ordering): a row finishing on this very block must
                        # have its final share billed while its entry is
                        # still open, not re-created as an orphan after
                        # finish() already rolled it up
                        wall, dcost, pcost, prows = self._cost_step
                        self._cost_step = None
                        self._cost.note_step(
                            wall,
                            decode_rows=[(slots[b].req, len(emitted[b]),
                                          len(slots[b].seq.pages))
                                         for b in range(self.B)
                                         if slots[b] is not None
                                         and active[b]],
                            prefill_rows=prows,
                            decode_cost_s=dcost, prefill_cost_s=pcost)
                    if not self.spec_k:
                        for (b, p, row) in deferred:
                            if slots[b] is None:
                                continue  # preempted: tok0 resampled later
                            tok0 = int(tok0s[p][row])
                            slots[b].generated.append(tok0)
                            self._note_first_token(slots[b], t_enq)
                            last_tok[b] = tok0
                            if not active[b]:
                                # STALLED this dispatch (no pages to grow):
                                # the slot emitted nothing, but its first
                                # token is real output — record it and
                                # check for an early finish; the emitted
                                # loop below skips inactive rows
                                self._maybe_finish(b, slots, results, active,
                                                   fresh, kv_lens, last_tok)
                    block_tokens = 0
                    for b in range(self.B):
                        st = slots[b]
                        if st is None or not active[b]:
                            continue
                        new = emitted[b]
                        st.generated.extend(new)
                        st.kv_len += len(new)
                        kv_lens[b] = st.kv_len
                        last_tok[b] = st.generated[-1] if st.generated else 0
                        self._c_decode_tokens.inc(len(new))
                        block_tokens += len(new)
                        if tr and new:
                            tr.instant("decode_block", ts=now,
                                       tid=self._tid(st.req),
                                       args={"tokens": len(new)})
                        self._maybe_finish(b, slots, results, active, fresh,
                                           kv_lens, last_tok)
                    if tr:
                        # scheduler-track span: dispatch issue through
                        # host-side result processing; start timestamps are
                        # the former LMRS_TRACE_DISPATCH list
                        # (Tracer.timestamps).  hbm_gb = the block's model
                        # byte cost (perf attribution; 0 for spec blocks,
                        # whose model differs)
                        tr.complete("decode_block", now, time.time(),
                                    args={"active": n_live,
                                          "tokens": block_tokens,
                                          "hbm_gb": self._attr_last_gb})
                    for b in stalled:  # stalled rows rejoin the next dispatch
                        if slots[b] is not None:
                            active[b] = True
                self._an.iter_end("spec" if self.spec_k else "plain")

        except Exception as run_exc:
            # Dispatch/step failure mid-run.  The exception re-raises —
            # every caller (MapExecutor, the HTTP batcher) already
            # translates engine exceptions into per-request error results —
            # but the ENGINE must survive for the next batch, so restore
            # the pool invariants first: live slots' pages free, the queue
            # drops (entries hold no pages), the device pools reallocate
            # (a failed DONATED dispatch leaves k/v consumed), and the
            # prefix cache — whose pages point into the discarded pool
            # content — drops its retained nodes.
            # Flight recorder FIRST (obs/flight.py): the postmortem must
            # capture the metrics/spans AS THE FAULT LEFT THEM, before
            # recovery rewrites the pool state.  No-op unless
            # LMRS_POSTMORTEM_DIR is armed; never raises.
            dump_postmortem(
                "dispatch_fault", metrics=self.metrics,
                extra={"error": f"{type(run_exc).__name__}: {run_exc}",
                       "live_slots": sum(s is not None for s in slots),
                       "queued": len(queue)})
            for b in range(self.B):
                if slots[b] is not None:
                    try:
                        self.cache.close_sequence(slots[b].seq)
                    except ValueError:
                        logger.exception(
                            "slot %d page release failed in recovery", b)
                    slots[b] = None
            queue.clear()
            # pinned-for-export KV content dies with the re-zeroed pool,
            # so the records are dropped (next ticket fetch 410s → the
            # router re-prefills) — but their PAGES must free through the
            # allocator, which survives reallocate() (it only re-zeros
            # the k/v buffers): clearing without close_sequence would
            # leak refcount-held pages forever.  Freed BEFORE the prefix-
            # cache clear: clear() skips nodes a live holder still shares,
            # so a pinned seq released after it would strand a cache node
            # pointing at discarded pool content.  Snapshot-and-clear is
            # atomic under the pin lock, so a racing off-thread release
            # (which pops/parks under the same lock) can never slip a
            # record past the sweep.
            with self._pinned_lock:
                dropped = ([r["seq"] for r in self._pinned.values()]
                           + [rec["seq"]
                              for _, rec, _ in self._release_deferred])
                self._pinned.clear()
                self._release_deferred.clear()
            for seq in dropped:
                try:
                    self.cache.close_sequence(seq)
                except ValueError:
                    logger.exception("pinned handoff page release failed "
                                     "in recovery")
            if dropped:
                logger.warning("pool recovery dropped %d pinned handoffs",
                               len(dropped))
                self._update_pinned_gauge()
            if self._prefix_cache is not None:
                self._prefix_cache.clear()
            self.cache.reallocate()
            if self._kv_quant:
                self.kscale = jnp.ones_like(self.kscale)
                self.vscale = jnp.ones_like(self.vscale)
            self._spec_buf = None  # donated with the pools; reseeds lazily
            raise
        finally:
            # runs on normal completion AND mid-run failure: a dead
            # callback, stale streamed text, or stale cancel ids must not
            # leak into a later run.  There is deliberately NO start-of-run
            # clear (see the NOTE at the top of run()): ids raced in
            # between runs persist until THIS clear fires at the end of
            # the next run, which is harmless because the HTTP batcher's
            # wave rids are globally unique — a stale id can never match a
            # future request.
            # clamped (same reason as _timed_get) — doubly important here:
            # this runs in a finally, where a raise would mask the real error
            self._c_run_seconds.inc(max(0.0, time.time() - t_run))
            if self._moe_pending:
                # routed-layer counts no decode fetch brought back; in a
                # finally, so a failed device must not mask the real error
                try:
                    self._moe_drain()
                except Exception:
                    logger.exception("routed-layer counts lost at run end")
                    self._moe_pending = []
            # an iteration a fault left open contributes NOTHING to the
            # anatomy totals (iter_abort discards) — conservation survives
            # the chaos arms by construction; no-op after a clean close
            self._an.iter_abort()
            if wd is not None:
                wd.run_ended()
            self._on_tokens = None
            self._streamed = {}
            self._cancelled.clear()
            # un-consumed ledger rows must not leak across runs (a run
            # abandoned mid-wave would bill its rows to the next run's
            # first dispatch)
            self._cost_pending_prefill = []
            self._cost_step = None
            with self._pinned_lock:
                self._run_live = False
            # releases parked during the run free here, on the scheduler
            # thread, so nothing stays deferred between runs
            self._drain_released()
        return [results[r.request_id] for r in all_requests]

    def _sweep_cancelled(self, queue, slots, results, active, fresh,
                         kv_lens, last_tok) -> None:
        """Apply pending cancel() calls at a block boundary: free live
        slots' pages, drop queued entries, record results.  Snapshot the id
        set first — cancel() may add concurrently from another thread, and
        ids added mid-sweep are simply handled next iteration."""
        pending = set(self._cancelled)
        hit: set[int] = set()
        for i in range(len(queue) - 1, -1, -1):
            req = queue[i][0]
            if req.request_id in pending:
                _, _, max_new, n_prompt, prior, _ = queue[i]
                del queue[i]
                # route the preemption-carry tokens through the same
                # trimming as the slot path — a preempted slot can't have
                # hit EOS/stop/budget (it would have finished instead),
                # but the two cancel paths must not be able to diverge if
                # preemption semantics ever change
                gen, text, stop_hit, _ = self._trim_tokens(
                    list(prior), max_new, req.stop)
                self._record_result(results, GenerationResult(
                    request_id=req.request_id,
                    text=text,
                    prompt_tokens=n_prompt,
                    completion_tokens=len(gen),
                    finish_reason="cancelled",
                    stop_sequence=stop_hit,
                ), req=req)
                fresh.append(req.request_id)
                hit.add(req.request_id)
                self._c_cancelled.inc()
                if self._tr:  # cancelled while still queued: no spans open
                    self._tr.instant("cancel",
                                     tid=self._tid(req),
                                     args={"state": "queued"})
        for b in range(self.B):
            st = slots[b]
            if st is None or st.req.request_id not in pending:
                continue
            gen, text, stop_hit, _ = self._trimmed_output(st)
            self._finish_slot(b, slots, results, active, fresh, kv_lens,
                              last_tok, gen, text, stop_hit, "cancelled")
            hit.add(st.req.request_id)
            self._c_cancelled.inc()
            logger.debug("cancelled request %d (slot %d)",
                         st.req.request_id, b)
        self._cancelled -= hit

    def _record_result(self, results: dict, res: GenerationResult,
                       req: GenerationRequest | None = None) -> None:
        """The ONE write path into a run's result dict: every submitted id
        must terminate exactly once, so an overwrite is recorded for the
        auditor instead of silently replacing the first outcome.  Also
        the one place every terminal outcome meets the cost ledger (the
        usage bill attaches here) and the SLO outcome stream."""
        if res.request_id in results:
            self._audit_double_finish += 1
            logger.error("request %d terminated more than once "
                         "(%s over %s)", res.request_id, res.finish_reason,
                         results[res.request_id].finish_reason)
        if req is not None:
            res.usage = self._cost.finish(req, res)
        self._slo.note_result(res.finish_reason, res.completion_tokens,
                              res.error)
        results[res.request_id] = res

    # ------------------------------------------------------------ deadlines

    def _ttft_estimate(self, n_tokens: int) -> float:
        """Optimistic engine-side TTFT estimate for admission shedding: the
        fastest TTFT this engine has ever delivered (it reflects the real
        chips, compiled programs, and host link), else the perf-model
        prefill roofline bound (utils/perf_model; 0 on a device without
        known peaks).  Optimistic by design — a request shed on this
        number is PROVABLY unmeetable, while a mean would embed
        multi-second first-compile samples and shed healthy traffic."""
        if self._ttft_min != float("inf"):
            return self._ttft_min
        from lmrs_tpu.utils.perf_model import chip_spec, prefill_flops

        spec = chip_spec()
        if spec is None:
            return 0.0
        return prefill_flops(self.model_cfg, max(1, n_tokens),
                             head_tokens=1) / spec.peak_flops

    def _expire_queue_entry(self, queue, i: int, results, fresh) -> None:
        """Terminate queue entry ``i`` that cannot (or can no longer) meet
        its deadline.  Fresh requests shed before any prefill
        (``finish_reason="shed"``, zero engine work); a preemption
        continuation already produced output, so it finishes as
        ``"deadline"`` keeping the trimmed prior tokens."""
        req, _ids, max_new, n_prompt, prior, t0 = queue[i]
        del queue[i]
        continuation = t0 is not None
        gen, text, stop_hit, _ = self._trim_tokens(list(prior), max_new,
                                                   req.stop)
        reason = "deadline" if continuation else "shed"
        self._record_result(results, GenerationResult(
            request_id=req.request_id,
            text=text if continuation else "",
            prompt_tokens=n_prompt,
            completion_tokens=len(gen) if continuation else 0,
            finish_reason=reason,
            stop_sequence=stop_hit if continuation else None,
        ), req=req)
        fresh.append(req.request_id)
        (self._c_deadline if continuation else self._c_shed).inc()
        if self._tr:
            self._tr.instant(reason, tid=self._tid(req),
                             args={"queued": True})

    def _sweep_deadlines(self, queue, slots, results, active, fresh,
                         kv_lens, last_tok) -> None:
        """Expire deadline-passed requests at a block boundary, riding the
        cancel machinery: live slots finish with ``finish_reason=
        "deadline"`` (pages freed, partial output kept — same teardown as a
        cancel, _finish_slot); queued entries terminate without prefilling.
        The WHOLE queue is scanned, not just the head: an entry stuck
        behind back-pressure must not have to reach the head to expire."""
        now = time.time()
        expired = 0
        for i in range(len(queue) - 1, -1, -1):
            req = queue[i][0]
            if req.deadline_s is not None and req.deadline_s <= now:
                self._expire_queue_entry(queue, i, results, fresh)
                expired += 1
        for b in range(self.B):
            st = slots[b]
            if (st is None or st.req.deadline_s is None
                    or st.req.deadline_s > now):
                continue
            gen, text, stop_hit, _ = self._trimmed_output(st)
            self._finish_slot(b, slots, results, active, fresh, kv_lens,
                              last_tok, gen, text, stop_hit, "deadline")
            self._c_deadline.inc()
            expired += 1
            logger.debug("request %d expired in flight (slot %d)",
                         st.req.request_id, b)
        # deadline-expiry STORM: one sweep reaping >= LMRS_DEADLINE_STORM
        # requests (default 3) means the pod is converting overload into
        # expired work — freeze the evidence (no-op when the flight
        # recorder is unarmed)
        if expired:
            storm = env_int("LMRS_DEADLINE_STORM", 3, lo=0)
            if storm > 0 and expired >= storm:
                dump_postmortem("deadline_storm", metrics=self.metrics,
                                extra={"expired_this_sweep": expired,
                                       "queued": len(queue)})

    # ---------------------------------------------------------------- audit

    def audit(self, live_seqs=None) -> list[str]:
        """Cross-layer invariant auditor (tests/test_chaos.py closes every
        soak scenario on it).  Checks, returning one string per violation
        (empty list = clean):

        * page conservation — free + live + prefix-cached pages cover the
          pool exactly (kv_cache.audit_allocator);
        * refcount balance — each page's allocator refcount equals its
          accounted holders (live sequences + radix-tree retention);
        * radix-tree structure — edge labels, child keys, parent links,
          no double retention (prefix_cache.audit);
        * termination discipline — no request of any run on this scheduler
          ever terminated more than once (_record_result bookkeeping);
        * pinned-for-export pages (disaggregated handoff) — sequences
          pinned awaiting a decode-pod ack hold exactly one reference per
          page, accounted like live sequences, so refcount balance and
          page conservation hold ACROSS the handoff transaction.

        Between runs (the default) there are no live sequences; pass
        ``live_seqs`` to audit mid-run state from a callback."""
        holders: dict[int, int] = {}
        with self._pinned_lock:
            pinned_seqs = ([r["seq"] for r in self._pinned.values()]
                           + [rec["seq"]
                              for _, rec, _ in self._release_deferred])
        for seq in list(live_seqs or ()) + pinned_seqs:
            for p in seq.pages:
                holders[p] = holders.get(p, 0) + 1
        violations: list[str] = []
        if self._prefix_cache is not None:
            violations += self._prefix_cache.audit()
            for p in self._prefix_cache.retained_pages():
                holders[p] = holders.get(p, 0) + 1
        violations += audit_allocator(self.cache.allocator,
                                      self.cache.num_pages, holders)
        if self._audit_double_finish:
            violations.append(f"{self._audit_double_finish} result "
                              "record(s) overwrote an existing result "
                              "(termination-exactly-once broken)")
        violations += self._cost.audit()
        # anatomy conservation: iteration wall == segment sums + residual
        # (obs/anatomy.py; totals only advance at iter_end, so this is
        # safe to call mid-run from a callback)
        # and the dispatch table's identities: its prompt positions add up
        # to lmrs_prefill_tokens_total, its query positions to the flat
        # counter, and every key holds dispatches x its bucket
        violations += self._an.audit(
            prefill_tokens=int(self._c_prefill_tokens.value))
        if violations:
            # an invariant break is exactly the moment the last-N spans
            # and counters matter; no-op unless the recorder is armed
            dump_postmortem("audit_failure", metrics=self.metrics,
                            extra={"violations": violations})
        return violations

    def _trimmed_output(self, st: _SlotState):
        """(gen, text, stop_hit, hit_eos) for a slot's output so far —
        budget-trimmed, EOS-trimmed, stop-sequence-applied.  The ONE
        implementation of output trimming, shared by the normal finish
        path, the per-block streaming cut, and both cancel-sweep paths
        (live slots here; queued preempted entries via _trim_tokens)."""
        return self._trim_tokens(st.prior + st.generated, st.max_new,
                                 st.req.stop)

    def _note_first_token(self, st: _SlotState, t_enq: dict) -> None:
        """Record a TTFT sample at a request's FIRST host-visible token.
        The clock starts at SCHEDULER enqueue (run()/submit() encode), so
        the sample covers queue wait + prefill + first decode block within
        this engine stream; time spent upstream (the HTTP batcher's
        ~20 ms micro-batch window, or waiting behind a PREVIOUS wave's
        run()) is not included — this is an engine metric, not a wire
        metric.  ``prior`` non-empty means a preemption continuation whose
        real first token was already recorded in an earlier slot life."""
        t0 = t_enq.pop(st.req.request_id, None)
        if t0 is not None and not st.prior:
            now = time.time()
            self._ttft_min = min(self._ttft_min, now - t0)
            self._h_ttft.observe(now - t0)
            self._slo.observe_ttft(now - t0)
            if self._tr:
                self._tr.instant("first_token", ts=now,
                                 tid=self._tid(st.req))

    def _trim_tokens(self, gen: list[int], max_new: int, stop):
        gen = gen[:max_new]
        eos = self.tokenizer.eos_id
        hit_eos = eos in gen
        if hit_eos:
            gen = gen[: gen.index(eos)]
        text, stop_hit = apply_stop_sequences(
            self.tokenizer.decode(gen), stop)
        return gen, text, stop_hit, hit_eos

    def _finish_slot(self, b, slots, results, active, fresh, kv_lens,
                     last_tok, gen, text, stop_hit, finish_reason) -> None:
        """Record a slot's result and tear the slot down (pages freed,
        freed-row invariant applied).  Shared by _maybe_finish and the
        cancel sweep so finish semantics can never diverge."""
        st = slots[b]
        now = time.time()
        self._record_result(results, GenerationResult(
            request_id=st.req.request_id,
            text=text,
            prompt_tokens=st.n_prompt,
            completion_tokens=len(gen),
            finish_reason=finish_reason,
            stop_sequence=stop_hit,
            device_seconds=now - st.t_start,
        ), req=st.req)
        if self._tr:
            tid = self._tid(st.req)
            if st.t_decode_start:  # close the decode span of this slot life
                self._tr.complete("decode", st.t_decode_start, now, tid=tid,
                                  args={"completion_tokens": len(gen)})
            self._tr.instant(
                "cancel" if finish_reason == "cancelled" else "finish",
                ts=now, tid=tid,
                args={"reason": finish_reason,
                      "completion_tokens": len(gen)})
        if fresh is not None:
            fresh.append(st.req.request_id)
        self.cache.close_sequence(st.seq)
        slots[b] = None
        active[b] = False
        # freed rows must carry length 0 (same invariant as admission): a
        # stale length makes every later decode dispatch walk null pages
        # for this row, and OOB safety should not rest on the kernel clamp
        if kv_lens is not None:
            kv_lens[b] = 0
            last_tok[b] = 0

    # ------------------------------------------- disaggregated handoff

    def _orig_budget(self, req: GenerationRequest) -> int:
        """The request's REAL token budget (before the handoff_export
        clamp to 1 in _encode) — what the ticket forwards to the decode
        pod, and the is-there-anything-left-to-hand-off test."""
        return min(req.max_new_tokens, self.cfg.max_tokens,
                   self.max_len - 1)

    def _pin_handoff(self, b, slots, results, active, fresh, kv_lens,
                     last_tok, gen, text) -> None:
        """Finish a prefill-role slot as ``finish_reason="handoff"``: the
        payload (page data + resume state) is captured host-side NOW, on
        the scheduler thread — later exports then never touch the device,
        so a handler-thread fetch cannot race a dispatch that donates the
        pools.  The sequence's pages stay allocated (the pinned-for-export
        class) until release_handoff (decode ack) or the orphan sweep.
        Capture failure (injected ``handoff.export`` fault or a real
        gather error) degrades to a marked per-request error — the router
        re-prefills elsewhere; the pool stays clean."""
        st = slots[b]
        rid = st.req.request_id
        now = time.time()
        keep = self.cache.pages_needed(len(st.prompt_ids))
        try:
            t0 = time.time()
            payload = self.cache.export_sequence(st.seq, len(st.prompt_ids))
            if self._kv_quant:
                # per-slot scales, frozen at prefill: the decode pod
                # scatters them into ITS slot's scale rows at admission.
                # One batched fetch — each device_get is a blocking
                # host sync the dispatch loop stalls on
                ks, vs = self._timed_get((self.kscale[:, b],
                                          self.vscale[:, b]))
                payload["kscale"] = np.asarray(ks)
                payload["vscale"] = np.asarray(vs)
            self._h_handoff_capture.observe(time.time() - t0)
        except Exception as e:  # noqa: BLE001 - degrade per request
            logger.warning("handoff export capture failed for request %d",
                           rid, exc_info=True)
            self._record_result(results, GenerationResult(
                request_id=rid, prompt_tokens=st.n_prompt,
                finish_reason="error",
                error=f"handoff export failed: {type(e).__name__}: {e}"),
                req=st.req)
            if fresh is not None:
                fresh.append(rid)
            self.cache.close_sequence(st.seq)
            slots[b] = None
            active[b] = False
            if kv_lens is not None:
                kv_lens[b] = 0
                last_tok[b] = 0
            return
        # resume state: exactly the tokens whose KV is exported, plus the
        # sampled-but-not-yet-written first token the decode pod feeds
        payload["tokens"] = [int(t) for t in st.prompt_ids]
        payload["generated"] = [int(t) for t in gen]
        payload["n_prompt"] = st.n_prompt
        # the trace rides the payload across the pod boundary: the decode
        # pod's import continues this request's span chain under the SAME
        # trace id even when the ticket is followed without the router
        if st.req.trace_id:
            payload["trace_id"] = st.req.trace_id
        # the tenant label crosses the pod boundary the same way: the
        # decode pod bills its share of the request to the same tenant
        if st.req.tenant:
            payload["tenant"] = st.req.tenant
        # ... and the QoS class: the decode leg competes in the class
        # the prefill leg was admitted under (fleet/qos.py)
        if st.req.qos_class:
            payload["qos_class"] = st.req.qos_class
        # budget-overshoot pages (decode-capacity growth past the prompt)
        # are NOT part of the handoff — release them before pinning
        if len(st.seq.pages) > keep:
            self.cache.allocator.free(st.seq.pages[keep:])
            st.seq.pages = st.seq.pages[:keep]
        st.seq.length = len(st.prompt_ids)
        rem = remaining_budget(st.req)
        ttl = self.cfg.handoff_ttl_s
        if rem is not None:
            # deadline budgets forward through the ticket: pages pinned
            # past the request's own deadline are already worthless
            ttl = max(0.5, min(ttl, rem))
        with self._pinned_lock:
            self._pinned[rid] = {"seq": st.seq, "payload": payload,
                                 "deadline_t": now + ttl, "t_pinned": now}
        self._update_pinned_gauge()
        self._c_handoff_exports.inc()
        self._record_result(results, GenerationResult(
            request_id=rid, text=text, prompt_tokens=st.n_prompt,
            completion_tokens=len(gen), finish_reason="handoff",
            device_seconds=now - st.t_start), req=st.req)
        if self._tr:
            tid = self._tid(st.req)
            if st.t_decode_start:
                self._tr.complete("decode", st.t_decode_start, now, tid=tid,
                                  args={"completion_tokens": len(gen)})
            self._tr.instant("handoff_export", ts=now, tid=tid,
                             args={"pages": len(st.seq.pages),
                                   "kv_len": len(st.prompt_ids)})
        if fresh is not None:
            fresh.append(rid)
        slots[b] = None
        active[b] = False
        if kv_lens is not None:
            kv_lens[b] = 0
            last_tok[b] = 0

    def _update_pinned_gauge(self) -> None:
        with self._pinned_lock:
            total = sum(len(r["seq"].pages) for r in self._pinned.values())
        self._g_pinned_pages.set(total)

    def export_handoff(self, request_id: int) -> dict:
        """Wire payload of a pinned export (serving-layer ticket fetch).
        Reads the host-side copy captured at pin time — no device access,
        so handler threads never race the dispatch loop — and is
        repeatable: a retried transfer re-reads the same payload.  Raises
        ``KeyError`` for unknown/released ids (the ticket 410 path)."""
        with self._pinned_lock:
            return self._pinned[request_id]["payload"]

    def release_handoff(self, request_id: int, orphaned: bool = False) -> int:
        """Release a pinned export's pages: the decode side acked (or,
        with ``orphaned=True``, the ticket deadline expired un-acked and
        the sweep is reclaiming).  Idempotent — unknown ids no-op, so a
        duplicate ack can never double-free.  Returns pages released.

        Callable from any thread.  While a run is live the actual free is
        DEFERRED to the scheduler thread's next block boundary (the
        allocator and prefix-cache refcounts are unsynchronized — only
        the dispatch loop may touch them mid-run); idle, the free happens
        inline under the pin lock, which a starting run must take before
        its first allocation."""
        with self._pinned_lock:
            rec = self._pinned.pop(request_id, None)
            if rec is None:
                return 0
            n = len(rec["seq"].pages)
            if self._run_live:
                self._release_deferred.append((request_id, rec, orphaned))
            else:
                self.cache.close_sequence(rec["seq"])
        self._update_pinned_gauge()
        if orphaned:
            self._c_handoff_orphaned.inc(n)
            logger.warning("handoff %d orphaned: %d pinned pages reclaimed",
                           request_id, n)
        if self._tr:
            trace = rec["payload"].get("trace_id")
            tid = (self._tr.track_for(trace) if trace
                   else req_tid(request_id))
            self._tr.instant("handoff_release", tid=tid,
                             args={"pages": n, "orphaned": orphaned})
        return n

    def _drain_released(self) -> None:
        """Free pages of releases parked while the run was live.  Runs on
        the scheduler thread only (block boundaries + end of run).  The
        frees happen UNDER the pin lock: the end-of-run drain executes
        after _run_live flips False, when an HTTP ack can already free
        inline — the shared lock serializes the two (the allocator has no
        synchronization of its own)."""
        with self._pinned_lock:
            items, self._release_deferred = self._release_deferred, []
            for rid, rec, _orphaned in items:
                try:
                    self.cache.close_sequence(rec["seq"])
                except ValueError:
                    logger.exception("deferred handoff release of request "
                                     "%d failed", rid)

    def sweep_handoffs(self, now: float | None = None) -> int:
        """Reclaim pinned exports whose ticket deadline expired (the
        orphan sweeper's engine half).  Returns pages released."""
        now = time.time() if now is None else now
        with self._pinned_lock:
            expired = [rid for rid, r in self._pinned.items()
                       if r["deadline_t"] <= now]
        return sum(self.release_handoff(rid, orphaned=True)
                   for rid in expired)

    def pinned_handoffs(self) -> dict[int, int]:
        """rid -> pinned page count snapshot (tests + metrics)."""
        with self._pinned_lock:
            return {rid: len(r["seq"].pages)
                    for rid, r in self._pinned.items()}

    # ------------------------------------------- cross-host KV migration

    def kv_export(self, preamble: str) -> dict | None:
        """Page-set export for cross-host KV migration (docs/SERVING.md
        "KV fabric"): the warm radix state of one published preamble
        hash — resident pages gathered device→host, spilled/disk
        segments read from their tiers — framed as one wire payload a
        sibling's ``kv_import`` installs.  This host's cache is left
        untouched (migration COPIES warmth; the drained host's state
        drops with the host).

        Control-plane only: callable while no run is live (a draining
        host has stopped serving; the router migrates between runs) —
        returns None mid-run, for unknown/cold preambles, and with the
        prefix cache off.  A torn disk entry truncates the set (fewer
        migrated tokens, never a failed export); the ``migrate.export``
        fault site fires before any capture work.

        Holds the pin lock for the duration: a run flips ``_run_live``
        under the same lock before its first allocation, so an export
        can never overlap a starting dispatch loop (the allocator and
        radix tree have no synchronization of their own)."""
        with self._pinned_lock:
            if self._run_live:
                return None
            return self._kv_export_locked(preamble)

    def _kv_export_locked(self, preamble: str) -> dict | None:
        if self._prefix_cache is None:
            return None
        ent = self._preambles.get(preamble)
        if ent is None:
            return None
        faults.fire("migrate.export")
        ids = list(ent["ids"])
        ps = self.cfg.page_size
        pages, matched, chain = self._prefix_cache.match_hier(ids)
        k_parts: list[np.ndarray] = []
        v_parts: list[np.ndarray] = []
        tokens = 0
        try:
            if matched:
                pay = self.cache.export_pages(pages)
                k_parts.append(pay["k"])
                v_parts.append(pay["v"])
                tokens += matched
        finally:
            if matched:
                self.cache.allocator.free(pages)
        for node, n_tok in chain:
            pay = self._prefix_cache.spill_payload(node)
            if pay is None:
                break
            k_parts.append(np.asarray(pay["k"]))
            v_parts.append(np.asarray(pay["v"]))
            tokens += n_tok
        if tokens == 0:
            return None
        k = (k_parts[0] if len(k_parts) == 1
             else np.concatenate(k_parts, axis=1))
        v = (v_parts[0] if len(v_parts) == 1
             else np.concatenate(v_parts, axis=1))
        kh, _ps, hd = (int(x) for x in self.cache.k.shape[1:])
        self._c_migrate_exports.inc()
        return {
            "kind": "kv_pageset",
            "version": 1,
            "preamble": preamble,
            "tokens": tokens,
            "ids": [int(t) for t in ids[:tokens]],
            "n_pages": tokens // ps,
            "page_size": ps,
            "n_layers": self.cache.n_layers,
            "n_kv_heads": kh,
            "head_dim": hd,
            "dtype": str(self.cache.k.dtype),
            "k": k,
            "v": v,
        }

    def kv_import(self, payload: dict) -> int:
        """Install a migrated page set into this engine's prefix cache:
        allocate device pages, scatter the payload (sync — control
        plane, not the hot path), insert under the payload's token ids,
        and publish the preamble into the routed summary so follow-up
        requests see it warm here.  Returns tokens now warm.

        Rejection discipline mirrors ``import_sequence``: geometry/
        dtype/framing mismatches raise ``ValueError`` (the router's
        cold-migration fallback owns the retry), pool pressure raises
        ``OutOfPages`` after a reclaim attempt, and a live run raises
        ``RuntimeError`` (busy — the caller retries between runs).  The
        ``migrate.import`` fault site fires before any mutation.

        Like ``kv_export``, holds the pin lock for the duration so a
        starting run can never overlap the scatter/insert."""
        with self._pinned_lock:
            if self._run_live:
                raise RuntimeError("engine busy; kv import retries between "
                                   "runs")
            return self._kv_import_locked(payload)

    def _kv_import_locked(self, payload: dict) -> int:
        if self._prefix_cache is None:
            raise ValueError("prefix cache off; nothing to import into")
        faults.fire("migrate.import")
        kh, ps, hd = (int(x) for x in self.cache.k.shape[1:])
        want = {"page_size": self.cache.page_size,
                "n_layers": self.cache.n_layers, "n_kv_heads": kh,
                "head_dim": hd, "dtype": str(self.cache.k.dtype)}
        for key, val in want.items():
            got = payload.get(key)
            if got != val:
                raise ValueError(
                    f"incompatible kv payload: {key}={got!r}, this pool "
                    f"has {val!r}")
        ids = [int(t) for t in payload.get("ids", ())]
        n = int(payload.get("n_pages", 0) or 0)
        tokens = int(payload.get("tokens", 0) or 0)
        if n <= 0 or tokens != n * ps or len(ids) != tokens:
            raise ValueError(
                f"inconsistent kv payload framing: {n} pages / {tokens} "
                f"tokens / {len(ids)} ids (page_size {ps})")
        k = np.asarray(payload["k"])
        v = np.asarray(payload["v"])
        shape = (self.cache.n_layers, n, kh, ps, hd)
        if k.shape != shape or v.shape != shape:
            raise ValueError(
                f"kv payload shape {k.shape} != expected {shape}")
        if n > self.cache.allocator.free_count:
            self._prefix_cache.evict(n - self.cache.allocator.free_count)
        pages = self.cache.alloc_pages(n)
        try:
            self.cache.import_pages(
                pages, {"k": k, "v": v, "dtype": payload["dtype"]},
                sync=True)
            self._prefix_cache.insert(ids, pages, max_tokens=tokens)
        finally:
            # the cache holds its own refs on adopted pages; ours drop
            self.cache.allocator.free(pages)
        key = payload.get("preamble")
        if isinstance(key, str) and key:
            self._preamble_tick += 1
            self._preambles[key] = {"ids": tuple(ids),
                                    "tick": self._preamble_tick}
            self._summary_memo = None
        self._c_migrate_imports.inc()
        self._c_migrate_tokens.inc(tokens)
        return tokens

    def _admit_import(self, b, queue, slots, results, fresh, kv_lens,
                      last_tok, active, temps, top_k, top_p) -> bool:
        """Admit the queue head's IMPORTED sequence (disaggregated decode
        role): scatter the transferred pages into the local pool and enter
        the slot directly in decode phase — no prefill ever dispatches for
        it.  Returns False on page back-pressure (the entry stays queued
        and admission waits, exactly like the prefill path); a payload
        failure (corrupt, incompatible pool geometry, token mismatch, or
        an injected ``handoff.import`` fault) terminates the entry with a
        MARKED error result — the router's re-prefill fallback owns the
        retry, and the pool stays clean either way."""
        req, ids, max_new, n_prompt, prior, t0 = queue[0]
        state = req.handoff_state
        # continue the exporter's trace: the payload carries the trace id
        # across the pod boundary, so the decode-side spans land on the
        # SAME fleet-wide chain (a request arriving with its own id —
        # the router re-sent the header — keeps it; they are equal anyway)
        if not req.trace_id and isinstance(state.get("trace_id"), str):
            req.trace_id = state["trace_id"]
        if not req.tenant and isinstance(state.get("tenant"), str):
            req.tenant = state["tenant"]
        try:
            need = int(state.get("n_pages", 0) or 0)
        except (TypeError, ValueError):
            need = -1
        if not 0 < need <= min(self.cache.max_pages_per_slot,
                               self.cache.num_pages - 1):
            # an unsatisfiable page claim must error-terminate, never wait:
            # treating it as back-pressure would wedge the queue head
            # forever and starve everything behind it
            queue.popleft()
            self._record_result(results, GenerationResult(
                request_id=req.request_id, prompt_tokens=n_prompt,
                finish_reason="error",
                error=f"handoff import failed: page claim {need} exceeds "
                      "this pool's capacity (geometry drift or corrupt "
                      "ticket)"), req=req)
            fresh.append(req.request_id)
            return True
        if need > self.cache.allocator.free_count:
            if self._prefix_cache is not None:
                self._prefix_cache.evict(
                    need - self.cache.allocator.free_count)
            if need > self.cache.allocator.free_count:
                return False
        queue.popleft()
        t_imp = time.time()
        try:
            gen = [int(t) for t in state.get("generated", ())]
            toks = [int(t) for t in state.get("tokens", ())]
            kv_len = int(state.get("kv_len", -1))
            if toks != list(ids):
                # tokenizer/config drift between pods: the imported KV
                # covers different token ids than this pod derives from
                # the same prompt — resuming would be silent corruption
                raise ValueError(
                    f"token mismatch: payload covers {len(toks)} prompt "
                    f"tokens, this pod encodes {len(ids)}"
                    + ("" if len(toks) != len(ids)
                       else " (same count, different ids)"))
            if kv_len != len(ids):
                raise ValueError(
                    f"inconsistent payload: kv_len {kv_len} != "
                    f"{len(ids)} prompt tokens")
            if not gen:
                raise ValueError("handoff state carries no resume token")
            scales = None
            if self._kv_quant:
                # int8 pool: the exporter's per-slot scales are REQUIRED
                # and shape-checked here, inside the marked-error guard —
                # silently keeping the previous slot occupant's scales
                # would dequantize the imported pages into garbage
                want = ((int(self.kscale.shape[0]),)
                        + tuple(int(s) for s in self.kscale.shape[2:]))
                try:
                    ks = np.asarray(state["kscale"], dtype=np.float32)
                    vs = np.asarray(state["vscale"], dtype=np.float32)
                except (KeyError, TypeError, ValueError) as e:
                    raise ValueError(
                        f"int8 pool payload missing/bad scales: {e}") from e
                if ks.shape != want or vs.shape != want:
                    raise ValueError(
                        f"scale shape {ks.shape}/{vs.shape} != pool's "
                        f"{want}")
                scales = (ks, vs)
            seq = self.cache.import_sequence(state)
            # consumed: if this slot is later PREEMPTED, its continuation
            # entry (prompt + generated so far) must re-admit through the
            # normal prefill path — routing it back through here would
            # fail the token-mismatch guard against the original prompt
            req.handoff_state = None
        except OutOfPages:
            queue.appendleft((req, ids, max_new, n_prompt, prior, t0))
            return False
        except Exception as e:  # noqa: BLE001 - degrade per request
            logger.warning("handoff import failed for request %d",
                           req.request_id, exc_info=True)
            self._record_result(results, GenerationResult(
                request_id=req.request_id, prompt_tokens=n_prompt,
                finish_reason="error",
                error=f"handoff import failed: {type(e).__name__}: {e}"),
                req=req)
            fresh.append(req.request_id)
            return True
        now = time.time()
        if req.deadline_s is not None:
            self._h_deadline_remaining.observe(req.deadline_s - now)
        st = _SlotState(req=req, prompt_ids=ids, max_new=max_new, seq=seq,
                        t_start=now, n_prompt=n_prompt)
        st.phase = "decode"
        st.prefill_pos = len(ids)
        st.kv_len = kv_len
        st.generated = gen
        st.t_admit = now
        st.t_decode_start = now
        slots[b] = st
        kv_lens[b] = st.kv_len
        last_tok[b] = gen[-1]
        active[b] = True
        temps[b] = req.temperature
        top_k[b] = req.top_k
        top_p[b] = min(max(req.top_p, 0.0), 1.0)
        if scales is not None:
            # the exporter's per-slot scales (validated above), scattered
            # into THIS slot's rows — imported int8 pages dequantize with
            # their own scales
            self.kscale = self.kscale.at[:, b].set(jnp.asarray(scales[0]))
            self.vscale = self.vscale.at[:, b].set(jnp.asarray(scales[1]))
        self.seed_history(b, st)
        self._c_handoff_imports.inc()
        self._h_handoff_import.observe(time.time() - t_imp)
        self._g_peak_pages.track_max(self.cache.num_pages - 1
                                     - self.cache.allocator.free_count)
        self._g_peak_slots.track_max(sum(s is not None for s in slots))
        if self._tr:
            self._tr.instant("handoff_import", ts=now,
                             tid=self._tid(req),
                             args={"slot": b, "kv_len": kv_len,
                                   "pages": len(seq.pages)})
        # stream the already-generated first token immediately (the slot
        # cannot be finished here: the pin guard excluded EOS/stop/budget-
        # complete first tokens from ever becoming handoffs)
        self._maybe_finish(b, slots, results, active, fresh, kv_lens,
                           last_tok)
        return True

    # ------------------------------------------------------------ internals

    def _encode(self, req: GenerationRequest) -> tuple[list[int], int]:
        text = (req.system_prompt + "\n\n" if req.system_prompt else "") + req.prompt
        ids = [self.tokenizer.bos_id] + self.tokenizer.encode(text)
        # max_new additionally caps at max_len-1: a budget >= the context
        # window would make the truncation limit below non-positive, turning
        # the middle-truncation slice into prompt DUPLICATION (negative-index
        # wraparound) or an empty prompt — and the admission invariant
        # ("every submitted request fits") rests on limit >= 1
        max_new = min(req.max_new_tokens, self.cfg.max_tokens,
                      self.max_len - 1)
        limit = self.max_len - max_new
        if len(ids) > limit:
            head, tail = limit // 2, limit - limit // 2
            ids = ids[:head] + ids[-tail:]
        if req.handoff_export:
            # prefill role: stop after the first token (the ticket carries
            # the rest of the budget).  Clamped AFTER the truncation math —
            # the prompt cut must be byte-identical to what a colocated run
            # (or the decode pod re-encoding this prompt) produces, or the
            # imported KV would disagree with the decode side's token ids.
            max_new = 1
        return ids, max_new

    # ------------------------------------------- page growth / preemption

    def _ensure_decode_capacity(self, slots, queue, kv_lens, last_tok,
                                active, extra_tokens: int | None = None
                                ) -> list[int]:
        """Grow each active decode slot's pages to cover the coming decode
        block — ``extra_tokens`` overrides the default block growth (a
        mixed fused step advances decode rows by ONE token, so it grows by
        one).  On pool exhaustion,
        preempt the YOUNGEST decode slot — free its pages and requeue it at
        the queue head as a continuation (prompt + generated-so-far
        re-prefills once pages free up) — and retry.  When no OTHER decode
        slot exists (the pages are held by mid-prefill slots), the slot is
        STALLED for this dispatch instead of discarding its own progress:
        its row is masked off, and the masked row's dummy writes land on
        the null page (unallocated table columns are zero).  Returns the
        stalled rows; the caller re-activates them after the dispatch.
        Deadlock-free: the pool holds at least one full-length sequence
        (pool sizing in __init__), so a slot alone in the pool always
        grows, and prefill slots always finish without growth."""
        block = (self.decode_block + self.spec_k if extra_tokens is None
                 else extra_tokens)
        stalled: list[int] = []
        for b in range(self.B):
            st = slots[b]
            if st is None or not active[b] or st.phase != "decode":
                continue
            target = min(st.kv_len + block, self.max_len)
            while True:
                try:
                    self.cache.grow(st.seq, target)
                    break
                except OutOfPages:
                    if self._qos is not None:
                        victim = self._qos_victim_slot(slots, active,
                                                       exclude=b)
                    else:
                        victim = self._youngest_decode_slot(slots, active,
                                                            exclude=b)
                    if victim is None:
                        stalled.append(b)
                        active[b] = False
                        self._c_stalls.inc()
                        break
                    self._preempt(victim, slots, queue, kv_lens, last_tok,
                                  active)
        return stalled

    def _qos_victim_slot(self, slots, active, exclude: int) -> int | None:
        """QoS preemption policy (fleet/qos.py): the WORST active decode
        slot by (batch class first, highest normalized windowed usage,
        youngest) — over-quota bulk work pays for the pool before a live
        session does.  Uniform traffic ties the first two keys and the
        rule degenerates to the youngest-slot order below."""
        best, best_key = None, None
        for b in range(self.B):
            st = slots[b]
            if (b == exclude or st is None or not active[b]
                    or st.phase != "decode"):
                continue
            key = self._qos.victim_key(st.req, st.t_start)
            if best_key is None or key >= best_key:
                best, best_key = b, key
        if best is not None:
            self._qos.note_preempt()
            if self._tr:
                # fleet-drift contract (trace.py): a QoS preemption is an
                # auditable scheduling decision, visible in the trace
                self._tr.instant("qos_preempt",
                                 args={"slot": best,
                                       "tenant": slots[best].req.tenant
                                       or "default"})
        return best

    def _youngest_decode_slot(self, slots, active, exclude: int) -> int | None:
        """Latest-admitted active decode slot, or None if only ``exclude``
        (the slot being grown) qualifies."""
        best, best_t = None, -1.0
        for b in range(self.B):
            st = slots[b]
            if (b == exclude or st is None or not active[b]
                    or st.phase != "decode"):
                continue
            if st.t_start >= best_t:
                best, best_t = b, st.t_start
        return best

    def _prefetch_spilled(self, chain, cached_pages: list[int],
                          fresh: list[int], cached_tokens: int):
        """Restore the matched spilled segments (host tier → device) into
        their share of the freshly allocated pages, in positional order.
        Each successful segment promotes its radix node back to resident
        on those pages (prefix_cache.prefetch_into) and extends the
        usable match; the FIRST failure — the ``prefix.prefetch`` fault,
        or an entry the host budget dropped between match and here —
        truncates the match at that segment, whose pages (and every later
        segment's) simply become prefill tail.  Returns
        ``(cached_pages, fresh_tail, cached_tokens, prefetched_tokens)``."""
        ps = self.cfg.page_size
        used = 0
        got_tokens = 0
        t0 = time.time()
        for node, n_tok in chain:
            npg = n_tok // ps
            dest = fresh[used: used + npg]
            try:
                # injection site: fires BEFORE any mutation for this
                # segment — a fault costs exactly the segment's reuse,
                # never a wedged admission
                faults.fire("prefix.prefetch")
                self._prefix_cache.prefetch_into(node, dest, self.cache,
                                                 sync=self._host_kv_sync)
            except Exception:  # noqa: BLE001 - degrade to re-prefill
                logger.warning("KV prefetch failed; re-prefilling the "
                               "spilled segment", exc_info=True)
                break
            used += npg
            got_tokens += n_tok
        if used:
            self._h_prefetch.observe(time.time() - t0)
            self._c_prefetch_pages.inc(used)
            self._c_prefetch_tokens.inc(got_tokens)
            self._c_spilled_hits.inc()
            # perf attribution: the scatter's HBM bytes ride into the
            # next block's wall — count them and keep that block from
            # polluting the clean-sample EMA
            self._perf.note_prefetch(used * self.cache.page_payload_bytes())
        return (cached_pages + fresh[:used], fresh[used:],
                cached_tokens + got_tokens, got_tokens)

    def _note_preamble(self, req: GenerationRequest) -> None:
        """Record a request's shared preamble for the published radix
        summary (prefix_summary): key = api.preamble_key over the same
        text region _cache_insert donates; the encoded token ids are kept
        so summary publication can re-probe LIVE resident/spilled
        coverage against the tree.  Bounded LRU (32 preambles — a fleet
        shares a handful of map/reduce/system preambles by design)."""
        key = preamble_key(req.system_prompt, req.prompt, req.cache_prefix)
        if key is None:
            return
        self._preamble_tick += 1
        ent = self._preambles.get(key)
        if ent is None:
            text = preamble_text(req.system_prompt, req.prompt,
                                 req.cache_prefix)
            ids = tuple([self.tokenizer.bos_id]
                        + self.tokenizer.encode(text))
            # tick stamped BEFORE the LRU trim: a zero-tick insert would
            # make the brand-new entry the min-by-tick victim and the
            # summary would stop learning past 32 preambles
            ent = {"ids": ids, "tick": self._preamble_tick}
            self._preambles[key] = ent
            while len(self._preambles) > 32:
                oldest = min(self._preambles,
                             key=lambda k: self._preambles[k]["tick"])
                del self._preambles[oldest]
        ent["tick"] = self._preamble_tick

    def prefix_summary(self, top_k: int = 16) -> list[dict]:
        """Compact radix summary for the control plane (served through
        /healthz and the JSON /metrics page): the top-K recently seen
        preamble hashes with their depth and LIVE resident/spilled
        coverage (prefix_cache.peek — full-page capacity view).  The
        router routes sticky-by-expected-prefix-hit on these
        (serving/router.py).  Callable from HTTP handler threads while
        the scheduler runs: reads are guarded snapshots, memoized for
        1 s, and degrade to the previous summary on a raced mutation."""
        if self._prefix_cache is None:
            return []
        now = time.time()
        memo = self._summary_memo
        if memo is not None and now - memo[0] < 1.0:
            return memo[1]
        out: list[dict] = []
        try:
            entries = sorted(self._preambles.items(),
                             key=lambda kv: -kv[1]["tick"])[:top_k]
            for key, ent in entries:
                cov = self._prefix_cache.peek(list(ent["ids"]))
                out.append({"hash": key,
                            "depth_tokens": len(ent["ids"]),
                            "tick": ent["tick"], **cov})
        except RuntimeError:  # dict/tree resized mid-walk: keep the last
            return memo[1] if memo is not None else []
        self._summary_memo = (now, out)
        return out

    def _cache_insert(self, st: _SlotState) -> None:
        """Donate a fully-prefilled slot's prompt-page prefix to the prefix
        cache.  The ``cache_prefix`` request hint (leading PROMPT chars
        expected to be shared) caps adoption so per-chunk unique bodies
        don't bloat the tree.  A hint of 0 means the prompt body shares
        nothing — the shared system preamble (always encoded FIRST by
        _encode) is still donated; only when there is no system prompt
        either is there nothing to cache."""
        if self._prefix_cache is None:
            return
        # summary bookkeeping rides the donation point: the preamble just
        # became (or refreshed as) cached content worth routing onto
        self._note_preamble(st.req)
        cap = None
        hint = st.req.cache_prefix
        if hint is not None:
            if hint < 0:
                return
            # token-level cap: bos + encoded system preamble + shared prompt
            # head (api.preamble_text — the SAME region the routing key
            # hashes, so placement and donation can never drift apart).
            # Approximate at the char boundary by design (the cap rounds
            # up to a page inside insert) — see GenerationRequest.
            text = preamble_text(st.req.system_prompt, st.req.prompt, hint)
            if not text:
                return  # hint 0 and no system prompt: nothing shared
            cap = 1 + len(self.tokenizer.encode(text))
        try:
            self._prefix_cache.insert(st.prompt_ids, st.seq.pages,
                                      max_tokens=cap)
        except Exception:
            # caching is an optimization: an insertion fault (injected or
            # real) must cost a cache hit, never the request
            logger.warning("prefix-cache insert failed; request continues "
                           "uncached", exc_info=True)

    def _preempt(self, b, slots, queue, kv_lens, last_tok, active) -> None:
        st = slots[b]
        # keep the victim's prompt prefix cached: its continuation (and any
        # same-preamble neighbor) re-matches instead of re-prefilling; the
        # pages stay evictable, so this never blocks the reclaim that the
        # preemption itself is after
        if st.phase == "decode":
            self._cache_insert(st)
        self.cache.close_sequence(st.seq)
        # continuation: generated tokens fold into the prefill ids, original
        # prompt length and prior output ride along for accounting/finish.
        # Insert ordered by t_start among the continuations already at the
        # queue head (a bare appendleft re-queued multiple same-pass victims
        # youngest-first — a fairness inversion under sustained pressure,
        # ADVICE r2): older continuations keep queue priority.
        entry = (st.req, st.prompt_ids + st.generated, st.max_new,
                 st.n_prompt, st.prior + st.generated, st.t_start)
        pos = 0
        while (pos < len(queue) and queue[pos][5] is not None
               and queue[pos][5] <= st.t_start):
            pos += 1
        queue.insert(pos, entry)
        slots[b] = None
        active[b] = False
        kv_lens[b] = 0  # same invariant as admission/_maybe_finish: a freed
        last_tok[b] = 0  # row must never carry a stale length into a kernel
        self._c_preemptions.inc()
        if self._tr:
            now = time.time()
            tid = self._tid(st.req)
            if st.t_decode_start:  # close this slot life's decode span
                self._tr.complete("decode", st.t_decode_start, now, tid=tid,
                                  args={"preempted": True})
            self._tr.instant("preempt", ts=now, tid=tid,
                             args={"slot": b,
                                   "generated_so_far": len(st.prior)
                                   + len(st.generated)})
        logger.debug("preempted slot %d (request %d) under page pressure",
                     b, st.req.request_id)

    def _maybe_finish(self, b, slots, results, active, fresh=None,
                      kv_lens=None, last_tok=None):
        st = slots[b]
        # decode runs in fixed blocks, so a slot can overshoot its budget by
        # up to decode_block-1 tokens between host syncs — trim to budget
        # (_trimmed_output).  prior = tokens generated before a preemption
        # (already re-prefilled as part of prompt_ids; still OUTPUT tokens).
        gen, text, stop_hit, hit_eos = self._trimmed_output(st)
        finished = hit_eos or stop_hit or len(gen) >= st.max_new
        if self._on_tokens is not None:
            # stream the block's new text: cut from the trimmed text, so the
            # deltas' concatenation is exactly the final result text.  A
            # multi-byte UTF-8 sequence straddling a block boundary decodes
            # as trailing U+FFFD until its bytes complete — hold those back
            # (they'd change retroactively); a real U+FFFD flushes at finish.
            # Guarded against non-prefix-stable decoders (HF tokenizers'
            # cleanup can rewrite earlier characters as tokens arrive): a
            # delta is emitted ONLY while the new text extends what was
            # already sent — on violation the stream FREEZES (undershoots)
            # rather than ever emitting characters that later change; the
            # non-streamed result text stays authoritative.
            sent = self._streamed.get(st.req.request_id, "")
            frontier = len(text)
            if not finished:
                while frontier > len(sent) and text[frontier - 1] == "�":
                    frontier -= 1
                if st.req.stop:
                    # a stop string can straddle block boundaries: a future
                    # match starts past len(text) - len(stop), so keeping
                    # max(len)-1 chars unstreamed guarantees no emitted char
                    # ever precedes a later truncation point
                    hold = max((len(s) for s in st.req.stop if s),
                               default=1) - 1
                    frontier = min(frontier, len(text) - hold)
            if frontier > len(sent) and text.startswith(sent):
                self._on_tokens(st.req.request_id, text[len(sent):frontier])
                self._streamed[st.req.request_id] = text[:frontier]
        if finished:
            if (st.req.handoff_export and not hit_eos and stop_hit is None
                    and not st.prior
                    and len(gen) < self._orig_budget(st.req)):
                # prefill role: the request is NOT complete — its budget
                # was clamped to 1 at encode; pin the pages for export
                # instead of freeing them.  A first token that IS terminal
                # (EOS, stop hit, or a genuine 1-token budget) takes the
                # normal finish below: there is nothing left to hand off
                # and the serving layer returns the completion directly.
                self._pin_handoff(b, slots, results, active, fresh,
                                  kv_lens, last_tok, gen, text)
                return
            finish = "stop" if (hit_eos or stop_hit) else "length"
            self._finish_slot(b, slots, results, active, fresh, kv_lens,
                              last_tok, gen, text, stop_hit, finish)

    # ------------------------------------------------- mixed dispatch

    def _pick_mixed_prefill(self, slots) -> int | None:
        """The prefilling slot whose slice rides this mixed step: oldest
        admission first (FIFO — every admitted prompt advances within a
        bounded number of steps), ties on slot index.  ONE slot per step
        by design (SARATHI): the slice is clipped to the step budget
        anyway, and a single contiguous slice keeps the fused program's
        shape zoo to (slice bucket, page window) pairs."""
        best, best_t = None, float("inf")
        for b in range(self.B):
            st = slots[b]
            if st is None or st.phase != "prefill":
                continue
            if st.t_admit < best_t:
                best, best_t = b, st.t_admit
        return best

    def _mixed_iteration(self, slots, queue, results, fresh, kv_lens,
                         last_tok, active, temps, top_k, top_p, t_enq,
                         last_block_t):
        """One SARATHI mixed step: every live decode row advances and one
        prefilling slot's next prompt slice (clipped to
        ``mixed_token_budget - decode_tokens``) rides the SAME span
        dispatch (``_rpa_mixed_iteration``) — decode cadence continues
        through the admission.  Returns ``(handled, last_block_t)``;
        ``handled=False`` (nothing to mix, or the budget left no room for
        a slice) falls back to the alternating path with no state
        disturbed beyond capacity growth."""
        pf = self._pick_mixed_prefill(slots)
        has_decode = any(
            slots[b] is not None and active[b]
            and slots[b].phase == "decode" for b in range(self.B))
        if pf is None or not has_decode:
            return False, last_block_t
        return self._rpa_mixed_iteration(
            pf, slots, queue, results, fresh, kv_lens, last_tok,
            active, temps, top_k, top_p, t_enq, last_block_t)

    # ------------------------------------------- ragged span dispatch (RPA)

    def _get_rpa_fn(self, tpb: int, w: int):
        """Unified ragged-span program (ISSUE 16 tentpole): every dispatch
        is a list of (row, query-span) pairs over the paged pool — each
        row carries (q_start, q_len, kv base, page-table slice) and
        per-token causal limits mask the padding, so plain decode is
        q_len=1 rows, verify q_len=k+1 rows (the spec variant below), a
        mixed step decode rows plus one prefill-slice row, and
        continuation chunks long-span rows.  ONE compile bucket family:
        (pow2 total-query-tokens, pow2 page window) replaces the
        per-phase decode/spec/mixed/chunk matrix.  Samples one token per
        dispatch row at its host-provided flat gather index."""
        key_ = ("rpa", tpb, w)
        if key_ in self._rpa_fns:
            return self._rpa_fns[key_]
        cfg = self.model_cfg
        max_len = self.max_len
        rope_max = self.max_len
        use_ragged = self._use_ragged and self._kernel_mesh() is None
        interp = self._interpret
        kv_q = bool(self._kv_quant)

        @partial(jax.jit, donate_argnums=(1, 2, 3, 4) if kv_q else (1, 2))
        def rpa_step(params, k_pages, v_pages, kscale, vscale, srows,
                     tokens, q_starts, q_lens, row_flat, base, gather_idx,
                     table, key, temps, tk, tp):
            nb = base.shape[0]
            rf = jnp.clip(row_flat, 0, nb - 1)
            off = jnp.arange(tpb) - q_starts[rf]
            # rope positions: each span token sits at consecutive absolute
            # positions from its row's own kv base (the context BEFORE
            # this dispatch); out-of-span tokens clamp to 0 — they are
            # masked from every real query and their writes park on the
            # null page, so the value never matters
            positions = jnp.clip(base[rf] + off, 0, max_len - 1)[None]
            out = forward_paged(
                params, cfg, tokens, positions, k_pages, v_pages, table,
                base, rope_max, use_ragged_kernel=use_ragged,
                interpret=interp, packed_last_idx=gather_idx,
                kv_scales=(kscale, vscale) if kv_q else None,
                scale_rows=srows if kv_q else None,
                spans=(q_starts, q_lens, row_flat),
            )
            logits, k_pages, v_pages = out[:3]
            if kv_q:
                kscale, vscale = out[3]
            # single step, no scan/vmap wrapper: sample_logits' lax.cond
            # fast paths are safe here (ops/sampling.py NOTE)
            nxt = sample_logits(logits[0], key, temps, tk, tp)
            return nxt, k_pages, v_pages, kscale, vscale

        logger.info("compiling ragged span step: B=%d token_bucket=%d "
                    "window=%d pages (ragged_kernel=%s)", self.B, tpb, w,
                    use_ragged)
        self._c_rpa_shapes.inc()
        self._rpa_fns[key_] = rpa_step
        return rpa_step

    def _get_rpa_spec_fn(self, tpb: int, w: int):
        """Spec-aware ragged span step (the spec x mixed unlock): decode
        rows carry (1 + spec_k)-token verify spans — the current token
        plus k n-gram drafts looked up IN-GRAPH from the device history
        buffer — while the piggybacked prefill slice rides the same
        dispatch, so speculation no longer yields during prefill windows
        and mixed steps stop marking rows spec-stale (the buffer appends
        in-graph).  Non-decode rows verify with n_valid=0: the machinery
        emits exactly ONE token from their last-span-position
        distribution — for the prefill row that is its sampled first
        token, through the same exact-distribution verify that keeps
        greedy outputs identical to every legacy path."""
        key_ = ("rpa_spec", tpb, w)
        if key_ in self._rpa_fns:
            return self._rpa_fns[key_]
        cfg = self.model_cfg
        max_len = self.max_len
        rope_max = self.max_len
        use_ragged = self._use_ragged and self._kernel_mesh() is None
        interp = self._interpret
        kv_q = bool(self._kv_quant)
        k = self.spec_k
        ngram = max(2, self.cfg.speculate_ngram)
        eos_id = self.tokenizer.eos_id

        if self._spec_tree:
            # Tree-spec variant (ISSUE 19 tentpole): decode rows carry a
            # (heal + 1 + W*k)-token span — leading "healing" re-sends of a
            # previously accepted non-first chain, the current token, then
            # W root-branching depth-k chains drafted IN-GRAPH by top-W
            # n-gram lookup.  Branch visibility follows parent pointers via
            # the host-built ancestor bitmasks (``anc``), rope positions are
            # depth-based via the host-built ``pos_off`` (write columns stay
            # span-offset — the caller's heal protocol fixes non-first-chain
            # columns on the next dispatch), and acceptance is the exact
            # sequential multi-candidate rule (ops/speculative.verify_tree),
            # so greedy outputs stay token-identical to every other path.
            # Same ("rpa_spec", tpb, w) bucket family — no new compile axis.
            W = self._spec_width
            from lmrs_tpu.ops.sampling import filtered_probs
            from lmrs_tpu.ops.speculative import (draft_tree_lookup,
                                                  verify_tree)

            @partial(jax.jit,
                     donate_argnums=(1, 2, 3, 4, 5) if kv_q else (1, 2, 3))
            def rpa_tree_step(params, k_pages, v_pages, buf, kscale, vscale,
                              srows, tokens, q_starts, q_lens, row_flat,
                              base, is_dec, cur_tok, hl, hoff, depth,
                              pos_off, anc, gather_idx, table, key, temps,
                              tk, tp):
                nb = base.shape[0]
                b_rows = jnp.arange(nb)[:, None]
                kvl = base + hl  # true kv_len (base excludes the heal span)
                # current token enters the history at its kv position plus
                # the row's cross-refresh hint offset (decode rows only)
                col0 = jnp.where(is_dec,
                                 jnp.minimum(kvl + hoff, max_len - 1),
                                 max_len)
                buf = buf.at[jnp.arange(nb), col0].set(cur_tok, mode="drop")
                chains, n_valid = draft_tree_lookup(
                    buf, kvl + hoff + 1, k, W, pad_id=eos_id, n=ngram,
                    depth=depth)
                n_valid = jnp.where(is_dec[:, None], n_valid, 0)
                # scatter [cur, chains] after each decode span's heal
                # prefix (heal tokens were host-built into ``tokens``)
                offs_t = jnp.arange(1 + W * k)[None, :]
                span_idx = jnp.where(is_dec[:, None],
                                     q_starts[:, None] + hl[:, None]
                                     + offs_t, tpb)
                tokens = tokens.at[0, span_idx].set(
                    jnp.concatenate(
                        [cur_tok[:, None], chains.reshape(nb, W * k)], 1),
                    mode="drop")
                rf = jnp.clip(row_flat, 0, nb - 1)
                positions = jnp.clip(base[rf] + pos_off, 0,
                                     max_len - 1)[None]
                out = forward_paged(
                    params, cfg, tokens, positions, k_pages, v_pages,
                    table, base, rope_max, use_ragged_kernel=use_ragged,
                    interpret=interp, packed_last_idx=gather_idx,
                    kv_scales=(kscale, vscale) if kv_q else None,
                    scale_rows=srows if kv_q else None,
                    spans=(q_starts, q_lens, row_flat), span_anc=anc,
                )
                logits, k_pages, v_pages = out[:3]
                if kv_q:
                    kscale, vscale = out[3]
                probs = jax.vmap(filtered_probs,
                                 in_axes=(1, None, None, None),
                                 out_axes=1)(
                    logits[0].reshape(nb, 1 + W * k, -1), temps, tk, tp)
                key, sub = jax.random.split(key)
                emit, count, chain, adepth = verify_tree(
                    probs, chains, n_valid, sub)
                # accepted tokens extend the history at hint-offset columns
                offs = jnp.arange(k + 1)[None, :]
                cols = jnp.minimum(kvl[:, None] + hoff[:, None] + 1 + offs,
                                   max_len - 1)
                cols = jnp.where((offs < count[:, None]) & is_dec[:, None],
                                 cols, max_len)
                buf = buf.at[b_rows, cols].set(emit, mode="drop")
                return (emit, count, chain, adepth, buf, k_pages, v_pages,
                        kscale, vscale)

            logger.info("compiling ragged span tree-spec step: B=%d "
                        "token_bucket=%d window=%d pages k=%d width=%d "
                        "(ragged_kernel=%s)", self.B, tpb, w, k, W,
                        use_ragged)
            self._c_rpa_shapes.inc()
            self._rpa_fns[key_] = rpa_tree_step
            return rpa_tree_step

        from lmrs_tpu.ops.sampling import filtered_probs
        from lmrs_tpu.ops.speculative import draft_lookup, verify_tokens

        @partial(jax.jit,
                 donate_argnums=(1, 2, 3, 4, 5) if kv_q else (1, 2, 3))
        def rpa_spec_step(params, k_pages, v_pages, buf, kscale, vscale,
                          srows, tokens, q_starts, q_lens, row_flat, base,
                          is_dec, cur_tok, gather_idx, table, key, temps,
                          tk, tp):
            nb = base.shape[0]
            b_rows = jnp.arange(nb)[:, None]
            offs = jnp.arange(k + 1)[None, :]
            # current token enters the history at index == its KV position
            # (decode rows only: other rows' columns land OOB and drop)
            col0 = jnp.where(is_dec, jnp.minimum(base, max_len - 1),
                             max_len)
            buf = buf.at[jnp.arange(nb), col0].set(cur_tok, mode="drop")
            draft, n_valid = draft_lookup(buf, base + 1, k, pad_id=eos_id,
                                          n=ngram)
            n_valid = jnp.where(is_dec, n_valid, 0)
            # scatter [current, drafts] into the decode spans of the flat
            # token row (prefill/pad rows keep their host-built tokens)
            span_idx = jnp.where(is_dec[:, None],
                                 q_starts[:, None] + offs, tpb)
            tokens = tokens.at[0, span_idx].set(
                jnp.concatenate([cur_tok[:, None], draft], axis=1),
                mode="drop")
            rf = jnp.clip(row_flat, 0, nb - 1)
            off = jnp.arange(tpb) - q_starts[rf]
            positions = jnp.clip(base[rf] + off, 0, max_len - 1)[None]
            out = forward_paged(
                params, cfg, tokens, positions, k_pages, v_pages, table,
                base, rope_max, use_ragged_kernel=use_ragged,
                interpret=interp, packed_last_idx=gather_idx,
                kv_scales=(kscale, vscale) if kv_q else None,
                scale_rows=srows if kv_q else None,
                spans=(q_starts, q_lens, row_flat),
            )
            logits, k_pages, v_pages = out[:3]
            if kv_q:
                kscale, vscale = out[3]
            # filtered_probs is deliberately cond-free, so this vmap over
            # the token axis is safe (ops/sampling.py NOTE)
            probs = jax.vmap(filtered_probs, in_axes=(1, None, None, None),
                             out_axes=1)(
                logits[0].reshape(nb, k + 1, -1), temps, tk, tp)
            key, sub = jax.random.split(key)
            emit, count = verify_tokens(probs, draft, n_valid, sub)
            # accepted tokens extend the history (decode rows only; the
            # final emitted token lands exactly at the next step's write
            # index — idempotent, same as the spec scan)
            cols = jnp.minimum(base[:, None] + 1 + offs, max_len - 1)
            cols = jnp.where((offs < count[:, None]) & is_dec[:, None],
                             cols, max_len)
            buf = buf.at[b_rows, cols].set(emit, mode="drop")
            return emit, count, buf, k_pages, v_pages, kscale, vscale

        logger.info("compiling ragged span spec step: B=%d token_bucket=%d "
                    "window=%d pages k=%d (ragged_kernel=%s)", self.B, tpb,
                    w, k, use_ragged)
        self._c_rpa_shapes.inc()
        self._rpa_fns[key_] = rpa_spec_step
        return rpa_spec_step

    def _tree_span_template(self, hl: int):
        """(pos_off, ancestor-bitmask) template for a tree-spec decode
        span with ``hl`` leading heal tokens: span-local layout is
        [heal_0..heal_{hl-1}, cur, chain_0 (k), ..., chain_{W-1} (k)].
        Heal tokens and cur keep the anc == 0 sentinel (plain causal
        rule); chain c's node j sees the heal+cur prefix plus its own
        chain up to itself.  Rope positions are DEPTH-based — chain c
        node j sits at kv offset hl+1+j regardless of c — while K/V
        writes land at span-offset columns (the heal protocol's whole
        reason to exist).  Bit 31 is reachable (hl=k, the capacity
        bound), so masks build in uint32 and reinterpret as int32."""
        tmpl = self._spec_tmpl.get(hl)
        if tmpl is None:
            W, k = self._spec_width, self.spec_k
            n = hl + 1 + W * k
            pos = np.zeros((n,), np.int32)
            anc = np.zeros((n,), np.uint32)
            pos[: hl + 1] = np.arange(hl + 1)
            prefix = (1 << (hl + 1)) - 1
            for c in range(W):
                bits = prefix
                for j in range(k):
                    o = hl + 1 + c * k + j
                    pos[o] = hl + 1 + j
                    bits |= 1 << o
                    anc[o] = bits
            self._spec_tmpl[hl] = tmpl = (pos, anc.view(np.int32))
        return tmpl

    def _spec_ramp(self, st: _SlotState, depth_used: int) -> int:
        """Next-step draft depth for one row off its acceptance EMA
        (LMRS_SPEC_ADAPTIVE): accept streaks deepen the chains toward
        spec_k, collapse ramps down to OFF, and an off row re-probes at
        half depth every 8 steps so a workload shift can re-arm it."""
        k = self.spec_k
        if depth_used == 0:
            st.spec_probe += 1
            if st.spec_probe >= 8:
                st.spec_probe = 0
                st.spec_ema = 0.5
                return max(1, k // 2)
            return 0
        st.spec_probe = 0
        if st.spec_ema >= 0.6:
            return min(depth_used + 1, k)
        if st.spec_ema < 0.2:
            return 0
        if st.spec_ema < 0.35:
            return max(depth_used - 1, 1)
        return depth_used

    def _rpa_mixed_iteration(self, pf, slots, queue, results, fresh,
                             kv_lens, last_tok, active, temps, top_k,
                             top_p, t_enq, last_block_t):
        """One ragged-span mixed step: every live decode row advances as a
        span — ONE token plain, a (1 + spec_k)-token verify span under
        speculation — and one prefilling slot's next slice rides the SAME
        dispatch as a long span row.  int8 KV pools mix (a fresh-start
        slice owns its slot's frozen scales through the span descriptor,
        every other row clamps to them) and spec blocks do not yield
        during prefill windows.  Same (handled, last_block_t) contract as
        _mixed_iteration."""
        spec = bool(self.spec_k)
        tree = spec and self._spec_tree
        k = self.spec_k
        W = self._spec_width

        def rearm(stalled):
            for b in stalled:  # stalled rows rejoin the next dispatch
                if slots[b] is not None:
                    active[b] = True

        adv = (1 + W * k) if tree else (1 + k if spec else 1)
        stalled = self._ensure_decode_capacity(slots, queue, kv_lens,
                                               last_tok, active,
                                               extra_tokens=adv)
        rows = [b for b in range(self.B)
                if slots[b] is not None and active[b]
                and slots[b].phase == "decode"]
        depth_of: dict[int, int] = {}
        hl_of: dict[int, int] = {}
        pressure = False
        if tree:
            # page pressure collapses draft depth to 0 for THIS dispatch
            # (the span family still runs when a heal is pending);
            # acceptance collapse ramps per-row depth to 0 via _spec_ramp.
            # When every row sits at depth 0 with no heal pending, the
            # step routes through the PLAIN span program (adv=1) and the
            # rows are marked spec-stale (the history buffer misses the
            # append).
            pressure = (self._spec_adaptive
                        and self.cache.allocator.free_count < self.B)
            for b in rows:
                st = slots[b]
                hl_of[b] = len(st.spec_heal)
                depth_of[b] = 0 if pressure else min(st.spec_depth, k)
            spec_live = any(depth_of[b] > 0 or hl_of[b] > 0 for b in rows)
        else:
            spec_live = spec
        use_spec = spec and spec_live
        tree_live = tree and use_spec
        if not use_spec:
            adv = 1

        def q_of(b):
            return hl_of[b] + adv if tree_live else adv

        dec_tokens = sum(q_of(b) for b in rows)
        budget_left = self.mixed_token_budget - dec_tokens
        if not rows or (pf is not None and budget_left < 16):
            rearm(stalled)
            return False, last_block_t
        if use_spec:
            with self._an.seg("draft"):
                if self._spec_buf is None:
                    self._spec_buf = jnp.zeros((self.B, self.max_len),
                                               jnp.int32)
                if self._spec_stale:
                    # same lazy re-seed as _spec_decode_block: rows
                    # advanced outside the device-appended paths since
                    # the last verify
                    for b in sorted(self._spec_stale):
                        if (slots[b] is not None
                                and slots[b].phase == "decode"):
                            self.seed_history(b, slots[b])
                    self._spec_stale.clear()

        if pf is not None:
            st_pf = slots[pf]
            pos = st_pf.prefill_pos
            c = min(len(st_pf.prompt_ids) - pos, budget_left,
                    self.prefill_chunk)
            is_final = pos + c >= len(st_pf.prompt_ids)
        else:
            # pure-decode tree-spec step: the alternating path routes
            # here under LMRS_SPEC_TREE so heal/hint column state never
            # meets the legacy spec block
            st_pf, pos, c, is_final = None, 0, 0, False

        q_lens_np = np.zeros((self.B,), np.int32)
        base_np = np.zeros((self.B,), np.int32)
        is_dec_np = np.zeros((self.B,), bool)
        hl_np = np.zeros((self.B,), np.int32)
        hoff_np = np.zeros((self.B,), np.int32)
        depth_np = np.zeros((self.B,), np.int32)
        table_rows = [None] * self.B
        max_pages = 1
        live_tokens = 0
        for b in rows:
            st = slots[b]
            q_lens_np[b] = q_of(b)
            # a heal span re-sends a non-first accepted chain's tokens as
            # leading queries with base = kv_len - heal: their K/V rewrite
            # at the true columns (rope intact) before any read this
            # dispatch — write-before-read in the XLA span path
            base_np[b] = st.kv_len - (hl_of[b] if tree_live else 0)
            is_dec_np[b] = True
            if tree_live:
                hl_np[b] = hl_of[b]
                hoff_np[b] = st.spec_hoff
                depth_np[b] = depth_of[b]
            table_rows[b] = st.seq
            live_tokens += st.kv_len
            max_pages = max(max_pages,
                            self.cache.pages_needed(st.kv_len + adv))
        if pf is not None:
            q_lens_np[pf] = c
            base_np[pf] = pos
            table_rows[pf] = st_pf.seq
            max_pages = max(max_pages, self.cache.pages_needed(pos + c))
        w = min(_pow2_bucket(max_pages, 4), self.cache.max_pages_per_slot)
        table = self.cache.page_table_array(table_rows)

        # host-side span packing: QT-aligned starts, pow2 total bucket —
        # the padding complement is what lmrs_rpa_span_tokens measures
        q_starts_np, total = pack_spans(q_lens_np)
        tpb = _pow2_bucket(total, 16)
        tokens_np = np.zeros((1, tpb), np.int32)
        row_flat_np = np.full((tpb,), self.B, np.int32)
        pos_off_np = anc_np = None
        if tree_live:
            pos_off_np = np.zeros((tpb,), np.int32)
            anc_np = np.zeros((tpb,), np.int32)
        for b in rows:
            s = q_starts_np[b]
            tokens_np[0, s] = last_tok[b]
            row_flat_np[s: s + q_lens_np[b]] = b
            if tree_live:
                # heal tokens ride host-side (cur + chains scatter
                # in-graph after them); positions and ancestor bitmasks
                # come from the per-heal-length span template
                hl_b = hl_of[b]
                tokens_np[0, s: s + hl_b] = slots[b].spec_heal
                t_pos, t_anc = self._tree_span_template(hl_b)
                pos_off_np[s: s + len(t_pos)] = t_pos
                anc_np[s: s + len(t_anc)] = t_anc
        if pf is not None:
            tokens_np[0, q_starts_np[pf]: q_starts_np[pf] + c] = \
                st_pf.prompt_ids[pos: pos + c]
            row_flat_np[q_starts_np[pf]: q_starts_np[pf] + c] = pf
            if tree_live:
                # the prefill slice keeps linear positions and the
                # anc == 0 sentinel (plain causal rule — slices can be
                # longer than the 32-offset bitmask)
                pos_off_np[q_starts_np[pf]: q_starts_np[pf] + c] = \
                    np.arange(c, dtype=np.int32)
        last_of = (q_starts_np + np.maximum(q_lens_np, 1) - 1).astype(
            np.int32)
        if tree_live:
            offs = np.arange(1 + W * k)[None, :]
            gidx = np.where(is_dec_np[:, None],
                            q_starts_np[:, None] + hl_np[:, None] + offs,
                            last_of[:, None]).reshape(-1).astype(np.int32)
        elif use_spec:
            offs = np.arange(self.spec_k + 1)[None, :]
            gidx = np.where(is_dec_np[:, None],
                            q_starts_np[:, None] + offs,
                            last_of[:, None]).reshape(-1).astype(np.int32)
        else:
            gidx = last_of

        # this dispatch pays for a tpb-token bucket but carries ``real``
        # span tokens (the dispatch record below says so)
        real = dec_tokens + c
        self._h_occupancy.observe(len(rows) / self.B)
        self._c_decode_dispatches.inc()
        self._h_mixed_fill.observe(real / self.mixed_token_budget)
        self._h_rpa_span.observe(real)
        now = time.time()
        if last_block_t is not None:
            self._h_block_gap.observe(now - last_block_t)
            self._slo.observe_gap(now - last_block_t)
        last_block_t = now
        flops = 0.0
        if pf is not None:
            self._c_piggybacked.inc(c)
            self._c_prefill_tokens.inc(c)
            self._h_prefill_batch.observe(c)
            flops = self._perf.prefill_flops(c, kv_start=pos)
            st_pf.prefill_pos = pos + c
        if tree_live:
            self._c_spec_tree_disp.inc()

        self._key, sub = jax.random.split(self._key)
        srows = jnp.arange(self.B, dtype=jnp.int32)
        common = (jnp.asarray(tokens_np), jnp.asarray(q_starts_np),
                  jnp.asarray(q_lens_np), jnp.asarray(row_flat_np),
                  jnp.asarray(base_np))
        key_ = ("rpa_spec", tpb, w) if use_spec else ("rpa", tpb, w)
        warm = key_ in self._ran_ok
        if not warm:
            self._wd_grace_cold()
        t_disp = time.time()

        def dispatch():
            if tree_live:
                return self._get_rpa_spec_fn(tpb, w)(
                    self.params, self.cache.k, self.cache.v,
                    self._spec_buf, self.kscale, self.vscale, srows,
                    *common, jnp.asarray(is_dec_np),
                    jnp.asarray(last_tok), jnp.asarray(hl_np),
                    jnp.asarray(hoff_np), jnp.asarray(depth_np),
                    jnp.asarray(pos_off_np), jnp.asarray(anc_np),
                    jnp.asarray(gidx), jnp.asarray(table[:, :w]), sub,
                    jnp.asarray(temps), jnp.asarray(top_k),
                    jnp.asarray(top_p))
            if use_spec:
                return self._get_rpa_spec_fn(tpb, w)(
                    self.params, self.cache.k, self.cache.v,
                    self._spec_buf, self.kscale, self.vscale, srows,
                    *common, jnp.asarray(is_dec_np),
                    jnp.asarray(last_tok), jnp.asarray(gidx),
                    jnp.asarray(table[:, :w]), sub, jnp.asarray(temps),
                    jnp.asarray(top_k), jnp.asarray(top_p))
            return self._get_rpa_fn(tpb, w)(
                self.params, self.cache.k, self.cache.v,
                self.kscale, self.vscale, srows,
                *common, jnp.asarray(gidx),
                jnp.asarray(table[:, :w]), sub, jnp.asarray(temps),
                jnp.asarray(top_k), jnp.asarray(top_p))

        # on a cold key the dispatch call blocks on the XLA compile: the
        # record bills that wall to the key
        with self._an.dispatch(
                "rpa", key_, rows=len(rows) + (pf is not None),
                row_slots=self.B, q_tokens=real, prompt_tokens=c,
                q_slots=tpb, ctx_tokens=live_tokens + pos, cold=not warm,
                layer_passes=self.model_cfg.cache_layers,
                **self._span_walk(q_lens_np, base_np, w,
                                  kernel=not tree_live)):
            out = dispatch()
        self._note_ran_ok(key_)
        with self._an.seg("fetch"):
            if tree_live:
                (emit, count, chain, adepth, self._spec_buf, self.cache.k,
                 self.cache.v, ks, vs) = out
                emit, count, chain, adepth = self._timed_get(
                    (emit, count, chain, adepth))
                emit, count = np.asarray(emit), np.asarray(count)
                chain, adepth = np.asarray(chain), np.asarray(adepth)
            elif use_spec:
                (emit, count, self._spec_buf, self.cache.k, self.cache.v,
                 ks, vs) = out
                emit, count = self._timed_get((emit, count))
                emit, count = np.asarray(emit), np.asarray(count)
            else:
                nxt, self.cache.k, self.cache.v, ks, vs = out
                nxt = np.asarray(self._timed_get(nxt))
        if self._kv_quant:
            self.kscale, self.vscale = ks, vs
        t_done = time.time()

        with self._an.seg("finish"):
            # exact-split attribution with SPAN-LEVEL token counts: the
            # decode side of a span step is adv tokens per live row, not
            # one
            extra_flops, cold_pf = self._consume_prefill_attr()
            nb = self._bytes.block(
                [slots[b].kv_len for b in rows], 1,
                self.model_cfg.cache_layers, queries=dec_tokens / len(rows))
            self._perf.note_mixed_step(t_disp, t_done, nb,
                                       flops + extra_flops,
                                       warm=warm and not cold_pf)
            self._attr_last_gb = round(nb / 1e9, 3)
            if self._cost.enabled:
                dcost, pcost = self._roofline_phase_costs(
                    nb, flops + extra_flops)
                self._cost.note_step(
                    max(0.0, t_done - t_disp),
                    decode_rows=[(slots[b].req,
                                  int(count[b]) if use_spec else 1,
                                  len(slots[b].seq.pages)) for b in rows],
                    prefill_rows=(self._consume_prefill_cost()
                                  + ([(st_pf.req, c, flops)]
                                     if pf is not None else [])),
                    decode_cost_s=dcost, prefill_cost_s=pcost)

            for b in rows:
                st = slots[b]
                if use_spec:
                    cnt = int(count[b])
                    new = [int(t) for t in emit[b, :cnt]]
                    self._c_spec_accepted.inc(max(0, cnt - 1))
                    if cnt > 1:
                        self._cost.note_saved(st.req, spec_tokens=cnt - 1)
                    if tree_live:
                        cs, ad = int(chain[b]), int(adepth[b])
                        # a non-first accepted chain's drafts sit at THAT
                        # chain's span-offset KV columns: re-send them as
                        # the next span's heal prefix so they rewrite at
                        # the true columns
                        st.spec_heal = (new[:ad] if cs > 0 and ad > 0
                                        else [])
                        d_used = depth_of[b]
                        self._h_spec_nodes.observe(1 + W * d_used)
                        self._h_spec_depth.observe(ad)
                        if not pressure:
                            if d_used > 0:
                                st.spec_ema = (0.8 * st.spec_ema
                                               + 0.2 * ad / d_used)
                            if self._spec_adaptive:
                                st.spec_depth = self._spec_ramp(st, d_used)
                else:
                    new = [int(nxt[b])]
                    if tree:
                        # plain-routed idle tree step: the history buffer
                        # missed this append — re-seed before the next
                        # spec-live dispatch; the depth-0 probe timer
                        # keeps ticking so speculation can re-arm
                        self._spec_stale.add(b)
                        if self._spec_adaptive and not pressure:
                            st.spec_depth = self._spec_ramp(st, 0)
                st.generated.extend(new)
                st.kv_len += len(new)
                kv_lens[b] = st.kv_len
                last_tok[b] = st.generated[-1] if st.generated else 0
                self._c_decode_tokens.inc(len(new))
                if self._tr:
                    self._tr.instant("decode_block", ts=now,
                                     tid=self._tid(st.req),
                                     args={"tokens": len(new)})
                self._maybe_finish(b, slots, results, active, fresh,
                                   kv_lens, last_tok)
            if is_final:
                # the slice completed the prompt: enter decode with the
                # first token this very step sampled at its last span
                # position
                st = st_pf
                st.phase = "decode"
                st.t_decode_start = time.time()
                if self._tr:
                    self._tr.complete("prefill", st.t_admit,
                                      st.t_decode_start,
                                      tid=self._tid(st.req),
                                      args={"prompt_tokens":
                                            len(st.prompt_ids)})
                st.kv_len = len(st.prompt_ids)
                kv_lens[pf] = st.kv_len
                active[pf] = True
                self._cache_insert(st)
                tok0 = int(emit[pf, 0]) if use_spec else int(nxt[pf])
                st.generated.append(tok0)
                self._note_first_token(st, t_enq)
                last_tok[pf] = tok0
                if spec:
                    # the verify graph cannot have appended pf's history
                    # (its span was a prompt slice): seed once at the
                    # prefill -> decode transition, like any admission
                    with self._an.seg("draft"):
                        self.seed_history(pf, st)
                self._maybe_finish(pf, slots, results, active, fresh,
                                   kv_lens, last_tok)
            if self._tr:
                self._tr.complete("decode_block", now, time.time(),
                                  args={"active": len(rows),
                                        "tokens": dec_tokens,
                                        "hbm_gb": self._attr_last_gb,
                                        "mixed": pf is not None,
                                        "rpa": True,
                                        "spec_tree": tree_live,
                                        "prefill_tokens": c})
            rearm(stalled)
        return True, last_block_t

    # ------------------------------------------------------------- prefill

    def _advance_prefills(self, slots) -> list[tuple[object, list[tuple[int, int]]]]:
        """Advance every prefilling slot by one prompt chunk and return
        [(tok0_device_array, [(slot, row)])] for the slots whose whole prompt
        is now in KV.  The first-token arrays are NOT fetched — the caller
        threads them into the decode dispatch and fetches them with the
        decode block's own transfer (each device_get is one more blocking
        host sync).

        Prompts that fit one chunk take the fresh-prefill program (attends
        the chunk directly); longer prompts run the windowed continuation
        program per chunk (attends the page window, which includes earlier
        chunks' KV).  A dispatch's batch dim is a rung of
        ``prefill_row_rung``'s short ladder between 1 and B (padded up to
        the rung): XLA compiles are seconds-long and a per-group-size shape
        zoo would thrash the cache at runtime, so the ladder doubles; a
        B-row operand for every group would make a two-thirds-full wave
        pay for the whole.

        The fresh prompts of one iteration that share a power-of-two
        bucket are one WAVE, planned by ``prefill_wave_plan``: its body on
        the sequence rung of its median prompt (``prefill_seq_rung``, whole
        pages), the prompts longer than that rung in a second dispatch on
        the power of two, never more positions than the one power-of-two
        dispatch of the rule before.  A lone prompt (a reduce step) keeps
        the power of two: its length follows the generated text, so a rung
        of it would compile in the window.  The windowed continuation,
        ring-sharded prompts and packed bins stay on the power-of-two
        bucket: no cell runs them with fresh prompts, and the latent and
        window caches refuse two of them.
        """
        groups: dict[tuple, list] = {}
        fresh_pack: list[tuple[int, object, list[int]]] = []
        for b in range(self.B):
            st = slots[b]
            if st is None or st.phase != "prefill":
                continue
            ids = st.prompt_ids
            pos = st.prefill_pos
            chunk = ids[pos: pos + self.prefill_chunk]
            is_final = pos + len(chunk) >= len(ids)
            fresh = pos == 0 and is_final  # whole prompt in one dispatch
            # long prompts under an sp mesh go to the ring path un-packed
            if (fresh and self._pack_prefill
                    and not (self._use_ring and len(chunk) >= self._ring_min)):
                fresh_pack.append((b, st, chunk))
                continue
            # Ring routing is decided by the REAL chunk length, not the
            # bucket (ADVICE r2): a 600-token prompt bucketing to 1024 must
            # not pay ppermute hops to ring-shard mostly-padding.  Ring
            # buckets round up to a multiple of sp so every shard is equal —
            # guaranteed <= max_len by the constructor divisibility check.
            ring = (fresh and self._use_ring
                    and len(chunk) >= self._ring_min)
            s_bucket = min(_pow2_bucket(len(chunk), 64), self.max_len)
            if ring:
                s_bucket = min(-(-s_bucket // self._sp) * self._sp,
                               self.max_len)
            if fresh:
                w = self.cache.max_pages_per_slot
            else:
                need_pages = self.cache.pages_needed(pos + len(chunk))
                w = min(_pow2_bucket(need_pages, 4), self.cache.max_pages_per_slot)
            groups.setdefault((fresh, s_bucket, w, ring), []).append(
                (b, st, chunk, pos, is_final))

        # dispatch each group (async), collecting unfetched [N] token arrays
        pending: list[tuple[object, list[tuple[int, int]]]] = []

        # packed fresh prompts: bins of <= max_len tokens, each ONE [1, S]
        # dispatch; a bin left with a single prompt takes the per-prompt
        # program (identical work, already compiled for the common case).
        # Under an sp mesh, bins cap at _ring_min so packed rows stay short
        # enough that skipping the ring is the right call for them.
        cap = self._ring_min if self._use_ring else self.max_len
        for bin_items in self._pack_bins(fresh_pack, cap):
            if len(bin_items) == 1:
                b, st, chunk = bin_items[0]
                s_bucket = min(_pow2_bucket(len(chunk), 64), self.max_len)
                # bin items are < _ring_min by the fresh_pack gate: no ring
                groups.setdefault(
                    (True, s_bucket, self.cache.max_pages_per_slot, False), []
                ).append((b, st, chunk, 0, True))
            else:
                pending.append(self._dispatch_packed(bin_items))
        batches = []
        for (fresh, s_bucket, w, ring), items in groups.items():
            if not fresh or ring:
                batches.append(((fresh, s_bucket, w, ring), items))
                continue
            items.sort(key=lambda it: len(it[2]))
            lo = 0
            for m, _, s_seq in prefill_wave_plan(
                    [len(it[2]) for it in items], self.B,
                    self.cache.page_size, self.max_len):
                batches.append(((True, s_seq, w, False), items[lo: lo + m]))
                lo += m
        for (fresh, s_bucket, w, ring), items in batches:
            if (not fresh and not self._latent and self._use_ragged
                    and self._kernel_mesh() is None):
                # windowed continuation chunks ride the unified span
                # program: the per-(s_bucket, w) chunked-prefill matrix
                # (_prefill_window_fns) never compiles under RPA — chunks
                # share the mixed step's (token bucket, window) family
                entry = self._dispatch_rpa_chunks(items)
                if entry[1]:
                    pending.append(entry)
                continue
            n = prefill_row_rung(len(items), self.B)
            tokens = np.full((n, s_bucket), self.tokenizer.pad_id, np.int32)
            start = np.zeros((n,), np.int32)
            length = np.ones((n,), np.int32)  # pad rows: 1 token on the null page
            alloc = np.full((n,), self.cache.page_size, np.int32)
            table = np.zeros((n, self.cache.max_pages_per_slot), np.int32)
            temps = np.ones((n,), np.float32)
            tks = np.zeros((n,), np.int32)
            tps = np.ones((n,), np.float32)
            # dispatch row -> slot id for the scale buffers: pad rows point
            # one past the end (scatter drops them, gather clamps — their
            # writes land on the null page anyway)
            srows = np.full((n,), self.B, np.int32)
            table[: len(items)] = self.cache.page_table_array(
                [st.seq for _, st, _, _, _ in items])
            for row, (b, st, chunk, pos, _) in enumerate(items):
                tokens[row, : len(chunk)] = chunk
                start[row] = pos
                length[row] = len(chunk)
                alloc[row] = st.seq.capacity(self.cache.page_size)
                temps[row] = st.req.temperature
                tks[row] = st.req.top_k
                tps[row] = min(max(st.req.top_p, 0.0), 1.0)
                srows[row] = b
                st.prefill_pos = pos + len(chunk)
                self._c_prefill_tokens.inc(len(chunk))
            lens = [len(c) for _, _, c, _, _ in items]
            batch_tokens = sum(lens)
            self._h_prefill_batch.observe(batch_tokens)
            # roofline attribution: real-token FLOPs of this dispatch
            # (window chunks additionally attend their cached prefix),
            # consumed by whichever block fetches the wave's results —
            # the ledger keeps the same work per ROW for its split
            flops = 0.0
            for _, st_i, c_i, p_i, _ in items:
                f_i = self._perf.prefill_flops(len(c_i), kv_start=p_i)
                flops += f_i
                if self._cost.enabled:
                    self._cost_pending_prefill.append(
                        (st_i.req, len(c_i), f_i))
            self._attr_pending_flops += flops
            self._key, sub = jax.random.split(self._key)
            args = (
                self.params, self.cache.k, self.cache.v,
                self.kscale, self.vscale, jnp.asarray(srows),
                jnp.asarray(tokens), jnp.asarray(start), jnp.asarray(length),
                jnp.asarray(alloc), jnp.asarray(table[:, :w]), sub,
                jnp.asarray(temps), jnp.asarray(tks), jnp.asarray(tps),
            )
            # n is in the key: every rung of prefill_row_rung is its own
            # compiled program (the dispatch table's q_slots == dispatches
            # x bucket identity found the key without it calling a
            # compiling [B, S] warm)
            key_ = ("prefill", fresh, n, s_bucket, w, ring)
            cold = key_ not in self._ran_ok
            if cold:
                self._attr_prefill_cold = True  # compiling: no MFU sample
                self._wd_grace_cold()
            with self._an.dispatch(
                    "prefill" if fresh else "prefill_chunk", key_,
                    rows=len(items), row_slots=n, q_tokens=batch_tokens,
                    prompt_tokens=batch_tokens, q_slots=n * s_bucket,
                    ctx_tokens=sum(p for _, _, _, p, _ in items),
                    page_writes=(n * whole_pages(
                        s_bucket, self.cache.page_size, w) if fresh else 0),
                    layer_passes=self.model_cfg.cache_layers, cold=cold,
                    seq_rung=s_bucket < min(_pow2_bucket(max(lens), 64),
                                            self.max_len),
                    window=self._window_counts(prefill=(lens, s_bucket))):
                fn = (self._get_prefill_fn(s_bucket, use_ring=ring)
                      if fresh
                      else self._get_prefill_window_fn(s_bucket, w))
                tok0, self.cache.k, self.cache.v, \
                    self.kscale, self.vscale = self._moe_take(
                        "prefill" if fresh else "prefill_chunk", key_,
                        fn(*args))
            self._note_ran_ok(key_)
            rows = [(b, row) for row, (b, _, _, _, is_final) in enumerate(items)
                    if is_final]
            if rows:
                pending.append((tok0, rows))

        return pending

    def _span_walk(self, q_lens, bases, w: int, kernel: bool = True) -> dict:
        """The span dispatch record's ``wide_tokens`` / ``kv_page_reads``:
        what the ragged span kernel will do with these spans, counted by
        its own rule.  Empty where the dispatch's attention is the XLA
        twin's (no kernel armed, a tp mesh, a tree-verify ancestor mask):
        there is no page walk to count."""
        if not (kernel and self._use_ragged and self._kernel_mesh() is None):
            return {}
        wide_tokens, kv_page_reads = span_walk_counts(
            q_lens, bases, self.cache.page_size, w, self.max_len)
        return {"wide_tokens": wide_tokens, "kv_page_reads": kv_page_reads}

    def _dispatch_rpa_chunks(self, items) -> tuple[object, list]:
        """Windowed continuation chunks as ragged SPANS (a K/V cache with the
        kernel armed): every chunk is one long-span row of a single
        unified dispatch.  Returns the ``(tok0_device_array, [(slot,
        row)])`` pending-entry contract of ``_advance_prefills``; the
        sampled array is B-wide and indexed by SLOT (rows ARE slots
        here)."""
        q_lens_np = np.zeros((self.B,), np.int32)
        base_np = np.zeros((self.B,), np.int32)
        is_final_rows: list[tuple[int, int]] = []
        table_rows = [None] * self.B
        max_pages = 1
        batch_tokens = 0
        flops = 0.0
        for (b, st, chunk, pos, is_final) in items:
            q_lens_np[b] = len(chunk)
            base_np[b] = pos
            table_rows[b] = st.seq
            max_pages = max(max_pages,
                            self.cache.pages_needed(pos + len(chunk)))
            batch_tokens += len(chunk)
            if is_final:
                is_final_rows.append((b, b))
        w = min(_pow2_bucket(max_pages, 4), self.cache.max_pages_per_slot)
        table = self.cache.page_table_array(table_rows)
        q_starts_np, total = pack_spans(q_lens_np)
        tpb = _pow2_bucket(total, 16)
        tokens_np = np.zeros((1, tpb), np.int32)
        row_flat_np = np.full((tpb,), self.B, np.int32)
        temps = np.ones((self.B,), np.float32)
        tks = np.zeros((self.B,), np.int32)
        tps = np.ones((self.B,), np.float32)
        for (b, st, chunk, pos, _) in items:
            s, c = int(q_starts_np[b]), len(chunk)
            tokens_np[0, s: s + c] = chunk
            row_flat_np[s: s + c] = b
            temps[b] = st.req.temperature
            tks[b] = st.req.top_k
            tps[b] = min(max(st.req.top_p, 0.0), 1.0)
            st.prefill_pos = pos + c
            self._c_prefill_tokens.inc(c)
            f_i = self._perf.prefill_flops(c, kv_start=pos)
            flops += f_i
            if self._cost.enabled:
                self._cost_pending_prefill.append((st.req, c, f_i))
        gidx = (q_starts_np + np.maximum(q_lens_np, 1) - 1).astype(np.int32)
        self._h_prefill_batch.observe(batch_tokens)
        self._h_rpa_span.observe(batch_tokens)
        self._attr_pending_flops += flops
        self._key, sub = jax.random.split(self._key)
        srows = jnp.arange(self.B, dtype=jnp.int32)
        args = (self.params, self.cache.k, self.cache.v,
                self.kscale, self.vscale, srows,
                jnp.asarray(tokens_np), jnp.asarray(q_starts_np),
                jnp.asarray(q_lens_np), jnp.asarray(row_flat_np),
                jnp.asarray(base_np), jnp.asarray(gidx),
                jnp.asarray(table[:, :w]), sub, jnp.asarray(temps),
                jnp.asarray(tks), jnp.asarray(tps))
        key_ = ("rpa", tpb, w)
        warm = key_ in self._ran_ok
        if not warm:
            self._attr_prefill_cold = True  # compiling: no MFU sample
            self._wd_grace_cold()
        # chunked-prefill spans ride the same ragged (token bucket, page
        # window) family as the mixed step — real tokens vs the tpb pad
        # tail is the padding-waste trade PR 16 made.  A cold key's wall
        # ~= compile time (tracing + lowering block the call; execution is
        # async)
        with self._an.dispatch(
                "rpa", key_, rows=len(items), row_slots=self.B,
                q_tokens=batch_tokens, prompt_tokens=batch_tokens,
                q_slots=tpb, ctx_tokens=int(base_np.sum()), cold=not warm,
                layer_passes=self.model_cfg.cache_layers,
                **self._span_walk(q_lens_np, base_np, w)):
            tok0, self.cache.k, self.cache.v, ks, vs = \
                self._get_rpa_fn(tpb, w)(*args)
        self._note_ran_ok(key_)
        if self._kv_quant:
            self.kscale, self.vscale = ks, vs
        return tok0, is_final_rows

    @staticmethod
    def _pack_bins(items: list, capacity: int) -> list[list]:
        """First-fit-decreasing bin packing of (slot, state, chunk) items by
        chunk length.  Segment count per bin is bounded by B (items are
        slots), so the packed program's shapes stay (s_bucket, B)."""
        bins: list[tuple[int, list]] = []  # (used, items)
        for it in sorted(items, key=lambda t: -len(t[2])):
            n = len(it[2])
            for i, (used, lst) in enumerate(bins):
                if used + n <= capacity:
                    lst.append(it)
                    bins[i] = (used + n, lst)
                    break
            else:
                bins.append((n, [it]))
        return [lst for _, lst in bins]

    def _dispatch_packed(self, items: list) -> tuple[object, list[tuple[int, int]]]:
        """One packed prefill dispatch: concatenate the items' prompts into a
        [1, S] row (segment ids, within-segment positions, host-built
        per-token page ids) and sample each segment's first token from its
        last row.  Returns the (unfetched tok0 [B], [(slot, segment)])
        pending entry, same contract as the per-prompt programs."""
        ps = self.cache.page_size
        s_real = sum(len(c) for _, _, c in items)
        # bins are capped at max_len tokens, so the clamp never truncates.
        # Bucket floor max_len//4: tail bins otherwise mint a fresh pow2
        # shape per wave, and at real model sizes each novel shape is a
        # multi-second XLA compile mid-run (same tradeoff as the quarter-
        # step bucket NOTE above) — at most 3 packed shapes ever compile.
        s_bucket = min(max(_pow2_bucket(s_real, 64), self.max_len // 4),
                       self.max_len)
        tokens = np.full((1, s_bucket), self.tokenizer.pad_id, np.int32)
        positions = np.zeros((1, s_bucket), np.int32)
        seg_ids = np.full((1, s_bucket), -1, np.int32)  # pad: matches nothing
        token_pages = np.zeros((1, s_bucket), np.int32)  # pad -> null page
        last_idx = np.zeros((self.B,), np.int32)
        temps = np.ones((self.B,), np.float32)
        tks = np.zeros((self.B,), np.int32)
        tps = np.ones((self.B,), np.float32)
        # segment -> slot for the KV scale buffers (int8 KV): unused
        # segments point one past the end (scale scatter drops them)
        srows = np.full((self.B,), self.B, np.int32)
        off = 0
        for si, (b, st, chunk) in enumerate(items):
            n = len(chunk)
            within = np.arange(n, dtype=np.int32)
            tokens[0, off: off + n] = chunk
            positions[0, off: off + n] = within
            seg_ids[0, off: off + n] = si
            token_pages[0, off: off + n] = np.asarray(
                st.seq.pages, np.int32)[within // ps]
            last_idx[si] = off + n - 1
            temps[si] = st.req.temperature
            tks[si] = st.req.top_k
            tps[si] = min(max(st.req.top_p, 0.0), 1.0)
            srows[si] = b
            st.prefill_pos = n
            self._c_prefill_tokens.inc(n)
            off += n
        self._h_prefill_batch.observe(s_real)
        flops = 0.0
        for _, st_i, c_i in items:
            f_i = self._perf.prefill_flops(len(c_i))
            flops += f_i
            if self._cost.enabled:
                self._cost_pending_prefill.append((st_i.req, len(c_i), f_i))
        self._attr_pending_flops += flops
        self._key, sub = jax.random.split(self._key)
        args = (
            self.params, self.cache.k, self.cache.v,
            self.kscale, self.vscale, jnp.asarray(srows),
            jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(token_pages), jnp.asarray(seg_ids),
            jnp.asarray(last_idx), jnp.asarray([s_real], np.int32), sub,
            jnp.asarray(temps), jnp.asarray(tks), jnp.asarray(tps),
        )
        key_ = ("packed", s_bucket)
        cold = key_ not in self._ran_ok
        if cold:
            self._attr_prefill_cold = True  # compiling: no MFU sample
            self._wd_grace_cold()
        with self._an.dispatch(
                "packed", key_, rows=len(items), row_slots=self.B,
                q_tokens=s_real, prompt_tokens=s_real, q_slots=s_bucket,
                ctx_tokens=0, layer_passes=self.model_cfg.cache_layers,
                cold=cold):
            tok0, self.cache.k, self.cache.v, \
                self.kscale, self.vscale = \
                self._get_packed_prefill_fn(s_bucket)(*args)
        self._note_ran_ok(key_)
        return tok0, [(b, si) for si, (b, _, _) in enumerate(items)]

    def _get_packed_prefill_fn(self, s_bucket: int):
        if s_bucket in self._packed_prefill_fns:
            return self._packed_prefill_fns[s_bucket]
        cfg = self.model_cfg
        rope_max = self.max_len
        use_flash = self._use_flash
        mesh_ = self._kernel_mesh()
        interp = self._interpret
        kv_q = bool(self._kv_quant)

        @partial(jax.jit, donate_argnums=(1, 2, 3, 4) if kv_q else (1, 2))
        def packed_prefill(params, k_pages, v_pages, kscale, vscale,
                           scale_rows, tokens, positions, token_pages,
                           seg_ids, last_idx, length, key, temp, tk, tp):
            out = forward_paged(
                params, cfg, tokens, positions, k_pages, v_pages,
                jnp.zeros((1, 1), jnp.int32),  # tables unused: token_pages
                length, rope_max, use_ragged_kernel=False,
                use_flash=use_flash, mesh=mesh_, interpret=interp,
                token_pages=token_pages, segment_ids=seg_ids,
                packed_last_idx=last_idx,
                kv_scales=(kscale, vscale) if kv_q else None,
                scale_rows=scale_rows,
            )
            logits, k_pages, v_pages = out[:3]
            kscale, vscale = out[3] if kv_q else (None, None)
            tok0 = sample_logits(logits[0], key, temp, tk, tp)  # [B]
            return tok0, k_pages, v_pages, kscale, vscale

        logger.info("compiling packed prefill: bucket=%d segments<=%d "
                    "(flash=%s)", s_bucket, self.B, use_flash)
        self._packed_prefill_fns[s_bucket] = packed_prefill
        return packed_prefill

    def _get_prefill_fn(self, s_bucket: int, use_ring: bool = False):
        """Fresh-prefill program.  ``use_ring`` is decided by the CALLER
        from the real chunk length (ADVICE r2: bucket-based gating sent
        600-token prompts through ppermute hops); ring buckets arrive
        pre-rounded to a multiple of sp — enforced, never warned."""
        fn_key = (s_bucket, use_ring)
        if fn_key in self._prefill_fns:
            return self._prefill_fns[fn_key]
        cfg = self.model_cfg
        rope_max = self.max_len
        use_flash = self._use_flash  # captured at build time
        mesh_ = self._kernel_mesh()
        interp = self._interpret
        kv_q = bool(self._kv_quant)
        moe = self._moe_on
        n_slots = self.B
        if use_ring and s_bucket % self._sp:
            raise ValueError(
                f"ring prefill bucket {s_bucket} not divisible by "
                f"sp={self._sp} — dispatch must round ring buckets up")

        @partial(jax.jit, donate_argnums=(1, 2, 3, 4) if kv_q else (1, 2))
        def prefill(params, k_pages, v_pages, kscale, vscale, scale_rows,
                    tokens, start, length, alloc_tokens, table, key, temp,
                    tk, tp):
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1])[None], tokens.shape)
            # Padded tail positions can exceed this sequence's allocated
            # pages (prompt bucket > budget): clamp them into the table.
            # forward_paged keeps the padding off every real token's slot
            # (the row form sends it to the null page; the page form leaves
            # it behind kv_lens in the row's own pages, and on the null page
            # past the allocation): written AT the clamped slot it would
            # land on the last real token of a prompt that fills its pages.
            write_pos = jnp.minimum(positions, alloc_tokens[:, None] - 1)
            out = forward_paged(
                params, cfg, tokens, write_pos, k_pages, v_pages, table,
                length, rope_max, use_ragged_kernel=False, use_flash=use_flash,
                mesh=mesh_, interpret=interp, use_ring=use_ring,
                last_pos=length - 1,  # LM head on the sampled row only
                kv_scales=(kscale, vscale) if kv_q else None,
                scale_rows=scale_rows,
                token_valid=(positions < length[:, None]) if moe else None,
                window_slots=n_slots,
            )
            logits, k_pages, v_pages = out[:3]
            kscale, vscale = out[3] if kv_q else (None, None)
            tok0 = sample_logits(logits[:, 0], key, temp, tk, tp)
            if moe:
                return tok0, k_pages, v_pages, kscale, vscale, out[-1]
            return tok0, k_pages, v_pages, kscale, vscale

        logger.info("compiling paged prefill: bucket=%d (flash=%s ring=%s)",
                    s_bucket, use_flash, use_ring)
        self._prefill_fns[fn_key] = prefill
        return prefill

    def _get_prefill_window_fn(self, s_bucket: int, w: int):
        """Continuation-prefill program: chunk at absolute positions
        [start, start+length) attending the page window (chunked prefill)."""
        key_ = (s_bucket, w)
        if key_ in self._prefill_window_fns:
            return self._prefill_window_fns[key_]
        cfg = self.model_cfg
        rope_max = self.max_len
        kv_q = bool(self._kv_quant)
        moe = self._moe_on

        @partial(jax.jit, donate_argnums=(1, 2, 3, 4) if kv_q else (1, 2))
        def prefill_chunk(params, k_pages, v_pages, kscale, vscale,
                          scale_rows, tokens, start, length, alloc_tokens,
                          table, key, temp, tk, tp):
            positions = start[:, None] + jnp.broadcast_to(
                jnp.arange(tokens.shape[1])[None], tokens.shape)
            write_pos = jnp.minimum(positions, alloc_tokens[:, None] - 1)
            out = forward_paged(
                params, cfg, tokens, write_pos, k_pages, v_pages, table,
                start + length, rope_max, use_ragged_kernel=False,
                window_prefill=True,
                last_pos=length - 1,  # local row index within this chunk
                kv_scales=(kscale, vscale) if kv_q else None,
                scale_rows=scale_rows,
                token_valid=((positions - start[:, None] < length[:, None])
                             if moe else None),
            )
            logits, k_pages, v_pages = out[:3]
            kscale, vscale = out[3] if kv_q else (None, None)
            tok0 = sample_logits(logits[:, 0], key, temp, tk, tp)
            if moe:
                return tok0, k_pages, v_pages, kscale, vscale, out[-1]
            return tok0, k_pages, v_pages, kscale, vscale

        logger.info("compiling chunked prefill: bucket=%d window=%d pages",
                    s_bucket, w)
        self._prefill_window_fns[key_] = prefill_chunk
        return prefill_chunk

    # -------------------------------------------------------------- decode

    def _decode_window(self, slots, extra_tokens: int):
        """(w, table) for one decode dispatch: page window bucketed to the
        widest active sequence plus ``extra_tokens`` of block growth.  Slots
        still in prefill phase get the null page table: the decode program's
        masked dummy writes must land on page 0, never on pages holding
        their half-prefilled KV."""
        decode_seqs = [
            s.seq if (s is not None and s.phase == "decode") else None
            for s in slots
        ]
        max_pages = 1
        for st in slots:
            if st is not None and st.phase == "decode":
                need = self.cache.pages_needed(st.kv_len + extra_tokens)
                max_pages = max(max_pages, need)
        w = min(_pow2_bucket(max_pages, 4), self.cache.max_pages_per_slot)
        return w, self.cache.page_table_array(decode_seqs)

    def _decode_block(self, slots, last_tok, kv_lens, active, temps, top_k,
                      top_p, pending=()):
        """One decode-block dispatch.  ``pending`` carries unfetched
        first-token arrays from this iteration's prefills: their values are
        scattered into the ``last_tok`` input on device (no host sync) and
        fetched together with the block's outputs in the one device_get."""
        w, table = self._decode_window(slots, self.decode_block)
        B = self.B
        # dispatch record inputs, taken from the caller's FULL slot arrays
        # before any compaction/permutation below rewrites them
        attr_live_rows = int(np.sum(active))
        attr_live_tokens = int(np.sum(kv_lens[active]))
        layer_passes = self.model_cfg.cache_layers * self.decode_block
        hbm = self._bytes.block(kv_lens[active], self.decode_block,
                                layer_passes)
        # Compact-batch drain: the decode program's cost scales with its
        # batch dim even for masked rows, so when few slots are live (queue
        # drained, reduce-tree tails) gather the live rows into one fixed
        # 8-row batch and scatter results back.  bc is pinned to 8 — exactly
        # one extra compiled shape per window; a pow2 ladder of compact
        # sizes would thrash multi-second runtime compiles (see the
        # quarter-step bucket NOTE above).  Skipped while prefill tok0s are
        # pending: those live on device and the compact gather is host-side.
        rows = np.flatnonzero(active)
        bc = 8 if (B > 8 and len(rows) <= 8 and not pending) else B
        if bc < B:
            n = len(rows)
            c_tok = np.zeros((bc,), np.int32)
            c_len = np.zeros((bc,), np.int32)
            c_act = np.zeros((bc,), bool)
            c_tab = np.zeros((bc, w), np.int32)  # pad rows: null page table
            c_tmp = np.zeros((bc,), np.float32)
            c_tk = np.zeros((bc,), np.int32)
            c_tp = np.ones((bc,), np.float32)
            c_tok[:n] = last_tok[rows]
            c_len[:n] = kv_lens[rows]
            c_act[:n] = True
            c_tab[:n] = table[rows, :w]
            c_tmp[:n] = temps[rows]
            c_tk[:n] = top_k[rows]
            c_tp[:n] = top_p[rows]
            last_tok, kv_lens, active = c_tok, c_len, c_act
            table, temps, top_k, top_p = c_tab, c_tmp, c_tk, c_tp
        # dispatch row -> slot for the KV scale buffers (compact-batch rows
        # are a gathered subset of slots; pad rows clamp harmlessly)
        if bc < B:
            srows = np.full((bc,), B, np.int32)
            srows[: len(rows)] = rows
        else:
            srows = np.arange(B, dtype=np.int32)
        # Multi-row kernel: length-balance the row→group assignment so a
        # straggler row can't serialize its group's shared DMA pipeline
        # (ops/paged_attention.balanced_row_order).  Pure host-side numpy
        # reorder of the dispatch rows; srows carries the slot mapping
        # through, so scales and the result scatter-back need no special
        # casing.  Greedy outputs are row-order-invariant; sampled rows
        # draw different (equally valid) tokens — LMRS_MULTIROW=0 restores
        # the unpermuted per-row dispatch exactly.
        perm = None
        if self._row_group > 1 and self._use_ragged:
            # grouping lives in the ragged kernel only: the XLA decode
            # path stays unpermuted (it has no groups to balance)
            from lmrs_tpu.ops.paged_attention import balanced_row_order
            # clamp to the dispatch width like the kernel does (compact
            # drain can pin bc below the configured group size); an
            # unclamped denominator would under-report occupancy exactly
            # where operators read it to pick G
            g = min(self._row_group, bc)
            self._h_group_occupancy.observe(
                len(rows) / (-(-bc // g) * g))
            perm = balanced_row_order(np.where(active, kv_lens, 0), g)
            if np.array_equal(perm, np.arange(len(perm))):
                perm = None
            else:
                last_tok = last_tok[perm]
                kv_lens = kv_lens[perm]
                active = active[perm]
                table = table[perm]
                temps, top_k, top_p = temps[perm], top_k[perm], top_p[perm]
                srows = srows[perm]
        lt = jnp.asarray(last_tok)
        for tok0_dev, prows in pending:  # on-device scatter, no host sync
            # both index vectors padded to the prefill dispatch's rows (pad
            # targets bc: dropped), so the program's shape follows the row
            # rung, not the count of prompts a cut gave the dispatch
            idx = np.full((tok0_dev.shape[0],), bc, np.int32)
            src = np.zeros((tok0_dev.shape[0],), np.int32)
            idx[: len(prows)] = [b for b, _ in prows]
            src[: len(prows)] = [r for _, r in prows]
            if perm is not None:
                # pending tok0s target SLOTS; map to their dispatch rows
                inv = np.empty(len(perm), np.int32)
                inv[perm] = np.arange(len(perm), dtype=np.int32)
                idx[: len(prows)] = inv[idx[: len(prows)]]
            lt = _put_first_tokens(lt, tok0_dev, idx, src)
        self._key, sub = jax.random.split(self._key)
        args = (
            self.params, self.cache.k, self.cache.v,
            self.kscale, self.vscale, jnp.asarray(srows),
            lt, jnp.asarray(kv_lens),
            jnp.asarray(table[:, :w]), jnp.asarray(active), sub,
            jnp.asarray(temps), jnp.asarray(top_k), jnp.asarray(top_p),
        )
        key_ = ("decode", bc, w)
        decode_warm = key_ in self._ran_ok
        if not decode_warm:
            self._wd_grace_cold()
        t_disp = time.time()
        # q_tokens: the tokens the block emits, known once it is fetched
        with self._an.dispatch(
                "decode", key_, rows=attr_live_rows, row_slots=bc,
                q_tokens=0, prompt_tokens=0,
                q_slots=bc * self.decode_block,
                ctx_tokens=attr_live_tokens, layer_passes=layer_passes,
                kv_page_reads=self._bytes.pages(kv_lens[active],
                                                self.decode_block),
                hbm_bytes=hbm, cold=not decode_warm,
                window=self._window_counts(decode=(
                    kv_lens[active], self.decode_block))) as disp:
            out = self._get_decode_fn(w)(*args)
        self._note_ran_ok(key_)
        toks, n_valid, self.cache.k, self.cache.v = self._moe_take(
            "decode", key_, out)
        moe, self._moe_pending = self._moe_pending, []
        with self._an.seg("fetch"):
            toks, n_valid, *tok0s = self._timed_get(  # one transfer
                (toks, n_valid, *[t for t, _ in pending],
                 *[m[2] for m in moe]))
        if moe:  # the routed layers' counts rode the same transfer
            hbm += self._moe_note(moe, tok0s[len(pending):])
            tok0s = tok0s[:len(pending)]
        toks, n_valid = np.asarray(toks), np.asarray(n_valid)
        disp.emitted(int(n_valid.sum()))
        t_done = time.time()
        with self._an.seg("finish"):
            # live roofline attribution: the fetch above waited out this
            # block's device work (plus any same-iteration prefill
            # sequenced before it — its FLOPs are pending and charged here)
            flops, cold_pf = self._consume_prefill_attr()
            self._perf.note_block(t_disp, t_done, hbm, flops,
                                  warm=decode_warm and not cold_pf)
            self._attr_last_gb = round(hbm / 1e9, 3)
            if self._cost.enabled:
                dcost, pcost = self._roofline_phase_costs(hbm, flops)
                self._cost_step = (max(0.0, t_done - t_disp), dcost, pcost,
                                   self._consume_prefill_cost())
            self._maybe_profile_slow_step(t_done - t_disp,
                                          decode_warm and not cold_pf)
        if bc < B or perm is not None:
            # scatter compact and/or group-permuted results back to
            # full-width slot arrays (srows maps dispatch row -> slot;
            # rows >= B are compact-batch pads)
            full_t = np.zeros((B, toks.shape[1]), toks.dtype)
            full_n = np.zeros((B,), n_valid.dtype)
            sel = srows < B
            full_t[srows[sel]] = toks[sel]
            full_n[srows[sel]] = n_valid[sel]
            return full_t, full_n, tok0s
        return toks, n_valid, tok0s

    def _get_decode_fn(self, w: int):
        if w in self._decode_fns:
            return self._decode_fns[w]
        cfg = self.model_cfg
        n_steps = self.decode_block
        eos_id = self.tokenizer.eos_id
        max_len = self.max_len
        rope_max = self.max_len
        use_ragged = self._use_ragged
        mesh_ = self._kernel_mesh()
        interp = self._interpret
        row_group = self._row_group

        kv_q = bool(self._kv_quant)
        moe = self._moe_on
        # a window layer's pages are its slot's ring: the rows' slots ride
        # along as they do for the int8 scales
        rows_to_slots = kv_q or bool(self._window)
        n_slots = self.B

        @partial(jax.jit, donate_argnums=(1, 2))
        def decode(params, k_pages, v_pages, kscale, vscale, scale_rows,
                   last_tok, kv_lens, table, active, key, temps, tk, tp):
            def step(carry, _):
                k_pages, v_pages, tok, lens, done, key = carry[:6]
                pos = jnp.minimum(lens, max_len - 1)[:, None]
                out = forward_paged(
                    params, cfg, tok[:, None], pos, k_pages, v_pages, table,
                    jnp.minimum(lens + 1, max_len), rope_max,
                    use_ragged_kernel=use_ragged,
                    mesh=mesh_, interpret=interp,
                    kv_scales=(kscale, vscale) if kv_q else None,
                    scale_rows=scale_rows if rows_to_slots else None,
                    decode_row_group=row_group,
                    token_valid=(~done)[:, None] if moe else None,
                    window_slots=n_slots,
                )
                logits, k_pages, v_pages = out[:3]
                key, sub = jax.random.split(key)
                # scan context, NOT vmap: sample_logits gates its full-
                # vocab sort behind lax.cond fast paths that vmap would
                # silently lower to compute-both-branches (ops/sampling.py;
                # test_model.test_sampler_cond_survives_scheduler_contexts)
                nxt = sample_logits(logits[:, 0], sub, temps, tk, tp)
                nxt = jnp.where(done, eos_id, nxt)
                newly_done = jnp.logical_or(done, nxt == eos_id)
                lens = jnp.where(done, lens, lens + 1)
                nxt_carry = (k_pages, v_pages, nxt, lens, newly_done, key)
                if moe:  # the block's counts: every step's, added up
                    nxt_carry += (carry[6] + out[-1],)
                return nxt_carry, (nxt, ~done)

            carry = (k_pages, v_pages, last_tok, kv_lens, ~active, key)
            if moe:
                carry += (jnp.zeros((cfg.n_routed_layers,
                                     cfg.experts_held + 3), jnp.int32),)
            carry, (toks, valid) = jax.lax.scan(
                step, carry, None, length=n_steps)
            k_pages, v_pages = carry[:2]
            toks = jnp.transpose(toks)
            valid = jnp.transpose(valid)
            if moe:
                return (toks, jnp.sum(valid, axis=1), k_pages, v_pages,
                        carry[6])
            return toks, jnp.sum(valid, axis=1), k_pages, v_pages

        logger.info("compiling paged decode: B=%d steps=%d window=%d pages "
                    "(ragged_kernel=%s row_group=%d)", self.B, n_steps, w,
                    use_ragged, row_group)
        self._decode_fns[w] = decode
        return decode

    # -------------------------------------------- speculative decode (k > 0)

    def seed_history(self, b: int, st: _SlotState) -> None:
        """Load slot b's token history into the device-resident buffer (one
        row upload at decode admission; the device appends from then on).
        Under tree speculation a cross-refresh draft hint (the previous
        refresh's summary, live/session.py) seeds AHEAD of the real
        history: the buffer column of the token at kv position p becomes
        p + spec_hoff, and the n-gram lookup window covers the hint — a
        near-perfect draft source for the next refresh's continuation."""
        if not self.spec_k:
            return
        if self._spec_buf is None:
            self._spec_buf = jnp.zeros((self.B, self.max_len), jnp.int32)
        row = np.zeros((self.max_len,), np.int32)
        hint = st.spec_hint if self._spec_tree else []
        hist = st.prompt_ids + st.generated
        hoff = min(len(hint), max(0, self.max_len - len(hist)))
        row[:hoff] = hint[:hoff]
        hist = hist[-(self.max_len - hoff):] if hoff < self.max_len else []
        row[hoff: hoff + len(hist)] = hist
        st.spec_hoff = hoff
        self._spec_buf = self._spec_buf.at[b].set(jnp.asarray(row))

    def _spec_decode_block(self, slots, last_tok, kv_lens, active, temps,
                           top_k, top_p) -> list[list[int]]:
        """One speculative decode dispatch; returns the per-slot emitted
        token lists.  The token-history buffer lives on device (seeded per
        row at decode admission, appended by the device inside the block) —
        no per-dispatch O(B*max_len) upload."""
        with self._an.seg("draft"):
            if self._spec_stale:
                # rows advanced by mixed steps since the last spec block:
                # their history rows missed the in-scan appends — re-seed
                # once per row here, at spec resumption, not per mixed step
                for b in sorted(self._spec_stale):
                    if slots[b] is not None and slots[b].phase == "decode":
                        self.seed_history(b, slots[b])
                self._spec_stale.clear()
        w, table = self._decode_window(slots,
                                       self.decode_block + self.spec_k)
        # the verify kernel passes the grouping but not the balanced
        # permutation: the token-history buffer is device-resident and
        # slot-indexed, so rows dispatch in slot order here.  Same gate as
        # _get_spec_decode_fn's use_ragged: under a multi-device mesh the
        # verify runs the ungrouped XLA path, and a sample here would
        # report padding waste for a dispatch that had no group layout
        if (self._row_group > 1 and self._use_ragged
                and self._kernel_mesh() is None):
            g = self._row_group
            self._h_group_occupancy.observe(
                int(np.sum(active)) / (-(-self.B // g) * g))
        self._key, sub = jax.random.split(self._key)
        args = (
            self.params, self.cache.k, self.cache.v, self._spec_buf,
            self.kscale, self.vscale,
            jnp.arange(self.B, dtype=jnp.int32),  # dispatch row -> slot
            jnp.asarray(last_tok), jnp.asarray(kv_lens),
            jnp.asarray(table[:, :w]), jnp.asarray(active), sub,
            jnp.asarray(temps), jnp.asarray(top_k), jnp.asarray(top_p),
        )
        key_ = ("specfn", w)
        cold = key_ not in self._ran_ok
        if cold:
            self._wd_grace_cold()
        t_disp = time.time()
        # a spec block is decode_steps verify steps of 1 + spec_k positions
        with self._an.dispatch(
                "spec", key_, rows=int(np.sum(active)), row_slots=self.B,
                q_tokens=0, prompt_tokens=0,
                q_slots=self.B * self.decode_steps * (1 + self.spec_k),
                ctx_tokens=int(np.sum(kv_lens[active])),
                layer_passes=self.model_cfg.cache_layers * self.decode_steps,
                hbm_bytes=self._bytes.block(
                    kv_lens[active], self.decode_steps,
                    self.model_cfg.cache_layers * self.decode_steps),
                cold=cold) as disp:
            out = self._get_spec_decode_fn(w)(*args)
        self._note_ran_ok(key_)
        toks, counts, self._spec_buf, self.cache.k, self.cache.v = out
        with self._an.seg("fetch"):
            toks, counts = self._timed_get((toks, counts))  # one transfer
        disp.emitted(int(np.sum(counts)))
        t_done = time.time()
        with self._an.seg("finish"):
            # spec blocks contribute step gaps but no byte/FLOP samples
            # (the verify-step byte model differs); pending prefill FLOPs
            # are consumed — still counted, never sampled — so they cannot
            # mis-attribute to a later plain block
            self._perf.note_gap(t_disp, t_done)
            flops, _ = self._consume_prefill_attr()
            if flops > 0:
                self._perf.c_flops.inc(flops)
            self._attr_last_gb = 0.0
            if self._cost.enabled:
                # no byte model for the verify step: phase costs 0 degrade
                # the ledger split to per-row token counts (documented)
                self._cost_step = (max(0.0, t_done - t_disp), 0.0, 0.0,
                                   self._consume_prefill_cost())
        emitted: list[list[int]] = []
        for b in range(self.B):
            row: list[int] = []
            accepted = 0
            for s in range(counts.shape[1]):
                c = int(counts[b, s])
                row.extend(int(t) for t in toks[b, s, :c])
                self._c_spec_accepted.inc(max(0, c - 1))
                accepted += max(0, c - 1)
            if accepted and slots[b] is not None:
                self._cost.note_saved(slots[b].req, spec_tokens=accepted)
            emitted.append(row)
        return emitted

    def _get_spec_decode_fn(self, w: int):
        key_ = ("specfn", w)
        if key_ in self._decode_fns:
            return self._decode_fns[key_]
        cfg = self.model_cfg
        n_steps = self.decode_steps
        k = self.spec_k
        ngram = max(2, self.cfg.speculate_ngram)
        eos_id = self.tokenizer.eos_id
        max_len = self.max_len
        rope_max = self.max_len
        # ragged multi-token verify: same gate as the decode kernel (the
        # multi kernel is its generalization); under a real multi-device
        # mesh the XLA multi path serves (one window gather — still not
        # window_prefill).  _kernel_mesh(), not self.mesh: a 1-device mesh
        # is single-device everywhere else too.
        use_ragged = self._use_ragged and self._kernel_mesh() is None
        interp = self._interpret
        row_group = self._row_group
        kv_q = bool(self._kv_quant)

        from lmrs_tpu.ops.sampling import filtered_probs
        from lmrs_tpu.ops.speculative import draft_lookup, verify_tokens

        @partial(jax.jit, donate_argnums=(1, 2, 3))
        def spec_decode(params, k_pages, v_pages, buf, kscale, vscale,
                        srows, last_tok, kv_lens, table, active, key,
                        temps, tk, tp):
            b_rows = jnp.arange(buf.shape[0])[:, None]
            offs = jnp.arange(k + 1)[None, :]

            def step(carry, _):
                k_pages, v_pages, buf, tok, lens, done, key = carry
                # current token enters the history at index == its KV position
                buf = buf.at[b_rows[:, 0], jnp.minimum(lens, max_len - 1)].set(tok)
                draft, n_valid = draft_lookup(buf, lens + 1, k, pad_id=eos_id,
                                              n=ngram)

                toks_in = jnp.concatenate([tok[:, None], draft], axis=1)
                positions = jnp.minimum(lens[:, None] + offs, max_len - 1)
                # kv_lens UNCLAMPED: the multi path derives the write base
                # as kv_lens - (k+1), which must be the true position even
                # when drafts overhang max_len (the max_pos cap masks the
                # overhang; a clamped length would slide the write span
                # backwards over real cache entries)
                out = forward_paged(
                    params, cfg, toks_in, positions, k_pages, v_pages, table,
                    lens + 1 + k, rope_max,
                    use_ragged_kernel=use_ragged, multi_decode=True,
                    interpret=interp,
                    kv_scales=(kscale, vscale) if kv_q else None,
                    scale_rows=srows if kv_q else None,
                    decode_row_group=row_group,
                )
                # scales are read-only in decode (frozen at prefill):
                # out[3:] returns them unchanged when kv_q
                logits, k_pages, v_pages = out[:3]
                # filtered_probs is deliberately cond-free, so this vmap
                # over the token axis is safe; sample_logits (lax.cond
                # fast paths) must never be called under it
                # (ops/sampling.py NOTE)
                probs = jax.vmap(filtered_probs, in_axes=(1, None, None, None),
                                 out_axes=1)(logits, temps, tk, tp)
                key, sub = jax.random.split(key)
                emit, count = verify_tokens(probs, draft, n_valid, sub)
                emit = jnp.where(done[:, None], eos_id, emit)
                count = jnp.where(done, 0, count)

                hit_eos = jnp.any((offs < count[:, None]) & (emit == eos_id), 1)
                newly_done = jnp.logical_or(done, hit_eos)
                # accepted tokens extend the history (the final emitted token
                # lands exactly at the next step's write index — idempotent)
                cols = jnp.minimum(lens[:, None] + 1 + offs, max_len - 1)
                buf = buf.at[b_rows, cols].set(emit)
                lens = jnp.minimum(lens + count, max_len)
                nxt = jnp.take_along_axis(
                    emit, jnp.maximum(count - 1, 0)[:, None], 1)[:, 0]
                nxt = jnp.where(done, tok, nxt)
                return (k_pages, v_pages, buf, nxt, lens, newly_done, key), (emit, count)

            carry = (k_pages, v_pages, buf, last_tok, kv_lens, ~active, key)
            (k_pages, v_pages, buf, *_), (toks, counts) = jax.lax.scan(
                step, carry, None, length=n_steps)
            # [steps, B, k+1] -> [B, steps, k+1]; counts [steps, B] -> [B, steps]
            return (jnp.transpose(toks, (1, 0, 2)), jnp.transpose(counts),
                    buf, k_pages, v_pages)

        logger.info("compiling speculative decode: B=%d steps=%d k=%d "
                    "window=%d pages", self.B, n_steps, k, w)
        self._decode_fns[key_] = spec_decode
        return spec_decode
