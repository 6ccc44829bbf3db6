"""LLMEngine: the serving engine core.

Owns params + paged KV caches on device, the block pool, the scheduler and
the jitted step functions.  Each step executes exactly one scheduler plan —
a bucketed prefill, a bucket-padded decode batch, or a fused MIXED step
(every running sequence's decode token plus a bounded prefill chunk of the
head waiting sequence in one packed invocation, so arriving prompts no
longer stall the decoders).  Every plan shape maps to a cached XLA
executable, so steady-state serving never recompiles.

Stepping is split into a ``dispatch()``/``collect()`` pair wired as an
async one-step-lookahead pipeline: decode step N+1 is dispatched to the
device (its input tokens chained from step N's still-in-flight sample)
BEFORE step N's result is read back, so host-side scheduling, sampling
post-processing and detokenization overlap device compute instead of
serializing against it.  ``step()`` keeps the classic contract
(one plan's outputs per call) on top of that pipeline.

The engine is the TPU-side counterpart of what the reference runs as an
external ``vllm serve`` container (deployment-vllm-multi.yaml:57-64); the
server wrapper in engine/server/ speaks the same OpenAI + /metrics contract
the router expects.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import threading
import time
import zlib
from collections import OrderedDict, deque
from functools import partial
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from production_stack_tpu.engine.config import (
    PAGED_KINDS, PRESETS, EngineConfig,
)
from production_stack_tpu.engine.core import step_programs
from production_stack_tpu.engine.core.scheduler import (
    DecodePlan,
    PrefillPlan,
    Scheduler,
)
from production_stack_tpu.engine.core.sequence import (
    FinishReason,
    SamplingParams,
    Sequence,
    SequenceStatus,
    StepOutput,
    host_state_flags as seq_host_state_flags,
)
from production_stack_tpu.engine.kv.block_pool import (
    BlockPool,
    extend_prefix_chain,
    prefix_block_hashes,
)
from production_stack_tpu.engine.kv import quant as kv_quant
from production_stack_tpu.engine.kv.offload import HostOffloadManager, OffloadStager
from production_stack_tpu.engine.kv.state_pool import StatePool, pool_slots
from production_stack_tpu.engine.kv.prefetch import PrefetchedChain, PrefetchManager
from production_stack_tpu.engine.models import get_model
from production_stack_tpu.engine.models.weights import load_params
from production_stack_tpu.engine.ops import attention as attn_ops
from production_stack_tpu.obs.engine import EngineObs
from production_stack_tpu.utils.compile_cache import compile_cache_report
from production_stack_tpu.obs.histogram import Histogram
from production_stack_tpu.engine.parallel import shardings as shardings_lib
from production_stack_tpu.engine.parallel.mesh import AXES, build_mesh
from production_stack_tpu.engine import sampling as sampling_lib
from production_stack_tpu.engine.sampling import sample_tokens
from production_stack_tpu.engine.tokenizer import get_tokenizer

logger = logging.getLogger(__name__)


def _dtype_size(dtype: str) -> int:
    return jnp.dtype(dtype).itemsize


def _enclosed(span: str):
    """Run an ``LLMEngine`` method under one of the two ENCLOSING phase
    spans of obs/engine.py: ``dispatch`` (an asynchronous dispatch: its
    build + launch) or ``mixed`` (a fused step, end to end) — the wall
    time their ``tpu:step_<span>_seconds`` families have always timed."""
    def decorate(fn):
        @functools.wraps(fn)
        def under_span(self, *args, **kwargs):
            with self.obs.phase(span):
                return fn(self, *args, **kwargs)
        return under_span
    return decorate


@dataclasses.dataclass
class _PendingStep:
    """One dispatched-but-not-yet-collected engine step.

    Synchronous steps (prefill, speculative, and decode batches using
    host-state sampling features) carry precomputed ``outputs``;
    pipelined decode steps carry the batch rows and the still-in-flight
    device sample instead — [S] for a single-token step, [K, S] emitted
    tokens for a K-step window (``steps`` holds the per-row iteration
    budgets and ``win_state`` the device-resident window carry the next
    window chains from)."""

    outputs: Optional[List[StepOutput]] = None
    seqs: Optional[List[Sequence]] = None
    sampled: Optional[object] = None  # jax.Array [S] or [K, S], uncollected
    is_decode: bool = False
    host_s: float = 0.0  # host time spent dispatching this step
    steps: Optional[List[int]] = None  # per-row window TOKEN budgets (windows)
    win_state: Optional[dict] = None  # device window carry (windows)
    # Fused speculative windows: ``sampled`` is [K, W, S] (W = draft_len
    # + 1 sub-steps per scan iteration) and ``spec_stats`` the still-in-
    # flight (drafted [K, S], accepted [K, S]) device counters collect()
    # folds into tpu:spec_tokens_* and tpu:spec_window_tokens_total;
    # ``spec_drafter`` names the proposal source that ran ("ngram" /
    # "model") for the per-drafter accounting.
    spec_stats: Optional[tuple] = None
    spec_drafter: Optional[str] = None
    # A routed model's still-in-flight per-step counts ([K, n] int32, the
    # module's stats_names), read back with the tokens at collect.
    routing: Optional[object] = None
    # Mixed K-step windows: the chunk schedule that rode the scan (one
    # PrefillPlan per live iteration — packed windows interleave several
    # prompts' chunks), the still-in-flight per-iteration tail logits
    # [n_scan, V] (None when no chunk in the window was final), and the
    # window's BASE step-counter ordinal — a final chunk at iteration f
    # samples its prompt's first token with ordinal base + f, the PRNG
    # key the K=1 path would burn for that step.
    chunk_sched: Optional[List] = None
    chunk_logits: Optional[object] = None
    chunk_ordinal: int = 0
    # A dedicated prefill launched without its read-back
    # (_dispatch_prefill_async): its plan and, for a final chunk, the prompt's
    # first token as the sampler left it on the device ([1] int32), which
    # collect() reads back and the window launched behind this prefill
    # takes on the device.  ``sampled`` stays None: nothing chains from it.
    chunk: Optional[PrefillPlan] = None
    first_token: Optional[object] = None
    # Window flight record (obs/flight_recorder.WindowRecord) stamped at
    # dispatch; collect() completes + publishes it.  None when tracing is
    # off (the recorder is never consulted) or the step completed its
    # record synchronously at dispatch.
    rec: Optional[object] = None


def _own_cache(cfg) -> bool:
    """A module with ``init_cache`` keeps a cache of its own shape
    (models/registry.py): allocation, byte count and sharding ask it."""
    return hasattr(get_model(cfg.name), "init_cache")


class LLMEngine:
    def __init__(self, config: EngineConfig):
        self.config = config
        cfg = config.model
        self.model = get_model(cfg.name)
        self.tokenizer = get_tokenizer(config.tokenizer)
        if self.tokenizer.vocab_size > cfg.vocab_size:
            raise ValueError(
                f"Tokenizer vocab ({self.tokenizer.vocab_size}) exceeds model "
                f"vocab ({cfg.vocab_size})"
            )

        # SPMD mesh: dp shards the decode batch, tp shards heads/channels,
        # sp is the ring-attention axis for long prefill (parallel/mesh.py).
        # world_size==1 builds a trivial single-device mesh so the code path
        # is identical on one chip and on a slice.
        par = config.parallel
        shardings_lib.validate_tp(cfg, par.tensor_parallel)
        shardings_lib.validate_sp_mode(cfg, par)
        if config.scheduler.max_num_seqs % par.data_parallel:
            raise ValueError(
                f"max_num_seqs={config.scheduler.max_num_seqs} must be "
                f"divisible by data_parallel={par.data_parallel}"
            )
        # Mixed prefill+decode steps pack one [S+T] token batch; that row
        # axis is neither dp- nor sp-shardable (its two segments shard
        # differently), so a dp/sp mesh turns the auto gate off and
        # rejects an explicit request rather than serving a silently
        # different schedule.
        if par.data_parallel > 1 or par.sequence_parallel > 1:
            if config.scheduler.mixed_batch:
                raise ValueError(
                    "mixed_batch=True requires data_parallel == "
                    "sequence_parallel == 1 (the packed mixed token batch "
                    "cannot be dp/sp-sharded); drop the flag or the mesh "
                    "axis"
                )
            config.scheduler.mixed_batch = False
        if config.scheduler.mixed_enabled:
            for bucket in config.scheduler.prefill_chunk_buckets:
                if bucket % config.cache.block_size:
                    raise ValueError(
                        f"prefill chunk bucket {bucket} not divisible by "
                        f"block_size={config.cache.block_size} (non-final "
                        "chunks must leave the cached prefix block-aligned)"
                    )
        if par.sequence_parallel > 1:
            if cfg.sliding_window is not None:
                raise ValueError(
                    "sequence_parallel>1 is not supported with "
                    "sliding_window models (the ring path has no local-"
                    "attention mask); use sp=1"
                )
            span = config.cache.block_size * par.sequence_parallel
            for bucket in config.scheduler.prefill_buckets:
                if bucket % span:
                    raise ValueError(
                        f"prefill bucket {bucket} not divisible by "
                        f"block_size*sp={span}"
                    )
            if config.scheduler.max_model_len % span:
                raise ValueError(
                    f"max_model_len={config.scheduler.max_model_len} not "
                    f"divisible by block_size*sp={span} (the cached-prefix "
                    "ring shards the prefix block table over sp)"
                )
        self.mesh = build_mesh(par)
        self._shardings: Dict[P, NamedSharding] = {}  # _sharding's memo
        self._report_device_and_kernels()

        logger.info("Loading params for %s ...", cfg.name)
        # Created (or read), quantized and placed tensor by tensor in the
        # final sharding: the whole model never sits on one device.
        self.params = load_params(
            cfg, config.weights_path, seed=config.seed,
            shardings=shardings_lib.param_shardings(cfg, self.mesh),
        )

        # Draft model for in-scan speculative decoding
        # (scheduler.speculative_model): a second, tiny model loaded
        # through the SAME registry/weights path as the target and
        # sharded on the same mesh.  Compatibility is validated LOUDLY
        # at boot whenever a draft model is configured — a vocab
        # mismatch would silently collapse acceptance (draft argmax over
        # a different token space) or propose out-of-range ids; params
        # are loaded only when the fused window will actually run
        # (spec_window_enabled), so an inert K=1 config stays cheap.
        self.draft_model = None
        self.draft_cfg = None
        self.draft_params = None
        if config.scheduler.speculative_model is not None:
            name = config.scheduler.speculative_model
            if name not in PRESETS:
                raise ValueError(
                    f"Unknown speculative_model preset {name!r}; "
                    f"available: {sorted(PRESETS)}"
                )
            draft_cfg = dataclasses.replace(PRESETS[name])
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"speculative_model {name!r} vocab "
                    f"({draft_cfg.vocab_size}) != target {cfg.name!r} vocab "
                    f"({cfg.vocab_size}): the drafter must share the "
                    "target's tokenizer/vocab — a mismatched drafter "
                    "proposes tokens the target cannot accept (or ids "
                    "outside its vocab), silently degrading acceptance; "
                    "refusing to boot"
                )
            shardings_lib.validate_tp(draft_cfg, par.tensor_parallel)
            self.draft_cfg = draft_cfg
            if config.scheduler.spec_window_enabled:
                self.draft_model = get_model(draft_cfg.name)
                logger.info("Loading draft params for %s ...", draft_cfg.name)
                self.draft_params = load_params(
                    draft_cfg, config.draft_weights_path, seed=config.seed,
                    shardings=shardings_lib.param_shardings(
                        draft_cfg, self.mesh
                    ),
                )

        # A module that keeps recurrent state beside its keys
        # (state_bytes_per_slot, models/registry.py) gets a pool of slots
        # beside the block pool, sized by rule from the batch; its bytes come
        # off what the block pool is sized from.
        self.state_pool = None
        if hasattr(self.model, "state_bytes_per_slot"):
            self.state_pool = StatePool(
                *pool_slots(config.scheduler.max_num_seqs))
        num_blocks = self._decide_num_blocks()
        # Pages one DMA of the paged decode walk carries for this model
        # (1: every page alone); the pool keeps runs of as many together.
        self._kv_group_blocks = self._decide_kv_group_blocks()
        self.block_pool = BlockPool(
            num_blocks,
            config.cache.block_size,
            enable_prefix_caching=config.cache.enable_prefix_caching,
            run=self._kv_group_blocks,
        )
        # Cross-engine prefix sharing (cache.disagg_role): content-keyed
        # block export/import through the remote store.
        self._disagg_role = config.cache.disagg_role
        self._exports = self._disagg_role in ("prefill", "both")
        imports = self._disagg_role in ("decode", "both")
        self._imports = imports
        # digest -> export expiry: entries re-export after the TTL so a
        # store-side eviction doesn't silently end sharing forever.
        self._exported_hashes: "OrderedDict[bytes, float]" = OrderedDict()
        self._export_ttl_s = 300.0
        self._export_queue = None
        self._export_thread = None
        # Guards the export thread/queue handles: lazily started from
        # the step thread, retired from the close path (asyncio loop).
        self._export_lock = threading.Lock()
        self.remote_prefix_blocks_fetched = 0
        self.remote_prefix_blocks_exported = 0
        # Disaggregated serving counters (written ONLY by the API
        # server's event loop — the single-writer-per-thread contract
        # the deadline counters follow): prefill-phase primes served,
        # and decode-phase handoff prefetch outcomes.
        self.disagg_prefill_primes = 0
        self.disagg_handoff_hits = 0
        self.disagg_handoff_misses = 0
        self.scheduler = Scheduler(
            config.scheduler,
            self.block_pool,
            offload_cb=self.offload_seq_blocks,
            restore_cb=self.restore_seq_blocks,
            remote_prefix_cb=self.fetch_remote_prefix if imports else None,
            state_pool=self.state_pool,
            state_stride=(
                self.model.snapshot_stride(cfg) if self.state_pool else 0),
        )
        weights_in_use = self.device_report()["memory"][0]["bytes_in_use"]
        self.kv_caches = self._allocate_kv(num_blocks)
        logger.info(
            "KV pool: %d blocks x %d tokens (%.2f GiB)",
            num_blocks,
            config.cache.block_size,
            self._kv_bytes(num_blocks) / 2**30,
        )
        if self.state_pool is not None:
            logger.info(
                "State pool: %d live + %d snapshot slots x %.2f MB (%d "
                "layers' state a slot; %.2f GiB), a snapshot every %d tokens "
                "of a prompt's last chunk",
                self.state_pool.live_slots, self.state_pool.snapshot_slots,
                self.model.state_bytes_per_slot(cfg) / 1e6,
                sum(cfg.layer_kind(i) not in PAGED_KINDS
                    for i in range(cfg.num_layers)),
                self._state_bytes() / 2**30,
                self.model.snapshot_stride(cfg),
            )
        mem = self.device_report()["memory"][0]
        if mem["bytes_in_use"] is not None:
            # What a deployment would hold: the weights, then the pool.
            logger.info(
                "Device memory: %.3f GB in use after the weights, %.3f GB "
                "with the KV pool, of %.3f GB",
                weights_in_use / 1e9, mem["bytes_in_use"] / 1e9,
                (mem["bytes_limit"] or 0) / 1e9,
            )

        # Dedicated draft-KV pool (model drafter only): the draft
        # model's device-resident cache lives in its OWN small block
        # pool, so target KV capacity is untouched and a draft-side
        # allocation failure can never preempt serving — it declines the
        # window to plain (tpu:multistep_fallback_total{reason=
        # draft_pool}).  Per-row capacity covers a full causal prime of
        # the carried history window plus _DRAFT_PRIME_CHAIN windows of
        # max-acceptance growth between primes (the skip-prime chain).
        # Dense dtype regardless of cache.kv_cache_dtype: the pool is
        # tiny (a 2-layer drafter at H+chain tokens per row) and the
        # int8 (data, scale) plumbing would buy nothing.
        self.draft_block_pool = None
        self.draft_kv_caches = None
        self._draft_blocks_per_row = 0
        # Host-side draft-cache coherence state (step-thread-only):
        # whether the device draft KV currently extends the batch's
        # committed context (any non-model-spec dispatch breaks it), and
        # how many windows chained since the last in-graph prime (the
        # conservative capacity watermark).
        self._draft_primed = False
        self._draft_windows_since_prime = 0
        self._draft_block_alloc: List[int] = []
        if self.draft_params is not None:
            bs = config.cache.block_size
            cap = (
                self._SPEC_HIST_WINDOW
                + self._DRAFT_PRIME_CHAIN * config.scheduler.window_max_tokens
            )
            self._draft_blocks_per_row = -(-cap // bs)
            pool_blocks = config.scheduler.speculative_draft_pool_blocks
            if pool_blocks is None:
                # Auto: every decode row fits simultaneously (+1 for the
                # reserved null block 0) — exhaustion only under an
                # explicit undersized override.
                pool_blocks = (
                    config.scheduler.max_num_seqs * self._draft_blocks_per_row
                    + 1
                )
            self.draft_block_pool = BlockPool(
                pool_blocks, bs, enable_prefix_caching=False
            )
            self.draft_kv_caches = self._allocate_draft_kv(pool_blocks)
            logger.info(
                "Draft KV pool: %d blocks x %d tokens (%d blocks/row)",
                pool_blocks, bs, self._draft_blocks_per_row,
            )

        offload_bytes = int(config.cache.host_offload_gb * 2**30)
        # Wire representation for offload/remote snapshots
        # (cache.kv_wire_format): with an int8 cache the tiers carry the
        # native (data, scale) tuples end-to-end; bytes crossing each
        # tier boundary and serde versions feed
        # tpu:kv_wire_bytes_total{tier,format} /
        # tpu:kv_snapshot_format_total{version}.
        from production_stack_tpu.kvserver.protocol import KVWireStats

        self._wire_quantized = config.cache.wire_quantized
        self.kv_wire_stats = KVWireStats()
        remote_client = None
        if config.cache.remote_kv_url:
            from production_stack_tpu.kvserver.client import RemoteKVClient

            remote_client = RemoteKVClient(
                config.cache.remote_kv_url, wire_stats=self.kv_wire_stats,
                require_v2=config.cache.kv_wire_format == "int8",
            )
        self.offload = HostOffloadManager(
            offload_bytes, remote_client,
            quantized_wire=self._wire_quantized,
            wire_stats=self.kv_wire_stats,
        )
        # Asynchronous batched KV transfer plane (cache.remote_prefetch):
        # admission-time remote-prefix prefetch on fetcher threads,
        # off-step offload staging, async restore page-in.  None when no
        # remote store (or the legacy synchronous path was requested) —
        # every consumer falls back to today's blocking behavior.
        self.kv_prefetch: Optional[PrefetchManager] = None
        self._offload_stager: Optional[OffloadStager] = None
        # The prefetch plane delivers through the prefix cache
        # (match_prefix over adopted blocks); with caching disabled it
        # could never serve a fetched block, so that config keeps the
        # legacy sync extension, which works per-request without the
        # cache.
        if (
            remote_client is not None
            and config.cache.remote_prefetch_enabled
            and config.cache.enable_prefix_caching
        ):
            self.kv_prefetch = PrefetchManager(
                remote_client,
                restore_sink=self.offload,
                num_threads=config.cache.prefetch_threads,
                observe_fetch=lambda s: self.obs.kv_phase(
                    "remote_kv_fetch", s
                ),
            )
        # The stager also covers host-DRAM-only offload (no remote tier):
        # the D2H snapshot wait is a step-thread stall either way.  Only
        # an explicit remote_prefetch=False keeps the blocking save.
        if offload_bytes > 0 and config.cache.remote_prefetch is not False:
            self._offload_stager = OffloadStager(
                self.offload,
                observe_stage=lambda s: self.obs.kv_phase(
                    "offload_stage", s
                ),
            )
        # Completed prefetches awaiting import into the prefix cache
        # (kept across steps under transient pool pressure).
        self._pending_prefetch_imports: List[PrefetchedChain] = []

        # Fixed shape constants.
        self._bmax = config.scheduler.max_model_len // config.cache.block_size
        self._smax = config.scheduler.max_num_seqs

        # Observability hub: request tracer + step-phase spans/histograms
        # + window flight recorder + compile-event tracker (all hooks
        # no-op when config.obs.tracing is off).  Made before the jitted
        # step functions, which are named and tracked through it (_jit).
        self.obs = EngineObs(
            enabled=config.obs.tracing,
            ring_size=config.obs.trace_ring_size,
            ring_bytes=config.obs.trace_ring_bytes,
            window_ring_size=config.obs.window_ring_size,
            annotation=jax.profiler.TraceAnnotation,
        )

        # Jitted step functions.  KV caches are donated so updates alias the
        # same HBM; cfg and mesh are closed over (static).
        # A module that counts on the device what its router (or its residual
        # path) did (stats_names, models/registry.py) is asked for the counts on the
        # steps the served path takes: the dedicated prefill and the K-step
        # window.  They come back as one more result and are read with the
        # tokens; a module without the attribute is called as ever.
        self._routing_names = (
            self.model.stats_names(cfg) if hasattr(self.model, "stats_names")
            else ())
        # Which of them fold by a maximum over steps and dispatches (a
        # fullest expert's rows, a worst row sum); every other adds.
        self._routing_max = np.array([
            name in self.model.STATS_MAX for name in self._routing_names],
            bool)
        counting = {"return_stats": True} if self._routing_names else {}
        # Prefill dispatches' counts still on the device: (record, [n]).
        self._routing_pending: Deque[tuple] = deque()
        # tpu:moe_assignments_total{where} / tpu:moe_experts_touched_total /
        # tpu:moe_zero_assigned_total (picks that named an identity expert:
        # models/longcat.py).
        self.moe_assignments: Dict[str, int] = {"held": 0, "away": 0}
        self.moe_experts_touched = 0
        self.moe_zero_assigned = 0
        # tpu:mhc_clamped_total / tpu:mhc_entries_total /
        # tpu:mhc_sinkhorn_err: a residual path of several streams' mixing
        # matrices (models/sarvam_mla.py: RESIDUAL_STATS), entries the clamp
        # changed of entries seen, and the largest |row sum - 1| any
        # dispatch has read after the last normalisation.
        self.mhc_clamped = 0
        self.mhc_entries = 0
        self.mhc_sinkhorn_err = 0.0
        # tpu:ssm_state_absmax / tpu:ssm_dt_max: a module with selective
        # state-space layers (models/jamba.py: SSM_STATS), the largest |h|
        # any dispatch has left in a slot and the largest step size.
        self.ssm_state_absmax = 0.0
        self.ssm_dt_max = 0.0
        # tpu:gdn_state_absmax / tpu:gdn_beta_max: a module with delta-rule
        # layers under a decay a head (models/olmo_hybrid.py: GDN_STATS), the
        # largest |S| any dispatch has left in a slot and the largest beta.
        self.gdn_state_absmax = 0.0
        self.gdn_beta_max = 0.0
        # tpu:sample_dispatch_total / tpu:sample_sorted_dispatch_total:
        # dispatched programs that sample, and those whose rows make the
        # sampler sort the vocabulary (sampling.needs_sort).
        self.sample_dispatches = 0
        self.sample_sorted_dispatches = 0
        # tpu:prefix_chain_blocks_total / ..._step_blocks_total: blocks of
        # a sequence's prefix chain hashed by the API server's handler
        # (event-loop-only writer, here) and on the step thread (the
        # pool's chain_blocks_hashed); stats() reports the sum and the
        # step thread's part.
        self.prefix_chain_handler_blocks = 0
        # What a prefill chunk is told beside its arrays, in the order its
        # packed vector holds them (step_programs.prefill_program).
        self._prefill_scalars = ("cached_len", "valid_len")
        if config.lora.enabled:
            self._prefill_scalars += ("adapter_idx",)
        if self.state_pool is not None:
            self._prefill_scalars += (
                "state_slot", "state_from", "snapshot_slot", "snapshot_len",
            )
        self._prefill_fn = self._jit(
            "prefill_fn",
            step_programs.prefill_program(
                partial(
                    self.model.prefill, cfg=cfg, mesh=self.mesh,
                    sp_mode=par.sequence_parallel_mode, **counting,
                ),
                self._prefill_scalars, config.cache.block_size,
                max(self._bmax, 1),
            ),
            donate_argnames=("kv_caches",),
            static_argnames=("prompt_topk",),
        )
        model_decode = partial(self.model.decode, cfg=cfg, mesh=self.mesh)
        self._decode_fn = self._jit(
            "decode_fn", model_decode, donate_argnames=("kv_caches",),
        )
        # Fused mixed prefill+decode step (StepPlan decode+chunk): one
        # executable per (decode bucket, chunk bucket) pair — jit retraces
        # per shape, and both axes come from small bucket sets.
        self._mixed_fn = None
        if config.scheduler.mixed_enabled and hasattr(self.model, "mixed_step"):
            self._mixed_fn = self._jit(
                "mixed_fn",
                partial(self.model.mixed_step, cfg=cfg, mesh=self.mesh),
                donate_argnames=("kv_caches",),
            )
        elif config.scheduler.mixed_enabled:
            # Model without a fused entry point: fall back to alternating
            # plans rather than failing at the first mixed dispatch.
            config.scheduler.mixed_batch = False
        self._sample_fn = self._jit("sample_fn", sample_tokens)

        # The step programs (engine/core/step_programs.py): what runs on
        # the device between two host round trips.  Built there from the
        # model's entry points and jitted here under the names the trace,
        # the flight records and the compile tracker read.
        self._window_fn = None
        self._spec_window_fn = None
        self._mixed_window_fn = None
        self._window_steps = config.scheduler.window_steps
        # Per-window per-row token ceiling (max-acceptance growth under
        # the fused speculative window): sizes the chained-window
        # block-table delta and mirrors the scheduler's block budget.
        self._window_max_tokens = config.scheduler.window_max_tokens
        if self._window_steps > 1:
            dims = dict(
                block_size=config.cache.block_size, vocab=cfg.vocab_size,
            )
            self._window_fn = self._jit(
                "window_fn",
                step_programs.window_program(
                    partial(model_decode, **counting),
                    n_steps=self._window_steps,
                    n_counts=len(self._routing_names), **dims
                ),
                static_argnames=("use_penalties", "use_min_floor"),
                donate_argnames=("kv_caches",),
            )
            if config.scheduler.spec_window_enabled:
                drafter = config.scheduler.spec_drafter
                self._spec_window_fn = self._jit(
                    "spec_window_fn",
                    step_programs.spec_window_program(
                        model_decode,
                        partial(
                            self.draft_model.decode, cfg=self.draft_cfg,
                            mesh=self.mesh,
                        ) if drafter == "model" else None,
                        drafter=drafter,
                        draft_len=config.scheduler.spec_draft_len,
                        hist_window=self._SPEC_HIST_WINDOW,
                        n_steps=self._window_steps, **dims,
                    ),
                    static_argnames=(
                        "use_penalties", "use_min_floor", "do_prime",
                    ),
                    donate_argnames=(
                        ("kv_caches", "draft_kv") if drafter == "model"
                        else ("kv_caches",)
                    ),
                )
            # Chained-window block-table growth: up to C new blocks a row.
            self._win_advance_fn = self._jit(
                "win_advance_fn", step_programs.table_scatter
            )
            self._win_occurrence_fn = self._jit(
                "win_occurrence_fn",
                partial(
                    sampling_lib.occurrence_state, vocab_size=cfg.vocab_size
                ),
            )
            if (
                self._mixed_fn is not None
                and config.scheduler.mixed_window_enabled
            ):
                self._mixed_window_fn = self._jit(
                    "mixed_window_fn",
                    step_programs.mixed_window_program(
                        partial(
                            self.model.mixed_step, cfg=cfg, mesh=self.mesh
                        ),
                        **dims,
                    ),
                    static_argnames=(
                        "n_steps", "use_penalties", "use_min_floor",
                    ),
                    donate_argnames=("kv_caches",),
                )
        self._penalties_fn = self._jit(
            "penalties_fn", sampling_lib.apply_penalties
        )
        # Speculative decoding effectiveness counters (fed by the fused
        # window path, both drafters).
        self.spec_tokens_drafted = 0
        self.spec_tokens_accepted = 0
        # Fused speculative-window outcomes per collected window
        # (tpu:spec_window_tokens_total{outcome,drafter}): draft tokens the
        # verifier accepted / rejected inside windows, and window tokens
        # emitted by the fused path but undeliverable at collect
        # (abort / out-of-band finish mid-window).  Step-thread-only
        # writer, like the multistep counters.
        self.spec_window_tokens: Dict[str, int] = {
            "accepted": 0, "rejected": 0, "wasted": 0,
        }
        # Scan seconds spent in the model drafter's forwards
        # (tpu:spec_draft_fraction_seconds): measured window sync time x
        # a static cost-model split — per scan iteration the drafter
        # runs (D+1) single rows (plus the amortized prime: (H-1) rows
        # every _DRAFT_PRIME_CHAIN x K iterations) through the DRAFT
        # parameter set while the verifier runs W = D+1 rows through the
        # TARGET set; decode is weight-streaming-bound, so row-count x
        # param-count is the honest first-order split.  Step-thread-only
        # writer.
        self.spec_draft_fraction_s = 0.0
        self._draft_cost_fraction = 0.0
        if self.draft_params is not None:
            tgt_n = sum(
                x.size for x in jax.tree_util.tree_leaves(self.params)
            )
            dft_n = sum(
                x.size for x in jax.tree_util.tree_leaves(self.draft_params)
            )
            d_len = config.scheduler.spec_draft_len
            draft_rows = (d_len + 1) + (self._SPEC_HIST_WINDOW - 1) / (
                self._DRAFT_PRIME_CHAIN * self._window_steps
            )
            self._draft_cost_fraction = (draft_rows * dft_n) / (
                draft_rows * dft_n + (d_len + 1) * tgt_n
            )
        self._logprobs_fn = self._jit(
            "logprobs_fn", sampling_lib.top_logprobs_of,
            static_argnames=("k",),
        )

        # Multi-LoRA slot arrays (engine/lora.py); None keeps the model's
        # lora-free code path (zero overhead, separate compiled programs).
        self.lora_registry = None
        if config.lora.enabled:
            from production_stack_tpu.engine.lora import AdapterRegistry

            self.lora_registry = AdapterRegistry(
                cfg, config.lora, jnp.dtype(cfg.dtype)
            )

        self._step_counter = 0
        self._encode_fn = None  # lazily jitted /v1/embeddings path
        # Lazily jitted [B, T]-bucketed encode-lane executable (one per
        # static shape, compile-tracked like every other jit family).
        self._encode_batch_fn = None
        # Encode-lane counters (tpu:encode_* families).  The batch
        # counters/histograms are STEP-THREAD-only writers (the batcher
        # runs encode batches from the step loop); encode_queue_depth is
        # a gauge the AsyncEngine's batcher overwrites from either side
        # (plain int store — racy-but-benign snapshot, never summed).
        self.encode_texts_total = 0
        self.encode_batch_size_hist = Histogram(
            bounds=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0)
        )
        self.encode_seconds_hist = Histogram(
            bounds=(0.005, 0.02, 0.05, 0.1, 0.25, 1.0, 4.0)
        )
        self.encode_queue_depth = 0
        self._token_texts = None  # guided decoding token-text cache
        self._seqs: Dict[str, Sequence] = {}
        # Cumulative counters for /metrics.
        self.total_prompt_tokens = 0
        self.total_generated_tokens = 0
        self.total_finished = 0
        # Prompt tokens prefilled INSIDE mixed steps (the interference-
        # removal signal: nonzero means prompts are chunking alongside
        # live decodes instead of stalling them).
        self.prefill_chunk_tokens = 0
        # The subset of prefill_chunk_tokens that rode a mixed K-STEP
        # window (tpu:mixed_window_chunk_tokens_total): nonzero means
        # sustained arrivals are amortizing the host round-trip instead
        # of forcing K=1 steps.  Step-thread-only writer.
        self.mixed_window_chunk_tokens = 0
        # Distinct prompts whose chunks rode each mixed K-step window
        # (tpu:mixed_window_prompts_per_window): >1 means the packed
        # multi-prompt path is filling windows under queue depth.
        # Lives on the engine (not EngineObs) because the packed-window
        # contract metrics render regardless of tracing.  Step-thread-
        # only writer; Histogram.observe is thread-safe anyway.
        self.mixed_window_prompts_hist = Histogram(
            bounds=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0)
        )
        # Steps each pure-decode window was planned to run
        # (tpu:decode_window_steps; scheduler._plan_window): mass under the
        # cap is windows that ended with a row's last token or as soon as
        # the step thread's pass was covered.  Step thread writes.
        self.window_steps_hist = Histogram(
            bounds=(1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 16.0)
        )
        # Whether this engine's own clocks may shape its plans (the window
        # length: scheduler.WindowPace).  Cleared by whoever steps this
        # engine in lockstep with others' (AsyncEngine under a lockstep
        # channel, a follower's loop): their clocks differ, their plans may
        # not.
        self.plan_from_clocks = True
        # Seconds of host<->device transfer work issued WHILE the device
        # was busy with an in-flight window — H2D chunk staging for a
        # chained window plus D2H offload gathers dispatched under the
        # scan (tpu:window_transfer_overlap_seconds_total): stalls the
        # overlap-everything dispatch avoided.  Step-thread-only writer.
        self.window_transfer_overlap_s = 0.0
        # Double-buffered host staging arrays for packed-window chunk
        # payloads, keyed by (n_scan, T): two alternating sets per scan
        # shape so building window N+1's H2D payload never waits on
        # window N's still-draining copy.
        self._mw_stage: Dict[tuple, list] = {}
        # Overload-protection counters (docs/robustness.md): requests the
        # API server shed with a structured 429 (bounded admission), and
        # requests shed or aborted because their client deadline expired.
        # deadline_expired is written by the STEP THREAD (queued-expiry
        # sweep) and deadline_expired_admission by the EVENT LOOP
        # (admission sheds) — one writer each, because a shared `+= 1`
        # across threads silently loses increments; stats() reports the
        # sum.  admission_rejected is event-loop-only.
        self.admission_rejected = 0
        self.deadline_expired = 0
        self.deadline_expired_admission = 0
        # K-step window observability (docs/observability.md): dispatches
        # that fell back to single-step because a co-scheduled request
        # needed host-sampled features (by reason — a single logprobs
        # request silently de-optimized every co-scheduled stream before
        # this counter existed), and emitted-but-undeliverable window
        # tokens (abort / out-of-band finish while the window flew; the
        # device stop-mask keeps ordinary stops at zero waste).  Both are
        # step-thread-only writers.
        self.multistep_fallback: Dict[str, int] = {}
        self.multistep_wasted_tokens = 0
        # Kv tiles of the prefill attention kernel's grid (the flash prefill
        # kernel's, or the module's own: _count_kv_tiles) that were computed
        # / skipped by its liveness rule, per layer, summed over dispatched
        # prefill chunks (tpu:prefill_attn_tiles_total{state}); host
        # arithmetic in _count_kv_tiles, step-thread-only writer.
        self.prefill_attn_tiles: Dict[str, int] = {"live": 0, "skipped": 0}
        # Where a descriptor of the paged decode walk carries several pages
        # (_kv_group_blocks > 1): the groups the live rows' tables held and
        # those that were one region of the pool, by the kernel's own rule,
        # summed over the decode batches built from host state
        # (tpu:paged_decode_groups*_total); step-thread-only writer.  The
        # last batch's pair goes on its flight record.
        self.paged_decode_groups = {"total": 0, "coalesced": 0}
        self._last_kv_groups = (0, 0)
        # The model's kinds of layer that attend over keys, as _open_record
        # and _count_kv_tiles read them: (label, window or None, layers of
        # the kind, whether its keys lie in slots of the state pool).  One
        # kind for a model with one (its scalar sliding_window); a kind a
        # spec where the layers differ (config.AttentionSpec).  And the
        # positions the decode rows attended, a row a layer a planned step,
        # by label (tpu:attn_positions_total{kind}); step-thread-only writer.
        self._attn_kinds = self._decide_attn_kinds()
        self.attn_positions = {"full": 0, "window": 0}
        # Last _can_window decline reason, stamped on the flight record
        # of the K=1 dispatch that replaced the declined window (step-
        # thread-only, overwritten every _can_window call).
        self._last_window_decline: Optional[str] = None
        # Host-side mirror of the device-resident window block tables
        # (how many columns of each row are populated), for the chained
        # windows' delta scatter.
        self._win_table_lens: List[int] = []
        self._step_time_accum = 0.0
        # (end_time, duration) of recent steps; duty_cycle = busy fraction
        # of the trailing window (the HPA/dashboard signal, vocabulary.py).
        self._busy_window: List[tuple] = []
        self._busy_window_s = 10.0

        # -- async one-step-lookahead decode pipeline ----------------------
        # dispatch() launches decode N+1 with tokens chained from step N's
        # still-in-flight device sample; collect() reads N back only when
        # N+1 is already enqueued.  Host-state sampling features drop a
        # batch to the classic synchronous path per step (same fallback
        # rule as the multi-step scan).
        self._pipeline_enabled = config.scheduler.pipeline_enabled
        self._pending: Deque[_PendingStep] = deque()
        # Device-resident decode batch state, valid for the most recently
        # dispatched pipelined step: block tables and sampling-parameter
        # arrays stay on device between steps, so steady-state dispatch
        # sends ONE packed [4, S] delta instead of eight per-array H2D
        # transfers.
        self._pipe_tables = None
        self._pipe_sampling = None  # (temps, top_ps, top_ks, min_ps, seeds)
        self._pipe_sample_sorts = False  # needs_sort of those, on the host
        self._pipe_adapter = None
        # {"state_slots": the rows' live slots} under a state pool, else {}.
        self._pipe_state_kwargs: Dict = {}
        self._pipe_table_lens: List[int] = []
        # decode_host_gap_ms: host time between one decode step retiring
        # and the next decode launch while the device had nothing queued —
        # the serialization the pipeline removes (≈0 when pipelining).
        self._gap_total_s = 0.0
        self._gap_steps = 0
        self._last_decode_end: Optional[float] = None

        self._pipe_unpack_fn = self._jit(
            "pipe_unpack_fn", step_programs.pipe_unpack
        )
        self._pipe_advance_fn = self._jit(
            "pipe_advance_fn",
            step_programs.pipe_advance(config.cache.block_size),
        )
        # A window rebuilt from host state gets its per-row scalars the same
        # way: one packed [N, S] int32 array (the rows this configuration
        # has), unpacked on the device into the shardings the window
        # program's inputs have always had.
        # tpu:step_build_transfers_total / tpu:step_unchained_dispatch_total:
        # calls of _stage (the one host -> device transfer of a dispatch
        # built from host state) and such dispatches: prefills and rebuilt
        # windows.  Step-thread-only writers.
        self.build_transfers = 0
        self.unchained_dispatches = 0
        # tpu:step_dispatch_behind_total{kind} /
        # tpu:step_dispatch_behind_declined_total{reason}: of those
        # dispatches, the ones launched while another program was in flight
        # (_dispatch_behind), and the admissions that met a program in
        # flight and took the synchronous path all the same, by why.
        self.dispatch_behind: Dict[str, int] = {"prefill": 0, "window": 0}
        self.dispatch_behind_declined: Dict[str, int] = {}
        self._win_rows = step_programs.WIN_ROWS
        if self.lora_registry is not None:
            self._win_rows += ("adapter",)
        if self.state_pool is not None:
            self._win_rows += ("state_slots",)
        if self.draft_block_pool is not None:
            self._win_rows += ("draft_pos",)
        self._win_row_at = {
            name: i for i, name in enumerate(self._win_rows)
        }
        self._win_unpack_fn = None
        if self._window_steps > 1:
            batch = self._sharding(shardings_lib.decode_batch_spec())
            out = {name: batch for name in self._win_rows}
            out["counts"] = out["seen"] = self._sharding(P(AXES.DP, None))
            self._win_unpack_fn = self._jit(
                "win_unpack_fn", step_programs.win_unpack(self._win_rows),
                out_shardings=out,
            )
            # win_unpack_fn's token argument where no row's first token is
            # on the device: made once, so that a rebuild sends nothing more.
            self._no_first_token = jax.device_put(
                np.zeros((1,), np.int32), self._sharding(P())
            )

    def _jit(self, name: str, fn, **jit_kwargs):
        """The ONE place a step function gets its name: jitted under
        ``name`` (the profiler's ``XLA Modules`` line then reads
        ``jit_<name>``, where a bare ``partial`` reads ``_unknown``) and
        wrapped in the compile tracker's cache-size probe under the same
        name, so XLA compiles are counted and timed per executable shape
        key (tpu:compile_seconds_total{executable}, GET /debug/compiles)
        and a flight record's ``programs`` name what the trace shows.
        With tracing off ``wrap`` is the identity: bare jit callables."""
        named = partial(fn)
        named.__name__ = named.__qualname__ = name
        return self.obs.compile_tracker.wrap(
            name, jax.jit(named, **jit_kwargs)
        )

    # -- sizing ------------------------------------------------------------

    def _kv_bytes(self, num_blocks: int) -> int:
        cfg = self.config.model
        if _own_cache(cfg):
            # A module that makes its own cache says what a position costs.
            return (
                num_blocks * self.config.cache.block_size
                * self.model.cache_bytes_per_token(cfg)
            )
        if self.config.cache.kv_cache_dtype == "int8":
            # int8 data + one fp32 scale per (token, kv head): bytes per
            # token roughly halve vs bf16, so _decide_num_blocks fits
            # roughly 2x the blocks in the same HBM budget.
            per_token = 2 * cfg.num_kv_heads * (cfg.head_dim * 1 + 4)
        else:
            per_token = (
                2 * cfg.num_kv_heads * cfg.head_dim * _dtype_size(cfg.dtype)
            )
        return num_blocks * self.config.cache.block_size * per_token * cfg.num_layers

    def _state_bytes(self) -> int:
        """Bytes of the state pool's slots on the device (0 without one)."""
        if self.state_pool is None:
            return 0
        return self.state_pool.num_slots * self.model.state_bytes_per_slot(
            self.config.model)

    def _state_stats(self) -> Dict[str, int]:
        """tpu:state_*: the state pool's book, zeros without one."""
        book = ("slots_in_use", "snapshots_taken", "resumes", "resume_misses",
                "recomputed_tokens")
        return {"state_" + name: getattr(self.state_pool, name, 0)
                for name in book}

    def device_report(self) -> Dict:
        """The devices this process sees, as JAX reports them (logged at
        boot, served under "device" in GET /debug/compiles)."""
        first = jax.devices()[0]
        memory = []
        for device in self.mesh.local_devices:
            stats = device.memory_stats() or {}
            memory.append({
                "id": device.id,
                "bytes_in_use": stats.get("bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit"),
            })
        return {
            "platform": first.platform,
            "kind": first.device_kind,
            "count": len(jax.devices()),
            "mesh": {axis: int(n) for axis, n in self.mesh.shape.items()},
            "memory": memory,
        }

    def _report_device_and_kernels(self) -> None:
        """Say at boot which device is held and which attention path each
        step takes there; refuse what cannot run on it."""
        cfg, par = self.config.model, self.config.parallel
        report = self.device_report()
        logger.info(
            "Device: platform=%s kind=%s count=%d mesh=%s",
            report["platform"], report["kind"], report["count"],
            report["mesh"],
        )
        if attn_ops.pallas_disabled():
            logger.warning(
                "PSTPU_DISABLE_PALLAS is set: both Pallas attention kernels "
                "are switched off, every step takes the XLA gather/dense path"
            )
        if _own_cache(cfg):
            self._refuse_what_the_module_lacks()
            logger.info(
                "Attention: decode=%s prefill=%s (%s)",
                *self.model.attention_paths(cfg), self.model.__name__,
            )
            if hasattr(self.model, "residual_path"):
                residual = self.model.residual_path(cfg)
                if residual:
                    logger.info(
                        "Residual: streams=%d sinkhorn=%d (%s)", *residual)
            if hasattr(self.model, "layer_form"):
                logger.info("Layer: %s", self.model.layer_form(cfg))
            return
        decode_kernel = attn_ops.use_pallas_decode(
            cfg.num_kv_heads // par.tensor_parallel, cfg.head_dim
        )
        prefill_kernel = self.mesh.size == 1 and attn_ops.use_pallas_prefill(
            cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            self.config.scheduler.prefill_buckets[0],
        )
        logger.info(
            "Attention: decode=%s prefill=%s",
            "pallas" if decode_kernel else "xla-gather",
            "pallas-flash" if prefill_kernel else "xla-dense",
        )
        if (
            report["platform"] == "tpu"
            and self.config.cache.kv_cache_dtype == "int8"
        ):
            # Mosaic refuses the int8-KV decode kernel: the [N, bs, K]
            # fp32 scale planes have K (8, or 2 under tp=4) as their minor
            # dimension, which is neither a 128-lane DMA slice nor dense
            # in HBM (ROADMAP S10).  Refused here rather than at the first
            # request or by quietly taking the gather path.
            raise ValueError(
                "--kv-cache-dtype int8 cannot run on a TPU yet: the int8-KV "
                "decode kernel does not compile for it (scale-plane layout, "
                "ROADMAP S10); serve with the default bf16 KV cache"
            )

    def _refuse_what_the_module_lacks(self) -> None:
        """A module that keeps a cache of its own offers ``prefill`` and
        ``decode`` over it and nothing else unless it says so.  Whatever
        the configuration asks of it beyond that is refused here, at boot
        and by name: the tiers that unpack a (K, V) pair a layer, a mesh
        it has no sharding rules for, weight or cache formats it does not
        have, step functions it does not bring.  No silent fallback."""
        config, module = self.config, self.model.__name__
        asked = {
            "tensor/data/sequence parallelism (a mesh of more than one "
            "device)": self.mesh.size > 1,
            "--quantization (int8 weights)":
                config.model.quantization is not None,
            "--kv-cache-dtype int8": config.cache.kv_cache_dtype == "int8",
            "LoRA adapters (--max-loras)": config.lora.enabled,
            "host KV offload (--host-offload-gb)":
                config.cache.host_offload_gb > 0,
            "the remote KV store, prefix prefetch and disaggregated "
            "prefill (--remote-kv-url, --disagg-role)":
                bool(config.cache.remote_kv_url)
                or config.cache.disagg_role is not None,
            "speculative decoding (--speculative-ngram, "
            "--speculative-model)":
                bool(config.scheduler.speculative_ngram)
                or config.scheduler.speculative_model is not None,
            "mixed prefill+decode steps (--mixed-batch; the module has no "
            "mixed_step)":
                config.scheduler.mixed_batch is True
                and not hasattr(self.model, "mixed_step"),
        }
        refused = [what for what, on in asked.items() if on]
        if refused:
            raise ValueError(
                f"{module} keeps a cache of its own (one array an attention) "
                f"and cannot serve with: {'; '.join(refused)}"
            )

    def _decide_attn_kinds(self):
        cfg = self.config.model
        if cfg.attention_specs:
            return [(kind, spec.window, cfg.layers_of(kind),
                     kind not in PAGED_KINDS)
                    for kind, spec in cfg.attention_specs.items()]
        # A cache array an attention: a layer may hold several.
        keyed = (sum(cfg.layer_kind(i) in PAGED_KINDS
                     for i in range(cfg.num_layers))
                 if cfg.layer_kinds else cfg.cache_layers)
        window = cfg.sliding_window
        return [("full" if window is None else "window", window, keyed,
                 False)]

    def _decide_kv_group_blocks(self) -> int:
        """``blocks_per_descriptor`` of the page the paged decode kernel is
        given for this model (its K / tp heads a shard), where that kernel
        serves; 1 on any other path."""
        from production_stack_tpu.engine.ops.pallas.paged_attention import (
            blocks_per_descriptor,
        )

        cfg, cache = self.config.model, self.config.cache
        heads = cfg.num_kv_heads // self.config.parallel.tensor_parallel
        if not attn_ops.use_pallas_decode(heads, cfg.head_dim):
            return 1
        return blocks_per_descriptor(
            cache.block_size * heads * cfg.head_dim * _dtype_size(cfg.dtype),
            quantized=cache.kv_cache_dtype == "int8",
        )

    def _decide_num_blocks(self) -> int:
        cache = self.config.cache
        if cache.num_blocks is not None:
            return cache.num_blocks
        report = self.device_report()
        if report["platform"] == "cpu":
            # The CPU reports no memory limit: enough for tests and smoke
            # serving.
            return 512
        # An accelerator that reports no limit is an error, not a default:
        # a silent 512-block pool would serve 8k tokens from a 16 GB chip.
        # hbm_utilization bounds weights + KV together: what is left of the
        # device is the step programs' workspace (about 1 GB for a
        # 2048-token prefill of a 7B model).
        budget = []
        for mem in report["memory"]:
            if not mem["bytes_limit"]:
                raise RuntimeError(
                    f"device {mem['id']} reports no bytes_limit in "
                    "memory_stats(); cannot size the KV pool — pass "
                    "--num-blocks"
                )
            budget.append(
                mem["bytes_limit"] * cache.hbm_utilization
                - (mem["bytes_in_use"] or 0) - self._state_bytes()
            )
        # KV heads are sharded over tp, so each device holds 1/tp of a
        # block; size the pool against the fullest device.
        per_block = self._kv_bytes(1) / self.config.parallel.tensor_parallel
        blocks = max(int(min(budget) // per_block), 16)
        return blocks

    def _allocate_kv(self, num_blocks: int):
        cfg = self.config.model
        bs = self.config.cache.block_size
        if _own_cache(cfg):
            # Whatever the module keeps a layer (a latent cache is one
            # array, no K and V); the step programs thread it as a tree.
            slots = {}
            if self.state_pool is not None:
                slots["state_slots"] = self.state_pool.num_slots
            return self.model.init_cache(
                cfg, num_blocks, bs, NamedSharding(self.mesh, P()), **slots
            )
        shape = (num_blocks, bs, cfg.num_kv_heads, cfg.head_dim)
        dtype = jnp.dtype(cfg.dtype)
        # Allocate directly sharded (jit with out_shardings): materializing
        # the full unsharded layer on one device first would OOM at high tp.
        layer_shardings = shardings_lib.kv_cache_shardings(cfg, self.mesh)
        if self.config.cache.kv_cache_dtype == "int8":
            # (data int8, scale fp32 [N, bs, K]) per side — kv/quant.py.
            scale_sharding = shardings_lib.kv_scale_sharding(self.mesh)
            zeros = jax.jit(
                lambda: (
                    jnp.zeros(shape, jnp.int8),
                    jnp.zeros(shape[:3], jnp.float32),
                ),
                out_shardings=(layer_shardings[0][0], scale_sharding),
            )
            return [(zeros(), zeros()) for _ in range(cfg.num_layers)]
        zeros = jax.jit(
            lambda: jnp.zeros(shape, dtype),
            out_shardings=layer_shardings[0][0],
        )
        return [(zeros(), zeros()) for _ in range(cfg.num_layers)]

    def _allocate_draft_kv(self, num_blocks: int):
        """Draft model's paged KV (model drafter): same block size as
        the target pool (one slot-targeting code path), the DRAFT
        architecture's head shapes, always dense dtype (the pool is tiny
        — see the boot-time sizing comment)."""
        cfg = self.draft_cfg
        bs = self.config.cache.block_size
        shape = (num_blocks, bs, cfg.num_kv_heads, cfg.head_dim)
        layer_shardings = shardings_lib.kv_cache_shardings(cfg, self.mesh)
        zeros = jax.jit(
            lambda: jnp.zeros(shape, jnp.dtype(cfg.dtype)),
            out_shardings=layer_shardings[0][0],
        )
        return [(zeros(), zeros()) for _ in range(cfg.num_layers)]

    # Where each array of a dispatch built from host state lives (_stage):
    # what _put gave it when each went alone.
    _BUILD_SPECS = {
        "packed": P(None, AXES.DP),
        **{k: P(AXES.DP, None) for k in (
            "tables", "stop_ids", "hist", "draft_tables",
            "out_tokens", "ctx_tokens",
        )},
        "chunk": P(),
        "first_row": P(),
    }

    def _sharding(self, spec: P) -> NamedSharding:
        got = self._shardings.get(spec)
        if got is None:
            got = self._shardings[spec] = NamedSharding(self.mesh, spec)
        return got

    def _stage(self, arrays: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        """Everything a dispatch built from host state sends to the device,
        in ONE transfer: the host arrays as they are (no hop through the
        default device, no eager op), each to its sharding.  Counted
        (tpu:step_build_transfers_total): a prefill or a rebuilt window
        that calls this more than twice has grown a transfer."""
        self.build_transfers += 1
        return jax.device_put(
            arrays, {k: self._sharding(self._BUILD_SPECS[k]) for k in arrays}
        )

    def _put(self, arr: np.ndarray, spec: P) -> jax.Array:
        """Host array -> device array with an explicit mesh sharding."""
        return jax.device_put(jnp.asarray(arr), self._sharding(spec))

    # -- request lifecycle -------------------------------------------------

    def add_request(
        self,
        request_id: str,
        prompt: Optional[str] = None,
        prompt_token_ids: Optional[List[int]] = None,
        sampling_params: Optional[SamplingParams] = None,
        adapter: Optional[str] = None,
        arrival_time: Optional[float] = None,
        submitted_time: Optional[float] = None,
        prefix_chain: Optional[List[bytes]] = None,
    ) -> None:
        """``arrival_time`` / ``submitted_time``: the API server's stamps
        of where the request arrived and where it was handed to the step
        thread (obs/engine.py); without them the arrival is now.
        ``prefix_chain``: the prompt's chain as ``prompt_prefix_chain``
        made it, off this thread; without it (a direct caller, a lockstep
        follower, an adapter's namespace) the chain is hashed here at
        first need."""
        if prompt_token_ids is None:
            if prompt is None:
                raise ValueError("need prompt or prompt_token_ids")
            prompt_token_ids = self.tokenizer.encode(prompt)
        if not prompt_token_ids:
            prompt_token_ids = [self.tokenizer.bos_token_id or 0]
        params_obj = sampling_params or SamplingParams()
        guide = None
        if params_obj.response_format == "json_object":
            from production_stack_tpu.engine.guided import JsonGuide

            guide = JsonGuide(require_object=True)
            # Completion forces EOS; ignore_eos would append eos text
            # forever.  Enforced here (not only at the API boundary) so
            # direct engine users get the same behavior.
            params_obj.ignore_eos = False
        elif (
            isinstance(params_obj.response_format, dict)
            and params_obj.response_format.get("type") == "json_schema"
        ):
            from production_stack_tpu.engine.guided_schema import SchemaGuide

            # Raises SchemaCompileError (a ValueError) for schemas
            # outside the supported subset -> 400 at the API boundary.
            guide = SchemaGuide(params_obj.response_format.get("schema") or {})
            params_obj.ignore_eos = False
        elif params_obj.response_format not in (None, "text"):
            raise ValueError(
                f"Unsupported response_format {params_obj.response_format!r}"
            )
        adapter_idx = 0
        cache_ns = 0
        if adapter:
            if self.lora_registry is None:
                raise ValueError(
                    "LoRA adapter requested but the engine was started with "
                    "max_loras=0"
                )
            adapter_idx = self.lora_registry.slot_of(adapter)  # raises if unknown
            cache_ns = self.lora_registry.namespace_of(adapter)
        seq = Sequence(
            seq_id=request_id,
            prompt_token_ids=list(prompt_token_ids),
            sampling_params=params_obj,
            adapter=adapter,
            adapter_idx=adapter_idx,
            cache_ns=cache_ns,
            echo_prompt_len=len(prompt_token_ids),
            guide=guide,
        )
        if prefix_chain is not None and not cache_ns:
            seq.prefix_chain = prefix_chain
        if arrival_time is not None:
            # The stamp the Sequence took just now is its admission; the
            # arrival is the handler's, before the wait for this thread.
            seq.admitted_time = seq.arrival_time
            seq.submitted_time = submitted_time
            seq.arrival_time = arrival_time
        self._seqs[request_id] = seq
        self.scheduler.add_seq(seq)
        self.total_prompt_tokens += len(prompt_token_ids)
        # Admission-time prefetch: start resolving the local prefix-cache
        # miss tail against the remote store NOW, so by the time the
        # scheduler considers this prompt the blocks are (often) already
        # in host staging — and never fetched inside schedule().
        if self.kv_prefetch is not None and self._imports:
            self._submit_prefix_prefetch(seq)

    def prompt_prefix_chain(
        self, prompt_token_ids: List[int], adapter: Optional[str] = None
    ) -> Optional[List[bytes]]:
        """The chain of a prompt's full blocks for ``add_request(...,
        prefix_chain=)``, hashed by the caller's thread — the API server's
        handler, while the pass in flight keeps the device busy — so that
        the step thread plans the admission without hashing.  None where
        the namespace is the step thread's to resolve (an adapter)."""
        if adapter or not self.block_pool.enable_prefix_caching:
            return None
        chain: List[bytes] = []
        bs = self.block_pool.block_size
        self.prefix_chain_handler_blocks += extend_prefix_chain(
            chain, prompt_token_ids, bs, len(prompt_token_ids) // bs
        )
        return chain

    def abort_request(self, request_id: str) -> None:
        seq = self.scheduler.abort_seq(request_id)
        if seq is not None:
            seq.status = SequenceStatus.FINISHED
            seq.finish_reason = FinishReason.ABORT
        if self.kv_prefetch is not None:
            self.kv_prefetch.cancel(request_id)
        if self._offload_stager is not None:
            # Tombstone BEFORE offload.discard: a snapshot still staging
            # must never be inserted (or remote-PUT) after the DEL.
            self._offload_stager.discard(request_id)
        self.offload.discard(request_id)
        self._seqs.pop(request_id, None)
        self.obs.on_abort(request_id)

    def has_unfinished(self) -> bool:
        return self.scheduler.has_unfinished()

    def scan_expired_deadlines(self, now: float) -> List[str]:
        """Ids of WAITING/PREEMPTED sequences whose client deadline has
        passed.  Pure scan (no aborts): the step loop folds the result
        into its abort batch so lockstep followers replay the identical
        aborts instead of evaluating wall clocks that diverge per
        replica.  Running sequences are exempt — they are streaming
        tokens, and cutting them is the client's call."""
        expired = []
        for queue in (self.scheduler.waiting, self.scheduler.preempted):
            for seq in queue:
                d = seq.sampling_params.deadline
                if d is not None and now > d:
                    expired.append(seq.seq_id)
        return expired

    # -- stepping ----------------------------------------------------------

    def step(self) -> List[StepOutput]:
        """One engine step: top up the device pipeline, then collect the
        oldest in-flight step.  With pipelining on, the collected outputs
        belong to a step whose successor is already running on the
        device; per-sequence greedy token streams are identical to
        classic synchronous stepping."""
        self.dispatch()
        return self.collect()

    def has_pending(self) -> bool:
        """A dispatched step is awaiting collection."""
        return bool(self._pending)

    # stackcheck: root=step-thread
    def dispatch(self) -> bool:
        """Launch device work without reading anything back, filling the
        pipeline to its depth (2 with lookahead, 1 otherwise).  Returns
        True when at least one step was dispatched."""
        depth = 2 if self._pipeline_enabled else 1
        launched = False
        while len(self._pending) < depth:
            ok = (
                self._dispatch_lookahead()
                if self._pending
                else self._dispatch_front()
            )
            if not ok:
                break
            launched = True
        return launched

    # stackcheck: root=step-thread
    def collect(self) -> List[StepOutput]:
        """Block on the oldest dispatched step and finalize it: append
        sampled tokens, run finish checks, and roll back rows whose
        sequence finished while the step was in flight (their token is a
        discarded overrun — vLLM multi-step semantics)."""
        if not self._pending:
            return []
        t0 = time.time()
        p = self._pending.popleft()
        if p.outputs is not None:
            outputs = p.outputs
        elif p.chunk is not None:
            outputs = self._collect_prefill(p)
        elif p.steps is not None:
            outputs = self._collect_window(p)
        else:
            with self.obs.phase("collect", p.rec):
                arr = np.asarray(p.sampled)  # the ONE device sync point
            with self.obs.phase("sample", p.rec):
                live = [
                    (i, s) for i, s in enumerate(p.seqs) if not s.is_finished
                ]
                outputs = self._append_and_check(
                    [s for _, s in live],
                    [int(arr[i]) for i, _ in live],
                    first_token=False,
                )
            if p.rec is not None:
                # Sample-side jits (penalties/argmax/logprobs) ran inside
                # _append_and_check: drain any compiles onto this record,
                # then complete it.  Rows whose sequence finished while
                # the step flew sampled a discarded overrun token.
                self._note_compiles(p.rec, [s.seq_id for s in p.seqs])
                self.obs.recorder.on_collect(
                    p.rec, host_s=p.host_s,
                    tokens_emitted=len(p.seqs),
                    tokens_delivered=len(live),
                    tokens_wasted=len(p.seqs) - len(live),
                )
        if p.outputs is None:
            # Drop in-flight successors whose every row has now finished:
            # pure overrun steps produce no outputs and must not wedge
            # the pipeline when the engine drains.  (For windows this is
            # the host side of the all-finished predicate: the device
            # carry's rows are all frozen no-ops, so the successor is
            # discarded without a second sync.)  A MIXED window is never
            # droppable this way: its chunk head is not a decode row, so
            # "every row finished" says nothing about the chunk schedule
            # — dropping it would skip the final chunk's first-token
            # finalization (and the chunk/waste accounting) for a prompt
            # whose KV the device already wrote.  A prefill launched
            # behind this step whose prompt was aborted since has no one
            # to read its token for.
            while self._pending and self._nothing_to_deliver(self._pending[0]):
                d = self._pending.popleft()
                if d.rec is not None:
                    # Complete the dropped overrun's record so every
                    # dispatched window appears exactly once: a plain
                    # window's rows are all frozen (the device emitted
                    # nothing), a single step sampled one discarded
                    # token per row, an aborted prompt's prefill none.
                    n = 0 if d.steps is not None or d.chunk else len(d.seqs)
                    self.obs.recorder.on_collect(
                        d.rec, host_s=d.host_s,
                        tokens_emitted=n, tokens_wasted=n,
                    )
        now = time.time()
        self._last_decode_end = now if p.is_decode else None
        busy = (now - t0) + p.host_s
        self._step_time_accum += busy
        self._busy_window.append((now, busy))
        cutoff = now - self._busy_window_s
        # stackcheck: allow=SC201 reason=duty-cycle window trim; feeds the tpu:duty_cycle metric only, never a plan (replicas may report different utilization, they may not schedule differently)
        self._busy_window = [(t, d) for (t, d) in self._busy_window if t > cutoff]
        return outputs

    @staticmethod
    def _nothing_to_deliver(p: _PendingStep) -> bool:
        """An in-flight step collect() may drop unread: every sequence it
        would deliver a token to has finished since it was launched."""
        if p.chunk is not None:
            return p.chunk.seq.is_finished
        return (
            p.sampled is not None
            and p.chunk_sched is None
            and all(s.is_finished for s in p.seqs)
        )

    def _dispatch_front(self) -> bool:
        """Dispatch with nothing in flight: full scheduler knowledge
        (admission, preemption, partial-prefill rollback) — the only
        place synchronous plans run."""
        with self.obs.phase("schedule"):
            # Land completed remote-prefix prefetches in the prefix cache
            # BEFORE planning, so this very schedule()'s match_prefix can
            # serve them (copy-in is an async device dispatch, not a wait).
            self._drain_prefetched()
            t0 = time.time()
            plan = self.scheduler.schedule()
        if plan.is_empty:
            # Nothing schedulable.  If that is because the async transfer
            # plane is mid-flight (a restore page-in or offload stage the
            # scheduler answered "retry" for), yield a tick so a tight
            # caller loop doesn't busy-spin through its step budget
            # faster than the worker threads can land the bytes.  The
            # device is idle here — this is backoff, not a data wait.
            if self._transfer_inflight():
                with self.obs.phase("wait"):
                    # stackcheck: allow=SC101 reason=1ms idle backoff while async transfers land; the device is idle here by definition (nothing scheduled) so this is pacing, not a data wait
                    time.sleep(0.001)
            return False
        if plan.window_fallback:
            # A waiting head forced K=1 stepping (the mixed-window path
            # could not serve it): the forfeited amortization is
            # visible, like every other window fallback reason.
            self.multistep_fallback[plan.window_fallback] = (
                self.multistep_fallback.get(plan.window_fallback, 0) + 1
            )
        if plan.decode is None:
            cp = plan.prefill_chunk
            if (
                self._pipeline_enabled
                and self._window_fn is not None
                and self._prefill_behind_decline(cp.seq) is None
            ):
                # Nothing to launch it behind, but nothing to read back
                # for either: the window that follows (or the next chunk)
                # goes behind it (_dispatch_behind).
                # stackcheck: allow=SC201 reason=t0 stamps the flight record and host_s (stats fields); no plan state reads it
                self._pending.append(self._dispatch_prefill_async(
                    cp, behind=False, t0=t0, fallback=plan.window_fallback,
                ))
                return True
            # The synchronous paths open their record before the work, so
            # that its spans, programs and launch stamps land on it.
            rec = self._open_record(
                "prefill", chunks=(cp,), fallback=plan.window_fallback, cover=cp.cover,
            )
            self._stamp_record(rec, t0, gap=False)
            outputs = self._run_prefill(cp, rec)
            self._step_counter += 1
            # stackcheck: allow=SC201 reason=host_s is a stats field (host-gap metric); no plan state reads it
            host_s = time.time() - t0
            if rec is not None:
                self._note_compiles(rec)
                self.obs.recorder.on_collect(
                    rec, host_s=host_s,
                    tokens_emitted=len(outputs),
                    tokens_delivered=len(outputs),
                    chunk_tokens_delivered=cp.num_new_tokens,
                )
            # stackcheck: allow=SC201 reason=host_s is a stats field (host-gap metric); no plan state reads it
            self._pending.append(_PendingStep(outputs=outputs, host_s=host_s))
            return True
        if plan.chunk_schedule is not None:
            # Mixed K-step window: the head prompt's chunks ride the
            # decode scan (chunk cursor carried in-graph); the final
            # chunk's first token is sampled at collect through the K=1
            # finalize path.
            self._pending.append(
                self._dispatch_mixed_window(plan, chain_from=None)
            )
            return True
        if plan.prefill_chunk is not None:
            # Fused decode+prefill-chunk step: synchronous (the chunk's
            # admission/finalization needs collected state), so the
            # lookahead pipeline pauses for the step and resumes on the
            # next pure-decode plan.
            cp = plan.prefill_chunk
            rec = self._open_record(
                "mixed", seqs=plan.decode.seqs, chunks=(cp,),
                fallback=plan.window_fallback,
            )
            self._stamp_record(rec, t0)
            outputs = self._run_mixed(plan, rec)
            self._step_counter += 1
            # stackcheck: allow=SC201 reason=host_s is a stats field (host-gap metric); no plan state reads it
            host_s = time.time() - t0
            if rec is not None:
                self._note_compiles(rec)
                self.obs.recorder.on_collect(
                    rec, host_s=host_s,
                    tokens_emitted=len(outputs),
                    tokens_delivered=len(outputs),
                    chunk_tokens_delivered=cp.num_new_tokens,
                )
            # stackcheck: allow=SC201 reason=host_s is a stats field (host-gap metric); no plan state reads it
            self._pending.append(_PendingStep(
                outputs=outputs, is_decode=True, host_s=host_s,
            ))
            return True
        seqs = plan.decode.seqs
        # A window of one step is still a window: the same program, the
        # same carry for the next one to chain from.
        if plan.window_cut is not None and self._can_window(seqs):
            self._pending.append(self._dispatch_window(plan, chain_from=None))
            return True
        # A window plan that fell out of the window path carries the decline
        # reason onto the replacing K=1 dispatch's flight record.
        decline = plan.window_fallback or (
            self._last_window_decline if plan.window_cut is not None else None
        )
        if self._can_pipeline(seqs):
            p = self._dispatch_decode_async(seqs, False)
            if p.rec is not None and decline:
                p.rec.fallback = decline
            self._pending.append(p)
        else:
            rec = self._open_record("decode", seqs=seqs, fallback=decline)
            self._stamp_record(rec, t0)
            outputs = self._run_decode(plan.decode, rec)
            self._step_counter += 1
            # stackcheck: allow=SC201 reason=host_s is a stats field (host-gap metric); no plan state reads it
            host_s = time.time() - t0
            if rec is not None:
                self._note_compiles(rec)
                self.obs.recorder.on_collect(
                    rec, host_s=host_s,
                    tokens_emitted=len(seqs),
                    tokens_delivered=len(outputs),
                )
            # stackcheck: allow=SC201 reason=host_s is a stats field (host-gap metric); no plan state reads it
            self._pending.append(_PendingStep(
                outputs=outputs, is_decode=True, host_s=host_s,
            ))
        return True

    def _dispatch_lookahead(self) -> bool:
        """Provisionally dispatch decode N+1 while N is still in flight.
        The scheduler plans under the optimistic no-finish assumption
        (rolling back at collect when wrong); inputs chain from N's
        device-resident sample — the [S] in-flight token for single
        steps, the whole window carry (tokens/positions/done/penalty
        state) for K-step windows — so no host sync separates them."""
        if not self._pipeline_enabled:
            return False
        prev = self._pending[-1]
        if prev.chunk is not None:
            return self._dispatch_behind(prev)
        if prev.sampled is None:
            return False  # only pipelined decode steps chain
        if prev.win_state is not None:
            with self.obs.phase("schedule"):
                plan = self.scheduler.schedule_provisional_window(
                    prev.seqs, prev.steps
                )
            if plan is None:
                # No window chains from this one.  Where that is because a
                # prompt waits, its prefill does not wait for the read-back.
                return bool(
                    self.scheduler.num_waiting
                ) and self._dispatch_behind(prev)
            if plan.chunk_schedule is not None:
                # A waiting head's chunks chain onto the in-flight
                # carry as a mixed window — the pipeline never drains
                # through the admission.
                self._pending.append(
                    self._dispatch_mixed_window(plan, chain_from=prev)
                )
                return True
            self._pending.append(self._dispatch_window(plan, chain_from=prev))
            return True
        if not self._can_pipeline(prev.seqs):
            return False
        with self.obs.phase("schedule"):
            plan = self.scheduler.schedule_provisional(prev.seqs)
        if plan is None:
            return False
        self._pending.append(
            self._dispatch_decode_async(plan.seqs, True, prev.sampled)
        )
        return True

    def _dispatch_behind(self, prev: _PendingStep) -> bool:
        """An admission's dispatches, launched behind the program in flight
        instead of after its read-back: while a prompt waits, its next
        prefill chunk (behind the window, or behind the prefill before it);
        once none does and ``prev`` is that prefill, the decode window that
        follows, rebuilt from host state with the row the prefill admits
        taking its first token on the device.  The launches are those of
        the synchronous order — window, prefill, its sampler, window — with
        the same arrays; what moves is when the host does its part.  Where
        the plan or the rows need what only collected state gives, declines
        (counted by reason) and _dispatch_front serves the admission at the
        boundary, as ever."""
        sched = self.scheduler
        if sched.num_waiting:
            t0 = time.time()
            reason = self._prefill_behind_decline(sched._admission_queue()[0])
            plan = None
            if reason is None:
                with self.obs.phase("schedule"):
                    plan, reason = sched.schedule_prefill_behind()
            if plan is not None:
                # stackcheck: allow=SC201 reason=t0 stamps the flight record and host_s (stats fields); no plan state reads it
                step = self._dispatch_prefill_async(plan, behind=True, t0=t0)
                self._pending.append(step)
                return True
        else:
            plan, reason = self._plan_window_behind(prev)
            if plan is not None:
                self._pending.append(self._dispatch_window(plan, behind=prev))
                return True
        if reason is not None:
            self.dispatch_behind_declined[reason] = (
                self.dispatch_behind_declined.get(reason, 0) + 1
            )
        return False

    def _prefill_behind_decline(self, seq: Sequence) -> Optional[str]:
        """Why ``seq``'s prefill has to be read back before anything follows
        it, from what the engine and the request show; None: it may be
        launched and left on the device (_dispatch_prefill_async)."""
        if self.config.scheduler.mixed_enabled:
            # With rows decoding, schedule() hands an admission to the mixed
            # planners, which plan at the boundary: a window launched early
            # behind this prefill would make the next prompt wait for it.
            return "mixed_batch"
        if self._spec_window_fn is not None:
            return "speculative"  # the window after it carries `hist`
        if self._exports:
            return "prefix_export"  # due at finalize, from collected state
        sp = seq.sampling_params
        if sp.echo and sp.logprobs:
            return "prompt_logprobs"
        if sp.max_tokens == 0:
            return "max_tokens_0"
        if self._host_state_flags(seq)[0]:
            return "host_state"
        return None

    def _plan_window_behind(self, prev: _PendingStep):
        """(the plan of the window to launch behind the prefill ``prev``,
        None), or (None, why it waits for that prefill's read-back; no
        reason where there is no window to launch)."""
        if not prev.chunk.is_final:
            return None, None
        seq = prev.chunk.seq
        first = seq if seq in self.scheduler.running else None
        if first is not None and self._host_state_flags(first)[1]:
            # Its occurrence state counts the token still on the device.
            return None, "penalties"
        if self._batch_uses_host_state(self.scheduler.running):
            return None, "host_state"
        with self.obs.phase("schedule"):
            return self.scheduler.schedule_window_behind(first)

    def _dispatch_prefill_async(
        self, plan: PrefillPlan, behind: bool, t0: float,
        fallback: Optional[str] = None,
    ) -> _PendingStep:
        """A dedicated prefill chunk launched and, after a final chunk, the
        sampler of the prompt's first token, both left on the device for
        collect() (_collect_prefill).  ``behind``: a program was in flight
        (counted); else the device was empty (_dispatch_front) and only
        what follows gains."""
        rec = self._open_record(
            "prefill", chunks=(plan,), cover=plan.cover, behind=behind,
            fallback=fallback,
        )
        self._stamp_record(rec, t0, gap=False)
        logits, _ = self._launch_prefill(plan, rec)
        first_token = None
        if plan.is_final:
            with self.obs.phase("launch", rec):
                _, first_token = self._sample_launch(
                    logits[None, :], [plan.seq], None
                )
        self._step_counter += 1
        self._note_compiles(rec)
        self.dispatch_behind["prefill"] += behind
        # stackcheck: allow=SC201 reason=host_s is a stats field (host-gap metric); no plan state reads it
        return _PendingStep(
            chunk=plan, first_token=first_token, host_s=time.time() - t0,
            rec=rec,
        )

    def _collect_prefill(self, p: _PendingStep) -> List[StepOutput]:
        """The read-back of a prefill left on the device: a final chunk's
        first token, appended as _finalize_final_prefill appends it.
        A sequence aborted while the prefill flew takes nothing."""
        seq, rec = p.chunk.seq, p.rec
        outputs: List[StepOutput] = []
        if p.first_token is not None:
            with self.obs.phase("collect", rec, family=False):
                token = int(np.asarray(p.first_token)[0])
            if not seq.is_finished:
                with self.obs.phase("sample", rec, family=False):
                    outputs = self._append_and_check(
                        [seq], [token], first_token=True
                    )
        if rec is not None:
            self._note_compiles(rec)
            self.obs.recorder.on_collect(
                rec, host_s=p.host_s,
                tokens_emitted=len(outputs), tokens_delivered=len(outputs),
                chunk_tokens_delivered=p.chunk.num_new_tokens,
            )
        return outputs

    # Host-state verdicts are cached per-sequence at admission instead of
    # re-reading SamplingParams attribute chains in a Python loop on the
    # step thread every dispatch.  Two static verdicts (they never change
    # over a request's life) plus ONE dynamic bit — the pending
    # min_tokens floor — which _append_and_check clears exactly once at
    # the boundary crossing.
    # (window_fallback, classic_fallback, greedy) cached verdicts — the
    # taxonomy itself moved to sequence.host_state_flags so the
    # scheduler's mixed-window planner reads the SAME verdicts the
    # dispatch gates below do (it must never plan a K-step mixed window
    # the engine would have to fall back out of).
    _host_state_flags = staticmethod(seq_host_state_flags)

    def _batch_uses_host_state(self, seqs: List[Sequence]) -> bool:
        """True when any sequence needs host-visible per-token state the
        K-step window cannot reproduce on-device (logprobs, logit_bias,
        guided decoding).  The ONE fallback gate for the window fast
        path; each reason is counted in tpu:multistep_fallback_total —
        a single such request de-optimizes every co-scheduled stream,
        and that used to be invisible."""
        return any(self._host_state_flags(s)[0] for s in seqs)

    def _can_window(self, seqs: List[Sequence]) -> bool:
        """K-step windows serve everything except host-sampled features;
        a fallback is observable, never silent."""
        self._last_window_decline = None
        if self._window_fn is None:
            return False
        if not self._batch_uses_host_state(seqs):
            return True
        # One increment per DISTINCT reason per dispatch (the registered
        # unit is fallback dispatches, not offending sequences — three
        # co-scheduled logprobs requests are still ONE de-optimized
        # dispatch).
        reasons = set()
        for s in seqs:
            if self._host_state_flags(s)[0]:
                sp = s.sampling_params
                reasons.add(
                    "logprobs" if sp.logprobs
                    else "logit_bias" if sp.logit_bias
                    else "guided"
                )
        for reason in reasons:
            self.multistep_fallback[reason] = (
                self.multistep_fallback.get(reason, 0) + 1
            )
        # Remembered for the flight record of the K=1 dispatch that
        # replaces the declined window (deterministic pick when several
        # reasons coincide).
        self._last_window_decline = min(reasons) if reasons else None
        return False

    def _can_pipeline(self, seqs: List[Sequence]) -> bool:
        """Single-step pipelined decode covers the common fast path
        only: its on-device sampler has no penalty/floor path, so
        penalty batches and pending min_tokens floors ALSO drop to the
        classic synchronous path per step (K-step windows serve those
        on-device)."""
        return self._pipeline_enabled and not any(
            self._host_state_flags(s)[1] or s._min_tok_pending
            for s in seqs
        )

    def _stamp_record(self, rec, t0: float, gap: bool = True) -> None:
        """``t0``: when this dispatch began, on the host's clock.  With
        ``gap``, also the host gap it inherited from the previous window
        (device idle since the last decode retired), so a stalled
        window's timeline shows WHERE the stall was — read before the
        launch bookkeeping clears it."""
        if rec is None:
            return
        rec.dispatched_at = t0
        last = self._last_decode_end
        if gap and last is not None:
            rec.host_gap_s = max(0.0, t0 - last)

    def _open_record(
        self, kind: str, *, seqs=(), chunks=(), ahead=0,
        bucket_tokens: Optional[int] = None, **fields,
    ):
        """Flight record for the dispatch that is about to run: ``seqs``
        its decode rows, ``chunks`` the PrefillPlans riding it.  Opened
        BEFORE the work so that the work's phase spans, program launches
        and stamps land on it; None with tracing off.  ``ahead``: tokens
        still in flight on the device, one number for every row, a number
        a sequence id, or the ``_PendingStep`` this dispatch chains from (a
        lookahead dispatch's rows are that much longer than the host knows).
        ``bucket_tokens``: the chunk program's token slots where they are
        not the plans' buckets (a mixed window scans a power of two)."""
        if bucket_tokens is None:
            bucket_tokens = sum(cp.bucket_len for cp in chunks)
        # Counted with tracing off too: /metrics carries the totals.
        kv_tiles_live, kv_tiles_grid, prefix_pages = self._count_kv_tiles(
            chunks, bucket_tokens
        )
        bs = self.block_pool.block_size
        if isinstance(ahead, _PendingStep):
            budget = {
                s.seq_id: n for s, n in zip(ahead.seqs, ahead.steps)
            }
            ahead = 0
        elif isinstance(ahead, dict):
            budget, ahead = ahead, 0
        else:
            budget = {}
        # Positions the decode rows attend, a layer of each kind, whole
        # blocks (what the decode kernels must read on the first step), and
        # those of them that lie in slots of the state pool.
        ctx = np.array(
            [s.num_tokens + budget.get(s.seq_id, ahead) for s in seqs],
            np.int64)
        steps = np.arange(fields.get("k", 1))
        kv_tokens = kv_tokens_slots = 0
        for label, window, layers, in_slots in (
                self._attn_kinds if seqs else ()):
            seen = ctx if window is None else np.minimum(ctx, window)
            first_step = int((-(-seen // bs) * bs).sum())
            kv_tokens += first_step
            if in_slots:
                kv_tokens_slots += first_step
            grown = ctx[:, None] + steps
            if window is not None:
                grown = np.minimum(grown, window)
            self.attn_positions[label] += int(grown.sum()) * layers
        if not self.obs.enabled:
            return None
        first = {}
        for cp in chunks:
            first.setdefault(cp.seq.seq_id, cp.cached_len)
        new_tokens = sum(cp.num_new_tokens for cp in chunks)
        if self.state_pool is not None:
            fields["state_rows"] = len(seqs)
            if chunks:
                fields["state_resumed"] = any(cp.resumed for cp in chunks)
        return self.obs.recorder.on_dispatch(
            kind, rows=len(seqs),
            seq_ids=tuple(s.seq_id for s in seqs) + tuple(first),
            chunk_prompts=len(first), chunk_tokens_planned=new_tokens,
            kv_tokens=kv_tokens, kv_tokens_slots=kv_tokens_slots,
            new_tokens=new_tokens, bucket_tokens=bucket_tokens,
            cached_tokens=sum(first.values()),
            kv_tiles_live=kv_tiles_live, kv_tiles_grid=kv_tiles_grid,
            prefix_pages=prefix_pages, **fields,
        )

    def _count_kv_tiles(self, chunks, bucket_tokens: int):
        """(kv tiles the prefill attention kernel computes, kv tiles in its
        grid), per layer, and the pages of the prefix it fetches over all its
        layers, for the PrefillPlans of one dispatch, by the kernel's own
        liveness rule — host arithmetic, no device read; also feeds
        ``tpu:prefill_attn_tiles_total``.  The kernel is the module's own
        where it says so (``prefill_attn_tiles``: the latent prefill kernel's
        (query tile, key stage) pairs; no pages are counted for it), else the
        flash prefill kernel, whose grid is the plan's block table
        (``max_model_len`` positions of pages) and the chunk.  The tiles
        describe the kernel's grid whether or not it is the path that runs
        (under a tp mesh, and off the TPU, prefill takes the XLA path); pages
        are counted only where it runs (``_flash_prefill_serves``): the dense
        form fetches none."""
        if not chunks:
            return 0, 0, 0
        from production_stack_tpu.engine.ops.pallas.flash_prefill import (
            count_kv_tiles,
        )

        bmax, bs = max(self._bmax, 1), self.block_pool.block_size
        own_rule = getattr(self.model, "prefill_attn_tiles", None)
        live = grid = pages = slots = 0
        for cp in chunks:
            n_grid = 0
            # A layer of each kind: a kind whose keys lie in slots of the
            # state pool hands one window of them as a pool of one page.
            for label, window, layers, in_slots in self._attn_kinds:
                if own_rule is not None:
                    n_live, n = own_rule(
                        self.config.model, cp.bucket_len, bmax, bs,
                        cp.cached_len, cp.num_new_tokens)
                    n_pages = 0
                else:
                    n_live, n, n_pages = count_kv_tiles(
                        cp.bucket_len, *((1, window) if in_slots
                                         else (bmax, bs)),
                        min(cp.cached_len, window) if in_slots
                        else cp.cached_len,
                        cp.num_new_tokens, window,
                    )
                live += n_live
                n_grid += n
                if n_pages and self._flash_prefill_serves(
                        label, cp.bucket_len):
                    pages += n_pages * layers
            grid += n_grid
            slots += cp.bucket_len
        # A mixed window's scan pads its schedule (one bucket) to a power
        # of two: the padding iterations carry valid_len 0, so their
        # tiles are in the grid and none is live.
        grid += (bucket_tokens - slots) // cp.bucket_len * n_grid
        self.prefill_attn_tiles["live"] += live
        self.prefill_attn_tiles["skipped"] += grid - live
        return live, grid, pages

    def _flash_prefill_serves(self, kind: str, bucket_len: int) -> bool:
        """Whether a chunk of ``bucket_len`` slots through a layer of
        ``kind`` (an ``_attn_kinds`` label) takes the flash prefill kernel
        here: ``ops/attention.py: prefill_attention``'s own selector."""
        cfg = self.config.model
        heads = (cfg.attention_specs[kind].num_heads if cfg.attention_specs
                 else cfg.num_heads)
        return self.mesh.size == 1 and attn_ops.use_pallas_prefill(
            heads, cfg.num_kv_heads, cfg.head_dim, bucket_len)

    def _note_compiles(self, rec, seq_ids=None) -> None:
        """Drain XLA compile events fired inside the jit calls this
        dispatch just made and attribute them: the window flight record
        goes compile-tainted and every co-scheduled request's trace
        (``seq_ids``; the record's own by default) is tagged compile=true
        (the compile-excluded-TTFT separator).  No record: tracing is
        off."""
        if rec is None:
            return
        self.obs.on_compile(
            rec.seq_ids if seq_ids is None else seq_ids,
            self.obs.compile_tracker.drain_events(), rec,
        )

    def _count_sample_dispatch(self, sorts) -> None:
        """One more dispatched program that samples (decode window, mixed
        window, single step, prefill tail).  ``sorts``: the host's reading
        of the device's predicate (``sampling.needs_sort``) over the
        parameter arrays the program was handed; no read-back."""
        self.sample_dispatches += 1
        self.sample_sorted_dispatches += bool(sorts)

    def _note_decode_launch(self) -> None:
        """Host-gap bookkeeping: time since the previous decode step
        retired with the device left idle.  Lookahead dispatches count a
        zero gap by construction (the device was still busy)."""
        if self._last_decode_end is not None:
            # stackcheck: allow=SC201 reason=gap bookkeeping feeds tpu:decode_host_gap_ms only; no plan state reads it
            self._gap_total_s += max(0.0, time.time() - self._last_decode_end)
            self._gap_steps += 1
        self._last_decode_end = None

    @_enclosed("dispatch")
    def _dispatch_decode_async(
        self, seqs: List[Sequence], lookahead: bool, prev_sampled=None
    ) -> _PendingStep:
        """Enqueue one decode+sample step on the device and return
        without any host round-trip.  ``lookahead=False`` (re)builds the
        device-resident batch state from host bookkeeping (one packed
        [11, S] transfer + the block tables); ``lookahead=True`` is the
        steady "same batch, +1 token" path (one packed [4, S] delta,
        tokens chained from the in-flight sample)."""
        t0 = time.time()
        rec = self._open_record(
            "decode", seqs=seqs, provisional=lookahead,
            ahead=1 if lookahead else 0,
        )
        self._stamp_record(rec, t0)
        with self.obs.phase("build", rec):
            st = self._pipe_state(seqs, lookahead, prev_sampled)
        self._pipe_tables = st["tables"]

        lora_kwargs = {}
        if self.lora_registry is not None:
            lora_kwargs = {
                "lora": self.lora_registry.params,
                "adapter_idx": self._pipe_adapter,
            }
        if lookahead:
            self._gap_steps += 1  # device busy: zero gap by construction
            self._last_decode_end = None
        else:
            self._note_decode_launch()
        with self.obs.phase("launch", rec):
            logits, self.kv_caches = self._decode_fn(
                self.params,
                tokens=st["tokens"],
                positions=st["positions"],
                block_tables=st["tables"],
                ctx_lens=st["ctx_lens"],
                slot_block_ids=st["slot_blocks"],
                slot_offsets=st["slot_offsets"],
                kv_caches=self.kv_caches,
                **lora_kwargs,
                **self._pipe_state_kwargs,
            )
            temps, top_ps, top_ks, min_ps, seeds = self._pipe_sampling
            step_key = jax.random.PRNGKey(
                self.config.seed + self._step_counter
            )
            sampled = self._sample_fn(
                logits, temps, top_ps, top_ks, step_key, seeds, min_p=min_ps,
            )
            self._count_sample_dispatch(self._pipe_sample_sorts)
        self._step_counter += 1
        self._note_compiles(rec)
        # stackcheck: allow=SC201 reason=host_s is a stats field (host-gap metric); no plan state reads it
        return _PendingStep(
            seqs=list(seqs), sampled=sampled, is_decode=True,
            host_s=time.time() - t0, rec=rec,
        )

    def _pipe_state(self, seqs: List[Sequence], lookahead: bool,
                    prev_sampled=None) -> dict:
        """Device-resident decode batch state for one pipelined step:
        rebuilt from host bookkeeping, or (``lookahead``) advanced by one
        token from the in-flight sample."""
        # Rebuilds pad to the decode batch-size bucket; lookahead steps
        # reuse the device-resident state, whose row count is by
        # construction the same bucket (identical running set).
        S = (
            self._decode_bucket(len(seqs))
            if not lookahead
            else self._pipe_tables.shape[0]
        )

        if not lookahead:
            (tokens, positions, tables, ctx_lens, slot_blocks,
             slot_offsets) = self._decode_batch_arrays(seqs, S)
            adapter = np.zeros((S,), np.int32)
            for i, seq in enumerate(seqs):
                adapter[i] = seq.adapter_idx
            temps, top_ps, top_ks, min_ps, seeds = self._sampling_arrays(
                seqs, S
            )
            self._pipe_sample_sorts = sampling_lib.needs_sort(
                temps, top_ps, top_ks
            )
            packed = np.stack([
                tokens, positions, ctx_lens, slot_blocks, slot_offsets,
                temps.view(np.int32), top_ps.view(np.int32), top_ks,
                min_ps.view(np.int32), seeds, adapter,
            ])
            st = self._pipe_unpack_fn(
                self._put(packed, P(None, AXES.DP)),
                self._put(tables, P(AXES.DP, None)),
            )
            self._pipe_sampling = (
                st["temps"], st["top_ps"], st["top_ks"], st["min_ps"],
                st["seeds"],
            )
            self._pipe_adapter = st["adapter"]
            self._pipe_table_lens = [len(s.block_table) for s in seqs]
            self._pipe_state_kwargs = self._state_kwargs(seqs, S)
        else:
            positions = np.zeros((S,), np.int32)
            ctx_lens = np.zeros((S,), np.int32)
            cols = np.full((S,), -1, np.int32)
            vals = np.zeros((S,), np.int32)
            for i, seq in enumerate(seqs):
                pos = seq.num_tokens  # consumes the in-flight token
                positions[i] = pos
                ctx_lens[i] = pos + 1
                have = self._pipe_table_lens[i]
                if len(seq.block_table) > have:
                    # schedule_provisional grows by at most one block.
                    cols[i] = have
                    vals[i] = seq.block_table[have]
                    self._pipe_table_lens[i] = have + 1
            packed = np.stack([positions, ctx_lens, cols, vals])
            st = self._pipe_advance_fn(
                self._put(packed, P(None, AXES.DP)),
                prev_sampled,
                self._pipe_tables,
            )
        return st

    # -- K-step device-resident decode windows -----------------------------

    @staticmethod
    def _pow2_bucket(n: int, floor: int) -> int:
        """Shared shape-bucketing for the window's token/stop-id arrays:
        XLA compiles O(log) variants, not one per length."""
        b = floor
        while b < n:
            b *= 2
        return b

    def _stop_set_ids(self, seq: Sequence) -> tuple:
        """THE per-sequence stop set: ``stop_token_ids`` plus EOS unless
        ``ignore_eos`` — what ends generation at sampling time, and
        (vLLM min_tokens semantics) exactly the set the unmet min_tokens
        floor suppresses.  Shared by the window's device stop-mask and
        the host path's min_tokens logit ban so the two can never
        diverge.  Out-of-vocab ids can never be sampled and are dropped
        (this also keeps both the device scatter and the host bias
        matrix in bounds)."""
        sp = seq.sampling_params
        V = self.config.model.vocab_size
        ids = [t for t in (sp.stop_token_ids or ()) if 0 <= t < V]
        eos = self.tokenizer.eos_token_id
        if eos is not None and not sp.ignore_eos:
            ids.append(eos)
        return tuple(sorted(set(ids)))

    def _row_static(self, seq: Sequence) -> tuple:
        """What a window's rebuild reads of a request's SamplingParams, read
        once in its life (the pattern of ``seq_host_state_flags``): (its
        column of step_programs.WIN_SAMPLING_ROWS, int32 with the floats
        bitcast; whether it set a seed, else the row's index stands in;
        min_tokens; its stop set)."""
        static = seq._row_static
        if static is None:
            sp = seq.sampling_params
            values = {
                "temps": sp.temperature, "top_ps": sp.top_p,
                "top_ks": sp.top_k, "min_ps": sp.min_p,
                "seeds": sp.seed if sp.seed is not None else 0,
                "presence": sp.presence_penalty,
                "frequency": sp.frequency_penalty,
                "repetition": sp.repetition_penalty,
            }
            names = step_programs.WIN_ROWS[step_programs.WIN_SAMPLING_ROWS]
            col = np.zeros((len(names),), np.int32)
            f32 = col.view(np.float32)
            for i, name in enumerate(names):
                into = f32 if name in step_programs.WIN_FLOAT_ROWS else col
                into[i] = values[name]
            seq._row_static = static = (
                col, sp.seed is not None, sp.min_tokens,
                self._stop_set_ids(seq),
            )
        return static

    def _window_host_state(self, seqs: List[Sequence], steps: List[int],
                           first_row: int = -1):
        """Host arrays + static flags for a window batch (re)build: every
        per-row scalar as one row of ``packed`` [N, S] int32 (the rows of
        ``self._win_rows``, floats bitcast), the block tables, the stop
        ids.  Padding rows: ``done``, ``top_ps`` 1, ``repetition`` 1, else
        0 (null block, temperature 0).  ``first_row``: the row whose first
        token is still on the device (-1: none): it stands one token further
        along than its sequence says, its token for the device to fill in."""
        S = self._decode_bucket(len(seqs))
        n = len(seqs)
        (tokens, positions, tables, ctx_lens, _sb, _so) = (
            self._decode_batch_arrays(seqs, S)
        )
        if first_row >= 0:
            tokens[first_row] = 0
            positions[first_row] += 1
            ctx_lens[first_row] += 1
        at = self._win_row_at
        packed = np.zeros((len(at), S), np.int32)
        f32 = packed.view(np.float32)
        packed[at["tokens"]] = tokens
        packed[at["positions"]] = positions
        packed[at["ctx_lens"]] = ctx_lens
        packed[at["done"], n:] = 1
        packed[at["max_steps"], :n] = steps
        f32[at["top_ps"], n:] = 1.0
        f32[at["repetition"], n:] = 1.0
        min_left = packed[at["min_left"]]
        seeds = packed[at["seeds"]]
        stop_lists = []
        for i, seq in enumerate(seqs):
            col, seeded, min_tokens, stop = self._row_static(seq)
            packed[step_programs.WIN_SAMPLING_ROWS, i] = col
            if not seeded:
                seeds[i] = i
            if min_tokens:
                min_left[i] = max(
                    0, min_tokens - len(seq.output_token_ids)
                    - (i == first_row)
                )
            stop_lists.append(stop)
        if "adapter" in at:
            packed[at["adapter"], :n] = [s.adapter_idx for s in seqs]
        if "state_slots" in at:
            # Each row's live slot; the null slot 0 for a padding row.
            packed[at["state_slots"], :n] = [s.state_slot for s in seqs]
        B = self._pow2_bucket(
            max([len(ids) for ids in stop_lists] + [1]), 1
        )
        stop_ids = np.full((S, B), -1, np.int32)
        for i, ids in enumerate(stop_lists):
            stop_ids[i, : len(ids)] = ids
        return {
            "S": S, "packed": packed, "tables": tables, "stop_ids": stop_ids,
            "use_penalties": bool(
                np.any(f32[at["presence"]]) or np.any(f32[at["frequency"]])
                or np.any(f32[at["repetition"]] != 1.0)
            ),
            "use_min_floor": bool(np.any(min_left > 0)),
            # The host's reading of the sampler's device predicate, for
            # the dispatch counters; chained windows carry it unchanged,
            # as they carry the arrays.
            "sample_sorts": sampling_lib.needs_sort(
                f32[at["temps"]], f32[at["top_ps"]], packed[at["top_ks"]]
            ),
        }

    def _window_build(self, seqs: List[Sequence], steps: List[int],
                      first: Optional[tuple] = None) -> dict:
        """Full batch (re)build: ONE transfer (_stage) carries every window
        input to the device, the per-row scalars packed and unpacked there
        (win_unpack_fn); the occurrence state the penalty math reads takes
        a second where a row has penalties.  Runs once per batch
        composition; steady-state windows chain through _window_chain's
        delta transfer instead.  ``first``: (a sequence of ``seqs`` whose
        prefill is still in flight, its sampled first token [1] on the
        device), which win_unpack_fn writes into that row's ``tokens``."""
        first_row, first_token = -1, self._no_first_token
        if first is not None:
            first_row = seqs.index(first[0])
            # The sampler's output under the sharding of the constant it
            # stands in for: one compiled win_unpack_fn for both.
            first_token = jax.device_put(first[1], self._sharding(P()))
        h = self._window_host_state(seqs, steps, first_row)
        S = h["S"]
        host = {
            "packed": h["packed"], "tables": h["tables"],
            "stop_ids": h["stop_ids"],
            "first_row": np.array([first_row], np.int32),
        }
        if self._spec_window_fn is not None:
            # Carried drafting history for the fused speculative window:
            # the last H tokens (prompt + generated), left -1-padded so
            # hist[:, -1] is always the committed last token.  The scan
            # appends accepted tokens on-device; only a batch rebuild
            # retransfers it.
            H = self._SPEC_HIST_WINDOW
            hist = np.full((S, H), -1, np.int32)
            for i, s in enumerate(seqs):
                ids = s.tail_token_ids(H)
                hist[i, H - len(ids):] = ids
            host["hist"] = hist
        if self.draft_block_pool is not None:
            # Model drafter: per-row draft-KV block tables from the
            # DEDICATED pool (static [S, Bd] width — the draft cache is
            # compact, so the table never grows mid-chain).  A rebuild
            # frees the previous batch's allocation wholesale and
            # re-allocates: any preempted / aborted / restored
            # sequence's draft KV is structurally reset (the draft
            # cache is rebuilt from `hist` by the next in-graph prime —
            # nothing stale can survive a batch change, and draft
            # writes never touch self.kv_caches at all).  Allocation
            # failure (an undersized explicit pool) declines this
            # batch's windows to plain — counted per declined dispatch
            # under tpu:multistep_fallback_total{reason=draft_pool},
            # never a stall.
            self._draft_primed = False
            if self._draft_block_alloc:
                self.draft_block_pool.free(self._draft_block_alloc)
                self._draft_block_alloc = []
            bd = self._draft_blocks_per_row
            need = len(seqs) * bd
            if self.draft_block_pool.can_allocate(need):
                blocks = self.draft_block_pool.allocate(need)
                self._draft_block_alloc = blocks
                dt = np.zeros((S, bd), np.int32)
                for i in range(len(seqs)):
                    dt[i] = blocks[i * bd:(i + 1) * bd]
                host["draft_tables"] = dt
        self.unchained_dispatches += 1
        dev = self._stage(host)
        state = self._win_unpack_fn(
            dev.pop("packed"), first_token, dev.pop("first_row")
        )
        state.update(dev)
        for flag in ("use_penalties", "use_min_floor", "sample_sorts"):
            state[flag] = h[flag]
        if h["use_penalties"]:
            # Device-resident occurrence state, built by scatter from
            # the bucketed [S, L] id arrays (same content as the host
            # path's arrays, so penalty values are bit-identical).
            L = self._pow2_bucket(
                max([len(s.output_token_ids) for s in seqs] + [1]), 64
            )
            out_tokens = np.full((S, L), -1, np.int32)
            for i, s in enumerate(seqs):
                ids = s.output_token_ids[-L:]
                out_tokens[i, : len(ids)] = ids
            Lc = self._pow2_bucket(max(s.num_tokens for s in seqs), 64)
            ctx_tokens = np.full((S, Lc), -1, np.int32)
            for i, s in enumerate(seqs):
                ids = s.all_token_ids[-Lc:]
                ctx_tokens[i, : len(ids)] = ids
            occ = self._stage(
                {"out_tokens": out_tokens, "ctx_tokens": ctx_tokens}
            )
            state["counts"], state["seen"] = self._win_occurrence_fn(
                occ["out_tokens"], occ["ctx_tokens"]
            )
        if "draft_tables" not in state:
            # No drafter, or its pool declined this batch.
            state.pop("draft_pos", None)
        state["state_kwargs"] = (
            {"state_slots": state.pop("state_slots")}
            if self.state_pool is not None else {}
        )
        self._win_table_lens = [len(s.block_table) for s in seqs]
        return state

    def _state_kwargs(self, seqs: List[Sequence], S: int) -> Dict:
        """What a single-step decode program is told of the state pool:
        ``state_slots`` [S] int32 on the device, each row's live slot and the
        null slot 0 for a padding row; nothing without a pool."""
        if self.state_pool is None:
            return {}
        slots = np.zeros((S,), np.int32)
        slots[: len(seqs)] = [s.state_slot for s in seqs]
        return {"state_slots": self._put(
            slots, shardings_lib.decode_batch_spec())}

    def _window_chain(self, prev: _PendingStep, seqs: List[Sequence],
                      steps: List[int]) -> dict:
        """Steady path: window N+1's state IS window N's still-in-flight
        device carry — tokens/positions/done/penalty state never touch
        the host.  Only the per-window budget and up to C new block-table
        columns per row transfer."""
        state = dict(prev.win_state)
        S = state["max_steps"].shape[0]
        batch_spec = shardings_lib.decode_batch_spec()
        max_steps = np.zeros((S,), np.int32)
        max_steps[: len(steps)] = steps
        state["max_steps"] = self._put(max_steps, batch_spec)
        # Fixed delta width: retraces would otherwise key on how many
        # blocks happened to be crossed this window.  Sized for the
        # MAX-ACCEPTANCE growth — a fused speculative window can land
        # K x (ngram + 1) tokens, not K.
        C = self._window_max_tokens // self.block_pool.block_size + 2
        cols = np.full((S, C), -1, np.int32)
        vals = np.zeros((S, C), np.int32)
        for i, seq in enumerate(seqs):
            have = self._win_table_lens[i]
            new = seq.block_table[have:]
            for j, blk in enumerate(new[:C]):
                cols[i, j] = have + j
                vals[i, j] = blk
            self._win_table_lens[i] = have + len(new[:C])
        state["tables"] = self._win_advance_fn(
            state["tables"],
            self._put(cols, P(AXES.DP, None)),
            self._put(vals, P(AXES.DP, None)),
        )
        return state

    # stackcheck: root=step-thread
    @_enclosed("dispatch")
    def _dispatch_window(self, plan, chain_from: Optional[_PendingStep] = None,
                         behind: Optional[_PendingStep] = None,
                         ) -> _PendingStep:
        """Enqueue one decode window of ``plan.decode_window`` steps (the
        program runs as many as the plan's longest row was budgeted) on the
        device and return without any host round-trip.  ``chain_from=None`` (re)builds the
        device-resident window state from host bookkeeping;  otherwise
        the state chains from the previous window's in-flight carry
        (pipelined windows — the device never drains between them).
        ``behind``: the prefill still in flight this rebuilt window is
        launched behind; the row it admits, if the plan has it, takes its
        first token on the device."""
        t0 = time.time()
        decode = plan.decode
        seqs = decode.seqs
        k = plan.decode_window
        self.window_steps_hist.observe(k)
        depth = 0
        if chain_from is not None and chain_from.rec is not None:
            depth = chain_from.rec.chain_depth + 1
        first = None
        if behind is not None and behind.chunk.seq in seqs:
            first = (behind.chunk.seq, behind.first_token)
        # Opened as a plain decode window; the fused speculative path
        # below renames it once it is known to be taken.
        rec = self._open_record(
            "decode", seqs=seqs,
            ahead={first[0].seq_id: 1} if first else chain_from or 0,
            k=k, cut=plan.window_cut, chain_depth=depth,
            provisional=chain_from is not None,
            fallback=plan.window_fallback, behind=behind is not None,
        )
        # A program in flight: no gap to inherit, as under a chained window.
        self._stamp_record(rec, t0, gap=behind is None)
        with self.obs.phase("build", rec):
            if chain_from is None:
                state = self._window_build(seqs, decode.steps, first)
                self._record_kv_groups(rec)
            else:
                state = self._window_chain(chain_from, seqs, decode.steps)
        if chain_from is None and behind is None:
            self._note_decode_launch()
        else:
            self._gap_steps += 1  # device busy: zero gap by construction
            self._last_decode_end = None
        if behind is not None:
            self.dispatch_behind["window"] += 1
        lora_kwargs = {}
        if self.lora_registry is not None:
            lora_kwargs = {
                "lora": self.lora_registry.params,
                "adapter_idx": state["adapter"],
            }
        # The fused speculative window drafts only for all-greedy
        # batches (acceptance compares the model's own argmax); a batch
        # with sampled rows runs the PLAIN window below with the classic
        # per-iteration key schedule, so seeded streams stay
        # bit-identical across window sizes with speculation configured.
        spec_stats = None
        spec_drafter = None
        routing = ()   # a counting model's per-step counts (plain window)
        use_spec = self._spec_window_fn is not None and all(
            self._host_state_flags(s)[2] for s in seqs
        )
        if use_spec and self.draft_params is not None and (
            "draft_tables" not in state
        ):
            # Model drafter configured but this batch's build could not
            # allocate draft blocks (undersized explicit pool): decline
            # to the plain window — observable, never a stall.  One
            # increment per declined dispatch, matching the _can_window
            # counting unit.
            use_spec = False
            self.multistep_fallback["draft_pool"] = (
                self.multistep_fallback.get("draft_pool", 0) + 1
            )
        if use_spec:
            spec_kwargs = {}
            if self.draft_params is not None:
                spec_drafter = "model"
                # Skip-prime chaining: re-prime the draft cache in-graph
                # on the first model-spec window after any break in the
                # chain (batch rebuild, plain/mixed dispatch) and every
                # _DRAFT_PRIME_CHAIN windows (capacity watermark: a
                # primed cache holds <= H-1 slots and each window adds
                # <= window_max_tokens; the pool sizes exactly that
                # chain).
                do_prime = (
                    not self._draft_primed
                    or self._draft_windows_since_prime
                    >= self._DRAFT_PRIME_CHAIN
                )
                spec_kwargs = {
                    "draft_params": self.draft_params,
                    "draft_tables": state["draft_tables"],
                    "draft_pos": state["draft_pos"],
                    "draft_kv": self.draft_kv_caches,
                    "do_prime": do_prime,
                }
            else:
                spec_drafter = "ngram"
            with self.obs.phase("launch", rec):
                out = self._spec_window_fn(
                    self.params,
                    tokens=state["tokens"],
                    positions=state["positions"],
                    ctx_lens=state["ctx_lens"],
                    done=state["done"],
                    min_left=state["min_left"],
                    block_tables=state["tables"],
                    max_steps=state["max_steps"],
                    kv_caches=self.kv_caches,
                    stop_ids=state["stop_ids"],
                    counts=state["counts"],
                    seen=state["seen"],
                    hist=state["hist"],
                    presence=state["presence"],
                    frequency=state["frequency"],
                    repetition=state["repetition"],
                    use_penalties=state["use_penalties"],
                    use_min_floor=state["use_min_floor"],
                    **spec_kwargs,
                    **lora_kwargs,
                )
            if spec_drafter == "model":
                (emitted, drafted, accepted, out_state, self.kv_caches,
                 self.draft_kv_caches) = out
                self._draft_windows_since_prime = (
                    0 if do_prime else self._draft_windows_since_prime + 1
                )
                self._draft_primed = True
            else:
                emitted, drafted, accepted, out_state, self.kv_caches = out
            spec_stats = (drafted, accepted)
            # Greedy argmax consumes no PRNG ordinals; the counter still
            # advances one per iteration (deterministic on every
            # lockstep replica — acceptance is a pure function of the
            # shared weights and carried state, never of wall clock).
            self._step_counter += k
        else:
            # Any non-model-spec dispatch advances positions without
            # extending the draft KV: the chain is broken and the next
            # model-spec window must re-prime from `hist`.
            self._draft_primed = False
            with self.obs.phase("launch", rec):
                emitted, out_state, self.kv_caches, *routing = self._window_fn(
                    self.params,
                    tokens=state["tokens"],
                    positions=state["positions"],
                    ctx_lens=state["ctx_lens"],
                    done=state["done"],
                    min_left=state["min_left"],
                    block_tables=state["tables"],
                    max_steps=state["max_steps"],
                    kv_caches=self.kv_caches,
                    temps=state["temps"],
                    top_ps=state["top_ps"],
                    top_ks=state["top_ks"],
                    min_ps=state["min_ps"],
                    seq_seeds=state["seeds"],
                    stop_ids=state["stop_ids"],
                    # Masked to 31 bits: a long-lived engine's monotone step
                    # counter would otherwise overflow the host->int32 cast
                    # and kill the step thread.  Below 2**31 key ordinals
                    # (years of serving) the schedule is bit-identical to
                    # single-token stepping; past it, +t wraps in-graph,
                    # which PRNGKey treats as bits — still deterministic
                    # across lockstep replicas.
                    key_base=np.int32(
                        (self.config.seed + self._step_counter) & 0x7FFFFFFF
                    ),
                    counts=state["counts"],
                    seen=state["seen"],
                    presence=state["presence"],
                    frequency=state["frequency"],
                    repetition=state["repetition"],
                    use_penalties=state["use_penalties"],
                    use_min_floor=state["use_min_floor"],
                    **lora_kwargs,
                    **state["state_kwargs"],
                )
            self._count_sample_dispatch(state["sample_sorts"])
            # One key ordinal per iteration the plan runs: single-token
            # stepping would have burned exactly these counter values for
            # the same tokens.
            self._step_counter += k
        state.update(out_state)
        if rec is not None:
            if spec_stats is not None:
                rec.kind = "spec"
                rec.spec_width = self.config.scheduler.spec_draft_len
                rec.drafter = spec_drafter
            self._note_compiles(rec)
        # stackcheck: allow=SC201 reason=host_s is a stats field (host-gap metric); no plan state reads it
        return _PendingStep(
            seqs=list(seqs), sampled=emitted, is_decode=True,
            host_s=time.time() - t0, steps=list(decode.steps),
            win_state=state, spec_stats=spec_stats,
            spec_drafter=spec_drafter, rec=rec,
            routing=routing[0] if routing else None,
        )

    # stackcheck: root=step-thread
    @_enclosed("dispatch")
    def _dispatch_mixed_window(
        self, plan, chain_from: Optional[_PendingStep] = None
    ) -> _PendingStep:
        """Enqueue one MIXED K-step window: each of the
        K = len(plan.chunk_schedule) scan iterations runs the packed
        [decode + chunk] mixed forward — decode rows advance from the
        carried state exactly like ``_dispatch_window`` while prompt
        chunks ride the same forward, each iteration's cursor
        (cached_len / valid_len / new-block row / prefix table /
        adapter slot) precomputed per iteration and carried as scan xs.
        Packed windows interleave cursors from
        SEVERAL prompts: a final chunk's iteration f finalizes its
        prompt at collect with PRNG ordinal base+f, and the next
        iteration's xs switch to the next prompt's tokens and block
        tables — the per-iteration prefix table is what makes the
        ragged hand-off transparent to the model fn.  ``chain_from``
        chains the decode carry from the previous window (pure or
        mixed) with no host round-trip; the chunk arrays are fresh per
        window either way, staged through double-buffered host arrays
        (two alternating sets per scan shape) so building window N+1's
        H2D payload never waits on window N's still-draining copy —
        time spent staging while the device is busy is counted in
        ``tpu:window_transfer_overlap_seconds_total``.  The scan length
        is the next power of two >= K (a static compile bucket —
        trailing iterations are no-ops frozen by ``max_steps`` and a
        zero-valid chunk row)."""
        t0 = time.time()
        decode = plan.decode
        seqs = decode.seqs
        sched = plan.chunk_schedule
        k_eff = len(sched)
        n_scan = self._pow2_bucket(k_eff, 1)
        depth = 0
        if chain_from is not None and chain_from.rec is not None:
            depth = chain_from.rec.chain_depth + 1
        rec = self._open_record(
            "mixed", seqs=seqs, chunks=sched, ahead=chain_from or 0,
            bucket_tokens=n_scan * sched[0].bucket_len,
            k=k_eff, chain_depth=depth, provisional=chain_from is not None,
            fallback=plan.window_fallback,
        )
        self._stamp_record(rec, t0)
        if self.obs.enabled:
            for cp in sched:
                if cp.seq.first_scheduled_time is None:
                    cp.seq.first_scheduled_time = t0
                    self.obs.on_first_scheduled(cp.seq, t0)
        with self.obs.phase("build", rec):
            if chain_from is None:
                state = self._window_build(seqs, decode.steps)
                self._record_kv_groups(rec)
            else:
                state = self._window_chain(chain_from, seqs, decode.steps)
        if chain_from is None:
            self._note_decode_launch()
        else:
            self._gap_steps += 1  # device busy: zero gap by construction
            self._last_decode_end = None
        # Mixed windows keep `hist` warm but advance positions without
        # extending the draft KV (drafting is a pure-decode-window
        # feature): the model drafter's skip-prime chain is broken and
        # the next model-spec window re-primes from the warm hist.
        self._draft_primed = False

        lora_kwargs = {}
        if self.lora_registry is not None:
            lora_kwargs = {
                "lora": self.lora_registry.params,
                "adapter_idx": state["adapter"],
            }
        with self.obs.phase("build", rec):
            pf_device, any_final, overlap_s = self._stage_chunks(
                sched, n_scan, chained=chain_from is not None
            )
        with self.obs.phase("launch", rec):
            emitted, tails, out_state, self.kv_caches = (
                self._mixed_window_fn(
                    self.params,
                    tokens=state["tokens"],
                    positions=state["positions"],
                    ctx_lens=state["ctx_lens"],
                    done=state["done"],
                    min_left=state["min_left"],
                    block_tables=state["tables"],
                    max_steps=state["max_steps"],
                    kv_caches=self.kv_caches,
                    temps=state["temps"],
                    top_ps=state["top_ps"],
                    top_ks=state["top_ks"],
                    min_ps=state["min_ps"],
                    seq_seeds=state["seeds"],
                    stop_ids=state["stop_ids"],
                    # Same 31-bit masking rationale as _dispatch_window.
                    key_base=np.int32(
                        (self.config.seed + self._step_counter) & 0x7FFFFFFF
                    ),
                    counts=state["counts"],
                    seen=state["seen"],
                    presence=state["presence"],
                    frequency=state["frequency"],
                    repetition=state["repetition"],
                    pf_tokens=pf_device["tokens"],
                    pf_cached=pf_device["cached"],
                    pf_valid=pf_device["valid"],
                    pf_new_blocks=pf_device["new_blocks"],
                    pf_prefix_ids=pf_device["prefix"],
                    pf_adapter=pf_device["adapter"],
                    n_steps=n_scan,
                    use_penalties=state["use_penalties"],
                    use_min_floor=state["use_min_floor"],
                    hist=state.get("hist"),
                    **lora_kwargs,
                )
            )
        self._count_sample_dispatch(state["sample_sorts"])
        # chunk_ordinal is the window's BASE step counter: a final
        # chunk at iteration f is K=1 step (base + f), and the
        # collect-side first-token sample burns exactly that ordinal —
        # per packed prompt.
        chunk_ordinal = self._step_counter
        # K_eff live iterations = K_eff single-step equivalents (dead
        # pow-2 padding iterations burn no ordinal anywhere).
        self._step_counter += k_eff
        state.update(out_state)
        if rec is not None:
            rec.transfer_overlap_s = overlap_s
            self._note_compiles(rec)
        # stackcheck: allow=SC201 reason=host_s is a stats field (host-gap metric); no plan state reads it
        return _PendingStep(
            seqs=list(seqs), sampled=emitted, is_decode=True,
            host_s=time.time() - t0, steps=list(decode.steps),
            win_state=state,
            chunk_sched=list(sched),
            chunk_logits=tails if any_final else None,
            chunk_ordinal=chunk_ordinal,
            rec=rec,
        )

    def _stage_chunks(self, sched, n_scan: int, chained: bool):
        """A mixed window's chunk schedule as device arrays: (the scan xs,
        whether any chunk is its prompt's last, seconds of staging that ran
        under the previous window's compute)."""
        k_eff = len(sched)
        # Per-iteration chunk schedule (host-precomputed, rides as scan
        # xs).  All chunks share ONE bucket T (static scan shape); dead
        # pow-2 padding iterations carry valid_len 0, new blocks parked
        # on null block 0, and the last chunk's END cursor as cached_len
        # (their masked rows compute garbage that lands only on the null
        # block, exactly like frozen decode rows).
        t_stage = time.time()
        bs = self.block_pool.block_size
        T = sched[0].bucket_len
        pmax = max(self._bmax, 1)
        stage = self._mw_stage.get((n_scan, T))
        if stage is None:
            mk = lambda: {  # noqa: E731
                "tokens": np.zeros((n_scan, T), np.int32),
                "cached": np.zeros((n_scan,), np.int32),
                "valid": np.zeros((n_scan,), np.int32),
                "new_blocks": np.zeros((n_scan, T // bs), np.int32),
                "prefix": np.zeros((n_scan, pmax), np.int32),
                "adapter": np.zeros((n_scan,), np.int32),
            }
            stage = self._mw_stage[(n_scan, T)] = [mk(), mk(), 0]
        buf = stage[stage[2]]
        stage[2] ^= 1
        for arr in buf.values():
            arr.fill(0)
        any_final = False
        for i, cp in enumerate(sched):
            toks = cp.seq.prompt_token_ids[
                cp.cached_len : cp.cached_len + cp.num_new_tokens
            ]
            buf["tokens"][i, : len(toks)] = toks
            buf["cached"][i] = cp.cached_len
            buf["valid"][i] = cp.num_new_tokens
            buf["new_blocks"][i, : len(cp.new_block_ids)] = cp.new_block_ids
            full = list(cp.prefix_block_ids) + list(cp.new_block_ids)
            buf["prefix"][i, : len(full)] = full
            buf["adapter"][i] = cp.seq.adapter_idx
            if cp.is_final:
                any_final = True
        # Dead pow-2 padding iterations replay the LAST live chunk's
        # cursor/table at valid_len 0 (frozen, null-block writes only).
        end_cursor = sched[-1].cached_len + sched[-1].num_new_tokens
        buf["cached"][k_eff:] = end_cursor
        buf["prefix"][k_eff:] = buf["prefix"][k_eff - 1]

        pf_device = {
            k: self._put(v, P()) for k, v in buf.items()
        }
        overlap_s = 0.0
        if chained:
            # The previous window still occupies the device: every
            # second of this H2D staging ran UNDER its compute instead
            # of serializing after it.
            overlap_s = time.time() - t_stage
            self.window_transfer_overlap_s += overlap_s
        return pf_device, any_final, overlap_s

    def _collect_window(self, p: _PendingStep) -> List[StepOutput]:
        """Read one window's emitted tokens back ([K, S] plain, or
        [K, W, S] from the fused speculative scan — flattened to the
        chronological [K*W, S] token order) and replay them through the
        single finish protocol, token by token — exactly the per-token
        path single stepping takes, so streams are identical.
        Device-frozen rows emit -1 (their stop already retired) and cost
        nothing; emitted tokens that can no longer be delivered (their
        sequence aborted / finished out-of-band while the window flew)
        are counted as multistep waste.  Fused windows additionally
        account drafted / accepted / wasted speculation per window."""
        t0 = time.time()
        with self.obs.phase("collect", p.rec):
            arr = np.asarray(p.sampled)  # the ONE device sync point
            if p.routing is not None:
                # Same program, already done: no second wait.
                self._count_routing(p.rec, np.asarray(p.routing))
        if p.spec_drafter == "model":
            # Scan seconds attributed to draft forwards
            # (tpu:spec_draft_fraction_seconds): the measured collect
            # sync wait times the static cost-model split computed at
            # boot from real parameter counts (the n-gram drafter's
            # lookup costs no forward, so it accrues nothing).
            # Pipelined windows under-attribute — the host overlaps part
            # of the scan — which keeps the counter a floor, never an
            # overclaim.
            self.spec_draft_fraction_s += (
                self._draft_cost_fraction * (time.time() - t0)
            )
        with self.obs.phase("sample", p.rec):
            outputs, counts = self._replay_window(p, arr)
        if p.rec is not None:
            # Sample-side jits ran inside the replay above: drain any
            # compiles onto this record, then complete it.
            self._note_compiles(p.rec, [s.seq_id for s in p.seqs])
            self.obs.recorder.on_collect(p.rec, host_s=p.host_s, **counts)
            self._note_window_pace(p.rec)
        return outputs

    def _note_window_pace(self, rec) -> None:
        """Feed the planner's two means (scheduler.WindowPace) from a decode
        window that has just closed, where it was chained from the window
        in flight: the telescoped clock then gives its device time
        (``attributed_s``: the device was never empty), and that less the
        time the step thread stood blocked in its read-back is what the
        thread was busy with since the close before -- the whole of its
        pass, whoever's work it was.  A window that met an empty device, or
        a compile, times something else."""
        if not (self.plan_from_clocks and rec.provisional) or rec.compile:
            return
        blocked = sum(
            (t1 - t0) / 1e9 for name, t0, t1 in rec.phases
            if name == "collect")
        # A clock reaches a plan here, and only here: ``plan_from_clocks``
        # is cleared wherever replicas must plan alike.
        self.scheduler.pace.note(
            rec.attributed_s / rec.k, max(0.0, rec.attributed_s - blocked))

    def _count_routing(self, rec, counts) -> None:
        """Fold a window's routing counts (``[K, n]``, a row a step) into
        the totals and onto its flight record, then those of the prefill
        chunks whose programs have finished since (``[n]`` each)."""
        done = [(rec, counts)]
        while self._routing_pending and self._routing_pending[0][1].is_ready():
            chunk_rec, chunk = self._routing_pending.popleft()
            done.append((chunk_rec, np.asarray(chunk)))
        for rec, counts in done:
            # Counts add over the steps; a fullest expert's rows and a worst
            # row sum are maxima.
            counts = counts.reshape(-1, counts.shape[-1])
            folded = dict(zip(self._routing_names, (int(n) for n in np.where(
                self._routing_max, counts.max(0), counts.sum(0)))))
            if "moe_assigned" in folded:
                here = folded["moe_assigned_here"]
                self.moe_assignments["held"] += here
                self.moe_assignments["away"] += folded["moe_assigned"] - here
                self.moe_experts_touched += folded["experts_touched"]
                self.moe_zero_assigned += folded.get("moe_zero_assigned", 0)
            if "ssm_dt_max_e3" in folded:
                self.ssm_state_absmax = max(
                    self.ssm_state_absmax,
                    folded["ssm_state_absmax_e3"] / 1e3)
                self.ssm_dt_max = max(
                    self.ssm_dt_max, folded["ssm_dt_max_e3"] / 1e3)
            if "gdn_beta_max_e3" in folded:
                self.gdn_state_absmax = max(
                    self.gdn_state_absmax,
                    folded["gdn_state_absmax_e3"] / 1e3)
                self.gdn_beta_max = max(
                    self.gdn_beta_max, folded["gdn_beta_max_e3"] / 1e3)
            if "mhc_entries" in folded:
                self.mhc_clamped += folded["mhc_clamped"]
                self.mhc_entries += folded["mhc_entries"]
                self.mhc_sinkhorn_err = max(
                    self.mhc_sinkhorn_err, folded["mhc_err_e6"] / 1e6)
            if rec is not None:
                rec.routing = folded

    def _replay_window(self, p: _PendingStep, arr):
        """The host half of a window's collect: (outputs, the token counts
        its flight record is completed with)."""
        spec = p.spec_stats is not None
        if arr.ndim == 3:
            arr = arr.reshape(-1, arr.shape[-1])  # [K*W, S], in order
        outputs: List[StepOutput] = []
        delivered = [0] * len(p.seqs)
        alive = [(i, s) for i, s in enumerate(p.seqs) if not s.is_finished]
        for t in range(arr.shape[0]):
            batch = []
            toks = []
            for i, s in alive:
                if delivered[i] >= p.steps[i]:
                    continue  # token budget exhausted (belt and braces)
                tok = int(arr[t, i])
                if tok < 0:
                    continue  # frozen row: stop-mask spent no token here
                batch.append((i, s))
                toks.append(tok)
            if not batch:
                if not spec:
                    # done/budget masks are monotone within a plain
                    # window: no row can re-activate later.
                    break
                # Fused windows interleave -1 gaps per iteration (a row
                # that accepted fewer drafts than a neighbor pads its
                # sub-steps), so an empty slice is NOT terminal.
                continue
            outs = self._append_and_check(
                [s for _, s in batch], toks, first_token=False
            )
            outputs.extend(outs)
            for i, _ in batch:
                delivered[i] += 1
            alive = [(i, s) for i, s in alive if not s.is_finished]
        # Waste = emitted (device-computed, >= 0) minus delivered to the
        # finish protocol: rows finished before the window collected
        # (abort, out-of-band) deliver none, and rows a HOST-side finish
        # (stop string, guided rejection) retires mid-replay skip their
        # tail.  Device-stopped rows emit -1 past the stop, so ordinary
        # stops contribute zero by construction.
        emitted = 0
        for i in range(len(p.seqs)):
            emitted += int((arr[:, i] >= 0).sum())
        wasted = emitted - sum(delivered)
        if wasted:
            self.multistep_wasted_tokens += wasted
        chunk_delivered = 0
        if p.chunk_sched is not None:
            # Mixed window: account the chunk tokens that rode the scan
            # and finalize EACH packed prompt whose final chunk landed —
            # the identical _finalize_final_prefill path (and PRNG
            # ordinal: window base + the final chunk's iteration index)
            # the K=1 mixed step uses, so first tokens are bit-identical
            # by construction.  A prompt aborted / deadline-shed while
            # the window flew skips its finalize — the written chunk KV
            # is unreachable and counted as waste, never silently
            # vanished — and the OTHER packed prompts are unaffected.
            tails = (
                np.asarray(p.chunk_logits)  # [n_scan, V] per-iter tails
                if p.chunk_logits is not None else None
            )
            by_seq = []  # [(seq, [(iteration, chunk), ...])] in order
            for i, cp in enumerate(p.chunk_sched):
                if by_seq and by_seq[-1][0] is cp.seq:
                    by_seq[-1][1].append((i, cp))
                else:
                    by_seq.append((cp.seq, [(i, cp)]))
            for seq, chunks in by_seq:
                chunk_tokens = sum(cp.num_new_tokens for _, cp in chunks)
                if seq.is_finished:
                    self.multistep_wasted_tokens += chunk_tokens
                    continue
                self.prefill_chunk_tokens += chunk_tokens
                self.mixed_window_chunk_tokens += chunk_tokens
                chunk_delivered += chunk_tokens
                if tails is None:
                    continue
                for i, cp in chunks:
                    if cp.is_final:
                        outputs.extend(self._finalize_final_prefill(
                            seq, tails[i],
                            step_ordinal=p.chunk_ordinal + i,
                        ))
            self.mixed_window_prompts_hist.observe(len(by_seq))
        drafted = accepted = 0
        if spec:
            # Per-window speculation accounting: drafted/accepted feed
            # the existing acceptance-rate counters; the outcome split
            # (accepted / rejected / wasted) is the fused family.
            n = len(p.seqs)
            drafted = int(np.asarray(p.spec_stats[0])[:, :n].sum())
            accepted = int(np.asarray(p.spec_stats[1])[:, :n].sum())
            self.spec_tokens_drafted += drafted
            self.spec_tokens_accepted += accepted
            self.spec_window_tokens["accepted"] += accepted
            self.spec_window_tokens["rejected"] += drafted - accepted
            self.spec_window_tokens["wasted"] += wasted
        return outputs, dict(
            tokens_emitted=emitted,
            tokens_delivered=emitted - wasted,
            tokens_wasted=wasted,
            chunk_tokens_delivered=chunk_delivered,
            drafted=drafted, accepted=accepted,
        )

    def restore_seq_blocks(self, seq: Sequence) -> str:
        """Scheduler restore_cb: page an offloaded sequence's KV snapshot
        back into freshly allocated blocks.  Returns "restored" (sequence
        now holds the blocks as a partial-prefill prefix — no recompute),
        "gone" (no snapshot: recompute), or "retry" (transient pool
        pressure: snapshot reinserted, try again next step)."""
        if self.obs.enabled:
            t0 = time.time()
            result = self._restore_seq_blocks(seq)
            if result != "retry":
                # KV paging shows up on the request's timeline: a restore
                # that precedes a slow re-admission is the attribution.
                self.obs.tracer.add_span(
                    seq.seq_id, "engine.kv_restore", t0, time.time(),
                    result=result,
                )
            return result
        return self._restore_seq_blocks(seq)

    # Sentinel: a remote restore page-in is in flight — schedule again
    # next pass instead of blocking (async analogue of pool-pressure
    # "retry").
    _RESTORE_PENDING = object()

    def _restore_entry(self, seq_id: str):
        """Snapshot lookup for restore: local host-DRAM tier first; a
        remote-tier miss triggers an ASYNC page-in (prefetch worker lands
        it in the local tier) and returns the pending sentinel — the
        scheduler re-checks readiness instead of blocking on the RPC.
        Legacy mode (remote_prefetch=False) keeps the blocking fetch."""
        if (
            self._offload_stager is not None
            and self._offload_stager.is_inflight(seq_id)
        ):
            # The snapshot is still between device and host: re-check
            # next pass rather than concluding "gone" and recomputing.
            return self._RESTORE_PENDING
        if self.kv_prefetch is None:
            return self.offload.restore(seq_id)
        entry = self.offload.restore_local(seq_id)
        if entry is not None:
            # Consume a completed page-in job, if one fed this entry.
            self.kv_prefetch.poll_restore(seq_id)
            return entry
        if self.offload.remote_client is None:
            return None
        state = self.kv_prefetch.poll_restore(seq_id)
        if state == "absent":
            self.kv_prefetch.submit_restore(seq_id)
            return self._RESTORE_PENDING
        if state == "inflight":
            return self._RESTORE_PENDING
        if state == "ready":
            return self.offload.restore_local(seq_id)
        return None  # "missing": recompute

    def _restore_seq_blocks(self, seq: Sequence) -> str:
        entry = self._restore_entry(seq.seq_id)
        if entry is self._RESTORE_PENDING:
            return "retry"
        if entry is None:
            return "gone"  # fall back to recompute via normal prefill
        bs = self.block_pool.block_size
        usable_tokens = min(entry.num_tokens, len(seq.prompt_token_ids) - 1)
        usable_blocks = usable_tokens // bs
        if usable_blocks == 0:
            return "gone"
        if not self.block_pool.can_allocate(usable_blocks):
            # Transient pool pressure must not cost the snapshot: put it
            # back so the next scheduling attempt can still use it.
            self.offload.reinsert(entry)
            return "retry"
        restored = self.block_pool.allocate(usable_blocks)
        ids = jnp.asarray(restored, jnp.int32)
        for layer_idx, (k_host, v_host) in enumerate(entry.layers):
            k_cache, v_cache = self.kv_caches[layer_idx]
            # set_blocks handles dense hosts (quantizing into int8
            # pools) and native (data, scale) wire tuples (adopted
            # untransformed — the no-requantize restore path).
            self.kv_caches[layer_idx] = (
                kv_quant.set_blocks(
                    k_cache, ids,
                    kv_quant.slice_host_side(k_host, usable_blocks),
                ),
                kv_quant.set_blocks(
                    v_cache, ids,
                    kv_quant.slice_host_side(v_host, usable_blocks),
                ),
            )
        seq.block_table = restored
        seq.num_cached_tokens = usable_blocks * bs
        seq.partial_prefill = True
        return "restored"

    # -- cross-engine prefix sharing (cache.disagg_role) -------------------

    def _px_key_prefix(self) -> str:
        """Content-key namespace binding blocks to THIS model's identity:
        structural shape AND a weight fingerprint (a sample of the
        embedding row), so two engines only exchange KV when they run the
        same weights — a peer serving a different model (or different
        random init) can never poison this one's cache."""
        if not hasattr(self, "_px_prefix_cache"):
            import hashlib

            cfg = self.config.model
            h = hashlib.blake2b(digest_size=8)
            h.update(
                f"{cfg.name}|{cfg.num_layers}|{cfg.num_kv_heads}|"
                f"{cfg.head_dim}|{cfg.dtype}|{self.block_pool.block_size}"
                .encode()
            )
            h.update(np.asarray(
                self.params["embed_tokens"][0], np.float32
            ).tobytes())
            self._px_prefix_cache = f"px:{h.hexdigest()}:"
        return self._px_prefix_cache

    def _seq_prefix_hashes(self, seq) -> List[bytes]:
        """The prompt's part of the sequence's chain (``Sequence.
        prefix_chain``), with the bound match_prefix keeps: >= 1 prompt
        token is left to prefill.  Hashes only what the memo lacks, e.g.
        the blocks that recompute-preemption absorbed into the prompt,
        which become export/fetch-able too."""
        n = (seq.num_prompt_tokens - 1) // self.block_pool.block_size
        return self.block_pool.extend_chain(
            seq.prefix_chain, seq.prompt_token_ids, n, seq.cache_ns
        )[:n]

    def _transfer_inflight(self) -> bool:
        """Any async KV transfer the scheduler may be waiting out."""
        if self._offload_stager is not None and self._offload_stager.busy:
            return True
        return self.kv_prefetch is not None and self.kv_prefetch.inflight > 0

    # -- admission-time remote-prefix prefetch (cache.remote_prefetch) -----

    def _submit_prefix_prefetch(self, seq) -> None:
        """Queue a background fetch of the sequence's local prefix-cache
        miss tail (called at admission, and again from the scheduler
        callback after recompute-preemption grows the prompt).  Pure host
        hashing + a queue put — no RPC, no device work."""
        hashes = self._seq_prefix_hashes(seq)
        if not hashes:
            return
        start = self.block_pool.count_cached_prefix(hashes)
        if start >= len(hashes):
            return
        # One fetch per distinct miss tail: without this memo a store-MISS
        # chain (submitted, completed empty) would re-fetch on every
        # scheduling pass the sequence spends waiting.  The key changes
        # when recompute-preemption grows the prompt or the local cache
        # absorbs more of the chain.  Set only on an ACCEPTED submit: a
        # decline (e.g. the same-head dedupe against another request's
        # in-flight job) must stay retryable, or an abort of that other
        # request would strand this one without a fetch forever.
        memo = (len(hashes), start)
        if getattr(seq, "_px_prefetch_memo", None) == memo:
            return
        key_prefix = self._px_key_prefix()
        if self.kv_prefetch.submit_chain(
            seq.seq_id,
            [key_prefix + d.hex() for d in hashes[start:]],
            hashes[start:],
            start,
        ):
            seq._px_prefetch_memo = memo

    # stackcheck: root=step-thread
    def _drain_prefetched(self) -> None:
        """Step-thread landing point for completed prefetches: import the
        staged host blocks into freshly allocated pool blocks (async
        device copy-in via set_blocks) and bind them to their chain
        digests in the prefix cache, then park them in the reclaimable
        cached-free tier — the next match_prefix serves them exactly like
        a local hit.  Transient pool pressure keeps a chain pending for a
        bounded number of retries; anything undeliverable counts as
        prefetch waste."""
        if self.kv_prefetch is None:
            return
        self._pending_prefetch_imports.extend(self.kv_prefetch.pop_completed())
        if not self._pending_prefetch_imports:
            return
        keep: List[PrefetchedChain] = []
        for chain in self._pending_prefetch_imports:
            outcome = self._import_prefetch_to_cache(chain)
            if outcome == "retry":
                chain.attempts += 1
                if chain.attempts < 16:
                    keep.append(chain)
                else:
                    self.kv_prefetch.note_waste(len(chain.blocks))
        self._pending_prefetch_imports = keep

    def _import_prefetch_to_cache(self, chain: PrefetchedChain) -> str:
        """Returns "done" (imported / nothing left to do), "retry"
        (pool pressure), or "drop" (malformed entries — degrade)."""
        # A chain is only usable as a PREFIX: stop at the first digest the
        # cache already holds a block for (earlier digests were local
        # hits at submit time; a digest appearing mid-chain means a
        # concurrent prefill registered it and our copy is redundant from
        # that point on).
        ready = []
        for digest, layers in zip(chain.hashes, chain.blocks):
            if self.block_pool.has_digest(digest):
                if not ready:
                    continue  # leading blocks already cached: skip them
                break
            ready.append((digest, layers))
        dropped = len(chain.blocks) - len(ready)
        if not ready:
            if dropped:
                self.kv_prefetch.note_waste(dropped)
            return "done"
        if not self.block_pool.can_allocate(len(ready)):
            return "retry"
        ids = self.block_pool.allocate(len(ready))
        try:
            idx = jnp.asarray(ids, jnp.int32)
            for layer_idx, (k_cache, v_cache) in enumerate(self.kv_caches):
                # Wire sides may be dense or native int8 tuples (and a
                # mixed fleet can interleave both within one chain):
                # stack_wire_blocks normalizes to THIS pool's format, so
                # int8 chains land in an int8 pool without a quantize
                # pass and bf16 pools dequantize at import.
                pool_q = kv_quant.is_quantized(k_cache)
                k_host = kv_quant.stack_wire_blocks(
                    [b[layer_idx][0] for _, b in ready], pool_q
                )
                v_host = kv_quant.stack_wire_blocks(
                    [b[layer_idx][1] for _, b in ready], pool_q
                )
                self.kv_caches[layer_idx] = (
                    kv_quant.set_blocks(k_cache, idx, k_host),
                    kv_quant.set_blocks(v_cache, idx, v_host),
                )
        except Exception:
            # Malformed store entry (wrong layer count / block shape):
            # free and degrade — unreferenced cache lines are harmless.
            self.block_pool.free(ids)
            self.kv_prefetch.note_waste(len(chain.blocks))
            logger.exception("prefetched block import failed; continuing")
            return "drop"
        for (digest, _), block in zip(ready, ids):
            self.block_pool.adopt_prefix_block(digest, block)
        # Freeing parks the adopted blocks in the reclaimable cached-free
        # tier; match_prefix re-claims them by digest.
        self.block_pool.free(ids)
        self.kv_prefetch.note_hit(len(ids))
        if dropped:
            self.kv_prefetch.note_waste(dropped)
        self.remote_prefix_blocks_fetched += len(ids)
        return "done"

    def flush_prefix_imports(self, timeout: float = 10.0) -> None:
        """Block until in-flight prefetches have resolved (tests;
        graceful drain).  The actual cache import still happens on the
        step thread at the next dispatch."""
        if self.kv_prefetch is not None:
            self.kv_prefetch.wait_idle(timeout)

    def fetch_remote_prefix(self, seq, prefix_blocks, cached_len):
        """Scheduler remote_prefix_cb.  With the async transfer plane
        (cache.remote_prefetch, default): NEVER blocks — completed
        prefetches were already imported into the prefix cache before
        schedule() ran (so the match_prefix result this call receives
        already includes them), and all this does is make sure a fetch is
        in flight for any remaining miss tail (admission covers the
        common case; this covers recompute-preemption prompt growth).
        With remote_prefetch=False: the legacy synchronous per-block GET
        loop, kept as the A/B baseline."""
        client = self.offload.remote_client
        if client is None:
            return prefix_blocks, cached_len
        if self.kv_prefetch is not None:
            if not self.kv_prefetch.has_job(seq.seq_id):
                self._submit_prefix_prefetch(seq)
            return prefix_blocks, cached_len
        return self._fetch_remote_prefix_sync(seq, prefix_blocks, cached_len)

    # stackcheck: boundary=step-thread reason=legacy sync fetch path, only reachable with cache.remote_prefetch=False (--no-remote-prefetch A/B baseline); blocking GETs inside the scheduler callback are its documented contract
    def _fetch_remote_prefix_sync(self, seq, prefix_blocks, cached_len):
        """Legacy synchronous remote-prefix extension: one blocking GET
        per block INSIDE the scheduler callback.  Returns the possibly
        extended (prefix_blocks, cached_len); never raises — a store
        outage (or a malformed entry) degrades to local-only prefill."""
        client = self.offload.remote_client
        bs = self.block_pool.block_size
        hashes = self._seq_prefix_hashes(seq)
        start = cached_len // bs
        if start >= len(hashes):
            return prefix_blocks, cached_len
        # Defense in depth: clamp the extension so >= 1 prompt token is
        # ALWAYS left to prefill.  Today the fetch keys come from
        # prefix_block_hashes, which stops at num_prompt_tokens - 1 like
        # the local match_prefix, so this bound is not reachable through
        # the local chain — but nothing else pins the invariant that a
        # PrefillPlan must have num_new_tokens >= 1 (a full-prompt
        # extension would leave no valid last-token logits to sample),
        # and the hash helper is shared code a refactor could loosen.
        # Enforce it where the extension happens, not by construction
        # three modules away.
        max_ext_blocks = (seq.num_prompt_tokens - 1 - cached_len) // bs
        if max_ext_blocks <= 0:
            return prefix_blocks, cached_len
        # Don't fetch what admission can't hold: the whole remaining
        # prompt (fetched + still-to-prefill blocks) must fit, or the
        # scheduler would free the fetch and re-issue it every step.
        remaining_blocks = -(
            -(seq.num_prompt_tokens - cached_len) // bs
        )
        if not self.block_pool.can_allocate(remaining_blocks):
            return prefix_blocks, cached_len
        key_prefix = self._px_key_prefix()
        try:
            fetched: List = []
            for digest in hashes[start : start + max_ext_blocks]:
                entry = client.get_blocks(key_prefix + digest.hex())
                if entry is None:
                    break
                layers, _ = entry
                fetched.append(layers)
            if not fetched or not self.block_pool.can_allocate(len(fetched)):
                return prefix_blocks, cached_len
        except Exception:
            # Includes a store outage mid-chain: degrade, never kill the
            # step loop.
            logger.exception("remote prefix fetch failed; continuing local")
            return prefix_blocks, cached_len
        ids = self.block_pool.allocate(len(fetched))
        try:
            idx = jnp.asarray(ids, jnp.int32)
            for layer_idx, (k_cache, v_cache) in enumerate(self.kv_caches):
                pool_q = kv_quant.is_quantized(k_cache)
                k_host = kv_quant.stack_wire_blocks(
                    [f[layer_idx][0] for f in fetched], pool_q
                )
                v_host = kv_quant.stack_wire_blocks(
                    [f[layer_idx][1] for f in fetched], pool_q
                )
                self.kv_caches[layer_idx] = (
                    kv_quant.set_blocks(k_cache, idx, k_host),
                    kv_quant.set_blocks(v_cache, idx, v_host),
                )
        except Exception:
            # A malformed entry (wrong layer count / block shape — a store
            # polluted by another binary version) fails here: return the
            # blocks to the pool (partially written cache lines are
            # unreferenced until a block_table points at them, so freeing
            # is safe) and degrade to local-only prefill.
            self.block_pool.free(ids)
            logger.exception("remote prefix copy-in failed; continuing local")
            return prefix_blocks, cached_len
        self.remote_prefix_blocks_fetched += len(ids)
        return prefix_blocks + ids, cached_len + len(ids) * bs

    # stackcheck: thread=px-export
    def _export_worker(self) -> None:
        client = self.offload.remote_client
        while True:
            item = self._export_queue.get()
            if item is None:
                self._export_queue.task_done()
                return
            # Coalesce the queue backlog into ONE batched MPUT: a final
            # prefill enqueues its whole chain at once, so the common
            # case is one round-trip per exported prompt instead of one
            # per block.
            batch = [item]
            while len(batch) < 32:
                try:
                    nxt = self._export_queue.get_nowait()
                except Exception:
                    break
                if nxt is None:
                    self._export_queue.task_done()
                    self._export_queue.put(None)  # re-arm shutdown
                    break
                batch.append(nxt)
            try:
                client.mput_blocks(batch)
                self.remote_prefix_blocks_exported += len(batch)
            except Exception:
                logger.exception("remote prefix export failed; continuing")
            finally:
                for _ in batch:
                    self._export_queue.task_done()

    def close(self, timeout: float = 10.0) -> None:
        """Release every worker thread and socket the engine owns (the
        SC6 lifecycle contract; AsyncEngine.close and the follower loop
        land here).  Producers stop before their sinks: the prefetch
        fetchers and the offload stager both write into the
        HostOffloadManager (`insert_fetched`/`insert_saved`), and the
        export worker reads `offload.remote_client` — so fetchers and
        writers retire first, the manager flushes its deleter queue
        second, and the remote client's sockets close last.

        `timeout` is a shared budget across ALL stages, not per stage:
        with the kvserver hung at drain time, per-stage budgets would
        stack to minutes while helm's drainGraceSeconds is 30 — the
        kubelet would SIGKILL the pod mid-close."""
        deadline = time.monotonic() + timeout

        def left() -> float:
            return max(0.0, deadline - time.monotonic())

        with self._export_lock:
            export_thread, self._export_thread = self._export_thread, None
        if export_thread is not None:
            import queue as _queue

            self.flush_prefix_exports(left())
            try:
                # The queue is bounded and full exactly when the writer
                # is wedged mid-RPC against a hung store — an untimed
                # put would block past the deadline this method promises.
                self._export_queue.put(None, timeout=left())
            except _queue.Full:
                logger.warning(
                    "prefix-export writer still wedged at shutdown; "
                    "abandoning its daemon thread past the close deadline"
                )
            export_thread.join(left())
        if self.kv_prefetch is not None:
            self.kv_prefetch.shutdown(left())
        if self._offload_stager is not None:
            self._offload_stager.shutdown(left())
        self.offload.close(left())
        if self.offload.remote_client is not None:
            self.offload.remote_client.close()

    def flush_prefix_exports(self, timeout: float = 10.0) -> None:
        """Block until queued exports have been written (tests; graceful
        shutdown).  No-op when nothing was ever exported."""
        if self._export_queue is None:
            return
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self._export_queue.unfinished_tasks == 0:
                return
            time.sleep(0.01)

    # -- disaggregated prefill/decode handoff (docs/engine.md) -------------

    def cache_ns_of(self, adapter: Optional[str]) -> int:
        """The prefix-cache namespace a request with this adapter would
        hash under (mirrors add_request; 0 = base model)."""
        if adapter and self.lora_registry is not None:
            return self.lora_registry.namespace_of(adapter)
        return 0

    def handoff_token(
        self, prompt_token_ids: List[int], cache_ns: int = 0
    ) -> dict:
        """The prefill-phase handoff token: the prompt's prefix hash
        chain (store content keys) + length, plus the model-identity key
        prefix so a decode peer can verify it shares weights before
        waiting on imports.  Called off the event loop (the first
        ``_px_key_prefix`` pays a small D2H for the weight fingerprint).

        ``exported`` reports whether this engine CAN have exported the
        chain (store + prefill role) — the router's fused fallback keys
        on it; it is not a per-block store receipt (content-keyed PUTs
        are idempotent and a racing eviction shows up as a decode-side
        miss, which degrades safely)."""
        hashes = prefix_block_hashes(
            prompt_token_ids, self.block_pool.block_size, namespace=cache_ns
        )
        key_prefix = self._px_key_prefix()
        return {
            "chain": [key_prefix + d.hex() for d in hashes],
            "chain_len": len(hashes),
            "chain_tail": hashes[-1].hex() if hashes else "",
            "prompt_tokens": len(prompt_token_ids),
            "block_size": self.block_pool.block_size,
            "px": key_prefix,
            "exported": bool(
                self._exports and self.offload.remote_client is not None
            ),
        }

    def wait_handoff_prefix(
        self,
        prompt_token_ids: List[int],
        cache_ns: int,
        handoff: dict,
        timeout: float,
    ) -> str:
        """Decode-phase handoff consumption: make sure a prefetch of the
        prompt's chain is in flight and wait (bounded) for the FETCH to
        complete into host staging.  A staged chain is imported by the
        step thread at the top of its next dispatch, BEFORE any
        ``schedule()`` runs — so admitting the request after this
        returns "hit" guarantees its first schedule serves the whole
        prompt from the prefix cache and decode never executes prompt
        tokens.  (Waiting for the cache import itself would deadlock an
        idle engine: the import point only runs when there is work.)

        Runs on an asyncio.to_thread worker: the polling sleep below
        never touches the event loop or the step thread.  Returns
        "hit" (chain staged or already cached), "partial", "miss", or
        "disabled" (no prefetch plane / imports off / model-identity
        mismatch).
        """
        if self.kv_prefetch is None or not self._imports:
            return "disabled"
        hashes = prefix_block_hashes(
            prompt_token_ids, self.block_pool.block_size, namespace=cache_ns
        )
        if not hashes:
            return "hit"  # prompt shorter than one block: nothing to import
        peer_px = handoff.get("px")
        if peer_px and peer_px != self._px_key_prefix():
            # Different weights/namespace: the peer's exports can never
            # match our keys — admit local-only immediately.
            return "disabled"
        start = self.block_pool.count_cached_prefix(hashes)
        if start >= len(hashes):
            return "hit"
        key_prefix = self._px_key_prefix()
        sid = f"handoff-{hashes[-1].hex()[:16]}"
        submitted = self.kv_prefetch.submit_chain(
            sid,
            [key_prefix + d.hex() for d in hashes[start:]],
            hashes[start:],
            start,
        )
        if not submitted:
            # A same-head job is already in flight (same-prompt burst,
            # or this handoff raced a sibling): we own no job to watch,
            # so poll coverage on a shortened budget.
            timeout = min(timeout, 0.5)
        deadline = time.time() + max(0.0, timeout)
        grace_until: Optional[float] = None
        while time.time() < deadline:
            covered = self.block_pool.count_cached_prefix(hashes)
            if covered >= len(hashes):
                return "hit"
            status = self.kv_prefetch.chain_status(sid)
            if status == "done":
                # Staged in host buffers: the step thread's dispatch
                # drains it into the prefix cache before the request's
                # first schedule() — that IS the hit.
                return "hit"
            if status == "absent" and submitted:
                # Our own fetch settled without a result (store miss
                # completes empty and pops the job) OR the step thread
                # already consumed it.  One short grace window for the
                # coverage check above to observe a consumed import,
                # then classify instead of burning the budget.  Without
                # `submitted` there never was a job under our sid — the
                # sibling that owns the in-flight twin fetch is what we
                # are waiting on, so poll coverage to the (shortened)
                # budget instead of grace-breaking immediately.
                if grace_until is None:
                    grace_until = time.time() + 0.1
                elif time.time() >= grace_until:
                    break
            time.sleep(0.005)
        covered = self.block_pool.count_cached_prefix(hashes)
        if covered >= len(hashes):
            return "hit"
        return "partial" if covered > start else "miss"

    # stackcheck: allow=SC201 reason=the TTL-keyed export dedupe gates only store-side export traffic; the local plan never reads it, and duplicate exports across replicas are idempotent content-keyed PUTs
    def _export_prefix_blocks(self, seq) -> None:
        """After a final prefill: push every full prompt block to the
        shared store under its chain-hash content key, so peer engines
        (and this one, post-restart) can import instead of recomputing.

        The device->host gather happens here (the step thread owns the
        kv_caches references — they are donated next step); the store RPCs
        happen on a writer thread so server latency never becomes serving
        latency.  Dedupe entries expire after a TTL so a store-side
        eviction doesn't permanently end sharing."""
        client = self.offload.remote_client
        if client is None:
            return
        bs = self.block_pool.block_size
        hashes = self._seq_prefix_hashes(seq)
        now = time.time()
        todo = [
            (i, digest)
            for i, digest in enumerate(hashes)
            if self._exported_hashes.get(digest, 0.0) < now
        ]
        if not todo:
            return
        with self._export_lock:
            if self._export_thread is None:
                import queue as _queue

                self._export_queue = _queue.Queue(maxsize=64)
                self._export_thread = threading.Thread(
                    target=self._export_worker, name="px-export", daemon=True
                )
                self._export_thread.start()
        ids = jnp.asarray(
            [seq.block_table[i] for i, _ in todo], jnp.int32
        )
        try:
            # One device->host gather per layer for all exported blocks.
            # Quantized wire: the int8 cache's (data, scale) tuples go
            # out natively (serde v2; the client's probe falls back to a
            # dense v1 encode against a legacy store).  Dense wire:
            # int8 caches dequantize here so any peer can import.
            host_layers = [
                (kv_quant.to_host_side(kv_quant.gather_blocks_wire(
                    k_cache, ids, self._wire_quantized)),
                 kv_quant.to_host_side(kv_quant.gather_blocks_wire(
                    v_cache, ids, self._wire_quantized)))
                for k_cache, v_cache in self.kv_caches
            ]
        except Exception:
            logger.exception("prefix export gather failed; continuing")
            return

        def _row(side, row):
            if kv_quant.is_quantized(side):
                return (side[0][row : row + 1], side[1][row : row + 1])
            return side[row : row + 1]

        key_prefix = self._px_key_prefix()
        for row, (_, digest) in enumerate(todo):
            layers = [
                (_row(k, row), _row(v, row)) for k, v in host_layers
            ]
            try:
                self._export_queue.put_nowait(
                    (key_prefix + digest.hex(), layers, bs)
                )
            except Exception:
                return  # writer backlogged: drop the rest of this export
            self._exported_hashes[digest] = now + self._export_ttl_s
        while len(self._exported_hashes) > 65536:
            self._exported_hashes.popitem(last=False)

    def _run_prefill(self, plan: PrefillPlan, rec=None) -> List[StepOutput]:
        seq = plan.seq
        logits, want_plp = self._launch_prefill(plan, rec)
        if not plan.is_final:
            # Non-final chunk of a long prompt: KV is written, but the
            # logits are mid-prompt — nothing to sample yet.
            return []
        outputs = self._finalize_final_prefill(seq, logits, rec=rec)
        if want_plp and outputs and seq.prompt_lp is not None:
            # Attach the assembled per-position entries to the request's
            # FIRST token event (position 0 has no predictor -> None).
            n = seq.echo_prompt_len
            entries: List = [(None, None)]
            for pos in range(1, n):
                entries.append(seq.prompt_lp.get(pos, (None, None)))
            outputs[0].prompt_logprobs = entries
        return outputs

    def _launch_prefill(self, plan: PrefillPlan, rec=None):
        """One chunk's dedicated prefill program, built and launched: (its
        last valid row's logits [V], still on the device; whether prompt
        logprobs were asked for, which are then read back here)."""
        seq = plan.seq
        if self.obs.enabled and seq.first_scheduled_time is None:
            seq.first_scheduled_time = time.time()
            self.obs.on_first_scheduled(seq, seq.first_scheduled_time)
        with self.obs.phase("build", rec):
            kwargs, want_plp = self._prefill_kwargs(plan)
        with self.obs.phase("launch", rec):
            out = self._prefill_fn(
                self.params, kv_caches=self.kv_caches, **kwargs
            )
        if self._routing_names:
            # Left on the device: a chunk's counts are read once a later
            # collect has waited for a program launched after it.
            *out, routing = out
            self._routing_pending.append((rec, routing))
        if want_plp:
            logits, self.kv_caches, plp = out
            with self.obs.phase("collect", rec, family=False):
                self._collect_prompt_logprobs(seq, plan, plp)
        else:
            logits, self.kv_caches = out
        return logits, want_plp

    def _prefill_kwargs(self, plan: PrefillPlan):
        """(the dedicated prefill executable's keyword arguments, on the
        device; whether it also returns prompt logprobs).  Everything built
        here goes over in ONE vector (step_programs.prefill_program)."""
        seq = plan.seq
        parts = list(self._prefill_plan_arrays(plan))
        kwargs = {}
        if self.lora_registry is not None:
            kwargs["lora"] = self.lora_registry.params

        sp = seq.sampling_params
        want_plp = sp.echo and sp.logprobs
        if want_plp:
            # Target of row t (absolute position cached_len+t) is the NEXT
            # prompt token; rows at/past the prompt tail target 0 (their
            # entries are discarded below).
            targets = np.zeros((plan.bucket_len,), np.int32)
            m = min(
                plan.num_new_tokens,
                len(seq.prompt_token_ids) - plan.cached_len - 1,
            )
            if m > 0:
                targets[:m] = seq.prompt_token_ids[
                    plan.cached_len + 1 : plan.cached_len + 1 + m
                ]
            parts.append(targets)
            # Fixed k: prompt_topk is a STATIC jit arg, and a
            # per-request value would compile a fresh prefill variant
            # per (bucket, k) pair; _collect_prompt_logprobs slices to
            # the request's k host-side.
            kwargs["prompt_topk"] = 20
        scalars = {
            "cached_len": plan.cached_len, "valid_len": plan.num_new_tokens,
            "adapter_idx": seq.adapter_idx, "state_slot": plan.state_slot,
            "state_from": plan.state_from,
            "snapshot_slot": plan.snapshot_slot,
            "snapshot_len": plan.snapshot_len,
        }
        parts.append(np.array(
            [scalars[name] for name in self._prefill_scalars], np.int32
        ))
        self.unchained_dispatches += 1
        kwargs.update(self._stage({"chunk": np.concatenate(parts)}))
        return kwargs, want_plp

    def _collect_prompt_logprobs(self, seq, plan, plp) -> None:
        """Stitch one chunk's (target_lp, top_ids, top_lps) into the
        sequence's absolute-position map (chunked prefill delivers the
        prompt in pieces)."""
        tlp = np.asarray(plp[0])
        top_ids = np.asarray(plp[1])
        top_lps = np.asarray(plp[2])
        if seq.prompt_lp is None:
            seq.prompt_lp = {}
        k = min(seq.sampling_params.top_logprobs or 0, top_ids.shape[1])
        for t in range(plan.num_new_tokens):
            pos = plan.cached_len + t + 1  # entry FOR the predicted token
            if pos >= seq.echo_prompt_len:
                break
            pairs = (
                [(int(top_ids[t, j]), float(top_lps[t, j])) for j in range(k)]
                if k else None
            )
            seq.prompt_lp[pos] = (float(tlp[t]), pairs)

    def _prefill_plan_arrays(self, plan: PrefillPlan):
        """Padded (tokens [T], new_block_ids [T//bs], prefix_ids [pmax])
        host arrays for one PrefillPlan — shared by the dedicated prefill
        executable and the mixed step's chunk segment, so the plan->array
        layout can never diverge between them."""
        seq = plan.seq
        bs = self.block_pool.block_size
        T = plan.bucket_len
        new_tokens = seq.prompt_token_ids[
            plan.cached_len : plan.cached_len + plan.num_new_tokens
        ]
        tokens = np.zeros((T,), np.int32)
        tokens[: len(new_tokens)] = new_tokens
        new_block_ids = np.zeros((T // bs,), np.int32)
        new_block_ids[: len(plan.new_block_ids)] = plan.new_block_ids
        pmax = max(self._bmax, 1)
        prefix_ids = np.zeros((pmax,), np.int32)
        prefix_ids[: len(plan.prefix_block_ids)] = plan.prefix_block_ids
        return tokens, new_block_ids, prefix_ids

    def _finalize_final_prefill(
        self, seq: Sequence, last_logits, step_ordinal: Optional[int] = None,
        rec=None,
    ) -> List[StepOutput]:
        """Shared tail of every FINAL prefill — dedicated executable,
        mixed-step chunk, or a mixed WINDOW's final chunk (which passes
        ``step_ordinal``: the first token must burn the PRNG ordinal of
        the K=1 step its iteration corresponds to, not the post-window
        counter): prefix export, the max_tokens==0 scoring sentinel, or
        sampling the request's first token from the last valid row's
        logits [V]."""
        if self._exports:
            with self.obs.phase("sample", rec, family=False):
                self._export_prefix_blocks(seq)
        if seq.sampling_params.max_tokens == 0:
            # Scoring-only request (echo+logprobs with max_tokens=0):
            # nothing to sample — finish at prefill with the text-free
            # sentinel the server already understands.
            seq.first_token_time = time.time()
            self._finish_seq_now(seq, FinishReason.LENGTH)
            return [StepOutput(
                seq_id=seq.seq_id,
                new_token_id=-1,
                finished=True,
                finish_reason=FinishReason.LENGTH,
                num_prompt_tokens=seq.num_prompt_tokens,
                num_output_tokens=0,
            )]
        token_ids, logprob_info = self._sample_batch(
            last_logits[None, :], [seq], step_ordinal=step_ordinal, rec=rec
        )
        with self.obs.phase("sample", rec, family=False):
            return self._append_and_check(
                [seq], token_ids, first_token=True, logprob_info=logprob_info
            )

    def _decode_batch_arrays(self, seqs: List[Sequence], S: int):
        """Padded decode-row host arrays ([S] x5 + [S, bmax] tables) for
        one single-token step — shared by the synchronous decode path,
        the pipeline's batch rebuild, and the mixed step's decode
        segment.  Padding rows keep null block 0 / ctx 0 (masked)."""
        bs = self.block_pool.block_size
        tokens = np.zeros((S,), np.int32)
        positions = np.zeros((S,), np.int32)
        block_tables = np.zeros((S, self._bmax), np.int32)
        ctx_lens = np.zeros((S,), np.int32)
        slot_blocks = np.zeros((S,), np.int32)
        slot_offsets = np.zeros((S,), np.int32)
        for i, seq in enumerate(seqs):
            pos = seq.num_tokens - 1
            tokens[i] = seq.last_token_id
            positions[i] = pos
            # No walk over the context: the table as the sequence keeps it.
            table = seq.block_table_array()[: self._bmax]
            block_tables[i, : len(table)] = table
            ctx_lens[i] = pos + 1
            slot_blocks[i] = seq.block_table[pos // bs]
            slot_offsets[i] = pos % bs
        if self._kv_group_blocks > 1:
            self._count_kv_groups(block_tables, ctx_lens)
        return tokens, positions, block_tables, ctx_lens, slot_blocks, slot_offsets

    def _record_kv_groups(self, rec) -> None:
        """The batch just built, on its flight record."""
        if rec is not None and self._kv_group_blocks > 1:
            rec.kv_groups, rec.kv_groups_coalesced = self._last_kv_groups

    def _count_kv_groups(self, block_tables, ctx_lens) -> None:
        """Groups of ``_kv_group_blocks`` table entries the live rows hold,
        and those the paged decode kernel fetches in one DMA a side
        (``whole_groups``, its own rule): one compare over the batch's
        tables."""
        from production_stack_tpu.engine.ops.pallas.paged_attention import (
            whole_groups,
        )

        R, bs = self._kv_group_blocks, self.block_pool.block_size
        whole = whole_groups(
            block_tables[:, : block_tables.shape[1] // R * R], R, xp=np)
        groups = -(-ctx_lens // (bs * R))
        live = np.arange(whole.shape[1]) < groups[:, None]
        pair = int(groups.sum()), int((whole & live).sum())
        self.paged_decode_groups["total"] += pair[0]
        self.paged_decode_groups["coalesced"] += pair[1]
        self._last_kv_groups = pair

    def _decode_bucket(self, n: int) -> int:
        """Static decode batch sizes: the smallest bucket of the
        (dp, 2dp, 4dp, ...) set holding ``n`` rows, capped at
        max_num_seqs.  Replaces the old unconditional max_num_seqs
        padding — a single-sequence stream stops paying full-batch
        attention, KV scatter and sampling; the executable inventory
        grows by one decode variant per power of two."""
        b = max(1, self.config.parallel.data_parallel)
        while b < n:
            b *= 2
        return min(b, self._smax)

    # stackcheck: root=step-thread
    @_enclosed("mixed")
    def _run_mixed(self, step_plan, rec=None) -> List[StepOutput]:
        """One fused step over the packed [decode bucket + chunk bucket]
        token batch (a StepPlan with both ``decode`` and
        ``prefill_chunk`` set): every running sequence decodes exactly
        as in _run_decode (paged attention, then the full host sampling
        surface), and the head waiting sequence's prefill chunk rides
        along paying only its attention/KV-write cost — the projection
        and MLP weight streaming is shared.  Only a FINAL chunk samples
        the prefill tail row (mid-prompt logits have no consumer),
        mirroring _run_prefill's chunked contract."""
        t_start = time.time()
        plan = step_plan.prefill_chunk
        seq = plan.seq
        seqs = step_plan.decode.seqs
        if self.obs.enabled and seq.first_scheduled_time is None:
            seq.first_scheduled_time = t_start
            self.obs.on_first_scheduled(seq, t_start)
        with self.obs.phase("build", rec):
            kwargs = self._mixed_kwargs(plan, seqs)
        self._note_decode_launch()
        with self.obs.phase("launch", rec):
            logits, self.kv_caches = self._mixed_fn(
                self.params, kv_caches=self.kv_caches, **kwargs
            )
        self.prefill_chunk_tokens += plan.num_new_tokens
        # Decode rows first (logits rows 0..len(seqs)-1).
        token_ids, logprob_info = self._sample_batch(
            logits[: len(seqs)], seqs, rec=rec
        )
        with self.obs.phase("sample", rec, family=False):
            outputs = self._append_and_check(
                seqs, token_ids, first_token=False,
                logprob_info=logprob_info,
            )
        if plan.is_final:
            # Row -1 is the chunk's last valid token: the request's
            # first sampled token (same finalize contract as the
            # dedicated prefill executable).
            outputs.extend(
                self._finalize_final_prefill(seq, logits[-1], rec=rec)
            )
        return outputs

    def _mixed_kwargs(self, plan: PrefillPlan, seqs: List[Sequence]) -> Dict:
        """The fused mixed step's keyword arguments, on the device."""
        seq = plan.seq
        S = self._decode_bucket(len(seqs))
        T = plan.bucket_len
        (tokens, positions, block_tables, ctx_lens, slot_blocks,
         slot_offsets) = self._decode_batch_arrays(seqs, S)
        pf_tokens, pf_new_blocks, pf_prefix = self._prefill_plan_arrays(plan)

        batch_spec = shardings_lib.decode_batch_spec()
        lora_kwargs = {}
        if self.lora_registry is not None:
            # Not _lora_kwargs: the mixed row layout is [S decode rows +
            # T chunk rows sharing ONE adapter], not a per-seq width
            # repeat, and the packed axis is replicated (dp/sp are gated
            # to 1 for mixed), so P() is the right spec.
            adapter_idx = np.zeros((S + T,), np.int32)
            for i, s in enumerate(seqs):
                adapter_idx[i] = s.adapter_idx
            adapter_idx[S:] = seq.adapter_idx
            lora_kwargs = {
                "lora": self.lora_registry.params,
                "adapter_idx": self._put(adapter_idx, P()),
            }

        return dict(
            dec_tokens=self._put(tokens, batch_spec),
            dec_positions=self._put(positions, batch_spec),
            dec_block_tables=self._put(block_tables, P(AXES.DP, None)),
            dec_ctx_lens=self._put(ctx_lens, batch_spec),
            dec_slot_block_ids=self._put(slot_blocks, batch_spec),
            dec_slot_offsets=self._put(slot_offsets, batch_spec),
            pf_tokens=self._put(pf_tokens, P(AXES.SP)),
            pf_cached_len=jnp.int32(plan.cached_len),
            pf_prefix_block_ids=self._put(pf_prefix, P(AXES.SP)),
            pf_new_block_ids=self._put(pf_new_blocks, P(AXES.SP)),
            pf_valid_len=jnp.int32(plan.num_new_tokens),
            **lora_kwargs,
        )

    def _run_decode(self, plan: DecodePlan, rec=None) -> List[StepOutput]:
        seqs = plan.seqs
        S = self._decode_bucket(len(seqs))

        with self.obs.phase("build", rec):
            batch_spec = shardings_lib.decode_batch_spec()
            (tokens, positions, block_tables, ctx_lens, slot_blocks,
             slot_offsets) = self._decode_batch_arrays(seqs, S)
            kwargs = dict(
                tokens=self._put(tokens, batch_spec),
                positions=self._put(positions, batch_spec),
                block_tables=self._put(block_tables, P(AXES.DP, None)),
                ctx_lens=self._put(ctx_lens, batch_spec),
                slot_block_ids=self._put(slot_blocks, batch_spec),
                slot_offsets=self._put(slot_offsets, batch_spec),
                **self._lora_kwargs(seqs, S, batch_spec),
                **self._state_kwargs(seqs, S),
            )
        self._note_decode_launch()
        with self.obs.phase("launch", rec):
            logits, self.kv_caches = self._decode_fn(
                self.params, kv_caches=self.kv_caches, **kwargs
            )
        token_ids, logprob_info = self._sample_batch(
            logits[: len(seqs)], seqs, rec=rec
        )
        with self.obs.phase("sample", rec, family=False):
            return self._append_and_check(
                seqs, token_ids, first_token=False,
                logprob_info=logprob_info,
            )

    def _lora_kwargs(self, seqs: List[Sequence], S: int, batch_spec) -> Dict:
        """Decode-call LoRA kwargs: each sequence's adapter on its batch
        row — the ONE place the adapter row layout lives."""
        if self.lora_registry is None:
            return {}
        adapter_idx = np.zeros((S,), np.int32)
        for i, seq in enumerate(seqs):
            adapter_idx[i] = seq.adapter_idx
        return {
            "lora": self.lora_registry.params,
            "adapter_idx": self._put(adapter_idx, batch_spec),
        }

    # Device-resident history window the FUSED drafter matches against
    # (a fixed [S, H] carry in the window scan — compile-time constant so
    # the executable inventory never keys on context length): the
    # lookup is O(S*H) per scan iteration and recent repetition
    # dominates prompt-lookup hits.
    _SPEC_HIST_WINDOW = 128

    # Model-drafter skip-prime chain length: windows that may chain off
    # one in-graph causal prime of the draft cache before the next prime
    # (the prime costs S x (H-1) draft rows; chained windows extend the
    # compact draft cache in place, so amortizing it over a chain keeps
    # the drafter's per-token overhead near the (D+1)-row floor).  Also
    # sizes the per-row draft-pool capacity: H + chain x
    # window_max_tokens compact slots, rounded up to whole blocks.
    _DRAFT_PRIME_CHAIN = 8

    def _sampling_arrays(self, seqs: List[Sequence], S: int):
        """Padded per-sequence sampling parameter arrays [S], from what
        each request's admission left (_row_static)."""
        packed = np.zeros((5, S), np.int32)
        f32 = packed.view(np.float32)
        f32[1, len(seqs):] = 1.0  # top_p of a padding row
        for i, seq in enumerate(seqs):
            col, seeded, _, _ = self._row_static(seq)
            packed[:, i] = col[:5]
            if not seeded:
                packed[4, i] = i
        return f32[0], f32[1], packed[2], f32[3], packed[4]

    def _sample_batch(
        self, logits: jax.Array, seqs: List[Sequence],
        step_ordinal: Optional[int] = None, rec=None,
    ):
        """Returns (token_ids, logprob_info) where logprob_info is a list of
        None or (chosen_logprob, [(token_id, logprob), ...]) per sequence.
        ``step_ordinal`` overrides the live step counter for the PRNG key
        (a mixed window's final-chunk first token samples with the
        ordinal of the iteration it landed in — the counter has already
        advanced past the whole window by collect time).  ``rec``: the
        flight record of the synchronous dispatch this sampling belongs
        to, which its launch / read-back / post-processing spans join."""
        with self.obs.phase("launch", rec):
            logits, out = self._sample_launch(logits, seqs, step_ordinal)
        with self.obs.phase("collect", rec, family=False):
            token_ids = [int(t) for t in np.asarray(out[: len(seqs)])]
        with self.obs.phase("sample", rec, family=False):
            return self._sample_finish(logits, out, seqs, token_ids)

    def _sample_launch(self, logits: jax.Array, seqs: List[Sequence],
                       step_ordinal: Optional[int]):
        """Penalties and biases applied and the sampler launched: (the
        adjusted logits, the still-in-flight sampled ids [S])."""
        S = logits.shape[0]
        pad = S - len(seqs)

        # Presence/frequency/repetition penalties (OpenAI + vLLM surface):
        # only pay the scatter-adds when some live sequence uses them.
        use_rep = any(
            s.sampling_params.repetition_penalty != 1.0 for s in seqs
        )
        if use_rep or any(
            (s.sampling_params.presence_penalty
             or s.sampling_params.frequency_penalty)
            and s.output_token_ids
            for s in seqs
        ):
            max_len = max(
                max((len(s.output_token_ids) for s in seqs), default=1), 1
            )
            # Bucket L so XLA compiles O(log) penalty variants, not one per
            # generated length.
            L = 64
            while L < max_len:
                L *= 2
            out_tokens = np.full((S, L), -1, np.int32)
            for i, s in enumerate(seqs):
                ids = s.output_token_ids[-L:]
                out_tokens[i, : len(ids)] = ids
            presence = np.array(
                [s.sampling_params.presence_penalty for s in seqs] + [0.0] * pad,
                np.float32,
            )
            frequency = np.array(
                [s.sampling_params.frequency_penalty for s in seqs] + [0.0] * pad,
                np.float32,
            )
            kwargs = {}
            if use_rep:
                # repetition_penalty covers prompt AND generated tokens
                # (HF/vLLM semantics) — needs the full context ids.
                max_ctx = max(len(s.all_token_ids) for s in seqs)
                Lc = 64
                while Lc < max_ctx:
                    Lc *= 2
                ctx_tokens = np.full((S, Lc), -1, np.int32)
                for i, s in enumerate(seqs):
                    ids = s.all_token_ids[-Lc:]
                    ctx_tokens[i, : len(ids)] = ids
                kwargs = {
                    "repetition": jnp.asarray(np.array(
                        [s.sampling_params.repetition_penalty
                         for s in seqs] + [1.0] * pad, np.float32,
                    )),
                    "ctx_tokens": jnp.asarray(ctx_tokens),
                }
            logits = self._penalties_fn(
                logits,
                jnp.asarray(out_tokens),
                jnp.asarray(presence),
                jnp.asarray(frequency),
                **kwargs,
            )

        # OpenAI logit_bias: sparse per-request token biases, applied to
        # the raw logits (so greedy argmax shifts too).  The dense [S, V]
        # device array is cached across steps keyed on the batch's bias
        # composition — a biased request decodes many tokens against the
        # same bias, and rebuilding/transferring it per token would
        # dominate the step.
        def _min_tokens_banned(s) -> tuple:
            """Token ids suppressed while min_tokens is unmet — the
            sequence's stop set (_stop_set_ids, shared with the window's
            device stop-mask so host and device semantics cannot
            drift)."""
            if s.sampling_params.min_tokens <= len(s.output_token_ids):
                return ()
            return self._stop_set_ids(s)

        min_tok_banned = [_min_tokens_banned(s) for s in seqs]
        if any(s.sampling_params.logit_bias for s in seqs) or any(
            min_tok_banned
        ):
            V = logits.shape[-1]
            # The cache key includes the min_tokens ban set, which flips
            # exactly once per sequence (unmet -> met): two rebuilds per
            # affected batch composition, not one per step.
            key = (S, V) + tuple(
                (i,
                 tuple(sorted((s.sampling_params.logit_bias or {}).items())),
                 min_tok_banned[i])
                for i, s in enumerate(seqs)
            )
            cached = getattr(self, "_bias_cache", None)
            if cached is None or cached[0] != key:
                bias = np.zeros((S, V), np.float32)
                for i, s in enumerate(seqs):
                    for tid, b in (s.sampling_params.logit_bias or {}).items():
                        t = int(tid)
                        if 0 <= t < V:
                            bias[i, t] = float(b)
                    for t in min_tok_banned[i]:
                        if 0 <= t < V:
                            bias[i, t] = -1e9
                self._bias_cache = (key, jnp.asarray(bias))
            logits = logits + self._bias_cache[1]

        temps, top_ps, top_ks, min_ps, seeds = self._sampling_arrays(seqs, S)
        ordinal = (
            self._step_counter if step_ordinal is None else step_ordinal
        )
        step_key = jax.random.PRNGKey(self.config.seed + ordinal)
        self._count_sample_dispatch(
            sampling_lib.needs_sort(temps, top_ps, top_ks)
        )
        return logits, self._sample_fn(
            logits,
            jnp.asarray(temps),
            jnp.asarray(top_ps),
            jnp.asarray(top_ks),
            step_key,
            jnp.asarray(seeds),
            min_p=jnp.asarray(min_ps),
        )

    def _sample_finish(self, logits: jax.Array, out, seqs: List[Sequence],
                       token_ids: List[int]):
        """Host post-processing of the read-back ids: guided overrides
        and the logprobs gather."""
        pad = logits.shape[0] - len(seqs)
        any_logprobs = any(s.sampling_params.logprobs for s in seqs)
        if any(s.guide is not None for s in seqs):
            token_ids = self._guided_override(logits, seqs, token_ids)
            if any_logprobs:
                # `out` feeds the logprobs gather below; keep it in sync
                # with the constrained choices.
                out = jnp.asarray(np.array(token_ids + [0] * pad, np.int32))

        logprob_info: List = [None] * len(seqs)
        if any_logprobs:
            # Fixed k = the API clamp (20): a per-batch k would compile a
            # fresh XLA variant inside the step thread for every new value,
            # stalling all in-flight sequences; per-sequence counts are
            # sliced on the host below.
            chosen, top_ids, top_logps = self._logprobs_fn(
                logits, out, k=20
            )
            chosen = np.asarray(chosen)
            top_ids = np.asarray(top_ids)
            top_logps = np.asarray(top_logps)
            for i, s in enumerate(seqs):
                if s.sampling_params.logprobs:
                    n = s.sampling_params.top_logprobs
                    logprob_info[i] = (
                        float(chosen[i]),
                        [
                            (int(top_ids[i, j]), float(top_logps[i, j]))
                            for j in range(n)
                        ],
                    )
        return token_ids, logprob_info

    def _guided_override(
        self, logits: jax.Array, seqs: List[Sequence], token_ids: List[int]
    ) -> List[int]:
        """Constrained choice for guided sequences (engine/guided.py):
        the device-sampled token is kept when the automaton accepts it;
        otherwise candidates are validated host-side in logit order and
        the best valid token replaces it.  A completed JSON value forces
        EOS."""
        from production_stack_tpu.engine.guided import TokenTextCache

        if self._token_texts is None:
            self._token_texts = TokenTextCache(self.tokenizer)
        cache = self._token_texts
        eos = self.tokenizer.eos_token_id or 0
        out = list(token_ids)
        for i, seq in enumerate(seqs):
            guide = seq.guide
            if guide is None:
                continue
            if guide.done:
                out[i] = eos
                continue
            # Budget-aware closing: when the remaining token budget nears
            # the bytes needed to close the JSON, admit only
            # closure-reducing tokens so the value completes instead of
            # truncating (tokens are >=1 byte, so cost+margin tokens
            # always suffice).
            sp = seq.sampling_params
            remaining = min(
                sp.max_tokens - seq.num_generated,
                # max_model_len can bind first (long prompts).
                self.config.scheduler.max_model_len - seq.num_tokens,
            )
            guide.closing = remaining <= guide.closure_cost() + 4
            # Fast path: the unconstrained choice is usually valid.  An
            # EOS pick at a may-finish point is a valid CHOICE to end
            # (root-position scalars: "42" may end or grow another digit;
            # finalize collapses the script so done holds).
            if out[i] == eos and guide.may_finish():
                guide.finalize()
                continue
            fast_bytes = cache.text(out[i]).encode()
            st = guide.try_token(fast_bytes)
            if st is not None and out[i] != eos:
                guide.accept(st, fast_bytes)
                continue
            row = np.asarray(logits[i])  # [V] fp32, post bias/penalties
            # Validate candidates in descending-logit order; with
            # temperature, sample among the first few valid candidates.
            # Valid tokens live at the top of the distribution in
            # practice, so scan an argpartitioned top slice first and only
            # pay the full sort if it comes up empty.
            want = 1 if sp.temperature <= 0 else 8
            valid: List = []
            for scope in (64, len(row)):
                if scope >= len(row):
                    order = np.argsort(-row)
                else:
                    top = np.argpartition(-row, scope)[:scope]
                    order = top[np.argsort(-row[top])]
                for tid in order:
                    tid = int(tid)
                    if tid == eos:
                        if guide.may_finish():
                            valid.append((tid, "FINISH"))
                            if len(valid) >= want:
                                break
                        continue
                    st = guide.try_token(cache.text(tid).encode())
                    if st is not None:
                        valid.append((tid, st))
                        if len(valid) >= want:
                            break
                if valid:
                    break
            if not valid:
                # No token makes progress (pathological vocab): end the
                # request rather than loop.
                logger.warning(
                    "guided decoding: no valid continuation for %s",
                    seq.seq_id,
                )
                out[i] = eos
                continue
            if len(valid) == 1:
                tid, st = valid[0]
            else:
                lps = np.array([row[t] for t, _ in valid], np.float64)
                lps = lps / max(sp.temperature, 1e-5)
                p = np.exp(lps - lps.max())
                p /= p.sum()
                rng = np.random.default_rng(
                    # Per-sequence stream: co-batched guided choices (the
                    # n>1 fan-out) must not collapse to the same picks.
                    (sp.seed if sp.seed is not None else 0) * 1000003
                    + self._step_counter * 31
                    + zlib.crc32(seq.seq_id.encode())
                )
                tid, st = valid[int(rng.choice(len(valid), p=p))]
            if st == "FINISH":
                guide.finalize()
            else:
                guide.accept(st, cache.text(tid).encode())
            out[i] = tid
        return out

    def _append_and_check(
        self,
        seqs: List[Sequence],
        token_ids: List[int],
        first_token: bool,
        logprob_info: Optional[List] = None,
    ) -> List[StepOutput]:
        outputs: List[StepOutput] = []
        now = time.time()
        if logprob_info is None:
            logprob_info = [None] * len(seqs)
        for seq, token_id, lp in zip(seqs, token_ids, logprob_info):
            sp = seq.sampling_params
            # vLLM stop_token_ids semantics: the token ends generation
            # like EOS but is never appended/streamed (the server treats
            # the -1 sentinel as text-free).
            stop_hit = bool(sp.stop_token_ids and token_id in sp.stop_token_ids)
            if not stop_hit:
                seq.output_token_ids.append(token_id)
                self.total_generated_tokens += 1
                if getattr(seq, "_min_tok_pending", False) and (
                    len(seq.output_token_ids) >= sp.min_tokens
                ):
                    # The ONE boundary crossing: the cached host-state
                    # verdict never needs re-reading after this.
                    seq._min_tok_pending = False
            if seq.first_token_time is None:
                seq.first_token_time = now
                if self.obs.enabled:
                    self.obs.on_first_token(seq, now)
            if self.obs.enabled:
                # The gaps between tokens are taken where the record that
                # carries them closes (obs: _on_record_close), not here.
                seq.last_token_time = now
            if stop_hit:
                finish = FinishReason.STOP
                token_id = -1
                lp = None
            else:
                finish = self._check_finish(seq, token_id)
            if finish is not None:
                finish = self._finish_seq_now(seq, finish)
            outputs.append(
                StepOutput(
                    seq_id=seq.seq_id,
                    new_token_id=token_id,
                    finished=finish is not None,
                    finish_reason=finish,
                    num_prompt_tokens=seq.num_prompt_tokens,
                    num_output_tokens=seq.num_generated,
                    logprob=lp[0] if lp else None,
                    top_logprobs=lp[1] if lp else None,
                )
            )
        return outputs

    def _finish_seq_now(
        self, seq: Sequence, reason: FinishReason
    ) -> FinishReason:
        """The single finish protocol: scheduler release + prefix-cache
        registration, offload cleanup, counters, registry removal.
        Returns the final reason (guided re-validation may rewrite it);
        callers must surface the returned value, not their local one."""
        rf = seq.sampling_params.response_format
        if (
            reason == FinishReason.STOP
            and seq.guide is not None
            and (rf == "json_object" or isinstance(rf, dict))
        ):
            # The automaton validated per-token text from decode([id]);
            # re-validate the assembled text, which is the ground truth
            # the client receives (for json_schema, against the schema).
            import json as _json

            try:
                obj = _json.loads(self.tokenizer.decode(seq.output_token_ids))
                if isinstance(rf, dict):
                    from production_stack_tpu.engine.guided_schema import (
                        validate_instance,
                    )

                    if not validate_instance(rf.get("schema") or {}, obj):
                        raise ValueError("schema mismatch")
            except Exception:
                logger.warning(
                    "guided json output failed final parse for %s",
                    seq.seq_id,
                )
                reason = FinishReason.GUIDED_INVALID
        seq.finish_reason = reason
        self.scheduler.finish_seq(seq)
        if self.kv_prefetch is not None:
            # Release any still-staged prefetch for this request (its
            # prefix is registered locally now anyway).
            self.kv_prefetch.cancel(seq.seq_id)
        if self._offload_stager is not None:
            self._offload_stager.discard(seq.seq_id)
        self.offload.discard(seq.seq_id)
        self.total_finished += 1
        self._seqs.pop(seq.seq_id, None)
        self.obs.on_finish(seq)
        return reason

    def _check_finish(self, seq: Sequence, token_id: int) -> Optional[FinishReason]:
        sp = seq.sampling_params
        if (
            not sp.ignore_eos
            and self.tokenizer.eos_token_id is not None
            and token_id == self.tokenizer.eos_token_id
        ):
            return FinishReason.STOP
        if seq.num_generated >= sp.max_tokens:
            return FinishReason.LENGTH
        if seq.num_tokens >= self.config.scheduler.max_model_len:
            return FinishReason.LENGTH
        return None

    # -- preemption hook (called by scheduler via engine wrapper) ----------

    def offload_seq_blocks(self, seq: Sequence, block_ids: List[int]) -> bool:
        """Scheduler offload_cb.  Async plane (default with a remote
        store): dispatch the device-side gather (a fresh buffer — the
        pool reuses the source blocks immediately) and hand the D2H wait
        + host insert + optional remote PUT to the stager's writer
        thread; the step thread never blocks.  A True return only
        promises a BEST-EFFORT snapshot: if staging later fails (host
        pool full), restore finds nothing and falls back to recompute —
        the same contract a failed synchronous save has.  Legacy mode
        blocks through offload.save as before."""
        if self._offload_stager is None or self.offload.capacity_bytes <= 0:
            return self._offload_seq_blocks_sync(seq, block_ids)
        if not block_ids:
            return False
        if not self._offload_stager.reserve(seq.seq_id):
            return False  # slot busy: recompute fallback (double-buffer)
        t0 = time.time()
        try:
            ids = jnp.asarray(block_ids, jnp.int32)
            # Quantized wire: the gather stays int8 (data, scale) — half
            # the D2H bytes, and restore adopts the tuples untransformed.
            device_layers = [
                (kv_quant.gather_blocks_wire(k_cache, ids, self._wire_quantized),
                 kv_quant.gather_blocks_wire(v_cache, ids, self._wire_quantized))
                for k_cache, v_cache in self.kv_caches
            ]
        except Exception:
            self._offload_stager.release(seq.seq_id)
            logger.exception("offload gather dispatch failed; recomputing")
            return False
        self._offload_stager.commit(
            seq.seq_id, device_layers, seq.num_tokens
        )
        if self._pending:
            # A window (or step) is still in flight: this D2H gather
            # dispatch rode the alternate stream UNDER its compute — an
            # avoided stall the overlap metric makes visible.
            self.window_transfer_overlap_s += time.time() - t0
        if self.obs.enabled:
            # Step-thread cost only (gather DISPATCH): the D2H wait lives
            # in tpu:offload_stage_seconds, observed by the writer.
            self.obs.tracer.add_span(
                seq.seq_id, "engine.kv_offload", t0, time.time(),
                blocks=len(block_ids), staged=True,
            )
        return True

    # stackcheck: boundary=step-thread reason=legacy sync offload path, only reachable with cache.remote_prefetch=False; the inline D2H wait + remote PUT is its documented A/B-baseline contract
    def _offload_seq_blocks_sync(
        self, seq: Sequence, block_ids: List[int]
    ) -> bool:
        if not self.obs.enabled:
            return self.offload.save(
                seq.seq_id, self.kv_caches, block_ids,
                num_tokens=seq.num_tokens,
            )
        t0 = time.time()
        saved = self.offload.save(
            seq.seq_id, self.kv_caches, block_ids, num_tokens=seq.num_tokens
        )
        if saved:
            # Preemption paging on the request's timeline: the span names
            # why this request's decode stalled.
            self.obs.tracer.add_span(
                seq.seq_id, "engine.kv_offload", t0, time.time(),
                blocks=len(block_ids),
            )
        return saved

    # -- metrics -----------------------------------------------------------

    def _duty_cycle(self) -> float:
        """Fraction of the trailing window spent inside step()."""
        now = time.time()
        cutoff = now - self._busy_window_s
        busy = sum(
            # Clip a step straddling the window edge to the in-window part.
            min(d, t - cutoff)
            for (t, d) in self._busy_window
            if t > cutoff
        )
        return min(1.0, busy / self._busy_window_s)

    def embed(self, prompt_token_ids: List[int]) -> np.ndarray:
        """Normalized mean-pooled embedding of a prompt (llama.encode).
        Pads to the nearest prefill bucket so repeat calls reuse one XLA
        program per bucket."""
        if not hasattr(self.model, "encode"):
            raise ValueError(
                f"model {self.config.model.name!r} has no encode path"
            )
        if not prompt_token_ids:
            # An embedding of the pad token would be silent garbage.
            raise ValueError("input produced no tokens")
        n = len(prompt_token_ids)
        max_len = min(
            self.config.scheduler.prefill_buckets[-1],
            self.config.scheduler.max_model_len,
        )
        if n > max_len:
            # Silent truncation would return an embedding of a prefix while
            # reporting the full token count; fail like completions does.
            raise ValueError(
                f"input is {n} tokens; the embedding path supports up to "
                f"{max_len}"
            )
        bucket = next(
            b for b in self.config.scheduler.prefill_buckets if b >= n
        )
        ids = (list(prompt_token_ids) + [0] * bucket)[:bucket]
        if self._encode_fn is None:
            self._encode_fn = self._jit(
                "encode_fn",
                partial(self.model.encode, cfg=self.config.model,
                        mesh=self.mesh),
            )
        out = self._encode_fn(
            self.params,
            tokens=jnp.asarray(ids, jnp.int32),
            valid_len=jnp.int32(n),
        )
        return np.asarray(out)

    def encode_max_len(self) -> int:
        """Longest input (tokens) the embedding path accepts — the bound
        both ``embed`` and ``encode_batch`` validate against, exposed so
        the API layer can reject over-long inputs before queueing."""
        return min(
            self.config.scheduler.prefill_buckets[-1],
            self.config.scheduler.max_model_len,
        )

    def encode_batch(self, batch_token_ids: List[List[int]]) -> np.ndarray:
        """Batched embeddings: ONE [B, T]-bucketed llama.encode_batch
        dispatch for up to encode_batch_buckets[-1] texts (B pads to an
        encode-batch bucket, T to a prefill bucket), replacing B serial
        ``embed`` round-trips.  Vectors agree with per-text ``embed``
        output to float32 rounding, not bit for bit: the vmap-batched
        program sums in another order (one ulp apart on the installed
        XLA).  STEP-THREAD-only
        caller in production (the EncodeBatcher) — this touches the
        device."""
        if not hasattr(self.model, "encode_batch"):
            raise ValueError(
                f"model {self.config.model.name!r} has no batched encode path"
            )
        if not batch_token_ids:
            raise ValueError("encode_batch needs at least one input")
        sched = self.config.scheduler
        if len(batch_token_ids) > sched.encode_batch_buckets[-1]:
            raise ValueError(
                f"encode_batch of {len(batch_token_ids)} texts exceeds the "
                f"largest encode batch bucket "
                f"({sched.encode_batch_buckets[-1]})"
            )
        max_len = self.encode_max_len()
        lens = []
        for ids in batch_token_ids:
            if not ids:
                raise ValueError("input produced no tokens")
            if len(ids) > max_len:
                raise ValueError(
                    f"input is {len(ids)} tokens; the embedding path "
                    f"supports up to {max_len}"
                )
            lens.append(len(ids))
        b_bucket = next(
            b for b in sched.encode_batch_buckets
            if b >= len(batch_token_ids)
        )
        t_bucket = next(b for b in sched.prefill_buckets if b >= max(lens))
        rows = [
            (list(ids) + [0] * t_bucket)[:t_bucket]
            for ids in batch_token_ids
        ]
        # Padding rows carry valid_len 0: the masked mean-pool yields a
        # zero vector we slice away below.
        rows += [[0] * t_bucket] * (b_bucket - len(rows))
        valid = lens + [0] * (b_bucket - len(lens))
        if self._encode_batch_fn is None:
            self._encode_batch_fn = self._jit(
                "encode_batch_fn",
                partial(self.model.encode_batch, cfg=self.config.model,
                        mesh=self.mesh),
            )
        t0 = time.time()
        out = self._encode_batch_fn(
            self.params,
            tokens=jnp.asarray(rows, jnp.int32),
            valid_lens=jnp.asarray(valid, jnp.int32),
        )
        vectors = np.asarray(out)[: len(batch_token_ids)]
        # Step-thread-only writers (see counter init): one batch per
        # observation, wall seconds include the device sync above.
        self.encode_texts_total += len(batch_token_ids)
        self.encode_batch_size_hist.observe(float(len(batch_token_ids)))
        self.encode_seconds_hist.observe(time.time() - t0)
        return vectors

    # -- multi-LoRA admin (engine/lora.py) ---------------------------------

    def _require_lora(self):
        if self.lora_registry is None:
            raise ValueError("engine started with max_loras=0")
        return self.lora_registry

    def load_lora(self, name: str, layer_factors, rank: int,
                  alpha: float = 16.0) -> int:
        return self._require_lora().load(name, layer_factors, rank, alpha)

    def load_lora_from_path(self, name: str, path: str,
                            alpha: float = 16.0) -> int:
        from production_stack_tpu.engine.lora import load_peft_safetensors

        factors, rank = load_peft_safetensors(
            path, self.config.model.num_layers
        )
        return self.load_lora(name, factors, rank, alpha)

    def unload_lora(self, name: str) -> None:
        self._require_lora().unload(name)

    def loaded_adapters(self) -> List[str]:
        return [] if self.lora_registry is None else self.lora_registry.loaded()

    def compile_inventory(self) -> Dict[str, int]:
        """Config-derived expected executable counts per jit family — the
        denominator of /debug/compiles' warmup coverage report.  These are
        upper bounds on steady-state inventory (a deployment that never
        sees a shape never compiles it); the report's point is naming the
        families still cold after boot, not exact equality."""
        sched = self.config.scheduler
        dp = max(1, self.config.parallel.data_parallel)
        decode_buckets = 1
        b = dp
        while b < sched.max_num_seqs:
            b *= 2
            decode_buckets += 1
        inv: Dict[str, int] = {
            "prefill_fn": len(sched.prefill_buckets),
            "decode_fn": decode_buckets,
            "sample_fn": decode_buckets,
        }
        if sched.mixed_enabled:
            # One fused variant per (decode bucket, chunk bucket) pair.
            inv["mixed_fn"] = decode_buckets * len(sched.prefill_chunk_buckets)
        if sched.window_steps > 1:
            inv["window_fn"] = decode_buckets
            if sched.spec_window_enabled:
                # The model drafter's do_prime static arg doubles the
                # spec-window inventory (prime / skip-prime variants
                # per decode bucket).
                inv["spec_window_fn"] = decode_buckets * (
                    2 if sched.spec_drafter == "model" else 1
                )
            if sched.mixed_window:
                # Chunk schedules pad to pow2 scan lengths <= decode_window.
                scan_variants, n = 1, 1
                while n < sched.decode_window:
                    n *= 2
                    scan_variants += 1
                inv["mixed_window_fn"] = decode_buckets * scan_variants
        if sched.encode_lane_enabled and hasattr(self.model, "encode_batch"):
            # One executable per (B bucket, T bucket) encode-batch shape.
            inv["encode_batch_fn"] = (
                len(sched.encode_batch_buckets) * len(sched.prefill_buckets)
            )
        return inv

    def compiles_payload(self) -> Dict:
        """GET /debug/compiles: per-executable compile events (most
        expensive first) + the warmup coverage join — compiled-shape
        counts per jit family against the config-derived inventory."""
        rows = self.obs.compile_tracker.snapshot()
        by_family: Dict[str, int] = {}
        for r in rows:
            fam = r["executable"].split("[", 1)[0]
            by_family[fam] = by_family.get(fam, 0) + 1
        inventory = self.compile_inventory()
        coverage = {
            fam: {"compiled": by_family.get(fam, 0), "expected": exp}
            for fam, exp in inventory.items()
        }
        return {
            "enabled": self.obs.enabled,
            "device": self.device_report(),
            "persistent_cache": compile_cache_report(),
            "compiled_shapes": self.obs.compile_tracker.compiled_shapes(),
            "compile_seconds": round(
                self.obs.compile_tracker.compile_seconds(), 6
            ),
            "executables": rows,
            "coverage": coverage,
        }

    def stats(self) -> Dict[str, float]:
        return {
            "num_requests_running": self.scheduler.num_running,
            "num_requests_waiting": self.scheduler.num_waiting,
            "hbm_kv_usage_perc": self.block_pool.usage,
            "prefix_cache_hit_rate": self.block_pool.prefix_hit_rate,
            # Prefix-cache truth counters/size (token granularity): the
            # router's fleet popularity view scrapes these to compute the
            # fleet-wide hit rate and to reconcile its prefix-owner map
            # against reality (a restarted engine's cache is empty no
            # matter what the router's routing history says).
            "prefix_cache_hit_tokens": self.block_pool.hit_tokens,
            "prefix_cache_query_tokens": self.block_pool.query_tokens,
            "prefix_cache_blocks": self.block_pool.num_cached_blocks,
            "host_kv_usage_perc": self.offload.usage,
            "duty_cycle": self._duty_cycle(),
            "total_prompt_tokens": self.total_prompt_tokens,
            "total_generated_tokens": self.total_generated_tokens,
            "total_finished": self.total_finished,
            # Prompt tokens prefilled inside fused mixed steps (decode
            # never stalled for them), and the subset that rode a mixed
            # K-step window.
            "prefill_chunk_tokens": self.prefill_chunk_tokens,
            "mixed_window_chunk_tokens": self.mixed_window_chunk_tokens,
            # Transfer seconds issued while the device was busy (H2D
            # chunk staging for chained windows + D2H offload gathers
            # under an in-flight scan) — stalls overlap dispatch avoided.
            "window_transfer_overlap_seconds": self.window_transfer_overlap_s,
            "num_preemptions": self.scheduler.num_preemptions,
            # Overload protection: structured 429s issued by bounded
            # admission, and requests shed/aborted on an expired client
            # deadline (docs/robustness.md).
            "admission_rejected_total": self.admission_rejected,
            "deadline_expired_total": (
                self.deadline_expired + self.deadline_expired_admission
            ),
            "queued_prompt_tokens": self.scheduler.queued_prompt_tokens,
            # Encode lane (batched embed/rerank/score): texts encoded via
            # the [B, T]-bucketed batch path and the current queue depth
            # the batcher is carrying (docs/engine.md).
            "encode_texts_total": self.encode_texts_total,
            "encode_queue_depth": self.encode_queue_depth,
            # Mean host-side serialization per decode step (ms): time the
            # device sat idle between decode steps.  ≈0 when the lookahead
            # pipeline is feeding the device ahead of collection.
            "decode_host_gap_ms": (
                1000.0 * self._gap_total_s / self._gap_steps
                if self._gap_steps else 0.0
            ),
            "loaded_loras": len(self.loaded_adapters()),
            "remote_prefix_blocks_fetched": self.remote_prefix_blocks_fetched,
            "remote_prefix_blocks_exported": self.remote_prefix_blocks_exported,
            # Disaggregated serving: prefill-phase primes served, and
            # decode-phase handoff prefetch outcomes (docs/engine.md).
            "disagg_prefill_primes": self.disagg_prefill_primes,
            "disagg_handoff_hits": self.disagg_handoff_hits,
            "disagg_handoff_misses": self.disagg_handoff_misses,
            # Async KV transfer plane (kv/prefetch.py): blocks imported /
            # dropped by admission-time prefetch, and fetches in flight.
            "kv_prefetch_hit": (
                self.kv_prefetch.hit_blocks if self.kv_prefetch else 0
            ),
            "kv_prefetch_waste": (
                self.kv_prefetch.waste_blocks if self.kv_prefetch else 0
            ),
            "kv_prefetch_inflight": (
                self.kv_prefetch.inflight if self.kv_prefetch else 0
            ),
            "spec_tokens_drafted": self.spec_tokens_drafted,
            "spec_tokens_accepted": self.spec_tokens_accepted,
            # Fused speculative windows: per-window outcome split
            # (accepted / rejected draft tokens, wasted emissions), the
            # configured proposal source ("" when none — keys the
            # drafter label on tpu:spec_window_tokens_total), and scan
            # seconds attributed to the model drafter's forwards.
            "spec_window_tokens": dict(self.spec_window_tokens),
            "spec_drafter": self.config.scheduler.spec_drafter or "",
            "spec_draft_fraction_seconds": self.spec_draft_fraction_s,
            # K-step decode windows: single-step fallbacks by reason and
            # emitted-but-undeliverable window tokens.
            "multistep_fallback": dict(self.multistep_fallback),
            "multistep_wasted_tokens": self.multistep_wasted_tokens,
            "prefill_attn_tiles": dict(self.prefill_attn_tiles),
            # Zero where every page travels alone.
            "paged_decode_groups": dict(self.paged_decode_groups),
            # Positions the decode rows attended, a row a layer a planned
            # step, by the layers' kind (a window layer: at most a window).
            "attn_positions": dict(self.attn_positions),
            # Routed experts held by share: (row, expert) pairs by where
            # they fell, and held experts with at least one row a layer
            # and step (zero for a model that routes nothing).
            "moe_assignments": dict(self.moe_assignments),
            "moe_experts_touched": self.moe_experts_touched,
            "moe_zero_assigned": self.moe_zero_assigned,
            # Several residual streams' mixing matrices (zero for a model
            # with one stream): entries the clamp changed, entries seen,
            # the largest |row sum - 1| after the last normalisation.
            "mhc_clamped": self.mhc_clamped,
            "mhc_entries": self.mhc_entries,
            "mhc_sinkhorn_err": self.mhc_sinkhorn_err,
            # Selective state-space layers (zero without): the largest |h|
            # any dispatch left in a slot, the largest step size.
            "ssm_state_absmax": self.ssm_state_absmax,
            "ssm_dt_max": self.ssm_dt_max,
            # Delta-rule layers under a decay a head (zero without): the
            # largest |S| any dispatch left in a slot, the largest beta.
            "gdn_state_absmax": self.gdn_state_absmax,
            "gdn_beta_max": self.gdn_beta_max,
            # The state pool of a model with recurrent state (zero without).
            **self._state_stats(),
            # Dispatched programs that sample, and those among them whose
            # rows make the sampler sort the vocabulary.
            "sample_dispatches": self.sample_dispatches,
            "sample_sorted_dispatches": self.sample_sorted_dispatches,
            # Blocks of sequences' prefix chains hashed, and the part of
            # them hashed on the step thread (the rest: by the handler).
            "prefix_chain_blocks": (
                self.block_pool.chain_blocks_hashed
                + self.prefix_chain_handler_blocks
            ),
            "prefix_chain_step_blocks": self.block_pool.chain_blocks_hashed,
            "step_build_transfers": self.build_transfers,
            "step_unchained_dispatches": self.unchained_dispatches,
            "step_dispatch_behind": dict(self.dispatch_behind),
            "step_dispatch_behind_declined": dict(
                self.dispatch_behind_declined
            ),
            # Quantized KV tiering plane: bytes crossing each tier
            # boundary by wire format, and snapshot serde versions put
            # on the kvserver wire (tpu:kv_wire_bytes_total /
            # tpu:kv_snapshot_format_total).
            "kv_wire_bytes": self.kv_wire_stats.wire_bytes(),
            "kv_snapshot_format": self.kv_wire_stats.snapshot_formats(),
            # XLA compile events (obs/compile_tracker.py): seconds spent
            # compiling, per executable shape key, plus the distinct-shape
            # count (tpu:compile_seconds_total / tpu:compiled_shapes).
            "compile_seconds": self.obs.compile_tracker.seconds_by_executable(),
            "compiled_shapes": self.obs.compile_tracker.compiled_shapes(),
            # Trace-ring byte-bound evictions (tpu:obs_trace_dropped_total).
            "obs_trace_dropped": self.obs.tracer.dropped,
        }
