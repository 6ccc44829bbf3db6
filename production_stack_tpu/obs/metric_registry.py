"""Metric-family registry: the single source of truth for every
``tpu:`` / ``tpu_router:`` Prometheus family the stack exports.

SURVEY §4 makes the stats plane the backbone of the serving stack: the
router's scraper, the Grafana dashboard, the prometheus-adapter/HPA rule
and the CI fake engine all key off these names.  Before this registry the
contract lived in four places at once (vocabulary.py, fake_engine.py,
observability/tpu-dashboard.json, docs/observability.md) and drifted
silently — a renamed family broke dashboards without failing any test.

stackcheck rule family SC3 (tools/stackcheck/rules_metrics.py) verifies
this file against all four surfaces in both directions on every CI run:
every entry must have an emit site, and every emitted/plotted/documented
family must have an entry.  **Adding a metric family starts HERE** — see
docs/static-analysis.md#adding-a-metric-family for the checklist.

Entry shape (plain literals only; stackcheck AST-parses this file and
never imports it, so the registry stays loadable in a bare CI venv):

    "tpu:family_name": {
        "kind": "gauge" | "counter" | "histogram",
        "layer": "engine" | "router",
        "mirrors": (surfaces that MUST reference the family:
                    "fake_engine", "dashboard", "docs"),
        "source_name": optional — the literal as written in source when
                    it differs from the exposition name (prometheus_client
                    exposes Counter("x") as x_total),
        "labels": optional tuple of label names,
        "help": one-line meaning,
    }

Histogram families expose ``<name>_bucket/_sum/_count`` series; the
registry stores the base name and stackcheck normalizes suffixes.
"""

from __future__ import annotations

REGISTRY = {
    # -- engine gauges (vocabulary.py, rendered by api_server + fake) ------
    "tpu:num_requests_running": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Sequences in the running (decode) set",
    },
    "tpu:num_requests_waiting": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Waiting + preempted queue depth (the HPA signal)",
    },
    "tpu:hbm_kv_usage_perc": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Paged-KV HBM pool usage (0-1)",
    },
    "tpu:prefix_cache_hit_rate": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Rolling prefix-cache hit rate (0-1)",
    },
    "tpu:host_kv_usage_perc": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Host-DRAM offload tier usage (0-1)",
    },
    "tpu:duty_cycle": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Busy fraction of the trailing window (TPU utilization)",
    },
    "tpu:decode_host_gap_ms": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Mean host-side serialization per decode step (pipeline "
                "health; ~0 with one-step lookahead active)",
    },
    "tpu:loaded_loras": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Live LoRA adapters",
    },
    "tpu:kv_prefetch_inflight": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Remote chain fetches currently in flight",
    },
    "tpu:last_step_age_seconds": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Step-loop watchdog age; /health fails past step_watchdog_s",
    },
    "tpu:queued_prompt_tokens": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Prompt tokens held by waiting+preempted sequences (the "
                "bound admission enforces)",
    },
    "tpu:prefix_cache_blocks": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Content-valid blocks resident in the prefix cache (the "
                "truth the router's popularity view reconciles its "
                "owner map against: a collapse to ~0 means the engine "
                "restarted and its cache is empty)",
    },
    # -- engine counters ---------------------------------------------------
    "tpu:prefix_cache_hit_tokens_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Prompt tokens served from the prefix cache since boot "
                "(fleet KV hit rate = sum hit / sum query across "
                "backends — the BASELINE.md north-star metric)",
    },
    "tpu:prefix_cache_query_tokens_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Prompt tokens queried against the prefix cache since "
                "boot (the hit-rate denominator)",
    },
    "tpu:total_prompt_tokens": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Prompt tokens prefilled since boot",
    },
    "tpu:total_generated_tokens": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Tokens sampled since boot",
    },
    "tpu:total_finished_requests": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Requests finished since boot",
    },
    "tpu:num_preemptions": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Sequences preempted under pool pressure",
    },
    "tpu:remote_prefix_blocks_fetched": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "KV blocks imported from the shared store (disagg_role)",
    },
    "tpu:remote_prefix_blocks_exported": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "KV blocks pushed to the shared store (disagg_role)",
    },
    "tpu:disagg_prefill_primes_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Disagg prefill-phase prime completions served (prefill "
                "ran, chain eagerly exported, handoff token returned)",
    },
    "tpu:disagg_handoff_hits_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Decode-phase handoffs whose prefetched chain covered the "
                "whole prompt (decode executed no prompt tokens)",
    },
    "tpu:disagg_handoff_misses_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Decode-phase handoffs admitted without a full chain "
                "import (prefill recomputed locally — in-place fused "
                "fallback)",
    },
    "tpu:spec_tokens_drafted": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "N-gram speculative tokens drafted",
    },
    "tpu:spec_tokens_accepted": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "N-gram speculative tokens accepted (rate = accepted/drafted)",
    },
    "tpu:prefill_chunk_tokens": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Prompt tokens prefilled inside fused mixed steps",
    },
    "tpu:kv_prefetch_hit": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "KV blocks imported into the prefix cache by remote prefetch",
    },
    "tpu:kv_prefetch_waste": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Prefetched KV blocks fetched then dropped unused",
    },
    "tpu:admission_rejected_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Structured 429s from bounded admission",
    },
    "tpu:deadline_expired_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Requests shed/aborted on an expired client deadline",
    },
    "tpu:multistep_fallback_total": {
        "kind": "counter", "layer": "engine", "labels": ("reason",),
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "K-step decode-window dispatches dropped to single-step "
                "because a co-scheduled request needed host-sampled "
                "features (reason: logprobs | logit_bias | guided) or "
                "because a waiting prompt forced K=1 admission cadence "
                "and the mixed K-step window could not serve it — split "
                "by WHY the mixed window declined (reason: "
                "pool_pressure — the KV pool could not hold the chunk; "
                "waiting_head — residual decline, e.g. mixed windows off "
                "or an unpackable final chunk; draft_pool — the draft "
                "model's dedicated KV pool could not cover the batch, so "
                "the window ran plain instead of speculative)",
    },
    "tpu:mixed_window_chunk_tokens_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Prompt tokens whose prefill chunks rode the "
                "device-resident decode scan (mixed K-step windows) — "
                "the subset of tpu:prefill_chunk_tokens that paid no "
                "per-chunk host round-trip",
    },
    "tpu:mixed_window_prompts_per_window": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Distinct prompts whose chunks rode each mixed K-step "
                "window (packed multi-prompt windows) — mass above "
                "bucket 1 is queue depth converted into device "
                "utilization",
    },
    "tpu:decode_window_steps": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Steps each pure-decode window was planned to run: the "
                "configured window (--decode-window), or fewer where the "
                "window ended with a row's last token or as soon as it "
                "covered the step thread's own pass",
    },
    "tpu:encode_texts_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Texts embedded via the step thread's [B, T]-bucketed "
                "encode batches (the batched embed/rerank/score lane)",
    },
    "tpu:encode_queue_depth": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Texts queued for the encode lane (the depth encode "
                "admission bounds; the step thread drains one batch per "
                "window boundary while generation is live)",
    },
    "tpu:encode_batch_size": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Actual texts per encode batch — mass near the top "
                "bucket means embed traffic is coalescing; mass stuck "
                "at 1 under load means it arrives too sparse to batch",
    },
    "tpu:encode_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Wall seconds per [B, T]-bucketed encode batch "
                "(dispatch through device sync, observed on the step "
                "thread)",
    },
    "tpu:window_transfer_overlap_seconds_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Seconds of host<->device transfer work issued while "
                "the device was busy with an in-flight window (H2D "
                "chunk staging for chained windows + D2H offload "
                "gathers under the scan) — stalls the overlap dispatch "
                "avoided",
    },
    "tpu:spec_window_tokens_total": {
        "kind": "counter", "layer": "engine", "labels": ("outcome", "drafter"),
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Fused speculative-window outcomes (outcome: accepted | "
                "rejected | wasted) — draft tokens the in-scan verifier "
                "accepted/rejected, and fused-window tokens emitted but "
                "undeliverable at collect — split by the proposal source "
                "(drafter: ngram — prompt-lookup from the carried history "
                "buffer; model — the tiny draft model riding the scan); "
                "acceptance rate per drafter is accepted / (accepted + "
                "rejected) over this family",
    },
    "tpu:spec_draft_fraction_seconds": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Scan wall-time attributed to the draft model's forwards "
                "inside fused speculative windows (static cost-model "
                "split of collect wait: draft rows x draft params vs "
                "verify rows x target params, prime amortized) — the "
                "speculation overhead the acceptance rate must pay for; "
                "the ngram drafter accrues zero here",
    },
    "tpu:multistep_wasted_tokens_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Window tokens emitted but undeliverable (abort / "
                "out-of-band finish mid-window; device stop-mask keeps "
                "ordinary stops at zero waste)",
    },
    "tpu:prefill_attn_tiles_total": {
        "kind": "counter", "layer": "engine", "labels": ("state",),
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Kv tiles of the prefill attention kernel's grid (the flash "
                "prefill kernel's, or the module's own: the latent prefill "
                "kernel's query-tile x key-stage pairs), per layer, "
                "over dispatched prefill chunks (state: live — computed; "
                "skipped — wholly masked, neither fetched nor computed: "
                "the block table's positions past cached_len, new keys past "
                "valid_len, padded query tiles); host arithmetic from "
                "each plan, no device read",
    },
    "tpu:moe_assignments_total": {
        "kind": "counter", "layer": "engine", "labels": ("where",),
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "(row, expert) pairs a routed model's router chose, over "
                "routed layers and steps, by where the expert lives "
                "(where: held — on this chip, computed; away — on "
                "another chip of the deployment this engine is a share "
                "of, left out); counted on the device, read back with "
                "the tokens; zero for a model that routes nothing",
    },
    "tpu:step_stall_total": {
        "kind": "counter", "layer": "engine", "labels": ("phase",),
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Step-thread phases that lasted over a second (every "
                "stream stood still as long); a WARNING line names each",
    },
    "tpu:sample_dispatch_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Dispatched programs that sample: decode window, mixed "
                "window, single step, prefill tail",
    },
    "tpu:sample_sorted_dispatch_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Of those, the ones in which a sampling row set top-k or "
                "top-p, so that every step sorts the vocabulary once",
    },
    "tpu:prefix_chain_blocks_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Blocks of sequences' prefix chains hashed, by the API "
                "server's handler or on the step thread: once a block in "
                "a sequence's life",
    },
    "tpu:prefix_chain_step_blocks_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Of those, the blocks hashed on the step thread, where "
                "the device may wait for the plan",
    },
    "tpu:step_build_transfers_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Host to device transfers the step thread started while "
                "building a dispatch from host state: one staged transfer "
                "a prefill or a rebuilt window, two where a row has "
                "penalties",
    },
    "tpu:step_unchained_dispatch_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Dispatches built from host state with nothing in flight "
                "to chain from: dedicated prefills and decode windows "
                "rebuilt after the running set changed",
    },
    "tpu:step_dispatch_behind_total": {
        "kind": "counter", "layer": "engine", "labels": ("kind",),
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Of the dispatches built from host state, those launched "
                "while another program was in flight (kind: prefill | "
                "window): an admission that did not empty the device",
    },
    "tpu:step_dispatch_behind_declined_total": {
        "kind": "counter", "layer": "engine", "labels": ("reason",),
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Admissions that met a program in flight and waited for "
                "its read-back all the same, by why: what the plan or the "
                "rows needed of collected state (reason: prompt_logprobs | "
                "max_tokens_0 | prefix_export | host_state | penalties | "
                "speculative | mixed_batch | preempted | block_fetch | "
                "no_free_row | no_free_blocks)",
    },
    "tpu:state_slots_in_use": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Slots of the state pool held: a live sequence's recurrent "
                "state over all linear-attention layers, or a snapshot of "
                "it at a block boundary; zero for a model without such state",
    },
    "tpu:state_snapshots_taken_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Snapshots of the recurrent state a prompt's last prefill "
                "chunk left at a block boundary, keyed by that block's "
                "digest in the prefix chain",
    },
    "tpu:state_resumes_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Admissions whose linear-attention layers started from a "
                "snapshot at the end of their cached prefix",
    },
    "tpu:state_resume_miss_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Admissions whose cached prefix was cut back to nothing "
                "for want of a snapshot: keys were cached, the state was not",
    },
    "tpu:state_recomputed_tokens_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Cached prompt tokens prefilled again between the snapshot "
                "an admission started from and the deepest cached block",
    },
    "tpu:paged_decode_groups_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("dashboard", "docs"),
        "help": "Groups of table entries the decode rows held, summed over "
                "decode batches built from host state, where one DMA of the "
                "paged decode kernel carries a group of small pages; zero "
                "where a page is a descriptor of its own",
    },
    "tpu:paged_decode_groups_coalesced_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("dashboard", "docs"),
        "help": "Those of tpu:paged_decode_groups_total that were ascending "
                "neighbours in the pool and went in one DMA a side; the "
                "ratio falling says the pool has fragmented back to pages",
    },
    "tpu:attn_positions_total": {
        "kind": "counter", "layer": "engine", "labels": ("kind",),
        "mirrors": ("docs",),
        "help": "Positions the decode rows attended, a row a layer a "
                "planned step, by the layers' kind (kind: full -- the whole "
                "context; window -- at most the kind's window, in pages or "
                "in a rolling buffer of the state pool); host arithmetic "
                "from each dispatch's contexts, no device read",
    },
    "tpu:moe_experts_touched_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Held experts with at least one row, summed over routed "
                "layers and decode steps: what a decode step streams of "
                "the expert stacks",
    },
    "tpu:moe_zero_assigned_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("docs",),
        "help": "Picks of live rows that named an identity (zero-compute) "
                "expert, over routed layers and steps: of "
                "tpu:moe_assignments_total's pairs those that computed "
                "nothing anywhere; counted on the device, read back with the "
                "tokens; zero for a router without identity experts",
    },
    "tpu:mhc_clamped_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Entries of the residual streams' mixing-matrix exponents "
                "that the clamp changed, over live rows and every mapping "
                "of a dispatch; counted on the device, read back with the "
                "tokens; zero for a model with one residual stream",
    },
    "tpu:mhc_entries_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Entries of those exponents seen (live rows x mappings x "
                "streams squared): the clamped share's denominator",
    },
    "tpu:mhc_sinkhorn_err": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Largest |row sum - 1| of a mixing matrix after its last "
                "Sinkhorn normalisation that any dispatch since boot has "
                "read (its columns sum to 1 by construction)",
    },
    "tpu:ssm_state_absmax": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Largest |h| that any dispatch since boot has left in a "
                "slot of a selective state-space layer's recurrent state "
                "(counted on the device, read back with the tokens); zero "
                "for a model without such layers",
    },
    "tpu:ssm_dt_max": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Largest step size (softplus of the projected step plus "
                "its bias) of a live token that any dispatch since boot "
                "has read in a selective state-space layer",
    },
    "tpu:gdn_state_absmax": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("docs",),
        "help": "Largest |S| that any dispatch since boot has left in a "
                "slot of a delta-rule layer's recurrent state under a decay "
                "a head (counted on the device, read back with the tokens); "
                "zero for a model without such layers",
    },
    "tpu:gdn_beta_max": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("docs",),
        "help": "Largest beta (2 sigmoid of the projected write strength) of "
                "a live token that any dispatch since boot has read in a "
                "delta-rule layer under a decay a head; at most 2",
    },
    "tpu:kv_wire_bytes_total": {
        "kind": "counter", "layer": "engine", "labels": ("tier", "format"),
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "KV snapshot bytes crossing a tier boundary (tier: host "
                "| remote) by wire representation (format: dense | int8 "
                "— int8 is the native quantized (data, scale) wire; a "
                "quantized-cache fleet stuck on dense is paying the "
                "retired fp32 round-trip)",
    },
    "tpu:kv_snapshot_format_total": {
        "kind": "counter", "layer": "engine", "labels": ("version",),
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "KV snapshots encoded onto the kvserver wire by serde "
                "version (v1: legacy untagged dense fp32; v2: tagged "
                "int8 data + fp32 scales — kvserver/protocol.py)",
    },
    "tpu:lockstep_member_last_ack_seconds": {
        "kind": "gauge", "layer": "engine", "labels": ("member",),
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Per-member seconds since the follower's lockstep acks "
                "last advanced (leader of a multi-host slice group; a "
                "member frozen near --slice-member-timeout-s is about "
                "to fail the whole slice's /health)",
    },
    "tpu:lockstep_group_epoch": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Slice group epoch (leader boot nonce carried in every "
                "lockstep event batch; strictly larger after every "
                "group restart — a step in this line IS a restart "
                "marker, and the split-brain guard's ordering)",
    },
    "tpu:lockstep_member_failures_total": {
        "kind": "counter", "layer": "engine", "labels": ("reason",),
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Slice members declared failed (reason: member_silent — "
                "acks stopped past the member timeout; epoch_mismatch — "
                "a member observed a different group incarnation); each "
                "failure restarts the whole group in parallel",
    },
    "tpu:slice_drain_relays_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Follower-initiated drains relayed to the leader "
                "(preStop/SIGTERM on a follower drains the WHOLE slice "
                "through the leader; followers keep stepping until the "
                "group shutdown so in-flight streams finish)",
    },
    "tpu:compile_seconds_total": {
        "kind": "counter", "layer": "engine", "labels": ("executable",),
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Seconds spent in XLA trace+compile per executable shape "
                "key (jit entry point + compact arg-shape signature) — "
                "the compile tax behind first-request TTFT outliers; a "
                "growing series under steady traffic means live shapes "
                "are still missing from warmup coverage "
                "(GET /debug/compiles)",
    },
    "tpu:compiled_shapes": {
        "kind": "gauge", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Distinct executable shape keys compiled since boot; "
                "read against the config-derived inventory in "
                "GET /debug/compiles for warmup coverage",
    },
    "tpu:obs_trace_dropped_total": {
        "kind": "counter", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Completed trace records evicted from the /debug/requests "
                "ring by the count or byte bound (obs.trace_ring_size / "
                "obs.trace_ring_bytes) — drops are visible, not silent",
    },
    # -- engine request-level histograms (obs layer) -----------------------
    "tpu:ttft_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Per-request time to first token, from the handler's entry",
    },
    "tpu:itl_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Inter-token latency, one observation per token gap: the "
                "stretch between the closes of the flight records that "
                "gave a row tokens, shared among the tokens it brought",
    },
    "tpu:e2e_latency_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Per-request end-to-end latency, from the handler's entry",
    },
    "tpu:queue_time_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Admission -> first schedule",
    },
    "tpu:request_upstream_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "The router's x-request-start -> the handler's entry "
                "(observed only where the header is present and sane)",
    },
    "tpu:request_admit_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Handler entry -> handed to the step thread: body, "
                "validation, template, tokenise, admission check",
    },
    "tpu:request_pending_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Handed to the step thread -> admitted by it: the wait "
                "for the pass in flight to end",
    },
    "tpu:first_token_write_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "First token sampled -> the stream's first write returned",
    },
    "tpu:prefill_time_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Prefill phase per request",
    },
    "tpu:request_prefill_behind_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "docs"),
        "help": "Of the prefill phase, how long the request's first prefill "
                "program, launched behind the program in flight, waited "
                "for the device (the flight record's behind_s)",
    },
    "tpu:request_decode_behind_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "docs"),
        "help": "Of the decode phase of a request of two tokens or more, "
                "how long it stood still behind other requests' prefill "
                "records (the flight record's finished row, prefill_s); "
                "its _sum over tpu:decode_time_seconds_sum is the share",
    },
    "tpu:decode_time_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Decode phase per request",
    },
    "tpu:detokenize_time_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Accumulated host detokenize cost per request",
    },
    # -- engine step-phase histograms --------------------------------------
    "tpu:step_schedule_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Scheduler planning time per step",
    },
    "tpu:step_dispatch_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Host-side H2D dispatch time per pipelined step",
    },
    "tpu:step_collect_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Device collect/readback wait per step",
    },
    "tpu:step_sample_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Sample post-process time per step",
    },
    "tpu:step_mixed_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "End-to-end wall time of fused mixed decode+prefill steps",
    },
    # -- async KV transfer-plane histograms --------------------------------
    "tpu:remote_kv_fetch_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Shared-store round-trip per MGET chain fetch / restore GET "
                "(observed on fetcher threads)",
    },
    "tpu:offload_stage_seconds": {
        "kind": "histogram", "layer": "engine",
        "mirrors": ("fake_engine", "dashboard", "docs"),
        "help": "Preemption-snapshot staging, gather dispatch -> host copy "
                "(observed on the stager's writer thread)",
    },
    # -- router gauges (prometheus_client, labeled by server) --------------
    "tpu_router:current_qps": {
        "kind": "gauge", "layer": "router", "labels": ("server",),
        "mirrors": ("dashboard", "docs"),
        "help": "Sliding-window QPS per backend",
    },
    "tpu_router:avg_ttft": {
        "kind": "gauge", "layer": "router", "labels": ("server",),
        "mirrors": ("dashboard", "docs"),
        "help": "Average TTFT per backend (window)",
    },
    "tpu_router:avg_latency": {
        "kind": "gauge", "layer": "router", "labels": ("server",),
        "mirrors": ("dashboard", "docs"),
        "help": "Average e2e latency per backend (window)",
    },
    "tpu_router:avg_itl": {
        "kind": "gauge", "layer": "router", "labels": ("server",),
        "mirrors": ("dashboard", "docs"),
        "help": "Average inter-token latency per backend (window)",
    },
    "tpu_router:avg_decoding_length": {
        "kind": "gauge", "layer": "router", "labels": ("server",),
        "mirrors": ("dashboard", "docs"),
        "help": "Average streamed chunks per request",
    },
    "tpu_router:queueing_delay_seconds": {
        "kind": "gauge", "layer": "router", "labels": ("server",),
        "mirrors": ("dashboard", "docs"),
        "help": "Average router-side queueing delay (window)",
    },
    "tpu_router:num_prefill_requests": {
        "kind": "gauge", "layer": "router", "labels": ("server",),
        "mirrors": ("dashboard", "docs"),
        "help": "Requests awaiting first token per backend",
    },
    "tpu_router:num_decoding_requests": {
        "kind": "gauge", "layer": "router", "labels": ("server",),
        "mirrors": ("dashboard", "docs"),
        "help": "Requests streaming tokens per backend",
    },
    "tpu_router:num_requests_finished": {
        "kind": "gauge", "layer": "router", "labels": ("server",),
        "mirrors": ("dashboard", "docs"),
        "help": "Completed requests per backend",
    },
    "tpu_router:num_requests_uncompleted": {
        "kind": "gauge", "layer": "router", "labels": ("server",),
        "mirrors": ("dashboard", "docs"),
        "help": "In-flight requests per backend",
    },
    "tpu_router:healthy_pods_total": {
        "kind": "gauge", "layer": "router", "labels": ("model",),
        "mirrors": ("dashboard", "docs"),
        "help": "Healthy serving-engine endpoints per model",
    },
    "tpu_router:engine_hbm_kv_usage_perc": {
        "kind": "gauge", "layer": "router", "labels": ("server",),
        "mirrors": ("docs",),
        "help": "Scraped engine KV usage re-exported per backend",
    },
    "tpu_router:engine_prefix_cache_hit_rate": {
        "kind": "gauge", "layer": "router", "labels": ("server",),
        "mirrors": ("docs",),
        "help": "Scraped engine prefix hit rate re-exported per backend",
    },
    "tpu_router:engine_num_requests_waiting": {
        "kind": "gauge", "layer": "router", "labels": ("server",),
        "mirrors": ("docs",),
        "help": "Scraped engine queue depth re-exported per backend",
    },
    "tpu_router:ttft_clean_p95_seconds": {
        "kind": "gauge", "layer": "router", "labels": ("server",),
        "mirrors": ("dashboard", "docs"),
        "help": "Compile-excluded TTFT p95 per backend (window): TTFT "
                "samples whose first chunk carried the engine's "
                "compile=true taint are excluded, separating steady-state "
                "latency from XLA warmup outliers (compare against "
                "tpu_router:ttft_seconds p95 for the compile tax)",
    },
    "tpu_router:circuit_state": {
        "kind": "gauge", "layer": "router", "labels": ("server",),
        "mirrors": ("dashboard", "docs"),
        "help": "Per-backend breaker state (0=closed, 1=half-open, 2=open)",
    },
    # -- fleet-level admission control (router/capacity.py) ----------------
    "tpu_router:fleet_headroom_slots": {
        "kind": "gauge", "layer": "router", "labels": ("pool",),
        "mirrors": ("dashboard", "docs"),
        "help": "Capacity-model fleet headroom in spare request slots per "
                "admission pool (fleet, or prefill/decode/encode under "
                "role pools — the encode lane's embed/rerank/score "
                "traffic is admitted against its own pool's headroom, so "
                "an embed burst cannot starve generation); the "
                "prom-adapter exposes it for HPA",
    },
    "tpu_router:backend_capacity_slots": {
        "kind": "gauge", "layer": "router", "labels": ("server",),
        "mirrors": ("docs",),
        "help": "Learned max useful concurrency per backend (the online "
                "capacity model's slot estimate)",
    },
    "tpu_router:backend_capacity_score": {
        "kind": "gauge", "layer": "router", "labels": ("server",),
        "mirrors": ("dashboard", "docs"),
        "help": "Free-capacity fraction per backend (1 = idle, 0 = "
                "saturated or inside an engine-429 Retry-After window)",
    },
    # -- fleet prefix-popularity view (routing kv_aware_popularity) --------
    "tpu_router:prefix_hot_total": {
        "kind": "counter", "layer": "router",
        "mirrors": ("dashboard", "docs"),
        "help": "Prefixes promoted to HOT by the popularity view (their "
                "decayed request frequency crossed the threshold; each "
                "is served by a replica set from then on)",
    },
    "tpu_router:prefix_replica_set_size": {
        "kind": "gauge", "layer": "router",
        "mirrors": ("dashboard", "docs"),
        "help": "Largest live hot-prefix replica set — the shared system "
                "prompt's replication degree (grows under member load, "
                "shrinks by TTL decay)",
    },
    "tpu_router:fleet_prefix_hit_rate": {
        "kind": "gauge", "layer": "router",
        "mirrors": ("dashboard", "docs"),
        "help": "Fleet-wide token-weighted KV prefix hit rate from the "
                "engines' scraped tpu:prefix_cache_{hit,query}_tokens_"
                "total truth counters (the BASELINE.md headline metric, "
                "at one scrape point)",
    },
    "tpu_router:semantic_cache_size": {
        "kind": "gauge", "layer": "router",
        "mirrors": ("dashboard", "docs"),
        "help": "Entries resident in the semantic cache",
    },
    # -- router counters (prometheus_client exposes Counter(x) as x_total) -
    "tpu_router:deadline_expired_total": {
        "kind": "counter", "layer": "router",
        "mirrors": ("dashboard", "docs"),
        "help": "Requests shed at the router on an expired deadline",
    },
    "tpu_router:fleet_admission_rejected_total": {
        "kind": "counter", "layer": "router", "labels": ("reason",),
        "mirrors": ("dashboard", "docs"),
        "help": "Requests shed at the router by fleet-level admission "
                "control (reason: no_headroom | low_priority) — in a "
                "healthy fleet these strictly precede any engine-side 429",
    },
    "tpu_router:semantic_cache_hits_total": {
        "kind": "counter", "layer": "router",
        "source_name": "tpu_router:semantic_cache_hits",
        "mirrors": ("dashboard", "docs"),
        "help": "Semantic cache hits served (chat experimental cache + "
                "the encode-lane cache fronting /v1/embeddings, rerank "
                "and score — an exact hit answers with the stored "
                "response bytes and zero engine work)",
    },
    "tpu_router:semantic_cache_misses_total": {
        "kind": "counter", "layer": "router",
        "source_name": "tpu_router:semantic_cache_misses",
        "mirrors": ("dashboard", "docs"),
        "help": "Semantic cache lookups that missed (chat experimental "
                "cache + the encode-lane cache)",
    },
    "tpu_router:pii_requests_scanned_total": {
        "kind": "counter", "layer": "router",
        "source_name": "tpu_router:pii_requests_scanned",
        "mirrors": ("dashboard", "docs"),
        "help": "Requests scanned by the PII middleware",
    },
    "tpu_router:pii_requests_blocked_total": {
        "kind": "counter", "layer": "router",
        "source_name": "tpu_router:pii_requests_blocked",
        "mirrors": ("dashboard", "docs"),
        "help": "Requests blocked because PII was detected",
    },
    "tpu_router:pii_detections_total": {
        "kind": "counter", "layer": "router", "labels": ("pii_type",),
        "source_name": "tpu_router:pii_detections",
        "mirrors": ("dashboard", "docs"),
        "help": "PII entities detected in request bodies",
    },
    "tpu_router:obs_trace_dropped_total": {
        "kind": "counter", "layer": "router",
        "source_name": "tpu_router:obs_trace_dropped",
        "mirrors": ("dashboard", "docs"),
        "help": "Completed trace records evicted from the router's "
                "/debug/requests ring by the count or byte bound "
                "(--trace-ring-size / --trace-ring-bytes)",
    },
    "tpu_router:disagg_fallback_total": {
        "kind": "counter", "layer": "router", "labels": ("reason",),
        "mirrors": ("dashboard", "docs"),
        "help": "Two-phase disagg requests degraded to the fused path "
                "(reason: prefill_pool_empty | prefill_breaker_open | "
                "decode_pool_empty | prime_failed | handoff_unexported | "
                "prefix_miss)",
    },
    "tpu_router:disagg_requests_total": {
        "kind": "counter", "layer": "router", "labels": ("role",),
        "mirrors": ("dashboard", "docs"),
        "help": "Requests routed by the disagg policy, by phase role "
                "(prefill | decode | fused)",
    },
    "tpu_router:disagg_handoff_seconds": {
        "kind": "histogram", "layer": "router",
        "mirrors": ("dashboard", "docs"),
        "help": "Disagg prefill-phase latency: prime connect + engine "
                "prefill + eager export + handoff response",
    },
    # -- router latency histograms (custom render, labeled by server) ------
    "tpu_router:ttft_seconds": {
        "kind": "histogram", "layer": "router", "labels": ("server",),
        "mirrors": ("dashboard", "docs"),
        "help": "Router-observed TTFT per backend",
    },
    "tpu_router:itl_seconds": {
        "kind": "histogram", "layer": "router", "labels": ("server",),
        "mirrors": ("dashboard", "docs"),
        "help": "Router-observed inter-token latency per backend",
    },
    "tpu_router:e2e_latency_seconds": {
        "kind": "histogram", "layer": "router", "labels": ("server",),
        "mirrors": ("dashboard", "docs"),
        "help": "Router-observed e2e latency per backend",
    },
    "tpu_router:request_queueing_seconds": {
        "kind": "histogram", "layer": "router", "labels": ("server",),
        "mirrors": ("dashboard", "docs"),
        "help": "Router-side queueing before backend connect",
    },
}
