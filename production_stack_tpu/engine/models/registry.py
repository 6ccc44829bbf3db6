"""Model registry: architecture name -> functional model module, and what
such a module offers the engine (the one place that says so).

**A module must offer** (``models/llama.py`` is the pattern and gives the
signatures): ``init_params(cfg, key, shardings=None)`` (seeded weights, each
tensor made in its final sharding), ``quantize_params(params, cfg)``,
``prefill(params, cfg, tokens, cached_len, prefix_block_ids, new_block_ids,
valid_len, kv_caches, mesh=None, sp_mode=..., prompt_targets=None,
prompt_topk=0) -> (last logits [V], caches)`` and ``decode(params, cfg,
tokens, positions, block_tables, ctx_lens, slot_block_ids, slot_offsets,
kv_caches, mesh=None) -> (logits [S, V], caches)``.  ``kv_caches`` is a tree
the step programs (``core/step_programs.py``) carry without looking inside.

**A module may offer**, and the engine asks with ``hasattr``:

- ``mixed_step``, ``encode`` / ``encode_batch``, ``lora=`` / ``adapter_idx=``
  on the steps: fused mixed batches, embeddings, adapters (llama.py has
  all; without them the engine turns the auto gate off or refuses the
  request or the flag by name).
- ``init_cache(cfg, num_blocks, block_size, sharding)`` with
  ``cache_bytes_per_token(cfg)``: a cache of the module's own shape (a
  latent cache is one array a layer, no K and V).  The engine's allocator,
  its byte count and the benchmark's compare then ask the module; without
  it a K and a V array a layer of ``num_kv_heads x head_dim``.  Such a
  module is refused at boot with the tiers that unpack a (K, V) pair
  (offload, remote store, prefetch, disaggregation), int8 KV, LoRA,
  speculation and a mesh (``core/engine.py:
  _refuse_what_the_module_lacks``).
- with ``init_cache``, ``attention_paths(cfg) -> (decode, prefill)``: the
  names of the attention paths its steps take in this process, which the
  engine's boot line says (``Attention: decode=... prefill=...``).
- with ``init_cache``, ``prefill_attn_tiles(cfg, bucket_len, prefix_blocks,
  block_size, cached_len, num_new_tokens) -> (live, grid)``: the tiles a
  layer's prefill attention computes and those of its grid for one chunk, by
  the rule of the module's own prefill kernel; the engine's ``kv_tiles_live``
  / ``kv_tiles_grid`` and ``tpu:prefill_attn_tiles_total`` then count those
  (``core/engine.py: _count_kv_tiles``), and the flash prefill kernel's kv
  tiles for a module without it.
- ``param_specs(cfg)``: the PartitionSpec tree of its own parameters
  (``parallel/shardings.py`` asks before it assumes llama's tree).
- ``return_choice=True`` on ``prefill`` / ``decode``: one more result, the
  experts each row chose (int32 [routed layers, rows, k], ids over the
  router's published width), logits bit-equal with and without; the
  benchmark's compare follows it (``bench/harness/compare.py``).
- ``stats_names(cfg)`` (names) and ``STATS_MAX`` (those of them that fold by
  a maximum, not a sum) with ``return_stats=True`` on both steps: one more
  result, an int32 vector of what routing (and a residual path of several
  streams) did, counted on the device; the engine asks for it on the
  dedicated prefill and inside the K-step window and reads it back with the
  tokens (flight records, ``/metrics``).
- with ``init_cache``, ``residual_path(cfg) -> None | (streams,
  normalisations, path)``: the boot line ``Residual: ...`` of a module whose
  tokens carry several residual streams.
- with ``init_cache``, ``layer_form(cfg) -> str``: the boot line ``Layer:
  ...`` of a module whose layer is not one attention and one FFN (how many
  attentions, FFNs and cache arrays, the router's width and what is held).
- with ``init_cache``, **a state pool**: ``state_bytes_per_slot(cfg)`` and
  ``snapshot_stride(cfg)`` say that some layers keep, in place of keys, a
  state that does not grow with the context (four modules do:
  ``solar_kda.py``, ``jamba.py`` and ``olmo_hybrid.py`` a recurrent state,
  ``laguna.py`` a window layer's last keys and values in a rolling buffer).  The engine then makes
  a ``kv/state_pool.py: StatePool`` beside the block pool (sized by rule from
  ``max_num_seqs``; its bytes come off what the block pool is sized from),
  calls ``init_cache(..., state_slots=n)`` so that the one cache tree holds
  pages for the layers with keys and ``n`` slots for those with state (slot 0
  is the null slot, the padding rows'), and hands the slots as **keyword
  arguments**: ``prefill(..., state_slot=, state_from=, snapshot_slot=,
  snapshot_len=)`` (the sequence's live slot; the slot the chunk starts from,
  a snapshot's on a resumed admission, negative: zeros; the slot that keeps
  the state ``snapshot_len`` tokens into the chunk, a multiple of the stride;
  the live slot itself: none) and ``decode(..., state_slots=[S])`` (each
  row's live slot, through ``step_programs.window_program`` too).  ``cache_
  bytes_per_token`` counts the layers with keys alone.  A padded slot of a
  chunk and a dead row of a decode batch must be the identity on the state.
  **A dispatch touches the slots it names and nothing else of a pool**: the
  module reads and writes slots where they lie, so that the compiled
  ``prefill_fn`` / ``window_fn`` hold no copy of a pool.  A state goes into a
  kernel and comes out of it in the pool's own orientation, or the pool is
  aliased through the kernel; and a slot is whole tiles of the device's memory,
  as a page of keys is: a slot's few rows of another kind -- the convolution's
  last ``kernel - 1`` -- lie one after another in lines of 128 channels,
  ``solar_kda.rows_pool_shape``: ``[slots, rows x width / 128, 128]`` (a
  ``[slots, 3, width]`` array is kept rows-outermost at a program's boundary
  and slots-outermost inside it, one relayout in and one out of the whole
  pool a layer a dispatch; in ``[slots, 3 x width]`` a slot is a sub-tile line
  and every scatter stages the pool or rewrites tiles row by row).
  ``tests/test_chip_compile.py: test_a_state_models_served_programs_copy_no_pool``
  compiles the four modules' served programs for a described v5e and is what
  holds a fourth such module to it: add the preset to its ``STATE_MODELS``.
  (A value written into a slot that is computed from a slot of the same pool
  -- a buffer carried over from the slot a chunk starts from -- is taken out
  whole first, ``jax.lax.optimization_barrier``: fused with the write, XLA
  copies the pool to keep the read safe.)
  **The default the benchmark's compare relies on**: ``bench/harness/
  compare.py`` calls both steps with the cache ``init_cache(cfg, blocks,
  block_size, sharding)`` returned and nothing else, so where no slot is
  handed the module derives it from what is there: the first block id of the
  row's table modulo the slots ``init_cache`` made, a chunk with
  ``cached_len == 0`` starting from zeros and a later one going on from its
  slot, no snapshot; bit-equal to the same run with explicit slots.

**What runs, by mechanism** (ROADMAP Queue 2 lists what does not): dense GQA
with one sliding window, int8 weights, a softmax-routed MoE (``llama.py``);
and in ``sarvam_mla.py`` a latent (MLA) cache of one array a layer with the
absorbed decode and the absorbed prefill each in a Pallas kernel, a full or a
low-rank query path
(``q_lora_rank``), a norm a query head or none, routed experts behind a biased
sigmoid router held by share or whole with a shared expert, leading dense
layers, ``deepseek_yarn``, and several residual streams mixed at every
sub-layer by a Sinkhorn-normalised matrix (``hc_mult``, ``hc_sinkhorn_iters``,
``hc_eps``, ``hc_res_clamp``; counters ``mhc_clamped`` / ``mhc_entries`` /
``mhc_err_e6``), taken in Python at trace time so that a configuration
without them traces the program it always had; and in ``solar_kda.py`` layers
of two kinds by a list (``layer_kinds``): softmax GQA with no position encoding
at all (``use_rope`` False) and an elementwise sigmoid gate on the heads'
output, through the two dense Pallas kernels; and the gated delta rule with a
decay a channel (depthwise causal convolution, low-rank decay and gate,
``beta`` up to 2), a float32 state a head a sequence in a slot of the state
pool, chunkwise in prefill and one step in decode (``ops/pallas/kda.py``),
with snapshots at block boundaries that the prefix cache resumes from; over
``sarvam_mla``'s routed experts held by share, imported; and in ``jamba.py``
the selective state-space mixer (Mamba-1: causal depthwise convolution with a
bias, a step size through a low-rank projection with a bias and a softplus, an
RMSNorm on the step size, ``B`` and ``C``, ``A = -exp(A_log)``, a skip ``D``,
a SiLU gate), a float32 state ``[states, channels]`` a sequence a layer in a
slot of the same state pool (26 layers deep at the published depth), a scan
over a chunk in prefill and one step in decode (``ops/pallas/ssm.py``), beside
``solar_kda``'s softmax layer with its gate off at one key/value head
(multi-query, 20 query heads: the paged kernel takes a one-head page as
``[block, head_dim]``), ``llama.py``'s dense SwiGLU and tied head; layer kinds
from one period (``attn_layer_period`` / ``attn_layer_offset``); counters
``ssm_state_absmax_e3`` / ``ssm_dt_max_e3``; and in ``laguna.py`` softmax
layers of two kinds in one model, each kind with a spec of its own
(``ModelConfig.attention_specs``, ``config.AttentionSpec``: query heads,
window, the share of a head that rotates, the rotary base, YaRN with its
``attention_factor``): ``"full"`` layers (48 query heads over 8 at the
published size, 6 a key head, partial rotary) through ``solar_kda``'s paged
softmax path with their own projection, and ``"window"`` layers (64 over 8, a
window of 512) whose rotated keys and values lie in a rolling buffer, position
p at row ``p mod window``, in a slot of the same state pool: decode reads the
slot as pool-adjacent pages through the paged kernel under the name
``window_decode_attention_pallas``, a prefill chunk takes the buffer in order
as its cached prefix through the flash kernel under the window mask; a sigmoid
gate a head (``use_head_gate``); a leading dense layer and ``sarvam_mla``'s
routed experts held by share with no selection bias, imported; counter
``tpu:attn_positions_total{kind}`` and the records' ``kv_tokens_slots``
(``config.PAGED_KINDS`` says which kinds keep pages); and in ``longcat.py`` a
layer of two latent attentions and two dense FFNs with one routed FFN across
them (shortcut-connected: the routed FFN reads what the first dense FFN reads
and is added after the second; ``attn_per_layer`` 2, so two cache arrays a
layer, ``ModelConfig.cache_layers`` in all, which everything that sizes,
allocates or counts the cache asks), ``sarvam_mla``'s latent attention with
the low-rank query path and the two latent scale factors
(``mla_scale_q_lora``, ``mla_scale_kv_lora``: the cache keeps the scaled
latent, the kernels are unchanged), plain rotary frequencies, and a router
scored by softmax over its whole width whose chosen shares are not
renormalised (``router_scoring``, ``norm_topk_prob``) and whose outputs past
``router_experts`` name identity experts (``zero_expert_num``: they return
their input, hold no weights and are computed whole on every chip of the
deployment, counted once), behind ``sarvam_mla``'s grouped dispatch held by
share, imported; boot line ``Layer: ...`` (``layer_form``), counter
``moe_zero_assigned`` / ``tpu:moe_zero_assigned_total``; and in
``olmo_hybrid.py`` blocks that norm what a sub-layer returns inside its
residual branch (no norm before it), ``"gdn"`` layers -- the gated delta rule
with a decay that is one number a head, a state of ``linear_head_dim`` key by
``linear_value_head_dim`` value channels (96 x 192: not square), 30 heads (a
count 16 does not divide: ``ops/pallas/kda.py: head_block``), a SiLU output
gate and full-rank decay and gate projections; prefill through
``gdn_prefill_pallas`` (the chunkwise form with one decay ratio a pair of
tokens), decode through ``kda_decode_pallas`` as ``solar_kda.py`` calls it --
beside ``"full"`` multi-head layers (30 key heads for 30 query heads, an
RMSNorm over the whole width of q and of k, no position encoding) through
``solar_kda``'s paged path with pages of ``olmo_hybrid.page_heads`` = 32 key
heads (whole bf16 tiles); ``llama.py``'s dense SwiGLU and untied head;
counters ``gdn_state_absmax_e3`` / ``gdn_beta_max_e3``.
"""

from __future__ import annotations

from types import ModuleType

from production_stack_tpu.engine.models import (
    jamba, laguna, llama, longcat, olmo_hybrid, sarvam_mla, solar_kda,
)

MODEL_REGISTRY = {
    # llama.py covers every RMSNorm+RoPE+GQA+gated-MLP family member; the
    # config (not the code) differentiates them — including QKV biases
    # (qwen2), sparse MoE (mixtral), and gemma's norm-offset/GeGLU/
    # embedding-scale switches.
    "llama": llama,
    "mistral": llama,
    "mixtral": llama,
    "qwen2": llama,
    "gemma": llama,
    # Latent attention over routed experts held by share: a cache of one
    # array a layer, which the module makes (init_cache).
    "sarvam": sarvam_mla,
    # The same module's other family: several residual streams mixed by a
    # doubly stochastic matrix around that latent attention with a low-rank
    # query path, every routed expert held (cfg.hc_mult, cfg.q_lora_rank).
    "xing": sarvam_mla,
    # Gated delta-rule layers beside gated softmax layers without position
    # encoding: two kinds of state in one cache tree, pages and slots.
    "solar": solar_kda,
    # Selective state-space (Mamba-1) layers beside multi-query softmax
    # layers without position encoding, a dense MLP, a tied head: the second
    # user of the state pool (its layer loop, its softmax path and its slot
    # addressing are solar_kda's, imported).
    "jamba": jamba,
    # Window and full softmax layers 3:1 with head counts and rotary forms by
    # layer kind and a gate a head, over routed experts held by share behind
    # a dense lead: the third user of the state pool (a window layer's last
    # keys in a rolling buffer a slot; the layer loop, the paged softmax path
    # and the slot addressing are solar_kda's, the routed FFN sarvam_mla's,
    # imported).
    "laguna": laguna,
    # A layer of two latent attentions and two dense FFNs with one routed
    # FFN across them (shortcut-connected), a softmax router some of whose
    # outputs are identity experts: two cache arrays a layer (the latent
    # attention with its kernels, the cache layout, the router and the
    # grouped dispatch are sarvam_mla's, imported).
    "longcat": longcat,
    # The gated delta rule with a decay a head and a state that is not square
    # (96 x 192) beside multi-head softmax layers without position encoding
    # whose pages keep 32 key heads for 30, a dense MLP, the norm after each
    # sub-layer: the state pool's fourth user (the paged softmax path, the
    # slot addressing and the plain recurrence are solar_kda's, the MLP and
    # the head llama's, imported).
    "olmo": olmo_hybrid,
}


def get_model(architecture: str) -> ModuleType:
    arch = architecture.lower()
    for key, module in MODEL_REGISTRY.items():
        if key in arch:
            return module
    raise ValueError(
        f"Unsupported architecture {architecture!r}; known: {sorted(MODEL_REGISTRY)}"
    )
