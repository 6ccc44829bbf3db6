"""The main path's Pallas kernels, compiled for a described TPU v5e.

No chip is attached: the TPU's compiler is installed here and compiles for
a topology that is described (``v5e:2x2``), about two seconds a kernel.
Interpret mode (every other kernel test) cannot see what Mosaic refuses —
a slice not aligned to the tiling, too much VMEM — so these compiles guard
each kernel variant the engine can dispatch for mistral-7b, at its
published widths and at its tp=4 shard shapes, and the latent decode and
prefill kernels at sarvam-105b's, xing4.0's and longcat-flash-omni's, against
every later PR.
A compile that passes is not a chip run: nothing executes here.

The topology is described inside a module-scoped fixture (never at import,
in a ``skipif`` or in ``parametrize``): only the xdist worker that is
given this file loads the TPU library.  All of these tests stay in this
one file for the same reason.
"""

import collections
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from production_stack_tpu.engine.config import PRESETS
from production_stack_tpu.engine.ops.pallas.flash_prefill import (
    flash_prefill_attention,
)
from production_stack_tpu.engine.ops.pallas.latent_attention import (
    latent_decode_attention_pallas,
    latent_prefill_attention_pallas,
)
from production_stack_tpu.engine.ops.pallas.paged_attention import (
    paged_decode_attention_pallas,
)

MISTRAL = PRESETS["mistral-7b"]
D = MISTRAL.head_dim  # 128
SCALE = D**-0.5
WINDOW = MISTRAL.sliding_window  # 4096: mistral sets one, so every variant does
BS = 16
MAX_LEN = 8192  # chip_smoke.py's --max-model-len
# (H, K): published widths on one chip, and one shard of --tensor-parallel 4.
ONE_CHIP = (MISTRAL.num_heads, MISTRAL.num_kv_heads)
TP4_SHARD = (MISTRAL.num_heads // 4, MISTRAL.num_kv_heads // 4)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def sds(one_chip):
    """Shape of one argument, on the described chip."""
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next one warns and
    compiles again): keep it out of these tests."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# S: two of the engine's decode buckets; 16 is what cell 2 of the benchmark
# (m7b-int8.sessions-prefix) decodes in.  The kernel holds the whole batch's
# queries and outputs in VMEM, so S is part of what Mosaic has to fit.
@pytest.mark.parametrize("S", [8, 16], ids=["S8", "S16"])
@pytest.mark.parametrize("heads", [ONE_CHIP, TP4_SHARD], ids=["H32K8", "tp4-H8K2"])
def test_decode_kernel_bf16_compiles(sds, no_persistent_cache, heads, S):
    H, K = heads
    N, bmax = 1024, MAX_LEN // BS

    cache = sds((N, BS, K, D), jnp.bfloat16)
    _compile(
        lambda q, k, v, bt, cl: paged_decode_attention_pallas(
            q, k, v, bt, cl, scale=SCALE, sliding_window=WINDOW
        ),
        sds((S, H, D), jnp.bfloat16), cache, cache,
        sds((S, bmax), jnp.int32), sds((S,), jnp.int32),
    )


def _prefix_copies(text, elements):
    """The ``gather`` / ``concatenate`` / ``pad`` instructions of a compiled
    program whose result holds at least ``elements`` values of bf16: what a
    gathered ``[C, K, D]`` prefix, its concatenation with the chunk's keys and
    the pad to whole tiles were."""
    found = []
    for line in text.splitlines():
        m = re.search(
            r"= bf16\[([0-9,]+)\]\S* (gather|concatenate|pad)\(", line)
        if m and math.prod(map(int, m.group(1).split(","))) >= elements:
            found.append(line.strip()[:120])
    return found


def _flash(sds, H, K, hd, T, pool, P, window):
    """``flash_prefill_attention`` compiled for T slots of H heads over K,
    behind a table of P pages of the K/V pools ``pool`` ([N, bs]); behind an
    empty table it is handed no pools."""
    new = sds((T, K, hd), jnp.bfloat16)
    pages = sds((*pool, K, hd), jnp.bfloat16) if P else None
    return _compile(
        lambda q, k, v, kp, vp, ids, cached, valid: flash_prefill_attention(
            q, k, v, kp, vp, ids, cached, valid,
            scale=hd ** -0.5, sliding_window=window,
        ),
        sds((T, H, hd), jnp.bfloat16), new, new, pages, pages,
        sds((P,), jnp.int32), sds((), jnp.int32), sds((), jnp.int32),
    )


# The engine always hands max_model_len of block ids, so P = MAX_LEN / BS
# is the table every served prefill compiles; P = 0 is the embeddings path
# (models/llama.py encode).  The grid is static, what it visits is not:
# the kernel's fence, its walk over the table's pages and its new keys' index
# map (which read the prefetched cached_len / valid_len) skip every kv tile
# that holds no visible key -- prefix positions past cached_len, new keys
# past valid_len -- and every query tile past valid_len (flash_prefill.py:
# live_kv_tiles).  The pools stay in HBM: the kernel copies the pages itself.
@pytest.mark.parametrize(
    "heads,T,P",
    [
        (ONE_CHIP, 256, MAX_LEN // BS),
        (ONE_CHIP, 2048, MAX_LEN // BS),
        (ONE_CHIP, 2048, 0),
        (TP4_SHARD, 2048, MAX_LEN // BS),
    ],
    ids=["H32K8-T256-P512", "H32K8-T2048-P512", "H32K8-T2048-P0",
         "tp4-H8K2-T2048-P512"],
)
def test_flash_prefill_kernel_compiles(sds, no_persistent_cache, heads, T, P):
    H, K = heads
    text = _flash(sds, H, K, D, T, (1024, BS), P, WINDOW).as_text()
    # Nothing of the prefix is copied beside the kernel: no gather of the
    # table's pages, no concatenate with the chunk's keys, no pad.
    assert not _prefix_copies(text, MAX_LEN * K * D)


# A quantized (data, scale) cache keeps the kernel (ops/attention.py:
# prefill_attention): its prefix is gathered and dequantized to bf16, as at
# every commit before, and that copy is the kernel's pool, 16 pages of 512
# positions in order -- nothing is concatenated or padded after it.  The
# engine still refuses int8 KV on a TPU at boot, for the decode kernel's sake
# (test_engine_refuses_int8_kv_on_tpu_at_boot); the day that lifts, prefill
# is served.
@pytest.mark.parametrize("T", [256, 2048], ids=["T256", "T2048"])
def test_flash_prefill_serves_a_quantized_cache(
        sds, no_persistent_cache, monkeypatch, T):
    from production_stack_tpu.engine.ops.attention import prefill_attention

    H, K = ONE_CHIP
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    new = sds((T, K, D), jnp.bfloat16)
    cache = (sds((1024, BS, K, D), jnp.int8), sds((1024, BS, K), jnp.float32))
    text = _compile(
        lambda q, k, v, kc, vc, ids, cached, valid: prefill_attention(
            q, k, v, kc, vc, ids, cached, valid,
            scale=SCALE, sliding_window=WINDOW),
        sds((T, H, D), jnp.bfloat16), new, new, cache, cache,
        sds((MAX_LEN // BS,), jnp.int32), sds((), jnp.int32),
        sds((), jnp.int32),
    ).as_text()
    assert text.count("flash_prefill_attention")
    copies = _prefix_copies(text, MAX_LEN * K * D)
    assert not [line for line in copies if " gather(" not in line], copies


# The latent (MLA) decode kernel at sarvam-105b's published widths: 64 heads,
# a cache row of 576 values in 640 lanes, the cell's pool (34,959 blocks) and
# its block table (32,768 positions); the whole batch's queries and outputs,
# the ring's three slots and the [16, 2048] table in SMEM have to fit.  And at
# Xing4.0-29B-A4B's: 32 heads a row, the same cache row.
@pytest.mark.parametrize("preset", ["sarvam-105b-ep4",
                                    "xing4.0-29b-a4b-stage",
                                    "longcat-flash-omni-ep32"])
@pytest.mark.parametrize("S", [8, 16], ids=["S8", "S16"])
def test_latent_decode_kernel_compiles(sds, no_persistent_cache, S, preset):
    from production_stack_tpu.engine.models import sarvam_mla

    cfg = PRESETS[preset]
    lanes = sarvam_mla.cache_lanes(cfg)
    assert lanes == 640
    _compile(
        lambda q, c, bt, cl: latent_decode_attention_pallas(
            q, c, bt, cl, latent_rank=cfg.kv_lora_rank,
            scale=sarvam_mla.softmax_scale(cfg),
        ),
        sds((S, cfg.num_heads, lanes), jnp.bfloat16),
        sds((34959, BS, lanes), jnp.bfloat16),
        sds((S, cfg.max_model_len // BS), jnp.int32), sds((S,), jnp.int32),
    )


# The latent prefill kernel at the same two models' widths, both prefill
# programs' slots (256: a round of sessions-20k; 2,048: a history's chunk,
# whose own rows are four stages), the cell's pool and its 2,048-entry block
# table in SMEM: a query tile of 1,024 rows x 640 lanes, a stage's fp32 scores
# and probabilities, the accumulator and the ring have to fit the VMEM the
# kernel asks for, and [slots, heads, lanes] has to read as rows where it lies.
@pytest.mark.parametrize("preset", ["sarvam-105b-ep4",
                                    "xing4.0-29b-a4b-stage",
                                    "longcat-flash-omni-ep32"])
@pytest.mark.parametrize("T", [256, 2048], ids=["T256", "T2048"])
def test_latent_prefill_kernel_compiles(sds, no_persistent_cache, T, preset):
    from production_stack_tpu.engine.models import sarvam_mla

    cfg = PRESETS[preset]
    lanes = sarvam_mla.cache_lanes(cfg)
    compiled = _compile(
        lambda q, rows, c, ids, cached, valid: latent_prefill_attention_pallas(
            q, rows, c, ids, cached, valid, latent_rank=cfg.kv_lora_rank,
            scale=sarvam_mla.softmax_scale(cfg),
        ),
        sds((T, cfg.num_heads, lanes), jnp.bfloat16),
        sds((T, lanes), jnp.bfloat16),
        sds((34959, BS, lanes), jnp.bfloat16),
        sds((cfg.max_model_len // BS,), jnp.int32),
        sds((), jnp.int32), sds((), jnp.int32),
    )
    # The name the trace and ``breakdown.device_ops`` show.
    assert "latent_prefill_attention_pallas" in compiled.as_text()


# The residual mappings' normalisation kernel at the shapes the
# xing4.0-29b-a4b-stage cell runs it at: a decode batch and both prefill
# programs' slots (each padded to whole (8, 128) tiles an entry inside).
@pytest.mark.parametrize("T", [16, 256, 2048], ids=["T16", "T256", "T2048"])
def test_mhc_sinkhorn_kernel_compiles(sds, no_persistent_cache, T):
    from production_stack_tpu.engine.ops.pallas.mhc_sinkhorn import (
        mhc_sinkhorn_pallas,
    )

    cfg = PRESETS["xing4.0-29b-a4b-stage"]
    n = cfg.hc_mult
    _compile(
        lambda E: mhc_sinkhorn_pallas(
            E, iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps),
        sds((n, n, T), jnp.float32),
    )


# solar-open2-250b-ep8 (models/solar_kda.py): the two delta-rule kernels at the
# published 64 heads of 128 (the chunkwise form at both prefill programs'
# slots, the one-step form at the cell's 16 rows over its 59 slots), and the
# two dense kernels at its softmax layer's geometry, 64 query heads over 8
# key/value heads with no window, behind 32,768 gathered prefix slots: a later
# change to either dense kernel is held by two configurations.
@pytest.mark.parametrize("T", [256, 2048], ids=["T256", "T2048"])
def test_kda_prefill_kernel_compiles(sds, no_persistent_cache, T):
    from production_stack_tpu.engine.ops.pallas.kda import kda_prefill_pallas

    cfg = PRESETS["solar-open2-250b-ep8"]
    H, Dl = cfg.linear_num_heads, cfg.linear_head_dim
    rows = sds((T, H, Dl), jnp.float32)
    _compile(
        lambda q, k, v, g, b, s0, at: kda_prefill_pallas(
            q, k, v, g, b, s0, at),
        rows, rows, rows, rows, sds((T, H), jnp.float32),
        sds((H, Dl, Dl), jnp.float32), sds((), jnp.int32),
    )


@pytest.mark.parametrize("S", [8, 16], ids=["S8", "S16"])
def test_kda_decode_kernel_compiles(sds, no_persistent_cache, S):
    from production_stack_tpu.engine.kv.state_pool import pool_slots
    from production_stack_tpu.engine.ops.pallas.kda import kda_decode_pallas

    cfg = PRESETS["solar-open2-250b-ep8"]
    H, Dl = cfg.linear_num_heads, cfg.linear_head_dim
    slots = 1 + sum(pool_slots(16))
    rows = sds((S, H, Dl), jnp.float32)
    pool = (slots, H, Dl, Dl)
    compiled = jax.jit(
        lambda q, k, v, g, b, state, at: kda_decode_pallas(
            q, k, v, g, b, state, at),
        donate_argnums=(5,),           # as the engine donates the cache tree
    ).lower(
        rows, rows, rows, rows, sds((S, H), jnp.float32),
        sds(pool, jnp.float32), sds((S,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # The pool goes in and comes out in place: no second 250 MB array.
    shape = "f32[" + ",".join(map(str, pool)) + "]"
    assert not [line for line in text.splitlines()
                if " copy(" in line and shape in line.split("=")[1][:60]]


def test_the_dense_kernels_compile_at_solars_geometry(sds, no_persistent_cache):
    cfg = PRESETS["solar-open2-250b-ep8"]
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    assert (H, K, hd, cfg.sliding_window) == (64, 8, 128, None)
    cache = sds((20000, BS, K, hd), jnp.bfloat16)
    _compile(
        lambda q, k, v, bt, cl: paged_decode_attention_pallas(
            q, k, v, bt, cl, scale=hd ** -0.5, sliding_window=None),
        sds((16, H, hd), jnp.bfloat16), cache, cache,
        sds((16, cfg.max_model_len // BS), jnp.int32), sds((16,), jnp.int32),
    )
    _flash(sds, H, K, hd, 256, (20000, BS), cfg.max_model_len // BS, None)


# jamba2-3b (models/jamba.py): the two state-space kernels at the published
# 5120 channels of 16 states (the scan at both prefill programs' slots, the
# one-step form at 1 to 16 rows over the cell's 59 slots), and the two dense
# kernels at its attention layers' geometry, 20 query heads over ONE key/value
# head: a third configuration holds them.  One key head is what Mosaic had
# refused (a page [16, 1, 128] pads its second-minor dim to a tile of 2 that a
# one-head DMA slice is not aligned to): the wrapper hands such a page in as
# [16, 128], a bitcast of what XLA keeps.
@pytest.mark.parametrize("T", [256, 2048], ids=["T256", "T2048"])
def test_ssm_prefill_kernel_compiles(sds, no_persistent_cache, T):
    from production_stack_tpu.engine.ops.pallas.ssm import ssm_prefill_pallas

    cfg = PRESETS["jamba2-3b"]
    Di, N = cfg.mamba_expand * cfg.hidden_size, cfg.mamba_d_state
    assert (Di, N) == (5120, 16)
    rows, cols = sds((T, Di), jnp.float32), sds((T, N), jnp.float32)
    state = sds((N, Di), jnp.float32)
    _compile(
        lambda c, dt, z, B, C, A, D, s0, at: ssm_prefill_pallas(
            c, dt, z, B, C, A, D, s0, at),
        rows, rows, rows, cols, cols, state, sds((Di,), jnp.float32), state,
        sds((), jnp.int32),
    )


@pytest.mark.parametrize("S", [1, 8, 16], ids=["S1", "S8", "S16"])
def test_ssm_decode_kernel_compiles(sds, no_persistent_cache, S):
    from production_stack_tpu.engine.kv.state_pool import pool_slots
    from production_stack_tpu.engine.ops.pallas.ssm import ssm_decode_pallas

    cfg = PRESETS["jamba2-3b"]
    Di, N = cfg.mamba_expand * cfg.hidden_size, cfg.mamba_d_state
    pool = (1 + sum(pool_slots(16)), N, Di)
    rows, cols = sds((S, Di), jnp.float32), sds((S, N), jnp.float32)
    compiled = jax.jit(
        lambda c, dt, z, B, C, A, D, state, at: ssm_decode_pallas(
            c, dt, z, B, C, A, D, state, at),
        donate_argnums=(7,),           # as the engine donates the cache tree
    ).lower(
        rows, rows, rows, cols, cols, sds((N, Di), jnp.float32),
        sds((Di,), jnp.float32), sds(pool, jnp.float32), sds((S,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # The pool goes in and comes out in place: no second array of it.
    shape = "f32[" + ",".join(map(str, pool)) + "]"
    assert not [line for line in text.splitlines()
                if " copy(" in line and shape in line.split("=")[1][:60]]


def test_the_dense_kernels_compile_at_jambas_geometry(sds, no_persistent_cache):
    cfg = PRESETS["jamba2-3b"]
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    assert (H, K, hd, cfg.sliding_window) == (20, 1, 128, None)
    pages = (500_000, BS, K, hd)       # the cell's pool: half a million blocks
    cache = sds(pages, jnp.bfloat16)
    text = _compile(
        lambda q, k, v, bt, cl: paged_decode_attention_pallas(
            q, k, v, bt, cl, scale=hd ** -0.5, sliding_window=None),
        sds((16, H, hd), jnp.bfloat16), cache, cache,
        sds((16, cfg.max_model_len // BS), jnp.int32), sds((16,), jnp.int32),
    ).as_text()
    # The 2 GB of pages reach the kernel as they lie: a bitcast, no copy.
    assert not [line for line in text.splitlines()
                if " copy(" in line and "bf16[500000," in line.split("=")[1][:60]]
    # What compiled is the grouped stage (paged_attention.py: eight 4 kB
    # pages a descriptor, 16 descriptors a side = 128 blocks a stage, two
    # slots of 512 kB a side in VMEM): the kernel takes a flag a group of
    # the table and a flag a stage beside the table and the contexts.
    from production_stack_tpu.engine.ops.pallas.paged_attention import (
        CHUNK_BLOCKS, blocks_per_descriptor,
    )
    R = blocks_per_descriptor(BS * K * hd * 2)
    bmax = cfg.max_model_len // BS
    assert (R, CHUNK_BLOCKS) == (8, 16)
    call = next(line for line in text.splitlines()
                if "tpu_custom_call" in line and "custom-call(" in line)
    assert f"s32[16,{bmax // R}]" in call
    assert f"s32[16,{bmax // (R * CHUNK_BLOCKS)}]" in call
    # The prefill kernel walks the same pool page by page (4 kB a copy).
    for T in (256, 2048):
        _flash(sds, H, K, hd, T, (500000, BS), bmax, None)


# laguna-xs.2-ep2 (models/laguna.py): the two dense kernels at its full
# layers' geometry, 48 query heads over 8 key heads -- 6 a key head, no power
# of two and no multiple of the sublane tile -- and at its window layers' (64
# over 8, the cell's 59 slots of 512 rows read as 32 pages of 16 each, under
# the call's own name; the flash kernel behind one window's buffer).
def test_the_dense_kernels_compile_at_lagunas_geometries(
        sds, no_persistent_cache):
    from production_stack_tpu.engine.models.laguna import WINDOW_DECODE_KERNEL

    cfg = PRESETS["laguna-xs.2-ep2"]
    K, hd = cfg.num_kv_heads, cfg.head_dim
    full, window = cfg.attention_specs["full"], cfg.attention_specs["window"]
    assert (full.num_heads, window.num_heads, K, hd, window.window) == (
        48, 64, 8, 128, 512)
    assert full.num_heads // K == 6
    pages = sds((58_768, BS, K, hd), jnp.bfloat16)
    _compile(
        lambda q, k, v, bt, cl: paged_decode_attention_pallas(
            q, k, v, bt, cl, scale=hd ** -0.5),
        sds((16, full.num_heads, hd), jnp.bfloat16), pages, pages,
        sds((16, cfg.max_model_len // BS), jnp.int32), sds((16,), jnp.int32),
    )
    slots = sds((59 * 512 // BS, BS, K, hd), jnp.bfloat16)
    text = _compile(
        lambda q, k, v, bt, cl: paged_decode_attention_pallas(
            q, k, v, bt, cl, scale=hd ** -0.5, name=WINDOW_DECODE_KERNEL),
        sds((16, window.num_heads, hd), jnp.bfloat16), slots, slots,
        sds((16, 512 // BS), jnp.int32), sds((16,), jnp.int32),
    ).as_text()
    assert WINDOW_DECODE_KERNEL in text
    # The flash kernel behind the block pool's pages, and behind a window
    # layer's buffer: a pool of one 512-token page with the table [0].
    for T in (256, 2048):
        _flash(sds, full.num_heads, K, hd, T, (58_768, BS),
               cfg.max_model_len // BS, full.window)
        _flash(sds, window.num_heads, K, hd, T, (1, window.window), 1,
               window.window)


# A module that owns a state pool reads and writes slots where they lie
# (models/registry.py): the programs the engine serves it with -- the packed
# ``prefill_fn`` at 256 slots with a snapshot slot named, the module's
# ``decode`` at 16 rows and the ``window_fn`` (up to 8 steps) that loops it -- compiled at
# the cells' pool sizes (59 slots; the K/V pools the engine's log gives) with
# the cache tree donated, hold NO synchronous ``copy`` of an array the size of
# a cache leaf (a state pool, a convolution rows' pool, a K/V pool).  What the
# parent of PR 55 compiled to: 12 such copies in solar's prefill (6 of them the
# 247.5 MB delta-rule pool: XLA folded the prefill kernel's transposes into
# the pool's layout), 6 in its decode, 52 in each of jamba's (a ``[slots, 3,
# W]`` pool is kept taps-outermost at the program's boundary and slots-
# outermost by the gather and the scatter).  The ``copy-start``s are what the
# compiler's own staging leaves -- one layer's pool moved through the faster
# memory, asynchronously -- pinned by count, so that one more fails: {what:
# count}, "rows" the convolution rows' pools, "state" the state pools.  (A
# two-dimensional rows' pool, ``[slots, 3 W]``, passes the first half and
# reads 24 and 26 ``copy-start``s in jamba's decode and window: every layer's
# pool staged whole, a slot being a sub-tile line of it; on the chip solar's
# step was slower than the parent's.)
STATE_MODELS = {
    # preset: (module, K/V blocks of its cell, {program: copy-starts})
    "solar-open2-250b-ep8": ("solar_kda", 119_517, {
        "prefill": {}, "decode": {"rows": 1}, "window": {"rows": 1}}),
    "jamba2-3b": ("jamba", 525_184, {
        "prefill": {}, "decode": {"state": 1},
        "window": {"rows": 1, "state": 1}}),
    # "state": a window layer's rolling buffers, K and V (bf16, as pages are).
    "laguna-xs.2-ep2": ("laguna", 58_768, {
        "prefill": {}, "decode": {}, "window": {}}),
    # Pages of 32 key heads for 30 (whole bf16 tiles), a 96 x 192 state.
    "olmo-hybrid-7b-stage": ("olmo_hybrid", 42_000, {
        "prefill": {}, "decode": {"rows": 1}, "window": {"rows": 1}}),
}
@pytest.mark.parametrize("program", ["prefill", "decode", "window"])
@pytest.mark.parametrize("preset", list(STATE_MODELS))
def test_a_state_models_served_programs_copy_no_pool(
        one_chip, no_persistent_cache, monkeypatch, preset, program):
    import importlib

    from production_stack_tpu.engine.core import step_programs
    from production_stack_tpu.engine.kv.state_pool import pool_slots

    module, blocks, staged = STATE_MODELS[preset]
    model = importlib.import_module(
        f"production_stack_tpu.engine.models.{module}")
    cfg = PRESETS[preset]
    # The module asks JAX for its backend to pick the served kernels.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(
        cfg, blocks, BS, state_slots=1 + sum(pool_slots(16)))))
    arg = lambda dtype, *shape: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    i32, f32 = (functools.partial(arg, dt) for dt in (jnp.int32, jnp.float32))
    S, T, bmax = 16, 256, cfg.max_model_len // BS
    if program == "prefill":
        scalars = ("cached_len", "valid_len", "state_slot", "state_from",
                   "snapshot_slot", "snapshot_len")
        lowered = jax.jit(step_programs.prefill_program(
            functools.partial(model.prefill, cfg=cfg, return_stats=True),
            scalars, BS, bmax), donate_argnames=("kv_caches",),
        ).lower(params, i32(T + T // BS + bmax + len(scalars)),
                kv_caches=cache)
    elif program == "decode":
        lowered = jax.jit(
            functools.partial(model.decode, cfg=cfg),
            donate_argnames=("kv_caches",),
        ).lower(params, tokens=i32(S), positions=i32(S),
                block_tables=i32(S, bmax), ctx_lens=i32(S),
                slot_block_ids=i32(S), slot_offsets=i32(S), kv_caches=cache,
                state_slots=i32(S))
    else:
        lowered = jax.jit(step_programs.window_program(
            functools.partial(model.decode, cfg=cfg, return_stats=True),
            block_size=BS, n_steps=8, vocab=cfg.vocab_size,
            n_counts=len(model.stats_names(cfg))),
            static_argnames=("use_penalties", "use_min_floor"),
            donate_argnames=("kv_caches",),
        ).lower(
            params, tokens=i32(S), positions=i32(S), ctx_lens=i32(S),
            done=arg(jnp.bool_, S), min_left=i32(S), block_tables=i32(S, bmax),
            max_steps=i32(S), kv_caches=cache, temps=f32(S), top_ps=f32(S),
            top_ks=i32(S), min_ps=f32(S), seq_seeds=i32(S), stop_ids=i32(S, 4),
            key_base=i32(), counts=arg(jnp.int16, S, 1),
            seen=arg(jnp.bool_, S, 1), presence=f32(S), frequency=f32(S),
            repetition=f32(S), use_penalties=False, use_min_floor=False,
            state_slots=i32(S))
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    if program == "prefill":
        # Nor a copy of the cached prefix: the kernel walks the pages of the
        # block table itself (PR 62; before, each layer that keeps pages
        # gathered, concatenated and padded max_model_len positions a side).
        assert not _prefix_copies(
            text, cfg.max_model_len * cfg.num_kv_heads * cfg.head_dim)
    # The counter is the microbenchmark's (it counts the program that ran).
    from tools.state_pool_microbench import pool_copies

    from production_stack_tpu.engine.config import PAGED_KINDS

    leaves, what = [], []
    for i, pair in enumerate(cache):
        leaves += pair
        if cfg.layer_kind(i) in PAGED_KINDS:
            what += ("pages", "pages")
        elif pair[0].dtype == jnp.float32:
            what += ("state", "rows")
        else:
            what += ("state", "state")
    found = collections.Counter(
        (op, what[i]) for op, i in pool_copies(text, leaves))
    assert not {k: n for k, n in found.items() if k[0] == "copy"}
    for (_, name), n in found.items():
        assert n <= staged[program].get(name, 0), (name, n)


# mistral-7b with int8 weights (cells 1-2): the packed ``prefill_fn`` as the
# engine builds it, 256 slots behind a table of 8,192 positions, the cache tree
# donated.  Its 32 layers hold 32 flash kernels and nothing that copies a
# prefix: the parent of PR 62 compiled to 64 gathers of bf16[512,16,8,128], as
# many concatenates to [8448,8,128] and pads to [8704,8,128] -- 5.6 ms of a 27.8 ms
# program, whatever was cached.
def test_mistrals_served_prefill_copies_no_prefix(
        one_chip, no_persistent_cache, monkeypatch):
    import dataclasses

    from production_stack_tpu.engine.core import step_programs
    from production_stack_tpu.engine.models import llama

    cfg = dataclasses.replace(MISTRAL, quantization="int8")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(lambda: llama.quantize_params(
        llama.init_params(cfg, jax.random.PRNGKey(0)), cfg)))
    pool = jax.ShapeDtypeStruct(
        (3_000, BS, cfg.num_kv_heads, D), jnp.bfloat16, sharding=one_chip)
    T, bmax, scalars = 256, MAX_LEN // BS, ("cached_len", "valid_len")
    text = jax.jit(step_programs.prefill_program(
        functools.partial(llama.prefill, cfg=cfg), scalars, BS, bmax),
        donate_argnames=("kv_caches",), static_argnames=("prompt_topk",),
    ).lower(
        params, jax.ShapeDtypeStruct(
            (T + T // BS + bmax + len(scalars),), jnp.int32,
            sharding=one_chip),
        kv_caches=[(pool, pool)] * cfg.num_layers,
    ).compile().as_text()
    assert text.count("flash_prefill_attention") >= cfg.num_layers
    assert not _prefix_copies(text, MAX_LEN * cfg.num_kv_heads * D)
    # What the check is made of: it finds the gather where there is one.
    from production_stack_tpu.engine.ops.attention import gather_prefix_kv

    gathered = jax.jit(gather_prefix_kv).lower(
        pool, pool, jax.ShapeDtypeStruct((bmax,), jnp.int32, sharding=one_chip)
    ).compile().as_text()
    assert len(_prefix_copies(gathered, MAX_LEN * cfg.num_kv_heads * D)) == 2


# longcat-flash-omni-ep32 (models/longcat.py): the programs the engine serves
# it with -- the packed ``prefill_fn`` at both buckets and the ``window_fn`` (up
# to 8 steps) at 16 rows -- at the published widths and the cell's pool (eight
# cache arrays of ~29,700 blocks beside 10.34 GB of weights), the cache tree
# donated.  Each holds both latent kernels' custom calls, once a cache array;
# and what a program needs beside its arguments fits in what the engine leaves
# of the device (it fills ~15.2 of 16.9 GB): 0.81 GB for the 2,048-slot chunk,
# whose 24,576 (row, pick) pairs are gathered for the grouped products.
@pytest.mark.parametrize("program", ["prefill-256", "prefill-2048", "window"])
def test_longcats_served_programs_compile(
        one_chip, no_persistent_cache, monkeypatch, program):
    from production_stack_tpu.engine.core import step_programs
    from production_stack_tpu.engine.models import longcat as model

    cfg = PRESETS["longcat-flash-omni-ep32"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(cfg, 29_700, BS)))
    assert len(cache) == 8
    arg = lambda dtype, *shape: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    i32, f32 = (functools.partial(arg, dt) for dt in (jnp.int32, jnp.float32))
    S, bmax = 16, cfg.max_model_len // BS
    if program == "window":
        kernel = "latent_decode_attention_pallas"
        lowered = jax.jit(step_programs.window_program(
            functools.partial(model.decode, cfg=cfg, return_stats=True),
            block_size=BS, n_steps=8, vocab=cfg.vocab_size,
            n_counts=len(model.stats_names(cfg))),
            static_argnames=("use_penalties", "use_min_floor"),
            donate_argnames=("kv_caches",),
        ).lower(
            params, tokens=i32(S), positions=i32(S), ctx_lens=i32(S),
            done=arg(jnp.bool_, S), min_left=i32(S), block_tables=i32(S, bmax),
            max_steps=i32(S), kv_caches=cache, temps=f32(S), top_ps=f32(S),
            top_ks=i32(S), min_ps=f32(S), seq_seeds=i32(S), stop_ids=i32(S, 4),
            key_base=i32(), counts=arg(jnp.int16, S, 1),
            seen=arg(jnp.bool_, S, 1), presence=f32(S), frequency=f32(S),
            repetition=f32(S), use_penalties=False, use_min_floor=False)
    else:
        kernel = "latent_prefill_attention_pallas"
        T, scalars = int(program.split("-")[1]), ("cached_len", "valid_len")
        lowered = jax.jit(step_programs.prefill_program(
            functools.partial(model.prefill, cfg=cfg, return_stats=True),
            scalars, BS, bmax), donate_argnames=("kv_caches",),
            static_argnames=("prompt_topk",),
        ).lower(params, i32(T + T // BS + bmax + len(scalars)),
                kv_caches=cache)
    compiled = lowered.compile()
    assert compiled.as_text().count(kernel) >= 8
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1.2e9
    assert memory.alias_size_in_bytes >= 8 * 29_700 * BS * 640 * 2   # donated


def test_int8_kv_decode_kernel_is_still_refused(sds, no_persistent_cache):
    """ROADMAP S10: Mosaic refuses the int8-KV decode kernel (the
    [N, bs, K] fp32 scale planes are no 128-lane DMA slice, and since the
    tiles go to the MXU as [T*K, D] their [C, bs, K] -> [T*K, 1] shape
    cast is refused first), which is why
    the engine refuses ``--kv-cache-dtype int8`` on a TPU at boot
    (test_engine_refuses_int8_kv_on_tpu_at_boot).  The day this test fails
    the kernel compiles: lift the refusal and compile it above instead."""
    H, K = ONE_CHIP
    S, N, bmax = 8, 1024, MAX_LEN // BS

    cache = (sds((N, BS, K, D), jnp.int8), sds((N, BS, K), jnp.float32))
    with pytest.raises(
            Exception, match="aligned to tiling|unsupported shape cast"):
        _compile(
            lambda q, k, v, bt, cl: paged_decode_attention_pallas(
                q, k, v, bt, cl, scale=SCALE, sliding_window=WINDOW
            ),
            sds((S, H, D), jnp.bfloat16), cache, cache,
            sds((S, bmax), jnp.int32), sds((S,), jnp.int32),
        )


def test_engine_refuses_int8_kv_on_tpu_at_boot():
    """Not at the first request, and not by quietly taking the gather
    path: the boot-time report raises on a TPU (steered here by the
    device report, the way the on-chip guide says a test steers code that
    asks JAX for its backend)."""
    from production_stack_tpu.engine.config import config_from_preset
    from production_stack_tpu.engine.core.engine import LLMEngine
    from production_stack_tpu.engine.parallel.mesh import single_device_mesh

    class Boot:
        config = config_from_preset(
            "tiny-llama", **{"cache.kv_cache_dtype": "int8"}
        )
        mesh = single_device_mesh()

        def device_report(self):
            return {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                    "mesh": {"dp": 1, "tp": 1, "sp": 1}}

    with pytest.raises(ValueError, match="kv-cache-dtype int8"):
        LLMEngine._report_device_and_kernels(Boot())
    Boot.config.cache.kv_cache_dtype = "auto"
    LLMEngine._report_device_and_kernels(Boot())  # bf16 KV boots


def test_kv_pool_is_sized_from_every_device_and_never_guessed_on_a_tpu():
    """On the CPU 512 blocks; on an accelerator weights + KV stay inside
    hbm_utilization of the fullest device, and a device that reports no
    memory limit is an error, not a silent 512-block (8k-token) pool."""
    from production_stack_tpu.engine.config import config_from_preset
    from production_stack_tpu.engine.core.engine import LLMEngine

    GB = 10**9

    class Boot:
        config = config_from_preset("tiny-llama")
        _kv_bytes = LLMEngine._kv_bytes
        _state_bytes = LLMEngine._state_bytes
        state_pool = None
        memory = []

        def device_report(self):
            return {"platform": "tpu", "memory": self.memory}

    boot = Boot()
    per_block = boot._kv_bytes(1)
    boot.memory = [
        {"id": 0, "bytes_limit": 16 * GB, "bytes_in_use": 4 * GB},
        {"id": 1, "bytes_limit": 16 * GB, "bytes_in_use": 8 * GB},  # fullest
    ]
    assert LLMEngine._decide_num_blocks(boot) == int(
        (0.9 * 16 * GB - 8 * GB) // per_block
    )
    boot.memory = [{"id": 0, "bytes_limit": None, "bytes_in_use": None}]
    with pytest.raises(RuntimeError, match="no bytes_limit"):
        LLMEngine._decide_num_blocks(boot)
    boot.device_report = lambda: {"platform": "cpu", "memory": boot.memory}
    assert LLMEngine._decide_num_blocks(boot) == 512


def test_the_state_pools_bytes_come_off_what_the_kv_pool_is_sized_from():
    """jamba2-3b at the published widths: 59 slots x 9,318,400 B of state are
    taken off the chip's budget before the 16 kB blocks (1,024 B a position)
    are counted: ~8 M positions, half a million blocks."""
    from production_stack_tpu.engine.config import config_from_preset
    from production_stack_tpu.engine.core.engine import LLMEngine
    from production_stack_tpu.engine.kv.state_pool import StatePool, pool_slots
    from production_stack_tpu.engine.models import jamba

    GB = 10**9

    class Boot:
        config = config_from_preset(
            "jamba2-3b", **{"scheduler.max_num_seqs": 16})
        model = jamba
        _kv_bytes = LLMEngine._kv_bytes
        _state_bytes = LLMEngine._state_bytes
        state_pool = StatePool(*pool_slots(16))

        def device_report(self):
            return {"platform": "tpu", "memory": [
                {"id": 0, "bytes_limit": 16 * GB, "bytes_in_use": 6 * GB}]}

    boot = Boot()
    assert boot.state_pool.num_slots == 59
    assert boot._state_bytes() == 59 * 9_318_400
    assert boot._kv_bytes(1) == 16 * 1024
    blocks = LLMEngine._decide_num_blocks(boot)
    assert blocks == int((0.9 * 16 * GB - 6 * GB - 59 * 9_318_400) // 16384)
    assert 450_000 < blocks < 520_000
