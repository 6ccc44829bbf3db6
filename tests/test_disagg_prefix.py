"""Cross-engine prefix sharing / disaggregated prefill
(CacheConfig.disagg_role).

A "prefill"-role engine exports full prompt blocks to the shared store
under content keys (the prefix-cache hash chain); a "decode"-role engine
with a cold local cache imports them on admission instead of recomputing.
The reference lists disaggregated prefill as roadmap-only (README.md:57,
docs/source/tutorials/disagg.rst); this is the working TPU-native
mechanism, built on the kvserver tier.
"""

import asyncio
import threading

import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.sequence import SamplingParams
from production_stack_tpu.engine.kv.block_pool import prefix_block_hashes
from production_stack_tpu.kvserver.server import KVStore, handle_client


@pytest.fixture()
def kv_port():
    store = KVStore(capacity_bytes=64 << 20)
    loop = asyncio.new_event_loop()
    started = threading.Event()
    state = {}

    def run():
        asyncio.set_event_loop(loop)

        async def boot():
            server = await asyncio.start_server(
                lambda r, w: handle_client(store, r, w), "127.0.0.1", 0
            )
            state["port"] = server.sockets[0].getsockname()[1]
            started.set()

        loop.run_until_complete(boot())
        loop.run_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(5)
    yield state["port"]
    loop.call_soon_threadsafe(loop.stop)
    t.join(timeout=5)


def make_engine(role, port, prefetch=None):
    """``prefetch=False`` pins the legacy synchronous remote-prefix path
    (cache.remote_prefetch) for the tests that unit-test it directly;
    the default exercises the async admission-time prefetch plane."""
    return LLMEngine(EngineConfig(
        model=ModelConfig(dtype="float32"),
        cache=CacheConfig(
            block_size=4,
            num_blocks=64,
            remote_kv_url=f"kv://127.0.0.1:{port}",
            disagg_role=role,
            remote_prefetch=prefetch,
        ),
        scheduler=SchedulerConfig(
            max_num_seqs=2, prefill_buckets=(16, 32, 64), max_model_len=128
        ),
    ))


PROMPT = "the quick brown fox jumps over the lazy dog again and again"


def drain(engine, rid, max_tokens=6, close=True):
    engine.add_request(rid, prompt=PROMPT,
                       sampling_params=SamplingParams(max_tokens=max_tokens))
    # The async prefetch plane resolves the store in the background; the
    # data-plane assertions here are about WHAT is imported, not when, so
    # let the in-flight fetch land before stepping (a real serving loop
    # would simply import on a later pass).
    engine.flush_prefix_imports()
    tokens = []
    steps = 0
    while engine.has_unfinished():
        steps += 1
        assert steps < 200
        for out in engine.step():
            tokens.append(out.new_token_id)
    if close and engine.offload.remote_client is not None:
        # Leaving the blocking socket open past the server loop's stop
        # raises "Event loop is closed" in the server's reader task.
        engine.offload.remote_client.close()
    return tokens


def test_prefill_role_exports_decode_role_imports(kv_port):
    producer = make_engine("prefill", kv_port)
    out_a = drain(producer, "a", close=False)
    producer.flush_prefix_exports()
    producer.offload.remote_client.close()
    assert producer.remote_prefix_blocks_exported > 0
    assert producer.remote_prefix_blocks_fetched == 0  # prefill never imports

    consumer = make_engine("decode", kv_port)
    out_b = drain(consumer, "b")
    # The consumer imported blocks it never computed...
    assert consumer.remote_prefix_blocks_fetched > 0
    assert consumer.remote_prefix_blocks_exported == 0
    # ...and still produces bit-identical greedy output.
    assert out_b == out_a

    # Baseline engine with no sharing agrees too (the imported KV is real).
    baseline = make_engine(None, kv_port)
    assert drain(baseline, "c") == out_a


def test_both_role_dedupes_reexport(kv_port):
    engine = make_engine("both", kv_port)
    drain(engine, "r1", close=False)
    engine.flush_prefix_exports()
    first = engine.remote_prefix_blocks_exported
    assert first > 0
    # Same prompt again within the dedupe TTL: every block digest is in
    # the export LRU (and the local prefix cache serves the match), so
    # nothing re-uploads.
    drain(engine, "r2", close=False)
    engine.flush_prefix_exports()
    assert engine.remote_prefix_blocks_exported == first
    engine.offload.remote_client.close()


def test_cross_model_blocks_never_imported(kv_port):
    """Content keys carry a model fingerprint (shape + weight sample):
    a peer serving a different model must never poison this engine."""
    producer = make_engine("prefill", kv_port)
    drain(producer, "a", close=False)
    producer.flush_prefix_exports()
    producer.offload.remote_client.close()

    other = LLMEngine(EngineConfig(
        model=ModelConfig(name="llama-debug-1l", num_layers=1, dtype="float32"),
        cache=CacheConfig(
            block_size=4, num_blocks=64,
            remote_kv_url=f"kv://127.0.0.1:{kv_port}",
            disagg_role="decode",
        ),
        scheduler=SchedulerConfig(
            max_num_seqs=2, prefill_buckets=(16, 32, 64), max_model_len=128
        ),
    ))
    out = drain(other, "b")
    assert len(out) == 6
    assert other.remote_prefix_blocks_fetched == 0

    # Same architecture but different weights (different seed): the
    # embedding fingerprint differs, so nothing is imported either.
    reseeded = LLMEngine(EngineConfig(
        model=ModelConfig(dtype="float32"),
        cache=CacheConfig(
            block_size=4, num_blocks=64,
            remote_kv_url=f"kv://127.0.0.1:{kv_port}",
            disagg_role="decode",
        ),
        scheduler=SchedulerConfig(
            max_num_seqs=2, prefill_buckets=(16, 32, 64), max_model_len=128
        ),
        seed=12345,
    ))
    drain(reseeded, "c")
    assert reseeded.remote_prefix_blocks_fetched == 0


def test_store_outage_degrades_gracefully(kv_port):
    engine = make_engine("decode", kv_port)
    # Point the client at a dead port: fetch must fail soft, not raise.
    engine.offload.remote_client.port = 1
    engine.offload.remote_client._reset()
    out = drain(engine, "x")
    assert len(out) == 6
    assert engine.remote_prefix_blocks_fetched == 0


def test_disagg_through_native_cpp_kvserver(tmp_path):
    """The production tier: the same export/import flow over the C++
    epoll server (native/kvserver) instead of the Python asyncio twin —
    the wire protocol and content keys must be implementation-agnostic."""
    import shutil
    import subprocess
    from pathlib import Path

    import pytest

    native_dir = Path(__file__).resolve().parent.parent / "native" / "kvserver"
    if shutil.which("g++") is None or shutil.which("make") is None:
        pytest.skip("no C++ toolchain")
    build = subprocess.run(
        ["make", "-C", str(native_dir)], capture_output=True, text=True
    )
    if build.returncode != 0:
        pytest.fail(f"native kvserver build failed:\n{build.stderr}")
    proc = subprocess.Popen(
        [str(native_dir / "kvserver"), "--host", "127.0.0.1", "--port", "0",
         "--capacity-gb", "0.0625"],
        stdout=subprocess.PIPE,
        text=True,
    )
    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("LISTENING "), line
        port = int(line.split()[1])

        producer = make_engine("prefill", port)
        out_a = drain(producer, "a", close=False)
        producer.flush_prefix_exports()
        producer.offload.remote_client.close()
        assert producer.remote_prefix_blocks_exported > 0

        consumer = make_engine("decode", port)
        out_b = drain(consumer, "b")
        assert consumer.remote_prefix_blocks_fetched > 0
        assert out_b == out_a
    finally:
        proc.terminate()
        proc.wait(timeout=5)


def test_malformed_store_entry_leaks_no_blocks(kv_port):
    """A polluted store (wrong layer count / block shape) must degrade to
    local-only prefill WITHOUT leaking pool blocks — host arrays are
    validated before allocation (advisor r4 finding)."""
    import numpy as np

    # The sync path validates at the consume site; the async plane's
    # equivalent (import-time validation) is covered in
    # tests/test_kv_prefetch.py.
    engine = make_engine("decode", kv_port, prefetch=False)
    engine.offload.remote_client.close()

    class PollutedClient:
        def get_blocks(self, key):
            # One bogus layer where the model has many: np.stack over
            # layer_idx > 0 raises IndexError during validation.
            bad = np.zeros((1, 2, 2), np.float32)
            return ([(bad, bad)], 4)

        def close(self):
            pass

    engine.offload.remote_client = PollutedClient()
    engine.add_request("r", prompt=PROMPT,
                       sampling_params=SamplingParams(max_tokens=2))
    seq = engine.scheduler.waiting[0]
    free_before = engine.block_pool.num_free_blocks
    blocks, cached = engine.fetch_remote_prefix(seq, [], 0)
    assert (blocks, cached) == ([], 0)
    assert engine.block_pool.num_free_blocks == free_before
    assert engine.remote_prefix_blocks_fetched == 0
    # And the engine still serves the request (local prefill).
    tokens = []
    while engine.has_unfinished():
        for out in engine.step():
            tokens.append(out.new_token_id)
    assert len(tokens) == 2


def test_prefix_hash_memo_follows_prompt_growth(kv_port):
    """Recompute-preemption absorbs generated tokens into
    prompt_token_ids; the per-seq chain must follow (advisor r4).  Tokens
    only append, so the memo is extended, never thrown away: the blocks
    hashed before are not hashed again."""
    engine = make_engine("decode", kv_port)
    engine.offload.remote_client.close()
    engine.offload.remote_client = None
    engine.add_request("r", prompt=PROMPT,
                       sampling_params=SamplingParams(max_tokens=2))
    seq = engine.scheduler.waiting[0]
    bs = engine.block_pool.block_size
    h1 = engine._seq_prefix_hashes(seq)
    hashed = engine.block_pool.chain_blocks_hashed
    assert engine._seq_prefix_hashes(seq) == h1  # memo hit
    assert engine.block_pool.chain_blocks_hashed == hashed
    seq.prompt_token_ids = list(seq.prompt_token_ids) + list(range(7, 7 + bs))
    h2 = engine._seq_prefix_hashes(seq)
    assert len(h2) == len(h1) + 1
    assert h2[: len(h1)] == h1  # chain prefix property preserved
    assert engine.block_pool.chain_blocks_hashed == hashed + 1
    assert h2 == prefix_block_hashes(seq.prompt_token_ids, bs)


def test_disagg_role_requires_remote_url():
    with pytest.raises(ValueError, match="remote_kv_url"):
        CacheConfig(disagg_role="prefill")
    with pytest.raises(ValueError, match="disagg_role"):
        CacheConfig(disagg_role="weird", remote_kv_url="kv://x:1")


class _InfiniteStoreClient:
    """Stub remote client serving a valid block entry for EVERY key —
    the adversarial store whose hash chain covers the whole prompt."""

    def __init__(self, engine):
        cfg = engine.config.model
        bs = engine.block_pool.block_size
        import numpy as np

        blk = np.zeros((1, bs, cfg.num_kv_heads, cfg.head_dim), np.float32)
        self._entry = (
            [(blk, blk) for _ in range(cfg.num_layers)],
            bs,
        )
        self.gets = 0

    def get_blocks(self, key):
        self.gets += 1
        return self._entry


def test_remote_prefix_extension_clamped_to_prompt_minus_one(kv_port):
    """The local match_prefix leaves >= 1 token uncached by
    construction, and today the fetch keys (prefix_block_hashes) carry
    the same bound — so this exercises fetch_remote_prefix's OWN
    defense-in-depth clamp by injecting the state a future loosening of
    the shared hash helper would produce: a chain covering the ENTIRE
    prompt, which unclamped would yield a PrefillPlan with
    num_new_tokens == 0 and no valid last-token logits.
    fetch_remote_prefix must cap the extension at
    num_prompt_tokens - 1 regardless of what the chain covers."""
    from production_stack_tpu.engine.kv.block_pool import _chain_hash

    engine = make_engine("decode", kv_port, prefetch=False)
    engine.offload.remote_client.close()
    engine.offload.remote_client = _InfiniteStoreClient(engine)
    bs = engine.block_pool.block_size
    # Prompt an exact multiple of the block size: an unclamped chain of
    # len(prompt)/bs blocks covers every token.
    prompt_ids = [(5 * i + 1) % 101 for i in range(4 * bs)]
    engine.add_request("r", prompt_token_ids=prompt_ids,
                       sampling_params=SamplingParams(max_tokens=2))
    seq = engine.scheduler.waiting[0]
    # Simulate the peer's unclamped chain: one digest per FULL block of
    # the whole prompt (local prefix_block_hashes stops at len-1).
    prev = None
    full_chain = []
    for start in range(0, len(prompt_ids), bs):
        prev = _chain_hash(prev, prompt_ids[start : start + bs])
        full_chain.append(prev)
    seq.prefix_chain = list(full_chain)

    blocks, cached = engine.fetch_remote_prefix(seq, [], 0)
    assert cached <= len(prompt_ids) - 1
    assert cached == ((len(prompt_ids) - 1) // bs) * bs
    assert len(blocks) == cached // bs
    # The plan built from this extension always has work to prefill.
    engine.block_pool.free(blocks)
    assert seq.prefix_chain == full_chain  # memo survives the free
    tokens = []
    steps = 0
    while engine.has_unfinished():
        steps += 1
        assert steps < 100
        for out in engine.step():
            tokens.append(out.new_token_id)
    assert len(tokens) == 2
