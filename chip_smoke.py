"""The quickest proof that the serving path still starts on the chip.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # one four-chip host, run by the builder

One chip, in this order:

1. **Serve.**  The parent has not imported JAX (a chip belongs to one
   process).  It starts ``api_server --model mistral-7b --quantization int8
   --max-model-len 8192`` (published widths, all 32 layers, seeded random
   weights) and ``router.app`` in front of it as child processes, and
   through the router asks for the model list, one non-streamed and one
   streamed chat completion, a second round of the streamed conversation
   (prefix-cache hit), and one prompt of more than 2048 tokens.  Then it
   reads the engine's /metrics and /debug/compiles: KV usage and prefix
   hits non-zero, the device report says ``tpu``, the compiled decode and
   prefill programs hold Pallas kernels.  SIGTERM to both; both exit 0.
2. **Compare**, in the parent, after both children have exited: at
   mistral-7b widths and a few layers, prefill (without and with a cached
   prefix) then decode steps through the paged cache, Pallas kernels
   against the XLA reference path, logits to logits.
3. The last line: ``{"ok": true, "device": {...}}`` from ``jax.devices()``.

``--chips 4`` runs only the four-chip path: the bf16 model (14.5 GB, which
one chip cannot hold) behind ``--tensor-parallel 4``, the same requests,
weights spread evenly over the devices; then the logits of a tp=4 mesh
against one device, same seed, at a depth one chip holds.

Any failed check raises: the exit code is non-zero and the last line is
never printed.  Seconds printed here are wall time including compilation,
to show where a run's time went.  They are not a metric.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import http.client
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

MODEL = "mistral-7b"
PLATFORM = "tpu"  # anything else is a failure, never a fallback
MAX_MODEL_LEN = 8192
# Two prefill shapes compile: a short-prompt bucket and the largest bucket.
PREFILL_BUCKETS = "256,2048"
LONG_PROMPT_TOKENS = 2048
ENGINE_ARGS = {
    1: ["--quantization", "int8"],
    4: ["--tensor-parallel", "4"],
}
BOOT_TIMEOUT_S = 700.0
REQUEST_TIMEOUT_S = 400.0
COMPARE_LAYERS = {1: 2, 4: 4}

# Logits tolerance of the Compare phase, as max|a - b| / max|b|.  Both
# sides round activations to bf16 (8 bits of mantissa, relative 2^-9 per
# rounding) after every projection; the XLA reference additionally rounds
# softmax probabilities to bf16 before the PV product where the kernels
# keep float32, and a tp=4 mesh sums its partial products in another
# order.  Over a few layers that compounds to a few bf16 ulps of the
# logits' scale; 3e-2 is about 8 of them.  A wrong mask, a wrong block or
# a dropped head moves logits by their own scale (~1.0 on this measure).
LOGITS_RTOL = 3e-2


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


_T0 = time.monotonic()


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError("FAILED: " + what)
    say(f"ok: {what}")


# -- phase 1: serve ---------------------------------------------------------


def http_text(port: int, method: str, path: str, body=None,
              timeout: float = REQUEST_TIMEOUT_S) -> str:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        payload = None if body is None else json.dumps(body)
        conn.request(method, path, payload,
                     {"content-type": "application/json"})
        resp = conn.getresponse()
        data = resp.read().decode()
        if resp.status != 200:
            raise RuntimeError(f"{method} {path}: {resp.status} {data[:500]}")
        return data
    finally:
        conn.close()


def http_json(port: int, method: str, path: str, body=None):
    return json.loads(http_text(port, method, path, body))


def stream_chat(port: int, body: dict) -> dict:
    """One streamed chat completion over SSE; returns text, event count,
    finish reason and usage."""
    conn = http.client.HTTPConnection(
        "127.0.0.1", port, timeout=REQUEST_TIMEOUT_S
    )
    out = {"text": "", "events": 0, "finish_reason": None, "usage": None,
           "done": False}
    try:
        conn.request("POST", "/v1/chat/completions", json.dumps(body),
                     {"content-type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise RuntimeError(f"stream: {resp.status} {resp.read()[:500]}")
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            data = line[5:].strip()
            if data == "[DONE]":
                out["done"] = True
                break
            event = json.loads(data)
            out["events"] += 1
            if event.get("usage"):
                out["usage"] = event["usage"]
            for choice in event.get("choices", []):
                out["text"] += choice.get("delta", {}).get("content") or ""
                if choice.get("finish_reason"):
                    out["finish_reason"] = choice["finish_reason"]
    finally:
        conn.close()
    return out


def metric_values(text: str, name: str) -> list:
    """Every sample of one metric family in Prometheus text."""
    values = []
    for line in text.splitlines():
        if line.startswith(name) and line[len(name):][:1] in (" ", "{"):
            values.append(float(line.rpartition(" ")[2]))
    return values


def chat_body(messages, max_tokens: int, stream: bool = False) -> dict:
    body = {
        "model": MODEL, "messages": messages, "max_tokens": max_tokens,
        # Random weights: greedy, and no early EOS, so the decode window
        # runs as many times as max_tokens says.
        "temperature": 0.0, "min_tokens": max_tokens,
    }
    if stream:
        body["stream"] = True
        body["stream_options"] = {"include_usage": True}
    return body


def serve(chips: int) -> None:
    from production_stack_tpu.testing.procs import Child, free_port

    engine_port, router_port = free_port(), free_port()
    engine = Child("engine", [
        sys.executable, "-m", "production_stack_tpu.engine.server.api_server",
        "--model", MODEL, "--port", str(engine_port), "--host", "127.0.0.1",
        "--max-model-len", str(MAX_MODEL_LEN),
        "--prefill-buckets", PREFILL_BUCKETS,
        *ENGINE_ARGS[chips],
    ], OUT_DIR, cwd=REPO)
    router = Child("router", [
        sys.executable, "-m", "production_stack_tpu.router.app",
        "--port", str(router_port), "--host", "127.0.0.1",
        "--static-backends", f"http://127.0.0.1:{engine_port}",
        "--static-models", MODEL,
        "--engine-stats-interval", "2",
    ], OUT_DIR, cwd=REPO)
    try:
        say("engine: " + " ".join(engine.cmd[2:]))
        engine.start()
        boot_s = engine.wait_http_ok(
            f"http://127.0.0.1:{engine_port}/health", BOOT_TIMEOUT_S
        )
        say(f"engine boot: {boot_s:.1f} s wall (weights made, quantized and "
            "placed; KV pool allocated)")
        router.start()
        router.wait_http_ok(f"http://127.0.0.1:{router_port}/health", 120.0)
        _drive(chips, engine_port, router_port)
        # The drain contract: SIGTERM, in-flight work finishes, exit 0.
        check(router.stop() == 0, "router exited 0 on SIGTERM")
        check(engine.stop(grace_s=120.0) == 0, "engine exited 0 on SIGTERM")
    except BaseException:
        for child in (router, engine):
            if child.proc is not None:
                print(f"--- tail of {child.log_path} ---\n{child.tail()}",
                      flush=True)
        raise
    finally:
        router.stop(grace_s=10.0)
        engine.stop(grace_s=10.0)


def _drive(chips: int, engine_port: int, router_port: int) -> None:
    models = http_json(router_port, "GET", "/v1/models")
    check(MODEL in [m["id"] for m in models["data"]],
          f"/v1/models through the router lists {MODEL}")

    question = (
        "Here is a long first question so that the conversation spans "
        "several KV blocks: explain how a paged key-value cache lets a "
        "serving engine share a common prefix between two requests, and "
        "what the block table holds for each sequence."
    )
    t0 = time.monotonic()
    first = http_json(
        router_port, "POST", "/v1/chat/completions",
        chat_body([{"role": "user", "content": "Warm-up: " + question}], 16),
    )
    first_s = time.monotonic() - t0
    check(first["usage"]["completion_tokens"] == 16
          and isinstance(first["choices"][0]["message"]["content"], str),
          "non-streamed chat completion: 16 tokens")
    say(f"first request: {first_s:.1f} s wall including compile")

    # Streamed, >= 64 tokens: the K-step decode window (and the Pallas
    # decode kernel inside it) runs many times.  KV usage is polled while
    # the stream is live: a finished request's blocks count as free.
    # (Random weights over a 32000-entry vocabulary mostly sample ids the
    # byte tokenizer has no text for, so few events carry content: the
    # token count comes from the usage block.)
    usage_seen, stream_over = [], threading.Event()

    def poll_kv_usage() -> None:
        while not stream_over.wait(0.05):
            usage_seen.extend(metric_values(
                http_text(engine_port, "GET", "/metrics", timeout=10),
                "tpu:hbm_kv_usage_perc",
            ))

    round1 = [{"role": "user", "content": question}]
    poller = threading.Thread(target=poll_kv_usage, daemon=True)
    poller.start()
    try:
        streamed = stream_chat(router_port, chat_body(round1, 96, stream=True))
    finally:
        stream_over.set()
        poller.join(15)
    check(streamed["done"] and streamed["finish_reason"] == "length"
          and streamed["usage"]["completion_tokens"] == 96,
          f"streamed chat completion: 96 tokens, {streamed['events']} SSE "
          "events, [DONE] received")
    check(bool(usage_seen) and max(usage_seen) > 0,
          f"tpu:hbm_kv_usage_perc non-zero mid-stream (max {max(usage_seen, default=0):.5f} "
          f"of {len(usage_seen)} readings)")

    # Round two of the same conversation: its prompt starts with round
    # one's, so prefill runs on a cached prefix (flash C > 0 path).
    round2 = round1 + [
        {"role": "assistant",
         "content": streamed["text"] or "(no printable text)"},
        {"role": "user", "content": "And what happens on a miss?"},
    ]
    second = http_json(
        router_port, "POST", "/v1/chat/completions", chat_body(round2, 16),
    )
    check(second["usage"]["completion_tokens"] == 16,
          "second round of the conversation: 16 tokens")

    # Byte tokenizer: about one token a byte.
    long_prompt = "tokens " * (LONG_PROMPT_TOKENS // 7 + 1)
    long = http_json(
        router_port, "POST", "/v1/chat/completions",
        chat_body([{"role": "user", "content": long_prompt}], 8),
    )
    check(long["usage"]["prompt_tokens"] >= LONG_PROMPT_TOKENS
          and long["usage"]["completion_tokens"] == 8,
          f"long prompt: {long['usage']['prompt_tokens']} prompt tokens "
          "(largest prefill bucket), 8 tokens out")

    t0 = time.monotonic()
    http_json(
        router_port, "POST", "/v1/chat/completions",
        chat_body([{"role": "user", "content": "Steady: " + question}], 16),
    )
    say(f"steady request (every shape compiled): "
        f"{time.monotonic() - t0:.2f} s wall")

    metrics = http_text(engine_port, "GET", "/metrics")
    hits = metric_values(metrics, "tpu:prefix_cache_hit_tokens_total")
    check(bool(hits) and max(hits) > 0,
          f"tpu:prefix_cache_hit_tokens_total > 0 ({hits})")

    compiles = http_json(engine_port, "GET", "/debug/compiles")
    device = compiles["device"]
    say(f"engine device report: {json.dumps(device)}")
    check(device["platform"] == PLATFORM and device["count"] == chips,
          f"the engine holds {chips} {PLATFORM} device(s)")
    cache = compiles["persistent_cache"]
    say(f"compiles: {compiles['compiled_shapes']} programs, "
        f"{compiles['compile_seconds']:.1f} s wall in total; persistent cache "
        f"{cache['dir']}: {cache['hits']} hits, {cache['misses']} misses")
    for row in compiles["executables"]:
        say(f"  {row['seconds']:7.1f} s  kernels={row['kernels']}  "
            f"{row['executable'][:80]}")
    if PLATFORM == "tpu":
        # A step that quietly took the XLA gather/dense path is a failure.
        # Under a tp mesh prefill takes the dense path by design
        # (ops/attention.py prefill_attention): only decode is checked.
        families = ("window_fn", "prefill_fn") if chips == 1 else ("window_fn",)
        for family in families:
            rows = [r for r in compiles["executables"]
                    if r["executable"].startswith(family + "[")]
            check(bool(rows) and all((r["kernels"] or 0) > 0 for r in rows),
                  f"every compiled {family} program holds Pallas kernels "
                  f"({[r['kernels'] for r in rows]})")
    if chips > 1:
        in_use = [m["bytes_in_use"] for m in device["memory"]]
        check(None not in in_use, "every device reports its bytes_in_use")
        say("bytes_in_use per device: " + ", ".join(
            f"{b / 2**30:.2f} GiB" for b in in_use))
        check(len(in_use) == chips and max(in_use) <= 1.1 * min(in_use),
              "weights and KV are spread evenly over the devices "
              "(nothing piled on the first)")


# -- phase 2: compare -------------------------------------------------------


@contextlib.contextmanager
def xla_reference_path():
    """Trace with both Pallas kernels switched off (the engine's own A/B
    switch, read at trace time by ops/attention.py)."""
    os.environ["PSTPU_DISABLE_PALLAS"] = "1"
    try:
        yield
    finally:
        del os.environ["PSTPU_DISABLE_PALLAS"]


def model_logits(jax, cfg, seed: int, mesh, label: str):
    """Prefill two sequences (one in two chunks, so the second chunk
    attends to a cached prefix), then four decode steps, through the paged
    cache.  Returns (logits of every step, compiled HLO text of the decode
    step).  ``mesh=None`` is one device."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from production_stack_tpu.engine.models import llama
    from production_stack_tpu.engine.parallel import shardings as sh

    bs, num_blocks, bmax, T = 16, 96, 64, 256
    kv_shape = (num_blocks, bs, cfg.num_kv_heads, cfg.head_dim)
    if mesh is None:
        shardings = kv_sharding = None
    else:
        shardings = sh.param_shardings(cfg, mesh)
        kv_sharding = NamedSharding(mesh, sh.kv_cache_spec())
    params = llama.init_params(cfg, jax.random.PRNGKey(seed), shardings)
    zeros = jax.jit(
        lambda: jnp.zeros(kv_shape, cfg.dtype), out_shardings=kv_sharding
    )
    kv = [(zeros(), zeros()) for _ in range(cfg.num_layers)]

    rng = np.random.default_rng(seed)
    prompt_a = rng.integers(1, cfg.vocab_size, 300).astype(np.int32)
    prompt_b = rng.integers(1, cfg.vocab_size, 100).astype(np.int32)
    decode_tokens = rng.integers(1, cfg.vocab_size, (4, 2)).astype(np.int32)
    blocks_a = np.arange(1, 33, dtype=np.int32)  # block 0 is the null block
    blocks_b = np.arange(40, 72, dtype=np.int32)

    # Fresh callables per path: the kernel switch is read at trace time.
    prefill = jax.jit(
        lambda p, t, c, pre, new, v, kv: llama.prefill(
            p, cfg, t, c, pre, new, v, kv, mesh=mesh),
        donate_argnums=(6,),
    )
    decode = jax.jit(
        lambda p, t, pos, bt, cl, sb, so, kv: llama.decode(
            p, cfg, t, pos, bt, cl, sb, so, kv, mesh=mesh),
        donate_argnums=(7,),
    )

    def run_prefill(prompt, start, blocks, kv):
        chunk = prompt[start:start + T]
        tokens = np.zeros((T,), np.int32)
        tokens[:len(chunk)] = chunk
        prefix = np.zeros((bmax,), np.int32)
        prefix[:start // bs] = blocks[:start // bs]
        new = np.zeros((T // bs,), np.int32)
        n_new = -(-len(chunk) // bs)
        new[:n_new] = blocks[start // bs:start // bs + n_new]
        return prefill(params, jnp.asarray(tokens), jnp.int32(start),
                       jnp.asarray(prefix), jnp.asarray(new),
                       jnp.int32(len(chunk)), kv)

    logits = []
    _, kv = run_prefill(prompt_a, 0, blocks_a, kv)  # chunk 1: no prefix
    out, kv = run_prefill(prompt_a, T, blocks_a, kv)  # chunk 2: 256 cached
    logits.append(np.asarray(out, np.float32))
    out, kv = run_prefill(prompt_b, 0, blocks_b, kv)
    logits.append(np.asarray(out, np.float32))

    tables = np.zeros((2, bmax), np.int32)
    tables[0, :32], tables[1, :32] = blocks_a, blocks_b
    ctx = np.array([len(prompt_a), len(prompt_b)], np.int32)
    hlo = None
    for step in range(4):
        ctx = ctx + 1
        pos = ctx - 1
        args = (
            params, jnp.asarray(decode_tokens[step]), jnp.asarray(pos),
            jnp.asarray(tables), jnp.asarray(ctx),
            jnp.asarray(tables[np.arange(2), pos // bs]),
            jnp.asarray(pos % bs), kv,
        )
        if hlo is None:
            hlo = decode.lower(*args).compile().as_text()
        out, kv = decode(*args)
        logits.append(np.asarray(out, np.float32))
    check(all(bool(np.isfinite(x).all()) and x.shape[-1] == cfg.vocab_size
              for x in logits),
          f"{label}: {len(logits)} steps of finite logits, vocabulary wide")
    return logits, hlo


def compare(chips: int, seed: int) -> None:
    import jax
    import numpy as np

    from production_stack_tpu.engine.config import PRESETS, ParallelConfig
    from production_stack_tpu.engine.parallel.mesh import build_mesh

    check(jax.default_backend() == PLATFORM,
          f"the parent's JAX backend is {PLATFORM}")
    cfg = dataclasses.replace(PRESETS[MODEL], num_layers=COMPARE_LAYERS[chips])
    say(f"compare: {MODEL} widths (hidden {cfg.hidden_size}, "
        f"{cfg.num_heads}/{cfg.num_kv_heads} heads x {cfg.head_dim}, FFN "
        f"{cfg.intermediate_size}, vocab {cfg.vocab_size}), "
        f"{cfg.num_layers} layers, bf16, seed {seed}")
    if chips == 1:
        got, hlo = model_logits(jax, cfg, seed, None, "pallas kernels")
        with xla_reference_path():
            want, ref_hlo = model_logits(jax, cfg, seed, None, "xla reference")
        if PLATFORM == "tpu":
            check("tpu_custom_call" in hlo
                  and "tpu_custom_call" not in ref_hlo,
                  "the kernel path's decode program holds tpu_custom_call, "
                  "the reference path's does not")
        say("int8 KV cache: not compared — its decode kernel does not "
            "compile for the TPU and the engine refuses it at boot "
            "(ROADMAP S10)")
    else:
        mesh = build_mesh(ParallelConfig(tensor_parallel=chips))
        got, hlo = model_logits(jax, cfg, seed, mesh, f"tp={chips} mesh")
        want, _ = model_logits(jax, cfg, seed, None, "one device")
        if PLATFORM == "tpu":
            check("tpu_custom_call" in hlo and "all-reduce" in hlo,
                  f"the tp={chips} decode program holds both its "
                  "collectives (all-reduce) and its tpu_custom_call kernels")
    names = ["prefill C=256", "prefill C=0"] + [f"decode {i}" for i in range(4)]
    for name, a, b in zip(names, got, want):
        err = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
        check(err <= LOGITS_RTOL,
              f"logits {name}: max|a-b|/max|b| = {err:.2e} <= {LOGITS_RTOL}")


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    # Asked of a throwaway child: this process stays off JAX until the
    # servers have exited, and a run without the chip fails here.
    from production_stack_tpu.testing.procs import probe_devices

    seen = probe_devices()
    say(f"devices as a child sees them: {json.dumps(seen)}")
    check(seen["platform"] == PLATFORM and seen["count"] >= args.chips,
          f"{args.chips} {PLATFORM} device(s) can be reached")
    serve(args.chips)

    # Both children have exited: the chip is free for this process.
    from production_stack_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    compare(args.chips, args.seed)

    import jax

    devices = jax.devices()
    check(len(devices) == args.chips,
          f"jax.devices() of the parent counts {args.chips}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)


if __name__ == "__main__":
    main()
