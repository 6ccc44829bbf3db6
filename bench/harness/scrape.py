"""What the engine says about itself over HTTP, as plain dicts."""

from __future__ import annotations

import time
from typing import Dict


def parse_prom(text: str) -> Dict[str, float]:
    """Prometheus text -> {family: sum over its label sets}."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, value = line.rpartition(" ")
        name = head.split("{", 1)[0]
        try:
            out[name] = out.get(name, 0.0) + float(value)
        except ValueError:
            continue
    return out


async def snapshot(client, engine_url: str) -> Dict:
    """Counters and the device report at one instant (host clock)."""
    wall = time.time()
    prom = parse_prom(await client.get_text(engine_url + "/metrics"))
    compiles = await client.get_json(engine_url + "/debug/compiles")
    dev = compiles["device"]
    in_use = [m["bytes_in_use"] for m in dev["memory"]
              if m["bytes_in_use"] is not None]
    return {
        "wall": wall,
        "prom": prom,
        "compiled_shapes": compiles["compiled_shapes"],
        # Compile events, not distinct keys: the tracker's key is a
        # truncated signature, so two shapes can share one.
        "compile_events": sum(r["count"] for r in compiles["executables"]),
        "compile_seconds": compiles["compile_seconds"],
        "persistent_cache": compiles["persistent_cache"],
        "executables": [[r["executable"][:100], r["count"], r["seconds"]]
                        for r in compiles["executables"]],
        "device": {
            "platform": dev["platform"], "kind": dev["kind"],
            "count": dev["count"],
            # The engine reports bytes in use, not the allocator's peak:
            # the fullest chip at the window's end, weights and KV pool
            # included, transient activations not (PERF.md, open questions).
            "memory_peak_bytes": max(in_use) if in_use else 0,
        },
    }
