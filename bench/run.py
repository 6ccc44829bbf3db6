"""One run of one benchmark cell.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The parent stays off JAX while its children live (a chip belongs to one
process).  It starts the engine, warms it up with the cell's own traffic,
puts the router in front, measures one window at the client, stops both
children, and only then imports JAX for the trace reduction and the
comparison with the plain reference.  The last line of stdout is the
contract's JSON object; earlier lines say what it may not.  See README.md.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()

import argparse
import asyncio
import dataclasses
import importlib
import json
import os
import shutil
import sys
from typing import Dict, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness.children import Child, free_port  # noqa: E402
from harness.client import Client  # noqa: E402
from harness import compare, layers, scrape  # noqa: E402
from harness.hostmon import LoopLag  # noqa: E402
from reduce import stats  # noqa: E402


def say(what: str, **fields) -> None:
    """An earlier line of stdout: one JSON object, never the last."""
    print(json.dumps({"t": round(time.monotonic() - _T_START, 3),
                      "say": what, **fields}), flush=True)


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def by_name(entries, name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"bench: no {what} named {name!r}; known: "
                     f"{[e['name'] for e in entries]}")


def resolve(benchmark_path: str, workload: str):
    """Everything one cell is made of, found by the names in the benchmark
    file: (bench, cell, config, traffic, cell_params, dirs).  ``dirs`` is
    where data files and drop-in modules are looked for: the benchmark
    file's own ``paths[0]`` first, then this directory."""
    bench = load_json(benchmark_path)
    root = os.path.dirname(os.path.abspath(benchmark_path))
    dirs = []
    for d in (os.path.join(root, bench["paths"][0]), BENCH):
        if d not in dirs:
            dirs.append(d)
            if d not in sys.path:
                sys.path.append(d)
    cell = by_name(bench["workloads"], workload, "workload")
    file = by_name(bench["configs"], cell["config"], "config")["file"]
    config = load_json(next(
        (p for p in (os.path.join(root, file), os.path.join(ROOT, file))
         if os.path.exists(p)), os.path.join(root, file)))
    traffic = load_json(layers.find(dirs, "traffic", cell["traffic"]))
    cell_file = layers.find(dirs, "cells", cell["name"], missing_ok=True)
    cell_params = load_json(cell_file) if cell_file else {}
    return bench, cell, config, traffic, cell_params, dirs


def metric_names(bench: Dict, cell: str, traced: bool) -> list:
    """What a run of ``cell`` reports: the per-layer metrics with ``--trace
    1``, else the end-to-end ones; a metric that lists ``workloads`` only in
    those cells."""
    return [m["name"] for m in bench["per_layer" if traced else "end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


class Stable:
    """Has the engine compiled anything since the last check?"""

    def __init__(self, client: Client, engine_url: str, max_s: float):
        self.client, self.url = client, engine_url
        self.deadline = time.monotonic() + max_s
        self.counts: Dict[str, int] = {}
        self.seen = 0
        self.checks = 0
        self.history = []   # what each stretch compiled, for run.json

    async def check(self, after: str) -> bool:
        """``after`` names the stretch: ``programs`` (the fixed warm-up
        requests, which are meant to compile) or ``traffic`` (the cell's
        own mix, which is meant to find everything compiled)."""
        compiles = await self.client.get_json(self.url + "/debug/compiles")
        # Compile events, not distinct keys: the tracker's key is a
        # truncated signature, so two shapes can share one.
        counts = {r["executable"]: r["count"] for r in compiles["executables"]}
        new = {k: n - self.counts.get(k, 0) for k, n in counts.items()
               if n > self.counts.get(k, 0)}
        self.counts, self.seen = counts, sum(counts.values())
        self.checks += 1
        self.history.append({"after": after, "new": new})
        say("warm-up stretch done", compile_events=self.seen,
            stretch=self.checks, after=after, new_events=sum(new.values()),
            compiled_shapes=compiles["compiled_shapes"],
            compile_seconds=compiles["compile_seconds"])
        if after == "traffic" and new:
            say("the warm-up's fixed requests missed programs that the "
                "cell's own traffic then compiled: one more stretch",
                programs=new)
        return after == "traffic" and (
            not new or time.monotonic() > self.deadline)


async def sleep_until(t: float) -> None:
    await asyncio.sleep(max(0.0, t - time.monotonic()))


async def drive(args, config, traffic, cell_params, engine, router,
                engine_url, router_url, out_dir) -> Dict:
    """Warm-up, router, window.  Returns what the reduction needs."""
    gen = importlib.import_module("generators." + traffic["generator"])
    got: Dict = {"timing": {}}
    async with Client(config["model"]) as client:
        t = time.monotonic()
        client.target(engine_url)
        stable = Stable(client, engine_url, traffic["warmup"]["max_s"])
        warm = (dict(cell_params, rate_rps=max(args.sweep)) if args.sweep
                else cell_params)
        await gen.warmup(client, traffic, warm, stable)
        got["timing"]["warmup_s"] = time.monotonic() - t
        # Boot and warm-up in the engine's own CPU seconds: a slow machine
        # shows here, more work would show in the stretches (PERF.md, PR 33).
        got["timing"]["engine_cpu_s"] = engine.cpu_seconds()
        got["warmup_stretches"] = stable.history

        # The router starts only now, so that its capacity model never sees
        # a compile's latency (it shed 9 of 18 requests when it did, PR 21).
        t = time.monotonic()
        router.start()
        await asyncio.to_thread(
            router.wait_http_ok, router_url + "/health", 120.0)
        client.target(router_url)
        got["timing"]["router_boot_s"] = time.monotonic() - t

        t = time.monotonic()
        state = await gen.prepare(client, traffic, cell_params, args.seed)
        got["timing"]["cache_seeding_s"] = time.monotonic() - t

        say("set-up parts", parts=got["timing"], programs=stable.seen)
        if args.sweep:
            got["sweep"] = []
            for rate in args.sweep:
                rung = await window(
                    args, client, gen, traffic, dict(cell_params, rate_rps=rate),
                    state, engine_url, out_dir, stable.seen, trace=False)
                got["sweep"].append(sweep_row(rate, rung, client.records))
                say("sweep rung", **got["sweep"][-1])
                await asyncio.sleep(2.0)
            return got
        # The generator's own loop is watched (harness/hostmon.py): a stall
        # of the machine inside the window is reported, never acted on.
        lag = LoopLag()
        watching = asyncio.ensure_future(lag.run())
        got.update(await window(
            args, client, gen, traffic, cell_params, state, engine_url,
            out_dir, stable.seen, trace=bool(args.trace)))
        watching.cancel()
        got["setup_s"] = got["t0"] - _T_START
        got["generator_lag_ms"] = lag.worst(
            got["t0"], got["t0"] + got["seconds"])
        got["windows"] = await client.get_json(engine_url + "/debug/windows")
        got["records"] = client.records
    return got


async def window(args, client, gen, traffic, cell_params, state, engine_url,
                 out_dir, programs, trace: bool) -> Dict:
    """One measured window: pre-roll, ``--seconds`` of offered load, drain."""
    got: Dict = {}
    pre = traffic.get("preroll_s", 0)
    t0 = time.monotonic() + pre + 0.25
    wall_t0 = time.time() + (t0 - time.monotonic())
    t1 = t0 + args.seconds
    got.update(t0=t0, wall_t0=wall_t0, seconds=args.seconds)
    say("window", setup_s=t0 - _T_START, programs=programs,
        rate_rps=cell_params.get("rate_rps"))

    async def snapshots() -> None:
        await sleep_until(t0)
        got["before"] = await scrape.snapshot(client, engine_url)
        await sleep_until(t1)
        got["after"] = await scrape.snapshot(client, engine_url)

    async def tracer() -> None:
        spec = traffic.get("trace", {"at_s": 5, "for_s": 5})
        at = min(spec["at_s"], max(0.0, args.seconds - spec["for_s"]))
        await sleep_until(t0 + at)
        trace_dir = os.path.join(out_dir, "trace")
        await client.post_json(engine_url + "/start_profile",
                               {"trace_dir": trace_dir})
        got["trace_wall"] = [time.time()]
        await asyncio.sleep(min(spec["for_s"], args.seconds))
        await client.post_json(engine_url + "/stop_profile", {})
        got["trace_wall"].append(time.time())
        got["trace_dir"] = trace_dir

    side = [asyncio.ensure_future(snapshots())]
    if trace:
        side.append(asyncio.ensure_future(tracer()))
    work = asyncio.ensure_future(gen.measure(
        client, traffic, cell_params, args.seed, state, t0, args.seconds))
    got["drain_s"] = (traffic.get("trace", {}).get("drain_s") if trace
                      else None) or traffic.get("drain_s", 15)
    try:
        await asyncio.wait_for(work, t1 + got["drain_s"] - time.monotonic())
    except asyncio.TimeoutError:
        say("requests still open after the drain grace: counted as failed")
    await asyncio.gather(*side)
    return got


def sweep_row(rate: float, rung: Dict, records) -> Dict:
    """One rung of the rate ladder: what the knee is read from."""
    t0, seconds = rung["t0"], rung["seconds"]
    mine = [r for r in records if r.phase == "measure" and r.due >= t0 - 60
            and r.due < t0 + seconds]
    summary = stats.summarize(mine, t0, seconds, rung["drain_s"])

    def in_flight(t: float) -> int:
        return sum(1 for r in mine if r.sent is not None and r.sent <= t
                   and (r.ended is None or r.ended > t))

    return {
        "rate_rps": rate, "attempted": summary["attempted"],
        "failed": summary["failed"], "reasons": summary["failure_reasons"],
        "completed_share": 1 - summary["failed"] / max(1, summary["attempted"]),
        "in_flight_mid": in_flight(t0 + seconds / 2),
        "in_flight_end": in_flight(t0 + seconds),
        "compiles": rung["after"]["compile_events"]
        - rung["before"]["compile_events"],
        **summary["metrics"],
    }


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--benchmark", default=os.path.join(ROOT, "BENCHMARK.json"),
                   help="the benchmark file (tests and rehearsals use another)")
    p.add_argument("--out-dir", default=None)
    p.add_argument("--keep-trace", action="store_true",
                   help="leave the .xplane.pb under <out-dir>/trace (tens of "
                   "megabytes) after it has been reduced")
    p.add_argument("--sweep", default=None,
                   help="the builder's rate ladder, e.g. 3,4,5,6: one boot, "
                   "one window of --seconds at each rate, a table, no "
                   "contract line")
    args = p.parse_args()

    args.sweep = ([float(x) for x in args.sweep.split(",")]
                  if args.sweep else None)
    bench, cell, config, traffic, cell_params, dirs = resolve(
        args.benchmark, args.workload)
    compare.check_file(config)

    # No TPU is a failure.  The one exception is an explicit CPU rehearsal
    # of a configuration marked for it, which prints no timing.
    rehearsal = (os.environ.get("JAX_PLATFORMS") == "cpu"
                 and bool(config.get("rehearsal")))
    platform = "cpu" if rehearsal else "tpu"
    out_dir = os.path.abspath(args.out_dir or os.path.join(
        ROOT, "bench_out", cell["name"], f"seed{args.seed}-trace{args.trace}"))
    os.makedirs(out_dir, exist_ok=True)

    env = dict(os.environ)
    if rehearsal:
        env.update(config.get("rehearsal_env", {}))
    engine_port, router_port = free_port(), free_port()
    engine_url = f"http://127.0.0.1:{engine_port}"
    router_url = f"http://127.0.0.1:{router_port}"
    engine = Child("engine", [
        sys.executable, "-m", "production_stack_tpu.engine.server.api_server",
        "--model", config["model"], "--host", "127.0.0.1",
        "--port", str(engine_port), *config["engine_argv"],
    ], out_dir, env=env, cwd=ROOT)
    router = Child("router", [
        sys.executable, "-m", "production_stack_tpu.router.app",
        "--host", "127.0.0.1", "--port", str(router_port),
        "--static-backends", engine_url, "--static-models", config["model"],
        *config["router_argv"],
    ], out_dir, env=env, cwd=ROOT)
    say("start", workload=cell["name"], seed=args.seed, seconds=args.seconds,
        trace=args.trace, out_dir=out_dir, engine_log=engine.log_path,
        router_log=router.log_path, engine=" ".join(engine.cmd[2:]))
    exit_codes = {}
    try:
        engine.start()
        device = engine.wait_device_line(300.0)
        if device["platform"] != platform or device["count"] < cell["chips"]:
            raise SystemExit(
                f"bench: cell {cell['name']} needs {cell['chips']} "
                f"{platform} device(s); the engine sees {device}")
        boot_s = engine.wait_http_ok(engine_url + "/health", 900.0)
        say("engine up", boot_s=boot_s, device=device)
        got = asyncio.run(drive(
            args, config, traffic, cell_params, engine, router, engine_url,
            router_url, out_dir))
        got["timing"]["engine_boot_s"] = boot_s
        if args.sweep:
            with open(os.path.join(out_dir, "sweep.json"), "w") as f:
                json.dump(got["sweep"], f, indent=1)
            say("sweep done", table=got["sweep"])
            return
        exit_codes["router"] = router.stop()
        exit_codes["engine"] = engine.stop(grace_s=120.0)
    except Exception:
        for child in (router, engine):
            if child.proc is not None:
                sys.stderr.write(
                    f"--- tail of {child.log_path} ---\n{child.tail()}\n")
        raise
    finally:
        router.stop(grace_s=10.0)
        engine.stop(grace_s=10.0)

    # -- both children have exited: the chip is free for this process ------
    summary = stats.summarize(got["records"], got["t0"], got["seconds"],
                              got["drain_s"])
    late = summary.pop("late_ms")
    say("requests", attempted=summary["attempted"], failed=summary["failed"],
        failure_reasons=summary["failure_reasons"], samples=summary["samples"],
        highest_supported_percentile=summary["highest_supported_percentile"],
        end_to_end=None if rehearsal else summary["metrics"],
        exit_codes=exit_codes)
    say("generator", late_max_ms=max(late, default=None),
        wakeups_late_ms=got["generator_lag_ms"])

    ctx = layers.Context(
        cell=cell, config=config, records=got["records"], late_ms=late,
        got=got, summary=summary, dirs=dirs)
    # The server's side of a stall: the longest the engine went without
    # dispatching anything inside the window (its flight records).
    at = sorted(w["dispatched_at"] for w in ctx.window_records())
    quiet = max(((b - a, a - got["wall_t0"]) for a, b in zip(at, at[1:])),
                default=(None, None))
    say("engine", dispatches=len(at), longest_gap_s=quiet[0],
        gap_began_at_s=quiet[1])
    served_ok, served_notes = layers.served_path_ok(
        ctx, platform, exit_codes)
    say("served path", ok=served_ok, notes=served_notes)

    trace = None
    if args.trace and got.get("trace_dir"):
        from reduce import xplane

        t = time.monotonic()
        trace = xplane.reduce_dir(got["trace_dir"], platform)
        if not args.keep_trace:
            shutil.rmtree(got["trace_dir"], ignore_errors=True)
        say("trace reduced", seconds=time.monotonic() - t,
            planes=trace["planes"], busy_s=trace["busy_s"],
            window_s=trace["window_s"], programs=trace["programs"][:12])
    ctx.trace = trace

    t = time.monotonic()
    model_ok, compare_notes, compared = compare.run(
        config, cell["chips"], args.seed, platform, env_root=ROOT)
    say("reference compare", ok=model_ok, seconds=time.monotonic() - t,
        notes=compare_notes)

    device_out = dict(got["after"]["device"])
    names = metric_names(bench, cell["name"], bool(args.trace))
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    if args.trace:
        values = layers.read_all(ctx, names)
        if trace is not None:
            device_out["busy_s"] = trace["busy_s"]
            device_out["window_s"] = trace["window_s"]
    else:
        values = dict(summary["metrics"], setup_s=got["setup_s"])
        values = {k: values[k] for k in names if k in values}
    timed = not rehearsal
    result = {
        "correct": bool(served_ok and model_ok),
        "attempted": summary["attempted"],
        "failed": summary["failed"],
        "metrics": {
            k: {"value": v if (timed or layers.is_count(
                k, dirs, cell["config"])) else None,
                "unit": units[k]}
            for k, v in values.items() if v is not None
        },
        "device": device_out,
    }
    if args.trace and trace is not None and timed:
        result["breakdown"] = layers.breakdown(ctx)
    # What ``correct`` was decided from, each number beside its limit: last
    # in the line and last on standard error.
    result["compared"] = dict(
        compared, served_path_faults=[len(served_notes), 0])
    with open(os.path.join(out_dir, "records.jsonl"), "w") as f:
        for r in got["records"]:
            f.write(json.dumps(dataclasses.asdict(r)) + "\n")
    with open(os.path.join(out_dir, "run.json"), "w") as f:
        json.dump({"result": result, "summary": summary,
                   "window": {k: got[k] for k in ("t0", "seconds", "drain_s")},
                   "timing": got["timing"], "late_ms": late,
                   "warmup_stretches": got["warmup_stretches"],
                   "generator_lag_ms": got["generator_lag_ms"],
                   "engine_longest_dispatch_gap_s": quiet[0],
                   "compare": compare_notes, "served": served_notes,
                   "engine_after": {k: got["after"][k] for k in (
                       "compiled_shapes", "compile_events", "compile_seconds",
                       "persistent_cache", "executables")},
                   "compile_events_in_window": got["after"]["compile_events"]
                   - got["before"]["compile_events"],
                   "trace": trace and {k: v for k, v in trace.items()
                                       if k != "modules"}}, f, indent=1)
    if not timed:
        say("rehearsal on the CPU: no timing is reported")
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name}: {value} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
