#!/usr/bin/env python3
"""One chain server on one chip: the benchmark's child process.

Started by ``perf/run.py`` with the chip pinned through its environment. It
builds ``petals_tpu.server.server.Server`` with the configuration's
``server_args`` (plus its own ``servers[index]`` span) and nothing else, but
for where the weights come from: the span is made on the chip from the
configuration's seed (``perf/weights``) where ``Server`` would read a
checkpoint. It says ``READY {...}`` on stdout, then obeys one-word commands on
stdin:

``mark <label>``   snapshot ``batcher.stats``, the device's bytes in use and both clocks under that label
``trace_start`` / ``trace_stop``   the profiler, around one steady window
``stop``           write the dump (stats, compiled programs, cache traffic,
                   memory, the reduced trace) and shut the server down

It refuses to start unless JAX's backend is a TPU whose kind is in
``perf/peaks.json``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def log(msg: str) -> None:
    print(f"[serve_child] {msg}", file=sys.stderr, flush=True)


def say(word: str, payload: dict) -> None:
    print(f"{word} {json.dumps(payload)}", flush=True)


def device_or_exit(allow_cpu: bool) -> dict:
    import jax

    from perf import costs

    devices = jax.local_devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if jax.default_backend() != "tpu":
        if not allow_cpu:
            sys.stderr.write(
                f"perf/serve_child.py: JAX backend is {jax.default_backend()!r} ({info['kind']}), "
                f"not a TPU; the benchmark measures nothing off the chip\n"
            )
            raise SystemExit(2)
        return info
    costs.peaks_for(info["kind"])  # an unknown kind is an error, not a default
    return info


async def serve(args) -> int:
    import jax

    from petals_tpu.server.server import Server
    from petals_tpu.telemetry.observatory import get_observatory
    from petals_tpu.utils.compile_cache import count_cache_events

    device = device_or_exit(args.allow_cpu)
    cache_events = count_cache_events()
    from perf.config import load as load_config

    from perf import weights

    config = load_config(Path(args.config_file), Path(args.config_file).stem)
    server_args = {**config["server_args"], **config["servers"][args.index]}
    made = {}

    class SeededServer(Server):
        """``Server``, its span made on the device instead of read: ``model_dir`` holds a ``config.json`` and no weights."""

        def _load_span_params(self, first_block: int, num_blocks: int):
            t = time.perf_counter()
            stacked, made["checksum"] = weights.span_params(config, first_block, num_blocks, self.compute_dtype)
            jax.block_until_ready(stacked)
            log(f"blocks [{first_block}, {first_block + num_blocks}) made on the device in {time.perf_counter() - t:.1f}s")
            return stacked

    log(f"Server({args.model_dir}, **{server_args}) on {device}")
    server = SeededServer(args.model_dir, initial_peers=[args.initial_peers], **server_args)
    await server.start()
    batcher = server.handler.batcher
    say("READY", {
        "device": device, "blocks": [server.first_block, server.first_block + server.num_blocks],
        "lanes": batcher.n_lanes, "page_size": batcher.page_size, "max_length": batcher.max_length,
        "prefill_token_budget": batcher.prefill_token_budget, "weights_checksum": made["checksum"],
    })

    loop = asyncio.get_running_loop()
    marks, trace, occupancy = {}, {}, []

    async def sample_occupancy() -> None:  # twice a second: lanes, waiters, free pages
        while True:
            info = batcher.occupancy_info()
            occupancy.append([time.perf_counter(), info.get("busy_lanes"), info.get("lane_waiters"), info.get("pages_free")])
            await asyncio.sleep(0.5)

    sampler = asyncio.create_task(sample_occupancy())

    def mark(label: str) -> None:
        memory = jax.local_devices()[0].memory_stats() or {}
        marks[label] = {"wall": time.time(), "mono": time.perf_counter(), "stats": dict(batcher.stats),
                        "bytes_in_use": memory.get("bytes_in_use")}

    while True:
        line = await loop.run_in_executor(None, sys.stdin.readline)
        words = line.split()
        if not line or words == ["stop"]:
            break
        if words[0] == "mark":
            mark(words[1])
        elif words[0] == "trace_start":
            await loop.run_in_executor(None, jax.profiler.start_trace, args.trace_dir)
            mark("trace_start")
        elif words[0] == "trace_stop":
            mark("trace_stop")
            await loop.run_in_executor(None, jax.profiler.stop_trace)
        say("ACK", {"cmd": words[0]})

    mark("stop")
    sampler.cancel()
    if "trace_stop" in marks:
        from perf import xplane

        path = xplane.find_trace(Path(args.trace_dir))
        trace = (xplane.reduce_file(path) if path else None) or {}
    memory = jax.local_devices()[0].memory_stats() or {}
    prefix_cache = getattr(server.handler, "prefix_cache", None)
    dump = {
        "device": device, "marks": marks, "trace": trace, "cache_events": dict(cache_events),
        "memory": {k: int(v) for k, v in memory.items() if isinstance(v, (int, float))},
        "programs": [
            {"fn": r.fn, "compile_s": r.compile_s, "wall": r.t, "anomaly": bool(r.anomaly)}
            for r in get_observatory().programs()
        ],
        "prefix_cache": dict(prefix_cache.stats) if prefix_cache is not None else None,
        "occupancy": occupancy, "n_pages": batcher.n_pages,
    }
    Path(args.dump).write_text(json.dumps(dump))
    say("DUMPED", {"path": args.dump})
    try:
        await asyncio.wait_for(server.shutdown(), timeout=20)
    except Exception as e:  # the parent ends the process group either way
        log(f"shutdown: {e!r}")
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)  # the runtime's threads abort an ordinary interpreter exit (SIGABRT on the v5e)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config-file", required=True)
    parser.add_argument("--index", type=int, default=0)
    parser.add_argument("--model-dir", required=True)
    parser.add_argument("--initial-peers", required=True)
    parser.add_argument("--dump", required=True)
    parser.add_argument("--trace-dir", required=True)
    parser.add_argument("--allow-cpu", action="store_true", help=argparse.SUPPRESS)  # the CPU tests of the harness
    return asyncio.run(serve(parser.parse_args(argv)))


if __name__ == "__main__":
    sys.exit(main())
