#!/usr/bin/env python3
"""Where the host's time inside ONE paged decode step call goes, on the device it finds:

    chiprun -- python3 benchmarks/time_step_host.py perf/configs/qwen3-next-80b-a3b-span8-ep4.json [--steps 300] [--live 4]

Builds the configuration's span as ``perf/serve_child.py`` does (weights made on the device from its seed), the
lane pool's pools at the configuration's ``batch_lanes`` x ``batch_max_length`` (pages of 64, every table slot
owned), and times, a step, on this thread's clock (medians over ``--steps`` steps after a warm-up, and under
``<name>.mean`` the means, which is what the benchmark's ``step_*_ms`` are; ms):

``call``      ``backend.paged_decode_step`` with host arrays, as a caller with separate ``hidden``, ``positions``
              and ``tables`` reaches it: ``dispatch`` until the call returns, ``wait`` until ``np.asarray(out)`` does
``packed``    the same call handed the lanes' one host buffer (``backend.pack_lanes``' form) and the tables already
              on the device, as the batcher reaches it: one copy in, the result's copy back queued with the launch
``resident``  the jitted program alone with EVERY input already on the device: its argument handling and launch
              (``dispatch``), then ``wait``; what is left of ``dispatch`` here no copy in can shorten
``copies``    ``jax.device_put`` of each host array a step sends, alone: ``put`` until it returns, ``done`` until
              the array is on the device
``back``      ``np.asarray`` of a result that is already computed: the copy back alone
``behind``    two ``packed`` launches back to back, as the batcher makes them for two groups of lanes that take turns
              (``DecodeBatcher._start_behind``): a second thread waits for the first step's rows (``first.home``, from
              the first launch's start) while this one launches the second behind it. ``idle.dispatch`` is the launch
              on an idle chip, ``behind.dispatch`` the same launch beside a step in flight and a thread that waits
              for it; ``second.home`` is when the second step's rows are there, and ``second.after_first`` what lies
              between the two: a step's time on the device where the second launch was hidden behind the first
``leaves``    the arrays that cross the jit boundary a call (weights, pools, state, the step's inputs)

Each row twice: with the interpreter quiet, and (``busy``) with a second thread that builds and serialises
``--replies`` decode replies of the span's hidden size in a loop with a pause between, as the event loop does for the
lanes of the OTHER group while a step is inside its call (the interpreter's lock is what the two share; the
switch interval, a burst's mean length and the pause are printed; ``--replies``, ``--pause-ms`` and ``--switch-ms`` set them); with ``--capture`` a third time (``captured``) under ``jax.profiler.start_trace``, as
the slice of a traced benchmark run is taken, which is what the per-layer ``step_*_ms`` metrics read. Rows go to ``chiprun_out/time_step_host.jsonl`` and the last line printed is a JSON
object of all of them. On the CPU the numbers say where Python's time goes and nothing of a chip: run it there at a
toy size (``tests/perf/data/qwen3-next-tiny.json``)."""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import threading
import time
from pathlib import Path
from queue import SimpleQueue

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("config_file")
    parser.add_argument("--steps", type=int, default=300)
    parser.add_argument("--live", type=int, default=0, help="lanes that feed a row (default: all)")
    parser.add_argument("--replies", type=int, default=4, help="replies the busy thread builds a burst")
    parser.add_argument("--pause-ms", type=float, default=2.0, help="the busy thread's pause between two bursts")
    parser.add_argument("--switch-ms", type=float, default=0.0, help="sys.setswitchinterval for the busy pass (0: as the interpreter has it)")
    parser.add_argument("--capture", action="store_true", help="a third pass under a profiler capture, as a traced run's slice is taken")
    args = parser.parse_args(argv)

    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perf import weights
    from perf.config import load as load_config
    from petals_tpu.server.backend import TransformerBackend
    from petals_tpu.server.from_pretrained import get_block_config
    from petals_tpu.rpc.serialization import serialize_array

    config = load_config(Path(args.config_file), Path(args.config_file).stem)
    server_args = {**config["server_args"], **config["servers"][0]}
    work = ROOT / "chiprun_out" / "time_step_host" / config["name"]
    work.mkdir(parents=True, exist_ok=True)
    (work / "config.json").write_text(json.dumps(config["config"]))
    family, cfg = get_block_config(str(work))
    depth = server_args["num_blocks"]
    n_lanes, page_size = server_args.get("batch_lanes") or 8, 64
    max_pages = -(-(server_args.get("batch_max_length") or 1024) // page_size)
    n_pages, max_length = n_lanes * max_pages, max_pages * page_size
    live = args.live or n_lanes

    t = time.perf_counter()
    dtype = jnp.dtype(server_args.get("compute_dtype", "bfloat16"))
    params, _ = weights.span_params(config, server_args["first_block"], depth, dtype)
    jax.block_until_ready(params)
    print(f"[time_step_host] {config['name']}: {depth} blocks made on {jax.devices()[0].device_kind} in {time.perf_counter() - t:.1f}s", file=sys.stderr, flush=True)
    backend = TransformerBackend(family, cfg, params, first_block=server_args["first_block"], n_blocks=depth, memory_cache=None, compute_dtype=dtype)
    pools = tuple(d.make_zeros() for d in backend.cache.pool_descriptors(n_pages, page_size, n_lanes, 0, depth))
    hsz = backend.hidden_size
    rng = np.random.default_rng(0)
    hidden = rng.standard_normal((n_lanes, 1, hsz)).astype(np.float32)
    tables = np.arange(n_pages, dtype=np.int32).reshape(n_lanes, max_pages)
    start = max_length // 2

    def positions_at(step: int) -> np.ndarray:
        positions = np.full(n_lanes, max_length, np.int32)  # the idle sentinel
        positions[:live] = start + step
        return positions

    leaves = len(jax.tree_util.tree_leaves((backend.params, pools))) + 2  # the lanes' buffer and the tables

    def med(xs) -> float:
        return round(1e3 * statistics.median(xs), 4)

    def run(rows: dict, tag: str) -> None:
        nonlocal pools
        step = 0

        def loop(body) -> dict:
            nonlocal step
            clocks: dict = {}
            for i in range(args.steps + 20):
                for name, seconds in body(step).items():
                    if i >= 20:
                        clocks.setdefault(name, []).append(seconds)
                step += 1
            means = {f"{name}.mean": round(1e3 * statistics.fmean(xs), 4) for name, xs in clocks.items()}
            return {**{name: med(xs) for name, xs in clocks.items()}, **means}

        def call(step):
            nonlocal pools
            positions = positions_at(step)
            t0 = time.perf_counter()
            out, pools = backend.paged_decode_step(hidden, pools, positions, tables)
            t1 = time.perf_counter()
            np.asarray(out)
            return {"dispatch": t1 - t0, "wait": time.perf_counter() - t1}

        rows[f"call{tag}"] = loop(call)

        buffer = backend.pack_lanes(hidden, positions_at(0))
        tables_dev = jax.block_until_ready(backend.device_tables(tables))

        def packed(step):
            nonlocal pools
            buffer[:, hsz] = positions_at(step)
            t0 = time.perf_counter()
            out, pools = backend.paged_decode_step(buffer, pools, buffer[:, hsz], tables_dev)
            out.copy_to_host_async()
            t1 = time.perf_counter()
            np.asarray(out)
            return {"dispatch": t1 - t0, "wait": time.perf_counter() - t1}

        rows[f"packed{tag}"] = loop(packed)

        buffers = (buffer, buffer.copy())  # a launch does not write rows whose copy in may still be read
        waiting: SimpleQueue = SimpleQueue()
        home: SimpleQueue = SimpleQueue()

        def readback() -> None:
            for out in iter(waiting.get, None):
                np.asarray(out)
                home.put(time.perf_counter())

        waiter = threading.Thread(target=readback, daemon=True)
        waiter.start()

        def behind(step):
            nonlocal pools
            for lanes in buffers:
                lanes[:, hsz] = positions_at(step)
            t0 = time.perf_counter()
            first, pools = backend.paged_decode_step(buffers[0], pools, buffers[0][:, hsz], tables_dev)
            first.copy_to_host_async()
            t1 = time.perf_counter()
            waiting.put(first)
            t2 = time.perf_counter()
            second, pools = backend.paged_decode_step(buffers[1], pools, buffers[1][:, hsz], tables_dev)
            second.copy_to_host_async()
            t3 = time.perf_counter()
            np.asarray(second)
            t4 = time.perf_counter()
            first_home = home.get()
            return {"idle.dispatch": t1 - t0, "behind.dispatch": t3 - t2, "first.home": first_home - t0,
                    "second.home": t4 - t0, "second.after_first": t4 - first_home}

        try:
            rows[f"behind{tag}"] = loop(behind)
        finally:
            waiting.put(None)
            waiter.join()
        host_arrays = {"lanes": buffer, "tables": tables, "positions": positions_at(0)}  # the last two: what a step sent before PR 51

        def copies(step):
            clocks = {}
            for name, array in host_arrays.items():
                t0 = time.perf_counter()
                on_device = jax.device_put(array)
                t1 = time.perf_counter()
                on_device.block_until_ready()
                clocks[f"{name}.put"], clocks[f"{name}.done"] = t1 - t0, time.perf_counter() - t0
            return clocks

        rows[f"copies{tag}"] = loop(copies)

        k_pool, v_pool, *state = pools
        fn = backend._paged_decode_fn

        def resident(step):
            nonlocal k_pool, v_pool, state
            buffer[:, hsz] = positions_at(step)
            inputs = jax.block_until_ready((jax.device_put(buffer), tables_dev))
            t0 = time.perf_counter()
            res = fn(backend.params, k_pool, v_pool, *inputs, tuple(state), with_fp=False)
            t1 = time.perf_counter()
            res[0].block_until_ready()
            t2 = time.perf_counter()
            np.asarray(res[0])
            t3 = time.perf_counter()
            out, k_pool, v_pool, *rest = res
            state = list(rest[0]) if rest else []
            return {"dispatch": t1 - t0, "wait": t2 - t1, "back": t3 - t2}

        rows[f"resident{tag}"] = loop(resident)
        pools = (k_pool, v_pool, *state)

    rows: dict = {
        "config": config["name"], "device": jax.devices()[0].device_kind, "lanes": n_lanes, "live": live, "steps": args.steps,
        "leaves": leaves, "runs": len(backend.runs), "state_leaves": len(backend.cache.lane_state), "switch_interval_ms": 1e3 * sys.getswitchinterval(),
    }
    run(rows, "")

    stop = threading.Event()
    bursts, burst_s = [0], [0.0]
    if args.switch_ms:
        sys.setswitchinterval(args.switch_ms / 1e3)
        rows["busy_switch_interval_ms"] = args.switch_ms

    def busy() -> None:  # a burst of replies built and serialised, then the pause in which a loop would sit in select()
        row = rng.standard_normal((1, 1, hsz)).astype(np.float32)
        while not stop.is_set():
            began = time.perf_counter()
            for lane in range(args.replies):
                meta = {"lane": lane, "step": bursts[0], "queue_s": 0.001, "compute_s": 0.005, "usage": {"tokens": 1, "pages": 3}}
                json.dumps(meta)
                serialize_array(row * 1.0)
            bursts[0] += 1
            burst_s[0] += time.perf_counter() - began
            time.sleep(args.pause_ms / 1e3)

    thread = threading.Thread(target=busy, daemon=True)
    thread.start()
    try:
        run(rows, ".busy")
    finally:
        stop.set()
        thread.join()
    rows["busy_bursts"], rows["busy_burst_ms"], rows["busy_pause_ms"] = bursts[0], round(1e3 * burst_s[0] / max(bursts[0], 1), 4), args.pause_ms

    if args.capture:  # and once under a profiler capture started as perf/serve_child.py starts its traced slice's
        import shutil

        trace_dir = work / "trace"
        jax.profiler.start_trace(str(trace_dir))
        try:
            run(rows, ".captured")
        finally:
            jax.profiler.stop_trace()
            shutil.rmtree(trace_dir, ignore_errors=True)

    out_dir = ROOT / "chiprun_out"
    with open(out_dir / "time_step_host.jsonl", "a") as f:
        f.write(json.dumps(rows) + "\n")
    print(json.dumps(rows), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
