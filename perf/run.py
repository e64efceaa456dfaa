#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process is the load generator. It keeps JAX on the CPU, so it never
holds a chip; each chip of the cell gets one server child
(``perf/serve_child.py``), which makes its weights on the chip from the
configuration's seed. In order: DHT bootstrap, server children, the
reference's output (computed while the servers start), the normal client (``RemoteSequential`` over the swarm), the
correctness check, warm-up of the cell's own shapes, the ramp and the window,
the drain, the children's dumps, then one JSON line. See perf/README.md.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # before any import of JAX: this process never takes a chip

import argparse
import contextlib
import json
import queue
import signal
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from perf import correct, costs, gaps, loadgen, reference, traffic  # noqa: E402
from perf.config import load as load_config  # noqa: E402
from perf.record import Record, load_reader  # noqa: E402

WORK_DIR = ROOT / "perf" / ".work"  # git-ignored: compile cache, logs, traces
READY_TIMEOUT_S = 1100.0
DRAIN_S = 60.0
TRACE_AT, TRACE_S = 0.4, 3.0  # the traced window starts 40% into the measured one


def log(msg: str) -> None:
    print(f"[perf {time.perf_counter() - T_PROCESS:7.1f}s] {msg}", file=sys.stderr, flush=True)


def chip_pin_env(chip: int) -> dict:
    """libtpu reads these before it touches a chip: the process sees exactly
    one (all three are needed, chip_smoke.py found)."""
    return {"TPU_VISIBLE_CHIPS": str(chip), "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1", "TPU_PROCESS_BOUNDS": "1,1,1"}


class Child:
    """A server child: its process (a group of its own), the words it says."""

    def __init__(self, index: int, cmd: list, env: dict, log_path: Path):
        self.index = index
        self.log_file = open(log_path, "w")
        self.proc = subprocess.Popen(
            cmd, env=env, cwd=ROOT, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self.log_file, text=True, start_new_session=True,
        )
        self.log_path = log_path
        self.stopping = False  # told to stop: give it time to leave by itself
        self.said: queue.Queue = queue.Queue()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            word, _, rest = line.strip().partition(" ")
            if word in ("READY", "ACK", "DUMPED"):
                self.said.put((word, json.loads(rest)))
        self.said.put(("EXIT", {}))

    def expect(self, word: str, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while True:
            try:
                got, payload = self.said.get(timeout=max(deadline - time.monotonic(), 0.01))
            except queue.Empty:
                raise TimeoutError(f"server child {self.index}: no {word} within {timeout:.0f}s") from None
            if got == word:
                return payload
            if got == "EXIT":
                tail = self.log_path.read_text(errors="replace")[-3000:]
                raise RuntimeError(f"server child {self.index} exited {self.proc.wait()} before {word}:\n{tail}")

    def tell(self, line: str) -> None:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()

    def kill(self) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        self.proc.wait()
        self.log_file.close()


def find_cell(benchmark: dict, workload: str) -> tuple:
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if workload not in cells:
        raise SystemExit(f"perf/run.py: no workload {workload!r} in BENCHMARK.json (has {sorted(cells)})")
    cell = cells[workload]
    config_entry = {c["name"]: c for c in benchmark["configs"]}[cell["config"]]
    return cell, config_entry


def metric_names(benchmark: dict, section: str, workload: str) -> list:
    return [m["name"] for m in benchmark[section] if workload in m.get("workloads", [workload])]


def warm_lengths(mix: dict, budget: int) -> list:
    """One prompt length per power-of-two chunk bucket the mix can produce:
    a chunk is at most the batcher's budget, and what is left of a prompt
    after page-aligned chunks can be of any smaller size."""
    longest = traffic.quantile(mix["prompt"], 1.0 - 1e-9)
    if mix.get("prefix", {}).get("kind") == "tree":
        longest += 4096  # a shared head: every bucket up to the budget can occur
    top = int(min(budget, longest))
    lengths, b = [], 8
    while b < top:
        lengths.append(b)
        b *= 2
    return lengths + [top]


@contextlib.contextmanager
def serving(config: dict, config_file: Path, run_dir: Path, *, root: Path = ROOT, work_dir: Path = WORK_DIR,
            allow_cpu: bool = False, meanwhile=lambda: None):
    """The cell's swarm: DHT bootstrap, one server child per entry of the
    configuration's ``servers``, the normal client. Yields a dict with
    ``remote``, ``children``, ``ready`` (each child's READY payload),
    ``device`` and ``meanwhile`` (what that callable returned: it runs while
    the servers start). Nothing outlives the block:
    every child's process group is killed on every path."""
    from petals_tpu.client.config import ClientConfig
    from petals_tpu.client.remote_sequential import RemoteSequential
    from petals_tpu.client.runtime import SwarmRuntime
    from petals_tpu.data_structures import make_uid
    from petals_tpu.dht import DHTNode
    from petals_tpu.server.server import default_dht_prefix

    work_dir.mkdir(parents=True, exist_ok=True)
    model_dir = work_dir / "models" / config["name"]  # what a Server reads of a model besides its weights
    model_dir.mkdir(parents=True, exist_ok=True)
    (model_dir / "config.json").write_text(json.dumps(config["config"], indent=1))
    trace_root = run_dir / "trace"
    if trace_root.exists():
        import shutil

        shutil.rmtree(trace_root)
    run_dir.mkdir(parents=True, exist_ok=True)
    n_servers = len(config["servers"])
    n_layers = sum(span["num_blocks"] for span in config["servers"])

    env = {**os.environ, "PYTHONPATH": str(root), "PYTHONUNBUFFERED": "1"}
    env.pop("JAX_PLATFORMS", None)
    env.pop("BENCH_RUN", None)
    if allow_cpu:
        env["JAX_PLATFORMS"] = "cpu"
    if not env.get("JAX_COMPILATION_CACHE_DIR"):
        env["JAX_COMPILATION_CACHE_DIR"] = str(work_dir / "jax_cache")  # a fixed path: it is part of the cache's key
    # the default threshold keeps only programs that took 1 s to compile (PR 21: 18 hits of 470)
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"

    swarm = SwarmRuntime()  # an asyncio loop on its own thread, for the DHT bootstrap node
    children, remote, bootstrap = [], None, None
    try:
        bootstrap = swarm.run(DHTNode.create(host="127.0.0.1"), timeout=120)
        addr = bootstrap.own_addr.to_string()
        for i in range(n_servers):
            cmd = [sys.executable, str(root / "perf" / "serve_child.py"), "--config-file", str(config_file),
                   "--index", str(i), "--model-dir", str(model_dir), "--initial-peers", addr,
                   "--dump", str(run_dir / f"child{i}.json"), "--trace-dir", str(trace_root / f"child{i}")]
            if allow_cpu:
                cmd.append("--allow-cpu")
            child_env = {**env, **chip_pin_env(i)} if n_servers > 1 and not allow_cpu else env
            children.append(Child(i, cmd, child_env, run_dir / f"child{i}.log"))
        log(f"{n_servers} server child(ren) starting; reference and schedule meanwhile")
        made = meanwhile()
        ready = [c.expect("READY", READY_TIMEOUT_S) for c in children]
        log(f"servers ready: {ready}")
        device = ready[0]["device"]
        if any(r["device"] != device for r in ready):
            raise RuntimeError(f"server children report different devices: {ready}")
        prefix = default_dht_prefix(str(model_dir))
        remote = RemoteSequential(
            ClientConfig(initial_peers=[addr], dht_prefix=prefix), [make_uid(prefix, i) for i in range(n_layers)]
        )
        yield {"remote": remote, "children": children, "ready": ready, "device": device, "meanwhile": made}
    finally:
        if remote is not None:
            try:
                remote.close()
            except Exception as e:
                log(f"client close: {e!r}")
        for c in children:
            try:
                c.proc.wait(timeout=25 if c.stopping else 0.1)
            except subprocess.TimeoutExpired:
                pass
            c.kill()
        try:
            if bootstrap is not None:
                swarm.run(bootstrap.shutdown(), timeout=20)
        except Exception as e:
            log(f"bootstrap shutdown: {e!r}")
        swarm.shutdown()


def same_weights(ready: list, weight_checks: list) -> None:
    """Each server's first block against the reference's layer of that index,
    by checksum: the check below compares outputs, which says nothing unless
    both sides hold the same weights."""
    for r in ready:
        if r["weights_checksum"] != weight_checks[r["blocks"][0]]:
            raise RuntimeError(f"the server of blocks {r['blocks']} made other weights than the reference "
                               f"({r['weights_checksum']} against {weight_checks[r['blocks'][0]]})")


def tell_all(children: list, line: str) -> None:
    for c in children:
        c.tell(line)
    for c in children:
        c.expect("ACK", 120)


def check(remote, children: list, config: dict, x: np.ndarray, want: np.ndarray, margin: np.ndarray) -> dict:
    """perf/correct.py's sessions through the served path, and its verdict."""
    rows = correct.alone(remote, x)
    again = correct.alone(remote, x)
    same = all(a[2].tobytes() == b[2].tobytes() for a, b in zip(rows, again))
    tell_all(children, "mark check")
    rows += correct.together(remote, x)
    tell_all(children, "mark check_end")
    verdict = correct.judge(rows, want, margin, reference.limits(config))
    verdict["repeat_identical"] = same
    verdict["ok"] = verdict["ok"] and same
    return verdict


def stop_and_dump(children: list) -> list:
    for c in children:
        c.stopping = True
        c.tell("stop")
    return [json.loads(Path(c.expect("DUMPED", 300)["path"]).read_text()) for c in children]


def run_cell(benchmark: dict, workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, traffic_dir: Path = traffic.TRAFFIC_DIR, work_dir: Path = WORK_DIR,
             allow_cpu: bool = False) -> dict:
    """Everything but the command line. ``allow_cpu`` exists for the CPU tests
    of the harness and is not reachable from the command line."""
    cell, config_entry = find_cell(benchmark, workload)
    config = load_config(root / config_entry["file"], config_entry["name"])
    hidden = costs.layer_params(config["family"], config["config"])["hidden"]
    mix = traffic.load_mix(cell["traffic"], traffic_dir)
    n_servers = len(config["servers"])
    if cell["chips"] != n_servers:
        raise SystemExit(f"cell {workload} asks for {cell['chips']} chips, its configuration has {n_servers} servers")

    def meanwhile() -> tuple:  # while the servers start: inputs, schedule, reference
        pool = traffic.input_pool(seed, hidden)
        sched = traffic.schedule(mix, seed, seconds)
        check_x = correct.inputs(seed, hidden)
        t_ref = time.perf_counter()
        want, margin, weight_checks = reference.run(config, check_x)
        log(f"reference: {len(check_x)} positions in {time.perf_counter() - t_ref:.1f}s")
        return pool, sched, check_x, want, margin, weight_checks

    with serving(config, root / config_entry["file"], work_dir / "runs" / workload, root=root, work_dir=work_dir,
                 allow_cpu=allow_cpu, meanwhile=meanwhile) as up:
        remote, children, ready, device = up["remote"], up["children"], up["ready"], up["device"]
        pool, sched, check_x, want, margin, weight_checks = up["meanwhile"]
        same_weights(ready, weight_checks)
        peaks = costs.peaks_for(device["kind"]) if device["platform"] == "tpu" else None

        verdict = check(remote, children, config, check_x, want, margin)
        log(f"check: {json.dumps(verdict)}")

        # ---- warm-up: every chunk bucket the mix can produce, then decode
        lengths = warm_lengths(mix, min(r["prefill_token_budget"] for r in ready))
        warm_pool = np.random.default_rng([int(seed), 4]).standard_normal((max(lengths) + 1, hidden), dtype=np.float32)
        for n in lengths:
            with remote.inference_session(max_length=n + 2) as session:
                session.step(warm_pool[None, :n])
                session.step(warm_pool[None, n : n + 1])
        log("warm-up done")

        replay = loadgen.Replay(remote, pool, traced=trace, hidden=hidden)
        t0 = time.perf_counter() + sched.ramp_s + 0.25

        def conduct() -> None:
            events = [(t0, "mark window")]
            if trace:
                events += [(t0 + TRACE_AT * seconds, "trace_start"), (t0 + TRACE_AT * seconds + min(TRACE_S, seconds / 3), "trace_stop")]
            events.append((t0 + seconds, "mark window_end"))
            for when, line in events:
                delay = when - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                tell_all(children, line)

        conductor = threading.Thread(target=conduct, daemon=True)
        conductor.start()
        replay.run(sched, t0, seconds, DRAIN_S)
        conductor.join(timeout=180)
        t_drained = time.perf_counter()
        log(f"window and drain over ({t_drained - t0 - seconds:.1f}s of drain)")
        dumps = stop_and_dump(children)

    record = Record(config=config, t_process=T_PROCESS, t0=t0, seconds=seconds, t_drained=t_drained,
                    sessions=replay.records, children=dumps, peaks=peaks)
    counted = record.counted()
    failed = [s for s in counted if s.error is not None or s.done is None]
    for s in failed[:5]:
        log(f"failed session {s.plan.index}: {s.error or 'not finished by the end of the drain'}")
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for name in metric_names(benchmark, section, workload):
        reader = load_reader("layer_metrics" if trace else "end_to_end", name)
        value = reader.read(record)
        if value is not None:
            metrics[name] = {"value": value, "unit": reader.UNIT}
    device_out = {**device, "count": device["count"] * n_servers,
                  "memory_peak_bytes": max(d["memory"].get("peak_bytes_in_use", 0) for d in dumps)}
    result = {"correct": verdict["ok"], "attempted": len(counted), "failed": len(failed), "metrics": metrics, "device": device_out}
    if trace:
        traces = [d.get("trace") or {} for d in dumps]
        if all(t.get("busy_s") for t in traces):
            device_out["busy_s"] = sum(t["busy_s"] for t in traces) / len(traces)
            device_out["window_s"] = sum(t["window_s"] for t in traces) / len(traces)
            ops: dict = {}
            for t in traces:
                for name, s in t["device_ops"]:
                    ops[name] = ops.get(name, 0.0) + s
            top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
            # idle gaps cannot be attributed yet: the program writes no TraceAnnotation (PERF.md section 7)
            result["breakdown"] = {"device_ops": [[n[:120], s] for n, s in top], "idle_gaps": []}

    def ttft_p50(lo: float, hi: float):
        waits = [s.first_reply - s.due for s in counted if s.first_reply is not None and lo <= s.due - t0 < hi]
        return float(np.median(waits)) * 1e3 if waits else None

    # not read by the driver: what perf/sweep.py and a reader of the logs want to see
    result["detail"] = {
        "check": {k: v for k, v in verdict.items() if k != "rows"},
        "together_decode_batch_mean": record.ratio_over_children("batched_tokens", "batched_steps", start="check", end="check_end"),
        "sessions_total": len(record.sessions),
        "drain_s": max((s.done or t_drained) for s in counted) - record.t_end if counted else 0.0,
        "ttft_p50_ms_first_half": ttft_p50(0, seconds / 2), "ttft_p50_ms_second_half": ttft_p50(seconds / 2, seconds),
        "cache_events": [d["cache_events"] for d in dumps],
        "recompiled": load_reader("layer_metrics", "recompiles_in_window").programs(record),
        # what an untraced run's line does not carry and a proof of a cell asks of every run (perf/prove.py, perf/gaps.py)
        "gen_late_ms_p95": load_reader("layer_metrics", "gen_late_ms_p95").read(record),
        "decode_batch_mean": load_reader("layer_metrics", "decode_batch_mean").read(record),
        "gaps": gaps.summary(record),
    }
    result["compared"] = correct.compared(verdict)  # after "detail", which main() takes out: the last key of the line
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if not (ROOT / "petals_tpu").is_dir():
        sys.stderr.write("perf/run.py: no petals_tpu/ beside perf/: there is no system to measure here\n")
        return 2
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    result = run_cell(benchmark, args.workload, args.seed, args.seconds, bool(args.trace))
    log(f"detail: {json.dumps(result.pop('detail'))}")  # for a reader of the logs; the line below has the contract's keys only
    for name, (number, limit) in result["compared"].items():
        print(f"compared {name} {number} limit {limit}", file=sys.stderr, flush=True)  # the last lines of standard error
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
