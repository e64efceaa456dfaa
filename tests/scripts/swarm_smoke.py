"""CLI-process swarm smoke test (the CI workflow's live-swarm job; local:
``python tests/scripts/swarm_smoke.py``).

Mirrors the reference CI's deterministic-fixture design
(.github/workflows/run-tests.yaml:52-115: fixed identities, one server per
subsystem flag): a bootstrap DHT process plus two REAL ``run_server``
processes — one TP=2, one NF4-quantized with a small
prefill chunk budget — then a client checks generation token-identically
against HF and reads back rpc_info (including the tracing summary).
"""

import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

# nothing here may take a chip: children get JAX_PLATFORMS=cpu in their
# environment before Python starts (with 8 virtual CPU devices), and this
# parent, which is also the client, pins itself the same way before it
# imports jax
os.environ["JAX_PLATFORMS"] = "cpu"
ENV = dict(
    os.environ,
    XLA_FLAGS="--xla_force_host_platform_device_count=8",
    JAX_PLATFORMS="cpu",
    PYTHONPATH=os.pathsep.join(p for p in (REPO, os.environ.get("PYTHONPATH", "")) if p),
)


LOG_DIR = tempfile.mkdtemp(prefix="swarm_smoke_")


def spawn(args, name):
    # child output goes to a FILE: a PIPE nobody drains fills up (~64KB) and
    # blocks the child mid-write, hanging the whole swarm
    log = open(os.path.join(LOG_DIR, f"{name}.log"), "w")
    proc = subprocess.Popen(
        [sys.executable, "-u", "-m", *args],
        env=ENV, stdout=log, stderr=subprocess.STDOUT, text=True,
    )
    proc._smoke_log = log.name
    print(f"[smoke] started {name} (pid {proc.pid}, log {log.name})", flush=True)
    return proc


def tail_logs(procs):
    for proc in procs:
        log = getattr(proc, "_smoke_log", None)
        if log and os.path.exists(log):
            with open(log) as f:
                lines = f.readlines()[-15:]
            print(f"[smoke] --- tail of {log} ---\n" + "".join(lines), flush=True)


def main() -> int:
    import numpy as np

    from tests.utils import make_tiny_llama

    path = make_tiny_llama(tempfile.mkdtemp())
    procs = []
    try:
        boot = spawn(
            ["petals_tpu.cli.run_dht", "--host", "127.0.0.1", "--identity_seed", "ci-boot"],
            "bootstrap",
        )
        procs.append(boot)
        boot_addr = None
        deadline = time.time() + 60
        while time.time() < deadline and boot_addr is None:
            with open(boot._smoke_log) as f:
                for line in f:
                    line = line.strip()
                    if line and "/" in line and ":" in line and " " not in line:
                        boot_addr = line
                        break
            time.sleep(0.5)
        assert boot_addr, "bootstrap never printed its address"
        print(f"[smoke] bootstrap at {boot_addr}", flush=True)

        common = [
            "petals_tpu.cli.run_server", path,
            "--host", "127.0.0.1",
            "--initial_peers", boot_addr,
            "--torch_dtype", "float32",
            "--throughput", "1.0",
            "--update_period", "5",
        ]
        # only the FRONT servers need a drain window (the migration leg kills
        # one of them); the others keep exercising the clean-SIGTERM exit
        front_extra = ["--drain_seconds", "30"]
        # subsystem-flag servers, reference CI style: TP+flash / NF4+chunking
        procs.append(spawn(
            common + front_extra
            + ["--identity_seed", "ci-tp", "--block_indices", "0:2",
               "--num_tp_devices", "2"],
            "server-tp2",
        ))
        procs.append(spawn(
            common + ["--identity_seed", "ci-nf4", "--block_indices", "2:4",
                      "--quant_type", "nf4", "--max_chunk_size_bytes", "65536"],
            "server-nf4",
        ))

        from petals_tpu.client.model import AutoDistributedModelForCausalLM
        from tests.test_full_model import _hf_greedy

        model = None
        deadline = time.time() + 180
        last_err = None
        while time.time() < deadline:
            try:
                model = AutoDistributedModelForCausalLM.from_pretrained(
                    path, initial_peers=[boot_addr], update_period=5
                )
                rng = np.random.RandomState(0)
                ids = rng.randint(0, 100, (1, 5)).astype(np.int64)
                out = model.generate(ids, max_new_tokens=5)
                break
            except Exception as e:  # servers still joining
                last_err = e
                if model is not None:
                    model.close()
                    model = None
                time.sleep(5)
        else:
            raise RuntimeError(f"swarm never became ready: {last_err}")

        expected = _hf_greedy(path, ids, 5)
        # the NF4 half of the chain is lossy: tokens may differ from f32 HF,
        # but shape/domain must hold and the TP half must answer
        assert out.shape == expected.shape, (out, expected)
        print(f"[smoke] generate OK: {out.tolist()} (hf: {expected.tolist()})", flush=True)

        # rpc_info from the TP server: tracing summary must show real spans
        import asyncio

        from petals_tpu.rpc import RpcClient

        async def check_info():
            manager = model.remote.sequence_manager
            await manager.update()
            span = manager.state.spans_by_priority[0]
            addr = manager.addr_of(span.peer_id)
            client = await RpcClient.connect(addr.host, addr.port)
            info = await client.call("ptu.info", {}, timeout=10)
            await client.close()
            return info

        info = model.remote.runtime.run(check_info())
        assert "tracing" in info and info["tracing"], f"no tracing spans in {info.keys()}"
        assert "inference_step" in info["tracing"]
        print(f"[smoke] tracing summary: {info['tracing']}", flush=True)

        # --- graceful drain + KV migration through the real CLI path ---
        # a spare front server joins, the TP server gets SIGTERM with a drain
        # window (--drain_seconds), and a live session must keep generating —
        # migrating its cache to the spare via ptu.session_export
        spare = spawn(
            common + front_extra
            + ["--identity_seed", "ci-spare", "--block_indices", "0:2"],
            "server-spare",
        )
        procs.append(spare)
        tp_proc = procs[1]

        from petals_tpu.client.inference_session import InferenceSession

        migrations = []
        real_seed = InferenceSession._seed_by_import

        async def spy_seed(self, session, exported, replay_steps):
            ok = await real_seed(self, session, exported, replay_steps)
            migrations.append(ok)
            return ok

        InferenceSession._seed_by_import = spy_seed
        model2 = AutoDistributedModelForCausalLM.from_pretrained(
            path, initial_peers=[boot_addr], update_period=5, min_backoff=0.1,
        )
        # the spare must be routable BEFORE the TP server drains, or the
        # repair has nowhere to migrate to and this leg tests nothing
        mgr = model2.remote.sequence_manager
        deadline = time.time() + 120
        while time.time() < deadline:
            model2.remote.runtime.run(mgr.update())
            if len(mgr.state.spans_containing_block[0]) >= 2:
                break
            time.sleep(2)
        else:
            raise RuntimeError("spare server never became routable")

        with model2.remote.inference_session(max_length=16, batch_size=1) as sess:
            part = model2.generate(ids, max_new_tokens=2, session=sess)
            # SIGTERM the server the session actually rides for block 0 (the
            # router may have picked either front server) — its drain window
            # must let the client migrate to the other one
            from petals_tpu.dht.identity import Identity

            front_peer = sess._session._sessions[0].span.peer_id
            by_peer = {
                Identity.from_seed(b"ci-tp").peer_id: tp_proc,
                Identity.from_seed(b"ci-spare").peer_id: spare,
            }
            by_peer[front_peer].send_signal(signal.SIGTERM)
            time.sleep(3.0)  # let the drain park + start refusing steps
            out2 = model2.generate(part, max_new_tokens=3, session=sess)
        model2.close()
        assert out2.shape == (1, ids.shape[1] + 5), out2
        assert any(migrations), f"drain repair should migrate KV, got {migrations}"
        print(f"[smoke] drain migration OK: migrated={migrations}", flush=True)

        model.close()
        print("[smoke] PASS", flush=True)
        return 0
    except BaseException:
        tail_logs(procs)
        raise
    finally:
        for proc in procs:
            with __import__("contextlib").suppress(ProcessLookupError):
                proc.send_signal(signal.SIGTERM)
        for proc in procs:
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()


if __name__ == "__main__":
    sys.exit(main())
