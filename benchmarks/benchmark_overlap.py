"""Inter-span microbatch overlap benchmark (the swarm-level pipeline schedule).

Measures the wall-clock effect of running training microbatches CONCURRENTLY
through a chain of server spans (sequential_autograd's asyncio.gather
pipelining — each server works on a different microbatch at the same time,
the swarm analogue of parallel/pipeline.py's intra-jit pp schedule) versus
pushing the same microbatches through the chain one after another.

Self-contained: boots a 2-server loopback swarm in-process (tiny llama,
span [0, L/2) + span [L/2, L)), so it needs no running swarm. With S spans
and M equal microbatches, serial costs ~M*S*t while pipelined costs
~(M+S-1)*t — the ideal speedup at S=2, M=8 is 16/9 ~= 1.8x.

Usage: python benchmarks/benchmark_overlap.py [--cpu] [--microbatches 8]
"""

import argparse
import asyncio
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cpu", action="store_true", help="force the CPU backend")
    parser.add_argument("--microbatches", type=int, default=8)
    parser.add_argument("--rows_per_microbatch", type=int, default=4)
    parser.add_argument("--seq_len", type=int, default=256)
    parser.add_argument("--n_layers", type=int, default=8)
    parser.add_argument("--repeats", type=int, default=3)
    args = parser.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from petals_tpu.client.config import ClientConfig
    from petals_tpu.client.remote_sequential import RemoteSequential
    from petals_tpu.client.sequential_autograd import sequential_forward
    from petals_tpu.data_structures import make_uid
    from petals_tpu.dht import DHTNode
    from petals_tpu.server.server import Server
    from tests.utils import make_tiny_llama

    tmpdir = tempfile.mkdtemp(prefix="ptu_overlap_")
    path = make_tiny_llama(tmpdir, n_layers=args.n_layers)
    half = args.n_layers // 2

    loop = asyncio.new_event_loop()
    import threading

    threading.Thread(target=loop.run_forever, daemon=True).start()

    def run(coro, timeout=600):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(timeout)

    async def boot():
        bootstrap = await DHTNode.create(maintenance_period=1000)
        servers = []
        for first, num in ((0, half), (half, args.n_layers - half)):
            server = Server(
                path,
                initial_peers=[bootstrap.own_addr],
                first_block=first,
                num_blocks=num,
                compute_dtype=jnp.float32,
                use_flash=False,
            )
            await server.start()
            servers.append(server)
        return bootstrap, servers

    bootstrap, servers = run(boot())
    dht_prefix = servers[0].dht_prefix
    uids = [make_uid(dht_prefix, i) for i in range(args.n_layers)]
    chain = RemoteSequential(
        ClientConfig(initial_peers=[bootstrap.own_addr.to_string()]), uids
    )
    seq_manager = chain.sequence_manager

    rng = np.random.RandomState(0)
    micro = [
        rng.randn(args.rows_per_microbatch, args.seq_len, 64).astype(np.float32) * 0.1
        for _ in range(args.microbatches)
    ]

    async def serial():
        for part in micro:
            await sequential_forward(seq_manager, part)

    async def pipelined():
        await asyncio.gather(*(sequential_forward(seq_manager, part) for part in micro))

    run(pipelined())  # warmup: compile both span shapes on both servers
    run(serial())

    t_serial, t_pipe = [], []
    for _ in range(args.repeats):
        t0 = time.perf_counter()
        run(serial())
        t_serial.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        run(pipelined())
        t_pipe.append(time.perf_counter() - t0)

    ts, tp = statistics.median(t_serial), statistics.median(t_pipe)
    tokens = args.microbatches * args.rows_per_microbatch * args.seq_len
    print(
        f"spans=2 microbatches={args.microbatches} tokens={tokens}: "
        f"serial {ts*1e3:.0f} ms ({tokens/ts:.0f} tok/s) | "
        f"pipelined {tp*1e3:.0f} ms ({tokens/tp:.0f} tok/s) | "
        f"overlap speedup {ts/tp:.2f}x"
    )

    chain.close()

    async def teardown():
        for server in servers:
            await server.shutdown()
        await bootstrap.shutdown()

    run(teardown())
    loop.call_soon_threadsafe(loop.stop)


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    main()
