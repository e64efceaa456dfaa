"""Single-stream inference benchmark against a running swarm
(counterpart of reference benchmarks/benchmark_inference.py:44-68).

Usage:
  python benchmarks/benchmark_inference.py MODEL_PATH --initial_peers ADDR \
      [--seq_len 128] [--n_processes 1]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import multiprocessing as mp
import time

import numpy as np


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("model")
    parser.add_argument("--initial_peers", nargs="+", required=True)
    parser.add_argument("--seq_len", type=int, default=128)
    parser.add_argument("--warmup", type=int, default=3)
    parser.add_argument("--n_processes", type=int, default=1)
    args = parser.parse_args()

    if args.n_processes == 1:
        benchmark_inference(0, args)
        return
    processes = [
        mp.Process(target=benchmark_inference, args=(i, args)) for i in range(args.n_processes)
    ]
    for p in processes:
        p.start()
    for p in processes:
        p.join()


def benchmark_inference(proc_idx, args):
    from petals_tpu.client.model import AutoDistributedModelForCausalLM

    model = AutoDistributedModelForCausalLM.from_pretrained(
        args.model, initial_peers=args.initial_peers
    )
    try:
        rng = np.random.RandomState(proc_idx)
        prompt = rng.randint(0, model.cfg.vocab_size, (1, 4)).astype(np.int64)
        with model.remote.inference_session(
            max_length=prompt.shape[1] + args.warmup + args.seq_len + 2, batch_size=1
        ) as session:
            warm = model.generate(prompt, max_new_tokens=args.warmup, session=session)
            start = time.perf_counter()
            model.generate(warm, max_new_tokens=args.seq_len, session=session)
            elapsed = time.perf_counter() - start
        tok_s = args.seq_len / elapsed
        print(f"[proc {proc_idx}] inference: {tok_s:.2f} tok/s ({args.seq_len} tokens)")
    finally:
        model.close()


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    main()
