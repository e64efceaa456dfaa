"""Batched forward-pass benchmark against a running swarm
(counterpart of reference benchmarks/benchmark_forward.py).

Usage:
  python benchmarks/benchmark_forward.py MODEL_PATH --initial_peers ADDR \
      [--batch_size 2] [--seq_len 128] [--n_steps 10]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import time

import numpy as np


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("model")
    parser.add_argument("--initial_peers", nargs="+", required=True)
    parser.add_argument("--batch_size", type=int, default=2)
    parser.add_argument("--seq_len", type=int, default=128)
    parser.add_argument("--n_steps", type=int, default=10)
    args = parser.parse_args()

    from petals_tpu.client.model import AutoDistributedModelForCausalLM

    model = AutoDistributedModelForCausalLM.from_pretrained(
        args.model, initial_peers=args.initial_peers
    )
    try:
        rng = np.random.RandomState(0)
        ids = rng.randint(0, model.cfg.vocab_size, (args.batch_size, args.seq_len)).astype(np.int64)
        model.forward(ids)  # warmup / compile
        start = time.perf_counter()
        for _ in range(args.n_steps):
            model.forward(ids)
        elapsed = time.perf_counter() - start
        tokens = args.n_steps * args.batch_size * args.seq_len
        print(f"forward: {tokens / elapsed:.1f} tok/s "
              f"(batch {args.batch_size} x seq {args.seq_len} x {args.n_steps} steps)")
    finally:
        model.close()


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    main()
