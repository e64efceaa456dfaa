"""Prompt-tuning training benchmark against a running swarm
(counterpart of reference benchmarks/benchmark_training.py:50-107).

Usage:
  python benchmarks/benchmark_training.py MODEL_PATH --initial_peers ADDR \
      [--batch_size 2] [--seq_len 64] [--pre_seq_len 8] [--n_steps 5]
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
    parser.add_argument("--seq_len", type=int, default=64)
    parser.add_argument("--pre_seq_len", type=int, default=8)
    parser.add_argument("--n_steps", type=int, default=5)
    parser.add_argument("--tuning_mode", default="ptune", choices=["ptune", "deep_ptune"])
    parser.add_argument("--lr", type=float, default=0.01)
    args = parser.parse_args()

    from petals_tpu.client.model import AutoDistributedModelForCausalLM
    from petals_tpu.client.ptune import PTuneConfig
    from petals_tpu.client.training import compute_loss_and_grads, sgd_step

    model = AutoDistributedModelForCausalLM.from_pretrained(
        args.model,
        initial_peers=args.initial_peers,
        ptune=PTuneConfig(pre_seq_len=args.pre_seq_len, tuning_mode=args.tuning_mode),
    )
    try:
        rng = np.random.RandomState(0)
        ids = rng.randint(0, model.cfg.vocab_size, (args.batch_size, args.seq_len)).astype(np.int64)

        compute_loss_and_grads(model, ids, ids)  # warmup / compile
        start = time.perf_counter()
        for step in range(args.n_steps):
            loss, grads = compute_loss_and_grads(model, ids, ids)
            sgd_step(model, grads, args.lr)
        elapsed = time.perf_counter() - start
        tokens = args.n_steps * args.batch_size * args.seq_len
        print(
            f"training ({args.tuning_mode}): {tokens / elapsed:.1f} tok/s fwd+bwd, "
            f"final loss {loss:.4f}"
        )
    finally:
        model.close()


if __name__ == "__main__":
    from petals_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    main()
