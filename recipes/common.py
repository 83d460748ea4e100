"""Shared recipe plumbing: argument parsing, data sources, train loop.

≙ the reference's runnable configs (BASELINE.json north-star workloads,
SURVEY.md §6): each recipe is `config dataclass + main()` over
TrainStep/hapi, runnable in one command with synthetic data by default
(offline image) or `--data file.txt|file.bin` for real tokens.
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass

import numpy as np


def std_parser(desc: str) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=desc)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--data", type=str, default=None,
                   help=".txt or .bin token file; default = synthetic")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=5)
    p.add_argument("--accumulate-steps", type=int, default=1)
    p.add_argument("--save", type=str, default=None,
                   help="checkpoint path to save at the end")
    return p


def token_source(args, vocab_size: int):
    from paddle_tpu.text import FileTokens, SyntheticTokens
    if args.data:
        src = FileTokens(args.data)
        if src.vocab_size > vocab_size:
            raise ValueError(
                f"data has ids up to {src.vocab_size}, model vocab is "
                f"{vocab_size}")
        return src
    need = args.batch_size * (args.seq_len + 1) * max(args.steps, 4)
    return SyntheticTokens(vocab_size, need, seed=args.seed)


def run_train(step_fn, loader, steps: int, log_every: int) -> float:
    """Drive `steps` train steps from an (endlessly cycled) loader;
    returns the final loss. Places the persistent compile cache first
    (`JAX_COMPILATION_CACHE_DIR` from outside wins), so a recipe run
    again does not compile its step again."""
    import itertools
    from paddle_tpu.device import enable_compile_cache
    enable_compile_cache()
    it = itertools.cycle(loader)
    loss = float("nan")
    t0 = time.perf_counter()
    for i in range(steps):
        batch = next(it)
        loss = float(step_fn(*batch))
        if log_every and (i % log_every == 0 or i == steps - 1):
            dt = time.perf_counter() - t0
            print(f"step {i:4d}  loss {loss:.4f}  "
                  f"({dt / (i + 1):.3f}s/step)", flush=True)
    return loss


@dataclass
class RecipeResult:
    final_loss: float
    steps: int
