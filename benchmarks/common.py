"""Shared benchmark utilities: timing, CSV emission, a cached tiny trained
model used by the accuracy benchmarks (Table 1 / Fig. 9 proxies)."""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn: Callable, *args, n: int = 3, warmup: int = 1) -> float:
    """Median wall microseconds per call."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


class Csv:
    """Collects ``name,us_per_call,derived`` rows (the harness contract)."""

    def __init__(self):
        self.rows: List[str] = []

    def add(self, name: str, us: float, derived):
        self.rows.append(f"{name},{us:.1f},{derived}")

    def dump(self):
        for r in self.rows:
            print(r)


def trained_tiny_lm(steps: int = 150, seed: int = 0):
    """Train (once, cached in-process) a tiny LM on the synthetic task;
    returns (cfg, params, eval_batches). Used as the paper's 'pre-trained
    model' stand-in for post-training quantization experiments."""
    global _TINY
    try:
        return _TINY
    except NameError:
        pass
    from repro.configs import reduced_config
    from repro.data import DataConfig, SyntheticLM
    from repro.models import init_params
    from repro.train import OptConfig, init_train_state, make_train_step

    cfg = reduced_config("mgs-paper-eval")
    params, _ = init_params(cfg, jax.random.PRNGKey(seed))
    state = init_train_state(params)
    step = jax.jit(make_train_step(
        cfg, OptConfig(lr=3e-3, warmup_steps=5, total_steps=steps)))
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=64,
                                  global_batch=8, seed=seed))
    for i in range(steps):
        hb = data.make_batch(i)
        state, _ = step(state, {k: jnp.asarray(v) for k, v in hb.items()})
    evals = [data.make_batch(10_000 + i) for i in range(4)]
    _TINY = (cfg, state["params"], evals)
    return _TINY


def top1_accuracy(cfg, params, batches) -> float:
    """Next-token top-1 accuracy of the model on held-out batches."""
    from repro.models import forward
    hits = total = 0
    for hb in batches:
        batch = {k: jnp.asarray(v) for k, v in hb.items()}
        logits, _ = forward(params, cfg, batch)
        pred = jnp.argmax(logits, axis=-1)
        hits += int(jnp.sum(pred == batch["labels"]))
        total += int(np.prod(batch["labels"].shape))
    return hits / max(total, 1)
