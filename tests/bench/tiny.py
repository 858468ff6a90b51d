"""A tiny cell on the CPU for the benchmark's own tests.

It writes a benchmark root (``BENCHMARK.json``, a config file and a
traffic mix) at the reduced deepseek widths, and patches the harness's
chip check and model build so that the rest of a run (engine, window,
reference comparison) drives the program's emulation path on the CPU.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CONFIG = {
    "name": "tiny", "arch": "deepseek-7b", "family": "dense",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 4096,
    "hidden_act": "silu", "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "serving": {"quant": "FP8_MGS_SERVE_PAGED"},
    "correct": {"max_logit_rel_err": 0.2},
}
CHAT = {
    "kind": "open_loop", "arrivals": "stratified_exponential",
    "rate_per_s": 4.0, "schedule_seed": 0, "lead_s": 1.0,
    "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.6, "min": 4,
               "max": 30},
    "output": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 2,
               "max": 12},
    "end": "first_token", "drain_cap_s": 60,
    "engine": {"slots": 4, "max_len": 48, "buckets": [16, 32]},
}
RESIDENT = {
    "kind": "resident", "requests": 4, "schedule_seed": 0,
    "prompt": {"dist": "fixed", "len": 20},
    "output": {"dist": "fixed", "len": 24},
    "end": "stop",
    "engine": {"slots": 4, "max_len": 48, "buckets": [24]},
}
PEAK = {"int8_ops_per_s": 393e12, "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def write_root(path: str, limit: float = 0.2) -> str:
    os.makedirs(os.path.join(path, "bench", "configs"), exist_ok=True)
    os.makedirs(os.path.join(path, "bench", "traffic"), exist_ok=True)
    with open(os.path.join(path, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(dict(CONFIG, correct={"max_logit_rel_err": limit}), f)
    for name, mix in (("tiny-chat", CHAT), ("tiny-resident", RESIDENT)):
        with open(os.path.join(path, "bench", "traffic", name + ".json"),
                  "w") as f:
            json.dump(mix, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [{"name": "tiny-chat", "config": "tiny", "traffic": "tiny-chat",
              "chips": 1, "why": "test"},
             {"name": "tiny-resident", "config": "tiny",
              "traffic": "tiny-resident", "chips": 1, "why": "test"}]
    bench.update(
        configs=[{"name": "tiny", "source": "test",
                  "file": "bench/configs/tiny.json", "reduced": [],
                  "why": "test"}],
        workloads=cells,
        end_to_end=[dict(m, workloads=["tiny-chat", "tiny-resident"])
                    for m in bench["end_to_end"]],
        per_layer=[dict(m, workloads=["tiny-chat", "tiny-resident"])
                   for m in bench["per_layer"]])
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return path


def tiny_model_config(config: dict):
    from repro.configs import reduced_config
    from repro.quant.config import FP8_MGS_SERVE_PAGED
    return dataclasses.replace(
        reduced_config("deepseek-7b"), n_layers=config["num_hidden_layers"],
        vocab=config["vocab_size"], quant=FP8_MGS_SERVE_PAGED.replace(use_kernel=False))


def patch_cpu(monkeypatch):
    """Skip the harness's look for a chip; build the tiny CPU model."""
    import jax
    from bench import cell
    monkeypatch.setattr(cell, "require_chip",
                        lambda chips, peaks: jax.devices()[:chips])
    monkeypatch.setattr(cell, "load_peaks",
                        lambda root=None: {jax.devices()[0].device_kind: PEAK})
    monkeypatch.setattr(cell, "model_config", tiny_model_config)


def args(workload: str, seed: int, seconds: float = 2.0, trace: int = 0):
    return types.SimpleNamespace(workload=workload, seed=seed,
                                 seconds=seconds, trace=trace)
