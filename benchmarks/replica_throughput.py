"""Replica-group serving throughput: aggregate requests/sec vs R.

Serves one fixed request stream through ``repro.launch.replica.
ReplicaServeDriver`` at R = 1, 2, 4 over a forced-4-host-device set (the
device count must be fixed at jax init, so the sweep runs in one
subprocess) and reports per-request wall time plus aggregate
requests/sec per R. Every engine keeps the deterministic
(``shard_batch=False``) layout, so the rows quantify exactly the
throughput the replica driver recovers *without* giving up bit-identical
logits; warmup compilation is excluded from the timed window.

On this CPU container the R sub-meshes share physical cores, so scaling
understates real accelerator behaviour (disjoint chips per replica);
the row shape — rps growing with R at fixed numerics — is the point.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_DEVICES = 4
_N_REQUESTS = 12

_SCRIPT = """
import dataclasses, json
import jax, numpy as np
from repro.configs import reduced_config
from repro.launch.replica import ReplicaServeDriver
from repro.launch.serve import Request
from repro.models import init_params
from repro.quant import QuantConfig

cfg = dataclasses.replace(reduced_config("deepseek-7b"), quant=
    QuantConfig(dtype="fp8_e4m3", accum="mgs_exact"))
params, dims = init_params(cfg, jax.random.PRNGKey(0))

def make_requests():
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=rng.integers(1, cfg.vocab, 8).astype(
        np.int32), max_new_tokens=4) for i in range(%(n)d)]

rows = {}
for R in (1, 2, 4):
    with ReplicaServeDriver(cfg, R, batch=2, max_len=16,
                            params=params, dims=dims) as driver:
        driver.warmup(prompt_len=8, max_new=4)
        stats = driver.run(make_requests())
    rows[R] = {"wall_s": stats["wall_s"],
               "rps": stats["requests_per_s"],
               "decode_tok_per_s": stats["decode_tok_per_s"]}
print(json.dumps(rows))
""" % {"n": _N_REQUESTS}


def run(csv):
    # the child runs on virtual host devices: it must never ask for the
    # chip this process may already hold
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={_DEVICES}")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(f"replica suite child exited {out.returncode}: "
                           f"{out.stderr[-2000:]}")
    rows = json.loads(out.stdout.strip().splitlines()[-1])
    for R, r in sorted(rows.items(), key=lambda kv: int(kv[0])):
        csv.add(f"replica/requests_r{R}",
                r["wall_s"] * 1e6 / _N_REQUESTS,
                f"rps={r['rps']:.2f} decode_tok_per_s="
                f"{r['decode_tok_per_s']:.1f}")
