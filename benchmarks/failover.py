"""Failover benchmark: recovery latency and throughput dip/restore vs R.

For each fleet size R the same request stream is served twice through
``repro.launch.replica.ReplicaServeDriver`` over a forced-4-host-device
set: once fault-free (baseline) and once with a persistent injected
fault that kills replica 0 mid-drain (retry budget exhausted ->
drain-and-requeue -> rebuild). Reported per R:

* ``recovery_s`` — detect-to-serving latency of the rebuild, from the
  driver's structured ``"rebuilt"`` event (supervisor drain + re-mesh +
  ``transfer_tree`` + health reset; never a re-quantization).
* ``rps_baseline`` / ``rps_fault`` and their ratio — the throughput dip
  the fault costs and how much the surviving replicas + the rebuilt
  replica restore.
* ``tokens_bitwise`` — the MGS determinism invariant: the faulted run's
  tokens are bitwise identical to the fault-free run's, every request.

Also emits ``BENCH_failover.json`` (repo root) with the full records.

On this CPU container the sub-meshes share physical cores, so the dip is
milder than on real disjoint-chip hardware; the row shape — bounded
recovery_s, restore ratio near 1, bitwise always true — is the point.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_DEVICES = 4
_N_REQUESTS = 12
_OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_failover.json")

_SCRIPT = """
import dataclasses, json
import jax, numpy as np
from repro.configs import reduced_config
from repro.launch.replica import ReplicaServeDriver
from repro.launch.serve import Request
from repro.models import init_params
from repro.quant import QuantConfig
from repro.runtime.fault_tolerance import FaultInjector, FaultSpec

cfg = dataclasses.replace(reduced_config("deepseek-7b"), quant=
    QuantConfig(dtype="fp8_e4m3", accum="mgs_exact"))
params, dims = init_params(cfg, jax.random.PRNGKey(0))

def make_requests():
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=rng.integers(1, cfg.vocab, 8).astype(
        np.int32), max_new_tokens=4) for i in range(%(n)d)]

def serve(R, injector=None):
    with ReplicaServeDriver(cfg, R, batch=2, max_len=16, params=params,
                            dims=dims, model_parallel=1, injector=injector,
                            max_retries=1, backoff_base_s=0.001) as driver:
        driver.warmup(prompt_len=8, max_new=4)
        reqs = make_requests()
        stats = driver.run(reqs)
        events = driver.events()
    return reqs, stats, events

rows = {}
for R in (2, 4):
    base_reqs, base, _ = serve(R)
    # replica 0 fails every execution of its first group incl. the retry,
    # exhausting max_retries=1 -> drain-and-requeue -> rebuild.
    inj = FaultInjector([FaultSpec(kind="raise", replica=0, group=0,
                                   count=2)])
    fault_reqs, fault, events = serve(R, injector=inj)
    recovery = [e["recovery_s"] for e in events if e["event"] == "rebuilt"]
    rows[R] = {
        "rps_baseline": base["requests_per_s"],
        "rps_fault": fault["requests_per_s"],
        "throughput_restore": fault["requests_per_s"]
                              / max(base["requests_per_s"], 1e-9),
        "recovery_s": recovery[0] if recovery else None,
        "retries": fault["retries"], "failovers": fault["failovers"],
        "requeued_requests": fault["requeued_requests"],
        "rebuilds": fault["rebuilds"],
        "tokens_bitwise": all(a.out_tokens == b.out_tokens
                              for a, b in zip(fault_reqs, base_reqs)),
        "complete": all(len(r.out_tokens) == 4 for r in fault_reqs),
    }
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
        raise RuntimeError(f"failover suite child exited {out.returncode}: "
                           f"{out.stderr[-2000:]}")
    rows = json.loads(out.stdout.strip().splitlines()[-1])
    record = {"devices": _DEVICES, "n_requests": _N_REQUESTS, "rows": rows}
    with open(_OUT, "w") as f:
        json.dump(record, f, indent=1)
    for R, r in sorted(rows.items(), key=lambda kv: int(kv[0])):
        ok = r["tokens_bitwise"] and r["complete"] and r["rebuilds"] == 1
        csv.add(f"failover/recovery_r{R}",
                (r["recovery_s"] or 0.0) * 1e6,
                f"restore={r['throughput_restore']:.2f} "
                f"requeued={r['requeued_requests']} "
                f"bitwise={'yes' if ok else 'NO'}")
    csv.add("failover/record_file", 0.0, os.path.abspath(_OUT))
