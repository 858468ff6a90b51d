#!/usr/bin/env bash
# Tier-1 verification — the command the ROADMAP pins and CI runs.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# Docs freshness: fail if README/docs reference a repro.* symbol that no
# longer exists, or link to a missing file. (Runs before the tier-1
# suite so it is reachable while known seed failures keep tier-1 red.)
python scripts/check_docs.py

# Forced-multi-device shards: the native sharded-serving tests need >= 8
# logical devices at jax init, and the project rule keeps the main
# pytest process at exactly 1 device — so they run as separate shards.
# Pure-TP shard (PR 2): sharded prepared planes on the model axis.
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -x -q -m multidevice tests/test_sharded_serving.py
# FSDP (data > 1) shard (ISSUE-3): data-axis-sharded prepared planes,
# pinning the cross-mesh qeinsum bit-identity on a pure data mesh.
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -x -q -m multidevice tests/test_qeinsum.py
# Replica-group serving shard (ISSUE-4): 8 devices carved into 2 disjoint
# (1, 4) sub-meshes, driver tokens == single-engine deterministic serve.
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -x -q -m multidevice tests/test_replica.py
# Packed-KV-cache shard (ISSUE-5): quantized-cache ServeEngine greedy
# tokens on an 8-device mesh == single device (flash-decode in the loop).
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -x -q -m multidevice tests/test_kvcache.py
# Chaos shard (ISSUE-6): replica killed mid-drain by injected faults on
# an 8-device fleet — zero requests dropped, requeued tokens bitwise
# identical to the fault-free single-engine run, PREP_STATS flat.
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -x -q -m multidevice tests/test_failover.py
# Continuous-batching shard (ISSUE-7): the ragged-traffic determinism
# harness on an 8-device mesh — slot-level admission over the paged KV
# pool, per-request tokens identical to the single-device engine.
# ISSUE-8 rides the same shard: speculative draft/verify rounds on the
# forced-8-device mesh, tokens bitwise equal to 1-device sequential —
# the shard-layout and speculation invariances compose.
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -x -q -m multidevice tests/test_continuous.py
# Streaming-calibration shard (ISSUE-9): fault-injected fleet hot swap
# on the 8-device replica set — versioned table pushed mid-traffic with
# zero drops, PREP_STATS flat, jit caches pinned, health undisturbed.
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -x -q -m multidevice tests/test_streaming_calib.py

# Decode-bench smoke (ISSUE-5): analytic HBM accounting + measured
# float-vs-packed decode wall time; refreshes BENCH_decode.json.
python -m benchmarks.run decode

# Failover-benchmark smoke (ISSUE-6): injected replica kill vs fault-free
# baseline at R=2,4 — recovery latency + throughput restore; refreshes
# BENCH_failover.json.
python -m benchmarks.run failover

# Speculative-decoding smoke (ISSUE-8): sequential vs draft/verify
# rounds on the same burst, asserting bitwise-equal tokens per row;
# the fast sweep keeps CI short — the full sweep (python -m
# benchmarks.run spec) refreshes the tracked BENCH_spec.json.
REPRO_SPEC_BENCH_FAST=1 python -m benchmarks.run spec

# Drift-benchmark smoke (ISSUE-9): synthetic mid-stream distribution
# shift — the streaming-refresh flush plan recovers to within 10% of
# the freshly-calibrated oracle, the static plan does not (the module
# asserts the acceptance itself); refreshes BENCH_drift.json.
python -m benchmarks.run drift

# Continuous-batching CLI smoke: slot-level serving end to end through
# the __main__ entry point (FP8_MGS_SERVE_PAGED preset, reduced tiles).
python -m repro.launch.serve --reduced --continuous \
    --batch 2 --n-requests 4 --prompt-len 8 --max-new 4

# Replica-driver example smoke: 2 replica engines on 2 forced host
# devices, shared prepared planes, tokens identical to single engine.
XLA_FLAGS="--xla_force_host_platform_device_count=2" \
    python examples/serve_lm.py --replicas 2

python -m pytest -x -q "$@"
