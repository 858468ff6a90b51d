"""End-to-end serving example (the paper is an inference paper, so the
primary driver is serving): batched prefill+decode of an LM with MGS
FP8 quantized matmuls, compared against the unquantized model.

    PYTHONPATH=src python examples/serve_lm.py

With ``--replicas R`` (and at least R visible devices, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=2`` on CPU) the FP8
stream is additionally served through the replica-group driver
(``repro.launch.replica.ReplicaServeDriver``): R deterministic engines
on disjoint sub-meshes sharing one set of prepared weight planes, with
every request's greedy tokens identical to the single-engine run —
data-parallel throughput without giving up bit-identical logits (see
docs/replica_serving.md).

Serving with prepared weights
-----------------------------
Static weights are quantized + encoded exactly once per process:
``ServeEngine`` calls ``quant.prepare_params`` at construction, replacing
every proj-consumed weight with a ``PreparedWeight`` holding

* packed FP8 codes (uint8, 1 byte/elem) — streamed by the exact kernel,
* the cached dequant scale and observed limb statistics (which feed the
  Markov flush planner via ``QuantConfig.flush_target``).

No request ever re-quantizes a parameter; ``quant.PREP_STATS`` proves it
(printed below). On TPU the production config is
``quant.config.FP8_MGS_SERVE`` (exact kernel + in-kernel epilogue);
on CPU this example uses the jnp emulation path, which also consumes the
prepared planes.
"""

import argparse
import dataclasses

import numpy as np

from repro.configs import reduced_config
from repro.launch.mesh import make_mesh
from repro.launch.serve import Request, ServeEngine
from repro.quant import PREP_STATS, QuantConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--replicas", type=int, default=1,
                    help="also serve through the replica-group driver "
                         "with R replica engines (needs >= R devices)")
    args = ap.parse_args()

    cfg = reduced_config("deepseek-7b")
    mesh = make_mesh((1, 1), ("data", "model"))

    def make_requests():
        rng = np.random.default_rng(0)
        return [Request(rid=i,
                        prompt=rng.integers(1, cfg.vocab, 32).astype(
                            np.int32),
                        max_new_tokens=8)
                for i in range(8)]

    print("== bf16 serving ==")
    engine = ServeEngine(cfg, mesh, batch=4, max_len=48)
    stats = engine.run(make_requests())
    print(stats)

    print("\n== FP8 MGS-exact serving (same weights, prepared once) ==")
    cfg_q = dataclasses.replace(
        cfg, quant=QuantConfig(dtype="fp8_e4m3", accum="mgs_exact"))
    engine_q = ServeEngine(cfg_q, mesh, batch=4, max_len=48,
                           params=engine.params)
    print(f"prepared weights at engine init: {PREP_STATS}")
    reqs_q = make_requests()
    stats_q = engine_q.run(reqs_q)
    print(stats_q)
    print(f"after serving {len(reqs_q)} requests:      {PREP_STATS} "
          "(unchanged: no per-request re-quantization)")

    if args.replicas > 1:
        from repro.launch.replica import ReplicaServeDriver
        print(f"\n== FP8 MGS-exact replica-group serving "
              f"(R={args.replicas}) ==")
        n0 = PREP_STATS["prepared"]
        # same raw weights as the engines above: replica 0 prepares (or
        # cache-hits the planes engine_q already built), the other
        # replicas receive device_put transfers — never a per-replica
        # rebuild.
        with ReplicaServeDriver(cfg_q, args.replicas, batch=4, max_len=48,
                                params=engine.params,
                                dims=engine_q.dims) as driver:
            driver.warmup(prompt_len=32, max_new=8)
            reqs_r = make_requests()
            stats_r = driver.run(reqs_r)
            print({k: stats_r[k] for k in
                   ("replicas", "requests", "groups_per_replica",
                    "decode_tokens", "wall_s", "requests_per_s")})
            same = all(a.out_tokens == b.out_tokens
                       for a, b in zip(reqs_r, reqs_q))
        print(f"replica tokens identical to single engine: {same}")
        print(f"new plane builds for {args.replicas} replicas: "
              f"{PREP_STATS['prepared'] - n0} "
              "(at most one engine's worth — replicas share the planes)")
        if not same:
            raise SystemExit("replica tokens diverged from the single "
                             "engine — bit-identity regression")

    print("\nNote: wall-clock on CPU reflects the *emulation*; on TPU the "
          "limb kernel (quant.config.FP8_MGS_SERVE) streams packed FP8 "
          "codes, 1 byte per operand element, and fuses the "
          "scale/activation epilogue into the matmul.")


if __name__ == "__main__":
    main()
